"""Worst lateness of a datagram against the open loop's schedule."""
LAYER = "load generator"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    return 1e3 * run["late_max_s"]
