"""A forward as the fleet feels it: the longest of an interval's
calls on the clients' own clocks, call to acknowledgement (the wait
for a handler's worker, for the ingest lock and the fold are all in
it), mean over the intervals the window's ticks closed.  A topology
without import clients reads nothing."""
LAYER = "load generator"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    worst = run["lags"].get("clients")
    if not worst:
        return None
    return 1e3 * sum(worst) / len(worst)
