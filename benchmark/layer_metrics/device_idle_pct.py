"""Share of the traced slice in which no operation ran on the device:
1 - union of the device's operation intervals over the slice."""
LAYER = "device"
UNIT = "%"
MOVES = "flush_lag_ms"


def read(run):
    t = run.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
