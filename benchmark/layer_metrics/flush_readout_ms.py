"""The local's flush cycle from the swap to the readback: stages snapshot + swap_apply + dispatch + device_wait of its flush ring, mean a cycle of the window."""
LAYER = "swap and flush readout"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    cycles = [r for r in run["rings"]["local"]
              if r["start_unix"] <= run["t_end"]]
    vals = [sum(r["stages"].get(k, 0) for k in ("snapshot", "swap_apply", "dispatch", "device_wait")) for r in cycles]
    if not vals:
        return None
    return sum(vals) / len(vals) / 1e6
