"""The local's gRPC forward to the global: stage forward of its flush ring, mean a cycle."""
LAYER = "forward"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    cycles = [r for r in run["rings"]["local"]
              if r["start_unix"] <= run["t_end"]]
    vals = [r["stages"].get("forward", 0) for r in cycles]
    if not vals:
        return None
    return sum(vals) / len(vals) / 1e6
