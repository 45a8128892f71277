"""The local building its frame of metrics and handing it to the
benchmark's sink: stages host_emit + sink.bench of its flush ring,
mean a cycle.  Not sink_flush: on the local that stage also waits for
the forward."""
LAYER = "emit and sink fan-out"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    cycles = [r for r in run["rings"]["local"]
              if r["start_unix"] <= run["t_end"]]
    vals = [sum(r["stages"].get(k, 0) for k in ("host_emit", "sink.bench")) for r in cycles]
    if not vals:
        return None
    return sum(vals) / len(vals) / 1e6
