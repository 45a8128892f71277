"""The global building its frame of metrics and handing it to the
benchmark's sink: stages host_emit + sink.bench of its flush ring,
mean a cycle of the window.  (``host_emit_ms`` is the same of a
local.)  A run without a global's ring reads nothing."""
LAYER = "emit and sink fan-out"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("host_emit", "sink.bench")


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES)
