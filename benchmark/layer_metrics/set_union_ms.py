"""The global decoding an interval's sketches and unioning their
register planes on the host, under its ingest lock: stage
import.apply.sets of its flush ring (all of the interval's wires
added up), mean over the window's cycles that hold it.  A program
without that stage reads nothing."""
LAYER = "import decode and fold"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("import.apply.sets",)


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding="import.apply.sets")
