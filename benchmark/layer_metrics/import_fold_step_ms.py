"""The device steps the import handlers ran after their folds,
outside the ingest lock, all of an interval's added up: stage
import.device_step of its flush ring (the staged digests ranked,
shipped and their merge dispatched), mean over the window's cycles
that hold imports."""
LAYER = "import decode and fold"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("import.device_step",)


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding="import.apply")
