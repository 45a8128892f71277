"""The import handlers' wait for the global's ingest lock, all of an
interval's wires added up: stage import.lock_wait of its flush ring,
mean over the window's cycles that hold imports.  A burst's handlers
queue on the one lock, so their waits add up to more than the burst
lasts."""
LAYER = "import decode and fold"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("import.lock_wait",)


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding="import.apply")
