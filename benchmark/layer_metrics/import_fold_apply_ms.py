"""The import handlers' work under the global's ingest lock, all of
an interval's wires added up: stage import.apply of its flush ring
(rows resolved, digests staged, sketches unioned: ``set_union_ms`` is
its last part), mean over the window's cycles that hold imports."""
LAYER = "import decode and fold"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("import.apply",)


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding="import.apply")
