"""All of an interval's import handlers on the global, summed: stages
import.decode + import.lock_wait + import.apply + import.device_step
of its flush ring (a cycle holds the imports made since its previous
one, every wire's durations added up), mean over the window's cycles
that hold imports.  Its parts are ``import_fold_wait_ms``,
``import_fold_apply_ms`` and ``import_fold_step_ms``; the rest is the
decode.  A program without those stages reads nothing."""
LAYER = "import decode and fold"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("import.decode", "import.lock_wait", "import.apply",
          "import.device_step")


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding="import.apply")
