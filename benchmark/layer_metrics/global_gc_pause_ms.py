"""The interpreter's collector pauses that fell under the global's
tick path: keys ``gc.<stage>`` of its flush ring for the stages in a
row on the flush thread up to delivery, mean a cycle of the window
that counts pauses (it holds the key ``gc``, 0 included).
(``gc_pause_ms`` is the same of a local.)  A program that does not
count reads nothing."""
LAYER = "swap and flush readout"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("gc.snapshot", "gc.swap_apply", "gc.dispatch",
          "gc.device_wait", "gc.host_emit", "gc.sink_flush")


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding="gc")
