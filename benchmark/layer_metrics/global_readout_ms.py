"""The global's flush cycle from the swap to the readback: stages
snapshot + swap_apply + dispatch + device_wait of its flush ring, mean
a cycle of the window.  (``flush_readout_ms`` is the same of a
local.)  A run without a global's ring reads nothing."""
LAYER = "swap and flush readout"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("snapshot", "swap_apply", "dispatch", "device_wait")


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES)
