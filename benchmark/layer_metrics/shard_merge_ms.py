"""The union of the chips' partial state at the tick, on the host's
clock: stage snapshot.shard_merge of the global's flush ring (the
sketches' host planes shipped, the collective merge program
dispatched, and its fence), mean a cycle of the window.  A program
whose swap has no such stage, or a run without a global's ring, reads
nothing."""
LAYER = "mesh table and shard merge"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("snapshot.shard_merge",)


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding=STAGES[0])
