"""The mesh table's last update steps at the tick: stage
snapshot.final_step of the global's flush ring (what was still staged
when the interval closed, through SPMD update calls on every chip,
under the ingest lock), mean a cycle of the window.  A program whose
swap has no such stage, or a run without a global's ring, reads
nothing."""
LAYER = "mesh table and shard merge"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("snapshot.final_step",)


def read(run):
    from benchmark import global_ring
    return global_ring.stage_ms(run, STAGES, holding=STAGES[0])
