"""Device time of the mesh table's merge program in the traced
interval, A SUM OVER THE CHIPS: every execution of the module
``jit_shard_merge`` on every device plane of the slice, as
``benchmark/trace.py`` adds a module's executions up (``modules``).
A module's time on a chip runs from its first operation to its last,
so a chip's wait for another chip's half of a collective is in it.
(The trace's list of operations is keyed by name alone and the
programs share names such as ``%fusion.3``, so the program's time is
read from its module and not from its operations.)  NOT in it: what
``ShardedAggregator.merge`` runs before that program for the
forwarded sketches, the transfer of the shards' host register planes
(32 MB at the default tables) and the ``jit(jnp.maximum)`` program
that takes them into the device planes (its module's name,
``jit_maximum``, is no one program's), so
``shard_merge_roofline`` divides by a time that leaves that part of
the register union out; the span ``snapshot.shard_merge``
(``shard_merge_ms``) holds all of it.  A trace without the module
reads nothing."""
LAYER = "mesh table and shard merge"
UNIT = "ms"
MOVES = "flush_lag_ms"
MODULE = "jit_shard_merge"


def read(run):
    t = run.get("trace")
    if not t:
        return None
    m = t.get("modules", {}).get(MODULE)
    if not m or not m["total_s"]:
        return None
    return 1e3 * m["total_s"]
