"""The mesh table's merge as a share of its memory roofline: the least
time the four chips together could take over the bytes the cell's two
files state (``benchmark/kernels/shard_merge.py``), over
``shard_merge_device_ms``, which is a sum over the chips too.  Memory
bounds it.  A device kind without a published peak raises."""
LAYER = "mesh table and shard merge"
UNIT = "%"
MOVES = "flush_lag_ms"


def read(run):
    from benchmark import harness
    ms = harness.load_module("layer_metrics",
                             "shard_merge_device_ms").read(run)
    if not ms:
        return None
    import jax
    c = harness.cell(run["cell"])
    floor = harness.load_module("kernels", "shard_merge").floor_ms(
        c["config"], c["traffic"], jax.devices()[0].device_kind)
    return 100.0 * floor / ms
