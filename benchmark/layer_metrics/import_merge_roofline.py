"""The imported digests' merge as a share of its memory roofline: the
least time the device could take over the bytes the cell's two files
state (``benchmark/kernels/import_merge.py``), over
``merge_device_ms`` (in a cell whose every digest is imported, all of
that time is the imports').  Memory bounds it.  A device kind without
a published peak raises."""
LAYER = "device apply, kernels"
UNIT = "%"
MOVES = "flush_lag_ms"


def read(run):
    from benchmark import harness
    ms = harness.load_module("layer_metrics", "merge_device_ms").read(run)
    if not ms:
        return None
    import jax
    c = harness.cell(run["cell"])
    floor = harness.load_module("kernels", "import_merge").floor_ms(
        c["config"], c["traffic"], jax.devices()[0].device_kind)
    return 100.0 * floor / ms
