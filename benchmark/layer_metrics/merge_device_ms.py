"""Device time of the t-digest merge in the traced interval: every
operation named ``tdigest_merge_c<cap>_k<k>`` (the Pallas kernel, one
name a bucket of staged samples a row) among all the device's
operations of the slice (``device_ops_all``: not only the ten that the
result line's breakdown ranks).  A trace without such an operation
reads nothing."""
import re

LAYER = "device apply, kernels"
UNIT = "ms"
MOVES = "flush_lag_ms"
KERNEL = re.compile(r"tdigest_merge_c\d+_k\d+")


def read(run):
    t = run.get("trace")
    if not t:
        return None
    times = [s for name, s in t.get("device_ops_all", ())
             if KERNEL.search(name)]
    if not times:
        return None
    return 1e3 * sum(times)
