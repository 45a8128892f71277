"""Device time of the collectives in the traced interval, A SUM OVER
THE CHIPS: every operation whose name the trace prints as all-gather,
all-reduce, collective-permute, reduce-scatter or all-to-all (their
-start and -done halves too) among all the device's operations of the
slice (``device_ops_all``, added up by name over every device plane).
The update step has none, so these are the merge program's and what
the flush readout's gathers over the row-sharded planes bring; the
trace's operations carry no module, so the two are not told apart.  A
trace without a collective (one chip) reads nothing."""
import re

LAYER = "mesh table and shard merge"
UNIT = "ms"
MOVES = "flush_lag_ms"
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|collective-permute|reduce-scatter|"
    r"all-to-all)")


def read(run):
    t = run.get("trace")
    if not t:
        return None
    times = [s for name, s in t.get("device_ops_all", ())
             if COLLECTIVE.match(name)]
    if not times:
        return None
    return 1e3 * sum(times)
