"""The tiny scale of the cells that came after ``bench_util.py``.

``bench_util.TINY`` maps a cell to the scale at which its body runs
on the CPU, and the tests that are parametrised over the cells of
``BENCHMARK.json`` (``test_bench_yardstick.py``'s control among them)
look every cell up there.  A later cell registers its scale here,
before any test module of this directory is collected, whichever of
them a run selects.
"""

import bench_util

# local-mixed-paced at the cell's own shape: 16 rounds an interval of
# 6 samples a timer (96 an interval stay singletons at compression
# 100), counters, global-only counters, gauges and sets side by side;
# 32 rounds, so two distinct intervals alternate.  Some fifty
# datagrams an interval: the sender is held back once four wait
# unread.
bench_util.TINY.setdefault("local-mixed-paced", {
    "start_s": 0.1, "end_s": 1.7, "inflight": 4,
    "round": {"timers": 20, "samples_per_timer": 6, "counters": 30,
              "global_counters": 5, "gauges": 30, "sets": 5,
              "set_members": 40},
    "servers": {"interval": "2s", "tpu_histo_rows": 64,
                "tpu_set_rows": 16, "tpu_counter_rows": 1024,
                "tpu_gauge_rows": 1024,
                "tpu_ingest_backend": "recvmmsg"}})
