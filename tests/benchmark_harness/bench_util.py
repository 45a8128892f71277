"""Shared by the benchmark's tests: the repo's root on ``sys.path``
and the tiny scale at which a cell's body runs on the CPU."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the chip's machine refuses io_uring, so the rehearsal runs the tier
# the chip runs; a 2 s interval, since a compile on the CPU backend
# (a new shape bucket can come in any interval) takes most of 1 s
_SERVERS = {"interval": "2s", "tpu_histo_rows": 64, "tpu_set_rows": 16,
            "tpu_ingest_backend": "recvmmsg"}
TINY = {
    "local-wide-paced": {
        "start_s": 0.1, "end_s": 1.7,
        # some thirty datagrams a round: the sender is held back once
        # four wait unread
        "inflight": 4,
        "round": {"timers": 20, "samples_per_timer": 50,
                  "counters": 300, "global_counters": 10,
                  "gauges": 300, "sets": 5, "set_members": 400},
        "servers": {**_SERVERS, "tpu_counter_rows": 1024,
                    "tpu_gauge_rows": 1024}},
}
