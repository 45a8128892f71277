"""One call a client an interval, at the client's own offset from the
tick.

Ticks are the multiples of the interval on the wall clock (the global
runs ``synchronize_with_interval``, and so would the locals whose
forwarders the clients are).  In the interval that tick number *k*
opens, the client sends round *k* mod ``rounds``, the same in every
client, at ``tick + offset``; its offset is its place in the traffic
file's span (``start_s`` to ``end_s``, in an order the seed permutes).
The schedule takes no notice of the server, with the one exception a
forwarder has too: a client has at most one call in flight, so a call
that outlasts the interval makes the next one start late.  The
harness holds the share of late starts to a limit of the
configuration (``calls_late_pct``).
"""

import math
import time


def run(io) -> None:
    while True:
        k = math.floor(time.time() / io.iv) + 1
        due = k * io.iv + io.offset
        while True:
            now = time.time()
            if io.stopped():
                return
            if now >= due:
                break
            time.sleep(min(due - now, 0.02))
        io.call(k % len(io.bodies), due)
