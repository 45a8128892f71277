"""Paced by the wall clock, lossless by backpressure.

Each interval carries ``rounds_per_interval`` rounds of the cyclic
stream, their datagrams evenly spaced from ``tick + start_s`` to
``tick + end_s``; ticks are the multiples of the interval on the wall
clock (the servers run ``synchronize_with_interval``).  The schedule
takes no notice of the server, with one exception that loopback UDP
forces: a datagram is held back while ``inflight`` datagrams are
ahead of the local reader's own packet counter (the chip's machine
caps a socket's buffer at 2 x 208 KiB, some ninety datagrams), so a
reader that stalls makes the sender late and loses nothing.
``late_max_s`` is the worst lateness of a datagram against its
schedule, ``blocked_s`` the time held back.  Load held back is load
withdrawn while the reader is held off, so the harness compares the
blocked share of the window with a limit of the configuration
(``sender_blocked_pct``): past it the run is not correct.
"""

import math
import time


def run(io) -> None:
    start = float(io.spec["start_s"])
    span = float(io.spec["end_s"]) - start
    per = int(io.spec["rounds_per_interval"])
    inflight = int(io.spec["inflight"])
    r = 0
    while True:
        tick = math.floor(time.time() / io.iv + 1) * io.iv
        n = sum(io.round_len[(r + j) % len(io.round_len)]
                for j in range(per))
        gap = span / n
        for i in range(n):
            due = tick + start + i * gap
            while True:
                now = time.time()
                io.note(now)
                if io.stopped():
                    return
                if now >= due:
                    break
                time.sleep(min(due - now, 0.001))
            while io.ahead() >= inflight:
                t = time.monotonic()
                time.sleep(0.0002)
                io.blocked_s += time.monotonic() - t
                if io.stopped():
                    return
            io.late_max_s = max(io.late_max_s, time.time() - due)
            io.send_next()
        r += per
