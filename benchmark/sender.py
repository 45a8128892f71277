#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX or
the program.

    python3 benchmark/sender.py --traffic <file> --seed <n> --port <p>
        --interval <s> [--scale <json>]

It makes the cell's rounds from the seed (the harness makes the same
bytes for the reference), prints ``{"ready": ...}``, and sends the
cyclic stream of rounds to ``127.0.0.1:<port>`` as the traffic file's
mode says until a ``quit`` line arrives on standard input (or the
input closes).  Its last line of output is its report: how
many datagrams it sent, and a series of (time, sent, blocked seconds,
worst lateness) from which the harness reads the window's share.
"""

from __future__ import annotations

import argparse
import json
import mmap
import os
import socket
import struct
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from benchmark import harness, traffic  # noqa: E402


class Io:
    """What a mode drives: the socket, the stream and the clock."""

    def __init__(self, spec: dict, rounds: list[list[bytes]], port: int,
                 interval_s: float, feedback: str):
        self.spec, self.iv = spec, float(interval_s)
        self.stream = [dg for r in rounds for dg in r]
        self.round_len = [len(r) for r in rounds]
        self.port = port
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        self.dest = ("127.0.0.1", port)
        self.n_sent = 0
        self.blocked_s = 0.0
        self.late_max_s = 0.0       # since the last series entry
        self.quit = False
        self.series: list[tuple] = []
        self._last_entry = 0.0
        with open(feedback, "rb") as f:
            self._fb = mmap.mmap(f.fileno(), 8, access=mmap.ACCESS_READ)

    def send_next(self) -> None:
        self.sock.sendto(self.stream[self.n_sent % len(self.stream)],
                         self.dest)
        self.n_sent += 1

    def stopped(self) -> bool:
        return self.quit

    def note(self, now: float, every: float = 0.02) -> None:
        if now - self._last_entry >= every:
            self._last_entry = now
            self.series.append((now, self.n_sent, self.blocked_s,
                                self.late_max_s))
            self.late_max_s = 0.0

    def ahead(self) -> int:
        """Datagrams sent that the local's reader has not counted yet:
        the harness publishes the reader's own packet counter
        (``received_dogstatsd-udp``) through a shared 8-byte file.
        The kernel's view (``rx_queue`` of ``/proc/net/udp``) reads 0
        on the chip's machine, whatever waits."""
        return self.n_sent - struct.unpack_from("<q", self._fb, 0)[0]


def _listen(io: Io) -> None:
    for line in sys.stdin:
        if line.strip() == "quit":
            break
    io.quit = True      # told to, or the harness went away


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--interval", type=float, required=True)
    ap.add_argument("--feedback", required=True,
                    help="8-byte file holding the reader's counter")
    ap.add_argument("--scale", default="{}")
    args = ap.parse_args(argv)
    with open(args.traffic, encoding="utf-8") as f:
        spec = traffic.scaled(json.load(f), json.loads(args.scale))
    mode = harness.load_module("modes", spec["mode"])
    rounds = traffic.make_rounds(spec, args.seed)
    io = Io(spec, rounds, args.port, args.interval, args.feedback)
    threading.Thread(target=_listen, args=(io,), daemon=True).start()
    print(json.dumps({"ready": True, "datagrams": len(io.stream)}),
          flush=True)
    try:
        mode.run(io)
    finally:
        io.sock.close()
    io.note(time.time(), every=0.0)
    print(json.dumps({"sent": io.n_sent, "series": io.series}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
