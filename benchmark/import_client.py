#!/usr/bin/env python3
"""One import client: a local's forwarder as a process of its own that
never imports JAX, numpy or the program.

    python3 benchmark/import_client.py --mode <mode> --port <p>
        --interval <s> --offset <s> --deadline <s> --bodies <file>...

It reads its bodies (serialized ``MetricList``s, one a round, made by
the harness), dials ``127.0.0.1:<port>``, prints ``{"ready": true}``
once the channel is up, and sends ``forwardrpc.Forward/SendMetrics``
calls as its mode says until a ``quit`` line arrives on standard input
(or the input closes).  Its last line of output is its report: every
call as ``[due, start, end, ok, round]`` on the wall clock.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import threading
import time

import grpc

HERE = os.path.dirname(os.path.abspath(__file__))

METHOD = "/forwardrpc.Forward/SendMetrics"


class Io:
    """What a mode drives: the bodies, the call and the clock."""

    def __init__(self, bodies: list[bytes], port: int, interval_s: float,
                 offset_s: float, deadline_s: float):
        self.bodies, self.iv = bodies, float(interval_s)
        self.offset, self.deadline = float(offset_s), float(deadline_s)
        self.channel = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_send_message_length", -1)])
        self._call = self.channel.unary_unary(
            METHOD, request_serializer=lambda b: b,
            response_deserializer=lambda b: b)
        self.calls: list[list] = []
        self.quit = False

    def stopped(self) -> bool:
        return self.quit

    def call(self, r: int, due: float) -> None:
        """Round ``r``'s body as one unary call, waited for."""
        start = time.time()
        try:
            self._call(self.bodies[r], timeout=self.deadline)
            ok = True
        except grpc.RpcError:
            ok = False
        self.calls.append([due, start, time.time(), ok, r])


def _listen(io: Io) -> None:
    for line in sys.stdin:
        if line.strip() == "quit":
            break
    io.quit = True      # told to, or the harness went away


def load_mode(name: str):
    """``modes/<name>.py``, as ``harness.load_module`` finds it; read
    here so that a client imports neither numpy nor the harness."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_mode", os.path.join(HERE, "modes", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mode", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--interval", type=float, required=True)
    ap.add_argument("--offset", type=float, required=True)
    ap.add_argument("--deadline", type=float, required=True)
    ap.add_argument("--bodies", nargs="+", required=True)
    args = ap.parse_args(argv)
    bodies = []
    for path in args.bodies:
        with open(path, "rb") as f:
            bodies.append(f.read())
    mode = load_mode(args.mode)
    io = Io(bodies, args.port, args.interval, args.offset,
            args.deadline)
    grpc.channel_ready_future(io.channel).result(timeout=60)
    threading.Thread(target=_listen, args=(io,), daemon=True).start()
    print(json.dumps({"ready": True, "bodies": len(bodies)}),
          flush=True)
    try:
        mode.run(io)
    finally:
        io.channel.close()
    print(json.dumps({"calls": io.calls}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
