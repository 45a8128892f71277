"""Collector pauses: one process-wide hook on ``gc.callbacks``.

A collection stops every thread of the interpreter (it runs under
the interpreter lock on whichever thread tripped the threshold), so
its cost to a flush stage is every pause that overlapped the stage,
whoever allocated.  ``PAUSES`` adds each collection's start-to-stop
nanoseconds to ``pause_ns`` and counts collections by generation;
``observe.tracer._traced`` reads the counter on a block's entry and
exit, and self-telemetry reports the total.

The hook counts; it changes nothing about when the collector runs.
``Server.start`` installs it and the last ``Server.shutdown``
removes it: once a process, however many servers.
"""

from __future__ import annotations

import gc
import threading
import time


class GcPauses:
    def __init__(self):
        self.pause_ns = 0
        self.collections = [0, 0, 0]    # by generation
        self._t0 = 0
        self._users = 0
        self._lock = threading.Lock()

    def _on_gc(self, phase: str, info: dict) -> None:
        # collections do not nest and run under the interpreter lock:
        # one start, then its stop
        if phase == "start":
            self._t0 = time.monotonic_ns()
        elif self._t0:
            self.pause_ns += time.monotonic_ns() - self._t0
            self._t0 = 0
            self.collections[info["generation"]] += 1

    @property
    def installed(self) -> bool:
        return self._users > 0

    def install(self) -> None:
        with self._lock:
            self._users += 1
            if self._users == 1:
                gc.callbacks.append(self._on_gc)

    def remove(self) -> None:
        with self._lock:
            if not self._users:
                return
            self._users -= 1
            if not self._users:
                gc.callbacks.remove(self._on_gc)
                self._t0 = 0


PAUSES = GcPauses()
