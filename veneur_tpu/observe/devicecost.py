"""Device-cost accounting for hot-path jitted callables.

``instrument(name, fn)`` wraps a ``jax.jit`` result; every call is
timed and checked for a cache miss (a compile).  On a compile the
wall time of that call is attributed to compilation — on a stable
workload shape the flush jits must compile once per shape bucket and
never again, so a moving compile counter in steady state is a bug
(shape drift, cache eviction, or a donated-buffer retrace), not noise.

Wall times here are DISPATCH times: jax dispatch is async, so a
non-compiling call returns as soon as the work is enqueued.  The
device-side cost lives in the ``cost_analysis()`` flops/bytes
estimates captured at compile time; the synchronous end-to-end cost
of pulling results to host is what ``add_readback`` accounts
(flusher readbacks report their ``device_get`` byte volume here).

``cost_analysis`` runs ``fn.lower(...).compile()`` a second time on
compile events only; where compiles are expensive it can be disabled
with ``VENEUR_TPU_COST_ANALYSIS=0``.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

_COST_ANALYSIS = os.environ.get(
    "VENEUR_TPU_COST_ANALYSIS", "1").lower() not in ("0", "false",
                                                     "off")


class _Entry:
    """Counters for one instrumented callable (guarded by the
    registry lock)."""

    __slots__ = ("calls", "compiles", "compile_ns", "call_ns",
                 "flops", "bytes_accessed", "h2d_bytes")

    def __init__(self):
        self.calls = 0
        self.compiles = 0
        self.compile_ns = 0
        self.call_ns = 0
        # latest compiled variant's per-execution estimates (the
        # newest shape bucket is the one the current interval runs)
        self.flops = 0.0
        self.bytes_accessed = 0.0
        # host->device transfer volume: bytes of HOST (numpy)
        # operands handed to the jit, which device_puts them at
        # dispatch.  Already-device-resident args cost nothing and
        # count nothing, so call sites pass staging arrays raw.
        self.h2d_bytes = 0

    def snapshot(self) -> dict:
        return {"calls": self.calls, "compiles": self.compiles,
                "compile_duration_ns": self.compile_ns,
                "dispatch_duration_ns": self.call_ns,
                "est_flops_per_call": self.flops,
                "est_bytes_accessed_per_call": self.bytes_accessed,
                "h2d_bytes": self.h2d_bytes}


class InstrumentedJit:
    """Callable wrapper around one jitted function; transparently
    forwards everything else (``lower``, ``_cache_size``, ...) to the
    wrapped jit."""

    def __init__(self, name: str, fn, registry: "DeviceCostRegistry"):
        self.name = name
        self.__wrapped__ = fn
        self._registry = registry
        self._seen = set()  # fallback signature cache (no _cache_size)

    def __getattr__(self, attr):
        return getattr(self.__wrapped__, attr)

    def _cache_len(self) -> int | None:
        size = getattr(self.__wrapped__, "_cache_size", None)
        if size is None:
            return None
        try:
            return size()
        except Exception:
            return None

    def _sig(self, args, kwargs):
        def one(a):
            shape = getattr(a, "shape", None)
            if shape is None:
                return repr(a)
            return (shape, str(getattr(a, "dtype", "")))
        return (tuple(one(a) for a in args),
                tuple(sorted((k, one(v)) for k, v in kwargs.items())))

    def __call__(self, *args, **kwargs):
        before = self._cache_len()
        t0 = time.monotonic_ns()
        out = self.__wrapped__(*args, **kwargs)
        dt = time.monotonic_ns() - t0
        if before is not None:
            compiled = (self._cache_len() or 0) > before
        else:
            sig = self._sig(args, kwargs)
            compiled = sig not in self._seen
            self._seen.add(sig)
        cost = None
        if compiled and _COST_ANALYSIS:
            cost = self._cost(args, kwargs)
        h2d = sum(a.nbytes for a in args
                  if isinstance(a, np.ndarray))
        self._registry._record(self.name, dt, compiled, cost, h2d)
        return out

    def _cost(self, args, kwargs) -> dict | None:
        """XLA's own flops / bytes-accessed estimate for the variant
        just compiled.  ``lower().compile()`` pays a second compile,
        which is why this runs on compile events only."""
        try:
            analysis = (self.__wrapped__.lower(*args, **kwargs)
                        .compile().cost_analysis())
        except Exception:
            return None
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if not isinstance(analysis, dict):
            return None
        return {"flops": float(analysis.get("flops", 0.0)),
                "bytes_accessed": float(
                    analysis.get("bytes accessed", 0.0))}


class _ReaderEntry:
    """Per-reader-thread ingest counters (multi-reader fused path):
    how much each SO_REUSEPORT reader actually carried, and whether
    it ran the fused shard or the split fallback."""

    __slots__ = ("batches", "packets", "samples", "ingest_ns",
                 "fused_batches")

    def __init__(self):
        self.batches = 0
        self.packets = 0
        self.samples = 0
        self.ingest_ns = 0
        self.fused_batches = 0

    def snapshot(self) -> dict:
        return {"batches": self.batches, "packets": self.packets,
                "samples": self.samples,
                "ingest_duration_ns": self.ingest_ns,
                "fused_batches": self.fused_batches}


class DeviceCostRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[str, _Entry] = {}
        self._readers: dict[str, _ReaderEntry] = {}
        self._readback_bytes = 0
        # persistent compilation cache traffic (fed by the
        # jax.monitoring listener utils/compile_cache installs): a hit
        # is a compile that loaded from disk instead of running XLA
        self._cache_hits = 0
        self._cache_misses = 0

    def instrument(self, name: str, fn) -> InstrumentedJit:
        with self._lock:
            self._entries.setdefault(name, _Entry())
        return InstrumentedJit(name, fn, self)

    def _record(self, name: str, dt_ns: int, compiled: bool,
                cost: dict | None, h2d_bytes: int = 0) -> None:
        with self._lock:
            e = self._entries.setdefault(name, _Entry())
            e.calls += 1
            e.call_ns += dt_ns
            e.h2d_bytes += int(h2d_bytes)
            if compiled:
                e.compiles += 1
                e.compile_ns += dt_ns
            if cost is not None:
                e.flops = cost["flops"]
                e.bytes_accessed = cost["bytes_accessed"]

    def add_readback(self, nbytes: int) -> None:
        with self._lock:
            self._readback_bytes += int(nbytes)

    def add_cache_hit(self) -> None:
        with self._lock:
            self._cache_hits += 1

    def add_cache_miss(self) -> None:
        with self._lock:
            self._cache_misses += 1

    def add_reader_batch(self, reader: str, packets: int,
                         samples: int, dt_ns: int,
                         fused: bool = False) -> None:
        """One ingested packet batch attributed to a reader thread
        (keyed by thread name, e.g. ``udp-reader-2``)."""
        with self._lock:
            r = self._readers.setdefault(reader, _ReaderEntry())
            r.batches += 1
            r.packets += int(packets)
            r.samples += int(samples)
            r.ingest_ns += int(dt_ns)
            if fused:
                r.fused_batches += 1

    # ------------------------------------------------------------------

    def totals(self) -> dict:
        """Cross-kernel totals — what Telemetry deltas per interval."""
        with self._lock:
            return {
                "compile_total": sum(e.compiles
                                     for e in self._entries.values()),
                "compile_duration_ns": sum(
                    e.compile_ns for e in self._entries.values()),
                "dispatch_total": sum(
                    e.calls for e in self._entries.values()),
                "dispatch_duration_ns": sum(
                    e.call_ns for e in self._entries.values()),
                "h2d_bytes_total": sum(
                    e.h2d_bytes for e in self._entries.values()),
                "readback_bytes_total": self._readback_bytes,
                "compile_cache_hits": self._cache_hits,
                "compile_cache_misses": self._cache_misses,
            }

    def snapshot(self) -> dict:
        """Full per-kernel dump for /debug/vars."""
        with self._lock:
            return {
                "kernels": {name: e.snapshot()
                            for name, e in self._entries.items()},
                "readers": {name: r.snapshot()
                            for name, r in self._readers.items()},
                "dispatch_total": sum(
                    e.calls for e in self._entries.values()),
                "h2d_bytes_total": sum(
                    e.h2d_bytes for e in self._entries.values()),
                "readback_bytes_total": self._readback_bytes,
                "compile_cache_hits": self._cache_hits,
                "compile_cache_misses": self._cache_misses,
            }


# One process-global registry: the instrumented jits are module-level
# objects (flusher/table kernels), so their counters are too.
REGISTRY = DeviceCostRegistry()


def instrument(name: str, fn,
               registry: DeviceCostRegistry | None = None):
    return (registry or REGISTRY).instrument(name, fn)
