"""Self-observation: the framework watching its own hot path.

The reference veneur traces its own flushes (flusher.go:29
``trace.StartSpanFromContext``) and exposes ``/debug/pprof``
(http.go:52-57); this package is the TPU-aware extension of both:

``devicecost`` — a registry of instrumented hot-path jitted callables
    counting compiles, compile wall time, per-call dispatch time, XLA
    ``cost_analysis()`` flops/bytes estimates, and cumulative
    host<-device readback bytes.  A silently recompiling flush jit is
    the exact failure mode SALSA-style adaptive sketches warn about
    when state shapes drift — the compile counter makes it an
    assertable, alertable number.
``flushring``  — per-flush-cycle records (stage durations, readback
    bytes, tallies) in a bounded ring, served at ``/debug/flushes``.
``tracer``     — the flush cycle's nested SSF span tree (snapshot ->
    dispatch -> device wait -> host emit -> sink flush -> forward ->
    the receiving tier's import), emitted through the server's own
    loopback trace client so flush spans flow to span sinks like any
    user trace, each span also a ``jax.profiler.TraceAnnotation`` on
    the device trace's clock.
``profiler``   — on-demand ``jax.profiler`` captures for
    ``/debug/pprof/device?seconds=N``.
``ledger``     — per-interval sample-conservation ledger: every hot
    path credits received/staged/dropped/emitted/forwarded counts and
    the interval closes with balance checks, served at
    ``/debug/ledger`` (strict mode: ``VENEUR_TPU_LEDGER_STRICT``).
``traceindex`` — bounded per-process index of recent internal spans
    keyed by trace id, served at ``/debug/trace/<trace_id>`` so one
    interval's cross-tier span tree is queryable on every node.
``signals``    — fixed-schema columnar ring of per-flush signal rows
    (EWMA rate + delta computed at append), served at
    ``/debug/signals?window=<sec>`` — the history plane the autopilot
    (ROADMAP item 4) will read.
``recorder``   — anomaly flight recorder: trigger predicates over the
    signal rows dump CRC-framed incident bundles (last K rows, sealed
    ledger records, flush record + trace tree, subsystem snapshots)
    to ``VENEUR_TPU_FLIGHT_DIR``, listed at ``/debug/flight``.
"""

from veneur_tpu.observe.devicecost import (DeviceCostRegistry, REGISTRY,
                                           instrument)
from veneur_tpu.observe.flushring import FlushRecord, FlushRing
from veneur_tpu.observe.ledger import (ClassDropTally, Ledger,
                                       LedgerRecord, SpoolLedger,
                                       SpoolLedgerRecord)
from veneur_tpu.observe.tracer import (FlushCycle, FlushTracer,
                                       ImportSpan, NULL_CYCLE,
                                       NullCycle, annotate)
from veneur_tpu.observe.traceindex import TraceIndex, span_to_dict
from veneur_tpu.observe.profiler import capture_device_profile
from veneur_tpu.observe.recorder import (FlightRecorder, read_bundle,
                                         TRIGGER_NAMES)
from veneur_tpu.observe.signals import SignalHistory

__all__ = ["DeviceCostRegistry", "REGISTRY", "instrument",
           "FlushRecord", "FlushRing", "FlushCycle", "FlushTracer",
           "NullCycle", "NULL_CYCLE", "ImportSpan", "annotate",
           "capture_device_profile",
           "ClassDropTally", "Ledger", "LedgerRecord",
           "SpoolLedger", "SpoolLedgerRecord",
           "TraceIndex", "span_to_dict",
           "SignalHistory", "FlightRecorder", "read_bundle",
           "TRIGGER_NAMES"]
