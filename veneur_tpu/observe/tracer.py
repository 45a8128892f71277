"""Flush self-tracing: one nested SSF span tree per flush cycle, the
import handlers' span tree on the receiving tier, and every span's
place on the device trace's clock.

The reference wraps its flush in ``trace.StartSpanFromContext``
(flusher.go:29) and child spans per phase; here ``FlushTracer.cycle``
opens the root ``flush`` span and ``FlushCycle.stage`` hangs one
child per pipeline stage off it.  A dotted stage is the child of the
stage before the dot:

    flush
      +- flush.snapshot     staging detach + metadata capture under the
      |                     ingest lock (pipelined: O(µs) begin_swap)
      +- flush.swap_apply   final combine dispatch after the lock drops
      |                     (pipelined mode only)
      +- flush.dispatch     combine/readout jit dispatch (async)
      +- flush.device_wait  device_get — the d2h sync point
      +- flush.host_emit    InterMetric assembly from row metadata
      +- flush.sink_flush   per-sink fan-out + interval-budget wait
      |    +- flush.sink_flush.route  every sink's routing, before
      |                               the first dispatch; tags
      |                               ``sinks``, ``sink_only_rows``
      |                               (live series with a
      |                               ``veneursinkonly:`` tag),
      |                               ``shared`` (sinks handed the
      |                               frame's blocks as they are)
      +- flush.sink.<name>  one sink's encode + delivery (its worker)
      +- flush.forward      upstream ship (local tier only)
           +- flush.forward.encode  rows -> MetricList bytes; tags
           |                        ``rows_block`` (rows encoded from
           |                        the flush's column blocks),
           |                        ``rows_loose`` (rows that came one
           |                        by one), ``ident_cached`` (series
           |                        whose identity bytes were kept
           |                        from an earlier interval)
           +- flush.forward.send    the unary call, call to return;
           |    |                   its ids ride the wire
           |    +- import             the receiving tier's handler,
           |         |                entry to exit (``ImportSpan``)
           |         +- import.decode       wire -> columns, no lock
           |         +- import.lock_wait    asking for the ingest
           |         |                      lock to having it
           |         +- import.apply        fold + dedup + ledger
           |         |    |                 credit, under the lock
           |         |    +- import.apply.resolve  wire items -> rows
           |         |    +- import.apply.digests  scalars, digest
           |         |    |                        stats and centroids
           |         |    |                        staged
           |         |    +- import.apply.sets     sketches decoded and
           |         |                             unioned on the host;
           |         |                             tags ``planes``,
           |         |                             ``planes_loose``
           |         |                             (those decoded one
           |         |                             by one)
           |         +- import.device_step  the staged apply, if the
           |                                wire crossed the threshold
           +- flush.forward.shard   sharded path: one per destination

Spans go through the server's own loopback trace client, so they flow
to span sinks (and ssfmetrics extraction) like any user trace.  Each
cycle also fills a ``FlushRecord`` for the ``/debug/flushes`` ring;
the durations of the imports a server handled since its last flush
are folded into the next cycle's record under their own names.

Every span here is opened through ``_traced`` (or ``_open`` /
``_close`` where it outlives a block), which enters a
``jax.profiler.TraceAnnotation`` of the span's own name around the
same work.  With no profiler session that is a sub-microsecond no-op;
under one (``/debug/pprof/device``, ``enable_profiling``, a
benchmark's ``--trace 1``) each span is also an event on the host
lines of the same ``.xplane.pb`` as the device's ``XLA Ops``, on the
profiler's clock.  ``annotate`` is the same for the two per-batch
sites that have no SSF span (``ingest.batch``, ``apply.staged``).

``_traced`` also reads the process's collector-pause counter
(``observe/gcpause.py``) on entry and exit: a span in which a
collection ended carries ``gc_ns``, a flush stage also the key
``gc.<stage>`` in its record's ``stages``, and every record the key
``gc`` (the cycle's whole pause) with ``gc_pause_ns`` / ``gc_gen2``.

``NULL_CYCLE`` is the no-tracer stand-in for direct ``Flusher.flush``
callers (tests, benches): stages are free, but readback accounting
still reaches the device-cost registry.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time

from jax.profiler import TraceAnnotation as annotate

from veneur_tpu.observe.devicecost import REGISTRY
from veneur_tpu.observe.flushring import FlushRecord, FlushRing
from veneur_tpu.observe.gcpause import PAUSES
from veneur_tpu.trace.spans import Span


class _NullSpan:
    trace_id = 0
    span_id = 0

    def add_tag(self, key, value):
        pass

    def set_error(self, err=True):
        pass

    def child(self, name, **kw):
        return self

    def finish(self, client=None):
        return None


_NULL_SPAN = _NullSpan()


def _finish(span, client, index) -> None:
    if span.finish(client) is not None and index is not None:
        index.add(span.proto)


@contextlib.contextmanager
def _traced(span, name: str, client, index, note, note_gc=None):
    """The one way a block is timed here: as the SSF span ``span`` and
    as a profiler annotation ``name`` around the same work, so a stage
    cannot have one without the other; ``note(ns)`` takes the block's
    monotonic duration.  Collector pauses that ended inside the block
    (on any thread: a collection holds the interpreter lock the block
    needs) are the span's tag ``gc_ns`` and go to ``note_gc(ns)``,
    both only where there were any."""
    ann = annotate(name)
    ann.__enter__()
    gc0 = PAUSES.pause_ns
    t0 = time.monotonic_ns()
    try:
        yield span
    except BaseException as e:
        span.set_error(e)
        raise
    finally:
        note(time.monotonic_ns() - t0)
        ann.__exit__(None, None, None)
        paused = PAUSES.pause_ns - gc0
        if paused:
            span.add_tag("gc_ns", str(paused))
            if note_gc is not None:
                note_gc(paused)
        _finish(span, client, index)


class NullCycle:
    """Stage spans are no-ops; readback bytes still count."""

    record = None

    @contextlib.contextmanager
    def stage(self, name: str, parent=None):
        yield _NULL_SPAN

    def child(self, parent, name: str, tags=None):
        return _NULL_SPAN

    def finish(self, span) -> None:
        pass

    def add_readback(self, nbytes: int) -> None:
        REGISTRY.add_readback(nbytes)

    def wire_context(self, span=None) -> tuple[int, int]:
        return 0, 0


NULL_CYCLE = NullCycle()


class FlushCycle:
    def __init__(self, root, client, record: FlushRecord, registry,
                 index=None):
        self.root = root
        self._client = client
        self.record = record
        self._registry = registry
        self._index = index
        self._lock = threading.Lock()

    def wire_context(self, span=None) -> tuple[int, int]:
        """(trace_id, span_id) to stamp onto a forward wire so the
        receiving tier can parent its import span under ours.  Pass
        the stage span actually doing the shipping (e.g. the
        ``forward.send`` child) to parent under it instead of the
        root."""
        sp = span if span is not None else self.root
        return sp.trace_id, sp.span_id

    def _add_stage(self, name: str, ns: int) -> None:
        with self._lock:
            self.record.stages[name] = (
                self.record.stages.get(name, 0) + ns)

    def stage(self, name: str, parent=None):
        """Time one pipeline stage as a child span of the flush root,
        or of ``parent`` for a dotted sub-stage (``forward.encode``
        under the ``forward`` stage's span).  Safe to enter from pool
        threads (the forward stage runs on one); re-entering a stage
        name accumulates its ns."""
        sp = (parent or self.root).child(f"flush.{name}")
        sp.add_tag("stage", name)
        sp.add_tag("veneur.internal", "true")
        return _traced(sp, f"flush.{name}", self._client, self._index,
                       functools.partial(self._add_stage, name),
                       functools.partial(self._add_stage, f"gc.{name}"))

    def child(self, parent, name: str, tags=None):
        """A live child span under ``parent`` (a stage span), for
        sub-stage work that outlives the stage block — e.g. one span
        per sharded-forward destination, so ``/debug/trace/<id>``
        renders M forward branches instead of M wires sharing the one
        ``flush.forward`` span id.  Callers finish it with
        :meth:`finish` (safe from destination-worker threads)."""
        sp = parent.child(f"flush.{name}")
        sp.add_tag("veneur.internal", "true")
        for k, v in (tags or {}).items():
            sp.add_tag(k, v)
        sp.annotation = annotate(f"flush.{name}")
        sp.annotation.__enter__()
        return sp

    def finish(self, span) -> None:
        """Record a :meth:`child` span to the trace client + debug
        index (mirrors the tail of :meth:`stage`)."""
        span.annotation.__exit__(None, None, None)
        _finish(span, self._client, self._index)

    def add_readback(self, nbytes: int) -> None:
        self._registry.add_readback(nbytes)
        with self._lock:
            self.record.readback_bytes += int(nbytes)

    def add_forward_bytes(self, nbytes: int) -> None:
        with self._lock:
            self.record.forward_bytes += int(nbytes)

    def note_forward_encode(self, counts: dict) -> None:
        """What ``encode_metric_list`` said it worked on, onto the
        cycle's record."""
        with self._lock:
            self.record.rows_block += counts["rows_block"]
            self.record.rows_loose += counts["rows_loose"]
            self.record.ident_cached += counts["ident_cached"]


class ImportSpan:
    """The receiving tier's half of a forward: an ``import`` span from
    the handler's entry to its exit, one ``import.<step>`` child per
    step, and their durations handed to ``note`` at the exit (the
    server folds them into its next flush record).

    The sending tier stamped its ``forward.send`` span's (trace_id,
    span_id) onto the wire (X-Veneur-Trace header / veneur-trace-*
    gRPC metadata), so the tree recorded here parents under it and
    the whole interval stitches into one tree at
    ``/debug/trace/<trace_id>`` on either end.  A wire without that
    context (an old peer, propagation gated off) is timed and
    annotated all the same, and leaves no SSF span."""

    def __init__(self, client, index, note, protocol: str,
                 trace_id: int, span_id: int):
        self._client = client
        self._index = index
        self._note = note
        self.stages: dict[str, int] = {}
        self.span = _NULL_SPAN
        if trace_id:
            self.span = Span(
                "import", service="veneur", trace_id=trace_id,
                parent_id=span_id,
                tags={"protocol": protocol,
                      "veneur.internal": "true"})

    def _timed(self, span, name: str):
        return _traced(span, name, self._client, self._index,
                       functools.partial(self._add, name))

    def _add(self, name: str, ns: int) -> None:
        self.stages[name] = self.stages.get(name, 0) + ns

    def __enter__(self):
        self._whole = self._timed(self.span, "import")
        self._whole.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            return self._whole.__exit__(*exc)
        finally:
            self._note(self.stages)

    def step(self, name: str, parent=None):
        """Time ``import.<name>`` as a child of the import span, or of
        ``parent`` for a dotted step (``apply.sets`` under the
        ``apply`` step's span); a step entered twice accumulates."""
        name = f"import.{name}"
        sp = (parent or self.span).child(name)
        sp.add_tag("veneur.internal", "true")
        return self._timed(sp, name)

    def result(self, accepted: int, dropped: int, nbytes: int) -> None:
        self.span.add_tag("accepted", str(accepted))
        self.span.add_tag("dropped", str(dropped))
        self.span.add_tag("bytes", str(nbytes))


class FlushTracer:
    def __init__(self, client, ring: FlushRing, registry=None,
                 service: str = "veneur", index=None):
        self.client = client
        self.ring = ring
        self.registry = registry or REGISTRY
        self.service = service
        self.index = index

    @contextlib.contextmanager
    def cycle(self):
        record = FlushRecord(seq=self.ring.next_seq(),
                             start_unix=time.time())
        # the internal marker exempts these spans from the user-span
        # throughput counter and the uniqueness sketch (core/spans.py,
        # sinks/ssfmetrics.py) — they still reach every span sink
        root = Span("flush", service=self.service,
                    tags={"veneur.internal": "true"})
        record.trace_id = root.trace_id
        cyc = FlushCycle(root, self.client, record, self.registry,
                         index=self.index)
        compiles0 = self.registry.totals()["compile_total"]
        gc0, gen2_0 = PAUSES.pause_ns, PAUSES.collections[2]
        try:
            with _traced(root, "flush", self.client, self.index,
                         functools.partial(setattr, record,
                                           "duration_ns")):
                try:
                    yield cyc
                except BaseException as e:
                    record.error = f"{type(e).__name__}: {e}"
                    raise
                finally:
                    record.compiles = (
                        self.registry.totals()["compile_total"]
                        - compiles0)
                    # the cycle's whole pause, 0 included: the key
                    # says that this program counts
                    record.gc_pause_ns = PAUSES.pause_ns - gc0
                    record.gc_gen2 = PAUSES.collections[2] - gen2_0
                    cyc._add_stage("gc", record.gc_pause_ns)
                    root.add_tag("flush.seq", str(record.seq))
        finally:
            self.ring.append(record)
