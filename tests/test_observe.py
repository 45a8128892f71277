"""Flush self-tracing + device-cost accounting (veneur_tpu/observe).

Covers the observability acceptance surface: the per-flush SSF span
tree delivered through the server's own trace client, the
/debug/flushes ring records, the device-cost registry's compile
detection (and its steady-state flatness — the property the
``veneur.xla.compile_total`` metric exists to alarm on), and the two
telemetry fixes (current-RSS gauge, stats_address config error).
"""

import socket
import time
import types

import jax
import jax.numpy as jnp
import pytest

from veneur_tpu import observe
from veneur_tpu.core.config import read_config
from veneur_tpu.core.server import Server
from veneur_tpu.core.telemetry import Telemetry, _rss_bytes
from veneur_tpu.observe.devicecost import DeviceCostRegistry
from veneur_tpu.observe.flushring import (FlushRecord, FlushRing,
                                          is_gc_key)
from veneur_tpu.observe.gcpause import PAUSES
from veneur_tpu.sinks.simple import CaptureSink


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


STAGES = ("snapshot", "dispatch", "device_wait",
          "host_emit", "sink_flush")
# the names dispatch / device_wait had before the pipeline split them
# apart: recorded nowhere any more
DEAD_NAMES = {"device_dispatch", "readback_sync"}


# ---------------------------------------------------------------------
# flush span tree

def test_flush_cycle_emits_stage_span_tree():
    """One flush -> a root ``flush`` span with one ``flush.<stage>``
    child per pipeline stage, all in one trace, delivered to span
    sinks through the server's own loopback trace client."""
    cap = CaptureSink()
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "trace-host"}), extra_span_sinks=[cap])
    srv.start()
    try:
        srv.handle_packet(b"obs.hits:3|c")
        srv.handle_packet(b"obs.lat:12|ms")
        srv.handle_packet(b"obs.users:u1|s")
        srv.flush_once()
        want = {"flush"} | {f"flush.{s}" for s in STAGES}
        assert _wait(lambda: want <=
                     {sp.name for sp in cap.spans}), \
            sorted({sp.name for sp in cap.spans})
        by_name = {sp.name: sp for sp in cap.spans}
        root = by_name["flush"]
        assert root.parent_id == 0
        assert root.service == "veneur"
        assert root.tags["flush.seq"] == "1"
        for stage in STAGES:
            sp = by_name[f"flush.{stage}"]
            # every stage hangs off the root, in the root's trace
            assert sp.parent_id == root.id
            assert sp.trace_id == root.trace_id
            assert sp.tags["stage"] == stage
            assert sp.end_timestamp >= sp.start_timestamp
        # >=5 distinct stage spans is the acceptance bar
        assert len(STAGES) >= 5
    finally:
        srv.shutdown()


def test_flush_ring_record_matches_cycle():
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "ring-host"}))
    srv.start()
    try:
        srv.handle_packet(b"ring.hits:3|c")
        srv.handle_packet(b"ring.lat:12|ms")
        srv.flush_once()
        srv.flush_once()
        recs = srv.flush_ring.records()
        assert [r.seq for r in recs] == [1, 2]
        for rec in recs:
            # pipelined swap (the default): swap_apply is a stage too
            assert set(rec.stages) >= set(STAGES) | {"swap_apply"}
            assert not DEAD_NAMES & set(rec.stages)
            assert all(ns >= 0 for ns in rec.stages.values())
            # top-level stages are disjoint intervals inside the
            # cycle (a dotted stage is a child of the one it names)
            assert sum(ns for k, ns in rec.stages.items()
                       if "." not in k and not is_gc_key(k)
                       ) <= rec.duration_ns
            assert rec.error == ""
            # nothing forwarded, nothing imported: both read zero
            d = rec.to_dict()
            assert d["forward_bytes"] == 0 and d["imports"] == 0
            assert not any(k.startswith(("import", "forward"))
                           for k in rec.stages)
        # the interval that carried the metrics read them back
        assert recs[0].readback_bytes > 0
        assert recs[0].tally["counters"] == 1
        assert recs[0].tally["histograms"] == 1
        assert recs[0].metrics_emitted > 0
    finally:
        srv.shutdown()


def test_sink_route_stage_says_what_the_routing_did():
    """Every sink's routing is one ``sink_flush.route`` stage under
    ``sink_flush``, in the record's stages; its span and the record
    carry how many live series have a ``veneursinkonly:`` tag, its
    span how many sinks there were and how many were handed the
    frame's blocks unrouted."""
    cap = CaptureSink()

    class Other(CaptureSink):
        name = "other"
    other = Other()
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "route-host"}), extra_sinks=[cap, other],
        extra_span_sinks=[cap])
    srv.start()
    try:
        sinks = len(srv.metric_sinks)
        assert sinks >= 2
        srv.handle_packet(b"rt.hits:3|c")
        srv.flush_once()
        srv.handle_packet(b"rt.hits:3|c")
        srv.handle_packet(b"rt.only:1|c|#veneursinkonly:capture")
        srv.flush_once()
        recs = srv.flush_ring.records()
        assert [r.sink_only_rows for r in recs] == [0, 1]
        assert [r.to_dict()["sink_only_rows"] for r in recs] == [0, 1]
        for rec, shared in zip(recs, (sinks, 0)):
            assert 0 <= rec.stages["sink_flush.route"] \
                <= rec.stages["sink_flush"]
            assert _wait(lambda: {
                "flush.sink_flush", "flush.sink_flush.route"} <= {
                s["name"] for s in srv.trace_index.get(rec.trace_id)})
            spans = {s["name"]: s
                     for s in srv.trace_index.get(rec.trace_id)}
            route = spans["flush.sink_flush.route"]
            assert (route["parent_id"]
                    == spans["flush.sink_flush"]["span_id"])
            assert route["tags"]["stage"] == "sink_flush.route"
            assert (route["tags"]["sinks"],
                    route["tags"]["sink_only_rows"],
                    route["tags"]["shared"]) == (
                str(sinks), str(rec.sink_only_rows), str(shared))
        assert _wait(lambda: len(cap.batches) == 2
                     and len(other.batches) == 2)
        assert "rt.only" in {m.name for m in cap.batches[1]}
        assert "rt.only" not in {m.name for m in other.metrics}
    finally:
        srv.shutdown()


def _host_events(trace_dir, prefixes):
    """name -> [(start_ns, duration_ns)] of the host planes' events
    whose name starts with one of ``prefixes``, read back from the
    capture's ``.xplane.pb``."""
    import glob
    import os
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    out = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns))
    return out


def test_flush_stages_are_events_on_the_profilers_clock(tmp_path):
    """Under a profiler session every stage of the flush record is
    also a ``flush.<stage>`` event on the capture's host lines, of
    the stage's own length, inside the cycle's ``flush`` event; so
    are the reader's batch and the staged apply (``ingest.batch``,
    ``apply.staged``), which have no SSF span."""
    srv = Server(read_config(data={
        "statsd_listen_addresses": ["udp://127.0.0.1:0"],
        "interval": "10s", "hostname": "prof-host"}),
        extra_sinks=[CaptureSink()])
    srv.start()
    try:
        srv.handle_packet(b"prof.hits:3|c")
        srv.flush_once()          # compiles happen outside the capture
        jax.profiler.start_trace(str(tmp_path))
        try:
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sock.sendto(b"\n".join(b"prof.lat:%d|ms" % v
                                   for v in range(8)),
                        ("127.0.0.1", srv.statsd_ports[0]))
            sock.close()
            assert _wait(lambda: srv.stats["metrics_processed"] >= 9)
            srv.flush_once()
        finally:
            jax.profiler.stop_trace()
        rec = srv.flush_ring.records()[-1]
    finally:
        srv.shutdown()
    events = _host_events(str(tmp_path),
                          ("flush", "ingest.batch", "apply.staged"))
    assert set(rec.stages) >= set(STAGES)
    for stage, ns in rec.stages.items():
        if is_gc_key(stage):      # a pause, not a stage: no event
            continue
        got = events.get(f"flush.{stage}")
        assert got, (stage, sorted(events))
        assert abs(sum(d for _, d in got) - ns) <= max(2e6, 0.1 * ns)
    (c0, cd), = events["flush"]
    assert abs(cd - rec.duration_ns) <= max(2e6, 0.1 * rec.duration_ns)
    for name, got in events.items():
        if name.startswith("flush."):
            assert all(c0 <= s and s + d <= c0 + cd for s, d in got)
    # the datagram's batch on the reader's thread; the interval's
    # staged samples applied inside the swap
    assert events["ingest.batch"]
    (a0, ad), = events["apply.staged"]
    (s0, sd), = events["flush.swap_apply"]
    assert s0 <= a0 and a0 + ad <= s0 + sd


# ---------------------------------------------------------------------
# collector pauses in the flush record

class _CollectingSink(CaptureSink):
    """Forces a full collection while it is handed its flush: inside
    its own ``sink.collecting`` stage, on its worker's thread, while
    the flush thread waits for it inside ``sink_flush``."""
    name = "collecting"
    collect = True

    def flush(self, metrics):
        import gc
        if self.collect:
            gc.collect()
        super().flush(metrics)


def test_forced_collection_shows_in_stage_record_and_debug_flushes():
    import gc
    import json
    import urllib.request
    sink, cap = _CollectingSink(), CaptureSink()
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "http_address": "127.0.0.1:0", "hostname": "gc-host"}),
        extra_sinks=[sink], extra_span_sinks=[cap])
    srv.start()
    try:
        srv.handle_packet(b"gc.hits:3|c")
        gen2 = PAUSES.collections[2]
        srv.flush_once()
        rec = srv.flush_ring.records()[-1]
        # the pause is the worker's stage's and, the interpreter lock
        # being one, that of the stage that waited for it
        paused = rec.stages["gc.sink.collecting"]
        assert paused > 0
        assert rec.stages["gc.sink_flush"] >= paused
        assert rec.gc_pause_ns == rec.stages["gc"] >= paused
        assert rec.gc_gen2 >= 1
        assert PAUSES.collections[2] - gen2 >= rec.gc_gen2
        # a stage no collection ended in has no key; every key is a
        # stage's
        for k in rec.stages:
            if is_gc_key(k) and k != "gc":
                assert k[3:] in rec.stages and rec.stages[k] > 0
        # the same on the spans: gc_ns where there was a pause
        assert _wait(lambda: {"flush", "flush.sink.collecting",
                              "flush.snapshot"}
                     <= {sp.name for sp in cap.spans})
        by_name = {sp.name: sp for sp in cap.spans}
        assert by_name["flush.sink.collecting"].tags["gc_ns"] == str(
            paused)
        assert int(by_name["flush"].tags["gc_ns"]) >= paused
        for sp in cap.spans:
            stage = sp.tags.get("stage")
            if stage:
                assert ("gc_ns" in sp.tags) == (
                    f"gc.{stage}" in rec.stages), stage
        d = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.http_port}/debug/flushes?n=1",
            timeout=5).read())[0]
        assert d["gc_pause_ns"] == rec.gc_pause_ns
        assert d["gc_gen2"] == rec.gc_gen2
        assert d["stages_ns"]["gc.sink.collecting"] == paused
        # a cycle without a collection still says that it counts
        sink.collect = False
        gc.disable()
        try:
            srv.flush_once()
        finally:
            gc.enable()
        assert srv.flush_ring.records()[-1].stages["gc"] == 0
        # self-telemetry: the last whole cycle's pause as a gauge, a
        # flush late, and no stage series for a pause key
        srv.flush_once()
        tele = {m.name: m for m in sink.metrics
                if m.name.startswith("veneur.flush.")}
        assert tele["veneur.flush.gc_pause_ns"].value > 0
        assert not any(
            t.startswith("stage:gc") for m in sink.metrics
            if m.name.startswith("veneur.flush.stage_duration_ns")
            for t in m.tags)
    finally:
        srv.shutdown()


def test_one_gc_hook_a_process_removed_by_the_last_shutdown(
        monkeypatch):
    import gc
    from veneur_tpu.observe.gcpause import GcPauses
    # a counter of this test's own: a server another test left
    # running holds the process's
    pauses = GcPauses()
    monkeypatch.setattr("veneur_tpu.core.server.PAUSES", pauses)
    hooks = len(gc.callbacks)
    a = Server(read_config(data={"statsd_listen_addresses": [],
                                 "interval": "10s"}))
    b = Server(read_config(data={"statsd_listen_addresses": [],
                                 "interval": "10s"}))
    assert len(gc.callbacks) == hooks      # constructing hooks nothing
    a.start()
    b.start()
    try:
        assert len(gc.callbacks) == hooks + 1
        gc.collect()
        total = pauses.pause_ns
        assert total > 0 and pauses.collections[2] == 1
        young = pauses.collections[0]
        gc.collect(0)
        assert pauses.pause_ns > total
        assert pauses.collections[0] > young
        assert pauses.collections[2] == 1
    finally:
        a.shutdown()
        assert len(gc.callbacks) == hooks + 1 and pauses.installed
        b.shutdown()
    assert len(gc.callbacks) == hooks and not pauses.installed
    b.shutdown()                            # twice: nothing to take off
    assert len(gc.callbacks) == hooks
    total = pauses.pause_ns
    gc.collect()
    assert pauses.pause_ns == total


# ---------------------------------------------------------------------
# compile stability (tier-1 acceptance criterion)

def test_flush_jits_do_not_recompile_for_stable_shapes():
    """Steady state: after warmup, consecutive same-shape flushes must
    not add a single compile — a moving ``veneur.xla.compile_total``
    on a stable workload is the shape-drift bug the registry exists
    to catch.  ``stats_address`` points at a throwaway UDP port so
    self-telemetry leaves the table alone (loopback injection would
    legitimately change touched-row counts between intervals)."""
    sink = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sink.bind(("127.0.0.1", 0))
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "jit-host",
        "stats_address": f"127.0.0.1:{sink.getsockname()[1]}"}))
    srv.start()
    try:
        packets = (b"stable.hits:3|c", b"stable.temp:7|g",
                   b"stable.lat:12|ms", b"stable.users:u1|s")

        def one_flush():
            for p in packets:
                srv.handle_packet(p)
            srv.flush_once()

        for _ in range(2):  # warmup: every shape bucket compiles here
            one_flush()
        before = observe.REGISTRY.totals()["compile_total"]
        for _ in range(3):
            one_flush()
        after = observe.REGISTRY.totals()["compile_total"]
        assert after == before, (
            f"{after - before} recompile(s) across 3 same-shape "
            f"flushes: {observe.REGISTRY.snapshot()['kernels']}")
        # the ring records the same fact per cycle
        assert all(r.compiles == 0
                   for r in srv.flush_ring.records()[-3:])
    finally:
        srv.shutdown()
        sink.close()


# ---------------------------------------------------------------------
# device-cost registry

def test_instrumented_jit_counts_compiles_per_shape():
    reg = DeviceCostRegistry()
    fn = observe.instrument(
        "t.double", jax.jit(lambda x: x * 2), registry=reg)
    a = jnp.arange(8, dtype=jnp.float32)
    fn(a)
    fn(a)          # cache hit
    fn(a[:4])      # new shape -> new variant
    snap = reg.snapshot()["kernels"]["t.double"]
    assert snap["calls"] == 3
    assert snap["compiles"] == 2
    assert snap["compile_duration_ns"] > 0
    assert snap["dispatch_duration_ns"] >= snap["compile_duration_ns"]
    totals = reg.totals()
    assert totals["compile_total"] == 2
    assert totals["readback_bytes_total"] == 0


def test_instrumented_jit_forwards_wrapped_attrs():
    reg = DeviceCostRegistry()
    fn = observe.instrument(
        "t.fwd", jax.jit(lambda x: x + 1), registry=reg)
    a = jnp.zeros(4)
    fn(a)
    # lower() must reach the real jit (devicecost uses it for
    # cost_analysis); _cache_size is the compile detector
    assert fn.lower(a) is not None
    assert fn._cache_size() >= 1


def test_null_cycle_readback_still_counts():
    before = observe.REGISTRY.totals()["readback_bytes_total"]
    observe.NULL_CYCLE.add_readback(123)
    assert observe.REGISTRY.totals()["readback_bytes_total"] == \
        before + 123


def test_flush_ring_bounded_and_summarized():
    ring = FlushRing(capacity=4)
    for _ in range(6):
        rec = FlushRecord(seq=ring.next_seq())
        rec.stages["host_emit"] = 100 * rec.seq
        rec.readback_bytes = 10
        ring.append(rec)
    recs = ring.records()
    assert [r.seq for r in recs] == [3, 4, 5, 6]  # oldest evicted
    summ = ring.stage_summary()
    assert summ["cycles"] == 4
    assert summ["stages_ns"]["host_emit"] == {
        "mean": 450, "max": 600, "last": 600, "count": 4}
    assert summ["readback_bytes_mean"] == 10


# ---------------------------------------------------------------------
# telemetry fixes

def test_rss_bytes_is_current_not_peak():
    rss = _rss_bytes()
    assert isinstance(rss, int)
    assert 0 < rss < 1 << 42  # a real, sane byte count


def _stub(addr):
    return types.SimpleNamespace(
        config=types.SimpleNamespace(stats_address=addr))


@pytest.mark.parametrize("addr", ["localhost", "127.0.0.1",
                                  "host:notaport"])
def test_stats_address_without_port_is_config_error(addr):
    with pytest.raises(ValueError, match="stats_address"):
        Telemetry(_stub(addr))


@pytest.mark.parametrize("addr", ["127.0.0.1:8125",
                                  "udp://127.0.0.1:8125"])
def test_stats_address_accepted_forms(addr):
    t = Telemetry(_stub(addr))
    assert t._addr == ("127.0.0.1", 8125)


# ---------------------------------------------------------------------
# eviction under concurrent writers (ISSUE 16): the flight recorder
# reads TraceIndex/FlushRing from the flush thread while importers and
# tracer callbacks append from others — reads must never tear or raise
# while eviction churns.

def _span_proto(trace_id, span_id):
    return types.SimpleNamespace(
        name="s", service="veneur", trace_id=trace_id, id=span_id,
        parent_id=0, start_timestamp=span_id, end_timestamp=span_id,
        error=False, tags={})


def test_trace_index_eviction_under_concurrent_writers():
    from veneur_tpu.observe.traceindex import TraceIndex
    import threading
    idx = TraceIndex(capacity=32, max_spans=8)
    stop = threading.Event()
    errors = []

    def writer(tid_base):
        i = 0
        while not stop.is_set():
            idx.add(_span_proto(tid_base + (i % 100), i + 1))
            i += 1

    def reader():
        while not stop.is_set():
            try:
                ids = idx.trace_ids()
                assert len(ids) <= 32  # capacity holds mid-churn
                for tid in ids[-4:]:
                    spans = idx.get(tid)
                    assert len(spans) <= 8
                    for sp in spans:
                        assert sp["trace_id"] == str(tid)
                if ids:
                    idx.to_json(ids[-1])
            except Exception as e:  # pragma: no cover - the failure
                errors.append(e)
                return

    ts = [threading.Thread(target=writer, args=(t * 1000,))
          for t in range(4)] + [threading.Thread(target=reader)]
    for t in ts:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in ts:
        t.join(5.0)
    assert not errors, errors
    assert len(idx.trace_ids()) <= 32


def test_flush_ring_eviction_under_concurrent_writers():
    import threading
    ring = FlushRing(capacity=16)
    stop = threading.Event()
    errors = []

    def writer():
        while not stop.is_set():
            rec = FlushRecord(seq=ring.next_seq())
            rec.stages["host_emit"] = rec.seq
            rec.readback_bytes = 10
            ring.append(rec)

    def reader():
        while not stop.is_set():
            try:
                recs = ring.records()
                assert len(recs) <= 16  # bound holds mid-churn
                # a torn read would show duplicate seqs or partially
                # initialized records (next_seq issues each once; the
                # writers race between next_seq and append, so order
                # within a snapshot is not promised — uniqueness is)
                seqs = [r.seq for r in recs]
                assert len(seqs) == len(set(seqs))
                assert all(r.readback_bytes == 10 for r in recs)
                ring.to_json(limit=4)
                ring.stage_summary()
            except Exception as e:  # pragma: no cover - the failure
                errors.append(e)
                return

    ts = [threading.Thread(target=writer) for _ in range(4)] + \
        [threading.Thread(target=reader)]
    for t in ts:
        t.start()
    time.sleep(0.4)
    stop.set()
    for t in ts:
        t.join(5.0)
    assert not errors, errors
    recs = ring.records()
    assert len(recs) == 16
    seqs = [r.seq for r in recs]
    assert len(seqs) == len(set(seqs))
