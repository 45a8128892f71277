"""Per-sink flush fan-out (sinks/fanout.py + server wiring): a
stalled sink must time out on its own worker without delaying or
dropping the other sinks' flushes, retries back off in-worker, and
the per-sink counters surface in /debug/vars."""

import threading
import time

import pytest

from veneur_tpu.sinks.fanout import SinkFanout


def test_stalled_sink_does_not_delay_or_drop_others():
    release = threading.Event()
    done = []

    fo = SinkFanout(["stalled", "fast1", "fast2"], retries=0)
    tasks = [
        fo.dispatch("stalled", lambda: release.wait(timeout=30)),
        fo.dispatch("fast1", lambda: done.append("fast1")),
        fo.dispatch("fast2", lambda: done.append("fast2")),
    ]
    t0 = time.monotonic()
    late = fo.wait(tasks, deadline=time.monotonic() + 0.5)
    waited = time.monotonic() - t0
    # only the stalled sink overran; the fast sinks' flushes landed
    assert late == ["stalled"]
    assert sorted(done) == ["fast1", "fast2"]
    assert waited < 5.0  # bounded by the deadline, not the stall
    assert fo.stats()["stalled"]["timeouts"] == 1
    assert fo.stats()["fast1"]["flushes"] == 1
    release.set()
    fo.stop()


def test_busy_worker_drops_not_queues():
    """One-slot queue: one flush may queue behind the running one;
    the next dispatch is a counted drop, not a pile-up."""
    started = threading.Event()
    release = threading.Event()

    def stall():
        started.set()
        release.wait(timeout=30)

    fo = SinkFanout(["s"], retries=0)
    t1 = fo.dispatch("s", stall)
    assert started.wait(timeout=5)  # worker picked t1 up; slot free
    t2 = fo.dispatch("s", lambda: None)   # queued behind the stall
    t3 = fo.dispatch("s", lambda: None)   # slot full -> dropped
    assert t1 is not None and t2 is not None
    assert t3 is None
    assert fo.stats()["s"]["busy_drops"] == 1
    release.set()
    assert not fo.wait([t1, t2], deadline=time.monotonic() + 5.0)
    fo.stop()


def test_retry_with_backoff_then_success():
    calls = []

    def flaky():
        calls.append(time.monotonic())
        if len(calls) < 3:
            raise RuntimeError("transient")

    fo = SinkFanout(["s"], retries=3, backoff=0.02)
    task = fo.dispatch("s", flaky)
    assert not fo.wait([task], deadline=time.monotonic() + 5.0)
    assert len(calls) == 3
    assert task.error is None
    st = fo.stats()["s"]
    assert st["retries"] == 2 and st["errors"] == 0
    # exponential backoff: second gap >= first gap
    assert (calls[2] - calls[1]) >= (calls[1] - calls[0]) * 0.5
    fo.stop()


def test_final_failure_counts_error_and_calls_on_error():
    seen = []
    fo = SinkFanout(["s"], retries=1, backoff=0.01,
                    on_error=lambda name, exc: seen.append(name))
    task = fo.dispatch("s", lambda: (_ for _ in ()).throw(
        RuntimeError("boom")))
    fo.wait([task], deadline=time.monotonic() + 5.0)
    assert isinstance(task.error, RuntimeError)
    assert fo.stats()["s"]["errors"] == 1
    assert seen == ["s"]
    fo.stop()


def test_ensure_adds_worker_for_late_sink():
    fo = SinkFanout([], retries=0)
    task = fo.dispatch("late", lambda: None)
    assert not fo.wait([task], deadline=time.monotonic() + 5.0)
    assert fo.stats()["late"]["flushes"] == 1
    fo.stop()


# ---------------------------------------------------------------------
# server integration


@pytest.fixture
def fanout_server():
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    servers = []

    def _make(**overrides):
        cap = CaptureSink()
        s = Server(read_config(data={
            "statsd_listen_addresses": [], "interval": "500ms",
            "hostname": "fanout-host", **overrides}),
            extra_sinks=[cap])
        servers.append(s)
        return s, cap

    yield _make
    for s in servers:
        s.shutdown()


def _wait_for(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_server_stalled_sink_isolated_from_capture(fanout_server):
    server, cap = fanout_server(tpu_sink_workers=1, interval="2s")
    assert server._fanout is not None
    release = threading.Event()

    class Stall:
        name = "stall"

        def start(self):
            pass

        def flush(self, metrics):
            release.wait(timeout=30)

        def flush_other_samples(self, samples):
            pass

    server.metric_sinks.append(Stall())
    from veneur_tpu.protocol import dogstatsd as dsd
    server.table.ingest(dsd.parse_metric(b"iso.hits:1|c"))
    t0 = time.monotonic()
    server.flush_once()
    assert time.monotonic() - t0 < 15.0  # bounded by the budget
    # capture delivered despite the wedged sibling
    assert _wait_for(lambda: any(m.name == "iso.hits"
                                 for m in cap.metrics))
    assert server._fanout.stats()["stall"]["timeouts"] >= 1
    # interval 2: the stalled worker is still wedged, so this flush
    # queues behind it; interval 3's is a counted drop.  The capture
    # sink keeps flowing throughout — no delay, no drops.
    server.table.ingest(dsd.parse_metric(b"iso.hits2:1|c"))
    server.flush_once()
    assert _wait_for(lambda: any(m.name == "iso.hits2"
                                 for m in cap.metrics))
    server.table.ingest(dsd.parse_metric(b"iso.hits3:1|c"))
    server.flush_once()
    assert _wait_for(lambda: any(m.name == "iso.hits3"
                                 for m in cap.metrics))
    st = server._fanout.stats()
    assert st["stall"]["busy_drops"] >= 1
    assert st["capture"]["busy_drops"] == 0
    assert st["capture"]["flushes"] >= 3
    release.set()


def test_server_shared_pool_mode_still_flushes(fanout_server):
    server, cap = fanout_server(tpu_sink_workers=0)
    assert server._fanout is None
    from veneur_tpu.protocol import dogstatsd as dsd
    server.table.ingest(dsd.parse_metric(b"pool.hits:2|c"))
    server.flush_once()
    assert any(m.name == "pool.hits" and m.value == 2.0
               for m in cap.metrics)


def test_debug_vars_surfaces_per_sink_counters():
    import urllib.request
    import json as _json
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    server = Server(read_config(data={
        "statsd_listen_addresses": [],
        "http_address": "127.0.0.1:0", "interval": "10s",
        "tpu_sink_workers": 1}), extra_sinks=[CaptureSink()])
    server.start()
    try:
        server.flush_once()
        doc = _json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{server.http_port}/debug/vars",
            timeout=5).read())
        assert "sinks" in doc
        cap = doc["sinks"]["capture"]
        for key in ("flushes", "errors", "retries", "timeouts",
                    "busy_drops", "last_duration_s",
                    "total_duration_s"):
            assert key in cap
        assert cap["flushes"] >= 1 and cap["errors"] == 0
    finally:
        server.shutdown()


@pytest.mark.parametrize("workers", [1, 0])
def test_frame_sinks_leave_the_flush_unmaterialized(workers):
    """A cycle whose sinks all take ``flush_frame`` builds no
    InterMetric list: the result holds the frame and the status-check
    riders, and ``metrics`` is built when it is read."""
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.base import SinkBase

    class FrameSink(SinkBase):
        name = "frames"

        def __init__(self):
            super().__init__()
            self.frames = []

        def flush(self, metrics):
            raise AssertionError("a frame-aware sink was handed a list")

        def flush_frame(self, frame):
            self.frames.append(frame)

    sink = FrameSink()
    server = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "frame-host", "tpu_sink_workers": workers}),
        extra_sinks=[sink])
    try:
        for i in range(5):
            server.handle_packet(f"fr.hits.{i}:{i + 1}|c".encode())
        server.handle_packet(b"fr.lat:3|ms\nfr.lat:5|ms")
        server.handle_packet(b"_sc|fr.up|1|m:warn")
        res = server.flush_once()
        assert _wait_for(lambda: sink.frames)
        rec = server.flush_ring.last()
        assert res.frame._materialized is None
        assert [m.name for m in res.riders] == ["fr.up"]
        # the sink got the frame's blocks as they are, the rider beside
        got = sink.frames[0]
        assert got.blocks is res.frame.blocks
        assert [m.name for m in got.extra] == ["fr.up"]
        assert rec.metrics_emitted == res.metric_count() == len(
            res.frame) + 1
        assert res.frame._materialized is None
        metrics = res.metrics
        assert res.frame._materialized is not None
        assert len(metrics) == rec.metrics_emitted
        assert metrics[0].name == "fr.up" and metrics[0].type == "status"
        assert metrics[1:] == res.frame.materialize()
        names = {m.name for m in metrics}
        assert {"fr.hits.0", "fr.hits.4", "fr.lat.count"} <= names
        # a flush that is refused (shutdown) has the same shape
        server.shutdown()
        empty = server.flush_once()
        assert empty.metrics == [] and empty.metric_count() == 0
        assert len(empty.frame) == 0 and not empty.forward
    finally:
        server.shutdown()
