"""/debug/* introspection surface (core/debughttp.py) over a live
server listener: pprof thread dump, heap tracing toggles, cProfile
sampling with the concurrent-503 guard, the jax device capture, the
expvar-style /debug/vars dump, and 404s for unknown paths."""

import json
import urllib.error
import urllib.request

import pytest

from veneur_tpu.core.config import read_config
from veneur_tpu.core.server import Server


@pytest.fixture
def server():
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "dbg", "http_address": "127.0.0.1:0"}))
    srv.start()
    yield srv
    srv.shutdown()


def _get(server, path):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{server.http_port}{path}", timeout=10)


def test_thread_dump(server):
    """/debug/pprof (and .../goroutine, .../threads) dumps every
    thread's stack — the flush thread must be in there."""
    for path in ("/debug/pprof", "/debug/pprof/goroutine",
                 "/debug/pprof/threads"):
        body = _get(server, path).read().decode()
        assert "Thread" in body
    assert "flush" in body


def test_heap_start_snapshot_stop(server):
    # not tracing yet: instructive message, not an error
    body = _get(server, "/debug/pprof/heap").read()
    assert b"not tracing" in body
    assert _get(server, "/debug/pprof/heap?start=1").read() == \
        b"tracing started"
    try:
        # tracing: a real top-allocations snapshot mentions a file
        body = _get(server, "/debug/pprof/heap").read().decode()
        assert ".py" in body
    finally:
        assert _get(server, "/debug/pprof/heap?stop=1").read() == \
            b"tracing stopped"


def test_profile_seconds(server):
    body = _get(server,
                "/debug/pprof/profile?seconds=0.1").read().decode()
    assert "cumulative" in body  # pstats table header


def test_profile_concurrent_503(server):
    """Only one profiler per process: while one capture holds the
    lock, a second request is refused, not queued."""
    assert server._pprof_lock.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(server, "/debug/pprof/profile?seconds=0.1")
        assert ei.value.code == 503
    finally:
        server._pprof_lock.release()


def test_device_profile_capture(server):
    """/debug/pprof/device grabs a jax profiler trace from the live
    process and lists the xplane artifacts."""
    out = json.loads(
        _get(server, "/debug/pprof/device?seconds=0.1").read())
    assert out["dir"].startswith("/")
    assert isinstance(out["files"], list)


def test_device_profile_concurrent_503(server):
    assert server._pprof_lock.acquire(blocking=False)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(server, "/debug/pprof/device?seconds=0.1")
        assert ei.value.code == 503
    finally:
        server._pprof_lock.release()


def test_pprof_unknown_404(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/debug/pprof/nosuchprofile")
    assert ei.value.code == 404


def test_http_unknown_404(server):
    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(server, "/debug/nosuch")
    assert ei.value.code == 404


def test_debug_vars(server):
    """expvar's role: stats dict + device-cost registry as one JSON
    object."""
    server.handle_packet(b"dbg.hits:1|c")
    server.flush_once()
    out = json.loads(_get(server, "/debug/vars").read())
    assert out["stats"]["flushes"] >= 1
    assert out["stats"]["metrics_processed"] == 1
    # the default cycle applies every class in one fused dispatch
    kernels = out["devicecost"]["kernels"]
    assert kernels["table.superbatch_apply"]["calls"] >= 1
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] >= 1
    assert out["devicecost"]["readback_bytes_total"] > 0
    assert "sent" in out["trace_client"]


def test_debug_flushes_empty_then_populated(server):
    assert json.loads(_get(server, "/debug/flushes").read()) == []
    server.handle_packet(b"dbg.hits:2|c")
    server.flush_once()
    recs = json.loads(_get(server, "/debug/flushes").read())
    assert len(recs) == 1
    rec = recs[0]
    assert rec["seq"] == 1
    for stage in ("snapshot", "dispatch", "device_wait",
                  "host_emit", "sink_flush"):
        assert rec["stages_ns"][stage] >= 0
    assert rec["forward_bytes"] == 0 and rec["imports"] == 0
    assert rec["readback_bytes"] > 0
    assert rec["tally"]["counters"] == 1
    assert rec["duration_ns"] > 0


def test_debug_flushes_n_param(server):
    """?n= bounds /debug/flushes to the newest N records (fleet
    scrapers must not pull 128 full records per poll); default stays
    the full ring."""
    for i in range(3):
        server.handle_packet(b"dbg.hits:1|c")
        server.flush_once()
    full = json.loads(_get(server, "/debug/flushes").read())
    assert len(full) == 3
    bounded = json.loads(_get(server, "/debug/flushes?n=2").read())
    assert len(bounded) == 2
    # newest-last, and the tail of the full dump
    assert [r["seq"] for r in bounded] == \
        [r["seq"] for r in full[-2:]]
    # a bogus n falls back to the full ring, never a 500
    assert len(json.loads(
        _get(server, "/debug/flushes?n=bogus").read())) == 3


def test_debug_ledger_n_param(server):
    """?n= bounds the /debug/ledger record dump; the imbalanced-seq
    index still covers the WHOLE ring so truncation can't hide an old
    imbalance."""
    for i in range(3):
        server.handle_packet(b"dbg.hits:1|c")
        server.flush_once()
    full = json.loads(_get(server, "/debug/ledger").read())
    assert full["intervals"] == 3
    assert full["returned"] == 3
    bounded = json.loads(_get(server, "/debug/ledger?n=1").read())
    assert bounded["intervals"] == 3
    assert bounded["returned"] == 1
    assert len(bounded["records"]) == 1
    assert bounded["records"][0]["seq"] == \
        full["records"][-1]["seq"]


def test_proxy_debug_surface():
    """The proxy's listener serves the same debughttp handlers
    (reference proxy.go:533-538 wires pprof + identity onto the proxy
    mux too)."""
    from veneur_tpu.core.config import ProxyConfig
    from veneur_tpu.core.proxy import ProxyServer
    proxy = ProxyServer(ProxyConfig(
        forward_address="127.0.0.1:9", http_address="127.0.0.1:0"))
    proxy.start()
    try:
        base = f"http://127.0.0.1:{proxy.http_port}"
        body = urllib.request.urlopen(
            base + "/debug/pprof", timeout=10).read()
        assert b"Thread" in body
        out = json.loads(urllib.request.urlopen(
            base + "/debug/vars", timeout=10).read())
        assert "stats" in out and "devicecost" in out
        assert out["destinations"] == 1
    finally:
        proxy.shutdown()
