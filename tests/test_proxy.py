"""Proxy tier tests: consistent-ring properties, discovery
keep-last-good refresh, and the in-process local -> proxy -> two
globals topology (the model of reference forward_grpc_test.go and
consul_discovery_test.go)."""

import json
import time

import pytest

pytest.importorskip("grpc")

from veneur_tpu.core.config import ProxyConfig, read_config
from veneur_tpu.core.proxy import ProxyServer
from veneur_tpu.core.server import Server
from veneur_tpu.forward.discovery import (ConsulDiscoverer,
                                          DestinationRing,
                                          StaticDiscoverer)
from veneur_tpu.forward.ring import ConsistentRing
from veneur_tpu.sinks.simple import CaptureSink


# ----------------------------------------------------------------------
# ring

def test_ring_stable_assignment():
    ring = ConsistentRing(["a:1", "b:1", "c:1"])
    keys = [f"metric-{i}" for i in range(1000)]
    first = [ring.get(k) for k in keys]
    assert first == [ring.get(k) for k in keys]
    # all members get a share
    assert set(first) == {"a:1", "b:1", "c:1"}


def test_ring_minimal_remap_on_member_change():
    keys = [f"metric-{i}" for i in range(2000)]
    r3 = ConsistentRing(["a:1", "b:1", "c:1"])
    before = {k: r3.get(k) for k in keys}
    r4 = ConsistentRing(["a:1", "b:1", "c:1", "d:1"])
    moved = sum(1 for k in keys if r4.get(k) != before[k])
    # adding 1 of 4 members should move roughly 1/4 of keys, far from
    # a full reshuffle
    assert 0.10 < moved / len(keys) < 0.45
    # keys that moved all moved TO the new member
    for k in keys:
        if r4.get(k) != before[k]:
            assert r4.get(k) == "d:1"


def test_ring_empty_raises():
    with pytest.raises(LookupError):
        ConsistentRing().get("x")


# ----------------------------------------------------------------------
# discovery

class _FlakyDiscoverer:
    def __init__(self):
        self.responses = []

    def get_destinations_for_service(self, service):
        r = self.responses.pop(0)
        if isinstance(r, Exception):
            raise r
        return r


def test_keep_last_good_on_error_and_empty():
    disc = _FlakyDiscoverer()
    disc.responses = [["a:1", "b:1"], RuntimeError("consul down"), [],
                      ["b:1", "c:1"]]
    ring = DestinationRing(disc, "svc")
    assert ring.refresh()
    assert ring.ring.members == ("a:1", "b:1")
    assert not ring.refresh()  # error: keep last good
    assert ring.ring.members == ("a:1", "b:1")
    assert not ring.refresh()  # empty: keep last good
    assert ring.ring.members == ("a:1", "b:1")
    assert ring.refresh()
    assert ring.ring.members == ("b:1", "c:1")
    assert ring.refresh_failures == 2


def test_consul_discoverer_parses_health_response():
    """Canned Consul health JSON through an injected opener — zero real
    Consul (the reference's RoundTripper fake,
    consul_discovery_test.go:14)."""
    payload = json.dumps([
        {"Node": {"Address": "10.0.0.1"},
         "Service": {"Address": "", "Port": 8128}},
        {"Node": {"Address": "10.0.0.2"},
         "Service": {"Address": "192.168.1.5", "Port": 8200}},
    ]).encode()

    class _Resp:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def read(self):
            return payload

    seen_urls = []

    def opener(url, timeout=None):
        seen_urls.append(url)
        return _Resp()

    d = ConsulDiscoverer("http://consul:8500", opener=opener)
    dests = d.get_destinations_for_service("veneur-global")
    assert dests == ["10.0.0.1:8128", "192.168.1.5:8200"]
    assert "health/service/veneur-global" in seen_urls[0]
    assert "passing" in seen_urls[0]


# ----------------------------------------------------------------------
# end-to-end: local -> proxy -> 2 globals

@pytest.fixture
def chain():
    servers = []
    caps = []
    for _ in range(2):
        cap = CaptureSink()
        g = Server(read_config(data={
            "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
            "interval": "10s"}), extra_sinks=[cap])
        g.start()
        servers.append(g)
        caps.append(cap)
    dests = ",".join(f"127.0.0.1:{g.grpc_ports[0]}" for g in servers)
    proxy = ProxyServer(ProxyConfig(
        forward_address=dests, grpc_address="127.0.0.1:0",
        http_address="127.0.0.1:0"))
    proxy.start()

    lcap = CaptureSink()
    local = Server(read_config(data={
        "statsd_listen_addresses": [],
        "forward_address": f"127.0.0.1:{proxy.grpc_port}",
        "forward_use_grpc": True, "interval": "10s"}),
        extra_sinks=[lcap])
    local.start()
    yield local, proxy, servers, caps
    local.shutdown()
    proxy.shutdown()
    for g in servers:
        g.shutdown()


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


def test_local_proxy_two_globals(chain):
    local, proxy, globals_, caps = chain
    for s in range(40):
        for v in range(20):
            local.handle_packet(
                f"px.lat:{v}|ms|#series:{s}".encode())
    local.flush_once()
    assert _wait(lambda: sum(g.stats.get("imports_received", 0)
                             for g in globals_) >= 40)
    for g in globals_:
        g.flush_once()
    # both globals got a share (consistent hashing spreads series)
    share = [g.stats["imports_received"] for g in globals_]
    assert all(s > 0 for s in share), share
    assert sum(share) == 40
    assert proxy.stats["metrics_routed"] == 40
    # no series double-delivered: total flushed percentile metrics ==
    # one per series.  Sink delivery is async (flush_once hands sink
    # emission to the pool and only waits within the interval budget;
    # a concurrent background-loop flush may also carry some of the
    # imports) — so wait for delivery rather than asserting
    # immediately.
    def _pct_metrics():
        return [m for c in caps for m in c.metrics
                if m.name == "px.lat.50percentile"]

    assert _wait(lambda: len(_pct_metrics()) >= 40), len(_pct_metrics())
    all_metrics = _pct_metrics()
    assert len(all_metrics) == 40
    series_seen = {t for m in all_metrics for t in m.tags}
    assert len(series_seen) == 40


def test_stable_routing_across_refresh(chain):
    """The same key routes to the same destination across refreshes
    with unchanged membership."""
    local, proxy, globals_, caps = chain
    key_dest = {f"k{i}": proxy.ring.get(f"k{i}") for i in range(50)}
    proxy.ring.refresh()
    assert {k: proxy.ring.get(k) for k in key_dest} == key_dest


def test_proxy_http_import_path(chain):
    import urllib.request
    local, proxy, globals_, caps = chain
    items = [{"kind": "counter", "name": f"hc{i}", "tags": [],
              "type": "counter", "scope": "", "value": 2.0}
             for i in range(10)]
    req = urllib.request.Request(
        f"http://127.0.0.1:{proxy.http_port}/import",
        data=json.dumps(items).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    resp = json.loads(urllib.request.urlopen(req).read())
    assert resp["accepted"] == 10
    # routed over HTTP to the globals' HTTP /import... the globals in
    # this fixture only listen on gRPC, so deliveries fail — but the
    # proxy must count routing and failures, not crash
    assert _wait(lambda: proxy.stats.get("metrics_routed", 0) >= 10)


def test_reference_wire_through_http_proxy():
    """A local emitting the REFERENCE JSONMetric wire
    (forward_json_schema: reference) -> proxy HTTP /import -> two
    globals: routing happens on the outer JSON fields, the opaque gob
    values pass through untouched, and each series lands on exactly
    one global with correct aggregates."""
    import numpy as np

    from veneur_tpu.protocol import dogstatsd as dsd

    servers, caps = [], []
    for _ in range(2):
        cap = CaptureSink()
        g = Server(read_config(data={
            "http_address": "127.0.0.1:0", "interval": "10s",
            "percentiles": [0.5]}), extra_sinks=[cap])
        g.start()
        servers.append(g)
        caps.append(cap)
    dests = ",".join(f"127.0.0.1:{g.http_port}" for g in servers)
    proxy = ProxyServer(ProxyConfig(
        forward_address=dests, http_address="127.0.0.1:0"))
    proxy.start()

    local = Server(read_config(data={
        "forward_address": f"http://127.0.0.1:{proxy.http_port}",
        "forward_json_schema": "reference", "interval": "10s"}),
        extra_sinks=[CaptureSink()])
    local.start()
    try:
        rng = np.random.default_rng(21)
        for i in range(20):
            for v in rng.gamma(2.0, 30.0, 50):
                local.table.ingest(dsd.parse_metric(
                    f"ref.lat.{i}:{v:.3f}|ms".encode()))
        local.flush_once()
        assert _wait(lambda: sum(
            g.stats.get("imports_received", 0) for g in servers) >= 20,
            timeout=15.0), [g.stats for g in servers]
        for g in servers:
            g.flush_once()
        got = {}
        for ci, c in enumerate(caps):
            for m in c.metrics:
                # only the series under test: a slow run lets the flush
                # ticker fire, which adds veneur.* self-telemetry
                # percentiles to the capture
                if (m.name.startswith("ref.lat.") and
                        m.name.endswith(".50percentile")):
                    got.setdefault(m.name, set()).add(ci)
        # every forwarded series produced percentiles on EXACTLY one
        # global (consistent-hash routing), and both globals got some
        assert len(got) == 20, sorted(got)
        assert all(len(v) == 1 for v in got.values())
        assert len({ci for v in got.values() for ci in v}) == 2
    finally:
        local.shutdown()
        proxy.shutdown()
        for g in servers:
            g.shutdown()


def test_proxy_full_config_surface_parses():
    """Every key of the reference's example_proxy.yaml parses
    (config_proxy.go, 23 keys)."""
    import os

    from veneur_tpu.core.config import ProxyConfig
    ref = "/root/reference/example_proxy.yaml"
    if not os.path.exists(ref):
        pytest.skip("reference tree not mounted")
    cfg = read_config(path=ref, strict=True, env={}, cls=ProxyConfig)
    assert cfg.consul_refresh_interval


def test_proxy_separate_grpc_ring():
    """grpc_forward_address routes gRPC-forwarded metrics on its own
    destination set while HTTP /import keeps the main ring
    (reference ForwardGRPCDestinations, proxy.go:138)."""
    from veneur_tpu.core.proxy import ProxyServer

    p = ProxyServer(ProxyConfig(
        forward_address="http-dest:8127",
        grpc_forward_address="grpc-dest:8129"))
    assert p.grpc_ring is not None
    assert p.ring.get("a|counter|") == "http-dest:8127"
    assert p.grpc_ring.get("a|counter|") == "grpc-dest:8129"


def test_proxy_trace_routing(tmp_path):
    """POST /spans bodies hash by trace id and re-POST flat span
    arrays to the trace destinations' /spans — the reference's exact
    wire (proxy.go:543-567 ProxyTraces)."""
    import http.server
    import threading
    import urllib.request

    got = []

    class TraceCap(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            got.append((self.path, json.loads(self.rfile.read(n))))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0),
                                            TraceCap)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    from veneur_tpu.core.proxy import ProxyServer
    p = ProxyServer(ProxyConfig(
        forward_address="unused:1",
        trace_address=f"127.0.0.1:{httpd.server_port}",
        http_address="127.0.0.1:0"))
    p.start()
    try:
        traces = [[{"trace_id": 7, "span_id": 1, "name": "x"}],
                  [{"trace_id": 9, "span_id": 2, "name": "y"}]]
        req = urllib.request.Request(
            f"http://127.0.0.1:{p.http_port}/spans",
            data=json.dumps(traces).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=5) as r:
            r.read()
        deadline = time.monotonic() + 5
        while not got and time.monotonic() < deadline:
            time.sleep(0.02)
        assert got and got[0][0] == "/spans"
        # flat span arrays (no per-trace nesting on the wire)
        delivered = [sp["trace_id"] for _, batch in got
                     for sp in batch]
        assert sorted(delivered) == [7, 9]
        assert all(isinstance(sp, dict) for _, b in got for sp in b)
    finally:
        p.shutdown()
        httpd.shutdown()


def test_proxy_ssf_self_telemetry(tmp_path):
    """ssf_destination_address: the proxy reports its own runtime
    metrics as SSF metric samples to the configured address."""
    import socket as _socket

    from veneur_tpu.core.proxy import ProxyServer
    from veneur_tpu.protocol.gen import ssf_pb2

    sock = _socket.socket(_socket.AF_INET, _socket.SOCK_DGRAM)
    sock.bind(("127.0.0.1", 0))
    sock.settimeout(5.0)
    port = sock.getsockname()[1]

    p = ProxyServer(ProxyConfig(
        forward_address="unused:1",
        ssf_destination_address=f"udp://127.0.0.1:{port}",
        runtime_metrics_interval="50ms"))
    p.start()
    try:
        data, _ = sock.recvfrom(65536)
        span = ssf_pb2.SSFSpan.FromString(data)
        names = {m.name for m in span.metrics}
        assert any(n.startswith("veneur_proxy.") for n in names)
    finally:
        p.shutdown()
        sock.close()


def test_proxy_trace_only_config_starts():
    """A trace-only proxy (no forward_address) is reference-valid
    (AcceptingForwards=false, proxy.go:131-139)."""
    from veneur_tpu.core.proxy import ProxyServer

    p = ProxyServer(ProxyConfig(trace_address="t:8126"))
    assert p.trace_ring is not None
    # metric routing drops-and-counts on the empty main ring
    p.route_json_items([{"name": "x", "type": "counter",
                         "tags": [], "value": 1.0}])
    assert p.stats["metrics_dropped"] == 1


# ----------------------------------------------------------------------
# end-to-end: emit wire -> local UDP -> proxy gRPC -> MESH-SHARDED
# global -> flush (VERDICT r3 item 5 / missing #3; the composition
# forward_grpc_test.go:19-57 exercises, with the mesh global from
# SURVEY §2.2 at the end of the chain)

def test_full_chain_emit_to_mesh_sharded_global():
    """Every tier composed over real loopback sockets, public entry
    points only: the emit CLI writes DogStatsD wire into the local's
    UDP socket, the local flush forwards digests/HLLs over gRPC to
    the proxy, the proxy hash-routes onto the mesh-sharded global
    (tpu_mesh_shards=4 over the 8 virtual devices), and the global's
    flush must produce percentiles and cardinalities matching exact
    values computed host-side."""
    import socket

    import numpy as np

    from veneur_tpu.cli import emit as emit_cli

    gcap = CaptureSink()
    g = Server(read_config(data={
        "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
        "tpu_mesh_shards": 4,
        "tpu_histo_rows": 256, "tpu_set_rows": 16,
        "percentiles": [0.5, 0.99],
        "interval": "10s"}), extra_sinks=[gcap])
    g.start()
    proxy = ProxyServer(ProxyConfig(
        forward_address=f"127.0.0.1:{g.grpc_ports[0]}",
        grpc_address="127.0.0.1:0"))
    proxy.start()
    lcap = CaptureSink()
    local = Server(read_config(data={
        "statsd_listen_addresses": ["udp://127.0.0.1:0"],
        "forward_address": f"127.0.0.1:{proxy.grpc_port}",
        "forward_use_grpc": True, "interval": "10s"}), extra_sinks=[lcap])
    local.start()
    try:
        port = local.statsd_ports[0]
        hp = f"udp://127.0.0.1:{port}"
        # the emit CLI generates the wire for one counter and one set
        # member (public entry point #1)
        assert emit_cli.main(["-hostport", hp, "-name", "chain.hits",
                              "-count", "7", "-tag", "env:e2e"]) == 0
        assert emit_cli.main(["-hostport", hp, "-name", "chain.uniq",
                              "-set", "member-from-cli"]) == 0
        # timer volume + set cardinality as raw DogStatsD wire (the
        # same bytes emit would build, batched for speed)
        rng = np.random.default_rng(5)
        vals = rng.gamma(2.0, 30.0, 2000)
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        addr = ("127.0.0.1", port)
        for i in range(0, 2000, 25):
            lines = [f"chain.lat:{v}|ms".encode()
                     for v in vals[i:i + 25]]
            sock.sendto(b"\n".join(lines), addr)
        for i in range(400):
            sock.sendto(f"chain.uniq:u{i}|s".encode(), addr)
        sock.close()
        # 2402 datagram-lines ride the kernel socket (2000 timers +
        # 400 sets + 2 from the CLI); wait for the reader threads to
        # drain them
        assert _wait(lambda: local.stats.get("metrics_processed", 0)
                     >= 2402), local.stats
        local.flush_once()
        assert _wait(lambda: g.stats.get("imports_received", 0) >= 1)
        g.flush_once()

        # local tier: counter value + timer count flush locally
        lm = {x.name: x for x in lcap.metrics}
        assert lm["chain.hits"].value == 7.0
        assert lm["chain.lat.count"].value == 2000.0
        assert "chain.lat.50percentile" not in lm  # global-only

        # global tier: merged digest percentiles + HLL cardinality
        gm = {x.name: x for x in gcap.metrics}
        for q, p in ((0.5, "50percentile"), (0.99, "99percentile")):
            exact = float(np.quantile(vals, q))
            got = gm[f"chain.lat.{p}"].value
            assert abs(got - exact) <= 0.02 * exact, (p, got, exact)
        # 400 raw members + 1 CLI member; p=14 HLL at this scale
        assert abs(gm["chain.uniq"].value - 401) <= 12
        assert proxy.stats["metrics_routed"] >= 2
    finally:
        local.shutdown()
        proxy.shutdown()
        g.shutdown()


def test_proxy_identity_and_pprof_surface(chain):
    """The proxy's HTTP listener serves the same identity + pprof
    endpoints as the server (reference proxy.go:533-538)."""
    import urllib.request
    from veneur_tpu import __version__
    _, proxy, _, _ = chain
    base = f"http://127.0.0.1:{proxy.http_port}"

    def get(path):
        return urllib.request.urlopen(base + path, timeout=5).read()

    assert get("/version").decode() == __version__
    assert get("/builddate") == b"dev"
    dump = get("/debug/pprof/goroutine").decode()
    assert "Thread" in dump
    heap = get("/debug/pprof/heap")
    assert b"tracemalloc" in heap or b"KiB" in heap
