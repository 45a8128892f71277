"""Cross-tier flush trace propagation.

One flush interval = one distributed trace: the local's forward
stage span stamps its (trace_id, span_id) onto the wire (HTTP
``X-Veneur-Trace`` header / gRPC ``veneur-trace-*`` metadata) and the
receiving tier parents its import span under it, so the global's
work renders inside the local's trace at ``/debug/trace/<id>``.
Propagation must be fail-open: wires without context still parse.
"""

from __future__ import annotations

import json
import socket
import time
import urllib.request

import pytest

from veneur_tpu.core.config import read_config
from veneur_tpu.core.server import Server
from veneur_tpu.forward import http_import
from veneur_tpu.sinks.simple import CaptureSink


@pytest.fixture
def make_server():
    servers = []

    def _make(**overrides):
        data = {"statsd_listen_addresses": ["udp://127.0.0.1:0"],
                "interval": "10s", "hostname": "trace-test",
                **overrides}
        cap = CaptureSink()
        s = Server(read_config(data=data), extra_sinks=[cap])
        s.start()
        servers.append(s)
        return s, cap

    yield _make
    for s in servers:
        s.shutdown()


def _send_udp(server, *lines: bytes):
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.sendto(b"\n".join(lines),
                ("127.0.0.1", server.statsd_ports[0]))
    sock.close()


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def _last_flush_trace(server) -> int:
    recs = server.flush_ring.records()
    assert recs
    return int(recs[-1].trace_id)


def _forward_span(server, tid, name="flush.forward"):
    spans = server.trace_index.get(tid)
    fwd = [s for s in spans if s["name"] == name]
    assert fwd, [s["name"] for s in spans]
    return fwd[-1]


def test_header_codec_roundtrip_and_fail_open():
    hdr = http_import.encode_trace_header(123, 456)
    assert hdr == "123:456"
    assert http_import.decode_trace_header(hdr) == (123, 456)
    for bad in (None, "", "junk", "1:2:3", "x:y", "-5:8", "0:0"):
        assert http_import.decode_trace_header(bad) == (0, 0)


def test_http_chain_single_stitched_trace(make_server):
    """Acceptance: a two-process local->global run produces ONE
    stitched trace — the global's import span parented under the
    local's forward span, same trace id on both ends."""
    glob, _ = make_server(http_address="127.0.0.1:0")
    local, _ = make_server(
        forward_address=f"http://127.0.0.1:{glob.http_port}",
        http_address="127.0.0.1:0")
    for v in range(50):
        _send_udp(local, f"tp.lat:{v}|ms".encode())
    assert _wait(lambda: local.stats.get("metrics_processed", 0) >= 50)
    local.flush_once()
    assert _wait(lambda: glob.stats.get("imports_received", 0) >= 1)

    tid = _last_flush_trace(local)
    assert tid
    fwd = _forward_span(local, tid)
    assert fwd["trace_id"] == str(tid)

    # the global indexed its import span under the SAME trace id,
    # parented under the local's forward span
    assert _wait(lambda: any(
        s["name"] == "import" for s in glob.trace_index.get(tid)))
    imp = [s for s in glob.trace_index.get(tid) if s["name"] == "import"]
    sp = imp[-1]
    assert sp["trace_id"] == str(tid)
    assert sp["parent_id"] == fwd["span_id"]
    assert sp["service"] == "veneur"
    assert sp["tags"]["protocol"] == "http"
    assert int(sp["tags"]["accepted"]) >= 1
    assert int(sp["tags"]["bytes"]) > 0

    # both ends serve the fragment over /debug/trace/<id>
    for srv, names in ((local, {"flush.forward"}), (glob, {"import"})):
        d = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.http_port}/debug/trace/{tid}",
            timeout=5).read())
        assert d["trace_id"] == str(tid)
        assert names <= {s["name"] for s in d["spans"]}
    # the id listing is the index into recent traces
    d = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{local.http_port}/debug/trace",
        timeout=5).read())
    assert str(tid) in d["trace_ids"]
    # /debug/flushes links the ring entry to the trace
    d = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{local.http_port}/debug/flushes",
        timeout=5).read())
    assert any(r.get("trace_id") == str(tid) for r in d)


IMPORT_STEPS = ("import.decode", "import.lock_wait", "import.apply",
                "import.device_step")
# the three parts of ``import.apply``, children of its span
APPLY_STEPS = ("import.apply.resolve", "import.apply.digests",
               "import.apply.sets")


def test_grpc_chain_single_stitched_trace(make_server):
    """The gRPC forward is split from inside: ``forward.encode`` and
    ``forward.send`` under the local's ``forward`` stage, and on the
    global a real ``import`` span tree under the local's
    ``forward.send``, whose durations land in the global's next
    flush record."""
    pytest.importorskip("grpc")
    glob, _ = make_server(
        grpc_listen_addresses=["tcp://127.0.0.1:0"],
        statsd_listen_addresses=[], http_address="127.0.0.1:0")
    local, _ = make_server(
        forward_address=f"127.0.0.1:{glob.grpc_ports[0]}",
        forward_use_grpc=True)
    for v in range(50):
        _send_udp(local, f"tg.lat:{v}|ms".encode())
    assert _wait(lambda: local.stats.get("metrics_processed", 0) >= 50)
    res = local.flush_once()
    assert _wait(lambda: glob.stats.get("imports_received", 0) >= 1)

    # the local's record: the two halves, inside the forward stage
    rec = local.flush_ring.records()[-1]
    enc, snd = rec.stages["forward.encode"], rec.stages["forward.send"]
    assert enc > 0 and snd > 0
    assert enc + snd <= rec.stages["forward"]
    # (c) the ledger's wire bytes are the serialized body's length
    from veneur_tpu.forward.grpc_forward import rows_to_metric_list
    body = rows_to_metric_list(
        res.forward, float(local.config.tpu_compression)
    ).SerializeToString()
    assert rec.forward_bytes == len(body) > 0
    led = local.ledger.records()[-1].to_dict()
    assert led["forward_wire"] == {**led["forward_wire"],
                                   "rows": len(res.forward),
                                   "bytes": len(body)}

    tid = _last_flush_trace(local)
    fwd = _forward_span(local, tid)
    halves = {s["name"]: s for s in local.trace_index.get(tid)
              if s["name"].startswith("flush.forward.")}
    assert set(halves) == {"flush.forward.encode", "flush.forward.send"}
    assert all(h["parent_id"] == fwd["span_id"] for h in halves.values())
    send = halves["flush.forward.send"]
    assert send["tags"]["bytes"] == str(len(body))
    assert halves["flush.forward.encode"]["tags"]["rows"] == str(
        len(res.forward))

    # the global's tree, as /debug/trace/<id> serves it: import under
    # the local's forward.send, its steps under import, all with a
    # real extent
    assert len(glob.trace_index.get(tid)) == 5 + len(APPLY_STEPS)
    d = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{glob.http_port}/debug/trace/{tid}",
        timeout=5).read())
    spans = {s["name"]: s for s in d["spans"]}
    imp = spans["import"]
    assert imp["parent_id"] == send["span_id"]
    assert imp["end_ns"] > imp["start_ns"]
    assert imp["tags"]["protocol"] == "grpc"
    assert imp["tags"]["bytes"] == str(len(body))
    assert int(imp["tags"]["accepted"]) == len(res.forward)
    for step in IMPORT_STEPS:
        assert spans[step]["parent_id"] == imp["span_id"]
        assert imp["start_ns"] <= spans[step]["start_ns"]
        assert spans[step]["end_ns"] <= imp["end_ns"]
    app = spans["import.apply"]
    for step in APPLY_STEPS:
        assert spans[step]["parent_id"] == app["span_id"]
        assert app["start_ns"] <= spans[step]["start_ns"]
        assert spans[step]["end_ns"] <= app["end_ns"]
    # the import ran inside the local's send, on one wall clock
    assert send["start_ns"] <= imp["start_ns"]
    assert imp["end_ns"] <= send["end_ns"]

    # into the ring: the global's next cycle takes what the handler
    # cost, once
    glob.flush_once()
    grec = glob.flush_ring.records()[-1]
    assert grec.imports == 1
    steps = sum(grec.stages[k] for k in IMPORT_STEPS)
    assert grec.stages["import"] >= steps > 0
    assert grec.stages["import"] <= snd
    # the fold's three parts inside ``import.apply``, and what the
    # interval's one wire came to: one digest fold of its centroids,
    # no sketch
    parts = sum(grec.stages[k] for k in APPLY_STEPS)
    assert grec.stages["import.apply"] >= parts > 0
    assert grec.import_steps_flat + grec.import_steps_stack == 1
    assert (grec.import_centroids, grec.import_spilled_centroids,
            grec.import_set_planes) == (50, 0, 0)
    assert json.loads(glob.flush_ring.to_json(1))[0][
        "import_centroids"] == 50
    glob.flush_once()
    grec = glob.flush_ring.records()[-1]
    assert grec.imports == 0 and "import" not in grec.stages
    assert (grec.import_steps_flat, grec.import_steps_stack,
            grec.import_centroids, grec.import_set_planes) == (
        0, 0, 0, 0)


def test_forward_encode_span_counts_centroids(make_server):
    """A flush of the benchmark cell's forward (1,000 timers of 100
    samples, 1,000 global-only counters, 100 sets) through two
    ``Server``s: the ``forward.encode`` span says how many rows and
    how many live centroids its time was spent on."""
    pytest.importorskip("grpc")
    glob, _ = make_server(
        grpc_listen_addresses=["tcp://127.0.0.1:0"],
        statsd_listen_addresses=[])
    local, _ = make_server(
        forward_address=f"127.0.0.1:{glob.grpc_ports[0]}",
        forward_use_grpc=True)
    for i in range(1000):
        local.handle_packet("\n".join(
            [f"tc.lat.{i}:{v}.25|ms" for v in range(100)]
            + [f"tc.hits.{i}:3|c|#veneurglobalonly"]).encode())
    for i in range(100):
        local.handle_packet("\n".join(
            f"tc.users.{i}:u{v}|s" for v in range(50)).encode())
    assert local.stats["metrics_processed"] == 106_000
    res = local.flush_once()
    assert len(res.forward) == 2100
    assert _wait(lambda: glob.stats.get("imports_received", 0) >= 2100)

    tid = _last_flush_trace(local)
    enc = _forward_span(local, tid, "flush.forward.encode")
    assert enc["tags"]["rows"] == "2100"
    assert enc["tags"]["centroids"] == "100000"
    assert int(enc["tags"]["bytes"]) > 100_000 * 20


ROW_TAGS = ("rows_histo", "rows_sets", "rows_scalars")


def test_row_counts_by_class_on_encode_import_and_swap(make_server):
    """All four kinds through a local -> global flush: the three row
    counts of ``forward.encode`` add up to its ``rows`` and equal
    those of the global's ``import.apply``; ``flush.swap_apply``
    counts what the interval staged; a flush with nothing to forward
    has no such span."""
    pytest.importorskip("grpc")
    glob, _ = make_server(
        grpc_listen_addresses=["tcp://127.0.0.1:0"],
        statsd_listen_addresses=[])
    local, _ = make_server(
        forward_address=f"127.0.0.1:{glob.grpc_ports[0]}",
        forward_use_grpc=True)
    for i in range(7):                         # 7 timers x 12
        local.handle_packet("\n".join(
            f"rc.lat.{i}:{v}.5|ms" for v in range(12)).encode())
    for i in range(5):                         # 5 sets x 9 members
        local.handle_packet("\n".join(
            f"rc.users.{i}:u{v}|s" for v in range(9)).encode())
    local.handle_packet("\n".join(             # 3 + 4 counters, 6 gauges
        [f"rc.g.{i}:2|c|#veneurglobalonly" for i in range(3)]
        + [f"rc.c.{i}:2|c" for i in range(4)]
        + [f"rc.v.{i}:{i}|g" for i in range(6)]).encode())
    res = local.flush_once()
    assert len(res.forward) == 7 + 5 + 3
    assert _wait(lambda: glob.stats.get("imports_received", 0) >= 15)

    tid = _last_flush_trace(local)
    enc = _forward_span(local, tid, "flush.forward.encode")["tags"]
    assert [enc[k] for k in ROW_TAGS] == ["7", "5", "3"]
    assert sum(int(enc[k]) for k in ROW_TAGS) == int(enc["rows"]) == 15
    assert enc["centroids"] == str(7 * 12)
    assert _wait(lambda: any(s["name"] == "import.apply"
                             for s in glob.trace_index.get(tid)))
    app = _forward_span(glob, tid, "import.apply")["tags"]
    assert {k: app[k] for k in ROW_TAGS + ("centroids",)} == {
        k: enc[k] for k in ROW_TAGS + ("centroids",)}

    # what the swap was about to apply: the test's lines (a server's
    # first interval holds none of its own telemetry yet)
    swap = _forward_span(local, tid, "flush.swap_apply")["tags"]
    assert {k: int(v) for k, v in swap.items() if k != "stage"
            and not k.startswith(("veneur.", "gc_"))} == {
        "histo_samples": 7 * 12, "histo_rows": 7,
        "set_members": 5 * 9, "set_rows": 5,
        "scalar_rows": 3 + 4 + 6}
    # the global's swap holds the imported centroids and rows
    glob.flush_once()
    gswap = _forward_span(glob, _last_flush_trace(glob),
                          "flush.swap_apply")["tags"]
    assert int(gswap["histo_samples"]) >= 7 * 12
    assert int(gswap["histo_rows"]) >= 7
    assert int(gswap["set_rows"]) >= 5
    # and its record what the interval's imports came to: as many
    # register planes as sketches were sent
    grec = glob.flush_ring.records()[-1]
    assert (grec.imports, grec.import_set_planes,
            grec.import_centroids) == (1, 5, 7 * 12)
    # all of them dense, so all in the one native pass: none went
    # through the per-item decode, by the record and by the span
    assert grec.import_set_planes_loose == 0
    assert json.loads(glob.flush_ring.to_json(1))[0][
        "import_set_planes_loose"] == 0
    sets = _forward_span(glob, tid, "import.apply.sets")["tags"]
    assert (sets["planes"], sets["planes_loose"]) == ("5", "0")
    assert grec.import_steps_flat + grec.import_steps_stack >= 1
    assert all(grec.stages[k] > 0 for k in APPLY_STEPS)

    # nothing to forward: no forward span, so no counts
    local.handle_packet(b"rc.c.0:1|c")
    assert local.flush_once().forward == []
    names = {s["name"] for s in
             local.trace_index.get(_last_flush_trace(local))}
    assert "flush.swap_apply" in names
    assert not any(n.startswith("flush.forward") for n in names)


def test_proxy_hop_parents_both_sides(make_server):
    """local -> proxy (gRPC) -> global: the proxy's route span
    parents under the local's forward.send span, and the global's import
    span parents under the proxy hop — one three-process tree."""
    pytest.importorskip("grpc")
    from veneur_tpu.core.config import ProxyConfig
    from veneur_tpu.core.proxy import ProxyServer

    glob, _ = make_server(
        grpc_listen_addresses=["tcp://127.0.0.1:0"],
        statsd_listen_addresses=[])
    proxy = ProxyServer(ProxyConfig(
        forward_address=f"127.0.0.1:{glob.grpc_ports[0]}",
        grpc_address="127.0.0.1:0", http_address="127.0.0.1:0"))
    proxy.start()
    try:
        local, _ = make_server(
            forward_address=f"127.0.0.1:{proxy.grpc_port}",
            forward_use_grpc=True)
        for v in range(30):
            _send_udp(local, f"pxt.lat:{v}|ms".encode())
        assert _wait(
            lambda: local.stats.get("metrics_processed", 0) >= 30)
        local.flush_once()
        assert _wait(lambda: glob.stats.get("imports_received", 0) >= 1)

        tid = _last_flush_trace(local)
        # the gRPC wire carries the ids of the span that ships it
        fwd = _forward_span(local, tid, "flush.forward.send")
        assert _wait(lambda: proxy.trace_index.get(tid))
        route = [s for s in proxy.trace_index.get(tid)
                 if s["name"] == "proxy.route"]
        assert route, proxy.trace_index.get(tid)
        rsp = route[-1]
        assert rsp["parent_id"] == fwd["span_id"]
        assert rsp["service"] == "veneur-proxy"

        assert _wait(lambda: any(
            s["name"] == "import" for s in glob.trace_index.get(tid)))
        imp = [s for s in glob.trace_index.get(tid)
               if s["name"] == "import"]
        # the global hangs under the PROXY hop, not the local directly
        assert imp[-1]["parent_id"] == rsp["span_id"]

        # the proxy serves its fragment at /debug/trace/<id> too
        d = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{proxy.http_port}/debug/trace/{tid}",
            timeout=5).read())
        assert any(s["name"] == "proxy.route" for s in d["spans"])
    finally:
        proxy.shutdown()


def test_old_peer_wire_without_header_fail_open(make_server):
    """An /import POST with no X-Veneur-Trace (or a garbage one)
    parses exactly as before: accepted, no import span recorded."""
    glob, _ = make_server(http_address="127.0.0.1:0")
    items = [{"kind": "counter", "name": "old.peer", "tags": [],
              "value": 3.0}]
    for hdr in (None, "garbage", "1:2:3"):
        headers = {"Content-Type": "application/json"}
        if hdr is not None:
            headers[http_import.TRACE_HEADER] = hdr
        req = urllib.request.Request(
            f"http://127.0.0.1:{glob.http_port}/import",
            data=json.dumps(items).encode(), headers=headers,
            method="POST")
        resp = json.loads(urllib.request.urlopen(req, timeout=5).read())
        assert resp["accepted"] == 1
    assert glob.trace_index.trace_ids() == []


def test_propagation_gate_disables_stamping(make_server):
    glob, _ = make_server(http_address="127.0.0.1:0")
    local, _ = make_server(
        forward_address=f"http://127.0.0.1:{glob.http_port}",
        tpu_trace_propagation=False)
    _send_udp(local, b"gate.lat:5|ms")
    assert _wait(lambda: local.stats.get("metrics_processed", 0) >= 1)
    local.flush_once()
    assert _wait(lambda: glob.stats.get("imports_received", 0) >= 1)
    tid = _last_flush_trace(local)
    # wire carried no context: the global never saw this trace
    time.sleep(0.2)
    assert glob.trace_index.get(tid) == []


def test_import_span_records_drops(make_server):
    """The import span's tags carry the accept/drop split — the
    trace view shows WHERE an interval lost samples."""
    import base64
    glob, _ = make_server(http_address="127.0.0.1:0")
    items = [
        {"kind": "counter", "name": "ok", "tags": [], "value": 1.0},
        {"kind": "histo", "name": "bad", "tags": [], "scope": "",
         "type": "timer", "stats": [1, 2, 3],
         "means": base64.b64encode(b"\x00" * 8).decode(),
         "weights": base64.b64encode(b"\x00" * 8).decode()},
    ]
    req = urllib.request.Request(
        f"http://127.0.0.1:{glob.http_port}/import",
        data=json.dumps(items).encode(),
        headers={"Content-Type": "application/json",
                 http_import.TRACE_HEADER: "777000111:555000999"},
        method="POST")
    resp = json.loads(urllib.request.urlopen(req, timeout=5).read())
    assert resp["accepted"] == 1
    spans = {s["name"]: s for s in glob.trace_index.get(777000111)}
    assert set(spans) == {"import", *IMPORT_STEPS}
    sp = spans["import"]
    assert sp["parent_id"] == "555000999"
    assert sp["end_ns"] > sp["start_ns"]
    assert sp["tags"]["accepted"] == "1"
    assert sp["tags"]["dropped"] == "1"
