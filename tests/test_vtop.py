"""vtop (cli/top.py): the one-screen fleet health view over
/debug/signals — one parallel scrape round against a real
4-local x 2-global cluster (bench's chaos topology), --json output,
table rendering, and dead-node rows."""

from __future__ import annotations

import json
import threading

import pytest

from veneur_tpu.cli import top
from veneur_tpu.core.config import read_config


@pytest.fixture(scope="module")
def cluster():
    """bench's --cluster topology, shrunk to smoke size: 4 locals
    (sharded gate on, forwarding over loopback gRPC) + 2 globals,
    each with a live /debug listener."""
    from veneur_tpu.core.server import Server
    globals_, locals_ = [], []
    for gi in range(2):
        g = Server(read_config(data={
            "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
            "http_address": "127.0.0.1:0",
            "interval": "10s", "hostname": f"vtop-g{gi}"}))
        g.start()
        globals_.append(g)
    addrs = ",".join(
        f"127.0.0.1:{g.grpc_ports[0]}" for g in globals_)
    for li in range(4):
        l = Server(read_config(data={
            "statsd_listen_addresses": [],
            "http_address": "127.0.0.1:0",
            "forward_address": addrs,
            "forward_use_grpc": True,
            "tpu_sharded_global": True,
            "interval": "10s", "hostname": f"vtop-l{li}"}))
        l.start()
        locals_.append(l)
    try:
        for li, l in enumerate(locals_):
            for i in range(8):
                l.handle_packet(f"vt{li}.lat.{i}:12|ms".encode())
            l.flush_once()
        for g in globals_:
            g.flush_once()
        yield locals_ + globals_
    finally:
        for srv in locals_ + globals_:
            srv.shutdown()


def _node_addrs(cluster):
    return [f"127.0.0.1:{s.http_port}" for s in cluster]


def test_one_scrape_round_covers_whole_fleet(cluster):
    """Acceptance pin: one scrape round renders every node's
    pressure/ledger/breaker state."""
    rows = top.scrape_fleet(_node_addrs(cluster))
    assert len(rows) == 6
    by_node = {r["node"]: r for r in rows}
    assert set(by_node) == {f"vtop-l{i}" for i in range(4)} | \
        {"vtop-g0", "vtop-g1"}
    for r in rows:
        assert not r.get("error"), r
        sig = r["signals"]
        # pressure, ledger, and breaker state present per node
        assert "pressure.level" in sig and "pressure.score" in sig
        assert sig["ledger.balanced"] == 1
        assert sig["ledger.imbalanced_total"] == 0
        for k in ("breaker.closed", "breaker.half_open",
                  "breaker.open"):
            assert k in sig
        assert r["rows"] >= 1
    for i in range(4):
        l = by_node[f"vtop-l{i}"]
        assert l["role"] == "local"
        # sharded forwarder: one closed breaker per global dest
        assert l["signals"]["breaker.closed"] == 2
        assert l["signals"]["breaker.open"] == 0
        assert l["signals"]["forward.destinations"] == 2
        assert l["signals"]["ingest.metrics_processed"] == 8
    for gname in ("vtop-g0", "vtop-g1"):
        assert by_node[gname]["role"] == "global"
    # the merge actually happened: the locals' forwarded digests
    # landed across the two globals
    imports = sum(by_node[g]["signals"]["ingest.imports_received"]
                  for g in ("vtop-g0", "vtop-g1"))
    assert imports == 4 * 8


def test_vtop_json_cli(cluster, capsys):
    rc = top.main(["--nodes", ",".join(_node_addrs(cluster)),
                   "--json"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert len(out["nodes"]) == 6
    assert {r["role"] for r in out["nodes"]} == {"local", "global"}
    for r in out["nodes"]:
        assert "signals" in r and "rates" in r and "addr" in r


def test_vtop_table_cli(cluster, capsys):
    rc = top.main(["--nodes", ",".join(_node_addrs(cluster))])
    assert rc == 0
    table = capsys.readouterr().out
    assert "NODE" in table and "BRK c/h/o" in table
    for i in range(4):
        assert f"vtop-l{i}" in table
    assert "vtop-g0" in table and "vtop-g1" in table
    # locals render their breaker map
    assert "2/0/0" in table


def test_dead_node_renders_down_row(cluster):
    addrs = _node_addrs(cluster)[:1] + ["127.0.0.1:1"]
    rows = top.scrape_fleet(addrs)
    assert not rows[0].get("error")
    assert rows[1]["error"]
    table = top.render_table(rows)
    assert "DOWN" in table
    rc = top.main(["--nodes", ",".join(addrs), "--json"])
    assert rc == 1  # nonzero when any node is down


def test_scrape_threads_do_not_outlive_round(cluster):
    top.scrape_fleet(_node_addrs(cluster))
    assert not [t for t in threading.enumerate()
                if t.name.startswith("vtop-scrape-")]


def test_render_table_proxy_row():
    rows = [{"addr": "p:1", "node": "px", "role": "proxy", "rows": 3,
             "signals": {"ledger.balanced": 1,
                         "ledger.imbalanced_total": 0,
                         "breaker.closed": 1, "breaker.half_open": 0,
                         "breaker.open": 0, "dest.queued": 4},
             "rates": {"route.routed": 1234.5,
                       "route.busy_dropped": 0.0}}]
    table = top.render_table(rows)
    assert "px" in table and "proxy" in table
    assert "1.2k" in table  # routed EWMA
    assert "1/0/0" in table


def test_debug_cluster_merges_peer_summaries(cluster):
    """The server-side fleet view: /debug/cluster on one node scrapes
    its configured peers' summaries (same payload vtop reads)."""
    import urllib.request
    from veneur_tpu.core.server import Server
    peer_addrs = ",".join(_node_addrs(cluster)[:2])
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "hostname": "vtop-hub", "http_address": "127.0.0.1:0",
        "tpu_cluster_peers": peer_addrs}))
    srv.start()
    try:
        srv.flush_once()
        out = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.http_port}/debug/cluster",
            timeout=10).read())
        assert out["node"] == "vtop-hub"
        assert set(out["peers"]) == set(peer_addrs.split(","))
        for summ in out["peers"].values():
            assert summ["stale"] is False
            assert "pressure.level" in summ["signals"]
    finally:
        srv.shutdown()
