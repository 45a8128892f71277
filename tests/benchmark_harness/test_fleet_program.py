"""The program against the plain reference of the topology
``fleet-global``, on seeded data at a small size, and the import
clients against the program's local side and the IDL: a global
``Server`` fed a fleet's wires over gRPC, in one burst and one by
one; a real local ``Server`` fed a client's raw samples; a client's
body parsed with the generated ``forward_pb2``."""

import threading

import numpy as np
import pytest

from bench_util import topology

from benchmark import fleet as fleet_mod
from benchmark import fleet_reference, harness, reference

pytest.importorskip("grpc")

CELL = "global-64-locals"
# the cell's own per-digest depth and set sizes, fewer series
SMALL = {"clients": 8, "locals_per_series": 4, "timers": 12, "sets": 4,
         "global_counters": 6}
SEED = 3400000007
_CARDS: dict = {}      # the first arrival order's cardinalities


@pytest.fixture(scope="module")
def made():
    c = harness.cell(CELL)
    fl = fleet_mod.Fleet({**c["traffic"], **SMALL}, SEED)
    rnd = fl.round(0)
    topo = topology("fleet-global")
    bodies = topo.Bodies(fl, c["config"]["sizes"]["compression"])
    pool = bodies.hashed_pool(rnd)
    return c, fl, rnd, topo, bodies, pool


def _global(topo):
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    sink = topo.lg.make_sink()
    glob = Server(read_config(data={
        "interval": "600s", "hostname": "fleet-test",
        "percentiles": [0.5, 0.9, 0.99], "statsd_listen_addresses": [],
        "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
        "tpu_histo_rows": 64, "tpu_set_rows": 16,
        "tpu_counter_rows": 1024, "tpu_gauge_rows": 1024}),
        extra_sinks=[sink])
    glob.start()
    return glob, sink


def _send(glob, wires, burst: bool) -> None:
    from veneur_tpu.forward.grpc_forward import ForwardClient
    clients = [ForwardClient(f"127.0.0.1:{glob.grpc_ports[0]}")
               for _ in wires]
    try:
        if burst:
            go = threading.Barrier(len(wires))

            def one(cl, body):
                go.wait()
                cl.send_wire(body)
            threads = [threading.Thread(target=one, args=a)
                       for a in zip(clients, wires)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        else:
            for cl, body in zip(clients, wires):
                cl.send_wire(body)
    finally:
        for cl in clients:
            cl.close()


@pytest.mark.parametrize("burst", [False, True],
                         ids=["one-by-one", "one-burst"])
def test_global_agrees_with_numpy_however_the_wires_arrive(made, burst):
    """Eight locals' wires (each series from four of them, a digest
    of 128 samples each: 512 a timer, so the global's digests are
    compressed) over real gRPC into a global ``Server``: its flush
    agrees with numpy within the configuration's limits, sums and
    cardinalities the same either way."""
    c, fl, rnd, topo, bodies, pool = made
    wires = [bodies.body(rnd, l, pool)[0] for l in range(fl.clients)]
    glob, sink = _global(topo)
    try:
        _send(glob, wires, burst)
        assert glob.stats["imports_received"] == sum(
            fl.rows_per_call(l) for l in range(fl.clients))
        glob.flush_once()
        rec = glob.flush_ring.records()[-1]
        got = reference.sink_values(sink.batches[-1][1].values())
    finally:
        glob.shutdown()
    ref = fleet_reference.interval(
        fl, [rnd], [(l, 0) for l in range(fl.clients)])
    res = fleet_reference.compare_interval(ref, got)
    limits = c["config"]["limits"]
    assert all(v <= limits[k] for k, v in res["numbers"].items()), res
    # compressed: 512 samples a timer into fewer centroids than that,
    # and still within a percent at every percentile
    assert max(res["p_rel_err"].values()) < 0.01
    assert all(len(xs) == 512 for xs in ref["timers"].values())
    # exact whatever the order: sums, and the unions' registers
    assert res["numbers"]["sums_off"] == 0
    cards = {k: got[k][0] for k in ref["sets"]}
    assert cards == _CARDS.setdefault("cards", cards)
    # the cycle's record: every wire, every plane, every centroid
    assert rec.imports == fl.clients
    assert rec.import_set_planes == fl.n["set"] * fl.per
    assert rec.import_centroids == fl.n["timer"] * fl.per * fl.samples
    assert rec.import_steps_flat + rec.import_steps_stack >= 1


def test_a_real_local_forwards_the_centroids_the_clients_assume(made):
    """A local ``Server`` fed one client's raw samples, members and
    increments as DogStatsD lines forwards what the client's body
    holds: each digest its sorted samples at weight 1, the same
    registers, the same counters, under the same identity bytes."""
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.forward.grpc_forward import encode_metric_list
    c, fl, rnd, _topo, bodies, pool = made
    local_id = 5
    j = fl.slot_of(local_id)
    lines = []
    for i in fl.series_of(local_id, "timer"):
        sfx = "|#" + ",".join(fl.tags["timer"][i])
        lines += [f"{fl.names['timer'][i]}:{v:.3f}|ms{sfx}"
                  for v in rnd["samples"][i, j]]
    for i in fl.series_of(local_id, "set"):
        sfx = "|#" + ",".join(fl.tags["set"][i])
        lines += [f"{fl.names['set'][i]}:{m.decode()}|s{sfx}"
                  for m in fleet_mod.member_bytes(rnd["members"][i, j])]
    for i in fl.series_of(local_id, "gcount"):
        sfx = "|#" + ",".join(fl.tags["gcount"][i]
                              + ("veneurglobalonly",))
        lines.append(f"{fl.names['gcount'][i]}:"
                     f"{rnd['increments'][i, j]}|c{sfx}")
    np.random.default_rng(1).shuffle(lines)
    local = Server(read_config(data={
        "interval": "600s", "hostname": "fleet-local",
        "statsd_listen_addresses": [],
        "forward_address": "127.0.0.1:1", "forward_use_grpc": True,
        "tpu_histo_rows": 64, "tpu_set_rows": 16,
        "tpu_counter_rows": 1024, "tpu_gauge_rows": 1024}))
    try:
        for k in range(0, len(lines), 20):
            local.handle_packet("\n".join(lines[k:k + 20]).encode())
        res = local.flusher.flush(local.table.swap())
    finally:
        local.shutdown()
    mine = {b.kind: b for b in bodies.blocks(rnd, local_id, pool)}
    theirs = {b.kind: b for b in res.forward.parts}
    assert set(theirs) == set(mine) == {"histo", "set", "counter"}

    def by_name(blk):
        return {m.name: i for i, m in enumerate(blk.metas)}
    for kind in mine:
        a, b = by_name(mine[kind]), by_name(theirs[kind])
        assert a.keys() == b.keys()
        for name, i in a.items():
            ma, mb = mine[kind].metas[i], theirs[kind].metas[b[name]]
            assert (ma.tags, ma.scope, ma.type) == (
                mb.tags, mb.scope, mb.type)
    a, b = by_name(mine["histo"]), by_name(theirs["histo"])
    for name, i in a.items():
        k = b[name]
        w = theirs["histo"].weights[k]
        live = w > 0
        assert live.sum() == fl.samples and (w[live] == 1.0).all()
        got = theirs["histo"].means[k][live]
        assert (np.diff(got) >= 0).all()            # sorted
        assert np.array_equal(got, mine["histo"].means[i])
        assert np.allclose(theirs["histo"].stats[k],
                           mine["histo"].stats[i], rtol=1e-5)
    a, b = by_name(mine["set"]), by_name(theirs["set"])
    for name, i in a.items():
        assert np.array_equal(mine["set"].regs[i],
                              theirs["set"].regs[b[name]])
    a, b = by_name(mine["counter"]), by_name(theirs["counter"])
    assert {n: mine["counter"].values[i] for n, i in a.items()} == {
        n: theirs["counter"].values[i] for n, i in b.items()}
    # and on the wire a series reads the same from either
    one = fl.names["timer"][int(fl.series_of(local_id, "timer")[0])]
    from veneur_tpu.core.flusher import ForwardBlock

    def wire_of(blk, k):
        return encode_metric_list([ForwardBlock(
            "histo", [blk.metas[k]], stats=mine["histo"].stats[:1],
            means=mine["histo"].means[:1],
            weights=mine["histo"].weights[:1])])[0]
    assert wire_of(mine["histo"], by_name(mine["histo"])[one]) \
        == wire_of(theirs["histo"], by_name(theirs["histo"])[one])


def test_a_clients_body_parsed_by_the_idl_gives_back_the_draws(made):
    """The encoder is the program's: the generated ``forward_pb2``
    reads a client's body back to the samples, members' registers and
    increments that were drawn."""
    from veneur_tpu.forward import hll_codec
    from veneur_tpu.forward.gen import forward_pb2, metric_pb2
    from veneur_tpu.utils import hashing
    c, fl, rnd, _topo, bodies, pool = made
    local_id = 2
    j = fl.slot_of(local_id)
    body, centroids = bodies.body(rnd, local_id, pool)
    ml = forward_pb2.MetricList.FromString(body)
    assert len(ml.metrics) == fl.rows_per_call(local_id)
    assert centroids == len(fl.series_of(local_id, "timer")) * fl.samples
    by_name = {m.name: m for m in ml.metrics}
    for i in fl.series_of(local_id, "timer"):
        m = by_name[fl.names["timer"][i]]
        assert tuple(m.tags) == fl.tags["timer"][i]
        assert (m.type, m.scope) == (metric_pb2.Timer, metric_pb2.Mixed)
        d = m.histogram.t_digest
        assert d.compression == c["config"]["sizes"]["compression"]
        assert [x.weight for x in d.main_centroids] == [1.0] * fl.samples
        want = np.sort(rnd["samples"][i, j]).astype(np.float32)
        assert np.array_equal(
            np.array([x.mean for x in d.main_centroids], np.float32),
            want)
        assert (d.min, d.max) == (want[0], want[-1])
    for i in fl.series_of(local_id, "set"):
        m = by_name[fl.names["set"][i]]
        assert m.type == metric_pb2.Set
        regs = hll_codec.decode(m.set.hyper_log_log)
        idx, rank = hashing.hash_members(
            fleet_mod.member_bytes(rnd["members"][i, j]))
        want = np.zeros(hll_codec.M, np.uint8)
        np.maximum.at(want, idx, rank.astype(np.uint8))
        assert np.array_equal(regs, want)
        assert (regs > 0).sum() > 0.95 * fl.members
    for i in fl.series_of(local_id, "gcount"):
        m = by_name[fl.names["gcount"][i]]
        assert (m.type, m.scope) == (metric_pb2.Counter,
                                     metric_pb2.Global)
        assert m.counter.value == rnd["increments"][i, j]
