"""Multi-device sharded aggregation tests on the virtual 8-device CPU
mesh (conftest forces ``xla_force_host_platform_device_count=8``) —
the in-process stand-in for a v5e-8 slice, mirroring the reference's
simulate-the-cluster-in-one-process strategy (forward_test.go:18).
"""

import jax
import numpy as np
import pytest

from veneur_tpu.parallel import (ShardedAggregator, ShardedConfig,
                                 make_mesh)
from veneur_tpu.utils import hashing


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8, "conftest must provide 8 devices"
    return make_mesh(jax.devices())


@pytest.fixture(scope="module")
def cfg():
    return ShardedConfig(rows=32, set_rows=8, slots=32, batch=256)


def test_mesh_shape(mesh):
    assert dict(mesh.shape) == {"shard": 4, "series": 2}


def test_counter_psum_across_shards(mesh, cfg):
    agg = ShardedAggregator(mesh, cfg)
    exact = np.zeros(cfg.rows)
    rng = np.random.default_rng(1)
    for shard in range(agg.n_shard):
        rows = rng.integers(0, cfg.rows, 100, dtype=np.int32)
        vals = rng.normal(2, 1, 100).astype(np.float32)
        np.add.at(exact, rows, vals)
        agg.stage(shard, counter_rows=rows, counter_vals=vals,
                  counter_wts=np.ones(100, np.float32))
    agg.step()
    out = agg.flush()
    np.testing.assert_allclose(np.asarray(out["counters"]), exact,
                               rtol=1e-4, atol=1e-3)


def test_counter_rate_correction(mesh, cfg):
    agg = ShardedAggregator(mesh, cfg)
    agg.stage(0, counter_rows=[3], counter_vals=[5.0],
              counter_wts=[10.0])  # 1/rate = 10
    agg.step()
    out = agg.flush()
    assert float(np.asarray(out["counters"])[3]) == pytest.approx(50.0)


def test_gauge_last_write_wins_across_shards(mesh, cfg):
    """The globally-latest ticket wins even when earlier and later
    writes land on different shards."""
    agg = ShardedAggregator(mesh, cfg)
    t1 = agg.next_ticket(1)
    t2 = agg.next_ticket(1)
    # later ticket staged on a DIFFERENT shard than the earlier one
    agg.stage(1, gauge_rows=[7], gauge_vals=[111.0], gauge_ticket=t2)
    agg.stage(0, gauge_rows=[7], gauge_vals=[5.0], gauge_ticket=t1)
    agg.stage(2, gauge_rows=[9], gauge_vals=[42.0],
              gauge_ticket=agg.next_ticket(1))
    agg.step()
    out = agg.flush()
    g = np.asarray(out["gauges"])
    assert g[7] == 111.0
    assert g[9] == 42.0


def test_histo_merge_and_quantiles(mesh, cfg):
    """Samples of one series scattered over all shards: merged digest
    quantiles must track the exact pooled quantiles."""
    agg = ShardedAggregator(mesh, cfg)
    rng = np.random.default_rng(3)
    all_vals = []
    for shard in range(agg.n_shard):
        vals = rng.gamma(3.0, 2.0, 200).astype(np.float32)
        all_vals.append(vals)
        agg.stage(shard,
                  histo_rows=np.zeros(200, np.int32),
                  histo_vals=vals,
                  histo_wts=np.ones(200, np.float32))
        agg.step()  # interleave steps: state accumulates across calls
    out = agg.flush(qs=(0.5, 0.9, 0.99))
    pooled = np.concatenate(all_vals)
    stats = np.asarray(out["histo_stats"])
    assert stats[0, 0] == pytest.approx(len(pooled))
    assert stats[0, 1] == pytest.approx(pooled.min(), rel=1e-5)
    assert stats[0, 2] == pytest.approx(pooled.max(), rel=1e-5)
    assert stats[0, 3] == pytest.approx(pooled.sum(), rel=1e-4)
    q = np.asarray(out["quantiles"])[0]
    for i, p in enumerate((0.5, 0.9, 0.99)):
        exact = np.quantile(pooled, p)
        assert q[i] == pytest.approx(exact, rel=0.05), (p, q[i], exact)


def test_hll_union_across_shards(mesh, cfg):
    """Same members inserted on different shards must not double-count
    (register max is a union, not a sum)."""
    agg = ShardedAggregator(mesh, cfg)
    members = [f"user-{i}".encode() for i in range(500)]
    for shard in range(agg.n_shard):
        # every shard sees an overlapping window of the member set
        window = members[shard * 100:shard * 100 + 200]
        idx, rank = hashing.hash_members(window)
        agg.stage(shard,
                  set_rows=np.zeros(len(window), np.int32),
                  set_idx=idx.astype(np.int32),
                  set_rank=rank.astype(np.int32))
    agg.step()
    out = agg.flush()
    est = float(np.asarray(out["hll_estimate"])[0])
    # union of the 4 windows = members[0:500]
    assert est == pytest.approx(500, rel=0.1)


def test_merge_reduces_no_plane_narrower_than_32_bits(mesh, cfg):
    """A TPU all-reduces a u8 plane four rows to a 32-bit word and
    keeps one shard's word whole (PR 22: the register pmax read
    cardinalities 3-10 % low on four chips).  The CPU mesh reduces
    u8 correctly and cannot show it, so hold the merge step's
    reducing collectives to 32-bit operands."""
    from veneur_tpu.parallel import sharded

    def reductions(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name.startswith(("psum", "pmax", "pmin")):
                yield eqn
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from reductions(inner)

    state = sharded.empty_state(mesh, cfg)
    found = list(reductions(jax.make_jaxpr(
        sharded.make_merge_step(mesh, cfg))(state).jaxpr))
    assert len(found) >= 9     # 3 counter/gauge, 5 stats, 1 registers
    narrow = [(e.primitive.name, v.aval.dtype) for e in found
              for v in e.invars if v.aval.dtype.itemsize < 4]
    assert not narrow, narrow


def test_row_sharding_routes_all_rows(mesh, cfg):
    """Rows across the whole table land in the right series block."""
    agg = ShardedAggregator(mesh, cfg)
    rows = np.arange(cfg.rows, dtype=np.int32)
    agg.stage(0, counter_rows=rows,
              counter_vals=np.ones(cfg.rows, np.float32),
              counter_wts=np.ones(cfg.rows, np.float32))
    agg.step()
    out = agg.flush()
    np.testing.assert_allclose(np.asarray(out["counters"]),
                               np.ones(cfg.rows))


def test_staging_overflow_chunks(mesh, cfg):
    """Past-batch staging splits across update calls (and the counter
    pre-combine collapses same-row samples first) — never raises."""
    agg = ShardedAggregator(mesh, cfg)
    n = cfg.batch + 1
    agg.stage(0, counter_rows=np.zeros(n, np.int32),
              counter_vals=np.ones(n, np.float32),
              counter_wts=np.ones(n, np.float32))
    agg.step()
    out = agg.flush()
    assert float(np.asarray(out["counters"])[0]) == n


def test_dryrun_multichip_entry():
    """The driver-facing dryrun must pass end-to-end."""
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_entry_compiles_single_device():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert len(out) == 7


@pytest.mark.parametrize("n_devices", [1, 2, 4])
def test_small_meshes_aggregate_correctly(n_devices):
    """Degenerate and small meshes (single chip, a 2-chip board) must
    produce the same exact counts as the 8-device mesh — shape
    assumptions about the shard axis tend to break exactly here."""
    devs = jax.devices()[:n_devices]
    mesh = make_mesh(devs)
    cfg = ShardedConfig(rows=16, set_rows=4, slots=16, batch=64)
    agg = ShardedAggregator(mesh, cfg)
    rng = np.random.default_rng(n_devices)
    exact = np.zeros(cfg.rows)
    for shard in range(agg.n_shard):
        rows = rng.integers(0, cfg.rows, 40, dtype=np.int32)
        vals = rng.normal(2.0, 0.5, 40).astype(np.float32)
        np.add.at(exact, rows, vals)
        agg.stage(shard, counter_rows=rows, counter_vals=vals,
                  counter_wts=np.ones(40, np.float32))
    agg.step()
    out = agg.flush(qs=(0.5,))
    np.testing.assert_allclose(np.asarray(out["counters"]), exact,
                               rtol=1e-4, atol=1e-3)


def test_sharded_table_server_path_production_rows():
    """VERDICT r2 item 5: the mesh global node at production shapes —
    rows=4096 on the full 8-device mesh, driven through the ordinary
    Server/Flusher path (tpu_mesh_shards), with gRPC-style imports
    landing next to raw ingest; values verified against exact."""
    import numpy as np

    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.protocol import dogstatsd as dsd
    from veneur_tpu.sinks.simple import CaptureSink

    cap = CaptureSink()
    srv = Server(read_config(data={
        "interval": "10s",
        "tpu_mesh_shards": 4,
        "tpu_histo_rows": 4096, "tpu_set_rows": 64,
        "percentiles": [0.5, 0.99]}), extra_sinks=[cap])
    try:
        rng = np.random.default_rng(31)
        # 64 series x 256 samples of raw ingest across the mesh
        per_series = {}
        for s in range(64):
            vals = rng.gamma(2.0, 30.0, 256)
            per_series[s] = vals
            for v in vals:
                srv.table.ingest(dsd.Sample(
                    name=f"lat.{s}", type=dsd.TIMER, value=float(v)))
        # plus a forwarded digest import for one series (the global
        # tier's import plane on the same table)
        extra = rng.gamma(2.0, 30.0, 500).astype(np.float32)
        stats = np.asarray(
            [len(extra), extra.min(), extra.max(), extra.sum(),
             (1.0 / extra).sum()], np.float32)
        assert srv.table.import_histo(
            "lat.0", dsd.TIMER, (), stats, extra,
            np.ones(len(extra), np.float32))
        per_series[0] = np.concatenate([per_series[0], extra])
        srv.flush_once()
    finally:
        srv.shutdown()
    m = {x.name: x for x in cap.metrics}
    errs = []
    for s, vals in per_series.items():
        exact = float(np.quantile(vals, 0.99))
        got = m[f"lat.{s}.99percentile"].value
        errs.append(abs(got - exact) / exact)
        # a mixed-scope row's count is what this node sampled itself
        # (samplers.go LocalWeight): the forwarded digest's 500 are
        # in the percentiles and in the import plane, as on one chip
        assert m[f"lat.{s}.count"].value == pytest.approx(256, rel=1e-5)
    assert max(errs) < 0.02, max(errs)


def test_sharded_aggregator_chunks_oversized_batches():
    """Staged batches past cfg.batch chunk across update calls
    instead of raising (VERDICT r2: 'staged-overflow raises instead
    of chunking')."""
    import numpy as np

    from veneur_tpu.parallel import (ShardedAggregator, ShardedConfig,
                                     make_mesh)

    mesh = make_mesh(jax.devices()[:4])
    cfg = ShardedConfig(rows=64, set_rows=16, slots=32, batch=256)
    agg = ShardedAggregator(mesh, cfg)
    n = 2000  # ~8x the batch width
    rng = np.random.default_rng(3)
    rows = rng.integers(0, cfg.rows, n).astype(np.int32)
    vals = rng.normal(5.0, 1.0, n).astype(np.float32)
    agg.stage(0, counter_rows=rows, counter_vals=vals,
              counter_wts=np.ones(n, np.float32),
              histo_rows=rows, histo_vals=vals,
              histo_wts=np.ones(n, np.float32))
    agg.step()  # must not raise
    out = agg.flush()
    exact = np.zeros(cfg.rows)
    np.add.at(exact, rows, vals)
    np.testing.assert_allclose(np.asarray(out["counters"]), exact,
                               rtol=1e-4, atol=1e-3)
    stats = np.asarray(out["histo_stats"])
    assert stats[:, 0].sum() == pytest.approx(n)


def test_sharded_swap_resets_interval():
    """swap() merges and RESETS the partial state: the next interval
    starts from zeros (the single-chip double-buffer contract)."""
    import numpy as np

    from veneur_tpu.parallel import (ShardedAggregator, ShardedConfig,
                                     make_mesh)

    mesh = make_mesh(jax.devices()[:4])
    agg = ShardedAggregator(mesh, ShardedConfig(rows=32, set_rows=8,
                                                slots=16, batch=128))
    agg.stage(0, counter_rows=[3], counter_vals=[7.0],
              counter_wts=[1.0])
    merged = agg.swap()
    assert float(np.asarray(merged["counters"])[3]) == 7.0
    merged2 = agg.swap()
    assert float(np.asarray(merged2["counters"]).sum()) == 0.0


def test_sharded_import_keeps_the_forwarded_statistics_exact():
    """A forwarded digest's statistics land exact in the import
    plane, whatever its centroids say (one wide centroid's mean
    wildly misstates sum(1/x)), and the plane of what this node
    sampled itself stays empty: a global emits no aggregate of a
    mixed-scope row it did not sample, as on one chip."""
    import numpy as np

    from veneur_tpu.core.flusher import Flusher
    from veneur_tpu.ops import segment
    from veneur_tpu.parallel import (ShardedConfig, ShardedTable,
                                     make_mesh)
    from veneur_tpu.protocol import dogstatsd as dsd

    vals = np.asarray([1.0, 100.0, 1.0, 100.0, 2.0], np.float32)
    stats = np.asarray([len(vals), vals.min(), vals.max(),
                        vals.sum(), (1.0 / vals).sum()], np.float32)
    # one wide centroid (as a lossy local might forward)
    means = np.asarray([float(vals.mean())], np.float32)
    weights = np.asarray([float(len(vals))], np.float32)

    mesh = make_mesh(jax.devices()[:4])
    t = ShardedTable(mesh, ShardedConfig(rows=32, set_rows=8,
                                         slots=16, batch=128))
    for _ in range(2):          # two locals: one to each shard pair
        assert t.import_histo("lat", dsd.TIMER, (), stats, means,
                              weights)
    snap = t.swap()
    imp = np.asarray(snap.histo_import_stats)[0]
    assert imp[segment.STAT_WEIGHT] == 2 * len(vals)
    assert imp[segment.STAT_MIN] == 1.0 and imp[segment.STAT_MAX] == 100.0
    assert imp[segment.STAT_SUM] == pytest.approx(2 * vals.sum())
    assert imp[segment.STAT_RSUM] == pytest.approx(2 * stats[4], rel=1e-6)
    assert np.asarray(snap.histo_stats)[0, segment.STAT_WEIGHT] == 0
    res = Flusher(is_local=False, percentiles=(0.5,),
                  aggregates=("hmean", "count", "max")).flush(snap)
    m = {x.name: x.value for x in res.metrics}
    assert set(m) == {"lat.50percentile"}
    assert m["lat.50percentile"] == pytest.approx(vals.mean())


def test_sharded_import_validates_before_staging():
    """Malformed imports are rejected BEFORE anything stages (the
    single-chip contract): nothing is half-applied."""
    import numpy as np

    from veneur_tpu.parallel import (ShardedConfig, ShardedTable,
                                     make_mesh)
    from veneur_tpu.protocol import dogstatsd as dsd

    mesh = make_mesh(jax.devices()[:4])
    t = ShardedTable(mesh, ShardedConfig(rows=32, set_rows=8,
                                         slots=16, batch=128))
    with pytest.raises(ValueError, match="stats shape"):
        t.import_histo("h", dsd.TIMER, (),
                       np.zeros((2, 5), np.float32),
                       np.ones(3, np.float32), np.ones(3, np.float32))
    with pytest.raises(ValueError, match="register plane"):
        t.import_set("s", (), np.zeros(7, np.uint8))
    assert t.staged() == 0


# ----------------------------------------------------------------------
# the cell ``global-64-locals-mesh2x2`` at a small size: the fleet's
# wires, made with the program's own encoder as the topology
# ``fleet-global`` makes them, folded into one chip's table and into
# a mesh table on an explicit 2 x 2 mesh, against the plain reference

_FLEET = {"clients": 16, "timers": 48, "sets": 12, "global_counters": 16,
          "locals_per_series": 8, "samples_per_digest": 128,
          "members_per_set": 320, "set_pool": 1280, "rounds": 1,
          "start_s": 0.2, "end_s": 0.95}


@pytest.fixture(scope="module")
def fleet_wires():
    """(fleet, its round, every local's wire, the cell's limits)."""
    from benchmark import fleet as fleet_mod
    from benchmark import harness
    cfg = harness.cell("global-64-locals-mesh2x2")["config"]
    fl = fleet_mod.Fleet(_FLEET, seed=3600000007)
    rnd = fl.round(0)
    bodies = harness.load_module("topologies", "fleet-global").Bodies(
        fl, cfg["sizes"]["compression"])
    pool = bodies.hashed_pool(rnd)
    wires = [bodies.body(rnd, l, pool)[0] for l in range(fl.clients)]
    return fl, rnd, wires, cfg["limits"]


def _mesh_table(batch=2048):
    from veneur_tpu.parallel import ShardedConfig, ShardedTable
    mesh = make_mesh(jax.devices()[:4], n_shard=2)
    assert dict(mesh.shape) == {"shard": 2, "series": 2}
    return ShardedTable(mesh, ShardedConfig(
        rows=64, set_rows=16, counter_rows=64, gauge_rows=64,
        compression=100.0, slots=512, batch=batch))


def _fold_and_flush(table, wires, order):
    """The wires through the import's decoded path in ``order``, a
    device step after each as the handler makes one, then the flush:
    (numbers against the reference's keys, the snapshot)."""
    from benchmark import reference
    from veneur_tpu.core.flusher import Flusher
    from veneur_tpu.forward.grpc_forward import apply_metric_list_bytes
    for l in order:
        acc, dropped = apply_metric_list_bytes(table, wires[l])
        assert dropped == 0 and acc > 0
        table.device_step()
    snap = table.swap()
    res = Flusher(is_local=False, percentiles=(0.5, 0.9, 0.99),
                  aggregates=("count",)).flush(snap)
    return reference.sink_values(
        (m.name, m.tags, m.value) for m in res.metrics), snap


def _against_reference(fl, rnd, got, limits):
    from benchmark import fleet_reference
    ref = fleet_reference.interval(
        fl, [rnd], [(l, 0) for l in range(fl.clients)])
    numbers = fleet_reference.compare_interval(ref, got)["numbers"]
    assert all(v <= limits[k] for k, v in numbers.items()), numbers
    return numbers


def test_fleet_wires_fold_alike_on_one_chip_and_on_a_2x2_mesh(
        fleet_wires):
    from veneur_tpu.core.table import MetricTable, TableConfig
    from veneur_tpu.forward.grpc_forward import decode_metric_list
    fl, rnd, wires, limits = fleet_wires
    if decode_metric_list(wires[0]) is None:
        pytest.skip("no native library")
    order = list(range(fl.clients))
    one, _ = _fold_and_flush(MetricTable(TableConfig(
        counter_rows=64, gauge_rows=64, histo_rows=64, set_rows=16)),
        wires, order)
    table = _mesh_table()
    mesh, snap = _fold_and_flush(table, wires, order)
    # the sums are exact, on both and alike; the rest inside the
    # cell's limits against the plain reference, on both
    assert _against_reference(fl, rnd, one, limits)["sums_off"] == 0
    assert _against_reference(fl, rnd, mesh, limits)["sums_off"] == 0
    exact = [k for k in one if "gcount" in k[0]]
    assert len(exact) == fl.n["gcount"]
    assert all(one[k] == mesh[k] for k in exact)
    assert set(one) == set(mesh)
    assert len(one) == fl.n["gcount"] + fl.n["set"] + 3 * fl.n["timer"]
    # a wire went whole to one shard, the next to the other; each
    # took two update calls of 2,048 (24 digests of 128 centroids and
    # a statistics row each), and the swap says so
    counts = snap.mesh_counts
    assert counts["mesh"] == "2x2" and len(counts["shard_staged"]) == 2
    assert counts["shard_staged"][0] == counts["shard_staged"][1] > 0
    assert counts["shard_steps"] == 2 * fl.clients
    assert counts["merge_path"] == "scatter"     # the CPU's path
    assert snap.import_counts == {
        "centroids": fl.clients * 24 * 128,
        "set_planes": fl.clients * 6, "set_planes_loose": 0}
    assert table.agg.merge_info["gathered_slots"] == 2 * 616
    # the next interval starts from nothing, the host planes too
    assert not table.agg._set_touched.any()
    assert not table.agg._set_planes.any()
    assert table.agg.steps == 0 and table.agg.staged == [0, 0]


@pytest.mark.parametrize("perm", [1, 2])
def test_whatever_shard_a_wire_lands_on_the_flush_is_the_same(
        fleet_wires, perm):
    """Two orders of the same calls put every wire but a few on the
    other shard and in another update call: sums and counts do not
    move, percentiles and cardinalities stay inside the limits."""
    fl, rnd, wires, limits = fleet_wires
    base, _ = _fold_and_flush(_mesh_table(), wires,
                              list(range(fl.clients)))
    order = np.random.default_rng(perm).permutation(fl.clients)
    assert list(order) != sorted(order)
    got, _ = _fold_and_flush(_mesh_table(), wires, order.tolist())
    _against_reference(fl, rnd, got, limits)
    exact = [k for k in base if "gcount" in k[0]]
    assert len(exact) == fl.n["gcount"]
    assert all(base[k] == got[k] for k in exact)
    # a register max commutes: the unions are the same bytes
    sets = [k for k in base if ".set." in k[0]]
    assert len(sets) == fl.n["set"]
    assert all(base[k] == got[k] for k in sets)


def test_http_import_bodies_take_the_shards_in_turn():
    """An HTTP import body is a wire too: its batched counters, gauges
    and digests go whole to one shard and the next body's to the
    other, as the gRPC path's do (``begin_wire`` in both)."""
    from veneur_tpu.core.flusher import ForwardRow, Flusher
    from veneur_tpu.core.table import RowMeta
    from veneur_tpu.forward import http_import
    from veneur_tpu.ops import segment
    from veneur_tpu.protocol import dogstatsd as dsd

    def meta(name, mtype):
        return RowMeta(name=name, tags=("env:t",),
                       scope=dsd.SCOPE_DEFAULT, type=mtype)
    rng = np.random.default_rng(5)
    stats = np.zeros(5, np.float32)
    vals = np.sort(rng.uniform(1, 9, 32)).astype(np.float32)
    stats[segment.STAT_WEIGHT] = len(vals)
    stats[segment.STAT_MIN], stats[segment.STAT_MAX] = vals[0], vals[-1]
    stats[segment.STAT_SUM] = vals.sum()
    stats[segment.STAT_RSUM] = (1.0 / vals).sum()
    rows = [ForwardRow(meta(f"h.c{i}", dsd.COUNTER), "counter",
                       value=float(i + 1)) for i in range(6)]
    rows += [ForwardRow(meta("h.g", dsd.GAUGE), "gauge", value=3.5),
             ForwardRow(meta("h.t", dsd.TIMER), "histo", stats=stats,
                        means=vals, weights=np.ones(32, np.float32))]
    body, headers = http_import.encode_rows_reference(rows,
                                                      deflate=False)
    items = http_import.decode_body(body)
    t = _mesh_table(batch=128)
    for _ in range(4):
        assert http_import.apply_import(t, items) == (len(rows), 0)
    # four bodies, two to each shard: what each staged is the same
    assert t.agg.staged[0] == t.agg.staged[1] > 0
    res = Flusher(is_local=False, percentiles=(0.5,),
                  aggregates=("count",)).flush(t.swap())
    m = {x.name: x.value for x in res.metrics}
    assert m["h.c5"] == 4 * 6.0 and m["h.g"] == 3.5
    assert m["h.t.50percentile"] == pytest.approx(
        float(np.median(vals)), rel=0.05)


def test_sharded_import_set_wire_gives_the_per_item_paths_planes():
    """``import_set_wire`` on the mesh table: a wire's dense sketches
    in one native pass into the host plane of the wire's shard, byte
    for byte what ``hll_codec.decode`` + ``import_set_at`` give item
    by item; a sparse and a malformed sketch are handed back."""
    from veneur_tpu.core.flusher import Flusher
    from veneur_tpu.forward import hll_codec
    from veneur_tpu.forward.gen import forward_pb2, metric_pb2
    from veneur_tpu.forward.grpc_forward import (apply_metric_list_bytes,
                                                 decode_metric_list)
    from veneur_tpu.ops import hll
    from veneur_tpu.utils import hashing

    ms, planes = [], []
    for s, n in (("u.a", 300), ("u.b", 40), ("u.a", 200), ("u.c", 900)):
        idx, rank = hashing.hll_position(hashing.hash64(
            [f"{s}-{n}-{i}".encode() for i in range(n)]))
        regs = np.zeros(hll.M, np.uint8)
        np.maximum.at(regs, idx, rank.astype(np.uint8))
        m = metric_pb2.Metric(name=s, type=metric_pb2.Set)
        m.set.hyper_log_log = hll_codec.encode_dense(regs)
        ms.append(m)
        planes.append((s, regs))
    from test_grpc_forward import _sparse_sketch
    sparse = metric_pb2.Metric(name="u.sparse", type=metric_pb2.Set)
    sparse.set.hyper_log_log = _sparse_sketch()
    planes.append(("u.sparse", hll_codec.decode(_sparse_sketch())))
    bad = metric_pb2.Metric(name="u.bad", type=metric_pb2.Set)
    bad.set.hyper_log_log = b"\x01\x0e\x00\x00"
    wire = forward_pb2.MetricList(
        metrics=ms + [sparse, bad]).SerializeToString()
    if decode_metric_list(wire) is None:
        pytest.skip("no native library")

    t = _mesh_table(batch=128)
    assert apply_metric_list_bytes(t, wire) == (5, 1)
    # four dense in the one pass; the sparse and the malformed one
    # handed back, the sparse one then unioned by itself
    assert t.import_counts == {"centroids": 0, "set_planes": 5,
                               "set_planes_loose": 2}
    # the wire's dense sketches all on one shard's plane
    assert sorted(t.agg._set_touched.sum(axis=1)) == [1, 3]
    by_wire = t.agg._set_planes.copy()

    item = _mesh_table(batch=128)
    for s, regs in planes:
        row = item.import_set_row(s, ())
        item.import_set_at(row, hll_codec.decode(
            hll_codec.encode_dense(regs)))
    assert item.import_counts["set_planes"] == 5
    # item by item each sketch takes the next shard: the planes are
    # the same bytes once the shards are unioned, as the merge does
    assert np.array_equal(by_wire.max(axis=0),
                          item.agg._set_planes.max(axis=0))
    a = t.swap()
    b = item.swap()
    assert np.array_equal(np.asarray(a.hll_regs), np.asarray(b.hll_regs))
    assert np.asarray(a.hll_regs).any()
    res = Flusher(is_local=False, percentiles=()).flush(a)
    m = {x.name: x.value for x in res.metrics}
    assert m["u.a"] == pytest.approx(500, rel=0.05)
    assert m["u.b"] == pytest.approx(40, rel=0.05)
    assert m["u.c"] == pytest.approx(900, rel=0.05)


# ----------------------------------------------------------------------
# the interval reset: the next interval's state made on the chips, the
# closed interval's host register planes cleared after its flush


def _host_built_empty_state(mesh, cfg):
    """The fresh state as it was once made: numpy planes on the host,
    each put on the mesh in its sharding."""
    from jax.sharding import NamedSharding

    from veneur_tpu.ops import hll
    from veneur_tpu.ops.segment import (HISTO_STAT_COLS, STAT_MAX,
                                        STAT_MAX_EMPTY, STAT_MIN,
                                        STAT_MIN_EMPTY)
    from veneur_tpu.parallel import sharded
    s = mesh.shape["shard"]
    r, cap = cfg.rows, cfg.capacity()
    stats = np.zeros((s, r, HISTO_STAT_COLS), np.float32)
    stats[:, :, STAT_MIN] = STAT_MIN_EMPTY
    stats[:, :, STAT_MAX] = STAT_MAX_EMPTY
    host = {
        "counters": np.zeros((s, cfg.c_rows()), np.float32),
        "gauges": np.zeros((s, cfg.g_rows()), np.float32),
        "gauge_ticket": np.full((s, cfg.g_rows()), -1, np.int32),
        "histo_stats": stats, "histo_import_stats": stats,
        "histo_means": np.zeros((s, r, cap), np.float32),
        "histo_weights": np.zeros((s, r, cap), np.float32),
        "hll": np.zeros((s, cfg.set_rows, hll.M), np.uint8)}
    specs = sharded._specs(mesh)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in host.items()}


@pytest.mark.parametrize("n_shard", [2, 4])
def test_empty_state_made_on_the_mesh_is_the_host_built_one(n_shard):
    from veneur_tpu.parallel import sharded
    mesh = make_mesh(jax.devices(), n_shard=n_shard)
    cfg = ShardedConfig(rows=32, set_rows=8, counter_rows=64,
                        gauge_rows=16, slots=16, batch=128)
    got = sharded.empty_state(mesh, cfg)
    want = _host_built_empty_state(mesh, cfg)
    assert set(got) == set(want)
    for k, w in want.items():
        g = got[k]
        assert (g.shape, g.dtype) == (w.shape, w.dtype), k
        assert g.sharding.spec == w.sharding.spec, k
        assert g.sharding.mesh == mesh, k
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # every plane a buffer of its own (the update step may donate them)
    ptrs = [sh.data.unsafe_buffer_pointer()
            for a in got.values() for sh in a.addressable_shards]
    assert len(set(ptrs)) == len(ptrs)


def _interval(table, series, value):
    """One interval's imports of every class into ``table``: one call
    a series, as many calls as ``series`` has entries."""
    from veneur_tpu.ops import hll
    from veneur_tpu.protocol import dogstatsd as dsd
    rng = np.random.default_rng(int(value))
    for name in series:
        kind = name[0]
        if kind == "c":
            assert table.import_counter(name, (), value)
        elif kind == "g":
            assert table.import_gauge(name, (), value + 0.5)
        elif kind == "h":
            vals = np.sort(rng.uniform(1, value + 2, 40)).astype(
                np.float32)
            stats = np.asarray([len(vals), vals[0], vals[-1],
                                vals.sum(), (1.0 / vals).sum()],
                               np.float32)
            assert table.import_histo(name, dsd.TIMER, (), stats, vals,
                                      np.ones(len(vals), np.float32))
        else:
            regs = rng.integers(0, 20, hll.M).astype(np.uint8)
            assert table.import_set(name, (), regs)
    table.device_step()


_PLANES = ("counters", "gauges", "histo_stats", "histo_import_stats",
           "histo_means", "histo_weights", "hll_regs")


@pytest.mark.parametrize("cleared_after_flush", [True, False])
def test_no_register_or_centroid_survives_a_reset(cleared_after_flush):
    """Intervals of sets, digests, counters and gauges, then the last
    interval's imports alone: its merged planes are bit for bit those
    of the same imports into a fresh table.  Three intervals, so the
    last writes the host register buffer the first one wrote; the
    server's cycle cleared the closed buffer (``clear_closed``), or
    the next reset had to, which then counts the bytes."""
    from veneur_tpu.ops import hll
    last = ["c0", "c1", "g0", "h0", "h1", "s0", "s1", "s2"]
    # the last interval's series first (the same rows on both tables),
    # and an even count of calls: the shard cursor is where a fresh
    # table's is when the last interval starts
    earlier = last + ["c7", "g7", "h7", "s7", "s8", "s9"]
    used = _mesh_table()
    snaps = []
    for i, series in enumerate((earlier, earlier, last)):
        _interval(used, series, 3.0 + i)
        snaps.append(used.swap())
        if cleared_after_flush:
            assert used.clear_closed() == snaps[-1].mesh_counts[
                "reset_deferred_bytes"]
    fresh = _mesh_table()
    _interval(fresh, last, 5.0)
    want = fresh.swap()
    for k in _PLANES:
        np.testing.assert_array_equal(np.asarray(getattr(snaps[-1], k)),
                                      np.asarray(getattr(want, k)), k)
    sets_of = {"earlier": 6, "last": 3}
    deferred = [s.mesh_counts["reset_deferred_bytes"] for s in snaps]
    assert deferred == [sets_of["earlier"] * hll.M,
                        sets_of["earlier"] * hll.M,
                        sets_of["last"] * hll.M]
    host = [s.mesh_counts["reset_host_bytes"] for s in snaps]
    # uncleared, each reset from the second clears the buffer the
    # interval before the last wrote
    assert host == ([0, 0, 0] if cleared_after_flush else
                    [0, sets_of["earlier"] * hll.M,
                     sets_of["earlier"] * hll.M])


def test_a_flush_cycle_leaves_both_host_plane_buffers_clean():
    """Through the server: the cycle clears the closed interval's host
    register planes once it has delivered, so the reset ships, builds
    and clears nothing on the host, and the next interval's first
    ``set_plane`` finds zeros.  The record and the ``state_reset``
    span say so."""
    import time

    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.ops import hll
    from veneur_tpu.sinks.simple import CaptureSink

    cap = CaptureSink()
    srv = Server(read_config(data={
        "statsd_listen_addresses": [], "interval": "10s",
        "tpu_mesh_shards": 2, "tpu_histo_rows": 64, "tpu_set_rows": 16,
        "tpu_counter_rows": 64, "tpu_gauge_rows": 64}),
        extra_sinks=[cap])
    srv.start()
    try:
        agg = srv.table.agg
        rng = np.random.default_rng(41)
        for n in (5, 3):
            for i in range(n):
                assert srv.table.import_set(
                    f"u.{i}", (), rng.integers(1, 30, hll.M).astype(
                        np.uint8))
            srv.flush_once()
            assert not agg._set_touched.any()
            assert not agg._closed_touched.any()
            assert not agg._set_planes.any()
            assert not agg._closed_planes.any()
        plane, touched = agg.set_plane(0)
        assert not plane.any() and not touched.any()
        recs = srv.flush_ring.records()[-2:]
        assert [r.reset_host_bytes for r in recs] == [0, 0]
        assert [r.reset_deferred_bytes for r in recs] == [
            5 * hll.M, 3 * hll.M]
        assert recs[-1].to_dict()["reset_deferred_bytes"] == 3 * hll.M
        assert recs[-1].to_dict()["reset_host_bytes"] == 0
        assert {m.name for m in cap.metrics} >= {"u.0", "u.4"}
        for rec in recs:
            deadline = time.monotonic() + 10
            spans = []
            while time.monotonic() < deadline and not spans:
                spans = [s for s in srv.trace_index.get(rec.trace_id)
                         if s["name"] == "flush.snapshot.state_reset"]
                time.sleep(0.01)
            assert spans and spans[0]["tags"]["host_bytes"] == "0"
    finally:
        srv.shutdown()


def test_compile_leaves_the_first_swap_nothing_to_compile(mesh, cfg):
    """``compile()`` before traffic: the first interval's update
    calls, the sketches' union, the merge and the fresh state compile
    nothing (an aggregator that skipped it compiles in its swap)."""
    from jax import monitoring

    from veneur_tpu.ops import hll
    events = []

    def note(name, secs, **kw):
        if name.startswith("/jax/core/compile/"):
            events.append(name)

    def first_swap(agg):
        rows = np.arange(6, dtype=np.int32)
        agg.stage(1, counter_rows=rows, counter_vals=np.ones(6, np.float32),
                  counter_wts=np.ones(6, np.float32),
                  histo_rows=rows, histo_vals=np.ones(6, np.float32),
                  histo_wts=np.ones(6, np.float32))
        plane, touched = agg.set_plane(0)
        plane[2] = np.arange(hll.M) % 7
        touched[2] = True
        del events[:]
        monitoring.register_event_duration_secs_listener(note)
        try:
            merged = agg.swap()
            jax.block_until_ready(agg.state)
        finally:
            monitoring.unregister_event_duration_listener(note)
        assert float(np.asarray(merged["counters"]).sum()) == 6.0
        return list(events)

    agg = ShardedAggregator(mesh, cfg)
    agg.compile()
    assert first_swap(agg) == []
    assert first_swap(ShardedAggregator(mesh, cfg)) != []
