"""The forward as column blocks: ``ForwardBlock`` / ``ForwardList``
in the flusher, and ``encode_metric_list`` encoding them.

- wire parity: the block encoder's bytes and centroid count equal the
  row-walking encoder's that it replaced (``forward_wire_reference``),
  case by case;
- ownership: a flush's forward encoded after ``snap.release()`` and
  after later intervals reused the host set plane still gives its own
  interval's bytes; a series' identity bytes live on its ``RowMeta``
  and go with it at a compaction;
- row view: the row-wise consumers send from a block-backed
  ``res.forward`` what they sent from the list of rows;
- the counters that say the block path engaged, from
  ``/debug/flushes``, and no ``ForwardRow`` built on the tick path.
"""

from __future__ import annotations

import gc
import json
import time
import urllib.request
import weakref

import numpy as np
import pytest

from tests import forward_wire_reference as reference
from tests.flush_reference import RowFlusher
from veneur_tpu.core.config import read_config
from veneur_tpu.core.flusher import (Flusher, ForwardBlock, ForwardList,
                                     ForwardRow, _pad_idx)
from veneur_tpu.core.server import Server
from veneur_tpu.core.table import MetricTable, RowMeta, TableConfig
from veneur_tpu.forward import handoff, http_import
from veneur_tpu.forward.gen import forward_pb2
from veneur_tpu.forward.grpc_forward import encode_metric_list
from veneur_tpu.forward.ring import ConsistentRing
from veneur_tpu.forward.shard import ShardedForwarder
from veneur_tpu.forward.spool import WireSpool
from veneur_tpu.ops import checkpoint, hll, segment
from veneur_tpu.protocol import dogstatsd as dsd

WIDTH = 616  # a digest row's slots at compression 100


# ----------------------------------------------------------------------
# case data: arrays first, then the blocks and the rows cut from them
# by two hands (the rows own copies, as the flusher's used to)

def _metas(prefix, n, mtype, scope=dsd.SCOPE_DEFAULT):
    return [RowMeta(f"{prefix}.{i:04d}", ("env:prod", f"shard:{i % 7}"),
                    scope, mtype) for i in range(n)]


def _digests(n, width, live, seed):
    """``n`` digests of ``live`` live centroids at random slots of
    ``width``: (stats f32[n,5], means f32[n,width], weights)."""
    rng = np.random.default_rng(seed)
    means = np.zeros((n, width), np.float32)
    weights = np.zeros((n, width), np.float32)
    for i in range(n):
        at = np.sort(rng.choice(width, min(live, width), replace=False))
        means[i, at] = np.sort(rng.gamma(2.0, 30.0, len(at)))
        weights[i, at] = rng.integers(1, 5, len(at))
    stats = np.zeros((n, segment.HISTO_STAT_COLS), np.float32)
    stats[:, segment.STAT_WEIGHT] = weights.sum(axis=1)
    stats[:, segment.STAT_MIN] = means.min(axis=1, initial=np.inf)
    stats[:, segment.STAT_MAX] = means.max(axis=1, initial=-np.inf)
    stats[:, segment.STAT_RSUM] = rng.random(n)
    return stats, means, weights


def _histo(metas, stats, means, weights):
    """A matrix block and its rows."""
    blk = ForwardBlock("histo", metas, stats=stats, means=means,
                       weights=weights)
    rows = [ForwardRow(m, "histo", stats=stats[i].copy(),
                       means=means[i].copy(), weights=weights[i].copy())
            for i, m in enumerate(metas)]
    return blk, rows


def _ragged(metas, stats, planes):
    """A block of rows of unequal width (flat planes and ``row_at``)
    and its rows; ``planes`` is [(means, weights)] a row."""
    row_at = np.concatenate(
        ([0], np.cumsum([len(m) for m, _ in planes]))).astype(np.int64)
    blk = ForwardBlock(
        "histo", metas, stats=stats,
        means=np.concatenate([m for m, _ in planes]),
        weights=np.concatenate([w for _, w in planes]), row_at=row_at)
    rows = [ForwardRow(meta, "histo", stats=stats[i].copy(),
                       means=planes[i][0].copy(),
                       weights=planes[i][1].copy())
            for i, meta in enumerate(metas)]
    return blk, rows


def _scalars(kind, metas, values):
    values = np.asarray(values, np.float64)
    return (ForwardBlock(kind, metas, values=values),
            [ForwardRow(m, kind, value=float(v))
             for m, v in zip(metas, values)])


def _sets(metas, regs):
    return (ForwardBlock("set", metas, regs=regs),
            [ForwardRow(m, "set", regs=regs[i].copy())
             for i, m in enumerate(metas)])


def _regs(n, seed):
    return np.random.default_rng(seed).integers(
        0, 20, (n, hll.M)).astype(np.uint8)


def _case_mixed_cell():
    """``local-mixed-paced``'s forward cut small, in the flusher's
    order: global counters, digests of 96 live in 616 slots, dense
    sets."""
    c = _scalars("counter", _metas("c", 40, dsd.COUNTER,
                                   dsd.SCOPE_GLOBAL),
                 np.arange(40) * 16.0)
    h = _histo(_metas("t", 120, dsd.TIMER), *_digests(120, WIDTH, 96, 1))
    s = _sets(_metas("s", 12, dsd.SET), _regs(12, 2))
    return [c[0], h[0], s[0]], c[1] + h[1] + s[1]


def _case_unequal_width():
    stats, _, _ = _digests(5, 8, 4, 3)
    planes = [_digests(1, w, live, 10 + w)[1:]
              for w, live in ((WIDTH, 96), (312, 312), (0, 0), (40, 7),
                              (WIDTH, 616))]
    planes = [(m[0], w[0]) for m, w in planes]
    blk, rows = _ragged(_metas("r", 5, dsd.HISTOGRAM), stats, planes)
    return [blk], rows


def _case_mean_zero():
    stats, means, weights = _digests(3, 6, 6, 4)
    means[0] = [0.0, -0.0, 1.5, 0.0, -0.0, 2.5]
    means[1] = 0.0
    means[2] = -0.0
    weights[:] = [[1, 2, 0, 3, 4, 0], [1] * 6, [2] * 6]
    stats[1, segment.STAT_MIN] = stats[1, segment.STAT_MAX] = 0.0
    stats[2, segment.STAT_MIN] = -0.0
    blk, rows = _histo(_metas("z", 3, dsd.TIMER), stats, means, weights)
    return [blk], rows


def _case_empty_digest():
    stats, means, weights = _digests(4, 32, 9, 5)
    weights[1] = 0
    weights[3] = 0
    some = _histo(_metas("e", 4, dsd.TIMER), stats, means, weights)
    none = _histo(_metas("n", 2, dsd.TIMER), stats[:2],
                  means[:2], np.zeros_like(weights[:2]))
    return [some[0], none[0]], some[1] + none[1]


def _case_zero_scalars():
    c = _scalars("counter", _metas("c", 6, dsd.COUNTER, dsd.SCOPE_GLOBAL),
                 [0.0, 5.0, -3.0, 0.4, -0.0, float(2 ** 53)])
    g = _scalars("gauge", _metas("g", 5, dsd.GAUGE, dsd.SCOPE_GLOBAL),
                 [0.0, -0.0, 2.5, -1e-300, 7.0])
    return [c[0], g[0]], c[1] + g[1]


def _case_empty_plane():
    regs = _regs(4, 6)
    regs[1] = 0
    regs[2] = 255
    blk, rows = _sets(_metas("s", 4, dsd.SET), regs)
    return [blk], rows


def _case_padded():
    """Blocks as the flusher cuts them: the head of a readback that
    ``_pad_idx`` padded to its bucket, with whatever the pad gathered
    behind it (here: live garbage that must never reach the wire)."""
    n = 37
    pad = int(_pad_idx(list(range(n)))[0].shape[0])
    assert pad > n
    stats, means, weights = _digests(pad, WIDTH, 50, 7)
    regs = _regs(pad, 8)
    h = _histo(_metas("p", n, dsd.TIMER), stats[:n], means[:n],
               weights[:n])
    s = _sets(_metas("ps", n, dsd.SET), regs[:n])
    assert not h[0].means.flags.owndata and not s[0].regs.flags.owndata
    return [h[0], s[0]], h[1] + s[1]


def _case_loose():
    """A plain list whose kinds interleave, as a shard's batch or a
    checkpoint's rows do: the wire keeps the list's order."""
    blocks, rows = _case_mixed_cell()
    rows = rows[::3] + rows[1::3] + rows[2::3]
    assert len({r.kind for r in rows[:60]}) > 1
    return list(rows), rows


def _case_mixed_parts():
    """Blocks and loose rows in one list, loose rows of two widths."""
    blocks, rows = _case_mixed_cell()
    (ragged,), rrows = _case_unequal_width()
    zero, zrows = _case_zero_scalars()
    parts = ([blocks[0]] + rrows[:2] + [blocks[1]] + zrows[:3]
             + rrows[2:] + [ragged, blocks[2]] + zrows[3:])
    want = (rows[:40] + rrows[:2] + rows[40:160] + zrows[:3]
            + rrows[2:] + rrows + rows[160:] + zrows[3:])
    return parts, want


PARITY_CASES = {
    "mixed_cell_cut_small": _case_mixed_cell,
    "rows_of_unequal_width": _case_unequal_width,
    "mean_zero_and_negative_zero": _case_mean_zero,
    "empty_digest": _case_empty_digest,
    "zero_counter_zero_gauge": _case_zero_scalars,
    "set_with_empty_plane": _case_empty_plane,
    "blocks_behind_pad_idx_padding": _case_padded,
    "loose_rows": _case_loose,
    "blocks_and_loose_rows_mixed": _case_mixed_parts,
}


@pytest.mark.parametrize("case", PARITY_CASES)
def test_block_wire_equals_row_wire(case):
    """Byte for byte and centroid for centroid the wire of the encoder
    that walked rows, for blocks, for loose rows and for both in one
    list; and the list's row view is those rows."""
    parts, rows = PARITY_CASES[case]()
    want, want_centroids = reference.encode_metric_list(rows, 50.0)
    counts: dict = {}
    body, centroids = encode_metric_list(ForwardList(parts), 50.0, counts)
    assert (body, centroids) == (want, want_centroids)
    assert encode_metric_list(parts, 50.0) == (want, want_centroids)
    assert len(forward_pb2.MetricList.FromString(body).metrics) == len(
        rows)

    fl = ForwardList(parts)
    assert len(fl) == len(rows) and bool(fl)
    assert reference.encode_metric_list(list(fl), 50.0)[0] == want
    loose = sum(isinstance(p, ForwardRow) for p in parts)
    assert counts["rows_loose"] == loose
    assert counts["rows_block"] == len(rows) - loose
    kinds = [r.kind for r in rows]
    assert (counts["rows_histo"], counts["rows_sets"],
            counts["rows_scalars"]) == (
        kinds.count("histo"), kinds.count("set"),
        kinds.count("counter") + kinds.count("gauge"))
    # the identity bytes were left on the rows' metas: a second encode
    # reuses every one and gives the same wire
    again: dict = {}
    assert encode_metric_list(parts, 50.0, again)[0] == want
    assert again["ident_cached"] == len(rows)


def test_forward_list_reads_as_the_list_it_was():
    empty = ForwardList()
    assert not empty and len(empty) == 0 and empty == []
    assert encode_metric_list(empty) == (b"", 0)
    parts, rows = _case_zero_scalars()
    fl = ForwardList(parts)
    fl.append(rows[0])
    assert len(fl) == len(rows) + 1
    assert fl[0].meta is rows[0].meta and fl[-1] is rows[0]
    assert [r.value for r in fl] == [r.value for r in rows + rows[:1]]
    assert fl != []


# ----------------------------------------------------------------------
# ownership

def _ingest(table, lines):
    for ln in lines:
        table.ingest(dsd.parse_metric(ln))


def _interval(k):
    """Interval ``k``'s lines: the same series every interval, other
    values and other set members."""
    lines = [f"ow.users.{s}:m{k}-{j}|s".encode()
             for s in range(6) for j in range(30 + 5 * s)]
    lines += [f"ow.lat.{t}:{(k + 1) * (j + 1)}.5|ms".encode()
              for t in range(5) for j in range(24)]
    lines += [f"ow.hits.{c}:{k + c + 1}|c|#veneurglobalonly".encode()
              for c in range(4)]
    return lines


@pytest.mark.parametrize("tiers", ["off", "2"])
def test_forward_outlives_its_snapshot(monkeypatch, tiers):
    """The forward is encoded on the pool after ``snap.release()`` and
    may outlive its cycle: a flush's blocks, encoded only after two
    later intervals have been through the recycled host set plane,
    give the bytes they gave before the release."""
    monkeypatch.setenv("VENEUR_TPU_PLANE_TIERS", tiers)
    monkeypatch.setenv("VENEUR_TPU_PROMOTE_HISTO_SAMPLES", "16")
    monkeypatch.setenv("VENEUR_TPU_PROMOTE_SET_ENTRIES", "16")
    t = MetricTable(TableConfig(counter_rows=64, gauge_rows=64,
                                histo_rows=64, set_rows=16))
    assert (t.tiers is not None) == (tiers == "2")
    flusher = Flusher(is_local=True)
    planes, held = [], None
    for k in range(5):
        _ingest(t, _interval(k))
        snap = t.swap()
        assert (snap.tiers is not None) == (tiers == "2")
        planes.append(snap.hll_host_plane)
        res = flusher.flush(snap)
        if k == 2:
            # tiered: by now the sets are wide rows of the host plane
            held = res.forward
            want = encode_metric_list(held)
            assert {type(p) for p in held.parts} == {ForwardBlock}
            assert len(held) == 6 + 5 + 4
        snap.release()
    # the plane interval 2 read from was handed out again afterwards
    assert planes[2] is not None
    assert any(p is planes[2] for p in planes[3:])
    assert encode_metric_list(held) == want
    assert encode_metric_list(res.forward)[0] != want[0]
    rows = list(held)
    assert reference.encode_metric_list(rows)[0] == want[0]


def test_identity_bytes_go_with_the_row_at_compaction():
    """A series' identity bytes sit on its ``RowMeta``: reused every
    interval the row lives, gone with the meta when a compaction drops
    the series, and never another series' when its row number passes
    on."""
    t = MetricTable(TableConfig(counter_rows=8, compact_threshold=0.5))
    flusher = Flusher(is_local=True)

    def forward(lines):
        _ingest(t, lines)
        snap = t.swap()
        res = flusher.flush(snap)
        counts: dict = {}
        body, _ = encode_metric_list(res.forward, 100.0, counts)
        assert body == reference.encode_metric_list(
            list(res.forward), 100.0)[0]
        names = [m.name for m in
                 forward_pb2.MetricList.FromString(body).metrics]
        return names, counts, snap

    glob = "|c|#veneurglobalonly"
    names, counts, snap = forward(
        [f"id.a{i}:1{glob}".encode() for i in range(6)])
    assert names == [f"id.a{i}" for i in range(6)]
    assert counts["ident_cached"] == 0 and counts["rows_block"] == 6
    dropped = weakref.ref(snap.counter_meta[0])
    kept = snap.counter_meta[4]
    assert dropped().name == "id.a0" and kept.wire_ident is not None

    # only a4 and a5 come again: their bytes are reused
    names, counts, snap = forward(
        [f"id.a{i}:2{glob}".encode() for i in (4, 5)])
    assert names == ["id.a4", "id.a5"]
    assert counts["ident_cached"] == 2
    epoch = t._reindex_epoch
    del snap

    # the swap above compacted a0..a3 away (untouched for an
    # interval): a4 is row 0 now, and new series take the rows after
    names, counts, snap = forward(
        [f"id.a4:3{glob}".encode()]
        + [f"id.b{i}:3{glob}".encode() for i in range(3)])
    assert t._reindex_epoch == epoch + 1
    assert snap.counter_meta[0] is kept
    assert names == ["id.a4", "id.b0", "id.b1", "id.b2"]
    assert counts["ident_cached"] == 1
    del snap
    gc.collect()
    assert dropped() is None


# ----------------------------------------------------------------------
# the row view, consumer by consumer

def _routed_table():
    t = MetricTable(TableConfig(counter_rows=64, gauge_rows=64,
                                histo_rows=64, set_rows=16))
    lines = [f"rv.hits.{i}:{i + 1}|c|#veneurglobalonly,k:{i}".encode()
             for i in range(9)]
    lines += [f"rv.temp.{i}:{i}.5|g|#veneurglobalonly".encode()
              for i in range(4)]
    lines += [f"rv.lat.{i}:{j * (i + 1)}.25|ms|#route:{i % 3}".encode()
              for i in range(11) for j in range(20)]
    lines += [f"rv.users.{i}:u{j}|s".encode()
              for i in range(5) for j in range(40)]
    lines += [b"rv.local:1|c", b"rv.l.lat:3|ms|#veneurlocalonly"]
    _ingest(t, lines)
    return t


def _flush_both(table):
    """One snapshot through the flush (blocks) and the per-row
    reference loops (a list of rows)."""
    snap = table.swap()
    blocks = Flusher(is_local=True).flush(snap, now=7)
    rows = RowFlusher(is_local=True).flush(snap, now=7)
    assert {type(p) for p in blocks.forward.parts} == {ForwardBlock}
    assert {type(p) for p in rows.forward.parts} == {ForwardRow}
    return blocks, rows


def _send_shard_split(fwd_rows, tmp_path):
    sf = ShardedForwarder(["10.0.0.1:1", "10.0.0.2:1", "10.0.0.3:1"])
    try:
        scalar = sorted(sf.route_rows_scalar(fwd_rows))
        routed = sf.route(sf.serialize(fwd_rows))
        cols = None if routed is None else sorted(
            (routed.members[d], bytes(body), n)
            for d, body, n in routed.batches)
        return scalar, cols
    finally:
        sf.stop()


def _send_handoff(fwd_rows, tmp_path):
    ring = ConsistentRing(["g1:1", "g2:1", "g3:1"])
    by_member, kept = handoff.partition(fwd_rows, ring, "g1:1")
    return kept, {m: encode_metric_list(rows)[0]
                  for m, rows in sorted(by_member.items())}


def _send_http_json(fwd_rows, tmp_path):
    return (http_import.encode_rows(fwd_rows, deflate=False),
            http_import.encode_rows_reference(fwd_rows, deflate=False))


def _send_spool(fwd_rows, tmp_path):
    spool = WireSpool(dir=str(tmp_path / f"spool-{len(fwd_rows.parts)}"))
    body = encode_metric_list(fwd_rows)[0]
    assert spool.put("g1:1", body, len(fwd_rows))
    entry = spool.take("g1:1")
    return entry.n_items, entry.read()


def _send_checkpoint(fwd_rows, tmp_path):
    """A checkpoint builds its own loose rows from a capture of the
    open interval; here: the same series staged again."""
    t = _routed_table()
    cap = t.checkpoint_capture()
    body, n = checkpoint.serialize_capture(cap, 616, 100.0)
    rows = checkpoint.build_rows(cap, 616)
    assert body == reference.encode_metric_list(rows, 100.0)[0]
    return n, sorted(m.name for m in
                     forward_pb2.MetricList.FromString(body).metrics)


CONSUMERS = {"shard_split": _send_shard_split, "handoff": _send_handoff,
             "http_json": _send_http_json, "spool": _send_spool,
             "checkpoint": _send_checkpoint}


@pytest.mark.parametrize("consumer", CONSUMERS)
def test_row_consumers_send_the_same_from_blocks(consumer, tmp_path):
    blocks, rows = _flush_both(_routed_table())
    assert len(blocks.forward) == len(rows.forward) == 9 + 4 + 11 + 5
    assert blocks.row_accounting == rows.row_accounting
    assert blocks.forward_split == rows.forward_split == {}
    send = CONSUMERS[consumer]
    assert send(blocks.forward, tmp_path) == send(rows.forward, tmp_path)
    # and the wire of the two flushes is one wire
    assert encode_metric_list(blocks.forward) == encode_metric_list(
        rows.forward)


# ----------------------------------------------------------------------
# the counters that say it engaged, and no row object on the tick path

def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_flushes_record_says_blocks_and_cached_identities(monkeypatch):
    """Two flushes of a local forwarding over gRPC: every row is
    encoded from a block, from the second flush on every identity is
    reused, the span and ``/debug/flushes`` say so, and no
    ``ForwardRow`` is built along the way."""
    pytest.importorskip("grpc")
    servers = []

    def make(**overrides):
        s = Server(read_config(data={
            "statsd_listen_addresses": [], "interval": "10s",
            "hostname": "fb-test", **overrides}))
        s.start()
        servers.append(s)
        return s

    built = []
    init = ForwardRow.__init__
    monkeypatch.setattr(
        ForwardRow, "__init__",
        lambda self, *a, **kw: (built.append(1), init(self, *a, **kw))[1])
    try:
        glob = make(grpc_listen_addresses=["tcp://127.0.0.1:0"])
        local = make(
            forward_address=f"127.0.0.1:{glob.grpc_ports[0]}",
            forward_use_grpc=True, http_address="127.0.0.1:0")
        for k in range(2):
            local.handle_packet("\n".join(
                [f"fb.lat.{i}:{v + k}.5|ms" for i in range(7)
                 for v in range(12)]
                + [f"fb.users.{i}:u{v + 100 * k}|s" for i in range(5)
                   for v in range(9)]
                + [f"fb.g.{i}:2|c|#veneurglobalonly" for i in range(3)]
                + [f"fb.v.{i}:{i}|g|#veneurglobalonly" for i in range(2)]
                + [f"fb.c.{i}:2|c" for i in range(4)]).encode())
            res = local.flush_once()
            assert len(res.forward) == 17
            assert _wait(lambda: glob.stats.get(
                "imports_received", 0) >= 17 * (k + 1))
        assert not built

        recs = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{local.http_port}/debug/flushes",
            timeout=5).read())
        first, second = recs[-2], recs[-1]
        for rec in (first, second):
            assert rec["forward_rows"] == rec["rows_block"] == 17
            assert rec["rows_loose"] == 0
        assert first["ident_cached"] == 0
        assert second["ident_cached"] == 17

        spans = local.trace_index.get(int(second["trace_id"]))
        enc = [s for s in spans
               if s["name"] == "flush.forward.encode"][-1]["tags"]
        assert (enc["rows"], enc["rows_block"], enc["rows_loose"],
                enc["ident_cached"]) == ("17", "17", "0", "17")
        assert (enc["rows_histo"], enc["rows_sets"],
                enc["rows_scalars"]) == ("7", "5", "5")
    finally:
        for s in servers:
            s.shutdown()
