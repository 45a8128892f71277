"""gRPC forward tier: wire-compatible ``forwardrpc.Forward`` client and
import server.

The reference's primary DCN comm backend: a local veneur forwards
mergeable sampler state as protobuf ``MetricList`` batches
(flusher.go:499 ``forwardGRPC``) to a global veneur's importsrv
(importsrv/server.go:102 ``SendMetrics``), which merges them into
worker state (worker.go:438 ``ImportMetricGRPC``).

Here the same service — identical package/method path
``/forwardrpc.Forward/SendMetrics`` and field numbers, so Go locals and
proxies interoperate — feeds the device metric table: counters +=,
gauge last-write, histogram centroids through the batched digest merge,
HLL register unions.  Stubs are hand-wired generic gRPC handlers over
protoc-generated messages (veneur_tpu/forward/gen), no grpc_tools
needed.
"""

from __future__ import annotations

import contextlib
import logging
import struct
from concurrent import futures

import numpy as np

from veneur_tpu.core.flusher import ForwardBlock, ForwardRow
from veneur_tpu.core.table import MetricTable
from veneur_tpu.forward import hll_codec
from veneur_tpu.forward.gen import forward_pb2, metric_pb2, tdigest_pb2
from veneur_tpu.ops import segment
from veneur_tpu.protocol import dogstatsd as dsd

try:
    import grpc
except ImportError:  # pragma: no cover
    grpc = None

from google.protobuf import empty_pb2

log = logging.getLogger("veneur_tpu.grpc")

_METHOD = "/forwardrpc.Forward/SendMetrics"

# cross-tier flush trace propagation: the same (trace_id, span_id)
# pair the HTTP wire carries in http_import.TRACE_HEADER rides gRPC
# as invocation metadata (keys must be lowercase ASCII).  Old peers
# ignore unknown metadata — fail-open by construction.
TRACE_ID_KEY = "veneur-trace-id"
SPAN_ID_KEY = "veneur-span-id"

# drain-and-handoff: a terminating local flags its final interval's
# wires so the receiving global accepts them past its normal interval
# cutoff and books them under a drain protocol in the ledger.  Old
# peers ignore the key — a drained wire degrades to a normal import.
DRAIN_KEY = "veneur-drain"

# spool-and-replay: a local that rode out a destination outage flags
# the replayed wires so the recovered global accepts them past its
# interval cutoff and books them under a replay protocol in the
# ledger.  Old peers ignore the key — a replayed wire degrades to a
# normal import.
REPLAY_KEY = "veneur-replay"

# crash recovery: a restarted node replays its predecessor's staged
# checkpoint and flags the wire with the segment's recovery id
# (``<incarnation>:<seq>``) so the receiving global accepts it past
# cutoff under a recovery protocol AND deduplicates a double-recovery
# by id — replayed-at-least-once at the wire, counted-exactly-once in
# the table.  Old peers ignore the key (degrades to a normal import).
RECOVERY_KEY = "veneur-recovery"

# scale-out arc handoff: an incumbent global shedding keyspace arcs to
# a new member flags the shipped rows so the receiver books them as a
# rebalance arrival (``grpc-import-handoff``), not organic traffic.
HANDOFF_KEY = "veneur-handoff"


def decode_drain_metadata(metadata) -> bool:
    """True when the wire is a shutdown drain handoff; False when the
    key is absent/malformed — a bad flag never rejects an import."""
    try:
        md = {k: v for k, v in (metadata or ())}
        return md.get(DRAIN_KEY, "") == "1"
    except (TypeError, ValueError):
        return False


def decode_replay_metadata(metadata) -> bool:
    """True when the wire is a spool replay after an outage; False
    when the key is absent/malformed — a bad flag never rejects an
    import (fail-open, same stance as the drain flag)."""
    try:
        md = {k: v for k, v in (metadata or ())}
        return md.get(REPLAY_KEY, "") == "1"
    except (TypeError, ValueError):
        return False


def decode_recovery_metadata(metadata) -> str:
    """The wire's recovery id (``incarnation:seq``) or "" when the
    key is absent/malformed — fail-open like the drain flag, so a bad
    id degrades to a normal (non-deduplicated) import rather than a
    rejection."""
    try:
        md = {k: v for k, v in (metadata or ())}
        rid = md.get(RECOVERY_KEY, "")
        return rid if ":" in rid else ""
    except (TypeError, ValueError):
        return ""


def decode_handoff_metadata(metadata) -> bool:
    """True when the wire is a scale-out arc handoff; False when the
    key is absent/malformed (fail-open)."""
    try:
        md = {k: v for k, v in (metadata or ())}
        return md.get(HANDOFF_KEY, "") == "1"
    except (TypeError, ValueError):
        return False


def decode_trace_metadata(metadata) -> tuple[int, int]:
    """(trace_id, span_id) from invocation metadata; (0, 0) when
    absent/malformed — a bad trace context never rejects an import."""
    try:
        md = {k: v for k, v in (metadata or ())}
        tid = int(md.get(TRACE_ID_KEY, 0))
        sid = int(md.get(SPAN_ID_KEY, 0))
    except (TypeError, ValueError):
        return 0, 0
    if tid <= 0 or sid <= 0:
        return 0, 0
    return tid, sid


def wire_metadata(trace_context: tuple[int, int] | None,
                  drain: bool = False):
    """Invocation metadata of a forward wire: the sending span's ids
    when both are set, and the drain flag."""
    md = []
    if trace_context and trace_context[0] and trace_context[1]:
        md = [(TRACE_ID_KEY, str(trace_context[0])),
              (SPAN_ID_KEY, str(trace_context[1]))]
    if drain:
        md.append((DRAIN_KEY, "1"))
    return md or None

_TYPE_TO_PB = {dsd.COUNTER: metric_pb2.Counter,
               dsd.GAUGE: metric_pb2.Gauge,
               dsd.HISTOGRAM: metric_pb2.Histogram,
               dsd.TIMER: metric_pb2.Timer,
               dsd.SET: metric_pb2.Set}
_PB_TO_TYPE = {v: k for k, v in _TYPE_TO_PB.items()}
_SCOPE_TO_PB = {dsd.SCOPE_DEFAULT: metric_pb2.Mixed,
                dsd.SCOPE_LOCAL: metric_pb2.Local,
                dsd.SCOPE_GLOBAL: metric_pb2.Global}
_PB_TO_SCOPE = {v: k for k, v in _SCOPE_TO_PB.items()}


# ----------------------------------------------------------------------
# ForwardBlock | ForwardRow -> MetricList wire

# One live centroid on the wire: MergingDigestData field 1, a Centroid
# of 18 bytes holding mean (field 1) and weight (field 2) as doubles.
_CENTROID = np.dtype([("tag", "u1"), ("len", "u1"),
                      ("mean_tag", "u1"), ("mean", "<f8"),
                      ("weight_tag", "u1"), ("weight", "<f8")])
# The digest's scalar doubles after its centroids: compression, min,
# max, reciprocalSum (fields 2-5).
_DIGEST_TAIL = np.dtype([("t2", "u1"), ("compression", "<f8"),
                         ("t3", "u1"), ("min", "<f8"),
                         ("t4", "u1"), ("max", "<f8"),
                         ("t5", "u1"), ("rsum", "<f8")])
_F64 = struct.Struct("<d")
_U64 = (1 << 64) - 1


_VARINT_1 = tuple(bytes((n,)) for n in range(0x80))


def _varint(n: int) -> bytes:
    if n < 0x80:
        return _VARINT_1[n]
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field(tag: bytes, payload: bytes) -> bytes:
    """A length-delimited field."""
    return tag + _varint(len(payload)) + payload


def _varints(n: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Each (non-negative, under 2**35) value's varint, left-aligned
    in a row of five bytes, and which of the five it takes."""
    sh = n.astype(np.int64)[:, None] >> (7 * np.arange(5))
    more = sh >> 7 != 0
    by = (sh & 0x7F | more << 7).astype(np.uint8)
    keep = np.ones(by.shape, bool)
    keep[:, 1:] = more[:, :-1]
    return by, keep


def _squeeze(by: np.ndarray, keep: np.ndarray
             ) -> tuple[np.ndarray, np.ndarray]:
    """The kept bytes of ``by`` (one record a row), laid end to end,
    and the offset of each row's record in them (one more offset than
    rows)."""
    at = np.zeros(len(by) + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=at[1:])
    return by[keep], at


def _lay(*cols) -> tuple[bytes, list[int]]:
    """One short record a row: ``cols`` are constant bytes (a tag) and
    ``_varints`` results, in wire order.  Bytes and offsets as
    ``_squeeze`` gives them."""
    n = next(len(c[0]) for c in cols if not isinstance(c, int))
    by, at = _squeeze(
        np.concatenate([np.full((n, 1), c, np.uint8)
                        if isinstance(c, int) else c[0]
                        for c in cols], axis=1),
        np.concatenate([np.ones((n, 1), bool)
                        if isinstance(c, int) else c[1]
                        for c in cols], axis=1))
    return by.tobytes(), at.tolist()


def _pack(rec: np.ndarray, doubles: tuple[str, ...]
          ) -> tuple[np.ndarray, np.ndarray]:
    """The bytes of wire records ``rec`` (as a u8 array) and the
    offset of each record in them (one more offset than records).
    Like protobuf's proto3 serializer, a field of ``doubles`` whose
    bits are all zero is left off the wire, its tag byte with it."""
    present = [rec[f].view("<u8") != 0 for f in doubles]
    by = rec.view(np.uint8).reshape(len(rec), rec.itemsize)
    if all(p.all() for p in present):
        return by.reshape(-1), np.arange(len(rec) + 1) * rec.itemsize
    keep = np.ones(by.shape, bool)
    for f, p in zip(doubles, present):
        at = rec.dtype.fields[f][1]
        keep[:, at - 1:at + 8] = p[:, None]
    return _squeeze(by, keep)


def _wire_ident(meta) -> tuple[bytes, bytes]:
    """A series' identity as the wire holds it: the Metric's bytes
    before its value (name, tags, type) and after it (scope).  Kept
    on the ``RowMeta``, so a series is encoded once and not once an
    interval."""
    m = [_field(b"\x0a", meta.name.encode())] if meta.name else []
    m += [_field(b"\x12", t.encode()) for t in meta.tags]
    mtype = _TYPE_TO_PB[meta.type]
    if mtype:
        m.append(b"\x18" + _VARINT_1[mtype])
    scope = _SCOPE_TO_PB[meta.scope]
    meta.wire_ident = ident = (
        b"".join(m), b"\x48" + _VARINT_1[scope] if scope else b"")
    return ident


def _encode_scalars(blk: ForwardBlock, idents: list,
                    out: list) -> None:
    """The Metrics of a counter or gauge block, appended to ``out``."""
    v = np.asarray(blk.values, np.float64)
    if blk.kind == "counter":
        # the reference wire type is int64 (metric.proto CounterValue)
        bad = ~(np.isfinite(v) & (v >= -2.0 ** 63) & (v < 2.0 ** 63))
        if bad.any():
            name = blk.metas[int(np.flatnonzero(bad)[0])].name
            raise ValueError(f"counter {name} out of int64")
        vals = [b"\x08" + _varint(x & _U64) if x else b""
                for x in np.rint(v).astype(np.int64).tolist()]
        tag = b"\x2a"
    else:
        vals = [b"\x09" + x if any(x) else b""
                for x in map(_F64.pack, v.tolist())]
        tag = b"\x32"
    for (head, scope), val in zip(idents, vals):
        m = b"".join((head, tag, _VARINT_1[len(val)], val, scope))
        out += (b"\x0a", _varint(len(m)), m)


def _encode_digests(blk: ForwardBlock, compression: float,
                    idents: list, out: list) -> int:
    """The Metrics of a histogram block, appended to ``out``, and the
    count of live centroids in them, with no Python-level work per
    centroid: the live mask is taken over the whole block (a matrix,
    or flat planes where rows differ in width), every live centroid is
    laid into one buffer of wire records, and a row's
    ``main_centroids`` goes to the wire as a view of it."""
    means, weights, row_at = blk.digest_planes()
    live = np.flatnonzero(weights > 0)
    cent = np.empty(len(live), _CENTROID)
    cent["tag"], cent["mean_tag"], cent["weight_tag"] = 0x0A, 0x09, 0x11
    cent["mean"] = means[live]
    cent["weight"] = weights[live]
    cent["len"] = np.where(cent["mean"].view("<u8") != 0, 18, 9)
    cents, cent_at = _pack(cent, ("mean",))
    # the byte at which each row's records start, and the last one ends
    cent_at = cent_at[np.searchsorted(live, row_at)]

    stats = np.asarray(blk.stats)
    tail = np.empty(len(blk), _DIGEST_TAIL)
    tail["t2"], tail["t3"], tail["t4"], tail["t5"] = (0x11, 0x19, 0x21,
                                                      0x29)
    tail["compression"] = compression
    tail["min"] = stats[:, segment.STAT_MIN]
    tail["max"] = stats[:, segment.STAT_MAX]
    tail["rsum"] = stats[:, segment.STAT_RSUM]
    tails, tail_at = _pack(tail, ("compression", "min", "max", "rsum"))

    # Metric > HistogramValue (field 7) > MergingDigestData (field 1):
    # each length from the one inside it, every row's at once
    heads, scopes = zip(*idents)
    dlen = np.diff(cent_at) + np.diff(tail_at)
    dvar = _varints(dlen)
    hlen = 1 + dvar[1].sum(axis=1) + dlen
    hvar = _varints(hlen)
    mlen = (np.fromiter(map(len, heads), np.int64, len(heads))
            + np.fromiter(map(len, scopes), np.int64, len(heads))
            + 1 + hvar[1].sum(axis=1) + hlen)
    pre, pre_at = _lay(0x0A, _varints(mlen))
    mid, mid_at = _lay(0x3A, hvar, 0x0A, dvar)
    cents, tails = memoryview(cents), tails.tobytes()
    cent_at, tail_at = cent_at.tolist(), tail_at.tolist()
    for i, (head, scope) in enumerate(idents):
        j = i + 1
        out += (pre[pre_at[i]:pre_at[j]], head,
                mid[mid_at[i]:mid_at[j]], cents[cent_at[i]:cent_at[j]],
                tails[tail_at[i]:tail_at[j]], scope)
    return len(live)


def _encode_sets(blk: ForwardBlock, idents: list, out: list) -> None:
    """The Metrics of a set block, appended to ``out``: every row's
    dense sketch from one pass over the block's registers."""
    sketches = hll_codec.encode_dense_rows(blk.regs)
    # SetValue (field 8) > hyper_log_log (field 1), the same lengths
    # for every row
    slen = sketches.shape[1]
    value = (b"\x42" + _varint(1 + len(_varint(slen)) + slen)
             + b"\x0a" + _varint(slen))
    for (head, scope), sketch in zip(idents, sketches):
        out += (b"".join((b"\x0a", _varint(len(head) + len(value) + slen
                                           + len(scope)), head, value)),
                sketch, scope)


def _block_of(run: list[ForwardRow]) -> ForwardBlock:
    """Loose rows of one kind as a block: what arrives row by row
    (checkpoint replay, a shard's batch, the per-row reference flush)
    is encoded like everything else."""
    kind, metas = run[0].kind, [r.meta for r in run]
    if kind in ("counter", "gauge"):
        return ForwardBlock(kind, metas, values=np.asarray(
            [r.value for r in run], np.float64))
    if kind == "histo":
        # flat planes, so rows of unequal width need no second path
        return ForwardBlock.ragged_histo(
            metas, np.stack([np.asarray(r.stats) for r in run]),
            [np.asarray(r.means) for r in run],
            [np.asarray(r.weights) for r in run])
    if kind == "set":
        for r in run:
            if np.shape(r.regs) != (hll_codec.M,):
                raise hll_codec.HLLCodecError(
                    f"bad register shape {np.shape(r.regs)}")
        return ForwardBlock(kind, metas, regs=np.stack(
            [np.asarray(r.regs, np.uint8) for r in run]))
    return ForwardBlock(kind, metas)  # no such kind: the encoder's to say


def _as_blocks(rows) -> tuple[list[ForwardBlock], int]:
    """``rows`` (a ``ForwardList``, or any sequence of blocks and
    rows) as blocks in the order given, each run of loose rows of one
    kind grouped into a block; and the count of rows that came
    loose."""
    blocks: list[ForwardBlock] = []
    run: list[ForwardRow] = []
    loose = 0
    for part in getattr(rows, "parts", rows):
        is_row = not isinstance(part, ForwardBlock)
        if run and not (is_row and part.kind == run[0].kind):
            blocks.append(_block_of(run))
            run = []
        if is_row:
            run.append(part)
            loose += 1
        else:
            blocks.append(part)
    if run:
        blocks.append(_block_of(run))
    return blocks, loose


def encode_metric_list(rows, compression: float = 100.0,
                       counts: dict | None = None) -> tuple[bytes, int]:
    """The forward wire of ``rows``: a serialized ``MetricList`` (the
    sending half of worker.go:181 ForwardableMetrics -> metricpb), and
    the count of live centroids in it.  Written by hand from the
    blocks' arrays, byte for byte what protobuf's serializer gives for
    the same message, metrics in the order given.  ``rows`` is a
    flush's ``ForwardList`` or any sequence of ``ForwardBlock``s and
    ``ForwardRow``s.  ``compression`` is the table's configured digest
    compression (a Go global sizes its MergingDigest from this
    field).  ``counts``, where given, takes what the encode worked
    on: ``rows_block`` and ``rows_loose`` (rows that came in blocks,
    and row by row), ``ident_cached`` (series whose identity bytes
    were there from an earlier encode), ``rows_histo``, ``rows_sets``
    and ``rows_scalars``."""
    blocks, loose = _as_blocks(rows)
    out: list = []
    centroids = cached = 0
    by_kind = dict.fromkeys(("histo", "set", "counter", "gauge"), 0)
    for blk in blocks:
        if blk.kind not in by_kind:
            raise ValueError(f"unknown forward kind {blk.kind}")
        by_kind[blk.kind] += len(blk)
        idents = [m.wire_ident for m in blk.metas]
        for i, ident in enumerate(idents):
            if ident is None:
                idents[i] = _wire_ident(blk.metas[i])
            else:
                cached += 1
        if not idents:
            continue
        if blk.kind == "histo":
            centroids += _encode_digests(blk, float(compression),
                                         idents, out)
        elif blk.kind == "set":
            _encode_sets(blk, idents, out)
        else:
            _encode_scalars(blk, idents, out)
    if counts is not None:
        n = sum(by_kind.values())
        counts.update(
            rows_block=n - loose, rows_loose=loose, ident_cached=cached,
            rows_histo=by_kind["histo"], rows_sets=by_kind["set"],
            rows_scalars=by_kind["counter"] + by_kind["gauge"])
    return b"".join(out), centroids


def rows_to_metric_list(rows: list[ForwardRow],
                        compression: float = 100.0
                        ) -> forward_pb2.MetricList:
    """``encode_metric_list``'s wire as a message object."""
    return forward_pb2.MetricList.FromString(
        encode_metric_list(rows, compression)[0])


def row_to_metric(r: ForwardRow,
                  compression: float = 100.0) -> metric_pb2.Metric:
    """One row of ``encode_metric_list``'s wire as a message object."""
    return rows_to_metric_list([r], compression).metrics[0]


def apply_metric(table: MetricTable, m: metric_pb2.Metric) -> bool:
    """Merge one received metricpb.Metric into the table (the receive
    half: worker.go:438 ImportMetricGRPC semantics)."""
    mtype = _PB_TO_TYPE.get(m.type)
    tags = tuple(m.tags)
    scope = _PB_TO_SCOPE.get(m.scope, dsd.SCOPE_DEFAULT)
    which = m.WhichOneof("value")
    if which == "counter":
        return table.import_counter(m.name, tags, float(m.counter.value))
    if which == "gauge":
        v = float(m.gauge.value)
        if not np.isfinite(v):
            raise ValueError("non-finite gauge value in gRPC import")
        return table.import_gauge(m.name, tags, v)
    if which == "histogram":
        d = m.histogram.t_digest
        means = np.asarray([c.mean for c in d.main_centroids],
                           np.float32)
        weights = np.asarray([c.weight for c in d.main_centroids],
                             np.float32)
        # same finiteness gate as the native bytes path and the DSD
        # parse path: one NaN poisons a whole row's aggregates
        if not (np.isfinite(means).all() and np.isfinite(weights).all()
                and (weights >= 0).all()):
            raise ValueError("non-finite centroids in gRPC import")
        total_w = float(weights.sum())
        if total_w and not (np.isfinite(d.min) and np.isfinite(d.max)
                            and np.isfinite(d.reciprocalSum)):
            raise ValueError("non-finite digest stats in gRPC import")
        # the Go digest's Sum() is sum(mean*weight)
        # (merging_digest.go:349); min/max/reciprocalSum ride in the
        # proto itself
        total_sum = float((means * weights).sum())
        stats = np.asarray(
            [total_w,
             d.min if total_w else segment.STAT_MIN_EMPTY,
             d.max if total_w else segment.STAT_MAX_EMPTY,
             total_sum, d.reciprocalSum if total_w else 0.0],
            np.float32)
        if mtype not in (dsd.HISTOGRAM, dsd.TIMER):
            mtype = dsd.HISTOGRAM
        return table.import_histo(m.name, mtype, tags, stats, means,
                                  weights, scope=scope)
    if which == "set":
        regs = hll_codec.decode(bytes(m.set.hyper_log_log))
        return table.import_set(m.name, tags, regs, scope=scope)
    log.warning("import metric %s with empty value oneof", m.name)
    return False


def apply_metric_list(table: MetricTable,
                      ml: forward_pb2.MetricList) -> tuple[int, int]:
    """Returns (accepted, dropped).  Per-item isolation as on the HTTP
    import path."""
    accepted = dropped = 0
    for m in ml.metrics:
        try:
            ok = apply_metric(table, m)
        except (ValueError, KeyError, hll_codec.HLLCodecError) as e:
            log.warning("dropping bad gRPC import item %s: %s",
                        m.name, e)
            dropped += 1
            continue
        accepted += int(ok)
        dropped += int(not ok)
    return accepted, dropped


# ----------------------------------------------------------------------
# columnar wire decode (native vtpu_metriclist_decode)


import threading as _threading

# Per-thread decode buffer scratch — policy in _decode_native's
# docstring.
_decode_scratch = _threading.local()


def _decode_call(lib, buf, n, cap_m, cap_c, cap_t, cols,
                 needed) -> int:
    import ctypes

    def p(a, ct):
        return a.ctypes.data_as(ctypes.POINTER(ct))

    return lib.vtpu_metriclist_decode(
        p(buf, ctypes.c_uint8), n, cap_m, cap_c, cap_t,
        p(cols["name_off"], ctypes.c_int64),
        p(cols["name_len"], ctypes.c_int32),
        p(cols["kind"], ctypes.c_uint8),
        p(cols["mtype"], ctypes.c_int32),
        p(cols["scope"], ctypes.c_int32),
        p(cols["scalar"], ctypes.c_double),
        p(cols["dstats"], ctypes.c_double),
        p(cols["cent_start"], ctypes.c_int64),
        p(cols["cent_cnt"], ctypes.c_int32),
        p(cols["means"], ctypes.c_float),
        p(cols["weights"], ctypes.c_float),
        p(cols["tag_start"], ctypes.c_int64),
        p(cols["tag_cnt"], ctypes.c_int32),
        p(cols["tag_off"], ctypes.c_int64),
        p(cols["tag_len"], ctypes.c_int32),
        p(cols["hll_off"], ctypes.c_int64),
        p(cols["hll_len"], ctypes.c_int32),
        p(needed, ctypes.c_int64))


_SCRATCH_MAX_BYTES = 32 << 20
# consecutive decodes needing <1/4 of the retained scratch before the
# high-water buffers are released (one giant wire must not pin its
# scratch for the life of the thread once traffic shrinks back)
_SCRATCH_SHRINK_AFTER = 8

_scratch_lock = _threading.Lock()
_scratch_bytes: dict[int, int] = {}  # thread ident -> retained bytes


def decode_scratch_bytes() -> int:
    """Total decode scratch retained across handler threads — the
    ``forward.decode_scratch_bytes`` gauge in /debug/vars."""
    with _scratch_lock:
        return sum(_scratch_bytes.values())


def _cols_nbytes(cols: dict) -> int:
    return sum(a.nbytes for a in cols.values()
               if isinstance(a, np.ndarray))


def _keep_scratch(cols: dict) -> None:
    nb = _cols_nbytes(cols)
    if nb <= _SCRATCH_MAX_BYTES:
        _decode_scratch.cols = cols
    else:
        _decode_scratch.cols = None
        nb = 0
    tid = _threading.get_ident()
    with _scratch_lock:
        if nb:
            _scratch_bytes[tid] = nb
        else:
            _scratch_bytes.pop(tid, None)
        if len(_scratch_bytes) > 32:
            # registry entries outlive their (dead) handler threads
            live = {t.ident for t in _threading.enumerate()}
            for t in [t for t in _scratch_bytes if t not in live]:
                del _scratch_bytes[t]


def _alloc_cols(cap_m: int, cap_c: int, cap_t: int) -> dict:
    return {
        "name_off": np.empty(cap_m, np.int64),
        "name_len": np.empty(cap_m, np.int32),
        "kind": np.empty(cap_m, np.uint8),
        "mtype": np.empty(cap_m, np.int32),
        "scope": np.empty(cap_m, np.int32),
        "scalar": np.empty(cap_m, np.float64),
        "dstats": np.empty((cap_m, 4), np.float64),
        "cent_start": np.empty(cap_m, np.int64),
        "cent_cnt": np.empty(cap_m, np.int32),
        "means": np.empty(cap_c, np.float32),
        "weights": np.empty(cap_c, np.float32),
        "tag_start": np.empty(cap_m, np.int64),
        "tag_cnt": np.empty(cap_m, np.int32),
        "tag_off": np.empty(cap_t, np.int64),
        "tag_len": np.empty(cap_t, np.int32),
        "hll_off": np.empty(cap_m, np.int64),
        "hll_len": np.empty(cap_m, np.int32),
    }


def _decode_native(lib, data: bytes):
    """Run the C++ wire walker, growing buffers once if the guess was
    small.  Returns the column dict, None when the wire is malformed
    (caller falls back to protobuf for its per-item isolation).

    Buffers come from a per-thread scratch cache: a steady-state
    global decodes same-sized wires from each peer every interval,
    and reallocating the ~15 column arrays per call profiled at
    ~100ms of a c4 interval.  Thread-local because concurrent gRPC
    handler threads need their own scratch; safe because
    apply_metric_list_bytes only reads the columns within the call
    (everything staged is a copy).  Scratch above _SCRATCH_MAX_BYTES
    is not retained — one near-max 64MB wire must not pin ~230MB of
    columns per handler thread forever."""
    n = len(data)
    buf = np.frombuffer(data, np.uint8)
    cap_m = max(256, n // 48)
    cap_c = max(1024, n // 18)
    cap_t = cap_m * 4
    needed = np.zeros(3, np.int64)
    cols = getattr(_decode_scratch, "cols", None)
    if cols is not None:
        oversized = (len(cols["name_off"]) > 4 * cap_m or
                     len(cols["means"]) > 4 * cap_c or
                     len(cols["tag_off"]) > 4 * cap_t)
        if oversized:
            streak = getattr(_decode_scratch, "oversized_streak", 0) + 1
            _decode_scratch.oversized_streak = streak
            if streak >= _SCRATCH_SHRINK_AFTER:
                cols = None  # release high-water scratch on shrink
                _decode_scratch.oversized_streak = 0
        else:
            _decode_scratch.oversized_streak = 0
    if (cols is None or len(cols["name_off"]) < cap_m or
            len(cols["means"]) < cap_c or
            len(cols["tag_off"]) < cap_t):
        cols = _alloc_cols(cap_m, cap_c, cap_t)
        _keep_scratch(cols)
    for _ in range(2):
        rc = _decode_call(lib, buf, n, len(cols["name_off"]),
                          len(cols["means"]), len(cols["tag_off"]),
                          cols, needed)
        if rc == -1:
            return None
        if rc >= 0:
            out = dict(cols)
            out["n"] = int(rc)
            return out
        # rc == -2: grow to the elementwise max of the exact need and
        # the size heuristic — exact-only buffers for a centroid-dense
        # wire would sit BELOW the next call's heuristic and be
        # evicted, re-walking every wire twice forever
        cols = _alloc_cols(max(int(needed[0]), cap_m, 1),
                           max(int(needed[1]), cap_c, 1),
                           max(int(needed[2]), cap_t, 1))
        _keep_scratch(cols)
    return None  # still over after the exact-size retry: give up


def decode_metric_list(data: bytes):
    """The LOCK-FREE half of apply_metric_list_bytes: native columnar
    wire decode + per-item identity keyhash, touching no table state.
    Handler threads run this OUTSIDE the server ingest lock, so the
    decode of cycle N+1's wires overlaps the device fold of cycle N
    (import pipelining — the _IntervalState double-buffer's host-side
    counterpart).  Returns the column dict or None (native library
    unavailable or malformed wire: caller takes the per-item protobuf
    fallback under the lock)."""
    from veneur_tpu import native
    lib = native.load()
    cols = _decode_native(lib, data) if lib is not None else None
    if cols is None:
        return None
    nm = cols["n"]
    if nm:
        import ctypes

        def p(a, ct):
            return a.ctypes.data_as(ctypes.POINTER(ct))

        buf = np.frombuffer(data, np.uint8)
        khash = np.empty(nm, np.uint64)
        lib.vtpu_metriclist_keyhash(
            p(buf, ctypes.c_uint8), nm,
            p(cols["name_off"], ctypes.c_int64),
            p(cols["name_len"], ctypes.c_int32),
            p(cols["kind"], ctypes.c_uint8),
            p(cols["mtype"], ctypes.c_int32),
            p(cols["scope"], ctypes.c_int32),
            p(cols["tag_start"], ctypes.c_int64),
            p(cols["tag_cnt"], ctypes.c_int32),
            p(cols["tag_off"], ctypes.c_int64),
            p(cols["tag_len"], ctypes.c_int32),
            p(khash, ctypes.c_uint64))
        cols["khash"] = khash
    return cols


_WIRE_PLAN_CACHE_MAX = 256


def _resolve_rows(table: MetricTable, data: bytes, cols: dict,
                  khash: np.ndarray) -> np.ndarray:
    """Map every item to its table row (or -1 overflow / -2 malformed).

    Steady-state fast path: a whole wire's khash vector keys a
    (wire-schema)->rows plan on the table, so a peer re-forwarding the
    same series set every interval resolves all rows with ONE dict get
    — no per-item Python at all.  Plans invalidate on compaction
    (``_reindex_epoch``); overflow drops recorded in a plan keep
    counting per sample on every replay, matching the uncached path."""
    nm = cols["n"]
    kind = cols["kind"][:nm]
    class_idx = {1: table.counter_idx, 2: table.gauge_idx,
                 3: table.histo_idx, 4: table.set_idx}
    epoch = getattr(table, "_reindex_epoch", 0)
    plan_cache = getattr(table, "_wire_plan_cache", None)
    pkey = khash.tobytes()
    if plan_cache is not None:
        hit = plan_cache.get(pkey)
        if hit is not None and hit[0] == epoch:
            rows, over_counts = hit[1], hit[2]
            for k, c in over_counts.items():
                class_idx[k].drops.add(c)
            return rows
    cache = table.import_row_cache
    khl = khash.tolist()
    rows = np.full(nm, -1, np.int64)
    over_counts: dict[int, int] = {}

    def _ident(i: int) -> tuple[str, tuple[str, ...]]:
        no, nl = int(cols["name_off"][i]), int(cols["name_len"][i])
        name = data[no:no + nl].decode()
        ts, tc = int(cols["tag_start"][i]), int(cols["tag_cnt"][i])
        tags = tuple(
            data[int(cols["tag_off"][ts + j]):
                 int(cols["tag_off"][ts + j]) +
                 int(cols["tag_len"][ts + j])].decode()
            for j in range(tc))
        return name, tags

    if len(cache) >= getattr(table, "import_row_cache_limit",
                             1 << 20):
        cache.clear()  # churning identities: rebound, self-rebuilds
    name_len = cols["name_len"]
    for i, h in enumerate(khl):
        ent = cache.get(h)
        had_pos = ent is not None and ent >= 0
        if ent is not None:
            if had_pos:
                # cheap collision guard on the 64-bit identity hash:
                # the cached entry carries the resolved name length;
                # a hit whose wire name length disagrees is a hash
                # collision between distinct series — fall through to
                # the slow path instead of silently merging them
                if (ent >> 32) == int(name_len[i]):
                    rows[i] = ent & 0xFFFFFFFF
                    continue
            else:
                rows[i] = ent
                if ent == -1:
                    # the slow path bumped overflow when it cached the
                    # drop; hits must keep counting per dropped sample
                    # or the operator counter undercounts vs the
                    # uncached path (every overflowing import counts)
                    idx = class_idx.get(int(kind[i]))
                    if idx is not None:
                        idx.drops.add(1)
                continue
        k = int(kind[i])
        row = None
        resolved = False
        try:
            name, tags = _ident(i)
            if k == 1:
                resolved = True
                row = table.import_counter_row(name, tags)
            elif k == 2:
                resolved = True
                row = table.import_gauge_row(name, tags)
            elif k == 3:
                mtype = _PB_TO_TYPE.get(int(cols["mtype"][i]))
                if mtype not in (dsd.HISTOGRAM, dsd.TIMER):
                    mtype = dsd.HISTOGRAM
                scope = _PB_TO_SCOPE.get(int(cols["scope"][i]),
                                         dsd.SCOPE_DEFAULT)
                resolved = True
                row = table.import_histo_row(name, mtype, tags, scope)
            elif k == 4:
                scope = _PB_TO_SCOPE.get(int(cols["scope"][i]),
                                         dsd.SCOPE_DEFAULT)
                resolved = True
                row = table.import_set_row(name, tags, scope)
            else:
                log.warning("import metric %s with empty value oneof",
                            name)
        except UnicodeDecodeError as e:
            log.warning("dropping bad gRPC import item: %s", e)
        # row None covers malformed identity, empty oneof AND class
        # overflow — all stable until the next compaction, which
        # clears the cache (overflow can only recover via compaction).
        # Overflow drops (-1, lookup ran and failed) keep counting
        # per sample on cache hits; malformed drops (-2) never
        # counted as overflow and must not start to.
        if row is None:
            rows[i] = -1 if resolved else -2
            # a collision-guard fallthrough that then overflows must
            # NOT evict the colliding series' live entry: the drop is
            # per-sample (lookup counted it), the cache entry stays
            # the surviving series'
            if not had_pos:
                cache[h] = rows[i]
        else:
            cache[h] = (int(name_len[i]) << 32) | int(row)
            rows[i] = int(row)

    if plan_cache is not None:
        # overflow (-1) rows were counted during this build (by
        # lookup or the ent==-1 branch above); plan replays repeat
        # those per-class counts so the operator counter keeps pace
        for k in (1, 2, 3, 4):
            c = int(((rows == -1) & (kind == k)).sum())
            if c:
                over_counts[k] = c
        if len(plan_cache) >= _WIRE_PLAN_CACHE_MAX:
            plan_cache.clear()
        plan_cache[pkey] = (epoch, rows, over_counts)
    return rows


def apply_decoded(table: MetricTable, data: bytes, cols: dict,
                  step=lambda name: contextlib.nullcontext()
                  ) -> tuple[int, int]:
    """The LOCKED half: resolve rows through the plan/row caches and
    stage every value with vectorized batch appliers.  Value-level
    validity (finiteness, HLL codec) is re-checked per wire — only
    series IDENTITY is cached, so a gauge that is NaN this interval
    and finite the next is not penalized; a wire's dense sketches are
    checked and unioned in one native pass (``_apply_sets``).
    ``step(name)`` times the three parts (``resolve``, ``digests``,
    ``sets``) and yields the part's span or None: the import handler
    passes its ``import.apply`` span's."""
    nm = cols["n"]
    if nm == 0:
        return 0, 0
    kind = cols["kind"][:nm]
    # a mesh table takes a wire whole on one shard, the next in turn
    getattr(table, "begin_wire", lambda: None)()
    with step("resolve"):
        rows = _resolve_rows(table, data, cols, cols["khash"])
    valid = rows >= 0
    dropped = int((~valid).sum())
    with step("digests"):
        acc, bad = _apply_scalars_and_digests(table, cols, kind, rows,
                                              valid)
    with step("sets") as sp:
        acc_s, bad_s = _apply_sets(table, data, cols, kind, rows, valid,
                                   span=sp)
    return acc + acc_s, dropped + bad + bad_s


def _apply_scalars_and_digests(table: MetricTable, cols: dict,
                               kind: np.ndarray, rows: np.ndarray,
                               valid: np.ndarray) -> tuple[int, int]:
    """Counters, gauges and histograms of a decoded wire, staged by
    the batch appliers; (accepted, dropped for their values)."""
    nm = cols["n"]
    dropped = 0
    accepted = 0

    # counters: += accumulate (no finiteness gate, matching
    # import_counter / reference Counter.Merge)
    selc = np.nonzero(valid & (kind == 1))[0]
    if len(selc):
        table.import_counter_batch(rows[selc], cols["scalar"][selc])
        accepted += len(selc)

    # gauges: last-write-wins in wire order; non-finite values drop
    # per wire (value-level, never cached)
    selg = np.nonzero(valid & (kind == 2))[0]
    if len(selg):
        vals = cols["scalar"][selg]
        fin = np.isfinite(vals)
        bad = int((~fin).sum())
        if bad:
            log.warning("dropping %d non-finite gauge imports", bad)
            dropped += bad
        if fin.any():
            table.import_gauge_batch(rows[selg][fin], vals[fin])
            accepted += int(fin.sum())

    # histograms: per-metric centroid aggregates in one vectorized
    # reduceat pass, then one batched staging append
    means, weights = cols["means"], cols["weights"]
    dstats = cols["dstats"]
    cs = cols["cent_start"][:nm]
    cc = cols["cent_cnt"][:nm]
    selh = np.nonzero(valid & (kind == 3))[0]
    if len(selh):
        w_tot = np.zeros(len(selh), np.float64)
        s_tot = np.zeros(len(selh), np.float64)
        with_c = cc[selh] > 0
        if with_c.any():
            # paired (start, end) reduceat segments: a metric whose
            # oneof value was overwritten after its histogram field
            # (proto3 last-one-wins) leaves ORPHANED centroids between
            # selected segments — plain start-only reduceat would
            # sweep them into the preceding histogram's sums.  The +1
            # zero pad keeps the final end index in reduceat's valid
            # range.
            starts = cs[selh][with_c]
            ends = starts + cc[selh][with_c]
            end_max = int(ends[-1])
            w64 = np.zeros(end_max + 1, np.float64)
            w64[:end_max] = weights[:end_max]
            wm64 = w64.copy()
            wm64[:end_max] *= means[:end_max]
            pairs = np.empty(2 * len(starts), np.int64)
            pairs[0::2] = starts
            pairs[1::2] = ends
            w_tot[with_c] = np.add.reduceat(w64, pairs)[0::2]
            s_tot[with_c] = np.add.reduceat(wm64, pairs)[0::2]
        dmin = dstats[selh, 0]
        dmax = dstats[selh, 1]
        drsum = dstats[selh, 2]
        has_w = w_tot != 0  # truthiness of the old per-item `if wt`
        ok_h = (np.isfinite(w_tot) & np.isfinite(s_tot) &
                (~has_w | (np.isfinite(dmin) & np.isfinite(dmax) &
                           np.isfinite(drsum))))
        bad = int((~ok_h).sum())
        if bad:
            log.warning("dropping %d non-finite digest imports", bad)
            dropped += bad
        if ok_h.any():
            wt = w_tot[ok_h]
            hw = has_w[ok_h]
            stats_mat = np.empty((int(ok_h.sum()),
                                  segment.HISTO_STAT_COLS), np.float32)
            stats_mat[:, 0] = wt
            stats_mat[:, 1] = np.where(hw, dmin[ok_h],
                                       segment.STAT_MIN_EMPTY)
            stats_mat[:, 2] = np.where(hw, dmax[ok_h],
                                       segment.STAT_MAX_EMPTY)
            stats_mat[:, 3] = s_tot[ok_h]
            stats_mat[:, 4] = np.where(hw, drsum[ok_h], 0.0)
            sel_ok = selh[ok_h]
            cnts = cc[sel_ok]
            rep_rows = np.repeat(rows[sel_ok], cnts).astype(np.int32)
            total_c = int(cnts.sum())
            if total_c:
                # ragged gather indices without a per-metric arange:
                # position-within-group + repeated segment starts
                within = (np.arange(total_c, dtype=np.int64) -
                          np.repeat(np.cumsum(cnts) - cnts, cnts))
                take = np.repeat(cs[sel_ok].astype(np.int64),
                                 cnts) + within
            else:
                take = np.empty(0, np.int64)
            cm = means[take]
            cw = weights[take]
            live = (cw > 0) & np.isfinite(cm) & np.isfinite(cw)
            table.import_histo_batch(
                rows[sel_ok].astype(np.int32), stats_mat,
                rep_rows[live], cm[live], cw[live])
            accepted += int(ok_h.sum())
    return accepted, dropped


def _apply_sets(table: MetricTable, data: bytes, cols: dict,
                kind: np.ndarray, rows: np.ndarray,
                valid: np.ndarray, span=None) -> tuple[int, int]:
    """The sketches of a decoded wire, unioned into their rows' host
    planes; (accepted, dropped).  A ``MetricTable`` takes the wire's
    dense sketches in one native pass (``import_set_wire``: validated,
    unpacked and maxed into the import plane with no interpreter
    lock held), and so does a ``ShardedTable``, into the host plane
    of the wire's shard; the HLL codec decode stays per item
    (value-level) for what that pass hands back (a sparse sketch from
    a Go local, a malformed one) and for a table without the batch
    entry.  Row resolution and name/tag decode are skipped on cache
    hits either way.  ``span`` (``import.apply.sets``) is told how
    many sketches there were and how many went one by one."""
    accepted = dropped = 0
    sels = np.nonzero(valid & (kind == 4))[0]
    loose = sels
    wire = getattr(table, "import_set_wire", None)
    if wire is not None and len(sels):
        status = wire(data, cols["hll_off"][sels], cols["hll_len"][sels],
                      rows[sels])
        loose = sels[status != 0]
        accepted = len(sels) - len(loose)
    for i in loose:
        ho, hl = int(cols["hll_off"][i]), int(cols["hll_len"][i])
        try:
            regs = hll_codec.decode(data[ho:ho + hl])
            table.import_set_at(int(rows[i]), regs)
            accepted += 1
        except (ValueError, hll_codec.HLLCodecError) as e:
            log.warning("dropping bad gRPC import item: %s", e)
            dropped += 1
    if span is not None:
        span.add_tag("planes", str(len(sels)))
        span.add_tag("planes_loose", str(len(loose)))
    return accepted, dropped


def wire_row_counts(cols: dict | None, pb=None) -> dict[str, int]:
    """What a received wire holds, by class, for the ``import.apply``
    span's tags: the same three row counts the sender's
    ``forward.encode`` span carries, and the centroids of its
    digests.  From the decoded columns, or from the protobuf on the
    fallback path."""
    if cols is not None:
        nm = cols["n"]
        kind = cols["kind"][:nm]
        by = np.bincount(kind, minlength=5)
        return {"rows_histo": int(by[3]), "rows_sets": int(by[4]),
                "rows_scalars": int(by[1] + by[2]),
                "centroids": int(cols["cent_cnt"][:nm][kind == 3].sum())}
    which = [m.WhichOneof("value") for m in pb.metrics]
    return {"rows_histo": which.count("histogram"),
            "rows_sets": which.count("set"),
            "rows_scalars": which.count("counter") + which.count("gauge"),
            "centroids": sum(len(m.histogram.t_digest.main_centroids)
                             for m, w in zip(pb.metrics, which)
                             if w == "histogram")}


def apply_metric_list_bytes(table: MetricTable,
                            data: bytes) -> tuple[int, int]:
    """apply_metric_list from the RAW wire: columnar native decode +
    hash-cached row resolution + batched staging.

    One upb Metric object per item with per-centroid Python traversal
    was ~60% of the global tier's import cost; the first columnar
    rewrite left a per-item Python loop (name/tag decode, tuple key,
    dict lookup) that profiled at ~700ms of the c4 interval.  The
    native decoder emits an import-identity hash per item
    (vtpu_metriclist_keyhash); ``table.import_row_cache`` maps one
    hash to a row and the wire-level plan cache (_resolve_rows) maps
    a whole repeated wire to its row vector in one dict get.

    This serial form runs decode and apply back to back; the
    ImportServer splits them (decode_metric_list outside the ingest
    lock, apply_decoded inside) so wire decode pipelines against the
    device fold.  Falls back to the protobuf path when the native
    library is unavailable or the wire is malformed (per-item
    isolation matters more than speed there)."""
    cols = decode_metric_list(data)
    if cols is None:
        return apply_metric_list(table,
                                 forward_pb2.MetricList.FromString(data))
    return apply_decoded(table, data, cols)


# ----------------------------------------------------------------------
# server (importsrv equivalent)

# Handler threads of one listener.  The reference gives each call a
# goroutine; here a handler's fold runs under the server's ingest lock
# and the interpreter's, so threads past the few that can decode ahead
# of the lock only move a burst's wait from the executor's queue to
# ``import.lock_wait``.  Measured on the v5e with 64 locals' calls of
# 4.3 MB inside 0.75 s (PERF.md, finding 34.3): a pool of 64 against
# this one lengthened an interval's longest call from 0.42-0.61 s to
# 0.51-0.75 s and its last acknowledgement from tick + 1.25-1.44 s to
# tick + 1.35-1.58 s, and left the flush where it was.  A call waits
# for a worker at most as long as the burst takes to fold, which has
# to stay under the locals' ``forward_timeout`` whatever the pool.
IMPORT_WORKERS = 8


class ImportServer:
    """gRPC listener merging forwarded MetricLists into a table.

    The role of importsrv.Server (importsrv/server.go:44) — with the
    worker fan-out replaced by the device table behind the server's
    ingest lock.
    """

    def __init__(self, server, address: str = "127.0.0.1:0",
                 credentials=None):
        """``server`` is the core Server (provides .table/.lock/.bump);
        ``address`` host:port, port 0 for ephemeral."""
        if grpc is None:  # pragma: no cover
            raise RuntimeError("grpcio unavailable")
        self._core = server
        self._grpc = grpc.server(
            futures.ThreadPoolExecutor(max_workers=IMPORT_WORKERS),
            options=[("grpc.max_receive_message_length",
                      64 * 1024 * 1024)])
        from veneur_tpu.protocol.gen import (dogstatsd_grpc_pb2,
                                             health_pb2, ssf_pb2)
        self._health_pb2 = health_pb2
        self._dsd_pb2 = dogstatsd_grpc_pb2
        # one listener, four services — the reference serves forward
        # import, SSF spans, DogStatsD packets and grpc health on the
        # same port (networking.go:295-358 startGRPCTCP)
        handlers = (
            grpc.method_handlers_generic_handler(
                "forwardrpc.Forward",
                {"SendMetrics": grpc.unary_unary_rpc_method_handler(
                    self._send_metrics,
                    # raw bytes: the columnar native decoder walks the
                    # wire itself (apply_metric_list_bytes); protobuf
                    # parse happens only on its fallback path
                    request_deserializer=lambda b: b,
                    response_serializer=(
                        empty_pb2.Empty.SerializeToString))}),
            grpc.method_handlers_generic_handler(
                "ssf.SSFGRPC",
                {"SendSpan": grpc.unary_unary_rpc_method_handler(
                    self._send_span,
                    request_deserializer=ssf_pb2.SSFSpan.FromString,
                    # ssf.Empty — zero fields, empty encoding
                    response_serializer=lambda _: b"")}),
            grpc.method_handlers_generic_handler(
                "dogstatsd.DogstatsdGRPC",
                {"SendPacket": grpc.unary_unary_rpc_method_handler(
                    self._send_packet,
                    request_deserializer=(
                        dogstatsd_grpc_pb2.DogstatsdPacket.FromString),
                    response_serializer=lambda _: b"")}),
            grpc.method_handlers_generic_handler(
                "grpc.health.v1.Health",
                {"Check": grpc.unary_unary_rpc_method_handler(
                    self._health_check,
                    request_deserializer=(
                        health_pb2.HealthCheckRequest.FromString),
                    response_serializer=(
                        health_pb2.HealthCheckResponse
                        .SerializeToString))}),
        )
        self._grpc.add_generic_rpc_handlers(handlers)
        if credentials is not None:
            self.port = self._grpc.add_secure_port(address, credentials)
        else:
            self.port = self._grpc.add_insecure_port(address)

    def _send_metrics(self, request, context):
        core = self._core
        md = context.invocation_metadata()
        with core.import_span("grpc", *decode_trace_metadata(md)) as imp:
            self._import_wire(request, md, imp)
        return empty_pb2.Empty()

    def _import_wire(self, request, md, imp) -> None:
        core = self._core
        drain = decode_drain_metadata(md)
        replay = decode_replay_metadata(md)
        recovery_id = decode_recovery_metadata(md)
        handoff = decode_handoff_metadata(md)
        ledger = getattr(core, "ledger", None)
        # decode outside the ingest lock: while another handler's
        # interval fold holds it (or _apply_staged runs the device
        # merge), this thread's wire decode proceeds in parallel —
        # cycle N+1 decode overlaps cycle N fold
        with imp.step("decode"):
            cols = decode_metric_list(request)
            pb = (forward_pb2.MetricList.FromString(request)
                  if cols is None else None)
        counts = wire_row_counts(cols, pb)
        with imp.step("lock_wait"):
            core.lock.acquire()
        try:
            with imp.step("apply") as sp:
                for k, v in counts.items():
                    sp.add_tag(k, str(v))
                # crash-recovery dedup, atomic with the apply: a
                # segment replayed twice (restart raced, or the
                # replayer retried a timed-out send that actually
                # landed) is counted ONCE
                if recovery_id is not None and recovery_id:
                    seen = getattr(core, "_recovery_seen", None)
                    if seen is not None:
                        if recovery_id in seen:
                            core.stats["recovery_wires_deduped"] = (
                                core.stats.get(
                                    "recovery_wires_deduped", 0) + 1)
                            return
                        seen.add(recovery_id)
                ov0 = core.table.overflow_total() if ledger else 0
                if cols is None:
                    acc, dropped = apply_metric_list(core.table, pb)
                else:
                    acc, dropped = apply_decoded(
                        core.table, request, cols,
                        step=lambda name: imp.step(f"apply.{name}", sp))
                if ledger is not None:
                    # the overflow delta splits this wire's drops into
                    # overflow (the table counted them) vs invalid
                    # (malformed/non-finite, dropped before the table)
                    ov = core.table.overflow_total() - ov0
                    proto = ("grpc-import-recovery" if recovery_id
                             else "grpc-import-handoff" if handoff
                             else "grpc-import-drain" if drain
                             else "grpc-import-replay" if replay
                             else "grpc-import")
                    ledger.ingest(proto, processed=acc + dropped,
                                  staged=acc, overflow=ov,
                                  invalid=dropped - ov)
                    if recovery_id:
                        inc = recovery_id.split(":", 1)[0]
                        ledger.recover(f"incarnation:{inc}", acc)
                    if handoff:
                        ledger.credit_reshard_received(acc)
                work = (core._maybe_device_step_locked()
                        if core.pipeline else None)
            if not core.pipeline:
                # a table that steps inline (the mesh table) does so
                # under the lock: the same step, timed as the same
                with imp.step("device_step"):
                    core._maybe_device_step_locked()
        finally:
            core.lock.release()
        with imp.step("device_step"):
            core._apply_staged(work)
        imp.result(acc, dropped, len(request))
        core.bump("imports_received", acc)
        core.bump("received_grpc", acc + dropped)
        if drain:
            # a peer's shutdown handoff: accepted past the interval
            # cutoff by construction (imports stage into the CURRENT
            # interval under core.lock), surfaced for the runbook
            core.bump("drain_wires_received")
            core.bump("drain_items_received", acc)
        if replay:
            # a peer rode out OUR outage in its spool: these samples
            # belong to an earlier interval but stage into the current
            # one (late-but-counted beats lost), surfaced for the
            # runbook
            core.bump("replay_wires_received")
            core.bump("replay_items_received", acc)
        if recovery_id:
            # a crashed peer's replacement replayed its checkpoint:
            # late mass from the dead incarnation's open interval,
            # accepted once (see the dedup above)
            core.bump("recovery_wires_received")
            core.bump("recovery_items_received", acc)
        if handoff:
            # an incumbent global shipped arcs this node now owns
            core.bump("handoff_wires_received")
            core.bump("handoff_items_received", acc)
        if dropped:
            core.bump("metrics_dropped", dropped)

    def _send_span(self, request, context):
        """ssf.SSFGRPC/SendSpan (reference networking.go:321
        grpcStatsServer.SendSpan -> handleSSF)."""
        from veneur_tpu.protocol import wire
        self._core.bump("received_ssf-grpc")
        self._core.handle_ssf(wire.normalize_span(request))
        return None  # ssf.Empty

    def _send_packet(self, request, context):
        """dogstatsd.DogstatsdGRPC/SendPacket (reference
        networking.go:314 SendPacket -> processMetricPacket: the body
        may hold many newline-separated lines)."""
        self._core.bump("received_dogstatsd-grpc")
        self._core.handle_packet(request.packetBytes)
        return None  # dogstatsd.Empty

    def _health_check(self, request, context):
        """grpc.health.v1.Health/Check; the reference marks service
        "veneur" SERVING (networking.go:340)."""
        pb = self._health_pb2.HealthCheckResponse
        if request.service in ("", "veneur"):
            return pb(status=pb.SERVING)
        return pb(status=pb.SERVICE_UNKNOWN)

    def start(self) -> None:
        self._grpc.start()

    def stop(self, grace: float = 0.5) -> None:
        self._grpc.stop(grace)


# ----------------------------------------------------------------------
# client (forwardGRPC equivalent)

class ForwardClient:
    """Dial-once client for the Forward service (flusher.go:499
    forwardGRPC: errors are dropped-and-counted, never retried within
    a flush)."""

    def __init__(self, target: str, timeout: float = 10.0,
                 credentials=None, compression: float = 100.0):
        if grpc is None:  # pragma: no cover
            raise RuntimeError("grpcio unavailable")
        target = target.removeprefix("http://")
        if credentials is not None:
            self._channel = grpc.secure_channel(target, credentials)
        else:
            self._channel = grpc.insecure_channel(target)
        self._timeout = timeout
        self._compression = compression
        # raw bytes: every sender serializes first (the single-global
        # forward so that encoding and the call are timed apart, the
        # columnar proxy because it re-encodes a destination's slice
        # as concatenated record spans)
        self._call_raw = self._channel.unary_unary(
            _METHOD,
            request_serializer=lambda b: b,
            response_deserializer=empty_pb2.Empty.FromString)

    def encode(self, rows: list[ForwardRow]) -> bytes:
        """The wire body of ``rows``: a serialized MetricList."""
        return encode_metric_list(rows, self._compression)[0]

    def send_wire(self, body: bytes, timeout: float | None = None,
                  metadata=None) -> None:
        """Send an already-serialized MetricList body verbatim.
        Raises grpc.RpcError on failure (caller drops-and-counts)."""
        self._call_raw(body, timeout=timeout or self._timeout,
                       metadata=metadata)

    def send(self, rows: list[ForwardRow],
             trace_context: tuple[int, int] | None = None,
             drain: bool = False) -> int:
        """Encode, then ship; returns the body's length in bytes.
        Raises grpc.RpcError on failure (caller drops-and-counts).
        ``trace_context`` = (trace_id, span_id) of the sending span,
        stamped as invocation metadata when set; ``drain`` flags the
        wire as a shutdown handoff."""
        body = self.encode(rows)
        self.send_wire(body,
                       metadata=wire_metadata(trace_context, drain))
        return len(body)

    def close(self) -> None:
        self._channel.close()
