"""The forward wire's encoder as it stood before the flush handed over
column blocks (PR 27's): one walk over a list of ``ForwardRow``s, the
digests' planes concatenated row by row, every name and tag encoded
anew, one ``encode_dense`` a set.  Kept, word for word, as the
reference that ``grpc_forward.encode_metric_list`` is compared with
byte for byte (``tests/test_forward_blocks.py``); nothing in the
program imports it."""

from __future__ import annotations

import struct

import numpy as np

from veneur_tpu.forward.gen import metric_pb2
from veneur_tpu.ops import segment
from veneur_tpu.protocol import dogstatsd as dsd

_TYPE_TO_PB = {dsd.COUNTER: metric_pb2.Counter,
               dsd.GAUGE: metric_pb2.Gauge,
               dsd.HISTOGRAM: metric_pb2.Histogram,
               dsd.TIMER: metric_pb2.Timer,
               dsd.SET: metric_pb2.Set}
_SCOPE_TO_PB = {dsd.SCOPE_DEFAULT: metric_pb2.Mixed,
                dsd.SCOPE_LOCAL: metric_pb2.Local,
                dsd.SCOPE_GLOBAL: metric_pb2.Global}
_HLL_P = 14
_HLL_M = 1 << _HLL_P


def encode_dense(regs: np.ndarray) -> bytes:
    """u8[16384] register plane -> dense axiomhq sketch bytes."""
    regs = np.asarray(regs, np.uint8)
    if regs.shape != (_HLL_M,):
        raise ValueError(f"bad register shape {regs.shape}")
    nib = np.minimum(regs, 15).astype(np.uint8)
    # even registers in the high nibble (registers.go:16 set offset 0)
    packed = (nib[0::2] << 4) | nib[1::2]
    header = bytes([1, _HLL_P, 0, 0])
    sz = (_HLL_M // 2).to_bytes(4, "big")
    return header + sz + packed.tobytes()


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


def _pack(rec: np.ndarray, doubles: tuple[str, ...]
          ) -> tuple[bytes, np.ndarray]:
    """The bytes of wire records ``rec`` and the offset of each record
    in them (one more offset than records).  Like protobuf's proto3
    serializer, a field of ``doubles`` whose bits are all zero is left
    off the wire, its tag byte with it."""
    present = [rec[f].view("<u8") != 0 for f in doubles]
    if all(p.all() for p in present):
        return rec.tobytes(), np.arange(len(rec) + 1) * rec.itemsize
    by = rec.view(np.uint8).reshape(len(rec), -1)
    keep = np.ones(by.shape, bool)
    for f, p in zip(doubles, present):
        at = rec.dtype.fields[f][1]
        keep[:, at - 1:at + 8] = p[:, None]
    return by[keep].tobytes(), np.concatenate(
        ([0], np.cumsum(keep.sum(axis=1))))


def _encode_digests(rows: list,
                    compression: float) -> tuple[list[bytes], int]:
    """The MergingDigestData bodies of ``rows`` (all histograms) and
    the count of live centroids in them, with no Python-level work per
    centroid: the rows' means and weights are concatenated once (so
    rows of unequal width need no second path), the live mask is taken
    over the whole flush, and every live centroid is laid into one
    buffer of wire records; a row's ``main_centroids`` is a slice of
    it."""
    weights = np.concatenate([np.asarray(r.weights) for r in rows])
    means = np.concatenate([np.asarray(r.means) for r in rows])
    live = np.flatnonzero(weights > 0)
    cent = np.empty(len(live), _CENTROID)
    cent["tag"], cent["mean_tag"], cent["weight_tag"] = 0x0A, 0x09, 0x11
    cent["mean"] = means[live]
    cent["weight"] = weights[live]
    cent["len"] = np.where(cent["mean"].view("<u8") != 0, 18, 9)
    cents, cent_at = _pack(cent, ("mean",))
    # the record at which each row starts, and the last one ends
    row_at = np.concatenate(
        ([0], np.cumsum([len(r.weights) for r in rows])))
    cent_at = cent_at[np.searchsorted(live, row_at)].tolist()

    stats = np.stack([np.asarray(r.stats) for r in rows])
    tail = np.empty(len(rows), _DIGEST_TAIL)
    tail["t2"], tail["t3"], tail["t4"], tail["t5"] = (0x11, 0x19, 0x21,
                                                      0x29)
    tail["compression"] = compression
    tail["min"] = stats[:, segment.STAT_MIN]
    tail["max"] = stats[:, segment.STAT_MAX]
    tail["rsum"] = stats[:, segment.STAT_RSUM]
    tails, tail_at = _pack(tail, ("compression", "min", "max", "rsum"))
    tail_at = tail_at.tolist()
    return ([cents[cent_at[i]:cent_at[i + 1]]
             + tails[tail_at[i]:tail_at[i + 1]]
             for i in range(len(rows))], len(live))


def encode_metric_list(rows: list,
                       compression: float = 100.0) -> tuple[bytes, int]:
    """The forward wire of ``rows``: a serialized ``MetricList`` (the
    sending half of worker.go:181 ForwardableMetrics -> metricpb), and
    the count of live centroids in it.  Written by hand from the rows'
    arrays, byte for byte what protobuf's serializer gives for the
    same message.  ``compression`` is the table's configured digest
    compression (a Go global sizes its MergingDigest from this
    field)."""
    histos = [r for r in rows if r.kind == "histo"]
    digests, centroids = (_encode_digests(histos, float(compression))
                          if histos else ((), 0))
    digests = iter(digests)
    out = []
    for r in rows:
        meta = r.meta
        m = [_field(b"\x0a", meta.name.encode())] if meta.name else []
        m += [_field(b"\x12", t.encode()) for t in meta.tags]
        mtype = _TYPE_TO_PB[meta.type]
        if mtype:
            m.append(b"\x18" + _VARINT_1[mtype])
        if r.kind == "counter":
            # the reference wire type is int64 (metric.proto
            # CounterValue)
            v = int(round(r.value))
            if not -(1 << 63) <= v < (1 << 63):
                raise ValueError(f"counter {meta.name} out of int64")
            m.append(_field(
                b"\x2a", b"\x08" + _varint(v & _U64) if v else b""))
        elif r.kind == "gauge":
            v = _F64.pack(float(r.value))
            m.append(_field(b"\x32", b"\x09" + v if any(v) else b""))
        elif r.kind == "histo":
            m.append(_field(b"\x3a", _field(b"\x0a", next(digests))))
        elif r.kind == "set":
            regs = encode_dense(r.regs)
            m.append(_field(
                b"\x42", _field(b"\x0a", regs) if regs else b""))
        else:
            raise ValueError(f"unknown forward kind {r.kind}")
        scope = _SCOPE_TO_PB[meta.scope]
        if scope:
            m.append(b"\x48" + _VARINT_1[scope])
        m = b"".join(m)
        out += (b"\x0a", _varint(len(m)), m)
    return b"".join(out), centroids
