"""gRPC forward tier tests: proto codec roundtrips, axiomhq HLL binary
compatibility (dense + sparse), and an in-process local -> global chain
over real loopback gRPC — the forwardGRPCFixture topology
(reference forward_grpc_test.go:19-57)."""

import time

import numpy as np
import pytest

pytest.importorskip("grpc")

from veneur_tpu.core.flusher import ForwardRow
from veneur_tpu.core.table import MetricTable, RowMeta, TableConfig
from veneur_tpu.forward import hll_codec
from veneur_tpu.forward.gen import forward_pb2, metric_pb2
from veneur_tpu.forward.grpc_forward import (_PB_TO_SCOPE, _PB_TO_TYPE,
                                             _SCOPE_TO_PB, _TYPE_TO_PB,
                                             apply_metric_list,
                                             decode_metric_list,
                                             encode_metric_list,
                                             row_to_metric,
                                             rows_to_metric_list)
from veneur_tpu.ops import hll, segment, tdigest
from veneur_tpu.protocol import dogstatsd as dsd
from veneur_tpu.utils import hashing


def _meta(name, mtype, tags=(), scope=dsd.SCOPE_DEFAULT):
    return RowMeta(name=name, tags=tuple(tags), scope=scope, type=mtype)


# ----------------------------------------------------------------------
# HLL binary codec

def test_hll_dense_roundtrip():
    rng = np.random.default_rng(0)
    regs = np.zeros(hll.M, np.uint8)
    idx = rng.integers(0, hll.M, 5000)
    regs[idx] = rng.integers(1, 15, 5000)
    data = hll_codec.encode_dense(regs)
    assert data[0] == 1 and data[1] == 14 and data[3] == 0
    out = hll_codec.decode(data)
    np.testing.assert_array_equal(out, regs)


def test_hll_dense_saturates_like_tailcut():
    """Registers above 15 clamp to the 4-bit tailcut ceiling, exactly
    as the axiomhq dense sketch stores them (hyperloglog.go:177)."""
    regs = np.zeros(hll.M, np.uint8)
    regs[7] = 40
    out = hll_codec.decode(hll_codec.encode_dense(regs))
    assert out[7] == 15


def _encode_sparse_key(h64: int) -> int:
    """Reference sparse.go:15 encodeHash (p=14, pp=25), reimplemented
    for fixture construction."""
    idx = (h64 >> (64 - 25)) & ((1 << 25) - 1)
    if (h64 >> (64 - 25)) & ((1 << (25 - 14)) - 1) == 0:
        w = ((h64 << 25) & ((1 << 64) - 1)) | (1 << (25 - 1))
        zeros = 64 - w.bit_length() + 1
        return (idx << 7) | (zeros << 1) | 1
    return idx << 1


def test_hll_sparse_decode_matches_hash_positions():
    """A hand-built sparse sketch (tmpSet + varint list) must decode to
    the same (index, rank) registers the host hasher computes."""
    members = [f"sparse-{i}".encode() for i in range(60)]
    hashes = hashing.hash64(members)
    keys = sorted({_encode_sparse_key(int(h)) for h in hashes})
    # half in tmpSet, half in the compressed list
    tmpset = keys[::2]
    listed = keys[1::2]
    body = bytearray([1, 14, 0, 1])
    body += len(tmpset).to_bytes(4, "big")
    for k in tmpset:
        body += int(k).to_bytes(4, "big")
    varbytes = bytearray()
    last = 0
    for k in listed:
        x = k - last
        last = k
        while x & ~0x7F:
            varbytes.append((x & 0x7F) | 0x80)
            x >>= 7
        varbytes.append(x)
    body += len(listed).to_bytes(4, "big")
    body += int(last).to_bytes(4, "big")
    body += len(varbytes).to_bytes(4, "big")
    body += varbytes
    out = hll_codec.decode(bytes(body))

    expect = np.zeros(hll.M, np.uint8)
    idx, rank = hashing.hll_position(hashes)
    for i, r in zip(idx, rank):
        # sparse encoding caps derivable rank information differently
        # only when rank overflows the 25-bit prefix; for random data
        # positions match exactly
        expect[i] = max(expect[i], r)
    np.testing.assert_array_equal(out, expect)


def test_hll_decode_rejects_garbage():
    with pytest.raises(hll_codec.HLLCodecError):
        hll_codec.decode(b"\x01")
    with pytest.raises(hll_codec.HLLCodecError):
        hll_codec.decode(bytes([1, 10, 0, 0]) + b"\x00" * 16)


# ----------------------------------------------------------------------
# metricpb codec

def test_counter_gauge_roundtrip():
    rows = [
        ForwardRow(_meta("c", dsd.COUNTER, ("a:1",),
                         dsd.SCOPE_GLOBAL), "counter", value=41.6),
        ForwardRow(_meta("g", dsd.GAUGE), "gauge", value=2.5),
    ]
    ml = forward_pb2.MetricList.FromString(
        rows_to_metric_list(rows).SerializeToString())
    assert ml.metrics[0].counter.value == 42  # int64 on the wire
    assert ml.metrics[0].scope == metric_pb2.Global
    assert ml.metrics[0].tags == ["a:1"]
    assert ml.metrics[1].gauge.value == 2.5

    table = MetricTable(TableConfig())
    acc, dropped = apply_metric_list(table, ml)
    assert (acc, dropped) == (2, 0)
    snap = table.swap()
    assert float(np.asarray(snap.counters)[0]) == 42.0
    assert float(np.asarray(snap.gauges)[0]) == 2.5
    # imported counters/gauges are forced global scope
    # (worker.go:445-447)
    assert snap.counter_meta[0].scope == dsd.SCOPE_GLOBAL


def test_histogram_roundtrip_preserves_quantiles():
    rng = np.random.default_rng(1)
    samples = rng.gamma(3, 10, 5000).astype(np.float32)
    src = MetricTable(TableConfig())
    for i in range(0, len(samples), 500):
        src._histo_device_step(
            src._state, np.zeros(500, np.int32), samples[i:i + 500],
            np.ones(500, np.float32))
    stats = np.asarray(src.histo_stats)[0]
    row = ForwardRow(_meta("lat", dsd.TIMER, ("svc:x",)), "histo",
                     stats=stats,
                     means=np.asarray(src.histo_means)[0],
                     weights=np.asarray(src.histo_weights)[0])
    m = metric_pb2.Metric.FromString(
        row_to_metric(row).SerializeToString())
    d = m.histogram.t_digest
    assert d.min == pytest.approx(samples.min(), rel=1e-6)
    assert d.max == pytest.approx(samples.max(), rel=1e-6)
    assert sum(c.weight for c in d.main_centroids) == pytest.approx(
        5000, rel=1e-5)

    dst = MetricTable(TableConfig())
    acc, dropped = apply_metric_list(
        dst, forward_pb2.MetricList(metrics=[m]))
    assert (acc, dropped) == (1, 0)
    dst.device_step(final=True)
    import jax.numpy as jnp
    got = np.asarray(tdigest.quantile(
        dst.histo_means, dst.histo_weights,
        jnp.asarray(np.asarray([0.5, 0.99], np.float32)),
        jnp.asarray(np.asarray(dst.histo_import_stats)[:, 1]),
        jnp.asarray(np.asarray(dst.histo_import_stats)[:, 2])))[0]
    for qi, p in enumerate((0.5, 0.99)):
        exact = float(np.quantile(samples, p))
        assert got[qi] == pytest.approx(exact, rel=0.03), (p, got[qi])


def test_set_roundtrip_cardinality():
    members = [f"u{i}".encode() for i in range(3000)]
    src = MetricTable(TableConfig())
    for mem in members:
        src.ingest(dsd.Sample(name="uniq", type=dsd.SET, value=mem))
    regs = src.swap().set_registers()[0]
    row = ForwardRow(_meta("uniq", dsd.SET), "set", regs=regs)
    ml = forward_pb2.MetricList.FromString(
        rows_to_metric_list([row]).SerializeToString())
    dst = MetricTable(TableConfig())
    apply_metric_list(dst, ml)
    dst.device_step(final=True)
    est = float(np.asarray(hll.estimate(dst.hll_regs))[0])
    assert est == pytest.approx(3000, rel=0.05)


def test_malformed_items_dropped_per_item():
    m_bad = metric_pb2.Metric(name="bad", type=metric_pb2.Set)
    m_bad.set.hyper_log_log = b"\x01\x02"  # truncated sketch
    m_good = metric_pb2.Metric(name="ok", type=metric_pb2.Counter)
    m_good.counter.value = 3
    table = MetricTable(TableConfig())
    acc, dropped = apply_metric_list(
        table, forward_pb2.MetricList(metrics=[m_bad, m_good]))
    assert (acc, dropped) == (1, 1)


# ----------------------------------------------------------------------
# the hand-written wire against the rows, protobuf and the native decoder

def _histo(name, means, weights, mtype=dsd.TIMER, tags=(),
           scope=dsd.SCOPE_DEFAULT, stats=(0.0, 1.0, 9.0, 0.0, 0.5)):
    return ForwardRow(_meta(name, mtype, tags, scope), "histo",
                      stats=np.asarray(stats, np.float32),
                      means=np.asarray(means, np.float32),
                      weights=np.asarray(weights, np.float32))


def _counter(value, name="c", scope=dsd.SCOPE_GLOBAL):
    return ForwardRow(_meta(name, dsd.COUNTER, ("veneurglobalonly",),
                            scope), "counter", value=value)


def _gauge(value):
    return ForwardRow(_meta("g", dsd.GAUGE, (), dsd.SCOPE_GLOBAL),
                      "gauge", value=value)


def _dense_set():
    regs = np.random.default_rng(3).integers(0, 16, hll.M)
    return ForwardRow(_meta("s", dsd.SET), "set",
                      regs=regs.astype(np.uint8))


def _interleaved(width, seed):
    rng = np.random.default_rng(seed)
    weights = np.where(rng.random(width) < 0.4,
                       rng.integers(1, 9, width), 0)
    return rng.gamma(2.0, 30.0, width), weights


_F32_TINY = float(np.float32(1e-45))  # the smallest denormal

WIRE_CASES = {
    "digest_0_live": [_histo("h", [5.0, 6.0], [0.0, 0.0])],
    "digest_1_live": [_histo("h", [5.0, 6.0, 7.0], [0.0, 2.0, 0.0])],
    "digest_616_live": [_histo("h", np.arange(616) + 0.5,
                               np.ones(616))],
    # 616 records are 12,320 bytes, a two-byte length; 1,024 are 20,480
    "digest_1024_live_3_byte_length": [_histo(
        "h", np.arange(1024) + 0.5, np.ones(1024))],
    "digest_dead_interleaved": [_histo("h", *_interleaved(616, 5))],
    "centroid_mean_edges": [_histo(
        "h", [0.0, -0.0, _F32_TINY, 3.4e38, 0.0], [1, 2, 3, 4, 0])],
    "digest_stats_zero": [_histo("h", [1.0], [1.0],
                                 stats=(1.0, 0.0, -0.0, 0.0, 0.0))],
    "counter_zero": [_counter(0.0)],
    "counter_negative": [_counter(-7.0)],
    "counter_rounds": [_counter(41.6)],
    "counter_2_53": [_counter(float(2 ** 53))],
    "gauge_zero": [_gauge(0.0)],
    "gauge_negative": [_gauge(-2.5)],
    "gauge_negative_zero": [_gauge(-0.0)],
    "set_dense_16384": [_dense_set()],
    "long_name_non_ascii_tag": [ForwardRow(
        _meta("n" * 200, dsd.COUNTER, ("ville:orl\u00e9ans", "", "k:v"),
              dsd.SCOPE_GLOBAL), "counter", value=3.0)],
    "empty_name": [ForwardRow(_meta("", dsd.GAUGE), "gauge", value=1.5)],
    "each_scope": [_counter(1.0, f"c.{s}", s) for s in _SCOPE_TO_PB],
    "each_type": [_histo(f"h.{t}", [1.0], [1.0], mtype=t)
                  for t in _TYPE_TO_PB],
    "two_widths": [_histo("h.a", *_interleaved(616, 7)), _counter(2.0),
                   _histo("h.b", *_interleaved(312, 8)),
                   _histo("h.c", [], []),
                   _histo("h.d", *_interleaved(616, 9)), _dense_set()],
    "empty_list": [],
}


def _plain_body(rows, compression):
    """The parent's encoder: one ``main_centroids.add()`` a centroid.
    It is the reference for the bytes and for the values."""
    ml = forward_pb2.MetricList()
    for r in rows:
        m = ml.metrics.add(name=r.meta.name, tags=list(r.meta.tags),
                           type=_TYPE_TO_PB[r.meta.type],
                           scope=_SCOPE_TO_PB[r.meta.scope])
        if r.kind == "counter":
            m.counter.value = int(round(r.value))
        elif r.kind == "gauge":
            m.gauge.value = float(r.value)
        elif r.kind == "histo":
            d = m.histogram.t_digest
            d.compression = float(compression)
            d.min = float(r.stats[segment.STAT_MIN])
            d.max = float(r.stats[segment.STAT_MAX])
            d.reciprocalSum = float(r.stats[segment.STAT_RSUM])
            for mean, w in zip(r.means, r.weights):
                if w > 0:
                    c = d.main_centroids.add()
                    c.mean = float(mean)
                    c.weight = float(w)
        else:
            m.set.hyper_log_log = hll_codec.encode_dense(r.regs)
    return ml.SerializeToString()


def _bits(values):
    return np.asarray(values, np.float64).view(np.uint64).tolist()


@pytest.mark.parametrize("case", WIRE_CASES)
def test_encoded_wire_equals_rows(case):
    """``encode_metric_list``'s body is, byte for byte, what protobuf
    serializes for the per-centroid ``add()`` loop, and both readers
    of the wire (protobuf, the native columnar decoder) give back the
    rows: same metrics, same order, the same float64 bits."""
    rows = WIRE_CASES[case]
    body, centroids = encode_metric_list(rows, 50.0)
    assert body == _plain_body(rows, 50.0)
    live = [np.asarray(r.weights) > 0 if r.kind == "histo" else None
            for r in rows]
    assert centroids == sum(int(m.sum()) for m in live if m is not None)

    ml = forward_pb2.MetricList.FromString(body)
    cols = decode_metric_list(body)
    assert len(ml.metrics) == len(rows)
    assert cols is not None and cols["n"] == len(rows)
    kinds = {"counter": 1, "gauge": 2, "histo": 3, "set": 4}
    for i, (r, m, lv) in enumerate(zip(rows, ml.metrics, live)):
        assert (m.name, tuple(m.tags)) == (r.meta.name, r.meta.tags)
        assert _PB_TO_TYPE[m.type] == r.meta.type
        assert _PB_TO_SCOPE[m.scope] == r.meta.scope
        assert m.WhichOneof("value") == {
            "histo": "histogram"}.get(r.kind, r.kind)
        off, n = int(cols["name_off"][i]), int(cols["name_len"][i])
        assert body[off:off + n].decode() == r.meta.name
        tags = [body[o:o + ln].decode() for o, ln in zip(
            cols["tag_off"][cols["tag_start"][i]:][:cols["tag_cnt"][i]],
            cols["tag_len"][cols["tag_start"][i]:][:cols["tag_cnt"][i]])]
        assert tuple(tags) == r.meta.tags
        assert _PB_TO_TYPE[int(cols["mtype"][i])] == r.meta.type
        assert _PB_TO_SCOPE[int(cols["scope"][i])] == r.meta.scope
        assert cols["kind"][i] == kinds[r.kind]
        if r.kind == "counter":
            assert m.counter.value == int(round(r.value))
            assert cols["scalar"][i] == int(round(r.value))
        elif r.kind == "gauge":
            assert _bits([m.gauge.value]) == _bits([r.value])
            assert _bits([cols["scalar"][i]]) == _bits([r.value])
        elif r.kind == "set":
            want = hll_codec.encode_dense(r.regs)
            assert m.set.hyper_log_log == want
            off, n = int(cols["hll_off"][i]), int(cols["hll_len"][i])
            assert body[off:off + n] == want
        else:
            d = m.histogram.t_digest
            want = [r.stats[segment.STAT_MIN], r.stats[segment.STAT_MAX],
                    r.stats[segment.STAT_RSUM], 50.0]
            assert _bits([d.min, d.max, d.reciprocalSum,
                          d.compression]) == _bits(want)
            assert _bits(cols["dstats"][i]) == _bits(want)
            means, weights = r.means[lv], r.weights[lv]
            assert _bits([c.mean for c in d.main_centroids]) == _bits(
                means)
            assert _bits([c.weight for c in d.main_centroids]) == _bits(
                weights)
            s, n = int(cols["cent_start"][i]), int(cols["cent_cnt"][i])
            assert n == len(means)
            assert _bits(cols["means"][s:s + n]) == _bits(means)
            assert _bits(cols["weights"][s:s + n]) == _bits(weights)


def test_encoded_wire_case_shapes():
    """The cases above are the shapes they claim to be."""
    assert encode_metric_list(WIRE_CASES["digest_616_live"])[1] == 616
    body, _ = encode_metric_list(
        WIRE_CASES["digest_1024_live_3_byte_length"])
    # the Metric's length takes three bytes: two continued, one last
    assert len(body) > 1 << 14
    assert body[1] & body[2] & 0x80 and body[3] == len(body) - 4 >> 14
    for case in ("digest_dead_interleaved", "two_widths"):
        w = WIRE_CASES[case][0].weights
        assert 0 < np.count_nonzero(w[:-1] * w[1:] == 0) and (w > 0).any()
    assert encode_metric_list([]) == (b"", 0)
    assert len(_dense_set().regs) == 16384
    assert {len(r.means) for r in WIRE_CASES["two_widths"]
            if r.kind == "histo"} == {616, 312, 0}


@pytest.mark.parametrize("value", [float(2 ** 63), float("nan"),
                                   float("inf")])
def test_counter_outside_int64_raises(value):
    """As protobuf's int64 field did: loudly, never wrapped."""
    with pytest.raises((ValueError, OverflowError)):
        encode_metric_list([_counter(value)])


def test_unknown_kind_raises():
    row = ForwardRow(_meta("x", dsd.COUNTER), "status", value=1.0)
    with pytest.raises(ValueError):
        encode_metric_list([row])


def _cell_rows(live=100):
    """The forward of ``local-wide-paced``: 1,000 digests of 100 live
    centroids in 616-wide rows, 1,000 global-only counters, 100 dense
    HLLs."""
    rng = np.random.default_rng(27)
    rows = []
    for i in range(1000):
        means, weights = np.zeros(616), np.zeros(616)
        at = np.sort(rng.choice(616, live, replace=False))
        means[at] = np.sort(rng.gamma(2.0, 30.0, live))
        weights[at] = 1.0
        rows.append(_histo(
            f"bench.timer.{i:04d}", means, weights,
            tags=("env:prod", f"shard:{i % 7}"),
            stats=(live, means[at[0]], means[at[-1]], 0.0, 1.5)))
    rows += [_counter(float(i), f"bench.counter.{i:04d}")
             for i in range(1000)]
    regs = rng.integers(0, 16, (100, hll.M)).astype(np.uint8)
    rows += [ForwardRow(_meta(f"bench.set.{i:03d}", dsd.SET), "set",
                        regs=regs[i]) for i in range(100)]
    return rows


def _calls_of_encode(rows):
    """(body, centroids, every Python-level call and C call the encode
    made), counted by a profile hook."""
    import sys
    calls = 0

    def count(frame, event, arg):
        nonlocal calls
        calls += event in ("call", "c_call")

    sys.setprofile(count)
    try:
        body, centroids = encode_metric_list(rows)
    finally:
        sys.setprofile(None)
    return body, centroids, calls


def test_cell_sized_wire_has_no_per_centroid_python():
    """At the benchmark cell's shape the body parses to 2,100 metrics
    and 100,000 centroids, and the encoder makes no Python-level call
    per centroid: with twice the live centroids in the same rows it
    makes as many calls (the per-centroid loop made 300,000 more)."""
    body, centroids, calls = _calls_of_encode(_cell_rows())
    assert centroids == 100_000
    _, twice, calls_twice = _calls_of_encode(_cell_rows(live=200))
    assert twice == 200_000
    assert abs(calls_twice - calls) < 1000, (calls, calls_twice)

    ml = forward_pb2.MetricList.FromString(body)
    assert len(ml.metrics) == 2100
    assert sum(len(m.histogram.t_digest.main_centroids)
               for m in ml.metrics) == 100_000
    cols = decode_metric_list(body)
    assert cols["n"] == 2100
    assert int(cols["cent_cnt"][:2100].sum()) == 100_000
    assert body == _plain_body(_cell_rows(), 100.0)


# ----------------------------------------------------------------------
# end-to-end over loopback gRPC

def test_grpc_forward_chain(tmp_path):
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    gcap = CaptureSink()
    glob = Server(read_config(data={
        "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
        "interval": "10s", "hostname": "g"}), extra_sinks=[gcap])
    glob.start()
    try:
        lcap = CaptureSink()
        local = Server(read_config(data={
            "statsd_listen_addresses": [],
            "forward_address": f"127.0.0.1:{glob.grpc_ports[0]}",
            "forward_use_grpc": True,
            "interval": "10s", "hostname": "l"}), extra_sinks=[lcap])
        local.start()
        try:
            for v in range(200):
                local.handle_packet(f"glat:{v}|ms".encode())
            local.handle_packet(b"ghits:7|c|#veneurglobalonly")
            for i in range(400):
                local.handle_packet(f"guniq:m{i}|s".encode())
            local.flush_once()
            assert glob.stats["imports_received"] >= 3
            glob.flush_once()
            gm = {x.name: x for x in gcap.metrics}
            assert gm["ghits"].value == 7.0
            assert gm["glat.50percentile"].value == pytest.approx(
                99.5, abs=3)
            assert gm["guniq"].value == pytest.approx(400, rel=0.05)
            # mixed-scope: no aggregates at the global
            assert "glat.count" not in gm
        finally:
            local.shutdown()
    finally:
        glob.shutdown()


def test_grpc_ingest_span_packet_health():
    """The gRPC listener serves SSF spans, DogStatsD packets and grpc
    health alongside forward import, like the reference's single
    stats listener (networking.go:295-358 startGRPCTCP)."""
    import grpc as grpclib

    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.protocol.gen import (dogstatsd_grpc_pb2, health_pb2,
                                         ssf_pb2)
    from veneur_tpu.sinks.simple import CaptureSink

    cap = CaptureSink()
    srv = Server(read_config(data={
        "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
        "interval": "10s", "hostname": "g"}), extra_sinks=[cap],
        extra_span_sinks=[cap])
    srv.start()
    chan = grpclib.insecure_channel(f"127.0.0.1:{srv.grpc_ports[0]}")
    try:
        # health: "veneur" and "" are SERVING, others unknown
        check = chan.unary_unary(
            "/grpc.health.v1.Health/Check",
            request_serializer=(
                health_pb2.HealthCheckRequest.SerializeToString),
            response_deserializer=(
                health_pb2.HealthCheckResponse.FromString))
        resp = check(health_pb2.HealthCheckRequest(service="veneur"),
                     timeout=5)
        assert resp.status == health_pb2.HealthCheckResponse.SERVING
        resp = check(health_pb2.HealthCheckRequest(service="nope"),
                     timeout=5)
        assert (resp.status ==
                health_pb2.HealthCheckResponse.SERVICE_UNKNOWN)

        # DogStatsD packet: multi-line body lands in the table
        send_packet = chan.unary_unary(
            "/dogstatsd.DogstatsdGRPC/SendPacket",
            request_serializer=(
                dogstatsd_grpc_pb2.DogstatsdPacket.SerializeToString),
            response_deserializer=dogstatsd_grpc_pb2.Empty.FromString)
        send_packet(dogstatsd_grpc_pb2.DogstatsdPacket(
            packetBytes=b"grpc.hits:3|c\ngrpc.hits:4|c"), timeout=5)
        assert srv.stats["received_dogstatsd-grpc"] == 1

        # SSF span with an attached sample: span reaches span sinks,
        # sample reaches the metric table via ssfmetrics
        send_span = chan.unary_unary(
            "/ssf.SSFGRPC/SendSpan",
            request_serializer=ssf_pb2.SSFSpan.SerializeToString,
            response_deserializer=dogstatsd_grpc_pb2.Empty.FromString)
        span = ssf_pb2.SSFSpan(
            version=0, trace_id=5, id=6, service="svc", name="op",
            start_timestamp=1_000_000_000, end_timestamp=2_000_000_000)
        span.metrics.append(ssf_pb2.SSFSample(
            metric=ssf_pb2.SSFSample.COUNTER, name="grpc.span.ctr",
            value=2.0, sample_rate=1.0))
        send_span(span, timeout=5)
        assert srv.stats["received_ssf-grpc"] == 1

        # span fan-out and sink delivery are both async (span worker
        # thread; flush pool): wait rather than assert immediately
        def _wait(pred, timeout=10.0):
            t0 = time.monotonic()
            while time.monotonic() - t0 < timeout:
                if pred():
                    return True
                time.sleep(0.02)
            return pred()

        # 2 packet lines + 1 span-attached sample extracted by
        # ssfmetrics must be in the table before the swap
        assert _wait(lambda: srv.stats["metrics_processed"] >= 3)
        assert _wait(lambda: any(s.name == "op" for s in cap.spans))
        srv.flush_once()
        assert _wait(lambda: any(m.name == "grpc.span.ctr"
                                 for m in cap.metrics))
        names = {m.name: m for m in cap.metrics}
        assert names["grpc.hits"].value == 7.0
        assert names["grpc.span.ctr"].value == 2.0
        assert any(s.name == "op" for s in cap.spans)
    finally:
        chan.close()
        srv.shutdown()


def test_wire_fixture_regression():
    """Checked-in serialized MetricList (the reference's
    regression_test.go strategy): decoding the frozen wire bytes must
    keep producing the same aggregates — guards against accidental
    proto-schema or codec drift between rounds."""
    import base64
    import os

    from veneur_tpu.core.flusher import Flusher

    path = os.path.join(os.path.dirname(__file__), "testdata",
                        "forward_fixture.b64")
    wire = base64.b64decode(open(path, "rb").read())
    ml = forward_pb2.MetricList.FromString(wire)
    assert len(ml.metrics) == 4
    dst = MetricTable(TableConfig(histo_rows=8, set_rows=8))
    acc, dropped = apply_metric_list(dst, ml)
    assert (acc, dropped) == (4, 0)
    res = Flusher(is_local=False, percentiles=(0.5,),
                  aggregates=("count",)).flush(dst.swap())
    m = {x.name: x for x in res.metrics}
    assert m["fix.total"].value == 7.0
    assert m["fix.depth"].value == 3.5
    # import-only histo rows emit percentiles ONLY — their aggregates
    # were already emitted by the local tier (samplers.go:530 gate)
    assert "fix.lat.count" not in m
    assert m["fix.lat.50percentile"].value == pytest.approx(
        52.87, rel=0.05)  # frozen digest's p50 for seed 42
    assert m["fix.users"].value == pytest.approx(250, rel=0.05)


def test_native_decode_matches_protobuf_path():
    """The columnar native decode (vtpu_metriclist_decode +
    apply_metric_list_bytes) must produce bit-identical table state to
    the protobuf object path for a full fleet wire: counters, gauges,
    tagged digests, sets."""
    from veneur_tpu.core.flusher import Flusher
    from veneur_tpu.forward.grpc_forward import (apply_metric_list,
                                                 apply_metric_list_bytes)

    rng = np.random.default_rng(21)
    src = MetricTable(TableConfig(histo_rows=64, set_rows=16,
                                  histo_slots=512,
                                  histo_merge_samples=1 << 30))
    for i in range(32):
        src.ingest(dsd.Sample(name=f"lat.{i}", type=dsd.TIMER,
                              value=1.0,
                              tags=(f"host:h{i % 7}", "dc:x")))
    rows = np.repeat(np.arange(32, dtype=np.int32), 64)
    vals = rng.gamma(2.0, 30.0, len(rows)).astype(np.float32)
    src._histo_stage.append(rows, vals, np.ones(len(rows), np.float32))
    for i in range(300):
        src.ingest(dsd.Sample(name=f"uniq.{i % 16}", type=dsd.SET,
                              value=f"m{i}".encode()))
    src.ingest(dsd.Sample(name="cnt", type=dsd.COUNTER, value=42.0,
                          scope=dsd.SCOPE_GLOBAL))
    src.ingest(dsd.Sample(name="gau", type=dsd.GAUGE, value=-2.5,
                          scope=dsd.SCOPE_GLOBAL))
    res = Flusher(is_local=True).flush(src.swap())
    wire = rows_to_metric_list(res.forward).SerializeToString()

    def build(apply_fn, arg):
        dst = MetricTable(TableConfig(histo_rows=128, set_rows=32,
                                      histo_slots=512,
                                      histo_merge_samples=1 << 30))
        acc, dropped = apply_fn(dst, arg)
        return acc, dropped, dst.swap()

    acc1, d1, s1 = build(apply_metric_list,
                         forward_pb2.MetricList.FromString(wire))
    acc2, d2, s2 = build(apply_metric_list_bytes, wire)
    assert (acc1, d1) == (acc2, d2)
    np.testing.assert_allclose(np.asarray(s1.histo_import_stats),
                               np.asarray(s2.histo_import_stats),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1.histo_means),
                               np.asarray(s2.histo_means), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1.histo_weights),
                               np.asarray(s2.histo_weights), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s1.counters),
                               np.asarray(s2.counters))
    np.testing.assert_allclose(np.asarray(s1.gauges),
                               np.asarray(s2.gauges))
    np.testing.assert_array_equal(s1.set_registers(),
                                  s2.set_registers())


def test_bytes_path_malformed_wire_falls_back():
    """Garbage bytes must not crash the bytes path: the native walker
    rejects them and the protobuf fallback's error surfaces as a
    decode error, not a wedged table."""
    from veneur_tpu.forward.grpc_forward import apply_metric_list_bytes

    dst = MetricTable(TableConfig(histo_rows=16, set_rows=8))
    with pytest.raises(Exception):
        apply_metric_list_bytes(dst, b"\xff\xff\xff\x01garbage")
    # table still usable
    assert dst.import_counter("c", (), 1.0)


def test_decode_scratch_cap_and_shrink(monkeypatch):
    """The per-thread decode scratch must (a) surface in the
    decode_scratch_bytes gauge, (b) refuse to retain buffers above
    _SCRATCH_MAX_BYTES, and (c) release high-water buffers after
    _SCRATCH_SHRINK_AFTER consecutive small decodes — one giant wire
    must not pin its columns for the life of the handler thread."""
    import threading

    from veneur_tpu import native
    from veneur_tpu.forward import grpc_forward as gf

    lib = native.load()
    if lib is None:
        pytest.skip("native library unavailable")

    def wire(n_rows):
        rows = [ForwardRow(_meta(f"scratch.cnt.{i:07d}", dsd.COUNTER,
                                 (), dsd.SCOPE_GLOBAL),
                           "counter", value=float(i))
                for i in range(n_rows)]
        return rows_to_metric_list(rows).SerializeToString()

    small, big = wire(2), wire(2600)
    # big's buffer heuristic must exceed 4x small's, else the
    # oversized-streak branch under test never arms
    assert len(big) // 48 > 4 * max(256, len(small) // 48)

    tid = threading.get_ident()

    def mine():
        with gf._scratch_lock:
            return gf._scratch_bytes.get(tid, 0)

    saved_cols = getattr(gf._decode_scratch, "cols", None)
    saved_streak = getattr(gf._decode_scratch, "oversized_streak", 0)
    with gf._scratch_lock:
        saved_bytes = gf._scratch_bytes.pop(tid, None)
    gf._decode_scratch.cols = None
    gf._decode_scratch.oversized_streak = 0
    try:
        # (b) over-cap scratch is dropped, not retained
        monkeypatch.setattr(gf, "_SCRATCH_MAX_BYTES", 1024)
        assert gf._decode_native(lib, small)["n"] == 2
        assert gf._decode_scratch.cols is None
        assert mine() == 0

        # (a) under the real cap the retained bytes hit the gauge
        monkeypatch.setattr(gf, "_SCRATCH_MAX_BYTES", 32 << 20)
        assert gf._decode_native(lib, small)["n"] == 2
        small_bytes = mine()
        assert small_bytes > 0
        assert small_bytes == gf._cols_nbytes(gf._decode_scratch.cols)

        assert gf._decode_native(lib, big)["n"] == 2600
        big_bytes = mine()
        assert big_bytes > small_bytes

        # (c) high-water scratch survives SHRINK_AFTER-1 small
        # decodes...
        for _ in range(gf._SCRATCH_SHRINK_AFTER - 1):
            assert gf._decode_native(lib, small)["n"] == 2
        assert mine() == big_bytes
        # ...and the next one releases it back to the small shape
        assert gf._decode_native(lib, small)["n"] == 2
        assert mine() == small_bytes

        # /debug/vars reads this exact gauge
        from veneur_tpu.core import server as server_mod
        assert server_mod._decode_scratch_bytes() == \
            gf.decode_scratch_bytes()
        assert gf.decode_scratch_bytes() >= mine()
    finally:
        gf._decode_scratch.cols = saved_cols
        gf._decode_scratch.oversized_streak = saved_streak
        with gf._scratch_lock:
            if saved_bytes is None:
                gf._scratch_bytes.pop(tid, None)
            else:
                gf._scratch_bytes[tid] = saved_bytes


# ----------------------------------------------------------------------
# a wire's dense sketches in one native pass (MetricTable.import_set_wire)
# against the per-item decode + import_set_at

def _sketch(seed, b=0, p=14, flag=0, size=hll.M // 2, body=hll.M // 2,
            tail=b""):
    """One dense axiomhq sketch as bytes, any header field or the
    body's length made wrong on request."""
    packed = np.random.default_rng(seed).integers(
        0, 256, body, dtype=np.uint8)
    return (bytes([1, p, b, flag]) + size.to_bytes(4, "big")
            + packed.tobytes() + tail)


def _sparse_sketch(n=40):
    keys = sorted({_encode_sparse_key(int(h)) for h in hashing.hash64(
        [f"loose-{i}".encode() for i in range(n)])})
    body = bytearray([1, 14, 0, 1]) + len(keys).to_bytes(4, "big")
    for k in keys:
        body += int(k).to_bytes(4, "big")
    return bytes(body) + bytes(12)  # an empty compressed list


def _set_wire(items):
    """(name, sketch bytes) pairs, and counters where the sketch is
    None, as a MetricList on the wire."""
    ms = []
    for name, sk in items:
        if sk is None:
            m = metric_pb2.Metric(name=name, type=metric_pb2.Counter)
            m.counter.value = 2
        else:
            m = metric_pb2.Metric(name=name, type=metric_pb2.Set)
            m.set.hyper_log_log = sk
        ms.append(m)
    return forward_pb2.MetricList(metrics=ms).SerializeToString()


class _PerItemTable(MetricTable):
    """A table without the batch entry: ``_apply_sets`` runs its
    per-item body for every sketch, as for a ``ShardedTable``."""
    import_set_wire = None


# name -> (items, sketches that go one by one, dropped)
SET_WIRES = {
    "950_distinct_rows": (
        [(f"s{i}", _sketch(i)) for i in range(950)], 0, 0),
    "one_row_three_times": (
        [("s", _sketch(1)), ("t", _sketch(2)), ("s", _sketch(3)),
         ("s", _sketch(4, b=2))], 0, 0),
    "base_0_3_250": (
        [("b0", _sketch(5, b=0)), ("b3", _sketch(6, b=3)),
         ("b250", _sketch(7, b=250)), ("b3", _sketch(8, b=250))], 0, 0),
    "bad_among_good": (
        [("g0", _sketch(10)), ("sparse", _sparse_sketch()),
         ("g1", _sketch(11, b=1)), ("short", _sketch(12, body=8191)),
         ("g0", _sketch(13)), ("p12", _sketch(14, p=12)),
         ("size", _sketch(15, size=4096, body=4096)),
         ("stub", b"\x01\x0e"), ("flag2", _sketch(16, flag=2)),
         ("tail", _sketch(17, tail=b"xyz")), ("c", None),
         ("g2", _sketch(18))],
        # sparse and flag2 decode one by one; short, p12, size, stub drop
        6, 4),
    "empty": ([], 0, 0),
    "no_sets": ([("c0", None), ("c1", None)], 0, 0),
}


def _fold(table, wire):
    from veneur_tpu.forward.grpc_forward import apply_decoded
    return apply_decoded(table, wire, decode_metric_list(wire))


def _import_state(t):
    plane = t._set_import_plane
    rows = t.config.set_rows
    return {
        "plane": (np.zeros((rows, hll.M), np.uint8) if plane is None
                  else plane),
        "plane_touched": (np.zeros(rows, bool)
                          if t._set_import_touched is None
                          else t._set_import_touched),
        "touched": t.set_idx.touched, "last_gen": t.set_idx.last_gen,
        "staged": t._staged_n, "ingested": t._interval_ingested,
        "counts": {k: v for k, v in t._state.import_counts.items()
                   if k != "set_planes_loose"}}


def _assert_same_import(a, b):
    sa, sb = _import_state(a), _import_state(b)
    for k in ("plane", "plane_touched", "touched", "last_gen"):
        np.testing.assert_array_equal(sa[k], sb[k], err_msg=k)
    assert {k: sa[k] for k in ("staged", "ingested", "counts")} == {
        k: sb[k] for k in ("staged", "ingested", "counts")}


@pytest.mark.parametrize("case", SET_WIRES)
def test_set_wire_native_pass_equals_per_item(case):
    """One native pass over a wire's sketches leaves the table as the
    per-item decode + import_set_at does, plane and bookkeeping; what
    it hands back is counted as loose."""
    from veneur_tpu import native
    if native.load() is None:
        pytest.skip("no native library")
    items, loose, dropped = SET_WIRES[case]
    wire = _set_wire(items)
    cfg = TableConfig(counter_rows=64, gauge_rows=64, histo_rows=64,
                      set_rows=1024 if len(items) > 100 else 16)
    batch, single = MetricTable(cfg), _PerItemTable(cfg)
    got, want = _fold(batch, wire), _fold(single, wire)
    assert got == want == (len(items) - dropped, dropped)
    _assert_same_import(batch, single)
    sets = sum(sk is not None for _, sk in items)
    counts = batch._state.import_counts
    assert counts["set_planes"] == sets - dropped
    assert counts["set_planes_loose"] == loose
    assert single._state.import_counts["set_planes_loose"] == 0
    if sets:
        assert batch._set_import_touched.sum() == len(
            {n for n, sk in items if sk is not None}) - dropped
    # a second wire into the same interval goes on from there
    assert _fold(batch, wire) == _fold(single, wire)
    _assert_same_import(batch, single)
    # and the swap reads the same registers out of both
    np.testing.assert_array_equal(batch.swap().set_registers(),
                                  single.swap().set_registers())


def test_set_wire_without_native_library_goes_per_item():
    """A table whose library is gone (the wire was decoded by a
    process that had it) takes the per-item path whole and says so."""
    from veneur_tpu import native
    if native.load() is None:
        pytest.skip("no native library")
    items, _, dropped = SET_WIRES["bad_among_good"]
    wire = _set_wire(items)
    cfg = TableConfig(counter_rows=64, gauge_rows=64, histo_rows=64,
                      set_rows=16)
    bare, single = MetricTable(cfg), _PerItemTable(cfg)
    bare._lib = None
    assert _fold(bare, wire) == _fold(single, wire)
    _assert_same_import(bare, single)
    sets = sum(sk is not None for _, sk in items)
    assert bare._state.import_counts["set_planes_loose"] == sets


def test_set_wire_native_pass_matches_codec_registers():
    """The plane a wire leaves is ``hll_codec.decode``'s registers,
    maxed a row: the native unpack against the reference decoder
    itself, tail-cut base and uint8 wrap included."""
    from veneur_tpu import native
    if native.load() is None:
        pytest.skip("no native library")
    items = SET_WIRES["base_0_3_250"][0]
    t = MetricTable(TableConfig(set_rows=16))
    _fold(t, _set_wire(items))
    want: dict = {}
    for name, sk in items:
        regs = hll_codec.decode(sk)
        want[name] = np.maximum(want.get(name, 0), regs)
    for name, regs in want.items():
        row = t.import_set_row(name, ())
        np.testing.assert_array_equal(t._set_import_plane[row], regs)


def test_apply_sets_span_tags_say_how_many_went_loose():
    import contextlib

    from veneur_tpu.forward.grpc_forward import apply_decoded

    class Tags(dict):
        add_tag = dict.__setitem__

    spans: dict = {}
    items, loose, _ = SET_WIRES["bad_among_good"]
    wire = _set_wire(items)
    apply_decoded(
        MetricTable(TableConfig(set_rows=16)), wire,
        decode_metric_list(wire),
        step=lambda name: contextlib.nullcontext(
            spans.setdefault(name, Tags())))
    sets = sum(sk is not None for _, sk in items)
    assert spans["sets"] == {"planes": str(sets),
                             "planes_loose": str(loose)}
    assert spans["resolve"] == spans["digests"] == {}
