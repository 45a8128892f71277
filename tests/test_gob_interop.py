"""Reference HTTP-import wire compatibility: the gob/binary JSONMetric
codec (forward/gob_codec.py) and both directions of the /import
schema bridge — a Go local's wire decodes into our global, and our
local can emit the Go wire (forward_json_schema: reference)."""

import base64
import json
import os
import zlib

import numpy as np
import pytest

from veneur_tpu.core.flusher import Flusher
from veneur_tpu.core.table import MetricTable, TableConfig
from veneur_tpu.forward import gob_codec, hll_codec, http_import
from veneur_tpu.protocol import dogstatsd as dsd

REF_FIXTURE = "/root/reference/testdata/import.uncompressed"


def test_digest_gob_roundtrip():
    rng = np.random.default_rng(3)
    means = rng.gamma(2, 30, 150).astype(np.float32)
    weights = rng.integers(1, 50, 150).astype(np.float32)
    enc = gob_codec.encode_digest(means, weights, 100.0,
                                  float(means.min()),
                                  float(means.max()), 0.25)
    d = gob_codec.decode_digest(enc)
    np.testing.assert_allclose(d["means"], means, rtol=1e-6)
    np.testing.assert_allclose(d["weights"], weights)
    assert d["min"] == pytest.approx(float(means.min()), rel=1e-6)
    assert d["rsum"] == pytest.approx(0.25)


def test_digest_gob_zero_fields_omitted():
    """gob omits zero-valued struct fields; both directions must
    handle centroids with mean 0."""
    enc = gob_codec.encode_digest([0.0, 3.0], [2.0, 1.0], 100.0,
                                  0.0, 3.0, 0.0)
    d = gob_codec.decode_digest(enc)
    assert list(d["means"]) == [0.0, 3.0]
    assert list(d["weights"]) == [2.0, 1.0]


def test_decode_rejects_garbage():
    for blob in (b"", b"\x01", b"\xff\xff\xff", bytes(64)):
        with pytest.raises(gob_codec.GobCodecError):
            gob_codec.decode_digest(blob)


@pytest.mark.skipif(not os.path.exists(REF_FIXTURE),
                    reason="reference tree not mounted")
def test_reference_fixture_imports_end_to_end():
    """The reference's own checked-in /import body (a REAL Go-encoded
    gob digest) must decode byte-for-byte and merge into a table with
    the exact centroid content Go wrote: (1,2,7,8,100) weight 1."""
    items = json.loads(open(REF_FIXTURE, "rb").read())
    table = MetricTable(TableConfig())
    acc, dropped = http_import.apply_import(table, items)
    assert (acc, dropped) == (1, 0)
    snap = table.swap()
    assert snap.histo_meta[0].name == "a.b.c"
    w = np.asarray(snap.histo_weights)[0]
    m = np.asarray(snap.histo_means)[0]
    live = sorted(zip(m[w > 0], w[w > 0]))
    assert [(round(float(a), 4), float(b)) for a, b in live] == [
        (1.0, 1.0), (2.0, 1.0), (7.0, 1.0), (8.0, 1.0), (100.0, 1.0)]
    st = np.asarray(snap.histo_import_stats)[0]
    assert st[0] == 5.0  # weight
    assert st[1] == 1.0 and st[2] == 100.0  # min/max


@pytest.mark.skipif(not os.path.exists(REF_FIXTURE),
                    reason="reference tree not mounted")
def test_reference_deflate_fixture_decodes():
    raw = open("/root/reference/testdata/import.deflate", "rb").read()
    items = http_import.decode_body(raw, content_encoding="deflate")
    assert items[0]["name"] == "a.b.c"


def test_reference_schema_forward_roundtrip():
    """Our local emitting forward_json_schema=reference wire, merged
    by our global: counters/gauges/digests/sets all survive with
    correct values (the same bytes an unmodified Go global reads)."""
    rng = np.random.default_rng(11)
    src = MetricTable(TableConfig())
    vals = rng.gamma(2.0, 30.0, 3000).astype(np.float32)
    for v in vals:
        src.ingest(dsd.Sample(name="lat", type=dsd.TIMER,
                              value=float(v)))
    for i in range(800):
        src.ingest(dsd.Sample(name="uniq", type=dsd.SET,
                              value=f"u{i}".encode()))
    src.ingest(dsd.Sample(name="total", type=dsd.COUNTER, value=41.0,
                          scope=dsd.SCOPE_GLOBAL))
    src.ingest(dsd.Sample(name="depth", type=dsd.GAUGE, value=2.5,
                          scope=dsd.SCOPE_GLOBAL))
    res = Flusher(is_local=True).flush(src.swap())
    body, headers = http_import.encode_rows_reference(res.forward)
    items = http_import.decode_body(
        body, headers.get("Content-Encoding", ""))
    # every item is reference-shaped: opaque base64 value string
    assert all(isinstance(it["value"], str) for it in items)

    dst = MetricTable(TableConfig())
    acc, dropped = http_import.apply_import(dst, items)
    assert dropped == 0 and acc == len(items)
    out = Flusher(is_local=False, percentiles=(0.5, 0.99)).flush(
        dst.swap())
    m = {x.name: x for x in out.metrics}
    assert m["total"].value == 41.0
    assert m["depth"].value == 2.5
    assert m["uniq"].value == pytest.approx(800, rel=0.05)
    for p, q in ((0.5, "lat.50percentile"), (0.99, "lat.99percentile")):
        assert m[q].value == pytest.approx(
            float(np.quantile(vals, p)), rel=0.03)


def test_nonfinite_gob_import_rejected():
    """Gob-decoded state gets the same finiteness gate as the DSD
    parse path: one NaN centroid or inf counter must be dropped, not
    merged into device aggregates."""
    table = MetricTable(TableConfig())
    bad_digest = gob_codec.encode_digest(
        [1.0, float("nan")], [1.0, 1.0], 100.0, 1.0, 1.0, 0.0)
    bad_counter = gob_codec.encode_counter(0)
    items = [
        {"name": "h", "type": "histogram", "tags": [],
         "value": base64.b64encode(bad_digest).decode()},
        # hand-craft an inf gauge: LE float64 +inf
        {"name": "g", "type": "gauge", "tags": [],
         "value": base64.b64encode(
             np.float64(np.inf).tobytes()).decode()},
    ]
    acc, dropped = http_import.apply_import(table, items)
    assert (acc, dropped) == (0, 2)
    # finite state still flows
    good = gob_codec.encode_digest([1.0, 2.0], [1.0, 1.0], 100.0,
                                   1.0, 2.0, 1.5)
    acc, dropped = http_import.apply_import(table, [
        {"name": "h", "type": "histogram", "tags": [],
         "value": base64.b64encode(good).decode()}])
    assert (acc, dropped) == (1, 0)


@pytest.mark.skipif(not os.path.exists(REF_FIXTURE),
                    reason="reference tree not mounted")
def test_proxy_routes_reference_items():
    """A Go local's /import body (tags: null, gob value) must route
    through the proxy on its MetricKey without touching the opaque
    value."""
    from veneur_tpu.core.proxy import ProxyServer

    items = json.loads(open(REF_FIXTURE, "rb").read())
    key = ProxyServer._json_key(items[0])
    assert key == "a.b.c|histogram|"


@pytest.mark.skipif(
    not os.path.exists("/root/reference/tdigest/testdata/oldgob.base64"),
    reason="reference tree not mounted")
def test_old_gob_digest_backwards_compat():
    """The reference pins gob back-compat with a recorded first-
    generation digest (tdigest/testdata/oldgob.base64; histo_test.go
    TestGobDecodeOldGob).  The same bytes must decode here with the
    same recovered statistics — including the ABSENT reciprocalSum
    field, which postdates the recording (that's what the fixture
    exists to catch)."""
    import base64
    import numpy as np
    from tests.go_digest_model import GoMergingDigest

    raw = base64.b64decode(open(
        "/root/reference/tdigest/testdata/oldgob.base64").read())
    d = gob_codec.decode_digest(raw)
    w = np.asarray(d["weights"], float)
    m = np.asarray(d["means"], float)
    assert w.sum() == 1000.0
    assert abs(d["min"] - 0.01) <= 0.02       # Adds were 0..999
    assert abs(d["max"] - 1000) / 1000 <= 0.02
    assert float((m * w).sum()) == 499500.0   # Sum() exact
    assert d.get("reciprocal_sum") in (None, 0.0)
    # the median through the reference quantile rule reads ~500
    god = GoMergingDigest(1000.0)
    god.main_mean = list(m)
    god.main_weight = list(w)
    god.main_total = float(w.sum())
    god.min, god.max = d["min"], d["max"]
    assert abs(god.quantile(0.5) - 500.0) / 500.0 <= 0.02


# ----------------------------------------------------------------------
# round-trip fuzz + native batch-decoder parity (the vtpu_gob_decode
# column path must agree byte-for-byte with the Python codec on every
# stream the codec itself can produce, plus the fail-open truncations)


def _native_cols(payloads):
    cols = gob_codec.decode_batch(
        payloads, [gob_codec.KIND_DIGEST] * len(payloads))
    if cols is None:
        pytest.skip("native library unavailable")
    return cols


def _assert_native_matches(payloads, decoded):
    """decode_batch columns == per-item decode_digest results, bit
    for bit (NaN-aware on the stats)."""
    cols = _native_cols(payloads)
    for i, d in enumerate(decoded):
        s, c = int(cols["cent_start"][i]), int(cols["cent_cnt"][i])
        assert cols["err"][i] == 0
        np.testing.assert_array_equal(cols["means"][s:s + c],
                                      d["means"])
        np.testing.assert_array_equal(cols["weights"][s:s + c],
                                      d["weights"])
        got = cols["dstats"][i]  # min, max, rsum, compression
        for gv, ev in zip(got, (d["min"], d["max"], d["rsum"],
                                d["compression"])):
            assert (gv == ev) or (np.isnan(gv) and np.isnan(ev))


def test_gob_roundtrip_fuzz_vs_go_model():
    """encode -> decode -> re-encode is a byte fixed point on digests
    the Go model built (realistic centroid structure after k-scale
    merges), with zero-weight centroids interleaved in the input —
    dropped on the wire exactly like the reference encoder's w>0
    filter — and the native batch decoder agreeing on every stream."""
    from tests.go_digest_model import GoMergingDigest
    rng = np.random.default_rng(23)
    payloads, decoded = [], []
    for trial in range(6):
        god = GoMergingDigest(100.0)
        god.add_many(rng.gamma(2.0, 30.0, 3000 + 500 * trial))
        god._merge_all_temps()
        means = np.asarray(god.main_mean, np.float32)
        weights = np.asarray(god.main_weight, np.float32)
        live = weights > 0
        means, weights = means[live], weights[live]
        # zero-weight slots the encoder must drop
        means_in = np.concatenate([means, [5.5, 0.0]])
        weights_in = np.concatenate([weights, [0.0, 0.0]])
        enc = gob_codec.encode_digest(
            means_in, weights_in, god.compression, god.min, god.max,
            god.reciprocal_sum)
        d = gob_codec.decode_digest(enc)
        np.testing.assert_array_equal(d["means"], means)
        np.testing.assert_array_equal(d["weights"], weights)
        assert d["min"] == god.min and d["max"] == god.max
        assert d["rsum"] == god.reciprocal_sum
        enc2 = gob_codec.encode_digest(
            d["means"], d["weights"], d["compression"], d["min"],
            d["max"], d["rsum"])
        assert enc2 == enc
        payloads.append(enc)
        decoded.append(d)
    _assert_native_matches(payloads, decoded)


def test_gob_nonfinite_minmax_roundtrip():
    """An EMPTY digest carries min=+inf / max=-inf (the reference's
    zero state) and a NaN sneaks through unharmed: the codec must
    transport the bits faithfully — rejecting nonfinite state is the
    import layer's job, not the wire's."""
    cases = [([], [], float("inf"), float("-inf")),
             ([2.5], [1.0], float("nan"), float("nan")),
             ([2.5], [1.0], float("-inf"), float("inf"))]
    payloads, decoded = [], []
    for means, wts, vmin, vmax in cases:
        enc = gob_codec.encode_digest(means, wts, 100.0, vmin, vmax,
                                      0.0)
        d = gob_codec.decode_digest(enc)
        assert (d["min"] == vmin) or (np.isnan(d["min"])
                                      and np.isnan(vmin))
        assert (d["max"] == vmax) or (np.isnan(d["max"])
                                      and np.isnan(vmax))
        enc2 = gob_codec.encode_digest(
            d["means"], d["weights"], d["compression"], d["min"],
            d["max"], d["rsum"])
        assert enc2 == enc
        payloads.append(enc)
        decoded.append(d)
    _assert_native_matches(payloads, decoded)


def test_gob_truncation_fails_open_like_reference():
    """Cutting the stream after the centroid slice (an old-generation
    Go digest predates reciprocalSum; older still lack min/max) must
    fail OPEN with the reference decoder's defaults — and the native
    decoder must produce the identical fail-open values."""
    enc = gob_codec.encode_digest([1.0, 9.0], [2.0, 1.0], 50.0,
                                  1.0, 9.0, 0.75)
    # message boundaries: typedefs, slice, comp, min, max, rsum
    bounds, pos = [], 0
    while pos < len(enc):
        n, p = gob_codec._read_uint(enc, pos)
        pos = p + n
        bounds.append(pos)
    expect = [(3, (50.0, 1.0, 9.0, 0.0)),     # rsum missing
              (2, (50.0, 1.0, float("-inf"), 0.0)),
              (1, (50.0, float("inf"), float("-inf"), 0.0)),
              (0, (100.0, float("inf"), float("-inf"), 0.0))]
    payloads, decoded = [], []
    for n_floats, (comp, vmin, vmax, rsum) in expect:
        cut = enc[:bounds[-(5 - n_floats)]]
        d = gob_codec.decode_digest(cut)
        assert (d["compression"], d["min"], d["max"],
                d["rsum"]) == (comp, vmin, vmax, rsum)
        assert list(d["weights"]) == [2.0, 1.0]
        payloads.append(cut)
        decoded.append(d)
    _assert_native_matches(payloads, decoded)


def test_gob_multibyte_message_length():
    """A centroid slice past 64KiB forces >2-byte gob uint lengths on
    the message frame (the reference hits this on debug-mode digests
    with Samples attached); both decoders must walk it."""
    n = 12_000
    means = (np.arange(n, dtype=np.float32) + 0.5) * 3.0
    wts = np.ones(n, np.float32)
    enc = gob_codec.encode_digest(means, wts, 100.0, float(means[0]),
                                  float(means[-1]), 0.0)
    assert len(enc) > (1 << 16)  # 3-byte length actually exercised
    d = gob_codec.decode_digest(enc)
    np.testing.assert_array_equal(d["means"], means)
    assert float(d["weights"].sum()) == float(n)
    _assert_native_matches([enc], [d])


def test_native_batch_isolates_malformed_items():
    """One malformed payload in a batch must flag err=1 for that item
    only; well-formed siblings still decode (the per-item codec's
    exception isolation, column-shaped)."""
    good = gob_codec.encode_digest([1.0], [1.0], 100.0, 1.0, 1.0, 0.0)
    cols = _native_cols([good, b"\xff\xff\xff", good, b""])
    assert list(cols["err"]) == [0, 1, 0, 1]
    for i in (0, 2):
        s, c = int(cols["cent_start"][i]), int(cols["cent_cnt"][i])
        assert list(cols["means"][s:s + c]) == [1.0]


# ----------------------------------------------------------------------
# batched columnar /import apply vs the per-item oracle


def _mixed_reference_body():
    """A real flush's reference-schema wire plus deliberately
    malformed riders: bad base64, truncated gob, unknown type, NaN
    gauge, non-finite digest stats."""
    rng = np.random.default_rng(11)
    src = MetricTable(TableConfig())
    vals = rng.gamma(2.0, 30.0, 2000).astype(np.float32)
    for v in vals:
        src.ingest(dsd.Sample(name="lat", type=dsd.TIMER,
                              value=float(v)))
    for v in vals[:500]:
        src.ingest(dsd.Sample(name="lat2", type=dsd.HISTOGRAM,
                              value=float(v), tags=("env:prod",)))
    for i in range(600):
        src.ingest(dsd.Sample(name="uniq", type=dsd.SET,
                              value=f"u{i}".encode()))
    for i in range(10):
        src.ingest(dsd.Sample(name=f"tot.{i}", type=dsd.COUNTER,
                              value=float(i + 1),
                              scope=dsd.SCOPE_GLOBAL))
        src.ingest(dsd.Sample(name=f"depth.{i}", type=dsd.GAUGE,
                              value=2.5 * i, scope=dsd.SCOPE_GLOBAL))
    res = Flusher(is_local=True).flush(src.swap())
    body, headers = http_import.encode_rows_reference(res.forward)
    items = http_import.decode_body(
        body, headers.get("Content-Encoding", ""))
    good = gob_codec.encode_digest([1.0, 2.0], [1.0, 1.0], 100.0,
                                   1.0, 2.0, 1.5)
    items += [
        {"name": "bad.b64", "type": "counter", "tags": [],
         "value": "!!!not-b64!!!"},
        {"name": "bad.gob", "type": "histogram", "tags": [],
         "value": base64.b64encode(good[:7]).decode()},
        {"name": "bad.type", "type": "mystery", "tags": [],
         "value": base64.b64encode(b"x").decode()},
        {"name": "bad.nan", "type": "gauge", "tags": [],
         "value": base64.b64encode(
             gob_codec.encode_gauge(float("nan"))).decode()},
        {"name": "bad.inf", "type": "histogram", "tags": [],
         "value": base64.b64encode(gob_codec.encode_digest(
             [1.0], [1.0], 100.0, float("inf"), 1.0, 0.0)).decode()},
    ]
    return items


def test_reference_batch_apply_matches_per_item_oracle(monkeypatch):
    """VENEUR_GOB_BATCH_DECODE=0's per-item loop is the oracle for
    the native columnar batch apply: identical accept/drop accounting
    (including all five malformed riders), bit-exact counter/gauge
    planes, set registers and centroid planes; the histo stats matrix
    agrees within accumulation tolerance (the per-item path sums
    weight/mean-weight in f32, the batch path in f64 — msum near zero
    cancels, so atol, not rtol alone)."""
    if gob_codec.decode_batch([b"x"], [gob_codec.KIND_DIGEST]) is None:
        pytest.skip("native library unavailable")
    items = _mixed_reference_body()

    def run(enabled):
        monkeypatch.setenv("VENEUR_GOB_BATCH_DECODE",
                           "1" if enabled else "0")
        t = MetricTable(TableConfig())
        acc, drop = http_import.apply_import(t, items)
        # repeat wire: the second apply rides the cached row plan and
        # must account identically
        acc2, drop2 = http_import.apply_import(t, items)
        assert (acc2, drop2) == (acc, drop)
        t.device_step(final=True)
        return acc, drop, t.swap()

    acc_b, drop_b, snap_b = run(True)
    acc_f, drop_f, snap_f = run(False)
    assert (acc_b, drop_b) == (acc_f, drop_f)
    assert drop_b == 5
    for attr in ("counters", "gauges", "histo_means", "histo_weights",
                 "hll_regs"):
        np.testing.assert_array_equal(
            np.asarray(getattr(snap_b, attr)),
            np.asarray(getattr(snap_f, attr)), err_msg=attr)
    sb = np.asarray(snap_b.histo_import_stats, np.float64)
    sf = np.asarray(snap_f.histo_import_stats, np.float64)
    np.testing.assert_array_equal(sb[:, 1], sf[:, 1])  # min exact
    np.testing.assert_array_equal(sb[:, 2], sf[:, 2])  # max exact
    np.testing.assert_allclose(sb, sf, rtol=1e-5, atol=1e-2)
