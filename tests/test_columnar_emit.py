"""Golden parity suite for the flush's emit: the MetricFrame
assembly must produce a bit-identical metric set to the per-row
reference loops (tests/flush_reference.py) — names, values, tags,
types, hostnames — order-insensitive, across scopes x aggregates x
percentile-naming modes, with exact forward-row agreement.  Plus the
frame-native sink encoders (datadog/signalfx/prometheus) against
their legacy dict encoders, and the satellite fixes (tally slicing,
zero-sum sum/avg emission)."""

import json
import zlib

import numpy as np
import pytest

from tests.flush_reference import RowFlusher
from veneur_tpu.core.flusher import Flusher
from veneur_tpu.core.metrics import InterMetric
from veneur_tpu.core.table import MetricTable, TableConfig
from veneur_tpu.protocol import dogstatsd as dsd
from veneur_tpu.sinks import base as sinks_base

ALL_AGGS = ("max", "min", "sum", "avg", "count", "hmean", "median")


def mixed_table():
    """Counters/gauges/histos/sets across all three scopes, tagged and
    untagged, plus a zero-sum histogram and a sink-only whitelist
    row."""
    t = MetricTable(TableConfig(counter_rows=64, gauge_rows=64,
                                histo_rows=64, set_rows=16))
    lines = [
        b"hits:3|c", b"hits:2|c|@0.5",
        b"api:1|c|#route:a,env:prod",
        b"g.hits:7|c|#veneurglobalonly",
        b"l.hits:4|c|#veneurlocalonly",
        b"temp:9|g", b"temp:4|g|#room:b",
        b"g.temp:2|g|#veneurglobalonly",
        b"l.temp:8|g|#veneurlocalonly",
        b"users:a|s", b"users:b|s", b"users:c|s|#tier:x",
        b"g.users:a|s|#veneurglobalonly",
        b"l.users:z|s|#veneurlocalonly",
        b"only.dd:5|c|#veneursinkonly:datadog",
        # zero-sum histogram: sum/avg must still emit (satellite fix)
        b"zs:-5|ms", b"zs:5|ms",
    ]
    for ln in lines:
        t.ingest(dsd.parse_metric(ln))
    rng = np.random.default_rng(3)
    for v in rng.uniform(0, 100, 400):
        t.ingest(dsd.parse_metric(f"lat:{v}|ms".encode()))
        t.ingest(dsd.parse_metric(f"lat:{v / 2}|ms|#route:a".encode()))
    for v in rng.uniform(1, 50, 200):
        t.ingest(dsd.parse_metric(
            f"g.lat:{v}|ms|#veneurglobalonly".encode()))
        t.ingest(dsd.parse_metric(
            f"l.lat:{v}|ms|#veneurlocalonly".encode()))
    return t


def metric_key(m):
    return (m.name, m.timestamp, m.value, m.tags, m.type, m.hostname)


def fwd_key(f):
    return (f.kind, f.meta.name, f.meta.tags, f.meta.scope)


def flush_pair(snap, **kw):
    """Flush the SAME snapshot through the reference loops and the
    program's flush (flush does not mutate the snapshot)."""
    legacy = RowFlusher(**kw).flush(snap, now=1234)
    col = Flusher(**kw).flush(snap, now=1234)
    return legacy, col


def assert_parity(legacy, col):
    lset = sorted(metric_key(m) for m in legacy.metrics)
    cset = sorted(metric_key(m) for m in col.metrics)
    assert lset == cset  # bit-identical, order-insensitive
    # exact forward-row agreement: same rows, same payloads
    assert len(legacy.forward) == len(col.forward)
    lf = sorted(legacy.forward, key=fwd_key)
    cf = sorted(col.forward, key=fwd_key)
    for a, b in zip(lf, cf):
        assert fwd_key(a) == fwd_key(b)
        assert a.value == b.value
        for attr in ("stats", "means", "weights", "regs"):
            av, bv = getattr(a, attr, None), getattr(b, attr, None)
            assert (av is None) == (bv is None)
            if av is not None:
                np.testing.assert_array_equal(np.asarray(av),
                                              np.asarray(bv))
    assert legacy.tally == col.tally
    assert legacy.row_accounting == col.row_accounting


@pytest.mark.parametrize("is_local", [False, True])
@pytest.mark.parametrize("naming", ["precise", "reference"])
def test_columnar_parity_scopes_x_aggregates_x_naming(is_local,
                                                      naming):
    snap = mixed_table().swap()
    legacy, col = flush_pair(
        snap, is_local=is_local, percentiles=(0.5, 0.95, 0.999),
        aggregates=ALL_AGGS, hostname="parity-host",
        tags=("shared:tag",), percentile_naming=naming)
    assert legacy.metrics, "oracle emitted nothing; fixture is broken"
    assert_parity(legacy, col)


@pytest.mark.parametrize("aggregates", [(), ("count",),
                                        ("sum", "avg", "hmean")])
def test_columnar_parity_aggregate_subsets(aggregates):
    snap = mixed_table().swap()
    for is_local in (False, True):
        legacy, col = flush_pair(snap, is_local=is_local,
                                 percentiles=(0.99,),
                                 aggregates=aggregates)
        assert_parity(legacy, col)


def test_columnar_parity_no_percentiles():
    snap = mixed_table().swap()
    legacy, col = flush_pair(snap, is_local=False, percentiles=(),
                             aggregates=("min", "max"))
    assert_parity(legacy, col)


def test_columnar_parity_quantile_interpolation_reference():
    snap = mixed_table().swap()
    legacy, col = flush_pair(snap, is_local=False,
                             percentiles=(0.25, 0.75),
                             aggregates=ALL_AGGS,
                             quantile_interpolation="reference")
    assert_parity(legacy, col)


def test_retained_frame_matches_materialized_list():
    """The one shape: nothing is materialised until ``metrics`` is
    read; what is read is the reference's list."""
    snap = mixed_table().swap()
    kw = dict(is_local=True, aggregates=ALL_AGGS, percentiles=(0.5,),
              hostname="h")
    res = Flusher(**kw).flush(snap, now=99)
    assert len(res.frame) and res.frame._materialized is None
    direct = RowFlusher(**kw).flush(snap, now=99)
    assert res.metric_count() == len(direct.metrics)
    assert res.frame._materialized is None  # counting builds nothing
    assert (sorted(metric_key(m) for m in res.metrics) ==
            sorted(metric_key(m) for m in direct.metrics))
    assert res.frame._materialized is not None
    assert res.metrics is res.metrics  # the frame's cache, no copy


# ---------------------------------------------------------------------
# satellite fixes


@pytest.mark.parametrize("flusher", [RowFlusher, Flusher])
def test_zero_sum_histogram_still_emits_sum_and_avg(flusher):
    """A locally-sampled histogram whose values sum to exactly 0 used
    to lose .sum and .avg to the st_sum != 0 gate; the reference gates
    on LocalWeight (samplers.go:592-607)."""
    t = MetricTable(TableConfig(histo_rows=16))
    t.ingest(dsd.parse_metric(b"zs:-5|ms"))
    t.ingest(dsd.parse_metric(b"zs:5|ms"))
    res = flusher(is_local=True,
                  aggregates=("sum", "avg", "count")).flush(t.swap())
    m = {x.name: x for x in res.metrics}
    assert m["zs.sum"].value == 0.0
    assert m["zs.avg"].value == 0.0
    assert m["zs.count"].value == 2.0


@pytest.mark.parametrize("flusher", [RowFlusher, Flusher])
def test_tally_slices_stale_touch_bits(flusher):
    """Touch bits past len(meta) (a stale plane) must not inflate the
    tallies — slice before summing."""
    t = MetricTable(TableConfig(counter_rows=64, gauge_rows=64,
                                histo_rows=64, set_rows=16))
    for ln in (b"a:1|c", b"b:2|c", b"g:3|g", b"lat:4|ms", b"u:x|s"):
        t.ingest(dsd.parse_metric(ln))
    snap = t.swap()
    snap.counter_touched[len(snap.counter_meta) + 3] = True
    snap.gauge_touched[len(snap.gauge_meta) + 3] = True
    snap.histo_touched[len(snap.histo_meta) + 3] = True
    snap.set_touched[len(snap.set_meta) + 3] = True
    res = flusher(is_local=False).flush(snap)
    assert res.tally["counters"] == 2
    assert res.tally["gauges"] == 1
    assert res.tally["histograms"] == 1
    assert res.tally["sets"] == 1


# ---------------------------------------------------------------------
# frame routing


def frame_for(snap, **kw):
    return Flusher(**kw).flush(snap, now=77).frame


def _sink(name, excluded=()):
    sink = sinks_base.SinkBase()
    sink.name = name
    sink.set_excluded_tags(excluded)
    return sink


def _routed_key(metrics):
    return sorted((m.name, m.value, m.tags) for m in metrics)


@pytest.mark.parametrize("name,excluded", [
    ("datadog", ()), ("signalfx", ()), ("datadog", ("env",)),
    ("signalfx", ("env", "route"))],
    ids=["named", "other", "named-excluding", "other-excluding"])
def test_frame_route_matches_legacy_route(name, excluded):
    """With a whitelisted series live, the frame's routing gives each
    kind of sink (the one the whitelist names, another, either with
    excluded tags) what the per-metric route gives over the
    materialized list: same metrics, same tag tuples."""
    snap = mixed_table().swap()
    assert snap.sink_only_rows == 1
    frame = frame_for(snap, is_local=False, aggregates=ALL_AGGS,
                      percentiles=(0.5,), tags=("c:t",))
    assert frame.sink_only_rows == 1
    legacy = frame.materialize()
    sink = _sink(name, excluded)
    routed = frame.route(sink.name, sink)
    assert routed is not frame
    want = sinks_base.route(legacy, sink.name, sink)
    assert _routed_key(routed.materialize()) == _routed_key(want)
    # the whitelist row reaches datadog and no other
    assert (any(m.name == "only.dd" for m in routed.materialize())
            == (name == "datadog"))
    assert len(routed) == len(legacy) - (name != "datadog")


@pytest.mark.parametrize("name", ["datadog", "signalfx"])
def test_frame_route_common_tag_whitelist_still_routes(name):
    """A ``veneursinkonly:`` among the server's common tags restricts
    every series, whitelisted rows or none."""
    t = MetricTable(TableConfig(counter_rows=16, histo_rows=16))
    for ln in (b"a:1|c", b"b:2|c|#env:prod", b"lat:4|ms"):
        t.ingest(dsd.parse_metric(ln))
    snap = t.swap()
    assert snap.sink_only_rows == 0
    frame = frame_for(snap, is_local=False,
                      tags=("veneursinkonly:datadog",))
    routed = frame.route(name, _sink(name))
    assert routed is not frame
    want = sinks_base.route(frame.materialize(), name, _sink(name))
    assert _routed_key(routed.materialize()) == _routed_key(want)
    assert len(routed) == (len(frame) if name == "datadog" else 0)


def _only_dd(t, how):
    if how == "ingested":
        t.ingest(dsd.parse_metric(
            b"only.dd:5|c|#veneursinkonly:datadog"))
    else:   # a global's row, made by a local's forward
        assert t.import_counter("only.dd",
                                ("veneursinkonly:datadog",), 5.0)


@pytest.mark.parametrize("how", ["ingested", "imported"])
def test_sink_only_count_follows_the_table(how):
    """The count the frame reads is kept where rows are made and
    dropped: 0 on an ordinary table, 1 from the interval a
    ``veneursinkonly:`` series appears, 0 again once a compaction
    has dropped it."""
    t = MetricTable(TableConfig(counter_rows=8, compact_threshold=0.5))
    plain = [f"p{i}:1|c|#env:prod".encode() for i in range(5)]
    for ln in plain:
        t.ingest(dsd.parse_metric(ln))
    assert t.counter_idx.sink_only_rows == 0
    assert t.swap().sink_only_rows == 0

    _only_dd(t, how)
    _only_dd(t, how)    # the row is there: counted once
    for ln in plain:
        t.ingest(dsd.parse_metric(ln))
    assert t.counter_idx.sink_only_rows == 1
    snap = t.swap()
    assert snap.sink_only_rows == 1
    meta, = [m for m in snap.counter_meta if m.sink_only is not None]
    assert (meta.name, meta.sink_only) == ("only.dd", {"datadog"})
    frame = frame_for(snap, is_local=False)
    assert frame.sink_only_rows == 1
    assert "only.dd" not in {
        m.name for m in frame.route("signalfx").materialize()}

    # untouched for an interval: the pool handed out still has the
    # row (and says so), the compaction at this swap's end drops it
    for ln in plain:
        t.ingest(dsd.parse_metric(ln))
    epoch = t._reindex_epoch
    snap = t.swap()
    assert t._reindex_epoch == epoch + 1
    assert snap.sink_only_rows == 1
    assert t.counter_idx.sink_only_rows == 0
    for ln in plain:
        t.ingest(dsd.parse_metric(ln))
    snap = t.swap()
    assert snap.sink_only_rows == 0
    frame = frame_for(snap, is_local=False)
    assert frame.route("signalfx") is frame


class _SealedPool(list):
    """A pool that may be indexed into by a sink's encoder, never
    walked or read by the routing."""

    def __iter__(self):
        raise AssertionError("the routing walked a pool")

    def __getitem__(self, i):
        raise AssertionError("the routing read a pool row")


def test_route_of_ordinary_series_reads_no_pool():
    """The tick path holds no walk: with no whitelisted series live,
    a sink without excluded tags is handed the frame itself and no
    pool row is looked at, whatever the pools' size."""
    n = 50_000
    t = MetricTable(TableConfig(counter_rows=1 << 16, gauge_rows=16,
                                histo_rows=16, set_rows=16))
    for i in range(n):
        t.ingest(dsd.parse_metric(
            b"wide.%d:1|c|#env:prod,k:%d" % (i, i % 7)))
    snap = t.swap()
    assert len(snap.counter_meta) == n and snap.sink_only_rows == 0
    frame = frame_for(snap, is_local=False, tags=("c:t",))
    assert len(frame) == n
    for b in frame.blocks:
        b.metas = _SealedPool(b.metas)
    sink = _sink("bench")
    assert frame.route(sink.name, sink) is frame
    extra = [InterMetric(name="x", timestamp=1, value=1.0)]
    with_extra = frame.route(sink.name, sink, extra=extra)
    assert with_extra.blocks is frame.blocks
    # the seal does what it says: a sink that excludes tags has to
    # read the rows
    with pytest.raises(AssertionError, match="walked a pool"):
        frame.route("dd", _sink("dd", ("env",)))


def test_frame_route_no_filter_shares_self_and_materialization():
    t = MetricTable(TableConfig(counter_rows=16))
    t.ingest(dsd.parse_metric(b"a:1|c"))
    frame = frame_for(t.swap(), is_local=False)
    routed = frame.route("blackhole", None)
    assert routed is frame  # nothing filtered -> shared
    extra = [InterMetric(name="x", timestamp=1, value=1.0, tags=(),
                         type="gauge")]
    with_extra = frame.route("blackhole", None, extra=extra)
    assert with_extra is not frame
    assert with_extra.blocks is frame.blocks
    base = frame.materialize()
    assert with_extra.materialize()[:len(base)] == base  # shared cache
    assert with_extra.materialize()[-1].name == "x"


# ---------------------------------------------------------------------
# frame-native sink encoders vs their legacy dict encoders


def test_datadog_flush_frame_matches_legacy_encoder(monkeypatch):
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    snap = mixed_table().swap()
    frame = frame_for(snap, is_local=False, aggregates=ALL_AGGS,
                      percentiles=(0.5, 0.999), hostname="em-host")
    bodies = []

    def fake_post_body(self, raw):
        bodies.append(json.loads(raw))

    monkeypatch.setattr(DatadogMetricSink, "_post_body",
                        fake_post_body)
    sink = DatadogMetricSink("k", "http://dd", interval_seconds=10.0,
                             hostname="fallback")
    sink.flush(frame.materialize())
    legacy = [e for b in bodies for e in b["series"]]
    bodies.clear()
    sink.flush_frame(frame)
    columnar = [e for b in bodies for e in b["series"]]

    def key(e):
        return (e["metric"], tuple(sorted(e["tags"])), e["host"],
                e["type"], e.get("interval"),
                tuple(tuple(p) for p in e["points"]),
                e.get("device_name"))
    assert sorted(map(key, legacy)) == sorted(map(key, columnar))


def test_datadog_frame_magic_tags_and_drops(monkeypatch):
    """host:/device: magic tags and name-prefix drops behave the same
    on the columnar path."""
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    t = MetricTable(TableConfig(counter_rows=16, gauge_rows=16))
    t.ingest(dsd.parse_metric(b"keep:1|g|#host:other,device:d0"))
    t.ingest(dsd.parse_metric(b"drop.me:2|g"))
    frame = frame_for(t.swap(), is_local=False, hostname="self")
    bodies = []
    monkeypatch.setattr(DatadogMetricSink, "_post_body",
                        lambda self, raw: bodies.append(
                            json.loads(raw)))
    sink = DatadogMetricSink("k", "http://dd", interval_seconds=10.0,
                             metric_name_prefix_drops=("drop.",))
    sink.flush_frame(frame)
    series = [e for b in bodies for e in b["series"]]
    assert [e["metric"] for e in series] == ["keep"]
    assert series[0]["host"] == "other"
    assert series[0]["device_name"] == "d0"
    assert series[0]["tags"] == []


def test_signalfx_flush_frame_matches_legacy_encoder(monkeypatch):
    from veneur_tpu.sinks.signalfx import SignalFxSink

    snap = mixed_table().swap()
    frame = frame_for(snap, is_local=False, aggregates=ALL_AGGS,
                      percentiles=(0.5,), hostname="em-host")
    posts = []
    monkeypatch.setattr(
        SignalFxSink, "_post_body",
        lambda self, token, raw, n: posts.append(
            (token, json.loads(raw))))

    def points(runs):
        out = []
        for token, body in runs:
            for kind in ("gauge", "counter"):
                for p in body[kind]:
                    out.append((token, kind, p["metric"], p["value"],
                                p["timestamp"],
                                tuple(sorted(
                                    p["dimensions"].items()))))
        return sorted(out)

    sink = SignalFxSink("tok", "http://sfx", hostname="sfx-host")
    sink.flush(frame.materialize())
    legacy = points(posts)
    posts.clear()
    sink.flush_frame(frame)
    assert points(posts) == legacy


def test_prometheus_flush_frame_matches_legacy_lines(monkeypatch):
    from veneur_tpu.sinks.prometheus import PrometheusRepeaterSink

    snap = mixed_table().swap()
    frame = frame_for(snap, is_local=False, aggregates=ALL_AGGS,
                      percentiles=(0.5,))
    sent = []
    monkeypatch.setattr(
        PrometheusRepeaterSink, "_send",
        lambda self, lines: sent.append(list(lines)))
    sink = PrometheusRepeaterSink("127.0.0.1:0", "udp")
    sink.flush(frame.materialize())
    legacy = sorted(sent.pop())
    sink.flush_frame(frame)
    assert sorted(sent.pop()) == legacy


def test_datadog_zlib_roundtrip_of_columnar_body(monkeypatch):
    """The columnar body really deflates/parses like the legacy one
    (guards the hand-built JSON against escaping mistakes)."""
    import urllib.request
    from veneur_tpu.sinks.datadog import DatadogMetricSink

    t = MetricTable(TableConfig(counter_rows=16))
    t.ingest(dsd.parse_metric(
        b'esc"ape:1|c|#quote:"x",uni:\xc3\xa9'))
    frame = frame_for(t.swap(), is_local=False)
    captured = {}

    def fake_urlopen(req, timeout=None):
        captured["body"] = req.data
        raise AssertionError("stop")

    monkeypatch.setattr(urllib.request, "urlopen", fake_urlopen)
    sink = DatadogMetricSink("k", "http://dd", interval_seconds=10.0)
    with pytest.raises(AssertionError):
        sink.flush_frame(frame)
    doc = json.loads(zlib.decompress(captured["body"]))
    assert doc["series"][0]["metric"] == 'esc"ape'
