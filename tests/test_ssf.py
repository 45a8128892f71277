"""SSF plane tests: frame codec, sample conversion, span worker
fan-out, ssfmetrics extraction, and spans over real sockets landing as
metrics (the model of reference protocol/wire_test.go and
sinks/ssfmetrics tests)."""

import io
import os
import socket
import time

import pytest

from veneur_tpu.protocol import ssf_convert, wire
from veneur_tpu.protocol import dogstatsd as dsd
from veneur_tpu.protocol.gen import ssf_pb2


def _span(**kw):
    defaults = dict(id=5, trace_id=5, name="op", service="svc",
                    start_timestamp=1_000_000_000,
                    end_timestamp=2_000_000_000)
    defaults.update(kw)
    return ssf_pb2.SSFSpan(**defaults)


def _sample(metric=ssf_pb2.SSFSample.COUNTER, name="c", value=1.0,
            **kw):
    s = ssf_pb2.SSFSample(metric=metric, name=name, value=value)
    for k, v in kw.items():
        if k == "tags":
            for tk, tv in v.items():
                s.tags[tk] = tv
        else:
            setattr(s, k, v)
    return s


# ----------------------------------------------------------------------
# framing

def test_frame_roundtrip():
    span = _span()
    span.metrics.append(_sample())
    buf = io.BytesIO()
    wire.write_ssf(buf, span)
    buf.seek(0)
    out = wire.read_ssf(buf)
    assert out.name == "op" and out.metrics[0].name == "c"
    assert wire.read_ssf(buf) is None  # clean EOF at boundary


def test_frame_bad_version_is_framing_error():
    with pytest.raises(wire.FramingError):
        wire.read_ssf(io.BytesIO(b"\x01\x00\x00\x00\x02hi"))


def test_frame_oversize_rejected():
    buf = io.BytesIO(b"\x00" + (wire.MAX_SSF_PACKET_LENGTH + 1)
                     .to_bytes(4, "big"))
    with pytest.raises(wire.FramingError):
        wire.read_ssf(buf)


def test_frame_truncated_mid_frame():
    buf = io.BytesIO(b"\x00\x00\x00\x00\x10abc")
    with pytest.raises(wire.FramingError):
        wire.read_ssf(buf)


def test_bad_payload_keeps_stream_sync():
    buf = io.BytesIO()
    buf.write(b"\x00" + (4).to_bytes(4, "big") + b"\xff\xff\xff\xff")
    span = _span()
    wire.write_ssf(buf, span)
    buf.seek(0)
    with pytest.raises(wire.SSFParseError):
        wire.read_ssf(buf)
    assert wire.read_ssf(buf).name == "op"  # next frame intact


def test_normalize_name_tag_and_rate():
    raw = ssf_pb2.SSFSpan(id=1, trace_id=1, start_timestamp=1,
                          end_timestamp=2)
    raw.tags["name"] = "from-tag"
    raw.metrics.append(ssf_pb2.SSFSample(name="m", value=1))
    span = wire.parse_ssf(raw.SerializeToString())
    assert span.name == "from-tag"
    assert "name" not in span.tags
    assert span.metrics[0].sample_rate == 1.0


def test_valid_trace():
    assert wire.valid_trace(_span())
    assert not wire.valid_trace(_span(id=0))
    assert not wire.valid_trace(_span(name=""))


# ----------------------------------------------------------------------
# conversion

def test_parse_metric_ssf_types_and_tags():
    s = ssf_convert.parse_metric_ssf(_sample(
        metric=ssf_pb2.SSFSample.GAUGE, name="g", value=2.5,
        tags={"b": "2", "a": "1"}))
    assert s.type == dsd.GAUGE and s.value == 2.5
    assert s.tags == ("a:1", "b:2")  # sorted k:v form

    st = ssf_convert.parse_metric_ssf(_sample(
        metric=ssf_pb2.SSFSample.SET, name="u", message="member-1"))
    assert st.type == dsd.SET and st.value == "member-1"

    status = ssf_convert.parse_metric_ssf(_sample(
        metric=ssf_pb2.SSFSample.STATUS, name="db",
        status=ssf_pb2.SSFSample.CRITICAL, message="down"))
    assert status.type == dsd.STATUS and status.value == 2.0
    assert status.message == "down"


def test_parse_metric_ssf_scope_tags():
    s = ssf_convert.parse_metric_ssf(_sample(
        tags={"veneurglobalonly": "true", "env": "x"}))
    assert s.scope == dsd.SCOPE_GLOBAL
    assert s.tags == ("env:x",)
    s2 = ssf_convert.parse_metric_ssf(_sample(
        scope=ssf_pb2.SSFSample.LOCAL))
    assert s2.scope == dsd.SCOPE_LOCAL


def test_convert_metrics_partial_failure():
    span = _span()
    span.metrics.append(_sample())
    span.metrics.append(ssf_pb2.SSFSample(name="", value=1))  # invalid
    out, invalid = ssf_convert.convert_metrics(span)
    assert len(out) == 1 and invalid == 1


def test_indicator_metrics():
    span = _span(indicator=True, error=True)
    out = ssf_convert.convert_indicator_metrics(
        span, "ssf.indicator", "ssf.objective")
    assert len(out) == 2
    ind, obj = out
    assert ind.name == "ssf.indicator" and ind.type == dsd.TIMER
    assert ind.value == pytest.approx(1e9)  # duration in ns
    assert "error:true" in ind.tags and "service:svc" in ind.tags
    assert obj.scope == dsd.SCOPE_GLOBAL
    assert "objective:op" in obj.tags

    # objective name override via ssf_objective tag
    span.tags["ssf_objective"] = "custom"
    out = ssf_convert.convert_indicator_metrics(span, "", "obj")
    assert out[0].tags[2] == "service:svc" or "objective:custom" in \
        out[0].tags

    # non-indicator spans produce nothing
    assert ssf_convert.convert_indicator_metrics(
        _span(), "a", "b") == []


def test_span_uniqueness_metrics():
    """reference ConvertSpanUniquenessMetrics (samplers/parser.go:
    183-208): a delivery-sampled ssf.names_unique Set tagged by
    service/indicator/root-ness."""
    span = _span(indicator=True)
    # rate=1 (deterministic accept)
    out = ssf_convert.convert_span_uniqueness_metrics(span, rate=1.1)
    assert len(out) == 1
    m = out[0]
    assert m.name == "ssf.names_unique" and m.type == dsd.SET
    assert m.value == span.name.encode()
    assert "service:svc" in m.tags and "indicator:true" in m.tags
    root_tag = [t for t in m.tags if t.startswith("root_span:")]
    assert root_tag == [
        f"root_span:{'true' if span.id == span.trace_id else 'false'}"]
    # deterministic reject
    assert ssf_convert.convert_span_uniqueness_metrics(
        span, rate=0.01, _random=lambda: 0.5) == []
    # accepted roll below rate
    assert len(ssf_convert.convert_span_uniqueness_metrics(
        span, rate=0.01, _random=lambda: 0.001)) == 1
    # no service -> nothing
    ns = _span()
    ns.service = ""
    assert ssf_convert.convert_span_uniqueness_metrics(
        ns, rate=1.1) == []


def test_extraction_sink_counts_and_error_total():
    """ssfmetrics counts spans/metrics and self-reports invalid
    extraction as ssf.error_total into its own pipeline (reference
    metrics.go:82-137); the telemetry tick emits per-span-sink
    veneur.sink.* counters (sinks.go MetricKeyTotal*)."""
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    cap = CaptureSink()
    srv = Server(read_config(data={
        "interval": "10s", "hostname": "h"}), extra_sinks=[cap])
    ext = srv.span_sinks[0]
    assert ext.name == "ssfmetrics"
    span = _span(indicator=False)
    span.metrics.append(_sample())
    span.metrics.append(ssf_pb2.SSFSample(name="", value=1))  # invalid
    ext.ingest(span)
    assert ext.submitted == 1
    assert ext.metrics_generated >= 2  # valid sample + error counter
    srv.flush_once()
    srv.flush_once()  # telemetry loopback surfaces next interval
    metrics = [m for b in cap.batches for m in b]
    names = {m.name for m in metrics}
    assert "ssf.error_total" in names
    flushed = [m for m in metrics
               if m.name == "veneur.sink.spans_flushed_total"
               and "sink:ssfmetrics" in m.tags]
    assert flushed and flushed[0].value >= 1
    gen = [m for m in metrics
           if m.name == "veneur.sink.metrics_flushed_total"
           and "sink:ssfmetrics" in m.tags]
    assert gen and gen[0].value >= 2
    srv.shutdown()


# ----------------------------------------------------------------------
# server integration over real sockets

@pytest.fixture
def ssf_server():
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    cap = CaptureSink()
    scap = CaptureSink()
    server = Server(read_config(data={
        "ssf_listen_addresses": ["udp://127.0.0.1:0"],
        "indicator_span_timer_name": "ssf.ind",
        "interval": "10s", "hostname": "h",
        "tags": ["common:yes"]}),
        extra_sinks=[cap], extra_span_sinks=[scap])
    server.start()
    yield server, cap, scap
    server.shutdown()


def _wait(pred, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.01)
    return False


def test_ssf_udp_span_with_samples_lands_as_metrics(ssf_server):
    server, cap, scap = ssf_server
    span = _span(indicator=True)
    span.metrics.append(_sample(name="ssf.hits", value=3))
    span.metrics.append(_sample(metric=ssf_pb2.SSFSample.HISTOGRAM,
                                name="ssf.lat", value=12.5))
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.sendto(span.SerializeToString(),
                ("127.0.0.1", server.ssf_ports[0]))
    assert _wait(lambda: server.stats.get("spans_processed", 0) >= 1)
    server.flush_once()
    names = {m.name for m in cap.metrics}
    assert "ssf.hits" in names
    assert "ssf.lat.count" in names or "ssf.lat.50percentile" in names
    # indicator timer synthesized from the span duration
    assert any(n.startswith("ssf.ind") for n in names)
    # span fanned out to the extra span sink with common tags applied
    # (the server's own flush self-trace spans may also be present —
    # the whole stage tree, all marked veneur.internal)
    test_spans = [s for s in scap.spans
                  if s.tags.get("veneur.internal") != "true"]
    assert len(test_spans) == 1
    assert test_spans[0].tags["common"] == "yes"


def test_ssf_unix_stream(tmp_path):
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    path = str(tmp_path / "ssf.sock")
    cap = CaptureSink()
    server = Server(read_config(data={
        "ssf_listen_addresses": [f"unix://{path}"],
        "interval": "10s"}), extra_sinks=[cap])
    server.start()
    try:
        span = _span()
        span.metrics.append(_sample(name="stream.c", value=2))
        with socket.socket(socket.AF_UNIX,
                           socket.SOCK_STREAM) as conn:
            conn.connect(path)
            f = conn.makefile("wb")
            wire.write_ssf(f, span)
            wire.write_ssf(f, span)
            f.flush()
            assert _wait(lambda: server.stats.get(
                "spans_processed", 0) >= 2)
        server.flush_once()
        m = {x.name: x for x in cap.metrics}
        assert m["stream.c"].value == 4.0
    finally:
        server.shutdown()


def test_empty_ssf_dropped(ssf_server):
    server, cap, _ = ssf_server
    # non-empty payload but no span identity and no metrics
    bad = ssf_pb2.SSFSpan(service="svc")
    sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    sock.sendto(bad.SerializeToString(),
                ("127.0.0.1", server.ssf_ports[0]))
    assert _wait(lambda: server.stats.get("empty_ssf", 0) >= 1)


def test_emit_cli_ssf_mode(ssf_server):
    """veneur-emit -ssf sends a span datagram whose samples land as
    metrics (reference cmd/veneur-emit SSF mode)."""
    from veneur_tpu.cli import emit

    server, cap, scap = ssf_server
    rc = emit.main([
        "-hostport", f"udp://127.0.0.1:{server.ssf_ports[0]}",
        "-name", "emit.ssf.ctr", "-count", "4",
        "-tag", "who:emit", "-ssf",
        "-span-service", "emitsvc"])
    assert rc == 0
    assert _wait(lambda: server.stats.get("received_ssf-udp", 0) >= 1)
    assert _wait(lambda: any(s.service == "emitsvc"
                             for s in scap.spans))
    server.flush_once()
    assert _wait(lambda: any(m.name == "emit.ssf.ctr" and m.value == 4
                             for m in cap.metrics))


def test_emit_cli_grpc_modes():
    """veneur-emit -grpc covers both DogstatsdGRPC packets and (with
    -ssf) SSFGRPC spans."""
    import pytest as _pytest
    _pytest.importorskip("grpc")
    from veneur_tpu.cli import emit
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.sinks.simple import CaptureSink

    cap = CaptureSink()
    server = Server(read_config(data={
        "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
        "interval": "10s", "hostname": "g"}), extra_sinks=[cap])
    server.start()
    try:
        hostport = f"127.0.0.1:{server.grpc_ports[0]}"
        assert emit.main(["-hostport", hostport, "-name",
                          "emit.grpc.ctr", "-count", "2",
                          "-grpc"]) == 0
        assert server.stats["received_dogstatsd-grpc"] == 1
        assert emit.main(["-hostport", hostport, "-name",
                          "emit.grpc.span", "-timing", "12.5",
                          "-ssf", "-grpc"]) == 0
        assert server.stats["received_ssf-grpc"] == 1
        assert _wait(lambda: server.stats["metrics_processed"] >= 2)
        server.flush_once()
        assert _wait(lambda: any(m.name == "emit.grpc.ctr"
                                 for m in cap.metrics))
        assert _wait(lambda: any(
            m.name.startswith("emit.grpc.span")
            for m in cap.metrics))
    finally:
        server.shutdown()


def test_ssf_frame_decode_never_crashes_on_fuzz():
    """Garbage framed-SSF streams must produce clean protocol errors,
    never arbitrary exceptions — the stream listener feeds this from
    untrusted sockets."""
    import numpy as np

    from veneur_tpu.protocol import wire

    import io

    rng = np.random.default_rng(99)
    for i in range(500):
        n = int(rng.integers(0, 64))
        blob = bytes(rng.integers(0, 256, n, dtype=np.uint8))
        try:
            wire.read_ssf(io.BytesIO(blob))
        except (wire.FramingError, wire.SSFParseError):
            pass


def test_emit_cli_command_timing():
    """veneur-emit -command wraps a child command, times it, emits the
    timer over statsd, and passes through the child's exit status
    (reference cmd/veneur-emit -command mode)."""
    import socket as socket_mod
    import sys

    from veneur_tpu.cli import emit

    rx = socket_mod.socket(socket_mod.AF_INET, socket_mod.SOCK_DGRAM)
    rx.bind(("127.0.0.1", 0))
    rx.settimeout(5.0)
    port = rx.getsockname()[1]
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-name", "cmd.dur", "-tag", "k:v",
                    "-command", sys.executable, "-c",
                    "import time; time.sleep(0.05)"])
    assert rc == 0
    data = rx.recv(4096).decode()
    assert data.startswith("cmd.dur:")
    assert "|ms" in data and "k:v" in data
    ms = float(data.split(":")[1].split("|")[0])
    assert ms >= 50.0

    # child exit status passes through
    rc = emit.main(["-hostport", f"udp://127.0.0.1:{port}",
                    "-name", "cmd.dur",
                    "-command", sys.executable, "-c",
                    "import sys; sys.exit(3)"])
    assert rc == 3
    rx.close()


REF_PB_DIR = "/root/reference/testdata/protobuf"


@pytest.mark.skipif(not os.path.exists(REF_PB_DIR),
                    reason="reference tree not mounted")
def test_reference_protobuf_regression_fixtures():
    """The reference's checked-in SSF wire blobs (2017-era real
    payloads; regression_test.go:90 TestOperation,
    server_sinks_test.go trace fixtures) must decode through our
    parse+normalize path: wire back-compat across protobuf
    generations."""
    import glob

    from veneur_tpu.protocol import wire as w

    blobs = sorted(glob.glob(os.path.join(REF_PB_DIR, "*.pb")))
    assert blobs, "no fixtures found"
    for path in blobs:
        data = open(path, "rb").read()
        span = w.parse_ssf(data)
        assert span.id != 0
        assert span.trace_id != 0
        # normalization contract: a tag 'name' promotes to span.name
        # when unset (regression_test.go TestTagNameSetNameNotSet)
        assert span.name or "name" not in span.tags


@pytest.mark.skipif(not os.path.exists(REF_PB_DIR),
                    reason="reference tree not mounted")
def test_reference_span_fixture_flows_through_server():
    """A reference wire blob ingested as a real SSF datagram reaches
    the span sinks AND its attached metrics reach aggregation."""
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.protocol import wire as w
    from veneur_tpu.sinks.simple import CaptureSink

    data = open(os.path.join(REF_PB_DIR, "trace.pb"), "rb").read()
    span = w.parse_ssf(data)

    class SpanCap:
        name = "spancap"

        def __init__(self):
            self.spans = []

        def start(self):
            pass

        def ingest(self, s):
            self.spans.append(s)

        def flush(self):
            pass

    cap = CaptureSink()
    scap = SpanCap()
    srv = Server(read_config(data={"interval": "60s"}),
                 extra_sinks=[cap], extra_span_sinks=[scap])
    srv.start()
    try:
        srv.handle_ssf(span)
        deadline = time.monotonic() + 5
        while not scap.spans and time.monotonic() < deadline:
            time.sleep(0.02)
        assert scap.spans and scap.spans[0].trace_id == span.trace_id
    finally:
        srv.shutdown()
