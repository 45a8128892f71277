"""Fault-injection tests: the failure model of SURVEY §5 — watchdog
crash-and-restart, drop-and-count forwarding, per-sink error
isolation (reference server.go:1031 FlushWatchdog, flusher.go:536
forward error suppression, sentry.go ConsumePanic's isolation role).
"""

import time

import pytest

from veneur_tpu.core.config import read_config
from veneur_tpu.core.server import Server
from veneur_tpu.sinks.simple import CaptureSink


@pytest.fixture
def make_server():
    servers = []

    def _make(extra_sinks=None, **overrides):
        data = {"statsd_listen_addresses": ["udp://127.0.0.1:0"],
                "interval": "50ms",
                "hostname": "test-host",
                **overrides}
        cfg = read_config(data=data)
        cap = CaptureSink()
        s = Server(cfg, extra_sinks=[cap] + list(extra_sinks or []))
        s.start()
        servers.append(s)
        return s, cap

    yield _make
    for s in servers:
        s.shutdown()


def test_watchdog_exits_after_missed_flushes(make_server, monkeypatch):
    """The watchdog's contract is a deliberate process exit for the
    supervisor (reference server.go:1031): stale last_flush past the
    allowance must trigger it exactly once."""
    server, _ = make_server(flush_watchdog_missed_flushes=2)
    exits = []
    monkeypatch.setattr("os._exit", lambda code: exits.append(code))
    server.last_flush = time.monotonic() - 10 * server.interval
    # drive one watchdog evaluation directly (the thread's loop body)
    allowed = server.config.flush_watchdog_missed_flushes
    missed = (time.monotonic() - server.last_flush) / server.interval
    assert missed > allowed
    # run the real loop briefly: it wakes every interval (50ms)
    deadline = time.monotonic() + 2.0
    while not exits and time.monotonic() < deadline:
        time.sleep(0.02)
    # disarm AND join before monkeypatch teardown restores the real
    # os._exit (the watchdog thread outlives the test body otherwise)
    _join_watchdog(server)
    assert exits and set(exits) == {2}


def _join_watchdog(server, timeout=15.0):
    """Disarm the watchdog and JOIN its thread: teardown restores the
    real os._exit before the server fixture shuts down (LIFO), so a
    watchdog mid-loop-body would kill the pytest process itself.
    Setting the flags is not enough — the thread must be DEAD before
    the test returns."""
    server._shutdown.set()
    server.last_flush = time.monotonic()
    for t in server._threads:
        if t.name == "watchdog":
            t.join(timeout)
            assert not t.is_alive(), "watchdog thread failed to stop"


def test_watchdog_reports_to_sentry_before_exit(make_server,
                                                monkeypatch,
                                                dsn_server):
    """The watchdog's fatal event must be AT the DSN endpoint before
    os._exit fires (the sentry flush in the exit path; reference
    sentry.go's Flush-before-die contract)."""
    server, _ = make_server(flush_watchdog_missed_flushes=2,
                            sentry_dsn=dsn_server.dsn(3))
    try:
        exits = []
        events_at_exit = []

        def fake_exit(code):
            # snapshot what had ARRIVED when exit fired — delivery
            # after the exit would be lost in a real process
            events_at_exit.append(list(dsn_server.events))
            exits.append(code)

        monkeypatch.setattr("os._exit", fake_exit)
        server.last_flush = time.monotonic() - 10 * server.interval
        deadline = time.monotonic() + 5.0
        while not exits and time.monotonic() < deadline:
            time.sleep(0.02)
        # the loop can fire again in the polling gap; every exit is 2
        assert exits and set(exits) == {2}
        fatal = [e for e in events_at_exit[0]
                 if e.get("level") == "fatal"]
        assert fatal, events_at_exit[0]
        assert "watchdog" in fatal[0]["message"]["formatted"]
    finally:
        _join_watchdog(server)


def test_forward_to_dead_global_drops_and_counts(make_server):
    """A local whose global is unreachable: flushes keep running,
    forward errors are counted, nothing retries within the interval
    and the process stays healthy (flusher.go:536 semantics)."""
    server, cap = make_server(
        forward_address="http://127.0.0.1:1",  # nothing listens
        forward_timeout="100ms")
    server.table.ingest_many(
        [__import__("veneur_tpu.protocol.dogstatsd",
                    fromlist=["parse_metric"]).parse_metric(
            f"lat:{v}|ms".encode()) for v in range(50)])
    for _ in range(2):
        server.flush_once()
    assert server.stats.get("forward_errors", 0) >= 1
    # local aggregates still reached the sink despite the dead global
    assert any(m.name == "lat.count" for m in cap.metrics)


def test_raising_sink_isolated_from_others(make_server):
    """One sink throwing every flush must not poison the flush loop
    or the other sinks (the reference wraps each sink flush;
    flusher.go:106-116)."""

    class BoomSink:
        name = "boom"

        def start(self, trace_client=None):
            pass

        def flush(self, metrics):
            raise RuntimeError("boom")

        def flush_other_samples(self, samples):
            raise RuntimeError("boom")

    # long interval: the test drives flush_once manually and ingests
    # directly into the table (no server lock) — a 50ms ticker flush
    # racing those direct ingests can wipe a value mid-step
    server, cap = make_server(extra_sinks=[BoomSink()],
                              interval="60s")
    from veneur_tpu.protocol import dogstatsd as dsd
    server.table.ingest(dsd.parse_metric(b"ok:5|c"))
    server.flush_once()
    time.sleep(0.2)  # sink pool tasks
    server.table.ingest(dsd.parse_metric(b"ok:6|c"))
    server.flush_once()
    time.sleep(0.2)
    vals = [m.value for m in cap.metrics if m.name == "ok"]
    assert 5.0 in vals and 6.0 in vals
    assert server.stats.get("flush_errors", 0) >= 1


def test_backend_init_error_surfaces_and_names_the_platform(monkeypatch):
    """A backend that cannot start fails the server: nothing probes,
    nothing retries on another platform, and the error says which
    platform was asked for (a deployment without a chip sets
    JAX_PLATFORMS=cpu itself)."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu': "
                           "no device found")

    monkeypatch.setattr(jax, "devices", no_backend)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    cfg = read_config(data={"statsd_listen_addresses":
                            ["udp://127.0.0.1:0"],
                            "interval": "50ms"})
    with pytest.raises(RuntimeError,
                       match=r"JAX_PLATFORMS=tpu.*Unable to initialize"):
        Server(cfg, extra_sinks=[CaptureSink()])


def test_table_init_error_surfaces_after_the_platform_is_logged(
        monkeypatch, caplog):
    """Table construction is tried once; whatever it raises surfaces,
    after the start-up line that names the platform in use."""
    import logging

    import veneur_tpu.core.server as srv

    calls = {"n": 0}

    class Broken:
        def __new__(cls, cfg):
            calls["n"] += 1
            raise RuntimeError("PJRT plugin failed to start")

    monkeypatch.setattr(srv, "MetricTable", Broken)
    cfg = read_config(data={"statsd_listen_addresses":
                            ["udp://127.0.0.1:0"],
                            "interval": "50ms"})
    with caplog.at_level(logging.INFO, logger="veneur_tpu.server"):
        with pytest.raises(RuntimeError, match="PJRT plugin"):
            Server(cfg, extra_sinks=[CaptureSink()])
    assert calls["n"] == 1
    assert "device: platform=cpu" in caplog.text


def test_table_init_oom_surfaces(monkeypatch):
    """An HBM OOM from an oversized table config must crash loudly."""
    import veneur_tpu.core.server as srv

    class AlwaysOOM:
        def __new__(cls, cfg):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating "
                "17179869184 bytes")

    monkeypatch.setattr(srv, "MetricTable", AlwaysOOM)
    cfg = read_config(data={"statsd_listen_addresses":
                            ["udp://127.0.0.1:0"],
                            "interval": "50ms"})
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        Server(cfg, extra_sinks=[CaptureSink()])


def test_unixgram_socket_flock_single_owner(tmp_path):
    """Two instances must not silently split one datagram socket: the
    second bind on the same path fails on the flock (reference
    networking.go:362 acquireLockForSocket), and the lock is released
    at shutdown so a restart can rebind."""
    path = str(tmp_path / "dsd.sock")
    cfg = lambda: read_config(data={
        "statsd_listen_addresses": [f"unix://{path}"],
        "interval": "10s"})
    s1 = Server(cfg(), extra_sinks=[CaptureSink()])
    s1.start()
    try:
        s2 = Server(cfg(), extra_sinks=[CaptureSink()])
        try:
            with pytest.raises(RuntimeError, match="lock file"):
                s2.start()
        finally:
            s2.shutdown()
    finally:
        s1.shutdown()
    # lock released: a restart takes the path cleanly
    s3 = Server(cfg(), extra_sinks=[CaptureSink()])
    s3.start()
    s3.shutdown()
