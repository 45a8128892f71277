"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is not available in CI; sharding tests run on an
8-device CPU mesh (mirrors the reference's approach of simulating
multi-node topologies in-process, /root/reference/forward_test.go:18-60).
"""

import os

# Must be set before jax is imported anywhere.  Force-assign (not
# setdefault): the suite needs the virtual 8-device CPU topology
# whatever the environment presets, and JAX_PLATFORMS alone decides
# the platform.
os.environ["JAX_PLATFORMS"] = "cpu"

# The device-cost registry's cost_analysis() pays a SECOND compile per
# new jit variant (observe/devicecost.py); across a suite that builds
# many shape buckets that doubles compile time for no assertion value
# (no test reads the flops estimates).  Respect an explicit override.
os.environ.setdefault("VENEUR_TPU_COST_ANALYSIS", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


# ----------------------------------------------------------------------
# shared fake Sentry DSN endpoint (used by test_sentry and
# test_failure; envelope protocol per core/sentry.py)

import http.server as _http_server  # noqa: E402
import json as _json  # noqa: E402
import threading as _threading  # noqa: E402


class FakeDSNServer:
    """Collects Sentry envelope POSTs: (path, auth header, event)."""

    def __init__(self):
        received = self.received = []

        class Handler(_http_server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                lines = body.split(b"\n")
                event = (_json.loads(lines[2])
                         if len(lines) >= 3 else {})
                received.append((self.path,
                                 self.headers.get("X-Sentry-Auth", ""),
                                 event))
                self.send_response(200)
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *a):
                pass

        self.httpd = _http_server.HTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.httpd.server_address[1]
        _threading.Thread(target=self.httpd.serve_forever,
                          daemon=True).start()

    @property
    def events(self):
        return [e for _, _, e in self.received]

    def dsn(self, project: int = 42) -> str:
        return f"http://pubkey@127.0.0.1:{self.port}/{project}"

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def dsn_server():
    s = FakeDSNServer()
    yield s
    s.close()


# ----------------------------------------------------------------------
# worker thread-leak guard (ISSUE 12): destination-pool and sink-fanout
# workers are named ("proxy-dest-<dest>" / "sink-flush-<name>") so a
# pool whose close()/retire()/stop() forgets to join is a visible test
# failure here, not a slow accumulation across the suite.  ISSUE 16
# extends it to the flight recorder's dump writer ("flight-dump-*",
# joined by FlightRecorder.stop()) and vtop's per-round scraper
# threads ("vtop-scrape-*", joined every scrape round).  ISSUE 18
# adds the collective forward plane-exchange worker
# ("collective-exchange-*", joined by CollectiveTransport.stop()).

_WORKER_PREFIXES = ("proxy-dest-", "sink-flush-", "flight-dump-",
                    "vtop-scrape-", "collective-exchange-")

_GUARDED_MODULES = ("test_breaker", "test_spool", "test_retry_budget",
                    "test_proxy_columnar", "test_sink_fanout",
                    "test_sharded_forward", "test_drain_handoff",
                    "test_live_reshard", "test_flight", "test_vtop",
                    "test_signals", "test_collective_forward")


def _worker_threads():
    return {t for t in _threading.enumerate()
            if t.name.startswith(_WORKER_PREFIXES) and t.is_alive()}


@pytest.fixture(autouse=True)
def _no_worker_thread_leak(request):
    if request.module.__name__.split(".")[-1] not in _GUARDED_MODULES:
        yield
        return
    before = _worker_threads()
    yield
    # grace poll: stop()/retire() join with a timeout, and a worker
    # that just popped its poison pill may still be mid-return
    import time as _time
    deadline = _time.monotonic() + 5.0
    leaked = _worker_threads() - before
    while leaked and _time.monotonic() < deadline:
        _time.sleep(0.02)
        leaked = _worker_threads() - before
    assert not leaked, (
        f"{request.node.nodeid} leaked worker threads: "
        f"{sorted(t.name for t in leaked)} — every DestinationPool / "
        f"SinkFanout / ShardedForwarder must be stop()'d or retire()'d")
