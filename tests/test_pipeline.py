"""Double-buffered device pipeline: no sample lost or double-counted
across the buffer swap, and the fused global merge is bit-identical to
the per-wire apply path.

The concurrency test is the acceptance gate for the overlapped
pipeline (VENEUR_TPU_PIPELINE=1, the default) and its serial escape
hatch (=0): reader threads hammer ``handle_packet`` while a flusher
thread swaps intervals, and the totals across every flush must be
EXACT — an off-by-one anywhere means a staged batch crossed the swap
into the wrong interval.
"""

import threading

import numpy as np
import pytest

from veneur_tpu.core.config import read_config
from veneur_tpu.core.server import Server
from veneur_tpu.core.table import MetricTable, TableConfig
from veneur_tpu.sinks.simple import CaptureSink


def _make_server(pipeline: bool, **overrides):
    cfg = read_config(data={
        "statsd_listen_addresses": [],
        "interval": "10s",
        "hostname": "test-host",
        "tpu_pipeline": pipeline,
        **overrides})
    cap = CaptureSink()
    return Server(cfg, extra_sinks=[cap]), cap


def _totals(cap):
    """Sum every flushed interval's counters / histo counts by name."""
    out: dict = {}
    for m in cap.metrics:
        if m.type == "counter":
            out[m.name] = out.get(m.name, 0.0) + m.value
    return out


@pytest.mark.parametrize("pipeline", [True, False])
def test_concurrent_ingest_exact_totals_across_swaps(pipeline):
    """Threads ingesting multi-line packets concurrently with repeated
    flushes: exact counter totals and histogram counts, no loss or
    double-count across the double-buffer swap."""
    server, cap = _make_server(
        pipeline,
        # tiny threshold so mid-interval device steps (take_staged /
        # apply_staged in pipelined mode) fire constantly
        tpu_stage_flush_samples=64)
    assert server.pipeline is pipeline

    n_threads, n_packets, lines = 4, 120, 5
    start = threading.Barrier(n_threads + 1)
    stop = threading.Event()

    def reader(tid):
        pkt = b"\n".join(
            b"hits:1|c\nlat:%d|ms" % (i % 37) for i in range(lines))
        start.wait()
        for _ in range(n_packets):
            server.handle_packet(pkt)

    def flusher():
        start.wait()
        while not stop.is_set():
            server.flush_once()

    threads = [threading.Thread(target=reader, args=(t,))
               for t in range(n_threads)]
    ft = threading.Thread(target=flusher)
    for t in threads + [ft]:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    ft.join()
    server.flush_once()  # drain whatever the last interval staged
    server.shutdown()

    expect = n_threads * n_packets * lines
    tot = _totals(cap)
    assert tot.get("hits") == float(expect)
    assert tot.get("lat.count") == float(expect)
    assert server.stats["metrics_processed"] == 2 * expect
    assert server.stats.get("metrics_dropped", 0) == 0


def _import_wires(table, mode, rng_seed=7, n_wires=6, n_series=5):
    """Stage n_wires forwarded digest lists onto ``table`` using the
    given fused-import mode, then run the final device step."""
    table.fused_import_mode = mode
    # the collective fold is a separate gate with its own parity suite
    # (test_collective_import.py); pin it off so this test isolates
    # stack-vs-perwire fusion under the 8-device conftest platform
    table.collective_import_mode = "off"
    rng = np.random.default_rng(rng_seed)
    for w in range(n_wires):
        rows, means, weights = [], [], []
        srows, stats = [], []
        for s in range(n_series):
            row = table.import_histo_row(f"lat{s}", "timer", ())
            n = int(rng.integers(3, 40))
            rows.extend([row] * n)
            means.extend(rng.gamma(3.0, 10.0, n))
            weights.extend(rng.integers(1, 9, n))
            srows.append(row)
            stats.append([1.0, 2.0, float(n), 0.0, float(n)])
        table.import_histo_batch(
            np.asarray(srows, np.int32),
            np.asarray(stats, np.float32),
            np.asarray(rows, np.int32),
            np.asarray(means, np.float32),
            np.asarray(weights, np.float32))
    table.device_step(final=True)


def test_fused_merge_bit_identical_vs_perwire():
    """The stacked one-kernel-call global merge must produce the SAME
    bits as one kernel call per wire: both run the identical merge
    body over the identical union-row plane in the identical wire
    order, so any divergence is a real fusion bug, not float noise."""
    cfg = TableConfig()
    stacked = MetricTable(cfg)
    perwire = MetricTable(cfg)
    _import_wires(stacked, "stack")
    _import_wires(perwire, "perwire")

    sm = np.asarray(stacked.histo_means)
    sw = np.asarray(stacked.histo_weights)
    pm = np.asarray(perwire.histo_means)
    pw = np.asarray(perwire.histo_weights)
    assert np.array_equal(sm, pm)
    assert np.array_equal(sw, pw)

    # the legacy flat path clusters differently (rank-interleaved) but
    # must conserve total weight exactly — integer weights sum exactly
    # in f32 at this scale
    legacy = MetricTable(cfg)
    _import_wires(legacy, "legacy")
    lw = np.asarray(legacy.histo_weights)
    assert float(sw.sum()) == float(lw.sum()) > 0


def test_wire_digests_leave_staging_with_every_step():
    """Forwarded wires' digests are folded by the next device step and
    not held for the swap, where the last wires of a burst would merge
    on the flush's path; raw samples still wait for theirs.  The
    interval's result is the same as with every wire held to the end:
    each step merges its wires in arrival order."""
    def stage(table, wires):
        rng = np.random.default_rng(11)
        for _w in range(wires):
            row = table.import_histo_row("lat", "timer", ())
            x = np.sort(rng.gamma(3.0, 10.0, 20)).astype(np.float32)
            table.import_histo_batch(
                np.asarray([row], np.int32),
                np.asarray([[20.0, x[0], x[-1], x.sum(), 1.0]],
                           np.float32),
                np.full(20, row, np.int32), x, np.ones(20, np.float32))

    stepped = MetricTable(TableConfig())
    stepped.collective_import_mode = "off"
    stepped.ingest_buffer(b"raw:5|ms")
    stage(stepped, 3)
    work = stepped.take_staged()
    assert [len(p[0]) for p in work.wire_parts] == [20, 20, 20]
    assert work.histo is None and stepped._wire_digest_parts == []
    assert len(stepped._histo_stage) == 1
    stepped.apply_staged(work)
    counts = stepped._state.import_counts
    assert counts["steps_flat"] + counts["steps_stack"] == 1
    assert counts["centroids"] == 60
    stage(stepped, 1)
    snap = stepped.swap()
    assert snap.import_counts["centroids"] == 80
    assert (snap.import_counts["steps_flat"]
            + snap.import_counts["steps_stack"]) == 2

    held = MetricTable(TableConfig())
    held.collective_import_mode = "off"
    held.ingest_buffer(b"raw:5|ms")
    stage(held, 3)
    stage(held, 1)
    ref = held.swap()
    assert float(np.asarray(snap.histo_weights).sum()) \
        == float(np.asarray(ref.histo_weights).sum()) == 81.0


@pytest.mark.slow
def test_pipeline_and_serial_flush_outputs_agree():
    """Perf-smoke (CPU, small shapes): the overlapped pipeline and the
    VENEUR_TPU_PIPELINE=0 serial fallback flush identical metrics for
    a deterministic single-threaded workload."""
    def run(pipeline):
        server, cap = _make_server(pipeline,
                                   tpu_stage_flush_samples=128)
        for i in range(300):
            server.handle_packet(
                b"hits:3|c\nlat:%d|ms\ntemp:%d|g\nusers:u%d|s"
                % (i % 50, i % 11, i % 7))
        server.handle_packet(b"_sc|db.up|0|m:fine")
        server.flush_once()
        out = sorted((m.name, m.type, round(float(m.value), 6))
                     for m in cap.metrics)
        server.shutdown()
        return out

    assert run(True) == run(False)
