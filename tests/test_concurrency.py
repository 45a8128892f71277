"""Concurrency-targeted tests: the table's swap-under-lock contract.

The reference gets per-series isolation from goroutine-sharded maps
and proves it with `go test -race`; here the equivalent invariant is
that concurrent readers staging into the table while the flush thread
swaps NEVER lose or double-count a sample.  These tests hammer that
boundary from multiple threads and assert exact conservation over the
FlushResults themselves (sink delivery is deliberately at-most-once —
a busy sink skips an interval — so conservation is a property of the
swap, not of any one sink's stream).
"""

from __future__ import annotations

import threading
import time

import pytest

from veneur_tpu.core.config import read_config
from veneur_tpu.core.server import Server


def _mk(interval="10s", **kw):
    return Server(read_config(data={"interval": interval,
                                    "hostname": "h", **kw}))


def test_concurrent_ingest_with_flushes_conserves_counts():
    """8 writer threads x 50 packets of counters+timers racing
    flush_once from a 9th thread: summing over every interval's
    FlushResult must account for EXACTLY every sample (no loss at
    the swap boundary, no double count from staging buffers)."""
    srv = _mk()
    writers = 8
    batches = 50
    per_batch = 40
    stop = threading.Event()
    results = []

    def writer(wid: int):
        for b in range(batches):
            lines = [f"race.ctr:1|c|#w:{wid}".encode()
                     for _ in range(per_batch)]
            lines += [f"race.lat:{(b * 7 + i) % 100}|ms".encode()
                      for i in range(per_batch)]
            srv.handle_packet(b"\n".join(lines))

    def flusher():
        while not stop.is_set():
            results.append(srv.flush_once())
            time.sleep(0.01)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(writers)]
    ft = threading.Thread(target=flusher)
    ft.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set()
        ft.join()
    results.append(srv.flush_once())  # drain the final interval

    total = writers * batches * per_batch
    ctr = sum(m.value for r in results for m in r.metrics
              if m.name == "race.ctr")
    cnt = sum(m.value for r in results for m in r.metrics
              if m.name == "race.lat.count")
    assert ctr == total, (ctr, total)
    assert cnt == total, (cnt, total)
    srv.shutdown()


def test_concurrent_batch_ingest_conserves_sets():
    """Columnar batch ingest (the SO_REUSEPORT reader path) from many
    threads with concurrent flushes: every unique member must be
    represented across interval HLLs (within estimator error; a swap
    dropping staged members would undercount wholesale)."""
    from veneur_tpu.protocol import columnar

    srv = _mk()
    if not columnar.ColumnarParser().available:
        pytest.skip("native parser unavailable")
    writers = 4
    uniq_per_writer = 1000
    stop = threading.Event()
    results = []

    def writer(wid: int):
        parser = columnar.ColumnarParser()
        base = wid * uniq_per_writer
        for start in range(0, uniq_per_writer, 100):
            batch = [f"race.uniq:m{base + start + i}|s".encode()
                     for i in range(100)]
            srv.handle_packet_batch(batch, parser)

    def flusher():
        while not stop.is_set():
            results.append(srv.flush_once())
            time.sleep(0.005)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(writers)]
    ft = threading.Thread(target=flusher)
    ft.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set()
        ft.join()
    results.append(srv.flush_once())

    est = sum(m.value for r in results for m in r.metrics
              if m.name == "race.uniq")
    total = writers * uniq_per_writer
    assert est >= total * 0.97, (est, total)
    srv.shutdown()


def test_flush_during_heavy_staging_is_linearizable():
    """A flush that lands mid-way through a writer's staging must
    attribute every sample to exactly one interval: the flushes'
    counter totals sum to the writer's total."""
    srv = _mk()
    n = 2000
    results = []

    def writer():
        for i in range(n):
            srv.handle_packet(b"mid.ctr:1|c")

    t = threading.Thread(target=writer)
    t.start()
    time.sleep(0.01)
    results.append(srv.flush_once())  # races the writer
    t.join()
    results.append(srv.flush_once())
    total = sum(m.value for r in results for m in r.metrics
                if m.name == "mid.ctr")
    assert total == n, total
    srv.shutdown()


def test_ticker_and_manual_flush_serialize_and_conserve():
    """The real flush TICKER racing manual flush_once calls and
    lockless-looking ingest: flushes serialize (_flush_serial) and
    conservation holds across BOTH flush streams.  This is the bug
    class where an in-flight ticker flush swapped the table while a
    test-style caller flushed concurrently.  Every flush — ticker and
    manual — passes through the serialized _flush_once_locked, so
    wrapping IT captures both streams' FlushResults; sink streams are
    deliberately at-most-once and not asserted (module docstring)."""
    srv = Server(read_config(data={"interval": "150ms",
                                   "hostname": "h"}))
    results = []
    results_lock = threading.Lock()
    orig = srv._flush_once_locked

    def recording(*a, **kw):
        res = orig(*a, **kw)
        with results_lock:
            results.append(res)
        return res

    srv._flush_once_locked = recording
    srv.start()  # ticker live
    writers = 4
    batches = 40
    per_batch = 25
    try:
        def writer(wid):
            for b in range(batches):
                lines = [f"tick.ctr:1|c|#w:{wid}".encode()
                         for _ in range(per_batch)]
                srv.handle_packet(b"\n".join(lines))
                if b % 10 == 0:
                    srv.flush_once()

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        srv.flush_once()  # drain the final interval
        total = writers * batches * per_batch
        with results_lock:
            got = sum(m.value for r in results for m in r.metrics
                      if m.name == "tick.ctr")
        assert got == total, (got, total)
    finally:
        srv.shutdown()


def test_mesh_sharded_server_conserves_under_concurrent_flushes():
    """The single-chip conservation property must hold on the
    MESH-SHARDED server path too (tpu_mesh_shards; ShardedTable
    staging + collective merge behind the same server lock): writer
    threads racing a flusher thread across swap boundaries must
    account for exactly every counter sample and every timer count,
    and set cardinality within estimator error."""
    srv = _mk(tpu_mesh_shards=4, tpu_histo_rows=256, tpu_set_rows=32)
    writers = 4
    batches = 20
    per_batch = 25
    stop = threading.Event()
    results = []

    def writer(wid: int):
        for b in range(batches):
            lines = [f"mrace.ctr:2|c|#w:{wid}".encode()
                     for _ in range(per_batch)]
            lines += [f"mrace.lat:{(b * 13 + i) % 90}|ms".encode()
                      for i in range(per_batch)]
            lines += [f"mrace.uniq:m{wid}-{b}-{i}|s".encode()
                      for i in range(5)]
            srv.handle_packet(b"\n".join(lines))

    def flusher():
        while not stop.is_set():
            results.append(srv.flush_once())
            time.sleep(0.01)

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(writers)]
    ft = threading.Thread(target=flusher)
    ft.start()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        stop.set()
        ft.join()
    results.append(srv.flush_once())

    total = writers * batches * per_batch
    ctr = sum(m.value for r in results for m in r.metrics
              if m.name == "mrace.ctr")
    cnt = sum(m.value for r in results for m in r.metrics
              if m.name == "mrace.lat.count")
    uniq = sum(m.value for r in results for m in r.metrics
               if m.name == "mrace.uniq")
    assert ctr == 2.0 * total, (ctr, total)
    assert cnt == total, (cnt, total)
    n_uniq = writers * batches * 5
    assert uniq >= 0.97 * n_uniq, (uniq, n_uniq)
    srv.shutdown()
