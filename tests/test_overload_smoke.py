"""Tier-1 overload smoke (<30s): a 2x ingest burst through the real
``Server``, passing on ACCOUNTING.

The full Zipf soak lives behind ``bench.py --overload`` (committed
artifact ``bench_results/overload_soak.json``); this smoke keeps the
core property in the tier-1 loop: a saturated local degrades
PREDICTABLY — every sample admission control refuses is credited to
the ledger's ``shed`` arm with a tenant and a reason, the interval
still seals balanced, and counters are never shed.  Plus unit
coverage for the pressure hysteresis, the histogram width ladder,
the flush-overrun coalesce arm, the kernel-drop reader, and the
``_ClassIndex`` capacity boundary.
"""

from __future__ import annotations

import socket

import pytest

from veneur_tpu.core import overload as overload_mod
from veneur_tpu.core.config import read_config
from veneur_tpu.core.overload import Overload, PressureSignals
from veneur_tpu.core.server import Server
from veneur_tpu.core.table import _ClassIndex
from veneur_tpu.protocol import columnar


def _server(**kw):
    return Server(read_config(data={
        "interval": "10s", "hostname": "h", **kw}))


# -- the smoke: 2x burst, balanced ledger, attributed shed ------------


def test_burst_sheds_attributed_and_ledger_balances():
    """Tenant buckets sized for half the offered load: the overage is
    shed, every shed sample is named (tenant, reason), and the
    interval seals balanced — ``shed_owed == 0`` is part of the
    seal, so a shed without attribution would FAIL, not shrink."""
    srv = _server(tpu_overload_tenant_rate=5.0,
                  tpu_overload_tenant_burst=5.0)
    try:
        assert srv.overload is not None
        assert srv.overload.buckets_enabled
        assert srv.overload.admission_active

        # scalar path: 20 gauges against a burst-5 bucket
        for i in range(20):
            srv.handle_packet(b"g.metric:%d|g|#tenant:acme,i:%d"
                              % (i, i))
        # columnar path: 30 timers for a second tenant
        parser = columnar.ColumnarParser()
        pkts = [b"h.metric.%d:%d|ms|#tenant:zipf" % (i % 4, i)
                for i in range(30)]
        srv.handle_packet_batch(pkts, parser)

        srv.flush_once()
        rec = srv.ledger.last().to_dict()
        shed = rec["shed"]
        assert rec["balanced"], rec
        assert shed["total"] > 0
        assert shed["owed"] == 0
        # fully attributed: the nested map sums back to the total
        total = sum(n for reasons in shed["by"].values()
                    for n in reasons.values())
        assert total == shed["total"]
        # both tenants were over budget
        assert "acme" in shed["by"] and "zipf" in shed["by"]
        assert all(r == "tenant_budget"
                   for reasons in shed["by"].values()
                   for r in reasons)
        # the stat and the cumulative counter agree with the ledger
        assert srv.stats.get("metrics_shed") == shed["total"]
        assert srv.overload.shed_total == shed["total"]
    finally:
        srv.shutdown()


def test_counters_are_never_shed():
    """Counters aggregate losslessly and are exempt from every
    shedding tier — a zero-budget bucket still admits all of them."""
    srv = _server(tpu_overload_tenant_rate=0.001,
                  tpu_overload_tenant_burst=0.001)
    try:
        for _ in range(50):
            srv.handle_packet(b"c.metric:1|c|#tenant:acme")
        parser = columnar.ColumnarParser()
        srv.handle_packet_batch(
            [b"c.batch:1|c|#tenant:acme" for _ in range(50)], parser)
        res = srv.flush_once()
        rec = srv.ledger.last().to_dict()
        assert rec["balanced"], rec
        assert rec["shed"]["total"] == 0
        # conservation through the flush too: raw counts survive
        flushed = {m.name: m.value for m in res.metrics}
        assert flushed.get("c.metric") == 50.0
        assert flushed.get("c.batch") == 50.0
    finally:
        srv.shutdown()


def test_pressure_freezes_new_series_and_sheds_classes():
    """Engaged pressure at level 3: known histograms shed as
    ``pressure:histogram``, brand-new gauges shed as
    ``series_freeze``, counters pass — and the interval still
    balances."""
    srv = _server()
    try:
        parser = columnar.ColumnarParser()
        # seed known series BEFORE pressure engages
        seed = [b"known.h.%d:5|ms|#tenant:a" % i for i in range(8)]
        srv.handle_packet_batch([b"\n".join(seed)], parser)

        srv.overload.pressure.update(10_000_000, 0.0, 0.0, 0)
        assert srv.overload.pressure.engaged
        assert srv.overload.pressure.level == 3
        assert srv.overload.admission_active

        pkts = [b"known.h.%d:7|ms|#tenant:a" % i for i in range(8)]
        pkts += [b"new.gauge.%d:1|g|#tenant:b" % i for i in range(20)]
        pkts += [b"cnt.%d:1|c|#tenant:b" % i for i in range(10)]
        srv.handle_packet_batch([b"\n".join(pkts)], parser)

        # scalar path under the same pressure
        srv.handle_packet(b"scalar.new:1|g|#tenant:c")
        srv.handle_packet(b"scalar.cnt:1|c|#tenant:c")

        srv.flush_once()
        rec = srv.ledger.last().to_dict()
        assert rec["balanced"], rec
        reasons = {r for by in rec["shed"]["by"].values() for r in by}
        assert "pressure:histogram" in reasons
        assert "series_freeze" in reasons
        # counters passed: no shed reason may name them, and the
        # attribution map still sums to the total
        shed = rec["shed"]
        total = sum(n for by in shed["by"].values()
                    for n in by.values())
        assert total == shed["total"] > 0
    finally:
        srv.shutdown()


def test_width_ladder_steps_and_restores():
    srv = _server()
    try:
        base = srv.table._eff_histo_slots_base
        srv.table.set_pressure_level(3)
        assert srv.table._eff_histo_slots < base
        srv.table.set_pressure_level(0)
        assert srv.table._eff_histo_slots == base
    finally:
        srv.shutdown()


def test_flush_overrun_coalesces_next_tick():
    """An overrunning flush arms the watchdog; the next tick is
    skipped (counted, and NAMED ``coalesced`` in its ledger record),
    and the one after covers both intervals balanced."""
    srv = _server()
    try:
        srv.handle_packet(b"before:1|c")
        srv.flush_once()
        srv.overload.note_flush(duration_s=99.0, budget_s=1.0)
        assert srv.overload.flush_overruns >= 1

        srv.handle_packet(b"after:1|c")
        srv.flush_once()          # coalesced: skipped entirely
        assert srv.stats.get("flush_coalesced") == 1
        rec = srv.ledger.last()

        srv.flush_once()          # the covering flush
        rec = srv.ledger.last()
        d = rec.to_dict()
        assert rec.coalesced
        assert d["balanced"], d
        assert srv.overload.coalesced_total == 1
    finally:
        srv.shutdown()


def test_idle_hot_path_stays_cheap():
    """With buckets off and no pressure, admission is one boolean:
    the controller exists but ``admission_active`` is False, so
    batches keep their fused branch."""
    srv = _server()
    try:
        assert srv.overload is not None
        assert not srv.overload.buckets_enabled
        assert not srv.overload.admission_active
    finally:
        srv.shutdown()


# -- pressure-signal unit coverage ------------------------------------


def test_pressure_hysteresis_band():
    p = PressureSignals(staging_hi=100, occupancy_hi=0.95,
                        lag_hi=1.0, exit_ratio=0.7)
    p.update(100, 0.0, 0.0, 0)       # score 1.0 -> engage
    assert p.engaged and p.level == 1
    p.update(80, 0.0, 0.0, 0)        # 0.8 > exit_ratio: stays engaged
    assert p.engaged
    p.update(60, 0.0, 0.0, 0)        # 0.6 <= 0.7: releases
    assert not p.engaged and p.level == 0
    assert p.transitions == 2


def test_pressure_levels_scale_with_score():
    p = PressureSignals(100, 0.95, 1.0, 0.7)
    p.update(140, 0.0, 0.0, 0)
    assert (p.engaged, p.level) == (True, 1)
    p.update(200, 0.0, 0.0, 0)
    assert p.level == 2
    p.update(300, 0.0, 0.0, 0)
    assert p.level == 3


def test_kernel_drop_engages_pressure():
    p = PressureSignals(1_000_000, 0.95, 1.0, 0.7)
    p.update(0, 0.0, 0.0, 1)
    assert p.engaged and p.score >= 1.0


def test_lag_ewma_smooths_single_slow_flush():
    p = PressureSignals(1_000_000, 0.95, 1.0, 0.7)
    p.update(0, 0.0, 1.5, 0)         # one slow flush: ewma 0.75
    assert not p.engaged
    p.update(0, 0.0, 1.5, 0)         # sustained: ewma 1.125
    assert p.engaged


def test_read_kernel_drops_finds_real_socket():
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.bind(("127.0.0.1", 0))
        drops = overload_mod.read_kernel_drops([s])
        if not drops:
            pytest.skip("/proc/net/udp not readable here")
        assert all(v >= 0 for v in drops.values())
    finally:
        s.close()


def test_coalesce_arm_is_consumed_once():
    ovl = Overload()
    ovl.note_flush(duration_s=5.0, budget_s=1.0)
    assert ovl.take_coalesce() is True
    assert ovl.take_coalesce() is False
    # within budget: never arms
    ovl.note_flush(duration_s=0.5, budget_s=1.0)
    assert ovl.take_coalesce() is False


def test_compile_warmup_overrun_is_exempt():
    """A flush that triggered XLA compiles never arms the watchdog —
    warm-up is a one-time cost, not sustained overload."""
    ovl = Overload()
    ovl.note_flush(duration_s=5.0, budget_s=1.0, compiled=True)
    assert ovl.flush_overruns == 0
    assert ovl.take_coalesce() is False
    ovl.note_flush(duration_s=5.0, budget_s=1.0, compiled=False)
    assert ovl.flush_overruns == 1
    assert ovl.take_coalesce() is True


def test_compile_warmup_lag_never_engages_pressure():
    """Cold-start flushes that compiled feed no lag to the pressure
    EWMA: a server whose idle occupancy sits above the exit band
    (here 0.673 / 0.95 = 0.708 > 0.7, the PR 22 chip run) would
    otherwise latch at level 1 for good and sample its sets."""
    ovl = Overload()
    for _ in range(3):               # 28 s cold flushes of a 10 s tick
        ovl.tick(0, 0.673, 2.8, 0, compiled=True)
    assert not ovl.pressure.engaged
    assert ovl.pressure.flush_lag_ewma == 0.0
    # sustained lag without compiles still engages, and there it stays
    ovl.tick(0, 0.673, 2.8, 0)
    assert ovl.pressure.engaged and ovl.pressure.level == 1
    ovl.tick(0, 0.673, 0.0, 0, compiled=True)
    assert ovl.pressure.flush_lag_ewma == 1.4


def test_coalesce_disabled_never_arms():
    ovl = Overload(coalesce=False)
    ovl.note_flush(duration_s=5.0, budget_s=1.0)
    assert ovl.take_coalesce() is False
    assert ovl.flush_overruns == 1   # still observed


# -- _ClassIndex capacity boundary ------------------------------------


def _fill(idx: _ClassIndex, n: int, gen: int = 1) -> None:
    for i in range(n):
        key = (f"m{i}", "gauge", (), "")
        assert idx.lookup(key, f"m{i}", (), "", "gauge", gen) == i


def test_class_index_admits_exactly_capacity():
    idx = _ClassIndex(capacity=4)
    _fill(idx, 4)
    assert idx.occupancy() == 4
    assert idx.overflow == 0
    # capacity+1: refused, counted as overflow
    key = ("m4", "gauge", (), "")
    assert idx.lookup(key, "m4", (), "", "gauge", 1) is None
    assert idx.overflow == 1
    # an EXISTING key still resolves at capacity (update, not insert)
    key0 = ("m0", "gauge", (), "")
    assert idx.lookup(key0, "m0", (), "", "gauge", 2) == 0
    assert idx.overflow == 1


def test_class_index_one_below_capacity_admits_one_more():
    idx = _ClassIndex(capacity=4)
    _fill(idx, 3)
    key = ("m3", "gauge", (), "")
    assert idx.lookup(key, "m3", (), "", "gauge", 1) == 3
    assert idx.overflow == 0


def test_class_index_compaction_reopens_capacity():
    """At capacity, a mid-interval compaction that evicts stale keys
    renumbers survivors and re-opens room for new inserts."""
    idx = _ClassIndex(capacity=4)
    _fill(idx, 4, gen=1)
    # touch only two keys at gen 2; compact keeps gen >= 2
    for i in (1, 3):
        key = (f"m{i}", "gauge", (), "")
        idx.lookup(key, f"m{i}", (), "", "gauge", 2)
    idx.compact(keep_gen=2)
    assert idx.occupancy() == 2
    # survivors renumbered densely and still resolvable
    assert set(idx.rows.values()) == {0, 1}
    key1 = ("m1", "gauge", (), "")
    assert idx.lookup(key1, "m1", (), "", "gauge", 3) in (0, 1)
    # room re-opened: two NEW keys admit, then the boundary holds
    for i in (9, 10):
        key = (f"m{i}", "gauge", (), "")
        assert idx.lookup(key, f"m{i}", (), "", "gauge", 3) is not None
    key = ("m11", "gauge", (), "")
    assert idx.lookup(key, "m11", (), "", "gauge", 3) is None
    assert idx.overflow == 1


def test_class_index_overflow_not_counted_when_asked():
    idx = _ClassIndex(capacity=1)
    _fill(idx, 1)
    key = ("x", "gauge", (), "")
    assert idx.lookup(key, "x", (), "", "gauge", 1,
                      count_overflow=False) is None
    assert idx.overflow == 0
