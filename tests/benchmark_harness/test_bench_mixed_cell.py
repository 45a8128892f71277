"""The cell ``local-mixed-paced`` (configuration ``local-defaults`` x
traffic ``mixed-paced``): its files and entries, its traffic at full
size, its body traced at the tiny scale on the CPU, the control at
that shape, the merge kernel's bytes function, and the four readers
it brought, each on a run that has its counter and on one without."""

import types

import pytest

import structure
from bench_util import ROOT, TINY, copy_benchmark, run_py

from benchmark import control, harness, traffic  # noqa: E402

CELL = "local-mixed-paced"
WIDE = "local-wide-paced"
NEW = ("reader_busy_pct", "merge_device_ms", "merge_roofline",
       "gc_pause_ms")
# what PRs 25 and 26 brought for the wide cell, and the one of the
# four that reads both
WIDE_TWELVE = (
    "sender_late_ms", "flush_readout_ms", "flush_lag_max_ms",
    "host_emit_ms", "forward_ms", "device_idle_pct",
    "forward_encode_ms", "forward_rpc_ms", "import_decode_ms",
    "import_lock_wait_ms", "import_apply_ms", "gc_pause_ms")
V5E = "TPU v5 lite"


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def _kernel():
    return harness.load_module("kernels", "tdigest_merge")


# ----------------------------------------------------------------------
# files and entries

def test_both_cells_still_list_what_they_brought_by_name():
    """Subsets, by name: the mixed cell PR 29's four, the wide cell
    its twelve, wherever they sit and whatever came since; and the
    mixed cell the eleven terms of its lag that read its rings as
    they read the wide cell's."""
    c = harness.cell(CELL)
    mixed = {m["name"] for m in c["per_layer"]}
    assert set(NEW) <= mixed
    assert {"flush_lag_ms", "setup_s"} <= {
        m["name"] for m in c["end_to_end"]}
    wide = {m["name"] for m in harness.cell(WIDE)["per_layer"]}
    assert set(WIDE_TWELVE) <= wide
    assert set(WIDE_TWELVE) <= mixed
    assert "sink_route_ms" in wide & mixed
    assert c["traffic"]["mode"] == "paced"


def test_configuration_is_every_default_and_the_wide_cells_limits():
    from veneur_tpu.core.config import read_config
    from veneur_tpu.ops.segment import HISTO_STAT_COLS
    from veneur_tpu.ops import tdigest
    cfg = harness.cell(CELL)["config"]
    wide = harness.cell("local-wide-paced")["config"]
    servers = cfg["servers"]
    assert not any(k.startswith("tpu_") for part in servers.values()
                   for k in part)
    assert servers["common"] == {"interval": "10s",
                                 "synchronize_with_interval": True}
    assert servers["global"]["percentiles"] == [0.5, 0.9, 0.99]
    assert cfg["reduced"] == ["global_placement"]
    # nothing is loosened for the new cell
    assert cfg["limits"] == wide["limits"]
    assert cfg["guarantees"] == wide["guarantees"]
    # the sizes the file states are what the program's defaults come to
    conf = read_config(data={**servers["common"], **servers["local"]})
    s = cfg["sizes"]
    assert (conf.tpu_counter_rows, conf.tpu_gauge_rows,
            conf.tpu_histo_rows, conf.tpu_set_rows,
            conf.tpu_compression) == (
        s["counter_rows"], s["gauge_rows"], s["histo_rows"],
        s["set_rows"], s["compression"])
    assert tdigest.capacity_for(conf.tpu_compression) \
        == s["digest_slots"] == 616
    assert HISTO_STAT_COLS == s["digest_stat_cols"]
    assert _kernel().row_bytes(cfg) == s["digest_row_bytes"]


def test_traffic_at_full_size_is_the_stated_mix():
    spec = harness.cell(CELL)["traffic"]
    assert (spec["rounds"], spec["rounds_per_interval"],
            spec["inflight"], spec["start_s"], spec["end_s"]) == (
        32, 16, 32, 0.1, 8.1)
    rounds = traffic.make_rounds(spec, seed=2900000011)
    assert len(rounds) == 32
    counters = []
    for r in rounds:
        n = {k: sum(dg.count(k) for dg in r)
             for k in (b"|ms|", b"|c|", b"|g|", b"|s|")}
        assert (n[b"|ms|"], n[b"|g|"], n[b"|s|"]) == (
            60_000, 10_000, 19_950)
        assert 11_000 <= n[b"|c|"] <= 33_000
        assert sum(n.values()) == sum(dg.count(b"\n") + 1 for dg in r)
        assert max(len(dg) for dg in r) <= 4096
        counters.append(n[b"|c|"])
    # one to three increments a counter: 22,000 a round on average,
    # 111,950 lines a round, 1.79M an interval
    assert 21_500 < sum(counters) / 32 < 22_500
    assert rounds[0] != rounds[16]       # two distinct intervals


# ----------------------------------------------------------------------
# the body, traced, at the tiny scale on the CPU

def test_traced_rehearsal_is_correct_and_reads_what_the_cpu_can_show():
    c = harness.cell(CELL)
    res = harness.run_cell(c, seed=2900000012, seconds=4.0, trace=True,
                           scale=TINY[CELL])
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    run = res["run"]
    # 16 of the 32 rounds an interval: every interval's lines are one
    # of the two halves
    per_round = sum(TINY[CELL]["round"][k] for k in (
        "gauges", "set_members")) + int(
        TINY[CELL]["round"]["set_members"] * 0.05) + 20 * 6
    assert res["attempted"] >= 2 * 16 * per_round
    m = {k: v["value"] for k, v in run_py().result_line(
        c, res, trace=True)["metrics"].items()}
    # no device plane on the CPU: the device's metrics are left out,
    # never a 0; the lag's terms read this cell's rings
    assert not {"merge_device_ms", "merge_roofline",
                "device_idle_pct"} & set(m)
    assert {"reader_busy_pct", "gc_pause_ms", "forward_encode_ms",
            "forward_rpc_ms", "import_apply_ms", "import_decode_ms",
            "flush_readout_ms", "host_emit_ms", "forward_ms",
            "flush_lag_max_ms", "sender_late_ms",
            "sink_route_ms"} <= set(m)
    assert 0 < m["reader_busy_pct"] < 100
    assert m["gc_pause_ms"] >= 0
    assert 0 < m["sink_route_ms"] < m["host_emit_ms"]
    assert m["flush_lag_max_ms"] >= 1e3 * max(run["lags"]["local"])
    # readers and ``phase: cycles`` get the whole cycle record
    from veneur_tpu.observe.flushring import FlushRecord
    import dataclasses
    fields = {f.name for f in dataclasses.fields(FlushRecord)}
    assert all(set(r) == fields for ring in run["rings"].values()
               for r in ring)
    # every cycle of both servers says that the program counts
    for ring in run["rings"].values():
        assert ring and all("gc" in r["stages"] for r in ring)
    # all four classes went through the forward
    assert all(r["forward_rows"] == 20 + 5 + 5
               for r in run["rings"]["local"]
               if r["start_unix"] <= run["t_end"])


@pytest.mark.parametrize("fault", control.FAULTS)
def test_control_at_the_rehearsals_shape(fault):
    """Each guarantee of the configuration broken in turn, at the
    rehearsal's own round and 16 rounds an interval."""
    c = harness.cell(CELL)
    res = control.run(c, seed=2900000013, fault=fault,
                      rounds_sent=c["traffic"]["rounds_per_interval"],
                      scale={"round": TINY[CELL]["round"]})
    assert res["correct"] == (fault == "none"), res["checks"]
    if fault == "none":
        assert all(v == 0 for v, _ in res["checks"].values())
    else:
        # 96 samples a timer: a digest at compression 20 is 1.5 ranks
        # off at the median or the p90 before it is 1 % off at the p99
        numbers = {"drop_datagram": ("sums_off",),
                   "halve_sets": ("card_rel_err",),
                   "coarse_digest": ("p99_out", "p50_rank_err",
                                     "p90_rank_err")}[fault]
        assert any(res["checks"][k][0] > res["checks"][k][1]
                   for k in numbers)


# ----------------------------------------------------------------------
# the merge kernel's bytes

def test_merge_floor_is_a_function_of_the_cells_two_files():
    c = harness.cell(CELL)
    k = _kernel()
    assert k.row_bytes(c["config"]) == 4948
    assert k.samples_per_timer(c["traffic"]) == 96
    # 10,000 x 2 x (2 x 4,948 + 8 x 96)
    assert k.bytes_per_interval(c["config"], c["traffic"]) \
        == 213_280_000
    assert k.floor_ms(c["config"], c["traffic"], V5E) == pytest.approx(
        0.2604, abs=5e-5)
    # 819e9 B/s, the v5e's published HBM bandwidth
    assert k.PEAKS[V5E]["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_merge_floor_raises_on_a_device_without_a_published_peak(kind):
    c = harness.cell(CELL)
    with pytest.raises(KeyError, match="no published peak"):
        _kernel().floor_ms(c["config"], c["traffic"], kind)


def test_merge_floor_refuses_more_timers_than_rows():
    c = harness.cell(CELL)
    spec = {**c["traffic"],
            "round": {**c["traffic"]["round"], "timers": 20_000}}
    with pytest.raises(ValueError, match="do not fit"):
        _kernel().bytes_per_interval(c["config"], spec)


# ----------------------------------------------------------------------
# the readers: a run that has the counter, a run that has not

MS = 1_000_000


def _fixture_run():
    """A window of four local cycles as the harness hands it over:
    the ledger's numbers for PR 28's traced run of the cell, a pause
    in the third cycle's encode."""
    def stages(pause):
        s = {"snapshot": 7 * MS, "swap_apply": 200 * MS,
             "dispatch": 9 * MS, "device_wait": 4 * MS,
             "host_emit": 150 * MS, "sink_flush": 900 * MS,
             "sink_flush.route": 30_000,
             "forward": 880 * MS, "forward.encode": 450 * MS,
             "gc": pause}
        if pause:
            # the pause ended inside the encode, so also inside the
            # forward and the flush thread's wait for it; and one more
            # in the trailing list, after delivery
            s.update({"gc.forward.encode": pause, "gc.forward": pause,
                      "gc.sink_flush": pause, "gc.swap_apply": 2 * MS,
                      "gc": pause + 2 * MS + 40 * MS})
        return s

    ops = [["%tdigest_merge_c616_k256.1 tpu_custom_call", 0.046559301],
           ["%tdigest_merge_c616_k96.1 tpu_custom_call", 0.045846175],
           ["%sort.15", 0.016075443], ["%fusion", 0.006903836]]

    def registry(ns):
        return {"registry": {"kernels": {}, "readers": {
            "udp-reader-0": {"batches": 1, "packets": 1, "samples": 1,
                             "ingest_duration_ns": ns,
                             "fused_batches": 1}}}}
    return {
        "cell": CELL, "t0": 60.0, "t_end": 100.0, "interval_s": 10.0,
        "rings": {"local": [
            {"start_unix": 60.0 + 10 * i,
             "stages": stages(500 * MS if i == 3 else 0)}
            for i in range(1, 6)],           # the last is past t_end
            "global": []},
        "at_t0": {"t": 60.0, **registry(int(30e9))},
        "at_end": {"t": 100.0, **registry(int(30e9 + 0.43 * 40e9))},
        "trace": {"busy_s": 0.1326, "window_s": 9.7124,
                  "device_ops": list(ops), "device_ops_all": list(ops)}}


def _on_a_v5e(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(device_kind=V5E, platform="tpu")])


def test_readers_on_a_run_that_has_their_counters(monkeypatch):
    _on_a_v5e(monkeypatch)
    run = _fixture_run()
    assert _reader("reader_busy_pct")(run) == pytest.approx(43.0)
    assert _reader("merge_device_ms")(run) == pytest.approx(92.405476)
    # the floor over the device time: the ledger's 0.28182 (PR 28)
    roof = _reader("merge_roofline")(run)
    assert roof == pytest.approx(0.28182, abs=5e-6)
    assert roof == pytest.approx(
        100 * 0.2604151404 / _reader("merge_device_ms")(run))
    # mean over the window's four cycles of the tick path's stages:
    # (500 + 2) / 4; gc.forward* lie inside gc.sink_flush, the
    # trailing 40 ms are off the lag
    assert _reader("gc_pause_ms")(run) == pytest.approx(125.5)


def test_gc_reader_counts_a_stage_without_its_key_as_zero():
    run = _fixture_run()
    for r in run["rings"]["local"]:
        r["stages"] = {k: v for k, v in r["stages"].items()
                       if not k.startswith("gc.")}
    assert _reader("gc_pause_ms")(run) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_on_a_run_without_its_counter(
        name, monkeypatch):
    """The parent commit's run: no ``gc`` key in a cycle, no reader
    in the registry, no trace (an untraced or a CPU run) or a trace
    with no merge kernel in it."""
    _on_a_v5e(monkeypatch)
    run = _fixture_run()
    for r in run["rings"]["local"]:
        r["stages"] = {k: v for k, v in r["stages"].items()
                       if k != "gc" and not k.startswith("gc.")}
    run["at_t0"]["registry"].pop("readers")
    run["at_end"]["registry"]["readers"] = {}
    run["trace"]["device_ops_all"] = run["trace"]["device_ops_all"][2:]
    assert _reader(name)(run) is None
    run["trace"] = None
    assert _reader(name)(run) is None


def test_roofline_reader_raises_on_an_unknown_device(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(device_kind="TPU v9", platform="tpu")])
    with pytest.raises(KeyError, match="no published peak"):
        _reader("merge_roofline")(_fixture_run())


def test_sink_route_reader_on_a_run_with_the_stage_and_without():
    run = _fixture_run()
    # 30 us a cycle over the window's four cycles
    assert _reader("sink_route_ms")(run) == pytest.approx(0.03)
    run["rings"]["local"][0]["stages"]["sink_flush.route"] = 4_030_000
    assert _reader("sink_route_ms")(run) == pytest.approx(1.03)
    # PR 31's program: no such stage in any cycle
    for r in run["rings"]["local"]:
        del r["stages"]["sink_flush.route"]
    assert _reader("sink_route_ms")(run) is None


def test_entries_are_found_by_name_wherever_they_sit(tmp_path,
                                                     monkeypatch):
    """The order of ``configs``, ``workloads``, ``per_layer`` and of a
    metric's ``workloads`` carries no meaning: with every list of a
    copy reversed the harness loads the same cells, and the file
    keeps every property."""
    bench = structure.load_bench(ROOT)
    want = {n: harness.cell(n) for n in structure.cells(bench)}
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key].reverse()
    for m in bench["per_layer"]:
        m.get("workloads", []).reverse()
    copy_benchmark(str(tmp_path), bench, monkeypatch)
    structure.check_all(bench, str(tmp_path))
    for name, c in want.items():
        got = harness.cell(name)
        assert (got["chips"], got["config"], got["traffic"]) == (
            c["chips"], c["config"], c["traffic"])
        for kind in ("end_to_end", "per_layer"):
            def by_name(ms):
                return {m["name"]: {**m, "workloads": sorted(
                    m.get("workloads", []))} for m in ms}
            assert by_name(got[kind]) == by_name(c[kind])
