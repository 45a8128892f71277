"""The yardstick's own arithmetic: the control, the trace reduction on
a recorded fixture, a tick's lag from hand-made records, and
``BENCHMARK.json`` against the contract's rules for names and against
the files it names."""

import json
import os

import pytest

import structure
from bench_util import ROOT, TINY, topology

from benchmark import control, harness, reference, trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = structure.load_bench(ROOT)
CELLS = structure.cells(BENCH)
# ``control.py`` is the control of the topology ``local-global``: its
# cells, whichever they are
CONTROL_CELLS = [n for n in CELLS if structure.topology_of(
    BENCH, ROOT, n) == "local-global"]


# ----------------------------------------------------------------------
# the control

@pytest.mark.parametrize("name", CONTROL_CELLS)
@pytest.mark.parametrize("fault", control.FAULTS)
def test_control_fails_and_faithful_reference_passes(name, fault):
    c = harness.cell(name)
    n = c["traffic"]["rounds_per_interval"]
    res = control.run(c, seed=9, fault=fault, rounds_sent=n,
                      scale={"round": {**TINY[name]["round"],
                                       "timers": 60,
                                       "samples_per_timer": 10 if n > 1
                                       else 100}})
    assert res["correct"] == (fault == "none"), res["checks"]
    if fault == "none":
        assert all(v == 0 for v, _ in res["checks"].values())


def test_rank_distance_allows_one_rank_and_no_more():
    import numpy as np
    xs = np.sort(np.random.default_rng(0).lognormal(0, 0.6, (1, 101)), 1)
    mid = xs[:, 50]
    assert reference._rank_distance(xs, 0.5, mid)[0] == 0
    assert reference._rank_distance(xs, 0.5, xs[:, 51])[0] == 0
    assert reference._rank_distance(xs, 0.5, xs[:, 53])[0] == 2
    between = (xs[:, 47] + xs[:, 48]) / 2
    assert reference._rank_distance(xs, 0.5, between)[0] == 1.5


# ----------------------------------------------------------------------
# the trace reduction

def test_trace_reduction_on_the_recorded_fixture():
    with open(os.path.join(HERE, "fixture_trace.json"),
              encoding="utf-8") as f:
        fx = json.load(f)
    out = trace.reduce(fx["data"], fx["anchors_unix"], fx["cycles"])
    want = fx["want"]
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(want["window_s"])
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    # worked out another way when the fixture was cut: the device's
    # operations rastered into 10 ns bins
    assert out["busy_s"] == pytest.approx(want["busy_s_raster"],
                                          abs=1e-6)
    for name, m in want["modules"].items():
        assert out["modules"][name]["n"] == m["n"]
        assert out["modules"][name]["total_s"] == pytest.approx(
            m["total_s"])
    assert out["device_ops"][0][0] == want["top_op"]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    # the ten are the head of the whole list, which no ranking cuts
    assert out["device_ops"] == out["device_ops_all"][:10]
    assert len(out["device_ops_all"]) >= len(out["device_ops"])
    assert out["idle_gaps"][0][0] == want["longest_gap_owner"]
    assert out["idle_gaps"][0][1] == pytest.approx(
        want["longest_gap_s"])
    assert 0 < out["busy_s"] < out["window_s"]


def test_trace_reduction_finds_nothing_without_a_device_plane():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [[trace.ANCHOR, 10.0, 5.0],
                                       [trace.ANCHOR, 2e9, 5.0]]}]}]}
    assert trace.reduce(host_only, [100.0, 102.0], []) is None


def test_union_and_hand_made_planes():
    data = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            [trace.ANCHOR, 0.0, 1.0], [trace.ANCHOR, 1e9, 1.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.MODULES_LINE, "events": [
                ["jit_step(1)", 1e8, 2e8], ["jit_step(1)", 5e8, 2e8]]},
            {"name": trace.OPS_LINE, "events": [
                ["fusion.1", 1e8, 1e8], ["fusion.2", 1.5e8, 1.5e8],
                ["fusion.1", 5e8, 2e8]]}]}]}
    # a flush cycle covers 0.3-0.5 s of the slice
    out = trace.reduce(data, [1000.0, 1001.0], [(1000.3, 1000.5)])
    assert out["busy_s"] == pytest.approx(0.4)
    assert out["window_s"] == pytest.approx(1.0)
    assert out["modules"] == {"jit_step": {"n": 2, "total_s":
                                           pytest.approx(0.4)}}
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(0.3)]
    assert out["idle_gaps"][0] == ["ingest", pytest.approx(0.3)]
    assert ["flush_cycle", pytest.approx(0.2)] in out["idle_gaps"]


def test_a_kernel_below_the_ten_longest_is_still_summed():
    """Twelve operations, the merge kernel's two buckets the
    shortest: the breakdown's ten do not hold them, the whole list
    does, and ``merge_device_ms`` reads that."""
    ops = [[f"%fusion.{i} = f32[8]{{0}} fusion(%p)", 1e6 * i, 1e5 * i]
           for i in range(3, 13)]
    ops += [['%tdigest_merge_c616_k96.1 = f32[8]{0} custom-call(%p), '
             'custom_call_target="tpu_custom_call"', 2e8, 2e4],
            ['%tdigest_merge_c616_k256.1 = f32[8]{0} custom-call(%p), '
             'custom_call_target="tpu_custom_call"', 3e8, 1e4]]
    data = {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            [trace.ANCHOR, 0.0, 1.0], [trace.ANCHOR, 1e9, 1.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": trace.OPS_LINE, "events": ops}]}]}
    out = trace.reduce(data, [1000.0, 1001.0], [])
    assert len(out["device_ops"]) == 10
    assert len(out["device_ops_all"]) == 12
    assert not any("tdigest_merge" in n for n, _ in out["device_ops"])
    read = harness.load_module("layer_metrics", "merge_device_ms").read
    assert read({"trace": out}) == pytest.approx(1e3 * 3e-5)
    # a trace reduced before the whole list was kept reads nothing
    assert read({"trace": {"device_ops": out["device_ops"]}}) is None


# ----------------------------------------------------------------------
# a tick's lag

class _Rec:
    def __init__(self, seq, start_unix, forward_rows, error=""):
        self.seq, self.start_unix = seq, start_unix
        self.forward_rows, self.error = forward_rows, error
        self.duration_ns = int(5e9)     # the cycle's own end: not read


class _Srv:
    def __init__(self, recs):
        self.flush_ring = type("Ring", (), {
            "records": staticmethod(lambda: recs)})


class _Sink:
    def __init__(self, stamps):
        self.batches = [(t, []) for t in stamps]


# the global's count of imported rows as the watcher saw it rise
_IMPORTS = [(90.0, 0), (100.4, 50), (100.7, 100), (110.3, 160),
            (110.35, 200), (121.0, 300)]


@pytest.mark.parametrize("stamps,recs,want", [
    # the later of the sink's stamp and the forward's arrival
    ([100.2, 110.5], [(1, 100.01, 100), (2, 110.01, 100)],
     ([0.7, 0.5], 0)),
    # nothing forwarded: the sink's stamp alone
    ([100.2, 110.5], [(1, 100.01, 0), (2, 110.01, 0)],
     ([0.2, 0.5], 0)),
    # the second forward arrived short of its rows
    ([100.2, 110.5], [(1, 100.01, 100), (2, 110.01, 120)],
     ([0.7], 1)),
    # no batch reached the sink for the second cycle
    ([100.2], [(1, 100.01, 100), (2, 110.01, 100)], ([0.7], 1)),
], ids=["forward-later", "no-forward", "forward-short", "no-batch"])
def test_tick_lag_ends_at_the_later_of_sink_and_forward(
        stamps, recs, want):
    srv = _Srv([_Rec(*r) for r in recs])
    lags, missing = topology().tick_lags(srv, _Sink(stamps),
                                      [100.0, 110.0], 10.0, _IMPORTS)
    assert lags == pytest.approx(want[0]) and missing == want[1]


def test_a_failed_cycle_is_a_missing_tick():
    srv = _Srv([_Rec(1, 100.01, 0, error="boom")])
    assert topology().tick_lags(srv, _Sink([100.2]), [100.0], 10.0,
                             _IMPORTS) == ([], 1)


# ----------------------------------------------------------------------
# BENCHMARK.json: every property by name (``structure.py``), on the
# repo's own file here and on a copy with a later PR's additions in
# ``test_bench_new_deployment.py``

def test_benchmark_json_has_exactly_the_contracts_keys():
    structure.check_contract_keys(BENCH, ROOT)
    assert BENCH["run_seconds"] == 40


def test_every_name_and_unit_is_made_of_the_allowed_characters():
    structure.check_names_and_units(BENCH, ROOT)


def test_configurations_are_used_and_four_chip_cells_are_the_fewer():
    structure.check_configs_and_chips(BENCH, ROOT)


@pytest.mark.parametrize("n_cells,n_four,ok", [
    (1, 1, True), (2, 1, True), (3, 1, True), (3, 2, False),
    (4, 2, True), (5, 3, False), (2, 2, False)])
def test_four_chip_share_is_half_rounded_down_and_one_always(
        n_cells, n_four, ok):
    """The rule itself, on hand-made lists: the repo's own file has
    no four-chip cell to show it on."""
    bench = {"paths": ["benchmark"], "configs": [
        {"name": f"c{i}", "file": f"benchmark/configs/c{i}.json"}
        for i in range(n_cells)], "workloads": [
        {"name": f"w{i}", "config": f"c{i}", "traffic": "t",
         "chips": 4 if i < n_four else 1} for i in range(n_cells)]}
    if ok:
        structure.check_configs_and_chips(bench, ROOT)
    else:
        with pytest.raises(AssertionError):
            structure.check_configs_and_chips(bench, ROOT)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
        metric):
    structure.check_metric(BENCH, ROOT, metric)
    # the harness finds the same reader by the same name
    reader = harness.load_module("layer_metrics", metric["name"])
    assert reader.MOVES == metric["moves"]


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_nothing_but_files_and_entries(name):
    structure.check_cell(BENCH, ROOT, name)
    # and the harness reads the same files
    c = harness.cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert c["chips"] == entry["chips"]
    assert c["config"]["name"] == entry["config"]
    assert c["traffic"]["name"] == entry["traffic"]
    assert {m["name"] for m in c["per_layer"]} == {
        m["name"] for m in BENCH["per_layer"]
        if name in structure.reports(BENCH, m)}


def test_a_limit_without_a_number_is_refused_before_any_result(
        monkeypatch):
    """``run_cell`` holds the comparison to the configuration's
    limits name for name: a limit that nothing is compared with, or a
    number without a limit, is a run that could not be checked."""
    stub = type("Topo", (), {
        "serve": staticmethod(lambda *a: {"lags": {}, "rings": {}}),
        "compare": staticmethod(lambda s, limits: ({"a": 0}, 0))})
    monkeypatch.setattr(harness, "load_module", lambda *a: stub)
    c = {"name": "x", "traffic": {}, "config": {"limits": {
        "a": 0, "b": 0}}}
    with pytest.raises(reference.Failed, match="limits name"):
        harness.run_cell(c, seed=1, seconds=1.0, trace=False)
