"""The yardstick's own arithmetic: the control, the trace reduction on
a recorded fixture, a tick's lag from hand-made records, and
``BENCHMARK.json`` against the contract's rules for names and against
the files it names."""

import json
import os
import re

import pytest

from bench_util import ROOT, TINY

from benchmark import control, harness, reference, trace

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


# ----------------------------------------------------------------------
# the control

@pytest.mark.parametrize("name", CELLS)
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
    lags, missing = harness.tick_lags(srv, _Sink(stamps),
                                      [100.0, 110.0], 10.0, _IMPORTS)
    assert lags == pytest.approx(want[0]) and missing == want[1]


def test_a_failed_cycle_is_a_missing_tick():
    srv = _Srv([_Rec(1, 100.01, 0, error="boom")])
    assert harness.tick_lags(srv, _Sink([100.2]), [100.0], 10.0,
                             _IMPORTS) == ([], 1)


# ----------------------------------------------------------------------
# BENCHMARK.json

def test_benchmark_json_has_exactly_the_contracts_keys():
    assert sorted(BENCH) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "bound", "name", "source", "unit"]
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_name_and_unit_is_made_of_the_allowed_characters():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [w["name"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(m["name"] for m in BENCH["end_to_end"]
                   + BENCH["per_layer"])) == len(
        BENCH["end_to_end"] + BENCH["per_layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for x in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(
        metric):
    reader = harness.load_module("layer_metrics", metric["name"])
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        metric["layer"], metric["unit"], metric["moves"])
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    moved = e2e[metric["moves"]]
    for cell_name in metric.get("workloads", CELLS):
        assert cell_name in moved.get("workloads", CELLS)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("name", CELLS)
def test_cell_is_nothing_but_files_and_entries(name):
    c = harness.cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert entry["chips"] == 1
    cfg = next(x for x in BENCH["configs"]
               if x["name"] == entry["config"])
    assert cfg["file"] == f"benchmark/configs/{entry['config']}.json"
    assert c["config"]["source"] == cfg["source"]
    assert sorted(c["config"]["reduced"]) == sorted(cfg["reduced"])
    assert c["traffic"]["name"] == entry["traffic"]
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "modes", c["traffic"]["mode"] + ".py"))
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"]
    # every number compared has a limit in the configuration's file
    assert set(c["config"]["limits"]) == {
        "sums_off", "readings_missing", "p99_out", "p50_rank_err",
        "p90_rank_err", "card_rel_err", "lines_unaccounted", "dropped",
        "ticks_missing", "sender_blocked_pct"}
