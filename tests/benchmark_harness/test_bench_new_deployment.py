"""A rehearsal of the next ``model_config`` PR, on the CPU: a
deployment with a topology of its own comes as new files and appended
entries, and nothing that exists is edited.

The copy of ``benchmark/`` and ``BENCHMARK.json`` under a temporary
root gains a configuration that names its own topology and a limit of
its own, a traffic file with a mode of its own, a four-chip cell, a
per-layer metric with its reader, and one existing metric's
``workloads`` list longer by the new cell.  The harness then runs the
added cell to a result line through that topology (a canned
``serve``, a ``compare`` that returns its own ``NUMBERS``; no
servers: a real global-only topology with import clients is the next
PR's), and every structural property of ``structure.py`` holds on the
copy.
"""

import copy
import filecmp
import json
import os

import pytest

import structure
from bench_util import ROOT, copy_benchmark, run_py

from benchmark import harness, reference

CELL = "global-stub-import"
NEW_METRIC = "global_merge_ms"
# the one existing metric whose list the new cell joins
EXTENDED = "flush_lag_max_ms"

TOPOLOGY = '''"""A global alone, canned: what ``serve`` has to return."""
NUMBERS = ("rows_off",)


def serve(c, spec, seed, seconds, trace, scale, t_start):
    ms = 1_000_000
    t0 = t_start + 3.0
    ticks = [t0 + 1.0, t0 + 3.0]
    snap = {"received": 0, "registry": {"kernels": {}}, "totals": {}}
    return {
        "interval_s": 2.0, "t0": t0, "t_end": t0 + seconds,
        "ticks": ticks,
        # the lag is the global's; a local's would be another list
        "lags": {"global": [0.1, 0.3], "local": [9.0, 9.0]},
        "lag_of": "global",
        "rings": {"global": [{"start_unix": t, "duration_ns": 300 * ms,
                              "metrics_emitted": 7, "forward_rows": 0,
                              "stages": {"merge": (4 + 2 * i) * ms}}
                             for i, t in enumerate(ticks)]},
        "at_t0": {"t": t0, **snap},
        "at_end": {"t": t0 + seconds, **snap},
        "peak": 1 << 20, "trace": None,
        "attempted": spec["clients"] * spec["rows_per_client"] * 2,
        "received": spec["clients"] * spec["rows_per_client"] * 2,
        "blocked_s": 0.0, "late_max_s": 0.002, "sent": 1000,
        "rows_off": seed % 2}


def compare(s, limits):
    off = s["rows_off"]
    return {"rows_off": off}, off
'''

READER = '''"""The global's merge: stage merge of its ring, mean a cycle."""
LAYER = "device apply, kernels"
UNIT = "ms"
MOVES = "flush_lag_ms"


def read(run):
    cycles = [r["stages"] for r in run["rings"].get("global", [])
              if "merge" in r["stages"]]
    if not cycles:
        return None
    return sum(s["merge"] for s in cycles) / len(cycles) / 1e6
'''

MODE = '''"""Import clients: the stub starts none."""


def run(io):
    raise NotImplementedError
'''

CONFIG = {
    "name": "global-stub",
    "source": "BASELINE.json configuration 5 (Global merge: 64 local "
              "nodes -> importsrv), a stand-in for the rehearsal",
    "topology": "stub-global",
    "reduced": ["locals"],
    "guarantees": "every imported row flushed once",
    "limits": {"rows_off": 0},
}
TRAFFIC = {"name": "import-stub", "mode": "import-clients",
           "clients": 64, "rows_per_client": 256}

FILES = {
    "benchmark/topologies/stub-global.py": TOPOLOGY,
    f"benchmark/layer_metrics/{NEW_METRIC}.py": READER,
    "benchmark/modes/import-clients.py": MODE,
    "benchmark/configs/global-stub.json": json.dumps(CONFIG),
    "benchmark/traffic/import-stub.json": json.dumps(TRAFFIC),
}


def _named(entries: list, name: str) -> dict:
    return next(e for e in entries if e["name"] == name)


def _add(bench: dict) -> dict:
    """``bench`` with the next PR's entries appended."""
    out = copy.deepcopy(bench)
    out["configs"].append({
        "name": CONFIG["name"], "source": CONFIG["source"],
        "file": "benchmark/configs/global-stub.json",
        "reduced": ["locals"], "why": "a global alone, 64 importers"})
    out["workloads"].append({
        "name": CELL, "config": CONFIG["name"],
        "traffic": TRAFFIC["name"], "chips": 4,
        "why": "64 import clients a tick into one sharded global"})
    out["per_layer"].append({
        "name": NEW_METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "device apply, kernels",
        "moves": "flush_lag_ms", "workloads": [CELL]})
    _named(out["per_layer"], EXTENDED)["workloads"].append(CELL)
    return out


@pytest.fixture
def added(tmp_path, monkeypatch):
    """The copy with the additions, and the harness looking at it."""
    root = str(tmp_path)
    bench = _add(structure.load_bench(ROOT))
    copy_benchmark(root, bench, monkeypatch)
    for rel, text in FILES.items():
        path = os.path.join(root, rel)
        assert not os.path.exists(path), f"{rel} is not a new file"
        with open(path, "w", encoding="utf-8") as f:
            f.write(text)
    return bench, root


def test_the_additions_edit_nothing_that_exists(added):
    bench, root = added
    before = structure.load_bench(ROOT)
    # every file the benchmark had is in the copy byte for byte
    for sub, _dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        if "__pycache__" in sub:
            continue
        for name in files:
            path = os.path.join(sub, name)
            assert filecmp.cmp(path, os.path.join(
                root, os.path.relpath(path, ROOT)), shallow=False)
    # every entry it had is there as it was, new ones after it; the
    # one list that grew kept what it held
    for key in ("configs", "workloads", "end_to_end"):
        assert all(entry in bench[key] for entry in before[key])
    for old in before["per_layer"]:
        new = dict(_named(bench["per_layer"], old["name"]))
        lists = old.pop("workloads"), new.pop("workloads")
        assert old == new
        assert [c for c in lists[1] if c != CELL] == lists[0]
        assert (lists[0] == lists[1]) == (old["name"] != EXTENDED)
    assert {k: bench[k] for k in ("command", "paths", "run_seconds")} \
        == {k: before[k] for k in ("command", "paths", "run_seconds")}


def test_every_structural_property_holds_on_the_copy(added):
    bench, root = added
    structure.check_all(bench, root)
    assert CELL in structure.cells(bench)
    assert structure.topology_of(bench, root, CELL) == "stub-global"
    # the cells there were are untouched by the newcomer
    for name in structure.cells(structure.load_bench(ROOT)):
        assert structure.topology_of(bench, root, name) \
            == "local-global"


@pytest.mark.parametrize("seed,correct", [(2, True), (3, False)])
def test_the_added_cell_runs_to_a_result_line(added, seed, correct):
    c = harness.cell(CELL)
    assert c["chips"] == 4
    assert c["config"]["topology"] == "stub-global"
    assert {m["name"] for m in c["per_layer"]} == {
        EXTENDED, "global_merge_ms"}
    res = harness.run_cell(c, seed=seed, seconds=4.0, trace=False)
    assert res["correct"] is correct
    assert res["checks"] == {"rows_off": [seed % 2, 0]}
    assert res["attempted"] == 2 * 64 * 256
    assert res["failed"] == (0 if correct else 1)
    line = run_py().result_line(c, res, trace=False)
    assert list(line)[:5] == ["correct", "attempted", "failed",
                              "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"flush_lag_ms", "setup_s"}
    # the mean of the ticks of the server the topology names, not of
    # a local's
    assert line["metrics"]["flush_lag_ms"]["value"] == pytest.approx(
        200.0)
    assert line["metrics"]["setup_s"]["value"] == pytest.approx(3.0)
    assert line["device"]["memory_peak_bytes"] == 1 << 20
    traced = run_py().result_line(c, res, trace=True)
    assert {k: v["value"] for k, v in traced["metrics"].items()} == {
        EXTENDED: pytest.approx(300.0),
        "global_merge_ms": pytest.approx(5.0)}
    json.dumps(traced)


def test_the_cells_there_were_load_as_before_beside_the_new_one(added):
    bench, root = added
    for name in structure.cells(structure.load_bench(ROOT)):
        c = harness.cell(name)
        assert "topology" not in c["config"]
        assert CELL not in [m["name"] for m in c["per_layer"]]
        assert "global_merge_ms" not in [m["name"]
                                         for m in c["per_layer"]]
    with pytest.raises(reference.Failed, match="no workload"):
        harness.cell("global-stub")


def _limit_renamed(bench, root):
    path = os.path.join(root, "benchmark/configs/global-stub.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({**CONFIG, "limits": {"sums_off": 0}}, f)


def _source_differs(bench, root):
    _named(bench["configs"], CONFIG["name"])["source"] += " (edited)"


def _two_chips(bench, root):
    _named(bench["workloads"], CELL)["chips"] = 2


def _every_cell_on_four(bench, root):
    for w in bench["workloads"]:
        w["chips"] = 4


def _no_mode(bench, root):
    os.remove(os.path.join(root, "benchmark/modes/import-clients.py"))


def _no_topology(bench, root):
    os.remove(os.path.join(root, "benchmark/topologies/stub-global.py"))


def _reader_moves_another(bench, root):
    _named(bench["per_layer"], NEW_METRIC)["moves"] = "setup_s"


def _metric_lists_no_such_cell(bench, root):
    _named(bench["per_layer"], NEW_METRIC)["workloads"].append(
        "global-sharded")


def _cell_without_a_layer_metric(bench, root):
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            m["workloads"].remove(CELL)


def _roofline_without_a_kernel(bench, root):
    _named(bench["per_layer"], NEW_METRIC).update(
        name="fold_roofline", unit="%")
    os.rename(
        os.path.join(root, "benchmark/layer_metrics/global_merge_ms.py"),
        os.path.join(root, "benchmark/layer_metrics/fold_roofline.py"))
    with open(os.path.join(
            root, "benchmark/layer_metrics/fold_roofline.py"), "w",
            encoding="utf-8") as f:
        f.write(READER.replace('UNIT = "ms"', 'UNIT = "%"'))


@pytest.mark.parametrize("fault", [
    _limit_renamed, _source_differs, _two_chips, _every_cell_on_four,
    _no_mode, _no_topology, _reader_moves_another,
    _metric_lists_no_such_cell, _cell_without_a_layer_metric,
    _roofline_without_a_kernel], ids=lambda f: f.__name__.strip("_"))
def test_the_properties_refuse_an_addition_that_disagrees(added, fault):
    """Each property with the addition broken against it: the checks
    that replaced the pins hold a later PR to something."""
    bench, root = added
    fault(bench, root)
    with pytest.raises(AssertionError):
        structure.check_all(bench, root)
