"""What ``BENCHMARK.json`` and the files under ``benchmark/`` have to
satisfy, each a function of ``(bench, root)``: the parsed file and the
directory that holds it.  Every entry is looked up by its name; no
check knows a position, a count or which names there are, so the same
functions hold on a copy to which a later PR's files and entries have
been added (``test_bench_new_deployment.py``) as on the repo's own.
"""

import importlib.util
import json
import os
import re

import bench_util  # noqa: F401  (puts the repo's root on sys.path)

from benchmark.harness import DEFAULT_TOPOLOGY

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter",
           "host_clock")


def _json(root: str, *parts: str) -> dict:
    with open(os.path.join(root, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_bench(root: str) -> dict:
    return _json(root, "BENCHMARK.json")


def _module(root: str, kind: str, name: str):
    """``<root>/benchmark/<kind>/<name>.py``, read from ``root`` and
    not through the harness (whose own root is the repo's)."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    assert os.path.exists(path), f"no file {path}"
    spec = importlib.util.spec_from_file_location(
        "structure_" + re.sub(r"\W", "_", f"{kind}_{name}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cells(bench: dict) -> list[str]:
    return [w["name"] for w in bench["workloads"]]


def reports(bench: dict, metric: dict) -> list[str]:
    """The cells that report ``metric``: its list, or every cell."""
    return metric.get("workloads", cells(bench))


def topology_of(bench: dict, root: str, cell_name: str) -> str:
    entry = next(w for w in bench["workloads"]
                 if w["name"] == cell_name)
    return _json(root, "benchmark", "configs",
                 entry["config"] + ".json").get(
        "topology", DEFAULT_TOPOLOGY)


# ----------------------------------------------------------------------
# the file as a whole

def check_contract_keys(bench: dict, root: str) -> None:
    assert sorted(bench) == sorted([
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"])
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) < 65536
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "bound", "name", "source", "unit"]
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert m["source"] in SOURCES
    for w in bench["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
    for c in bench["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]


def check_names_and_units(bench: dict, root: str) -> None:
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in (metrics, bench["workloads"], bench["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for x in bench["workloads"] + bench["configs"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"]
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16


def check_configs_and_chips(bench: dict, root: str) -> None:
    """Every configuration is some cell's and has a file of its own
    under ``paths``; a pair of configuration and traffic appears
    once; at most half the cells, rounded down, and one always, ask
    for four chips."""
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    assert all(f.startswith(tuple(p + "/" for p in bench["paths"]))
               for f in files)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


# ----------------------------------------------------------------------
# one cell, one metric

def check_cell(bench: dict, root: str, name: str) -> None:
    """The cell is nothing but files and entries: a configuration
    whose file agrees with its entry, a traffic file whose mode
    exists, limits that name exactly the numbers its topology's
    comparison returns, ``setup_s`` and one more end-to-end metric,
    and a per-layer metric."""
    entry = next(w for w in bench["workloads"] if w["name"] == name)
    cfg = next(c for c in bench["configs"]
               if c["name"] == entry["config"])
    assert cfg["file"] == f"benchmark/configs/{entry['config']}.json"
    config = _json(root, cfg["file"])
    assert config["name"] == cfg["name"]
    assert config["source"] == cfg["source"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    assert config["guarantees"]
    traffic = _json(root, "benchmark", "traffic",
                    entry["traffic"] + ".json")
    assert traffic["name"] == entry["traffic"]
    assert os.path.exists(os.path.join(
        root, "benchmark", "modes", traffic["mode"] + ".py"))
    topo = _module(root, "topologies",
                   config.get("topology", DEFAULT_TOPOLOGY))
    assert callable(topo.serve) and callable(topo.compare)
    # every number compared has a limit in the configuration's file,
    # and the file holds no limit that nothing is compared with
    assert set(config["limits"]) == set(topo.NUMBERS)
    assert all(isinstance(v, (int, float))
               for v in config["limits"].values())
    e2e = {m["name"] for m in bench["end_to_end"]
           if name in reports(bench, m)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(name in reports(bench, m) for m in bench["per_layer"])


def check_metric(bench: dict, root: str, metric: dict) -> None:
    """The metric has a reader that says the same of itself; the
    cells it lists exist and report the end-to-end metric it moves; a
    roofline share is a percentage and has its bytes or operations
    function under ``benchmark/kernels/``."""
    reader = _module(root, "layer_metrics", metric["name"])
    assert callable(reader.read)
    assert (reader.LAYER, reader.UNIT, reader.MOVES) == (
        metric["layer"], metric["unit"], metric["moves"])
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == metric["moves"])
    for cell_name in reports(bench, metric):
        assert cell_name in cells(bench)
        assert cell_name in reports(bench, moved)
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"
        kernel = metric["name"][:-len("_roofline")]
        mods = [f[:-3] for f in os.listdir(os.path.join(
            root, "benchmark", "kernels"))
            if f.endswith(".py") and kernel in f]
        assert mods, f"no benchmark/kernels/*{kernel}*.py"
        assert any(callable(getattr(_module(root, "kernels", m),
                                    "floor_ms", None)) for m in mods)


def check_all(bench: dict, root: str) -> None:
    check_contract_keys(bench, root)
    check_names_and_units(bench, root)
    check_configs_and_chips(bench, root)
    for name in cells(bench):
        check_cell(bench, root, name)
    for metric in bench["per_layer"]:
        check_metric(bench, root, metric)
