"""Shared by the benchmark's tests: the repo's root on ``sys.path``,
``benchmark/run.py`` as a module, and the tiny scale at which a
cell's body runs on the CPU.

``TINY`` maps a cell to that scale: ``tiny/<cell>.json`` beside this
file, found by the cell's name, so a later cell brings its scale as
a file of its own.  The scales there run the tier the chip runs (the
chip's machine refuses io_uring: ``recvmmsg``) at a 2 s interval,
since a compile on the CPU backend (a new shape bucket can come in
any interval) takes most of 1 s; some thirty to fifty datagrams an
interval, the sender held back once four wait unread.
``local-mixed-paced`` keeps the cell's own shape: 16 rounds an
interval of 6 samples a timer (96 an interval stay singletons at
compression 100), all four classes side by side, 32 rounds, so two
distinct intervals alternate.
"""

import glob
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _tiny() -> dict:
    out = {}
    for path in sorted(glob.glob(os.path.join(HERE, "tiny", "*.json"))):
        with open(path, encoding="utf-8") as f:
            out[os.path.basename(path)[:-len(".json")]] = json.load(f)
    return out


TINY = _tiny()


def run_py():
    """``benchmark/run.py`` as a module (it is a script: not on the
    package's import path under that name)."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_py", os.path.join(ROOT, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def topology(name: str = "local-global"):
    from benchmark import harness
    return harness.load_module("topologies", name)


def copy_benchmark(root: str, bench: dict, monkeypatch) -> None:
    """A copy of ``benchmark/`` under ``root`` with ``bench`` as its
    ``BENCHMARK.json``, and the harness looking there for the rest of
    the test."""
    from benchmark import harness
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f, indent=1)
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "HERE", os.path.join(root, "benchmark"))
