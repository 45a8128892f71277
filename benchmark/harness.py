"""One run of one cell: what every topology shares.

A cell's configuration names its topology (``"topology"`` in
``configs/<config>.json``; absent, ``local-global``), found like
modes, readers and kernels by that name:
``benchmark/topologies/<name>.py``.  The topology brings the servers
up, drives the window and compares what the sinks hold with its
reference (its docstring has the contract of ``serve`` and
``compare``).  Here is the rest of a run: the cell's files, the limits
and the rehearsal's ``scale``, ``checks`` and ``correct``, the device
block, and the ``phase:`` lines on standard error.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time

from benchmark import reference, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

DEFAULT_TOPOLOGY = "local-global"

# every one of these has to read 0 on every server over the compared
# intervals (stats are deltas from the last warm-up interval's start).
# A task that overran its budget or a destination skipped as busy is
# printed and not held: what it failed to deliver, the comparison
# misses; what it delivered late, the lag shows.
ZERO_STATS = ("flush_errors", "forward_errors", "metrics_dropped",
              "packet_errors", "flush_coalesced")
SHOWN_STATS = ("flush_skipped_busy", "flush_slow_tasks")


def log(**kw) -> None:
    """An earlier line of the run: one JSON object on standard error."""
    print(json.dumps(kw, sort_keys=True, default=str), file=sys.stderr,
          flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts), encoding="utf-8") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, found by the name in the data."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    if spec is None or not os.path.exists(path):
        raise reference.Failed(f"no file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(workload: str) -> dict:
    """The cell's entry of ``BENCHMARK.json`` with its files read."""
    with open(os.path.join(ROOT, "BENCHMARK.json"),
              encoding="utf-8") as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"]
                  if w["name"] == workload), None)
    if entry is None:
        raise reference.Failed(f"no workload {workload!r} in "
                               "BENCHMARK.json")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]
    return {"name": workload, "chips": entry["chips"],
            "config": load_json("configs", entry["config"] + ".json"),
            "traffic": load_json("traffic", entry["traffic"] + ".json"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def accounting(name: str, server, seq0: int, stats0: dict) -> dict:
    """Everything that may have been swallowed or dropped since the
    last warm-up interval opened; all of it has to read 0."""
    stats = dict(server.stats)
    recs = [r for r in server.ledger.records() if r.seq > seq0]
    out = {k: stats.get(k, 0) - stats0.get(k, 0) for k in ZERO_STATS}
    out.update(
        table_overflow=sum(sum(r.table_overflow.values())
                           for r in recs),
        ledger_dropped=sum(r.dropped_total() for r in recs),
        ledger_shed=sum(r.shed for r in recs),
        kernel_drops=sum(r.kernel_drops for r in recs),
        coalesced=sum(r.coalesced for r in recs),
        unbalanced=sum(1 for r in recs if not r.balanced),
        cycle_errors=sum(1 for r in server.flush_ring.records()
                         if r.seq > seq0 and r.error))
    log(phase="accounting", server=name, intervals=len(recs), **out,
        **{k: stats.get(k, 0) - stats0.get(k, 0) for k in SHOWN_STATS})
    return out


def memory_peak() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") or 0
             for d in jax.devices()]
    return int(max(peaks))


def ring_dict(r) -> dict:
    """One flush cycle as readers and ``phase: cycles`` get it: every
    field of the program's ``FlushRecord`` (``observe/flushring.py``)
    under its own name, ``stages`` in nanoseconds."""
    return dataclasses.asdict(r)


def log_cycles(lags: dict, rings: dict) -> None:
    """Every cycle of the window whole, by server: each stage in
    seconds to the microsecond (``sink_flush.route`` takes some 30 of
    them), the other fields of the record as they are."""
    log(phase="cycles",
        lags={f"{name}_s": [round(x, 3) for x in v]
              for name, v in lags.items()},
        **{name: [{"wall_s": round(r["duration_ns"] / 1e9, 3),
                   "emitted": r["metrics_emitted"],
                   "forwarded": r["forward_rows"],
                   "stages_s": {k: round(v / 1e9, 6)
                                for k, v in r["stages"].items()},
                   **{k: v for k, v in r.items() if k not in (
                       "duration_ns", "metrics_emitted", "forward_rows",
                       "stages", "trace_id")}} for r in recs]
           for name, recs in rings.items()})


# ----------------------------------------------------------------------
# the run

def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             scale: dict | None = None) -> dict:
    """The body of a run.  ``scale`` is the CPU rehearsal's: it may
    override the ``round`` and the numbers of the traffic file, the
    servers' keys (the interval among them) and the limits;
    ``run.py`` never passes it.  Returns what the result line is made
    from; only ``run.py`` insists on a TPU."""
    import jax

    t_start = c.get("t_start", time.time())
    scale = scale or {}
    spec = traffic.scaled(c["traffic"], scale)
    limits = {**c["config"]["limits"], **scale.get("limits", {})}
    topology = load_module("topologies", c["config"].get(
        "topology", DEFAULT_TOPOLOGY))
    s = topology.serve(c, spec, seed, seconds, trace, scale, t_start)
    log_cycles(s["lags"], s["rings"])

    # the reference, once the window is closed, the peak is read and
    # the program's state is freed
    t = time.monotonic()
    numbers, failed = topology.compare(s, limits)
    compare_s = time.monotonic() - t
    if set(numbers) != set(limits):
        raise reference.Failed(
            f"the comparison returned {sorted(numbers)} and the "
            f"configuration's limits name {sorted(limits)}")

    t0, t_end = s["t0"], s["t_end"]
    devs = jax.devices()
    run = {
        "cell": c["name"], "seed": seed, "seconds": seconds,
        "interval_s": s["interval_s"], "t0": t0, "t_end": t_end,
        "ticks": s["ticks"], "setup_s": t0 - t_start,
        "lines_received": s["received"], "lines_sent": s["attempted"],
        "lags": s["lags"], "lag_of": s["lag_of"],
        "rings": s["rings"], "at_t0": s["at_t0"],
        "at_end": s["at_end"], "blocked_s": s["blocked_s"],
        "late_max_s": s["late_max_s"], "trace": s["trace"],
    }
    k0 = s["at_t0"]["registry"]["kernels"]
    log(phase="compiled_in_window", kernels={
        k: v["compiles"] - k0.get(k, {}).get("compiles", 0)
        for k, v in s["at_end"]["registry"]["kernels"].items()
        if v["compiles"] - k0.get(k, {}).get("compiles", 0)})
    log(phase="sender", sent=s["sent"], blocked_s=run["blocked_s"],
        late_max_s=run["late_max_s"], lines_sent=run["lines_sent"],
        lines_received=run["lines_received"])
    log(phase="reference", compare_s=round(compare_s, 2),
        run_s=round(time.time() - t_start, 1))
    checks = {k: [numbers[k], limits[k]] for k in limits}
    correct = all(v <= lim for v, lim in checks.values())
    if not correct and not failed:
        failed = s["attempted"]      # the window is not borne out
    return {"run": run, "correct": correct,
            "attempted": s["attempted"], "failed": failed,
            "checks": checks,
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs),
                       "memory_peak_bytes": s["peak"]}}
