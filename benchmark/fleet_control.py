#!/usr/bin/env python3
"""The control of the topology ``fleet-global``: the reference in the
program's place, with one guarantee of the configuration broken.  It
has to come out as not correct, by the comparison and the limits every
run uses.

    python3 benchmark/fleet_control.py --workload <name> --seed <n>
        [--fault none|drop_wire|double_wire|coarse_digest|
                 halve_sketches]

No server runs and no chip is needed: the sink's values are made from
the fleet's raw draws by the reference itself.  ``none`` is the
faithful reference (every number 0); the faults are what would tempt a
later PR: an acknowledged wire left out of the flush (delivery), a wire
folded twice (a retry counted again), percentiles from a t-digest at
compression 20 where the configuration states 100, half of a set's
sketches left out of its union.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import fleet as fleet_mod  # noqa: E402
from benchmark import fleet_reference, reference, traffic  # noqa: E402
from benchmark.control import digest_quantile  # noqa: E402

FAULTS = ("none", "drop_wire", "double_wire", "coarse_digest",
          "halve_sketches")


def outputs(ref: dict, fault: str) -> dict:
    """What the global's sink would hold for an interval whose folds
    came to ``ref``."""
    glob: dict = {}
    for key, v in ref["gcounters"].items():
        glob[key] = [float(v)]
    by_n: dict[int, list] = {}
    for key, xs in ref["timers"].items():
        by_n.setdefault(len(xs), []).append(key)
    for _n, keys in by_n.items():
        xs = np.sort(np.asarray([ref["timers"][k] for k in keys]), 1)
        for q in reference.PERCENTILES:
            vals = (digest_quantile(xs, q, 20.0)
                    if fault == "coarse_digest"
                    else np.quantile(xs, q, axis=1))
            suffix = f".{int(round(q * 100))}percentile"
            for (name, tags), v in zip(keys, vals):
                glob[(name + suffix, tags)] = [float(v)]
    for key, n in ref["sets"].items():
        glob[key] = [float(n)]
    return glob


def run(c: dict, seed: int, fault: str, scale: dict | None = None
        ) -> dict:
    """One interval of round 0, every client's call acknowledged, and
    the sink's values of a program with ``fault``."""
    spec = traffic.scaled(c["traffic"], scale or {})
    fl = fleet_mod.Fleet(spec, seed)
    rounds = [fl.round(0)]
    rng = np.random.default_rng(seed + 1)
    calls = [(l, 0) for l in range(fl.clients)]
    folded = list(calls)
    victim = int(rng.integers(fl.clients))
    if fault == "drop_wire":
        folded.remove((victim, 0))
    elif fault == "double_wire":
        folded.append((victim, 0))
    ref = fleet_reference.interval(fl, rounds, calls)
    mine = fleet_reference.interval(fl, rounds, folded)
    if fault == "halve_sketches":
        # every second sender's sketches never reach the union
        half = rounds[0]["members"][:, ::2]
        mine["sets"] = {
            reference._key(fl.names["set"][i], fl.tags["set"][i]):
                len(np.unique(half[i])) for i in range(fl.n["set"])}
    res = fleet_reference.compare_interval(ref, outputs(mine, fault))
    res["numbers"]["wires_unaccounted"] = abs(len(folded) - len(calls))
    limits = c["config"]["limits"]
    checks = {k: [v, limits[k]] for k, v in res["numbers"].items()}
    return {"fault": fault, "seed": seed, "rows": ref["rows"],
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": checks, "notes": res["notes"][:3]}


def main(argv=None) -> int:
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", choices=FAULTS, default="drop_wire")
    args = ap.parse_args(argv)
    c = harness.cell(args.workload)
    print(json.dumps(run(c, args.seed, args.fault)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
