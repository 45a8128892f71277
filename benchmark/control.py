#!/usr/bin/env python3
"""The control: the reference in the program's place, with one
guarantee of the configuration broken.  It has to come out as not
correct, by the comparison and the limits every run uses.

    python3 benchmark/control.py --workload <name> --seed <n>
        [--fault none|drop_datagram|halve_sets|coarse_digest]

No server runs and no chip is needed: the sinks' values are made from
the sent bytes by the reference itself.  ``none`` is the faithful
reference (every number 0); the faults are what would tempt a later
PR: a datagram not aggregated (delivery), half of the set lines
sampled away (the overload policy's level 1, without the pressure
that licenses it), percentiles from a t-digest at compression 20
where the configuration states 100.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmark import reference, traffic  # noqa: E402

FAULTS = ("none", "drop_datagram", "halve_sets", "coarse_digest")


def digest_quantile(xs: np.ndarray, q: float, compression: float
                    ) -> np.ndarray:
    """Quantile ``q`` of each sorted row of ``xs`` from a merging
    t-digest of the row at ``compression`` (scale function k1, one
    pass over sorted data), centroids interpolated at their middle
    ranks."""
    n = xs.shape[1]

    def k(rank: float) -> float:
        return compression / (2 * math.pi) * math.asin(
            2 * min(max(rank / n, 0.0), 1.0) - 1)
    bounds = [0]
    while bounds[-1] < n:
        a = bounds[-1]
        b = a + 1
        while b < n and k(b + 1) - k(a) <= 1.0:
            b += 1
        bounds.append(b)
    means = np.stack([xs[:, a:b].mean(1)
                      for a, b in zip(bounds, bounds[1:])], 1)
    mids = np.array([(a + b - 1) / 2
                     for a, b in zip(bounds, bounds[1:])])
    pos = q * (n - 1)
    return np.array([np.interp(pos, mids, row) for row in means])


def outputs(ref: dict, fault: str, rng) -> tuple[dict, dict]:
    """What the two sinks would hold for this interval."""
    local: dict = {}
    glob: dict = {}
    for key, v in ref["counters"].items():
        local[key] = [float(v)]
    for key, v in ref["gauges"].items():
        local[key] = [float(v)]
    for (name, tags), xs in ref["timers"].items():
        local[(name + ".count", tags)] = [float(len(xs))]
    for key, v in ref["gcounters"].items():
        glob[key] = [float(v)]
    by_n: dict[int, list] = {}
    for key, xs in ref["timers"].items():
        by_n.setdefault(len(xs), []).append(key)
    for n, keys in by_n.items():
        xs = np.sort(np.asarray([ref["timers"][k] for k in keys]), 1)
        for q in reference.PERCENTILES:
            vals = (digest_quantile(xs, q, 20.0)
                    if fault == "coarse_digest"
                    else np.quantile(xs, q, axis=1))
            suffix = f".{int(round(q * 100))}percentile"
            for (name, tags), v in zip(keys, vals):
                glob[(name + suffix, tags)] = [float(v)]
    for key, members in ref["sets"].items():
        n = len(members)
        if fault == "halve_sets":
            n = int(rng.binomial(n, 0.5))
        glob[key] = [float(n)]
    return local, glob


def run(c: dict, seed: int, fault: str, rounds_sent: int,
        scale: dict | None = None) -> dict:
    spec = traffic.scaled(c["traffic"], scale or {})
    rounds = traffic.make_rounds(spec, seed)
    rng = np.random.default_rng(seed + 1)
    parsed = [reference.parse_round(r) for r in rounds]
    order = [i % len(rounds) for i in range(rounds_sent)]
    ref = reference.combine([parsed[i] for i in order])
    seen = parsed
    if fault == "drop_datagram":
        r = order[int(rng.integers(len(order)))]
        d = int(rng.integers(len(rounds[r])))
        seen = list(parsed)
        seen[r] = reference.parse_round(rounds[r][:d]
                                        + rounds[r][d + 1:])
    local, glob = outputs(reference.combine([seen[i] for i in order]),
                          fault, rng)
    res = reference.compare_interval(ref, local, glob)
    limits = c["config"]["limits"]
    checks = {k: [v, limits[k]] for k, v in res["numbers"].items()}
    return {"fault": fault, "seed": seed, "lines": ref["lines"],
            "correct": all(v <= lim for v, lim in checks.values()),
            "checks": checks, "notes": res["notes"][:3]}


def main(argv=None) -> int:
    from benchmark import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fault", choices=FAULTS, default="drop_datagram")
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds sent in the interval (default: the "
                         "traffic file's rounds_per_interval)")
    args = ap.parse_args(argv)
    c = harness.cell(args.workload)
    n = args.rounds or int(c["traffic"]["rounds_per_interval"])
    print(json.dumps(run(c, args.seed, args.fault, n)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
