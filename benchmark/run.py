#!/usr/bin/env python3
"""``python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.

Fails without a TPU, or with another number of chips than the cell
asks for: there is no CPU fallback.  The last line of standard output
is the result; earlier lines, on standard error, are one JSON object
each, the numbers compared beside their limits last.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def result_line(c: dict, res: dict, trace: bool) -> dict:
    """The contract's last line.  An untraced run reports the cell's
    end-to-end metrics, a traced run its per-layer metrics."""
    from benchmark import harness
    run = res["run"]
    metrics = {}
    if not trace:
        values = {"flush_lag_ms": _mean_ms(run["lags"][run["lag_of"]]),
                  "setup_s": run["setup_s"]}
        for m in c["end_to_end"]:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        for m in c["per_layer"]:
            reader = harness.load_module("layer_metrics", m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value,
                                      "unit": m["unit"]}
    out = {"correct": res["correct"], "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics,
           "device": dict(res["device"])}
    t = run.get("trace")
    if trace and t:
        out["device"]["busy_s"] = t["busy_s"]
        out["device"]["window_s"] = t["window_s"]
        out["breakdown"] = {"device_ops": t["device_ops"],
                            "idle_gaps": t["idle_gaps"]}
    out["checks"] = res["checks"]
    return out


def _mean_ms(lags: list[float]):
    return 1e3 * sum(lags) / len(lags) if lags else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness, reference
    try:
        c = harness.cell(args.workload)
    except (reference.Failed, OSError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    c["t_start"] = T_START

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: no TPU: JAX gives {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < c["chips"]:
        print(f"benchmark: {args.workload} needs {c['chips']} chips, "
              f"JAX gives {len(devs)}", file=sys.stderr)
        return 2
    from veneur_tpu import native
    from veneur_tpu.ops import tdigest
    from veneur_tpu.utils import compile_cache
    if tdigest.resolved_merge_mode() != "pallas":
        print("benchmark: merge mode is "
              f"{tdigest.resolved_merge_mode()!r}, not the Pallas "
              "kernel", file=sys.stderr)
        return 2
    if native.load() is None:
        print("benchmark: the native parser did not build (no g++?)",
              file=sys.stderr)
        return 2
    # JAX_COMPILATION_CACHE_DIR where the environment sets it, else
    # <checkout>/.jax_cache: a fixed path inside the checkout
    warm = compile_cache.enable()
    harness.log(phase="start", workload=args.workload, seed=args.seed,
                seconds=args.seconds, trace=args.trace,
                cache_dir=jax.config.jax_compilation_cache_dir,
                cache_warm=warm, jax=jax.__version__,
                devices=[str(d) for d in devs])
    try:
        res = harness.run_cell(c, args.seed, args.seconds,
                               bool(args.trace))
    except reference.Failed as e:
        print(f"benchmark: FAILED: {e}", file=sys.stderr)
        return 1
    out = result_line(c, res, bool(args.trace))
    print("checks: " + json.dumps(out["checks"]), file=sys.stderr,
          flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
