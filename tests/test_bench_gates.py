"""Bench-infrastructure honesty: platform stamps and device A/B gates.

VERDICT r3 weak #1 — every bench/probe artifact must record the
backend it ran on, and the prepared device levers (tail refinement
capacity, f16 plane shipping, merge kernel) must be switchable via
env so a chip run can A/B them on real hardware.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from veneur_tpu.utils import devprobe

_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
            VENEUR_PROBE_PLATFORM="cpu")


def test_probe_info_reports_platform(monkeypatch):
    # the probe subprocess escapes conftest's jax.config override, so
    # pin it to CPU the way bench.py's VENEUR_BENCH_PLATFORM path does
    monkeypatch.setenv("VENEUR_PROBE_PLATFORM", "cpu")
    err, info = devprobe.probe_device_info(120)
    assert err is None, err
    assert info["platform"] == "cpu"
    assert info["jax_version"]
    assert info["num_devices"] >= 1
    assert "device_kind" in info


def test_probe_device_compat_wrapper(monkeypatch):
    monkeypatch.setenv("VENEUR_PROBE_PLATFORM", "cpu")
    assert devprobe.probe_device(120) is None


def _capacity_with(env_extra: dict) -> int:
    out = subprocess.run(
        [sys.executable, "-c",
         "from veneur_tpu.ops import tdigest;"
         "print(tdigest.DEFAULT_CAPACITY)"],
        env={**_ENV, **env_extra}, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-500:]
    return int(out.stdout.strip())


def test_tail_refine_gate_shrinks_capacity():
    # default: asin body + tail refinement; gated: plain-asin 312
    assert _capacity_with({}) == 616
    assert _capacity_with({"VENEUR_TPU_TAIL_REFINE": "0"}) == 312


def test_tail_refine_off_still_accurate_at_p99():
    """The 312-slot plain-asin scale must stay a valid digest (the
    A/B compares its throughput, not its correctness)."""
    code = """
import numpy as np, jax.numpy as jnp
from veneur_tpu.ops import tdigest
assert tdigest.DEFAULT_CAPACITY == 312
rng = np.random.default_rng(7)
vals = rng.gamma(2.0, 30.0, 200_000).astype(np.float32)
m, w = tdigest.empty_state(1)
chunk = 20_000
for i in range(0, len(vals), chunk):
    v = jnp.asarray(vals[i:i+chunk])
    rows = jnp.zeros(len(v), jnp.int32)
    m, w = tdigest.add_samples_unit(m, w, rows, v, slots=chunk)
qs = jnp.asarray(np.asarray([0.5, 0.99], np.float32))
mins = jnp.asarray([float(vals.min())]); maxs = jnp.asarray([float(vals.max())])
got = np.asarray(tdigest.quantile(m, w, qs, mins, maxs))[0]
exact = np.quantile(vals, [0.5, 0.99])
rel = np.abs(got - exact) / np.abs(exact)
assert rel.max() < 0.02, (got, exact, rel)
print("OK", rel.max())
"""
    out = subprocess.run(
        [sys.executable, "-c", code],
        env={**_ENV, "VENEUR_TPU_TAIL_REFINE": "0"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith("OK")


def test_f16_gate_forces_f32_planes():
    """VENEUR_TPU_F16_PLANE=0 must keep every shipped plane f32 while
    producing the same flush stats."""
    code = """
import numpy as np
from veneur_tpu.core import table as table_mod
from veneur_tpu.core.table import MetricTable, TableConfig
assert table_mod._F16_PLANE is %s
t = MetricTable(TableConfig(histo_rows=64, histo_slots=512))
rows = np.repeat(np.arange(64, dtype=np.int32), 200)
vals = np.abs(np.random.default_rng(3).normal(50.0, 10.0,
              len(rows))).astype(np.float32) + 1.0
t._histo_stage.append(rows, vals, np.ones(len(rows), np.float32))
t.device_step()
snap = t.swap()
s = np.asarray(snap.histo_stats)
print("SUM", float(s[:64, 0].sum()))
"""
    outs = {}
    for flag, expect in (("1", "True"), ("0", "False")):
        out = subprocess.run(
            [sys.executable, "-c", code % expect],
            env={**_ENV, "VENEUR_TPU_F16_PLANE": flag},
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        outs[flag] = float(out.stdout.strip().split()[-1])
    # count column is exact in both modes
    assert outs["1"] == outs["0"] == float(len(np.arange(64)) * 200)


def test_accuracy_soak_quick_smoke():
    """bench.py --accuracy (VERDICT r3 item 3) runs device-free and
    emits the full error distribution; quick scale here keeps the
    suite fast — the committed full-scale artifact
    (bench_results/accuracy_soak.json) carries the asserted budgets."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--accuracy", "--quick"],
        env={**_ENV, "VENEUR_BENCH_PLATFORM": "cpu"},
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["mode"] == "accuracy" and d["platform"] == "cpu"
    t = d["timers"]
    assert t["p99_err_max"] <= 0.01, t
    assert d["sets"]["hll_err_mean"] <= 0.02


def test_full_scale_accuracy_artifact_committed():
    """The full-scale soak's artifact must exist, be platform-stamped,
    and record asserted budgets (the 'committed results file' half of
    VERDICT item 3)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "accuracy_soak.json")
    with open(path) as f:
        d = json.load(f)
    assert d["budgets_asserted"] is True
    assert d["quick"] is False
    assert d["timers"]["samples"] == 10_000_000
    assert d["timers"]["p99_err_max"] <= 0.01
    assert d["sets"]["uniques_per_series"] == 1000
    assert d["sets"]["hll_err_mean"] <= 0.01
    # distribution sweep (SURVEY §4d harness model): five
    # distributions incl. two heavy tails, all at p50..p999
    dists = d["distributions"]
    assert set(dists) == {"uniform", "normal", "exponential",
                          "pareto_a3", "lognormal_s2"}
    for dname, derr in dists.items():
        budget = 0.02 if dname == "lognormal_s2" else 0.01
        for k, v in derr.items():
            if isinstance(v, dict):
                continue  # go_serial / beats_go sub-structures
            if k.endswith("_err_max"):
                assert v <= budget, (dname, k, v)
            else:
                assert v <= 0.005, (dname, k, v)
        # the BASELINE claim is RELATIVE to the Go serial digest:
        # the committed artifact must carry the side-by-side and win
        # the tail quantiles on every distribution
        for lbl in ("p90", "p99", "p999"):
            assert derr["beats_go_max"][lbl], (dname, lbl)
            assert derr["go_serial"][f"{lbl}_err_max"] >= 0.0
    assert "platform" in d and "gates" in d


def test_sockets_bench_artifact_committed():
    """bench.py --sockets captures the real-socket ingest surface
    behind the reference's 60k packets/s production headline
    (README.md:310-312); the committed artifact must beat it and be
    platform-stamped."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "sockets_bench.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "sockets" and d["quick"] is False
    single = d["single_line"]
    assert single["packets_per_sec"] > 60_000  # the reference bar
    assert single["received_pct"] > 80.0
    assert d["batch_25"]["metrics_per_sec"] > 1_000_000
    assert "platform" in d and "gates" in d
    # ingest provenance stamps (ISSUE 17): a socket number divorced
    # from the kernel, rcvbuf ceiling and drain backend that produced
    # it is unreviewable
    assert d["kernel_release"], d.get("kernel_release")
    assert d["effective_rcvbuf"] >= 1 << 20
    assert d["ingest_backend"] in ("uring", "recvmmsg", "python")
    assert d["platform_pin"], "artifact captured without platform pin"


def test_sockets_bench_backend_sweep_gated():
    """The uring-over-recvmmsg gate, platform-relative: on a host
    whose probe grants io_uring the sweep must exist, uring must not
    regress delivery, and where the loadgen and the reader do NOT
    timeshare one core the single-line ratio must clear 1.5x.  On a
    single-core host both backends receive ~everything the sender can
    offer, so pkts/s measures the sender's CPU share and the ratio
    gate is meaningless — the no-regression floor still applies."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "sockets_bench.json")
    with open(path) as f:
        d = json.load(f)
    sweep = d.get("backend_sweep")
    assert sweep, "artifact predates the backend sweep"
    if sweep.get("uring", {}).get("skipped"):
        pytest.skip("io_uring refused on the capture host: "
                    + str(sweep["uring"].get("reason")))
    u, r = sweep["uring"]["single_line"], sweep["recvmmsg"]["single_line"]
    assert u["backend"] == "uring" and r["backend"] == "recvmmsg"
    speedup = d["uring_speedup_single_line"]
    assert speedup == pytest.approx(
        u["packets_per_sec"] / r["packets_per_sec"], rel=0.01)
    # no-regression floor: uring never loses to recvmmsg on rate or
    # on delivery, on any host that grants it
    assert speedup >= 0.9, speedup
    assert u["received_pct"] >= r["received_pct"] - 2.0, (
        u["received_pct"], r["received_pct"])
    if d.get("cpu_count", 1) < 2:
        pytest.skip(
            "1-core capture host: blast loadgen and reader timeshare "
            "the core, both backends deliver ~100%, and the ratio "
            f"measures sender CPU share (measured {speedup}x)")
    assert speedup >= 1.5, speedup


def test_tls_bench_artifact_committed():
    """bench.py --tls captures TLS connection-establishment rates vs
    the reference's published ~700/s ECDH / ~110/s RSA (1 CPU,
    localhost; reference README.md:369)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "tls_bench.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "tls" and d["quick"] is False
    # RSA beats the published bar outright; ECDSA within 2x on a
    # shared single vCPU vs unspecified 2017 hardware (setup note in
    # the artifact)
    assert d["rsa_2048"]["connections_per_sec"] > 110.0
    assert d["ecdsa_p256"]["connections_per_sec"] > 350.0
    assert "setup" in d and "platform" in d


def test_bench_error_line_carries_platform_fields():
    """The dead-link JSON line must still say what it failed to
    reach (bench.py main error path)."""
    from veneur_tpu.utils import devprobe as dp
    err, info = dp.probe_device_info(0.001)
    assert err is not None and info == {}


def test_chain_bench_artifact_committed():
    """bench.py --chain: full local->proxy->global wire chain.  The
    committed artifact must show complete delivery and a per-local
    forward latency far inside the 10s interval (the shape behind
    config 4's 2,048 items/s aggregate requirement; the global's
    intake capacity itself is bench config 4)."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "chain_bench.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "chain" and d["quick"] is False
    assert d["timed_out"] is False
    assert d["items_forwarded"] == d["items_expected"]
    assert d["local_interval_headroom_x"] >= 5.0
    assert "platform" in d and "gates" in d


def test_proxy_chain_artifact_committed():
    """bench.py --proxy-chain: the proxy hop at 100k+ series.  The
    committed artifact must show the columnar route path >=5x the
    per-item oracle (ISSUE acceptance bar — platform-relative: both
    paths ran on the same host in the same process), a balanced
    routing ledger (routed == enqueued + busy_dropped every
    interval), and zero fail-open fallbacks during the capture."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "proxy_chain.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "proxy_chain" and d["quick"] is False
    assert d["series"] >= 100_000
    assert d["speedup_vs_oracle"] >= 5.0
    assert d["routed_items_per_sec"] > d["oracle_items_per_sec"]
    led = d["ledger"]
    assert led["imbalanced"] == 0
    assert led["owed_total"] == 0
    assert led["balanced"] == led["intervals"]
    assert led["fallbacks_total"] == 0
    # every routed item settled at a destination worker
    assert (led["routed_total"] ==
            led["enqueued_total"] + led["busy_dropped_total"])
    assert {"decode_s", "keyhash_s", "assign_s",
            "group_encode_s"} <= set(d["phases"])
    assert "platform" in d and "gates" in d


def test_global_merge_artifact_committed():
    """bench.py --global-merge: config 4 (device-resident global
    import) as a committed artifact.  The headline is the median of
    WARM intervals, and the per-wire claims are a same-host A/B
    against the per-metric protobuf oracle the native columnar decode
    replaced — platform-relative, so the gate holds on the CPU
    capture too; the absolute BENCH_r05 2x bar (>=46k items/s)
    applies when the artifact was captured on the device."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "global_merge_import.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "global_merge_import" and d["quick"] is False
    assert d["headline_policy"] == "median_warm_interval"
    assert d["items_per_sec"] > 0
    assert d["locals"] == 64
    # native columnar decode + wire-plan cache vs protobuf per-metric
    # oracle, same process, same wires: the ISSUE's 2x floor with
    # margin
    assert d["apply_speedup_vs_oracle"] >= 2.0
    ph = d["phases"]
    assert ph["decode_only_per_wire"] <= 0.002
    # host decode+apply per forwarded wire (256 digests + 64 sets)
    assert d["apply_decode_host_per_wire"] <= 0.005
    assert "platform" in d and "gates" in d
    if d["platform"] == "tpu":
        assert d["items_per_sec"] >= 46_000
        assert d["apply_decode_host_per_wire"] <= 0.002


def test_cluster_shard_artifact_committed():
    """bench.py --cluster: the sharded global tier's N-local x
    M-global soak (ISSUE 10 headline).  The committed artifact must
    show exact cluster-wide sample conservation on the real-server
    e2e half, >=100k distinct series on the scaling half, M-scaling
    over the modeled per-shard service floor (>=1.6x at M=2, >=2.5x
    at M=4 — the keyspace split must actually parallelize the global
    tier), measured per-item python work far under that floor (the
    topology, not the host, was the variable), and every tier's
    ledger balanced."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "cluster_shard.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "cluster_shard" and d["quick"] is False

    e = d["e2e"]
    assert e["locals"] >= 4 and e["globals"] >= 2
    assert e["conservation_exact"] is True
    assert e["items_received"] == e["items_expected"]
    assert e["ledgers_balanced"] is True
    assert e["split_equals_global_intake"] is True
    assert e["both_dests_hit"] is True
    assert e["zero_fallbacks"] is True

    s = d["scaling"]
    assert s["series_total"] >= 100_000
    assert s["n_locals"] >= 4
    for m in ("m1", "m2", "m4"):
        c = s[m]
        assert c["conservation_exact"] is True, m
        assert c["wire_errors"] == 0 and c["busy_dropped"] == 0, m
        assert c["route_fallbacks"] == 0, m
        assert c["local_ledgers_balanced"], m
        assert c["global_ledgers_balanced"], m
        # the modeled service floor must dominate the python work, or
        # the M-ratio measures the host instead of the topology
        assert (c["measured_work_us_per_item"]
                < s["service_us_per_item"] / 10), m
    assert s["scaling_m2_vs_m1"] >= 1.6
    assert s["scaling_m4_vs_m1"] >= 2.5
    for gate, ok in d["cluster_gates"].items():
        assert ok is True, gate
    assert d["cluster_items_per_sec"] > 0
    assert d["global_shards"] == 4
    assert "platform" in d and "gates" in d


def test_chaos_soak_artifact_committed():
    """bench.py --chaos: the fault-injection soak (ISSUE 11).  The
    committed artifact must show all four fault kinds injected (wire
    drop/delay, stalled destination, discovery flap, shard kill), the
    attribution identity holding exactly — every routed item landed
    on a shard or is attributed to a NAMED drop counter, zero silent
    loss — every tier's ledger balanced, the live reshard and the
    rolling-restart drain conserving their intervals, and the
    cross-process trace tree stitched through the fault."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "chaos_soak.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "chaos_soak" and d["quick"] is False
    assert d["chaos_pass"] is True
    for gate, ok in d["chaos_gates"].items():
        assert ok is True, gate

    ms = d["model_soak"]
    assert {"wire_drop_retry", "wire_drop_fatal", "wire_delay",
            "dest_stall", "discovery_flap", "shard_kill",
            "shard_kill_reshard"} <= set(ms["faults_injected"])
    assert ms["unattributed_lost"] == 0
    # the injected faults must have actually BITTEN: attributed wire
    # errors from the fatal drop + dead shard, and >=2 credited
    # reshard records covering >=3 swap events
    assert ms["items_error_attributed"] > 0
    assert ms["reshards"] >= 2 and ms["reshard_events"] >= 3
    assert ms["route_fallbacks"] == 0
    assert ms["ledgers_balanced"] is True
    # attribution identity, re-derived from the raw counts
    assert (ms["items_routed"] + ms["overdelivered"] ==
            ms["items_accepted"] + ms["items_error_attributed"] +
            ms["items_busy_dropped"])

    e = d["e2e"]
    assert e["trace_stitched"] is True and e["import_spans"] >= 1
    assert e["reshard_conserved"] is True
    assert e["reshard_credited"] is True
    assert e["drain_conserved"] is True
    assert e["drain_wires_received"] >= 1
    assert e["drain_flushes"] >= 1
    assert e["ledgers_balanced"] is True

    # the ISSUE 12 recovery leg: kill -> spool -> restart -> replay,
    # ZERO loss (every routed item landed, not merely attributed)
    rcv = d["recovery"]
    assert rcv["total_lost"] == 0
    assert rcv["error_items"] == 0 and rcv["busy_dropped"] == 0
    assert rcv["breaker_opens"] >= 1
    assert rcv["spool"]["spooled_items"] > 0
    assert rcv["spooled_route_items"] > 0
    assert rcv["replay_wires_received"] >= 1
    assert rcv["spool"]["queued_items"] == 0
    assert rcv["spool"]["expired_items"] == 0
    assert rcv["spool"]["replayed_items"] == \
        rcv["spool"]["spooled_items"]
    assert rcv["spool_balance_owed"] == 0
    assert rcv["ledger"]["imbalanced"] == 0
    assert rcv["spool_ledger"]["imbalanced"] == 0

    # the ISSUE 15 crash leg: SIGKILL a live local mid-soak under
    # UDP ingest, restart with fd adoption + checkpoint recovery.
    # Loss is bounded by ONE checkpoint interval of offered ingest
    # (the named window between the last surviving segment and the
    # kill), never negative (recovery deduped, no double delivery),
    # and the kernel boundary drops nothing across the restart.
    cr = d["crash"]
    assert cr["kernel_drops"] == 0
    assert cr["first_child"]["fds_adopted"] >= 1
    assert cr["second_child"]["fds_adopted"] >= 1
    assert cr["second_child"]["incarnation"] == \
        cr["first_child"]["incarnation"] + 1
    assert 0 <= cr["unattributed_lost"] <= cr["loss_bound_items"]
    assert cr["recovery_wires_received"] >= 1
    assert cr["recovered_total"] > 0
    assert cr["global_ledger"]["imbalanced"] == 0
    assert cr["global_ledger"]["recovered_owed_total"] == 0

    # the ISSUE 15 scale-out leg: an incumbent global hands the new
    # member's keyspace arcs over the flagged import wire; the
    # CLUSTER conserves mass exactly, the receiver credits the
    # arrival, both ledgers seal balanced
    so = d["scale_out"]
    assert so["mass_conserved"] is True
    assert so["double_emitted_series"] == 0
    assert so["counter_mass"] == so["counter_mass_expected"]
    assert so["handoff"]["errors"] == 0
    assert so["handoff"]["dropped_items"] == 0
    assert so["handoff_wires_received"] >= 1
    assert so["reshard_received_items"] == so["handoff"]["items"] > 0
    assert so["sender_ledger_balanced"] is True
    assert so["receiver_ledger_balanced"] is True
    assert "platform" in d and "gates" in d


@pytest.mark.slow
def test_chaos_soak_quick_rerun():
    """Re-run the chaos soak end to end (quick scale) — the committed
    artifact's gates must be reproducible, not a lucky capture."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--chaos", "--quick"],
        env={**_ENV, "VENEUR_BENCH_PLATFORM": "cpu"},
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["chaos_summary"] is True
    assert d["chaos_pass"] is True, d["gates"]


def _bench_module():
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench.py")
    spec = importlib.util.spec_from_file_location("_bench_mod", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_bench_mod"] = mod
    spec.loader.exec_module(mod)
    return mod


def test_summary_line_compact_and_parseable():
    """The post-blob summary line is the driver's machine-readable
    record when its bounded tail capture truncates the full artifact
    (BENCH_r05 lost its record exactly that way): it must stay under
    1KB with every config populated — including long error strings —
    and parse as standalone JSON."""
    m = _bench_module()
    configs = {
        "0_counters_1k_names": {"samples_per_sec": 19.4e6,
                                "platform": "cpu"},
        "1_cardinality_100k": {"samples_per_sec": 10.3e6},
        "2_timers_10k_series": {"error": "config timed out " * 40},
        "3_sets_1m_uniques": {"skipped": True, "reason": "link down"},
        "4_global_merge": {"items_per_sec": 46600.0},
    }
    out = m._assemble(configs, 0.0, {"platform": "cpu"})
    line = m._summary_line(out)
    assert len(line) < 1024
    d = json.loads(line)
    assert d["bench_summary"] is True
    assert d["configs"]["0_counters_1k_names"]["rate"] == 19.4e6
    assert d["configs"]["4_global_merge"]["rate"] == 46600.0
    assert len(d["configs"]["2_timers_10k_series"]["error"]) <= 80
    assert d["configs"]["3_sets_1m_uniques"]["skipped"] is True
    # the normal line never grows the cluster fields...
    assert "cluster_items_per_sec" not in d
    # ...and a --cluster artifact's line carries exactly its verdict
    cline = m._summary_line({"cluster_items_per_sec": 23040.2,
                             "global_shards": 4, "platform": "cpu"})
    assert len(cline) < 1024
    cd = json.loads(cline)
    assert cd["cluster_items_per_sec"] == 23040.2
    assert cd["global_shards"] == 4


def test_median_pass_result_headline_is_median():
    """Multi-pass headline: the published rate must be the median of
    the per-pass rates (one bad host/link window lands on one pass),
    with totals summed and every pass's raw intervals retained."""
    m = _bench_module()

    def mk(rate, total=700):
        return {"samples": total, "seconds": total / rate,
                "samples_per_sec": rate,
                "mean_samples_per_sec": rate,
                "warm_mean_samples_per_sec": rate,
                "interval_seconds": [0.1] * 7, "intervals": 7,
                "cold_interval_seconds": 0.5}

    res = m._median_pass_result([mk(100.0), mk(10.0), mk(90.0)])
    assert res["samples_per_sec"] == 90.0
    assert sorted(res["pass_rates"]) == [10.0, 90.0, 100.0]
    assert res["samples"] == 2100
    assert len(res["passes"]) == 3
    assert all(len(p["interval_seconds"]) == 7 for p in res["passes"])
    # degenerate single pass (budget-tripped sweep) passes through
    one = m._median_pass_result([mk(50.0)])
    assert one["samples_per_sec"] == 50.0 and one["pass_rates"] == [50.0]


def _ledger_summaries(block: dict) -> list[dict]:
    """A soak artifact stamps one Ledger.summary(); chain stamps one
    per tier ({"local": ..., "global": ...})."""
    if "intervals" in block:
        return [block]
    return list(block.values())


def test_soak_chain_artifacts_ledger_balanced():
    """Soak/chain artifacts must carry a balanced conservation-ledger
    block: a perf capture that lost samples is not a valid capture.
    Pre-ledger captures (no block yet) pass until re-captured — the
    stamping itself is pinned by test_bench_source_stamps_ledger."""
    import pathlib
    results = pathlib.Path(__file__).parent.parent / "bench_results"
    for stem in ("soak_bench", "chain_bench"):
        d = json.loads((results / f"{stem}.json").read_text())
        block = d.get("ledger")
        if block is None:
            continue
        for s in _ledger_summaries(block):
            assert s["imbalanced"] == 0, (stem, s)
            assert s["owed_total"] == 0, (stem, s)
            assert s["balanced"] == s["intervals"], (stem, s)


def test_bench_source_stamps_ledger():
    """bench.py must keep stamping ledger summaries into BOTH
    artifacts (the conditional gate above can't notice the block
    silently disappearing from future captures)."""
    import pathlib
    src = (pathlib.Path(__file__).parent.parent / "bench.py").read_text()
    assert '"ledger": srv.ledger.summary()' in src
    assert '"local": local.ledger.summary()' in src
    assert '"global": g.ledger.summary()' in src


def test_soak_artifact_committed_and_stable():
    """The committed 20-minute soak artifact must carry passing
    stability verdicts (RSS slope, thread flatness, flush cadence) —
    the long-run counterpart of the throughput gates."""
    import pathlib
    path = pathlib.Path(__file__).parent.parent / "bench_results" / \
        "soak_bench.json"
    d = json.loads(path.read_text())
    assert d["duration_seconds"] >= 300
    assert d["ok"] is True, d.get("verdicts")
    v = d["verdicts"]
    assert v["py_heap_stable"] and v["threads_stable"] and \
        v["flush_cadence_ok"] and v["rss_stable"]
    if v.get("rss_stable_raw") is False:
        # raw process RSS grew: legal ONLY with the python heap flat
        # and the in-artifact pure-dispatch control demonstrating the
        # platform client leaks without any framework code involved
        assert d["control_pure_dispatch_leak_kb"] >= 0.5
        assert "rss_attribution" in d
    assert d["platform"]  # stamped


def test_overload_soak_artifact_committed():
    """bench.py --overload: the overload soak (ISSUE 14).  >=2x the
    admitted load offered through Zipf-skewed tenants, then a
    cardinality burst under engaged pressure, then an injected slow
    flush — and the artifact passes on ACCOUNTING, not throughput:
    zero unattributed loss, every shed sample named tenant+reason,
    counters conserved EXACTLY, and each degradation mechanism
    (freeze, class shed, width ladder, coalesce) observed firing."""
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "overload_soak.json")
    with open(path) as f:
        d = json.load(f)
    assert d["mode"] == "overload_soak" and d["quick"] is False
    assert d["overload_pass"] is True
    for gate, ok in d["overload_gates"].items():
        assert ok is True, gate

    led = d["ledger"]
    assert d["unattributed_lost"] == 0
    assert led["imbalanced"] == 0
    assert led["shed_owed_total"] == 0
    # the attribution map re-sums to the shed arm exactly
    attributed = sum(n for reasons in led["shed_by"].values()
                     for n in reasons.values())
    assert attributed == led["shed_total"] > 0
    # genuinely overloaded: >=2x what admission let through
    assert d["phase_a"]["shed"] >= d["phase_a"]["admitted_noncounter"]
    # counters: never shed, conserved exactly through the flush
    assert d["flushed_counter_sum"] == d["offered_counters"]
    reasons = {r for by in led["shed_by"].values() for r in by}
    assert "tenant_budget" in reasons
    assert "series_freeze" in reasons
    assert any(r.startswith("pressure:") for r in reasons)
    # degradation mechanisms all observed
    assert d["phase_b"]["pressure"]["engaged"] is True
    assert d["phase_b"]["histo_width_now"] < \
        d["phase_b"]["histo_width_base"]
    assert d["phase_c"]["flush_overruns"] >= 1
    assert d["phase_c"]["coalesced_ticks"] >= 1
    assert led["coalesced_total"] >= 1
    assert "platform" in d and "gates" in d


@pytest.mark.slow
def test_overload_soak_quick_rerun():
    """Re-run the overload soak end to end (quick scale) — the
    committed artifact's gates must be reproducible, not a lucky
    capture."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--overload", "--quick"],
        env={**_ENV, "VENEUR_BENCH_PLATFORM": "cpu"},
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["overload_summary"] is True
    assert d["overload_pass"] is True, d["gates"]


def test_summary_line_overload_fields():
    """The --overload summary line carries exactly its verdict (and
    the normal line never grows the overload fields)."""
    m = _bench_module()
    oline = m._summary_line({
        "overload_pass": True,
        "ledger": {"shed_total": 44792},
        "unattributed_lost": 0,
        "platform": "cpu"})
    assert len(oline) < 1024
    od = json.loads(oline)
    assert od["overload_pass"] is True
    assert od["overload_shed_total"] == 44792
    assert od["overload_unattributed_lost"] == 0

    nline = m._summary_line({"platform": "cpu"})
    nd = json.loads(nline)
    assert "overload_pass" not in nd
    assert "overload_shed_total" not in nd


def _committed_artifact(name):
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", name)
    with open(path) as f:
        return json.load(f)


def test_chaos_soak_flight_recorder_coverage():
    """ISSUE 16: every injected fault class in the committed chaos
    artifact left a CRC-verified flight bundle naming its trigger —
    reshard (e2e kill), breaker_open + recovery_replay (outage ride),
    recovery_replay (crash checkpoint replay), handoff (scale-out) —
    and every bundle a real Server dumped carries the triggering
    interval's sealed ledger record and trace tree."""
    d = _committed_artifact("chaos_soak.json")
    expect = {"e2e": "reshard", "recovery": "breaker_open",
              "crash": "recovery_replay", "scale_out": "handoff"}
    for leg, trig in expect.items():
        f = d[leg]["flight"]
        assert f["by_trigger"].get(trig, 0) >= 1, (leg, trig)
        assert f["retained"] >= 1, leg
        assert f["crc_verified"] == f["retained"], leg
        assert f["errors_total"] == 0, leg
        assert d[leg]["signal_rows"] >= 2, leg
    # the outage ride fires BOTH its triggers: breaker trip on the
    # kill, recovery_replay when the spool drains through
    assert d["recovery"]["flight"]["by_trigger"].get(
        "recovery_replay", 0) >= 1
    # server-dumped bundles carry the incident context
    for leg in ("e2e", "crash", "scale_out"):
        f = d[leg]["flight"]
        assert f["with_ledger_record"] == f["retained"], leg
        assert f["with_trace"] >= 1, leg
    assert d["flight_bundles"] == sum(
        d[leg]["flight"]["bundles_total"] for leg in expect) > 0
    assert d["signal_rows"] == sum(
        d[leg]["signal_rows"] for leg in expect) > 0


def test_overload_soak_flight_recorder_coverage():
    """ISSUE 16: the committed overload artifact shows the flight
    recorder catching both injected fault classes — the pressure
    engage between phases A and B and the phase C flush overrun —
    with every retained bundle CRC-clean and context-bearing."""
    d = _committed_artifact("overload_soak.json")
    f = d["flight"]
    assert f["by_trigger"].get("pressure_change", 0) >= 1
    assert f["by_trigger"].get("flush_overrun", 0) >= 1
    assert f["retained"] >= 2
    assert f["crc_verified"] == f["retained"]
    assert f["with_ledger_record"] == f["retained"]
    assert f["errors_total"] == 0
    assert d["flight_bundles"] == f["bundles_total"] >= 2
    assert d["signal_rows"] >= 5


def test_summary_line_flight_fields():
    """The chaos/overload summary lines carry the signal-plane
    verdict; the normal bench line never grows the fields."""
    m = _bench_module()
    line = m._summary_line({"platform": "cpu",
                            "flight_bundles": 8,
                            "signal_rows": 26})
    assert len(line) < 1024
    d = json.loads(line)
    assert d["flight_bundles"] == 8
    assert d["signal_rows"] == 26
    nd = json.loads(m._summary_line({"platform": "cpu"}))
    assert "flight_bundles" not in nd
    assert "signal_rows" not in nd


# ----------------------------------------------------------------------
# collective forward plane-exchange (ISSUE 18)


def _collective_artifact() -> dict:
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "bench_results", "collective_forward.json")
    with open(path) as f:
        return json.load(f)


def test_collective_forward_artifact_committed():
    """bench.py --collective-forward: N-local x M-global REAL mesh
    processes racing the fixed-schema plane exchange against the
    production gRPC wire.  The committed artifact must show exact
    delivery on BOTH transports (a transport race that lost samples
    is not a capture), zero fallbacks, balanced global ledgers, the
    per-phase timing split, and the full ISSUE 18 provenance stamp."""
    d = _collective_artifact()
    assert d["mode"] == "collective_forward" and d["quick"] is False
    assert not d.get("skipped"), d.get("reason")
    assert not d.get("error"), d["error"]
    g = d["collective_gates"]
    assert g["wire_conserved"] and g["collective_conserved"], g
    assert g["zero_fallbacks"] and g["zero_bad_blocks"], g
    assert g["ledger_balanced"], g
    c = d["conservation"]
    assert c["wire_received"] == c["collective_received"] == \
        d["items_per_phase"]
    # both transports measured, with the phase split that attributes
    # where the cycle's time went
    assert d["wire_items_per_sec"] > 0
    assert d["collective_items_per_sec"] > 0
    ph = d["phase_seconds"]
    for k in ("wire_wall", "collective_wall", "serialize", "pack",
              "exchange", "fold"):
        assert ph[k] >= 0, k
    # provenance floor: every artifact names the host that produced
    # it (the satellite of ISSUE 18 — no more platform_pin: null)
    assert d["platform_pin"], "artifact captured without platform pin"
    assert d["kernel_release"]
    assert d["cpu_count"] >= 1
    assert d["gates"]["merge_resolved"] in ("pallas", "scatter")
    assert d["mesh_procs"] == d["n_locals"] + d["n_globals"] >= 2


def test_collective_forward_speedup_gated():
    """The collective-beats-wire gate, platform-relative like the
    sockets uring sweep: wherever each mesh process had its own core
    the one-collective-per-cycle exchange must out-run the
    per-destination gRPC wire.  With fewer cores than mesh processes
    every all_to_all rendezvous costs scheduler quanta (~165ms per
    exchange at 1 core on loopback REGARDLESS of payload — the probe
    that sized this leg measured identical latency at 1KB and 5.5MB),
    so the ratio measures the scheduler, not the transport, and the
    gate skips with the measured ratio named.  The conservation
    floors in the committed-artifact gate above always apply."""
    d = _collective_artifact()
    if d.get("skipped"):
        pytest.skip(str(d.get("reason")))
    speedup = d["collective_speedup_vs_wire"]
    assert speedup is not None and speedup > 0
    if d["cpu_count"] < d["mesh_procs"]:
        pytest.skip(
            f"{d['cpu_count']}-core capture host for "
            f"{d['mesh_procs']} mesh processes: the rendezvous "
            f"measures scheduler quanta, not the transport "
            f"(measured {speedup}x)")
    assert speedup > 1.0, speedup


def test_collective_forward_provenance_on_all_artifacts():
    """ISSUE 18 satellite: the provenance stamp (kernel release, cpu
    count, resolved gates) must ride EVERY committed bench artifact
    via _backend_info — recapturing any leg keeps it attributable."""
    m = _bench_module()
    info = m._backend_info()
    assert info["kernel_release"] == os.uname().release
    assert info["cpu_count"] == os.cpu_count()
    assert "merge_resolved" in info["gates"]
    # the main-leg assembly stamps them without importing jax
    out = m._assemble({}, 0.0, {"platform": "cpu"})
    assert out["kernel_release"] == os.uname().release
    assert out["cpu_count"] == os.cpu_count()
    # and the one-line record carries them unconditionally
    line = json.loads(m._summary_line(out))
    assert line["kernel_release"] == os.uname().release
    assert line["cpu_count"] == os.cpu_count()
    assert "platform_pin" in line and "device_kind" in line


@pytest.mark.slow
def test_collective_forward_quick_rerun():
    """Re-run the transport race end to end at quick scale (2 real
    mesh processes) — the committed artifact's conservation gates
    must be reproducible, not a lucky capture."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--collective-forward",
         "--quick"],
        env={**_ENV, "VENEUR_BENCH_PLATFORM": "cpu"},
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    blob = json.loads(out.stdout.strip().splitlines()[-2])
    if blob.get("skipped"):
        pytest.skip(str(blob.get("reason")))
    g = blob["collective_gates"]
    assert g["wire_conserved"] and g["collective_conserved"], g
    assert g["zero_fallbacks"] and g["ledger_balanced"], g
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["collective_items_per_sec"] > 0
    assert line["mesh_procs"] == 2


# ----------------------------------------------------------------------
# adaptive-precision tier soak (ISSUE 19)


def test_cardinality_soak_artifact_committed():
    """bench.py --cardinality: the adaptive-tier soak.  Zipf traffic
    at 52k series against pooled wide slots — the committed artifact
    must hold device_bytes_per_series >= 4x under the analytic
    all-wide baseline, FLAT across steady intervals, with the
    accuracy pins (promoted p99, compact p99, exact count/max, HLL
    estimates) intact, both movements fired and ledger-named, and
    zero unattributed loss."""
    d = _committed_artifact("cardinality_soak.json")
    assert d["mode"] == "cardinality_soak" and d["quick"] is False
    assert d["cardinality_pass"] is True
    for gate, ok in d["cardinality_gates"].items():
        assert ok is True, gate

    assert d["dbps_reduction_x"] >= 4.0
    assert (d["device_bytes_per_series"]
            < d["baseline_device_bytes_per_series"] / 4.0)
    # flat: every interval's pooled total within 10% of the smallest
    totals = [iv["total_bytes"] for iv in d["intervals"]]
    assert max(totals) <= 1.10 * min(totals)
    # both movements fired, attributed per class, refusals included
    mv = d["movements"]
    assert mv["histo"]["promotions"] > 0 and mv["set"][
        "promotions"] > 0
    assert d["demotions_total"] > 0
    assert d["promotions_total"] == sum(
        c["promotions"] for c in mv.values())
    # idle tail demoted the whole wide pool back to compact
    assert d["intervals"][-1]["histo_wide_rows"] == 0
    assert d["intervals"][-1]["set_wide_rows"] == 0
    # conservation: precision moved, mass never did
    assert d["unattributed_lost"] == 0
    assert d["ledger"]["imbalanced"] == 0
    # provenance travels on the artifact
    assert "platform" in d and "kernel_release" in d


@pytest.mark.slow
def test_cardinality_soak_quick_rerun():
    """Re-run the adaptive-tier soak end to end (quick scale) — the
    committed artifact's gates must be reproducible, not a lucky
    capture."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--cardinality", "--quick"],
        env={**_ENV, "VENEUR_BENCH_PLATFORM": "cpu"},
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["cardinality_summary"] is True
    assert d["cardinality_pass"] is True, d["gates"]
    assert d["dbps_reduction_x"] >= 4.0
    assert d["unattributed_lost"] == 0


# ----------------------------------------------------------------------
# superbatch fused apply (ISSUE 20)


def test_superbatch_artifact_committed():
    """bench.py --superbatch: the fused one-buffer apply A/B.  The
    committed CPU artifact must show the sets config >=1.3x warm
    samples/sec over superbatch-off with BIT-EQUAL estimates (the
    speedup cannot come from computing something else), the mixed
    four-class cycle collapsing 4 apply dispatches to 1, and the
    per-interval dispatch/H2D accounting that makes the collapse
    auditable.  The absolute >=10M samples/sec/chip line applies only
    to device captures."""
    d = _committed_artifact("superbatch_apply.json")
    assert d["mode"] == "superbatch" and d["quick"] is False
    # the tentpole speedup, with its honesty pin
    assert d["sets_speedup_warm"] >= 1.3, d["sets_speedup_warm"]
    assert d["sets_estimates_equal"] is True
    assert (d["sets_on"]["warm_mean_samples_per_sec"] >=
            1.3 * d["sets_off"]["warm_mean_samples_per_sec"])
    # dispatch collapse: the mixed cycle's 4 per-class applies fuse
    # into exactly one; the legacy arm must NOT regress (still its
    # 4 — a drop there means the oracle silently changed shape)
    assert d["mixed_on"]["apply_dispatches_per_cycle"] == 1.0
    assert d["mixed_off"]["apply_dispatches_per_cycle"] == 4.0
    # accounting fields travel with both arms (satellite: the
    # DeviceCostRegistry counters telemetry ships per interval)
    for arm in ("sets_off", "sets_on"):
        assert d[arm]["device_dispatches_per_interval"] >= 1.0, arm
        assert d[arm]["h2d_bytes_per_interval"] > 0, arm
        assert d[arm]["apply_dispatches_per_interval"] == 1.0, arm
    assert "platform" in d and "gates" in d
    if d["platform"] == "tpu":
        assert d["sets_on"]["warm_mean_samples_per_sec"] >= 10e6


@pytest.mark.slow
def test_superbatch_quick_rerun():
    """Re-run the fused-apply A/B end to end (quick scale) — the
    collapse and the estimate-equality gates must be reproducible.
    The 1.3x speedup is full-scale-only: at 1/10 the members the
    per-class scatter is too cheap for the fixed plane-transfer cost
    to win."""
    out = subprocess.run(
        [sys.executable, "bench.py", "--superbatch", "--quick"],
        env={**_ENV, "VENEUR_BENCH_PLATFORM": "cpu"},
        capture_output=True, text=True, timeout=560,
        cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    d = json.loads(out.stdout.strip().splitlines()[-1])
    assert d["sets_estimates_equal"] is True
    assert d["mixed_dispatches_on"] == 1.0
    assert d["mixed_dispatches_off"] == 4.0
    assert d["sets_speedup_warm"] > 0


def test_summary_line_superbatch_fields():
    """The --superbatch summary line carries exactly its verdict (and
    the normal line never grows the superbatch fields)."""
    m = _bench_module()
    sline = m._summary_line({
        "mode": "superbatch",
        "sets_speedup_warm": 1.87,
        "sets_estimates_equal": True,
        "sets_on": {"warm_mean_samples_per_sec": 4.0e6},
        "mixed_off": {"apply_dispatches_per_cycle": 4.0},
        "mixed_on": {"apply_dispatches_per_cycle": 1.0},
        "platform": "cpu"})
    assert len(sline) < 1024
    sd = json.loads(sline)
    assert sd["sets_speedup_warm"] == 1.87
    assert sd["sets_estimates_equal"] is True
    assert sd["mixed_dispatches_off"] == 4.0
    assert sd["mixed_dispatches_on"] == 1.0

    nd = json.loads(m._summary_line({"platform": "cpu"}))
    assert "sets_speedup_warm" not in nd
    assert "mixed_dispatches_on" not in nd


def test_summary_line_cardinality_fields():
    """The --cardinality summary line carries exactly its verdict
    (and the normal line never grows the cardinality fields)."""
    m = _bench_module()
    cline = m._summary_line({
        "cardinality_pass": True,
        "device_bytes_per_series": 1489.1,
        "dbps_reduction_x": 5.12,
        "promotions_total": 334,
        "demotions_total": 334,
        "platform": "cpu"})
    assert len(cline) < 1024
    cd = json.loads(cline)
    assert cd["cardinality_pass"] is True
    assert cd["dbps_reduction_x"] == 5.12
    assert cd["promotions_total"] == 334

    nline = m._summary_line({"platform": "cpu"})
    nd = json.loads(nline)
    assert "cardinality_pass" not in nd
    assert "dbps_reduction_x" not in nd
