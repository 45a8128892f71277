"""Each cell's body at a tiny scale and a 2 s interval on the CPU,
through the Python entry; the result line's shape; ``run.py`` without
a TPU; and the timed path broken underneath, which has to come out as
not correct."""

import os
import subprocess
import sys

import pytest

from bench_util import ROOT, TINY, run_py, topology

from benchmark import harness  # noqa: E402

RUN = os.path.join(ROOT, "benchmark", "run.py")


def _body(name, trace=False, seed=3):
    c = harness.cell(name)
    return c, harness.run_cell(c, seed=seed, seconds=4.0, trace=trace,
                               scale=TINY[name])


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_body_is_correct_at_tiny_scale(name):
    c, res = _body(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert all(v <= lim for v, lim in res["checks"].values())
    line = run_py().result_line(c, res, trace=False)
    assert list(line)[:5] == ["correct", "attempted", "failed",
                              "metrics", "device"]
    assert list(line)[-1] == "checks"
    want = {m["name"] for m in c["end_to_end"]}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    run = res["run"]
    # every tick has its lag on every server, and the cell's lag is
    # the mean over the ticks of the server its topology names
    assert line["metrics"]["setup_s"]["value"] == run["setup_s"]
    assert len(run["ticks"]) == 2
    assert all(len(lags) == 2 for lags in run["lags"].values())
    assert line["metrics"]["flush_lag_ms"]["value"] == pytest.approx(
        1e3 * sum(run["lags"][run["lag_of"]]) / 2)
    assert run["lines_received"] > 0


def test_traced_run_reports_layer_metrics_and_same_attempted_shape():
    """A traced run reports the per-layer metrics whose readers find
    something to read; on the CPU the device's have nothing."""
    c, res = _body("local-wide-paced", trace=True)
    assert res["correct"], res["checks"]
    line = run_py().result_line(c, res, trace=True)
    got = set(line["metrics"])
    listed = {m["name"] for m in c["per_layer"]}
    assert got <= listed
    assert {"sender_late_ms", "forward_ms", "flush_readout_ms",
            "host_emit_ms", "flush_lag_max_ms"} <= got
    # no device plane on the CPU: no device metric, never a 0
    assert "device_idle_pct" not in got
    assert "busy_s" not in line["device"]


def test_the_sink_keeps_columns_and_the_lag_ends_at_delivery():
    """The benchmark's sink takes the frame as a production sink does
    (no legacy list built on the way in, none kept alive after), and a
    tick's lag ends when the sink holds the flush and the global holds
    the forward: before the cycle's own end, which only the program's
    bookkeeping sees."""
    import numpy as np
    from veneur_tpu.core.frame import TYPE_COUNTER, MetricFrame
    from veneur_tpu.core.table import RowMeta
    sink = topology().make_sink()
    frame = MetricFrame(ts=1, common_tags=("c:1",))
    metas = [RowMeta("bench.count.0", ("a:1",), "", "counter"),
             RowMeta("bench.count.1", (), "", "counter")]
    frame.add_block(metas, np.array([1, 0]), np.array([5.0, 7.0]),
                    type_code=TYPE_COUNTER)
    frame.add_block(metas, np.array([0]), np.array([9.0]), ".max")
    sink.flush_frame(frame)
    assert frame._materialized is None
    kept = sink.batches[0][1]
    assert list(kept.values()) == [
        (m.name, m.tags, m.value) for m in frame.materialize()] == [
        ("bench.count.1", ("c:1",), 5.0),
        ("bench.count.0", ("a:1", "c:1"), 7.0),
        ("bench.count.0.max", ("a:1", "c:1"), 9.0)]
    _, res = _body("local-wide-paced", seed=5)
    run = res["run"]
    walls = [r["duration_ns"] / 1e9 for r in run["rings"]["local"]
             if r["start_unix"] <= run["t_end"]]
    assert len(run["lags"]["local"]) == 2
    for lag, wall, r in zip(run["lags"]["local"], walls,
                            run["rings"]["local"]):
        assert r["forward_rows"] > 0
        assert 0 < lag < wall + 0.25   # the swap starts near the tick


def test_run_py_fails_without_a_tpu(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VENEUR_")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run(
        [sys.executable, RUN, "--workload", "local-wide-paced",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


# ----------------------------------------------------------------------
# the timed path broken underneath: `correct` has to come out false

def _unchanged_step(monkeypatch):
    """The fused apply returns its state as it got it."""
    from veneur_tpu.ops import superbatch
    monkeypatch.setattr(
        superbatch, "step",
        lambda spec, c, g, m, w, st, regs, buf: (c, g, m, w, st, regs))


def _half_batch(monkeypatch):
    """The reader leaves out every second line of a batch (and what
    it drained beside it), and still counts the batch as received."""
    from veneur_tpu.core.server import Server
    real = Server.handle_packet_batch

    def half(self, batch, parser, drained=None, drained_pkts=0, **kw):
        batch = [b"\n".join(dg.split(b"\n")[::2]) for dg in batch]
        return real(self, batch, parser, drained=None, drained_pkts=0,
                    **kw)
    monkeypatch.setattr(Server, "handle_packet_batch", half)


def _altered_answer(monkeypatch):
    """One counter's value is altered where the flush produces it:
    in the column the frame is handed."""
    from veneur_tpu.core import frame
    real = frame.MetricFrame.add_block

    def altered(self, metas, rows, values, suffix="", *a, **kw):
        values = list(values)
        for j, r in enumerate(rows):
            if not suffix and metas[int(r)].name.startswith(
                    "bench.count."):
                values[j] += 1
                break
        return real(self, metas, rows, values, suffix, *a, **kw)
    monkeypatch.setattr(frame.MetricFrame, "add_block", altered)


def _slow_reader(monkeypatch):
    """The reader stalls on every batch, so the sender is held back
    and the load the cell states is not offered."""
    import time
    from veneur_tpu.core.server import Server
    real = Server.handle_packet_batch

    def slow(self, batch, parser, **kw):
        time.sleep(0.1)
        return real(self, batch, parser, **kw)
    monkeypatch.setattr(Server, "handle_packet_batch", slow)


@pytest.mark.parametrize("fault,number", [
    (_unchanged_step, "sums_off"),
    (_half_batch, "lines_unaccounted"),
    (_altered_answer, "sums_off"),
    (_slow_reader, "sender_blocked_pct"),
], ids=["state-unchanged", "half-batch", "answer-altered",
        "load-withheld"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, number):
    fault(monkeypatch)
    _, res = _body("local-wide-paced", seed=4)
    assert not res["correct"]
    value, limit = res["checks"][number]
    assert value > limit
    assert res["failed"] > 0
