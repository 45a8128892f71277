"""Mesh-sharded collective import fold: gate resolution and parity.

The collective fold (parallel.sharded.CollectiveWireFold) partitions a
cycle's wire stack over the mesh ``shard`` axis, folds per-device
partials, and unions them with one all_gather + single k-scale
re-cluster into the table rows.  The union's merge TOPOLOGY differs
from the serial scan, so dense inputs agree only statistically; in the
SPREAD regime — every centroid more than one k-width from its
neighbours and totals under capacity — the cluster pass combines
nothing, and any fold topology must produce the same bits.  That is
the regime the parity tests pin.  Conftest forces an 8-device host
platform, so auto-gating and the N-device fold run in-process; the
slow subprocess test covers other device counts (1 and 4).
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from veneur_tpu.core.table import (MetricTable, TableConfig,
                                   _collective_import_mode)
from veneur_tpu.ops import hll
from veneur_tpu.parallel import sharded


# ----------------------------------------------------------------------
# gate resolution


def test_gate_env_matrix(monkeypatch):
    cases = {"": "auto", "auto": "auto", "1": "on", "on": "on",
             "true": "on", "0": "off", "off": "off", "false": "off"}
    for raw, want in cases.items():
        monkeypatch.setenv("VENEUR_TPU_COLLECTIVE_IMPORT", raw)
        assert _collective_import_mode() == want, raw


def test_gate_defers_to_config_when_env_unset(monkeypatch):
    monkeypatch.delenv("VENEUR_TPU_COLLECTIVE_IMPORT", raising=False)
    assert _collective_import_mode("off") == "off"
    assert _collective_import_mode("on") == "on"
    assert _collective_import_mode("auto") == "auto"
    # env wins over config
    monkeypatch.setenv("VENEUR_TPU_COLLECTIVE_IMPORT", "off")
    assert _collective_import_mode("on") == "off"


def test_auto_engages_iff_multi_device(monkeypatch):
    monkeypatch.delenv("VENEUR_TPU_COLLECTIVE_IMPORT", raising=False)
    t = MetricTable(TableConfig())
    assert t.collective_import_mode == "auto"
    fold = t._collective_wire_fold()
    assert fold is not None  # conftest platform has 8 devices
    assert fold.n_shard == len(jax.devices())
    # resolved once, cached
    assert t._collective_wire_fold() is fold
    # single visible device -> auto falls back to the serial scan
    one = MetricTable(TableConfig())
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [jax.local_devices()[0]])
    assert one._collective_wire_fold() is None


def test_off_and_on_force(monkeypatch):
    monkeypatch.setenv("VENEUR_TPU_COLLECTIVE_IMPORT", "off")
    assert MetricTable(TableConfig())._collective_wire_fold() is None
    monkeypatch.setenv("VENEUR_TPU_COLLECTIVE_IMPORT", "on")
    fold = MetricTable(TableConfig())._collective_wire_fold()
    assert fold is not None


def test_pad_wires_multiple_of_shards():
    mesh = sharded.make_import_mesh()
    fold = sharded.CollectiveWireFold(mesh)
    s = fold.n_shard
    for n in (1, s - 1, s, s + 1, 3 * s):
        p = fold.pad_wires(n)
        assert p >= max(n, 1) and p % s == 0
        assert p - n < s  # minimal padding


# ----------------------------------------------------------------------
# parity


def _spread_wires(n_wires=6, n_series=5, per_wire=3):
    """Deterministic wire parts whose centroids stay >1 k-width apart
    and far under capacity, so no merge topology ever clusters."""
    wires = []
    for w in range(n_wires):
        rows, means, wts = [], [], []
        for s in range(n_series):
            for j in range(per_wire):
                rows.append(s)
                # unique, widely separated means per (wire, series, j)
                means.append(1e4 * (w * n_series * per_wire
                                    + s * per_wire + j) + 17.0)
                wts.append(1.0)
        wires.append((np.asarray(rows, np.int32),
                      np.asarray(means, np.float32),
                      np.asarray(wts, np.float32)))
    return wires


def _apply(collective, wires, dense=False, seed=3):
    t = MetricTable(TableConfig())
    t.fused_import_mode = "stack"
    t.collective_import_mode = collective
    rng = np.random.default_rng(seed)
    srows = np.arange(max(int(r.max()) + 1 for r, _, _ in wires),
                      dtype=np.int32)
    names = [t.import_histo_row(f"lat{s}", "timer", ())
             for s in srows]
    for rows, means, wts in wires:
        stats = np.tile(np.asarray(
            [1.0, 2.0, 3.0, 0.0, 3.0], np.float32), (len(srows), 1))
        t.import_histo_batch(np.asarray(names, np.int32), stats,
                             np.asarray(names, np.int32)[rows],
                             means, wts)
        # non-histo classes ride the same wires: the fold must leave
        # them untouched in every gate setting
        t.import_counter_batch(
            np.asarray([t.import_counter_row("hits", ())], np.int32),
            np.asarray([2.0]))
        t.import_gauge_batch(
            np.asarray([t.import_gauge_row("temp", ())], np.int32),
            np.asarray([41.5]))
        t.import_set_at(t.import_set_row("users", ()),
                        rng.integers(0, 32, hll.M).astype(np.uint8))
    t.device_step(final=True)
    return t


def test_collective_bit_identical_in_spread_regime():
    wires = _spread_wires()
    serial = _apply("off", wires)
    coll = _apply("on", wires)
    assert coll._collective_fold is not None
    assert coll._collective_fold.n_shard > 1
    for attr in ("histo_means", "histo_weights", "counters", "gauges",
                 "hll_regs"):
        a = np.asarray(getattr(serial, attr))
        b = np.asarray(getattr(coll, attr))
        assert np.array_equal(a, b), attr


def test_collective_conserves_mass_on_dense_digests():
    """Dense digests DO cluster, so bits legitimately differ between
    topologies — but integer-weight mass must be conserved exactly and
    the centroid span must agree."""
    rng = np.random.default_rng(11)
    wires = []
    for w in range(6):
        n = 160
        rows = rng.integers(0, 5, n).astype(np.int32)
        means = rng.gamma(3.0, 10.0, n).astype(np.float32)
        wts = rng.integers(1, 9, n).astype(np.float32)
        wires.append((rows, means, wts))
    serial = _apply("off", wires)
    coll = _apply("on", wires)
    sw = np.asarray(serial.histo_weights)
    cw = np.asarray(coll.histo_weights)
    assert float(sw.sum()) == float(cw.sum()) > 0
    sm = np.asarray(serial.histo_means)
    cm = np.asarray(coll.histo_means)
    for row in range(5):
        s_live, c_live = sw[row] > 0, cw[row] > 0
        assert sm[row][s_live].min() == cm[row][c_live].min()
        assert sm[row][s_live].max() == cm[row][c_live].max()


_SUBPROC = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d")
import jax
sys.path.insert(0, %r)
from test_collective_import import (_apply, _spread_wires)
import numpy as np
wires = _spread_wires()
serial = _apply("off", wires)
coll = _apply("on", wires)
assert coll._collective_fold is not None
assert coll._collective_fold.n_shard == len(jax.devices())
assert np.array_equal(np.asarray(serial.histo_means),
                      np.asarray(coll.histo_means))
assert np.array_equal(np.asarray(serial.histo_weights),
                      np.asarray(coll.histo_weights))
print("OK", len(jax.devices()))
"""


@pytest.mark.slow
@pytest.mark.parametrize("ndev", [1, 4])
def test_parity_across_device_counts(ndev):
    """Re-run the spread parity at other device counts (the in-process
    platform is pinned to 8 by conftest): S=1 exercises the forced-on
    single-device union, S=4 a different shard split."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "VENEUR_TPU_COLLECTIVE_IMPORT")}
    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC % (ndev, here)],
        env=env, cwd=os.path.dirname(here), capture_output=True,
        text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert f"OK {ndev}" in out.stdout


def test_tpu_pipeline_ignored_warning_with_sharded_table(caplog):
    """tpu_pipeline is a no-op with the mesh-sharded table; the
    capability downgrade must be logged, not silent (operators tuning
    the knob would otherwise chase nothing)."""
    import logging

    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server

    with caplog.at_level(logging.WARNING, logger="veneur_tpu.server"):
        srv = Server(read_config(data={
            "interval": "10s",
            "tpu_mesh_shards": 2,
            "tpu_histo_rows": 64, "tpu_set_rows": 8,
            "tpu_counter_rows": 16, "tpu_gauge_rows": 16}))
    try:
        assert srv.pipeline is False
        assert any("tpu_pipeline is ignored" in r.message
                   for r in caplog.records)
    finally:
        srv.shutdown()
