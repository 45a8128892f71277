"""Fused Pallas merge kernel vs the XLA cluster-merge path.

The kernel runs through the Pallas interpreter on the CPU mesh (the
same ops, minus Mosaic lowering), so these tests pin its SEMANTICS —
cluster assignment, weight conservation, packing contract, quantile
accuracy — against ops/tdigest's scatter path.  Device timing A/Bs
belong to a chip run (VENEUR_TPU_MERGE=pallas there).
"""

from __future__ import annotations

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.ops import pallas_merge, tdigest


def _merge_both(means, weights, bm, bw, compression=100.0):
    """Run the same merge through the scatter path and the fused
    kernel (interpret mode)."""
    xm, xw = tdigest._merge_impl(
        jnp.asarray(means), jnp.asarray(weights), jnp.asarray(bm),
        jnp.asarray(bw), compression=compression)
    pm, pw = pallas_merge.merge_planes(
        jnp.asarray(means), jnp.asarray(weights), jnp.asarray(bm),
        jnp.asarray(bw),
        delta=tdigest._SCALE_MULT * compression,
        tail_coeff=tdigest._TAIL_MULT * compression,
        tail_q0=tdigest._TAIL_Q0, tail_qmin=tdigest._TAIL_QMIN,
        interpret=True)
    return (np.asarray(xm), np.asarray(xw),
            np.asarray(pm), np.asarray(pw))


def _random_case(rng, rows, cap, slots):
    means = np.zeros((rows, cap), np.float32)
    weights = np.zeros((rows, cap), np.float32)
    occ = rng.integers(0, cap // 2, size=rows)
    for r in range(rows):
        vals = np.sort(rng.normal(200.0, 40.0, occ[r])).astype(
            np.float32)
        means[r, :occ[r]] = vals
        weights[r, :occ[r]] = rng.integers(
            1, 50, occ[r]).astype(np.float32)
    bm = rng.normal(200.0, 40.0, (rows, slots)).astype(np.float32)
    bw = (rng.random((rows, slots)) < 0.8).astype(np.float32)
    bm = np.where(bw > 0, bm, 0.0).astype(np.float32)
    return means, weights, bm, bw


def test_weight_conservation_and_packing():
    rng = np.random.default_rng(7)
    means, weights, bm, bw = _random_case(rng, rows=16,
                                          cap=tdigest.DEFAULT_CAPACITY,
                                          slots=64)
    xm, xw, pm, pw = _merge_both(means, weights, bm, bw)
    total_in = weights.sum(axis=1) + bw.sum(axis=1)
    np.testing.assert_allclose(pw.sum(axis=1), total_in, rtol=1e-6)
    np.testing.assert_allclose(xw.sum(axis=1), total_in, rtol=1e-6)
    # packing contract: occupied slots contiguous from 0, mean-sorted,
    # empty slots zeroed — same as the XLA pack sort
    for r in range(pw.shape[0]):
        occ = pw[r] > 0
        n = occ.sum()
        assert occ[:n].all() and not occ[n:].any()
        ms = pm[r, :n]
        assert (np.diff(ms) >= 0).all()
        assert (pm[r, n:] == 0).all()


def test_matches_scatter_path_clusters():
    """Same centroids in, near-identical centroids out: the two paths
    share the clustering math, so per-slot means/weights agree to f32
    noise (the f32 q-cumsum can move a boundary-straddling centroid,
    so compare through the quantile readout, which is what flushes)."""
    rng = np.random.default_rng(11)
    means, weights, bm, bw = _random_case(rng, rows=8,
                                          cap=tdigest.DEFAULT_CAPACITY,
                                          slots=32)
    xm, xw, pm, pw = _merge_both(means, weights, bm, bw)
    qs = jnp.asarray(np.array([0.1, 0.5, 0.9, 0.99], np.float32))
    qx = np.asarray(tdigest.quantile(jnp.asarray(xm), jnp.asarray(xw),
                                     qs))
    qp = np.asarray(tdigest.quantile(jnp.asarray(pm), jnp.asarray(pw),
                                     qs))
    np.testing.assert_allclose(qp, qx, rtol=2e-3, atol=1e-3)


def test_quantile_accuracy_vs_exact():
    """End-to-end digest built ONLY through the fused kernel stays
    inside the 1% p99 budget vs exact quantiles."""
    rng = np.random.default_rng(3)
    rows, cap, slots = 8, tdigest.DEFAULT_CAPACITY, 128
    m = jnp.zeros((rows, cap), jnp.float32)
    w = jnp.zeros((rows, cap), jnp.float32)
    all_samples = []
    for _ in range(20):
        batch = rng.exponential(100.0, (rows, slots)).astype(
            np.float32)
        all_samples.append(batch)
        bw = np.ones_like(batch)
        m, w = (jnp.asarray(a) for a in (m, w))
        pm, pw = pallas_merge.merge_planes(
            m, w, jnp.asarray(batch), jnp.asarray(bw),
            delta=tdigest._SCALE_MULT * 100.0,
            tail_coeff=tdigest._TAIL_MULT * 100.0,
            tail_q0=tdigest._TAIL_Q0, tail_qmin=tdigest._TAIL_QMIN,
            interpret=True)
        m, w = pm, pw
    samples = np.concatenate(all_samples, axis=1)
    qs = np.array([0.5, 0.9, 0.99], np.float32)
    est = np.asarray(tdigest.quantile(m, w, jnp.asarray(qs)))
    exact = np.quantile(samples, qs, axis=1).T
    rel = np.abs(est - exact) / np.maximum(np.abs(exact), 1e-9)
    assert rel.max() < 0.01, rel


def test_empty_rows_and_row_padding():
    """Rows with no state and no batch stay empty; row counts that
    aren't a block multiple go through the pad/slice wrapper."""
    cap = tdigest.DEFAULT_CAPACITY
    rows = 11  # not a multiple of 8
    means = np.zeros((rows, cap), np.float32)
    weights = np.zeros((rows, cap), np.float32)
    bm = np.zeros((rows, 16), np.float32)
    bw = np.zeros((rows, 16), np.float32)
    bm[0, :3] = [5.0, 1.0, 9.0]
    bw[0, :3] = 1.0
    pm, pw = pallas_merge.merge_planes(
        jnp.asarray(means), jnp.asarray(weights), jnp.asarray(bm),
        jnp.asarray(bw), delta=600.0, tail_coeff=40.0,
        tail_q0=tdigest._TAIL_Q0, tail_qmin=tdigest._TAIL_QMIN,
        interpret=True)
    pm, pw = np.asarray(pm), np.asarray(pw)
    assert pm.shape == (rows, cap)
    assert pw[0].sum() == 3.0
    assert (pw[1:] == 0).all() and (pm[1:] == 0).all()
    occ = pw[0] > 0
    np.testing.assert_allclose(np.sort(pm[0, occ]), [1.0, 5.0, 9.0])


def test_supported_bounds():
    assert pallas_merge.supported(616, 256)   # timer hot path
    assert pallas_merge.supported(312, 256)   # tail-refine-off plane
    assert pallas_merge.supported(616, 512)   # widest ingest chunk
    assert pallas_merge.supported(616, 616)   # global-tier union
    assert not pallas_merge.supported(1232, 1232)  # beyond the bound


def test_wide_union_matches_scatter():
    """The 616+616 digest-vs-digest union (global tier) through the
    widened 2048-lane kernel."""
    rng = np.random.default_rng(13)
    cap = tdigest.DEFAULT_CAPACITY
    a_m, a_w, _, _ = _random_case(rng, rows=8, cap=cap, slots=8)
    b_m, b_w, _, _ = _random_case(rng, rows=8, cap=cap, slots=8)
    xm, xw, pm, pw = _merge_both(a_m, a_w, b_m, b_w)
    total = a_w.sum(axis=1) + b_w.sum(axis=1)
    np.testing.assert_allclose(pw.sum(axis=1), total, rtol=1e-6)
    qs = jnp.asarray(np.array([0.25, 0.5, 0.9, 0.99], np.float32))
    qx = np.asarray(tdigest.quantile(jnp.asarray(xm), jnp.asarray(xw),
                                     qs))
    qp = np.asarray(tdigest.quantile(jnp.asarray(pm), jnp.asarray(pw),
                                     qs))
    np.testing.assert_allclose(qp, qx, rtol=2e-2, atol=1e-3)


def test_mode_dispatch_end_to_end():
    """VENEUR_TPU_MERGE=pallas routes table-level timer ingest through
    the fused kernel (interpret mode) and still flushes accurate
    percentiles — the integration a chip run A/Bs on device."""
    code = """
import numpy as np, jax.numpy as jnp
from veneur_tpu.ops import tdigest
assert tdigest._MERGE_MODE == "pallas"
rng = np.random.default_rng(5)
m, w = tdigest.empty_state(8)
vals = rng.normal(300.0, 50.0, (8, 4000)).astype(np.float32)
for i in range(0, 4000, 200):
    chunk = jnp.asarray(vals[:, i:i+200])
    m, w = tdigest._merge_impl(m, w, chunk, jnp.ones_like(chunk),
                               compression=100.0)
est = np.asarray(tdigest.quantile(m, w, jnp.asarray(
    np.array([0.5, 0.99], np.float32))))
exact = np.quantile(vals, [0.5, 0.99], axis=1).T
rel = np.abs(est - exact) / np.abs(exact)
assert rel.max() < 0.01, rel
print("ok", float(rel.max()))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               VENEUR_TPU_MERGE="pallas",
               VENEUR_TPU_PALLAS_INTERPRET="1")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith("ok")


_KW = dict(tail_q0=tdigest._TAIL_Q0, tail_qmin=tdigest._TAIL_QMIN,
           interpret=True)


def _boundary_rows(means, weights, bm, bw, compression=100.0):
    """Rows with a live lane whose k(q) lies within 2e-4 of a cluster
    boundary.  The scatter path's ``arcsin`` and the kernel's Hastings
    polynomial agree on k to one f32 ulp at 512 (6.1e-5), so only such
    a lane past the first can floor to different ids on the two paths; every other
    row must match slot for slot."""
    m = np.concatenate([means, bm], axis=1)
    w = np.concatenate([weights, bw], axis=1)
    order = np.argsort(np.where(w > 0, m, np.inf), axis=1, kind="stable")
    ws = np.take_along_axis(w, order, axis=1)
    total = ws.sum(axis=1, keepdims=True)
    q = (np.cumsum(ws, axis=1) - ws) / np.maximum(total, 1e-30)
    delta = tdigest._SCALE_MULT * compression
    k = np.asarray(tdigest._k_scale(jnp.asarray(q), delta, compression) -
                   tdigest._k_scale(jnp.float32(0.0), delta, compression))
    # the first lane reads 0 on both paths, which clip it to cluster 0
    near = (np.abs(k - np.round(k)) < 2e-4) & (ws > 0) & (k > 0.5)
    return near.any(axis=1)


@pytest.mark.parametrize("slots", [96, 256, 512, 1232])
def test_parity_with_scatter_at_the_cells_widths(slots):
    """The four widths the cells run against a 616-slot state (the
    local's swap merge, a wire fold, the mesh update call and the mesh
    flush merge): integer weights make every prefix sum exact, so the
    per-slot weights equal the scatter path's exactly and the means
    agree to f32 rounding, wherever no lane sits on a boundary."""
    rng = np.random.default_rng(3700 + slots)
    cap = tdigest.DEFAULT_CAPACITY
    means, weights, bm, bw = _random_case(rng, rows=16, cap=cap,
                                          slots=slots)
    xm, xw, pm, pw = _merge_both(means, weights, bm, bw)
    same = ~_boundary_rows(means, weights, bm, bw)
    assert same.sum() >= 4, same
    np.testing.assert_array_equal(pw[same], xw[same])
    np.testing.assert_allclose(pm[same], xm[same], rtol=1e-6)
    np.testing.assert_array_equal(pw.sum(axis=1),
                                  weights.sum(axis=1) + bw.sum(axis=1))
    qs = jnp.asarray(np.array([0.1, 0.5, 0.9, 0.99], np.float32))
    qx = np.asarray(tdigest.quantile(jnp.asarray(xm), jnp.asarray(xw),
                                     qs))
    qp = np.asarray(tdigest.quantile(jnp.asarray(pm), jnp.asarray(pw),
                                     qs))
    np.testing.assert_allclose(qp, qx, rtol=2e-3, atol=1e-3)


def _inverse_k(kv, delta):
    """q at which the plain asin scale (no tail term) reads ``kv``."""
    return (np.sin(2.0 * np.pi * (kv / delta - 0.25)) + 1.0) / 2.0


def test_edge_rows():
    """An empty row; one live lane; a row whose 616 lanes each open a
    cluster of their own, so the runs fill every slot; a row whose
    three lanes leave gaps between their ids."""
    cap, delta = tdigest.DEFAULT_CAPACITY, 2.0 * tdigest.DEFAULT_CAPACITY
    means = np.zeros((8, cap), np.float32)
    weights = np.zeros((8, cap), np.float32)
    bm = np.zeros((8, 96), np.float32)
    bw = np.zeros((8, 96), np.float32)
    bm[1, 40], bw[1, 40] = 7.5, 3.0
    # lane i's left q sits mid-cluster i, for ids 0..cap-1 on a scale
    # of delta = 2 * cap
    q_left = np.concatenate([[0.0], _inverse_k(np.arange(1, cap) + 0.5,
                                                delta)])
    full_w = np.diff(np.concatenate([q_left, [1.0]])).astype(np.float32)
    means[2] = np.arange(cap, dtype=np.float32) * 0.5 - 100.0
    weights[2] = full_w
    means[3, :3] = [1.0, 2.0, 3.0]
    weights[3, :3] = 1.0
    pm, pw = (np.asarray(a) for a in pallas_merge.merge_planes(
        jnp.asarray(means), jnp.asarray(weights), jnp.asarray(bm),
        jnp.asarray(bw), delta=delta, tail_coeff=0.0, **_KW))
    assert (pw[0] == 0).all() and (pm[0] == 0).all()
    assert pw[1, 0] == 3.0 and pm[1, 0] == 7.5
    assert (pw[1, 1:] == 0).all() and (pm[1, 1:] == 0).all()
    assert (pw[2] > 0).all()
    np.testing.assert_allclose(pw[2], full_w, rtol=1e-6)
    np.testing.assert_allclose(pm[2], means[2], rtol=1e-6)
    # q 0, 1/3, 2/3 on that scale: ids 0, 241 and 374, gaps between
    np.testing.assert_array_equal(pw[3, :3], [1.0, 1.0, 1.0])
    np.testing.assert_array_equal(pm[3, :3], [1.0, 2.0, 3.0])
    assert (pw[3, 3:] == 0).all() and (pm[3, 3:] == 0).all()
    assert (pw[4:] == 0).all()


def test_tail_clusters_exact_beside_a_heavy_bulk():
    """1,000 weight-1 centroids at 1e4, a tenth of the weight, above a
    bulk of 300 centroids of weight 30 at 100: the refined tail splits
    them into some 270 clusters of 1 to 25 centroids, each of whose
    mean comes out within 1e-6 of float64.  The w * m prefix sum there
    is near 1e7, so a plain f32 prefix-sum difference misses a
    cluster's sum by up to one unit in 1e4 (ops/tdigest.py: what
    corrupted p999)."""
    rng = np.random.default_rng(37)
    cap = tdigest.DEFAULT_CAPACITY
    rows, bulk, tail = 8, 300, 1000
    means = np.zeros((rows, cap), np.float32)
    weights = np.zeros((rows, cap), np.float32)
    means[:, :bulk] = np.sort(rng.normal(100.0, 5.0, (rows, bulk)),
                              axis=1)
    weights[:, :bulk] = 30.0
    bm = np.zeros((rows, 1232), np.float32)
    bw = np.zeros((rows, 1232), np.float32)
    bm[:, :tail] = 1e4 + rng.random((rows, tail)) * 50.0
    bw[:, :tail] = 1.0
    pm, pw = (np.asarray(a) for a in pallas_merge.merge_planes(
        jnp.asarray(means), jnp.asarray(weights), jnp.asarray(bm),
        jnp.asarray(bw), delta=tdigest._SCALE_MULT * 100.0,
        tail_coeff=tdigest._TAIL_MULT * 100.0, **_KW))
    checked = 0
    for r in range(rows):
        vals = np.concatenate([means[r, :bulk], np.sort(bm[r, :tail])])
        wts = np.concatenate([weights[r, :bulk], bw[r, :tail]])
        cum = np.cumsum(wts.astype(np.float64))
        # integer weights sum exactly: the slots' prefix weights are
        # the boundaries between members
        ends = np.cumsum(pw[r][pw[r] > 0].astype(np.float64))
        assert ends[-1] == cum[-1]
        lo = 0
        for t, e in enumerate(ends):
            hi = int(np.searchsorted(cum, e)) + 1
            if vals[lo] >= 1e4:
                v = vals[lo:hi].astype(np.float64)
                ref = (v * wts[lo:hi]).sum() / wts[lo:hi].sum()
                assert abs(pm[r, t] - ref) <= 1e-6 * ref, (r, t)
                checked += 1
            lo = hi
    assert checked >= rows * 200, checked


@pytest.mark.parametrize("n", [8, 128, 2048])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5, 1.0])
def test_compaction_against_numpy(n, density):
    """The shift network moves exactly the selected lanes to the front,
    in order, for random selection masks."""
    rng = np.random.default_rng(n + int(density * 100))
    sel = rng.random((8, n)) < density
    vals = [rng.normal(size=(8, n)).astype(np.float32),
            rng.integers(-9, 9, (8, n)).astype(np.float32)]
    occ, moved = pallas_merge._compact(
        jnp.asarray(sel), [jnp.asarray(v) for v in vals], n)
    occ = np.asarray(occ)
    for r in range(8):
        c = int(sel[r].sum())
        np.testing.assert_array_equal(occ[r], np.arange(n) < c)
        for v, mv in zip(vals, moved):
            np.testing.assert_array_equal(np.asarray(mv)[r, :c],
                                          v[r][sel[r]])
