"""Batched t-digest kernels: fixed-shape centroid planes on device.

The reference keeps one ``tdigest.MergingDigest`` per timer/histogram
series: a temp buffer of raw samples merged into a centroid list by a
sequential greedy pass over the k-scale (reference
tdigest/merging_digest.go:115 ``Add``, :140 ``mergeAllTemps``, :229
``mergeOne``, :302 ``Quantile``).  That algorithm is inherently serial
per digest — the wrong shape for a TPU.

Here ALL series merge at once.  State is a pair of planes
``means f32[R, C]`` / ``weights f32[R, C]`` (weight 0 = empty slot) and a
merge is:

1. concatenate incoming centroids (raw samples are centroids of weight
   ``1/rate``) onto the state planes along the slot axis,
2. one batched ``lax.sort`` by mean (empty slots keyed to +inf),
3. cumulative weight -> left quantile ``q`` per centroid,
4. cluster index ``floor(k(q) - k(0))`` with the Dunning k1 scale
   ``k(q) = delta/(2*pi) * asin(2q - 1)``,
5. weighted segment reduction of (mean, weight) by cluster index.

Clustering by k-index instead of greedy boundary scanning is the
parallel-friendly construction from the t-digest paper (arXiv:1902.04023
"Computing Extremely Accurate Quantiles Using t-Digests", Alg. 2 family)
and yields the same size bound (<= delta/2 + 1 clusters for k1).  To
absorb the slightly looser clustering and repeated re-merging, the
internal scale uses a multiple of the configured compression, plus a
clamped log-term that refines the upper tail to constant RELATIVE
cluster width (see _TAIL_MULT); with the default compression=100
(reference samplers/samplers.go:502) the plane capacity ``C=616``
holds the body's ~300 clusters plus the tail refinement's ~305 and
keeps the slot axis lane-aligned.

Digest-vs-digest merge (the global tier's Histo.Merge,
samplers/samplers.go:726) is the same kernel with the other digest's
centroids as the incoming batch; the cross-chip union is therefore a
gather of centroid planes followed by one merge step.
"""

from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp

from veneur_tpu.utils import jitopts

Array = jax.Array

# Cluster-reduction strategy for the merge kernel.  "scatter"
# (default): per-cluster sums via scatter-add — exact, but the
# 18M-element scatter was measured at ~60% of the merge on a v5e
# (round-2 profile).  "dfcumsum": double-float (two-f32 compensated)
# cumulative sums + sorted-boundary gather — no scatter at all, and
# the compensation keeps per-cluster sums exact-in-practice (~2^-48
# relative; a plain f32 cumsum-diff was measured to corrupt p999 by
# perturbing tail cluster contents).  "pallas": the whole merge
# (sort + cluster ids + the same compensated prefix sums, differenced
# at run ends + compaction) fused into one Pallas TPU kernel
# (ops/pallas_merge.py) — one HBM pass each way, no scatter, no
# second sort, no matmul; falls back to _FALLBACK_MODE where the fused
# kernel doesn't apply (combined width > its 2048-lane bound, which
# no table-emitted shape exceeds).  The default, "auto",
# resolves to pallas on a TPU backend and scatter elsewhere (an A/B
# from before PR 1 favoured the fused kernel on the chip; it has not
# been re-measured on today's code), while CPUs prefer scatter (cheap
# scatter-add; the interpreted kernel would crawl).
_MERGE_MODE = os.environ.get("VENEUR_TPU_MERGE", "auto")

# Cluster-reduction used where the fused pallas kernel doesn't apply
# (combined width > its VMEM bound).
_FALLBACK_MODE = os.environ.get("VENEUR_TPU_MERGE_FALLBACK", "scatter")


def resolve_merge_mode_for(platform: str) -> str:
    """Pure resolution rule, usable without touching a jax backend
    (bench's parent process stays off JAX and stamps headlines from
    the platform string its child captured)."""
    if _MERGE_MODE != "auto":
        return _MERGE_MODE
    return "pallas" if platform == "tpu" else "scatter"


def resolved_merge_mode() -> str:
    """The merge strategy in effect: "auto" resolves per backend at
    call time (bench artifacts record this resolved value).  A
    backend that cannot start raises here; it does not pick a mode."""
    return resolve_merge_mode_for(jax.default_backend())


def merge_path(cap: int, batch_width: int) -> str:
    """The path ``_merge_impl`` takes for a ``cap``-slot state and a
    ``batch_width``-slot batch: the mode in effect, or the fallback
    where that is the fused kernel and the pair sorts past its lanes.
    Callers that gather wide (the mesh merge) say so with this; a
    fallback is theirs to report."""
    mode = resolved_merge_mode()
    if mode == "pallas":
        from veneur_tpu.ops import pallas_merge
        if not pallas_merge.supported(cap, batch_width):
            return _FALLBACK_MODE
    return mode


DEFAULT_COMPRESSION = 100.0

_EPS = 1e-30


# Internal k-scale multiplier: the digest clusters on a scale of
# _SCALE_MULT * compression, i.e. ~3x the centroid count of a greedy
# merging digest at the configured compression.  Extra slots are cheap
# in HBM and the batched sort is tiny; the payoff is ~3x finer tail
# resolution, which is what the p99/p999 accuracy budget rides on
# (p999 on heavy-tailed distributions needs the finer clusters).
_SCALE_MULT = 6.0

# Upper-tail refinement: the k1 (asin) scale's cluster width at the
# tail is ~(2*pi/delta)*sqrt(1-q), so the RELATIVE q-width
# dq/(1-q) ~ 1/sqrt(1-q) -> at q=0.99 a cluster spans ~3.5% of the
# remaining tail regardless of sample count, which on heavy-tailed
# data (pareto) is a ~3-4% value-space span — the whole p99 error
# budget.  A clamped log-term (the k2 scale family of the t-digest
# paper, arXiv:1902.04023 §3) adds clusters with CONSTANT relative
# width dq/(1-q) = 1/(_TAIL_MULT*compression) for 1-q in
# [_TAIL_QMIN, _TAIL_Q0]: at the defaults every tail cluster spans
# 2.5% of the remaining tail down to p9999, i.e. <=0.9% of value for
# pareto(alpha>=3) and far less for lighter tails.  Timers care about
# the UPPER tail only (p50/p90/p99/p999), so the refinement is
# one-sided; the lower tail keeps the k1 resolution and the true-min
# anchor.
_TAIL_MULT = 0.4
_TAIL_Q0 = 0.2     # refinement active where (1-q) < _TAIL_Q0 (p80 up,
#                    so p90 sits fully inside the refined region)
_TAIL_QMIN = 1e-4  # clamp: no extra resolution beyond p9999

# Device A/B gate: VENEUR_TPU_TAIL_REFINE=0 turns the tail log-term
# off, shrinking the plane to the plain-asin 312 slots — for measuring
# the refinement's capacity cost (sort width) against its accuracy win
# on real accelerator hardware (it cost ~24% CPU timer throughput at
# quick scale; the device trade was never measured).
if os.environ.get("VENEUR_TPU_TAIL_REFINE", "1").lower() in (
        "0", "false", "off"):
    _TAIL_MULT = 0.0


def capacity_for(compression: float) -> int:
    """Slot capacity: cluster count of the internal scale — the asin
    body plus the clamped upper-tail log-term (+ slack), rounded up to
    a multiple of 8 for lane alignment."""
    clusters = (int(math.ceil(_SCALE_MULT * compression / 2.0)) +
                int(math.ceil(_TAIL_MULT * compression *
                              math.log(_TAIL_Q0 / _TAIL_QMIN))) + 8)
    return ((clusters + 7) // 8) * 8


# Plane capacity for the default compression (see module docstring):
# asin body (300) + clamped tail refinement (305) + slack = 616, or
# 312 with the refinement gated off (VENEUR_TPU_TAIL_REFINE=0).
DEFAULT_CAPACITY = capacity_for(DEFAULT_COMPRESSION)


def empty_state(num_rows: int,
                capacity: int = DEFAULT_CAPACITY) -> tuple[Array, Array]:
    means = jnp.zeros((num_rows, capacity), dtype=jnp.float32)
    weights = jnp.zeros((num_rows, capacity), dtype=jnp.float32)
    return means, weights


def _k_scale(q: Array, delta: float, compression: float) -> Array:
    """Monotone cluster scale: asin body + clamped upper-tail log
    refinement (see _TAIL_MULT).  floor(k) is the cluster id."""
    body = (delta / (2.0 * jnp.pi)) * jnp.arcsin(
        jnp.clip(2.0 * q - 1.0, -1.0, 1.0))
    tail = (_TAIL_MULT * compression) * jnp.log(
        _TAIL_Q0 / jnp.clip(1.0 - q, _TAIL_QMIN, None))
    return body + jnp.maximum(tail, 0.0)


def k_scale_np(q: "np.ndarray | float", compression: float):
    """Numpy mirror of _k_scale (same constants, f64) for host-side
    pre-clustering (core/table._host_precluster) — host and device
    MUST cluster on the same scale or host-pre-clustered batches lose
    the tail refinement."""
    import numpy as np
    delta = _SCALE_MULT * compression
    body = (delta / (2.0 * np.pi)) * np.arcsin(
        np.clip(2.0 * q - 1.0, -1.0, 1.0))
    tail = (_TAIL_MULT * compression) * np.log(
        _TAIL_Q0 / np.clip(1.0 - q, _TAIL_QMIN, None))
    return body + np.maximum(tail, 0.0)


def _two_sum(a: Array, b: Array) -> tuple[Array, Array]:
    """Error-free transform: a+b = s+err exactly (Knuth two-sum)."""
    s = a + b
    bb = s - a
    err = (a - (s - bb)) + (b - bb)
    return s, err


def _df_add(x, y):
    """Double-float addition: (hi, lo) pairs carrying ~2^-48 relative
    precision in pure f32 — associative_scan's combine op."""
    s, e = _two_sum(x[0], y[0])
    e = e + (x[1] + y[1])
    hi = s + e
    lo = e - (hi - s)
    return (hi, lo)


def _df_take(df, pos, valid):
    hi = jnp.where(valid, jnp.take_along_axis(df[0], pos, axis=1), 0.0)
    lo = jnp.where(valid, jnp.take_along_axis(df[1], pos, axis=1), 0.0)
    return hi, lo


def _df_diff(a, b) -> Array:
    """Compensated a-b of double-floats -> f32 (the boundary diff is
    where a plain f32 cumsum loses the tail clusters)."""
    s, e = _two_sum(a[0], -b[0])
    return s + (e + (a[1] - b[1]))


def _seg_sums_dfcumsum(m: Array, w: Array, cluster: Array,
                       cap: int) -> tuple[Array, Array]:
    """Per-cluster (w*m, w) sums WITHOUT a scatter: compensated
    cumulative sums along the sorted axis + a searchsorted boundary
    gather per cluster slot (cluster ids are non-decreasing per row
    after the sort by mean)."""
    zeros = jnp.zeros_like(w)
    cw = jax.lax.associative_scan(_df_add, (w, zeros), axis=1)
    cwm = jax.lax.associative_scan(_df_add, (w * m, zeros), axis=1)
    cs = jnp.arange(cap, dtype=cluster.dtype)
    pos = jax.vmap(
        lambda cl: jnp.searchsorted(cl, cs, side="right"))(cluster) - 1
    posc = jnp.maximum(pos, 0)
    valid = pos >= 0
    W_at = _df_take(cw, posc, valid)
    WM_at = _df_take(cwm, posc, valid)
    zcol = jnp.zeros((m.shape[0], 1), jnp.float32)
    W_prev = (jnp.concatenate([zcol, W_at[0][:, :-1]], axis=1),
              jnp.concatenate([zcol, W_at[1][:, :-1]], axis=1))
    WM_prev = (jnp.concatenate([zcol, WM_at[0][:, :-1]], axis=1),
               jnp.concatenate([zcol, WM_at[1][:, :-1]], axis=1))
    return _df_diff(WM_at, WM_prev), _df_diff(W_at, W_prev)


def _merge_impl(means: Array, weights: Array, new_means: Array,
                new_weights: Array, compression: float
                ) -> tuple[Array, Array]:
    """Merge incoming centroids/samples into every row's digest at once.

    means, weights: f32[R, C] state planes (weight 0 = empty).
    new_means, new_weights: f32[R, K] incoming (weight 0 = padding).
    Returns updated f32[R, C] planes, sorted by mean with empty slots at
    the end.
    """
    num_rows, cap = means.shape
    needed = capacity_for(compression)
    if cap < needed:
        raise ValueError(
            f"digest capacity {cap} < {needed} required for "
            f"compression={compression}; clusters would silently collapse "
            f"into the last slot (use empty_state(R, capacity_for(c)))")
    delta = _SCALE_MULT * compression  # internal scale, see module docstring

    # past the fused kernel's 2048-lane bound the fallback runs —
    # none of the one-chip table's own shapes get there (widest: 616
    # state + 616 union); a mesh merge that gathers over more than
    # two shards does, and says so (parallel/sharded.py).  Scatter by
    # default: routing wide ingest chunks through dfcumsum was
    # measured to cost the timer config ~45% end-to-end (1.02s vs
    # 0.55s intervals).
    mode = merge_path(cap, new_means.shape[1])
    if mode == "pallas":
        from veneur_tpu.ops import pallas_merge
        return pallas_merge.merge_planes(
            means, weights, new_means, new_weights, delta=delta,
            tail_coeff=_TAIL_MULT * compression,
            tail_q0=_TAIL_Q0, tail_qmin=_TAIL_QMIN)

    m = jnp.concatenate([means, new_means], axis=1)
    w = jnp.concatenate([weights, new_weights], axis=1)
    key = jnp.where(w > 0, m, jnp.inf)
    _, m, w = jax.lax.sort((key, m, w), dimension=-1, num_keys=1)

    total = jnp.sum(w, axis=1, keepdims=True)
    cum = jnp.cumsum(w, axis=1)
    q_left = (cum - w) / jnp.maximum(total, _EPS)
    k = (_k_scale(q_left, delta, compression) -
         _k_scale(jnp.float32(0.0), delta, compression))
    cluster = jnp.clip(jnp.floor(k).astype(jnp.int32), 0, cap - 1)

    if mode == "dfcumsum":
        out_wm, out_w = _seg_sums_dfcumsum(m, w, cluster, cap)
    else:
        rows = jnp.arange(num_rows, dtype=jnp.int32)[:, None]
        flat = (rows * cap + cluster).ravel()
        out_w = jnp.zeros((num_rows * cap,), jnp.float32).at[flat].add(
            w.ravel()).reshape(num_rows, cap)
        out_wm = jnp.zeros((num_rows * cap,),
                           jnp.float32).at[flat].add(
            (w * m).ravel()).reshape(num_rows, cap)
    out_m = jnp.where(out_w > 0,
                      out_wm / jnp.maximum(out_w, _EPS), 0.0)

    # Re-pack so occupied slots are contiguous and mean-sorted (cluster
    # ids are monotone in mean, but sparse rows leave embedded gaps).
    pack_key = jnp.where(out_w > 0, out_m, jnp.inf)
    _, out_m, out_w = jax.lax.sort((pack_key, out_m, out_w),
                                   dimension=-1, num_keys=1)
    return out_m, out_w


# Ingest path (donation policy: utils/jitopts).
merge_batch = partial(
    jax.jit(_merge_impl, static_argnames=("compression",),
            donate_argnums=jitopts.donate(0, 1)),
    compression=DEFAULT_COMPRESSION)

# Union path (global tier): callers typically still need both inputs
# afterwards (e.g. quantile over a local digest that was just merged
# into a union), so nothing is donated.
_merge_no_donate = jax.jit(_merge_impl, static_argnames=("compression",))


def densify(row_ids: Array, values: Array, weights: Array, num_rows: int,
            slots: int) -> tuple[Array, Array]:
    """Pack a flat sample batch into per-row dense planes f32[R, K].

    Samples beyond ``slots`` per row in one call are dropped (mode=drop),
    so callers must chunk batches such that no row exceeds ``slots``
    samples (host side: np.bincount + chunking, see core/table.py).
    Padding entries use row_id == num_rows.
    """
    n = row_ids.shape[0]
    order = jnp.argsort(row_ids, stable=True)
    sid = row_ids[order]
    sval = values[order]
    swt = weights[order]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos, 0))
    rank = pos - start
    dense_v = jnp.zeros((num_rows, slots), jnp.float32).at[
        sid, rank].set(sval, mode="drop")
    dense_w = jnp.zeros((num_rows, slots), jnp.float32).at[
        sid, rank].set(swt, mode="drop")
    return dense_v, dense_w


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples(means: Array, weights: Array, row_ids: Array,
                values: Array, sample_weights: Array,
                slots: int = 256,
                compression: float = DEFAULT_COMPRESSION
                ) -> tuple[Array, Array]:
    """Flat-sample ingest: densify then merge in one fused jit (the
    batched equivalent of MergingDigest.Add over an entire tick's
    samples).  Callers should pad batches to a fixed length per
    ``slots`` bucket to avoid shape-driven recompiles."""
    num_rows = means.shape[0]
    dense_v, dense_w = densify(row_ids, values, sample_weights, num_rows,
                               slots)
    return _merge_impl(means, weights, dense_v, dense_w,
                       compression=compression)


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_ranked(means: Array, weights: Array, row_ids: Array,
                       ranks: Array, values: Array,
                       sample_weights: Array, slots: int = 256,
                       compression: float = DEFAULT_COMPRESSION
                       ) -> tuple[Array, Array]:
    """add_samples with the within-row rank precomputed on host
    (native vtpu_rank, an O(n) counter pass): the device does only the
    two scatters + cluster merge.  Replaces densify's 1M-element
    bitonic argsort (~0.6s/call on device) with ~5ms of host work.
    Padding entries MUST use row_id == num_rows (dropped)."""
    num_rows = means.shape[0]
    dense_v = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(sample_weights, mode="drop")
    return _merge_impl(means, weights, dense_v, dense_w,
                       compression=compression)


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_ranked_unit(means: Array, weights: Array,
                            row_ids: Array, ranks: Array,
                            values: Array, slots: int = 256,
                            compression: float = DEFAULT_COMPRESSION
                            ) -> tuple[Array, Array]:
    """add_samples_ranked with unit sample weights synthesised on
    device (one less h2d column on the timer hot path)."""
    num_rows = means.shape[0]
    dense_v = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(jnp.ones_like(values), mode="drop")
    return _merge_impl(means, weights, dense_v, dense_w,
                       compression=compression)


def _stats_from_dense(stats: Array, dense_v: Array, dense_w: Array
                      ) -> Array:
    """Fold a dense sample plane into the per-row (weight, min, max,
    sum, rsum) aggregates (reference samplers/samplers.go:484-494) as
    row reductions — the scatter-add variant costs ~0.2s per 4M
    samples on device; these reductions are O(planes) elementwise."""
    from veneur_tpu.ops import segment
    occ = dense_w > 0
    w = stats[:, segment.STAT_WEIGHT] + dense_w.sum(axis=1)
    mn = jnp.minimum(
        stats[:, segment.STAT_MIN],
        jnp.where(occ, dense_v, segment._F32_MAX).min(axis=1))
    mx = jnp.maximum(
        stats[:, segment.STAT_MAX],
        jnp.where(occ, dense_v, -segment._F32_MAX).max(axis=1))
    sm = stats[:, segment.STAT_SUM] + (dense_v * dense_w).sum(axis=1)
    rs = stats[:, segment.STAT_RSUM] + jnp.where(
        occ & (dense_v != 0), dense_w / dense_v, 0.0).sum(axis=1)
    return jnp.stack([w, mn, mx, sm, rs], axis=1)


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1, 2))
def ingest_ranked(means: Array, weights: Array, stats: Array,
                  row_ids: Array, ranks: Array, values: Array,
                  sample_weights: Array, slots: int = 256,
                  compression: float = DEFAULT_COMPRESSION
                  ) -> tuple[Array, Array, Array]:
    """One fused device pass for the histo hot path: scatter the
    ranked batch into dense planes, fold the local aggregates, cluster
    into the digests.  Replaces add_samples + a separate 4M-wide
    stats scatter with one kernel."""
    num_rows = means.shape[0]
    dense_v = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(sample_weights, mode="drop")
    stats = _stats_from_dense(stats, dense_v, dense_w)
    m, w = _merge_impl(means, weights, dense_v, dense_w,
                       compression=compression)
    return m, w, stats


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1, 2))
def ingest_ranked_unit(means: Array, weights: Array, stats: Array,
                       row_ids: Array, ranks: Array, values: Array,
                       slots: int = 256,
                       compression: float = DEFAULT_COMPRESSION
                       ) -> tuple[Array, Array, Array]:
    """ingest_ranked with unit sample weights synthesised on device."""
    num_rows = means.shape[0]
    dense_v = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_rows, slots), jnp.float32).at[
        row_ids, ranks].set(jnp.ones_like(values), mode="drop")
    stats = _stats_from_dense(stats, dense_v, dense_w)
    m, w = _merge_impl(means, weights, dense_v, dense_w,
                       compression=compression)
    return m, w, stats


# ---- touched-row-subset variants -----------------------------------
# A batch touching m rows of an R-row plane pays the k-scale merge
# (sort + scan over R x (C+slots)) for every row, live or not; when
# m << R the gather/merge-compact/scatter-back trio below makes the
# interval cost O(m), not O(table capacity).  ``row_idx`` is the
# padded array of ABSOLUTE row ids (pad entries use an out-of-range
# id: take fills zeros, the scatter-back drops them); ``row_ids`` are
# batch sample ids REMAPPED into the subset's local space (pad
# samples use row_idx.shape[0], densify's drop contract).


def _take_rows(plane: Array, row_idx: Array) -> Array:
    return jnp.take(plane, row_idx, axis=0, mode="fill",
                    fill_value=0.0)


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_ranked_rows(means: Array, weights: Array,
                            row_idx: Array, row_ids: Array,
                            ranks: Array, values: Array,
                            sample_weights: Array, slots: int = 256,
                            compression: float = DEFAULT_COMPRESSION
                            ) -> tuple[Array, Array]:
    num_sub = row_idx.shape[0]
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)
    dense_v = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(sample_weights, mode="drop")
    sub_m, sub_w = _merge_impl(sub_m, sub_w, dense_v, dense_w,
                               compression=compression)
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"))


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_ranked_unit_rows(means: Array, weights: Array,
                                 row_idx: Array, row_ids: Array,
                                 ranks: Array, values: Array,
                                 slots: int = 256,
                                 compression: float =
                                 DEFAULT_COMPRESSION
                                 ) -> tuple[Array, Array]:
    num_sub = row_idx.shape[0]
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)
    dense_v = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(jnp.ones_like(values), mode="drop")
    sub_m, sub_w = _merge_impl(sub_m, sub_w, dense_v, dense_w,
                               compression=compression)
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"))


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1, 2))
def ingest_ranked_rows(means: Array, weights: Array, stats: Array,
                       row_idx: Array, row_ids: Array, ranks: Array,
                       values: Array, sample_weights: Array,
                       slots: int = 256,
                       compression: float = DEFAULT_COMPRESSION
                       ) -> tuple[Array, Array, Array]:
    num_sub = row_idx.shape[0]
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)
    sub_s = _take_rows(stats, row_idx)
    dense_v = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(sample_weights, mode="drop")
    sub_s = _stats_from_dense(sub_s, dense_v, dense_w)
    sub_m, sub_w = _merge_impl(sub_m, sub_w, dense_v, dense_w,
                               compression=compression)
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"),
            stats.at[row_idx].set(sub_s, mode="drop"))


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1, 2))
def ingest_ranked_unit_rows(means: Array, weights: Array,
                            stats: Array, row_idx: Array,
                            row_ids: Array, ranks: Array,
                            values: Array, slots: int = 256,
                            compression: float = DEFAULT_COMPRESSION
                            ) -> tuple[Array, Array, Array]:
    num_sub = row_idx.shape[0]
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)
    sub_s = _take_rows(stats, row_idx)
    dense_v = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(values, mode="drop")
    dense_w = jnp.zeros((num_sub, slots), jnp.float32).at[
        row_ids, ranks].set(jnp.ones_like(values), mode="drop")
    sub_s = _stats_from_dense(sub_s, dense_v, dense_w)
    sub_m, sub_w = _merge_impl(sub_m, sub_w, dense_v, dense_w,
                               compression=compression)
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"),
            stats.at[row_idx].set(sub_s, mode="drop"))


@partial(jax.jit, static_argnames=("slots", "n_chunks", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_ranked_scan(means: Array, weights: Array,
                            row_ids: Array, ranks: Array,
                            values: Array, sample_weights: Array,
                            slots: int, n_chunks: int,
                            compression: float = DEFAULT_COMPRESSION
                            ) -> tuple[Array, Array]:
    """Deep-batch ingest in ONE dispatch: ranks may exceed ``slots``
    (up to slots * n_chunks); a lax.scan densifies and merges one
    slots-wide chunk per step on device.  Replaces the host-side
    k-scale precluster for global-tier imports (a 1.6M-centroid
    interval cost ~0.7s of lexsort/bincount on the single host core)
    AND the python-loop alternative of n_chunks separate dispatches
    (n_chunks-1 launches of overhead).  Accuracy is the chunked-merge semantics
    the ranked path already has (each chunk is a plain digest merge),
    not the precluster's lossier collapse-then-merge."""
    num_rows = means.shape[0]

    def step(carry, ci):
        m, w = carry
        rk = ranks - ci * slots
        live = (rk >= 0) & (rk < slots)
        rid = jnp.where(live, row_ids, num_rows)
        rk = jnp.clip(rk, 0, slots - 1)
        dense_v = jnp.zeros((num_rows, slots), jnp.float32).at[
            rid, rk].set(values, mode="drop")
        dense_w = jnp.zeros((num_rows, slots), jnp.float32).at[
            rid, rk].set(sample_weights, mode="drop")
        return _merge_impl(m, w, dense_v, dense_w,
                           compression=compression), None

    (m, w), _ = jax.lax.scan(
        step, (means, weights),
        jnp.arange(n_chunks, dtype=jnp.int32))
    return m, w


@partial(jax.jit, static_argnames=("slots", "n_chunks", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_ranked_scan_rows(means: Array, weights: Array,
                                 row_idx: Array, row_ids: Array,
                                 ranks: Array, values: Array,
                                 sample_weights: Array,
                                 slots: int, n_chunks: int,
                                 compression: float =
                                 DEFAULT_COMPRESSION
                                 ) -> tuple[Array, Array]:
    """add_samples_ranked_scan over a gathered row subset (see
    add_samples_ranked_rows): a deep import batch touching m of R
    rows merges compactly and scatters back, so the scan's per-chunk
    sort runs on m rows, not R."""
    num_sub = row_idx.shape[0]
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)

    def step(carry, ci):
        m, w = carry
        rk = ranks - ci * slots
        live = (rk >= 0) & (rk < slots)
        rid = jnp.where(live, row_ids, num_sub)
        rk = jnp.clip(rk, 0, slots - 1)
        dense_v = jnp.zeros((num_sub, slots), jnp.float32).at[
            rid, rk].set(values, mode="drop")
        dense_w = jnp.zeros((num_sub, slots), jnp.float32).at[
            rid, rk].set(sample_weights, mode="drop")
        return _merge_impl(m, w, dense_v, dense_w,
                           compression=compression), None

    (sub_m, sub_w), _ = jax.lax.scan(
        step, (sub_m, sub_w),
        jnp.arange(n_chunks, dtype=jnp.int32))
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"))


@partial(jax.jit, static_argnames=("slots", "n_chunks", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def merge_dense_scan(means: Array, weights: Array, plane_v: Array,
                     plane_w: Array, slots: int, n_chunks: int,
                     compression: float = DEFAULT_COMPRESSION
                     ) -> tuple[Array, Array]:
    """Deep-batch merge from a HOST-densified plane f32[R, n_chunks *
    slots] in one dispatch: lax.scan merges one slots-wide slice per
    step.  Unlike add_samples_ranked_scan there is no device scatter
    at all — each step is a pure slice + the cluster merge, which is
    what makes the deep path run at kernel speed (a 2M-element XLA
    scatter re-executed per chunk dominated the scan variant
    on-device)."""
    def step(carry, ci):
        m, w = carry
        dv = jax.lax.dynamic_slice_in_dim(plane_v, ci * slots, slots,
                                          axis=1)
        dw = jax.lax.dynamic_slice_in_dim(plane_w, ci * slots, slots,
                                          axis=1)
        return _merge_impl(m, w, dv, dw,
                           compression=compression), None

    (m, w), _ = jax.lax.scan(
        step, (means, weights),
        jnp.arange(n_chunks, dtype=jnp.int32))
    return m, w


@partial(jax.jit, static_argnames=("slots", "n_chunks", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def merge_dense_scan_rows(means: Array, weights: Array,
                          row_idx: Array, plane_v: Array,
                          plane_w: Array, slots: int, n_chunks: int,
                          compression: float = DEFAULT_COMPRESSION
                          ) -> tuple[Array, Array]:
    """merge_dense_scan over a gathered row subset (plane rows are
    the subset's rows; row_idx maps them back, padding row_idx ==
    num_rows drops)."""
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)

    def step(carry, ci):
        m, w = carry
        dv = jax.lax.dynamic_slice_in_dim(plane_v, ci * slots, slots,
                                          axis=1)
        dw = jax.lax.dynamic_slice_in_dim(plane_w, ci * slots, slots,
                                          axis=1)
        return _merge_impl(m, w, dv, dw,
                           compression=compression), None

    (sub_m, sub_w), _ = jax.lax.scan(
        step, (sub_m, sub_w),
        jnp.arange(n_chunks, dtype=jnp.int32))
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"))


@partial(jax.jit, static_argnames=("compression",),
         donate_argnums=jitopts.donate(0, 1))
def merge_wire_stack_rows(means: Array, weights: Array,
                          row_idx: Array, stack_m: Array,
                          stack_w: Array, live: Array,
                          compression: float = DEFAULT_COMPRESSION
                          ) -> tuple[Array, Array]:
    """Fused global merge: fold a stack of per-wire centroid planes
    f32[W, U, K] into the gathered row subset in ONE dispatch — a
    lax.scan over the wire axis whose body is the same _merge_impl
    (Pallas-fused when supported(cap, K) engages) the per-wire path
    runs, in the same order, so the result is bit-identical to W
    sequential per-wire merges of the same planes.

    ``live`` (bool[W]) masks padding wires: W is bucketed to bound
    compile variants, and a dead wire's step must be the IDENTITY via
    lax.cond — merging an all-empty batch is not a no-op (the k-scale
    cluster pass may still re-cluster adjacent centroids), so a
    jnp.where over an unconditional merge would corrupt parity."""
    sub_m = _take_rows(means, row_idx)
    sub_w = _take_rows(weights, row_idx)

    def step(carry, wire):
        m, w = carry
        wm, ww, alive = wire

        def do_merge(operands):
            m, w, wm, ww = operands
            return _merge_impl(m, w, wm, ww, compression=compression)

        def skip(operands):
            m, w, _, _ = operands
            return m, w

        return jax.lax.cond(alive, do_merge, skip,
                            (m, w, wm, ww)), None

    (sub_m, sub_w), _ = jax.lax.scan(step, (sub_m, sub_w),
                                     (stack_m, stack_w, live))
    return (means.at[row_idx].set(sub_m, mode="drop"),
            weights.at[row_idx].set(sub_w, mode="drop"))


def _combine_row_stats(stats: Array, batch_stats: Array) -> Array:
    """Elementwise fold of per-row batch aggregates (host-accumulated
    by vtpu_dense_plane) into the stats plane — columns follow
    segment.STAT_*; untouched rows carry the identity values
    (0, +F32_MAX, -F32_MAX, 0, 0) so no masking is needed."""
    return jnp.stack([
        stats[:, 0] + batch_stats[:, 0],
        jnp.minimum(stats[:, 1], batch_stats[:, 1]),
        jnp.maximum(stats[:, 2], batch_stats[:, 2]),
        stats[:, 3] + batch_stats[:, 3],
        stats[:, 4] + batch_stats[:, 4],
    ], axis=1)


@partial(jax.jit, static_argnames=("compression",),
         donate_argnums=jitopts.donate(0, 1, 2))
def ingest_plane_pre_unit(means: Array, weights: Array, stats: Array,
                          batch_stats: Array, counts: Array,
                          dense_v: Array,
                          compression: float = DEFAULT_COMPRESSION
                          ) -> tuple[Array, Array, Array]:
    """Histo plane ingest with the local aggregates PRE-computed on
    host (exact f32, every sample) — which frees the value plane to
    ship at float16 when the batch's range allows: the digest means
    absorb the ~0.05% quantization (far inside the 1% p99 budget)
    while min/max/sum stay exact.  Unit-weight variant."""
    w = dense_v.shape[1]
    dense_v = dense_v.astype(jnp.float32)
    dense_w = jnp.where(
        jnp.arange(w, dtype=jnp.int32)[None, :] < counts[:, None],
        1.0, 0.0).astype(jnp.float32)
    stats = _combine_row_stats(stats, batch_stats)
    m, wg = _merge_impl(means, weights, dense_v, dense_w,
                        compression=compression)
    return m, wg, stats


@partial(jax.jit, static_argnames=("compression",),
         donate_argnums=jitopts.donate(0, 1, 2))
def ingest_plane_pre(means: Array, weights: Array, stats: Array,
                     batch_stats: Array, dense_v: Array,
                     dense_w: Array,
                     compression: float = DEFAULT_COMPRESSION
                     ) -> tuple[Array, Array, Array]:
    """ingest_plane_pre_unit for weighted samples: the weight plane
    ships too (both planes f32 — the f16 gate applies only to
    unit-weight batches, see table._histo_plane_step)."""
    dense_v = dense_v.astype(jnp.float32)
    dense_w = dense_w.astype(jnp.float32)
    stats = _combine_row_stats(stats, batch_stats)
    m, wg = _merge_impl(means, weights, dense_v, dense_w,
                        compression=compression)
    return m, wg, stats


@partial(jax.jit, static_argnames=("slots", "compression"),
         donate_argnums=jitopts.donate(0, 1))
def add_samples_unit(means: Array, weights: Array, row_ids: Array,
                     values: Array, slots: int = 256,
                     compression: float = DEFAULT_COMPRESSION
                     ) -> tuple[Array, Array]:
    """add_samples specialised to unit sample weights (no sample-rate
    correction), synthesised on device so batches ship only
    (rows, values) — a third less host->device traffic on the timer hot
    path.  Padding entries MUST use row_id == num_rows: densify's
    scatter drops them, so the synthetic weight never lands."""
    num_rows = means.shape[0]
    ones = jnp.ones_like(values)
    dense_v, dense_w = densify(row_ids, values, ones, num_rows, slots)
    return _merge_impl(means, weights, dense_v, dense_w,
                       compression=compression)


def quantile(means: Array, weights: Array, qs: Array,
             mins: Array | None = None,
             maxs: Array | None = None,
             method: str = "interp") -> Array:
    """Estimate quantiles for every row -> f32[R, Q].

    ``method="interp"`` (default, used by the flush readout):
    rank-space linear interpolation between centroid means with the
    R-7 convention (numpy's default) — the mass of centroid i sits at
    0-based rank position ``cum_before_i + (w_i-1)/2`` and the target
    rank is ``q*(total-1)``.  On runs of singleton centroids (which is
    what the refined tail scale produces near p99, see _TAIL_MULT)
    this reproduces ``np.quantile(..)`` EXACTLY — the uniform-bounds
    scheme below is off by up to half an order-statistic gap there,
    which on heavy-tailed data is the entire 1%-max p99 budget.

    ``method="reference"`` implements the reference's scheme EXACTLY
    (tdigest/merging_digest.go:302 ``Quantile`` + :360
    ``centroidUpperBound``): each centroid is a uniform distribution
    over value-space bounds given by the midpoints to its neighbors'
    means, with the first lower bound = true min and the last upper
    bound = true max.  Matching the scheme (not just the sketch) keeps
    the "vs the Go t-digest" delta at zero for identical centroids.

    ``mins``/``maxs`` (f32[R]) are the per-row true extremes the Histo
    sampler tracks anyway (samplers/samplers.go:484); without them the
    extreme centroid means serve as the bounds.  Empty rows -> NaN.
    """
    if mins is None:
        mins = jnp.full((means.shape[0],), jnp.nan, jnp.float32)
    if maxs is None:
        maxs = jnp.full((means.shape[0],), jnp.nan, jnp.float32)
    if method == "reference":
        return _quantile(means, weights, qs, mins, maxs)
    return _quantile_interp(means, weights, qs, mins, maxs)


def _bounds(m: Array, w: Array, mins: Array, maxs: Array):
    """Sorted centroids + per-centroid value-space (lb, ub) per the
    reference's centroidUpperBound; returns (m, w, cum, lb, ub,
    nvalid, total)."""
    key = jnp.where(w > 0, m, jnp.inf)
    _, m, w = jax.lax.sort((key, m, w), dimension=-1, num_keys=1)
    cum = jnp.cumsum(w, axis=1)
    total = cum[:, -1:]
    nvalid = jnp.sum(w > 0, axis=1)
    last = jnp.maximum(nvalid - 1, 0)[:, None]

    last_m = jnp.take_along_axis(m, last, axis=1)
    first_m = m[:, :1]
    lo_anchor = jnp.where(jnp.isnan(mins)[:, None], first_m,
                          mins[:, None])
    hi_anchor = jnp.where(jnp.isnan(maxs)[:, None], last_m,
                          maxs[:, None])

    slot = jnp.arange(m.shape[1])[None, :]
    m_next = jnp.concatenate([m[:, 1:], m[:, -1:]], axis=1)
    ub = jnp.where(slot >= last, hi_anchor, 0.5 * (m + m_next))
    lb = jnp.concatenate([lo_anchor, ub[:, :-1]], axis=1)
    return m, w, cum, lb, ub, nvalid, total


@jax.jit
def _quantile(means: Array, weights: Array, qs: Array, mins: Array,
              maxs: Array) -> Array:
    m, w, cum, lb, ub, nvalid, total = _bounds(means, weights, mins,
                                               maxs)
    last = jnp.maximum(nvalid - 1, 0)[:, None]
    t = qs[None, :] * total  # [R, Q]
    # first centroid i with q <= cum_i  (strict-< count, as the
    # reference's walk); empty slots mask to +inf so they never count
    # below the target
    cum_masked = jnp.where(w > 0, cum, jnp.inf)
    idx = jnp.sum(cum_masked[:, None, :] < t[:, :, None], axis=-1)
    idx = jnp.clip(idx, 0, last)
    w_i = jnp.take_along_axis(w, idx, axis=1)
    cum_before = jnp.take_along_axis(cum - w, idx, axis=1)
    lb_i = jnp.take_along_axis(lb, idx, axis=1)
    ub_i = jnp.take_along_axis(ub, idx, axis=1)
    prop = jnp.clip((t - cum_before) / jnp.maximum(w_i, _EPS), 0.0, 1.0)
    est = lb_i + prop * (ub_i - lb_i)
    return jnp.where((nvalid[:, None] > 0) & (total > 0), est, jnp.nan)


@jax.jit
def _quantile_interp(means: Array, weights: Array, qs: Array,
                     mins: Array, maxs: Array) -> Array:
    """Rank-space centroid-mean interpolation (see quantile(),
    method="interp").  Knots: (-0.5, min), (pos_i, mean_i)...,
    (total-0.5, max) with pos_i = cum_i - (w_i+1)/2; target rank
    h = q*(total-1)."""
    key = jnp.where(weights > 0, means, jnp.inf)
    _, m, w = jax.lax.sort((key, means, weights), dimension=-1,
                           num_keys=1)
    cum = jnp.cumsum(w, axis=1)
    total = cum[:, -1:]
    nvalid = jnp.sum(w > 0, axis=1)
    last = jnp.maximum(nvalid - 1, 0)[:, None]
    pos = cum - (w + 1.0) * 0.5  # mass centre, 0-based rank space
    first_m = m[:, :1]
    last_m = jnp.take_along_axis(m, last, axis=1)
    lo_anchor = jnp.where(jnp.isnan(mins)[:, None], first_m,
                          mins[:, None])
    hi_anchor = jnp.where(jnp.isnan(maxs)[:, None], last_m,
                          maxs[:, None])

    h = qs[None, :] * jnp.maximum(total - 1.0, 0.0)  # [R, Q]
    # number of valid knots with pos < h  ->  knots idx-1, idx bracket h
    pos_masked = jnp.where(w > 0, pos, jnp.inf)
    idx = jnp.sum(pos_masked[:, None, :] < h[:, :, None], axis=-1)
    below = idx == 0           # h before the first knot
    above = idx > last         # h past the last knot
    idx_hi = jnp.clip(idx, 0, last)
    idx_lo = jnp.clip(idx - 1, 0, last)

    def take(a, i):
        return jnp.take_along_axis(a, i, axis=1)

    p_lo = jnp.where(below, -0.5, take(pos, idx_lo))
    v_lo = jnp.where(below, lo_anchor, take(m, idx_lo))
    p_hi = jnp.where(above, total - 0.5, take(pos, idx_hi))
    v_hi = jnp.where(above, hi_anchor, take(m, idx_hi))
    frac = jnp.clip((h - p_lo) / jnp.maximum(p_hi - p_lo, _EPS),
                    0.0, 1.0)
    est = v_lo + frac * (v_hi - v_lo)
    # exact anchors outside the knot range
    est = jnp.clip(est, lo_anchor, hi_anchor)
    return jnp.where((nvalid[:, None] > 0) & (total > 0), est, jnp.nan)


@jax.jit
def cdf(means: Array, weights: Array, xs: Array,
        mins: Array | None = None, maxs: Array | None = None) -> Array:
    """Fraction of weight below each value -> f32[R, X], using the same
    value-space uniform-centroid model as quantile (the inverse map;
    reference tdigest/merging_digest.go:266 ``CDF``)."""
    if mins is None:
        mins = jnp.full((means.shape[0],), jnp.nan, jnp.float32)
    if maxs is None:
        maxs = jnp.full((means.shape[0],), jnp.nan, jnp.float32)
    m, w, cum, lb, ub, nvalid, total = _bounds(means, weights, mins,
                                               maxs)
    last = jnp.maximum(nvalid - 1, 0)[:, None]
    x = xs[None, :]
    # first centroid whose upper bound exceeds x
    ub_masked = jnp.where(w > 0, ub, jnp.inf)
    idx = jnp.sum(ub_masked[:, None, :] <= x[:, :, None], axis=-1)
    idx = jnp.clip(idx, 0, last)
    w_i = jnp.take_along_axis(w, idx, axis=1)
    cum_before = jnp.take_along_axis(cum - w, idx, axis=1)
    lb_i = jnp.take_along_axis(lb, idx, axis=1)
    ub_i = jnp.take_along_axis(ub, idx, axis=1)
    span = ub_i - lb_i
    frac = jnp.clip(jnp.where(span > 0,
                              (x - lb_i) / jnp.maximum(span, _EPS),
                              1.0), 0.0, 1.0)
    out = (cum_before + w_i * frac) / jnp.maximum(total, _EPS)
    # outside the anchors: exact 0/1, as the reference returns
    lo_anchor = lb[:, :1]
    hi_anchor = jnp.take_along_axis(ub, last, axis=1)
    out = jnp.where(x <= lo_anchor, 0.0, out)
    out = jnp.where(x >= hi_anchor, 1.0, out)
    return jnp.where(nvalid[:, None] > 0, jnp.clip(out, 0.0, 1.0),
                     jnp.nan)


def merge_digests(means: Array, weights: Array, other_means: Array,
                  other_weights: Array,
                  compression: float = DEFAULT_COMPRESSION
                  ) -> tuple[Array, Array]:
    """Row-aligned union of two digest tables (global-tier merge).
    Non-donating: both input tables remain valid afterwards."""
    return _merge_no_donate(means, weights, other_means, other_weights,
                            compression=compression)


def total_weight(weights: Array) -> Array:
    return jnp.sum(weights, axis=1)
