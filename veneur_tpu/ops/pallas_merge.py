"""Fused t-digest merge as a single Pallas TPU kernel.

The XLA merge path (ops/tdigest._merge_impl) lowers to ~6 HBM passes
over the concatenated planes: a 3-operand ``lax.sort``, a cumulative
sum, the k-scale math, an 18M-element scatter-add (or the dfcumsum
scan variant), and a second pack sort.  On a v5e the scatter alone was
profiled at ~60% of the merge (round-2 note in ops/tdigest.py).  This
kernel does the whole per-row merge in VMEM in one pass:

  HBM read (means,weights) -> bitonic sort (lanes) -> log-step cumsum
  -> k-scale cluster ids -> per-row one-hot matmul segment sums (MXU)
  -> compact (second bitonic) -> HBM write

so the planes cross HBM exactly once each way and the serial scatter
disappears entirely.  Cluster semantics mirror _merge_impl exactly
(same scale constants are passed in by ops/tdigest so the two paths
can never drift): sort by mean with empty slots keyed to +inf,
``q_left`` from the cumulative weight, ``floor(k(q)-k(0))`` cluster
ids clipped to the plane capacity, weighted per-cluster means.  The
only numeric difference is the q cumsum running in plain f32 (the XLA
scatter path sums clusters in scatter order; dfcumsum compensates a
boundary-difference scheme).  Here per-cluster sums are DIRECT masked
dot products — each weight is summed exactly once into its own
cluster, so no compensation is needed; the f32 cumsum feeds only the
cluster-id floor, where a 1e-7 relative error can at most move a
boundary-straddling centroid into the adjacent cluster (both
assignments are valid t-digests).

Bitonic compare-exchange and the Hillis-Steele cumsum use static
slice+concat rotations only (no dynamic gathers, no lane reshapes),
which Mosaic lowers without relayout surprises; the one transpose per
row (cluster ids to the sublane axis for the one-hot mask) is what
buys the MXU segment reduction.

This is the third merge strategy, selected with VENEUR_TPU_MERGE=
pallas and the "auto" default on TPU backends (see
ops/tdigest._MERGE_MODE).  It handles combined plane widths up to
_MAX_WIDTH = 2048, which covers every shape the table emits: the
timer ingest chunks (616 + up to 512 slots), and the global tier's
digest-vs-digest union (616 + 616).  The one-hot mask is built in
column chunks of _MASK_CHUNK so VMEM holds N x 512, not N^2; only
genuinely wider calls fall back to the XLA path.

Reference analog: tdigest/merging_digest.go:140 ``mergeAllTemps`` /
:229 ``mergeOne`` — the serial greedy pass this kernel replaces with
a data-parallel construction (t-digest paper, arXiv:1902.04023,
cluster-by-k-index family).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

_BLOCK_ROWS = 8      # f32 sublane tile; rows per grid step
_MAX_WIDTH = 2048    # pow2 sort width bound (mask is column-chunked,
#                      so VMEM holds N*_MASK_CHUNK, not N*N)
_MASK_CHUNK = 512    # one-hot mask column chunk (N x 512 bf16 = 2 MB)
_EPS = 1e-30

# Interpret-mode gate for CPU testing: the kernel runs through the
# Pallas interpreter (pure jax ops) instead of Mosaic.  The driver's
# CPU mesh and the test suite use this; on a real TPU leave it unset.
_INTERPRET = os.environ.get(
    "VENEUR_TPU_PALLAS_INTERPRET", "").lower() in ("1", "true", "on")


def _pow2_at_least(w: int) -> int:
    n = 8
    while n < w:
        n <<= 1
    return n


def supported(cap: int, batch_width: int) -> bool:
    """Whether the fused kernel handles this (state, batch) shape."""
    return _pow2_at_least(cap + batch_width) <= _MAX_WIDTH


def max_batch_slots(cap: int) -> int:
    """Largest incoming-batch width that keeps a merge against a
    ``cap``-slot state inside the fused kernel's bound — the table
    caps its ingest chunk width to this on TPU backends so every
    digest merge stays fused (an oversized chunk silently falls back
    to the scatter path, measured ~4x slower on device).  May be <= 0
    for capacities beyond the kernel's reach (exotic compressions):
    callers must NOT cap chunks then — micro-chunking a merge that
    falls back to scatter anyway only multiplies dispatches."""
    return _MAX_WIDTH - cap


def _rot_left(x: Array, j: int) -> Array:
    """x[i] <- x[i+j] cyclically along lanes (static j)."""
    return jnp.concatenate([x[:, j:], x[:, :j]], axis=1)


def _rot_right(x: Array, j: int) -> Array:
    return jnp.concatenate([x[:, -j:], x[:, :-j]], axis=1)


def _bitonic(key: Array, w: Array, n: int) -> tuple[Array, Array]:
    """Ascending bitonic sort of ``key`` along lanes, co-moving ``w``.

    Partner of lane i at stride j is i^j; for j a power of two that is
    a +/-j rotation selected by bit j of the lane index, so every
    stage is static slices + selects (no gathers).  Swap decisions are
    made from the PAIR's perspective (key at the low index vs the high
    index), so both elements of a pair always agree — including ties,
    which never swap.
    """
    li = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            low_half = (li & j) == 0   # lane is the pair's low index
            pk = jnp.where(low_half, _rot_left(key, j),
                           _rot_right(key, j))
            pw = jnp.where(low_half, _rot_left(w, j),
                           _rot_right(w, j))
            key_low = jnp.where(low_half, key, pk)
            key_high = jnp.where(low_half, pk, key)
            ascending = (li & k) == 0
            # logical combine, not a where-select: Mosaic can't
            # truncate the i8 a bool-select round-trips through
            swap = ((ascending & (key_low > key_high)) |
                    (~ascending & (key_low < key_high)))
            key = jnp.where(swap, pk, key)
            w = jnp.where(swap, pw, w)
            j //= 2
        k *= 2
    return key, w


def _asin(x: Array) -> Array:
    """arcsin on [-1, 1] — Mosaic has no asin lowering, so this is the
    Hastings polynomial (Abramowitz-Stegun 4.4.45, |err| < 2e-8):
    asin(|x|) = pi/2 - sqrt(1-|x|) * poly(|x|), odd-extended.  At the
    digest's internal scale (delta ~ 600) a 2e-8 asin error moves a
    cluster boundary by ~2e-6 of a cluster width — far below the f32
    cumsum noise the clustering already tolerates."""
    ax = jnp.abs(x)
    p = jnp.float32(-0.0012624911)
    for c in (0.0066700901, -0.0170881256, 0.0308918810,
              -0.0501743046, 0.0889789874, -0.2145988016,
              1.5707963050):
        p = p * ax + jnp.float32(c)
    half = jnp.float32(jnp.pi / 2)
    r = half - jnp.sqrt(jnp.maximum(1.0 - ax, 0.0)) * p
    return jnp.where(x < 0, -r, r)


def _cumsum_lanes(w: Array, n: int) -> Array:
    """Hillis-Steele inclusive prefix sum along lanes (log2(n) adds)."""
    c = w
    s = 1
    while s < n:
        shifted = jnp.concatenate(
            [jnp.zeros_like(c[:, :s]), c[:, :-s]], axis=1)
        c = c + shifted
        s <<= 1
    return c


@functools.lru_cache(maxsize=None)
def _build(cap: int, batch_width: int, num_rows: int, delta: float,
           tail_coeff: float, tail_q0: float, tail_qmin: float,
           interpret: bool):
    """Compile the fused merge for one (shape, scale) configuration.

    ``delta`` is the internal scale (tdigest._SCALE_MULT *
    compression); ``tail_coeff`` is _TAIL_MULT * compression (0 with
    the refinement gated off).  Scale constants arrive as arguments so
    this module never imports ops/tdigest (which imports us).
    """
    n = _pow2_at_least(cap + batch_width)
    if n > _MAX_WIDTH:
        raise ValueError(f"width {cap}+{batch_width} > {_MAX_WIDTH}")
    if num_rows % _BLOCK_ROWS:
        raise ValueError(f"rows {num_rows} not a multiple of "
                         f"{_BLOCK_ROWS} (wrapper pads)")
    b = _BLOCK_ROWS
    k0 = -delta / 4.0  # k(0): asin(-1) body, tail term clamps to 0

    def kernel(m_ref, w_ref, om_ref, ow_ref):
        m = m_ref[:]
        w = w_ref[:]
        key = jnp.where(w > 0, m, jnp.inf)
        key, w = _bitonic(key, w, n)
        m = jnp.where(w > 0, key, 0.0)

        cum = _cumsum_lanes(w, n)
        total = jnp.sum(w, axis=1, keepdims=True)
        q = (cum - w) / jnp.maximum(total, _EPS)
        body = (delta / (2.0 * jnp.pi)) * _asin(
            jnp.clip(2.0 * q - 1.0, -1.0, 1.0))
        if tail_coeff > 0.0:
            tail = tail_coeff * jnp.log(
                tail_q0 / jnp.clip(1.0 - q, tail_qmin, None))
            kv = body + jnp.maximum(tail, 0.0) - k0
        else:
            kv = body - k0
        cluster = jnp.clip(jnp.floor(kv), 0, cap - 1).astype(jnp.int32)

        wm = w * m
        chunk = min(_MASK_CHUNK, n)
        # cluster ids are < cap, so only the chunks covering [0, cap)
        # can receive weight; lanes past them stay zero
        live_chunks = -(-cap // chunk)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

        def _dot_exact(vec: Array, mask_b16: Array) -> Array:
            # the TPU dot runs bf16 x bf16 -> f32; a plain cast of the
            # weight vector quantizes it (~0.2% rel — measured to push
            # quantile deltas to 5.8e-2 on device), while f32 HIGHEST
            # precision OOMs VMEM on the unrolled f32 masks.  The
            # 0/1 mask is EXACT in bf16, so splitting only the vector
            # into hi+lo bf16 terms gives ~2^-16 relative accuracy
            # for two MXU passes and half the mask footprint.
            hi = vec.astype(jnp.bfloat16)
            lo = (vec - hi.astype(jnp.float32)).astype(jnp.bfloat16)
            return (jnp.dot(hi, mask_b16,
                            preferred_element_type=jnp.float32) +
                    jnp.dot(lo, mask_b16,
                            preferred_element_type=jnp.float32))
        rows_w = []
        rows_wm = []
        tail_w = n - live_chunks * chunk
        tail = ([jnp.zeros((1, tail_w), jnp.float32)] if tail_w
                else [])
        for i in range(b):
            # cluster ids to the sublane axis -> one-hot matmul puts
            # the segment reduction on the MXU: out[c] = sum_i w[i] *
            # (cluster[i] == c), each weight counted exactly once.
            # The mask is built per column chunk so VMEM holds
            # (n, chunk), not (n, n) — what bounds _MAX_WIDTH.
            cl_t = jnp.swapaxes(cluster[i:i + 1, :], 0, 1)  # (n, 1)
            pw = []
            pwm = []
            for c0 in range(live_chunks):
                mask = (cl_t == (col + c0 * chunk)).astype(
                    jnp.bfloat16)                           # (n, chunk)
                pw.append(_dot_exact(w[i:i + 1, :], mask))
                pwm.append(_dot_exact(wm[i:i + 1, :], mask))
            rows_w.append(jnp.concatenate(pw + tail, axis=1))
            rows_wm.append(jnp.concatenate(pwm + tail, axis=1))
        out_w = jnp.concatenate(rows_w, axis=0)
        out_wm = jnp.concatenate(rows_wm, axis=0)
        out_m = jnp.where(out_w > 0,
                          out_wm / jnp.maximum(out_w, _EPS), 0.0)

        # compact: occupied clusters (ids < cap) to the front, mean-
        # sorted — the same contract as _merge_impl's pack sort
        key2 = jnp.where(out_w > 0, out_m, jnp.inf)
        key2, out_w = _bitonic(key2, out_w, n)
        om_ref[:] = jnp.where(out_w > 0, key2, 0.0)
        ow_ref[:] = out_w

    grid = (num_rows // b,)
    spec = pl.BlockSpec((b, n), lambda r: (r, 0),
                        memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((num_rows, n), jnp.float32),
                   jax.ShapeDtypeStruct((num_rows, n), jnp.float32)],
        interpret=interpret,
        # what a device trace calls this kernel: state capacity and
        # incoming width are its static shape (rows are the grid)
        name=f"tdigest_merge_c{cap}_k{batch_width}",
    )

    def merge(m_all: Array, w_all: Array) -> tuple[Array, Array]:
        om, ow = call(m_all, w_all)
        return om[:, :cap], ow[:, :cap]

    return merge


def merge_planes(means: Array, weights: Array, new_means: Array,
                 new_weights: Array, *, delta: float, tail_coeff: float,
                 tail_q0: float, tail_qmin: float,
                 interpret: bool | None = None
                 ) -> tuple[Array, Array]:
    """Drop-in replacement for the XLA cluster-merge: state planes
    f32[R, C] + incoming f32[R, K] -> merged f32[R, C], packed and
    mean-sorted.  Pads R to the row-block multiple and the width to
    the sort's power of two outside the kernel (one fused XLA pad —
    HBM-cheap next to the passes the kernel eliminates)."""
    num_rows, cap = means.shape
    k_in = new_means.shape[1]
    n = _pow2_at_least(cap + k_in)
    rows_pad = (-num_rows) % _BLOCK_ROWS
    m_all = jnp.concatenate([means, new_means], axis=1)
    w_all = jnp.concatenate([weights, new_weights], axis=1)
    pad = ((0, rows_pad), (0, n - cap - k_in))
    m_all = jnp.pad(m_all, pad)
    w_all = jnp.pad(w_all, pad)
    fn = _build(cap, k_in, num_rows + rows_pad, float(delta),
                float(tail_coeff), float(tail_q0), float(tail_qmin),
                _INTERPRET if interpret is None else interpret)
    om, ow = fn(m_all, w_all)
    if rows_pad:
        om = om[:num_rows]
        ow = ow[:num_rows]
    return om, ow
