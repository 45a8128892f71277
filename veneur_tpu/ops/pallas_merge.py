"""Fused t-digest merge as a single Pallas TPU kernel.

The XLA merge path (ops/tdigest._merge_impl) lowers to ~6 HBM passes
over the concatenated planes: a 3-operand ``lax.sort``, a cumulative
sum, the k-scale math, an 18M-element scatter-add (or the dfcumsum
scan variant), and a second pack sort.  On a v5e the scatter alone was
profiled at ~60% of the merge (round-2 note in ops/tdigest.py).  This
kernel does the whole per-row merge in VMEM in one pass:

  HBM read (means,weights) -> bitonic sort (lanes) -> log-step cumsum
  -> k-scale cluster ids -> run ends -> compensated prefix sums
  -> compact the run ends to the front -> difference -> HBM write

so the planes cross HBM exactly once each way and the serial scatter
disappears entirely.  Cluster semantics mirror _merge_impl exactly
(same scale constants are passed in by ops/tdigest so the two paths
can never drift): sort by mean with empty slots keyed to +inf,
``q_left`` from the cumulative weight, ``floor(k(q)-k(0))`` cluster
ids clipped to the plane capacity, weighted per-cluster means.  The q
cumsum runs in plain f32: it feeds only the cluster-id floor, where a
1e-7 relative error can at most move a boundary-straddling centroid
into the adjacent cluster (both assignments are valid t-digests).

After the sort the ids are non-decreasing along the lanes (a prefix
max makes that hold at f32 rounding too), so each cluster is one run
of lanes and its sums are double-float prefix sums (ops/tdigest
``_df_add``) differenced at consecutive run ends (``_df_diff``): the
compensation keeps a weight-1 tail cluster exact beside a heavy bulk,
where a plain f32 difference was measured to corrupt p999.  The run
ends are compacted to the front by a log-step shift network, so the
clusters come out packed and in ascending mean with no second sort.

Bitonic compare-exchange, the scans and the compaction use static
slice+concat rotations only (no dynamic gathers, no lane reshapes,
no transposes, no matmuls), which Mosaic lowers without relayout
surprises.

This is the third merge strategy, selected with VENEUR_TPU_MERGE=
pallas and the "auto" default on TPU backends (see
ops/tdigest._MERGE_MODE).  It handles combined plane widths up to
_MAX_WIDTH = 2048, which covers every shape the table emits: the
timer ingest chunks (616 + up to 512 slots), and the global tier's
digest-vs-digest union (616 + 616); only genuinely wider calls fall
back to the XLA path.

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

from veneur_tpu.ops.tdigest import _df_add, _df_diff

Array = jax.Array

_BLOCK_ROWS = 8      # f32 sublane tile; rows per grid step
_MAX_WIDTH = 2048    # pow2 sort width bound: the table's widest
#                      shapes (616 + 512, 616 + 616) sort at 2048
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


def _shift_in(x: Array, s: int, fill) -> Array:
    """x[i] <- x[i-s] along lanes, the first s lanes ``fill`` (static s)."""
    return jnp.concatenate([jnp.full_like(x[:, :s], fill), x[:, :-s]],
                           axis=1)


def _scan_lanes(x, n: int, op, fill=0):
    """Hillis-Steele inclusive scan along lanes (log2(n) steps) of an
    associative ``op`` whose identity is ``fill``; ``x`` may be a tuple
    of planes that ``op`` combines as one (a double-float pair)."""
    s = 1
    while s < n:
        x = op(jax.tree.map(lambda a: _shift_in(a, s, fill), x), x)
        s <<= 1
    return x


def _compact(sel: Array, vals: list, n: int) -> tuple[Array, list]:
    """Move the ``sel`` lanes of each plane in ``vals`` to the front,
    in order: a lane goes left by the count of unselected lanes before
    it, one bit of that distance a step from the least significant.
    The distances do not decrease along the selected lanes, so no two
    ever land on one lane, and a mover never wraps (its target is >=
    0).  Static rotations and selects only.  Returns the occupancy of
    the result (a prefix) and the moved planes; lanes past it hold
    stale values."""
    li = jax.lax.broadcasted_iota(jnp.int32, sel.shape, 1)
    one = jnp.where(sel, 1, 0)
    before = _scan_lanes(one, n, jnp.add) - one
    # n marks a lane that carries nothing: no bit below n is set
    d = jnp.where(sel, li - before, n)
    s = 1
    while s < n:
        rd = _rot_left(d, s)
        take = (rd & s) != 0
        d = jnp.where(take, rd, jnp.where((d & s) != 0, n, d))
        vals = [jnp.where(take, _rot_left(v, s), v) for v in vals]
        s <<= 1
    return d < n, vals


@functools.lru_cache(maxsize=None)
def _build(cap: int, batch_width: int, num_rows: int, delta: float,
           tail_coeff: float, tail_q0: float, tail_qmin: float,
           interpret: bool):
    """Compile the fused merge for one (shape, scale) configuration.

    ``delta`` is the internal scale (tdigest._SCALE_MULT *
    compression); ``tail_coeff`` is _TAIL_MULT * compression (0 with
    the refinement gated off).  Scale constants arrive as arguments so
    they are part of the compiled kernel's cache key.
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
        live = w > 0               # a prefix of the lanes after the sort
        m = jnp.where(live, key, 0.0)

        cum = _scan_lanes(w, n, jnp.add)
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
        # non-decreasing by construction (neither the asin polynomial
        # nor the f32 cumsum is proven monotone at rounding), so every
        # cluster is one run of lanes and there are at most cap runs
        cluster = _scan_lanes(cluster, n, jnp.maximum)

        # a run ends at a live lane whose next lane is empty or holds
        # another id; its sums are prefix sums differenced at run ends,
        # compensated (a plain f32 cumsum difference loses the tail
        # clusters' contents against the bulk, ops/tdigest.py)
        li = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        end = live & ((li == n - 1) | (_rot_left(w, 1) <= 0) |
                      (_rot_left(cluster, 1) != cluster))
        zero = jnp.zeros_like(w)
        cw = _scan_lanes((w, zero), n, _df_add)
        cwm = _scan_lanes((w * m, zero), n, _df_add)
        occ, (wh, wl, mh, ml) = _compact(end, [*cw, *cwm], n)

        def prev(x):
            return _shift_in(x, 1, 0.0)

        out_w = _df_diff((wh, wl), (prev(wh), prev(wl)))
        out_wm = _df_diff((mh, ml), (prev(mh), prev(ml)))
        out_m = out_wm / jnp.maximum(out_w, _EPS)
        # a value that straddles a boundary can round one cluster's
        # mean an ulp past the next one's: keep the ascending contract
        out_m = _scan_lanes(jnp.where(occ, out_m, -jnp.inf), n,
                            jnp.maximum, -jnp.inf)
        om_ref[:] = jnp.where(occ, out_m, 0.0)
        ow_ref[:] = jnp.where(occ, out_w, 0.0)

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
