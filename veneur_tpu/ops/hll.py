"""HyperLogLog register-plane kernels (p=14, LogLog-Beta estimator).

The reference's Set sampler wraps axiomhq/hyperloglog (sparse->dense
2^14-register sketch, samplers/samplers.go:367-430).  Here every set
series is one dense row of a ``u8[num_rows, 16384]`` register plane in
HBM:

- insert  = scatter-max of (register index, rank) pairs
- union   = elementwise maximum of planes (reference Merge,
  samplers/samplers.go:423)
- estimate = LogLog-Beta over register histograms (reference
  hyperloglog.go:206-226 Estimate), evaluated for all rows at once

Sparse representation is deliberately dropped: 16 KiB/row is cheap in
HBM, the dense form makes union a pure vector op, and the cross-chip
global merge becomes an elementwise-max collective.

Member hashing to (index, rank) happens host-side
(veneur_tpu.utils.hashing.hash_members) so the device never touches
strings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from veneur_tpu.utils.hashing import HLL_P

Array = jax.Array

P = HLL_P  # single source of truth shared with the host hash split
M = 1 << P  # 16384 registers, ~0.81% standard error

# LogLog-Beta bias-correction polynomial for p=14 — published constants
# from the LogLog-Beta paper (arXiv:1612.02284), as used by the
# reference's vendored estimator (hyperloglog/utils.go beta14).
_BETA14 = (-0.370393911, 0.070471823, 0.17393686, 0.16339839,
           -0.09237745, 0.03738027, -0.005384159, 0.00042419)

_ALPHA = 0.7213 / (1.0 + 1.079 / M)


def empty_state(num_rows: int) -> Array:
    return jnp.zeros((num_rows, M), dtype=jnp.uint8)


def insert(regs: Array, row_ids: Array, reg_idx: Array,
           ranks: Array) -> Array:
    """Scatter-max a batch of hashed members into their rows.

    regs: u8[R, M]; row_ids, reg_idx: i32[N]; ranks: i32[N] (1..51).
    Padding uses row_id == R (dropped).
    """
    return regs.at[row_ids, reg_idx].max(ranks.astype(regs.dtype),
                                         mode="drop")


def insert_packed(regs: Array, row_ids: Array, packed: Array) -> Array:
    """Scatter-max with (index, rank) packed into one i32 per member:
    ``packed = (reg_idx << 6) | rank`` (rank <= 51 < 64 for p=14, so 6
    bits always hold it).  Halves host->device bytes per set sample —
    the ingest link, not the scatter, is the set path's bottleneck.
    """
    reg_idx = packed >> 6
    ranks = packed & 0x3F
    return regs.at[row_ids, reg_idx].max(ranks.astype(regs.dtype),
                                         mode="drop")


def pack_positions(reg_idx, ranks):
    """Host-side packing matching insert_packed's layout."""
    import numpy as np
    return ((np.asarray(reg_idx, np.int32) << 6) |
            np.asarray(ranks, np.int32))


def union(a: Array, b: Array) -> Array:
    """HLL union is register-wise maximum (same-shape planes)."""
    return jnp.maximum(a, b)


def merge_rows(regs: Array, row_ids: Array, incoming: Array) -> Array:
    """Merge forwarded register rows (u8[K, M]) into table rows — the
    global tier's Set.Merge (samplers/samplers.go:423)."""
    return regs.at[row_ids].max(incoming, mode="drop")


def estimate_np(plane) -> "np.ndarray":
    """LogLog-Beta estimate over a HOST register plane (u8[R, M]) —
    the same formula as ``estimate``, evaluated with numpy.

    When an interval's set traffic was folded entirely into the host
    staging plane (see MetricTable._hll_host_fold) there is nothing
    device-resident to merge with, and shipping 16 KiB/row to the
    device just to run a row reduction costs more than the
    reduction.  The device
    ``estimate`` remains the path whenever registers live in HBM
    (global-tier imports, multi-chip meshes)."""
    import numpy as np
    ez = (plane == 0).sum(axis=-1).astype(np.float64)
    # exp2(-rank) via a 64-entry table: ranks are <= 51 for p=14.
    # Row-chunked so the float64 temp stays ~8 MiB regardless of
    # plane size (one-shot lut[plane] would spike 8x the plane).
    lut = np.exp2(-np.arange(64, dtype=np.float64))
    inv_sum = np.empty(plane.shape[0], np.float64)
    step = max(1, (8 << 20) // (M * 8))
    for i in range(0, plane.shape[0], step):
        inv_sum[i:i + step] = lut[plane[i:i + step]].sum(axis=-1)
    return estimate_from_stats(ez, inv_sum)


def estimate_from_stats(ez, inv_sum) -> "np.ndarray":
    """LogLog-Beta estimate from per-row sufficient statistics
    (ez = zero-register count, inv_sum = sum_j 2^-reg_j) — either a
    fresh plane rescan (estimate_np) or the running values maintained
    by the native fold (vtpu_hll_plane_stats).  The fold-maintained
    path is O(rows) at flush, which is what lets a set-heavy
    interval's estimate cost vanish from the single-core host
    budget."""
    import numpy as np
    ez = np.asarray(ez, np.float64)
    inv_sum = np.asarray(inv_sum, np.float64)
    zl = np.log(ez + 1.0)
    beta = _BETA14[0] * ez
    zp = zl.copy()
    for c in _BETA14[1:]:
        beta = beta + c * zp
        zp = zp * zl
    return (_ALPHA * M * (M - ez) / (inv_sum + beta)).astype(
        np.float32)


def estimate(regs: Array) -> Array:
    """LogLog-Beta cardinality estimate per row -> f32[R].

    est = alpha * m * (m - ez) / (sum_j 2^-reg_j + beta(ez))
    where ez is the zero-register count (hyperloglog.go:206-226).
    """
    r = regs.astype(jnp.float32)
    ez = jnp.sum(regs == 0, axis=-1).astype(jnp.float32)
    inv_sum = jnp.sum(jnp.exp2(-r), axis=-1)
    zl = jnp.log(ez + 1.0)
    beta = _BETA14[0] * ez
    zp = zl
    for c in _BETA14[1:]:
        beta = beta + c * zp
        zp = zp * zl
    m = jnp.float32(M)
    return _ALPHA * m * (m - ez) / (inv_sum + beta)
