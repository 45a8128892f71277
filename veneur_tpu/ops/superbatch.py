"""Superbatch apply: one H2D transfer + one fused dispatch per cycle.

The per-class apply path (core/table._apply_work) pays one
``jnp.asarray`` host->device transfer and one jitted dispatch per
metric class per staged batch — counter dense add, gauge last-write,
histo ranked merge and HLL scatter each launch separately, so
per-dispatch overhead and serialized transfers dominate exactly where
batched single-pass updates win (HLL accelerator ports batch register
updates for the same reason; the t-digest merge literature leans on
one buffered merge per cycle).  Here the whole cycle's detached
staging packs into ONE fixed-schema host buffer of int32 words:

  header (8 words: magic, total, per-class word offsets)
  counter   f32[counter_rows]            dense deltas (bitcast)
  gauge     f32[gauge_rows] + i32 mask   last-writes + touched mask
  histo     i32 rows + i32 rank + f32 vals (+ f32 wts) (+ i32 idx)
  set POS   i32 rows + i32 packed        (index << 6 | rank) positions
  set PLANE i32 idx + u8[T,16384]        compact touched-row registers

Every segment is padded to the same pow-2(+half-step) bucket ladder
the per-class path uses, with the SAME pad sentinels, so the fused
step's scatters see bit-identical operands to the per-class oracle.
Segment offsets are static Python ints derived from the ``SBSpec``
(the jit's static arg), so slicing compiles to fixed-offset views; the
in-buffer header exists for host-side debugging/dump tooling, not for
the kernel.  f32 segments ship bitcast inside the i32 buffer
(``lax.bitcast_convert_type`` round-trips exactly; byte order matches
numpy ``.view``), and the u8 register plane rides as M/4 words per row.

The fused step updates all four class planes in one dispatch.  The
histo arm inlines the SAME ``tdigest.ingest_ranked*`` entry points the
per-class path dispatches (inner jits inline bit-identically), so the
Pallas merge arm engages on TPU through the existing
``pallas_merge`` auto-resolution with no superbatch-specific kernel.
The set arm is either the packed scatter (``hll.insert_packed``, the
per-class oracle's exact operands) or — when the touched-row compact
plane is the cheaper device op — a row-granular register max
(``hll.merge_rows``) over a host-folded plane.  Scatter-max and
segment-sum are order-free, so both arms are register-bit-identical.

Double-buffering: two host staging buffers alternate per cycle, so
packing cycle N+1 never writes the buffer cycle N's transfer may still
be reading while the device computes (the same async-dispatch overlap
the readback path exploits).
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from veneur_tpu import observe
from veneur_tpu.ops import hll, segment, tdigest
from veneur_tpu.utils import jitopts

_MAGIC = 0x53425631  # "SBV1"
HEADER_WORDS = 8


def mode() -> str:
    """VENEUR_TPU_SUPERBATCH gate: "on", "off", or "auto" (resolves
    on — the fused step is profitable on every backend because the
    per-class oracle stays available for the shapes it wins)."""
    raw = os.environ.get("VENEUR_TPU_SUPERBATCH", "auto").lower()
    if raw in ("0", "false", "off"):
        return "off"
    if raw in ("1", "true", "on"):
        return "on"
    return "auto"


def enabled() -> bool:
    return mode() != "off"


def plane_scatter_factor(platform: str) -> int:
    """How many plane bytes one scatter byte is worth when choosing
    the set arm.  XLA's CPU scatter costs ~200ns/update (measured:
    1M packed positions take ~210ms vs ~5ms for the equivalent
    vector max over a 16 MiB plane), so the compact-plane arm wins
    even when the plane is an order of magnitude more bytes.  On
    accelerators the link is the bottleneck, so bytes compare 1:1."""
    return 16 if platform == "cpu" else 1


class SBSpec(NamedTuple):
    """Static (hashable) superbatch schema: segment lengths and the
    histo merge variant.  A zero length means the class is absent
    this cycle and its plane passes through untouched."""

    counter_rows: int = 0
    gauge_rows: int = 0
    histo_n: int = 0       # bucketed sample count
    histo_slots: int = 0   # merge chunk width for this batch
    histo_sub: int = 0     # bucketed touched-row count; 0 = global rows
    histo_unit: bool = False
    histo_stats: bool = False
    compression: float = 0.0
    pos_n: int = 0         # bucketed member count (packed-scatter arm)
    plane_rows: int = 0    # plane segment rows (plane arm)
    plane_full: bool = False  # plane covers the whole pool: union,
    #                           no idx segment (row scatter is the
    #                           expensive op on CPU XLA, elementwise
    #                           max is not)


def layout(spec: SBSpec) -> dict[str, int]:
    """Word offset of every segment (and "total"), derived statically
    from the spec.  Order matches the module docstring schema."""
    o = HEADER_WORDS
    out = {}
    out["counter"] = o
    o += spec.counter_rows
    out["gauge_dense"] = o
    o += spec.gauge_rows
    out["gauge_mask"] = o
    o += spec.gauge_rows
    out["histo_rows"] = o
    o += spec.histo_n
    out["histo_rank"] = o
    o += spec.histo_n
    out["histo_vals"] = o
    o += spec.histo_n
    out["histo_wts"] = o
    o += 0 if spec.histo_unit else spec.histo_n
    out["histo_idx"] = o
    o += spec.histo_sub
    out["pos_rows"] = o
    o += spec.pos_n
    out["pos_pk"] = o
    o += spec.pos_n
    out["plane_idx"] = o
    o += 0 if spec.plane_full else spec.plane_rows
    out["plane_regs"] = o
    o += spec.plane_rows * (hll.M // 4)
    out["total"] = o
    return out


def fill_header(buf: np.ndarray, spec: SBSpec,
                off: dict[str, int]) -> None:
    """Self-describing header for host-side dump tooling (the kernel
    slices by static offsets and never reads it)."""
    buf[0] = _MAGIC
    buf[1] = off["total"]
    buf[2] = off["counter"]
    buf[3] = off["gauge_dense"]
    buf[4] = off["histo_rows"]
    buf[5] = off["pos_rows"]
    buf[6] = off["plane_idx"]
    buf[7] = 0


class DoubleBuffer:
    """Two alternating grow-only host staging buffers: take() hands
    back a view of the slot the device is NOT (possibly still)
    transferring from, so packing cycle N+1 overlaps compute of
    cycle N without aliasing cycle N's in-flight buffer."""

    def __init__(self):
        self._slots: list[np.ndarray | None] = [None, None]
        self._i = 0

    def take(self, words: int) -> np.ndarray:
        i = self._i
        self._i ^= 1
        buf = self._slots[i]
        if buf is None or len(buf) < words:
            cap = max(1024, 1 << (max(words, 1) - 1).bit_length())
            buf = np.empty(cap, np.int32)
            self._slots[i] = buf
        return buf[:words]


def _fused(spec: SBSpec, counters, gauges, means, weights, stats,
           regs, buf):
    """The one fused step.  All offsets are static; f32/u8 segments
    are bitcast views of the int32 buffer.  Absent classes pass
    their planes through untouched (the caller skips reassignment).
    Each class's operations sit in a ``sb.<class>`` scope, which a
    device trace shows in every operation's ``op_name``."""
    off = layout(spec)

    def seg(name: str, n: int):
        o = off[name]
        return buf[o:o + n]

    def f32(name: str, n: int):
        return lax.bitcast_convert_type(seg(name, n), jnp.float32)

    if spec.counter_rows:
        with jax.named_scope("sb.counter"):
            counters = segment.counter_dense_update(
                counters, f32("counter", spec.counter_rows))
    if spec.gauge_rows:
        with jax.named_scope("sb.gauge"):
            gauges = segment.gauge_dense_update(
                gauges, f32("gauge_dense", spec.gauge_rows),
                seg("gauge_mask", spec.gauge_rows).astype(bool))
    if spec.histo_n:
        with jax.named_scope("sb.histo"):
            rows = seg("histo_rows", spec.histo_n)
            rank = seg("histo_rank", spec.histo_n)
            vals = f32("histo_vals", spec.histo_n)
            sub = spec.histo_sub > 0
            pre = (seg("histo_idx", spec.histo_sub),) if sub else ()
            kw = dict(slots=spec.histo_slots,
                      compression=spec.compression)
            if spec.histo_stats:
                if spec.histo_unit:
                    fn = (tdigest.ingest_ranked_unit_rows if sub
                          else tdigest.ingest_ranked_unit)
                    means, weights, stats = fn(
                        means, weights, stats, *pre, rows, rank, vals,
                        **kw)
                else:
                    fn = (tdigest.ingest_ranked_rows if sub
                          else tdigest.ingest_ranked)
                    means, weights, stats = fn(
                        means, weights, stats, *pre, rows, rank, vals,
                        f32("histo_wts", spec.histo_n), **kw)
            elif spec.histo_unit:
                fn = (tdigest.add_samples_ranked_unit_rows if sub
                      else tdigest.add_samples_ranked_unit)
                means, weights = fn(means, weights, *pre, rows, rank,
                                    vals, **kw)
            else:
                fn = (tdigest.add_samples_ranked_rows if sub
                      else tdigest.add_samples_ranked)
                means, weights = fn(means, weights, *pre, rows, rank,
                                    vals, f32("histo_wts", spec.histo_n),
                                    **kw)
    if spec.pos_n:
        with jax.named_scope("sb.set"):
            regs = hll.insert_packed(regs,
                                     seg("pos_rows", spec.pos_n),
                                     seg("pos_pk", spec.pos_n))
    if spec.plane_rows:
        with jax.named_scope("sb.set"):
            words = spec.plane_rows * (hll.M // 4)
            plane = lax.bitcast_convert_type(
                seg("plane_regs", words),
                jnp.uint8).reshape(spec.plane_rows, hll.M)
            if spec.plane_full:
                regs = hll.union(regs, plane)
            else:
                regs = hll.merge_rows(regs,
                                      seg("plane_idx", spec.plane_rows),
                                      plane)
    return counters, gauges, means, weights, stats, regs


# The donated argnums are the six state planes (the buffer is a host
# staging array, never donated); donation stays behind the global
# VENEUR_TPU_DONATE gate (utils/jitopts) like every other step.
step = observe.instrument(
    "table.superbatch_apply",
    jax.jit(_fused, static_argnums=0,
            donate_argnums=jitopts.donate(1, 2, 3, 4, 5, 6)))
