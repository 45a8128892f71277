"""Flush: device table snapshot -> MetricFrame + forwardable state.

The reference's flush pipeline (flusher.go:28 ``Flush`` ->
:172 ``tallyMetrics`` -> :228 ``generateInterMetrics``) walks every
sampler object and calls its ``Flush()``.  Here the equivalent work is a
handful of device readouts over whole tables — counter/gauge vectors,
the histo quantile kernel over all rows at once, the HLL estimate kernel
over all register planes — followed by host-side assembly of column
blocks over the row metadata: a ``MetricFrame`` of what is emitted here,
``ForwardBlock``s of what goes upstream.

Role semantics (reference flusher.go:61-99, worker.go:181
``ForwardableMetrics``):

- A LOCAL node (has a forward address) emits counters/gauges of
  default/local scope, histo aggregates from local stats (NO
  percentiles), and forwards histos/timers/sets plus global-scope
  counters/gauges upstream as mergeable state.
- A GLOBAL node emits everything, computing percentiles from the merged
  digests and min/max/etc from the merged stat columns.
- ``veneurlocalonly`` metrics never forward; ``veneurglobalonly``
  metrics never emit locally (samplers/parser.go:397-407 scope
  semantics).

Histo aggregate emission matches samplers/samplers.go:511-672: .min
.max .sum .avg .count .median .hmean gauges (count is a counter) plus
``.<p>percentile`` gauges, with the reference's sparse-emission guards.
"""

from __future__ import annotations

import time
from collections.abc import Sequence
from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu import observe
from veneur_tpu.core import metrics as im
from veneur_tpu.core.frame import (MetricFrame, TYPE_COUNTER,
                                   TYPE_GAUGE)
from veneur_tpu.core.table import RowMeta, Snapshot
from veneur_tpu.ops import hll, segment, tdigest
from veneur_tpu.protocol import dogstatsd as dsd

DEFAULT_AGGREGATES = ("min", "max", "count")
DEFAULT_PERCENTILES = (0.5, 0.75, 0.99)

# vectorized scope gates: RowMeta.scope as a small int column
_SCOPE_DEFAULT, _SCOPE_LOCAL, _SCOPE_GLOBAL = 0, 1, 2
_SCOPE_CODE = {dsd.SCOPE_DEFAULT: _SCOPE_DEFAULT,
               dsd.SCOPE_LOCAL: _SCOPE_LOCAL,
               dsd.SCOPE_GLOBAL: _SCOPE_GLOBAL}


def _scope_codes(metas: list, rows: np.ndarray) -> np.ndarray:
    """uint8 scope code per selected row — the one O(touched-rows)
    Python pass the flush makes over metadata."""
    code = _SCOPE_CODE
    return np.fromiter((code[metas[r].scope] for r in rows),
                       np.uint8, len(rows))


def _combine_stats_fn(stats, imp):
    """Device-side combine of the local-sample and imported stat
    planes (weight/sum/rsum add, min min, max max), so the host does
    one batched readback instead of ping-ponging stats -> host ->
    device (each leg is a synchronous transfer).  Kept as a plain
    function so the fused readout kernels inline it; the instrumented
    ``_combine_stats`` below is the host-level entry point."""
    return jnp.stack([
        stats[:, segment.STAT_WEIGHT] + imp[:, segment.STAT_WEIGHT],
        jnp.minimum(stats[:, segment.STAT_MIN], imp[:, segment.STAT_MIN]),
        jnp.maximum(stats[:, segment.STAT_MAX], imp[:, segment.STAT_MAX]),
        stats[:, segment.STAT_SUM] + imp[:, segment.STAT_SUM],
        stats[:, segment.STAT_RSUM] + imp[:, segment.STAT_RSUM],
    ], axis=1)


_combine_stats = observe.instrument("flusher.combine_stats",
                                    jax.jit(_combine_stats_fn))


@partial(jax.jit, static_argnames=("method",))
def _histo_readout_jit(stats, imp, means, weights, qs, method="interp"):
    """_combine_stats plus the per-row quantile kernel in one
    dispatch — used only when someone will actually emit quantiles
    (the batched sort over every digest row is not free).  ``method``
    selects the interpolation (see ops/tdigest.quantile): "interp"
    (default, singleton-exact) or "reference" (Go-identical)."""
    comb = _combine_stats_fn(stats, imp)
    qfn = (tdigest._quantile if method == "reference"
           else tdigest._quantile_interp)
    qvals = qfn(means, weights, qs,
                comb[:, segment.STAT_MIN],
                comb[:, segment.STAT_MAX])
    return comb, qvals


_histo_readout = observe.instrument("flusher.histo_readout",
                                    _histo_readout_jit)


@partial(jax.jit, static_argnames=("method",))
def _histo_readout_rows_jit(stats, imp, means, weights, qs, idx,
                            method="interp"):
    """_histo_readout restricted to a padded row-index slice: both the
    readback bytes and the quantile kernel's batched sort scale with
    the touched-row count instead of the table capacity."""
    st = stats[idx]
    comb = _combine_stats_fn(st, imp[idx])
    qfn = (tdigest._quantile if method == "reference"
           else tdigest._quantile_interp)
    qvals = qfn(means[idx], weights[idx], qs,
                comb[:, segment.STAT_MIN],
                comb[:, segment.STAT_MAX])
    return st, comb, qvals


_histo_readout_rows = observe.instrument("flusher.histo_readout_rows",
                                         _histo_readout_rows_jit)


@partial(jax.jit, static_argnames=("method",))
def _histo_quantiles_slots_jit(stats, imp, means, weights, qs,
                               row_idx, slot_idx, method="interp"):
    """Tiered variant of the quantile readout: the stat planes stay
    row-space while the centroid planes live in the wide-slot pool,
    so min/max gather at ``row_idx`` and centroids at ``slot_idx``
    (same padded length, position-aligned)."""
    comb = _combine_stats_fn(stats[row_idx], imp[row_idx])
    qfn = (tdigest._quantile if method == "reference"
           else tdigest._quantile_interp)
    return qfn(means[slot_idx], weights[slot_idx], qs,
               comb[:, segment.STAT_MIN],
               comb[:, segment.STAT_MAX])


_histo_quantiles_slots = observe.instrument(
    "flusher.histo_quantiles_slots", _histo_quantiles_slots_jit)


@jax.jit
def _gather_rows_jit(plane, idx):
    """Compact selected rows on device before readback — reading a
    full register/centroid plane to forward a handful of touched rows
    would dominate the flush."""
    return plane[idx]


_gather_rows = observe.instrument("flusher.gather_rows",
                                  _gather_rows_jit)

# mixed-interval host-plane union (raw set traffic + imports in one
# interval): instrumented so its dispatch and host-plane h2d bytes
# show up in the per-interval device accounting like every other
# flush kernel
_union_host_plane = observe.instrument("flusher.hll_union_host_plane",
                                       jax.jit(hll.union))


def _pad_idx(rows: list[int]) -> tuple[jnp.ndarray, int]:
    from veneur_tpu.core.table import _bucket_len
    n = len(rows)
    idx = np.zeros(_bucket_len(n, wide=True), np.int32)
    idx[:n] = rows
    return jnp.asarray(idx), n


@dataclass
class ForwardRow:
    """One row of mergeable state bound for the global tier."""
    meta: RowMeta
    kind: str  # counter | gauge | histo | set
    value: float = 0.0
    stats: np.ndarray | None = None  # f32[5]
    means: np.ndarray | None = None  # f32[C]
    weights: np.ndarray | None = None  # f32[C]
    regs: np.ndarray | None = None  # u8[M]


@dataclass
class ForwardBlock:
    """One class's rows of mergeable state as columns (the forward's
    counterpart of ``MetricFrame``): the series' ``RowMeta`` in row
    order and the class's values as arrays over them.  A block owns
    its arrays: the forward is encoded on the pool after
    ``snap.release()`` and may outlive its cycle, so nothing here may
    alias a plane the snapshot hands back."""
    kind: str  # counter | gauge | histo | set
    metas: list[RowMeta]
    values: np.ndarray | None = None  # f64[n], counter | gauge
    stats: np.ndarray | None = None  # f32[n,5]
    # f32[n,C]; or, where rows differ in width (the tiered path's
    # compact rows), f32[total] with row i at [row_at[i]:row_at[i+1]]
    means: np.ndarray | None = None
    weights: np.ndarray | None = None
    row_at: np.ndarray | None = None  # i64[n+1], ragged digests only
    regs: np.ndarray | None = None  # u8[n,M]

    def __len__(self) -> int:
        return len(self.metas)

    @classmethod
    def ragged_histo(cls, metas: list[RowMeta], stats: np.ndarray,
                     means: list, weights: list) -> ForwardBlock:
        """A histogram block from one (means, weights) pair of arrays
        a row, of any widths: both planes laid flat, once."""
        row_at = np.zeros(len(metas) + 1, np.int64)
        np.cumsum([len(w) for w in weights], out=row_at[1:])
        return cls("histo", metas, stats=stats,
                   means=np.concatenate(means),
                   weights=np.concatenate(weights), row_at=row_at)

    def digest_planes(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(means, weights, row_at) with both planes flat and row i's
        slots at ``[row_at[i]:row_at[i+1]]``, whichever way the block
        holds them."""
        if self.row_at is not None:
            return self.means, self.weights, self.row_at
        n, width = self.means.shape
        return (self.means.reshape(-1), self.weights.reshape(-1),
                np.arange(n + 1, dtype=np.int64) * width)

    def rows(self):
        """The block's ``ForwardRow``s, built on demand: views into
        the block's arrays, for the row-wise consumers (shard router,
        arc handoff, HTTP forward, tests)."""
        kind, metas = self.kind, self.metas
        if kind == "histo":
            means, weights, at = self.means, self.weights, self.row_at
            for i, meta in enumerate(metas):
                sl = i if at is None else slice(at[i], at[i + 1])
                yield ForwardRow(meta, kind, stats=self.stats[i],
                                 means=means[sl], weights=weights[sl])
        elif kind == "set":
            for meta, regs in zip(metas, self.regs):
                yield ForwardRow(meta, kind, regs=regs)
        else:
            for meta, v in zip(metas, self.values.tolist()):
                yield ForwardRow(meta, kind, value=v)


class ForwardList(Sequence):
    """``FlushResult.forward``: a flush's forwardable state in wire
    order, as ``ForwardBlock``s (the flush appends one per class) and
    loose ``ForwardRow``s (checkpoint replay, the tests' reference).
    It reads as the list of rows it used to be, rows built on demand:
    ``len()`` counts rows, iteration yields ``ForwardRow``s, an empty
    one is false and equals ``[]``.  The encoder takes ``parts``."""

    def __init__(self, parts=()):
        self.parts: list[ForwardBlock | ForwardRow] = list(parts)

    def append(self, part: ForwardBlock | ForwardRow) -> None:
        self.parts.append(part)

    def __len__(self) -> int:
        return sum(len(p) if isinstance(p, ForwardBlock) else 1
                   for p in self.parts)

    def __iter__(self):
        for p in self.parts:
            if isinstance(p, ForwardBlock):
                yield from p.rows()
            else:
                yield p

    def __getitem__(self, i):
        return list(self)[i]

    def __eq__(self, other) -> bool:
        if not isinstance(other, Sequence):
            return NotImplemented
        return list(self) == list(other)


@dataclass
class FlushResult:
    """What one flush produced.  ``frame`` holds every emitted
    aggregate as columns (an empty frame where nothing was flushed);
    ``riders`` are the InterMetrics synthesized beside it (the
    server's status checks); ``forward`` is the mergeable state bound
    for the global tier.  ``metrics`` reads frame and riders as one
    list of InterMetrics, built when somebody asks for it."""
    frame: MetricFrame = field(default_factory=lambda: MetricFrame(0))
    riders: list[im.InterMetric] = field(default_factory=list)
    forward: ForwardList = field(default_factory=ForwardList)
    tally: dict[str, int] = field(default_factory=dict)
    # row-granularity routing counts for the conservation ledger:
    # every touched row is emitted, forwarded, both (overlap —
    # default-scope histos on a local node), or retained (neither).
    # Counted from the actual routing decisions, NOT derived as a
    # residual, so `staged == emitted + forwarded - overlap +
    # retained` is a real check on the routing paths
    row_accounting: dict = field(default_factory=lambda: {
        "staged_rows": 0, "emitted_rows": 0, "forwarded_rows": 0,
        "overlap_rows": 0, "retained_rows": 0})
    # sharded forward: ``forwarded_rows`` above is the scalar total;
    # when the tpu_sharded_global router splits the wire this records
    # the per-destination counts (the ledger's seal holds
    # ``forwarded == sum(split) + dropped`` against it)
    forward_split: dict = field(default_factory=dict)

    def account_rows(self, staged: int = 0, emitted: int = 0,
                     forwarded: int = 0, overlap: int = 0,
                     retained: int = 0) -> None:
        acct = self.row_accounting
        acct["staged_rows"] += int(staged)
        acct["emitted_rows"] += int(emitted)
        acct["forwarded_rows"] += int(forwarded)
        acct["overlap_rows"] += int(overlap)
        acct["retained_rows"] += int(retained)

    def account_forward_split(self, split: dict) -> None:
        """Fold one sharded forward's {destination: rows} routing
        outcome into the result (runs from the forward stage, after
        ``account_rows`` already counted the scalar total)."""
        for dest, n in split.items():
            self.forward_split[dest] = (
                self.forward_split.get(dest, 0) + int(n))

    @property
    def metrics(self) -> list[im.InterMetric]:
        """Riders first, then the frame's metrics in block order; the
        frame caches its materialization."""
        made = self.frame.materialize()
        return self.riders + made if self.riders else made

    def metric_count(self) -> int:
        return len(self.riders) + len(self.frame)


def _percentile_suffix(p: float, naming: str = "precise") -> str:
    """Reference emits ``.50percentile`` for 0.5 (samplers.go:657);
    sub-percent quantiles keep their digits (``.999percentile``
    for 0.999) instead of truncating.  ``naming="reference"`` keeps
    the Go fleet's exact ``int(p*100)`` truncation (0.999 ->
    ``99percentile`` — colliding with 0.99, the reference's own noted
    TODO) so mixed-fleet dashboards see byte-identical names."""
    if naming == "reference":
        return f"{int(p * 100)}percentile"
    scaled = p * 100
    if abs(scaled - round(scaled)) < 1e-9:
        return f"{int(round(scaled))}percentile"
    return f"{str(scaled).replace('.', '')}percentile"


class Flusher:
    def __init__(self, is_local: bool,
                 percentiles: tuple[float, ...] = DEFAULT_PERCENTILES,
                 aggregates: tuple[str, ...] = DEFAULT_AGGREGATES,
                 hostname: str = "", tags: tuple[str, ...] = (),
                 percentile_naming: str = "precise",
                 quantile_interpolation: str = "interp"):
        self.is_local = is_local
        self.percentiles = tuple(percentiles)
        self.aggregates = tuple(aggregates)
        self.hostname = hostname
        self.common_tags = tuple(tags)
        self.percentile_naming = percentile_naming
        self.quantile_interpolation = quantile_interpolation
        # scale-out arc handoff override: a ``(meta) -> bool``
        # installed for exactly one flush (Server.arc_handoff).  True
        # force-forwards the row even on a node whose flusher never
        # forwards (a global) — the keyspace arc now belongs to
        # another member, so the row must LEAVE as mergeable state
        # instead of being emitted here.  None in steady state.
        self.handoff = None

    # ------------------------------------------------------------------

    def flush(self, snap: Snapshot, now: int | None = None,
              cycle=None) -> FlushResult:
        """Read the snapshot out into a ``FlushResult``: the emitted
        aggregates as ``res.frame``'s column blocks, the forwardable
        state as ``res.forward``'s.  ``cycle`` is an
        observe.FlushCycle (or the NULL_CYCLE default): stage spans
        and readback accounting for the three phases this method owns
        (device dispatch, readback sync, host emit)."""
        if cycle is None:
            cycle = observe.NULL_CYCLE
        ts = int(now if now is not None else time.time())
        res = FlushResult(frame=MetricFrame(ts, self.hostname,
                                            self.common_tags,
                                            snap.sink_only_rows))
        pre = self._prefetch(snap, cycle)
        with cycle.stage("host_emit"):
            self._frame_counters(snap, res, pre)
            self._frame_gauges(snap, res, pre)
            self._frame_histos(snap, res, pre)
            self._frame_sets(snap, res, pre)
        res.tally["overflow"] = sum(snap.overflow.values())
        return res

    # ------------------------------------------------------------------

    def _prefetch(self, snap: Snapshot, cycle=observe.NULL_CYCLE) -> dict:
        """Launch every device computation the flush needs, then pull
        all results to host in ONE pipelined jax.device_get — each
        separate synchronous readback pays a full round trip, but
        async copies overlap to a single latency.

        Two traced stages: ``dispatch`` covers the async kernel
        launches (dispatch wall time only), ``device_wait`` covers
        the blocking device_get plus host re-scatter — the stage whose
        span duration IS the d2h cost an operator wants attributed."""
        with cycle.stage("dispatch") as sp:
            devs, pre, expand = self._dispatch(snap)
            sp.add_tag("device_arrays", str(len(devs)))
        with cycle.stage("device_wait") as sp:
            got = jax.device_get(devs)
            nbytes = int(sum(getattr(v, "nbytes", 0)
                             for v in got.values()))
            cycle.add_readback(nbytes)
            sp.add_tag("readback_bytes", str(nbytes))
            pre.update(got)
            for dev_key, out_key, rows, shape in expand:
                out = pre.pop(dev_key)
                full = np.zeros(shape, out.dtype)
                full[rows] = out[:len(rows)]
                pre[out_key] = full
            # tiered snapshots register host-side assembly steps that
            # need the readback in hand (compact-row quantiles,
            # mixed-tier forward planes) — run them after the expand
            # so they see full row-space arrays
            for fn in pre.pop("_tier_post", []):
                fn(pre)
        return pre

    def _dispatch(self, snap: Snapshot) -> tuple[dict, dict, list]:
        devs: dict = {}
        pre: dict = {}
        expand: list = []  # (dev_key, out_key, rows, full shape)

        def _plane_readback(key, plane, touched, meta_len):
            """Read back only the TOUCHED rows when they are sparse:
            a 256k-row counter plane is ~1 MB of d2h per flush, but
            the touched slice usually is not.  The gathered values are re-scattered into a
            full-size host array so consumers index by absolute row
            either way."""
            rows = np.nonzero(touched[:meta_len])[0]
            total = plane.shape[0]
            if len(rows) * 2 >= total:
                devs[key] = plane
                return
            idx, _ = _pad_idx(rows)
            devs[key + "_g"] = _gather_rows(plane, idx)
            expand.append((key + "_g", key, rows, plane.shape))

        if snap.counter_meta and snap.counter_touched.any():
            _plane_readback("counters", snap.counters,
                            snap.counter_touched,
                            len(snap.counter_meta))
        if snap.gauge_meta and snap.gauge_touched.any():
            _plane_readback("gauges", snap.gauges, snap.gauge_touched,
                            len(snap.gauge_meta))

        histo_rows = np.nonzero(
            snap.histo_touched[:len(snap.histo_meta)])[0]
        pre["histo_rows"] = histo_rows
        if len(histo_rows):
            all_pcts = tuple(self.percentiles) + (
                (0.5,) if "median" in self.aggregates else ())
            pre["all_pcts"] = all_pcts
            emit_pcts = not self.is_local
            any_local_scope = any(
                snap.histo_meta[r].scope == dsd.SCOPE_LOCAL
                for r in histo_rows)
            need_q = bool(all_pcts) and (
                emit_pcts or "median" in self.aggregates or
                any_local_scope)
            if snap.tiers is not None:
                self._dispatch_histos_tiered(
                    snap, histo_rows, all_pcts, need_q, devs, pre,
                    expand)
            else:
                sparse = (len(histo_rows) * 2 <
                          snap.histo_stats.shape[0])
                if sparse:
                    # slice the touched rows on device FIRST: the
                    # stat planes and the quantile kernel (a batched
                    # sort over every digest row) then cost
                    # O(touched), and the d2h readback shrinks the
                    # same way
                    idx, _ = _pad_idx(histo_rows)
                    if need_q:
                        qs = np.asarray(all_pcts, np.float32)
                        st_g, comb_g, qvals_g = _histo_readout_rows(
                            snap.histo_stats, snap.histo_import_stats,
                            snap.histo_means, snap.histo_weights,
                            jnp.asarray(qs), idx,
                            method=self.quantile_interpolation)
                        devs["qvals_g"] = qvals_g
                        expand.append(("qvals_g", "qvals", histo_rows,
                                       (snap.histo_stats.shape[0],
                                        len(all_pcts))))
                    else:
                        st_g = _gather_rows(snap.histo_stats, idx)
                        comb_g = _combine_stats(
                            st_g,
                            _gather_rows(snap.histo_import_stats,
                                         idx))
                    devs["stats_g"] = st_g
                    devs["comb_g"] = comb_g
                    shape5 = (snap.histo_stats.shape[0],
                              segment.HISTO_STAT_COLS)
                    expand.append(("stats_g", "stats", histo_rows,
                                   shape5))
                    expand.append(("comb_g", "comb", histo_rows,
                                   shape5))
                else:
                    if need_q:
                        qs = np.asarray(all_pcts, np.float32)
                        comb, qvals = _histo_readout(
                            snap.histo_stats, snap.histo_import_stats,
                            snap.histo_means, snap.histo_weights,
                            jnp.asarray(qs),
                            method=self.quantile_interpolation)
                        devs["qvals"] = qvals
                    else:
                        comb = _combine_stats(snap.histo_stats,
                                              snap.histo_import_stats)
                    devs["stats"] = snap.histo_stats
                    devs["comb"] = comb
                fwd = [int(r) for r in histo_rows
                       if self._forwardable(snap.histo_meta[r],
                                            always=True)]
                pre["histo_fwd"] = fwd
                if fwd:
                    idx, _ = _pad_idx(fwd)
                    devs["fwd_means"] = _gather_rows(snap.histo_means,
                                                     idx)
                    devs["fwd_weights"] = _gather_rows(
                        snap.histo_weights, idx)

        set_rows = np.nonzero(snap.set_touched[:len(snap.set_meta)])[0]
        pre["set_rows"] = set_rows
        if len(set_rows):
            fwd = [int(r) for r in set_rows
                   if self._forwardable(snap.set_meta[r], always=True)]
            pre["set_fwd"] = fwd
            fwd_set = set(fwd)
            need_est = any(int(r) not in fwd_set and
                           self._emit_local(snap.set_meta[r])
                           for r in set_rows)
            if snap.tiers is not None:
                # tiered interval: the host plane is SLOT-indexed and
                # compact rows live in the sparse store, so both the
                # estimates and the forward registers go through the
                # tier snapshot (upgrade-on-pack: compact rows
                # materialize to dense u8[M] for the frozen wire)
                if fwd:
                    pre["fwd_regs"] = [
                        snap.tiers.set_row_regs(snap, r) for r in fwd]
                if need_est:
                    pre["ests"] = snap.tiers.set_estimates(snap,
                                                           set_rows)
            elif snap.host_only_sets:
                # whole interval's set state lives on host: estimate
                # and gather forward rows with zero device round trips
                if fwd:
                    pre["fwd_regs"] = snap.hll_host_plane[
                        np.asarray(fwd, np.int64)]
                if need_est:
                    pre["ests"] = snap.host_set_estimates()
            else:
                regs = snap.hll_regs
                if snap.hll_host_plane is not None:
                    # rare mixed interval (raw traffic + imports):
                    # union the host plane in once, then read on device
                    regs = _union_host_plane(regs,
                                             snap.hll_host_plane)
                if fwd:
                    idx, _ = _pad_idx(fwd)
                    devs["fwd_regs"] = _gather_rows(regs, idx)
                if need_est:
                    devs["ests"] = hll.estimate(regs)
        return devs, pre, expand

    # ------------------------------------------------------------------
    # tiered dispatch: a tier snapshot keeps the stat planes row-space
    # (aggregates read back exactly as single-tier) but the centroid
    # planes are a wide-slot pool and compact rows hold raw host
    # samples.  Quantiles therefore split by tier: wide rows run the
    # device kernel at their pool slots, compact rows run the SAME
    # kernel over host-built singleton planes once the combined stats
    # are back (their true min/max live there) — one math path for
    # both tiers, so a compact row in its singleton regime is
    # bit-compatible with the wide-only oracle.

    def _dispatch_histos_tiered(self, snap: Snapshot, histo_rows,
                                all_pcts, need_q, devs: dict,
                                pre: dict, expand: list) -> None:
        ti = snap.tiers
        R = snap.histo_stats.shape[0]
        shape5 = (R, segment.HISTO_STAT_COLS)
        sparse = len(histo_rows) * 2 < R
        if sparse:
            idx, _ = _pad_idx(histo_rows)
            st_g = _gather_rows(snap.histo_stats, idx)
            comb_g = _combine_stats(
                st_g, _gather_rows(snap.histo_import_stats, idx))
            devs["stats_g"] = st_g
            devs["comb_g"] = comb_g
            expand.append(("stats_g", "stats", histo_rows, shape5))
            expand.append(("comb_g", "comb", histo_rows, shape5))
        else:
            devs["stats"] = snap.histo_stats
            devs["comb"] = _combine_stats(snap.histo_stats,
                                          snap.histo_import_stats)
        wide = ti.histo_tier[histo_rows].astype(bool)
        wrows = histo_rows[wide]
        crows = histo_rows[~wide]
        if need_q:
            qs = np.asarray(all_pcts, np.float32)
            if len(wrows):
                ridx, _ = _pad_idx(list(wrows))
                sl = np.zeros(int(ridx.shape[0]), np.int32)
                sl[:len(wrows)] = ti.histo_slot[wrows]
                qv_w = _histo_quantiles_slots(
                    snap.histo_stats, snap.histo_import_stats,
                    snap.histo_means, snap.histo_weights,
                    jnp.asarray(qs), ridx, jnp.asarray(sl),
                    method=self.quantile_interpolation)
                devs["qvals_w"] = qv_w
                expand.append(("qvals_w", "qvals", wrows,
                               (R, len(all_pcts))))
            method = self.quantile_interpolation

            def _compact_quantiles(pre, crows=crows, qs=qs,
                                   store=ti.histo_compact,
                                   npcts=len(all_pcts), R=R,
                                   method=method):
                qv = pre.get("qvals")
                if qv is None:
                    qv = np.zeros((R, npcts), np.float32)
                    pre["qvals"] = qv
                if not len(crows):
                    return
                planes = [store.samples(int(r)) if store is not None
                          else (np.empty(0, np.float32),) * 2
                          for r in crows]
                comb = pre["comb"]
                qfn = (tdigest._quantile if method == "reference"
                       else tdigest._quantile_interp)
                # bucket rows by sample count: padding the whole
                # batch to the global max would square up to rows x
                # max_count (a still-compact Zipf head row can carry
                # tens of thousands of samples pre-promotion, turning
                # that into gigabytes).  Pow-2 caps and row counts
                # keep every device shape on a small reusable lattice
                counts = np.array([len(v) for v, _ in planes],
                                  np.int64)
                order = np.argsort(counts, kind="stable")
                qv_c = np.zeros((len(crows), npcts), np.float32)
                qsj = jnp.asarray(qs)
                lo = 0
                while lo < len(order):
                    c = int(max(counts[order[lo]], 1))
                    cap = 1 << max(6, (c - 1).bit_length())
                    hi = lo
                    while hi < len(order) and counts[order[hi]] <= cap:
                        hi += 1
                    sel = order[lo:hi]
                    n = 1 << max(3, int(len(sel) - 1).bit_length())
                    cm = np.zeros((n, cap), np.float32)
                    cw = np.zeros((n, cap), np.float32)
                    for k, i in enumerate(sel):
                        v, w = planes[i]
                        cm[k, :len(v)] = v
                        cw[k, :len(v)] = w
                    rr = crows[sel]
                    mn = np.zeros(n, np.float32)
                    mx = np.zeros(n, np.float32)
                    mn[:len(sel)] = comb[rr, segment.STAT_MIN]
                    mx[:len(sel)] = comb[rr, segment.STAT_MAX]
                    cq = qfn(jnp.asarray(cm), jnp.asarray(cw), qsj,
                             jnp.asarray(mn), jnp.asarray(mx))
                    qv_c[sel] = np.asarray(cq)[:len(sel)]
                    lo = hi
                qv[crows] = qv_c

            pre.setdefault("_tier_post", []).append(_compact_quantiles)
        fwd = [int(r) for r in histo_rows
               if self._forwardable(snap.histo_meta[r], always=True)]
        pre["histo_fwd"] = fwd
        if fwd:
            fwide = ti.histo_tier[np.asarray(fwd, np.int64)] != 0
            wf = [r for r, w in zip(fwd, fwide) if w]
            if wf:
                sidx, _ = _pad_idx(list(ti.histo_slot[
                    np.asarray(wf, np.int64)]))
                devs["fwd_means_w"] = _gather_rows(snap.histo_means,
                                                   sidx)
                devs["fwd_weights_w"] = _gather_rows(
                    snap.histo_weights, sidx)

            def _assemble_fwd(pre, fwd=fwd, fwide=fwide,
                              store=ti.histo_compact):
                mw = pre.pop("fwd_means_w", None)
                ww = pre.pop("fwd_weights_w", None)
                means, weights = [], []
                j = 0
                for i, r in enumerate(fwd):
                    if fwide[i]:
                        means.append(np.asarray(mw[j]))
                        weights.append(np.asarray(ww[j]))
                        j += 1
                    else:
                        v, w = (store.samples(r) if store is not None
                                else (np.empty(0, np.float32),) * 2)
                        # mean-sorted like a digest plane, so the
                        # wire's live-centroid list reads the same
                        # either tier
                        o = np.argsort(v, kind="stable")
                        means.append(np.ascontiguousarray(v[o]))
                        weights.append(np.ascontiguousarray(w[o]))
                pre["fwd_means"] = means
                pre["fwd_weights"] = weights

            pre.setdefault("_tier_post", []).append(_assemble_fwd)

    # ------------------------------------------------------------------

    def _emit_local(self, meta: RowMeta) -> bool:
        return meta.scope != dsd.SCOPE_GLOBAL or not self.is_local

    def _forwardable(self, meta: RowMeta, always: bool) -> bool:
        if self.handoff is not None and self.handoff(meta):
            return True
        if not self.is_local or meta.scope == dsd.SCOPE_LOCAL:
            return False
        return always or meta.scope == dsd.SCOPE_GLOBAL

    # ------------------------------------------------------------------
    # emit: the routing and gating semantics of the module docstring,
    # evaluated as boolean arrays over whole planes.  One scope-code
    # pass per class; percentile suffixes are built once per flush.
    # (tests/flush_reference.py holds the same semantics one row at a
    # time, as the parity reference.)

    def _frame_scalar_class(self, metas, touched, vals, kind,
                            type_code, res) -> None:
        """Counters and gauges share one shape: forward global-scope
        rows on a local node, emit everything else."""
        rows = np.nonzero(touched[:len(metas)])[0]
        if not len(rows):
            return
        v64 = np.asarray(vals)[rows].astype(np.float64)
        # arc-handoff rows forward ONLY, on either tier: their state
        # now lives on the new ring owner, which emits it next interval
        ho = np.zeros(len(rows), dtype=bool)
        if self.handoff is not None:
            ho = np.fromiter(
                (bool(self.handoff(metas[int(r)])) for r in rows),
                dtype=bool, count=len(rows))
        if self.is_local:
            sc = _scope_codes(metas, rows)
            fwd = ho | (sc == _SCOPE_GLOBAL)
        else:
            fwd = ho
        if fwd.any():
            res.forward.append(ForwardBlock(
                kind, [metas[r] for r in rows[fwd]], values=v64[fwd]))
        emit = ~fwd
        res.frame.add_block(metas, rows[emit], v64[emit],
                            type_code=type_code)
        res.account_rows(staged=len(rows),
                         emitted=int(emit.sum()),
                         forwarded=int(fwd.sum()))

    def _frame_counters(self, snap: Snapshot, res: FlushResult,
                        pre: dict) -> None:
        vals = pre.get("counters")
        if vals is None:
            return
        self._frame_scalar_class(snap.counter_meta,
                                 snap.counter_touched, vals,
                                 "counter", TYPE_COUNTER, res)
        res.tally["counters"] = int(
            snap.counter_touched[:len(snap.counter_meta)].sum())

    def _frame_gauges(self, snap: Snapshot, res: FlushResult,
                      pre: dict) -> None:
        vals = pre.get("gauges")
        if vals is None:
            return
        self._frame_scalar_class(snap.gauge_meta, snap.gauge_touched,
                                 vals, "gauge", TYPE_GAUGE, res)
        res.tally["gauges"] = int(
            snap.gauge_touched[:len(snap.gauge_meta)].sum())

    def _frame_histos(self, snap: Snapshot, res: FlushResult,
                      pre: dict) -> None:
        rows = pre["histo_rows"]
        if not len(rows):
            return
        metas = snap.histo_meta
        stats = pre["stats"]
        comb = pre["comb"]
        qvals = pre.get("qvals")
        all_pcts = pre["all_pcts"]

        # forward rows first, in row order.  The gathers'
        # readbacks and ``stats[fwd]`` are private to this flush, so
        # the block takes them as they are, less ``_pad_idx``'s tail
        fwd = pre["histo_fwd"]
        if fwd:
            fmetas = [metas[r] for r in fwd]
            if snap.tiers is not None:
                # one array a row, of unequal width, some of them the
                # compact store's own
                res.forward.append(ForwardBlock.ragged_histo(
                    fmetas, stats[fwd], pre["fwd_means"],
                    pre["fwd_weights"]))
            else:
                res.forward.append(ForwardBlock(
                    "histo", fmetas, stats=stats[fwd],
                    means=pre["fwd_means"][:len(fwd)],
                    weights=pre["fwd_weights"][:len(fwd)]))

        sc = _scope_codes(metas, rows)
        # routing counts: on a local node every non-local-scope row
        # forwards and every non-global-scope row emits (default scope
        # does both); a global node emits all.
        # Arc-handoff rows forward ONLY on either tier (emitting too
        # would double-report their mass for the handoff interval).
        ho = np.zeros(len(rows), dtype=bool)
        if self.handoff is not None:
            ho = np.fromiter(
                (bool(self.handoff(metas[int(r)])) for r in rows),
                dtype=bool, count=len(rows))
        if self.is_local:
            fwd_mask = ho | (sc != _SCOPE_LOCAL)
            emit_mask = ~ho & (sc != _SCOPE_GLOBAL)
        else:
            fwd_mask = ho
            emit_mask = ~ho
        res.account_rows(
            staged=len(rows), emitted=int(emit_mask.sum()),
            forwarded=len(pre["histo_fwd"]),
            overlap=int((emit_mask & fwd_mask).sum()),
            retained=int((~emit_mask & ~fwd_mask).sum()))
        if self.is_local:
            # mixed-scope histos emit local aggregates even while
            # their digest forwards; global-only histos emit nothing
            # locally
            erows = rows[emit_mask]
            esc = sc[emit_mask]
            if not len(erows):
                res.tally["histograms"] = int(
                    snap.histo_touched[:len(metas)].sum())
                return
            gm = np.zeros(len(erows), dtype=bool)
            with_pcts = esc == _SCOPE_LOCAL
        else:
            erows = rows[emit_mask]
            if not len(erows):
                res.tally["histograms"] = int(
                    snap.histo_touched[:len(metas)].sum())
                return
            gm = sc[emit_mask] == _SCOPE_GLOBAL
            with_pcts = np.ones(len(erows), dtype=bool)

        # Two stat planes: ``stats`` holds aggregates of raw samples
        # ingested by THIS node ("Local*" in the reference,
        # samplers/samplers.go:484); ``comb`` is that plane combined on
        # device with the merged forwarded stat rows.  Aggregates for
        # mixed-scope rows come only from the local plane (reference
        # gates on LocalWeight/LocalMin/LocalMax, samplers.go:530-621:
        # emitting them from merged state would double-count against
        # the local tier's own emission); rows flushed with global=true
        # (global scope on a global node, samplers.go:511) use the
        # combined plane, the analogue of reading min/max/sum off the
        # merged digest itself.
        st = np.where(gm[:, None], comb[erows], stats[erows]) \
            .astype(np.float64)
        weight = st[:, segment.STAT_WEIGHT]
        st_min = st[:, segment.STAT_MIN]
        st_max = st[:, segment.STAT_MAX]
        st_sum = st[:, segment.STAT_SUM]
        st_rsum = st[:, segment.STAT_RSUM]
        sampled = weight != 0

        agg = set(self.aggregates)

        def block(mask, vals, suffix, type_code=TYPE_GAUGE):
            res.frame.add_block(metas, erows[mask], vals, suffix,
                                type_code)

        # sparse-emission gates (samplers.go:530-660): each aggregate
        # is emitted from local values only when locally sampled, or
        # unconditionally in global mode (merged state); min/max use
        # the untouched sentinels as the reference uses +/-Inf.  sum
        # and avg gate on SAMPLED (weight != 0), not on sum != 0: a
        # histogram whose values sum to exactly 0 still emits both
        if "max" in agg:
            m = gm | (st_max != float(segment.STAT_MAX_EMPTY))
            block(m, st_max[m], ".max")
        if "min" in agg:
            m = gm | (st_min != float(segment.STAT_MIN_EMPTY))
            block(m, st_min[m], ".min")
        if "sum" in agg:
            m = gm | sampled
            block(m, st_sum[m], ".sum")
        if "avg" in agg:
            m = weight != 0
            block(m, st_sum[m] / weight[m], ".avg")
        if "count" in agg:
            m = gm | sampled
            block(m, weight[m], ".count", TYPE_COUNTER)
        if "hmean" in agg:
            m = (weight != 0) & (st_rsum != 0)
            block(m, weight[m] / st_rsum[m], ".hmean")
        if qvals is not None:
            q64 = qvals[erows].astype(np.float64)
            if "median" in agg:
                m = np.ones(len(erows), dtype=bool)
                block(m, q64[:, len(all_pcts) - 1], ".median")
            for pi, p in enumerate(self.percentiles):
                suffix = "." + _percentile_suffix(
                    p, self.percentile_naming)
                block(with_pcts, q64[with_pcts, pi], suffix)
        res.tally["histograms"] = int(
            snap.histo_touched[:len(metas)].sum())

    def _frame_sets(self, snap: Snapshot, res: FlushResult,
                    pre: dict) -> None:
        rows = pre["set_rows"]
        if not len(rows):
            return
        metas = snap.set_meta
        ests = pre.get("ests")
        fwd = pre.get("set_fwd", ())
        if fwd:
            # a gather of the host plane or a readback of the device
            # gather: a private copy either way (the plane itself goes
            # back to the table's pool at ``snap.release()``); the
            # tiered path hands one fresh array a row
            regs = pre["fwd_regs"]
            regs = (np.stack(regs) if snap.tiers is not None
                    else regs[:len(fwd)])
            res.forward.append(ForwardBlock(
                "set", [metas[r] for r in fwd], regs=regs))
        in_fwd = np.zeros(len(rows), dtype=bool)
        if fwd:
            in_fwd = np.isin(rows, np.asarray(fwd))
        sc = _scope_codes(metas, rows)
        emit = ~in_fwd & ~((sc == _SCOPE_GLOBAL) & self.is_local)
        res.account_rows(staged=len(rows), emitted=int(emit.sum()),
                         forwarded=len(fwd),
                         retained=int((~emit & ~in_fwd).sum()))
        erows = rows[emit]
        if len(erows) and ests is not None:
            vals = np.round(np.asarray(ests)[erows]).astype(np.float64)
            res.frame.add_block(metas, erows, vals)
        res.tally["sets"] = int(
            snap.set_touched[:len(metas)].sum())
