"""Multi-chip sharded aggregation: the global tier over a device mesh.

The reference scales the global tier by running importsrv gRPC fan-in
into N worker goroutines (importsrv/server.go:102-133) and merging
forwarded sketches per worker (worker.go:438 ``ImportMetricGRPC``); a
proxy consistent-hashes series across global *processes*
(proxysrv/server.go:190).  On a TPU slice both levels collapse into one
SPMD program over a 2D ``jax.sharding.Mesh``:

  axis ``shard``   — ingest parallelism.  Each device along this axis
                     accumulates PARTIAL state for every series from its
                     own slice of the sample stream (the moral
                     equivalent of one importsrv worker / one local
                     veneur's worth of state).  Merging partials is
                     exactly the CRDT merge the reference does at
                     import time — but here it happens once per flush
                     as ICI collectives instead of per-RPC.
  axis ``series``  — table-row parallelism.  The row dimension of every
                     state plane is partitioned, so series-cardinality
                     scales with devices (the reference's fnv1a%N worker
                     sharding, server.go:1152, as a sharding
                     annotation).

State planes (leading axis = shard, rows sharded over series):

  counters      f32[S, R]        merge: psum over shard
  gauges        f32[S, R]        merge: value at pmax arrival ticket
  gauge_ticket  i32[S, R]
  histo_stats   f32[S, R, 5]     merge: psum / pmin / pmax per column
  histo_means   f32[S, R, C]     merge: all_gather slots + one k-scale
  histo_weights f32[S, R, C]            re-cluster (ops.tdigest)
  hll           u8[S, R, M]      merge: pmax over shard (register max,
                                        reduced as i32)

The update step and the merge step are each one ``shard_map``-ped jitted
function; everything between flushes is pure per-device work with zero
communication, and the flush-time collectives ride ICI.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from veneur_tpu.utils import jitopts
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.ops import tdigest
from veneur_tpu.ops.segment import (HISTO_STAT_COLS, STAT_MAX, STAT_MIN,
                                    STAT_MAX_EMPTY, STAT_MIN_EMPTY,
                                    STAT_RSUM, STAT_SUM, STAT_WEIGHT)

SHARD = "shard"
SERIES = "series"


def make_mesh(devices=None, n_shard: int | None = None) -> Mesh:
    """Build the 2D (shard, series) mesh over the given devices.

    Default split: series axis gets 2 when the device count is even and
    >2 (row-space sharding is the cheaper axis to under-provision —
    partial-state merge cost grows with ``shard``), else 1.
    """
    devs = np.asarray(devices if devices is not None else jax.devices())
    n = devs.size
    if n_shard is None:
        n_series = 2 if n % 2 == 0 and n > 2 else 1
        n_shard = n // n_series
    else:
        if n % n_shard:
            raise ValueError(f"{n} devices not divisible by {n_shard}")
        n_series = n // n_shard
    return Mesh(devs.reshape(n_shard, n_series), (SHARD, SERIES))


@dataclass(frozen=True)
class ShardedConfig:
    rows: int = 1024          # histo/timer table rows (global)
    set_rows: int = 64
    # counter/gauge cardinality can far exceed histo cardinality
    # (their planes are 1 value/row, digests are ~2*capacity); 0
    # inherits ``rows``
    counter_rows: int = 0
    gauge_rows: int = 0
    compression: float = 100.0
    slots: int = 64           # densify slots per update call
    batch: int = 1024         # per-shard samples per update call

    def capacity(self) -> int:
        return tdigest.capacity_for(self.compression)

    def c_rows(self) -> int:
        return self.counter_rows or self.rows

    def g_rows(self) -> int:
        return self.gauge_rows or self.rows


def _specs(mesh: Mesh):
    """(state spec pytree, batch spec) for shard_map."""
    st = P(SHARD, SERIES)
    return {
        "counters": st, "gauges": st, "gauge_ticket": st,
        "histo_stats": P(SHARD, SERIES, None),
        "histo_means": P(SHARD, SERIES, None),
        "histo_weights": P(SHARD, SERIES, None),
        "hll": P(SHARD, SERIES, None),
    }


def empty_state(mesh: Mesh, cfg: ShardedConfig) -> dict:
    """Allocate the sharded state pytree on the mesh."""
    s = mesh.shape[SHARD]
    r, rs = cfg.rows, cfg.set_rows
    rc, rg = cfg.c_rows(), cfg.g_rows()
    cap = cfg.capacity()
    specs = _specs(mesh)

    def dev(name, arr):
        return jax.device_put(arr, NamedSharding(mesh, specs[name]))

    stats = np.zeros((s, r, HISTO_STAT_COLS), np.float32)
    stats[:, :, STAT_MIN] = STAT_MIN_EMPTY
    stats[:, :, STAT_MAX] = STAT_MAX_EMPTY
    return {
        "counters": dev("counters", np.zeros((s, rc), np.float32)),
        "gauges": dev("gauges", np.zeros((s, rg), np.float32)),
        "gauge_ticket": dev("gauge_ticket",
                            np.full((s, rg), -1, np.int32)),
        "histo_stats": dev("histo_stats", stats),
        "histo_means": dev("histo_means",
                           np.zeros((s, r, cap), np.float32)),
        "histo_weights": dev("histo_weights",
                             np.zeros((s, r, cap), np.float32)),
        "hll": dev("hll", np.zeros((s, rs, hll_ops.M), np.uint8)),
    }


def batch_specs():
    """Batch arrays are [S, N]: split over shard, replicated over
    series (each series-device sees the full batch and keeps only the
    row ids that fall in its block)."""
    b = P(SHARD, None)
    return {k: b for k in (
        "counter_rows", "counter_vals", "counter_wts",
        "gauge_rows", "gauge_vals", "gauge_ticket",
        "histo_rows", "histo_vals", "histo_wts",
        "rsum_rows", "rsum_vals",
        "set_rows", "set_idx", "set_rank")}


def _localize(rows, n_local, axis):
    """Global row ids -> block-local ids; out-of-block -> n_local
    (the drop sentinel).  Negative ids must NOT reach the scatter
    (JAX would wrap them to the end of the block)."""
    offset = jax.lax.axis_index(axis) * n_local
    local = rows - offset
    in_block = (local >= 0) & (local < n_local)
    return jnp.where(in_block, local, n_local)


def make_update_step(mesh: Mesh, cfg: ShardedConfig):
    """Jitted donated SPMD ingest step: state, batch -> state.

    Pure per-device work — no collectives; communication happens only
    in the flush-time merge.
    """
    state_specs = _specs(mesh)
    n_series = mesh.shape[SERIES]
    r_local = cfg.rows // n_series
    rc_local = cfg.c_rows() // n_series
    rg_local = cfg.g_rows() // n_series
    rs_local = cfg.set_rows // n_series
    if (cfg.rows % n_series or cfg.set_rows % n_series or
            cfg.c_rows() % n_series or cfg.g_rows() % n_series):
        raise ValueError("rows must divide by the series axis size")

    def step(state, batch):
        # every local plane has leading shard dim 1 — squeeze it
        cnt = state["counters"][0]
        g = state["gauges"][0]
        gt = state["gauge_ticket"][0]
        hs = state["histo_stats"][0]
        hm = state["histo_means"][0]
        hw = state["histo_weights"][0]
        regs = state["hll"][0]

        crow = _localize(batch["counter_rows"][0], rc_local, SERIES)
        cnt = cnt.at[crow].add(
            batch["counter_vals"][0] * batch["counter_wts"][0],
            mode="drop")

        # gauge last-write-wins with a global arrival ticket: scatter
        # max of ticket, then adopt the batch value wherever its ticket
        # won (ticket uniqueness is the host's contract)
        grow = _localize(batch["gauge_rows"][0], rg_local, SERIES)
        new_t = gt.at[grow].max(batch["gauge_ticket"][0], mode="drop")
        won = jnp.zeros_like(g).at[grow].max(
            jnp.where(
                batch["gauge_ticket"][0] ==
                new_t[jnp.clip(grow, 0, rg_local - 1)],
                batch["gauge_vals"][0], -jnp.inf),
            mode="drop")
        changed = new_t > gt
        g = jnp.where(changed, won, g)
        gt = new_t

        hrow = _localize(batch["histo_rows"][0], r_local, SERIES)
        hv = batch["histo_vals"][0]
        hwt = batch["histo_wts"][0]
        incoming = jnp.stack([
            hwt, jnp.where(hwt > 0, hv, STAT_MIN_EMPTY),
            jnp.where(hwt > 0, hv, STAT_MAX_EMPTY), hv * hwt,
            jnp.where(hv != 0, hwt / hv, 0.0)], axis=1)
        hs = jnp.stack([
            hs[:, STAT_WEIGHT].at[hrow].add(incoming[:, STAT_WEIGHT],
                                            mode="drop"),
            hs[:, STAT_MIN].at[hrow].min(incoming[:, STAT_MIN],
                                         mode="drop"),
            hs[:, STAT_MAX].at[hrow].max(incoming[:, STAT_MAX],
                                         mode="drop"),
            hs[:, STAT_SUM].at[hrow].add(incoming[:, STAT_SUM],
                                         mode="drop"),
            hs[:, STAT_RSUM].at[hrow].add(incoming[:, STAT_RSUM],
                                          mode="drop"),
        ], axis=1)

        # forwarded-digest reciprocal-sum corrections land directly
        # in the RSUM column (centroid means alone misstate it; the
        # import path stages the exact delta)
        rrow = _localize(batch["rsum_rows"][0], r_local, SERIES)
        hs = hs.at[rrow, STAT_RSUM].add(batch["rsum_vals"][0],
                                        mode="drop")

        dense_v, dense_w = tdigest.densify(hrow, hv, hwt, r_local,
                                           cfg.slots)
        hm, hw = tdigest._merge_impl(hm, hw, dense_v, dense_w,
                                     compression=cfg.compression)

        srow = _localize(batch["set_rows"][0], rs_local, SERIES)
        regs = regs.at[srow, batch["set_idx"][0]].max(
            batch["set_rank"][0].astype(regs.dtype), mode="drop")

        return {
            "counters": cnt[None], "gauges": g[None],
            "gauge_ticket": gt[None], "histo_stats": hs[None],
            "histo_means": hm[None], "histo_weights": hw[None],
            "hll": regs[None],
        }

    mapped = jax.shard_map(step, mesh=mesh,
                       in_specs=(state_specs, batch_specs()),
                       out_specs=state_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=jitopts.donate(0))


def make_merge_step(mesh: Mesh, cfg: ShardedConfig):
    """Jitted SPMD flush merge: partial per-shard state -> one merged
    table, via ICI collectives.

    counter psum / gauge ticket-pmax / stat psum+pmin+pmax / t-digest
    all_gather+re-cluster / HLL register pmax — the device-side
    equivalent of the reference's import-merge semantics
    (samplers.go:208 Counter.Merge, :423 Set.Merge, :726 Histo.Merge).
    """
    state_specs = _specs(mesh)
    merged_specs = {
        "counters": P(SERIES), "gauges": P(SERIES),
        "histo_stats": P(SERIES, None),
        "histo_means": P(SERIES, None),
        "histo_weights": P(SERIES, None),
        "hll": P(SERIES, None),
    }

    def merge(state):
        cnt = jax.lax.psum(state["counters"][0], SHARD)

        ticket = state["gauge_ticket"][0]
        best = jax.lax.pmax(ticket, SHARD)
        gv = jax.lax.pmax(
            jnp.where((ticket == best) & (best >= 0),
                      state["gauges"][0], -jnp.inf), SHARD)
        gauges = jnp.where(best >= 0, gv, 0.0)

        hs = state["histo_stats"][0]
        stats = jnp.stack([
            jax.lax.psum(hs[:, STAT_WEIGHT], SHARD),
            jax.lax.pmin(hs[:, STAT_MIN], SHARD),
            jax.lax.pmax(hs[:, STAT_MAX], SHARD),
            jax.lax.psum(hs[:, STAT_SUM], SHARD),
            jax.lax.psum(hs[:, STAT_RSUM], SHARD),
        ], axis=1)

        # digest union: gather every shard's centroid slots along the
        # slot axis, then one batched re-cluster into fresh planes
        gm = jax.lax.all_gather(state["histo_means"][0], SHARD,
                                axis=1, tiled=True)
        gw = jax.lax.all_gather(state["histo_weights"][0], SHARD,
                                axis=1, tiled=True)
        zm = jnp.zeros_like(state["histo_means"][0])
        zw = jnp.zeros_like(state["histo_weights"][0])
        mm, mw = tdigest._merge_impl(zm, zw, gm, gw,
                                     compression=cfg.compression)

        # widened for the all-reduce: a TPU reduces a u8 plane four
        # rows to a 32-bit word and keeps the whole word of the shard
        # whose word is largest, dropping the other shards' registers
        regs = jax.lax.pmax(state["hll"][0].astype(jnp.int32),
                            SHARD).astype(jnp.uint8)

        return {"counters": cnt, "gauges": gauges, "histo_stats": stats,
                "histo_means": mm, "histo_weights": mw, "hll": regs}

    mapped = jax.shard_map(merge, mesh=mesh, in_specs=(state_specs,),
                       out_specs=merged_specs, check_vma=False)
    return jax.jit(mapped)


def readout(merged: dict, qs: np.ndarray) -> dict:
    """Flush readout over the merged table: per-row quantiles and HLL
    estimates (row-parallel over the series sharding — XLA keeps the
    row partitioning without any reshard)."""
    quant = tdigest.quantile(
        merged["histo_means"], merged["histo_weights"],
        jnp.asarray(qs, jnp.float32),
        merged["histo_stats"][:, STAT_MIN],
        merged["histo_stats"][:, STAT_MAX])
    est = hll_ops.estimate(merged["hll"])
    return {"quantiles": quant, "hll_estimate": est}


def make_import_mesh(devices=None) -> Mesh:
    """1D all-``shard`` mesh for the collective import fold: every
    device folds wires, the series axis stays size 1 because the
    import table's planes live replicated (one host-side table).

    ``jax.devices()`` is the GLOBAL device list, so after
    :func:`init_process_mesh` this same constructor yields a mesh that
    spans every process of a ``jax.distributed`` job — the fold's
    all_gather then rides the cross-process (DCN) axis with no code
    change in the fold body itself."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devs.reshape(devs.size, 1), (SHARD, SERIES))


def init_process_mesh(coordinator_address: str | None = None,
                      num_processes: int | None = None,
                      process_id: int | None = None) -> bool:
    """Join a multi-process ``jax.distributed`` job so one global
    "node" can span hosts/slices (ROADMAP item 1: the DCN-distributed
    collective merge).

    Arguments default from the operator env knobs
    ``VENEUR_TPU_DIST_COORDINATOR`` (host:port of process 0),
    ``VENEUR_TPU_DIST_NUM_PROCS`` and ``VENEUR_TPU_DIST_PROCESS_ID``.
    Returns False (single-process mode) when no coordinator is
    configured.  On the CPU backend the cross-process collective
    implementation must be selected BEFORE the backend initializes —
    XLA:CPU refuses multi-process computations under the default
    ("Multiprocess computations aren't implemented on the CPU
    backend"), so this flips ``jax_cpu_collectives_implementation`` to
    gloo first.  Call before any other jax use in the process.
    """
    import os
    coord = coordinator_address or os.environ.get(
        "VENEUR_TPU_DIST_COORDINATOR", "")
    if not coord:
        return False
    nproc = num_processes if num_processes is not None else int(
        os.environ.get("VENEUR_TPU_DIST_NUM_PROCS", "0"))
    pid = process_id if process_id is not None else int(
        os.environ.get("VENEUR_TPU_DIST_PROCESS_ID", "-1"))
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass  # older/newer jaxlib without the knob: TPU paths need none
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc or None,
                               process_id=pid if pid >= 0 else None)
    return True


def mesh_process_count(mesh: Mesh) -> int:
    """Number of distinct processes owning the mesh's devices."""
    return len({d.process_index for d in mesh.devices.flat})


class CollectiveWireFold:
    """Mesh-sharded fold of one import cycle's wire stack.

    The serial fused path (table._wire_digest_step ->
    tdigest.merge_wire_stack_rows) scans a cycle's W wire planes one
    after another on a single device.  Here the wire axis is
    partitioned over the ``shard`` axis: each device folds its W/S
    slice with the same lax.scan/lax.cond body into ZERO-initialized
    partial planes, then the partials are unioned with one all_gather
    along the centroid-slot axis and a single k-scale re-cluster into
    the gathered table rows — the make_merge_step digest-union idiom
    applied to import folding, so fold wall-time scales with W/S
    instead of W.

    Within a shard the merge order is wire arrival order, and the
    final union is one re-cluster over (table content ++ all shards'
    partials).  When centroid spacing keeps the k-scale cluster pass
    from combining anything — under-capacity digests with >1 k-width
    between centroids — the result is bit-identical to the serial
    scan (tests pin this); in general it is an equally valid t-digest
    union of the same mass, which is why the serial path stays
    available as the oracle (VENEUR_TPU_COLLECTIVE_IMPORT=off).

    The mesh may span PROCESSES: after :func:`init_process_mesh`,
    ``make_import_mesh()`` covers every device of the
    ``jax.distributed`` job, each process stages its own local wire
    slice (``scatter_wires``), and the same all_gather union rides the
    cross-process axis — one logical global node spread over
    hosts/slices, bit-compatible with the single-host fold.
    """

    def __init__(self, mesh: Mesh,
                 compression: float = tdigest.DEFAULT_COMPRESSION):
        self.mesh = mesh
        self.n_shard = int(mesh.shape[SHARD])
        self.n_proc = mesh_process_count(mesh)
        self.compression = comp = compression

        def fold(sub_m, sub_w, stack_m, stack_w, live):
            def step(carry, wire):
                m, w = carry
                wm, ww, alive = wire

                def do_merge(ops):
                    m, w, wm, ww = ops
                    return tdigest._merge_impl(m, w, wm, ww,
                                               compression=comp)

                def skip(ops):
                    m, w, _, _ = ops
                    return m, w

                return jax.lax.cond(alive, do_merge, skip,
                                    (m, w, wm, ww)), None

            part = (jnp.zeros_like(sub_m), jnp.zeros_like(sub_w))
            (pm, pw), _ = jax.lax.scan(step, part,
                                       (stack_m, stack_w, live))
            gm = jax.lax.all_gather(pm, SHARD, axis=1, tiled=True)
            gw = jax.lax.all_gather(pw, SHARD, axis=1, tiled=True)
            return tdigest._merge_impl(sub_m, sub_w, gm, gw,
                                       compression=comp)

        mapped = jax.shard_map(
            fold, mesh=mesh,
            in_specs=(P(), P(), P(SHARD), P(SHARD), P(SHARD)),
            out_specs=(P(), P()), check_vma=False)

        @partial(jax.jit, donate_argnums=jitopts.donate(0, 1))
        def run(means, weights, row_idx, stack_m, stack_w, live):
            sub_m = tdigest._take_rows(means, row_idx)
            sub_w = tdigest._take_rows(weights, row_idx)
            sub_m, sub_w = mapped(sub_m, sub_w, stack_m, stack_w, live)
            return (means.at[row_idx].set(sub_m, mode="drop"),
                    weights.at[row_idx].set(sub_w, mode="drop"))

        self._run = run

    def pad_wires(self, n: int) -> int:
        """Wire-axis length the stack must pad to: a multiple of the
        shard count, so every device scans an equal slice.  On a
        multi-process mesh ``n`` is the PER-PROCESS local wire count
        (every process must stage the same count) and the result is
        the padded per-process length."""
        s = self.n_shard // self.n_proc
        return ((max(n, 1) + s - 1) // s) * s

    def scatter_wires(self, stack_m, stack_w, live):
        """Assemble the mesh-global wire stack from this process's
        local slice.  Single-process meshes pass through as device
        arrays; on a multi-process mesh each process contributes its
        own (equal-length, ``pad_wires``-padded) slice and the global
        wire order is process-major — the cross-process twin of the
        per-device split the shard_map applies within a host."""
        if self.n_proc <= 1:
            return (jnp.asarray(stack_m), jnp.asarray(stack_w),
                    jnp.asarray(live))
        sh = NamedSharding(self.mesh, P(SHARD))
        return tuple(
            jax.make_array_from_process_local_data(sh, np.asarray(x))
            for x in (stack_m, stack_w, live))

    def __call__(self, means, weights, row_idx, stack_m, stack_w,
                 live):
        # table planes ride in replicated (identical on every process
        # of a distributed mesh — they're the shared global table);
        # only the wire stack is scattered over the shard axis
        stack_m, stack_w, live = self.scatter_wires(stack_m, stack_w,
                                                    live)
        return self._run(means, weights, row_idx, stack_m, stack_w,
                         live)


class ShardedAggregator:
    """Host-side wrapper: per-shard columnar staging + one SPMD step.

    The host routes each sample to a shard (round-robin or by packet
    origin — any assignment is correct, the merge is a CRDT union) and
    row ids are global.  This is the ingest surface the gRPC importsrv
    listener feeds on a multi-chip global node.
    """

    def __init__(self, mesh: Mesh, cfg: ShardedConfig | None = None):
        self.mesh = mesh
        self.cfg = cfg or ShardedConfig()
        self.n_shard = mesh.shape[SHARD]
        self.state = empty_state(mesh, self.cfg)
        self._update = make_update_step(mesh, self.cfg)
        self._merge = make_merge_step(mesh, self.cfg)
        self._ticket = 0
        self._stage = [self._empty_stage() for _ in range(self.n_shard)]

    @staticmethod
    def _empty_stage():
        return {k: [] for k in (
            "counter_rows", "counter_vals", "counter_wts",
            "gauge_rows", "gauge_vals", "gauge_ticket",
            "histo_rows", "histo_vals", "histo_wts",
            "rsum_rows", "rsum_vals",
            "set_rows", "set_idx", "set_rank")}

    def next_ticket(self, n: int = 1) -> np.ndarray:
        t = np.arange(self._ticket, self._ticket + n, dtype=np.int32)
        self._ticket += n
        return t

    def stage(self, shard: int, **cols) -> None:
        st = self._stage[shard % self.n_shard]
        for k, v in cols.items():
            st[k].append(np.asarray(v))

    _DTYPES = {"counter_rows": np.int32, "counter_vals": np.float32,
               "counter_wts": np.float32, "gauge_rows": np.int32,
               "gauge_vals": np.float32, "gauge_ticket": np.int32,
               "histo_rows": np.int32, "histo_vals": np.float32,
               "histo_wts": np.float32,
               "rsum_rows": np.int32, "rsum_vals": np.float32,
               "set_rows": np.int32,
               "set_idx": np.int32, "set_rank": np.int32}

    def step(self) -> None:
        """Push staged samples through SPMD updates.

        Host pre-combine first: counters collapse to one (row, total)
        pair per touched row per shard (addition is associative), so
        the shipped batch is O(rows) regardless of sample volume —
        the same trick the single-chip table's dense accumulators
        play.  Oversized residual batches CHUNK across multiple
        update calls instead of raising.  Histo samples additionally
        chunk by within-row rank so no row exceeds ``cfg.slots``
        samples per call — ``densify`` drops beyond the slot width
        (the contract the single-chip table honors in
        ``_histo_device_step``).
        """
        n = self.cfg.batch
        cols = {}
        for key, dt in self._DTYPES.items():
            planes = []
            for st in self._stage:
                col = (np.concatenate([np.asarray(a, dt).ravel()
                                       for a in st[key]])
                       if st[key] else np.zeros(0, dt))
                planes.append(col)
            cols[key] = planes
        self._stage = [self._empty_stage() for _ in range(self.n_shard)]

        # counter pre-combine per shard: bincount over touched rows
        for si in range(self.n_shard):
            rows = cols["counter_rows"][si]
            if len(rows) <= 1:
                continue
            totals = np.bincount(
                rows, weights=cols["counter_vals"][si] *
                cols["counter_wts"][si], minlength=0)
            touched = np.nonzero(totals)[0]
            cols["counter_rows"][si] = touched.astype(np.int32)
            cols["counter_vals"][si] = totals[touched].astype(
                np.float32)
            cols["counter_wts"][si] = np.ones(len(touched), np.float32)

        # per-shard selection lists, one entry per update call:
        # histo selections group by within-row rank (rank // slots —
        # densify's drop contract) THEN split to <= batch; the other
        # classes split positionally to <= batch
        def _pos_sels(length: int) -> list[np.ndarray]:
            return [np.arange(off, min(off + n, length))
                    for off in range(0, length, n)] or []

        def _histo_sels(rows: np.ndarray) -> list[np.ndarray]:
            if len(rows) == 0:
                return []
            order = np.argsort(rows, kind="stable")
            srows = rows[order]
            first = np.ones(len(rows), bool)
            first[1:] = srows[1:] != srows[:-1]
            start = np.maximum.accumulate(
                np.where(first, np.arange(len(rows)), 0))
            rank = np.empty(len(rows), np.int64)
            rank[order] = np.arange(len(rows)) - start
            primary = rank // self.cfg.slots
            sels = []
            for ci in range(int(primary.max()) + 1):
                idx = np.nonzero(primary == ci)[0]
                for off in range(0, len(idx), n):
                    sels.append(idx[off:off + n])
            return sels

        group_of = {"counter_rows": "counter", "counter_vals": "counter",
                    "counter_wts": "counter", "gauge_rows": "gauge",
                    "gauge_vals": "gauge", "gauge_ticket": "gauge",
                    "histo_rows": "histo", "histo_vals": "histo",
                    "histo_wts": "histo",
                    "rsum_rows": "rsum", "rsum_vals": "rsum",
                    "set_rows": "set",
                    "set_idx": "set", "set_rank": "set"}
        sels: dict[tuple[str, int], list[np.ndarray]] = {}
        n_calls = 0
        for si in range(self.n_shard):
            sels[("histo", si)] = _histo_sels(cols["histo_rows"][si])
            for grp, key in (("counter", "counter_rows"),
                             ("gauge", "gauge_rows"),
                             ("rsum", "rsum_rows"),
                             ("set", "set_rows")):
                sels[(grp, si)] = _pos_sels(len(cols[key][si]))
            n_calls = max(n_calls, *(len(sels[(g, si)]) for g in
                                     ("histo", "counter", "gauge",
                                      "rsum", "set")), 0)

        specs = batch_specs()
        for ci in range(n_calls):
            batch = {}
            for key, dt in self._DTYPES.items():
                fill = {"counter_rows": self.cfg.c_rows(),
                        "gauge_rows": self.cfg.g_rows(),
                        "histo_rows": self.cfg.rows,
                        "rsum_rows": self.cfg.rows,
                        "set_rows": self.cfg.set_rows,
                        "gauge_ticket": -1}.get(key, 0)
                planes = []
                for si in range(self.n_shard):
                    grp_sels = sels[(group_of[key], si)]
                    col = (cols[key][si][grp_sels[ci]]
                           if ci < len(grp_sels) else
                           cols[key][si][:0])
                    plane = np.full(n, fill, dt)
                    plane[:len(col)] = col
                    planes.append(plane)
                batch[key] = np.stack(planes)
            jbatch = {k: jax.device_put(
                jnp.asarray(v), NamedSharding(self.mesh, specs[k]))
                for k, v in batch.items()}
            self.state = self._update(self.state, jbatch)

    def flush(self, qs=(0.5, 0.9, 0.99)) -> dict:
        """Merge partial shards with collectives and read out."""
        merged = self._merge(self.state)
        out = readout(merged, np.asarray(qs, np.float32))
        merged.update(out)
        return merged

    def swap(self) -> dict:
        """Interval boundary: push any staged work, merge, and reset
        the partial state for the next interval (the double-buffer
        swap the single-chip table does at flush, worker.go:498).

        The merge is FENCED before returning: its collectives must
        finish while no other device program can be dispatched.  On
        an oversubscribed host (virtual CPU mesh, or a shared-core
        TPU host under ingest load) a partition of an in-flight
        collective can starve past XLA's 40s rendezvous termination
        — which aborts the whole process — if later-dispatched
        programs compete for the executor pool.  One synchronous
        point per flush interval costs ~nothing next to what it
        rules out."""
        self.step()
        merged = self._merge(self.state)
        jax.block_until_ready(merged)
        self.state = empty_state(self.mesh, self.cfg)
        return merged


class ShardedTable:
    """MetricTable-compatible facade over a device mesh: the surface
    ``core.Server``/``Flusher`` drive (ingest / import_* / device_step
    / swap -> Snapshot), backed by the SPMD sharded planes.  A
    multi-chip global node runs through the ordinary Server path with
    this table (config: ``tpu_mesh_shards``); gRPC imports land in
    host staging here exactly as on the single-chip table, and the
    flush-time shard merge rides ICI collectives.

    Replaces the reference's importsrv worker fan-in + proxy tier for
    nodes that share a slice (importsrv/server.go:102, collapsed to
    collectives)."""

    def __init__(self, mesh: Mesh, cfg: ShardedConfig | None = None):
        from veneur_tpu.core import table as core_table
        self.mesh = mesh
        self.cfg = cfg or ShardedConfig()
        self.agg = ShardedAggregator(mesh, self.cfg)
        self.gen = 0
        self.counter_idx = core_table._ClassIndex(self.cfg.c_rows())
        self.gauge_idx = core_table._ClassIndex(self.cfg.g_rows())
        self.histo_idx = core_table._ClassIndex(self.cfg.rows)
        self.set_idx = core_table._ClassIndex(self.cfg.set_rows)
        self.status: dict = {}
        # gRPC import fast path's identity-hash -> row cache (see
        # core/table.py) — the facade never compacts, so no
        # invalidation hook is needed; the size bound below guards
        # churning-identity growth (cleared + rebuilt when hit)
        self.import_row_cache: dict[int, int] = {}
        self.import_row_cache_limit = 4 * (
            2 * self.cfg.c_rows() + self.cfg.rows +
            self.cfg.set_rows) + 1024
        self._staged_n = 0
        # interval conservation count at ITEM granularity, matching
        # the single-chip table's (table.py _note_staged): the ledger
        # cross-checks it against site-credited staged totals
        self._interval_ingested = 0
        self._rr = 0  # round-robin shard cursor

    # -- ingest (the slow-path Sample surface the Server uses) --------

    def _next_shard(self) -> int:
        self._rr = (self._rr + 1) % self.agg.n_shard
        return self._rr

    def ingest(self, s) -> bool:
        from veneur_tpu.protocol import dogstatsd as dsd
        from veneur_tpu.utils import hashing
        key = (s.name, s.type, s.tags, s.scope)
        weight = 1.0 / s.sample_rate
        sh = self._next_shard()
        if s.type == dsd.COUNTER:
            row = self.counter_idx.lookup(key, s.name, s.tags,
                                          s.scope, s.type, self.gen)
            if row is None:
                return False
            self.agg.stage(sh, counter_rows=[row],
                           counter_vals=[s.value],
                           counter_wts=[weight])
        elif s.type == dsd.GAUGE:
            row = self.gauge_idx.lookup(key, s.name, s.tags, s.scope,
                                        s.type, self.gen)
            if row is None:
                return False
            self.agg.stage(sh, gauge_rows=[row],
                           gauge_vals=[s.value],
                           gauge_ticket=self.agg.next_ticket())
        elif s.type in (dsd.TIMER, dsd.HISTOGRAM):
            row = self.histo_idx.lookup(key, s.name, s.tags, s.scope,
                                        s.type, self.gen)
            if row is None:
                return False
            self.agg.stage(sh, histo_rows=[row], histo_vals=[s.value],
                           histo_wts=[weight])
        elif s.type == dsd.SET:
            row = self.set_idx.lookup(key, s.name, s.tags, s.scope,
                                      s.type, self.gen)
            if row is None:
                return False
            member = (s.value if isinstance(s.value, bytes)
                      else str(s.value).encode())
            idx, rank = hashing.hash_members([member])
            self.agg.stage(sh, set_rows=[row], set_idx=idx,
                           set_rank=rank)
        elif s.type == dsd.STATUS:
            self.status[key] = (float(s.value), s.message, s.tags)
            return True
        else:
            raise ValueError(f"unknown metric type {s.type}")
        self._staged_n += 1
        self._interval_ingested += 1
        return True

    def ingest_many(self, samples) -> int:
        dropped = 0
        for s in samples:
            if not self.ingest(s):
                dropped += 1
        return dropped

    def ingest_columns(self, pb) -> tuple[int, int]:
        """Columnar parse batches sweep through the per-sample path: a
        mesh global node's hot ingest is the gRPC import plane, not
        raw DSD volume, so the single-chip table's vectorized identity
        index is not replicated here.  Lines the caller handles
        (events/checks/errors, type codes past CODE_SET) are left to
        its slow sweep."""
        from veneur_tpu.protocol import columnar
        from veneur_tpu.protocol import dogstatsd as dsd
        processed = dropped = 0
        fast = np.nonzero(pb.type_code[:pb.n] <=
                          columnar.CODE_SET)[0]
        for i in fast:
            try:
                parsed = dsd.parse_line(pb.line(int(i)))
            except dsd.ParseError:
                dropped += 1
                continue
            if self.ingest(parsed):
                processed += 1
            else:
                dropped += 1
        return processed, dropped

    # -- global-tier imports ------------------------------------------

    # -- cached-fast-path surface (forward/grpc_forward
    #    apply_metric_list_bytes): row-resolution halves + batch
    #    appliers.  The facade never compacts, so the cache (filled
    #    by the forward module) needs no invalidation hook ----------

    def import_counter_row(self, name, tags):
        from veneur_tpu.protocol import dogstatsd as dsd
        return self.counter_idx.lookup(
            (name, dsd.COUNTER, tags, dsd.SCOPE_GLOBAL), name, tags,
            dsd.SCOPE_GLOBAL, dsd.COUNTER, self.gen)

    def import_gauge_row(self, name, tags):
        from veneur_tpu.protocol import dogstatsd as dsd
        return self.gauge_idx.lookup(
            (name, dsd.GAUGE, tags, dsd.SCOPE_GLOBAL), name, tags,
            dsd.SCOPE_GLOBAL, dsd.GAUGE, self.gen)

    def import_set_row(self, name, tags, scope=None):
        from veneur_tpu.protocol import dogstatsd as dsd
        scope = scope or dsd.SCOPE_DEFAULT
        return self.set_idx.lookup((name, dsd.SET, tags, scope), name,
                                   tags, scope, dsd.SET, self.gen)

    def import_counter_batch(self, rows, values) -> None:
        rows = np.ascontiguousarray(rows, np.int64)
        self.agg.stage(self._next_shard(),
                       counter_rows=rows.astype(np.int32),
                       counter_vals=np.asarray(values, np.float32),
                       counter_wts=np.ones(len(rows), np.float32))
        self.counter_idx.touch_rows(rows, self.gen)
        self._staged_n += len(rows)
        self._interval_ingested += len(rows)

    def import_gauge_batch(self, rows, values) -> None:
        # one ticket per write preserves last-write-wins in wire
        # order across the whole mesh (stage() takes one ticket per
        # call, so gauges stage individually)
        rows = np.ascontiguousarray(rows, np.int64)
        values = np.asarray(values, np.float64)
        for r, v in zip(rows, values):
            self.agg.stage(self._next_shard(), gauge_rows=[int(r)],
                           gauge_vals=[float(v)],
                           gauge_ticket=self.agg.next_ticket())
        self.gauge_idx.touch_rows(rows, self.gen)
        self._staged_n += len(rows)
        self._interval_ingested += len(rows)

    def import_set_at(self, row, regs) -> None:
        regs = np.asarray(regs, np.uint8)
        if regs.shape != (hll_ops.M,):
            raise ValueError(f"bad register plane shape {regs.shape}")
        nz = np.nonzero(regs)[0]
        if len(nz):
            self.agg.stage(self._next_shard(),
                           set_rows=np.full(len(nz), int(row),
                                            np.int32),
                           set_idx=nz.astype(np.int32),
                           set_rank=regs[nz].astype(np.int32))
        self.set_idx.touched[row] = True
        self.set_idx.last_gen[row] = self.gen
        self._staged_n += max(1, len(nz))
        self._interval_ingested += 1

    def import_counter(self, name, tags, value) -> bool:
        from veneur_tpu.protocol import dogstatsd as dsd
        row = self.counter_idx.lookup(
            (name, dsd.COUNTER, tags, dsd.SCOPE_GLOBAL), name, tags,
            dsd.SCOPE_GLOBAL, dsd.COUNTER, self.gen)
        if row is None:
            return False
        self.agg.stage(self._next_shard(), counter_rows=[row],
                       counter_vals=[value], counter_wts=[1.0])
        self._staged_n += 1
        self._interval_ingested += 1
        return True

    def import_gauge(self, name, tags, value) -> bool:
        from veneur_tpu.protocol import dogstatsd as dsd
        row = self.gauge_idx.lookup(
            (name, dsd.GAUGE, tags, dsd.SCOPE_GLOBAL), name, tags,
            dsd.SCOPE_GLOBAL, dsd.GAUGE, self.gen)
        if row is None:
            return False
        self.agg.stage(self._next_shard(), gauge_rows=[row],
                       gauge_vals=[value],
                       gauge_ticket=self.agg.next_ticket())
        self._staged_n += 1
        self._interval_ingested += 1
        return True

    def import_histo_row(self, name, mtype, tags, scope=None):
        from veneur_tpu.protocol import dogstatsd as dsd
        scope = scope or dsd.SCOPE_DEFAULT
        return self.histo_idx.lookup((name, mtype, tags, scope), name,
                                     tags, scope, mtype, self.gen)

    def import_histo(self, name, mtype, tags, stats, means, weights,
                     scope=None) -> bool:
        """Forwarded digest: centroids re-enter as weighted samples
        (a centroid IS a weighted sample; min/max ride separately as
        two weight-epsilon anchor samples so the merged stats keep the
        true extremes, and the reciprocal-sum delta lands in a direct
        RSUM correction — centroid means alone misstate it)."""
        import numpy as _np
        from veneur_tpu.ops import segment
        # shapes validated BEFORE anything stages, matching the
        # single-chip contract (table.py import_histo): a malformed
        # item must not leave half its state staged
        stats = _np.asarray(stats, _np.float32)
        means = _np.asarray(means, _np.float32)
        weights = _np.asarray(weights, _np.float32)
        if stats.shape != (segment.HISTO_STAT_COLS,):
            raise ValueError(f"bad stats shape {stats.shape}")
        if means.shape != weights.shape or means.ndim != 1:
            raise ValueError(
                f"centroid shape mismatch {means.shape}/"
                f"{weights.shape}")
        row = self.import_histo_row(name, mtype, tags, scope)
        if row is None:
            return False
        live = weights > 0
        n_live = int(live.sum())
        sh = self._next_shard()
        eps = _np.float32(1e-6)
        rsum_from_samples = 0.0
        if n_live:
            self.agg.stage(sh,
                           histo_rows=_np.full(n_live, row, _np.int32),
                           histo_vals=means[live],
                           histo_wts=weights[live])
            nz = live & (means != 0)
            rsum_from_samples = float(
                (weights[nz] / means[nz]).sum())
        w = float(stats[segment.STAT_WEIGHT])
        if w > 0:
            # zero-ish-weight anchors carry the forwarded min/max into
            # the stat plane without perturbing sums
            mn = float(stats[segment.STAT_MIN])
            mx = float(stats[segment.STAT_MAX])
            self.agg.stage(sh, histo_rows=[row, row],
                           histo_vals=[mn, mx], histo_wts=[eps, eps])
            if mn != 0:
                rsum_from_samples += float(eps) / mn
            if mx != 0:
                rsum_from_samples += float(eps) / mx
        # exact forwarded rsum minus what the staged samples will add
        corr = float(stats[segment.STAT_RSUM]) - rsum_from_samples
        if corr:
            self.agg.stage(sh, rsum_rows=[row], rsum_vals=[corr])
        # count every ACTUALLY staged item: the staging-memory bound
        # that triggers device_step rides on this counter (table.py:694)
        self._staged_n += (n_live + (2 if w > 0 else 0) +
                           (1 if corr else 0))
        self._interval_ingested += 1
        return True

    def import_histo_batch(self, rows, stats, cent_rows, cent_means,
                           cent_weights) -> None:
        """Columnar sibling of import_histo with the SAME fidelity:
        min/max eps anchors and an exact per-row RSUM correction (the
        gRPC import fast path must not diverge from the scalar
        path)."""
        import numpy as _np
        from veneur_tpu.ops import segment
        rows = _np.ascontiguousarray(rows, _np.int64)
        sh = self._next_shard()
        n_staged = 0
        nrows = self.cfg.rows
        # per-row rsum contribution of the staged centroids
        rsum_samples = _np.zeros(nrows, _np.float64)
        if len(cent_rows):
            self.agg.stage(sh, histo_rows=cent_rows,
                           histo_vals=cent_means,
                           histo_wts=cent_weights)
            n_staged += len(cent_rows)
            cr = _np.ascontiguousarray(cent_rows, _np.int64)
            nz = cent_means != 0
            rsum_samples += _np.bincount(
                cr[nz], weights=cent_weights[nz] / cent_means[nz],
                minlength=nrows)[:nrows]
        live = stats[:, segment.STAT_WEIGHT] > 0
        if live.any():
            eps = _np.float32(1e-6)
            r = rows[live]
            mns = stats[live, segment.STAT_MIN]
            mxs = stats[live, segment.STAT_MAX]
            self.agg.stage(
                sh,
                histo_rows=_np.concatenate([r, r]).astype(_np.int32),
                histo_vals=_np.concatenate([mns, mxs]),
                histo_wts=_np.full(2 * len(r), eps, _np.float32))
            n_staged += 2 * len(r)
            for vals in (mns, mxs):
                vnz = vals != 0
                rsum_samples += _np.bincount(
                    r[vnz], weights=float(eps) / vals[vnz],
                    minlength=nrows)[:nrows]
        # exact forwarded rsum per row minus what the samples will add
        rsum_true = _np.bincount(
            rows, weights=stats[:, segment.STAT_RSUM].astype(
                _np.float64), minlength=nrows)[:nrows]
        corr = rsum_true - rsum_samples
        crows = _np.nonzero(corr)[0]
        if len(crows):
            self.agg.stage(sh, rsum_rows=crows.astype(_np.int32),
                           rsum_vals=corr[crows].astype(_np.float32))
            n_staged += len(crows)
        # rows may arrive cache-resolved (no lookup ran): touch them
        # so flush emission sees the series
        self.histo_idx.touch_rows(rows, self.gen)
        self._staged_n += n_staged
        self._interval_ingested += len(rows)

    def import_set(self, name, tags, regs, scope=None) -> bool:
        """Forwarded HLL plane: registers convert to (idx, rank)
        positions (a register IS the max rank seen at that index)."""
        import numpy as _np
        from veneur_tpu.protocol import dogstatsd as dsd
        regs = _np.asarray(regs, _np.uint8)
        if regs.shape != (hll_ops.M,):
            raise ValueError(f"bad register plane shape {regs.shape}")
        scope = scope or dsd.SCOPE_DEFAULT
        row = self.set_idx.lookup((name, dsd.SET, tags, scope), name,
                                  tags, scope, dsd.SET, self.gen)
        if row is None:
            return False
        nz = _np.nonzero(regs)[0]
        if len(nz):
            self.agg.stage(self._next_shard(),
                           set_rows=_np.full(len(nz), row, _np.int32),
                           set_idx=nz.astype(_np.int32),
                           set_rank=regs[nz].astype(_np.int32))
        self._staged_n += max(1, len(nz))
        self._interval_ingested += 1
        return True

    # -- lifecycle -----------------------------------------------------

    def staged(self) -> int:
        return self._staged_n

    def overflow_total(self) -> int:
        """Interval overflow drops summed over classes — same surface
        as the single-chip table's (table.py): import call sites delta
        this around an apply to split dropped counts into overflow vs
        invalid for the conservation ledger."""
        return (self.counter_idx.overflow + self.gauge_idx.overflow +
                self.histo_idx.overflow + self.set_idx.overflow)

    def plane_devices(self) -> dict[str, int]:
        """How many devices hold each state plane: the mesh's size
        for every plane when the table is spread."""
        return {name: len(a.sharding.device_set)
                for name, a in self.agg.state.items()}

    def device_step(self, final: bool = False) -> None:
        if final or self._staged_n >= self.cfg.batch:
            self.agg.step()
            self._staged_n = 0

    def take_status(self):
        out = self.status
        self.status = {}
        return out

    def swap(self):
        """Interval boundary -> a core-table Snapshot the Flusher
        consumes unchanged: merged planes land in the same fields the
        single-chip table fills, with the merged stat plane serving as
        the local-stats plane and an identity import plane."""
        from veneur_tpu.core import table as core_table
        from veneur_tpu.ops import segment
        self.device_step(final=True)
        merged = self.agg.swap()
        rows, set_rows = self.cfg.rows, self.cfg.set_rows
        imp = np.zeros((rows, segment.HISTO_STAT_COLS), np.float32)
        imp[:, segment.STAT_MIN] = segment.STAT_MIN_EMPTY
        imp[:, segment.STAT_MAX] = segment.STAT_MAX_EMPTY
        snap = core_table.Snapshot(
            gen=self.gen,
            counters=merged["counters"],
            counter_meta=list(self.counter_idx.meta),
            counter_touched=self.counter_idx.touched.copy(),
            gauges=merged["gauges"],
            gauge_meta=list(self.gauge_idx.meta),
            gauge_touched=self.gauge_idx.touched.copy(),
            histo_stats=merged["histo_stats"],
            histo_import_stats=imp,
            histo_means=merged["histo_means"],
            histo_weights=merged["histo_weights"],
            histo_meta=list(self.histo_idx.meta),
            histo_touched=self.histo_idx.touched.copy(),
            hll_regs=merged["hll"],
            set_meta=list(self.set_idx.meta),
            set_touched=self.set_idx.touched.copy(),
            sink_only_rows=sum(
                idx.sink_only_rows for idx in (
                    self.counter_idx, self.gauge_idx, self.histo_idx,
                    self.set_idx)),
            hll_host_plane=None,
            hll_device_touched=True,
            overflow={
                "counter": self.counter_idx.overflow,
                "gauge": self.gauge_idx.overflow,
                "histo": self.histo_idx.overflow,
                "set": self.set_idx.overflow,
            },
            ingested=self._interval_ingested)
        self._interval_ingested = 0
        self.gen += 1
        for idx in (self.counter_idx, self.gauge_idx, self.histo_idx,
                    self.set_idx):
            idx.reset_interval()
        return snap
