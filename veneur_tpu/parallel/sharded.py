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
  histo_import_stats  (same)     the forwarded digests' statistics, a
                                 plane of their own as on one chip
  histo_means   f32[S, R, C]     merge: all_gather slots + one k-scale
  histo_weights f32[S, R, C]            re-cluster (ops.tdigest)
  hll           u8[S, R, M]      merge: pmax over shard (register max,
                                        reduced as i32)

The update step and the merge step are each one ``shard_map``-ped jitted
function; everything between flushes is pure per-device work with zero
communication, and the flush-time collectives ride ICI.
"""

from __future__ import annotations

import contextlib
import logging
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

log = logging.getLogger("veneur_tpu.parallel.sharded")

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
        "histo_import_stats": P(SHARD, SERIES, None),
        "histo_means": P(SHARD, SERIES, None),
        "histo_weights": P(SHARD, SERIES, None),
        "hll": P(SHARD, SERIES, None),
    }


def make_empty_state(mesh: Mesh, cfg: ShardedConfig):
    """Jitted program with no input: a fresh state pytree, made on the
    mesh in its sharding.  Every plane is a broadcast constant, so
    nothing is built on the host or crosses to the device."""
    s = mesh.shape[SHARD]
    r, rs = cfg.rows, cfg.set_rows
    rc, rg = cfg.c_rows(), cfg.g_rows()
    cap = cfg.capacity()
    empty_row = np.zeros(HISTO_STAT_COLS, np.float32)
    empty_row[STAT_MIN] = STAT_MIN_EMPTY
    empty_row[STAT_MAX] = STAT_MAX_EMPTY

    def fresh():
        def stats():
            return jnp.broadcast_to(jnp.asarray(empty_row),
                                    (s, r, HISTO_STAT_COLS))
        return {
            "counters": jnp.zeros((s, rc), jnp.float32),
            "gauges": jnp.zeros((s, rg), jnp.float32),
            "gauge_ticket": jnp.full((s, rg), -1, jnp.int32),
            "histo_stats": stats(),
            "histo_import_stats": stats(),
            "histo_means": jnp.zeros((s, r, cap), jnp.float32),
            "histo_weights": jnp.zeros((s, r, cap), jnp.float32),
            "hll": jnp.zeros((s, rs, hll_ops.M), jnp.uint8),
        }

    return jax.jit(fresh, out_shardings={
        k: NamedSharding(mesh, spec)
        for k, spec in _specs(mesh).items()})


def empty_state(mesh: Mesh, cfg: ShardedConfig) -> dict:
    """Allocate the sharded state pytree on the mesh."""
    return make_empty_state(mesh, cfg)()


# a forwarded digest's statistics, staged as a row of their own in
# the stat columns' order
ISTAT_COLS = ("istat_weight", "istat_min", "istat_max", "istat_sum",
              "istat_rsum")

# the staged batch, column by column: its dtype and the group whose
# rows it belongs to (a group's columns are staged together and share
# a length)
BATCH_COLS = {
    "counter_rows": (np.int32, "counter"),
    "counter_vals": (np.float32, "counter"),
    "counter_wts": (np.float32, "counter"),
    "gauge_rows": (np.int32, "gauge"),
    "gauge_vals": (np.float32, "gauge"),
    "gauge_ticket": (np.int32, "gauge"),
    "histo_rows": (np.int32, "histo"),
    "histo_vals": (np.float32, "histo"),
    "histo_wts": (np.float32, "histo"),
    "histo_imp": (np.int32, "histo"),    # 1: a forwarded centroid
    "istat_rows": (np.int32, "istat"),
    **dict.fromkeys(ISTAT_COLS, (np.float32, "istat")),
    "set_rows": (np.int32, "set"),
    "set_idx": (np.int32, "set"),
    "set_rank": (np.int32, "set"),
}


def batch_specs():
    """Batch arrays are [S, N]: split over shard, replicated over
    series (each series-device sees the full batch and keeps only the
    row ids that fall in its block)."""
    return dict.fromkeys(BATCH_COLS, P(SHARD, None))


def _scatter_stats(hs, rows, incoming):
    """Stat rows ``incoming`` f32[N, 5] into the plane ``hs`` at the
    block-local ``rows`` (the drop sentinel leaves it alone)."""
    return jnp.stack([
        hs[:, STAT_WEIGHT].at[rows].add(incoming[:, STAT_WEIGHT],
                                        mode="drop"),
        hs[:, STAT_MIN].at[rows].min(incoming[:, STAT_MIN],
                                     mode="drop"),
        hs[:, STAT_MAX].at[rows].max(incoming[:, STAT_MAX],
                                     mode="drop"),
        hs[:, STAT_SUM].at[rows].add(incoming[:, STAT_SUM],
                                     mode="drop"),
        hs[:, STAT_RSUM].at[rows].add(incoming[:, STAT_RSUM],
                                      mode="drop"),
    ], axis=1)


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

    def shard_update(state, batch):
        # every local plane has leading shard dim 1 — squeeze it
        cnt = state["counters"][0]
        g = state["gauges"][0]
        gt = state["gauge_ticket"][0]
        hs = state["histo_stats"][0]
        his = state["histo_import_stats"][0]
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
        # a forwarded centroid feeds the digest alone: its digest's
        # statistics come exact as a row of their own, into their own
        # plane (a global emits a mixed-scope row's aggregates from
        # what it sampled itself, samplers.go:530-621)
        lw = jnp.where(batch["histo_imp"][0] > 0, 0.0, hwt)
        hs = jax.lax.cond(
            jnp.any(lw > 0),     # a batch of forwarded centroids: none
            lambda: _scatter_stats(hs, hrow, jnp.stack([
                lw, jnp.where(lw > 0, hv, STAT_MIN_EMPTY),
                jnp.where(lw > 0, hv, STAT_MAX_EMPTY), hv * lw,
                jnp.where(hv != 0, lw / hv, 0.0)], axis=1)),
            lambda: hs)
        irow = _localize(batch["istat_rows"][0], r_local, SERIES)
        his = _scatter_stats(his, irow, jnp.stack(
            [batch[k][0] for k in ISTAT_COLS], axis=1))

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
            "histo_import_stats": his[None],
            "histo_means": hm[None], "histo_weights": hw[None],
            "hll": regs[None],
        }

    mapped = jax.shard_map(shard_update, mesh=mesh,
                           in_specs=(state_specs, batch_specs()),
                           out_specs=state_specs, check_vma=False)
    return jax.jit(mapped, donate_argnums=jitopts.donate(0))


def _union_registers(regs):
    """The register max over the shard axis, inside the merge's
    shard_map.  Widened for the all-reduce: a TPU reduces a u8 plane
    four rows to a 32-bit word and keeps the whole word of the shard
    whose word is largest, dropping the other shards' registers."""
    return jax.lax.pmax(regs.astype(jnp.int32), SHARD).astype(jnp.uint8)


def make_merge_step(mesh: Mesh, cfg: ShardedConfig):
    """Jitted SPMD flush merge: partial per-shard state -> one merged
    table, via ICI collectives.

    counter psum / gauge ticket-pmax / stat psum+pmin+pmax (the
    sampled and the forwarded plane alike) / t-digest
    all_gather+re-cluster / HLL register pmax — the device-side
    equivalent of the reference's import-merge semantics
    (samplers.go:208 Counter.Merge, :423 Set.Merge, :726 Histo.Merge).
    """
    state_specs = _specs(mesh)
    merged_specs = {
        "counters": P(SERIES), "gauges": P(SERIES),
        "histo_stats": P(SERIES, None),
        "histo_import_stats": P(SERIES, None),
        "histo_means": P(SERIES, None),
        "histo_weights": P(SERIES, None),
        "hll": P(SERIES, None),
    }

    def union_stats(hs):
        return jnp.stack([
            jax.lax.psum(hs[:, STAT_WEIGHT], SHARD),
            jax.lax.pmin(hs[:, STAT_MIN], SHARD),
            jax.lax.pmax(hs[:, STAT_MAX], SHARD),
            jax.lax.psum(hs[:, STAT_SUM], SHARD),
            jax.lax.psum(hs[:, STAT_RSUM], SHARD),
        ], axis=1)

    def shard_merge(state):
        cnt = jax.lax.psum(state["counters"][0], SHARD)

        ticket = state["gauge_ticket"][0]
        best = jax.lax.pmax(ticket, SHARD)
        gv = jax.lax.pmax(
            jnp.where((ticket == best) & (best >= 0),
                      state["gauges"][0], -jnp.inf), SHARD)
        gauges = jnp.where(best >= 0, gv, 0.0)

        stats = union_stats(state["histo_stats"][0])
        istats = union_stats(state["histo_import_stats"][0])

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

        regs = _union_registers(state["hll"][0])

        return {"counters": cnt, "gauges": gauges, "histo_stats": stats,
                "histo_import_stats": istats,
                "histo_means": mm, "histo_weights": mw, "hll": regs}

    mapped = jax.shard_map(shard_merge, mesh=mesh,
                           in_specs=(state_specs,),
                           out_specs=merged_specs, check_vma=False)
    return jax.jit(mapped)


_max_planes = jax.jit(jnp.maximum)


def readout(merged: dict, qs: np.ndarray) -> dict:
    """Flush readout over the merged table: per-row quantiles and HLL
    estimates (row-parallel over the series sharding — XLA keeps the
    row partitioning without any reshard)."""
    quant = tdigest.quantile(
        merged["histo_means"], merged["histo_weights"],
        jnp.asarray(qs, jnp.float32),
        jnp.minimum(merged["histo_stats"][:, STAT_MIN],
                    merged["histo_import_stats"][:, STAT_MIN]),
        jnp.maximum(merged["histo_stats"][:, STAT_MAX],
                    merged["histo_import_stats"][:, STAT_MAX]))
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
        # the fresh state, made on the chips: the first one here (which
        # compiles the program before traffic), the next at each reset
        self._empty_state = make_empty_state(mesh, self.cfg)
        self.state = self._empty_state()
        self._update = make_update_step(mesh, self.cfg)
        self._merge = make_merge_step(mesh, self.cfg)
        self._ticket = 0
        self._stage = [self._empty_stage() for _ in range(self.n_shard)]
        self._batch_sharding = {
            k: NamedSharding(mesh, spec)
            for k, spec in batch_specs().items()}
        # what pads a batch column: the drop sentinel for rows
        self._fill = {"counter_rows": self.cfg.c_rows(),
                      "gauge_rows": self.cfg.g_rows(),
                      "histo_rows": self.cfg.rows,
                      "istat_rows": self.cfg.rows,
                      "set_rows": self.cfg.set_rows, "gauge_ticket": -1}
        # what the merge does on this mesh, for the flush's record and
        # its ``shard_merge`` span: every shard's slots gathered a
        # row, and the digest path that width takes
        cap = self.cfg.capacity()
        self.merge_info = {
            "mesh": f"{self.n_shard}x{mesh.shape[SERIES]}",
            "shards": self.n_shard, "series": mesh.shape[SERIES],
            "gathered_slots": cap * self.n_shard,
            "merge_path": tdigest.merge_path(cap, cap * self.n_shard)}
        if self.merge_info["merge_path"] != tdigest.resolved_merge_mode():
            log.warning(
                "mesh %s gathers %d slots a digest row, past the fused "
                "merge kernel's lanes: the flush merge takes the %s "
                "path", self.merge_info["mesh"],
                self.merge_info["gathered_slots"],
                self.merge_info["merge_path"])
        # the interval's SPMD update calls and the items staged to
        # each shard (every staged column entry, every unioned sketch)
        self.steps = 0
        self.staged = [0] * self.n_shard
        # forwarded sketches: a host register plane a shard, unioned
        # natively as the wires arrive and shipped whole at the merge;
        # made with its touched mask at the first imported sketch.
        # Two such buffers: ``reset`` turns to the clean one, and the
        # closed interval's is cleared by ``clear_closed`` once its
        # flush has delivered, off the ingest lock
        self._set_planes: np.ndarray | None = None
        self._set_touched = np.zeros(
            (self.n_shard, self.cfg.set_rows), bool)
        self._closed_planes: np.ndarray | None = None
        self._closed_touched = np.zeros_like(self._set_touched)
        # what the last reset did on the host: the bytes it cleared
        # itself (0 unless the closed buffer was not cleared after its
        # flush) and the bytes it left to ``clear_closed``
        self.reset_host_bytes = 0
        self.reset_deferred_bytes = 0

    @staticmethod
    def _empty_stage():
        return {k: [] for k in BATCH_COLS}

    def next_ticket(self, n: int = 1) -> np.ndarray:
        t = np.arange(self._ticket, self._ticket + n, dtype=np.int32)
        self._ticket += n
        return t

    def stage(self, shard: int, **cols) -> None:
        shard %= self.n_shard
        st = self._stage[shard]
        if "histo_rows" in cols and "histo_imp" not in cols:
            # samples of this node's own: not forwarded
            cols["histo_imp"] = np.zeros(np.size(cols["histo_rows"]),
                                         np.int32)
        for k, v in cols.items():
            v = np.asarray(v)
            st[k].append(v)
            if k in self._COUNTED:
                self.staged[shard] += v.size

    # one column a staged group: its length is the group's items
    _COUNTED = ("counter_rows", "gauge_rows", "histo_rows", "istat_rows",
                "set_rows")

    def set_plane(self, shard: int) -> tuple[np.ndarray, np.ndarray]:
        """(register plane u8[set_rows, M], touched rows) of the
        host-side union of ``shard``'s forwarded sketches."""
        if self._set_planes is None:
            shape = (self.n_shard, self.cfg.set_rows, hll_ops.M)
            self._set_planes = np.zeros(shape, np.uint8)
            self._closed_planes = np.zeros(shape, np.uint8)
        shard %= self.n_shard
        return self._set_planes[shard], self._set_touched[shard]

    _DTYPES = {k: dt for k, (dt, _) in BATCH_COLS.items()}

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

        sels: dict[tuple[str, int], list[np.ndarray]] = {}
        n_calls = 0
        for si in range(self.n_shard):
            sels[("histo", si)] = _histo_sels(cols["histo_rows"][si])
            for grp, key in (("counter", "counter_rows"),
                             ("gauge", "gauge_rows"),
                             ("istat", "istat_rows"),
                             ("set", "set_rows")):
                sels[(grp, si)] = _pos_sels(len(cols[key][si]))
            n_calls = max(n_calls, *(len(sels[(g, si)]) for g in
                                     ("histo", "counter", "gauge",
                                      "istat", "set")), 0)

        self.steps += n_calls
        for ci in range(n_calls):
            batch = {}
            for key, dt in self._DTYPES.items():
                fill = self._fill.get(key, 0)
                planes = []
                for si in range(self.n_shard):
                    grp_sels = sels[(BATCH_COLS[key][1], si)]
                    col = (cols[key][si][grp_sels[ci]]
                           if ci < len(grp_sels) else
                           cols[key][si][:0])
                    plane = np.full(n, fill, dt)
                    plane[:len(col)] = col
                    planes.append(plane)
                batch[key] = np.stack(planes)
            self.state = self._update(
                self.state, jax.device_put(batch, self._batch_sharding))

    def compile(self) -> None:
        """Compile the update and merge programs before traffic
        arrives: an all-padding batch (every row the drop sentinel)
        through the update step and the state through the merge,
        whose result is dropped.  A first-sight compile takes tens of seconds
        on a mesh and would otherwise run inside an import handler or
        a flush, under the server's ingest lock.  The fresh-state
        program compiled at construction, making the first state."""
        batch = {k: np.full((self.n_shard, self.cfg.batch),
                            self._fill.get(k, 0), dt)
                 for k, dt in self._DTYPES.items()}
        # a batch of padding leaves the state as it was (and a build
        # that donates the state has the new one in its place); twice,
        # since jit traces the update once for a fresh state and once
        # more for a state the update made
        for _ in range(2):
            self.state = self._update(
                self.state, jax.device_put(batch, self._batch_sharding))
        self.set_plane(0)       # the sketches' union compiles as well
        jax.block_until_ready(self._merge({
            **self.state,
            "hll": self._with_set_planes(self.state["hll"])}))

    def _with_set_planes(self, regs):
        """``regs`` with the host planes of the forwarded sketches
        maxed in, shard by shard."""
        planes = jax.device_put(
            self._set_planes, NamedSharding(self.mesh,
                                            _specs(self.mesh)["hll"]))
        return _max_planes(regs, planes)

    def merge(self) -> dict:
        """Union the shards' partials with collectives.  FENCED before
        returning: the collectives must finish while no other device
        program can be dispatched.  On an oversubscribed host (virtual
        CPU mesh, or a shared-core TPU host under ingest load) a
        partition of an in-flight collective can starve past XLA's
        40s rendezvous termination — which aborts the whole process —
        if later-dispatched programs compete for the executor pool.
        One synchronous point per flush interval costs ~nothing next
        to what it rules out."""
        state = self.state
        if self._set_touched.any():
            state = {**state,
                     "hll": self._with_set_planes(state["hll"])}
        merged = self._merge(state)
        jax.block_until_ready(merged)
        return merged

    def reset(self) -> None:
        """Fresh partial state for the next interval (the
        double-buffer swap the single-chip table does at flush,
        worker.go:498): made on the chips, and the host planes turned
        to the clean buffer.  The closed interval's planes, which the
        fenced merge has shipped, are left to ``clear_closed``; a
        buffer still dirty from the interval before is cleared here,
        and counted in ``reset_host_bytes``."""
        self.state = self._empty_state()
        self.reset_host_bytes = self.clear_closed()
        self.reset_deferred_bytes = (int(self._set_touched.sum()) *
                                     hll_ops.M)
        if self._set_planes is not None:
            self._set_planes, self._closed_planes = (
                self._closed_planes, self._set_planes)
            self._set_touched, self._closed_touched = (
                self._closed_touched, self._set_touched)
        self.steps = 0
        self.staged = [0] * self.n_shard

    def clear_closed(self) -> int:
        """Clear the closed interval's host planes where it wrote them,
        so the next ``reset`` finds that buffer clean.  Returns the
        bytes cleared."""
        touched = self._closed_touched
        n = int(touched.sum())
        if n:
            self._closed_planes[touched] = 0
            touched[:] = False
        return n * hll_ops.M

    def flush(self, qs=(0.5, 0.9, 0.99)) -> dict:
        """Merge partial shards with collectives and read out."""
        merged = self.merge()
        out = readout(merged, np.asarray(qs, np.float32))
        merged.update(out)
        return merged

    def swap(self) -> dict:
        """Interval boundary: push any staged work, merge, and reset
        the partial state for the next interval."""
        self.step()
        merged = self.merge()
        self.reset()
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
        # what the interval's imports came to, under the names the
        # single-chip table counts them by (the snapshot carries it to
        # the cycle's ``FlushRecord``, ``import_*``)
        self.import_counts = dict.fromkeys(
            ("centroids", "set_planes", "set_planes_loose"), 0)
        from veneur_tpu import native
        self._lib = native.load()

    # -- ingest (the slow-path Sample surface the Server uses) --------

    def _next_shard(self) -> int:
        self._rr = (self._rr + 1) % self.agg.n_shard
        return self._rr

    def begin_wire(self) -> None:
        """A decoded wire goes whole to one shard, the next in turn:
        its batch appliers stage to the cursor where this leaves it.
        Both import paths call it once a wire (``grpc_forward.
        apply_decoded``, ``http_import.apply_import``).  Items that
        come one by one each take the next shard."""
        self._next_shard()

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
        self.agg.stage(self._rr,
                       counter_rows=rows.astype(np.int32),
                       counter_vals=np.asarray(values, np.float32),
                       counter_wts=np.ones(len(rows), np.float32))
        self.counter_idx.touch_rows(rows, self.gen)
        self._staged_n += len(rows)
        self._interval_ingested += len(rows)

    def import_gauge_batch(self, rows, values) -> None:
        # one ticket per write preserves last-write-wins in wire
        # order across the whole mesh
        rows = np.ascontiguousarray(rows, np.int64)
        self.agg.stage(self._rr, gauge_rows=rows.astype(np.int32),
                       gauge_vals=np.asarray(values, np.float32),
                       gauge_ticket=self.agg.next_ticket(len(rows)))
        self.gauge_idx.touch_rows(rows, self.gen)
        self._staged_n += len(rows)
        self._interval_ingested += len(rows)

    def import_set_at(self, row, regs) -> None:
        """One forwarded sketch for a resolved row: a 16 KiB register
        max into the host plane of the next shard in turn (Set.Merge,
        samplers/samplers.go:423)."""
        regs = np.asarray(regs, np.uint8)
        if regs.shape != (hll_ops.M,):
            raise ValueError(f"bad register plane shape {regs.shape}")
        plane, touched = self.agg.set_plane(self._next_shard())
        np.maximum(plane[row], regs, out=plane[row])
        touched[row] = True
        self.agg.staged[self._rr] += 1
        self.set_idx.touched[row] = True
        self.set_idx.last_gen[row] = self.gen
        self._staged_n += 1
        self._interval_ingested += 1
        self.import_counts["set_planes"] += 1

    def import_set_wire(self, data: bytes, offs: np.ndarray,
                        lens: np.ndarray,
                        rows: np.ndarray) -> np.ndarray:
        """``MetricTable.import_set_wire`` for the mesh: the dense
        sketches of one decoded wire, validated, unpacked and maxed in
        one native call (vtpu_hll_union_dense, no interpreter lock
        held) into the host plane of the wire's shard, which reaches
        the device whole at the flush merge.  Returns a status an
        item: 0 unioned, non-zero left for ``hll_codec.decode`` +
        ``import_set_at`` one by one (sparse, malformed; all of them
        without the native library), counted as
        ``import_counts["set_planes_loose"]``."""
        from veneur_tpu.core import table as core_table
        plane, touched = self.agg.set_plane(self._rr)
        status = core_table.union_dense_sketches(
            self._lib, data, offs, lens, rows, lambda: plane)
        done = np.asarray(rows, np.int64)[status == 0]
        if len(done):
            touched[done] = True
            self.agg.staged[self._rr] += len(done)
            self.set_idx.touch_rows(done, self.gen)
            self._staged_n += len(done)
            self._interval_ingested += len(done)
            self.import_counts["set_planes"] += len(done)
        self.import_counts["set_planes_loose"] += int(
            (status != 0).sum())
        return status

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
        """Forwarded digest: its centroids re-enter the row's digest
        as weighted samples (a centroid IS a weighted sample) marked
        forwarded, its statistics as one exact row of the import
        plane (``import_histo_batch``, one digest)."""
        from veneur_tpu.ops import segment
        # shapes validated BEFORE anything stages, matching the
        # single-chip contract (table.py import_histo): a malformed
        # item must not leave half its state staged
        stats = np.asarray(stats, np.float32)
        means = np.asarray(means, np.float32)
        weights = np.asarray(weights, np.float32)
        if stats.shape != (segment.HISTO_STAT_COLS,):
            raise ValueError(f"bad stats shape {stats.shape}")
        if means.shape != weights.shape or means.ndim != 1:
            raise ValueError(
                f"centroid shape mismatch {means.shape}/"
                f"{weights.shape}")
        row = self.import_histo_row(name, mtype, tags, scope)
        if row is None:
            return False
        self._next_shard()
        live = weights > 0
        self.import_histo_batch(
            np.asarray([row]), stats[None],
            np.full(int(live.sum()), row, np.int32), means[live],
            weights[live])
        return True

    def import_histo_batch(self, rows, stats, cent_rows, cent_means,
                           cent_weights) -> None:
        """Forwarded digests, columnar, to the wire's shard: the
        centroids staged as weighted samples marked forwarded (they
        feed the digests alone), each digest's statistics staged
        exact as a row for the import plane."""
        rows = np.ascontiguousarray(rows, np.int64)
        sh = self._rr
        if len(cent_rows):
            self.agg.stage(sh, histo_rows=cent_rows,
                           histo_vals=cent_means,
                           histo_wts=cent_weights,
                           histo_imp=np.ones(len(cent_rows), np.int32))
        stats = np.asarray(stats, np.float32)
        self.agg.stage(sh, istat_rows=rows.astype(np.int32), **{
            k: stats[:, i] for i, k in enumerate(ISTAT_COLS)})
        # rows may arrive cache-resolved (no lookup ran): touch them
        # so flush emission sees the series
        self.histo_idx.touch_rows(rows, self.gen)
        # every staged item counts: the staging-memory bound that
        # triggers device_step rides on this counter
        self._staged_n += len(cent_rows) + len(rows)
        self._interval_ingested += len(rows)
        self.import_counts["centroids"] += len(cent_rows)

    def import_set(self, name, tags, regs, scope=None) -> bool:
        """Forwarded HLL plane by name: the row resolved, then
        ``import_set_at``."""
        from veneur_tpu.protocol import dogstatsd as dsd
        regs = np.asarray(regs, np.uint8)
        if regs.shape != (hll_ops.M,):
            raise ValueError(f"bad register plane shape {regs.shape}")
        row = self.import_set_row(name, tags, scope)
        if row is None:
            return False
        self.import_set_at(row, regs)
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
        """Whatever is staged through SPMD updates.  When to step is
        the caller's to say (the server's staging threshold,
        ``_maybe_device_step_locked``; the swap's ``final``)."""
        if final or self._staged_n:
            self.agg.step()
            self._staged_n = 0

    def clear_closed(self) -> int:
        """The closed interval's host register planes cleared, off the
        ingest lock: the server calls it once the interval's flush has
        delivered (``ShardedAggregator.clear_closed``)."""
        return self.agg.clear_closed()

    def take_status(self):
        out = self.status
        self.status = {}
        return out

    def swap(self, stage=lambda name: contextlib.nullcontext()):
        """Interval boundary -> a core-table Snapshot the Flusher
        consumes unchanged: merged planes land in the same fields the
        single-chip table fills.

        ``stage(name)`` times the three parts and yields the part's
        span or None (the server passes its ``snapshot`` stage's):
        ``final_step``, the staged remainder through SPMD updates;
        ``shard_merge``, the collective merge up to its fence, tagged
        with what it did; ``state_reset``, the fresh state made on the
        mesh, tagged ``host_bytes`` (what it built, shipped or cleared
        on the host: 0 once the closed planes are cleared after each
        flush, ``clear_closed``)."""
        from veneur_tpu.core import table as core_table
        with stage("final_step"):
            self.device_step(final=True)
        info = self.agg.merge_info
        with stage("shard_merge") as sp:
            merged = self.agg.merge()
            if sp is not None:
                for k, v in {**info, "histo_rows_live": int(
                        self.histo_idx.touched.sum()),
                        "set_rows_live": int(
                            self.set_idx.touched.sum())}.items():
                    sp.add_tag(k, str(v))
        mesh_counts = {"shard_steps": self.agg.steps,
                       "shard_staged": list(self.agg.staged),
                       "mesh": info["mesh"],
                       "merge_path": info["merge_path"]}
        with stage("state_reset") as sp:
            self.agg.reset()
            if sp is not None:
                sp.add_tag("host_bytes", str(self.agg.reset_host_bytes))
        mesh_counts["reset_host_bytes"] = self.agg.reset_host_bytes
        mesh_counts["reset_deferred_bytes"] = \
            self.agg.reset_deferred_bytes
        snap = core_table.Snapshot(
            gen=self.gen,
            counters=merged["counters"],
            counter_meta=list(self.counter_idx.meta),
            counter_touched=self.counter_idx.touched.copy(),
            gauges=merged["gauges"],
            gauge_meta=list(self.gauge_idx.meta),
            gauge_touched=self.gauge_idx.touched.copy(),
            histo_stats=merged["histo_stats"],
            histo_import_stats=merged["histo_import_stats"],
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
            ingested=self._interval_ingested,
            import_counts=dict(self.import_counts),
            mesh_counts=mesh_counts)
        self.import_counts = dict.fromkeys(self.import_counts, 0)
        self._interval_ingested = 0
        self.gen += 1
        for idx in (self.counter_idx, self.gauge_idx, self.histo_idx,
                    self.set_idx):
            idx.reset_interval()
        return snap
