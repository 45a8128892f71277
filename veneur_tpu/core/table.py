"""Device-resident metric tables: the TPU replacement for worker maps.

The reference shards series across N worker goroutines, each owning Go
maps of pointer-y sampler structs (worker.go:60-84 ``WorkerMetrics``,
:108 ``Upsert``).  Here ALL series of a metric class live in one
fixed-capacity columnar table in device memory, addressed by a dense row
id that the host allocates per MetricKey:

  class     state                                   update kernel
  counter   f32[R]                                  segment add
  gauge     f32[R]                                  last-write select
  histo     f32[R,5] stats + f32[R,C] digest planes segment + t-digest merge
  set       u8[R,16384] HLL registers               scatter-max

Ingest appends to host-side columnar staging buffers; ``device_step``
flushes staging to the device as a handful of jitted scatter/merge calls
(padded to power-of-two bucket lengths to bound compile count).  At the
flush boundary ``swap()`` hands the current device arrays to the flusher
and re-seeds fresh state — the moral equivalent of the reference's
worker mutex swap (worker.go:498 ``Flush``), except nothing blocks:
JAX's async dispatch lets readback of the old interval overlap ingestion
into the new one.

Row allocation is persistent across intervals (hot series keep their
row); stale keys are compacted out at swap time when occupancy crosses a
threshold.  Status checks are host-side (low volume, message-carrying),
matching their modest role in the reference (samplers/samplers.go:307).
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from veneur_tpu import native, observe
from veneur_tpu.core import tiers as tiersmod
from veneur_tpu.core.metrics import parse_sink_only
from veneur_tpu.observe.ledger import ClassDropTally
from veneur_tpu.ops import hll, segment, superbatch, tdigest
from veneur_tpu.protocol import columnar, dogstatsd as dsd
from veneur_tpu.utils import hashing, intern, jitopts

# jitted update steps (donation policy: utils/jitopts).  Counters
# and gauges take
# host-precombined dense vectors (np.bincount / last-write collapse):
# a batch ships as R floats instead of 12 bytes/sample.
# All are registered with the device-cost registry: steady-state
# ingest must never recompile (a moving veneur.xla.compile_total is a
# shape-drift bug), and the per-kernel dispatch and transfer numbers
# feed /debug/vars.
_counter_dense_step = observe.instrument(
    "table.counter_dense",
    jax.jit(segment.counter_dense_update,
            donate_argnums=jitopts.donate(0)))
_gauge_dense_step = observe.instrument(
    "table.gauge_dense",
    jax.jit(segment.gauge_dense_update,
            donate_argnums=jitopts.donate(0)))
_hll_step_packed = observe.instrument(
    "table.hll_insert_packed",
    jax.jit(hll.insert_packed, donate_argnums=jitopts.donate(0)))
_hll_union_plane = observe.instrument(
    "table.hll_union",
    jax.jit(hll.union, donate_argnums=jitopts.donate(0)))
# global-tier merge steps (forwarded partial state; duplicates within a
# batch reduce correctly because every column is an associative scatter)
_histo_stats_merge = observe.instrument(
    "table.histo_stats_merge",
    jax.jit(segment.merge_histo_stats,
            donate_argnums=jitopts.donate(0)))
_hll_merge_rows = observe.instrument(
    "table.hll_merge_rows",
    jax.jit(hll.merge_rows, donate_argnums=jitopts.donate(0)))
# elementwise fold of host-computed per-row batch aggregates (see
# _host_stats_fold); identity-filled untouched rows need no mask
_histo_stats_fold = observe.instrument(
    "table.histo_stats_fold",
    jax.jit(tdigest._combine_row_stats,
            donate_argnums=jitopts.donate(0)))
# The per-class histo merges dispatch tdigest's jitted entry points;
# wrap each in the device-cost registry so the per-interval dispatch
# telemetry (veneur.device.dispatches_total) sees the per-class path
# and the superbatch A/B comparison is honest.


class _TdStep:
    """Resolves ``tdigest.<name>`` at call time, not wrap time — the
    branch-engagement tests monkeypatch the module attributes to spy
    which merge path fired, and a captured reference would go dark."""

    def __init__(self, name: str):
        self._name = name

    def __call__(self, *args, **kwargs):
        return getattr(tdigest, self._name)(*args, **kwargs)

    def __getattr__(self, attr):  # _cache_size etc. from the live fn
        return getattr(getattr(tdigest, self._name), attr)


_td_step = {
    name: observe.instrument("table.td_" + name, _TdStep(name))
    for name in (
        "ingest_ranked", "ingest_ranked_unit",
        "ingest_ranked_rows", "ingest_ranked_unit_rows",
        "add_samples_ranked", "add_samples_ranked_unit",
        "add_samples_ranked_rows", "add_samples_ranked_unit_rows",
        "ingest_plane_pre", "ingest_plane_pre_unit",
        "add_samples_ranked_scan", "add_samples_ranked_scan_rows",
        "merge_dense_scan", "merge_dense_scan_rows")}

_MIN_BUCKET = 256
_MIN_BUCKET_WIDE = 8  # for batches whose rows are whole planes

# Device A/B gate: VENEUR_TPU_F16_PLANE=0 forces f32 value planes even
# for batches whose range fits f16 — for measuring the half-width
# transfer's throughput win against its ~0.05% mean quantization on
# real accelerator hardware.
_F16_PLANE = os.environ.get("VENEUR_TPU_F16_PLANE", "1").lower() \
    not in ("0", "false", "off")


def _bucket_len(n: int, wide: bool = False) -> int:
    """Pad-to bucket: powers of two plus 1.5x half-steps, capping pad
    waste at 33% (a pure pow-2 ladder wastes up to 100%, which is real
    h2d bytes on multi-MB timer batches) while keeping the compile
    cache small."""
    b = _MIN_BUCKET_WIDE if wide else _MIN_BUCKET
    while True:
        if n <= b:
            return b
        if n <= b + b // 2:
            return b + b // 2
        b *= 2


def _pad_np(arr: np.ndarray, length: int, fill) -> np.ndarray:
    out = np.full(length, fill, arr.dtype)
    out[:len(arr)] = arr
    return out


def _ladder_floor(n: int) -> int:
    """Largest wide-ladder bucket <= n (inverse of _bucket_len): the
    per-wire spill threshold for the stacked merge must itself be a
    ladder value, or bucketing the observed depth could round the
    stack width past the fused kernel's chunk bound."""
    b = best = _MIN_BUCKET_WIDE
    while b <= n:
        best = b
        if b + b // 2 <= n:
            best = b + b // 2
        b *= 2
    return best


def _fused_import_mode() -> str:
    """VENEUR_TPU_FUSED_IMPORT: unset/"auto" (default) picks per
    backend at apply time — the stacked kernel where the Pallas merge
    gate engages (each scan step stays inside the kernel's lane
    bound, where the flat merge's combined width would blow it and
    fall back), the flat rank-interleaved merge elsewhere (fewer
    total FLOPs when every path is scatter anyway).  "1"/"stack"
    forces the stacked call; "0"/"perwire" keeps one kernel call per
    wire — bit-identical to the stacked mode (same merge body, order,
    and operand shapes), kept as the reference for
    tests/test_pipeline.py; "legacy" restores the flat
    rank-interleaved staging path from before the fusion."""
    raw = os.environ.get("VENEUR_TPU_FUSED_IMPORT", "auto").lower()
    if raw in ("0", "false", "off", "perwire", "per-wire"):
        return "perwire"
    if raw == "legacy":
        return "legacy"
    if raw in ("", "auto"):
        return "auto"
    return "stack"


def _collective_import_mode(cfg_default: str = "auto") -> str:
    """VENEUR_TPU_COLLECTIVE_IMPORT: gate for the mesh-sharded
    collective import fold (parallel.sharded.CollectiveWireFold).
    Unset defers to TableConfig.collective_import.  "auto" (default)
    resolves at first apply to ON iff more than one device is visible
    — on a single device the all-gather is a copy and the serial scan
    is strictly cheaper; "on"/"off" force.  The serial per-wire scan
    stays available under "off" as the bit-parity oracle
    (tests/test_collective_import.py)."""
    raw = os.environ.get("VENEUR_TPU_COLLECTIVE_IMPORT", "").lower()
    if raw == "":
        raw = str(cfg_default).lower()
    if raw in ("0", "false", "off", "no"):
        return "off"
    if raw in ("1", "true", "on", "yes"):
        return "on"
    return "auto"


def _state_property(name: str) -> property:
    def _get(self):
        return getattr(self._state, name)

    def _set(self, value):
        setattr(self._state, name, value)

    return property(_get, _set)


class _IntervalState:
    """One interval's device-resident accumulation state.  The table
    has exactly one CURRENT state receiving new staging; at a swap
    boundary the outgoing object stays pinned by any in-flight staged
    work that still targets it (take_staged binds the state at detach
    time), so a late apply can never land in the wrong interval — the
    object identity IS the generation guarantee, and ``pending`` is
    the count complete_swap waits out before snapshotting."""

    __slots__ = ("gen", "pending", "fresh", "counters", "gauges",
                 "histo_stats", "histo_import_stats", "histo_means",
                 "histo_weights", "hll_regs", "hll_host_plane",
                 "hll_host_ez", "hll_host_inv", "hll_device_touched",
                 "histo_compact", "set_sparse", "set_dense_overflow",
                 "tier_frozen", "import_counts")

    def __init__(self, gen: int):
        self.gen = gen
        self.pending = 0
        self.fresh: set = set()
        # what the imports folded into this interval came to, counted
        # where the folds run (``_wire_digest_step``, the imported
        # digests' ``_histo_device_step``, ``import_set_at``,
        # ``import_set_wire``): digest folds that took the flat ranked
        # merge and the stacked scan, centroids the stack left to the
        # flat merge, centroids and register planes in all, and the
        # sketches of decoded wires that the one native pass handed
        # back to the per-item decode.  The snapshot carries it to
        # the cycle's ``FlushRecord`` (``import_*``).
        self.import_counts = dict.fromkeys(
            ("steps_flat", "steps_stack", "spilled_centroids",
             "centroids", "set_planes", "set_planes_loose"), 0)
        self.hll_host_plane: np.ndarray | None = None
        self.hll_host_ez: np.ndarray | None = None
        self.hll_host_inv: np.ndarray | None = None
        self.hll_device_touched = False
        # tiered-mode per-interval state: compact-tier stores (exact
        # host-side sketches for below-threshold series) and the
        # (tier, slot) maps frozen at begin_swap so late pipelined
        # applies route by the assignments this interval's earlier
        # data used (see tiers.TierSnapshot)
        self.histo_compact: Any = None
        self.set_sparse: Any = None
        self.set_dense_overflow: dict[int, np.ndarray] | None = None
        self.tier_frozen: dict | None = None


class _StagedWork:
    """Staging buffers detached under the ingest lock (O(µs): list and
    dense-buffer handoffs, no concatenation or hashing), applied to
    the pinned interval state outside it (apply_staged)."""

    __slots__ = ("state", "final", "counter", "gauge", "histo",
                 "digest", "wire_parts", "set_parts", "stats_parts",
                 "set_import", "empty")

    def histo_samples(self) -> int:
        """Staged timer samples and centroids, local and imported."""
        n = sum(len(st) for st in (self.histo, self.digest)
                if st is not None)
        if self.wire_parts is not None:
            n += sum(len(p[0]) for p in self.wire_parts)
        return n

    def set_members(self) -> int:
        if self.set_parts is None:
            return 0
        rows, _members, pos_rows, _pos = self.set_parts
        return len(rows) + sum(len(r) for r in pos_rows)


class _PendingSwap:
    """begin_swap's output: the final detached staging plus the row
    metadata captured at the interval boundary, everything
    complete_swap needs to finish the snapshot off-lock."""

    __slots__ = ("work", "state", "counter_meta", "counter_touched",
                 "gauge_meta", "gauge_touched", "histo_meta",
                 "histo_touched", "set_meta", "set_touched",
                 "sink_only_rows", "overflow", "ingested", "row_maps")

    def staged_counts(self) -> dict[str, int]:
        """What the final apply is about to take, for the
        ``flush.swap_apply`` span's tags: lengths and sums of host
        arrays already in hand.  Samples and members are those still
        staged at the swap (a mid-interval device step took its own);
        rows are the interval's touched rows."""
        return {"histo_samples": self.work.histo_samples(),
                "histo_rows": int(self.histo_touched.sum()),
                "set_members": self.work.set_members(),
                "set_rows": int(self.set_touched.sum()),
                "scalar_rows": int(self.counter_touched.sum()
                                   + self.gauge_touched.sum())}


@dataclass
class TableConfig:
    counter_rows: int = 4096
    gauge_rows: int = 4096
    histo_rows: int = 4096
    set_rows: int = 512
    compression: float = 100.0
    histo_slots: int = 512  # max samples per row per merge call
    compact_threshold: float = 0.75
    # histo AND set samples accumulate across device steps and flush
    # in ONE device pass at the swap (or when this many are staged):
    # per-reader-batch digest merges did 10x the cluster work for the
    # same digests, and whole-interval set batches dedup into a
    # register plane (one h2d plane beats 8 bytes/member)
    histo_merge_samples: int = 4 << 20
    # mesh-sharded collective import fold ("auto" = on iff >1 device
    # at first apply; "on"/"off" force; VENEUR_TPU_COLLECTIVE_IMPORT
    # overrides — see _collective_import_mode)
    collective_import: str = "auto"
    # raw set samples fold into a HOST register plane (16 KiB/row)
    # when the plane fits this bound; past it (very high set-row
    # configs) they scatter to the device as before.  The host plane
    # makes the single-node set path device-free: the flusher
    # estimates from it directly unless global-tier imports also
    # landed in the device registers (see flusher._prepare)
    host_set_plane_max_bytes: int = 64 << 20


@dataclass
class RowMeta:
    name: str
    tags: tuple[str, ...]
    scope: str
    type: str
    # 64-bit series-identity hash (utils.hashing.key_hash64) when the
    # row is known to the fast-path key index; 0 for rows only ever
    # touched by the slow path
    key_hash: int = 0
    # the series' identity on the forward wire, (bytes before the
    # value, bytes after it), written by the encoder at the row's
    # first forward and reused every interval after
    # (grpc_forward._wire_ident).  It lives and dies with the row: a
    # compaction that drops the series drops its RowMeta, and a row
    # number that passes to another series gets that series' own
    wire_ident: tuple[bytes, bytes] | None = field(
        default=None, repr=False, compare=False)
    # the sinks its ``veneursinkonly:`` tags restrict it to, None for
    # a series every sink gets: read off the tags once, here, so the
    # flush's routing (frame.MetricFrame.route) never walks them
    sink_only: frozenset[str] | None = field(
        init=False, repr=False, compare=False)

    def __post_init__(self):
        self.sink_only = parse_sink_only(self.tags)


class _ClassIndex:
    """Host-side MetricKey -> row allocation for one metric class."""

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.rows: dict[tuple, int] = {}
        self.meta: list[RowMeta] = []
        # live rows with a ``sink_only``: what the flush's routing
        # reads in place of the pool
        self.sink_only_rows = 0
        self.touched = np.zeros(capacity, dtype=bool)
        self.last_gen = np.zeros(capacity, dtype=np.int64)
        # centralized drop tally: every fast-path drop site goes
        # through drops.add, so /debug/vars, interval snapshots, and
        # the conservation ledger all read ONE number
        self.drops = ClassDropTally()

    @property
    def overflow(self) -> int:
        """Interval overflow-drop count (SAMPLES, not keys).  Mutate
        via ``drops.add``/``drops.take`` only."""
        return self.drops.count

    def lookup(self, sample_key: tuple, name: str,
               tags: tuple[str, ...], scope: str, mtype: str,
               gen: int, key_hash: int = 0,
               count_overflow: bool = True) -> int | None:
        row = self.rows.get(sample_key)
        if row is None:
            if len(self.meta) >= self.capacity:
                if count_overflow:
                    self.drops.add(1)
                return None
            row = len(self.meta)
            self.rows[sample_key] = row
            meta = RowMeta(name, tags, scope, mtype, key_hash)
            self.meta.append(meta)
            self.sink_only_rows += meta.sink_only is not None
        elif key_hash and not self.meta[row].key_hash:
            self.meta[row].key_hash = key_hash
        self.last_gen[row] = gen
        self.touched[row] = True
        return row

    def touch_rows(self, rows: np.ndarray, gen: int) -> None:
        """Vectorized touch for fast-path batches."""
        self.touched[rows] = True
        self.last_gen[rows] = gen

    def occupancy(self) -> int:
        return len(self.meta)

    def compact(self, keep_gen: int) -> np.ndarray:
        """Drop keys untouched since ``keep_gen``; renumber survivors.
        Only legal at a swap boundary (device state is fresh zeros).
        Returns the old-row -> new-row mapping (-1 for dropped rows)
        so tier directories and other row-keyed sidecars can follow
        the renumbering."""
        new_rows: dict[tuple, int] = {}
        new_meta: list[RowMeta] = []
        new_gen = np.zeros(self.capacity, dtype=np.int64)
        mapping = np.full(self.capacity, -1, np.int32)
        sink_only_rows = 0
        for key, row in self.rows.items():
            if self.last_gen[row] >= keep_gen:
                new_row = len(new_meta)
                new_rows[key] = new_row
                new_gen[new_row] = self.last_gen[row]
                meta = self.meta[row]
                new_meta.append(meta)
                sink_only_rows += meta.sink_only is not None
                mapping[row] = new_row
        self.rows = new_rows
        self.meta = new_meta
        self.sink_only_rows = sink_only_rows
        self.last_gen = new_gen
        self.touched = np.zeros(self.capacity, dtype=bool)
        return mapping

    def reset_interval(self) -> None:
        self.touched = np.zeros(self.capacity, dtype=bool)


class _Staging:
    """Columnar append buffers for one class."""

    def __init__(self):
        self.rows: list[np.ndarray] = []
        self.values: list[np.ndarray] = []
        self.weights: list[np.ndarray] = []

    def append(self, rows, values, weights=None):
        self.rows.append(np.asarray(rows, np.int32))
        self.values.append(np.asarray(values, np.float32))
        if weights is not None:
            self.weights.append(np.asarray(weights, np.float32))

    def take(self):
        if not self.rows:
            return None
        rows = np.concatenate(self.rows)
        vals = np.concatenate(self.values)
        wts = np.concatenate(self.weights) if self.weights else None
        self.rows, self.values, self.weights = [], [], []
        return rows, vals, wts

    def __len__(self):
        return sum(len(r) for r in self.rows)


class _MissLines:
    """ParsedBatch-shaped view over the fused pass's compact miss
    columns — just enough surface for _resolve_misses (line bytes +
    type codes)."""

    def __init__(self, buf: np.ndarray, off: np.ndarray,
                 ln: np.ndarray, types: np.ndarray):
        self._buf = buf
        self._off = off
        self._len = ln
        self.type_code = types

    def line(self, i: int) -> bytes:
        o = int(self._off[i])
        return self._buf[o:o + int(self._len[i])].tobytes()


@dataclass
class Snapshot:
    """Everything the flusher needs from one interval, per class:
    device arrays (still async; readback happens in the flusher) plus
    row metadata."""
    gen: int
    counters: Any
    counter_meta: list[RowMeta]
    counter_touched: np.ndarray
    gauges: Any
    gauge_meta: list[RowMeta]
    gauge_touched: np.ndarray
    histo_stats: Any  # raw-sample ("local") stats plane
    histo_import_stats: Any  # forwarded-stat-row merges only
    histo_means: Any
    histo_weights: Any
    histo_meta: list[RowMeta]
    histo_touched: np.ndarray
    hll_regs: Any
    set_meta: list[RowMeta]
    set_touched: np.ndarray
    # rows of the four pools above that have a ``RowMeta.sink_only``,
    # counted by their indexes as the rows came and went
    sink_only_rows: int
    # host-folded raw-set registers for the interval (None when the
    # plane exceeded host_set_plane_max_bytes) and whether anything
    # (imports, oversized-plane scatters) touched the DEVICE registers
    hll_host_plane: np.ndarray | None = None
    hll_device_touched: bool = False
    # per-row LogLog-Beta sufficient statistics maintained by the
    # native fold (ez = zero-register count, inv_sum = sum 2^-reg);
    # None when the pure-Python fold ran (estimate_np covers it)
    hll_host_ez: np.ndarray | None = None
    hll_host_inv: np.ndarray | None = None
    overflow: dict[str, int] = field(default_factory=dict)
    # samples staged into this interval (the table's own count — the
    # conservation ledger cross-checks it against site-credited totals)
    ingested: int = 0
    # set by swap(): hands the host set plane back to the table's
    # reuse pool (see Snapshot.release)
    recycle: Any = None
    # tiered-mode view (tiers.TierSnapshot): the frozen per-row
    # (tier, slot) assignments this interval's data was routed under
    # plus the compact-tier stores.  None in single-tier mode — every
    # consumer that dispatches on it first falls through to today's
    # exact code paths when absent.
    tiers: Any = None
    # what the interval's imports came to (``_IntervalState``'s
    # ``import_counts``), final once ``complete_swap`` has applied the
    # last staged work; empty where the table keeps no such count
    import_counts: dict[str, int] = field(default_factory=dict)
    # what a mesh table's swap did, under the ``FlushRecord``'s field
    # names (``shard_steps``, ``shard_staged``, ``mesh``,
    # ``merge_path``, ``reset_host_bytes``, ``reset_deferred_bytes``);
    # empty from one chip's table
    mesh_counts: dict[str, Any] = field(default_factory=dict)

    @property
    def host_only_sets(self) -> bool:
        """True when the interval's entire set state is the host
        plane — the single definition the flusher and bench dispatch
        on to skip the device for set reads.  Tiered intervals are
        always host-only (the sparse store and the wide pool both
        live host-side), but their plane is SLOT-indexed, so tiered
        consumers must go through Snapshot.tiers helpers instead."""
        if self.tiers is not None:
            return True
        return (self.hll_host_plane is not None and
                not self.hll_device_touched)

    def host_set_estimates(self) -> np.ndarray:
        """Cardinality estimates f32[set_rows] for a host-only-sets
        interval — O(rows) from the fold-maintained statistics when
        available, full-plane rescan otherwise.  Row-indexed in both
        modes (the tiered helper translates slots internally)."""
        from veneur_tpu.ops import hll as _hll
        if self.tiers is not None:
            return self.tiers.set_estimates(
                self, np.nonzero(self.set_touched)[0])
        if self.hll_host_ez is not None:
            return _hll.estimate_from_stats(self.hll_host_ez,
                                            self.hll_host_inv)
        return _hll.estimate_np(self.hll_host_plane)

    def release(self) -> None:
        """Return the host set plane to the owning table's pool once
        all reads are done.  Faulting in a fresh 16 MiB np.zeros
        inside the fold costs ~2x the fold itself; clearing a warm
        recycled plane is ~10x cheaper.  The plane (and its stats)
        are invalid after this call."""
        if self.recycle is not None and self.hll_host_plane is not None:
            plane, self.hll_host_plane = self.hll_host_plane, None
            self.hll_host_ez = None
            self.hll_host_inv = None
            self.recycle(plane)

    def set_registers(self) -> np.ndarray:
        """Effective HLL registers for the interval as a host array:
        the host-folded plane unioned with any device-resident state
        (global-tier import merges).  Reads the device plane back only
        when it was actually touched.  Tiered intervals materialize
        the full row-space dense plane (parity/interop view; O(rows *
        16 KiB), meant for tests and small tables)."""
        if self.tiers is not None:
            return self.tiers.materialize_registers(self)
        if self.host_only_sets:
            return self.hll_host_plane
        regs = np.asarray(self.hll_regs)
        if self.hll_host_plane is not None:
            regs = np.maximum(regs, self.hll_host_plane)
        return regs



def union_dense_sketches(lib, data: bytes, offs, lens, rows,
                         plane_of) -> np.ndarray:
    """The dense sketches of one decoded wire maxed into a host
    register plane u8[n_rows, M] in one native call
    (vtpu_hll_union_dense: validated and unpacked in wire order, no
    interpreter lock held): sketch i is the ``lens[i]`` bytes of
    ``data`` at ``offs[i]`` for row ``rows[i]``.  ``plane_of()`` gives
    the plane, asked only where there is something to union.  Returns
    a status an item: 0 unioned, non-zero left untouched (sparse, a
    header the dense decode refuses, short; all of them without the
    native library)."""
    n = len(rows)
    status = np.full(n, 255, np.uint8)
    if n and lib is not None:
        import ctypes as ct
        plane = plane_of()
        buf = np.frombuffer(data, np.uint8)
        offs = np.ascontiguousarray(offs, np.int64)
        lens = np.ascontiguousarray(lens, np.int32)
        rows = np.ascontiguousarray(rows, np.int64)
        u8p = ct.POINTER(ct.c_uint8)
        i64p = ct.POINTER(ct.c_int64)
        lib.vtpu_hll_union_dense(
            buf.ctypes.data_as(u8p), len(buf),
            offs.ctypes.data_as(i64p),
            lens.ctypes.data_as(ct.POINTER(ct.c_int32)),
            rows.ctypes.data_as(i64p), n, len(plane),
            plane.ctypes.data_as(u8p), status.ctypes.data_as(u8p))
    return status

class MetricTable:
    def __init__(self, config: TableConfig | None = None):
        self.config = config or TableConfig()
        c = self.config
        self.gen = 0
        self.capacity = tdigest.capacity_for(c.compression)

        self.counter_idx = _ClassIndex(c.counter_rows)
        self.gauge_idx = _ClassIndex(c.gauge_rows)
        self.histo_idx = _ClassIndex(c.histo_rows)
        self.set_idx = _ClassIndex(c.set_rows)

        # Adaptive sketch tiers (core/tiers.py): when the dense wide
        # allocation for sketch classes would blow the auto budget
        # (or VENEUR_TPU_PLANE_TIERS forces it), histogram centroid
        # planes and HLL register rows are pooled at a FRACTION of
        # the row table and per-series tier bits route each row to
        # the wide pool or an exact compact-tier store.  Single-tier
        # mode keeps self.tiers None and every tiered branch below is
        # dead code — bit-identical to the untiered table.
        dense_bytes = (c.set_rows * hll.M +
                       c.histo_rows * 2 * self.capacity * 4)
        self.tiers = (tiersmod.TierDirectory(c.histo_rows, c.set_rows)
                      if tiersmod.tiers_enabled(dense_bytes) else None)
        if self.tiers is not None:
            self._histo_pool_rows = self.tiers.histo.wide_slots
            self._set_pool_rows = self.tiers.set.wide_slots
        else:
            self._histo_pool_rows = c.histo_rows
            self._set_pool_rows = c.set_rows

        # Counters and gauges stage as DENSE per-row host buffers —
        # every ingest path combines into them directly (counter merge
        # is associative add, gauge merge is last-write), so a whole
        # interval's samples ship as R values however many arrived.
        # f64 accumulator: repeated f32 adds of a hot counter would
        # drift; one f32 round-off happens at ship time.
        self._counter_dense = np.zeros(c.counter_rows, np.float64)
        self._gauge_dense = np.zeros(c.gauge_rows, np.float32)
        self._gauge_mask = np.zeros(c.gauge_rows, np.uint8)
        self._counter_dirty = False
        self._gauge_dirty = False
        self._histo_stage = _Staging()
        self._set_rows: list[int] = []
        self._set_members: list[bytes] = []
        # fast-path set staging: positions already hashed (columnar
        # ingest hashes members natively; slow path stores raw bytes)
        # packed (idx << 6) | rank per member — see hll.insert_packed
        self._set_pos_rows: list[np.ndarray] = []
        self._set_pos: list[np.ndarray] = []
        # fast-path series index: identity hash -> row (see
        # utils.intern); rebuilt after compaction renumbers rows.
        # Backed by the C++ table when the native library is available
        # so vtpu_ingest can probe it in its single combine pass.
        self._lib = native.load()
        self.key_index = (intern.NativeHashIndex(self._lib)
                          if self._lib is not None
                          else intern.HashIndex())

        # global-tier import staging (merge of forwarded state; the
        # receive half of reference worker.go:438 ImportMetricGRPC).
        # Imported centroids merge into digests ONLY — their aggregate
        # stats arrive separately via the forwarded stat row, so pushing
        # them through the raw-sample path would double-count.  Imported
        # stat rows land in a SEPARATE plane (histo_import_stats) from
        # raw-sample stats: the reference only emits histogram
        # aggregates from locally-sampled values or (for global-scope
        # rows) from fully-merged state (samplers/samplers.go:530
        # LocalMax/LocalWeight gates), so the flusher must be able to
        # tell the two apart or downstream count-sums double.
        self._digest_stage = _Staging()
        # (rows i32[N], stats f32[N,5]) parts — single imports append
        # 1-row parts, the batched gRPC decode appends whole batches
        self._stats_import_parts: list[tuple[np.ndarray, np.ndarray]] = []
        # forwarded set sketches fold incrementally into a host plane
        # (register max is associative): K received planes for the
        # same row cost K 16 KiB vector maxes at import time and ONE
        # gathered ship at the swap — the list-accumulate-then-dedup
        # design paid an O(K * 16 KiB) stack + argsort + reduceat at
        # the swap (~0.75s at 4096 planes/interval on one core)
        self._set_import_plane: np.ndarray | None = None
        self._set_import_touched: np.ndarray | None = None

        # host register plane for raw set traffic (lazy; see
        # TableConfig.host_set_plane_max_bytes), device-touch flag,
        # and fold-maintained per-row estimate statistics all live on
        # the interval state (_IntervalState) — forwarded as
        # _hll_host_plane/_hll_host_ez/_hll_host_inv below.
        # cleared planes handed back by consumed snapshots
        # (Snapshot.release); list ops are GIL-atomic, so the flusher
        # thread appends while the ingest thread pops
        self._plane_pool: list[np.ndarray] = []

        # fused parse+ingest scratch (see ingest_buffer), grow-only
        self._fused_scratch: dict | None = None

        # row-renumbering epoch: bumped (under the caller's ingest
        # lock) whenever compaction renumbers rows and rebuilds the
        # key index.  Reader shards record it before their lock-free
        # fused pass; a mismatch at commit time means the shard's
        # locally combined row ids are stale, and the raw buffer is
        # re-ingested through the locked path instead (rare: at most
        # once per reader per compacting flush)
        self._reindex_epoch = 0

        self.status: dict[tuple, tuple[float, str, tuple[str, ...]]] = {}
        # gRPC import fast path: native import-identity hash -> row
        # (-1 for known-dropped items), maintained by
        # forward/grpc_forward.apply_metric_list_bytes so steady-state
        # imports never decode name/tag strings.  Invalidated on
        # compaction (rows renumber) and cleared when it reaches
        # import_row_cache_limit (churning identities rebuild it).
        self.import_row_cache: dict[int, int] = {}
        # wire-level row-plan cache: a whole MetricList's khash vector
        # (as bytes) -> (epoch, row vector, per-class overflow counts).
        # A steady-state peer re-forwarding the same series set every
        # interval resolves ALL rows in one dict get
        # (grpc_forward._resolve_rows); epoch-stamped entries
        # self-invalidate on compaction.
        self._wire_plan_cache: dict[bytes, tuple] = {}
        # Effective digest chunk width: on TPU backends, cap merge
        # chunks so state capacity + chunk stays inside the fused
        # Pallas kernel's bound — a wider chunk silently drops to the
        # scatter path (~4x slower on device, round-4 A/B)
        self._eff_histo_slots = c.histo_slots
        from veneur_tpu.ops import tdigest as _td
        if _td.resolved_merge_mode() == "pallas":
            from veneur_tpu.ops import pallas_merge
            mb = pallas_merge.max_batch_slots(self.capacity)
            # only cap when the kernel can actually engage at a sane
            # chunk width — for capacities beyond its bound every
            # merge scatters regardless, and micro-chunking would
            # multiply dispatches for nothing
            if mb >= _MIN_BUCKET:
                self._eff_histo_slots = min(c.histo_slots, mb)
        # bound for the gRPC import row cache (see import_row_cache):
        # churning tag identities would otherwise grow it forever
        self.import_row_cache_limit = 4 * (
            c.counter_rows + c.gauge_rows + c.histo_rows +
            c.set_rows) + 1024
        # O(1) staged-sample counter (``staged()`` must be callable per
        # sample to drive threshold-triggered device steps without
        # walking the staging lists); _interval_ingested is the
        # whole-interval total, reset only at begin_swap, that the
        # conservation ledger cross-checks against site-credited sums
        self._staged_n = 0
        self._interval_ingested = 0
        # samples that left host staging mid-interval (threshold
        # device steps): a crash checkpoint can't see them, so the
        # checkpointer records the count as a NAMED uncovered quantity
        # instead of letting it read as covered (see
        # checkpoint_capture)
        self._interval_device_staged = 0
        # overload pressure: set_pressure_level walks histogram merge
        # width down the ladder so the expensive class loses precision
        # (more collapse per merge) before anyone loses samples; the
        # base value restores exactly on release
        self._eff_histo_slots_base = self._eff_histo_slots
        self._pressure_level = 0

        # fused global merge staging: one part per decoded wire list
        # (rows, means, weights), stacked at apply time into one
        # (n_wires, rows, K) kernel call — see _wire_digest_step
        self._wire_digest_parts: list[tuple] = []
        self.fused_import_mode = _fused_import_mode()
        # widest ladder bucket the stacked merge may use per wire;
        # rows deeper than this in one wire spill to the ranked path
        self._wire_stack_kmax = _ladder_floor(self._eff_histo_slots)
        self.collective_import_mode = _collective_import_mode(
            c.collective_import)
        # lazily resolved parallel.sharded.CollectiveWireFold:
        # "unset" until the gate first resolves at apply time (device
        # topology is only trustworthy then), None when it resolves
        # off, else the fold object (holds the jitted collective)
        self._collective_fold: object = "unset"

        # superbatch apply (ops/superbatch): pack the whole cycle's
        # detached staging into ONE host buffer and apply it with ONE
        # fused dispatch.  Double-buffered so packing cycle N+1
        # overlaps the device computing cycle N; the per-class path
        # below stays intact as the bit-parity oracle and the
        # fallback for tiered tables and fused-ineligible batches.
        self.superbatch_mode = superbatch.mode()
        self._sb_on = self.superbatch_mode != "off"
        self._sb_bufs = superbatch.DoubleBuffer()
        self._sb_plane_factor = superbatch.plane_scatter_factor(
            jax.default_backend())

        # pipelined apply machinery: device dispatch serializes on
        # _device_lock so staged work applies outside the ingest lock;
        # _pending_cv guards per-state pending counts (take_staged
        # increments, apply_staged decrements, complete_swap waits)
        self._device_lock = threading.Lock()
        self._pending_cv = threading.Condition()

        self._init_state()

    _KINDS = ("counter", "gauge", "histo", "hll")

    def _init_state(self):
        st = _IntervalState(self.gen)
        for kind in self._KINDS:
            self._alloc_state(st, kind)
        self._state = st

    def _alloc_state(self, st: _IntervalState, kind: str) -> None:
        c = self.config
        if kind == "counter":
            st.counters = segment.empty_counter_state(c.counter_rows)
        elif kind == "gauge":
            st.gauges = segment.empty_gauge_state(c.gauge_rows)
        elif kind == "histo":
            # ALL FOUR histo planes freshen as one kind: the flusher
            # reads local + import stats under one touched gate, so a
            # split freshness would let a stale import plane from a
            # prior interval leak into every later flush
            st.histo_stats = segment.empty_histo_stats(c.histo_rows)
            st.histo_import_stats = segment.empty_histo_stats(
                c.histo_rows)
            # stats planes stay ROW-indexed in both modes (exact
            # aggregates are cheap: 5 floats/row); only the centroid
            # planes pool down to wide slots under tiering
            st.histo_means, st.histo_weights = tdigest.empty_state(
                self._histo_pool_rows, self.capacity)
        elif kind == "hll":
            st.hll_regs = hll.empty_state(self._set_pool_rows)

    def _ensure_fresh(self, st: _IntervalState, kind: str) -> None:
        """Lazy per-type state reinit.  After a swap the old planes
        belong to the snapshot; a type is only given NEW zeroed planes
        when something actually touches it — re-zeroing every state
        family every interval is a dispatch each, which dominated
        sparse intervals.  Alloc BEFORE
        discarding from fresh so an allocation failure can't leave
        the table aliasing (and later donating) a snapshot's plane."""
        if kind in st.fresh:
            self._alloc_state(st, kind)
            st.fresh.discard(kind)

    # ------------------------------------------------------------------
    # interval-state forwarding: direct consumers (tests, benches, the
    # sharded aggregator's shards) address the CURRENT interval's
    # planes as plain table attributes; the pipelined apply path pins
    # explicit _IntervalState objects instead (take_staged/begin_swap)

    counters = _state_property("counters")
    gauges = _state_property("gauges")
    histo_stats = _state_property("histo_stats")
    histo_import_stats = _state_property("histo_import_stats")
    histo_means = _state_property("histo_means")
    histo_weights = _state_property("histo_weights")
    hll_regs = _state_property("hll_regs")
    _hll_host_plane = _state_property("hll_host_plane")
    _hll_host_ez = _state_property("hll_host_ez")
    _hll_host_inv = _state_property("hll_host_inv")
    _hll_device_touched = _state_property("hll_device_touched")
    _fresh = _state_property("fresh")

    # ------------------------------------------------------------------
    # ingest

    def ingest(self, s: dsd.Sample) -> bool:
        """Slow-path single-sample ingest (tests / low-volume paths).
        Returns False on row-table overflow (sample dropped+counted)."""
        key = (s.name, s.type, s.tags, s.scope)
        weight = 1.0 / s.sample_rate
        if s.type == dsd.COUNTER:
            row = self.counter_idx.lookup(key, s.name, s.tags, s.scope,
                                          s.type, self.gen)
            if row is None:
                return False
            self._counter_dense[row] += s.value * weight
            self._counter_dirty = True
            self._note_staged(1)
        elif s.type == dsd.GAUGE:
            row = self.gauge_idx.lookup(key, s.name, s.tags, s.scope,
                                        s.type, self.gen)
            if row is None:
                return False
            self._gauge_dense[row] = s.value
            self._gauge_mask[row] = 1
            self._gauge_dirty = True
            self._note_staged(1)
        elif s.type in (dsd.TIMER, dsd.HISTOGRAM):
            row = self.histo_idx.lookup(key, s.name, s.tags, s.scope,
                                        s.type, self.gen)
            if row is None:
                return False
            self._histo_stage.append([row], [s.value], [weight])
            self._note_staged(1)
        elif s.type == dsd.SET:
            row = self.set_idx.lookup(key, s.name, s.tags, s.scope,
                                      s.type, self.gen)
            if row is None:
                return False
            self._set_rows.append(row)
            member = s.value if isinstance(s.value, bytes) else str(
                s.value).encode()
            self._set_members.append(member)
            self._note_staged(1)
        elif s.type == dsd.STATUS:
            self.status[key] = (float(s.value), s.message, s.tags)
        else:
            raise ValueError(f"unknown metric type {s.type}")
        return True

    def ingest_many(self, samples) -> int:
        dropped = 0
        for s in samples:
            if not self.ingest(s):
                dropped += 1
        return dropped

    # ------------------------------------------------------------------
    # columnar fast path

    def _class_for_code(self, code: int) -> _ClassIndex:
        if code == columnar.CODE_COUNTER:
            return self.counter_idx
        if code == columnar.CODE_GAUGE:
            return self.gauge_idx
        if code in (columnar.CODE_TIMER, columnar.CODE_HISTOGRAM):
            return self.histo_idx
        return self.set_idx

    def _resolve_misses(self, pb: columnar.ParsedBatch,
                        miss_lines: np.ndarray,
                        miss_keys: np.ndarray) -> None:
        """Allocate rows for never-seen series: slow-parse ONE
        representative line per unique identity hash, allocate through
        the authoritative dict index, and remember the mapping (or a
        DROPPED marker on class overflow) in the key index."""
        _, first = np.unique(miss_keys, return_index=True)
        for fp in first:
            i = int(miss_lines[fp])
            k = int(miss_keys[fp])
            try:
                s = dsd.parse_metric(pb.line(i))
            except dsd.ParseError:
                self.key_index.insert(k, intern.DROPPED)
                continue
            cls = self._class_for_code(int(pb.type_code[i]))
            row = cls.lookup((s.name, s.type, s.tags, s.scope), s.name,
                             s.tags, s.scope, s.type, self.gen,
                             key_hash=k, count_overflow=False)
            self.key_index.insert(
                k, row if row is not None else intern.DROPPED)

    def ingest_columns(self, pb: columnar.ParsedBatch
                       ) -> tuple[int, int]:
        """Batch ingest of a parsed buffer's metric lines (type codes
        0-4; events/service-checks/errors are the caller's per-line
        business).  Returns (processed, dropped).  With the native
        library this is ONE C++ pass (probe + combine); the numpy
        fallback is a handful of vectorized passes — either way no
        per-sample Python.
        """
        if self._lib is not None and isinstance(
                self.key_index, intern.NativeHashIndex):
            return self._ingest_columns_native(pb)
        tc = pb.type_code
        sel = np.nonzero(tc <= columnar.CODE_SET)[0]
        if len(sel) == 0:
            return 0, 0
        keys = pb.key_hash[sel]
        rows = self.key_index.lookup(keys)
        miss = rows == intern.MISSING
        if miss.any():
            self._resolve_misses(pb, sel[miss], keys[miss])
            rows = self.key_index.lookup(keys)
        live = rows >= 0
        dropped = int((~live).sum())
        if dropped:
            # count overflow per class (reference drops-and-counts)
            for code in np.unique(tc[sel][~live]):
                self._class_for_code(int(code)).drops.add(int(
                    ((tc[sel] == code) & ~live).sum()))

        codes = tc[sel]
        vals = pb.value[sel]
        wts = pb.weight[sel]

        cmask = (codes == columnar.CODE_COUNTER) & live
        if cmask.any():
            r = rows[cmask]
            self._counter_dense += np.bincount(
                r, weights=vals[cmask] * wts[cmask],
                minlength=self.config.counter_rows)
            self._counter_dirty = True
            self.counter_idx.touch_rows(r, self.gen)

        gmask = (codes == columnar.CODE_GAUGE) & live
        if gmask.any():
            r = rows[gmask]
            # fancy assignment applies in index order: last write wins
            self._gauge_dense[r] = vals[gmask]
            self._gauge_mask[r] = 1
            self._gauge_dirty = True
            self.gauge_idx.touch_rows(r, self.gen)

        hmask = ((codes == columnar.CODE_TIMER) |
                 (codes == columnar.CODE_HISTOGRAM)) & live
        if hmask.any():
            r = rows[hmask]
            self._histo_stage.append(r, vals[hmask], wts[hmask])
            self.histo_idx.touch_rows(r, self.gen)

        smask = (codes == columnar.CODE_SET) & live
        if smask.any():
            r = rows[smask]
            idx, rank = hashing.hll_position(pb.member_hash[sel][smask])
            self._set_pos_rows.append(np.asarray(r, np.int32))
            self._set_pos.append(hll.pack_positions(idx, rank))
            self.set_idx.touch_rows(r, self.gen)

        processed = len(sel)
        self._note_staged(processed - dropped)
        return processed, dropped

    def ingest_buffer(self, buf
                      ) -> tuple[int, int, list[tuple[int, int, int]]]:
        """Fused parse + probe + combine over a raw newline-separated
        buffer (native vtpu_parse_ingest): no column materialization
        between the grammar and the table.  For SINGLE-READER
        pipelines — the split parse/ingest_columns design exists so
        multi-reader servers can parse outside the table lock.

        Returns (processed, dropped, others) where others is
        [(offset, length, type_code)] for event / service-check /
        error lines — the caller's per-line business, as with
        ingest_columns.  Falls back to parse + ingest_columns when
        the native library is unavailable."""
        if self._lib is None or not isinstance(
                self.key_index, intern.NativeHashIndex):
            parser = getattr(self, "_fallback_parser", None)
            if parser is None:
                parser = columnar.ColumnarParser()
                self._fallback_parser = parser
            pb = parser.parse(bytes(buf), copy=False)
            processed, dropped = self.ingest_columns(pb)
            tc = pb.type_code[:pb.n]
            others = [(int(pb.line_off[i]), int(pb.line_len[i]),
                       int(tc[i]))
                      for i in np.nonzero(
                          (tc > columnar.CODE_SET)
                          & (tc != columnar.CODE_SHED))[0]]
            return processed, dropped, others
        import ctypes as ct
        buf_b = bytes(buf) if not isinstance(buf, bytes) else buf
        buf_np = np.frombuffer(buf_b, np.uint8)
        n_est = buf_b.count(b"\n") + 1
        sc = self._fused_scratch
        if sc is None or len(sc["hr"]) < n_est:
            cap = max(n_est, 4096)
            sc = self._fused_scratch = {
                "hr": np.empty(cap, np.int32),
                "hv": np.empty(cap, np.float32),
                "hw": np.empty(cap, np.float32),
                "sr": np.empty(cap, np.int32),
                "sp": np.empty(cap, np.int32),
                "mk": np.empty(cap, np.uint64),
                "mt": np.empty(cap, np.uint8),
                "mv": np.empty(cap, np.float64),
                "mm": np.empty(cap, np.uint64),
                "mw": np.empty(cap, np.float32),
                "mo": np.empty(cap, np.int64),
                "ml": np.empty(cap, np.int32),
                "oo": np.empty(cap, np.int64),
                "ol": np.empty(cap, np.int32),
                "ok": np.empty(cap, np.uint8),
            }
        meta = np.zeros(12, np.int64)

        def p(a, t):
            return a.ctypes.data_as(ct.POINTER(t))

        u8p = ct.c_uint8
        self._lib.vtpu_parse_ingest(
            p(buf_np, u8p), len(buf_np),
            self.key_index.handle, hashing.HLL_P,
            p(self._counter_dense, ct.c_double),
            p(self.counter_idx.touched.view(np.uint8), u8p),
            p(self._gauge_dense, ct.c_float),
            p(self._gauge_mask, u8p),
            p(self.gauge_idx.touched.view(np.uint8), u8p),
            p(sc["hr"], ct.c_int32), p(sc["hv"], ct.c_float),
            p(sc["hw"], ct.c_float),
            p(self.histo_idx.touched.view(np.uint8), u8p),
            p(sc["sr"], ct.c_int32), p(sc["sp"], ct.c_int32),
            p(self.set_idx.touched.view(np.uint8), u8p),
            p(sc["mk"], ct.c_uint64), p(sc["mt"], u8p),
            p(sc["mv"], ct.c_double), p(sc["mm"], ct.c_uint64),
            p(sc["mw"], ct.c_float),
            p(sc["mo"], ct.c_int64), p(sc["ml"], ct.c_int32),
            p(sc["oo"], ct.c_int64), p(sc["ol"], ct.c_int32),
            p(sc["ok"], u8p),
            p(meta, ct.c_int64))

        n_miss = int(meta[2])
        if n_miss:
            shim = _MissLines(buf_np, sc["mo"], sc["ml"], sc["mt"])
            self._resolve_misses(shim, np.arange(n_miss),
                                 sc["mk"][:n_miss])
            # replay the compact miss columns through the column
            # combiner (resolved keys now hit; unparseable ones are
            # DROPPED and counted) — same staging buffers, same meta
            i64p = ct.POINTER(ct.c_int64)
            miss2 = np.empty(n_miss, np.int64)
            self._lib.vtpu_ingest(
                self.key_index.handle,
                p(sc["mk"], ct.c_uint64), p(sc["mt"], u8p),
                p(sc["mv"], ct.c_double), p(sc["mm"], ct.c_uint64),
                p(sc["mw"], ct.c_float), n_miss,
                miss2.ctypes.data_as(i64p), -1,
                hashing.HLL_P,
                p(self._counter_dense, ct.c_double),
                p(self.counter_idx.touched.view(np.uint8), u8p),
                p(self._gauge_dense, ct.c_float),
                p(self._gauge_mask, u8p),
                p(self.gauge_idx.touched.view(np.uint8), u8p),
                p(sc["hr"], ct.c_int32), p(sc["hv"], ct.c_float),
                p(sc["hw"], ct.c_float),
                p(self.histo_idx.touched.view(np.uint8), u8p),
                p(sc["sr"], ct.c_int32), p(sc["sp"], ct.c_int32),
                p(self.set_idx.touched.view(np.uint8), u8p),
                miss2.ctypes.data_as(i64p),
                p(meta, ct.c_int64))

        processed = int(meta[3])
        dropped = int(meta[6:11].sum())
        if dropped:
            self.counter_idx.drops.add(int(meta[6]))
            self.gauge_idx.drops.add(int(meta[7]))
            self.histo_idx.drops.add(int(meta[8] + meta[9]))
            self.set_idx.drops.add(int(meta[10]))
        if meta[4]:
            self._counter_dirty = True
        if meta[5]:
            self._gauge_dirty = True
        hn = int(meta[0])
        if hn:
            self._histo_stage.append(sc["hr"][:hn].copy(),
                                     sc["hv"][:hn].copy(),
                                     sc["hw"][:hn].copy())
        sn = int(meta[1])
        if sn:
            self._set_pos_rows.append(sc["sr"][:sn].copy())
            self._set_pos.append(sc["sp"][:sn].copy())
        self._note_staged(processed - dropped)
        n_other = int(meta[11])
        others = [(int(sc["oo"][i]), int(sc["ol"][i]),
                   int(sc["ok"][i])) for i in range(n_other)]
        return processed, dropped, others

    def _ingest_columns_native(self, pb: columnar.ParsedBatch
                               ) -> tuple[int, int]:
        """Single-pass C++ ingest (vtpu_ingest): probe the native
        identity index and combine into dense counter/gauge buffers and
        histo/set append columns, all in one cache-friendly loop.
        Python only resolves never-seen keys, then re-runs the pass
        over just the recorded miss lines."""
        import ctypes as ct
        n = pb.n
        if n == 0:
            return 0, 0
        lib = self._lib
        u8p = ct.POINTER(ct.c_uint8)
        u64p = ct.POINTER(ct.c_uint64)
        f32p = ct.POINTER(ct.c_float)
        f64p = ct.POINTER(ct.c_double)
        i32p = ct.POINTER(ct.c_int32)
        i64p = ct.POINTER(ct.c_int64)

        hr = np.empty(n, np.int32)
        hv = np.empty(n, np.float32)
        hw = np.empty(n, np.float32)
        sr = np.empty(n, np.int32)
        sp = np.empty(n, np.int32)
        miss = np.empty(n, np.int64)
        meta = np.zeros(11, np.int64)

        def run(subset_n: int) -> None:
            lib.vtpu_ingest(
                self.key_index.handle,
                pb.key_hash.ctypes.data_as(u64p),
                pb.type_code.ctypes.data_as(u8p),
                pb.value.ctypes.data_as(f64p),
                pb.member_hash.ctypes.data_as(u64p),
                pb.weight.ctypes.data_as(f32p),
                n,
                miss.ctypes.data_as(i64p), subset_n,
                hashing.HLL_P,
                self._counter_dense.ctypes.data_as(f64p),
                self.counter_idx.touched.view(np.uint8)
                    .ctypes.data_as(u8p),
                self._gauge_dense.ctypes.data_as(f32p),
                self._gauge_mask.ctypes.data_as(u8p),
                self.gauge_idx.touched.view(np.uint8)
                    .ctypes.data_as(u8p),
                hr.ctypes.data_as(i32p),
                hv.ctypes.data_as(f32p),
                hw.ctypes.data_as(f32p),
                self.histo_idx.touched.view(np.uint8)
                    .ctypes.data_as(u8p),
                sr.ctypes.data_as(i32p),
                sp.ctypes.data_as(i32p),
                self.set_idx.touched.view(np.uint8)
                    .ctypes.data_as(u8p),
                miss.ctypes.data_as(i64p),
                meta.ctypes.data_as(i64p))

        run(-1)
        n_miss = int(meta[2])
        if n_miss:
            miss_lines = miss[:n_miss].copy()
            self._resolve_misses(pb, miss_lines,
                                 pb.key_hash[miss_lines])
            # second pass over just the miss lines (resolved keys now
            # hit; unparseable ones are DROPPED and counted)
            run(n_miss)

        processed = int(meta[3])
        dropped = int(meta[6:11].sum())
        if dropped:
            self.counter_idx.drops.add(int(meta[6]))
            self.gauge_idx.drops.add(int(meta[7]))
            self.histo_idx.drops.add(int(meta[8] + meta[9]))
            self.set_idx.drops.add(int(meta[10]))
        if meta[4]:
            self._counter_dirty = True
        if meta[5]:
            self._gauge_dirty = True
        hn = int(meta[0])
        if hn:
            # copy: the slices view n-sized scratch, and staging now
            # holds them until the swap — a view would pin 12 bytes
            # per parsed LINE for the interval, not per histo sample
            self._histo_stage.append(hr[:hn].copy(), hv[:hn].copy(),
                                     hw[:hn].copy())
        sn = int(meta[1])
        if sn:
            # copy: sr/sp are n-sized per-call scratch and set staging
            # now holds entries until the swap (see _histo_stage note)
            self._set_pos_rows.append(sr[:sn].copy())
            self._set_pos.append(sp[:sn].copy())
        self._note_staged(processed - dropped)
        return processed, dropped

    def staged(self) -> int:
        return self._staged_n

    def overflow_total(self) -> int:
        """Interval overflow drops summed over classes.  Import call
        sites delta this around an apply (under the ingest lock) to
        split their dropped counts into overflow vs invalid for the
        conservation ledger."""
        return (self.counter_idx.overflow + self.gauge_idx.overflow +
                self.histo_idx.overflow + self.set_idx.overflow)

    def set_pressure_level(self, level: int) -> None:
        """Overload pressure hook (core/overload.py): level > 0 steps
        the effective histogram merge width down the pad ladder (one
        halving per level, floored at the ladder minimum) so deep
        batches collapse earlier — reduced sketch resolution instead
        of dropped samples, per the SALSA tradeoff.  Level 0 restores
        the configured width.  Takes effect on the next merge call;
        every width is a ladder value, so the compile cache stays
        bounded."""
        level = max(0, int(level))
        if level == self._pressure_level:
            return
        self._pressure_level = level
        base = self._eff_histo_slots_base
        if level == 0:
            self._eff_histo_slots = base
        else:
            self._eff_histo_slots = _ladder_floor(
                max(base >> level, 1))
        # Composition with per-series tiers: the emergency ladder
        # narrows MERGE WIDTH on the wide pool only — compact-tier
        # series hold raw samples / sparse registers that never pass
        # through the merge, so a level-3 narrow cannot double-shrink
        # an already-compact series below its accuracy floor.  Levels
        # >= 2 additionally pause BOUNDARY promotions (steady-state
        # economics defer to the emergency; correctness escalations
        # still run so compact stores stay bounded), and because the
        # per-row tier bits are never touched here, release restores
        # each series' own tier, not a global base.
        if self.tiers is not None:
            with self.tiers.lock:
                self.tiers.promote_frozen = level >= 2

    def _note_staged(self, n: int) -> None:
        """Staged-sample bookkeeping shared by every DSD ingest path:
        the device-step trigger counter and the interval conservation
        count move together so they can't diverge.  Import paths bump
        ``_interval_ingested`` at ITEM granularity instead (their
        staging parts — centroids, register planes — don't map 1:1 to
        wire items)."""
        self._staged_n += n
        self._interval_ingested += n

    # ------------------------------------------------------------------
    # global-tier import (merge of forwarded mergeable state)

    # -- row-resolution halves + batch appliers for the cached gRPC
    #    fast path (forward/grpc_forward.apply_metric_list_bytes):
    #    resolution runs once per novel series, application runs
    #    vectorized over whole decoded MetricLists ------------------

    def import_counter_row(self, name: str,
                           tags: tuple[str, ...]) -> int | None:
        key = (name, dsd.COUNTER, tags, dsd.SCOPE_GLOBAL)
        return self.counter_idx.lookup(key, name, tags,
                                       dsd.SCOPE_GLOBAL, dsd.COUNTER,
                                       self.gen)

    def import_gauge_row(self, name: str,
                         tags: tuple[str, ...]) -> int | None:
        key = (name, dsd.GAUGE, tags, dsd.SCOPE_GLOBAL)
        return self.gauge_idx.lookup(key, name, tags,
                                     dsd.SCOPE_GLOBAL, dsd.GAUGE,
                                     self.gen)

    def import_set_row(self, name: str, tags: tuple[str, ...],
                       scope: str = dsd.SCOPE_DEFAULT) -> int | None:
        key = (name, dsd.SET, tags, scope)
        return self.set_idx.lookup(key, name, tags, scope, dsd.SET,
                                   self.gen)

    def import_counter_batch(self, rows: np.ndarray,
                             values: np.ndarray) -> None:
        """Vectorized import_counter over resolved rows (+= merge;
        duplicate rows accumulate, matching per-item order
        independence of addition)."""
        rows = np.ascontiguousarray(rows, np.int64)
        np.add.at(self._counter_dense, rows,
                  np.asarray(values, np.float64))
        self.counter_idx.touch_rows(rows, self.gen)
        self._counter_dirty = True
        self._staged_n += len(rows)
        self._interval_ingested += len(rows)

    def import_gauge_batch(self, rows: np.ndarray,
                           values: np.ndarray) -> None:
        """Vectorized import_gauge (last-write-wins in wire order —
        duplicates resolve to the LAST occurrence explicitly; numpy's
        duplicate-index assignment order is unspecified)."""
        rows = np.ascontiguousarray(rows, np.int64)
        values = np.asarray(values, np.float64)
        rev_u, rev_first = np.unique(rows[::-1], return_index=True)
        last = len(rows) - 1 - rev_first
        self._gauge_dense[rev_u] = values[last]
        self._gauge_mask[rev_u] = 1
        self.gauge_idx.touch_rows(rows, self.gen)
        self._gauge_dirty = True
        self._staged_n += len(rows)
        self._interval_ingested += len(rows)

    def _set_import_rows(self) -> np.ndarray:
        """The interval's host import plane, made with its touched
        mask at the first imported sketch."""
        if self._set_import_plane is None:
            c = self.config
            self._set_import_plane = np.zeros((c.set_rows, hll.M),
                                              np.uint8)
            self._set_import_touched = np.zeros(c.set_rows, bool)
        return self._set_import_plane

    def import_set_at(self, row: int, regs: np.ndarray) -> None:
        """import_set's staging half for a pre-resolved row: one
        16 KiB register max into the host import plane (Set.Merge,
        samplers/samplers.go:423)."""
        regs = np.asarray(regs, np.uint8)
        if regs.shape != (hll.M,):
            raise ValueError(f"bad register plane shape {regs.shape}")
        prow = self._set_import_rows()[row]
        np.maximum(prow, regs, out=prow)
        self._set_import_touched[row] = True
        self.set_idx.touched[row] = True
        self.set_idx.last_gen[row] = self.gen
        self._staged_n += 1
        self._interval_ingested += 1
        self._state.import_counts["set_planes"] += 1

    def import_set_wire(self, data: bytes, offs: np.ndarray,
                        lens: np.ndarray,
                        rows: np.ndarray) -> np.ndarray:
        """import_set_at for every dense sketch of one decoded wire:
        sketch i is the ``lens[i]`` bytes of ``data`` at ``offs[i]``
        (axiomhq form, forward/hll_codec.py) for the resolved row
        ``rows[i]``.  One native call (vtpu_hll_union_dense)
        validates, unpacks and maxes them into the host import plane
        in wire order, without the interpreter lock; the bookkeeping
        of that many import_set_at calls follows once, as arrays.
        Returns a status an item: 0 unioned, non-zero left untouched
        (sparse, a header the dense decode refuses, short; all of
        them without the native library) for the caller to put
        through ``hll_codec.decode`` + ``import_set_at`` one by one,
        counted in ``import_counts["set_planes_loose"]``."""
        counts = self._state.import_counts
        status = union_dense_sketches(self._lib, data, offs, lens, rows,
                                      self._set_import_rows)
        done = np.asarray(rows, np.int64)[status == 0]
        if len(done):
            self._set_import_touched[done] = True
            self.set_idx.touch_rows(done, self.gen)
            self._staged_n += len(done)
            self._interval_ingested += len(done)
            counts["set_planes"] += len(done)
        counts["set_planes_loose"] += int((status != 0).sum())
        return status

    def import_counter(self, name: str, tags: tuple[str, ...],
                       value: float) -> bool:
        """Merge a forwarded counter total (+=; reference
        samplers/samplers.go:208).  Imported counters/gauges are forced
        global scope (reference worker.go:445-447)."""
        key = (name, dsd.COUNTER, tags, dsd.SCOPE_GLOBAL)
        row = self.counter_idx.lookup(key, name, tags, dsd.SCOPE_GLOBAL,
                                      dsd.COUNTER, self.gen)
        if row is None:
            return False
        self._counter_dense[row] += value
        self._counter_dirty = True
        self._staged_n += 1
        self._interval_ingested += 1
        return True

    def import_gauge(self, name: str, tags: tuple[str, ...],
                     value: float) -> bool:
        key = (name, dsd.GAUGE, tags, dsd.SCOPE_GLOBAL)
        row = self.gauge_idx.lookup(key, name, tags, dsd.SCOPE_GLOBAL,
                                    dsd.GAUGE, self.gen)
        if row is None:
            return False
        self._gauge_dense[row] = value
        self._gauge_mask[row] = 1
        self._gauge_dirty = True
        self._staged_n += 1
        self._interval_ingested += 1
        return True

    def import_histo(self, name: str, mtype: str, tags: tuple[str, ...],
                     stats: np.ndarray, means: np.ndarray,
                     weights: np.ndarray,
                     scope: str = dsd.SCOPE_DEFAULT) -> bool:
        """Merge a forwarded digest: centroids re-enter as weighted
        samples through the normal merge kernel (a centroid IS a
        weighted sample); the 5-column stat row merges by scatter.

        Shapes are validated BEFORE anything is staged: a malformed
        item staged with the wrong width would make the next
        device_step raise with the bad entry still queued, wedging the
        whole table until restart."""
        stats = np.asarray(stats, np.float32)
        means = np.asarray(means, np.float32)
        weights = np.asarray(weights, np.float32)
        if stats.shape != (segment.HISTO_STAT_COLS,):
            raise ValueError(f"bad stats shape {stats.shape}")
        if means.shape != weights.shape or means.ndim != 1:
            raise ValueError(
                f"centroid shape mismatch {means.shape}/{weights.shape}")
        key = (name, mtype, tags, scope)
        row = self.histo_idx.lookup(key, name, tags, scope, mtype,
                                    self.gen)
        if row is None:
            return False
        self._stats_import_parts.append(
            (np.asarray([row], np.int32), stats[None, :]))
        self._staged_n += 1
        self._interval_ingested += 1
        live = weights > 0
        if live.any():
            n_live = int(live.sum())
            self._digest_stage.append(
                np.full(n_live, row, np.int32),
                means[live], weights[live])
            # count every staged centroid, not 1 per import — the
            # staging-memory bound rides on this counter
            self._staged_n += n_live
        return True

    def import_histo_row(self, name: str, mtype: str,
                         tags: tuple[str, ...],
                         scope: str = dsd.SCOPE_DEFAULT) -> int | None:
        """Row allocation only (the lookup half of import_histo), for
        the batched gRPC decode path."""
        key = (name, mtype, tags, scope)
        return self.histo_idx.lookup(key, name, tags, scope, mtype,
                                     self.gen)

    def import_histo_batch(self, rows: np.ndarray, stats: np.ndarray,
                           cent_rows: np.ndarray,
                           cent_means: np.ndarray,
                           cent_weights: np.ndarray) -> None:
        """Batched import_histo: one staging append for a whole
        decoded MetricList (the columnar half of the native
        vtpu_metriclist_decode path).  ``rows``/``stats`` are
        row-aligned (N,)/(N,5); centroid arrays are pre-filtered to
        live (weight>0, finite) entries with per-centroid target rows.
        Caller guarantees validity — malformed items must be dropped
        BEFORE staging (see import_histo's shape note)."""
        if len(rows):
            self._stats_import_parts.append(
                (np.ascontiguousarray(rows, np.int32),
                 np.ascontiguousarray(stats, np.float32)))
            # rows may be cache-resolved (no lookup ran): touch here
            # so flush emission and compaction survival see them
            self.histo_idx.touch_rows(np.asarray(rows, np.int64),
                                      self.gen)
            self._staged_n += len(rows)
            self._interval_ingested += len(rows)
        if len(cent_rows):
            part = (np.ascontiguousarray(cent_rows, np.int32),
                    np.ascontiguousarray(cent_means, np.float32),
                    np.ascontiguousarray(cent_weights, np.float32))
            if self.fused_import_mode == "legacy":
                # pre-fusion behavior: all wires' centroids interleave
                # by within-row rank into one flat ranked merge
                self._digest_stage.append(*part)
            else:
                # one part per wire list: the apply stacks what is
                # staged at the next step into a single (n_wires,
                # rows, K) kernel call (_wire_digest_step)
                self._wire_digest_parts.append(part)
            self._staged_n += len(cent_rows)

    def import_set(self, name: str, tags: tuple[str, ...],
                   regs: np.ndarray,
                   scope: str = dsd.SCOPE_DEFAULT) -> bool:
        """Merge a forwarded HLL register plane (union by max).  Shape
        validated before staging (see import_histo)."""
        regs = np.asarray(regs, np.uint8)
        if regs.shape != (hll.M,):
            raise ValueError(f"bad register plane shape {regs.shape}")
        key = (name, dsd.SET, tags, scope)
        row = self.set_idx.lookup(key, name, tags, scope, dsd.SET,
                                  self.gen)
        if row is None:
            return False
        self.import_set_at(row, regs)
        return True

    # ------------------------------------------------------------------
    # device step

    def device_step(self, final: bool = False) -> None:
        """Push all staged samples to the device as batched updates
        (serial form: detach + apply back-to-back; the pipelined path
        is take_staged/apply_staged).

        Counters and gauges are pre-combined on host into dense per-row
        vectors (duplicate rows collapse — legal because counter merge
        is associative addition and gauge merge is last-write), so the
        h2d transfer is O(rows) not O(samples).  Histo values ship as
        a host-densified value plane when dense enough, else
        per-sample; sets ship either a host-folded register plane or
        8 packed bytes per member (whichever is smaller).

        Histo/digest AND set staging only flush when ``final`` (the
        swap) or past ``histo_merge_samples`` — per-step digest merges
        multiply cluster work by the number of steps per interval, and
        whole-interval set batches dedup into the register plane.
        Forwarded wires' digests go with every step: each is a batch
        already (``_detach_staged``)."""
        w = self._detach_staged(final)
        if w.empty:
            return
        with self._device_lock, observe.annotate("apply.staged"):
            self._apply_work(w)

    def take_staged(self, final: bool = False) -> _StagedWork | None:
        """Pipelined half 1: detach the staging buffers in O(µs) and
        pin the current interval state.  MUST run under the same lock
        that serializes ingest and begin_swap — the pending count it
        bumps is what complete_swap waits out, so the bump has to be
        atomic with the detach (a swap slipping between them could
        snapshot before this work lands and lose its samples)."""
        w = self._detach_staged(final)
        if w.empty:
            return None
        with self._pending_cv:
            w.state.pending += 1
        return w

    def apply_staged(self, w: _StagedWork) -> None:
        """Pipelined half 2: run the detached work's host concat/hash
        and jitted dispatch OUTSIDE the ingest lock.  Any thread may
        call it; applies serialize on the table's device lock.  Order
        between two mid-interval applies is immaterial — every staged
        family merges associatively (counter add, gauge last-write
        only ships in the single final work, digest merge order only
        perturbs centroid placement, set register max) — and the
        pinned state guarantees the right interval."""
        try:
            with self._device_lock, observe.annotate("apply.staged"):
                self._apply_work(w)
        finally:
            with self._pending_cv:
                w.state.pending -= 1
                self._pending_cv.notify_all()

    def checkpoint_capture(self) -> dict | None:
        """Copy the open interval's HOST staging for a crash
        checkpoint.  MUST run under the caller's ingest lock; does no
        device work and detaches nothing — ingest keeps combining into
        the live buffers while the checkpointer serializes the copies
        off-lock (the copy IS the double-buffer).

        Mid-interval essentially all staged mass is host-side: dense
        counter/gauge accumulators only ship at the swap, and the
        list stagings detach early only past the histo_merge_samples
        (4M-sample) / 64K-stat-row thresholds, forwarded wires'
        digests with any step.  Whatever DID move to
        device state early is counted in ``device_staged`` so the
        checkpoint names its blind spot instead of hiding it.

        Staging lists are captured as shallow list copies: they only
        ever append ndarray chunks that no ingest path mutates
        afterwards (reader-shard commits copy their scratch before
        appending), so the chunks themselves are safe to share.  The
        per-class meta lists are captured as (reference, length)
        pairs: they are append-only, and compaction REPLACES the list
        object at a swap boundary, so a held reference stays
        self-consistent with the captured row ids.

        Returns None when nothing is staged (nothing to lose)."""
        cap: dict = {"gen": self.gen,
                     "ingested": self._interval_ingested,
                     "device_staged": self._interval_device_staged}
        data = False
        if self._counter_dirty:
            cap["counter"] = self._counter_dense.copy()
            data = True
        if self._gauge_dirty:
            cap["gauge"] = (self._gauge_dense.copy(),
                            self._gauge_mask.copy())
            data = True
        if self._histo_stage.rows:
            s = self._histo_stage
            cap["histo"] = (list(s.rows), list(s.values),
                            list(s.weights))
            data = True
        if self._digest_stage.rows:
            s = self._digest_stage
            cap["digest"] = (list(s.rows), list(s.values),
                            list(s.weights))
            data = True
        if self._wire_digest_parts:
            cap["wire_parts"] = list(self._wire_digest_parts)
            data = True
        if self._stats_import_parts:
            cap["stats_parts"] = list(self._stats_import_parts)
            data = True
        if self._set_rows:
            cap["set_members"] = (list(self._set_rows),
                                  list(self._set_members))
            data = True
        if self._set_pos_rows:
            cap["set_pos"] = (list(self._set_pos_rows),
                              list(self._set_pos))
            data = True
        if (self._set_import_touched is not None and
                self._set_import_touched.any()):
            rows = np.flatnonzero(self._set_import_touched)
            cap["set_import"] = (rows.astype(np.int32),
                                 self._set_import_plane[rows].copy())
            data = True
        if not data:
            return None
        cap["counter_meta"] = (self.counter_idx.meta,
                               len(self.counter_idx.meta))
        cap["gauge_meta"] = (self.gauge_idx.meta,
                             len(self.gauge_idx.meta))
        cap["histo_meta"] = (self.histo_idx.meta,
                             len(self.histo_idx.meta))
        cap["set_meta"] = (self.set_idx.meta,
                           len(self.set_idx.meta))
        return cap

    def _detach_staged(self, final: bool) -> _StagedWork:
        """Hand off staging buffers for one apply.  Runs under the
        ingest lock and does NO concatenation, hashing, or device
        work: dense buffers swap for zeroed ones, list staging swaps
        for empty lists — the O(n) work happens in _apply_work."""
        c = self.config
        w = _StagedWork()
        w.state = self._state
        w.final = final
        w.counter = w.gauge = w.histo = w.digest = None
        w.wire_parts = w.set_parts = w.stats_parts = None
        w.set_import = None
        self._staged_n = 0
        # counters/gauges are DENSE per-row interval accumulators —
        # nothing grows with sample count — so their single O(R) ship
        # happens once, at the swap, not per device step (mid-interval
        # ships doubled the h2d bytes for zero benefit)
        if self._counter_dirty and final:
            w.counter = self._counter_dense
            self._counter_dense = np.zeros(c.counter_rows, np.float64)
            self._counter_dirty = False
        if self._gauge_dirty and final:
            w.gauge = (self._gauge_dense, self._gauge_mask)
            self._gauge_dense = np.zeros(c.gauge_rows, np.float32)
            self._gauge_mask = np.zeros(c.gauge_rows, np.uint8)
            self._gauge_dirty = False
        if self._histo_stage.rows and (
                final or
                len(self._histo_stage) >= c.histo_merge_samples):
            w.histo = self._histo_stage
            self._histo_stage = _Staging()
        if self._digest_stage.rows and (
                final or
                len(self._digest_stage) >= c.histo_merge_samples):
            w.digest = self._digest_stage
            self._digest_stage = _Staging()
        # a forwarded wire's digests are a batch already, and merging
        # them alone costs no more than beside other wires (one merge
        # a row a wire on the stack, one a row a step on the flat
        # path), so every step takes the wires that are staged.  Held
        # for the swap, the last wires of the locals' burst merge on
        # the flush's path: 0.1 to 0.35 s of a global's 0.14 to 0.4 s
        # lag on the v5e, by which locals happened to call last
        # (PERF.md, finding 34.2)
        if self._wire_digest_parts:
            w.wire_parts = self._wire_digest_parts
            self._wire_digest_parts = []
        staged_sets = (len(self._set_rows) +
                       sum(len(r) for r in self._set_pos_rows))
        if (staged_sets and
                (final or staged_sets >= c.histo_merge_samples)):
            w.set_parts = (self._set_rows, self._set_members,
                           self._set_pos_rows, self._set_pos)
            self._set_rows, self._set_members = [], []
            self._set_pos_rows, self._set_pos = [], []
        # The imports' statistics and register planes flush at the
        # swap like the digest stage: a global node receiving K wire
        # lists per interval otherwise pays K small dispatches of a
        # few rows (and, for sets, ships
        # every list's register planes separately — the cross-list
        # dedup collapsed 64 MB/interval to ~2 MB once deferred).
        # Size gates bound host staging between swaps.
        if self._stats_import_parts and (
                final or
                sum(len(p[0]) for p in self._stats_import_parts)
                >= (1 << 16)):
            w.stats_parts = self._stats_import_parts
            self._stats_import_parts = []
        if (final and self._set_import_touched is not None and
                self._set_import_touched.any()):
            w.set_import = (self._set_import_plane,
                            self._set_import_touched)
            self._set_import_plane = None
            self._set_import_touched = None
        w.empty = (w.counter is None and w.gauge is None and
                   w.histo is None and w.digest is None and
                   w.wire_parts is None and w.set_parts is None and
                   w.stats_parts is None and w.set_import is None)
        if not final and not w.empty:
            # mid-interval detach: these samples move to device state
            # and out of any future checkpoint's view — tally them so
            # the checkpoint header names what it does NOT cover
            n = w.histo_samples() + w.set_members()
            if w.stats_parts is not None:
                n += sum(len(p[0]) for p in w.stats_parts)
            self._interval_device_staged += n
        return w

    def _apply_work(self, w: _StagedWork) -> None:
        """Apply detached staging to its pinned interval state: the
        concat/hash host work and every jitted dispatch — everything
        the ingest lock must NOT cover.  Caller holds _device_lock.

        When the superbatch gate is on (and the table untiered), the
        fused arm consumes every family the one-buffer schema can
        carry and nulls it on ``w``; whatever it declines — wire and
        import merges, plane-densified or deep histo batches, the
        device-free host set fold — falls through to the per-class
        dispatches below, which double as the bit-parity oracle."""
        st = w.state
        c = self.config
        if self._sb_on and self.tiers is None:
            self._superbatch_apply(w)
        if w.counter is not None:
            self._ensure_fresh(st, "counter")
            st.counters = _counter_dense_step(
                st.counters, w.counter.astype(np.float32))
        if w.gauge is not None:
            dense, mask = w.gauge
            self._ensure_fresh(st, "gauge")
            st.gauges = _gauge_dense_step(st.gauges, dense,
                                          mask.astype(bool))
        if w.histo is not None:
            batch = w.histo.take()
            if batch is not None:
                if self.tiers is None:
                    self._histo_device_step(st, *batch,
                                            with_stats=True)
                else:
                    self._tiered_histo_step(st, *batch,
                                            with_stats=True)
        if w.digest is not None:
            batch = w.digest.take()
            if batch is not None:
                # imported centroids that came one digest at a time
                # (or under the legacy fused-import mode)
                st.import_counts["steps_flat"] += 1
                st.import_counts["centroids"] += len(batch[0])
                if self.tiers is None:
                    self._histo_device_step(st, *batch,
                                            with_stats=False)
                else:
                    self._tiered_histo_step(st, *batch,
                                            with_stats=False)
        if w.wire_parts:
            if self.tiers is None:
                self._wire_digest_step(st, w.wire_parts)
            else:
                self._tiered_wire_digest_step(st, w.wire_parts)
        if w.set_parts is not None:
            set_rows, set_members, pos_rows, pos = w.set_parts
            parts_rows, parts_pos = [], []
            if set_rows:
                idx, rank = hashing.hash_members(set_members)
                parts_rows.append(np.asarray(set_rows, np.int32))
                parts_pos.append(hll.pack_positions(idx, rank))
            parts_rows.extend(pos_rows)
            parts_pos.extend(pos)
            srows = np.concatenate(parts_rows)
            spos = np.concatenate(parts_pos)
            if self.tiers is not None:
                self._tiered_set_step(st, srows, spos)
            elif c.set_rows * hll.M <= c.host_set_plane_max_bytes:
                # device-free path: fold into the host plane; the
                # flusher estimates/forwards from it directly
                self._hll_host_fold(st, srows, spos)
            elif not self._hll_plane_step(st, srows, spos):
                self._ensure_fresh(st, "hll")
                st.hll_device_touched = True
                b = _bucket_len(len(srows))
                st.hll_regs = _hll_step_packed(
                    st.hll_regs,
                    _pad_np(srows, b, self._set_pool_rows),
                    _pad_np(spos, b, 0))
        if w.stats_parts is not None:
            rows = np.concatenate([p[0] for p in w.stats_parts])
            vals = np.concatenate([p[1] for p in w.stats_parts])
            # padding row ids are out of bounds -> dropped by the
            # scatter, so padding row contents never participate
            b = _bucket_len(len(rows), wide=True)
            padded = np.zeros((b, vals.shape[1]), np.float32)
            padded[:len(vals)] = vals
            self._ensure_fresh(st, "histo")
            st.histo_import_stats = _histo_stats_merge(
                st.histo_import_stats,
                _pad_np(rows, b, c.histo_rows), padded)
        if w.set_import is not None:
            plane, touched = w.set_import
            # imports fold into the host plane at receive time, so
            # the swap ships just the touched rows, pre-deduped (a
            # fleet of locals forwards the SAME series: K received
            # planes for U series ship as U rows, not K)
            rows = np.nonzero(touched)[0].astype(np.int32)
            regs = plane[rows]
            if self.tiers is not None:
                self._tiered_set_import(st, rows, regs)
                return
            st.hll_device_touched = True
            # wide rows (16 KiB each): small bucket floor, padding a
            # 256-row plane for one import would cost 4 MiB of
            # host->device bandwidth per flush
            b = _bucket_len(len(rows), wide=True)
            padded = np.zeros((b, regs.shape[1]), np.uint8)
            padded[:len(regs)] = regs
            self._ensure_fresh(st, "hll")
            st.hll_regs = _hll_merge_rows(
                st.hll_regs,
                _pad_np(rows, b, c.set_rows), padded)

    # ------------------------------------------------------------------
    # superbatch apply (ops/superbatch): one packed host buffer, one
    # fused dispatch per apply cycle

    def _superbatch_apply(self, w: _StagedWork) -> None:
        """Consume every staged family the fused one-buffer schema
        can carry this cycle, pack them into one int32 host buffer
        (per-class segments at static offsets, per-class pad
        sentinels identical to the per-class path's) and apply them
        with ONE fused jitted dispatch.  Consumed families are
        nulled on ``w``; everything else stays for the per-class
        oracle.  Caller holds _device_lock."""
        st = w.state
        c = self.config
        counter = None
        if w.counter is not None:
            counter = np.ascontiguousarray(w.counter, np.float32)
            w.counter = None
        gauge = None
        if w.gauge is not None:
            dense, mask = w.gauge
            gauge = (np.ascontiguousarray(dense, np.float32),
                     np.ascontiguousarray(mask, np.int32))
            w.gauge = None
        histo = None
        if w.histo is not None:
            batch = w.histo.take()
            w.histo = None
            if batch is not None:
                histo = self._sb_histo_pack(st, *batch)
        sets = None
        if (w.set_parts is not None and
                c.set_rows * hll.M > c.host_set_plane_max_bytes):
            # the host-fold route (small pools) is device-FREE —
            # nothing the fused dispatch does beats zero dispatches,
            # so it keeps w.set_parts and the per-class path
            sets = self._sb_set_pack(w.set_parts)
            w.set_parts = None
        if (counter is None and gauge is None and histo is None
                and sets is None):
            return
        kw: dict = {}
        if counter is not None:
            kw["counter_rows"] = c.counter_rows
        if gauge is not None:
            kw["gauge_rows"] = c.gauge_rows
        if histo is not None:
            kw.update(histo[0])
        if sets is not None:
            kw.update(sets[1])
        spec = superbatch.SBSpec(**kw)
        off = superbatch.layout(spec)
        buf = self._sb_bufs.take(off["total"])
        superbatch.fill_header(buf, spec, off)
        if counter is not None:
            o = off["counter"]
            buf[o:o + c.counter_rows].view(np.float32)[:] = counter
        if gauge is not None:
            o = off["gauge_dense"]
            buf[o:o + c.gauge_rows].view(np.float32)[:] = gauge[0]
            o = off["gauge_mask"]
            buf[o:o + c.gauge_rows] = gauge[1]
        if histo is not None:
            self._sb_fill_histo(buf, off, spec, histo)
        if sets is not None:
            self._sb_fill_set(buf, off, spec, sets)
        args = []
        if spec.counter_rows:
            self._ensure_fresh(st, "counter")
            args.append(st.counters)
        else:
            args.append(jnp.zeros(0, jnp.float32))
        if spec.gauge_rows:
            self._ensure_fresh(st, "gauge")
            args.append(st.gauges)
        else:
            args.append(jnp.zeros(0, jnp.float32))
        if spec.histo_n:
            self._ensure_fresh(st, "histo")
            args += [st.histo_means, st.histo_weights,
                     st.histo_stats]
        else:
            args += [jnp.zeros(0, jnp.float32) for _ in range(3)]
        if spec.pos_n or spec.plane_rows:
            self._ensure_fresh(st, "hll")
            st.hll_device_touched = True
            args.append(st.hll_regs)
        else:
            args.append(jnp.zeros(0, jnp.uint8))
        out = superbatch.step(spec, *args, buf)
        if spec.counter_rows:
            st.counters = out[0]
        if spec.gauge_rows:
            st.gauges = out[1]
        if spec.histo_n:
            (st.histo_means, st.histo_weights,
             st.histo_stats) = out[2:5]
        if spec.pos_n or spec.plane_rows:
            st.hll_regs = out[5]

    def _sb_histo_pack(self, st, rows, vals, wts):
        """Route one histo batch: ride the superbatch when the
        shallow ranked merge is its transfer shape, else fall to the
        per-class step (host-densified plane and deep-scan batches
        ship fewer bytes through their own shapes).  Thresholds are
        shared with _histo_device_step so the two routers can never
        disagree.  Returns the packed operands, or None when the
        batch was handled per-class."""
        c = self.config
        n = len(rows)
        if not n:
            return None
        unit = bool(np.all(wts == 1.0))
        rows = np.ascontiguousarray(rows, np.int32)
        vals = np.ascontiguousarray(vals, np.float32)
        if (self._lib is not None and
                self._plane_choice(rows, vals, unit, n)[2]):
            self._histo_device_step(st, rows, vals, wts,
                                    with_stats=True)
            return None
        rank, max_count = self._rank(rows)
        if max_count > self._eff_histo_slots:
            self._histo_device_step(st, rows, vals, wts,
                                    with_stats=True)
            return None
        b = _bucket_len(n)
        slots = min(self._eff_histo_slots, _bucket_len(max_count))
        uniq = np.unique(rows)
        mb = _bucket_len(len(uniq))
        sub = mb * 2 <= c.histo_rows
        if sub:
            local = np.searchsorted(uniq, rows).astype(np.int32)
            rows_seg = _pad_np(local, b, mb)
            idx_seg = _pad_np(uniq.astype(np.int32), mb,
                              c.histo_rows)
        else:
            rows_seg = _pad_np(rows, b, c.histo_rows)
            idx_seg = None
        wts_seg = (None if unit else
                   _pad_np(np.ascontiguousarray(wts, np.float32),
                           b, 0.0))
        spec_kw = dict(histo_n=b, histo_slots=slots,
                       histo_sub=mb if sub else 0, histo_unit=unit,
                       histo_stats=True, compression=c.compression)
        return (spec_kw, rows_seg, _pad_np(rank, b, 0),
                _pad_np(vals, b, 0.0), wts_seg, idx_seg)

    def _sb_fill_histo(self, buf, off, spec, histo) -> None:
        _kw, rows_seg, rank_seg, vals_seg, wts_seg, idx_seg = histo
        b = spec.histo_n
        buf[off["histo_rows"]:off["histo_rows"] + b] = rows_seg
        buf[off["histo_rank"]:off["histo_rank"] + b] = rank_seg
        o = off["histo_vals"]
        buf[o:o + b].view(np.float32)[:] = vals_seg
        if wts_seg is not None:
            o = off["histo_wts"]
            buf[o:o + b].view(np.float32)[:] = wts_seg
        if idx_seg is not None:
            o = off["histo_idx"]
            buf[o:o + spec.histo_sub] = idx_seg

    def _sb_set_pack(self, set_parts):
        """Choose the fused set arm for the cycle's staged members.
        Three arms, cheapest viable device op first:

        - compact PLANE (touched rows folded natively into a
          T-row register plane; device does a row-granular max)
          when the compact plane is the smaller transfer;
        - full-plane PLANE (pool-shaped plane; device does one
          elementwise max) on backends where the packed scatter is
          the pathological op (XLA CPU: ~200ns per scattered
          member) and the plane fits the scatter-cost budget;
        - packed POS scatter otherwise — the per-class oracle's
          exact operands inside the fused step.

        All arms are register-bit-identical (byte max is
        order-free).  Returns (arm, spec_kw, parts_rows, parts_pos,
        touched)."""
        c = self.config
        set_rows_l, set_members, pos_rows, pos = set_parts
        parts_rows: list[np.ndarray] = []
        parts_pos: list[np.ndarray] = []
        if set_rows_l:
            idx, rank = hashing.hash_members(set_members)
            parts_rows.append(np.asarray(set_rows_l, np.int32))
            parts_pos.append(hll.pack_positions(idx, rank))
        parts_rows.extend(np.ascontiguousarray(p, np.int32)
                          for p in pos_rows)
        parts_pos.extend(np.ascontiguousarray(p, np.int32)
                         for p in pos)
        n = sum(len(p) for p in parts_rows)
        if not n:
            return None
        pool = self._set_pool_rows
        nb = _bucket_len(n)
        if self._lib is not None:
            counts = np.zeros(pool, np.int64)
            for pr in parts_rows:
                counts += np.bincount(pr, minlength=pool)[:pool]
            touched = np.nonzero(counts)[0].astype(np.int32)
            tb = _bucket_len(len(touched), wide=True)
            if tb * hll.M <= 8 * nb:
                return ("plane", dict(plane_rows=tb), parts_rows,
                        parts_pos, touched)
            if (self._sb_plane_factor > 1 and
                    pool * hll.M <= self._sb_plane_factor * 8 * nb):
                return ("plane_full",
                        dict(plane_rows=pool, plane_full=True),
                        parts_rows, parts_pos, None)
        return ("pos", dict(pos_n=nb), parts_rows, parts_pos, None)

    def _sb_fill_set(self, buf, off, spec, sets) -> None:
        import ctypes as ct
        _arm, _kw, parts_rows, parts_pos, touched = sets
        if spec.pos_n:
            self._sb_gather(parts_rows, buf, off["pos_rows"],
                            spec.pos_n, self._set_pool_rows)
            self._sb_gather(parts_pos, buf, off["pos_pk"],
                            spec.pos_n, 0)
            return
        # plane arms (native lib guaranteed by _sb_set_pack): zero
        # the register segment, then fold every staged part straight
        # into it — no intermediate concatenate
        words = spec.plane_rows * (hll.M // 4)
        seg = buf[off["plane_regs"]:off["plane_regs"] + words]
        seg[:] = 0
        plane_u8 = seg.view(np.uint8)
        i32p = ct.POINTER(ct.c_int32)
        u8p = plane_u8.ctypes.data_as(ct.POINTER(ct.c_uint8))
        remap = None
        if not spec.plane_full:
            t = len(touched)
            remap = np.full(self._set_pool_rows, -1, np.int32)
            remap[touched] = np.arange(t, dtype=np.int32)
            o = off["plane_idx"]
            buf[o:o + t] = touched
            # pad sentinel = pool rows: dropped by merge_rows'
            # out-of-bounds scatter mode, same as the per-class path
            buf[o + t:o + spec.plane_rows] = self._set_pool_rows
        for pr, pp in zip(parts_rows, parts_pos):
            if remap is not None:
                pr = np.ascontiguousarray(remap[pr], np.int32)
            self._lib.vtpu_hll_plane(
                pr.ctypes.data_as(i32p), pp.ctypes.data_as(i32p),
                len(pp), spec.plane_rows, hll.M, u8p)

    def _sb_gather(self, parts, buf, o: int, cap: int,
                   fill: int) -> None:
        """Emit staged part arrays directly into one superbatch
        segment (native vtpu_sb_gather_i32 when available): the
        concat + pad copy pair collapses into a single pass."""
        dst = buf[o:o + cap]
        if self._lib is not None and parts:
            import ctypes as ct
            i32p = ct.POINTER(ct.c_int32)
            k = len(parts)
            ptrs = (i32p * k)(*(p.ctypes.data_as(i32p)
                                for p in parts))
            lens = (ct.c_int64 * k)(*(len(p) for p in parts))
            self._lib.vtpu_sb_gather_i32(
                ptrs, lens, k, dst.ctypes.data_as(i32p), cap, fill)
            return
        pos = 0
        for p in parts:
            dst[pos:pos + len(p)] = p
            pos += len(p)
        dst[pos:] = fill

    # ------------------------------------------------------------------
    # tiered apply routing (self.tiers is not None; every entry point
    # here is reached only in tiered mode, so single-tier behavior is
    # bit-identical to the untiered table)

    def _tiered_histo_step(self, st: _IntervalState, rows, vals, wts,
                           with_stats: bool) -> None:
        """Tiered histogram apply: exact row-space aggregate fold
        first (stats planes are row-indexed in both modes), then
        partition the batch by tier bit — wide rows translate to pool
        slots and take the normal ranked device merge; compact rows
        retain their raw weighted samples host-side (below the
        promote threshold that sample list IS the digest: singleton
        regime of arxiv 1903.09921).  Rows crossing the threshold
        escalate mid-interval: slot alloc + drain of the retained
        samples through the same merge kernels — the exact lossless
        upgrade.  Escalation is skipped for frozen (post-begin_swap)
        states: the data stays in the exact compact store instead,
        and the boundary promotes the row for the next interval."""
        dirs = self.tiers
        th = dirs.thresholds
        rows = np.ascontiguousarray(rows, np.int32)
        vals = np.ascontiguousarray(vals, np.float32)
        wts = np.ascontiguousarray(wts, np.float32)
        if with_stats:
            self._host_stats_fold(st, rows, vals, wts)
        dev_parts = []
        with dirs.lock:
            frozen = st.tier_frozen
            if frozen is not None:
                ftier, fslot = frozen["histo"]
                mask = ftier[rows] != 0
                wpos = np.nonzero(mask)[0]
                wslots = fslot[rows[wpos]]
                cpos = np.nonzero(~mask)[0]
            else:
                wpos, wslots, cpos = tiersmod.split_by_tier(
                    rows, dirs.histo, self._lib)
            if len(wpos):
                dev_parts.append((np.asarray(wslots, np.int32),
                                  vals[wpos], wts[wpos]))
            if len(cpos):
                store = st.histo_compact
                if store is None:
                    store = st.histo_compact = \
                        tiersmod.CompactHistoStore(
                            self.config.histo_rows)
                crows = rows[cpos]
                store.append(crows, vals[cpos], wts[cpos])
                if frozen is None:
                    cand = np.unique(crows)
                    cand = cand[store.counts[cand] >=
                                th.histo_samples]
                    for r in cand:
                        s = dirs.histo.ensure_wide(int(r),
                                                   escalation=True)
                        if s is None:
                            # pool exhausted: the row stays compact —
                            # exact, just host-resident; counted as a
                            # refused promotion, never a loss
                            continue
                        dv, dw = store.drain_row(int(r))
                        dev_parts.append(
                            (np.full(len(dv), s, np.int32), dv, dw))
        if dev_parts:
            self._histo_device_step(
                st, np.concatenate([p[0] for p in dev_parts]),
                np.concatenate([p[1] for p in dev_parts]),
                np.concatenate([p[2] for p in dev_parts]),
                with_stats=False)

    def _tiered_set_step(self, st: _IntervalState, srows,
                         spos) -> None:
        """Tiered set apply: wide rows fold into the slot-indexed
        host register plane; compact rows append to the sparse
        (index,value) register list — exact, since the dense row is a
        pure function of the deduped list.  Register occupancy
        crossing the promote threshold escalates: the sparse list
        scatters into a freshly allocated pool slot (lossless by
        construction)."""
        dirs = self.tiers
        th = dirs.thresholds
        srows = np.ascontiguousarray(srows, np.int32)
        spos = np.ascontiguousarray(spos, np.int32)
        fold_rows, fold_pos = [], []
        with dirs.lock:
            frozen = st.tier_frozen
            if frozen is not None:
                ftier, fslot = frozen["set"]
                mask = ftier[srows] != 0
                wpos = np.nonzero(mask)[0]
                wslots = fslot[srows[wpos]]
                cpos = np.nonzero(~mask)[0]
            else:
                wpos, wslots, cpos = tiersmod.split_by_tier(
                    srows, dirs.set, self._lib)
            if len(wpos):
                fold_rows.append(np.asarray(wslots, np.int32))
                fold_pos.append(spos[wpos])
            if len(cpos):
                store = st.set_sparse
                if store is None:
                    store = st.set_sparse = tiersmod.SparseSetStore(
                        self.config.set_rows)
                crows = srows[cpos]
                store.append(crows, spos[cpos])
                if frozen is None:
                    cand = np.unique(crows)
                    cand = cand[store.counts[cand] >= th.set_entries]
                    if len(cand):
                        # raw append counts over-estimate occupancy;
                        # consolidate (dedup) before deciding
                        store.consolidate()
                        for r in cand:
                            if store.counts[r] < th.set_entries:
                                continue
                            s = dirs.set.ensure_wide(int(r),
                                                     escalation=True)
                            if s is None:
                                continue
                            p = store.drain_row(int(r))
                            fold_rows.append(
                                np.full(len(p), s, np.int32))
                            fold_pos.append(p)
        if fold_rows:
            self._hll_host_fold(st, np.concatenate(fold_rows),
                                np.concatenate(fold_pos))

    def _tiered_set_import(self, st: _IntervalState, rows,
                           regs) -> None:
        """Forwarded dense register planes in tiered mode: the target
        row force-promotes (a peer already holds dense state — the
        series is wide by definition) and the plane unions host-side
        into its slot, with the fold statistics recomputed exactly.
        Pool-refused rows keep their dense regs in a per-interval
        overflow sidecar: exact, never lost, just unpromoted."""
        self._ensure_host_plane(st)
        plane = st.hll_host_plane
        dirs = self.tiers
        for i, r in enumerate(np.asarray(rows, np.int64)):
            r = int(r)
            with dirs.lock:
                frozen = st.tier_frozen
                if frozen is not None:
                    ftier, fslot = frozen["set"]
                    s = int(fslot[r]) if ftier[r] else -1
                else:
                    s0 = dirs.set.ensure_wide(r, escalation=True)
                    s = -1 if s0 is None else int(s0)
                    if s >= 0 and st.set_sparse is not None and \
                            st.set_sparse.counts[r] > 0:
                        p = st.set_sparse.drain_row(r)
                        if len(p):
                            plane[s, p >> 6] = np.maximum(
                                plane[s, p >> 6],
                                (p & 0x3F).astype(np.uint8))
            if s < 0:
                ov = st.set_dense_overflow
                if ov is None:
                    ov = st.set_dense_overflow = {}
                prev = ov.get(r)
                ov[r] = (regs[i].copy() if prev is None
                         else np.maximum(prev, regs[i]))
                continue
            prow = plane[s]
            np.maximum(prow, regs[i], out=prow)
            if st.hll_host_ez is not None:
                ez = int((prow == 0).sum())
                st.hll_host_ez[s] = ez
                nz = prow[prow != 0].astype(np.int64)
                st.hll_host_inv[s] = float(ez) + float(
                    np.ldexp(1.0, -nz).sum())

    def _tiered_wire_digest_step(self, st: _IntervalState,
                                 parts: list[tuple]) -> None:
        """Forwarded centroid parts translate row -> slot before the
        fused wire merge: forwarded digests are wide-tier traffic by
        definition, so their rows force-promote (draining any compact
        samples through the merge on the way).  Pool-refused rows'
        centroids retain as weighted samples in the compact store —
        a centroid IS a weighted sample, so the mass is conserved."""
        dirs = self.tiers
        out_parts = []
        extra = []
        with dirs.lock:
            frozen = st.tier_frozen
            store = st.histo_compact
            smap = np.full(self.config.histo_rows, -1, np.int32)
            smapped = np.zeros(self.config.histo_rows, bool)
            for rows, means, wts in parts:
                if not len(rows):
                    continue
                rows = np.ascontiguousarray(rows, np.int32)
                for r in np.unique(rows):
                    r = int(r)
                    if smapped[r]:
                        continue
                    smapped[r] = True
                    if frozen is not None:
                        ftier, fslot = frozen["histo"]
                        smap[r] = fslot[r] if ftier[r] else -1
                        continue
                    s = dirs.histo.ensure_wide(r, escalation=True)
                    if s is None:
                        continue
                    smap[r] = s
                    if store is not None and store.counts[r] > 0:
                        dv, dw = store.drain_row(r)
                        if len(dv):
                            extra.append(
                                (np.full(len(dv), s, np.int32),
                                 dv, dw))
                slots = smap[rows]
                ok = slots >= 0
                if not ok.all():
                    if store is None:
                        store = st.histo_compact = \
                            tiersmod.CompactHistoStore(
                                self.config.histo_rows)
                    bad = ~ok
                    store.append(rows[bad],
                                 np.asarray(means, np.float32)[bad],
                                 np.asarray(wts, np.float32)[bad])
                if ok.any():
                    out_parts.append((slots[ok],
                                      np.asarray(means)[ok],
                                      np.asarray(wts)[ok]))
        if out_parts:
            self._wire_digest_step(st, out_parts)
        for erows, ev, ew in extra:
            self._histo_device_step(st, erows, ev, ew,
                                    with_stats=False)

    def _histo_device_step(self, st: _IntervalState, rows: np.ndarray,
                           vals: np.ndarray, wts: np.ndarray,
                           with_stats: bool = True) -> None:
        """Histo ingest: ONE fused device pass per batch — ranked
        scatter into dense planes, local aggregates folded as plane
        reductions, k-scale cluster into the digests
        (tdigest.ingest_ranked).  The within-row rank comes from a host
        O(n) counter pass (native vtpu_rank), so the device never
        argsorts the sample batch.  Rows exceeding ``histo_slots``
        samples split across calls by rank.  ``with_stats=False`` for
        imported centroids, whose stats arrive via the stat-row path."""
        c = self.config
        # unit-weight batches (no client sample-rate — the common case)
        # skip shipping the weights column entirely
        unit = bool(np.all(wts == 1.0))
        if with_stats and self._lib is not None and len(rows):
            handled, spill = self._histo_plane_step(st, rows, vals,
                                                    wts, unit)
            if handled:
                if spill is None:
                    return
                # hot rows past the plane width fall through to the
                # ranked path, which chunks ITERATIVELY (a recursive
                # plane retry would strip only `width` samples of the
                # hot row per level — quadratic work and a stack bomb).
                # The plane step's host stats pass already counted the
                # spilled samples, so they re-enter digest-only.
                rows, vals, wts = spill
                with_stats = False
        rank, max_count = self._rank(rows)
        eff = self._eff_histo_slots
        if max_count <= eff:
            self._digest_merge(st, rows, vals, wts, rank, unit,
                               with_stats)
            return
        # Deep batch (a row carries more samples than one merge
        # width): fold the local aggregates on host once (exact), then
        # merge digest-only through the single-dispatch device scan —
        # a 1.6M-centroid global-tier import interval previously paid
        # ~0.7s of single-core k-scale precluster (or, before that,
        # one dispatch per chunk).
        # The host precluster survives only as the ultra-deep escape
        # (> 64 chunk widths in one row), where bounding the scan's
        # compile variants and h2d bytes is worth its lossier
        # collapse-then-merge accuracy.
        if with_stats:
            self._host_stats_fold(st, rows, vals, wts)
            with_stats = False
        n_chunks = -(-max_count // eff)
        if n_chunks > 64:
            rows, vals, wts = self._host_precluster(rows, vals, wts)
            rank, max_count = self._rank(rows)
            if max_count <= eff:
                self._digest_merge(st, rows, vals, wts, rank, False,
                                   False)
                return
            n_chunks = -(-max_count // eff)
        self._digest_merge_scan(st, rows, vals, wts, rank, n_chunks)

    def _host_stats_fold(self, st, rows, vals, wts) -> None:
        """Fold a batch's per-row local aggregates into the device
        stats plane from HOST-computed exact values (numpy bincount
        reductions) — used when the batch bypasses the plane step but
        is about to be pre-clustered, which would corrupt min/max."""
        c = self.config
        rows = np.ascontiguousarray(rows, np.int64)
        batch = np.zeros((c.histo_rows, segment.HISTO_STAT_COLS),
                         np.float32)
        batch[:, segment.STAT_MIN] = segment.STAT_MIN_EMPTY
        batch[:, segment.STAT_MAX] = segment.STAT_MAX_EMPTY
        R = c.histo_rows
        batch[:, segment.STAT_WEIGHT] = np.bincount(
            rows, weights=wts, minlength=R)[:R]
        batch[:, segment.STAT_SUM] = np.bincount(
            rows, weights=vals * wts, minlength=R)[:R]
        nz = vals != 0
        batch[:, segment.STAT_RSUM] = np.bincount(
            rows[nz], weights=wts[nz] / vals[nz], minlength=R)[:R]
        np.minimum.at(batch[:, segment.STAT_MIN], rows, vals)
        np.maximum.at(batch[:, segment.STAT_MAX], rows, vals)
        self._ensure_fresh(st, "histo")
        st.histo_stats = _histo_stats_fold(st.histo_stats, batch)

    def _host_precluster(self, rows, vals, wts
                         ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Collapse a sample batch into <= capacity weighted centroids
        per row using the SAME k-scale clustering as the device merge
        (ops/tdigest._merge_impl): sort by (row, value), exact q from
        the within-row cumulative weight, cluster = floor(k(q)-k(0)),
        weighted mean per cluster.  The device then merges centroids —
        a centroid IS a weighted sample — so accuracy matches feeding
        the raw batch through the same scale."""
        c = self.config
        cap = self.capacity
        rows = np.ascontiguousarray(rows, np.int64)
        order = np.lexsort((vals, rows))
        r = rows[order]
        v = np.ascontiguousarray(vals, np.float64)[order]
        w = np.ascontiguousarray(wts, np.float64)[order]
        cw = np.cumsum(w)
        first = np.ones(len(r), bool)
        first[1:] = r[1:] != r[:-1]
        base = np.maximum.accumulate(np.where(first, cw - w, 0.0))
        totals = np.bincount(r, weights=w)[r]
        q_left = (cw - w - base) / np.maximum(totals, 1e-30)
        k = (tdigest.k_scale_np(q_left, c.compression) -
             tdigest.k_scale_np(0.0, c.compression))
        cl = np.clip(np.floor(k).astype(np.int64), 0, cap - 1)
        key = r * cap + cl
        uniq, inv = np.unique(key, return_inverse=True)
        cw_sum = np.bincount(inv, weights=w)
        cwv = np.bincount(inv, weights=w * v)
        return ((uniq // cap).astype(np.int32),
                (cwv / np.maximum(cw_sum, 1e-30)).astype(np.float32),
                cw_sum.astype(np.float32))

    def _plane_choice(self, rows, vals, unit, n):
        """Width / f16 / engagement decision for the host-densified
        plane ingest — shared by _histo_plane_step and the
        superbatch router so the two can never disagree about which
        transfer shape a batch takes.  Returns (width, f16, engage);
        width == 0 means the batch touched no rows."""
        c = self.config
        counts_full = np.bincount(rows, minlength=c.histo_rows)
        occupied = counts_full[counts_full > 0]
        if not len(occupied):
            return 0, False, True
        w_hi = int(occupied.max())
        w_p99 = int(np.percentile(occupied, 99.5))
        # width at 128-lane granularity around the p99.5 row count
        # (compile-cache variants bounded by histo_slots/128); the
        # coarse 1.5-step ladder only caps via the max row
        width = min(max(128, -(-w_p99 // 128) * 128),
                    _bucket_len(w_hi, wide=True),
                    self._eff_histo_slots)
        # f16 plane only for unit-weight batches whose nonzero values
        # all sit in f16's NORMAL range: rel. quantization there is
        # 2^-11 (~0.05%), while subnormals (<6.1e-5) would quantize at
        # percent-level and weights (1/rate, up to 1e5+) could
        # overflow to inf.  Stats stay exact either way.  The range
        # scan is skipped for weighted batches (always f32 there).
        f16 = False
        if unit and _F16_PLANE:
            av = np.abs(vals)
            vmax = float(av.max(initial=0.0))
            nz = av[av > 0]
            vmin_nz = float(nz.min()) if len(nz) else 1.0
            f16 = vmax < 6.0e4 and vmin_nz >= 6.2e-5
        vbytes = 2 if f16 else 4
        planes = 1 if unit else 2
        engage = c.histo_rows * width * vbytes * planes <= 12 * n
        return width, f16, engage

    def _histo_plane_step(self, st, rows, vals, wts, unit):
        """Host-densified plane ingest (native vtpu_dense_plane +
        tdigest.ingest_plane_pre*): ships a dense value plane instead
        of 12 bytes/sample.  Three transfer reductions compose here:

        - width targets the 99.5th-percentile row count (ladder-
          rounded), not the max — the few hotter rows spill to the
          ranked path instead of padding every row to the hot one;
        - per-row local aggregates are accumulated on host in exact
          f32 over ALL samples (including spills) by the same native
          pass, so
        - the value plane can ship as float16 when the batch's range
          fits: digest means absorb the ~0.05% quantization, while
          min/max/sum stay exact.

        Returns (handled, spill): handled=False when the batch is too
        sparse for the plane to be the smaller transfer (the ranked
        path takes over); spill holds samples of rows past the plane
        width — the CALLER routes them digest-only (stats already
        counted)."""
        import ctypes as ct
        c = self.config
        n = len(rows)
        rows = np.ascontiguousarray(rows, np.int32)
        vals = np.ascontiguousarray(vals, np.float32)
        width, f16, engage = self._plane_choice(rows, vals, unit, n)
        if width == 0:
            return True, None
        if not engage:
            return False, None
        f32p = ct.POINTER(ct.c_float)
        i32p = ct.POINTER(ct.c_int32)
        plane_v = np.zeros((c.histo_rows, width), np.float32)
        plane_w = (None if unit else
                   np.zeros((c.histo_rows, width), np.float32))
        counts = np.zeros(c.histo_rows, np.int32)
        # f64 batch-stat accumulators (see vtpu_dense_plane); rounded
        # to f32 once, after accumulation
        batch_stats = np.zeros((c.histo_rows, segment.HISTO_STAT_COLS),
                               np.float64)
        batch_stats[:, segment.STAT_MIN] = segment.STAT_MIN_EMPTY
        batch_stats[:, segment.STAT_MAX] = segment.STAT_MAX_EMPTY
        ov_rows = np.empty(n, np.int32)
        ov_vals = np.empty(n, np.float32)
        if unit:
            wts_p = ov_wts_p = None
            ov_wts = None
        else:
            wts = np.ascontiguousarray(wts, np.float32)
            wts_p = wts.ctypes.data_as(f32p)
            ov_wts = np.empty(n, np.float32)
            ov_wts_p = ov_wts.ctypes.data_as(f32p)
        spill = self._lib.vtpu_dense_plane(
            rows.ctypes.data_as(i32p),
            vals.ctypes.data_as(f32p), wts_p, n,
            c.histo_rows, width,
            plane_v.ctypes.data_as(f32p),
            plane_w.ctypes.data_as(f32p) if plane_w is not None
            else None,
            counts.ctypes.data_as(i32p),
            ov_rows.ctypes.data_as(i32p),
            ov_vals.ctypes.data_as(f32p), ov_wts_p,
            batch_stats.ctypes.data_as(ct.POINTER(ct.c_double)))
        batch_stats = batch_stats.astype(np.float32)
        if f16:
            plane_v = plane_v.astype(np.float16)
        self._ensure_fresh(st, "histo")
        if unit:
            (st.histo_means, st.histo_weights,
             st.histo_stats) = _td_step["ingest_plane_pre_unit"](
                st.histo_means, st.histo_weights,
                st.histo_stats, batch_stats, counts, plane_v,
                compression=c.compression)
        else:
            (st.histo_means, st.histo_weights,
             st.histo_stats) = _td_step["ingest_plane_pre"](
                st.histo_means, st.histo_weights,
                st.histo_stats, batch_stats, plane_v, plane_w,
                compression=c.compression)
        if spill:
            return True, (
                ov_rows[:spill].copy(), ov_vals[:spill].copy(),
                np.ones(spill, np.float32) if unit
                else ov_wts[:spill].copy())
        return True, None

    def _ensure_host_plane(self, st: _IntervalState) -> None:
        """Lazy host register plane + fold statistics for the
        interval.  POOL-sized: in single-tier mode the pool is the
        whole row table; under tiering it is the wide-slot pool and
        rows are slot ids."""
        if st.hll_host_plane is not None:
            return
        pool = self._set_pool_rows
        if self._plane_pool:
            st.hll_host_plane = self._plane_pool.pop()
        else:
            st.hll_host_plane = np.zeros((pool, hll.M), np.uint8)
        if self._lib is not None:
            # all-zero row: every register counts in ez and
            # contributes 2^0 to the inverse-power sum
            st.hll_host_ez = np.full(pool, hll.M, np.int32)
            st.hll_host_inv = np.full(pool, float(hll.M),
                                      np.float64)

    def _hll_host_fold(self, st: _IntervalState, rows: np.ndarray,
                       pos: np.ndarray) -> None:
        """Fold packed member positions into the persistent host
        register plane for this interval — no device dispatch at all
        (see TableConfig.host_set_plane_max_bytes).  ``rows`` are
        pool-space ids (row == slot in single-tier mode)."""
        self._ensure_host_plane(st)
        pool = self._set_pool_rows
        rows = np.ascontiguousarray(rows, np.int32)
        pos = np.ascontiguousarray(pos, np.int32)
        if self._lib is not None:
            import ctypes as ct
            i32p = ct.POINTER(ct.c_int32)
            self._lib.vtpu_hll_plane_stats(
                rows.ctypes.data_as(i32p), pos.ctypes.data_as(i32p),
                len(rows), pool, hll.M,
                st.hll_host_plane.ctypes.data_as(
                    ct.POINTER(ct.c_uint8)),
                st.hll_host_inv.ctypes.data_as(
                    ct.POINTER(ct.c_double)),
                st.hll_host_ez.ctypes.data_as(i32p))
            return
        idx = pos >> 6
        rank = (pos & 0x3F).astype(np.uint8)
        live = (rows >= 0) & (rows < pool)
        np.maximum.at(st.hll_host_plane,
                      (rows[live], idx[live]), rank[live])

    def _recycle_plane(self, plane: np.ndarray) -> None:
        """Accept a consumed snapshot's plane back into the pool,
        cleared.  Runs on the releasing (flusher) thread, keeping the
        memset off the ingest path.  Bounded: FLUSH_LAG snapshots can
        be in flight, more than that is a leak, not a pool."""
        if (len(self._plane_pool) < 4 and
                plane.shape == (self._set_pool_rows, hll.M)):
            plane.fill(0)
            self._plane_pool.append(plane)

    def _hll_plane_step(self, st: _IntervalState, rows: np.ndarray,
                        pos: np.ndarray) -> bool:
        """Fold the interval's packed member positions into a host
        register plane (native vtpu_hll_plane) and union it on device
        with one elementwise max — ships R*16384 plane bytes instead
        of 8 bytes/member.  Returns False when the batch is small
        enough that the packed scatter is the smaller transfer."""
        import ctypes as ct
        c = self.config
        n = len(rows)
        if (self._lib is None or
                c.set_rows * hll.M > 8 * n):
            return False
        rows = np.ascontiguousarray(rows, np.int32)
        pos = np.ascontiguousarray(pos, np.int32)
        plane = np.zeros((c.set_rows, hll.M), np.uint8)
        i32p = ct.POINTER(ct.c_int32)
        self._lib.vtpu_hll_plane(
            rows.ctypes.data_as(i32p), pos.ctypes.data_as(i32p), n,
            c.set_rows, hll.M,
            plane.ctypes.data_as(ct.POINTER(ct.c_uint8)))
        self._ensure_fresh(st, "hll")
        st.hll_device_touched = True
        st.hll_regs = _hll_union_plane(st.hll_regs, plane)
        return True

    def _rank(self, rows: np.ndarray,
              num_rows: int | None = None) -> tuple[np.ndarray, int]:
        """Within-row occurrence rank + max per-row count.  ``rows``
        may be local (subset) indices when ``num_rows`` bounds them —
        the wire-stack builder ranks within union-row space."""
        n = len(rows)
        if num_rows is None:
            num_rows = self.config.histo_rows
        rows = np.ascontiguousarray(rows, np.int32)
        if self._lib is not None:
            import ctypes as ct
            i32p = ct.POINTER(ct.c_int32)
            counts = np.zeros(num_rows, np.int32)
            rank = np.empty(n, np.int32)
            self._lib.vtpu_rank(
                rows.ctypes.data_as(i32p), n,
                num_rows,
                counts.ctypes.data_as(i32p),
                rank.ctypes.data_as(i32p))
            return rank, int(counts.max(initial=0))
        order = np.argsort(rows, kind="stable")
        sorted_rows = rows[order]
        first = np.ones(n, dtype=bool)
        first[1:] = sorted_rows[1:] != sorted_rows[:-1]
        start = np.maximum.accumulate(
            np.where(first, np.arange(n), 0))
        rank = np.empty(n, np.int32)
        rank[order] = np.arange(n) - start
        return rank, int(rank.max(initial=-1)) + 1

    def _digest_merge(self, st, rows, vals, wts, rank, unit,
                      with_stats) -> None:
        c = self.config
        self._ensure_fresh(st, "histo")
        b = _bucket_len(len(rows))
        vals_dev = _pad_np(vals, b, 0.0)
        rank_dev = _pad_np(rank, b, 0)
        # dense-plane width: what the batch's deepest row needs (the
        # old min(histo_slots, b) keyed on the FLAT batch length, so
        # a shallow-but-wide batch shipped an oversized plane and —
        # on TPU — pushed the merge past the fused kernel's bound)
        slots = min(self._eff_histo_slots,
                    _bucket_len(int(rank.max(initial=-1)) + 1))
        # Touched-row-subset merge: a batch touching m rows of an
        # R-row plane otherwise pays the k-scale sort for every row
        # (seconds per interval on the CPU-fallback backend at the
        # default 16k rows; wasted sort lanes on device).  Gather the
        # touched rows, merge compactly, scatter back — engaged only
        # when the subset bucket is at most half the plane.
        uniq = np.unique(rows)
        mb = _bucket_len(len(uniq))
        sub = mb * 2 <= c.histo_rows
        if sub:
            local = np.searchsorted(uniq, rows).astype(np.int32)
            rows_dev = _pad_np(local, b, mb)
            idx_dev = _pad_np(uniq.astype(np.int32), mb,
                              c.histo_rows)
        else:
            rows_dev = _pad_np(rows, b, c.histo_rows)
        if with_stats:
            if unit:
                fn = _td_step["ingest_ranked_unit_rows" if sub
                              else "ingest_ranked_unit"]
                args = (st.histo_means, st.histo_weights,
                        st.histo_stats)
                args += (idx_dev,) if sub else ()
                (st.histo_means, st.histo_weights,
                 st.histo_stats) = fn(
                    *args, rows_dev, rank_dev, vals_dev,
                    slots=slots, compression=c.compression)
            else:
                fn = _td_step["ingest_ranked_rows" if sub
                              else "ingest_ranked"]
                args = (st.histo_means, st.histo_weights,
                        st.histo_stats)
                args += (idx_dev,) if sub else ()
                (st.histo_means, st.histo_weights,
                 st.histo_stats) = fn(
                    *args, rows_dev, rank_dev, vals_dev,
                    _pad_np(wts, b, 0.0),
                    slots=slots, compression=c.compression)
        elif unit:
            fn = _td_step["add_samples_ranked_unit_rows" if sub
                          else "add_samples_ranked_unit"]
            args = (st.histo_means, st.histo_weights)
            args += (idx_dev,) if sub else ()
            st.histo_means, st.histo_weights = fn(
                *args, rows_dev, rank_dev, vals_dev, slots=slots,
                compression=c.compression)
        else:
            fn = _td_step["add_samples_ranked_rows" if sub
                          else "add_samples_ranked"]
            args = (st.histo_means, st.histo_weights)
            args += (idx_dev,) if sub else ()
            st.histo_means, st.histo_weights = fn(
                *args, rows_dev, rank_dev, vals_dev,
                _pad_np(wts, b, 0.0),
                slots=slots, compression=c.compression)

    def _digest_merge_scan(self, st, rows, vals, wts, rank,
                           n_chunks: int) -> None:
        """Digest-only merge of a deep batch (per-row counts beyond
        one merge width) in ONE device dispatch: lax.scan merges an
        eff-slots-wide chunk per step.  The chunk count is bucketed
        to a power of two so the static scan length doesn't mint a
        compile variant per interval shape; chunks past the real
        depth merge empty plane slices.

        The batch ships HOST-DENSIFIED whenever the touched rows are
        uniform enough that the plane is not much bigger than the
        flat triplets: the scan then never scatters on device (an XLA
        scatter of the full flat batch re-executed per chunk measured
        ~2.5s/interval for the 64-local import config, vs ~ms for
        slice+merge).  Skewed deep batches (plane would blow past 2x
        the flat bytes) keep the flat scatter-scan."""
        c = self.config
        self._ensure_fresh(st, "histo")
        eff = self._eff_histo_slots
        nc = 1 << max(0, (n_chunks - 1).bit_length())
        uniq = np.unique(rows)
        mb = _bucket_len(len(uniq))
        sub = mb * 2 <= c.histo_rows
        n_plane_rows = mb if sub else c.histo_rows
        if sub:
            local = np.searchsorted(uniq, rows).astype(np.int32)
        else:
            local = np.ascontiguousarray(rows, np.int32)
        width = nc * eff
        b = _bucket_len(len(rows))
        if n_plane_rows * width * 8 <= 32 * b:
            plane_v = np.zeros((n_plane_rows, width), np.float32)
            plane_w = np.zeros((n_plane_rows, width), np.float32)
            plane_v[local, rank] = vals
            plane_w[local, rank] = wts
            if sub:
                idx_dev = _pad_np(uniq.astype(np.int32), mb,
                                  c.histo_rows)
                st.histo_means, st.histo_weights = \
                    _td_step["merge_dense_scan_rows"](
                        st.histo_means, st.histo_weights,
                        idx_dev, plane_v, plane_w, slots=eff,
                        n_chunks=nc, compression=c.compression)
            else:
                st.histo_means, st.histo_weights = \
                    _td_step["merge_dense_scan"](
                        st.histo_means, st.histo_weights,
                        plane_v, plane_w, slots=eff, n_chunks=nc,
                        compression=c.compression)
            return
        # padding rank nc*eff is past every chunk's live window, so
        # padded entries drop without needing a row-id sentinel
        vals_dev = _pad_np(vals, b, 0.0)
        rank_dev = _pad_np(rank, b, nc * eff)
        wts_dev = _pad_np(wts, b, 0.0)
        if sub:
            rows_dev = _pad_np(local, b, mb)
            idx_dev = _pad_np(uniq.astype(np.int32), mb,
                              c.histo_rows)
            st.histo_means, st.histo_weights = \
                _td_step["add_samples_ranked_scan_rows"](
                    st.histo_means, st.histo_weights, idx_dev,
                    rows_dev, rank_dev, vals_dev, wts_dev,
                    slots=eff, n_chunks=nc,
                    compression=c.compression)
        else:
            rows_dev = _pad_np(rows, b, c.histo_rows)
            st.histo_means, st.histo_weights = \
                _td_step["add_samples_ranked_scan"](
                    st.histo_means, st.histo_weights, rows_dev,
                    rank_dev, vals_dev, wts_dev,
                    slots=eff, n_chunks=nc,
                    compression=c.compression)

    def _collective_wire_fold(self):
        """Resolve the collective-import gate once and cache the
        result: a parallel.sharded.CollectiveWireFold when the fold
        should run collectively (mode "on", or "auto" with more than
        one visible device), else None — the serial scan path.  The
        import stays self-contained so single-device deployments never
        touch the mesh machinery."""
        if self._collective_fold == "unset":
            fold = None
            mode = self.collective_import_mode
            if mode != "off" and (
                    mode == "on" or len(jax.devices()) > 1):
                from veneur_tpu.parallel import sharded
                fold = sharded.CollectiveWireFold(
                    sharded.make_import_mesh(),
                    compression=self.config.compression)
            self._collective_fold = fold
        return self._collective_fold

    def _wire_digest_step(self, st: _IntervalState,
                          parts: list[tuple]) -> None:
        """Fused global merge: a cycle's decoded wire digests — one
        (rows, means, weights) part per forwarded MetricList — stack
        into (n_wires, union_rows, K) centroid planes and fold with
        ONE jitted call (tdigest.merge_wire_stack_rows: lax.scan over
        the wire axis, Pallas merge body when the gate engages)
        instead of one dispatch per wire.

        Per-row merge ORDER is wire arrival order in both the stacked
        and per-wire modes, and every merge step sees operands of
        identical width, so the two modes are bit-identical
        (tests/test_pipeline.py locks this).  Rows deeper than the
        stack width within one wire spill to the flat ranked path
        (exact, just not fused); a batch whose union-row bucket
        exceeds half the plane falls back entirely.

        The default mode is "auto": the stacked scan pays off exactly
        where the Pallas merge gate engages — each scan step's
        operand width stays inside the kernel's lane bound, while the
        flat merge's combined width (sum of all wires' depths) blows
        past it and drops to the slow chunked fallback.  Where every
        path is scatter anyway (CPU/GPU) the flat merge does strictly
        fewer FLOPs, so auto keeps it there."""
        c = self.config
        parts = [p for p in parts if len(p[0])]
        if not parts:
            return
        mode = self.fused_import_mode
        if mode == "auto":
            mode = ("stack"
                    if tdigest.resolved_merge_mode() == "pallas"
                    else "legacy")

        counts = st.import_counts
        counts["centroids"] += sum(len(p[0]) for p in parts)

        def _flat() -> None:
            counts["steps_flat"] += 1
            self._histo_device_step(
                st, np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]),
                np.concatenate([p[2] for p in parts]),
                with_stats=False)

        if mode == "legacy" or len(parts) == 1:
            _flat()
            return
        uniq = np.unique(np.concatenate([p[0] for p in parts]))
        mb = _bucket_len(len(uniq))
        if mb * 2 > c.histo_rows:
            _flat()
            return
        kmax = self._wire_stack_kmax
        built = []
        spill = _Staging()
        kdeep = 0
        for rows, means, wts in parts:
            rows = np.ascontiguousarray(rows, np.int32)
            local = np.searchsorted(uniq, rows).astype(np.int32)
            rank, maxc = self._rank(local, num_rows=len(uniq))
            if maxc > kmax:
                over = rank >= kmax
                spill.append(rows[over], means[over], wts[over])
                keep = ~over
                local, rank = local[keep], rank[keep]
                means, wts = means[keep], wts[keep]
                maxc = kmax
            built.append((local, rank, means, wts))
            kdeep = max(kdeep, maxc)
        K = _bucket_len(kdeep, wide=True)
        idx_dev = jnp.asarray(_pad_np(
            uniq.astype(np.int32), mb, c.histo_rows))
        self._ensure_fresh(st, "histo")
        counts["steps_stack"] += 1
        counts["spilled_centroids"] += len(spill)
        if mode == "stack":
            fold = self._collective_wire_fold()
            wb = _bucket_len(len(built), wide=True)
            if fold is not None:
                # the mesh fold scans equal per-device wire slices:
                # pad the wire axis to a multiple of the shard count
                # (padding wires stay live=False -> identity steps)
                wb = fold.pad_wires(wb)
            stack_m = np.zeros((wb, mb, K), np.float32)
            stack_w = np.zeros((wb, mb, K), np.float32)
            live = np.zeros(wb, bool)
            for i, (local, rank, means, wts) in enumerate(built):
                stack_m[i, local, rank] = means
                stack_w[i, local, rank] = wts
                live[i] = True
            if fold is not None:
                st.histo_means, st.histo_weights = fold(
                    st.histo_means, st.histo_weights, idx_dev,
                    stack_m, stack_w, live)
            else:
                st.histo_means, st.histo_weights = \
                    tdigest.merge_wire_stack_rows(
                        st.histo_means, st.histo_weights, idx_dev,
                        jnp.asarray(stack_m), jnp.asarray(stack_w),
                        jnp.asarray(live), compression=c.compression)
        else:
            # per-wire reference mode (VENEUR_TPU_FUSED_IMPORT=0):
            # same kernel, same union rows and width, one wire per
            # call — the bit-exact baseline the fused mode is tested
            # against, and the escape hatch if the fusion misbehaves
            wb = _MIN_BUCKET_WIDE
            live = np.zeros(wb, bool)
            live[0] = True
            live_dev = jnp.asarray(live)
            for local, rank, means, wts in built:
                stack_m = np.zeros((wb, mb, K), np.float32)
                stack_w = np.zeros((wb, mb, K), np.float32)
                stack_m[0, local, rank] = means
                stack_w[0, local, rank] = wts
                st.histo_means, st.histo_weights = \
                    tdigest.merge_wire_stack_rows(
                        st.histo_means, st.histo_weights, idx_dev,
                        jnp.asarray(stack_m), jnp.asarray(stack_w),
                        live_dev, compression=c.compression)
        batch = spill.take()
        if batch is not None:
            self._histo_device_step(st, *batch, with_stats=False)

    # ------------------------------------------------------------------
    # flush boundary

    def swap(self) -> Snapshot:
        """End the interval: push remaining staging, hand the device
        arrays to the caller, re-seed fresh state, maybe compact.
        Serial form of begin_swap + complete_swap."""
        return self.complete_swap(self.begin_swap())

    def begin_swap(self) -> _PendingSwap:
        """Swap half 1, under the caller's ingest lock: detach the
        final staging (O(µs), no device work), capture the interval's
        row metadata, install a fresh interval state, bump the
        generation, and run end-of-interval index compaction.  The
        heavy device apply and snapshot assembly happen in
        complete_swap, which the pipelined flush runs OUTSIDE the
        ingest lock so ingest into the new interval proceeds while
        the old interval's final merge and readback are in flight."""
        st = self._state
        # Freeze the outgoing interval's tier routing BEFORE anything
        # else: late pipelined applies pinned to this state partition
        # by these copies (escalations re-check tier_frozen under the
        # same directory lock, so an escalation either lands before
        # the freeze — and the copy sees the flip — or is skipped).
        # Copies are in CURRENT (pre-compaction) row space, matching
        # the pend metadata captured below.
        if self.tiers is not None:
            with self.tiers.lock:
                st.tier_frozen = {
                    "histo": (self.tiers.histo.tier.copy(),
                              self.tiers.histo.slot.copy()),
                    "set": (self.tiers.set.tier.copy(),
                            self.tiers.set.slot.copy()),
                }
        work = self._detach_staged(final=True)
        # the native ingest marks touched[] but defers last_gen (gen is
        # constant within an interval, so one vectorized stamp here is
        # equivalent to stamping per batch)
        for idx in (self.counter_idx, self.gauge_idx, self.histo_idx,
                    self.set_idx):
            idx.last_gen[idx.touched] = self.gen
        pend = _PendingSwap()
        pend.work = work
        pend.state = st
        pend.counter_meta = list(self.counter_idx.meta)
        pend.counter_touched = self.counter_idx.touched.copy()
        pend.gauge_meta = list(self.gauge_idx.meta)
        pend.gauge_touched = self.gauge_idx.touched.copy()
        pend.histo_meta = list(self.histo_idx.meta)
        pend.histo_touched = self.histo_idx.touched.copy()
        pend.set_meta = list(self.set_idx.meta)
        pend.set_touched = self.set_idx.touched.copy()
        pend.sink_only_rows = sum(
            idx.sink_only_rows for idx in (
                self.counter_idx, self.gauge_idx, self.histo_idx,
                self.set_idx))
        pend.overflow = {
            "counter": self.counter_idx.overflow,
            "gauge": self.gauge_idx.overflow,
            "histo": self.histo_idx.overflow,
            "set": self.set_idx.overflow,
        }
        # interval staged-sample count, captured with the overflow
        # tallies inside the same critical section so the conservation
        # ledger's cross-check sees a consistent boundary
        pend.ingested = self._interval_ingested
        self._interval_ingested = 0
        self._interval_device_staged = 0
        # the old planes belong to the outgoing state (and, soon, its
        # snapshot); the new interval ADOPTS the array references with
        # every kind marked fresh — new zeroed planes are allocated
        # lazily on first touch (see _ensure_fresh), so an untouched
        # type's snapshot keeps referencing the pristine plane, which
        # is never donated because the first touch of the NEXT
        # interval allocates a new one before any donating update
        ns = _IntervalState(self.gen + 1)
        ns.counters = st.counters
        ns.gauges = st.gauges
        ns.histo_stats = st.histo_stats
        ns.histo_import_stats = st.histo_import_stats
        ns.histo_means = st.histo_means
        ns.histo_weights = st.histo_weights
        ns.hll_regs = st.hll_regs
        ns.fresh = set(self._KINDS)
        self._state = ns
        self.gen += 1
        compacted = False
        pend.row_maps = {}
        for idx in (self.counter_idx, self.gauge_idx, self.histo_idx,
                    self.set_idx):
            idx.drops.take()
            occ = idx.occupancy()
            if occ > idx.capacity * self.config.compact_threshold:
                # compaction only pays when it frees meaningful
                # headroom; a near-full index whose rows are all live
                # (steady workload at high occupancy) would otherwise
                # compact EVERY interval, rebuilding the fast-path key
                # index each time for zero freed rows
                freed = occ - int(
                    (idx.last_gen[:occ] >= self.gen - 1).sum())
                # a FULL index must reclaim whatever it can (new keys
                # are dropping as overflow); below full, skipping a
                # low-yield compaction costs nothing until capacity
                if (freed >= max(1, idx.capacity // 8) or
                        (occ >= idx.capacity and freed > 0)):
                    mapping = idx.compact(keep_gen=self.gen - 1)
                    if idx is self.histo_idx:
                        pend.row_maps["histo"] = mapping
                    elif idx is self.set_idx:
                        pend.row_maps["set"] = mapping
                    compacted = True
                else:
                    idx.reset_interval()
            else:
                idx.reset_interval()
        if compacted and self.tiers is not None:
            # the tier directory is row-keyed: follow the renumbering
            # (dropped wide rows hand their slots back — a named
            # demotion).  The outgoing state's FROZEN copies stay in
            # old row space on purpose: they pair with the pend
            # metadata, and the boundary pass translates through
            # pend.row_maps.
            with self.tiers.lock:
                if "histo" in pend.row_maps:
                    self.tiers.histo.renumber(pend.row_maps["histo"])
                if "set" in pend.row_maps:
                    self.tiers.set.renumber(pend.row_maps["set"])
        if compacted:
            # compaction renumbered rows: rebuild the fast-path index
            # from surviving metas (rows the fast path never saw have
            # key_hash 0 and simply re-resolve on next sight)
            self.key_index.clear()
            for idx in (self.counter_idx, self.gauge_idx,
                        self.histo_idx, self.set_idx):
                for row, m in enumerate(idx.meta):
                    if m.key_hash:
                        self.key_index.insert(m.key_hash, row)
            # the gRPC import row cache maps its own hash space to
            # the same renumbered rows — drop it; the next wire list
            # re-resolves through the slow path
            self.import_row_cache.clear()
            # wire-level plans are epoch-stamped (self-invalidating),
            # but dropping them now frees the stale row vectors
            self._wire_plan_cache.clear()
            getattr(self, "_http_plan_cache", {}).clear()
            # invalidate reader shards' lock-free probes: any fused
            # pass that began against pre-compaction row numbering
            # must discard and re-ingest (ReaderShard.commit)
            self._reindex_epoch += 1
        return pend

    def complete_swap(self, pend: _PendingSwap) -> Snapshot:
        """Swap half 2 — needs no ingest lock.  Waits out any
        in-flight pipelined applies still targeting the outgoing
        interval (the pending count only reaches zero once every
        pre-swap take_staged has landed — that, plus work pinning its
        state object, is the generation guarantee: no sample lost, no
        sample double-counted across the buffer swap), applies the
        final detached staging, and assembles the snapshot."""
        with self._pending_cv:
            while pend.state.pending:
                self._pending_cv.wait()
        if not pend.work.empty:
            with self._device_lock, observe.annotate("apply.staged"):
                self._apply_work(pend.work)
        st = pend.state
        snap_tiers = None
        if self.tiers is not None:
            # every apply pinned to this state has landed (pending
            # drained above), so the boundary sees the interval's
            # final stores and no apply can race the tier flips
            with self._device_lock:
                snap_tiers = self._tier_boundary(pend, st)
        return Snapshot(
            gen=st.gen,
            counters=st.counters,
            counter_meta=pend.counter_meta,
            counter_touched=pend.counter_touched,
            gauges=st.gauges,
            gauge_meta=pend.gauge_meta,
            gauge_touched=pend.gauge_touched,
            histo_stats=st.histo_stats,
            histo_import_stats=st.histo_import_stats,
            histo_means=st.histo_means,
            histo_weights=st.histo_weights,
            histo_meta=pend.histo_meta,
            histo_touched=pend.histo_touched,
            hll_regs=st.hll_regs,
            set_meta=pend.set_meta,
            set_touched=pend.set_touched,
            sink_only_rows=pend.sink_only_rows,
            hll_host_plane=st.hll_host_plane,
            hll_device_touched=st.hll_device_touched,
            hll_host_ez=st.hll_host_ez,
            hll_host_inv=st.hll_host_inv,
            recycle=self._recycle_plane,
            overflow=pend.overflow,
            ingested=pend.ingested,
            tiers=snap_tiers,
            import_counts=dict(st.import_counts),
        )

    def _tier_boundary(self, pend: _PendingSwap,
                       st: _IntervalState):
        """End-of-interval promotion/demotion boundary + capture of
        the interval's tier view.  Runs under _device_lock after the
        final apply, so directory flips here affect the NEXT interval
        only.  Rows that already have next-interval data in flight
        (live touched) skip their flip until the following boundary —
        that is what makes every flip lossless: a flipped row never
        has one interval's data on both sides of the tier.  Boundary
        promotions are tier flips only (interval planes reset at every
        swap, so there is nothing to migrate); mid-interval
        escalations did the in-place lossless upgrades."""
        dirs = self.tiers
        th = dirs.thresholds
        if st.histo_compact is not None:
            st.histo_compact.consolidate()
        if st.set_sparse is not None:
            st.set_sparse.consolidate()
        with dirs.lock:
            for name, cls, idx, store, thresh in (
                    ("histo", dirs.histo, self.histo_idx,
                     st.histo_compact, th.histo_samples),
                    ("set", dirs.set, self.set_idx,
                     st.set_sparse, th.set_entries)):
                mapping = pend.row_maps.get(name)
                touched = (pend.histo_touched if name == "histo"
                           else pend.set_touched)
                if mapping is not None:
                    tn = np.zeros(cls.rows, bool)
                    live = np.nonzero(mapping >= 0)[0]
                    tn[mapping[live]] = touched[live]
                    touched = tn
                wide = cls.tier != 0
                cls.idle[wide & touched] = 0
                cls.idle[wide & ~touched] += 1
                for r in np.nonzero(
                        wide & (cls.idle >= th.demote_idle) &
                        ~idx.touched)[0]:
                    cls.demote(int(r))
                if store is not None and not dirs.promote_frozen:
                    for ro in np.nonzero(
                            store.counts >= thresh)[0]:
                        rn = (int(ro) if mapping is None
                              else int(mapping[ro]))
                        if rn < 0 or cls.tier[rn] or idx.touched[rn]:
                            continue
                        cls.ensure_wide(rn)
            frozen = st.tier_frozen or {}
            fh = frozen.get("histo") or (dirs.histo.tier.copy(),
                                         dirs.histo.slot.copy())
            fs = frozen.get("set") or (dirs.set.tier.copy(),
                                       dirs.set.slot.copy())
            movements = {"histo": dirs.histo.take_delta(),
                         "set": dirs.set.take_delta()}
            occupancy = {"histo": dirs.histo.occupancy(),
                         "set": dirs.set.occupancy()}
        pb = self.plane_bytes()
        return tiersmod.TierSnapshot(
            histo_tier=fh[0], histo_slot=fh[1],
            set_tier=fs[0], set_slot=fs[1],
            histo_compact=st.histo_compact,
            set_sparse=st.set_sparse,
            set_dense_overflow=st.set_dense_overflow or {},
            movements=movements,
            occupancy=occupancy,
            plane_bytes=pb,
            device_bytes_per_series=pb["device_bytes_per_series"],
            pool_rows={"histo": self._histo_pool_rows,
                       "set": self._set_pool_rows})

    def plane_bytes(self) -> dict:
        """Per-class, per-tier sketch-memory accounting: the `planes`
        block in /debug/vars, the veneur.device.plane_bytes{class,
        tier} gauges, and the table.plane_bytes_* signal-history
        columns all read THIS one dict.  Values are computed from the
        actual live allocations (current interval state), so a
        promotion/demotion is visible the flush after it happens.
        Reads race ingest benignly — these are gauges, not
        invariants."""
        st = self._state

        def _b(x) -> int:
            return int(sum(getattr(leaf, "nbytes", 0)
                           for leaf in jax.tree_util.tree_leaves(x)))

        counter_b = _b(st.counters) + self._counter_dense.nbytes
        gauge_b = (_b(st.gauges) + self._gauge_dense.nbytes +
                   self._gauge_mask.nbytes)
        histo_wide = _b(st.histo_means) + _b(st.histo_weights)
        histo_stats = _b(st.histo_stats) + _b(st.histo_import_stats)
        histo_compact = (st.histo_compact.nbytes()
                         if st.histo_compact is not None else 0)
        set_wide = _b(st.hll_regs)
        for arr in (st.hll_host_plane, st.hll_host_ez,
                    st.hll_host_inv):
            if arr is not None:
                set_wide += arr.nbytes
        set_compact = (st.set_sparse.nbytes()
                       if st.set_sparse is not None else 0)
        ov = st.set_dense_overflow
        if ov:
            set_compact += sum(r.nbytes for r in ov.values())
        directory = 0
        tier_info = None
        if self.tiers is not None:
            with self.tiers.lock:
                for cls in (self.tiers.histo, self.tiers.set):
                    directory += (cls.tier.nbytes + cls.slot.nbytes +
                                  cls.idle.nbytes +
                                  cls.slot_row.nbytes)
                tier_info = {
                    "occupancy": {
                        "histo": self.tiers.histo.occupancy(),
                        "set": self.tiers.set.occupancy()},
                    "movements": self.tiers.counters(),
                    "promote_frozen": self.tiers.promote_frozen,
                }
        total = (counter_b + gauge_b + histo_wide + histo_stats +
                 histo_compact + set_wide + set_compact + directory)
        occ = (self.counter_idx.occupancy() +
               self.gauge_idx.occupancy() +
               self.histo_idx.occupancy() +
               self.set_idx.occupancy())
        return {
            "counter": {"wide": counter_b, "compact": 0},
            "gauge": {"wide": gauge_b, "compact": 0},
            "histo": {"wide": histo_wide, "stats": histo_stats,
                      "compact": histo_compact},
            "set": {"wide": set_wide, "compact": set_compact},
            "directory": directory,
            "total": total,
            "occupancy": occ,
            "device_bytes_per_series": total / max(1, occ),
            "tiers": tier_info,
        }

    def take_status(self):
        out = self.status
        self.status = {}
        return out

    def make_reader_shard(self) -> "ReaderShard | None":
        """Per-reader-thread fused-ingest scratch for the multi-reader
        SO_REUSEPORT path, or None when the native fused pass isn't
        available (the caller falls back to split parse +
        ingest_columns)."""
        if self._lib is None or not isinstance(
                self.key_index, intern.NativeHashIndex):
            return None
        return ReaderShard(self)


class ReaderShard:
    """One reader thread's private half of the fused native ingest.

    The single-reader fused path (``MetricTable.ingest_buffer``) holds
    the table lock across the whole parse+probe+combine C pass.  With
    N SO_REUSEPORT readers that serializes the hot loop; this shard
    splits it so the O(lines) work runs concurrently on every reader:

    - ``parse(buf)`` — NO lock: ``vtpu_parse_ingest`` combines into
      this shard's private dense/append scratch.  Index probes are
      lock-free (the native index publishes an immutable-capacity
      inner table RCU-style); every output buffer is shard-private;
      the delimiter-mask scratch is thread_local in C.
    - ``commit()`` — under the caller's ingest lock: resolve misses
      (new-series row allocation, batched per unique identity),
      replay them, then merge the shard's touched rows into the
      shared staging in O(touched rows + appended samples).
    - ``reset()`` — NO lock: zero the rows commit() touched.

    A compaction between parse and commit renumbers rows; the table's
    ``_reindex_epoch`` detects that, and commit discards the scratch
    and re-ingests the raw buffer through the locked path instead.

    Gauge last-write-wins resolves in commit order across shards —
    the same inherent nondeterminism as any concurrent-UDP ordering;
    counter/histo/set merges are associative and order-free.
    """

    def __init__(self, table: MetricTable):
        self.table = table
        c = table.config
        self._c_dense = np.zeros(c.counter_rows, np.float64)
        self._c_touch = np.zeros(c.counter_rows, np.uint8)
        self._g_dense = np.zeros(c.gauge_rows, np.float32)
        self._g_mask = np.zeros(c.gauge_rows, np.uint8)
        self._g_touch = np.zeros(c.gauge_rows, np.uint8)
        self._h_touch = np.zeros(c.histo_rows, np.uint8)
        self._s_touch = np.zeros(c.set_rows, np.uint8)
        self._cols: dict | None = None  # per-line columns, grow-only
        self._meta = np.zeros(12, np.int64)
        self._buf: bytes | None = None
        self._ring = None  # UringReader when fed by parse_ring
        self.last_slow_src = None  # what commit's offsets index
        self._epoch = -1
        # rows commit() merged, for the off-lock zeroing in reset()
        self._zc = self._zg = self._zh = self._zs = None

    def _ensure_cols(self, n_est: int) -> dict:
        sc = self._cols
        if sc is None or len(sc["hr"]) < n_est:
            cap = max(n_est, 4096)
            sc = self._cols = {
                "hr": np.empty(cap, np.int32),
                "hv": np.empty(cap, np.float32),
                "hw": np.empty(cap, np.float32),
                "sr": np.empty(cap, np.int32),
                "sp": np.empty(cap, np.int32),
                "mk": np.empty(cap, np.uint64),
                "mt": np.empty(cap, np.uint8),
                "mv": np.empty(cap, np.float64),
                "mm": np.empty(cap, np.uint64),
                "mw": np.empty(cap, np.float32),
                "mo": np.empty(cap, np.int64),
                "ml": np.empty(cap, np.int32),
                "oo": np.empty(cap, np.int64),
                "ol": np.empty(cap, np.int32),
                "ok": np.empty(cap, np.uint8),
            }
        return sc

    def parse(self, buf) -> None:
        """Lock-free fused parse+probe+combine into private scratch.
        ctypes releases the GIL for the C pass, so N readers parse
        genuinely in parallel."""
        import ctypes as ct
        t = self.table
        buf_b = bytes(buf) if not isinstance(buf, bytes) else buf
        self._buf = buf_b
        self._ring = None
        # epoch BEFORE the probe pass: if compaction lands during the
        # pass, commit sees the bumped epoch and discards
        self._epoch = t._reindex_epoch
        buf_np = np.frombuffer(buf_b, np.uint8)
        sc = self._ensure_cols(buf_b.count(b"\n") + 1)
        meta = self._meta
        meta[:] = 0

        def p(a, ty):
            return a.ctypes.data_as(ct.POINTER(ty))

        u8p = ct.c_uint8
        t._lib.vtpu_parse_ingest(
            p(buf_np, u8p), len(buf_np),
            t.key_index.handle, hashing.HLL_P,
            p(self._c_dense, ct.c_double), p(self._c_touch, u8p),
            p(self._g_dense, ct.c_float), p(self._g_mask, u8p),
            p(self._g_touch, u8p),
            p(sc["hr"], ct.c_int32), p(sc["hv"], ct.c_float),
            p(sc["hw"], ct.c_float), p(self._h_touch, u8p),
            p(sc["sr"], ct.c_int32), p(sc["sp"], ct.c_int32),
            p(self._s_touch, u8p),
            p(sc["mk"], ct.c_uint64), p(sc["mt"], u8p),
            p(sc["mv"], ct.c_double), p(sc["mm"], ct.c_uint64),
            p(sc["mw"], ct.c_float),
            p(sc["mo"], ct.c_int64), p(sc["ml"], ct.c_int32),
            p(sc["oo"], ct.c_int64), p(sc["ol"], ct.c_int32),
            p(sc["ok"], u8p),
            p(meta, ct.c_int64))

    def parse_ring(self, ring, max_msgs: int, max_len: int,
                   wait_ms: int, wait_batch: int = 1
                   ) -> tuple[int, int, int, int]:
        """Lock-free fused parse straight from an io_uring buffer
        pool (``veneur_tpu.native.uring.UringReader``): waits up to
        wait_ms for completions, then parses each datagram IN PLACE
        in the ring arena — no recv syscall, no join/copy round.
        ``wait_batch`` > 1 asks the kernel to pool that many
        completions before waking us (the multishot batching lever —
        under load it turns per-arrival wakeups into one walk over
        hundreds of datagrams).  Miss/slow offsets index the arena;
        the buffers backing them stay held until ``ring.release()``,
        which the caller runs AFTER commit.  Returns (payload_bytes,
        n_msgs, n_oversize, n_enobufs); raises UringError when the
        ring is dead and the caller must fall back to the recvmmsg
        tier."""
        import ctypes as ct
        from ..native.uring import UringError
        t = self.table
        self._buf = None
        self._ring = ring
        # epoch BEFORE the probe pass, same as parse()
        self._epoch = t._reindex_epoch
        # scratch sized for the recvmmsg tier's worst case; the C
        # side stops consuming completions before the worst-case
        # line count could overrun it
        sc = self._ensure_cols(8192)
        meta = self._meta
        meta[:] = 0
        io_out = ring.io_out
        io_out[:] = 0

        def p(a, ty):
            return a.ctypes.data_as(ct.POINTER(ty))

        u8p = ct.c_uint8
        nbytes = t._lib.vtpu_uring_parse_ingest(
            ring.handle, max_msgs, max_len, wait_ms, wait_batch,
            len(sc["hr"]), t.key_index.handle, hashing.HLL_P,
            p(self._c_dense, ct.c_double), p(self._c_touch, u8p),
            p(self._g_dense, ct.c_float), p(self._g_mask, u8p),
            p(self._g_touch, u8p),
            p(sc["hr"], ct.c_int32), p(sc["hv"], ct.c_float),
            p(sc["hw"], ct.c_float), p(self._h_touch, u8p),
            p(sc["sr"], ct.c_int32), p(sc["sp"], ct.c_int32),
            p(self._s_touch, u8p),
            p(sc["mk"], ct.c_uint64), p(sc["mt"], u8p),
            p(sc["mv"], ct.c_double), p(sc["mm"], ct.c_uint64),
            p(sc["mw"], ct.c_float),
            p(sc["mo"], ct.c_int64), p(sc["ml"], ct.c_int32),
            p(sc["oo"], ct.c_int64), p(sc["ol"], ct.c_int32),
            p(sc["ok"], u8p),
            p(meta, ct.c_int64), p(io_out, ct.c_int32))
        if nbytes < 0:
            self._ring = None
            raise UringError(int(nbytes), "io_uring parse")
        return (int(nbytes), int(io_out[0]), int(io_out[1]),
                int(io_out[2]))

    def commit(self) -> tuple[int, int, list[tuple[int, int, int]]]:
        """Locked merge half — the caller MUST hold the same lock
        that serializes every other table mutation.  Returns
        (processed, dropped, others) exactly like ingest_buffer."""
        import ctypes as ct
        t = self.table
        if self._epoch != t._reindex_epoch:
            # rows renumbered under us: local combines used stale row
            # ids.  Drop them and run the raw buffer through the
            # locked single-reader fused path.  On the ring path the
            # raw bytes only exist as held pool buffers — materialize
            # them first (rare: one copy per compaction, not per
            # batch).
            if self._ring is not None:
                buf = self._ring.pending_copy()
            else:
                buf = self._buf
            self._discard()
            out = t.ingest_buffer(buf)
            # slow-path offsets now index the replay buffer, not the
            # ring arena — callers slice last_slow_src either way
            self.last_slow_src = buf
            return out
        sc, meta = self._cols, self._meta

        def p(a, ty):
            return a.ctypes.data_as(ct.POINTER(ty))

        u8p = ct.c_uint8
        n_miss = int(meta[2])
        if n_miss:
            # miss offsets index the parse source: the joined batch
            # buffer, or (ring path) the io_uring arena the held
            # buffers live in
            if self._ring is not None:
                buf_np = self._ring.arena
            else:
                buf_np = np.frombuffer(self._buf, np.uint8)
            shim = _MissLines(buf_np, sc["mo"], sc["ml"], sc["mt"])
            t._resolve_misses(shim, np.arange(n_miss),
                              sc["mk"][:n_miss])
            # replay the compact miss columns into the SHARD's
            # buffers (appends continue at meta's cursors), so the
            # merge below handles hits and resolved misses uniformly
            i64p = ct.POINTER(ct.c_int64)
            miss2 = np.empty(n_miss, np.int64)
            t._lib.vtpu_ingest(
                t.key_index.handle,
                p(sc["mk"], ct.c_uint64), p(sc["mt"], u8p),
                p(sc["mv"], ct.c_double), p(sc["mm"], ct.c_uint64),
                p(sc["mw"], ct.c_float), n_miss,
                miss2.ctypes.data_as(i64p), -1,
                hashing.HLL_P,
                p(self._c_dense, ct.c_double),
                p(self._c_touch, u8p),
                p(self._g_dense, ct.c_float), p(self._g_mask, u8p),
                p(self._g_touch, u8p),
                p(sc["hr"], ct.c_int32), p(sc["hv"], ct.c_float),
                p(sc["hw"], ct.c_float), p(self._h_touch, u8p),
                p(sc["sr"], ct.c_int32), p(sc["sp"], ct.c_int32),
                p(self._s_touch, u8p),
                miss2.ctypes.data_as(i64p),
                p(meta, ct.c_int64))

        processed = int(meta[3])
        dropped = int(meta[6:11].sum())
        if dropped:
            t.counter_idx.drops.add(int(meta[6]))
            t.gauge_idx.drops.add(int(meta[7]))
            t.histo_idx.drops.add(int(meta[8] + meta[9]))
            t.set_idx.drops.add(int(meta[10]))

        cr = np.nonzero(self._c_touch)[0]
        if len(cr):
            t._counter_dense[cr] += self._c_dense[cr]
            t.counter_idx.touched[cr] = True
            t._counter_dirty = True
        gr = np.nonzero(self._g_mask)[0]
        if len(gr):
            t._gauge_dense[gr] = self._g_dense[gr]
            t._gauge_mask[gr] = 1
            t.gauge_idx.touched[gr] = True
            t._gauge_dirty = True
        hn = int(meta[0])
        hr_t = None
        if hn:
            t._histo_stage.append(sc["hr"][:hn].copy(),
                                  sc["hv"][:hn].copy(),
                                  sc["hw"][:hn].copy())
            hr_t = np.nonzero(self._h_touch)[0]
            t.histo_idx.touched[hr_t] = True
        sn = int(meta[1])
        sr_t = None
        if sn:
            t._set_pos_rows.append(sc["sr"][:sn].copy())
            t._set_pos.append(sc["sp"][:sn].copy())
            sr_t = np.nonzero(self._s_touch)[0]
            t.set_idx.touched[sr_t] = True
        t._note_staged(processed - dropped)
        n_other = int(meta[11])
        others = [(int(sc["oo"][i]), int(sc["ol"][i]),
                   int(sc["ok"][i])) for i in range(n_other)]
        self._zc, self._zg, self._zh, self._zs = cr, gr, hr_t, sr_t
        # what the returned slow-path offsets index: the ring arena
        # on the parse_ring path, else the parsed bytes buffer
        self.last_slow_src = (self._ring.arena
                              if self._ring is not None else self._buf)
        self._buf = None
        self._ring = None
        return processed, dropped, others

    def reset(self) -> None:
        """Zero the locally-touched rows — off the lock, so the O(R)
        scrub never extends the critical section."""
        if self._zc is not None and len(self._zc):
            self._c_dense[self._zc] = 0.0
            self._c_touch[self._zc] = 0
        if self._zg is not None and len(self._zg):
            self._g_dense[self._zg] = 0.0
            self._g_mask[self._zg] = 0
            self._g_touch[self._zg] = 0
        if self._zh is not None and len(self._zh):
            self._h_touch[self._zh] = 0
        if self._zs is not None and len(self._zs):
            self._s_touch[self._zs] = 0
        self._zc = self._zg = self._zh = self._zs = None

    def _discard(self) -> None:
        """Full scrub for the rare epoch-mismatch path."""
        self._c_dense.fill(0.0)
        self._c_touch.fill(0)
        self._g_dense.fill(0.0)
        self._g_mask.fill(0)
        self._g_touch.fill(0)
        self._h_touch.fill(0)
        self._s_touch.fill(0)
        self._buf = None
        self._ring = None
        self._zc = self._zg = self._zh = self._zs = None
