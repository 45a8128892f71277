"""Per-flush-cycle records in a bounded ring.

Every flush cycle leaves one ``FlushRecord`` behind: per-stage wall
times, readback bytes, emit/forward counts, the interval's tally, and
the compile delta.  The last 128 live in a ``FlushRing`` served as
JSON at ``/debug/flushes`` — the evidence an operator (or a perf PR)
reads to attribute a slow interval to a STAGE instead of a total.
"""

from __future__ import annotations

import json
import threading
from collections import deque
from dataclasses import dataclass, field

DEFAULT_CAPACITY = 128


def not_a_stage(key: str) -> bool:
    """Keys of ``FlushRecord.stages`` that say something of a stage
    and are not one: the collector's pauses, ``gc`` (the cycle's) and
    ``gc.<stage>``, and the thread CPU time of a stage, ``cpu.<stage>``
    (``cpu.import`` of an import's)."""
    return key == "gc" or key.startswith(("gc.", "cpu."))


@dataclass
class FlushRecord:
    seq: int = 0
    start_unix: float = 0.0
    duration_ns: int = 0
    # stage name -> cumulative ns (a stage entered twice accumulates);
    # also the collector's pauses (``gc``, ``gc.<stage>``) and the
    # stages' thread CPU time (``cpu.<stage>``), see ``not_a_stage``
    stages: dict[str, int] = field(default_factory=dict)
    readback_bytes: int = 0
    metrics_emitted: int = 0
    forward_rows: int = 0
    forward_bytes: int = 0  # serialized forward bodies shipped
    # the single-global forward's encode: rows it took from the
    # flush's column blocks, rows that came one by one, and series
    # whose identity bytes an earlier interval had left on the row
    rows_block: int = 0
    rows_loose: int = 0
    ident_cached: int = 0
    # live series with a ``veneursinkonly:`` tag, as the frame was
    # told (stage ``sink_flush.route``): 0 is a cycle whose routing
    # looked at no pool for a sink without excluded tags
    sink_only_rows: int = 0
    # import wires this server folded since its previous cycle; their
    # handler durations are in ``stages`` under ``import[.<step>]``,
    # their thread CPU time under ``cpu.import``
    imports: int = 0
    # what those wires' folds came to in the interval this cycle
    # closed, counted where they run (``core/table.py``
    # ``_IntervalState.import_counts``): digest folds through the
    # flat ranked merge and through the stacked scan, centroids the
    # stack left to the flat merge, centroids folded in all,
    # register planes unioned on the host, and of a decoded wire's
    # sketches those that went through the per-item decode and not
    # the one native pass (sparse or malformed; 0 for dense wires)
    import_steps_flat: int = 0
    import_steps_stack: int = 0
    import_spilled_centroids: int = 0
    import_centroids: int = 0
    import_set_planes: int = 0
    import_set_planes_loose: int = 0
    # a mesh table's interval (``parallel/sharded.py``; left empty by
    # one chip's table): SPMD update calls, the items staged to each
    # shard, the mesh as ``<shards>x<series>``, and the digest path
    # its flush merge took over the gathered slots (``pallas``, or
    # the fallback past the kernel's lanes).  The swap's parts are in
    # ``stages`` under ``snapshot.final_step|shard_merge|state_reset``.
    # Of the reset: the bytes it built, shipped or cleared on the host
    # (0 when the closed interval's register planes were cleared after
    # their flush), and the bytes of those planes it left to be
    # cleared once this cycle has delivered
    shard_steps: int = 0
    shard_staged: list[int] = field(default_factory=list)
    mesh: str = ""
    merge_path: str = ""
    reset_host_bytes: int = 0
    reset_deferred_bytes: int = 0
    tally: dict[str, int] = field(default_factory=dict)
    compiles: int = 0  # compile events observed during this cycle
    # collector pauses that ended inside the cycle, on any thread
    # (observe/gcpause.py), and how many were full collections; a
    # stage's share is ``stages["gc.<stage>"]``, the whole is also
    # ``stages["gc"]``
    gc_pause_ns: int = 0
    gc_gen2: int = 0
    error: str = ""
    # trace id of the cycle's span tree — the /debug/flushes ->
    # /debug/trace/<id> link (string in JSON: ids are 63-bit)
    trace_id: int = 0

    def to_dict(self) -> dict:
        return {"seq": self.seq, "start_unix": self.start_unix,
                "duration_ns": self.duration_ns,
                "stages_ns": dict(self.stages),
                "readback_bytes": self.readback_bytes,
                "metrics_emitted": self.metrics_emitted,
                "forward_rows": self.forward_rows,
                "forward_bytes": self.forward_bytes,
                "rows_block": self.rows_block,
                "rows_loose": self.rows_loose,
                "ident_cached": self.ident_cached,
                "sink_only_rows": self.sink_only_rows,
                "imports": self.imports,
                "import_steps_flat": self.import_steps_flat,
                "import_steps_stack": self.import_steps_stack,
                "import_spilled_centroids":
                    self.import_spilled_centroids,
                "import_centroids": self.import_centroids,
                "import_set_planes": self.import_set_planes,
                "import_set_planes_loose":
                    self.import_set_planes_loose,
                "shard_steps": self.shard_steps,
                "shard_staged": list(self.shard_staged),
                "mesh": self.mesh,
                "merge_path": self.merge_path,
                "reset_host_bytes": self.reset_host_bytes,
                "reset_deferred_bytes": self.reset_deferred_bytes,
                "tally": dict(self.tally),
                "compiles": self.compiles,
                "gc_pause_ns": self.gc_pause_ns,
                "gc_gen2": self.gc_gen2,
                "error": self.error,
                "trace_id": str(self.trace_id)}


class FlushRing:
    """Thread-safe bounded ring of the most recent flush records."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._ring: deque[FlushRecord] = deque(maxlen=capacity)
        self._seq = 0

    def next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    def append(self, record: FlushRecord) -> None:
        with self._lock:
            self._ring.append(record)

    def records(self) -> list[FlushRecord]:
        """Oldest -> newest."""
        with self._lock:
            return list(self._ring)

    def last(self) -> FlushRecord | None:
        with self._lock:
            return self._ring[-1] if self._ring else None

    def to_json(self, limit: int | None = None) -> bytes:
        """``limit`` bounds the dump to the newest N records (the
        ``?n=`` query param on /debug/flushes)."""
        recs = self.records()
        if limit and limit > 0:
            recs = recs[-limit:]
        return json.dumps([r.to_dict() for r in recs],
                          indent=1).encode()

    def stage_summary(self) -> dict:
        """Aggregate per-stage timings across the retained records —
        what bench.py stamps into its artifacts so the perf
        trajectory attributes a regression to a stage."""
        recs = self.records()
        out: dict = {"cycles": len(recs)}
        if not recs:
            return out
        stages: dict[str, list[int]] = {}
        for r in recs:
            for name, ns in r.stages.items():
                stages.setdefault(name, []).append(ns)
        out["stages_ns"] = {
            name: {"mean": int(sum(v) / len(v)), "max": max(v),
                   "last": v[-1], "count": len(v)}
            for name, v in stages.items()}
        out["readback_bytes_mean"] = int(
            sum(r.readback_bytes for r in recs) / len(recs))
        out["compiles_total"] = sum(r.compiles for r in recs)
        return out
