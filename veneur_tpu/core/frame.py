"""MetricFrame: the columnar flush->emit interchange.

What a flush emits is one ``MetricFrame`` (``FlushResult.frame``).  One
``InterMetric`` object per aggregate — a single histogram row fans out
to 8+ of them — is, at wide cardinality (100k-1M live series), a cost
larger than the d2h readback or the XLA merge (the "serialization cost
dominates sketch cost" regime SALSA identifies for streaming sketches),
so a frame keeps the data columnar from the device readback to the
sink wire:

- a frame is a list of ``Block``s; each block is ONE aggregate kind
  (the counter plane, ``<histo>.max``, one percentile column, ...)
  over many series rows
- a block indexes into a shared row-metadata pool (the snapshot's
  ``RowMeta`` list) via a NumPy index array, so names and tag tuples
  are never copied per metric — a histogram's 8 aggregate blocks all
  point at the same pool rows
- values are one f64 NumPy column per block (widened from the f32
  device planes, bit-identical to ``float()`` per row)
- the name suffix (``".max"``, ``".99percentile"``) and the metric
  type are per-BLOCK scalars, computed once per flush instead of once
  per row

Sinks that understand frames (``flush_frame``) encode straight off the
columns; whoever needs the list of ``InterMetric`` (a sink that only
knows ``flush(list)``, a plugin, a reader of ``FlushResult.metrics``)
goes through ``materialize()``, which builds it on first call and
caches it; a cycle in which nobody asks builds none.  Per-sink
routing (``veneursinkonly:`` whitelists + excluded-tag stripping,
reference sinks/sinks.go:51) is evaluated once per POOL ROW, not once
per metric — the masks broadcast to every block sharing the pool —
and only in a flush that has something to route: a row's whitelist is
read off its tags when the row is made (``RowMeta.sink_only``), the
table's indexes count the rows that have one, and the frame is told
the count (``sink_only_rows``), so a frame of ordinary series goes to
a sink without exclusions as it is, no pool looked at.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from veneur_tpu.core import metrics as im
from veneur_tpu.core.metrics import InterMetric

# per-block metric type codes
TYPE_GAUGE = 0
TYPE_COUNTER = 1
TYPE_NAMES = (im.GAUGE, im.COUNTER)


@dataclass
class Block:
    """One aggregate kind over many series rows.

    ``rows`` indexes into ``metas``; ``tag_table`` (set by routing)
    replaces the pool's raw tag tuples with the sink's final
    (common-tag-appended, excluded-tag-stripped) tuples, aligned to
    the POOL, so blocks sharing a pool share the table."""
    metas: list
    rows: np.ndarray  # int64[n] pool indices
    values: np.ndarray  # f64[n]
    suffix: str = ""
    type_code: int = TYPE_GAUGE
    tag_table: list | None = None

    def __len__(self) -> int:
        return len(self.rows)


class MetricFrame:
    def __init__(self, ts: int, hostname: str = "",
                 common_tags: tuple[str, ...] = (),
                 sink_only_rows: int = 0):
        self.ts = int(ts)
        self.hostname = hostname
        self.common_tags = tuple(common_tags)
        # how many rows of the pools have a ``RowMeta.sink_only``
        # (``Snapshot.sink_only_rows``; whoever builds a frame by hand
        # over such rows says so here): with the common tags' own
        # whitelist, all that decides whether acceptance can differ
        # from sink to sink
        self.sink_only_rows = int(sink_only_rows)
        self._common_sink_only = im.parse_sink_only(self.common_tags)
        self.blocks: list[Block] = []
        # InterMetrics that ride along with a routed frame (the
        # sink's filtered slice of ``FlushResult.riders``: status
        # checks, anything synthesized outside the flush)
        self.extra: list[InterMetric] = []
        self._materialized: list[InterMetric] | None = None
        # when a routed view shares this frame's blocks verbatim, it
        # points back here so the block materialization is built once
        # and shared across every no-filter sink
        self._block_src: "MetricFrame | None" = None
        # (id(pool), sink_name, excluded) -> (accept bool[], tags list)
        self._route_cache: dict = {}

    # ------------------------------------------------------------------

    def add_block(self, metas: list, rows: np.ndarray,
                  values: np.ndarray, suffix: str = "",
                  type_code: int = TYPE_GAUGE) -> None:
        if len(rows) == 0:
            return
        self.blocks.append(Block(metas, np.asarray(rows),
                                 np.asarray(values, np.float64),
                                 suffix, type_code))
        self._materialized = None

    def __len__(self) -> int:
        return sum(len(b) for b in self.blocks)

    def total_len(self) -> int:
        return len(self) + len(self.extra)

    # ------------------------------------------------------------------

    def block_tags(self, block: Block, j: int) -> tuple[str, ...]:
        """Final tag tuple for position ``j`` of ``block``."""
        r = int(block.rows[j])
        if block.tag_table is not None:
            return block.tag_table[r]
        return block.metas[r].tags + self.common_tags

    def block_name(self, block: Block, j: int) -> str:
        return block.metas[int(block.rows[j])].name + block.suffix

    def iter_metrics(self):
        """Yield InterMetrics in block order (then extras)."""
        yield from self._iter_block_metrics()
        yield from self.extra

    def _iter_block_metrics(self):
        for b in self.blocks:
            mtype = TYPE_NAMES[b.type_code]
            suffix = b.suffix
            metas = b.metas
            tag_table = b.tag_table
            common = self.common_tags
            ts = self.ts
            host = self.hostname
            vals = b.values
            for j, r in enumerate(b.rows):
                r = int(r)
                meta = metas[r]
                tags = (tag_table[r] if tag_table is not None
                        else meta.tags + common)
                yield InterMetric(name=meta.name + suffix,
                                  timestamp=ts, value=float(vals[j]),
                                  tags=tags, type=mtype,
                                  hostname=host)

    def _materialize_blocks(self) -> list[InterMetric]:
        src = self._block_src or self
        if src._materialized is None:
            src._materialized = list(src._iter_block_metrics())
        return src._materialized

    def materialize(self) -> list[InterMetric]:
        """The list of InterMetrics, blocks then extras, built on
        first call and cached — the adapter for sinks and plugins
        that never learned frames."""
        blocks = self._materialize_blocks()
        return blocks + self.extra if self.extra else blocks

    # ------------------------------------------------------------------
    # per-sink routing

    def _pool_route(self, metas: list, sink_name: str,
                    excluded: frozenset):
        """(accept mask, final tag table) for one meta pool x one
        sink — O(pool rows), shared by every block over the pool and
        cached for re-entrant routing of the same sink."""
        key = (id(metas), sink_name, excluded)
        hit = self._route_cache.get(key)
        if hit is not None:
            return hit
        n = len(metas)
        accept = np.ones(n, dtype=bool)
        tags_out: list = [()] * n
        common = self.common_tags
        common_wl = self._common_sink_only
        for i, meta in enumerate(metas):
            wl = meta.sink_only
            if common_wl is not None:
                # the common tags' whitelist joins every row's own
                wl = common_wl if wl is None else wl | common_wl
            if wl is not None and sink_name not in wl:
                accept[i] = False
                continue
            tags = meta.tags + common
            if excluded:
                tags = tuple(t for t in tags
                             if t.split(":", 1)[0] not in excluded)
            tags_out[i] = tags
        out = (accept, tags_out)
        self._route_cache[key] = out
        return out

    def _needs_routing(self) -> bool:
        """True when a pool row or the common tags carry a sink
        whitelist — the only case where acceptance can differ per
        sink."""
        return (self.sink_only_rows > 0
                or self._common_sink_only is not None)

    def route(self, sink_name: str, sink=None,
              extra: list[InterMetric] | None = None) -> "MetricFrame":
        """Filter the frame for one sink: whitelist routing + excluded
        tags (the frame analogue of sinks.base.route).  Returns
        ``self`` untouched when the sink filters nothing, so the
        common no-whitelist/no-exclusion case shares one
        materialization across sinks."""
        excluded = frozenset(getattr(sink, "excluded_tags", ())
                             if sink is not None else ())
        routed = MetricFrame(self.ts, self.hostname, self.common_tags,
                             self.sink_only_rows)
        routed.extra = list(extra or ())
        routed._route_cache = self._route_cache  # share pool work
        if not excluded and not self._needs_routing():
            if not routed.extra:
                return self
            # share the block list AND its one-time materialization
            routed.blocks = self.blocks
            routed._block_src = self._block_src or self
            return routed
        for b in self.blocks:
            accept, tags_out = self._pool_route(b.metas, sink_name,
                                                excluded)
            if accept.all():
                routed.blocks.append(Block(
                    b.metas, b.rows, b.values, b.suffix, b.type_code,
                    tag_table=tags_out))
                continue
            keep = accept[b.rows]
            if not keep.any():
                continue
            routed.blocks.append(Block(
                b.metas, b.rows[keep], b.values[keep], b.suffix,
                b.type_code, tag_table=tags_out))
        return routed
