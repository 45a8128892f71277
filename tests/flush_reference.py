"""The flush's emit as it stood before the column blocks were its
only producer: one walk over every touched row, one ``InterMetric``
an aggregate and one ``ForwardRow`` a forwarded row.  Kept, word for
word, as the reference that ``Flusher.flush`` is compared with
(``tests/test_columnar_emit.py``: the emitted metrics, the tally;
``tests/test_forward_blocks.py``: the forward's rows, the row
accounting); nothing in the program imports it.

``RowFlusher`` is a ``Flusher`` (same constructor, same readout: it
reuses ``_prefetch``, ``_emit_local`` and ``_forwardable``) whose
``flush`` returns a ``RowFlushResult``: the list of ``InterMetric``,
the ``ForwardRow``s loose in a ``ForwardList``, the tally and the row
accounting."""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from veneur_tpu.core import metrics as im
from veneur_tpu.core.flusher import (FlushResult, Flusher, ForwardList,
                                     ForwardRow, _percentile_suffix)
from veneur_tpu.core.table import RowMeta, Snapshot
from veneur_tpu.ops import segment
from veneur_tpu.protocol import dogstatsd as dsd


@dataclass
class RowFlushResult:
    metrics: list[im.InterMetric] = field(default_factory=list)
    forward: ForwardList = field(default_factory=ForwardList)
    tally: dict[str, int] = field(default_factory=dict)
    row_accounting: dict = field(default_factory=lambda: {
        "staged_rows": 0, "emitted_rows": 0, "forwarded_rows": 0,
        "overlap_rows": 0, "retained_rows": 0})
    forward_split: dict = field(default_factory=dict)

    account_rows = FlushResult.account_rows


class RowFlusher(Flusher):

    def flush(self, snap: Snapshot,
              now: int | None = None) -> RowFlushResult:
        ts = int(now if now is not None else time.time())
        res = RowFlushResult()
        pre = self._prefetch(snap)
        self._flush_counters(snap, ts, res, pre)
        self._flush_gauges(snap, ts, res, pre)
        self._flush_histos(snap, ts, res, pre)
        self._flush_sets(snap, ts, res, pre)
        res.tally["overflow"] = sum(snap.overflow.values())
        return res

    def _mk(self, name: str, ts: int, value: float, meta: RowMeta,
            mtype: str) -> im.InterMetric:
        return im.InterMetric(name=name, timestamp=ts, value=value,
                              tags=meta.tags + self.common_tags,
                              type=mtype, hostname=self.hostname)

    def _flush_counters(self, snap: Snapshot, ts: int, res: FlushResult,
                        pre: dict) -> None:
        vals = pre.get("counters")
        if vals is None:
            return
        n_fwd = n_emit = n_ret = 0
        for row in np.nonzero(
                snap.counter_touched[:len(snap.counter_meta)])[0]:
            meta = snap.counter_meta[row]
            v = float(vals[row])
            if self._forwardable(meta, always=False):
                res.forward.append(ForwardRow(meta, "counter", value=v))
                n_fwd += 1
            elif self._emit_local(meta):
                res.metrics.append(
                    self._mk(meta.name, ts, v, meta, im.COUNTER))
                n_emit += 1
            else:
                n_ret += 1
        res.account_rows(staged=n_fwd + n_emit + n_ret,
                         emitted=n_emit, forwarded=n_fwd,
                         retained=n_ret)
        # slice to the meta-backed rows before summing so the tally
        # matches emitted+forwarded rows (the full plane can carry
        # stale touch bits past len(meta))
        res.tally["counters"] = int(
            snap.counter_touched[:len(snap.counter_meta)].sum())

    def _flush_gauges(self, snap: Snapshot, ts: int, res: FlushResult,
                      pre: dict) -> None:
        vals = pre.get("gauges")
        if vals is None:
            return
        n_fwd = n_emit = n_ret = 0
        for row in np.nonzero(
                snap.gauge_touched[:len(snap.gauge_meta)])[0]:
            meta = snap.gauge_meta[row]
            v = float(vals[row])
            if self._forwardable(meta, always=False):
                res.forward.append(ForwardRow(meta, "gauge", value=v))
                n_fwd += 1
            elif self._emit_local(meta):
                res.metrics.append(
                    self._mk(meta.name, ts, v, meta, im.GAUGE))
                n_emit += 1
            else:
                n_ret += 1
        res.account_rows(staged=n_fwd + n_emit + n_ret,
                         emitted=n_emit, forwarded=n_fwd,
                         retained=n_ret)
        res.tally["gauges"] = int(
            snap.gauge_touched[:len(snap.gauge_meta)].sum())

    def _flush_histos(self, snap: Snapshot, ts: int, res: FlushResult,
                      pre: dict) -> None:
        rows = pre["histo_rows"]
        if not len(rows):
            return
        # Two stat planes: ``stats`` holds aggregates of raw samples
        # ingested by THIS node ("Local*" in the reference,
        # samplers/samplers.go:484); ``imp`` holds merged forwarded stat
        # rows, pre-combined on device into ``comb``.  Aggregates for
        # mixed-scope rows come only from the local plane (reference
        # gates on LocalWeight/LocalMin/LocalMax, samplers.go:530-621 —
        # emitting them from merged state would double-count against
        # the local tier's own emission); rows flushed with global=true
        # use the combined plane, the analogue of reading min/max/sum
        # off the merged digest itself.
        stats = pre["stats"]
        comb = pre["comb"]
        qvals = pre.get("qvals")
        all_pcts = pre["all_pcts"]
        emit_pcts = not self.is_local
        fwd_pos = {r: i for i, r in enumerate(pre["histo_fwd"])}

        n_fwd = n_emit = n_both = n_ret = 0
        for row in rows:
            meta = snap.histo_meta[row]
            st = stats[row]
            pos = fwd_pos.get(int(row))
            if pos is not None:
                res.forward.append(ForwardRow(
                    meta, "histo", stats=st.copy(),
                    means=pre["fwd_means"][pos].copy(),
                    weights=pre["fwd_weights"][pos].copy()))
                n_fwd += 1
                # an arc handed off to a new ring owner forwards ONLY:
                # the state now lives on the new member, which emits it
                # next interval — emitting here too would double-report
                # the row's mass cluster-wide for the handoff interval
                if self.handoff is not None and self.handoff(meta):
                    continue
            # mixed-scope histos emit local aggregates even while their
            # digest forwards; global-only histos emit nothing locally
            if meta.scope == dsd.SCOPE_GLOBAL and self.is_local:
                if pos is None:
                    n_ret += 1
                continue
            n_emit += 1
            if pos is not None:
                n_both += 1
            # the reference's ``global`` flag (samplers.go:511 Flush):
            # true only for global-scope rows flushed on a global node
            global_mode = (meta.scope == dsd.SCOPE_GLOBAL and
                           not self.is_local)
            self._emit_histo_row(res, meta, ts,
                                 comb[row] if global_mode else st,
                                 qvals, row, all_pcts,
                                 with_percentiles=emit_pcts or
                                 meta.scope == dsd.SCOPE_LOCAL,
                                 global_mode=global_mode)
        res.account_rows(staged=len(rows), emitted=n_emit,
                         forwarded=n_fwd, overlap=n_both,
                         retained=n_ret)
        res.tally["histograms"] = int(
            snap.histo_touched[:len(snap.histo_meta)].sum())

    def _emit_histo_row(self, res, meta, ts, st, qvals, row,
                        all_pcts, with_percentiles, global_mode=False):
        agg = set(self.aggregates)
        out = res.metrics
        weight = float(st[segment.STAT_WEIGHT])
        st_min = float(st[segment.STAT_MIN])
        st_max = float(st[segment.STAT_MAX])
        st_sum = float(st[segment.STAT_SUM])
        st_rsum = float(st[segment.STAT_RSUM])
        # sparse-emission gates (samplers.go:530-660): each aggregate is
        # emitted from local values only when locally sampled, or
        # unconditionally in global mode (merged state).  min/max use
        # the untouched sentinels as the reference uses +/-Inf.
        sampled = weight != 0
        if "max" in agg and (global_mode or
                             st_max != float(segment.STAT_MAX_EMPTY)):
            out.append(self._mk(f"{meta.name}.max", ts, st_max, meta,
                                im.GAUGE))
        if "min" in agg and (global_mode or
                             st_min != float(segment.STAT_MIN_EMPTY)):
            out.append(self._mk(f"{meta.name}.min", ts, st_min, meta,
                                im.GAUGE))
        # sum/avg gate on SAMPLED (weight != 0), not st_sum != 0, like
        # the reference (samplers.go:592-607 LocalWeight guards) — a
        # locally-sampled histogram whose values sum to exactly 0 must
        # still emit both aggregates
        if "sum" in agg and (global_mode or sampled):
            out.append(self._mk(f"{meta.name}.sum", ts, st_sum, meta,
                                im.GAUGE))
        if "avg" in agg and weight != 0:
            out.append(self._mk(
                f"{meta.name}.avg", ts, st_sum / weight, meta, im.GAUGE))
        if "count" in agg and (global_mode or sampled):
            out.append(self._mk(f"{meta.name}.count", ts, weight, meta,
                                im.COUNTER))
        if "hmean" in agg and weight != 0 and st_rsum != 0:
            out.append(self._mk(
                f"{meta.name}.hmean", ts, weight / st_rsum, meta,
                im.GAUGE))
        if "median" in agg and qvals is not None:
            out.append(self._mk(f"{meta.name}.median", ts,
                                float(qvals[row, len(all_pcts) - 1]),
                                meta, im.GAUGE))
        if with_percentiles and qvals is not None:
            for pi, p in enumerate(self.percentiles):
                out.append(self._mk(
                    f"{meta.name}."
                    f"{_percentile_suffix(p, self.percentile_naming)}",
                    ts, float(qvals[row, pi]), meta, im.GAUGE))

    def _flush_sets(self, snap: Snapshot, ts: int, res: FlushResult,
                    pre: dict) -> None:
        rows = pre["set_rows"]
        if not len(rows):
            return
        ests = pre.get("ests")
        fwd_pos = {r: i for i, r in enumerate(pre.get("set_fwd", ()))}
        n_fwd = n_emit = n_ret = 0
        for row in rows:
            meta = snap.set_meta[row]
            pos = fwd_pos.get(int(row))
            if pos is not None:
                res.forward.append(ForwardRow(
                    meta, "set", regs=pre["fwd_regs"][pos].copy()))
                n_fwd += 1
            elif self._emit_local(meta):
                res.metrics.append(self._mk(
                    meta.name, ts, float(round(ests[row])), meta,
                    im.GAUGE))
                n_emit += 1
            else:
                n_ret += 1
        res.account_rows(staged=len(rows), emitted=n_emit,
                         forwarded=n_fwd, retained=n_ret)
        res.tally["sets"] = int(
            snap.set_touched[:len(snap.set_meta)].sum())
