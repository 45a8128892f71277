"""Self-telemetry: the framework reports its own operation using the
reference's documented operator metric names (README.md:253-299;
flusher.go:32-47 runtime stats, :305-361 flush-count reporting), so
existing veneur dashboards and alerts keep working.

Two emission paths, as in the reference:
- ``stats_address`` set: DogStatsD datagrams to an external agent
  (the scopedstatsd client role, server.go:335-345).
- otherwise: samples are injected into the server's own aggregation
  table — the moral of the reference's in-process loopback channel
  client (server.go:347-354 NewChannelClient).

All counters are per-interval deltas of the server's stats dict.
"""

from __future__ import annotations

import gc
import logging
import os
import resource
import socket
import time

from veneur_tpu import observe
from veneur_tpu.observe.flushring import is_gc_key
from veneur_tpu.observe.gcpause import PAUSES
from veneur_tpu.protocol import dogstatsd as dsd
from veneur_tpu.protocol.addr import parse_addr


def _rss_bytes() -> int:
    """CURRENT resident set size.  ``ru_maxrss`` is the lifetime PEAK
    — on a server whose jit warmup transiently balloons memory it
    never comes back down, so the heap gauge would flatline at the
    high-water mark and hide every later change.  /proc/self/statm
    field 2 is live resident pages; fall back to the peak only where
    procfs is unavailable (non-Linux)."""
    try:
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * (os.sysconf("SC_PAGE_SIZE") or 4096)
    except (OSError, ValueError, IndexError):
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

log = logging.getLogger("veneur_tpu.telemetry")

# stats-dict key -> (metric name, extra tags)
_COUNTER_MAP = {
    "metrics_processed": ("veneur.worker.metrics_processed_total",
                          ("worker:0",)),
    "imports_received": ("veneur.worker.metrics_imported_total", ()),
    "packet_errors": ("veneur.packet.error_total", ()),
    "import_errors": ("veneur.import.request_error_total", ()),
    "flush_errors": ("veneur.flush.error_total", ()),
    "forward_errors": ("veneur.forward.error_total", ()),
    "spans_processed": ("veneur.worker.spans_processed_total", ()),
    "ssf_errors": ("veneur.packet.error_total",
                   ("packet_type:ssf_metric",)),
}

# per-protocol receive counters (README: veneur.listen.
# received_per_protocol_total tagged by protocol)
_PROTOCOLS = ("dogstatsd-udp", "dogstatsd-tcp", "dogstatsd-unixgram",
              "ssf-udp", "ssf-unix", "grpc")

_FLUSHED_TYPES = ("counters", "gauges", "histograms", "sets")


class Telemetry:
    def __init__(self, server):
        self.server = server
        self._last: dict[str, int] = {}
        self._sock: socket.socket | None = None
        self._addr = None
        addr = server.config.stats_address
        if addr:
            # accept both url style (udp://host:port, as every other
            # address key) and bare host:port; a bare value with no
            # port (e.g. "localhost") must fail as a CONFIG error at
            # construction, not as a naked int() ValueError
            if "://" in addr:
                _, host, port, _ = parse_addr(addr)
            else:
                host, sep, port = addr.rpartition(":")
                if not sep or not port.isdigit():
                    raise ValueError(
                        f"stats_address {addr!r}: expected host:port "
                        f"with a numeric port (e.g. "
                        f"'127.0.0.1:8125' or 'udp://host:8125')")
                port = int(port)
            self._addr = (host or "127.0.0.1", port)
            self._sock = socket.socket(socket.AF_INET,
                                       socket.SOCK_DGRAM)
        self._send_errs = 0

    # ------------------------------------------------------------------

    def _delta(self, key: str) -> int:
        cur = self.server.stats.get(key, 0)
        d = cur - self._last.get(key, 0)
        self._last[key] = cur
        return d

    def flush_tick(self, tally: dict, flush_duration_ns: float,
                   sink_durations: dict[str, float],
                   record=None) -> None:
        """Called once per flush with the interval's numbers; builds
        and emits the operator samples.  ``record`` is the cycle's
        observe.FlushRecord (per-stage durations), when the caller
        traced the flush."""
        samples: list[dsd.Sample] = []
        cfg = self.server.config
        # per-type scope overrides + fixed extra tags on the server's
        # OWN metrics (reference scopesFromConfig server.go:278 +
        # veneur_metrics_additional_tags)
        name_to_scope = {"local": dsd.SCOPE_LOCAL,
                         "global": dsd.SCOPE_GLOBAL,
                         "default": dsd.SCOPE_DEFAULT}
        scope_cfg = cfg.veneur_metrics_scopes
        extra = tuple(cfg.veneur_metrics_additional_tags)

        def _scope(mtype: str) -> str:
            return name_to_scope.get(scope_cfg.get(mtype, "local"),
                                     dsd.SCOPE_LOCAL)

        def count(name, value, tags=()):
            if value:
                samples.append(dsd.Sample(
                    name=name, type=dsd.COUNTER, value=float(value),
                    tags=tuple(sorted(tuple(tags) + extra)),
                    scope=_scope("counter")))

        def gauge(name, value, tags=()):
            samples.append(dsd.Sample(
                name=name, type=dsd.GAUGE, value=float(value),
                tags=tuple(sorted(tuple(tags) + extra)),
                scope=_scope("gauge")))

        def timer(name, value_ns, tags=()):
            samples.append(dsd.Sample(
                name=name, type=dsd.TIMER, value=float(value_ns),
                tags=tuple(sorted(tuple(tags) + extra)),
                scope=_scope("histogram")))

        for key, (name, tags) in _COUNTER_MAP.items():
            count(name, self._delta(key), tags)
        for proto in _PROTOCOLS:
            count("veneur.listen.received_per_protocol_total",
                  self._delta(f"received_{proto}"),
                  (f"protocol:{proto}",))
        for mtype in _FLUSHED_TYPES:
            count("veneur.worker.metrics_flushed_total",
                  tally.get(mtype, 0), (f"metric_type:{mtype}",))
        count("veneur.forward.post_metrics_total",
              self._delta("forward_post_metrics"))
        # sharded global forward (tpu_sharded_global): per-destination
        # wires shipped, items busy-dropped on a wedged shard's
        # bounded queue, and fail-open takes (columnar router ->
        # per-row path, or sharded -> legacy single-destination)
        count("veneur.forward.shard.wires_total",
              self._delta("forward_shard_wires"))
        count("veneur.forward.shard.busy_dropped_total",
              self._delta("forward_busy_dropped"))
        count("veneur.forward.shard.fallback_total",
              self._delta("sharded_route_fallbacks"),
              ("reason:route",))
        count("veneur.forward.shard.fallback_total",
              self._delta("sharded_forward_fallbacks"),
              ("reason:forward",))
        # collective forward plane-exchange: cycles and rows that
        # rode the mesh instead of the wire, schema-capacity rows
        # rejected back onto the wire, whole cycles that fell open
        # (exchange error/deadline), and items a global folded off
        # landed planes (the collective twin of imports_received)
        count("veneur.forward.collective.cycles_total",
              self._delta("collective_forward_cycles"))
        count("veneur.forward.collective.rows_total",
              self._delta("collective_forward_rows"))
        count("veneur.forward.collective.rejected_rows_total",
              self._delta("collective_rejected_rows"))
        count("veneur.forward.collective.fallback_total",
              self._delta("collective_forward_fallbacks"))
        count("veneur.forward.collective.fallback_rows_total",
              self._delta("collective_fallback_rows"))
        count("veneur.import.collective_items_total",
              self._delta("collective_items_received"))
        # live-reshard + deadline accounting (zero-downtime ops):
        # membership swaps, the rows they moved, and per-interval rows
        # dropped because a send missed the interval deadline
        count("veneur.forward.shard.reshards_total",
              self._delta("forward_reshards"))
        count("veneur.forward.shard.moved_rows_total",
              self._delta("forward_reshard_moved_rows"))
        count("veneur.forward.shard.timeout_dropped_total",
              self._delta("forward_timeout_dropped"))
        # drain-and-handoff traffic, both directions: wires this node
        # flagged drain=true on its shutdown flush, and drained wires
        # accepted from terminating peers
        count("veneur.forward.drain.wires_total",
              self._delta("drain_wires_sent"))
        count("veneur.forward.drain.items_total",
              self._delta("drain_items_sent"))
        count("veneur.import.drain_wires_total",
              self._delta("drain_wires_received"))
        count("veneur.import.drain_items_total",
              self._delta("drain_items_received"))
        # spool-and-replay traffic, both directions: wires this node
        # replayed out of its outage spool after a destination
        # recovered, and replay-flagged wires accepted from peers
        # that rode out OUR outage
        count("veneur.forward.replay.wires_total",
              self._delta("replay_wires_sent"))
        count("veneur.forward.replay.items_total",
              self._delta("replay_items_sent"))
        count("veneur.import.replay_wires_total",
              self._delta("replay_wires_received"))
        count("veneur.import.replay_items_total",
              self._delta("replay_items_received"))
        # crash recovery, both directions: checkpoint segments this
        # node replayed at startup (wire or local re-ingest), and
        # recovery-flagged wires accepted from restarting peers —
        # deduped counts retransmits the inc:seq registry absorbed
        count("veneur.recovery.segments_total",
              self._delta("recovery_segments_replayed"))
        count("veneur.recovery.items_total",
              self._delta("recovery_items_replayed"))
        count("veneur.recovery.errors_total",
              self._delta("recovery_errors"))
        count("veneur.import.recovery_wires_total",
              self._delta("recovery_wires_received"))
        count("veneur.import.recovery_items_total",
              self._delta("recovery_items_received"))
        count("veneur.import.recovery_deduped_total",
              self._delta("recovery_wires_deduped"))
        # scale-out arc handoff, both directions; plus listener fds
        # adopted from a predecessor at boot (einhorn-style restart)
        count("veneur.forward.handoff.wires_total",
              self._delta("handoff_wires_sent"))
        count("veneur.forward.handoff.items_total",
              self._delta("handoff_items_sent"))
        count("veneur.forward.handoff.errors_total",
              self._delta("handoff_errors"))
        count("veneur.import.handoff_wires_total",
              self._delta("handoff_wires_received"))
        count("veneur.import.handoff_items_total",
              self._delta("handoff_items_received"))
        count("veneur.restart.fds_adopted_total",
              self._delta("listener_fds_adopted"))
        # staged-plane checkpointer (ops/checkpoint.py): segment
        # writes, prunes after flush seals, and stale discards (a
        # capture the flush overtook mid-serialize)
        ckpt = getattr(self.server, "_checkpointer", None)
        if ckpt is not None:
            for attr, metric in (
                    ("written", "veneur.checkpoint.written_total"),
                    ("bytes", "veneur.checkpoint.bytes_total"),
                    ("rows", "veneur.checkpoint.rows_total"),
                    ("pruned", "veneur.checkpoint.pruned_total"),
                    ("stale_discarded",
                     "veneur.checkpoint.stale_discarded_total"),
                    ("errors", "veneur.checkpoint.errors_total")):
                key = f"checkpoint_{attr}"
                self.server.stats[key] = int(ckpt.stats[attr])
                count(metric, self._delta(key))
            gauge("veneur.checkpoint.last_items",
                  ckpt.stats["last_items"])
        # discovery refresh health for the sharded forward ring:
        # reason-tagged refresh errors (keep-last-good degradation)
        fwd = getattr(self.server, "_sharded_fwd", None)
        if fwd is not None:
            disc = fwd.discovery_stats()
            for reason, total in sorted(
                    disc.get("refresh_errors", {}).items()):
                key = f"discovery_refresh_errors_{reason}"
                self.server.stats[key] = int(total)
                count("veneur.discovery.refresh_errors_total",
                      self._delta(key), (f"reason:{reason}",))
            # per-destination circuit breakers on the forward
            # workers: live state gauge (0=closed 1=half_open
            # 2=open) + cumulative trips and short-circuited sends
            for dest, bs in sorted(fwd.breaker_states().items()):
                gauge("veneur.forward.breaker.state",
                      bs["state_code"], (f"destination:{dest}",))
                key = f"breaker_opens_{dest}"
                self.server.stats[key] = int(bs["opens"])
                count("veneur.forward.breaker.opens_total",
                      self._delta(key), (f"destination:{dest}",))
                key = f"breaker_short_circuits_{dest}"
                self.server.stats[key] = int(bs["short_circuits"])
                count("veneur.forward.breaker.short_circuit_total",
                      self._delta(key), (f"destination:{dest}",))
            # outage spool accounting: lifetime intake/replay totals,
            # reason-tagged expiry (the attributed-loss path), and
            # the live backlog gauges an operator sizes the spool by
            sp = fwd.spool_stats()
            if sp is not None:
                for skey, metric in (
                        ("spooled_items",
                         "veneur.forward.spool.spooled_items_total"),
                        ("replayed_items",
                         "veneur.forward.spool.replayed_items_total"),
                        ("rejected_items",
                         "veneur.forward.spool.rejected_items_total")):
                    key = f"spool_{skey}"
                    self.server.stats[key] = int(sp[skey])
                    count(metric, self._delta(key))
                for reason, n in sorted(
                        sp["expired_by_reason"].items()):
                    key = f"spool_expired_{reason}"
                    self.server.stats[key] = int(n)
                    count("veneur.forward.spool.expired_items_total",
                          self._delta(key), (f"reason:{reason}",))
                gauge("veneur.forward.spool.queued_items",
                      sp["queued_items"])
                gauge("veneur.forward.spool.queued_bytes",
                      sp["queued_bytes"])
        # cross-interval spool-ledger verdict (spooled == replayed +
        # expired + queued + inflight; see docs/observability.md)
        count("veneur.ledger.spool_imbalance_total",
              self._delta("spool_ledger_imbalance"))
        sentry_client = getattr(self.server, "sentry", None)
        if sentry_client is not None:
            # reference sentry.go:61 reports sentry.errors_total per
            # delivered crash event
            self.server.stats["sentry_errors"] = \
                sentry_client.errors_total
            count("sentry.errors_total", self._delta("sentry_errors"))
        fwd_ns = self._delta("forward_duration_ns")
        if fwd_ns:
            timer("veneur.forward.duration_ns", fwd_ns)

        timer("veneur.flush.total_duration_ns", flush_duration_ns)
        # per-stage flush timings (observe/tracer.py span tree) — the
        # number that tells an operator WHERE the interval went:
        # device dispatch vs readback sync vs host emit vs sink I/O
        if record is not None:
            for stage, ns in list(record.stages.items()):
                if not is_gc_key(stage):
                    timer("veneur.flush.stage_duration_ns", ns,
                          (f"stage:{stage}",))
            # collector pauses inside the last whole cycle (this one
            # goes on past this call).  A gauge: a dense row, so the
            # series adds no sample to the digest merge's batch
            ring = getattr(self.server, "flush_ring", None)
            done = ring.last() if ring is not None else None
            if done is not None:
                gauge("veneur.flush.gc_pause_ns", done.gc_pause_ns)
        # device-cost registry deltas (observe/devicecost.py): compile
        # activity in steady state means a hot-path jit silently
        # recompiled — the shape-drift failure mode the registry
        # exists to expose — and readback bytes price the d2h link
        dev = observe.REGISTRY.totals()
        self.server.stats["xla_compiles"] = dev["compile_total"]
        count("veneur.xla.compile_total", self._delta("xla_compiles"))
        self.server.stats["xla_compile_ns"] = \
            dev["compile_duration_ns"]
        compile_ns = self._delta("xla_compile_ns")
        if compile_ns:
            timer("veneur.xla.compile_duration_ns", compile_ns)
        self.server.stats["device_readback_bytes"] = \
            dev["readback_bytes_total"]
        count("veneur.device.readback_bytes_total",
              self._delta("device_readback_bytes"))
        # dispatch count and host->device transfer volume: the pair
        # the superbatch apply path exists to collapse — a rising
        # per-interval dispatch delta under VENEUR_TPU_SUPERBATCH=on
        # means staged work is falling back per-class
        self.server.stats["device_dispatches"] = \
            dev["dispatch_total"]
        count("veneur.device.dispatches_total",
              self._delta("device_dispatches"))
        self.server.stats["device_h2d_bytes"] = \
            dev["h2d_bytes_total"]
        count("veneur.device.h2d_bytes_total",
              self._delta("device_h2d_bytes"))
        # adaptive sketch tiers (core/tiers.py): per-class/per-tier
        # sketch memory as gauges and the boundary's cumulative
        # movement counters as deltas.  Absent entirely when the
        # table resolved single-tier (_last_plane_bytes stays None)
        pb = getattr(self.server, "_last_plane_bytes", None)
        if pb is not None:
            for cls in ("counter", "gauge", "histo", "set"):
                for tier_name, nbytes in sorted(
                        pb.get(cls, {}).items()):
                    gauge("veneur.device.plane_bytes", int(nbytes),
                          (f"class:{cls}", f"tier:{tier_name}"))
            gauge("veneur.device.plane_bytes_per_series",
                  float(pb.get("device_bytes_per_series", 0.0)))
            ti = pb.get("tiers") or {}
            for cls, mv in sorted((ti.get("movements") or {}).items()):
                for mname, metric in (
                        ("promotions",
                         "veneur.tier.promotions_total"),
                        ("demotions",
                         "veneur.tier.demotions_total"),
                        ("escalations",
                         "veneur.tier.escalations_total"),
                        ("promote_refused",
                         "veneur.tier.promote_refused_total")):
                    key = f"tier_{cls}_{mname}"
                    self.server.stats[key] = int(mv.get(mname, 0))
                    count(metric, self._delta(key),
                          (f"class:{cls}",))
            for cls, occ in sorted(
                    (ti.get("occupancy") or {}).items()):
                gauge("veneur.tier.wide_rows", int(occ.get("wide", 0)),
                      (f"class:{cls}",))
                gauge("veneur.tier.free_slots",
                      int(occ.get("free_slots", 0)),
                      (f"class:{cls}",))
        # persistent compilation cache traffic: hits are compiles the
        # disk cache absorbed (startup/restart cost, not steady-state)
        self.server.stats["xla_cache_hits"] = dev["compile_cache_hits"]
        self.server.stats["xla_cache_misses"] = \
            dev["compile_cache_misses"]
        count("veneur.xla.compile_cache_hits",
              self._delta("xla_cache_hits"))
        count("veneur.xla.compile_cache_misses",
              self._delta("xla_cache_misses"))
        if self.server.config.count_unique_timeseries:
            # touched-row counts ARE the unique-timeseries tally (the
            # reference's tallyTimeseries HLL exists because worker
            # maps shard; one table needs no sketch, flusher.go:135)
            uniq = sum(tally.get(k, 0) for k in _FLUSHED_TYPES)
            is_global = not self.server.is_local
            count("veneur.flush.unique_timeseries_total", uniq,
                  (f"global_veneur:{str(is_global).lower()}",))
        for sink_name, dur_ns in sink_durations.items():
            timer("veneur.sink.metric_flush_total_duration_ns", dur_ns,
                  (f"sink:{sink_name}",))
        # per-span-sink delivery counters (reference sinks.go
        # MetricKeyTotalSpansFlushed/Dropped/Skipped, reported by each
        # sink's Flush via the trace client; here the sinks keep plain
        # counters and the tick reads the deltas)
        for sink in getattr(self.server, "span_sinks", []):
            sname = getattr(sink, "name", type(sink).__name__)
            for attr, metric in (
                    ("submitted", "veneur.sink.spans_flushed_total"),
                    ("dropped", "veneur.sink.spans_dropped_total"),
                    ("skipped", "veneur.sink.spans_skipped_total"),
                    ("metrics_generated",
                     "veneur.sink.metrics_flushed_total")):
                cur = getattr(sink, attr, None)
                if cur is None:
                    continue
                key = f"span_sink_{sname}_{attr}"
                self.server.stats[key] = int(cur)
                count(metric, self._delta(key), (f"sink:{sname}",))
        # per-sink fan-out worker counters (sinks/fanout.py): a busy
        # drop means this interval skipped a sink whose previous flush
        # was still running; retries/timeouts price its flakiness
        fanout = getattr(self.server, "_fanout", None)
        if fanout is not None:
            for sname, fs in fanout.stats().items():
                for attr, metric in (
                        ("busy_drops",
                         "veneur.sink.flush_busy_drops_total"),
                        ("retries",
                         "veneur.sink.flush_retries_total"),
                        ("timeouts",
                         "veneur.sink.flush_timeouts_total"),
                        ("errors",
                         "veneur.sink.flush_errors_total")):
                    key = f"fanout_{sname}_{attr}"
                    self.server.stats[key] = int(fs.get(attr, 0))
                    count(metric, self._delta(key),
                          (f"sink:{sname}",))
        # conservation-ledger verdict for the interval just sealed
        # (the seal runs before this tick): per-reason drop counts and
        # any imbalance, under the names documented in
        # docs/observability.md
        ledger = getattr(self.server, "ledger", None)
        rec = ledger.last() if ledger is not None else None
        if rec is not None:
            count("veneur.ledger.received_total", rec.received_total())
            count("veneur.ledger.staged_total", rec.staged)
            count("veneur.ledger.dropped_total", rec.overflow,
                  ("reason:overflow",))
            count("veneur.ledger.dropped_total", rec.invalid,
                  ("reason:invalid",))
            count("veneur.ledger.parse_errors_total", rec.parse_errors)
            count("veneur.ledger.emitted_rows_total", rec.emitted_rows)
            count("veneur.ledger.forwarded_rows_total",
                  rec.forwarded_rows)
            count("veneur.ledger.owed_total",
                  abs(rec.owed) + abs(rec.staged_drift)
                  + abs(rec.overflow_drift) + abs(rec.rows_owed)
                  + abs(rec.split_owed))
            count("veneur.ledger.forward_split_dropped_total",
                  rec.forward_split_dropped)
            count("veneur.ledger.imbalance_total",
                  self._delta("ledger_imbalance"))
            count("veneur.ledger.shed_total", rec.shed)
            # the recovered arm: crash-tail items this interval
            # accepted under a recovery flag (paired with a normal
            # ingest credit; owed != 0 means a recovery credit
            # arrived without its source attribution) — plus the
            # receiving side of a scale-out arc handoff
            count("veneur.ledger.recovered_total", rec.recovered)
            count("veneur.ledger.recovered_owed_total",
                  abs(rec.recovered_owed))
            count("veneur.ledger.reshard_received_items_total",
                  rec.reshard_received_items)

        # overload control: shed attribution (the metric twin of the
        # ledger's shed block — every turned-away sample named by
        # tenant and reason), pressure state, the flush-overrun
        # watchdog, and kernel-boundary receive drops
        ovl = getattr(self.server, "overload", None)
        if ovl is not None:
            for (tenant, reason), total in sorted(
                    ovl.shed_by_total.items()):
                key = f"overload_shed_{tenant}_{reason}"
                self.server.stats[key] = int(total)
                count("veneur.overload.shed_total", self._delta(key),
                      (f"tenant:{tenant}", f"reason:{reason}"))
            gauge("veneur.overload.pressure_level",
                  ovl.pressure.level)
            gauge("veneur.overload.pressure_score",
                  ovl.pressure.score)
            self.server.stats["flush_overruns"] = int(
                ovl.flush_overruns)
            count("veneur.flush.overrun_total",
                  self._delta("flush_overruns"))
        count("veneur.flush.coalesced_total",
              self._delta("flush_coalesced"))
        count("veneur.socket.kernel_drops_total",
              self._delta("socket_kernel_drops"))
        # io_uring ingest tier health: uring->recvmmsg fallbacks by
        # reason (probe refused / ring died at runtime) and
        # buffer-pool-exhaustion drops at the kernel boundary
        for reason in ("enosys", "eperm", "enomem", "einval",
                       "error"):
            d = self._delta(f"socket_backend_fallback_{reason}")
            if d:
                count("veneur.socket.backend_fallback_total", d,
                      (f"reason:{reason}",))
        count("veneur.socket.uring_enobufs_total",
              self._delta("socket_uring_enobufs"))
        # signal-history plane + anomaly flight recorder
        # (observe/signals.py / observe/recorder.py): rows sampled
        # into the columnar ring, and incident bundles dumped —
        # tagged by the trigger that fired them — plus dumps the
        # per-trigger cooldown suppressed and writer-path errors
        sig = getattr(self.server, "signals", None)
        if sig is not None:
            self.server.stats["signals_rows"] = int(
                sig.appended_total)
            count("veneur.signals.rows_total",
                  self._delta("signals_rows"))
        flt = getattr(self.server, "flight", None)
        if flt is not None:
            for trig, total in sorted(flt.by_trigger().items()):
                key = f"flight_bundles_{trig}"
                self.server.stats[key] = int(total)
                count("veneur.flight.bundles_total",
                      self._delta(key), (f"trigger:{trig}",))
            self.server.stats["flight_suppressed"] = int(
                flt.suppressed_total)
            count("veneur.flight.suppressed_total",
                  self._delta("flight_suppressed"))
            self.server.stats["flight_errors"] = int(
                flt.errors_total)
            count("veneur.flight.errors_total",
                  self._delta("flight_errors"))
        # "other"-sample drops at sinks that only speak samples they
        # understand (kafka's FlushOtherSamples contract): counted,
        # never silent
        for sink in getattr(self.server, "metric_sinks", []):
            cur = getattr(sink, "other_dropped", None)
            if cur is None:
                continue
            sname = getattr(sink, "name", type(sink).__name__)
            key = f"sink_{sname}_other_dropped"
            self.server.stats[key] = int(cur)
            count("veneur.sink.kafka.other_dropped_total",
                  self._delta(key), (f"sink:{sname}",))

        # import response timing (reference README:
        # veneur.import.response_duration_ns)
        # ns read BEFORE the count: a request landing in between
        # contributes its count now and its ns next interval — the
        # average can only deflate transiently, never inflate
        imp_ns = self._delta("import_response_ns")
        resp = self._delta("import_responses")
        if resp:
            timer("veneur.import.response_duration_ns",
                  imp_ns / resp, ("part:merge",))

        # runtime stats (flusher.go:32-43: gc.number, heap bytes).
        # gc pause time comes from the process-wide gc.callbacks hook
        # (observe/gcpause.py; the Python stand-in for Go's
        # PauseTotalNs), installed by Server.start.
        counts = gc.get_stats()
        gauge("veneur.gc.number",
              sum(s.get("collections", 0) for s in counts))
        gauge("veneur.gc.pause_total_ns", PAUSES.pause_ns)
        gauge("veneur.mem.heap_alloc_bytes", _rss_bytes())
        gauge("veneur.flush.flush_timestamp_ns", time.time_ns())

        self._emit(samples)

    # ------------------------------------------------------------------

    def _emit(self, samples: list[dsd.Sample]) -> None:
        if self._sock is not None:
            lines = []
            for s in samples:
                t = {dsd.COUNTER: "c", dsd.GAUGE: "g",
                     dsd.TIMER: "ms"}[s.type]
                tagstr = ("|#" + ",".join(s.tags)) if s.tags else ""
                lines.append(f"{s.name}:{s.value}|{t}{tagstr}")
            try:
                self._sock.sendto("\n".join(lines).encode(), self._addr)
            except OSError as e:
                self._send_errs += 1
                if self._send_errs <= 3:  # don't spam every interval
                    log.warning("stats_address %s send failed: %s",
                                self._addr, e)
            return
        # loopback: inject into our own table (next interval's flush
        # carries them, like the reference's async statsd client).
        # These are table samples like any other, so they credit the
        # conservation ledger — uncredited they'd show as staged_drift
        srv = self.server
        with srv.lock:
            staged = dropped = 0
            for s in samples:
                if srv.table.ingest(s):
                    staged += 1
                else:
                    dropped += 1
            srv.ledger.ingest("self-telemetry",
                              processed=staged + dropped,
                              staged=staged, overflow=dropped)
