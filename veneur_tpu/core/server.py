"""Server lifecycle: listeners, flush ticker, sinks, forwarding, HTTP.

The role of reference server.go (``NewFromConfig`` :299, ``Start``
:886, ``Serve`` :1478, ``Shutdown`` :1593) and networking.go: construct
every layer from config, run ingest listeners, tick the flush clock,
and tear down cleanly.

Concurrency model: the Go original runs one goroutine per worker shard;
here the device table IS the aggregation worker, so threads exist only
at the edges — reader threads parse datagrams and append to columnar
staging under a short lock, a flush thread swaps the table every
interval, and sink flushes fan out to a thread pool.  The flush
watchdog mirrors reference server.go:1031 FlushWatchdog: if too many
intervals elapse with no flush, crash loudly so a supervisor restarts
the process.
"""

from __future__ import annotations

import http.server
import json
import logging
import os
import socket
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as _FuturesTimeout

from veneur_tpu import __version__
from veneur_tpu.core import metrics as im
from veneur_tpu.core.config import Config, parse_duration
from veneur_tpu.core.flusher import Flusher, FlushResult
from veneur_tpu.core.table import MetricTable, TableConfig
import numpy as np

from veneur_tpu.forward import http_import
from veneur_tpu.observe import ImportSpan, annotate
from veneur_tpu.observe.gcpause import PAUSES
from veneur_tpu.protocol import columnar, dogstatsd as dsd
from veneur_tpu.protocol.addr import parse_addr
from veneur_tpu.sinks import base as sinks_base
from veneur_tpu.sinks.datadog import DatadogMetricSink
from veneur_tpu.sinks.prometheus import PrometheusRepeaterSink
from veneur_tpu.sinks.simple import (BlackholeSink, DebugSink,
                                     LocalFilePlugin)

log = logging.getLogger("veneur_tpu.server")


def _device_info() -> dict:
    """The devices JAX gives this process.  Nothing here picks or
    changes the platform: a deployment without a chip sets
    JAX_PLATFORMS=cpu, and a backend that cannot start fails the
    server with the platform that was asked for in the message."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        asked = os.environ.get("JAX_PLATFORMS") or "default"
        raise RuntimeError(
            f"JAX backend init failed (JAX_PLATFORMS={asked}): {e}"
        ) from e
    return {"platform": devs[0].platform,
            "kind": devs[0].device_kind, "count": len(devs)}


def _decode_scratch_bytes() -> int:
    """Sum of native-decode scratch retained across gRPC import
    reader threads (forward.grpc_forward registry); 0 when the
    forward path never loaded."""
    try:
        from veneur_tpu.forward import grpc_forward
        return grpc_forward.decode_scratch_bytes()
    except Exception:
        return 0


def _is_deadline_error(err) -> bool:
    """True when a forward wire failure was a deadline miss — either
    our own pre-send cutoff (shard.DeadlineExceeded) or gRPC's
    DEADLINE_EXCEEDED status — so timeout drops get their own
    per-destination ledger attribution."""
    try:
        from veneur_tpu.forward.shard import DeadlineExceeded
        if isinstance(err, DeadlineExceeded):
            return True
    except Exception:
        pass
    code = getattr(err, "code", None)
    if callable(code):
        try:
            return getattr(code(), "name", "") == "DEADLINE_EXCEEDED"
        except Exception:
            return False
    return False


def _is_inline_pem(value: str) -> bool:
    """TLS config values are either PEM material inline (the
    reference's example.yaml style) or file paths."""
    return value.lstrip().startswith("-----BEGIN")


def _pem_bytes(value: str) -> bytes:
    if _is_inline_pem(value):
        return value.encode()
    with open(value, "rb") as f:
        return f.read()


def tags_to_dict(tags) -> dict[str, str]:
    """``["k:v", ...]`` config tags -> dict, skipping bare tags — the
    shape span sinks and the span worker share for common tags."""
    return dict(t.split(":", 1) for t in tags if ":" in t)


def generate_excluded_tags(rules: list[str],
                           sink_name: str) -> list[str]:
    """tags_exclude rules -> tag names excluded for one sink:
    "tagname" applies everywhere, "tagname|sink1|sink2" only on the
    named sinks (reference server.go generateExcludedTags)."""
    out = []
    for rule in rules:
        parts = rule.split("|")
        if len(parts) == 1 or sink_name in parts[1:]:
            out.append(parts[0])
    return out


class Server:
    def __init__(self, config: Config, extra_sinks: list | None = None,
                 extra_plugins: list | None = None,
                 extra_span_sinks: list | None = None):
        self.config = config
        self.device_info = _device_info()
        log.info("device: platform=%s kind=%s count=%d",
                 self.device_info["platform"],
                 self.device_info["kind"], self.device_info["count"])
        # before the table below triggers the first jit compiles;
        # restarts then hit the on-disk cache (the fast half of the
        # watchdog's crash-and-restart model).  enable() also installs
        # the jax.monitoring listener that counts persistent-cache
        # hits/misses into the device-cost registry.  With neither
        # JAX_COMPILATION_CACHE_DIR nor compile_cache_dir the server
        # stays uncached.
        from veneur_tpu.utils import compile_cache
        if config.compile_cache_dir or os.environ.get(
                compile_cache.JAX_ENV_VAR):
            compile_cache.enable(config.compile_cache_dir)
        self.interval = config.interval_seconds()
        self.is_local = config.is_local()
        table_cfg = TableConfig(
            counter_rows=config.tpu_counter_rows,
            gauge_rows=config.tpu_gauge_rows,
            histo_rows=config.tpu_histo_rows,
            set_rows=config.tpu_set_rows,
            compression=config.tpu_compression,
            histo_slots=config.tpu_histo_slots,
            collective_import=str(getattr(
                config, "tpu_collective_import", "auto")))
        if config.tpu_mesh_shards:
            # multi-chip global node: SPMD sharded planes over the
            # full device mesh; flush merge = ICI collectives
            from veneur_tpu.parallel.sharded import (ShardedConfig,
                                                     ShardedTable,
                                                     make_mesh)
            mesh = make_mesh(n_shard=config.tpu_mesh_shards)
            self.table = ShardedTable(mesh, ShardedConfig(
                rows=config.tpu_histo_rows,
                set_rows=config.tpu_set_rows,
                counter_rows=config.tpu_counter_rows,
                gauge_rows=config.tpu_gauge_rows,
                compression=config.tpu_compression,
                slots=config.tpu_histo_slots,
                # ``_maybe_device_step_locked`` says when to step (at
                # the staging threshold); an update call costs a merge
                # of the whole table whatever it holds, and a forwarded
                # wire stages several thresholds' worth at once
                # (160,000 centroids against 65,536): wide enough to
                # take a wire in one (ROADMAP B-i 5: measured in one
                # cell, the DogStatsD path on a mesh unmeasured)
                batch=4 * max(1024, config.tpu_stage_flush_samples)))
            # no config key asks for this: a first-sight compile on a
            # mesh takes tens of seconds, and would run under the
            # ingest lock inside the first import handler and flush
            t0 = time.monotonic()
            self.table.agg.compile()
            log.info("mesh %s: update and merge programs compiled in "
                     "%.2fs", self.table.agg.merge_info["mesh"],
                     time.monotonic() - t0)
            self._init_after_table(config, extra_sinks, extra_plugins,
                                   extra_span_sinks)
            return
        self.table = MetricTable(table_cfg)
        self._init_after_table(config, extra_sinks, extra_plugins,
                               extra_span_sinks)

    def _init_after_table(self, config, extra_sinks, extra_plugins,
                          extra_span_sinks) -> None:
        """Everything downstream of table construction — shared by the
        single-chip and mesh-sharded table paths."""
        self.lock = threading.Lock()
        # overlapped device pipeline (VENEUR_TPU_PIPELINE): staged work
        # is detached under self.lock in O(µs) and the jitted combine
        # kernels dispatch outside it, so ingest never stalls behind
        # XLA.  ShardedTable has its own step machinery, hence the
        # capability probe rather than a bare config check.
        want_pipeline = bool(getattr(config, "tpu_pipeline", True))
        self.pipeline = (want_pipeline
                         and hasattr(self.table, "take_staged"))
        if want_pipeline and not self.pipeline:
            # make the silent capability downgrade visible: operators
            # tuning tpu_pipeline with tpu_mesh_shards set would
            # otherwise chase a knob that does nothing
            # (docs/performance.md "pipelined flush")
            log.warning(
                "tpu_pipeline is ignored with the mesh-sharded table "
                "(tpu_mesh_shards=%s): ShardedTable runs its own SPMD "
                "step machinery and flushes synchronously",
                getattr(config, "tpu_mesh_shards", 0))
        self.sentry = None  # set by _build_sinks when sentry_dsn is
        self.flusher = Flusher(
            is_local=self.is_local,
            percentiles=tuple(config.percentiles),
            aggregates=tuple(config.aggregates),
            hostname=(config.hostname if (config.hostname or
                                          config.omit_empty_hostname)
                      else socket.gethostname()),
            tags=tuple(config.tags),
            percentile_naming=config.percentile_naming,
            quantile_interpolation=config.quantile_interpolation)

        self.metric_sinks: list = list(extra_sinks or [])
        self.plugins: list = list(extra_plugins or [])
        self.span_sinks: list = list(extra_span_sinks or [])
        self._build_sinks()

        # the span plane: ssfmetrics extraction always runs first — it
        # is part of the metric hot path (reference server.go:444-452)
        from veneur_tpu.core.spans import SpanWorker
        from veneur_tpu.sinks.ssfmetrics import MetricExtractionSink
        self.span_sinks.insert(0, MetricExtractionSink(
            self,
            indicator_timer_name=config.indicator_span_timer_name,
            objective_timer_name=config.objective_span_timer_name))
        self.span_worker = SpanWorker(
            self.span_sinks,
            common_tags=tags_to_dict(config.tags),
            capacity=config.span_channel_capacity,
            stats_cb=self.bump,
            workers=config.num_span_workers)
        # per-sink tag exclusion (reference server.go:1642
        # setSinkExcludedTags) — after ALL sinks exist
        if config.tags_exclude:
            for sink in self.metric_sinks + self.span_sinks:
                if hasattr(sink, "set_excluded_tags"):
                    sink.set_excluded_tags(generate_excluded_tags(
                        config.tags_exclude, sink.name))
        # in-process loopback trace client: the server (and any
        # embedding code) traces into its OWN span pipeline — the role
        # of the reference's NewChannelClient (server.go:347-354)
        from veneur_tpu import trace as vtrace
        self.trace_client = vtrace.Client(
            vtrace.ChannelBackend(self.span_worker.submit),
            capacity=256)
        # flush self-observation: every cycle leaves a span tree in
        # the span pipeline (via the loopback client above) and a
        # record in the ring served at /debug/flushes; device-cost
        # counters live in the process-global registry the flusher
        # and table kernels are instrumented against
        from veneur_tpu import observe
        self.device_costs = observe.REGISTRY
        self.flush_ring = observe.FlushRing()
        # cross-tier trace stitching: this process's fragment of every
        # recent flush trace (the cycle's span tree plus any import
        # spans parented under a remote tier's forward span) lives in
        # a bounded index served at /debug/trace/<trace_id>
        self.trace_index = observe.TraceIndex()
        self.flush_tracer = observe.FlushTracer(
            self.trace_client, self.flush_ring,
            registry=self.device_costs, index=self.trace_index)
        # end-to-end sample-conservation ledger: ingest paths credit
        # under self.lock (same critical section as the table
        # counters), the interval closes inside begin_swap's lock
        # round, and the sealed record lands at /debug/ledger
        self.ledger = observe.Ledger(
            strict=bool(getattr(config, "tpu_ledger_strict", False)),
            node="local" if self.is_local else "global",
            on_imbalance=lambda rec: self.bump("ledger_imbalance"))
        self._ledger_fanout_last = (0, 0, 0)
        # adaptive-tier byte accounting captured at the last boundary
        # (None until the first tiered flush, and always None when the
        # table resolved single-tier) — /debug/vars and the signal row
        # read the snapshot instead of re-walking live planes
        self._last_plane_bytes = None
        # cross-interval conservation for the outage spool: one
        # snapshot sealed per flush from WireSpool.stats(); strict
        # mode escalates a leaking spool exactly like an interval
        # imbalance
        self._spool_ledger = observe.SpoolLedger(
            strict=bool(getattr(config, "tpu_ledger_strict", False)),
            node="local" if self.is_local else "global",
            on_imbalance=lambda rec: self.bump(
                "spool_ledger_imbalance"))
        # replayed items already credited to a ledger record (the
        # replay counter on the forwarder is cumulative)
        self._replayed_credited = 0
        # overload control: admission buckets + priority shedding +
        # flush-overrun coalesce (core/overload.py).  None when
        # disabled — every call site guards, so VENEUR_TPU_OVERLOAD=0
        # removes the subsystem entirely
        self.overload = None
        if bool(getattr(config, "tpu_overload", True)):
            from veneur_tpu.core.overload import Overload
            self.overload = Overload(
                tenant_tag=str(getattr(
                    config, "tpu_overload_tenant_tag", "tenant")),
                tenant_rate=float(getattr(
                    config, "tpu_overload_tenant_rate", 0.0)),
                tenant_burst=float(getattr(
                    config, "tpu_overload_tenant_burst", 0.0)),
                max_tenants=int(getattr(
                    config, "tpu_overload_max_tenants", 256)),
                staging_hi=int(getattr(
                    config, "tpu_overload_staging_hi", 1_000_000)),
                occupancy_hi=float(getattr(
                    config, "tpu_overload_occupancy_hi", 0.95)),
                lag_hi=float(getattr(
                    config, "tpu_overload_lag_hi", 1.0)),
                exit_ratio=float(getattr(
                    config, "tpu_overload_exit_ratio", 0.7)),
                coalesce=bool(getattr(
                    config, "tpu_overload_coalesce", True)))
        # kernel-side UDP receive drops observed per flush: inode ->
        # cumulative drop count from /proc/net/udp at the previous
        # sample, so each interval records only the delta
        self._kernel_drops_last: dict[int, int] = {}
        self._uring_enobufs_last = 0

        self.events: list[dsd.Event] = []
        self.checks: list[dsd.ServiceCheck] = []
        # stats increments come from every reader/HTTP thread; dict
        # read-modify-write is not atomic, so guard with a dedicated
        # lock (cheaper than widening self.lock's critical sections)
        self._stats_lock = threading.Lock()
        # import handlers' durations and wire count since the last
        # flush cycle took them (under _stats_lock)
        self._import_stages: dict[str, int] = {}
        self._import_wires = 0
        self._pprof_lock = threading.Lock()
        self.stats: dict[str, int] = {
            "packets_received": 0, "packet_errors": 0,
            "metrics_processed": 0, "metrics_dropped": 0,
            "imports_received": 0, "flushes": 0,
        }

        from veneur_tpu.core.telemetry import Telemetry
        self.telemetry = Telemetry(self)
        self._gc_hooked = False
        self._sink_durations: dict[str, float] = {}
        self._flush_pending: dict[str, object] = {}
        # per-sink flush fan-out (VENEUR_TPU_SINK_WORKERS > 0): every
        # metric sink gets a dedicated worker + one-slot queue so a
        # stalled sink times out alone instead of holding a shared
        # pool slot; 0 falls back to the shared flush pool
        self._fanout = None
        if int(getattr(config, "tpu_sink_workers", 1)) > 0:
            from veneur_tpu.sinks.fanout import SinkFanout
            self._fanout = SinkFanout(
                [s.name for s in self.metric_sinks],
                on_error=lambda name, exc: self.bump("flush_errors"),
                retry_budget=max(self.interval * 0.9, 1.0))
        self._tls_context = self._build_tls()

        # serializes whole flushes: the ticker thread and a manual
        # flush_once (tests, /quitquitquit drain) must not interleave —
        # a concurrent pair would each swap an interval and emit out of
        # order, and a caller returning from flush_once could observe
        # the OTHER flush's data still in flight (the reference has one
        # flush goroutine, so this serialization is implicit there)
        self._flush_serial = threading.Lock()
        self._shutdown = threading.Event()
        self._threads: list[threading.Thread] = []
        self._sockets: list[socket.socket] = []
        # held flocks on unix socket paths: (lock path, open fd)
        self._socket_locks: list[tuple[str, int]] = []
        self._httpd: http.server.ThreadingHTTPServer | None = None
        self._pool = ThreadPoolExecutor(max_workers=8)
        self.last_flush = time.monotonic()
        self.http_port: int | None = None
        self.statsd_ports: list[int] = []
        self.ssf_ports: list[int] = []
        # gRPC importsrv listeners (global tier) + forward client
        self.grpc_servers: list = []
        self.grpc_ports: list[int] = []
        self._grpc_client = None
        # sharded global forward (tpu_sharded_global): consistent-hash
        # split of the forward wire across the comma-separated
        # forward_address members (or a discovered Consul service),
        # lazily built on first forward
        self._sharded_fwd = None
        # collective forward plane-exchange (tpu_collective_forward):
        # mesh-peer destinations leave the gRPC wire and ride one
        # all_to_all per cycle; lazily built on first forward.
        # ``collective_exchange`` is the injectable exchange seam —
        # tests set it to a loopback hub or a failure injector before
        # the first flush
        self._collective_fwd = None
        self.collective_exchange = None
        # discovery refresh throttle for the sharded ring (0 = static
        # membership, never polls)
        self._fwd_refresh_interval = 0.0
        self._fwd_refresh_next = 0.0
        # drain-and-handoff: True only inside _drain_handoff's final
        # flush, which flags forward wires drain=true and widens the
        # send deadline so the handoff lands before exit
        self._draining = False
        # crash-riding state (ops/checkpoint, ops/fdpass): process
        # start time, listener fds adopted from a predecessor via
        # VENEUR_TPU_SOCK_CLOAKED, and the monotonic incarnation id
        # that stamps checkpoint segments and spool filenames
        self.start_epoch = time.time()
        from veneur_tpu.ops import fdpass
        self._adopted_socks = {}
        for slot, fd in fdpass.parse_cloak().items():
            try:
                self._adopted_socks[slot] = fdpass.adopt_socket(fd)
            except OSError as e:
                # fail-open: a dead fd degrades that slot to a fresh
                # bind, never a crash
                log.warning("cloaked fd %d for slot %s unusable: %s",
                            fd, slot, e)
        self.restarts_adopted = 0
        # live listener sockets by cloak slot name, for handing down
        # to a replacement (fdpass.send_sockets / encode_cloak)
        self._cloak_slots: dict[str, socket.socket] = {}
        # ingest backend tier (ISSUE 17): resolved once at listener
        # startup — "uring" iff the probe shows the kernel grants the
        # multishot provided-buffer receive, else "recvmmsg"/"python".
        # A reader whose ring dies at runtime drops itself to the
        # recvmmsg tier (never exits), bumping the named fallback
        # counter; _urings tracks live rings for /debug/vars.
        self.ingest_backend: str | None = None
        self._uring_probe_err = 0
        self._backend_fallback_logged = False
        self._urings: dict[str, object] = {}
        self.incarnation = 0
        self._checkpointer = None
        if config.checkpoint_enabled():
            from veneur_tpu.ops import checkpoint as _ckpt
            self.incarnation = _ckpt.next_incarnation(
                config.tpu_checkpoint_dir)
        # recovery ids already ingested by THIS process: the
        # receiver-side dedup for retransmitted recovery wires
        # (guarded by self.lock, same critical section as the apply)
        self._recovery_seen: set[str] = set()
        # scale-out arc handoff (forward/handoff.py): (ring,
        # self_member) pending for exactly one flush, set by
        # arc_handoff(); the shipper is lazily built and reused
        self._handoff_pending = None
        self._handoff_shipper = None
        self._handoff_last: dict = {}

        # signal history plane + anomaly flight recorder: one
        # fixed-schema row of every internal signal per flush seal
        # into a bounded columnar ring (/debug/signals), with trigger
        # predicates over the rows dumping CRC-framed incident
        # bundles (/debug/flight).  The schema is derived ONCE here —
        # before any subsystem has data — so a late-built forwarder
        # can never grow the row mid-history.  This ring is the plane
        # the autopilot (ROADMAP item 4) will read.
        self.signals = None
        self.flight = None
        self._flight_record = None  # triggering interval's flush rec
        if int(getattr(config, "tpu_signal_history", 512)) > 0:
            self.signals = observe.SignalHistory(
                schema=tuple(self._signal_row()),
                capacity=int(getattr(config, "tpu_signal_history",
                                     512)),
                node=config.hostname or "",
                role="local" if self.is_local else "global")
            self.flight = observe.FlightRecorder(
                self.signals, context_fn=self._flight_context,
                directory=str(getattr(config, "tpu_flight_dir", "")),
                max_bundles=int(getattr(
                    config, "tpu_flight_max_bundles", 64)),
                max_bytes=int(getattr(
                    config, "tpu_flight_max_bytes", 67108864)),
                cooldown=parse_duration(str(getattr(
                    config, "tpu_flight_cooldown", "30s"))),
                node=config.hostname or "")
        # /debug/cluster peer-summary cache: addr -> (unix, summary)
        self._cluster_cache: dict = {}
        self._cluster_lock = threading.Lock()

        if getattr(config, "tpu_warmup", False) and \
                hasattr(self.table, "take_staged"):
            self._warmup()

    # ------------------------------------------------------------------
    # construction

    def _build_sinks(self) -> None:
        c = self.config
        if c.blackhole_sink:
            self.metric_sinks.append(BlackholeSink())
        if c.debug_flushed_metrics:
            self.metric_sinks.append(DebugSink())
        if c.datadog_api_key:
            self.metric_sinks.append(DatadogMetricSink(
                c.datadog_api_key, c.datadog_api_hostname,
                self.interval, hostname=c.hostname,
                flush_max_per_body=c.datadog_flush_max_per_body,
                metric_name_prefix_drops=tuple(
                    c.datadog_metric_name_prefix_drops),
                exclude_tags_prefix_by_prefix_metric=(
                    c.datadog_exclude_tags_prefix_by_prefix_metric)))
        if c.prometheus_repeater_address:
            self.metric_sinks.append(PrometheusRepeaterSink(
                c.prometheus_repeater_address, c.prometheus_network_type))
        if c.signalfx_api_key:
            from veneur_tpu.core.config import parse_duration
            from veneur_tpu.sinks.signalfx import SignalFxSink
            self.metric_sinks.append(SignalFxSink(
                c.signalfx_api_key, endpoint=c.signalfx_endpoint_base,
                vary_key_by=c.signalfx_vary_key_by,
                per_tag_api_keys=c.signalfx_per_tag_api_keys,
                max_per_body=c.signalfx_flush_max_per_body,
                hostname=c.hostname,
                hostname_tag=c.signalfx_hostname_tag,
                metric_name_prefix_drops=tuple(
                    c.signalfx_metric_name_prefix_drops),
                metric_tag_prefix_drops=tuple(
                    c.signalfx_metric_tag_prefix_drops),
                dynamic_per_tag_api_keys_enable=(
                    c.signalfx_dynamic_per_tag_api_keys_enable),
                dynamic_per_tag_api_keys_refresh_period=parse_duration(
                    c.signalfx_dynamic_per_tag_api_keys_refresh_period
                    or "10m"),
                endpoint_api=c.signalfx_endpoint_api))
        if c.newrelic_insert_key:
            from veneur_tpu.sinks.newrelic import (NewRelicMetricSink,
                                                   NewRelicSpanSink)
            common = {k: v for k, _, v in
                      (t.partition(":") for t in c.newrelic_common_tags)}
            self.metric_sinks.append(NewRelicMetricSink(
                c.newrelic_insert_key,
                endpoint=c.newrelic_metric_endpoint,
                common_attributes=common, interval=self.interval,
                account_id=c.newrelic_account_id,
                region=c.newrelic_region,
                event_type=c.newrelic_event_type,
                service_check_event_type=(
                    c.newrelic_service_check_event_type)))
            self.span_sinks.append(NewRelicSpanSink(
                c.newrelic_insert_key,
                endpoint=c.newrelic_trace_endpoint,
                trace_observer_url=c.newrelic_trace_observer_url,
                region=c.newrelic_region))
        if c.kafka_broker:
            from veneur_tpu.sinks.kafka import (KafkaMetricSink,
                                                KafkaSpanSink)
            self.metric_sinks.append(KafkaMetricSink(
                c.kafka_broker, check_topic=c.kafka_check_topic,
                event_topic=c.kafka_event_topic,
                metric_topic=c.kafka_metric_topic,
                require_acks=c.kafka_metric_require_acks,
                partitioner=c.kafka_partitioner,
                retry_max=c.kafka_retry_max,
                buffer_bytes=c.kafka_metric_buffer_bytes,
                buffer_messages=c.kafka_metric_buffer_messages))
            if c.kafka_span_topic:
                self.span_sinks.append(KafkaSpanSink(
                    c.kafka_broker, span_topic=c.kafka_span_topic,
                    serialization=c.kafka_span_serialization_format,
                    require_acks=c.kafka_span_require_acks,
                    partitioner=c.kafka_partitioner,
                    retry_max=c.kafka_retry_max,
                    buffer_bytes=c.kafka_span_buffer_bytes,
                    buffer_messages=c.kafka_span_buffer_mesages,
                    sample_rate_percent=(
                        c.kafka_span_sample_rate_percent),
                    sample_tag=c.kafka_span_sample_tag))
        if c.datadog_trace_api_address:
            from veneur_tpu.sinks.datadog import DatadogSpanSink
            self.span_sinks.append(DatadogSpanSink(
                c.datadog_trace_api_address, hostname=c.hostname,
                buffer_size=c.datadog_span_buffer_size))
        if c.splunk_hec_address and c.splunk_hec_token:
            from veneur_tpu.core.config import parse_duration
            from veneur_tpu.sinks.splunk import SplunkSpanSink

            def _dur(text: str) -> float:
                return parse_duration(text) if text else 0.0

            self.span_sinks.append(SplunkSpanSink(
                c.splunk_hec_address, c.splunk_hec_token,
                sample_rate=c.splunk_span_sample_rate,
                hostname=c.hostname,
                batch_size=c.splunk_hec_batch_size,
                submission_workers=c.splunk_hec_submission_workers,
                send_timeout=_dur(c.splunk_hec_send_timeout),
                ingest_timeout=_dur(c.splunk_hec_ingest_timeout),
                max_connection_lifetime=_dur(
                    c.splunk_hec_max_connection_lifetime),
                connection_lifetime_jitter=_dur(
                    c.splunk_hec_connection_lifetime_jitter),
                tls_validate_hostname=(
                    c.splunk_hec_tls_validate_hostname)))
        if c.xray_address:
            from veneur_tpu.sinks.xray import XRaySpanSink
            self.span_sinks.append(XRaySpanSink(
                c.xray_address,
                sample_percentage=c.xray_sample_percentage,
                annotation_tags=tuple(c.xray_annotation_tags),
                # server-wide tags ride in segment metadata
                # (reference server.go passes Config.Tags as the
                # sink's commonTags)
                common_tags=tags_to_dict(c.tags)))
        if c.lightstep_access_token:
            from veneur_tpu.core.config import parse_duration
            from veneur_tpu.sinks.lightstep import LightStepSpanSink
            self.span_sinks.append(LightStepSpanSink(
                c.lightstep_access_token,
                collector_host=c.lightstep_collector_host,
                maximum_spans=c.lightstep_maximum_spans,
                num_clients=c.lightstep_num_clients,
                reconnect_period=parse_duration(
                    c.lightstep_reconnect_period or "5m")))
        if c.falconer_address:
            from veneur_tpu.sinks.grpsink import FalconerSpanSink
            self.span_sinks.append(FalconerSpanSink(c.falconer_address))
        if c.flush_file:
            self.plugins.append(LocalFilePlugin(
                c.flush_file, c.hostname,
                fmt=c.flush_file_format, interval=self.interval))
        if c.aws_s3_bucket:
            from veneur_tpu.sinks.s3 import S3Plugin
            self.plugins.append(S3Plugin(
                c.aws_s3_bucket, hostname=c.hostname,
                region=c.aws_region, endpoint=c.aws_s3_endpoint,
                access_key=c.aws_access_key_id,
                secret_key=c.aws_secret_access_key,
                fmt=c.flush_file_format, interval=self.interval))
        if c.sentry_dsn:
            # SDK-free DSN client (core/sentry.py), matching the
            # reference's init-if-configured (server.go:357-365) +
            # error-level log hook attached once (server.go:391-403)
            from veneur_tpu.core.sentry import (
                SentryClient, SentryLogHandler)
            self.sentry = SentryClient(c.sentry_dsn,
                                       server_name=c.hostname)
            # latest init wins, like the SDK's global hub: drop any
            # handler a previous Server attached (it points at a dead
            # client), then add ours; shutdown() removes it again
            root = logging.getLogger("veneur_tpu")
            for h in list(root.handlers):
                if isinstance(h, SentryLogHandler):
                    root.removeHandler(h)
            self._sentry_handler = SentryLogHandler(self.sentry)
            root.addHandler(self._sentry_handler)

    def _build_tls(self):
        """TLS (optionally mutual) for the TCP statsd listener
        (reference server.go:484-518: tls_key + tls_certificate enable
        TLS; tls_authority_certificate additionally requires client
        certs)."""
        c = self.config
        if not (c.tls_key and c.tls_certificate):
            if c.tls_authority_certificate:
                raise ValueError(
                    "tls_authority_certificate requires tls_key and "
                    "tls_certificate")
            return None
        import ssl
        import tempfile

        def _matfile(value: str) -> str:
            # the reference's config carries inline PEM strings
            # (example.yaml tls_key); file paths also accepted.  Inline
            # material is spilled 0600 and unlinked at exit so private
            # keys never persist in /tmp
            if _is_inline_pem(value):
                import atexit
                f = tempfile.NamedTemporaryFile(
                    mode="w", suffix=".pem", delete=False)
                os.chmod(f.name, 0o600)
                f.write(value)
                f.close()
                atexit.register(
                    lambda p=f.name: os.path.exists(p) and
                    os.unlink(p))
                return f.name
            return value

        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(certfile=_matfile(c.tls_certificate),
                            keyfile=_matfile(c.tls_key))
        if c.tls_authority_certificate:
            ctx.load_verify_locations(
                cafile=_matfile(c.tls_authority_certificate))
            ctx.verify_mode = ssl.CERT_REQUIRED
        return ctx

    # ------------------------------------------------------------------
    # ingest

    def bump(self, key: str, n: int = 1) -> None:
        with self._stats_lock:
            self.stats[key] = self.stats.get(key, 0) + n

    def _sample_kernel_drops(self) -> int:
        """Per-flush delta of kernel-side UDP receive drops across
        this server's reader sockets (the ``drops`` column of
        /proc/net/udp{,6}).  These packets were lost BEFORE the
        process saw them — observed-unattributed in the interval
        record, cumulative in stats[socket_kernel_drops], and a
        saturation input to the overload pressure signal."""
        from veneur_tpu.core import overload as _ovl
        try:
            cur = _ovl.read_kernel_drops(self._sockets)
        except Exception:
            return 0
        delta = 0
        for inode, drops in cur.items():
            delta += max(
                0, drops - self._kernel_drops_last.get(inode, 0))
        self._kernel_drops_last = cur
        if delta:
            self.bump("socket_kernel_drops", delta)
        # uring buffer-pool exhaustion is the same failure at a new
        # site — a packet arrived, no buffer could land it — so its
        # delta rides the identical pressure input (cumulative in
        # stats[socket_uring_enobufs], delta into overload.tick)
        with self._stats_lock:
            eb = self.stats.get("socket_uring_enobufs", 0)
        eb_delta = max(0, eb - self._uring_enobufs_last)
        self._uring_enobufs_last = eb
        return delta + eb_delta

    def handle_packet(self, data: bytes) -> None:
        """Parse one datagram (possibly multi-line) into the table
        (reference server.go:1253 processMetricPacket -> :1103
        HandleMetricPacket)."""
        if len(data) > self.config.metric_max_length:
            self.bump("packet_errors")
            return
        self.bump("packets_received")
        errors = processed = dropped = 0
        # parse every line lock-free first, then take ONE self.lock
        # round for the whole datagram — multi-line packets previously
        # paid a lock acquisition per sample (they already tallied
        # stats once per packet)
        samples: list = []
        events: list = []
        checks: list = []
        for line in dsd.split_packet(data):
            try:
                parsed = dsd.parse_line(line)
            except dsd.ParseError:
                errors += 1
                continue
            if isinstance(parsed, dsd.Sample):
                samples.append(parsed)
            elif isinstance(parsed, dsd.Event):
                events.append(parsed)
            elif isinstance(parsed, dsd.ServiceCheck):
                # service checks ingest as STATUS samples but never
                # count as dropped (matching ingest_parsed)
                checks.append(parsed)
        work = None
        n_status = 0
        shed = 0
        shed_by: dict = {}
        # overload admission gate: one boolean when the subsystem is
        # idle; the per-sample check only runs with tenant budgets
        # configured or pressure engaged
        adm = (self.overload is not None
               and self.overload.admission_active)
        if samples or events or checks:
            with self.lock:
                for s in samples:
                    processed += 1
                    if s.type == dsd.STATUS:
                        n_status += 1
                        self.table.ingest(s)
                        continue
                    if adm:
                        ok, tenant, reason = \
                            self.overload.admit_sample(s, self.table)
                        if not ok:
                            shed += 1
                            k = (tenant, reason)
                            shed_by[k] = shed_by.get(k, 0) + 1
                            continue
                    if not self.table.ingest(s):
                        dropped += 1
                for chk in checks:
                    processed += 1
                    n_status += 1
                    self.table.ingest(dsd.Sample(
                        name=chk.name, type=dsd.STATUS,
                        value=float(chk.status), tags=chk.tags,
                        message=chk.message))
                if events:
                    self.events.extend(events)
                if checks:
                    self.checks.extend(checks)
                # ledger credit in the same critical section as the
                # table counters, so an interval close (begin_swap)
                # can never split a packet's table bumps from its
                # ledger entry
                self.ledger.ingest(
                    "dogstatsd", processed=processed,
                    staged=processed - dropped - n_status - shed,
                    overflow=dropped, status=n_status, shed=shed,
                    parse_errors=errors)
                if shed:
                    self.ledger.credit_shed(shed_by)
                work = self._maybe_device_step_locked()
        elif errors:
            self.ledger.ingest("dogstatsd", parse_errors=errors)
        self._apply_staged(work)
        # one stats-lock round per packet, not per line
        if errors:
            self.bump("packet_errors", errors)
        if processed:
            self.bump("metrics_processed", processed)
        if dropped:
            self.bump("metrics_dropped", dropped)
        if shed:
            self.bump("metrics_shed", shed)

    def ingest_parsed(self, parsed, bump: bool = True) -> tuple[int, int]:
        """Ingest one parsed object; returns (processed, dropped) so
        batch callers can tally stats once per batch."""
        processed = dropped = shed = 0
        if isinstance(parsed, dsd.Sample):
            adm = (self.overload is not None
                   and self.overload.admission_active)
            with self.lock:
                if parsed.type == dsd.STATUS:
                    ok = True
                    self.table.ingest(parsed)
                    self.ledger.ingest("dogstatsd", processed=1,
                                       status=1)
                else:
                    ok = True
                    if adm:
                        ok_adm, tenant, reason = \
                            self.overload.admit_sample(
                                parsed, self.table)
                        if not ok_adm:
                            shed = 1
                            self.ledger.ingest("dogstatsd",
                                               processed=1, shed=1)
                            self.ledger.credit_shed(
                                {(tenant, reason): 1})
                    if not shed:
                        ok = self.table.ingest(parsed)
                        self.ledger.ingest(
                            "dogstatsd", processed=1,
                            staged=1 if ok else 0,
                            overflow=0 if ok else 1)
                work = self._maybe_device_step_locked()
            self._apply_staged(work)
            processed = 1
            dropped = 0 if ok else 1
        elif isinstance(parsed, dsd.Event):
            with self.lock:
                self.events.append(parsed)
        elif isinstance(parsed, dsd.ServiceCheck):
            sample = dsd.Sample(
                name=parsed.name, type=dsd.STATUS,
                value=float(parsed.status), tags=parsed.tags,
                message=parsed.message)
            with self.lock:
                self.table.ingest(sample)
                self.checks.append(parsed)
                self.ledger.ingest("dogstatsd", processed=1, status=1)
            processed = 1
        if bump:
            if processed:
                self.bump("metrics_processed", processed)
            if dropped:
                self.bump("metrics_dropped", dropped)
            if shed:
                self.bump("metrics_shed", shed)
        return processed, dropped

    def import_span(self, protocol: str, trace_id: int, span_id: int):
        """This tier's half of a cross-process flush trace, opened at
        an import handler's entry and closed at its exit
        (observe.ImportSpan): the ``import`` span and its steps, under
        the remote ``forward.send`` span when the wire carried its
        ids, and their durations into the next flush record."""
        if not getattr(self.config, "tpu_trace_propagation", True):
            trace_id = 0
        return ImportSpan(self.trace_client, self.trace_index,
                          self._note_import, protocol, trace_id,
                          span_id)

    def _note_import(self, stages: dict[str, int]) -> None:
        with self._stats_lock:
            self._import_wires += 1
            for name, ns in stages.items():
                self._import_stages[name] = (
                    self._import_stages.get(name, 0) + ns)

    def _take_imports(self, record) -> None:
        """Move what the import handlers cost since the last cycle
        into ``record``; called in the swap's lock round, as the
        ledger's interval close is."""
        with self._stats_lock:
            stages, self._import_stages = self._import_stages, {}
            record.imports, self._import_wires = self._import_wires, 0
        record.stages.update(stages)

    def _maybe_device_step_locked(self):
        """Mid-interval device step once enough samples are staged
        (bounds host staging memory; caller holds self.lock).

        Pipelined mode returns the detached staged work — the caller
        MUST hand it to ``_apply_staged`` after releasing self.lock so
        the XLA dispatch happens outside the ingest critical section.
        Serial mode (VENEUR_TPU_PIPELINE=0, or a table without the
        staged-work API) dispatches inline and returns None."""
        if self.table.staged() < self.config.tpu_stage_flush_samples:
            return None
        if self.pipeline:
            return self.table.take_staged()
        self.table.device_step()
        return None

    def _apply_staged(self, work) -> None:
        """Dispatch detached staged work outside the ingest lock (the
        flush's complete_swap waits for every pending apply, so no
        sample is lost or double-counted across the swap)."""
        if work is not None:
            self.table.apply_staged(work)

    def _warmup(self) -> None:
        """Compile the canonical kernel shapes before traffic arrives
        (VENEUR_TPU_WARMUP): a scratch table with the server's exact
        geometry takes one sample of each kind through a device step,
        swap, and flush readout, so the first real interval dispatches
        from the jit (or persistent compilation) cache instead of
        eating the cold compiles.  The jitted kernels are module-level
        objects, so warming them through the scratch table warms the
        live one."""
        t0 = time.monotonic()
        scratch = MetricTable(TableConfig(
            counter_rows=self.config.tpu_counter_rows,
            gauge_rows=self.config.tpu_gauge_rows,
            histo_rows=self.config.tpu_histo_rows,
            set_rows=self.config.tpu_set_rows,
            compression=self.config.tpu_compression,
            histo_slots=self.config.tpu_histo_slots))
        for s in (dsd.Sample("veneur.warmup", dsd.COUNTER, 1.0),
                  dsd.Sample("veneur.warmup", dsd.GAUGE, 1.0),
                  dsd.Sample("veneur.warmup", dsd.HISTOGRAM, 1.0),
                  dsd.Sample("veneur.warmup", dsd.TIMER, 1.0),
                  dsd.Sample("veneur.warmup", dsd.SET, "w")):
            scratch.ingest(s)
        snap = scratch.swap()
        self.flusher.flush(snap)
        snap.release()
        log.info("kernel warmup finished in %.2fs",
                 time.monotonic() - t0)

    # ------------------------------------------------------------------
    # listeners

    def _crashguard(self, fn):
        """Wrap a thread target so a crashing exception is reported to
        Sentry (with stack, flushed within the timeout) before it
        propagates — the reference defers ConsumePanic in every
        long-lived goroutine (server.go:434,897,994,1040,1376)."""
        from veneur_tpu.core import sentry as _sentry

        def run(*a, **kw):
            try:
                return fn(*a, **kw)
            except BaseException as e:
                # consume_panic re-raises; threading.excepthook then
                # prints the traceback (the analog of panic's stack
                # dump).  Not logged through the veneur_tpu logger —
                # the SentryLogHandler would double-report it.
                _sentry.consume_panic(
                    self.sentry, self.flusher.hostname, e)
        return run

    def start(self) -> None:
        if not self._gc_hooked:
            # collector pauses into the flush record (observe/
            # gcpause.py): one hook a process, the last shutdown
            # takes it off
            self._gc_hooked = True
            PAUSES.install()
        for ai, addr in enumerate(self.config.statsd_listen_addresses):
            self._start_statsd(addr, ai)
        if self.config.http_address:
            self._start_http(self.config.http_address)
        for addr in self.config.grpc_listen_addresses:
            self._start_grpc(addr)
        for addr in self.config.ssf_listen_addresses:
            self._start_ssf(addr)
        self.span_worker.start()
        for s in self.span_sinks:
            s.start()
        if self.config.enable_profiling:
            self._start_profiling()
        t = threading.Thread(target=self._crashguard(self._flush_loop),
                             daemon=True,
                             name="flush")
        t.start()
        self._threads.append(t)
        if self.config.flush_watchdog_missed_flushes > 0:
            t = threading.Thread(target=self._crashguard(self._watchdog),
                                 daemon=True,
                                 name="watchdog")
            t.start()
            self._threads.append(t)
        for s in self.metric_sinks:
            s.start()
        # cloak slots nobody claimed (listener-count/config drift
        # between incarnations): close them so the fds don't leak —
        # loudly, because an unclaimed slot means kernel-queued
        # packets on that socket are now orphaned
        for name, sock in self._adopted_socks.items():
            log.warning("unclaimed cloaked listener %r; closing it",
                        name)
            try:
                sock.close()
            except OSError:
                pass
        self._adopted_socks.clear()
        # crash-riding: start the staged-plane checkpointer, then
        # replay any predecessor's surviving segments through the
        # import path (recovery runs AFTER listeners so a forwarded
        # recovery wire can stitch into live telemetry immediately)
        if (self.config.checkpoint_enabled()
                and hasattr(self.table, "checkpoint_capture")):
            from veneur_tpu.ops.checkpoint import Checkpointer
            self._checkpointer = Checkpointer(
                self, self.config.tpu_checkpoint_dir,
                self.config.checkpoint_interval_seconds(),
                self.incarnation)
            self._checkpointer.start()
            try:
                self._recover_from_checkpoints()
            except Exception:
                self.bump("recovery_errors")
                log.exception("checkpoint recovery failed")

    def _resolve_ingest_backend(self) -> str:
        """Resolve tpu_ingest_backend ("auto" probes the kernel) to
        the tier the readers will actually run: uring / recvmmsg /
        python.  Cached — the answer cannot change in-process."""
        if self.ingest_backend is not None:
            return self.ingest_backend
        from veneur_tpu import native as native_mod
        from veneur_tpu.native import uring as uring_mod
        mode = getattr(self.config, "tpu_ingest_backend", "auto")
        lib = native_mod.load()
        if lib is None or mode == "python":
            self.ingest_backend = "python"
            return self.ingest_backend
        if mode == "recvmmsg":
            self.ingest_backend = "recvmmsg"
            return self.ingest_backend
        err = uring_mod.probe(lib)
        self._uring_probe_err = err
        if err == 0:
            self.ingest_backend = "uring"
        else:
            # auto or explicit uring on a kernel that refuses: land
            # on the recvmmsg tier with the named counter — an
            # explicit request degrading silently is how ENOSYS
            # becomes a 3am packet-loss mystery
            self.ingest_backend = "recvmmsg"
            self._note_backend_fallback(
                uring_mod.probe_reason(err),
                "startup probe refused (%s)" % os.strerror(-err))
        return self.ingest_backend

    def _note_backend_fallback(self, reason: str, detail: str) -> None:
        """Count (by reason) and log-once a uring->recvmmsg drop."""
        self.bump("socket_backend_fallback")
        self.bump(f"socket_backend_fallback_{reason}")
        if not self._backend_fallback_logged:
            self._backend_fallback_logged = True
            log.warning("io_uring ingest unavailable: %s; readers "
                        "run the recvmmsg drain tier", detail)

    def _pin_reader_core(self, index: int) -> None:
        """Pin this reader thread to one CPU so its ring, buffer pool
        and parse scratch stay core-local (tpu_reader_pin_cores:
        "auto" = reader i -> core i%N when cores >= readers, "off",
        or an explicit comma list)."""
        pin = getattr(self.config, "tpu_reader_pin_cores", "auto")
        if pin == "off" or not hasattr(os, "sched_setaffinity"):
            return
        try:
            avail = sorted(os.sched_getaffinity(0))
            if pin == "auto":
                n = max(1, self.config.num_readers)
                if len(avail) < n:
                    return  # oversubscribed: pinning would stack
                core = avail[index % len(avail)]
            else:
                cores = [int(c) for c in pin.split(",") if c.strip()]
                core = cores[index % len(cores)]
                if core not in avail:
                    return
            os.sched_setaffinity(0, {core})
        except (OSError, ValueError):
            pass  # pinning is an optimization, never a failure

    def _start_statsd(self, addr: str, index: int = 0) -> None:
        scheme, host, port, path = parse_addr(addr)
        if scheme == "udp":
            # resolve (and probe, under "auto") the drain tier before
            # the readers spawn, so /debug/vars never shows None and
            # a probe-refused fallback is counted exactly once
            self._resolve_ingest_backend()
            n = max(1, self.config.num_readers)
            for i in range(n):
                slot = f"statsd.udp.{index}.{i}"
                sock = self._adopted_socks.pop(slot, None)
                if sock is not None:
                    # einhorn-style fd adoption: the predecessor (or
                    # a supervising master) cloaked this bound socket
                    # into VENEUR_TPU_SOCK_CLOAKED, so datagrams
                    # queued in the kernel across the restart are
                    # read by this process, never dropped at the
                    # kernel boundary
                    self.restarts_adopted += 1
                    self.bump("listener_fds_adopted")
                else:
                    sock = socket.socket(socket.AF_INET,
                                         socket.SOCK_DGRAM)
                    if n > 1:
                        sock.setsockopt(socket.SOL_SOCKET,
                                        socket.SO_REUSEPORT, 1)
                    sock.setsockopt(socket.SOL_SOCKET,
                                    socket.SO_RCVBUF,
                                    self.config.read_buffer_size_bytes)
                    sock.bind((host, port))
                # periodic wake: SO_REUSEPORT hashes the shutdown
                # wake datagram to ONE group member, so a timeout is
                # the guarantee every reader re-checks _shutdown
                sock.settimeout(1.0)
                port = sock.getsockname()[1]  # resolve port 0 once
                self._sockets.append(sock)
                self._cloak_slots[slot] = sock
                t = threading.Thread(target=self._crashguard(self._udp_reader),
                                     args=(sock, "dogstatsd-udp", i),
                                     daemon=True,
                                     name=f"udp-reader-{i}")
                t.start()
                self._threads.append(t)
            self.statsd_ports.append(port)
        elif scheme == "tcp":
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            sock.bind((host, port))
            sock.listen(128)
            if self._tls_context is not None:
                # TLS termination on the listener; per-connection
                # handshakes happen in the acceptor thread (reference
                # server.go:484-518 TLS config + networking.go:104)
                sock = self._tls_context.wrap_socket(
                    sock, server_side=True,
                    do_handshake_on_connect=False)
            self._sockets.append(sock)
            self.statsd_ports.append(sock.getsockname()[1])
            t = threading.Thread(target=self._crashguard(self._tcp_acceptor),
                                 args=(sock,), daemon=True,
                                 name="tcp-acceptor")
            t.start()
            self._threads.append(t)
        elif scheme == "unix":
            self._acquire_socket_lock(path)
            if os.path.exists(path):
                os.unlink(path)
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_DGRAM)
            sock.bind(path)
            self._sockets.append(sock)
            t = threading.Thread(target=self._crashguard(self._udp_reader),
                                 args=(sock, "dogstatsd-unixgram"),
                                 daemon=True,
                                 name="unixgram-reader")
            t.start()
            self._threads.append(t)
        else:
            raise ValueError(f"unsupported statsd address {addr!r}")

    def _acquire_socket_lock(self, path: str) -> None:
        """Single-owner flock on ``<path>.lock`` before binding a unix
        socket (reference networking.go:362 acquireLockForSocket):
        without it a second instance silently unlinks-and-rebinds the
        path and the two split the datagram stream.  The fd is held
        for the server's lifetime and released at shutdown."""
        import fcntl
        lockname = path + ".lock"
        fd = os.open(lockname, os.O_CREAT | os.O_RDWR, 0o644)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            os.close(fd)
            raise RuntimeError(
                f"lock file {lockname!r} is held by another process "
                f"already; refusing to take over {path!r}")
        self._socket_locks.append((lockname, fd))

    def _forward_grpc_credentials(self):
        """Channel credentials for dialing a TLS gRPC global
        (forward_grpc_tls / forward_grpc_tls_ca; the reference always
        dials insecure, server.go:983 — this is the client half its
        TLS-capable listener never got)."""
        c = self.config
        if not (c.forward_grpc_tls or c.forward_grpc_tls_ca):
            return None
        import grpc
        root = (_pem_bytes(c.forward_grpc_tls_ca)
                if c.forward_grpc_tls_ca else None)
        key = cert = None
        if c.tls_key and c.tls_certificate:
            key = _pem_bytes(c.tls_key)
            cert = _pem_bytes(c.tls_certificate)
        return grpc.ssl_channel_credentials(
            root_certificates=root, private_key=key,
            certificate_chain=cert)

    def _grpc_credentials(self):
        """grpc server credentials from the config's TLS material
        (the reference serves gRPC under the same tlsConfig as TCP
        statsd, networking.go:333-340; client CA => mutual auth)."""
        c = self.config
        if not (c.tls_key and c.tls_certificate):
            return None
        import grpc

        root = (_pem_bytes(c.tls_authority_certificate)
                if c.tls_authority_certificate else None)
        return grpc.ssl_server_credentials(
            [(_pem_bytes(c.tls_key), _pem_bytes(c.tls_certificate))],
            root_certificates=root,
            require_client_auth=root is not None)

    def _start_grpc(self, addr: str) -> None:
        """gRPC Forward import listener — the importsrv role
        (reference networking.go:295 StartGRPC, importsrv/server.go);
        TLS-aware under the server's TLS config."""
        from veneur_tpu.forward.grpc_forward import ImportServer
        scheme, host, port, _ = parse_addr(addr)
        if scheme != "tcp":
            raise ValueError(f"grpc listener must be tcp://: {addr!r}")
        srv = ImportServer(self, f"{host}:{port}",
                           credentials=self._grpc_credentials())
        srv.start()
        self.grpc_servers.append(srv)
        self.grpc_ports.append(srv.port)

    def _start_ssf(self, addr: str) -> None:
        """SSF listeners (reference networking.go:205 StartSSF):
        udp:// datagrams carry one bare protobuf SSFSpan; unix://
        streams carry framed spans."""
        scheme, host, port, path = parse_addr(addr)
        if scheme == "udp":
            sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
            sock.bind((host, port))
            self._sockets.append(sock)
            self.ssf_ports.append(sock.getsockname()[1])
            t = threading.Thread(target=self._crashguard(self._ssf_packet_reader),
                                 args=(sock,), daemon=True,
                                 name="ssf-udp")
            t.start()
            self._threads.append(t)
        elif scheme == "unix":
            self._acquire_socket_lock(path)
            if os.path.exists(path):
                os.unlink(path)
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.bind(path)
            sock.listen(64)
            self._sockets.append(sock)
            t = threading.Thread(target=self._crashguard(self._ssf_stream_acceptor),
                                 args=(sock,), daemon=True,
                                 name="ssf-unix")
            t.start()
            self._threads.append(t)
        else:
            raise ValueError(f"unsupported ssf address {addr!r}")

    def _ssf_packet_reader(self, sock: socket.socket) -> None:
        """UDP SSF: one span per datagram (reference server.go:1300
        ReadSSFPacketSocket)."""
        from veneur_tpu.protocol import wire
        bufsize = min(self.config.trace_max_length_bytes, 65536)
        while not self._shutdown.is_set():
            try:
                data = sock.recv(bufsize)
            except TimeoutError:
                continue  # periodic shutdown check (see settimeout)
            except OSError:
                return
            if not data:
                continue
            try:
                span = wire.parse_ssf(data)
            except wire.SSFParseError:
                self.bump("ssf_errors")
                continue
            self.bump("received_ssf-udp")
            self.handle_ssf(span)

    def _ssf_stream_acceptor(self, sock: socket.socket) -> None:
        while not self._shutdown.is_set():
            try:
                conn, _ = sock.accept()
            except OSError:
                return
            t = threading.Thread(target=self._ssf_stream_conn,
                                 args=(conn,), daemon=True)
            t.start()

    def _ssf_stream_conn(self, conn: socket.socket) -> None:
        """Framed SSF stream (reference server.go:1335
        ReadSSFStreamSocket): framing errors drop the connection, bad
        payloads only drop the one span."""
        from veneur_tpu.protocol import wire
        f = conn.makefile("rb")
        try:
            while not self._shutdown.is_set():
                try:
                    span = wire.read_ssf(f)
                except wire.SSFParseError:
                    self.bump("ssf_errors")
                    continue
                except wire.FramingError:
                    self.bump("ssf_errors")
                    return
                if span is None:
                    return
                self.bump("received_ssf-unix")
                self.handle_ssf(span)
        except OSError:
            pass
        finally:
            conn.close()

    def handle_ssf(self, span) -> None:
        """Enqueue one span (reference server.go:1190 handleSSF);
        per-protocol receive counters are bumped at the listeners."""
        if self.config.debug_ingested_spans:
            log.debug("ingested span service=%s name=%s trace=%s",
                      span.service, span.name, span.trace_id)
        self.span_worker.submit(span)

    def _udp_reader(self, sock: socket.socket,
                    proto: str = "dogstatsd-udp",
                    reader_index: int = 0) -> None:
        """Blocking datagram read loop (reference server.go:1240
        ReadMetricSocket).

        With the native columnar parser available, each reader drains
        the socket into a packet batch (block for the first datagram,
        then non-blocking sweep) and pushes the whole batch through one
        parse + one lock acquisition — the TPU-shaped replacement for
        the reference's per-packet goroutine hop (server.go:1152).

        On the "uring" backend tier the loop above is replaced
        entirely: a multishot io_uring receive completes into a
        kernel-provided buffer pool and the fused parse reads the
        datagrams IN PLACE there (no recv syscall, no join/copy).  A
        ring that dies at runtime drops this reader HERE, to the
        recvmmsg tier below — the reader never exits over it.
        """
        self._pin_reader_core(reader_index)
        bufsize = self.config.metric_max_length + 1
        # one parser per reader thread (scratch buffers are reused
        # across calls, so sharing would race)
        parser = columnar.ColumnarParser()
        if not parser.available:
            parser = None
        backend = self._resolve_ingest_backend()
        # multi-reader fused ingest: a per-reader shard runs the fused
        # parse+probe+combine C pass lock-free against private scratch
        # (index probes are RCU-safe), holding self.lock only for the
        # miss-resolve + O(touched-rows) merge.  Single-reader servers
        # keep the whole-pass-under-lock path (nothing contends) —
        # except on the uring tier, whose zero-copy parse IS the
        # shard pass, so every uring reader gets one.
        want_shard = (self.config.num_readers > 1 and
                      getattr(self.config, "tpu_multi_reader_fused",
                              True))
        uring_ok = (backend == "uring" and proto == "dogstatsd-udp"
                    and sock.family == socket.AF_INET)
        shard = None
        if parser is not None and (want_shard or uring_ok):
            make = getattr(self.table, "make_reader_shard", None)
            if make is not None:
                shard = make()
        if uring_ok and shard is not None:
            if self._uring_reader(sock, proto, parser, shard):
                return  # clean shutdown on the ring
            # ring refused or died: fall through to recvmmsg, with
            # the shard only if the multi-reader path wants one
            if not want_shard:
                shard = None
        max_batch = self.config.reader_batch_packets
        # native bulk drain: one recvmmsg syscall per batch instead of
        # one recv + bytes object per packet (see vtpu_recv_drain);
        # the first read stays blocking in Python so shutdown and
        # socket errors surface normally
        from veneur_tpu import native as native_mod
        lib = native_mod.load() if parser is not None else None
        drain_buf = None
        has_drain = lib is not None and hasattr(lib, "vtpu_recv_drain")
        if has_drain:
            import ctypes as _ct
            drain_cap = max(1, min(max_batch, 512)) * (bufsize + 1)
            drain_buf = np.empty(drain_cap, np.uint8)
            drain_ptr = drain_buf.ctypes.data_as(
                _ct.POINTER(_ct.c_uint8))
            drain_n = _ct.c_int32(0)
            drain_over = _ct.c_int32(0)
        while not self._shutdown.is_set():
            try:
                data = sock.recv(bufsize)
            except TimeoutError:
                continue  # periodic shutdown check (see settimeout)
            except OSError:
                return
            if not data:
                continue
            if parser is None:
                self.handle_packet(data)
                self.bump(f"received_{proto}")
                continue
            batch = [data]
            n_pkts = 1
            drained = None
            if drain_buf is not None:
                # max_len = metric_max_length: a datagram one byte
                # over must MSG_TRUNC so the drain rejects it, as the
                # blocking path's length check would
                nbytes = lib.vtpu_recv_drain(
                    sock.fileno(), drain_ptr, drain_buf.nbytes,
                    min(max_batch - 1, 512), bufsize - 1, drain_n,
                    drain_over)
                if nbytes:
                    drained = drain_buf[:nbytes].tobytes()
                    n_pkts += int(drain_n.value)
                if drain_over.value:
                    # received but rejected whole (MSG_TRUNC: parsing
                    # the clipped tail could yield a valid WRONG
                    # value): both counters move as on the blocking
                    # path, and the ledger attributes the packet as a
                    # parse error so truncation is never silent
                    n_over = int(drain_over.value)
                    n_pkts += n_over
                    self.bump("packet_errors", n_over)
                    self.ledger.ingest("dogstatsd",
                                       parse_errors=n_over)
            # (no native drain — library without the symbol, e.g. a
            # stale cached .so: packets process one per loop; a
            # MSG_DONTWAIT sweep would BLOCK on the timeout socket,
            # CPython retries flagged recvs until the timeout)
            t0 = time.monotonic_ns()
            with annotate("ingest.batch"):
                processed = self.handle_packet_batch(
                    batch, parser, drained=drained,
                    drained_pkts=int(drain_n.value) if drained else 0,
                    shard=shard)
            self.device_costs.add_reader_batch(
                threading.current_thread().name, n_pkts, processed,
                time.monotonic_ns() - t0, fused=shard is not None)
            self.bump(f"received_{proto}", n_pkts)

    def _uring_reader(self, sock: socket.socket, proto: str,
                      parser, shard) -> bool:
        """io_uring multishot drain tier: returns True on clean
        shutdown, False when the ring could not be built or died at
        runtime (the caller continues this reader on the recvmmsg
        tier — a backend failure must never cost a reader).

        Steady state is zero syscalls per packet and zero copies
        before parse: the kernel lands datagrams in the ring's buffer
        pool while the previous batch parses, and the fused pass
        reads them in place (ReaderShard.parse_ring).  When overload
        admission is active the ring degrades to a copy-out drain
        through handle_packet_batch, whose columnar branch carries
        the vectorized admission check.
        """
        from veneur_tpu import native as native_mod
        from veneur_tpu.native import uring as uring_mod
        lib = native_mod.load()
        c = self.config
        bufsize = c.metric_max_length + 1
        try:
            ring = uring_mod.UringReader(
                lib, sock.fileno(),
                int(getattr(c, "tpu_uring_buffers", 2048)), bufsize)
        except (uring_mod.UringError, ValueError) as e:
            reason = getattr(e, "reason", "error")
            self._note_backend_fallback(
                reason, "ring setup failed (%s)" % e)
            return False
        name = threading.current_thread().name
        self._urings[name] = ring
        drain_buf = np.empty(
            min(ring.buf_count, 512) * (bufsize + 1), np.uint8)
        # cap each walk at half the pool: the zero-copy pass holds
        # its buffers through commit, and a round that held them all
        # would starve the multishot into an ENOBUFS termination on
        # every cycle.  Half in flight, half landing keeps the recv
        # armed continuously.
        max_msgs = max(1, ring.buf_count // 2)
        # adaptive batch pooling: under load, ask the kernel to
        # accumulate completions before waking us (one walk over
        # hundreds of datagrams instead of a wakeup per arrival);
        # at a trickle, wake per packet so latency stays flat.  The
        # previous round's size is the load signal.
        wait_batch = 1
        max_batch = min(max_msgs, 512)
        try:
            while not self._shutdown.is_set():
                try:
                    adm = (self.overload is not None
                           and self.overload.admission_active)
                    if adm:
                        # admission needs a contiguous buffer for the
                        # columnar shed pass: one copy, same backend
                        nbytes, n_msgs, n_over, n_eb = ring.drain(
                            drain_buf, min(max_msgs, 512),
                            bufsize - 1,
                            50 if wait_batch > 1 else 1000,
                            wait_batch)
                        self._uring_batch_stats(proto, n_over, n_eb)
                        wait_batch = min(max_batch,
                                         max(1, n_msgs // 2))
                        if n_msgs == 0:
                            continue
                        t0 = time.monotonic_ns()
                        with annotate("ingest.batch"):
                            processed = self.handle_packet_batch(
                                [], parser,
                                drained=drain_buf[:nbytes].tobytes(),
                                drained_pkts=n_msgs, shard=None)
                        self.device_costs.add_reader_batch(
                            name, n_msgs, processed,
                            time.monotonic_ns() - t0, fused=False)
                        self.bump(f"received_{proto}", n_msgs)
                        continue
                    t0 = time.monotonic_ns()
                    nbytes, n_msgs, n_over, n_eb = shard.parse_ring(
                        ring, max_msgs, bufsize - 1,
                        50 if wait_batch > 1 else 1000, wait_batch)
                    wait_batch = min(max_batch, max(1, n_msgs // 2))
                    self._uring_batch_stats(proto, n_over, n_eb)
                    if n_msgs == 0:
                        continue
                    self.bump("packets_received", n_msgs)
                    # (parse_ring above waits for datagrams: the
                    # annotation starts where the host's work does)
                    with annotate("ingest.batch"):
                        with self.lock:
                            processed, dropped, others = shard.commit()
                            self.ledger.ingest(
                                "dogstatsd", processed=processed,
                                staged=processed - dropped,
                                overflow=dropped)
                            work = self._maybe_device_step_locked()
                        self._apply_staged(work)
                    shard.reset()  # scrub local scratch off the lock
                    # slow-path lines point into commit's source
                    # (the arena, or the replay buffer on the rare
                    # epoch-fallback): slice them out BEFORE release
                    # hands the arena buffers back to the kernel
                    src = shard.last_slow_src
                    if isinstance(src, (bytes, bytearray)):
                        slow = [src[off:off + ln]
                                for off, ln, _kind in others]
                    else:
                        slow = [src[off:off + ln].tobytes()
                                for off, ln, _kind in others]
                    ring.release()
                    errors = 0
                    for line in slow:
                        try:
                            parsed = dsd.parse_line(line)
                        except dsd.ParseError:
                            errors += 1
                            continue
                        p, d = self.ingest_parsed(parsed, bump=False)
                        processed += p
                        dropped += d
                    if errors:
                        self.bump("packet_errors", errors)
                        self.ledger.ingest("dogstatsd",
                                           parse_errors=errors)
                    if processed:
                        self.bump("metrics_processed", processed)
                    if dropped:
                        self.bump("metrics_dropped", dropped)
                    self.device_costs.add_reader_batch(
                        name, n_msgs, processed,
                        time.monotonic_ns() - t0, fused=True)
                    self.bump(f"received_{proto}", n_msgs)
                except uring_mod.UringError as e:
                    self._note_backend_fallback(
                        e.reason, "ring died at runtime (%s)" % e)
                    return False
        finally:
            self._urings.pop(name, None)
            ring.close()
        return True

    def _uring_batch_stats(self, proto: str, n_over: int,
                           n_eb: int) -> None:
        """Oversize + ENOBUFS accounting shared by both ring modes:
        oversize datagrams were received-then-rejected whole (the
        ledger sees them as parse errors, like MSG_TRUNC on the
        recvmmsg tier); ENOBUFS completions are kernel-side drops at
        the pool boundary, observed like /proc/net/udp drops."""
        if n_over:
            self.bump(f"received_{proto}", n_over)
            self.bump("packet_errors", n_over)
            self.ledger.ingest("dogstatsd", parse_errors=n_over)
        if n_eb:
            self.bump("socket_uring_enobufs", n_eb)

    def handle_packet_batch(self, packets: list[bytes], parser,
                            drained: bytes | None = None,
                            drained_pkts: int = 0,
                            shard=None) -> int:
        """Columnar ingest of many datagrams: one native parse, one
        table lock, one stats round.  ``drained`` is a pre-validated
        newline-joined chunk from the native recvmmsg drain (each
        datagram already bounded/oversize-rejected in C), so it skips
        the per-packet length check.  ``shard`` is this reader
        thread's ReaderShard on the multi-reader fused path: parse
        and combine run lock-free against the shard's scratch, and
        only the miss-resolve + merge holds the lock.  Returns the
        processed sample count."""
        errors = 0
        good = []
        for p in packets:
            if len(p) > self.config.metric_max_length:
                errors += 1
            else:
                good.append(p)
        self.bump("packets_received", len(good) + drained_pkts)
        if drained is not None:
            good.append(drained)
        # overload admission: when active (tenant budgets configured
        # or pressure engaged) the batch routes through the columnar
        # branch below, whose vectorized admission check rewrites shed
        # lines to CODE_SHED before the table sees them.  The fused
        # native branches have no admission hook — diverting them is
        # what keeps the idle-path cost at this single boolean.
        adm = (self.overload is not None
               and self.overload.admission_active)
        if shard is not None and not adm:
            buf = b"\n".join(good)
            shard.parse(buf)  # lock-free fused pass (NO ledger work)
            with self.lock:
                processed, dropped, others = shard.commit()
                self.ledger.ingest("dogstatsd",
                                   processed=processed,
                                   staged=processed - dropped,
                                   overflow=dropped)
                work = self._maybe_device_step_locked()
            self._apply_staged(work)
            shard.reset()  # scrub local scratch off the lock
            for off, ln, _kind in others:
                try:
                    parsed = dsd.parse_line(buf[off:off + ln])
                except dsd.ParseError:
                    errors += 1
                    continue
                p, d = self.ingest_parsed(parsed, bump=False)
                processed += p
                dropped += d
        elif not adm and self.config.num_readers <= 1 and \
                getattr(self.table, "_lib", None) is not None:
            # single reader: nothing contends for the table lock, so
            # the fused native parse+probe+combine pass (no column
            # materialization) replaces parse-then-ingest; the split
            # design exists so MULTI-reader servers parse outside the
            # lock (and the fused multi-reader path above shards it)
            buf = b"\n".join(good)
            with self.lock:
                processed, dropped, others = \
                    self.table.ingest_buffer(buf)
                self.ledger.ingest("dogstatsd",
                                   processed=processed,
                                   staged=processed - dropped,
                                   overflow=dropped)
                work = self._maybe_device_step_locked()
            self._apply_staged(work)
            for off, ln, _kind in others:
                try:
                    parsed = dsd.parse_line(buf[off:off + ln])
                except dsd.ParseError:
                    errors += 1
                    continue
                p, d = self.ingest_parsed(parsed, bump=False)
                processed += p
                dropped += d
        else:
            # views into the reader's own parser scratch: consumed
            # fully (ingest + slow-path sweep) before this reader
            # parses again
            pb = parser.parse(b"\n".join(good), copy=False)
            shed = 0
            with self.lock:
                if adm:
                    # vectorized admission under the same lock round
                    # that credits the ledger: shed lines leave this
                    # critical section already attributed
                    shed, shed_by = self.overload.admit_columns(
                        pb, self.table)
                processed, dropped = self.table.ingest_columns(pb)
                self.ledger.ingest("dogstatsd",
                                   processed=processed + shed,
                                   staged=processed - dropped,
                                   overflow=dropped, shed=shed)
                if shed:
                    self.ledger.credit_shed(shed_by)
                work = self._maybe_device_step_locked()
            self._apply_staged(work)
            processed += shed
            if shed:
                self.bump("metrics_shed", shed)
            # events / service checks / malformed lines: per-line
            # slow path (CODE_SHED lines are already fully accounted
            # above — not errors, not events)
            slow = np.nonzero(
                (pb.type_code > columnar.CODE_SET)
                & (pb.type_code != columnar.CODE_SHED))[0]
            for i in slow:
                line = pb.line(int(i))
                try:
                    parsed = dsd.parse_line(line)
                except dsd.ParseError:
                    errors += 1
                    continue
                p, d = self.ingest_parsed(parsed, bump=False)
                processed += p
                dropped += d
        if errors:
            self.bump("packet_errors", errors)
            # informational (not a balance input), so out-of-lock is
            # fine — slow-path sample credits happened in
            # ingest_parsed above
            self.ledger.ingest("dogstatsd", parse_errors=errors)
        if processed:
            self.bump("metrics_processed", processed)
        if dropped:
            self.bump("metrics_dropped", dropped)
        return processed

    def _tcp_acceptor(self, sock: socket.socket) -> None:
        import ssl as _ssl
        while not self._shutdown.is_set():
            try:
                conn, _ = sock.accept()
            except _ssl.SSLError:
                # failed handshake (bad/missing client cert, protocol
                # junk): count and keep accepting — except the
                # shutdown wake connection, which is self-inflicted
                if not self._shutdown.is_set():
                    self.bump("tls_handshake_errors")
                continue
            except OSError:
                return
            t = threading.Thread(target=self._tcp_conn, args=(conn,),
                                 daemon=True)
            t.start()

    def _tcp_conn(self, conn: socket.socket) -> None:
        """Line-delimited statsd over TCP with idle timeout (reference
        server.go:1374 handleTCPGoroutine, 10min timeout :80)."""
        import ssl as _ssl
        conn.settimeout(600)
        if isinstance(conn, _ssl.SSLSocket):
            # handshake here, in the per-connection thread, so a slow
            # client can't block the acceptor
            try:
                conn.do_handshake()
            except (OSError, _ssl.SSLError):
                if not self._shutdown.is_set():
                    self.bump("tls_handshake_errors")
                conn.close()
                return
        buf = b""
        try:
            while not self._shutdown.is_set():
                chunk = conn.recv(65536)
                if not chunk:
                    break
                buf += chunk
                nlines = 0
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    if line:
                        self.handle_packet(line)
                        nlines += 1
                if nlines:
                    self.bump("received_dogstatsd-tcp", nlines)
                if len(buf) > self.config.metric_max_length:
                    self.bump("packet_errors")
                    buf = b""
        except OSError:
            pass
        finally:
            conn.close()

    # ------------------------------------------------------------------
    # http api

    def _start_http(self, address: str) -> None:
        host, _, port = address.rpartition(":")
        server = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _ok(self, body: bytes = b"ok",
                    ctype: str = "text/plain"):
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthcheck":
                    self._ok()
                elif self.path == "/version":
                    self._ok(__version__.encode())
                elif self.path == "/builddate":
                    self._ok(b"dev")
                elif self.path.startswith("/debug/pprof"):
                    # the role of net/http/pprof (reference
                    # http.go:52-57): live profiling without restart
                    self._pprof()
                elif self.path.startswith("/debug/flushes"):
                    from veneur_tpu.core import debughttp
                    debughttp.respond_ok(
                        self, server.flush_ring.to_json(
                            limit=debughttp.query_int(
                                self.path, "n", 0)),
                        "application/json")
                elif self.path.startswith("/debug/ledger"):
                    from veneur_tpu.core import debughttp
                    debughttp.ledger_dump(
                        self, server.ledger,
                        limit=debughttp.query_int(self.path, "n", 0))
                elif self.path.startswith("/debug/signals"):
                    # the columnar signal-history ring: ?window=<sec>
                    # bounds it in time, ?summary=1 serves the
                    # one-row shape vtop / /debug/cluster scrape
                    from veneur_tpu.core import debughttp
                    debughttp.signals_dump(self, server.signals,
                                           self.path)
                elif self.path.startswith("/debug/flight"):
                    # flight-recorder bundles: listing + raw
                    # CRC-framed fetch for offline replay
                    from veneur_tpu.core import debughttp
                    debughttp.flight_dump(self, server.flight,
                                          self.path)
                elif self.path.startswith("/debug/cluster"):
                    # fleet view: own latest signal row merged with
                    # cached peer summaries (tpu_cluster_peers, or
                    # the forward destinations)
                    from veneur_tpu.core import debughttp
                    import json as _json
                    debughttp.respond_ok(
                        self,
                        _json.dumps(server._cluster_view(),
                                    indent=1).encode(),
                        "application/json")
                elif self.path.startswith("/debug/trace"):
                    from veneur_tpu.core import debughttp
                    debughttp.trace_dump(self, server.trace_index,
                                         self.path)
                elif self.path.startswith("/debug/overload"):
                    # the overload-control surface on its own: is
                    # pressure engaged, at what level, who is being
                    # shed and why (same block as /debug/vars
                    # "overload", for operators riding out a surge)
                    from veneur_tpu.core import debughttp
                    import json as _json
                    debughttp.respond_ok(
                        self,
                        _json.dumps(
                            server.overload.snapshot()
                            if server.overload is not None
                            else {"enabled": False},
                            indent=2).encode(),
                        "application/json")
                elif self.path.startswith("/debug/vars"):
                    from veneur_tpu.core import debughttp
                    with server._stats_lock:
                        stats = dict(server.stats)
                    debughttp.vars_dump(self, {
                        "version": __version__,
                        "device": server.device_info,
                        "stats": stats,
                        "devicecost": server.device_costs.snapshot(),
                        "trace_client": {
                            "sent": server.trace_client.sent,
                            "dropped": server.trace_client.dropped,
                            "errors": server.trace_client.errors,
                        },
                        # per-sink flush duration/error counters from
                        # the fan-out workers; {} when
                        # tpu_sink_workers=0
                        "sinks": (server._fanout.stats()
                                  if server._fanout is not None
                                  else {}),
                        "last_flush_age_s": round(
                            time.monotonic() - server.last_flush, 3),
                        # retained native-decode scratch across the
                        # gRPC import readers (forward.grpc_forward;
                        # bounded by the oversized-streak release)
                        "forward": {
                            "decode_scratch_bytes":
                                _decode_scratch_bytes(),
                        },
                        # sharded-ring membership + refresh health
                        # (refresh_errors is the reason-tagged source
                        # of veneur.discovery.refresh_errors_total)
                        "discovery": (
                            server._sharded_fwd.discovery_stats()
                            if server._sharded_fwd is not None
                            else {}),
                        # collective forward plane-exchange: cycle/
                        # row/fallback counters, pack+exchange time,
                        # the peer map and the block schema (None
                        # until the transport first builds)
                        "forward.collective": (
                            server._collective_fwd.stats()
                            if server._collective_fwd is not None
                            else None),
                        # per-destination circuit breaker state
                        # (closed/half_open/open + trip counts) for
                        # the sharded forward workers
                        "breakers": (
                            server._sharded_fwd.breaker_states()
                            if server._sharded_fwd is not None
                            else {}),
                        # outage spool: queued/replayed/expired wire
                        # accounting; None when disabled or the
                        # sharded forwarder never built
                        "spool": (
                            server._sharded_fwd.spool_stats()
                            if server._sharded_fwd is not None
                            else None),
                        # per-class/per-tier sketch-memory accounting
                        # (core/table.plane_bytes): live byte totals,
                        # wide-pool occupancy, and the cumulative
                        # promotion/demotion counters — `tiers` inside
                        # is None when the table resolved single-tier
                        "planes": server.table.plane_bytes(),
                        # conservation at a glance; full per-interval
                        # records live at /debug/ledger
                        "ledger": server.ledger.summary(),
                        # cross-interval spool conservation (spooled
                        # == replayed + expired + queued + inflight)
                        "spool_ledger": server._spool_ledger.summary(),
                        # overload control: pressure signals, tenant
                        # buckets, shed attribution, coalesce state
                        # (full view at /debug/overload)
                        "overload": (
                            server.overload.snapshot()
                            if server.overload is not None
                            else None),
                        # kernel-boundary receive accounting per
                        # reader socket: cumulative drops observed in
                        # /proc/net/udp{,6} (loss the process never
                        # saw; also stats[socket_kernel_drops])
                        "sockets": {
                            "kernel_drops_total": stats.get(
                                "socket_kernel_drops", 0),
                            "by_inode": dict(
                                server._kernel_drops_last),
                            # resolved ingest drain tier (None until
                            # the first reader starts) and the
                            # startup probe's -errno when refused
                            "backend": server.ingest_backend,
                            "uring_probe_errno":
                                -server._uring_probe_err,
                            "backend_fallback_total": stats.get(
                                "socket_backend_fallback", 0),
                            # ENOBUFS completions: packets the kernel
                            # dropped at the provided-buffer pool
                            # boundary (pressure input, like
                            # kernel_drops_total)
                            "uring_enobufs_total": stats.get(
                                "socket_uring_enobufs", 0),
                            # per-reader ring health: pool occupancy
                            # (kernel-held vs parse-held buffers), cq
                            # backlog, completion-batch histogram
                            "uring": {
                                name: ring.stats()
                                for name, ring in
                                sorted(server._urings.items())
                            } or None,
                        },
                        # crash-riding lifecycle: when this process
                        # started, its checkpoint incarnation id, and
                        # how many listener fds it adopted from a
                        # predecessor (VENEUR_TPU_SOCK_CLOAKED)
                        "start_epoch": server.start_epoch,
                        "incarnation": server.incarnation,
                        "restarts_adopted": server.restarts_adopted,
                        # staged-plane checkpointer counters; None
                        # when checkpointing is disabled
                        "checkpoint": (
                            dict(server._checkpointer.stats)
                            if server._checkpointer is not None
                            else None),
                        # last scale-out arc handoff shipped by this
                        # node ({} until arc_handoff runs)
                        "handoff": dict(server._handoff_last),
                        # signal-history plane + flight recorder at a
                        # glance (full views at /debug/signals and
                        # /debug/flight); None when disabled
                        "signals": (
                            server.signals.summary()
                            if server.signals is not None else None),
                        "flight": (
                            server.flight.stats()
                            if server.flight is not None else None),
                    })
                elif (self.path == "/quitquitquit" and
                      server.config.http_quit):
                    # graceful shutdown endpoint (reference
                    # server.go:82 httpQuit + handlers_global.go)
                    self._ok(b"terminating")
                    threading.Thread(target=server.shutdown,
                                     daemon=True).start()
                else:
                    self.send_error(404)

            def _pprof(self):
                from veneur_tpu.core import debughttp
                debughttp.pprof(self, server._pprof_lock)

            def do_POST(self):
                if self.path == "/import":
                    t_imp0 = time.monotonic_ns()
                    length = int(self.headers.get("Content-Length", 0))
                    body = self.rfile.read(length)
                    with server.import_span(
                            "http", *http_import.decode_trace_header(
                                self.headers.get(
                                    http_import.TRACE_HEADER))) as imp:
                        err, acc = self._import(body, imp)
                    # answered once the span is closed, as the gRPC
                    # handler does: a sender that has its reply finds
                    # the whole tree at /debug/trace/<id>
                    if err is not None:
                        self.send_error(400, err)
                        return
                    server.bump("import_response_ns",
                                time.monotonic_ns() - t_imp0)
                    server.bump("import_responses")
                    self._ok(json.dumps({"accepted": acc}).encode(),
                             "application/json")
                else:
                    self.send_error(404)

            def _import(self, body, imp):
                """One /import wire, step by step as the gRPC handler
                takes it (``ImportServer._import_wire``); returns
                (error, accepted)."""
                hdr = self.headers.get
                try:
                    with imp.step("decode"):
                        items = http_import.decode_body(
                            body, hdr("Content-Encoding", ""))
                    drain = http_import.decode_drain_header(
                        hdr(http_import.DRAIN_HEADER))
                    replay = http_import.decode_replay_header(
                        hdr(http_import.REPLAY_HEADER))
                    recovery = http_import.decode_recovery_header(
                        hdr(http_import.RECOVERY_HEADER))
                    handoff = http_import.decode_handoff_header(
                        hdr(http_import.HANDOFF_HEADER))
                    deduped = False
                    acc = dropped = 0
                    work = None
                    with imp.step("lock_wait"):
                        server.lock.acquire()
                    try:
                        with imp.step("apply"):
                            if (recovery and recovery
                                    in server._recovery_seen):
                                # retransmitted recovery wire: the
                                # inc:seq id already landed — accept
                                # and discard so the sender's retry
                                # can't double-count the crash tail
                                deduped = True
                            else:
                                if recovery:
                                    server._recovery_seen.add(recovery)
                                # split dropped into overflow vs
                                # invalid exactly: every overflow bump
                                # happens under this same lock, so the
                                # tally delta across apply_import is
                                # this request's
                                ov0 = server.table.overflow_total()
                                acc, dropped = http_import.apply_import(
                                    server.table, items)
                                ov = server.table.overflow_total() - ov0
                                server.ledger.ingest(
                                    "http-import-recovery" if recovery
                                    else "http-import-handoff"
                                    if handoff
                                    else "http-import-drain" if drain
                                    else "http-import-replay"
                                    if replay
                                    else "http-import",
                                    processed=acc + dropped,
                                    staged=acc, overflow=ov,
                                    invalid=dropped - ov)
                                if recovery:
                                    inc = recovery.split(":", 1)[0]
                                    server.ledger.recover(
                                        f"incarnation:{inc}", acc)
                                if handoff:
                                    server.ledger.\
                                        credit_reshard_received(acc)
                                work = \
                                    server._maybe_device_step_locked()
                    finally:
                        server.lock.release()
                    with imp.step("device_step"):
                        server._apply_staged(work)
                    if deduped:
                        server.bump("recovery_wires_deduped")
                    elif recovery:
                        server.bump("recovery_wires_received")
                        server.bump("recovery_items_received", acc)
                    if handoff and not deduped:
                        server.bump("handoff_wires_received")
                        server.bump("handoff_items_received", acc)
                    if drain:
                        server.bump("drain_wires_received")
                        server.bump("drain_items_received", acc)
                    if replay:
                        server.bump("replay_wires_received")
                        server.bump("replay_items_received", acc)
                    imp.result(acc, dropped, len(body))
                    server.bump("imports_received", acc)
                    server.bump("metrics_dropped", dropped)
                    return None, acc
                except (ValueError, KeyError) as e:
                    server.bump("import_errors")
                    imp.span.set_error(e)
                    return str(e), 0

        adopted = self._adopted_socks.pop("http", None)
        if adopted is not None:
            # fd adoption (VENEUR_TPU_SOCK_CLOAKED): the predecessor
            # handed down its listening TCP socket, so connections
            # queued in the accept backlog across the restart are
            # served, and the port is never released (no bind race
            # with a sibling).  Mirrors the einhorn@ branch below.
            self._httpd = http.server.ThreadingHTTPServer(
                adopted.getsockname()[:2], Handler,
                bind_and_activate=False)
            self._httpd.socket.close()
            self._httpd.socket = adopted
            (self._httpd.server_name,
             self._httpd.server_port) = adopted.getsockname()[:2]
            self.restarts_adopted += 1
            self.bump("listener_fds_adopted")
        elif address.startswith("einhorn@"):
            # adopt the listening socket einhorn inherited to us
            # (reference README 'Einhorn Usage': http_address
            # einhorn@0 via goji/bind) and ACK the master so it stops
            # routing to the old worker
            from veneur_tpu.protocol.addr import parse_addr
            _, _, fd_idx, _ = parse_addr(address)
            fd = int(os.environ[f"EINHORN_FD_{fd_idx}"])
            sock = socket.fromfd(fd, socket.AF_INET,
                                 socket.SOCK_STREAM)
            self._httpd = http.server.ThreadingHTTPServer(
                sock.getsockname()[:2], Handler,
                bind_and_activate=False)
            # TCPServer.__init__ created a placeholder socket even
            # with bind_and_activate=False: close it before adopting
            self._httpd.socket.close()
            self._httpd.socket = sock
            # bind_and_activate=False skipped server_bind, which is
            # what fills in the name/port attributes
            (self._httpd.server_name,
             self._httpd.server_port) = sock.getsockname()[:2]
            self._einhorn_ack()
        else:
            self._httpd = http.server.ThreadingHTTPServer(
                (host or "127.0.0.1", int(port)), Handler)
        self.http_port = self._httpd.server_port
        self._cloak_slots["http"] = self._httpd.socket
        t = threading.Thread(target=self._httpd.serve_forever,
                             daemon=True, name="http")
        t.start()
        self._threads.append(t)

    def _einhorn_ack(self) -> None:
        """Send the worker ack over einhorn's control socket (the
        einhorn worker protocol; goji/bind does the same on adopt)."""
        path = os.environ.get("EINHORN_SOCK_PATH")
        if not path:
            return
        try:
            c = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            c.settimeout(5.0)  # a wedged master must not hang startup
            c.connect(path)
            c.sendall((json.dumps(
                {"command": "worker:ack", "pid": os.getpid()})
                + "\n").encode())
            c.close()
        except OSError as e:
            log.warning("einhorn ack failed: %s", e)

    # ------------------------------------------------------------------
    # flush

    def _flush_loop(self) -> None:
        next_tick = time.monotonic() + self.interval
        if self.config.synchronize_with_interval:
            now = time.time()
            next_tick = time.monotonic() + (
                self.interval - now % self.interval)
        while not self._shutdown.wait(
                max(0.0, next_tick - time.monotonic())):
            next_tick += self.interval
            try:
                self.flush_once()
            except Exception:
                log.exception("flush failed")

    def flush_once(self) -> FlushResult:
        """One flush: swap table state, read out, emit to sinks, forward
        (reference flusher.go:28 Flush).  Serialized (_flush_serial):
        when a ticker flush is in flight, a concurrent caller waits for
        it and then flushes what's left."""
        with self._flush_serial:
            return self._flush_once_locked()

    def _flush_once_locked(self) -> FlushResult:
        if self._shutdown.is_set():
            return FlushResult()
        # flush-overrun watchdog: the previous flush blew its interval
        # budget, so this tick coalesces — no swap, no sink fan-out;
        # the NEXT flush covers both intervals in one swap.  Staging
        # stays bounded via the mid-interval device steps, counters
        # keep folding exactly (two intervals of increments report
        # once: reduced temporal resolution, zero lost increments),
        # and the skip is named in the ledger + stats instead of
        # letting the ticker silently fall behind.  Drain/handoff
        # flushes never coalesce: they must land now.
        if (self.overload is not None and not self._draining
                and self.overload.take_coalesce()):
            self.bump("flush_coalesced")
            self.ledger.note_coalesced()
            log.warning(
                "flush overran its budget last interval; coalescing "
                "this tick (one swap will cover two intervals)")
            return FlushResult()
        t_flush0 = time.monotonic_ns()
        # self-trace the flush through the loopback client (reference
        # flusher.go:29 StartSpan("flush")): the cycle's root span plus
        # one child per stage re-enter the span pipeline (and
        # ssfmetrics extraction) next interval, and the cycle record
        # lands in the /debug/flushes ring
        with self.flush_tracer.cycle() as cyc:
            res = self._flush_stages(cyc, t_flush0)
        return res

    def _flush_stages(self, cyc, t_flush0: int) -> FlushResult:
        # kernel-side receive-drop delta for the closing interval:
        # loss BEFORE the process saw a packet, so it is observed but
        # unattributable — recorded on the interval (not a balance
        # input) and fed to the pressure signal
        kdrops = self._sample_kernel_drops()
        compiles0 = self.device_costs.totals()["compile_total"]
        if self.pipeline:
            # pipelined swap: only the O(µs) buffer detach + metadata
            # capture happens under the ingest lock; the final combine
            # dispatch (swap_apply) waits out in-flight staged applies
            # and runs with ingest already admitted to the new interval
            with cyc.stage("snapshot"):
                with self.lock:
                    pend = self.table.begin_swap()
                    events = self.events
                    checks = self.checks
                    self.events, self.checks = [], []
                    status = self.table.take_status()
                    # interval close in the SAME lock round as
                    # begin_swap: in-flight batches can't straddle the
                    # boundary, so site credits and the table's own
                    # counters describe the same sample population
                    led = self.ledger.close_interval(
                        seq=cyc.record.seq,
                        trace_id=cyc.record.trace_id,
                        table_staged=pend.ingested,
                        table_overflow=pend.overflow,
                        kernel_drops=kdrops)
                    self._take_imports(cyc.record)
            staged = pend.staged_counts()
            with cyc.stage("swap_apply") as sp:
                for k, v in staged.items():
                    sp.add_tag(k, str(v))
                snap = self.table.complete_swap(pend)
        else:
            with cyc.stage("snapshot") as sp:
                with self.lock:
                    # the mesh table times its swap's parts under
                    # this stage's span
                    snap = (self.table.swap(
                        stage=lambda name: cyc.stage(
                            f"snapshot.{name}", sp))
                        if self.config.tpu_mesh_shards
                        else self.table.swap())
                    events = self.events
                    checks = self.checks
                    self.events, self.checks = [], []
                    status = self.table.take_status()
                    led = self.ledger.close_interval(
                        seq=cyc.record.seq,
                        trace_id=cyc.record.trace_id,
                        table_staged=snap.ingested,
                        table_overflow=snap.overflow,
                        kernel_drops=kdrops)
                    self._take_imports(cyc.record)
        # the closed interval's last fold has run: its counts are
        # final (a table without them leaves the record's at 0)
        for k, v in getattr(snap, "import_counts", {}).items():
            setattr(cyc.record, f"import_{k}", v)
        for k, v in getattr(snap, "mesh_counts", {}).items():
            setattr(cyc.record, k, v)
        # dispatch / device_wait / host_emit stages happen inside the
        # flusher, against the same cycle
        res = self.flusher.flush(snap, cycle=cyc)
        # row-granularity flush balance: the flusher's routing counts
        # are synchronous, so they are balance inputs (wire outcomes
        # below are async and informational only)
        acct = getattr(res, "row_accounting", None)
        if acct:
            self.ledger.credit_rows(led, acct)
        # adaptive-tier boundary movements for the sealed interval:
        # promotions/demotions are named on the record (never balance
        # inputs — a moved row's mass balances through the normal
        # arms), and the post-boundary byte accounting feeds the
        # signal row below
        tsnap = getattr(snap, "tiers", None)
        if tsnap is not None:
            self.ledger.credit_tiers(led, tsnap.movements)
            self._last_plane_bytes = tsnap.plane_bytes
        # the interval's reads are done (the forward blocks own their
        # arrays); recycle the host set plane into the table's reuse
        # pool
        snap.release()
        self.last_flush = time.monotonic()
        self.bump("flushes")

        ts = int(time.time())
        for (name, _, tags, _), (val, msg, stags) in (
                (k, v) for k, v in status.items()):
            res.riders.append(im.InterMetric(
                name=name, timestamp=ts, value=val, tags=stags,
                type=im.STATUS, message=msg,
                hostname=self.flusher.hostname))

        futures = []

        def submit(key, fn, *args):
            # per-destination wedge isolation: if a previous interval's
            # task for this sink/plugin is still running, skip this
            # interval's rather than leak another pool worker behind it
            prev = self._flush_pending.get(key)
            if prev is not None and not prev.done():
                self.bump("flush_skipped_busy")
                log.warning("%s still busy from a previous interval; "
                            "skipping its flush", key)
                return
            try:
                fut = self._pool.submit(fn, *args)
            except RuntimeError:
                # shutdown() closed the pool mid-flush; drop the task
                return
            self._flush_pending[key] = fut
            futures.append(fut)

        def traced_forward(rows):
            # runs on the pool; the forward stage span hangs off the
            # same cycle root (stage timing is lock-guarded).  The
            # forward span's (trace_id, span_id) ride the wire so the
            # receiving tier parents its import span under it; the
            # sharded path re-stamps a CHILD span per destination so
            # /debug/trace renders one forward branch per shard.
            with cyc.stage("forward") as sp:
                sp.add_tag("rows", str(len(rows)))
                split = self._forward(
                    rows, trace_ctx=cyc.wire_context(sp), led=led,
                    cyc=cyc, span=sp)
                if split:
                    res.account_forward_split(split)

        with cyc.stage("sink_flush") as sink_span:
            fanout_tasks = []
            for sink, fn in self._route_sinks(res, events + checks,
                                              cyc, led, sink_span):
                if self._fanout is not None:
                    task = self._fanout.dispatch(sink.name, fn)
                    if task is not None:
                        fanout_tasks.append(task)
                    else:
                        self.bump("flush_skipped_busy")
                else:
                    submit(f"sink:{sink.name}",
                           self._guarded_sink_flush, fn)
            for plugin in self.plugins:
                submit(f"plugin:{plugin.name}", plugin.flush,
                       list(res.metrics), self.flusher.hostname)
            handoff_pending = self._handoff_pending
            if handoff_pending is not None and res.forward:
                # scale-out arc handoff (Server.arc_handoff): this
                # flush's forward rows are arcs the NEW ring assigns
                # to other members — ship them over the import wire
                # flagged veneur-handoff instead of the (on a global:
                # unconfigured) forward path
                ring, self_member = handoff_pending

                def traced_handoff(rows):
                    with cyc.stage("handoff") as sp:
                        sp.add_tag("rows", str(len(rows)))
                        self._ship_handoff(rows, ring, self_member,
                                           led, cyc.wire_context(sp))
                submit("handoff", traced_handoff, res.forward)
            elif self.is_local and res.forward:
                submit("forward", traced_forward, res.forward)
            submit("spans", self.span_worker.flush)
            # Wait for sink/forward/span tasks only within the interval
            # budget — the reference gives each flush a ctx deadline of
            # one interval (server.go:1022-1026) so a slow sink or a
            # wedged global can never delay the next tick.  Overrunning
            # tasks keep running on the pool and are counted, not
            # cancelled.
            # floored so tiny test intervals under load still give
            # healthy sinks a moment to land — a wedged sink only ever
            # eats one wait (its next dispatch busy-drops un-awaited)
            deadline = t_flush0 / 1e9 + max(self.interval * 0.9, 1.0)
            t_wait0 = time.monotonic_ns()
            if fanout_tasks:
                for name in self._fanout.wait(fanout_tasks, deadline):
                    self.bump("flush_slow_tasks")
                    log.warning("sink %s overran the interval budget;"
                                " its worker keeps running", name)
            for f in futures:
                try:
                    f.result(timeout=max(0.0,
                                         deadline - time.monotonic()))
                # futures.TimeoutError only aliases the builtin from
                # 3.11; on 3.10 catching the builtin alone silently
                # misfiles every budget overrun as a flush ERROR
                except (TimeoutError, _FuturesTimeout):
                    self.bump("flush_slow_tasks")
                    log.warning("flush task overran the interval "
                                "budget; continuing without it")
                except Exception:
                    self.bump("flush_errors")
                    log.exception("flush task failed")
            sink_wait_ns = time.monotonic_ns() - t_wait0
        with self._stats_lock:
            sink_durs = dict(self._sink_durations)
            self._sink_durations.clear()
        cyc.record.metrics_emitted = res.metric_count()
        cyc.record.forward_rows = len(res.forward)
        cyc.record.tally = dict(res.tally)
        # fan-out worker deltas (busy-drops / retries / timeouts) for
        # this interval, then seal: the balance checks run and the
        # record joins the /debug/ledger ring before self-telemetry
        # reads it
        if self._fanout is not None:
            fstats = self._fanout.stats()
            busy = sum(v.get("busy_drops", 0) for v in fstats.values())
            rets = sum(v.get("retries", 0) for v in fstats.values())
            touts = sum(v.get("timeouts", 0) for v in fstats.values())
            last = self._ledger_fanout_last
            self.ledger.credit_fanout(
                led, busy_drops=busy - last[0],
                retries=rets - last[1], timeouts=touts - last[2])
            self._ledger_fanout_last = (busy, rets, touts)
        if self.overload is not None:
            # pressure tick + overrun watchdog, once per flush: the
            # same budget the sink waits use above defines "overrun".
            # The bounded sink/forward waits are EXCLUDED — they can
            # never delay the next tick (a wedged sink eats one wait
            # and is then busy-dropped), so only the synchronous
            # pipeline blowing the budget threatens staging memory
            # and warrants coalescing
            dur_s = max(
                0.0, time.monotonic_ns() - t_flush0 - sink_wait_ns
            ) / 1e9
            compiled = (self.device_costs.totals()["compile_total"]
                        - compiles0) > 0
            self.overload.note_flush(
                dur_s, max(self.interval * 0.9, 1.0),
                compiled=compiled)
            occ = 0.0
            for name in ("counter_idx", "gauge_idx", "histo_idx",
                         "set_idx"):
                idx = getattr(self.table, name, None)
                if idx is not None and getattr(idx, "capacity", 0):
                    occ = max(occ, idx.occupancy() / idx.capacity)
            self.overload.tick(
                staging_depth=int(self.table.staged()),
                occupancy=occ,
                flush_lag_ratio=dur_s / max(self.interval, 1e-9),
                socket_drop_delta=kdrops, compiled=compiled)
            # histogram width ladder follows the pressure level: the
            # expensive class loses precision before anyone loses
            # data; level 0 restores the configured width
            setp = getattr(self.table, "set_pressure_level", None)
            if setp is not None:
                with self.lock:
                    setp(self.overload.pressure.level)
        self.ledger.seal(led)
        # signal-history sample at every seal: the sealed record, the
        # cycle's stage timings, and every subsystem's counters become
        # one row; the flight recorder's triggers run on it
        self._sample_signals(led, cyc.record,
                             time.monotonic_ns() - t_flush0)
        if self._checkpointer is not None:
            # the sealed interval's mass is delivered: its checkpoint
            # segments (and every older gen's) are now replay
            # hazards, not safety — prune them
            try:
                self._checkpointer.on_flush(int(snap.gen))
            except Exception:
                log.exception("checkpoint prune after flush failed")
        try:
            self.telemetry.flush_tick(
                res.tally, time.monotonic_ns() - t_flush0, sink_durs,
                record=cyc.record)
        except Exception:
            log.exception("self-telemetry emission failed")
        # the closed interval's host register planes, cleared now that
        # its flush has delivered: on this thread, off the ingest lock
        # and the lag's path, before the next swap turns to them (the
        # mesh table's; one chip's recycles its plane at release)
        clear_closed = getattr(self.table, "clear_closed", None)
        if clear_closed is not None:
            clear_closed()
        return res

    def _route_sinks(self, res, other, cyc, led, parent):
        """Every metric sink with its flush closure, the routing of
        all of them timed as the one stage ``sink_flush.route`` under
        ``parent`` (the ``sink_flush`` span).  Its tags say what the
        routing had to do: ``sink_only_rows`` is the count the frame
        was given (series with a ``veneursinkonly:`` tag), ``shared``
        the sinks that were handed the frame's blocks as they are."""
        with cyc.stage("sink_flush.route", parent=parent) as sp:
            routed, shared = [], 0
            for sink in self.metric_sinks:
                fn, unrouted = self._sink_flush_fn(sink, res, other,
                                                   cyc, led)
                routed.append((sink, fn))
                shared += unrouted
            cyc.record.sink_only_rows = res.frame.sink_only_rows
            sp.add_tag("sinks", str(len(routed)))
            sp.add_tag("sink_only_rows",
                       str(res.frame.sink_only_rows))
            sp.add_tag("shared", str(shared))
        return routed

    def _sink_flush_fn(self, sink, res, other, cyc, led=None):
        """Build the flush closure for one sink: routing (whitelists +
        excluded tags) happens HERE on the flush thread — per pool row
        for the frame and only where a live series has a whitelist or
        the sink excludes tags (``MetricFrame.route``), per metric for
        the riders — so the worker only encodes and POSTs.  A sink
        with ``flush_frame`` (every ``SinkBase``) gets the routed
        MetricFrame, the routed riders as its ``extra``; a duck-typed
        sink that only has ``flush`` gets the routed list of
        ``res.metrics``, which materializes the frame (once, cached on
        it).  Returns the closure, which raises on failure so the
        fan-out worker can retry, and whether the sink got the
        frame's blocks unrouted."""
        base = sink if isinstance(sink, sinks_base.SinkBase) else None
        shared = False
        if hasattr(sink, "flush_frame"):
            extra = sinks_base.route(res.riders, sink.name, base)
            payload = res.frame.route(sink.name, sink, extra=extra)
            shared = payload.blocks is res.frame.blocks
            n_routed = payload.total_len()

            def call():
                sink.flush_frame(payload)
        else:
            batch = sinks_base.route(res.metrics, sink.name, base)
            n_routed = len(batch)

            def call():
                sink.flush(batch)

        def fn():
            t0 = time.monotonic_ns()
            try:
                with cyc.stage(f"sink.{sink.name}"):
                    call()
                    if other:
                        sink.flush_other_samples(other)
                if led is not None:
                    # post-success: what actually left through this
                    # sink (async; may land after seal)
                    self.ledger.credit_sink(led, sink.name, n_routed)
            finally:
                with self._stats_lock:
                    self._sink_durations[sink.name] = (
                        self._sink_durations.get(sink.name, 0) +
                        time.monotonic_ns() - t0)
        return fn, shared

    def _guarded_sink_flush(self, fn) -> None:
        """Shared-pool wrapper (tpu_sink_workers=0): same
        swallow-and-count stance the pool path always had."""
        try:
            fn()
        except Exception:
            self.bump("flush_errors")
            log.exception("sink flush failed")

    def _forward(self, rows, trace_ctx, led, cyc, span):
        """Ship mergeable state upstream over gRPC or HTTP (reference
        flusher.go:82-99: forwardGRPC when configured, else
        flushForward; errors dropped-and-counted, never retried).
        ``trace_ctx`` is the flush cycle's (trace_id, span_id) stamped
        onto the wire for cross-tier stitching; ``led`` is the closed
        interval's ledger record (wire outcomes credit it
        asynchronously, possibly after seal).  ``cyc``/``span`` are
        the flush cycle and its forward stage span — the sharded path
        hangs one child span per destination off ``span``.  Returns
        the per-destination row split when the sharded router ran,
        else None."""
        t0 = time.monotonic_ns()
        if not getattr(self.config, "tpu_trace_propagation", True):
            trace_ctx = None
        try:
            if self.config.forward_use_grpc:
                fwd = self._sharded_forwarder()
                if fwd is not None:
                    return self._forward_sharded(
                        fwd, rows, trace_ctx, led, cyc, span)
                self._forward_grpc(rows, trace_ctx, led, cyc, span)
                return None
            if getattr(self.config, "tpu_sharded_global", False):
                # the split rides MetricList wires; HTTP JSON has no
                # record-span router — fail open to the legacy POST
                self.bump("sharded_forward_fallbacks")
            self._forward_http(rows, trace_ctx, led, cyc)
        except Exception as e:
            # encoding bugs / missing grpcio / anything: forwarding
            # must never abort the flush pipeline
            self.bump("metrics_dropped", len(rows))
            self.bump("forward_errors")
            if led is not None:
                self.ledger.credit_forward_wire(led, errors=1)
            log.exception("forward failed: %s", e)
        finally:
            self.bump("forward_duration_ns",
                      time.monotonic_ns() - t0)
            self.bump("forward_post_metrics", len(rows))
        return None

    def _sharded_forwarder(self):
        """The lazily-built ShardedForwarder when tpu_sharded_global
        is on (gRPC mode only); None keeps the legacy single-global
        path, which stays the M=1 parity oracle."""
        if not getattr(self.config, "tpu_sharded_global", False):
            return None
        if self._sharded_fwd is None:
            from veneur_tpu.forward.shard import ShardedForwarder
            addrs = [a.strip()
                     for a in self.config.forward_address.split(",")
                     if a.strip()]
            discoverer = None
            service = "forward"
            svc = getattr(self.config,
                          "consul_forward_service_name", "")
            if svc:
                from veneur_tpu.forward.discovery import \
                    ConsulDiscoverer
                discoverer = ConsulDiscoverer(self.config.consul_url)
                service = svc
                self._fwd_refresh_interval = \
                    self.config.consul_refresh_interval_seconds()
            spool = None
            if getattr(self.config, "tpu_forward_spool", True):
                from veneur_tpu.forward.spool import WireSpool
                spool = WireSpool(
                    max_bytes=int(getattr(
                        self.config, "tpu_forward_spool_max_bytes",
                        32 << 20)),
                    max_age=self.config.forward_spool_max_age_seconds(),
                    dir=(getattr(self.config,
                                 "tpu_forward_spool_dir", "") or None),
                    incarnation=self.incarnation)
            self._sharded_fwd = ShardedForwarder(
                addrs, compression=float(self.config.tpu_compression),
                credentials=self._forward_grpc_credentials(),
                discoverer=discoverer, service=service,
                retry_budget=max(self.interval * 0.9, 1.0),
                breaker_threshold=int(getattr(
                    self.config, "tpu_breaker_threshold", 5)),
                breaker_cooldown=self.config.breaker_cooldown_seconds(),
                spool=spool, on_replay=self._on_spool_replay)
        return self._sharded_fwd

    def _on_spool_replay(self, dest: str, n_items: int) -> None:
        """Worker-thread callback: one spooled wire replayed to a
        recovered destination (ledger crediting happens by cumulative
        delta at the next flush — this just surfaces the live
        counters)."""
        self.bump("replay_wires_sent")
        self.bump("replay_items_sent", n_items)

    def _collective_transport(self):
        """The lazily-built CollectiveTransport when the
        tpu_collective_forward gate resolves on; None keeps every
        destination on the wire.  "auto" engages iff
        tpu_collective_peers names at least one mesh peer — a node
        with no peer map has nothing to exchange with."""
        gate = str(getattr(self.config, "tpu_collective_forward",
                           "auto")).lower()
        if gate in ("off", "0", "false", "no"):
            return None
        peers_spec = getattr(self.config, "tpu_collective_peers", "")
        if gate == "auto" and not peers_spec:
            return None
        if self._collective_fwd is None:
            from veneur_tpu.forward.collective import (
                CollectiveTransport, parse_peers)
            from veneur_tpu.parallel.collective_forward import \
                PlaneSchema
            schema = PlaneSchema(
                compression=float(self.config.tpu_compression),
                max_rows=int(getattr(
                    self.config, "tpu_collective_max_rows", 512)),
                key_bytes=int(getattr(
                    self.config, "tpu_collective_key_bytes", 192)))
            self._collective_fwd = CollectiveTransport(
                schema, peers=parse_peers(peers_spec),
                exchange=self.collective_exchange,
                deadline=max(self.interval * 0.9, 1.0),
                on_late=self.apply_collective_blocks)
        return self._collective_fwd

    def collective_receive_cycle(self, timeout=None) -> tuple:
        """One receive-side rendezvous: participate in the mesh's
        plane exchange with nothing to send and fold whatever lands.
        A receiving global drives this in a loop paced by the
        senders' flush cycles (the collective blocks until they
        arrive); returns (accepted, dropped)."""
        coll = self._collective_transport()
        if coll is None:
            raise RuntimeError(
                "collective forward is off (gate/peers)")
        landed = coll.exchange_empty(timeout)
        return self.apply_collective_blocks(landed)

    def apply_collective_blocks(self, landed) -> tuple:
        """Fold every non-empty landed plane block into the local
        table — the collective twin of the gRPC import's
        _send_metrics, with the same ledger discipline: intake is
        credited under protocol "collective-import" with the
        overflow delta splitting drops into overflow vs invalid.
        Thread-safe (takes the ingest lock per block), so the
        late-land path may call it off the exchange worker."""
        from veneur_tpu.parallel import collective_forward as cplanes
        coll = self._collective_fwd
        schema = coll.schema
        total_acc = total_drop = blocks = 0
        for s in range(landed.shape[0]):
            block = landed[s]
            try:
                counts = cplanes.block_counts(block)
            except cplanes.PlaneFormatError:
                self.bump("collective_bad_blocks")
                continue
            if not any(counts):
                continue
            blocks += 1
            with self.lock:
                ov0 = self.table.overflow_total()
                acc, dropped = cplanes.fold_block(
                    self.table, block, schema)
                ov = self.table.overflow_total() - ov0
                self.ledger.ingest(
                    "collective-import", processed=acc + dropped,
                    staged=acc, overflow=ov, invalid=dropped - ov)
                work = self._maybe_device_step_locked()
            self._apply_staged(work)
            self.bump("imports_received", acc)
            self.bump("collective_items_received", acc)
            self.bump("collective_blocks_received")
            if dropped:
                self.bump("metrics_dropped", dropped)
            total_acc += acc
            total_drop += dropped
        if blocks:
            coll.note_landed(blocks)
        return total_acc, total_drop

    def _forward_sharded(self, fwd, rows, trace_ctx, led, cyc,
                         span) -> dict:
        """Split the flush's forward wire by route-key hash across the
        global ring and fan the per-destination bodies out on their
        workers.  Synchronous routing counts credit the ledger's
        forward split (seal checks forwarded == sum per-dest +
        dropped); wire outcomes land via worker callbacks.  The tail
        waits for this flush's wires within the interval budget — the
        M sends overlap (the fan-out win) and the legacy path's
        send-within-the-flush semantics hold, but a wedged shard can
        only eat its slice of the budget, never stall the next tick.
        Returns {dest: rows} for the flush result's accounting."""
        # discovery-driven live resharding: throttled membership poll
        # on the forward path, so a scale-out/in reshards the ring
        # BEFORE this flush routes (keep-last-good on failure — a
        # flapping Consul degrades to the previous membership and a
        # counted refresh error, never a lost interval)
        if self._fwd_refresh_interval > 0 and not self._draining:
            now = time.monotonic()
            if now >= self._fwd_refresh_next:
                self._fwd_refresh_next = (
                    now + self._fwd_refresh_interval)
                try:
                    fwd.refresh()
                except Exception:
                    log.exception("forward discovery refresh failed")
        # ONE ring snapshot per flush: the collective grouping below
        # and the wire routing must hash against the same membership
        # epoch even while discovery swaps underneath
        ring = fwd.ring
        # collective-first stage: mesh-peer destinations leave the
        # wire and ride the plane exchange.  Drain flushes never take
        # the collective (the wire is the only recovery path), and
        # any failure here falls open to the wire — counted, never a
        # lost flush.
        coll = None if self._draining else self._collective_transport()
        coll_groups: dict[str, list] = {}
        coll_split: dict[str, int] = {}
        if coll is not None and rows:
            from veneur_tpu.forward.shard import row_route_key
            wire_rows = []
            for row in rows:
                dest = ring.get(row_route_key(row))
                if coll.is_peer(dest):
                    coll_groups.setdefault(dest, []).append(row)
                else:
                    wire_rows.append(row)
            if coll_groups:
                rows = wire_rows
        if coll_groups:
            ch = None
            if cyc is not None and span is not None:
                ch = cyc.child(span, "forward.collective",
                               {"dests": str(len(coll_groups)),
                                "rows": str(sum(
                                    len(g)
                                    for g in coll_groups.values()))})
            try:
                sent, rejected, landed_planes = \
                    coll.send_cycle(coll_groups)
            except Exception as e:
                # fall open: the whole cycle's peer rows re-merge
                # onto the wire, named by the fallback counter
                n_back = sum(len(g) for g in coll_groups.values())
                self.bump("collective_forward_fallbacks")
                self.bump("collective_fallback_rows", n_back)
                log.warning("collective forward fell open to the "
                            "wire (%d rows): %s", n_back, e)
                rows = list(rows) + [r for g in coll_groups.values()
                                     for r in g]
                coll_groups = {}
                if ch is not None:
                    ch.set_error(e)
                    if cyc is not None:
                        cyc.finish(ch)
            else:
                self.bump("collective_forward_cycles")
                for dest, n in sent.items():
                    coll_split[dest] = n
                    self.bump("collective_forward_rows", n)
                    if led is not None:
                        self.ledger.credit_forward_collective(
                            led, dest, n)
                if rejected:
                    # schema-capacity rejects ship on the wire this
                    # cycle (rejected, never truncated)
                    self.bump("collective_rejected_rows",
                              len(rejected))
                    rows = list(rows) + list(rejected)
                # planes mesh peers addressed to US this rendezvous
                self.apply_collective_blocks(landed_planes)
                if ch is not None:
                    if rejected:
                        ch.add_tag("rejected", str(len(rejected)))
                    if cyc is not None:
                        cyc.finish(ch)
        data = fwd.serialize(rows)
        routed = None
        try:
            routed = fwd.route(data, ring=ring)
        except Exception:
            log.exception("columnar forward route failed; falling "
                          "back to the per-row path")
        if routed is not None:
            batches = [(routed.members[d], body, n)
                       for d, body, n in routed.batches]
            if routed.dropped:
                self.bump("metrics_dropped", routed.dropped)
                if led is not None:
                    self.ledger.credit_forward_split(
                        led, dropped=routed.dropped)
        else:
            self.bump("sharded_route_fallbacks")
            batches = fwd.route_rows_scalar(rows)
        # a membership change since the last flush: credit the moved
        # arcs so the ledger names this interval's per-dest skew as a
        # REBALANCE (re-route against the pre-swap ring and count the
        # rows whose owner changed), not a loss
        resh = fwd.take_reshard()
        if resh is not None:
            epoch, added, removed, prev_ring = resh
            moved = 0
            if routed is not None:
                prev_routed = None
                try:
                    prev_routed = fwd.route(data, ring=prev_ring)
                except Exception:
                    log.exception("pre-reshard route diff failed")
                if prev_routed is not None:
                    old_counts: dict[str, int] = {}
                    for d, _body, n in prev_routed.batches:
                        m = prev_routed.members[d]
                        old_counts[m] = old_counts.get(m, 0) + n
                    new_counts: dict[str, int] = {}
                    for d, _body, n in routed.batches:
                        m = routed.members[d]
                        new_counts[m] = new_counts.get(m, 0) + n
                    moved = sum(
                        max(0, new_counts.get(m, 0)
                            - old_counts.get(m, 0))
                        for m in set(new_counts) | set(old_counts))
            if coll_groups:
                # the collective rows re-route against the pre-swap
                # ring too: their moved arcs are the same rebalance,
                # counted scalar-wise over the grouped subset
                from veneur_tpu.forward.shard import row_route_key
                old_cc: dict[str, int] = {}
                for g in coll_groups.values():
                    for row in g:
                        d = prev_ring.get(row_route_key(row))
                        old_cc[d] = old_cc.get(d, 0) + 1
                moved += sum(
                    max(0, len(g) - old_cc.get(d, 0))
                    for d, g in coll_groups.items())
            if led is not None:
                self.ledger.credit_reshard(
                    led, epoch, added, removed, moved)
            self.bump("forward_reshards")
            self.bump("forward_reshard_moved_rows", moved)
        # per-destination deadline from the remaining interval budget:
        # no Forward call may block past it (a drain handoff gets a
        # wider floor so the final wires land before exit)
        budget = max(self.interval * 0.9, 1.0)
        if self._draining:
            budget = max(self.interval, 5.0)
        deadline = time.monotonic() + budget
        from veneur_tpu.forward.spool import Spooled
        split: dict[str, int] = {}
        done: list[threading.Event] = []
        for dest, body, n in batches:
            # outage absorption at route time: a destination whose
            # breaker is open (cooldown running) gets its wire parked
            # in the spool without occupying a queue slot; once the
            # cooldown elapses should_spool turns False and exactly
            # one wire rides through as the half-open probe.  Drain
            # flushes never spool — shutdown ships or drops, now.
            if not self._draining and fwd.should_spool(dest):
                if fwd.spool.put(dest, body, n):
                    self.bump("forward_spooled_wires")
                    self.bump("forward_spooled_items", n)
                    if led is not None:
                        self.ledger.credit_forward_spooled(led, n)
                else:
                    # single body over the spool's byte cap: an
                    # attributed drop, same bucket as a busy-drop
                    self.bump("forward_spool_rejected_items", n)
                    self.bump("metrics_dropped", n)
                    if led is not None:
                        self.ledger.credit_forward_split(
                            led, dropped=n)
                continue
            ch = None
            if cyc is not None and span is not None:
                ch = cyc.child(span, "forward.shard",
                               {"dest": dest, "rows": str(n)})
            wire_ctx = trace_ctx
            if trace_ctx and ch is not None and ch.trace_id:
                # per-destination child ids: each shard's wire parents
                # the remote import span under its OWN branch
                wire_ctx = (ch.trace_id, ch.span_id)

            landed = threading.Event()

            def _result(dest, n_items, err, retries, ch=ch,
                        nbytes=len(body), landed=landed):
                if err is None:
                    if cyc is not None:
                        cyc.add_forward_bytes(nbytes)
                    if led is not None:
                        self.ledger.credit_forward_wire(
                            led, rows=n_items, nbytes=nbytes)
                elif isinstance(err, Spooled):
                    # the failed wire was absorbed into the spool,
                    # not dropped: its rows stay split-credited (the
                    # spool ledger owns them from here), so no
                    # metrics_dropped
                    self.bump("forward_spooled_async_items", n_items)
                    self.bump("forward_errors")
                    if led is not None:
                        self.ledger.credit_spool_outcome(
                            led, spooled_async=n_items)
                        self.ledger.credit_forward_wire(led, errors=1)
                else:
                    self.bump("metrics_dropped", n_items)
                    self.bump("forward_errors")
                    if _is_deadline_error(err):
                        # deadline drops get their own per-dest
                        # attribution: a slow shard is NAMED, not
                        # folded into generic wire errors
                        self.bump("forward_timeout_dropped", n_items)
                        if led is not None:
                            self.ledger.credit_forward_timeout(
                                led, dest, n_items)
                    if led is not None:
                        self.ledger.credit_forward_wire(led, errors=1)
                if ch is not None:
                    if err is not None:
                        ch.set_error(err)
                    if retries:
                        ch.add_tag("retries", str(retries))
                    if cyc is not None:
                        cyc.finish(ch)
                landed.set()

            if fwd.send(dest, body, n, trace_context=wire_ctx,
                        on_result=_result, deadline=deadline,
                        drain=self._draining):
                self.bump("forward_shard_wires")
                if self._draining:
                    self.bump("drain_wires_sent")
                    self.bump("drain_items_sent", n)
                split[dest] = split.get(dest, 0) + n
                done.append(landed)
                if led is not None:
                    self.ledger.credit_forward_split(led, dest, n)
            else:
                # bounded-queue busy-drop: the wedged shard loses its
                # own wire, the other destinations sail on
                self.bump("forward_busy_dropped", n)
                self.bump("metrics_dropped", n)
                if led is not None:
                    self.ledger.credit_forward_split(led, dropped=n)
                if ch is not None:
                    ch.add_tag("busy_dropped", "true")
                    ch.set_error(True)
                    if cyc is not None:
                        cyc.finish(ch)
        for landed in done:
            if not landed.wait(max(0.0, deadline - time.monotonic())):
                self.bump("forward_shard_overruns")
        if fwd.spool is not None:
            # age out over-cap wires, credit replays since the last
            # flush to this interval's record, and seal one spool
            # conservation snapshot — the cross-interval proof that
            # spooled == replayed + expired + queued + inflight
            expired = fwd.spool.sweep()
            if expired:
                self.bump("spool_expired_swept_items", expired)
            replayed_now = fwd.replayed_items
            delta = replayed_now - self._replayed_credited
            if delta > 0:
                self._replayed_credited = replayed_now
                if led is not None:
                    self.ledger.credit_spool_outcome(
                        led, replayed=delta)
            self._spool_ledger.seal_snapshot(
                fwd.spool.stats(),
                seq=led.seq if led is not None else 0)
        for dest, n in coll_split.items():
            split[dest] = split.get(dest, 0) + n
        return split

    def _forward_http(self, rows, trace_ctx, led, cyc) -> None:
        if self.config.forward_json_schema == "reference":
            body, headers = http_import.encode_rows_reference(
                rows, compression=float(self.config.tpu_compression))
        else:
            body, headers = http_import.encode_rows(rows)
        if trace_ctx and trace_ctx[0]:
            # header-only: an old peer that predates tracing ignores
            # it and parses the body unchanged (fail-open)
            headers = dict(headers)
            headers[http_import.TRACE_HEADER] = \
                http_import.encode_trace_header(*trace_ctx)
        if self._draining:
            headers = dict(headers)
            headers[http_import.DRAIN_HEADER] = "1"
        url = self.config.forward_address.rstrip("/") + "/import"
        if not url.startswith("http"):
            url = "http://" + url
        req = urllib.request.Request(url, data=body, headers=headers,
                                     method="POST")
        try:
            with urllib.request.urlopen(req, timeout=10.0) as r:
                r.read()
        except OSError as e:
            self.bump("metrics_dropped", len(rows))
            self.bump("forward_errors")
            if led is not None:
                self.ledger.credit_forward_wire(led, errors=1)
            log.warning("forward failed: %s", e)
        else:
            if self._draining:
                self.bump("drain_wires_sent")
                self.bump("drain_items_sent", len(rows))
            cyc.add_forward_bytes(len(body))
            if led is not None:
                self.ledger.credit_forward_wire(
                    led, rows=len(rows), nbytes=len(body))

    def _forward_grpc(self, rows, trace_ctx, led, cyc, span) -> None:
        """The single-global wire, in two timed halves under the
        cycle's ``forward`` stage: ``forward.encode`` (rows -> bytes)
        and ``forward.send`` (the unary call, call to return), whose
        span's ids ride the wire."""
        from veneur_tpu.forward.grpc_forward import (ForwardClient,
                                                     encode_metric_list,
                                                     wire_metadata)
        import grpc as _grpc
        if self._grpc_client is None:
            self._grpc_client = ForwardClient(
                self.config.forward_address,
                compression=float(self.config.tpu_compression),
                credentials=self._forward_grpc_credentials())
        counts: dict = {}
        with cyc.stage("forward.encode", parent=span) as sp:
            body, centroids = encode_metric_list(
                rows, float(self.config.tpu_compression), counts)
            sp.add_tag("rows", str(len(rows)))
            sp.add_tag("bytes", str(len(body)))
            sp.add_tag("centroids", str(centroids))
            for key, n in counts.items():
                sp.add_tag(key, str(n))
        cyc.note_forward_encode(counts)
        try:
            with cyc.stage("forward.send", parent=span) as sp:
                sp.add_tag("bytes", str(len(body)))
                self._grpc_client.send_wire(body, metadata=wire_metadata(
                    trace_ctx and cyc.wire_context(sp), self._draining))
        except _grpc.RpcError as e:
            self.bump("metrics_dropped", len(rows))
            self.bump("forward_errors")
            if led is not None:
                self.ledger.credit_forward_wire(led, errors=1)
            log.warning("grpc forward failed: %s", e)
        else:
            if self._draining:
                self.bump("drain_wires_sent")
                self.bump("drain_items_sent", len(rows))
            cyc.add_forward_bytes(len(body))
            if led is not None:
                self.ledger.credit_forward_wire(
                    led, rows=len(rows), nbytes=len(body))

    # ------------------------------------------------------------------

    def _start_profiling(self) -> None:
        """Device+host profile capture behind enable_profiling
        (reference server.go:1512 pkg/profile CPU profiles; here the
        jax profiler's xplane traces, viewable in tensorboard/xprof)."""
        import jax
        try:
            jax.profiler.start_trace("./jax_profile")
            log.info("jax profiler trace -> ./jax_profile")
        except Exception:
            log.exception("could not start jax profiler")

    def _watchdog(self) -> None:
        """Crash if flushes stop happening (reference server.go:1031
        FlushWatchdog: deliberate crash-and-restart)."""
        allowed = self.config.flush_watchdog_missed_flushes
        while not self._shutdown.wait(self.interval):
            missed = (time.monotonic() - self.last_flush) / self.interval
            if missed > allowed:
                log.critical(
                    "flush watchdog: %.1f intervals without a flush "
                    "(allowed %d) — exiting for supervisor restart",
                    missed, allowed)
                if self.sentry is not None:
                    # the log handler above already queued the fatal
                    # event; bound the drain like ConsumePanic's
                    # Flush(SentryFlushTimeout) before dying
                    from veneur_tpu.core.sentry import FLUSH_TIMEOUT
                    self.sentry.flush(FLUSH_TIMEOUT)
                os._exit(2)

    def _drain_handoff(self) -> None:
        """Final-interval handoff: one last flush whose forward wires
        are flagged drain=true, so the receiving global books this
        local's staged planes past its normal interval cutoff and a
        rolling restart conserves every sample.  Runs BEFORE
        ``_shutdown`` is set (flush_once no-ops after)."""
        self._draining = True
        try:
            self.flush_once()
            self.bump("drain_flushes")
        except Exception:
            log.exception("drain handoff flush failed")
        finally:
            self._draining = False

    # ------------------------------------------------------------------
    # crash recovery + scale-out arc handoff

    def _recover_from_checkpoints(self) -> None:
        """Replay a crashed predecessor's surviving checkpoint
        segments (newest per incarnation+gen, unconsumed, younger
        than the recovery grace).  A local with a gRPC forward ships
        each segment body over the forward wire flagged
        ``veneur-recovery`` — the global books it past its interval
        cutoff under ``grpc-import-recovery`` and dedups on the
        ``inc:seq`` recovery id; everyone else re-ingests the body
        locally through the columnar import path, credited
        ``checkpoint-recovery`` and paired with the ledger's
        ``recovered`` arm.  Consumed ids are registered in the
        checkpoint dir so a crash DURING recovery (or two racing
        replacements) replays nothing twice."""
        from veneur_tpu.ops import checkpoint as ckpt
        directory = self.config.tpu_checkpoint_dir
        max_age = ckpt.RECOVERY_GRACE * max(
            self.config.checkpoint_interval_seconds(), self.interval)
        segs = ckpt.scan_recoverable(directory, self.incarnation,
                                     max_age)
        if not segs:
            return
        use_wire = (self.is_local and self.config.forward_use_grpc
                    and bool(self.config.forward_address))
        client = None
        try:
            if use_wire:
                from veneur_tpu.forward.grpc_forward import \
                    ForwardClient
                # sharded locals recover through the FIRST member:
                # the global tier merges a row wherever it lands, and
                # one off-arc recovery wire beats re-deriving the
                # predecessor's rows for per-arc routing
                dest = self.config.forward_address.split(
                    ",")[0].strip()
                client = ForwardClient(
                    dest,
                    compression=float(self.config.tpu_compression),
                    credentials=self._forward_grpc_credentials())
            for seg in segs:
                rid = seg.recovery_id
                items = int(seg.header.get("items", 0))
                try:
                    if client is not None:
                        from veneur_tpu.forward import \
                            grpc_forward as gf
                        client.send_wire(
                            seg.body,
                            metadata=[(gf.RECOVERY_KEY, rid)])
                    else:
                        self._recover_local(seg, rid)
                except Exception:
                    self.bump("recovery_errors")
                    log.exception("recovery replay of %s failed",
                                  seg.path)
                    continue
                ckpt.mark_consumed(directory, rid)
                self.bump("recovery_segments_replayed")
                self.bump("recovery_items_replayed", items)
                log.info(
                    "recovered checkpoint %s (%d items; %d device-"
                    "staged beyond its reach) via %s", rid, items,
                    int(seg.header.get("device_staged", 0)),
                    "forward wire" if client is not None
                    else "local re-ingest")
        finally:
            if client is not None:
                client.close()

    def _recover_local(self, seg, rid: str) -> None:
        """Re-ingest one segment body through the columnar import
        path under the ingest lock — the same receiver-side dedup
        the wire path gets from the import server."""
        from veneur_tpu.forward.grpc_forward import \
            apply_metric_list_bytes
        deduped = False
        acc = dropped = 0
        work = None
        with self.lock:
            if rid in self._recovery_seen:
                deduped = True
            else:
                self._recovery_seen.add(rid)
                ov0 = self.table.overflow_total()
                acc, dropped = apply_metric_list_bytes(self.table,
                                                       seg.body)
                ov = self.table.overflow_total() - ov0
                self.ledger.ingest(
                    "checkpoint-recovery", processed=acc + dropped,
                    staged=acc, overflow=ov, invalid=dropped - ov)
                inc = rid.split(":", 1)[0]
                self.ledger.recover(f"incarnation:{inc}", acc)
                work = self._maybe_device_step_locked()
        self._apply_staged(work)
        if deduped:
            self.bump("recovery_wires_deduped")
            return
        self.bump("imports_received", acc)
        self.bump("metrics_dropped", dropped)

    def arc_handoff(self, members: list[str],
                    self_member: str) -> dict:
        """Scale-out keyspace handoff, global tier: flush once with
        the flusher's handoff gate installed, so every resident row
        whose route-key arc belongs to another member under the NEW
        ring force-forwards, and ship those rows over the import wire
        flagged ``veneur-handoff`` (_ship_handoff).  Run on each
        incumbent when discovery adds global M+1, BEFORE the locals'
        rings flip — the newcomer receives its arcs' staged history
        instead of starting cold while the incumbent re-reports the
        same keys.  Returns the shipped-arc stats."""
        if not getattr(self.config, "tpu_arc_handoff", True):
            return {"enabled": False}
        from veneur_tpu.forward import handoff as ho
        from veneur_tpu.forward.ring import ConsistentRing
        ring = ConsistentRing(list(members))
        with self._flush_serial:
            self._handoff_last = {}
            self.flusher.handoff = ho.make_flusher_gate(
                ring, self_member)
            self._handoff_pending = (ring, self_member)
            try:
                self._flush_once_locked()
            finally:
                self.flusher.handoff = None
                self._handoff_pending = None
        self.bump("arc_handoffs")
        return dict(self._handoff_last)

    def _ship_handoff(self, rows, ring, self_member, led,
                      trace_ctx=None) -> None:
        """Partition a handoff flush's forward rows by the new ring
        and send each member its arcs, flagged ``veneur-handoff``;
        the receiver books them as a rebalance arrival
        (reshard_received_items).  Wire failures drop loudly —
        counted, ledger-credited, never silent."""
        from veneur_tpu.forward import handoff as ho
        if self._handoff_shipper is None:
            self._handoff_shipper = ho.HandoffShipper(
                compression=float(self.config.tpu_compression),
                credentials=self._forward_grpc_credentials())
        by_member, kept = ho.partition(rows, ring, self_member)
        moved = sum(len(v) for v in by_member.values())
        stats = self._handoff_shipper.ship(by_member, trace_ctx)
        stats["moved_rows"] = moved
        stats["kept_rows"] = kept
        self._handoff_last = stats
        self.bump("handoff_wires_sent", stats["wires"])
        self.bump("handoff_items_sent", stats["items"])
        if stats["errors"]:
            self.bump("handoff_errors", stats["errors"])
            self.bump("metrics_dropped", stats["dropped_items"])
        if led is not None:
            # name the outward rebalance on the interval record: the
            # ring gained every member that is not this node and
            # ``moved`` of this flush's rows left for new owners
            self.ledger.credit_reshard(
                led, 0, [m for m in ring.members
                         if m != self_member], [], moved)
            self.ledger.credit_forward_wire(
                led, rows=stats["items"], errors=stats["errors"])

    # ------------------------------------------------------------------
    # signal history plane (observe/signals.py + observe/recorder.py)

    def _signal_row(self, led=None, record=None,
                    flush_ns: int = 0) -> dict:
        """One fixed-schema row of every internal signal.  Called with
        no args at init to derive the schema, so every subsystem
        access is guarded — a disabled/lazily-built subsystem reports
        0, never a missing column.  Cumulative counters are preferred
        (the ring computes delta + EWMA rate at append); per-interval
        values (stage ns, pressure score) ride as instants."""
        with self._stats_lock:
            st = dict(self.stats)
        row = {
            "ingest.packets_received": st.get("packets_received", 0),
            "ingest.packet_errors": st.get("packet_errors", 0),
            "ingest.metrics_processed": st.get("metrics_processed", 0),
            "ingest.metrics_dropped": st.get("metrics_dropped", 0),
            "ingest.imports_received": st.get("imports_received", 0),
            "ingest.import_errors": st.get("import_errors", 0),
            "ingest.kernel_drops": st.get("socket_kernel_drops", 0),
            "flush.count": st.get("flushes", 0),
            "flush.errors": st.get("flush_errors", 0),
            "flush.slow_tasks": st.get("flush_slow_tasks", 0),
            "flush.duration_ns": int(flush_ns),
            "flush.compiles":
                self.device_costs.totals()["compile_total"],
            "handoff.shipped_items": st.get("handoff_items_sent", 0),
            "handoff.received_items":
                st.get("handoff_items_received", 0),
            "recover.recovered_items":
                st.get("recovery_items_received", 0),
            "recover.replay_wires": st.get("replay_wires_received", 0),
            "recover.segments_replayed":
                st.get("recovery_segments_replayed", 0),
            "trace.spans_sent": self.trace_client.sent,
            "trace.spans_dropped": self.trace_client.dropped,
        }
        stages = record.stages if record is not None else {}
        for stage in ("snapshot", "dispatch", "device_wait",
                      "host_emit", "sink_flush", "forward"):
            row[f"flush.stage.{stage}_ns"] = stages.get(stage, 0)
        row["flush.readback_bytes"] = (
            record.readback_bytes if record is not None else 0)
        ov = self.overload
        row["pressure.score"] = (
            ov.pressure.score if ov is not None else 0.0)
        row["pressure.level"] = (
            ov.pressure.level if ov is not None else 0)
        row["pressure.engaged"] = int(
            ov.pressure.engaged if ov is not None else False)
        row["pressure.transitions"] = (
            ov.pressure.transitions if ov is not None else 0)
        row["flush.overruns"] = (
            ov.flush_overruns if ov is not None else 0)
        row["flush.coalesced"] = (
            ov.coalesced_total if ov is not None else 0)
        row["shed.total"] = ov.shed_total if ov is not None else 0
        row["shed.tenants"] = (
            len({t for t, _ in ov.shed_by_total})
            if ov is not None else 0)
        row["ledger.received"] = (
            led.received_total() if led is not None else 0)
        row["ledger.staged"] = led.staged if led is not None else 0
        row["ledger.status"] = led.status if led is not None else 0
        row["ledger.shed"] = led.shed if led is not None else 0
        row["ledger.overflow"] = (
            led.overflow if led is not None else 0)
        row["ledger.invalid"] = led.invalid if led is not None else 0
        row["ledger.owed"] = led.owed if led is not None else 0
        row["ledger.balanced"] = int(
            led.balanced if led is not None else True)
        row["ledger.emitted_rows"] = (
            led.emitted_rows if led is not None else 0)
        row["ledger.forwarded_rows"] = (
            led.forwarded_rows if led is not None else 0)
        row["ledger.retained_rows"] = (
            led.retained_rows if led is not None else 0)
        row["ledger.coalesced"] = (
            led.coalesced if led is not None else 0)
        row["ledger.parse_errors"] = (
            led.parse_errors if led is not None else 0)
        row["ledger.imbalanced_total"] = self.ledger.imbalanced_total
        row["reshard.received_items"] = (
            led.reshard_received_items if led is not None else 0)
        table = getattr(self, "table", None)
        row["table.staged"] = (
            int(table.staged()) if table is not None else 0)
        occ = 0.0
        for name in ("counter_idx", "gauge_idx", "histo_idx",
                     "set_idx"):
            idx = getattr(table, name, None)
            if idx is not None and getattr(idx, "capacity", 0):
                occ = max(occ, idx.occupancy() / idx.capacity)
        row["table.occupancy"] = round(occ, 6)
        fwd = getattr(self, "_sharded_fwd", None)
        states = fwd.breaker_states() if fwd is not None else {}
        row["breaker.closed"] = sum(
            1 for s in states.values() if s["state"] == "closed")
        row["breaker.half_open"] = sum(
            1 for s in states.values() if s["state"] == "half_open")
        row["breaker.open"] = sum(
            1 for s in states.values() if s["state"] == "open")
        tot = fwd.totals() if fwd is not None else {}
        row["breaker.opens_total"] = tot.get("breaker_opens", 0)
        row["forward.sent_items"] = tot.get("sent_items", 0)
        row["forward.error_items"] = tot.get("error_items", 0)
        row["forward.busy_dropped_items"] = tot.get(
            "busy_dropped_items", 0)
        row["forward.replayed_items"] = tot.get("replayed_items", 0)
        row["forward.queued"] = sum(
            w.get("queued", 0)
            for w in (fwd.stats() if fwd is not None else {}).values())
        disc = fwd.discovery_stats() if fwd is not None else {}
        row["forward.destinations"] = len(disc.get("members", ()))
        row["reshard.epoch"] = disc.get("epoch", 0)
        row["reshard.moved_rows"] = st.get(
            "forward_reshard_moved_rows", 0)
        sp = fwd.spool_stats() if fwd is not None else None
        for key in ("queued_items", "queued_bytes", "spooled_items",
                    "replayed_items", "expired_items",
                    "inflight_items"):
            row[f"spool.{key}"] = (sp or {}).get(key, 0)
        # collective forward plane-exchange (zeros until the
        # transport builds — the schema is fixed at construction)
        coll = getattr(self, "_collective_fwd", None)
        cst = coll.stats() if coll is not None else {}
        row["forward.collective.cycles"] = cst.get("cycles", 0)
        row["forward.collective.rows"] = cst.get("sent_rows", 0)
        row["forward.collective.rejected_rows"] = cst.get(
            "rejected_rows", 0)
        row["forward.collective.fallback_cycles"] = cst.get(
            "fallback_cycles", 0)
        row["forward.collective.landed_blocks"] = cst.get(
            "landed_blocks", 0)
        row["forward.collective.items_received"] = st.get(
            "collective_items_received", 0)
        fan = (self._fanout.stats()
               if getattr(self, "_fanout", None) is not None else {})
        row["sink.flushes"] = sum(
            w.get("flushes", 0) for w in fan.values())
        row["sink.errors"] = sum(
            w.get("errors", 0) for w in fan.values())
        row["sink.busy_drops"] = sum(
            w.get("busy_drops", 0) for w in fan.values())
        row["sink.timeouts"] = sum(
            w.get("timeouts", 0) for w in fan.values())
        # adaptive sketch tiers (core/tiers.py): the boundary's byte
        # accounting and this interval's ledger-attributed movements.
        # Zeros when the table resolved single-tier — the schema is
        # frozen at construction either way
        pb = self._last_plane_bytes or {}
        row["table.plane_bytes_total"] = pb.get("total", 0)
        row["table.plane_bytes_histo_wide"] = pb.get(
            "histo", {}).get("wide", 0)
        row["table.plane_bytes_histo_compact"] = pb.get(
            "histo", {}).get("compact", 0)
        row["table.plane_bytes_set_wide"] = pb.get(
            "set", {}).get("wide", 0)
        row["table.plane_bytes_set_compact"] = pb.get(
            "set", {}).get("compact", 0)
        row["table.plane_bytes_per_series"] = round(
            pb.get("device_bytes_per_series", 0.0), 3)
        row["table.tier_promotions"] = (
            led.tier_promotions if led is not None else 0)
        row["table.tier_demotions"] = (
            led.tier_demotions if led is not None else 0)
        row["table.tier_escalations"] = (
            led.tier_escalations if led is not None else 0)
        row["table.tier_promote_refused"] = (
            led.tier_promote_refused if led is not None else 0)
        return row

    def _sample_signals(self, led, record, flush_ns: int) -> None:
        """The per-seal sampling hook: append one row to the history
        ring, evaluate the flight-recorder triggers on it, and count
        both (veneur.signals.rows_total / veneur.flight.*)."""
        if self.signals is None:
            return
        try:
            row = self._signal_row(led, record, flush_ns)
            t_now = time.time()
            seq = led.seq if led is not None else 0
            self.signals.append(row, t=t_now, seq=seq)
            if self.flight is not None:
                # the triggering interval's flush record is not in the
                # flush ring yet (appended after the seal hook) —
                # stash it for _flight_context
                self._flight_record = record
                self.flight.observe(row, t=t_now, seq=seq)
            self.bump("signal_rows")
        except Exception:
            log.exception("signal sample failed")

    def _flight_context(self, trigger: str, row: dict) -> dict:
        """Incident context captured into a flight bundle at trigger
        time: the triggering interval's sealed ledger record(s), its
        flush record + trace tree, and the live subsystem snapshots.
        Cheap dict copies only — this runs on the flush thread."""
        out: dict = {}
        recs = self.ledger.records()
        out["ledger_records"] = [r.to_dict() for r in recs[-4:]]
        rec = getattr(self, "_flight_record", None)
        if rec is None:
            flushes = self.flush_ring.records()
            rec = flushes[-1] if flushes else None
        if rec is not None:
            out["flush_record"] = rec.to_dict()
            out["trace"] = self.trace_index.get(rec.trace_id)
        fwd = self._sharded_fwd
        out["breakers"] = (
            fwd.breaker_states() if fwd is not None else {})
        out["spool"] = fwd.spool_stats() if fwd is not None else None
        out["discovery"] = (
            fwd.discovery_stats() if fwd is not None else {})
        out["overload"] = (
            self.overload.snapshot()
            if self.overload is not None else None)
        out["spool_ledger"] = self._spool_ledger.summary()
        with self._stats_lock:
            out["stats"] = dict(self.stats)
        return out

    # ------------------------------------------------------------------
    # /debug/cluster: own latest row merged with cached peer summaries

    _CLUSTER_TTL = 10.0

    def _cluster_peers(self) -> list[str]:
        peers = [p.strip() for p in str(getattr(
            self.config, "tpu_cluster_peers", "")).split(",")
            if p.strip()]
        if not peers and self._sharded_fwd is not None:
            peers = list(self._sharded_fwd.discovery_stats().get(
                "members", ()))
        return peers

    def _scrape_peer(self, addr: str) -> dict:
        url = addr if "://" in addr else f"http://{addr}"
        url = url.rstrip("/") + "/debug/signals?summary=1"
        with urllib.request.urlopen(url, timeout=1.0) as resp:
            return json.loads(resp.read().decode())

    def _cluster_view(self) -> dict:
        """Own signal summary merged with peer summaries, cached per
        peer for ``_CLUSTER_TTL`` seconds (keep-last-good: a peer that
        stops answering serves its stale summary, flagged, instead of
        vanishing from the fleet view)."""
        now = time.monotonic()
        peers = {}
        for addr in self._cluster_peers():
            with self._cluster_lock:
                cached = self._cluster_cache.get(addr)
            if cached is not None and (now - cached[0]) < \
                    self._CLUSTER_TTL:
                peers[addr] = cached[1]
                continue
            try:
                summ = self._scrape_peer(addr)
                summ["stale"] = False
                summ.pop("error", None)
                with self._cluster_lock:
                    self._cluster_cache[addr] = (now, summ)
                peers[addr] = summ
            except Exception as e:
                if cached is not None:
                    stale = dict(cached[1])
                    stale["stale"] = True
                    stale["error"] = f"{type(e).__name__}: {e}"
                    peers[addr] = stale
                else:
                    peers[addr] = {
                        "error": f"{type(e).__name__}: {e}",
                        "stale": True}
        return {
            "node": self.config.hostname or "",
            "role": "local" if self.is_local else "global",
            "self": (self.signals.summary()
                     if self.signals is not None else None),
            "peers": peers,
        }

    def shutdown(self) -> None:
        if (not self._shutdown.is_set()
                and getattr(self.config, "tpu_drain_on_shutdown", True)
                and self.config.is_local()):
            self._drain_handoff()
        self._shutdown.set()
        if self._gc_hooked:
            self._gc_hooked = False
            PAUSES.remove()
        if self._checkpointer is not None:
            self._checkpointer.stop()
            self._checkpointer = None
        if self._handoff_shipper is not None:
            self._handoff_shipper.close()
            self._handoff_shipper = None
        if getattr(self, "_sentry_handler", None) is not None:
            # don't leave error logs mirroring to a dead client (and
            # blocking the next Server's handler)
            logging.getLogger("veneur_tpu").removeHandler(
                self._sentry_handler)
            self._sentry_handler = None
        if self.sentry is not None:
            self.sentry.close()
            self.sentry = None
        # wake every datagram reader BEFORE closing: on Linux a
        # close() does NOT interrupt a thread blocked in recv, so the
        # reader would sit in the dead syscall until killed mid-C-call
        # at interpreter exit (observed as glibc 'FATAL: exception not
        # rethrown' aborts after otherwise-green runs).  An empty
        # datagram pops the recv; the loop then sees _shutdown.
        for s in self._sockets:
            try:
                if s.type == socket.SOCK_DGRAM:
                    wake = socket.socket(s.family, socket.SOCK_DGRAM)
                    wake.sendto(b"", s.getsockname())
                    wake.close()
                else:  # listening TCP: accept() needs a connection
                    wake = socket.socket(s.family, socket.SOCK_STREAM)
                    wake.settimeout(0.5)
                    wake.connect(s.getsockname())
                    wake.close()
            except OSError:
                pass
        for s in self._sockets:
            try:
                s.close()
            except OSError:
                pass
        # stop the HTTP server before joining: its serve_forever
        # thread is in _threads and only returns on shutdown()
        if self._httpd:
            self._httpd.shutdown()
        for g in self.grpc_servers:
            g.stop()
        for t in self._threads:
            t.join(timeout=1.5)
        self.trace_client.close()
        self.span_worker.stop()
        if self.config.enable_profiling:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
        if self._grpc_client is not None:
            self._grpc_client.close()
        if self._sharded_fwd is not None:
            self._sharded_fwd.stop()
        if self._collective_fwd is not None:
            self._collective_fwd.stop()
        if self.flight is not None:
            self.flight.stop()
        for s in self.metric_sinks + self.span_sinks:
            if hasattr(s, "stop"):
                try:
                    s.stop()
                except Exception:
                    pass
        self._pool.shutdown(wait=False)
        if self._fanout is not None:
            self._fanout.stop()
        # close releases the flock; the lock FILE stays (unlinking it
        # would race two starting instances onto different inodes of
        # the same path, each holding "the" lock — the reference's
        # acquireLockForSocket likewise leaves the file behind)
        for _lockname, fd in self._socket_locks:
            try:
                os.close(fd)
            except OSError:
                pass
        self._socket_locks.clear()
