"""YAML configuration with environment overrides and validation.

Mirrors the reference's config system (config.go struct of ~130 YAML
keys; config_parse.go:102 ``ReadConfig``): a single YAML file, semi-
strict parsing (unknown keys warn, ``strict`` mode fails), ``VENEUR_*``
environment-variable overrides (config_parse.go:144 envconfig), and
defaults applied afterwards (config_parse.go:153, defaults at :14-24).

TPU-specific sizing knobs live under ``tpu_*`` keys (table row
capacities, digest compression, merge slot width) — these have no
reference equivalent because Go maps grow unboundedly; device tables
are fixed-capacity with compaction.
"""

from __future__ import annotations

import logging
import os
import re
from dataclasses import dataclass, field, fields

log = logging.getLogger("veneur_tpu.config")

try:
    import yaml
except ImportError:  # pragma: no cover
    yaml = None

_DURATION_RE = re.compile(r"^\s*([\d.]+)\s*(ms|s|m|h|us)?\s*$")
_DURATION_UNITS = {"us": 1e-6, "ms": 1e-3, "s": 1.0, "m": 60.0,
                   "h": 3600.0, None: 1.0}


def parse_duration(text: str | float | int) -> float:
    """'10s' / '50ms' / 10 -> seconds (reference durations are Go
    duration strings)."""
    if isinstance(text, (int, float)):
        return float(text)
    m = _DURATION_RE.match(text)
    if not m:
        raise ValueError(f"bad duration: {text!r}")
    return float(m.group(1)) * _DURATION_UNITS[m.group(2)]


@dataclass
class Config:
    # lifecycle / identity
    hostname: str = ""
    tags: list[str] = field(default_factory=list)
    interval: str = "10s"
    # debug-level logging (reference config.go Debug)
    debug: bool = False
    flush_watchdog_missed_flushes: int = 0
    synchronize_with_interval: bool = False

    # listeners (reference networking.go; url-style addresses,
    # protocol/addr.go:18)
    statsd_listen_addresses: list[str] = field(default_factory=list)
    ssf_listen_addresses: list[str] = field(default_factory=list)
    grpc_listen_addresses: list[str] = field(default_factory=list)
    # deprecated single-listener alias of grpc_listen_addresses
    # (reference config.go GrpcAddress)
    grpc_address: str = ""
    http_address: str = ""
    # serve POST-free GET /quitquitquit for graceful shutdown
    # (reference server.go:82 http_quit)
    http_quit: bool = False
    num_readers: int = 1
    # datagrams a reader sweeps into one columnar parse batch
    reader_batch_packets: int = 512
    metric_max_length: int = 4096
    trace_max_length_bytes: int = 16 * 1024 * 1024
    read_buffer_size_bytes: int = 2 * 1048576

    # aggregation
    percentiles: list[float] = field(default_factory=lambda: [0.5, 0.75,
                                                              0.99])
    aggregates: list[str] = field(default_factory=lambda: ["min", "max",
                                                           "count"])
    count_unique_timeseries: bool = False
    # "precise" emits .999percentile for 0.999; "reference" keeps the
    # Go fleet's int(p*100) truncation (samplers.go:664 — 0.999 ->
    # .99percentile) for byte-identical mixed-fleet dashboards
    percentile_naming: str = "precise"
    # "interp" (default): singleton-exact rank-space interpolation —
    # the accuracy the p99<=1% budget is measured against; "reference"
    # reproduces the Go digest's uniform-bounds walk exactly
    # (merging_digest.go:302) for value-identical mixed fleets
    quantile_interpolation: str = "interp"

    # forwarding / tiering
    forward_address: str = ""
    forward_use_grpc: bool = False
    # dial the gRPC global over TLS (no reference equivalent — the
    # reference always dials insecure, server.go:983, though its own
    # listener is TLS-capable).  forward_grpc_tls uses system roots;
    # forward_grpc_tls_ca pins a CA (file path or inline PEM); the
    # node's tls_key/tls_certificate double as the client pair for
    # mutual auth when present.
    forward_grpc_tls: bool = False
    forward_grpc_tls_ca: str = ""
    # HTTP /import wire schema when forwarding: "native" (default)
    # carries scope; "reference" emits the reference's JSONMetric
    # format (gob digests, LE counter/gauge, axiomhq HLL binary) so an
    # unmodified Go global can receive this local.  Inbound /import
    # always accepts BOTH schemas.
    forward_json_schema: str = "native"

    # span plane (reference: indicator_span_timer_name,
    # objective_span_timer_name config keys; ssf_buffer via SpanChan)
    indicator_span_timer_name: str = ""
    objective_span_timer_name: str = ""
    span_channel_capacity: int = 1024

    # hostname/tag emission controls (config.go:74,111)
    # keep hostname EMPTY on emitted metrics instead of defaulting to
    # the os hostname (reference server.go hostname fallback)
    omit_empty_hostname: bool = False
    # per-sink tag exclusion rules: "tagname" strips everywhere,
    # "tagname|sink1|sink2" strips only on the named sinks
    # (reference server.go:1642-1668 setSinkExcludedTags)
    tags_exclude: list[str] = field(default_factory=list)
    # scope overrides for the server's OWN metrics by type
    # ({counter: local|global|default, gauge: ..., ...}; reference
    # scopesFromConfig server.go:278) and extra tags on them
    veneur_metrics_scopes: dict = field(default_factory=dict)
    veneur_metrics_additional_tags: list[str] = field(
        default_factory=list)

    # worker sizing.  num_workers is parsed for config compatibility
    # but is an intentional no-op: the reference shards across N
    # aggregation goroutines (worker.go:31); here ONE device-resident
    # columnar table replaces the shard set, and reader parallelism is
    # num_readers.  num_span_workers sizes the span fan-out pool
    # (reference worker.go:575).
    num_workers: int = 0
    num_span_workers: int = 1

    # profiling knobs: Go-runtime specific (mutex/block profiling,
    # server.go:371-384); parsed for compatibility, documented no-ops
    # under the JAX runtime (enable_profiling drives the jax trace)
    mutex_profile_fraction: int = 0
    block_profile_rate: int = 0
    # log every ingested span (reference debug_ingested_spans)
    debug_ingested_spans: bool = False

    # sinks
    debug_flushed_metrics: bool = False
    blackhole_sink: bool = False
    datadog_api_key: str = ""
    datadog_api_hostname: str = "https://app.datadoghq.com"
    datadog_flush_max_per_body: int = 25000
    # deprecated alias of datadog_flush_max_per_body (example.yaml:188)
    flush_max_per_body: int = 0
    # drop metrics whose name starts with any of these prefixes before
    # the datadog sink (config.go DatadogMetricNamePrefixDrops)
    datadog_metric_name_prefix_drops: list[str] = field(
        default_factory=list)
    # strip tag PREFIXES from metrics with matching name prefixes
    # ([{metric_prefix: "...", tags: [...]}]; example.yaml:301)
    datadog_exclude_tags_prefix_by_prefix_metric: list = field(
        default_factory=list)
    # ring-buffer span capacity for the datadog span sink; the
    # deprecated ssf_buffer_size aliases it (example.yaml:190)
    datadog_span_buffer_size: int = 16384
    ssf_buffer_size: int = 0
    prometheus_repeater_address: str = ""
    prometheus_network_type: str = "tcp"
    flush_file: str = ""  # localfile plugin
    # "native" (readable raw values) or "reference" (byte-exact
    # plugins/s3/csv.go schema: rate conversion, Redshift timestamp,
    # partition column) — applies to flush_file AND the s3 plugin
    flush_file_format: str = "native"
    aws_s3_bucket: str = ""
    aws_region: str = ""
    # SigV4 credentials for the s3 plugin; empty falls back to the
    # AWS_* env vars, and with neither the plugin spools locally
    aws_access_key_id: str = ""
    aws_secret_access_key: str = ""
    # override for S3-compatible stores (minio, test fakes)
    aws_s3_endpoint: str = ""
    # kafka (reference config.go:38-55)
    kafka_broker: str = ""
    kafka_metric_topic: str = "veneur_metrics"
    kafka_check_topic: str = ""
    kafka_event_topic: str = ""
    kafka_span_topic: str = ""
    kafka_span_serialization_format: str = "protobuf"
    # producer tuning (sarama equivalents): flushes batch per interval
    # here, and these bound the per-interval produce batches
    kafka_metric_buffer_bytes: int = 0
    kafka_metric_buffer_messages: int = 0
    kafka_metric_buffer_frequency: str = ""
    kafka_span_buffer_bytes: int = 0
    kafka_span_buffer_mesages: int = 0  # reference's own typo, kept
    kafka_span_buffer_frequency: str = ""
    # acks required from the broker: none, local or all
    kafka_metric_require_acks: str = "all"
    kafka_span_require_acks: str = "all"
    kafka_partitioner: str = "hash"  # hash | random
    kafka_retry_max: int = 0
    # span sampling: percent kept, hashed on a tag (or trace id)
    kafka_span_sample_rate_percent: float = 100.0
    kafka_span_sample_tag: str = ""
    # datadog span half: local trace agent (config.go:20)
    datadog_trace_api_address: str = ""
    # signalfx (config.go:80-93)
    signalfx_api_key: str = ""
    signalfx_endpoint_base: str = "https://ingest.signalfx.com"
    # separate API (metadata) endpoint for dynamic key fetch; empty
    # falls back to endpoint_base (reference SignalfxEndpointAPI)
    signalfx_endpoint_api: str = ""
    signalfx_flush_max_per_body: int = 5000
    signalfx_vary_key_by: str = ""
    signalfx_per_tag_api_keys: dict = field(default_factory=dict)
    # periodically refresh the per-tag key map from the API endpoint
    # (reference server.go:530-541)
    signalfx_dynamic_per_tag_api_keys_enable: bool = False
    signalfx_dynamic_per_tag_api_keys_refresh_period: str = "10m"
    # dimension name carrying the hostname (default "host")
    signalfx_hostname_tag: str = "host"
    # drop metrics/tags by name prefix before emission
    signalfx_metric_name_prefix_drops: list[str] = field(
        default_factory=list)
    signalfx_metric_tag_prefix_drops: list[str] = field(
        default_factory=list)
    # splunk HEC span sink (config.go:95-104, server.go:660-697)
    splunk_hec_address: str = ""
    splunk_hec_token: str = ""
    splunk_span_sample_rate: int = 1
    splunk_hec_batch_size: int = 100
    splunk_hec_submission_workers: int = 1
    splunk_hec_tls_validate_hostname: str = ""
    splunk_hec_send_timeout: str = ""
    splunk_hec_ingest_timeout: str = ""
    # recycle HEC connections after at most this lifetime, jittered
    # so a fleet's connections don't stampede the indexer together
    splunk_hec_max_connection_lifetime: str = ""
    splunk_hec_connection_lifetime_jitter: str = ""
    # newrelic (config.go:63-69)
    newrelic_insert_key: str = ""
    newrelic_account_id: int = 0
    newrelic_region: str = ""
    newrelic_event_type: str = "veneur"
    newrelic_service_check_event_type: str = "veneurCheck"
    newrelic_trace_observer_url: str = ""
    newrelic_metric_endpoint: str = "https://metric-api.newrelic.com"
    newrelic_trace_endpoint: str = "https://trace-api.newrelic.com"
    newrelic_common_tags: list[str] = field(default_factory=list)
    # xray (config.go:129-131)
    xray_address: str = ""
    xray_sample_percentage: float = 100.0
    xray_annotation_tags: list[str] = field(default_factory=list)
    # lightstep (config.go:56-57); trace_lightstep_* are the
    # reference's deprecated aliases (example.yaml:191-204)
    lightstep_access_token: str = ""
    lightstep_collector_host: str = "https://collector.lightstep.com"
    lightstep_maximum_spans: int = 100000
    lightstep_num_clients: int = 1
    lightstep_reconnect_period: str = "5m"
    trace_lightstep_access_token: str = ""
    trace_lightstep_collector_host: str = ""
    trace_lightstep_maximum_spans: int = 0
    trace_lightstep_num_clients: int = 0
    trace_lightstep_reconnect_period: str = ""
    # falconer: thin grpsink wrapper (config.go:25)
    falconer_address: str = ""

    # tls
    tls_key: str = ""
    tls_certificate: str = ""
    tls_authority_certificate: str = ""

    # observability
    enable_profiling: bool = False
    # persistent XLA compilation cache: restart-after-crash (the
    # watchdog model) pays ~0.3s per kernel instead of 20-40s cold
    # compiles.  Empty disables.
    compile_cache_dir: str = ""
    sentry_dsn: str = ""
    stats_address: str = ""

    # tpu table sizing (no reference equivalent; see module docstring)
    tpu_counter_rows: int = 16384
    tpu_gauge_rows: int = 16384
    tpu_histo_rows: int = 16384
    tpu_set_rows: int = 1024
    tpu_compression: float = 100.0
    tpu_histo_slots: int = 512
    # staged-sample threshold that triggers a mid-interval device step,
    # bounding host staging memory and smoothing device work instead of
    # landing the whole interval's batch at the flush boundary
    tpu_stage_flush_samples: int = 65536
    # overlapped device pipeline: detach staged work under the ingest
    # lock and dispatch the jitted combine kernels outside it, with
    # the flush split into begin_swap (locked, O(µs)) / complete_swap
    # (unlocked).  VENEUR_TPU_PIPELINE=0 is the serial escape hatch —
    # every device_step/swap runs inline under the lock as before.
    tpu_pipeline: bool = True
    # multi-reader fused native ingest: with num_readers > 1, each
    # SO_REUSEPORT reader runs the fused parse+probe+combine C pass
    # lock-free against per-reader scratch (probes ride the native
    # index's RCU inner table) and only the O(touched-rows) merge into
    # shared staging holds the table lock.
    # VENEUR_TPU_MULTI_READER_FUSED=0 falls back to the split
    # parse-then-ingest_columns path.
    tpu_multi_reader_fused: bool = True
    # ingest backend for the UDP reader drain tier: "uring" walks an
    # io_uring multishot-receive completion ring straight into the
    # fused native parse (zero syscalls per packet, zero copies
    # before parse), "recvmmsg" is the bulk-drain syscall tier,
    # "python" the per-packet recv loop.  "auto" picks uring iff the
    # startup probe shows the kernel grants it (io_uring + provided
    # buffer rings + multishot recv, i.e. >= 6.0 and not denied by
    # seccomp/sysctl), else recvmmsg.  Runtime failures fall back one
    # tier with a named counter rather than dropping the reader.
    # VENEUR_TPU_INGEST_BACKEND overrides.
    tpu_ingest_backend: str = "auto"
    # provided-buffer pool size per reader ring (power of two).  Each
    # buffer holds one datagram of up to metric_max_length bytes, so
    # the pool is also the max completion batch one parse pass can
    # consume — bigger pools amortize the per-batch Python round
    # further but pin more memory (buffers * (metric_max_length+1)).
    # VENEUR_TPU_URING_BUFFERS overrides.
    tpu_uring_buffers: int = 2048
    # per-reader CPU core pinning: "auto" pins reader i to core
    # i % cpu_count when there are at least as many cores as readers
    # (each shard's ring, pool and parse scratch stay on one core),
    # "off" never pins, or an explicit comma list like "2,3,4,5"
    # assigns reader i to the i-th listed core.
    # VENEUR_TPU_READER_PIN_CORES overrides.
    tpu_reader_pin_cores: str = "auto"
    # compile every canonical kernel shape at startup (against a
    # scratch table) so the first flush interval doesn't eat the XLA
    # compiles; off by default because it adds seconds to process
    # start when the persistent compilation cache is cold
    tpu_warmup: bool = False
    # multi-chip global tier: nonzero runs the table as SPMD sharded
    # planes over a (shard, series) jax Mesh of ALL visible devices,
    # with this many entries on the shard (ingest-parallel) axis; the
    # flush merge rides ICI collectives (parallel/sharded.py).  0 =
    # single-chip table.
    tpu_mesh_shards: int = 0
    # mesh-sharded collective import fold: partition each import
    # cycle's wire stack over the device mesh's shard axis and union
    # the per-device partials with one all_gather + k-scale
    # re-cluster (parallel/sharded.py CollectiveWireFold).  "auto"
    # (default) engages iff more than one device is visible; "on" /
    # "off" force.  VENEUR_TPU_COLLECTIVE_IMPORT overrides; the
    # serial per-wire scan stays available under "off" as the parity
    # oracle.
    tpu_collective_import: str = "auto"
    # per-sink flush fan-out: >0 gives every metric sink its own
    # dedicated worker thread with a one-slot queue, per-sink timeout
    # accounting and retry-with-backoff, so one stalled sink can't
    # stretch the interval for the rest.  0 = legacy shared flush
    # pool.  VENEUR_TPU_SINK_WORKERS overrides.
    tpu_sink_workers: int = 1
    # conservation-ledger strict mode: any interval whose sample
    # accounting doesn't balance (received != staged + status +
    # dropped, or drift against the table's own counters) logs an
    # ERROR and bumps veneur.ledger.imbalance_total instead of a
    # warning.  VENEUR_TPU_LEDGER_STRICT=1 overrides.
    tpu_ledger_strict: bool = False
    # cross-tier flush trace propagation: stamp the flush cycle's
    # (trace_id, span_id) onto forward wires (X-Veneur-Trace header /
    # veneur-trace-* gRPC metadata) and parent import spans under the
    # remote forward span.  Fail-open both ways: old peers ignore the
    # header, missing headers just start no span.
    # VENEUR_TPU_TRACE_PROPAGATION=0 disables.
    tpu_trace_propagation: bool = True
    # sharded global tier: split each flush's gRPC forward wire by
    # route-key consistent hash across the comma-separated
    # forward_address members (one bounded worker per destination),
    # so the keyspace scales across M globals instead of funnelling
    # into one.  M=1 routes byte-identically to the legacy single
    # destination (the parity oracle).  gRPC forwards only; the HTTP
    # path fails open to the legacy POST.
    # VENEUR_TPU_SHARDED_GLOBAL=1 overrides.
    tpu_sharded_global: bool = False
    # live membership for the sharded forward ring: instead of the
    # static comma-separated forward_address list, poll Consul's
    # health API for passing instances of this service and reshard
    # the ring on membership change (same discovery surface the proxy
    # uses, proxy.go:491 RefreshDestinations).  Requires
    # tpu_sharded_global + forward_use_grpc.
    consul_forward_service_name: str = ""
    consul_url: str = "http://127.0.0.1:8500"
    consul_refresh_interval: str = "30s"
    # drain-and-handoff: on shutdown a local runs one final flush and
    # forwards its staged planes flagged drain=true, so a rolling
    # restart conserves the in-flight interval instead of losing it.
    # VENEUR_TPU_DRAIN_ON_SHUTDOWN=0 disables (the pre-PR-11 exit).
    tpu_drain_on_shutdown: bool = True
    # per-destination circuit breaker on the sharded forward workers
    # (and sink flush workers): this many CONSECUTIVE send failures
    # trip the destination open — sends short-circuit instantly,
    # consuming no retry budget — until tpu_breaker_cooldown elapses
    # and a single half-open probe tests recovery.  0 disables the
    # breaker.  VENEUR_TPU_BREAKER_THRESHOLD overrides.
    tpu_breaker_threshold: int = 5
    # how long an open breaker rejects before allowing one probe.
    # VENEUR_TPU_BREAKER_COOLDOWN overrides.
    tpu_breaker_cooldown: str = "5s"
    # outage spool on the sharded forward path: wire batches that
    # can't ship (breaker open, retry budget exhausted, deadline
    # missed) park in a bounded per-destination spool and replay —
    # flagged veneur-replay — when the destination recovers, so an
    # outage shorter than the spool's caps loses ZERO samples instead
    # of merely attributing the loss.  VENEUR_TPU_FORWARD_SPOOL=0
    # disables (pre-PR-12 drop-and-attribute behavior).
    tpu_forward_spool: bool = True
    # total spooled wire bytes across all destinations; adding past
    # the cap evicts oldest-first (credited spool_expired, reason
    # "cap").  VENEUR_TPU_FORWARD_SPOOL_MAX_BYTES overrides.
    tpu_forward_spool_max_bytes: int = 32 * 1024 * 1024
    # spooled wires older than this expire (credited spool_expired,
    # reason "age") — the bound on how stale a replayed sample can
    # be.  VENEUR_TPU_FORWARD_SPOOL_MAX_AGE overrides.
    tpu_forward_spool_max_age: str = "300s"
    # optional disk spool directory (s3-sink-style segment files,
    # <dir>/<dest>/<seq>.wire); empty = in-memory only.
    # VENEUR_TPU_FORWARD_SPOOL_DIR overrides.
    tpu_forward_spool_dir: str = ""
    # overload control (core/overload.py): admission buckets,
    # priority-tiered shedding, and the flush-overrun coalesce
    # watchdog.  With the subsystem on but no tenant rate configured
    # and pressure disengaged, the ingest hot path is untouched (one
    # boolean per batch).  VENEUR_TPU_OVERLOAD=0 removes it entirely.
    tpu_overload: bool = True
    # tag key whose value names the tenant for admission buckets and
    # shed attribution; series without the tag account to tenant
    # "default".  VENEUR_TPU_OVERLOAD_TENANT_TAG overrides.
    tpu_overload_tenant_tag: str = "tenant"
    # per-tenant admitted samples/second (token-bucket rate) for
    # non-counter classes; 0 = no tenant budget (counters always
    # land: their increments fold exactly regardless of load).
    # VENEUR_TPU_OVERLOAD_TENANT_RATE overrides.
    tpu_overload_tenant_rate: float = 0.0
    # bucket burst depth in samples; 0 = 2x the rate.
    # VENEUR_TPU_OVERLOAD_TENANT_BURST overrides.
    tpu_overload_tenant_burst: float = 0.0
    # distinct tenants tracked before the rest aggregate into the
    # "other" bucket.  VENEUR_TPU_OVERLOAD_MAX_TENANTS overrides.
    tpu_overload_max_tenants: int = 256
    # pressure-signal ceilings ("1.0 = saturated" per dimension):
    # host staging depth in samples, class-index occupancy fraction,
    # and flush duration as a fraction of the interval (EWMA).  The
    # overall score is the max, entry at >= 1.0, exit below
    # tpu_overload_exit_ratio — the hysteresis band.
    # VENEUR_TPU_OVERLOAD_STAGING_HI / _OCCUPANCY_HI / _LAG_HI /
    # _EXIT_RATIO override.
    tpu_overload_staging_hi: int = 1_000_000
    tpu_overload_occupancy_hi: float = 0.95
    tpu_overload_lag_hi: float = 1.0
    tpu_overload_exit_ratio: float = 0.7
    # flush-overrun watchdog: a flush past its interval budget makes
    # the next tick coalesce (one swap covering two intervals, named
    # in the ledger + veneur.flush.coalesced_total) so staging stays
    # bounded.  VENEUR_TPU_OVERLOAD_COALESCE=0 keeps the old
    # warn-and-continue behavior.
    tpu_overload_coalesce: bool = True
    # crash-riding checkpoints (ops/checkpoint.py): every interval the
    # checkpointer copies the open interval's host staging and writes
    # an atomically-renamed cumulative segment under
    # tpu_checkpoint_dir, so a SIGKILL/OOM loses at most one
    # checkpoint interval of ingest — and recovery replays the rest
    # through the import wire, flagged veneur-recovery.  Enabled iff
    # the dir is set AND the interval is > 0.
    # VENEUR_TPU_CHECKPOINT_INTERVAL overrides ("0" disables).
    tpu_checkpoint_interval: str = "1s"
    # segment directory; empty disables checkpointing entirely.
    # VENEUR_TPU_CHECKPOINT_DIR overrides.
    tpu_checkpoint_dir: str = ""
    # global-side keyspace-arc handoff on scale-out: when enabled, a
    # global told of new ring members (Server.arc_handoff) ships the
    # resident rows whose route-keys fall in the new members' arcs
    # over the import wire, flagged veneur-handoff, before the locals
    # flip their ring epoch — conserving mid-interval mass
    # cluster-wide.  VENEUR_TPU_ARC_HANDOFF=0 disables.
    tpu_arc_handoff: bool = True
    # signal history plane (observe/signals.py): rows retained in the
    # columnar per-flush signal ring served at /debug/signals.
    # VENEUR_TPU_SIGNAL_HISTORY overrides; 0 disables the plane (and
    # with it the flight recorder, which watches its rows).
    tpu_signal_history: int = 512
    # anomaly flight recorder (observe/recorder.py): directory for
    # CRC-framed incident bundles.  Empty keeps bundles in a bounded
    # in-memory store (still served at /debug/flight); set to persist
    # across restarts.  VENEUR_TPU_FLIGHT_DIR overrides.
    tpu_flight_dir: str = ""
    # flight-recorder retention: bundle count and total bytes, evict
    # oldest past either; and the per-trigger cooldown so a flapping
    # trigger writes one bundle per window, not one per flush.
    # VENEUR_TPU_FLIGHT_MAX_BUNDLES / VENEUR_TPU_FLIGHT_MAX_BYTES /
    # VENEUR_TPU_FLIGHT_COOLDOWN override.
    tpu_flight_max_bundles: int = 64
    tpu_flight_max_bytes: int = 67108864
    tpu_flight_cooldown: str = "30s"
    # /debug/cluster peer list (comma separated http hosts); empty
    # falls back to this node's forward destinations, so a local tier
    # serves its globals' summaries with zero extra config.
    # VENEUR_TPU_CLUSTER_PEERS overrides.
    tpu_cluster_peers: str = ""
    # collective forward plane-exchange: when this local and its
    # global destinations are processes of one init_process_mesh, the
    # sharded forward hop ships each mesh peer's routed rows as fixed
    # -schema tensor planes over ONE all_to_all per cycle instead of
    # serialize->gRPC->decode (parallel/collective_forward.py).
    # "auto" (default) engages iff tpu_collective_peers names at
    # least one destination; "on" / "off" force.  The gob/gRPC wire
    # stays the cross-slice fallback, the bit-parity oracle, and the
    # only recovery path — drain/replay/checkpoint wires never take
    # the collective, and any exchange failure falls open to the wire
    # with a named counter.  VENEUR_TPU_COLLECTIVE_FORWARD overrides.
    tpu_collective_forward: str = "auto"
    # which forward ring destinations are mesh peers: comma list of
    # dest_addr=mesh_process_index (e.g.
    # "10.0.0.2:8128=1,10.0.0.3:8128=2").  Destinations not listed
    # always ride the wire.  Requires tpu_sharded_global +
    # forward_use_grpc.  VENEUR_TPU_COLLECTIVE_PEERS overrides.
    tpu_collective_peers: str = ""
    # fixed plane-schema capacity per destination block: rows per
    # metric class, and identity bytes per row (type + scope + name +
    # tags, length-prefixed).  Rows over either cap are REJECTED to
    # the wire (never truncated).  VENEUR_TPU_COLLECTIVE_MAX_ROWS /
    # VENEUR_TPU_COLLECTIVE_KEY_BYTES override.
    tpu_collective_max_rows: int = 512
    tpu_collective_key_bytes: int = 192

    def resolve_aliases(self) -> None:
        """Fold the reference's deprecated alias keys into their
        replacements (example.yaml:187-204): deprecated value applies
        only when the replacement still holds its default."""
        if self.grpc_address and not self.grpc_listen_addresses:
            addr = self.grpc_address
            if "://" not in addr:
                addr = "tcp://" + addr
            self.grpc_listen_addresses = [addr]
        if self.flush_max_per_body and \
                self.datadog_flush_max_per_body == 25000:
            self.datadog_flush_max_per_body = self.flush_max_per_body
        if self.ssf_buffer_size and \
                self.datadog_span_buffer_size == 16384:
            self.datadog_span_buffer_size = self.ssf_buffer_size
        if self.trace_lightstep_access_token and \
                not self.lightstep_access_token:
            self.lightstep_access_token = \
                self.trace_lightstep_access_token
        if self.trace_lightstep_collector_host and \
                self.lightstep_collector_host == \
                "https://collector.lightstep.com":
            self.lightstep_collector_host = \
                self.trace_lightstep_collector_host
        if self.trace_lightstep_maximum_spans and \
                self.lightstep_maximum_spans == 100000:
            self.lightstep_maximum_spans = \
                self.trace_lightstep_maximum_spans
        if self.trace_lightstep_num_clients and \
                self.lightstep_num_clients == 1:
            self.lightstep_num_clients = \
                self.trace_lightstep_num_clients
        if self.trace_lightstep_reconnect_period and \
                self.lightstep_reconnect_period == "5m":
            self.lightstep_reconnect_period = \
                self.trace_lightstep_reconnect_period

    def interval_seconds(self) -> float:
        return parse_duration(self.interval)

    def is_local(self) -> bool:
        """A node with a forward destination — a static address or a
        discovered service — is a 'local' tier instance (reference
        server.go:1609 IsLocal)."""
        return bool(self.forward_address
                    or self.consul_forward_service_name)

    def consul_refresh_interval_seconds(self) -> float:
        return parse_duration(self.consul_refresh_interval)

    def breaker_cooldown_seconds(self) -> float:
        return parse_duration(self.tpu_breaker_cooldown)

    def forward_spool_max_age_seconds(self) -> float:
        return parse_duration(self.tpu_forward_spool_max_age)

    def checkpoint_interval_seconds(self) -> float:
        return parse_duration(self.tpu_checkpoint_interval or "0")

    def checkpoint_enabled(self) -> bool:
        return bool(self.tpu_checkpoint_dir) and \
            self.checkpoint_interval_seconds() > 0

    def validate(self) -> list[str]:
        problems = []
        try:
            if self.interval_seconds() <= 0:
                problems.append("interval must be positive")
        except ValueError as e:
            problems.append(str(e))
        for p in self.percentiles:
            if not (0.0 < p < 1.0):
                problems.append(f"percentile out of range: {p}")
        known_aggs = {"min", "max", "median", "avg", "count", "sum",
                      "hmean"}
        for a in self.aggregates:
            if a not in known_aggs:
                problems.append(f"unknown aggregate: {a}")
        if self.metric_max_length <= 0:
            problems.append("metric_max_length must be positive")
        if self.forward_json_schema not in ("reference", "native"):
            problems.append(
                "forward_json_schema must be 'reference' or 'native'")
        if self.flush_file_format not in ("native", "reference"):
            problems.append(
                "flush_file_format must be 'native' or 'reference'")
        if self.percentile_naming not in ("precise", "reference"):
            problems.append(
                "percentile_naming must be 'precise' or 'reference'")
        if self.quantile_interpolation not in ("interp", "reference"):
            problems.append(
                "quantile_interpolation must be 'interp' or "
                "'reference'")
        for n in ("tpu_counter_rows", "tpu_gauge_rows", "tpu_histo_rows",
                  "tpu_set_rows", "span_channel_capacity",
                  "reader_batch_packets", "tpu_stage_flush_samples"):
            if getattr(self, n) <= 0:
                problems.append(f"{n} must be positive")
        if str(self.tpu_collective_import).lower() not in (
                "auto", "on", "off", "1", "0", "true", "false",
                "yes", "no"):
            problems.append(
                "tpu_collective_import must be auto, on or off")
        if self.tpu_ingest_backend not in ("auto", "uring",
                                           "recvmmsg", "python"):
            problems.append(
                "tpu_ingest_backend must be auto, uring, recvmmsg "
                "or python")
        if self.tpu_uring_buffers < 2 or \
                self.tpu_uring_buffers > 32768 or \
                self.tpu_uring_buffers & (self.tpu_uring_buffers - 1):
            problems.append(
                "tpu_uring_buffers must be a power of two in "
                "[2, 32768]")
        pin = self.tpu_reader_pin_cores
        if pin not in ("auto", "off"):
            try:
                cores = [int(c) for c in pin.split(",") if c.strip()]
                if not cores or any(c < 0 for c in cores):
                    raise ValueError
            except ValueError:
                problems.append(
                    "tpu_reader_pin_cores must be auto, off or a "
                    "comma list of core ids")
        if "," in self.forward_address and not self.tpu_sharded_global:
            problems.append(
                "multiple forward_address members need "
                "tpu_sharded_global (the legacy path dials one)")
        if self.consul_forward_service_name:
            if not self.tpu_sharded_global:
                problems.append(
                    "consul_forward_service_name needs "
                    "tpu_sharded_global (discovery drives the ring)")
            if not self.forward_use_grpc:
                problems.append(
                    "consul_forward_service_name needs "
                    "forward_use_grpc (the sharded ring is gRPC-only)")
            try:
                if self.consul_refresh_interval_seconds() <= 0:
                    problems.append(
                        "consul_refresh_interval must be positive")
            except ValueError as e:
                problems.append(str(e))
        if str(self.tpu_collective_forward).lower() not in (
                "auto", "on", "off", "1", "0", "true", "false",
                "yes", "no"):
            problems.append(
                "tpu_collective_forward must be auto, on or off")
        if self.tpu_collective_peers:
            if not self.tpu_sharded_global:
                problems.append(
                    "tpu_collective_peers needs tpu_sharded_global "
                    "(the collective rides the sharded ring split)")
            if not self.forward_use_grpc:
                problems.append(
                    "tpu_collective_peers needs forward_use_grpc "
                    "(the wire fallback is gRPC-only)")
            try:
                from veneur_tpu.forward.collective import parse_peers
                parse_peers(self.tpu_collective_peers)
            except ValueError as e:
                problems.append(str(e))
        for n in ("tpu_collective_max_rows",
                  "tpu_collective_key_bytes"):
            if getattr(self, n) <= 0:
                problems.append(f"{n} must be positive")
        if self.tpu_breaker_threshold < 0:
            problems.append("tpu_breaker_threshold must be >= 0")
        try:
            if self.breaker_cooldown_seconds() <= 0:
                problems.append("tpu_breaker_cooldown must be positive")
        except ValueError as e:
            problems.append(str(e))
        if self.tpu_forward_spool_max_bytes <= 0:
            problems.append(
                "tpu_forward_spool_max_bytes must be positive")
        try:
            if self.forward_spool_max_age_seconds() <= 0:
                problems.append(
                    "tpu_forward_spool_max_age must be positive")
        except ValueError as e:
            problems.append(str(e))
        if self.tpu_overload_tenant_rate < 0:
            problems.append("tpu_overload_tenant_rate must be >= 0")
        if self.tpu_overload_tenant_burst < 0:
            problems.append("tpu_overload_tenant_burst must be >= 0")
        if self.tpu_overload_max_tenants <= 0:
            problems.append("tpu_overload_max_tenants must be positive")
        if self.tpu_overload_staging_hi <= 0:
            problems.append("tpu_overload_staging_hi must be positive")
        if not (0.0 < self.tpu_overload_occupancy_hi <= 1.0):
            problems.append(
                "tpu_overload_occupancy_hi must be in (0, 1]")
        if self.tpu_overload_lag_hi <= 0:
            problems.append("tpu_overload_lag_hi must be positive")
        if not (0.0 < self.tpu_overload_exit_ratio <= 1.0):
            problems.append(
                "tpu_overload_exit_ratio must be in (0, 1]")
        if self.kafka_span_serialization_format not in ("protobuf",
                                                        "json"):
            problems.append(
                "kafka_span_serialization_format must be "
                "'protobuf' or 'json', got "
                f"{self.kafka_span_serialization_format!r}")
        for key in ("kafka_metric_require_acks",
                    "kafka_span_require_acks"):
            if getattr(self, key) not in ("none", "local", "all"):
                problems.append(
                    f"{key} must be none, local or all")
        if self.kafka_partitioner not in ("hash", "random"):
            problems.append("kafka_partitioner must be hash or random")
        if not (0.0 < self.kafka_span_sample_rate_percent <= 100.0):
            problems.append(
                "kafka_span_sample_rate_percent must be in (0, 100]")
        if self.num_span_workers <= 0:
            problems.append("num_span_workers must be positive")
        for scope_type, scope in self.veneur_metrics_scopes.items():
            if scope_type not in ("counter", "gauge", "histogram",
                                  "set", "status"):
                problems.append(
                    f"veneur_metrics_scopes: unknown type "
                    f"{scope_type!r}")
            if scope not in ("local", "global", "default"):
                problems.append(
                    f"veneur_metrics_scopes: unknown scope {scope!r}")
        for rule in self.datadog_exclude_tags_prefix_by_prefix_metric:
            if not (isinstance(rule, dict) and "metric_prefix" in rule):
                problems.append(
                    "datadog_exclude_tags_prefix_by_prefix_metric "
                    "entries need a metric_prefix")
        return problems


@dataclass
class ProxyConfig:
    """veneur-proxy configuration (reference config_proxy.go; the
    full 23-key surface parses)."""
    debug: bool = False
    http_address: str = ""
    grpc_address: str = ""
    # static destination list (comma separated), XOR consul discovery
    forward_address: str = ""
    consul_forward_service_name: str = ""
    consul_refresh_interval: str = "30s"
    consul_url: str = "http://127.0.0.1:8500"
    forward_timeout: float = 10.0
    stats_address: str = ""
    # SEPARATE destination set for gRPC-forwarded metrics (reference
    # proxy.go:138,184 ForwardGRPCDestinations); unset falls back to
    # the main ring
    grpc_forward_address: str = ""
    consul_forward_grpc_service_name: str = ""
    # datadog-format trace proxying: POST /spans bodies hash by trace
    # id across these destinations (proxy.go:543 ProxyTraces)
    trace_address: str = ""
    consul_trace_service_name: str = ""
    # accepted for config compat; unused even by the reference's
    # proxy.go (vestigial)
    trace_api_address: str = ""
    # the proxy's OWN telemetry as SSF spans to this address
    # (proxy.go:219-250), with the trace client's buffer knobs
    ssf_destination_address: str = ""
    tracing_client_capacity: int = 1024
    tracing_client_flush_interval: str = "500ms"
    tracing_client_metrics_interval: str = "1s"
    # cadence of the proxy's periodic runtime stats (proxy.go:210)
    runtime_metrics_interval: str = "10s"
    # Go http.Transport pool tuning: parsed for compat, documented
    # no-ops (forward connections here are one persistent HTTP
    # connection per destination and persistent gRPC channels, not a
    # pooled Go transport)
    idle_connection_timeout: str = ""
    max_idle_conns: int = 0
    max_idle_conns_per_host: int = 0
    # Go pprof profiling flag: no-op (the proxy does no device work)
    enable_profiling: bool = False
    sentry_dsn: str = ""
    # dial TLS gRPC globals (same semantics as the server's
    # forward_grpc_tls_ca)
    forward_grpc_tls: bool = False
    forward_grpc_tls_ca: str = ""
    # columnar route path: native batched decode + vectorized
    # consistent-hash assignment + per-destination worker pool
    # (VENEUR_TPU_COLUMNAR_PROXY=0 falls back to the per-item legacy
    # loop, which stays as the bit-parity oracle)
    tpu_columnar_proxy: bool = True
    # per-destination worker pool knobs (VENEUR_TPU_PROXY_DEST_QUEUE /
    # VENEUR_TPU_PROXY_SEND_RETRIES / VENEUR_TPU_PROXY_SEND_BACKOFF):
    # bounded handoff queue depth per destination, in-worker retry
    # count, and the exponential-backoff base between retries
    tpu_proxy_dest_queue: int = 8
    tpu_proxy_send_retries: int = 2
    tpu_proxy_send_backoff: float = 0.25
    # proxy-side signal history (same ring as the server's, with the
    # proxy's ProxyLedger/destpool signal set, sampled at the
    # discovery-refresh cadence); VENEUR_TPU_SIGNAL_HISTORY overrides,
    # 0 disables
    tpu_signal_history: int = 512

    def consul_refresh_interval_seconds(self) -> float:
        return parse_duration(self.consul_refresh_interval)

    def runtime_metrics_interval_seconds(self) -> float:
        return parse_duration(self.runtime_metrics_interval or "10s")

    def validate(self) -> list[str]:
        problems = []
        # any ONE routing surface suffices (the reference runs
        # trace-only or grpc-only proxies with AcceptingForwards
        # false, proxy.go:131-139)
        if not (self.forward_address or
                self.consul_forward_service_name or
                self.grpc_forward_address or
                self.consul_forward_grpc_service_name or
                self.trace_address or
                self.consul_trace_service_name):
            problems.append(
                "proxy needs at least one destination surface: "
                "forward_address / grpc_forward_address / "
                "trace_address (or their consul service names)")
        try:
            if self.consul_refresh_interval_seconds() <= 0:
                problems.append(
                    "consul_refresh_interval must be positive")
        except ValueError as e:
            problems.append(str(e))
        return problems


def _coerce(cls, name: str, raw: str):
    """Coerce an environment-variable string to the field's type."""
    current = getattr(cls(), name)
    if isinstance(current, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(current, int):
        return int(raw)
    if isinstance(current, float):
        return float(raw)
    if isinstance(current, list):
        items = [x.strip() for x in raw.split(",") if x.strip()]
        if current and isinstance(current[0], float):
            return [float(x) for x in items]
        return items
    if isinstance(current, dict):
        # "k1:v1,k2:v2" (the signalfx per-tag key map shape)
        out = {}
        for item in raw.split(","):
            if item.strip():
                k, _, v = item.partition(":")
                out[k.strip()] = v.strip()
        return out
    return raw


def read_config(path: str | None = None, data: dict | None = None,
                strict: bool = False, env: dict | None = None,
                cls=Config):
    """Load config: YAML file -> env overrides -> defaults/validation.

    ``strict`` mirrors -validate-config-strict (cmd/veneur/main.go:17):
    unknown keys become errors instead of warnings.  ``cls`` selects
    the config dataclass (Config or ProxyConfig — the reference's
    config.go / config_proxy.go split).
    """
    field_types = {f.name: f.type for f in fields(cls)}
    raw: dict = {}
    if path is not None:
        if yaml is None:
            raise RuntimeError("pyyaml unavailable")
        with open(path) as f:
            raw = yaml.safe_load(f) or {}
    if data:
        raw.update(data)

    cfg = cls()
    unknown = []
    for key, value in raw.items():
        if key in field_types:
            if value is not None:
                setattr(cfg, key, value)
        else:
            unknown.append(key)
    if unknown:
        msg = f"unknown config keys: {sorted(unknown)}"
        if strict:
            raise ValueError(msg)
        log.warning(msg)

    env = os.environ if env is None else env
    for name in field_types:
        env_key = "VENEUR_" + name.upper()
        if env_key in env:
            setattr(cfg, name, _coerce(cls, name, env[env_key]))

    if hasattr(cfg, "resolve_aliases"):
        cfg.resolve_aliases()
    problems = cfg.validate()
    if problems:
        raise ValueError("; ".join(problems))
    return cfg
