#!/usr/bin/env python3
"""Start-up proof on the chip: local -> global through ``Server``.

One process, the only one that touches JAX.  It builds a global and a
local ``Server`` the way ``veneur_tpu/cli/main.py`` does (``Server(cfg)``,
``.start()``), at the default table sizes and every default gate, sends
real DogStatsD datagrams made from ``--seed`` to the local's UDP
listener for three flush intervals, and compares what both sinks
received with a plain numpy reference computed from the bytes that
were sent.  Every rate it prints is a smoke reading, not a benchmark.

    python chip_smoke.py              # one chip; fails without a TPU
    python chip_smoke.py --chips 4    # the mesh-sharded global only

Earlier lines of the output are one JSON object each; the last line is
``{"ok": true, "device": {...}}`` and is printed only if every check
passed.  Any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import socket
import sys
import threading
import time

import numpy as np

PERCENTILES = (0.5, 0.9, 0.99)
MAX_DATAGRAM = 4096
INFLIGHT = 24           # datagrams sent ahead of the reader
SETTLE_INTERVALS = 8    # for a window to come out of both sinks


@dataclasses.dataclass(frozen=True)
class Scale:
    """How much traffic one window carries, and what holds it.

    The defaults are ``BASELINE.json`` configs 1-4 cut to the default
    table (16,384 rows a class, 1,024 set rows).  ``table`` overrides
    table sizes for the CPU rehearsal only; ``main()`` never sets it.
    """

    timers: int = 10_000
    samples_per_timer: int = 100
    counters: int = 10_000
    gauges: int = 10_000
    global_counters: int = 1_000    # more counters, veneurglobalonly
    # the most the default gates admit, not ISSUE 22's 1,000: at or
    # above tpu_overload_occupancy_hi (0.95) of a class's rows the
    # overload control engages by design and samples sets first
    # (core/overload.py), and 0.95 x 1,024 set rows = 972.8
    sets: int = 972
    set_members: int = 1_000_000    # unique, over the checked windows
    windows: int = 3
    interval_s: int = 10            # upstream's default
    table: dict = dataclasses.field(default_factory=dict)


def emit(**kw) -> None:
    print(json.dumps(kw, sort_keys=True), flush=True)


def _round(x: float | None, digits: int) -> float | None:
    """A window that failed midway has readings it never took."""
    return None if x is None else round(x, digits)


class Failed(Exception):
    """A check did not hold; the run exits non-zero."""


# ----------------------------------------------------------------------
# traffic: bytes made from the seed, by numpy and str only

def _tag_suffixes(rng, n: int, extra: str = "") -> list[str]:
    """``|#k:v,...`` with 2-8 tags for each of ``n`` series."""
    counts = rng.integers(2, 9, n)
    vals = rng.integers(0, 50, (n, 8))
    out = []
    for c, row in zip(counts, vals):
        tags = [f"t{j}:v{v}" for j, v in enumerate(row[:c])]
        if extra:
            tags[-1] = extra
        out.append("|#" + ",".join(tags))
    return out


class Traffic:
    """Series identities for the run, and the datagrams of one window
    after another.  Every window carries the same mix and its share
    of new set members, so the checked ones hold ``set_members``."""

    def __init__(self, scale: Scale, seed: int):
        self.scale = scale
        self.rng = np.random.default_rng(seed)
        r = self.rng
        s = scale
        self.timer = [f"smoke.timer.{i:05d}" for i in range(s.timers)]
        self.timer_sfx = _tag_suffixes(r, s.timers)
        self.timer_scale = r.uniform(1.0, 500.0, s.timers)
        self.counter = [f"smoke.count.{i:05d}"
                        for i in range(s.counters)]
        self.counter_sfx = _tag_suffixes(r, s.counters)
        self.gauge = [f"smoke.gauge.{i:05d}" for i in range(s.gauges)]
        self.gauge_sfx = _tag_suffixes(r, s.gauges)
        self.gcount = [f"smoke.gcount.{i:05d}"
                       for i in range(s.global_counters)]
        self.gcount_sfx = _tag_suffixes(r, s.global_counters,
                                        extra="veneurglobalonly")
        self.sets = [f"smoke.set.{i:05d}" for i in range(s.sets)]
        self.set_sfx = _tag_suffixes(r, s.sets)
        self._next_member = 0

    def window(self) -> list[bytes]:
        s, r = self.scale, self.rng
        lines: list[str] = []
        # timers: lognormal latencies, three decimals on the wire
        vals = (r.lognormal(0.0, 0.6, (s.timers, s.samples_per_timer))
                * self.timer_scale[:, None])
        for name, sfx, row in zip(self.timer, self.timer_sfx, vals):
            lines += [f"{name}:{v:.3f}|ms{sfx}" for v in row]
        # counters: one to three small integer increments a series
        for names, sfxs in ((self.counter, self.counter_sfx),
                            (self.gcount, self.gcount_sfx)):
            reps = r.integers(1, 4, len(names))
            incs = r.integers(1, 10, (len(names), 3))
            for name, sfx, n, row in zip(names, sfxs, reps, incs):
                lines += [f"{name}:{v}|c{sfx}" for v in row[:n]]
        # gauges: written once, quarter steps (exact in float32)
        g = r.integers(0, 1 << 18, s.gauges) / 4.0
        lines += [f"{n}:{v}|g{x}"
                  for n, x, v in zip(self.gauge, self.gauge_sfx, g)]
        # sets: members unique over the whole run, 5 % sent twice
        n_new = -(-s.set_members // s.windows)
        ids = np.arange(self._next_member, self._next_member + n_new)
        self._next_member += n_new
        ids = np.concatenate([ids, r.choice(ids, n_new // 20)])
        which = r.integers(0, s.sets, len(ids))
        lines += [f"{self.sets[w]}:m{i}|s{self.set_sfx[w]}"
                  for w, i in zip(which, ids)]
        order = r.permutation(len(lines))
        out: list[bytes] = []
        cur: list[str] = []
        size = 0
        for j in order:
            ln = lines[j]
            if size + len(ln) + 1 > MAX_DATAGRAM and cur:
                out.append("\n".join(cur).encode())
                cur, size = [], 0
            cur.append(ln)
            size += len(ln) + 1
        if cur:
            out.append("\n".join(cur).encode())
        return out


# ----------------------------------------------------------------------
# the plain reference: parses the sent bytes, nothing from veneur_tpu

def _key(name: bytes | str, tags) -> tuple:
    """A series' identity as both sides spell it: the name and its
    sorted tags, the scope tag aside."""
    if isinstance(name, bytes):
        name = name.decode()
    tags = [t.decode() if isinstance(t, bytes) else t for t in tags]
    return name, tuple(sorted(t for t in tags
                              if t != "veneurglobalonly"))


def reference(datagrams: list[bytes]) -> dict:
    timers: dict = {}
    counters: dict = {}
    gcounters: dict = {}
    gauges: dict = {}
    sets: dict = {}
    n_lines = 0
    for dg in datagrams:
        for ln in dg.split(b"\n"):
            n_lines += 1
            head, typ, tags = ln.split(b"|")
            name, val = head.split(b":")
            raw = (name, tags)
            if typ == b"ms":
                timers.setdefault(raw, []).append(float(val))
            elif typ == b"c":
                into = (gcounters if b"veneurglobalonly" in tags
                        else counters)
                into[raw] = into.get(raw, 0) + int(val)
            elif typ == b"g":
                gauges[raw] = float(val)
            elif typ == b"s":
                sets.setdefault(raw, set()).add(val)
            else:
                raise Failed(f"reference: unknown type in {ln!r}")

    def keyed(d: dict) -> dict:
        out = {_key(name, tags[1:].split(b",")): v
               for (name, tags), v in d.items()}
        if len(out) != len(d):
            raise Failed("reference: two series share one identity")
        return out
    return {"lines": n_lines, "timers": keyed(timers),
            "counters": keyed(counters),
            "gcounters": keyed(gcounters), "gauges": keyed(gauges),
            "sets": keyed(sets)}


def _quantile_ok(sorted_x: np.ndarray, q: float, got: float) -> bool:
    """Within 1 % of numpy.quantile, or inside the neighbouring order
    statistics: at 100 samples a series two interpolation rules
    differ by more than the budget."""
    n = len(sorted_x)
    want = float(np.quantile(sorted_x, q))
    if abs(got - want) <= 0.01 * abs(want):
        return True
    pos = q * (n - 1)
    lo = sorted_x[max(0, math.floor(pos) - 1)]
    hi = sorted_x[min(n - 1, math.ceil(pos) + 1)]
    slack = 1e-5 * max(abs(lo), abs(hi), 1.0)   # float32 planes
    return lo - slack <= got <= hi + slack


# ----------------------------------------------------------------------
# what the sinks received

def _sink_values(batches) -> dict:
    """``(name, tags) -> [values]`` over the smoke's own series."""
    out: dict = {}
    for b in batches:
        for m in b:
            if m.name.startswith("smoke."):
                out.setdefault(_key(m.name, m.tags), []).append(m.value)
    return out


def _suffixed(values: dict, suffix: str) -> dict:
    cut = len(suffix)
    return {(name[:-cut], tags): v for (name, tags), v in values.items()
            if name.endswith(suffix)}


def check_window(k: int, ref: dict, local: dict, glob: dict,
                 strict: bool) -> dict:
    """Compare one window's sink output with the reference.  A strict
    window must have flushed exactly once a series; the warm-up may
    have been split across flushes, so only its sums are held."""
    bad: list[str] = []

    def fail(msg):
        if len(bad) < 12:
            bad.append(f"window {k}: {msg}")

    def exact(kind, want, got):
        for key, w in want.items():
            vals = got.get(key)
            if vals is None:
                fail(f"{kind} {key} missing")
            elif strict and len(vals) != 1:
                fail(f"{kind} {key} flushed {len(vals)} times")
            elif float(sum(vals)) != float(w):
                fail(f"{kind} {key}: {sum(vals)} != {w}")

    exact("counter", ref["counters"], local)
    exact("global counter", ref["gcounters"], glob)
    exact("timer count",
          {key: len(v) for key, v in ref["timers"].items()},
          _suffixed(local, ".count"))
    worst_q = 0.0
    worst_card = 0.0
    if strict:
        exact("gauge", ref["gauges"], local)
        pct = {q: _suffixed(glob, f".{int(round(q * 100))}percentile")
               for q in PERCENTILES}
        for key, xs in ref["timers"].items():
            xs = np.sort(np.asarray(xs))
            for q in PERCENTILES:
                vals = pct[q].get(key)
                if vals is None or len(vals) != 1:
                    fail(f"timer {key} p{q}: flushed "
                         f"{0 if vals is None else len(vals)} times")
                    continue
                want = float(np.quantile(xs, q))
                worst_q = max(worst_q,
                              abs(vals[0] - want) / abs(want))
                if not _quantile_ok(xs, q, vals[0]):
                    fail(f"timer {key} p{q}: {vals[0]} vs {want}")
        for key, members in ref["sets"].items():
            vals = glob.get(key)
            if vals is None or len(vals) != 1:
                fail(f"set {key}: flushed "
                     f"{0 if vals is None else len(vals)} times")
                continue
            err = abs(vals[0] - len(members)) / len(members)
            worst_card = max(worst_card, err)
            if err > 0.03:
                fail(f"set {key}: {vals[0]} vs {len(members)}")
    else:
        for key in ref["gauges"]:
            if key not in local:
                fail(f"gauge {key} missing")
    if bad:
        raise Failed("; ".join(bad))
    return {"worst_quantile_rel_err": worst_q,
            "worst_cardinality_rel_err": worst_card}


# ----------------------------------------------------------------------
# the run

def _sealed(server) -> int:
    """Sequence number of the server's last finished flush."""
    last = server.ledger.last()
    return last.seq if last is not None else 0


def _pressure(server) -> dict:
    """The server's overload pressure state (core/overload.py)."""
    p = server.overload.pressure.to_dict()
    return {k: p[k] for k in ("engaged", "level", "score",
                              "transitions")}


def _took_in(server, seq0: int) -> list[tuple[int, int]]:
    """(flush seq, DogStatsD samples received) for the intervals the
    server closed after flush ``seq0`` that received any."""
    return [(r.seq, r.received["dogstatsd"])
            for r in server.ledger.records()
            if r.seq > seq0 and r.received.get("dogstatsd")]


def _wait(cond, timeout: float, what: str, poll: float = 0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(poll)
    raise Failed(f"timed out after {timeout:.0f}s waiting for {what}")


class Sender(threading.Thread):
    """Writes one window after another to the local's UDP listener.
    Never touches JAX: it reads the servers' host-side counters to
    pace itself (the reader must not fall more than ``INFLIGHT``
    datagrams behind, or loopback UDP drops) and to see flushes.

    Window 0 is the warm-up: cold compiles block the reader longer
    than one interval, so it may straddle flushes, and only its sums
    are held.  Every later window must fit one interval of the local
    and start with the local's overload pressure released (an engaged
    server samples the sets it ingests): one that straddles a flush,
    or finds the local still engaged, fails the run."""

    def __init__(self, scale: Scale, windows: list[list[bytes]],
                 local, glob, lcap, gcap):
        super().__init__(name="smoke-sender", daemon=True)
        self.scale = scale
        self.windows = windows
        self.local, self.glob = local, glob
        self.lcap, self.gcap = lcap, gcap
        self.marks: list[dict] = []
        self.error: BaseException | None = None

    def run(self):
        try:
            for k, dgs in enumerate(self.windows):
                self._one(k, dgs)
        except BaseException as e:   # surfaces in the main thread
            self.error = e

    def _received(self) -> int:
        return self.local.stats.get("received_dogstatsd-udp", 0)

    def _one(self, k: int, dgs: list[bytes]) -> None:
        iv = float(self.scale.interval_s)
        # start right after a flush of the local is seen and done (its
        # ledger sealed a record), most of the interval still ahead
        seen = [_sealed(self.local)]

        def after_flush():
            n = _sealed(self.local)
            fresh, seen[0] = n > seen[0], n
            return fresh and time.time() % iv < 0.3 * iv
        _wait(after_flush, 4 * iv + 120,
              f"a flush of the local to start window {k} after")
        mark = {"window": k, "strict": k > 0, "straddled": None,
                "intervals": [], "settle_s": None,
                "lines": sum(dg.count(b"\n") + 1 for dg in dgs),
                "local_batch0": len(self.lcap.batches),
                "global_batch0": len(self.gcap.batches),
                "local_sealed0": _sealed(self.local),
                "compiles0": _compile_totals(),
                "send_s": None, "datagrams": len(dgs),
                "pressure0": {"local": _pressure(self.local),
                              "global": _pressure(self.glob)}}
        self.marks.append(mark)
        try:
            if k > 0 and mark["pressure0"]["local"]["engaged"]:
                # (imports pass no admission control, so the global's
                # state is printed and not held)
                raise Failed(
                    f"window {k}: the local's overload pressure is "
                    f"still engaged after the warm-up drained: "
                    f"{self.local.overload.pressure.to_dict()}")
            self._send_and_settle(k, dgs, mark)
        finally:
            mark["compiles1"] = _compile_totals()
            mark["pressure1"] = {"local": _pressure(self.local),
                                 "global": _pressure(self.glob)}

    def _send_and_settle(self, k: int, dgs: list[bytes],
                         mark: dict) -> None:
        s = self.scale
        iv = float(s.interval_s)
        base = self._received()
        sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        dest = ("127.0.0.1", self.local.statsd_ports[0])
        t0 = time.monotonic()
        stall_deadline = t0 + 240
        for i, dg in enumerate(dgs):
            while i - (self._received() - base) >= INFLIGHT:
                if time.monotonic() > stall_deadline:
                    raise Failed(f"window {k}: reader stalled at "
                                 f"{self._received() - base}/{i}")
                time.sleep(0.0002)
            sock.sendto(dg, dest)
        _wait(lambda: self._received() - base >= len(dgs), 240,
              f"window {k}: all {len(dgs)} datagrams received")
        sock.close()
        mark["send_s"] = time.monotonic() - t0
        # the local's ledger says which of its intervals took the
        # window in: one, or the window straddled a flush
        lines = mark["lines"]
        took = _wait(
            lambda: (t := _took_in(self.local, mark["local_sealed0"]))
            and sum(n for _, n in t) >= lines and t, 3 * iv + 120,
            f"the local to close the interval of window {k}")
        mark["intervals"] = took
        mark["straddled"] = len(took) != 1
        if sum(n for _, n in took) != lines:
            raise Failed(f"window {k}: sent {lines} lines, the local "
                         f"received {took}")
        if mark["strict"] and mark["straddled"]:
            raise Failed(f"window {k} straddled a flush of the local "
                         f"(sent+ingested in {mark['send_s']:.2f}s of "
                         f"a {iv:.0f}s interval)")
        # settle: every series of the window is out of both sinks
        done = [0, 0]
        have = {"count": set(), "p50": set(), "set": set(),
                "gcount": set()}

        def settled():
            # incremental: each sink batch is looked at once
            lb = self.lcap.batches[mark["local_batch0"] + done[0]:]
            gb = self.gcap.batches[mark["global_batch0"] + done[1]:]
            done[0] += len(lb)
            done[1] += len(gb)
            for key in _sink_values(lb):
                if key[0].endswith(".count"):
                    have["count"].add(key)
            for key in _sink_values(gb):
                if key[0].endswith(".50percentile"):
                    have["p50"].add(key)
                elif key[0].startswith("smoke.set."):
                    have["set"].add(key)
                elif key[0].startswith("smoke.gcount."):
                    have["gcount"].add(key)
            return (len(have["count"]) >= s.timers
                    and len(have["p50"]) >= s.timers
                    and len(have["set"]) >= s.sets
                    and len(have["gcount"]) >= s.global_counters)
        t1 = time.monotonic()
        _wait(settled, SETTLE_INTERVALS * iv + 120,
              f"window {k} to come out of both sinks", poll=0.2)
        if not mark["strict"]:
            # the warm-up may still have a tail in flight: let two
            # more flushes of each tier pass before the next starts
            nl, ng = _sealed(self.local), _sealed(self.glob)
            _wait(lambda: _sealed(self.local) >= nl + 2
                  and _sealed(self.glob) >= ng + 2, 4 * iv + 120,
                  "the warm-up window to drain")
        mark["settle_s"] = time.monotonic() - t1
        mark["local_batch1"] = len(self.lcap.batches)
        mark["global_batch1"] = len(self.gcap.batches)


def _registry():
    """The process-wide device-cost registry both servers feed."""
    from veneur_tpu.observe.devicecost import REGISTRY
    return REGISTRY


def _compile_totals() -> dict:
    t = _registry().totals()
    return {k: t[k] for k in ("compile_total", "compile_duration_ns",
                              "compile_cache_hits",
                              "compile_cache_misses")}


def _flush_records(server) -> list[dict]:
    """The server's flush cycles that did work: wall seconds, rows
    out, compiles seen and the error the cycle recorded, if any."""
    return [{"seq": r.seq, "wall_s": round(r.duration_ns / 1e9, 3),
             "emitted": r.metrics_emitted, "forwarded": r.forward_rows,
             "compiles": r.compiles, "error": r.error,
             "stages_s": {k: round(v / 1e9, 3)
                          for k, v in r.stages.items() if v > 5e6}}
            for r in server.flush_ring.records()
            if r.forward_rows or r.compiles or r.error
            or r.metrics_emitted > 200]


def _memory() -> list[dict]:
    import jax
    out = []
    for d in jax.devices():
        ms = d.memory_stats() or {}
        out.append({"id": d.id,
                    "bytes_in_use": ms.get("bytes_in_use"),
                    "peak_bytes_in_use": ms.get("peak_bytes_in_use")})
    return out


def _nothing_swallowed(name: str, server) -> dict:
    stats = dict(server.stats)
    led = server.ledger.summary()
    recs = server.ledger.records()
    out = {
        "server": name,
        "flush_errors": stats.get("flush_errors", 0),
        "forward_errors": stats.get("forward_errors", 0),
        "metrics_dropped": stats.get("metrics_dropped", 0),
        "packet_errors": stats.get("packet_errors", 0),
        "flush_coalesced": stats.get("flush_coalesced", 0),
        "table_overflow": sum(sum(r.table_overflow.values())
                              for r in recs),
        "ledger_dropped": led["dropped_total"],
        "ledger_shed": sum(r.shed for r in recs),
        "kernel_drops": sum(r.kernel_drops for r in recs),
        "unattributed_lost": (led["imbalanced"] + led["owed_total"]
                              + led.get("shed_owed_total", 0)),
    }
    nonzero = {k: v for k, v in out.items() if k != "server" and v}
    emit(phase="accounting", intervals=led["intervals"],
         pressure_transitions=_pressure(server)["transitions"], **out)
    if nonzero:
        raise Failed(f"{name}: swallowed or dropped: {nonzero}")
    return out


def run(scale: Scale, seed: int = 0, mesh_shards: int = 0) -> None:
    """The smoke's body: raises ``Failed`` unless every check holds.
    Runs on whatever platform JAX has; only ``main()`` insists on a
    TPU, so the CPU rehearsal at a tiny scale runs this same code."""
    import jax

    from veneur_tpu import native
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from veneur_tpu.ops import tdigest
    from veneur_tpu.sinks.simple import CaptureSink
    from veneur_tpu.utils import compile_cache, jitopts

    t_run = time.monotonic()
    cache_warm = compile_cache.enable()
    emit(phase="setup", seed=seed, scale=dataclasses.asdict(scale),
         mesh_shards=mesh_shards, interval_s=scale.interval_s,
         upstream_interval_s=10, percentiles=list(PERCENTILES),
         merge_mode=tdigest.resolved_merge_mode(),
         native_parser=native.load() is not None,
         donate=jitopts.DONATE,
         cache_dir=jax.config.jax_compilation_cache_dir,
         cache_warm=cache_warm, jax=jax.__version__,
         devices=[str(d) for d in jax.devices()])

    t0 = time.monotonic()
    traffic = Traffic(scale, seed)
    windows = [traffic.window() for _ in range(scale.windows + 1)]
    emit(phase="traffic", windows=len(windows), warmup_window=0,
         datagrams=[len(w) for w in windows],
         bytes=[sum(map(len, w)) for w in windows],
         make_s=round(time.monotonic() - t0, 2))

    mem0 = _memory()
    common = {"interval": f"{scale.interval_s}s",
              "synchronize_with_interval": True, **scale.table}
    gcfg = {**common, "hostname": "smoke-global",
            "grpc_listen_addresses": ["tcp://127.0.0.1:0"],
            "percentiles": list(PERCENTILES)}
    if mesh_shards:
        gcfg["tpu_mesh_shards"] = mesh_shards
    gcap, lcap = CaptureSink(), CaptureSink()
    glob = Server(read_config(data=gcfg), extra_sinks=[gcap])
    glob.start()
    local = None
    try:
        local = Server(read_config(data={
            **common, "hostname": "smoke-local",
            "statsd_listen_addresses": ["udp://127.0.0.1:0"],
            "forward_address": f"127.0.0.1:{glob.grpc_ports[0]}",
            "forward_use_grpc": True}), extra_sinks=[lcap])
        local.start()
        emit(phase="servers", device=local.device_info,
             statsd_port=local.statsd_ports[0],
             grpc_port=glob.grpc_ports[0],
             ingest_backend=local.ingest_backend,
             global_table=type(glob.table).__name__,
             local_table=type(local.table).__name__,
             memory_after_tables=_memory(),
             start_s=round(time.monotonic() - t_run, 2))
        if mesh_shards:
            _check_spread(glob, local, mem0)
        sender = Sender(scale, windows, local, glob, lcap, gcap)
        sender.start()
        sender.join()
        # what is worth knowing comes out before any check can fail
        for mark in sender.marks:
            c0, c1 = mark["compiles0"], mark["compiles1"]
            emit(phase="window", window=mark["window"],
                 warmup=mark["window"] == 0, strict=mark["strict"],
                 lines=mark["lines"], datagrams=mark["datagrams"],
                 send_and_ingest_s=_round(mark["send_s"], 3),
                 smoke_reading_lines_per_s=mark["send_s"] and round(
                     mark["lines"] / mark["send_s"]),
                 local_intervals=mark["intervals"],
                 pressure_at_start=mark["pressure0"],
                 pressure_at_end=mark["pressure1"],
                 straddled=mark["straddled"],
                 settle_s=_round(mark["settle_s"], 2),
                 compiles=c1["compile_total"] - c0["compile_total"],
                 compile_s=round((c1["compile_duration_ns"]
                                  - c0["compile_duration_ns"]) / 1e9, 2),
                 cache_hits=c1["compile_cache_hits"]
                 - c0["compile_cache_hits"],
                 cache_misses=c1["compile_cache_misses"]
                 - c0["compile_cache_misses"])
        flushes = {"local": _flush_records(local),
                   "global": _flush_records(glob)}
        emit(phase="flushes", **flushes)
        snap = _registry().snapshot()
        totals = _compile_totals()
        emit(phase="dispatch",
             kernels={k: {"calls": v["calls"], "compiles": v["compiles"],
                          "dispatch_s": round(
                              v["dispatch_duration_ns"] / 1e9, 3)}
                      for k, v in snap["kernels"].items() if v["calls"]},
             h2d_bytes_total=snap["h2d_bytes_total"],
             readback_bytes_total=snap["readback_bytes_total"],
             compile_cache_hits=totals["compile_cache_hits"],
             compile_cache_misses=totals["compile_cache_misses"],
             compile_total=totals["compile_total"],
             compile_s=round(totals["compile_duration_ns"] / 1e9, 2))
        emit(phase="memory", devices=_memory())
        if sender.error is not None:
            raise sender.error
        errs = [r for rs in flushes.values() for r in rs if r["error"]]
        if errs:
            raise Failed(f"flush cycles recorded errors: {errs}")
        t0 = time.monotonic()
        refs = [reference(w) for w in windows]
        emit(phase="reference", parse_s=round(time.monotonic() - t0, 2))
        for mark, ref in zip(sender.marks, refs):
            lv = _sink_values(
                lcap.batches[mark["local_batch0"]:mark["local_batch1"]])
            gv = _sink_values(
                gcap.batches[mark["global_batch0"]:
                             mark["global_batch1"]])
            worst = check_window(mark["window"], ref, lv, gv,
                                 mark["strict"])
            emit(phase="checked", window=mark["window"],
                 held=("counters, gauges, timer counts, percentiles, "
                       "cardinalities") if mark["strict"]
                 else "sums of counters and timer counts", **worst)
        strict = [m for m in sender.marks if m["strict"]]
        members = sum(len(v) for m in strict
                      for v in refs[m["window"]]["sets"].values())
        emit(phase="steady", checked_windows=len(strict),
             unique_set_members_checked=members,
             compiled_after_first_checked=(
                 strict[-1]["compiles1"]["compile_total"]
                 - strict[0]["compiles1"]["compile_total"]))
        if members < scale.set_members:
            raise Failed(f"only {members} unique set members checked")
        if mesh_shards:
            _check_spread(glob, local, mem0, after=True)
        _nothing_swallowed("local", local)
        _nothing_swallowed("global", glob)
    finally:
        if local is not None:
            local.shutdown()
        glob.shutdown()
    left = [t.name for t in threading.enumerate()
            if t.is_alive() and t.name.startswith(
                ("udp-reader", "flush", "watchdog", "smoke-sender"))]
    if left:
        raise Failed(f"threads still alive after shutdown: {left}")
    emit(phase="done", wall_s=round(time.monotonic() - t_run, 1))


def _check_spread(glob, local, mem0: list[dict],
                  after: bool = False) -> None:
    """The mesh-sharded global's state lives on every device."""
    import jax
    n = len(jax.devices())
    spread = glob.table.plane_devices()
    mem = _memory()
    grew = [m1["bytes_in_use"] is not None
            and m1["bytes_in_use"] > (m0["bytes_in_use"] or 0)
            for m0, m1 in zip(mem0, mem)]
    # tpu_collective_import ("auto": on above one device) is the plain
    # table's wire fold; the sharded global's own merge is the
    # collective, and the key does not reach it
    emit(phase="spread", after_traffic=after, devices=n,
         mesh=dict(glob.table.mesh.shape), plane_devices=spread,
         every_device_grew=all(grew), memory=mem,
         local_table_device=str(next(iter(
             local.table.histo_means.sharding.device_set))),
         tpu_collective_import=glob.config.tpu_collective_import,
         collective_import_engaged=hasattr(
             glob.table, "collective_import_mode"))
    if any(v != n for v in spread.values()):
        raise Failed(f"planes not on all {n} devices: {spread}")
    if mem[0]["bytes_in_use"] is not None and not all(grew):
        raise Failed(f"not every device's bytes_in_use grew: {mem}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the mesh-sharded global and its "
                         "reference")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU: JAX gives {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        # the last line's count is what the run had, no more
        print(f"chip_smoke: --chips {args.chips} runs on exactly "
              f"{args.chips}, JAX gives {len(devs)} devices",
              file=sys.stderr)
        return 2
    from veneur_tpu import native
    from veneur_tpu.ops import tdigest
    if tdigest.resolved_merge_mode() != "pallas":
        print("chip_smoke: merge mode is "
              f"{tdigest.resolved_merge_mode()!r}, not the Pallas "
              "kernel", file=sys.stderr)
        return 2
    if native.load() is None:
        print("chip_smoke: the native parser did not build (no g++?)",
              file=sys.stderr)
        return 2
    try:
        run(Scale(), seed=args.seed,
            mesh_shards=4 if args.chips == 4 else 0)
    except Failed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
