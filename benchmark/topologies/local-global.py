"""The topology ``local-global``: one UDP sender into one local that
forwards over gRPC to one global.

The deployment is the one ``chip_smoke.py`` proved: a local ``Server``
forwarding over gRPC to a global ``Server``, one process, one chip.  The
load generator is a process of its own (``sender.py``) that writes
the cell's traffic to the local's UDP listener as its mode says; a
thread here watches the local's ticks and the global's count of
imported rows, and publishes the reader's counter.  Which interval a
datagram fell into is read afterwards from the local's ledger
(datagrams are ingested whole and in order, so each interval's count
of lines has to end on a datagram's boundary of the sent stream, or
the run is not correct).  Every interval that closed from the last
warm-up interval on is compared whole with the reference
(``benchmark/reference.py``; its control is ``benchmark/control.py``).

**What a topology is.**  A configuration's file may carry
``"topology": "<name>"`` (absent: this one); ``harness.run_cell``
loads ``benchmark/topologies/<name>.py`` and calls its two functions.
Everything else of a run (limits and ``scale``, ``checks`` and
``correct``, ``failed``, the device block, the ``phase:`` lines of
the cycles, the load and the reference) is ``run_cell``'s and the
same for every topology.

``serve(c, spec, seed, seconds, trace, scale, t_start) -> dict``:
servers up, clients started, warm-up, the window, every compared
interval followed to its sinks, shutdown.  ``c`` is ``harness.cell``'s
dict, ``spec`` the traffic file under the rehearsal's ``scale``,
``t_start`` the process's start.  It returns, for the rest of a run:

- ``interval_s``, ``t0``, ``t_end`` (the window's first and last
  instant on the wall clock; ``setup_s`` is ``t0 - t_start``) and
  ``ticks`` (the ticks inside it);
- ``lags``: a dict by server of one list of seconds, a tick's lag
  each, and ``lag_of``: the server whose ticks the cell's
  ``flush_lag_ms`` is the mean of (here ``"local"``);
- ``rings``: by server, the flush cycles from the window's first
  instant on, each ``harness.ring_dict`` of its ``FlushRecord``;
- ``at_t0``, ``at_end``: ``{"t", "received", "registry", "totals"}``
  at the window's two ends (the registry's snapshot and totals of
  ``observe/devicecost.py``);
- ``peak``: ``harness.memory_peak()`` once the window has closed;
- ``trace``: ``benchmark/trace.py``'s reduction of the traced slice,
  or None;
- ``attempted``: the operations (here lines) offered in the window;
  ``received`` those taken in; ``blocked_s`` the time the load was
  held back in the window and ``late_max_s`` its worst lateness;
  ``sent`` the generator's own count over its whole life;
- whatever its own ``compare`` needs (here ``stream``, ``parts``,
  ``held``, ``lost``, ``acct``, ``ticks_missing``, ``seconds``).

``compare(s, limits) -> (numbers, failed)``: every number compared,
by the names of the module constant ``NUMBERS`` and no others, and the
operations of the window that one of them did not bear out.  A
configuration's ``limits`` name exactly ``NUMBERS``.  Which reference
it imports is the topology's own business.
"""

from __future__ import annotations

import json
import math
import mmap
import os
import struct
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from benchmark import harness, reference, traffic
from benchmark.harness import log

MIN_WARMUP_TICKS = 4     # ticks crossed under traffic before the window
MAX_WARMUP_TICKS = 24    # no quiet interval by then: the run fails
QUIET_SHARE = 1.0        # of an interval with no compile, to start
FOLLOW_INTERVALS = 6     # past the window's close, for a late flush

# every number ``compare`` returns; a configuration's limits name these
NUMBERS = ("sums_off", "readings_missing", "p99_out", "p50_rank_err",
           "p90_rank_err", "card_rel_err", "lines_unaccounted",
           "dropped", "ticks_missing", "sender_blocked_pct")


# ----------------------------------------------------------------------
# the benchmark's own sink: stamps each flush as it arrives

def make_sink():
    from veneur_tpu.sinks.base import SinkBase

    class StampSink(SinkBase):
        """Stamps each flush as it arrives and keeps its columns: a
        frame-aware sink, as the Datadog, SignalFx and Prometheus
        sinks are, so the flush does not pay for the legacy list of
        ``InterMetric`` on its way here.  It keeps the frame's blocks
        and not the frame: the program caches that list on the frame,
        and a heap that grows by 203,100 objects a flush makes the
        interpreter's full collections longer tick after tick (0.13 to
        0.27 s by the tenth flush), which no deployment's sink does."""
        name = "bench"

        def __init__(self):
            super().__init__()
            self.batches: list[tuple[float, object]] = []

        def flush_frame(self, frame) -> None:
            self.batches.append((time.time(), Kept(
                list(frame.blocks), list(frame.extra),
                frame.common_tags)))

        def flush(self, metrics=None) -> None:
            if metrics is not None:
                self.batches.append((time.time(), Kept(
                    [], list(metrics), ())))

    return StampSink()


class Kept:
    """One flush as the sink was handed it."""

    def __init__(self, blocks: list, extra: list, common_tags: tuple):
        self.blocks, self.extra = blocks, extra
        self.common_tags = common_tags

    def values(self):
        """``(name, tags, value)`` of every metric of the flush, read
        off the columns as a frame-aware sink reads them; for the
        comparison, after the window."""
        for b in self.blocks:
            for r, v in zip(b.rows.tolist(), b.values.tolist()):
                meta = b.metas[r]
                yield (meta.name + b.suffix,
                       b.tag_table[r] if b.tag_table is not None
                       else meta.tags + self.common_tags, v)
        for m in self.extra:
            yield m.name, m.tags, m.value


# ----------------------------------------------------------------------
# the watcher: sees the local's ticks, places the window

class Watcher(threading.Thread):
    """Publishes the reader's packet counter to the sender every
    millisecond, watches the local's swaps, decides where the window
    starts, and takes the counters at the window's first and last
    instant.  Reads the servers'
    host-side counters only."""

    def __init__(self, feedback: mmap.mmap, local, glob,
                 interval_s: float, seconds: float):
        super().__init__(name="bench-watcher", daemon=True)
        self.feedback = feedback
        self.child = None
        self.local, self.glob = local, glob
        self.iv, self.seconds = float(interval_s), float(seconds)
        self.recv0 = self.received_raw()
        self.t0 = self.t_end = None
        self.t0_swap = -1
        self.swaps: list[dict] = []
        self.compile_log: list[tuple[float, int]] = []
        # (seen at, rows the global has imported so far): the forward
        # of a flush has arrived once the count stops rising
        self.imports: list[tuple[float, int]] = [
            (time.time(), self.imported())]
        self.at_t0 = self.at_end = None
        self.error: BaseException | None = None
        self.done = False      # set by the harness once all is read
        self.open_id = self.first_open = self._open_id()
        self.next_tick = math.floor(time.time() / self.iv + 1) * self.iv

    def received_raw(self) -> int:
        return self.local.stats.get("received_dogstatsd-udp", 0)

    def received(self) -> int:
        """Datagrams of the stream that the reader has taken in."""
        return self.received_raw() - self.recv0

    def imported(self) -> int:
        return self.glob.stats.get("imports_received", 0)

    def _open_id(self) -> float:
        """The identity of the local's open interval: the instant its
        ledger opened it, which is inside the swap's critical section
        (``Ledger.close_interval``)."""
        return self.local.ledger.open_to_dict()["start_unix"]

    def run(self):
        try:
            self._run()
        except BaseException as e:   # surfaces in the main thread
            self.error = e

    def _run(self) -> None:
        from veneur_tpu.observe.devicecost import REGISTRY
        give_up = time.monotonic() + 120 + MAX_WARMUP_TICKS * self.iv
        sampled = 0.0
        while not self.done:
            now = time.time()
            struct.pack_into("<q", self.feedback, 0, self.received())
            rows = self.imported()
            if rows != self.imports[-1][1]:
                self.imports.append((time.time(), rows))
            if self.t0 is not None and now >= self.t_end \
                    and self.at_end is None:
                self.at_end = self._snapshot(now)
            if now - sampled >= 0.1:
                sampled = now
                self.compile_log.append(
                    (now, REGISTRY.totals()["compile_total"]))
                if self.t0 is None and time.monotonic() > give_up:
                    raise reference.Failed("warm-up never settled")
                if self.child.poll() is not None and self.t0 is None:
                    raise reference.Failed("the sender died")
            if now < self.next_tick - 0.002:
                time.sleep(0.001)
                continue
            oid = self._open_id()
            if oid != self.open_id:
                self.open_id = oid
                self._on_swap(self.next_tick, time.time())
                self.next_tick += self.iv
            elif now > self.next_tick + 3 * self.iv + 120:
                raise reference.Failed(
                    "the local never swapped after the tick at "
                    f"{self.next_tick}")
            else:
                time.sleep(0.0005)

    def _snapshot(self, now: float) -> dict:
        from veneur_tpu.observe.devicecost import REGISTRY
        return {"t": now, "received": self.received(),
                "registry": REGISTRY.snapshot(),
                "totals": REGISTRY.totals()}

    def _quiet(self, now: float) -> bool:
        """Every program the window runs has been compiled: the global
        has flushed imported rows, nothing compiled in the whole last
        interval (the first imports and the flushes after them each
        bring new programs, a tick apart), both servers' newest flush
        cycle is the previous tick's, and the local's overload
        pressure is released (an engaged server samples its sets)."""
        since = now - QUIET_SHARE * self.iv
        seen = [c for t, c in self.compile_log if t >= since]
        before = [c for t, c in self.compile_log if t < since]
        if not seen or not before or seen[-1] != before[-1]:
            return False
        for srv in (self.local, self.glob):
            recs = srv.flush_ring.records()
            if not recs or recs[-1].start_unix < now - 1.5 * self.iv:
                return False
        if not any(r.metrics_emitted > 0 and r.tally
                   for r in self.glob.flush_ring.records()):
            return False
        return not self.local.overload.pressure.to_dict()["engaged"]

    def _on_swap(self, tick: float, now: float) -> None:
        from veneur_tpu.observe.devicecost import REGISTRY
        totals = REGISTRY.totals()
        swap = {"tick": tick, "seen": now, "open_id": self.open_id,
                "received": self.received(),
                "compiles": [totals["compile_total"],
                             totals["compile_cache_hits"],
                             totals["compile_cache_misses"]],
                "stats": {"local": dict(self.local.stats),
                          "global": dict(self.glob.stats)}}
        self.swaps.append(swap)
        if self.t0 is not None:
            return
        n = sum(1 for w in self.swaps if w["received"])
        if n >= MAX_WARMUP_TICKS:
            raise reference.Failed(
                f"warm-up never settled: {n} ticks under traffic and "
                "no whole interval without a compile")
        if n >= MIN_WARMUP_TICKS and self._quiet(now):
            self.t0, self.t_end = now, now + self.seconds
            self.t0_swap = len(self.swaps) - 1
            self.at_t0 = self._snapshot(now)


def start_sender(traffic_path: str, seed: int, port: int, iv: float,
                 scale: dict, feedback: str) -> subprocess.Popen:
    """The generator's process; returns once it has made its rounds."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("JAX_", "XLA_", "TPU_"))}
    child = subprocess.Popen(
        [sys.executable, os.path.join(harness.HERE, "sender.py"),
         "--traffic", traffic_path, "--seed", str(seed),
         "--port", str(port), "--interval", repr(iv),
         "--feedback", feedback,
         "--scale", json.dumps(scale)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        env=env)
    return child


# ----------------------------------------------------------------------
# the sent stream: which interval took which datagrams, and its pieces

class Stream:
    """The cyclic stream of the cell's rounds, as the sender sends it
    (the same bytes, made here from the same seed)."""

    def __init__(self, rounds: list[list[bytes]]):
        self.rounds = rounds
        self.datagrams = [dg for r in rounds for dg in r]
        self.starts = np.cumsum([0] + [len(r) for r in rounds])
        self.dg_lines = np.array([dg.count(b"\n") + 1
                                  for dg in self.datagrams])
        self.prefix = np.concatenate([[0], np.cumsum(self.dg_lines)])
        self._whole: dict[int, dict] = {}

    def lines_upto(self, n: int) -> int:
        """Lines of the first ``n`` datagrams sent."""
        return int((n // len(self.datagrams)) * self.prefix[-1]
                   + self.prefix[n % len(self.datagrams)])

    def split_by_ledger(self, local, first_open: float
                        ) -> tuple[list[dict], int]:
        """The local's intervals that took stream lines in, in order,
        with the range of datagrams each took: ``[a, b)`` of the sent
        stream.  Where a count does not end on a datagram's boundary
        the split stops: the lines from there on are unaccounted."""
        per_cycle = int(self.prefix[-1])
        out = []
        a = lines = lost = 0
        for rec in local.ledger.records():
            got = rec.received.get("dogstatsd", 0)
            if rec.start_unix < first_open - 1e-6 or not got:
                continue
            if lost:
                lost += got
                continue
            lines += got
            k = int(np.searchsorted(self.prefix, lines % per_cycle))
            if self.prefix[k] != lines % per_cycle:
                log(phase="split", error=f"interval seq {rec.seq} took "
                    f"in {got} lines, {lines} in all: that ends inside "
                    "a datagram")
                lost = got
                continue
            b = (lines // per_cycle) * len(self.datagrams) + k
            out.append({"seq": rec.seq, "open": rec.start_unix, "a": a,
                        "b": b, "lines": got})
            a = b
        return out, lost

    def pieces(self, a: int, b: int) -> list[dict]:
        """Datagrams ``[a, b)`` parsed, in order: each whole round once
        (the same object wherever it recurs), a cut round as it is."""
        out = []
        n = len(self.datagrams)
        while a < b:
            r = int(np.searchsorted(self.starts, a % n, "right")) - 1
            lo, hi = a % n, min(int(self.starts[r + 1]), a % n + b - a)
            if (lo, hi) == (int(self.starts[r]), int(self.starts[r + 1])):
                if r not in self._whole:
                    self._whole[r] = reference.parse_round(
                        self.rounds[r])
                out.append(self._whole[r])
            else:
                out.append(reference.parse_round(self.datagrams[lo:hi]))
            a += hi - lo
        return out


# ----------------------------------------------------------------------
# after the window: lags

def _cycle_for(records, tick: float, iv: float):
    for r in records:
        if tick - 0.25 <= r.start_unix < tick + iv - 0.25:
            return r
    return None


def _batch_for(batches, start: float, end: float):
    for stamp, b in batches:
        if start <= stamp < end:
            return stamp, b
    return None


def _next_start(records, seq: int) -> float:
    return min((x.start_unix for x in records if x.seq > seq),
               default=float("inf"))


def _forward_arrived(imports, rec, end: float):
    """When the global had imported the last of the rows that cycle
    ``rec`` forwarded: the instant the watcher saw the global's count
    of imported rows reach what it was before the cycle plus the
    cycle's ``forward_rows``.  None where it never did before the
    next cycle."""
    before = max((n for t, n in imports if t < rec.start_unix),
                 default=None)
    if before is None:
        return None
    for t, n in imports:
        if rec.start_unix <= t < end and n - before == rec.forward_rows:
            return t
    return None


def tick_lags(server, sink, ticks: list[float], iv: float,
              imports=None) -> tuple[list[float], int]:
    """For each tick: seconds from the tick to the later of the sink
    holding that flush (stamped by the sink as the frame arrives) and,
    where the flush forwarded rows, the global holding the last of
    them (stamped by the watcher).  A tick with no cycle, a failed
    cycle, no batch or a forward that did not arrive whole is
    missing."""
    recs = server.flush_ring.records()
    lags, missing = [], 0
    for t in ticks:
        r = _cycle_for(recs, t, iv)
        end = r and _next_start(recs, r.seq)
        got = r and not r.error and _batch_for(
            sink.batches, r.start_unix, end)
        done = got and got[0]
        if got and imports is not None and r.forward_rows:
            arrived = _forward_arrived(imports, r, end)
            done = arrived and max(done, arrived)
        if not done:
            missing += 1
            continue
        lags.append(done - t)
    return lags, missing


def _diagnose(local, glob, watcher) -> None:
    stats = dict(local.stats)
    log(phase="diagnose", received=watcher.received(),
        stats={k: v for k, v in stats.items()
               if k.startswith(("received_", "packet_", "flush_",
                                "forward_", "metrics_"))},
        pressure=local.overload.pressure.to_dict(),
        ledger=[{"seq": r.seq, "open": r.start_unix,
                 "lines": r.received.get("dogstatsd", 0),
                 "shed": r.shed, "kernel_drops": r.kernel_drops,
                 "coalesced": r.coalesced}
                for r in local.ledger.records()[-8:]],
        **{name: [{"start": r.start_unix,
                   "wall_s": round(r.duration_ns / 1e9, 3),
                   "emitted": r.metrics_emitted,
                   "forwarded": r.forward_rows, "compiles": r.compiles,
                   "stages_s": {k: round(v / 1e9, 3)
                                for k, v in r.stages.items()
                                if v > 5e6}}
                  for r in srv.flush_ring.records()[-8:]]
           for name, srv in (("local", local), ("global", glob))})


def _wait(cond, what: str, deadline: float, watcher=None):
    while time.monotonic() < deadline:
        if watcher is not None and watcher.error is not None:
            raise watcher.error
        v = cond()
        if v:
            return v
        time.sleep(0.05)
    raise reference.Failed(f"timed out waiting for {what}")


# ----------------------------------------------------------------------
# the run

def serve(c: dict, spec: dict, seed: int, seconds: float, trace: bool,
          scale: dict, t_start: float) -> dict:
    """Servers up, warm-up, the window, and every interval of it
    followed to both sinks; then the servers are shut down.  Returns
    what the comparison and the metrics read (the module's docstring
    has the contract)."""
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from benchmark import trace as trace_mod

    cfg = c["config"]
    common = {**cfg["servers"]["common"], **scale.get("servers", {})}
    iv = float(str(common["interval"]).rstrip("s"))
    gsink, lsink = make_sink(), make_sink()
    glob = Server(read_config(data={
        **common, **cfg["servers"]["global"],
        "grpc_listen_addresses": ["tcp://127.0.0.1:0"]}),
        extra_sinks=[gsink])
    glob.start()
    local = child = watcher = tracing = fb_file = None
    try:
        local = Server(read_config(data={
            **common, **cfg["servers"]["local"],
            "statsd_listen_addresses": ["udp://127.0.0.1:0"],
            "forward_address": f"127.0.0.1:{glob.grpc_ports[0]}",
            "forward_use_grpc": True}), extra_sinks=[lsink])
        local.start()
        log(phase="servers", device=local.device_info,
            ingest_backend=local.ingest_backend,
            start_s=round(time.time() - t_start, 2))
        fb_file = tempfile.NamedTemporaryFile(prefix="bench-feedback-")
        fb_file.write(bytes(8))
        fb_file.flush()
        watcher = Watcher(mmap.mmap(fb_file.fileno(), 8), local, glob,
                          iv, seconds)
        # the generator makes its rounds while this process makes the
        # same bytes for the reference
        watcher.child = child = start_sender(
            os.path.join(harness.HERE, "traffic",
                         c["traffic"]["name"] + ".json"),
            seed, local.statsd_ports[0], iv, scale, fb_file.name)
        t = time.monotonic()
        stream = Stream(traffic.make_rounds(spec, seed))
        log(phase="traffic", seed=seed,
            datagrams=[len(r) for r in stream.rounds],
            lines=int(stream.prefix[-1]),
            make_s=round(time.monotonic() - t, 2))
        ready = json.loads(child.stdout.readline() or "{}")
        if ready.get("datagrams") != len(stream.datagrams):
            raise reference.Failed(f"the sender is not ready: {ready}")
        watcher.start()
        while watcher.t0 is None and watcher.is_alive():
            time.sleep(0.01)
        if watcher.t0 is None:
            raise watcher.error or reference.Failed("no window")
        t0, t_end = watcher.t0, watcher.t_end
        log(phase="window", t0=t0, setup_s=round(t0 - t_start, 3),
            warmup_ticks=watcher.t0_swap + 1,
            # at each warm-up tick: compiles, cache hits, misses so far
            compiles_at_tick=[w["compiles"] for w in watcher.swaps])
        tick0 = watcher.swaps[watcher.t0_swap]["tick"]
        ticks = [tick0 + iv * i for i in range(1, int(seconds / iv) + 2)
                 if tick0 + iv * i <= t_end]
        if trace:
            # one whole interval: the window's first tick, the flush
            # it starts and the ingest that runs beside and after it
            tracing = trace_mod.Slice(
                start=(ticks[0] - 0.3) if ticks else t0,
                stop=min(t_end, (ticks[0] + iv - 0.5) if ticks
                         else t_end))
            tracing.run()
            log(phase="trace", **tracing.timing)
        while watcher.at_end is None and watcher.is_alive():
            time.sleep(0.01)

        # follow every interval of the window to both sinks, under
        # the same traffic: the sender stops only once all is read
        deadline = time.monotonic() + FOLLOW_INTERVALS * iv + 60
        # compared: the last warm-up interval and every interval that
        # a tick of the window closed
        first = watcher.t0_swap - 1
        last = _wait(lambda: next(
            (i for i, w in enumerate(watcher.swaps)
             if w["tick"] >= ticks[-1] - 1e-6), None),
            "the window's last tick", deadline, watcher) \
            if ticks else watcher.t0_swap
        swap_c = watcher.swaps[first]
        opened = {round(w["open_id"], 6)
                  for w in watcher.swaps[first:last]}
        try:
            _wait(lambda: opened <= {round(r.start_unix, 6)
                                     for r in local.ledger.records()},
                  "the local to seal the compared intervals",
                  min(deadline, time.monotonic() + 2.5 * iv + 15),
                  watcher)
        except reference.Failed as e:
            log(phase="ledger", error=str(e))
        parts, lost = stream.split_by_ledger(local, watcher.first_open)
        parts = [p for p in parts if round(p["open"], 6) in opened]
        lost += len(opened) - len(parts)   # an interval never sealed

        def outputs(p):
            """The sink batches that hold interval ``p``: the local's
            from the cycle that closed it, the global's from its
            first cycle to start half an interval after that one
            (the global flushes at its next tick what the local
            forwarded at this one)."""
            lrecs = local.flush_ring.records()
            grecs = glob.flush_ring.records()
            rec = next((r for r in lrecs if r.seq == p["seq"]), None)
            if rec is None:
                return None
            lb = _batch_for(lsink.batches, rec.start_unix,
                            _next_start(lrecs, rec.seq))
            gcyc = next((g for g in grecs if g.start_unix
                         >= rec.start_unix + 0.5 * iv), None)
            gb = gcyc and _batch_for(gsink.batches, gcyc.start_unix,
                                     _next_start(grecs, gcyc.seq))
            return (lb[1], gb[1]) if lb and gb else None
        _wait(lambda: all(outputs(p) for p in parts),
              "both sinks to hold every compared interval", deadline,
              watcher)
        held = {p["seq"]: outputs(p) for p in parts}
        _wait(lambda: all(_cycle_for(glob.flush_ring.records(), t, iv)
                          for t in ticks), "the global's cycles",
              deadline, watcher)
        child.stdin.write("quit\n")
        child.stdin.flush()
        out, _ = child.communicate(timeout=60)
        report = json.loads(out.strip().splitlines()[-1])
        peak = harness.memory_peak()
        seq0 = min((p["seq"] for p in parts),
                   default=local.ledger.last().seq + 1) - 1
        gseq0 = max((r.seq for r in glob.ledger.records()
                     if r.start_unix < swap_c["seen"] - 0.25),
                    default=0)
        acct = {"local": harness.accounting(
                    "local", local, seq0, swap_c["stats"]["local"]),
                "global": harness.accounting(
                    "global", glob, gseq0, swap_c["stats"]["global"])}
        llag, lmiss = tick_lags(local, lsink, ticks, iv,
                                watcher.imports)
        glag, gmiss = tick_lags(glob, gsink, ticks, iv)
        rings = {name: [harness.ring_dict(r)
                        for r in srv.flush_ring.records()
                        if r.start_unix >= t0 - 0.25]
                 for name, srv in (("local", local), ("global", glob))}
        log(phase="memory", memory_peak_bytes=peak, pressure={
            "local": local.overload.pressure.to_dict(),
            "global": glob.overload.pressure.to_dict()})
        cycles = [(r.start_unix, r.start_unix + r.duration_ns / 1e9)
                  for srv in (local, glob)
                  for r in srv.flush_ring.records()]
    except BaseException:
        # what the record keeps of a run that could not be compared
        if local is not None and watcher is not None:
            _diagnose(local, glob, watcher)
        raise
    finally:
        if tracing is not None:
            tracing.abort()
        if watcher is not None:
            watcher.done = True
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if local is not None:
            local.shutdown()
        glob.shutdown()
        if watcher is not None and watcher.is_alive():
            watcher.join(timeout=5)
        if fb_file is not None:
            fb_file.close()
    series = report["series"]
    sent0, blocked0 = _sent_at(series, t0)
    sent1, blocked1 = _sent_at(series, t_end)
    at_t0, at_end = watcher.at_t0, watcher.at_end
    return {"interval_s": iv, "t0": t0, "t_end": t_end, "ticks": ticks,
            "seconds": seconds, "stream": stream, "parts": parts,
            "held": held, "lost": lost, "acct": acct, "peak": peak,
            "lags": {"local": llag, "global": glag}, "lag_of": "local",
            "ticks_missing": lmiss + gmiss, "rings": rings,
            "at_t0": at_t0, "at_end": at_end, "sent": report["sent"],
            "attempted": stream.lines_upto(sent1)
            - stream.lines_upto(sent0),
            "received": stream.lines_upto(at_end["received"])
            - stream.lines_upto(at_t0["received"]),
            "blocked_s": blocked1 - blocked0,
            "late_max_s": max((e[3] for e in series
                               if t0 <= e[0] <= t_end + 0.1),
                              default=0.0),
            "trace": tracing.reduce(cycles) if tracing else None}


def compare(s: dict, limits: dict) -> tuple[dict, int]:
    """Every compared interval against its reference: the numbers
    compared (the worst or the sum over the intervals; ``NUMBERS``)
    and the lines of the window's intervals that one of them did not
    bear out."""
    numbers = {"sums_off": 0, "readings_missing": 0, "p99_out": 0,
               "p50_rank_err": 0.0, "p90_rank_err": 0.0,
               "card_rel_err": 0.0}
    failed_lines = 0
    for p in s["parts"]:
        lb, gb = s["held"][p["seq"]]
        res = reference.compare_interval(
            reference.combine(s["stream"].pieces(p["a"], p["b"])),
            reference.sink_values(lb.values()),
            reference.sink_values(gb.values()))
        in_window = p["open"] >= s["t0"] - 1e-3
        if in_window and any(v > limits[k]
                             for k, v in res["numbers"].items()):
            failed_lines += p["lines"]
        log(phase="compared", seq=p["seq"], lines=p["lines"],
            datagrams=p["b"] - p["a"], in_window=in_window,
            **res["numbers"], p_rel_err=res["p_rel_err"],
            notes=res["notes"])
        for k, v in res["numbers"].items():
            numbers[k] = max(numbers[k], v) if isinstance(
                numbers[k], float) else numbers[k] + v
    numbers["lines_unaccounted"] = s["lost"]
    numbers["dropped"] = sum(sum(a.values())
                             for a in s["acct"].values())
    numbers["ticks_missing"] = s["ticks_missing"]
    # the load is withdrawn while the reader does not drain (see
    # modes/paced.py): past this share of the window the lag was taken
    # under less ingest than the cell states, and the run is not sound
    numbers["sender_blocked_pct"] = (
        100.0 * s["blocked_s"] / s["seconds"])
    return numbers, failed_lines


def _sent_at(series: list, t: float) -> tuple[int, float]:
    """(datagrams sent, seconds blocked) by the instant ``t``, from
    the sender's series: its last entry at or before ``t``."""
    sent, blocked = 0, 0.0
    for at, n, b, _late in series:
        if at > t:
            break
        sent, blocked = n, b
    return sent, blocked

