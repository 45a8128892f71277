"""The topology ``fleet-global``: a fleet of locals' forwards into one
global, and no local.

One global ``Server`` with its gRPC listener (``ImportServer``) on
loopback; ``clients`` import clients, each a process of its own
(``import_client.py``) that never imports JAX or the program, each
sending one ``forwardrpc.Forward/SendMetrics`` call an interval on the
schedule its mode states (``modes/import-calls.py``).  What a client
sends is what a local's flush would forward: the bodies are built
here, before the servers start, from the fleet's raw draws
(``fleet.py``) with the program's own local side (``ForwardBlock``,
``encode_metric_list``, the host hash of set members), and handed to
the clients as files.  The reference (``fleet_reference.py``; its
control is ``fleet_control.py``) sees only the raw draws.

The lag is the global's: from its tick to its own sink holding that
flush's frame (``lag_of: "global"``).  ``lags["clients"]`` holds, for
the same ticks, the longest call of the interval each tick closed.
Which interval took which call is read from the clients' own reports
(a call's start and acknowledgement against the instants the watcher
saw the global swap); every interval a tick of the window closed, and
the last warm-up interval, is compared whole with the reference.

``serve`` and ``compare`` follow the contract in ``local-global.py``'s
docstring; the sink, the watcher's warm-up rule and ``tick_lags`` are
that module's, loaded by name.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from benchmark import fleet as fleet_mod
from benchmark import fleet_reference, harness, reference
from benchmark.harness import log

lg = harness.load_module("topologies", "local-global")

# every number ``compare`` returns; a configuration's limits name these
NUMBERS = ("sums_off", "readings_missing", "p99_out", "p50_rank_err",
           "p90_rank_err", "card_rel_err", "dropped", "ticks_missing",
           "wires_unaccounted", "calls_late_pct")
LATE_S = 0.1     # a call that started this long after it was due is late


# ----------------------------------------------------------------------
# the clients' bodies: a local's forward, made with the program's
# local side

class Bodies:
    """What each local forwards in each round, as the wire's bytes."""

    def __init__(self, fleet: fleet_mod.Fleet, compression: float):
        from veneur_tpu.core.table import RowMeta
        from veneur_tpu.protocol import dogstatsd as dsd
        self.fleet, self.compression = fleet, float(compression)
        kinds = {"timer": (dsd.TIMER, dsd.SCOPE_DEFAULT),
                 "set": (dsd.SET, dsd.SCOPE_DEFAULT),
                 "gcount": (dsd.COUNTER, dsd.SCOPE_GLOBAL)}
        # one RowMeta a series, shared by the locals that forward it:
        # the encoder keeps a series' identity bytes on it
        self.metas = {k: [RowMeta(name, tags, scope, typ)
                          for name, tags in zip(fleet.names[k],
                                                fleet.tags[k])]
                      for k, (typ, scope) in kinds.items()}

    def hashed_pool(self, rnd: dict) -> tuple:
        """(register index, rank) of every id of every set's pool for
        the round, hashed once by the program's member hash, and each
        pool's first id."""
        from veneur_tpu.utils import hashing
        f = self.fleet
        base = (rnd["round"] * f.n["set"] + np.arange(f.n["set"])) \
            * f.pool
        ids = (base[:, None] + np.arange(f.pool)).reshape(-1)
        idx, rank = hashing.hash_members(fleet_mod.member_bytes(ids))
        shape = (f.n["set"], f.pool)
        return idx.reshape(shape), rank.reshape(shape), base

    def blocks(self, rnd: dict, local: int, pool) -> list:
        """The ``ForwardBlock``s of ``local``'s flush in round ``rnd``:
        each timer's digest its sorted samples at weight 1, each set's
        registers its members inserted on the host, each counter its
        value."""
        from veneur_tpu.core.flusher import ForwardBlock
        from veneur_tpu.forward import hll_codec
        from veneur_tpu.ops import segment
        f = self.fleet
        j = f.slot_of(local)
        out = []
        ids = f.series_of(local, "timer")
        if len(ids):
            x = np.sort(rnd["samples"][ids, j, :], axis=1)
            stats = np.empty((len(ids), segment.HISTO_STAT_COLS),
                             np.float32)
            stats[:, segment.STAT_WEIGHT] = x.shape[1]
            stats[:, segment.STAT_MIN] = x[:, 0]
            stats[:, segment.STAT_MAX] = x[:, -1]
            stats[:, segment.STAT_SUM] = x.sum(1)
            stats[:, segment.STAT_RSUM] = (1.0 / x).sum(1)
            out.append(ForwardBlock(
                "histo", [self.metas["timer"][i] for i in ids],
                stats=stats, means=x.astype(np.float32),
                weights=np.ones(x.shape, np.float32)))
        ids = f.series_of(local, "set")
        if len(ids):
            idx, rank, base = pool
            picks = rnd["members"][ids, j, :] - base[ids][:, None]
            regs = np.zeros((len(ids), hll_codec.M), np.uint8)
            at = np.arange(len(ids))[:, None]
            np.maximum.at(regs, (at, idx[ids][at, picks]),
                          rank[ids][at, picks].astype(np.uint8))
            out.append(ForwardBlock(
                "set", [self.metas["set"][i] for i in ids], regs=regs))
        ids = f.series_of(local, "gcount")
        if len(ids):
            out.append(ForwardBlock(
                "counter", [self.metas["gcount"][i] for i in ids],
                values=rnd["increments"][ids, j].astype(np.float64)))
        return out

    def body(self, rnd: dict, local: int, pool) -> tuple[bytes, int]:
        """One call's body and the live centroids in it."""
        from veneur_tpu.forward.grpc_forward import encode_metric_list
        return encode_metric_list(self.blocks(rnd, local, pool),
                                  self.compression)

    def write(self, rounds: list[dict], into: str) -> dict:
        """Every client's body for every round as a file under
        ``into``; returns ``{"files": [local][round], "bytes",
        "centroids"}`` (the totals of one interval, round 0's)."""
        files = [[] for _ in range(self.fleet.clients)]
        total = cents = 0
        for rnd in rounds:
            pool = self.hashed_pool(rnd)
            for l in range(self.fleet.clients):
                data, c = self.body(rnd, l, pool)
                path = os.path.join(into, f"l{l:03d}.r{rnd['round']}")
                with open(path, "wb") as fh:
                    fh.write(data)
                files[l].append(path)
                if rnd["round"] == 0:
                    total += len(data)
                    cents += c
        return {"files": files, "bytes": total, "centroids": cents}


# ----------------------------------------------------------------------
# the clients

class Clients:
    """The fleet's processes: started, asked whether all live, told to
    stop, their reports read."""

    def __init__(self, spec: dict, files: list, offsets, port: int,
                 iv: float):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.procs = [subprocess.Popen(
            [sys.executable,
             os.path.join(harness.HERE, "import_client.py"),
             "--mode", spec["mode"], "--port", str(port),
             "--interval", repr(iv), "--offset", repr(float(off)),
             "--deadline", repr(float(spec["deadline_s"])),
             "--bodies", *paths],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env=env) for paths, off in zip(files, offsets)]

    def wait_ready(self) -> None:
        for i, p in enumerate(self.procs):
            ready = json.loads(p.stdout.readline() or "{}")
            if not ready.get("ready"):
                raise reference.Failed(f"client {i} is not ready: "
                                       f"{ready}")

    def poll(self):
        """None while every client lives (a ``Popen``'s answer)."""
        return next((p.poll() for p in self.procs
                     if p.poll() is not None), None)

    def quit(self) -> list[list]:
        """Every client's calls: ``[due, start, end, ok, round]``."""
        for p in self.procs:
            p.stdin.write("quit\n")
            p.stdin.flush()
        calls = []
        for p in self.procs:
            out, _ = p.communicate(timeout=60)
            calls.append(json.loads(out.strip().splitlines()[-1])
                         ["calls"])
        return calls

    def kill(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.kill()
            p.wait()


class Watcher(lg.Watcher):
    """``local-global``'s watcher over the global alone: the ticks it
    sees are the global's swaps, what it counts as received are the
    rows the global has imported, and the warm-up ends by the same
    rule (four ticks under traffic, then a whole interval that
    compiled nothing)."""

    def __init__(self, glob, clients: Clients, interval_s: float,
                 seconds: float):
        super().__init__(bytearray(8), glob, glob, interval_s, seconds)
        self.child = clients

    def received_raw(self) -> int:
        return self.imported()

    def _quiet(self, now: float) -> bool:
        """And the global is in its steady state: this swap came
        within a second of its tick (a cold compile in a warm-up flush
        can hold the next ticks back by seconds), and the cycle before
        it, which closed a whole interval of calls, folded every
        client's wire and ended inside its interval."""
        last = self.glob.flush_ring.last()
        return (super()._quiet(now) and now - self.next_tick < 1.0
                and last is not None and not last.error
                and last.imports == len(self.child.procs)
                and last.duration_ns < 0.9e9 * self.iv)


# ----------------------------------------------------------------------
# the run

def serve(c: dict, spec: dict, seed: int, seconds: float, trace: bool,
          scale: dict, t_start: float) -> dict:
    """Bodies made, the global up, the clients started, warm-up, the
    window, every compared interval followed to the sink; then all is
    shut down.  Returns what the comparison and the metrics read."""
    from veneur_tpu.core.config import read_config
    from veneur_tpu.core.server import Server
    from benchmark import trace as trace_mod

    cfg = c["config"]
    common = {**cfg["servers"]["common"], **scale.get("servers", {})}
    iv = float(str(common["interval"]).rstrip("s"))
    t = time.monotonic()
    fl = fleet_mod.Fleet(spec, seed)
    rounds = [fl.round(r) for r in range(fl.rounds)]
    tmp = tempfile.mkdtemp(prefix="bench-fleet-")
    glob = clients = watcher = tracing = None
    try:
        made = Bodies(fl, cfg["sizes"]["compression"]).write(rounds, tmp)
        log(phase="traffic", seed=seed, clients=fl.clients,
            bytes_per_interval=made["bytes"],
            centroids_per_interval=made["centroids"],
            rows_per_interval=sum(fl.rows_per_call(l)
                                  for l in range(fl.clients)),
            make_s=round(time.monotonic() - t, 2))
        gsink = lg.make_sink()
        glob = Server(read_config(data={
            **common, **cfg["servers"]["global"],
            "grpc_listen_addresses": ["tcp://127.0.0.1:0"]}),
            extra_sinks=[gsink])
        glob.start()
        log(phase="servers", device=glob.device_info,
            start_s=round(time.time() - t_start, 2))
        clients = Clients(spec, made["files"], fl.offsets,
                          glob.grpc_ports[0], iv)
        clients.wait_ready()
        shutil.rmtree(tmp, ignore_errors=True)   # the clients hold them
        watcher = Watcher(glob, clients, iv, seconds)
        watcher.start()
        while watcher.t0 is None and watcher.is_alive():
            time.sleep(0.01)
        if watcher.t0 is None:
            raise watcher.error or reference.Failed("no window")
        t0, t_end = watcher.t0, watcher.t_end
        log(phase="window", t0=t0, setup_s=round(t0 - t_start, 3),
            warmup_ticks=watcher.t0_swap + 1,
            compiles_at_tick=[w["compiles"] for w in watcher.swaps])
        tick0 = watcher.swaps[watcher.t0_swap]["tick"]
        ticks = [tick0 + iv * i for i in range(1, int(seconds / iv) + 2)
                 if tick0 + iv * i <= t_end]
        if trace:
            # one whole interval: the window's first tick, the flush
            # it starts and the burst of calls beside and after it
            tracing = trace_mod.Slice(
                start=(ticks[0] - 0.3) if ticks else t0,
                stop=min(t_end, (ticks[0] + iv - 0.5) if ticks
                         else t_end))
            tracing.run()
            log(phase="trace", **tracing.timing)
        while watcher.at_end is None and watcher.is_alive():
            time.sleep(0.01)

        # follow every interval of the window to the sink, under the
        # same calls: the clients stop only once all is read
        deadline = time.monotonic() + lg.FOLLOW_INTERVALS * iv + 60
        first = watcher.t0_swap - 1
        last = lg._wait(lambda: next(
            (i for i, w in enumerate(watcher.swaps)
             if w["tick"] >= ticks[-1] - 1e-6), None),
            "the window's last tick", deadline, watcher) \
            if ticks else watcher.t0_swap

        def flushed(i):
            """The global's cycle that closed the interval swap ``i``
            opened, and the sink's batch of it."""
            recs = glob.flush_ring.records()
            rec = lg._cycle_for(recs, watcher.swaps[i + 1]["tick"], iv)
            got = rec and not rec.error and lg._batch_for(
                gsink.batches, rec.start_unix,
                lg._next_start(recs, rec.seq))
            return (rec, got[1]) if got else None
        compared = list(range(first, last))
        lg._wait(lambda: all(flushed(i) for i in compared),
                 "the sink to hold every compared interval", deadline,
                 watcher)
        calls = clients.quit()
        peak = harness.memory_peak()
        swap_c = watcher.swaps[first]
        gseq0 = max((r.seq for r in glob.ledger.records()
                     if r.start_unix < swap_c["seen"] - 0.25),
                    default=0)
        acct = harness.accounting("global", glob, gseq0,
                                  swap_c["stats"]["global"])
        parts = []
        for i in compared:
            rec, kept = flushed(i)
            a, b = watcher.swaps[i]["seen"], watcher.swaps[i + 1]["seen"]
            mine = [(l, cl) for l, per in enumerate(calls)
                    for cl in per if a <= cl[2] < b]
            parts.append({
                "seq": rec.seq, "open": watcher.swaps[i]["open_id"],
                "tick": watcher.swaps[i + 1]["tick"], "kept": kept,
                "imports": rec.imports, "mine": mine,
                "acked": [(l, cl[4]) for l, cl in mine if cl[3]],
                # not acknowledged, or begun in another interval than
                # it was acknowledged in
                "astray": sum(1 for _l, cl in mine
                              if not cl[3] or cl[1] < a),
                "call_s": max((cl[2] - cl[1] for _l, cl in mine),
                              default=None)})
        log(phase="calls", by_interval=[{
            "tick": p["tick"], "calls": len(p["acked"]),
            "astray": p["astray"], "imports": p["imports"],
            "worst_s": p["call_s"] and round(p["call_s"], 3),
            # the burst as the fleet saw it, against the tick that
            # opened the interval
            "first_start_s": round(min(
                (cl[1] for _l, cl in p["mine"]), default=0.0)
                - p["tick"] + iv, 3),
            "last_ack_s": round(max(
                (cl[2] for _l, cl in p["mine"]), default=0.0)
                - p["tick"] + iv, 3),
            "over_1s": sum(1 for _l, cl in p["mine"]
                           if cl[2] - cl[1] > 1.0)}
            for p in parts])
        glag, gmiss = lg.tick_lags(glob, gsink, ticks, iv)
        by_tick = {round(p["tick"], 3): p["call_s"] for p in parts}
        clag = [by_tick[round(t, 3)] for t in ticks
                if by_tick.get(round(t, 3)) is not None]
        rings = {"global": [harness.ring_dict(r)
                            for r in glob.flush_ring.records()
                            if r.start_unix >= t0 - 0.25]}
        log(phase="memory", memory_peak_bytes=peak,
            pressure={"global": glob.overload.pressure.to_dict()})
        cycles = [(r.start_unix, r.start_unix + r.duration_ns / 1e9)
                  for r in glob.flush_ring.records()]
    except BaseException:
        if glob is not None and watcher is not None:
            _diagnose(glob, watcher)
        raise
    finally:
        if tracing is not None:
            tracing.abort()
        if watcher is not None:
            watcher.done = True
        if clients is not None:
            clients.kill()
        if glob is not None:
            glob.shutdown()
        if watcher is not None and watcher.is_alive():
            watcher.join(timeout=5)
        shutil.rmtree(tmp, ignore_errors=True)
    window = [(l, cl) for l, per in enumerate(calls) for cl in per
              if t0 <= cl[1] <= t_end]
    at_t0, at_end = watcher.at_t0, watcher.at_end
    return {"interval_s": iv, "t0": t0, "t_end": t_end, "ticks": ticks,
            "seconds": seconds, "fleet": fl, "rounds": rounds,
            "parts": parts, "acct": acct, "peak": peak,
            "lags": {"global": glag, "clients": clag},
            "lag_of": "global",
            "ticks_missing": gmiss + len(ticks) - len(clag),
            "rings": rings, "at_t0": at_t0, "at_end": at_end,
            "sent": sum(len(per) for per in calls),
            "attempted": sum(fl.rows_per_call(l) for l, _cl in window),
            "received": at_end["received"] - at_t0["received"],
            "blocked_s": 0.0,
            "late_max_s": max((cl[1] - cl[0] for _l, cl in window),
                              default=0.0),
            "calls_late": sum(1 for _l, cl in window
                              if cl[1] - cl[0] > LATE_S),
            "calls": len(window),
            "trace": tracing.reduce(cycles) if tracing else None}


def _diagnose(glob, watcher) -> None:
    """What the record keeps of a run that could not be compared."""
    stats = dict(glob.stats)
    log(phase="diagnose", received=watcher.received(),
        stats={k: v for k, v in stats.items()
               if k.startswith(("received_", "imports_", "flush_",
                                "metrics_"))},
        cycles=[{"start": r.start_unix,
                 "wall_s": round(r.duration_ns / 1e9, 3),
                 "emitted": r.metrics_emitted, "imports": r.imports,
                 "compiles": r.compiles}
                for r in glob.flush_ring.records()[-8:]])


def compare(s: dict, limits: dict) -> tuple[dict, int]:
    """Every compared interval against the reference of the calls it
    acknowledged: the numbers compared (the worst or the sum over the
    intervals; ``NUMBERS``) and the rows of the window's intervals
    that one of them did not bear out."""
    fl = s["fleet"]
    numbers = {"sums_off": 0, "readings_missing": 0, "p99_out": 0,
               "p50_rank_err": 0.0, "p90_rank_err": 0.0,
               "card_rel_err": 0.0, "wires_unaccounted": 0}
    failed_rows = 0
    for p in s["parts"]:
        ref = fleet_reference.interval(fl, s["rounds"], p["acked"])
        res = fleet_reference.compare_interval(
            ref, reference.sink_values(p["kept"].values()))
        res["numbers"]["wires_unaccounted"] = (
            p["astray"] + abs(len(p["acked"]) - fl.clients)
            + abs(p["imports"] - fl.clients))
        in_window = p["open"] >= s["t0"] - 1e-3
        if in_window and any(v > limits[k]
                             for k, v in res["numbers"].items()):
            failed_rows += ref["rows"]
        log(phase="compared", seq=p["seq"], rows=ref["rows"],
            calls=len(p["acked"]), imports=p["imports"],
            in_window=in_window, **res["numbers"],
            p_rel_err=res["p_rel_err"], notes=res["notes"])
        for k, v in res["numbers"].items():
            numbers[k] = max(numbers[k], v) if isinstance(
                numbers[k], float) else numbers[k] + v
    numbers["dropped"] = sum(s["acct"].values())
    numbers["ticks_missing"] = s["ticks_missing"]
    # the stated load offered: a call starts when it is due, unless
    # the client's last one is still out
    numbers["calls_late_pct"] = (
        100.0 * s["calls_late"] / s["calls"] if s["calls"] else 100.0)
    return numbers, failed_rows
