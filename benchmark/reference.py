"""The plain reference and the comparison that decides ``correct``.

The reference parses the bytes that were sent, with numpy and str
only: nothing of ``veneur_tpu`` is imported here and nothing the
program made is read, except the values its sinks received.  Every
distinct round is parsed once; an interval's reference combines the
rounds by the order in which they were sent inside it.
"""

from __future__ import annotations

import math

import numpy as np

PREFIX = "bench."
PERCENTILES = (0.5, 0.9, 0.99)
SCOPE_TAG = "veneurglobalonly"


class Failed(Exception):
    """A check could not be made at all (not: a number over a limit)."""


def _key(name, tags) -> tuple:
    """A series' identity as both sides spell it: the name and its
    sorted tags, the scope tag aside."""
    if isinstance(name, bytes):
        name = name.decode()
    tags = [t.decode() if isinstance(t, bytes) else t for t in tags]
    return name, tuple(sorted(t for t in tags if t != SCOPE_TAG))


def parse_round(datagrams: list[bytes]) -> dict:
    """One round's sums, samples, last writes and members a series."""
    timers: dict = {}
    counters: dict = {}
    gcounters: dict = {}
    gauges: dict = {}
    sets: dict = {}
    n_lines = 0
    scope = SCOPE_TAG.encode()
    for dg in datagrams:
        for ln in dg.split(b"\n"):
            n_lines += 1
            head, typ, tags = ln.split(b"|")
            name, val = head.split(b":")
            raw = (name, tags)
            if typ == b"ms":
                timers.setdefault(raw, []).append(float(val))
            elif typ == b"c":
                into = gcounters if scope in tags else counters
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


def combine(pieces: list[dict]) -> dict:
    """The reference of one interval from the parsed pieces of the
    stream that went into it, in the order they were sent (a whole
    round that was sent twice is the same piece twice)."""
    times: dict[int, int] = {}
    for p in pieces:
        times[id(p)] = times.get(id(p), 0) + 1
    out = {"lines": sum(p["lines"] for p in pieces),
           "counters": {}, "gcounters": {}, "gauges": {}, "sets": {},
           "timers": {}}
    done: set[int] = set()
    for p in pieces:
        out["gauges"].update(p["gauges"])       # the last write wins
        for key, xs in p["timers"].items():
            out["timers"].setdefault(key, []).extend(xs)
        if id(p) in done:
            continue
        done.add(id(p))
        n = times[id(p)]
        for kind in ("counters", "gcounters"):
            into = out[kind]
            for key, v in p[kind].items():
                into[key] = into.get(key, 0) + n * v
        for key, members in p["sets"].items():
            out["sets"].setdefault(key, set()).update(members)
    return out


# ----------------------------------------------------------------------
# what the sinks received

def sink_values(metrics) -> dict:
    """``(name, tags) -> [values]`` over the benchmark's own series,
    from ``(name, tags, value)`` of everything a sink was handed."""
    out: dict = {}
    for name, tags, value in metrics:
        if name.startswith(PREFIX):
            out.setdefault(_key(name, tags), []).append(value)
    return out


def _suffixed(values: dict, suffix: str) -> dict:
    cut = len(suffix)
    return {(name[:-cut], tags): v for (name, tags), v in values.items()
            if name.endswith(suffix)}


def _rank_distance(xs: np.ndarray, q: float, got: np.ndarray
                   ) -> np.ndarray:
    """How many ranks ``got`` lies from where quantile ``q`` of each
    sorted row of ``xs`` may lie.  Nought inside the order statistics
    next to ``q * (n - 1)``, one rank either side allowed, since two
    interpolation rules differ by that much; float32 planes get a
    slack of 1e-5."""
    n = xs.shape[1]
    pos = q * (n - 1)
    slack = 1e-5 * np.maximum(np.abs(got), 1.0)
    below = (xs < (got - slack)[:, None]).sum(1)     # strictly under
    upto = (xs <= (got + slack)[:, None]).sum(1)     # at or under
    # got equals the samples at ranks [below, upto - 1], or lies
    # between two samples, halfway between ranks below - 1 and below
    lo_rank = np.where(upto > below, below, below - 0.5)
    hi_rank = np.where(upto > below, upto - 1, below - 0.5)
    want_lo = math.floor(pos) - 1
    want_hi = math.ceil(pos) + 1
    return np.maximum(0, np.maximum(lo_rank - want_hi,
                                    want_lo - hi_rank)).astype(float)


def compare_interval(ref: dict, local: dict, glob: dict) -> dict:
    """One interval's sink output against its reference.  Returns the
    numbers compared (each has a limit of its own, see ``LIMITS``)
    and a few lines that say what was off."""
    notes: list[str] = []

    def note(msg):
        if len(notes) < 8:
            notes.append(msg)

    def exact(kind, want, got) -> int:
        bad = 0
        for key, w in want.items():
            vals = got.get(key)
            if vals is None:
                bad += 1
                note(f"{kind} {key} missing")
            elif len(vals) != 1:
                bad += 1
                note(f"{kind} {key} flushed {len(vals)} times")
            elif float(vals[0]) != float(w):
                bad += 1
                note(f"{kind} {key}: {vals[0]} != {w}")
        return bad

    sums = exact("counter", ref["counters"], local)
    sums += exact("global counter", ref["gcounters"], glob)
    sums += exact("timer count",
                  {k: len(v) for k, v in ref["timers"].items()},
                  _suffixed(local, ".count"))
    sums += exact("gauge", ref["gauges"], local)

    missing = 0
    p99_out = 0
    rank_err = {q: 0.0 for q in PERCENTILES}
    rel_err = {q: 0.0 for q in PERCENTILES}
    by_n: dict[int, list] = {}
    for key, xs in ref["timers"].items():
        by_n.setdefault(len(xs), []).append(key)
    pct = {q: _suffixed(glob, f".{int(round(q * 100))}percentile")
           for q in PERCENTILES}
    for n, keys in by_n.items():
        xs = np.sort(np.asarray([ref["timers"][k] for k in keys]), 1)
        for q in PERCENTILES:
            got = np.full(len(keys), np.nan)
            for i, key in enumerate(keys):
                vals = pct[q].get(key)
                if vals is not None and len(vals) == 1:
                    got[i] = vals[0]
                else:
                    missing += 1
                    note(f"timer {key} p{q}: flushed "
                         f"{0 if vals is None else len(vals)} times")
            have = ~np.isnan(got)
            if not have.any():
                continue
            want = np.quantile(xs[have], q, axis=1)
            rel = np.abs(got[have] - want) / np.abs(want)
            dist = _rank_distance(xs[have], q, got[have])
            rel_err[q] = max(rel_err[q], float(rel.max()))
            # a reading within 1 % of numpy's is inside the budget
            # whatever its rank (samples can lie closer than that)
            dist = np.where(rel <= 0.01, 0.0, dist)
            rank_err[q] = max(rank_err[q], float(dist.max()) / n)
            if q == 0.99:
                out = int((dist > 0).sum())
                p99_out += out
                if out:
                    i = int(np.argmax(dist))
                    note(f"p99 of {keys[i]}: {got[have][i]} vs "
                         f"{want[i]}")
    card = 0.0
    for key, members in ref["sets"].items():
        vals = glob.get(key)
        if vals is None or len(vals) != 1:
            missing += 1
            note(f"set {key}: flushed "
                 f"{0 if vals is None else len(vals)} times")
            continue
        card = max(card, abs(vals[0] - len(members)) / len(members))
    return {"numbers": {
        "sums_off": sums, "readings_missing": missing,
        "p99_out": p99_out,
        "p50_rank_err": rank_err[0.5], "p90_rank_err": rank_err[0.9],
        "card_rel_err": card},
        "p_rel_err": {str(q): rel_err[q] for q in PERCENTILES},
        "notes": notes}
