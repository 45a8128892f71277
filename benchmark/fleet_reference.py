"""The plain reference of the topology ``fleet-global`` and the
comparison that decides ``correct`` there.

From the fleet's raw draws (``fleet.py``) and the list of calls an
interval acknowledged, with numpy alone: each timer's samples over the
locals that sent it (exact quantiles by ``numpy.quantile``), the exact
size of each set's union, each global-only counter's sum.  Nothing of
``veneur_tpu`` is imported and nothing the program or the clients made
is read, except the values the global's sink received.  The rank
arithmetic and the sink's keys are ``reference.py``'s.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import PERCENTILES, _key, _rank_distance, \
    _suffixed


def interval(fleet, rounds: list[dict], calls: list[tuple[int, int]]
             ) -> dict:
    """What the global should flush for an interval that acknowledged
    ``calls``, each ``(local, round)``, a call named twice counted
    twice: ``timers`` (series -> its samples), ``sets`` (series -> the
    size of its members' union), ``gcounters`` (series -> sum) and
    ``rows`` (the rows those calls carried)."""
    samples: dict[int, list] = {}
    members: dict[int, list] = {}
    sums: dict[int, int] = {}
    rows = 0
    for local, r in calls:
        rnd, j = rounds[r], fleet.slot_of(local)
        rows += fleet.rows_per_call(local)
        for i in fleet.series_of(local, "timer").tolist():
            samples.setdefault(i, []).append(rnd["samples"][i, j])
        for i in fleet.series_of(local, "set").tolist():
            members.setdefault(i, []).append(rnd["members"][i, j])
        for i in fleet.series_of(local, "gcount").tolist():
            sums[i] = sums.get(i, 0) + int(rnd["increments"][i, j])

    def keyed(kind: str, d: dict) -> dict:
        return {_key(fleet.names[kind][i], fleet.tags[kind][i]): v
                for i, v in d.items()}
    return {"rows": rows,
            "timers": keyed("timer", {i: np.concatenate(v)
                                      for i, v in samples.items()}),
            "sets": keyed("set", {i: len(np.unique(np.concatenate(v)))
                                  for i, v in members.items()}),
            "gcounters": keyed("gcount", sums)}


def compare_interval(ref: dict, glob: dict) -> dict:
    """One interval of the global's sink (``reference.sink_values``)
    against ``interval``'s reference: the numbers compared and a few
    lines that say what was off.  The numbers mean what
    ``reference.compare_interval``'s of the same names mean."""
    notes: list[str] = []

    def note(msg):
        if len(notes) < 8:
            notes.append(msg)

    def once(kind, key, vals) -> bool:
        if vals is not None and len(vals) == 1:
            return True
        note(f"{kind} {key}: flushed "
             f"{0 if vals is None else len(vals)} times")
        return False

    sums = 0
    for key, want in ref["gcounters"].items():
        vals = glob.get(key)
        if not once("global counter", key, vals):
            sums += 1
        elif float(vals[0]) != float(want):
            sums += 1
            note(f"global counter {key}: {vals[0]} != {want}")

    missing = p99_out = 0
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
                if once(f"timer p{q}", key, vals):
                    got[i] = vals[0]
                else:
                    missing += 1
            have = ~np.isnan(got)
            if not have.any():
                continue
            want = np.quantile(xs[have], q, axis=1)
            rel = np.abs(got[have] - want) / np.abs(want)
            # a reading within 1 % of numpy's is inside the budget
            # whatever its rank (samples can lie closer than that)
            dist = np.where(rel <= 0.01, 0.0,
                            _rank_distance(xs[have], q, got[have]))
            rel_err[q] = max(rel_err[q], float(rel.max()))
            rank_err[q] = max(rank_err[q], float(dist.max()) / n)
            if q == 0.99:
                p99_out += int((dist > 0).sum())
                if (dist > 0).any():
                    i = int(np.argmax(dist))
                    note(f"p99 of {keys[i]}: {got[have][i]} vs "
                         f"{want[i]}")
    card = 0.0
    for key, n in ref["sets"].items():
        vals = glob.get(key)
        if once("set", key, vals):
            card = max(card, abs(vals[0] - n) / n)
        else:
            missing += 1
    return {"numbers": {
        "sums_off": sums, "readings_missing": missing,
        "p99_out": p99_out,
        "p50_rank_err": rank_err[0.5], "p90_rank_err": rank_err[0.9],
        "card_rel_err": card},
        "p_rel_err": {str(q): rel_err[q] for q in PERCENTILES},
        "notes": notes}

