"""Traffic: DogStatsD datagrams made from the seed, by numpy and str.

One general generator reads a traffic file (``traffic/<name>.json``).
A *round* is one pass over the mix: every series of every class
reports once.  ``rounds`` distinct rounds are made from ``--seed`` and
the mode's sender cycles through them.  Every seed makes the same
sizes (series, samples, members) in another order with other values.
"""

from __future__ import annotations

import dataclasses

import numpy as np

PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Mix:
    """What one round carries (the ``round`` object of a traffic file)."""

    timers: int
    samples_per_timer: int
    counters: int
    global_counters: int        # more counters, veneurglobalonly
    gauges: int
    sets: int
    set_members: int            # new members a round, over all sets
    set_resend_share: float = 0.05
    min_tags: int = 2
    max_tags: int = 8
    max_datagram: int = 4096


def _tag_suffixes(rng, n: int, mix: Mix, extra: str = "") -> list[str]:
    """``|#k:v,...`` with ``min_tags``-``max_tags`` tags a series."""
    counts = rng.integers(mix.min_tags, mix.max_tags + 1, n)
    vals = rng.integers(0, 50, (n, mix.max_tags))
    out = []
    for c, row in zip(counts, vals):
        tags = [f"t{j}:v{v}" for j, v in enumerate(row[:c])]
        if extra:
            tags[-1] = extra
        out.append("|#" + ",".join(tags))
    return out


class Traffic:
    """Series identities for the run and one round after another."""

    def __init__(self, mix: Mix, seed: int):
        self.mix = mix
        self.rng = r = np.random.default_rng(seed)
        m = mix

        def names(kind: str, n: int) -> list[str]:
            return [f"{PREFIX}{kind}.{i:06d}" for i in range(n)]
        self.timer = names("timer", m.timers)
        self.timer_sfx = _tag_suffixes(r, m.timers, m)
        self.timer_scale = r.uniform(1.0, 500.0, m.timers)
        self.counter = names("count", m.counters)
        self.counter_sfx = _tag_suffixes(r, m.counters, m)
        self.gauge = names("gauge", m.gauges)
        self.gauge_sfx = _tag_suffixes(r, m.gauges, m)
        self.gcount = names("gcount", m.global_counters)
        self.gcount_sfx = _tag_suffixes(r, m.global_counters, m,
                                        extra="veneurglobalonly")
        self.sets = names("set", m.sets)
        self.set_sfx = _tag_suffixes(r, m.sets, m)
        self._next_member = 0

    def round(self) -> list[bytes]:
        """The datagrams of the next round, lines in random order."""
        m, r = self.mix, self.rng
        lines: list[str] = []
        # timers: lognormal latencies, three decimals on the wire
        vals = (r.lognormal(0.0, 0.6, (m.timers, m.samples_per_timer))
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
        # gauges: written once a round, quarter steps (exact in f32)
        g = r.integers(0, 1 << 18, m.gauges) / 4.0
        lines += [f"{n}:{v}|g{x}"
                  for n, x, v in zip(self.gauge, self.gauge_sfx, g)]
        # sets: members unique over the run, a share sent twice
        if m.sets:
            ids = np.arange(self._next_member,
                            self._next_member + m.set_members)
            self._next_member += m.set_members
            again = int(m.set_members * m.set_resend_share)
            ids = np.concatenate([ids, r.choice(ids, again)])
            which = r.integers(0, m.sets, len(ids))
            lines += [f"{self.sets[w]}:m{i}|s{self.set_sfx[w]}"
                      for w, i in zip(which, ids)]
        order = r.permutation(len(lines))
        out: list[bytes] = []
        cur: list[str] = []
        size = 0
        for j in order:
            ln = lines[j]
            if size + len(ln) + 1 > m.max_datagram and cur:
                out.append("\n".join(cur).encode())
                cur, size = [], 0
            cur.append(ln)
            size += len(ln) + 1
        if cur:
            out.append("\n".join(cur).encode())
        return out


def make_rounds(spec: dict, seed: int) -> list[list[bytes]]:
    """The distinct rounds of a traffic file, from the seed."""
    traffic = Traffic(Mix(**spec["round"]), seed)
    return [traffic.round() for _ in range(int(spec["rounds"]))]


def scaled(spec: dict, scale: dict) -> dict:
    """The traffic file with the CPU rehearsal's overrides: the keys
    of its ``round`` (where it has one) and its top-level numbers.  A
    cell runs the file as it is (``scale`` empty)."""
    out = {**spec, **{k: v for k, v in scale.items()
                      if k not in ("round", "servers", "limits")}}
    if "round" in spec:
        out["round"] = {**spec["round"], **scale.get("round", {})}
    return out
