"""The fleet's draws: what a fleet of locals forwards to one global in
an interval, made from the seed by numpy alone.

The generator of mode ``import-calls`` (``traffic.py`` makes DogStatsD
lines for one local; this one makes what many locals' flushes hold).
A traffic file of that mode states the fleet: ``clients`` locals;
``timers``, ``sets`` and ``global_counters`` series; each series
forwarded by ``locals_per_series`` of the locals (series *i* of a
class by the locals *l* with *l* = *i* mod ``clients /
locals_per_series``: services of several hosts each); a timer's digest
from a local is ``samples_per_digest`` raw samples; a set's sketch
``members_per_set`` members drawn without replacement from the set's
pool of ``set_pool`` ids for the interval, so the locals' unions
overlap; a counter's value one small integer.  ``rounds`` distinct
intervals are made and sent in turn; the calls start evenly from
``start_s`` to ``end_s`` after the tick in an order the seed permutes.
Every seed makes the same sizes with other values, tags and another
order of calls.

What is drawn here is raw: samples, member ids, increments.  The
reference (``fleet_reference.py``) reads only that; turning a local's
share into the wire's bytes is the clients' business
(``topologies/fleet-global.py``, with the program's own encoder).
"""

from __future__ import annotations

import numpy as np

PREFIX = "bench."
MIN_TAGS, MAX_TAGS = 2, 8


def _tags(rng, n: int) -> list[tuple[str, ...]]:
    """2 to 8 tags a series, as ``traffic.py`` draws them."""
    counts = rng.integers(MIN_TAGS, MAX_TAGS + 1, n)
    vals = rng.integers(0, 50, (n, MAX_TAGS))
    return [tuple(f"t{j}:v{v}" for j, v in enumerate(row[:c]))
            for c, row in zip(counts, vals)]


class Fleet:
    """The series, who forwards which, when each local calls, and one
    round's draws after another."""

    def __init__(self, spec: dict, seed: int):
        self.seed = int(seed)
        self.clients = int(spec["clients"])
        self.per = int(spec["locals_per_series"])
        if self.clients % self.per:
            raise ValueError("clients is not a multiple of "
                             "locals_per_series")
        self.groups = self.clients // self.per
        self.n = {"timer": int(spec["timers"]), "set": int(spec["sets"]),
                  "gcount": int(spec["global_counters"])}
        self.samples = int(spec["samples_per_digest"])
        self.members = int(spec["members_per_set"])
        self.pool = int(spec["set_pool"])
        self.rounds = int(spec["rounds"])
        rng = np.random.default_rng(self.seed)
        self.names = {k: [f"{PREFIX}{k}.{i:06d}" for i in range(n)]
                      for k, n in self.n.items()}
        self.tags = {k: _tags(rng, n) for k, n in self.n.items()}
        self.timer_scale = rng.uniform(1.0, 500.0, self.n["timer"])
        # the calls of an interval start evenly over the span, in an
        # order the seed permutes
        start, end = float(spec["start_s"]), float(spec["end_s"])
        order = rng.permutation(self.clients)
        self.offsets = start + (end - start) * order / max(
            1, self.clients - 1)

    def series_of(self, local: int, kind: str) -> np.ndarray:
        """The series of a class that ``local`` forwards."""
        return np.arange(local % self.groups, self.n[kind], self.groups)

    def slot_of(self, local: int) -> int:
        """Which of a series' ``locals_per_series`` senders it is."""
        return local // self.groups

    def rows_per_call(self, local: int) -> int:
        return sum(len(self.series_of(local, k)) for k in self.n)

    def round(self, r: int) -> dict:
        """Round ``r``'s draws: ``samples`` f64[timers, senders,
        samples_per_digest] to three decimals (a DogStatsD line's),
        ``members`` i64[sets, senders, members_per_set] ids unique to
        the set and the round, ``increments`` i64[counters, senders].
        The second axis is the sender's slot (``slot_of``)."""
        rng = np.random.default_rng([self.seed, 1 + r])
        n, per = self.n, self.per
        samples = np.round(
            rng.lognormal(0.0, 0.6, (n["timer"], per, self.samples))
            * self.timer_scale[:, None, None], 3)
        picks = rng.permuted(
            np.tile(np.arange(self.pool), (n["set"] * per, 1)),
            axis=1)[:, :self.members].reshape(
                n["set"], per, self.members)
        base = (r * n["set"] + np.arange(n["set"])) * self.pool
        return {"round": r, "samples": samples,
                "members": picks + base[:, None, None],
                "increments": rng.integers(1, 28, (n["gcount"], per))}


def member_bytes(ids: np.ndarray) -> list[bytes]:
    """Member ids as the strings a client's hosts would have sent."""
    return [b"m%d" % i for i in ids.tolist()]
