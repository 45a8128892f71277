"""What the readers of a global's own flush ring share: the cycles of
the window, and a sum of stages over them.

A cycle's ``stages`` (nanoseconds) hold the flush's own steps and, as
``import.<step>``, the durations of every import made since the cycle
before, all wires added up; ``gc.<stage>`` the collector's pauses that
ended inside a stage.
"""


def stage_ms(run, stages, holding=None):
    """The mean, a cycle of the window, of ``stages`` added up, in ms;
    over the cycles that hold the key ``holding`` where one is named.
    A run without such a cycle reads nothing."""
    cycles = [r["stages"] for r in run["rings"].get("global", ())
              if run["t0"] <= r["start_unix"] <= run["t_end"]
              and (holding is None or holding in r["stages"])]
    if not cycles:
        return None
    return sum(s.get(k, 0) for s in cycles
               for k in stages) / len(cycles) / 1e6
