"""Every sink's routing on the local's flush thread, before the first
dispatch: stage sink_flush.route of its flush ring, mean a cycle of
the window.  Some 0.03 ms while no series carries a
``veneursinkonly:`` tag (the cycle's ``sink_only_rows`` 0); a walk of
every live series' tags where one does.  A program without that stage
reads nothing."""
LAYER = "emit and sink fan-out"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("sink_flush.route",)


def read(run):
    cycles = [r["stages"] for r in run["rings"]["local"]
              if r["start_unix"] <= run["t_end"]
              and STAGES[0] in r["stages"]]
    if not cycles:
        return None
    return sum(s.get(k, 0) for s in cycles
               for k in STAGES) / len(cycles) / 1e6
