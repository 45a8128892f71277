"""The local's unary call to the global, from call to return: stage
forward.send of its flush ring, mean a cycle of the window.  The
global's whole import handler runs inside it; what is left over is the
wire.  A program without that stage reads nothing."""
LAYER = "forward"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("forward.send",)


def read(run):
    cycles = [r["stages"] for r in run["rings"]["local"]
              if r["start_unix"] <= run["t_end"]
              and STAGES[0] in r["stages"]]
    if not cycles:
        return None
    return sum(s.get(k, 0) for s in cycles
               for k in STAGES) / len(cycles) / 1e6
