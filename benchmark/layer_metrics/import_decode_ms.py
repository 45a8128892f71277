"""The global decoding a forwarded wire into columns, outside its
ingest lock: stage import.decode of its flush ring.  A global cycle
holds the imports made since its previous one, so the mean is over
the global's cycles that follow the window's cycles of the local by
one interval: the same wires forward_rpc_ms is the mean of, each
import inside its call.  A program without that stage reads nothing."""
LAYER = "import decode and fold"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("import.decode",)


def read(run):
    iv = run["interval_s"]
    sent = [r["start_unix"] for r in run["rings"]["local"]
            if r["start_unix"] <= run["t_end"]]
    cycles = [r["stages"] for r in run["rings"]["global"]
              if STAGES[0] in r["stages"] and any(
                  0.5 * iv < r["start_unix"] - t < 1.5 * iv for t in sent)]
    if not cycles:
        return None
    return sum(s.get(k, 0) for s in cycles
               for k in STAGES) / len(cycles) / 1e6
