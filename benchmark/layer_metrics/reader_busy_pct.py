"""Share of the window the local's UDP readers spent draining the
socket, parsing, probing rows and staging: the registry's per-reader
``ingest_duration_ns``, the window's end less its start, over the
window (the mean over the readers that took a batch in it; the
global has no statsd listener, so the readers are the local's).  A
program whose registry keeps no readers reads nothing."""
LAYER = "socket drain, parse, row probe, staging"
UNIT = "%"
MOVES = "flush_lag_ms"


def read(run):
    a, b = run.get("at_t0"), run.get("at_end")
    if not a or not b:
        return None
    r0 = a["registry"].get("readers")
    r1 = b["registry"].get("readers")
    if r0 is None or not r1:
        return None
    busy = [v["ingest_duration_ns"]
            - r0.get(k, {}).get("ingest_duration_ns", 0)
            for k, v in r1.items()]
    busy = [ns for ns in busy if ns > 0]
    window_ns = 1e9 * (b["t"] - a["t"])
    if not busy or window_ns <= 0:
        return None
    return 100.0 * sum(busy) / len(busy) / window_ns
