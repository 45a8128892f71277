"""The interpreter's collector pauses that fell under the local's tick
path: keys ``gc.<stage>`` of its flush ring (nanoseconds of
collections, on any thread, that ended inside the stage) for the
stages that run in a row on the flush thread up to delivery, mean a
cycle of the window.  ``sink_flush`` waits for the forward, so pauses
during ``forward.encode`` and ``forward.send`` fall in it and are not
added twice; the trailing list after delivery is left out.  A stage
without its key counts 0.  Every cycle of a program that counts holds
the key ``gc`` (the cycle's whole pause, 0 included); a program
without it reads nothing."""
LAYER = "swap and flush readout"
UNIT = "ms"
MOVES = "flush_lag_ms"
STAGES = ("snapshot", "swap_apply", "dispatch", "device_wait",
          "host_emit", "sink_flush")


def read(run):
    cycles = [r["stages"] for r in run["rings"]["local"]
              if r["start_unix"] <= run["t_end"] and "gc" in r["stages"]]
    if not cycles:
        return None
    return sum(s.get("gc." + k, 0) for s in cycles
               for k in STAGES) / len(cycles) / 1e6
