"""The t-digest merge's memory floor for a cell, from the cell's two
files alone: it reads the same work whatever implements the merge.

An interval reads and writes each live timer's digest row once on the
local and once on the global (``digest_slots`` means and weights and
``digest_stat_cols`` statistics, 4 bytes each), reads each staged
sample (a 4-byte value and a 4-byte weight) on the local, and each
forwarded centroid (the same 8 bytes) on the global.  At compression
100 the cell's 96 samples a timer stay singletons, so the global
reads as many centroids as the local read samples.  The merge does
no arithmetic to speak of beside those bytes: memory bounds it.
"""

# published peaks, keyed by ``device_kind``.  Google Cloud
# documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}

SERVERS = 2          # the local and the global each merge every row
VALUE_BYTES = 4      # f32 planes, f32 staged values and weights


def row_bytes(config: dict) -> int:
    """One digest row of the configuration: means, weights, stats."""
    s = config["sizes"]
    return VALUE_BYTES * (2 * int(s["digest_slots"])
                          + int(s["digest_stat_cols"]))


def samples_per_timer(traffic: dict) -> int:
    """Samples a timer takes in an interval."""
    return (int(traffic["round"]["samples_per_timer"])
            * int(traffic["rounds_per_interval"]))


def bytes_per_interval(config: dict, traffic: dict) -> int:
    timers = int(traffic["round"]["timers"])
    if timers > int(config["sizes"]["histo_rows"]):
        raise ValueError(f"{timers} timers do not fit the "
                         "configuration's histogram rows")
    per_row = (2 * row_bytes(config)
               + 2 * VALUE_BYTES * samples_per_timer(traffic))
    return timers * SERVERS * per_row


def floor_ms(config: dict, traffic: dict, device_kind: str) -> float:
    """The least time the device could take over those bytes.  A
    device kind without a published peak is an error, not a
    default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}: add it to PEAKS with its "
                       "source")
    return 1e3 * bytes_per_interval(config, traffic) \
        / PEAKS[device_kind]["hbm_bytes_per_s"]
