"""The imported digests' merge on a global: its memory floor for a
cell, from the cell's two files alone.  It is the least any fold of
the interval has to move, however the program batches it.

By the interval's end every timer's row holds the merge of the digests
its ``locals_per_series`` locals forwarded.  The least a fold can do is
read each of those centroids once (a 4-byte mean and a 4-byte weight)
and read and write each timer's row once (``digest_slots`` means and
weights and ``digest_stat_cols`` statistics, 4 bytes each): a fold
that takes a wire or a few at a time touches a row once a step and
moves more.  The merge does no arithmetic to speak of beside those
bytes: memory bounds it.
"""

# published peaks, keyed by ``device_kind``.  Google Cloud
# documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}

VALUE_BYTES = 4      # f32 planes, f32 centroid means and weights


def row_bytes(config: dict) -> int:
    """One digest row of the configuration: means, weights, stats."""
    s = config["sizes"]
    return VALUE_BYTES * (2 * int(s["digest_slots"])
                          + int(s["digest_stat_cols"]))


def bytes_per_interval(config: dict, traffic: dict) -> int:
    timers = int(traffic["timers"])
    if timers > int(config["sizes"]["histo_rows"]):
        raise ValueError(f"{timers} timers do not fit the "
                         "configuration's histogram rows")
    centroids = (timers * int(traffic["locals_per_series"])
                 * int(traffic["samples_per_digest"]))
    return timers * 2 * row_bytes(config) + centroids * 2 * VALUE_BYTES


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
