"""The union of a mesh table's partial state at the flush: its memory
floor for a cell, from the cell's two files alone, on all the chips
together.

When the interval closes, every live series has a partial row on each
of the ``shards`` shards (a wire goes whole to one shard in turn, and
every series comes from several locals), held by the chip of that
shard that owns the row's half of the series axis.  Whatever unions
them has to read each live partial row once, on the chip that holds
it, and write each merged live row once, on the chip that owns it:
``shards + 1`` passes over the live digest rows (``digest_slots``
means and weights and ``digest_stat_cols`` statistics, 4 bytes each)
and over the live register rows (``hll_row_bytes``).  The counters'
4 bytes a row are left out: a floor may leave out, not add.  The merge
does no arithmetic to speak of beside those bytes: memory bounds it.
The bytes are all the chips' and so is the time they are held
against (``shard_merge_device_ms`` adds the chips up), so one chip's
bandwidth divides them.

What crosses ICI is not counted: each live row's partials cross one
link of the shard axis, but this file cannot state the v5e's
published per-chip ICI figure with its source from inside the
sandbox, and a floor without a source is no floor.  With it the floor
would be the larger of the two terms.
"""

# published peaks, keyed by ``device_kind``.  Google Cloud
# documentation, "TPU v5e": 16 GB of HBM at 819 GB/s.
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}

VALUE_BYTES = 4      # f32 planes


def row_bytes(config: dict) -> int:
    """One digest row of the configuration: means, weights, stats."""
    s = config["sizes"]
    return VALUE_BYTES * (2 * int(s["digest_slots"])
                          + int(s["digest_stat_cols"]))


def bytes_per_merge(config: dict, traffic: dict) -> int:
    s = config["sizes"]
    timers, sets = int(traffic["timers"]), int(traffic["sets"])
    if timers > int(s["histo_rows"]) or sets > int(s["set_rows"]):
        raise ValueError(f"{timers} timers and {sets} sets do not fit "
                         "the configuration's rows")
    passes = int(s["shards"]) + 1    # every partial read, one written
    return passes * (timers * row_bytes(config)
                     + sets * int(s["hll_row_bytes"]))


def floor_ms(config: dict, traffic: dict, device_kind: str) -> float:
    """The least chip-time the mesh could take over those bytes.  A
    device kind without a published peak is an error, not a
    default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}: add it to PEAKS with its "
                       "source")
    return 1e3 * bytes_per_merge(config, traffic) \
        / PEAKS[device_kind]["hbm_bytes_per_s"]
