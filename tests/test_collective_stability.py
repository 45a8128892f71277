"""Collective layout stability across mesh sizes (VERDICT r3 item 7).

The sharded merge's ICI cost model only holds if growing the mesh
keeps the COUNT and KIND of collectives fixed (per-device bytes
shrink, op count must not grow): a regression that loops a collective
per row/slot would compile and verify numerically but scale as
O(rows) on real ICI.  These tests pin the compiled-HLO collective op
census of the merge step across 2/4/8-device meshes — the CPU-mesh
proxy for ICI cost until real multi-chip exists (SURVEY §2.2).
"""

from __future__ import annotations

import re

import jax
import pytest

from veneur_tpu.parallel.sharded import (ShardedConfig, empty_state,
                                         make_merge_step,
                                         make_update_step, make_mesh)

# HLO instruction names for cross-device movement (sync + async-start
# spellings; async -done pairs would double-count)
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "all-to-all", "collective-permute")

_CFG = ShardedConfig(rows=64, set_rows=16, slots=32, batch=256)


def _census(hlo_text: str) -> dict[str, int]:
    return {op: len(re.findall(rf"\s{op}(?:-start)?\(", hlo_text))
            for op in _COLLECTIVES}


def _merge_census(n_devices: int) -> dict[str, int]:
    devs = jax.devices()[:n_devices]
    mesh = make_mesh(devs, n_shard=n_devices)
    state = empty_state(mesh, _CFG)
    merge = make_merge_step(mesh, _CFG)
    return _census(merge.lower(state).compile().as_text())


@pytest.mark.parametrize("n", [2, 4, 8])
def test_merge_collective_census_matches_2dev(n):
    base = _merge_census(2)
    got = _merge_census(n)
    assert got == base, (n, got, base)


def test_merge_collective_census_nonzero_and_bounded():
    """The merge genuinely rides collectives (psum/pmax fold to
    all-reduce, the digest slot union to all-gather) and their count
    is small and fixed — not O(rows) or O(slots)."""
    census = _merge_census(4)
    total = sum(census.values())
    assert census["all-reduce"] >= 1
    assert census["all-gather"] >= 1
    # rows=64, capacity=616: any per-row/per-slot collective loop
    # would blow far past this
    assert total <= 16, census


def test_update_step_has_no_collectives():
    """Ingest is embarrassingly shard-parallel: ALL cross-device
    traffic belongs to the merge.  A collective sneaking into the
    per-interval update step would turn every device_step into an
    ICI round-trip."""
    devs = jax.devices()[:4]
    mesh = make_mesh(devs, n_shard=4)
    state = empty_state(mesh, _CFG)
    import numpy as np
    from veneur_tpu.parallel import sharded as sharded_mod
    update = make_update_step(mesh, _CFG)
    batch = {
        "counter_rows": np.zeros((4, 8), np.int32),
        "counter_vals": np.zeros((4, 8), np.float32),
        "counter_wts": np.ones((4, 8), np.float32),
        "gauge_rows": np.zeros((4, 8), np.int32),
        "gauge_vals": np.zeros((4, 8), np.float32),
        "gauge_ticket": np.zeros((4, 8), np.int32),
        "histo_rows": np.zeros((4, 8), np.int32),
        "histo_vals": np.zeros((4, 8), np.float32),
        "histo_wts": np.ones((4, 8), np.float32),
        "histo_imp": np.zeros((4, 8), np.int32),
        "istat_rows": np.zeros((4, 8), np.int32),
        **{k: np.zeros((4, 8), np.float32)
           for k in sharded_mod.ISTAT_COLS},
        "set_rows": np.zeros((4, 8), np.int32),
        "set_idx": np.zeros((4, 8), np.int32),
        "set_rank": np.zeros((4, 8), np.int32),
    }
    txt = update.lower(state, batch).compile().as_text()
    census = _census(txt)
    assert sum(census.values()) == 0, census