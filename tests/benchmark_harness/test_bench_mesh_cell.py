"""The cell ``global-64-locals-mesh2x2`` (configuration
``global-mesh2x2`` x traffic ``fleet64-import``, topology
``fleet-global``, four chips): its body traced at the tiny scale on
the CPU through the real topology and a ``Server`` with
``tpu_mesh_shards: 2`` (the conftest's eight virtual devices: shard
2 x series 4), two faults planted under the harness in the mesh
table's merge, its files and entries, its traffic at full size, the
merge's bytes function, and the five readers it brought."""

import json
import os
import types

import numpy as np
import pytest

import structure
from bench_util import ROOT, TINY, run_py

from benchmark import fleet as fleet_mod
from benchmark import harness

CELL = "global-64-locals-mesh2x2"
ONE_CHIP = "global-64-locals"
NEW = ("shard_final_step_ms", "shard_merge_ms", "shard_merge_device_ms",
       "collective_device_ms", "shard_merge_roofline")
# the older metrics whose readers read this cell's run as it is
OLDER = ("sender_late_ms", "flush_lag_max_ms", "device_idle_pct",
         "global_readout_ms", "global_emit_ms", "global_gc_pause_ms",
         "import_fold_ms", "import_fold_wait_ms", "import_fold_apply_ms",
         "import_fold_step_ms", "set_union_ms", "import_call_ms",
         "merge_device_ms")
DEVICE = ("device_idle_pct", "merge_device_ms", "shard_merge_device_ms",
          "collective_device_ms", "shard_merge_roofline")
V5E = "TPU v5 lite"
BENCH = structure.load_bench(ROOT)


def _reader(name):
    return harness.load_module("layer_metrics", name).read


def _body(seed=3600000011, trace=False, **scale):
    c = harness.cell(CELL)
    return c, harness.run_cell(c, seed=seed, seconds=4.0, trace=trace,
                               scale={**TINY[CELL], **scale})


def _window(run):
    return [r for r in run["rings"]["global"]
            if run["t0"] <= r["start_unix"]
            <= run["t_end"] + 0.5 * run["interval_s"]]


# ----------------------------------------------------------------------
# the body, traced, at the tiny scale on the CPU

def test_traced_rehearsal_runs_the_mesh_table_to_a_result_line():
    c, res = _body(trace=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    run = res["run"]
    assert run["lag_of"] == "global"
    fl = fleet_mod.Fleet({**c["traffic"], **TINY[CELL]}, 1)
    window = _window(run)
    assert len(window) == 2
    for r in window:
        # every client's wire folded, the sketches in the one native
        # pass, by the mesh table: two shards, the CPU's eight
        # devices four to a shard
        assert r["imports"] == fl.clients
        assert r["import_set_planes"] == fl.n["set"] * fl.per
        assert r["import_set_planes_loose"] == 0
        assert r["import_centroids"] == fl.n["timer"] * fl.per * fl.samples
        assert r["mesh"] == "2x4" and r["merge_path"] == "scatter"
        # handlers step under the lock as the staging passes its
        # threshold, the tick takes the rest
        assert r["shard_steps"] >= 2
        assert r["stages"]["import.device_step"] > 0
        assert len(r["shard_staged"]) == 2
        # a wire goes whole to one shard in turn: half the calls each
        assert r["shard_staged"][0] == r["shard_staged"][1] > 0
        st = r["stages"]
        parts = [st[f"snapshot.{k}"] for k in (
            "final_step", "shard_merge", "state_reset")]
        assert all(p > 0 for p in parts)
        assert sum(parts) <= st["snapshot"]
        for k in ("resolve", "digests", "sets"):
            assert st[f"import.apply.{k}"] > 0
    line = run_py().result_line(c, res, trace=False)
    assert {"flush_lag_ms", "setup_s"} <= set(line["metrics"])
    assert not (set(NEW) | set(OLDER)) & set(line["metrics"])
    traced = run_py().result_line(c, res, trace=True)
    # on the CPU the device's have nothing to read: never a 0
    host = (set(NEW) | set(OLDER)) - set(DEVICE)
    assert host <= set(traced["metrics"])
    assert not set(DEVICE) & set(traced["metrics"])
    v = {k: traced["metrics"][k]["value"] for k in host}
    assert v.pop("global_gc_pause_ms") >= 0
    assert all(x > 0 for x in v.values())
    # the swap's parts lie inside the readout
    assert v["shard_final_step_ms"] + v["shard_merge_ms"] \
        < v["global_readout_ms"]


# ----------------------------------------------------------------------
# the mesh table's merge broken underneath

def _a_shard_left_out(monkeypatch):
    """The second shard's partials never reach the merge."""
    import jax

    from veneur_tpu.parallel import sharded
    real = sharded.make_merge_step

    def leaky(mesh, cfg):
        merge = real(mesh, cfg)
        empty = sharded.empty_state(mesh, cfg)

        def run(state):
            return merge({k: v.at[1].set(empty[k][1])
                          for k, v in state.items()})
        return jax.jit(run)
    monkeypatch.setattr(sharded, "make_merge_step", leaky)


def _registers_reduced_as_u8(monkeypatch):
    """The register union as a TPU reduces a u8 plane (PR 22): four
    rows packed to a 32-bit word, the all-reduce keeping the whole
    word of the shard whose word is largest."""
    import jax
    import jax.numpy as jnp

    from veneur_tpu.parallel import sharded

    def packed(regs):
        rows, m = regs.shape
        lanes = regs.reshape(rows // 4, 4, m).astype(jnp.uint32)
        shift = jnp.arange(4, dtype=jnp.uint32)[None, :, None] * 8
        word = jax.lax.pmax((lanes << shift).sum(axis=1,
                                                dtype=jnp.uint32),
                            sharded.SHARD)
        return ((word[:, None, :] >> shift) & 0xFF).astype(
            jnp.uint8).reshape(rows, m)
    monkeypatch.setattr(sharded, "_union_registers", packed)


# sets as large as fill most of a sketch's registers: where a register
# of one row is seldom set beside the same register of the next, a
# packed word's maximum is nearly always the registers' own (the
# cell's 320 members leave 95 % of 16,384 registers at nought; PR 22
# saw the fault at 950 rows of them, the tiny scale has 6)
_DENSE_SETS = {"members_per_set": 30_000, "set_pool": 120_000}


@pytest.mark.parametrize("fault,numbers,scale", [
    (_a_shard_left_out, ("sums_off", "card_rel_err"), {}),
    (_registers_reduced_as_u8, ("card_rel_err",), _DENSE_SETS),
], ids=["shard-left-out", "u8-register-union"])
def test_broken_merge_is_not_correct_by_the_number_meant_for_it(
        monkeypatch, fault, numbers, scale):
    # sound, the same body at the same scale is correct (the traced
    # rehearsal above; dense sets: the last test of this file)
    fault(monkeypatch)
    _, res = _body(seed=3600000013, **scale)
    assert not res["correct"]
    assert res["failed"] > 0
    for number in numbers:
        value, limit = res["checks"][number]
        assert value > limit, (number, res["checks"])
    if "sums_off" not in numbers:
        # the registers' fault leaves sums and digests alone
        assert res["checks"]["sums_off"][0] == 0
        assert res["checks"]["p99_out"][0] == 0


# ----------------------------------------------------------------------
# files and entries

def test_every_structural_property_holds_on_the_repos_own_files():
    structure.check_all(BENCH, ROOT)
    assert structure.topology_of(BENCH, ROOT, CELL) == "fleet-global"
    entry = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "global-mesh2x2", "fleet64-import", 4)
    assert "ICI" in entry["why"] and "lag" in entry["why"]
    # found by name, wherever later entries put them: the cell lists
    # the five it brought and the older ones that read its run
    c = harness.cell(CELL)
    assert set(NEW) | set(OLDER) <= {m["name"] for m in c["per_layer"]}
    assert {"flush_lag_ms", "setup_s"} <= {m["name"]
                                           for m in c["end_to_end"]}
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["layer"] == "mesh table and shard merge"
        assert by_name[name]["moves"] == "flush_lag_ms"
    # the fold's roofline describes one chip's import merge (PR 34's
    # floor), not a mesh's whole-table update merges: not this cell's
    assert CELL not in by_name["import_merge_roofline"]["workloads"]


def test_configuration_is_global_defaults_plus_the_one_key():
    import jax

    from veneur_tpu.core.config import read_config
    from veneur_tpu.ops import pallas_merge
    from veneur_tpu.parallel import make_mesh
    cfg = harness.cell(CELL)["config"]
    one = harness.cell(ONE_CHIP)["config"]
    assert cfg["topology"] == one["topology"] == "fleet-global"
    assert cfg["servers"]["common"] == one["servers"]["common"]
    assert cfg["servers"]["global"] == {**one["servers"]["global"],
                                        "tpu_mesh_shards": 2}
    assert [k for part in cfg["servers"].values() for k in part
            if k.startswith("tpu_")] == ["tpu_mesh_shards"]
    # the guarantees word for word, the limits number for number
    assert cfg["guarantees"] == one["guarantees"]
    assert cfg["limits"] == one["limits"]
    assert sorted(cfg["reduced"]) == ["chips", "locals"]
    assert set(cfg["reduced_note"]) == set(cfg["reduced"])
    assert "v5e-8" in cfg["reduced_note"]["chips"]
    assert "v5e-8" in cfg["source"] and "ICI" in cfg["source"]
    assert {**cfg["assumed"]} == {**one["assumed"],
                                  "wire_to_shard":
                                  cfg["assumed"]["wire_to_shard"]}
    s = cfg["sizes"]
    assert {k: s[k] for k in one["sizes"]} == one["sizes"]
    conf = read_config(data={**cfg["servers"]["common"],
                             **cfg["servers"]["global"]})
    assert conf.tpu_mesh_shards == s["shards"] == 2
    assert conf.forward_address == ""        # a global: no local
    # four devices under that key are shard 2 x series 2, which is
    # also the program's own split of four
    mesh = make_mesh(jax.devices()[:4], n_shard=conf.tpu_mesh_shards)
    assert dict(mesh.shape) == dict(make_mesh(jax.devices()[:4]).shape) \
        == {"shard": s["shards"], "series": s["series"]}
    assert s["mesh"] == f"{s['shards']}x{s['series']}"
    assert s["rows_per_chip"]["digest_rows"] \
        == s["histo_rows"] // s["series"]
    assert s["rows_per_chip"]["set_rows"] == s["set_rows"] // s["series"]
    # the merge gathers every shard's slots a row: inside the Pallas
    # kernel's lanes on this mesh, past them on shard 4 x series 1
    assert s["gathered_slots"] == s["shards"] * s["digest_slots"]
    assert pallas_merge.supported(s["digest_slots"], s["gathered_slots"])
    assert not pallas_merge.supported(s["digest_slots"],
                                      4 * s["digest_slots"])
    # rows in order of first sight, cut in contiguous halves
    t = harness.cell(CELL)["traffic"]
    live = s["live_rows_per_series_half"]
    for kind, n, rows in (("timers", t["timers"], s["histo_rows"]),
                          ("sets", t["sets"], s["set_rows"]),
                          ("global_counters", t["global_counters"],
                           s["counter_rows"])):
        half = rows // s["series"]
        assert live[kind] == [min(n, half), max(0, n - half)]


def test_traffic_is_the_one_chip_cells_file_unedited():
    c = harness.cell(CELL)
    assert c["traffic"] == harness.cell(ONE_CHIP)["traffic"]
    spec = c["traffic"]
    assert (spec["clients"], spec["rounds"], spec["start_s"],
            spec["end_s"], spec["deadline_s"]) == (64, 2, 0.2, 0.95, 10.0)
    fl = fleet_mod.Fleet(spec, seed=3600000014)
    per_call = {k: {len(fl.series_of(l, k)) for l in range(64)}
                for k in fl.n}
    assert per_call == {"timer": {1250}, "set": {118, 119},
                        "gcount": {125}}
    assert sum(fl.rows_per_call(l) for l in range(64)) == 95_600
    assert 64 * 1250 * spec["samples_per_digest"] == 10_240_000
    # what the mesh table makes of it: a wire stages its centroids and
    # a statistics row a digest to one shard, over the server's
    # staging threshold and inside one update call of four thresholds
    from veneur_tpu.core.config import read_config
    threshold = read_config(data={}).tpu_stage_flush_samples
    assert threshold < 1250 * 128 + 1250 <= 4 * threshold


# ----------------------------------------------------------------------
# the merge's bytes function

def test_shard_merge_floor_is_a_function_of_the_cells_two_files():
    c = harness.cell(CELL)
    k = harness.load_module("kernels", "shard_merge")
    assert k.row_bytes(c["config"]) == 4948
    # every live partial row read once, every merged row written once
    assert k.bytes_per_merge(c["config"], c["traffic"]) \
        == (2 * 10_000 + 10_000) * 4948 + (2 * 950 + 950) * 16384 \
        == 195_134_400
    assert k.floor_ms(c["config"], c["traffic"], V5E) == pytest.approx(
        0.2383, abs=5e-5)
    fewer = {**c["traffic"], "timers": 5_000, "sets": 0}
    assert k.bytes_per_merge(c["config"], fewer) == 15_000 * 4948
    four = {**c["config"], "sizes": {**c["config"]["sizes"], "shards": 4}}
    assert k.bytes_per_merge(four, c["traffic"]) \
        == 5 * (10_000 * 4948 + 950 * 16384)
    with pytest.raises(ValueError):
        k.bytes_per_merge(c["config"], {**c["traffic"],
                                        "timers": 20_000})
    # no literal of the cell in the file: the numbers come from the
    # two files
    with open(os.path.join(ROOT, "benchmark", "kernels",
                           "shard_merge.py")) as f:
        src = f.read().split('"""', 2)[2]
    assert not any(n in src for n in ("10000", "10_000", "950", "4948",
                                      "16384"))


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_shard_merge_floor_raises_on_an_unknown_device(kind):
    c = harness.cell(CELL)
    with pytest.raises(KeyError, match="no published peak"):
        harness.load_module("kernels", "shard_merge").floor_ms(
            c["config"], c["traffic"], kind)


# ----------------------------------------------------------------------
# the readers

_MS = 1_000_000


def _run(**over):
    stages = {"snapshot": 200 * _MS, "snapshot.final_step": 2 * _MS,
              "snapshot.shard_merge": 60 * _MS,
              "snapshot.state_reset": 130 * _MS,
              "dispatch": 30 * _MS, "device_wait": 4 * _MS,
              "host_emit": 6 * _MS, "sink.bench": 1 * _MS,
              "import.apply": 640 * _MS}
    run = {"cell": CELL, "t0": 100.0, "t_end": 140.0,
           "lags": {"global": [0.3, 0.5], "clients": [1.5, 2.5]},
           "lag_of": "global", "late_max_s": 0.004,
           "rings": {"global": [
               {"start_unix": 90.0, "stages": {
                   "snapshot.shard_merge": 999 * _MS}},
               {"start_unix": 110.0, "stages": dict(stages)},
               {"start_unix": 120.0, "stages": {
                   k: 2 * v for k, v in stages.items()}},
               {"start_unix": 150.0, "stages": {
                   "snapshot.shard_merge": 999 * _MS}}]},
           "trace": {"busy_s": 4.0, "window_s": 10.0, "devices": 4,
                     "modules": {
                         "jit_shard_merge": {"n": 4, "total_s": 0.120},
                         "jit_shard_update": {"n": 768,
                                              "total_s": 12.0}},
                     "device_ops_all": [
                         ["%all-gather.3", 0.010],
                         ["%all-gather-start.1", 0.002],
                         ["%all-reduce.7", 0.006],
                         ["%collective-permute-done.2", 0.001],
                         ["%tdigest_merge_c616_k512.2 tpu_custom_call",
                          9.0],
                         ["%fusion.3", 0.5]]}}
    run.update(over)
    return run


def test_readers_on_a_run_that_has_their_spans(monkeypatch):
    import jax
    run = _run()
    assert _reader("shard_final_step_ms")(run) == pytest.approx(1.5 * 2)
    assert _reader("shard_merge_ms")(run) == pytest.approx(1.5 * 60)
    # a sum over the chips: the module's executions on every plane
    assert _reader("shard_merge_device_ms")(run) == pytest.approx(120.0)
    assert _reader("collective_device_ms")(run) == pytest.approx(19.0)
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind=V5E)])
    assert _reader("shard_merge_roofline")(run) == pytest.approx(
        100.0 * (1e3 * 195_134_400 / 819e9) / 120.0)
    assert 0 < _reader("shard_merge_roofline")(run) < 100
    monkeypatch.setattr(jax, "devices", lambda: [
        types.SimpleNamespace(device_kind="cpu")])
    with pytest.raises(KeyError, match="no published peak"):
        _reader("shard_merge_roofline")(run)
    # the older readers the cell lists read the same run as it is
    assert _reader("global_readout_ms")(run) == pytest.approx(1.5 * 234)
    assert _reader("flush_lag_max_ms")(run) == pytest.approx(500.0)
    assert _reader("device_idle_pct")(run) == pytest.approx(60.0)
    assert _reader("import_call_ms")(run) == pytest.approx(2000.0)
    # the Pallas kernel's operations by name, the chips' added up
    assert _reader("merge_device_ms")(run) == pytest.approx(9000.0)


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_where_the_run_has_nothing_for_it(name):
    """A local's run, a program whose swap has no such stage (the
    parent commit's, one chip's table), an untraced run, a trace of
    one chip with no such module and no collective: nothing, and no
    raise."""
    bare = {"start_unix": 110.0, "stages": {
        "snapshot": 1 * _MS, "swap_apply": 30 * _MS, "gc": 0}}
    for run in (_run(rings={}, lags={"local": [0.2]}, trace=None),
                _run(rings={"global": []}, lags={"global": []},
                     trace=None),
                _run(rings={"global": [bare]}, lags={"global": [0.1]},
                     trace={"busy_s": 0.0, "window_s": 1.0,
                            "modules": {"jit__fused": {
                                "n": 1, "total_s": 0.1}},
                            "device_ops_all": [["%fusion.1", 0.5]]}),
                _run(rings={"global": [bare]}, lags={"global": [0.1]},
                     trace={"busy_s": 0.0, "window_s": 1.0,
                            "device_ops_all": []})):
        assert _reader(name)(run) is None


def test_dense_sets_are_correct_on_a_sound_merge():
    """The scale of the u8 fault's run, with nothing planted."""
    _, res = _body(seed=3600000013, **_DENSE_SETS)
    assert res["correct"], res["checks"]
    assert res["checks"]["card_rel_err"][0] < 0.03


def test_tiny_scale_is_the_one_chip_cells_and_says_what_the_cpu_makes():
    mine, one = json.loads(json.dumps(TINY[CELL])), TINY[ONE_CHIP]
    note = mine.pop("note")
    # an update call is four thresholds wide and the CPU pays for
    # every padded lane: the one key more
    assert mine["servers"].pop("tpu_stage_flush_samples") == 2048
    assert mine == one
    assert "shard 2 x series 4" in note
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "fleet64-import.json")) as f:
        assert json.load(f)["name"] == "fleet64-import"
    assert one["servers"]["tpu_histo_rows"] % 4 == 0
    assert one["servers"]["tpu_set_rows"] % 4 == 0
    assert np.prod([2, 4]) == 8
