"""The main path's kernels compile for the v5e, at full width.

The TPU's compiler is installed in the sandbox and compiles for a chip
that is described, not attached (on-chip-measurement guide, section
2.3).  Nothing runs here, so a pass says nothing about results or
times: ``chip_smoke.py`` is the run.  This is the only file in the
repository that describes a topology; it does so inside module-scoped
fixtures (never at import, in a ``skipif`` or in ``parametrize``
arguments), because only one process may load the chip's library and
every xdist worker imports every test file.

The Pallas kernel's grid is over 8-row blocks, so 1,024 rows at the
default capacity (616 at compression 100) stand for the table's
16,384: the widths are what the compiler is asked about.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from veneur_tpu.ops import hll, pallas_merge, segment, superbatch, tdigest

ROWS = 1024
COMPRESSION = tdigest.DEFAULT_COMPRESSION
CAP = tdigest.capacity_for(COMPRESSION)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def shape(one_chip):
    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(dims), dtype,
                                    sharding=one_chip)
    return make


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# the table's widths give two kernels: 616+256 pads to 1,024 lanes,
# 616+512 and 616+616 to 2,048
@pytest.mark.parametrize("batch", [256, 616])
def test_pallas_merge_with_tail_refinement(shape, batch):
    assert pallas_merge.supported(CAP, batch)

    def merge(m, w, nm, nw):
        return pallas_merge.merge_planes(
            m, w, nm, nw, delta=tdigest._SCALE_MULT * COMPRESSION,
            tail_coeff=tdigest._TAIL_MULT * COMPRESSION,
            tail_q0=tdigest._TAIL_Q0, tail_qmin=tdigest._TAIL_QMIN,
            interpret=False)

    compiled = jax.jit(merge).lower(
        shape((ROWS, CAP)), shape((ROWS, CAP)),
        shape((ROWS, batch)), shape((ROWS, batch))).compile()
    assert _has_kernel(compiled)
    # the kernel's own name and static shape, as a trace will show it
    assert f"%tdigest_merge_c{CAP}_k{batch}" in compiled.as_text()


def test_superbatch_step_with_kernel_inlined(shape, monkeypatch):
    """The default cycle's one fused dispatch: counters, gauges and a
    ranked unit-weight timer batch with row stats, planes donated as
    the build donates them.  Code that asks ``jax.default_backend()``
    sees the CPU here, so the test steers the merge to the kernel."""
    monkeypatch.setattr(tdigest, "_MERGE_MODE", "pallas")
    monkeypatch.setattr(pallas_merge, "_INTERPRET", False)
    spec = superbatch.SBSpec(
        counter_rows=ROWS, gauge_rows=ROWS, histo_n=49152,
        histo_slots=256, histo_unit=True, histo_stats=True,
        compression=COMPRESSION)
    words = superbatch.layout(spec)["total"]
    compiled = superbatch.step.lower(
        spec, shape((ROWS,)), shape((ROWS,)),
        shape((ROWS, CAP)), shape((ROWS, CAP)),
        shape((ROWS, segment.HISTO_STAT_COLS)),
        shape((0,), jnp.uint8), shape((words,), jnp.int32)).compile()
    assert _has_kernel(compiled)
    # every class's operations carry its scope, the kernel its name
    text = compiled.as_text()
    assert ("jit(_fused)/sb.histo/jit(ingest_ranked_unit)/"
            f"tdigest_merge_c{CAP}_k256/pallas_call") in text
    assert "jit(_fused)/sb.counter/" in text
    assert "jit(_fused)/sb.gauge/" in text


def test_hll_insert_packed(shape):
    jax.jit(hll.insert_packed).lower(
        shape((ROWS, hll.M), jnp.uint8), shape((65536,), jnp.int32),
        shape((65536,), jnp.int32)).compile()


def test_quantile_readout_three_percentiles(shape):
    tdigest._quantile_interp.lower(
        shape((ROWS, CAP)), shape((ROWS, CAP)), shape((3,)),
        shape((ROWS,)), shape((ROWS,))).compile()


# ----------------------------------------------------------------------
# the mesh table's two SPMD programs on the four described chips: the
# cell ``global-64-locals-mesh2x2``'s mesh, widths and batch

@pytest.fixture(scope="module")
def mesh_2x2(topo):
    from veneur_tpu.parallel import sharded
    mesh = sharded.make_mesh(topo.devices, n_shard=2)
    assert dict(mesh.shape) == {"shard": 2, "series": 2}
    return mesh


def _mesh_programs(mesh, monkeypatch):
    """(update, merge, state shapes, batch shapes) at the default
    widths and the server's batch (four staging thresholds), 1,024
    rows a class standing for 16,384."""
    from jax.sharding import NamedSharding

    from veneur_tpu.parallel import sharded
    monkeypatch.setattr(tdigest, "_MERGE_MODE", "pallas")
    monkeypatch.setattr(pallas_merge, "_INTERPRET", False)
    cfg = sharded.ShardedConfig(
        rows=ROWS, set_rows=64, counter_rows=ROWS, gauge_rows=ROWS,
        compression=COMPRESSION, slots=512, batch=4 * 65536)
    s = mesh.shape["shard"]
    dims = {"counters": ((s, ROWS), jnp.float32),
            "gauges": ((s, ROWS), jnp.float32),
            "gauge_ticket": ((s, ROWS), jnp.int32),
            "histo_stats": ((s, ROWS, segment.HISTO_STAT_COLS),
                            jnp.float32),
            "histo_import_stats": ((s, ROWS, segment.HISTO_STAT_COLS),
                                   jnp.float32),
            "histo_means": ((s, ROWS, CAP), jnp.float32),
            "histo_weights": ((s, ROWS, CAP), jnp.float32),
            "hll": ((s, 64, hll.M), jnp.uint8)}
    specs = sharded._specs(mesh)
    assert set(dims) == set(specs)
    state = {k: jax.ShapeDtypeStruct(d, dt, sharding=NamedSharding(
        mesh, specs[k])) for k, (d, dt) in dims.items()}
    batch = {k: jax.ShapeDtypeStruct(
        (s, cfg.batch), dt, sharding=NamedSharding(mesh, spec))
        for (k, spec), dt in zip(sharded.batch_specs().items(),
                                 sharded.ShardedAggregator
                                 ._DTYPES.values())}
    return (sharded.make_update_step(mesh, cfg),
            sharded.make_merge_step(mesh, cfg), state, batch)


_COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
                "reduce-scatter", "all-to-all")


def _collectives(text: str) -> dict:
    import re
    return {op: len(re.findall(rf"\s{op}(?:-start)?\(", text))
            for op in _COLLECTIVES}


def test_mesh_update_step_on_2x2_has_the_kernel_and_no_collective(
        mesh_2x2, monkeypatch):
    update, _, state, batch = _mesh_programs(mesh_2x2, monkeypatch)
    compiled = update.lower(state, batch).compile()
    text = compiled.as_text()
    assert _has_kernel(compiled)
    # 616 slots of state beside 512 of a batch: the 2,048-lane kernel
    assert f"tdigest_merge_c{CAP}_k512" in text
    assert sum(_collectives(text).values()) == 0
    assert "jit_shard_update" in update.lower(state, batch).as_text()[:200]


def test_mesh_merge_step_on_2x2_gathers_inside_the_kernels_lanes(
        mesh_2x2, monkeypatch):
    _, merge, state, _ = _mesh_programs(mesh_2x2, monkeypatch)
    assert tdigest.merge_path(CAP, 2 * CAP) == "pallas"
    assert tdigest.merge_path(CAP, 4 * CAP) == tdigest._FALLBACK_MODE
    compiled = merge.lower(state).compile()
    text = compiled.as_text()
    assert _has_kernel(compiled)
    # two shards' slots gathered a row beside an empty state: 1,232
    assert f"tdigest_merge_c{CAP}_k{2 * CAP}" in text
    found = _collectives(text)
    assert found["all-gather"] >= 1 and found["all-reduce"] >= 1
    # the module name the benchmark's reader sums by
    assert "jit_shard_merge" in merge.lower(state).as_text()[:200]
