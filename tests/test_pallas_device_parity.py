"""Mosaic-compiled fused-merge parity on the live device.

The interpret-mode suite (test_pallas_merge.py) pins kernel
SEMANTICS; this test re-proves the invariants on real hardware where
the Mosaic lowering (bf16 splits, polynomial asin, logical-op
selects) actually runs.  Auto-skips on non-TPU backends — under the
CI conftest (forced 8-device CPU mesh) it always skips: pytest can
never see a chip here, and ``chip_smoke.py`` holds the compiled
kernel's results to a reference on the chip (bench.py --pallas-parity
emits the matching artifact)."""

import jax
import pytest


def test_compiled_kernel_parity_on_device():
    if jax.default_backend() != "tpu":
        pytest.skip("lowering parity needs a real TPU backend")
    import bench
    out = bench.pallas_parity()
    assert not out.get("skipped"), out
    assert out["ok"], out["checks"]
