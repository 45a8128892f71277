"""``chip_smoke.py`` away from the chip: it refuses to pass without a
TPU, its body holds every comparison at a tiny scale on the CPU, and
the compile cache it shares with ``bench.py`` and ``Server`` can be
placed from outside."""

import json
import os
import subprocess
import sys

import jax
import pytest

from veneur_tpu.utils import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _env(tmp_path, **extra):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("VENEUR_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               **extra)
    return env


def test_fails_without_a_tpu(tmp_path):
    out = subprocess.run([sys.executable, SMOKE], env=_env(tmp_path),
                         capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


_TINY = """
import json
import chip_smoke
scale = chip_smoke.Scale(
    timers=40, counters=50, gauges=50, global_counters=10, sets=8,
    set_members=6000, windows=2, interval_s=1,
    table={"tpu_histo_rows": 64, "tpu_counter_rows": 128,
           "tpu_gauge_rows": 128, "tpu_set_rows": 16})
chip_smoke.run(scale, seed=3)
print(json.dumps({"body": "passed"}))
"""


def test_body_holds_every_comparison_at_tiny_scale_on_cpu(tmp_path):
    """Rehearsal 1 of the on-chip-measurement guide: the same code
    ``main()`` runs on the chip, on the CPU backend, small tables."""
    out = subprocess.run([sys.executable, "-c", _TINY],
                         env=_env(tmp_path), capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[-1] == '{"body": "passed"}'
    phases = [json.loads(ln)["phase"] for ln in lines[:-1]]
    assert phases.count("checked") == 3      # warm-up + two windows
    assert phases.count("accounting") == 2   # local and global
    # the cache is where the environment placed it
    assert f'"cache_dir": "{tmp_path / "cache"}"' in lines[0]


@pytest.mark.parametrize("env_dir,path,want", [
    ("/placed/outside", "ignored", None),
    ("", "cache/xla", os.path.join(ROOT, "cache/xla")),
    ("", "", os.path.join(ROOT, ".jax_cache")),
], ids=["variable-set", "relative-path", "nothing"])
def test_compile_cache_placement(monkeypatch, env_dir, path, want):
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set no directory is set
    in code; else a relative path resolves against the checkout, and
    no path at all means ``<checkout>/.jax_cache``."""
    if env_dir:
        monkeypatch.setenv(compile_cache.JAX_ENV_VAR, env_dir)
    else:
        monkeypatch.delenv(compile_cache.JAX_ENV_VAR, raising=False)
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(compile_cache, "install_monitoring",
                        lambda registry=None: None)
    assert compile_cache.resolve_dir(path) == want
    compile_cache.enable(path)
    assert updates.get("jax_compilation_cache_dir") == want
    assert "jax_persistent_cache_min_compile_time_secs" in updates
