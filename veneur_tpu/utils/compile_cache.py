"""Persistent XLA compilation cache policy, in one place.

Restart-after-crash (the flush-watchdog model) loads each kernel from
disk instead of compiling it again when the cache is enabled.  The
policy knobs (minimum compile time worth persisting) live here so the
server, the bench and ``chip_smoke.py`` can't drift.

Where the cache lives, in this order: ``JAX_COMPILATION_CACHE_DIR``
when the environment sets it (JAX reads it itself; nothing here sets a
directory then, so whoever runs the program can place the cache); else
the path the caller gives, a relative one resolved against the
checkout root; else ``<checkout>/.jax_cache``.  A cache directory
that moves never hits, so none of these is built from a temporary
name, a uid, a pid or the time.
"""

from __future__ import annotations

import os

JAX_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_monitoring_installed = False


def resolve_dir(path: str = "") -> str | None:
    """The directory ``enable(path)`` sets in code, or None when the
    environment already placed the cache and code sets none."""
    if os.environ.get(JAX_ENV_VAR):
        return None
    return os.path.join(CHECKOUT_ROOT, path or ".jax_cache")


def enable(path: str = "") -> bool:
    """Turn on JAX's persistent compilation cache (see the module
    docstring for where).  Returns True when the directory already
    held entries (a warm cache) — callers that report compile times
    should surface this, since warm 'cold intervals' measure cache
    loads, not compiles."""
    import jax
    directory = resolve_dir(path)
    if directory is not None:
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      0.5)
    install_monitoring()
    try:
        return bool(os.listdir(jax.config.jax_compilation_cache_dir))
    except OSError:
        return False


def install_monitoring(registry=None) -> None:
    """Feed JAX's persistent-cache hit/miss events into the device
    cost registry so /debug/vars and the bench can distinguish a disk
    load from a real XLA compile.  Idempotent; safe when the running
    jax predates the events (the listener just never fires)."""
    global _monitoring_installed
    if _monitoring_installed:
        return
    if registry is None:
        from veneur_tpu.observe.devicecost import REGISTRY as registry
    try:
        from jax import monitoring
    except ImportError:
        return

    def _on_event(event, **kwargs):
        if event == _HIT_EVENT:
            registry.add_cache_hit()
        elif event == _MISS_EVENT:
            registry.add_cache_miss()

    monitoring.register_event_listener(_on_event)
    _monitoring_installed = True
