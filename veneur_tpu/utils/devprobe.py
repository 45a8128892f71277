"""Killable accelerator-reachability probe, for ``bench.py``'s
orchestrator only: its parent process stays off JAX (a chip belongs
to one process at a time) and runs every cell as a child, so it asks
a short-lived child which backend the cells will get.  ``Server``
never probes: it uses the platform JAX gives it.

Backend init can hang inside the client library, so the probe runs in
a subprocess.  Two classic subprocess gotchas are handled here:

- ``subprocess.run(capture_output=True, timeout=...)`` calls
  ``communicate()`` with no timeout after killing the child; if the
  stuck client forked (or the child sits uninterruptible in a
  transport), the pipe never closes and the caller hangs anyway.  Output goes to a temp file instead of pipes.
- the post-kill ``wait()`` can block on a D-state child; it gets its
  own short timeout and the zombie is abandoned (reaped at our exit).
"""

from __future__ import annotations

import json
import os
import random
import subprocess
import sys
import tempfile
import time

# VENEUR_PROBE_PLATFORM pins the probe's platform (the bench tests
# pass it; it belongs to the benchmark).  On success the probe prints
# one JSON line describing the backend it actually touched, so the
# bench orchestrator can stamp its artifacts with the platform the
# number was measured on — a CPU capture must never be mistakable
# for a device capture.
_PROBE_CODE = ("import os, json, jax, numpy, jax.numpy as jnp;"
               "p = os.environ.get('VENEUR_PROBE_PLATFORM');"
               "p and jax.config.update('jax_platforms', p);"
               "a = jnp.asarray(numpy.zeros(8, numpy.float32));"
               "a.block_until_ready();"
               "d = jax.devices()[0];"
               "print(json.dumps({'platform': d.platform,"
               " 'device_kind': getattr(d, 'device_kind', '?'),"
               " 'num_devices': jax.device_count(),"
               " 'jax_version': jax.__version__}))")


def probe_device_info(timeout_s: float) -> tuple[str | None, dict]:
    """Probe the default backend in a killable subprocess.

    Returns ``(None, info)`` when reachable — ``info`` holds the
    platform/device_kind/jax_version the probe touched — or
    ``(error, {})`` with a one-line description otherwise."""
    with tempfile.TemporaryFile() as errf, \
            tempfile.TemporaryFile() as outf:
        p = subprocess.Popen([sys.executable, "-c", _PROBE_CODE],
                             stdout=outf, stderr=errf)
        try:
            rc = p.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            p.kill()
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                pass  # uninterruptible child: abandon it
            return (f"probe did not finish in {timeout_s:.0f}s "
                    "(device link hung)"), {}
        if rc == 0:
            outf.seek(0)
            line = outf.read().decode(errors="replace").strip()
            try:
                info = json.loads(line.splitlines()[-1])
            except (ValueError, IndexError):
                info = {}
            return None, info
        errf.seek(0)
        tail = errf.read().decode(errors="replace").strip()
        lines = tail.splitlines()
        return ("probe failed (rc={}): {}".format(
            rc, lines[-1] if lines else "no stderr")), {}


def probe_device(timeout_s: float) -> str | None:
    """Returns None when the default backend is reachable, else a
    one-line error description."""
    err, _ = probe_device_info(timeout_s)
    return err


def probe_device_retry_info(budget_s: float, attempt_s: float = 30.0,
                            on_attempt=None
                            ) -> tuple[str | None, dict]:
    """Retry ``probe_device_info`` in short attempts until one succeeds
    or ``budget_s`` of wall-clock is spent: a live probe finishes in
    seconds, so many short attempts with jittered gaps ride out a
    transient stall that one monolithic long attempt surrenders to.  Returns ``(None, info)`` on the first success, else
    ``(last_error, {})``."""
    deadline = time.monotonic() + budget_s
    last_err: str | None = "probe budget is zero"
    attempt = 0
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            break
        attempt += 1
        if on_attempt is not None:
            on_attempt(attempt, remaining)
        last_err, info = probe_device_info(
            min(attempt_s, max(remaining, 5.0)))
        if last_err is None:
            return None, info
        # jittered gap so retry cadence doesn't phase-lock with a
        # periodic link stall; never sleep past the deadline
        gap = min(random.uniform(1.0, 4.0),
                  max(deadline - time.monotonic(), 0.0))
        if gap > 0:
            time.sleep(gap)
    return last_err, {}


def probe_device_retry(budget_s: float, attempt_s: float = 30.0,
                       on_attempt=None) -> str | None:
    """Compatibility wrapper: ``probe_device_retry_info`` minus the
    backend info."""
    err, _ = probe_device_retry_info(budget_s, attempt_s, on_attempt)
    return err
