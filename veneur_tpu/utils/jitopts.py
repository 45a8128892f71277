"""Buffer-donation policy for hot-path jits.

Donating the state planes (``donate_argnums``) lets the runtime alias
the output onto the input buffer, so an update-in-place loop copies
nothing.  Donation defaults OFF and is opt-in via VENEUR_TPU_DONATE=1;
whether the default should flip is a measurement the chip has not
made yet (ROADMAP.md A2/C1).  ``chip_smoke.py`` prints the setting.
"""

from __future__ import annotations

import os

DONATE = os.environ.get("VENEUR_TPU_DONATE", "").lower() in (
    "1", "true", "yes", "on")


def donate(*argnums: int) -> tuple[int, ...]:
    """donate_argnums for a hot-path state-update jit: the requested
    argnums when donation is enabled, else none."""
    return argnums if DONATE else ()
