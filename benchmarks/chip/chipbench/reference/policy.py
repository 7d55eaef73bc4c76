"""The reserve-skip energy policy of one static ring, in plain NumPy.

Pass ``k`` is served by satellite ``k mod M``.  It skips the pass when
its battery holds less than the reserve; otherwise it trains the planned
steps and pays the satellite-side energy of its plan (processing, the
downlink and the ISL hand-off), the battery floored at 0.  After every
pass each satellite of the ring recharges by panel power x pass time, up
to the capacity.  The reading of pass ``k`` is the serving satellite's
battery after both.
"""
from __future__ import annotations

import numpy as np

TRAINED, SHED, SKIPPED = 0, 1, 2


def run(n_passes: int, n_sats: int, drain_j, kept_fraction, n_steps,
        battery_j: float, recharge_j: float, reserve_j: float,
        battery0=None, k0: int = 0) -> dict:
    """Follow passes ``k0 .. k0 + n_passes - 1`` from ``battery0``."""
    battery = (np.full(n_sats, battery_j, np.float64) if battery0 is None
               else np.array(battery0, np.float64))
    action = np.zeros(n_passes, np.int32)
    sat = np.zeros(n_passes, np.int32)
    level = np.zeros(n_passes, np.float64)
    steps = np.zeros(n_passes, np.int32)
    for i in range(n_passes):
        s = (k0 + i) % n_sats
        sat[i] = s
        if battery[s] < reserve_j:
            action[i] = SKIPPED
        else:
            action[i] = SHED if kept_fraction[s] < 1.0 else TRAINED
            steps[i] = n_steps[s]
            battery[s] = min(max(battery[s] - drain_j[s], 0.0), battery_j)
        battery = np.minimum(battery + recharge_j, battery_j)
        level[i] = battery[s]
    return {"action": action, "sat": sat, "battery_j": level,
            "n_steps": steps, "battery": battery}
