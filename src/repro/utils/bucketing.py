"""Shared size-bucketing schedule for jit recompile control.

One copy of the padding schedule used where a size arrives from the
host on every call: the step count of ``make_sl_pass(bucket=True)``
(:mod:`repro.core.sl_step`) and the JAX solver's batch padding
(:mod:`repro.core.resource_opt_jax`): exact powers of two up to 16,
then 1/8-octave granularity.  Keeping it in one place keeps their
recompile-count guarantees in sync.  The device engines' pass scans
do not use it: they plan once and size the scan at the plan's own
largest step count (``repro.sim.device_sim.measure_and_plan``).
"""
from __future__ import annotations


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def bucket_size(n: int) -> int:
    """Padded size: powers of two up to 16, then 1/8-octave steps.

    Pure pow2 bucketing wastes up to ~2x compute on padding (n=65 would
    pad to 128).  Above 16 we round up to a multiple of next_pow2(n)/8
    instead: still O(1) distinct compilations per octave, but padding is
    bounded at 25% worst-case (typically <12%).
    """
    if n <= 16:
        return next_pow2(n)
    gran = next_pow2(n) // 8
    return -(-n // gran) * gran
