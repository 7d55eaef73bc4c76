"""Problem (13) by plain bisection: the reference for the planner.

Each instance is one satellite pass: four phases (satellite processing,
downlink, ground processing, uplink) share the time budget
``T = T_pass - T_fixed``, and the plan minimizes their energy
``sum E_i(t_i)`` subject to ``sum t_i <= T`` and ``t_i >= t_min_i``:

* processing at DVFS frequency f: ``t = n W / (N_c N_F f)``, power
  ``P_p (f / f_max)^3``, so ``E(t) = k / t^2`` with
  ``k = P_p / f_max^3 (n W / (N_c N_F))^3`` (paper eqs. 6-7);
* a Shannon link moving ``b`` bits in ``t``: ``E(t) = t (2^(b/(B t)) - 1)
  / g`` with ``g`` the SNR per watt at the mean slant range (eqs. 8-9).

Where the phases' shortest times exceed the budget, the pass sheds
items: it keeps the largest fraction of them that fits, but no less than
``min_fraction`` (then it is infeasible and runs every phase flat out).
The optimum equalizes the marginal energies ``-E_i'(t_i) = lambda``; the
reference finds lambda by geometric bisection and, for each lambda, each
link phase's time by a bisection of its own.  Plain NumPy in one dtype:
float64 for the reference, float32 for the control.
"""
from __future__ import annotations

import math

import numpy as np

R_EARTH_M = 6_371_000.0
MU_EARTH = 3.986_004_418e14
C_LIGHT = 299_792_458.0
LN2 = math.log(2.0)


def plane_geometry(altitude_m: float, min_elevation_deg: float,
                   ring_sats: int) -> dict:
    """Pass duration, mean GS-satellite distance and ISL hop (eqs. 1-5)."""
    re, h = R_EARTH_M, altitude_m
    a = re + h
    period = 2.0 * math.pi * math.sqrt(a ** 3 / MU_EARTH)
    s = math.sin(math.radians(min_elevation_deg))
    d_max = math.sqrt(re ** 2 * s ** 2 + 2.0 * re * h + h ** 2) - re * s
    cos_half = (a ** 2 + re ** 2 - d_max ** 2) / (2.0 * (re ** 2 + re * h))
    alpha = 2.0 * math.acos(min(1.0, max(-1.0, cos_half)))
    # mean slant range over the visible arc, uniform in time (256 points)
    phi = (alpha / 2.0) * (np.arange(256) + 0.5) / 256
    d_mean = float(np.sqrt(re ** 2 + a ** 2 - 2.0 * re * a
                           * np.cos(phi)).mean())
    return {"pass_s": period * alpha / (2.0 * math.pi),
            "d_mean_m": d_mean,
            "isl_m": 2.0 * a * math.sin(math.pi / ring_sats)}


def coefficients(dep: dict, ring_sats: int, w1, w2, dtx, disl, n_items,
                 dtype=np.float64) -> dict:
    """Arrays of every instance's phase constants, broadcast together."""
    geo = plane_geometry(dep["altitude_m"], dep["min_elevation_deg"],
                         ring_sats)
    link, isl, dev = dep["link"], dep["isl"], dep["device"]
    fspl = (4.0 * math.pi * geo["d_mean_m"] * link["carrier_hz"]
            / C_LIGHT) ** 2
    gain = (10.0 ** (link["antenna_gain_db"] / 10.0)
            / (fspl * 10.0 ** (link["noise_power_dbw"] / 10.0)))
    r_max = link["bandwidth_hz"] * math.log2(1.0 + link["max_tx_power_w"]
                                             * gain)
    ops = dev["n_cores"] * dev["flops_per_cycle"]
    w1, w2, dtx, disl, n = np.broadcast_arrays(
        *[np.asarray(x, np.float64) for x in (w1, w2, dtx, disl, n_items)])
    t_fixed = (2.0 * geo["d_mean_m"] / C_LIGHT + disl / isl["rate_bps"]
               + geo["isl_m"] / C_LIGHT)
    k_of = lambda w: (dev["power_max_w"] / dev["f_max_hz"] ** 3   # noqa
                      * (n * w / ops) ** 3)
    bits = n * dtx
    cast = lambda x: np.asarray(x, dtype)                          # noqa
    return {
        "k": cast(np.stack([k_of(w1), k_of(w2)], -1)),
        "tmin_p": cast(np.stack([n * w1, n * w2], -1)
                       / (ops * dev["f_max_hz"])),
        "cc": cast(np.stack([bits, bits], -1) / link["bandwidth_hz"]),
        "tmin_c": cast(np.stack([bits, bits], -1) / r_max),
        "gain": cast(np.full(n.shape, gain)),
        "t_budget": cast(geo["pass_s"] - t_fixed),
        "e_isl": cast(isl["tx_power_w"] * disl / isl["rate_bps"]),
    }


def _comm_marginal(cc, gain, t):
    """-E'(t) of a link phase: (e^x (x - 1) + 1) / g with x = c ln2 / t."""
    x = cc * LN2 / t
    em = np.expm1(x)
    return (em * (x - 1.0) + x) / gain


def _comm_time(cc, gain, lam, lo, hi, iters):
    """The t in [lo, hi] with -E'(t) = lam (the marginal falls with t)."""
    a, b = lo.copy(), hi.copy()
    for _ in range(iters):
        mid = np.sqrt(a) * np.sqrt(b)
        over = _comm_marginal(cc, gain, mid) > lam     # t too short
        a = np.where(over, mid, a)
        b = np.where(over, b, mid)
    t = np.sqrt(a) * np.sqrt(b)
    t = np.where(_comm_marginal(cc, gain, hi) >= lam, hi, t)
    return np.where(_comm_marginal(cc, gain, lo) <= lam, lo, t)


def solve(co: dict, min_fraction: float = 0.05, iters: int = 64) -> dict:
    """Shed, then solve every instance; returns kept fraction, the four
    phase times and energies (canonical order sat, down, gs, up), the
    total energy with E_ISL, and feasibility."""
    dt = co["k"].dtype.type
    err = np.seterr(over="ignore", divide="ignore", invalid="ignore")
    try:
        live_p, live_c = co["k"] > 0, co["cc"] > 0
        T = co["t_budget"]
        tmin_sum = (np.where(live_p, co["tmin_p"], 0).sum(-1)
                    + np.where(live_c, co["tmin_c"], 0).sum(-1))
        no_phase = tmin_sum == 0
        fits = no_phase | ((T > 0) & (tmin_sum <= T))
        frac = np.where(fits, dt(1.0), np.clip(
            T / np.where(tmin_sum > 0, tmin_sum, dt(1.0)),
            dt(min_fraction), dt(1.0)))
        frac = np.where(no_phase | (T > 0), frac, dt(min_fraction))
        f1 = frac[..., None]
        k, tmin_p = co["k"] * f1 ** 3, co["tmin_p"] * f1
        cc, tmin_c = co["cc"] * f1, co["tmin_c"] * f1
        gain = co["gain"][..., None]
        feasible = no_phase | ((T > 0) & (tmin_sum * frac
                                          <= T * (1.0 + 1e-9)))
        t_hi = np.maximum(T, 0)[..., None]
        tmin_p = np.where(live_p, tmin_p, 0)
        tmin_c = np.where(live_c, tmin_c, 0)

        lo_c = np.where(live_c, tmin_c, dt(1.0))
        hi_c = np.broadcast_to(t_hi, cc.shape)

        def times(lam):
            lam2 = lam[..., None]
            tp = np.clip(np.cbrt(2.0 * k / lam2), tmin_p, t_hi)
            tc = _comm_time(cc, gain, lam2, lo_c, hi_c, iters)
            return np.where(live_p, tp, 0), np.where(live_c, tc, 0)

        # lambda between the smallest marginal at the whole budget and
        # the largest at the shortest times
        big = np.finfo(dt).max
        m_hi = np.where(live_p, 2.0 * k / np.where(live_p, tmin_p, 1) ** 3,
                        0).max(-1)
        m_hi = np.maximum(m_hi, np.where(
            live_c, _comm_marginal(cc, gain, lo_c), 0).max(-1))
        m_lo = np.where(live_p, 2.0 * k / t_hi ** 3, big).min(-1)
        m_lo = np.minimum(m_lo, np.where(
            live_c, _comm_marginal(cc, gain, np.maximum(t_hi, 1e-30)),
            big).min(-1))
        lo = np.clip(np.nan_to_num(m_lo, posinf=big), np.finfo(dt).tiny,
                     big)
        hi = np.clip(np.nan_to_num(m_hi, posinf=big), lo, big)
        for _ in range(iters):
            mid = np.sqrt(lo) * np.sqrt(hi)
            tp, tc = times(mid)
            over = tp.sum(-1) + tc.sum(-1) > T                # too slow
            lo = np.where(over, mid, lo)
            hi = np.where(over, hi, mid)
        tp, tc = times(hi)
        tp = np.where(feasible[..., None], tp, tmin_p)
        tc = np.where(feasible[..., None], tc, tmin_c)
        e_p = np.where(live_p & (tp > 0), k / np.where(tp > 0, tp, 1) ** 2,
                       0)
        x = cc * LN2 / np.where(tc > 0, tc, 1)
        e_c = np.where(live_c & (tc > 0), tc * np.expm1(x) / gain, 0)
    finally:
        np.seterr(**err)
    t4 = np.stack([tp[..., 0], tc[..., 0], tp[..., 1], tc[..., 1]], -1)
    e4 = np.stack([e_p[..., 0], e_c[..., 0], e_p[..., 1], e_c[..., 1]], -1)
    return {"kept_fraction": frac, "phase_times": t4, "phase_energy": e4,
            "e_total": e4.sum(-1) + co["e_isl"], "feasible": feasible}
