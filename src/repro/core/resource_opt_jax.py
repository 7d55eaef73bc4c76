"""Device-resident problem-(13) engine: the jit+vmap JAX solver backend.

This is the accelerator twin of the NumPy :func:`~repro.core.
resource_opt.solve_batch`: the same dual-waterfilling algorithm — the
Lambert-W closed form for the comm phases, the cube-root closed form for
the processing phases, and one geometric bisection on the dual λ — but
written per-instance in pure JAX, ``vmap``-ped over the batch axis and
``jit``-compiled, so constellation-scale sweeps (1000-sat rings × cut
points × budgets) run entirely on device with zero host round-trips
between planning and pass execution.

Three mutually-checking implementations now exist:

* :func:`resource_opt.solve_reference` — the scalar pure-Python oracle;
* :func:`resource_opt.solve_batch` — NumPy lockstep arrays (the CPU
  fallback and the parity oracle for this module);
* :func:`solve_batch_jax` — this backend, selected through
  ``resource_opt.solve_batch(..., backend="jax"|"auto")``.

Numerical notes
---------------
The dual λ spans hundreds of decades (λ_hi/λ_lo brackets are analytic,
from the marginals at t_min and at the whole budget), so the solver runs
in **float64** regardless of the process-wide JAX default: every entry
point traces and executes under ``jax.enable_x64(True)``, which scopes
double precision to this module without flipping the global flag
(the SL training stack stays float32).  The Lambert-W branch point gets
the same series guard as the NumPy path: for λ·g̃ below ~1e-6 the
argument (λ·g̃ − 1)/e rounds into the branch point where W₀ loses all
precision, and the series x ≈ √(2·λ·g̃) of ``e^x (x−1) + 1 = λ·g̃`` is
exact; two Newton polish steps on the cancellation-free residual restore
full double precision everywhere else.

On a TPU, float64 is emulated with pairs of float32 values: it gains
precision but keeps float32's exponent range (about 1e±38), and the
compiler rounds any constant beyond it to 0 or inf.  So every guard
constant here (``_TINY``, ``_HUGE``, the ``_X_MAX`` exponent cap) lies
inside that range, the bisection midpoint is √λ_lo·√λ_hi (the product
λ_lo·λ_hi can leave the range), and the processing constants travel as
cube roots so ``k = (k_cbrt·n·w)³`` never forms the out-of-range
``(n·w)³``.  The NumPy oracle keeps its float64-range guards.

The phase structure is static (the canonical [sat_proc, downlink,
gs_proc, uplink] layout with liveness masks), so one compiled executable
serves every instance mix; batch sizes are bucketed to the next power of
two with inert padding rows to keep recompiles O(log B).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# batch padding shares the repo-wide bucketing schedule with
# make_sl_pass(bucket=True): O(log B) compilations, <=25% inert pad rows
from repro.utils.bucketing import bucket_size as _bucket_batch

_EPS = 1e-12
_LN2 = math.log(2.0)
# guards inside float32's exponent range, which the TPU's emulated
# float64 keeps (module docstring); exp(_X_MAX) is still finite there
_TINY = 1e-30
_HUGE = 1e30
_X_MAX = 80.0


def on_accelerator() -> bool:
    """True when the default JAX backend is not the host CPU."""
    return jax.default_backend() != "cpu"


# --------------------------------------------------------------------------
# Elementwise building blocks (float64 under x64_scope).
# --------------------------------------------------------------------------

def _lambert_w0(z):
    """Principal-branch Lambert W for z >= -1/e, elementwise.

    Branch-point series init below zero, log init above, then Halley
    iterations (the same scheme as the NumPy fallback in resource_opt).
    """
    w = jnp.where(z < 0.0,
                  -1.0 + jnp.sqrt(jnp.maximum(2.0 * (1.0 + math.e * z), 0.0)),
                  jnp.log1p(jnp.maximum(z, 0.0)))
    big = z > math.e
    lz = jnp.log(jnp.where(big, z, math.e))
    w = jnp.where(big, lz - jnp.log(lz), w)

    def halley(_, w):
        w = jnp.maximum(w, -1.0 + 1e-12)        # keep 2w+2 away from zero
        ew = jnp.exp(jnp.minimum(w, _X_MAX))
        f = w * ew - z
        denom = ew * (w + 1.0) - (w + 2.0) * f / (2.0 * w + 2.0)
        return w - f / jnp.where(denom != 0.0, denom, 1.0)

    # 5 cubic steps from these inits reach ~1e-10 everywhere on z >= -1/e;
    # the Newton polish on the solver's own residual finishes the job, so
    # more iterations here only burn device time on the hot path.
    w = lax.fori_loop(0, 5, halley, w)
    return jnp.maximum(w, -1.0)


def _comm_neg_deriv_vec(c, gain, t):
    """−E'(t) of a comm phase, cancellation-free (see resource_opt)."""
    x = jnp.where(t > 0.0, c * _LN2 / jnp.maximum(t, _TINY), jnp.inf)
    xs = jnp.minimum(x, _X_MAX)
    e = jnp.expm1(xs)
    nd = (e * xs - (e - xs)) / gain
    return jnp.where(x > _X_MAX, jnp.inf, nd)


def _comm_t_of_lambda_vec(c, gain, lam, t_min, t_hi):
    """Closed-form t(λ) for the comm phases via Lambert W.

    −E'(t) = λ  ⟺  e^x (x−1) + 1 = λ·g̃  with x = c·ln2/t, so
    x = 1 + W₀((λ·g̃ − 1)/e); series guard x ≈ √(2·λ·g̃) at the branch
    point, two Newton polish steps on the stable residual.
    """
    lg = lam * gain
    z = jnp.maximum((lg - 1.0) / math.e, -1.0 / math.e)
    x = 1.0 + _lambert_w0(z)
    small = lg < 1e-6
    x = jnp.where(small, jnp.sqrt(2.0 * jnp.maximum(lg, 0.0)), x)
    x = jnp.maximum(x, _TINY)
    for _ in range(2):
        xs = jnp.minimum(x, _X_MAX)
        em = jnp.expm1(xs)
        f = em * xs - (em - xs) - lg
        fp = (em + 1.0) * xs
        x = jnp.maximum(x - f / jnp.maximum(fp, _TINY), _TINY)
    t = c * _LN2 / x
    return jnp.clip(t, t_min, t_hi)


def _proc_t_of_lambda_vec(k, lam, t_min, t_hi):
    """Closed-form t(λ) = (2k/λ)^{1/3} for the processing phases."""
    t = jnp.cbrt(2.0 * k / jnp.maximum(lam, _TINY))
    return jnp.clip(t, t_min, t_hi)


# --------------------------------------------------------------------------
# The per-instance solver (vmapped over the batch axis).
# --------------------------------------------------------------------------

class CoeffArrays(NamedTuple):
    """Problem-(13) coefficients as arrays — the device-level interface.

    Shapes: ``k``/``tmin_p`` are (..., 2) for [sat_proc, gs_proc];
    ``cc``/``tmin_c`` are (..., 2) for [downlink, uplink] (bits/Hz);
    the rest are (...,).  Any leading batch shape works — it is
    flattened for the vmapped solve and restored on the outputs.  A
    phase with ``k``/``cc`` equal to 0 is absent.
    """

    k: "jnp.ndarray"
    tmin_p: "jnp.ndarray"
    cc: "jnp.ndarray"
    tmin_c: "jnp.ndarray"
    gain: "jnp.ndarray"
    t_budget: "jnp.ndarray"
    e_isl: "jnp.ndarray"
    t_fixed: "jnp.ndarray"

    def scaled_items(self, frac):
        """Coefficients at a per-instance kept item fraction ``frac``.

        Every t_min and the comm payload scale linearly with n_items,
        the processing constant k cubically; the time budget and the
        fixed ISL terms do not depend on it.
        """
        f = jnp.asarray(frac)
        f1 = f[..., None]
        return self._replace(k=self.k * f1**3, tmin_p=self.tmin_p * f1,
                             cc=self.cc * f1, tmin_c=self.tmin_c * f1)


class ArraySolveReport(NamedTuple):
    """Device-array solution of problem (13); see BatchSolveReport."""

    phase_times: "jnp.ndarray"     # (..., 4) seconds
    phase_energy: "jnp.ndarray"    # (..., 4) joules
    lam: "jnp.ndarray"             # (...,)  dual (inf if infeasible)
    kkt_residual: "jnp.ndarray"    # (...,)
    feasible: "jnp.ndarray"        # (...,)  bool
    e_isl: "jnp.ndarray"           # (...,)  joules
    t_fixed: "jnp.ndarray"         # (...,)  seconds

    @property
    def e_total(self):
        """eq. (11) per instance, including the constant E_ISL."""
        return self.phase_energy.sum(axis=-1) + self.e_isl

    @property
    def t_total(self):
        """eq. (12) per instance, including the fixed overhead."""
        return self.phase_times.sum(axis=-1) + self.t_fixed


@jax.named_scope("planner.solve")
def _solve_one(k, tmin_p, cc, tmin_c, gain, t_budget, *, tol, max_iters):
    """Solve one problem-(13) instance; shapes (2,)/(); pure JAX."""
    live_p = k > 0.0
    live_c = cc > 0.0
    tmin_p = jnp.where(live_p, tmin_p, 0.0)
    tmin_c = jnp.where(live_c, tmin_c, 0.0)

    t_min_sum = tmin_p.sum() + tmin_c.sum()
    any_live = live_p.any() | live_c.any()
    infeasible = any_live & ((t_budget <= 0.0) | (t_min_sum > t_budget))
    active = any_live & ~infeasible
    t_hi = jnp.maximum(t_budget, 0.0)

    # ---- analytic λ bracket: total_time(λ) is decreasing in λ ----------
    nd_p_lo = 2.0 * k / jnp.maximum(tmin_p, _TINY) ** 3
    nd_p_hi = 2.0 * k / jnp.maximum(t_hi, _TINY) ** 3
    nd_c_lo = _comm_neg_deriv_vec(cc, gain, jnp.maximum(tmin_c, _TINY))
    nd_c_hi = _comm_neg_deriv_vec(cc, gain, jnp.maximum(t_hi, _TINY))
    nd_lo = jnp.concatenate([jnp.where(live_p, nd_p_lo, -jnp.inf),
                             jnp.where(live_c, nd_c_lo, -jnp.inf)])
    nd_hi = jnp.concatenate([jnp.where(live_p, nd_p_hi, jnp.inf),
                             jnp.where(live_c, nd_c_hi, jnp.inf)])
    lam_hi0 = jnp.maximum(jnp.nan_to_num(nd_lo.max(), neginf=1.0,
                                         posinf=_HUGE), _TINY)
    lam_lo0 = jnp.clip(jnp.nan_to_num(nd_hi.min(), posinf=1.0),
                       _TINY, lam_hi0)

    def times_at(lam):
        tp = jnp.where(live_p,
                       _proc_t_of_lambda_vec(k, lam, tmin_p, t_hi), 0.0)
        tc = jnp.where(live_c,
                       _comm_t_of_lambda_vec(cc, gain, lam, tmin_c, t_hi),
                       0.0)
        return tp, tc

    # ---- geometric bisection on λ (lax.while_loop; lockstep via vmap) --
    def cond(carry):
        it, lam_lo, lam_hi = carry
        return (it < max_iters) & active & (lam_hi > lam_lo * (1.0 + tol))

    def body(carry):
        it, lam_lo, lam_hi = carry
        # geometric mid (λ spans decades); the product could leave the
        # float32 exponent range a TPU's float64 keeps
        lam = jnp.sqrt(lam_lo) * jnp.sqrt(lam_hi)
        tp, tc = times_at(lam)
        over = (tp.sum() + tc.sum()) > t_budget
        return (it + 1, jnp.where(over, lam, lam_lo),
                jnp.where(over, lam_hi, lam))

    _, lam_lo, lam_hi = lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), lam_lo0, lam_hi0))
    lam = jnp.sqrt(lam_lo) * jnp.sqrt(lam_hi)
    tp, tc = times_at(lam)

    # ---- slack redistribution (t_min-clamped phases leave headroom) ----
    slack = t_budget - (tp.sum() + tc.sum())
    int_p = live_p & (tp > tmin_p * (1.0 + 1e-9))
    int_c = live_c & (tc > tmin_c * (1.0 + 1e-9))
    n_int = int_p.sum() + int_c.sum()
    bump = jnp.where(active & (slack > 1e-9 * t_budget) & (n_int > 0),
                     slack / jnp.maximum(n_int, 1), 0.0)
    tp = jnp.where(int_p, tp + bump, tp)
    tc = jnp.where(int_c, tc + bump, tc)

    # ---- infeasible / no-phase instances -------------------------------
    tp = jnp.where(infeasible, tmin_p, tp)
    tc = jnp.where(infeasible, tmin_c, tc)
    tp = jnp.where(any_live, tp, 0.0)
    tc = jnp.where(any_live, tc, 0.0)

    # ---- energies at the final times -----------------------------------
    e_p = jnp.where(live_p & (tp > 0.0),
                    k / jnp.maximum(tp, _TINY) ** 2, 0.0)
    xc = cc * _LN2 / jnp.maximum(tc, _TINY)
    e_c = jnp.where(live_c & (tc > 0.0),
                    tc * jnp.expm1(jnp.minimum(xc, _X_MAX)) / gain, 0.0)
    e_c = jnp.where(live_c & (xc > _X_MAX), jnp.inf, e_c)

    # ---- KKT residual: spread of marginals among interior phases -------
    nd_p = 2.0 * k / jnp.maximum(tp, _TINY) ** 3
    nd_c = _comm_neg_deriv_vec(cc, gain, jnp.maximum(tc, _TINY))
    io_p = live_p & (tp > tmin_p * (1.0 + 1e-6)) & (tp < t_hi * (1.0 - 1e-6))
    io_c = live_c & (tc > tmin_c * (1.0 + 1e-6)) & (tc < t_hi * (1.0 - 1e-6))
    marg = jnp.concatenate([jnp.where(io_p, nd_p, jnp.nan),
                            jnp.where(io_c, nd_c, jnp.nan)])
    n_io = io_p.sum() + io_c.sum()
    filled = jnp.where(n_io >= 2, marg, 1.0)
    mmax = jnp.nanmax(filled)
    mmin = jnp.nanmin(filled)
    kkt = jnp.where(n_io >= 2, (mmax - mmin) / jnp.maximum(mmax, _EPS), 0.0)
    kkt = jnp.where(infeasible, jnp.inf, kkt)

    lam_out = jnp.where(infeasible, jnp.inf, jnp.where(any_live, lam, 0.0))
    phase_times = jnp.stack([tp[0], tc[0], tp[1], tc[1]])
    phase_energy = jnp.stack([e_p[0], e_c[0], e_p[1], e_c[1]])
    return phase_times, phase_energy, lam_out, kkt, ~infeasible


@functools.lru_cache(maxsize=8)
def _solver_fn(tol: float, max_iters: int):
    """jit(vmap(solve_one)) specialized to a (tol, max_iters) pair."""
    one = functools.partial(_solve_one, tol=tol, max_iters=max_iters)
    return jax.jit(jax.vmap(one))


def solve_coeffs(coeffs: CoeffArrays, tol: float = 1e-10,
                 max_iters: int = 80) -> ArraySolveReport:
    """Solve problem (13) for an array of instances, fully on device.

    ``coeffs`` may carry any leading batch shape; the call is traceable,
    so it composes inside larger jitted programs (the revolution sweep
    jits grid construction + shedding + this solve as one executable).
    NOTE: run under :func:`x64_scope` — the dual
    bisection needs float64 range.
    """
    lead = coeffs.gain.shape
    flat = CoeffArrays(*[jnp.reshape(a, (-1,) + a.shape[len(lead):])
                         for a in coeffs])
    pt, pe, lam, kkt, feas = _solver_fn(tol, max_iters)(
        flat.k, flat.tmin_p, flat.cc, flat.tmin_c, flat.gain, flat.t_budget)
    return ArraySolveReport(
        phase_times=jnp.reshape(pt, lead + (4,)),
        phase_energy=jnp.reshape(pe, lead + (4,)),
        lam=jnp.reshape(lam, lead), kkt_residual=jnp.reshape(kkt, lead),
        feasible=jnp.reshape(feas, lead),
        e_isl=coeffs.e_isl, t_fixed=coeffs.t_fixed)


@jax.named_scope("planner.shed")
def shed_fractions(coeffs: CoeffArrays,
                   min_fraction: float = 0.05) -> "jnp.ndarray":
    """Per-instance kept fraction restoring feasibility, closed form.

    Every phase's t_min scales linearly with n_items while the time
    budget does not, so the largest feasible fraction is simply
    T_budget / Σ t_min (the NumPy path bisects to the same value within
    its tolerance).  Clamped to [min_fraction, 1]; instances with no
    budget at all sit at the floor, instances with no live phase keep 1.
    """
    tmin_sum = (jnp.where(coeffs.k > 0.0, coeffs.tmin_p, 0.0).sum(axis=-1)
                + jnp.where(coeffs.cc > 0.0, coeffs.tmin_c, 0.0).sum(axis=-1))
    no_phase = tmin_sum == 0.0
    feas_full = no_phase | ((coeffs.t_budget > 0.0)
                            & (tmin_sum <= coeffs.t_budget))
    # one-ulp shave keeps the scaled Σ t_min on the feasible side
    fit = (coeffs.t_budget / jnp.maximum(tmin_sum, _TINY)) * (1.0 - 1e-12)
    frac = jnp.where(feas_full, 1.0,
                     jnp.clip(fit, min_fraction, 1.0))
    return jnp.where(no_phase | (coeffs.t_budget > 0.0), frac, min_fraction)


def shed_and_solve_coeffs(coeffs: CoeffArrays, min_fraction: float = 0.05,
                          tol: float = 1e-10, max_iters: int = 80
                          ) -> Tuple[ArraySolveReport, "jnp.ndarray"]:
    """Vectorized shedding + solve at the kept item counts, on device."""
    frac = shed_fractions(coeffs, min_fraction)
    return solve_coeffs(coeffs.scaled_items(frac), tol, max_iters), frac


def x64_scope():
    """The float64 scope every entry point of this module runs under."""
    return jax.enable_x64(True)


# --------------------------------------------------------------------------
# Drop-in batch API over (PassBudget, SplitCosts) instances.
# --------------------------------------------------------------------------



def _coeffs_from_instances(blist, clist) -> CoeffArrays:
    """Host gather of per-instance coefficients into padded device arrays.

    Pads the batch to a bucketed size with inert no-phase rows
    (k = cc = 0) so distinct batch sizes share O(log B) compilations.
    """
    from repro.core import resource_opt

    arrs = resource_opt._gather_coeff_arrays(blist, clist)
    B = len(blist)
    Bp = _bucket_batch(B)
    if Bp > B:
        pad = Bp - B

        def _pad(a, fill=0.0):
            width = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
            return np.pad(a, width, constant_values=fill)

        arrs = {k: _pad(a, 1.0 if k in ("gain", "t_budget") else 0.0)
                for k, a in arrs.items()}
    return CoeffArrays(
        k=jnp.asarray(arrs["k"]), tmin_p=jnp.asarray(arrs["tmin_p"]),
        cc=jnp.asarray(arrs["cc"]), tmin_c=jnp.asarray(arrs["tmin_c"]),
        gain=jnp.asarray(arrs["gain"]),
        t_budget=jnp.asarray(arrs["t_budget"]),
        e_isl=jnp.asarray(arrs["e_isl"]),
        t_fixed=jnp.asarray(arrs["t_fixed"]))


def solve_batch_jax(budgets, costs, tol: float = 1e-10,
                    max_iters: int = 80):
    """JAX twin of :func:`resource_opt.solve_batch` — same report type.

    Accepts the same (budget | sequence, costs | sequence) broadcasting
    and returns a host :class:`~repro.core.resource_opt.BatchSolveReport`
    (NumPy arrays), so every existing consumer — shedding, best-split,
    the revolution planner — runs on device by flipping ``backend``.
    For a zero-copy device pipeline use :func:`solve_coeffs` directly.
    """
    from repro.core import resource_opt

    blist, clist = resource_opt._broadcast_instances(budgets, costs)
    B = len(blist)
    with x64_scope():
        coeffs = _coeffs_from_instances(blist, clist)
        rep = solve_coeffs(coeffs, tol=tol, max_iters=max_iters)
        out = jax.tree.map(np.asarray, rep)
    return resource_opt.BatchSolveReport(
        phase_times=out.phase_times[:B], phase_energy=out.phase_energy[:B],
        lam=out.lam[:B], kkt_residual=out.kkt_residual[:B],
        feasible=out.feasible[:B], e_isl=out.e_isl[:B],
        t_fixed=out.t_fixed[:B], budgets=tuple(blist), costs=tuple(clist))


# --------------------------------------------------------------------------
# On-device coefficient grids (ring size × cut point × item budget).
# --------------------------------------------------------------------------

class GridScalars(NamedTuple):
    """Scenario constants of a revolution sweep, as dynamic scalars.

    Passing these as traced scalars (not Python closure constants) keeps
    ONE compiled sweep executable across scenario variations — only the
    grid *shape* triggers a recompile.
    """

    pass_duration_s: "jnp.ndarray"
    t_prop_s: "jnp.ndarray"
    gain: "jnp.ndarray"                 # g̃ at the mean slant range
    r_max_bps: "jnp.ndarray"            # link rate at P_max
    bandwidth_hz: "jnp.ndarray"
    isl_rate_bps: "jnp.ndarray"
    isl_tx_power_w: "jnp.ndarray"
    orbit_radius_m: "jnp.ndarray"       # R_earth + altitude
    sat_k_cbrt: "jnp.ndarray"           # P_p^(1/3) / (f_max · N_c·N_F)
    sat_t_const: "jnp.ndarray"          # 1 / (N_c·N_F·f_max)
    gs_k_cbrt: "jnp.ndarray"
    gs_t_const: "jnp.ndarray"


def grid_scalars(plane, link, isl, sat_device, gs_device) -> GridScalars:
    """Fold the scenario dataclasses into :class:`GridScalars`."""
    from repro.core.orbits import R_EARTH_M

    d = plane.mean_slant_range_m()
    with x64_scope():                     # float64 from the very first cast
        f64 = functools.partial(jnp.asarray, dtype=jnp.float64)

        def dev_consts(dev):
            nc = dev.n_cores * dev.flops_per_cycle
            return (f64(dev.power_max_w ** (1.0 / 3.0) / (dev.f_max_hz * nc)),
                    f64(1.0 / (nc * dev.f_max_hz)))

        sat_k, sat_t = dev_consts(sat_device)
        gs_k, gs_t = dev_consts(gs_device)
        return GridScalars(
            pass_duration_s=f64(plane.pass_duration_s),
            t_prop_s=f64(plane.mean_prop_delay_s),
            gain=f64(link.channel_gain(d)),
            r_max_bps=f64(link.rate_bps(link.max_tx_power_w, d)),
            bandwidth_hz=f64(link.bandwidth_hz),
            isl_rate_bps=f64(isl.rate_bps),
            isl_tx_power_w=f64(isl.tx_power_w),
            orbit_radius_m=f64(R_EARTH_M + plane.altitude_m),
            sat_k_cbrt=sat_k, sat_t_const=sat_t,
            gs_k_cbrt=gs_k, gs_t_const=gs_t)


def ring_grid_coeffs(sc: GridScalars, ring_sizes, w1, w2, dtx, disl,
                     n_items) -> CoeffArrays:
    """Build the (R, C, B) coefficient grid with pure array math.

    ``ring_sizes`` (R,) enters through the ISL hop distance (eq. 5);
    the cut arrays ``w1``/``w2``/``dtx``/``disl`` (C,) carry the split
    plan; ``n_items`` (B,) is the per-pass item budget axis.  Mirrors
    :func:`resource_opt._phase_coeffs` element for element — asserted by
    the sweep parity tests — but never leaves the device.
    """
    from repro.core.orbits import C_LIGHT

    N = jnp.asarray(ring_sizes, jnp.float64)[:, None, None]       # (R,1,1)
    w1 = jnp.asarray(w1, jnp.float64)[None, :, None]              # (1,C,1)
    w2 = jnp.asarray(w2, jnp.float64)[None, :, None]
    dtx = jnp.asarray(dtx, jnp.float64)[None, :, None]
    disl = jnp.asarray(disl, jnp.float64)[None, :, None]
    n = jnp.asarray(n_items, jnp.float64)[None, None, :]          # (1,1,B)

    isl_dist = 2.0 * sc.orbit_radius_m * jnp.sin(jnp.pi / N)
    t_fixed = (2.0 * sc.t_prop_s + disl / sc.isl_rate_bps
               + isl_dist / C_LIGHT)
    t_budget = sc.pass_duration_s - t_fixed
    e_isl = sc.isl_tx_power_w * disl / sc.isl_rate_bps

    k_sat = (sc.sat_k_cbrt * n * w1) ** 3
    k_gs = (sc.gs_k_cbrt * n * w2) ** 3
    tmin_sat = sc.sat_t_const * n * w1
    tmin_gs = sc.gs_t_const * n * w2
    bits = n * dtx
    c_comm = bits / sc.bandwidth_hz
    tmin_comm = jnp.where(bits > 0.0, bits / sc.r_max_bps, 0.0)

    shape = jnp.broadcast_shapes(N.shape, w1.shape, n.shape)
    bcast = functools.partial(jnp.broadcast_to, shape=shape)
    return CoeffArrays(
        k=jnp.stack([bcast(k_sat), bcast(k_gs)], axis=-1),
        tmin_p=jnp.stack([bcast(tmin_sat), bcast(tmin_gs)], axis=-1),
        cc=jnp.stack([bcast(c_comm), bcast(c_comm)], axis=-1),
        tmin_c=jnp.stack([bcast(tmin_comm), bcast(tmin_comm)], axis=-1),
        gain=jnp.broadcast_to(sc.gain, shape),
        t_budget=bcast(t_budget), e_isl=bcast(e_isl),
        t_fixed=bcast(t_fixed))


@jax.named_scope("planner.coeffs")
def ring_pass_coeffs(sc: GridScalars, n_sats, w1, w2, dtx, disl,
                     n_items, *, ring_n: Optional[int] = None
                     ) -> CoeffArrays:
    """One ring revolution's N problem-(13) instances as ``(N,)`` rows.

    The per-*satellite* sibling of :func:`ring_grid_coeffs`: the ring
    population (it enters through the ISL hop distance, eq. 5) is fixed
    and every coefficient input may be a scalar (broadcast ring-wide)
    or a ``(N,)`` array (per-satellite measured boundary payloads,
    heterogeneous item budgets).  Pure array math, so it traces inside
    the device constellation engine's jitted planning call.  Run under
    :func:`x64_scope`.

    ``n_sats`` may also be a shape tuple — e.g. ``(P, M)`` for a fleet
    of P orbital planes whose rings carry M slots each (joiner slots
    included) — in which case ``ring_n`` gives the orbital population
    entering the ISL hop distance (default: the last dimension).  The
    host planner always prices eq. (5) off the configured
    ``budget.plane.n_sats`` regardless of live membership, so elastic
    rings pass that as ``ring_n`` to stay oracle-exact.
    """
    from repro.core.orbits import C_LIGHT

    shape = ((int(n_sats),) if isinstance(n_sats, (int, np.integer))
             else tuple(int(s) for s in n_sats))
    ring_n = shape[-1] if ring_n is None else int(ring_n)
    f64 = functools.partial(jnp.asarray, dtype=jnp.float64)
    bcast = lambda a: jnp.broadcast_to(f64(a), shape)       # noqa: E731
    w1, w2, dtx, disl = bcast(w1), bcast(w2), bcast(dtx), bcast(disl)
    n = bcast(n_items)

    isl_dist = 2.0 * sc.orbit_radius_m * jnp.sin(jnp.pi / float(ring_n))
    t_fixed = (2.0 * sc.t_prop_s + disl / sc.isl_rate_bps
               + isl_dist / C_LIGHT)
    t_budget = sc.pass_duration_s - t_fixed
    e_isl = sc.isl_tx_power_w * disl / sc.isl_rate_bps

    k_sat = (sc.sat_k_cbrt * n * w1) ** 3
    k_gs = (sc.gs_k_cbrt * n * w2) ** 3
    tmin_sat = sc.sat_t_const * n * w1
    tmin_gs = sc.gs_t_const * n * w2
    bits = n * dtx
    c_comm = bits / sc.bandwidth_hz
    tmin_comm = jnp.where(bits > 0.0, bits / sc.r_max_bps, 0.0)

    return CoeffArrays(
        k=jnp.stack([k_sat, k_gs], axis=-1),
        tmin_p=jnp.stack([tmin_sat, tmin_gs], axis=-1),
        cc=jnp.stack([c_comm, c_comm], axis=-1),
        tmin_c=jnp.stack([tmin_comm, tmin_comm], axis=-1),
        gain=jnp.broadcast_to(sc.gain, shape),
        t_budget=t_budget, e_isl=e_isl, t_fixed=t_fixed)


@functools.lru_cache(maxsize=4)
def _sweep_fn(min_fraction: float, tol: float, max_iters: int):
    """One jitted executable: grid build + shedding + solve, zero host."""

    def sweep(sc, ring_sizes, w1, w2, dtx, disl, n_items):
        coeffs = ring_grid_coeffs(sc, ring_sizes, w1, w2, dtx, disl,
                                  n_items)
        rep, frac = shed_and_solve_coeffs(coeffs, min_fraction, tol,
                                          max_iters)
        return rep, frac

    return jax.jit(sweep)


def sweep_grid(sc: GridScalars, ring_sizes, w1, w2, dtx, disl, n_items,
               min_fraction: float = 0.05, tol: float = 1e-10,
               max_iters: int = 80):
    """Plan a whole (ring × cut × budget) grid in one jitted call."""
    with x64_scope():
        return _sweep_fn(min_fraction, tol, max_iters)(
            sc, jnp.asarray(ring_sizes, jnp.float64),
            jnp.asarray(w1, jnp.float64), jnp.asarray(w2, jnp.float64),
            jnp.asarray(dtx, jnp.float64), jnp.asarray(disl, jnp.float64),
            jnp.asarray(n_items, jnp.float64))
