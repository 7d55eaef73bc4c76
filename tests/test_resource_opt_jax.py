"""Solver-backend parity: the jit+vmap JAX problem-(13) engine vs the
NumPy batch path vs the scalar reference oracle, element-wise over a
randomized instance grid (feasibility, E_total, phase times, KKT
residuals), plus the device-resident revolution sweep and its
zero-host-transfer bridge into the fused pass executor."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import resource_opt as ro
from repro.core.energy import PassBudget, SplitCosts, direct_download_costs
from repro.core.mission import RevolutionPlanner, sweep_revolutions
from repro.core import resource_opt_jax as roj
from repro.core.orbits import OrbitalPlane

BUDGET = PassBudget()
W_MAX = BUDGET.sat_device.peak_flops * BUDGET.plane.pass_duration_s \
    / BUDGET.n_items


def _instance_grid():
    """Feasible, comm/proc-heavy, phase-absent, infeasible, and
    Lambert-W branch-point (series-guard) instances + a random cloud."""
    cases = [
        SplitCosts(1e9, 1e9, 1e4, 1e6),              # easy feasible
        SplitCosts(3e11, 1e11, 1e6, 1e8),            # paper-scale
        SplitCosts(0.0, 1e9, 1e5, 0.0),              # no sat segment
        SplitCosts(1e9, 1e9, 0.0, 1e6),              # no comm phases
        SplitCosts(0.0, 1e6, 0.0, 0.0),              # degenerate: gs only
        SplitCosts(W_MAX * 0.9, 1e6, 1e3, 0.0),      # near the deadline
        SplitCosts(W_MAX * 1000, 1e6, 1e3, 0.0),     # infeasible budget
        SplitCosts(1e9, 1e9, 5e9, 1e6),              # comm-infeasible
        direct_download_costs(1.605e6, 3.4e9),       # fig-3 baseline
        # tiny payloads: λ·g̃ underflows the W₀ branch point, exercising
        # the series guard x ≈ √(2·λ·g̃)
        SplitCosts(0.0, 0.0, 1.0, 0.0),
        SplitCosts(0.0, 0.0, 1e-3, 0.0),
        SplitCosts(1e9, 1e9, 1.0, 1e6),
    ]
    rng = np.random.default_rng(11)
    for _ in range(28):
        cases.append(SplitCosts(
            w1_flops=float(rng.uniform(0, 5e11)),
            w2_flops=float(rng.uniform(1e6, 5e11)),
            dtx_bits=float(10.0 ** rng.uniform(-3, 7)),
            d_isl_bits=float(rng.uniform(0, 1e9))))
    return cases


def test_solve_batch_jax_matches_reference_elementwise():
    costs = _instance_grid()
    rep = roj.solve_batch_jax(BUDGET, costs)
    assert rep.n == len(costs)
    for i, c in enumerate(costs):
        ref = ro.solve_reference(BUDGET, c)
        assert bool(rep.feasible[i]) == ref.allocation.feasible, c
        assert rep.e_total[i] == pytest.approx(ref.allocation.e_total,
                                               rel=1e-6, abs=1e-12), c
        assert rep.t_total[i] == pytest.approx(ref.allocation.t_total,
                                               rel=1e-6, abs=1e-12), c
        if ref.allocation.feasible:
            assert rep.kkt_residual[i] < 1e-6


def test_solve_batch_jax_matches_numpy_phase_times():
    costs = _instance_grid()
    rj = roj.solve_batch_jax(BUDGET, costs)
    rn = ro.solve_batch(BUDGET, costs, backend="numpy")
    np.testing.assert_allclose(rj.phase_times, rn.phase_times,
                               rtol=1e-6, atol=1e-12)
    np.testing.assert_allclose(rj.phase_energy, rn.phase_energy,
                               rtol=1e-6, atol=1e-12)
    np.testing.assert_array_equal(rj.feasible, rn.feasible)
    # finite duals agree (loosely at clamp-dominated optima, where λ is
    # only identified to bisection-path noise); infeasible rows are inf
    # on both sides
    fin = np.isfinite(rn.lam) & (rn.lam > 0)
    np.testing.assert_allclose(rj.lam[fin], rn.lam[fin], rtol=1e-4)
    assert np.array_equal(np.isinf(rj.lam), np.isinf(rn.lam))


def test_backend_selector_dispatch_and_validation():
    costs = _instance_grid()[:4]
    rj = ro.solve_batch(BUDGET, costs, backend="jax")
    rn = ro.solve_batch(BUDGET, costs, backend="numpy")
    np.testing.assert_allclose(rj.e_total, rn.e_total, rtol=1e-8)
    with pytest.raises(ValueError, match="backend"):
        ro.solve_batch(BUDGET, costs, backend="fortran")
    # "auto" resolves without error at any batch size
    assert ro._resolve_backend("auto", 1) in ("numpy", "jax")
    assert ro._resolve_backend(None, 10**6) in ("numpy", "jax")


def test_shedding_batch_backend_parity():
    grid = [
        SplitCosts(1e9, 1e9, 1e4, 1e6),              # no shed
        SplitCosts(W_MAX * 2, 1e6, 1e3, 0.0),        # sheds ~0.5
        SplitCosts(W_MAX * 1000, 1e6, 1e3, 0.0),     # floor
        SplitCosts(1e9, 1e9, 5e9, 1e6),              # comm-driven shed
        SplitCosts(0.0, 1e6, 0.0, 0.0),              # gs-proc only
    ]
    sj = ro.solve_with_shedding_batch(BUDGET, grid, backend="jax")
    sn = ro.solve_with_shedding_batch(BUDGET, grid, backend="numpy")
    np.testing.assert_allclose(sj.kept_fraction, sn.kept_fraction,
                               atol=2e-4)
    np.testing.assert_allclose(sj.report.e_total, sn.report.e_total,
                               rtol=1e-8)
    # fully-device shedding (closed-form fraction) matches the host
    # bisection within its tolerance
    with roj.x64_scope():
        coeffs = roj._coeffs_from_instances(
            *ro._broadcast_instances(BUDGET, grid))
        _, frac = roj.shed_and_solve_coeffs(coeffs)
        frac = np.asarray(frac)[:len(grid)]
    np.testing.assert_allclose(frac, sn.kept_fraction, atol=2e-4)


def test_best_split_batch_backend_parity():
    from repro.core.splitting import resnet18_plan
    cands = resnet18_plan().enumerate_cuts()
    cj, repj = ro.best_split_batch(BUDGET, cands, backend="jax")
    cn, repn = ro.best_split_batch(BUDGET, cands, backend="numpy")
    assert cj.name == cn.name
    assert repj.allocation.e_total == pytest.approx(
        repn.allocation.e_total, rel=1e-8)


def test_planner_jax_backend_matches_numpy():
    ring = list(range(8))
    budgets = [PassBudget(n_items=100.0 + 150.0 * s) for s in ring]
    costs = [SplitCosts(1e9 * (s + 1), 1e9, 1e4 * (s + 1), 1e6)
             for s in ring]
    ej = RevolutionPlanner(backend="jax").plan_revolution(
        ring, budgets, costs)
    en = RevolutionPlanner(backend="numpy").plan_revolution(
        ring, budgets, costs)
    for s in ring:
        assert ej[s].allocation.e_total == pytest.approx(
            en[s].allocation.e_total, rel=1e-8)
        assert ej[s].shed.kept_fraction == pytest.approx(
            en[s].shed.kept_fraction, abs=2e-4)


# --------------------------------------------------------------------------
# On-device revolution sweeps
# --------------------------------------------------------------------------

def test_sweep_revolutions_matches_scalar_shedding_oracle():
    ring_sizes = [4, 25, 1000]
    cuts = [SplitCosts(1e9, 1e9, 1e4, 1e6, name="light"),
            SplitCosts(3e11, 1e11, 1e6, 1e8, name="paper"),
            SplitCosts(W_MAX * 3, 1e6, 1e3, 0.0, name="shed")]
    n_items = [100.0, 400.0]
    sweep = sweep_revolutions(ring_sizes, cuts, n_items)
    assert sweep.shape == (3, 3, 2)
    host = sweep.to_host()
    for i, N in enumerate(ring_sizes):
        plane = OrbitalPlane(n_sats=N)
        for j, c in enumerate(cuts):
            for b, n in enumerate(n_items):
                shed = ro.solve_with_shedding(
                    PassBudget(plane=plane, n_items=n), c)
                ref = shed.report.allocation
                assert bool(host["feasible"][i, j, b]) == ref.feasible
                assert host["kept_fraction"][i, j, b] == pytest.approx(
                    shed.kept_fraction, abs=2e-4)
                # shed cells inherit the fraction tolerance cubed through
                # the processing energy; exact cells are tight
                rel = 1e-2 if shed.kept_fraction < 1.0 else 1e-6
                assert host["e_pass"][i, j, b] == pytest.approx(
                    ref.e_total, rel=rel)
                assert host["t_pass"][i, j, b] == pytest.approx(
                    ref.t_total, rel=1e-6)
    # revolution energy scales with the ring population
    np.testing.assert_allclose(
        host["e_revolution"],
        host["e_pass"] * np.asarray(ring_sizes)[:, None, None], rtol=1e-12)
    # best_cut picks the min-energy feasible cut per (ring, budget) cell
    e = np.where(host["feasible"], host["e_pass"], np.inf)
    np.testing.assert_array_equal(host["best_cut"], np.argmin(e, axis=1))


def test_sweep_best_cut_sentinel_when_nothing_feasible():
    """A cell where even floor-shedding leaves every cut infeasible must
    report best_cut = -1, not a silent argmin-over-inf zero."""
    hopeless = SplitCosts(W_MAX * 1e6, 1e6, 1e3, 0.0, name="hopeless")
    sweep = sweep_revolutions([25], [hopeless], [400.0])
    host = sweep.to_host()
    assert not host["feasible"].any()
    assert (host["best_cut"] == -1).all()


def test_sweep_revolutions_measured_dtx_override():
    cuts = [SplitCosts(1e9, 1e9, 1e4, 1e6, name="a"),
            SplitCosts(1e9, 1e9, 1e4, 1e6, name="b")]
    base = sweep_revolutions([25], cuts, [400.0])
    bigger = sweep_revolutions([25], cuts, [400.0],
                               dtx_bits=[1e4, 5e6])   # measured payloads
    h0, h1 = base.to_host(), bigger.to_host()
    np.testing.assert_allclose(h1["e_pass"][0, 0], h0["e_pass"][0, 0],
                               rtol=1e-9)              # unchanged cut
    assert h1["e_pass"][0, 1, 0] > h0["e_pass"][0, 1, 0]  # heavier boundary


def test_sweep_steps_feed_sl_pass_without_host_sync():
    """RevolutionSweep.steps_for -> make_sl_pass(..., n_valid=...): the
    planned step count drives the fused pass as a device scalar, and
    exactly n_valid steps train (the rest are NaN-masked no-ops)."""
    from repro.core.sl_step import autoencoder_adapter, make_sl_pass
    from repro.core.train_state import SLTrainState
    from repro.data.synthetic import ImageryShards
    from repro.train.optimizer import sgd

    ad = autoencoder_adapter(cut=5, img=32)
    batch_size = 4
    sweep = sweep_revolutions([25], [ad.costs()], [3 * batch_size])
    n_valid = sweep.steps_for(batch_size)[0, 0, 0]     # device int32 scalar
    assert isinstance(n_valid, jax.Array)
    assert n_valid.dtype == jnp.int32

    shards = ImageryShards(img=32, batch=batch_size)
    batches = [jax.tree.map(jnp.asarray, shards.batch_at(0, i))
               for i in range(5)]                      # more than allocated
    state = SLTrainState.create(*ad.init(jax.random.key(0)), sgd(lr=1e-2))
    res = make_sl_pass(ad, optimizer=sgd(lr=1e-2))(state, batches,
                                                   n_valid=n_valid)
    losses = np.asarray(res.losses)
    assert losses.shape == (5,)
    assert np.isfinite(losses[:3]).all()               # planned steps ran
    assert np.isnan(losses[3:]).all()                  # beyond-plan masked
    # masked steps left the weights untouched: replaying only the first
    # 3 batches from the same init lands on identical params
    state2 = SLTrainState.create(*ad.init(jax.random.key(0)), sgd(lr=1e-2))
    res3 = make_sl_pass(ad, optimizer=sgd(lr=1e-2))(state2, batches[:3])
    for got, ref in zip(jax.tree.leaves(res.state.params_a),
                        jax.tree.leaves(res3.state.params_a)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-6, atol=1e-7)


def test_ring_boundary_bits_array_feed():
    from repro.core.sl_step import (autoencoder_adapter, boundary_bits,
                                    ring_boundary_bits)
    from repro.data.synthetic import ImageryShards

    # cut=4 keeps the boundary spatially dependent on the input size
    # (at cut=5 the AE latent collapses to 1x1 for both image sizes)
    ad = autoencoder_adapter(cut=4, img=32)
    b32 = jax.tree.map(jnp.asarray, ImageryShards(img=32, batch=4)
                       .batch_at(0, 0))
    b16 = jax.tree.map(jnp.asarray, ImageryShards(img=16, batch=4)
                       .batch_at(1, 0))
    bits = ring_boundary_bits(ad, [b32, b16, b32])
    assert bits.shape == (3,)
    assert bits[0] == boundary_bits(ad, b32)
    assert bits[1] == boundary_bits(ad, b16)
    assert bits[0] == bits[2] != bits[1]


def test_constellation_threads_per_sat_boundary_measurements():
    """Ring members with different batch shapes contribute their OWN
    measured boundary payloads to the revolution plan — one batched
    solve covers the heterogeneous ring, no replan per observation."""
    from repro import configs
    from repro.core.constellation import (ConstellationConfig,
                                          ConstellationSim)
    from repro.core.sl_step import lm_adapter
    from repro.data.synthetic import TokenShards

    cfg = configs.get_smoke("smollm_360m")
    ad = lm_adapter(cfg, cut_units=1, seq_len=16)
    # sat 1 serves shorter sequences => its boundary payload per item
    # (S · d_model · 32 bits) is half everyone else's
    long_sh = TokenShards(vocab=cfg.vocab, seq_len=16, batch=2)
    short_sh = TokenShards(vocab=cfg.vocab, seq_len=8, batch=2)

    def data(s, i):
        shards = short_sh if s == 1 else long_sh
        return jax.tree.map(jnp.asarray, shards.batch_at(s, i))

    plane = OrbitalPlane(n_sats=3)
    sim = ConstellationSim(
        ad, PassBudget(plane=plane, n_items=4.0), data,
        ConstellationConfig(n_passes=6, batch_size=2))
    recs = sim.run()
    assert all(r.action in ("trained", "shed") for r in recs)
    # per-sat measurement, not a ring-wide broadcast of sat 0's payload
    assert sim._sat_costs[1].dtx_bits == pytest.approx(
        sim._sat_costs[0].dtx_bits / 2.0)
    assert sim._sat_costs[0].dtx_bits == sim._sat_costs[2].dtx_bits
    # stable heterogeneous ring: ONE batched solve for both revolutions
    assert sim.planner.solve_calls == 1
    assert sim.planner.invalidations == 0
    # the cheaper boundary shows up in sat 1's energy accounting
    assert recs[1].e_comm_j < recs[0].e_comm_j
