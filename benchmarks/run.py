"""Benchmark entry point: one block per paper table/figure + the
beyond-paper rows + micro-benchmarks of the SL step, the batched pass
engine (before/after rows for the vectorized problem-(13) solver and the
scan-fused pass executor), the solver backends (NumPy lockstep vs the
jit+vmap JAX engine), the on-device revolution sweep, and each kernel's
jnp path.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick]

``--quick`` is the CI smoke mode (scripts/check.sh): small solver grids,
no 1000-sat sweep, paper tables skipped, results written to
``results/bench_quick.json`` only — fast enough to catch a regression in
the jitted solver without a full sweep.

Alongside the stdout tables a full run emits machine-readable JSON to
``results/BENCH_<rev>.json`` (``<rev>`` = current git short hash, "dev"
outside a checkout) so the perf trajectory is tracked across PRs, plus
``results/bench.json`` as a stable latest-run alias.
"""
from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import subprocess
import time
import traceback


def _timeit(fn, *args, n=3, warmup=1, **kw):
    for _ in range(warmup):
        out = fn(*args, **kw)
    t0 = time.time()
    for _ in range(n):
        out = fn(*args, **kw)
    return (time.time() - t0) / n * 1e6, out      # us/call


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, check=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "dev"
    except Exception:
        return "dev"


def run_header(quick: bool) -> dict:
    """The shared run header emitted into every BENCH JSON: everything
    needed to judge whether two trend rows are comparable (same jax,
    same device topology, same mode) across machines."""
    import platform

    import jax

    devices = jax.devices()
    try:
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh()
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    except Exception:                              # pragma: no cover
        mesh_shape = None
    return {
        "rev": _git_rev(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": len(devices),
        "device_kind": devices[0].device_kind if devices else None,
        "mesh_shape": mesh_shape,
        "python": platform.python_version(),
        "platform": platform.platform(),
        "quick": quick,
    }


def engine_benchmarks():
    """Before/after rows for the batched pass engine (the tentpole):

    * problem-(13): loop of the scalar reference solver vs one
      ``solve_batch`` call over the same >=256-instance cut x pass sweep;
    * SL pass execution: 16 Python-loop ``make_sl_step`` + eager SGD
      calls vs ONE jitted ``make_sl_pass`` scan of the same 16 steps;
    * revolution planning: a per-pass scalar ``solve_with_shedding``
      loop (the pre-planner scheduler) vs one ``RevolutionPlanner``
      batched solve for the same ring revolution.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import resource_opt
    from repro.core.energy import PassBudget
    from repro.core.mission import RevolutionPlanner
    from repro.core.sl_step import autoencoder_adapter, make_sl_pass, \
        make_sl_step
    from repro.core.splitting import resnet18_plan
    from repro.core.train_state import SLTrainState
    from repro.data.synthetic import ImageryShards
    from repro.train.optimizer import sgd, sgd_init, sgd_update

    print("== pass-engine benchmarks (batched solver + fused SL pass) ==")
    print("name,us_per_call,derived")
    out = {}

    # --- problem (13): 32 n_items variants x every ResNet-18 cut --------
    plan = resnet18_plan(img=224, n_classes=1000)
    cuts = plan.enumerate_cuts()
    budgets, costs = [], []
    for j in range(32):
        b = PassBudget(n_items=50.0 * (j + 1))
        for c in cuts:
            budgets.append(b)
            costs.append(c)
    n_inst = len(costs)
    assert n_inst >= 256, n_inst

    def scalar_loop():
        return [resource_opt.solve_reference(b, c)
                for b, c in zip(budgets, costs)]

    def batched():
        return resource_opt.solve_batch(budgets, costs)

    us_loop, _ = _timeit(scalar_loop, n=1, warmup=0)   # pure python: no jit
    us_batch, rep = _timeit(batched, n=3, warmup=1)
    speedup = us_loop / us_batch
    out["solve_scalar_loop"] = dict(us=us_loop, n_instances=n_inst)
    out["solve_batch"] = dict(us=us_batch, n_instances=n_inst,
                              speedup_vs_scalar=speedup,
                              feasible=int(rep.feasible.sum()))
    print(f"solve13_scalar_loop_{n_inst},{us_loop:.0f},"
          f"{us_loop/n_inst:.0f}us/instance")
    print(f"solve13_batch_{n_inst},{us_batch:.0f},{speedup:.1f}x-speedup")

    # --- SL pass: 16 steps, python loop vs one fused scan ---------------
    ad = autoencoder_adapter(cut=5, img=32)
    pa, pb = ad.init(jax.random.key(0))
    shards = ImageryShards(img=32, batch=4)
    batches = [jax.tree.map(jnp.asarray, shards.batch_at(0, i))
               for i in range(16)]
    step = make_sl_step(ad)
    opt = sgd(lr=1e-2)
    sl_pass = make_sl_pass(ad, optimizer=opt, donate=False)

    def step_loop():
        p_a, p_b = pa, pb
        oa, ob = sgd_init(pa), sgd_init(pb)
        for bt in batches:
            r = step(p_a, p_b, bt)
            p_a, oa, _ = sgd_update(r.grads_a, oa, p_a, lr=1e-2)
            p_b, ob, _ = sgd_update(r.grads_b, ob, p_b, lr=1e-2)
        return jax.block_until_ready(p_a)

    def fused_pass():
        r = sl_pass(SLTrainState.create(pa, pb, opt), batches)
        return jax.block_until_ready(r.params_a)

    us_steps, _ = _timeit(step_loop, n=3, warmup=1)
    us_pass, _ = _timeit(fused_pass, n=3, warmup=1)
    speedup = us_steps / us_pass
    out["sl_step_loop_16"] = dict(us=us_steps)
    out["sl_pass_16"] = dict(us=us_pass, speedup_vs_step_loop=speedup)
    print(f"sl_step_loop_16,{us_steps:.0f},16-python-dispatches")
    print(f"sl_pass_16,{us_pass:.0f},{speedup:.2f}x-speedup-one-scan")

    # --- revolution planning: per-pass scalar solves vs one planner -----
    # 64-sat ring, work spread so some rows shed: the pre-planner
    # scheduler paid one scalar solve_with_shedding per pass.
    ring_ids = list(range(64))
    w_max = PassBudget().sat_device.peak_flops \
        * PassBudget().plane.pass_duration_s / PassBudget().n_items
    rev_budgets = [PassBudget(n_items=200.0 + 25.0 * s) for s in ring_ids]
    rev_costs = [dataclasses.replace(cuts[s % len(cuts)],
                                     w1_flops=w_max * (0.02 * s))
                 for s in ring_ids]

    def per_pass_loop():
        return [resource_opt.solve_with_shedding(b, c)
                for b, c in zip(rev_budgets, rev_costs)]

    def planner_call():
        return RevolutionPlanner().plan_revolution(ring_ids, rev_budgets,
                                                   rev_costs)

    us_scalar, _ = _timeit(per_pass_loop, n=1, warmup=0)
    us_planner, entries = _timeit(planner_call, n=3, warmup=1)
    speedup = us_scalar / us_planner
    out["revolution_scalar_loop_64"] = dict(us=us_scalar)
    out["revolution_planner_64"] = dict(us=us_planner,
                                        speedup_vs_scalar=speedup,
                                        n_sats=len(entries))
    print(f"revolution_scalar_loop_64,{us_scalar:.0f},64-scalar-sheds")
    print(f"revolution_planner_64,{us_planner:.0f},"
          f"{speedup:.1f}x-speedup-one-batched-solve")
    return out


def solver_backend_benchmarks(quick: bool = False):
    """Backend rows for the problem-(13) solver (the device tentpole):

    * ``solve13_numpy_<B>``: the lockstep NumPy ``solve_batch`` over a
      >=4096-instance (cut x n_items) grid (full call incl. the host
      coefficient gather, i.e. what any consumer pays);
    * ``solve13_jax_<B>``: ``solve_batch_jax`` post-compile, same grid,
      same full-call accounting;
    * ``solve13_jax_device_<B>``: the device-resident core
      (``solve_coeffs`` on pre-staged CoeffArrays) — the number a
      zero-host-transfer pipeline (sweep_revolutions) actually sees.
    """
    import jax
    from repro.core import resource_opt, resource_opt_jax
    from repro.core.energy import PassBudget
    from repro.core.splitting import resnet18_plan

    print("== solver-backend benchmarks (numpy vs jit+vmap jax) ==")
    print("name,us_per_call,derived")
    out = {}

    plan = resnet18_plan(img=224, n_classes=1000)
    cuts = plan.enumerate_cuts()
    n_variants = 36 if quick else 512
    budgets, costs = [], []
    for j in range(n_variants):
        b = PassBudget(n_items=50.0 * (j + 1))
        for c in cuts:
            budgets.append(b)
            costs.append(c)
    n_inst = len(costs)
    if not quick:
        assert n_inst >= 4096, n_inst

    def np_call():
        return resource_opt.solve_batch(budgets, costs, backend="numpy")

    def jax_call():
        return resource_opt.solve_batch(budgets, costs, backend="jax")

    us_np, rep_np = _timeit(np_call, n=3, warmup=1)
    us_jax, rep_jax = _timeit(jax_call, n=3, warmup=1)   # warmup compiles

    blist, clist = resource_opt._broadcast_instances(budgets, costs)
    with resource_opt_jax.x64_scope():
        coeffs = resource_opt_jax._coeffs_from_instances(blist, clist)

        def device_call():
            return jax.block_until_ready(
                resource_opt_jax.solve_coeffs(coeffs).phase_times)

        us_dev, _ = _timeit(device_call, n=3, warmup=1)

    import numpy as np
    agree = bool(np.allclose(rep_np.e_total, rep_jax.e_total, rtol=1e-8))
    out["solve13_numpy"] = dict(us=us_np, n_instances=n_inst)
    out["solve13_jax"] = dict(us=us_jax, n_instances=n_inst,
                              speedup_vs_numpy=us_np / us_jax,
                              parity_vs_numpy=agree)
    out["solve13_jax_device"] = dict(us=us_dev, n_instances=n_inst,
                                     speedup_vs_numpy=us_np / us_dev)
    print(f"solve13_numpy_{n_inst},{us_np:.0f},host-lockstep")
    print(f"solve13_jax_{n_inst},{us_jax:.0f},"
          f"{us_np / us_jax:.2f}x-vs-numpy,parity={agree}")
    print(f"solve13_jax_device_{n_inst},{us_dev:.0f},"
          f"{us_np / us_dev:.2f}x-vs-numpy-device-resident")
    return out


def sweep_benchmarks(quick: bool = False):
    """The on-device revolution sweep: a (ring x cut x budget) grid —
    including the 1000-sat ring in full mode — planned (coefficients,
    shedding, dual bisection) in ONE jitted call with zero host
    transfers, then chained into a fused SL pass via a device-side step
    count (``steps_for`` -> ``n_valid``) without ever syncing the plan.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.mission import sweep_revolutions
    from repro.core.sl_step import autoencoder_adapter, make_sl_pass
    from repro.core.splitting import resnet18_plan
    from repro.core.train_state import SLTrainState
    from repro.data.synthetic import ImageryShards
    from repro.train.optimizer import sgd

    print("== revolution-sweep benchmarks (on-device planning) ==")
    print("name,us_per_call,derived")
    out = {}

    cuts = resnet18_plan(img=224, n_classes=1000).enumerate_cuts()
    ring_sizes = [25, 100] if quick else [25, 100, 1000]
    n_items = [100.0 * (j + 1) for j in range(4 if quick else 32)]

    def sweep_call():
        sw = sweep_revolutions(ring_sizes, cuts, n_items)
        jax.block_until_ready(sw.e_pass)
        return sw

    us_sweep, sw = _timeit(sweep_call, n=3, warmup=1)
    r, c, b = sw.shape
    n_cells = r * c * b
    host = sw.to_host()
    out["sweep_revolutions"] = dict(
        us=us_sweep, ring_sizes=list(map(int, ring_sizes)),
        n_cells=n_cells, us_per_cell=us_sweep / n_cells,
        feasible_cells=int(host["feasible"].sum()),
        max_ring=int(max(ring_sizes)))
    print(f"sweep_revolutions_{n_cells},{us_sweep:.0f},"
          f"rings={ring_sizes}-x-{c}cuts-x-{b}budgets,"
          f"{us_sweep / n_cells:.1f}us/cell")

    # plan -> train with no host sync: the planned step count reaches the
    # fused pass as a device scalar (n_valid); time the chained call.
    ad = autoencoder_adapter(cut=5, img=32)
    shards = ImageryShards(img=32, batch=4)
    batches = [jax.tree.map(jnp.asarray, shards.batch_at(0, i))
               for i in range(8)]
    opt = sgd(lr=1e-2)
    sl_pass = make_sl_pass(ad, optimizer=opt, donate=False)
    plan_sweep = sweep_revolutions([25], [ad.costs()], [24.0])
    n_valid = plan_sweep.steps_for(4)[0, 0, 0]     # 6 of 8 steps, on device

    def planned_pass():
        r = sl_pass(SLTrainState.create(*ad.init(jax.random.key(0)), opt),
                    batches, n_valid=n_valid)
        return jax.block_until_ready(r.losses)

    us_pass, losses = _timeit(planned_pass, n=3, warmup=1)
    n_ran = int(np.isfinite(np.asarray(losses)).sum())
    out["sweep_planned_pass"] = dict(us=us_pass, steps_planned=n_ran,
                                     steps_offered=len(batches))
    print(f"sweep_planned_pass,{us_pass:.0f},"
          f"{n_ran}/{len(batches)}-steps-device-masked")
    return out


def device_sim_benchmarks(quick: bool = False):
    """Closed-loop rows: the host Python scheduler
    (``ConstellationSim.run()``) vs the device-resident engine
    (``repro.sim.device_sim``) running the SAME steady-state scenario —
    planning + reserve-skip policy + masked fused passes +
    battery/recharge accounting — on identical data (the traceable
    provider serves both).  Quick mode: a 16-sat ring × 2 revolutions;
    full mode adds the 64-sat and 1000-sat rings the ISSUE/ROADMAP
    target.  Parity of trained/skipped counts is asserted per row.
    """
    from repro.core.constellation import (ConstellationConfig,
                                          ConstellationSim)
    from repro.core.energy import PassBudget
    from repro.core.orbits import OrbitalPlane
    from repro.core.sl_step import autoencoder_adapter
    from repro.sim.data import DeviceImageryShards

    print("== closed-loop benchmarks (host scheduler vs device engine) ==")
    print("name,us_per_call,derived")
    out = {}
    shards = DeviceImageryShards(img=32, batch=2)
    adapter = autoencoder_adapter(cut=5, img=32)
    # (ring size, revolutions, fused steps per pass): the 1000-sat row
    # runs 1 step/pass so the host baseline stays affordable on CPU
    scenarios = [(16, 2, 2)] if quick else [(64, 2, 2), (1000, 1, 1)]
    for n_sats, n_rev, k_steps in scenarios:
        budget = PassBudget(plane=OrbitalPlane(n_sats=n_sats), n_items=4e6)
        cfg = ConstellationConfig(
            batch_size=2, n_passes=n_rev * n_sats, battery_j=200.0,
            recharge_w=1e-4, reserve_j=150.0,
            max_steps_per_pass=k_steps)

        # both cold rows are symmetric end-to-end accounting (fresh sim,
        # jit compiles included — what a consumer pays once); the
        # post-compile row re-dispatches the SAME engine, i.e. the
        # steady-state cost of every further revolution batch.
        def host_run():
            sim = ConstellationSim(adapter, budget, shards, cfg)
            sim.run()
            return sim.summary()

        us_host, hs = _timeit(host_run, n=1, warmup=0)
        engine = ConstellationSim(adapter, budget, shards,
                                  cfg).as_device_sim()
        us_cold, res = _timeit(engine.run, n=1, warmup=0)
        ds = res.summary()
        us_warm, _ = _timeit(engine.run, n=1, warmup=0)
        parity = (hs["trained"] == ds["trained"]
                  and hs["skipped"] == ds["skipped"])
        assert parity, (f"host/device closed-loop divergence at "
                        f"{n_sats} sats: host {hs} vs device {ds}")
        n_passes = n_rev * n_sats
        out[f"closed_loop_host_{n_sats}"] = dict(
            us=us_host, n_passes=n_passes, us_per_pass=us_host / n_passes)
        out[f"closed_loop_device_{n_sats}"] = dict(
            us=us_cold, n_passes=n_passes, us_per_pass=us_cold / n_passes,
            speedup_vs_host=us_host / us_cold, parity_vs_host=parity)
        out[f"closed_loop_device_warm_{n_sats}"] = dict(
            us=us_warm, n_passes=n_passes, us_per_pass=us_warm / n_passes,
            speedup_vs_host=us_host / us_warm)
        print(f"closed_loop_host_{n_sats},{us_host:.0f},"
              f"{n_passes}-python-dispatched-passes-cold")
        print(f"closed_loop_device_{n_sats},{us_cold:.0f},"
              f"{us_host / us_cold:.1f}x-vs-host-cold-incl-compile,"
              f"parity={parity}")
        print(f"closed_loop_device_warm_{n_sats},{us_warm:.0f},"
              f"{us_host / us_warm:.1f}x-vs-host-post-compile")
    return out


def fleet_benchmarks(quick: bool = False):
    """Sharded fleet rows: the P-plane elastic engine
    (``repro.fleet.FleetEngine`` — one jitted scan, vmapped over planes,
    plane axis sharded over the host mesh, inter-plane checkpoint
    averaging every revolution) vs the *per-plane loop* of P single-ring
    device engines with explicit averaging between revolutions.  Quick
    mode runs a 2x16 fleet; full mode the 2x64 and 4x256 fleets the
    ISSUE targets.  Parity (action sequences + losses vs the per-plane
    reference) is asserted per row.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.energy import PassBudget
    from repro.core.orbits import OrbitalPlane
    from repro.core.sl_step import autoencoder_adapter
    from repro.core.train_state import SLTrainState
    from repro.fleet import FleetConfig, FleetEngine, average_planes
    from repro.sim.data import DeviceImageryShards
    from repro.sim.device_sim import (DeviceConstellationSim,
                                      DeviceSimConfig)
    from repro.train.optimizer import resolve_optimizer

    print("== fleet benchmarks (P-plane sharded engine vs per-plane "
          "loop) ==")
    print("name,us_per_call,derived")
    out = {}
    shards = DeviceImageryShards(img=32, batch=2)
    adapter = autoencoder_adapter(cut=5, img=32)
    energy = dict(battery_j=200.0, recharge_w=1e-4, reserve_j=150.0,
                  max_steps_per_pass=1, seed=0)
    scenarios = [(2, 16, 2)] if quick else [(2, 64, 2), (4, 256, 1)]
    for P, N, R in scenarios:
        budget = PassBudget(plane=OrbitalPlane(n_sats=N), n_items=4e6)
        cfg = FleetConfig(n_planes=P, n_revolutions=R, avg_every=1,
                          **energy)

        def fleet_run():
            eng = FleetEngine(adapter, budget, shards, cfg)
            return eng, eng.run()

        us_cold, (eng, res) = _timeit(fleet_run, n=1, warmup=0)
        cold_syncs = eng.host_syncs           # before the warm re-run
        us_warm, _ = _timeit(eng.run, n=1, warmup=0)
        M = eng.n_slots

        # the pre-fleet workflow: P independent single-ring engines,
        # checkpoints averaged on the host loop between revolutions
        opt = resolve_optimizer("sgd", lr=cfg.lr)
        init = SLTrainState.create(*adapter.init(jax.random.key(0)), opt)

        def plane_loop():
            engines = [DeviceConstellationSim(
                adapter, budget, lambda s, i, p=p: shards(p * M + s, i),
                DeviceSimConfig(**energy),
                state=jax.tree.map(jnp.copy, init)) for p in range(P)]
            acts, losses = [], []
            for _ in range(R):
                rr = [e.run(1, stream_telemetry=True) for e in engines]
                acts.append(np.stack([r.action[0] for r in rr]))
                losses.append(np.stack([r.loss[0] for r in rr]))
                avg = average_planes(jax.tree.map(
                    lambda *xs: jnp.stack(xs),
                    *[e.state for e in engines]))
                for p, e in enumerate(engines):
                    e.state = jax.tree.map(lambda x: x[p], avg)
            return np.concatenate(acts, 1), np.concatenate(losses, 1)

        us_ref, (ref_act, ref_loss) = _timeit(plane_loop, n=1, warmup=0)
        parity = bool((res.action == ref_act).all()
                      and np.allclose(np.nan_to_num(res.loss),
                                      np.nan_to_num(ref_loss),
                                      rtol=2e-4, atol=2e-5))
        assert parity, (f"fleet/per-plane divergence at {P}x{N}: "
                        f"{res.summary()}")
        n_passes = P * R * N
        name = f"closed_loop_fleet_{P}x{N}"
        # both cold rows are end-to-end incl. construction + compiles
        # (the reference pays P of them); the warm row re-dispatches the
        # SAME fleet program — the steady-state per-revolution cost
        out[name] = dict(
            us=us_cold, n_passes=n_passes, n_planes=P,
            us_per_pass=us_cold / n_passes, parity_vs_plane_loop=parity,
            speedup_vs_plane_loop=us_ref / us_cold,
            host_syncs=cold_syncs)
        out[f"{name}_warm"] = dict(us=us_warm, n_passes=n_passes,
                                   us_per_pass=us_warm / n_passes)
        out[f"closed_loop_plane_loop_{P}x{N}"] = dict(us=us_ref,
                                                      n_passes=n_passes)
        print(f"{name},{us_cold:.0f},"
              f"{us_ref / us_cold:.1f}x-vs-per-plane-loop-cold,"
              f"parity={parity}")
        print(f"{name}_warm,{us_warm:.0f},"
              f"{us_warm / n_passes:.0f}us/pass-post-compile")
        print(f"closed_loop_plane_loop_{P}x{N},{us_ref:.0f},"
              f"{P}-engines-host-averaged-cold")
    return out


def degraded_ops_benchmarks(quick: bool = False):
    """Degraded-ops scenario rows (``repro.fleet.scenarios``):

    * Byzantine recovery (full mode) — the ISSUE acceptance scenario: a
      4-plane fleet with one whole plane sign-flipping its updates
      (scale 8).  Plain ``mean`` aggregation lets the corrupted plane
      poison the inter-plane exchange (final loss blows up orders of
      magnitude); ``trimmed_mean`` / ``median`` drop the outlier
      coordinate-wise and land within a few percent of the fault-free
      run.  Final loss = mean of the honest planes' last finite loss.
    * Eclipse duty sweep — the same fleet energy envelope under 0%,
      50% and 100% orbital shadow: trained/skipped counts show the
      shadow reaching the reserve-skip policy through the battery.
    """
    import numpy as np
    from repro.core.energy import PassBudget
    from repro.core.orbits import OrbitalPlane
    from repro.core.sl_step import autoencoder_adapter
    from repro.fleet import (ByzantineConfig, EclipseConfig, FleetConfig,
                             FleetEngine, ScenarioConfig)
    from repro.sim.data import DeviceImageryShards

    print("== degraded-ops benchmarks (byzantine planes + eclipse) ==")
    print("name,us_per_call,derived")
    out = {}
    shards = DeviceImageryShards(img=32, batch=4)
    adapter = autoencoder_adapter(cut=5, img=32)

    if not quick:
        # --- Byzantine recovery: 1 of 4 planes lies, scale 8 ----------
        P, N, R = 4, 4, 6
        budget = PassBudget(plane=OrbitalPlane(n_sats=N), n_items=4e6)
        byz = ScenarioConfig(byzantine=ByzantineConfig(
            planes=(3,), mode="sign_flip", scale=8.0))

        def final_loss(res):
            last = [row[np.isfinite(row)][-1] for row in res.loss[:3]]
            return float(np.mean(last))

        losses = {}
        for tag, scn, agg in (("fault_free", None, "mean"),
                              ("byzantine_mean", byz, "mean"),
                              ("byzantine_trimmed", byz, "trimmed_mean"),
                              ("byzantine_median", byz, "median")):
            cfg = FleetConfig(n_planes=P, n_revolutions=R,
                              battery_j=5000.0, recharge_w=20.0,
                              reserve_j=100.0, max_steps_per_pass=4,
                              seed=0, avg_every=1, scenario=scn,
                              aggregate=agg)

            def degraded_run(cfg=cfg):
                eng = FleetEngine(adapter, budget, shards, cfg)
                return eng, eng.run()

            us, (eng, res) = _timeit(degraded_run, n=1, warmup=0)
            losses[tag] = final_loss(res)
            name = f"degraded_ops_{tag}_{P}x{N}"
            out[name] = dict(us=us, n_passes=P * R * N, aggregate=agg,
                             final_loss=losses[tag],
                             host_syncs=eng.host_syncs)
            print(f"{name},{us:.0f},aggregate={agg},"
                  f"final_loss={losses[tag]:.4g}")
        clean = losses["fault_free"]
        out["degraded_ops_recovery"] = dict(
            loss_fault_free=clean,
            loss_byzantine_mean=losses["byzantine_mean"],
            loss_byzantine_trimmed=losses["byzantine_trimmed"],
            loss_byzantine_median=losses["byzantine_median"],
            mean_blowup=losses["byzantine_mean"] / clean,
            trimmed_gap_pct=100.0
            * abs(losses["byzantine_trimmed"] - clean) / clean,
            median_gap_pct=100.0
            * abs(losses["byzantine_median"] - clean) / clean)
        print(f"degraded_ops_recovery,-,"
              f"mean-blowup={losses['byzantine_mean'] / clean:.0f}x,"
              f"trimmed-gap="
              f"{out['degraded_ops_recovery']['trimmed_gap_pct']:.1f}%")

    # --- eclipse duty sweep: shadow -> battery -> reserve skips -------
    ecl_budget = PassBudget(plane=OrbitalPlane(n_sats=4), n_items=4e6)
    for duty in (0.0, 0.5, 1.0):
        scn = (None if duty == 0.0 else ScenarioConfig(
            eclipse=EclipseConfig(period=4, duty=duty, stagger=1)))
        cfg = FleetConfig(n_planes=2, n_revolutions=3, battery_j=200.0,
                          recharge_w=0.05, reserve_j=180.0,
                          max_steps_per_pass=2, seed=0, avg_every=1,
                          scenario=scn)

        def eclipse_run(cfg=cfg):
            eng = FleetEngine(adapter, ecl_budget, shards, cfg)
            return eng.run()

        us, res = _timeit(eclipse_run, n=1, warmup=0)
        s = res.summary()
        name = f"degraded_ops_eclipse_duty{int(duty * 100):03d}"
        out[name] = dict(
            us=us, n_passes=int(res.action.size), trained=s["trained"],
            skipped=s["skipped"],
            energy_spent_j=float(
                np.asarray(res.energy.energy_spent_j).sum()))
        print(f"{name},{us:.0f},trained={s['trained']},"
              f"skipped={s['skipped']}")
    return out


def isl_frontier_benchmarks(quick: bool = False):
    """ISL exchange frontier (``repro.isl``): what compressed,
    bandwidth-limited inter-plane exchange buys and costs.

    Sweeps the codec grid {none, int8, top-k 10%, top-k 1%} across both
    exchange modes on a 2x16 fleet, entirely on device: ``sync`` is the
    revolution-boundary aggregation routed through the codec + meter
    (``none`` = the metered legacy barrier), ``async`` is contact-window
    gossip with staleness-discounted merges and no barrier at all.
    Each row reports the final loss, the actual wire bits / ISL joules
    drained from the batteries, and the *planned* per-pass
    ``d_isl_bits`` — the problem-(13) feedback that makes compression a
    resource-allocation decision rather than a counter.

    Asserts the acceptance frontier: (a) async top-k 1% lands within
    50% of the full-float sync barrier's final loss; (b) wire bits
    shrink monotonically with compression in both modes; (c) the
    planned allocation differs between compression levels.
    """
    import numpy as np
    from repro.core.energy import PassBudget
    from repro.core.orbits import OrbitalPlane
    from repro.core.sl_step import autoencoder_adapter
    from repro.fleet import FleetConfig, FleetEngine
    from repro.isl import (CodecConfig, ContactConfig, ExchangeConfig,
                           codec_label)
    from repro.sim.data import DeviceImageryShards

    P, N = 2, 16
    R = 2 if quick else 6
    print(f"== isl exchange frontier (codec x mode, {P}x{N} fleet) ==")
    print("name,us_per_call,derived")
    out = {}
    shards = DeviceImageryShards(img=32, batch=4)
    adapter = autoencoder_adapter(cut=5, img=32)
    budget = PassBudget(plane=OrbitalPlane(n_sats=N), n_items=4e6)
    codecs = [CodecConfig("none"), CodecConfig("int8"),
              CodecConfig("topk", topk_ratio=0.10),
              CodecConfig("topk", topk_ratio=0.01)]

    def final_loss(res):
        last = [row[np.isfinite(row)][-1] for row in res.loss]
        return float(np.mean(last))

    rows = {}
    for mode in ("sync", "async"):
        for codec in codecs:
            if mode == "sync":
                cfg = FleetConfig(
                    n_planes=P, n_revolutions=R, max_steps_per_pass=2,
                    seed=0, avg_every=1,
                    exchange=ExchangeConfig(mode="sync", codec=codec))
            else:
                cfg = FleetConfig(
                    n_planes=P, n_revolutions=R, max_steps_per_pass=2,
                    seed=0, avg_every=0,
                    exchange=ExchangeConfig(
                        mode="async", codec=codec,
                        contact=ContactConfig(period=2), mix=0.5,
                        staleness_lam=0.1))

            def frontier_run(cfg=cfg):
                eng = FleetEngine(adapter, budget, shards, cfg)
                return eng, eng.run()

            us, (eng, res) = _timeit(frontier_run, n=1, warmup=0)
            s = res.summary()
            row = dict(
                us=us, n_passes=P * R * N, final_loss=final_loss(res),
                isl_bits=float(s["ISL_exchange_bits"]),
                isl_j=float(s["ISL_exchange_J"]),
                contacts=int(np.asarray(res.isl_contacts).sum()),
                plan_d_isl_bits=float(
                    np.asarray(eng.plan.d_isl_bits).mean()),
                host_syncs=eng.host_syncs)
            rows[(mode, codec_label(codec))] = row
            name = f"isl_frontier_{mode}_{codec_label(codec)}"
            out[name] = row
            print(f"{name},{us:.0f},loss={row['final_loss']:.4g},"
                  f"bits={row['isl_bits']:.3g},"
                  f"isl_J={row['isl_j']:.3g},"
                  f"plan_d_isl={row['plan_d_isl_bits']:.4g}")

    # -- the acceptance frontier ------------------------------------------
    order = ("none", "int8", "topk10pc", "topk1pc")
    for mode in ("sync", "async"):
        bits = [rows[(mode, c)]["isl_bits"] for c in order]
        assert bits == sorted(bits, reverse=True) and bits[-1] > 0, (
            "wire bits must shrink monotonically with compression",
            mode, dict(zip(order, bits)))
        plans = [rows[(mode, c)]["plan_d_isl_bits"] for c in order]
        assert len(set(plans)) == len(plans), (
            "planned d_isl_bits must differ between compression levels",
            mode, dict(zip(order, plans)))
    ref = rows[("sync", "none")]["final_loss"]
    got = rows[("async", "topk1pc")]["final_loss"]
    gap = abs(got - ref) / ref
    assert gap <= 0.5, (
        "async top-k 1% must land within 50% of the full-float sync "
        "barrier", got, ref)
    out["isl_frontier_acceptance"] = dict(
        sync_none_loss=ref, async_topk1pc_loss=got, rel_gap=gap,
        tolerance=0.5, bits_monotone=True, plans_differ=True)
    print(f"isl_frontier_acceptance,-,async-topk1pc-gap={gap * 100:.1f}%"
          f"-of-sync-full-float,bits-monotone,plans-differ")
    return out


def serve_fleet_benchmarks(quick: bool = False):
    """Serving-fleet rows (``repro.serve_fleet``): the constellation as
    an inference fleet.

    * ``serve_split_decode`` — one satellite's sustained generated
      tokens/sec, measured wall-clock on the real split-model
      continuous-batching engine (ground-half bulk prefill, satellite
      half + boundary downlink + ground half per decode step).
    * ``serve_fleet_PxM`` — constellation-scale pass-window serving at
      >= 1M offered users/day: Poisson arrivals with a diurnal profile,
      routed to the satellite overhead, FIFO backlog carry-over along
      the ring.  Reports sustained tokens/sec and FIFO p99 latency;
      the NumPy host oracle asserts bit-exact f32 energy parity per
      row.  Capacity scales with the number of planes (one terminal
      serves one overhead satellite at a time — the paper's geometry),
      so 1x64 vs 4x256 is the constellation-size comparison.
    * ``serve_fleet_contention`` — the same offered load with a
      concurrent planned training pass per window on ONE shared
      battery: trained-pass count with vs without serving drain (the
      reserve-skip gate reads the post-serve battery).  Uses a fixed
      ServeCost so the row is measurement-noise-free for the trend
      report.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.models import lm
    from repro.serve_fleet import (FleetServeEngine, ServeCost,
                                   ServeFleetConfig, SplitDecodeEngine,
                                   TrafficConfig, TrainLoad,
                                   assert_host_parity,
                                   measure_decode_rate, serve_cost)

    print("== serve-fleet benchmarks (constellation as an inference "
          "fleet) ==")
    print("name,us_per_call,derived")
    out = {}

    # -- per-satellite split-decode rate (real engine, wall-clock) --------
    cfg = configs.get_smoke("smollm_360m")
    params = lm.init(cfg, jax.random.key(0))
    cut = max(1, cfg.n_units // 2)
    eng = SplitDecodeEngine(cfg, params, cut_units=cut, n_slots=8,
                            s_max=64, act_dtype=jnp.float32)
    rate = measure_decode_rate(eng, n_requests=8 if quick else 48,
                               prompt_len=6, new_tokens=12)
    cost = serve_cost(cfg, params, cut, tokens_per_s=rate)
    out["serve_split_decode"] = dict(
        arch=cfg.name, cut_units=cut, n_slots=8, tokens_per_s=rate,
        e_token_j=cost.e_token_j, dtx_bits_token=cost.dtx_bits_token)
    print(f"serve_split_decode,,{rate:.1f}tok/s,"
          f"e_token={cost.e_token_j:.2e}J")

    # -- constellation-size rows at >= 1M users/day -----------------------
    # offered load = 2x ONE satellite's measured capacity (>= 1.5M
    # users/day): a single plane saturates (one sat overhead at a time),
    # four planes = four terminals serve the same load comfortably —
    # capacity scales with planes, and the p99 gap shows it
    decode_len = 12
    users = max(1.5e6, 2.0 * rate * 86_400.0 / decode_len)
    traffic = TrafficConfig(users_per_day=users, prompt_len=6,
                            decode_len=decode_len)
    scenarios = [(1, 8, 16)] if quick else [(1, 64, 192), (4, 256, 192)]
    for P, M, K in scenarios:
        scfg = ServeFleetConfig(n_planes=P, n_sats=M, n_windows=K,
                                battery_j=5000.0, recharge_w=25.0,
                                reserve_serve_j=100.0)
        fleet = FleetServeEngine(scfg, traffic, cost)
        us, res = _timeit(fleet.run, n=1, warmup=0)
        assert_host_parity(res, None)            # f32 energy parity
        s = res.summary()
        name = f"serve_fleet_{P}x{M}"
        out[name] = dict(us=us, host_syncs=fleet.host_syncs,
                         energy_parity=True, **s)
        print(f"{name},{us:.0f},"
              f"{s['sustained_tokens_per_s']:.0f}tok/s,"
              f"p99={s['p99_latency_s']:.0f}s,"
              f"backlog={s['final_backlog_requests']:.0f}")

    # -- train-vs-serve contention on one battery -------------------------
    M, K = (8, 32) if quick else (16, 192)
    fixed = ServeCost(tokens_per_s=2000.0, e_token_j=5e-3,
                      dtx_bits_token=cost.dtx_bits_token)
    scfg = ServeFleetConfig(n_planes=1, n_sats=M, n_windows=K,
                            battery_j=1000.0, recharge_w=0.15,
                            reserve_serve_j=50.0, reserve_train_j=600.0)
    train = TrainLoad(drain_j=500.0, e_total_j=700.0)

    def contention(users):
        fleet = FleetServeEngine(
            scfg, dataclasses.replace(traffic, users_per_day=users),
            fixed, train=train)
        res = fleet.run()
        assert_host_parity(res, train)
        return res.summary()

    us, s_with = _timeit(lambda: contention(1.5e6), n=1, warmup=0)
    _, s_without = _timeit(lambda: contention(0.0), n=1, warmup=0)
    assert s_with["trained_passes"] < s_without["trained_passes"], (
        "serving drain must cost trained passes", s_with, s_without)
    out["serve_fleet_contention"] = dict(
        us=us, n_windows=K, n_sats=M,
        trained_with_serve=s_with["trained_passes"],
        skipped_with_serve=s_with["skipped_passes"],
        trained_without_serve=s_without["trained_passes"],
        skipped_without_serve=s_without["skipped_passes"],
        serve_energy_spent_j=s_with["serve_energy_spent_j"])
    print(f"serve_fleet_contention,{us:.0f},"
          f"trained {s_with['trained_passes']} (serving) vs "
          f"{s_without['trained_passes']} (idle) of {K}")
    return out


def micro_benchmarks():
    """us/call for the SL step + each kernel's jnp path (CPU; the numbers
    are for regression tracking, not TPU performance claims)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.sl_step import autoencoder_adapter, make_sl_step
    from repro.data.synthetic import ImageryShards
    from repro.kernels import ops

    print("== micro-benchmarks (CPU reference timings) ==")
    print("name,us_per_call,derived")
    rng = np.random.default_rng(0)
    out = {}

    ad = autoencoder_adapter(cut=5, img=32)
    pa, pb = ad.init(jax.random.key(0))
    batch = jax.tree.map(jnp.asarray, ImageryShards(img=32, batch=4)
                         .batch_at(0, 0))
    step = make_sl_step(ad)
    us, _ = _timeit(lambda: step(pa, pb, batch))
    out["sl_step_autoencoder"] = us
    print(f"sl_step_autoencoder,{us:.0f},loss+both-grads")

    q = jnp.asarray(rng.standard_normal((1, 8, 512, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 512, 64)), jnp.float32)
    f = jax.jit(lambda q, k, v: ops.flash_attention(
        q, k, v, causal=True, use_pallas=False))
    us, _ = _timeit(lambda: jax.block_until_ready(f(q, k, v)))
    flops = 4 * 8 * 512 * 512 / 2 * 64
    out["flash_attention_512"] = us
    print(f"flash_attention_512,{us:.0f},{flops/us/1e3:.1f}GFLOP/s")

    x = jnp.asarray(rng.standard_normal((1, 512, 4, 64)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((1, 512, 4))))
    alog = jnp.asarray(rng.standard_normal(4)) * 0.5
    b = jnp.asarray(rng.standard_normal((1, 512, 16)), jnp.float32)
    g = jax.jit(lambda *a: ops.mamba_scan(*a, chunk=128, use_pallas=False))
    us, _ = _timeit(lambda: jax.block_until_ready(g(x, dt, alog, b, b)[0]))
    out["mamba_scan_512"] = us
    print(f"mamba_scan_512,{us:.0f},chunked-ssd")

    xq = jnp.asarray(rng.standard_normal((4096, 512)), jnp.float32)
    h = jax.jit(lambda t: ops.quantize_boundary(t, use_pallas=False))
    us, _ = _timeit(lambda: jax.block_until_ready(h(xq)[0]))
    out["split_quant_4096x512"] = us
    print(f"split_quant_4096x512,{us:.0f},{xq.nbytes/us/1e3:.2f}GB/s")
    return out


def _flatten_metrics(obj, prefix=""):
    """Dotted-path -> float map of every numeric leaf in a results dict."""
    out = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_flatten_metrics(v, f"{prefix}{k}."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix.rstrip(".")] = float(obj)
    return out


def trend_report(results_dir: str, current: dict, rev: str,
                 threshold: float = 0.20) -> dict:
    """Compare this run against the previous ``BENCH_<rev>.json``.

    Timing metrics (dotted paths ending in ``.us`` or named ``us_*``)
    regress when they grow; each >``threshold`` change is flagged.  The
    report is printed and returned so it lands inside the current JSON.
    """
    prev_path, prev = None, None
    candidates = []
    for p in glob.glob(os.path.join(results_dir, "BENCH_*.json")):
        if os.path.basename(p) == f"BENCH_{rev}.json":
            continue
        try:
            with open(p) as f:
                data = json.load(f)
            candidates.append((data.get("meta", {}).get("unix_time", 0.0),
                               p, data))
        except (json.JSONDecodeError, OSError):
            continue
    if candidates:
        _, prev_path, prev = max(candidates, key=lambda t: t[0])

    # errored sections (benchmark code raised; see their recorded
    # traceback in this run's JSON) are flagged up front — their rows
    # carry no metrics, so silence here would read as "no regression"
    errored = sorted(k for k, v in current.items()
                     if isinstance(v, dict) and v.get("status") == "error")
    report = {"baseline": prev_path and os.path.basename(prev_path),
              "threshold": threshold, "regressions": [],
              "improvements": [], "errored_sections": errored}
    for name in errored:
        print(f"  ERRORED section '{name}': benchmark raised — metrics "
              f"missing this run (traceback recorded in JSON)")
    if prev is None:
        print("\n== trend report: no previous BENCH_<rev>.json — baseline "
              "run ==")
        return report

    cur_m = _flatten_metrics(current)
    prev_m = _flatten_metrics(prev)
    # timing metrics: engine rows expose an `us` field; micro rows are
    # bare us/call floats.  Table values (losses, energies) are not
    # regressions in the timing sense and are left out.
    timing = {k for k in cur_m if k.endswith(".us") or k.startswith("micro.")}
    for k in sorted(timing & prev_m.keys()):
        if prev_m[k] <= 0.0:
            continue
        delta = cur_m[k] / prev_m[k] - 1.0
        row = {"metric": k, "prev_us": prev_m[k], "cur_us": cur_m[k],
               "delta_pct": 100.0 * delta}
        if delta > threshold:
            report["regressions"].append(row)
        elif delta < -threshold:
            report["improvements"].append(row)

    base = report["baseline"]
    print(f"\n== trend report vs {base} "
          f"(flagging >{threshold:.0%} timing changes) ==")
    if not report["regressions"] and not report["improvements"]:
        print(f"  all {len(timing & prev_m.keys())} shared timing metrics "
              f"within {threshold:.0%}")
    for row in report["regressions"]:
        print(f"  REGRESSION {row['metric']}: {row['prev_us']:.0f}us -> "
              f"{row['cur_us']:.0f}us (+{row['delta_pct']:.0f}%)")
    for row in report["improvements"]:
        print(f"  improved   {row['metric']}: {row['prev_us']:.0f}us -> "
              f"{row['cur_us']:.0f}us ({row['delta_pct']:.0f}%)")
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke mode: small solver grids, no 1000-sat "
                         "sweep, paper tables skipped, no BENCH_<rev> "
                         "emission (results/bench_quick.json only)")
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    # a fresh global metrics registry: every engine any section builds
    # parents to it, and its aggregate snapshot becomes the BENCH
    # "metrics" block below
    from repro.obs.metrics import global_registry, reset_global

    reset_global()
    results = {}
    results["header"] = run_header(args.quick)
    print("== run header ==")
    for k, v in results["header"].items():
        print(f"  {k}: {v}")

    def section(name, fn, *a, **kw):
        # one failing section must not take the whole run (and its
        # BENCH_<rev>.json history entry) down with it: record the
        # failure as a row so the trend report can flag it
        try:
            results[name] = fn(*a, **kw)
        except Exception as exc:                  # noqa: BLE001
            tb = traceback.format_exc()
            print(f"!! benchmark section '{name}' FAILED: {exc!r}")
            print(tb)
            results[name] = {"status": "error", "error": repr(exc),
                             "traceback": tb}

    if not args.quick:
        from benchmarks import paper_tables

        try:
            results.update(paper_tables.run_all())
        except Exception as exc:                  # noqa: BLE001
            tb = traceback.format_exc()
            print(f"!! paper tables FAILED: {exc!r}")
            print(tb)
            results["paper_tables"] = {"status": "error",
                                       "error": repr(exc), "traceback": tb}
    section("engine", engine_benchmarks)
    section("solver_backend", solver_backend_benchmarks, quick=args.quick)
    section("sweep", sweep_benchmarks, quick=args.quick)
    section("device_sim", device_sim_benchmarks, quick=args.quick)
    section("fleet", fleet_benchmarks, quick=args.quick)
    section("degraded_ops", degraded_ops_benchmarks, quick=args.quick)
    section("isl_frontier", isl_frontier_benchmarks, quick=args.quick)
    section("serve_fleet", serve_fleet_benchmarks, quick=args.quick)
    section("micro", micro_benchmarks)
    errored = sorted(k for k, v in results.items()
                     if isinstance(v, dict) and v.get("status") == "error")
    rev = _git_rev()
    results["meta"] = {"rev": rev, "wall_s": time.time() - t0,
                       "unix_time": time.time(), "quick": args.quick,
                       "errored_sections": errored}
    # aggregate registry snapshot across every engine the sections
    # built: sim.*/fleet.*/serve_fleet.* traces / device_calls /
    # host_syncs / events_recorded counters + dispatch_s histograms
    results["metrics"] = global_registry().to_dict()

    os.makedirs("results", exist_ok=True)
    if not args.quick:
        # quick runs never enter the trend history: their small grids
        # would read as huge spurious "improvements" next full run
        results["trend"] = trend_report("results", results, rev)

    def _clean(o):
        if isinstance(o, dict):
            return {k: _clean(v) for k, v in o.items()}
        if isinstance(o, (list, tuple)):
            return [_clean(v) for v in o]
        if isinstance(o, (float, int, str, bool)) or o is None:
            return o
        return float(o) if hasattr(o, "__float__") else str(o)

    cleaned = _clean(results)
    if args.quick:
        path = os.path.join("results", "bench_quick.json")
        with open(path, "w") as f:
            json.dump(cleaned, f, indent=1)
        print(f"\nquick benchmarks done in {time.time()-t0:.1f}s -> {path}")
        return
    bench_path = os.path.join("results", f"BENCH_{rev}.json")
    for path in (bench_path, os.path.join("results", "bench.json")):
        with open(path, "w") as f:
            json.dump(cleaned, f, indent=1)
    print(f"\nall benchmarks done in {time.time()-t0:.1f}s "
          f"-> {bench_path} (+ results/bench.json)")


if __name__ == "__main__":
    main()
