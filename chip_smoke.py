"""Bring-up smoke of the fleet closed loop on a TPU, at ResNet-18/224 width.

    python chip_smoke.py            # one chip: device, planner, kernels, fleet
    python chip_smoke.py --chips 4  # four chips: the plane-sharded fleet only

Everything runs in this one process; no phase's failure is caught.  The
script exits nonzero, printing no result, when JAX finds no TPU (for
example under ``JAX_PLATFORMS=cpu``) or when it runs outside a checkout
of this repository.  Each phase prints one line of results; the last
line of standard output is the JSON object
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Phases (one chip):

* **device** — platform, device kind and count.
* **planner** — problem (13), shed and solved on the chip under float64
  for a 1000-satellite ring × every ResNet-18/224 cut, against the NumPy
  oracle on the same instances at the tolerances of
  ``tests/test_resource_opt_jax.py``.
* **kernels** — ``split_quant`` at the ResNet-18 ``l2`` boundary
  activation, ``flash_attn`` and ``decode_attn`` at smollm_360m widths
  (15 query heads, 5 KV heads, head dim 64), compiled for the chip and
  checked against ``kernels/ref.py``.
* **fleet** — ``FleetEngine`` with one 25-satellite plane training the
  ResNet-18/224 split at the paper's ``l2`` cut, two revolutions with
  streamed telemetry: one trace, one host sync per revolution, finite
  losses; the first revolution's actions, serving satellites and
  batteries equal to the host ``ConstellationSim`` oracle, and its first
  pass's loss within the tolerances of ``repro.fleet.engine._smoke``.

``--chips 4`` runs a 4-plane fleet with the synchronous ISL exchange
after every pass on a 4-device plane mesh, and the same configuration
on a 1-device mesh in this process, and checks that both agree pass by
pass and that the fleet state is spread over the four devices.

Times printed here are bring-up timings of one run, not benchmark
numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import resource_opt as ro  # noqa: E402
from repro.core import resource_opt_jax as roj  # noqa: E402
from repro.core.constellation import (ConstellationConfig,  # noqa: E402
                                      ConstellationSim)
from repro.core.energy import PassBudget  # noqa: E402
from repro.core.orbits import OrbitalPlane  # noqa: E402
from repro.core.sl_step import resnet18_adapter  # noqa: E402
from repro.core.splitting import (RESNET18_PAPER_CUTS,  # noqa: E402
                                  resnet18_plan)
from repro.core.train_state import SLTrainState  # noqa: E402
from repro.fleet import FleetConfig, FleetEngine  # noqa: E402
from repro.isl import ExchangeConfig  # noqa: E402
from repro.kernels import (decode_attn, flash_attn, ref,  # noqa: E402
                           split_quant)
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.sim.data import DeviceImageryShards  # noqa: E402
from repro.sim.device_sim import (ACTION_NAMES, ACTION_SHED,  # noqa: E402
                                  ACTION_TRAINED)
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

IMG, N_CLASSES, BATCH = 224, 1000, 32
CUT = RESNET18_PAPER_CUTS["l2"]
# smollm_360m attention widths (configs/smollm_360m.py)
Q_HEADS, KV_HEADS, HEAD_DIM, SEQ = 15, 5, 64, 2048
# the parity tolerances of tests/test_resource_opt_jax.py, of
# fleet/engine._smoke and of tests/test_kernels.py (bfloat16 inputs)
SOLVE_RTOL, SOLVE_ATOL, FRAC_ATOL = 1e-6, 1e-12, 2e-4
LOSS_RTOL, LOSS_ATOL, BATT_RTOL, BATT_ATOL = 2e-4, 2e-5, 1e-5, 0.05
BF16_TOL = 2e-2


def _check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _resnet_split():
    adapter = resnet18_adapter(cut=CUT, img=IMG, n_classes=N_CLASSES)
    return adapter, DeviceImageryShards(img=IMG, n_classes=N_CLASSES,
                                        batch=BATCH)


# ------------------------------------------------------------------ planner
def phase_planner(seed: int) -> None:
    cuts = resnet18_plan(img=IMG, n_classes=N_CLASSES).enumerate_cuts()
    plane = OrbitalPlane(n_sats=1000)
    budget = PassBudget(plane=plane)
    C, N = len(cuts), plane.n_sats
    # per-satellite item budgets over three decades around Table I's 400
    # images, so some rows keep every item and some shed
    n_items = np.exp(np.random.default_rng(seed).uniform(
        np.log(10.0), np.log(3e4), N))
    col = lambda f: np.array([[f(c)] for c in cuts])      # noqa: E731
    w1, w2 = col(lambda c: c.w1_flops), col(lambda c: c.w2_flops)
    dtx, disl = col(lambda c: c.dtx_bits), col(lambda c: c.d_isl_bits)

    sc = roj.grid_scalars(plane, budget.link, budget.isl,
                          budget.sat_device, budget.gs_device)

    def plan(sc, w1, w2, dtx, disl, n):
        coeffs = roj.ring_pass_coeffs(sc, (C, N), w1, w2, dtx, disl, n,
                                      ring_n=N)
        return roj.shed_and_solve_coeffs(coeffs)

    t0 = time.perf_counter()
    with roj.x64_scope():
        rep, frac = jax.jit(plan)(sc, w1, w2, dtx, disl, n_items)
        rep, frac = jax.tree.map(np.asarray, (rep, frac))
    t_solve = time.perf_counter() - t0

    # the NumPy oracle on the same (cut-major) instances: its shedding
    # bisection for the kept fraction, then its solve at the chip's kept
    # item counts
    blist = [dataclasses.replace(budget, n_items=float(n))
             for _ in cuts for n in n_items]
    clist = [c for c in cuts for _ in range(N)]
    shed = ro.solve_with_shedding_batch(blist, clist, backend="numpy")
    kept = [dataclasses.replace(b, n_items=b.n_items * float(f))
            for b, f in zip(blist, frac.reshape(-1))]
    want = ro.solve_batch(kept, clist, backend="numpy")

    frac_err = float(np.max(np.abs(frac.reshape(-1) - shed.kept_fraction)))
    _check(frac_err <= FRAC_ATOL, f"kept fraction off by {frac_err}")
    feas = rep.feasible.reshape(-1)
    _check(np.array_equal(feas, want.feasible), "feasibility differs")
    for name, got, ref_ in (
            ("phase times", rep.phase_times, want.phase_times),
            ("phase energies", rep.phase_energy, want.phase_energy)):
        got = got.reshape(-1, 4)
        _check(np.all(np.isfinite(got[feas])), f"non-finite {name}")
        np.testing.assert_allclose(got, ref_, rtol=SOLVE_RTOL,
                                   atol=SOLVE_ATOL, err_msg=name)
    err = np.abs(rep.phase_energy.reshape(-1, 4) - want.phase_energy)
    rel = float(np.max(err / np.maximum(np.abs(want.phase_energy), 1e-30)))
    print(f"planner: {C * N} instances ({N}-sat ring x {C} ResNet-18/224 "
          f"cuts), {int((frac < 1.0).sum())} shed, {int(feas.sum())} "
          f"feasible; vs NumPy oracle max|dfrac|={frac_err:.3g} "
          f"max rel energy err={rel:.3g} (rtol {SOLVE_RTOL:g}) OK; "
          f"bring-up: first call incl. compile {t_solve:.2f}s")


# ------------------------------------------------------------------ kernels
def phase_kernels(seed: int) -> None:
    k0, k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 5)

    # split_quant at the l2 boundary activation of a real batch
    adapter, shards = _resnet_split()
    params_a, _ = adapter.init(k0)
    z = jax.jit(adapter.forward_a)(params_a, shards(0, 0))
    x = z.reshape(-1, z.shape[-1])
    q, s = split_quant.quantize_rows(x, interpret=False)
    q_ref, s_ref = ref.quantize_rows(x)
    dq = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
    # a one-step difference is a rounding tie decided by a division the
    # kernel and XLA may round differently; more is a wrong kernel
    _check(dq.max() <= 1, f"int8 codes differ by {dq.max()}")
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)
    print(f"kernels: split_quant {tuple(z.shape)} l2 boundary -> "
          f"{tuple(x.shape)} int8 rows; {int((dq > 0).sum())} of {dq.size} "
          f"codes one step off ref, scales rtol 1e-6 OK")

    bf = jnp.bfloat16
    highest = jax.default_matmul_precision("highest")

    qa = jax.random.normal(k1, (1, Q_HEADS, SEQ, HEAD_DIM), bf)
    ka = jax.random.normal(k2, (1, KV_HEADS, SEQ, HEAD_DIM), bf)
    va = jax.random.normal(k3, (1, KV_HEADS, SEQ, HEAD_DIM), bf)
    out = flash_attn.flash_attention_fwd(qa, ka, va, causal=True,
                                         interpret=False)
    with highest:
        want = ref.attention(*(a.astype(jnp.float32) for a in (qa, ka, va)),
                             causal=True)
    got = np.asarray(out.astype(jnp.float32))
    np.testing.assert_allclose(got, np.asarray(want), rtol=BF16_TOL,
                               atol=BF16_TOL)
    err_fa = float(np.max(np.abs(got - np.asarray(want))))
    print(f"kernels: flash_attn causal {Q_HEADS}q/{KV_HEADS}kv heads x "
          f"{HEAD_DIM} x {SEQ} bf16 max|err|={err_fa:.3g} vs ref OK")

    slots = 8
    kq, kk, kv, kl = jax.random.split(k4, 4)
    qd = jax.random.normal(kq, (slots, Q_HEADS, 1, HEAD_DIM), bf)
    kd = jax.random.normal(kk, (slots, KV_HEADS, SEQ, HEAD_DIM), bf)
    vd = jax.random.normal(kv, (slots, KV_HEADS, SEQ, HEAD_DIM), bf)
    lengths = jax.random.randint(kl, (slots,), 1, SEQ + 1, jnp.int32)
    out = decode_attn.decode_attention(qd, kd, vd, lengths, interpret=False)
    with highest:
        want = ref.attention(*(a.astype(jnp.float32) for a in (qd, kd, vd)),
                             causal=False, kv_len=lengths)
    got = np.asarray(out.astype(jnp.float32))
    np.testing.assert_allclose(got, np.asarray(want), rtol=BF16_TOL,
                               atol=BF16_TOL)
    err_da = float(np.max(np.abs(got - np.asarray(want))))
    print(f"kernels: decode_attn {slots} slots x {Q_HEADS}q/{KV_HEADS}kv "
          f"heads x {HEAD_DIM}, cache {SEQ} bf16 max|err|={err_da:.3g} "
          f"vs ref OK")


# -------------------------------------------------------------------- fleet
def _trained(action):
    return (action == ACTION_TRAINED) | (action == ACTION_SHED)


def _loss_mismatches(got, want) -> int:
    return int(np.sum(np.abs(got - want)
                      > LOSS_RTOL * np.abs(want) + LOSS_ATOL))


def phase_fleet(seed: int) -> None:
    adapter, shards = _resnet_split()
    budget = PassBudget(plane=OrbitalPlane())
    cfg = FleetConfig(n_planes=1, n_revolutions=2, seed=seed)
    fleet = FleetEngine(adapter, budget, shards, cfg)

    # two chained one-revolution dispatches: the first traces and
    # compiles, the second reuses the program
    t0 = time.perf_counter()
    res1 = fleet.run(1, stream_telemetry=True)
    t1 = time.perf_counter()
    res2 = fleet.run(1, stream_telemetry=True)
    t2 = time.perf_counter()
    _check(fleet.traces == 1, f"{fleet.traces} traces, want 1")
    _check(fleet.host_syncs <= 2, f"{fleet.host_syncs} host syncs for 2 "
           "revolutions")
    action = np.concatenate([res1.action, res2.action], axis=1)
    loss = np.concatenate([res1.loss, res2.loss], axis=1)
    trained = _trained(action)
    _check(trained.sum() > 0, "no pass trained")
    _check(np.all(np.isfinite(loss[trained])), "non-finite training loss")
    print(f"fleet: 1 plane x {fleet.n_slots} sats x 2 revolutions, "
          f"ResNet-18/{IMG} cut l2, batch {BATCH}, {int(res1.n_steps.max())} "
          f"steps/pass: trained={int(trained.sum())} of {action.size} "
          f"passes, traces={fleet.traces} host_syncs={fleet.host_syncs}, "
          f"loss {float(loss[trained][0]):.4f} -> "
          f"{float(loss[trained][-1]):.4f}")

    # The host oracle replays the first revolution pass by pass.  The
    # actions, serving satellites and batteries follow from the plan and
    # the energy policy, so all of them must match.  Losses are compared
    # on the first pass, which both engines start from the same weights:
    # ResNet-18 SGD amplifies the rounding differences between the two
    # programs (on CPU, a 1e-6 relative change of the initial weights
    # moves the loss by ~1e-1 after 25 passes), so the later passes'
    # divergence is reported, not compared.
    host = ConstellationSim(adapter, budget, shards, ConstellationConfig(
        n_passes=fleet.rev_len, batch_size=BATCH, seed=seed))
    host.state = SLTrainState.create(*adapter.init(jax.random.key(seed)),
                                     host.optimizer)
    host.run()
    _check([r.action for r in host.records]
           == [ACTION_NAMES[int(a)] for a in res1.action[0]],
           "actions differ from the host oracle")
    _check([r.sat_id for r in host.records] == list(res1.sat[0]),
           "serving satellites differ from the host oracle")
    np.testing.assert_allclose(res1.battery_j[0],
                               [r.battery_j for r in host.records],
                               rtol=BATT_RTOL, atol=BATT_ATOL)
    h_loss = np.array([np.nan if r.loss is None else r.loss
                       for r in host.records])
    on = np.isfinite(h_loss)
    _check(np.array_equal(on, np.isfinite(res1.loss[0])),
           "trained passes differ from the host oracle")
    first = int(np.argmax(on))
    _check(_loss_mismatches(res1.loss[0][first], h_loss[first]) == 0,
           f"first pass loss {res1.loss[0][first]} != host {h_loss[first]}")
    rel = np.abs(res1.loss[0][on] - h_loss[on]) / np.abs(h_loss[on])
    print(f"fleet: first revolution vs host ConstellationSim: actions, "
          f"serving sats and batteries (rtol {BATT_RTOL:g}) equal; first "
          f"pass loss rel err={float(rel[0]):.3g} (rtol {LOSS_RTOL:g}) OK; "
          f"later passes diverge to max rel err={float(rel.max()):.3g}")
    print(f"fleet: bring-up timings, not benchmark numbers: revolution 1 "
          f"incl. trace+compile {t1 - t0:.2f}s, revolution 2 "
          f"{t2 - t1:.2f}s, so compile ~{(t1 - t0) - (t2 - t1):.2f}s")


def phase_fleet4(seed: int) -> None:
    from jax.sharding import AxisType, Mesh

    adapter, shards = _resnet_split()
    budget = PassBudget(plane=OrbitalPlane())
    # one pass per exchange period: the planes train from the same init,
    # all-reduce their checkpoints, train again, and so on, so every
    # pass's loss is compared before SGD amplifies the rounding
    # differences between the two programs (see phase_fleet)
    cfg = FleetConfig(n_planes=4, n_revolutions=3, passes_per_revolution=1,
                      seed=seed, avg_every=1,
                      exchange=ExchangeConfig(mode="sync"))

    def run(mesh):
        t0 = time.perf_counter()
        fleet = FleetEngine(adapter, budget, shards, cfg, mesh=mesh)
        res = fleet.run(stream_telemetry=True)
        _check(fleet.traces == 1 and fleet.host_syncs <= cfg.n_revolutions,
               f"traces={fleet.traces} host_syncs={fleet.host_syncs}")
        # the last exchange left every plane the same averaged weights
        for leaf in jax.tree.leaves((res.state.params_a,
                                     res.state.params_b)):
            leaf = np.asarray(leaf)
            np.testing.assert_allclose(leaf, np.broadcast_to(
                leaf[:1], leaf.shape), rtol=1e-6, atol=1e-7)
        return res, time.perf_counter() - t0

    mesh4 = make_fleet_mesh(4)
    _check(mesh4.devices.size == 4, f"plane mesh has {mesh4.devices.size} "
           "devices, want 4")
    res4, t4 = run(mesh4)
    leaves = jax.tree.leaves(res4.state)
    spread = [len({sh.device for sh in leaf.addressable_shards})
              for leaf in leaves]
    _check(min(spread) == 4, f"state leaves on {min(spread)} devices")
    _check(all(leaf.sharding.shard_shape(leaf.shape)[0] == 1
               for leaf in leaves), "state not split over the plane axis")
    res1, t1 = run(Mesh(np.asarray(jax.devices()[:1]), ("plane",),
                        axis_types=(AxisType.Auto,)))

    _check(np.array_equal(res4.action, res1.action), "actions differ")
    _check(np.array_equal(res4.sat, res1.sat), "serving satellites differ")
    trained = _trained(res4.action)
    _check(trained.all(), "a pass did not train")
    _check(np.all(np.isfinite(res4.loss)), "non-finite loss")
    bad = _loss_mismatches(res4.loss, res1.loss)
    _check(bad == 0, f"{bad} pass losses differ between the meshes")
    np.testing.assert_allclose(res4.isl_bits, res1.isl_bits, rtol=1e-6)
    _check(int(res4.isl_contacts.min()) == cfg.n_revolutions,
           "a plane missed an ISL exchange")
    rel = float(np.max(np.abs(res4.loss - res1.loss) / np.abs(res1.loss)))
    print(f"fleet4: 4 planes x {cfg.n_revolutions} passes, ResNet-18/{IMG} "
          f"cut l2, batch {BATCH}, sync ISL exchange after every pass "
          f"({float(res4.isl_bits.sum()):.4g} wire bits): 4-device mesh vs "
          f"1-device mesh actions equal, max rel loss err={rel:.3g} (rtol "
          f"{LOSS_RTOL:g}), planes equal after the exchange; "
          f"{len(leaves)} state leaves each on {min(spread)} devices OK")
    print(f"fleet4: bring-up timings, not benchmark numbers: 4-device run "
          f"incl. compile {t4:.2f}s, 1-device run incl. compile {t1:.2f}s")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                    "plane-sharded fleet against its 1-device run")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights, data and item budgets")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found "
                         f"{dev.platform} devices only")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices; JAX found {len(devices)}")
    enable_compile_cache()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if args.chips == 4:
        phase_fleet4(args.seed)
    else:
        phase_planner(args.seed)
        phase_kernels(args.seed)
        phase_fleet(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
