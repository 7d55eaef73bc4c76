"""Fleet cells: ``FleetEngine`` trains the split ResNet-18 over the planes
that the traffic mix describes.

The traffic file holds the fleet as data: ``sats_per_plane``; ``fleet``,
fields of the program's ``FleetConfig`` over those that the
configuration's optimizer and deployment give (nested settings such as
``exchange`` or ``scenario`` as objects of their own fields); ``checks``,
the modules under ``checks/`` that hold the run to its references; and
``traced_calls``.

Set-up builds the engine (which plans the passes) and drives its first
revolution through the window's own call, ``run(1,
stream_telemetry=True)``; the window repeats that call for
``--seconds``.  Once the window has closed, each named check compares
what the program produced with its plain reference.  A traced run
profiles window calls 2 to ``traced_calls + 1``, past the first call's
one-off stall, and hands each metric reader the trace, the traced window
and the steps those calls ran.
"""
from __future__ import annotations

import gc
from typing import Dict

import numpy as np

from chipbench import flops, harness, trace as tr
from chipbench.data import Imagery
from chipbench.reference import resnet18 as ref_net

SPANS = ("dispatch", "ring_ingest")


def _budget(cfg: dict, traffic: dict):
    from repro.core.compute_model import DeviceComputeSpec
    from repro.core.energy import PassBudget
    from repro.core.linkbudget import ISLConfig, LinkConfig
    from repro.core.orbits import OrbitalPlane

    dep = cfg["deployment"]
    dev = DeviceComputeSpec(**dep["device"])
    plane = OrbitalPlane(
        n_sats=traffic["sats_per_plane"], altitude_m=dep["altitude_m"],
        min_elevation_rad=float(np.radians(dep["min_elevation_deg"])))
    return PassBudget(plane=plane, link=LinkConfig(**dep["link"]),
                      isl=ISLConfig(**dep["isl"]), sat_device=dev,
                      gs_device=dev, n_items=float(cfg["items_per_pass"]))


def _leaves(params_a, params_b) -> Dict[str, np.ndarray]:
    """Host copies of plane 0's weights, keyed like the reference's."""
    import jax

    take = lambda t: jax.tree.map(lambda x: x[0], t)    # noqa: E731
    return {k: np.asarray(v, np.float32) for k, v in
            ref_net.leaf_paths(take(params_a), "a")
            + ref_net.leaf_paths(take(params_b), "b")}


def fleet_config(cfg: dict, traffic: dict, seed: int):
    """The program's ``FleetConfig``: the configuration's optimizer and
    deployment, then the traffic mix's ``fleet`` fields over them."""
    from repro.fleet import FleetConfig

    opt, dep = cfg["optimizer"], cfg["deployment"]
    base = {"n_revolutions": 1, "lr": opt["lr"],
            "battery_j": dep["battery_j"], "recharge_w": dep["recharge_w"],
            "reserve_j": dep["reserve_j"],
            "max_steps_per_pass": dep["max_steps_per_pass"],
            "min_fraction": dep["min_fraction"], "seed": seed}
    return harness.from_json(FleetConfig, {**base, **traffic["fleet"]})


def build(cfg: dict, traffic: dict, seed: int):
    """The engine, fed by the benchmark's imagery, with weights made on
    the device in one call from the seed; its ``FleetConfig``; and those
    weights."""
    import jax

    from repro.core.sl_step import resnet18_adapter
    from repro.core.train_state import SLTrainState
    from repro.fleet import FleetEngine
    from repro.train.optimizer import resolve_optimizer

    opt_cfg = cfg["optimizer"]
    adapter = resnet18_adapter(cut=cfg["cut_index"], img=cfg["image_size"],
                               n_classes=cfg["num_classes"])
    data = Imagery(img=cfg["image_size"], n_classes=cfg["num_classes"],
                   batch=cfg["batch_size"])
    fcfg = fleet_config(cfg, traffic, seed)
    optimizer = resolve_optimizer("sgd", lr=opt_cfg["lr"],
                                  beta=opt_cfg["momentum"],
                                  grad_clip=opt_cfg["grad_clip"])
    state = jax.jit(lambda key: SLTrainState.create(
        *adapter.init(key), optimizer))(jax.random.key(seed))
    params0 = {k: np.asarray(v, np.float32) for k, v in
               ref_net.leaf_paths(state.params_a, "a")
               + ref_net.leaf_paths(state.params_b, "b")}
    fleet = FleetEngine(adapter, _budget(cfg, traffic), data, fcfg,
                        state=state)
    return fleet, fcfg, params0


def outputs(results, rev_len: int, params0, params1) -> dict:
    """What the checks read from the program: the plan, every pass of
    ``results`` and each leaf's change over the first revolution."""
    cat = lambda f: np.concatenate([f(r) for r in results], 1)  # noqa
    plan = results[0].plan
    return {"plan": {k: np.asarray(getattr(plan, k)) for k in
                     ("n_steps", "feasible")},
            "action": cat(lambda r: r.action), "sat": cat(lambda r: r.sat),
            "n_steps": cat(lambda r: r.n_steps),
            "battery_j": cat(lambda r: r.battery_j),
            "loss": cat(lambda r: r.loss), "rev_len": rev_len,
            "delta_norm": {k: float(np.linalg.norm(params1[k] - params0[k]))
                           for k in params0}}


def record(cfg, traffic, seed, fcfg, out) -> dict:
    """The run record that the checks read (``chipbench/fleet_ref.py``)."""
    return {"cfg": cfg, "traffic": traffic, "seed": seed, "fleet": fcfg,
            "out": out}


def run(ctx: dict) -> dict:
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    checks = harness.Checks(ctx["limits"])

    fleet, fcfg, params0 = build(cfg, traffic, seed)
    if ctx["trace"]:
        ingest = fleet.recorder.ingest

        def spanned_ingest(ring, **kw):
            with harness.span("ring_ingest", True):
                return ingest(ring, **kw)

        fleet.recorder.ingest = spanned_ingest
    first = fleet.run(1, stream_telemetry=True)
    params1 = _leaves(first.state.params_a, first.state.params_b)
    setup_s = harness.now() - ctx["t_start"]

    results = [first]
    prof = harness.Profiler(ctx["trace"])
    traced = traffic["traced_calls"] if ctx["trace"] else 0
    ctx["counter"].on = True
    t0 = harness.now()
    while True:
        if len(results) == 2 and traced:
            prof.start()
        with harness.span("dispatch", prof.on):
            results.append(fleet.run(1, stream_telemetry=True))
        if len(results) - 1 == 1 + traced:
            prof.stop()
        if (harness.now() - t0 >= ctx["seconds"]
                and len(results) - 1 > traced):
            break
    window_s = harness.now() - t0
    ctx["counter"].on = False
    device = harness.device_info(ctx["devices"], ctx["chips"])

    window = results[1:]
    images = sum(int(r.n_steps.sum()) for r in window) * cfg["batch_size"]
    L = fleet.rev_len
    out = outputs(results, L, params0, params1)
    del fleet, results, first, window
    gc.collect()

    checks.add("window_compiles", ctx["counter"].count)
    harness.run_checks(traffic["checks"], record(cfg, traffic, seed, fcfg,
                                                 out), checks)

    n_calls = out["n_steps"].shape[1] // L - 1
    breakdown = None
    if ctx["trace"]:
        trace = tr.load(prof.path, SPANS)
        prof.remove()
        lo, hi = tr.traced_window(trace, "dispatch", "dispatch", traced)
        red = tr.reduce(trace, lo, hi)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        metrics = harness.read_per_layer(ctx["per_layer"], {
            "trace": trace, "window": (lo, hi), "reduced": red,
            "calls": traced, "chips": ctx["chips"], "peaks": ctx["peaks"],
            "planes": fcfg.n_planes, "batch_size": cfg["batch_size"],
            # window calls 2 .. traced + 1, after the set-up revolution
            "n_steps": out["n_steps"][:, 2 * L:(2 + traced) * L],
            "train_flops_per_image": flops.resnet18_train_flops(
                cfg["image_size"], cfg["num_classes"])})
    else:
        metrics = {"train_images_per_s": {"value": images / window_s,
                                          "unit": "images/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    return {"checks": checks, "attempted": n_calls,
            "failed": 0 if checks.correct else n_calls,
            "metrics": metrics, "device": device, "breakdown": breakdown}
