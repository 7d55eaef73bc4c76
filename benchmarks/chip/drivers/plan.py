"""Planner cells: problem (13) for every cut of a model across a shell.

Each call sheds and solves ``cuts x planes x sats_per_plane`` instances
(``ring_pass_coeffs`` + ``shed_and_solve_coeffs`` under the planner's
float64 scope) for item budgets drawn afresh from the seed, and ends when
its results are on the host.  Set-up puts the scenario's constants on
the device, compiles (or loads) the call and makes one; the window
repeats it for ``--seconds``.  Once the window has closed, a sample of
the window's calls, drawn from the seed, is solved again by the plain
reference (``reference/planner``, float64).  A traced run profiles
window calls 2 to ``traced_calls + 1``, past the first call.
"""
from __future__ import annotations

import numpy as np

from chipbench import flops, harness, trace as tr
from chipbench.reference import planner as ref_planner

SPANS = ("draw_items", "planner_call")


def instances(cfg: dict, traffic: dict):
    """Per-cut costs ``(C, 1)`` and the shell's size."""
    cuts = np.asarray(flops.resnet18_cut_costs(cfg["image_size"],
                                               cfg["num_classes"]))
    n = traffic["planes"] * traffic["sats_per_plane"]
    return [cuts[:, i:i + 1] for i in range(4)], n


def draw_items(rng, n: int, traffic: dict) -> np.ndarray:
    lo, hi = np.log(traffic["items_min"]), np.log(traffic["items_max"])
    return np.exp(rng.uniform(lo, hi, n))


def build(cfg: dict, traffic: dict):
    """The jitted planner call and its scenario constants."""
    import jax

    from repro.core import resource_opt_jax as roj
    from repro.core.compute_model import DeviceComputeSpec
    from repro.core.linkbudget import ISLConfig, LinkConfig
    from repro.core.orbits import OrbitalPlane

    dep = cfg["deployment"]
    dev = DeviceComputeSpec(**dep["device"])
    plane = OrbitalPlane(
        n_sats=traffic["sats_per_plane"], altitude_m=traffic["altitude_m"],
        min_elevation_rad=float(np.radians(dep["min_elevation_deg"])))
    sc = roj.grid_scalars(plane, LinkConfig(**dep["link"]),
                          ISLConfig(**dep["isl"]), dev, dev)
    cuts, n = instances(cfg, traffic)
    shape = (cuts[0].shape[0], n)
    ring_n = traffic["sats_per_plane"]
    with roj.x64_scope():
        consts = jax.device_put((sc, *cuts))

    @jax.jit
    def plan(sc, w1, w2, dtx, disl, items):
        coeffs = roj.ring_pass_coeffs(sc, shape, w1, w2, dtx, disl, items,
                                      ring_n=ring_n)
        return roj.shed_and_solve_coeffs(coeffs, dep["min_fraction"])

    def call(items):
        with roj.x64_scope():
            rep, frac = jax.device_get(plan(*consts, items))
        return {"kept_fraction": frac, "phase_times": rep.phase_times,
                "phase_energy": rep.phase_energy, "e_isl": rep.e_isl,
                "feasible": rep.feasible}

    return call


def reference(cfg: dict, traffic: dict, items, dtype=np.float64) -> dict:
    (w1, w2, dtx, disl), _ = instances(cfg, traffic)
    dep = dict(cfg["deployment"], altitude_m=traffic["altitude_m"])
    co = ref_planner.coefficients(dep, traffic["sats_per_plane"], w1, w2,
                                  dtx, disl, items[None, :], dtype)
    sol = ref_planner.solve(co, dep["min_fraction"])
    sol["t_budget"] = co["t_budget"]
    return sol


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared between one call and its reference."""
    feas = want["feasible"]
    both = feas & got["feasible"]
    budget = np.broadcast_to(want["t_budget"], feas.shape)[both]
    e_ref = want["e_total"][both]
    e_got = (got["phase_energy"].sum(-1) + got["e_isl"])[both]
    return {
        "feasible_mismatch": int(np.sum(got["feasible"] != feas)),
        "frac_gap": float(np.max(np.abs(got["kept_fraction"]
                                        - want["kept_fraction"]))),
        "time_gap": float(np.max(np.abs(got["phase_times"][both]
                                        - want["phase_times"][both])
                                 / budget[:, None])),
        "energy_gap": float(np.max(np.abs(e_got - e_ref) / np.abs(e_ref))),
    }


class Sample:
    """A seeded uniform sample of ``k`` of the window's calls, kept as
    they come (reservoir sampling), so the window holds no more."""

    def __init__(self, k: int, seed: int):
        self.k, self.n, self.kept = k, 0, []
        self.rng = np.random.default_rng([seed, 1])

    def offer(self, call) -> None:
        if len(self.kept) < self.k:
            self.kept.append(call)
        else:
            j = self.rng.integers(0, self.n + 1)
            if j < self.k:
                self.kept[j] = call
        self.n += 1


def compare(cfg, traffic, calls, checks: harness.Checks) -> None:
    """Hold ``calls`` = ``[(items, result)]`` to the reference: the worst
    reading of each number over them."""
    worst: dict = {}
    for items, got in calls:
        for k, v in gaps(got, reference(cfg, traffic, items)).items():
            worst[k] = max(worst.get(k, 0), v)
    for k, v in worst.items():
        checks.add(k, v)


def run(ctx: dict) -> dict:
    cfg, traffic, seed = ctx["cfg"], ctx["traffic"], ctx["seed"]
    checks = harness.Checks(ctx["limits"])
    rng = np.random.default_rng(seed)
    (_, n) = instances(cfg, traffic)
    call = build(cfg, traffic)
    call(draw_items(rng, n, traffic))
    setup_s = harness.now() - ctx["t_start"]

    sample = Sample(traffic["checked_calls"], seed)
    prof = harness.Profiler(ctx["trace"])
    traced = traffic["traced_calls"] if ctx["trace"] else 0
    ctx["counter"].on = True
    t0 = harness.now()
    while True:
        if sample.n == 1 and traced:
            prof.start()
        with harness.span("draw_items", prof.on):
            items = draw_items(rng, n, traffic)
        with harness.span("planner_call", prof.on):
            sample.offer((items, call(items)))
        if sample.n == 1 + traced:
            prof.stop()
        if harness.now() - t0 >= ctx["seconds"] and sample.n > traced:
            break
    window_s = harness.now() - t0
    ctx["counter"].on = False
    device = harness.device_info(ctx["devices"], ctx["chips"])

    checks.add("window_compiles", ctx["counter"].count)
    compare(cfg, traffic, sample.kept, checks)
    per_call = sample.kept[0][1]["kept_fraction"].size
    metrics, breakdown = {}, None
    if ctx["trace"]:
        trace = tr.load(prof.path, SPANS)
        prof.remove()
        lo, hi = tr.traced_window(trace, "draw_items", "planner_call",
                                  traced)
        red = tr.reduce(trace, lo, hi)
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        metrics = harness.read_per_layer(ctx["per_layer"], {
            "trace": trace, "window": (lo, hi), "reduced": red,
            "calls": traced, "chips": ctx["chips"], "peaks": ctx["peaks"]})
    else:
        metrics = {"plan_instances_per_s": {
                       "value": per_call * sample.n / window_s,
                       "unit": "instances/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}
    return {"checks": checks, "attempted": sample.n,
            "failed": 0 if checks.correct else sample.n,
            "metrics": metrics, "device": device, "breakdown": breakdown}
