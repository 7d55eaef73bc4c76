"""What a fleet run should have produced, by the plain references.

Each function reads a run record, the dict that ``drivers/fleet.py``
builds and hands to the checks under ``checks/``: the configuration
(``cfg``), the traffic mix (``traffic``), the fleet's settings as the
program got them (``fleet``, a ``FleetConfig``: only its numbers are
read), the run's seed and the program's outputs (``out``).  Nothing here
imports the program.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from chipbench import flops
from chipbench.data import Imagery
from chipbench.reference import planner as ref_planner
from chipbench.reference import policy as ref_policy
from chipbench.reference import resnet18 as ref_net


def plan(run: dict, dtype=np.float64) -> dict:
    """The fleet's plan rows by the problem-(13) reference."""
    cfg, fc, traffic = run["cfg"], run["fleet"], run["traffic"]
    img, nc, cut = cfg["image_size"], cfg["num_classes"], cfg["cut_index"]
    w1, w2, dtx, _ = flops.resnet18_cut_costs(img, nc)[cut - 1]
    sats = traffic["sats_per_plane"]
    co = ref_planner.coefficients(
        cfg["deployment"], sats, w1, w2, dtx, ref_net.param_bits(nc, cut),
        np.full((fc.n_planes, sats), cfg["items_per_pass"]), dtype)
    sol = ref_planner.solve(co, fc.min_fraction)
    kept = sol["kept_fraction"] * cfg["items_per_pass"]
    steps = np.maximum(np.round(kept / cfg["batch_size"]), 1)
    steps = np.minimum(steps, fc.max_steps_per_pass).astype(np.int32)
    e = sol["phase_energy"]
    return {"n_steps": steps, "kept_fraction": sol["kept_fraction"],
            "feasible": sol["feasible"],
            "drain_j": e[..., 0] + e[..., 1] + co["e_isl"]}


def policy(run: dict, n_passes: int):
    """Each plane's first ``n_passes`` passes by the reserve-skip policy on
    the reference plan."""
    cfg, fc, traffic = run["cfg"], run["fleet"], run["traffic"]
    dep, sats = cfg["deployment"], traffic["sats_per_plane"]
    rp = plan(run)
    geo = ref_planner.plane_geometry(dep["altitude_m"],
                                     dep["min_elevation_deg"], sats)
    return [ref_policy.run(n_passes, sats, rp["drain_j"][p],
                           rp["kept_fraction"][p], rp["n_steps"][p],
                           fc.battery_j, fc.recharge_w * geo["pass_s"],
                           fc.reserve_j)
            for p in range(fc.n_planes)]


def training(run: dict, **kw) -> dict:
    """Plain ResNet-18 over plane 0's first revolution as the reference
    policy schedules it: each trained pass's satellite, batches and
    steps, from batch index 0."""
    cfg, L = run["cfg"], run["out"]["rev_len"]
    pol = policy(run, L)[0]
    opt = cfg["optimizer"]
    passes, idx = [], 0
    for sat, n in zip(pol["sat"], pol["n_steps"]):
        if n:
            passes.append((int(sat), idx, int(n)))
        idx += int(n)
    return ref_net.train(
        Imagery(img=cfg["image_size"], n_classes=cfg["num_classes"],
                batch=cfg["batch_size"]),
        seed=run["seed"], n_classes=cfg["num_classes"],
        cut=cfg["cut_index"], lr=opt["lr"], momentum=opt["momentum"],
        grad_clip=opt["grad_clip"], passes=passes, **kw)


def leaf_gaps(prog: Dict[str, float], ref: dict) -> Dict[str, float]:
    """Each leaf's gap between the two norms of its change, over the
    larger of the reference's norm of that leaf and of the median leaf.
    Leaves whose first reference gradient is under a thousandth of the
    median leaf's move by rounding alone and are left out."""
    g = ref["first_grad_norm"]
    g_med = float(np.median(list(g.values())))
    keep = [k for k in g if g[k] >= 1e-3 * g_med]
    d_ref = ref["delta_norm"]
    d_med = float(np.median([d_ref[k] for k in keep]))
    return {k: abs(prog[k] - d_ref[k]) / max(d_ref[k], d_med) for k in keep}
