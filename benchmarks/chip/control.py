"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        [--controls 1,2,3]

For every seed of ``--seeds`` it runs the program as the cell's set-up
does and prints, as one JSON line, each number the cell compares against
its reference (the lower readings).  For every seed of ``--controls`` it
also puts in the program's place

* ``control``: the reference computed one precision down (the fleet's
  ResNet-18 in bfloat16, the planner in float32), and, for fleet cells,
* ``half_batch``: the reference training on half of each batch,
* ``default``: the float32 reference at the default matmul precision
  (one bfloat16 pass, as the program's): a second witness of what
  rounding alone does to each number, not a control,

and prints the same numbers for each (the upper readings).  A fleet
cell's third fault, a step that returns its state unchanged, reads 1 on
``delta_gap`` by construction.  Needs the cell's chips, as ``run.py``.
"""
import argparse
import json
import math
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class Readings:
    """A ``Checks`` stand-in that records every number and fails none."""

    def __init__(self):
        self.values = {}

    def add(self, name, value):
        self.values[name] = float(value)


def fleet_readings(drv, cfg, traffic, seed, controls: bool):
    import jax.numpy as jnp
    import numpy as np

    from chipbench import fleet_ref, harness

    fleet, fcfg, params0 = drv.build(cfg, traffic, seed)
    first = fleet.run(1, stream_telemetry=True)
    params1 = drv._leaves(first.state.params_a, first.state.params_b)
    out = drv.outputs([first], fleet.rev_len, params0, params1)
    del fleet, first
    rows = {}
    prog = Readings()
    run = drv.record(cfg, traffic, seed, fcfg, out)
    harness.run_checks(traffic["checks"], run, prog)
    rows["program"] = prog.values
    ref = run["train_ref"]
    gaps = fleet_ref.leaf_gaps(out["delta_norm"], ref)
    worst = sorted(gaps, key=gaps.get)[-3:]
    print(f"seed {seed}: worst leaves {[(k, gaps[k]) for k in worst]}",
          file=sys.stderr)
    if controls:
        L = out["rev_len"]
        for name, kw in (("control", {"dtype": jnp.bfloat16,
                                      "precision": "default"}),
                         ("half_batch", {"half_batch": True}),
                         ("default", {"precision": "default"})):
            alt = fleet_ref.training(run, **kw)
            loss = np.array(out["loss"], copy=True)
            loss[0, np.flatnonzero(out["n_steps"][0, :L] > 0)] = \
                alt["pass_loss"]
            r = Readings()
            harness.run_checks(["fleet_training"], dict(run, out=dict(
                out, loss=loss, delta_norm=alt["delta_norm"])), r)
            rows[name] = r.values
    return rows


def plan_readings(drv, cfg, traffic, seed, controls: bool):
    import numpy as np

    _, n = drv.instances(cfg, traffic)
    items = drv.draw_items(np.random.default_rng(seed), n, traffic)
    call = drv.build(cfg, traffic)
    want = drv.reference(cfg, traffic, items)
    rows = {"program": drv.gaps(call(items), want)}
    if controls:
        low = drv.reference(cfg, traffic, items, np.float32)
        low = {k: (v if k == "feasible" else np.asarray(v, np.float64))
               for k, v in low.items()}
        low["e_isl"] = low["e_total"] - low["phase_energy"].sum(-1)
        rows["control"] = drv.gaps(low, want)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(HERE), str(ROOT / "src")]
    from chipbench import harness

    run = harness.load_module(HERE / "run.py", "chipbench_run")
    _, cell, cfg, traffic = run.load_cell(args.workload)
    run.enable_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    drv = harness.load_module(HERE / "drivers" / f"{traffic['driver']}.py",
                              "chipbench_driver_" + traffic["driver"])
    readings = {"fleet": fleet_readings, "plan": plan_readings}[
        traffic["driver"]]
    controls = {int(s) for s in args.controls.split(",") if s}
    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds + sorted(controls - set(seeds)):
        rows = readings(drv, cfg, traffic, harness.derive_seed(seed),
                        seed in controls)
        for variant, values in rows.items():
            print(json.dumps({"cell": cell["name"], "seed": seed,
                              "variant": variant, **{
                                  k: (v if math.isfinite(v) else str(v))
                                  for k, v in values.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
