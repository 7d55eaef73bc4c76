"""A run's ``correct`` on the CPU at a small size: true for the program
as it is, false with the timed path broken underneath or with the
one-precision-down control in the program's place.

Each test skips only the harness's look for a chip: it builds the run's
context as ``run.py`` does, with the cell's own limits, and drives the
cell's driver.
"""
import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

FLEET_CELL = "fleet.in1k224.p1x25"
PLAN_CELL = "plan.in1k224.shell1584"


def _small_fleet():
    cfg = json.loads((CHIP / "configs/resnet18_in1k_224.json").read_text())
    cfg.update(image_size=32, num_classes=10, batch_size=4,
               items_per_pass=8)
    traffic = json.loads((CHIP / "traffic/fleet_p1x25.json").read_text())
    traffic.update(sats_per_plane=3)
    return cfg, traffic


def _small_plan():
    cfg = json.loads((CHIP / "configs/resnet18_in1k_224.json").read_text())
    traffic = json.loads((CHIP / "traffic/plan_shell1584.json").read_text())
    traffic.update(planes=4)
    return cfg, traffic


def _drive(cell, cfg, traffic, seed=2 ** 31 + 11):
    """``run.py`` for ``cell`` at the given sizes, past its look for chips,
    on this process's devices."""
    import jax

    run = harness.load_module(CHIP / "run.py", "chipbench_run")
    man, entry, _, _ = run.load_cell(cell)
    return run.run_cell(man, entry, cfg, traffic, seed=seed, seconds=0.2,
                        trace=False, devices=jax.devices(), peaks={},
                        t_start=time.perf_counter())["checks"]


def _break_pass_step(monkeypatch, fault):
    from repro.fleet import engine

    orig = engine.make_pass_step

    def broken(*args, **kwargs):
        step = orig(*args, **kwargs)

        def pass_step(state, batch, valid):
            if fault == "half_batch":
                half = batch["labels"].shape[0] // 2
                return step(state, {k: v[:half] for k, v in batch.items()},
                            valid)
            new, loss = step(state, batch, valid)
            if fault == "state_unchanged":
                return state, loss
            return new, loss * 1.01                  # answer altered

        return pass_step

    monkeypatch.setattr(engine, "make_pass_step", broken)


def test_fleet_sound_run_is_correct():
    checks = _drive(FLEET_CELL, *_small_fleet())
    assert checks.correct, checks.failed()


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fleet_fault_is_not_correct(monkeypatch, fault):
    _break_pass_step(monkeypatch, fault)
    checks = _drive(FLEET_CELL, *_small_fleet())
    assert not checks.correct


def test_fleet_control_fails_where_the_program_passes():
    control = harness.load_module(CHIP / "control.py", "chipbench_control")
    drv = harness.load_module(CHIP / "drivers/fleet.py", "chipbench_fleet")
    limits = harness.limits_for(FLEET_CELL)
    rows = control.fleet_readings(drv, *_small_fleet(),
                                  harness.derive_seed(5), True)

    def fails(values):
        return [k for k, v in values.items() if not v <= limits[k]]

    assert fails(rows["program"]) == []
    assert "delta_gap" in fails(rows["control"])
    assert fails(rows["half_batch"])


def test_plan_sound_run_is_correct():
    checks = _drive(PLAN_CELL, *_small_plan())
    assert checks.correct, checks.failed()


def test_plan_answer_altered_is_not_correct(monkeypatch):
    from repro.core import resource_opt_jax as roj

    orig = roj.shed_and_solve_coeffs

    def altered(*args, **kwargs):
        rep, frac = orig(*args, **kwargs)
        # one instance's plan costs 0.1% more energy than it should
        pe = rep.phase_energy.at[0, 0, :].multiply(1.001)
        return rep._replace(phase_energy=pe), frac

    monkeypatch.setattr(roj, "shed_and_solve_coeffs", altered)
    checks = _drive(PLAN_CELL, *_small_plan())
    assert not checks.correct


def test_plan_control_fails_where_the_program_passes():
    control = harness.load_module(CHIP / "control.py", "chipbench_control")
    drv = harness.load_module(CHIP / "drivers/plan.py", "chipbench_plan")
    limits = harness.limits_for(PLAN_CELL)
    rows = control.plan_readings(drv, *_small_plan(), 9, True)
    assert all(v <= limits[k] for k, v in rows["program"].items())
    assert any(not v <= limits[k] for k, v in rows["control"].items())
    assert np.isfinite(list(rows["control"].values())).all()
