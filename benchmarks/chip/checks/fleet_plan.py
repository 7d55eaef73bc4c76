"""The fleet's plan against problem (13) by the plain reference: every
satellite's planned steps and feasibility."""
import numpy as np

from chipbench import fleet_ref


def check(run, checks):
    want, got = fleet_ref.plan(run), run["out"]["plan"]
    checks.add("plan_steps_mismatch",
               int(np.sum(got["n_steps"] != want["n_steps"])))
    checks.add("plan_feasible_mismatch",
               int(np.sum(got["feasible"] != want["feasible"])))
