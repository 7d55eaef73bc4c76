"""Every pass the program ran against the reserve-skip policy on the
reference plan (action, serving satellite, steps), and no trained pass
with a loss that is not finite."""
import numpy as np

from chipbench import fleet_ref


def check(run, checks):
    out = run["out"]
    policy = fleet_ref.policy(run, out["action"].shape[1])
    checks.add("policy_mismatch", sum(
        int(np.sum((out["action"][p] != pol["action"])
                   | (out["sat"][p] != pol["sat"])
                   | (out["n_steps"][p] != pol["n_steps"])))
        for p, pol in enumerate(policy)))
    trained = out["n_steps"] > 0
    checks.add("nonfinite_losses",
               int(np.sum(~np.isfinite(out["loss"][trained]))))
