"""Plane 0's first revolution against plain ResNet-18 split SGD (float32
at ``highest``): the first pass's loss, the worst of the first three,
and each weight leaf's change over the revolution, by the worst leaf and
the median leaf.  The reference training is kept in the run record as
``train_ref``; a record that brings one is compared with it."""
import numpy as np

from chipbench import fleet_ref


def check(run, checks):
    out = run["out"]
    if run.get("train_ref") is None:
        run["train_ref"] = fleet_ref.training(run)
    ref = run["train_ref"]
    L = out["rev_len"]
    losses = out["loss"][0, :L][out["n_steps"][0, :L] > 0]
    ref_loss = np.asarray(ref["pass_loss"])
    loss_gaps = np.abs(losses - ref_loss) / np.abs(ref_loss)
    checks.add("loss_gap_first", float(loss_gaps[0]))
    checks.add("loss_gap_3", float(np.max(loss_gaps[:3])))
    gaps = fleet_ref.leaf_gaps(out["delta_norm"], ref)
    checks.add("delta_gap", max(gaps.values()))
    checks.add("delta_gap_median", float(np.median(list(gaps.values()))))
