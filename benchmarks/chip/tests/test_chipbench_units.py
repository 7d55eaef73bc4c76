"""The benchmark's yardstick on the CPU: trace reduction, FLOP count,
peaks, manifest rules, and the copies that must match the program."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

from chipbench import flops, manifest, trace  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402


def _trace():
    # two device ops with a 30 ns gap inside a "dispatch" span, a 50 ns
    # gap across the boundary between two dispatches, idle at both ends
    ops = {"/device:TPU:0": [(10, 40, "conv"), (20, 50, "conv"),
                             (80, 100, "add"), (150, 190, "conv")]}
    spans = [(0, 200, "window"), (5, 110, "dispatch"),
             (110, 130, "ring_ingest"), (140, 195, "dispatch")]
    return trace.Trace(ops=ops, spans=spans)


def test_union_busy_and_gaps():
    merged = trace.union([(10, 40), (20, 50), (80, 100), (150, 190)])
    assert merged == [(10, 50), (80, 100), (150, 190)]
    assert trace.busy_ns(merged, 0, 200) == 100
    assert trace.busy_ns(merged, 30, 160) == 20 + 20 + 10
    assert trace.idle_gaps(merged, 0, 200) == [(0, 10), (50, 80),
                                               (100, 150), (190, 200)]


def test_reduce_labels_gaps_by_innermost_span():
    red = trace.reduce(_trace(), 0, 200)
    assert red["busy_s"] == pytest.approx(100e-9)
    assert red["window_s"] == pytest.approx(200e-9)
    assert red["idle_share"] == pytest.approx(0.5)
    assert [g[0] for g in red["idle_gaps"]] == [
        "ring_ingest", "dispatch", "dispatch", "window"]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [50e-9, 30e-9, 10e-9, 10e-9])
    assert red["device_ops"][0] == ["conv", pytest.approx(100e-9)]
    assert red["device_ops"][1] == ["add", pytest.approx(20e-9)]


def test_program_gaps_between_executions_of_the_main_program():
    t = _trace()
    # the main program runs 10-100 and 150-190; a small one runs in the
    # gap for 5 ns and is busy time, not idle
    t.ops["/device:TPU:0"].append((120, 125, "copy"))
    t.modules = {"/device:TPU:0": [(10, 100, "jit_loop"),
                                   (120, 125, "jit_ring"),
                                   (150, 190, "jit_loop")]}
    assert trace.program_gaps(t, 0, 200) == [50 - 5]
    # a window that holds one execution has no gap
    assert trace.program_gaps(t, 0, 110) == []


def test_op_runs_count_the_main_program_steps():
    t = _trace()
    t.ops["/device:TPU:0"] += [(120, 125, "conv"), (60, 70, "while.3")]
    t.modules = {"/device:TPU:0": [(10, 100, "jit_loop"),
                                   (120, 125, "jit_ring"),
                                   (150, 190, "jit_loop")]}
    # the ring program's "conv" and the loop's control flow are not steps
    assert trace.op_runs(t, 0, 200) == {"conv": (3, 100), "add": (1, 20)}
    assert trace.op_runs(t, 140, 200) == {"conv": (1, 40)}


def test_useful_step_share_reads_steps_from_the_trace():
    from chipbench import harness

    t = _trace()
    t.modules = {"/device:TPU:0": [(10, 100, "jit_loop"),
                                   (150, 190, "jit_loop")]}
    read = harness.load_module(
        CHIP / "metrics/pass_step.useful_step_share.py", "m_share").read
    # "conv" ran 3 times for 2 planes: 6 steps scanned, 4 planned
    ctx = {"trace": t, "window": (0, 200), "planes": 2,
           "n_steps": np.array([[1, 1], [2, 0]])}
    assert read(ctx) == pytest.approx(100.0 * 4 / 6)
    assert read({"reduced": {}}) is None


def test_traced_window_needs_one_main_run_per_call():
    t = _trace()
    t.modules = {"/device:TPU:0": [(10, 100, "jit_loop"),
                                   (150, 190, "jit_loop")]}
    assert trace.traced_window(t, "dispatch", "dispatch", 2) == (5, 195)
    with pytest.raises(RuntimeError):
        trace.traced_window(t, "dispatch", "dispatch", 3)


def test_op_names_drop_the_hlo_text():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%p)") == "fusion.12"


def test_from_json_builds_nested_dataclasses():
    import dataclasses
    from typing import Dict, Optional, Tuple

    from chipbench import harness

    @dataclasses.dataclass(frozen=True)
    class Inner:
        offsets: Tuple[int, ...] = (1,)

    @dataclasses.dataclass
    class Outer:
        n: int = 1
        inner: Optional[Inner] = None
        events: Dict[int, int] = dataclasses.field(default_factory=dict)

    got = harness.from_json(Outer, {"n": 4, "inner": {"offsets": [1, 2]},
                                    "events": {"3": 1}})
    assert got == Outer(n=4, inner=Inner(offsets=(1, 2)), events={3: 1})
    assert harness.from_json(Outer, {"inner": None}).inner is None


def test_fleet_traffic_builds_the_program_config():
    from chipbench import harness

    drv = harness.load_module(CHIP / "drivers/fleet.py", "chipbench_fleet")
    cfg = json.loads((CHIP / "configs/resnet18_in1k_224.json").read_text())
    traffic = json.loads((CHIP / "traffic/fleet_p1x25.json").read_text())
    traffic = dict(traffic, fleet=dict(
        traffic["fleet"], n_planes=4,
        exchange={"mode": "sync", "codec": {"scheme": "int8"}},
        scenario={"eclipse": {"period": 10, "duty": 0.4}}))
    fc = drv.fleet_config(cfg, traffic, 7)
    assert (fc.n_planes, fc.seed, fc.lr) == (4, 7, 0.01)
    assert fc.battery_j == cfg["deployment"]["battery_j"]
    assert fc.exchange.mode == "sync"
    assert fc.exchange.codec.scheme == "int8"
    assert fc.scenario.eclipse.duty == 0.4


def test_resnet18_forward_flops():
    # He et al. 2016, Table 1: 1.8 GMAC at 224x224 (1000 classes)
    fwd = flops.resnet18_fwd_flops(224, 1000)
    assert fwd == pytest.approx(2 * 1.82e9, rel=0.01)
    assert flops.resnet18_train_flops(224, 1000) == 3 * fwd
    # the l2 cut sends 28x28x128 activations of 32 bits a value
    w1, w2, dtx, _ = flops.resnet18_cut_costs(224, 1000)[4]
    assert dtx == 28 * 28 * 128 * 32
    assert w1 + w2 == pytest.approx(3 * fwd)


def test_peaks_lookup():
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v99")


def test_manifest_names_units_and_files():
    man = manifest.load(ROOT)
    assert manifest.problems(man) == []
    for c in man["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in man["workloads"]:
        traffic = json.loads(
            (CHIP / "traffic" / f"{w['traffic']}.json").read_text())
        assert (CHIP / "drivers" / f"{traffic['driver']}.py").is_file()
        for check in traffic.get("checks", []):
            assert (CHIP / "checks" / f"{check}.py").is_file()
        assert (CHIP / "limits" / f"{w['name']}.json").is_file()
    for m in man["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("field,value,needle", [
    ("name", "bad name", "bad name"),
    ("name", "x" * 65, "bad name"),
    ("unit", "tokens per second", "bad unit"),
    ("unit", "µs", "bad unit"),
    ("better", "up", "better"),
    ("source", "program_span", "bad source"),
])
def test_manifest_rules_catch_breaches(field, value, needle):
    man = manifest.load(ROOT)
    man["end_to_end"][0][field] = value
    assert any(needle in p for p in manifest.problems(man))


def test_data_copy_matches_program_generator():
    from chipbench.data import Imagery
    from repro.sim.data import DeviceImageryShards

    ours = Imagery(img=16, n_classes=10, batch=3, seed=7)
    theirs = DeviceImageryShards(img=16, n_classes=10, batch=3, seed=7)
    for sat, idx in ((0, 0), (4, 11)):
        a, b = ours(sat, idx), theirs(sat, idx)
        np.testing.assert_array_equal(a["labels"], b["labels"])
        np.testing.assert_array_equal(a["images"], b["images"])


def test_reference_weights_match_program_init():
    import jax

    from chipbench.reference import resnet18 as ref
    from repro.core.sl_step import resnet18_adapter

    pa, pb = resnet18_adapter(cut=5, img=32, n_classes=10).init(
        jax.random.key(3))
    ra, rb = ref.split(ref.init(jax.random.key(3), 10), 5)
    got = dict(ref.leaf_paths(pa, "a") + ref.leaf_paths(pb, "b"))
    want = dict(ref.leaf_paths(ra, "a") + ref.leaf_paths(rb, "b"))
    assert sorted(got) == sorted(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
    assert ref.param_bits(10, 5) == 32 * sum(
        x.size for x in jax.tree.leaves(pa))


def test_run_without_a_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload",
         "plan.in1k224.shell1584", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "TPU" in proc.stderr


def test_limits_are_numbers_for_every_compared_name():
    for f in (CHIP / "limits").glob("*.json"):
        limits = json.loads(f.read_text())
        assert limits["window_compiles"] == 0
        assert all(isinstance(v, (int, float)) and v >= 0
                   for v in limits.values())
