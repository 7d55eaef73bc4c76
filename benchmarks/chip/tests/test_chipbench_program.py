"""The reading of the program's own spans, scopes and counters, on the
CPU: synthetic traces for the interval arithmetic, a CPU profiler trace
and a compiled module for the readers of real files."""
import sys
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path[:0] = [str(CHIP), str(ROOT / "src")]

from chipbench import harness, program, trace  # noqa: E402

PLANE = "/device:TPU:0"
PASS_SCOPES = ("sat_fwd", "ground", "sat_bwd", "update")


def _fleet_trace(offset: int = 0):
    """Three runs of the main program, with host spans ``offset`` ns
    behind the device's clock; each boundary: the device idles from the
    run's end to the end of ``telemetry_sync`` (sync), through the ring
    flush, the result and the harness's own code (remainder), to the
    next run's first op inside ``launch``.  A small program runs once
    inside the second ring flush."""
    runs = [(100, 400), (600, 900), (1100, 1400)]
    ops = [(s + 10, e, "fusion.1") for s, e in runs]
    ops += [(s, s + 10, "copy.2") for s, _ in runs]
    ops += [(940, 950, "fusion.9")]
    modules = [(s, e, "jit_closed_loop") for s, e in runs]
    modules += [(940, 950, "jit_ring")]
    host = []                                   # (s, e, name) host clock
    for s, e in runs:
        host += [(s - 50, s + 20, "fleet.launch"),
                 (s + 30, e + 40, "fleet.telemetry_sync"),
                 (e + 40, e + 100, "fleet.ring_flush"),
                 (e + 100, e + 130, "fleet.result")]
    spans = [(a - offset, b - offset, n, {}) for a, b, n in host]
    t = trace.Trace(ops={PLANE: sorted(ops)}, spans=[],
                    modules={PLANE: modules})
    return t, sorted(spans)


BOUNDARY = ("fleet.telemetry_sync", "fleet.ring_flush", "fleet.result",
            "fleet.launch")


def test_boundary_split_partitions_the_program_gaps():
    t, spans = _fleet_trace()
    split, n = program.boundary_split(t, 0, 2000, spans, BOUNDARY, 0.0)
    assert n == 2
    gaps = trace.program_gaps(t, 0, 2000)
    assert sum(split.values()) == sum(gaps)
    assert split == {"fleet.telemetry_sync": 80, "fleet.ring_flush": 110,
                     "fleet.result": 60, "fleet.launch": 100,
                     "remainder": 40}


def test_clock_offset_is_recovered_within_its_bound():
    t, spans = _fleet_trace(offset=1200)
    runs = program.runs_in(t, 0, 2000)
    got, bound = program.clock_offset(spans, runs, "fleet.launch",
                                      "fleet.telemetry_sync")
    assert abs(got - 1200) <= bound
    # launch began 50 ns before each run, the sync ended 40 ns after
    assert bound == pytest.approx((50 + 40) / 2)
    # with the offset, the split matches the one on a shared clock
    moved, _ = program.boundary_split(t, 0, 2000, spans, BOUNDARY, 1200)
    same, _ = program.boundary_split(t, 0, 2000, *_fleet_trace()[1:],
                                     BOUNDARY, 0)
    assert moved == same
    short = [sp for sp in spans if sp != max(
        (x for x in spans if x[2] == "fleet.telemetry_sync"))]
    with pytest.raises(ValueError):
        program.clock_offset(short, runs, "fleet.launch",
                             "fleet.telemetry_sync")


def test_scope_shares_sum_to_the_main_program():
    t = trace.Trace(ops={PLANE: [(0, 10, "fusion.1"), (10, 40, "fusion.2"),
                                 (40, 45, "copy.3"), (45, 60, "fusion.4"),
                                 (0, 60, "while.5"), (70, 75, "fusion.1")]},
                    spans=[], modules={PLANE: [(0, 60, "jit_loop")]})
    paths = {"fusion.1": "jit(loop)/while/body/jvp(sat_fwd)/conv",
             "fusion.2": "jit(loop)/while/body/sat_bwd/"
                         "transpose(jvp(sat_fwd))/conv",
             "fusion.4": "jit(loop)/while/body/update/sub"}
    ns = program.scope_ns(t, 0, 100, paths, PASS_SCOPES)
    assert ns == {"sat_fwd": 10, "sat_bwd": 30, None: 5, "update": 15}
    total = sum(n for _, n in trace.op_runs(t, 0, 100).values())
    shares = [100.0 * v / total for v in ns.values()]
    assert sum(shares) == pytest.approx(100.0)


def test_scope_of_takes_the_outermost_scope():
    assert program.scope_of("a/sat_bwd/transpose(jvp(sat_fwd))/mul",
                            PASS_SCOPES) == "sat_bwd"
    assert program.scope_of("a/jvp(sat_fwd)/tanh", PASS_SCOPES) == "sat_fwd"
    assert program.scope_of("a/ground/jvp()/dot", PASS_SCOPES) == "ground"
    assert program.scope_of("jit(p)/planner.solve/while",
                            ("planner.solve",)) == "planner.solve"
    assert program.scope_of("a/sat_fwd_extra/x", PASS_SCOPES) is None


def test_op_scopes_come_from_the_compiled_text():
    import jax
    import jax.numpy as jnp

    def step(w, x):
        with jax.named_scope("ground"):
            y = jnp.tanh(x @ w)
        with jax.named_scope("update"):
            return w - 0.1 * (y.T @ x)

    text = jax.jit(step).lower(jnp.ones((4, 4)),
                               jnp.ones((2, 4))).compile().as_text()
    paths = program.op_scopes_from_hlo(text)
    found = {program.scope_of(p, PASS_SCOPES) for p in paths.values()}
    assert {"ground", "update"} <= found
    line = ('  ROOT %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(s)/update/sub" '
            'source_file="x.py" source_line=3}')
    assert program.op_scopes_from_hlo(line) == {"fusion.7":
                                                "jit(s)/update/sub"}


def test_program_spans_are_read_from_a_profiler_trace(tmp_path):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("fleet.run"):
            for i in range(2):
                with jax.profiler.TraceAnnotation("fleet.revolution",
                                                  revolution=4 + i):
                    with jax.profiler.TraceAnnotation("fleet.launch"):
                        pass
        with jax.profiler.TraceAnnotation("dispatch"):
            pass
    finally:
        jax.profiler.stop_trace()
    spans = program.load_spans(tmp_path, "fleet.")
    names = [n for _, _, n, _ in spans]
    assert names.count("fleet.launch") == 2 and "dispatch" not in names
    assert [k["revolution"] for _, _, n, k in spans
            if n == "fleet.revolution"] == [4, 5]
    (run,) = [(s, e) for s, e, n, _ in spans if n == "fleet.run"]
    assert all(run[0] <= s and e <= run[1] for s, e, _, _ in spans)


@pytest.mark.parametrize("metric,snapshot,want", [
    ("fleet.d2h_arrays_per_rev",
     {"fleet.d2h_arrays": 93, "fleet.device_calls": 3}, 31.0),
    ("setup.fleet_init_s", {"fleet.init_s": {"count": 1, "sum": 2.5}}, 2.5),
    ("setup.first_launch_s",
     {"fleet.first_launch_s": {"count": 2, "sum": 1.25}}, 1.25),
])
def test_registry_readers_read_the_program_or_nothing(monkeypatch, metric,
                                                      snapshot, want):
    read = harness.load_module(CHIP / "metrics" / f"{metric}.py",
                               "m_" + metric.replace(".", "_")).read
    monkeypatch.setattr(program, "registry", lambda: snapshot)
    assert read({}) == pytest.approx(want)
    # a program that keeps no such counter or span reads nothing
    monkeypatch.setattr(program, "registry",
                        lambda: {"fleet.device_calls": 3})
    assert read({}) is None


def test_histogram_sum_needs_samples():
    assert program.histogram_sum({"a": {"count": 0}}, "a") is None
    assert program.histogram_sum({}, "a") is None
    assert np.isclose(program.histogram_sum({"a": {"count": 3, "sum": 1.5}},
                                            "a"), 1.5)
