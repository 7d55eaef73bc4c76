"""Tracing inside the program: the registry's host spans in a profiler
trace, the named scopes of the compiled fleet and planner programs, and
the device→host transfer counters."""
import re

import jax
import numpy as np
import pytest

from repro.core import resource_opt_jax as roj
from repro.core.compute_model import DeviceComputeSpec
from repro.core.energy import PassBudget
from repro.core.linkbudget import ISLConfig, LinkConfig
from repro.core.orbits import OrbitalPlane
from repro.core.sl_step import autoencoder_adapter
from repro.fleet import FleetConfig, FleetEngine
from repro.fleet.engine import FleetTelemetry
from repro.obs import MetricsRegistry, TelemetryRing, to_host
from repro.sim.data import DeviceImageryShards
from repro.sim.device_sim import DevicePassPlan
from repro.sim.energy_state import EnergyState

SHARDS = DeviceImageryShards(img=32, batch=4)
ADAPTER = autoencoder_adapter(cut=5, img=32)


@pytest.fixture(scope="module")
def fleet():
    cfg = FleetConfig(battery_j=200.0, recharge_w=0.01, reserve_j=150.0,
                      max_steps_per_pass=2, seed=0)
    return FleetEngine(ADAPTER, PassBudget(plane=OrbitalPlane(n_sats=4),
                                           n_items=16.0), SHARDS, cfg)


def _scopes(hlo_text: str):
    """The ``op_name`` scope paths of a compiled module's instructions."""
    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _under(paths, scope: str) -> int:
    """Instructions whose path holds ``scope`` as a component, bare or
    wrapped by a transform (``jvp(sat_fwd)``)."""
    pat = re.compile(rf"(^|[/(]){re.escape(scope)}($|[/)])")
    return sum(bool(pat.search(p)) for p in paths)


def test_span_records_a_histogram_and_rolls_up():
    parent = MetricsRegistry()
    child = MetricsRegistry("fleet", parent=parent)
    with child.span("launch") as sp:
        pass
    assert sp.seconds is not None and sp.seconds >= 0.0
    assert child.histogram("launch_s").count == 1
    assert parent.histogram("fleet.launch_s").count == 1
    np.testing.assert_allclose(parent.histogram("fleet.launch_s").sum,
                               sp.seconds)


def test_to_host_copies_and_counts_device_arrays():
    reg = MetricsRegistry("fleet")
    tree = {"a": jax.numpy.ones((3, 2), np.float32), "b": np.zeros(4),
            "c": jax.numpy.zeros((5,), np.int32)}
    host = to_host(tree, reg)
    assert all(type(v) is np.ndarray for v in host.values())
    np.testing.assert_array_equal(host["a"], np.ones((3, 2)))
    # host arrays are not transfers; the two device arrays are
    assert reg.counter("d2h_arrays").value == 2
    assert reg.counter("d2h_bytes").value == 3 * 2 * 4 + 5 * 4
    # an array whose host copy JAX keeps (after a copying np.asarray on
    # an accelerator) is not copied again
    kept = jax.numpy.ones((7,), np.float32)
    kept._npy_value = np.ones((7,), np.float32)
    to_host(kept, reg)
    assert reg.counter("d2h_arrays").value == 2


def test_fleet_spans_nest_in_a_profiler_trace(fleet, tmp_path):
    from jax.profiler import ProfileData

    fleet.run(1, stream_telemetry=True)          # revolution 0, untraced
    launches = fleet.metrics.histogram("launch_s").count
    jax.profiler.start_trace(str(tmp_path))
    try:
        fleet.run(2, stream_telemetry=True)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fleet."):
                    events.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                   ev.name, dict(ev.stats)))
    by = lambda n: [e for e in events if e[2] == n]          # noqa: E731
    inside = lambda a, b: b[0] <= a[0] and a[1] <= b[1]      # noqa: E731

    (run,) = by("fleet.run")
    revs = by("fleet.revolution")
    assert [int(r[3]["revolution"]) for r in revs] == [1, 2]
    for name in ("fleet.launch", "fleet.telemetry_sync",
                 "fleet.ring_flush"):
        spans = by(name)
        assert len(spans) == 2
        for span, rev in zip(spans, revs):
            assert inside(span, rev), name
    for rev in revs:
        assert inside(rev, run)
        launch, sync, flush = (next(s for s in by(n) if inside(s, rev))
                               for n in ("fleet.launch",
                                         "fleet.telemetry_sync",
                                         "fleet.ring_flush"))
        assert launch[1] <= sync[0] and sync[1] <= flush[0]
    (result,) = by("fleet.result")
    assert inside(result, run) and result[0] >= revs[-1][1]
    # the histograms count every span, traced or not
    assert fleet.metrics.histogram("launch_s").count == launches + 2
    assert fleet.metrics.histogram("first_launch_s").count == 1
    assert fleet.metrics.histogram("init_s").count == 1
    assert fleet.metrics.histogram("plan_s").count == 1


def test_fleet_program_names_its_phases(fleet):
    paths = _scopes(fleet.lower(1).compile().as_text())
    for scope in ("sat_fwd", "ground", "sat_bwd", "update", "batch",
                  "policy", "ring_record"):
        assert _under(paths, scope) >= 1, scope
    # the satellite VJP transposes the scoped forward
    assert any("sat_bwd/transpose(jvp(sat_fwd))" in p for p in paths)


def test_planner_program_names_its_phases():
    dev = DeviceComputeSpec()
    plane = OrbitalPlane(n_sats=6)
    sc = roj.grid_scalars(plane, LinkConfig(), ISLConfig(), dev, dev)
    cut = np.asarray([[1e8], [2e8]])

    def plan(sc, w1, w2, dtx, disl, items):
        coeffs = roj.ring_pass_coeffs(sc, (2, 6), w1, w2, dtx, disl,
                                      items)
        return roj.shed_and_solve_coeffs(coeffs)

    with roj.x64_scope():
        text = jax.jit(plan).lower(sc, cut, cut, cut * 1e2, cut,
                                   np.full((6,), 100.0)).compile().as_text()
    paths = _scopes(text)
    for scope in ("planner.coeffs", "planner.shed", "planner.solve"):
        assert _under(paths, scope) >= 1, scope


def test_d2h_counters_count_every_copy_of_a_run(fleet):
    before = {n: fleet.metrics.counter(n).value
              for n in ("d2h_arrays", "d2h_bytes", "host_syncs")}
    res = fleet.run(1, stream_telemetry=True)
    spent = {n: fleet.metrics.counter(n).value - v
             for n, v in before.items()}
    # telemetry, ring, plan, energy, the two masks, the three ISL meters
    n_arrays = (len(FleetTelemetry._fields) + len(TelemetryRing._fields)
                + len(DevicePassPlan._fields) + len(EnergyState._fields)
                + 2 + 3)
    assert n_arrays == 31
    assert spent["d2h_arrays"] == n_arrays
    assert spent["host_syncs"] == 1            # one wait, many copies
    P, L = fleet.n_planes, fleet.rev_len
    ring_rows = P * (L + 1)                    # L passes + 1 marker
    ring_bytes = ring_rows * (3 * 4 + 8 * 4) + P * 4
    telem_bytes = sum(getattr(res, f).nbytes for f in FleetTelemetry._fields)
    result_bytes = (sum(a.nbytes for a in res.plan)
                    + sum(a.nbytes for a in res.energy)
                    + res.failed.nbytes + res.fault_ttl.nbytes
                    + res.isl_bits.nbytes + res.isl_e_j.nbytes
                    + res.isl_contacts.nbytes)
    assert spent["d2h_bytes"] == telem_bytes + ring_bytes + result_bytes


def test_plane_exchange_runs_under_its_scope():
    cfg = FleetConfig(n_planes=2, avg_every=1, battery_j=200.0,
                      recharge_w=0.01, reserve_j=150.0,
                      max_steps_per_pass=2, seed=0)
    two = FleetEngine(ADAPTER, PassBudget(plane=OrbitalPlane(n_sats=4),
                                          n_items=16.0), SHARDS, cfg)
    assert _under(_scopes(two.lower(1).compile().as_text()),
                  "isl.exchange") >= 1
