"""Sharded elastic fleet engine: event-schedule semantics, host-vs-fleet
parity for join/leave/seeded-failure runs, inter-plane checkpoint
averaging, the <=1-sync-per-revolution contract, and plane sharding on a
multi-CPU-device mesh (subprocess)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.constellation import ConstellationConfig, ConstellationSim
from repro.core.energy import PassBudget
from repro.core.orbits import OrbitalPlane
from repro.core.sl_step import autoencoder_adapter, make_sl_pass
from repro.core.train_state import SLTrainState
from repro.fleet import (FleetConfig, FleetEngine, average_planes,
                         build_event_schedule)
from repro.sim.data import DeviceImageryShards
from repro.sim.device_sim import (ACTION_NAMES, ACTION_TRAINED,
                                  DeviceConstellationSim, DeviceSimConfig,
                                  plan_ring_passes)
from repro.train.optimizer import resolve_optimizer
from repro.utils.bucketing import bucket_size

SHARDS = DeviceImageryShards(img=32, batch=4)
ADAPTER = autoencoder_adapter(cut=5, img=32)

# the standard elastic scenario: one join, one leave, seeded failures,
# batteries tight enough that reserve-policy skips appear
ELASTIC = dict(join_events={2: 1}, leave_events={5: 0}, fail_prob=0.3)
ENERGY = dict(battery_j=200.0, recharge_w=0.01, reserve_j=150.0,
              max_steps_per_pass=2)


def _budget(n_sats=4, n_items=16.0):
    return PassBudget(plane=OrbitalPlane(n_sats=n_sats), n_items=n_items)


def _host_sim(budget, seed=0, data=None, **cfg_kw):
    sim = ConstellationSim(ADAPTER, budget, data or SHARDS,
                           ConstellationConfig(batch_size=4, seed=seed,
                                               **cfg_kw))
    # pin the model init to seed 0 regardless of the failure seed, so a
    # per-plane oracle (seed + p) still trains the fleet's shared init
    sim.state = SLTrainState.create(
        *ADAPTER.init(jax.random.key(0)), sim.optimizer)
    return sim


def _assert_plane_parity(host, res, p):
    """One plane of a FleetResult against its host oracle's records."""
    assert [r.action for r in host.records] == \
        [ACTION_NAMES[int(a)] for a in res.action[p]]
    assert [r.sat_id for r in host.records] == list(res.sat[p])
    for hr, dl, db in zip(host.records, res.loss[p], res.battery_j[p]):
        if hr.loss is None:
            assert not np.isfinite(dl)
        else:
            np.testing.assert_allclose(dl, hr.loss, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(db, hr.battery_j, rtol=1e-5, atol=0.05)


# ---------------------------------------------------------------- events

def test_event_schedule_matches_host_semantics():
    """The precomputed schedule replays the host's join/leave rules:
    joins append slots (id = current total) before a leave resolves its
    ``sid % len(sats)`` at that pass."""
    sched = build_event_schedule(
        3, 10, join_events={2: 2, 4: 1}, leave_events={1: 4, 4: 5})
    assert sched.n_slots == 6
    assert list(sched.join_pass) == [0, 0, 0, 2, 2, 4]
    # pass 1: 3 sats -> 4 % 3 = slot 1; pass 4: joins first (6 sats),
    # then 5 % 6 = slot 5 (the just-joined sat leaves immediately)
    assert sched.leave_pass[1] == 1
    assert sched.leave_pass[5] == 4
    member = sched.member_at(4)
    assert list(member) == [True, False, True, True, True, False]
    # failure stream == the host oracle's own numpy draws
    sched = build_event_schedule(3, 8, fail_prob=0.4, n_planes=2, seed=7)
    for p in range(2):
        rng = np.random.default_rng(7 + p)
        host_draws = np.array([rng.random() < 0.4 for _ in range(8)])
        assert (sched.fail_mask[p] == host_draws).all()


# ------------------------------------------- host-vs-fleet parity (P=1)

def test_seeded_failure_parity_via_delegation():
    """The ISSUE acceptance scenario: a host ``fail_prob`` run vs the
    device aliveness-mask run with the same event schedule produces
    identical action sequences and battery trajectories (and the
    elastic delegation guards are gone — ``run(engine="device")`` now
    executes join/leave/failure runs on device)."""
    budget = _budget(n_items=4e6)

    def mk():
        return _host_sim(budget, n_passes=12, **ELASTIC, **ENERGY)

    host, dev = mk(), mk()
    host.run()
    dev.run(engine="device")

    assert [r.action for r in host.records] == \
        [r.action for r in dev.records]
    assert [r.sat_id for r in host.records] == \
        [r.sat_id for r in dev.records]
    actions = [r.action for r in host.records]
    assert "failed" in actions and "skipped_energy" in actions \
        and "trained" in actions
    for h, d in zip(host.records, dev.records):
        if h.loss is None:
            assert d.loss is None
        else:
            np.testing.assert_allclose(d.loss, h.loss, rtol=2e-4,
                                       atol=2e-5)
        np.testing.assert_allclose(d.battery_j, h.battery_j, rtol=1e-5,
                                   atol=0.05)
        np.testing.assert_allclose(d.e_total_j, h.e_total_j, rtol=1e-5,
                                   atol=1e-9)
    hs, ds = host.summary(), dev.summary()
    for key in ("passes", "trained", "skipped", "failed"):
        assert hs[key] == ds[key], key
    assert ds["failed"] > 0
    np.testing.assert_allclose(ds["E_total_J"], hs["E_total_J"],
                               rtol=1e-5)
    # fleet slot state folded back onto the host SatelliteStates
    # (joiners appended, failed/left sats dead, batteries carried over)
    assert len(dev.sats) == len(host.sats) > 4
    for hsat, dsat in zip(host.sats, dev.sats):
        assert dsat.alive == hsat.alive
        assert dsat.passes_served == hsat.passes_served
        np.testing.assert_allclose(dsat.battery_j, hsat.battery_j,
                                   rtol=1e-5, atol=0.05)
    assert dev._batch_idx == host._batch_idx
    eng = dev.device_engine
    assert eng.traces == 1 and eng.host_syncs <= 3  # <= 1 per revolution


def test_chained_elastic_delegation():
    """Two chained elastic device runs equal two chained host runs: the
    second delegation's ring already carries the first run's joiners
    and casualties (slot layout follows the schedule, not the
    configured plane), and the failure stream keeps consuming the
    sim's one live generator across segments."""
    budget = _budget()

    def mk():
        return _host_sim(budget, n_passes=6, join_events={1: 1},
                         fail_prob=0.3, max_steps_per_pass=4)

    host, dev = mk(), mk()
    host.run()
    host.run()
    dev.run(engine="device")
    dev.run(engine="device")
    assert [(r.action, r.sat_id) for r in host.records] == \
        [(r.action, r.sat_id) for r in dev.records]
    assert len(host.records) == 12 and len(dev.sats) == len(host.sats)
    for hsat, dsat in zip(host.sats, dev.sats):
        assert dsat.alive == hsat.alive
        np.testing.assert_allclose(dsat.battery_j, hsat.battery_j,
                                   rtol=1e-5, atol=0.05)
    assert dev._batch_idx == host._batch_idx


def test_ragged_elastic_delegation():
    """Elastic runs need not be whole revolutions: a 7-pass fail run
    delegates as one chunk and still matches the host oracle."""
    budget = _budget()
    host = _host_sim(budget, n_passes=7, fail_prob=0.4,
                     max_steps_per_pass=4)
    dev = _host_sim(budget, n_passes=7, fail_prob=0.4,
                    max_steps_per_pass=4)
    host.run()
    dev.run(engine="device")
    assert [r.action for r in host.records] == \
        [r.action for r in dev.records]
    assert dev.device_engine.host_syncs == 1


# --------------------------------------------- multi-plane fleet parity

def test_two_plane_fleet_matches_per_plane_host_oracles():
    """2 planes x (4+1) slots with joins, leaves and per-plane seeded
    failures (averaging off): every plane's action/sat/loss/battery
    timeline equals a host oracle running the same schedule with its
    data ids offset to the plane's global range."""
    budget = _budget(n_sats=4, n_items=4e6)
    cfg = FleetConfig(n_planes=2, n_revolutions=3, seed=0, avg_every=0,
                      **ELASTIC, **ENERGY)
    fleet = FleetEngine(ADAPTER, budget, SHARDS, cfg)
    M, K = fleet.n_slots, fleet.n_passes
    res = fleet.run(stream_telemetry=True)
    assert fleet.traces == 1
    assert fleet.host_syncs == 3          # exactly one per revolution
    assert res.action.shape == (2, K)

    failures = 0
    for p in range(2):
        host = _host_sim(budget, seed=cfg.seed + p,
                         data=lambda s, i, p=p: SHARDS(p * M + s, i),
                         n_passes=K, **ELASTIC, **ENERGY)
        host.run()
        _assert_plane_parity(host, res, p)
        failures += sum(r.action == "failed" for r in host.records)
    assert failures > 0
    assert res.summary()["failed"] == failures


def test_interplane_averaging_matches_manual_reference():
    """avg_every=1 equals P independent single-ring device engines with
    explicit checkpoint averaging between revolutions — the fleet's
    all-reduce is exactly the paper's inter-plane ISL exchange."""
    N, P, R = 4, 2, 2
    budget = _budget(n_sats=N)
    cfg = FleetConfig(n_planes=P, n_revolutions=R, max_steps_per_pass=8,
                      avg_every=1, seed=0)
    fleet = FleetEngine(ADAPTER, budget, SHARDS, cfg)
    M = fleet.n_slots
    res = fleet.run(stream_telemetry=True)

    opt = resolve_optimizer("sgd", lr=cfg.lr)
    init = SLTrainState.create(*ADAPTER.init(jax.random.key(0)), opt)
    engines = [DeviceConstellationSim(
        ADAPTER, budget, lambda s, i, p=p: SHARDS(p * M + s, i),
        DeviceSimConfig(max_steps_per_pass=8, seed=0),
        state=jax.tree.map(jnp.copy, init)) for p in range(P)]
    ref = [[] for _ in range(P)]
    for _ in range(R):
        for p, eng in enumerate(engines):
            ref[p].extend(eng.run(1, stream_telemetry=True).loss[0])
        avg = average_planes(jax.tree.map(lambda *xs: jnp.stack(xs),
                                          *[e.state for e in engines]))
        for p, eng in enumerate(engines):
            eng.state = jax.tree.map(lambda x: x[p], avg)
    np.testing.assert_allclose(res.loss, np.asarray(ref), rtol=2e-4,
                               atol=2e-5)
    # averaging actually coupled the planes: the final segment params
    # are identical across the plane axis
    pa = jax.tree.leaves(res.state.params_a)[0]
    np.testing.assert_allclose(np.asarray(pa[0]), np.asarray(pa[1]),
                               rtol=1e-6)


def test_averaging_off_keeps_planes_independent():
    N, P = 4, 2
    budget = _budget(n_sats=N)
    cfg = FleetConfig(n_planes=P, n_revolutions=1, max_steps_per_pass=4,
                      avg_every=0, seed=0)
    res = FleetEngine(ADAPTER, budget, SHARDS, cfg).run()
    pa = jax.tree.leaves(res.state.params_a)[0]
    assert not np.allclose(np.asarray(pa[0]), np.asarray(pa[1]))


# ----------------------------------------------- planning / integration

def test_fleet_plan_heterogeneous_rows():
    """All P x M problem-(13) instances solve in ONE device call, with
    per-satellite dtx rows planning mixed payloads."""
    budget = _budget()
    dtx = np.array([[1e4, 2e4, 3e4, 4e4], [4e4, 3e4, 2e4, 1e4]])
    plan = plan_ring_passes(budget, ADAPTER.costs(), batch_size=4,
                            n_sats=(2, 4), ring_n=4, dtx_bits=dtx,
                            max_steps_per_pass=8)
    e = np.asarray(plan.e_total_j)
    assert e.shape == (2, 4)
    assert (np.diff(e[0]) > 0).all()      # heavier payloads cost more
    np.testing.assert_allclose(e[1], e[0, ::-1], rtol=1e-6)


def test_delegation_threads_measured_per_sat_dtx():
    """ROADMAP open item 2, host half: ``as_device_sim`` feeds the
    device planner a measured per-satellite (N,) payload array (the
    ``sl_step.ring_boundary_bits`` feed), not slot 0's scalar."""
    budget = _budget()
    sim = ConstellationSim(ADAPTER, budget, SHARDS,
                           ConstellationConfig(batch_size=4, n_passes=4))
    eng = sim.as_device_sim(n_revolutions=1)
    assert isinstance(eng.dtx_bits, np.ndarray)
    assert eng.dtx_bits.shape == (4,)
    # the measured array equals each slot's metered payload per item
    from repro.core.sl_step import ring_boundary_bits
    batches = [SHARDS(s, 0) for s in range(4)]
    expect = ring_boundary_bits(ADAPTER, batches) / 4.0
    np.testing.assert_allclose(eng.dtx_bits, expect)


def test_sweep_cell_feeds_fleet():
    """A planned sweep cell broadcasts into a (P, N) fleet plan the
    engine executes directly (mission -> fleet bridge)."""
    from repro.core.mission import sweep_revolutions

    budget = _budget()
    cfg = FleetConfig(n_planes=2, n_revolutions=1, max_steps_per_pass=8,
                      seed=0)
    fleet = FleetEngine(ADAPTER, budget, SHARDS, cfg)
    sweep = sweep_revolutions([4], [fleet.costs], [16.0], budget=budget)
    plan = sweep.fleet_plan(4, 2, cut=0, max_steps_per_pass=8)
    for field in plan._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(plan, field)),
            np.asarray(getattr(fleet.plan, field)),
            rtol=1e-6, atol=1e-12, err_msg=field)
    fleet2 = FleetEngine(ADAPTER, budget, SHARDS, cfg, plan=plan)
    res = fleet2.run()
    assert (res.action == ACTION_TRAINED).all()
    assert np.isfinite(res.loss).all()


# ------------------------------------------------------ pass scan sizing

@pytest.mark.parametrize("k", [0, 3, 5, 12])
def test_scan_steps_are_the_plans_largest_count(k):
    """Both device engines size the per-pass scan at the plan's own
    largest step count (at least 1), not at a bucketed one: an engine
    plans once, so padding steps would only compute masked no-ops."""
    budget = _budget()
    base = plan_ring_passes(budget, ADAPTER.costs(), batch_size=4,
                            n_sats=4, max_steps_per_pass=8)
    row = np.array([k // 2, k, 0, k], np.int32)
    ring = DeviceConstellationSim(
        ADAPTER, budget, SHARDS, DeviceSimConfig(seed=0),
        plan=base._replace(n_steps=jnp.asarray(row)))
    fleet_plan = jax.tree.map(lambda x: jnp.stack([x, x]), base)
    fleet = FleetEngine(
        ADAPTER, budget, SHARDS, FleetConfig(n_planes=2, seed=0),
        plan=fleet_plan._replace(n_steps=jnp.asarray(np.stack([row, row]))))
    assert ring._scan_steps == fleet._scan_steps == max(k, 1)


@pytest.mark.parametrize("n_items,k,optimizer",
                         [(12.0, 3, "sgd"), (20.0, 5, "adamw")])
def test_exact_scan_matches_padded_pass_chain(n_items, k, optimizer):
    """One revolution of a fault-free plane whose plan gives k steps a
    pass (not a power of two): the padding steps the scan no longer runs
    were exact no-ops.  The final state equals, bit for bit, the same
    fleet run at the bucketed scan length, and matches chaining the
    padded ``make_sl_pass(bucket=True)`` pass by pass over the same
    batches (a separately compiled program: equal up to rounding)."""
    assert bucket_size(k) > k
    budget = _budget(n_sats=3, n_items=n_items)
    cfg = FleetConfig(n_planes=1, n_revolutions=1, optimizer=optimizer,
                      avg_every=0, seed=0)
    fleet = FleetEngine(ADAPTER, budget, SHARDS, cfg)
    assert fleet._scan_steps == k
    plan_steps = np.asarray(fleet.plan.n_steps)[0]
    assert (plan_steps == k).all()
    res = fleet.run()
    assert (res.action == ACTION_TRAINED).all()
    np.testing.assert_array_equal(res.n_steps[0], plan_steps[res.sat[0]])

    padded = FleetEngine(ADAPTER, budget, SHARDS, cfg)
    padded._scan_steps = bucket_size(k)
    res_padded = padded.run()
    np.testing.assert_array_equal(res.loss, res_padded.loss)
    for got, want in zip(jax.tree.leaves(res.state),
                         jax.tree.leaves(res_padded.state)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    opt = resolve_optimizer(cfg.optimizer, lr=cfg.lr)
    state = SLTrainState.create(*ADAPTER.init(jax.random.key(cfg.seed)),
                                opt)
    sl_pass = make_sl_pass(ADAPTER, optimizer=opt, bucket=True,
                           donate=False)
    bidx = 0
    for slot in res.sat[0]:
        n = int(plan_steps[slot])
        state = sl_pass(state, [SHARDS(int(slot), bidx + j)
                                for j in range(n)]).state
        bidx += n
    assert int(np.asarray(fleet._batch_idx)[0]) == bidx
    assert int(np.asarray(res.state.step)[0]) == int(state.step) \
        == plan_steps.sum()
    for got, want in zip(jax.tree.leaves(res.state), jax.tree.leaves(state)):
        got, want = np.asarray(got)[0], np.asarray(want)
        scale = float(np.abs(want).max()) if want.size else 0.0
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-4 * scale)


def test_fleet_chaining_and_counters():
    budget = _budget()
    cfg = FleetConfig(n_planes=2, n_revolutions=2, max_steps_per_pass=4,
                      seed=0)
    fleet = FleetEngine(ADAPTER, budget, SHARDS, cfg)
    res = fleet.run(stream_telemetry=True)
    assert fleet.traces == 1
    assert fleet.device_calls == 2 and fleet.host_syncs == 2
    res2 = fleet.run(1, stream_telemetry=True)
    assert fleet.traces == 1              # same program, reused
    # beyond the precomputed horizon membership persists and, with
    # fail_prob=0, no failure stream exists (fail_prob>0 refreshes from
    # jax.random past the horizon — tests/test_scenarios.py): every
    # chained pass still serves and trains
    assert np.isfinite(res2.loss).all()
    assert (res2.sat >= 0).all()
    # training continued from where the first run stopped
    assert res2.loss[0, 0] < res.loss[0, -1]
    assert int(np.asarray(fleet._pass_idx)) == 12


# ------------------------------------------------- multi-device sharding

def test_fleet_accepts_host_mesh_data_axis():
    """Any mesh with a suitable axis shards the plane dimension —
    ``make_host_mesh``'s data axis serves CPU-device tests."""
    from repro.launch.mesh import make_host_mesh

    budget = _budget()
    cfg = FleetConfig(n_planes=2, n_revolutions=1, max_steps_per_pass=2,
                      seed=0)
    with pytest.raises(ValueError, match="planes"):
        FleetEngine(ADAPTER, budget, SHARDS, cfg,
                    schedule=build_event_schedule(4, 4, n_planes=1))
    fleet = FleetEngine(ADAPTER, budget, SHARDS, cfg,
                        mesh=make_host_mesh(), plane_axis="data")
    res = fleet.run()
    assert np.isfinite(res.loss).all()


def test_fleet_on_two_cpu_devices_subprocess():
    """The acceptance scenario end to end: a 2-plane fleet with join,
    leave and seeded-failure events runs on >= 2 CPU host devices,
    sharded over the plane mesh axis, with <= 1 host sync per
    revolution and host-oracle parity — in a subprocess because the
    device count must be forced before jax initializes."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               REPRO_FLEET_SMOKE_SATS="4", REPRO_FLEET_SMOKE_PLANES="2",
               REPRO_FLEET_SMOKE_REVS="2",
               JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH="src" + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.fleet"],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "on 2 device(s)" in proc.stdout, proc.stdout
    assert "'plane': 2" in proc.stdout, proc.stdout
    assert "parity OK" in proc.stdout, proc.stdout
