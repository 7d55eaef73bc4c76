"""Flight recorder: ring semantics, the metrics registry + sync-budget
guard, ring-vs-telemetry parity on all three engines, the chained
≤-1-sync-per-revolution regression contracts, timeline export, the
scan-purity lint and the benchmark run header."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.energy import PassBudget
from repro.core.orbits import OrbitalPlane
from repro.core.sl_step import autoencoder_adapter
from repro.fleet import (EclipseConfig, EpidemicConfig, FleetConfig,
                         FleetEngine, ScenarioConfig)
from repro.obs import (EV_EXCHANGE, EV_PASS, EV_SERVE, PASS_FIELDS,
                       SERVE_FIELDS, FlightRecorder, MetricsRegistry,
                       SyncBudgetExceeded, flush, merge_events,
                       payload_column, record, ring_init, sync_budget,
                       timeline_summary, to_chrome_trace,
                       validate_chrome_trace)
from repro.sim.data import DeviceImageryShards
from repro.sim.device_sim import (ACTION_NAMES, DeviceConstellationSim,
                                  DeviceSimConfig)
from repro.serve_fleet.engine import (FleetServeEngine, ServeCost,
                                      ServeFleetConfig, TrainLoad)
from repro.serve_fleet.traffic import TrafficConfig

SHARDS = DeviceImageryShards(img=32, batch=4)
ADAPTER = autoencoder_adapter(cut=5, img=32)
ENERGY = dict(battery_j=200.0, recharge_w=0.01, reserve_j=150.0,
              max_steps_per_pass=2)


def _budget(n_sats=4, n_items=16.0):
    return PassBudget(plane=OrbitalPlane(n_sats=n_sats), n_items=n_items)


def _serve_fleet(*, train=None, eclipse=None, P=2, M=8, K=24, seed=2):
    cost = ServeCost(tokens_per_s=50.0, e_token_j=0.02,
                     dtx_bits_token=2048.0)
    scfg = ServeFleetConfig(n_planes=P, n_sats=M, n_windows=K,
                            battery_j=60.0, recharge_w=0.02,
                            reserve_serve_j=5.0, reserve_train_j=30.0,
                            window_s=90.0, eclipse=eclipse)
    return FleetServeEngine(scfg, TrafficConfig(users_per_day=60_000.0,
                                                decode_len=4, seed=seed),
                            cost, train=train)


# ------------------------------------------------------------------ ring

def test_ring_record_order_and_flush():
    ring = ring_init(8)
    for i in range(5):
        ring = record(ring, EV_PASS, 10 + i, i, (float(i), 100.0 + i))
    ev = flush(ring)
    assert ev.dropped == 0
    np.testing.assert_array_equal(ev.kind, [EV_PASS] * 5)
    np.testing.assert_array_equal(ev.t, np.arange(10, 15))
    np.testing.assert_array_equal(ev.slot, np.arange(5))
    np.testing.assert_allclose(ev.payload[:, 0], np.arange(5.0))
    np.testing.assert_allclose(ev.payload[:, 1], 100.0 + np.arange(5.0))
    # short payloads zero-pad to the full row width
    assert ev.payload.shape[1] == 8
    np.testing.assert_array_equal(ev.payload[:, 2:], 0.0)


def test_ring_wraparound_keeps_newest_and_reports_dropped():
    ring = ring_init(4)
    for i in range(10):
        ring = record(ring, EV_PASS, i, 0, (float(i),))
    ev = flush(ring)
    assert ev.dropped == 6
    # oldest-first among the surviving newest 4
    np.testing.assert_array_equal(ev.t, [6, 7, 8, 9])
    np.testing.assert_allclose(ev.payload[:, 0], [6.0, 7.0, 8.0, 9.0])


def test_ring_masked_record_is_noop():
    ring = ring_init(4)
    ring = record(ring, EV_PASS, 0, 0, (1.0,))
    skipped = record(ring, EV_PASS, 1, 1, (2.0,), mask=False)
    for a, b in zip(ring, skipped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(skipped.cursor) == 1


def test_ring_records_under_vmap_and_jit():
    P = 3

    @jax.jit
    def go(ring):
        def body(r, k):
            r = jax.vmap(
                lambda rp, p: record(rp, EV_PASS, k, p,
                                     (p.astype(jnp.float32),)))(
                r, jnp.arange(P, dtype=jnp.int32))
            return r, None
        ring, _ = jax.lax.scan(body, ring, jnp.arange(5, dtype=jnp.int32))
        return ring

    ring = go(ring_init(8, batch=(P,)))
    rec = FlightRecorder()
    assert rec.ingest(ring) == 15
    ev = rec.events()
    for p in range(P):
        sel = ev["plane"] == p
        assert sel.sum() == 5
        np.testing.assert_array_equal(ev["t"][sel], np.arange(5))
        np.testing.assert_allclose(ev["payload"][sel][:, 0], float(p))


def test_recorder_t_offset_and_merge():
    r1 = record(ring_init(2), EV_PASS, 0, 0, (1.0,))
    r2 = record(ring_init(2), EV_SERVE, 0, 0, (2.0,))
    rec = FlightRecorder()
    rec.ingest(r1)
    rec.ingest(r2, t_offset=7)
    ev = rec.events()
    np.testing.assert_array_equal(ev["t"], [0, 7])
    merged = merge_events(ev, ev)
    assert merged["kind"].shape[0] == 4
    assert list(merged["t"]) == sorted(merged["t"])


def test_recorder_save_load_roundtrip(tmp_path):
    rec = FlightRecorder()
    rec.ingest(record(ring_init(2), EV_PASS, 3, 1, (5.0,)))
    path = str(tmp_path / "events.npz")
    rec.save(path)
    back = FlightRecorder.load(path)
    a, b = rec.events(), back.events()
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


# --------------------------------------------------------------- metrics

def test_registry_counters_propagate_to_parent():
    parent = MetricsRegistry()
    child = MetricsRegistry("fleet", parent=parent)
    child.inc("host_syncs")
    child.inc("host_syncs", 2)
    assert child.counter("host_syncs").value == 3
    assert parent.counter("fleet.host_syncs").value == 3
    child.counter("host_syncs").set(1)       # absolute writes re-sync too
    assert parent.counter("fleet.host_syncs").value == 1
    d = parent.to_dict()
    assert d == {"fleet.host_syncs": 1}


def test_histogram_summary():
    reg = MetricsRegistry()
    h = reg.histogram("dispatch_s")
    for x in (0.5, 1.5, 200.0):
        h.record(x)
    v = h.to_value()
    assert v["count"] == 3 and v["min"] == 0.5 and v["max"] == 200.0
    np.testing.assert_allclose(v["mean"], (0.5 + 1.5 + 200.0) / 3)
    assert v["buckets"]["le_0.5"] == 1 and v["buckets"]["le_inf"] == 1


def test_sync_budget_passes_and_raises():
    reg = MetricsRegistry()
    child = MetricsRegistry("sim", parent=reg)
    with sync_budget(2, registry=reg):
        child.inc("host_syncs", 2)
    with pytest.raises(SyncBudgetExceeded):
        with sync_budget(1, registry=reg):
            child.inc("host_syncs", 2)
    # counters created inside the region count from zero
    with pytest.raises(SyncBudgetExceeded):
        with sync_budget(0, registry=reg):
            MetricsRegistry("fresh", parent=reg).inc("host_syncs")


def test_engine_counters_are_registry_backed():
    sim = DeviceConstellationSim(
        ADAPTER, _budget(), SHARDS,
        DeviceSimConfig(n_revolutions=2, **ENERGY))
    res = sim.run()
    assert sim.traces == 1 and sim.device_calls == 1
    assert sim.host_syncs == 1
    # the old attributes are live views of the registry counters
    assert sim.metrics.counter("host_syncs").value == 1
    sim.host_syncs = 5                       # compat setter writes through
    assert sim.metrics.counter("host_syncs").value == 5
    # ring events mirror the dense telemetry one-for-one
    assert len(sim.recorder) == res.action.size
    ev = sim.recorder.events()
    np.testing.assert_array_equal(
        payload_column(ev, EV_PASS, "action").astype(np.int32),
        res.action.reshape(-1))
    np.testing.assert_allclose(
        payload_column(ev, EV_PASS, "battery_j"),
        res.battery_j.reshape(-1), rtol=1e-6)


# -------------------------------------------- chained sync-contract tests

def test_chained_scenario_runs_keep_sync_contract():
    """The ≤-1-sync-per-revolution contract under eclipse + epidemic +
    seeded failures, across CHAINED runs (the regression the plain
    closed-loop assertions never covered)."""
    scn = ScenarioConfig(
        eclipse=EclipseConfig(period=4, duty=0.5, stagger=1),
        epidemic=EpidemicConfig(beta=0.6, ttl=2, init_slots=(0,),
                                start=0))
    cfg = FleetConfig(n_planes=2, n_revolutions=2, fail_prob=0.2,
                      seed=0, avg_every=1, scenario=scn,
                      aggregate="median", **ENERGY)
    fleet = FleetEngine(ADAPTER, _budget(), SHARDS, cfg)
    with sync_budget(2, registry=fleet.metrics):
        res1 = fleet.run(stream_telemetry=True)
    with sync_budget(1, registry=fleet.metrics):
        res2 = fleet.run(n_revolutions=1)
    assert fleet.traces <= 2                 # one per distinct R at most
    assert fleet.host_syncs == 3
    with pytest.raises(SyncBudgetExceeded):
        with sync_budget(0, registry=fleet.metrics):
            fleet.run(n_revolutions=1)
    # the recorder saw every pass of every chained run, on one absolute
    # timeline (no t collisions between runs), plus exchange markers
    ev = fleet.recorder.events()
    n_pass = int((ev["kind"] == EV_PASS).sum())
    assert n_pass == res1.action.size + 2 * res2.action.size
    assert (ev["kind"] == EV_EXCHANGE).sum() > 0
    # 2 streamed revolutions + two chained 1-revolution runs
    pass_t = ev["t"][ev["kind"] == EV_PASS]
    assert pass_t.max() == fleet.n_passes + 2 * fleet.rev_len - 1
    # eclipse bits made it into the payload
    sunlit = payload_column(ev, EV_PASS, "sunlit")
    assert (sunlit == 0.0).any() and (sunlit == 1.0).any()


def test_fleet_ring_matches_telemetry_per_plane():
    cfg = FleetConfig(n_planes=2, n_revolutions=2, fail_prob=0.3,
                      seed=0, avg_every=0, **ENERGY)
    fleet = FleetEngine(ADAPTER, _budget(), SHARDS, cfg)
    res = fleet.run()
    ev = fleet.recorder.events()
    for p in range(2):
        sel = (ev["kind"] == EV_PASS) & (ev["plane"] == p)
        order = np.argsort(ev["t"][sel])
        pay = ev["payload"][sel][order]
        np.testing.assert_array_equal(
            pay[:, PASS_FIELDS.index("action")].astype(np.int32),
            res.action[p])
        np.testing.assert_array_equal(
            ev["slot"][sel][order], res.sat[p])
        # NaN batteries (failed pass) must match elementwise too
        np.testing.assert_array_equal(
            np.isnan(pay[:, PASS_FIELDS.index("battery_j")]),
            np.isnan(res.battery_j[p]))
        np.testing.assert_allclose(
            pay[:, PASS_FIELDS.index("battery_j")],
            res.battery_j[p], rtol=1e-6)


def test_serve_train_contention_chained_sync_contract():
    train = TrainLoad(drain_j=8.0, e_total_j=12.0)
    fleet = _serve_fleet(train=train,
                         eclipse=EclipseConfig(period=6, duty=0.5))
    with sync_budget(1, registry=fleet.metrics):
        res1 = fleet.run()
    with sync_budget(1, registry=fleet.metrics):
        res2 = fleet.run(n_windows=8)
    assert fleet.host_syncs == 2 and fleet.device_calls == 2
    ev = fleet.recorder.events()
    assert (ev["kind"] == EV_SERVE).sum() == \
        res1.arrivals.size + res2.arrivals.size
    # chained runs continue the absolute window timeline
    assert ev["t"].max() == 24 + 8 - 1
    served = payload_column(ev, EV_SERVE, "served")
    total = res1.served.sum() + res2.served.sum()
    np.testing.assert_allclose(served.sum(), total)
    trained = payload_column(ev, EV_SERVE, "trained")
    assert set(np.unique(trained)) <= {0.0, 1.0}


# -------------------------------------------------------------- timeline

def test_chrome_trace_render_and_validate(tmp_path):
    cfg = FleetConfig(n_planes=2, n_revolutions=1, seed=0, avg_every=1,
                      scenario=ScenarioConfig(
                          eclipse=EclipseConfig(period=2, duty=0.5)),
                      aggregate="median", **ENERGY)
    fleet = FleetEngine(ADAPTER, _budget(), SHARDS, cfg)
    fleet.run()
    serve = _serve_fleet(K=6)
    serve.run()
    merged = merge_events(fleet.recorder.events(),
                          serve.recorder.events())
    trace = to_chrome_trace(merged, window_s=90.0)
    validate_chrome_trace(trace)
    path = tmp_path / "trace.json"
    with open(path, "w") as fh:
        json.dump(trace, fh)
    validate_chrome_trace(json.loads(path.read_text()))
    evs = trace["traceEvents"]
    cats = {e.get("cat") for e in evs}
    assert "train" in cats and "serve" in cats and "eclipse" in cats
    names = {e["name"] for e in evs if e["ph"] == "X"}
    assert names & set(ACTION_NAMES.values())
    # metadata names every plane process
    procs = {e["pid"] for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert procs == {0, 1}
    # ts/dur scale with window_s
    xs = [e for e in evs if e["ph"] == "X" and e["cat"] == "train"]
    assert all(abs(e["dur"] - 90e6) < 1e-3 for e in xs)
    assert timeline_summary(merged).startswith("flight recorder:")


def test_validate_rejects_malformed_traces():
    with pytest.raises(ValueError):
        validate_chrome_trace({"events": []})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X", "pid": 0,
                                                "tid": 0, "name": "x",
                                                "ts": 0}]})  # no dur


# ------------------------------------------------------------------ lint

def _load_lint():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "lint_scan_purity.py")
    spec = importlib.util.spec_from_file_location("lint_scan_purity", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_lint_scan_purity_repo_is_clean():
    assert _load_lint().main([]) == 0


def test_lint_scan_purity_flags_violations(tmp_path):
    lint = _load_lint()
    bad = tmp_path / "engine.py"
    bad.write_text(
        "import numpy as np\n"
        "import jax\n"
        "class E:\n"
        "    def _compiled(self):\n"
        "        def body(c, x):\n"
        "            jax.debug.print('k={}', x)\n"
        "            y = np.float32(c)\n"
        "            x.block_until_ready()\n"
        "            with jax.profiler.TraceAnnotation('step'):\n"
        "                c = c + 1\n"
        "            return c, y\n"
        "        return body\n")
    hits, found = lint.lint_file(str(bad), ("_compiled",))
    assert found == ["_compiled"]
    msgs = " ".join(m for _, _, m in hits)
    assert len(hits) == 4
    assert "jax.debug.print" in msgs
    assert "block_until_ready" in msgs and "numpy" in msgs
    assert "jax.profiler.TraceAnnotation" in msgs
    # clean scope -> no hits; missing scope -> reported
    ok = tmp_path / "ok.py"
    ok.write_text("def _compiled():\n    return 1\n")
    assert lint.lint_file(str(ok), ("_compiled",)) == ([], ["_compiled"])
    assert lint.lint_file(str(ok), ("nope",)) == ([], [])


# ------------------------------------------------------------ benchmarks

def test_bench_run_header_fields():
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from benchmarks.run import run_header
    finally:
        sys.path.pop(0)
    h = run_header(quick=True)
    assert h["quick"] is True
    assert h["jax_version"] == jax.__version__
    assert h["device_count"] == len(jax.devices())
    assert isinstance(h["rev"], str) and h["rev"]
    assert h["mesh_shape"] is None or isinstance(h["mesh_shape"], dict)
