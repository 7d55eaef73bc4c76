"""Device-resident constellation simulator: the closed loop as ONE scan.

The host :class:`~repro.core.constellation.ConstellationSim` advances
battery/recharge state and dispatches every pass from Python — fine for
protocol studies, but each pass costs a host round-trip, which caps
closed-loop energy studies at small rings and few revolutions.  This
module promotes the whole loop to a first-class device program:

    one jitted nested ``lax.scan`` over (revolution × ring-slot), where
    each slot's pass = [reserve-skip policy → masked fused SL steps →
    battery drain → fleet recharge], with the model state and the
    per-satellite :class:`~repro.sim.energy_state.EnergyState` riding
    the donated carry.

Layering (who owns what):

* **planning** — :func:`plan_ring_passes` builds the ring's N
  problem-(13) instances with
  :func:`~repro.core.resource_opt_jax.ring_pass_coeffs` and sheds+solves
  them on device (``shed_and_solve_coeffs``) under the solver's float64
  scope, then casts the pass plan (:class:`DevicePassPlan`) to
  float32/int32 arrays at the planning/training boundary — the SL stack
  stays float32.  The plan is revolution-invariant for a static ring
  (membership and batch shapes fixed), so planning once inside setup
  equals replanning every revolution.  A plan may also come from a
  whole scenario grid: ``RevolutionSweep.revolution_plan`` broadcasts
  one planned grid cell over its ring (see :mod:`repro.core.mission`).
* **training** — every step runs the SAME masked kernel as the host
  pass engine (:func:`~repro.core.sl_step.make_pass_step`); ``n_valid``
  step masks gate allocation-driven step counts, a reserve skip masks
  the whole pass.  The handoff is the carry itself: the train state
  simply arrives at the next slot ("segment A rides the scan"), with
  the ISL cost charged by the plan.
* **energy** — :mod:`repro.sim.energy_state` arrays; the battery clamp
  policy is shared verbatim with the host sim.

Host contact: ZERO dispatch between passes; telemetry syncs at most
once per revolution (``stream_telemetry=True``) or once per run.  The
``traces`` / ``device_calls`` / ``host_syncs`` counters make that
contract testable.

The host sim remains the parity oracle: with a traceable batch provider
(:class:`~repro.sim.data.DeviceImageryShards`) both engines consume
identical samples, and ``ConstellationSim.run(engine="device")``
delegates steady-state runs here, folding telemetry back into
``PassRecord`` form.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy import PassBudget, SplitCosts
from repro.obs.metrics import (MetricsRegistry, counter_property,
                               global_registry)
from repro.obs.ring import (EV_PASS, FlightRecorder, TelemetryRing,
                            record as ring_record, ring_init)
from repro.core.sl_step import (SplitAdapter, boundary_bits,
                                dedupe_state_buffers, make_pass_step)
from repro.core.train_state import SLTrainState
from repro.sim import energy_state as es_mod
from repro.sim.energy_state import EnergyState, init_energy_state
from repro.train.optimizer import resolve_optimizer
from repro.utils.treeutil import tree_bytes

ACTION_TRAINED = 0
ACTION_SHED = 1
ACTION_SKIPPED = 2
ACTION_FAILED = 3          # fleet engine only: a static ring cannot fail
ACTION_FAULT = 4           # fleet scenarios only: transient epidemic fault
ACTION_NAMES = {ACTION_TRAINED: "trained", ACTION_SHED: "shed",
                ACTION_SKIPPED: "skipped_energy", ACTION_FAILED: "failed",
                ACTION_FAULT: "faulted"}


class DevicePassPlan(NamedTuple):
    """One ring revolution of pre-solved pass allocations, ``(N,)`` arrays.

    Everything the closed loop needs per slot, already at the float32
    training boundary: fused-step counts (``n_steps``, the ``n_valid``
    feed of the shared pass kernel), the satellite-side battery drain
    (E_proc^sat + E_comm^down + E_ISL — what the host sim subtracts) and
    the eq. (11)/(12) records.  Built by :func:`plan_ring_passes` or
    broadcast from a swept grid cell
    (``RevolutionSweep.revolution_plan``).
    """

    n_steps: Any              # (N,) int32   fused SL steps per pass (>=1)
    n_items_kept: Any         # (N,) float32 post-shedding item count
    kept_fraction: Any        # (N,) float32
    drain_j: Any              # (N,) float32 satellite battery draw / pass
    e_total_j: Any            # (N,) float32 eq. (11) incl. E_ISL
    e_proc_j: Any             # (N,) float32 sat + gs processing
    e_comm_j: Any             # (N,) float32 downlink + uplink
    e_isl_j: Any              # (N,) float32
    t_total_s: Any            # (N,) float32 eq. (12)
    d_isl_bits: Any           # (N,) float32 segment-A handoff payload
    feasible: Any             # (N,) bool   post-shedding feasibility

    @property
    def n_sats(self) -> int:
        return self.n_steps.shape[0]

    def to_host(self) -> "DevicePassPlan":
        """One explicit device→host sync of the whole plan."""
        return DevicePassPlan(*[np.asarray(a) for a in self])


def plan_from_report(rep, frac, n_items, d_isl_bits, batch_size,
                     max_steps_per_pass=None) -> DevicePassPlan:
    """Fold a solved ``ArraySolveReport`` (+ shed fractions) into a
    :class:`DevicePassPlan`, casting to the float32 training boundary.

    Shared by :func:`plan_ring_passes` and the sweep-cell bridge in
    :mod:`repro.core.mission`; call under the solver's x64 scope.  The
    step count mirrors the host scheduler exactly:
    ``max(1, round(n_items_kept / batch_size))`` capped at
    ``max_steps_per_pass``.
    """
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    n_kept = jnp.asarray(frac) * jnp.asarray(n_items, jnp.float64)
    steps = jnp.maximum(jnp.round(n_kept / float(batch_size)), 1.0)
    if max_steps_per_pass is not None:
        steps = jnp.minimum(steps, float(max_steps_per_pass))
    pe = rep.phase_energy                # (..., 4) canonical phase order
    return DevicePassPlan(
        n_steps=steps.astype(jnp.int32),
        n_items_kept=f32(n_kept),
        kept_fraction=f32(frac),
        drain_j=f32(pe[..., 0] + pe[..., 1] + rep.e_isl),
        e_total_j=f32(rep.e_total),
        e_proc_j=f32(pe[..., 0] + pe[..., 2]),
        e_comm_j=f32(pe[..., 1] + pe[..., 3]),
        e_isl_j=f32(rep.e_isl),
        t_total_s=f32(rep.t_total),
        d_isl_bits=f32(jnp.broadcast_to(jnp.asarray(d_isl_bits),
                                        steps.shape)),
        feasible=rep.feasible)


def plan_ring_passes(budget: PassBudget, costs: SplitCosts, *,
                     batch_size: int, n_sats=None,
                     dtx_bits=None, n_items=None,
                     max_steps_per_pass: Optional[int] = None,
                     min_fraction: float = 0.05, tol: float = 1e-10,
                     max_iters: int = 80,
                     ring_n: Optional[int] = None) -> DevicePassPlan:
    """Shed + solve one ring revolution's N passes, entirely on device.

    The device twin of ``RevolutionPlanner.plan_revolution``: N
    problem-(13) instances (one per ring slot) built by
    :func:`~repro.core.resource_opt_jax.ring_pass_coeffs` — scalars
    broadcast ring-wide, or per-satellite ``(N,)`` arrays for measured
    heterogeneous payloads (``dtx_bits``) / item budgets (``n_items``).

    ``n_sats`` may be a shape tuple (the fleet engine plans ``(P, M)``
    rows in one solve); ``ring_n`` then pins the orbital population of
    the eq.-(5) ISL hop distance (the host oracle always prices it off
    the configured plane, not live membership).
    """
    from repro.core import resource_opt_jax as roj

    n_sats = budget.plane.n_sats if n_sats is None else n_sats
    dtx = costs.dtx_bits if dtx_bits is None else dtx_bits
    items = budget.n_items if n_items is None else n_items
    sc = roj.grid_scalars(budget.plane, budget.link, budget.isl,
                          budget.sat_device, budget.gs_device)
    with roj.x64_scope():
        coeffs = roj.ring_pass_coeffs(sc, n_sats, costs.w1_flops,
                                      costs.w2_flops, dtx,
                                      costs.d_isl_bits, items,
                                      ring_n=ring_n)
        rep, frac = roj.shed_and_solve_coeffs(coeffs, min_fraction, tol,
                                              max_iters)
        return plan_from_report(rep, frac, items, costs.d_isl_bits,
                                batch_size, max_steps_per_pass)


def measure_and_plan(adapter: SplitAdapter, budget: PassBudget, batch_fn,
                     *, quantize_boundary: bool, params_a, n_sats,
                     ring_n: Optional[int] = None, dtx_bits=None,
                     max_steps_per_pass: Optional[int] = None,
                     min_fraction: float = 0.05, plan=None,
                     isl_extra_bits=0.0):
    """The shared construction block of every device engine.

    Measures the boundary payload shape-only (one ``eval_shape`` probe
    batch), folds the measured costs (``dtx_bits`` per item, segment-A
    handoff bytes from the live ``params_a``), plans the pass rows on
    device (or accepts an external ``plan``), and sizes the static
    per-pass scan from the plan's actual largest step count (ONE host
    read, construction only; at least 1).  An engine plans once and
    compiles its own programs, so the scan carries no padding steps
    beyond that count: a pass with fewer planned steps masks the rest.
    Returns ``(batch_size, costs, plan, scan_steps)``.  Keeping this in
    one place is what keeps the single-ring engine and the fleet engine
    measuring and planning identically — the host-oracle parity
    invariant.
    """
    abstract = jax.eval_shape(lambda: batch_fn(0, 0))
    batch_size = int(jax.tree.leaves(abstract)[0].shape[0])
    dtx = boundary_bits(adapter, abstract, quantize_boundary) / batch_size
    # ``isl_extra_bits`` (scalar or per-instance array) adds the fleet
    # exchange's amortized per-pass wire volume (repro.isl) on top of
    # the segment-A handoff, so a codec choice reshapes the planned
    # problem-(13) allocation, not just a telemetry counter
    costs = dataclasses.replace(adapter.costs(), dtx_bits=dtx,
                                d_isl_bits=8.0 * tree_bytes(params_a)
                                + isl_extra_bits)
    if plan is None:
        plan = plan_ring_passes(budget, costs, batch_size=batch_size,
                                n_sats=n_sats, ring_n=ring_n,
                                dtx_bits=dtx_bits,
                                max_steps_per_pass=max_steps_per_pass,
                                min_fraction=min_fraction)
    k_max = int(np.asarray(jnp.max(plan.n_steps)))
    return batch_size, costs, plan, max(k_max, 1)


class PassTelemetry(NamedTuple):
    """Per-pass scan outputs, stacked to ``(R, N)`` by the nested scan."""

    action: Any               # int32 ACTION_* code
    loss: Any                 # float32 mean loss over executed steps (NaN
                              # when skipped)
    battery_j: Any            # float32 serving sat's battery at pass end
                              # (post-drain, post-recharge)
    n_steps: Any              # int32 steps actually executed


@dataclasses.dataclass(frozen=True)
class DeviceSimConfig:
    """Closed-loop knobs, mirroring the steady-state subset of
    :class:`~repro.core.constellation.ConstellationConfig`.  Elastic
    membership and random failures belong to the fleet engine
    (:mod:`repro.fleet`, whose scan carry holds the aliveness mask);
    checkpoint *persistence* (``handoff_dir``) remains host-oracle —
    it touches the filesystem, which no device program can."""

    n_revolutions: int = 1
    lr: float = 1e-2
    optimizer: Union[str, Any] = "sgd"
    quantize_boundary: bool = False
    battery_j: float = 5_000.0
    recharge_w: float = 20.0
    reserve_j: float = 100.0
    # cap on the planned steps of a pass (None = uncapped); the static
    # scan length is the plan's own largest step count (one host read
    # at construction time)
    max_steps_per_pass: Optional[int] = 128
    min_fraction: float = 0.05
    seed: int = 0


@dataclasses.dataclass
class DeviceSimResult:
    """Host-side view of one closed-loop run (synced telemetry)."""

    action: np.ndarray        # (R, N)
    loss: np.ndarray          # (R, N) NaN where skipped
    battery_j: np.ndarray     # (R, N) serving sat battery at pass end
    n_steps: np.ndarray       # (R, N)
    plan: DevicePassPlan      # host copies
    energy: EnergyState       # final fleet state, host copies
    state: Any                # final SLTrainState (device arrays)

    def summary(self) -> Dict[str, Any]:
        """Same shape as ``ConstellationSim.summary()``."""
        R, N = self.action.shape
        sat = np.tile(np.arange(N), (R, 1))
        trained = self.action != ACTION_SKIPPED
        losses = self.loss[trained]
        return {
            "passes": int(R * N),
            "trained": int(trained.sum()),
            "skipped": int((~trained).sum()),
            "failed": 0,
            "loss_first": float(losses[0]) if losses.size else None,
            "loss_last": float(losses[-1]) if losses.size else None,
            "E_total_J": float(self.plan.e_total_j[sat[trained]].sum()),
            "E_comm_J": float(self.plan.e_comm_j[sat[trained]].sum()),
            "E_proc_J": float(self.plan.e_proc_j[sat[trained]].sum()),
            "E_isl_J": float(self.plan.e_isl_j[sat[trained]].sum()),
        }


class DeviceConstellationSim:
    """The paper's cyclical SL protocol as one jitted device program.

    ``batch_fn(sat, idx) -> batch`` must be traceable (e.g.
    :class:`~repro.sim.data.DeviceImageryShards`): it runs INSIDE the
    scan, so the engine never stages a dataset.  ``state`` chains an
    existing :class:`~repro.core.train_state.SLTrainState` (donated —
    the input is consumed); ``plan`` overrides on-device planning with
    an external :class:`DevicePassPlan` (e.g. a swept grid cell).

    Observability: every pass also records an ``EV_PASS`` event into a
    :class:`~repro.obs.ring.TelemetryRing` riding the scan carry,
    flushed into ``self.recorder`` at the existing telemetry sync — the
    flight-recorder feed of :mod:`repro.obs.timeline`.  The legacy
    counters ``traces`` (jit traces of the closed loop — stays at 1
    across repeated runs of the same shape), ``device_calls``
    (dispatches; one per run, or one per revolution when streaming) and
    ``host_syncs`` (telemetry device→host reads; ≤ 1 per revolution by
    construction) live on ``self.metrics`` (a
    :class:`~repro.obs.metrics.MetricsRegistry` under the ``sim``
    namespace) behind read-through properties.
    """

    traces = counter_property("traces")
    device_calls = counter_property("device_calls")
    host_syncs = counter_property("host_syncs")

    def __init__(self, adapter: SplitAdapter, budget: PassBudget,
                 batch_fn: Callable[[Any, Any], Dict],
                 cfg: Optional[DeviceSimConfig] = None, *,
                 state: Optional[SLTrainState] = None,
                 plan: Optional[DevicePassPlan] = None,
                 dtx_bits=None):
        cfg = DeviceSimConfig() if cfg is None else cfg
        self.adapter = adapter
        self.budget = budget
        self.batch_fn = batch_fn
        self.cfg = cfg
        self.n_sats = budget.plane.n_sats
        self.optimizer = resolve_optimizer(cfg.optimizer, lr=cfg.lr)
        if state is None:
            pa, pb = adapter.init(jax.random.key(cfg.seed))
            state = SLTrainState.create(pa, pb, self.optimizer)
        self.state = state
        self.energy = init_energy_state(self.n_sats, cfg.battery_j)

        # measured costs + on-device plan + static scan sizing, via the
        # construction block shared with the fleet engine.  dtx_bits:
        # per-satellite measured boundary payloads ((N,) rows, e.g.
        # from sl_step.ring_boundary_bits) plan a heterogeneous ring in
        # the same single device solve; None broadcasts the measured
        # scalar.
        self.dtx_bits = dtx_bits
        self.batch_size, self.costs, self.plan, self._scan_steps = \
            measure_and_plan(adapter, budget, batch_fn,
                             quantize_boundary=cfg.quantize_boundary,
                             params_a=state.params_a, n_sats=self.n_sats,
                             dtx_bits=dtx_bits,
                             max_steps_per_pass=cfg.max_steps_per_pass,
                             min_fraction=cfg.min_fraction, plan=plan)
        if self.plan.n_sats != self.n_sats:
            raise ValueError(f"plan covers {self.plan.n_sats} slots but the "
                             f"ring has {self.n_sats} satellites")

        self._pass_step = make_pass_step(
            adapter, self.optimizer,
            quantize_boundary=cfg.quantize_boundary)
        self._batch_idx = jnp.zeros((), jnp.int32)
        self._fns: Dict[int, Any] = {}
        self.metrics = MetricsRegistry("sim", parent=global_registry())
        self.metrics.gauge("n_sats").set(self.n_sats)
        self.recorder = FlightRecorder(self.metrics)
        self._passes_done = 0      # absolute pass count across chained runs

    # ------------------------------------------------------- the program
    def _compiled(self, n_revolutions: int):
        """The jitted (revolution × ring-slot) closed loop for R
        revolutions; cached per R (same trace serves every run)."""
        fn = self._fns.get(n_revolutions)
        if fn is not None:
            return fn

        cfg = self.cfg
        N, K = self.n_sats, self._scan_steps
        pass_step = self._pass_step
        batch_fn = self.batch_fn
        recharge_j = jnp.float32(cfg.recharge_w
                                 * self.budget.plane.pass_duration_s)
        reserve = jnp.float32(cfg.reserve_j)
        cap = jnp.float32(cfg.battery_j)
        step_ids = jnp.arange(K, dtype=jnp.int32)

        def pass_body(carry, sat):
            state, energy, bidx, ring, plan = carry
            # energy policy first, exactly like the host scheduler: below
            # reserve => the whole pass is a masked no-op (the segment
            # still "moves on" — it's the carry)
            skip = energy.battery_j[sat] < reserve
            n_valid = jnp.where(skip, 0,
                                jnp.minimum(plan.n_steps[sat], K))

            def step_body(st, j):
                return pass_step(st, batch_fn(sat, bidx + j), j < n_valid)

            state, losses = jax.lax.scan(step_body, state, step_ids)
            valid = step_ids < n_valid
            loss = jnp.where(
                skip, jnp.nan,
                jnp.where(valid, losses, 0.0).sum()
                / jnp.maximum(n_valid, 1).astype(jnp.float32))

            energy = es_mod.apply_pass(energy, sat, plan.drain_j[sat],
                                       plan.e_total_j[sat], cap, ~skip)
            energy = es_mod.recharge(energy, recharge_j, cap)
            bidx = bidx + n_valid
            action = jnp.where(
                skip, ACTION_SKIPPED,
                jnp.where(plan.kept_fraction[sat] < 1.0, ACTION_SHED,
                          ACTION_TRAINED)).astype(jnp.int32)
            telem = PassTelemetry(action=action, loss=loss,
                                  battery_j=energy.battery_j[sat],
                                  n_steps=n_valid)
            # flight recorder: one EV_PASS per pass; the ring's own
            # cursor IS the dispatch-local pass index (every pass
            # records exactly once), rebased to the run timeline by
            # the host at ingest
            ring = ring_record(
                ring, EV_PASS, ring.cursor, sat,
                (action.astype(jnp.float32), energy.battery_j[sat], loss,
                 n_valid.astype(jnp.float32), plan.kept_fraction[sat],
                 0.0, 1.0, 0.0))
            return (state, energy, bidx, ring, plan), telem

        def rev_body(carry, _):
            return jax.lax.scan(pass_body, carry,
                                jnp.arange(N, dtype=jnp.int32))

        def closed_loop(state, energy, bidx, ring, plan):
            # side effect fires at trace time
            self.metrics.inc("traces")
            carry, telem = jax.lax.scan(rev_body,
                                        (state, energy, bidx, ring, plan),
                                        None, length=n_revolutions)
            state, energy, bidx, ring, _ = carry
            return state, energy, bidx, ring, telem

        fn = jax.jit(closed_loop, donate_argnums=(0, 1, 3))
        self._fns[n_revolutions] = fn
        return fn

    # --------------------------------------------------------------- run
    def run(self, n_revolutions: Optional[int] = None, *,
            stream_telemetry: bool = False) -> DeviceSimResult:
        """Run R closed-loop revolutions; chainable (state persists).

        ``stream_telemetry=True`` dispatches one revolution at a time
        and syncs its telemetry (exactly one host sync per revolution —
        long 1000-sat studies stay observable); the default runs all R
        revolutions in one dispatch with a single sync at the end.
        """
        R = self.cfg.n_revolutions if n_revolutions is None else n_revolutions
        if R < 1:
            raise ValueError("need at least one revolution")
        self.state._require_live("device closed loop")
        state = dedupe_state_buffers(self.state)
        self.state.mark_consumed()
        energy, bidx = self.energy, self._batch_idx

        chunks = []
        r_chunk = 1 if stream_telemetry else R
        fn = self._compiled(r_chunk)
        for _ in range(R if stream_telemetry else 1):
            # the ring is donated with the carry: a fresh (empty) one
            # per dispatch, flushed whole at the telemetry sync below
            ring = ring_init(r_chunk * self.n_sats)
            t0 = time.perf_counter()
            state, energy, bidx, ring, telem = fn(state, energy, bidx,
                                                  ring, self.plan)
            # commit the carry per dispatch: an interrupted streaming
            # study keeps every completed revolution and stays chainable
            self.state, self.energy, self._batch_idx = state, energy, bidx
            self.metrics.inc("device_calls")
            chunks.append(jax.tree.map(np.asarray, telem))   # the ONE sync
            self.metrics.inc("host_syncs")
            self.metrics.histogram("dispatch_s").record(
                time.perf_counter() - t0)
            # ring flush rides the same sync boundary — no extra sync
            self.recorder.ingest(ring, t_offset=self._passes_done)
            self._passes_done += r_chunk * self.n_sats

        telem = jax.tree.map(lambda *xs: np.concatenate(xs), *chunks)
        return DeviceSimResult(
            action=telem.action, loss=telem.loss,
            battery_j=telem.battery_j, n_steps=telem.n_steps,
            plan=self.plan.to_host(),
            energy=EnergyState(*[np.asarray(a) for a in energy]),
            state=state)


def _smoke(argv=None) -> None:                     # pragma: no cover
    """``python -m repro.sim.device_sim --smoke``: a fast host-vs-device
    closed-loop parity check (8 sats × 2 revolutions) for CI."""
    import time

    from repro.core.constellation import (ConstellationConfig,
                                          ConstellationSim)
    from repro.core.orbits import OrbitalPlane
    from repro.core.sl_step import autoencoder_adapter
    from repro.sim.data import DeviceImageryShards

    shards = DeviceImageryShards(img=32, batch=4)
    adapter = autoencoder_adapter(cut=5, img=32)
    # n_items scales the per-pass satellite drain to ~48 J so the 200 J
    # batteries hit the reserve-skip policy mid-run (max_steps_per_pass
    # caps the simulated compute; the allocation itself is per-item)
    budget = PassBudget(plane=OrbitalPlane(n_sats=4), n_items=4e6)

    def sim():
        return ConstellationSim(adapter, budget, shards, ConstellationConfig(
            n_passes=16, batch_size=4, battery_j=200.0, recharge_w=0.01,
            reserve_j=150.0, max_steps_per_pass=4))

    t0 = time.time()
    host = sim()
    host.run()
    hs = host.summary()
    t1 = time.time()
    dev = sim()
    dev.run(engine="device")
    ds = dev.summary()
    t2 = time.time()

    eng = dev.device_engine
    print(f"host   {t1 - t0:6.1f}s  {hs}")
    print(f"device {t2 - t1:6.1f}s  {ds}  "
          f"(traces={eng.traces}, syncs={eng.host_syncs})")
    actions = [(h.action, d.action) for h, d in zip(host.records,
                                                    dev.records)]
    assert all(h == d for h, d in actions), actions
    assert hs["skipped"] == ds["skipped"] and hs["skipped"] > 0, actions
    np.testing.assert_allclose(ds["loss_last"], hs["loss_last"],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ds["E_total_J"], hs["E_total_J"], rtol=1e-5)
    assert eng.traces == 1 and eng.host_syncs <= eng.cfg.n_revolutions
    print("device-sim smoke: OK (host == device closed loop)")


if __name__ == "__main__":                          # pragma: no cover
    import sys

    _smoke(sys.argv[1:])
