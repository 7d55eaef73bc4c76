"""Sharded elastic fleet engine: a constellation on a device mesh.

The PR-4 device engine (:mod:`repro.sim.device_sim`) runs ONE static
ring on ONE device; elastic membership and random failures stayed
host-oracle features, and multi-plane constellations meant multiple
independent runs.  This module is the path from "one ring on one chip"
to "a constellation on a mesh":

* **Elastic + faults on device** — the scan carry grows a per-slot
  ``failed`` mask; combined with the precomputed join/leave schedule
  (:mod:`repro.fleet.events`) it yields each pass's aliveness mask, and
  the serving slot is computed *inside* the scan as the host's
  ``ring[k % len(ring)]``.  A seeded failure stream (the host oracle's
  own ``numpy`` draws, realized per plane) flips slots dead mid-run; a
  dead or absent slot's pass masks through the shared step kernel
  (``SLTrainState.apply_updates(where=)``), so the successor trains
  through unchanged — checkpoint restoration is the carry itself.
* **Plane-sharded execution** — a :class:`FleetConfig` of P planes × N
  sats lays the :class:`~repro.sim.energy_state.EnergyState`, the
  :class:`~repro.sim.device_sim.DevicePassPlan` and the per-plane data
  cursors out as ``(P, ...)`` arrays sharded over a
  ``launch/mesh.make_fleet_mesh`` plane axis
  (``jax.sharding.NamedSharding``); every plane runs its ring's closed
  loop under one ``vmap``, so the whole fleet advances as ONE jitted
  (revolution × pass) scan that the host waits for once per revolution
  (``stream_telemetry``) or once per run.
* **Inter-plane ISL exchange** — at revolution boundaries
  (``avg_every``) the segment checkpoints are averaged across the
  plane axis (:func:`average_planes`, an all-reduce over the mesh) —
  the paper's inter-plane ISL checkpoint exchange.
* **Heterogeneous planning** — all P×M problem-(13) instances are shed
  and solved in one device call
  (:func:`~repro.sim.device_sim.plan_ring_passes` with a ``(P, M)``
  row shape), with per-satellite measured ``dtx_bits`` rows (e.g. from
  :func:`~repro.core.sl_step.ring_boundary_bits`) planning mixed
  payloads in the same solve.

The host :class:`~repro.core.constellation.ConstellationSim` stays the
parity oracle: one host sim per plane (seeded ``seed + p``) must
reproduce the fleet's action/skip/fail sequences, losses and battery
trajectories — ``ConstellationSim.run(engine="device")`` now delegates
elastic runs here (P=1) instead of refusing them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy import PassBudget, clamp_battery
from repro.obs.metrics import (MetricsRegistry, counter_property,
                               global_registry, to_host)
from repro.obs.ring import (EV_EXCHANGE, EV_PASS, FlightRecorder,
                            record as ring_record, ring_init)
from repro.core.sl_step import (SplitAdapter, dedupe_state_buffers,
                                make_pass_step)
from repro.core.train_state import SLTrainState
from repro.fleet.events import EventSchedule, build_event_schedule
from repro.fleet.scenarios import (ScenarioConfig, aggregate_planes,
                                   build_scenario_schedule,
                                   epidemic_step as scn_epidemic_step)
from repro.isl.codec import delta_payload_bits
from repro.isl.exchange import (ExchangeConfig, async_gossip_step,
                                exchange_init, null_exchange_state,
                                sync_exchange_step)
from repro.launch.mesh import make_fleet_mesh, plane_sharding
from repro.sim import energy_state as es_mod
from repro.sim.device_sim import (ACTION_FAILED, ACTION_FAULT, ACTION_SHED,
                                  ACTION_SKIPPED, ACTION_TRAINED,
                                  DevicePassPlan, measure_and_plan)
from repro.sim.energy_state import EnergyState
from repro.train.optimizer import resolve_optimizer


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Knobs of a P-plane elastic constellation run.

    The steady-state fields mirror
    :class:`~repro.sim.device_sim.DeviceSimConfig`; the elastic fields
    mirror the host :class:`~repro.core.constellation
    .ConstellationConfig` (``join_events`` / ``leave_events`` /
    ``fail_prob`` / ``join_battery_frac``) — the SAME schedules drive
    both engines, which is what makes the host the parity oracle.
    Plane ``p``'s failure stream is seeded ``seed + p``.
    """

    n_planes: int = 1
    n_revolutions: int = 1
    lr: float = 1e-2
    optimizer: Any = "sgd"
    quantize_boundary: bool = False
    battery_j: float = 5_000.0
    recharge_w: float = 20.0
    reserve_j: float = 100.0
    # cap on the planned steps of a pass (None = uncapped); the static
    # scan length is the plan's own largest step count
    max_steps_per_pass: Optional[int] = 128
    min_fraction: float = 0.05
    seed: int = 0
    # ---- elastic membership / fault injection (host-oracle parity) ----
    fail_prob: float = 0.0
    join_events: Dict[int, int] = dataclasses.field(default_factory=dict)
    leave_events: Dict[int, int] = dataclasses.field(default_factory=dict)
    join_battery_frac: float = 1.0
    # seed+p failure streams (host-parity default) vs collision-free
    # SeedSequence.spawn streams — see fleet/events.py
    legacy_streams: bool = True
    # ---- fleet structure ----------------------------------------------
    # passes per revolution (telemetry/streaming/averaging granularity);
    # None = the initial ring size
    passes_per_revolution: Optional[int] = None
    # inter-plane checkpoint averaging period, in revolutions; 0 = off
    avg_every: int = 1
    # ---- degraded-ops scenario (fleet/scenarios.py) -------------------
    # eclipse windows + Byzantine slots + epidemic faults; None = the
    # cooperative, permanently-sunlit baseline (host-parity default)
    scenario: Optional[ScenarioConfig] = None
    # inter-plane aggregation: "mean" (parity default) | "median" |
    # "trimmed_mean" — see fleet/scenarios.aggregate_planes
    aggregate: str = "mean"
    # ---- ISL comms subsystem (repro.isl) ------------------------------
    # modeled inter-plane exchange: contact windows, compressed deltas,
    # metered bits/joules charged to the shared batteries and priced
    # into the problem-(13) plan.  None = the free, instantaneous
    # legacy barrier above (host-parity default).
    exchange: Optional[ExchangeConfig] = None


class FleetTelemetry(NamedTuple):
    """Per-pass scan outputs; stacked to (R, L, P) by the nested scan."""

    action: Any               # int32 ACTION_* code
    sat: Any                  # int32 serving slot id (-1: ring empty)
    loss: Any                 # float32 mean loss (NaN unless trained)
    battery_j: Any            # float32 serving sat battery at pass end
    n_steps: Any              # int32 fused steps executed
    n_infected: Any           # int32 epidemic-faulted slots this pass


def average_planes(tree):
    """Inter-plane checkpoint averaging over the leading plane axis —
    the ``mode="mean"`` case of
    :func:`repro.fleet.scenarios.aggregate_planes` (kept as the named
    parity default; robust runs select ``median`` / ``trimmed_mean``
    via ``FleetConfig.aggregate``)."""
    return aggregate_planes(tree, "mean")


@dataclasses.dataclass
class FleetResult:
    """Host-side view of one fleet run (synced telemetry).

    Per-pass arrays are ``(P, K)`` — plane-major, pass index within the
    plane's own K-pass timeline; per-slot arrays are ``(P, M)``.
    """

    action: np.ndarray        # (P, K) int32 ACTION_* codes
    sat: np.ndarray           # (P, K) serving slot (-1: ring empty)
    loss: np.ndarray          # (P, K) NaN unless trained
    battery_j: np.ndarray     # (P, K) serving sat battery at pass end
    n_steps: np.ndarray       # (P, K)
    n_infected: np.ndarray    # (P, K) epidemic-faulted slots per pass
    plan: DevicePassPlan      # (P, M) host copies
    energy: EnergyState       # (P, M) final fleet state, host copies
    failed: np.ndarray        # (P, M) final failure mask
    fault_ttl: np.ndarray     # (P, M) final epidemic recovery counters
    state: Any                # final SLTrainState, (P, ...) leaves
    isl_bits: Optional[np.ndarray] = None      # (P,) pushed wire bits
    isl_e_j: Optional[np.ndarray] = None       # (P,) ISL transmit joules
    isl_contacts: Optional[np.ndarray] = None  # (P,) successful pushes

    def summary(self) -> Dict[str, Any]:
        """Fleet-wide roll-up, same shape as ``ConstellationSim.summary``
        (loss_first/loss_last are time-ordered across the fleet)."""
        trained = (self.action == ACTION_TRAINED) | \
                  (self.action == ACTION_SHED)
        # time-major flatten so first/last match the host's pass order
        t_order = trained.T.reshape(-1)
        losses = self.loss.T.reshape(-1)[t_order]
        p_idx, k_idx = np.nonzero(trained)
        sats = self.sat[p_idx, k_idx]
        return {
            "passes": int(self.action.size),
            "trained": int(trained.sum()),
            "skipped": int((self.action == ACTION_SKIPPED).sum()),
            "failed": int((self.action == ACTION_FAILED).sum()),
            "faulted": int((self.action == ACTION_FAULT).sum()),
            "loss_first": float(losses[0]) if losses.size else None,
            "loss_last": float(losses[-1]) if losses.size else None,
            "E_total_J": float(self.plan.e_total_j[p_idx, sats].sum()),
            "E_comm_J": float(self.plan.e_comm_j[p_idx, sats].sum()),
            "E_proc_J": float(self.plan.e_proc_j[p_idx, sats].sum()),
            "E_isl_J": float(self.plan.e_isl_j[p_idx, sats].sum()),
            # measured exchange meter (repro.isl) — 0 when the legacy
            # free barrier (exchange=None) ran
            "ISL_exchange_bits": (float(self.isl_bits.sum())
                                  if self.isl_bits is not None else 0.0),
            "ISL_exchange_J": (float(self.isl_e_j.sum())
                               if self.isl_e_j is not None else 0.0),
        }


class FleetEngine:
    """P orbital planes × an elastic M-slot ring each, as ONE program.

    ``batch_fn(sat, idx) -> batch`` must be traceable (the same
    contract as :class:`~repro.sim.device_sim.DeviceConstellationSim`);
    plane ``p``'s slot ``m`` reads global satellite id ``p * M + m``,
    so a per-plane host oracle is simply the same provider with its sat
    ids offset.  ``state`` is a *single-copy*
    :class:`~repro.core.train_state.SLTrainState`; the engine
    replicates it to a ``(P, ...)``-leading fleet state sharded over
    the plane mesh axis.

    Observability: every pass records an ``EV_PASS`` event (and every
    inter-plane exchange an ``EV_EXCHANGE`` marker) into a per-plane
    :class:`~repro.obs.ring.TelemetryRing` sharded with the carry,
    flushed into ``self.recorder`` after the telemetry sync.  The
    counters live on ``self.metrics`` (namespace ``fleet``):
    ``traces``, ``device_calls``, ``host_syncs`` — the points where the
    host waits for the device, ≤ 1 per revolution as in the static
    engine — and ``d2h_arrays`` / ``d2h_bytes``, every device→host copy
    (:func:`~repro.obs.metrics.to_host`): a revolution's telemetry, its
    ring and the :class:`FleetResult` are separate copies (11 arrays a
    revolution, 9 a result, and the plan's 11 once: JAX keeps their
    host copy).  Host spans
    (:meth:`~repro.obs.metrics.MetricsRegistry.span`, in the profiler's
    trace and as ``<span>_s`` histograms): ``fleet.init`` ⊃
    ``fleet.plan``; ``fleet.run`` ⊃ per dispatch ``fleet.revolution``
    (its stat ``revolution`` the absolute index of its first revolution)
    ⊃ ``fleet.launch``, ``fleet.telemetry_sync``, ``fleet.ring_flush``;
    then ``fleet.result``.  ``first_launch_s`` holds the first launch of
    each compiled program (tracing, lowering, compiling or loading it).
    The device program's ops carry ``jax.named_scope`` paths: ``batch``,
    ``policy``, ``ring_record``, ``isl.exchange`` here and the pass
    step's ``sat_fwd`` / ``ground`` / ``sat_bwd`` / ``update``
    (:func:`~repro.core.sl_step.make_pass_step`).
    """

    traces = counter_property("traces")
    device_calls = counter_property("device_calls")
    host_syncs = counter_property("host_syncs")

    def __init__(self, adapter: SplitAdapter, budget: PassBudget,
                 batch_fn: Callable[[Any, Any], Dict],
                 cfg: Optional[FleetConfig] = None, *,
                 state: Optional[SLTrainState] = None,
                 plan: Optional[DevicePassPlan] = None,
                 dtx_bits=None, schedule: Optional[EventSchedule] = None,
                 mesh=None, plane_axis: str = "plane",
                 battery0=None, failed0=None):
        self.metrics = MetricsRegistry("fleet", parent=global_registry())
        with self.metrics.span("init"):
            self._build(adapter, budget, batch_fn, cfg, state=state,
                        plan=plan, dtx_bits=dtx_bits, schedule=schedule,
                        mesh=mesh, plane_axis=plane_axis,
                        battery0=battery0, failed0=failed0)

    def _build(self, adapter, budget, batch_fn, cfg, *, state, plan,
               dtx_bits, schedule, mesh, plane_axis, battery0, failed0):
        cfg = FleetConfig() if cfg is None else cfg
        self.adapter = adapter
        self.budget = budget
        self.batch_fn = batch_fn
        self.cfg = cfg
        self.n_planes = int(cfg.n_planes)
        # slot layout follows the schedule (a chained delegation's ring
        # may already carry joiners beyond the configured plane); the
        # eq.-(5) ISL physics below stays pinned to budget.plane.n_sats
        self.n_initial = (budget.plane.n_sats if schedule is None
                          else schedule.n_initial)
        self.rev_len = (self.n_initial if cfg.passes_per_revolution is None
                        else int(cfg.passes_per_revolution))
        self.n_passes = cfg.n_revolutions * self.rev_len

        if schedule is None:
            schedule = build_event_schedule(
                self.n_initial, self.n_passes,
                join_events=cfg.join_events, leave_events=cfg.leave_events,
                fail_prob=cfg.fail_prob, n_planes=self.n_planes,
                seed=cfg.seed, legacy_streams=cfg.legacy_streams)
        if schedule.n_planes != self.n_planes:
            raise ValueError(f"schedule covers {schedule.n_planes} planes "
                             f"but the fleet has {self.n_planes}")
        self.schedule = schedule
        self.n_slots = schedule.n_slots
        P, M = self.n_planes, self.n_slots
        aggregate_planes({}, cfg.aggregate)   # validate the mode early
        self.scenario_schedule = build_scenario_schedule(
            cfg.scenario, P, M, schedule.n_passes, seed=cfg.seed)

        self.optimizer = resolve_optimizer(cfg.optimizer, lr=cfg.lr)
        if state is None:
            pa, pb = adapter.init(jax.random.key(cfg.seed))
            state = SLTrainState.create(pa, pb, self.optimizer)

        # ---- ISL exchange statics (repro.isl) --------------------------
        # wire bits, contact capacity and per-push transmit energy are
        # shape-static, so they are Python floats baked into the trace;
        # a payload over the contact capacity disables the exchange
        # outright (hard bandwidth limit, not a price), and the
        # amortized per-pass bit volume feeds the problem-(13) planner
        # below so the codec choice changes the planned allocation
        exch = cfg.exchange
        self.exchange = exch
        self._ex_bits = 0.0
        self._ex_energy_j = 0.0
        self._ex_cap_bits = float("inf")
        self._ex_fits = False
        isl_extra_bits = 0.0
        if exch is not None:
            ptree = (state.params_a, state.params_b)
            self._ex_bits = delta_payload_bits(ptree, exch.codec)
            self._ex_cap_bits = exch.contact.capacity_bits(budget.isl,
                                                           budget.link)
            self._ex_fits = self._ex_bits <= self._ex_cap_bits
            if self._ex_fits:
                self._ex_energy_j = exch.contact.tx_energy_j(
                    self._ex_bits, budget.isl, budget.link)
                isl_extra_bits = (self._ex_bits
                                  * exch.mean_contacts_per_pass(
                                      self.rev_len, int(cfg.avg_every)))
        self._ex_on = exch is not None and self._ex_fits and P > 1

        # measured costs + plan + scan sizing via the construction block
        # shared with the single-ring engine; all P*M problem-(13)
        # instances shed + solve in ONE device call, with eq. (5)
        # priced off the configured plane (host parity)
        self.dtx_bits = dtx_bits
        with self.metrics.span("plan"):
            self.batch_size, self.costs, self.plan, self._scan_steps = \
                measure_and_plan(adapter, budget, batch_fn,
                                 quantize_boundary=cfg.quantize_boundary,
                                 params_a=state.params_a, n_sats=(P, M),
                                 ring_n=budget.plane.n_sats,
                                 dtx_bits=dtx_bits,
                                 max_steps_per_pass=cfg.max_steps_per_pass,
                                 min_fraction=cfg.min_fraction, plan=plan,
                                 isl_extra_bits=isl_extra_bits)
        if tuple(self.plan.n_steps.shape) != (P, M):
            raise ValueError(f"plan shape {self.plan.n_steps.shape} != "
                             f"fleet layout ({P}, {M})")

        # ---- mesh + (P, ...) layout ------------------------------------
        self.mesh = make_fleet_mesh(P) if mesh is None else mesh
        axis_size = dict(zip(self.mesh.axis_names,
                             self.mesh.devices.shape))[plane_axis]
        if P % axis_size:
            raise ValueError(
                f"{P} planes cannot shard evenly over the {axis_size}-way "
                f"'{plane_axis}' mesh axis; use make_fleet_mesh({P})")
        self._shard = plane_sharding(self.mesh, plane_axis)
        put = lambda t: jax.device_put(t, self._shard)    # noqa: E731

        self.state = put(jax.tree.map(
            lambda x: jnp.broadcast_to(jnp.asarray(x)[None],
                                       (P,) + jnp.shape(x)), state))
        battery = np.full((P, M), cfg.battery_j, np.float32)
        battery[:, self.n_initial:] = clamp_battery(
            cfg.battery_j * cfg.join_battery_frac, cfg.battery_j)
        if battery0 is not None:
            battery[:, :self.n_initial] = np.broadcast_to(
                np.asarray(battery0, np.float32), (P, self.n_initial))
        self.energy = put(EnergyState(
            battery_j=jnp.asarray(battery),
            energy_spent_j=jnp.zeros((P, M), jnp.float32),
            passes_served=jnp.zeros((P, M), jnp.int32),
            passes_skipped=jnp.zeros((P, M), jnp.int32)))
        failed = np.zeros((P, M), bool)
        if failed0 is not None:
            failed[:, :self.n_initial] = np.broadcast_to(
                np.asarray(failed0, bool), (P, self.n_initial))
        self._failed = put(jnp.asarray(failed))
        self._fail_mask = put(jnp.asarray(schedule.fail_mask))
        self._batch_idx = put(jnp.zeros((P,), jnp.int32))
        # the pass index is replicated on the mesh, as the program
        # returns it, so every dispatch after the first hits the trace
        self._pass_idx = jax.device_put(
            jnp.zeros((), jnp.int32),
            jax.sharding.NamedSharding(self.mesh,
                                       jax.sharding.PartitionSpec()))
        # epidemic recovery counters ride the carry; the precomputed
        # spread draws and the static Byzantine mask ship as sharded
        # inputs so the scan reads its own plane's rows
        self._ttl = put(jnp.zeros((P, M), jnp.int32))
        self._spread = put(jnp.asarray(self.scenario_schedule.spread_draw))
        self._byz = put(jnp.asarray(self.scenario_schedule.byz_mask))
        self.plan = put(self.plan)
        # exchange carry: anchors/residuals/meters ride the scan like
        # any other state (empty trees when the exchange is off, so the
        # scan signature never changes shape)
        self._ex_state = put(
            exchange_init((self.state.params_a, self.state.params_b), P)
            if self._ex_on else null_exchange_state(P))

        self._pass_step = make_pass_step(
            adapter, self.optimizer,
            quantize_boundary=cfg.quantize_boundary)
        # stateless streams for beyond-horizon draws: fold_in on the
        # pass index (and plane) means chained runs need no RNG carry.
        # Built here, not inside the traced program — the scan bodies
        # stay host-op-free (scripts/lint_scan_purity.py).
        base_key = jax.random.key(np.uint32(cfg.seed))
        self._fail_key = jax.random.fold_in(base_key, 1)
        self._spread_key = jax.random.fold_in(base_key, 2)
        self._noise_key = jax.random.fold_in(base_key, 3)
        self._fns: Dict[int, Any] = {}
        self._launched = set()        # R of the programs launched once
        self._revolution = 0          # revolutions dispatched so far
        self.metrics.gauge("n_planes").set(P)
        self.metrics.gauge("n_slots").set(M)
        self.recorder = FlightRecorder(self.metrics)

    # ------------------------------------------------------- the program
    def _compiled(self, n_revolutions: int):
        """The jitted (revolution × pass) fleet loop for R revolutions,
        vmapped over planes; cached per R."""
        fn = self._fns.get(n_revolutions)
        if fn is not None:
            return fn

        cfg = self.cfg
        P, M, L = self.n_planes, self.n_slots, self.rev_len
        K = self._scan_steps
        pass_step = self._pass_step
        batch_fn = self.batch_fn
        avg_every = int(cfg.avg_every)
        horizon = self.schedule.n_passes
        recharge_j = jnp.float32(cfg.recharge_w
                                 * self.budget.plane.pass_duration_s)
        reserve = jnp.float32(cfg.reserve_j)
        cap = jnp.float32(cfg.battery_j)
        step_ids = jnp.arange(K, dtype=jnp.int32)
        plane_ids = jnp.arange(P, dtype=jnp.int32)
        join_pass = jnp.asarray(self.schedule.join_pass, jnp.int32)
        leave_pass = jnp.asarray(self.schedule.leave_pass, jnp.int32)
        # static scenario structure (Python-level: absent stressors are
        # dead code, so a scenario-free fleet compiles to the same
        # program as before)
        scn = cfg.scenario
        eclipse = None if scn is None else scn.eclipse
        byz_cfg = None if scn is None else scn.byzantine
        epidemic = None if scn is None else scn.epidemic
        init_mask = jnp.asarray(self.scenario_schedule.init_mask)
        fail_prob = float(cfg.fail_prob)
        fail_key = self._fail_key
        spread_key = self._spread_key
        noise_key = self._noise_key
        # ISL exchange statics: an inactive exchange (off / over
        # capacity / single plane) is dead code, so the program matches
        # the legacy one exactly
        exch = self.exchange if self._ex_on else None
        ex_async = exch is not None and exch.mode == "async"
        ex_sync = exch is not None and exch.mode == "sync"
        ex_bits = float(self._ex_bits)
        ex_e_j = float(self._ex_energy_j)
        battery_cap = float(cfg.battery_j)

        def corrupt_params(new_tree, old_tree, lie, plane, k, salt):
            """Byzantine injection at the pass kernel: where ``lie``,
            replace the pass delta Δ with -scale·Δ (sign_flip) or add
            scale·N(0,1) per float leaf (scaled_noise)."""
            scale = jnp.float32(byz_cfg.scale)
            leaves, treedef = jax.tree.flatten(new_tree)
            old_leaves = jax.tree.leaves(old_tree)
            out = []
            for i, (new, old) in enumerate(zip(leaves, old_leaves)):
                if not jnp.issubdtype(new.dtype, jnp.floating):
                    out.append(new)
                    continue
                if byz_cfg.mode == "sign_flip":
                    bad = old - scale * (new - old)
                else:       # scaled_noise
                    kk = jax.random.fold_in(jax.random.fold_in(
                        jax.random.fold_in(noise_key, k), plane),
                        2 * i + salt)
                    bad = new + scale * jax.random.normal(
                        kk, new.shape, new.dtype)
                out.append(jnp.where(lie, bad, new))
            return jax.tree.unflatten(treedef, out)

        def closed_loop(state, energy, failed, ttl, bidx, k, ring, ex,
                        plan, fail_mask, spread, byz):
            # side effect fires at trace time
            self.metrics.inc("traces")

            def plane_pass(plane, fail_k, spread_k, byz_row, state,
                           energy, failed, ttl, bidx, ring, plan, k):
                # epidemic dynamics first: faults spread along the slot
                # ring gated by the precomputed prefix draws, or by
                # in-scan jax.random draws beyond the horizon — chained
                # runs stay fault-active
                with jax.named_scope("policy"):
                    faulted_m = jnp.zeros((M,), bool)
                    if epidemic is not None:
                        live = jax.random.uniform(
                            jax.random.fold_in(
                                jax.random.fold_in(spread_key, k), plane),
                            (M,)) < epidemic.beta
                        draw = jnp.where(k < horizon, spread_k, live)
                        faulted_m, ttl = scn_epidemic_step(
                            ttl, draw, k, epidemic, init_mask, xp=jnp)

                    # membership next, exactly like the host scheduler:
                    # joins and leaves apply at pass start, then the
                    # serving slot is ring[k % len(ring)] over the alive
                    # slots in slot order
                    member = (join_pass <= k) & (k < leave_pass) & ~failed
                    n_alive = member.sum()
                    served = n_alive > 0
                    rank = jnp.where(served, k % jnp.maximum(n_alive, 1), 0)
                    cums = jnp.cumsum(member.astype(jnp.int32))
                    slot = jnp.argmax((cums == rank + 1)
                                      & member).astype(jnp.int32)

                    # the host's decision order: seeded failure draw,
                    # then the transient epidemic fault, then the
                    # reserve-skip policy, then the planned masked pass
                    fail = served & fail_k
                    fault = served & ~fail & faulted_m[slot]
                    skip = energy.battery_j[slot] < reserve
                    trains = served & ~fail & ~fault & ~skip
                    n_valid = jnp.where(
                        trains, jnp.minimum(plan.n_steps[slot], K), 0)

                def step_body(st, j):
                    with jax.named_scope("batch"):
                        batch = batch_fn(plane * M + slot, bidx + j)
                    return pass_step(st, batch, j < n_valid)

                old_state = state
                state, losses = jax.lax.scan(step_body, state, step_ids)
                valid = step_ids < n_valid
                loss = jnp.where(
                    trains,
                    jnp.where(valid, losses, 0.0).sum()
                    / jnp.maximum(n_valid, 1).astype(jnp.float32),
                    jnp.nan)

                if byz_cfg is not None:
                    # a Byzantine serving slot corrupts the update its
                    # pass just produced (params only; its optimizer
                    # state stays the honest trajectory's)
                    lie = byz_row[slot] & trains
                    state = state.replace(
                        params_a=corrupt_params(
                            state.params_a, old_state.params_a, lie,
                            plane, k, 0),
                        params_b=corrupt_params(
                            state.params_b, old_state.params_b, lie,
                            plane, k, 1))

                with jax.named_scope("policy"):
                    failed = failed.at[slot].set(failed[slot] | fail)
                    energy = es_mod.apply_pass(
                        energy, slot, plan.drain_j[slot],
                        plan.e_total_j[slot], cap, trains,
                        skipped=served & ~fail & ~fault & skip)
                    # recharge this pass's members that are still alive
                    # (a slot that just failed collects nothing — it is
                    # dead); an eclipsed plane harvests nothing at all,
                    # which is how orbital shadow reaches the
                    # reserve-skip policy
                    sunlit = (None if eclipse is None
                              else eclipse.sunlit(k, plane))
                    energy = es_mod.recharge(energy, recharge_j, cap,
                                             member_mask=member & ~failed,
                                             sunlit=sunlit)
                bidx = bidx + n_valid
                action = jnp.where(
                    ~served | fail, ACTION_FAILED,
                    jnp.where(fault, ACTION_FAULT,
                              jnp.where(skip, ACTION_SKIPPED,
                                        jnp.where(
                                            plan.kept_fraction[slot] < 1.0,
                                            ACTION_SHED, ACTION_TRAINED)))
                ).astype(jnp.int32)
                telem = FleetTelemetry(
                    action=action,
                    sat=jnp.where(served, slot, -1).astype(jnp.int32),
                    loss=loss,
                    battery_j=jnp.where(served, energy.battery_j[slot],
                                        jnp.nan),
                    n_steps=n_valid,
                    n_infected=faulted_m.sum().astype(jnp.int32))
                # flight recorder: one EV_PASS per (plane, pass) into
                # this plane's ring (t is the absolute pass index, so
                # chained runs land on one timeline with no rebasing)
                with jax.named_scope("ring_record"):
                    ring = ring_record(
                        ring, EV_PASS, k, telem.sat,
                        (action.astype(jnp.float32), telem.battery_j, loss,
                         n_valid.astype(jnp.float32),
                         plan.kept_fraction[slot],
                         (fail | fault).astype(jnp.float32),
                         (jnp.float32(1.0) if sunlit is None
                          else sunlit.astype(jnp.float32)),
                         faulted_m.sum().astype(jnp.float32)))
                return (state, energy, failed, ttl, bidx, ring), telem

            vpass = jax.vmap(
                plane_pass,
                in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, None))

            def pass_body(carry, _):
                state, energy, failed, ttl, bidx, k, ring, ex = carry
                # scheduled failures fire inside the precomputed prefix
                # (bit-parity with the host oracle); beyond it the
                # stream refreshes from jax.random so chained runs keep
                # drawing failures at the same rate
                with jax.named_scope("policy"):
                    fail_k = (jnp.take(fail_mask,
                                       jnp.minimum(k, horizon - 1), axis=1)
                              & (k < horizon))
                    if fail_prob > 0.0:
                        live = jax.random.uniform(
                            jax.random.fold_in(fail_key, k),
                            (P,)) < fail_prob
                        fail_k = fail_k | (live & (k >= horizon))
                    spread_k = jnp.take(
                        spread, jnp.minimum(k, spread.shape[1] - 1), axis=1)
                (state, energy, failed, ttl, bidx, ring), telem = vpass(
                    plane_ids, fail_k, spread_k, byz, state, energy,
                    failed, ttl, bidx, ring, plan, k)
                if ex_async:
                    # contact-window gossip (repro.isl): compressed
                    # delta push + staleness-discounted merge + battery
                    # charge, every pass the window opens — no barrier
                    with jax.named_scope("isl.exchange"):
                        state, ex, energy, ring = async_gossip_step(
                            exch, state, ex, energy, ring, k, telem.sat,
                            telem.action, wire_bits=ex_bits,
                            e_push_j=ex_e_j, battery_cap=battery_cap,
                            n_planes=P, action_failed=ACTION_FAILED)
                return (state, energy, failed, ttl, bidx, k + 1,
                        ring, ex), telem

            def rev_body(carry, _):
                carry, telem = jax.lax.scan(pass_body, carry, None,
                                            length=L)
                state, energy, failed, ttl, bidx, k, ring, ex = carry
                if ex_sync and avg_every > 0:
                    # the revolution-boundary exchange, codec'd and
                    # metered (repro.isl): compressed delta
                    # reconstructions cross the link, the pushing slot
                    # pays the transmit energy
                    do = (k // L) % avg_every == 0
                    with jax.named_scope("isl.exchange"):
                        state, ex, energy, ring = sync_exchange_step(
                            exch, cfg.aggregate, state, ex, energy, ring,
                            k, telem.sat[-1], telem.action[-1], do,
                            wire_bits=ex_bits, e_push_j=ex_e_j,
                            battery_cap=battery_cap, n_planes=P,
                            action_failed=ACTION_FAILED)
                elif cfg.exchange is None and avg_every > 0 and P > 1:
                    # inter-plane ISL exchange at the revolution
                    # boundary — robust modes (median / trimmed_mean)
                    # are what survive Byzantine planes
                    do = (k // L) % avg_every == 0
                    with jax.named_scope("isl.exchange"):
                        state = jax.tree.map(
                            lambda a, o: jnp.where(do, a, o),
                            aggregate_planes(state, cfg.aggregate), state)
                    with jax.named_scope("ring_record"):
                        ring = jax.vmap(
                            lambda r: ring_record(r, EV_EXCHANGE, k, -1,
                                                  (1.0,), mask=do))(ring)
                return (state, energy, failed, ttl, bidx, k, ring,
                        ex), telem

            carry, telem = jax.lax.scan(
                rev_body,
                (state, energy, failed, ttl, bidx, k, ring, ex),
                None, length=n_revolutions)
            return carry + (telem,)

        fn = jax.jit(closed_loop, donate_argnums=(0, 1, 2, 3, 4, 6, 7))
        self._fns[n_revolutions] = fn
        return fn

    def _ring(self, n_revolutions: int):
        """An empty per-plane telemetry ring for one dispatch: L passes
        + exchange markers (one per boundary, or one per contact window
        when gossiping) a revolution — nothing ever drops."""
        n_ex = (self.rev_len // self.exchange.contact.period + 1
                if self._ex_on and self.exchange.mode == "async" else 1)
        return jax.device_put(
            ring_init(n_revolutions * (self.rev_len + n_ex),
                      batch=(self.n_planes,)), self._shard)

    def lower(self, n_revolutions: int = 1) -> jax.stages.Lowered:
        """The R-revolution program lowered for the current carry, as
        :meth:`run` dispatches it: ``.compile().as_text()`` names each
        op of the device trace with its ``jax.named_scope`` path.
        Compile it with JAX's persistent cache off: the cache key leaves
        the scopes out, so an executable cached before them has none."""
        return self._compiled(n_revolutions).lower(
            self.state, self.energy, self._failed, self._ttl,
            self._batch_idx, self._pass_idx, self._ring(n_revolutions),
            self._ex_state, self.plan, self._fail_mask, self._spread,
            self._byz)

    # --------------------------------------------------------------- run
    def run(self, n_revolutions: Optional[int] = None, *,
            stream_telemetry: bool = False) -> FleetResult:
        """Run R fleet revolutions; chainable (state/aliveness persist).

        ``stream_telemetry=True`` dispatches one revolution at a time
        and waits for each one's telemetry (one host sync per
        revolution); the default runs all R revolutions in one dispatch
        with a single sync at the end.  A sync is one wait, not one
        copy: every dispatch copies its telemetry (6 arrays) and its
        ring (5) to the host, and the result 9 more (energy, masks, ISL
        meters; the plan's 11 on the first run only), each counted in
        ``d2h_arrays`` / ``d2h_bytes``.  The spans ``fleet.run`` ⊃
        ``fleet.launch`` / ``fleet.telemetry_sync`` /
        ``fleet.ring_flush`` (per dispatch, inside
        ``fleet.revolution``) and ``fleet.result`` split the host's
        time.
        """
        cfg = self.cfg
        R = cfg.n_revolutions if n_revolutions is None else n_revolutions
        if R < 1:
            raise ValueError("need at least one revolution")
        self.state._require_live("fleet closed loop")
        state = dedupe_state_buffers(self.state)
        self.state.mark_consumed()
        energy, failed = self.energy, self._failed
        ttl, bidx, k = self._ttl, self._batch_idx, self._pass_idx
        ex = self._ex_state

        chunks = []
        r_chunk = 1 if stream_telemetry else R
        first_launch = r_chunk not in self._launched
        fn = self._compiled(r_chunk)
        with self.metrics.span("run"):
            for _ in range(R if stream_telemetry else 1):
                # a plain span, not a profiler step marker: a
                # StepTraceAnnotation here cost a TPU v5e ~2.8 ms of
                # device idle a revolution while tracing
                with self.metrics.span("revolution",
                                       revolution=self._revolution):
                    with self.metrics.span("launch") as launch:
                        ring = self._ring(r_chunk)
                        state, energy, failed, ttl, bidx, k, ring, ex, \
                            telem = fn(state, energy, failed, ttl, bidx, k,
                                       ring, ex, self.plan, self._fail_mask,
                                       self._spread, self._byz)
                    if first_launch:
                        # tracing, lowering and compiling (or loading)
                        # the program ride its first launch
                        self.metrics.histogram("first_launch_s").record(
                            launch.seconds)
                        self._launched.add(r_chunk)
                        first_launch = False
                    # commit the carry per dispatch: an interrupted
                    # streaming study keeps every completed revolution
                    # and stays chainable
                    self.state, self.energy, self._failed = \
                        state, energy, failed
                    self._ttl, self._batch_idx, self._pass_idx = ttl, bidx, k
                    self._ex_state = ex
                    self.metrics.inc("device_calls")
                    with self.metrics.span("telemetry_sync"):
                        # the host waits for the device here
                        chunks.append(to_host(telem, self.metrics))
                    self.metrics.inc("host_syncs")
                    # the ring's copies wait for no further device work
                    # (events carry absolute pass indices, no rebasing)
                    with self.metrics.span("ring_flush"):
                        self.recorder.ingest(ring)
                self._revolution += r_chunk

            with self.metrics.span("result"):
                telem = jax.tree.map(lambda *xs: np.concatenate(xs),
                                     *chunks)
                # (R, L, P) -> (P, R*L): plane-major per-pass timelines
                flat = lambda x: np.transpose(      # noqa: E731
                    x, (2, 0, 1)).reshape(self.n_planes, -1)
                host = lambda x: to_host(x, self.metrics)   # noqa: E731
                return FleetResult(
                    action=flat(telem.action), sat=flat(telem.sat),
                    loss=flat(telem.loss), battery_j=flat(telem.battery_j),
                    n_steps=flat(telem.n_steps),
                    n_infected=flat(telem.n_infected),
                    plan=host(self.plan), energy=host(energy),
                    failed=host(failed), fault_ttl=host(ttl),
                    state=state, isl_bits=host(ex.bits),
                    isl_e_j=host(ex.e_j), isl_contacts=host(ex.n_contacts))


def _smoke(n_sats: int = 8, n_planes: int = 2,
           n_revolutions: int = 2) -> None:       # pragma: no cover
    """``python -m repro.fleet``: host-vs-fleet closed-loop parity with
    join, leave and seeded-failure events, for CI.

    Each plane's host oracle is a :class:`ConstellationSim` with the
    same event schedule and failure seed (``seed + p``), same model
    init and its data ids offset to the plane's global range; the fleet
    must reproduce every action (trained/shed/skip/**failed**), serving
    sat id, loss and battery reading, with ≤ 1 host sync per
    revolution.
    """
    import time

    from repro.core.constellation import (ConstellationConfig,
                                          ConstellationSim)
    from repro.core.orbits import OrbitalPlane
    from repro.core.sl_step import autoencoder_adapter
    from repro.sim.data import DeviceImageryShards
    from repro.sim.device_sim import ACTION_NAMES

    shards = DeviceImageryShards(img=32, batch=4)
    adapter = autoencoder_adapter(cut=5, img=32)
    budget = PassBudget(plane=OrbitalPlane(n_sats=n_sats), n_items=4e6)
    events = dict(join_events={3: 1}, leave_events={5: 1})
    cfg = FleetConfig(
        n_planes=n_planes, n_revolutions=n_revolutions,
        battery_j=200.0, recharge_w=0.01, reserve_j=150.0,
        max_steps_per_pass=2, fail_prob=0.2, seed=0, avg_every=0,
        **events)

    t0 = time.time()
    fleet = FleetEngine(adapter, budget, shards, cfg)
    M, K = fleet.n_slots, fleet.n_passes
    res = fleet.run(stream_telemetry=True)
    t1 = time.time()
    devs = len(jax.devices())
    print(f"fleet: {n_planes} planes x {n_sats}(+{M - n_sats} join) sats "
          f"x {n_revolutions} revolutions on {devs} device(s), mesh "
          f"{dict(zip(fleet.mesh.axis_names, fleet.mesh.devices.shape))} "
          f"({t1 - t0:.1f}s)")
    print(f"  {res.summary()}")
    print(f"  traces={fleet.traces} device_calls={fleet.device_calls} "
          f"host_syncs={fleet.host_syncs} (<=1/revolution)")
    assert fleet.traces == 1 and fleet.host_syncs <= n_revolutions

    mism = 0
    for p in range(n_planes):
        hcfg = ConstellationConfig(
            n_passes=K, batch_size=4, battery_j=200.0, recharge_w=0.01,
            reserve_j=150.0, max_steps_per_pass=2, fail_prob=0.2,
            seed=cfg.seed + p, **events)
        host = ConstellationSim(
            adapter, budget, lambda s, i, p=p: shards(p * M + s, i), hcfg)
        host.state = SLTrainState.create(
            *adapter.init(jax.random.key(cfg.seed)), host.optimizer)
        host.run()
        h_act = [r.action for r in host.records]
        d_act = [ACTION_NAMES[int(a)] for a in res.action[p]]
        assert h_act == d_act, (p, h_act, d_act)
        assert [r.sat_id for r in host.records] == list(res.sat[p])
        for hr, dl, db in zip(host.records, res.loss[p], res.battery_j[p]):
            if hr.loss is not None:
                mism += abs(dl - hr.loss) > 2e-4 * abs(hr.loss) + 2e-5
            np.testing.assert_allclose(db, hr.battery_j, rtol=1e-5,
                                       atol=0.05)
    assert mism == 0
    s = res.summary()
    assert s["failed"] > 0 and s["skipped"] > 0 and s["trained"] > 0, s
    print(f"  host-vs-fleet parity OK for all {n_planes} planes "
          f"({time.time() - t1:.1f}s host oracle)")


if __name__ == "__main__":                          # pragma: no cover
    _smoke()
