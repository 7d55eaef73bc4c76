"""Revolution-level mission planning: batch problem (13) over the ring.

The paper's protocol is *cyclical* — every satellite in the ring trains
exactly once per revolution — yet the scheduler used to re-solve
problem (13) from scratch at every pass, a scalar solve per pass.  The
:class:`RevolutionPlanner` exploits the cycle structure: the N upcoming
passes of one revolution are N instances of (13) differing only in
their per-satellite budgets and boundary payloads, so ONE
``solve_with_shedding_batch`` call (vectorized dual bisection +
vectorized kept-fraction shedding, core/resource_opt) pre-plans the
whole revolution.

The plan is cached and reused across revolutions; it is invalidated
only when the inputs actually change:

* **membership change** — a satellite joins, leaves, or fails, so the
  ring (and with it d_ISL, the pass order, and possibly per-sat
  budgets) shifts;
* **boundary-shape change** — the measured boundary payload or the
  segment-A handoff size changes (different batch shape, different cut,
  quantization toggled), which alters the (13) coefficients.

Steady-state constellations therefore pay ZERO per-pass solves: the
planner's ``solve_calls`` counter (asserted in tests) shows one batched
solve per plan epoch, however many passes consume it.

The planner's batched solve dispatches through the solver backend
selector (``backend="numpy" | "jax" | "auto"``, see
:mod:`repro.core.resource_opt_jax`), and :func:`sweep_revolutions`
goes one step further: a whole (ring size × cut point × item budget)
scenario grid — e.g. 1000-sat rings × every ``SplitCosts`` cut — is
built, shed and solved as ONE jitted device program, and its outputs
(kept item counts, allocations) feed the fused pass executor as device
arrays, with no host transfer between planning and training.

A swept grid also feeds *whole-revolution* execution:
:meth:`RevolutionSweep.revolution_plan` broadcasts one planned cell
over its ring into a :class:`~repro.sim.device_sim.DevicePassPlan`
(per-slot step counts, battery drains, eq. (11)/(12) records) that the
device constellation engine consumes directly — N masked fused passes
per revolution with zero per-pass Python dispatch and the plan resident
on device end to end.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, Hashable, Optional, Sequence,
                    Tuple, Union)

import numpy as np

from repro.core import resource_opt
from repro.core.energy import PassBudget, SplitCosts


def _costs_key(c: SplitCosts) -> Tuple[float, float, float, float]:
    """Numeric identity of a cost instance (name changes don't replan)."""
    return (c.w1_flops, c.w2_flops, c.dtx_bits, c.d_isl_bits)


def _budget_key(b: PassBudget) -> Hashable:
    # PassBudget and all its components are frozen dataclasses, hence
    # hashable by value — the object itself is the cache key.
    return b


@dataclasses.dataclass(frozen=True)
class PlanEntry:
    """One satellite's pre-solved allocation for its pass this revolution."""

    sat_id: int
    slot: int                                # position in the revolution
    shed: resource_opt.SheddingReport        # allocation (+ kept fraction)

    @property
    def allocation(self):
        return self.shed.report.allocation


class RevolutionPlanner:
    """Pre-solves problem (13) for a whole ring revolution at once.

    Usage (the constellation scheduler's flow)::

        planner = RevolutionPlanner()
        entry = planner.entry_for(sat_id, ring_ids, budget, costs)
        alloc = entry.allocation          # this pass's (f, p) allocation

    ``entry_for`` is cheap when the plan is warm; on a cold or
    invalidated cache it issues exactly one
    :func:`~repro.core.resource_opt.solve_with_shedding_batch` call for
    every satellite in ``ring_ids`` (per-satellite budgets/costs as
    batch instances) and stores the entries.  ``solve_calls`` counts
    batched solves, ``invalidations`` counts cache drops — both are
    observable for tests and benchmarks.

    ``backend`` selects the problem-(13) solver implementation for the
    batched solve ("numpy" | "jax" | "auto", default auto — see
    :func:`~repro.core.resource_opt.solve_batch`).
    """

    def __init__(self, backend: Optional[str] = None) -> None:
        self.backend = backend
        self.solve_calls = 0
        self.invalidations = 0
        self._key: Optional[Hashable] = None
        self._entries: Dict[int, PlanEntry] = {}

    # ----------------------------------------------------------- planning
    @staticmethod
    def _instances(ring: Sequence[int], budgets, costs):
        """Broadcast (budgets, costs) over the ring; returns the
        per-satellite instance lists and their canonical cache key."""
        blist, clist = resource_opt._broadcast_instances(budgets, costs)
        if len(blist) == 1:
            blist = blist * len(ring)
            clist = clist * len(ring)
        if len(blist) != len(ring):
            raise ValueError(f"{len(blist)} instances for {len(ring)} "
                             "satellites")
        key = (tuple(ring),
               tuple(_budget_key(b) for b in blist),
               tuple(_costs_key(c) for c in clist))
        return blist, clist, key

    def plan_revolution(self, ring_ids: Sequence[int],
                        budgets: Union[PassBudget, Sequence[PassBudget]],
                        costs: Union[SplitCosts, Sequence[SplitCosts]],
                        ) -> Dict[int, PlanEntry]:
        """Solve (13) for every satellite of the revolution in one batch.

        ``budgets``/``costs`` are broadcast against ``ring_ids`` the way
        :func:`solve_batch` broadcasts (a single object serves all
        satellites; a sequence gives each its own instance).  The cache
        key is updated to these instances, so a subsequent
        :meth:`entry_for` with matching inputs reuses this plan.
        """
        ring = list(ring_ids)
        if not ring:
            raise ValueError("cannot plan an empty ring")
        blist, clist, key = self._instances(ring, budgets, costs)
        shed = resource_opt.solve_with_shedding_batch(blist, clist,
                                                      backend=self.backend)
        self.solve_calls += 1
        self._entries = {sid: PlanEntry(sid, slot, shed.at(slot))
                         for slot, sid in enumerate(ring)}
        self._key = key
        return self._entries

    def entry_for(self, sat_id: int, ring_ids: Sequence[int],
                  budgets: Union[PassBudget, Sequence[PassBudget]],
                  costs: Union[SplitCosts, Sequence[SplitCosts]],
                  ) -> PlanEntry:
        """This pass's pre-solved entry; replans only on invalidation.

        ``budgets``/``costs`` may be a single object (broadcast ring-
        wide) or one instance per satellite of ``ring_ids``.  The cache
        key is (ring membership, per-satellite budget and cost
        signatures): joins/leaves/failures change the membership tuple,
        a batch-shape or handoff-size change alters a cost signature —
        anything else reuses the cached revolution plan.
        """
        _, _, key = self._instances(list(ring_ids), budgets, costs)
        if key != self._key:
            if self._key is not None:
                self.invalidations += 1
            self.plan_revolution(ring_ids, budgets, costs)
        entry = self._entries.get(sat_id)
        if entry is None:
            raise KeyError(f"satellite {sat_id} is not in the planned ring "
                           f"{sorted(self._entries)}")
        return entry

    # ---------------------------------------------------------- inspection
    @property
    def planned(self) -> bool:
        return self._key is not None

    def invalidate(self) -> None:
        """Drop the cached plan (next entry_for replans)."""
        if self._key is not None:
            self.invalidations += 1
        self._key = None
        self._entries = {}


# --------------------------------------------------------------------------
# On-device revolution sweeps: (ring size × cut point × item budget) grids.
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RevolutionSweep:
    """A planned (ring size × cut × budget) grid, resident on device.

    Every array is a JAX device array of shape (R, C, B) — ring sizes ×
    cut points × item budgets — in float64 (the sweep solves under the
    backend's x64 scope).  Nothing here has touched the host: chaining
    into pass execution (:meth:`steps_for` → ``make_sl_pass(...,
    n_valid=...)``) keeps the whole plan→train pipeline device-side.
    Call :meth:`to_host` once at the end to materialize results.
    """

    ring_sizes: np.ndarray              # (R,) host metadata
    cut_names: Tuple[str, ...]          # (C,) host metadata
    n_items: np.ndarray                 # (B,) host metadata
    d_isl_bits: np.ndarray              # (C,) host metadata (handoff bits)
    e_pass: Any                         # (R,C,B) eq. (11) per pass [J]
    t_pass: Any                         # (R,C,B) eq. (12) per pass [s]
    kept_fraction: Any                  # (R,C,B) shedding outcome
    n_items_kept: Any                   # (R,C,B)
    feasible: Any                       # (R,C,B) bool (post-shedding)
    kkt_residual: Any                   # (R,C,B)
    phase_times: Any                    # (R,C,B,4) canonical phase order
    phase_energy: Any                   # (R,C,B,4) [J] same order
    e_isl: Any                          # (R,C,B) constant E_ISL term [J]
    e_revolution: Any                   # (R,C,B) ring size × e_pass
    best_cut: Any                       # (R,B) argmin-energy cut; -1 if
                                        # no cut is feasible in that cell

    @property
    def shape(self) -> Tuple[int, int, int]:
        return (len(self.ring_sizes), len(self.cut_names),
                len(self.n_items))

    def steps_for(self, batch_size: int):
        """Fused-pass step counts per grid cell, as a device int32 array.

        The bridge into :func:`~repro.core.sl_step.make_sl_pass`: pick a
        cell of this array (still on device) and hand it to the executor
        as ``n_valid`` — the pass scans exactly the allocated number of
        steps without ever reading the plan back to the host.
        """
        from repro.core import resource_opt_jax as roj
        import jax.numpy as jnp

        with roj.x64_scope():
            steps = jnp.ceil(self.n_items_kept / float(batch_size))
            return jnp.maximum(steps, 1.0).astype(jnp.int32)

    def revolution_plan(self, batch_size: int, *, ring: int = 0,
                        cut: Optional[int] = None, budget: int = 0,
                        max_steps_per_pass: Optional[int] = None):
        """One planned grid cell as a whole-revolution execution plan.

        Broadcasts cell ``(ring, cut, budget)`` over its ring's N slots
        into a :class:`~repro.sim.device_sim.DevicePassPlan` — per-slot
        fused step counts, battery drains and eq. (11)/(12) records as
        float32/int32 device arrays — which
        :class:`~repro.sim.device_sim.DeviceConstellationSim` executes
        as N masked fused passes with zero per-pass Python dispatch.
        This closes the plan→train bridge at *revolution* granularity:
        a swept scenario grid feeds closed-loop execution directly,
        without re-solving and without the plan ever visiting the host.

        ``cut=None`` picks the cell's minimum-energy feasible cut
        (``best_cut``; one host scalar read).  The cell's allocation is
        identical for every slot — per-satellite heterogeneous plans
        come from :func:`repro.sim.device_sim.plan_ring_passes` instead.
        """
        from repro.core import resource_opt_jax as roj
        from repro.sim.device_sim import plan_from_report
        import jax.numpy as jnp

        n = int(self.ring_sizes[ring])
        if cut is None:
            cut = int(np.asarray(self.best_cut[ring, budget]))
            if cut < 0:
                raise ValueError(
                    f"no feasible cut in sweep cell (ring={ring}, "
                    f"budget={budget}); pass cut= explicitly to plan an "
                    "infeasible allocation anyway")
        sel = (ring, cut, budget)
        with roj.x64_scope():
            bcast = lambda a: jnp.broadcast_to(a[sel], (n,))   # noqa: E731
            rep = roj.ArraySolveReport(
                phase_times=jnp.broadcast_to(self.phase_times[sel], (n, 4)),
                phase_energy=jnp.broadcast_to(self.phase_energy[sel],
                                              (n, 4)),
                lam=jnp.zeros((n,)), kkt_residual=bcast(self.kkt_residual),
                feasible=bcast(self.feasible), e_isl=bcast(self.e_isl),
                t_fixed=bcast(self.t_pass)
                - jnp.broadcast_to(self.phase_times[sel], (n, 4)).sum(-1))
            return plan_from_report(
                rep, bcast(self.kept_fraction),
                jnp.full((n,), float(self.n_items[budget])),
                float(self.d_isl_bits[cut]), batch_size,
                max_steps_per_pass)

    def fleet_plan(self, batch_size: int, n_planes: int, *, ring: int = 0,
                   cut: Optional[int] = None, budget: int = 0,
                   max_steps_per_pass: Optional[int] = None):
        """One planned grid cell as a P-plane fleet execution plan.

        Broadcasts :meth:`revolution_plan`'s ``(N,)`` cell plan over
        ``n_planes`` into the ``(P, N)`` layout the fleet engine
        (:class:`repro.fleet.FleetEngine`) consumes — a swept scenario
        grid drives a whole sharded constellation with zero re-solves.
        Heterogeneous per-satellite fleet plans come from
        :func:`repro.sim.device_sim.plan_ring_passes` with a ``(P, M)``
        row shape instead.
        """
        import jax.numpy as jnp

        plan = self.revolution_plan(batch_size, ring=ring, cut=cut,
                                    budget=budget,
                                    max_steps_per_pass=max_steps_per_pass)
        return type(plan)(*[jnp.broadcast_to(a, (int(n_planes),)
                                             + a.shape) for a in plan])

    def to_host(self) -> Dict[str, np.ndarray]:
        """One explicit device→host sync of every result array."""
        out = {"ring_sizes": self.ring_sizes, "n_items": self.n_items,
               "d_isl_bits": self.d_isl_bits}
        for f in ("e_pass", "t_pass", "kept_fraction", "n_items_kept",
                  "feasible", "kkt_residual", "phase_times",
                  "phase_energy", "e_isl", "e_revolution", "best_cut"):
            out[f] = np.asarray(getattr(self, f))
        return out


def sweep_revolutions(ring_sizes: Sequence[int],
                      costs: Sequence[SplitCosts],
                      n_items: Sequence[float],
                      *,
                      budget: Optional[PassBudget] = None,
                      dtx_bits=None,
                      min_fraction: float = 0.05,
                      tol: float = 1e-10,
                      max_iters: int = 80) -> RevolutionSweep:
    """Plan a whole scenario grid as ONE jitted device program.

    The grid is (ring size × cut point × item budget): ``ring_sizes``
    vary the ring population (entering problem (13) through the ISL hop
    distance, eq. 5), ``costs`` carry the candidate cut points, and
    ``n_items`` the per-pass item budgets.  Coefficient construction,
    the vectorized kept-fraction shedding, and the jit+vmap dual
    bisection all run inside one compiled call on the default JAX
    device — the classic 1000-sat × every-cut sweep never round-trips
    through host NumPy, and the resulting plan feeds
    :func:`~repro.core.sl_step.make_sl_pass` as arrays
    (:meth:`RevolutionSweep.steps_for`).

    ``budget`` is the scenario template (plane/link/ISL/devices; its
    ``n_items`` and the plane's ``n_sats`` are overridden by the grid
    axes).  ``dtx_bits`` optionally overrides the cuts' boundary
    payloads with *measured* per-cut values — e.g. the array produced
    by :func:`~repro.core.sl_step.ring_boundary_bits` — so the sweep
    plans from what the model actually transmits.
    """
    from repro.core import resource_opt_jax as roj

    import jax.numpy as jnp

    budget = PassBudget() if budget is None else budget
    costs = list(costs)
    ring = np.asarray(list(ring_sizes), dtype=np.int64)
    items = np.asarray(list(n_items), dtype=np.float64)
    if ring.size == 0 or not costs or items.size == 0:
        raise ValueError("sweep_revolutions needs non-empty ring_sizes, "
                         "costs and n_items axes")
    if np.any(ring < 1):
        raise ValueError("ring sizes must be >= 1 satellite")

    w1 = [c.w1_flops for c in costs]
    w2 = [c.w2_flops for c in costs]
    disl = [c.d_isl_bits for c in costs]
    dtx = [c.dtx_bits for c in costs] if dtx_bits is None else dtx_bits

    sc = roj.grid_scalars(budget.plane, budget.link, budget.isl,
                          budget.sat_device, budget.gs_device)
    rep, frac = roj.sweep_grid(sc, ring, w1, w2, dtx, disl, items,
                               min_fraction=min_fraction, tol=tol,
                               max_iters=max_iters)
    with roj.x64_scope():                 # derived arrays, still on device
        e_pass = rep.e_total
        t_pass = rep.t_total
        n_kept = frac * jnp.asarray(items)[None, None, :]
        e_rev = jnp.asarray(ring, jnp.float64)[:, None, None] * e_pass
        # -1 sentinel where even max shedding leaves every cut infeasible
        # (argmin over all-inf would silently report cut 0)
        best_cut = jnp.where(
            rep.feasible.any(axis=1),
            jnp.argmin(jnp.where(rep.feasible, e_pass, jnp.inf), axis=1),
            -1).astype(jnp.int32)
    return RevolutionSweep(
        ring_sizes=ring, cut_names=tuple(c.name for c in costs),
        n_items=items,
        d_isl_bits=np.asarray(disl, dtype=np.float64),
        e_pass=e_pass, t_pass=t_pass, kept_fraction=frac,
        n_items_kept=n_kept, feasible=rep.feasible,
        kkt_residual=rep.kkt_residual, phase_times=rep.phase_times,
        phase_energy=rep.phase_energy, e_isl=rep.e_isl,
        e_revolution=e_rev, best_cut=best_cut)
