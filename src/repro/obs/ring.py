"""In-scan telemetry rings: the device half of the flight recorder.

The engines (:mod:`repro.sim.device_sim`, :mod:`repro.fleet.engine`,
:mod:`repro.serve_fleet.engine`) run entire (revolution × pass) and
(window × plane) loops as single jitted scans; anything they want to
tell the host has to either ride the scan outputs or break the
≤-1-host-sync-per-revolution contract.  A :class:`TelemetryRing` is the
first option made first-class: a fixed-size structured event buffer
(kind / time / slot / float32 payload row) plus a monotonic cursor,
carried through the scan like any other state and **flushed at the
existing revolution-boundary sync**: the device has finished the
program by then, so the flush waits for nothing (the registry's
``host_syncs`` counts no extra sync, see :mod:`repro.obs.metrics`).
It is not free: each of the ring's five arrays is a device→host copy
of its own, counted in ``d2h_arrays`` / ``d2h_bytes``.

Device API (traceable, vmap-safe — the fleet engine records into a
``(P, ...)``-leading ring under its plane ``vmap``):

* :func:`ring_init` — allocate a ring of ``capacity`` event slots;
* :func:`record` — write one event at the cursor (a ``mask=False``
  record is a no-op: same trace, nothing written).  When the ring is
  full the cursor keeps counting but the write wraps — newest events
  overwrite the oldest, and the overflow is reported as ``dropped`` at
  flush time, never silently.

Host API:

* :func:`flush` — the ring copied to the host (one copy per array),
  unwrapped into chronological event arrays (+ the dropped-event
  count);
* :class:`FlightRecorder` — accumulates flushed rings across
  dispatches/planes into one event table (feeding the engine's metrics
  registry), ready for :mod:`repro.obs.timeline` to render.

Event payload rows are plain float32; the *meaning* of each column is
fixed per event kind (``PASS_FIELDS`` / ``SERVE_FIELDS``) so the host
side can name them without the device side carrying strings.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .metrics import to_host

# ---------------------------------------------------------------- schema

EV_PASS = 0          # one training pass (sim + fleet engines)
EV_SERVE = 1         # one serving window (serve-fleet engine)
EV_EXCHANGE = 2      # inter-plane ISL checkpoint exchange

EVENT_NAMES = {EV_PASS: "pass", EV_SERVE: "serve", EV_EXCHANGE: "exchange"}

#: float32 payload columns, fixed per event kind (unused tail = 0)
PASS_FIELDS = ("action", "battery_j", "loss", "n_steps", "kept_fraction",
               "fault", "sunlit", "n_infected")
SERVE_FIELDS = ("arrivals", "battery_j", "served", "backlog", "tokens",
                "trained", "sunlit", "capacity_req")
EXCHANGE_FIELDS = ("aggregate", "bits", "e_isl_j", "staleness", "weight")
FIELDS_BY_KIND = {EV_PASS: PASS_FIELDS, EV_SERVE: SERVE_FIELDS,
                  EV_EXCHANGE: EXCHANGE_FIELDS}

#: every ring row is this wide — the max any kind needs
PAYLOAD_WIDTH = 8


class TelemetryRing(NamedTuple):
    """Fixed-size structured event buffer riding a scan carry.

    All fields are arrays (a pytree by NamedTuple construction), so a
    ring vmaps/shards/donates like any other carry leaf.  ``cursor``
    counts every recorded event monotonically; the write index is
    ``cursor % capacity``, so ``cursor > capacity`` means the oldest
    ``cursor - capacity`` events were overwritten.
    """

    kind: Any        # (C,)   int32  EV_* code
    t: Any           # (C,)   int32  pass / window index
    slot: Any        # (C,)   int32  ring slot (satellite), -1 = plane-wide
    payload: Any     # (C, W) float32 columns named by FIELDS_BY_KIND
    cursor: Any      # ()     int32  total events recorded (monotonic)

    @property
    def capacity(self) -> int:
        return self.kind.shape[-1]


def ring_init(capacity: int, payload_width: int = PAYLOAD_WIDTH,
              batch: Tuple[int, ...] = ()) -> TelemetryRing:
    """A fresh ring of ``capacity`` event slots (``batch`` adds leading
    axes — the fleet engine allocates one ring per plane as
    ``batch=(P,)`` and records under its plane ``vmap``)."""
    if capacity < 1:
        raise ValueError(f"ring capacity must be >= 1, got {capacity}")
    return TelemetryRing(
        kind=jnp.full(batch + (capacity,), -1, jnp.int32),
        t=jnp.zeros(batch + (capacity,), jnp.int32),
        slot=jnp.zeros(batch + (capacity,), jnp.int32),
        payload=jnp.zeros(batch + (capacity, payload_width), jnp.float32),
        cursor=jnp.zeros(batch, jnp.int32))


def record(ring: TelemetryRing, kind, t, slot, payload,
           mask=True) -> TelemetryRing:
    """Write one event at the cursor; traceable, called INSIDE scans.

    ``payload`` is a sequence/array of up to ``PAYLOAD_WIDTH`` float32
    scalars (shorter rows are zero-padded); ``mask=False`` leaves the
    ring bit-identical (the event never happened — same trace either
    way, so conditional events cost nothing).  Must stay jnp-pure: it
    runs inside the engines' jitted scan bodies, where a stray host op
    would break the sync contract (``scripts/lint_scan_purity.py``
    guards this function alongside the scan bodies themselves).
    """
    cap = ring.kind.shape[-1]
    width = ring.payload.shape[-1]
    pay = jnp.asarray(payload, jnp.float32).reshape(-1)
    if pay.shape[0] > width:
        raise ValueError(f"payload has {pay.shape[0]} columns; the ring "
                         f"holds {width}")
    if pay.shape[0] < width:
        pay = jnp.concatenate(
            [pay, jnp.zeros((width - pay.shape[0],), jnp.float32)])
    m = jnp.asarray(mask, bool)
    idx = ring.cursor % cap
    return TelemetryRing(
        kind=ring.kind.at[idx].set(
            jnp.where(m, jnp.asarray(kind, jnp.int32), ring.kind[idx])),
        t=ring.t.at[idx].set(
            jnp.where(m, jnp.asarray(t, jnp.int32), ring.t[idx])),
        slot=ring.slot.at[idx].set(
            jnp.where(m, jnp.asarray(slot, jnp.int32), ring.slot[idx])),
        payload=ring.payload.at[idx].set(
            jnp.where(m, pay, ring.payload[idx])),
        cursor=ring.cursor + m.astype(jnp.int32))


# ------------------------------------------------------------- host side

class RingEvents(NamedTuple):
    """One flushed ring, chronological, host arrays."""

    kind: np.ndarray      # (n,) int32
    t: np.ndarray         # (n,) int32
    slot: np.ndarray      # (n,) int32
    payload: np.ndarray   # (n, W) float32
    dropped: int          # events overwritten before this flush


def flush(ring: TelemetryRing) -> RingEvents:
    """A (flat) ring copied to the host, unwrapped oldest-first.

    Call it where the engine already syncs telemetry: the device is
    done with the ring by then, so flushing waits for no further device
    work, but each of the ring's arrays is a device→host copy of its
    own.  Rings with leading batch axes (one per plane) are flushed per
    plane by :meth:`FlightRecorder.ingest`, which counts those copies.
    """
    host = TelemetryRing(*[np.asarray(a) for a in ring])
    if host.cursor.ndim != 0:
        raise ValueError("flush() takes a flat ring; index the plane axis "
                         "first (FlightRecorder.ingest does)")
    cap = host.kind.shape[-1]
    cursor = int(host.cursor)
    n = min(cursor, cap)
    if cursor <= cap:
        order = np.arange(n)
    else:                       # wrapped: oldest event sits at cursor % cap
        start = cursor % cap
        order = np.concatenate([np.arange(start, cap), np.arange(start)])
    return RingEvents(kind=host.kind[order], t=host.t[order],
                      slot=host.slot[order], payload=host.payload[order],
                      dropped=cursor - n)


_EVENT_COLUMNS = ("kind", "t", "slot", "plane", "payload")


class FlightRecorder:
    """Host-side accumulator of flushed rings — the mission's black box.

    Engines own one recorder each and call :meth:`ingest` right where
    they sync telemetry (one call per dispatch).  The recorder splits
    plane-batched rings, tags every event with its plane, feeds the
    engine's metrics registry (``events_recorded`` / ``events_dropped``
    counters, and ``d2h_arrays`` / ``d2h_bytes`` for the ring's copies)
    and serves the merged, time-ordered event table to
    :mod:`repro.obs.timeline`.
    """

    def __init__(self, metrics=None):
        self.metrics = metrics
        self.dropped = 0
        self._chunks = []          # list of per-ingest column dicts

    def __len__(self) -> int:
        return sum(int(c["kind"].shape[0]) for c in self._chunks)

    def ingest(self, ring: TelemetryRing, *, t_offset: int = 0) -> int:
        """Flush ``ring`` (flat, or plane-batched ``(P, ...)``) into the
        event table; returns the number of events ingested.

        ``t_offset`` shifts event times into the run's absolute
        timeline for engines that record dispatch-local indices (the
        sim engine's ``t`` restarts at 0 every dispatch; the fleet and
        serve engines record absolute indices and pass 0).
        """
        host = to_host(ring, self.metrics)
        planes = ([None] if host.cursor.ndim == 0
                  else range(host.cursor.shape[0]))
        n_total = 0
        for p in planes:
            r = host if p is None else TelemetryRing(
                *[a[p] for a in host])
            ev = flush(r)
            n = ev.kind.shape[0]
            n_total += n
            self.dropped += ev.dropped
            self._chunks.append({
                "kind": ev.kind, "t": ev.t + np.int32(t_offset),
                "slot": ev.slot,
                "plane": np.full((n,), 0 if p is None else p, np.int32),
                "payload": ev.payload})
        if self.metrics is not None:
            self.metrics.inc("events_recorded", n_total)
            if self.dropped:
                self.metrics.counter("events_dropped").set(self.dropped)
        return n_total

    def events(self) -> Dict[str, np.ndarray]:
        """The merged event table, stably sorted by (t, plane)."""
        if not self._chunks:
            return {"kind": np.zeros((0,), np.int32),
                    "t": np.zeros((0,), np.int32),
                    "slot": np.zeros((0,), np.int32),
                    "plane": np.zeros((0,), np.int32),
                    "payload": np.zeros((0, PAYLOAD_WIDTH), np.float32)}
        cols = {k: np.concatenate([c[k] for c in self._chunks])
                for k in _EVENT_COLUMNS}
        order = np.lexsort((cols["plane"], cols["t"]))
        return {k: v[order] for k, v in cols.items()}

    # --------------------------------------------------- persistence
    def save(self, path: str) -> None:
        """One ``.npz`` with the merged table (+ dropped count) — what
        ``python -m repro.obs render --events`` re-renders offline."""
        ev = self.events()
        np.savez(path, dropped=np.int64(self.dropped), **ev)

    @staticmethod
    def load(path: str) -> "FlightRecorder":
        data = np.load(path)
        rec = FlightRecorder()
        rec.dropped = int(data["dropped"])
        rec._chunks.append({k: data[k] for k in _EVENT_COLUMNS})
        return rec


def merge_events(*tables: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    """Merge event tables (e.g. a train fleet's and a serve fleet's)
    into one, stably sorted by (t, plane)."""
    tables = [t for t in tables if t["kind"].shape[0]]
    if not tables:
        return FlightRecorder().events()
    cols = {k: np.concatenate([t[k] for t in tables])
            for k in _EVENT_COLUMNS}
    order = np.lexsort((cols["plane"], cols["t"]))
    return {k: v[order] for k, v in cols.items()}


def payload_column(events: Dict[str, np.ndarray], kind: int,
                   field: str) -> np.ndarray:
    """The named payload column of every ``kind`` event (host helper)."""
    fields = FIELDS_BY_KIND[kind]
    mask = events["kind"] == kind
    return events["payload"][mask][:, fields.index(field)]
