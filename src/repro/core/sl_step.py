"""The split-learning train step (paper Fig. 1 steps 1-8, on real models).

One SL step over a batch at the current satellite:

  (1-2) satellite forward on segment A          -> boundary activations z
  (3)   downlink z (optionally int8-quantized)           [D_tx, eq. 8-9]
  (4-5) ground forward+loss+backward on segment B
  (6)   uplink boundary gradient dz (optionally quantized)
  (7)   satellite backward through segment A (jax.vjp)
  (8)   both sides apply SGD; at pass end segment A ships over the ISL.

The step is built once per (model, cut) via an adapter; the actual
boundary tensors and their exact bit-counts are returned so the energy
accounting (core/energy) charges what the model really transmitted, not
a spec-sheet estimate.

Boundary quantization (beyond-paper) uses the split_quant kernel's STE
wrapper so training remains end-to-end differentiable.

Pass engine (the per-pass hot path)
-----------------------------------
:func:`make_sl_step` runs ONE step per jitted call; a pass that the
problem-(13) allocation budgets for k steps used to pay k Python
dispatches plus k eager optimizer updates.  :func:`make_sl_pass` fuses
the whole pass into a single jitted ``jax.lax.scan``: one
:class:`~repro.core.train_state.SLTrainState` (both segments' params +
optimizer states + step counter) threads through the scan carry
(buffers donated, so segment weights update in place across the pass;
the input state is marked consumed), batches are stacked along the scan
axis, and the per-step losses come back as one (k,) array.  The
optimizer is pluggable (:class:`~repro.train.optimizer.Optimizer` —
SGD or AdamW with its lr schedule) and updates inside the scan body.
Step counts are bucketed to the next power of two with a per-step
validity mask — padded steps leave the carry untouched — so
recompilation is O(log k) over a constellation run instead of one
compile per distinct allocation.  The scanned step applies exactly the
same grads + optimizer update as the scalar path, so k scanned SGD
steps match k sequential ``make_sl_step`` + ``sgd_update`` calls
loss-for-loss.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.energy import SplitCosts
from repro.core.splitting import SplitPlan
from repro.kernels import ops
# padded step counts share the repo-wide bucketing schedule (pow2 up to
# 16, then 1/8-octave) with the solver backend's batch padding
from repro.utils.bucketing import bucket_size as _bucket_size


@dataclasses.dataclass(frozen=True)
class SplitAdapter:
    """Model-agnostic view of a cut model."""

    name: str
    init: Callable[[Any], Tuple[Any, Any]]          # rng -> (params_a, params_b)
    forward_a: Callable[[Any, Dict], jnp.ndarray]   # (params_a, batch) -> z
    loss_b: Callable[[Any, jnp.ndarray, Dict], jnp.ndarray]
    plan: SplitPlan
    cut_index: int

    def costs(self, act_bits: int = 32) -> SplitCosts:
        return self.plan.costs_at(self.cut_index)


@dataclasses.dataclass
class SLStepResult:
    loss: jnp.ndarray
    grads_a: Any
    grads_b: Any
    dtx_bits_down: int                  # measured boundary payload (one way)
    dtx_bits_up: int


def _make_sl_grads(adapter: SplitAdapter, quantize_boundary: bool):
    """The traced body shared by make_sl_step and make_sl_pass:
    (params_a, params_b, batch) -> (loss, g_a, g_b, payload_bits).

    Its ops carry the named scopes of the step's phases: ``sat_fwd``
    (the satellite forward, ``jvp(sat_fwd)`` in an op's path),
    ``ground`` (loss and backward of segment B) and ``sat_bwd`` (the
    satellite VJP, ``sat_bwd/transpose(jvp(sat_fwd))``)."""

    q_bits = 8 if quantize_boundary else 32

    @jax.named_scope("sat_fwd")
    def sat_fwd(pa, batch):
        return adapter.forward_a(pa, batch)

    def sl_grads(params_a, params_b, batch):
        # satellite forward, with vjp closure kept for step (7)
        z, vjp_a = jax.vjp(lambda pa: sat_fwd(pa, batch), params_a)
        z_tx = ops.ste_quantize(z) if quantize_boundary else z

        # ground: loss + backward wrt segment B and wrt the boundary
        def ground(pb, zz):
            return adapter.loss_b(pb, zz, batch)

        with jax.named_scope("ground"):
            loss, (g_b, g_z) = jax.value_and_grad(ground, argnums=(0, 1))(
                params_b, z_tx)

        # uplink gradient (quantized the same way on the return path)
        g_z_tx = ops.ste_quantize(g_z) if quantize_boundary else g_z
        with jax.named_scope("sat_bwd"):
            (g_a,) = vjp_a(g_z_tx.astype(z.dtype))

        payload = z.size * q_bits
        return loss, g_a, g_b, payload

    return sl_grads


def make_sl_step(adapter: SplitAdapter, *, quantize_boundary: bool = False):
    """Returns jit'd sl_step(params_a, params_b, batch) -> SLStepResult."""

    jitted = jax.jit(_make_sl_grads(adapter, quantize_boundary))

    def run(params_a, params_b, batch) -> SLStepResult:
        loss, g_a, g_b, payload = jitted(params_a, params_b, batch)
        return SLStepResult(loss=loss, grads_a=g_a, grads_b=g_b,
                            dtx_bits_down=int(payload),
                            dtx_bits_up=int(payload))

    return run


def boundary_bits(adapter: SplitAdapter, batch,
                  quantize_boundary: bool = False) -> int:
    """Exact one-way boundary payload (bits) for ``batch`` — shape-only.

    Uses ``jax.eval_shape`` on the satellite segment, so measuring the
    payload for the energy model costs no FLOPs (the old protocol ran a
    full probe train step just to read off ``z.size``).
    """
    params_shape = jax.eval_shape(adapter.init, jax.random.key(0))[0]
    z = jax.eval_shape(adapter.forward_a, params_shape, batch)
    return z.size * (8 if quantize_boundary else 32)


def _batch_shape_key(batch):
    return (jax.tree_util.tree_structure(batch),
            tuple((x.shape, str(x.dtype)) for x in jax.tree.leaves(batch)))


def make_boundary_meter(adapter: SplitAdapter,
                        quantize_boundary: bool = False):
    """A :func:`boundary_bits` memoized per batch shape.

    The shared payload cache for the pass engine and the constellation
    scheduler: steady-state passes (constant batch shapes) trace the
    satellite segment exactly once.
    """
    cache: Dict[Any, int] = {}

    def measure(batch) -> int:
        key = _batch_shape_key(batch)
        bits = cache.get(key)
        if bits is None:
            bits = boundary_bits(adapter, batch, quantize_boundary)
            cache[key] = bits
        return bits

    return measure


def ring_boundary_bits(adapter: SplitAdapter, batches: Sequence[Dict],
                       quantize_boundary: bool = False) -> np.ndarray:
    """Per-satellite boundary payloads (bits, one way) as ONE array.

    ``batches`` holds one representative batch per ring member (their
    shapes may differ — non-IID shards, ragged tails); the result is the
    array feed for the device-resident planner
    (:func:`repro.core.mission.sweep_revolutions` ``dtx_bits=`` or the
    per-satellite instance lists of ``plan_revolution``) instead of a
    Python-int-at-a-time protocol.  Shape-only via ``jax.eval_shape``,
    memoized per distinct shape.
    """
    meter = make_boundary_meter(adapter, quantize_boundary)
    return np.asarray([float(meter(b)) for b in batches], dtype=np.float64)


# --------------------------------------------------------------------------
# The scan-fused pass engine.
# --------------------------------------------------------------------------

def make_pass_step(adapter: SplitAdapter, optimizer, *,
                   quantize_boundary: bool = False):
    """The shared masked SL step kernel: one traced train step.

    ``pass_step(state, batch, valid) -> (new_state, loss)``

    Runs one split-learning step (both grads + the optimizer update on
    an :class:`~repro.core.train_state.SLTrainState`) and gates it on
    ``valid``: an invalid step passes the whole carry through untouched
    and reports NaN loss.  This is THE scan body of the repo — used by
    :func:`make_sl_pass` (padded / planner-masked steps of one fused
    pass) and by the device constellation engine
    (:mod:`repro.sim.device_sim`, where skip-below-reserve passes and
    beyond-allocation steps mask the same way) — so host and device
    closed loops train through literally the same kernel.  The optimizer
    update runs under the named scope ``update``, beside the phases of
    :func:`_make_sl_grads`.
    """
    sl_grads = _make_sl_grads(adapter, quantize_boundary)

    def pass_step(state, batch, valid):
        loss, g_a, g_b, _ = sl_grads(state.params_a, state.params_b, batch)
        with jax.named_scope("update"):
            state = state.apply_updates(g_a, g_b, optimizer, where=valid)
        return state, jnp.where(valid, loss, jnp.nan)

    return pass_step


def dedupe_state_buffers(state):
    """Copy leaves that alias the same buffer (e.g. a tied LM embedding
    shared between segments A and B): XLA rejects donating one buffer
    twice, and the segments diverge after the first update anyway.
    Shared by every donating engine (fused pass, device sim)."""
    seen = set()

    def uniq(x):
        if id(x) in seen:
            return jnp.copy(x)
        seen.add(id(x))
        return x

    return jax.tree.map(uniq, state)


@dataclasses.dataclass
class SLPassResult:
    """One whole pass: k fused SL steps + optimizer updates, as a state.

    ``state`` is the :class:`~repro.core.train_state.SLTrainState` after
    the pass; the ``params_a``/``params_b``/``opt_a``/``opt_b``
    properties are read-only conveniences over it.

    When the pass ran with a device-side ``n_valid`` (planner-driven
    step count), ``losses`` still has static length k but entries at or
    beyond the allocated count are NaN — aggregate with ``nanmean``.
    """

    losses: jnp.ndarray                 # (k,) per-step training loss
    state: Any                          # SLTrainState after the pass
    n_steps: int
    dtx_bits_down: int                  # boundary payload per step (one way)
    dtx_bits_up: int

    @property
    def params_a(self):
        return self.state.params_a

    @property
    def params_b(self):
        return self.state.params_b

    @property
    def opt_a(self):
        return self.state.opt_a

    @property
    def opt_b(self):
        return self.state.opt_b




def make_sl_pass(adapter: SplitAdapter, *, quantize_boundary: bool = False,
                 optimizer=None, lr: float = 1e-2, grad_clip: float = 1.0,
                 donate: bool = True, bucket: bool = True):
    """Returns a fused pass executor running k SL steps in one jitted call.

    ``sl_pass(state, batches) -> SLPassResult``

    ``state`` is an :class:`~repro.core.train_state.SLTrainState`; it
    rides the ``lax.scan`` carry and (with ``donate=True``) its buffers
    are donated to the call, so a pass updates segment weights in place
    instead of round-tripping k times through Python.  The input state
    is marked *consumed* — chain ``result.state`` forward; reusing a
    consumed state raises instead of crashing on freed buffers.

    ``optimizer`` is an :class:`~repro.train.optimizer.Optimizer`, a
    registered name (``"sgd"``/``"adamw"``), or None for SGD built from
    the legacy ``lr``/``grad_clip`` kwargs.  Any optimizer whose state
    is a pytree works — the update runs inside the scan body.

    ``batches`` is either a list of k per-step batch dicts (shapes may
    vary between steps — consecutive same-shape groups are scanned and
    chained) or one pytree whose leaves carry a leading scan axis of
    length k.  With ``bucket=True`` k is padded to a bucketed step count
    (powers of two up to 16, then 1/8-octave granularity, see
    ``_bucket_size``) with masked no-op steps — the carry passes through
    unchanged — keeping recompiles rare at <=25% worst-case padded
    compute.

    ``sl_pass(state, batches, n_valid=...)`` accepts a *device* integer
    scalar bounding how many of the k steps actually train — the raw
    output of the on-device revolution planner
    (:meth:`~repro.core.mission.RevolutionSweep.steps_for`).  Steps at
    index >= n_valid are carry passthroughs and report NaN loss, so the
    planner's allocation drives the pass with no host synchronization.
    """
    from repro.core.train_state import SLTrainState
    from repro.train.optimizer import resolve_optimizer

    opt = resolve_optimizer(optimizer, lr=lr, grad_clip=grad_clip)
    # padded steps leave the whole carry (params, opt, step) untouched —
    # the masking lives inside the shared kernel (make_pass_step)
    step_kernel = make_pass_step(adapter, opt,
                                 quantize_boundary=quantize_boundary)
    measure_payload = make_boundary_meter(adapter, quantize_boundary)

    def one_step(state, xs):
        batch, valid = xs
        return step_kernel(state, batch, valid)

    def scan_pass(state, batches, valid):
        return jax.lax.scan(one_step, state, (batches, valid))

    jitted = jax.jit(scan_pass, donate_argnums=(0,) if donate else ())

    def run_state(state, batches: Union[Sequence[Dict], Dict],
                  n_valid=None) -> SLPassResult:
        # even a donate=False pass must reject a consumed state: its
        # buffers may already be freed by the pass that consumed it
        state._require_live("pass")
        if isinstance(batches, (list, tuple)):
            if not batches:
                raise ValueError("a pass needs at least one batch")
            keys = [_batch_shape_key(b) for b in batches]
            if any(key != keys[0] for key in keys):
                if n_valid is not None:
                    raise ValueError("n_valid requires same-shape batches "
                                     "(one fused scan)")
                # ragged pass (e.g. a partial final shard batch): scan
                # consecutive same-shape groups, chaining the donated
                # state between them.  Payload is reported for the first
                # group's step shape.
                results = []
                i = 0
                while i < len(batches):
                    j = i + 1
                    while j < len(batches) and keys[j] == keys[i]:
                        j += 1
                    r = run_state(state, list(batches[i:j]))
                    state = r.state
                    results.append(r)
                    i = j
                return SLPassResult(
                    losses=jnp.concatenate([r.losses for r in results]),
                    state=state, n_steps=len(batches),
                    dtx_bits_down=results[0].dtx_bits_down,
                    dtx_bits_up=results[0].dtx_bits_up)
            batches = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
        k = jax.tree.leaves(batches)[0].shape[0]
        if k == 0:
            raise ValueError("a pass needs at least one batch")
        payload = measure_payload(jax.tree.map(lambda x: x[0], batches))
        kb = _bucket_size(k) if bucket else k
        if kb > k:
            # pad the scan axis by repeating the last batch; the validity
            # mask turns those steps into carry passthroughs.
            batches = jax.tree.map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[-1:], kb - k, axis=0)]), batches)
        if n_valid is None:
            valid = jnp.arange(kb) < k
        else:
            # device-resident step budget (e.g. RevolutionSweep.steps_for):
            # the comparison runs on device — no host sync of the plan
            valid = jnp.arange(kb) < jnp.minimum(
                jnp.asarray(n_valid, jnp.int32), k)
        call_state = dedupe_state_buffers(state) if donate else state
        new_state, losses = jitted(call_state, batches, valid)
        if donate:
            state.mark_consumed()
        return SLPassResult(losses=losses[:k], state=new_state, n_steps=k,
                            dtx_bits_down=payload, dtx_bits_up=payload)

    def run(state, batches, n_valid=None) -> SLPassResult:
        if not isinstance(state, SLTrainState):
            raise TypeError(
                "sl_pass(state, batches) expects an SLTrainState (the old "
                "4-tuple (params_a, params_b, opt_a, opt_b, batches) call "
                "was removed; build one with SLTrainState.create), got "
                f"{type(state).__name__}")
        return run_state(state, batches, n_valid=n_valid)

    return run


# --------------------------------------------------------------------------
# Adapters for the paper's models and the LM track.
# --------------------------------------------------------------------------

def autoencoder_adapter(cut: int = 5, img: int = 64, base: int = 16,
                        latent_ch: int = 3) -> SplitAdapter:
    """Encoder (satellite) / decoder (ground) — paper §V-A (cut=5)."""
    from repro.core.splitting import autoencoder_plan
    from repro.models import vision
    from repro.models.param import init_params

    names = vision.ae_stage_names()

    def _init(rng):
        p = init_params(vision.ae_abstract_params(base, latent_ch), rng)
        pa = {k: p[k] for k in names[:cut]}
        pb = {k: p[k] for k in names[cut:]}
        return pa, pb

    def fa(pa, batch):
        return vision.ae_apply_range(pa, batch["images"], 0, cut)

    def lb(pb, z, batch):
        recon = vision.ae_apply_range(pb, z, cut, len(names))
        return jnp.mean(jnp.square(recon.astype(jnp.float32)
                                   - batch["images"].astype(jnp.float32)))

    return SplitAdapter("autoencoder", _init, fa, lb,
                        plan=autoencoder_plan(img=img, base=base,
                                              latent_ch=latent_ch),
                        cut_index=cut)


def resnet18_adapter(cut: int = 5, img: int = 64,
                     n_classes: int = 10) -> SplitAdapter:
    """ResNet-18 classification, Table II cuts l1/l2/l3 = 3/5/7."""
    from repro.core.splitting import resnet18_plan
    from repro.models import vision
    from repro.models.param import init_params

    names = vision.RESNET_STAGES

    def _init(rng):
        p = init_params(vision.resnet18_abstract_params(n_classes), rng)
        pa = {k: p[k] for k in names[:cut]}
        pb = {k: p[k] for k in names[cut:]}
        return pa, pb

    def fa(pa, batch):
        return vision.resnet18_apply_range(pa, batch["images"], 0, cut)

    def lb(pb, z, batch):
        logits = vision.resnet18_apply_range(pb, z, cut, len(names))
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, batch["labels"][:, None],
                                 axis=-1)[:, 0]
        return jnp.mean(lse - ll)

    return SplitAdapter("resnet18", _init, fa, lb,
                        plan=resnet18_plan(img=img, n_classes=n_classes),
                        cut_index=cut)


def lm_adapter(cfg, cut_units: int, seq_len: int) -> SplitAdapter:
    """LM split at a pattern-unit boundary: embed+units[:u] on-sat."""
    from repro.core.splitting import lm_plan
    from repro.models import lm
    from repro.models.layers import Ctx

    pat_len = len(cfg.pattern_unit())
    cut_blocks = cut_units * pat_len
    ctx = Ctx(cfg=cfg, act_dtype=jnp.float32)

    def _init(rng):
        p = lm.init(cfg, rng)
        pa = {"embed": p["embed"],
              "units": jax.tree.map(lambda t: t[:cut_units], p["units"])}
        pb = {"units": jax.tree.map(lambda t: t[cut_units:], p["units"]),
              "final_norm": p["final_norm"]}
        if "head" in p:
            pb["head"] = p["head"]
        else:
            pb["head_tied"] = p["embed"]     # ground needs the head copy
        if "shared" in p:
            pa["shared"] = p["shared"]
            pb["shared"] = p["shared"]
        return pa, pb

    def fa(pa, batch):
        return lm.forward_segment(cfg, pa, None, 0, cut_blocks, ctx=ctx,
                                  tokens=batch["tokens"])

    def lb(pb, z, batch):
        pfull = dict(pb)
        if "head_tied" in pb:
            pfull = {k: v for k, v in pb.items() if k != "head_tied"}
            pfull["embed"] = pb["head_tied"]
            cfg_b = cfg
        else:
            cfg_b = cfg
        logits = lm.forward_segment(
            cfg_b, pfull, z, cut_blocks, lm.n_blocks(cfg), ctx=ctx,
            unit_offset=cut_units)
        labels = batch["labels"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - ll)

    return SplitAdapter(cfg.name, _init, fa, lb,
                        plan=lm_plan(cfg, seq_len), cut_index=cut_blocks)
