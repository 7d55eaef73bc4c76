"""Flash attention forward — Pallas TPU kernel.

TPU-native adaptation (DESIGN.md §5): instead of a CUDA warp-level
softmax, the kernel streams (block_k x head_dim) K/V tiles HBM->VMEM over
the innermost ("arbitrary") grid axis while the (block_q x head_dim) Q
tile and the fp32 accumulator stay resident in VMEM; the two matmuls hit
the MXU with 128-aligned dims. GQA is handled in the *index maps* — the
K/V BlockSpecs map query-head h to kv-head h // group, so grouped heads
re-stream the same KV tiles without materializing a repeated KV tensor.

Grid: (B, H, n_q_blocks, n_kv_blocks), kv innermost sequential.
Scratch (VMEM): m (block_q,1) row max, l (block_q,1) row sum,
acc (block_q, head_dim) fp32 output accumulator.

Causal / sliding-window masking is positional (iota compare); fully
masked KV blocks are skipped with pl.when so the sequential axis does no
work outside the band — the same work-skipping the paper's deadline
optimizer assumes when it budgets W(ℓ).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int, n_kv: int, seq_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Band check: skip KV blocks fully outside the (causal, window) band.
    in_band = True
    if causal:
        in_band = k_start <= q_start + block_q - 1
    if window is not None:
        in_band = jnp.logical_and(
            in_band, k_start + block_k - 1 > q_start - window)

    @pl.when(in_band)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)
        # zero OOB tail rows: pallas pads the last block with undefined
        # values, and 0 * garbage in the PV matmul would poison the acc
        kv_valid = (k_start
                    + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                    < seq_kv)
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_kv                              # tail padding
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                               # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)         # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                            # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                   # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool):
    """q: (B, H, Sq, D); k, v: (B, KV, Skv, D). Returns (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)

    n_q = pl.cdiv(Sq, block_q)
    n_kv = pl.cdiv(Skv, block_k)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_kv=n_kv, seq_kv=Skv)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
