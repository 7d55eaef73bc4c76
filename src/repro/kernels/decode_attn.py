"""Flash-decode attention — Pallas TPU kernel for the memory-bound cells.

decode_32k / long_500k lower a single new token against a (possibly
huge) KV cache: arithmetic intensity ~ O(1) FLOP/byte, so the kernel's
job is purely to stream the cache HBM->VMEM once at line rate. The
(1 x head_dim) query and the fp32 (m, l, acc) running softmax state stay
in VMEM across the sequential kv-block axis; per-batch valid cache
lengths mask the tail.

Grid: (B, H, n_kv_blocks), kv innermost sequential. GQA via index maps
(h -> h // group), same as flash_attn.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_k: int, n_kv: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_start = ki * block_k
    valid_len = len_ref[pl.program_id(0), 0]

    @pl.when(k_start < valid_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)              # (bk, d)
        kv_valid = (k_start
                    + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
                    < valid_len)
        k = jnp.where(kv_valid, k, 0.0)
        v = jnp.where(kv_valid, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (1, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = jnp.where(kpos < valid_len, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths, *, block_k: int = 512,
                     interpret: bool):
    """q: (B, H, 1, D); k, v: (B, KV, S, D); lengths: (B,) valid cache len."""
    B, H, _, D = q.shape
    KV, S = k.shape[1], k.shape[2]
    group = H // KV
    block_k = min(block_k, S)
    n_kv = pl.cdiv(S, block_k)
    scale = 1.0 / math.sqrt(D)

    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, n_kv=n_kv)
    lengths2d = lengths.reshape(B, 1).astype(jnp.int32)

    return pl.pallas_call(
        kernel,
        grid=(B, H, n_kv),
        in_specs=[
            # the whole (B, 1) length table sits in SMEM: a (1, 1) block
            # of it breaks the TPU's (8, 128) block-tiling rule
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths2d, q, k, v)
