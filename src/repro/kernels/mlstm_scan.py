"""xLSTM mLSTM chunkwise-parallel scan — Pallas TPU kernel.

The mLSTM matrix memory C_t = f_t C_{t-1} + i_t k_t v_t^T (xLSTM,
arXiv:2405.04517) is computed in its chunkwise-parallel form: within a
(chunk x P) VMEM tile the recurrence becomes a decay-masked (L x L)
attention matrix (two MXU matmuls), and the (P x P) matrix memory plus
its (P,) normalizer and scalar stabilizer are carried across the
sequential chunk axis in VMEM scratch.

Exact stabilization: unrolling the sequential stabilizer
m_t = max(lf_t + m_{t-1}, li_t) gives m_t = max(b_t + m_0,
max_{s<=t}(b_t - b_s + li_s)) with b = cumsum(log f) — so the chunkwise
row stabilizers equal the sequential ones exactly and the kernel is
bit-faithful (up to fp) to the paper's recurrence, including the
max(|den|, exp(-m_t)) normalizer.

Grid: (B, H, n_chunks), chunks sequential. VMEM note: the (P x P)
memory tile bounds P at ~512 for fp32 scratch; larger head dims tile the
value dimension (n_v_tiles grid axis would be added) — the assigned
xlstm-1.3b (P=1024) runs the jnp chunked path at train shapes and this
kernel validates the algorithm at P<=512.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mlstm_kernel(q_ref, k_ref, v_ref, i_ref, f_ref,
                  h_ref, cout_ref, nout_ref, mout_ref,
                  c_s, n_s, m_s, *, chunk: int, n_chunks: int,
                  seq_len: int, scale: float):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        c_s[...] = jnp.zeros_like(c_s)
        n_s[...] = jnp.zeros_like(n_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)

    qc = q_ref[0, :, 0, :].astype(jnp.float32) * scale    # (L, P)
    kc = k_ref[0, :, 0, :].astype(jnp.float32)
    vc = v_ref[0, :, 0, :].astype(jnp.float32)
    li = i_ref[0, :, 0].astype(jnp.float32)[:, None]      # (L, 1)
    lf = -jax.nn.softplus(-f_ref[0, :, 0].astype(jnp.float32))[:, None]

    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    valid = pos < seq_len
    lf = jnp.where(valid, lf, 0.0)                        # pad: f=1, i=0
    li = jnp.where(valid, li, NEG_INF)
    qc = jnp.where(valid, qc, 0.0)                        # zero OOB tails
    kc = jnp.where(valid, kc, 0.0)
    vc = jnp.where(valid, vc, 0.0)

    b = jnp.cumsum(lf, axis=0)                            # (L, 1) inclusive
    m_prev = m_s[0, 0]
    c_prev, n_prev = c_s[...], n_s[...]                   # (P,P), (P,1)

    # D_{ts} = b_t - b_s + li_s for s <= t
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    dmat = jnp.where(tri, b - b.T + li.T, NEG_INF)        # (L, L)

    m_intra = jnp.max(dmat, axis=1, keepdims=True)        # (L, 1)
    m_inter = b + m_prev
    m_row = jnp.maximum(m_intra, m_inter)                 # == sequential m_t

    s_intra = jax.lax.dot_general(qc, kc, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    w = jnp.exp(dmat - m_row)                             # (L, L)
    sw = s_intra * w
    inter_scale = jnp.exp(m_inter - m_row)                # (L, 1)

    num = (jax.lax.dot_general(sw, vc, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
           + inter_scale * jax.lax.dot_general(
               qc, c_prev, (((1,), (0,)), ((), ())),
               preferred_element_type=jnp.float32))       # (L, P)
    den = (jnp.sum(sw, axis=1, keepdims=True)
           + inter_scale * jax.lax.dot_general(
               qc, n_prev, (((1,), (0,)), ((), ())),
               preferred_element_type=jnp.float32))       # (L, 1)
    den = jnp.maximum(jnp.abs(den), jnp.exp(-m_row))
    h_ref[0, :, 0, :] = (num / den).astype(h_ref.dtype)

    # carry update to end-of-chunk state
    btot = b[-1:, :]                                      # (1, 1)
    m_new = m_row[-1, 0]                                  # sequential m at L-1
    wk = jnp.exp(btot - b + li - m_new)                   # (L, 1)
    decay = jnp.exp(btot[0, 0] + m_prev - m_new)
    c_s[...] = decay * c_prev + jax.lax.dot_general(
        kc * wk, vc, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    n_s[...] = decay * n_prev + jax.lax.dot_general(
        kc * wk, jnp.ones((chunk, 1), jnp.float32),
        (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_s[0, 0] = m_new

    @pl.when(ci == n_chunks - 1)
    def _final():
        cout_ref[0, 0] = c_s[...]
        nout_ref[0, 0, :, 0] = n_s[:, 0]
        mout_ref[0, 0] = m_s[0, 0]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_chunk_scan(q, k, v, i_pre, f_pre, *, chunk: int = 128,
                     interpret: bool):
    """q,k,v: (B,S,H,P); i_pre,f_pre: (B,S,H).

    Returns (h: (B,S,H,P), (C: (B,H,P,P), n: (B,H,P,1), m: (B,H))).
    """
    B, S, H, P = q.shape
    chunk = min(chunk, S)
    n_chunks = pl.cdiv(S, chunk)
    scale = 1.0 / math.sqrt(P)

    kernel = functools.partial(_mlstm_kernel, chunk=chunk,
                               n_chunks=n_chunks, seq_len=S, scale=scale)
    h, c, n, m = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, hh, ci: (b, ci, hh, 0)),
            pl.BlockSpec((1, chunk, 1, P), lambda b, hh, ci: (b, ci, hh, 0)),
            pl.BlockSpec((1, chunk, 1, P), lambda b, hh, ci: (b, ci, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, ci: (b, ci, hh)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, ci: (b, ci, hh)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda b, hh, ci: (b, ci, hh, 0)),
            pl.BlockSpec((1, 1, P, P), lambda b, hh, ci: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1, P, 1), lambda b, hh, ci: (b, hh, 0, 0)),
            pl.BlockSpec((1, 1), lambda b, hh, ci: (b, hh)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), q.dtype),
            jax.ShapeDtypeStruct((B, H, P, P), jnp.float32),
            jax.ShapeDtypeStruct((B, H, P, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((P, P), jnp.float32),
            pltpu.VMEM((P, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, i_pre, f_pre)
    return h, (c, n, m)
