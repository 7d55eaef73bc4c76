"""Mamba-2 SSD chunked selective scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (Mamba-2, arXiv:2405.21060): the
sequence is tiled into (chunk x P) VMEM blocks; within a chunk the scan
is re-expressed as two MXU matmuls (an (L x L) decay-masked "attention"
for the intra-chunk term and an (L x N) x (N x P) contraction for the
inter-chunk term), while the (P x N) recurrent state is carried across
the sequential chunk axis in VMEM scratch — the HBM traffic is exactly
one pass over x/dt/B/C plus one (P x N) state, which is what makes long
sequences memory-optimal.

Recurrence (per head): h_t = exp(a·dt_t) h_{t-1} + dt_t · x_t ⊗ b_t;
y_t = h_t c_t, with a = −exp(a_log) < 0 so every decay factor is ≤ 1
(no stabilizer needed).

Grid: (B, H, n_chunks), chunks sequential.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, y_ref, hout_ref,
                h_ref, *, chunk: int, n_chunks: int, seq_len: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xc = x_ref[0, :, 0, :].astype(jnp.float32)            # (L, P)
    dtc = dt_ref[0, :, 0].astype(jnp.float32)[:, None]    # (L, 1)
    bc = b_ref[0].astype(jnp.float32)                     # (L, N)
    cc = c_ref[0].astype(jnp.float32)                     # (L, N)
    a = -jnp.exp(alog_ref[0, 0].astype(jnp.float32))      # scalar < 0

    # tail padding: zero dt => identity decay, zero update; zero the data
    # tensors too (pallas pads OOB tail blocks with undefined values)
    pos = ci * chunk + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    valid = pos < seq_len
    dtc = jnp.where(valid, dtc, 0.0)
    xc = jnp.where(valid, xc, 0.0)
    bc = jnp.where(valid, bc, 0.0)
    cc = jnp.where(valid, cc, 0.0)

    ad = a * dtc                                          # (L,1) log-decays
    cum = jnp.cumsum(ad, axis=0)                          # b_t = sum_{s<=t} ad_s

    # intra-chunk: M_{ts} = exp(b_t - b_s) (c_t . b_s) dt_s for s <= t
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    decay = jnp.exp(cum - cum.T)                          # (L, L)
    scores = jax.lax.dot_general(cc, bc, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    m = jnp.where(tri, decay * scores * dtc.T, 0.0)       # (L, L)
    y = jax.lax.dot_general(m, xc, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_t += exp(b_t) c_t . h_prev^T
    h_prev = h_ref[...]                                   # (P, N)
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        cc, h_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: h_new = exp(b_L) h_prev + x^T (b * dt * exp(b_L - b_s))
    total = cum[-1:, :]                                   # (1,1)
    w = jnp.exp(total - cum) * dtc                        # (L,1)
    h_ref[...] = (jnp.exp(total) * h_prev
                  + jax.lax.dot_general(xc, bc * w, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        hout_ref[0, 0] = h_ref[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba_chunk_scan(x, dt, a_log, b, c, *, chunk: int = 128,
                     interpret: bool):
    """x: (B,S,H,P); dt: (B,S,H); a_log: (H,); b,c: (B,S,N).

    Returns (y: (B,S,H,P), h_final: (B,H,P,N)).
    """
    B, S, H, P = x.shape
    N = b.shape[-1]
    chunk = min(chunk, S)
    n_chunks = pl.cdiv(S, chunk)
    alog2d = a_log.reshape(H, 1)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks,
                               seq_len=S)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, h, ci: (bi, ci, h, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, h, ci: (bi, ci, h)),
            pl.BlockSpec((1, 1), lambda bi, h, ci: (h, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, N), lambda bi, h, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bi, h, ci: (bi, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, P), lambda bi, h, ci: (bi, ci, h, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, alog2d, b, c)
    return y, h
