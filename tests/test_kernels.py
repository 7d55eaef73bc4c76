"""Per-kernel allclose sweeps: Pallas (interpret=True) and the chunked
jnp ops paths against the pure-jnp oracles, across shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attn import decode_attention as pallas_decode
from repro.kernels.flash_attn import flash_attention_fwd
from repro.kernels.mamba_scan import mamba_chunk_scan
from repro.kernels.mlstm_scan import mlstm_chunk_scan
from repro.kernels.split_quant import quantize_rows as pallas_quant

RNG = np.random.default_rng(42)


def rnd(*s, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(s), dtype)


ATTN_SWEEP = [
    # (B, H, KV, Sq, Skv, D, causal, window)
    (1, 2, 2, 64, 64, 16, True, None),
    (2, 4, 2, 200, 200, 32, True, None),       # GQA + ragged tail
    (2, 4, 4, 128, 128, 64, False, None),      # bidir MHA
    (1, 8, 2, 96, 96, 32, True, 48),           # sliding window
    (2, 3, 1, 65, 130, 16, False, None),       # cross-attn Sq != Skv
]


@pytest.mark.parametrize("B,H,KV,Sq,Skv,D,causal,window", ATTN_SWEEP)
def test_flash_attn_pallas_vs_ref(B, H, KV, Sq, Skv, D, causal, window):
    q, k, v = rnd(B, H, Sq, D), rnd(B, KV, Skv, D), rnd(B, KV, Skv, D)
    r = ref.attention(q, k, v, causal=causal, window=window)
    p = flash_attention_fwd(q, k, v, causal=causal, window=window,
                            block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(p, r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,H,KV,Sq,Skv,D,causal,window", ATTN_SWEEP)
def test_flash_attn_chunked_vs_ref(B, H, KV, Sq, Skv, D, causal, window):
    q, k, v = rnd(B, H, Sq, D), rnd(B, KV, Skv, D), rnd(B, KV, Skv, D)
    r = ref.attention(q, k, v, causal=causal, window=window)
    c = ops.flash_attention(q, k, v, causal=causal, window=window,
                            block_q=48, block_k=32, use_pallas=False)
    np.testing.assert_allclose(c, r, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attn_dtypes(dtype):
    q = rnd(1, 4, 64, 32, dtype=dtype)
    k = rnd(1, 2, 64, 32, dtype=dtype)
    v = rnd(1, 2, 64, 32, dtype=dtype)
    r = ref.attention(q, k, v, causal=True)
    p = flash_attention_fwd(q, k, v, causal=True, block_q=32, block_k=32,
                            interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(p.astype(jnp.float32),
                               r.astype(jnp.float32), atol=tol, rtol=tol)
    assert p.dtype == dtype


def test_flash_attn_grads_vs_ref():
    B, H, KV, S, D = 1, 4, 2, 96, 16
    q, k, v = rnd(B, H, S, D), rnd(B, KV, S, D), rnd(B, KV, S, D)

    def f_ref(q, k, v):
        return (ref.attention(q, k, v, causal=True) ** 2).sum()

    def f_chk(q, k, v):
        return (ops.flash_attention(q, k, v, causal=True, block_q=32,
                                    block_k=32, use_pallas=False) ** 2).sum()

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(f_chk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gc):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=5e-4)


def test_flash_attn_grads_windowed():
    B, H, KV, S, D = 1, 2, 2, 80, 16
    q, k, v = rnd(B, H, S, D), rnd(B, KV, S, D), rnd(B, KV, S, D)

    def f_ref(q, k, v):
        return (ref.attention(q, k, v, causal=True, window=32) ** 2).sum()

    def f_chk(q, k, v):
        return (ops.flash_attention(q, k, v, causal=True, window=32,
                                    block_q=16, block_k=16,
                                    use_pallas=False) ** 2).sum()

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gc = jax.grad(f_chk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gc):
        np.testing.assert_allclose(b, a, atol=5e-4, rtol=5e-4)


DECODE_SWEEP = [
    (3, 8, 2, 130, 32, [130, 64, 1]),
    (1, 4, 4, 512, 64, [300]),
    (2, 2, 1, 64, 128, [64, 17]),
]


@pytest.mark.parametrize("B,H,KV,S,D,lens", DECODE_SWEEP)
def test_decode_attn(B, H, KV, S, D, lens):
    q = rnd(B, H, 1, D)
    k, v = rnd(B, KV, S, D), rnd(B, KV, S, D)
    lengths = jnp.asarray(lens, jnp.int32)
    r = ref.attention(q, k, v, causal=False, kv_len=lengths)
    p = pallas_decode(q, k, v, lengths, block_k=64, interpret=True)
    c = ops.decode_attention(q, k, v, lengths, use_pallas=False)
    np.testing.assert_allclose(p, r, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(c, r, atol=2e-5, rtol=2e-5)


MAMBA_SWEEP = [(1, 64, 2, 8, 4, 32), (2, 100, 3, 16, 8, 32),
               (1, 257, 4, 32, 16, 64)]


@pytest.mark.parametrize("B,S,H,P,N,chunk", MAMBA_SWEEP)
def test_mamba_scan(B, S, H, P, N, chunk):
    x = rnd(B, S, H, P)
    dt = jax.nn.softplus(rnd(B, S, H))
    alog = rnd(H) * 0.5
    b, c = rnd(B, S, N), rnd(B, S, N)
    yr, hr = ref.mamba_ssd(x, dt, alog, b, c)
    yp, hp = mamba_chunk_scan(x, dt, alog, b, c, chunk=chunk,
                              interpret=True)
    yj, hj = ops.mamba_scan(x, dt, alog, b, c, chunk=chunk, use_pallas=False)
    np.testing.assert_allclose(yp, yr, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(hp, hr, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(yj, yr, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(hj, hr, atol=5e-4, rtol=5e-4)


def test_mamba_decode_step_matches_scan():
    B, S, H, P, N = 2, 33, 2, 8, 4
    x = rnd(B, S, H, P)
    dt = jax.nn.softplus(rnd(B, S, H))
    alog = rnd(H) * 0.5
    b, c = rnd(B, S, N), rnd(B, S, N)
    y_all, h_all = ref.mamba_ssd(x, dt, alog, b, c)
    # run scan on first S-1, then one decode step
    y0, h0 = ops.mamba_scan(x[:, :-1], dt[:, :-1], alog, b[:, :-1],
                            c[:, :-1], chunk=16, use_pallas=False)
    y1, h1 = ops.mamba_decode_step(h0, x[:, -1], dt[:, -1], alog,
                                   b[:, -1], c[:, -1])
    np.testing.assert_allclose(y1, y_all[:, -1], atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(h1, h_all, atol=5e-4, rtol=5e-4)


MLSTM_SWEEP = [(1, 64, 2, 8, 16), (2, 100, 2, 16, 32), (1, 130, 1, 32, 64)]


@pytest.mark.parametrize("B,S,H,P,chunk", MLSTM_SWEEP)
def test_mlstm_scan(B, S, H, P, chunk):
    q, k, v = rnd(B, S, H, P), rnd(B, S, H, P), rnd(B, S, H, P)
    ip, fp = rnd(B, S, H), rnd(B, S, H) + 1.0
    hr, (Cr, nr, mr) = ref.mlstm(q, k, v, ip, fp)
    hp, (Cp, np_, mp) = mlstm_chunk_scan(q, k, v, ip, fp, chunk=chunk,
                                         interpret=True)
    hj, (Cj, nj, mj) = ops.mlstm_scan(q, k, v, ip, fp, chunk=chunk,
                                      use_pallas=False)
    np.testing.assert_allclose(hp, hr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(Cp, Cr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np_[..., 0], nr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(hj, hr, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(Cj, Cr, atol=1e-4, rtol=1e-4)


def test_mlstm_decode_step_matches_scan():
    B, S, H, P = 1, 17, 2, 8
    q, k, v = rnd(B, S, H, P), rnd(B, S, H, P), rnd(B, S, H, P)
    ip, fp = rnd(B, S, H), rnd(B, S, H)
    h_all, (C_all, n_all, m_all) = ref.mlstm(q, k, v, ip, fp)
    _, st = ops.mlstm_scan(q[:, :-1], k[:, :-1], v[:, :-1], ip[:, :-1],
                           fp[:, :-1], chunk=8, use_pallas=False)
    h1, (C1, n1, m1) = ops.mlstm_decode_step(
        st, q[:, -1], k[:, -1], v[:, -1], ip[:, -1], fp[:, -1])
    np.testing.assert_allclose(h1, h_all[:, -1], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(C1, C_all, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(m1, m_all, atol=1e-5)


@pytest.mark.parametrize("rows,d,block", [(16, 32, 8), (37, 64, 16),
                                          (5, 128, 256)])
def test_split_quant(rows, d, block):
    x = rnd(rows, d) * 7.3
    qq, ss = pallas_quant(x, block_rows=block, interpret=True)
    qr, sr = ref.quantize_rows(x)
    np.testing.assert_array_equal(np.asarray(qq), np.asarray(qr))
    np.testing.assert_allclose(ss, sr, rtol=1e-6)
    # dequant error bounded by scale/2 per element
    deq = ops.dequantize_boundary(qq, ss)
    assert float(jnp.max(jnp.abs(deq - x))) <= float(jnp.max(ss)) * 0.51


def test_ste_quantize_grad_passthrough():
    x = rnd(8, 16)
    g = jax.grad(lambda t: (ops.ste_quantize(t) * 3.0).sum())(x)
    np.testing.assert_allclose(g, jnp.full_like(x, 3.0))


def test_inner_unroll_equivalence():
    """The dry-run cost mode (unrolled inner scans) is numerically
    identical to the streaming mode."""
    q, k, v = rnd(1, 4, 96, 16), rnd(1, 2, 96, 16), rnd(1, 2, 96, 16)
    base = ops.flash_attention(q, k, v, causal=True, block_q=32,
                               block_k=32, use_pallas=False)
    ops.set_inner_unroll(True)
    try:
        unrolled = ops.flash_attention(q, k, v, causal=True, block_q=32,
                                       block_k=32, use_pallas=False)
    finally:
        ops.set_inner_unroll(False)
    np.testing.assert_allclose(base, unrolled, atol=1e-6)
