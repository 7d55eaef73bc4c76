"""Compile-only checks for a TPU v5e: the main path's Pallas kernels at
the widths ``chip_smoke.py`` runs them, and the float64 problem-(13)
planner, compiled by the TPU compiler for a described (not attached)
``v5e:2x2`` topology.  Nothing runs, so these check what interpret-mode
tests cannot: block tiling, fast-memory use and lowering."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import resource_opt_jax as roj
from repro.kernels import decode_attn, flash_attn, split_quant

# smollm_360m attention widths; the ResNet-18/224 l2 boundary activation
# of a 32-image batch, (32, 28, 28, 128), as quantized rows
Q_HEADS, KV_HEADS, HEAD_DIM, SEQ, SLOTS = 15, 5, 64, 2048, 8
L2_ROWS, L2_CH = 32 * 28 * 28, 128
N_PLAN = 4608               # the benchmark's solver_backend grid


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16 * 2**30        # one v5e's HBM
    return compiled.as_text()


@pytest.mark.parametrize("kernel", ["split_quant", "flash_attn",
                                    "decode_attn"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bf = jnp.bfloat16
    if kernel == "split_quant":
        hlo = _compile(
            lambda x: split_quant.quantize_rows(x, interpret=False),
            sds((L2_ROWS, L2_CH), jnp.float32))
    elif kernel == "flash_attn":
        hlo = _compile(
            lambda q, k, v: flash_attn.flash_attention_fwd(
                q, k, v, causal=True, interpret=False),
            sds((1, Q_HEADS, SEQ, HEAD_DIM), bf),
            sds((1, KV_HEADS, SEQ, HEAD_DIM), bf),
            sds((1, KV_HEADS, SEQ, HEAD_DIM), bf))
    else:
        hlo = _compile(
            lambda q, k, v, n: decode_attn.decode_attention(
                q, k, v, n, interpret=False),
            sds((SLOTS, Q_HEADS, 1, HEAD_DIM), bf),
            sds((SLOTS, KV_HEADS, SEQ, HEAD_DIM), bf),
            sds((SLOTS, KV_HEADS, SEQ, HEAD_DIM), bf),
            sds((SLOTS,), jnp.int32))
    assert "tpu_custom_call" in hlo          # the Mosaic kernel, not jnp


def test_planner_compiles_for_v5e_under_x64(one_chip):
    with roj.x64_scope():
        def sds(*shape):
            return jax.ShapeDtypeStruct((N_PLAN,) + shape, jnp.float64,
                                        sharding=one_chip)

        coeffs = roj.CoeffArrays(
            k=sds(2), tmin_p=sds(2), cc=sds(2), tmin_c=sds(2), gain=sds(),
            t_budget=sds(), e_isl=sds(), t_fixed=sds())
        hlo = _compile(roj.shed_and_solve_coeffs, coeffs)
    assert "while" in hlo                    # the dual bisection loop
