"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: the installed TPU compiler lowers and compiles each kernel
for a ``v5e:2x2`` topology that is described, not attached, and refuses
what the chip would refuse (VMEM over the scoped limit, misaligned
tiles). Shapes are deepseek-7b's (d 4096, 32x128 heads, d_ff 11008,
vocab 102400, bf16) at decode (M = 8) and at the largest prefill chunk
(M = 512), plus Swin-T's 7x7 windows with their score bias.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under pytest
workers the others must still collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_p
from repro.kernels.rowwise_matmul import rowwise_matmul_p

D, F, V = 4096, 11008, 102400


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# name -> (kernel call, operand shapes given M); the M = 512 prologue
# cases are the fused-norm panels the 16 MiB scoped VMEM limit refused
# before the planner counted the prologue's fp32 row panel
_MATMULS = {
    "qkv_rms": (
        lambda x, w, g: rowwise_matmul_p(x, w, prologue="rms", gamma=g),
        lambda m: [((m, D), jnp.bfloat16), ((D, 3 * D), jnp.bfloat16),
                   ((D,), jnp.float32)]),
    "gate_up_rms": (
        lambda x, w, wg, g: rowwise_matmul_p(
            x, w, w_gate=wg, activation="silu", prologue="rms", gamma=g),
        lambda m: [((m, D), jnp.bfloat16), ((D, F), jnp.bfloat16),
                   ((D, F), jnp.bfloat16), ((D,), jnp.float32)]),
    "down_residual": (
        lambda x, w, r: rowwise_matmul_p(x, w, residual=r),
        lambda m: [((m, F), jnp.bfloat16), ((F, D), jnp.bfloat16),
                   ((m, D), jnp.bfloat16)]),
    "lm_head_f32": (
        lambda x, w: rowwise_matmul_p(x, w, out_dtype=jnp.float32),
        lambda m: [((m, D), jnp.bfloat16), ((D, V), jnp.bfloat16)]),
}


@pytest.mark.parametrize("m", [8, 512])
@pytest.mark.parametrize("name", sorted(_MATMULS))
def test_rowwise_matmul_compiles_for_v5e(one_chip, name, m):
    fn, shapes = _MATMULS[name]
    _compile(fn, *[jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                   for s, dt in shapes(m)])


def test_flash_attention_prefill_compiles_for_v5e(one_chip):
    qkv = jax.ShapeDtypeStruct((1, 32, 512, 128), jnp.bfloat16,
                               sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_p(q, k, v, causal=True),
             qkv, qkv, qkv)


def test_flash_attention_swin_window_compiles_for_v5e(one_chip):
    # stage 1 of Swin-T on 8 images: 64 windows of 49 tokens each, 3
    # heads of 32, with the shifted-window bias (64 window positions)
    qkv = jax.ShapeDtypeStruct((64 * 8, 3, 49, 32), jnp.bfloat16,
                               sharding=one_chip)
    bias = jax.ShapeDtypeStruct((64, 3, 49, 49), jnp.float32,
                                sharding=one_chip)
    _compile(lambda q, k, v, b: flash_attention_p(q, k, v, causal=False,
                                                  bias=b),
             qkv, qkv, qkv, bias)
