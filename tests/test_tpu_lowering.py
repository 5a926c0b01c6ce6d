"""Cross-lowers every Pallas entry point for the TPU, from the CPU.

Interpret mode accepts programs Mosaic refuses (a scalar store into VMEM,
a scalar bitcast, a misaligned block), so the interpret-mode oracle tests
say nothing about whether a kernel BUILDS for the chip.
``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
real Pallas->Mosaic lowering without a TPU backend; each case asserts the
lowered module carries the Mosaic custom call. What this cannot see is the
TPU compiler proper (VMEM budget, layout inference) — ``chip_smoke.py``'s
``kernels`` phase covers that on hardware.
"""

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu.ops import (
    cast_bf16,
    dequantize_q8,
    flash_attention,
    quantize_q8_ef,
)


def _mosaic_calls(fn, *args) -> int:
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize(
    "seq,window",
    [(256, None), (99, None), (2047, None), (1024, 256), (1024, None),
     (1023, None), (4096, None)],
)
def test_flash_forward_and_backward_lower(head_dim, seq, window):
    q = jnp.ones((2, seq, 2, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, window=window, interpret=False)
        return out.astype(jnp.float32).sum()

    assert _mosaic_calls(loss, q, q, q) == 1
    # forward + the single fused backward kernel
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == 2


# one element, a single ragged block, exactly one block, the big model's
# 1024x4096 leaf (128 blocks), and an odd multi-block length
@pytest.mark.parametrize(
    "shape", [(1,), (257,), (256, 128), (1024, 4096), (70001,)]
)
def test_wire_kernels_lower(shape):
    x = jnp.ones(shape, jnp.float32)
    q = jnp.ones(shape, jnp.int8)
    assert _mosaic_calls(
        lambda x, r: quantize_q8_ef(x, r, interpret=False), x, x
    ) == 1
    assert _mosaic_calls(
        lambda q: dequantize_q8(q, jnp.float32(0.5), interpret=False), q
    ) == 1
    assert _mosaic_calls(lambda x: cast_bf16(x, interpret=False), x) == 1
