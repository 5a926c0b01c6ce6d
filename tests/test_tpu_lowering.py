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
    flash_attention_qkv,
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


def _lowered_text(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def _qkv_grad_text(n_heads: int, head_dim: int = 64) -> str:
    qkv = jnp.ones((2, 1024, 3 * n_heads * head_dim), jnp.bfloat16)

    def loss(qkv):
        out = flash_attention_qkv(qkv, n_heads, interpret=False)
        return out.astype(jnp.float32).sum()

    return _lowered_text(jax.grad(loss), qkv)


def test_fused_projection_kernels_do_not_grow_with_the_head_count():
    """What refused PR 27 (PERF.md section 6): set-up time that grew with
    the model. The lowered text carries each kernel's serialized body, and
    tracing, lowering and compiling all follow its size; heads are on the
    grid, so 16 of them cost what 2 do, and there are two kernels."""
    two, sixteen = _qkv_grad_text(2), _qkv_grad_text(16)
    assert two.count("tpu_custom_call") == 2
    assert sixteen.count("tpu_custom_call") == 2
    assert len(sixteen) <= 1.2 * len(two), (len(two), len(sixteen))


@pytest.mark.parametrize("head_dim", [64, 128])
def test_fused_projection_body_is_at_most_twice_the_three_array_one(head_dim):
    """Two heads of 64 columns share a 128-lane block, so its body holds
    the three-array kernel's schedule twice; a 128-wide head once, plus
    the backward's own copies of dq, dk, dv into the one cotangent. (The
    serialized bodies carry their Python call stack, so the lengths move
    a few per cent with the caller.)"""
    q = jnp.ones((2, 1024, 2, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    three = _lowered_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    fused = _qkv_grad_text(2, head_dim)
    assert fused.count("tpu_custom_call") == three.count("tpu_custom_call") == 2
    assert len(fused) <= (1.7 if head_dim == 64 else 1.4) * len(three)


@pytest.mark.parametrize("n_heads,seq", [(2, 1025), (2, 1024), (3, 1025)])
def test_transformer_gradient_has_two_mosaic_calls_a_layer(n_heads, seq, monkeypatch):
    """The benchmark's own check (``common.require_mosaic``): exactly two
    Mosaic calls a layer in the gradient of the loss - no third kernel, no
    shared outlined function - through the fused entry (even heads), its
    padded form (1023 positions) and its three-array way out (odd)."""
    import sys

    from torchft_tpu.models import TransformerConfig, init_params, loss_fn

    fa = sys.modules["torchft_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_pick_interpret", lambda _i: False)
    cfg = TransformerConfig(
        vocab_size=256, d_model=64 * n_heads, n_heads=n_heads, n_layers=4,
        d_ff=128, max_seq_len=1024, use_flash=True,
    )
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    text = _lowered_text(jax.grad(lambda p, t: loss_fn(cfg, p, t)), params, tokens)
    assert text.count("tpu_custom_call") == 2 * cfg.n_layers
