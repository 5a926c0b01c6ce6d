"""``next_token_loss`` against the formula it replaced.

The oracle is the parent's loss - ``log_softmax`` + ``take_along_axis`` and
JAX's own differentiation of both - on float32 logits. The ``custom_vjp``
must give its value and gradient for float32 logits, and for bf16 logits
what the oracle gives on the same values widened: the softmax runs in
float32 either way, only the storage and the cotangent's type follow the
input.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models.transformer import next_token_loss

DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}
# bf16 keeps 8 bits: a float32 gradient rounded once lies within 2**-9 of
# itself, relatively; one more bit of room for the oracle's own rounding
BF16_RTOL = 2.0 ** -8
# the gradient's tolerances by the logits' type: (rtol, atol); float16's
# smallest step is 2**-24, and a probability of 1 / V / N is below 2**-14
GRAD_TOL = {
    jnp.dtype(jnp.float32): (1e-6, 1e-9),
    jnp.dtype(jnp.bfloat16): (BF16_RTOL, 1e-9),
    jnp.dtype(jnp.float16): (2.0 ** -10, 2.0 ** -24),
}


def oracle(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def draw(shape, dtype, seed=0, spread=3.0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    logits = (jax.random.normal(k1, shape, jnp.float32) * spread).astype(dtype)
    targets = jax.random.randint(k2, shape[:-1], 0, shape[-1], jnp.int32)
    return logits, targets


def check(logits, targets, scale=1.0, wrap=lambda f: f):
    """Value and gradient of ``scale * loss`` (``wrap``ped, e.g. in
    ``jax.jit``) against the oracle on the widened logits; returns the
    gradient."""
    got, grad = wrap(jax.value_and_grad(lambda l: scale * next_token_loss(l, targets)))(logits)
    want, want_grad = jax.value_and_grad(lambda l: scale * oracle(l, targets))(
        logits.astype(jnp.float32)
    )
    assert got.dtype == jnp.float32
    assert grad.dtype == logits.dtype and grad.shape == logits.shape
    np.testing.assert_allclose(got, want, rtol=1e-6)
    rtol, atol = GRAD_TOL[logits.dtype]
    np.testing.assert_allclose(
        grad.astype(jnp.float32), want_grad, rtol=rtol, atol=atol
    )
    return grad


# 256: whole lanes; 257: one over; 50257: GPT-2's, odd and no multiple of 128
@pytest.mark.parametrize("vocab", [256, 257, 50257])
@pytest.mark.parametrize("dtype", DTYPES)
def test_value_and_gradient_match_the_parents_formula(dtype, vocab):
    logits, targets = draw((2, 3, vocab), DTYPES[dtype], seed=vocab)
    check(logits, targets)


@pytest.mark.parametrize("which", ["first", "last"])
@pytest.mark.parametrize("dtype", DTYPES)
def test_targets_at_the_ends_of_the_vocabulary(dtype, which):
    logits, targets = draw((5, 257), DTYPES[dtype], seed=3)
    targets = jnp.full_like(targets, 0 if which == "first" else 256)
    grad = check(logits, targets)
    # the target's column is the only one with probability - 1 < 0
    column = grad[:, 0 if which == "first" else 256].astype(jnp.float32)
    assert bool(jnp.all(column < 0))


@pytest.mark.parametrize("dtype", DTYPES)
def test_large_logits_do_not_overflow(dtype):
    """Rows at +-80: exp(80) overflows nothing only because the row's
    maximum is subtracted first; exp(160) in float32 is inf. The backward
    pass subtracts the maximum too (``exp(l - lse)`` at |l| = 80, where
    float32 steps by 7.6e-6, would agree to 1e-5 and not to 1e-6)."""
    logits, targets = draw((4, 257), DTYPES[dtype], seed=5, spread=1.0)
    logits = logits.at[0].add(80).at[1].add(-80).at[2, ::2].set(80).at[2, 1::2].set(-80)
    grad = check(logits, targets)
    assert bool(jnp.all(jnp.isfinite(grad.astype(jnp.float32))))


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_row_of_equal_logits_is_uniform(dtype):
    vocab = 257
    logits = jnp.full((3, vocab), 7.5, DTYPES[dtype])
    targets = jnp.array([0, 100, vocab - 1], jnp.int32)
    grad = check(logits, targets)
    np.testing.assert_allclose(
        next_token_loss(logits, targets), np.log(vocab), rtol=1e-6
    )
    # every non-target column holds 1 / (V N)
    np.testing.assert_allclose(
        grad[0, 1:].astype(jnp.float32), 1 / (vocab * 3), rtol=BF16_RTOL
    )


@pytest.mark.parametrize("dtype", DTYPES)
def test_an_upstream_cotangent_scales_the_gradient(dtype):
    logits, targets = draw((2, 3, 256), DTYPES[dtype], seed=7)
    one = check(logits, targets)
    scaled = check(logits, targets, scale=-3.5)
    np.testing.assert_allclose(
        scaled.astype(jnp.float32), -3.5 * one.astype(jnp.float32),
        rtol=2 * BF16_RTOL, atol=1e-9,
    )


@pytest.mark.parametrize("dtype", DTYPES)
def test_under_jit_with_flat_rows(dtype):
    """(N, V) logits, as a pipeline's last stage holds them, jitted."""
    check(*draw((6, 257), DTYPES[dtype], seed=11), wrap=jax.jit)


def test_float16_logits_come_back_as_float16():
    """The storage follows the input's type, whatever float it is."""
    logits, targets = draw((4, 256), jnp.float16, seed=13)
    assert check(logits, targets).dtype == jnp.float16


def test_the_targets_take_no_gradient():
    logits, targets = draw((4, 256), jnp.float32, seed=17)
    _, vjp = jax.vjp(next_token_loss, logits, targets)
    d_logits, d_targets = vjp(jnp.float32(1.0))
    assert d_logits.dtype == jnp.float32
    assert d_targets.dtype == jax.dtypes.float0
