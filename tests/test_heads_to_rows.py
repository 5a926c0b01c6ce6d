"""``olmoe._heads_to_rows``, the pass that takes q and k from their
projections to the flash kernels' rows and their cotangents back, against
the plain composition it replaced in ``olmoe.attention``: ``_rmsnorm`` ->
``rope`` -> ``jnp.repeat`` -> the softmax scale -> the kernels' layout,
in float32 - values and the hand-written gradients, one case for each
variant a cell of the benchmark runs, in miniature. And what the pass must
NOT touch: the dense family's lowered step and every configuration's count
of Mosaic calls."""

import hashlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import models
from torchft_tpu.models import mellum, olmoe, ouro, sdar
from torchft_tpu.models.transformer import _rmsnorm

B, S, DH = 2, 32, 16
THETA = 10000.0
YARN = mellum.tiny_mellum_config().kinds[-1].yarn

# heads, group, norm ("head", "whole" or None), yarn, stated positions
CASES = {
    # mellum2-ft1: norm per head, YaRN's blend, 8 query heads a key head
    "per_head_yarn_group_8": (2, 8, "head", YARN, None),
    # sdar-ft1: norm per head, both copies count 0..L-1, 8 a key head
    "per_head_stated_group_8": (2, 8, "head", None, jnp.tile(jnp.arange(S // 2), 2)),
    # ouro-ft1: no norm, every head its own
    "no_norm_group_1": (4, 1, None, None, None),
    # olmoe-ft1: one norm over the whole projection
    "whole_projection_group_1": (4, 1, "whole", None, None),
}


def _composition(spec, x, scale, yarn, positions):
    """The chain as ``attention`` had it, in float32 throughout."""
    n, dh = spec.heads, x.shape[-1] // spec.heads
    y = x.astype(jnp.float32)
    if scale is not None:
        y = y.reshape(B, S, n, dh) if spec.per_head else y
        y = _rmsnorm(y, scale.astype(jnp.float32), spec.eps)
    y = olmoe.rope(y.reshape(B, S, n, dh), THETA, yarn, positions)
    y = jnp.repeat(y, spec.group, axis=2) * spec.multiplier
    return y.transpose(0, 2, 1, 3).reshape(-1, S, dh)


def _case(name, dtype):
    heads, group, norm, yarn, positions = CASES[name]
    spec = olmoe.HeadsToRows(heads, group, norm == "head", 1e-5, DH ** -0.5)
    keys = jax.random.split(jax.random.PRNGKey(sum(map(ord, name))), 3)
    x = jax.random.normal(keys[0], (B, S, heads * DH), jnp.float32).astype(dtype)
    width = {"head": DH, "whole": heads * DH, None: 0}[norm]
    scale = None if norm is None else (
        1.0 + 0.2 * jax.random.normal(keys[1], (width,), jnp.float32)
    ).astype(dtype)
    g = jax.random.normal(keys[2], (B * heads * group, S, DH), jnp.float32).astype(dtype)
    tables = olmoe.rotary_tables(S, DH, THETA, yarn, positions)

    def mine(x, scale):
        return olmoe._heads_to_rows(spec, x, scale, tables)

    def plain(x, scale):
        return _composition(spec, x, scale, yarn, positions)

    return mine, plain, x, scale, g


def _one_rounding(got, want, dtype):
    """``got`` is ``want`` but for one rounding to ``dtype`` (and float32's
    own last bits): half a unit in the last place at ``want``'s size."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    eps = float(jnp.finfo(dtype).eps)
    ulp = eps * np.maximum(np.maximum(np.abs(want), np.abs(got)), float(jnp.finfo(dtype).tiny))
    slack = 4e-6 * np.max(np.abs(want))  # float32 sums in another order
    assert np.all(np.abs(got - want) <= 0.5 * ulp + slack + 1e-30), (
        float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_pass_is_the_plain_composition(name, dtype):
    """Rows, ``dx`` and the scale's gradient: in float32 the composition's
    to float32's last bits, in bfloat16 within ONE rounding of the float32
    composition of the same bfloat16 inputs (the chain rounded q three
    times and k twice)."""
    mine, plain, x, scale, g = _case(name, dtype)
    rows, back = jax.vjp(mine, x, scale)
    # the composition on float32 COPIES of the inputs, so that its
    # cotangents come back unrounded
    wide = [None if a is None else a.astype(jnp.float32) for a in (x, scale)]
    want_rows, want_back = jax.vjp(plain, *wide)
    assert rows.shape == want_rows.shape and rows.dtype == dtype
    _one_rounding(rows, want_rows, dtype)
    grads, want = back(g), want_back(g.astype(jnp.float32))
    assert grads[0].dtype == dtype and grads[0].shape == x.shape
    _one_rounding(grads[0], want[0], dtype)
    if scale is None:
        assert grads[1] is None
    else:
        assert grads[1].dtype == dtype and grads[1].shape == scale.shape
        _one_rounding(grads[1], want[1], dtype)


def test_a_value_projection_is_only_laid_out():
    """No scale and no tables: the heads copied to their group and laid
    out as rows, the cotangent the float32 sum of the copies'."""
    spec = olmoe.HeadsToRows(2, 4)
    v = jax.random.normal(jax.random.PRNGKey(0), (B, S, 2 * DH), jnp.bfloat16)
    rows, back = jax.vjp(lambda v: olmoe._heads_to_rows(spec, v, None, None), v)
    want = jnp.repeat(v.reshape(B, S, 2, DH), 4, axis=2).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(rows, want.reshape(-1, S, DH))
    g = jax.random.normal(jax.random.PRNGKey(1), rows.shape, jnp.bfloat16)
    summed = g.astype(jnp.float32).reshape(B, 2, 4, S, DH).sum(2).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(back(g)[0], summed.reshape(B, S, -1).astype(jnp.bfloat16))


def test_the_whole_row_tables_are_ropes_pairs():
    """``x cos + swap(x) sin`` over whole rows is ``rope``, bit for bit,
    with YaRN's blend and at stated positions."""
    x = jax.random.normal(jax.random.PRNGKey(2), (B, S, 3, DH), jnp.float32)
    for yarn, positions in ((None, None), (YARN, None), (YARN, jnp.tile(jnp.arange(S // 2), 2))):
        cos, sin = olmoe.rotary_tables(S, DH, THETA, yarn, positions)
        turned = x * cos[:, None, :] + jnp.roll(x, DH // 2, axis=-1) * sin[:, None, :]
        np.testing.assert_array_equal(turned, olmoe.rope(x, THETA, yarn, positions))
        np.testing.assert_array_equal(olmoe._swap_halves(x), jnp.roll(x, DH // 2, axis=-1))


# -- what the pass must not touch ------------------------------------------------

# sha256 of the tiny GPT-2 configuration's lowered gradient step (use_flash,
# bf16 compute copy, 2 x 33 tokens) as PR 48's parent lowers it on the CPU.
# The dense family calls ``flash_attention_qkv`` and nothing of
# ``models/olmoe.py``; a change that moves this text moved the four GPT-2
# cells' programs, and has to say so (then: print the new digest here).
TINY_GPT2_STEP = "e4044625273c5e5bdc1cd95ecfbf79bd46ec8fed81a7d380e35d89c60617d02d"


def _bf16(shapes):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype
        ), shapes,
    )


def test_the_dense_familys_lowered_step_is_the_parents_text():
    import dataclasses

    cfg = dataclasses.replace(models.tiny_config(), use_flash=True)
    params = _bf16(jax.eval_shape(lambda: models.init_params(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((2, 33), jnp.int32)
    text = jax.jit(jax.grad(lambda p, t: models.loss_fn(cfg, p, t))).lower(params, tokens).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == TINY_GPT2_STEP


@pytest.mark.parametrize("model", ["olmoe", "mellum2", "sdar", "ouro"])
def test_the_sparse_familys_mosaic_calls_are_what_they_were(model, monkeypatch):
    """Lowered for the TPU, a configuration's gradient step holds the
    Mosaic calls it held before the pass: the flash forward and the fused
    backward of every layer (a looped stack: of its two loop bodies, and
    the recomputed forward's), which the benchmark's families count
    (``lowered_mosaic_calls``). The pass adds none: it is XLA's."""
    cfg, calls_a_layer, seq = {
        "olmoe": (olmoe.tiny_olmoe_config(), 2, 33),
        "mellum2": (mellum.tiny_mellum_config(), 2, 33),
        "sdar": (sdar.tiny_sdar_config(), 2, 32),
        "ouro": (ouro.tiny_ouro_config(), 3, 33),
    }[model]
    fa = sys.modules["torchft_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_pick_interpret", lambda _i: False)
    params = _bf16(jax.eval_shape(lambda: olmoe.init_params(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    lowered = jax.jit(jax.grad(lambda p, t: olmoe.loss_fn(cfg, p, t))).trace(
        params, tokens
    ).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == calls_a_layer * cfg.n_layers
