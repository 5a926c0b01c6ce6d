"""Nemotron 3 Nano as a configuration of the family in models/olmoe.py
(torchft_tpu.models.nemotron: layers that are ONE sublayer each - a Mamba-2
mixer with grouped maps over ``ops/ssd.py``'s chunked scan and a gated norm a
group, a softmax-attention layer with no position signal, or a rank's share
of sigmoid-routed ungated ``relu ** 2`` experts beside a shared one - an
untied readout, a stack recomputed a layer) against its plain reference
(benchmark/reference_nemotron.py: the recurrence position by position, every
held expert on every token), at the rehearsal sizes on the CPU, seeded
weights: the pattern ``MEM*E``; Mamba 8 heads of 16 in 2 groups over a state
of 16 in chunks of 16; 4 query heads over 2 key/value heads of 16; 2 of 8
experts of width 24 held, 2 a token, a shared expert of 48.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (the scan in chunks against one position at
a time; flash tiles against a dense softmax a head; the share's tiles against
every expert on every token), so they differ by float32 rounding alone: the
loss is held to 1e-5 and every gradient leaf to 1e-4 of its largest entry,
far under what the smallest wrong term costs (``test_a_wrong_term_is_caught``).
"""

import dataclasses
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import common, controls_nemotron, reference, reference_nemotron
from benchmark.reduce import spans
from torchft_tpu.models import dsv2, granite, ling, mellum, nemotron, olmoe, ouro, sdar
from torchft_tpu.ops.ssd import ssd_scan

CONFIG = os.path.join(
    os.path.dirname(__file__), "..", "benchmark", "configs", "nemotron3-nano-l9-ep16.json"
)
LOSS_RTOL_F32, GRAD_RTOL_F32 = 1e-5, 1e-4


def _sizes(rehearse=True):
    with open(CONFIG) as f:
        sizes = json.load(f)
    return {**sizes, **sizes["rehearsal"]} if rehearse else sizes


FAMILY = common.load_family("nemotron_lm")
PUB = _sizes()
DEPLOYMENT = PUB["deployment"]
BF16 = FAMILY.build(PUB)
F32 = dataclasses.replace(BF16, dtype=jnp.float32, recompute_layers=False)


class _Program:
    """What ``controls_nemotron.controls`` asks of a family: the loss."""

    loss = staticmethod(nemotron.loss_fn)


def _weights(cfg=F32, seed=0):
    """Seeded weights with selection biases that are not 0, so that a bias
    in the wrong place shows."""
    params = nemotron.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)
    return dict(params, blocks=[
        dict(b, moe=dict(b["moe"], bias=0.1 * jax.random.normal(
            jax.random.fold_in(key, i), b["moe"]["bias"].shape))) if "moe" in b else b
        for i, b in enumerate(params["blocks"])
    ])


def _tokens(batch=2, seq=41, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, F32.vocab_size, jnp.int32)


def _reference(params, tokens, pub=PUB, deployment=DEPLOYMENT):
    # a jit of its own a call: a test may have changed a term under it
    with jax.default_matmul_precision("highest"):
        return jax.jit(
            lambda p, t: reference_nemotron.grads(pub, deployment, p, t))(params, tokens)


def _program(loss, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(loss))(params, tokens)


def _assert_leaves_close(got, want, rtol):
    flat = jax.tree_util.tree_leaves_with_path(got)
    assert len(flat) == len(jax.tree_util.tree_leaves(want))
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            a, b, rtol=0, atol=rtol * scale, err_msg=jax.tree_util.keystr(path)
        )


# ---------------------------------------------------------------------------
# the op with groups against the recurrence
# ---------------------------------------------------------------------------


def _scan_inputs(batch, s, h, groups, p=8, n=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (batch, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, s, h), jnp.float32))
    A = -jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
    B = jax.random.normal(ks[3], (batch, s, groups, n), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], (batch, s, groups, n), jnp.float32).astype(dtype)
    D = jax.random.normal(ks[5], (h,), jnp.float32)
    return x, dt, A, B, C, D


def _by_position(x, dt, A, B, C, D):
    f32 = jnp.float32
    one = lambda x, dt, B, C: reference_nemotron.recurrence(  # noqa: E731
        x.astype(f32), dt, A, B.astype(f32), C.astype(f32), D
    )
    return jax.vmap(one)(x, dt, B, C)


@pytest.mark.parametrize("batch,s,h,groups,chunk", [
    (1, 64, 8, 8, 16),  # a head a group
    (1, 64, 8, 4, 16),  # two heads a group
    (1, 48, 16, 2, 16),  # eight heads a group
    (1, 50, 8, 2, 16),  # a padded last chunk
    (2, 40, 8, 2, 16),  # batch 2, padded
    (1, 7, 4, 2, 16),  # shorter than a chunk
    (1, 32, 4, 1, 16),  # one group, on its own axis
])
def test_ssd_scan_with_groups_is_the_recurrence(batch, s, h, groups, chunk):
    args = _scan_inputs(batch, s, h, groups)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk=chunk)
        want = _by_position(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)


@pytest.mark.parametrize("batch,s,h,groups", [
    (1, 64, 8, 4),  # two heads a group: a group's dB and dC sum its heads'
    (2, 40, 8, 2),  # batch 2, a padded last chunk
    (1, 64, 8, 8),  # a head a group
    (1, 7, 4, 2),  # shorter than a chunk
    (1, 48, 4, 1),  # one group, on its own axis
])
def test_ssd_scan_with_groups_cotangents_are_the_recurrences(batch, s, h, groups):
    """The op's own backward under a group axis against autodiff of the
    recurrence with its groups, in float32: 1e-5 of each cotangent's largest
    entry, each in its argument's shape and type."""
    args = _scan_inputs(batch, s, h, groups)
    weight = jax.random.normal(jax.random.PRNGKey(9), (batch, s, h, 8), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(
            lambda *a: jnp.sum(ssd_scan(*a, chunk=16) * weight), argnums=range(6))(*args)
        want = jax.grad(lambda *a: jnp.sum(_by_position(*a) * weight), argnums=range(6))(*args)
    for name, g, w in zip("x dt A B C D".split(), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_allclose(
            g, w, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(w))), err_msg=name)


def test_ssd_scan_with_groups_in_bf16_comes_as_near_as_its_inputs_rounding():
    args = _scan_inputs(1, 64, 8, 4, dtype=jnp.bfloat16)
    got = ssd_scan(*args, chunk=16)
    with jax.default_matmul_precision("highest"):
        want = _by_position(*args)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0, atol=2e-2 * scale)
    grads = jax.grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk=16).astype(jnp.float32) ** 2), argnums=range(6)
    )(*args)
    wants = jax.grad(lambda *a: jnp.sum(_by_position(*a) ** 2), argnums=range(6))(*args)
    assert [(g.shape, g.dtype) for g in grads] == [(a.shape, a.dtype) for a in args]
    for name, g, w in zip("x dt A B C D".split(), grads, wants):
        np.testing.assert_allclose(
            g.astype(jnp.float32), w.astype(jnp.float32), rtol=0,
            atol=5e-2 * float(jnp.max(jnp.abs(w))), err_msg=name)


def test_one_group_is_the_op_it_was_to_the_bit():
    """Maps without a group axis run the einsums they ran (no ``g`` in the
    jaxpr's shapes), one group on an axis of its own gives their bits, and a
    group's heads see what they see alone."""
    x, dt, A, B, C, D = _scan_inputs(2, 40, 8, 1)
    plain = ssd_scan(x, dt, A, B[:, :, 0], C[:, :, 0], D, chunk=16)
    assert jnp.array_equal(plain, ssd_scan(x, dt, A, B, C, D, chunk=16))
    text = str(jax.make_jaxpr(
        lambda *a: ssd_scan(*a, chunk=16))(x, dt, A, B[:, :, 0], C[:, :, 0], D))
    ranks = {len(shape.split(",")) for shape in re.findall(r"f32\[([\d,]+)\]", text)}
    assert max(ranks) == 5  # (B, N, H, chunk, .): no group axis beside the heads'
    x, dt, A, B, C, D = _scan_inputs(1, 48, 8, 4)
    whole = ssd_scan(x, dt, A, B, C, D, chunk=16)
    for g in range(4):
        mine = slice(2 * g, 2 * g + 2)
        alone = ssd_scan(
            x[:, :, mine], dt[:, :, mine], A[mine], B[:, :, g], C[:, :, g], D[mine], chunk=16)
        np.testing.assert_allclose(whole[:, :, mine], alone, rtol=0, atol=1e-6)
    with pytest.raises(ValueError, match="whole groups"):
        olmoe.Mamba2(state=16, conv_taps=4, chunk=16, inner_heads=8, inner_head_dim=16, groups=3)


def test_the_gated_norm_takes_its_statistic_a_group():
    m = F32.kinds[0].mixer
    assert (m.groups, m.inner, m.convolved) == (2, 128, 128 + 2 * 2 * 16)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    y, z = (jax.random.normal(k, (2, 5, m.inner)) for k in ks[:2])
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (m.inner,))
    got = olmoe._gated_norm(
        F32, {"norm": scale}, y.reshape(2, 5, 2, 64), z.reshape(2, 5, 2, 64)).reshape(2, 5, 128)
    gated = y * jax.nn.silu(z)
    want = jnp.concatenate([
        half / jnp.sqrt(jnp.mean(half * half, axis=-1, keepdims=True) + F32.rms_norm_eps)
        for half in (gated[..., :64], gated[..., 64:])
    ], axis=-1) * scale
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    over_all = olmoe._gated_norm(F32, {"norm": scale}, y, z)
    assert float(jnp.max(jnp.abs(over_all - want))) > 1e-2


# ---------------------------------------------------------------------------
# the ungated held share
# ---------------------------------------------------------------------------


def _share_case(heavy):
    cfg = dataclasses.replace(F32, held_experts=(2, 3))
    n, d, f = 64, cfg.d_model, cfg.expert_width
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    p = {
        "w_up": jax.random.normal(ks[0], (3, d, f)) * d ** -0.5,
        "w_down": jax.random.normal(ks[1], (3, f, d)) * f ** -0.5,
    }
    tokens = jax.random.normal(ks[2], (n, d))
    first = jnp.arange(n) % 8  # 16 claims an expert, under ``L`` = 17: all light
    if heavy:  # most tokens choose held expert 3: over ``L``, applied to all
        first = jnp.where(jnp.arange(n) % 8 < 6, 3, first)
    chosen = jnp.stack([first, (first + 4) % 8], axis=1)
    assert olmoe._share_buffer(cfg, n) == (72, 8, 17)
    weights = jax.random.uniform(ks[5], (n, 2), jnp.float32, 0.2, 1.0)
    return cfg, p, tokens, weights, chosen


def _every_held_expert(cfg, p, tokens, weights, chosen):
    first, held = cfg.held
    out = 0.0
    for e in range(held):
        gate = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=1)
        out = out + gate[:, None] * reference_nemotron._relu2(tokens, p["w_up"][e], p["w_down"][e])
    return out


@pytest.mark.parametrize("heavy", [False, True])
def test_the_ungated_share_is_every_held_expert_on_every_token(heavy):
    cfg, p, tokens, weights, chosen = _share_case(heavy)
    assert "w_gate" not in p and not cfg.gated
    with jax.default_matmul_precision("highest"):
        y, claims, n_heavy = olmoe._held_share(cfg, p, tokens, weights, chosen)
        want = _every_held_expert(cfg, p, tokens, weights, chosen)
        cot = jax.random.normal(jax.random.PRNGKey(8), y.shape)
        got_g = jax.grad(
            lambda p, t, w: jnp.sum(olmoe._held_share(cfg, p, t, w, chosen)[0] * cot),
            argnums=(0, 1, 2))(p, tokens, weights)
        want_g = jax.grad(
            lambda p, t, w: jnp.sum(_every_held_expert(cfg, p, t, w, chosen) * cot),
            argnums=(0, 1, 2))(p, tokens, weights)
    assert int(n_heavy) == (1 if heavy else 0)
    assert int(claims) == int(jnp.sum((chosen >= 2) & (chosen < 5)))
    np.testing.assert_allclose(y, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))
    _assert_leaves_close(got_g, want_g, 1e-4)


def test_the_ranks_shares_add_up_to_the_uncut_layer():
    """The guide's tying test: the parts of a sparse layer's output that the
    four ranks' shares give (2 of the 8 experts each, the same router), the
    shared expert counted ONCE, are what the uncut reference - every one of
    the 8 experts held - gives for the whole layer."""
    whole = dict(PUB, n_routed_experts=8)
    params = _weights()
    blk = params["blocks"][1]
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (2, 24, F32.d_model))
    all_up = jax.random.normal(ks[1], (8, F32.d_model, 24)) * F32.d_model ** -0.5
    all_down = jax.random.normal(ks[2], (8, 24, F32.d_model)) * 24 ** -0.5
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference_nemotron.layer(
            whole, 0, "E", dict(blk, moe=dict(blk["moe"], w_up=all_up, w_down=all_down)), x)
        u = olmoe._rmsnorm(x, blk["ln2"]["scale"], F32.rms_norm_eps)
        shared = olmoe.dense_mlp(F32, blk["moe"]["shared"], u)
        parts = 0.0
        for rank in range(4):
            cfg = dataclasses.replace(F32, held_experts=(2 * rank, 2))
            mine = dict(
                blk["moe"], w_up=all_up[2 * rank:2 * rank + 2], w_down=all_down[2 * rank:2 * rank + 2])
            y, _ = olmoe.moe_layer(cfg, mine, u)
            parts = parts + (y - shared)
            ref, _ = reference_nemotron.layer(PUB, rank, "E", dict(blk, moe=mine), x)
            np.testing.assert_allclose(x + y, ref, rtol=0, atol=2e-5)
    np.testing.assert_allclose(x + parts + shared, uncut, rtol=0, atol=5e-5)
    assert float(jnp.max(jnp.abs(parts))) > 0.1


# ---------------------------------------------------------------------------
# the layers and the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("index,kind", [(0, "M"), (3, "*"), (1, "E")])
def test_a_layer_of_each_kind_is_the_references(index, kind):
    """One published layer, ONE sublayer: the block's output and its
    cotangents against ``reference_nemotron.layer``."""
    assert PUB["hybrid_override_pattern"][index] == kind
    blk = _weights()["blocks"][index]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, F32.d_model))
    cot = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def mine(blk, x):
        y, _ = olmoe._block(F32, blk, x, F32.kinds[index], F32.ff[index], F32.parts[index])
        return y

    def theirs(blk, x):
        return reference_nemotron.layer(PUB, 0, kind, blk, x)[0]

    assert set(blk) == ({"ln2", "moe"} if kind == "E" else {"ln1", "attn"})
    with jax.default_matmul_precision("highest"):
        got, want = mine(blk, x), theirs(blk, x)
        got_g = jax.grad(lambda b, x: jnp.sum(mine(b, x) * cot), argnums=(0, 1))(blk, x)
        want_g = jax.grad(lambda b, x: jnp.sum(theirs(b, x) * cot), argnums=(0, 1))(blk, x)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))
    if kind == "E":  # the bias has no gradient of the loss on either side
        assert not float(jnp.max(jnp.abs(got_g[0]["moe"]["bias"])))
    _assert_leaves_close(got_g, want_g, GRAD_RTOL_F32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_f32_loss_and_gradients_match_the_reference(seed):
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 1)
    loss, grads = _program(lambda p, t: nemotron.loss_fn(F32, p, t), params, tokens)
    want, want_grads = _reference(params, tokens)
    assert float(loss) == pytest.approx(float(want), rel=LOSS_RTOL_F32)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


@pytest.mark.parametrize("seed", [0, 1])
def test_bf16_path_matches_the_reference_at_what_bf16_earns(seed):
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 1)
    loss, grads = jax.jit(common.mixed_precision_grad(FAMILY, BF16))(params, tokens)
    want, want_grads = _reference(params, tokens)
    assert float(loss) == pytest.approx(float(want), rel=3e-2)
    norm, want_norm = float(common.tree_norm(grads)), float(common.tree_norm(want_grads))
    assert norm == pytest.approx(want_norm, rel=0.1)


def test_three_adamw_steps_are_the_references():
    params = _weights()
    batches = jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, want_norms = jax.jit(
            lambda p, b: reference_nemotron.train(PUB, DEPLOYMENT, p, b))(params, batches)
    tx = optax.adamw(reference.LEARNING_RATE)
    opt = tx.init(params)
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: nemotron.loss_fn(F32, p, t)))
    for i in range(3):
        with jax.default_matmul_precision("highest"):
            loss, grads = grad_fn(params, batches[i])
        assert float(loss) == pytest.approx(float(want[i]), rel=LOSS_RTOL_F32)
        assert float(common.tree_norm(grads)) == pytest.approx(float(want_norms[i]), rel=1e-4)
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)


# ---------------------------------------------------------------------------
# a wrong term is caught: the plants are ``controls_nemotron.py``'s own
# ---------------------------------------------------------------------------

CONTROLS = controls_nemotron.controls(F32)
PLANTS = sorted(set(CONTROLS) - {"sound", "float8 weights", "no optimizer update"})


def test_the_controls_are_the_ten_the_cell_reads():
    assert len(PLANTS) == 9 and "no optimizer update" in CONTROLS
    params, tokens = _weights(), _tokens()
    loss, _ = _program(CONTROLS["sound"](_Program), params, tokens)
    assert float(loss) == pytest.approx(float(_reference(params, tokens)[0]), rel=LOSS_RTOL_F32)
    assert float(loss) == pytest.approx(  # the family's own loss is the one the plants replace
        float(_program(lambda p, t: FAMILY.loss(F32, p, t), params, tokens)[0]), rel=1e-7)
    _, grads = _program(CONTROLS["no optimizer update"](_Program), params, tokens)
    assert float(common.tree_norm(grads)) == 0.0


@pytest.fixture(scope="module")
def spread_case():
    """Weights whose attention scores have some spread, so that a rotation
    shows in the softmax; their tokens; the reference's loss and norm."""
    params, tokens = _weights(), _tokens()
    params = dict(params, blocks=[
        dict(b, attn=dict(b["attn"], wq=4.0 * b["attn"]["wq"], wk=4.0 * b["attn"]["wk"]))
        if "wq" in b.get("attn", {}) else b for b in params["blocks"]
    ])
    want, want_grads = _reference(params, tokens)
    return params, tokens, float(want), float(common.tree_norm(want_grads))


@pytest.mark.parametrize("wrong", PLANTS)
def test_a_wrong_term_is_caught(wrong, spread_case):
    """One case a control of ``controls_nemotron.py`` - the norm's span, the
    groups, the activation and its square, the router's scale, its
    renormalisation and its bias, the rank, the rotation: the program with
    the term wrong parts from the reference by far more than float32's
    rounding, and the plant is gone when its loss has been traced."""
    params, tokens, want, want_norm = spread_case
    scan, norm_fn, act, choice = (
        olmoe.ssd_scan, olmoe._gated_norm, olmoe._swiglu, olmoe._sigmoid_choice)
    loss, grads = _program(CONTROLS[wrong](_Program), params, tokens)
    off = abs(float(loss) - want) / want
    norm = float(common.tree_norm(grads))
    assert off > 10 * LOSS_RTOL_F32 or abs(norm - want_norm) / want_norm > 10 * GRAD_RTOL_F32, (
        wrong, off, norm, want_norm,
    )
    assert (olmoe.ssd_scan, olmoe._gated_norm, olmoe._swiglu, olmoe._sigmoid_choice) == (
        scan, norm_fn, act, choice)


# ---------------------------------------------------------------------------
# recomputation, scopes, the lowered step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_recomputation_changes_no_bit_of_the_loss_and_the_last_of_a_gradient(dtype):
    """A held share - a ``custom_vjp`` whose loops follow the routing - and a
    chunked scan in one stack under ``jax.checkpoint``: the loss to the bit,
    the gradients to their last bits (``tests/test_granite.py`` says why not
    further)."""
    kept = dataclasses.replace(BF16, dtype=dtype, recompute_layers=False)
    again = dataclasses.replace(BF16, dtype=dtype, recompute_layers=True)
    params = jax.tree_util.tree_map(lambda l: l.astype(dtype), _weights(kept))
    tokens = _tokens(batch=1, seq=25)
    # primitive by primitive, so that both run the same compiled operations
    a = jax.value_and_grad(lambda p: nemotron.loss_fn(kept, p, tokens))(params)
    b = jax.value_and_grad(lambda p: nemotron.loss_fn(again, p, tokens))(params)
    assert float(a[0]) == float(b[0])
    _assert_leaves_close(
        jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), a[1]),
        jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), b[1]),
        1e-5 if dtype == jnp.float32 else 4e-2,
    )


def test_every_operation_of_the_gradient_step_is_under_a_scope():
    """The compiled gradient of the recomputed model: no operation without a
    scope; the mixer's six scopes, the attention layer's kind and the sparse
    layer's five, forward and backward, the recomputed forward under
    ``rematted_computation`` - the experts' loop in it, the scan (kept by
    name, ``STACK_KEPT``) not."""
    assert BF16.recompute_layers
    params = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), _weights(BF16))
    tokens = _tokens()
    compiled = jax.jit(jax.grad(lambda p: nemotron.loss_fn(BF16, p, tokens))).lower(
        params).compile().as_text()
    named = {n for n in re.findall(r'op_name="([^"]*)"', compiled) if n.startswith("jit(")}
    paths = {(spans.scope_class(n), spans.scope_path(n)) for n in named}
    assert not [n for n in named if not spans.scope_path(n)]
    wanted = [f"attn/mamba/{s}" for s in ("proj", "conv", "gates", "scan", "norm", "out")]
    for scope in wanted + ["attn/nope"]:
        found = {which for which, path in paths if f"/{scope}/" in f"/{path}/"}
        assert {"forward", "backward"} <= found, (scope, found)
    # the share's loops put ``while/body`` between ``moe`` and a scope: the
    # ``moe_held_*`` readers take a name AFTER ``moe`` (``held_scope_ms``)
    for scope in ("router", "dispatch", "experts", "combine", "shared"):
        found = {
            which for which, path in paths
            if "mlp/moe" in path and scope in path.split("/")[path.split("/").index("moe"):]
        }
        assert {"forward", "backward"} <= found, (scope, found)
    assert [p for _, p in paths if "rematted_computation" in p and p.endswith("mlp/moe/while/body/experts")]
    assert not [p for _, p in paths if "rematted_computation" in p and "attn/mamba/scan" in p]


def test_the_lowered_step_holds_three_flash_calls_where_it_is_recomputed(monkeypatch):
    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    tiny = dict(PUB)
    for recompute, want in ((True, 3), (False, 2)):
        tiny["deployment"] = dict(tiny["deployment"], recompute_layers=recompute)
        cfg = FAMILY.build(tiny)
        params = jax.eval_shape(lambda: FAMILY.init(cfg, jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct((1, 65), jnp.int32)
        lowered = jax.jit(common.mixed_precision_grad(FAMILY, cfg)).trace(
            params, tokens).lower(lowering_platforms=("tpu",))
        assert FAMILY.lowered_mosaic_calls(cfg) == want
        assert lowered.as_text().count("tpu_custom_call") == want
        assert FAMILY.flash_calls(cfg, 1, 65)["calls"] == want


# ---------------------------------------------------------------------------
# the configuration's file and the family's counts
# ---------------------------------------------------------------------------


def test_the_published_configuration_is_the_cut_it_says():
    sizes = _sizes(rehearse=False)
    cfg = FAMILY.build(sizes)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl"
    ) else open(CONFIG) as f:
        rows = [json.loads(line) for line in f] if f.name.endswith(".jsonl") else []
    for row in rows:
        if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
            reduced = set(sizes["reduced"])
            assert sizes["source"] == row["source_url"]
            for key, value in row["config"].items():
                assert key in reduced or sizes[key] == value, key
    assert sizes["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072}
    assert set(sizes["reduced"]) == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert len(sizes["hybrid_override_pattern"]) == 52
    assert "".join(reference_nemotron.pattern_of(sizes, sizes["deployment"]["layers"])) == "MEMEM*EME"
    # nine layers of the program, one sublayer each, none paired with its neighbour
    assert cfg.n_layers == 9 and cfg.parts == (
        "mixer", "ff", "mixer", "ff", "mixer", "mixer", "ff", "mixer", "ff")
    assert [k.name for k, p in zip(cfg.kinds, cfg.parts) if p == "mixer"] == [
        "mamba", "mamba", "mamba", "nope", "mamba"]
    m = cfg.kinds[0].mixer
    assert (m.inner_heads, m.inner_head_dim, m.state, m.groups, m.conv_taps, m.chunk) == (
        64, 64, 128, 8, 4, 128)
    assert (m.inner, m.convolved) == (4096, 6144)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2688, 32, 2, 128)
    assert not cfg.kinds[5].rotary and cfg.kinds[5].softmax_scale is None and not cfg.qk_norm
    assert (cfg.n_experts, cfg.experts_per_token, cfg.held, cfg.expert_width, cfg.shared_width) == (
        128, 6, (0, 8), 1856, 3712)
    assert cfg.router == olmoe.SigmoidRouter(groups=1, kept=1, scale=2.5)
    assert cfg.renormalize_top_k and cfg.ff_activation == "relu2" and not cfg.tied_readout
    assert cfg.expert_layers == 4 and cfg.recompute_layers and cfg.vocab_size == 16384
    # the pieces of the count in the file's ``deployment.parameters``
    tree = jax.eval_shape(lambda: FAMILY.init(cfg, jax.random.PRNGKey(0)))
    count = lambda t: sum(l.size for l in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert count(tree["blocks"][0]) == 38_744_896 and count(tree["blocks"][5]) == 23_399_040
    assert count(tree["blocks"][1]) == 100_125_440
    assert "w_gate" not in tree["blocks"][1]["moe"] and "w_gate" not in tree["blocks"][1]["moe"]["shared"]
    assert tree["blocks"][1]["moe"]["w_up"].shape == (8, 2688, 1856)
    assert FAMILY.parameters(cfg) == count(tree) == 666_963_456
    assert "666,963,456" in sizes["deployment"]["parameters"]
    assert olmoe._share_buffer(cfg, 8192) == (4608, 64, 513)  # tiles of 64 rows


def test_the_familys_counts_at_the_cells_shape():
    sizes = _sizes(rehearse=False)
    cfg = FAMILY.build(sizes)
    batch, seq = sizes["batch"], sizes["seq"]
    positions = batch * (seq - 1)
    assert FAMILY.tokens_per_step(batch, seq) == positions
    assert FAMILY.layers_of(cfg) == {"ssm": 4, "attention": 1, "sparse": 4}
    assert FAMILY.lowered_mosaic_calls(cfg) == 3 == FAMILY.flash_calls(cfg, batch, seq)["calls"]
    held = FAMILY.held_expert_matmuls(cfg, batch, seq)
    rows = positions * 6 * 8 / 128
    assert held["calls"] == 6 * 4 and held["rows"] == rows  # SIX a sparse layer, not nine
    assert held["flops"] == 24 * 2 * rows * 2688 * 1856
    work = FAMILY.ssm_scan_work(cfg, batch, seq)
    assert work["layers"] == 4 and work["flops"] == 4 * positions * 64 * 15 * 64 * 128
    ins = 64 * 64 * 2 + 64 * 4 + 2 * 8 * 128 * 2  # x; dt; eight groups of B and C
    assert work["bytes"] == 4 * positions * (3 * ins + 2 * 64 * 64 * 2)
    mixers = 4 * (2688 * 10304 + 4 * 6144 + 4096 * 2688) + 2 * 2688 * 4096 + 2 * 2688 * 256
    sparse = 4 * (2688 * 128 + 2 * 2688 * 3712 + 0.375 * 2 * 2688 * 1856)
    assert FAMILY.matmul_params(cfg) == mixers + sparse + 2688 * 16384
    assert FAMILY.flops_per_step(cfg, batch, seq) == pytest.approx(
        positions * 6 * FAMILY.matmul_params(cfg) + work["flops"]
        + FAMILY.attention_flash(cfg, batch, seq)["flops"])
    facts = FAMILY.facts(cfg, batch, seq)
    assert facts["ssm_scan"]["groups"] == 8 and facts["parameters"] == 666_963_456
    assert {"ssm_scan_work", "attention_flash", "held_expert_matmuls"} <= set(facts)
    other = dataclasses.replace(cfg, rms_norm_eps=1e-3)
    with pytest.raises(ValueError, match="a configuration that nemotron_lm.build returned"):
        FAMILY.reference_train(other, None, None)


def test_the_new_reader_and_the_routed_entries_read_what_the_program_names():
    with open(os.path.join(os.path.dirname(CONFIG), "..", "..", "BENCHMARK.json")) as f:
        contract = json.load(f)
    cell = "nemotron3n-ft1"
    mine = [m for m in contract["per_layer"] if m.get("workloads") == [cell]]
    assert [m["name"] for m in mine] == [
        "attn_ssm_ms.routed", "ssm_scan_ms.routed", "ssm_scan_roofline.routed",
        "layer_recompute_ms.routed", "attn_nope_ms.routed", "moe_router_ms.routed"]
    assert all(m["moves"] == "step_p90_routed_ms" for m in mine)
    assert contract["per_layer"][-6:] == mine and contract["workloads"][-1]["name"] == cell
    remat = "layers/layers/checkpoint/rematted_computation/"
    paths = {
        "forward": {
            "layers/attn/mamba/scan": 0.030, "layers/attn/mamba/proj": 0.010,
            "layers/attn/nope/flash_fwd": 0.004, "layers/mlp/moe/router": 0.006,
            "layers/mlp/moe/experts": 0.020, "layers/mlp/moe_gate": 0.5,
        },
        "backward": {
            remat + "attn/mamba/scan": 0.030, remat + "mlp/moe/router": 0.006,
            remat + "attn/nope/flash_fwd": 0.004,
            "layers/layers/checkpoint/attn/mamba/scan": 0.060,
            "layers/layers/checkpoint/mlp/moe/router": 0.004,
            "layers/layers/checkpoint/attn/nope/flash_bwd": 0.010,
        },
    }
    facts = {
        "trace": {"paths_s": paths, "steps": 2},
        "peaks": {"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 8e11},
        "family": {"ssm_scan_work": {"flops": 1e11, "bytes": 3.2e9, "layers": 4}},
    }
    import benchmark.run as run

    got = run.read_metrics("layer_metrics", mine, facts)
    assert {k: v["value"] for k, v in got.items()} == pytest.approx({
        "attn_ssm_ms.routed": 65.0, "ssm_scan_ms.routed": 60.0,
        "ssm_scan_roofline.routed": 100 * 4e-3 / 60e-3, "layer_recompute_ms.routed": 20.0,
        "attn_nope_ms.routed": 9.0, "moe_router_ms.routed": 8.0,
    })
    # a program that names no such scope (the parent of PR 60 under another
    # cell's trace), or an untraced run: nothing, and no error
    other = {"trace": {"paths_s": {"forward": {"attn/full/flash_fwd": 0.01}}, "steps": 2},
             "peaks": facts["peaks"], "family": {}}
    assert run.read_metrics("layer_metrics", mine, other) == {}
    assert run.read_metrics("layer_metrics", mine, dict(facts, trace=None)) == {}


# ---------------------------------------------------------------------------
# the older configurations
# ---------------------------------------------------------------------------

OLDER = {
    "olmoe": olmoe.tiny_olmoe_config, "mellum2": mellum.tiny_mellum_config,
    "ouro": ouro.tiny_ouro_config, "sdar": sdar.tiny_sdar_config,
    "ling3": ling.tiny_ling_config, "dsv2": dsv2.tiny_dsv2_config,
    "granite4h": granite.tiny_granite_config,
    "granite4h-recomputed": lambda: granite.tiny_granite_config(recompute_layers=True),
}


@pytest.mark.parametrize("model", sorted(OLDER))
def test_the_older_configurations_never_meet_the_new_fields(model, monkeypatch):
    """A configuration that says nothing of them takes the path it took:
    every layer has both sublayers and a gated feed-forward with its
    ``w_gate`` leaf, a state-space mixer's maps come without a group axis,
    and the loss's gradient traces - with the ungated activation made to fail
    and the maps' rank checked - to the jaxpr it traces to with the fields
    spelled out as their defaults."""
    cfg = OLDER[model]()
    assert cfg.sublayers is None and cfg.ff_activation == "swiglu" and cfg.gated
    assert set(cfg.parts) == {"both"}
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    for blk in params["blocks"]:
        assert {"ln1", "attn", "ln2"} <= set(blk)
        ff = blk["mlp"] if "mlp" in blk else blk["moe"]
        assert {"w_gate", "w_up", "w_down"} <= set(ff)
    seq = 32 if cfg.diffusion_block else 33  # a diffusion model's L in whole blocks
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, cfg.vocab_size, jnp.int32)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def traced(cfg):
        text = str(jax.make_jaxpr(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens)))(compute))
        return re.sub(r"0x[0-9a-f]+", "0x", text)  # a custom_vjp's functions by address

    text = traced(cfg)
    scan = olmoe.ssd_scan

    def ungrouped(x, dt, A, B, C, D, chunk):
        assert B.ndim == C.ndim == 3
        return scan(x, dt, A, B, C, D, chunk=chunk)

    def never(*_, **__):
        raise AssertionError("a gated feed-forward met the ungated activation")

    monkeypatch.setattr(olmoe, "ssd_scan", ungrouped)
    monkeypatch.setattr(jax.nn, "relu", never)
    spelled = dataclasses.replace(
        cfg, sublayers=("both",) * cfg.n_layers, ff_activation="swiglu")
    assert traced(cfg) == text == traced(spelled)
    for kind in cfg.kinds:
        if isinstance(kind.mixer, olmoe.Mamba2):
            assert kind.mixer.groups == 1 and kind.mixer.convolved == kind.mixer.inner + 2 * kind.mixer.state


def test_a_configuration_names_its_sublayers_or_is_refused():
    with pytest.raises(ValueError, match="sublayers"):
        dataclasses.replace(olmoe.tiny_olmoe_config(), sublayers=("mixer",))
    with pytest.raises(ValueError, match="sublayers"):
        dataclasses.replace(olmoe.tiny_olmoe_config(), sublayers=("mixer", "mlp"))
    with pytest.raises(ValueError, match="activation"):
        dataclasses.replace(olmoe.tiny_olmoe_config(), ff_activation="gelu")
    cfg = nemotron.tiny_nemotron_config()
    assert cfg.parts == ("mixer", "ff", "mixer", "mixer", "ff") and cfg.expert_layers == 2
    assert nemotron.sublayers(nemotron.TINY_CONFIG, range(5)) == cfg.parts
    assert cfg == dataclasses.replace(BF16, recompute_layers=False)
