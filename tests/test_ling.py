"""Ling 3.0 as a configuration of the sparse family (torchft_tpu.models.ling
over models/olmoe.py: Kimi Delta Attention five layers in six, latent
attention in the sixth, a sigmoid router under a selection bias, a shared
expert) against its plain reference (benchmark/reference_ling.py), at tiny
sizes on the CPU, seeded weights: a dense KDA layer, a sparse KDA layer and a
sparse MLA layer, 2 heads of 32, 4 of 16 experts held in 4 groups.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (the delta rule in chunks against a scan
over positions; flash tiles over zero-padded lanes against a dense softmax;
the held share's tiles against a loop over the held experts), so they differ
by float32 rounding alone: measured here at 1e-7 relative on the loss and
2e-5 of its largest entry on the worst gradient leaf (the chunked scan's
triangular solve). The loss is held to 1e-5 and every gradient leaf to 2e-4,
far under what the smallest wrong term costs (``test_a_wrong_term_is_caught``).
The chunked scan alone against the recurrence: 5e-7 on the output and 3e-5 on
a gradient at the decay's bound, held to 1e-5 and 2e-4. In bf16 (the
configuration's precision) a model of width 64 is held to 3e-2 on the loss
and 0.15 on the gradient norm: the L2 norms of q and k over 32 channels
carry a bf16 rounding straight into every score.
"""

import dataclasses
import json
import os
import re
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import common, reference, reference_ling
from benchmark.reduce import spans
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.models import ling, mellum, olmoe, ouro
from torchft_tpu.ops import delta_rule, flash_attention_rows
from torchft_tpu.ops.delta_rule import LEAST_LOG_DECAY, causal_conv, gated_delta_rule

BF16 = ling.tiny_ling_config()
F32 = dataclasses.replace(BF16, dtype=jnp.float32)
KDA, MLA = F32.kinds[0], F32.kinds[2]
LOSS_RTOL_F32, GRAD_RTOL_F32 = 1e-5, 2e-4
SCAN_ATOL, SCAN_GRAD_RTOL = 1e-5, 2e-4


def _sizes():
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "ling3-flash-l6-ep64.json"
    )
    with open(path) as f:
        return json.load(f)


def _weights(cfg=F32, seed=0, bias=0.3):
    """Seeded weights with selection biases that are NOT zero (as drawn
    they are), so that selection with and without them differ."""
    params = ling.init_params(cfg, jax.random.PRNGKey(seed))
    blocks = [
        b if "moe" not in b else dict(b, moe=dict(b["moe"], bias=bias * jax.random.normal(
            jax.random.PRNGKey(100 + i), (cfg.n_experts,), jnp.float32
        )))
        for i, b in enumerate(params["blocks"])
    ]
    return dict(params, blocks=blocks)


def _tokens(cfg=F32, batch=2, seq=41, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(cfg, params, tokens):
    # a jit of its own a call: a test may have changed a term under it
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: reference_ling.grads(cfg, p, t))(params, tokens)


_program_f32 = jax.jit(jax.value_and_grad(lambda p, t: ling.loss_fn(F32, p, t)))


def _program(cfg, params, tokens):
    """The float32 program's loss and gradients: one compiled step for
    every test that asks."""
    assert cfg is F32
    with jax.default_matmul_precision("highest"):
        return _program_f32(params, tokens)


def _assert_leaves_close(got, want, rtol):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            a, b, rtol=0, atol=rtol * scale, err_msg=jax.tree_util.keystr(path)
        )


# ---------------------------------------------------------------------------
# the whole model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_f32_loss_and_gradients_match_the_reference(seed):
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 10)
    loss, grads = _program(F32, params, tokens)
    want, want_grads = _reference(F32, params, tokens)
    assert abs(float(loss) - float(want)) <= LOSS_RTOL_F32 * float(want)
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(want_grads)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


def test_bf16_path_matches_the_reference_at_what_bf16_earns():
    params, tokens = _weights(BF16), _tokens()
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: ling.loss_fn(BF16, p, tokens)))(compute)
    want, want_grads = _reference(F32, params, tokens)
    assert abs(float(loss) - float(want)) <= 3e-2 * float(want)
    norm, want_norm = float(common.tree_norm(grads)), float(common.tree_norm(want_grads))
    assert abs(norm - want_norm) <= 0.15 * want_norm


_MOE, _MLA, _KDA = reference_ling._moe, reference_ling._mla, reference_ling._kda


def _without_the_bias(cfg, x, w):
    return _MOE(cfg, x, w, with_bias=False)


def _without_the_shared(cfg, x, w):
    out, stats = _MOE(cfg, x, w)
    return out - reference_ling._swiglu(x, w["shared"]), stats


def _bias_in_the_weights(cfg, x, w):
    """The gate from ``s + bias`` where the model takes it from ``s``."""
    first, held = cfg.held_experts
    score = reference_ling._sigmoid(x @ w["router"])
    chose = reference_ling.choice(cfg, score, w["bias"])
    gate = jnp.where(chose, score + w["bias"], 0.0)
    gate = cfg.router.scale * gate / jnp.sum(gate, axis=-1, keepdims=True)
    out = sum(
        gate[:, first + e, None] * reference_ling._swiglu(
            x, {k: w[k][e] for k in ("w_gate", "w_up", "w_down")}
        ) for e in range(held)
    )
    claims = jnp.sum(chose.astype(jnp.float32), axis=0)
    share = score / jnp.sum(score, axis=-1, keepdims=True)
    return out + reference_ling._swiglu(x, w["shared"]), (claims / x.shape[0], jnp.mean(share, axis=0), claims)


WRONG = {
    # the reference with one term of the equations changed: the program,
    # which has the term, must part from it by more than the tolerance
    "the bias left out of selection": lambda m: m.setattr(reference_ling, "_moe", _without_the_bias),
    "the bias in the weights too": lambda m: m.setattr(reference_ling, "_moe", _bias_in_the_weights),
    "the decay's lower bound ignored": lambda m: m.setattr(
        reference_ling, "_kda", _kda_with(floor=-1.0)),
    "conv4 left out": lambda m: m.setattr(
        reference_ling, "conv", lambda x, w: x * w[-1]),
    "the causal mask dropped in MLA": lambda m: m.setattr(
        reference_ling, "_mla", lambda cfg, kind, u, w: _MLA(cfg, kind, u, w, causal=False)),
    "rotate-half pairs for interleaved ones": lambda m: m.setattr(
        reference_ling, "_rotated", _rotate_half),
    "the groups ignored": lambda m: m.setattr(
        reference_ling, "choice", _ungrouped_choice),
    "the shared expert left out": lambda m: m.setattr(reference_ling, "_moe", _without_the_shared),
}


def _kda_with(floor):
    def kda(cfg, kind, u, w):
        other = dataclasses.replace(kind, mixer=dataclasses.replace(kind.mixer, floor=floor))
        return _KDA(cfg, other, u, w)
    return kda


def _rotate_half(x, theta):
    s, _, r = x.shape
    freq = 1.0 / theta ** (2.0 * jnp.arange(r // 2) / r)
    angle = jnp.arange(s)[:, None, None] * freq
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1
    )


def _ungrouped_choice(cfg, score, bias):
    """The groups ignored: the K largest of all E."""
    pick = score + bias
    return pick >= jnp.sort(pick, axis=-1)[:, -cfg.experts_per_token][:, None]


@pytest.mark.parametrize("wrong", sorted(WRONG))
def test_a_wrong_term_is_caught(wrong, monkeypatch):
    params, tokens = _weights(), _tokens()
    loss, grads = _program(F32, params, tokens)
    WRONG[wrong](monkeypatch)
    want, want_grads = _reference(F32, params, tokens)
    off = abs(float(loss) - float(want)) / float(want)
    norm, want_norm = float(common.tree_norm(grads)), float(common.tree_norm(want_grads))
    assert off > 10 * LOSS_RTOL_F32 or abs(norm - want_norm) / want_norm > 10 * GRAD_RTOL_F32, (
        wrong, off, norm, want_norm,
    )


# ---------------------------------------------------------------------------
# the chunked scan and the convolution, alone
# ---------------------------------------------------------------------------


def _scan_inputs(S, decays, seed=0, B=2, H=3, dk=32, dv=16, dtype=jnp.float32):
    """``dtype`` is q's, k's and v's; the decays and the steps are float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed + S), 5)
    q = jax.random.normal(ks[0], (B, S, H, dk))
    k = jax.random.normal(ks[1], (B, S, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, dv))
    g = {
        "spread": -5.0 * jax.nn.sigmoid(4.0 * jax.random.normal(ks[3], (B, S, H, dk))),
        "at the bound": jnp.full((B, S, H, dk), -5.0),
        "near 0": jnp.full((B, S, H, dk), -1e-6),
        "at the least": jnp.full((B, S, H, dk), LEAST_LOG_DECAY),
    }[decays]
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _recurrence(q, k, v, g, beta):
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return jax.vmap(reference_ling.delta_rule)(q, k, v, jnp.exp(g), beta)


# what ``_scan_inputs`` is asked for beside the length and the decays, by the
# words after the decays' name in a case of the test below
SCAN_SHAPES = {
    "": {},
    "batch 2, 3 heads, d_k 16 under d_v 48": dict(B=2, H=3, dk=16, dv=48),
    "bf16": dict(dtype=jnp.bfloat16),
}
# bf16 q, k and v: the output and the three cotangents come back rounded to
# bf16, 2^-9 of their size each, and the output's rounding passes into every
# cotangent through the loss's
SCAN_ATOL_BF16, SCAN_GRAD_RTOL_BF16 = 2e-2, 1e-2


# lengths that are whole chunks (128, 192), are not (100, 70), are less than
# one chunk (16) and less than one sub-chunk (7)
@pytest.mark.parametrize(
    "S,decays",
    [(128, "spread"), (100, "spread"), (192, "at the bound"), (70, "at the bound"),
     (128, "near 0"), (70, "near 0"), (16, "spread"), (7, "at the bound"),
     (70, "at the least"),
     # the hand-written backward: five chunks, so that the state's cotangent
     # crosses four boundaries on its way back; the same with a padded sixth;
     # another batch, heads and widths; q, k and v in bf16
     (320, "spread"), (330, "at the bound"),
     (192, "spread, batch 2, 3 heads, d_k 16 under d_v 48"),
     (200, "spread, bf16"), (70, "at the bound, bf16")],
)
def test_the_chunked_scan_is_the_recurrence(S, decays):
    """Output and all five gradients of ``ops.delta_rule.gated_delta_rule``
    against the delta rule a position at a time, with every decay at the
    bound of -5 for the whole sequence (where ``e^{-G}`` would pass
    float32's range inside a chunk), every decay near 0, and every decay at
    the least the sub-chunks carry (``LEAST_LOG_DECAY``). Each cotangent
    comes back in its argument's type and shape."""
    decays, _, shape = decays.partition(", ")
    args = _scan_inputs(S, decays, **SCAN_SHAPES[shape])
    bf16 = args[0].dtype == jnp.bfloat16
    out_atol, grad_rtol = (
        (SCAN_ATOL_BF16, SCAN_GRAD_RTOL_BF16) if bf16 else (SCAN_ATOL, SCAN_GRAD_RTOL)
    )
    with jax.default_matmul_precision("highest"):
        got, want = gated_delta_rule(*args), _recurrence(*args)
        assert got.dtype == args[2].dtype and got.shape == args[2].shape
        assert bool(jnp.all(jnp.isfinite(got)))
        np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0, atol=out_atol)

        def of(fn):
            return jax.grad(
                lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32))), argnums=(0, 1, 2, 3, 4)
            )(*args)

        for a, b, x in zip(of(gated_delta_rule), of(_recurrence), args):
            assert a.dtype == x.dtype and a.shape == x.shape
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert bool(jnp.all(jnp.isfinite(a)))
            # the decay's gradient at the least bound is 5e-5 at most (a
            # position later the state is gone): a difference of sums near 1,
            # held to float32's rounding of those
            scale = max(float(jnp.max(jnp.abs(b))), 1e-3)
            np.testing.assert_allclose(a, b, rtol=0, atol=grad_rtol * scale)


@pytest.mark.parametrize("floor", [LEAST_LOG_DECAY - 0.5, 0.0, 1.0])
def test_a_decay_bound_the_scan_cannot_carry_is_refused(floor):
    """The op cannot see what bounded its ``g``; the mixer's kind can, and
    refuses a bound under which a sub-chunk's factors leave float32."""
    assert olmoe.Kda(floor=LEAST_LOG_DECAY).floor == -8.0
    with pytest.raises(ValueError, match="log-decay"):
        olmoe.Kda(floor=floor)


def test_the_scan_traces_no_loop_over_positions():
    """The timed path's only loop is the one over CHUNKS: its trip count is
    the sequence over the chunk, not the sequence."""
    args = _scan_inputs(256, "spread")
    text = str(jax.make_jaxpr(gated_delta_rule)(*args))
    assert "length=4" in text and "length=256" not in text


def test_the_gradient_runs_one_loop_forward_and_one_back_and_solves_nothing():
    """``jax.grad`` through the op at four chunks: two loops of four trips,
    the forward's and the reverse one of the hand-written backward, and one
    over the 15 rows a diagonal block's inverse is made by - no second
    forward (nothing is under ``jax.checkpoint``), no loop over positions,
    no triangular-solve call."""
    args = _scan_inputs(256, "spread")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(gated_delta_rule(*a)), argnums=(0, 1, 2, 3, 4)))(*args)

    def equations(jaxpr):
        for e in jaxpr.eqns:
            yield e
            for inner in jax.core.jaxprs_in_params(e.params):
                yield from equations(inner)

    names = [e.primitive.name for e in equations(jaxpr.jaxpr)]
    scans = [e for e in equations(jaxpr.jaxpr) if e.primitive.name == "scan"]
    # beside the two over the chunks, the forward's loop over a sub-block's rows
    assert [(e.params["length"], e.params["reverse"]) for e in scans] == [
        (delta_rule._SUB - 1, False), (4, False), (4, True)
    ]
    # a trip over the chunks is one product and the stacking of what it carried
    for scan in scans[1:]:
        body = [e.primitive.name for e in equations(scan.params["jaxpr"].jaxpr)]
        assert body.count("dot_general") == 1 and "exp" not in body
    assert "while" not in names and "custom_vjp_call" not in names
    for absent in ("triangular_solve", "checkpoint", "remat"):
        assert not [n for n in names if absent in n]


def test_the_backward_keeps_the_scans_scope():
    """The gradient of the mixer under the layer's scopes, compiled: no
    operation of the program is without a scope, forward or backward, and
    the reverse loop's body reads ``attn/kda/scan/while/body`` in the
    backward class. An operation of the ``custom_vjp``'s backward could be
    named by the scope the op was called under or by none, so with none
    unscoped ``kda_scan_ms`` reads all of it (``reduce/program.py``:
    ``scope_ms`` matches whole names of the path)."""
    cfg = dataclasses.replace(F32, n_layers=1, layer_kinds=(KDA,), dense_ff=(None,))
    p = ling.init_params(cfg, jax.random.PRNGKey(0))["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 130, cfg.d_model), jnp.float32)

    def loss(w, x):
        with jax.named_scope("attn"), jax.named_scope(KDA.name):
            return jnp.sum(olmoe.kda_mixer(cfg, w, x, KDA))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(p, x).compile().as_text()
    # an argument's name and a reduction's scalar body carry no ``jit(..)``
    named = {n for n in re.findall(r'op_name="([^"]*)"', compiled) if n.startswith("jit(")}
    paths = {(spans.scope_class(n), spans.scope_path(n)) for n in named}
    assert not [n for n in named if not spans.scope_path(n).startswith("attn/kda")]
    assert ("forward", "attn/kda/scan/while/body") in paths
    assert ("backward", "attn/kda/scan/while/body") in paths
    backward = {path for which, path in paths if which == "backward"}
    mine = {path for path in backward if f"/{path}/".startswith("/attn/kda/scan/")}
    assert len(mine) >= 2 and not [path for path in mine if "checkpoint" in path or "remat" in path]


def test_what_the_forward_keeps_at_the_cells_shape():
    """``jax.eval_shape`` of the ``custom_vjp``'s forward at 1 x 8,192 x 8
    heads x 128: beside the five arguments it keeps every chunk's starting
    state, ``u``, the system's solution ``U_0 | W``, the inverse ``T`` and
    the query pairs - 0.20 GB a layer, under the 0.25 GB the five layers'
    1.0 GB was budgeted from - and the column factors of no sub-chunk."""
    f32 = jnp.float32
    shapes = [jax.ShapeDtypeStruct((1, 8192, 8, 128), t) for t in (jnp.bfloat16,) * 3 + (f32,)]
    shapes.append(jax.ShapeDtypeStruct((1, 8192, 8), f32))
    out, (arguments, *kept) = jax.eval_shape(delta_rule._forward, *shapes)
    assert (out.shape, out.dtype) == ((1, 8192, 8, 128), jnp.bfloat16)
    assert [(a.shape, a.dtype) for a in arguments] == [(a.shape, a.dtype) for a in shapes]
    chunks = (128, 1, 8)
    assert [a.shape for a in kept] == [
        chunks + (128, 128), chunks + (64, 128), chunks + (64, 256), chunks + (64, 64),
        chunks + (64, 64),
    ]
    assert sum(a.size * a.dtype.itemsize for a in kept) == 201_326_592 <= 0.25e9


@pytest.mark.parametrize("seed", [0, 1])
def test_the_inverse_by_products_is_the_inverse(seed):
    """``(I + A)^-1`` by rows inside the blocks of 16 and by block products
    below them against ``jnp.linalg.inv``, for a strictly lower ``A`` of
    entries up to 1 either way: the inverse's own entries reach 1e4 there
    (a unit triangular matrix's condition grows with its size), so the two
    are held to 5e-6 of the largest; and the blocks of 16 alone, which no
    matrix product touches, where every row is a sum of the rows above."""
    A = jnp.tril(jax.random.uniform(
        jax.random.PRNGKey(seed), (2, 3, 64, 64), minval=-1.0, maxval=1.0), -1)
    with jax.default_matmul_precision("highest"):
        got, want = delta_rule._unit_lower_inverse(A), jnp.linalg.inv(jnp.eye(64) + A)
    assert float(jnp.max(jnp.abs(want))) > 100.0
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6 * float(jnp.max(jnp.abs(want))))
    assert not np.any(np.triu(np.asarray(got), 1)) and np.all(np.diagonal(got, axis1=-2, axis2=-1) == 1.0)
    ones = jnp.tril(jnp.ones((64, 64)), -1)  # (I + A)^-1 is 1 on the diagonal, -1 under it
    np.testing.assert_array_equal(
        delta_rule._unit_lower_inverse(ones), np.eye(64) - np.eye(64, k=-1))


def test_conv4_is_the_loop():
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, 3))
    w = jax.random.normal(jax.random.PRNGKey(2), (4, 3))
    want = np.zeros((2, 10, 3), np.float32)
    for t in range(10):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += np.asarray(w[j]) * np.asarray(x[:, t - 3 + j])
    np.testing.assert_allclose(causal_conv(x, w), want, atol=1e-6)
    np.testing.assert_allclose(jax.vmap(lambda s: reference_ling.conv(s, w))(x), want, atol=1e-6)


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def test_mla_is_dense_attention_at_192_and_128():
    """The flash kernels on q and k of 192 and v of 128, each at its own
    width, equal the call that pads all three to 256 lanes (what the mixer
    ran until PR 54: exact, and 512 lanes of products a pair for 320), and
    both equal dense causal attention at the true widths: output and the
    three gradients."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 96, 192)) * 192 ** -0.5
    k, v = jax.random.normal(ks[1], (2, 96, 192)), jax.random.normal(ks[2], (2, 96, 128))

    def padded(q, k, v):
        pad = lambda t: jnp.pad(t, ((0, 0), (0, 0), (0, 256 - t.shape[-1])))  # noqa: E731
        out = flash_attention_rows(pad(q), pad(k), pad(v))
        return out[..., :128], out[..., 128:]

    def dense(q, k, v):
        scores = jnp.where(jnp.tril(jnp.ones((96, 96), bool)), q @ k.swapaxes(1, 2), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    def grads_of(attend):
        return jax.grad(lambda *a: jnp.sum(jnp.cos(attend(*a))), argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got, (was, rest), want = flash_attention_rows(q, k, v), padded(q, k, v), dense(q, k, v)
        assert got.shape == v.shape and not np.any(np.asarray(rest))
        # the same products in the same tiles: a zero lane added nothing
        np.testing.assert_allclose(got, was, atol=1e-6)
        np.testing.assert_allclose(got, want, atol=2e-6)
        grads = grads_of(flash_attention_rows)
        were = grads_of(lambda *a: padded(*a)[0])
        wants = grads_of(dense)
    for a, b, c, like in zip(grads, were, wants, (q, k, v)):
        assert a.shape == like.shape
        np.testing.assert_allclose(a, b, atol=1e-6 * float(jnp.max(jnp.abs(c))))
        np.testing.assert_allclose(a, c, atol=5e-6 * float(jnp.max(jnp.abs(c))))


@pytest.mark.parametrize("head_dim,rope_dim", [(32, 8), (128, 64)])
def test_the_mla_layer_is_the_references(head_dim, rope_dim):
    """At the tiny head (q.k 40 and v 32: one tile of lanes holds both) and
    at the PUBLISHED one (q.k 192, a tile and a half, beside v 128): the
    kernels take each at its own width, output and every weight's gradient."""
    kind = olmoe.AttentionKind("mla", mixer=olmoe.Mla(latent=16, rope_dim=rope_dim))
    cfg = dataclasses.replace(
        F32, head_dim=head_dim, n_layers=1, layer_kinds=(kind,), dense_ff=(None,))
    p = ling.init_params(cfg, jax.random.PRNGKey(0))["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda w: jnp.sum(olmoe.mla_mixer(cfg, w, x, kind) ** 2))(p)
        want, want_grads = jax.value_and_grad(lambda w: jnp.sum(
            jax.vmap(lambda u: reference_ling._mla(cfg, kind, u, w))(x) ** 2))(p)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


def test_a_latent_layer_is_one_forward_and_one_backward_call_at_192_and_128(monkeypatch):
    """``ling3-ft1``'s latent layer at the published head (128 + 64 rotated,
    v 128), normed and gated, its gradient lowered for the chip: ONE
    ``flash_fwd`` and ONE ``flash_bwd`` Mosaic call (what the family's
    ``lowered_mosaic_calls`` states a layer: 2), their rows 192 and 128
    lanes wide and none padded to 256."""
    import sys

    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    kind = olmoe.AttentionKind("mla", mixer=olmoe.Mla(latent=16, rope_dim=64))
    cfg = dataclasses.replace(
        BF16, head_dim=128, n_layers=1, layer_kinds=(kind,), dense_ff=(None,))
    p = ling.init_params(cfg, jax.random.PRNGKey(0))["blocks"][0]["attn"]
    x = jnp.zeros((1, 256, cfg.d_model), jnp.bfloat16)
    text = jax.jit(
        jax.grad(lambda w: jnp.sum(olmoe.mla_mixer(cfg, w, x, kind).astype(jnp.float32)))
    ).trace(p).lower(lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sorted(re.findall(r'kernel_name = \\?"(\w+)\\?"', text)) == ["flash_bwd", "flash_fwd"]
    widths = {int(w) for line in calls for w in re.findall(r"tensor<\d+x\d+x(\d+)xbf16>", line)}
    assert len(calls) == 2 and widths == {192, 128}


# ---------------------------------------------------------------------------
# the router: selection, the bias, the shared expert
# ---------------------------------------------------------------------------


def test_the_selection_on_planted_scores():
    """16 experts in 4 groups, 2 kept, 2 a token, on scores written by
    hand: the bias lifts an expert into the choice and stays out of its
    weight; an expert outside the kept groups is not chosen however high;
    the weights are the chosen scores over their sum times 2.5."""
    s = np.full((3, 16), 0.1, np.float32)
    s[0, [0, 1]] = 0.9, 0.8      # group 0 wins, then group 1 (0.5 + 0.45)
    s[0, [4, 5]] = 0.5, 0.45
    s[1, [8, 9]] = 0.7, 0.6      # token 1: the bias makes expert 12 the second choice
    s[1, 12] = 0.5
    s[2, 3], s[2, [4, 5]], s[2, [8, 9]] = 0.95, (0.6, 0.6), (0.55, 0.55)  # 3 is alone in group 0
    bias = np.zeros(16, np.float32)
    bias[12] = 0.3
    logits = jnp.log(s) - jnp.log1p(-s)
    _, weights, chosen = olmoe._sigmoid_choice(F32, jnp.asarray(logits), jnp.asarray(bias))
    assert sorted(np.asarray(chosen[0])) == [0, 1]
    assert sorted(np.asarray(chosen[1])) == [8, 12]
    # groups 1 (1.2) and 2 (1.1) beat group 0 (0.95 + 0.1): expert 3 is out
    assert sorted(np.asarray(chosen[2])) == [4, 5]
    np.testing.assert_allclose(sorted(np.asarray(weights[1])), [0.5, 0.7], atol=1e-6)
    want = reference_ling.choice(F32, jnp.asarray(s), jnp.asarray(bias))
    for row in range(3):
        assert sorted(np.flatnonzero(np.asarray(want[row]))) == sorted(np.asarray(chosen[row]))
    # through the layer: renormalised, times the scaling factor
    p = _weights()["blocks"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, F32.d_model))
    whole = dataclasses.replace(F32, held_experts=None)
    every = dict(p, **{w: jnp.tile(p[w], (4, 1, 1)) for w in ("w_gate", "w_up", "w_down")})
    with jax.default_matmul_precision("highest"):
        y, _ = olmoe.moe_layer(whole, every, x)
        want_y, _ = reference_ling._moe(whole, x.reshape(8, -1), every)
    np.testing.assert_allclose(y.reshape(8, -1), want_y, atol=1e-5)


def test_the_loss_has_no_gradient_of_the_bias_and_its_step_is_the_excess_loads_sign():
    params, tokens = _weights(), _tokens()
    # the loss alone (the pull left off): nothing reaches a bias
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(jax.grad(lambda p: olmoe._hidden(F32, p, tokens[:, :-1])[0].sum()))(params)
        _, grads = _program(F32, params, tokens)
        _, claims = jax.jit(lambda p: reference_ling.loss_and_claims(F32, p, tokens))(params)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    sparse = [b for b in grads["blocks"] if "moe" in b]
    assert len(sparse) == len(claims) == 2
    for b, got, c in zip((b for b in plain["blocks"] if "moe" in b), sparse, claims):
        assert not np.any(np.asarray(b["moe"]["bias"]))
        excess = c / (n * F32.experts_per_token) - 1.0 / F32.n_experts
        np.testing.assert_allclose(got["moe"]["bias"], excess, atol=1e-7)
        assert abs(float(jnp.sum(excess))) < 1e-6
    # under ling's optimizer a bias steps by -gamma sign(excess), no moment
    # and no decay, and every other leaf by the caller's transformation
    gamma, tx = 1e-3, optax.adamw(1e-3)
    both = ling.bias_steps(tx, gamma)
    updates, _ = both.update(grads, both.init(params), params)
    plain_updates, _ = tx.update(grads, tx.init(params), params)
    for u, g, pu in zip(updates["blocks"], grads["blocks"], plain_updates["blocks"]):
        if "moe" in u:
            np.testing.assert_array_equal(u["moe"]["bias"], -gamma * jnp.sign(g["moe"]["bias"]))
            np.testing.assert_array_equal(u["moe"]["router"], pu["moe"]["router"])
        np.testing.assert_array_equal(u["attn"]["wo"], pu["attn"]["wo"])


def test_the_pull_adds_nothing_to_the_loss():
    tokens = _tokens()
    ours = jax.jit(lambda p: ling.loss_fn(F32, p, tokens))
    theirs = jax.jit(lambda p: reference_ling.loss(F32, p, tokens))
    with jax.default_matmul_precision("highest"):
        for params in (_weights(), _weights(bias=0.0)):
            assert float(ours(params)) == pytest.approx(float(theirs(params)), rel=LOSS_RTOL_F32)


# ---------------------------------------------------------------------------
# the shares add up: heads, experts, and what every rank holds whole
# ---------------------------------------------------------------------------


def _head_share(kind, p, cfg, first, count):
    """The weights of heads ``first .. first + count`` of a mixer's ``p``."""
    dh, h = cfg.head_dim, cfg.n_heads

    def columns(w, per_head):
        whole = w.reshape(w.shape[:-1] + (h, per_head))
        return whole[..., first:first + count, :].reshape(w.shape[:-1] + (count * per_head,))

    if isinstance(kind.mixer, olmoe.Kda):
        out = {
            w: columns(p[w], dh) for w in (
                "wq", "wk", "wv", "w_decay", "w_gate", "conv_q", "conv_k", "conv_v", "decay_bias")
        }
        out.update(
            w_beta=p["w_beta"][:, first:first + count], decay_a=p["decay_a"][first:first + count],
            o_norm=p["o_norm"], wo=p["wo"].reshape(h, dh, -1)[first:first + count].reshape(count * dh, -1),
        )
        return out
    r = kind.mixer.rope_dim
    return dict(
        p, wq=columns(p["wq"], dh + r), w_kvb=columns(p["w_kvb"], 2 * dh),
        w_gate=p["w_gate"][:, first:first + count],
        wo=p["wo"].reshape(h, dh, -1)[first:first + count].reshape(count * dh, -1),
    )


@pytest.mark.parametrize("layer", ["kda", "mla"])
def test_the_shares_add_up(layer):
    """A small layer whole - 8 heads, 16 experts - against its 4 head
    shares and its 4 expert shares: the parts the shares give, with what
    every rank computes alike (the shared expert; the router, its bias and
    MLA's latent map are whole on each and give nothing of their own)
    counted once, sum to what the uncut reference gives for the layer."""
    kind = KDA if layer == "kda" else MLA
    whole = dataclasses.replace(
        F32, n_heads=8, n_layers=1, layer_kinds=(kind,), dense_ff=(None,), held_experts=None,
    )
    p = ling.init_params(whole, jax.random.PRNGKey(4))["blocks"][0]
    p = dict(p, moe=dict(p["moe"], bias=0.2 * jax.random.normal(jax.random.PRNGKey(5), (16,))))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 48, whole.d_model), jnp.float32)
    mixer_of = {"kda": (olmoe.kda_mixer, reference_ling._kda), "mla": (olmoe.mla_mixer, reference_ling._mla)}
    mixer, ref_mixer = mixer_of[layer]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.vmap(lambda u: ref_mixer(whole, kind, u, p["attn"])))(x)
        quarter = dataclasses.replace(whole, n_heads=2)
        parts = [
            jax.jit(lambda w: mixer(quarter, w, x, kind))(_head_share(kind, p["attn"], whole, first, 2))
            for first in range(0, 8, 2)
        ]
        np.testing.assert_allclose(sum(parts), want, atol=2e-5 * float(jnp.max(jnp.abs(want))))

        tokens = x.reshape(-1, whole.d_model)
        want, _ = jax.jit(lambda w: reference_ling._moe(whole, tokens, w))(p["moe"])
        shared = reference_ling._swiglu(tokens, p["moe"]["shared"])
        routed, held_claims = [], 0.0
        for first in range(0, 16, 4):
            held = dataclasses.replace(whole, held_experts=(first, 4))
            mine = dict(p["moe"], **{w: p["moe"][w][first:first + 4] for w in ("w_gate", "w_up", "w_down")})
            y, s = jax.jit(lambda w, held=held: olmoe.moe_layer(held, w, x))(mine)
            routed.append(y.reshape(tokens.shape) - shared)  # the rank's own experts' part
            held_claims += float(s["held_claims"])
        np.testing.assert_allclose(
            sum(routed) + shared, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    assert held_claims == tokens.shape[0] * whole.experts_per_token


# ---------------------------------------------------------------------------
# the configuration's file and the family
# ---------------------------------------------------------------------------


def test_the_published_configuration_is_the_rank_it_says():
    sizes = _sizes()
    family = common.load_family("ling_lm")
    cfg = family.build(sizes)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.held) == (512, 8, (0, 8))
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.expert_width) == (2560, 8, 128, 768)
    assert cfg.router == olmoe.SigmoidRouter(groups=8, kept=4, scale=2.5)
    assert cfg.shared_width == 768 and cfg.renormalize_top_k
    assert cfg.ff == (6144, None, None, None, None, None) and cfg.vocab_size == 19648
    kda = olmoe.AttentionKind("kda", mixer=olmoe.Kda(taps=4, floor=-5.0))
    mla = olmoe.AttentionKind("mla", mixer=olmoe.Mla(latent=512, rope_dim=64))
    assert cfg.kinds == (kda,) * 5 + (mla,)
    assert sizes["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512,
        "num_attention_heads": 32, "vocab_size": 157184, "num_nextn_predict_layers": 1,
    }
    assert list(sizes["reduced"]) == list(sizes["published"])
    deployment = sizes["deployment"]
    assert (deployment["chips_per_layer"], deployment["rank"], deployment["layers"]) == (64, 0, [0, 7, 8, 9, 10, 11])
    assert 512 // deployment["experts_ways"] == 8 and 32 // deployment["heads_ways"] == 8
    assert 157184 // deployment["vocabulary_ways"] == 19648
    # every width as the catalog's row has it
    assert (sizes["hidden_size"], sizes["head_dim"], sizes["qk_rope_head_dim"], sizes["kv_lora_rank"]) == (2560, 128, 64, 512)
    assert (sizes["intermediate_size"], sizes["moe_intermediate_size"], sizes["short_conv_kernel_size"]) == (6144, 768, 4)
    batch, seq = sizes["batch"], sizes["seq"]
    assert (batch, seq) == (1, 8193)
    # 8,192 positions, 8 of 512 held: tiles of 32 rows, a buffer of 1,536,
    # an expert heavy from 162 claims (sdar-ft1: 256, 24,576, 1,281)
    assert olmoe._share_buffer(cfg, 8192) == (1536, 32, 161)
    assert family.expected_held_claims(cfg, 8192) == 1024
    assert family.tokens_per_step(batch, seq) == 8192
    kda_mixer = 5 * 2560 * 1024 + 1024 * 2560 + 2560 * 8 + 3 * 4 * 1024 + 8 + 1024 + 128
    mla_mixer = 2560 * 1536 + 2560 * 576 + 512 + 512 * 2048 + 2 * 192 + 2560 * 8 + 1024 * 2560
    sparse = 2560 * 512 + 512 + 3 * 2560 * 768 + 8 * 3 * 2560 * 768
    want = (
        5 * kda_mixer + mla_mixer + 3 * 2560 * 6144 + 5 * sparse + 12 * 2560
        + 2 * 19648 * 2560 + 2560
    )
    assert family.parameters(cfg) == want == 507_704_872
    flash = family.flash_calls(cfg, batch, seq)
    assert flash["calls"] == family.lowered_mosaic_calls(cfg) == 2
    assert flash["flops"] == 8 * 6 * (192 + 128) * (8192 * 8193 // 2)
    work = family.kda_scan_work(cfg, batch, seq)
    assert work["flops"] == 5 * 8 * 8192 * 21 * 128 * 128
    assert round(family.flops_per_step(cfg, batch, seq) / 1e12, 2) == 11.69
    assert family.facts(cfg, batch, seq)["held_expert_matmuls"]["rows"] == 1024
    assert family.facts(cfg, batch, seq)["held_expert_matmuls"]["calls"] == 45


def test_the_six_readers_read_what_the_program_names_and_nothing_else():
    names = (
        "attn_kda_ms", "kda_scan_ms", "kda_scan_roofline", "attn_mla_ms",
        "attn_mla_flash_roofline", "moe_shared_expert_ms",
    )
    read = {name: common.load_by_name("layer_metrics", name).read for name in names}
    # the scan's paths as the op's ``custom_vjp`` names them in a trace: the
    # chunk-parallel part under the scope itself, each loop's trip under
    # ``while/body``, its product one call deeper, forward and backward alike
    paths = {
        "forward": {
            "attn/kda/proj": 0.010, "attn/kda/scan": 0.030, "attn/kda/scan/while/body": 0.004,
            "attn/kda/scan/while/body/closed_call": 0.020, "attn/mla/flash_fwd": 0.004,
            "attn/mla/proj": 0.002, "mlp/moe/shared": 0.003, "mlp/moe/router": 0.001,
        },
        "backward": {
            "attn/kda/scan/while/body/closed_call": 0.020, "attn/kda/scan/while/body": 0.010,
            "attn/kda/scan": 0.040, "attn/kda/out": 0.006,
            "attn/mla/flash_bwd": 0.006, "mlp/moe/shared": 0.005,
        },
    }
    facts = {
        "trace": {"paths_s": paths, "steps": 2},
        "peaks": {"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 8e11},
        "family": {
            "kda_scan_work": {"flops": 1e11, "bytes": 1.6e9},
            "kind_flash": {"mla": {"layers": 1, "flops": 4e11, "bytes": 2e8}},
        },
    }
    # every operation under the scope, both loops' bodies among them; the
    # maps and the gate are not the scan's
    assert read["kda_scan_ms"](facts) == pytest.approx(62.0)
    assert read["attn_kda_ms"](facts) == pytest.approx(70.0)
    assert read["kda_scan_roofline"](facts) == pytest.approx(100 * 2e-3 / 62e-3)
    assert read["attn_mla_ms"](facts) == pytest.approx(6.0)
    assert read["attn_mla_flash_roofline"](facts) == pytest.approx(100 * 2e-3 / 5e-3)
    assert read["moe_shared_expert_ms"](facts) == pytest.approx(4.0)
    assert all(reader(dict(facts, trace=None)) is None for reader in read.values())
    other = {"trace": {"paths_s": {"forward": {"attn/full/flash_fwd": 0.01, "mlp/moe/experts": 0.01}}, "steps": 2},
             "peaks": facts["peaks"], "family": {"kind_flash": {"full": {"flops": 1.0, "bytes": 1.0}}}}
    assert all(reader(other) is None for reader in read.values())


@pytest.mark.parametrize("model", ["olmoe", "mellum2", "ouro"])
def test_the_other_configurations_never_meet_the_new_mechanisms(model, monkeypatch):
    """A configuration without the new fields takes the old path: its loss's
    gradient lowers with the new mixers, the sigmoid router and the scan
    made to fail, to the text it lowers to with them."""
    cfg = {
        "olmoe": olmoe.tiny_olmoe_config(), "mellum2": mellum.tiny_mellum_config(),
        "ouro": ouro.tiny_ouro_config(),
    }[model]
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size, jnp.int32)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def lowered():
        return jax.jit(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens))).lower(compute).as_text()

    text = lowered()

    def never(*_, **__):
        raise AssertionError("a model without the mechanism met it")

    for name in ("_sigmoid_choice", "gated_delta_rule", "causal_conv", "_rope_pairs"):
        monkeypatch.setattr(olmoe, name, never)
    monkeypatch.setitem(olmoe._MIXERS, olmoe.Kda, (never, never))
    monkeypatch.setitem(olmoe._MIXERS, olmoe.Mla, (never, never))
    assert lowered() == text
    assert "bias" not in params["blocks"][-1].get("moe", {})


# ---------------------------------------------------------------------------
# through the step transaction: the bias is state no gradient of the loss moves
# ---------------------------------------------------------------------------


def _one_member(state, name):
    lighthouse = Lighthouse(bind="[::]:0", min_replicas=1)
    collectives = HostCollectives(timeout=timedelta(seconds=30))
    manager = Manager(
        collectives=collectives, load_state_dict=state.load_state_dict,
        state_dict=state.state_dict, min_replica_size=1,
        timeout=timedelta(seconds=30), quorum_timeout=timedelta(seconds=60),
        lighthouse_addr=lighthouse.address(), replica_id=name,
    )
    return lighthouse, collectives, manager


def _biases(params):
    return [np.asarray(b["moe"]["bias"]) for b in params["blocks"] if "moe" in b]


def test_three_adamw_steps_through_optimizer_wrapper_match_the_reference():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program under the generator's optimizer (AdamW for every leaf,
    the biases too): its first three losses are the reference's own
    training run's, and the biases moved as the reference's did."""
    params = _weights(bias=0.0)
    batches = jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_ling.train(F32, p, b))(params, batches)
    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: ling.loss_fn(F32, p, t)))
    lighthouse, collectives, manager = _one_member(state, "ling_test")
    optimizer = OptimizerWrapper(manager, state)
    losses = []
    try:
        with jax.default_matmul_precision("highest"):
            for tokens in batches:
                optimizer.zero_grad()
                loss, grads = grad_fn(state.params, tokens)
                assert optimizer.step(manager.allreduce(grads).wait())
                losses.append(float(loss))
    finally:
        manager.shutdown()
        collectives.shutdown()
        lighthouse.shutdown()
    np.testing.assert_allclose(losses, want, rtol=5e-5)
    assert all(np.any(b) and np.max(np.abs(b)) < 3.5e-3 for b in _biases(state.params))


def test_an_aborted_step_leaves_the_bias_and_a_committed_one_steps_it():
    """Under ``ling.bias_steps``: a step that aborts (an error reported
    before the vote) leaves every bias as it was; the next, committed, moves
    each by ``-gamma sign`` of its expert's excess load in the step that
    committed; ``state_dict`` -> ``load_state_dict`` through the Manager's
    callbacks carries the biases to a second state, with no state of their
    own in the optimizer's."""
    gamma = 1e-3
    tx = ling.bias_steps(optax.adamw(1e-3), gamma)
    state = FTTrainState(_weights(), tx)
    before = _biases(state.params)
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: ling.loss_fn(F32, p, t)))
    lighthouse, collectives, manager = _one_member(state, "ling_abort")
    optimizer = OptimizerWrapper(manager, state)
    try:
        optimizer.zero_grad()
        _, grads = grad_fn(state.params, _tokens(seed=1))
        avg = manager.allreduce(grads).wait()
        manager.report_error(RuntimeError("a peer died"))
        assert not optimizer.step(avg)
        for a, b in zip(_biases(state.params), before):
            np.testing.assert_array_equal(a, b)

        optimizer.zero_grad()
        _, grads = grad_fn(state.params, _tokens(seed=2))
        assert optimizer.step(manager.allreduce(grads).wait())
        excess = [np.asarray(b["moe"]["bias"]) for b in grads["blocks"] if "moe" in b]
        for a, b, e in zip(_biases(state.params), before, excess):
            np.testing.assert_allclose(a, b - gamma * np.sign(e), atol=1e-7)
            assert np.any(e > 0) and np.any(e < 0)

        # what a healing replica is sent, by the callbacks the Manager holds
        other = FTTrainState(_weights(seed=9, bias=0.0), tx)
        other.load_state_dict(state.state_dict())
        for a, b in zip(_biases(other.params), _biases(state.params)):
            np.testing.assert_array_equal(a, b)
        assert jax.tree_util.tree_structure(other.state_dict()) == jax.tree_util.tree_structure(
            state.state_dict())
    finally:
        manager.shutdown()
        collectives.shutdown()
        lighthouse.shutdown()


def test_make_train_step_takes_the_configuration():
    """``models.make_train_step`` (the raw loop's fused step) serves Ling
    as it serves OLMoE: one loss for the family."""
    from torchft_tpu.models import make_train_step

    tokens, tx, params = _tokens(), optax.adamw(1e-3), _weights(BF16)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    want = float(jax.jit(lambda p, t: ling.loss_fn(BF16, p, t))(compute, tokens))
    _, _, loss = make_train_step(BF16, tx, bf16_params=True)(params, tx.init(params), tokens)
    assert abs(float(loss) - want) <= 2e-3 * want
