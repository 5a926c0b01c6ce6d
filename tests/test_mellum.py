"""Mellum2 as a configuration of the sparse family (torchft_tpu.models.mellum
over models/olmoe.py) against its plain reference
(benchmark/reference_mellum.py), at tiny sizes on the CPU, seeded weights:
one period of three sliding-window layers and one full layer, 4 query
heads over 2 key/value heads of 32, a window of 16 under 64 positions, 2
of 8 experts held.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (flash tiles against a dense masked
softmax; every held expert on every token in bf16-shaped batched matmuls, or
sorted chunks of claims through grouped matmuls and a scatter-add, against
the reference's loop over the held experts), so they differ by float32
rounding alone: measured here at 8e-8 relative on the loss, 2.7e-6 of its
largest entry on the worst gradient leaf and 7e-7 of the largest logit.
The loss is held to 1e-5, every gradient leaf to 1e-4 and the logits to
1e-5 of the largest: some ten to a hundred times what was measured, and
far under what the smallest wrong term costs
(``test_a_wrong_term_is_caught``: YaRN left off the one full layer moves
the loss by 4.0e-4 and the logits by 0.32 of the largest, the attention
factor left out by 6.2e-4, weights not renormalised 7.4e-4, QK-norm over
the whole projection 9.9e-4, a window one key too wide 2.1e-3, the window
ignored 4.0e-3, the next rank's experts 1.5e-2, key/value head ``h % G``
2.3e-2). In bf16 (the configuration's precision: a bf16 copy of the f32
weights, f32 accumulation) the tiny model's loss is a mean over only 192
positions: measured 2.0e-4 on the loss and 9e-4 on the gradient norm, held
to 4e-4 and 1e-2 (``tests/test_olmoe.py``'s bounds); at that bound the two
smallest faults would pass, which is why they are held in float32.
"""

import dataclasses
import json
import os
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import reference, reference_mellum
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.models import mellum, olmoe

BF16 = mellum.tiny_mellum_config()
F32 = dataclasses.replace(BF16, dtype=jnp.float32)
LOSS_RTOL_F32, GRAD_RTOL_F32, LOGIT_RTOL_F32 = 1e-5, 1e-4, 1e-5
LOSS_RTOL_BF16, GRAD_NORM_RTOL_BF16 = 4e-4, 1e-2


def _published(**changes):
    """The program's configuration from the benchmark's configuration
    file, which holds the published config.json: the one place the
    model's numbers live."""
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs",
        "mellum2-12b-a2.5b-l4-ep8.json",
    )
    with open(path) as f:
        sizes = json.load(f)
    return mellum.mellum2_config(dict(sizes, **changes))


def _weights(cfg=F32, seed=0):
    return mellum.init_params(cfg, jax.random.PRNGKey(seed))


def _tokens(cfg=F32, batch=3, seq=65, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_mellum.loss(cfg, p, tokens)
        )(params)


def _program(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: mellum.loss_fn(cfg, p, tokens))(params)


def _norm(tree):
    return float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(tree)
    )))


def _reference_logits(cfg, params, tokens):
    """The reference's layers, one after another, to the logits of every
    position (``reference_mellum.loss`` keeps only their mean)."""
    eps = cfg.rms_norm_eps
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        for kind, blk in zip(cfg.layer_kinds, params["blocks"]):
            h = reference_mellum._rmsnorm(x, blk["ln1"]["scale"], eps)
            x = x + jnp.stack(
                [reference_mellum._attention(cfg, kind, s, blk["attn"]) for s in h]
            )
            h = reference_mellum._rmsnorm(x, blk["ln2"]["scale"], eps)
            y, _ = reference_mellum._moe(cfg, h.reshape(-1, cfg.d_model), blk["moe"])
            x = x + y.reshape(x.shape)
        return reference_mellum._rmsnorm(x, params["ln_f"]["scale"], eps) @ params["readout"]


def _logit_error(logits, want):
    return float(jnp.max(jnp.abs(logits - want)) / jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------------
# the float32 program is the reference's mathematics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_loss_and_gradients_match_the_reference(seed):
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 1)
    loss, grads = _program(F32, params, tokens)
    ref_loss, ref_grads = _reference(F32, params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL_F32 * float(ref_loss)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree_util.tree_leaves(ref_grads)
    ):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err <= GRAD_RTOL_F32, (jax.tree_util.keystr(path), err)


def test_f32_forward_matches_the_reference_layer_by_layer():
    """The logits of every position, through 1, 2, 3 and all 4 layers: a
    sliding layer alone, and the full layer on top of three."""
    params, tokens = _weights(), _tokens()[:, :-1]
    for depth in (1, 2, 3, 4):
        cfg = dataclasses.replace(F32, n_layers=depth, layer_kinds=F32.kinds[:depth])
        cut = dict(params, blocks=params["blocks"][:depth])
        with jax.default_matmul_precision("highest"):
            logits, _ = mellum.forward(cfg, cut, tokens)
        assert _logit_error(logits, _reference_logits(cfg, cut, tokens)) <= LOGIT_RTOL_F32, depth


def test_bf16_path_matches_the_reference_at_what_bf16_earns():
    params, tokens = _weights(), _tokens()
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.value_and_grad(lambda p: mellum.loss_fn(BF16, p, tokens))(compute)
    ref_loss, ref_grads = _reference(BF16, params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL_BF16 * float(ref_loss)
    assert abs(_norm(grads) - _norm(ref_grads)) <= GRAD_NORM_RTOL_BF16 * _norm(ref_grads)


# ---------------------------------------------------------------------------
# what the tolerances catch: each fault planted in the PROGRAM
# ---------------------------------------------------------------------------


def _kinds(cfg, **changes):
    """The configuration with every layer kind that has what ``changes``
    names a value for (a window, a YaRN blend) given the new value."""
    def changed(kind):
        fit = {k: v(getattr(kind, k)) for k, v in changes.items() if getattr(kind, k) is not None}
        return dataclasses.replace(kind, **fit)

    return dataclasses.replace(cfg, layer_kinds=tuple(changed(k) for k in cfg.kinds))


def _faulty(wrong, cfg, params, monkeypatch):
    """(configuration, weights) of a program with the fault planted."""
    if wrong == "window_ignored":
        return dataclasses.replace(cfg, layer_kinds=tuple(
            dataclasses.replace(k, window=None) for k in cfg.kinds
        )), params
    if wrong == "window_off_by_one":
        return _kinds(cfg, window=lambda w: w + 1), params
    if wrong == "kv_head_h_mod_g":
        right = olmoe._heads_to_rows

        def tiled(spec, x, scale, tables):  # head h meets h % G, not h // (H / G)
            rows = right(spec, x, scale, tables)
            copies = rows.reshape(x.shape[0], spec.heads, spec.group, *rows.shape[1:])
            return copies.swapaxes(1, 2).reshape(rows.shape)

        monkeypatch.setattr(olmoe, "_heads_to_rows", tiled)
        return cfg, params
    if wrong == "yarn_left_off":
        return dataclasses.replace(cfg, layer_kinds=tuple(
            dataclasses.replace(k, yarn=None) for k in cfg.kinds
        )), params
    if wrong == "attention_factor_left_out":
        return _kinds(
            cfg, yarn=lambda y: dataclasses.replace(y, attention_factor=1.0)
        ), params
    if wrong == "top_k_not_renormalised":
        return dataclasses.replace(cfg, renormalize_top_k=False), params
    if wrong == "qk_norm_over_the_whole_projection":
        blocks = [
            dict(b, attn=dict(
                b["attn"],
                q_norm=jnp.tile(b["attn"]["q_norm"], cfg.n_heads),
                k_norm=jnp.tile(b["attn"]["k_norm"], cfg.kv_heads),
            ))
            for b in params["blocks"]
        ]
        return dataclasses.replace(cfg, qk_norm_per_head=False), dict(params, blocks=blocks)
    if wrong == "next_ranks_experts":  # experts 2-3 claimed for 0-1's weights
        return dataclasses.replace(cfg, held_experts=(cfg.held[1], cfg.held[1])), params
    raise ValueError(wrong)


@pytest.mark.parametrize("wrong", [
    "window_ignored", "window_off_by_one", "kv_head_h_mod_g", "yarn_left_off",
    "attention_factor_left_out", "top_k_not_renormalised",
    "qk_norm_over_the_whole_projection", "next_ranks_experts",
])
def test_a_wrong_term_is_caught(wrong, monkeypatch):
    """Each of these is a plausible mistake; the bounds the float32 loss
    and forward are held to must not let it through (ten times over)."""
    params, tokens = _weights(), _tokens()
    want = _reference_logits(F32, params, tokens[:, :-1])
    with jax.default_matmul_precision("highest"):
        ref_loss = float(reference_mellum.loss(F32, params, tokens))
        cfg, given = _faulty(wrong, F32, params, monkeypatch)
        logits, _ = mellum.forward(cfg, given, tokens[:, :-1])
        loss = float(mellum.loss_fn(cfg, given, tokens))
    assert _logit_error(logits, want) > 10 * LOGIT_RTOL_F32
    assert abs(loss - ref_loss) > 10 * LOSS_RTOL_F32 * ref_loss


# ---------------------------------------------------------------------------
# a rank's share of the expert layer
# ---------------------------------------------------------------------------


# experts of the whole layer, under top-2: a rank of two holds a quarter of
# them or a twelfth
WIDTHS = (8, 24)


def _whole_layer(n_experts, seed=0):
    """A whole layer of 8 or 24 experts (no share), its weights and inputs."""
    cfg = dataclasses.replace(
        F32, held_experts=None, n_layers=1, layer_kinds=F32.kinds[:1],
        n_experts=n_experts,
    )
    p = _weights(cfg, seed)["blocks"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model), jnp.float32)
    return cfg, p, x


def _share(cfg, p, first, count):
    """Rank ``first // count``'s configuration and weights of the layer."""
    held = dataclasses.replace(cfg, held_experts=(first, count))
    return held, dict(p, **{
        w: p[w][first:first + count] for w in ("w_gate", "w_up", "w_down")
    })


@pytest.mark.parametrize("n_experts", WIDTHS)
def test_the_shares_add_up(n_experts):
    """The parts of one layer that the ranks of two experts each give add
    up to what the uncut reference gives for the whole layer, and so does
    the program's own whole layer."""
    cfg, p, x = _whole_layer(n_experts)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_mellum._moe(cfg, x.reshape(-1, cfg.d_model), p)
        whole, stats = olmoe.moe_layer(cfg, p, x)
        parts, held_claims = [], 0.0
        for first in range(0, cfg.n_experts, 2):
            y, s = olmoe.moe_layer(*_share(cfg, p, first, 2), x)
            np.testing.assert_array_equal(s["claims"], stats["claims"])  # over all
            assert float(s["held_claims"]) == float(stats["claims"][first:first + 2].sum())
            parts.append(y)
            held_claims += float(s["held_claims"])
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(sum(parts).reshape(want.shape), want, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(whole.reshape(want.shape), want, rtol=0, atol=1e-5 * scale)
    assert held_claims == float(stats["held_claims"]) == x.shape[0] * x.shape[1] * 2
    # and one share alone is the reference's, given the same share
    held, mine = _share(cfg, p, 4, 2)
    with jax.default_matmul_precision("highest"):
        ref_part, _ = reference_mellum._moe(held, x.reshape(-1, cfg.d_model), mine)
    np.testing.assert_allclose(parts[2].reshape(want.shape), ref_part, rtol=0, atol=1e-5 * scale)


def _biased_to(cfg, p, x, experts):
    """Inputs and a router under which every token picks ``experts``."""
    x = x.at[..., 0].set(30.0)  # one large coordinate every token shares
    router = 0.01 * p["router"]
    return x, dict(p, router=router.at[0, jnp.asarray(experts)].set(1.0))


@pytest.mark.parametrize("n_experts", WIDTHS)
def test_dropless_when_every_token_picks_held_experts_only(n_experts):
    """All N x K claims on the two held experts: every claim is computed,
    and the share is the whole layer."""
    cfg, p, x = _whole_layer(n_experts)
    x, p = _biased_to(cfg, p, x, [0, 1])
    held, mine = _share(cfg, p, 0, 2)
    n = x.shape[0] * x.shape[1]
    with jax.default_matmul_precision("highest"):
        y, stats = jax.jit(lambda p, x: olmoe.moe_layer(held, p, x))(mine, x)
        want, _ = reference_mellum._moe(cfg, x.reshape(-1, cfg.d_model), p)
    assert float(stats["held_claims"]) == n * 2
    assert stats["claims"].tolist() == [n, n] + [0] * (cfg.n_experts - 2)
    np.testing.assert_allclose(y.reshape(n, -1), want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))


@pytest.mark.parametrize("n_experts", WIDTHS)
def test_zeros_when_no_token_picks_a_held_expert(n_experts):
    """Every claim on experts 6 and 7: the rank that holds 0 and 1 returns
    zeros, gives its experts zero gradients and the tokens none through
    them."""
    cfg, p, x = _whole_layer(n_experts)
    x, p = _biased_to(cfg, p, x, [6, 7])
    held, mine = _share(cfg, p, 0, 2)

    def total(p, x):
        y, stats = olmoe.moe_layer(held, p, x)
        return jnp.sum(y * y) + jnp.sum(y), (y, stats)

    (_, (y, stats)), (dp, dx) = jax.jit(
        jax.value_and_grad(total, argnums=(0, 1), has_aux=True)
    )(mine, x)
    assert float(stats["held_claims"]) == 0 and not np.asarray(y).any()
    for leaf in jax.tree_util.tree_leaves((dp, dx)):
        assert not np.asarray(leaf).any()


@pytest.mark.parametrize("picks, held_claims", [([0, 5], 64), ([0, 1], 128), ([6, 7], 0)])
def test_a_shares_gradients_are_the_references_whatever_the_routing(picks, held_claims):
    """Half of the claims held (every token picks one held expert), all of
    them, none: the share's gradients, to its experts, its router and its
    tokens, are the reference's for the same share."""
    cfg, p, x = _whole_layer(24)
    x, p = _biased_to(cfg, p, x, picks)
    held, mine = _share(cfg, p, 0, 2)
    n = x.shape[0] * x.shape[1]

    def program(p, x):
        y, stats = olmoe.moe_layer(held, p, x)
        return jnp.sum(y * jnp.cos(y)), stats["held_claims"]

    def plain(p, x):
        y, _ = reference_mellum._moe(held, x.reshape(n, -1), p)
        return jnp.sum(y * jnp.cos(y))

    with jax.default_matmul_precision("highest"):
        got, claims = jax.grad(program, argnums=(0, 1), has_aux=True)(mine, x)
        want = jax.grad(plain, argnums=(0, 1))(mine, x)
    assert float(claims) == held_claims
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * max(float(jnp.max(jnp.abs(b))), 1e-6))


# ---------------------------------------------------------------------------
# the grouped form of a share: one buffer of R rows in tiles of T
# ---------------------------------------------------------------------------


# 256 tokens under top-2 of 8 experts, 2 held: 128 claims expected, so a
# buffer of R = 192 rows in 12 tiles of T = 16, and an expert is heavy past
# L = 81 claims; each case is how many tokens pick held expert 0 and how
# many held expert 1 (as their other choice), and the share of the two that
# is heavy, applied to every token in place
PLANTED = {
    "even": (64, 64, 0.0),
    "every_claim_on_one_expert": (192, 0, 0.5),
    "no_held_claim": (0, 0, 0.0),
    "fills_the_buffer_to_its_last_tile": (81, 81, 0.0),  # 6 tiles (15 rows empty) + 6
    "one_claim_more": (82, 81, 0.5),  # expert 0 leaves the buffer
    "a_group_ends_on_a_tile_boundary": (64, 48, 0.0),
    "every_held_expert_heavy": (200, 150, 1.0),
}


def _planted(on_first, on_second, n=256):
    """A layer of 8 experts whose router reads a token's first 8
    coordinates, and inputs that plant each token's two picks there: the
    first ``on_first`` tokens pick held expert 0 and the last ``on_second``
    held expert 1, every other pick is an expert that is not held; the
    logits differ from token to token, so the weights do."""
    cfg, p, _ = _whole_layer(8)
    token = np.arange(n)
    first = np.where(token < on_first, 0, 2 + token % 3)
    second = np.where(token >= n - on_second, 1, 5 + token % 3)
    logits = np.zeros((n, 8), np.float32)
    logits[token, first] = 4.0 + 0.3 * (token % 5)
    logits[token, second] = 3.0 - 0.2 * (token % 7)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, n, cfg.d_model), jnp.float32)
    x = x.at[0, :, :8].set(logits)
    router = jnp.zeros_like(p["router"]).at[:8].set(jnp.eye(8))
    return _share(cfg, dict(p, router=router), 0, 2) + (x,)


def _held_dense(cfg, p, tokens, weights, chosen):
    """The reference that ``olmoe._held_share`` is held to, output and
    gradients: the held experts' part of the layer's output, (N, D)
    float32, and how many of the N x K claims they hold - every held
    expert applied to every token and kept, times its weight, where the
    token chose it. Exact and dropless by construction; its work is
    N x held rows whatever the routing. (The step's own form until PR 40.)"""
    first, held = cfg.held
    # (N, held): the token's weight on each held expert, 0 where it chose
    # another
    mine = (chosen - first)[:, :, None] == jnp.arange(held)
    gate = jnp.sum(jnp.where(mine, weights[:, :, None], 0.0), axis=1)

    def into(w):  # (held, N, f)
        return jnp.einsum("nd,edf->enf", tokens, p[w].astype(cfg.dtype))

    hidden = jax.nn.silu(into("w_gate")) * into("w_up")
    hidden = (hidden * gate.T[:, :, None]).astype(cfg.dtype)
    y = jnp.einsum(
        "enf,efd->nd", hidden, p["w_down"].astype(cfg.dtype),
        preferred_element_type=jnp.float32,
    )
    return y, jnp.sum(mine)


def _dense_share(cfg, p, tokens, weights, chosen):
    y, held_claims = _held_dense(cfg, p, tokens, weights, chosen)
    return y, held_claims, jnp.zeros((), jnp.float32)


@pytest.mark.parametrize("routing", sorted(PLANTED))
def test_the_grouped_share_is_the_dense_share_and_the_references(routing, monkeypatch):
    """Output and gradients (inputs, router, the three expert weights) of
    the share under a planted routing: the grouped form's, the dense
    form's and the reference's are one; an expert is applied to every
    token only past ``L`` claims, and the layer says how many were."""
    on_first, on_second, heavy = PLANTED[routing]
    held, mine, x = _planted(on_first, on_second)
    n = x.shape[1]
    assert olmoe._share_buffer(held, n) == (192, 16, 81)

    def program(p, x):
        y, stats = olmoe.moe_layer(held, p, x)
        return jnp.sum(y * jnp.cos(y)), (y, stats)

    def plain(p, x):
        y, _ = reference_mellum._moe(held, x.reshape(n, -1), p)
        return jnp.sum(y * jnp.cos(y)), y

    with jax.default_matmul_precision("highest"):
        run = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))
        (_, (y, stats)), got = run(mine, x)
        (_, want_y), want = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(mine, x)
        monkeypatch.setattr(olmoe, "_held_share", _dense_share)
        (_, (dense_y, _)), dense = jax.value_and_grad(program, argnums=(0, 1), has_aux=True)(mine, x)
    assert float(stats["held_claims"]) == on_first + on_second
    assert float(stats["held_dense_layers"]) == heavy
    scale = max(float(jnp.max(jnp.abs(want_y))), 1e-6)
    for other in (want_y, dense_y):
        np.testing.assert_allclose(y.reshape(n, -1), other.reshape(n, -1), rtol=0, atol=1e-5 * scale)
    for other in (want, dense):
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(other)):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-4 * max(float(jnp.max(jnp.abs(b))), 1e-6))


def test_a_held_shares_gradient_step_has_no_grouped_kernel(monkeypatch):
    """Plain XLA operations alone: no ``ragged_dot`` and no ``pallas_call``
    in the share's gradient, and in the whole model's gradient step the
    Pallas calls that the dense share leaves too - the flash kernels."""
    held, mine, x = _planted(64, 64)
    share = str(jax.make_jaxpr(jax.grad(
        lambda p, x: jnp.sum(olmoe.moe_layer(held, p, x)[0]), argnums=(0, 1)
    ))(mine, x))
    assert "while" in share and "dot_general" in share
    assert "ragged_dot" not in share and "pallas_call" not in share

    def step():
        return str(jax.make_jaxpr(jax.grad(
            lambda p: mellum.loss_fn(F32, p, _tokens())
        ))(_weights()))

    grouped = step()
    monkeypatch.setattr(olmoe, "_held_share", _dense_share)
    assert "ragged_dot" not in grouped
    assert grouped.count("pallas_call") == step().count("pallas_call") > 0


def _matmul_flops(jaxpr, trips):
    """Operations of every ``dot_general`` in ``jaxpr``; one inside a loop
    is counted ``trips[rows]`` times, ``rows`` being the largest key of
    ``trips`` among the edges of the loop's matmuls (every token, or a
    tile's rows)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (contract, _), _ = eqn.params["dimension_numbers"]
            depth = np.prod([eqn.invars[0].aval.shape[i] for i in contract])
            total += 2 * int(depth) * int(np.prod(eqn.outvars[0].aval.shape))
        inner = sum(_matmul_flops(j, trips) for j in jax.core.jaxprs_in_params(eqn.params))
        if eqn.primitive.name == "while" and inner:
            edges = _matmul_edges(eqn.params["body_jaxpr"].jaxpr)
            inner *= trips[max(rows for rows in trips if rows in edges)]
        total += inner
    return total


def _matmul_edges(jaxpr):
    edges = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            edges.update(d for v in eqn.invars for d in v.aval.shape)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            edges |= _matmul_edges(inner)
    return edges


def test_the_grouped_share_multiplies_a_fifth_of_the_dense_shares_rows():
    """At Mellum2's widths (abstract shapes, traced and not compiled):
    16,384 positions, 8 of 64 experts held, a buffer of 24,576 rows in 48
    tiles of 512 where the dense form has 131,072 rows. With EVERY tile
    in use and no expert heavy, forward and backward multiply under a
    fifth of what the dense form does (nine matmuls a layer in both); a
    heavy expert costs its eighth of the dense form and its products of
    gate and up once more (two matmuls of eleven)."""
    cfg = dataclasses.replace(_published(num_experts=64), held_experts=(0, 8))
    n, d, f = 2 * 8192, cfg.d_model, cfg.expert_width
    rows, tile, light_up_to = olmoe._share_buffer(cfg, n)
    assert (rows, tile, light_up_to) == (24576, 512, 2561)
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    of = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype)
    tiles = rows // tile

    def flops(trips, share, *args):
        total = lambda *a: jnp.sum(share(*a))
        jaxpr = jax.make_jaxpr(jax.grad(total, argnums=(0, 1, 2)))(*args)
        return _matmul_flops(jaxpr.jaxpr, trips)

    grouped = lambda tokens, w_in, w_down, gate, *layout: olmoe._held_experts(
        cfg, tokens, w_in, w_down, gate, layout
    )
    abstract = (
        bf16(n, d), bf16(8, d, 2 * f), bf16(8, f, d), of(jnp.float32, 8, n),
        of(jnp.int32, tiles, tile), of(jnp.int32, tiles, tile), of(jnp.int32, tiles),
        of(jnp.int32), of(jnp.int32, 8), of(jnp.int32),
    )
    light = flops({tile: tiles, n: 0}, grouped, *abstract)
    one_heavy = flops({tile: 0, n: 1}, grouped, *abstract)
    dense = flops(
        {}, lambda tokens, w_in, w_down, *routing: _held_dense(
            cfg, {"w_gate": w_in[..., :f], "w_up": w_in[..., f:], "w_down": w_down},
            tokens, *routing,
        )[0],
        bf16(n, d), bf16(8, d, 2 * f), bf16(8, f, d), of(jnp.float32, n, 8), of(jnp.int32, n, 8),
    )
    assert dense == 9 * 2 * n * 8 * d * f
    assert light == 9 * 2 * rows * d * f < dense // 5
    assert one_heavy == 11 * 2 * n * d * f


# ---------------------------------------------------------------------------
# the mechanisms, one by one
# ---------------------------------------------------------------------------


def test_yarn_blends_the_frequencies_between_the_published_pairs():
    """The published numbers: the ramp is 0 up to pair 18 and 1 from pair
    35 on, so the fast pairs turn as plain RoPE does and the slow ones 16
    times slower, cos and sin both times the attention factor."""
    yarn = _published().kinds[3].yarn
    ramp = olmoe._yarn_ramp(yarn, 500000.0, 128)
    assert ramp.shape == (64,)
    assert float(ramp[18]) == 0.0 and float(ramp[19]) > 0.0
    assert float(ramp[34]) < 1.0 and float(ramp[35]) == 1.0
    one = jnp.zeros((1, 3, 1, 128), jnp.float32).at[..., 0].set(1.0).at[..., 40].set(1.0)
    turned = olmoe.rope(one, 500000.0, yarn)[0, :, 0]
    plain = olmoe.rope(one, 500000.0)[0, :, 0]
    factor = yarn.attention_factor
    # pair 0 (columns 0 and 64) turns by the plain angle, longer by the factor
    np.testing.assert_allclose(turned[:, [0, 64]], factor * plain[:, [0, 64]], rtol=1e-6)
    # pair 40 (columns 40 and 104) turns by a sixteenth of the plain angle
    angle = 2.0 * 500000.0 ** (-40 / 64) / 16
    np.testing.assert_allclose(
        turned[2, [40, 104]], factor * np.array([np.cos(angle), np.sin(angle)]), rtol=1e-5
    )


def test_the_published_configuration_is_the_rank_it_says():
    """Shapes of the real configuration's weights, nothing allocated: 32
    x 128 query columns over a model width of 2304, 4 x 128 key/value
    columns, 8 held experts of 2304 x 896 under a router of 64, and the
    340.3 M parameters the configuration file counts."""
    cfg = dataclasses.replace(_published(num_experts=64), held_experts=(0, 8))
    assert [k.name for k in cfg.kinds] == ["sliding"] * 3 + ["full"]
    assert [k.window for k in cfg.kinds] == [1024] * 3 + [None]
    shapes = jax.eval_shape(lambda: mellum.init_params(cfg, jax.random.PRNGKey(0)))
    blk = shapes["blocks"][3]
    assert blk["attn"]["wq"].shape == (2304, 4096) and blk["attn"]["wo"].shape == (4096, 2304)
    assert blk["attn"]["wk"].shape == blk["attn"]["wv"].shape == (2304, 512)
    assert blk["attn"]["q_norm"].shape == blk["attn"]["k_norm"].shape == (128,)
    assert blk["moe"]["router"].shape == (2304, 64)
    assert blk["moe"]["w_gate"].shape == (8, 2304, 896) and blk["moe"]["w_down"].shape == (8, 896, 2304)
    total = sum(l.size for l in jax.tree_util.tree_leaves(shapes))
    assert round(total / 1e6, 2) == 340.35  # the file's 340.3 M, with the norms
    whole = _published(num_experts=64, num_hidden_layers=28, vocab_size=98304)
    assert (whole.n_layers, whole.vocab_size, whole.held) == (28, 98304, (0, 64))
    assert [k.name for k in whole.kinds[-4:]] == ["sliding"] * 3 + ["full"]


# ---------------------------------------------------------------------------
# through the step transaction
# ---------------------------------------------------------------------------


def test_three_adamw_steps_through_optimizer_wrapper_match_the_reference():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program: its first three losses are the reference's own
    training run's (plain AdamW written out)."""
    params, batches = _weights(), jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_mellum.train(F32, p, b))(params, batches)

    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: mellum.loss_fn(F32, p, t)))
    lighthouse = Lighthouse(bind="[::]:0", min_replicas=1)
    collectives = HostCollectives(timeout=timedelta(seconds=30))
    manager = Manager(
        collectives=collectives, load_state_dict=state.load_state_dict,
        state_dict=state.state_dict, min_replica_size=1,
        timeout=timedelta(seconds=30), quorum_timeout=timedelta(seconds=60),
        lighthouse_addr=lighthouse.address(), replica_id="mellum_test",
    )
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
    np.testing.assert_allclose(losses, want, rtol=2e-5)


def test_state_tree_round_trips_state_dict_and_the_checkpoint_transport():
    """The state tree whose expert leaves are (held, d, f) and whose
    attention leaves are of three widths."""
    tx = optax.adamw(1e-3)
    state = FTTrainState(_weights(), tx)
    assert state.params["blocks"][0]["moe"]["w_gate"].shape == (2, 64, 32)
    _, grads = _program(F32, state.params, _tokens())
    state.apply_gradients(grads)  # moments that are not zeros
    snapshot = state.snapshot()

    other = FTTrainState(_weights(seed=9), tx)
    other.load_state_dict(state.state_dict())
    server = CheckpointServer(timeout=timedelta(seconds=10))
    try:
        server.send_checkpoint(
            [1], step=3, state_dict=state.state_dict(), timeout=timedelta(seconds=10)
        )
        fetched = server.recv_checkpoint(
            src_rank=0, metadata=server.metadata(), step=3, timeout=timedelta(seconds=10)
        )
    finally:
        server.shutdown()
    healed = FTTrainState(_weights(seed=9), tx)
    healed.load_state_dict(fetched)
    for holder in (other, healed):
        got = holder.state_dict()
        assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(snapshot)
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(snapshot)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    healed.apply_gradients(grads)  # and the healed state trains on


def test_make_train_step_takes_the_configuration():
    """``models.make_train_step`` (the raw loop's fused step) serves
    Mellum2 as it serves OLMoE: one loss for the family."""
    from torchft_tpu.models import make_train_step

    tokens, tx, params = _tokens(), optax.adamw(1e-3), _weights()
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    # both compiled: an eager bf16 pass rounds in other places than a fused one
    want = float(jax.jit(lambda p, t: mellum.loss_fn(BF16, p, t))(compute, tokens))
    _, _, loss = make_train_step(BF16, tx, bf16_params=True)(params, tx.init(params), tokens)
    assert abs(float(loss) - want) <= LOSS_RTOL_BF16 * want
