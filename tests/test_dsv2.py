"""DeepSeek-V2 as a configuration of the sparse family (torchft_tpu.models.dsv2
over models/olmoe.py: latent attention in every layer, unnormed and ungated
under YaRN with its factor on the softmax scale, softmax top-K over a rank's
held experts beside two shared experts, a dense layer first, a balance loss
a sequence and a layer) against its plain reference
(benchmark/reference_dsv2.py), at tiny sizes on the CPU, seeded weights: a
dense layer and two sparse ones, 2 heads of 32 + 8 rotated, 4 of 16 experts
held, 3 a token.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (flash tiles over zero-padded lanes
against a dense softmax a head; the held share's tiles against a loop over
the held experts; one shared SwiGLU of twice the width against two), so they
differ by float32 rounding alone: measured here at 1e-7 relative on the loss
and 3e-6 of its largest entry on the worst gradient leaf. The loss is held
to 1e-5 and every gradient leaf to 1e-4, far under what the smallest wrong
term costs (``test_a_wrong_term_is_caught``). In bf16 (the configuration's
precision) a model of width 64 is held to 3e-2 on the loss and 0.1 on the
gradient norm.
"""

import dataclasses
import json
import math
import os
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import common, reference, reference_dsv2
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.models import dsv2, ling, olmoe

# a balance weight at which the loss feels the balance term in float32
BF16 = dataclasses.replace(dsv2.tiny_dsv2_config(), balance_coef=1e-2)
F32 = dataclasses.replace(BF16, dtype=jnp.float32)
KIND = F32.kinds[0]
# the tiny configuration's ``rope_scaling``, as a config.json would state it
ROPE = {
    "type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
    "beta_fast": 4, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
}
LOSS_RTOL_F32, GRAD_RTOL_F32 = 1e-5, 1e-4


def _sizes():
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "dsv2-lite-l5-ep8.json"
    )
    with open(path) as f:
        return json.load(f)


def _weights(cfg=F32, seed=0):
    return dsv2.init_params(cfg, jax.random.PRNGKey(seed))


def _tokens(cfg=F32, batch=2, seq=41, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(cfg, params, tokens, rope=ROPE):
    # a jit of its own a call: a test may have changed a term under it
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: reference_dsv2.grads(cfg, p, t, rope))(params, tokens)


def _program(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p, t: dsv2.loss_fn(cfg, p, t)))(params, tokens)


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


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_path_matches_the_reference_at_what_bf16_earns(seed):
    params, tokens = _weights(BF16, seed), _tokens(seed=seed + 10)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: dsv2.loss_fn(BF16, p, tokens)))(compute)
    want, want_grads = _reference(F32, params, tokens)
    assert abs(float(loss) - float(want)) <= 3e-2 * float(want)
    norm, want_norm = float(common.tree_norm(grads)), float(common.tree_norm(want_grads))
    assert abs(norm - want_norm) <= 0.1 * want_norm


def test_the_latent_layer_has_no_norm_and_no_gate_in_its_tree():
    attn = _weights()["blocks"][0]["attn"]
    assert sorted(attn) == ["kv_norm", "w_kva", "w_kvb", "wo", "wq"]
    # Ling's latent layer keeps all three
    theirs = ling.init_params(ling.tiny_ling_config(), jax.random.PRNGKey(0))["blocks"][2]["attn"]
    assert {"q_norm", "k_norm", "w_gate"} <= set(theirs)


# ---------------------------------------------------------------------------
# wrong terms: in the program (a configuration that says something else) or
# in the reference (an equation changed); each parts the two
# ---------------------------------------------------------------------------


def _with_mixer(cfg, yarn=KIND.yarn, **changed):
    kind = dataclasses.replace(KIND, yarn=yarn, mixer=dataclasses.replace(KIND.mixer, **changed))
    return dataclasses.replace(cfg, layer_kinds=(kind,) * cfg.n_layers)


WRONG_PROGRAM = {
    "no mscale^2 on the softmax scale": _with_mixer(F32, softmax_factor=1.0),
    "plain frequencies for YaRN's": _with_mixer(F32, yarn=None),
    "the norm of q and k left on": _with_mixer(F32, qk_norm=True),
    "the gate left on": _with_mixer(F32, gated=True),
    "the pooled balance for the sequence's": dataclasses.replace(F32, seq_balance=False),
    "the top-K renormalised": dataclasses.replace(F32, renormalize_top_k=True),
    "the next rank's experts": dataclasses.replace(F32, held_experts=(4, 4)),
}

_MLA, _MOE, _SWIGLU = reference_dsv2._mla, reference_dsv2._moe, reference_dsv2._swiglu


def _rotate_half(x, theta, rope):
    """Halves for pairs: (i, i + r / 2) turned where the model turns (2 i, 2 i + 1)."""
    s, _, r = x.shape
    angle = jnp.arange(s, dtype=jnp.float32)[:, None, None] * reference_dsv2._yarn_frequencies(
        r, theta, rope)
    a, b = x[..., : r // 2], x[..., r // 2:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle), b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1
    )


def _one_shared_expert(cfg, x, w):
    """The first of the two shared experts alone."""
    f = cfg.expert_width
    shared = w["shared"]
    one = {"w_gate": shared["w_gate"][:, :f], "w_up": shared["w_up"][:, :f], "w_down": shared["w_down"][:f]}
    return _MOE(cfg, x, dict(w, shared=one))


WRONG_REFERENCE = {
    "halves for interleaved pairs": lambda m: m.setattr(reference_dsv2, "_rotated", _rotate_half),
    "one shared expert for two": lambda m: m.setattr(reference_dsv2, "_moe", _one_shared_expert),
    "the causal mask dropped": lambda m: m.setattr(
        reference_dsv2, "_mla", lambda cfg, kind, u, w, rope: _MLA(cfg, kind, u, w, rope, causal=False)),
    "another m(mscale_all_dim)": lambda m: m.setattr(
        reference_dsv2, "_mscale", lambda rope, s: 1.1),
}


@pytest.mark.parametrize("wrong", sorted(WRONG_PROGRAM) + sorted(WRONG_REFERENCE))
def test_a_wrong_term_is_caught(wrong, monkeypatch):
    tokens = _tokens()
    cfg = WRONG_PROGRAM.get(wrong, F32)
    params = _weights(cfg)
    if wrong == "the gate left on":  # a gate of 1/2 everywhere is no gate to a norm's eye
        params = dict(params, blocks=[
            dict(b, attn=dict(b["attn"], w_gate=4.0 * b["attn"]["w_gate"])) for b in params["blocks"]
        ])
    loss, grads = _program(cfg, params, tokens)
    if wrong in WRONG_REFERENCE:
        WRONG_REFERENCE[wrong](monkeypatch)
    # the reference reads the leaves it knows by name: a gate's map or a
    # norm's scale that a wrong program carries is not among them
    sound = _weights(F32)
    want, want_grads = _reference(F32, _only(params, sound), tokens)
    mine = _only(grads, sound)
    off = abs(float(loss) - float(want)) / float(want)
    norm, want_norm = float(common.tree_norm(mine)), float(common.tree_norm(want_grads))
    assert off > 10 * LOSS_RTOL_F32 or abs(norm - want_norm) / want_norm > 10 * GRAD_RTOL_F32, (
        wrong, off, norm, want_norm,
    )


def _only(tree, like):
    """``tree`` cut to the leaves ``like`` has (dicts and lists alike)."""
    if isinstance(like, dict):
        return {k: _only(tree[k], like[k]) for k in like}
    if isinstance(like, (list, tuple)):
        return [_only(t, l) for t, l in zip(tree, like)]
    return tree


# ---------------------------------------------------------------------------
# latent attention without norm and gate; the rotation under YaRN
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("head_dim,rope_dim", [(32, 8), (128, 64)])
def test_the_mla_layer_is_dense_attention_a_head(head_dim, rope_dim):
    """At the tiny head (q.k 40 and v 32: one tile of lanes holds both) and
    at the PUBLISHED one (q.k 192, a tile and a half, beside v 128; each goes
    to the kernels at its own width):
    ``mla_mixer`` unnormed and ungated against the dense masked softmax of
    the reference, output and every weight's gradient."""
    kind = dataclasses.replace(KIND, mixer=dataclasses.replace(KIND.mixer, rope_dim=rope_dim))
    cfg = dataclasses.replace(
        F32, head_dim=head_dim, n_layers=1, layer_kinds=(kind,), dense_ff=(None,))
    p = dsv2.init_params(cfg, jax.random.PRNGKey(0))["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda w: jnp.sum(olmoe.mla_mixer(cfg, w, x, kind) ** 2))(p)
        want, want_grads = jax.value_and_grad(lambda w: jnp.sum(
            jax.vmap(lambda u: reference_dsv2._mla(cfg, kind, u, w, ROPE))(x) ** 2))(p)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


def test_every_latent_layer_is_one_forward_and_one_backward_call_at_192_and_128(monkeypatch):
    """The model at the published head (128 + 64 rotated, v 128), two latent
    layers, its loss's gradient lowered for the chip: one ``flash_fwd`` and
    one ``flash_bwd`` Mosaic call a layer (what the family's
    ``lowered_mosaic_calls`` states: 2 a layer), their rows 192 and 128
    lanes wide and none padded to 256."""
    import re
    import sys

    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    cfg = dataclasses.replace(BF16, head_dim=128, layer_kinds=tuple(
        dataclasses.replace(k, mixer=dataclasses.replace(k.mixer, rope_dim=64)) for k in BF16.kinds
    ))
    params = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16), dsv2.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jnp.zeros((1, 257), jnp.int32)
    text = jax.jit(jax.grad(lambda w: dsv2.loss_fn(cfg, w, tokens))).trace(params).lower(
        lowering_platforms=("tpu",)).as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    layers = len(cfg.kinds)
    assert len(calls) == 2 * layers
    for name in ("flash_fwd", "flash_bwd"):
        assert len(re.findall(r'kernel_name = \\?"%s\\?"' % name, text)) == layers
    widths = {int(w) for line in calls for w in re.findall(r"tensor<\d+x\d+x(\d+)xbf16>", line)}
    assert widths == {192, 128}


def _turned_by_hand(x, freq, factor=1.0):
    """(B, S, r): the pair (2 i, 2 i + 1) at position s by ``s x freq[i]``."""
    out = np.zeros_like(x)
    for s in range(x.shape[1]):
        for i in range(x.shape[-1] // 2):
            c, n = math.cos(s * freq[i]) * factor, math.sin(s * freq[i]) * factor
            a, b = x[:, s, 2 * i], x[:, s, 2 * i + 1]
            out[:, s, 2 * i], out[:, s, 2 * i + 1] = a * c - b * n, b * c + a * n
    return out


def test_the_rotation_in_pairs_under_yarn_is_the_formula():
    r, theta, S = 8, 10000.0, 24
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2, S, r)), np.float64)
    plain = [theta ** (-2 * i / r) for i in range(r // 2)]
    # factor 1: the blend of a frequency with itself, the plain rotation
    same = olmoe.Yarn(factor=1.0, original_positions=16, beta_fast=4.0, beta_slow=1.0)
    for yarn in (None, same):
        got = olmoe._rope_pairs(jnp.asarray(x, jnp.float32), theta, yarn)
        np.testing.assert_allclose(got, _turned_by_hand(x, plain), atol=2e-5)
    # the ramp written out: low = floor(r ln(L / (beta_fast 2 pi)) / (2 ln
    # theta)), high = ceil(.. beta_slow ..); pairs up to low keep their
    # frequency, pairs from high on turn at 1 / factor of it
    yarn = olmoe.Yarn(factor=4.0, original_positions=16, beta_fast=4.0, beta_slow=1.0,
                      attention_factor=0.9)
    low = max(math.floor(r * math.log(16 / (4.0 * 2 * math.pi)) / (2 * math.log(theta))), 0)
    high = min(math.ceil(r * math.log(16 / (1.0 * 2 * math.pi)) / (2 * math.log(theta))), r - 1)
    assert (low, high) == (0, 1)
    ramp = [min(max((i - low) / (high - low), 0.0), 1.0) for i in range(r // 2)]
    assert ramp[0] == 0.0 and ramp[-1] == 1.0  # both ends of the ramp are met
    blended = [(1 - t) * f + t * f / 4.0 for t, f in zip(ramp, plain)]
    got = olmoe._rope_pairs(jnp.asarray(x, jnp.float32), theta, yarn)
    np.testing.assert_allclose(got, _turned_by_hand(x, blended, 0.9), atol=2e-5)
    np.testing.assert_allclose(
        reference_dsv2._yarn_frequencies(r, theta, ROPE), blended, rtol=1e-6)
    # one implementation of the blend: rotate-half's angles are these too
    cos, _ = olmoe._rotary_angles(S, r, theta, yarn, None)
    np.testing.assert_allclose(
        cos, 0.9 * np.cos(np.arange(S)[:, None] * np.asarray(blended)), atol=2e-5)


def test_the_published_rope_scaling_gives_factor_one_and_the_scales_1_5896():
    sizes = _sizes()
    kind = dsv2.latent_kind(sizes)
    assert kind.name == "mla" and kind.mixer == olmoe.Mla(
        latent=512, rope_dim=64, qk_norm=False, gated=False,
        softmax_factor=kind.mixer.softmax_factor)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert kind.yarn.attention_factor == 1.0
    assert kind.mixer.softmax_factor == pytest.approx(m * m) == pytest.approx(1.5896, abs=1e-4)
    assert (kind.yarn.factor, kind.yarn.original_positions) == (40.0, 4096)
    assert (kind.yarn.beta_fast, kind.yarn.beta_slow) == (32.0, 1.0)
    # the ramp over the 32 pairs of the 64 rotated numbers: pairs 0-10 keep
    # their frequency, pairs 23-31 turn at a fortieth of it
    ramp = np.asarray(olmoe._yarn_ramp(kind.yarn, 10000.0, 64))
    assert ramp[10] == 0.0 and ramp[11] > 0.0 and ramp[22] < 1.0 and ramp[23] == 1.0
    freq = np.asarray(reference_dsv2._yarn_frequencies(64, 10000.0, sizes["rope_scaling"]))
    np.testing.assert_allclose(freq, olmoe._pair_frequencies(32, 10000.0, kind.yarn), rtol=1e-6)
    assert freq[31] == pytest.approx(10000.0 ** (-62 / 64) / 40, rel=1e-6)


# ---------------------------------------------------------------------------
# the balance loss a sequence and a layer
# ---------------------------------------------------------------------------


def test_the_balance_loss_is_the_equation_a_sequence_and_a_layer():
    """Batch 2, two sparse layers: ``sum_l mean_b sum_e f_lbe P_lbe`` from
    the routers' own probabilities, computed here by hand; it is not the
    pooled form's value, and its gradient reaches a router through ``P``
    alone (``f`` is a count: the gradient of the term is the gradient of
    ``sum f_fixed P``)."""
    params, tokens = _weights(), _tokens()
    inputs = tokens[:, :-1]
    B, S = inputs.shape
    E, K = F32.n_experts, F32.experts_per_token
    with jax.default_matmul_precision("highest"):
        _, stats = olmoe.forward(F32, params, inputs)
        pooled_cfg = dataclasses.replace(F32, seq_balance=False)
        _, pooled_stats = olmoe.forward(pooled_cfg, params, inputs)
    assert "seq_balance" not in pooled_stats
    balance, _ = olmoe.aux_losses(F32, stats, inputs.size)
    pooled, _ = olmoe.aux_losses(pooled_cfg, pooled_stats, inputs.size)

    # by hand: every sparse layer's normed input, its router's softmax, top-K
    def by_hand(params, fixed=None):
        x = params["embed"][inputs]
        total, counts = 0.0, []
        for i, blk in enumerate(params["blocks"]):
            h = reference_dsv2._rmsnorm(x, blk["ln1"]["scale"], 1e-6)
            x = x + jax.vmap(lambda u: reference_dsv2._mla(F32, KIND, u, blk["attn"], ROPE))(h)
            h = reference_dsv2._rmsnorm(x, blk["ln2"]["scale"], 1e-6)
            if "mlp" in blk:
                x = x + reference_dsv2._swiglu(h, **blk["mlp"])
                continue
            p = jax.nn.softmax(h @ blk["moe"]["router"], axis=-1)  # (B, S, E)
            top = jnp.argsort(-p, axis=-1)[..., :K]
            count = jnp.sum(jax.nn.one_hot(top, E), axis=(1, 2))  # (B, E)
            counts.append(count)
            f = (count if fixed is None else fixed[len(counts) - 1]) * E / (K * S)
            total = total + jnp.mean(jnp.sum(f * jnp.mean(p, axis=1), axis=-1))
            x = x + reference_dsv2._moe(F32, h, blk["moe"])[0]
        return total, counts

    with jax.default_matmul_precision("highest"):
        want, counts = by_hand(params)
        assert float(balance) == pytest.approx(float(want), rel=1e-5)
        assert abs(float(balance) - float(pooled)) > 0.5  # two layers summed, not their mean
        # the pooled form at two layers' weight is still another number
        assert abs(float(balance) - 2 * float(pooled)) > 1e-3
        # the gradient through P alone: f held at its counts
        got = jax.grad(lambda p: olmoe.aux_losses(F32, olmoe.forward(F32, p, inputs)[1], inputs.size)[0])(params)
        held = jax.grad(lambda p: by_hand(p, counts)[0])(params)
    for layer in (1, 2):
        a, b = got["blocks"][layer]["moe"]["router"], held["blocks"][layer]["moe"]["router"]
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))))


# ---------------------------------------------------------------------------
# the shares add up
# ---------------------------------------------------------------------------


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """A sparse layer whole - 16 experts - against its 8 expert shares of 2:
    the parts the shares give, with what every rank computes alike (the two
    shared experts; the router is whole on each and gives nothing of its
    own) counted once, sum to what the uncut reference gives for the layer,
    and the shares' claims to every claim."""
    whole = dataclasses.replace(
        F32, n_layers=1, layer_kinds=(KIND,), dense_ff=(None,), held_experts=None)
    p = dsv2.init_params(whole, jax.random.PRNGKey(4))["blocks"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 48, whole.d_model), jnp.float32)
    tokens = x.reshape(-1, whole.d_model)
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda w: reference_dsv2._moe(whole, x, w))(p)
        want = want.reshape(tokens.shape)
        f = whole.expert_width
        shared = sum(
            _SWIGLU(tokens, p["shared"]["w_gate"][:, lo:lo + f], p["shared"]["w_up"][:, lo:lo + f],
                    p["shared"]["w_down"][lo:lo + f]) for lo in (0, f))
        routed, held_claims = [], 0.0
        for first in range(0, 16, 2):
            held = dataclasses.replace(whole, held_experts=(first, 2))
            mine = dict(p, **{w: p[w][first:first + 2] for w in ("w_gate", "w_up", "w_down")})
            y, s = jax.jit(lambda w, held=held: olmoe.moe_layer(held, w, x))(mine)
            routed.append(y.reshape(tokens.shape) - shared)  # the rank's own experts' part
            held_claims += float(s["held_claims"])
        np.testing.assert_allclose(
            sum(routed) + shared, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))
    assert len(routed) == 8
    assert held_claims == tokens.shape[0] * whole.experts_per_token


# ---------------------------------------------------------------------------
# the configuration's file and the family
# ---------------------------------------------------------------------------


def test_the_published_configuration_is_the_rank_it_says():
    sizes = _sizes()
    family = common.load_family("dsv2_lm")
    cfg = family.build(sizes)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.held) == (64, 6, (0, 8))
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.expert_width) == (2048, 16, 128, 1408)
    assert cfg.router is None and not cfg.renormalize_top_k and cfg.seq_balance
    assert cfg.shared_width == 2816 and cfg.balance_coef == 0.001 and cfg.z_coef == 0.0
    assert cfg.ff == (10944, None, None, None, None) and cfg.vocab_size == 12800
    assert cfg.kinds == (dsv2.latent_kind(sizes),) * 5
    assert sizes["published"] == {
        "num_hidden_layers": 27, "n_routed_experts": 64, "vocab_size": 102400}
    assert list(sizes["reduced"]) == list(sizes["published"])
    with open(os.path.join(os.path.dirname(__file__), "..", "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "dsv2-lite-l5-ep8")
    assert entry["reduced"] == list(sizes["reduced"]) and entry["source"] == sizes["source"]
    deployment = sizes["deployment"]
    assert (deployment["chips_per_layer"], deployment["rank"], deployment["layers"]) == (8, 0, [0, 1, 2, 3, 4])
    assert 64 // deployment["experts_ways"] == 8 and 102400 // deployment["vocabulary_ways"] == 12800
    # every width as the catalog's row has it
    assert (sizes["hidden_size"], sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]) == (2048, 128, 64)
    assert (sizes["v_head_dim"], sizes["kv_lora_rank"], sizes["num_attention_heads"]) == (128, 512, 16)
    assert (sizes["intermediate_size"], sizes["moe_intermediate_size"]) == (10944, 1408)
    assert (sizes["n_shared_experts"], sizes["num_experts_per_tok"]) == (2, 6)
    assert sizes["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 4096, "type": "yarn"}
    batch, seq = sizes["batch"], sizes["seq"]
    assert (batch, seq) == (1, 8193)
    # 8,192 positions, 8 of 64 held at top-6: tiles of 128 rows, a buffer of
    # 9,216, an expert heavy from 1,026 claims
    assert olmoe._share_buffer(cfg, 8192) == (9216, 128, 1025)
    assert family.expected_held_claims(cfg, 8192) == 6144
    assert family.tokens_per_step(batch, seq) == 8192
    mla = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    sparse = 2048 * 64 + 3 * 2048 * 2816 + 8 * 3 * 2048 * 1408
    want = 5 * mla + 3 * 2048 * 10944 + 4 * sparse + 10 * 2048 + 2 * 12800 * 2048 + 2048
    assert mla == 13_763_072
    assert family.parameters(cfg) == want == 535_060_992
    flash = family.flash_calls(cfg, batch, seq)
    assert flash["calls"] == family.lowered_mosaic_calls(cfg) == 10
    assert flash["flops"] == 5 * 16 * 6 * (192 + 128) * (8192 * 8193 // 2)
    assert family.kind_flash(cfg, batch, seq)["mla"]["layers"] == 5
    assert round(family.flops_per_step(cfg, batch, seq) / 1e12, 1) == 17.8
    held = family.facts(cfg, batch, seq)["held_expert_matmuls"]
    assert (held["rows"], held["calls"]) == (6144, 36)


def test_the_family_reads_the_routing_with_its_balance():
    sizes = _sizes()
    sizes = {**sizes, **sizes["rehearsal"]}
    family = common.load_family("dsv2_lm")
    cfg = family.build(sizes)
    params = family.init(cfg, jax.random.PRNGKey(0))
    pool = jax.random.randint(jax.random.PRNGKey(1), (3, 1, 33), 0, cfg.vocab_size, jnp.int32)
    got = jax.jit(lambda p, t: family.routing(cfg, p, t))(params, pool)
    assert sorted(got) == ["balance", "heavy_experts", "held_claims", "load_max"]
    assert all(v.shape == (3,) for v in got.values())
    assert np.all(np.asarray(got["balance"]) >= 1.0 - 1e-3)  # 1.0 at even routing, more off it
    # the reference of the family is handed the published rope_scaling of
    # the sizes it was built from, not what the program made of it
    with jax.default_matmul_precision("highest"):
        losses, norms = jax.jit(lambda p, b: family.reference_train(cfg, p, b))(params, pool[:2])
        want, _ = jax.jit(lambda p, t: reference_dsv2.grads(cfg, p, t, sizes["rope_scaling"]))(
            params, pool[0])
    assert losses.shape == norms.shape == (2,) and float(losses[0]) == pytest.approx(float(want), rel=1e-6)


def test_the_reference_gets_the_rope_scaling_its_own_configuration_was_built_from():
    """Two builds in one process (a rehearsal's sizes, then the cell's)
    keep each its own published numbers, and a configuration the family did
    not build is refused: nothing says what its factors were derived from."""
    sizes = _sizes()
    small = {**sizes, **sizes["rehearsal"]}
    stretched = {**small, "rope_scaling": {**small["rope_scaling"], "factor": 8}}
    family = common.load_family("dsv2_lm")
    first, second = family.build(small), family.build(stretched)
    assert (first.kinds[0].yarn.factor, second.kinds[0].yarn.factor) == (4.0, 8.0)
    params = family.init(first, jax.random.PRNGKey(0))
    pool = jax.random.randint(jax.random.PRNGKey(1), (1, 1, 33), 0, first.vocab_size, jnp.int32)
    read = []
    with jax.default_matmul_precision("highest"):
        for cfg, rope in ((first, small["rope_scaling"]), (second, stretched["rope_scaling"])):
            got, _ = jax.jit(lambda p, b: family.reference_train(cfg, p, b))(params, pool)
            want, _ = jax.jit(lambda p, t: reference_dsv2.grads(cfg, p, t, rope))(params, pool[0])
            assert float(got[0]) == pytest.approx(float(want), rel=1e-6)
            read.append(float(got[0]))
    assert abs(read[0] - read[1]) > 1e-5 * read[0]  # the two factors are told apart
    with pytest.raises(ValueError, match="a configuration that dsv2_lm.build returned"):
        family.reference_train(dataclasses.replace(first, balance_coef=0.5), params, pool)


def test_the_rows_reader_reads_the_layout_and_nothing_else():
    read = common.load_by_name("layer_metrics", "attn_mla_rows_ms").read
    paths = {
        "forward": {"attn/mla/qk_rows": 0.004, "attn/mla/proj": 0.010, "attn/mla/flash_fwd": 0.02},
        "backward": {"attn/mla/qk_rows": 0.006, "attn/mla/flash_bwd": 0.03, "attn/qk_rows": 0.5},
    }
    facts = {"trace": {"paths_s": paths, "steps": 2}}
    assert read(facts) == pytest.approx(5.0)
    assert read({"trace": None}) is None
    # a program that names no such scope (the parent of PR 53 under another
    # cell's trace): nothing, and no error
    other = {"trace": {"paths_s": {"forward": {"attn/full/qk_rows": 0.01}}, "steps": 2}}
    assert read(other) is None


@pytest.mark.parametrize("model", ["ling3", "olmoe"])
def test_the_older_configurations_never_meet_the_new_fields(model, monkeypatch):
    """A configuration that says nothing of them takes the path it took: no
    ``seq_balance`` among its routers' sums, its latent layer normed and
    gated at a factor of 1, and its loss's gradient lowers, with YaRN's ramp
    made to fail, to the text it lowers to with it."""
    cfg = {"ling3": ling.tiny_ling_config(), "olmoe": olmoe.tiny_olmoe_config()}[model]
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size, jnp.int32)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def lowered():
        return jax.jit(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens))).lower(compute).as_text()

    text = lowered()

    def never(*_, **__):
        raise AssertionError("a model without YaRN met its ramp")

    monkeypatch.setattr(olmoe, "_yarn_ramp", never)
    assert lowered() == text
    assert not cfg.seq_balance
    assert "seq_balance" not in jax.eval_shape(lambda: olmoe.forward(cfg, compute, tokens[:, :-1])[1])
    for kind in cfg.kinds:
        if isinstance(kind.mixer, olmoe.Mla):
            assert (kind.mixer.qk_norm, kind.mixer.gated, kind.mixer.softmax_factor) == (True, True, 1.0)


# ---------------------------------------------------------------------------
# through the step transaction
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


def test_an_aborted_step_a_committed_one_and_a_state_dict_round_trip():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program under the generator's optimizer: a step that aborts (an
    error reported before the vote) leaves every leaf as it was; the
    committed steps' losses are the reference's own training run's;
    ``state_dict`` -> ``load_state_dict`` through the callbacks the Manager
    holds carries the state to a second one, leaf for leaf."""
    params = _weights()
    batches = jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_dsv2.train(F32, p, b, ROPE))(params, batches)
    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: dsv2.loss_fn(F32, p, t)))
    lighthouse, collectives, manager = _one_member(state, "dsv2_test")
    optimizer = OptimizerWrapper(manager, state)
    losses = []
    try:
        with jax.default_matmul_precision("highest"):
            optimizer.zero_grad()
            _, grads = grad_fn(state.params, batches[2])
            avg = manager.allreduce(grads).wait()
            manager.report_error(RuntimeError("a peer died"))
            assert not optimizer.step(avg)
            for a, b in zip(jax.tree_util.tree_leaves(state.params), jax.tree_util.tree_leaves(params)):
                np.testing.assert_array_equal(a, b)
            for tokens in batches:
                optimizer.zero_grad()
                loss, grads = grad_fn(state.params, tokens)
                assert optimizer.step(manager.allreduce(grads).wait())
                losses.append(float(loss))
        other = FTTrainState(_weights(seed=9), optax.adamw(reference.LEARNING_RATE))
        other.load_state_dict(state.state_dict())
        mine, theirs = state.state_dict(), other.state_dict()
        assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
        for a, b in zip(jax.tree_util.tree_leaves(mine), jax.tree_util.tree_leaves(theirs)):
            np.testing.assert_array_equal(a, b)
    finally:
        manager.shutdown()
        collectives.shutdown()
        lighthouse.shutdown()
    np.testing.assert_allclose(losses, want, rtol=5e-5)


def test_make_train_step_takes_the_configuration():
    """``models.make_train_step`` (the raw loop's fused step) serves
    DeepSeek-V2 as it serves OLMoE: one loss for the family."""
    from torchft_tpu.models import make_train_step

    tokens, tx, params = _tokens(), optax.adamw(1e-3), _weights(BF16)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    want = float(jax.jit(lambda p, t: dsv2.loss_fn(BF16, p, t))(compute, tokens))
    _, _, loss = make_train_step(BF16, tx, bf16_params=True)(params, tx.init(params), tokens)
    assert abs(float(loss) - want) <= 2e-3 * want
