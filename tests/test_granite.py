"""Granite 4.0-H as a configuration of the family in models/olmoe.py
(torchft_tpu.models.granite: a Mamba-2 state-space mixer in most layers over
``ops/ssd.py``'s chunked scan, one softmax-attention layer with no position
signal at the config's own scale, the scaled residual stream, a tied
readout, a stack recomputed a layer) against its plain reference
(benchmark/reference_granite.py: the recurrence position by position), at
tiny sizes on the CPU, seeded weights: two state-space layers, the
attention layer, one more; Mamba 8 heads of 16 over a state of 16 in chunks
of 16; 4 query heads over 2 key/value heads.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (the scan in chunks against one position
at a time; flash tiles against a dense softmax a head), so they differ by
float32 rounding alone: measured here at 1e-7 relative on the loss and 2e-6
of its largest entry on the worst gradient leaf. The loss is held to 1e-5
and every gradient leaf to 1e-4, far under what the smallest wrong term
costs (``test_a_wrong_term_is_caught``). In bf16 (the configuration's
precision) a model of width 64 is held to 3e-2 on the loss and 0.1 on the
gradient norm.
"""

import collections
import dataclasses
import json
import os
import re
import sys
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import common, reference, reference_granite
from benchmark.reduce import spans
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.models import dsv2, granite, ling, mellum, olmoe, ouro, sdar
from torchft_tpu.ops.ssd import ssd_scan

PUB = granite.TINY_CONFIG
LAYERS = range(4)
BF16 = granite.tiny_granite_config()
F32 = dataclasses.replace(BF16, dtype=jnp.float32)
MAMBA, NOPE = F32.kinds[0], F32.kinds[2]
LOSS_RTOL_F32, GRAD_RTOL_F32 = 1e-5, 1e-4


def _sizes():
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "granite4-h-micro-l10-v8.json"
    )
    with open(path) as f:
        return json.load(f)


def _weights(cfg=F32, seed=0):
    return granite.init_params(cfg, jax.random.PRNGKey(seed))


def _tokens(cfg=F32, batch=2, seq=41, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(params, tokens, pub=PUB):
    # a jit of its own a call: a test may have changed a term under it
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, t: reference_granite.grads(pub, LAYERS, p, t))(params, tokens)


def _program(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p, t: granite.loss_fn(cfg, p, t)))(params, tokens)


def _assert_leaves_close(got, want, rtol):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            a, b, rtol=0, atol=rtol * scale, err_msg=jax.tree_util.keystr(path)
        )


# ---------------------------------------------------------------------------
# the op against the recurrence
# ---------------------------------------------------------------------------


def _scan_inputs(batch, s, h=4, p=8, n=16, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (batch, s, h, p), jnp.float32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, s, h), jnp.float32))
    A = -jnp.exp(jnp.log(jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)))
    B = jax.random.normal(ks[3], (batch, s, n), jnp.float32).astype(dtype)
    C = jax.random.normal(ks[4], (batch, s, n), jnp.float32).astype(dtype)
    D = jax.random.normal(ks[5], (h,), jnp.float32)
    return x, dt, A, B, C, D


def _by_position(x, dt, A, B, C, D):
    f32 = jnp.float32
    one = lambda x, dt, B, C: reference_granite.recurrence(  # noqa: E731
        x.astype(f32), dt, A, B.astype(f32), C.astype(f32), D
    )
    return jax.vmap(one)(x, dt, B, C)


@pytest.mark.parametrize("batch,s,chunk", [
    (1, 16, 16),  # one chunk
    (1, 64, 16),  # several
    (1, 50, 16),  # a padded last chunk
    (2, 40, 16),  # batch 2, padded
    (1, 300, 256),  # the published chunk, padded
    (1, 7, 16),  # shorter than a chunk
])
def test_ssd_scan_is_the_recurrence(batch, s, chunk):
    args = _scan_inputs(batch, s)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(*args, chunk=chunk)
        want = _by_position(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)


def _cotangents(fn, args, weight):
    return jax.grad(lambda *a: jnp.sum(weight * fn(*a)), argnums=range(6))(*args)


@pytest.mark.parametrize("batch,s,chunk", [
    (1, 64, 16),  # several chunks: the reverse loop carries the states' cotangents
    (2, 40, 16),  # batch 2, a padded last chunk
    (1, 50, 16),  # a padded last chunk
    (1, 7, 16),  # shorter than a chunk
    (1, 16, 16),  # one chunk: nothing is carried
])
def test_ssd_scan_cotangents_are_the_recurrences(batch, s, chunk):
    """The op's own backward (``ops/ssd.py``: ``_backward``) against autodiff
    of the recurrence a position at a time, in float32: each of the six
    cotangents to 1e-5 of its largest entry, in its argument's shape and
    type - ``A``'s and ``D``'s a head."""
    args = _scan_inputs(batch, s, seed=3)
    weight = jax.random.normal(jax.random.PRNGKey(9), args[0].shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _cotangents(lambda *a: ssd_scan(*a, chunk=chunk), args, weight)
        want = _cotangents(_by_position, args, weight)
    assert got[2].shape == got[5].shape == (args[0].shape[2],)
    for name, a, b in zip("x dt A B C D".split(), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5 * scale, err_msg=name)


def test_ssd_scan_cotangents_come_back_in_their_arguments_types():
    """Whatever comes in: bf16 steps, rates and skips beside float32 maps."""
    x, dt, A, B, C, D = _scan_inputs(1, 40, dtype=jnp.bfloat16, seed=4)
    bf16 = jnp.bfloat16
    args = (x, dt.astype(bf16), A.astype(bf16), B.astype(jnp.float32), C, D.astype(bf16))
    got = jax.grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk=16).astype(jnp.float32)), argnums=range(6))(*args)
    assert [(g.shape, g.dtype) for g in got] == [(a.shape, a.dtype) for a in args]
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in got)


def test_ssd_scan_in_bf16_comes_as_near_as_its_inputs_rounding():
    """bf16 inputs: the products run in bf16 and add in float32; the output
    and the cotangents of x, B and C come back in bf16, dt's, A's and D's in
    float32, each within a bf16 rounding's reach of the recurrence on the
    same (rounded) inputs."""
    args = _scan_inputs(2, 64, dtype=jnp.bfloat16, seed=5)
    got = ssd_scan(*args, chunk=16)
    want = _by_position(*args)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0, atol=2e-2 * scale)
    grads = jax.grad(
        lambda *a: jnp.sum(ssd_scan(*a, chunk=16).astype(jnp.float32) ** 2), argnums=range(6)
    )(*args)
    wants = jax.grad(lambda *a: jnp.sum(_by_position(*a) ** 2), argnums=range(6))(*args)
    assert [g.dtype for g in grads] == [a.dtype for a in args]
    for name, a, b in zip("x dt A B C D".split(), grads, wants):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(
            a.astype(jnp.float32), b.astype(jnp.float32), rtol=0, atol=5e-2 * scale, err_msg=name
        )


def test_ssd_scan_carries_a_fast_decay_and_a_slow_one():
    """Steps of 20 under a rate of -16 (a decay of e^-320 a position: the
    state forgets at once) beside steps of 1e-4 under -1 (it forgets
    nothing): every factor stays in (0, 1] and nothing overflows."""
    x, dt, A, B, C, D = _scan_inputs(1, 48, h=2)
    dt = dt.at[..., 0].set(20.0).at[..., 1].set(1e-4)
    A = jnp.array([-16.0, -1.0])
    weight = jax.random.normal(jax.random.PRNGKey(9), x.shape, jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = ssd_scan(x, dt, A, B, C, D, chunk=16)
        want = _by_position(x, dt, A, B, C, D)
        grads = _cotangents(lambda *a: ssd_scan(*a, chunk=16), (x, dt, A, B, C, D), weight)
        wants = _cotangents(_by_position, (x, dt, A, B, C, D), weight)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))
    # the backward builds the same factors again: each in (0, 1], and a head
    # that forgets at once has the cotangents of one that sees its own position
    for name, a, b in zip("x dt A B C D".split(), grads, wants):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(b))), err_msg=name)


# ---------------------------------------------------------------------------
# the two mixers against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("batch,s", [(2, 40), (1, 65)])
def test_the_mamba_mixer_is_the_reference(batch, s):
    p = _weights()["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (batch, s, F32.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda w: jnp.sum(olmoe.mamba2_mixer(F32, w, x, MAMBA) ** 2))(p)
        want, want_grads = jax.value_and_grad(lambda w: jnp.sum(
            jax.vmap(lambda u: reference_granite._mamba(PUB, u, w))(x) ** 2))(p)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


@pytest.mark.parametrize("batch,s", [(2, 40), (1, 65)])
def test_the_nope_layer_is_dense_attention_a_head(batch, s):
    p = _weights()["blocks"][2]["attn"]
    assert sorted(p) == ["wk", "wo", "wq", "wv"]  # no norm of q or k
    x = jax.random.normal(jax.random.PRNGKey(3), (batch, s, F32.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(
            lambda w: jnp.sum(olmoe.attention(F32, w, x, NOPE) ** 2))(p)
        want, want_grads = jax.value_and_grad(lambda w: jnp.sum(
            jax.vmap(lambda u: reference_granite._attention(PUB, u, w))(x) ** 2))(p)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


def test_the_mixers_heads_are_its_own():
    """The state-space mixer's 8 heads of 16 and the attention layer's 4
    heads of 16 live in one configuration; the tree holds Mamba-2's leaves
    at their published layout ``[z | xBC | dt]``."""
    p = _weights()["blocks"][0]["attn"]
    inner, n, h = 8 * 16, 16, 8
    assert p["w_in"].shape == (64, 2 * inner + 2 * n + h)
    assert p["conv"].shape == (4, inner + 2 * n) and p["conv_bias"].shape == (inner + 2 * n,)
    assert p["norm"].shape == (inner,) and p["wo"].shape == (inner, 64)
    assert {p[k].shape for k in ("dt_bias", "a_log", "d")} == {(h,)}
    dt = jax.nn.softplus(p["dt_bias"])
    assert float(jnp.min(dt)) >= 1e-3 * 0.999 and float(jnp.max(dt)) <= 0.1 * 1.001
    assert float(jnp.min(p["a_log"])) >= 0.0 and float(jnp.max(p["a_log"])) <= np.log(16.0)
    assert (F32.n_heads, F32.kv_heads, F32.head_dim) == (4, 2, 16)


# ---------------------------------------------------------------------------
# the whole model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_f32_loss_and_gradients_match_the_reference(seed):
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 10)
    loss, grads = _program(F32, params, tokens)
    want, want_grads = _reference(params, tokens)
    assert abs(float(loss) - float(want)) <= LOSS_RTOL_F32 * float(want)
    assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(want_grads)
    _assert_leaves_close(grads, want_grads, GRAD_RTOL_F32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bf16_path_matches_the_reference_at_what_bf16_earns(seed):
    params, tokens = _weights(BF16, seed), _tokens(seed=seed + 10)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: granite.loss_fn(BF16, p, tokens)))(compute)
    want, want_grads = _reference(params, tokens)
    assert abs(float(loss) - float(want)) <= 3e-2 * float(want)
    norm, want_norm = float(common.tree_norm(grads)), float(common.tree_norm(want_grads))
    assert abs(norm - want_norm) <= 0.1 * want_norm


def test_the_tied_embedding_gets_both_terms():
    """No ``readout`` leaf; the embedding's gradient is the lookup's part
    plus the readout's, each of which the reference gives when the other use
    is cut off from the gradient."""
    params, tokens = _weights(), _tokens()
    assert "readout" not in params
    _, grads = _program(F32, params, tokens)
    held = jax.lax.stop_gradient(params["embed"])

    def reference_with(lookup, readout):
        # the reference's loss with each use of E given its own matrix
        pub, p = PUB, dict(params)
        x = pub["embedding_multiplier"] * lookup[tokens[:, :-1]]
        runs = reference_granite.stacked(pub, LAYERS, p)
        types = reference_granite.types_of(pub, LAYERS)
        first = 0
        for run in runs["blocks"]:
            for i in range(jax.tree_util.tree_leaves(run)[0].shape[0]):
                blk = jax.tree_util.tree_map(lambda l: l[i], run)
                u = reference_granite._rmsnorm(x, blk["ln1"]["scale"], pub["rms_norm_eps"])
                mixer = reference_granite._MIXERS[types[first]]
                x = x + pub["residual_multiplier"] * jax.vmap(
                    lambda us: mixer(pub, us, blk["attn"]))(u)
                u, mlp = reference_granite._rmsnorm(x, blk["ln2"]["scale"], pub["rms_norm_eps"]), blk["mlp"]
                x = x + pub["residual_multiplier"] * (
                    (reference_granite._silu(u @ mlp["w_gate"]) * (u @ mlp["w_up"])) @ mlp["w_down"])
                first += 1
        logits = reference_granite._rmsnorm(
            x, p["ln_f"]["scale"], pub["rms_norm_eps"]) @ readout.T / pub["logits_scaling"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    with jax.default_matmul_precision("highest"):
        of_lookup = jax.grad(lambda e: reference_with(e, held))(params["embed"])
        of_readout = jax.grad(lambda e: reference_with(held, e))(params["embed"])
    assert float(jnp.max(jnp.abs(of_lookup))) > 0 and float(jnp.max(jnp.abs(of_readout))) > 0
    _assert_leaves_close(grads["embed"], of_lookup + of_readout, GRAD_RTOL_F32)
    # and neither term alone is the gradient
    assert float(jnp.max(jnp.abs(grads["embed"] - of_readout))) > 1e-2 * float(
        jnp.max(jnp.abs(grads["embed"])))


def test_three_adamw_steps_are_the_references():
    params = _weights()
    batches = jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, want_norms = jax.jit(
            lambda p, b: reference_granite.train(PUB, LAYERS, p, b))(params, batches)
    tx = optax.adamw(reference.LEARNING_RATE)
    opt = tx.init(params)
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: granite.loss_fn(F32, p, t)))
    for i in range(3):
        with jax.default_matmul_precision("highest"):
            loss, grads = grad_fn(params, batches[i])
        assert float(loss) == pytest.approx(float(want[i]), rel=LOSS_RTOL_F32)
        assert float(common.tree_norm(grads)) == pytest.approx(float(want_norms[i]), rel=1e-4)
        updates, opt = tx.update(grads, opt, params)
        params = optax.apply_updates(params, updates)


# ---------------------------------------------------------------------------
# a wrong term, in the program or in the reference, is caught
# ---------------------------------------------------------------------------


def _kinds(cfg, **changed):
    return dataclasses.replace(cfg, layer_kinds=tuple(
        dataclasses.replace(k, **changed) if k.mixer is None else k for k in cfg.kinds
    ))


# a configuration that says something else than the published keys
WRONG_PROGRAM = {
    "residual_multiplier left at 1": dataclasses.replace(F32, residual_multiplier=1.0),
    "embedding_multiplier left at 1": dataclasses.replace(F32, embedding_multiplier=1.0),
    "logits_scaling left at 1": dataclasses.replace(F32, logits_scaling=1.0),
    "head_dim ** -0.5 for attention_multiplier": _kinds(F32, softmax_scale=None),
    "a rotary embedding applied": _kinds(F32, rotary=True),
}


WRONG_LEAVES = {
    # the mixer reads a leaf the reference reads too: hand the PROGRAM another
    "D left out": lambda attn: dict(attn, d=jnp.zeros_like(attn["d"])),
    "dt_bias left out": lambda attn: dict(attn, dt_bias=jnp.zeros_like(attn["dt_bias"])),
    "the convolution's bias left out": lambda attn: dict(
        attn, conv_bias=jnp.zeros_like(attn["conv_bias"])),
}

def _reference_norm_before_gate(pub, u, w):
    """The reference's mixer with the gate AFTER the norm."""
    s = u.shape[0]
    h, p, n = pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"]
    inner = h * p
    z = (u @ w["w_in"])[:, :inner]
    # the sound mixer's y before its norm and gate: undo W_o by running the
    # sound pieces again
    taps = pub["mamba_d_conv"]
    into = u @ w["w_in"]
    xbc, dt = into[:, inner:2 * inner + 2 * n], into[:, 2 * inner + 2 * n:]
    before = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = reference_granite._silu(
        sum(before[j:j + s] * w["conv"][j] for j in range(taps)) + w["conv_bias"])
    x, b, c = xbc[:, :inner], xbc[:, inner:inner + n], xbc[:, inner + n:]
    y = reference_granite.recurrence(
        x.reshape(s, h, p), reference_granite._softplus(dt + w["dt_bias"]),
        -jnp.exp(w["a_log"]), b, c, w["d"])
    y = reference_granite._rmsnorm(y.reshape(s, inner), w["norm"], pub["rms_norm_eps"])
    return (y * reference_granite._silu(z)) @ w["wo"]


WRONG_REFERENCE = {
    "the norm before the gate": lambda m: m.setitem(
        reference_granite._MIXERS, "mamba", _reference_norm_before_gate),
    "an exponential for the softplus": lambda m: m.setattr(
        reference_granite, "_softplus", jnp.exp),
    "no causal mask in attention": lambda m: m.setitem(
        reference_granite._MIXERS, "attention", lambda pub, u, w: _ATTENTION(pub, u, w)),
    "B and C exchanged": lambda m: m.setattr(
        reference_granite, "recurrence",
        lambda x, dt, a, b, c, d: _RECURRENCE(x, dt, a, c, b, d)),
    "a norm a head": lambda m: m.setattr(
        reference_granite, "_rmsnorm", _rmsnorm_a_head),
}

_RECURRENCE, _RMSNORM = reference_granite.recurrence, reference_granite._rmsnorm


def _rmsnorm_a_head(x, scale, eps):
    """The gated norm's statistic a head of 16 and not over all 128 inner
    channels (every other norm is over the 64 of the stream)."""
    if x.shape[-1] != 128:
        return _RMSNORM(x, scale, eps)
    heads = x.reshape(x.shape[:-1] + (8, 16))
    return _RMSNORM(heads, scale.reshape(8, 16), eps).reshape(x.shape)


def _ATTENTION(pub, u, w):
    """The reference's attention without its mask (a dense softmax over
    every key)."""
    s = u.shape[0]
    h, kv = pub["num_attention_heads"], pub["num_key_value_heads"]
    dh = pub["hidden_size"] // h
    q = (u @ w["wq"]).reshape(s, h, dh)
    k = jnp.repeat((u @ w["wk"]).reshape(s, kv, dh), h // kv, axis=1)
    v = jnp.repeat((u @ w["wv"]).reshape(s, kv, dh), h // kv, axis=1)
    probs = jax.nn.softmax(jnp.einsum("thd,shd->hts", q, k) * pub["attention_multiplier"], axis=-1)
    return jnp.einsum("hts,shd->thd", probs, v).reshape(s, h * dh) @ w["wo"]


@pytest.mark.parametrize(
    "wrong", sorted(WRONG_PROGRAM) + sorted(WRONG_LEAVES) + sorted(WRONG_REFERENCE)
)
def test_a_wrong_term_is_caught(wrong, monkeypatch):
    """One case a multiplier, the rotation, the softmax scale, the gate's
    side of the norm, ``D``, ``dt_bias`` and more: the program with the term
    wrong (or the reference with it wrong, where the program has no switch
    that names it) parts from the other by far more than float32's rounding."""
    tokens, params = _tokens(), _weights()
    # scores of some spread: at 1/16 of q.k from unit weights the softmax is
    # near enough uniform that no rotation shows in it
    params = dict(params, blocks=[
        b if "w_in" in b["attn"] else dict(b, attn=dict(
            b["attn"], wq=4.0 * b["attn"]["wq"], wk=4.0 * b["attn"]["wk"]))
        for b in params["blocks"]
    ])
    cfg = WRONG_PROGRAM.get(wrong, F32)
    mine = params
    if wrong in WRONG_LEAVES:
        mine = dict(params, blocks=[
            dict(b, attn=WRONG_LEAVES[wrong](b["attn"])) if "w_in" in b["attn"] else b
            for b in params["blocks"]
        ])
    loss, grads = _program(cfg, mine, tokens)
    if wrong in WRONG_REFERENCE:
        WRONG_REFERENCE[wrong](monkeypatch)
    want, want_grads = _reference(params, tokens)
    off = abs(float(loss) - float(want)) / float(want)
    norm, want_norm = float(common.tree_norm(grads)), float(common.tree_norm(want_grads))
    assert off > 10 * LOSS_RTOL_F32 or abs(norm - want_norm) / want_norm > 10 * GRAD_RTOL_F32, (
        wrong, off, norm, want_norm,
    )


def test_bf16_running_sums_in_the_scan_are_seen(monkeypatch):
    """The decays' running sums rounded to bf16 (what ``controls_granite``
    plants on the chip): far outside float32's agreement with the
    recurrence, at steps and rates as the mixer draws them. The plant itself -
    ``ssd._EXACT`` at the DEFAULT precision while the loss is traced, put
    back before its gradient is - reaches BOTH passes: the output and the
    cotangents change, and the backward's sums are the forward's (a backward
    that read the attribute when IT is traced would build other decays than
    the forward used)."""
    from torchft_tpu.ops import ssd

    x, dt, A, B, C, D = _scan_inputs(1, 64, seed=2)
    with jax.default_matmul_precision("highest"):
        want = _by_position(x, dt, A, B, C, D)
        sound = ssd_scan(x, dt, A, B, C, D, chunk=16)
        rounded = ssd_scan(
            x, dt.astype(jnp.bfloat16).astype(jnp.float32),
            A.astype(jnp.bfloat16).astype(jnp.float32), B, C, D, chunk=16)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(sound - want))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(rounded - want))) > 1e-3 * scale

    # a CPU's DEFAULT product of float32s is float32's: plant what the TPU's
    # does to the sums' terms, one bf16 pass, under a precision of that name
    class Bf16Pass:
        @staticmethod
        def einsum(spec, *operands, precision=None, **how):
            if precision == "one bf16 pass":
                operands = [o.astype(jnp.bfloat16).astype(o.dtype) for o in operands]
                precision = None
            return jnp.einsum(spec, *operands, precision=precision, **how)

        def __getattr__(self, name):
            return getattr(jnp, name)

    monkeypatch.setattr(ssd, "jnp", Bf16Pass())
    args, sq = (x, dt, A, B, C, D), lambda y: jnp.sum(y ** 2)  # noqa: E731

    def planted(precision):
        # as ``controls_granite.patched``: the attribute is back before the
        # backward is traced
        def loss(*a):
            with monkeypatch.context() as m:
                m.setattr(ssd, "_EXACT", precision)
                y = ssd_scan(*a, chunk=16)
                return sq(y), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(*args)

    ((_, y), grads), ((_, wrong), wrong_grads) = planted(ssd._EXACT), planted("one bf16 pass")
    assert float(jnp.max(jnp.abs(wrong - y))) > 1e-4 * scale  # the sound one: under 1e-5
    for a, b in zip(wrong_grads, grads):
        assert float(jnp.max(jnp.abs(a - b))) > 1e-4 * float(jnp.max(jnp.abs(b)))
    both = jax.jit(jax.grad(
        lambda *a: sq(ssd._scan(16, "one bf16 pass", *a)), argnums=(0, 1, 2)))(*args)
    for a, b in zip(wrong_grads, both):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * float(jnp.max(jnp.abs(b))))


# ---------------------------------------------------------------------------
# recomputation a layer
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_recomputation_changes_no_bit_of_the_loss_and_the_last_of_a_gradient(dtype):
    """The loss to the bit. The gradients to their last bits and not
    further: the scan over the chunks inside the mixer is one compiled loop
    where its layer is recomputed and two (what is known, what is not) where
    its residuals are kept, and a sum in another order differs in its last
    bit - 1e-6 of a leaf's largest entry in float32, a bf16 rounding in bf16."""
    kept = dataclasses.replace(granite.tiny_granite_config(False), dtype=dtype)
    again = dataclasses.replace(granite.tiny_granite_config(True), dtype=dtype)
    assert not kept.recompute_layers and again.recompute_layers
    params = jax.tree_util.tree_map(lambda l: l.astype(dtype), _weights(kept))
    tokens = _tokens()
    # primitive by primitive, so that both run the same compiled operations
    # (as whole programs the compiler fuses the two differently, and a sum in
    # another order differs in its last bit)
    a = jax.value_and_grad(lambda p: granite.loss_fn(kept, p, tokens))(params)
    b = jax.value_and_grad(lambda p: granite.loss_fn(again, p, tokens))(params)
    assert float(a[0]) == float(b[0])
    _assert_leaves_close(
        jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), a[1]),
        jax.tree_util.tree_map(lambda l: l.astype(jnp.float32), b[1]),
        1e-5 if dtype == jnp.float32 else 4e-2,
    )


def _residuals(loss, params):
    """The shapes the backward pass of ``loss(params)`` holds between its
    two passes, the arguments (the weights) left out."""
    from jax._src.ad_checkpoint import saved_residuals as saved

    return collections.Counter(
        tuple(aval.shape) for aval, why in saved(loss, params) if "argument" not in why
    )


def _chunks_of(cfg, chunk):
    """``cfg`` with its scans in chunks of ``chunk``."""
    return dataclasses.replace(cfg, layer_kinds=tuple(
        k if k.mixer is None else dataclasses.replace(
            k, mixer=dataclasses.replace(k.mixer, chunk=chunk))
        for k in cfg.kinds
    ))


def _recomputed_case(batch=1, seq=32):
    """The recomputed tiny model with what the tests of its save policy
    count: (the configuration, its loss of the weights, the weights, the
    gradient's forward products, the shapes of the stream and of a SwiGLU's
    hidden rows, the number of Mamba layers). Its scans run in chunks of 8,
    so that a decay's (chunk, chunk) square is no state's (P, n)."""
    cfg = _chunks_of(granite.tiny_granite_config(True), 8)
    tokens, params = _tokens(batch=batch, seq=seq + 1), _weights(BF16)

    def products(of=cfg):
        return _forward_products(of, _products(of, params, tokens), batch, seq)

    return (
        cfg, lambda p: granite.loss_fn(cfg, p, tokens), params, products,
        (batch, seq, cfg.d_model), (batch, seq, cfg.ff[0]),
        sum(isinstance(kind.mixer, olmoe.Mamba2) for kind in cfg.kinds),
    )


def _products(cfg, params, tokens):
    """Every matrix product of the loss's gradient, forward, recomputed and
    backward, nested programs included: (the left shape, the right shape,
    the contracted axes) -> how many the jaxpr holds (a loop's body once)."""
    found = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                left, right = (tuple(v.aval.shape) for v in eqn.invars)
                found[left, right, eqn.params["dimension_numbers"][0]] += 1
            for value in eqn.params.values():
                for sub in value if isinstance(value, (tuple, list)) else (value,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    compute = jax.tree_util.tree_map(lambda l: l.astype(cfg.dtype), params)
    walk(jax.make_jaxpr(jax.grad(lambda p: granite.loss_fn(cfg, p, tokens)))(compute).jaxpr)
    return found


def _forward_products(cfg, products, batch, seq):
    """Of ``_products``, the forward products ``x W`` a layer by what they
    are: the SwiGLU's gate and up (one shape), a Mamba mixer's ``wo``, its
    map to ``[z | xBC | dt]``, and the scan's (the decays' square pairs)."""
    mixer = cfg.kinds[0].mixer
    wide = 2 * mixer.inner + 2 * mixer.state + mixer.inner_heads
    rows, last = (batch, seq), ((2,), (0,))

    def onto(k, n):
        return products[(*rows, k), (k, n), last]

    return {
        "gate_up": onto(cfg.d_model, cfg.ff[0]),
        "wo": onto(mixer.inner, cfg.d_model),
        "map": onto(cfg.d_model, wide),
        "scan": sum(
            count for (left, right, _), count in products.items()
            if {left[-2:], right[-2:]} & {(mixer.chunk, mixer.chunk)}
        ),
    }


def _scan_kept(cfg, stream):
    """The shapes of what a Mamba layer's scan keeps by name: its output (B,
    S, H, P) and its chunks' starting states (B, S / chunk, H, P, n)."""
    mixer, (batch, seq) = cfg.kinds[0].mixer, stream[:2]
    heads = (mixer.inner_heads, mixer.inner_head_dim)
    return (batch, seq, *heads), (batch, seq // mixer.chunk, *heads, mixer.state)


def test_a_recomputed_layer_keeps_its_input_and_what_is_named(monkeypatch):
    """What the backward pass of the recomputed stack holds between the two
    passes: per layer the layer's input and exactly what ``STACK_KEPT``
    names - the mixer's output, (B, S, D), the SwiGLU's gate and up
    products, (B, S, ff) each, and a Mamba layer's scan's output and starting
    states - no decay matrix, nothing as wide as the Mamba map's product, no
    residual of a flash kernel. And in the gradient's jaxpr no gate, up or
    ``wo`` product is computed a second time and the scan's forward products
    run ONCE a Mamba layer (its count is the unrecomputed stack's), while the
    Mamba map's is computed as often as with nothing kept."""
    cfg, loss, params, products, stream, hidden, mambas = _recomputed_case()
    # no ``flash_out``, ``flash_lse``
    assert olmoe.STACK_KEPT == ("mixer_out", "mlp_gate", "mlp_up", "ssd_y", "ssd_states")
    scanned, states = _scan_kept(cfg, stream)

    kept, made = _residuals(loss, params), products()
    inner_wide = [s for s in kept if s and s[-1] > max(cfg.d_model, cfg.vocab_size)]
    square = [s for s in kept if len(s) >= 2 and s[-1] == s[-2] == cfg.kinds[0].mixer.chunk]
    assert not inner_wide and not square, kept
    assert kept[hidden] == 2 * cfg.n_layers and kept[stream] >= 2 * cfg.n_layers
    assert kept[scanned] == kept[states] == mambas
    assert made["gate_up"] == 2 * cfg.n_layers and made["wo"] == mambas

    # against the layer's input alone (a policy that names nothing): the
    # named values and NOTHING else - no flash residual, no other shape
    monkeypatch.setattr(olmoe, "STACK_KEPT", ())
    bare, again = _residuals(loss, params), products()
    assert kept - bare == {
        stream: cfg.n_layers, hidden: 2 * cfg.n_layers, scanned: mambas, states: mambas,
    }
    assert not bare - kept and bare[stream] >= cfg.n_layers and not bare[hidden]
    assert again["gate_up"] == 4 * cfg.n_layers and again["wo"] == 2 * mambas
    assert made["map"] == again["map"] and made["scan"] < again["scan"]
    whole = products(_chunks_of(granite.tiny_granite_config(False), 8))
    assert made["map"] == 2 * mambas == 2 * whole["map"] and made["scan"] == whole["scan"]


@pytest.mark.parametrize("name", ["mixer_out", "mlp_gate", "mlp_up", "ssd_y", "ssd_states"])
def test_a_name_out_of_the_tuple_is_computed_again(name, monkeypatch):
    """Each name of ``STACK_KEPT`` is carried by a value and read by the
    policy: taken out of the tuple, its value is no longer among the
    residuals and is computed a second time (out of the tuple, a scan's
    output or states bring the scan's forward products back into the
    recomputed pass); the others stay."""
    cfg, loss, params, products, stream, hidden, mambas = _recomputed_case()
    assert name in olmoe.STACK_KEPT
    scanned, states = _scan_kept(cfg, stream)
    kept, once = _residuals(loss, params), products()
    monkeypatch.setattr(olmoe, "STACK_KEPT", tuple(n for n in olmoe.STACK_KEPT if n != name))
    gone, made = kept - _residuals(loss, params), products()
    if name == "mixer_out":
        assert gone == {stream: cfg.n_layers}
        assert (made["wo"], made["gate_up"]) == (2 * mambas, 2 * cfg.n_layers)
    elif name.startswith("mlp"):
        assert gone == {hidden: cfg.n_layers}
        assert (made["wo"], made["gate_up"]) == (mambas, 3 * cfg.n_layers)
    else:
        assert gone == {scanned if name == "ssd_y" else states: mambas}
        assert made["scan"] > once["scan"]
        assert (made["wo"], made["gate_up"]) == (mambas, 2 * cfg.n_layers)
    if not name.startswith("ssd"):
        assert made["scan"] == once["scan"]


def test_a_looped_model_does_not_take_the_new_fields():
    with pytest.raises(ValueError, match="a looped model"):
        dataclasses.replace(ouro.tiny_ouro_config(), tied_readout=True)
    with pytest.raises(ValueError, match="a looped model"):
        dataclasses.replace(ouro.tiny_ouro_config(), recompute_layers=True)


# ---------------------------------------------------------------------------
# scopes, the lowered step, the family, the readers
# ---------------------------------------------------------------------------


def test_every_operation_of_the_gradient_step_is_under_a_scope():
    """The compiled gradient of the recomputed model: no operation without a
    scope; the scan's ``while`` body under ``attn/mamba/scan`` in the forward
    class and in the backward class (the op's own backward, a ``custom_vjp``'s,
    keeps the scope it was called under), the recomputed forward under
    ``rematted_computation`` WITHOUT the scan, whose output and states are
    kept by name; the six scopes of the mixer and the attention layer's kind
    all there."""
    cfg = granite.tiny_granite_config(True)
    params = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), _weights(cfg))
    tokens = _tokens()
    compiled = jax.jit(jax.grad(lambda p: granite.loss_fn(cfg, p, tokens))).lower(
        params).compile().as_text()
    named = {n for n in re.findall(r'op_name="([^"]*)"', compiled) if n.startswith("jit(")}
    paths = {(spans.scope_class(n), spans.scope_path(n)) for n in named}
    assert not [n for n in named if not spans.scope_path(n)]
    both = {which for which, path in paths if "/attn/mamba/scan/" in f"/{path}/"}
    assert {"forward", "backward"} <= both
    again = [p for _, p in paths if "rematted_computation" in p]
    assert [p for p in again if "attn/mamba/proj" in p]
    assert not [p for p in again if "attn/mamba/scan" in p]
    every = {path for _, path in paths}
    for scope in ("proj", "conv", "gates", "scan", "norm", "out"):
        assert [p for p in every if f"/attn/mamba/{scope}/" in f"/{p}/"], scope
    assert [p for p in every if "/attn/nope/" in f"/{p}/"]


def test_the_lowered_step_holds_three_flash_calls_where_it_is_recomputed(monkeypatch):
    """The family's own count against the text lowered for the chip: the one
    attention layer's ``flash_fwd``, its recomputed ``flash_fwd`` and its
    ``flash_bwd``; two where the stack keeps its activations."""
    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    family = common.load_family("granite_lm")
    sizes = _sizes()
    tiny = {**sizes, **sizes["rehearsal"]}
    for recompute, want in ((True, 3), (False, 2)):
        tiny["deployment"] = dict(tiny["deployment"], recompute_layers=recompute)
        cfg = family.build(tiny)
        params = jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct((1, 65), jnp.int32)
        lowered = jax.jit(common.mixed_precision_grad(family, cfg)).trace(
            params, tokens).lower(lowering_platforms=("tpu",))
        assert family.lowered_mosaic_calls(cfg) == want
        assert lowered.as_text().count("tpu_custom_call") == want
        assert family.flash_calls(cfg, 1, 65)["calls"] == want


def test_the_published_configuration_is_the_cut_it_says():
    sizes = _sizes()
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    row = [
        json.loads(line) for line in open("/opt/skills/guides/model-configs/architectures.jsonl")
        if '"granite-4.0-h-micro"' in line
    ] if os.path.exists("/opt/skills/guides/model-configs/architectures.jsonl") else []
    for published in row:  # every key of the catalog's row but the two cut
        for key, value in published["config"].items():
            if key not in sizes["reduced"]:
                assert sizes[key] == value, key
        assert sizes["source"] == published["source_url"]
    assert sorted(sizes["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert sizes["published"] == {"num_hidden_layers": 40, "vocab_size": 100352}
    assert len(sizes["layer_types"]) == 40 and sizes["deployment"]["layers"] == list(range(10))
    assert sizes["vocab_size"] * 8 == sizes["published"]["vocab_size"]
    assert [k.name for k in cfg.kinds] == ["mamba"] * 5 + ["nope"] + ["mamba"] * 4
    mamba = cfg.kinds[0].mixer
    assert (mamba.inner_heads, mamba.inner_head_dim, mamba.state, mamba.conv_taps, mamba.chunk) == (
        64, 64, 128, 4, 256)
    nope = cfg.kinds[5]
    assert nope.mixer is None and not nope.rotary and nope.softmax_scale == 0.015625
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_model) == (32, 8, 64, 2048)
    assert cfg.ff == (8192,) * 10 and cfg.expert_layers == 0
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (12.0, 0.22, 8.0)
    assert cfg.tied_readout and cfg.recompute_layers and not cfg.qk_norm
    assert family.parameters(cfg) == 772_160_448
    # ISSUE 58's count, piece by piece
    tree = jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0)))
    count = lambda t: sum(l.size for l in jax.tree_util.tree_leaves(t))  # noqa: E731
    assert count(tree["blocks"][0]["attn"]) == 25_847_232
    assert count(tree["blocks"][0]) == 76_182_976
    assert count(tree["blocks"][5]) == 60_821_504
    assert count(tree["embed"]) == 25_690_112 and "readout" not in tree


def test_the_familys_counts_at_the_cells_shape():
    sizes = _sizes()
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    batch, seq = sizes["batch"], sizes["seq"]
    assert family.tokens_per_step(batch, seq) == 4096
    work = family.ssm_scan_work(cfg, batch, seq)
    assert work["layers"] == 9
    assert work["flops"] == 9 * 4096 * 64 * 15 * 64 * 128
    assert work["bytes"] == 9 * 4096 * (3 * (8192 + 256 + 512) + 2 * 8192)
    flash = family.flash_calls(cfg, batch, seq)
    pairs = 4096 * 4097 // 2
    assert flash["calls"] == 3
    assert flash["flops"] == 32 * (6 + 2) * 2 * pairs * 64
    # required work: one forward and its backward, whatever is recomputed
    mamba = 2048 * 8512 + 4 * 4352 + 4096 * 2048 + 3 * 2048 * 8192
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert family.matmul_params(cfg) == 9 * mamba + attention + 2048 * 12544
    assert family.flops_per_step(cfg, batch, seq) == pytest.approx(
        4096 * 6 * family.matmul_params(cfg) + 32 * 6 * 2 * pairs * 64 + work["flops"])
    again = dataclasses.replace(cfg, recompute_layers=False)
    assert family.flops_per_step(again, batch, seq) == family.flops_per_step(cfg, batch, seq)
    assert family.facts(cfg, batch, seq)["ssm_scan"] == {
        "heads": 64, "head_dim": 64, "state": 128, "chunk": 256, "positions": 4096}


def test_the_family_draws_the_attention_layers_q_and_k_wider_and_nothing_else():
    """The one departure in the seeded weights (the configuration's
    ``departures``): ``wq`` and ``wk`` of the attention layer times
    ``ATTENTION_SPREAD``; every other leaf is the program's own."""
    family = common.load_family("granite_lm")
    sizes = _sizes()
    cfg = family.build({**sizes, **sizes["rehearsal"]})
    key = jax.random.PRNGKey(3)
    drawn, own = family.init(cfg, key), granite.init_params(cfg, key)
    assert family.ATTENTION_SPREAD == 4.0
    # the one limit that sees the update is this family's own, under the dense cells'
    assert family.LOSS_RTOL == 7e-5 < reference.LOSS_RTOL
    assert [d.split(":")[0] for d in sizes["departures"]] == ["attention"]
    wider = 0
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(drawn), jax.tree_util.tree_leaves(own)
    ):
        name = jax.tree_util.keystr(path)
        if name.endswith("['wq']") or name.endswith("['wk']"):
            assert "[2]" in name, name  # the rehearsal's one attention layer
            np.testing.assert_array_equal(np.asarray(a), 4.0 * np.asarray(b))
            wider += 1
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert wider == 2


def test_the_reference_reads_the_published_keys_and_not_the_programs():
    """Two configurations from files that differ in ONE published factor give
    the reference two answers on the same weights; a configuration ``build``
    did not return is refused."""
    family = common.load_family("granite_lm")
    sizes = _sizes()
    tiny = {**sizes, **sizes["rehearsal"]}
    first = family.build(tiny)
    second = family.build(dict(tiny, residual_multiplier=0.5))
    params = family.init(first, jax.random.PRNGKey(0))
    pool = jnp.stack([_tokens(batch=1, seq=33, seed=s) for s in (1, 2)])
    read = []
    for cfg in (first, second):
        with jax.default_matmul_precision("highest"):
            got = jax.jit(lambda p, b, cfg=cfg: family.reference_train(cfg, p, b))(params, pool)
        read.append(float(got[1][0]))
    assert abs(read[0] - read[1]) > 1e-3 * read[0]
    with pytest.raises(ValueError, match="a configuration that granite_lm.build returned"):
        family.reference_train(dataclasses.replace(first, rms_norm_eps=1e-3), params, pool)


def test_the_five_readers_read_what_the_program_names_and_nothing_else():
    names = ("attn_ssm_ms", "ssm_scan_ms", "ssm_scan_roofline", "layer_recompute_ms", "attn_nope_ms")
    read = {name: common.load_by_name("layer_metrics", name).read for name in names}
    remat = "checkpoint/rematted_computation/"
    paths = {
        "forward": {
            "attn/mamba/proj": 0.010, "attn/mamba/scan": 0.030, "attn/mamba/scan/while/body": 0.004,
            "attn/nope/flash_fwd": 0.004, "attn/nope/qk_rows": 0.002, "mlp": 0.020,
        },
        "backward": {
            remat + "attn/mamba/scan": 0.030, remat + "attn/mamba/scan/while/body": 0.006,
            remat + "attn/mamba/proj": 0.010, remat + "attn/nope/flash_fwd": 0.004,
            remat + "mlp": 0.016,
            "attn/mamba/scan": 0.050, "attn/mamba/out": 0.006, "attn/nope/flash_bwd": 0.010,
        },
    }
    facts = {
        "trace": {"paths_s": paths, "steps": 2},
        "peaks": {"bf16_flops_per_s": 2e14, "hbm_bytes_per_s": 8e11},
        "family": {"ssm_scan_work": {"flops": 1e11, "bytes": 3.2e9, "layers": 9}},
    }
    assert read["ssm_scan_ms"](facts) == pytest.approx(60.0)
    assert read["attn_ssm_ms"](facts) == pytest.approx(73.0)
    assert read["ssm_scan_roofline"](facts) == pytest.approx(100 * 4e-3 / 60e-3)
    assert read["layer_recompute_ms"](facts) == pytest.approx(33.0)
    assert read["attn_nope_ms"](facts) == pytest.approx(10.0)
    assert all(reader(dict(facts, trace=None)) is None for reader in read.values())
    # a program that names no such scope (the parent of PR 58 under another
    # cell's trace, a stack that keeps its activations): nothing, no error
    other = {"trace": {"paths_s": {"forward": {"attn/full/flash_fwd": 0.01, "mlp/moe/experts": 0.01}}, "steps": 2},
             "peaks": facts["peaks"], "family": {"kind_flash": {"full": {"flops": 1.0, "bytes": 1.0}}}}
    assert all(reader(other) is None for reader in read.values())


# ---------------------------------------------------------------------------
# the older configurations
# ---------------------------------------------------------------------------

OLDER = {
    "olmoe": olmoe.tiny_olmoe_config, "mellum2": mellum.tiny_mellum_config,
    "ouro": ouro.tiny_ouro_config, "sdar": sdar.tiny_sdar_config,
    "ling3": ling.tiny_ling_config, "dsv2": dsv2.tiny_dsv2_config,
}


@pytest.mark.parametrize("model", sorted(OLDER))
def test_the_older_configurations_never_meet_the_new_fields(model, monkeypatch):
    """A configuration that says nothing of them takes the path it took:
    its kinds rotate at ``head_dim ** -0.5``, its stream is unscaled, its
    readout its own and its stack kept; and its loss's gradient traces, with
    the scan, the checkpoint of a layer and the scaled stream's arithmetic
    made to fail, to the jaxpr it traces to with them."""
    cfg = OLDER[model]()
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    seq = 32 if cfg.diffusion_block else 33  # a diffusion model's L in whole blocks
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, cfg.vocab_size, jnp.int32)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def traced():
        text = str(jax.make_jaxpr(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens)))(compute))
        return re.sub(r"0x[0-9a-f]+", "0x", text)  # a custom_vjp's functions by address

    text = traced()

    def never(*_, **__):
        raise AssertionError("a model without a state-space layer met its scan")

    monkeypatch.setattr(olmoe, "ssd_scan", never)
    monkeypatch.setattr(olmoe, "mamba2_mixer", never)
    if cfg.passes == 1:  # a looped model's pass is under a checkpoint of its own
        monkeypatch.setattr(jax, "checkpoint", never)
    assert traced() == text
    assert "readout" in params and not cfg.tied_readout and not cfg.recompute_layers
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.logits_scaling) == (1.0, 1.0, 1.0)
    for kind in cfg.kinds:
        assert kind.rotary and kind.softmax_scale is None
        assert not isinstance(kind.mixer, olmoe.Mamba2)


@pytest.mark.parametrize("model", sorted(OLDER))
def test_the_older_configurations_lower_to_the_text_they_had_without_the_names(model, monkeypatch):
    """A stack that is not recomputed a layer meets no policy that reads
    ``STACK_KEPT``'s names, and a name lowers to no operation: with the
    three names taken off their products the gradient step lowers to the
    same text, letter for letter. The looped model runs ``_stack`` INSIDE
    ``_looped``'s checkpoint, whose ``KEPT`` names none of the three: it
    holds its down products between the passes and nothing of theirs."""
    cfg = OLDER[model]()
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    seq = 32 if cfg.diffusion_block else 33
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, cfg.vocab_size, jnp.int32)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def lowered():
        text = jax.jit(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens))).lower(compute).as_text()
        # a private function's number counts the lowering's rules as they
        # run: a name's moves it by one and leaves no operation
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    def residuals():
        return _residuals(lambda p: olmoe.loss_fn(cfg, p, tokens), compute)

    text, held = lowered(), residuals()
    assert not set(olmoe.STACK_KEPT) & set(olmoe.KEPT)
    named = olmoe.checkpoint_name
    met = set()

    def unnamed(x, name):
        met.add(name)
        return x if name in olmoe.STACK_KEPT else named(x, name)

    monkeypatch.setattr(olmoe, "checkpoint_name", unnamed)
    assert lowered() == text
    assert "mixer_out" in met  # the patch is the one the program calls
    assert residuals() == held
    if cfg.passes > 1:
        monkeypatch.undo()
        wide = (cfg.passes, 2, 32, cfg.ff[0])
        down = (cfg.passes, 2, 32, cfg.d_model)
        assert not held[wide] and held[down] >= cfg.n_layers
        monkeypatch.setattr(olmoe, "KEPT", ())
        assert held - residuals() == {down: cfg.n_layers}  # ``mlp_down`` a layer, no more
        monkeypatch.setattr(olmoe, "KEPT", ("mlp_down",) + olmoe.STACK_KEPT)
        assert residuals() - held == {down: cfg.n_layers, wide: 2 * cfg.n_layers}


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


def test_an_aborted_step_and_committed_ones_through_the_manager():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program under the generator's optimizer: a step that aborts
    leaves every leaf as it was - the tied embedding among them - and the
    committed steps' losses are the reference's own training run's."""
    params = _weights()
    batches = jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_granite.train(PUB, LAYERS, p, b))(params, batches)
    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: granite.loss_fn(F32, p, t)))
    lighthouse, collectives, manager = _one_member(state, "granite_test")
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
                assert bool(jnp.array_equal(a, b))
            for i in range(3):
                optimizer.zero_grad()
                loss, grads = grad_fn(state.params, batches[i])
                assert optimizer.step(manager.allreduce(grads).wait())
                losses.append(float(loss))
    finally:
        manager.shutdown()
        collectives.shutdown()
        lighthouse.shutdown()
    np.testing.assert_allclose(losses, np.asarray(want), rtol=LOSS_RTOL_F32)


def test_make_train_step_takes_the_configuration():
    from torchft_tpu.models import make_train_step

    cfg = granite.tiny_granite_config(True)
    params, tokens = _weights(cfg), _tokens()
    tx = optax.adamw(1e-3)
    before = np.asarray(params["embed"])
    new_params, _, loss = make_train_step(cfg, tx, bf16_params=True)(params, tx.init(params), tokens)
    assert np.isfinite(float(loss)) and "readout" not in new_params
    assert not np.array_equal(np.asarray(new_params["embed"]), before)
