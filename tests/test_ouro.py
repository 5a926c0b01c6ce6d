"""Ouro as a configuration of the sparse family (torchft_tpu.models.ouro over
models/olmoe.py) against its plain reference (benchmark/reference_ouro.py),
at tiny sizes on the CPU, seeded weights: two sandwich-norm layers of 4
heads of 16 and a dense SwiGLU of 96, run four times on the same weights,
an exit a pass, the loss over the four exits.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (flash tiles against a dense masked
softmax, one scan over the passes against a Python loop, the exit
distribution in logarithms against plain products, a custom backward pass
of the cross entropy against autodiff's), so they differ by float32
rounding alone: measured here at 2.4e-7 relative on the loss, 2.5e-6 of its
largest entry on the worst gradient leaf and 1e-6 of the largest logit. The
loss is held to 1e-5, every gradient leaf to 1e-4, the logits to 1e-5 of
the largest and an exit probability to 1e-5: some ten to a hundred times
what was measured, and far under what the smallest wrong term costs
(``test_a_wrong_term_is_caught``). In bf16 (the configuration's precision:
a bf16 copy of the f32 weights, f32 accumulation) the tiny model's loss is
a mean over only 192 positions: held to 4e-4 and 1e-2 on the gradient norm
(``tests/test_olmoe.py``'s bounds).
"""

import collections
import contextlib
import dataclasses
import functools
import json
import os
import re
from datetime import timedelta

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import reference, reference_ouro
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.models import olmoe, ouro, transformer

BF16 = ouro.tiny_ouro_config()
F32 = dataclasses.replace(BF16, dtype=jnp.float32)
LOSS_RTOL_F32, GRAD_RTOL_F32, LOGIT_RTOL_F32, PROB_ATOL_F32 = 1e-5, 1e-4, 1e-5, 1e-5
LOSS_RTOL_BF16, GRAD_NORM_RTOL_BF16 = 4e-4, 1e-2


def _passes(t, cfg=F32):
    return dataclasses.replace(cfg, passes=t)


def _weights(cfg=F32, seed=0):
    return ouro.init_params(cfg, jax.random.PRNGKey(seed))


def _tokens(cfg=F32, batch=3, seq=65, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference_ouro.loss(cfg, p, tokens))(params)


def _program(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: ouro.loss_fn(cfg, p, tokens))(params)


def _norm(tree):
    return float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(tree)
    )))


def _leaf_errors(got, want):
    """(path, largest error over the largest entry) of every leaf."""
    return [
        (jax.tree_util.keystr(path), float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
        )
    ]


# ---------------------------------------------------------------------------
# the float32 program is the reference's mathematics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("passes", [4, 1])
def test_f32_loss_and_gradients_match_the_reference(passes, seed):
    """The loss and the gradient of EVERY leaf: the stack's matrices, the
    four norms of each layer, the final norm, the gate, the embedding and
    the readout (one pass has one exit and no gate to learn)."""
    cfg = _passes(passes)
    params, tokens = _weights(cfg, seed), _tokens(seed=seed + 1)
    loss, grads = _program(cfg, params, tokens)
    ref_loss, ref_grads = _reference(cfg, params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL_F32 * float(ref_loss)
    paths = [path for path, _ in _leaf_errors(grads, ref_grads)]
    assert any("ln1_post" in p for p in paths) and any("ln2_post" in p for p in paths)
    assert any("exit_gate" in p for p in paths) == (passes > 1)
    for path, err in _leaf_errors(grads, ref_grads):
        assert err <= GRAD_RTOL_F32, (path, err)


@pytest.mark.parametrize("passes", [4, 1])
def test_f32_exits_match_the_reference(passes):
    """Every exit's logits at every position, and where the exit mass lies."""
    cfg = _passes(passes)
    params, inputs = _weights(cfg), _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        logits, sums = ouro.forward(cfg, params, inputs)
        # the reference runs a sequence at a time: (B, T, S, ..) -> (T, B, S, ..)
        hs = [reference_ouro.passes(cfg, params, sequence) for sequence in inputs]
        want = jnp.stack([jnp.stack(h) @ params["readout"] for h in hs]).swapaxes(0, 1)
        probs = jnp.stack(
            [jnp.stack(reference_ouro.exit_probs(cfg, params, h)) for h in hs]
        ).swapaxes(0, 1)
    if passes == 1:  # the plain model's forward: one exit, no axis for it
        assert logits.shape == want.shape[1:] and sums is None
        logits = logits[None]
    else:
        np.testing.assert_allclose(
            sums["exit_probs"], jnp.mean(probs, axis=(1, 2)), rtol=0, atol=PROB_ATOL_F32
        )
    assert logits.dtype == jnp.float32
    for t in range(passes):
        err = float(jnp.max(jnp.abs(logits[t] - want[t])) / jnp.max(jnp.abs(want[t])))
        assert err <= LOGIT_RTOL_F32, (t, err)
    np.testing.assert_allclose(jnp.sum(probs, axis=0), 1.0, rtol=0, atol=1e-6)


def test_bf16_path_matches_the_reference_at_what_bf16_earns():
    params, tokens = _weights(), _tokens()
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.value_and_grad(lambda p: ouro.loss_fn(BF16, p, tokens))(compute)
    assert all(g.dtype == jnp.bfloat16 for g in jax.tree_util.tree_leaves(grads))
    ref_loss, ref_grads = _reference(BF16, params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL_BF16 * float(ref_loss)
    assert abs(_norm(grads) - _norm(ref_grads)) <= GRAD_NORM_RTOL_BF16 * _norm(ref_grads)


# ---------------------------------------------------------------------------
# what the tolerances catch: each fault planted in the PROGRAM
# ---------------------------------------------------------------------------


def _faulty(wrong, cfg, params, monkeypatch):
    """(configuration, weights) of a program with the fault planted."""
    if wrong == "three_passes_for_four":
        return _passes(3, cfg), params
    if wrong == "second_norm_left_out":
        return dataclasses.replace(cfg, sandwich_norms=False), params
    if wrong == "qk_norm_applied":
        blocks = [
            dict(b, attn=dict(
                b["attn"], q_norm=jnp.ones(cfg.d_model), k_norm=jnp.ones(cfg.d_model)
            ))
            for b in params["blocks"]
        ]
        return dataclasses.replace(cfg, qk_norm=True), dict(params, blocks=blocks)
    if wrong == "no_entropy_term":
        return dataclasses.replace(cfg, exit_entropy_coef=0.0), params
    if wrong == "last_gate_asked":
        monkeypatch.setattr(olmoe, "exit_log_probs", _every_gate_asked)
        return cfg, params
    raise ValueError(wrong)


def _every_gate_asked(gate_logits):
    """A distribution that asks the last exit's gate too: it sums to less
    than 1."""
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)
    return jnp.concatenate([jnp.zeros_like(stayed[:1]), stayed[:-1]]) + jax.nn.log_sigmoid(gate_logits)


@pytest.mark.parametrize("wrong", [
    "three_passes_for_four", "second_norm_left_out", "qk_norm_applied",
    "no_entropy_term", "last_gate_asked",
])
def test_a_wrong_term_is_caught(wrong, monkeypatch):
    """Each of these is a plausible mistake; the bound the float32 loss is
    held to must not let it through (ten times over)."""
    params, tokens = _weights(), _tokens()
    with jax.default_matmul_precision("highest"):
        ref_loss = float(reference_ouro.loss(F32, params, tokens))
        cfg, given = _faulty(wrong, F32, params, monkeypatch)
        loss = float(ouro.loss_fn(cfg, given, tokens))
    assert abs(loss - ref_loss) > 10 * LOSS_RTOL_F32 * ref_loss


def test_the_final_norm_is_carried_into_the_next_pass():
    """``h_t = RMSNorm_f(Stack(h_{t-1}))``: scaling the final norm's scale
    changes what the SECOND pass sees, so exit 2's logits move by more than
    the scale alone would move them; exit 1's move by the scale alone."""
    params, inputs = _weights(), _tokens()[:, :-1]
    doubled = dict(params, ln_f={"scale": 2.0 * params["ln_f"]["scale"]})
    with jax.default_matmul_precision("highest"):
        logits, _ = ouro.forward(F32, params, inputs)
        moved, _ = ouro.forward(F32, doubled, inputs)
    np.testing.assert_allclose(moved[0], 2.0 * logits[0], rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(moved[1] - 2.0 * logits[1]))) > 1e-2 * float(jnp.max(jnp.abs(logits[1])))


# ---------------------------------------------------------------------------
# the loop is tied to the model
# ---------------------------------------------------------------------------


def _unrolled_loss(cfg, params, copies, tokens, nll_of=transformer.next_token_losses):
    """The looped model's loss with pass t run on ``copies[t]`` of the
    stack: a Python loop over the program's own pieces, no scan and no
    checkpoint, every exit's logits and cross entropy (``nll_of``) left to
    autodiff."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    h = olmoe._embed(cfg, params, inputs)
    gates, nll, total = [], [], None
    for blocks in copies:
        u, stats = olmoe._stack(cfg, blocks, h)
        if stats is not None:
            total = stats if total is None else jax.tree_util.tree_map(jnp.add, total, stats)
        h = transformer._rmsnorm(u, params["ln_f"]["scale"], cfg.rms_norm_eps)
        gates.append(h.astype(jnp.float32) @ params["exit_gate"]["w"] + params["exit_gate"]["b"])
        nll.append(nll_of(h @ params["readout"].astype(cfg.dtype), targets))
    log_p = olmoe.exit_log_probs(jnp.stack(gates))
    p = jnp.exp(log_p)
    loss = jnp.mean(jnp.sum(p * jnp.stack(nll) - cfg.exit_entropy_coef * -p * log_p, axis=0))
    if total is not None:
        balance, z = olmoe.aux_losses(cfg, total, inputs.size)
        loss = loss + cfg.balance_coef * balance + cfg.z_coef * z
    return loss


LOOPED_EXPERTS = dataclasses.replace(
    olmoe.tiny_olmoe_config(), dtype=jnp.float32, passes=2, exit_entropy_coef=0.05
)


@pytest.mark.parametrize("cfg", [F32, LOOPED_EXPERTS], ids=["ouro", "looped_experts"])
def test_the_shared_stacks_gradient_is_the_sum_over_the_passes_copies(cfg):
    """Give each of the T passes its own copy of the stack: the loss is the
    same, and the copies' gradients add up to the shared stack's."""
    params, tokens = olmoe.init_params(cfg, jax.random.PRNGKey(0)), _tokens(cfg)
    copies = [params["blocks"]] * cfg.passes
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: olmoe.loss_fn(cfg, p, tokens))(params)
        unrolled, by_copy = jax.value_and_grad(
            lambda c: _unrolled_loss(cfg, params, c, tokens)
        )(copies)
    assert abs(float(loss) - float(unrolled)) <= LOSS_RTOL_F32 * float(unrolled)
    summed = jax.tree_util.tree_map(lambda *g: sum(g), *by_copy)
    for path, err in _leaf_errors(grads["blocks"], summed):
        assert err <= GRAD_RTOL_F32, (path, err)
    # and no copy's share is nothing: every pass reaches the loss
    assert all(_norm(g) > 1e-3 * _norm(summed) for g in by_copy)


def test_one_pass_is_the_plain_next_token_loss():
    """T = 1: one exit holds all of the mass, there is no gate, and the
    loss is the plain model's mean cross entropy."""
    cfg = _passes(1)
    params, tokens = _weights(cfg), _tokens()
    assert "exit_gate" not in params
    with jax.default_matmul_precision("highest"):
        logits, _ = ouro.forward(cfg, params, tokens[:, :-1])
        loss = ouro.loss_fn(cfg, params, tokens)
    want = transformer.next_token_loss(logits, tokens[:, 1:])
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)


def test_the_exit_distribution_sums_to_one_at_every_position():
    logits = 4.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 3, 64), jnp.float32)
    p = jnp.exp(olmoe.exit_log_probs(logits))
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=0, atol=1e-6)
    g = jax.nn.sigmoid(logits)
    np.testing.assert_allclose(p[1], g[1] * (1 - g[0]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(p[3], (1 - g[0]) * (1 - g[1]) * (1 - g[2]), rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("bias, exit_", [(-40.0, 3), (40.0, 0)])
def test_a_driven_gate_puts_the_loss_on_one_exit(bias, exit_):
    """A gate driven to 0 never lets a position leave early: the loss is
    the LAST exit's cross entropy alone. Driven to 1, the first's. Finite,
    loss and gradients, at either end."""
    params, tokens = _weights(), _tokens()
    driven = dict(params, exit_gate={"w": 0.0 * params["exit_gate"]["w"], "b": jnp.float32(bias)})
    with jax.default_matmul_precision("highest"):
        logits, sums = ouro.forward(F32, driven, tokens[:, :-1])
        loss, grads = jax.value_and_grad(lambda p: ouro.loss_fn(F32, p, tokens))(driven)
    want = transformer.next_token_loss(logits[exit_], tokens[:, 1:])
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    assert float(sums["exit_probs"][exit_]) > 1.0 - 1e-6
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))


@contextlib.contextmanager
def _loop_body_under(how, seen=None):
    """``_looped``'s body as the program has it (``kept``: the checkpoint
    with ``olmoe.KEPT`` as its save policy), under the checkpoint without
    a policy (``no_policy``: ``h_t`` alone is kept) or under none
    (``none``). ``seen`` collects the options the program asked for."""
    real = jax.checkpoint

    def checkpoint(f, **options):
        if seen is not None:
            seen.append(options)
        if how == "none":
            return f
        if how == "no_policy":
            options = dict(options, policy=None)
        return real(f, **options)

    olmoe.jax.checkpoint = checkpoint
    try:
        yield
    finally:
        olmoe.jax.checkpoint = real


LOOPED = {"ouro": F32, "looped_experts": LOOPED_EXPERTS}
WAYS = ("none", "no_policy", "kept")


@functools.lru_cache(maxsize=None)
def _loss_and_gradients(model, how):
    cfg = LOOPED[model]
    params, tokens = _weights(cfg), _tokens(cfg)
    seen = []
    with _loop_body_under(how, seen):
        out = jax.jit(jax.value_and_grad(lambda p: olmoe.loss_fn(cfg, p, tokens)))(params)
    (options,) = seen  # the body was under the checkpoint the module describes, and no other
    assert set(options) == {"prevent_cse", "policy"}
    assert options["prevent_cse"] is False and options["policy"] is not None
    return out


@pytest.mark.parametrize("model", list(LOOPED))
@pytest.mark.parametrize("how", WAYS)
def test_recomputation_changes_no_value(how, model):
    """The loop's body under no ``jax.checkpoint``, under the checkpoint
    that keeps ``h_t`` alone, or under the one that keeps what ``KEPT``
    names: the same loss and the same gradient of every leaf, to the last
    bit - a recomputed pass is the same operations on the same values, and
    a kept value is the one the forward scan computed. Each way is held
    to the next one round, so the three agree."""
    loss, grads = _loss_and_gradients(model, how)
    other_loss, other_grads = _loss_and_gradients(model, WAYS[(WAYS.index(how) + 1) % len(WAYS)])
    assert float(loss) == float(other_loss)
    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(other_grads)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _subjaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jax.extend.core.Jaxpr):
                yield v


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the programs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _subjaxprs(eqn):
            yield from _equations(inner)


def _gradient_and_its_scans(cfg, how="kept"):
    """The jaxpr of the loss's gradient as the program runs it, and its two
    scans over the passes, forward and backward."""
    params, tokens = _weights(cfg), _tokens(cfg)
    with _loop_body_under(how):
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens)))(params)
    scans = [e for e in _equations(jaxpr.jaxpr) if e.primitive.name == "scan"]
    assert [(e.params["length"], e.params["reverse"]) for e in scans] == [
        (cfg.passes, False), (cfg.passes, True)
    ]
    return jaxpr.jaxpr, scans


def _scans_of_the_gradient(cfg, how):
    """(forward, backward): of each of the gradient's two scans over the
    passes, how often its body holds each primitive, and the shape and
    type of every output it stacks over the passes."""
    _, scans = _gradient_and_its_scans(cfg, how)
    return [
        (
            collections.Counter(
                e.primitive.name for e in _equations(scan.params["jaxpr"].jaxpr)
            ),
            collections.Counter(
                (v.aval.shape, str(v.aval.dtype)) for v in scan.outvars[scan.params["num_carry"]:]
            ),
        )
        for scan in scans
    ]


def test_the_kept_product_leaves_the_recomputed_pass_and_nothing_else_does():
    """The mechanism ENGAGES: against the checkpoint without a policy the
    backward scan's body multiplies one matrix fewer a dense layer (the
    down product is not computed again), and the forward scan stacks
    exactly one more (T, B, S, d) array in ``cfg.dtype`` a dense layer -
    the kept product - and nothing else. And it stays inside its fence:
    the flash kernel is STILL in the recomputed pass, 3 calls a layer over
    the two bodies, the count ``benchmark/families/ouro_lm.py`` pins."""
    from benchmark.families import ouro_lm

    cfg, tokens = BF16, _tokens()
    dense = sum(width is not None for width in cfg.ff)
    assert olmoe.KEPT == ("mlp_down",) and dense == cfg.n_layers == 2
    (fwd, stacked), (bwd, none) = _scans_of_the_gradient(cfg, "kept")
    (plain_fwd, plain_stacked), (plain_bwd, _) = _scans_of_the_gradient(cfg, "no_policy")
    assert plain_bwd["dot_general"] - bwd["dot_general"] == dense
    assert fwd["dot_general"] == plain_fwd["dot_general"]  # the pass itself is whole
    kept = (cfg.passes, tokens.shape[0], tokens.shape[1] - 1, cfg.d_model), "bfloat16"
    assert stacked - plain_stacked == collections.Counter({kept: dense})
    assert not plain_stacked - stacked and not none
    calls = fwd["pallas_call"], bwd["pallas_call"]
    assert calls == (plain_fwd["pallas_call"], plain_bwd["pallas_call"])
    assert calls == (cfg.n_layers, 2 * cfg.n_layers)
    assert sum(calls) == ouro_lm.lowered_mosaic_calls(cfg)


def _readout_products(jaxpr, cfg):
    """``dot_general``s of ``jaxpr`` (nested programs included) with the
    readout's (D, V) among their operands or as their result."""
    width = (cfg.d_model, cfg.vocab_size)
    return sum(
        e.primitive.name == "dot_general"
        and any(v.aval.shape == width for v in (*e.invars, *e.outvars))
        for e in _equations(jaxpr)
    )


@pytest.mark.parametrize("model", list(LOOPED) + ["ouro_bf16"])
def test_an_exits_logits_are_multiplied_once_a_step(model):
    """The mechanism ENGAGES, in every looped model's gradient: NEITHER scan
    over the passes holds a product with the readout - where the readout
    ran inside the checkpointed pass, the forward body held the logits'
    product and the backward body held it AGAIN beside the two cotangents' -
    and the whole gradient holds three an exit, after the loop: the logits,
    ``h_t``'s cotangent, the readout's own. Outside differentiation the
    head is the logits' product alone, and the forward scan stacks one
    (T, B, S, D) array for it, the passes' ``h_t``, and no logits."""
    cfg = dict(LOOPED, ouro_bf16=BF16)[model]
    gradient, (forward, backward) = _gradient_and_its_scans(cfg)
    assert _readout_products(forward.params["jaxpr"].jaxpr, cfg) == 0
    assert _readout_products(backward.params["jaxpr"].jaxpr, cfg) == 0
    assert _readout_products(gradient, cfg) == 3 * cfg.passes
    loss = jax.make_jaxpr(lambda p: olmoe.loss_fn(cfg, p, _tokens(cfg)))(_weights(cfg))
    assert _readout_products(loss.jaxpr, cfg) == cfg.passes
    batch, seq = _tokens(cfg).shape
    stacked = [v.aval.shape for v in forward.outvars[forward.params["num_carry"]:]]
    assert (cfg.passes, batch, seq - 1, cfg.d_model) in stacked
    assert not any(shape[-1] == cfg.vocab_size for shape in stacked)


def _plain_cross_entropies(logits, targets):
    """Each position's cross entropy with nothing of its own: a log-softmax
    in float32, autodiff's backward pass."""
    log_q = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(log_q, targets[..., None], axis=-1)[..., 0]


def _written_out(cfg, tokens, nll_of, scale=1.0):
    """``scale`` x the objective ``mean(sum_t p_t CE_t - beta H(p))`` as a
    function of the weights: the passes unrolled on the one stack, no scan,
    no checkpoint, no head of its own."""
    return lambda p: scale * _unrolled_loss(cfg, p, [p["blocks"]] * cfg.passes, tokens, nll_of)


@pytest.mark.parametrize("bias", [None, -40.0, 40.0], ids=["learned", "driven_to_0", "driven_to_1"])
@pytest.mark.parametrize("coef", [0.0, 0.05])
def test_the_exits_head_is_plain_autodiff_of_the_written_out_objective(coef, bias):
    """``_exits_nll`` computes its cotangents in its forward rule, from the
    weight ``p_t / (B S)`` and not from what arrives; held, in float32, to
    autodiff of the objective written out with a plain log-softmax: the
    loss, and the gradient of EVERY leaf (the readout, the stack, the
    gate's ``w`` and ``b`` - which the head reaches only through its
    weight's cotangent -, the final norm, the embedding), with and without
    the entropy term, with a gate driven to either end (an exit's weight
    0 at every position), and times 3: the scalar that arrives is applied.
    Measured 1.1e-7 on the loss (the sum is taken in another order) and
    2.0e-6 of a leaf's largest entry."""
    cfg = dataclasses.replace(F32, exit_entropy_coef=coef)
    params, tokens = _weights(cfg), _tokens()
    if bias is not None:
        params = dict(params, exit_gate={"w": 0.0 * params["exit_gate"]["w"], "b": jnp.float32(bias)})
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: 3.0 * olmoe.loss_fn(cfg, p, tokens))(params)
        want, want_grads = jax.value_and_grad(
            _written_out(cfg, tokens, _plain_cross_entropies, 3.0)
        )(params)
        once = olmoe.loss_fn(cfg, params, tokens)  # outside differentiation: the sum alone
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    assert abs(3.0 * float(once) - float(loss)) <= 1e-6 * float(loss)
    assert set(grads) == {"embed", "blocks", "ln_f", "exit_gate", "readout"}
    for path, err in _leaf_errors(grads, want_grads):
        assert err <= GRAD_RTOL_F32 / 10, (path, err)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_exits_head_in_bf16_rounds_where_autodiff_rounds(seed):
    """The configuration's precision: the head's logits in bf16 and their
    cotangent rounded to bf16 as ``next_token_losses``' backward pass rounds
    it, so against autodiff of the unrolled objective (both compiled) the
    loss is float32 rounding away (measured 5e-7), the gradient's norm
    4e-5 and no leaf further than one bf16 step of its largest entry
    (measured 0.9%: 2 ** -7). The readout's gradient is the float32 sum of
    the exits', rounded once, like the stack's."""
    params, tokens = _weights(BF16, seed), _tokens(seed=seed + 1)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: olmoe.loss_fn(BF16, p, tokens)))(compute)
    want, want_grads = jax.jit(jax.value_and_grad(
        _written_out(BF16, tokens, transformer.next_token_losses)
    ))(compute)
    assert all(g.dtype == jnp.bfloat16 for g in jax.tree_util.tree_leaves(grads))
    assert abs(float(loss) - float(want)) <= LOSS_RTOL_BF16 / 10 * float(want)
    assert abs(_norm(grads) - _norm(want_grads)) <= GRAD_NORM_RTOL_BF16 / 10 * _norm(want_grads)
    widened = lambda tree: jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), tree)
    for path, err in _leaf_errors(widened(grads), widened(want_grads)):
        assert err <= 2.0 ** -6, (path, err)


def test_a_looped_expert_layer_names_nothing_and_keeps_nothing():
    """A layer with experts marks no value: the looped expert model's two
    scans are what they are under the checkpoint without a policy."""
    assert _scans_of_the_gradient(LOOPED_EXPERTS, "kept") == _scans_of_the_gradient(
        LOOPED_EXPERTS, "no_policy"
    )


@pytest.mark.parametrize("model", ["olmoe", "mellum2", "ouro_one_pass"])
def test_without_a_loop_a_name_lowers_to_nothing(model, monkeypatch):
    """T = 1: there is no checkpoint, and the lowered text of the loss's
    gradient is the text with ``checkpoint_name`` patched to the identity -
    the sparse cells (experts in every layer: the mixer's output is the one
    name met) and a plain dense decoder (the SwiGLU's three are met too, and
    each lowers to no operation) pay nothing for what a looped pass or a
    stack recomputed a layer keeps."""
    from torchft_tpu.models import mellum

    cfg = {
        "olmoe": olmoe.tiny_olmoe_config(), "mellum2": mellum.tiny_mellum_config(),
        "ouro_one_pass": _passes(1, BF16),
    }[model]
    params, tokens = _weights(cfg), _tokens(cfg)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def lowered():
        return jax.jit(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens))).lower(compute).as_text()

    text = lowered()
    named = []
    monkeypatch.setattr(olmoe, "checkpoint_name", lambda x, name: named.append(name) or x)
    again = lowered()
    assert named == [
        name for width in cfg.ff
        for name in ("mixer_out", *(("mlp_gate", "mlp_up", "mlp_down") if width is not None else ()))
    ]
    # a name met moves the numbers the private functions after it carry
    text, again = (re.sub(r"(@\w+?)_\d+\b", r"\1", t) for t in (text, again))
    assert again == text


def test_a_bf16_copys_gradient_is_summed_over_the_passes_in_float32():
    """The scan closes over the stack's weights widened to float32, so the
    T passes' gradients are added in float32 and rounded to bf16 once: the
    bf16 gradient is the ROUNDED float32 sum of the passes' bf16 gradients,
    not a sum rounded after every addition. Both sides are compiled to
    round every bf16 value where it is written: left to keep a fusion's
    intermediate in float32 (the compiler's default), two programs that add
    ``h_t``'s three cotangents in one order round them in different places -
    the unrolled form takes the readout's from the product, the program from
    the array the exits' head stacked - and that noise is as large as what
    is measured here."""
    cfg = BF16
    params, tokens = _weights(cfg), _tokens()
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def rounded_as_written(f, x):
        return jax.jit(f).lower(x).compile({"xla_allow_excess_precision": False})(x)

    grads = rounded_as_written(jax.grad(lambda p: ouro.loss_fn(cfg, p, tokens)), compute)
    by_copy = rounded_as_written(
        jax.grad(lambda c: _unrolled_loss(cfg, compute, c, tokens)),
        [compute["blocks"]] * cfg.passes,
    )
    in_f32 = jax.tree_util.tree_map(
        lambda *g: sum(x.astype(jnp.float32) for x in g).astype(jnp.bfloat16), *by_copy
    )
    in_bf16 = jax.tree_util.tree_map(lambda *g: sum(g[1:], g[0]), *by_copy)
    w = lambda tree: np.asarray(tree[0]["mlp"]["w_down"].astype(jnp.float32))
    got = w(grads["blocks"])
    # closer to the float32 sum than the bf16 running sum is
    assert np.abs(got - w(in_f32)).mean() < 0.5 * np.abs(w(in_bf16) - w(in_f32)).mean()


# ---------------------------------------------------------------------------
# the shared loss path, and the family's other variations
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_cross_entropy_a_position_averages_to_next_token_loss(dtype):
    """``next_token_losses`` is ``next_token_loss`` before its mean: the
    same value when averaged, the same gradient of the logits, in the
    logits' own type."""
    logits = (3.0 * jax.random.normal(jax.random.PRNGKey(0), (2, 33, 256))).astype(dtype)
    targets = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 256, jnp.int32)
    each = transformer.next_token_losses(logits, targets)
    assert each.shape == targets.shape and each.dtype == jnp.float32
    mean, want_grad = jax.value_and_grad(transformer.next_token_loss)(logits, targets)
    assert abs(float(jnp.mean(each)) - float(mean)) <= 1e-6 * float(mean)
    grad = jax.grad(lambda l: jnp.mean(transformer.next_token_losses(l, targets)))(logits)
    assert grad.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(grad, np.float32), np.asarray(want_grad, np.float32), rtol=1e-5, atol=1e-9
    )
    # and weighted, a position's cotangent scales that position's row alone
    weights = jax.random.uniform(jax.random.PRNGKey(2), targets.shape)
    weighted = jax.grad(
        lambda l: jnp.sum(weights * transformer.next_token_losses(l, targets))
    )(logits.astype(jnp.float32))
    plain = jax.grad(
        lambda l: jnp.sum(transformer.next_token_losses(l, targets))
    )(logits.astype(jnp.float32))
    np.testing.assert_allclose(weighted, plain * weights[..., None], rtol=1e-5, atol=1e-9)


def test_the_variations_default_to_olmoes_form():
    """An OLMoE configuration's tree and loss are what they were: experts
    in every layer, QK-norm over the whole projection, two norms a layer,
    no gate; a dense layer beside an expert layer counts once in the
    routers' means."""
    cfg = dataclasses.replace(olmoe.tiny_olmoe_config(), dtype=jnp.float32)
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params) == {"embed", "blocks", "ln_f", "readout"}
    assert set(params["blocks"][0]) == {"ln1", "attn", "ln2", "moe"}
    assert set(params["blocks"][0]["attn"]) == {"wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    mixed = dataclasses.replace(cfg, dense_ff=(48, None))
    assert (mixed.expert_layers, cfg.expert_layers) == (1, 2)
    given = olmoe.init_params(mixed, jax.random.PRNGKey(0))
    assert set(given["blocks"][0]) == {"ln1", "attn", "ln2", "mlp"}
    assert given["blocks"][0]["mlp"]["w_down"].shape == (48, 64)
    # the expert layer's weights are the same draw as in the all-expert model
    np.testing.assert_array_equal(given["blocks"][1]["moe"]["router"], params["blocks"][1]["moe"]["router"])
    tokens = _tokens(cfg)
    x = olmoe._embed(mixed, given, tokens[:, :-1])
    x, none = olmoe._block(mixed, given["blocks"][0], x, width=48)
    x, stats = olmoe._block(mixed, given["blocks"][1], x)
    assert none is None
    balance, z = olmoe.aux_losses(mixed, stats, tokens[:, :-1].size)
    want = transformer.next_token_loss(
        olmoe._readout_product(mixed, given, x), tokens[:, 1:]
    ) + mixed.balance_coef * balance + mixed.z_coef * z
    got = olmoe.loss_fn(mixed, given, tokens)
    assert abs(float(got) - float(want)) <= 1e-6 * float(want)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, dense_ff=(48,))


def test_the_published_configuration_is_the_model_it_says():
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "ouro-2.6b-l6.json"
    )
    with open(path) as f:
        sizes = json.load(f)
    cfg = ouro.ouro_config(sizes, sizes["assumed"]["exit_entropy_coef"])
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2048, 16, 16, 128)
    assert cfg.ff == (5632,) * 6 and cfg.passes == 4 and cfg.vocab_size == 49152
    assert not cfg.qk_norm and cfg.sandwich_norms and cfg.expert_layers == 0
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-6 and cfg.exit_entropy_coef == 0.05
    shapes = jax.eval_shape(lambda: ouro.init_params(cfg, jax.random.PRNGKey(0)))
    count = sum(l.size for l in jax.tree_util.tree_leaves(shapes))
    assert count == 6 * 51_388_416 + 201_326_592 + 2048 + 2049


# ---------------------------------------------------------------------------
# through the step transaction
# ---------------------------------------------------------------------------


def test_three_adamw_steps_through_optimizer_wrapper_match_the_reference():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program: its first three losses are the reference's own
    training run's (plain AdamW written out), every step committed."""
    params, batches = _weights(), jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_ouro.train(F32, p, b))(params, batches)

    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: ouro.loss_fn(F32, p, t)))
    lighthouse = Lighthouse(bind="[::]:0", min_replicas=1)
    collectives = HostCollectives(timeout=timedelta(seconds=30))
    manager = Manager(
        collectives=collectives, load_state_dict=state.load_state_dict,
        state_dict=state.state_dict, min_replica_size=1,
        timeout=timedelta(seconds=30), quorum_timeout=timedelta(seconds=60),
        lighthouse_addr=lighthouse.address(), replica_id="ouro_test",
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
        assert manager.current_step() == 3
    finally:
        manager.shutdown()
        collectives.shutdown()
        lighthouse.shutdown()
    np.testing.assert_allclose(losses, want, rtol=2e-5)


def test_make_train_step_takes_the_configuration():
    """``models.make_train_step`` (the raw loop's fused step) serves Ouro
    as it serves OLMoE: one loss for the family."""
    from torchft_tpu.models import make_train_step

    tokens, tx, params = _tokens(), optax.adamw(1e-3), _weights(BF16)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    # both compiled: an eager bf16 pass rounds in other places than a fused one
    want = float(jax.jit(lambda p, t: ouro.loss_fn(BF16, p, t))(compute, tokens))
    _, _, loss = make_train_step(BF16, tx, bf16_params=True)(params, tx.init(params), tokens)
    assert abs(float(loss) - want) <= LOSS_RTOL_BF16 * want
