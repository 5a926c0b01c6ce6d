"""The OLMoE family (torchft_tpu.models.olmoe) against its plain reference
(benchmark/reference_olmoe.py), at tiny sizes on the CPU, seeded weights.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (a sort and grouped matmuls against
every expert on every token; a fused softmax against one written out), so
they differ by float32 rounding alone: measured here at 8e-8 relative on
the loss and 1.2e-6 of its largest entry on the worst gradient leaf. The
loss is held to 1e-5 and every gradient leaf to 1e-4: about a hundred
times what was measured, and a hundred times under what the
smallest wrong term costs (``test_a_wrong_term_is_caught``: dropping the
z-loss moves the loss by 1.1e-3, the first-choice balance loss by 1.9e-3,
renormalised weights by 3.2e-3, the wrong rotary pairing by 6.7e-3). In
bf16 (the configuration's precision: a bf16 copy of the f32 weights, f32
accumulation) each rounding is 2^-9 and the tiny model's loss is a mean
over only 192 positions: measured 3.8e-5 on the loss and 1.2e-3 on the
gradient norm, held to 4e-4 and 1e-2, some ten times that; the same four
wrong terms cost 1.1e-3 to 7.0e-3 there, each over twice the bound.
"""

import dataclasses
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import reference, reference_olmoe
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.checkpointing import CheckpointServer
from torchft_tpu.models import olmoe, tiny_moe_config, tiny_olmoe_config

F32 = dataclasses.replace(tiny_olmoe_config(), dtype=jnp.float32)
LOSS_RTOL_F32, GRAD_RTOL_F32 = 1e-5, 1e-4
LOSS_RTOL_BF16, GRAD_NORM_RTOL_BF16 = 4e-4, 1e-2


def _weights(cfg=F32, seed=0):
    return olmoe.init_params(cfg, jax.random.PRNGKey(seed))


def _tokens(cfg=F32, batch=3, seq=65, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: reference_olmoe.loss(cfg, p, tokens)
        )(params)


def _program(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: olmoe.loss_fn(cfg, p, tokens))(params)


def _norm(tree):
    return float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(tree)
    )))


def _assert_grads_close(got, want, rtol):
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(got), jax.tree_util.tree_leaves(want)
    ):
        err = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        assert err <= rtol, (jax.tree_util.keystr(path), err)


# ---------------------------------------------------------------------------
# the float32 program is the reference's mathematics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_loss_and_gradients_match_the_reference(seed):
    cfg = F32
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 1)
    loss, grads = _program(cfg, params, tokens)
    ref_loss, ref_grads = _reference(cfg, params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL_F32 * float(ref_loss)
    _assert_grads_close(grads, ref_grads, GRAD_RTOL_F32)


def test_f32_forward_matches_the_reference_layer_by_layer():
    """The logits, not only their mean: one layer's attention and MoE
    outputs for every position."""
    cfg = dataclasses.replace(F32, n_layers=1)
    params, tokens = _weights(cfg), _tokens()[:, :-1]
    with jax.default_matmul_precision("highest"):
        logits, _ = olmoe.forward(cfg, params, tokens)
        blk = params["blocks"][0]
        x = params["embed"][tokens]
        eps = cfg.rms_norm_eps
        h = reference_olmoe._rmsnorm(x, blk["ln1"]["scale"], eps)
        x = x + jnp.stack([reference_olmoe._attention(cfg, s, blk["attn"]) for s in h])
        h = reference_olmoe._rmsnorm(x, blk["ln2"]["scale"], eps)
        y, _ = reference_olmoe._moe(cfg, h.reshape(-1, cfg.d_model), blk["moe"])
        x = x + y.reshape(x.shape)
        want = reference_olmoe._rmsnorm(x, params["ln_f"]["scale"], eps) @ params["readout"]
    np.testing.assert_allclose(logits, want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))


# ---------------------------------------------------------------------------
# the configuration's precision, and what the tolerances catch
# ---------------------------------------------------------------------------


def _bf16_program(cfg, params, tokens):
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    return jax.value_and_grad(lambda p: olmoe.loss_fn(cfg, p, tokens))(compute)


def test_bf16_path_matches_the_reference_at_what_bf16_earns():
    cfg = tiny_olmoe_config()
    params, tokens = _weights(), _tokens()
    loss, grads = _bf16_program(cfg, params, tokens)
    ref_loss, ref_grads = _reference(cfg, params, tokens)
    assert abs(float(loss) - float(ref_loss)) <= LOSS_RTOL_BF16 * float(ref_loss)
    assert abs(_norm(grads) - _norm(ref_grads)) <= GRAD_NORM_RTOL_BF16 * _norm(ref_grads)


def _no_z_loss(cfg, params, tokens):
    return olmoe.loss_fn(dataclasses.replace(cfg, z_coef=0.0), params, tokens)


def _renormalised_top_k(cfg, params, tokens, monkeypatch):
    top_k = jax.lax.top_k

    def renormalising(x, k):
        values, index = top_k(x, k)
        return values / jnp.sum(values, axis=-1, keepdims=True), index

    monkeypatch.setattr(jax.lax, "top_k", renormalising)
    return olmoe.loss_fn(cfg, params, tokens)


def _wrong_half_rotated(cfg, params, tokens, monkeypatch):
    """Pairs (2i, 2i + 1), the interleaved form, instead of (i, i + half):
    the pass ``attention`` runs, given every head's lanes (and the norm's
    scale with them) in the interleaved order and its rows put back."""
    right = olmoe._heads_to_rows

    def interleaved(spec, x, scale, tables):
        if tables is None:  # a value projection turns nothing
            return right(spec, x, scale, tables)
        B, S, width = x.shape
        half = width // spec.heads // 2
        mix = jnp.stack([jnp.arange(half), jnp.arange(half) + half], -1).reshape(-1)
        unmix = jnp.argsort(mix)

        def lanes(t, order):  # the lanes of every head of (..., heads x dh)
            return t.reshape(*t.shape[:-1], -1, 2 * half)[..., order].reshape(t.shape)

        if scale is not None:
            scale = scale[unmix] if spec.per_head else lanes(scale, unmix)
        return right(spec, lanes(x, unmix), scale, tables)[..., mix]

    monkeypatch.setattr(olmoe, "_heads_to_rows", interleaved)
    return olmoe.loss_fn(cfg, params, tokens)


def _top_1_balance_loss(cfg, params, tokens, monkeypatch):
    """moe.py's balance loss: the first choice only."""
    right = olmoe.aux_losses

    def top_1(cfg, stats, n):
        balance, z = right(cfg, stats, n)
        return balance / cfg.experts_per_token, z

    monkeypatch.setattr(olmoe, "aux_losses", top_1)
    return olmoe.loss_fn(cfg, params, tokens)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize(
    "wrong", ["no_z_loss", "renormalised_top_k", "wrong_half_rotated", "top_1_balance_loss"],
)
def test_a_wrong_term_is_caught(wrong, precision, monkeypatch):
    """Each of these is a plausible mistake; the loss tolerance of the two
    tests above must not let it through, in either precision."""
    cfg = F32 if precision == "f32" else tiny_olmoe_config()
    params, tokens = _weights(), _tokens()
    ref_loss, _ = _reference(cfg, params, tokens)
    compute = params if precision == "f32" else jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16), params
    )
    args = () if wrong == "no_z_loss" else (monkeypatch,)
    with jax.default_matmul_precision("highest"):
        loss = globals()["_" + wrong](cfg, compute, tokens, *args)
    rtol = LOSS_RTOL_F32 if precision == "f32" else LOSS_RTOL_BF16
    assert abs(float(loss) - float(ref_loss)) > 2 * rtol * float(ref_loss)


# ---------------------------------------------------------------------------
# the mechanisms, one by one
# ---------------------------------------------------------------------------


def test_dropless_when_every_token_picks_the_same_experts():
    """A router biased so that every token picks experts 0 and 1: the
    dropless layer still equals the reference, where the capacity path of
    ``moe.moe_layer`` drops most of the claims."""
    cfg = dataclasses.replace(F32, n_layers=1)
    params = _weights(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model), jnp.float32)
    x = x.at[..., 0].set(30.0)  # one large coordinate every token shares
    router = 0.01 * params["blocks"][0]["moe"]["router"]
    params["blocks"][0]["moe"]["router"] = router.at[0, :2].set(1.0)
    with jax.default_matmul_precision("highest"):
        y, stats = olmoe.moe_layer(cfg, params["blocks"][0]["moe"], x)
        want, _ = reference_olmoe._moe(
            cfg, x.reshape(-1, cfg.d_model), params["blocks"][0]["moe"]
        )
    n = x.shape[0] * x.shape[1]
    assert stats["claims"][:2].tolist() == [n, n] and float(stats["claims"][2:].sum()) == 0
    np.testing.assert_allclose(y.reshape(n, -1), want, rtol=0, atol=1e-5 * float(jnp.max(jnp.abs(want))))
    # the capacity dispatch, given the same collapse, drops claims: 64
    # tokens x 2 claims on 2 experts are over its capacity
    assert tiny_moe_config().capacity(n) < n


def test_the_router_chooses_in_float32():
    """The bf16 configuration's router takes its top-K in float32: on
    bf16 activations it picks, for every token, the experts a float64
    top-K over the same inputs picks (compared by each expert's count of
    claims, which one swapped claim changes). Logits rounded to bf16 pick
    others for some of these tokens, so the comparison would see a router
    that rounds. No loss limit on the chip can hold this (PERF.md section
    6, PR 26: a bf16 router reads ``correct`` there); this test does."""
    cfg = tiny_olmoe_config()
    assert cfg.dtype == jnp.bfloat16
    p = _weights()["blocks"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (8, 64, cfg.d_model)).astype(cfg.dtype)
    _, stats = olmoe.moe_layer(cfg, p, x)

    def counts(logits):
        top = np.argsort(-logits, axis=-1, kind="stable")[:, :cfg.experts_per_token]
        return np.bincount(top.ravel(), minlength=cfg.n_experts)

    tokens = np.asarray(x.astype(jnp.float32), np.float64).reshape(-1, cfg.d_model)
    logits = tokens @ np.asarray(p["router"], np.float64)
    np.testing.assert_array_equal(np.asarray(stats["claims"]), counts(logits))
    rounded = np.asarray(jnp.asarray(logits, cfg.dtype).astype(jnp.float32))
    assert (counts(rounded) != counts(logits)).any()


def test_rope_depends_on_relative_position_only():
    """<rope(q)[i], rope(k)[j]> is a function of i - j: shifting both by
    the same number of positions leaves every score where it was."""
    key_q, key_k = jax.random.split(jax.random.PRNGKey(4))
    q = jax.random.normal(key_q, (1, 1, 2, 16), jnp.float32)
    k = jax.random.normal(key_k, (1, 1, 2, 16), jnp.float32)
    s = 24
    rq = olmoe.rope(jnp.broadcast_to(q, (1, s, 2, 16)), 10000.0)
    rk = olmoe.rope(jnp.broadcast_to(k, (1, s, 2, 16)), 10000.0)
    scores = jnp.einsum("bqhd,bkhd->hqk", rq, rk)
    for shift in (1, 5):
        np.testing.assert_allclose(
            scores[:, shift:, shift:], scores[:, :-shift, :-shift], atol=1e-4
        )
    # position 0 is not rotated, and the rotation keeps lengths
    np.testing.assert_allclose(rq[0, 0], q[0, 0], atol=1e-6)
    np.testing.assert_allclose(
        jnp.linalg.norm(rq, axis=-1), jnp.linalg.norm(q, axis=-1) * jnp.ones((1, s, 2)), rtol=1e-5
    )
    # rotate-half pairing: coordinate i pairs with i + 8, not with i + 1
    e0 = jnp.zeros((1, 2, 1, 16)).at[..., 0].set(1.0)
    assert float(jnp.abs(olmoe.rope(e0, 10000.0)[0, 1, 0, 8])) > 0.5
    assert float(jnp.abs(olmoe.rope(e0, 10000.0)[0, 1, 0, 1])) == 0.0


def test_qk_norm_is_over_the_whole_projection():
    """Scaling ONE head's slice of the q projection changes the other
    heads' normed values (a per-head norm would leave them alone), and
    scaling the whole projection changes nothing."""
    cfg = dataclasses.replace(F32, n_layers=1)
    p = _weights(cfg)["blocks"][0]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, cfg.d_model), jnp.float32)
    base = olmoe.attention(cfg, p, x)
    whole = olmoe.attention(cfg, dict(p, wq=3.0 * p["wq"]), x)
    np.testing.assert_allclose(whole, base, rtol=0, atol=1e-5)  # eps apart
    one_head = p["wq"].at[:, :cfg.head_dim].multiply(3.0)
    moved = olmoe.attention(cfg, dict(p, wq=one_head), x)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-3
    # each of q and k has its own scale
    assert p["q_norm"].shape == p["k_norm"].shape == (cfg.d_model,)
    other = olmoe.attention(cfg, dict(p, k_norm=2.0 * p["k_norm"]), x)
    assert float(jnp.max(jnp.abs(other - base))) > 1e-3


# ---------------------------------------------------------------------------
# through the step transaction
# ---------------------------------------------------------------------------


def test_three_adamw_steps_through_optimizer_wrapper_match_the_reference():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program: its first three losses are the reference's own
    training run's (plain AdamW written out), so the model, its gradient
    and the update each agree."""
    cfg = F32
    params, batches = _weights(), jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_olmoe.train(cfg, p, b))(params, batches)

    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: olmoe.loss_fn(cfg, p, t)))
    lighthouse = Lighthouse(bind="[::]:0", min_replicas=1)
    collectives = HostCollectives(timeout=timedelta(seconds=30))
    manager = Manager(
        collectives=collectives, load_state_dict=state.load_state_dict,
        state_dict=state.state_dict, min_replica_size=1,
        timeout=timedelta(seconds=30), quorum_timeout=timedelta(seconds=60),
        lighthouse_addr=lighthouse.address(), replica_id="olmoe_test",
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
    tx = optax.adamw(1e-3)
    state = FTTrainState(_weights(), tx)
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
    # and the healed state trains on: one more update runs
    healed.apply_gradients(grads)


def test_make_train_step_finds_the_familys_loss():
    """``models.make_train_step`` (the raw loop's fused step) serves the
    OLMoE configuration: its loss is ``olmoe.loss_fn``'s."""
    from torchft_tpu.models import make_train_step

    cfg, tokens = tiny_olmoe_config(), _tokens()
    tx = optax.adamw(1e-3)
    params = _weights()
    want = float(_bf16_program(cfg, params, tokens)[0])
    _, _, loss = make_train_step(cfg, tx, bf16_params=True)(params, tx.init(params), tokens)
    # the fused program and the eager one round bf16 in other places
    assert abs(float(loss) - want) <= LOSS_RTOL_BF16 * want


def test_warm_compiles_without_copying_or_touching_the_state():
    """``FTTrainState.warm`` compiles the update ahead of time: the live
    state keeps its buffers and its values, and the first real update
    finds the executable."""
    state = FTTrainState(_weights(), optax.adamw(1e-3))
    before = jax.tree_util.tree_leaves(state.state_dict())
    _, grads = _program(F32, state.params, _tokens())
    state.warm(grads)
    after = jax.tree_util.tree_leaves(state.state_dict())
    assert all(a is b for a, b in zip(before, after))
    state.apply_gradients(grads)
    assert not any(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(state.params), jax.tree_util.tree_leaves(_weights()))
        if a.ndim > 1
    )
