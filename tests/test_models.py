"""Model + intra-group parallelism tests (8-device virtual CPU mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models import (
    TransformerConfig,
    forward,
    init_params,
    loss_fn,
    param_sharding_rules,
    tiny_config,
)
from torchft_tpu.parallel import (
    build_apply_step,
    build_grad_step,
    make_mesh,
    replicate_pytree,
    shard_pytree,
)


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


class TestTransformer:
    def test_forward_shapes_and_finite(self, cfg, params):
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = forward(cfg, params, tokens)
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert logits.dtype == jnp.float32
        assert bool(jnp.all(jnp.isfinite(logits)))

    def test_loss_decreases_under_sgd(self, cfg, params):
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)),
            jnp.int32,
        )
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        grad_fn = jax.jit(jax.value_and_grad(lambda p, t: loss_fn(cfg, p, t)))
        losses = []
        p = params
        for _ in range(8):
            loss, grads = grad_fn(p, tokens)
            updates, opt_state = tx.update(grads, opt_state, p)
            p = optax.apply_updates(p, updates)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_causality(self, cfg, params):
        # Changing a future token must not affect earlier logits.
        t1 = jnp.zeros((1, 8), jnp.int32)
        t2 = t1.at[0, 7].set(5)
        l1 = forward(cfg, params, t1)
        l2 = forward(cfg, params, t2)
        np.testing.assert_allclose(
            np.asarray(l1[:, :7]), np.asarray(l2[:, :7]), rtol=1e-4, atol=1e-4
        )

    def test_sharding_rules_match_params_structure(self, cfg, params):
        from jax.sharding import PartitionSpec

        rules = param_sharding_rules(cfg)
        td_p = jax.tree_util.tree_structure(params)
        td_r = jax.tree_util.tree_structure(
            rules, is_leaf=lambda l: isinstance(l, PartitionSpec)
        )
        assert td_p == td_r


class TestShardedTraining:
    def test_tp_dp_train_step_on_virtual_mesh(self, cfg):
        assert len(jax.devices()) >= 8
        mesh = make_mesh({"data": 2, "model": 4}, devices=jax.devices()[:8])
        rules = param_sharding_rules(cfg)
        params = shard_pytree(init_params(cfg, jax.random.PRNGKey(0)), rules, mesh)
        tx = optax.adamw(1e-3)
        opt_state = tx.init(params)
        grad_step = build_grad_step(
            lambda p, b: loss_fn(cfg, p, b), mesh, rules
        )
        apply_step = build_apply_step(tx)
        batch = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab_size, (4, 32)),
            jnp.int32,
        )
        loss, grads = grad_step(params, batch)
        params, opt_state = apply_step(params, opt_state, grads)
        assert np.isfinite(float(loss))

    def test_sharded_matches_single_device(self, cfg):
        # TP+DP sharding must not change the math (up to float tolerance).
        tokens = jnp.asarray(
            np.random.default_rng(1).integers(0, cfg.vocab_size, (4, 32)),
            jnp.int32,
        )
        params = init_params(cfg, jax.random.PRNGKey(0))
        expected = float(loss_fn(cfg, params, tokens))

        mesh = make_mesh({"data": 2, "model": 4}, devices=jax.devices()[:8])
        rules = param_sharding_rules(cfg)
        sharded = shard_pytree(params, rules, mesh)
        grad_step = build_grad_step(lambda p, b: loss_fn(cfg, p, b), mesh, rules)
        loss, _ = grad_step(sharded, tokens)
        assert abs(float(loss) - expected) < 5e-2  # bf16 matmul tolerance

    def test_context_parallel_train_step_dp_sp_tp(self, cfg):
        # Full 3D intra-group sharding: batch over "data", sequence ring
        # over "seq" (ring attention), heads over "model" — one jitted
        # step, loss matching the dense single-device model.
        import dataclasses

        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(
            {"data": 2, "seq": 2, "model": 2}, devices=jax.devices()[:8]
        )
        cp_cfg = dataclasses.replace(
            cfg,
            cp_seq_axis="seq",
            cp_mesh=mesh,
            cp_head_axis="model",
        )
        tokens = jnp.asarray(
            np.random.default_rng(2).integers(0, cfg.vocab_size, (4, 33)),
            jnp.int32,
        )
        params = init_params(cfg, jax.random.PRNGKey(0))
        expected = float(loss_fn(cfg, params, tokens))

        rules = param_sharding_rules(cp_cfg)
        sharded = shard_pytree(params, rules, mesh)
        grad_step = build_grad_step(
            lambda p, b: loss_fn(cp_cfg, p, b), mesh, rules,
            batch_spec=P("data"),
        )
        loss, grads = grad_step(sharded, tokens)
        assert abs(float(loss) - expected) < 5e-2  # bf16 matmul tolerance
        for leaf in jax.tree_util.tree_leaves(grads):
            assert bool(jnp.all(jnp.isfinite(leaf)))

    def test_make_mesh_validates_sizes(self):
        with pytest.raises(ValueError):
            make_mesh({"data": 3, "model": 3}, devices=jax.devices()[:8])

    def test_replicate_pytree(self):
        mesh = make_mesh({"data": 8}, devices=jax.devices()[:8])
        tree = {"x": jnp.ones((4, 4))}
        out = replicate_pytree(tree, mesh)
        assert out["x"].sharding.is_fully_replicated


class TestGraftEntry:
    def test_entry_compiles(self):
        import __graft_entry__

        fn, args = __graft_entry__.entry()
        logits = jax.jit(fn)(*args)
        assert logits.shape[0] == args[1].shape[0]

    def test_dryrun_multichip(self):
        import __graft_entry__

        __graft_entry__.dryrun_multichip(8)


def test_remat_policy_prunes_flash_fwd_recompute():
    """The point of save_attn + flash: the backward replay must NOT
    relaunch the forward flash kernel. Counted in the lowered HLO: one
    _fwd_kernel launch per layer with the policy, two without."""
    import dataclasses

    import numpy as np

    from torchft_tpu.models import init_params, loss_fn, tiny_config

    base = dataclasses.replace(tiny_config(), remat=True, use_flash=True)
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, base.vocab_size, (2, 33)),
        jnp.int32,
    )

    def pallas_calls(cfg):
        # jaxpr-level count (the CPU interpret lowering erases kernel
        # names from HLO); jaxpr text dedupes shared sub-jaxprs, so only
        # RELATIVE counts are meaningful. On the TPU lowering the HLO
        # shows exactly 2 fwd launches/layer plain vs 1 with the policy.
        jx = str(
            jax.make_jaxpr(jax.grad(lambda p: loss_fn(cfg, p, tokens)))(
                params
            )
        )
        return jx.count("pallas_call")

    plain = pallas_calls(base)
    saved = pallas_calls(
        dataclasses.replace(base, remat_policy="save_attn")
    )
    assert saved < plain, (saved, plain)


def test_bad_config_knobs_rejected():
    import dataclasses

    import pytest

    from torchft_tpu.models import tiny_config

    with pytest.raises(ValueError, match="cp_strategy"):
        dataclasses.replace(tiny_config(), cp_strategy="Ulysses")
    with pytest.raises(ValueError, match="remat_policy"):
        dataclasses.replace(tiny_config(), remat_policy="save-attn")


def test_remat_policy_save_attn_matches_plain():
    """save_attn remat keeps numerics identical (it only changes what
    backward recomputes) for both dense and flash attention paths."""
    import dataclasses

    import numpy as np

    from torchft_tpu.models import init_params, loss_fn, tiny_config

    base = dataclasses.replace(tiny_config(), remat=True)
    params = init_params(base, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, base.vocab_size, (2, 33)),
        jnp.int32,
    )
    for use_flash in (False, True):
        cfg = dataclasses.replace(base, use_flash=use_flash)
        cfg_pol = dataclasses.replace(cfg, remat_policy="save_attn")
        l_plain = float(loss_fn(cfg, params, tokens))
        l_pol = float(loss_fn(cfg_pol, params, tokens))
        np.testing.assert_allclose(l_pol, l_plain, rtol=1e-5, atol=1e-5)
        g_plain = jax.grad(lambda p: loss_fn(cfg, p, tokens))(params)
        g_pol = jax.grad(lambda p: loss_fn(cfg_pol, p, tokens))(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_pol),
            jax.tree_util.tree_leaves(g_plain),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4,
                err_msg=f"use_flash={use_flash}",
            )


def test_bf16_params_master_copy_train_step():
    """make_train_step(bf16_params=True): the gradient pass reads a bf16
    working copy, the optimizer updates the f32 master — params stay f32,
    the loss trajectory tracks the f32 path closely, and training makes
    progress. VERDICT r3 item 1a (mixed precision with master weights)."""
    import numpy as np
    import optax

    from torchft_tpu.models import init_params, make_train_step, tiny_config

    cfg = tiny_config()
    tx = optax.adamw(1e-2)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 33)),
        jnp.int32,
    )
    losses = {}
    for bf16 in (False, True):
        step = make_train_step(cfg, tx, bf16_params=bf16)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt_state = tx.init(params)
        ls = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens)
            ls.append(float(loss))
        losses[bf16] = ls
        # master stays f32 under the mixed path
        for leaf in jax.tree_util.tree_leaves(params):
            assert leaf.dtype == jnp.float32
        assert ls[-1] < ls[0]
    # same trajectory up to bf16 gradient-accumulation noise
    np.testing.assert_allclose(losses[True], losses[False], rtol=0.05)


def test_train_state_accepts_bf16_wire_grads():
    """FTTrainState.apply_gradients harmonizes lower-precision (wire)
    gradient dtypes with the f32 master before the optax update."""
    import numpy as np
    import optax

    from torchft_tpu.train_state import FTTrainState

    params = {"w": jnp.ones((4, 4), jnp.float32)}
    state = FTTrainState(params, optax.sgd(0.5))
    grads = {"w": jnp.full((4, 4), 0.5, jnp.bfloat16)}
    state.apply_gradients(grads)
    assert state.params["w"].dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(state.params["w"]), 0.75)
