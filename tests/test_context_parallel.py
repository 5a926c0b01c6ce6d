"""Ring attention (context parallelism) tests on the virtual 8-device CPU
mesh: numerical equivalence with dense causal attention, differentiability,
and composition with data- and tensor-parallel axes in one mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.context_parallel import ring_attention
from torchft_tpu.parallel import make_mesh


def _dense_causal(q, k, v):
    """Reference: full-materialization causal attention, f32."""
    Dh = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (Dh ** -0.5)
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _qkv(key, B=2, S=32, H=4, Dh=8, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    shape = (B, S, H, Dh)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


class TestRingAttention:
    def test_matches_dense_seq_only(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(0))
        out = ring_attention(q, k, v, mesh=mesh, seq_axis="seq",
                             batch_axis=None)
        ref = _dense_causal(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_dense_dp_x_seq_x_tp(self):
        # The composition claim: batch over "data", sequence ring over
        # "seq", heads over "model" — one mesh, one op.
        mesh = make_mesh({"data": 2, "seq": 2, "model": 2},
                         devices=jax.devices()[:8])
        q, k, v = _qkv(jax.random.PRNGKey(1), B=4, S=16, H=4)
        out = ring_attention(q, k, v, mesh=mesh, seq_axis="seq",
                             batch_axis="data", head_axis="model")
        ref = _dense_causal(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_non_causal(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(2))
        out = ring_attention(q, k, v, mesh=mesh, batch_axis=None,
                             causal=False)
        Dh = q.shape[-1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (Dh ** -0.5)
        ref = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_gradients_flow_through_ring(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(3))

        def loss_ring(qkv):
            out = ring_attention(*qkv, mesh=mesh, batch_axis=None)
            return jnp.sum(out ** 2)

        def loss_dense(qkv):
            return jnp.sum(_dense_causal(*qkv) ** 2)

        g_ring = jax.grad(loss_ring)((q, k, v))
        g_dense = jax.grad(loss_dense)((q, k, v))
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                       rtol=2e-4, atol=2e-4)

    def test_inside_jit(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(4))
        f = jax.jit(lambda a, b, c: ring_attention(
            a, b, c, mesh=mesh, batch_axis=None))
        np.testing.assert_allclose(np.asarray(f(q, k, v)),
                                   np.asarray(_dense_causal(q, k, v)),
                                   rtol=2e-5, atol=2e-5)

    def test_uneven_sequence_rejected(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(5), S=30)
        with pytest.raises(ValueError, match="not divisible"):
            ring_attention(q, k, v, mesh=mesh, batch_axis=None)

    def test_bf16_inputs(self):
        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(6), dtype=jnp.bfloat16)
        out = ring_attention(q, k, v, mesh=mesh, batch_axis=None)
        assert out.dtype == jnp.bfloat16
        ref = _dense_causal(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=0.05, atol=0.05,
        )


class TestUlyssesAttention:
    """Ulysses: all-to-all seq<->heads around full-sequence attention
    (flash or dense per device)."""

    def test_matches_dense_seq_only(self):
        from torchft_tpu.context_parallel import ulysses_attention

        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(0))
        for use_flash in (False, True):
            out = ulysses_attention(
                q, k, v, mesh=mesh, seq_axis="seq", batch_axis=None,
                use_flash=use_flash,
            )
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(_dense_causal(q, k, v)),
                rtol=2e-5, atol=2e-5, err_msg=f"use_flash={use_flash}",
            )

    def test_matches_dense_dp_x_seq_x_tp(self):
        from torchft_tpu.context_parallel import ulysses_attention

        # H=4 over model:2 -> 2 local heads; seq:2 needs 2 | 2 ok
        mesh = make_mesh({"data": 2, "seq": 2, "model": 2},
                         devices=jax.devices()[:8])
        q, k, v = _qkv(jax.random.PRNGKey(1), B=4, S=16, H=4)
        out = ulysses_attention(q, k, v, mesh=mesh, seq_axis="seq",
                                batch_axis="data", head_axis="model")
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_dense_causal(q, k, v)),
            rtol=2e-5, atol=2e-5,
        )

    def test_grads_match_dense(self):
        from torchft_tpu.context_parallel import ulysses_attention

        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        q, k, v = _qkv(jax.random.PRNGKey(3))

        def loss_u(qkv):
            out = ulysses_attention(*qkv, mesh=mesh, batch_axis=None)
            return jnp.sum(out ** 2)

        def loss_dense(qkv):
            return jnp.sum(_dense_causal(*qkv) ** 2)

        g_u = jax.grad(loss_u)((q, k, v))
        g_d = jax.grad(loss_dense)((q, k, v))
        for a, b in zip(g_u, g_d):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)

    def test_too_few_heads_rejected(self):
        from torchft_tpu.context_parallel import ulysses_attention

        mesh = make_mesh({"seq": 8})
        q, k, v = _qkv(jax.random.PRNGKey(5), H=4)  # 4 heads < seq:8
        with pytest.raises(ValueError, match="head count"):
            ulysses_attention(q, k, v, mesh=mesh, batch_axis=None)

    def test_transformer_strategy_switch(self):
        import dataclasses

        from torchft_tpu.models import init_params, loss_fn, tiny_config

        mesh = make_mesh({"seq": 4}, devices=jax.devices()[:4])
        cfg_ring = dataclasses.replace(
            tiny_config(), cp_seq_axis="seq", cp_mesh=mesh,
            cp_batch_axis=None,
        )
        cfg_uly = dataclasses.replace(cfg_ring, cp_strategy="ulysses")
        params = init_params(cfg_ring, jax.random.PRNGKey(0))
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg_ring.vocab_size, (2, 33)),
            jnp.int32,
        )
        l_ring = loss_fn(cfg_ring, params, tokens)
        l_uly = loss_fn(cfg_uly, params, tokens)
        np.testing.assert_allclose(float(l_uly), float(l_ring),
                                   rtol=1e-4, atol=1e-4)
