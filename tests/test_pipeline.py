"""Pipeline parallelism: GPipe schedule numerics + gradient parity.

The pipelined program must be bit-for-bit a reordering of the sequential
layer stack — same outputs, same grads — with stage weights sharded over
the ``pipe`` axis.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.parallel import make_mesh, shard_pytree
from torchft_tpu.pipeline import pipeline_blocks, stack_blocks, stage_specs


def _mk_blocks(n_layers, d, key):
    ks = jax.random.split(key, n_layers)
    return [
        {
            "w": jax.random.normal(k, (d, d)) * (d ** -0.5),
            "b": jax.random.normal(k, (d,)) * 0.1,
        }
        for k in ks
    ]


def _block_fn(p, x):
    return x + jnp.tanh(x @ p["w"] + p["b"])


def _sequential(blocks, x):
    for p in blocks:
        x = _block_fn(p, x)
    return x


@pytest.mark.parametrize("n_stages,microbatches", [(2, 4), (4, 2), (4, 8)])
def test_pipeline_matches_sequential(n_stages, microbatches):
    d, n_layers = 16, 8
    blocks = _mk_blocks(n_layers, d, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    mesh = make_mesh(
        {"pipe": n_stages}, devices=jax.devices()[:n_stages]
    )
    stacked = stack_blocks(blocks)
    out = pipeline_blocks(
        _block_fn, stacked, x, mesh=mesh, microbatches=microbatches
    )
    ref = _sequential(blocks, x)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_pipeline_grads_match_sequential():
    d, n_layers, n_stages = 8, 4, 4
    blocks = _mk_blocks(n_layers, d, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, d))
    mesh = make_mesh(
        {"pipe": n_stages}, devices=jax.devices()[:n_stages]
    )
    stacked = stack_blocks(blocks)

    def loss_pp(stacked, x):
        return jnp.sum(
            pipeline_blocks(
                _block_fn, stacked, x, mesh=mesh, microbatches=2
            ) ** 2
        )

    def loss_seq(blocks, x):
        return jnp.sum(_sequential(blocks, x) ** 2)

    g_pp = jax.grad(loss_pp)(stacked, x)
    g_seq = stack_blocks(
        [g for g in jax.grad(loss_seq)(blocks, x)]
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(g_pp), jax.tree_util.tree_leaves(g_seq)
    ):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_pipeline_composes_with_dp_and_sharded_stage_weights():
    d, n_layers = 8, 4
    blocks = _mk_blocks(n_layers, d, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    mesh = make_mesh({"data": 2, "pipe": 4})
    stacked = shard_pytree(
        stack_blocks(blocks), stage_specs(stack_blocks(blocks)), mesh
    )
    out = jax.jit(
        functools.partial(
            pipeline_blocks, _block_fn, mesh=mesh, microbatches=2,
            data_axis="data",
        )
    )(stacked, x)
    np.testing.assert_allclose(
        out, _sequential(blocks, x), atol=1e-5, rtol=1e-5
    )


def test_pipeline_under_jit_and_remat():
    d, n_layers, n_stages = 8, 4, 2
    blocks = _mk_blocks(n_layers, d, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, d))
    mesh = make_mesh(
        {"pipe": n_stages}, devices=jax.devices()[:n_stages]
    )
    stacked = stack_blocks(blocks)
    block = jax.checkpoint(_block_fn)

    @jax.jit
    def loss(stacked, x):
        return jnp.sum(
            pipeline_blocks(
                block, stacked, x, mesh=mesh, microbatches=2
            )
        )

    g = jax.grad(loss)(stacked, x)
    assert np.isfinite(
        np.asarray(jax.tree_util.tree_leaves(g)[0])
    ).all()


def test_bad_divisibility_raises():
    d = 8
    blocks = _mk_blocks(3, d, jax.random.PRNGKey(0))
    mesh = make_mesh({"pipe": 2}, devices=jax.devices()[:2])
    x = jnp.ones((4, d))
    with pytest.raises(ValueError, match="divisible"):
        pipeline_blocks(
            _block_fn, stack_blocks(blocks), x, mesh=mesh, microbatches=2
        )
    blocks4 = _mk_blocks(4, d, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="microbatches"):
        pipeline_blocks(
            _block_fn, stack_blocks(blocks4), jnp.ones((3, d)), mesh=mesh,
            microbatches=2,
        )
    # with a data axis the split happens on the PER-SHARD batch: global
    # B=8 divides by 8 microbatches but the per-shard batch of 4 does not
    mesh_dp = make_mesh({"data": 2, "pipe": 4})
    with pytest.raises(ValueError, match="per-shard"):
        pipeline_blocks(
            _block_fn, stack_blocks(blocks4), jnp.ones((8, d)),
            mesh=mesh_dp, microbatches=8, data_axis="data",
        )
