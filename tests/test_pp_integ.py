"""Pipeline parallelism composed with the fault-tolerance layer, end to
end: each replica group runs the flagship blocks GPipe-pipelined over its
OWN {data:2, pipe:2} mesh, gradients average across groups through a REAL
2-member host TCP ring, with kill + heal and the bit-identical oracle.

Same claim as test_hsdp_integ (reference analog fsdp_test.py:38-74) with
the intra-group dimension being the pipeline instead of tp.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models.transformer import (
    _block,
    embed_tokens,
    init_params,
    next_token_loss,
    readout,
    tiny_config,
)
from torchft_tpu.parallel import make_mesh
from torchft_tpu.pipeline import pipeline_blocks, stack_blocks, stage_specs

from sharded_integ import (
    DEVICES_PER_GROUP,
    GroupSetup,
    assert_bitwise_identical,
    run_kill_and_heal,
    run_sharded_groups,
)


def _setup(gid: int) -> GroupSetup:
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()[
        gid * DEVICES_PER_GROUP : (gid + 1) * DEVICES_PER_GROUP
    ]
    mesh = make_mesh({"data": 2, "pipe": 2}, devices=devices)
    cfg = tiny_config()  # n_layers=2 -> one layer per stage

    def fresh_params():
        raw = init_params(cfg, jax.random.PRNGKey(42))
        return {
            "backbone": {k: v for k, v in raw.items() if k != "blocks"},
            "stacked": stack_blocks(raw["blocks"]),
        }

    raw = fresh_params()
    rules = {
        "backbone": jax.tree_util.tree_map(lambda _l: P(), raw["backbone"]),
        "stacked": stage_specs(raw["stacked"]),
    }

    def loss_fn(params, tokens):
        x = embed_tokens(cfg, params["backbone"], tokens[:, :-1])
        x = pipeline_blocks(
            functools.partial(_block, cfg),
            params["stacked"],
            x,
            mesh=mesh,
            microbatches=2,
            data_axis="data",
        )
        return next_token_loss(
            readout(cfg, params["backbone"], x), tokens[:, 1:]
        )

    def batch_fn(step: int):
        rng = np.random.default_rng(9000 + step)
        return jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(4, 33), dtype=np.int32)
        )

    return GroupSetup(
        devices=devices,
        mesh=mesh,
        rules=rules,
        grad_step=jax.jit(jax.value_and_grad(loss_fn)),
        fresh_params=fresh_params,
        batch_fn=batch_fn,
        check_subtree="stacked",
    )


class TestPipelineUnderFaults:
    def test_pipelined_groups_stay_identical(self):
        results = run_sharded_groups("pp", _setup, num_steps=4)
        for r in results:
            assert r["manager_state"]["step"] == 4
        assert_bitwise_identical(results)

    def test_pipelined_group_kill_and_heal(self):
        run_kill_and_heal("pp", _setup)

    def test_zero_sharded_groups_stay_identical(self):
        # Per-step ZeRO engine (rs grads, ~1/W opt shard, param ag)
        # composed with the dp x pipe sharding.
        results = run_sharded_groups(
            "pp", _setup, num_steps=4, engine="zero"
        )
        for r in results:
            assert r["manager_state"]["step"] == 4
        assert_bitwise_identical(results)
