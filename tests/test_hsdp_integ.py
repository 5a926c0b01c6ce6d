"""HSDP composition under faults: intra-group dp x tp sharding composed with
the cross-group fault-tolerance layer, end to end.

The reference proves FSDP composes with the managed replicate dimension
(reference fsdp_test.py:38-74, device_mesh_test.py:25-85). The TPU-native
equivalent proven here: each replica group runs the flagship transformer's
jitted sharded train step on its OWN 4-device mesh (data:2 x model:2 — the
slice's ICI dimensions), while gradients are averaged across groups through
a REAL 2-member host TCP ring (the DCN/replicate dimension), with kill +
heal and the bit-identical-state oracle (reference
manager_integ_test.py:279-282).

Runs on the virtual 8-device CPU platform from conftest.py: group g owns
devices [4g, 4g+4), so both sharded steps execute concurrently in one
process exactly as two slices would. Harness shared with the pp/ep
variants: sharded_integ.py.
"""

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu.models import (
    init_params,
    loss_fn,
    param_sharding_rules,
    tiny_config,
)
from torchft_tpu.parallel import build_grad_step, make_mesh

from sharded_integ import (
    DEVICES_PER_GROUP,
    GroupSetup,
    assert_bitwise_identical,
    run_kill_and_heal,
    run_sharded_groups,
)


def _setup(gid: int) -> GroupSetup:
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = jax.devices()[
        gid * DEVICES_PER_GROUP : (gid + 1) * DEVICES_PER_GROUP
    ]
    mesh = make_mesh({"data": 2, "model": 2}, devices=devices)
    cfg = tiny_config()
    rules = param_sharding_rules(cfg)

    def batch_fn(step: int):
        # Deterministic per-step batch, identical across groups, sharded
        # over the group's data axis.
        rng = np.random.default_rng(7000 + step)
        tokens = rng.integers(0, cfg.vocab_size, size=(4, 32), dtype=np.int32)
        return jax.device_put(
            jnp.asarray(tokens), NamedSharding(mesh, P("data"))
        )

    return GroupSetup(
        devices=devices,
        mesh=mesh,
        rules=rules,
        grad_step=build_grad_step(
            lambda p, b: loss_fn(cfg, p, b), mesh, rules
        ),
        fresh_params=lambda: init_params(cfg, jax.random.PRNGKey(42)),
        batch_fn=batch_fn,
    )


class TestHSDPUnderFaults:
    def test_sharded_groups_stay_identical(self):
        results = run_sharded_groups("hsdp", _setup, num_steps=4)
        for r in results:
            assert r["manager_state"]["step"] == 4
        assert_bitwise_identical(results)

    def test_sharded_group_kill_and_heal(self):
        run_kill_and_heal("hsdp", _setup)

    def test_zero_sharded_groups_stay_identical(self):
        # Per-step ZeRO engine: reduce-scattered grads (q8 wire), ~1/W
        # optimizer shard, bf16 param allgather — composed with the
        # intra-group dp x tp sharding.
        results = run_sharded_groups(
            "hsdp", _setup, num_steps=4, engine="zero"
        )
        for r in results:
            assert r["manager_state"]["step"] == 4
        assert_bitwise_identical(results)

    def test_zero_sharded_group_kill_and_heal(self):
        # The heal carries the optimizer shard (donor's shard + meta);
        # the rejoin's quorum bump forces the cohort-wide re-partition.
        run_kill_and_heal("hsdp", _setup, engine="zero")
