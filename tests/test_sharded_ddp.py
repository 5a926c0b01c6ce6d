"""Per-step ZeRO (ShardedDDP) tests.

The claims the engine makes, pinned as oracles:

- sharded-vs-unsharded BIT-identity on the f32 wire (W=2,3,5, striped
  plans): reduce-scatter + shard-local optimizer + allgather produces the
  very same bytes as the fused plan allreduce + full-size update, because
  the sharded plan reuses the fused plan's ring sums and f32 divide and
  the optimizer arithmetic is elementwise;
- on lossy wires (bf16/q8 grad leg, bf16 param leg) every member still
  holds IDENTICAL params (the cohort-determinism oracle) that track the
  exact trajectory closely;
- the memory claim: each member's optimizer state covers ~1/W of the
  model and the cohort's shards tile it exactly, with the resident bytes
  published through ``report_opt_state_bytes``;
- membership changes re-partition the optimizer state through the
  quorum-id-keyed mask-allgather — surviving members' momentum carries,
  a departed member's positions restart at zero (replayed against a full
  host-side oracle);
- a heal voids the shard meta so the restored member re-shards the
  donor's shard at its next step;
- ``ShardedOptimizerWrapper`` is the same transaction behind the
  OptimizerWrapper loop shape.

All over a REAL HostCollectives ring with the deterministic ring-manager
fake (fixed quorum, always-commit) — the join-timing nondeterminism a
live lighthouse adds would break bit-equality oracles.
"""

import threading
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from typing import Any, Dict

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from torchft_tpu import FTTrainState, ShardedDDP, ShardedOptimizerWrapper
from torchft_tpu._native import Store
from torchft_tpu.collectives import HostCollectives, ReduceOp
from torchft_tpu.metrics import Metrics
from torchft_tpu.parallel import build_shard_apply_step


def _ring(store, world_size, prefix, stripes=1):
    cols = [
        HostCollectives(timeout=timedelta(seconds=15), stripes=stripes)
        for _ in range(world_size)
    ]
    addr = f"{store.address()}/{prefix}"
    with ThreadPoolExecutor(max_workers=world_size) as ex:
        for f in [
            ex.submit(cols[r].configure, addr, r, world_size)
            for r in range(world_size)
        ]:
            f.result()
    return cols


def _run_all(cols, fn):
    results = [None] * len(cols)
    errors = []

    def run(r):
        try:
            results[r] = fn(r, cols[r])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=(r,)) for r in range(len(cols))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


class _PlanRingManager:
    """Deterministic manager fake over a REAL HostCollectives ring with
    the sharded-plan surface: full participation, always-commit, fixed
    quorum id — removes join-timing nondeterminism so trajectory oracles
    can demand bit-equality (the test_local_sgd._RingManager pattern)."""

    def __init__(self, col, quorum_id: int = 1):
        self._col = col
        self.qid = quorum_id
        self.commit = True
        self.opt_bytes_reports: list = []
        self._metrics = Metrics()

    def start_quorum(self, **kw):
        pass

    def metrics(self):
        return self._metrics

    def _div(self, op):
        return float(self._col.size()) if op == ReduceOp.AVG else None

    def plan_reduce_scatter(self, tree, op=ReduceOp.AVG, wire=None,
                            ag_wire=None):
        return self._col.plan_reduce_scatter(
            tree, ReduceOp.SUM, divisor=self._div(op), wire=wire,
            ag_wire=ag_wire,
        )

    def plan_allgather_into(self, shard, wire=None):
        return self._col.plan_allgather_into(shard, wire=wire)

    def allgather(self, tree):
        return self._col.allgather(tree)

    def quorum_id(self):
        return self.qid

    def should_commit(self):
        return self.commit

    def report_error(self, e):
        raise e

    def report_opt_state_bytes(self, nbytes):
        self.opt_bytes_reports.append(int(nbytes))


# Model: two leaves whose total (5003 + 257 = 5260) is not divisible by
# any tested world size, so the stripe partition's remainder handling is
# in play. Dict keys sort under tree-flatten ("b" before "w").
_W_N, _B_N = 5003, 257
_TOTAL = _W_N + _B_N


def _params():
    return {
        "w": jnp.asarray(
            np.linspace(-1.0, 1.0, _W_N, dtype=np.float32)
        ),
        "b": jnp.asarray(
            np.linspace(0.5, 2.0, _B_N, dtype=np.float32)
        ),
    }


def _grads(r, s):
    rng = np.random.default_rng(1000 + 37 * r + s)
    return {
        "w": jnp.asarray(rng.standard_normal(_W_N).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal(_B_N).astype(np.float32)),
    }


def _flat(tree):
    return np.concatenate(
        [
            np.asarray(l).ravel()
            for l in jax.tree_util.tree_leaves(tree)
        ]
    )


def _run_sharded(store, world, prefix, tx, steps, stripes=1,
                 shard_wire=None, param_wire=None):
    cols = _ring(store, world, prefix, stripes)

    def member(r, col):
        st = FTTrainState(_params(), tx, opt_state=())
        m = _PlanRingManager(col)
        ddp = ShardedDDP(
            m, st, grad_fn=None, shard_wire=shard_wire,
            param_wire=param_wire,
        )
        for s in range(steps):
            assert ddp.apply_gradients(_grads(r, s))
        return st, ddp, m

    try:
        return _run_all(cols, member)
    finally:
        for c in cols:
            c.shutdown()


def _run_unsharded_oracle(store, world, prefix, tx, steps, stripes=1):
    """The fused path: plan allreduce (SUM + f32 divide — the identical
    arithmetic the sharded rs leg performs) and a full-size optimizer
    update through the SAME jitted shard-apply program (the full flat
    vector is just a shard of size total). Returns the flat params, one
    per member (all identical by the fused plan's own bit-identity)."""
    cols = _ring(store, world, prefix, stripes)

    def member(r, col):
        params = jnp.asarray(_flat(_params()))
        opt = tx.init(params)
        apply = build_shard_apply_step(tx)
        for s in range(steps):
            avg = col.plan_allreduce(
                _grads(r, s), ReduceOp.SUM, divisor=float(world)
            ).wait()
            params, opt = apply(params, opt, jnp.asarray(_flat(avg)))
        return np.asarray(params)

    try:
        return _run_all(cols, member)
    finally:
        for c in cols:
            c.shutdown()


class TestShardedStepBitIdentity:
    @pytest.mark.parametrize(
        "world,stripes", [(2, 1), (2, 4), (3, 1), (5, 2)]
    )
    def test_f32_matches_unsharded_bitwise(self, world, stripes):
        store = Store()
        tx = optax.adam(1e-2)
        try:
            oracle = _run_unsharded_oracle(
                store, world, f"or_{world}_{stripes}", tx, steps=3,
                stripes=stripes,
            )
            res = _run_sharded(
                store, world, f"sh_{world}_{stripes}", tx, steps=3,
                stripes=stripes,
            )
            for st, _, _ in res:
                got = _flat(st.params)
                assert got.tobytes() == oracle[0].tobytes(), (
                    "sharded step diverged bitwise from the fused path"
                )
        finally:
            store.shutdown()

    @pytest.mark.parametrize(
        "shard_wire,param_wire",
        [("bf16", None), ("q8", "bf16"), ("q8", None)],
    )
    def test_lossy_wires_cohort_identical_and_close(
        self, shard_wire, param_wire
    ):
        store = Store()
        tx = optax.adam(1e-2)
        try:
            oracle = _run_unsharded_oracle(
                store, 3, f"orl_{shard_wire}_{param_wire}", tx, steps=3
            )
            res = _run_sharded(
                store, 3, f"shl_{shard_wire}_{param_wire}", tx, steps=3,
                shard_wire=shard_wire, param_wire=param_wire,
            )
            flats = [_flat(st.params) for st, _, _ in res]
            # Determinism oracle: lossy wires, IDENTICAL params anyway
            # (every member adopts the same decoded words).
            for f in flats[1:]:
                assert f.tobytes() == flats[0].tobytes()
            # And they track the exact trajectory.
            np.testing.assert_allclose(
                flats[0], oracle[0], rtol=0.05, atol=0.05
            )
        finally:
            store.shutdown()

    def test_auto_param_wire_is_bf16_iff_q8(self):
        st = FTTrainState(_params(), optax.adam(1e-2), opt_state=())
        assert ShardedDDP(None, st, None, shard_wire="q8")._param_wire \
            == "bf16"
        assert ShardedDDP(None, st, None, shard_wire="bf16")._param_wire \
            is None
        assert ShardedDDP(None, st, None)._param_wire is None

    def test_rejects_non_f32_masters(self):
        st = FTTrainState(
            {"w": jnp.ones((4,), jnp.bfloat16)}, optax.sgd(0.1),
            opt_state=(),
        )
        with pytest.raises(ValueError, match="f32 master"):
            ShardedDDP(None, st, None)


class TestShardedOptimizerState:
    def test_state_is_sharded_and_tiles_the_model(self):
        store = Store()
        try:
            res = _run_sharded(
                store, 3, "mem", optax.adam(1e-2), steps=1
            )
            seen = np.zeros(_TOTAL, np.int32)
            for st, ddp, m in res:
                meta = ddp._shard_meta
                assert meta is not None and meta["quorum_id"] == 1
                ln = 0
                for s, l in meta["ranges"]["float32"]:
                    seen[s: s + l] += 1
                    ln += l
                assert ln < _TOTAL  # strictly smaller than the model
                # adam: mu and nu are shard-sized
                leaves = jax.tree_util.tree_leaves(ddp._opt_shard)
                assert (
                    sum(
                        1 for x in leaves if getattr(x, "size", 0) == ln
                    ) >= 2
                )
                # the resident footprint was published for the policy
                # engine's opt-memory signal
                assert m.opt_bytes_reports
                assert m.opt_bytes_reports[-1] == ddp.opt_state_bytes()
                assert ddp.opt_state_bytes() >= 2 * 4 * ln
            np.testing.assert_array_equal(
                seen, np.ones(_TOTAL, np.int32)
            )
        finally:
            store.shutdown()

    def test_opt_state_bytes_scale_inverse_with_world(self):
        store = Store()
        try:
            per_world = {}
            for world in (2, 3):
                res = _run_sharded(
                    store, world, f"scale{world}", optax.adam(1e-2),
                    steps=1,
                )
                per_world[world] = sum(
                    ddp.opt_state_bytes() for _, ddp, _ in res
                )
            # the cohort TOTAL stays ~constant (the model's 2 moments),
            # so per-member bytes scale ~1/W
            assert per_world[2] == pytest.approx(per_world[3], rel=0.05)
        finally:
            store.shutdown()


class TestReshardOnMembershipChange:
    OPT = dict(learning_rate=0.05, momentum=0.9, nesterov=True)

    def test_survivor_momentum_carries_departed_restarts_zero(self):
        tx = optax.sgd(**self.OPT)
        store = Store()
        try:
            cols3 = _ring(store, 3, "pre")
            states, ddps, mans = [], [], []

            def one_step(r):
                st = FTTrainState(_params(), tx, opt_state=())
                m = _PlanRingManager(cols3[r], quorum_id=1)
                ddp = ShardedDDP(m, st, grad_fn=None)
                assert ddp.apply_gradients(_grads(r, 0))
                return st, ddp, m

            for st, ddp, m in _run_all(
                cols3, lambda r, c: one_step(r)
            ):
                states.append(st)
                ddps.append(ddp)
                mans.append(m)
            params_after1 = _flat(states[0].params)
            # Reassemble the FULL momentum from the three shards (the
            # trace is the only model-sized state leaf of momentum-sgd).
            full_trace = np.zeros(_TOTAL, np.float32)
            for ddp in ddps:
                tr = next(
                    np.asarray(l)
                    for l in jax.tree_util.tree_leaves(ddp._opt_shard)
                    if getattr(l, "size", 0) > 1
                )
                off = 0
                for s, ln in ddp._shard_meta["ranges"]["float32"]:
                    full_trace[s: s + ln] = tr[off: off + ln]
                    off += ln
            # Positions only the departed member (2) owned restart at 0.
            carried = full_trace.copy()
            for s, ln in ddps[2]._shard_meta["ranges"]["float32"]:
                carried[s: s + ln] = 0.0
            for c in cols3:
                c.shutdown()

            # Member 2 departs; survivors re-form at quorum 2.
            cols2 = _ring(store, 2, "post")

            def resync(r, col):
                mans[r]._col = col
                mans[r].qid = 2
                assert ddps[r].apply_gradients(_grads(r, 1))
                return None

            _run_all(cols2, resync)
            for c in cols2:
                c.shutdown()

            # Survivors hold identical params.
            assert _flat(states[0].params).tobytes() == _flat(
                states[1].params
            ).tobytes()
            # Momentum oracle: replay the post-reshard step on the full
            # vector — init state, graft the carried trace, one update
            # through the SAME jitted apply.
            avg_g2 = (
                _flat(_grads(0, 1)) + _flat(_grads(1, 1))
            ) / 2.0
            oracle_opt = tx.init(jnp.asarray(params_after1))
            o_leaves, o_def = jax.tree_util.tree_flatten(oracle_opt)
            o_leaves = [
                jnp.asarray(carried)
                if getattr(l, "size", 0) == _TOTAL
                else l
                for l in o_leaves
            ]
            oracle_opt = jax.tree_util.tree_unflatten(o_def, o_leaves)
            apply = build_shard_apply_step(tx)
            new_full, new_opt = apply(
                jnp.asarray(params_after1), oracle_opt,
                jnp.asarray(avg_g2),
            )
            np.testing.assert_allclose(
                _flat(states[0].params), np.asarray(new_full),
                rtol=1e-6, atol=1e-6,
            )
            oracle_trace = next(
                np.asarray(l)
                for l in jax.tree_util.tree_leaves(new_opt)
                if getattr(l, "size", 0) == _TOTAL
            )
            for r in (0, 1):
                meta = ddps[r]._shard_meta
                assert meta["quorum_id"] == 2  # re-keyed to the new quorum
                tr = next(
                    np.asarray(l)
                    for l in jax.tree_util.tree_leaves(ddps[r]._opt_shard)
                    if getattr(l, "size", 0) > 1
                )
                expect = np.concatenate(
                    [
                        oracle_trace[s: s + ln]
                        for s, ln in meta["ranges"]["float32"]
                    ]
                )
                np.testing.assert_allclose(
                    tr, expect, rtol=1e-6, atol=1e-6
                )
                # the re-partition re-published the resident footprint
                assert len(mans[r].opt_bytes_reports) == 2
        finally:
            store.shutdown()


class TestHealAndCheckpoint:
    def test_state_dict_roundtrip_voids_meta_and_reshards(self):
        tx = optax.adam(1e-2)
        store = Store()
        try:
            # Uninterrupted solo run: 4 steps.
            (ref, _, _), = _run_sharded(store, 1, "ref", tx, steps=4)

            # Interrupted: 2 steps, checkpoint, restore into a FRESH
            # engine, 2 more steps.
            cols = _ring(store, 1, "ckpt")
            st = FTTrainState(_params(), tx, opt_state=())
            m = _PlanRingManager(cols[0])
            ddp = ShardedDDP(m, st, grad_fn=None)
            for s in range(2):
                assert ddp.apply_gradients(_grads(0, s))
            sd = ddp.state_dict()

            st2 = FTTrainState(_params(), tx, opt_state=())
            m2 = _PlanRingManager(cols[0])
            ddp2 = ShardedDDP(m2, st2, grad_fn=None)
            ddp2.load_state_dict(sd)
            # The heal discipline: meta is voided so the next step takes
            # the re-shard path instead of trusting the donor's quorum.
            assert ddp2._shard_meta["quorum_id"] == -1
            assert ddp2._opt_shard is not None
            for s in range(2, 4):
                assert ddp2.apply_gradients(_grads(0, s))
            assert ddp2._shard_meta["quorum_id"] == 1  # re-keyed
            assert m2.opt_bytes_reports  # reshard republished the bytes
            assert _flat(st2.params).tobytes() == _flat(
                ref.params
            ).tobytes()
            for c in cols:
                c.shutdown()
        finally:
            store.shutdown()

    def test_begin_fresh_shard_drops_state(self):
        tx = optax.adam(1e-2)
        store = Store()
        try:
            (st, ddp, _), = _run_sharded(store, 1, "fresh", tx, steps=1)
            assert ddp._opt_shard is not None
            ddp.begin_fresh_shard()
            assert ddp._opt_shard is None
            assert ddp._shard_meta is None
        finally:
            store.shutdown()


class TestShardedOptimizerWrapper:
    def test_wrapper_matches_engine_bitwise(self):
        tx = optax.adam(1e-2)
        store = Store()
        try:
            ref = _run_sharded(store, 2, "eng", tx, steps=3)
            cols = _ring(store, 2, "wrap")

            def member(r, col):
                st = FTTrainState(_params(), tx, opt_state=())
                m = _PlanRingManager(col)
                opt = ShardedOptimizerWrapper(m, st)
                for s in range(3):
                    opt.zero_grad()
                    assert opt.step(_grads(r, s))
                assert opt.last_commit is True
                assert opt.opt_state_bytes() > 0
                return st, opt

            res = _run_all(cols, member)
            for c in cols:
                c.shutdown()
            for (st, _), (ref_st, _, _) in zip(res, ref):
                assert _flat(st.params).tobytes() == _flat(
                    ref_st.params
                ).tobytes()
        finally:
            store.shutdown()

    def test_wrapper_state_dict_delegates(self):
        st = FTTrainState(_params(), optax.adam(1e-2), opt_state=())
        opt = ShardedOptimizerWrapper(None, st, shard_wire="q8")
        sd = opt.state_dict()
        assert set(sd) == {"state", "opt_shard", "shard_meta"}
        opt.load_state_dict(sd)
        assert opt._core._opt_shard is None


class TestAbortKeepsPreStepState:
    def test_failed_commit_rolls_back(self):
        tx = optax.adam(1e-2)
        store = Store()
        try:
            cols = _ring(store, 2, "abort")

            def member(r, col):
                st = FTTrainState(_params(), tx, opt_state=())
                m = _PlanRingManager(col)
                ddp = ShardedDDP(m, st, grad_fn=None)
                assert ddp.apply_gradients(_grads(r, 0))
                p1 = _flat(st.params)
                opt1 = jax.tree_util.tree_map(
                    np.asarray, ddp._opt_shard
                )
                m.commit = False  # the vote fails: discard the step
                assert not ddp.apply_gradients(_grads(r, 1))
                assert ddp.last_commit is False
                # params AND the optimizer shard keep pre-step values
                assert _flat(st.params).tobytes() == p1.tobytes()
                for a, b in zip(
                    jax.tree_util.tree_leaves(opt1),
                    jax.tree_util.tree_leaves(ddp._opt_shard),
                ):
                    assert np.asarray(a).tobytes() == np.asarray(
                        b
                    ).tobytes()
                return None

            _run_all(cols, member)
            for c in cols:
                c.shutdown()
        finally:
            store.shutdown()
