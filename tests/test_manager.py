"""Manager state-machine unit tests with a mocked ManagerClient.

Mirrors the reference's dominant pattern (reference manager_test.py:131-581):
the native client is patched wholesale, QuorumResult objects are fabricated
field by field, and the collectives are fakes — so quorum transitions,
healing, error latching, FIXED_WITH_SPARES numerics and commit votes are
tested without any network or lighthouse.
"""

import json
import time
from concurrent.futures import Future
from datetime import timedelta
from unittest.mock import MagicMock, patch

import numpy as np
import pytest

from tools.graftlint.latch_discipline import MANAGED_OPS
from torchft_tpu._native import QuorumResult, Store, StoreClient
from torchft_tpu.collectives import DummyCollectives, ReduceOp, Work
from torchft_tpu.manager import (
    MANAGER_ADDR_KEY,
    REPLICA_ID_KEY,
    Manager,
    WorldSizeMode,
)


class FailingCollectives(DummyCollectives):
    """Allreduce resolves (or raises) with an error."""

    def __init__(self, immediate: bool, **kwargs) -> None:
        super().__init__(**kwargs)
        self._immediate = immediate

    def allreduce(self, tree, op=ReduceOp.SUM, divisor=None) -> Work:
        self.op_count += 1
        if self._immediate:
            raise RuntimeError("injected immediate failure")
        f: Future = Future()
        f.set_exception(RuntimeError("injected async failure"))
        return Work(f)


def _quorum_result(**overrides) -> QuorumResult:
    defaults = dict(
        quorum_id=1,
        replica_rank=0,
        replica_world_size=2,
        recover_src_manager_address="",
        recover_src_rank=None,
        recover_dst_ranks=[],
        store_address="localhost:0",
        max_step=0,
        max_rank=0,
        max_world_size=2,
        heal=False,
    )
    defaults.update(overrides)
    return QuorumResult(**defaults)


@pytest.fixture(autouse=True)
def mock_manager_client():
    # Patch for the whole test: the healing path constructs a second
    # ManagerClient for the recovery peer from inside the quorum thread.
    with patch("torchft_tpu.manager.ManagerClient") as cls:
        yield cls


@pytest.fixture
def store():
    s = Store()
    client = StoreClient(s.address())
    client.set(MANAGER_ADDR_KEY, b"mock://manager")
    client.set(REPLICA_ID_KEY, b"testrep")
    yield s
    s.shutdown()


def _create_manager(
    store,
    use_async_quorum: bool = True,
    min_replica_size: int = 2,
    world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
    collectives=None,
    timeout: timedelta = timedelta(seconds=10),
    load_state_dict=None,
    state_dict=None,
    transport=None,
    iso_collectives=None,
):
    collectives = collectives if collectives is not None else DummyCollectives()
    transport = transport if transport is not None else MagicMock()
    if not isinstance(transport, MagicMock):
        pass
    else:
        transport.metadata.return_value = "transport:meta"
    import torchft_tpu.manager as manager_mod

    client = manager_mod.ManagerClient.return_value  # the active patch
    manager = Manager(
        collectives=collectives,
        load_state_dict=load_state_dict,
        state_dict=state_dict,
        min_replica_size=min_replica_size,
        use_async_quorum=use_async_quorum,
        world_size_mode=world_size_mode,
        timeout=timeout,
        rank=1,  # not group rank 0: no native server is spawned
        world_size=2,
        store_addr=store.address(),
        checkpoint_transport=transport,
        iso_collectives=iso_collectives,
    )
    return manager, client, collectives, transport


class _UntouchableCollectives(DummyCollectives):
    """A backend whose managed ops record the call and fail: for tests of
    paths that must return before any backend is reached."""

    def __init__(self) -> None:
        super().__init__()
        self.touched = []


def _touch(name):
    def op(self, *args, **kwargs):
        self.touched.append(name)
        raise AssertionError(f"backend op {name} reached")

    return op


for _name in sorted(MANAGED_OPS):  # the lint rule's list: one to extend
    setattr(_UntouchableCollectives, _name, _touch(_name))


class TestManagerState:
    def test_state_dict_roundtrip(self, store):
        m, _, _, _ = _create_manager(store)
        assert m.state_dict() == {"step": 0, "batches_committed": 0}
        m.load_state_dict({"step": 1234, "batches_committed": 2345})
        assert m.current_step() == 1234
        assert m.batches_committed() == 2345
        m.shutdown()

    def test_replica_id_comes_from_store(self, store):
        m, _, _, _ = _create_manager(store)
        assert m._replica_id == "testrep"
        m.shutdown()


class TestQuorumHappyPath:
    def test_step_commit_increments(self, store):
        m, client, col, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True

        m.start_quorum()
        grads = {"w": np.full(4, 6.0, np.float32)}
        out = m.allreduce(grads).wait()
        # Dummy collectives return input; AVG divides by num_participants=2.
        np.testing.assert_array_equal(out["w"], np.full(4, 3.0))
        assert m.should_commit()
        assert m.current_step() == 1
        assert m.batches_committed() == 2
        # local vote was True
        assert client.should_commit.call_args.args[2] is True
        m.shutdown()

    def test_collectives_reconfigured_only_on_quorum_change(self, store):
        m, client, col, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result(quorum_id=7)
        client.should_commit.return_value = True
        m.start_quorum()
        m.wait_quorum()
        assert col.configure_count == 1
        assert m.should_commit()

        m.start_quorum()  # same quorum id: no reconfigure
        m.wait_quorum()
        assert col.configure_count == 1

        client.quorum.return_value = _quorum_result(quorum_id=8)
        m.start_quorum()
        m.wait_quorum()
        assert col.configure_count == 2
        m.shutdown()

    def test_quorum_uses_step_and_metadata(self, store):
        m, client, _, transport = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True
        m.load_state_dict({"step": 5, "batches_committed": 10})
        m.start_quorum()
        m.wait_quorum()
        kwargs = client.quorum.call_args.kwargs
        assert kwargs["rank"] == 1
        assert kwargs["step"] == 5
        assert kwargs["checkpoint_metadata"] == "transport:meta"
        m.shutdown()


class TestHealing:
    def test_sync_quorum_heals_eagerly(self, store):
        loaded = {}
        m, client, _, transport = _create_manager(
            store,
            use_async_quorum=False,
            load_state_dict=lambda sd: loaded.update(sd),
        )
        client.quorum.return_value = _quorum_result(
            quorum_id=2,
            replica_rank=1,
            heal=True,
            max_step=20,
            max_rank=None,
            recover_src_manager_address="mock://peer",
            recover_src_rank=0,
        )
        client.checkpoint_metadata.return_value = "peer:meta"
        transport.recv_checkpoint.return_value = {
            "user": {"model": "weights"},
            "torchft": {"step": 20, "batches_committed": 40},
        }
        client.should_commit.return_value = True

        m.start_quorum()  # sync: heal completes before returning
        assert m.current_step() == 20
        assert loaded == {"model": "weights"}
        # Sync-mode healing participates in the step (replica cohort).
        assert m.is_participating()
        assert m.participating_rank() == 1
        m.shutdown()

    def test_async_quorum_healing_sits_out(self, store):
        loaded = {}
        m, client, col, transport = _create_manager(
            store,
            use_async_quorum=True,
            min_replica_size=1,
            load_state_dict=lambda sd: loaded.update(sd),
        )
        client.quorum.return_value = _quorum_result(
            quorum_id=2,
            replica_rank=1,
            replica_world_size=2,
            heal=True,
            max_step=20,
            max_rank=None,  # not in the max-step cohort
            max_world_size=1,
            recover_src_manager_address="mock://peer",
            recover_src_rank=0,
        )
        client.checkpoint_metadata.return_value = "peer:meta"
        transport.recv_checkpoint.return_value = {
            "user": {"model": "w"},
            "torchft": {"step": 20, "batches_committed": 40},
        }
        client.should_commit.return_value = True

        m.start_quorum()
        grads = {"g": np.full(3, 8.0, np.float32)}
        out = m.allreduce(grads).wait()
        # Healing: contribution zeroed, divided by max-step cohort size (1).
        np.testing.assert_array_equal(out["g"], np.zeros(3))
        assert not m.is_participating()
        assert m.num_participants() == 1

        # User state dict applied at the should_commit safe point.
        assert loaded == {}
        assert m.should_commit()
        assert loaded == {"model": "w"}
        assert m.current_step() == 21
        m.shutdown()

    def test_allgather_zeroes_non_participating_entry(self, store):
        # Same participation discipline as allreduce: a healing replica's
        # allgather entry must arrive zeroed, so entry-wise averages
        # (int8 DiLoCo) divided by num_participants stay correct.
        m, client, col, transport = _create_manager(
            store,
            use_async_quorum=True,
            min_replica_size=1,
        )
        client.quorum.return_value = _quorum_result(
            quorum_id=2,
            replica_rank=1,
            replica_world_size=2,
            heal=True,
            max_step=20,
            max_rank=None,
            max_world_size=1,
            recover_src_manager_address="mock://peer",
            recover_src_rank=0,
        )
        client.checkpoint_metadata.return_value = "peer:meta"
        transport.recv_checkpoint.return_value = {
            "user": {},
            "torchft": {"step": 20, "batches_committed": 40},
        }
        m.start_quorum()
        out = m.allgather({"g": np.full(3, 8.0, np.float32)}).wait()
        assert not m.is_participating()
        assert isinstance(out, list)
        np.testing.assert_array_equal(
            np.asarray(out[0]["g"]), np.zeros(3)
        )
        m.shutdown()

    def test_recovery_source_sends_checkpoint(self, store):
        m, client, _, transport = _create_manager(
            store, state_dict=lambda: {"model": "mine"}
        )
        client.quorum.return_value = _quorum_result(
            quorum_id=3, recover_dst_ranks=[2], max_step=7
        )
        client.should_commit.return_value = True
        m.start_quorum()
        m.wait_quorum()
        call = transport.send_checkpoint.call_args.kwargs
        assert call["dst_ranks"] == [2]
        assert call["step"] == 7
        assert call["state_dict"]["user"] == {"model": "mine"}
        assert call["state_dict"]["torchft"] == {
            "step": 0,
            "batches_committed": 0,
        }
        m.shutdown()


class TestErrorHandling:
    def test_immediate_allreduce_failure_latches(self, store):
        col = FailingCollectives(immediate=True)
        m, client, _, _ = _create_manager(store, collectives=col)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = False
        m.start_quorum()
        grads = {"g": np.ones(2, np.float32)}
        out = m.allreduce(grads).wait()
        np.testing.assert_array_equal(out["g"], np.ones(2))  # input unchanged
        assert m.errored() is not None
        assert not m.should_commit()
        assert client.should_commit.call_args.args[2] is False
        assert m.current_step() == 0
        m.shutdown()

    def test_async_allreduce_failure_swallowed_and_latched(self, store):
        col = FailingCollectives(immediate=False)
        m, client, _, _ = _create_manager(store, collectives=col)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = False
        m.start_quorum()
        grads = {"g": np.full(2, 5.0, np.float32)}
        out = m.allreduce(grads).wait()
        # Default = the (participating, so unzeroed) input tree.
        np.testing.assert_array_equal(out["g"], np.full(2, 5.0))
        assert m.errored() is not None
        assert not m.should_commit()
        m.shutdown()

    @pytest.mark.parametrize(
        "op_name, default",
        [
            ("allreduce", "tree"),
            ("plan_allreduce", None),
            ("allreduce_hier", "tree"),
            ("iso_allreduce", None),
            ("reduce_scatter", None),
            ("allgather_into", None),
            ("plan_reduce_scatter", None),
            ("plan_allgather_into", None),
            ("allgather", "[tree]"),
        ],
    )
    def test_errored_managed_op_is_noop(self, store, op_name, default):
        # An error latched before the op is issued short-circuits every
        # managed op to its documented default without touching either
        # backend: the input tree, None, or [tree].
        col, iso = _UntouchableCollectives(), _UntouchableCollectives()
        m, client, _, _ = _create_manager(
            store, collectives=col, iso_collectives=iso
        )
        client.quorum.return_value = _quorum_result()
        m.start_quorum()
        m.report_error(RuntimeError("user error"))
        tree = {"g": np.ones(1)}
        out = getattr(m, op_name)(tree).wait()
        if default == "tree":
            assert out is tree
        elif default == "[tree]":
            assert len(out) == 1 and out[0] is tree
        else:
            assert out is None
        assert col.touched == [] and iso.touched == []
        m.shutdown()

    def test_error_requests_force_reconfigure(self, store):
        # A latched error leaves the ring sockets shut down (native
        # fail-fast propagation); the next quorum request must carry
        # force_reconfigure so every member rebuilds even when membership
        # is unchanged. The flag is one-shot.
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = False
        m.start_quorum()
        m.wait_quorum()
        assert client.quorum.call_args.kwargs["force_reconfigure"] is False
        m.report_error(RuntimeError("ring failed"))
        m.should_commit()
        m.start_quorum()
        m.wait_quorum()
        assert client.quorum.call_args.kwargs["force_reconfigure"] is True
        m.should_commit()
        m.start_quorum()
        m.wait_quorum()
        assert client.quorum.call_args.kwargs["force_reconfigure"] is False
        m.shutdown()

    def test_error_cleared_by_next_quorum(self, store):
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True
        m.start_quorum()
        m.report_error(RuntimeError("boom"))
        m.should_commit()
        # Local vote was False while errored...
        assert client.should_commit.call_args.args[2] is False
        m.start_quorum()
        m.wait_quorum()
        assert m.errored() is None
        m.should_commit()
        # ...and True again after the next quorum cleared the error.
        assert client.should_commit.call_args.args[2] is True
        m.shutdown()

    def test_healing_applies_state_dict_even_when_errored(self, store):
        # An error latched during a healing step must not skip the apply:
        # the quorum thread already advanced the manager step to max_step,
        # so without the apply the replica would report max_step on stale
        # weights and never be healed again (reference manager.py:575-577).
        loaded = {}
        m, client, _, transport = _create_manager(
            store,
            use_async_quorum=True,
            min_replica_size=1,
            load_state_dict=lambda sd: loaded.update(sd),
        )
        client.quorum.return_value = _quorum_result(
            quorum_id=2,
            replica_rank=1,
            heal=True,
            max_step=20,
            max_rank=None,
            max_world_size=1,
            recover_src_manager_address="mock://peer",
            recover_src_rank=0,
        )
        client.checkpoint_metadata.return_value = "peer:meta"
        transport.recv_checkpoint.return_value = {
            "user": {"model": "recovered"},
            "torchft": {"step": 20, "batches_committed": 40},
        }
        client.should_commit.return_value = False
        m.start_quorum()
        m.wait_quorum()
        m.report_error(RuntimeError("mid-heal failure"))
        assert not m.should_commit()
        # The step aborted, but the recovered weights were still applied —
        # consistent with the advanced manager step.
        assert loaded == {"model": "recovered"}
        assert m.current_step() == 20
        m.shutdown()

    def test_early_error_does_not_skip_heal_apply(self, store):
        # An error latched BEFORE any allreduce (so nothing ever waited on
        # the quorum) must not let should_commit read _healing while the
        # quorum thread is still fetching: the apply would be skipped while
        # the step counter advances to max_step — permanent stale weights.
        import time

        loaded = {}
        m, client, _, transport = _create_manager(
            store,
            use_async_quorum=True,
            min_replica_size=1,
            load_state_dict=lambda sd: loaded.update(sd),
        )

        def slow_quorum(*args, **kwargs):
            time.sleep(0.3)
            return _quorum_result(
                quorum_id=2,
                replica_rank=1,
                heal=True,
                max_step=20,
                max_rank=None,
                max_world_size=1,
                recover_src_manager_address="mock://peer",
                recover_src_rank=0,
            )

        client.quorum.side_effect = slow_quorum
        client.checkpoint_metadata.return_value = "peer:meta"
        transport.recv_checkpoint.return_value = {
            "user": {"model": "recovered"},
            "torchft": {"step": 20, "batches_committed": 40},
        }
        client.should_commit.return_value = False
        m.start_quorum()
        m.report_error(RuntimeError("pre-allreduce failure"))  # no wait_quorum
        assert not m.should_commit()
        assert loaded == {"model": "recovered"}
        assert m.current_step() == 20
        m.shutdown()

    def test_failed_quorum_raises_from_allreduce(self, store):
        # Contract pin: data-plane errors are latched, but a failed quorum
        # RPC raises out of allreduce via wait_quorum (reference
        # manager.py:265).
        m, client, _, _ = _create_manager(store)
        client.quorum.side_effect = TimeoutError("quorum timed out")
        m.start_quorum()
        with pytest.raises(TimeoutError):
            m.allreduce({"g": np.ones(1)})
        m.shutdown()

    def test_stale_work_error_does_not_latch_next_step(self, store):
        # A work abandoned by a fail-fast should_commit that settles with an
        # error AFTER the next start_quorum must not latch into the new step.
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True
        m.start_quorum()
        late: Future = Future()
        m.wrap_work(Work(late), default="fallback")
        m.report_error(RuntimeError("step-N error"))  # triggers fail-fast
        m.should_commit()  # drains; vote value irrelevant here
        m.start_quorum()
        m.wait_quorum()
        assert m.errored() is None
        late.set_exception(RuntimeError("stale step-N work error"))
        import time

        time.sleep(0.1)  # let callbacks run
        assert m.errored() is None  # stale error did not latch
        m.shutdown()

    def test_wrap_work_timeout_returns_default(self, store):
        m, client, _, _ = _create_manager(
            store, timeout=timedelta(milliseconds=100)
        )
        client.quorum.return_value = _quorum_result()
        m.start_quorum()
        never: Future = Future()
        out = m.wrap_work(Work(never), default="fallback").wait(
            timeout=timedelta(seconds=5)
        )
        assert out == "fallback"
        assert isinstance(m.errored(), TimeoutError)
        m.shutdown()


class TestWorldSizeModes:
    def test_fixed_with_spares_clamps(self, store):
        m, client, _, _ = _create_manager(
            store,
            min_replica_size=2,
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        )
        # 3 live replicas, we are the spare (max_rank=2 >= min_replica_size)
        client.quorum.return_value = _quorum_result(
            replica_rank=2, replica_world_size=3, max_rank=2, max_world_size=3
        )
        client.should_commit.return_value = True
        m.start_quorum()
        assert m.num_participants() == 2  # fixed divisor
        assert not m.is_participating()  # spare
        out = m.allreduce({"g": np.full(2, 4.0, np.float32)}).wait()
        np.testing.assert_array_equal(out["g"], np.zeros(2))  # zeroed, /2
        m.shutdown()

    def test_fixed_with_spares_below_min_aborts(self, store):
        # Live cohort BELOW min_replica_size: the divisor must follow the
        # live count (min()-clamped, reference manager.py:459-468) so the
        # enough-replicas vote fails and the step aborts — it must NOT be
        # pinned to min_replica_size (which would commit a lone replica's
        # halved gradient).
        m, client, _, _ = _create_manager(
            store,
            min_replica_size=2,
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        )
        client.quorum.return_value = _quorum_result(
            replica_rank=0, replica_world_size=1, max_rank=0, max_world_size=1
        )
        client.should_commit.return_value = False
        m.start_quorum()
        assert m.num_participants() == 1  # live count, not min_replica_size
        assert not m.should_commit()
        assert client.should_commit.call_args.args[2] is False  # local vote
        assert m.current_step() == 0
        m.shutdown()

    def test_fixed_with_spares_participant(self, store):
        m, client, _, _ = _create_manager(
            store,
            min_replica_size=2,
            world_size_mode=WorldSizeMode.FIXED_WITH_SPARES,
        )
        client.quorum.return_value = _quorum_result(
            replica_rank=1, replica_world_size=3, max_rank=1, max_world_size=3
        )
        m.start_quorum()
        assert m.num_participants() == 2
        assert m.is_participating()
        m.shutdown()


class TestMinReplicaVote:
    def test_below_min_votes_false(self, store):
        m, client, _, _ = _create_manager(store, min_replica_size=2)
        client.quorum.return_value = _quorum_result(
            replica_world_size=1, max_world_size=1
        )
        client.should_commit.return_value = False
        m.start_quorum()
        assert not m.should_commit()
        assert client.should_commit.call_args.args[2] is False
        m.shutdown()


class FailingShardedCollectives(DummyCollectives):
    """reduce_scatter / allgather_into fail (immediately or async)."""

    def __init__(self, immediate: bool, fail_op: str = "reduce_scatter",
                 **kwargs) -> None:
        super().__init__(**kwargs)
        self._immediate = immediate
        self._fail_op = fail_op

    def _fail(self) -> Work:
        if self._immediate:
            raise RuntimeError("injected immediate failure")
        f: Future = Future()
        f.set_exception(RuntimeError("injected async failure"))
        return Work(f)

    def reduce_scatter(self, tree, op=ReduceOp.SUM, divisor=None, wire=None):
        self.op_count += 1
        if self._fail_op == "reduce_scatter":
            return self._fail()
        return super().reduce_scatter(tree, op, divisor=divisor, wire=wire)

    def allgather_into(self, shard, wire=None):
        self.op_count += 1
        if self._fail_op == "allgather_into":
            return self._fail()
        return super().allgather_into(shard, wire=wire)


class TestShardedManagedDispatch:
    """Manager.reduce_scatter / allgather_into: the managed error
    discipline (latch, resolve to the None failure default, discard the
    step through the commit vote) extended to the sharded split ops."""

    @pytest.mark.parametrize("immediate", [True, False])
    @pytest.mark.parametrize("fail_op", ["reduce_scatter", "allgather_into"])
    def test_failure_latches_and_resolves_none(
        self, store, immediate, fail_op
    ):
        col = FailingShardedCollectives(immediate=immediate, fail_op=fail_op)
        m, client, _, _ = _create_manager(store, collectives=col)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = False
        m.start_quorum()
        grads = {"g": np.ones(4, np.float32)}
        if fail_op == "reduce_scatter":
            out = m.reduce_scatter(grads).wait()
        else:
            shard = m.reduce_scatter(grads).wait()
            assert shard is not None
            out = m.allgather_into(shard).wait()
        assert out is None  # failure default: no meaningful partial shard
        assert m.errored() is not None
        assert not m.should_commit()  # step discarded, not half-applied
        assert m.current_step() == 0
        m.shutdown()

    def test_happy_path_roundtrip(self, store):
        m, client, col, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True
        m.start_quorum()
        grads = {"g": np.full(4, 6.0, np.float32)}
        shard = m.reduce_scatter(grads).wait()  # AVG over 2 participants
        assert shard is not None
        np.testing.assert_allclose(
            np.asarray(next(iter(shard.values.values()))), np.full(4, 3.0)
        )
        out = m.allgather_into(shard).wait()
        np.testing.assert_allclose(out["g"], np.full(4, 3.0))
        assert m.errored() is None
        assert m.should_commit()
        m.shutdown()

    def test_allgather_into_does_not_zero_non_participants(self, store):
        # A healing/spare member's param shard is replicated state, not a
        # contribution: zeroing it would corrupt every member's gathered
        # params. The dispatch must pass the shard through untouched.
        m, client, col, _ = _create_manager(store)
        # max_rank=None => this replica is not participating
        client.quorum.return_value = _quorum_result(max_rank=None)
        client.should_commit.return_value = True
        m.start_quorum()
        assert not m.is_participating()
        shard = col.reduce_scatter({"g": np.full(4, 8.0, np.float32)}).wait()
        out = m.allgather_into(shard).wait()
        np.testing.assert_allclose(out["g"], np.full(4, 8.0))
        m.shutdown()

    def test_quorum_id_accessor(self, store):
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result(quorum_id=7)
        m.start_quorum()
        assert m.quorum_id() == 7
        m.shutdown()


@pytest.mark.parametrize(
    "op_name",
    [
        "allreduce",
        "plan_allreduce",
        "allreduce_hier",
        "iso_allreduce",
        "reduce_scatter",
        "plan_reduce_scatter",
    ],
)
def test_reduction_bad_op_raises_eagerly(store, op_name):
    # A static usage error must raise at the call site, not be latched as
    # a cohort data-plane failure (which would force a reconfigure and
    # discard the step).
    col, iso = _UntouchableCollectives(), _UntouchableCollectives()
    m, client, _, _ = _create_manager(
        store, collectives=col, iso_collectives=iso
    )
    client.quorum.return_value = _quorum_result()
    m.start_quorum()
    with pytest.raises(ValueError, match=f"unsupported managed {op_name} op"):
        getattr(m, op_name)({"g": np.ones(2, np.float32)}, op=ReduceOp.MAX)
    assert m.errored() is None
    assert col.touched == [] and iso.touched == []
    m.shutdown()


class TestPolicySignals:
    """The observability surface the policy engine consumes: rolling churn
    rate, measured wire bandwidth, heal-cost breakdown."""

    def test_churn_marks_on_quorum_change_but_not_cold_start(self, store):
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result(quorum_id=7)
        m.start_quorum()
        m.wait_quorum()
        # the FIRST configure is a cold start, not churn
        assert "churn" not in m.metrics().snapshot()["events"]
        assert m.signals()["churn_per_min"] == 0.0

        client.quorum.return_value = _quorum_result(quorum_id=8)
        m.start_quorum()
        m.wait_quorum()
        snap = m.metrics().snapshot()["events"]["churn"]
        assert snap["n"] == 1
        assert m.signals()["churn_per_min"] > 0.0
        m.shutdown()

    def test_observe_op_stats_measures_effective_bandwidth(self, store):
        class StatCollectives(DummyCollectives):
            def pop_op_stats(self):
                return [
                    {
                        "op": "allreduce",
                        "bytes": 8 << 20,
                        "wire_bytes": 4 << 20,
                        "ring": 2.0,
                        "stripe_s": [2.0, 2.0],
                    },
                    {"op": "barrier"},  # no payload: skipped
                ]

        m, client, _, _ = _create_manager(
            store, collectives=StatCollectives()
        )
        drained = m.observe_op_stats()
        assert len(drained) == 2  # pop semantics preserved for callers
        sig = m.signals()
        # 4 MiB over 2 s = 2 MB/s effective
        assert abs(sig["wire_eff_MBps"] - 2.0) < 1e-6
        # the signal is the sink; no rate is filed among the timers
        assert not any(
            "MBps" in name for name in m.metrics().snapshot()["timers_s"]
        )
        m.shutdown()

    def test_the_transport_files_in_the_managers_metrics(self, store):
        """The Manager hands the transport it owns its ``Metrics``: the
        donor's serving threads time into the manager's timers, stamped
        with the manager's step."""
        from torchft_tpu.checkpointing import CheckpointServer

        transport = CheckpointServer(timeout=timedelta(seconds=5))
        own = transport.metrics  # a bare transport has its own
        m, _, _, _ = _create_manager(store, transport=transport)
        try:
            assert transport.metrics is m.metrics() and own is not m.metrics()
            m.metrics().step = 3
            transport.send_checkpoint(
                [1], step=3, state_dict={"w": np.ones(16, np.float32)},
                timeout=timedelta(seconds=5),
            )
            transport.recv_checkpoint(
                0, transport.metadata(), 3, timeout=timedelta(seconds=5)
            )
            snap = m.metrics().snapshot()
            assert snap["timers_s"]["send_stage"]["n"] == 1
            assert snap["counters"]["send_bytes"] == (
                transport.last_fetch_stats["bytes"]
            )
        finally:
            m.shutdown()

    def test_signals_heal_none_until_healed(self, store):
        transport = MagicMock()
        transport.metadata.return_value = "transport:meta"
        transport.last_fetch_stats = None
        m, _, _, _ = _create_manager(store, transport=transport)
        assert m.signals()["heal"] is None
        transport.last_fetch_stats = {
            "path": "stream", "bytes": 123, "fetch_s": 0.5, "h2d_s": 0.1,
        }
        heal = m.signals()["heal"]
        assert heal["last_fetch"]["path"] == "stream"
        m.shutdown()

    def test_control_transaction_skips_batch_accounting(self, store):
        # A policy-engine decision is a committed transaction (the step
        # clock must advance) but trains no batch: batches_committed must
        # not inflate.
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True
        m.start_quorum()
        assert m.should_commit(count_batches=False)
        assert m.current_step() == 1
        assert m.batches_committed() == 0
        m.start_quorum()
        assert m.should_commit()
        assert m.current_step() == 2
        assert m.batches_committed() == 2  # 2 participants, 1 real step
        m.shutdown()

    def test_push_status_is_noop_without_native_manager(self, store):
        # rank != 0 hosts no native manager server; the push must be safe
        m, _, _, _ = _create_manager(store)
        m.push_status({"policy": "ddp"})  # must not raise
        m.shutdown()


class TestDurableArbitration:
    """Restore-time donor/durable arbitration: start_quorum consults the
    durable tier's restore_latest exactly once, and only on a cold fleet
    (no live donor, nothing restored locally)."""

    def _restore_fn(self, m, step):
        calls = []

        def restore():
            calls.append(1)
            m.load_state_dict({"step": step, "batches_committed": step * 2})
            return step

        return restore, calls

    def test_durable_only_cold_fleet_restores(self, store):
        m, client, _, _ = _create_manager(store)
        restore, calls = self._restore_fn(m, 7)
        m.set_durable_restore(restore)
        client.quorum.return_value = _quorum_result(max_step=0)
        client.should_commit.return_value = True
        m.start_quorum()
        m.wait_quorum()
        assert calls == [1]
        assert m.current_step() == 7
        assert m.batches_committed() == 14
        # one-shot: the next quorum never re-consults
        m.start_quorum()
        m.wait_quorum()
        assert calls == [1]
        m.shutdown()

    def test_donor_beats_durable(self, store):
        # A live donor (max_step > 0) wins: the durable fallback is
        # never invoked; the normal heal path owns recovery.
        m, client, _, _ = _create_manager(store)
        restore, calls = self._restore_fn(m, 7)
        m.set_durable_restore(restore)
        client.quorum.return_value = _quorum_result(max_step=5)
        m.start_quorum()
        m.wait_quorum()
        assert calls == []
        assert m.current_step() == 0  # donor state arrives via heal, not here
        m.shutdown()

    def test_trainer_restore_first_disarms(self, store):
        # The pre-arbitration idiom — trainer calls restore_latest()
        # before the first quorum — must keep working: a nonzero local
        # step disarms the consult even when the quorum sees max_step 0.
        m, client, _, _ = _create_manager(store)
        restore, calls = self._restore_fn(m, 7)
        m.set_durable_restore(restore)
        m.load_state_dict({"step": 3, "batches_committed": 6})
        client.quorum.return_value = _quorum_result(max_step=0)
        m.start_quorum()
        m.wait_quorum()
        assert calls == []
        assert m.current_step() == 3
        m.shutdown()

    def test_restore_none_trains_from_scratch(self, store):
        # Empty durable store: the consult happens, returns None, and
        # training starts cold at step 0.
        m, client, _, _ = _create_manager(store)
        calls = []

        def restore():
            calls.append(1)
            return None

        m.set_durable_restore(restore)
        client.quorum.return_value = _quorum_result(max_step=0)
        m.start_quorum()
        m.wait_quorum()
        assert calls == [1]
        assert m.current_step() == 0
        m.shutdown()

    def test_ctor_arg_registers(self, store):
        import torchft_tpu.manager as manager_mod
        from torchft_tpu.collectives import DummyCollectives

        calls = []
        m = Manager(
            collectives=DummyCollectives(),
            load_state_dict=None,
            state_dict=None,
            min_replica_size=2,
            rank=1,
            world_size=2,
            store_addr=store.address(),
            checkpoint_transport=MagicMock(metadata=MagicMock(return_value="x")),
            durable_restore=lambda: calls.append(1) or None,
        )
        client = manager_mod.ManagerClient.return_value
        client.quorum.return_value = _quorum_result(max_step=0)
        m.start_quorum()
        m.wait_quorum()
        assert calls == [1]
        m.shutdown()


class TestStartupRecord:
    """The process's way to its first commit (startup.py): closed at the
    first vote that passes, carried by the snapshot as ``process``."""

    INTERVALS = (
        "spawn_to_import", "import_to_manager", "manager_init",
        "first_quorum", "heal", "first_step",
    )

    @pytest.fixture
    def record(self, monkeypatch):
        from torchft_tpu import startup

        now = time.monotonic()
        fresh = startup.StartupRecord(started=now - 2.0, imported=now - 1.5)
        monkeypatch.setattr(startup, "_record", fresh)
        return fresh

    @staticmethod
    def _step(m, commit=True):
        m.start_quorum()
        m.allreduce({"w": np.ones(2, np.float32)}).wait()
        return m.should_commit()

    def test_closes_at_the_first_commit_and_logs_once(self, store, record, caplog):
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        with caplog.at_level("INFO", logger="torchft_tpu.manager"):
            client.should_commit.return_value = False
            assert not self._step(m)  # a vote that fails closes nothing
            assert "ready" not in m.metrics().snapshot()["process"]["timers_s"]
            assert not [r for r in caplog.records if "ready in" in r.getMessage()]
            client.should_commit.return_value = True
            assert self._step(m)
            first = m.metrics().snapshot()["process"]
            assert self._step(m)  # a second commit changes nothing
            assert m.metrics().snapshot()["process"] == first
        (line,) = [r.getMessage() for r in caplog.records if "ready in" in r.getMessage()]
        assert line.startswith("[testrep/1 - step 1] ready in ")
        for word in (*self.INTERVALS, "compile", "hits", "misses"):
            assert word in line, (word, line)
        # another Manager of this process finds the record closed: no line
        m2, client2, _, _ = _create_manager(store)
        client2.quorum.return_value = _quorum_result()
        client2.should_commit.return_value = True
        with caplog.at_level("INFO", logger="torchft_tpu.manager"):
            caplog.clear()
            assert self._step(m2)
        assert not [r for r in caplog.records if "ready in" in r.getMessage()]
        assert m2.metrics().snapshot()["process"] == first
        m.shutdown()
        m2.shutdown()

    def test_the_intervals_sum_to_ready(self, store, record):
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = True
        time.sleep(0.02)  # the trainer between the Manager and its first step
        assert self._step(m)
        snap = m.metrics().snapshot()
        seconds = {k: v["total_s"] for k, v in snap["process"]["timers_s"].items()}
        assert set(self.INTERVALS) | {"ready", "startup_compile"} <= set(seconds)
        assert all(snap["process"]["timers_s"][k]["n"] == 1 for k in self.INTERVALS)
        assert sum(seconds[k] for k in self.INTERVALS) == pytest.approx(seconds["ready"], abs=1e-3)
        # the stamps the fixture planted, and what lies between the others
        assert seconds["spawn_to_import"] == pytest.approx(0.5, abs=1e-3)
        assert 1.5 <= seconds["import_to_manager"] < 1.5 + 5.0
        assert 0.0 < seconds["manager_init"] < 5.0 and seconds["first_step"] >= 0.02
        assert seconds["heal"] == 0.0 and 2.0 < seconds["ready"] < 12.0
        assert seconds["first_quorum"] == pytest.approx(
            snap["timers_s"]["quorum"]["total_s"] + snap["timers_s"]["reconfigure"]["total_s"],
            abs=1e-5,
        )
        assert snap["process"]["counters"]["startup_cache_misses"] == 0
        json.dumps(snap)  # a run file carries it
        m.shutdown()

    def test_a_later_slow_quorum_is_not_the_first(self, store, record):
        m, client, _, _ = _create_manager(store)
        client.quorum.return_value = _quorum_result()
        client.should_commit.return_value = False
        assert not self._step(m)  # the first quorum, and no commit yet

        def slow(**kwargs):
            time.sleep(0.3)
            return _quorum_result(quorum_id=2)  # and a second reconfigure

        client.quorum.side_effect = slow
        client.should_commit.return_value = True
        assert self._step(m)
        snap = m.metrics().snapshot()
        assert snap["timers_s"]["quorum"]["n"] == 2 and snap["timers_s"]["quorum"]["max"] >= 0.3
        assert snap["timers_s"]["reconfigure"]["n"] == 2
        seconds = {k: v["total_s"] for k, v in snap["process"]["timers_s"].items()}
        assert seconds["first_quorum"] < 0.25
        assert seconds["first_step"] >= 0.3  # the slow one is the first step's
        assert sum(seconds[k] for k in self.INTERVALS) == pytest.approx(seconds["ready"], abs=1e-3)
        m.shutdown()

    def test_the_first_lifes_heal_is_in_heal(self, store, record):
        def load(sd):
            time.sleep(0.05)

        m, client, _, transport = _create_manager(
            store, use_async_quorum=False, load_state_dict=load,
        )
        client.quorum.return_value = _quorum_result(
            quorum_id=2, replica_rank=1, heal=True, max_step=20, max_rank=None,
            recover_src_manager_address="mock://peer", recover_src_rank=0,
        )
        client.checkpoint_metadata.return_value = "peer:meta"

        def recv(**kwargs):
            time.sleep(0.1)
            return {"user": {"model": "w"}, "torchft": {"step": 20, "batches_committed": 40}}

        transport.recv_checkpoint.side_effect = recv
        client.should_commit.return_value = True
        assert self._step(m)
        snap = m.metrics().snapshot()
        seconds = {k: v["total_s"] for k, v in snap["process"]["timers_s"].items()}
        assert seconds["heal"] == pytest.approx(
            snap["timers_s"]["heal_fetch"]["total_s"] + snap["timers_s"]["heal_apply"]["total_s"],
            abs=1e-5,
        )
        assert seconds["heal"] >= 0.15
        m.shutdown()

    def test_manager_init_is_a_span_of_its_own(self, store, record, tmp_path):
        from test_profiling import _captured, _one

        made = []
        events = _captured(tmp_path, lambda: made.append(_create_manager(store)[0]))
        event = _one(events, "torchft::startup/manager_init")
        assert (event[3] - event[2]) / 1e9 == pytest.approx(
            record._built - record._entered, abs=2e-3
        )
        made[0].shutdown()
