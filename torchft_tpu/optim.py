"""Optimizer wrapper binding optax updates to the commit protocol.

Reference: torchft/optim.py — ``zero_grad()`` starts the quorum,
``step()`` applies the update only if the distributed commit vote passes.
State lives in an :class:`~torchft_tpu.train_state.FTTrainState` so a heal
applied at the ``should_commit`` safe point is visible to the very update
that follows it (the reference gets this from torch's in-place
``load_state_dict``; immutable jax pytrees need the holder).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .manager import Manager
from .train_state import FTTrainState


class OptimizerWrapper:
    """Quorum + commit gating around an optax optimizer.

    Canonical loop (reference train_ddp.py:119-152 shape)::

        state = FTTrainState(params, optax.adamw(1e-3))
        manager = Manager(..., state_dict=state.state_dict,
                          load_state_dict=state.load_state_dict)
        optimizer = OptimizerWrapper(manager, state)
        for step in ...:
            optimizer.zero_grad()                  # starts async quorum
            grads = grad_fn(state.params, batch)
            avg = manager.allreduce(grads).wait()  # fault-tolerant average
            optimizer.step(avg)                    # applies iff committed
    """

    def __init__(self, manager: Manager, state: FTTrainState) -> None:
        self.manager = manager
        self.state = state

    def zero_grad(self) -> None:
        """Starts the (async) quorum for this step. Name kept for parity
        with the reference API (optim.py:48-50)."""
        self.manager.start_quorum()

    def step(self, grads: Any) -> bool:
        """Votes, then applies ``grads`` iff every rank committed (reference
        optim.py:52-54). ``should_commit`` applies any pending recovery
        checkpoint into ``self.state`` first, so the update always starts
        from the healed weights. Returns whether the step committed.
        Timed as ``optimizer_step`` (the vote and the update's dispatch;
        the update itself runs on the device after it returns)."""
        with self.manager.metrics().timed("optimizer_step"):
            if not self.manager.should_commit():
                return False
            self.state.apply_gradients(grads)
            return True


class ShardedOptimizerWrapper:
    """The :class:`OptimizerWrapper` loop shape over the per-step ZeRO
    engine: ``zero_grad()`` starts the quorum, ``step(grads)`` runs the
    whole sharded transaction — reduce-scatter, ~1/W shard-local
    optimizer update, param allgather, commit vote — instead of the
    fused allreduce + full-size update. Drop-in where the canonical loop
    computes raw (un-averaged) gradients::

        state = FTTrainState(params, optax.adamw(1e-3), opt_state=())
        optimizer = ShardedOptimizerWrapper(manager, state,
                                            shard_wire="q8")
        for step in ...:
            optimizer.zero_grad()                 # starts async quorum
            loss, grads = grad_fn(state.params, batch)
            optimizer.step(grads)                 # rs -> update -> ag

    Note the contract difference from :class:`OptimizerWrapper`: pass
    RAW gradients (the reduce-scatter averages them); there is no
    separate ``manager.allreduce`` call. Construct the train state with
    ``opt_state=()`` so no full-size optimizer state is ever allocated,
    and wire the manager's state callbacks to :meth:`state_dict` /
    :meth:`load_state_dict` so heals carry the optimizer shard."""

    def __init__(
        self,
        manager: Manager,
        state: FTTrainState,
        shard_wire: Optional[str] = None,
        param_wire: Optional[str] = "auto",
    ) -> None:
        from .ddp import ShardedDDP

        self.manager = manager
        self.state = state
        self._core = ShardedDDP(
            manager, state, grad_fn=None,
            shard_wire=shard_wire, param_wire=param_wire,
        )

    def zero_grad(self) -> None:
        """Starts the (async) quorum for this step."""
        self.manager.start_quorum()

    def step(self, grads: Any) -> bool:
        """Runs the sharded transaction for ``grads``; applies iff the
        cohort committed. Returns whether it did. Timed as
        ``optimizer_step``, like :meth:`OptimizerWrapper.step`."""
        with self.manager.metrics().timed("optimizer_step"):
            return self._core.apply_gradients(grads)

    @property
    def last_commit(self) -> Optional[bool]:
        return self._core.last_commit

    def opt_state_bytes(self) -> int:
        """Resident bytes of this replica's optimizer-state shard."""
        return self._core.opt_state_bytes()

    def state_dict(self) -> Dict[str, Any]:
        return self._core.state_dict()

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self._core.load_state_dict(sd)
