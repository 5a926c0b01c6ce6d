"""Identity-stable training state for fault-tolerant JAX loops.

In torch, healing works because ``load_state_dict`` mutates the same tensors
the optimizer later steps (reference manager.py:528-543). JAX pytrees are
immutable values, so a recovered checkpoint applied through a callback can
be silently shadowed by stale ``params`` bound earlier in the step — the
divergence class the reference never has. :class:`FTTrainState` restores the
in-place property at the *holder* level: the manager's state callbacks and
the optimizer update both go through one mutable object, so post-heal reads
always see the recovered weights.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from . import startup
from .profiling import span


def _to_device_tree(tree: Any) -> Any:
    """Checkpointed leaves arrive as host numpy; rebuild jax arrays (same
    dtypes) so downstream jitted code never sees numpy."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda l: jnp.asarray(l) if isinstance(l, np.ndarray) else l, tree
    )


def make_apply_fn(tx: Any) -> Any:
    """Jits ``(params, opt_state, grads) -> (params, opt_state)`` for an
    optax transform, with donation (old buffers consumed by the new ones).
    Shardings are inferred from the inputs, so the same function serves
    single-device and mesh-sharded states."""
    import jax
    import optax

    def apply(params: Any, opt_state: Any, grads: Any):
        # the scope the device trace files the update's operations under,
        # here and in models.make_train_step's fused program alike
        with jax.named_scope("optimizer"):
            # Mixed-precision-friendly: grads may arrive in a lower
            # wire/compute dtype (bf16 ring payloads,
            # models.make_train_step(bf16_params=True)); the master update
            # always runs in the params' own (f32) dtype.
            grads = jax.tree_util.tree_map(
                lambda g, p: g.astype(p.dtype) if g.dtype != p.dtype else g,
                grads, params,
            )
            updates, new_opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt_state

    return jax.jit(apply, donate_argnums=(0, 1))


class FTTrainState:
    """Mutable holder for ``params`` + ``opt_state`` + the optax transform.

    Wire its ``state_dict``/``load_state_dict`` into the
    :class:`~torchft_tpu.manager.Manager` so live recovery flows through the
    same object the train loop reads::

        state = FTTrainState(params, optax.adamw(1e-3))
        manager = Manager(..., state_dict=state.state_dict,
                          load_state_dict=state.load_state_dict)
    """

    def __init__(self, params: Any, tx: Any, opt_state: Optional[Any] = None) -> None:
        startup.listen()  # the optimizer state's programs compile next
        self.params = params
        self.tx = tx
        self.opt_state = opt_state if opt_state is not None else tx.init(params)
        self._apply_jit: Optional[Any] = None

    def state_dict(self) -> Dict[str, Any]:
        """Snapshot for recovery transfer / durable checkpoints.

        The returned dict references the CURRENT buffers, and
        ``apply_gradients`` donates them — a snapshot is only valid until
        the next update. This is safe for live recovery because the manager
        re-locks the checkpoint gate (blocking on in-flight transfers)
        before the optimizer runs (reference manager.py:591 discipline);
        for durable checkpoints, serialize before the next step."""
        return {"params": self.params, "opt_state": self.opt_state}

    def load_state_dict(self, state_dict: Dict[str, Any]) -> None:
        self.params = _to_device_tree(state_dict["params"])
        self.opt_state = _to_device_tree(state_dict["opt_state"])

    def snapshot(self) -> Dict[str, Any]:
        """Host copy of the full state (numpy leaves, fresh buffers).

        Unlike ``state_dict`` (which aliases live device buffers), the
        snapshot survives the device backend being torn down — the
        round-trip ``XLACollectives`` reconfiguration needs: a membership
        change rebuilds the XLA distributed runtime, orphaning every live
        jax array (torchft_tpu/xla_collectives.py:19-31)."""
        import jax

        return jax.tree_util.tree_map(
            lambda l: np.asarray(l).copy() if hasattr(l, "dtype") else l,
            {"params": self.params, "opt_state": self.opt_state},
        )

    def restore(self, snapshot: Dict[str, Any]) -> None:
        """Re-uploads a :meth:`snapshot` onto the (possibly new) backend.
        Drops the cached apply jit: its executable belongs to the old
        backend after a distributed-runtime rebuild."""
        self.load_state_dict(snapshot)
        self._apply_jit = None

    def warm(self, grads_like: Any) -> None:
        """AOT warm-up of the optimizer-update executable (standby
        discipline): lowers and compiles the apply function for the live
        state and ``grads_like``, so the first real ``apply_gradients``
        after a standby promotion pays no trace or compile. Nothing runs
        and nothing is copied - running it once on throwaway copies, as
        this did before, needs the state twice over, which a 626 M-
        parameter model's 7.5 GB of masters and moments do not have on a
        16 GB chip. The jit's own call finds the lowering and the
        executable in JAX's caches (same function, same shapes), and the
        executable lands in the persistent compilation cache too, so it
        also pre-warms future cold restarts."""
        if self._apply_jit is None:
            self._apply_jit = make_apply_fn(self.tx)
        self._apply_jit.lower(self.params, self.opt_state, grads_like).compile()

    def apply_gradients(self, grads: Any) -> None:
        """One optimizer update, in place (holder-level).

        The update is jitted (one fused kernel instead of an eager dispatch
        per optax op) with buffer donation, so HBM stays flat: old
        params/opt_state are consumed by the new ones (see the
        ``state_dict`` snapshot-lifetime note)."""
        if self._apply_jit is None:
            self._apply_jit = make_apply_fn(self.tx)
        # dispatch only: the call returns before the device has run it
        with span("torchft::apply_gradients"):
            self.params, self.opt_state = self._apply_jit(
                self.params, self.opt_state, grads
            )
