"""Fault-tolerant data parallelism across replica groups.

Reference: torchft/ddp.py — there, a comm-hook routes each gradient bucket
through ``Manager.allreduce`` during backward. JAX has no backward hooks;
gradients materialize as one pytree from ``jax.grad``, which is *better* for
this transport: the whole tree is packed into one ring pass per dtype by the
collectives layer (the bucketing DDP's reducer approximates).

Intra-replica-group sharding (FSDP/TP-style) stays in user pjit code over
the slice mesh — this wrapper only averages across groups, mirroring the
reference's division of labor (torchft owns the replicate dim only,
process_group.py:1067-1341).
"""

from __future__ import annotations

import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

# One parser for the TORCHFT_DEVICE_PACK knob across every layer —
# duplicating the mapping here would let the two layers drift.
from .collectives import ReduceOp, Work, _resolve_device_pack_setting
from .manager import Manager
from .train_state import FTTrainState, _to_device_tree

logger: logging.Logger = logging.getLogger(__name__)


class DistributedDataParallel:
    """Averages gradient pytrees across replica groups, fault-tolerantly.

    Usage::

        ddp = DistributedDataParallel(manager)
        grads = grad_fn(params, batch)
        grads = ddp.allreduce_grads(grads).wait()   # async; overlap-friendly

    or wrap a grad function so the average happens on call::

        value_and_avg_grads = ddp.wrap_grad_fn(jax.value_and_grad(loss_fn))
    """

    def __init__(self, manager: Manager) -> None:
        self._manager = manager

    def allreduce_grads(self, grads: Any) -> Work:
        """Starts the async cross-group average of ``grads``; the Work
        resolves to the averaged pytree (input unchanged on error, with the
        error latched for ``should_commit`` — reference ddp.py:67-71)."""
        return self._manager.allreduce(grads)

    def wrap_grad_fn(
        self, grad_fn: Callable[..., Tuple[Any, Any]]
    ) -> Callable[..., Tuple[Any, Any]]:
        """Wraps a ``jax.value_and_grad``-style fn so returned grads are
        already averaged across replica groups (blocking)."""

        def wrapped(*args: Any, **kwargs: Any) -> Tuple[Any, Any]:
            value, grads = grad_fn(*args, **kwargs)
            return value, self.allreduce_grads(grads).wait()

        return wrapped


class PipelinedDDP:
    """Per-step DDP with the cross-group ring overlapped with compute.

    The reference hides its allreduce behind backward via bucket hooks
    (reference ddp.py:47-71): bucket ``b``'s ring pass overlaps computing
    bucket ``b+1``'s gradients. JAX materializes the whole gradient pytree
    from one jitted program, so the equivalent overlap is across the *step*
    boundary instead: step ``i``'s ring pass runs while the device computes
    step ``i+1``'s forward/backward (a one-step-stale gradient schedule,
    the standard pipelined-SGD delay-1 discipline). Device dispatch is
    async, so the host thread that would otherwise idle in ``wait()``
    instead settles the previous step's transaction.

    Per call, the full manager transaction still runs for every step —
    quorum, managed allreduce, AND-vote commit — just one iteration behind
    the compute. Recovery is handled: when a heal lands at the commit safe
    point, the already-dispatched gradients were computed from pre-heal
    weights, so they are recomputed from the recovered state before being
    contributed (a fresh restart otherwise pollutes the cohort average
    with init-weight gradients).

    ``compress="bf16"`` casts float32 gradients to bfloat16 for the wire
    (half the cross-group bytes; ring hops accumulate in f32) and restores
    the original dtypes on return — the JAX analog of torch DDP's
    ``bf16_compress_hook``.

    Quantized modes (both: per-leaf int8 quantization with ERROR
    FEEDBACK — the per-step quantization error carries into the next
    step's gradients, the standard EF-SGD recipe, reset on heal along
    with the rest of the local trajectory; the analog of torch DDP's
    compressed comm hooks). Two transports for two bottlenecks:

    - ``compress="int8"``: the int8 payload itself ({q, scale} leaves)
      rides a managed device-packed ALLGATHER and is dequantize-averaged
      on settle. The DEVICE<->HOST link carries int8 bytes — the mode for
      hosts where that link (PCIe) is the bottleneck. Allgather traffic
      grows with cohort size; intended for small cohorts.
    - ``compress="q8"``: the dequantized (f32, int8-gridded) gradients
      ride the native ring's quantized wire (int8 chunks + per-chunk
      scales, dequant-accumulated per hop): TCP bytes are ~4x below f32
      and CONSTANT in cohort size, but the device link carries f32 — the
      mode for real DCN deployments where the network is the bottleneck
      and cohorts are larger.

    Usage::

        ddp = PipelinedDDP(manager, state, grad_fn)  # grad_fn: (params, batch) -> (loss, grads)
        for batch in batches:
            loss = ddp.step(batch)
        ddp.flush()      # settle the final in-flight step
    """

    def __init__(
        self,
        manager: Manager,
        state: FTTrainState,
        grad_fn: Callable[..., Tuple[Any, Any]],
        compress: Optional[str] = None,
        transport: str = "legacy",
        device_pack: Any = None,
        hier: bool = False,
    ) -> None:
        """``transport="plan"`` routes the gradient sync through
        ``Manager.plan_allreduce`` — the persistent native comm plan —
        instead of the legacy managed allreduce. The wire encoding then
        happens NATIVELY at pack time (``compress="bf16"`` -> plan wire
        "bf16"; ``compress="q8"`` -> plan wire "q8ef", error feedback
        included), so no jitted compress/quantize program runs on the
        per-step hot path. ``compress="int8"`` (the allgather transport)
        has no plan form and rejects ``transport="plan"``. On a
        non-committed step the plan transport RESETS the native EF carry
        (the legacy transport rolls its jax carry back exactly; the
        plan's carry lives native-side, and dropping it only costs
        signal on the already-discarded step).

        ``device_pack`` (plan transport only): where the wire encoding
        runs — ``True``/``"on"`` on the accelerator (Pallas kernels, d2h
        bytes scale with the wire — the q8 EF carry then lives
        device-resident and never crosses the link), ``False``/``"off"``
        on the host, ``None`` (default) / ``"auto"`` the
        ``TORCHFT_DEVICE_PACK`` env discipline (auto device-packs only
        on a real device backend; every setting is bit-identical, so
        members need not agree).

        ``hier`` (plan transport only) runs the sync over the TWO-TIER
        topology-aware schedule — intra-region rings plus an inter-region
        leader ring, with the wire applied on the slow inter hop only
        (``compress="q8"`` -> the leader-side q8+EF inter wire). Requires
        the cohort's quorum to carry a usable region map
        (``TORCHFT_REGION`` on every member, >= 2 regions); otherwise
        every sync latches an error and the steps are discarded — which
        is exactly the sentinel AdaptiveDDP's ``plan_hier`` candidate
        records, so under ``TORCHFT_DDP_MODE=auto`` an un-hierarchical
        cohort simply never picks it."""
        if compress not in (None, "bf16", "int8", "q8"):
            raise ValueError(f"unsupported compress: {compress!r}")
        if transport not in ("legacy", "plan", "iso"):
            raise ValueError(f"unsupported transport: {transport!r}")
        if transport in ("plan", "iso") and compress == "int8":
            raise ValueError(
                "compress='int8' rides a managed allgather; the plan and "
                "isolated transports have no allgather form (use "
                "compress='q8')"
            )
        if transport == "iso" and not getattr(
            manager, "has_iso_plane", lambda: False
        )():
            raise ValueError(
                "transport='iso' needs Manager(iso_collectives=...)"
            )
        if hier and transport != "plan":
            raise ValueError(
                "hier=True rides the plan transport (the two-tier schedule "
                "is a comm-plan form)"
            )
        self._manager = manager
        self._state = state
        self._grad_fn = grad_fn
        self._compress_mode = compress
        self._transport = transport
        self._hier = hier
        self._device_pack = _resolve_device_pack_setting(device_pack)
        self._inflight: Optional[Work] = None
        self._inflight_dtypes: Any = None  # grad dtype TUPLE at dispatch
        #                                    (may change across restores)
        self._inflight_transport = transport  # transport AT dispatch:
        #   settle must branch on what the work was dispatched through,
        #   not on the (mutable) current setting
        # Outcome of the most recent settle (None before the first): the
        # only error signal that survives the step — the step-final
        # start_quorum clears the manager's latched error before any
        # caller can read it. AdaptiveDDP's probe depends on this.
        self.last_commit: Optional[bool] = None
        self._compress_jit: Optional[Any] = None
        self._decompress_jit: Optional[Any] = None
        self._quant_jit: Optional[Any] = None
        self._combine_fns: dict = {}     # int8: per-cohort dequant-avg
        self._residual: Any = None       # int8/q8: error-feedback carry
        self._prev_residual: Any = None  # pre-dispatch carry (non-commit
        #                                  settles roll back to it)

    def _compress(self, grads: Any) -> Any:
        """Returns the wire payload for ``grads`` and records the dtype
        tree the settle-side decompress restores (recomputed every step —
        a restore can change the gradient pytree's dtypes mid-run)."""
        import jax

        # hashable tuple (leaf order = tree_flatten order): doubles as
        # the static arg of the jitted decompress cast
        self._inflight_dtypes = tuple(
            l.dtype for l in jax.tree_util.tree_leaves(grads)
        )
        if self._compress_mode is None:
            return grads
        import jax.numpy as jnp

        if self._compress_mode in ("int8", "q8"):
            if self._quant_jit is None:
                from .quantize import quantize_with_feedback

                self._quant_jit = jax.jit(quantize_with_feedback)
            if self._residual is None:
                self._residual = jax.tree_util.tree_map(
                    lambda l: jnp.zeros(l.shape, jnp.float32), grads
                )
            self._prev_residual = self._residual  # restored on non-commit
            out = self._quant_jit(grads, self._residual)
            self._residual = out["res"]
            if self._compress_mode == "int8":
                # int8 BYTES cross the device link (device-packed
                # allgather); settle dequantize-averages
                return {"q": out["q"], "scale": out["scale"]}
            # q8: f32 on the device link, int8 on the TCP ring
            return out["dq"]

        if self._compress_jit is None:

            def down(t: Any) -> Any:
                return jax.tree_util.tree_map(
                    lambda l: l.astype(jnp.bfloat16)
                    if l.dtype == jnp.float32
                    else l,
                    t,
                )

            self._compress_jit = jax.jit(down)
        return self._compress_jit(grads)

    def _decompress(self, avg: Any) -> Any:
        if self._compress_mode in (None, "int8", "q8"):
            return avg
        import jax

        # restore the dtypes recorded AT dispatch (not a forever-cached
        # tree: a restore may legitimately change grad dtypes mid-run).
        # Jitted with the dtype tuple STATIC: one fused cast program per
        # distinct dtype signature instead of per-leaf eager dispatches
        # on the per-step hot path.
        if self._decompress_jit is None:

            def up(t: Any, dts: Any) -> Any:
                leaves, treedef = jax.tree_util.tree_flatten(t)
                return jax.tree_util.tree_unflatten(
                    treedef, [l.astype(d) for l, d in zip(leaves, dts)]
                )

            self._decompress_jit = jax.jit(up, static_argnums=(1,))
        return self._decompress_jit(avg, self._inflight_dtypes)

    def _dispatch(self, grads: Any) -> Work:
        self._inflight_transport = self._transport
        if self._transport == "plan":
            # Raw grads in, native cast/quantize at pack: the plan is
            # the whole wire pipeline, no jitted compress program. Under
            # hier the wire moves to the leader's inter-region hop
            # (device_pack has no hier form and is ignored there).
            wire = {None: None, "bf16": "bf16", "q8": "q8ef"}[
                self._compress_mode
            ]
            kwargs: dict = {"wire": wire, "device_pack": self._device_pack}
            if self._hier:
                # Passed only when set: pre-hier Manager stand-ins (test
                # scaffolding, older wrappers) keep working on the flat
                # schedule they know.
                kwargs["hier"] = True
            return self._manager.plan_allreduce(grads, **kwargs)
        if self._transport == "iso":
            # Isolated XLA data plane: same compress pipeline as legacy
            # (the backend serves every wire losslessly — the compiled
            # path's contract), dispatched through the disposable child.
            payload = self._compress(grads)
            wire = "q8" if self._compress_mode == "q8" else None
            return self._manager.iso_allreduce(payload, wire=wire)
        payload = self._compress(grads)
        if self._compress_mode == "int8":
            return self._manager.allgather(payload)
        if self._compress_mode == "q8":
            # the quantized ring returns the averaged f32 tree directly
            # (FTTrainState harmonizes dtypes against the master params)
            return self._manager.allreduce(payload, wire="q8")
        return self._manager.allreduce(payload)

    def _settle(self) -> bool:
        """Waits the in-flight ring pass, votes, applies on commit."""
        assert self._inflight is not None
        result = self._inflight.wait()
        self._inflight = None
        committed = self._manager.should_commit()
        self.last_commit = committed
        if self._inflight_transport == "plan":
            if committed:
                # plan results arrive decoded in the leaf dtypes; a
                # committed step can never see the None failure default
                # (an error would have failed the commit vote)
                self._state.apply_gradients(result)
            elif self._compress_mode == "q8":
                # The discarded step advanced the native EF carry; the
                # legacy transport rolls its jax carry back exactly,
                # the plan drops it (conservative — only the abandoned
                # step's quantization error is lost).
                self._manager.reset_plan_feedback()
            return committed
        if committed:
            if self._compress_mode == "int8":
                # member-wise dequantize, average over PARTICIPANTS
                # (healing/spare entries arrive zeroed and must not
                # dilute the divisor — Manager.allgather discipline)
                import jax
                import jax.numpy as jnp

                cohort = len(result)
                combine = self._combine_fns.get(cohort)
                if combine is None:
                    from .quantize import make_dequant_average

                    combine = self._combine_fns[cohort] = \
                        make_dequant_average()
                avg = combine(
                    result,
                    float(max(self._manager.num_participants(), 1)),
                )
            else:
                avg = self._decompress(result)
            self._state.apply_gradients(avg)
        elif self._compress_mode in ("int8", "q8"):
            # The step was discarded: its gradients were never applied, so
            # carrying ITS quantization error forward would inject signal
            # from an abandoned payload into the next step — roll the EF
            # carry back to the pre-dispatch value (AsyncDiLoCo's
            # restored-on-abort discipline).
            self._residual = self._prev_residual
        return committed

    def blocking_step(self, *batch: Any) -> Any:
        """One UNPIPELINED step: quorum, dispatch, settle — the whole
        transaction in-step (the schedule AdaptiveDDP probes as
        ``blocking``/``plan`` and the policy engine's per-step-DDP
        strategy runs). Drains any overlap left by earlier ``step`` calls
        first, so the two schedules can be mixed."""
        if self._inflight is not None:
            self._settle()
        self._manager.start_quorum()
        loss, grads = self._grad_fn(self._state.params, *batch)
        self._inflight = self._dispatch(grads)
        self._settle()
        return loss

    def step(self, *batch: Any) -> Any:
        """One pipelined step: dispatches this batch's gradient program,
        settles the PREVIOUS step's transaction while the device computes,
        then contributes these gradients to a newly-started quorum. Returns
        the loss (a device value; don't block on it in the hot loop)."""
        loss, grads = self._grad_fn(self._state.params, *batch)
        if self._inflight is not None:
            healed = self._manager.is_healing()
            self._settle()
            if healed:
                # The dispatched grads came from pre-heal weights; recompute
                # from the recovered (and just-updated) state. The EF carry
                # belongs to the abandoned trajectory — drop it.
                loss, grads = self._grad_fn(self._state.params, *batch)
                self._residual = None
                if self._transport == "plan":
                    self._manager.reset_plan_feedback()
        self._manager.start_quorum()
        self._inflight = self._dispatch(grads)
        return loss

    def flush(self) -> bool:
        """Settles the final in-flight step; returns whether it committed.
        Call once after the loop (and before reading ``state`` as the
        final model)."""
        if self._inflight is None:
            return False
        return self._settle()


class ShardedDDP:
    """Per-step ZeRO across replica groups: each step reduce-scatters the
    gradients, runs the optimizer on this group's ~1/W shard of the
    (flat-packed) parameters, and allgathers the updated parameters back
    — optimizer state and update FLOPs scale with the shard, not the
    model (ZeRO stage 1/2 across the DCN replicate dimension, per step
    rather than per DiLoCo window).

    The data plane is the precompiled SHARDED comm plan
    (``Manager.plan_reduce_scatter`` / ``plan_allgather_into``): one
    GIL-released native call per leg, composed from the proven rs/ag ring
    phase bodies over the flat ring. On the f32 wire the whole step is
    BIT-IDENTICAL to the fused plan-f32 step — same stripe partition,
    same ring sums, same f32 divide, and every member applies the same
    optimizer arithmetic to its slice. ``shard_wire="q8"`` quantizes the
    grad leg's ring hops while this rank's owned shard stays full f32
    (the PR-2 reduce-scatter discipline); ``param_wire="bf16"`` (the
    DEFAULT whenever ``shard_wire="q8"``) halves the param leg, with
    every member — owner included — adopting the identical decoded bf16
    words, so params stay bit-identical across the cohort on every wire.

    Fault tolerance is the DiLoCo sharded-outer machinery at per-step
    cadence: the optimizer shard is keyed by ``quorum_id`` — membership
    changes re-partition it through a cohort mask-allgather
    (first-owner-wins; positions a departed member took with it restart
    at zero), and a heal voids the meta (``load_state_dict`` sets
    ``quorum_id=-1``) so the healed member re-shards the donor's shard
    into its own ranges at the next step. Any leg's failure latches, the
    commit vote fails, and params + optimizer shard keep their pre-step
    values — committed-or-discarded, same as every other strategy.

    Requires f32 master params (the flat shard layout is one f32 group).
    Construct the FTTrainState with ``opt_state=()`` so no full-size
    optimizer state is ever allocated::

        state = FTTrainState(params, optax.adamw(1e-3), opt_state=())
        ddp = ShardedDDP(manager, state, grad_fn, shard_wire="q8")
        for batch in batches:
            loss = ddp.step(batch)

    Wire the manager's state callbacks to :meth:`state_dict` /
    :meth:`load_state_dict` so a heal carries the donor's shard + meta
    (not ``state.state_dict``, which never sees the shard)."""

    def __init__(
        self,
        manager: Manager,
        state: FTTrainState,
        grad_fn: Optional[Callable[..., Tuple[Any, Any]]],
        shard_wire: Optional[str] = None,
        param_wire: Optional[str] = "auto",
    ) -> None:
        """``grad_fn(params, *batch) -> (loss, grads)`` — the PipelinedDDP
        contract (None is allowed when only :meth:`apply_gradients` is
        used, e.g. under ``ShardedOptimizerWrapper``). ``param_wire``
        defaults to ``"auto"``: bf16 when ``shard_wire="q8"`` (the
        quantized grad leg already accepts wire loss; a full-f32 param
        broadcast would dominate the step's bytes), native f32 otherwise
        — pass ``None`` explicitly to force the f32 param leg."""
        if shard_wire not in (None, "bf16", "q8"):
            raise ValueError(f"unsupported shard_wire: {shard_wire!r}")
        if param_wire == "auto":
            param_wire = "bf16" if shard_wire == "q8" else None
        if param_wire not in (None, "bf16"):
            raise ValueError(f"unsupported param_wire: {param_wire!r}")
        import jax

        bad = {
            str(np.dtype(l.dtype))
            for l in jax.tree_util.tree_leaves(state.params)
            if np.dtype(l.dtype) != np.dtype(np.float32)
        }
        if bad:
            raise ValueError(
                "ShardedDDP requires f32 master params (found "
                f"{sorted(bad)}); keep masters in f32 and use "
                "shard_wire/param_wire for wire compression"
            )
        self._manager = manager
        self._state = state
        self._grad_fn = grad_fn
        self._shard_wire = shard_wire
        self._param_wire = param_wire
        # Sharded optimizer state: built lazily at the first committed
        # step over the shard this replica owns under the quorum's
        # partition (unknowable before the first quorum forms).
        self._opt_shard: Any = None
        self._shard_meta: Optional[Dict[str, Any]] = None
        self._slice_fns: Dict[Any, Any] = {}
        self._apply_jit: Optional[Any] = None
        self.last_commit: Optional[bool] = None

    # -- train-loop surface (blocking per-step) --

    def step(self, *batch: Any) -> Any:
        """One full sharded step: quorum, grads, rs -> shard update ->
        ag, vote. Returns the loss."""
        assert self._grad_fn is not None, "construct with a grad_fn"
        self._manager.start_quorum()
        loss, grads = self._grad_fn(self._state.params, *batch)
        self.apply_gradients(grads)
        return loss

    def blocking_step(self, *batch: Any) -> Any:
        """Alias of :meth:`step` (every ShardedDDP step is blocking) —
        the PolicyEngine's per-step-DDP engine surface."""
        return self.step(*batch)

    def flush(self) -> bool:
        """Nothing is ever left in flight (each step settles in-step);
        returns the last step's outcome for surface parity."""
        return bool(self.last_commit)

    def apply_gradients(self, grads: Any) -> bool:
        """The sharded transaction for already-computed ``grads``:
        reduce-scatter, shard-local optimizer update, param allgather,
        commit vote. Applies iff committed; returns whether it did. The
        quorum must already be started (``step`` does; so does
        ``ShardedOptimizerWrapper.zero_grad``)."""
        shard = self._manager.plan_reduce_scatter(
            grads, op=ReduceOp.AVG, wire=self._shard_wire,
            ag_wire=self._param_wire,
        ).wait()
        gathered = None
        new_opt = None
        new_meta = None
        resharded = False
        if shard is not None:
            try:
                qid = self._manager.quorum_id()
                opt_shard, resharded = self._opt_state_for(shard, qid)
                p_shard = self._slice_params(shard)
                if self._apply_jit is None:
                    from .parallel import build_shard_apply_step

                    self._apply_jit = build_shard_apply_step(self._state.tx)
                new_p, new_opt = self._apply_jit(
                    p_shard, opt_shard, shard.values["float32"]
                )
                gathered = self._manager.plan_allgather_into(
                    shard.replace_values({"float32": new_p}),
                    wire=self._param_wire,
                ).wait()
                new_meta = {
                    "quorum_id": qid,
                    "counts": dict(shard.counts),
                    "ranges": {
                        k: [tuple(r) for r in v]
                        for k, v in shard.ranges.items()
                    },
                }
            except Exception as e:  # noqa: BLE001 - latch, vote, roll back
                logger.exception("sharded step failed: %s", e)
                self._manager.report_error(e)
                gathered = None
        committed = self._manager.should_commit() and gathered is not None
        self.last_commit = committed
        if committed:
            self._state.params = _to_device_tree(gathered)
            self._opt_shard = new_opt
            self._shard_meta = new_meta
            if resharded:
                # New partition (first step, membership change, or a
                # healed member's re-shard): publish the shard's resident
                # footprint — the policy engine's opt-memory signal.
                self._manager.report_opt_state_bytes(self.opt_state_bytes())
        # abort: params and the optimizer shard keep their pre-step
        # values (new_opt was computed into fresh buffers; the old shard
        # is never donated).
        return committed

    # -- sharded optimizer state --

    def opt_state_bytes(self) -> int:
        """Resident bytes of this replica's optimizer-state shard (0
        before the first committed step) — scales ~1/W with the cohort."""
        import jax

        return int(
            sum(
                int(getattr(l, "nbytes", 0) or 0)
                for l in jax.tree_util.tree_leaves(self._opt_shard)
            )
        )

    def begin_fresh_shard(self) -> None:
        """Strategy re-entry discipline (the AdaptiveDDP/PolicyEngine
        tenure boundary): drops the shard and its meta so the next step
        re-initializes the optimizer over the live params — a
        deterministic momentum cold start on every member, never a
        cross-member divergence (the shard belongs to a trajectory
        another strategy superseded)."""
        self._opt_shard = None
        self._shard_meta = None

    def _opt_state_for(self, shard: Any, qid: int) -> Tuple[Any, bool]:
        """The optimizer state matching ``shard``'s partition (and
        whether it was (re)built): reused when the quorum — and so the
        partition — is unchanged, initialized fresh at the first step,
        re-partitioned through a cohort mask-allgather after a
        membership change."""
        meta = self._shard_meta
        if (
            self._opt_shard is not None
            and meta is not None
            and meta["quorum_id"] == qid
            and meta["counts"] == shard.counts
            and {k: [tuple(r) for r in v] for k, v in shard.ranges.items()}
            == {k: [tuple(r) for r in v] for k, v in meta["ranges"].items()}
        ):
            return self._opt_shard, False
        if self._opt_shard is None:
            # First step of a fresh run (or after begin_fresh_shard):
            # init over the owned param shard — state ∝ 1/W from step 0.
            return self._state.tx.init(self._slice_params(shard)), True
        return self._reshard_opt_state(shard), True

    def _slice_params(self, shard: Any) -> Any:
        """This rank's owned flat slice of the master params — on device
        (jitted pack + slice, cached per partition) for jax trees, host-
        side otherwise. Leaf order is tree-flatten order, the same order
        the plan packed the gradients, so the slice aligns with the grad
        shard element-for-element."""
        import jax

        leaves = jax.tree_util.tree_leaves(self._state.params)
        rng = tuple(tuple(r) for r in shard.ranges["float32"])
        if leaves and all(isinstance(l, jax.Array) for l in leaves):
            fn = self._slice_fns.get(rng)
            if fn is None:
                import jax.numpy as jnp

                def slice_fn(ls: Any, _rng: Any = rng) -> Any:
                    flat = jnp.concatenate([l.reshape(-1) for l in ls])
                    return jnp.concatenate(
                        [flat[s: s + n] for s, n in _rng]
                    )

                fn = self._slice_fns[rng] = jax.jit(slice_fn)
            return fn(leaves)
        flat = np.concatenate(
            [np.asarray(l).ravel() for l in leaves]
        ).astype(np.float32, copy=False)
        return np.concatenate([flat[s: s + n] for s, n in rng])

    def _reshard_opt_state(self, shard: Any) -> Any:
        """Re-partitions the optimizer shard after a membership change:
        every member scatters its OLD shard of each shard-shaped state
        leaf into a full-size (mask, vals) pair, the cohort allgathers
        them, and this member slices its NEW ranges out of the
        first-owner-wins merge. Positions no surviving member owned (a
        departed replica took its shard with it) restart at zero — a
        one-step momentum cold start on 1/W_old of the model (the DiLoCo
        sharded-outer reshard, at per-step cadence)."""
        import jax
        import jax.numpy as jnp

        meta = self._shard_meta
        assert meta is not None
        count = shard.counts["float32"]
        old_ranges = [tuple(r) for r in meta["ranges"]["float32"]]
        old_len = sum(n for _, n in old_ranges)

        state_leaves, state_def = jax.tree_util.tree_flatten(
            self._opt_shard
        )
        shard_like = [
            i
            for i, l in enumerate(state_leaves)
            if getattr(l, "ndim", None) == 1 and l.size == old_len
        ]
        mask = np.zeros(count, np.uint8)
        for s, n in old_ranges:
            mask[s: s + n] = 1
        scattered = []
        for i in shard_like:
            arr = np.asarray(state_leaves[i]).astype(np.float32)
            full = np.zeros(count, np.float32)
            off = 0
            for s, n in old_ranges:
                full[s: s + n] = arr[off: off + n]
                off += n
            scattered.append(full)
        members = self._manager.allgather(
            {"m": mask, "v": scattered}
        ).wait()

        new_leaves = list(state_leaves)
        for j, i in enumerate(shard_like):
            acc = np.zeros(count, np.float32)
            seen = np.zeros(count, bool)
            for m in members:
                mm = np.asarray(m["m"]).astype(bool)
                take = mm & ~seen
                if take.any():
                    acc[take] = np.asarray(m["v"][j], np.float32)[take]
                    seen |= take
            new_shard = np.concatenate(
                [acc[s: s + n] for s, n in shard.ranges["float32"]]
            )
            new_leaves[i] = jnp.asarray(new_shard)
        return jax.tree_util.tree_unflatten(state_def, new_leaves)

    # -- checkpoint plumbing (manager state callbacks) --

    def state_dict(self) -> Dict[str, Any]:
        return {
            "state": self._state.state_dict(),
            "opt_shard": self._opt_shard,
            "shard_meta": self._shard_meta,
        }

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self._state.load_state_dict(sd["state"])
        self._opt_shard = (
            _to_device_tree(sd["opt_shard"])
            if sd["opt_shard"] is not None
            else None
        )
        # The restored shard is the SOURCE replica's (a heal copies the
        # donor's state verbatim); keep its meta so the next re-shard
        # scatters it at the right positions, and force a re-partition by
        # voiding the quorum id — this replica's join bumped it anyway.
        meta = sd.get("shard_meta")
        if meta is not None:
            meta = dict(meta, quorum_id=-1)
        self._shard_meta = meta


class AdaptiveDDP:
    """Per-step DDP that PICKS its schedule per cohort instead of trusting
    a static choice: a cheap runtime probe times a few steps of each
    candidate — ``blocking`` (settle every step, legacy transport),
    ``plan`` (settle every step, persistent native comm plan), and
    ``pipelined`` (one-step-stale overlap) — then locks in the
    cohort-agreed fastest. Pipelined DDP measured SLOWER than blocking on
    some links (VERDICT item 8: the overlap only pays when compute covers
    the ring); the probe makes that regression structurally impossible:
    ``blocking`` is always a candidate and ties resolve to it, so the
    locked mode is never slower than blocking *as measured on this
    cohort's own hardware*.

    Cohort agreement: after the probe, every member allgathers its
    per-candidate timings through the manager and computes the identical
    argmin over the cohort-summed times — one deterministic decision from
    identical data, no leader. The decision is recorded in
    ``self.decision`` and in the manager's metrics
    (``ddp_probe_<mode>`` timings + a ``ddp_mode_<mode>`` counter).

    Lockstep discipline: the probe clock counts ATTEMPTED steps since an
    anchor transaction, and the anchor is the step where this member
    first observed the current ``quorum_id`` — which every member
    observes at the SAME global transaction (the quorum is the step's
    barrier), so schedules align regardless of when each process
    started, and discarded steps advance the clock identically
    everywhere (a committed-step clock would stall forever on a
    candidate whose steps never commit). A probe step whose transaction
    errored records a failure sentinel instead of its (meaninglessly
    fast) wall time, so a candidate that cannot run here — e.g. ``plan``
    on a backend without comm plans — can never win the argmin; and a
    member whose decision GATHER errored locks ``blocking`` (the safe
    default) and lets the self-healing below reconcile it.

    Membership changes re-probe: whenever ``quorum_id`` moves on a CLEAN
    step (join, leave, heal), every member observes it at the same step
    and restarts the probe at the same anchor. A qid bump observed on an
    ERRORED step is a forced reconfigure (every data-plane error
    requests one), not a membership signal — re-anchoring on those would
    loop forever against a permanently-failing candidate, so errored
    steps keep the clock running and record sentinels instead. Transient
    mode disagreement between members is self-healing: mismatched native
    op kinds error immediately, the step is discarded, and — as the
    final backstop — a run of consecutive errored steps locks
    ``blocking`` outright (errors propagate ring-wide by design, so a
    sustained storm is cohort-visible and every member converges to the
    same safe mode; the next clean membership change re-probes).

    ``TORCHFT_DDP_MODE`` pins the mode (``blocking`` | ``pipelined`` |
    ``plan``) and skips probing entirely; ``auto`` (the default) probes.
    All members must use the same setting, like every other schedule
    knob.

    Probe refresh: a locked argmin is otherwise revisited only on a
    quorum change — a cohort whose BANDWIDTH moved (congestion, a paced
    link, a recovered NIC) but whose membership didn't would ride a stale
    schedule forever. ``reprobe_steps`` (env
    ``TORCHFT_DDP_REPROBE_STEPS``, default 0 = never) revalidates the
    lock every N attempted steps: the refresh fires on the same global
    step on every member (steps advance in lockstep and the lock itself
    anchored at a global transaction), only on a clean step following a
    clean step (the reconfigure-echo discipline above), so the cohort
    re-enters the probe schedule together.

    Usage (identical surface to PipelinedDDP)::

        ddp = AdaptiveDDP(manager, state, grad_fn)
        for batch in batches:
            loss = ddp.step(batch)
        ddp.flush()
    """

    # Probe order. "blocking" first: argmin ties resolve to the lowest
    # index, so equal-measuring candidates fall back to blocking.
    # "plan_devpack" (the plan transport with the Pallas device-side wire
    # pack) joins the list only under TORCHFT_DEVICE_PACK=auto with the
    # kernels importable: the device-pack-vs-host-pack choice then rides
    # the SAME lockstep-vote argmin as the schedule choice — on hosts
    # where the interpret-mode kernels are slower than the host pack the
    # probe measures it and host pack wins (the CPU fallback), on real
    # device links the d2h saving wins. "plan_hier" (the plan transport
    # over the TWO-TIER topology-aware schedule) joins whenever "plan"
    # does: on a region-labeled multi-region cohort its probe steps
    # measure the real inter-link saving; on any other cohort every
    # probe step latches the dispatch error and records the sentinel,
    # so it can never win — the lockstep vote stays shape-identical on
    # every member either way. "xla_iso" (the isolated-child
    # XLA data plane) joins only when the manager carries an iso plane:
    # host-ring vs compiled-XLA-path is then LOCKED per cohort by the
    # same vote, never assumed — and an un-spawnable or store-fallback
    # child simply measures slow (or records the failure sentinel), so
    # the candidate can never win by crashing.
    _CANDIDATES = ("blocking", "plan", "pipelined")

    # Recorded instead of wall time for a probe step whose transaction
    # errored: large enough that a failing candidate can never win the
    # argmin, finite so the non-participant zeroing (``inf * 0 = nan``)
    # can't poison the gathered sums.
    _PROBE_FAILED_S = 1e9

    def __init__(
        self,
        manager: Manager,
        state: FTTrainState,
        grad_fn: Callable[..., Tuple[Any, Any]],
        compress: Optional[str] = None,
        mode: Optional[str] = None,
        probe_steps: int = 3,
        device_pack: Any = None,
        reprobe_steps: Optional[int] = None,
    ) -> None:
        mode = mode or os.environ.get("TORCHFT_DDP_MODE", "auto")
        if mode not in ("auto", "blocking", "pipelined", "plan",
                        "plan_hier", "xla_iso", "ddp_sharded"):
            raise ValueError(f"unsupported TORCHFT_DDP_MODE: {mode!r}")
        self._manager = manager
        # One underlying engine; mode switches flip (transport, overlap).
        self._ddp = PipelinedDDP(manager, state, grad_fn, compress)
        self._devpack_setting = _resolve_device_pack_setting(device_pack)
        self._candidates = [
            c for c in self._CANDIDATES
            if not (c == "plan" and compress == "int8")
        ]
        import jax

        f32_masters = all(
            np.dtype(l.dtype) == np.dtype(np.float32)
            for l in jax.tree_util.tree_leaves(state.params)
        )
        if mode == "ddp_sharded":
            if compress == "int8":
                raise ValueError("compress='int8' has no sharded transport")
            if not f32_masters:
                raise ValueError(
                    "TORCHFT_DDP_MODE=ddp_sharded requires f32 master "
                    "params (the flat shard layout is one f32 group)"
                )
        if (
            os.environ.get("TORCHFT_DDP_SHARDED", "")
            not in ("", "0", "false", "off")
            and compress != "int8"
            and f32_masters
        ):
            # Opt-in probe candidate (TORCHFT_DDP_SHARDED=1): the per-step
            # ZeRO engine joins the race on its measured step wall. Opt-in
            # rather than default because mode switches around a sharded
            # tenure reset optimizer momentum (see _run_step) — a cost the
            # operator should choose, not inherit. A cohort whose backend
            # can't serve sharded plans latches every probe step into the
            # failure sentinel, so the candidate can never win there —
            # the same never-a-crash discipline as plan_hier. All members
            # must set the knob or none, like every other schedule knob.
            self._candidates.append("ddp_sharded")
        # Topology opt-in markers. Region: the member carries a label
        # (TORCHFT_REGION / Manager(region=)). Host: the operator set
        # TORCHFT_HOST EXPLICITLY — the Manager's hostname DEFAULT is
        # deliberately not enough here, or every unlabeled single-host
        # dev fleet would grow an extra probe candidate; the quorum's
        # host map (hostname-defaulted) still drives the data plane's
        # tier selection either way, this only gates the probe list.
        region_labeled = bool(
            getattr(manager, "_region", "") or os.environ.get(
                "TORCHFT_REGION", ""
            )
        )
        host_labeled = bool(os.environ.get("TORCHFT_HOST", ""))
        if "plan" in self._candidates and (region_labeled or host_labeled):
            # Topology-aware candidate: the plan transport over the
            # hierarchical schedule. Candidate-list membership is keyed
            # on CONSTRUCTION (this member carries a region label, or
            # the operator explicitly labeled hosts with TORCHFT_HOST for
            # the shm intra-host tier — set on every member of the fleet
            # or on none, like every other schedule knob), so unlabeled
            # deployments keep the exact pre-hier probe. Whether the
            # COHORT is actually hierarchical is only known per quorum: a
            # labeled member in a single-region cohort with no >= 2-
            # member host group probes it anyway, each probe step latches
            # the dispatch error and records the failure sentinel, so the
            # candidate can never win there — never a crash, same
            # discipline as an un-spawnable xla_iso child.
            self._candidates.insert(
                self._candidates.index("plan") + 1, "plan_hier"
            )
        if (
            self._devpack_setting is None  # TORCHFT_DEVICE_PACK=auto
            and "plan" in self._candidates
        ):
            # Probe device pack against host pack with the same lockstep
            # vote that picks the schedule; "plan" itself pins host pack
            # while probing, so the two candidates actually contrast.
            self._candidates.insert(
                self._candidates.index("plan") + 1, "plan_devpack"
            )
        has_iso = getattr(manager, "has_iso_plane", lambda: False)()
        if has_iso and compress != "int8":
            # Isolated-XLA-path candidate: the host-ring-vs-XLA decision
            # rides the same cohort-agreed argmin as everything else.
            # Candidate-list membership is keyed on the manager's
            # CONSTRUCTION (every member attaches the plane or none do,
            # like every other schedule knob), never on child health —
            # a sick child records sentinels, not a shorter list.
            self._candidates.append("xla_iso")
        if mode in ("plan", "plan_hier") and compress == "int8":
            raise ValueError("compress='int8' has no plan transport")
        if mode == "xla_iso":
            if compress == "int8":
                raise ValueError("compress='int8' has no iso transport")
            if not has_iso:
                raise ValueError(
                    "TORCHFT_DDP_MODE=xla_iso needs "
                    "Manager(iso_collectives=...)"
                )
        self._probe_steps = max(int(probe_steps), 2)
        self._sharded_engine: Optional[ShardedDDP] = None
        # Mode the previous _run_step ran: crossing the ddp_sharded
        # tenure boundary in either direction resets optimizer state
        # deterministically on every member (see _run_step).
        self._prev_run_mode: Optional[str] = None
        self._mode: Optional[str] = mode if mode != "auto" else None
        self._auto = mode == "auto"
        # Probe clock: attempted steps since the anchor transaction (the
        # step where this member first observed the current quorum_id —
        # the same global transaction on every member, so schedules
        # align). _probe_qid None = not yet anchored.
        self._probe_qid: Optional[int] = None
        self._probe_idx = 0
        self._probe_t: List[List[float]] = [[] for _ in self._candidates]
        self._decision_qid: Optional[int] = None
        self.decision: Optional[dict] = None
        # Sustained-error backstop: after this many CONSECUTIVE errored
        # steps, lock "blocking" (errors propagate ring-wide, so a storm
        # is cohort-visible and every member converges to the same safe
        # mode instead of chasing desynced probe schedules).
        self._consec_errors = 0
        self._error_backstop = max(6, 3 * self._probe_steps)
        # An errored step's forced reconfigure bumps quorum_id at the
        # NEXT step's quorum — a clean step right after an error still
        # observes the echo. Only a clean step FOLLOWING a clean step
        # treats a new id as a membership change.
        self._last_errored = False
        if reprobe_steps is None:
            reprobe_steps = int(
                os.environ.get("TORCHFT_DDP_REPROBE_STEPS", "0")
            )
        # <= 0 disables: a locked schedule then only revalidates on a
        # quorum change (the pre-refresh behavior).
        self._reprobe_steps = max(int(reprobe_steps), 0)
        self._steps_since_lock = 0

    @property
    def mode(self) -> Optional[str]:
        """The locked mode, or None while probing."""
        return self._mode

    def _plan_device_pack(self) -> Optional[bool]:
        """device_pack for the "plan" candidate: host pack is pinned ONLY
        while a "plan_devpack" candidate is in the race (the auto probe
        needs the contrast); otherwise the caller's resolved setting
        applies — in particular TORCHFT_DEVICE_PACK=on under
        TORCHFT_DDP_MODE=auto device-packs the plan candidate itself."""
        if "plan_devpack" in self._candidates:
            return False
        return self._devpack_setting

    def _sharded(self) -> ShardedDDP:
        if self._sharded_engine is None:
            d = self._ddp
            shard_wire = {None: None, "bf16": "bf16", "q8": "q8"}[
                d._compress_mode
            ]
            self._sharded_engine = ShardedDDP(
                self._manager, d._state, d._grad_fn, shard_wire=shard_wire
            )
        return self._sharded_engine

    def _run_step(self, mode: str, *batch: Any) -> Any:
        d = self._ddp
        if mode != self._prev_run_mode:
            # Crossing the sharded tenure boundary is a trajectory change
            # for OPTIMIZER state (the two regimes hold it in different
            # shapes): entering drops the stale shard, leaving re-inits
            # the full state the unsharded engines update through
            # state.apply_gradients. Both resets are deterministic from
            # the (cohort-identical) params, so every member takes them
            # at the same step and cross-member identity holds — the
            # begin_fresh_window discipline, paid only at mode switches
            # (a pinned TORCHFT_DDP_MODE=ddp_sharded run never pays it).
            if mode == "ddp_sharded":
                self._sharded().begin_fresh_shard()
            elif self._prev_run_mode == "ddp_sharded":
                st = d._state
                st.opt_state = st.tx.init(st.params)
        self._prev_run_mode = mode
        if mode == "ddp_sharded":
            if d._inflight is not None:
                d.flush()  # settle any pipelined overlap before sharding
            s = self._sharded()
            loss = s.step(*batch)
            # the probe's error signal reads the shared engine's outcome
            d.last_commit = s.last_commit
            return loss
        if mode == "pipelined":
            d._transport = "legacy"
            if d._inflight is None:
                # Fresh pipeline: this step only dispatches (no settle),
                # so there is no outcome yet — clear the previous
                # candidate's settle verdict rather than inherit it.
                d.last_commit = None
            return d.step(*batch)
        # Blocking schedule (settle in-step); legacy, plan or iso
        # transport.
        if mode in ("plan", "plan_devpack", "plan_hier"):
            d._transport = "plan"
        elif mode == "xla_iso":
            d._transport = "iso"
        else:
            d._transport = "legacy"
        # The two-tier schedule is the plan_hier candidate's alone; every
        # other mode pins the flat ring (and hier has no device-pack
        # form, so the candidate always host-packs).
        d._hier = mode == "plan_hier"
        if mode == "plan_devpack":
            d._device_pack = True
        elif mode == "plan":
            d._device_pack = self._plan_device_pack()
        elif mode == "plan_hier":
            d._device_pack = False
        return d.blocking_step(*batch)

    def _decide(self) -> None:
        import numpy as np

        # Median per-step wall per candidate over its CLEAN samples: a
        # transient cohort error during one candidate's window (the
        # commit vote fails on every member for ANY peer's hiccup) must
        # not disqualify a working candidate — in particular it must
        # never knock out "blocking", or the probe could lock a mode
        # slower than blocking, the exact regression this class forbids.
        # Only a candidate with NO clean sample (it failed every timed
        # step — it cannot run here) carries the failure sentinel.
        def _candidate_s(samples: List[float]) -> float:
            clean = [t for t in samples if t < self._PROBE_FAILED_S]
            return float(np.median(clean)) if clean else self._PROBE_FAILED_S

        mine = np.array(
            [_candidate_s(t) for t in self._probe_t], np.float64
        )
        gathered = self._manager.allgather({"probe_t": mine}).wait()
        if self._manager.errored() is not None or any(
            np.asarray(e["probe_t"], np.float64).shape != mine.shape
            for e in gathered
        ):
            # The decision gather failed — OR the cohort's candidate
            # lists disagree (mismatched TORCHFT_DEVICE_PACK under auto,
            # or a member without the Pallas kernels: its probe vector
            # has a different length). Either way no cohort-agreed argmin
            # exists; lock the safe default. If it differs from another
            # member's choice, the mismatch errors, reconfigures, and the
            # quorum-id bump re-probes every member in lockstep.
            total = mine
            best = 0
        else:
            total = np.zeros_like(mine)
            for entry in gathered:
                total = total + np.asarray(entry["probe_t"], np.float64)
            # A non-participating member's entry was zeroed by the
            # managed gather (inf would have become nan); scrub any
            # residual non-finite before ranking.
            total = np.where(np.isfinite(total), total, self._PROBE_FAILED_S)
            # Identical data on every member -> identical argmin
            # everywhere. Ties pick the lowest index = "blocking", so the
            # locked mode is never slower than blocking as measured.
            best = int(np.argmin(total))
        self._mode = self._candidates[best]
        self._decision_qid = self._probe_qid
        self._steps_since_lock = 0
        self.decision = {
            "mode": self._mode,
            "probe_s": {
                c: round(float(total[i]), 6)
                for i, c in enumerate(self._candidates)
            },
            "quorum_id": self._decision_qid,
        }
        metrics = self._manager.metrics()
        for i, c in enumerate(self._candidates):
            metrics.record(f"ddp_probe_{c}", float(total[i]))
        metrics.incr(f"ddp_mode_{self._mode}")

    def _restart_probe(self, qid: Optional[int]) -> None:
        """Re-anchors the probe clock at the current transaction — every
        member observes a given quorum change at the same global step,
        so the schedules align by construction."""
        if self._ddp._inflight is not None:
            self._ddp.flush()
        self._mode = None
        self._probe_qid = qid
        self._probe_idx = 0
        self._probe_t = [[] for _ in self._candidates]

    def _observed_qid(self) -> Optional[int]:
        try:
            return self._manager.quorum_id()
        except Exception:  # noqa: BLE001 - quorum failed; next step retries
            return self._probe_qid

    def _note_errored(self, errored: bool) -> bool:
        """Tracks the consecutive-error run; True when the backstop just
        tripped (the caller locks blocking)."""
        if not errored:
            self._consec_errors = 0
            return False
        self._consec_errors += 1
        if self._consec_errors < self._error_backstop:
            return False
        if self._ddp._inflight is not None:
            self._ddp.flush()
        self._mode = "blocking"
        self._decision_qid = self._observed_qid()
        self.decision = {
            "mode": "blocking",
            "fallback": f"{self._consec_errors} consecutive errored "
                        "steps — locked the safe default",
            "quorum_id": self._decision_qid,
        }
        self._manager.metrics().incr("ddp_mode_blocking_backstop")
        self._consec_errors = 0
        self._steps_since_lock = 0
        return True

    def step(self, *batch: Any) -> Any:
        if self._mode is not None:
            loss = self._run_step(self._mode, *batch)
            if self._auto:
                errored = self._errored_now()
                clean = not errored and not self._last_errored
                self._last_errored = errored
                if self._note_errored(errored):
                    return loss
                qid = self._observed_qid()
                if qid != self._decision_qid:
                    if clean:
                        # Membership moved on a clean step (no pending
                        # reconfigure echo): every member sees the new id
                        # at this same step and re-probes in lockstep.
                        self._restart_probe(qid)
                        return loss
                    # The bump is (or may be) the echo of an errored
                    # step's forced reconfigure — track it, don't
                    # re-probe, or an error storm loops forever.
                    self._decision_qid = qid
                self._steps_since_lock += 1
                if (
                    self._reprobe_steps > 0
                    and self._steps_since_lock >= self._reprobe_steps
                    and clean
                ):
                    # Scheduled refresh: revalidate the locked argmin
                    # against CURRENT conditions (bandwidth may have moved
                    # without a membership change). Clean-after-clean only
                    # — fires at the same global step on every member, so
                    # the cohort re-enters the probe together; under a
                    # sustained error run the counter just keeps waiting
                    # (the backstop owns that regime).
                    self._manager.metrics().incr("ddp_reprobe")
                    self._restart_probe(qid)
            return loss

        # Probe phase: candidate = attempted steps since the anchor,
        # divided by probe_steps. Attempts advance identically on every
        # member between quorum changes (each step is one global
        # transaction), so the schedule stays lockstep even when steps
        # are discarded — and cannot stall on a candidate whose steps
        # never commit.
        idx = self._probe_idx
        cand = min(idx // self._probe_steps, len(self._candidates) - 1)
        mode = self._candidates[cand]
        t0 = time.perf_counter()
        loss = self._run_step(mode, *batch)
        elapsed = time.perf_counter() - t0
        errored = self._errored_now()
        clean = not errored and not self._last_errored
        self._last_errored = errored
        if self._note_errored(errored):
            return loss
        qid = self._observed_qid()
        if qid != self._probe_qid:
            if clean:
                # First step of a fresh cohort (or a membership change
                # landed mid-probe, with no reconfigure echo pending):
                # anchor the clock here — every member observes this
                # quorum id first at the same transaction — and time
                # nothing from the transition step.
                self._restart_probe(qid)
                return loss
            # Error (or its one-step echo): the id moved because a
            # data-plane failure forced a reconfigure. Track it without
            # re-anchoring and fall through to record this step.
            self._probe_qid = qid
        if idx % self._probe_steps != 0 or errored:
            # step 0 of each candidate is mode-switch warmup (jit caches,
            # plan build, pipeline fill) — never timed; errored steps
            # always record the failure sentinel (their wall time is
            # meaninglessly fast: the managed op resolved instantly to
            # its failure default), so a candidate that cannot run here
            # can never win the argmin.
            self._probe_t[cand].append(
                self._PROBE_FAILED_S if errored else elapsed
            )
        self._probe_idx += 1
        if self._probe_idx >= len(self._candidates) * self._probe_steps:
            if self._ddp._inflight is not None:
                self._ddp.flush()  # pipelined probe leaves one in flight
            self._decide()
        return loss

    def _errored_now(self) -> bool:
        """Whether the step that just ran failed its transaction. Reads
        the settle outcome PipelinedDDP records, NOT manager.errored():
        a pipelined step ends with start_quorum, which clears the
        manager's latched error before this runs (for pipelined the
        signal is the previous dispatch's settle — one step of lag, which
        the per-candidate warmup step already absorbs)."""
        return self._ddp.last_commit is False

    def flush(self) -> bool:
        """Settles any in-flight overlap step; call once after the loop."""
        return self._ddp.flush()
