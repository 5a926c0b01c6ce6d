"""Fault-tolerant LocalSGD and DiLoCo: communication-efficient data
parallelism across replica groups.

Reference: torchft/local_sgd.py. Inner steps run purely locally (no
cross-group traffic); every ``sync_every`` steps the groups synchronize
through the manager — a quorum + fault-tolerant allreduce + commit vote. On
a failed commit the whole window is discarded and parameters reset to the
last synchronized state, preserving exactly-``sync_every`` semantics
(reference local_sgd.py:35-46).

JAX shape: the reference hooks ``optimizer.step``; here the train loop calls
``local_sgd.step(grads)`` explicitly (optax has no hooks), which applies the
inner update and triggers ``sync()`` on the window boundary. The backup copy
stays ON DEVICE — the reference offloads it to pinned CPU memory
(local_sgd.py:81-91) because GPU memory is scarce, but on TPU a second
params copy is cheap HBM while every device↔host crossing rides the slow
link; an HBM↔HBM copy per window replaces two full-tree transfers. The
checkpoint transport converts to host only when a recovery peer actually
asks (checkpointing._to_host).

DiLoCo (https://arxiv.org/pdf/2311.08105): inner optimizer steps locally;
at the window boundary the *pseudogradient* Δ = θ_global_old − θ_local_new
is averaged across groups and fed to an outer optimizer (typically SGD with
Nesterov momentum) on the restored global params. Note the sign: this
follows the paper; the reference snapshot computes ``p.data - backup``
(local_sgd.py:214), the negation (fixed upstream later).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

import numpy as np

from .collectives import ReduceOp
from .manager import Manager
from .train_state import FTTrainState, _to_device_tree

logger: logging.Logger = logging.getLogger(__name__)


def _tree_leaves(tree: Any) -> Any:
    import jax

    return jax.tree_util.tree_leaves(tree)


_copy_jit: Any = None


def _detached_copy(tree: Any) -> Any:
    """Detached same-device copy of every array leaf (HBM→HBM for jax
    arrays — never crosses the host link); numpy leaves are copied on
    host. All-jax trees copy through ONE jitted program (one dispatch per
    window instead of one per leaf — eager per-leaf RPCs add up on remote
    device runtimes)."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(tree)
    if leaves and all(isinstance(l, jax.Array) for l in leaves):
        global _copy_jit
        if _copy_jit is None:
            # jit outputs never alias non-donated inputs: fresh buffers.
            _copy_jit = jax.jit(
                lambda t: jax.tree_util.tree_map(jnp.copy, t)
            )
        return _copy_jit(tree)
    return jax.tree_util.tree_map(
        lambda l: l.copy() if isinstance(l, jax.Array) else np.array(l), tree
    )


class LocalSGD:
    """Periodic parameter averaging (https://arxiv.org/pdf/1805.09767),
    fault-tolerant. Reference local_sgd.py:26-174.

    Usage::

        local = LocalSGD(manager, state, sync_every=32)
        for batch in data:
            grads = grad_fn(state.params, batch)
            local.step(grads)           # inner update; syncs every 32 steps

    Wire the manager's state callbacks to :meth:`state_dict` /
    :meth:`load_state_dict` (NOT the bare train state) so recovering
    replicas receive the backup copy and sync bookkeeping too.
    """

    def __init__(self, manager: Manager, state: FTTrainState, sync_every: int) -> None:
        assert sync_every >= 1, "sync_every must be >= 1"
        self._manager = manager
        self._state = state
        self._sync_every = sync_every
        self._local_step = 0
        # On-device backup of the last synchronized params (role of the
        # reference's CPU backup, :81-95; see module docstring).
        self._backup_params: Any = _detached_copy(state.params)
        # Outcome of the most recent window sync (None before the first):
        # the sync's commit vote happens inside _perform_sync, so without
        # this record a wrapper (the policy engine) could not tell a
        # committed window from a rolled-back one.
        self.last_sync_commit: Optional[bool] = None

    # -- train-loop surface --

    def step(self, grads: Any) -> None:
        """One inner optimizer step; synchronizes on the window boundary
        (the reference's optimizer post-hook, local_sgd.py:133-141)."""
        self._state.apply_gradients(grads)
        self.step_applied()

    def step_applied(self) -> None:
        """Window accounting for a caller that already applied the inner
        update itself — e.g. a FUSED grad+apply train step
        (models.make_train_step), one program launch instead of two and
        measured ~8% faster per inner step on v5e at the 111M-param
        config. Inner steps have no per-step cross-group work, so the
        LocalSGD family only needs the count::

            train_step = make_train_step(cfg, optax.adamw(1e-3))
            for batch in data:
                state.params, state.opt_state, loss = train_step(
                    state.params, state.opt_state, batch)
                local.step_applied()      # syncs every sync_every steps
        """
        self._local_step += 1
        if self._local_step >= self._sync_every:
            self.sync()

    def sync(self) -> None:
        """Synchronizes across replica groups. Reference local_sgd.py:143-149."""
        self._manager.start_quorum()
        self._perform_sync()
        self._local_step = 0

    def begin_fresh_window(self) -> None:
        """Re-anchors the window at the CURRENT params: the backup becomes
        the live params and the inner-step count restarts. The policy
        engine's strategy-entry hook — when a runtime strategy switch
        hands control to this engine mid-run, the first window's rollback
        / pseudogradient baseline must be the switch point, not a stale
        snapshot from this engine's last tenure. DiLoCo outer-optimizer
        state is deliberately NOT touched (momentum survives a strategy
        round trip; membership drift is handled by the quorum-id-keyed
        reshard machinery at the next sync)."""
        self._backup_params = _detached_copy(self._state.params)
        self._local_step = 0
        self.last_sync_commit = None

    # -- checkpoint plumbing (manager state callbacks) --

    def state_dict(self) -> Dict[str, Any]:
        return {
            "state": self._state.state_dict(),
            "backup_params": self._backup_params,
            "local_step": self._local_step,
        }

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        self._state.load_state_dict(sd["state"])
        # Checkpoints deliver numpy leaves; bring the backup to device.
        self._backup_params = _to_device_tree(sd["backup_params"])
        self._local_step = sd["local_step"]

    # -- internals --

    def _save_parameters(self) -> None:
        self._backup_params = _detached_copy(self._state.params)

    def _restore_parameters(self) -> None:
        # COPY, never alias: FTTrainState.apply_gradients donates its
        # params buffers, so handing the backup itself to state.params
        # would let the next inner step delete the backup.
        self._state.params = _detached_copy(self._backup_params)

    def _perform_sync(self) -> None:
        """Average params; commit -> new backup, abort -> roll the whole
        window back (reference local_sgd.py:151-162)."""
        averaged = self._manager.allreduce(
            self._state.params, op=ReduceOp.AVG
        ).wait()
        committed = self._manager.should_commit()
        self.last_sync_commit = committed
        if committed:
            self._state.params = averaged
            self._save_parameters()
        else:
            self._restore_parameters()


class DiLoCo(LocalSGD):
    """Distributed Low-Communication training. Reference local_sgd.py:177-239.

    Requires sync quorum (``use_async_quorum=False``) so a recovering
    replica restores the checkpoint before its first inner step (reference
    :195-199).

    ``sharded=True`` replaces the outer sync's "full allreduce + W
    redundant outer updates" with the weight-update-sharded schedule of
    "Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
    Training" (PAPERS.md #1): reduce-scatter the pseudogradient (stop the
    collective at the reduce-scatter boundary), run the outer optimizer on
    the ~1/W shard this replica owns, then allgather the *updated
    parameters*. One logical sync, outer-optimizer FLOPs/memory shrunk ~W×
    (the Nesterov momentum is sharded across the cohort), and the h2d
    return leg of the reduction carries 1/W of the model. On a membership
    change (join/leave/heal — detected via the manager's quorum id) the
    sharded outer state is re-partitioned: every member scatters its old
    shard into a full-size buffer, the cohort allgathers them, and each
    member slices its new shard; slices owned by a departed replica
    restart cold (zeros — one window of momentum, self-healing).
    Constraints: the outer optimizer must be ELEMENTWISE (SGD/Nesterov —
    the standard DiLoCo outer — is; a global-norm-clipping chain is not,
    it would see per-shard norms), and master params should be f32.

    ``shard_wire="q8"`` ships the reduce-scatter over the int8-quantized
    ring wire with device-side error feedback (the quantization residual
    joins the next window's delta); the averaged shard still lands in
    full f32 — the fused q8 op's lossy allgather phase never runs.
    ``param_wire="bf16"`` rounds the parameter allgather to bfloat16
    (half its bytes; every member — including each shard's owner — adopts
    the decoded bf16 words, so params stay bit-identical across the
    cohort).

    ``hier=True`` (unsharded only) rides the outer pseudogradient
    average over the TOPOLOGY-AWARE two-tier schedule
    (``Manager.allreduce_hier``): on a region-labeled cohort the slow
    inter-region links carry a fraction of the flat ring's bytes, on
    the leaders only. ``hier_wire`` (``None`` | ``"bf16"`` | ``"q8"``)
    compresses the inter hop only, so the once-per-window quantization
    noise is paid exactly where the bandwidth is scarce. On a cohort
    without a usable region map the sync latches an error and the
    window is discarded (retry next window) — pin ``hier`` only on
    fleets actually deployed across regions."""

    def __init__(
        self,
        manager: Manager,
        state: FTTrainState,
        outer_tx: Any,
        sync_every: int,
        sharded: bool = False,
        shard_wire: Optional[str] = None,
        param_wire: Optional[str] = None,
        hier: bool = False,
        hier_wire: Optional[str] = None,
    ) -> None:
        if manager._use_async_quorum:
            raise ValueError(
                "DiLoCo requires synchronous quorum: construct the Manager "
                "with use_async_quorum=False"
            )
        if shard_wire not in (None, "q8"):
            raise ValueError(f"unsupported shard_wire: {shard_wire!r}")
        if param_wire not in (None, "bf16"):
            raise ValueError(f"unsupported param_wire: {param_wire!r}")
        if (shard_wire or param_wire) and not sharded:
            raise ValueError("shard_wire/param_wire require sharded=True")
        if hier_wire not in (None, "bf16", "q8"):
            raise ValueError(f"unsupported hier_wire: {hier_wire!r}")
        if hier_wire is not None and not hier:
            raise ValueError("hier_wire requires hier=True")
        if hier and sharded:
            raise ValueError(
                "hier=True composes with the unsharded outer sync only "
                "(the sharded schedule's shard layout is the FLAT ring's)"
            )
        if sharded:
            # The shard must pack into ONE flat group: the outer-state
            # re-partition after a membership change identifies shard-
            # shaped state leaves by size, which is only unambiguous for
            # a single group. Mixed-dtype masters would split into
            # per-dtype groups and stall the first post-change sync, so
            # reject them at construction, not mid-run.
            bad = {
                str(np.dtype(l.dtype))
                for l in _tree_leaves(state.params)
                if np.dtype(l.dtype) != np.dtype(np.float32)
            }
            if bad:
                raise ValueError(
                    "sharded DiLoCo requires f32 master params (found "
                    f"{sorted(bad)}); keep masters in f32 and use "
                    "shard_wire/param_wire for wire compression"
                )
        super().__init__(manager, state, sync_every)
        self._outer_tx = outer_tx
        self._sharded = sharded
        self._hier = hier
        self._hier_wire = hier_wire
        self._shard_wire = shard_wire
        self._param_wire = param_wire
        if sharded:
            # Outer state is built lazily at the first sync, over the shard
            # this replica owns under the quorum's partition (unknowable
            # before the first quorum forms).
            self._outer_state: Any = None
            self._outer_shard_meta: Optional[Dict[str, Any]] = None
        else:
            self._outer_state = outer_tx.init(state.params)
            self._outer_shard_meta = None
        self._shard_residual: Any = None  # q8 wire error-feedback carry
        self._quant_fn: Any = None
        self._slice_fns: Dict[Any, Any] = {}

    def state_dict(self) -> Dict[str, Any]:
        sd = super().state_dict()
        sd["outer_state"] = self._outer_state
        if self._sharded:
            sd["outer_shard_meta"] = self._outer_shard_meta
        return sd

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        super().load_state_dict(sd)
        self._outer_state = (
            _to_device_tree(sd["outer_state"])
            if sd["outer_state"] is not None
            else None
        )
        if self._sharded:
            # The restored shard is the SOURCE replica's (a heal copies the
            # peer's state verbatim); keep its meta so the next re-shard
            # scatters it at the right positions, and force a re-partition
            # by voiding the quorum id — this replica's join bumped it
            # anyway.
            meta = sd.get("outer_shard_meta")
            if meta is not None:
                meta = dict(meta, quorum_id=-1)
            self._outer_shard_meta = meta
        # Error-feedback carry is trajectory-local: after a heal/restore
        # the replica is on another trajectory's params, so a stale
        # residual would inject a fraction of a discarded correction.
        self._shard_residual = None

    def begin_fresh_window(self) -> None:
        # Strategy re-entry is a trajectory change for the EF carry (the
        # residual belongs to deltas another strategy superseded), not for
        # the outer state (momentum legitimately survives — see LocalSGD).
        super().begin_fresh_window()
        self._shard_residual = None

    def _perform_sync(self) -> None:
        """Sharded: RS → outer step on the owned shard → param allgather.
        Unsharded: average pseudogradients, outer-step from the restored
        global params on commit (reference local_sgd.py:205-225)."""
        if self._sharded:
            self._perform_sync_sharded()
            return
        import jax
        import optax

        old_global = _to_device_tree(self._backup_params)
        # Paper sign: Δ = θ_global_old − θ_local_new, so the outer optimizer
        # descends toward the inner-trained weights.
        pseudo_grads = jax.tree_util.tree_map(
            lambda old, new: old - new, old_global, self._state.params
        )
        if self._hier:
            # Topology-aware outer sync: intra-region rings + the
            # inter-region leader ring, with hier_wire compressing the
            # slow hop only. Managed discipline is allreduce's own — an
            # un-hierarchical cohort latches and the window is discarded.
            averaged = self._manager.allreduce_hier(
                pseudo_grads, op=ReduceOp.AVG, wire=self._hier_wire
            ).wait()
        else:
            averaged = self._manager.allreduce(
                pseudo_grads, op=ReduceOp.AVG
            ).wait()

        # Restore to the last global state before applying the outer step.
        # Copy: state.params buffers get donated by the next inner step,
        # and old_global aliases the on-device backup.
        self._state.params = _detached_copy(old_global)

        committed = self._manager.should_commit()
        self.last_sync_commit = committed
        if committed:
            updates, self._outer_state = self._outer_tx.update(
                averaged, self._outer_state, self._state.params
            )
            self._state.params = optax.apply_updates(
                self._state.params, updates
            )
            self._save_parameters()

    # -- sharded outer sync --

    def _perform_sync_sharded(self) -> None:
        """reduce-scatter(Δ) → outer step on the owned shard → allgather
        the updated params. All three legs ride the manager's error
        discipline: any failure latches, the commit vote fails, and every
        member rolls the window back — committed-or-discarded, same as the
        fused path."""
        import jax
        import optax

        old_global = _to_device_tree(self._backup_params)
        if self._shard_wire == "q8":
            ship, new_residual = self._quantized_delta(old_global)
        else:
            ship = jax.tree_util.tree_map(
                lambda old, new: old - new, old_global, self._state.params
            )
            new_residual = None
        rs_work = self._manager.reduce_scatter(
            ship, op=ReduceOp.AVG, wire=self._shard_wire
        )

        # Restore to the last global state while the ring runs (copy:
        # inner steps donate params buffers, old_global aliases the
        # backup).
        self._state.params = _detached_copy(old_global)

        shard = rs_work.wait()  # TreeShard | None (failure default)
        gathered = None
        new_outer = None
        new_meta = None
        if shard is not None:
            try:
                qid = self._manager.quorum_id()
                outer_state = self._outer_state_for(shard, qid, old_global)
                g_shard = self._slice_params(old_global, shard)
                updates, new_outer = self._outer_tx.update(
                    shard.values, outer_state, g_shard
                )
                new_vals = optax.apply_updates(g_shard, updates)
                gathered = self._manager.allgather_into(
                    shard.replace_values(new_vals), wire=self._param_wire
                ).wait()
                new_meta = {
                    "quorum_id": qid,
                    "counts": dict(shard.counts),
                    "ranges": {k: list(v) for k, v in shard.ranges.items()},
                }
            except Exception as e:  # noqa: BLE001 - latch, vote, roll back
                logger.exception("sharded outer step failed: %s", e)
                self._manager.report_error(e)
                gathered = None

        committed = self._manager.should_commit() and gathered is not None
        self.last_sync_commit = committed
        if committed:
            self._state.params = _to_device_tree(gathered)
            self._outer_state = new_outer
            self._outer_shard_meta = new_meta
            if new_residual is not None:
                self._shard_residual = new_residual
            self._save_parameters()
        # abort: params already restored; outer state, its meta, and the
        # error-feedback carry keep their pre-window values.

    def _quantized_delta(self, old_global: Any) -> Any:
        """Δ = B − θ with int8-grid error feedback: the residual of the
        grid rounding joins the next window's delta, so wire quantization
        error never accumulates (the carry is committed only when the
        window commits)."""
        import jax
        import jax.numpy as jnp

        if self._shard_residual is None:
            self._shard_residual = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, jnp.float32),
                self._state.params,
            )
        if self._quant_fn is None:
            from .quantize import quantize_with_feedback

            def quant_fn(old, new, residual):
                delta = jax.tree_util.tree_map(lambda o, n: o - n, old, new)
                return quantize_with_feedback(delta, residual)

            self._quant_fn = jax.jit(quant_fn)
        out = self._quant_fn(
            old_global, self._state.params, self._shard_residual
        )
        # Ship the leaf-gridded f32 delta: EF accounts for this grid; the
        # ring's per-hop requantization noise stays at the int8 class.
        return out["dq"], out["res"]

    def _outer_state_for(self, shard: Any, qid: int, old_global: Any) -> Any:
        """The outer-optimizer state matching ``shard``'s partition:
        reused when the quorum (and so the partition) is unchanged,
        initialized fresh at the first sync, re-partitioned through a
        cohort allgather after a membership change."""
        meta = self._outer_shard_meta
        if (
            self._outer_state is not None
            and meta is not None
            and meta["quorum_id"] == qid
            and meta["counts"] == shard.counts
            and {k: list(v) for k, v in shard.ranges.items()}
            == {k: list(v) for k, v in meta["ranges"].items()}
        ):
            return self._outer_state
        if self._outer_state is None:
            # First sync of a fresh run: init over the owned param shard.
            return self._outer_tx.init(self._slice_params(old_global, shard))
        return self._reshard_outer_state(shard)

    def _slice_params(self, tree: Any, shard: Any) -> Dict[str, Any]:
        """Packs ``tree`` into the shard's flat layout and slices this
        rank's owned ranges — on device for jax trees (the full params
        never cross to host for this), host-side otherwise."""
        import jax

        leaves = jax.tree_util.tree_leaves(tree)
        all_jax = leaves and all(
            isinstance(l, jax.Array) for l in leaves
        )
        out: Dict[str, Any] = {}
        for name in sorted(shard.counts):
            rng = tuple(tuple(r) for r in shard.ranges[name])
            if all_jax and shard.packer is not None:
                key = (name, rng)
                fn = self._slice_fns.get(key)
                if fn is None:
                    import jax.numpy as jnp

                    packer = shard.packer

                    def slice_fn(ls, _name=name, _rng=rng, _packer=packer):
                        flat = _packer.pack(ls)[_name]
                        return jnp.concatenate(
                            [flat[s: s + l] for s, l in _rng]
                        )

                    fn = self._slice_fns[key] = jax.jit(slice_fn)
                out[name] = fn(leaves)
            else:
                idxs = shard.groups[name]
                flat = np.concatenate(
                    [
                        np.asarray(leaves[i])
                        .astype(np.dtype(shard.dtypes[name]), copy=False)
                        .ravel()
                        for i in idxs
                    ]
                )
                out[name] = np.concatenate(
                    [flat[s: s + l] for s, l in rng]
                ) if len(rng) != 1 or rng[0] != (0, flat.size) else flat
        return out

    def _reshard_outer_state(self, shard: Any) -> Any:
        """Re-partitions the sharded outer state after a membership
        change: every member scatters its OLD shard of each param-shaped
        state leaf into a full-size (vals, mask) pair, the cohort
        allgathers them, and this member slices its NEW ranges out of the
        first-owner-wins merge. Positions no surviving member owned (a
        departed replica took its shard with it) restart at zero — a
        one-window momentum cold start on 1/W_old of the model."""
        import jax

        meta = self._outer_shard_meta
        assert meta is not None
        (name,) = list(shard.counts)  # sharded mode packs ONE f32 group
        count = shard.counts[name]
        old_ranges = [tuple(r) for r in meta["ranges"][name]]
        old_len = sum(l for _, l in old_ranges)

        state_leaves, state_def = jax.tree_util.tree_flatten(
            self._outer_state
        )
        shard_like = [
            i
            for i, l in enumerate(state_leaves)
            if getattr(l, "ndim", None) == 1 and l.size == old_len
        ]
        mask = np.zeros(count, np.uint8)
        scattered = []
        for s, ln in old_ranges:
            mask[s: s + ln] = 1
        for i in shard_like:
            arr = np.asarray(state_leaves[i]).astype(np.float32)
            full = np.zeros(count, np.float32)
            off = 0
            for s, ln in old_ranges:
                full[s: s + ln] = arr[off: off + ln]
                off += ln
            scattered.append(full)
        payload = {"m": mask, "v": scattered}
        members = self._manager.allgather(payload).wait()

        import jax.numpy as jnp

        new_leaves = list(state_leaves)
        for j, i in enumerate(shard_like):
            acc = np.zeros(count, np.float32)
            seen = np.zeros(count, bool)
            for m in members:
                mm = np.asarray(m["m"]).astype(bool)
                take = mm & ~seen
                if take.any():
                    acc[take] = np.asarray(m["v"][j], dtype=np.float32)[take]
                    seen |= take
            new_shard = np.concatenate(
                [acc[s: s + ln] for s, ln in shard.ranges[name]]
            )
            new_leaves[i] = jnp.asarray(new_shard)
        return jax.tree_util.tree_unflatten(state_def, new_leaves)


class AsyncDiLoCo(DiLoCo):
    """DiLoCo with the cross-group sync OVERLAPPED with the next window's
    inner steps (the delayed/eager outer-update idea of Streaming DiLoCo,
    https://arxiv.org/pdf/2501.18512): at a window boundary the
    pseudogradient allreduce is *launched* asynchronously and training
    continues immediately; the outer update is applied one window late,
    reconciled against the inner progress made in the meantime.

    This is the bandwidth-appropriate cross-replica-group mode on TPU pods:
    the host ring rides DCN at a fraction of step time only if it can hide
    behind compute, and inner steps never leave the chip. Let B be the last
    global params, θ the live params. At boundary k:

      1. finish window k-1's in-flight sync (below),
      2. compute Δ = B − θ, launch ``allreduce(Δ)`` (device→host packing and
         ring transfer run on the collectives' op thread), keep training.

    When the result lands (checked at boundary k+1):
      commit → G' = outer_update(B, Δ_avg);  θ += G' − (B − Δ);  B = G'
               (replaces window k's local-only progress with the
               globally-agreed version, keeping window k+1's progress)
      abort  → θ += Δ   (rolls back window k, keeps window k+1's progress)

    With a single group and outer SGD(lr=1), G' = B − Δ and the correction
    vanishes — AsyncDiLoCo degenerates to pure local training, the identity
    the unit tests pin. Inherits DiLoCo's sync-quorum requirement for heal
    correctness; call :meth:`flush` before checkpointing or shutdown so no
    window is left in flight."""

    def __init__(
        self,
        manager: Manager,
        state: FTTrainState,
        outer_tx: Any,
        sync_every: int,
        compress: Any = None,
        overlap: bool = True,
    ) -> None:
        """``compress="bf16"`` casts pseudogradients to bfloat16 on-device
        before the allreduce — halving device→host, wire (native bf16
        dtype), and host→device bytes. Standard DiLoCo practice: the outer
        optimizer sees bf16-rounded pseudogradients, the f32 master params
        are untouched.

        Quantized modes (both: per-leaf int8 with a f32 scale and ERROR
        FEEDBACK — the quantization residual is added to the next
        window's delta, so rounding error never accumulates). Two
        transports for two bottlenecks:

        ``compress="int8"``: the int8 payload itself ({q, scale} leaves)
        rides a managed device-packed ALLGATHER and is dequantize-averaged
        member-wise — the DEVICE<->HOST link carries int8 bytes (4x fewer
        than f32, 2x fewer than bf16), for hosts where that link is the
        bottleneck. Allgather traffic grows with cohort size; intended
        for small cohorts.

        ``compress="q8"``: the dequantized (int8-gridded f32) delta rides
        the native ring's quantized wire (int8 chunks with per-chunk
        scales, dequant-accumulated per hop): TCP sync bytes are CONSTANT
        in cohort size, for DCN deployments where the network is the
        bottleneck and cohorts are larger. The ring's per-chunk regrid
        adds at most one quantization step of noise, which the next
        window's error feedback does not see (documented lossy wire).

        ``overlap=False`` completes the sync AT the boundary instead of one
        window later (the reconciliation degenerates to θ = G', i.e. exact
        synchronous DiLoCo, but through the same jitted ops). Use it on
        hosts where device↔host transfers contend with compute dispatch:
        there, an in-flight transfer under a stream of async dispatches
        can starve for far longer than its serial wall time, and a
        blocking boundary sync is strictly faster."""
        if compress not in (None, "bf16", "int8", "q8"):
            raise ValueError(f"unsupported compress mode: {compress}")
        super().__init__(manager, state, outer_tx, sync_every)
        self._compress = compress
        self._overlap = overlap
        # (work, shipped delta, pre-launch residual) of the in-flight window
        self._pending: Any = None
        self._delta_fn: Any = None  # jitted Δ = B − θ (with optional cast)
        self._commit_fn: Any = None  # jitted delayed outer update + reconcile
        self._abort_fn: Any = None  # jitted window rollback
        self._quant_fn: Any = None    # int8/q8: jitted quantize + EF update
        self._combine_fns: Dict[int, Any] = {}  # int8: per-cohort avg
        self._residual: Any = None    # int8/q8: error-feedback carry

    def sync(self) -> None:
        self._finish_pending()
        self._manager.start_quorum()
        self._launch_sync()
        if not self._overlap:
            self._finish_pending()
        self._local_step = 0

    def flush(self) -> None:
        """Completes any in-flight window sync (call before reading final
        params, checkpointing durably, or shutdown)."""
        self._finish_pending()

    def begin_fresh_window(self) -> None:
        # An overlapped sync still in flight belongs to the OLD tenure's
        # trajectory: settle it before re-anchoring, and drop the int8 EF
        # carry with it.
        self._finish_pending()
        super().begin_fresh_window()
        self._residual = None

    def state_dict(self) -> Dict[str, Any]:
        self._finish_pending()
        return super().state_dict()

    def load_state_dict(self, sd: Dict[str, Any]) -> None:
        super().load_state_dict(sd)
        # The int8 error-feedback carry is trajectory-local: after a heal
        # or durable restore the replica is on ANOTHER trajectory's
        # params, so the stale residual would inject a fraction of a
        # discarded correction into the next window. Reset it (a clean
        # restart's state).
        self._residual = None

    def _launch_sync(self) -> None:
        import time

        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        old_global = _to_device_tree(self._backup_params)

        if self._compress in ("int8", "q8"):
            if self._residual is None:
                self._residual = jax.tree_util.tree_map(
                    lambda l: jnp.zeros(l.shape, jnp.float32),
                    self._state.params,
                )
            if self._quant_fn is None:
                from .quantize import quantize_with_feedback

                def quant_fn(old, new, residual):
                    delta = jax.tree_util.tree_map(
                        lambda o, n: o - n, old, new
                    )
                    return quantize_with_feedback(delta, residual)

                self._quant_fn = jax.jit(quant_fn)

            prev_residual = self._residual
            out = self._quant_fn(
                old_global, self._state.params, prev_residual
            )
            self._residual = out["res"]  # EF carry (restored on abort)
            if self._compress == "int8":
                # int8 BYTES cross the device link (device-packed
                # allgather); the finish side dequantize-averages
                work = self._manager.allgather(
                    {"q": out["q"], "scale": out["scale"]}
                )
            else:
                # q8: ship the DEQUANTIZED delta over the ring's
                # quantized wire — the values are already on the int8
                # grid leaf-wise (EF accounts for that rounding); the
                # ring re-grids per chunk and returns the averaged f32
                # tree directly, constant TCP bytes in cohort size
                work = self._manager.allreduce(
                    out["dq"], op=ReduceOp.AVG, wire="q8"
                )
            # reconcile against what we actually SHIPPED (the dequantized
            # local delta), same role as the bf16-rounded delta below
            self._pending = (work, out["dq"], prev_residual)
            logger.debug(
                "int8 sync launched in %.2fs", time.perf_counter() - t0
            )
            return

        if self._delta_fn is None:
            wire_dtype = jnp.bfloat16 if self._compress == "bf16" else None

            def delta_fn(old, new):
                return jax.tree_util.tree_map(
                    lambda o, n: (o - n).astype(wire_dtype)
                    if wire_dtype is not None
                    else o - n,
                    old,
                    new,
                )

            self._delta_fn = jax.jit(delta_fn)

        delta = self._delta_fn(old_global, self._state.params)
        work = self._manager.allreduce(delta, op=ReduceOp.AVG)
        self._pending = (work, delta, None)
        logger.debug(
            "sync launched in %.2fs", time.perf_counter() - t0
        )

    def _finish_pending(self) -> None:
        import time

        import jax
        import optax

        if self._pending is None:
            return
        work, delta, prev_residual = self._pending
        self._pending = None
        t0 = time.perf_counter()
        result = work.wait()
        logger.debug("sync ring wait %.2fs", time.perf_counter() - t0)
        t0 = time.perf_counter()
        if self._compress == "int8":
            # member-wise dequantize, then average over PARTICIPANTS:
            # non-participating (healing/spare) entries arrive zeroed
            # (Manager.allgather) and must not dilute the divisor
            import jax.numpy as jnp

            cohort = len(result)
            combine = self._combine_fns.get(cohort)
            if combine is None:
                from .quantize import make_dequant_average

                combine = self._combine_fns[cohort] = \
                    make_dequant_average()
            averaged = combine(
                result,
                jnp.float32(max(self._manager.num_participants(), 1)),
            )
        else:
            # bf16 / q8 / plain: the wire returns the averaged delta tree
            averaged = result
        old_global = _to_device_tree(self._backup_params)

        if self._commit_fn is None:
            outer_tx = self._outer_tx

            def commit_fn(avg, glob, dlt, outer_state, theta):
                # Upcast the (possibly bf16) averaged pseudogradient to the
                # master param dtype before the outer update.
                avg = jax.tree_util.tree_map(
                    lambda a, g: a.astype(g.dtype), avg, glob
                )
                updates, new_outer = outer_tx.update(avg, outer_state, glob)
                new_global = optax.apply_updates(glob, updates)
                # θ += G' − L0 where L0 = B − Δ is the launch point: window
                # k's local-only progress is replaced by the agreed version,
                # window k+1's progress (already in θ) is kept.
                new_theta = jax.tree_util.tree_map(
                    lambda th, g, b, d: th + (g - (b - d.astype(th.dtype))),
                    theta, new_global, glob, dlt,
                )
                return new_theta, new_global, new_outer

            def abort_fn(theta, dlt):
                return jax.tree_util.tree_map(
                    lambda th, d: th + d.astype(th.dtype), theta, dlt
                )

            self._commit_fn = jax.jit(commit_fn)
            self._abort_fn = jax.jit(abort_fn)
        logger.debug(
            "sync reconcile prep %.2fs", time.perf_counter() - t0
        )

        t0 = time.perf_counter()
        committed = self._manager.should_commit()
        self.last_sync_commit = committed
        if committed:
            self._state.params, new_global, self._outer_state = self._commit_fn(
                averaged, old_global, delta, self._outer_state,
                self._state.params,
            )
            self._backup_params = _detached_copy(new_global)
            logger.debug(
                "sync commit apply+backup %.2fs", time.perf_counter() - t0
            )
        else:
            # Window k discarded; window k+1's local progress survives.
            self._state.params = self._abort_fn(self._state.params, delta)
            if prev_residual is not None:
                # discard the aborted window's EF update with it
                self._residual = prev_residual
            logger.debug(
                "sync abort rollback %.2fs", time.perf_counter() - t0
            )
