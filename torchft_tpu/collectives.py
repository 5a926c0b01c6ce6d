"""Reconfigurable collective communication for cross-replica-group traffic.

Plays the role of the reference's reconfigurable ProcessGroup abstraction
(reference torchft/process_group.py:109-166): a ``Collectives`` object can be
``configure()``d onto a new membership every time the quorum changes, using a
per-quorum store prefix so stale members never cross-talk (reference
torchft/manager.py:470-477).

TPU-first design: these collectives deliberately run on the HOST, outside
XLA. Intra-replica-group parallelism (the HSDP "shard" dimension) belongs to
pjit/``shard_map`` over the slice's ICI mesh and never spans a failure
domain; only the cross-group gradient average travels through this layer
(over DCN in production). Because the transport is plain sockets, a dead
replica group surfaces as an abortable socket error instead of a wedged
device collective — the property the reference buys with subprocess-isolated
NCCL ("Baby" process groups, reference torchft/process_group.py:551-1064).

Ops are asynchronous: each returns a :class:`Work` whose result is the
reduced pytree. A single-thread executor issues ops in submission order (the
ordering contract collective backends require), and the GIL is released for
the duration of each native call.
"""

from __future__ import annotations

import ctypes
import json
import os
import threading
from abc import ABC, abstractmethod
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, replace
from datetime import timedelta
from enum import IntEnum
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import _native
from ._native import _check, _lib, _ms
from .profiling import span, timed_span


class ReduceOp(IntEnum):
    """Matches tft::ReduceOp in native/src/collectives.h. AVG is SUM followed
    by a host-side divide (the reference divides in the manager too,
    torchft/manager.py:279-291)."""

    SUM = 0
    PRODUCT = 1
    MIN = 2
    MAX = 3
    AVG = 100


# Native dtype codes (tft::Dtype). Other dtypes (e.g. f16) are accumulated
# in f32 and cast back. bfloat16 ships natively — 2 bytes on the wire, half
# the DCN traffic of an f32 upcast; reduction math is f32 per ring hop with
# round-to-nearest-even back to bf16 (for long-chain exact accumulation,
# cast leaves to f32 before the allreduce).
import ml_dtypes

_BF16 = np.dtype(ml_dtypes.bfloat16)
_NATIVE_DTYPES = {
    np.dtype(np.float32): 0,
    np.dtype(np.float64): 1,
    np.dtype(np.int32): 2,
    np.dtype(np.int64): 3,
    _BF16: 4,
}


class Work:
    """Handle for an async collective; the result is the output pytree.

    Mirrors the role of torch.distributed Work / torch futures in the
    reference (torchft/process_group.py:318-330).
    """

    def __init__(self, future: "Future[Any]", metrics: Any = None) -> None:
        self._future = future
        # the owning Manager's Metrics, where there is one: ``wait`` then
        # also feeds its ``work_wait`` timer
        self._metrics = metrics

    def wait(self, timeout: Optional[timedelta] = None) -> Any:
        seconds = timeout.total_seconds() if timeout is not None else None
        if self._future.done():  # nothing to wait for: no span, no sample
            return self._future.result(timeout=seconds)
        # The caller's thread blocked on a collective: the exposed
        # synchronisation of every schedule (they all end in ``.wait()``).
        with (
            self._metrics.timed("work_wait") if self._metrics is not None
            else span("torchft::work_wait")
        ):
            return self._future.result(timeout=seconds)

    def result(self, timeout: Optional[timedelta] = None) -> Any:
        return self.wait(timeout)

    def done(self) -> bool:
        return self._future.done()

    def exception(self) -> Optional[BaseException]:
        return self._future.exception()

    def add_done_callback(self, fn: Callable[["Future[Any]"], None]) -> None:
        self._future.add_done_callback(fn)

    def then(self, fn: Callable[[Any], Any]) -> "Work":
        """Returns a Work whose result is fn(result); errors propagate."""
        out: "Future[Any]" = Future()

        def _chain(f: "Future[Any]") -> None:
            exc = f.exception()
            if exc is not None:
                out.set_exception(exc)
                return
            try:
                out.set_result(fn(f.result()))
            except Exception as e:  # noqa: BLE001 - propagate into future
                out.set_exception(e)

        self._future.add_done_callback(_chain)
        return Work(out, self._metrics)


def _completed(value: Any) -> Work:
    f: "Future[Any]" = Future()
    f.set_result(value)
    return Work(f)


def _divide_leaf(leaf: Any, divisor: float) -> Any:
    """Same-dtype divide for the divisor/AVG contract: integers
    floor-divide (matching the multi-member ring), floats keep their
    dtype. Handles numpy and jax leaves alike."""
    dtype = np.dtype(getattr(leaf, "dtype", np.float64))
    if np.issubdtype(dtype, np.integer):
        return leaf // int(divisor)
    return (leaf / divisor).astype(dtype)


def _flatten(tree: Any) -> Tuple[List[Any], Any]:
    """Flatten a pytree without importing jax at module load."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def _unflatten(treedef: Any, leaves: Sequence[Any]) -> Any:
    import jax

    return jax.tree_util.tree_unflatten(treedef, leaves)


@dataclass
class TreeShard:
    """This rank's shard of a flat-packed pytree, the unit the sharded
    (split) collectives trade in.

    ``reduce_scatter`` returns one; ``allgather_into`` consumes one. The
    pytree is packed into one contiguous flat buffer per accumulation-dtype
    group (the same grouping the fused allreduce uses, or a single f32
    group on the q8 wire), and the shard is the union of the per-stripe
    ring chunks this rank owns, compacted in stripe order. ``values`` is
    what a caller updates in place of the full tree (the weight-update
    sharding of PAPERS.md #1: outer-optimizer state and FLOPs scale with
    the shard, not the model); everything else is layout bookkeeping that
    must ride along unchanged so ``allgather_into`` can scatter the
    updated shard back to the identical wire schedule on every member.
    """

    # group name -> this rank's flat shard (jax or numpy array)
    values: Dict[str, Any]
    # group name -> total flat elements of the group's full buffer
    counts: Dict[str, int]
    # group name -> [(start, len)] element ranges this rank owns, in
    # compaction order (global positions within the group's flat buffer)
    ranges: Dict[str, List[Tuple[int, int]]]
    # group name -> the stripe partition pinned for this sync; an
    # allgather_into of a DIFFERENT wire dtype must reuse it or the two
    # ops would partition the payload differently (see native
    # collectives.h shard-layout contract)
    layout: Dict[str, int]
    # group name -> numpy dtype of the group's packed buffer
    dtypes: Dict[str, Any]
    # group name -> leaf indices packed into that group (sig order)
    groups: Dict[str, List[int]]
    treedef: Any
    sig: Any
    rank: int
    world_size: int
    # packer used for the device-side pack/unpack (None on the host path)
    packer: Any = None
    # host path only: which leaves were jax arrays on input
    was_jax: Any = None
    # sharded comm plan that produced this shard (plan_reduce_scatter
    # only): plan_allgather_into routes the updated shard back through
    # the same precompiled schedule — layout agreement by construction.
    plan: Any = None

    def replace_values(self, values: Dict[str, Any]) -> "TreeShard":
        """Same shard layout, new per-group values (e.g. the updated
        parameter shard after an outer-optimizer step)."""
        return replace(self, values=values)


class Collectives(ABC):
    """Reconfigurable collectives over replica groups.

    Reference interface: torchft/process_group.py:109-166 (configure /
    allreduce / allgather / broadcast / size).
    """

    # The step the owning Manager is on (it keeps this current): the
    # ``step`` stat of the ``torchft::<op>`` spans a backend emits, which
    # pairs a phase on the exchange thread with the trainer's step.
    trace_step: Optional[int] = None

    @abstractmethod
    def configure(
        self,
        store_addr: str,
        rank: int,
        world_size: int,
        regions: Optional[Sequence[str]] = None,
        hosts: Optional[Sequence[str]] = None,
    ) -> None:
        """(Re)builds the communicator for a new membership. ``store_addr``
        is ``host:port/prefix`` with a prefix unique to the quorum.

        ``regions`` (optional): one topology label per rank — the quorum's
        region map. Backends that understand topology (the host ring)
        compile it into a two-tier schedule when every member is labeled
        and >= 2 regions are present; every other backend accepts and
        ignores it (the kwarg is part of the reconfigure contract so the
        manager can hand the map to whichever plane it drives).

        ``hosts`` (optional): one host label per rank — the quorum's host
        map (``TORCHFT_HOST``, default hostname). The host ring groups
        members sharing a (region, host) pair into the SHARED-MEMORY
        intra-host ring tier (loopback TCP under ``TORCHFT_HC_SHM=0``);
        every other backend accepts and ignores it."""

    def hier_capable(self) -> bool:
        """Whether the LAST configure built a topology-aware
        (hierarchical) schedule — a region map with >= 2 distinct labels
        and/or a host map grouping >= 2 co-hosted members reached a
        backend that compiles one. Backends without the capability return
        False; callers feature-detect (the plan_hier probe candidate's
        sentinel discipline rides this)."""
        return False

    def allreduce_hier(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        """Like :meth:`allreduce` but over the TWO-TIER schedule (intra-
        region reduce-scatter -> intra allgather -> inter-region ring
        among one leader per region -> intra broadcast): the slow
        inter-region links carry (L-1)/L of the payload per ring phase
        per LEADER instead of 2*(W-1)/W per MEMBER. ``wire`` selects the
        inter hop's encoding only (``None`` | ``"bf16"`` | ``"q8"``;
        intra stays full precision — quantization noise is paid once, on
        the link that needs it). Results are bit-identical across members
        and across runs; the summation ORDER differs from the flat ring
        (two-tier reduction tree), so values match the flat result at the
        accumulation-reordering tolerance class, not bit-for-bit. Raises
        when the cohort has no usable region map (callers under the
        managed discipline see the error latched — the sentinel path)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no two-tier schedule"
        )

    @abstractmethod
    def allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        """Reduces a pytree of arrays across the group; result pytree has the
        same structure/dtypes. Bit-identical on every rank.

        ``divisor`` (SUM only) divides the reduced result before it returns
        — the manager's num_participants average, applied host-side where
        the data already is, so no extra device dispatch or jit program is
        needed. ``op=AVG`` is equivalent to SUM with divisor=world_size.

        ``wire="q8"`` (SUM/AVG only): ship int8-quantized chunks with
        per-chunk f32 scales through the ring, dequant-accumulating per
        hop — ~4x fewer wire bytes than f32, CONSTANT in world size
        (unlike a quantized allgather's O(world) traffic). The result is
        lossy at the int8 quantization class; callers doing error
        feedback should treat the RETURNED tree as what was shipped.
        Implementations without a quantized wire may raise for it."""

    # Planned ops: not abstract — backends without a persistent native
    # plan keep working; callers feature-detect by catching
    # NotImplementedError (the adaptive DDP mode does exactly that).
    def plan_allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
        device_pack: Optional[bool] = None,
        hier: bool = False,
    ) -> Work:
        """Like :meth:`allreduce` (SUM/AVG only) but through a persistent
        precompiled comm plan: the leaf->bucket layout, dtype casts, wire
        encoding and staging buffers are compiled once per tree signature
        and each step is a single GIL-released native call — no per-step
        ``tree_flatten -> astype -> concatenate -> tobytes`` Python work
        on the gradient hot path. Results are bit-identical to the
        legacy managed path. ``wire``: ``None`` ships native dtypes,
        ``"bf16"`` rounds f32 leaves to bfloat16 on the wire, ``"q8"``
        ships int8 ring chunks, ``"q8ef"`` adds the per-leaf int8
        quantization with error feedback (the carry persists inside the
        plan; see :meth:`plan_reset_feedback`). ``device_pack``
        (True/False/None = ``TORCHFT_DEVICE_PACK``) moves the wire
        encoding onto the accelerator where supported, so the
        device->host leg costs wire bytes instead of f32 bytes —
        results stay bit-identical, backends without the capability
        host-pack. ``hier`` runs the plan over the TWO-TIER schedule
        (requires a hier-capable configure — see
        :meth:`allreduce_hier`): the wire then applies at the leader's
        inter-region hop only, staging and the intra tier stay native
        width, and ``q8ef``'s error-feedback carry refines each REGION's
        contribution at its leader."""
        raise NotImplementedError(
            f"{type(self).__name__} has no persistent comm plans"
        )

    def plan_reset_feedback(self) -> None:
        """Zeroes the error-feedback carry of every cached ``q8ef`` plan
        (no-op for backends without plans): call on heal/abort — a
        recovered member must not carry a residual from its abandoned
        trajectory."""

    # Sharded split ops: not abstract — backends whose transport has no
    # reduce-scatter boundary to expose (XLA's in-program psum is already
    # bandwidth-optimal in-chip) keep working; callers feature-detect by
    # catching NotImplementedError.
    def reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        """Reduces a pytree but stops at the reduce-scatter boundary: the
        result is a :class:`TreeShard` holding only the ~1/world_size of
        the flat-packed reduction this rank owns. Composing it with
        :meth:`allgather_into` at the same wire dtype is bit-identical to
        :meth:`allreduce`; updating the shard BEFORE the allgather is the
        sharded-weight-update schedule (PAPERS.md #1) that skips the
        redundant full-tree return traffic. ``divisor``/``op``/``wire``
        as in :meth:`allreduce` (``wire="q8"`` reduces a single f32 group
        over the quantized ring; the returned shard is full f32 — the
        fused op's lossy phase-2 quantization never happens)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no sharded split ops"
        )

    def allgather_into(
        self, shard: "TreeShard", wire: Optional[str] = None
    ) -> Work:
        """Gathers every rank's (possibly updated) :class:`TreeShard` back
        into the full pytree — phase 2 of the ring, run on current values.
        ``wire="bf16"`` ships f32 groups as bfloat16 (half the bytes; all
        members decode identical bf16 words, so results stay bit-identical
        across ranks). All ranks must pass shards from the same logical
        reduce_scatter (same layout)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no sharded split ops"
        )

    # Sharded PLAN ops (the per-step ZeRO hot path): not abstract —
    # callers feature-detect by catching NotImplementedError, exactly
    # like the fused plan path.
    def plan_reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
        ag_wire: Optional[str] = None,
    ) -> Work:
        """Like :meth:`reduce_scatter` (SUM/AVG only) but through a
        persistent precompiled SHARDED comm plan: leaf layout, staging and
        the stripe partition are compiled once per (signature, wires) and
        the grad leg runs as one GIL-released native call. The returned
        :class:`TreeShard` carries the plan, and
        :meth:`plan_allgather_into` MUST receive it back — both legs share
        the plan's partition, so shard boundaries are one arithmetic fact.
        ``wire`` encodes the grad leg (``None``/``"bf16"``/``"q8"``; the
        owned shard lands full f32 regardless); ``ag_wire`` pre-declares
        the param leg's encoding (``None``/``"bf16"``), baked into the
        plan so a native-gathering member and a bf16-gathering one error
        apart at the header. f32 leaves only — the shard layout is one
        flat f32 group (keep f32 master weights, the same constraint the
        sharded DiLoCo path enforces)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no sharded comm plans"
        )

    def plan_allgather_into(
        self, shard: "TreeShard", wire: Optional[str] = None
    ) -> Work:
        """Param leg of the sharded plan: gathers every rank's (updated)
        shard back into the full pytree through the plan that produced it
        (:meth:`plan_reduce_scatter`). ``wire`` must match the plan's
        ``ag_wire`` (``"bf16"``: every member adopts the identical decoded
        words, so gathered params stay bit-identical across the cohort)."""
        raise NotImplementedError(
            f"{type(self).__name__} has no sharded comm plans"
        )

    @abstractmethod
    def allgather(self, tree: Any) -> Work:
        """Gathers each rank's pytree; result is a list of pytrees in rank
        order (all ranks must pass identical structures and shapes)."""

    @abstractmethod
    def broadcast(self, tree: Any, root: int = 0) -> Work:
        """Broadcasts root's pytree to all ranks."""

    @abstractmethod
    def barrier(self) -> Work:
        ...

    @abstractmethod
    def size(self) -> int:
        ...

    @abstractmethod
    def rank(self) -> int:
        ...

    def abort(self) -> None:
        """Unblocks in-flight ops with an error (safe from any thread)."""

    def shutdown(self) -> None:
        ...


# Cap on the per-stripe timing readback; matches tft::kMaxStripes.
_MAX_STRIPES = 64

# Mirrors native kMinStripeBytes / effective_stripes (collectives.cc): the
# payload-derived stripe partition. Python computes it so a sharded sync
# can PIN one partition across a q8 reduce-scatter (1 wire byte/element)
# and a bf16 parameter allgather (2 bytes/element) — left to the native
# auto-derivation, the two ops would partition the payload differently and
# the shard would scatter to the wrong chunk boundaries. The
# decomposed-vs-fused bit-identity tests pin this mirror against native.
_MIN_STRIPE_BYTES = 64 << 10


def _effective_stripes(payload_bytes: int, configured: int) -> int:
    return max(1, min(configured, max(1, payload_bytes // _MIN_STRIPE_BYTES)))


def _as_numpy(leaf: Any) -> np.ndarray:
    """Host copy of a leaf (device→host transfer for jax arrays)."""
    return np.asarray(leaf)


def _is_jax_array(leaf: Any) -> bool:
    import jax

    return isinstance(leaf, jax.Array)


class _DevicePacker:
    """Jitted pack/unpack of a fixed tree signature into ONE flat buffer per
    accumulation dtype.

    Per-transfer latency dominates device↔host links (PCIe DMA setup), so
    shipping ~100 gradient leaves individually costs ~100 round-trips.
    Packing on-device via a jitted concatenate makes the whole pytree
    cross as one transfer per dtype group, and unpacking (split + reshape
    + cast back) stays on-device too.
    """

    def __init__(
        self,
        leaves: Sequence[Any],
        exact_dtypes: bool = False,
        force_f32: bool = False,
    ) -> None:
        """``exact_dtypes``: group by each leaf's own dtype with no
        casting — for BYTE-PRESERVING ops (allgather ships opaque bytes,
        e.g. int8-quantized payloads, where upcasting to an accumulation
        dtype would 4x the wire). ``force_f32``: ONE f32 group for the
        whole tree — the quantized (q8) ring reduces a single flat f32
        buffer. Reduction ops keep the default accumulation-dtype
        grouping (the ring arithmetic needs native dtypes)."""
        import jax
        import jax.numpy as jnp

        assert not (exact_dtypes and force_f32)
        self.sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        groups: dict = {}
        for i, (_, dt) in enumerate(self.sig):
            if force_f32:
                acc = np.dtype(np.float32)
            elif exact_dtypes:
                acc = dt
            else:
                acc = dt if dt in _NATIVE_DTYPES else np.dtype(np.float32)
            groups.setdefault(acc, []).append(i)
        self.groups = groups
        sig = self.sig

        def pack(ls):
            return {
                str(acc): jnp.concatenate(
                    [ls[i].ravel().astype(acc) for i in idxs]
                )
                for acc, idxs in groups.items()
            }

        def unpack(bufs):
            out = [None] * len(sig)
            for acc, idxs in groups.items():
                buf = bufs[str(acc)]
                off = 0
                for i in idxs:
                    shape, dt = sig[i]
                    n = int(np.prod(shape)) if shape else 1
                    out[i] = buf[off : off + n].reshape(shape).astype(dt)
                    off += n
            return out

        self.pack = jax.jit(pack)
        self.unpack = jax.jit(unpack)


# Python wire names -> native PlanWire codes (collectives.h).
_PLAN_WIRES = {None: 0, "bf16": 1, "q8": 2, "q8ef": 3}

# Python wire names -> native HierWire codes (the INTER hop's encoding of
# the two-tier schedule; intra always rides native dtypes).
_HIER_WIRES = {None: 0, "bf16": 1, "q8": 2}

# Wires the DEVICE pack (Pallas kernels emitting the wire encoding on the
# accelerator) supports. Plain "q8" is deliberately absent: its host-pack
# contract ships RAW f32 to the quantized ring, and quantizing at the
# device boundary would change the numerics — callers wanting the device
# quantize use "q8ef" (what the DDP q8 mode maps to anyway).
_DEVICE_PACK_WIRES = (None, "bf16", "q8ef")

# Bytes of the native per-op header exchange (check_op_header's struct:
# magic, kind, count, dtype, op — collectives.cc).
_OP_HEADER_BYTES = 24


def _resolve_device_pack_setting(setting: Any) -> Optional[bool]:
    """ONE parser for the TORCHFT_DEVICE_PACK knob, shared by every layer
    (HostCollectives, PipelinedDDP, AdaptiveDDP): maps a ctor/env setting
    to True (pack on device) / False (host) / None (backend auto).
    ``None`` input reads the env; raises ValueError on junk — callers
    invoke this EAGERLY so a typo'd knob fails loudly instead of latching
    per step in the managed dispatch."""
    if setting is None:
        setting = os.environ.get("TORCHFT_DEVICE_PACK", "auto")
    if isinstance(setting, str):
        try:
            return {"on": True, "off": False, "auto": None}[setting]
        except KeyError:
            raise ValueError(
                f"TORCHFT_DEVICE_PACK={setting!r} (want auto|on|off)"
            ) from None
    return bool(setting)


def _q8_wire_overhead(eff: int, world: int, phases: int = 2) -> int:
    """Bytes the q8 wire ships beyond its int8 payload: one f32 scale per
    (stripe, ring chunk) per quantized phase — the fused allreduce runs
    two (reduce-scatter + allgather), reduce_scatter one — plus the
    per-op header exchange. Counted so compression ratios are honest
    (`wire_bytes: count` alone pretends the sidecar is free)."""
    return 4 * eff * max(world, 1) * phases + _OP_HEADER_BYTES


def _plan_groups(
    sig: Sequence[Tuple[Any, Any]], wire: Optional[str]
) -> List[Tuple[Any, List[int]]]:
    """leaf -> group assignment of a comm plan, replicating native
    plan_build EXACTLY (first-appearance order of the group dtype over
    leaves in signature order) — the device packer and the prepacked
    execute index groups positionally, so the two layouts must be one.
    Returns [(group np.dtype, [leaf indices])]; raises KeyError on a
    signature the plan path cannot take (the callers' fallback signal)."""
    f32 = np.dtype(np.float32)
    groups: List[Tuple[Any, List[int]]] = []
    for i, (_, dt) in enumerate(sig):
        if wire in ("q8", "q8ef"):
            if dt not in (f32, _BF16):
                raise KeyError(dt)
            gdt = f32
        else:
            if dt not in _NATIVE_DTYPES:
                raise KeyError(dt)
            gdt = _BF16 if (wire == "bf16" and dt == f32) else dt
        for g in groups:
            if g[0] == gdt:
                g[1].append(i)
                break
        else:
            groups.append((gdt, [i]))
    return groups


class _DeviceWirePacker:
    """Pallas-kernel pack of a fixed tree signature into the WIRE
    encoding, ON DEVICE (torchft_tpu.ops.quantize_kernels), emitting the
    pre-packed per-group buffers a prepacked CommPlan decodes:

    - ``wire="q8ef"``: per-leaf int8 EF quantization — the codes
      concatenate into the plan's single f32 group layout, the per-leaf
      scales form the sidecar, and the error-feedback carry lives HERE as
      device-resident f32 arrays that never cross the link. ~1 byte per
      element crosses d2h instead of 4.
    - ``wire="bf16"``: f32 leaves concatenate and cast to bf16 on device
      (2 bytes/element d2h); other dtypes pack natively.
    - ``wire=None``: the plain concat pack (native bytes — no byte win,
      but one transfer per dtype group instead of one per leaf).

    The group layout replicates native plan_build positionally
    (_plan_groups), which is what lets plan_execute_pre skip its pack
    stage. The quantization arithmetic is the FMA-free mirror of the
    native EF (the kernels' tested contract), so device-packed staging is
    bit-identical to host-packed staging and mixed rings interoperate."""

    def __init__(self, leaves: Sequence[Any], wire: Optional[str]) -> None:
        import jax
        import jax.numpy as jnp

        from .ops import quantize_kernels as qk

        if wire not in _DEVICE_PACK_WIRES:
            raise KeyError(wire)
        self.wire = wire
        self.sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        self.groups = _plan_groups(self.sig, wire)  # KeyError -> no packer
        sig = self.sig
        groups = self.groups
        f32 = np.dtype(np.float32)

        if wire == "q8ef":
            ((_, idxs),) = groups  # q8 plans are a single f32 group
            self.residuals: Optional[List[Any]] = [
                jnp.zeros(sig[i][0], jnp.float32) for i in idxs
            ]

            def pack(ls: Sequence[Any], residuals: Sequence[Any]):
                qs, scales, new_res = [], [], []
                for k, i in enumerate(idxs):
                    q, s, r = qk.quantize_q8_ef(
                        ls[i].astype(jnp.float32), residuals[k]
                    )
                    qs.append(q.ravel())
                    scales.append(s.reshape(1))
                    new_res.append(r)
                return [jnp.concatenate(qs)], [jnp.concatenate(scales)], new_res
        else:
            self.residuals = None

            def pack(ls: Sequence[Any], residuals: Sequence[Any]):
                payloads = []
                for gdt, idxs in groups:
                    if gdt == _BF16 and any(sig[i][1] != _BF16 for i in idxs):
                        # f32 (or mixed) sources: concat in f32, one cast
                        # kernel per group (bf16->f32->bf16 round-trips
                        # exactly, so native-bf16 leaves are unharmed)
                        buf = jnp.concatenate(
                            [ls[i].astype(f32).ravel() for i in idxs]
                        )
                        payloads.append(qk.cast_bf16(buf))
                    else:
                        payloads.append(jnp.concatenate(
                            [ls[i].astype(gdt).ravel() for i in idxs]
                        ))
                return payloads, [], []

        self._pack = jax.jit(pack)

    def pack_step(self, leaves: Sequence[Any]):
        """(payload arrays, scale arrays, residual rollover) — one entry
        per plan group (scales empty off the q8 wires). Advances the
        device-resident EF carry."""
        payloads, scales, new_res = self._pack(
            leaves, self.residuals if self.residuals is not None else []
        )
        if self.residuals is not None:
            self.residuals = new_res
        return payloads, scales

    def reset_feedback(self) -> None:
        """Zeroes the device-resident EF carry (the heal/abort
        discipline, same contract as the native plan carry)."""
        if self.residuals is not None:
            import jax.numpy as jnp

            self.residuals = [jnp.zeros_like(r) for r in self.residuals]


class _CommPlan:
    """Python handle for one native CommPlan.

    Everything a step needs is allocated HERE, once: the input pointer
    array, and two alternating sets of output leaf arrays (a caller may
    still hold step k's result while step k+1 executes — PipelinedDDP's
    one-step overlap — so outputs double-buffer; a result older than two
    executes is clobbered). Steady-state execute therefore performs zero
    Python-side staging allocation: the only per-step Python work is
    writing leaf pointers.
    """

    def __init__(self, handle: Any, sig: Sequence[Any], treedef: Any,
                 wire: Optional[str], stripes: int = 1, world: int = 1,
                 prepacked: bool = False, hier: bool = False) -> None:
        self.treedef = treedef
        self.sig = tuple(sig)
        self.wire = wire
        self.prepacked = prepacked
        self.hier = hier
        n = len(self.sig)
        counts = [int(np.prod(s)) if s else 1 for s, _ in self.sig]
        # KeyError on a non-native dtype: the caller treats it as
        # "unsupported signature" and falls back to the legacy path.
        codes = [_NATIVE_DTYPES[dt] for _, dt in self.sig]
        assert not (prepacked and hier)
        build = (
            _lib.tft_plan_build_hier if hier
            else _lib.tft_plan_build_pre if prepacked
            else _lib.tft_plan_build
        )
        plan_id = build(
            handle,
            (ctypes.c_int64 * n)(*counts),
            (ctypes.c_int32 * n)(*codes),
            n,
            _PLAN_WIRES[wire],
        )
        if plan_id < 0:
            _check(2)
        self.plan_id = plan_id
        self._handle = handle
        self.in_ptrs = (ctypes.c_void_p * n)()
        if prepacked:
            # Per-GROUP wire payload + scale-sidecar pointer arrays, in
            # the native plan's group order (_plan_groups replicates it).
            ng = len(_plan_groups(self.sig, wire))
            self.group_in = (ctypes.c_void_p * ng)()
            self.group_aux = (ctypes.c_void_p * ng)()
        self.out_sets: List[List[np.ndarray]] = []
        self.out_ptrs: List[Any] = []
        for _ in range(2):
            outs = [np.empty(s, dt) for s, dt in self.sig]
            self.out_sets.append(outs)
            self.out_ptrs.append(
                (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
            )
        self.flip = 0
        self.execs = 0
        self.bytes = sum(
            c * np.dtype(dt).itemsize for c, (_, dt) in zip(counts, self.sig)
        )
        if wire in ("q8", "q8ef"):
            # int8 codes + the per-(stripe, ring chunk) scale sidecar and
            # the op header — the honest quantized-wire bill (q8 plans
            # pack ONE f32 group, so its stripe partition is the op's)
            total = sum(counts)
            eff = _effective_stripes(total, stripes)
            self.wire_bytes = total + _q8_wire_overhead(eff, world)
        elif wire == "bf16":
            self.wire_bytes = sum(
                c * (2 if np.dtype(dt) == np.dtype(np.float32)
                     else np.dtype(dt).itemsize)
                for c, (_, dt) in zip(counts, self.sig)
            )
        else:
            self.wire_bytes = self.bytes


class _ShardedPlan:
    """Python handle for one native SHARDED CommPlan (per-step ZeRO).

    Like :class:`_CommPlan`, everything a step needs is allocated once:
    the input pointer array, two alternating f32 shard buffers for the
    grad leg (the caller may still hold step k's shard while step k+1
    reduces — so shards double-buffer like plan outputs), and two
    alternating full-leaf output sets for the param leg.
    """

    def __init__(self, handle: Any, sig: Sequence[Any], treedef: Any,
                 wire: Optional[str], ag_wire: Optional[str],
                 stripes: int = 1, world: int = 1) -> None:
        f32 = np.dtype(np.float32)
        if any(np.dtype(dt) != f32 for _, dt in sig):
            # The callers' fallback signal, like _plan_groups.
            raise KeyError("sharded plans take f32 leaves only")
        self.treedef = treedef
        self.sig = tuple(sig)
        self.wire = wire
        self.ag_wire = ag_wire
        n = len(self.sig)
        counts = [int(np.prod(s)) if s else 1 for s, _ in self.sig]
        codes = [_NATIVE_DTYPES[np.dtype(dt)] for _, dt in self.sig]
        plan_id = _lib.tft_plan_build_sharded(
            handle,
            (ctypes.c_int64 * n)(*counts),
            (ctypes.c_int32 * n)(*codes),
            n,
            _PLAN_WIRES[wire],
            _PLAN_WIRES[ag_wire],
        )
        if plan_id < 0:
            _check(2)
        self.plan_id = plan_id
        self._handle = handle
        meta = (ctypes.c_int64 * 3)()
        _check(_lib.tft_plan_sharded_meta(handle, plan_id, meta))
        self.shard_count = int(meta[0])
        self.eff = int(meta[1])
        self.total = int(meta[2])
        self.in_ptrs = (ctypes.c_void_p * n)()
        self.shard_sets = [
            np.empty(self.shard_count, np.float32) for _ in range(2)
        ]
        self.shard_flip = 0
        self.out_sets: List[List[np.ndarray]] = []
        self.out_ptrs: List[Any] = []
        for _ in range(2):
            outs = [np.empty(s, dt) for s, dt in self.sig]
            self.out_sets.append(outs)
            self.out_ptrs.append(
                (ctypes.c_void_p * n)(*[o.ctypes.data for o in outs])
            )
        self.flip = 0
        self.execs = 0
        self.bytes = self.total * 4
        # Per-leg wire bills (the honest accounting satellite): the grad
        # leg runs ONE ring phase at the rs wire, the param leg one at
        # the ag wire.
        if wire == "q8":
            self.rs_wire_bytes = self.total + _q8_wire_overhead(
                self.eff, world, phases=1
            )
        elif wire == "bf16":
            self.rs_wire_bytes = self.total * 2
        else:
            self.rs_wire_bytes = self.total * 4
        self.ag_wire_bytes = self.total * (2 if ag_wire == "bf16" else 4)


class _OpPhase(timed_span):
    """One phase of an op: the span ``torchft::<op>/<name>`` and its
    seconds, added to the op's ``phases[name]`` on exit (a phase
    entered once per chunk accumulates). ``seconds`` is this entry's own
    share, for per-bucket accounting."""

    def __init__(self, op: "_OpSpan", name: str) -> None:
        super().__init__(f"torchft::{op.op}/{name}", op.step)
        self._totals = op.phases
        self._name = name

    def __exit__(self, *exc: object) -> None:
        super().__exit__(*exc)
        self._totals[self._name] = (
            self._totals.get(self._name, 0.0) + self.seconds
        )


class _OpSpan(timed_span):
    """One collective op, timed once for both sinks: as a ``with`` it is
    the profiler span ``torchft::<op>``; ``phase(name)`` nests
    ``torchft::<op>/<name>`` in it; ``record(**fields)`` makes the
    ``pop_op_stats()`` entry - ``op``, the fields, and the seconds of
    every phase under the phase's own name - which the ``with`` files on
    its way out with its own seconds as ``op_s``, so that what lies
    between the phases (``op_s`` less their sum) is a number."""

    def __init__(self, owner: "OpStatsMixin", op: str) -> None:
        self.step = owner.trace_step  # a backend is a Collectives
        super().__init__(f"torchft::{op}", self.step)
        self._owner = owner
        self.op = op
        self.phases: Dict[str, float] = {}
        self._stats: Optional[dict] = None

    def __exit__(self, *exc: object) -> None:
        super().__exit__(*exc)
        if self._stats is not None:
            self._stats["op_s"] = self.seconds
            self._owner._record_op_stats(self._stats)

    def phase(self, name: str) -> _OpPhase:
        return _OpPhase(self, name)

    def ready(self, arrays: Any) -> None:
        """The phase ``ready``: the wait until the DEVICE has computed
        ``arrays`` (the packed buffers, their host copies already
        queued). The first blocking read would have waited for the same
        event; taken here, ``d2h`` after it is the link alone."""
        import jax

        with self.phase("ready"):
            jax.block_until_ready(arrays)

    def record(self, **fields: Any) -> None:
        self._stats = {"op": self.op, **fields, **self.phases}


class OpStatsMixin:
    """Per-op phase-timing recorder shared by every data-plane backend
    (host ring, XLA, isolated XLA): the accounting contract AdaptiveDDP's
    probe comparisons and the diagnosis tooling rely on is that EVERY
    backend's ops drain through one ``pop_op_stats`` with the same core
    keys — ``op``, ``bytes`` (payload) and ``d2h_bytes`` (what actually
    crossed the device link) — plus backend-specific phase timings.

    The host ring times its ops through ``_op(name)`` (one ``with`` per
    op and per phase, which is also the profiler span); the XLA planes
    call ``_record_op_stats`` with a dict they timed themselves."""

    _op_stats: List[dict]
    trace_step: Optional[int]  # Collectives', kept current by the Manager

    def _op(self, name: str) -> _OpSpan:
        return _OpSpan(self, name)

    def _record_op_stats(self, stats: dict) -> None:
        if not hasattr(self, "_op_stats"):
            self._op_stats = []
        self._op_stats.append(stats)
        # Bounded: diagnostics, not a log. 256 keeps a full per-step
        # breakdown window alive — at one gradient op + a handful of
        # control ops per step, 64 silently dropped the early entries
        # before the caller's median ever saw them.
        del self._op_stats[:-256]

    def pop_op_stats(self) -> List[dict]:
        """Drains the recorded per-op phase timings (seconds). Core keys
        on every backend: ``op``, ``bytes`` (the logical payload) and
        ``d2h_bytes`` (bytes that crossed the DEVICE link — the number
        that tells a slow transfer from a slow wire). Host-ring entries
        additionally carry ``wire_bytes``/``chunks``/``stripe_s`` and the
        per-bucket plan breakdown; XLA-path entries carry the
        stack/dispatch/localize split; isolated entries add the
        child-side wall and reduction path."""
        out, self._op_stats = getattr(self, "_op_stats", []), []
        for st in out:
            # Plan entries carry their native per-bucket stats as a raw
            # JSON string (decoding per step would put a parse on the
            # zero-Python hot path); decode at drain time.
            raw = st.pop("_buckets_json", None)
            if raw is not None:
                st["buckets"] = json.loads(raw).get("buckets", [])
        return out


class HostCollectives(OpStatsMixin, Collectives):
    """Deterministic TCP ring collectives (native C++), the Gloo role.

    One contiguous buffer per dtype group is reduced per op — leaves are
    packed ON DEVICE (jitted concatenate, one device↔host transfer per
    dtype group) when the tree is jax arrays, host-side otherwise — so a
    whole gradient pytree costs a single ring pass per dtype (the bucketing
    the reference gets from DDP's reducer).
    """

    def __init__(
        self,
        timeout: timedelta = timedelta(seconds=60),
        connect_timeout: timedelta = timedelta(seconds=60),
        pipeline_chunks: Optional[int] = None,
        pipeline_min_bytes: int = 4 << 20,
        stripes: Optional[int] = None,
        stripes_inter: Optional[int] = None,
        wire_crc: Optional[bool] = None,
    ) -> None:
        """``pipeline_chunks`` > 1 splits large device-packed buffers so
        device->host DMA, the TCP ring, and host->device upload overlap
        (chunk i rides the ring while chunk i+1 is still downloading and
        chunk i-1 re-uploads — and the pipeline runs ACROSS dtype buckets,
        not just within one packed buffer). Buffers under
        ``pipeline_min_bytes`` take the single-shot path — per-transfer
        latency would beat the overlap. Chunk boundaries depend only on
        size, so results stay bit-identical across ranks and against the
        unchunked path.

        Default: env ``TORCHFT_HC_PIPELINE_CHUNKS`` (else 4); 1 disables
        the overlap — every member of a ring must use the same value.

        ``stripes`` > 1 spreads every ring op over that many parallel TCP
        connections per neighbor (contiguous payload sub-ranges, one
        reducer thread per stripe) — a single TCP connection is
        window-limited on high-bandwidth-delay links, so striping
        multiplies achievable cross-group throughput the way NCCL
        channels do. Default: env ``TORCHFT_HC_STRIPES`` (else 4). Every
        member of a ring must use the same value; configure() negotiates
        it through the rendezvous store (exactly like the pipeline knobs)
        and fails fast on a mismatch.

        ``stripes_inter`` is the INTER-REGION (leader) ring's parallel-
        connection count under a two-tier configure — the slow wide-area
        hop is exactly where striping pays, so it gets its own knob.
        Default: env ``TORCHFT_HC_STRIPES_INTER`` (else ``stripes``).
        Store-negotiated like the rest of the schedule knobs.

        ``wire_crc`` (default: env ``TORCHFT_WIRE_CRC``, off) puts a
        CRC32C trailer on every ring/stripe payload frame; a mismatch
        raises the typed :class:`~torchft_tpu._native.WireCorruption`
        (latched by the Manager, step discarded by the vote) instead of
        committing poisoned bytes — the one failure mode the vote alone
        cannot catch. All members must agree: the knob rides the same
        store-negotiated fingerprint as the stripes, and the ring hello
        carries the frame format so a drifted member fails at connect.
        Off, the wire format is byte-identical to the pre-CRC protocol
        (un-upgraded peers interop) and the hot path pays one branch."""
        self._handle = _lib.tft_hc_create()
        self._timeout = timeout
        self._connect_timeout = connect_timeout
        if pipeline_chunks is None:
            pipeline_chunks = int(
                os.environ.get("TORCHFT_HC_PIPELINE_CHUNKS", "4")
            )
        self._pipeline_chunks = max(int(pipeline_chunks), 1)
        self._pipeline_min_bytes = int(pipeline_min_bytes)
        if stripes is None:
            stripes = int(os.environ.get("TORCHFT_HC_STRIPES", "4"))
        self._stripes = min(max(int(stripes), 1), _MAX_STRIPES)
        if stripes_inter is None:
            stripes_inter = int(
                os.environ.get("TORCHFT_HC_STRIPES_INTER", "0")
            )
        # <= 0: follow the main stripe knob (resolved at configure, so
        # the negotiated string stays honest about the effective value).
        self._stripes_inter = min(int(stripes_inter), _MAX_STRIPES)
        if wire_crc is None:
            wire_crc = os.environ.get("TORCHFT_WIRE_CRC", "").lower() in (
                "1", "on", "true",
            )
        self._wire_crc = bool(wire_crc)
        self._world_size = 0
        self._rank = -1
        # One thread: collectives must issue in submission order.
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="host_collectives"
        )
        self._shutdown = False
        self._packers: dict = {}
        # Device WIRE packers (Pallas quantize/cast on the accelerator)
        # keyed like plans; a None value marks a signature/wire the
        # device pack cannot serve (host pack serves it instead). These
        # hold the device-resident q8 EF carries, so plan_reset_feedback
        # zeroes them alongside the native plan carries. Survive
        # configure(): the pack is ring-geometry-free (pure per-leaf
        # encoding), unlike the plans themselves.
        self._dev_packers: dict = {}
        # Persistent comm plans keyed by (wire, treedef, signature); a
        # None value marks a signature the plan path cannot take (the
        # legacy path serves it). Invalidated wholesale on configure() —
        # the native layer drops its side at the same moment.
        self._plans: dict = {}
        # Per-op phase timings recorded by the device-packed paths (see
        # pop_op_stats): nothing else tells a slow d2h leg from a slow
        # ring leg.
        self._op_stats: List[dict] = []

    def _last_stripe_seconds(self) -> List[float]:
        """Per-stripe wall times (s) of the last native ring op; safe only
        on the op-executor thread (which is where all ring calls run)."""
        buf = (ctypes.c_int64 * _MAX_STRIPES)()
        n = _lib.tft_hc_last_stripe_ns(self._handle, buf, _MAX_STRIPES)
        return [buf[i] / 1e9 for i in range(min(n, _MAX_STRIPES))]

    # pop_op_stats: OpStatsMixin. Host-ring entries record ``op_s`` (the
    # op whole), ``pack`` (jitted concat dispatch), ``ready`` (the wait
    # for the device to finish what was packed: ``allreduce``, the
    # device-packed ``plan_allreduce``, ``allgather``; the other ops'
    # ``d2h`` still holds it), ``d2h`` (the blocking device→host reads,
    # ``d2h_calls`` of them), ``ring`` (the native TCP op; ``allreduce``
    # also ``ring_transport``, its slowest stripes alone, so that the
    # rest of ``ring`` is the wait for peers), ``h2d`` (result upload + unpack
    # DISPATCH — jax uploads asynchronously, so the actual transfer
    # completes under the caller's next use/drain and is charged there),
    # ``wire_bytes`` where the TCP wire ships a different encoding, and
    # per-bucket ``buckets`` with per-stripe ring wall times.

    # -- lifecycle --

    def configure(
        self,
        store_addr: str,
        rank: int,
        world_size: int,
        regions: Optional[Sequence[str]] = None,
        hosts: Optional[Sequence[str]] = None,
    ) -> None:
        # Abort synchronously so a wedged op can't block the executor, then
        # run the (blocking) rendezvous on the op thread to keep ordering.
        _lib.tft_hc_abort(self._handle)
        # The region and host maps are part of the schedule contract (they
        # decide which tiers exist and who leads them); normalize them
        # here so the negotiated fingerprint below and the native build
        # see one form.
        region_list: List[str] = (
            [str(r) for r in regions] if regions else []
        )
        if region_list and len(region_list) != world_size:
            raise ValueError(
                f"regions must carry one label per rank "
                f"({len(region_list)} labels for world_size {world_size})"
            )
        host_list: List[str] = [str(h) for h in hosts] if hosts else []
        if host_list and len(host_list) != world_size:
            raise ValueError(
                f"hosts must carry one label per rank "
                f"({len(host_list)} labels for world_size {world_size})"
            )
        stripes_inter = (
            self._stripes_inter if self._stripes_inter > 0 else self._stripes
        )
        # The shm knobs are schedule-relevant for co-hosted members (the
        # producer and consumer of one ring must agree on transport and
        # capacity), so they ride the negotiated fingerprint like every
        # other knob. Snapshotted here; the native side re-reads the env
        # at configure, so the two stay in step.
        shm_on = os.environ.get("TORCHFT_HC_SHM", "").lower() not in (
            "0", "off", "false",
        )
        shm_ring = max(
            int(os.environ.get("TORCHFT_HC_SHM_RING_BYTES", str(1 << 20))),
            4096,
        )

        def do_configure() -> None:
            # The pipeline parameters are part of the ring's op schedule
            # (they decide how many native allreduce calls one logical
            # allreduce issues, and the wire has no per-op framing), so
            # every member must agree — validate against rank 0's via the
            # rendezvous store and fail fast instead of desyncing. A solo
            # member has no peers (and possibly no real store) to check.
            # The two-tier inputs (inter stripes + the region map) ride
            # the same fingerprint: a member with a drifted map would
            # otherwise build a different topology and wedge mid-op.
            if world_size > 1:
                hostport, _, prefix = store_addr.partition("/")
                store = _native.StoreClient(
                    hostport, connect_timeout=self._connect_timeout
                )
                # The CRC token is appended ONLY when on: a CRC-off fleet
                # keeps the exact pre-CRC fingerprint, so un-upgraded
                # peers interop; a mixed on/off pair mismatches here with
                # a descriptive error (and would fail at the hello
                # anyway — this is the friendlier first line of defense).
                mine = (
                    f"{self._pipeline_chunks}:{self._pipeline_min_bytes}"
                    f":{self._stripes}:{stripes_inter}"
                    f":{','.join(region_list)}"
                    + (":crc1" if self._wire_crc else "")
                    # Appended ONLY when the host map is USABLE (every
                    # rank labeled — the native hosts_labeled rule): a
                    # partially labeled map (mixed-version fleet, some
                    # members pre-host-PR) builds no host tier, so the
                    # knobs are schedule-irrelevant there and appending
                    # them would break interop with un-upgraded peers
                    # for nothing. Fully unlabeled fleets keep the exact
                    # pre-host fingerprint.
                    + (
                        f":hosts={','.join(host_list)}"
                        f":shm{1 if shm_on else 0}:{shm_ring}"
                        if host_list and all(host_list) else ""
                    )
                )
                key = f"{prefix}/pipecfg" if prefix else "pipecfg"
                if rank == 0:
                    store.set(key, mine.encode())
                else:
                    theirs = store.get(
                        key, timeout=self._connect_timeout
                    ).decode()
                    if theirs != mine:
                        raise RuntimeError(
                            f"pipeline config mismatch: rank {rank} has "
                            f"{mine}, rank 0 has {theirs} — all ring members "
                            "must construct HostCollectives with the same "
                            "pipeline_chunks / pipeline_min_bytes / stripes "
                            "/ stripes_inter and see the same region map"
                        )
            _lib.tft_hc_set_wire_crc(self._handle, 1 if self._wire_crc else 0)
            _check(
                _lib.tft_hc_configure_hier(
                    self._handle,
                    store_addr.encode(),
                    rank,
                    world_size,
                    _ms(self._connect_timeout),
                    self._stripes,
                    stripes_inter,
                    json.dumps(region_list).encode()
                    if region_list else b"",
                    json.dumps(host_list).encode()
                    if host_list else b"",
                )
            )
            # Assign on the op thread: ops queued after this configure see
            # the new size, earlier ones the old — never a mix.
            self._rank = rank
            self._world_size = world_size
            # The native side just dropped every plan (their layout bakes
            # in the old ring); drop the Python handles in the same
            # ordered position so no queued op can execute a stale id.
            self._plans = {}
            # Device packers survive (their jitted encode is geometry-
            # free) but their EF carries zero — a host-packed member's
            # carry died with its plan just now, and the two modes must
            # stay bit-identical across reconfigures.
            for packer in self._dev_packers.values():
                if packer is not None:
                    packer.reset_feedback()

        self._executor.submit(do_configure).result()

    def abort(self) -> None:
        _lib.tft_hc_abort(self._handle)

    def prewarm(self, tree: Any = None) -> None:
        """Shadow-mode warm-up for hot-spare standbys: spins up the op
        executor thread and, given a ``tree`` shaped like the payload the
        promoted worker will sync (its gradient pytree), jits and runs
        the device pack/unpack programs for that signature — so the first
        post-promotion allreduce pays neither thread start nor packer
        compile. NO network is touched (the ring only exists after
        ``configure``), which is what makes it safe for a parked standby
        that must not be visible to the quorum."""

        def warm() -> None:
            if tree is None:
                return
            leaves, treedef = _flatten(tree)
            if not leaves or not all(_is_jax_array(l) for l in leaves):
                return
            import jax

            key = (treedef, tuple((l.shape, np.dtype(l.dtype)) for l in leaves))
            packer = self._packers.get(key)
            if packer is None:
                packer = self._packers[key] = _DevicePacker(leaves)
            # Round-trip once: both executables compile (and land in the
            # persistent cache), no ring op is issued.
            jax.block_until_ready(packer.unpack(packer.pack(leaves)))

        self._submit(warm).wait()

    def shutdown(self) -> None:
        if self._shutdown:
            return
        self._shutdown = True
        _lib.tft_hc_abort(self._handle)
        self._executor.shutdown(wait=True)
        # Deterministic ring teardown (sockets, listener, shm segments):
        # named kernel resources must not live until garbage collection
        # gets around to the handle.
        _lib.tft_hc_release(self._handle)

    def __del__(self) -> None:
        handle = getattr(self, "_handle", None)
        if handle and _lib is not None:
            try:
                self.shutdown()  # aborts + drains the executor, handle intact
            except Exception:
                pass
            self._handle = None
            _lib.tft_hc_destroy(handle)

    def size(self) -> int:
        return self._world_size

    def rank(self) -> int:
        return self._rank

    # -- ops --

    def _submit(self, fn: Callable[[], Any]) -> Work:
        if self._shutdown:
            raise RuntimeError("collectives already shut down")
        return Work(self._executor.submit(fn))

    def allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        timeout_ms = _ms(self._timeout)
        if wire not in (None, "q8"):
            raise ValueError(f"unsupported wire: {wire!r}")
        if wire == "q8":
            if op == ReduceOp.AVG:
                divisor, op = float(self._world_size), ReduceOp.SUM
            if op != ReduceOp.SUM:
                raise ValueError("wire='q8' supports SUM/AVG only")
            return self._submit(
                lambda: self._allreduce_q8_sync(tree, divisor, timeout_ms)
            )
        return self._submit(
            lambda: self._allreduce_sync(tree, op, timeout_ms, divisor)
        )

    def _allreduce_q8_sync(
        self, tree: Any, divisor: Optional[float], timeout_ms: int
    ) -> Any:
        """Quantized ring SUM: the whole tree packs into ONE flat f32
        buffer (jitted on-device concat for jax leaves — one transfer per
        direction), the native ring ships int8 chunks with per-chunk
        scales, and the result unpacks to the original dtypes."""
        if self._world_size == 1:
            if divisor is not None and divisor != 1:
                import jax

                return jax.tree_util.tree_map(
                    lambda l: _divide_leaf(l, divisor)
                    if hasattr(l, "__truediv__")
                    else l,
                    tree,
                )
            return tree
        leaves, treedef = _flatten(tree)
        if not leaves:
            return tree
        all_jax = all(_is_jax_array(l) for l in leaves)
        with self._op("allreduce_q8") as timing:
            if all_jax:
                key = (
                    "q8", treedef,
                    tuple((l.shape, np.dtype(l.dtype)) for l in leaves),
                )
                packer = self._packers.get(key)
                if packer is None:
                    packer = self._packers[key] = _DevicePacker(
                        leaves, force_f32=True
                    )
                with timing.phase("d2h"):
                    buf = np.asarray(
                        packer.pack(leaves)[str(np.dtype(np.float32))]
                    )
                    if not buf.flags.writeable or not buf.flags.c_contiguous:
                        buf = np.array(buf)
            else:
                arrays = [_as_numpy(l) for l in leaves]
                buf = np.concatenate(
                    [a.astype(np.float32, copy=False).ravel() for a in arrays]
                )
            with timing.phase("ring"):
                _check(
                    _lib.tft_hc_allreduce_q8(
                        self._handle,
                        buf.ctypes.data_as(ctypes.c_void_p),
                        buf.size,
                        timeout_ms,
                    )
                )
                stripe_s = self._last_stripe_seconds()
                if divisor is not None:
                    buf /= divisor
            if all_jax:
                import jax.numpy as jnp

                with timing.phase("h2d"):
                    out = _unflatten(
                        treedef,
                        packer.unpack(
                            {str(np.dtype(np.float32)): jnp.asarray(buf)}
                        ),
                    )
                timing.record(
                    bytes=buf.nbytes,
                    # TCP wire ships int8 chunks + per-chunk f32 scales +
                    # the op header, not the f32 device payload — the
                    # sidecar is counted (one scale per stripe x ring chunk
                    # x phase) so the compression ratio is honest.
                    wire_bytes=buf.size + _q8_wire_overhead(
                        _effective_stripes(buf.size, self._stripes),
                        self._world_size,
                    ),
                    # Host-side quantization: the device link still carried
                    # the FULL f32 payload (the device-pack plan path is
                    # what shrinks this).
                    d2h_bytes=buf.nbytes,
                    stripe_s=stripe_s,
                )
                return out
        out_leaves = []
        offset = 0
        for a in arrays:
            n = a.size
            out_leaves.append(
                buf[offset : offset + n]
                .reshape(a.shape)
                .astype(a.dtype, copy=False)
            )
            offset += n
        return _unflatten(treedef, out_leaves)

    def _allreduce_sync(
        self,
        tree: Any,
        op: ReduceOp,
        timeout_ms: int,
        divisor: Optional[float] = None,
    ) -> Any:
        if divisor is not None and op != ReduceOp.SUM:
            raise ValueError("divisor only composes with ReduceOp.SUM")
        if self._world_size == 1:
            # Identity-ish (SUM of one member; AVG divides by 1): skip the
            # host pack/transfer entirely — device arrays never leave HBM.
            # NOTE: single-member undivided results may ALIAS the input
            # tree (treat op results as immutable, the jax norm —
            # multi-member paths return fresh buffers).
            if divisor is not None and divisor != 1:
                import jax

                return jax.tree_util.tree_map(
                    lambda l: _divide_leaf(l, divisor)
                    if hasattr(l, "__truediv__")
                    else l,
                    tree,
                )
            return tree
        leaves, treedef = _flatten(tree)
        if not leaves:
            return tree
        if op == ReduceOp.AVG:
            divisor = self._world_size
        native_op = int(ReduceOp.SUM if op == ReduceOp.AVG else op)

        if all(_is_jax_array(l) for l in leaves):
            return self._allreduce_device_packed(
                leaves, treedef, native_op, divisor, timeout_ms
            )

        arrays = [_as_numpy(l) for l in leaves]
        was_jax = [_is_jax_array(l) for l in leaves]
        # Group leaves by accumulation dtype; pack each group into one
        # contiguous buffer so the ring runs once per dtype.
        out_arrays: List[Optional[np.ndarray]] = [None] * len(arrays)
        groups: dict = {}
        for i, a in enumerate(arrays):
            acc = a.dtype if a.dtype in _NATIVE_DTYPES else np.dtype(np.float32)
            groups.setdefault(acc, []).append(i)
        for acc_dtype, idxs in groups.items():
            buf = np.concatenate(
                [arrays[i].astype(acc_dtype, copy=False).ravel() for i in idxs]
            )
            _check(
                _lib.tft_hc_allreduce(
                    self._handle,
                    buf.ctypes.data_as(ctypes.c_void_p),
                    buf.size,
                    _NATIVE_DTYPES[acc_dtype],
                    native_op,
                    timeout_ms,
                )
            )
            if divisor is not None:
                if buf.dtype == _BF16:
                    buf = (buf.astype(np.float32) / divisor).astype(_BF16)
                elif np.issubdtype(buf.dtype, np.floating):
                    buf /= divisor
                else:
                    # int groups floor-divide by the integral divisor
                    # (the _divide_leaf contract); ``//= float`` would
                    # raise an unsafe-cast error in-place.
                    buf //= int(divisor)
            offset = 0
            for i in idxs:
                n = arrays[i].size
                out_arrays[i] = (
                    buf[offset : offset + n]
                    .reshape(arrays[i].shape)
                    .astype(arrays[i].dtype, copy=False)
                )
                offset += n
        out_leaves: List[Any] = []
        for i, a in enumerate(out_arrays):
            if was_jax[i]:
                import jax.numpy as jnp

                out_leaves.append(jnp.asarray(a))
            else:
                out_leaves.append(a)
        return _unflatten(treedef, out_leaves)

    def _allreduce_device_packed(
        self, leaves, treedef, native_op: int, divisor, timeout_ms: int
    ) -> Any:
        """All-jax-leaf fast path: pack on device, then pipeline the WHOLE
        op schedule — every dtype bucket's chunk DMAs are enqueued up
        front, so bucket i+1's d2h streams while bucket i rides the ring
        and bucket i-1's result re-uploads under jax's async dispatch. The
        old per-buffer pipeline drained between dtype groups; a mixed
        f32/bf16/int gradient tree paid a full pipeline fill+drain per
        group."""
        import jax.numpy as jnp

        key = (treedef, tuple((l.shape, np.dtype(l.dtype)) for l in leaves))
        packer = self._packers.get(key)
        if packer is None:
            packer = self._packers[key] = _DevicePacker(leaves)
        with self._op("allreduce") as timing:
            with timing.phase("pack"):
                bufs = packer.pack(leaves)
                names = sorted(bufs)  # deterministic bucket order = the op schedule

                # Chunk schedule across ALL buckets. Chunk boundaries depend
                # only on (size, pipeline config), both store-negotiated, so
                # every rank issues the identical sequence of native ring ops.
                schedule: List[Tuple[str, Any]] = []
                for name in names:
                    dev = bufs[name]
                    itemsize = np.dtype(dev.dtype).itemsize
                    k = self._pipeline_chunks
                    if k <= 1 or dev.size * itemsize < self._pipeline_min_bytes:
                        schedule.append((name, dev))
                    else:
                        bounds = [dev.size * i // k for i in range(k + 1)]
                        schedule.extend(
                            (name, dev[a:b]) for a, b in zip(bounds, bounds[1:])
                        )
                for _, c in schedule:
                    c.copy_to_host_async()  # queue every DMA before the first block
            # the backward pass and the pack are still running on the
            # device: that wait is the chip's, not the link's
            timing.ready([c for _, c in schedule])

            out_chunks: dict = {name: [] for name in names}
            buckets: dict = {
                name: {"bytes": 0, "d2h": 0.0, "ring": 0.0, "h2d": 0.0,
                       "stripe_s": [], "stripe_wall": 0.0}
                for name in names
            }
            for name, c in schedule:
                st = buckets[name]
                with timing.phase("d2h") as d2h:
                    arr = np.asarray(c)  # completes when THIS chunk's DMA lands
                    if not arr.flags.writeable or not arr.flags.c_contiguous:
                        arr = np.array(arr)  # ring reduces in place
                with timing.phase("ring") as ring:
                    self._ring_chunk(arr, native_op, timeout_ms)
                    stripe_s = self._last_stripe_seconds()
                    if divisor is not None:
                        arr = self._apply_divisor(arr, divisor)
                with timing.phase("h2d") as h2d:
                    # Async dispatch: the upload starts now and overlaps the
                    # next chunk's (possibly next bucket's) ring pass.
                    out_chunks[name].append(jnp.asarray(arr))
                st["bytes"] += arr.nbytes
                st["d2h"] += d2h.seconds
                st["ring"] += ring.seconds
                st["h2d"] += h2d.seconds
                # elementwise-sum the per-stripe ring seconds over the
                # bucket's chunks (chunks can use fewer effective stripes)
                acc = st["stripe_s"]
                for i, s in enumerate(stripe_s):
                    if i < len(acc):
                        acc[i] += s
                    else:
                        acc.append(s)
                # pure transport wall: the slowest stripe bounds each chunk's
                # ring pass; summing per-chunk maxima excludes the peer-skew
                # wait the `ring` phase absorbs at the op-header sync, so
                # this is the number a stripe-count sweep compares
                if stripe_s:
                    st["stripe_wall"] += max(stripe_s)
            dev_bufs = {
                name: (chunks[0] if len(chunks) == 1 else jnp.concatenate(chunks))
                for name, chunks in out_chunks.items()
            }
            total_bytes = sum(b["bytes"] for b in buckets.values())
            timing.record(
                bytes=total_bytes,
                # native dtypes ride both legs at full width
                d2h_bytes=total_bytes,
                d2h_calls=len(schedule),  # one blocking read a chunk
                chunks=len(schedule),
                # the wire alone; ``ring`` less this is the wait for peers
                ring_transport=sum(b["stripe_wall"] for b in buckets.values()),
                buckets=buckets,
            )
            return _unflatten(treedef, packer.unpack(dev_bufs))

    def _apply_divisor(self, arr: np.ndarray, divisor) -> np.ndarray:
        if arr.dtype == _BF16:
            return (arr.astype(np.float32) / divisor).astype(_BF16)
        if np.issubdtype(arr.dtype, np.floating):
            arr /= divisor
            return arr
        arr //= int(divisor)
        return arr

    def _ring_chunk(self, arr: np.ndarray, native_op: int, timeout_ms: int) -> None:
        _check(
            _lib.tft_hc_allreduce(
                self._handle,
                arr.ctypes.data_as(ctypes.c_void_p),
                arr.size,
                _NATIVE_DTYPES[arr.dtype],
                native_op,
                timeout_ms,
            )
        )

    # -- two-tier (topology-aware) ops --

    def hier_capable(self) -> bool:
        """Whether the last configure() received a usable topology map —
        a region map with >= 2 distinct labels and/or a host map grouping
        >= 2 co-hosted ranks — and built the hierarchical topology
        alongside the flat ring."""
        return bool(_lib.tft_hc_hier_capable(self._handle))

    def host_tier_transport(self) -> str:
        """Transport of the host (intra-host) tier after the last
        configure: ``"shm"`` (shared-memory rings), ``"tcp"`` (the
        ``TORCHFT_HC_SHM=0`` loopback fallback) or ``"none"`` (this
        member's (region, host) group has < 2 ranks)."""
        code = int(_lib.tft_hc_host_tier_transport(self._handle))
        return {0: "none", 1: "tcp", 2: "shm"}[code]

    def _last_hier_dict(self) -> dict:
        out = ctypes.c_void_p()
        _check(_lib.tft_hc_last_hier_json(self._handle, ctypes.byref(out)))
        return json.loads(_native._take_string(out))

    @staticmethod
    def _hier_stats_fields(h: dict) -> dict:
        """The op-stat fragment shared by the bulk and plan hier paths:
        per-tier phase seconds + MEASURED per-tier tx bytes (duplex's
        per-connection counters, summed) — ONE schema, so consumers
        (bench accounting, diagnosis tooling) never see the two paths
        drift."""
        out = {
            # The wire bill: MEASURED socket traffic only. The shm host
            # tier hands nothing to the kernel, so its hops contribute 0
            # here by construction (host_tx_bytes is non-zero only under
            # the TORCHFT_HC_SHM=0 TCP fallback).
            "wire_bytes": h["intra_tx_bytes"] + h["inter_tx_bytes"]
            + h["host_tx_bytes"],
            "intra_rs_s": h["intra_rs_s"],
            "intra_ag_s": h["intra_ag_s"],
            "inter_ring_s": h["inter_ring_s"],
            "intra_bcast_s": h["intra_bcast_s"],
            "tiers": {
                "intra": {
                    "tx_bytes": h["intra_tx_bytes"],
                    "world": h["intra_world"],
                    "eff": h["eff_intra"],
                    "rs_s": h["intra_rs_s"],
                    "ag_s": h["intra_ag_s"],
                    "bcast_s": h["intra_bcast_s"],
                },
                "inter": {
                    "tx_bytes": h["inter_tx_bytes"],
                    "rs_tx_bytes": h["inter_rs_tx_bytes"],
                    "ag_tx_bytes": h["inter_ag_tx_bytes"],
                    "world": h["inter_world"],
                    "eff": h["eff_inter"],
                    "ring_s": h["inter_ring_s"],
                    "leader": h["leader"],
                },
            },
        }
        if h.get("host_world", 0) > 1:
            # The third (intra-host) tier, present only on co-hosted
            # cohorts: shm_* phase keys + the honest byte split (tx_bytes
            # = kernel traffic, 0 under shm; shm_bytes = ring movement).
            out["shm_rs_s"] = h["shm_rs_s"]
            out["shm_ag_s"] = h["shm_ag_s"]
            out["shm_bcast_s"] = h["shm_bcast_s"]
            out["tiers"]["host"] = {
                "tx_bytes": h["host_tx_bytes"],
                "shm_bytes": h["shm_bytes"],
                "world": h["host_world"],
                "eff": h["eff_host"],
                "rs_s": h["shm_rs_s"],
                "ag_s": h["shm_ag_s"],
                "bcast_s": h["shm_bcast_s"],
                "leader": h["host_leader"],
                "transport": "shm" if h["host_shm"] else "tcp",
            }
        return out

    @staticmethod
    def _merge_hier_stats(acc: Optional[dict], h: dict) -> dict:
        """Accumulates per-group native hier breakdowns (one native op per
        dtype group overwrites last_hier_) into one per-op record."""
        if acc is None:
            return dict(h)
        for k in (
            "intra_rs_s", "intra_ag_s", "inter_ring_s", "intra_bcast_s",
            "intra_tx_bytes", "inter_tx_bytes", "inter_rs_tx_bytes",
            "inter_ag_tx_bytes", "payload_bytes",
            "shm_rs_s", "shm_ag_s", "shm_bcast_s", "host_tx_bytes",
            "shm_bytes",
        ):
            acc[k] += h[k]
        return acc

    def allreduce_hier(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        """Two-tier allreduce (see Collectives.allreduce_hier): intra-
        region reduce-scatter -> intra allgather -> striped inter-region
        ring among one deterministic leader per region (lowest
        replica-id) -> chunk-pipelined intra broadcast, composed from the
        SAME native rs/ag stripe bodies as the flat ring. ``wire``
        applies to the inter hop only (``"bf16"`` halves its bytes,
        ``"q8"`` quarters them with per-chunk scales) — quantization
        noise is paid once per sync, on the slow link. Requires a
        hier-capable configure; raises otherwise (the managed dispatch
        latches it — the probe candidates' sentinel discipline)."""
        timeout_ms = _ms(self._timeout)
        if wire not in _HIER_WIRES:
            raise ValueError(f"unsupported hier wire: {wire!r}")
        if op == ReduceOp.AVG:
            if divisor is not None:
                raise ValueError("divisor only composes with ReduceOp.SUM")
            divisor, op = float(self._world_size), ReduceOp.SUM
        if divisor is not None and op != ReduceOp.SUM:
            raise ValueError("divisor only composes with ReduceOp.SUM")
        if wire is not None and op != ReduceOp.SUM:
            raise ValueError("hier wire bf16/q8 supports SUM/AVG only")
        return self._submit(
            lambda: self._allreduce_hier_sync(tree, op, divisor, wire,
                                              timeout_ms)
        )

    def _allreduce_hier_sync(
        self,
        tree: Any,
        op: ReduceOp,
        divisor: Optional[float],
        wire: Optional[str],
        timeout_ms: int,
    ) -> Any:
        if self._world_size == 1:
            if divisor is not None and divisor != 1:
                import jax

                return jax.tree_util.tree_map(
                    lambda l: _divide_leaf(l, divisor)
                    if hasattr(l, "__truediv__")
                    else l,
                    tree,
                )
            return tree
        leaves, treedef = _flatten(tree)
        if not leaves:
            return tree
        native_op = int(op)
        all_jax = all(_is_jax_array(l) for l in leaves)
        f32 = np.dtype(np.float32)

        with self._op("allreduce_hier") as timing:
            with timing.phase("d2h"):
                if all_jax:
                    key = (
                        "hier_q8" if wire == "q8" else "hier", treedef,
                        tuple((l.shape, np.dtype(l.dtype)) for l in leaves),
                    )
                    packer = self._packers.get(key)
                    if packer is None:
                        packer = self._packers[key] = _DevicePacker(
                            leaves, force_f32=(wire == "q8")
                        )
                    bufs = packer.pack(leaves)
                    names = sorted(bufs)
                    for name in names:  # queue every DMA before blocking on one
                        bufs[name].copy_to_host_async()
                    host = {}
                    for name in names:
                        arr = np.asarray(bufs[name])
                        if not arr.flags.writeable or not arr.flags.c_contiguous:
                            arr = np.array(arr)  # the schedule reduces in place
                        host[name] = arr
                    arrays = was_jax = None
                else:
                    packer = None
                    arrays = [_as_numpy(l) for l in leaves]
                    was_jax = [_is_jax_array(l) for l in leaves]
                    groups: dict = {}
                    for i, a in enumerate(arrays):
                        if wire == "q8":
                            acc = f32  # the quantized inter hop reduces ONE f32 group
                        else:
                            acc = (a.dtype if a.dtype in _NATIVE_DTYPES else f32)
                        groups.setdefault(str(acc), []).append(i)
                    host = {
                        name: np.concatenate(
                            [arrays[i].astype(np.dtype(name), copy=False).ravel()
                             for i in idxs]
                        )
                        for name, idxs in groups.items()
                    }
                    names = sorted(host)

            with timing.phase("ring"):
                hier_stats: Optional[dict] = None
                for name in names:
                    buf = host[name]
                    # The wire applies where it means something: the q8 grouping
                    # is a single f32 buffer by construction, and bf16 compresses
                    # f32 groups only (others ride the inter hop at native width).
                    if wire == "q8":
                        gw = _HIER_WIRES["q8"]
                    elif wire == "bf16" and buf.dtype == f32:
                        gw = _HIER_WIRES["bf16"]
                    else:
                        gw = _HIER_WIRES[None]
                    _check(
                        _lib.tft_hc_allreduce_hier(
                            self._handle,
                            buf.ctypes.data_as(ctypes.c_void_p),
                            buf.size,
                            _NATIVE_DTYPES[buf.dtype],
                            native_op,
                            gw,
                            timeout_ms,
                        )
                    )
                    hier_stats = self._merge_hier_stats(
                        hier_stats, self._last_hier_dict()
                    )
                    if divisor is not None and divisor != 1:
                        host[name] = self._apply_divisor(buf, divisor)

            with timing.phase("h2d"):
                if all_jax:
                    import jax.numpy as jnp

                    out = _unflatten(
                        treedef,
                        packer.unpack(
                            {name: jnp.asarray(host[name]) for name in names}
                        ),
                    )
                else:
                    out_leaves: List[Any] = [None] * len(arrays)
                    for name, idxs in groups.items():
                        buf = host[name]
                        offset = 0
                        for i in idxs:
                            n = arrays[i].size
                            leaf = (
                                buf[offset:offset + n]
                                .reshape(arrays[i].shape)
                                .astype(arrays[i].dtype, copy=False)
                            )
                            offset += n
                            if was_jax[i]:
                                import jax.numpy as jnp

                                leaf = jnp.asarray(leaf)
                            out_leaves[i] = leaf
                    out = _unflatten(treedef, out_leaves)
            total_bytes = sum(host[n].nbytes for n in names)
            timing.record(
                wire=wire,
                bytes=total_bytes,
                d2h_bytes=total_bytes if all_jax else 0,
                # MEASURED traffic this member sent, per tier (duplex's
                # per-connection counters, summed) — the number that shows
                # the inter-tier byte reduction directly, not a model.
                **(
                    self._hier_stats_fields(hier_stats)
                    if hier_stats is not None else {}
                ),
            )
            return out

    # -- planned ops --

    def plan_allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
        device_pack: Optional[bool] = None,
        hier: bool = False,
    ) -> Work:
        """The plan-path allreduce (see Collectives.plan_allreduce): one
        native call per step over a cached, precompiled plan. Bit-identical
        to the legacy managed path — the plan executes the identical
        per-group stripe partition through the same native ring bodies.
        Unsupported signatures (non-native leaf dtypes; q8 wires with
        non-float leaves) silently take the legacy path with equivalent
        semantics where one exists (``wire=None``), else raise.

        ``hier`` executes the plan over the TWO-TIER schedule (requires a
        hier-capable configure; the error latches under the managed
        discipline otherwise). The wire applies at the leader's
        inter-region hop only; ``device_pack`` is ignored on this path —
        there is no pre-packed hier form, because the wire encoding
        happens at the inter boundary, not at pack.

        ``device_pack``: ``True`` packs the wire encoding ON DEVICE
        (Pallas quantize/cast kernels + prepacked plan leaves) so the
        device->host transfer costs wire bytes, not f32 bytes — supported
        for wires ``None``/``"bf16"``/``"q8ef"`` on all-jax trees, with a
        silent host-pack fallback where the capability is missing (CPU
        rings without the kernels, non-jax leaves, plain ``"q8"``).
        ``False`` pins host pack. ``None`` (default) resolves
        ``TORCHFT_DEVICE_PACK``: ``on``/``off`` pin, ``auto`` (the
        default) device-packs only where a real device link exists (the
        TPU backend). Results are bit-identical either way — device- and
        host-packing members may share one ring."""
        timeout_ms = _ms(self._timeout)
        if wire not in _PLAN_WIRES:
            raise ValueError(f"unsupported wire: {wire!r}")
        if op == ReduceOp.AVG:
            if divisor is not None:
                # Mirror the legacy path's loud error — silently
                # replacing a caller's participant divisor with
                # world_size would corrupt the average whenever
                # participants < world.
                raise ValueError("divisor only composes with ReduceOp.SUM")
            divisor, op = float(self._world_size), ReduceOp.SUM
        if op != ReduceOp.SUM:
            raise ValueError("plan_allreduce supports SUM/AVG only")
        # Parse the knob EAGERLY (static usage errors raise here, before
        # the submit, matching the wire/op validation above — an op-thread
        # ValueError would be latched by Manager's dispatch and silently
        # discard every step instead).
        device_pack = _resolve_device_pack_setting(device_pack)
        return self._submit(
            lambda: self._plan_allreduce_sync(
                tree, divisor, wire, timeout_ms, device_pack, hier
            )
        )

    def _resolve_device_pack(
        self, setting: Optional[bool], leaves: Sequence[Any],
        wire: Optional[str],
    ) -> bool:
        """Whether this sync should ATTEMPT the device pack (a signature
        the packer cannot take still falls back to host pack — the
        verdict caches).
        ``setting`` is the already-parsed knob (True/False/None = auto);
        auto engages only where the pack saves a real device-link leg."""
        if setting is False:
            return False
        if wire not in _DEVICE_PACK_WIRES:
            return False
        if not leaves or not all(_is_jax_array(l) for l in leaves):
            return False
        if setting is True:
            return True
        import jax

        return jax.default_backend() == "tpu"

    def _device_packer_for(
        self, leaves: Sequence[Any], treedef: Any, wire: Optional[str]
    ) -> Optional[_DeviceWirePacker]:
        sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        key = (wire, treedef, sig)
        if key in self._dev_packers:
            return self._dev_packers[key]
        try:
            packer: Optional[_DeviceWirePacker] = _DeviceWirePacker(
                leaves, wire
            )
        except KeyError:
            # Unsupported signature (_plan_groups): cache the verdict,
            # host pack serves the identical contract. Anything else — a
            # kernel that fails to build — raises.
            packer = None
        self._dev_packers[key] = packer
        return packer

    def _plan_for(
        self, leaves: Sequence[Any], treedef: Any, wire: Optional[str],
        prepacked: bool = False, hier: bool = False,
    ) -> Optional[_CommPlan]:
        # The signature MUST stay in the key: executing a plan against a
        # same-treedef tree with different shapes/dtypes would pack with
        # the wrong per-leaf counts (reading past leaf buffers). It is
        # computed once here and handed to the plan, never recomputed.
        sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        key: Any = (wire, treedef, sig)
        if prepacked:
            key = (wire, treedef, sig, "pre")
        elif hier:
            key = (wire, treedef, sig, "hier")
        if key in self._plans:
            return self._plans[key]
        try:
            plan: Optional[_CommPlan] = _CommPlan(
                self._handle, sig, treedef, wire,
                stripes=self._stripes, world=self._world_size,
                prepacked=prepacked, hier=hier,
            )
        except (KeyError, RuntimeError):
            # Non-native leaf dtype, or a wire/dtype combination the
            # native plan rejects: remember the verdict so the per-step
            # path doesn't re-attempt the build.
            plan = None
        self._plans[key] = plan
        return plan

    def _plan_allreduce_sync(
        self,
        tree: Any,
        divisor: Optional[float],
        wire: Optional[str],
        timeout_ms: int,
        device_pack: Optional[bool] = None,
        hier: bool = False,
    ) -> Any:
        leaves, treedef = _flatten(tree)
        if not leaves:
            return tree
        if hier:
            return self._plan_hier_sync(
                leaves, treedef, tree, divisor, wire, timeout_ms
            )
        if self._resolve_device_pack(device_pack, leaves, wire):
            packer = self._device_packer_for(leaves, treedef, wire)
            plan = (
                self._plan_for(leaves, treedef, wire, prepacked=True)
                if packer is not None else None
            )
            if packer is not None and plan is not None:
                return self._plan_execute_device(
                    plan, packer, leaves, treedef, divisor, wire, timeout_ms
                )
            # unsupported signature: host pack serves the identical
            # contract
        plan = self._plan_for(leaves, treedef, wire)
        if plan is None:
            if wire is None:
                return self._allreduce_sync(
                    tree, ReduceOp.SUM, timeout_ms, divisor
                )
            if wire in ("q8", "q8ef"):
                raise ValueError(
                    "plan wire 'q8'/'q8ef' requires f32/bf16 leaves"
                )
            raise ValueError(
                "plan wire 'bf16' requires native-dtype leaves"
            )
        with self._op("plan_allreduce") as timing:
            # pointer gather; host leaves make it ~free
            with timing.phase("d2h"):
                staging_allocs = 0
                refs = []  # keep host views alive across the native call
                in_ptrs = plan.in_ptrs
                for i, l in enumerate(leaves):
                    a = np.asarray(l)  # zero-copy for numpy / CPU jax leaves
                    if not a.flags.c_contiguous:
                        a = np.ascontiguousarray(a)
                        staging_allocs += 1
                    refs.append(a)
                    in_ptrs[i] = a.ctypes.data
            # the single native call: pack+ring+unpack
            with timing.phase("ring"):
                outs = plan.out_sets[plan.flip]
                out_ptrs = plan.out_ptrs[plan.flip]
                plan.flip ^= 1
                _check(
                    _lib.tft_plan_execute(
                        self._handle,
                        plan.plan_id,
                        in_ptrs,
                        out_ptrs,
                        float(divisor if divisor is not None else 1.0),
                        0 if divisor is None else 1,
                        timeout_ms,
                    )
                )
            del refs
            plan.execs += 1
            timing.record(
                wire=wire,
                device_pack=False,
                bytes=plan.bytes,
                wire_bytes=plan.wire_bytes,
                # Host pack reads every leaf at full source width: the device
                # link pays f32-size bytes regardless of the wire encoding.
                d2h_bytes=plan.bytes,
                # Per-bucket phases, fetched raw here and decoded lazily at
                # pop_op_stats: the JSON parse stays off the per-step path.
                _buckets_json=self._plan_stats_json(plan.plan_id),
                # The zero-allocation contract: after warmup, no Python-side
                # staging buffer is allocated on this path (only forced
                # copies of non-contiguous inputs would count here).
                py_staging_allocs=staging_allocs,
                plan_execs=plan.execs,
            )
        return _unflatten(treedef, outs)

    def _plan_hier_sync(
        self,
        leaves: Sequence[Any],
        treedef: Any,
        tree: Any,
        divisor: Optional[float],
        wire: Optional[str],
        timeout_ms: int,
    ) -> Any:
        """Hier plan execute: ONE native call runs the whole two-tier
        schedule per group (pack streamed into the intra reduce-scatter,
        unpack out of the broadcast — the triple pipeline survives the
        extra tiers), with the wire applied at the leader's inter hop."""
        if self._world_size > 1 and not self.hier_capable():
            raise RuntimeError(
                "plan_allreduce(hier=True) needs a hier-capable configure: "
                "the quorum's region map had < 2 distinct labels (or "
                "unlabeled members) — single-region cohorts ride the flat "
                "plan"
            )
        plan = self._plan_for(leaves, treedef, wire, hier=True)
        if plan is None:
            if wire is None:
                # Non-native leaf dtypes: the bulk hier path groups them
                # into f32 with equivalent semantics.
                return self._allreduce_hier_sync(
                    tree, ReduceOp.SUM, divisor, None, timeout_ms
                )
            if wire in ("q8", "q8ef"):
                raise ValueError(
                    "hier plan wire 'q8'/'q8ef' requires f32/bf16 leaves"
                )
            raise ValueError(
                "hier plan wire 'bf16' requires native-dtype leaves"
            )
        with self._op("plan_allreduce") as timing:
            # pointer gather; host leaves make it ~free
            with timing.phase("d2h"):
                staging_allocs = 0
                refs = []  # keep host views alive across the native call
                in_ptrs = plan.in_ptrs
                for i, l in enumerate(leaves):
                    a = np.asarray(l)  # zero-copy for numpy / CPU jax leaves
                    if not a.flags.c_contiguous:
                        a = np.ascontiguousarray(a)
                        staging_allocs += 1
                    refs.append(a)
                    in_ptrs[i] = a.ctypes.data
            # the single native call: the whole schedule
            with timing.phase("ring"):
                outs = plan.out_sets[plan.flip]
                out_ptrs = plan.out_ptrs[plan.flip]
                plan.flip ^= 1
                _check(
                    _lib.tft_plan_execute(
                        self._handle,
                        plan.plan_id,
                        in_ptrs,
                        out_ptrs,
                        float(divisor if divisor is not None else 1.0),
                        0 if divisor is None else 1,
                        timeout_ms,
                    )
                )
            del refs
            plan.execs += 1
            timing.record(
                wire=wire,
                hier=True,
                device_pack=False,
                bytes=plan.bytes,
                d2h_bytes=plan.bytes,
                _buckets_json=self._plan_stats_json(plan.plan_id),
                py_staging_allocs=staging_allocs,
                plan_execs=plan.execs,
                **(
                    self._hier_stats_fields(self._last_hier_dict())
                    if self._world_size > 1
                    else {"wire_bytes": plan.wire_bytes}
                ),
            )
        return _unflatten(treedef, outs)

    def _plan_execute_device(
        self,
        plan: _CommPlan,
        packer: _DeviceWirePacker,
        leaves: Sequence[Any],
        treedef: Any,
        divisor: Optional[float],
        wire: Optional[str],
        timeout_ms: int,
    ) -> Any:
        """Device-packed plan execute: the Pallas kernels emit the wire
        encoding on the accelerator (advancing the device-resident EF
        carry on the q8ef wire), only WIRE-sized bytes cross d2h, and the
        prepacked native plan decodes them straight into its staging —
        ring and unpack are the host-pack plan's own, so results are
        bit-identical to host packing."""
        with self._op("plan_allreduce") as timing:
            # device kernel dispatch + DMA enqueue
            with timing.phase("pack"):
                payloads, scales = packer.pack_step(leaves)
                for a in payloads:
                    a.copy_to_host_async()
                for a in scales:
                    a.copy_to_host_async()
            timing.ready([*payloads, *scales])
            # blocking readback of the wire buffers
            with timing.phase("d2h"):
                staging_allocs = 0
                host_payloads: List[np.ndarray] = []
                for a in payloads:
                    h = np.asarray(a)
                    if not h.flags.c_contiguous:
                        h = np.ascontiguousarray(h)
                        staging_allocs += 1
                    host_payloads.append(h)
                host_scales = [
                    np.ascontiguousarray(np.asarray(a)) for a in scales
                ]
            # the single native call: decode+ring+unpack
            with timing.phase("ring"):
                gin, gaux = plan.group_in, plan.group_aux
                q8 = wire in ("q8", "q8ef")
                for gi, h in enumerate(host_payloads):
                    gin[gi] = h.ctypes.data
                    gaux[gi] = host_scales[gi].ctypes.data if q8 else None
                outs = plan.out_sets[plan.flip]
                out_ptrs = plan.out_ptrs[plan.flip]
                plan.flip ^= 1
                _check(
                    _lib.tft_plan_execute_pre(
                        self._handle,
                        plan.plan_id,
                        gin,
                        gaux,
                        out_ptrs,
                        float(divisor if divisor is not None else 1.0),
                        0 if divisor is None else 1,
                        timeout_ms,
                    )
                )
            plan.execs += 1
            timing.record(
                wire=wire,
                device_pack=True,
                bytes=plan.bytes,
                wire_bytes=plan.wire_bytes,
                # The tentpole number: the device link carried the WIRE
                # encoding (int8 codes + scale sidecar / bf16 words), not the
                # full-width leaves.
                d2h_bytes=sum(h.nbytes for h in host_payloads) + sum(
                    h.nbytes for h in host_scales
                ),
                d2h_calls=len(host_payloads) + len(host_scales),
                _buckets_json=self._plan_stats_json(plan.plan_id),
                py_staging_allocs=staging_allocs,
                plan_execs=plan.execs,
            )
        return _unflatten(treedef, outs)

    def _plan_stats_json(self, plan_id: int) -> str:
        out = ctypes.c_void_p()
        _check(_lib.tft_plan_stats_json(self._handle, plan_id, ctypes.byref(out)))
        return _native._take_string(out)

    def plan_reset_feedback(self) -> None:
        """Zeroes the EF carry of every cached q8ef plan — native AND
        device-resident (the device packer owns the carry on the
        device-pack path) — the heal/abort discipline. Runs on the op
        thread so it cannot interleave with an in-flight execute."""
        def reset() -> None:
            for plan in self._plans.values():
                if plan is not None and plan.wire == "q8ef":
                    _check(
                        _lib.tft_plan_reset_feedback(
                            self._handle, plan.plan_id
                        )
                    )
            for packer in self._dev_packers.values():
                if packer is not None:
                    packer.reset_feedback()
        self._submit(reset).wait()

    def allgather(self, tree: Any) -> Work:
        timeout_ms = _ms(self._timeout)
        return self._submit(lambda: self._allgather_sync(tree, timeout_ms))

    def _allgather_sync(self, tree: Any, timeout_ms: int) -> List[Any]:
        if self._world_size == 1:
            return [tree]
        leaves, treedef = _flatten(tree)
        if leaves and all(_is_jax_array(l) for l in leaves):
            # Device-packed fast path, mirroring allreduce's: without it,
            # a quantized {q, scale} payload of ~60 leaves costs ~60
            # device->host round-trips.
            return self._allgather_device_packed(leaves, treedef, timeout_ms)
        arrays = [np.ascontiguousarray(_as_numpy(l)) for l in leaves]
        was_jax = [_is_jax_array(l) for l in leaves]
        packed = b"".join(a.tobytes() for a in arrays)
        nbytes = len(packed)
        inbuf = ctypes.create_string_buffer(packed, nbytes) if nbytes else None
        out = np.empty(max(nbytes * self._world_size, 1), dtype=np.uint8)
        _check(
            _lib.tft_hc_allgather(
                self._handle,
                inbuf,
                out.ctypes.data_as(ctypes.c_void_p),
                nbytes,
                timeout_ms,
            )
        )
        results: List[Any] = []
        for r in range(self._world_size):
            offset = r * nbytes
            out_leaves: List[Any] = []
            for i, a in enumerate(arrays):
                leaf = (
                    out[offset : offset + a.nbytes]
                    .view(a.dtype)
                    .reshape(a.shape)
                    .copy()
                )
                offset += a.nbytes
                if was_jax[i]:
                    import jax.numpy as jnp

                    leaf = jnp.asarray(leaf)
                out_leaves.append(leaf)
            results.append(_unflatten(treedef, out_leaves))
        return results

    def _allgather_device_packed(
        self, leaves, treedef, timeout_ms: int
    ) -> List[Any]:
        """All-jax-leaf allgather: one jitted on-device concat per EXACT
        dtype (byte-preserving — no accumulation upcasts), one d2h per
        dtype group, one ring gather over the concatenated groups, then
        per-member on-device unpack."""
        import jax.numpy as jnp

        key = (
            "ag", treedef,
            tuple((l.shape, np.dtype(l.dtype)) for l in leaves),
        )
        packer = self._packers.get(key)
        if packer is None:
            packer = self._packers[key] = _DevicePacker(
                leaves, exact_dtypes=True
            )
        with self._op("allgather") as timing:
            with timing.phase("pack"):
                bufs = packer.pack(leaves)
                names = sorted(bufs)  # deterministic group order on the wire
                for name in names:  # queue every DMA before blocking on the first
                    bufs[name].copy_to_host_async()
            timing.ready([bufs[name] for name in names])
            with timing.phase("d2h"):
                host = {name: np.ascontiguousarray(np.asarray(bufs[name]))
                        for name in names}
            # host staging copies are not the wire
            with timing.phase("host_copy"):
                packed = b"".join(host[name].tobytes() for name in names)
                nbytes = len(packed)
                inbuf = ctypes.create_string_buffer(packed, nbytes) if nbytes else None
                out = np.empty(max(nbytes * self._world_size, 1), dtype=np.uint8)
            with timing.phase("ring"):
                _check(
                    _lib.tft_hc_allgather(
                        self._handle,
                        inbuf,
                        out.ctypes.data_as(ctypes.c_void_p),
                        nbytes,
                        timeout_ms,
                    )
                )
            with timing.phase("h2d"):
                stripe_s = self._last_stripe_seconds()
                results: List[Any] = []
                for r in range(self._world_size):
                    offset = r * nbytes
                    member_bufs = {}
                    for name in names:
                        a = host[name]
                        member_bufs[name] = jnp.asarray(
                            out[offset : offset + a.nbytes].view(a.dtype)
                        )
                        offset += a.nbytes
                    results.append(_unflatten(treedef, packer.unpack(member_bufs)))
            timing.record(
                bytes=nbytes,
                # this rank's packed groups cross down once; the gathered
                # members come back on the h2d leg
                d2h_bytes=nbytes,
                d2h_calls=len(names),
                stripe_s=stripe_s,
            )
        return results

    # -- sharded (split) ops --

    def _shard_ranges(
        self, count: int, esize: int, eff: int
    ) -> List[Tuple[int, int]]:
        """(start, len) element ranges this rank owns of a count-element
        group at the pinned stripe partition (native layout arithmetic)."""
        if self._world_size == 1:
            return [(0, count)]
        buf = (ctypes.c_int64 * (2 * _MAX_STRIPES))()
        n = _lib.tft_hc_shard_ranges(
            self._handle, count, esize, self._rank, eff, buf, _MAX_STRIPES
        )
        if n < 0:
            _check(2)
        return [(buf[2 * i], buf[2 * i + 1]) for i in range(n)]

    def reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
        grid_shard: bool = False,
    ) -> Work:
        """``grid_shard`` (q8 wire only) applies the fused op's phase-2
        owner quantize+decode to the owned shard, so reduce_scatter +
        allgather_into reproduces ``allreduce(wire='q8')`` bit-for-bit —
        the determinism oracle for decomposed-vs-fused tests. Production
        callers leave it False: the shard never rides the lossy phase-2
        wire, so it keeps full f32 precision for free."""
        timeout_ms = _ms(self._timeout)
        if wire not in (None, "q8"):
            raise ValueError(f"unsupported wire: {wire!r}")
        if grid_shard and wire != "q8":
            raise ValueError("grid_shard only applies to wire='q8'")
        if op == ReduceOp.AVG:
            divisor, op = float(self._world_size), ReduceOp.SUM
        if op != ReduceOp.SUM and (divisor is not None or wire == "q8"):
            raise ValueError(
                "divisor / wire='q8' compose with ReduceOp.SUM/AVG only"
            )
        return self._submit(
            lambda: self._reduce_scatter_sync(tree, op, divisor, wire,
                                              grid_shard, timeout_ms)
        )

    def _reduce_scatter_sync(
        self,
        tree: Any,
        op: ReduceOp,
        divisor: Optional[float],
        wire: Optional[str],
        grid_shard: bool,
        timeout_ms: int,
    ) -> TreeShard:
        """Phase 1 of the ring only: the full tree crosses d2h ONCE, the
        ring reduces it in place, and only the ~1/world_size owned shard
        re-uploads — the return leg and everything downstream of it scale
        with the shard, not the model."""
        leaves, treedef = _flatten(tree)
        if not leaves:
            raise ValueError("reduce_scatter of an empty tree")
        sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        all_jax = all(_is_jax_array(l) for l in leaves)
        native_op = int(op)

        with self._op("reduce_scatter") as timing:
            with timing.phase("d2h"):
                if all_jax:
                    key = ("rsq8" if wire == "q8" else "rs", treedef, sig)
                    packer = self._packers.get(key)
                    if packer is None:
                        packer = self._packers[key] = _DevicePacker(
                            leaves, force_f32=(wire == "q8")
                        )
                    bufs = packer.pack(leaves)
                    names = sorted(bufs)
                    for name in names:  # queue every DMA before blocking on one
                        bufs[name].copy_to_host_async()
                    host = {}
                    for name in names:
                        arr = np.asarray(bufs[name])
                        if not arr.flags.writeable or not arr.flags.c_contiguous:
                            arr = np.array(arr)  # ring reduces in place
                        host[name] = arr
                    groups = {str(acc): idxs for acc, idxs in packer.groups.items()}
                    was_jax = None
                else:
                    packer = None
                    arrays = [_as_numpy(l) for l in leaves]
                    was_jax = [_is_jax_array(l) for l in leaves]
                    groups = {}
                    for i, a in enumerate(arrays):
                        if wire == "q8":
                            acc = np.dtype(np.float32)
                        else:
                            acc = (a.dtype if a.dtype in _NATIVE_DTYPES
                                   else np.dtype(np.float32))
                        groups.setdefault(str(acc), []).append(i)
                    host = {
                        name: np.concatenate(
                            [arrays[i].astype(np.dtype(name), copy=False).ravel()
                             for i in idxs]
                        )
                        for name, idxs in groups.items()
                    }
                    names = sorted(host)

            with timing.phase("ring"):
                values: Dict[str, Any] = {}
                counts: Dict[str, int] = {}
                ranges: Dict[str, List[Tuple[int, int]]] = {}
                layout: Dict[str, int] = {}
                dtypes: Dict[str, Any] = {}
                stripe_s: List[float] = []
                for name in names:
                    buf = host[name]
                    count = buf.size
                    esize = 1 if wire == "q8" else buf.itemsize
                    eff = _effective_stripes(count * esize, self._stripes)
                    counts[name] = count
                    layout[name] = eff
                    dtypes[name] = buf.dtype
                    rng = self._shard_ranges(count, esize, eff)
                    ranges[name] = rng
                    shard = np.empty(sum(l for _, l in rng), dtype=buf.dtype)
                    if self._world_size == 1:
                        shard[:] = buf
                    elif wire == "q8":
                        _check(
                            _lib.tft_hc_reduce_scatter_q8(
                                self._handle,
                                buf.ctypes.data_as(ctypes.c_void_p),
                                count,
                                shard.ctypes.data_as(ctypes.c_void_p),
                                1 if grid_shard else 0,
                                eff,
                                timeout_ms,
                            )
                        )
                    else:
                        _check(
                            _lib.tft_hc_reduce_scatter(
                                self._handle,
                                buf.ctypes.data_as(ctypes.c_void_p),
                                count,
                                _NATIVE_DTYPES[buf.dtype],
                                native_op,
                                shard.ctypes.data_as(ctypes.c_void_p),
                                eff,
                                timeout_ms,
                            )
                        )
                    if self._world_size > 1:
                        stripe_s.extend(self._last_stripe_seconds())
                    if divisor is not None and divisor != 1:
                        shard = self._apply_divisor(shard, divisor)
                    values[name] = shard

            with timing.phase("h2d"):
                if all_jax:
                    import jax.numpy as jnp

                    values = {name: jnp.asarray(v) for name, v in values.items()}
            timing.record(
                bytes=sum(host[n].nbytes for n in names),
                shard_bytes=sum(
                    np.asarray(v).nbytes for v in values.values()
                ),
                # q8 counts its scale sidecar (reduce-scatter runs ONE
                # quantized phase) + the op header, like every q8 path
                wire_bytes=sum(
                    counts[n] + _q8_wire_overhead(
                        layout[n], self._world_size, phases=1
                    ) if wire == "q8" else counts[n] * host[n].itemsize
                    for n in names
                ),
                # the full tree crosses down once (when it started on
                # device); only the shard returns
                d2h_bytes=(
                    sum(host[n].nbytes for n in names) if all_jax else 0
                ),
                stripe_s=stripe_s,
            )
        return TreeShard(
            values=values, counts=counts, ranges=ranges, layout=layout,
            dtypes=dtypes, groups=groups, treedef=treedef, sig=sig,
            rank=self._rank, world_size=self._world_size, packer=packer,
            was_jax=was_jax,
        )

    def allgather_into(
        self, shard: TreeShard, wire: Optional[str] = None
    ) -> Work:
        timeout_ms = _ms(self._timeout)
        if wire not in (None, "bf16"):
            raise ValueError(f"unsupported wire: {wire!r}")
        return self._submit(
            lambda: self._allgather_into_sync(shard, wire, timeout_ms)
        )

    def _allgather_into_sync(
        self, shard: TreeShard, wire: Optional[str], timeout_ms: int
    ) -> Any:
        """Phase 2 of the ring on CURRENT shard values: each member ships
        its (updated) shard, every member ends with the identical full
        tree. ``wire="bf16"`` rounds f32 groups to bfloat16 on the wire —
        half the bytes; every member (including the owner) adopts the
        decoded bf16 words, so the gathered tree is still bit-identical
        across ranks."""
        with self._op("allgather_into") as timing:
            with timing.phase("ring"):
                out_bufs: Dict[str, np.ndarray] = {}
                stripe_s: List[float] = []
                wire_bytes = 0
                d2h_bytes = 0
                for name in sorted(shard.counts):
                    count = shard.counts[name]
                    gdtype = np.dtype(shard.dtypes[name])
                    eff = shard.layout[name]
                    if _is_jax_array(shard.values[name]):
                        d2h_bytes += np.asarray(shard.values[name]).nbytes
                    vals = np.ascontiguousarray(np.asarray(shard.values[name]))
                    if vals.dtype != gdtype:
                        vals = vals.astype(gdtype)
                    expected = sum(l for _, l in shard.ranges[name])
                    if vals.size != expected:
                        raise ValueError(
                            f"shard group {name!r} has {vals.size} elements, layout "
                            f"expects {expected} — pass the TreeShard from "
                            "reduce_scatter (values replaced, layout intact)"
                        )
                    wdtype = gdtype
                    if wire == "bf16":
                        if gdtype == np.dtype(np.float32):
                            wdtype = _BF16
                        elif gdtype != _BF16:
                            raise ValueError(
                                "wire='bf16' applies to f32/bf16 groups only"
                            )
                    wvals = np.ascontiguousarray(vals.astype(wdtype, copy=False))
                    full = np.empty(count, dtype=wdtype)
                    if self._world_size == 1:
                        full[:] = wvals
                    else:
                        _check(
                            _lib.tft_hc_allgather_into(
                                self._handle,
                                wvals.ctypes.data_as(ctypes.c_void_p),
                                full.ctypes.data_as(ctypes.c_void_p),
                                count,
                                _NATIVE_DTYPES[np.dtype(wdtype)],
                                eff,
                                timeout_ms,
                            )
                        )
                        stripe_s.extend(self._last_stripe_seconds())
                    wire_bytes += count * np.dtype(wdtype).itemsize
                    if np.dtype(wdtype) != gdtype:
                        full = full.astype(gdtype)
                    out_bufs[name] = full

            with timing.phase("h2d"):
                if shard.packer is not None:
                    import jax.numpy as jnp

                    dev = {name: jnp.asarray(b) for name, b in out_bufs.items()}
                    out = _unflatten(shard.treedef, shard.packer.unpack(dev))
                else:
                    out_leaves: List[Any] = [None] * len(shard.sig)
                    for name, idxs in shard.groups.items():
                        buf = out_bufs[name]
                        off = 0
                        for i in idxs:
                            shape, dt = shard.sig[i]
                            n = int(np.prod(shape)) if shape else 1
                            leaf = buf[off:off + n].reshape(shape).astype(
                                dt, copy=False
                            )
                            off += n
                            if shard.was_jax is not None and shard.was_jax[i]:
                                import jax.numpy as jnp

                                leaf = jnp.asarray(leaf)
                            out_leaves[i] = leaf
                    out = _unflatten(shard.treedef, out_leaves)
            timing.record(
                bytes=sum(b.nbytes for b in out_bufs.values()),
                wire_bytes=wire_bytes,
                # only this rank's (updated) shard crosses down; the full
                # gathered tree returns on the h2d leg
                d2h_bytes=d2h_bytes,
                stripe_s=stripe_s,
            )
            return out

    def plan_reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
        ag_wire: Optional[str] = None,
    ) -> Work:
        """The plan-path grad leg (see Collectives.plan_reduce_scatter):
        one native call over a precompiled sharded plan — pack, rs phase,
        shard compaction and the divisor in one GIL release. At
        ``wire=None`` the reduced shard is bit-identical to the matching
        slice of ``plan_allreduce(wire=None)``'s result (same partition,
        same phase body, same f32 divide)."""
        timeout_ms = _ms(self._timeout)
        if wire not in (None, "bf16", "q8"):
            raise ValueError(f"unsupported wire: {wire!r}")
        if ag_wire not in (None, "bf16"):
            raise ValueError(f"unsupported ag_wire: {ag_wire!r}")
        if op == ReduceOp.AVG:
            if divisor is not None:
                raise ValueError("divisor only composes with ReduceOp.SUM")
            divisor, op = float(self._world_size), ReduceOp.SUM
        if op != ReduceOp.SUM:
            raise ValueError("plan_reduce_scatter supports SUM/AVG only")
        return self._submit(
            lambda: self._plan_reduce_scatter_sync(
                tree, divisor, wire, ag_wire, timeout_ms
            )
        )

    def _sharded_plan_for(
        self, leaves: Sequence[Any], treedef: Any, wire: Optional[str],
        ag_wire: Optional[str],
    ) -> Optional[_ShardedPlan]:
        sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        key: Any = (wire, ag_wire, treedef, sig, "sharded")
        if key in self._plans:
            return self._plans[key]
        try:
            plan: Optional[_ShardedPlan] = _ShardedPlan(
                self._handle, sig, treedef, wire, ag_wire,
                stripes=self._stripes, world=self._world_size,
            )
        except (KeyError, RuntimeError):
            # Non-f32 leaves (or a wire combination native rejects):
            # cache the verdict like the fused plan path.
            plan = None
        self._plans[key] = plan
        return plan

    def _plan_reduce_scatter_sync(
        self,
        tree: Any,
        divisor: Optional[float],
        wire: Optional[str],
        ag_wire: Optional[str],
        timeout_ms: int,
    ) -> TreeShard:
        leaves, treedef = _flatten(tree)
        if not leaves:
            raise ValueError("plan_reduce_scatter of an empty tree")
        plan = self._sharded_plan_for(leaves, treedef, wire, ag_wire)
        if plan is None:
            raise ValueError(
                "sharded comm plans take f32 leaves only (keep f32 master "
                "weights — the DiLoCo sharded-outer constraint — or use "
                "the fused plan path)"
            )
        # Its own op key: the grad leg bills separately from the param leg
        # (and from any fused plan op) in pop_op_stats.
        with self._op("plan_reduce_scatter") as timing:
            with timing.phase("d2h"):
                staging_allocs = 0
                refs = []  # keep host views alive across the native call
                in_ptrs = plan.in_ptrs
                all_jax = True
                for i, l in enumerate(leaves):
                    a = np.asarray(l)  # zero-copy for numpy / CPU jax leaves
                    if not a.flags.c_contiguous:
                        a = np.ascontiguousarray(a)
                        staging_allocs += 1
                    refs.append(a)
                    in_ptrs[i] = a.ctypes.data
                    all_jax = all_jax and _is_jax_array(l)
            with timing.phase("ring"):
                # Shards double-buffer like plan outputs: the caller may still
                # hold step k's shard while step k+1 reduces; older shards are
                # clobbered.
                shard_buf = plan.shard_sets[plan.shard_flip]
                plan.shard_flip ^= 1
                _check(
                    _lib.tft_plan_execute_rs(
                        self._handle,
                        plan.plan_id,
                        in_ptrs,
                        shard_buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                        float(divisor if divisor is not None else 1.0),
                        0 if divisor is None else 1,
                        timeout_ms,
                    )
                )
            del refs
            plan.execs += 1
            with timing.phase("h2d"):
                values: Dict[str, Any] = {"float32": shard_buf}
                if all_jax:
                    import jax.numpy as jnp

                    values = {"float32": jnp.asarray(shard_buf)}
            timing.record(
                wire=wire,
                bytes=plan.bytes,
                shard_bytes=plan.shard_count * 4,
                wire_bytes=plan.rs_wire_bytes,
                # the full tree crosses down once (when it started on
                # device); only the shard returns
                d2h_bytes=plan.bytes if all_jax else 0,
                _buckets_json=self._plan_stats_json(plan.plan_id),
                py_staging_allocs=staging_allocs,
                plan_execs=plan.execs,
            )
        return TreeShard(
            values=values,
            counts={"float32": plan.total},
            ranges={"float32": self._shard_ranges(plan.total, 4, plan.eff)},
            layout={"float32": plan.eff},
            dtypes={"float32": np.dtype(np.float32)},
            groups={"float32": list(range(len(leaves)))},
            treedef=treedef,
            sig=plan.sig,
            rank=self._rank,
            world_size=self._world_size,
            packer=None,
            was_jax=[_is_jax_array(l) for l in leaves],
            plan=plan,
        )

    def plan_allgather_into(
        self, shard: TreeShard, wire: Optional[str] = None
    ) -> Work:
        timeout_ms = _ms(self._timeout)
        if wire not in (None, "bf16"):
            raise ValueError(f"unsupported wire: {wire!r}")
        return self._submit(
            lambda: self._plan_allgather_into_sync(shard, wire, timeout_ms)
        )

    def _plan_allgather_into_sync(
        self, shard: TreeShard, wire: Optional[str], timeout_ms: int
    ) -> Any:
        """Param leg of the sharded plan: scatter the updated shard back,
        one ag phase at the plan's ag wire, unpack into the double-
        buffered output leaves. bf16: every member (owner included)
        adopts the identical decoded words — gathered params stay
        bit-identical across the cohort."""
        plan = shard.plan
        if plan is None:
            # A bulk-path TreeShard (reduce_scatter): same contract, bulk
            # ops serve it.
            return self._allgather_into_sync(shard, wire, timeout_ms)
        if wire != plan.ag_wire:
            raise ValueError(
                f"plan_allgather_into wire {wire!r} does not match the "
                f"plan's ag_wire {plan.ag_wire!r} (pre-declared at "
                "plan_reduce_scatter — the header pins it cohort-wide)"
            )
        vals = shard.values.get("float32")
        if vals is None or len(shard.values) != 1:
            raise ValueError(
                "pass the TreeShard from plan_reduce_scatter (values "
                "replaced, layout intact)"
            )
        # The param leg's own op key, billed at the AG wire. Its buckets
        # (leg=2) append after the grad leg's (leg=1) in the plan's stat
        # window, so the pair reads as one step.
        with self._op("plan_allgather_into") as timing:
            with timing.phase("d2h"):
                d2h_bytes = 0
                if _is_jax_array(vals):
                    d2h_bytes = np.asarray(vals).nbytes
                v = np.ascontiguousarray(np.asarray(vals))
                if v.dtype != np.dtype(np.float32):
                    v = v.astype(np.float32)
                if v.size != plan.shard_count:
                    raise ValueError(
                        f"shard has {v.size} elements, the plan's layout expects "
                        f"{plan.shard_count} — pass the TreeShard from "
                        "plan_reduce_scatter (values replaced, layout intact)"
                    )
            with timing.phase("ring"):
                outs = plan.out_sets[plan.flip]
                out_ptrs = plan.out_ptrs[plan.flip]
                plan.flip ^= 1
                _check(
                    _lib.tft_plan_execute_ag(
                        self._handle,
                        plan.plan_id,
                        v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                        out_ptrs,
                        timeout_ms,
                    )
                )
            plan.execs += 1
            with timing.phase("h2d"):
                out_leaves: List[Any] = []
                for i in range(len(plan.sig)):
                    leaf: Any = outs[i]
                    if shard.was_jax is not None and shard.was_jax[i]:
                        import jax.numpy as jnp

                        leaf = jnp.asarray(leaf)
                    out_leaves.append(leaf)
                out = _unflatten(shard.treedef, out_leaves)
            timing.record(
                wire=wire,
                bytes=plan.bytes,
                wire_bytes=plan.ag_wire_bytes,
                # only this rank's (updated) shard crosses down; the full
                # gathered tree returns on the h2d leg
                d2h_bytes=d2h_bytes,
                _buckets_json=self._plan_stats_json(plan.plan_id),
                plan_execs=plan.execs,
            )
            return out

    def broadcast(self, tree: Any, root: int = 0) -> Work:
        timeout_ms = _ms(self._timeout)
        return self._submit(lambda: self._broadcast_sync(tree, root, timeout_ms))

    def _broadcast_sync(self, tree: Any, root: int, timeout_ms: int) -> Any:
        if self._world_size == 1:
            if root != 0:
                raise RuntimeError(f"bad broadcast root {root} for world size 1")
            return tree
        leaves, treedef = _flatten(tree)
        arrays = [np.ascontiguousarray(_as_numpy(l)) for l in leaves]
        was_jax = [_is_jax_array(l) for l in leaves]
        packed = bytearray(b"".join(a.tobytes() for a in arrays))
        nbytes = len(packed)
        buf = (ctypes.c_char * nbytes).from_buffer(packed) if nbytes else None
        _check(_lib.tft_hc_broadcast(self._handle, buf, nbytes, root, timeout_ms))
        offset = 0
        view = memoryview(packed)
        out_leaves: List[Any] = []
        for i, a in enumerate(arrays):
            size = a.nbytes
            out = (
                np.frombuffer(view[offset : offset + size], dtype=a.dtype)
                .reshape(a.shape)
                .copy()
            )
            offset += size
            if was_jax[i]:
                import jax.numpy as jnp

                out = jnp.asarray(out)
            out_leaves.append(out)
        return _unflatten(treedef, out_leaves)

    def barrier(self) -> Work:
        timeout_ms = _ms(self._timeout)
        return self._submit(
            lambda: _check(_lib.tft_hc_barrier(self._handle, timeout_ms))
        )


class DummyCollectives(Collectives):
    """No-op fake for tests and wrapper semantics, the reference's
    ProcessGroupDummy (torchft/process_group.py:333-384)."""

    def __init__(self, rank: int = 0, world_size: int = 1) -> None:
        self._rank = rank
        self._world_size = world_size
        self.configure_count = 0
        self.op_count = 0
        self.last_regions: Optional[List[str]] = None
        self.last_hosts: Optional[List[str]] = None
        self._hier = False

    def configure(
        self,
        store_addr: str,
        rank: int,
        world_size: int,
        regions: Optional[Sequence[str]] = None,
        hosts: Optional[Sequence[str]] = None,
    ) -> None:
        self.configure_count += 1
        self._rank = rank
        self._world_size = world_size
        self.last_regions = list(regions) if regions else None
        self.last_hosts = list(hosts) if hosts else None
        # Mirror the host ring's capability rule so wrapper-semantics
        # tests can drive the hier dispatch paths without a real ring:
        # multi-region, or a (region, host) pair grouping >= 2 ranks.
        multi_region = bool(
            regions
            and len(set(regions)) >= 2
            and all(regions)
            and world_size > 1
        )
        host_grouped = False
        if hosts and all(hosts) and world_size > 1:
            keys = [
                ((regions[i] if regions and all(regions) else ""), hosts[i])
                for i in range(len(hosts))
            ]
            host_grouped = any(keys.count(k) >= 2 for k in keys)
        self._hier = multi_region or host_grouped

    def hier_capable(self) -> bool:
        return self._hier

    def allreduce_hier(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        """Lossless fake of the two-tier schedule (sum of one member);
        raises without a usable region map, like the real backend."""
        if not self._hier and self._world_size > 1:
            raise RuntimeError("DummyCollectives: no region map configured")
        return self.allreduce(tree, op, divisor=divisor)

    def allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,  # accepted, ignored (lossless fake)
    ) -> Work:
        self.op_count += 1
        if divisor is not None and divisor != 1:
            # The manager's AVG contract delegates the participant divide
            # to the backend; the fake must honor it or wrapper-semantics
            # tests see undivided gradients.
            import jax

            tree = jax.tree_util.tree_map(
                lambda l: _divide_leaf(l, divisor), tree
            )
        return _completed(tree)

    def plan_allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,  # accepted, ignored (lossless fake)
        device_pack: Optional[bool] = None,  # accepted, ignored
        hier: bool = False,
    ) -> Work:
        """Same lossless semantics as the fake allreduce — wrapper tests
        exercise the plan-path call shape without a ring. ``hier``
        reproduces the real backend's capability rule (raises on a
        multi-member cohort without a usable region map)."""
        if op == ReduceOp.AVG:
            if divisor is not None:
                raise ValueError("divisor only composes with ReduceOp.SUM")
            divisor = float(self._world_size)
        if hier and not self._hier and self._world_size > 1:
            raise RuntimeError("DummyCollectives: no region map configured")
        return self.allreduce(tree, ReduceOp.SUM, divisor=divisor)

    def reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        """Lossless fake: the 'shard' is the whole flat-packed tree (the
        world-size-1 shard layout), so reduce_scatter → update →
        allgather_into round-trips exactly."""
        self.op_count += 1
        leaves, treedef = _flatten(tree)
        sig = tuple((l.shape, np.dtype(l.dtype)) for l in leaves)
        flat = np.concatenate(
            [np.asarray(l).astype(np.float32, copy=False).ravel()
             for l in leaves]
        ) if leaves else np.zeros((0,), np.float32)
        if divisor is not None and divisor != 1:
            flat = flat / divisor
        name = str(np.dtype(np.float32))
        return _completed(TreeShard(
            values={name: flat},
            counts={name: flat.size},
            ranges={name: [(0, flat.size)]},
            layout={name: 1},
            dtypes={name: np.dtype(np.float32)},
            groups={name: list(range(len(leaves)))},
            treedef=treedef, sig=sig,
            rank=self._rank, world_size=self._world_size,
        ))

    def allgather_into(
        self, shard: TreeShard, wire: Optional[str] = None
    ) -> Work:
        self.op_count += 1
        name = str(np.dtype(np.float32))
        buf = np.asarray(shard.values[name])
        if wire == "bf16":
            buf = buf.astype(_BF16).astype(np.float32)
        out_leaves = []
        off = 0
        for shape, dt in shard.sig:
            n = int(np.prod(shape)) if shape else 1
            out_leaves.append(
                buf[off:off + n].reshape(shape).astype(dt, copy=False)
            )
            off += n
        return _completed(_unflatten(shard.treedef, out_leaves))

    def allgather(self, tree: Any) -> Work:
        self.op_count += 1
        return _completed([tree] * self._world_size)

    def broadcast(self, tree: Any, root: int = 0) -> Work:
        self.op_count += 1
        return _completed(tree)

    def barrier(self) -> Work:
        self.op_count += 1
        return _completed(None)

    def size(self) -> int:
        return self._world_size

    def rank(self) -> int:
        return self._rank
