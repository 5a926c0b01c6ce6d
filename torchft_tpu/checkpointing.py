"""Live checkpoint transport: recovering replicas fetch weights from a healthy
peer over HTTP instead of from disk.

Reference: torchft/checkpointing.py (CheckpointTransport ABC :34-88,
CheckpointServer :110-270). The lock-gating discipline is identical: the
server starts *disallowed*; ``send_checkpoint`` publishes a state dict for
exactly one step and allows reads; ``disallow_checkpoint`` (called from
``Manager.should_commit``, reference manager.py:591) re-locks it so the dict
can never be read mid-mutation. A request for any other step gets a 400.

Serialization is pytree-native: leaves are pulled to host (numpy) and the
tree is pickled STREAMING in both directions — chunked transfer encoding
into the socket on send, incremental unpickle off the response on receive —
so neither end ever holds the serialized payload as one buffer (peak extra
memory is one leaf, matching the reference's streamed torch.save,
reference checkpointing.py:139-170). jax arrays are reconstructed as numpy
on the receiver; the caller decides device placement/sharding
(``jax.device_put``) — the transport never touches devices.

Transport striping: by default the receiver fetches the payload as N byte
ranges over N PARALLEL connections (``TORCHFT_CKPT_STRIPES``, default 4;
the server serves ``/checkpoint/{step}/part/{i}/{n}`` from a per-step
pickle cache). A single TCP stream is window-limited on the
high-bandwidth-delay links heal traffic actually crosses — the same
bottleneck the collectives ring escapes with striped connections — and
heal time is dominated by this transfer. Striped mode trades the streamed
path's bounded memory for bandwidth (one full serialized copy on each
end); ``stripes=1`` or a pre-striping peer falls back to the streamed
single-connection path.

Streamed ZERO-COPY heal (the default when both ends speak it): the pickle
paths above serialize the whole dict, ship it, then deserialize, then
upload — three full-payload stop-the-world passes on the heal critical
path. The stream endpoints apply the CommPlan discipline (persistent
native comm plans, torchft_tpu/collectives.py) to the heal payload
instead: the LAYOUT (skeleton tree + per-leaf byte offsets) is computed
once per published step, the donor serves raw byte ranges straight out of
the live host buffers (memoryview slices — no per-request pickle, no
serialized copy), and the receiver ``readinto``s the ranges over
``TORCHFT_HEAL_STREAMS`` parallel connections into ONE preallocated
buffer, reconstructing each leaf as a zero-copy view the moment its bytes
land and dispatching its (async) device upload while later stripes are
still on the wire. Only the small skeleton rides pickle (through the same
safelist); the bulk payload is pure bytes — never executable. An optional
``wire="bf16"`` (``TORCHFT_HEAL_WIRE``) halves the bytes of f32 leaves
under an ``"opt_state"`` key — optimizer moments tolerate bf16 rounding
— while everything else (params included, whatever the caller named
them) ships raw bytes, so the healed replica's weights are bit-identical
to the donor's. Pre-stream peers 404 the endpoints and the client falls
back to the pickle paths unchanged.

Security model: deserialization uses a SAFELISTED unpickler — only CLASSES
from the scientific-stack modules state dicts are actually made of (numpy,
optax, jax, collections, ml_dtypes), the two numpy array reconstructors,
and a narrow builtins set can be referenced. Plain functions are never
resolvable (a REDUCE on a function is the pickle code-execution
primitive), and the safelist is snapshotted per load so a payload cannot
widen it mid-deserialization. This is deliberately stricter than the
reference's ``torch.load(weights_only=False)`` (reference
checkpointing.py:203). It is hardening, not authentication: the endpoint
is unauthenticated HTTP, so the checkpoint port must only be reachable
inside the training cluster's trusted network — same deployment
requirement as the reference. Custom user state classes outside the
safelist: call :func:`register_safe_modules` at startup on every replica.
"""

from __future__ import annotations

import io
import logging
import os
import pickle
import socket
import threading
import time
import urllib.error
import urllib.request
from abc import ABC, abstractmethod
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from datetime import timedelta
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Generic, List, Optional, Tuple, TypeVar

import numpy as np

from ._native import (
    WireCorruption,
    crc32c as _crc32c,
    crc32c_combine as _crc32c_combine,
    crc32c_update as _crc32c_update,
)
from .metrics import Metrics
from .profiling import timed_span

logger: logging.Logger = logging.getLogger(__name__)

T = TypeVar("T")


class CheckpointTransport(Generic[T], ABC):
    """Pluggable live-recovery transport. Reference checkpointing.py:34-88.

    ``metrics`` is the owning Manager's ``Metrics`` once a Manager owns
    the transport (it sets the attribute): where a transport times its
    own work, it files it there and stamps its spans with that step."""

    metrics: Optional[Metrics] = None

    @abstractmethod
    def metadata(self) -> str:
        """Returns transport metadata (e.g. the URL prefix) that recovering
        replicas need; shipped to peers through the quorum RPC."""

    @abstractmethod
    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: T, timeout: timedelta
    ) -> None:
        """Makes ``state_dict`` for ``step`` available to ``dst_ranks``."""

    def disallow_checkpoint(self) -> None:
        """Called once the training loop may mutate the state dict again."""

    @abstractmethod
    def recv_checkpoint(
        self, src_rank: int, metadata: str, step: int, timeout: timedelta
    ) -> T:
        """Fetches the state dict for ``step`` from the peer described by
        ``metadata``."""

    def shutdown(self, wait: bool = True) -> None:
        ...


def _to_host(tree: Any) -> Any:
    """Device→host: every array leaf becomes numpy (zero-copy where possible)."""
    import jax

    return jax.tree_util.tree_map(
        lambda l: np.asarray(l) if hasattr(l, "__array__") else l, tree
    )


def serialize_state_dict(state_dict: Any) -> bytes:
    """Pickles a pytree with all array leaves on host."""
    buf = io.BytesIO()
    pickle.dump(_to_host(state_dict), buf, protocol=pickle.HIGHEST_PROTOCOL)
    return buf.getvalue()


def dump_state_dict_stream(state_dict: Any, fileobj: Any) -> None:
    """Streams the pickled pytree straight into ``fileobj`` (a socket
    wrapper): pickle emits incrementally, so peak extra memory is one
    leaf's buffer, not the whole payload — the reference streams
    torch.save into the HTTP response the same way (reference
    checkpointing.py:139-170)."""
    pickle.dump(_to_host(state_dict), fileobj, protocol=pickle.HIGHEST_PROTOCOL)


def load_state_dict_stream(fileobj: Any) -> Any:
    """Safelisted unpickle reading incrementally from ``fileobj`` (e.g. an
    HTTP response): bounded-memory inverse of
    :func:`dump_state_dict_stream` — the full payload is never held as one
    bytes object. The safelist applies unchanged (it gates global lookups,
    not framing)."""
    return _SafeUnpickler(fileobj).load()


class _ChunkedWriter:
    """Minimal HTTP/1.1 chunked transfer encoder over the handler's
    ``wfile``; lets the server stream a response whose length is unknown
    up front (the streamed pickle)."""

    def __init__(self, wfile: Any) -> None:
        self._wfile = wfile

    def write(self, data: Any) -> int:
        # protocol-5 pickle passes PickleBuffer objects, not just bytes;
        # go through a flat memoryview so any buffer-protocol payload
        # (numpy array data included) streams without a copy
        mv = memoryview(data).cast("B")
        if mv.nbytes:
            self._wfile.write(f"{mv.nbytes:x}\r\n".encode("ascii"))
            self._wfile.write(mv)
            self._wfile.write(b"\r\n")
        return mv.nbytes

    def close(self) -> None:
        self._wfile.write(b"0\r\n\r\n")


# Module roots whose CLASSES state dicts are really made of. Extendable for
# user classes via register_safe_modules. NOTE: deliberately does NOT
# include torchft_tpu itself — a payload resolving this module's own
# helpers (e.g. register_safe_modules) could widen the list mid-load.
_SAFE_MODULE_ROOTS = {
    "numpy", "optax", "jax", "collections", "ml_dtypes",
}
# Non-class globals required by the numpy array pickle format. Functions
# are otherwise NEVER resolvable (a REDUCE on an arbitrary function is the
# code-execution primitive); these two reconstructors only build arrays.
# _ArraySlot is this module's own streamed-heal placeholder (a frozen
# data-only dataclass) — the ONE torchft_tpu name a skeleton payload may
# reference; everything else in this package stays unresolvable.
_SAFE_EXACT = {
    ("torchft_tpu.checkpointing", "_ArraySlot"),
    ("numpy.core.multiarray", "_reconstruct"),
    ("numpy._core.multiarray", "_reconstruct"),
    ("numpy.core.multiarray", "scalar"),
    ("numpy._core.multiarray", "scalar"),
    ("numpy.core.numeric", "_frombuffer"),
    ("numpy._core.numeric", "_frombuffer"),
}
# Builtins narrowed to data constructors: resolving e.g. builtins.eval or
# getattr is how pickle payloads become code execution.
_SAFE_BUILTINS = {
    "bool", "bytearray", "bytes", "complex", "dict", "float", "frozenset",
    "int", "list", "range", "set", "slice", "str", "tuple",
}


def register_safe_modules(*roots: str) -> None:
    """Allows CLASSES from additional top-level modules (e.g. your package
    defining a custom state holder) to be referenced by incoming
    checkpoints. Call at startup on every replica — the set is snapshotted
    when a load begins, so a payload cannot extend it mid-load."""
    _SAFE_MODULE_ROOTS.update(roots)


class _SafeUnpickler(pickle.Unpickler):
    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        # Snapshot: registration during a hostile load has no effect on it.
        self._roots = frozenset(_SAFE_MODULE_ROOTS)

    def find_class(self, module: str, name: str) -> Any:
        if module == "builtins" and name in _SAFE_BUILTINS:
            return super().find_class(module, name)
        if (module, name) in _SAFE_EXACT:
            return super().find_class(module, name)
        if module.partition(".")[0] in self._roots:
            obj = super().find_class(module, name)
            # Classes only: data containers may be constructed, but plain
            # functions (the REDUCE code-execution primitive) may not.
            if isinstance(obj, type):
                return obj
        raise pickle.UnpicklingError(
            f"checkpoint references disallowed global {module}.{name}; "
            "if this is your own state CLASS, call "
            "torchft_tpu.checkpointing.register_safe_modules"
            f"({module.partition('.')[0]!r}) on every replica"
        )


def deserialize_state_dict(raw: bytes) -> Any:
    """Inverse of :func:`serialize_state_dict` through the safelisted
    unpickler (see module docstring). Array leaves come back as numpy."""
    return _SafeUnpickler(io.BytesIO(raw)).load()


# -- streamed zero-copy heal transport --------------------------------------

# readinto granularity on the receiver: also the grain at which completed
# leaves become eligible for their h2d dispatch while later bytes are
# still on the wire.
_STREAM_CHUNK = 1 << 20


@dataclass(frozen=True)
class _ArraySlot:
    """Placeholder for one array leaf in the streamed-heal skeleton: where
    its bytes live in the packed stream and how to decode them. Pure data
    — safe to reconstruct from an untrusted payload (safelisted exactly,
    see ``_SAFE_EXACT``)."""

    shape: Tuple[int, ...]
    dtype: str       # original dtype name (what the receiver restores)
    wire_dtype: str  # dtype as shipped (bf16 when downcast on the wire)
    offset: int      # byte offset into the packed stream
    nbytes: int


def _dtype_by_name(name: str) -> np.dtype:
    """np.dtype from its name, resolving ml_dtypes names (bfloat16) that
    plain numpy only knows once ml_dtypes is imported."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def _is_opt_state_path(path: Any) -> bool:
    """True when a tree_flatten_with_path keypath passes through a
    component named ``opt_state`` — the ONLY leaves the bf16 wire may
    downcast. Protect-by-default: a layout this predicate doesn't
    recognize ships raw f32 (no compression) rather than silently
    rounding what might be weights — bit-identity of the healed
    replica's parameters must hold for ARBITRARY user state dicts, not
    just ones that happen to name their weights ``params``."""
    for entry in path:
        key = getattr(entry, "key", getattr(entry, "name", None))
        if key is None:
            key = getattr(entry, "idx", None)
        if key == "opt_state":
            return True
    return False


def _heal_wire_from_env() -> Optional[str]:
    wire = os.environ.get("TORCHFT_HEAL_WIRE", "").strip().lower()
    if wire in ("", "none", "f32", "raw"):
        return None
    if wire != "bf16":
        raise ValueError(f"unsupported TORCHFT_HEAL_WIRE: {wire!r}")
    return wire


class _StreamStaging:
    """The donor half of the streamed heal: the CommPlan discipline
    applied to a published state dict. Built ONCE per (step, wire) —
    layout = skeleton tree (array leaves replaced by :class:`_ArraySlot`)
    + per-leaf byte offsets — and then every range request is served as
    memoryview slices straight off the live host buffers: no per-request
    pickle, no concatenated serialized copy. ``wire="bf16"`` casts f32
    leaves INSIDE an ``opt_state`` subtree once at build (the only
    copies the staging ever makes beyond non-contiguous inputs).

    ``shard_of=(rank, world)`` range-limits the capture: the layout
    (offsets, skeleton, ``total``) is computed from shapes alone, then
    only the byte span intersecting this member's ``total*rank//world
    .. total*(rank+1)//world`` range is materialized — a straddling
    leaf contributes just its in-range element slice (aligned to the
    wire itemsize), never the whole array. A durable snapshot member
    only ever writes its own ~1/W shard, so this caps the
    trainer-visible capture cost at ~1/W of the packed stream instead
    of all of it. The floor split MUST mirror ``durable.shard_bounds``;
    range reads outside the captured span raise rather than ship
    silent gaps."""

    def __init__(
        self,
        state_dict: Any,
        wire: Optional[str],
        seq: int = 0,
        snapshot: bool = False,
        shard_of: Optional[Tuple[int, int]] = None,
        pin_leaves: bool = False,
    ) -> None:
        import jax

        leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(
            state_dict
        )
        # Pass 1 — layout only. Offsets, wire dtypes and the packed
        # total follow from shapes, so the full skeleton exists before a
        # single byte of array data is touched. ``None`` plan entries
        # keep alignment with the skeleton's non-array leaves.
        plan: List[
            Optional[Tuple[Any, Any, np.dtype, np.dtype, int, int]]
        ] = []
        skeleton_leaves: List[Any] = []
        offset = 0
        for path, leaf in leaves_with_path:
            if not (isinstance(leaf, np.ndarray) or _is_jax_leaf(leaf)):
                # scalars / strings / exotic leaves ride the skeleton
                # pickle exactly as before
                skeleton_leaves.append(leaf)
                plan.append(None)
                continue
            odtype = np.dtype(leaf.dtype)
            if (
                wire == "bf16"
                and odtype == np.dtype(np.float32)
                and _is_opt_state_path(path)
            ):
                import ml_dtypes

                wdtype = np.dtype(ml_dtypes.bfloat16)
            else:
                wdtype = odtype
            shape = tuple(leaf.shape)
            nbytes = int(np.prod(shape, dtype=np.int64)) * wdtype.itemsize
            skeleton_leaves.append(
                _ArraySlot(
                    shape=shape,
                    dtype=odtype.name,
                    wire_dtype=wdtype.name,
                    offset=offset,
                    nbytes=nbytes,
                )
            )
            plan.append((path, leaf, odtype, wdtype, offset, nbytes))
            offset += nbytes
        self.total = offset
        if shard_of is not None:
            rank, world = shard_of
            begin = offset * rank // world
            end = offset * (rank + 1) // world
        else:
            begin, end = 0, offset
        self._range = (begin, end)
        if snapshot:
            # Snapshot capture: dispatch every in-range leaf's d2h
            # before materializing any of them, so the transfers overlap
            # each other instead of serializing leaf by leaf — this is
            # the whole trainer stall of an async durable snapshot.
            for ent in plan:
                if ent is None:
                    continue
                _, leaf, _, _, off, nbytes = ent
                if off < end and off + nbytes > begin and _is_jax_leaf(
                    leaf
                ):
                    try:
                        leaf.copy_to_host_async()
                    except AttributeError:
                        pass
        # entries: materialized memoryview, or a deferred-cast
        # ``(f32_slice_view, wire_dtype)`` pair resolved by _seg()
        segments: List[Any] = []
        starts: List[int] = []
        captured = 0
        # ``pin_leaves``: instead of an owning host copy, an uncompressed
        # jax leaf is captured as a zero-copy view with the immutable
        # Array itself pinned here — the XLA buffer cannot be freed while
        # the staging lives. ONLY sound when the trainer never donates
        # these buffers to a jit (donation reuses the device allocation
        # under the view); numpy leaves are mutable in place and always
        # get the owning copy regardless.
        self._pins: List[Any] = []
        for ent in plan:
            if ent is None:
                continue
            path, leaf, odtype, wdtype, off, nbytes = ent
            if off >= end or off + nbytes <= begin:
                # outside this member's shard: layout only, no copy
                continue
            # Leaf-local byte span this shard needs, aligned outward to
            # whole wire elements (a floor-split boundary can land
            # mid-element; the overlapping element is captured by both
            # neighbours, and write_range slices it back to the exact
            # byte). Only the in-range element slice is ever
            # materialized — the straddled remainder of a huge leaf is
            # a peer's duty, not this member's stall.
            ws = wdtype.itemsize
            lo = (max(begin, off) - off) // ws * ws
            hi = min(
                nbytes, -(-(min(end, off + nbytes) - off) // ws) * ws
            )
            sub = np.ascontiguousarray(np.asarray(leaf)).reshape(-1)[
                lo // ws: hi // ws
            ]
            if wdtype != odtype:
                if snapshot and pin_leaves and _is_jax_leaf(leaf):
                    # Deferred wire downcast: the pin keeps the
                    # immutable f32 leaf alive, so the astype (the
                    # compression itself) runs on the WRITER thread at
                    # first segment access — off the trainer stall
                    # entirely.
                    self._pins.append(leaf)
                    segments.append((sub, wdtype))
                    starts.append(off + lo)
                    captured += hi - lo
                    continue
                arr = sub.astype(wdtype)  # the cast owns its bytes
            elif not snapshot:
                # live heal staging: views of the trainer's buffers are
                # fine, the trainer blocks while ranges are read
                arr = np.ascontiguousarray(sub)
            elif pin_leaves and _is_jax_leaf(leaf):
                # zero-copy capture: the pinned immutable Array backs
                # the view for the staging's whole lifetime
                self._pins.append(leaf)
                arr = sub
            elif isinstance(leaf, np.ndarray) and not np.may_share_memory(
                sub, leaf
            ):
                arr = sub  # ascontiguousarray above already copied
            else:
                # Donation/aliasing guard: a SNAPSHOT staging outlives
                # the commit boundary — the background writer reads it
                # while the trainer runs steps N+1..N+k. Every captured
                # slice must own its bytes: a numpy leaf the trainer
                # mutates in place, or a jax leaf whose ``__array__``
                # aliased the device buffer (CPU backend zero-copy /
                # cached npy value) that a later donated jit overwrites,
                # would otherwise leak step-N+1 tensors into the step-N
                # snapshot.
                arr = sub.copy()
            if arr.nbytes != hi - lo:
                raise AssertionError(
                    f"packed layout drift: leaf materialized to "
                    f"{arr.nbytes} bytes, layout planned {hi - lo}"
                )
            # byte view (not a copy): numpy refuses buffer-protocol
            # export of non-native dtypes (ml_dtypes bfloat16), so go
            # through a uint8 reinterpret first
            segments.append(
                memoryview(arr.reshape(-1).view(np.uint8)).cast("B")
            )
            starts.append(off + lo)
            captured += hi - lo
        self.captured_bytes = captured
        self._segments = segments
        self._starts = starts
        skeleton = jax.tree_util.tree_unflatten(treedef, skeleton_leaves)
        buf = io.BytesIO()
        pickle.dump(
            {
                "v": 1,
                "wire": wire,
                "total": offset,
                "seq": seq,
                "skeleton": skeleton,
            },
            buf,
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        self.meta = buf.getvalue()

    def _seg(self, i: int) -> memoryview:
        """Segment ``i`` as a byte view, resolving a deferred wire cast
        on first access (writer-thread side of the zero-copy capture;
        cached so crc + write cast once)."""
        seg = self._segments[i]
        if not isinstance(seg, memoryview):
            sub, wdtype = seg
            arr = sub.astype(wdtype)
            seg = memoryview(arr.reshape(-1).view(np.uint8)).cast("B")
            self._segments[i] = seg
        return seg

    def _check_range(self, begin: int, end: int) -> None:
        cb, ce = self._range
        if begin < cb or end > ce:
            raise ValueError(
                f"range [{begin}, {end}) outside captured span "
                f"[{cb}, {ce}) of a shard-limited staging"
            )

    def write_range(self, wfile: Any, begin: int, end: int) -> None:
        """Streams bytes [begin, end) of the packed layout into ``wfile``
        as zero-copy slices of the staged buffers."""
        import bisect

        if begin >= end:
            return
        self._check_range(begin, end)
        i = bisect.bisect_right(self._starts, begin) - 1
        pos = begin
        while pos < end and i < len(self._segments):
            seg = self._seg(i)
            seg_start = self._starts[i]
            lo = pos - seg_start
            hi = min(len(seg), end - seg_start)
            if lo < hi:
                wfile.write(seg[lo:hi])
                pos = seg_start + hi
            i += 1

    def range_crc32c(self, begin: int, end: int) -> int:
        """CRC32C over bytes [begin, end) of the packed layout — the
        integrity header each /stream/ range response carries (the same
        Castagnoli polynomial the ring frames ride). Walks the exact
        slices :meth:`write_range` ships (zero-copy, chained through the
        native incremental update), so header and body can never
        disagree about what was covered."""
        import bisect

        if begin >= end:
            return _crc32c(b"")
        self._check_range(begin, end)
        i = bisect.bisect_right(self._starts, begin) - 1
        pos = begin
        parts: List[memoryview] = []
        while pos < end and i < len(self._segments):
            seg = self._seg(i)
            seg_start = self._starts[i]
            lo = pos - seg_start
            hi = min(len(seg), end - seg_start)
            if lo < hi:
                parts.append(seg[lo:hi])
                pos = seg_start + hi
            i += 1
        return _crc32c_combine(parts)


def _is_jax_leaf(leaf: Any) -> bool:
    import sys

    jax = sys.modules.get("jax")
    return jax is not None and isinstance(leaf, jax.Array)


def load_packed_meta(raw: bytes) -> Dict[str, Any]:
    """Safelisted unpickle of a packed-stream meta blob (the
    :class:`_StreamStaging` ``meta`` bytes): layout skeleton and wire
    parameters, never arbitrary code (same ``_SafeUnpickler`` the heal
    receiver applies to donor metadata)."""
    meta = _SafeUnpickler(io.BytesIO(raw)).load()
    if not isinstance(meta, dict) or "skeleton" not in meta:
        raise ValueError("packed meta blob missing skeleton")
    return meta


def rebuild_from_packed(
    meta: Dict[str, Any], buf: Any, *, device_put: bool = False
) -> Any:
    """Reconstruct a state tree from a packed byte buffer laid out by
    :class:`_StreamStaging` — the streamed-heal walker without the wire.
    ``buf`` must hold all ``meta['total']`` bytes (a durable snapshot
    reassembled from its shard files, or one donor range already
    verified). Wire-downcast leaves (bf16 opt-state) are cast back to
    their original dtype; with ``device_put`` each rebuilt leaf
    dispatches its async upload and the call blocks only on the residual
    drain."""
    import jax

    total = int(meta["total"])
    if len(buf) < total:
        raise ValueError(
            f"packed buffer holds {len(buf)} bytes, layout needs {total}"
        )
    slots, treedef = jax.tree_util.tree_flatten(meta["skeleton"])
    out_leaves: List[Any] = []
    device_leaves: List[Any] = []
    for slot in slots:
        if not isinstance(slot, _ArraySlot):
            out_leaves.append(slot)
            continue
        wdtype = _dtype_by_name(slot.wire_dtype)
        arr = np.frombuffer(
            buf,
            dtype=wdtype,
            count=slot.nbytes // wdtype.itemsize,
            offset=slot.offset,
        ).reshape(slot.shape)
        odtype = _dtype_by_name(slot.dtype)
        if wdtype != odtype:
            arr = arr.astype(odtype)
        if device_put and jax.dtypes.canonicalize_dtype(odtype) == odtype:
            import jax.numpy as jnp

            leaf: Any = jnp.asarray(arr)
            device_leaves.append(leaf)
        else:
            leaf = arr
        out_leaves.append(leaf)
    if device_leaves:
        jax.block_until_ready(device_leaves)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


class _TimedAcquire:
    """Lock acquire with timeout that raises instead of returning False.
    Reference checkpointing.py:91-107."""

    def __init__(self, lock: threading.Lock, timeout: timedelta) -> None:
        self._lock = lock
        self._timeout = timeout

    def __enter__(self) -> None:
        if not self._lock.acquire(timeout=self._timeout.total_seconds()):
            raise TimeoutError(
                f"timed out acquiring checkpoint lock after {self._timeout}"
            )

    def __exit__(self, *exc: object) -> None:
        self._lock.release()


class CheckpointServer(CheckpointTransport[T]):
    """Threaded HTTP server streaming ``GET /checkpoint/{step}``.

    Reference checkpointing.py:110-270. The server starts in the *disallowed*
    state: requests block on the gate lock until ``send_checkpoint``
    publishes a dict, and re-block after ``disallow_checkpoint``.
    """

    def __init__(self, timeout: timedelta = timedelta(seconds=30)) -> None:
        self._checkpoint_lock = threading.Lock()
        self._disallowed = False
        self._step = -1
        self._timeout = timeout
        self._state_dict: Any = None
        # One-shot pickle cache backing the striped /part/ endpoint
        self._serialized: Any = None
        self._serialized_step = -1
        # Streamed-heal staging, one per wire encoding, built once per
        # published step (the /streammeta/ + /stream/ endpoints)
        self._stagings: Dict[Optional[str], _StreamStaging] = {}
        self._stagings_step = -1
        # Publish nonce: bumped on every allow_checkpoint. Range
        # requests must echo the nonce their meta established — a
        # republish AT THE SAME STEP between a client's meta fetch and a
        # straggler range request would otherwise serve that range from
        # the NEW dict (identical layout, so no framing error) and hand
        # the healer a silently torn mix of two checkpoints.
        self._publish_seq = 0
        # In-flight /stream/ range responses: their bodies are zero-copy
        # views of the LIVE state-dict buffers (unlike the /part/
        # endpoint's immutable pickle cache), so disallow_checkpoint must
        # drain them before the training loop may mutate the dict.
        self._stream_inflight = 0
        self._stream_cv = threading.Condition()
        # What the last recv_checkpoint measured (path taken, meta/fetch/
        # h2d seconds, bytes, wire, streams) — benches fold this into
        # their heal breakdowns.
        self.last_fetch_stats: Optional[Dict[str, Any]] = None
        # its own until a Manager hands it the Manager's: the donor's
        # timers ``send_stage`` / ``send_serve`` and counter ``send_bytes``
        self.metrics = Metrics()

        # Gate starts held: nothing readable until the first send_checkpoint.
        self.disallow_checkpoint()

        ckpt_server = self

        class RequestHandler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_GET(self) -> None:
                try:
                    prefix = "/checkpoint/"
                    if not self.path.startswith(prefix):
                        self.send_error(404, "unknown path")
                        return
                    rest = self.path[len(prefix):].split("/")
                    if len(rest) == 4 and rest[1] == "part":
                        # striped fetch: /checkpoint/{step}/part/{i}/{n}
                        self._serve_part(
                            int(rest[0]), int(rest[2]), int(rest[3])
                        )
                        return
                    if len(rest) == 3 and rest[1] == "streammeta":
                        # streamed-heal layout: /checkpoint/{step}/streammeta/{wire}
                        self._serve_stream_meta(int(rest[0]), rest[2])
                        return
                    if len(rest) == 6 and rest[1] == "stream":
                        # streamed-heal range:
                        # /checkpoint/{step}/stream/{i}/{n}/{wire}/{seq}
                        self._serve_stream_part(
                            int(rest[0]), int(rest[2]), int(rest[3]),
                            rest[4], int(rest[5]),
                        )
                        return
                    if len(rest) != 1:
                        self.send_error(404, "unknown path")
                        return
                    with _TimedAcquire(
                        ckpt_server._checkpoint_lock, ckpt_server._timeout
                    ):
                        step = ckpt_server._step
                        requested = int(rest[0])
                        if requested != step:
                            self.send_error(
                                400,
                                f"invalid checkpoint requested: serving {step} "
                                f"but got {requested}",
                            )
                            return
                        # STREAMED response (chunked): the pickle goes
                        # straight to the socket as it is produced — no
                        # full-payload buffer on the server, so multi-GB
                        # states don't spike host RAM inside the lock
                        # window (reference checkpointing.py:139-170
                        # streams torch.save the same way). The
                        # device->host pull happens BEFORE the 200 is
                        # committed: a wedged d2h (the dominant failure
                        # class) still gets a clean 500, and only a
                        # pickling error can corrupt an in-flight chunk
                        # stream (the peer then fails loudly on framing).
                        host_tree = _to_host(ckpt_server._state_dict)
                        self.send_response(200)
                        self.send_header(
                            "Content-Type", "application/octet-stream"
                        )
                        self.send_header("Transfer-Encoding", "chunked")
                        self.end_headers()
                        out = _ChunkedWriter(self.wfile)
                        pickle.dump(
                            host_tree, out,
                            protocol=pickle.HIGHEST_PROTOCOL,
                        )
                        out.close()
                except Exception as e:  # noqa: BLE001 - report to the peer
                    logger.exception("checkpoint server error")
                    try:
                        self.send_error(500, str(e))
                    except Exception:
                        pass

            def _serve_part(self, requested: int, i: int, n: int) -> None:
                """One byte-range of the serialized checkpoint, for the
                striped (parallel-connection) fetch. The gate lock is held
                only to validate the step and build/fetch the serialized
                cache — NOT while the body streams, or the N part requests
                would serialize and the parallel fetch would be a no-op.
                The cache is an immutable bytes object, so a concurrent
                disallow_checkpoint (which drops the server's reference)
                cannot mutate an in-flight response."""
                if n < 1 or not (0 <= i < n):
                    self.send_error(404, f"bad part {i}/{n}")
                    return
                with _TimedAcquire(
                    ckpt_server._checkpoint_lock, ckpt_server._timeout
                ):
                    step = ckpt_server._step
                    if requested != step:
                        self.send_error(
                            400,
                            f"invalid checkpoint requested: serving {step} "
                            f"but got {requested}",
                        )
                        return
                    payload = ckpt_server._serialized
                    if payload is None or ckpt_server._serialized_step != step:
                        # Serialized exactly once per published step, shared
                        # by every part of every striped reader. Memory cost
                        # (one full pickle) is the striped transport's
                        # bandwidth-for-memory trade; the single-stream
                        # endpoint above stays allocation-free.
                        payload = serialize_state_dict(
                            ckpt_server._state_dict
                        )
                        ckpt_server._serialized = payload
                        ckpt_server._serialized_step = step
                start = len(payload) * i // n
                end = len(payload) * (i + 1) // n
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(end - start))
                self.end_headers()
                self.wfile.write(payload[start:end])

            def _staging_for(
                self, requested: int, wire_tok: str, track: bool = False,
                seq: Optional[int] = None,
            ) -> Optional[_StreamStaging]:
                """Validates the step and returns the (lazily built)
                zero-copy staging for ``wire_tok`` under the gate lock;
                the LAYOUT is immutable after build, so range bodies
                stream OUTSIDE the lock (parallel range fetches would
                otherwise serialize). ``track=True`` additionally
                registers an in-flight reader WHILE the gate lock is
                still held — range bodies alias the live state-dict
                buffers, and disallow_checkpoint drains tracked readers
                before the dict may mutate. Returns None after having
                sent an error response."""
                wire = None if wire_tok in ("none", "f32", "raw") else wire_tok
                if wire not in (None, "bf16"):
                    self.send_error(404, f"unknown heal wire {wire_tok!r}")
                    return None
                with _TimedAcquire(
                    ckpt_server._checkpoint_lock, ckpt_server._timeout
                ):
                    step = ckpt_server._step
                    if requested != step:
                        self.send_error(
                            400,
                            f"invalid checkpoint requested: serving {step} "
                            f"but got {requested}",
                        )
                        return None
                    if seq is not None and seq != ckpt_server._publish_seq:
                        # Stale publish: the dict was republished (same
                        # step is possible) since this client's meta
                        # fetch — serving the range would mix two
                        # checkpoints. Fail loudly; the client's heal
                        # errors and retries against the new publish.
                        self.send_error(
                            400,
                            f"stale publish: serving seq "
                            f"{ckpt_server._publish_seq}, range asked "
                            f"for {seq}",
                        )
                        return None
                    if ckpt_server._stagings_step != step:
                        ckpt_server._stagings = {}
                        ckpt_server._stagings_step = step
                    staging = ckpt_server._stagings.get(wire)
                    if staging is None:
                        # the d2h of the whole state, once a publish, on
                        # whichever serving thread asks first
                        with ckpt_server.metrics.timed(
                            "send_stage", span="send_checkpoint/stage"
                        ):
                            staging = _StreamStaging(
                                ckpt_server._state_dict,
                                wire,
                                seq=ckpt_server._publish_seq,
                            )
                        ckpt_server._stagings[wire] = staging
                    if track:
                        with ckpt_server._stream_cv:
                            ckpt_server._stream_inflight += 1
                    return staging

            def _serve_stream_meta(self, requested: int, wire_tok: str) -> None:
                staging = self._staging_for(requested, wire_tok)
                if staging is None:
                    return
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(len(staging.meta)))
                self.end_headers()
                self.wfile.write(staging.meta)

            def _serve_stream_part(
                self, requested: int, i: int, n: int, wire_tok: str,
                seq: int,
            ) -> None:
                if n < 1 or not (0 <= i < n):
                    self.send_error(404, f"bad stream part {i}/{n}")
                    return
                staging = self._staging_for(
                    requested, wire_tok, track=True, seq=seq
                )
                if staging is None:
                    return
                try:
                    begin = staging.total * i // n
                    end = staging.total * (i + 1) // n
                    with ckpt_server.metrics.timed(
                        "send_serve", span="send_checkpoint/serve",
                        bytes=end - begin,
                    ):
                        self.send_response(200)
                        self.send_header(
                            "Content-Type", "application/octet-stream"
                        )
                        self.send_header("Content-Length", str(end - begin))
                        # Per-range CRC32C (same polynomial as the ring
                        # frames): the receiver verifies before trusting
                        # the bytes — a flipped bit on a heal range
                        # otherwise installs corrupted weights with no
                        # vote to catch it.
                        self.send_header(
                            "X-TFT-Crc32c",
                            f"{staging.range_crc32c(begin, end):08x}",
                        )
                        self.end_headers()
                        staging.write_range(self.wfile, begin, end)
                    ckpt_server.metrics.incr("send_bytes", end - begin)
                finally:
                    with ckpt_server._stream_cv:
                        ckpt_server._stream_inflight -= 1
                        ckpt_server._stream_cv.notify_all()

            def log_message(self, format: str, *args: object) -> None:
                logger.debug(f"checkpoint server: {format % args}")

        class _Server(ThreadingHTTPServer):
            address_family = socket.AF_INET6
            request_queue_size = 1024
            daemon_threads = True

        self._server = _Server(("::", 0), RequestHandler)
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            daemon=True,
            name="checkpoint_server",
        )
        self._thread.start()

    @classmethod
    def load_from_address(
        cls,
        address: str,
        timeout: timedelta,
        stripes: Optional[int] = None,
        wire: Optional[str] = "env",
        streams: Optional[int] = None,
        device_put: Optional[bool] = None,
    ) -> T:
        """Fetches a checkpoint from a step-qualified URL.
        Reference checkpointing.py:187-203.

        The STREAMED zero-copy pipeline is tried first (see module
        docstring): layout fetch, then ``streams`` parallel raw byte
        ranges (default: env ``TORCHFT_HEAL_STREAMS``, else ``stripes``)
        read straight into one preallocated buffer, each leaf's device
        upload dispatched while later ranges are still on the wire.
        ``wire`` selects the stream encoding (default: env
        ``TORCHFT_HEAL_WIRE``; ``"bf16"`` halves non-param f32 bytes,
        ``None`` ships everything raw). Pre-stream peers fall back to the
        pickled paths: ``stripes`` > 1 (default: env
        ``TORCHFT_CKPT_STRIPES``, else 4) fetches the pickle as parallel
        byte ranges; a pre-striping peer or ``stripes=1`` takes the
        single-connection streamed-pickle fetch."""
        out, _stats = cls._fetch(
            address, timeout, stripes, wire, streams, device_put
        )
        return out

    @classmethod
    def _fetch(
        cls,
        address: str,
        timeout: timedelta,
        stripes: Optional[int] = None,
        wire: Optional[str] = "env",
        streams: Optional[int] = None,
        device_put: Optional[bool] = None,
        step: Optional[int] = None,
    ) -> Tuple[T, Dict[str, Any]]:
        """load_from_address returning ``(tree, stats)`` — the stats dict
        names the path taken and its meta/fetch/h2d seconds for
        heal-latency attribution. Each of those seconds is a phase
        ``torchft::heal_fetch/<phase>`` stamped with ``step`` (the
        owner's; the Manager's ``torchft::heal_fetch`` lies around them):
        ``meta``, ``stream``, ``h2d``, and ``striped`` / ``single`` on
        the pickled fallbacks, where ``fetch_s`` is the fetch that
        succeeded (a stream attempt that failed before it shows as its
        own phases)."""
        if stripes is None:
            stripes = int(os.environ.get("TORCHFT_CKPT_STRIPES", "4"))
        stripes = max(1, min(int(stripes), 64))
        if wire == "env":
            wire = _heal_wire_from_env()
        if streams is None:
            streams = int(
                os.environ.get("TORCHFT_HEAL_STREAMS", str(stripes))
            )
        streams = max(1, min(int(streams), 64))
        logger.info(
            f"fetching checkpoint from {address} "
            f"(streams={streams}, wire={wire}, pickle stripes={stripes})"
        )
        try:
            return cls._load_stream(
                address, timeout, wire, streams, device_put, step
            )
        except urllib.error.HTTPError as e:
            if e.code not in (404, 500):
                raise
            # 404/500: a pre-stream peer (or a gate-timeout) — heal must
            # proceed over the pickled paths, not fail
            logger.warning(
                "peer checkpoint server lacks the zero-copy stream "
                f"endpoint (HTTP {e.code}); falling back to pickled fetch"
            )
        except TimeoutError:
            # The stream burned the caller's whole timeout budget
            # (TimeoutError is an OSError subclass — without this clause
            # it would fall through below and each pickled fallback
            # would start a FRESH full-timeout attempt against the same
            # wedged donor, stretching a 30 s heal budget to ~90 s of
            # no-redundancy window the quorum never agreed to).
            raise
        except WireCorruption as e:
            # DETECTED corruption on a stream range: never install the
            # bytes. The pickled fallback re-reads everything from
            # scratch (a transient flip heals itself; a persistently
            # corrupting path will fail there too and surface as a
            # failed heal, not silent weight rot).
            logger.error(
                f"heal stream failed integrity check ({e}); refetching "
                "via the pickled fallback"
            )
        except OSError as e:
            if isinstance(
                getattr(e, "reason", None), TimeoutError
            ):
                # urllib wraps connect/read timeouts as
                # URLError(reason=TimeoutError) — same budget-exhaustion
                # case as the clause above, same verdict.
                raise
            logger.warning(
                f"streamed checkpoint fetch failed ({e!r}); "
                "falling back to pickled fetch"
            )
        if stripes > 1:
            try:
                with timed_span("torchft::heal_fetch/striped", step) as fetch:
                    out = cls._load_striped(address, timeout, stripes)
                return out, {
                    "path": "striped",
                    "stripes": stripes,
                    "fetch_s": fetch.seconds,
                }
            except urllib.error.HTTPError as e:
                if e.code not in (404, 500):
                    raise
                # 404/500: a pre-striping peer that can't parse the /part/
                # path — heal must proceed at single-stream speed, not fail
                logger.warning(
                    "peer checkpoint server lacks the striped endpoint "
                    f"(HTTP {e.code}); falling back to single-stream fetch"
                )
            except OSError as e:
                # socket timeout / reset mid-stripe (e.g. the server is
                # still serializing a large dict under the gate lock). The
                # streamed path needs no up-front serialize, so the heal
                # can still succeed there.
                logger.warning(
                    f"striped checkpoint fetch failed ({e!r}); "
                    "falling back to single-stream fetch"
                )
        with timed_span("torchft::heal_fetch/single", step) as fetch:
            with urllib.request.urlopen(
                address, timeout=timeout.total_seconds()
            ) as f:
                # incremental unpickle off the response stream (http.client
                # de-chunks transparently): bounded memory on the receiver too
                out = load_state_dict_stream(f)
        return out, {"path": "single", "fetch_s": fetch.seconds}

    @classmethod
    def _load_stream(
        cls,
        address: str,
        timeout: timedelta,
        wire: Optional[str],
        streams: int,
        device_put: Optional[bool],
        step: Optional[int] = None,
    ) -> Tuple[T, Dict[str, Any]]:
        """The zero-copy receiver: layout fetch, ``streams`` parallel
        range readers ``readinto``-ing one preallocated buffer, and a
        walker that reconstructs each leaf as a view (f32 path: zero
        copies) the moment its bytes are covered — dispatching its async
        device upload while later ranges are still on the wire. Raises
        ``urllib.error.HTTPError(404)`` against pre-stream peers (the
        caller falls back).

        Three phases under ``torchft::heal_fetch``: ``meta`` (the layout
        fetch), ``stream`` (the buffer the ranges land in, the first
        range request to the last byte) and ``h2d`` (the upload drain
        after the last byte). ``fetch_s`` is the first two."""
        import jax

        if device_put is None:
            # Heal payloads feed straight into jitted code; uploading
            # during the fetch costs nothing extra and removes a full
            # payload pass after it. Host-only users pass False.
            device_put = True
        deadline = time.monotonic() + timeout.total_seconds()
        wire_tok = wire if wire is not None else "none"
        with timed_span("torchft::heal_fetch/meta", step) as meta_t:
            with urllib.request.urlopen(
                f"{address}/streammeta/{wire_tok}",
                timeout=timeout.total_seconds(),
            ) as f:
                meta = _SafeUnpickler(f).load()
        with timed_span("torchft::heal_fetch/stream", step) as stream_t:
            treedef, out_leaves, device_leaves = cls._pull_ranges(
                address, timeout, deadline, wire_tok, streams, device_put,
                meta,
            )
        h2d_s = 0.0
        if device_leaves:
            # The residual upload drain AFTER the last byte arrived — the
            # part of h2d the overlap could not hide.
            with timed_span("torchft::heal_fetch/h2d", step) as h2d_t:
                jax.block_until_ready(device_leaves)
            h2d_s = h2d_t.seconds
        return (
            jax.tree_util.tree_unflatten(treedef, out_leaves),
            {
                "path": "stream",
                "wire": wire,
                "streams": streams,
                "bytes": int(meta["total"]),
                "meta_s": meta_t.seconds,
                "fetch_s": meta_t.seconds + stream_t.seconds,
                "h2d_s": h2d_s,
            },
        )

    @classmethod
    def _pull_ranges(
        cls,
        address: str,
        timeout: timedelta,
        deadline: float,
        wire_tok: str,
        streams: int,
        device_put: bool,
        meta: Dict[str, Any],
    ) -> Tuple[Any, List[Any], List[Any]]:
        """``_load_stream``'s middle: pulls the payload ``meta`` lays out
        and returns ``(treedef, leaves, the leaves whose upload was
        dispatched)`` once the last byte is in."""
        import jax

        total = int(meta["total"])
        seq = int(meta.get("seq", 0))
        skeleton = meta["skeleton"]
        slots, treedef = jax.tree_util.tree_flatten(skeleton)
        buf = bytearray(total)
        view = memoryview(buf)
        bounds = [total * i // streams for i in range(streams + 1)]
        progress = list(bounds[:-1])
        cond = threading.Condition()
        errors: List[BaseException] = []
        # Set when the walker gives up (error/timeout): surviving pull
        # threads must stop downloading, or they'd compete with the
        # pickled fallback fetch for the same link and pin the donor's
        # in-flight reader count against its next disallow.
        cancel = threading.Event()

        def pull(i: int) -> None:
            try:
                begin, end = bounds[i], bounds[i + 1]
                if begin >= end:
                    return
                with urllib.request.urlopen(
                    # the publish nonce from the meta rides every range
                    # request: a republish in between (same step
                    # included) 400s instead of serving torn bytes
                    f"{address}/stream/{i}/{streams}/{wire_tok}/{seq}",
                    timeout=timeout.total_seconds(),
                ) as resp:
                    want_crc = resp.headers.get("X-TFT-Crc32c")
                    pos = begin
                    # Incremental CRC folded into the readinto loop: the
                    # verify never costs a second memory pass on the
                    # heal critical path.
                    crc_state = 0xFFFFFFFF
                    while pos < end and not cancel.is_set():
                        n = resp.readinto(
                            view[pos:min(pos + _STREAM_CHUNK, end)]
                        )
                        if not n:
                            raise OSError(
                                f"heal stream {i} ended early at "
                                f"{pos}/{end}"
                            )
                        if want_crc is not None:
                            crc_state = _crc32c_update(
                                crc_state, view[pos:pos + n]
                            )
                        pos += n
                        if pos >= end and want_crc is not None:
                            # Verify BEFORE publishing the final
                            # progress: the walker only ever consumes
                            # integrity-checked ranges (a pre-CRC donor
                            # sends no header and is trusted as before).
                            got_crc = crc_state ^ 0xFFFFFFFF
                            if got_crc != int(want_crc, 16):
                                raise WireCorruption(
                                    "wire corruption: heal stream range "
                                    f"{i} CRC32C mismatch (got "
                                    f"{got_crc:08x}, donor sent "
                                    f"{want_crc}, bytes [{begin}, {end}))"
                                )
                        with cond:
                            progress[i] = pos
                            cond.notify_all()
            except BaseException as e:  # noqa: BLE001 - wake the walker
                with cond:
                    errors.append(e)
                    cond.notify_all()

        threads = [
            threading.Thread(
                target=pull, args=(i,), daemon=True,
                name=f"heal_stream_{i}",
            )
            for i in range(streams)
        ]
        for t in threads:
            t.start()

        def wait_covered(begin: int, end: int) -> None:
            with cond:
                while True:
                    if errors:
                        raise errors[0]
                    if all(
                        progress[j] >= min(end, bounds[j + 1])
                        for j in range(streams)
                        if bounds[j] < end and bounds[j + 1] > begin
                    ):
                        return
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            "streamed heal fetch timed out "
                            f"(covered through ~{min(progress)}/{total} "
                            "bytes)"
                        )
                    cond.wait(min(remaining, 1.0))

        out_leaves: List[Any] = []
        device_leaves: List[Any] = []
        try:
            for slot in slots:
                if not isinstance(slot, _ArraySlot):
                    out_leaves.append(slot)
                    continue
                wait_covered(slot.offset, slot.offset + slot.nbytes)
                wdtype = _dtype_by_name(slot.wire_dtype)
                arr = np.frombuffer(
                    buf,
                    dtype=wdtype,
                    count=slot.nbytes // wdtype.itemsize,
                    offset=slot.offset,
                ).reshape(slot.shape)
                odtype = _dtype_by_name(slot.dtype)
                if wdtype != odtype:
                    arr = arr.astype(odtype)
                if (
                    device_put
                    # x64-off jax would silently narrow f64/i64 leaves
                    # at upload; those stay host-side numpy (the
                    # transport contract returns the donor's exact
                    # dtypes — the caller owns any canonicalizing
                    # placement)
                    and jax.dtypes.canonicalize_dtype(odtype) == odtype
                ):
                    import jax.numpy as jnp

                    # async h2d dispatch: the upload rides under the
                    # remaining range reads
                    leaf: Any = jnp.asarray(arr)
                    device_leaves.append(leaf)
                else:
                    leaf = arr
                out_leaves.append(leaf)
            for t in threads:
                t.join(max(0.0, deadline - time.monotonic()))
            with cond:
                if errors:
                    raise errors[0]
                if any(t.is_alive() for t in threads):
                    raise TimeoutError(
                        "streamed heal fetch timed out draining"
                    )
        except BaseException:
            # Stop surviving pull threads before the caller falls back
            # (or gives up): abandoned full-range downloads would race
            # the fallback for the same link and hold the donor's
            # in-flight reader count against its next disallow.
            cancel.set()
            raise
        return treedef, out_leaves, device_leaves

    @classmethod
    def _load_striped(cls, address: str, timeout: timedelta, stripes: int) -> T:
        """Parallel byte-range fetch + one safelisted deserialize. Holds
        the full serialized payload on the receiver (the striped
        transport's bandwidth-for-memory trade)."""

        def fetch(i: int) -> bytes:
            # One retry on 500: the server builds its pickle cache lazily
            # under the gate lock, so the FIRST part request of a large
            # checkpoint can hold the lock past the server's lock timeout
            # and 500 its siblings. By the retry the cache exists and
            # parts stream immediately — without it, one slow serialize
            # would kick the whole heal down to single-stream speed.
            for attempt in (0, 1):
                try:
                    with urllib.request.urlopen(
                        f"{address}/part/{i}/{stripes}",
                        timeout=timeout.total_seconds(),
                    ) as f:
                        return f.read()
                except urllib.error.HTTPError as e:
                    if attempt or e.code != 500:
                        raise

        with ThreadPoolExecutor(
            max_workers=stripes, thread_name_prefix="ckpt_stripe"
        ) as ex:
            parts = list(ex.map(fetch, range(stripes)))
        return deserialize_state_dict(b"".join(parts))

    def address(self) -> str:
        """URL prefix of this server; append the step to fetch."""
        port = self._server.socket.getsockname()[1]
        return f"http://{socket.gethostname()}:{port}/checkpoint/"

    def allow_checkpoint(self, step: int) -> None:
        """Publishes ``step``; unblocks readers. Reference :246-254."""
        self._step = step
        self._publish_seq += 1
        # A staging built under the previous publish carries that
        # publish's nonce in its meta; serving it now would 400 every
        # range. Rebuild lazily under the new nonce.
        self._stagings = {}
        self._stagings_step = -1
        if self._disallowed:
            self._disallowed = False
            self._checkpoint_lock.release()

    def disallow_checkpoint(self) -> None:
        """Re-locks the gate so the dict can be mutated. Reference :256-259.

        Additionally drains in-flight /stream/ range responses before
        returning: their bodies are zero-copy views of the live buffers,
        and a mutation racing a tail of the stream would ship torn bytes
        to a healing replica. New stream readers can't start once the
        gate lock is held (they register under it); stragglers are waited
        out up to the server timeout — a reader still writing past that
        is itself beyond its deadline, and wedging the training loop on
        it would be worse."""
        if not self._disallowed:
            self._disallowed = True
            self._checkpoint_lock.acquire()
            # the dict may mutate now; the pickle + stream caches are stale
            self._serialized = None
            self._serialized_step = -1
            self._stagings = {}
            self._stagings_step = -1
            deadline = time.monotonic() + self._timeout.total_seconds()
            with self._stream_cv:
                while self._stream_inflight > 0:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        logger.warning(
                            f"{self._stream_inflight} streamed heal "
                            "reader(s) still in flight at disallow "
                            "timeout; proceeding (their fetch already "
                            "exceeded its deadline)"
                        )
                        break
                    self._stream_cv.wait(remaining)

    # -- CheckpointTransport --

    def metadata(self) -> str:
        return self.address()

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: T, timeout: timedelta
    ) -> None:
        self._state_dict = state_dict
        self._serialized = None  # new dict, even at an unchanged step
        self._serialized_step = -1
        self._stagings = {}
        self._stagings_step = -1
        self.allow_checkpoint(step)

    def recv_checkpoint(
        self, src_rank: int, metadata: str, step: int, timeout: timedelta
    ) -> T:
        out, stats = self._fetch(
            f"{metadata}{step}", timeout, step=self.metrics.step
        )
        self.last_fetch_stats = stats
        return out

    def shutdown(self, wait: bool = True) -> None:
        """Stops serving. Requests in flight hold the gate lock until done."""
        self._server.shutdown()
        if wait:
            self._thread.join()
        self._server.server_close()
