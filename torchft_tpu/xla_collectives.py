"""Cross-replica-group collectives compiled by XLA over a multi-process mesh.

The second of the two cross-group (DCN) data-plane options SURVEY.md §5
maps out for the TPU build (the role of the reference's NCCL backend choice,
reference torchft/process_group.py:299-315):

- :class:`~torchft_tpu.collectives.HostCollectives` (the default): host TCP
  ring, outside XLA. Elastic — reconfigure is a millisecond-scale socket
  rendezvous, device state is untouched, and a dead peer surfaces as an
  abortable socket error.
- :class:`XLACollectives` (this module): the reduction is a jitted psum over
  a GLOBAL device mesh spanning every replica group's processes — gloo
  between CPU hosts, DCN between TPU slices. XLA owns the wire, so large
  payloads ride the fastest path available with zero host involvement
  (pass ``keep_global=True``), but the membership is baked into the
  distributed runtime:

  * ``configure()`` onto a NEW membership must tear down and re-create the
    XLA distributed runtime (``jax.distributed.shutdown`` + backend clear +
    re-initialize), **orphaning every live jax array in the process**:
    measured on CPU, their buffers keep their data (the retired client
    lives while referenced) and implicit transfers let new jits consume
    them, but they pin old-backend memory and none of this is contractual
    on accelerator backends — snapshot training state to host around a
    reconfigure. About a second per reconfigure on the CPU backend where
    the host ring takes about a millisecond (DCN.md).
  * a peer that dies mid-collective wedges the compiled op until the
    distributed-runtime heartbeat gives up (minutes by default) — exactly
    the hazard the reference isolates NCCL in a subprocess for (reference
    process_group.py:303-307,551-1064) and that keeps the host ring the
    default here.

  Use it for static-membership deployments (fixed cohort, spares handled by
  ``WorldSizeMode.FIXED_WITH_SPARES`` restarts) where cross-group bandwidth
  dominates; use the host ring whenever membership is elastic.

Deployment model: ONE process per replica group (slice), same as the
manager. ``configure()`` performs coordinator rendezvous through the same
store/prefix discipline as the host ring, so healthy-membership quorum
changes drop into ``Manager``'s reconfiguration; after a WEDGED collective,
however, ``configure()`` can only fail fast with ``TimeoutError`` (a
compiled op cannot be interrupted — see ``abort()``) and the process must
be restarted, unlike the ring's in-place abort.
"""

from __future__ import annotations

import socket
import time
from concurrent.futures import ThreadPoolExecutor
from concurrent.futures import TimeoutError as FuturesTimeoutError
from datetime import timedelta
from typing import Any, Callable, List, Optional, Sequence

import numpy as np

from ._native import StoreClient
from .collectives import (
    Collectives,
    OpStatsMixin,
    ReduceOp,
    Work,
    _flatten,
    _unflatten,
)

_COORD_KEY = "xla_coordinator"

# Bounded retries of the coordinator-port race (see _reserve_port): each
# lost race re-reserves and republishes under the next attempt key, so a
# loss is recovered in-place instead of burning a whole quorum round.
_COORD_ATTEMPTS = 3


def _reserve_port() -> tuple:
    """Reserves an ephemeral port for the distributed-runtime coordinator:
    binds port 0 and returns ``(port, bound_socket)`` with the socket
    STILL HELD — the caller publishes the actual bound port through the
    store while holding it, and closes it only immediately before
    ``jax.distributed.initialize`` binds the same port. The old
    probe-then-close helper released the port before publication, leaving
    a publication-to-initialize window (a full cross-rank rendezvous) in
    which any process could take it; holding the bind shrinks the race to
    the close→re-bind instant, and the attempt-keyed retry in
    ``configure()`` recovers the residual loss in-place."""
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("", 0))
    return s.getsockname()[1], s


def _is_bind_failure(exc: BaseException) -> bool:
    """Whether an initialize() failure is the coordinator losing the
    reserved port (the lost race the attempt-keyed retry recovers), as
    opposed to a backend-predates-runtime error or a peer outage."""
    msg = str(exc).lower()
    return "address already in use" in msg or (
        "bind" in msg and "fail" in msg
    )


def _is_backend_predates(exc: BaseException) -> bool:
    """Whether an initialize() failure is "the XLA backend pre-dates the
    distributed runtime" ("initialize() must be called before any JAX
    computations") — the ONE failure the teardown-and-retry-once branch
    exists for. Anything else must propagate to the attempt loop: the
    old catch-all retried ARBITRARY RuntimeErrors against the same
    (possibly doomed) coordinator address, paying a spurious
    array-orphaning teardown and, on runtimes whose registration
    timeout is a fatal process abort, dying before the retry protocol
    could ever run."""
    msg = str(exc).lower()
    return "must be called before" in msg or "already initialized" in msg


def _split_store_addr(store_addr: str) -> tuple:
    """``host:port/prefix`` -> (``host:port``, ``prefix``)."""
    if "/" in store_addr:
        hostport, prefix = store_addr.split("/", 1)
    else:
        hostport, prefix = store_addr, ""
    return hostport, prefix


def _leaf_bytes(leaves) -> int:
    """Payload bytes of a leaf list from shapes/dtypes alone (no device
    fetch — ``np.asarray`` on a jax leaf would pull it to host just to
    count)."""
    total = 0
    for l in leaves:
        shape = getattr(l, "shape", ())
        n = 1
        for d in shape:
            n *= int(d)
        total += n * np.dtype(getattr(l, "dtype", np.float64)).itemsize
    return total


def _coord_key(prefix: str, attempt: int) -> str:
    base = f"{prefix}/{_COORD_KEY}" if prefix else _COORD_KEY
    return base if attempt == 0 else f"{base}/r{attempt}"


def _rendezvous_coordinator(
    store: StoreClient,
    prefix: str,
    rank: int,
    attempt: int,
    connect_timeout: timedelta,
    probe_listen: bool = False,
) -> tuple:
    """One coordinator rendezvous attempt, shared by ``XLACollectives``
    and the isolated backend's child. Rank 0 reserves a port (held bind),
    publishes the ACTUAL bound ``host:port`` under the attempt key and
    returns ``(coord, held_socket)`` — the caller must close the socket
    immediately before ``jax.distributed.initialize``. Other ranks fetch
    the key and return ``(coord, None)``.

    ``probe_listen`` (non-zero ranks): poll a TCP connect against the
    coordinator until it accepts before returning. The distributed
    runtime's client retries a failed first connect on a ~1 s backoff, so
    a cohort whose processes (re)start simultaneously pays a full second
    per member without the probe — the dominant term in the measured
    ~1.0 s in-process reconfigure. The isolated child probes; the
    in-process path keeps its historical behavior."""
    key = _coord_key(prefix, attempt)
    if rank == 0:
        port, held = _reserve_port()
        coord = f"{socket.gethostname()}:{port}"
        store.set(key, coord.encode())
        return coord, held
    coord = store.get(key, timeout=connect_timeout).decode()
    if probe_listen:
        host, _, port = coord.rpartition(":")
        deadline = time.perf_counter() + connect_timeout.total_seconds()
        while True:
            try:
                socket.create_connection((host, int(port)), timeout=0.25).close()
                break
            except OSError:
                if time.perf_counter() >= deadline:
                    # NEVER hand a dead coordinator to initialize(): on
                    # runtimes whose registration timeout is a fatal
                    # process abort (observed on jax 0.4's coordination
                    # client) the caller's retry protocol would die with
                    # it. Raising here routes to the attempt loop, which
                    # checks whether rank 0 republished after a lost
                    # port race.
                    raise TimeoutError(
                        f"coordinator {coord} never started listening "
                        f"(attempt {attempt})"
                    )
                time.sleep(0.005)
    return coord, None


class XLACollectives(OpStatsMixin, Collectives):
    """Reconfigurable cross-group collectives as jitted global-mesh psums.

    Results are returned as host-backed local arrays by default (drop-in
    parity with ``HostCollectives``: downstream per-group jitted steps can
    consume them); construct with ``keep_global=True`` to keep results on
    the global mesh (no host hop — the pure-DCN path) when the consumer is
    itself jitted over the global mesh.
    """

    def __init__(
        self,
        timeout: timedelta = timedelta(seconds=60),
        connect_timeout: timedelta = timedelta(seconds=60),
        keep_global: bool = False,
        probe_listen: bool = False,
    ) -> None:
        """``probe_listen``: non-zero ranks poll a TCP connect against
        the published coordinator until it accepts before calling
        ``initialize()`` — the distributed client retries a failed first
        connect on a ~1 s backoff, so cohorts whose processes (re)start
        simultaneously pay ~1 s per configure without it. Default off
        (historical behavior); the isolated backend's child turns it on
        (its whole point is cheap respawn)."""
        self._timeout = timeout
        self._connect_timeout = connect_timeout
        self._keep_global = keep_global
        self._probe_listen = probe_listen
        self._rank = -1
        self._world_size = 0
        self._mesh: Optional[Any] = None
        self._initialized = False
        # One thread: collectives must issue in submission order.
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="xla_collectives"
        )
        self._shutdown_flag = False
        self._aborted = False
        self._jit_cache: dict = {}
        self._protected: List[Any] = []
        # Host snapshots of _protected taken at teardown, restored by the
        # next SUCCESSFUL configure (survives an initialize() failure
        # in between — see teardown_backends in configure()).
        self._pending_snapshots: Optional[List[Any]] = None

    def register_state(self, state: Any) -> None:
        """Registers a state holder (anything with ``snapshot()`` /
        ``restore(snap)``, e.g. :class:`~torchft_tpu.train_state.FTTrainState`)
        to be round-tripped through the host across every reconfigure:
        ``configure()`` onto a new membership tears down the XLA
        distributed runtime and orphans live jax arrays (module
        docstring), so protected holders are snapshotted to host before
        the teardown and restored onto the new backend after it. This is
        the automated form of the manual snapshot discipline the hazard
        note prescribes."""
        self._protected.append(state)

    # -- lifecycle --

    def abort(self) -> None:
        """Fails queued-but-unstarted ops fast. An IN-FLIGHT compiled
        collective cannot be interrupted — XLA owns it until the
        distributed runtime gives up (the wedge hazard DCN.md documents;
        after that the process must reconfigure or restart)."""
        self._aborted = True

    def configure(
        self,
        store_addr: str,
        rank: int,
        world_size: int,
        regions: Optional[Sequence[str]] = None,
        hosts: Optional[Sequence[str]] = None,
    ) -> None:
        # `regions` accepted and ignored (the reconfigure contract): the
        # compiled XLA data plane has no host-side topology to compile —
        # the runtime owns placement.
        # Unblock the queue the way HostCollectives does pre-configure;
        # do_configure clears the flag once the new membership is live.
        self._aborted = True

        def do_configure() -> None:
            import jax

            hostport, prefix = _split_store_addr(store_addr)
            store = StoreClient(hostport, connect_timeout=self._connect_timeout)

            from jax.extend import backend as jax_backend

            def teardown_backends() -> None:
                # Orphans live jax arrays (see module docstring), so
                # registered state holders are snapshotted to host first
                # — lazily, right before the clear, so a no-teardown
                # configure never pays the d2h state copy. Snapshots live
                # on SELF, not a local: if initialize() fails after a
                # teardown, the next configure attempt must still restore
                # the holders (whose arrays are already orphaned) — a
                # local list would leak them and silently hand training
                # stale-backend arrays. Never overwrite pending snapshots:
                # after a failed attempt the holders' current arrays are
                # orphans, and re-snapshotting them would capture garbage.
                if self._pending_snapshots is None:
                    self._pending_snapshots = [
                        s.snapshot() for s in self._protected
                    ]
                jax.clear_caches()
                jax_backend.clear_backends()
                self._jit_cache.clear()

            if self._initialized:
                # Membership change: the distributed runtime is torn down
                # and rebuilt.
                jax.distributed.shutdown()
                teardown_backends()
                self._initialized = False

            attempt = 0
            while True:
                try:
                    coord, held = _rendezvous_coordinator(
                        store, prefix, rank, attempt, self._connect_timeout,
                        probe_listen=self._probe_listen,
                    )
                    init_kwargs = dict(
                        coordinator_address=coord,
                        num_processes=world_size,
                        process_id=rank,
                        initialization_timeout=max(
                            int(self._connect_timeout.total_seconds()), 1
                        ),
                    )
                    if held is not None:
                        # The reserved port was held through publication;
                        # the close→bind instant below is the only
                        # residual race window, and losing it is
                        # recovered by the attempt loop instead of
                        # failing the quorum round.
                        held.close()
                    try:
                        jax.distributed.initialize(**init_kwargs)
                    except RuntimeError as e:
                        if not _is_backend_predates(e):
                            raise
                        # The process already ran jax computations, so the
                        # XLA backend pre-dates the distributed runtime
                        # ("initialize() must be called before any JAX
                        # calls"). Clear it and retry once — pre-existing
                        # arrays are orphaned, same contract as a
                        # reconfigure.
                        teardown_backends()
                        jax.distributed.initialize(**init_kwargs)
                    break
                except Exception as e:  # noqa: BLE001 - attempt routing
                    if attempt + 1 >= _COORD_ATTEMPTS:
                        raise
                    if rank == 0:
                        if not _is_bind_failure(e):
                            raise
                        # Lost the close→bind instant: reserve a fresh
                        # port and republish under the next attempt key.
                        attempt += 1
                        continue
                    # Non-zero rank: a failed initialize may mean rank 0
                    # lost the race and republished. The next attempt
                    # key's presence tells a recoverable loss from a real
                    # outage (absent -> re-raise the original failure).
                    # Short bounded poll: rank 0 republishes within
                    # milliseconds of ITS bind failure (which precedes
                    # this rank's timeout), so waiting a full
                    # connect_timeout here would only stall quorum-level
                    # recovery on every genuine outage.
                    try:
                        store.get(
                            _coord_key(prefix, attempt + 1),
                            timeout=min(
                                self._connect_timeout, timedelta(seconds=2)
                            ),
                        )
                    except Exception:
                        raise e
                    attempt += 1
            self._initialized = True
            from jax.sharding import Mesh

            # One mesh row per process, its local devices as columns, so
            # multi-chip processes (a TPU slice per replica group) shard
            # correctly: the replica axis has size world_size and local
            # devices hold replicated copies of their process's row.
            devs = sorted(
                jax.devices(), key=lambda d: (d.process_index, d.id)
            )
            local_counts = {d.process_index: 0 for d in devs}
            for d in devs:
                local_counts[d.process_index] += 1
            if len(set(local_counts.values())) != 1:
                raise RuntimeError(
                    f"uneven devices per process: {local_counts}"
                )
            per_proc = len(devs) // world_size
            self._mesh = Mesh(
                np.array(devs).reshape(world_size, per_proc),
                ("replica", "local"),
            )
            self._rank = rank
            self._world_size = world_size
            if self._pending_snapshots is not None:
                # Only a teardown orphans device arrays; a no-teardown
                # configure must not pay the host round-trip (or drop the
                # holders' cached executables). Pending snapshots may also
                # be carried over from a PREVIOUS configure whose
                # initialize() failed post-teardown — restored here on the
                # first attempt that succeeds.
                for holder, snap in zip(
                    self._protected, self._pending_snapshots
                ):
                    holder.restore(snap)
                self._pending_snapshots = None
            self._aborted = False

        # Bounded wait: if a wedged in-flight collective is holding the op
        # thread (see abort()), surface a TimeoutError for the manager's
        # error latching instead of blocking the train loop forever.
        budget = (
            self._connect_timeout.total_seconds()
            + self._timeout.total_seconds()
        )
        self._executor.submit(do_configure).result(timeout=budget)

    def global_mesh(self) -> Any:
        """The global mesh spanning every group's devices — jit whole train
        steps over it for the zero-host-copy multi-slice mode."""
        assert self._mesh is not None, "configure() first"
        return self._mesh

    def shutdown(self) -> None:
        if self._shutdown_flag:
            return
        self._shutdown_flag = True

        def do_shutdown() -> None:
            if self._initialized:
                import jax

                jax.distributed.shutdown()
                self._initialized = False

        # Same bounded-wait rationale as configure(): a wedged in-flight
        # collective must not hang process teardown forever. On timeout the
        # op thread stays wedged (only process exit reclaims it — the
        # documented hazard); skip joining it.
        try:
            self._executor.submit(do_shutdown).result(
                timeout=self._timeout.total_seconds()
            )
            self._executor.shutdown(wait=True)
        except FuturesTimeoutError:
            self._executor.shutdown(wait=False)

    def size(self) -> int:
        return self._world_size

    def rank(self) -> int:
        return self._rank

    # -- ops --

    def _submit(self, fn: Callable[[], Any]) -> Work:
        if self._shutdown_flag:
            raise RuntimeError("collectives already shut down")

        def guarded() -> Any:
            if self._aborted:
                raise RuntimeError("collectives aborted")
            return fn()

        return Work(self._executor.submit(guarded))

    def _stack_global(self, leaves: List[Any]) -> List[Any]:
        """Each process's leaf becomes row ``rank`` of a (world, *shape)
        global array sharded over the replica axis. jax-array leaves stay
        on device (the process's row IS its local shard); host leaves are
        uploaded."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self._mesh
        out = []
        for leaf in leaves:
            if isinstance(leaf, jax.Array):
                local = jnp.expand_dims(leaf, 0)  # no host hop
                sharding = NamedSharding(
                    mesh, P("replica", *([None] * leaf.ndim))
                )
                # The replica axis shards dim 0 (size world == mesh rows);
                # the local axis is unused, so EVERY local device holds a
                # replicated copy of this process's row.
                shards = [
                    jax.device_put(local, d)
                    for d in sorted(
                        sharding.addressable_devices, key=lambda d: d.id
                    )
                ]
                out.append(
                    jax.make_array_from_single_device_arrays(
                        (self._world_size,) + tuple(leaf.shape),
                        sharding,
                        shards,
                    )
                )
            else:
                local = np.asarray(leaf)[None]
                sharding = NamedSharding(
                    mesh, P("replica", *([None] * (local.ndim - 1)))
                )
                out.append(
                    jax.make_array_from_process_local_data(sharding, local)
                )
        return out

    def _localize(self, leaves: List[Any]) -> List[Any]:
        if self._keep_global:
            return list(leaves)
        import jax.numpy as jnp

        return [jnp.asarray(np.asarray(l)) for l in leaves]

    def _reduce_jit(self, n_leaves: int, op: ReduceOp, with_divisor: bool) -> Any:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = ("reduce", n_leaves, int(op), with_divisor)
        fn = self._jit_cache.get(key)
        if fn is None:
            world = self._world_size
            replicated = NamedSharding(self._mesh, P())

            def _div(s, leaf_dtype, d):
                # Same-dtype contract (Collectives.allreduce): integers
                # floor-divide like the host ring does.
                if jnp.issubdtype(leaf_dtype, jnp.integer):
                    return s // jnp.asarray(d, s.dtype)
                return (s / d).astype(leaf_dtype)

            def reduce(leaves, divisor=None):
                outs = []
                for l in leaves:
                    if op == ReduceOp.SUM:
                        r = jnp.sum(l, axis=0)
                        if divisor is not None:
                            r = _div(r, l.dtype, divisor)
                    elif op == ReduceOp.AVG:
                        r = _div(jnp.sum(l, axis=0), l.dtype, world)
                    elif op == ReduceOp.MAX:
                        r = jnp.max(l, axis=0)
                    elif op == ReduceOp.MIN:
                        r = jnp.min(l, axis=0)
                    elif op == ReduceOp.PRODUCT:
                        r = jnp.prod(l, axis=0)
                    else:
                        raise ValueError(f"unsupported op {op}")
                    outs.append(r)
                return outs

            fn = self._jit_cache[key] = jax.jit(
                reduce, out_shardings=[replicated] * n_leaves
            )
        return fn

    def allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.SUM,
        divisor: Optional[float] = None,
        wire: Optional[str] = None,
    ) -> Work:
        # wire="q8" is accepted and served LOSSLESSLY: XLA collectives ride
        # ICI/DCN where the f32 psum is the native (and cheaper) path; the
        # quantized wire exists for the host ring's TCP links.
        return self._submit(lambda: self._allreduce_sync(tree, op, divisor))

    def _allreduce_sync(
        self, tree: Any, op: ReduceOp, divisor: Optional[float] = None
    ) -> Any:
        if divisor is not None and op != ReduceOp.SUM:
            raise ValueError("divisor only composes with ReduceOp.SUM")
        if self._world_size == 1:
            if divisor is not None and divisor != 1:
                import jax

                from .collectives import _divide_leaf

                return jax.tree_util.tree_map(
                    lambda l: _divide_leaf(l, divisor), tree
                )
            return tree
        leaves, treedef = _flatten(tree)
        if not leaves:
            return tree
        t0 = time.perf_counter()
        stacked = self._stack_global(leaves)
        fn = self._reduce_jit(len(leaves), op, divisor is not None)
        t1 = time.perf_counter()
        if divisor is not None:
            import jax.numpy as jnp

            reduced = fn(stacked, jnp.float32(divisor))
        else:
            reduced = fn(stacked)
        t2 = time.perf_counter()
        out = self._localize(reduced)
        # pop_op_stats parity with the host ring: payload bytes, the
        # bytes that crossed the device link (the localize fetch when
        # results come back host-backed; keep_global leaves everything on
        # the mesh), and the stack/dispatch/localize phase split. The
        # compiled reduce is async — ``ring`` is its DISPATCH, and the
        # wire wall is absorbed by the blocking localize (``h2d``) or the
        # caller's next use under keep_global.
        nbytes = _leaf_bytes(leaves)
        self._record_op_stats({
            "op": "allreduce",
            "backend": "xla",
            "bytes": nbytes,
            "d2h_bytes": 0 if self._keep_global else nbytes,
            "pack": t1 - t0,
            "ring": t2 - t1,
            "h2d": time.perf_counter() - t2,
        })
        return _unflatten(treedef, out)

    def allgather(self, tree: Any) -> Work:
        return self._submit(lambda: self._allgather_sync(tree))

    def _allgather_sync(self, tree: Any) -> List[Any]:
        if self._world_size == 1:
            return [tree]
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        leaves, treedef = _flatten(tree)
        if not leaves:
            return [tree] * self._world_size
        t0 = time.perf_counter()
        stacked = self._stack_global(leaves)
        key = ("gather", len(leaves))
        fn = self._jit_cache.get(key)
        if fn is None:
            replicated = NamedSharding(self._mesh, P())
            fn = self._jit_cache[key] = jax.jit(
                lambda ls: [l + 0 for l in ls],
                out_shardings=[replicated] * len(leaves),
            )
        gathered = fn(stacked)  # (world, *shape), replicated everywhere
        if self._keep_global:
            # Slice on the global mesh so rows keep the no-host-hop
            # contract (same as allreduce/broadcast in this mode).
            skey = ("gather_rows", len(leaves))
            row_fn = self._jit_cache.get(skey)
            if row_fn is None:
                replicated = NamedSharding(self._mesh, P())
                world = self._world_size
                row_fn = self._jit_cache[skey] = jax.jit(
                    lambda ls: [[l[r] for l in ls] for r in range(world)],
                    out_shardings=[[replicated] * len(leaves)]
                    * self._world_size,
                )
            out = [
                _unflatten(treedef, rows) for rows in row_fn(gathered)
            ]
            # parity contract: every op drains through pop_op_stats,
            # keep_global included (nothing crossed the device link)
            self._record_op_stats({
                "op": "allgather",
                "backend": "xla",
                "bytes": _leaf_bytes(leaves),
                "d2h_bytes": 0,
                "pack": time.perf_counter() - t0,
            })
            return out
        t1 = time.perf_counter()
        host = [np.asarray(g) for g in gathered]
        out = [
            _unflatten(treedef, self._localize([h[r] for h in host]))
            for r in range(self._world_size)
        ]
        nbytes = _leaf_bytes(leaves)
        self._record_op_stats({
            "op": "allgather",
            "backend": "xla",
            "bytes": nbytes,
            # every member's row comes back through the host fetch
            "d2h_bytes": nbytes * self._world_size,
            "pack": t1 - t0,
            "h2d": time.perf_counter() - t1,
        })
        return out

    def broadcast(self, tree: Any, root: int = 0) -> Work:
        return self._submit(lambda: self._broadcast_sync(tree, root))

    def _broadcast_sync(self, tree: Any, root: int) -> Any:
        if self._world_size == 1:
            if root != 0:
                raise RuntimeError(f"bad broadcast root {root} for world size 1")
            return tree
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        leaves, treedef = _flatten(tree)
        if not leaves:
            return tree
        stacked = self._stack_global(leaves)
        key = ("bcast", len(leaves), root)
        fn = self._jit_cache.get(key)
        if fn is None:
            replicated = NamedSharding(self._mesh, P())
            fn = self._jit_cache[key] = jax.jit(
                lambda ls: [l[root] for l in ls],
                out_shardings=[replicated] * len(leaves),
            )
        t0 = time.perf_counter()
        out = _unflatten(treedef, self._localize(fn(stacked)))
        nbytes = _leaf_bytes(leaves)
        self._record_op_stats({
            "op": "broadcast",
            "backend": "xla",
            "bytes": nbytes,
            "d2h_bytes": 0 if self._keep_global else nbytes,
            "h2d": time.perf_counter() - t0,
        })
        return out

    def barrier(self) -> Work:
        import jax.numpy as jnp

        return self._submit(
            lambda: self._allreduce_sync(
                jnp.zeros((1,), jnp.float32), ReduceOp.SUM
            )
        )
