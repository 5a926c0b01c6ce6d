"""Fault-tolerant training-step state machine.

Reference: torchft/manager.py:73-705. Every training step is a transaction:

- ``start_quorum()`` establishes membership asynchronously, overlapped with
  the forward/backward computation (quorum RPCs ride a one-thread executor;
  the jitted step runs concurrently — XLA dispatch is already async).
- ``allreduce()`` averages gradient pytrees across replica groups through the
  reconfigurable host collectives; errors are latched, never raised into the
  train loop, and a failed reduce returns the input unchanged so the step can
  be discarded by the commit vote.
- ``should_commit()`` is a distributed AND-vote: if any rank in the group saw
  an error, every group discards the step.
- Recovering replicas fetch live weights from a healthy peer over HTTP
  (:mod:`torchft_tpu.checkpointing`) instead of restarting the world.

TPU mapping: a "replica group" is a TPU slice. Intra-group parallelism (the
HSDP shard dimension) is pjit/shard_map over the slice's ICI mesh and is
invisible to this class; only the cross-group (DCN) gradient average and the
control plane live here, so a dead slice can never wedge an ICI collective.
"""

from __future__ import annotations

import functools
import logging
import os
import socket
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, TypeVar, cast

from . import _native, startup
from ._native import ManagerClient, StoreClient
from .checkpointing import CheckpointServer, CheckpointTransport
from .collectives import Collectives, ReduceOp, Work, _completed
from .futures import work_timeout
from .metrics import Metrics
from .profiling import Profiler, span

logger: logging.Logger = logging.getLogger(__name__)

MANAGER_ADDR_KEY: str = "manager_addr"
REPLICA_ID_KEY: str = "replica_id"
T = TypeVar("T")


class WorldSizeMode(Enum):
    """How the effective world size behaves under faults.
    Reference manager.py:55-70."""

    DYNAMIC = 0
    FIXED_WITH_SPARES = 1


def _startup_span(init: Callable[..., None]) -> Callable[..., None]:
    """``Manager.__init__`` whole as ``torchft::startup/manager_init``,
    the start-up record's ``manager_init`` (startup.py)."""

    @functools.wraps(init)
    def timed(self: "Manager", *args: Any, **kwargs: Any) -> None:
        with startup.record().manager_init():
            init(self, *args, **kwargs)

    return timed


class Manager:
    """Fault tolerance manager for one rank of one replica group.

    Reference manager.py:73-705. Typically composed with
    :class:`torchft_tpu.optim.OptimizerWrapper` and a gradient-averaging
    wrapper so the train loop stays ``zero_grad(); grads; step()``-shaped.
    """

    @_startup_span
    def __init__(
        self,
        collectives: Collectives,
        load_state_dict: Optional[Callable[[T], None]],
        state_dict: Optional[Callable[[], T]],
        min_replica_size: int,
        use_async_quorum: bool = True,
        timeout: timedelta = timedelta(seconds=60),
        quorum_timeout: timedelta = timedelta(seconds=60),
        connect_timeout: timedelta = timedelta(seconds=20),
        rank: Optional[int] = None,
        world_size: Optional[int] = None,
        world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
        store_addr: Optional[str] = None,
        lighthouse_addr: Optional[str] = None,
        lighthouse_root_addr: Optional[str] = None,
        region_probe_max: Optional[int] = None,
        lease_ttl: Optional[timedelta] = None,
        region: Optional[str] = None,
        host_label: Optional[str] = None,
        replica_id: Optional[str] = None,
        hostname: str = socket.gethostname(),
        heartbeat_interval: timedelta = timedelta(milliseconds=100),
        checkpoint_transport: Optional[CheckpointTransport[Dict[str, T]]] = None,
        profiler: Optional["Profiler"] = None,
        iso_collectives: Optional[Collectives] = None,
        durable_restore: Optional[Callable[[], Optional[int]]] = None,
    ) -> None:
        """
        Args:
            collectives: the reconfigurable cross-replica-group collectives.
            load_state_dict: callback restoring USER state from a recovery
                checkpoint (the manager handles its own state separately).
            state_dict: callback capturing USER state for recovery transfer.
            min_replica_size: minimum replica groups for a committable step.
            use_async_quorum: overlap quorum with forward/backward; healing
                replicas then skip participation for one step (reference
                manager.py:119-127).
            rank / world_size: this rank within the replica group (env
                ``RANK``/``WORLD_SIZE`` when None).
            store_addr: ``host:port`` of the replica group's rendezvous
                Store (env ``MASTER_ADDR``+``MASTER_PORT`` when None; if
                neither is set and world_size == 1, an in-process Store is
                created).
            lighthouse_addr: this group's lighthouse (env
                ``TORCHFT_LIGHTHOUSE``): the flat/root service, or the
                group's REGION lighthouse under a hierarchical tier.
            lighthouse_root_addr: root fallback for the hierarchical tier
                (env ``TORCHFT_LIGHTHOUSE_ROOT``): a dead region demotes
                the group to direct-root registration until it returns.
                May be a COMMA-SEPARATED endpoint list (the durable
                control plane's root failover set — active root + warm
                standbys); renewals rotate to the next endpoint on
                failure. ``lighthouse_addr`` accepts a list the same way.
            region_probe_max: bounded give-up for the demoted manager's
                once-per-TTL region re-probes (env
                ``TORCHFT_REGION_PROBE_MAX``, default 20): after this
                many consecutive failed probes the manager stops probing
                and stays on the root — a region GONE from the topology
                must not leak a doomed connect attempt per TTL for the
                rest of the tenure. 0 = probe forever (the pre-bound
                behavior; a revived region then always wins the group
                back).
            lease_ttl: membership lease duration (env
                ``TORCHFT_LEASE_TTL_MS``; None = the lighthouse's
                heartbeat-timeout default). Renewals are jittered and back
                off exponentially while the lighthouse is unreachable.
            region: this replica group's topology label (env
                ``TORCHFT_REGION``; "" = unlabeled) — the same label the
                hierarchical lighthouse tier is deployed by. It rides the
                quorum, and when EVERY quorum member carries one (>= 2
                distinct regions), ``configure`` hands the region map to
                the data plane, which compiles the topology-aware
                two-tier collective schedule (intra-region rings + an
                inter-region leader ring; see
                ``HostCollectives.allreduce_hier``).
            host_label: this replica group's HOST label (env
                ``TORCHFT_HOST``; defaults to the machine hostname, ""
                disables). It rides the quorum like ``region``, and
                whenever a (region, host) pair groups >= 2 members,
                ``configure`` hands the host map to the data plane, which
                builds the shared-memory intra-host ring tier below the
                region tiers — co-hosted members sync at memcpy speed
                instead of loopback TCP (``TORCHFT_HC_SHM`` gates the
                transport).
            replica_id: replica group name; a uuid suffix is appended by
                group rank 0 (reference manager.py:196-200).
            profiler: windowed jax profiler capture advanced once per
                step; defaults to ``Profiler.from_env()``
                (``TORCHFT_PROFILE_DIR`` etc., torchft_tpu.profiling).
            iso_collectives: optional SECONDARY data plane — an
                :class:`~torchft_tpu.isolated_xla.IsolatedXLACollectives`
                backend reconfigured alongside the primary on every
                quorum change (on an ``/iso`` store sub-prefix, so the
                two planes never cross-talk) and dispatched through
                :meth:`iso_allreduce`. AdaptiveDDP's ``xla_iso``
                candidate probes it against the host ring with the same
                lockstep-vote argmin that picks the schedule.
            durable_restore: the durable tier's cold-start fallback —
                a callable (``DurableCheckpointer.restore_latest``)
                that applies the latest committed durable checkpoint
                (user + manager state) and returns its step, or None
                when nothing is committed. Consulted ONCE, inside the
                first quorum, and only when the quorum reports no live
                donor (``max_step == 0``): a cold fleet restores
                without the trainer calling restore before its loop,
                while a live donor always wins (its weights are at
                least as fresh as any durable snapshot).
                ``DurableCheckpointer`` registers itself through
                :meth:`set_durable_restore`.
        """
        self._load_state_dict = load_state_dict
        self._user_state_dict = state_dict
        self._min_replica_size = min_replica_size
        self._use_async_quorum = use_async_quorum
        self._timeout = timeout
        self._quorum_timeout = quorum_timeout
        self._connect_timeout = connect_timeout
        self._world_size_mode = world_size_mode

        self._rank: int = rank if rank is not None else int(os.environ.get("RANK", 0))
        self._world_size: int = (
            world_size
            if world_size is not None
            else int(os.environ.get("WORLD_SIZE", 1))
        )

        self._owned_store: Optional[_native.Store] = None
        if store_addr is None:
            if "MASTER_ADDR" in os.environ and "MASTER_PORT" in os.environ:
                store_addr = (
                    f"{os.environ['MASTER_ADDR']}:{os.environ['MASTER_PORT']}"
                )
            elif self._world_size == 1:
                self._owned_store = _native.Store()
                store_addr = self._owned_store.address()
            else:
                raise ValueError(
                    "store_addr (or MASTER_ADDR/MASTER_PORT) required when "
                    "world_size > 1"
                )
        self._store_addr = store_addr
        self._store = StoreClient(store_addr, connect_timeout=connect_timeout)

        self._collectives = collectives
        self._iso_collectives = iso_collectives
        self._iso_ok = False
        self._checkpoint_transport: CheckpointTransport[Dict[str, T]] = (
            checkpoint_transport
            if checkpoint_transport is not None
            else CheckpointServer(timeout=timeout)
        )
        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="async_quorum"
        )
        self._quorum_future: Optional[Any] = None

        self._step = 0
        self._batches_committed = 0
        self._quorum_id = -1
        self._errored: Optional[Exception] = None
        self._op_epoch = 0
        # Makes the {epoch check -> error latch} in work callbacks atomic
        # against the {epoch bump -> error clear} in start_quorum; without
        # it a stale callback could pass the check, lose the GIL across the
        # bump+clear, then latch into the new step.
        self._error_lock = threading.Lock()
        self._force_reconfigure = False
        self._healing = False
        self._pending_work: List[Work] = []
        self._commit_hooks: List[Any] = []
        self._durable_restore = durable_restore
        self._durable_consulted = False
        self._pending_state_dict: Optional[Dict[str, object]] = None
        self._participating_rank: Optional[int] = None
        self._participating_world_size: int = 0
        self._metrics = Metrics()
        # sampled only when a call really blocks (wait_quorum, Work.wait)
        self._metrics.declare("quorum_wait", "work_wait")
        # the process's way to its first commit (startup.py): the snapshot
        # carries it as ``process``; closed at the first vote that passes
        startup.record().bind(self._metrics)
        self._starting = True
        # the transport times its own work (the donor's staging and the
        # ranges it serves) on its serving threads: into our timers,
        # stamped with our step
        self._checkpoint_transport.metrics = self._metrics
        # Last measured effective wire throughput (MB/s), updated by
        # observe_op_stats(); None until a ring op has been observed.
        self._last_wire_eff_mbps: Optional[float] = None
        # Per-tier effective throughput of the last hierarchical op
        # (MB/s per tier key; shm host tiers measure ring movement over
        # phase wall). Empty until a hier op has been observed.
        self._last_tier_mbps: Dict[str, float] = {}
        # Resident optimizer-state bytes as reported by the training
        # strategy (ShardedDDP reports its ~1/W shard); None until one
        # reports. Exported through signals() so the policy engine can
        # price the sharded candidate's memory term.
        self._opt_state_bytes: Optional[int] = None
        self._profiler = (
            profiler if profiler is not None else Profiler.from_env()
        )

        lighthouse_addr = lighthouse_addr or os.environ.get("TORCHFT_LIGHTHOUSE")
        lighthouse_root_addr = lighthouse_root_addr or os.environ.get(
            "TORCHFT_LIGHTHOUSE_ROOT", ""
        )
        if region_probe_max is None:
            region_probe_max = int(
                os.environ.get("TORCHFT_REGION_PROBE_MAX", "20")
            )
        self._region_probe_max = region_probe_max
        if lease_ttl is None:
            env_ttl = os.environ.get("TORCHFT_LEASE_TTL_MS")
            if env_ttl:
                lease_ttl = timedelta(milliseconds=int(env_ttl))
        if region is None:
            region = os.environ.get("TORCHFT_REGION", "")
        self._region = region
        if host_label is None:
            host_label = os.environ.get("TORCHFT_HOST", socket.gethostname())
        self._host_label = host_label
        # The quorum's region and host maps (replica-rank order),
        # refreshed every quorum; what hier_capable() and the configure
        # call key off.
        self._replica_regions: List[str] = []
        self._replica_hosts: List[str] = []
        replica_id = replica_id if replica_id is not None else ""

        self._manager: Optional[_native.Manager] = None
        if self._rank == 0:
            if lighthouse_addr is None:
                raise ValueError(
                    "lighthouse_addr (or TORCHFT_LIGHTHOUSE) required on rank 0"
                )
            # Group rank 0 hosts the native manager server and publishes its
            # address + the uuid-qualified replica id through the store
            # (reference manager.py:184-211).
            replica_id = (
                f"{replica_id}:{uuid.uuid4()}" if replica_id else str(uuid.uuid4())
            )
            bind = f"[::]:{int(os.environ.get('TORCHFT_MANAGER_PORT', 0))}"
            self._manager = _native.Manager(
                replica_id=replica_id,
                lighthouse_addr=lighthouse_addr,
                hostname=hostname,
                bind=bind,
                store_addr=store_addr,
                world_size=self._world_size,
                heartbeat_interval=heartbeat_interval,
                connect_timeout=connect_timeout,
                root_addr=lighthouse_root_addr,
                lease_ttl=lease_ttl,
                region=region,
                host=host_label,
                region_probe_max=region_probe_max,
            )
            self._store.set(MANAGER_ADDR_KEY, self._manager.address().encode())
            self._store.set(REPLICA_ID_KEY, replica_id.encode())

        addr = self._store.get(MANAGER_ADDR_KEY, timeout=connect_timeout).decode()
        self._client = ManagerClient(addr, connect_timeout=connect_timeout)
        self._replica_id = self._store.get(
            REPLICA_ID_KEY, timeout=connect_timeout
        ).decode()
        self._logger = _ManagerLogger(self, self._replica_id, self._rank)

    def shutdown(self) -> None:
        if self._profiler is not None:
            self._profiler.shutdown()
        self._checkpoint_transport.shutdown(wait=False)
        self._executor.shutdown(wait=True)
        if self._iso_collectives is not None:
            self._iso_collectives.shutdown()
        if self._manager is not None:
            self._manager.shutdown()

    # -- step lifecycle --

    def start_quorum(
        self,
        allow_heal: bool = True,
        shrink_only: bool = False,
        timeout: Optional[timedelta] = None,
    ) -> None:
        """Computes a new quorum, asynchronously unless configured otherwise.

        Must be called at the start of every train step (before the first
        ``allreduce``) on every rank. Reference manager.py:365-415.
        """
        if self._profiler is not None:
            self._profiler.on_step(self._step)
        # every torchft::* span of this step, on whichever thread, carries
        # the step it began on
        self._metrics.step = self._collectives.trace_step = self._step
        if self._quorum_future is not None:
            # Wait for the previous quorum (and any healing) to finish. Its
            # errors were already surfaced through allreduce/should_commit;
            # a new step starts from a clean slate.
            try:
                self._quorum_future.result()
            except Exception:
                pass

        with self._error_lock:
            self._op_epoch += 1
            self._errored = None
        self._healing = False
        self._pending_work = []
        self._quorum_future = self._executor.submit(
            self._async_quorum,
            allow_heal=allow_heal,
            shrink_only=shrink_only,
            quorum_timeout=timeout or self._quorum_timeout,
        )
        if not self._use_async_quorum:
            self.wait_quorum()
            if self._healing:
                # Eagerly apply the fetched checkpoint so the optimizer sees
                # the recovered state this same step; sync-mode healers then
                # participate fully (reference :406-414).
                self._apply_pending_state_dict()
                self._healing = False

    def wait_quorum(self) -> None:
        """Blocks until the quorum started by ``start_quorum`` completes."""
        assert (
            self._quorum_future is not None
        ), "must call start_quorum before wait_quorum"
        if self._quorum_future.done():  # settled: nobody is held
            self._quorum_future.result()
            return
        # how long the CALLER was held by the quorum (a step asks several
        # times; only the call that blocks is a sample); the RPC itself is
        # `quorum`, on the quorum thread
        with self._metrics.timed("quorum_wait"):
            self._quorum_future.result()

    def _async_quorum(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: timedelta
    ) -> None:
        # Atomically consume the rebuild request so a report_error racing
        # with the RPC can't be wiped by an unconditional clear afterwards;
        # restore it if the RPC fails (the rebuild still hasn't happened).
        with self._error_lock:
            force_reconfigure = self._force_reconfigure
            self._force_reconfigure = False
        try:
            with self._metrics.timed("quorum"):
                result = self._client.quorum(
                    rank=self._rank,
                    step=self._step,
                    checkpoint_metadata=self._checkpoint_transport.metadata(),
                    shrink_only=shrink_only,
                    force_reconfigure=force_reconfigure,
                    timeout=quorum_timeout,
                )
        except Exception:
            if force_reconfigure:
                with self._error_lock:
                    self._force_reconfigure = True
            raise

        quorum_id = result.quorum_id
        store_address = result.store_address

        if self._use_async_quorum or not allow_heal:
            # Participate only if already at max step: healing overlaps with
            # this step, so recovering replicas sit it out (reference
            # manager.py:452-456).
            participating_rank: Optional[int] = result.max_rank
            participating_world = result.max_world_size
        else:
            participating_rank = result.replica_rank
            participating_world = result.replica_world_size

        if self._world_size_mode == WorldSizeMode.FIXED_WITH_SPARES:
            # Spares join collectives with zeroed grads; the divisor stays
            # fixed so numerics never change under churn. Clamped with min()
            # so a cohort BELOW min_replica_size still fails the
            # enough-replicas vote in should_commit (reference :459-468).
            if (
                participating_rank is not None
                and participating_rank >= self._min_replica_size
            ):
                participating_rank = None
            participating_world = min(participating_world, self._min_replica_size)

        self._participating_rank = participating_rank
        self._participating_world_size = participating_world
        heal = allow_heal and result.heal

        if self._durable_restore is not None and not self._durable_consulted:
            # Restore-time donor/durable arbitration, one-shot at the
            # first quorum. A live donor (max_step > 0) always beats the
            # durable tier — its weights are at least as fresh as any
            # committed snapshot and the normal heal path ships them —
            # so the durable fallback only fires on a COLD fleet: no
            # member has committed a step and this member hasn't
            # restored anything itself. Every member consults its own
            # restore_latest against the shared store, so the fleet
            # rises at one consistent committed step; members that find
            # nothing init-sync from a restored peer as usual.
            self._durable_consulted = True
            if self._step == 0 and result.max_step == 0:
                restored = self._durable_restore()
                if restored is not None:
                    self._metrics.incr("durable_cold_restores")
                    self._logger.info(
                        f"cold fleet: restored durable step {restored} "
                        "(no live donor in quorum)"
                    )

        if quorum_id != self._quorum_id:
            if self._quorum_id != -1:
                # Membership moved (or a data-plane error forced a rebuild)
                # mid-run — the rolling churn signal the policy engine and
                # the status export watch. The FIRST configure is a cold
                # start, not churn.
                self._metrics.mark("churn")
            # Reconfigure the data plane on a store prefix unique to this
            # quorum AND this local rank: cross-group rings are per local
            # rank, and stale members can't collide (reference :470-477).
            prefix = f"{store_address}/torchft/{quorum_id}/{self._rank}"
            self._logger.info(f"reconfiguring collectives quorum_id={quorum_id}")
            # The quorum's region and host maps (one label per replica
            # rank) ride into the data plane: a host ring compiles them
            # into the hierarchical schedule when usable; other backends
            # ignore them. The hosts kwarg is passed only to backends
            # that declare it (every in-repo backend does) so external
            # stand-ins with the pre-host signature keep working.
            regions = list(result.replica_regions)
            self._replica_regions = regions
            hosts = list(result.replica_hosts)
            self._replica_hosts = hosts
            cfg_kwargs: Dict[str, Any] = {"regions": regions or None}
            if hosts and any(hosts) and self._configure_takes_hosts():
                cfg_kwargs["hosts"] = hosts
            with self._metrics.timed("reconfigure"):
                self._collectives.configure(
                    prefix, result.replica_rank, result.replica_world_size,
                    **cfg_kwargs,
                )
            if self._iso_collectives is not None:
                # The secondary (isolated) plane reconfigures on its own
                # sub-prefix: same quorum, disjoint store keys — its
                # kill-and-respawn cannot collide with the ring's
                # rendezvous, and a stale child never cross-talks. A
                # failure here (un-spawnable child, dead fork server)
                # must NEVER take the primary plane down with it: the
                # plane is marked unusable, iso dispatches latch, and the
                # AdaptiveDDP probe's failure sentinel keeps the
                # candidate from ever winning ("never beat-by-crash").
                with self._metrics.timed("reconfigure_iso"):
                    try:
                        self._iso_collectives.configure(
                            f"{prefix}/iso",
                            result.replica_rank,
                            result.replica_world_size,
                        )
                        self._iso_ok = True
                    except Exception as e:  # noqa: BLE001
                        self._iso_ok = False
                        self._metrics.incr("iso_configure_failures")
                        self._logger.exception(
                            f"isolated data plane configure failed "
                            f"(primary plane unaffected): {e}"
                        )
            self._metrics.incr("reconfigures")
            self._quorum_id = quorum_id

        if allow_heal:
            if result.recover_dst_ranks:
                # This replica is a recovery source: publish live weights.
                self._logger.info(
                    f"peers need recovery from us {result.recover_dst_ranks}"
                )
                with self._metrics.timed("send_checkpoint"):
                    self._checkpoint_transport.send_checkpoint(
                        dst_ranks=result.recover_dst_ranks,
                        step=result.max_step,
                        state_dict=self._manager_state_dict(),
                        timeout=self._timeout,
                    )
            if heal:
                self._healing = True
                # A recovery at max_step 0 is the initial weight
                # synchronization every fresh cohort's non-primary runs —
                # not a fault. Counting it as a heal would seed the policy
                # engine's churn-cost signal with a phantom fault recovery
                # on every clean startup.
                self._metrics.incr(
                    "heals" if result.max_step > 0 else "init_sync_heals"
                )
                self._logger.info(
                    f"healing required, fetching checkpoint from "
                    f"{result.recover_src_manager_address} step={result.max_step}"
                )
                primary_client = ManagerClient(
                    result.recover_src_manager_address,
                    connect_timeout=self._connect_timeout,
                )
                checkpoint_metadata = primary_client.checkpoint_metadata(
                    self._rank, timeout=self._timeout
                )
                assert result.recover_src_rank is not None
                with self._metrics.timed("heal_fetch"):
                    checkpoint = self._checkpoint_transport.recv_checkpoint(
                        src_rank=result.recover_src_rank,
                        metadata=checkpoint_metadata,
                        step=result.max_step,
                        timeout=self._timeout,
                    )
                # Manager state is applied immediately (so step/commit
                # counters are right); user state waits for a safe point on
                # the main thread (reference :514-526).
                self._pending_state_dict = cast(Dict[str, object], checkpoint)
                self.load_state_dict(
                    cast(Dict[str, int], checkpoint["torchft"])
                )

    def _apply_pending_state_dict(self) -> None:
        assert self._healing, "apply_pending_state_dict called when not healing"
        # Settle the quorum thread first: it is the writer of
        # _pending_state_dict (reference manager.py:531-532).
        self.wait_quorum()
        assert (
            self._pending_state_dict is not None
        ), "checkpoint was not fetched before apply"
        assert self._load_state_dict is not None, "no load_state_dict callback"
        self._logger.info("applying pending state dict")
        with self._metrics.timed("heal_apply"):
            self._load_state_dict(cast(T, self._pending_state_dict["user"]))
        self._pending_state_dict = None

    # -- data plane --

    def allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.AVG,
        wire: Optional[str] = None,
    ) -> Work:
        """Fault-tolerantly averages a gradient pytree across replica groups.

        Data-plane errors never raise: on a collective failure the returned
        Work resolves to the tree AS CONTRIBUTED — the input tree for a
        participating replica, the zeroed tree for a healing/spare one
        (zero-contribution holds even on the fallback) — and the error is
        latched for ``should_commit`` (reference manager.py:242-303). A failed or
        timed-out QUORUM, however, DOES raise out of this call (via
        ``wait_quorum``) — membership failure means the step cannot proceed
        at all, matching reference manager.py:265. Non-participating
        (healing/spare) replicas contribute zeros. ``op`` must be AVG
        (divide by ``num_participants``, the live divisor, reference
        :279-291) or SUM. ``wire`` forwards to the collectives backend
        (``"q8"`` = int8-quantized ring chunks, constant wire bytes in
        world size — see Collectives.allreduce).
        """
        divisor = self._participant_divisor(op, "allreduce")

        def dispatch(zeroed_tree: Any) -> Work:
            return self._collectives.allreduce(
                zeroed_tree, ReduceOp.SUM, divisor=divisor(), wire=wire
            )

        return self._managed_dispatch("allreduce", tree, dispatch, lambda t: t)

    def plan_allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.AVG,
        wire: Optional[str] = None,
        device_pack: Optional[bool] = None,
        hier: bool = False,
    ) -> Work:
        """Fault-tolerantly averages a gradient pytree through a
        persistent precompiled comm plan (one GIL-released native call
        per step — see Collectives.plan_allreduce). Same quorum and
        latching discipline as :meth:`allreduce`, with one difference in
        the failure default: a failed plan execute resolves to ``None``
        (not the input tree) — the plan's persistent output buffers may
        hold a partial unpack, so there is no meaningful "as contributed"
        tree to return. The error latches and ``should_commit`` discards
        the step; callers must treat a ``None`` result as an aborted
        sync, never as data. Plans are invalidated (and transparently
        rebuilt) whenever the quorum changes — configure() drops them
        with the old ring. ``wire``: None | "bf16" | "q8" | "q8ef"
        (native error feedback; reset the carry on heal via
        :meth:`reset_plan_feedback`). ``device_pack`` forwards to the
        backend (True/False/None = ``TORCHFT_DEVICE_PACK``): pack the
        wire encoding on the accelerator so d2h bytes scale with the
        wire, results bit-identical either way — see
        Collectives.plan_allreduce. ``hier`` runs the plan over the
        TWO-TIER schedule (see :meth:`allreduce_hier`); a cohort without
        a usable region map latches the error and the step is discarded
        — the sentinel path AdaptiveDDP's ``plan_hier`` candidate relies
        on, never a crash."""
        divisor = self._participant_divisor(op, "plan_allreduce")

        def dispatch(zeroed_tree: Any) -> Work:
            return self._collectives.plan_allreduce(
                zeroed_tree, ReduceOp.SUM, divisor=divisor(), wire=wire,
                device_pack=device_pack, hier=hier,
            )

        return self._managed_dispatch(
            "plan_allreduce", tree, dispatch, lambda t: None
        )

    def allreduce_hier(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.AVG,
        wire: Optional[str] = None,
    ) -> Work:
        """Fault-tolerantly averages a pytree over the TOPOLOGY-AWARE
        two-tier schedule (``Collectives.allreduce_hier``): intra-region
        reduce-scatter -> intra allgather -> inter-region ring among one
        leader per region -> intra broadcast, so the slow inter-region
        links carry a fraction of the flat ring's bytes and only on the
        leaders. ``wire`` (``None`` | ``"bf16"`` | ``"q8"``) applies to
        the inter hop only. Same quorum/zeroing/latching discipline as
        :meth:`allreduce` (failure resolves to the tree as contributed,
        the error latches, ``should_commit`` discards); a cohort whose
        region map is unusable (single region, unlabeled members, or a
        backend without the schedule) latches the dispatch error — the
        sentinel discipline, never a crash."""
        divisor = self._participant_divisor(op, "allreduce_hier")

        def dispatch(zeroed_tree: Any) -> Work:
            return self._collectives.allreduce_hier(
                zeroed_tree, ReduceOp.SUM, divisor=divisor(), wire=wire
            )

        return self._managed_dispatch(
            "allreduce_hier", tree, dispatch, lambda t: t
        )

    def hier_capable(self) -> bool:
        """Whether the CURRENT quorum's data plane compiled a two-tier
        (topology-aware) schedule: every member carried a region label
        and >= 2 distinct regions were present, on a backend that
        understands topology (the host ring). Settles the quorum thread
        first — the region map is its writer."""
        if self._quorum_future is not None:
            self.wait_quorum()
        cap = getattr(self._collectives, "hier_capable", None)
        return bool(cap()) if cap is not None else False

    def _configure_takes_hosts(self) -> bool:
        try:
            import inspect

            sig = inspect.signature(self._collectives.configure)
        except (TypeError, ValueError):
            return False
        params = sig.parameters
        return "hosts" in params or any(
            p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
        )

    def replica_hosts(self) -> List[str]:
        """The current quorum's host map, indexed by replica rank (empty
        until the first quorum; empty strings for unlabeled members).
        Paired with :meth:`replica_regions`: (region, host) groups are
        what the data plane compiles into the shared-memory intra-host
        tier."""
        return list(self._replica_hosts)

    def replica_regions(self) -> List[str]:
        """The current quorum's region map, indexed by replica rank
        (empty strings for unlabeled members; empty before the first
        quorum). Settles the quorum thread first."""
        if self._quorum_future is not None:
            self.wait_quorum()
        return list(self._replica_regions)

    def has_iso_plane(self) -> bool:
        """Whether a secondary isolated data plane was attached at
        construction (NOT whether its child is currently healthy — a
        sick plane still exists, and its dispatch failures are exactly
        what the probe's sentinel discipline measures)."""
        return self._iso_collectives is not None

    def iso_collectives(self) -> Optional[Collectives]:
        """The attached isolated data plane (None without one)."""
        return self._iso_collectives

    def iso_allreduce(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.AVG,
        wire: Optional[str] = None,
    ) -> Work:
        """Fault-tolerantly averages a gradient pytree through the
        ISOLATED data plane (the disposable-child XLA backend attached
        as ``iso_collectives``): same quorum/zeroing/latching discipline
        as :meth:`allreduce`, with the failure default ``None`` — a
        child that died mid-op leaves no meaningful "as contributed"
        tree (its shared-memory staging may hold a partial result), so
        the Work resolves to ``None``, the error latches, and
        ``should_commit`` discards the step; the error's forced
        reconfigure then respawns the child at the next quorum (step-
        granularity recovery). Raises eagerly (static usage error) when
        no isolated plane was attached."""
        if self._iso_collectives is None:
            raise ValueError(
                "no isolated data plane: construct the Manager with "
                "iso_collectives=IsolatedXLACollectives(...)"
            )
        divisor = self._participant_divisor(op, "iso_allreduce")

        def dispatch(zeroed_tree: Any) -> Work:
            if not self._iso_ok:
                raise RuntimeError(
                    "isolated data plane unusable this quorum (its "
                    "configure failed; primary plane unaffected)"
                )
            return self._iso_collectives.allreduce(
                zeroed_tree, ReduceOp.SUM, divisor=divisor(), wire=wire
            )

        return self._managed_dispatch(
            "iso_allreduce", tree, dispatch, lambda t: None
        )

    def reset_plan_feedback(self) -> None:
        """Zeroes the error-feedback carry of every cached ``q8ef`` comm
        plan — native and device-resident alike (no-op for backends
        without plans): the heal/abort discipline — a recovered or
        rolled-back member must not carry a residual from its abandoned
        trajectory."""
        self._collectives.plan_reset_feedback()

    def reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.AVG,
        wire: Optional[str] = None,
    ) -> Work:
        """Fault-tolerantly reduces a pytree but stops at the
        reduce-scatter boundary: the Work resolves to this rank's
        :class:`~torchft_tpu.collectives.TreeShard` of the averaged
        flat-packed tree (the sharded-weight-update schedule — update the
        shard, then :meth:`allgather_into` the result). Same error
        contract as :meth:`allreduce` except the failure default is
        ``None`` — there is no meaningful "as contributed" shard, so a
        mid-sync failure resolves to ``None``, the error latches, and
        ``should_commit`` discards the step; callers must treat a ``None``
        shard as an aborted sync, never as data. ``op`` must be AVG or
        SUM; ``wire="q8"`` reduces over the quantized ring (the returned
        shard is full f32 — the fused op's lossy allgather phase never
        runs)."""
        divisor = self._participant_divisor(op, "reduce_scatter")

        def dispatch(zeroed_tree: Any) -> Work:
            return self._collectives.reduce_scatter(
                zeroed_tree, ReduceOp.SUM, divisor=divisor(), wire=wire
            )

        return self._managed_dispatch(
            "reduce_scatter", tree, dispatch, lambda t: None
        )

    def allgather_into(self, shard: Any, wire: Optional[str] = None) -> Work:
        """Fault-tolerantly gathers every member's (updated) TreeShard
        back into the full pytree — the parameter-allgather leg of the
        sharded outer sync (``wire="bf16"`` halves its bytes). Failure
        default is ``None`` (same contract as :meth:`reduce_scatter`).
        Unlike the reduction ops, a non-participating (healing/spare)
        member's shard is NOT zeroed: the gathered tree is replicated
        state every ring member owns a slice of, not a contribution sum —
        zeroing a spare's slice would corrupt every member's result."""
        return self._managed_dispatch(
            "allgather_into",
            shard,
            lambda s: self._collectives.allgather_into(s, wire=wire),
            lambda s: None,
            zero_nonparticipating=False,
        )

    def plan_reduce_scatter(
        self,
        tree: Any,
        op: ReduceOp = ReduceOp.AVG,
        wire: Optional[str] = None,
        ag_wire: Optional[str] = None,
    ) -> Work:
        """Fault-tolerantly reduces a gradient pytree through the PLAN
        path but stops at the reduce-scatter boundary: one GIL-released
        native call over a precompiled sharded schedule, resolving to
        this rank's :class:`~torchft_tpu.collectives.TreeShard` of the
        averaged flat tree (``shard.plan`` set — route the updated shard
        back through :meth:`plan_allgather_into`). The per-step ZeRO
        grad leg. ``wire``: None | "bf16" | "q8" (the returned shard is
        full f32 on every wire — the owner's chunk never rides a lossy
        hop, the PR-2 discipline; no "q8ef": error feedback corrects a
        FUSED lossy result, and the shard isn't one). ``ag_wire``
        (None | "bf16") pre-declares the param leg's wire — it is baked
        into the plan schedule and checked cohort-wide in the op header.
        Failure default ``None`` (plan buffers may hold a partial
        result), the error latches, ``should_commit`` discards — same
        contract as :meth:`plan_allreduce`. A cohort whose backend or
        leaves can't take the sharded plan (non-f32 leaves, no plan
        support) latches the dispatch error — the sentinel discipline
        AdaptiveDDP's ``ddp_sharded`` candidate relies on, never a
        crash."""
        divisor = self._participant_divisor(op, "plan_reduce_scatter")

        def dispatch(zeroed_tree: Any) -> Work:
            return self._collectives.plan_reduce_scatter(
                zeroed_tree, ReduceOp.SUM, divisor=divisor(), wire=wire,
                ag_wire=ag_wire,
            )

        return self._managed_dispatch(
            "plan_reduce_scatter", tree, dispatch, lambda t: None
        )

    def plan_allgather_into(
        self, shard: Any, wire: Optional[str] = None
    ) -> Work:
        """Fault-tolerantly gathers the cohort's (updated) plan shards
        back into the full pytree — the param leg of the per-step ZeRO
        schedule, one native call over the same precompiled plan that
        produced the shard. ``wire`` must equal the ``ag_wire`` declared
        at :meth:`plan_reduce_scatter` (``"bf16"`` halves the leg's
        bytes; every member — owner included — adopts the identically
        decoded words, so gathered params stay bit-identical across the
        cohort). Failure default ``None``; like :meth:`allgather_into`,
        a non-participating member's shard is NOT zeroed — the gather is
        replicated state, not a contribution sum."""
        return self._managed_dispatch(
            "plan_allgather_into",
            shard,
            lambda s: self._collectives.plan_allgather_into(s, wire=wire),
            lambda s: None,
            zero_nonparticipating=False,
        )

    def allgather(self, tree: Any) -> Work:
        """Fault-tolerantly gathers ``tree`` from every cohort member.

        Same error contract as :meth:`allreduce` (data-plane errors latch
        and the Work resolves to ``[tree]``; quorum failure raises), and
        the same participation discipline: a non-participating
        (healing/spare) replica's entry is ZEROED before the gather, so
        consumers averaging entry-wise must divide by
        ``num_participants()``, not the cohort size. Every ring member's
        entry appears, ordered by replica rank. Intended for
        LocalSGD-family window syncs (quantized payloads average
        member-wise after dequantization — a SUM over the wire dtype
        would overflow). No reference analog at the Manager level (the
        reference exposes allgather only on the raw PG, reference
        process_group.py:130-137).
        """
        return self._managed_dispatch(
            "allgather", tree, self._collectives.allgather, lambda t: [t]
        )

    def _participant_divisor(
        self, op: ReduceOp, op_name: str
    ) -> Callable[[], Optional[float]]:
        """The one divisor rule of the six managed reduction ops. Raises
        ``ValueError`` HERE, at the call site, for any ``op`` but AVG or
        SUM: a static usage error must not be swallowed by the managed
        error discipline and masquerade as a cohort data-plane failure.
        Returns the rule for the dispatch closure to call once the quorum
        is joined: AVG -> ``num_participants`` (the live divisor, NOT the
        ring size: healing/spare members contribute zeros and don't
        count, reference manager.py:279-291), applied host-side in the
        ring where the bytes already are, so no extra jit program or
        device dispatch per step; SUM -> ``None``."""
        if op not in (ReduceOp.AVG, ReduceOp.SUM):
            raise ValueError(f"unsupported managed {op_name} op: {op}")

        def divisor() -> Optional[float]:
            if op == ReduceOp.SUM:
                return None
            num_participants = self.num_participants()
            assert num_participants >= 1
            return float(num_participants)

        return divisor

    def _managed_dispatch(
        self,
        op_name: str,
        tree: Any,
        dispatch: Callable[[Any], Work],
        default_factory: Callable[[Any], Any],
        zero_nonparticipating: bool = True,
    ) -> Work:
        """The shared managed-collective discipline: errored short-circuit,
        quorum join, participant zeroing, profiler span + metrics timer,
        timeout + error-latching wrap; failures AFTER the quorum join
        latch and resolve to ``default_factory`` applied to the tree AS
        DISPATCHED — for a non-participating (healing/spare) replica that
        is the zeroed tree, preserving the zero-contribution discipline on
        that fallback (reference manager.py:242-303, 326-363). The
        PRE-quorum short-circuit (an error already latched when the op is
        issued) returns the INPUT tree unzeroed: participation isn't
        knowable without the quorum, and the step is unconditionally
        discarded by ``should_commit`` — consumers must not treat that
        early fallback as a zero contribution."""
        if self.errored() is not None:
            return _completed(default_factory(tree))
        self.wait_quorum()
        try:
            import jax

            if zero_nonparticipating and not self.is_participating():
                tree = jax.tree_util.tree_map(
                    lambda l: l * 0 if hasattr(l, "__mul__") else l, tree
                )
            t0 = time.perf_counter()
            with span(f"torchft::{op_name}_dispatch", self._step):
                work = dispatch(tree)
            work.add_done_callback(
                lambda _f: self._metrics.record(
                    op_name, time.perf_counter() - t0
                )
            )
            return self.wrap_work(work, default=default_factory(tree))
        except Exception as e:  # noqa: BLE001 - latch, never raise
            self._logger.exception(f"{op_name} failed immediately: {e}")
            self.report_error(e)
            return _completed(default_factory(tree))

    def wrap_work(self, work: Work, default: Any, timeout: Optional[timedelta] = None) -> Work:
        """Adds a timeout and error-swallowing to a Work: on failure the
        error is latched and ``default`` is returned (reference
        manager.py:326-363)."""
        timed = work_timeout(work, timeout or self._timeout)
        epoch = self._op_epoch

        def swallow() -> Work:
            from concurrent.futures import Future

            out: "Future[Any]" = Future()

            def on_done(f: "Future[Any]") -> None:
                exc = f.exception()
                if exc is not None:
                    self._logger.exception(f"async work failed: {exc}")
                    with self._error_lock:
                        if epoch == self._op_epoch:
                            # Works abandoned by a fail-fast should_commit
                            # may settle during a LATER step; their errors
                            # belong to the (already aborted) step that
                            # issued them and must not latch into the
                            # current one.
                            self._errored = cast(Exception, exc)
                            self._force_reconfigure = True
                    out.set_result(default)
                else:
                    out.set_result(f.result())

            timed._future.add_done_callback(on_done)
            return Work(out, self._metrics)

        wrapped = swallow()
        self._pending_work.append(wrapped)
        return wrapped

    # -- error tracking --

    def report_error(self, e: Exception) -> None:
        """Latch an error: the current step will not commit and collectives
        are no-ops until the next quorum (reference manager.py:305-317).

        Any error also requests a data-plane rebuild through the next quorum
        (``force_reconfigure``): a failed ring op shuts the ring down
        (native fail-fast propagation), and if membership happens to be
        unchanged the quorum_id would otherwise not bump — leaving every
        member with dead sockets. Spurious rebuilds cost one rendezvous."""
        with self._error_lock:
            self._errored = e
            self._force_reconfigure = True

    def errored(self) -> Optional[Exception]:
        return self._errored

    # -- commit protocol --

    def should_commit(
        self,
        timeout: Optional[timedelta] = None,
        count_batches: bool = True,
    ) -> bool:
        """Distributed AND-vote on step validity. Reference manager.py:545-598.

        Returns True iff every rank of every participating replica group
        completed the step without errors and quorum size >= min_replica_size.
        ``count_batches=False`` marks a CONTROL transaction (e.g. the policy
        engine's decision step): the committed step counter still advances
        (transaction ordering and heal max_step depend on it) but
        ``batches_committed`` does not — no batch was trained.
        """
        # Settle the quorum thread before reading _healing/_errored: it is
        # their writer, and an early-errored step may reach here without any
        # allreduce having waited on it. (A failed quorum raises, as it
        # would from num_participants below.)
        self.wait_quorum()

        for work in self._pending_work:
            if self._errored is not None:
                break
            work.wait()  # error-swallowing: never raises, latches instead
        self._pending_work = []

        # Apply the fetched checkpoint whenever healing — even if an error
        # latched this step. The manager step was already advanced to
        # max_step by the quorum thread, so skipping the apply would leave
        # this replica reporting max_step on stale weights and never healed
        # again (reference manager.py:575-577 applies unconditionally).
        if self._healing:
            self._apply_pending_state_dict()

        local_should_commit = (
            self._errored is None
            and self.num_participants() >= self._min_replica_size
        )
        with self._metrics.timed("commit_vote"):
            should_commit = self._client.should_commit(
                self._rank,
                self._step,
                local_should_commit,
                timeout=timeout or self._timeout,
            )
        self._logger.info(
            f"should_commit={should_commit} enough_replicas="
            f"{self.num_participants() >= self._min_replica_size}, "
            f"errored={self._errored}"
        )

        # The checkpoint dict must not be readable while the optimizer
        # mutates it (reference manager.py:591).
        self._checkpoint_transport.disallow_checkpoint()

        if should_commit:
            self._step += 1
            if count_batches:
                self._batches_committed += self.num_participants()
            if self._starting:
                self._starting = False
                ready = startup.record().close(self._metrics)
                if ready is not None:  # the first commit of this life
                    self._logger.info(ready)
        self._metrics.incr("commits" if should_commit else "aborts")
        if self._errored is not None:
            self._metrics.incr("errors")
        self._healing = False
        # Commit boundary: the quorum thread is settled (wait_quorum above)
        # and the vote is final, so (step, quorum_id) here names exactly
        # one committed fleet state — the only point where a durable
        # snapshot may capture. Hooks are observers: a failing snapshot
        # must never abort training, so exceptions are logged and dropped.
        for hook in self._commit_hooks:
            try:
                hook(self._step, self._quorum_id, should_commit)
            except Exception as e:  # noqa: BLE001
                self._logger.warn(f"commit hook failed: {e}")
        return should_commit

    # -- state --

    def load_state_dict(self, state_dict: Dict[str, int]) -> None:
        """Restores manager state (call when resuming from a durable
        checkpoint, alongside the user state). Reference manager.py:600-613."""
        self._step = state_dict["step"]
        self._batches_committed = state_dict["batches_committed"]

    def _manager_state_dict(self) -> Dict[str, object]:
        assert self._user_state_dict is not None, "no state_dict callback"
        return {
            "user": self._user_state_dict(),
            "torchft": self.state_dict(),
        }

    def state_dict(self) -> Dict[str, int]:
        """Manager state to persist alongside user checkpoints.
        Reference manager.py:615-629."""
        return {"step": self._step, "batches_committed": self._batches_committed}

    # -- observability / policy signals --

    def observe_op_stats(self) -> List[dict]:
        """Drains the data plane's per-op phase timings (``pop_op_stats``)
        THROUGH the manager, folding ring entries into the rolling
        effective-bandwidth estimate ``signals()`` reports: per op,
        ``wire_bytes / ring_s`` is the achieved wire throughput (the number
        the policy cost model divides by). Returns the drained entries,
        so a caller that wants the raw breakdown (benches, diagnosis
        tooling) consumes the SAME drain — pop semantics are preserved,
        just routed. A backend without op stats yields ``[]``."""
        pop = getattr(self._collectives, "pop_op_stats", None)
        entries: List[dict] = pop() if pop is not None else []
        for st in entries:
            # Hierarchical entries additionally fold PER-TIER effective
            # throughput (measured tier bytes over that tier's phase
            # wall): the policy engine prices hier/shm candidates on the
            # bottleneck tier, not this op's folded average. Shm host
            # tiers bill ring movement (tx_bytes is honestly 0 there).
            tiers = st.get("tiers")
            if tiers:
                for name, t in tiers.items():
                    if name == "inter":
                        phase_s = t.get("ring_s") or 0.0
                    else:
                        phase_s = (
                            (t.get("rs_s") or 0.0) + (t.get("ag_s") or 0.0)
                            + (t.get("bcast_s") or 0.0)
                        )
                    moved = t.get("tx_bytes") or t.get("shm_bytes") or 0
                    if phase_s > 0 and moved > 0:
                        self._last_tier_mbps[name] = (
                            moved / phase_s / (1 << 20)
                        )
            ring_s = st.get("ring")
            wire_bytes = st.get("wire_bytes") or st.get("bytes")
            if not ring_s or not wire_bytes or ring_s <= 0:
                continue
            self._last_wire_eff_mbps = wire_bytes / ring_s / (1 << 20)
        return entries

    def signals(self, churn_window_s: float = 600.0) -> Dict[str, Any]:
        """The policy engine's input signals as one JSON-able dict:

        - ``churn_per_min``: rolling rate of data-plane reconfigures
          (quorum-id bumps after the first — kills, joins, heals, forced
          rebuilds) over the trailing ``churn_window_s``.
        - ``wire_eff_MBps``: last measured effective wire throughput of a
          ring op (``None`` until :meth:`observe_op_stats` has seen one).
        - ``tier_eff_MBps``: per-tier effective throughput of the last
          hierarchical op ({"host"/"intra"/"inter": MB/s}; ``None`` until
          one has been observed) — what prices hier/shm strategy
          candidates on their bottleneck tier.
        - ``heal``: the last streamed-heal cost breakdown (the transport's
          ``last_fetch_stats``: path/wire/bytes/fetch_s/h2d_s), plus the
          ``heal_fetch``/``heal_apply`` timer snapshots — ``None`` when
          this replica never healed.
        - ``opt_state_bytes``: resident optimizer-state bytes as last
          reported by the training strategy via
          :meth:`report_opt_state_bytes` (ShardedDDP reports its ~1/W
          shard each reshard; ``None`` until a strategy reports) — the
          policy engine's memory term for pricing ``ddp_sharded``.

        Also the payload pushed to the lighthouse ``status.json`` member
        view (see :meth:`push_status`)."""
        heal: Optional[Dict[str, Any]] = None
        fetch_stats = getattr(
            self._checkpoint_transport, "last_fetch_stats", None
        )
        timers = self._metrics.snapshot()["timers_s"]
        if fetch_stats is not None or "heal_fetch" in timers:
            heal = {
                "last_fetch": fetch_stats,
                "fetch_s": timers.get("heal_fetch"),
                "apply_s": timers.get("heal_apply"),
            }
        return {
            "churn_per_min": round(
                self._metrics.rate_per_min("churn", churn_window_s), 6
            ),
            "wire_eff_MBps": self._last_wire_eff_mbps,
            "tier_eff_MBps": dict(self._last_tier_mbps) or None,
            "heal": heal,
            "opt_state_bytes": getattr(self, "_opt_state_bytes", None),
        }

    def report_opt_state_bytes(self, nbytes: Optional[int]) -> None:
        """Records the strategy's resident optimizer-state footprint for
        :meth:`signals`. ShardedDDP calls this on every (re)shard with
        its ~1/W shard's bytes; an unsharded strategy may report its
        full state. ``None`` clears the signal."""
        self._opt_state_bytes = None if nbytes is None else int(nbytes)

    def push_status(self, extra: Optional[Dict[str, Any]] = None) -> None:
        """Publishes the current :meth:`signals` digest (plus step/commit
        progress and any ``extra`` — e.g. the policy engine's active
        strategy) to the lighthouse: it rides the native manager's lease
        renewals and appears under this member in ``/status.json``. No-op
        on ranks that don't host the native manager (group rank != 0) —
        the group's digest is rank 0's."""
        if self._manager is None:
            return
        counters = self._metrics.snapshot()["counters"]
        status: Dict[str, Any] = {
            "step": self._step,
            "commits": counters.get("commits", 0),
            "aborts": counters.get("aborts", 0),
            "heals": counters.get("heals", 0),
            **self.signals(),
        }
        if extra:
            status.update(extra)
        try:
            self._manager.set_status(status)
        except Exception as e:  # noqa: BLE001 - observability must not kill
            self._logger.warn(f"status push failed (ignored): {e}")

    # -- introspection --

    def checkpoint_transport(self) -> CheckpointTransport[Dict[str, T]]:
        """The live-recovery transport this manager heals through.
        Benches and diagnostics read its ``last_fetch_stats`` (streamed
        heal path/wire/fetch/h2d breakdown) after a heal; swapping the
        transport itself happens at construction."""
        return self._checkpoint_transport

    def metrics(self) -> "Metrics":
        """Step-level counters and timers (commits/aborts/heals/errors,
        quorum / reconfigure / allreduce / commit-vote latencies). Closes
        the observability gap the reference leaves at batches_committed
        (reference manager.py:642-653); ``metrics().snapshot()`` is
        JSON-able."""
        return self._metrics

    def current_step(self) -> int:
        """Committed step count; skipped steps don't increment it."""
        return self._step

    def replica_id(self) -> str:
        """This group's replica id (stable across restarts when the
        launcher pins it — what the durable tier keys per-member local
        state, e.g. the dataloader position, on)."""
        return self._replica_id

    def add_commit_hook(self, hook: Any) -> None:
        """Registers ``hook(step, quorum_id, committed)`` to fire at every
        ``should_commit`` resolution, after the vote settled (and after
        the step counter advanced on a commit). This is the durable
        tier's capture point: the hook runs on the trainer thread with
        the state dict quiescent — the optimizer has not yet mutated the
        next step — so a snapshot captured here is provably step-pure.
        Hooks must not raise; exceptions are swallowed and logged (a
        failing snapshot never aborts training)."""
        self._commit_hooks.append(hook)

    def set_durable_restore(
        self, fn: Optional[Callable[[], Optional[int]]]
    ) -> None:
        """Registers (or clears) the durable tier's cold-start fallback —
        see the ``durable_restore`` constructor arg.
        ``DurableCheckpointer.__init__`` calls this so the arbitration
        is wired by merely constructing the checkpointer; a trainer that
        still calls ``restore_latest()`` itself before the first quorum
        is unaffected (a nonzero restored step disarms the consult)."""
        self._durable_restore = fn

    def batches_committed(self) -> int:
        """Total batches committed across all replicas and steps."""
        return self._batches_committed

    def num_participants(self) -> int:
        """Replica groups participating in the current step."""
        assert self._quorum_future is not None, "quorum not started"
        self.wait_quorum()
        return self._participating_world_size

    def quorum_id(self) -> int:
        """Id of the current quorum (bumps exactly when membership — and
        therefore the data plane — was reconfigured). Sharded consumers
        key partition-dependent state on it: the DiLoCo sharded outer
        sync re-shards its outer-optimizer state whenever the id moved
        since the state was built (a join/leave/heal changed the ring, so
        the old shard boundaries no longer tile the cohort). Settles the
        quorum thread first — it is the writer."""
        assert self._quorum_future is not None, "quorum not started"
        self.wait_quorum()
        return self._quorum_id

    def participating_rank(self) -> Optional[int]:
        """This group's rank among participants; None when healing/spare."""
        assert self._quorum_future is not None, "quorum not started"
        self.wait_quorum()
        return self._participating_rank

    def is_participating(self) -> bool:
        """False while healing or a spare: gradients are zeroed then
        (reference manager.py:693-705)."""
        if self._participating_rank is None:
            return False
        if self._healing:
            assert self._use_async_quorum
            return False
        return True

    def is_healing(self) -> bool:
        """True while this step is recovering state from a peer (the fetched
        checkpoint is applied at the ``should_commit`` safe point). Pipelined
        wrappers read this BEFORE voting to know that gradients dispatched
        earlier in the step were computed from pre-heal weights and must be
        recomputed (torchft_tpu.ddp.PipelinedDDP). Settles the quorum thread
        first — it is the writer."""
        assert self._quorum_future is not None, "quorum not started"
        self.wait_quorum()
        return self._healing


class _ManagerLogger:
    """Prefixes logs with [replica/rank - step N]. Reference manager.py:708-727."""

    def __init__(self, manager: Manager, replica_id: str, rank: int) -> None:
        self._logger = logging.getLogger(f"{__name__}.{replica_id}")
        self._replica_id = replica_id
        self._rank = rank
        self._manager = manager

    def prefix(self) -> str:
        return (
            f"[{self._replica_id}/{self._rank} - step "
            f"{self._manager.current_step()}]"
        )

    def info(self, msg: str) -> None:
        self._logger.info(f"{self.prefix()} {msg}")

    def warn(self, msg: str) -> None:
        self._logger.warning(f"{self.prefix()} {msg}")

    def exception(self, msg: str) -> None:
        self._logger.exception(f"{self.prefix()} {msg}")
