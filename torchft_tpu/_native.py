"""ctypes bindings for the C++ control plane (native/).

Plays the role of the reference's pyo3 module ``torchft.torchft``
(reference src/lib.rs): exposes ``Lighthouse``, ``Manager`` (the native
per-replica-group server), ``ManagerClient``, ``QuorumResult`` and the
rendezvous ``Store``/``StoreClient``. Timeouts surface as ``TimeoutError``
(matching the DeadlineExceeded/Cancelled mapping in reference
src/lib.rs:321-333); other failures as ``RuntimeError``.

ctypes releases the GIL for the duration of each native call, so blocking
RPCs (quorum long-polls, store waits) never stall other Python threads —
the same property the reference gets from ``py.allow_threads``.
"""

from __future__ import annotations

import atexit
import ctypes
import json
import os
import weakref
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, List, Optional, Union

_LIB_PATH = os.path.join(os.path.dirname(__file__), "_libtorchft.so")


def _load_lib() -> ctypes.CDLL:
    if not os.path.exists(_LIB_PATH):
        raise ImportError(
            f"native library not found at {_LIB_PATH}; build it with "
            f"`make -C native` from the repository root"
        )
    lib = ctypes.CDLL(_LIB_PATH)

    lib.tft_last_error.restype = ctypes.c_char_p
    lib.tft_string_free.argtypes = [ctypes.c_void_p]

    lib.tft_lighthouse_create.restype = ctypes.c_void_p
    lib.tft_lighthouse_create.argtypes = [
        ctypes.c_char_p,
        ctypes.c_uint64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_char_p,  # wal dir ("" = no durability)
        ctypes.c_int64,   # snapshot every N records (0 = default 512)
        ctypes.c_char_p,  # peer root endpoints, comma-separated ("" = none)
        ctypes.c_int,     # standby (1 = start passive)
        ctypes.c_int64,   # takeover ms (0 = default 3000)
    ]
    lib.tft_lighthouse_address.restype = ctypes.c_void_p
    lib.tft_lighthouse_address.argtypes = [ctypes.c_void_p]
    lib.tft_lighthouse_shutdown.argtypes = [ctypes.c_void_p]
    lib.tft_lighthouse_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_lighthouse_active.restype = ctypes.c_int
    lib.tft_lighthouse_active.argtypes = [ctypes.c_void_p]
    lib.tft_lighthouse_root_epoch.restype = ctypes.c_int64
    lib.tft_lighthouse_root_epoch.argtypes = [ctypes.c_void_p]
    lib.tft_lighthouse_heartbeat.restype = ctypes.c_int
    lib.tft_lighthouse_heartbeat.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int64,
    ]
    lib.tft_lighthouse_status_json.restype = ctypes.c_int
    lib.tft_lighthouse_status_json.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]

    # Region lighthouse (the hierarchical tier's middle layer).
    lib.tft_region_create.restype = ctypes.c_void_p
    lib.tft_region_create.argtypes = [
        ctypes.c_char_p,  # bind
        ctypes.c_char_p,  # root addr
        ctypes.c_char_p,  # region id
        ctypes.c_int64,   # digest interval ms
        ctypes.c_int64,   # heartbeat timeout ms (must match the root's)
        ctypes.c_int64,   # connect timeout ms
    ]
    lib.tft_region_address.restype = ctypes.c_void_p
    lib.tft_region_address.argtypes = [ctypes.c_void_p]
    lib.tft_region_shutdown.argtypes = [ctypes.c_void_p]
    lib.tft_region_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_region_status_json.restype = ctypes.c_int
    lib.tft_region_status_json.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_region_quorum_json.restype = ctypes.c_int
    lib.tft_region_quorum_json.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]

    # Persistent lighthouse-protocol client: batched lease renewal /
    # heartbeat / depart over ONE connection (host-level batchers, tests).
    lib.tft_lease_client_create.restype = ctypes.c_void_p
    lib.tft_lease_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.tft_lease_client_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_lease_client_renew.restype = ctypes.c_int
    lib.tft_lease_client_renew.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,  # entries JSON
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),  # quorum_id out
    ]
    lib.tft_lease_client_heartbeat.restype = ctypes.c_int
    lib.tft_lease_client_heartbeat.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
    ]
    lib.tft_lease_client_depart.restype = ctypes.c_int
    lib.tft_lease_client_depart.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
    ]

    lib.tft_manager_create.restype = ctypes.c_void_p
    lib.tft_manager_create.argtypes = [ctypes.c_char_p] * 5 + [
        ctypes.c_uint64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_char_p,  # root fallback addr list ("" = none)
        ctypes.c_int64,   # lease ttl ms (<=0 = lighthouse default)
        ctypes.c_char_p,  # region label ("" = unlabeled)
        ctypes.c_char_p,  # host label ("" = unlabeled)
        ctypes.c_int64,   # region re-probe give-up bound (0 = forever)
    ]
    lib.tft_manager_address.restype = ctypes.c_void_p
    lib.tft_manager_address.argtypes = [ctypes.c_void_p]
    lib.tft_manager_shutdown.argtypes = [ctypes.c_void_p]
    lib.tft_manager_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_manager_using_root.restype = ctypes.c_int
    lib.tft_manager_using_root.argtypes = [ctypes.c_void_p]
    lib.tft_manager_probe_given_up.restype = ctypes.c_int
    lib.tft_manager_probe_given_up.argtypes = [ctypes.c_void_p]
    lib.tft_manager_set_status.restype = ctypes.c_int
    lib.tft_manager_set_status.argtypes = [ctypes.c_void_p, ctypes.c_char_p]

    lib.tft_client_create.restype = ctypes.c_void_p
    lib.tft_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.tft_client_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_client_quorum.restype = ctypes.c_int
    lib.tft_client_quorum.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_client_checkpoint_metadata.restype = ctypes.c_int
    lib.tft_client_checkpoint_metadata.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_client_should_commit.restype = ctypes.c_int
    lib.tft_client_should_commit.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.tft_client_kill.restype = ctypes.c_int
    lib.tft_client_kill.argtypes = [ctypes.c_void_p, ctypes.c_char_p]

    lib.tft_store_create.restype = ctypes.c_void_p
    lib.tft_store_create.argtypes = [ctypes.c_char_p]
    lib.tft_store_address.restype = ctypes.c_void_p
    lib.tft_store_address.argtypes = [ctypes.c_void_p]
    lib.tft_store_port.restype = ctypes.c_int
    lib.tft_store_port.argtypes = [ctypes.c_void_p]
    lib.tft_store_shutdown.argtypes = [ctypes.c_void_p]
    lib.tft_store_destroy.argtypes = [ctypes.c_void_p]

    lib.tft_store_client_create.restype = ctypes.c_void_p
    lib.tft_store_client_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.tft_store_client_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_store_client_set.restype = ctypes.c_int
    lib.tft_store_client_set.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_size_t,
        ctypes.c_int64,
    ]
    lib.tft_store_client_get.restype = ctypes.c_int
    lib.tft_store_client_get.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_size_t),
    ]
    lib.tft_store_client_add.restype = ctypes.c_int
    lib.tft_store_client_add.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
    ]

    lib.tft_quorum_compute.restype = ctypes.c_int
    lib.tft_quorum_compute.argtypes = [
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_compute_quorum_results.restype = ctypes.c_int
    lib.tft_compute_quorum_results.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    # Pure-function entry points of the lease/digest protocol (the
    # flat-vs-hierarchical equivalence property suite drives these).
    lib.tft_quorum_step.restype = ctypes.c_int
    lib.tft_quorum_step.argtypes = [
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_lease_apply.restype = ctypes.c_int
    lib.tft_lease_apply.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_depart_apply.restype = ctypes.c_int
    lib.tft_depart_apply.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_digest_make.restype = ctypes.c_int
    lib.tft_digest_make.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_digest_apply.restype = ctypes.c_int
    lib.tft_digest_apply.argtypes = [
        ctypes.c_char_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    # Write-ahead quorum log (pure entry points: the kill-at-every-record
    # property suites drive the exact encoder/decoder the live root runs).
    lib.tft_wal_open.restype = ctypes.c_void_p
    lib.tft_wal_open.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.tft_wal_close.argtypes = [ctypes.c_void_p]
    lib.tft_wal_log_lease.restype = ctypes.c_int
    lib.tft_wal_log_lease.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,  # post-apply member slices JSON
        ctypes.c_int64,   # unix ms stamp
    ]
    lib.tft_wal_log_depart.restype = ctypes.c_int
    lib.tft_wal_log_depart.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.tft_wal_log_quorum.restype = ctypes.c_int
    lib.tft_wal_log_quorum.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,  # quorum JSON
        ctypes.c_int64,   # quorum gen
        ctypes.c_int64,   # root epoch
    ]
    lib.tft_wal_log_epoch.restype = ctypes.c_int
    lib.tft_wal_log_epoch.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.tft_wal_snapshot.restype = ctypes.c_int
    lib.tft_wal_snapshot.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,  # lighthouse state JSON (monotonic times)
        ctypes.c_int64,   # quorum gen
        ctypes.c_int64,   # root epoch
        ctypes.c_int64,   # mono now
        ctypes.c_int64,   # unix now
    ]
    lib.tft_wal_recover.restype = ctypes.c_int
    lib.tft_wal_recover.argtypes = [
        ctypes.c_char_p,
        ctypes.c_int64,   # mono now
        ctypes.c_int64,   # unix now
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_backoff_ms.restype = ctypes.c_int64
    lib.tft_backoff_ms.argtypes = [
        ctypes.c_int,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_uint64,
    ]
    lib.tft_jittered_interval_ms.restype = ctypes.c_int64
    lib.tft_jittered_interval_ms.argtypes = [
        ctypes.c_int64,
        ctypes.c_uint64,
        ctypes.c_uint64,
    ]

    # HostCollectives (the striped TCP ring; consumed by
    # torchft_tpu.collectives.HostCollectives).
    lib.tft_hc_create.restype = ctypes.c_void_p
    lib.tft_hc_destroy.argtypes = [ctypes.c_void_p]
    lib.tft_hc_configure.restype = ctypes.c_int
    lib.tft_hc_configure.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,  # stripes: parallel ring connections per neighbor
    ]
    # Two-tier (topology-aware) configure + ops: a region map compiles
    # into intra-region + inter-region (leader) rings alongside the flat
    # one (consumed by torchft_tpu.collectives.HostCollectives).
    lib.tft_hc_configure_hier.restype = ctypes.c_int
    lib.tft_hc_configure_hier.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int64,  # stripes (flat + intra tiers)
        ctypes.c_int64,  # stripes_inter (<=0 = stripes)
        ctypes.c_char_p,  # regions JSON array (one label per rank; "" = flat)
        ctypes.c_char_p,  # hosts JSON array (one label per rank; "" = none)
    ]
    lib.tft_hc_hier_capable.restype = ctypes.c_int64
    lib.tft_hc_hier_capable.argtypes = [ctypes.c_void_p]
    # Host-tier transport of the last configure: 0 none, 1 loopback TCP
    # (TORCHFT_HC_SHM=0), 2 shared-memory rings.
    lib.tft_hc_host_tier_transport.restype = ctypes.c_int64
    lib.tft_hc_host_tier_transport.argtypes = [ctypes.c_void_p]
    lib.tft_hc_release.restype = ctypes.c_int
    lib.tft_hc_release.argtypes = [ctypes.c_void_p]
    lib.tft_hc_allreduce_hier.restype = ctypes.c_int
    lib.tft_hc_allreduce_hier.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,    # inter-hop wire: 0 native, 1 bf16, 2 q8
        ctypes.c_int64,
    ]
    lib.tft_hc_last_hier_json.restype = ctypes.c_int
    lib.tft_hc_last_hier_json.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    lib.tft_hc_allreduce.restype = ctypes.c_int
    lib.tft_hc_allreduce.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int64,
    ]
    lib.tft_hc_allreduce_q8.restype = ctypes.c_int
    lib.tft_hc_allreduce_q8.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_int64,
    ]
    lib.tft_hc_allgather.restype = ctypes.c_int
    lib.tft_hc_allgather.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_int64,
    ]
    # Sharded (split) collectives: the two phases of the ring allreduce as
    # first-class ops, plus the shard-layout query (consumed by
    # torchft_tpu.collectives for the sharded outer sync).
    lib.tft_hc_reduce_scatter.restype = ctypes.c_int
    lib.tft_hc_reduce_scatter.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_void_p,  # shard_out
        ctypes.c_int64,   # layout_stripes (<=0: auto from payload bytes)
        ctypes.c_int64,
    ]
    lib.tft_hc_reduce_scatter_q8.restype = ctypes.c_int
    lib.tft_hc_reduce_scatter_q8.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_void_p,  # shard_out
        ctypes.c_int,     # grid_shard: reproduce fused q8 bits exactly
        ctypes.c_int64,   # layout_stripes
        ctypes.c_int64,
    ]
    lib.tft_hc_allgather_into.restype = ctypes.c_int
    lib.tft_hc_allgather_into.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,  # shard (this rank's)
        ctypes.c_void_p,  # full output buffer
        ctypes.c_size_t,
        ctypes.c_int,
        ctypes.c_int64,   # layout_stripes
        ctypes.c_int64,
    ]
    lib.tft_hc_shard_ranges.restype = ctypes.c_int64
    lib.tft_hc_shard_ranges.argtypes = [
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_size_t,
        ctypes.c_int64,   # rank
        ctypes.c_int64,   # layout_stripes
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    lib.tft_hc_broadcast.restype = ctypes.c_int
    lib.tft_hc_broadcast.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p,
        ctypes.c_size_t,
        ctypes.c_int64,
        ctypes.c_int64,
    ]
    lib.tft_hc_barrier.restype = ctypes.c_int
    lib.tft_hc_barrier.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.tft_hc_abort.argtypes = [ctypes.c_void_p]
    lib.tft_hc_set_wire_crc.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tft_hc_wire_crc.restype = ctypes.c_int
    lib.tft_hc_wire_crc.argtypes = [ctypes.c_void_p]
    lib.tft_hc_world_size.restype = ctypes.c_int64
    lib.tft_hc_world_size.argtypes = [ctypes.c_void_p]
    lib.tft_hc_stripes.restype = ctypes.c_int64
    lib.tft_hc_stripes.argtypes = [ctypes.c_void_p]
    lib.tft_hc_last_stripe_ns.restype = ctypes.c_int64
    lib.tft_hc_last_stripe_ns.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    # Persistent comm plans: a precompiled per-signature gradient sync
    # executed each step as ONE GIL-released native call (consumed by
    # torchft_tpu.collectives.HostCollectives.plan_allreduce).
    lib.tft_plan_build.restype = ctypes.c_int64
    lib.tft_plan_build.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),  # per-leaf flat element counts
        ctypes.POINTER(ctypes.c_int32),  # per-leaf native dtype codes
        ctypes.c_int64,                  # leaf count
        ctypes.c_int,                    # wire: 0 native, 1 bf16, 2 q8, 3 q8+EF
    ]
    lib.tft_plan_execute.restype = ctypes.c_int
    lib.tft_plan_execute.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,                  # plan id
        ctypes.POINTER(ctypes.c_void_p),  # leaf input pointers
        ctypes.POINTER(ctypes.c_void_p),  # leaf output pointers
        ctypes.c_double,                 # divisor
        ctypes.c_int,                    # has_divisor
        ctypes.c_int64,
    ]
    # Pre-packed plans: the device-side Pallas pack already emitted the
    # wire encoding, so execute takes per-GROUP payload (+ q8 scale
    # sidecar) pointers and the native pack stage is a straight decode.
    lib.tft_plan_build_pre.restype = ctypes.c_int64
    lib.tft_plan_build_pre.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),  # per-leaf flat element counts
        ctypes.POINTER(ctypes.c_int32),  # per-leaf native dtype codes
        ctypes.c_int64,                  # leaf count
        ctypes.c_int,                    # wire: 0 native, 1 bf16, 2 q8, 3 q8+EF
    ]
    # Hierarchical plans: the two-tier schedule behind the one-call
    # execute (wire applies at the leader's inter hop only).
    lib.tft_plan_build_hier.restype = ctypes.c_int64
    lib.tft_plan_build_hier.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),  # per-leaf flat element counts
        ctypes.POINTER(ctypes.c_int32),  # per-leaf native dtype codes
        ctypes.c_int64,                  # leaf count
        ctypes.c_int,                    # wire: 0 native, 1 bf16, 2 q8, 3 q8+EF
    ]
    lib.tft_plan_execute_pre.restype = ctypes.c_int
    lib.tft_plan_execute_pre.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,                  # plan id
        ctypes.POINTER(ctypes.c_void_p),  # per-group wire payload pointers
        ctypes.POINTER(ctypes.c_void_p),  # per-group scale sidecars (q8)
        ctypes.POINTER(ctypes.c_void_p),  # leaf output pointers
        ctypes.c_double,                 # divisor
        ctypes.c_int,                    # has_divisor
        ctypes.c_int64,
    ]
    # Sharded plans (per-step ZeRO): the fused schedule split at the
    # reduce-scatter boundary — a grad rs leg, a shard-local update in
    # the caller, and a param allgather leg (consumed by
    # HostCollectives.plan_reduce_scatter / plan_allgather_into).
    lib.tft_plan_build_sharded.restype = ctypes.c_int64
    lib.tft_plan_build_sharded.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int64),  # per-leaf flat element counts
        ctypes.POINTER(ctypes.c_int32),  # per-leaf native dtype codes (f32)
        ctypes.c_int64,                  # leaf count
        ctypes.c_int,                    # rs wire: 0 native, 1 bf16, 2 q8
        ctypes.c_int,                    # ag wire: 0 native, 1 bf16
    ]
    lib.tft_plan_execute_rs.restype = ctypes.c_int
    lib.tft_plan_execute_rs.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,                  # plan id
        ctypes.POINTER(ctypes.c_void_p),  # leaf input pointers
        ctypes.POINTER(ctypes.c_float),  # shard output (f32)
        ctypes.c_double,                 # divisor
        ctypes.c_int,                    # has_divisor
        ctypes.c_int64,
    ]
    lib.tft_plan_execute_ag.restype = ctypes.c_int
    lib.tft_plan_execute_ag.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,                  # plan id
        ctypes.POINTER(ctypes.c_float),  # updated shard input (f32)
        ctypes.POINTER(ctypes.c_void_p),  # leaf output pointers
        ctypes.c_int64,
    ]
    lib.tft_plan_sharded_meta.restype = ctypes.c_int
    lib.tft_plan_sharded_meta.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,                  # plan id
        ctypes.POINTER(ctypes.c_int64),  # out[3]: shard count, eff, total
    ]
    lib.tft_plan_free.restype = ctypes.c_int
    lib.tft_plan_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.tft_plan_reset_feedback.restype = ctypes.c_int
    lib.tft_plan_reset_feedback.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.tft_plan_stats_json.restype = ctypes.c_int
    lib.tft_plan_stats_json.argtypes = [
        ctypes.c_void_p,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p),
    ]
    # Shared-memory segments (the isolated accelerator data plane's
    # staging buffers; consumed by torchft_tpu.isolated_xla).
    lib.tft_shm_create.restype = ctypes.c_void_p
    lib.tft_shm_create.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.tft_shm_attach.restype = ctypes.c_void_p
    lib.tft_shm_attach.argtypes = [ctypes.c_char_p, ctypes.c_int64]
    lib.tft_shm_data.restype = ctypes.c_void_p
    lib.tft_shm_data.argtypes = [ctypes.c_void_p]
    lib.tft_shm_size.restype = ctypes.c_int64
    lib.tft_shm_size.argtypes = [ctypes.c_void_p]
    lib.tft_shm_close.argtypes = [ctypes.c_void_p]
    lib.tft_shm_unlink.restype = ctypes.c_int
    lib.tft_shm_unlink.argtypes = [ctypes.c_char_p]
    lib.tft_shm_live_count.restype = ctypes.c_int64
    lib.tft_shm_layout_json.restype = ctypes.c_int
    lib.tft_shm_layout_json.argtypes = [
        ctypes.POINTER(ctypes.c_int64),  # per-leaf flat element counts
        ctypes.POINTER(ctypes.c_int32),  # per-leaf native dtype codes
        ctypes.c_int64,                  # leaf count
        ctypes.c_int,                    # wire: 0 native, 1 bf16, 2 q8, 3 q8+EF
        ctypes.POINTER(ctypes.c_void_p),
    ]
    # Chaos plane: process-global seeded fault injection (see
    # native/src/fault.h and torchft_tpu.chaos).
    lib.tft_fault_arm.restype = ctypes.c_int
    lib.tft_fault_arm.argtypes = [ctypes.c_char_p]  # plan JSON
    lib.tft_fault_disarm.argtypes = []
    lib.tft_fault_armed.restype = ctypes.c_int
    lib.tft_fault_armed.argtypes = []
    lib.tft_fault_stats_json.restype = ctypes.c_int
    lib.tft_fault_stats_json.argtypes = [ctypes.POINTER(ctypes.c_void_p)]
    # CRC32C (Castagnoli) — the ring frame / heal range checksum.
    lib.tft_crc32c.restype = ctypes.c_uint32
    lib.tft_crc32c.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.tft_crc32c_update.restype = ctypes.c_uint32
    lib.tft_crc32c_update.argtypes = [
        ctypes.c_uint32,
        ctypes.c_char_p,
        ctypes.c_uint64,
    ]
    return lib


_lib = _load_lib()

_OK = 0
_TIMEOUT = 1


class WireCorruption(RuntimeError):
    """A CRC-guarded wire frame failed its integrity check (ring/stripe
    payload frame or heal stream range). The one failure mode the commit
    vote cannot catch on its own — a flipped bit that decoded cleanly
    would commit wrong gradients everywhere — so it gets a TYPE: callers
    and the chaos harness count detections, while the error itself rides
    the ordinary managed-collective latch -> vote-discard -> reconfigure
    machinery (it subclasses RuntimeError like every native failure)."""


# The native WireCorruptionError's message prefix — the cross-language
# contract _check keys the typed re-raise on.
_WIRE_CORRUPTION_PREFIX = "wire corruption:"


def _check(rc: int) -> None:
    if rc == _OK:
        return
    msg = _lib.tft_last_error().decode("utf-8", "replace")
    if rc == _TIMEOUT:
        raise TimeoutError(msg)
    if msg.startswith(_WIRE_CORRUPTION_PREFIX):
        raise WireCorruption(msg)
    raise RuntimeError(msg)


def _take_string(ptr: ctypes.c_void_p) -> str:
    try:
        return ctypes.cast(ptr, ctypes.c_char_p).value.decode("utf-8")
    finally:
        _lib.tft_string_free(ptr)


def _ms(t: Union[timedelta, float, int]) -> int:
    """Convert a timedelta (or seconds) to integer milliseconds."""
    if isinstance(t, timedelta):
        return int(t.total_seconds() * 1000)
    return int(t * 1000)


# Native servers own background threads; if the interpreter exits while they
# are still running, libc teardown races those threads and can segfault. Every
# server registers here and is shut down at exit (CPython does not guarantee
# __del__ for module-global objects).
_live_servers: "weakref.WeakSet" = weakref.WeakSet()


@atexit.register
def _shutdown_live_servers() -> None:
    for server in list(_live_servers):
        try:
            server.shutdown()
        except Exception:
            pass


@dataclass
class QuorumResult:
    """Per-rank quorum outcome. Reference: src/lib.rs:199-232."""

    quorum_id: int = 0
    replica_rank: int = 0
    replica_world_size: int = 0
    recover_src_manager_address: str = ""
    recover_src_rank: Optional[int] = None
    recover_dst_ranks: List[int] = field(default_factory=list)
    store_address: str = ""
    max_step: int = 0
    max_rank: Optional[int] = None
    max_world_size: int = 0
    heal: bool = False
    # Region label of EVERY participant, indexed by replica rank (empty
    # strings for unlabeled members; empty list from pre-region servers).
    # What Manager.configure hands the data plane for the two-tier
    # collective schedule.
    replica_regions: List[str] = field(default_factory=list)
    # Host label of EVERY participant, same indexing/emptiness contract:
    # (region, host) groups are what the data plane compiles into the
    # shared-memory intra-host ring tier.
    replica_hosts: List[str] = field(default_factory=list)

    @classmethod
    def _from_json(cls, raw: str) -> "QuorumResult":
        d = json.loads(raw)
        return cls(
            quorum_id=d["quorum_id"],
            replica_rank=d["replica_rank"],
            replica_world_size=d["replica_world_size"],
            recover_src_manager_address=d["recover_src_manager_address"],
            recover_src_rank=d.get("recover_src_rank"),
            recover_dst_ranks=list(d.get("recover_dst_ranks", [])),
            store_address=d["store_address"],
            max_step=d["max_step"],
            max_rank=d.get("max_rank"),
            max_world_size=d["max_world_size"],
            heal=d["heal"],
            replica_regions=list(d.get("replica_regions", [])),
            replica_hosts=list(d.get("replica_hosts", [])),
        )


class Lighthouse:
    """In-process global quorum server (C++). Reference: src/lib.rs:266-319.

    Durable-control-plane knobs (all optional; see docs/OPERATIONS.md
    "control-plane durability & failover"): ``wal_dir`` enables the
    write-ahead quorum log + snapshot (``TORCHFT_LH_WAL_DIR``) so a
    restart replays to the exact pre-crash quorum_id watermark;
    ``peers`` is the comma-separated list of the OTHER roots of this
    root's failover set; ``standby=True`` starts passive (tails the
    active peer, takes over after ``takeover_ms`` of sync starvation)."""

    def __init__(
        self,
        bind: str = "[::]:0",
        min_replicas: int = 1,
        join_timeout_ms: int = 100,
        quorum_tick_ms: int = 100,
        heartbeat_timeout_ms: int = 5000,
        wal_dir: str = "",
        snapshot_every: int = 0,
        peers: str = "",
        standby: bool = False,
        takeover_ms: int = 0,
    ) -> None:
        self._handle = _lib.tft_lighthouse_create(
            bind.encode(),
            min_replicas,
            join_timeout_ms,
            quorum_tick_ms,
            heartbeat_timeout_ms,
            wal_dir.encode(),
            snapshot_every,
            peers.encode(),
            1 if standby else 0,
            takeover_ms,
        )
        if not self._handle:
            _check(2)
        _live_servers.add(self)

    def address(self) -> str:
        return _take_string(_lib.tft_lighthouse_address(self._handle))

    def active(self) -> bool:
        """True while this root SERVES (vs a passive warm standby that
        rejects the protocol with UNAVAILABLE so clients rotate)."""
        return bool(_lib.tft_lighthouse_active(self._handle))

    def root_epoch(self) -> int:
        """Monotonic root epoch: bumped at every active claim (startup or
        standby takeover) and fenced through the WAL when one is
        configured. 0 = never active."""
        return int(_lib.tft_lighthouse_root_epoch(self._handle))

    def status_json(self) -> dict:
        """Machine-readable status: members + lease deadlines, last quorum,
        tier role (``flat``/``root``/``standby``), tick cost counters,
        region digests, and the durability stamps (``root_epoch``,
        ``wal_replayed``, ``wal`` replay/append counters) that tell a
        COLD root from an AMNESIAC one. Served over HTTP as
        ``GET /status.json`` on the same port."""
        out = ctypes.c_void_p()
        _check(_lib.tft_lighthouse_status_json(self._handle, ctypes.byref(out)))
        return json.loads(_take_string(out))

    def shutdown(self) -> None:
        if self._handle:
            _lib.tft_lighthouse_shutdown(self._handle)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_lighthouse_destroy(handle)

    def __enter__(self) -> "Lighthouse":
        return self

    def __exit__(self, *exc: object) -> None:
        self.shutdown()


class RegionLighthouse:
    """In-process region lighthouse: the middle tier of the hierarchical
    quorum service. Speaks the manager-facing lighthouse protocol locally,
    pushes membership digests to the root, long-polls the global quorum back
    out. See native/src/region.h for the equivalence + failover contract."""

    def __init__(
        self,
        root_addr: str,
        region_id: str,
        bind: str = "[::]:0",
        digest_interval_ms: int = 100,
        heartbeat_timeout_ms: int = 5000,
        connect_timeout_ms: int = 10000,
    ) -> None:
        self._handle = _lib.tft_region_create(
            bind.encode(),
            root_addr.encode(),
            region_id.encode(),
            digest_interval_ms,
            heartbeat_timeout_ms,
            connect_timeout_ms,
        )
        if not self._handle:
            _check(2)
        _live_servers.add(self)

    def address(self) -> str:
        return _take_string(_lib.tft_region_address(self._handle))

    def status_json(self) -> dict:
        out = ctypes.c_void_p()
        _check(_lib.tft_region_status_json(self._handle, ctypes.byref(out)))
        return json.loads(_take_string(out))

    def quorum_json(self) -> dict:
        """The region-side quorum CACHE: the last global quorum pulled
        from the root, served locally with its refresh ``age_ms`` (also
        over HTTP as ``GET /quorum.json``). Read-mostly consumers use
        this instead of long-polling the root — the root sees one
        standing poll per region regardless of reader count, and with
        the root down the cache keeps serving with a growing age."""
        out = ctypes.c_void_p()
        _check(_lib.tft_region_quorum_json(self._handle, ctypes.byref(out)))
        return json.loads(_take_string(out))

    def shutdown(self) -> None:
        if self._handle:
            _lib.tft_region_shutdown(self._handle)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_region_destroy(handle)

    def __enter__(self) -> "RegionLighthouse":
        return self

    def __exit__(self, *exc: object) -> None:
        self.shutdown()


class LeaseClient:
    """Persistent lighthouse-protocol client: batched lease renewals,
    heartbeats and explicit departs over ONE connection. The client surface
    host-level renewal batchers (and the control-plane tests' simulated
    groups) ride; real managers renew through their native server instead."""

    def __init__(
        self, addr: str, connect_timeout: timedelta = timedelta(seconds=10)
    ) -> None:
        self._handle = _lib.tft_lease_client_create(addr.encode(), _ms(connect_timeout))

    def renew(
        self,
        entries: List[dict],
        timeout: timedelta = timedelta(seconds=10),
    ) -> int:
        """Renews a batch of leases; each entry is ``{replica_id, ttl_ms,
        participating, member}``. Returns the service's current quorum_id."""
        out = ctypes.c_int64()
        _check(
            _lib.tft_lease_client_renew(
                self._handle,
                json.dumps(entries).encode(),
                _ms(timeout),
                ctypes.byref(out),
            )
        )
        return out.value

    def heartbeat(
        self, replica_id: str, timeout: timedelta = timedelta(seconds=10)
    ) -> None:
        _check(
            _lib.tft_lease_client_heartbeat(
                self._handle, replica_id.encode(), _ms(timeout)
            )
        )

    def depart(
        self, replica_id: str, timeout: timedelta = timedelta(seconds=10)
    ) -> None:
        _check(
            _lib.tft_lease_client_depart(
                self._handle, replica_id.encode(), _ms(timeout)
            )
        )

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_lease_client_destroy(handle)


def lighthouse_heartbeat(
    addr: str, replica_id: str, timeout: timedelta = timedelta(seconds=5)
) -> None:
    """One-shot heartbeat, used by tests to simulate live non-participants."""
    _check(
        _lib.tft_lighthouse_heartbeat(addr.encode(), replica_id.encode(), _ms(timeout))
    )


class Manager:
    """Native per-replica-group manager server, hosted by group rank 0.

    Reference: src/lib.rs:33-86 (pyo3 ``Manager``).
    """

    def __init__(
        self,
        replica_id: str,
        lighthouse_addr: str,
        hostname: str,
        bind: str,
        store_addr: str,
        world_size: int,
        heartbeat_interval: timedelta = timedelta(milliseconds=100),
        connect_timeout: timedelta = timedelta(seconds=60),
        root_addr: str = "",
        lease_ttl: Optional[timedelta] = None,
        region: str = "",
        host: str = "",
        region_probe_max: int = 0,
    ) -> None:
        """``lighthouse_addr`` is this group's assigned lighthouse (the
        flat/root service, or a REGION lighthouse under a hierarchical
        tier). ``root_addr`` is the optional root fallback: a dead region
        demotes the group to direct-root registration until it returns.
        Both addresses may be COMMA-SEPARATED endpoint lists (a root
        failover set: active root + warm standbys); a failed renewal
        rotates to the next endpoint on the jittered-backoff schedule.
        ``lease_ttl`` (None = lighthouse default) is how long the group
        stays live without a renewal; renewals are jittered and back off
        exponentially while the lighthouse is unreachable.
        ``region_probe_max`` bounds the demoted manager's once-per-TTL
        region re-probes: after that many consecutive failures it stops
        probing (stays on the root) instead of leaking a doomed connect
        attempt per TTL forever; 0 = probe forever. ``region``
        ("" = unlabeled) is the group's topology label: it rides the
        quorum requester into every member's QuorumMember, and the quorum
        result's region map is what the data plane compiles into the
        two-tier collective schedule. ``host`` ("" = unlabeled) rides the
        same way: the quorum's host map is what groups co-hosted members
        into the shared-memory intra-host tier."""
        self._handle = _lib.tft_manager_create(
            replica_id.encode(),
            lighthouse_addr.encode(),
            hostname.encode(),
            bind.encode(),
            store_addr.encode(),
            world_size,
            _ms(heartbeat_interval),
            _ms(connect_timeout),
            root_addr.encode(),
            _ms(lease_ttl) if lease_ttl is not None else 0,
            region.encode(),
            host.encode(),
            region_probe_max,
        )
        if not self._handle:
            _check(2)
        _live_servers.add(self)

    def address(self) -> str:
        return _take_string(_lib.tft_manager_address(self._handle))

    def using_root_fallback(self) -> bool:
        """True while region failover has this group registered directly at
        the root (always False without a ``root_addr``)."""
        return bool(_lib.tft_manager_using_root(self._handle))

    def region_probe_given_up(self) -> bool:
        """True once the bounded region re-probe (``region_probe_max``)
        exhausted its budget: the manager stays on the root and probes no
        more (the region is gone from the topology, not restarting)."""
        return bool(_lib.tft_manager_probe_given_up(self._handle))

    def set_status(self, status: dict) -> None:
        """Publishes a member-health digest that rides every subsequent
        lease renewal to the lighthouse, where it appears under this
        member's entry in ``/status.json`` (``members[i].status``).
        Display-only — the quorum logic never reads it. The lighthouse
        keeps the LAST digest it saw until the member departs or its
        lease is pruned (a renewal without a digest is indistinguishable
        from a pre-status client), so readers should treat the embedded
        step/commit counters as the digest's freshness stamp."""
        _check(
            _lib.tft_manager_set_status(
                self._handle, json.dumps(status).encode()
            )
        )

    def shutdown(self) -> None:
        if self._handle:
            _lib.tft_manager_shutdown(self._handle)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_manager_destroy(handle)


class ManagerClient:
    """Blocking client for a manager server. Reference: src/lib.rs:88-197."""

    def __init__(
        self, addr: str, connect_timeout: timedelta = timedelta(seconds=60)
    ) -> None:
        self._handle = _lib.tft_client_create(addr.encode(), _ms(connect_timeout))

    def quorum(
        self,
        rank: int,
        step: int,
        checkpoint_metadata: str,
        shrink_only: bool = False,
        force_reconfigure: bool = False,
        timeout: timedelta = timedelta(seconds=60),
    ) -> QuorumResult:
        out = ctypes.c_void_p()
        _check(
            _lib.tft_client_quorum(
                self._handle,
                rank,
                step,
                checkpoint_metadata.encode(),
                1 if shrink_only else 0,
                1 if force_reconfigure else 0,
                _ms(timeout),
                ctypes.byref(out),
            )
        )
        return QuorumResult._from_json(_take_string(out))

    def checkpoint_metadata(
        self, rank: int, timeout: timedelta = timedelta(seconds=60)
    ) -> str:
        out = ctypes.c_void_p()
        _check(
            _lib.tft_client_checkpoint_metadata(
                self._handle, rank, _ms(timeout), ctypes.byref(out)
            )
        )
        return _take_string(out)

    def should_commit(
        self,
        rank: int,
        step: int,
        should_commit: bool,
        timeout: timedelta = timedelta(seconds=60),
    ) -> bool:
        out = ctypes.c_int()
        _check(
            _lib.tft_client_should_commit(
                self._handle,
                rank,
                step,
                1 if should_commit else 0,
                _ms(timeout),
                ctypes.byref(out),
            )
        )
        return bool(out.value)

    def kill(self, msg: str = "") -> None:
        _check(_lib.tft_client_kill(self._handle, msg.encode()))

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_client_destroy(handle)


class Store:
    """Rendezvous KV store server (the c10d TCPStore role)."""

    def __init__(self, bind: str = "[::]:0") -> None:
        self._handle = _lib.tft_store_create(bind.encode())
        if not self._handle:
            _check(2)
        _live_servers.add(self)

    def address(self) -> str:
        return _take_string(_lib.tft_store_address(self._handle))

    @property
    def port(self) -> int:
        return _lib.tft_store_port(self._handle)

    def shutdown(self) -> None:
        if self._handle:
            _lib.tft_store_shutdown(self._handle)

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_store_destroy(handle)


class StoreClient:
    """Client for a :class:`Store`; supports per-quorum key prefixes the way
    the reference uses PrefixStore (reference torchft/process_group.py:81-99).
    """

    def __init__(
        self,
        addr: str,
        prefix: str = "",
        connect_timeout: timedelta = timedelta(seconds=60),
    ) -> None:
        self._addr = addr
        self._prefix = prefix
        self._handle = _lib.tft_store_client_create(addr.encode(), _ms(connect_timeout))
        if not self._handle:
            _check(2)

    def _key(self, key: str) -> bytes:
        return (f"{self._prefix}/{key}" if self._prefix else key).encode()

    def set(
        self,
        key: str,
        value: bytes,
        timeout: timedelta = timedelta(seconds=60),
    ) -> None:
        if isinstance(value, str):
            value = value.encode()
        _check(
            _lib.tft_store_client_set(
                self._handle, self._key(key), value, len(value), _ms(timeout)
            )
        )

    def get(self, key: str, timeout: timedelta = timedelta(seconds=60)) -> bytes:
        out = ctypes.c_void_p()
        out_len = ctypes.c_size_t()
        _check(
            _lib.tft_store_client_get(
                self._handle,
                self._key(key),
                _ms(timeout),
                ctypes.byref(out),
                ctypes.byref(out_len),
            )
        )
        try:
            return ctypes.string_at(out, out_len.value)
        finally:
            _lib.tft_string_free(out)

    def add(
        self, key: str, delta: int, timeout: timedelta = timedelta(seconds=60)
    ) -> int:
        out = ctypes.c_int64()
        _check(
            _lib.tft_store_client_add(
                self._handle, self._key(key), delta, _ms(timeout), ctypes.byref(out)
            )
        )
        return out.value

    def __del__(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_store_client_destroy(handle)


def quorum_compute(now_ms: int, state: dict, opt: dict) -> dict:
    """Pure-function entry to the C++ quorum_compute, for unit tests.

    Returns ``{"quorum": [members] | None, "reason": str}``.
    """
    out = ctypes.c_void_p()
    _check(
        _lib.tft_quorum_compute(
            now_ms,
            json.dumps(state).encode(),
            json.dumps(opt).encode(),
            ctypes.byref(out),
        )
    )
    return json.loads(_take_string(out))


def compute_quorum_results(replica_id: str, rank: int, quorum: dict) -> QuorumResult:
    """Pure-function entry to the C++ compute_quorum_results, for unit tests."""
    out = ctypes.c_void_p()
    _check(
        _lib.tft_compute_quorum_results(
            replica_id.encode(), rank, json.dumps(quorum).encode(), ctypes.byref(out)
        )
    )
    return QuorumResult._from_json(_take_string(out))


def quorum_step(now_ms: int, unix_now_ms: int, state: dict, opt: dict) -> dict:
    """One full quorum tick as a pure state transition — the exact C++
    function both the flat lighthouse and the hierarchical root run. Returns
    ``{"state": ..., "quorum": {...}|None, "changed": bool, "reason": str}``.
    The flat-vs-hierarchical equivalence property suite is built on this."""
    out = ctypes.c_void_p()
    _check(
        _lib.tft_quorum_step(
            now_ms,
            unix_now_ms,
            json.dumps(state).encode(),
            json.dumps(opt).encode(),
            ctypes.byref(out),
        )
    )
    return json.loads(_take_string(out))


def lease_apply(state: dict, entries: list, now_ms: int) -> dict:
    """Applies a batched lease renewal to a lighthouse state (pure)."""
    out = ctypes.c_void_p()
    _check(
        _lib.tft_lease_apply(
            json.dumps(state).encode(),
            json.dumps(entries).encode(),
            now_ms,
            ctypes.byref(out),
        )
    )
    return json.loads(_take_string(out))


def depart_apply(state: dict, replica_id: str) -> dict:
    """Applies an explicit depart to a lighthouse state (pure)."""
    out = ctypes.c_void_p()
    _check(
        _lib.tft_depart_apply(
            json.dumps(state).encode(), replica_id.encode(), ctypes.byref(out)
        )
    )
    return json.loads(_take_string(out))


def digest_make(state: dict, now_ms: int, opt: dict) -> list:
    """Region side of the digest protocol: state -> age-relative entries."""
    out = ctypes.c_void_p()
    _check(
        _lib.tft_digest_make(
            json.dumps(state).encode(),
            now_ms,
            json.dumps(opt).encode(),
            ctypes.byref(out),
        )
    )
    return json.loads(_take_string(out))


def digest_apply(state: dict, digest: list, now_ms: int) -> dict:
    """Root side of the digest protocol: merges entries into a state."""
    out = ctypes.c_void_p()
    _check(
        _lib.tft_digest_apply(
            json.dumps(state).encode(),
            json.dumps(digest).encode(),
            now_ms,
            ctypes.byref(out),
        )
    )
    return json.loads(_take_string(out))


class WalLog:
    """A handle on the root's write-ahead quorum log (native DurableLog) —
    the pure-function surface the kill-at-every-record property suites
    and the scripted hierarchy interpreter drive. The LIVE lighthouse
    writes through the identical C++ class; this wrapper exists so tests
    can author byte-exact logs with scripted clocks (pass the scripted
    ``t`` as both mono and unix everywhere — the rebase is then an
    identity)."""

    def __init__(self, dir: str, snapshot_every: int = 0) -> None:
        self._handle = _lib.tft_wal_open(dir.encode(), snapshot_every)
        if not self._handle:
            _check(2)

    def log_lease(self, entries: List[dict], unix_ms: int) -> None:
        """Appends post-apply member slices: each entry is ``{replica_id,
        age_ms, ttl_ms, participating, joined_age_ms, member}`` with ages
        relative to ``unix_ms``."""
        _check(
            _lib.tft_wal_log_lease(
                self._handle, json.dumps(entries).encode(), unix_ms
            )
        )

    def log_depart(self, replica_id: str) -> None:
        _check(_lib.tft_wal_log_depart(self._handle, replica_id.encode()))

    def log_quorum(self, quorum: dict, quorum_gen: int, root_epoch: int) -> None:
        _check(
            _lib.tft_wal_log_quorum(
                self._handle, json.dumps(quorum).encode(), quorum_gen, root_epoch
            )
        )

    def log_epoch(self, epoch: int) -> None:
        _check(_lib.tft_wal_log_epoch(self._handle, epoch))

    def snapshot(
        self,
        state: dict,
        quorum_gen: int,
        root_epoch: int,
        mono_now: int,
        unix_now: int,
    ) -> None:
        """Compacts: writes snapshot.json (atomic) and truncates the log."""
        _check(
            _lib.tft_wal_snapshot(
                self._handle,
                json.dumps(state).encode(),
                quorum_gen,
                root_epoch,
                mono_now,
                unix_now,
            )
        )

    def close(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_wal_close(handle)

    def __del__(self) -> None:
        self.close()


def wal_recover(dir: str, mono_now: int, unix_now: int) -> dict:
    """Replays a WAL directory (snapshot + log): returns ``{"state",
    "quorum_gen", "root_epoch", "replayed", "records_replayed",
    "dropped_tail_bytes"}`` with times re-based onto ``mono_now``. Torn
    or truncated tail records are detected (length/CRC) and dropped,
    never partially applied."""
    out = ctypes.c_void_p()
    _check(_lib.tft_wal_recover(dir.encode(), mono_now, unix_now, ctypes.byref(out)))
    return json.loads(_take_string(out))


def backoff_ms(failures: int, base_ms: int, max_ms: int, seed: int) -> int:
    """Deterministic jittered exponential backoff delay (the manager
    renewal loop's retry schedule)."""
    return _lib.tft_backoff_ms(failures, base_ms, max_ms, seed)


def jittered_interval_ms(interval_ms: int, seed: int, tick: int) -> int:
    """Deterministic jittered renewal interval (herd spreading)."""
    return _lib.tft_jittered_interval_ms(interval_ms, seed, tick)


class ShmSegment:
    """A mapped POSIX shared-memory segment (native lifecycle, see
    native/src/shm.h): the staging buffer the isolated XLA backend feeds
    its disposable child through. The CREATOR owns the name (unlinks it
    on close); attachments never unlink. ``buffer()`` exposes the mapped
    bytes as a writable memoryview — numpy views of it are zero-copy, and
    a child attached to the same name reads the identical pages."""

    def __init__(self, name: str, nbytes: int, create: bool) -> None:
        fn = _lib.tft_shm_create if create else _lib.tft_shm_attach
        self._handle = fn(name.encode(), nbytes)
        if not self._handle:
            _check(2)
        self._nbytes = nbytes
        self.name = name

    @classmethod
    def create(cls, name: str, nbytes: int) -> "ShmSegment":
        return cls(name, nbytes, create=True)

    @classmethod
    def attach(cls, name: str, nbytes: int) -> "ShmSegment":
        return cls(name, nbytes, create=False)

    def buffer(self) -> memoryview:
        """Writable view of the mapped pages (zero-copy; valid until
        ``close``). Callers must drop every numpy view derived from it
        before closing — the mapping is unmapped underneath them."""
        assert self._handle, "segment closed"
        ptr = _lib.tft_shm_data(self._handle)
        return memoryview(
            (ctypes.c_char * self._nbytes).from_address(ptr)
        ).cast("B")

    @property
    def nbytes(self) -> int:
        return self._nbytes

    def close(self) -> None:
        handle, self._handle = getattr(self, "_handle", None), None
        if handle and _lib is not None:
            _lib.tft_shm_close(handle)

    def __del__(self) -> None:
        self.close()


def shm_unlink(name: str) -> None:
    """Removes a segment NAME (idempotent; existing mappings stay valid)
    — the defensive cleanup respawn paths run before re-creating."""
    _check(_lib.tft_shm_unlink(name.encode()))


def shm_live_count() -> int:
    """Live ShmSegment handles in this process — the leak oracle."""
    return _lib.tft_shm_live_count()


def fault_arm(plan: dict) -> None:
    """Arms (replaces) the process-global seeded fault plan — see
    native/src/fault.h for the rule schema and torchft_tpu.chaos for the
    declarative layer that builds these. Stats persist across re-arms;
    :func:`fault_disarm` resets everything."""
    _check(_lib.tft_fault_arm(json.dumps(plan).encode()))


def fault_disarm() -> None:
    """Disarms fault injection and clears the plan + stats. The disarmed
    state is the production state: every native injection point costs one
    relaxed atomic load."""
    _lib.tft_fault_disarm()


def fault_armed() -> bool:
    return bool(_lib.tft_fault_armed())


def fault_stats() -> dict:
    """Cumulative injection counts: ``{"armed", "fired_total",
    "fired": {"seam:kind": n}}`` — the harness's injected-fault ledger."""
    out = ctypes.c_void_p()
    _check(_lib.tft_fault_stats_json(ctypes.byref(out)))
    return json.loads(_take_string(out))


def _crc_arg(
    data: Union[bytes, bytearray, memoryview]
) -> "tuple[Any, int]":
    """One marshalling rule for every CRC entry point: bytes pass
    through; writable buffers (the heal receiver's shared bytearray)
    hash zero-copy via a c_char view; readonly non-bytes views pay one
    copy."""
    if isinstance(data, bytes):
        return data, len(data)
    mv = memoryview(data).cast("B")
    n = mv.nbytes
    if n == 0:
        return b"", 0
    if mv.readonly:
        return mv.tobytes(), n
    return (ctypes.c_char * n).from_buffer(mv), n


def crc32c(data: Union[bytes, bytearray, memoryview]) -> int:
    """CRC32C (Castagnoli) — the exact checksum the native ring frames
    and the heal stream ranges carry."""
    buf, n = _crc_arg(data)
    return int(_lib.tft_crc32c(buf, n))


def crc32c_update(
    state: int, data: Union[bytes, bytearray, memoryview]
) -> int:
    """Incremental CRC32C: seed with ``0xFFFFFFFF``, chain updates, and
    finalize with ``state ^ 0xFFFFFFFF`` — what the heal receiver folds
    into its readinto loop so the verify costs no extra memory pass."""
    buf, n = _crc_arg(data)
    if n == 0:
        return state
    return int(_lib.tft_crc32c_update(state, buf, n))


def crc32c_combine(parts: List[Union[bytes, bytearray, memoryview]]) -> int:
    """CRC32C over the logical concatenation of ``parts`` without
    materializing it (the donor's multi-segment heal ranges)."""
    state = 0xFFFFFFFF
    for part in parts:
        state = crc32c_update(state, part)
    return state ^ 0xFFFFFFFF


def shm_layout(counts: List[int], dtype_codes: List[int], wire: int = 0) -> dict:
    """The CommPlan leaf->offset layout of a flat-packed signature — the
    native authority BOTH sides of the shm boundary lay payloads out with
    (plan_build's first-appearance grouping; 64-byte-aligned group bases).
    Returns ``{"total_bytes", "groups": [{dtype, offset, count}],
    "leaves": [{group, off, count}]}``."""
    n = len(counts)
    out = ctypes.c_void_p()
    _check(
        _lib.tft_shm_layout_json(
            (ctypes.c_int64 * n)(*counts),
            (ctypes.c_int32 * n)(*dtype_codes),
            n,
            wire,
            ctypes.byref(out),
        )
    )
    return json.loads(_take_string(out))
