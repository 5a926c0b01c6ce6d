"""Measures the data-plane overlap pipeline and the striped-connection ring.

Two CPU-loopback-measurable modes (no TPU required), both over a real
2-member host ring with a gradient-sized payload (~10x the flagship bench
model's gradients — where transfer+ring cost is the dominant
fault-tolerance overhead):

  default          chunked-pipeline ON vs OFF at a single connection
                   (d2h DMA / TCP ring / h2d upload overlap) ->
                   OVERLAP_BENCH.json
  --sharded-sweep  full-allreduce outer sync (fused allreduce + redundant
                   full-model outer update on every member) vs the SHARDED
                   outer sync (reduce-scatter -> outer update on the owned
                   1/W shard -> bf16 parameter allgather), per delta wire
                   (f32 and q8) and per stripe count, under the
                   BDP-emulated per-connection cap -> SHARD_BENCH.json.
                   Headline: the f32-delta row, where the sharded schedule
                   strictly cuts wire bytes (RS 4B/elem + AG 2B/elem vs
                   the fused 8B/elem) on top of the ~W× outer-update and
                   h2d-return savings. The q8 rows are reported for
                   completeness: a quantized fused ring already ships ~2
                   wire bytes/elem, so adding a bf16 param allgather can
                   COST wire there — the sharded win in that regime is
                   outer FLOPs/memory, not bytes, and the artifact says
                   which side won honestly. --dryrun shrinks the payload
                   and iterations to a smoke test (no artifact written).
  --sharded-step-sweep
                   PER-STEP ZeRO vs the fused plan-f32 per-step schedule:
                   plan reduce-scatter (q8 grad wire, owner shard full
                   f32) -> optimizer update on the owned ~1/W shard ->
                   bf16 param allgather, vs plan-f32 allreduce + the
                   redundant full-model update — at W=2 and W=3 under the
                   starved-link cap, with both legs' MEASURED wire bytes
                   and each member's resident optimizer bytes (∝ 1/W) in
                   the rows -> merged into SHARD_BENCH.json under
                   "per_step". --dryrun shrinks payload/iters to a smoke
                   test asserting the 1/W scaling (no artifact written).
  --plan-sweep     legacy managed gradient sync vs the persistent native
                   COMM PLAN on a ddp_small-shaped gradient tree (the
                   real model's param signature: ~0.72M params over its
                   actual leaf structure), per wire (f32 / bf16 / q8),
                   under the BDP-emulated per-connection cap ->
                   PLAN_BENCH.json. Legacy per wire = what PipelinedDDP
                   ships today (device-packed allreduce; jitted bf16
                   downcast; jitted int8 quantize+EF feeding the q8
                   ring); planned = ONE native call per step (casts,
                   EF, staging, ring, unpack all below Python). The
                   artifact reports steps/s both ways, the ratio, and
                   the plan path's per-step Python staging-allocation
                   count (zero after warmup is the contract). --dryrun
                   shrinks iterations to a smoke test (no artifact).
  --device-pack-sweep
                   host-pack vs DEVICE-pack comm plans on the ddp_small
                   gradient signature, per wire (f32 / bf16 / q8), under
                   the 12 MB/s BDP cap -> DEVPACK_BENCH.json. Host pack
                   reads every leaf at full f32 width before encoding;
                   device pack runs the Pallas quantize/cast kernels on
                   the accelerator and ships only WIRE bytes across the
                   device link (int8 codes + scale sidecar, or bf16),
                   feeding the prepacked native plan. The artifact
                   reports steps/s both ways and the measured per-step
                   `d2h_bytes` (from pop_op_stats), whose q8:f32 ratio
                   is the tentpole number (~0.25x). On this CPU host the
                   kernels run in interpret mode and there is no real
                   device link — the d2h accounting is exact anyway, and
                   the steps/s comparison is the honest worst case for
                   device pack (it pays the interpret-mode kernels and
                   saves nothing). --dryrun shrinks iterations to a
                   smoke test (no artifact written).
  --hier-sweep     FLAT ring vs the TWO-TIER topology-aware schedule on a
                   W=8 / R=2-regions fleet of real processes, per wire
                   (f32 / bf16 / q8+EF) and per stripe count, with the
                   fast-intra/slow-inter fabric emulated via the existing
                   per-connection pacing (TORCHFT_HC_WIRE_CAP_MBPS caps
                   every flat edge AND the inter tier at 12 MB/s — the
                   topology-oblivious placement where any flat hop may be
                   a DCN hop — while the intra tier rides unpaced
                   loopback) -> HIER_BENCH.json. Both sides ride the comm
                   PLAN path (the AdaptiveDDP plan / plan_hier
                   candidates). The artifact also carries: MEASURED
                   per-leader inter-tier bytes (from the duplex tx
                   accounting, checked against the (R-1)/R * N per-phase
                   prediction), cross-member + cross-iteration
                   bit-identity digests (incl. an uneven 5/3 region
                   split), and a LEADER-KILL probe (SIGKILL a region
                   leader mid-collective: the survivors must error within
                   one op deadline and commit again after reconfiguring
                   to W=7). --dryrun shrinks to W=4 / tiny payload as a
                   CI smoke (no artifact written).
  --stripe-sweep   ring striped over N parallel TCP connections per
                   neighbor, N swept over STRIPE_COUNTS at the pipelined
                   chunk config -> STRIPE_BENCH.json. Two passes:
                   (a) raw loopback — a CONTROL: loopback under this
                   sandbox is CPU-bound (a raw-socket probe here tops out
                   ~700 MB/s at 1 connection and gets SLOWER with more),
                   so stripes can only show parity; (b) per-connection
                   send cap (TORCHFT_HC_WIRE_CAP_MBPS) — emulates the
                   window/BDP-limited paths the striping exists for,
                   where aggregate
                   throughput scaling with N is a real end-to-end property
                   of the transport: serialized stripes, lock contention,
                   or a desynced schedule would all fail it.

Writes the JSON artifact and prints one summary line per config.

Usage: python bench_overlap.py [--stripe-sweep] [--peer <store_addr> <mode>]
"""

import json
import os
import subprocess
import sys
import threading
import time
from datetime import timedelta

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_LEAVES = 64
TOTAL_MB = 256  # ~64M f32 elements ~= 10x the bench model's ~25M params
ITERS = 3


def _tree(fill: float):
    import jax.numpy as jnp

    n = TOTAL_MB * (1 << 20) // 4 // N_LEAVES
    return {f"g{i}": jnp.full((n,), fill, jnp.float32) for i in range(N_LEAVES)}


# (name, pipeline_chunks) at a single ring connection — isolates the
# intra-buffer overlap pipeline from connection striping.
PHASES = (("single_shot", 1), ("pipelined", 8))

# Ring connections per neighbor edge for the stripe sweep; chunk config held
# at the pipelined setting so the sweep isolates the transport.
STRIPE_COUNTS = (1, 2, 4, 8)
STRIPE_CHUNKS = 8
# Per-connection send cap (MB/s) for the BDP-emulated pass: ~4x the
# 12 MB/s starved-link cap of the sharded and plan sweeps.
WIRE_CAP_MBPS = 50


# Sharded-sweep knobs: payload sized so the capped wire leg dominates but a
# full config sweep stays under a couple of minutes end-to-end. The cap is
# a starved 12 MB/s per connection — the stripe sweep's 50 MB/s is
# generous-by-4x on purpose (it probes aggregation headroom);
# this sweep compares two schedules' WIRE BYTES, so the cap models the
# starved path where bytes are the bill.
SHARD_PAYLOAD_MB = 32
SHARD_WIRE_CAP_MBPS = 12
SHARD_STRIPES = (1, 8)
SHARD_WIRES = ("f32", "q8")
SHARD_ITERS = 3
# Nesterov outer step, the standard DiLoCo outer optimizer.
SHARD_OUTER_LR, SHARD_OUTER_MOM = 0.7, 0.9

# Sharded-step-sweep knobs: PER-STEP ZeRO (plan reduce-scatter on the q8
# wire -> optimizer update on the owned 1/W shard -> bf16 param
# allgather) vs the fused plan-f32 per-step schedule (full allreduce +
# redundant full-model update), at W=2 and W=3 under the same
# starved-link cap the plan sweep models. Two stories, both honest: the
# sharded schedule cuts WIRE BYTES only vs plan-f32 (vs a fused q8 ring
# it trades bytes for exactness — SHARD_BENCH's q8 rows); it always
# cuts optimizer update FLOPs and resident state by ~W.
SHSTEP_PAYLOAD_MB = 8
SHSTEP_WIRE_CAP_MBPS = 12
SHSTEP_STRIPES = 4
SHSTEP_CHUNKS = 8
SHSTEP_ITERS = 3
SHSTEP_WORLDS = (2, 3)

# Plan-sweep knobs: the ddp_small gradient signature under the same
# starved-link cap the sharded sweep uses, plus enough iterations that the median
# shakes off scheduler noise.
PLAN_WIRES = ("f32", "bf16", "q8")
PLAN_WIRE_CAP_MBPS = 12
PLAN_STRIPES = 4
PLAN_ITERS = 8

# Hier-sweep knobs: a W=8 fleet split into R=2 regions of 4, every member
# its own PROCESS (the leader-kill probe needs real SIGKILL). The
# per-connection cap models the slow wide-area path (the plan sweep's
# cap); in FLAT mode it paces
# every edge — the topology-oblivious placement where any hop may cross
# the DCN — while the hier schedule's intra tier rides unpaced loopback
# (TORCHFT_HC_WIRE_CAP_INTRA_MBPS unset), which is exactly the
# fast-intra/slow-inter fabric the two-tier schedule exists for.
HIER_WORLD = 8
HIER_REGIONS = 2
HIER_PAYLOAD_MB = 16
HIER_WIRE_CAP_MBPS = 12
HIER_STRIPES = (1, 4)
HIER_ITERS = 3
HIER_WIRES = {"f32": None, "bf16": "bf16", "q8": "q8ef"}
# Leader-kill probe payload: sized so the inter phase runs for seconds
# under the cap — the SIGKILL must land mid-collective, and the op
# timeout bounds how fast the survivors must surface the death.
HIER_KILL_MB = 24
HIER_KILL_TIMEOUT_S = 30


def _hier_world() -> int:
    return 4 if "--dryrun" in sys.argv else HIER_WORLD


def _hier_payload_mb() -> float:
    return 1 if "--dryrun" in sys.argv else HIER_PAYLOAD_MB


def _hier_kill_mb() -> float:
    return 4 if "--dryrun" in sys.argv else HIER_KILL_MB


def _hier_iters() -> int:
    return 1 if "--dryrun" in sys.argv else HIER_ITERS


def _hier_stripes():
    return (1,) if "--dryrun" in sys.argv else HIER_STRIPES


def _hier_regions(world: int):
    half = world // 2
    return ["east"] * half + ["west"] * (world - half)


def _hier_digest(tree) -> str:
    import hashlib

    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(tree)).tobytes()
    ).hexdigest()


def _hier_member(store_addr: str, rank: int, rec=None) -> None:
    """The full hier-sweep protocol for ONE member; rank 0 (the measurer)
    passes `rec` and records timings/accounting. Every rank runs the
    identical op sequence — the ring has no slack for divergence."""
    import signal

    from torchft_tpu._native import StoreClient
    from torchft_tpu.collectives import HostCollectives, ReduceOp

    W = _hier_world()
    regions = _hier_regions(W)
    count = int(_hier_payload_mb() * (1 << 20)) // 4
    data = (np.arange(count, dtype=np.float32) % 1001) * 0.01 + (rank + 1)
    iters = _hier_iters()
    client = StoreClient(store_addr, connect_timeout=timedelta(seconds=60))

    for stripes in _hier_stripes():
        for wname, wire in HIER_WIRES.items():
            cfg = f"{wname}_s{stripes}"
            hc = HostCollectives(
                timeout=timedelta(seconds=600),
                connect_timeout=timedelta(seconds=600),
                stripes=stripes,
            )
            hc.configure(f"{store_addr}/{cfg}", rank, W, regions)

            def flat():
                return hc.plan_allreduce(
                    data.copy(), ReduceOp.SUM, divisor=float(W), wire=wire
                ).wait()

            def hier():
                return hc.plan_allreduce(
                    data.copy(), ReduceOp.SUM, divisor=float(W), wire=wire,
                    hier=True,
                ).wait()

            flat()  # warm: plan builds
            hier()
            hc.pop_op_stats()
            t0 = time.perf_counter()
            for _ in range(iters):
                flat()
            flat_s = (time.perf_counter() - t0) / iters
            hc.pop_op_stats()
            digests = []
            t0 = time.perf_counter()
            for _ in range(iters):
                digests.append(_hier_digest(hier()))
            hier_s = (time.perf_counter() - t0) / iters
            stats = [
                s for s in hc.pop_op_stats()
                if s["op"] == "plan_allreduce" and s.get("hier")
            ]
            client.set(f"hier_digest/{cfg}/{rank}", digests[-1].encode())
            if rec is not None:
                rec[cfg] = {
                    "wire": wname,
                    "stripes": stripes,
                    "flat_s": round(flat_s, 4),
                    "hier_s": round(hier_s, 4),
                    "flat_steps_per_s": round(1.0 / flat_s, 3),
                    "hier_steps_per_s": round(1.0 / hier_s, 3),
                    "hier_speedup": round(flat_s / hier_s, 3),
                    # identical inputs every iteration: equal digests =
                    # deterministic across runs of the reduction tree.
                    # NOT asserted on the q8+EF wire — the leader's
                    # error-feedback carry advances between syncs BY
                    # DESIGN, so consecutive results differ while
                    # cross-member identity (the real contract) holds.
                    "deterministic_across_iters": (
                        len(set(digests)) == 1 if wire != "q8ef" else None
                    ),
                    "tiers": stats[-1]["tiers"],
                    "phase_s": {
                        k: stats[-1][k]
                        for k in ("intra_rs_s", "intra_ag_s",
                                  "inter_ring_s", "intra_bcast_s")
                    },
                }
            hc.shutdown()

    # Uneven region split (5/3): the bit-identity contract must hold off
    # the symmetric case too (the bulk op this time, q8 inter wire).
    half = W // 2 + 1
    uneven = ["east"] * half + ["west"] * (W - half)
    hc = HostCollectives(
        timeout=timedelta(seconds=600),
        connect_timeout=timedelta(seconds=600),
        stripes=_hier_stripes()[-1],
    )
    hc.configure(f"{store_addr}/uneven", rank, W, uneven)
    out = hc.allreduce_hier(data.copy(), ReduceOp.SUM, wire="q8").wait()
    client.set(f"hier_digest/uneven/{rank}", _hier_digest(out).encode())
    hc.shutdown()

    # ---- three-tier (host -> region -> fleet) shm sweep ----
    # 2 hosts x W/2 members, each host one region's whole membership: the
    # schedule is host rings + the capped inter (leader) ring. The SAME
    # layout runs twice — TORCHFT_HC_SHM on (shared-memory rings) vs off
    # (loopback TCP, the honest control) — and the host-tier PHASE walls
    # are the comparison: the shm rings must move the same payload >= 2x
    # faster than loopback TCP pays for its kernel copies + syscalls.
    hosts3 = ["hostA"] * (W // 2) + ["hostB"] * (W - W // 2)
    # Gradient-scale frames: ring buffers sized so a stripe's ring chunk
    # lands without producer/consumer ping-pong (the 1 MiB default is
    # tuned for pipelined chunks; the knob row documents the tradeoff).
    os.environ["TORCHFT_HC_SHM_RING_BYTES"] = str(8 << 20)
    for transport in ("shm", "tcp"):
        os.environ["TORCHFT_HC_SHM"] = "1" if transport == "shm" else "0"
        hc = HostCollectives(
            timeout=timedelta(seconds=600),
            connect_timeout=timedelta(seconds=600),
            stripes=_hier_stripes()[-1],
        )
        hc.configure(f"{store_addr}/shm3_{transport}", rank, W, regions,
                     hosts3)

        def tier3():
            return hc.plan_allreduce(
                data.copy(), ReduceOp.SUM, divisor=float(W), hier=True,
            ).wait()

        tier3()  # warm: plan build + shm rings touched
        hc.pop_op_stats()
        digests = []
        t0 = time.perf_counter()
        for _ in range(max(iters, 5)):
            digests.append(_hier_digest(tier3()))
        wall_s = (time.perf_counter() - t0) / max(iters, 5)
        stats = [
            s for s in hc.pop_op_stats()
            if s["op"] == "plan_allreduce" and s.get("hier")
        ]
        client.set(
            f"hier_digest/shm3_{transport}/{rank}", digests[-1].encode()
        )
        # Every member publishes its least-diluted host-phase sample:
        # min across iterations AND members. A single bench box runs the
        # whole W-process fleet, so any one member's phase wall folds in
        # scheduler preemption of its co-hosted peers — identical for
        # both transports, pure dilution of the ratio. The fleet-wide
        # minimum is the cleanest measurement of the transport itself.
        my_phase = min(
            s["shm_rs_s"] + s["shm_ag_s"] + s["shm_bcast_s"]
            for s in stats
        )
        client.set(
            f"shm3_phase/{transport}/{rank}",
            repr(my_phase).encode(),
        )
        if rec is not None:
            st = stats[-1]
            host_tier = st["tiers"]["host"]
            host_phase_s = min(
                float(
                    client.get(
                        f"shm3_phase/{transport}/{r}",
                        timeout=timedelta(seconds=120),
                    ).decode()
                )
                for r in range(W)
            )
            rec[f"shm3_{transport}"] = {
                "transport": hc.host_tier_transport(),
                "stripes": _hier_stripes()[-1],
                "step_s": round(wall_s, 4),
                "steps_per_s": round(1.0 / wall_s, 3),
                "host_phase_s": round(host_phase_s, 5),
                "host_moved_bytes": host_tier.get("shm_bytes", 0)
                or host_tier.get("tx_bytes", 0),
                "tiers": st["tiers"],
                "deterministic_across_iters": len(set(digests)) == 1,
            }
        hc.shutdown()
    os.environ.pop("TORCHFT_HC_SHM", None)
    os.environ.pop("TORCHFT_HC_SHM_RING_BYTES", None)

    # Uneven HOST layout (a 3-member group, a singleton, a pair inside
    # uneven regions), q8 inter wire: the three-tier bit-identity
    # contract must hold off the symmetric case too.
    half = W // 2 + 1
    uneven_r = ["east"] * half + ["west"] * (W - half)
    uneven_h = []
    for i in range(W):
        grp = "hU0" if i < min(3, half) else (
            "hU1" if i < half else f"hU{2 + (i - half) // 2}"
        )
        uneven_h.append(grp)
    hc = HostCollectives(
        timeout=timedelta(seconds=600),
        connect_timeout=timedelta(seconds=600),
        stripes=_hier_stripes()[-1],
    )
    hc.configure(f"{store_addr}/shm3_uneven", rank, W, uneven_r, uneven_h)
    out = hc.allreduce_hier(data.copy(), ReduceOp.SUM, wire="q8").wait()
    client.set(f"hier_digest/shm3_uneven/{rank}", _hier_digest(out).encode())
    hc.shutdown()

    # Oracle pinning payload: one small seeded op per wire on the 3-tier
    # layout; rank 0 checks every digest against the numpy three-tier
    # oracle (tests/test_hier_collectives.hier_oracle) after the sweep.
    oracle_count = 50_000
    odata = (
        np.arange(oracle_count, dtype=np.float32) % 997
    ) * 0.01 + (rank + 1)
    for wname, wire in (("f32", None), ("bf16", "bf16"), ("q8", "q8")):
        hc = HostCollectives(
            timeout=timedelta(seconds=600),
            connect_timeout=timedelta(seconds=600),
            stripes=1,
        )
        hc.configure(f"{store_addr}/shm3_oracle_{wname}", rank, W, regions,
                     hosts3)
        out = hc.allreduce_hier(odata.copy(), ReduceOp.SUM, wire=wire).wait()
        client.set(
            f"hier_digest/shm3_oracle_{wname}/{rank}",
            _hier_digest(out).encode(),
        )
        hc.shutdown()

    # Leader-kill probe: the WEST leader SIGKILLs itself mid-collective;
    # every survivor must error within ONE op deadline (the configured
    # timeout), not the 600 s rendezvous budget, and the reconfigured
    # W-1 cohort must commit the next op.
    victim = W // 2
    hc = HostCollectives(
        timeout=timedelta(seconds=HIER_KILL_TIMEOUT_S),
        connect_timeout=timedelta(seconds=600),
        stripes=1,
    )
    hc.configure(f"{store_addr}/kill", rank, W, regions)
    big = np.ones(int(_hier_kill_mb() * (1 << 20)) // 4, np.float32)
    if rank == victim:
        # Early enough that the kill lands inside the op's inter phase
        # even at the dryrun payload (the self-kill after the op is the
        # backstop if the op still wins the race).
        threading.Timer(
            0.05, lambda: os.kill(os.getpid(), signal.SIGKILL)
        ).start()
    t0 = time.perf_counter()
    died = None
    try:
        hc.allreduce_hier(big).wait()
    except Exception as e:  # noqa: BLE001
        died = e
    err_s = time.perf_counter() - t0
    if rank == victim:
        # The op can race the timer and complete first; the victim must
        # NEVER reach the recovery rendezvous (it would rejoin under a
        # surviving rank and corrupt the handshake) — die here if the
        # timer hasn't landed yet.
        os.kill(os.getpid(), signal.SIGKILL)
    hc.shutdown()
    if rec is not None:
        rec["leader_kill"] = {
            "victim_rank": victim,
            "payload_MB": _hier_kill_mb(),
            "op_timeout_s": HIER_KILL_TIMEOUT_S,
            "errored": died is not None,
            "error_s": round(err_s, 3),
            "error": str(died)[:120] if died else None,
        }

    new_rank = rank if rank < victim else rank - 1
    new_regions = [g for i, g in enumerate(regions) if i != victim]
    hc = HostCollectives(
        timeout=timedelta(seconds=600),
        connect_timeout=timedelta(seconds=600),
        stripes=1,
    )
    hc.configure(f"{store_addr}/recover", new_rank, W - 1, new_regions)
    out = hc.allreduce_hier(
        np.arange(4096, dtype=np.float32) + new_rank
    ).wait()
    client.set(f"hier_digest/recover/{new_rank}", _hier_digest(out).encode())
    hc.shutdown()
    if rec is not None:
        rec["leader_kill"]["recovered_commit"] = True
        rec["leader_kill"]["surviving_world"] = W - 1

    # Co-hosted kill probe (three-tier): SIGKILL a member that shares a
    # SHARED-MEMORY ring with the measurer mid-collective. The shm tier
    # has no socket FIN — the poisoned-magic / deadline discipline must
    # surface the death within ONE op deadline on every survivor, and
    # the reconfigured cohort must commit the next op.
    Ws = W - 1  # the surviving cohort from the leader-kill probe
    hostsK = ["hK0"] * ((Ws + 1) // 2) + ["hK1"] * (Ws // 2)
    victim2 = 1  # co-hosted with the measurer (rank 0) on hK0
    hc = HostCollectives(
        timeout=timedelta(seconds=HIER_KILL_TIMEOUT_S),
        connect_timeout=timedelta(seconds=600),
        stripes=1,
    )
    hc.configure(f"{store_addr}/cohost_kill", new_rank, Ws, None, hostsK)
    assert hc.hier_capable()
    big = np.ones(int(_hier_kill_mb() * (1 << 20)) // 4, np.float32)
    if new_rank == victim2:
        # Die INSIDE the collective window without ever feeding the shm
        # ring: a SIGKILL closes no socket and poisons no magic, so the
        # co-hosted survivors' only signal is the pid-liveness probe the
        # blocked ring waiter runs each futex slice — the exact path this
        # probe exists to verify. (The shm ring is so fast that a timer
        # racing a live op loses at any payload; a never-arriving peer is
        # the honest mid-collective shape.)
        time.sleep(0.25)
        os.kill(os.getpid(), signal.SIGKILL)
    t0 = time.perf_counter()
    died = None
    try:
        hc.allreduce_hier(big).wait()
    except Exception as e:  # noqa: BLE001
        died = e
    err_s = time.perf_counter() - t0
    hc.shutdown()
    if rec is not None:
        rec["cohost_kill"] = {
            "victim_new_rank": victim2,
            "victim_cohosted_with_measurer": True,
            "host_transport": "shm",
            "payload_MB": _hier_kill_mb(),
            "op_timeout_s": HIER_KILL_TIMEOUT_S,
            "errored": died is not None,
            "error_s": round(err_s, 3),
            "error": str(died)[:120] if died else None,
        }

    rank2 = new_rank if new_rank < victim2 else new_rank - 1
    # The survivor cohort commits its next op THROUGH the shm tier: one
    # shared host label (they really are co-hosted) keeps the
    # hierarchical schedule alive at any surviving world size.
    hostsK2 = ["hR0"] * (Ws - 1)
    hc = HostCollectives(
        timeout=timedelta(seconds=600),
        connect_timeout=timedelta(seconds=600),
        stripes=1,
    )
    hc.configure(f"{store_addr}/cohost_recover", rank2, Ws - 1, None,
                 hostsK2)
    out = hc.allreduce_hier(
        np.arange(4096, dtype=np.float32) + rank2
    ).wait()
    client.set(
        f"hier_digest/cohost_recover/{rank2}", _hier_digest(out).encode()
    )
    hc.shutdown()
    if rec is not None:
        rec["cohost_kill"]["recovered_commit"] = True
        rec["cohost_kill"]["surviving_world"] = Ws - 1


def _plan_iters() -> int:
    return 2 if "--dryrun" in sys.argv else PLAN_ITERS


def _ddp_small_grad_tree(scale: float):
    """A gradient pytree with the ddp_small model's EXACT parameter
    signature (bench.py's link-sized per-step DDP config): the plan's
    win is per-leaf Python overhead, so the leaf structure must be the
    real model's, not a synthetic blob."""
    import jax
    import jax.numpy as jnp

    from bench import DDP_SMALL_CONFIG
    from torchft_tpu.models import TransformerConfig, init_params

    cfg = TransformerConfig(**DDP_SMALL_CONFIG, use_flash=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda l: (jnp.ones(l.shape, jnp.float32) * scale), params
    )


def _plan_sync_legacy(hc, tree, wire, box):
    """What PipelinedDDP ships per step today, per wire: the jitted
    compress (bf16 downcast / int8 quantize with error feedback) plus the
    managed device-packed allreduce."""
    import jax

    from torchft_tpu.collectives import ReduceOp

    if wire == "f32":
        res = hc.allreduce(tree, ReduceOp.SUM, divisor=2.0).wait()
    elif wire == "bf16":
        import jax.numpy as jnp

        if box.get("down") is None:
            box["down"] = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda l: l.astype(jnp.bfloat16), t))
            box["up"] = jax.jit(lambda t: jax.tree_util.tree_map(
                lambda l: l.astype(jnp.float32), t))
        res = box["up"](
            hc.allreduce(box["down"](tree), ReduceOp.SUM, divisor=2.0).wait()
        )
    else:  # q8: jitted EF quantize -> quantized ring
        import jax.numpy as jnp

        from torchft_tpu.quantize import quantize_with_feedback

        if box.get("quant") is None:
            box["quant"] = jax.jit(quantize_with_feedback)
            box["res"] = jax.tree_util.tree_map(
                lambda l: jnp.zeros(l.shape, jnp.float32), tree
            )
        out = box["quant"](tree, box["res"])
        box["res"] = out["res"]
        res = hc.allreduce(
            out["dq"], ReduceOp.SUM, divisor=2.0, wire="q8"
        ).wait()
    jax.block_until_ready(res)
    return res


def _plan_sync_planned(hc, tree, wire, device_pack=False):
    """The same logical sync through the persistent comm plan: one
    native call (pack/cast/EF + striped ring + unpack), no jitted
    compress program, no per-step staging allocation. ``device_pack``
    moves the wire encoding onto the accelerator (Pallas kernels +
    prepacked plan leaves) so only wire-sized bytes cross d2h."""
    from torchft_tpu.collectives import ReduceOp

    plan_wire = {"f32": None, "bf16": "bf16", "q8": "q8ef"}[wire]
    return hc.plan_allreduce(
        tree, ReduceOp.SUM, divisor=2.0, wire=plan_wire,
        device_pack=device_pack,
    ).wait()


def _configs(mode):
    """(prefix, pipeline_chunks, stripes) per phase — IDENTICAL on both ring
    members (the chunk/stripe schedule is part of the wire contract;
    configure() validates it through the store)."""
    if mode in ("stripes", "stripes_capped"):
        pre = "cap_" if mode == "stripes_capped" else ""
        return [(f"{pre}stripe{s}", STRIPE_CHUNKS, s) for s in STRIPE_COUNTS]
    if mode.startswith("sharded"):
        return [(f"{w}_s{s}", STRIPE_CHUNKS, s)
                for w in SHARD_WIRES for s in SHARD_STRIPES]
    if mode.startswith("plan") or mode.startswith("devpack"):
        return [(w, STRIPE_CHUNKS, PLAN_STRIPES) for w in PLAN_WIRES]
    return [(name, chunks, 1) for name, chunks in PHASES]


def _apply_cap(mode) -> None:
    # The cap is pure send pacing (no wire-format effect), read by the
    # native layer at configure(); set it identically in both processes so
    # each DIRECTION of the ring is capped.
    if mode == "stripes_capped":
        os.environ["TORCHFT_HC_WIRE_CAP_MBPS"] = str(WIRE_CAP_MBPS)
    elif mode == "sharded_capped":
        os.environ["TORCHFT_HC_WIRE_CAP_MBPS"] = str(SHARD_WIRE_CAP_MBPS)
    elif mode.startswith("shstep"):
        os.environ["TORCHFT_HC_WIRE_CAP_MBPS"] = str(SHSTEP_WIRE_CAP_MBPS)
    elif mode in ("plan_capped", "devpack_capped"):
        os.environ["TORCHFT_HC_WIRE_CAP_MBPS"] = str(PLAN_WIRE_CAP_MBPS)
    else:
        os.environ.pop("TORCHFT_HC_WIRE_CAP_MBPS", None)


def _shard_payload_mb() -> int:
    return 4 if "--dryrun" in sys.argv else SHARD_PAYLOAD_MB


def _shard_iters() -> int:
    return 1 if "--dryrun" in sys.argv else SHARD_ITERS


def _shard_tree(fill: float):
    import jax.numpy as jnp

    n = _shard_payload_mb() * (1 << 20) // 4 // N_LEAVES
    return {f"g{i}": jnp.full((n,), fill, jnp.float32)
            for i in range(N_LEAVES)}


def _nesterov(avg, mom, params):
    # One elementwise Nesterov outer step in numpy — identical arithmetic
    # on both sides of the comparison, sized by what each side holds (the
    # full model for the fused path, the owned shard for the sharded one).
    mom *= SHARD_OUTER_MOM
    mom += avg
    params -= SHARD_OUTER_LR * (avg + SHARD_OUTER_MOM * mom)


def _sync_full(hc, tree, wire, box):
    """The fused outer sync: full allreduce + a full-model outer update
    (every member runs it redundantly — that redundancy is the point of
    comparison)."""
    import jax

    from torchft_tpu.collectives import ReduceOp

    res = hc.allreduce(
        tree, ReduceOp.SUM, divisor=2.0,
        wire=("q8" if wire == "q8" else None),
    ).wait()
    leaves = jax.tree_util.tree_leaves(res)
    if box.get("m") is None:
        box["m"] = [np.zeros(l.size, np.float32) for l in leaves]
        box["p"] = [np.zeros(l.size, np.float32) for l in leaves]
    for i, leaf in enumerate(leaves):
        _nesterov(np.asarray(leaf).ravel(), box["m"][i], box["p"][i])
    return res


def _sync_sharded(hc, tree, wire, box):
    """The sharded outer sync: reduce-scatter -> outer update on the
    owned 1/W shard -> bf16 parameter allgather."""
    import jax

    from torchft_tpu.collectives import ReduceOp

    sh = hc.reduce_scatter(
        tree, ReduceOp.SUM, divisor=2.0,
        wire=("q8" if wire == "q8" else None),
    ).wait()
    (name,) = list(sh.values)
    avg = np.asarray(sh.values[name])
    if box.get("m") is None or box["m"].size != avg.size:
        box["m"] = np.zeros(avg.size, np.float32)
        box["p"] = np.zeros(avg.size, np.float32)
    _nesterov(avg, box["m"], box["p"])
    out = hc.allgather_into(
        sh.replace_values({name: box["p"].copy()}), wire="bf16"
    ).wait()
    jax.block_until_ready(out)
    return out


def _shstep_payload_mb() -> int:
    return 2 if "--dryrun" in sys.argv else SHSTEP_PAYLOAD_MB


def _shstep_iters() -> int:
    return 1 if "--dryrun" in sys.argv else SHSTEP_ITERS


def _shstep_tree(fill: float):
    import jax.numpy as jnp

    n = _shstep_payload_mb() * (1 << 20) // 4 // N_LEAVES
    return {f"g{i}": jnp.full((n,), fill, jnp.float32)
            for i in range(N_LEAVES)}


def _shstep_fused(hc, tree, world, box):
    """The plan-f32 per-step baseline: fused plan allreduce + redundant
    full-model optimizer update on every member."""
    import jax

    from torchft_tpu.collectives import ReduceOp

    res = hc.plan_allreduce(
        tree, ReduceOp.SUM, divisor=float(world)
    ).wait()
    leaves = jax.tree_util.tree_leaves(res)
    if box.get("m") is None:
        box["m"] = [np.zeros(l.size, np.float32) for l in leaves]
        box["p"] = [np.zeros(l.size, np.float32) for l in leaves]
    for i, leaf in enumerate(leaves):
        _nesterov(np.asarray(leaf).ravel(), box["m"][i], box["p"][i])
    return res


def _shstep_sharded(hc, tree, world, box):
    """The per-step ZeRO schedule: plan reduce-scatter (q8 grad wire,
    owner shard full f32) -> optimizer update on the owned ~1/W shard ->
    bf16 param allgather through the same plan."""
    import jax

    from torchft_tpu.collectives import ReduceOp

    sh = hc.plan_reduce_scatter(
        tree, ReduceOp.SUM, divisor=float(world),
        wire="q8", ag_wire="bf16",
    ).wait()
    avg = np.asarray(sh.values["float32"])
    if box.get("m") is None or box["m"].size != avg.size:
        box["m"] = np.zeros(avg.size, np.float32)
        box["p"] = np.zeros(avg.size, np.float32)
    _nesterov(avg, box["m"], box["p"])
    out = hc.plan_allgather_into(
        sh.replace_values({"float32": box["p"].copy()}), wire="bf16"
    ).wait()
    jax.block_until_ready(out)
    return out


def _shstep_member(hc, tree, world) -> dict:
    """The full sharded-step protocol for one member (measurer and peers
    run the same sequence — the ring has no slack for divergence): warm
    both schedules, then ITERS of each. Returns the member's boxes."""
    fbox, sbox = {}, {}
    _shstep_fused(hc, tree, world, fbox)
    _shstep_sharded(hc, tree, world, sbox)
    hc.pop_op_stats()  # drop warmup timings
    iters = _shstep_iters()
    t0 = time.perf_counter()
    for _ in range(iters):
        _shstep_fused(hc, tree, world, fbox)
    fused_s = (time.perf_counter() - t0) / iters
    fused_stats = hc.pop_op_stats()
    t0 = time.perf_counter()
    for _ in range(iters):
        _shstep_sharded(hc, tree, world, sbox)
    sharded_s = (time.perf_counter() - t0) / iters
    sharded_stats = hc.pop_op_stats()
    return {"fbox": fbox, "sbox": sbox, "fused_s": fused_s,
            "sharded_s": sharded_s, "fused_stats": fused_stats,
            "sharded_stats": sharded_stats}


def peer(store_addr: str, mode: str) -> None:
    if mode.startswith("hier:"):
        # Hier-sweep member: the cap env was inherited from the parent
        # (flat edges + inter tier paced, intra unpaced).
        _hier_member(store_addr, int(mode.split(":", 1)[1]))
        return

    _apply_cap(mode)
    from torchft_tpu.collectives import HostCollectives, ReduceOp

    if mode.startswith("shstep:"):
        # Sharded-step member: rank r of a W-member ring, mirroring the
        # measurer's op sequence exactly.
        _, r, world = mode.split(":")
        r, world = int(r), int(world)
        zeros = _shstep_tree(0.0)
        hc = HostCollectives(timeout=timedelta(seconds=600),
                             connect_timeout=timedelta(seconds=600),
                             pipeline_chunks=SHSTEP_CHUNKS,
                             stripes=SHSTEP_STRIPES)
        hc.configure(f"{store_addr}/shstep{world}", r, world)
        _shstep_member(hc, zeros, world)
        hc.shutdown()
        return

    if mode.startswith("sharded"):
        # Mirror the measuring side's op sequence exactly (the ring has no
        # slack for schedule divergence): warm full+sharded, then ITERS of
        # each, per (wire, stripes) config.
        zeros = _shard_tree(0.0)
        for prefix, chunks, stripes in _configs(mode):
            wire = prefix.split("_")[0]
            hc = HostCollectives(timeout=timedelta(seconds=600),
                                 connect_timeout=timedelta(seconds=600),
                                 pipeline_chunks=chunks,
                                 stripes=stripes)
            hc.configure(f"{store_addr}/{prefix}", 1, 2)
            fbox, sbox = {}, {}
            _sync_full(hc, zeros, wire, fbox)
            _sync_sharded(hc, zeros, wire, sbox)
            for _ in range(_shard_iters()):
                _sync_full(hc, zeros, wire, fbox)
            for _ in range(_shard_iters()):
                _sync_sharded(hc, zeros, wire, sbox)
            hc.shutdown()
        return

    if mode.startswith("devpack"):
        # Mirror the measuring side exactly: warm host-pack + device-pack
        # plans, then iters of each, per wire config. Pack placement is
        # ring-schedule-neutral (prepacked is not in the plan hash), but
        # mirroring keeps the two sides' per-step wall comparable.
        zeros = _ddp_small_grad_tree(0.0)
        for prefix, chunks, stripes in _configs(mode):
            hc = HostCollectives(timeout=timedelta(seconds=600),
                                 connect_timeout=timedelta(seconds=600),
                                 pipeline_chunks=chunks,
                                 stripes=stripes)
            hc.configure(f"{store_addr}/{prefix}", 1, 2)
            _plan_sync_planned(hc, zeros, prefix, device_pack=False)
            _plan_sync_planned(hc, zeros, prefix, device_pack=True)
            for _ in range(_plan_iters()):
                _plan_sync_planned(hc, zeros, prefix, device_pack=False)
            for _ in range(_plan_iters()):
                _plan_sync_planned(hc, zeros, prefix, device_pack=True)
            hc.shutdown()
        return

    if mode.startswith("plan"):
        # Mirror the measuring side's op sequence exactly: warm legacy +
        # warm planned, then iters of each, per wire config.
        zeros = _ddp_small_grad_tree(0.0)
        for prefix, chunks, stripes in _configs(mode):
            hc = HostCollectives(timeout=timedelta(seconds=600),
                                 connect_timeout=timedelta(seconds=600),
                                 pipeline_chunks=chunks,
                                 stripes=stripes)
            hc.configure(f"{store_addr}/{prefix}", 1, 2)
            box = {}
            _plan_sync_legacy(hc, zeros, prefix, box)
            _plan_sync_planned(hc, zeros, prefix)
            for _ in range(_plan_iters()):
                _plan_sync_legacy(hc, zeros, prefix, box)
            for _ in range(_plan_iters()):
                _plan_sync_planned(hc, zeros, prefix)
            hc.shutdown()
        return

    zeros = _tree(0.0)
    for prefix, chunks, stripes in _configs(mode):
        hc = HostCollectives(timeout=timedelta(seconds=600),
                             connect_timeout=timedelta(seconds=600),
                             pipeline_chunks=chunks,
                             stripes=stripes)
        hc.configure(f"{store_addr}/{prefix}", 1, 2)
        for _ in range(1 + ITERS):  # warm + timed
            hc.allreduce(zeros, ReduceOp.SUM).wait()
        hc.shutdown()


def _measure(store, tree, mode):
    """Times every config of `mode` against the already-running peer;
    returns {config_name: {"s", "MBps"}}."""
    import jax

    from torchft_tpu.collectives import HostCollectives, ReduceOp

    _apply_cap(mode)
    out = {}
    for prefix, chunks, stripes in _configs(mode):
        hc = HostCollectives(
            timeout=timedelta(seconds=600),
            connect_timeout=timedelta(seconds=600),
            pipeline_chunks=chunks,
            stripes=stripes,
        )
        hc.configure(f"{store.address()}/{prefix}", 0, 2)
        res = hc.allreduce(tree, ReduceOp.SUM).wait()  # warm (jit pack)
        jax.block_until_ready(res)
        hc.pop_op_stats()  # drop the warm iter's timings
        t0 = time.perf_counter()
        for _ in range(ITERS):
            res = hc.allreduce(tree, ReduceOp.SUM).wait()
            jax.block_until_ready(res)
        dt = (time.perf_counter() - t0) / ITERS
        # Ring-leg transport wall from the op stats: per-chunk slowest-
        # stripe maxima, excluding the d2h/h2d memcpy legs and the
        # peer-skew wait at the op-header sync — the number the stripe
        # count actually moves.  End-to-end `s` stays the headline for
        # the overlap mode, where the pipeline overlap is the story.
        ring_wall = 0.0
        for st in hc.pop_op_stats():
            for b in st.get("buckets", {}).values():
                ring_wall += b.get("stripe_wall") or b["ring"]
        ring_s = ring_wall / ITERS
        out[prefix] = {"s": round(dt, 3), "MBps": round(TOTAL_MB / dt, 1),
                       "ring_s": round(ring_s, 3),
                       "ring_MBps": round(TOTAL_MB / ring_s, 1)}
        label = (f"stripes={stripes}" if mode.startswith("stripes")
                 else f"chunks={chunks}")
        print(f"{prefix} ({label}): {dt:.3f}s {TOTAL_MB / dt:.1f} MB/s "
              f"end-to-end, ring {ring_s:.3f}s {TOTAL_MB / ring_s:.1f} MB/s",
              flush=True)
        hc.shutdown()
    return out


def _measure_sharded(store, tree, mode):
    """Times full-allreduce vs sharded outer sync per (wire, stripes)
    config against the already-running peer; returns
    {config: {"full_s", "sharded_s", "speedup"}}."""
    from torchft_tpu.collectives import HostCollectives

    _apply_cap(mode)
    out = {}
    iters = _shard_iters()
    for prefix, chunks, stripes in _configs(mode):
        wire = prefix.split("_")[0]
        hc = HostCollectives(
            timeout=timedelta(seconds=600),
            connect_timeout=timedelta(seconds=600),
            pipeline_chunks=chunks,
            stripes=stripes,
        )
        hc.configure(f"{store.address()}/{prefix}", 0, 2)
        fbox, sbox = {}, {}
        _sync_full(hc, tree, wire, fbox)      # warm (jit pack + scratch)
        _sync_sharded(hc, tree, wire, sbox)
        t0 = time.perf_counter()
        for _ in range(iters):
            _sync_full(hc, tree, wire, fbox)
        full_s = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            _sync_sharded(hc, tree, wire, sbox)
        sharded_s = (time.perf_counter() - t0) / iters
        out[prefix] = {
            "wire": wire,
            "stripes": stripes,
            "full_s": round(full_s, 3),
            "sharded_s": round(sharded_s, 3),
            "speedup": round(full_s / sharded_s, 3),
        }
        print(
            f"{prefix}: full {full_s:.3f}s, sharded {sharded_s:.3f}s "
            f"-> {full_s / sharded_s:.2f}x",
            flush=True,
        )
        hc.shutdown()
    return out


def _measure_plan(store, tree, mode):
    """Times legacy vs planned gradient sync per wire against the
    already-running peer; returns {wire: row}."""
    from torchft_tpu.collectives import HostCollectives

    _apply_cap(mode)
    out = {}
    iters = _plan_iters()
    for prefix, chunks, stripes in _configs(mode):
        hc = HostCollectives(
            timeout=timedelta(seconds=600),
            connect_timeout=timedelta(seconds=600),
            pipeline_chunks=chunks,
            stripes=stripes,
        )
        hc.configure(f"{store.address()}/{prefix}", 0, 2)
        box = {}
        _plan_sync_legacy(hc, tree, prefix, box)   # warm: jit programs
        _plan_sync_planned(hc, tree, prefix)       # warm: plan build
        hc.pop_op_stats()
        t0 = time.perf_counter()
        for _ in range(iters):
            _plan_sync_legacy(hc, tree, prefix, box)
        legacy_s = (time.perf_counter() - t0) / iters
        hc.pop_op_stats()
        t0 = time.perf_counter()
        for _ in range(iters):
            _plan_sync_planned(hc, tree, prefix)
        planned_s = (time.perf_counter() - t0) / iters
        plan_stats = [
            s for s in hc.pop_op_stats() if s["op"] == "plan_allreduce"
        ]
        staging_allocs = max(
            (s["py_staging_allocs"] for s in plan_stats), default=None
        )
        out[prefix] = {
            "wire": prefix,
            "stripes": stripes,
            "legacy_s": round(legacy_s, 4),
            "planned_s": round(planned_s, 4),
            "legacy_steps_per_s": round(1.0 / legacy_s, 2),
            "planned_steps_per_s": round(1.0 / planned_s, 2),
            "speedup": round(legacy_s / planned_s, 3),
            # The zero-allocation contract, measured not asserted: the
            # max over every timed step's Python staging allocations.
            "py_staging_allocs_after_warmup": staging_allocs,
            "buckets": len(plan_stats[-1]["buckets"]) if plan_stats else 0,
        }
        print(
            f"{prefix}: legacy {legacy_s:.4f}s, planned {planned_s:.4f}s "
            f"-> {legacy_s / planned_s:.2f}x "
            f"(py staging allocs {staging_allocs})",
            flush=True,
        )
        hc.shutdown()
    return out


def _measure_devpack(store, tree, mode):
    """Times host-pack vs device-pack comm plans per wire against the
    already-running peer, and drains pop_op_stats for the measured
    per-step d2h_bytes of each; returns {wire: row}."""
    from torchft_tpu.collectives import HostCollectives

    _apply_cap(mode)
    out = {}
    iters = _plan_iters()
    for prefix, chunks, stripes in _configs(mode):
        hc = HostCollectives(
            timeout=timedelta(seconds=600),
            connect_timeout=timedelta(seconds=600),
            pipeline_chunks=chunks,
            stripes=stripes,
        )
        hc.configure(f"{store.address()}/{prefix}", 0, 2)
        # warm: plan builds + (device side) Pallas kernel jits
        _plan_sync_planned(hc, tree, prefix, device_pack=False)
        _plan_sync_planned(hc, tree, prefix, device_pack=True)
        hc.pop_op_stats()
        t0 = time.perf_counter()
        for _ in range(iters):
            _plan_sync_planned(hc, tree, prefix, device_pack=False)
        host_s = (time.perf_counter() - t0) / iters
        host_stats = [
            s for s in hc.pop_op_stats() if s["op"] == "plan_allreduce"
        ]
        t0 = time.perf_counter()
        for _ in range(iters):
            _plan_sync_planned(hc, tree, prefix, device_pack=True)
        dev_s = (time.perf_counter() - t0) / iters
        dev_stats = [
            s for s in hc.pop_op_stats() if s["op"] == "plan_allreduce"
        ]
        assert all(not s["device_pack"] for s in host_stats)
        assert all(s["device_pack"] for s in dev_stats), (
            "device pack silently fell back to host pack — the Pallas "
            "kernels are unavailable on this host"
        )
        d2h_host = host_stats[-1]["d2h_bytes"]
        d2h_dev = dev_stats[-1]["d2h_bytes"]
        # Capped-link model: a device link as slow as the ring's emulated
        # cap, so a step costs the measured wall PLUS d2h_bytes at the
        # capped rate. Pure arithmetic on measured numbers — the formula
        # is in the artifact, not a hidden sleep. A directly attached chip
        # moves GB/s over d2h; this column models a link that is not one.
        link_s = PLAN_WIRE_CAP_MBPS * 1e6
        host_cap = host_s + d2h_host / link_s
        dev_cap = dev_s + d2h_dev / link_s
        out[prefix] = {
            "wire": prefix,
            "stripes": stripes,
            "host_pack_s": round(host_s, 4),
            "device_pack_s": round(dev_s, 4),
            "host_pack_steps_per_s": round(1.0 / host_s, 2),
            "device_pack_steps_per_s": round(1.0 / dev_s, 2),
            # raw loopback: d2h is a memcpy here, so device pack pays
            # its kernels and banks nothing — the honest control
            "devpack_speedup_raw": round(host_s / dev_s, 3),
            # the tentpole accounting: bytes that crossed the DEVICE link
            "d2h_bytes_host_pack": d2h_host,
            "d2h_bytes_device_pack": d2h_dev,
            "wire_bytes": dev_stats[-1]["wire_bytes"],
            "capped_link_host_pack_s": round(host_cap, 4),
            "capped_link_device_pack_s": round(dev_cap, 4),
            "capped_link_device_pack_steps_per_s": round(1.0 / dev_cap, 2),
            "devpack_speedup_capped_link": round(host_cap / dev_cap, 3),
        }
        print(
            f"{prefix}: host-pack {host_s:.4f}s, device-pack {dev_s:.4f}s "
            f"(raw {host_s / dev_s:.2f}x, capped-link model "
            f"{host_cap / dev_cap:.2f}x); d2h {d2h_host} -> "
            f"{d2h_dev} B/step",
            flush=True,
        )
        hc.shutdown()
    return out


def _run_mode(mode):
    import jax

    from torchft_tpu import Store

    store = Store()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    peer_args = [sys.executable, os.path.abspath(__file__), "--peer",
                 store.address(), mode]
    if "--dryrun" in sys.argv:
        peer_args.append("--dryrun")
    peer_proc = subprocess.Popen(peer_args, env=env)
    if mode.startswith("sharded"):
        tree = _shard_tree(1.0)
    elif mode.startswith("plan") or mode.startswith("devpack"):
        tree = _ddp_small_grad_tree(1.0)
    else:
        tree = _tree(1.0)
    jax.block_until_ready(tree)
    try:
        if mode.startswith("sharded"):
            results = _measure_sharded(store, tree, mode)
        elif mode.startswith("devpack"):
            results = _measure_devpack(store, tree, mode)
        elif mode.startswith("plan"):
            results = _measure_plan(store, tree, mode)
        else:
            results = _measure(store, tree, mode)
        assert peer_proc.wait(timeout=600) == 0
    finally:
        if peer_proc.poll() is None:
            peer_proc.kill()
        store.shutdown()
    return results


def _run_shstep(world: int) -> dict:
    """One W-member sharded-step row: spawns W-1 peer processes, runs the
    measurer in-process, returns the row with measured per-leg bytes."""
    import jax

    from torchft_tpu import Store
    from torchft_tpu.collectives import HostCollectives

    store = Store()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    peers = []
    for r in range(1, world):
        args = [sys.executable, os.path.abspath(__file__), "--peer",
                store.address(), f"shstep:{r}:{world}"]
        if "--dryrun" in sys.argv:
            args.append("--dryrun")
        peers.append(subprocess.Popen(args, env=env))
    _apply_cap("shstep")
    tree = _shstep_tree(1.0)
    jax.block_until_ready(tree)
    total_bytes = sum(
        int(l.size) for l in jax.tree_util.tree_leaves(tree)
    ) * 4
    try:
        hc = HostCollectives(timeout=timedelta(seconds=600),
                             connect_timeout=timedelta(seconds=600),
                             pipeline_chunks=SHSTEP_CHUNKS,
                             stripes=SHSTEP_STRIPES)
        hc.configure(f"{store.address()}/shstep{world}", 0, world)
        m = _shstep_member(hc, tree, world)
        hc.shutdown()
        for p in peers:
            assert p.wait(timeout=900) == 0
    finally:
        for p in peers:
            if p.poll() is None:
                p.kill()
        store.shutdown()
    iters = _shstep_iters()
    fused_wire = sum(
        st.get("wire_bytes") or st["bytes"] for st in m["fused_stats"]
    ) / iters
    rs_stats = [st for st in m["sharded_stats"]
                if st["op"] == "plan_reduce_scatter"]
    ag_stats = [st for st in m["sharded_stats"]
                if st["op"] == "plan_allgather_into"]
    rs_wire = sum(st["wire_bytes"] for st in rs_stats) / iters
    ag_wire = sum(st["wire_bytes"] for st in ag_stats) / iters
    # Optimizer residency: the momentum buffer each member actually
    # holds — the full model for the fused schedule, the owned shard
    # for the sharded one (~1/W).
    opt_fused = sum(mm.nbytes for mm in m["fbox"]["m"])
    opt_sharded = int(m["sbox"]["m"].nbytes)
    row = {
        "world": world,
        "payload_MB": _shstep_payload_mb(),
        "fused_s": round(m["fused_s"], 3),
        "sharded_s": round(m["sharded_s"], 3),
        "steps_per_s_fused": round(1.0 / m["fused_s"], 3),
        "steps_per_s_sharded": round(1.0 / m["sharded_s"], 3),
        "speedup": round(m["fused_s"] / m["sharded_s"], 3),
        "fused_wire_MB_per_step": round(fused_wire / (1 << 20), 2),
        "rs_wire_MB_per_step": round(rs_wire / (1 << 20), 2),
        "ag_wire_MB_per_step": round(ag_wire / (1 << 20), 2),
        "model_bytes": total_bytes,
        "opt_state_bytes_fused": opt_fused,
        "opt_state_bytes_sharded": opt_sharded,
    }
    print(
        f"W={world}: fused {m['fused_s']:.3f}s/step, sharded "
        f"{m['sharded_s']:.3f}s/step -> {row['speedup']:.2f}x; wire/step "
        f"fused {row['fused_wire_MB_per_step']}MB vs rs "
        f"{row['rs_wire_MB_per_step']}MB + ag "
        f"{row['ag_wire_MB_per_step']}MB; opt bytes {opt_fused} -> "
        f"{opt_sharded}",
        flush=True,
    )
    return row


def _run_hier():
    """Spawns W-1 member processes, runs the measurer in-process, then
    verifies cross-member digests and peer exit codes (the kill victim
    must die by SIGKILL, everyone else exits clean)."""
    from torchft_tpu import Store
    from torchft_tpu._native import StoreClient

    os.environ["TORCHFT_HC_WIRE_CAP_MBPS"] = str(HIER_WIRE_CAP_MBPS)
    os.environ.pop("TORCHFT_HC_WIRE_CAP_INTRA_MBPS", None)
    store = Store()
    W = _hier_world()
    victim = W // 2
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    peers = []
    for r in range(1, W):
        args = [sys.executable, os.path.abspath(__file__), "--peer",
                store.address(), f"hier:{r}"]
        if "--dryrun" in sys.argv:
            args.append("--dryrun")
        peers.append(subprocess.Popen(args, env=env))
    rec = {}
    try:
        _hier_member(store.address(), 0, rec)
        # Two SIGKILL victims across the probe sequence: the region
        # leader (original rank W//2), then the co-hosted member
        # (original rank 1 — new_rank 1 of the surviving cohort).
        victims = {victim, 1}
        for i, p in enumerate(peers):
            r = i + 1
            code = p.wait(timeout=900)
            if r in victims:
                assert code != 0, f"kill victim {r} exited cleanly"
            else:
                assert code == 0, f"peer {r} exited {code}"
        client = StoreClient(
            store.address(), connect_timeout=timedelta(seconds=30)
        )
        t = timedelta(seconds=30)

        def digests(cfg, world):
            return {
                client.get(f"hier_digest/{cfg}/{r}", timeout=t).decode()
                for r in range(world)
            }

        for cfg, row in rec.items():
            if cfg in ("leader_kill", "cohost_kill"):
                continue
            row["digests_identical_across_members"] = (
                len(digests(cfg, W)) == 1
            )
        rec["uneven_regions_bit_identity"] = len(digests("uneven", W)) == 1
        rec["uneven_hosts_bit_identity"] = (
            len(digests("shm3_uneven", W)) == 1
        )
        rec["leader_kill"]["recover_bit_identity"] = (
            len(digests("recover", W - 1)) == 1
        )
        rec["cohost_kill"]["recover_bit_identity"] = (
            len(digests("cohost_recover", W - 2)) == 1
        )

        # Three-tier ORACLE pinning: the numpy host->region->fleet oracle
        # (the test suite's own, imported — one source of truth) must
        # match every member's bytes on every wire.
        sys.path.insert(0, os.path.join(REPO, "tests"))
        from test_hier_collectives import hier_oracle

        import hashlib

        regions = _hier_regions(W)
        hosts3 = ["hostA"] * (W // 2) + ["hostB"] * (W - W // 2)
        oracle_count = 50_000
        odatas = [
            (np.arange(oracle_count, dtype=np.float32) % 997) * 0.01
            + (r + 1)
            for r in range(W)
        ]
        oracle_ok = {}
        for wname, wire in (("f32", None), ("bf16", "bf16"), ("q8", "q8")):
            expect = hier_oracle(odatas, regions, wire=wire, hosts=hosts3)
            exp_digest = hashlib.sha256(
                np.ascontiguousarray(expect[0]).tobytes()
            ).hexdigest()
            got = digests(f"shm3_oracle_{wname}", W)
            oracle_ok[wname] = got == {exp_digest}
        rec["three_tier_oracle_ok"] = oracle_ok
    finally:
        for p in peers:
            if p.poll() is None:
                p.kill()
        store.shutdown()
    return rec


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--peer":
        peer(sys.argv[2], sys.argv[3] if len(sys.argv) > 3 else "overlap")
        return

    import jax

    if "--sharded-sweep" in sys.argv:
        results = _run_mode("sharded_capped")
        # Headline: the f32-delta configs — the regime where the sharded
        # schedule strictly cuts wire bytes on top of the ~W× compute/h2d
        # savings. q8 rows stay in the artifact: there the fused ring
        # already ships ~2B/elem so the bf16 param leg can cost wire, and
        # the honest number shows it.
        f32_rows = {k: v for k, v in results.items() if v["wire"] == "f32"}
        best_key = max(f32_rows, key=lambda k: f32_rows[k]["speedup"])
        report = {
            "platform": jax.devices()[0].platform,
            "payload_MB": _shard_payload_mb(),
            "leaves": N_LEAVES,
            "iters": _shard_iters(),
            "world_size": 2,
            "outer": {"optimizer": "nesterov-sgd",
                      "lr": SHARD_OUTER_LR, "momentum": SHARD_OUTER_MOM},
            "bdp_emulated": {
                "per_connection_cap_MBps": SHARD_WIRE_CAP_MBPS,
                "how": "TORCHFT_HC_WIRE_CAP_MBPS send pacing per ring "
                       "connection, both directions — an emulated "
                       "starved link",
            },
            "sync": "full = fused allreduce(delta) + redundant full-model "
                    "outer update on every member; sharded = "
                    "reduce_scatter(delta) -> outer update on the owned "
                    "1/W shard -> allgather_into(params, bf16 wire)",
            "configs": results,
            "headline_config": best_key,
            "headline_full_s": f32_rows[best_key]["full_s"],
            "headline_sharded_s": f32_rows[best_key]["sharded_s"],
            "sharded_speedup": f32_rows[best_key]["speedup"],
        }
        if "--dryrun" in sys.argv:
            print(json.dumps({"dryrun": True,
                              "sharded_speedup": report["sharded_speedup"]}))
            return
        with open(os.path.join(REPO, "SHARD_BENCH.json"), "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({
            "sharded_speedup": report["sharded_speedup"],
            "headline_config": best_key,
        }))
        return

    if "--sharded-step-sweep" in sys.argv:
        rows = [_run_shstep(w) for w in SHSTEP_WORLDS]
        per_step = {
            "platform": jax.devices()[0].platform,
            "leaves": N_LEAVES,
            "iters": _shstep_iters(),
            "stripes": SHSTEP_STRIPES,
            "per_connection_cap_MBps": SHSTEP_WIRE_CAP_MBPS,
            "sync": "fused = plan-f32 allreduce + redundant full-model "
                    "update on every member; sharded = plan "
                    "reduce-scatter (q8 grad wire, owner shard full "
                    "f32) -> update on the owned ~1/W shard -> bf16 "
                    "param allgather",
            "optimizer": {"kind": "nesterov-sgd", "lr": SHARD_OUTER_LR,
                          "momentum": SHARD_OUTER_MOM},
            "rows": rows,
            "note": "wins steps/s vs plan-f32 (fewer f32 wire bytes AND "
                    "~W x less update work); vs a fused q8 ring it wins "
                    "memory/FLOPs, not bytes — the rs+ag legs ship "
                    "~1.5B/elem where fused q8 ships ~1B/elem",
        }
        if "--dryrun" in sys.argv:
            r2 = next(r for r in rows if r["world"] == 2)
            r3 = next(r for r in rows if r["world"] == 3)
            ratio = (r2["opt_state_bytes_sharded"]
                     / max(r3["opt_state_bytes_sharded"], 1))
            # 1/W scaling: W=2 shard ~ 1.5x the W=3 shard (3/2).
            assert 1.2 < ratio < 1.9, f"opt shard not ~1/W: {ratio}"
            for r in rows:
                assert (r["opt_state_bytes_sharded"]
                        < r["opt_state_bytes_fused"])
                assert r["rs_wire_MB_per_step"] > 0
                assert r["ag_wire_MB_per_step"] > 0
            print(json.dumps({
                "dryrun": True,
                "speedup_w2": r2["speedup"],
                "speedup_w3": r3["speedup"],
                "opt_bytes_w2": r2["opt_state_bytes_sharded"],
                "opt_bytes_w3": r3["opt_state_bytes_sharded"],
            }))
            return
        path = os.path.join(REPO, "SHARD_BENCH.json")
        with open(path) as f:
            report = json.load(f)
        report["per_step"] = per_step
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({
            "per_step_speedups": {str(r["world"]): r["speedup"]
                                  for r in rows},
        }))
        return

    if "--plan-sweep" in sys.argv:
        results = _run_mode("plan_capped")
        worst = min(results.values(), key=lambda r: r["speedup"])
        best = max(results.values(), key=lambda r: r["speedup"])
        report = {
            "platform": jax.devices()[0].platform,
            "model": "ddp_small gradient signature (~0.72M params, the "
                     "real leaf structure of bench.py's link-sized "
                     "per-step DDP config)",
            "iters": _plan_iters(),
            "world_size": 2,
            "stripes": PLAN_STRIPES,
            "bdp_emulated": {
                "per_connection_cap_MBps": PLAN_WIRE_CAP_MBPS,
                "how": "TORCHFT_HC_WIRE_CAP_MBPS send pacing per ring "
                       "connection, both directions — an emulated "
                       "starved link",
            },
            "sync": "legacy = what PipelinedDDP ships today per wire "
                    "(device-packed managed allreduce; jitted bf16 "
                    "downcast; jitted int8 quantize+EF into the q8 "
                    "ring); planned = ONE native comm-plan call (cast/"
                    "EF/staging/striped ring/unpack below Python), "
                    "bit-identical results",
            "adaptive_mode": {
                "rule": "AdaptiveDDP probes blocking/plan/pipelined, "
                        "allgathers cohort timings, locks the argmin; "
                        "ties resolve to blocking, so the locked mode "
                        "is never slower than blocking as measured "
                        "(TORCHFT_DDP_MODE pins it explicitly)",
            },
            "configs": results,
            "worst_wire": worst["wire"],
            "worst_speedup": worst["speedup"],
            "best_wire": best["wire"],
            "best_speedup": best["speedup"],
            "planned_not_slower": all(
                r["speedup"] >= 0.98 for r in results.values()
            ),
            "zero_py_staging_allocs": all(
                r["py_staging_allocs_after_warmup"] == 0
                for r in results.values()
            ),
        }
        if "--dryrun" in sys.argv:
            print(json.dumps({
                "dryrun": True,
                "worst_speedup": report["worst_speedup"],
                "zero_py_staging_allocs": report["zero_py_staging_allocs"],
            }))
            return
        with open(os.path.join(REPO, "PLAN_BENCH.json"), "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({
            "plan_worst_speedup": report["worst_speedup"],
            "plan_best_speedup": report["best_speedup"],
            "zero_py_staging_allocs": report["zero_py_staging_allocs"],
        }))
        return

    if "--device-pack-sweep" in sys.argv:
        results = _run_mode("devpack_capped")
        f32_d2h = results["f32"]["d2h_bytes_host_pack"]
        ratios = {
            w: round(results[w]["d2h_bytes_device_pack"] / f32_d2h, 4)
            for w in results
        }
        compressed = [results["bf16"], results["q8"]]
        worst_raw = min(
            results.values(), key=lambda r: r["devpack_speedup_raw"]
        )
        worst_cap = min(
            compressed, key=lambda r: r["devpack_speedup_capped_link"]
        )
        report = {
            "platform": jax.devices()[0].platform,
            "model": "ddp_small gradient signature (~0.72M params, the "
                     "real leaf structure of bench.py's link-sized "
                     "per-step DDP config)",
            "iters": _plan_iters(),
            "world_size": 2,
            "stripes": PLAN_STRIPES,
            "bdp_emulated": {
                "per_connection_cap_MBps": PLAN_WIRE_CAP_MBPS,
                "how": "TORCHFT_HC_WIRE_CAP_MBPS send pacing per ring "
                       "connection, both directions — an emulated "
                       "starved link",
            },
            "sync": "host-pack = the PR-3 comm plan (full-width leaves "
                    "cross d2h, native cast/EF packs on the host); "
                    "device-pack = Pallas quantize/cast kernels emit the "
                    "wire encoding on the accelerator, only wire bytes "
                    "cross d2h, the prepacked plan decodes into the "
                    "SAME staging — bit-identical results either way",
            "measurement_note": "this host is CPU-only: the kernels run "
                    "in interpret mode and d2h is a memcpy, so the RAW "
                    "steps/s column is device pack's worst case (it "
                    "pays the kernel cost and banks no link saving — "
                    "kept as the honest control, like the stripe "
                    "sweep's raw-loopback pass). The capped_link_* columns "
                    "apply the stated linear model of a device link as "
                    "slow as the ring cap: wall + d2h_bytes / cap, "
                    "12 MB/s. "
                    "d2h_bytes itself is exact accounting either way.",
            "configs": results,
            "d2h_ratio_vs_f32_host": ratios,
            "q8_d2h_ratio": ratios["q8"],
            "bf16_d2h_ratio": ratios["bf16"],
            "q8_d2h_target_0p3_met": ratios["q8"] <= 0.3,
            "bf16_d2h_target_0p55_met": ratios["bf16"] <= 0.55,
            "worst_wire_raw": worst_raw["wire"],
            "worst_devpack_speedup_raw": worst_raw["devpack_speedup_raw"],
            # The acceptance comparison, on the compressed wires (f32
            # stays in configs as the no-byte-win control): under the
            # capped-link model device pack must not lose to host pack.
            "worst_compressed_devpack_speedup_capped_link":
                worst_cap["devpack_speedup_capped_link"],
            "devpack_not_slower_capped_link": all(
                r["devpack_speedup_capped_link"] >= 1.0 for r in compressed
            ),
        }
        if "--dryrun" in sys.argv:
            print(json.dumps({
                "dryrun": True,
                "q8_d2h_ratio": report["q8_d2h_ratio"],
                "bf16_d2h_ratio": report["bf16_d2h_ratio"],
                "devpack_not_slower_capped_link":
                    report["devpack_not_slower_capped_link"],
            }))
            return
        with open(os.path.join(REPO, "DEVPACK_BENCH.json"), "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({
            "q8_d2h_ratio": report["q8_d2h_ratio"],
            "bf16_d2h_ratio": report["bf16_d2h_ratio"],
            "worst_devpack_speedup_raw":
                report["worst_devpack_speedup_raw"],
            "devpack_not_slower_capped_link":
                report["devpack_not_slower_capped_link"],
        }))
        return

    if "--hier-sweep" in sys.argv:
        rec = _run_hier()
        W, L = _hier_world(), HIER_REGIONS
        count = int(_hier_payload_mb() * (1 << 20)) // 4
        _extra_keys = (
            "leader_kill", "cohost_kill", "uneven_regions_bit_identity",
            "uneven_hosts_bit_identity", "three_tier_oracle_ok",
            "shm3_shm", "shm3_tcp",
        )
        configs = {k: v for k, v in rec.items() if k not in _extra_keys}
        # Accounting check: the leader's inter-tier bytes per ring phase
        # must be ~(L-1)/L of the WIRE-sized payload — measured from the
        # duplex tx counters, not modeled. Wire esize: f32 4, bf16 2,
        # q8+EF ~1 (+ per-hop scales, allowed in the upper bound).
        esize = {"f32": 4, "bf16": 2, "q8": 1}
        for row in configs.values():
            expected = count * esize[row["wire"]] * (L - 1) // L
            inter = row["tiers"]["inter"]
            row["expected_inter_phase_bytes"] = expected
            row["inter_bytes_ok"] = all(
                expected <= inter[k] <= expected * 1.10 + 8192
                for k in ("rs_tx_bytes", "ag_tx_bytes")
            )
        f32_rows = {k: v for k, v in configs.items() if v["wire"] == "f32"}
        best_key = max(f32_rows, key=lambda k: f32_rows[k]["hier_speedup"])
        kill = rec["leader_kill"]
        report = {
            "platform": jax.devices()[0].platform,
            "world_size": W,
            "regions": {"east": W // 2, "west": W - W // 2},
            "payload_MB": _hier_payload_mb(),
            "iters": _hier_iters(),
            "emulation": {
                "inter_cap_MBps": HIER_WIRE_CAP_MBPS,
                "how": "TORCHFT_HC_WIRE_CAP_MBPS send pacing per "
                       "connection: in FLAT mode it paces EVERY ring edge "
                       "(topology-oblivious placement — any hop may cross "
                       "the DCN); the hier schedule's inter (leader) tier "
                       "is paced by the same knob while the intra tier "
                       "rides unpaced loopback "
                       "(TORCHFT_HC_WIRE_CAP_INTRA_MBPS unset) — the "
                       "fast-intra/slow-inter fabric the topology exists "
                       "for",
            },
            "sync": "both sides ride the comm-plan path (the AdaptiveDDP "
                    "plan vs plan_hier candidates): flat = one striped "
                    "ring over all W members; hier = intra-region "
                    "reduce-scatter -> intra allgather -> inter ring "
                    "among the 2 region leaders (the only capped-link "
                    "traffic) -> chunk-pipelined intra broadcast. Wires "
                    "apply to the whole flat ring vs the inter hop only "
                    "(f32 / bf16 / q8+EF at the leader).",
            "determinism": "hier results are bit-identical across members "
                    "and across iterations (sha256 digests in configs); "
                    "the SUM ORDER differs from the flat ring, so "
                    "flat-vs-hier values agree at the f32 reordering "
                    "tolerance, never bit-for-bit (documented contract)",
            "configs": configs,
            "headline_config": best_key,
            "hier_speedup": f32_rows[best_key]["hier_speedup"],
            "hier_speedup_target_1p5_met":
                f32_rows[best_key]["hier_speedup"] >= 1.5,
            "inter_bytes_accounting_ok": all(
                r["inter_bytes_ok"] for r in configs.values()
            ),
            "bit_identity_ok": all(
                r["digests_identical_across_members"]
                and r["deterministic_across_iters"] is not False
                for r in configs.values()
            ) and rec["uneven_regions_bit_identity"],
            "uneven_regions_bit_identity": rec[
                "uneven_regions_bit_identity"],
            "leader_kill": kill,
            "leader_kill_ok": bool(
                kill["errored"]
                and kill["error_s"] < kill["op_timeout_s"]
                and kill.get("recovered_commit")
                and kill.get("recover_bit_identity")
            ),
        }
        # ---- the three-tier (host -> region -> fleet) SHM section ----
        shm_row, tcp_row = rec["shm3_shm"], rec["shm3_tcp"]
        ck = rec["cohost_kill"]
        shm_speedup = (
            tcp_row["host_phase_s"] / shm_row["host_phase_s"]
            if shm_row["host_phase_s"] > 0 else float("inf")
        )
        report["SHM_BENCH"] = {
            "topology": "three tiers: 2 hosts x W/2 co-hosted members "
                        "(the host ring: shared-memory rings vs the "
                        "TORCHFT_HC_SHM=0 loopback-TCP control, same "
                        "geometry) -> inter leader ring under the "
                        "wire cap; each host is one region's whole "
                        "membership",
            "hosts": {"hostA": W // 2, "hostB": W - W // 2},
            "payload_MB": _hier_payload_mb(),
            "rows": {"shm": shm_row, "tcp": tcp_row},
            # The tentpole number: wall of the intra-host ring phases
            # (rs + ag + bcast) moving the identical payload.
            "host_phase_speedup_shm_vs_tcp": round(shm_speedup, 3),
            "host_phase_speedup_target_2x_met": shm_speedup >= 2.0,
            # Honest zero-tx contract: shm hops hand nothing to the
            # kernel, the TCP control pays for every byte.
            "shm_zero_tx_bytes_ok": (
                shm_row["tiers"]["host"]["tx_bytes"] == 0
                and tcp_row["tiers"]["host"]["tx_bytes"] > 0
            ),
            "transports_ok": (
                shm_row["transport"] == "shm"
                and tcp_row["transport"] == "tcp"
            ),
            "bit_identity": {
                "across_members": bool(
                    shm_row.get("digests_identical_across_members")
                    and tcp_row.get("digests_identical_across_members")
                ),
                "uneven_hosts": rec["uneven_hosts_bit_identity"],
                "three_tier_numpy_oracle": rec["three_tier_oracle_ok"],
            },
            "cohost_kill": ck,
            "cohost_kill_ok": bool(
                ck["errored"]
                and ck["error_s"] < ck["op_timeout_s"]
                and ck.get("recovered_commit")
                and ck.get("recover_bit_identity")
            ),
        }
        if "--dryrun" in sys.argv:
            shm_bench = report["SHM_BENCH"]
            print(json.dumps({
                "dryrun": True,
                "hier_speedup": report["hier_speedup"],
                "inter_bytes_accounting_ok":
                    report["inter_bytes_accounting_ok"],
                "bit_identity_ok": report["bit_identity_ok"],
                "leader_kill_ok": report["leader_kill_ok"],
                "leader_kill": kill,
                "shm_host_phase_speedup":
                    shm_bench["host_phase_speedup_shm_vs_tcp"],
                "shm_zero_tx_bytes_ok": shm_bench["shm_zero_tx_bytes_ok"],
                "shm_bit_identity": shm_bench["bit_identity"],
                "cohost_kill_ok": shm_bench["cohost_kill_ok"],
            }))
            # The CI smoke ASSERTS the contracts it exists for (a broken
            # schedule must fail the step, not just print false). The
            # speedups are NOT asserted here — a loaded CI runner's
            # timing is noise at the dryrun payload; the accounting,
            # identity and fault contracts are timing-free.
            assert report["inter_bytes_accounting_ok"], (
                "per-leader inter-tier bytes drifted from (L-1)/L * wire "
                "payload"
            )
            assert report["bit_identity_ok"], (
                "cross-member/cross-iteration bit identity broken"
            )
            assert report["leader_kill_ok"], (
                f"leader-kill contract broken: {kill}"
            )
            # Three-tier smoke contracts: a real 3-tier record with shm
            # phase keys, the honest zero-tx split, the numpy oracle
            # across wires, uneven host layouts, and the co-hosted kill.
            assert shm_bench["transports_ok"], (
                f"host tier transports wrong: {shm_bench['rows']}"
            )
            for trow in shm_bench["rows"].values():
                assert trow["tiers"]["host"]["world"] >= 2
                assert trow["host_phase_s"] > 0, (
                    "host tier phase walls missing from the record"
                )
            assert shm_bench["shm_zero_tx_bytes_ok"], (
                "shm tier billed kernel bytes (or the TCP control "
                "billed none)"
            )
            assert all(
                shm_bench["bit_identity"]["three_tier_numpy_oracle"]
                .values()
            ), f"three-tier oracle broken: {shm_bench['bit_identity']}"
            assert shm_bench["bit_identity"]["uneven_hosts"], (
                "uneven host layout bit identity broken"
            )
            assert shm_bench["cohost_kill_ok"], (
                f"co-hosted kill contract broken: {shm_bench['cohost_kill']}"
            )
            return
        with open(os.path.join(REPO, "HIER_BENCH.json"), "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({
            "hier_speedup": report["hier_speedup"],
            "headline_config": best_key,
            "inter_bytes_accounting_ok":
                report["inter_bytes_accounting_ok"],
            "bit_identity_ok": report["bit_identity_ok"],
            "leader_kill_ok": report["leader_kill_ok"],
        }))
        return

    if "--stripe-sweep" in sys.argv:
        capped = _run_mode("stripes_capped")
        raw = _run_mode("stripes")
        base = capped["cap_stripe1"]
        # Headline = the capped pass, ranked on the ring leg: striping is a
        # transport optimization for per-connection-limited paths, and the
        # capped pass is the loopback-measurable stand-in for them. The
        # raw pass stays in the artifact as the control (CPU-bound here:
        # parity is the expected result, see module docstring).
        best_s = max(STRIPE_COUNTS,
                     key=lambda s: capped[f"cap_stripe{s}"]["ring_MBps"])
        best = capped[f"cap_stripe{best_s}"]
        report = {
            "platform": jax.devices()[0].platform,
            "payload_MB": TOTAL_MB,
            "leaves": N_LEAVES,
            "iters": ITERS,
            "pipeline_chunks": STRIPE_CHUNKS,
            "bdp_emulated": {
                "per_connection_cap_MBps": WIRE_CAP_MBPS,
                "how": "TORCHFT_HC_WIRE_CAP_MBPS send pacing per ring "
                       "connection, both directions — models the "
                       "window/BDP-limited DCN links the "
                       "striped transport targets",
                "stripes": {
                    str(s): capped[f"cap_stripe{s}"] for s in STRIPE_COUNTS
                },
            },
            "raw_loopback_control": {
                "note": "this sandbox's loopback is CPU-bound (~700 MB/s "
                        "at 1 raw connection, slower with more), so "
                        "stripe parity — not speedup — is the honest "
                        "expectation here",
                "stripes": {
                    str(s): raw[f"stripe{s}"] for s in STRIPE_COUNTS
                },
            },
            "single_connection_MBps": base["MBps"],
            "single_connection_ring_MBps": base["ring_MBps"],
            "best_stripes": best_s,
            "best_MBps": best["MBps"],
            "best_ring_MBps": best["ring_MBps"],
            "speedup_vs_single_connection": round(
                best["MBps"] / base["MBps"], 3
            ),
            "ring_speedup_vs_single_connection": round(
                best["ring_MBps"] / base["ring_MBps"], 3
            ),
        }
        with open(os.path.join(REPO, "STRIPE_BENCH.json"), "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps({
            "stripe_speedup": report["speedup_vs_single_connection"],
            "ring_speedup": report["ring_speedup_vs_single_connection"],
            "best_stripes": best_s,
        }))
        return

    results = _run_mode("overlap")
    report = {
        "platform": jax.devices()[0].platform,
        "payload_MB": TOTAL_MB,
        "leaves": N_LEAVES,
        "iters": ITERS,
    }
    report.update(results)
    report["speedup"] = round(
        report["single_shot"]["s"] / report["pipelined"]["s"], 3
    )
    with open(os.path.join(REPO, "OVERLAP_BENCH.json"), "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps({"overlap_speedup": report["speedup"]}))


if __name__ == "__main__":
    main()
