"""Measured comparison of the THREE cross-replica-group data planes.

VERDICT.md round 1 item 7 asked for the DCN story to be decided with data,
not defaults. This benchmark runs the backends over the same 2-process
cohort on this host and records, for each:

  - allreduce throughput at small/large payloads (the steady-state cost),
  - configure() latency on a membership change (the churn cost),
  - behavior when the peer dies mid-collective (the wedge hazard).

Backends: the host TCP ring, the in-process ``XLACollectives`` (compiled
psums; membership baked into ``jax.distributed``), and the ISOLATED
``IsolatedXLACollectives`` (the same compiled runtime in a disposable
child process: membership change = SIGKILL + respawn + store
re-rendezvous, so the parent's device state is never orphaned and a
mid-collective child death recovers at step granularity). The isolated
rows record the child's measured reduction path ("psum" where the
compiled multi-process backend exists, the "store" fallback elsewhere) —
transport numbers differ by path, but the reconfigure and kill→recovery
structure is what this bench compares.

Writes DCN_BENCH.json and prints a summary. The architectural conclusions
live in DCN.md. CPU/gloo/localhost numbers are proxies for TPU-host/DCN —
absolute bandwidths will differ on real fabric, but the structural gaps
(reconfigure invalidating device state; wedge-on-death vs fail-fast vs
kill-and-respawn) are platform-independent.

Usage: python bench_dcn.py            # orchestrates everything
       python bench_dcn.py --dryrun   # seconds-scale CI smoke (host +
                                      # isolated rows only, tiny payloads,
                                      # asserts a kill->recovery record,
                                      # writes no artifact)
"""

import json
import os
import signal
import subprocess
import sys
import time
from datetime import timedelta

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

DRYRUN = "--dryrun" in sys.argv

if DRYRUN:
    SIZES = {"256KB": 1 << 16}  # f32 element counts
    ITERS = 2
    DEATH_CAP_S = 6.0
else:
    SIZES = {"4MB": 1 << 20, "64MB": 16 << 20}
    ITERS = 5
    DEATH_CAP_S = 20.0


def _sync_peers(store_addr: str, tag: str, rank: int,
                timeout_s: float = 120.0) -> None:
    """Two-rank rendezvous through the store: reconfigure measurements
    must start SIMULTANEOUSLY on both members (the quorum-boundary
    reality — every member reconfigures at the same transaction), or the
    numbers flip between the staggered and simultaneous regimes run to
    run."""
    from torchft_tpu._native import StoreClient

    sc = StoreClient(store_addr, connect_timeout=timedelta(seconds=60))
    sc.set(f"{tag}/{rank}", b"1")
    sc.get(f"{tag}/{1 - rank}", timeout=timedelta(seconds=timeout_s))


def _worker_host(rank: int, store_addr: str, mode: str) -> None:
    import numpy as np

    from torchft_tpu.collectives import HostCollectives, ReduceOp

    hc = HostCollectives(timeout=timedelta(seconds=60),
                         connect_timeout=timedelta(seconds=60))
    t0 = time.perf_counter()
    hc.configure(f"{store_addr}/q0", rank, 2)
    configure_s = time.perf_counter() - t0
    results = {"configure_s": configure_s}

    if mode == "bench":
        for name, n in SIZES.items():
            buf = np.ones((n,), np.float32) * (rank + 1)
            hc.allreduce(buf, ReduceOp.SUM).wait()  # warm
            t0 = time.perf_counter()
            for _ in range(ITERS):
                hc.allreduce(buf, ReduceOp.SUM).wait()
            dt = (time.perf_counter() - t0) / ITERS
            results[name] = {"s": dt, "MBps": (n * 4 / 1e6) / dt}
        t0 = time.perf_counter()
        hc.configure(f"{store_addr}/q1", rank, 2)  # membership change
        results["reconfigure_s"] = time.perf_counter() - t0
    elif mode == "death":
        buf = np.ones((SIZES["4MB"],), np.float32)
        hc.allreduce(buf, ReduceOp.SUM).wait()  # both alive
        if rank == 1:
            os._exit(1)  # die before the next op
        time.sleep(0.5)
        t0 = time.perf_counter()
        try:
            hc.allreduce(buf, ReduceOp.SUM).wait(
                timeout=timedelta(seconds=DEATH_CAP_S)
            )
            results["death"] = {"outcome": "no-error", "s": None}
        except Exception as e:  # noqa: BLE001
            results["death"] = {
                "outcome": f"error:{type(e).__name__}",
                "s": time.perf_counter() - t0,
            }
    print("RESULT " + json.dumps(results), flush=True)
    hc.shutdown()


def _worker_xla(rank: int, store_addr: str, mode: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu import XLACollectives
    from torchft_tpu.collectives import ReduceOp

    keep_global = mode == "bench_global"
    xc = XLACollectives(timeout=timedelta(seconds=60),
                        connect_timeout=timedelta(seconds=60),
                        keep_global=keep_global)
    t0 = time.perf_counter()
    xc.configure(f"{store_addr}/q0", rank, 2)
    results = {"configure_s": time.perf_counter() - t0}

    # The compiled multi-process reduction may be absent on this install
    # (CPU jax without a gloo collectives build): payload rows are then
    # honestly SKIPPED, but configure/reconfigure — the churn cost this
    # bench's headline comparison is about, runtime init + teardown +
    # the device-state round trip — is still fully measurable.
    ops_ok = True
    if mode in ("bench", "bench_global", "death"):
        try:
            jax.block_until_ready(
                xc.allreduce(jnp.ones((8,), jnp.float32), ReduceOp.SUM).wait()
            )
        except Exception as e:  # noqa: BLE001
            ops_ok = False
            results["ops_skipped"] = (
                f"no compiled multiprocess path: {type(e).__name__}"
            )

    if mode in ("bench", "bench_global"):
        for name, n in SIZES.items():
            if not ops_ok:
                break
            buf = jnp.ones((n,), jnp.float32) * (rank + 1)
            jax.block_until_ready(buf)
            jax.block_until_ready(xc.allreduce(buf, ReduceOp.SUM).wait())
            t0 = time.perf_counter()
            for _ in range(ITERS):
                jax.block_until_ready(xc.allreduce(buf, ReduceOp.SUM).wait())
            dt = (time.perf_counter() - t0) / ITERS
            results[name] = {"s": dt, "MBps": (n * 4 / 1e6) / dt}
        if mode == "bench":
            # Membership change = full runtime teardown + re-init; live
            # arrays (params!) do not survive, so the realistic cost also
            # includes snapshotting state to host and re-placing it.
            state = jnp.ones((max(SIZES.values()),), jnp.float32)
            jax.block_until_ready(state)
            # Median of 3: the first-connect race at simultaneous
            # restart is probabilistic (a member that beats the fresh
            # coordinator's bind pays the client's ~1 s retry backoff),
            # so one shot flips between regimes run to run.
            samples = []
            for i in range(3):
                _sync_peers(store_addr, f"xla_sync_reconf{i}", rank)
                t0 = time.perf_counter()
                saved = np.asarray(state)
                xc.configure(f"{store_addr}/q{i + 1}", rank, 2)
                state = jnp.asarray(saved)
                jax.block_until_ready(state)
                samples.append(time.perf_counter() - t0)
            results["reconfigure_samples_s"] = samples
            results["reconfigure_s"] = sorted(samples)[len(samples) // 2]
    elif mode == "death" and not ops_ok:
        results["death"] = {
            "outcome": "skipped:no-compiled-multiprocess-path", "s": None,
        }
    elif mode == "death":
        buf = jnp.ones((SIZES["4MB"],), jnp.float32)
        jax.block_until_ready(xc.allreduce(buf, ReduceOp.SUM).wait())
        if rank == 1:
            os._exit(1)
        time.sleep(0.5)
        t0 = time.perf_counter()
        try:
            w = xc.allreduce(buf, ReduceOp.SUM)
            jax.block_until_ready(
                w.wait(timeout=timedelta(seconds=DEATH_CAP_S))
            )
            results["death"] = {"outcome": "no-error", "s": None}
        except Exception as e:  # noqa: BLE001
            elapsed = time.perf_counter() - t0
            kind = type(e).__name__
            outcome = (
                f"wedged>= {DEATH_CAP_S}s" if elapsed >= DEATH_CAP_S - 0.5
                else f"error:{kind}"
            )
            results["death"] = {"outcome": outcome, "s": elapsed}
    print("RESULT " + json.dumps(results), flush=True)
    if mode != "death":
        xc.shutdown()
    else:
        os._exit(0)  # distributed runtime knows the peer is gone; skip teardown


def _worker_iso(rank: int, store_addr: str, mode: str) -> None:
    import jax
    import jax.numpy as jnp

    from torchft_tpu import IsolatedXLACollectives
    from torchft_tpu.collectives import ReduceOp

    parent_pid = os.getpid()
    op_timeout = timedelta(seconds=DEATH_CAP_S if mode == "death" else 60)
    iso = IsolatedXLACollectives(timeout=op_timeout,
                                 connect_timeout=timedelta(seconds=60))
    t0 = time.perf_counter()
    iso.configure(f"{store_addr}/q0", rank, 2)
    results = {"configure_s": time.perf_counter() - t0,
               "path": iso.reduction_path()}

    if mode == "bench":
        for name, n in SIZES.items():
            buf = jnp.ones((n,), jnp.float32) * (rank + 1)
            jax.block_until_ready(buf)
            jax.block_until_ready(iso.allreduce(buf, ReduceOp.SUM).wait())
            t0 = time.perf_counter()
            for _ in range(ITERS):
                jax.block_until_ready(iso.allreduce(buf, ReduceOp.SUM).wait())
            dt = (time.perf_counter() - t0) / ITERS
            results[name] = {"s": dt, "MBps": (n * 4 / 1e6) / dt}
        # Membership change = SIGKILL + respawn + re-rendezvous. The
        # parent's LIVE device state is untouched (no runtime teardown,
        # no snapshot-to-host round trip) — proven by holding a
        # 64 MB-class array across the reconfigure and checksumming it,
        # where the in-process XLA row must pay an explicit host
        # round-trip for the same state.
        n_state = max(SIZES.values())
        state = jnp.arange(n_state, dtype=jnp.float32)
        jax.block_until_ready(state)
        digest = float(jnp.sum(state))
        # One untimed warmup reconfigure, then median of 3: the settle
        # between rounds lets the background spare re-arm — the steady
        # state of quorum-separated reconfigures in a real run (a spare
        # armed mid-payload-loop on a saturated 2-CPU host can still be
        # forking when the first reconfigure lands).
        _sync_peers(store_addr, "iso_sync_warm", rank)
        iso.configure(f"{store_addr}/qw", rank, 2)
        samples = []
        breakdowns = []
        for i in range(3):
            time.sleep(3.0)
            _sync_peers(store_addr, f"iso_sync_reconf{i}", rank)
            iso.pop_op_stats()
            t0 = time.perf_counter()
            iso.configure(f"{store_addr}/q{i + 1}", rank, 2)
            samples.append(time.perf_counter() - t0)
            cfg = [s for s in iso.pop_op_stats() if s["op"] == "configure"]
            if cfg:
                breakdowns.append({
                    k: v for k, v in cfg[-1].items()
                    if k not in ("op", "backend")
                })
        results["reconfigure_samples_s"] = samples
        results["reconfigure_s"] = sorted(samples)[len(samples) // 2]
        results["reconfigure_breakdown"] = breakdowns[
            samples.index(results["reconfigure_s"])
        ] if breakdowns else None
        results["state_intact"] = bool(float(jnp.sum(state)) == digest)
        jax.block_until_ready(
            iso.allreduce(jnp.ones((8,), jnp.float32), ReduceOp.SUM).wait()
        )
    elif mode == "death":
        buf = jnp.ones((SIZES[min(SIZES)],), jnp.float32)
        jax.block_until_ready(iso.allreduce(buf, ReduceOp.SUM).wait())
        t_kill = time.perf_counter()
        if rank == 1:
            # SIGKILL our own CHILD, then dispatch: rank 0's compiled
            # collective is mid-flight against a dead peer when the
            # death surfaces (the wedge scenario), and THIS parent
            # process never restarts — the entire point of the
            # isolation. The peer's cost is bounded by the op deadline,
            # never the runtime heartbeat's minutes.
            os.kill(iso.child_pid(), signal.SIGKILL)
        else:
            time.sleep(0.05)  # let the victim's kill land first
        try:
            work = iso.allreduce(buf, ReduceOp.SUM)
            jax.block_until_ready(
                work.wait(timeout=timedelta(seconds=DEATH_CAP_S + 10))
            )
            results["death"] = {"outcome": "no-error", "s": None}
        except Exception as e:  # noqa: BLE001
            results["death"] = {
                "outcome": f"error:{type(e).__name__}",
                "s": time.perf_counter() - t_kill,
            }
        # Step-granularity recovery: once every member has observed the
        # failure (the manager's quorum synchronizes this; the store
        # rendezvous plays that role here), the next configure respawns
        # onto a fresh prefix and the cohort commits again — in the SAME
        # parent process.
        _sync_peers(store_addr, "iso_sync_dead", rank,
                    timeout_s=DEATH_CAP_S + 60)
        t0 = time.perf_counter()
        iso.configure(f"{store_addr}/q1", rank, 2)
        reconf_s = time.perf_counter() - t0
        out = iso.allreduce(buf, ReduceOp.SUM).wait()
        jax.block_until_ready(out)
        results["recovery"] = {
            "reconfigure_s": reconf_s,
            "kill_to_next_commit_s": time.perf_counter() - t_kill,
            "parent_pid_stable": os.getpid() == parent_pid,
            "value_ok": bool(abs(float(out[0]) - 2.0) < 1e-6),
        }
    print("RESULT " + json.dumps(results), flush=True)
    iso.shutdown()


def _spawn(backend: str, mode: str, store_addr: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_CPU_COLLECTIVES_IMPLEMENTATION="gloo")
    env.pop("XLA_FLAGS", None)
    cmd_tail = ["--dryrun"] if DRYRUN else []
    return [
        subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", backend,
             str(r), store_addr, mode] + cmd_tail,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(2)
    ]


def _collect(procs, allow_fail=False, timeout=300.0):
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out = "(timeout)"
        outs.append((p.returncode, out))
    results = []
    for rc, out in outs:
        if not allow_fail:
            assert rc == 0, f"worker failed:\n{out[-2000:]}"
        for line in out.splitlines():
            if line.startswith("RESULT "):
                results.append(json.loads(line[len("RESULT "):]))
    return results


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        backend, rank, store_addr, mode = (
            sys.argv[2], int(sys.argv[3]), sys.argv[4], sys.argv[5]
        )
        if backend == "host":
            _worker_host(rank, store_addr, mode)
        elif backend == "iso":
            _worker_iso(rank, store_addr, mode)
        else:
            _worker_xla(rank, store_addr, mode)
        return

    from torchft_tpu import Store

    report = {"sizes": {k: v * 4 / (1 << 20) for k, v in SIZES.items()},
              "iters": ITERS, "dryrun": DRYRUN}
    suites = (
        ("host", ["bench", "death"]),
        ("xla", ["bench", "bench_global", "death"]),
        ("iso", ["bench", "death"]),
    )
    if DRYRUN:
        # seconds-scale smoke: host + isolated only (the in-process XLA
        # death row intentionally wedges for DEATH_CAP_S by design)
        suites = (("host", ["bench"]), ("iso", ["bench", "death"]))
    for backend, modes in suites:
        report[backend] = {}
        for mode in modes:
            store = Store()
            try:
                procs = _spawn(backend, mode, store.address())
                res = _collect(procs, allow_fail=(mode == "death"))
            finally:
                store.shutdown()
            # rank 0's numbers (rank 1 exits early in the host/xla death
            # modes); the ISO death recovery is measured on rank 1 — the
            # member whose child was killed — so keep its record too
            report[backend][mode] = res[0] if res else {}
            if backend == "iso" and mode == "death" and len(res) > 1:
                # the member whose child was killed carries the headline
                # kill->next-commit number; the survivor's bounded error
                # latency rides along
                report[backend][mode] = dict(res[1])
                report[backend][mode]["survivor"] = {
                    "death": res[0].get("death"),
                    "recovery": res[0].get("recovery"),
                }
            print(f"{backend}/{mode}: {json.dumps(report[backend][mode])}",
                  flush=True)

    iso_bench = report.get("iso", {}).get("bench", {})
    xla_bench = report.get("xla", {}).get("bench", {})
    if iso_bench.get("reconfigure_s") and xla_bench.get("reconfigure_s"):
        # The in-process reconfigure is BIMODAL on this host: the
        # port-reservation fix (publish the held port, then initialize)
        # lets a lucky member connect on its first try (~0.08 s), an
        # unlucky one pays the distributed client's ~1 s retry backoff —
        # and on CPU the device-state round trip is ~zero-copy, so the
        # proxy UNDERSTATES the in-process cost vs real accelerators
        # (where the snapshot scales with state and the teardown orphans
        # live arrays either way). Both regimes are reported; the
        # isolated reconfigure is unimodal and state-independent.
        xla_samples = xla_bench.get(
            "reconfigure_samples_s", [xla_bench["reconfigure_s"]]
        )
        report["summary"] = {
            "iso_reconfigure_s": iso_bench["reconfigure_s"],
            "xla_inprocess_reconfigure_median_s": xla_bench["reconfigure_s"],
            "xla_inprocess_reconfigure_worst_s": max(xla_samples),
            "reconfigure_speedup_vs_median": (
                xla_bench["reconfigure_s"] / iso_bench["reconfigure_s"]
            ),
            # vs the historical teardown regime (the documented ~1.0 s
            # path: teardown + connect-race + state round trip)
            "reconfigure_speedup_vs_worst": (
                max(xla_samples) / iso_bench["reconfigure_s"]
            ),
            "iso_state_survived_reconfigure": iso_bench.get("state_intact"),
        }
        print(f"summary: {json.dumps(report['summary'])}", flush=True)

    if DRYRUN:
        # the smoke's contract: at least one isolated-backend record with
        # a measured kill->recovery, in a never-restarted parent
        death = report["iso"]["death"]
        assert death.get("recovery"), death
        assert death["recovery"]["parent_pid_stable"] is True, death
        assert death["recovery"]["kill_to_next_commit_s"] > 0, death
        assert death["recovery"]["value_ok"] is True, death
        assert report["iso"]["bench"].get("state_intact") is True
        print("dryrun OK (no artifact written)")
        return

    from torchft_tpu.chaos import bench_fault_stamp

    report["fault_plan"] = bench_fault_stamp(
        bench="bench_dcn", kill_kind="sigkill_mid_collective",
    )
    with open(os.path.join(REPO, "DCN_BENCH.json"), "w") as f:
        json.dump(report, f, indent=2)
    print("wrote DCN_BENCH.json")


if __name__ == "__main__":
    main()
