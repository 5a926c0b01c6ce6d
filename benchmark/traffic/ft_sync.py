"""Traffic generator ``ft_sync``: the README's loop under the step
transaction, one replica group per chip.

    optimizer.zero_grad()                        # starts the quorum
    loss, grads = grad_fn(state.params, batch)   # bf16 copy over f32 masters
    avg = manager.allreduce(grads).wait()        # across the groups
    committed = optimizer.step(avg)              # applies iff the vote passes

The parent (this process) holds no JAX backend: it starts the lighthouse
in-process and ``python -m torchft_tpu.launcher --chips-per-group N``,
which pins one worker process (this file's ``worker``) to each chip and
restarts the one that kills itself. Parent and workers meet in the run's
scratch directory: a start line, the kill marker, one line per step and
one closing record per life of a group, all on the host's one monotonic
clock. Copied, as sound, from ``chip_smoke.run_group`` / ``phase_fleet``.

Parameters (``params`` of the mix's file; ``DEFAULTS`` below for those a
mix leaves out): ``groups``, ``chips_per_group``, ``pool``,
``warmup_steps`` (committed steps at full strength) and
``warmup_seconds`` before the window opens, ``trace_steps``,
``raw_steps`` (the raw loop of a traced one-group run),
``reference_steps`` (the loop's first losses held to the plain
reference, one group; with several, group 0's first loss and gradient),
``kill_group`` / ``kill_after_commit`` (absent: nobody dies),
``rejoin_commits`` at full strength after the rejoin, ``rejoin_cap_s``,
``max_restarts``, the lighthouse's ``heartbeat_timeout_ms``,
``join_timeout_ms``, ``min_replicas`` and ``collectives_timeout_s``.

No cell of ``BENCHMARK.json`` has more than one group yet: the paths for
several groups and for a kill ran on four chips in PR 23 (PERF.md
sections 6 and 7) and wait there for the cell that proves them.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from datetime import timedelta
from typing import Any, Dict, List, Optional

from benchmark import common

DEFAULTS = {
    "chips_per_group": 1, "max_restarts": 0, "rejoin_commits": 0,
    "rejoin_cap_s": 90, "heartbeat_timeout_ms": 3000,
    "join_timeout_ms": 60000, "min_replicas": 1, "collectives_timeout_s": 120,
}

# ---------------------------------------------------------------------------
# the parent
# ---------------------------------------------------------------------------


def run(cell: Dict[str, Any]) -> Dict[str, Any]:
    p = {**DEFAULTS, **cell["params"]}
    phases: common.Phases = cell["phases"]
    from torchft_tpu import _native  # the control plane; no JAX backend

    phases.mark("imports")
    scratch = cell["scratch"]
    lighthouse = _native.Lighthouse(
        bind="[::]:0", min_replicas=p["min_replicas"],
        join_timeout_ms=p["join_timeout_ms"],
        heartbeat_timeout_ms=p["heartbeat_timeout_ms"],
    )
    worker_cmd = [
        sys.executable, os.path.join(common.BENCH, "run.py"), "--worker", scratch,
        *cell["argv"],
    ]
    env = dict(
        os.environ, PYTHONUNBUFFERED="1",
        JAX_PLATFORMS="cpu" if cell["rehearse"] else "tpu",
    )
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "torchft_tpu.launcher",
            "--num-replica-groups", str(p["groups"]),
            "--chips-per-group", str(p["chips_per_group"]),
            "--lighthouse", lighthouse.address(),
            "--max-restarts", str(p["max_restarts"]),
            "--", *worker_cmd,
        ],
        env=env, cwd=common.REPO, start_new_session=True,
        stdout=sys.stderr,  # the workers' chatter is not a result
    )
    try:
        code = proc.wait(timeout=cell["deadline_s"])
    finally:
        # nothing this run started outlives it
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
        lighthouse.shutdown()
    if code != 0:
        raise common.Refused(f"the fleet failed (launcher exit {code})")

    lives = []
    for name in sorted(os.listdir(scratch)):
        if name.startswith("life_"):
            with open(os.path.join(scratch, name)) as f:
                lives.append(json.load(f))
    steps: Dict[str, List[Dict[str, Any]]] = {}
    for name in sorted(os.listdir(scratch)):
        if name.startswith("steps_"):
            with open(os.path.join(scratch, name)) as f:
                steps[name[len("steps_"):-len(".jsonl")]] = [
                    json.loads(line) for line in f
                ]
    groups = []
    for key, records in steps.items():
        g, life = (int(x) for x in key.split("_"))
        closing = next(
            (l for l in lives if l["group"] == g and l["life"] == life), {}
        )
        groups.append({"group": g, "life": life, "steps": records, **closing})
    return collect(cell, groups, scratch)


def collect(
    cell: Dict[str, Any], groups: List[Dict[str, Any]], scratch: str
) -> Dict[str, Any]:
    """Joins the groups' records into the run's facts and checks."""
    p = {**DEFAULTS, **cell["params"]}
    n = p["groups"]
    first_lives = [g for g in groups if g["life"] == 0]
    finished = [g for g in groups if "digest" in g]
    if len(first_lives) != n or len(finished) != n:
        raise common.Refused(
            f"{len(first_lives)} group(s) started and {len(finished)} finished, "
            f"want {n}"
        )
    lead = next(g for g in finished if g["group"] == 0)
    every_step = [s for g in groups for s in g["steps"]]
    committed = [s for s in every_step if s["committed"]]
    checks = {
        "losses_finite": all(g["losses_finite"] for g in finished),
        "reference": lead["reference"]["ok"],
        "masters_f32": all(g["masters_f32"] for g in finished),
        "no_commit_with_error": not any(s.get("errored") for s in committed),
        "digests_equal": len({(g["final_step"], g["digest"]) for g in finished}) == 1,
        "participants": all(
            s["participants"] >= min(n, max(1, n - 1)) for s in committed
        ),
    }
    facts: Dict[str, Any] = {
        "device": dict(lead["device"], count=n * p["chips_per_group"],
                       memory_peak_bytes=max(g["memory_peak_bytes"] for g in finished)),
        "groups": groups,
        "t_open": lead["steps"][lead["open_at"]]["t"],
        "tokens_per_step": lead["tokens_per_step"],
        "flops_per_step": lead["flops_per_step"],
        "flash": lead["flash"],
        "family": lead["family"],
        "trace": lead.get("trace"),
        "routing": lead.get("routing"),
        "reference": lead["reference"],
        "manager_metrics": lead["manager_metrics"],
        "op_stats": lead["op_stats"],
        "raw": lead.get("raw"),
        "memory_stats": lead.get("memory_stats"),
        "worker_phases": {f"{g['group']}_{g['life']}": g["phases"] for g in finished},
        "attempted": len(every_step),
        "failed": len(every_step) - len(committed),
        "discarded_at_kill": 0,
    }
    if n == 1:
        # one group votes with nobody: a step that does not commit is a fault
        checks["every_step_committed"] = len(committed) == len(every_step)
    if lead.get("raw"):
        checks["first_losses_match_raw"] = lead["raw"]["first_losses_match"]
    if "kill_group" in p:
        victim = next(
            (g for g in finished if g["group"] == p["kill_group"] and g["life"] == 1),
            None,
        )
        with open(os.path.join(scratch, f"killed_{p['kill_group']}")) as f:
            killed_t = float(f.read())
        facts["kill"] = {"t": killed_t}
        # The steps the kill itself discards - a survivor's step that was
        # in flight when the peer died, until the first short-handed
        # commit - are the protocol at work, not failures of the traffic;
        # they are counted apart (README.md, "attempted and failed").
        resumed = min(
            (s["t"] for g in first_lives for s in g["steps"]
             if s["committed"] and s["t"] > killed_t and s["participants"] < n),
            default=float("inf"),
        )
        facts["discarded_at_kill"] = sum(
            1 for s in every_step
            if not s["committed"] and killed_t - 1.0 <= s["t"] <= resumed
        )
        facts["failed"] -= facts["discarded_at_kill"]
        checks["victim_rejoined"] = victim is not None
        if victim is not None:
            first = next((s for s in victim["steps"] if s["committed"]), None)
            checks["victim_rejoined"] = first is not None
            checks["heal_streamed"] = (victim.get("fetch_stats") or {}).get("path") == "stream"
            facts["kill"].update(
                first_commit_t=first["t"] if first else None,
                ready_t=victim["ready_t"],
                fetch_stats=victim.get("fetch_stats"),
            )
    facts["checks"] = checks
    return facts


# ---------------------------------------------------------------------------
# one replica group
# ---------------------------------------------------------------------------


def worker(cell: Dict[str, Any]) -> None:
    p = {**DEFAULTS, **cell["params"]}
    scratch = cell["scratch"]
    phases: common.Phases = cell["phases"]
    group = int(os.environ["REPLICA_GROUP_ID"])
    n = int(os.environ["NUM_REPLICA_GROUPS"])
    kill_marker = os.path.join(scratch, f"killed_{group}")
    life = int(os.path.exists(kill_marker))
    victim = p.get("kill_group") == group and life == 0
    leader = group == 0

    device = common.require_tpu(p["chips_per_group"], cell["rehearse"])
    import jax
    import optax

    from torchft_tpu import FTTrainState, HostCollectives, Manager, OptimizerWrapper
    from torchft_tpu.serving import tree_digest

    phases.mark("imports_and_backend_init")
    family = common.load_family(cell["sizes"]["family"])
    cfg = family.build(cell["sizes"])
    batch, seq = cell["sizes"]["batch"], cell["sizes"]["seq"]
    tx = optax.adamw(1e-3)
    params, batches, opt_state = common.make_state(
        family, cfg, cell["seed"], group, batch, seq, p["pool"], tx
    )
    state = FTTrainState(params, tx, opt_state)
    del params, opt_state
    phases.mark("weight_init")

    loss_and_grads = common.mixed_precision_grad(family, cfg)
    lowered = jax.jit(loss_and_grads).lower(state.params, batches[0])
    if not cell["rehearse"]:
        common.require_mosaic(
            lowered, family.lowered_mosaic_calls(cfg), "ft-sync gradient step"
        )
    grad_fn = lowered.compile()
    _, grads0 = grad_fn(state.params, batches[0])
    state.warm(grads0)  # the optimizer-update executable, on copies
    # the measured program's own gradient of step 0, for the reference
    grad_norm0 = jax.jit(common.tree_norm)(grads0) if leader and life == 0 else None
    del grads0
    # what routing the run times, where the family can say: read on the
    # state after the window's close and, in a traced run, on both sides of
    # the traced steps (the reader says why an untraced run reads no more)
    read_routing = None
    if leader and life == 0 and hasattr(family, "routing"):
        read_routing = _routing_reader(family, cfg, state, batches)
    phases.mark("compile_or_cache_load")
    ready_t = time.monotonic()  # a backend and a compiled step in hand

    collectives = HostCollectives(
        timeout=timedelta(seconds=p["collectives_timeout_s"])
    )
    manager = Manager(
        collectives=collectives,
        load_state_dict=state.load_state_dict,
        state_dict=state.state_dict,
        min_replica_size=p["min_replicas"],
        timeout=timedelta(seconds=p["collectives_timeout_s"]),
        quorum_timeout=timedelta(seconds=p["join_timeout_ms"] / 1e3 + 120),
        lighthouse_addr=os.environ["TORCHFT_LIGHTHOUSE"],
        replica_id=f"bench_{group}",
    )
    optimizer = OptimizerWrapper(manager, state)

    if life == 0:
        # Start line: every group has compiled and is heartbeating before
        # any asks for a quorum, so the first quorum holds all of them.
        open(os.path.join(scratch, f"ready_{group}"), "w").close()
        deadline = time.monotonic() + 900
        while not all(
            os.path.exists(os.path.join(scratch, f"ready_{g}")) for g in range(n)
        ):
            if time.monotonic() > deadline:
                raise TimeoutError("peers never reached the start line")
            time.sleep(0.02)
    phases.mark("manager_and_start_line")

    tracer = None
    if cell["trace"] and leader:
        tracer = common.Tracer(os.path.join(scratch, "trace"), cell["rehearse"])
    span = tracer.span if tracer else common.null_span
    log = common.StepLog(span)
    steps_path = os.path.join(scratch, f"steps_{group}_{life}.jsonl")
    stop_path = os.path.join(scratch, "stop")

    def ft_step() -> Dict[str, Any]:
        i = len(log.records)
        try:
            with span("bench::zero_grad"):
                optimizer.zero_grad()
            with span("bench::grad_dispatch"):
                loss, grads = grad_fn(state.params, batches[i % len(batches)])
            with span("bench::allreduce_wait"):
                avg = manager.allreduce(grads).wait()
            with span("bench::optimizer_step"):
                committed = optimizer.step(avg)
            record = log.done(
                loss, committed=bool(committed),
                participants=manager.num_participants(),
                step=manager.current_step(),
                errored=manager.errored() is not None,
            )
        except Exception as e:  # noqa: BLE001 - a peer killed mid-rendezvous
            # raises out of the quorum by design; the step is a failed one
            record = log.done(
                None, committed=False, participants=0,
                step=manager.current_step(), error=repr(e)[:300],
            )
        with open(steps_path, "a") as f:
            f.write(json.dumps(record) + "\n")
        return record

    def full(record: Dict[str, Any]) -> bool:
        return record["committed"] and record["participants"] == n

    # -- warm-up: the window opens at the stamp of its last step -----------
    trace = None
    open_at = None
    routing: Dict[str, Any] = {}
    if life == 0:
        first = time.monotonic()
        strong = 0
        while strong < p["warmup_steps"] or time.monotonic() - first < p["warmup_seconds"]:
            strong += full(ft_step())
            if len(log.records) > 10 * p["warmup_steps"] + 1000:
                raise RuntimeError("warm-up never reached full strength")
        if cell["trace"]:
            log.drain()
            if read_routing:
                routing["traced"] = read_routing()
            traced_from = len(log.records)
            if tracer:
                tracer.start()
            for _ in range(p["trace_steps"]):
                ft_step()
            log.drain()
            if tracer:
                tracer.stop()
                trace = tracer.reduce()
                if trace:
                    trace["steps"] = p["trace_steps"]
                    trace["batches"] = [
                        (traced_from + j) % len(batches) for j in range(p["trace_steps"])
                    ]
            if read_routing:
                routing["open"] = read_routing()
            ft_step()
        collectives.pop_op_stats()  # the window's own entries from here
        open_at = len(log.records) - 1
        phases.mark("warmup_steps")
    t_open = log.records[open_at]["t"] if open_at is not None else None

    # -- the window, the kill, the rejoin ----------------------------------
    commits_in_window = 0
    seen_short = life == 1
    strong_after_rejoin = 0
    while True:
        if os.path.exists(stop_path):
            with open(stop_path) as f:
                text = f.read().strip()
            if text and manager.current_step() >= int(text):
                break
        record = ft_step()
        if record["committed"]:
            commits_in_window += 1
            if record["participants"] < n:
                seen_short = True
            elif seen_short:
                strong_after_rejoin += 1
        if victim and commits_in_window >= p["kill_after_commit"]:
            with open(kill_marker, "w") as f:
                f.write(repr(time.monotonic()))
            os.kill(os.getpid(), signal.SIGKILL)
        if leader and not os.path.exists(stop_path):
            past_window = record["t"] > t_open + cell["seconds"]
            settled = "kill_group" not in p or (
                strong_after_rejoin >= p["rejoin_commits"]
            )
            marker = os.path.join(scratch, f"killed_{p.get('kill_group')}")
            capped = os.path.exists(marker) and (
                record["t"] > _read_float(marker) + p["rejoin_cap_s"]
            )
            if past_window and (settled or capped):
                # two commits ahead: every group reads this before it gets there
                tmp = stop_path + ".tmp"
                with open(tmp, "w") as f:
                    f.write(str(manager.current_step() + 2))
                os.replace(tmp, stop_path)
    log.drain()
    phases.mark("window")
    final_step = manager.current_step()
    digest = tree_digest(state.params)
    memory_peak = common.peak_memory_bytes()
    if read_routing:
        routing["close"] = read_routing()
    op_stats = [
        {k: v for k, v in s.items() if k != "buckets"}
        for s in collectives.pop_op_stats() if s.get("op") == "allreduce"
    ]
    fetch_stats = manager.checkpoint_transport().last_fetch_stats if life == 1 else None
    manager_metrics = manager.metrics().snapshot()
    losses = log.loss_values()

    # -- after the window: the reference, and a traced run's raw loop ------
    masters_f32 = common.masters_are_f32((state.params, state.opt_state))
    state.params = state.opt_state = None  # room for the reference's copies
    reference = None
    if grad_norm0 is not None:  # group 0 stands for all: one seed, one program
        reference = common.check_first_steps(
            family, cfg, cell["seed"], group, batch, seq, p["pool"],
            losses[:p["reference_steps"] if n == 1 else 1], float(grad_norm0),
        )
    phases.mark("digest_and_reference_check")
    manager.shutdown()
    collectives.shutdown()
    phases.mark("shutdown")
    raw = None
    if cell["trace"] and n == 1 and p.get("raw_steps"):
        raw = _raw_after(cell, family, cfg, batches, losses, p)
        phases.mark("raw_loop")

    closing = {
        "group": group, "life": life, "device": device, "open_at": open_at,
        "final_step": final_step, "digest": digest,
        "memory_peak_bytes": memory_peak, "memory_stats": common.memory_stats(),
        "op_stats": op_stats,
        "fetch_stats": _plain(fetch_stats), "manager_metrics": manager_metrics,
        "losses": losses,
        "losses_finite": common.all_finite(losses),
        "reference": reference, "masters_f32": masters_f32, "raw": raw,
        "trace": trace, "ready_t": ready_t, "phases": phases.seconds,
        "tokens_per_step": family.tokens_per_step(batch, seq),
        "flops_per_step": family.flops_per_step(cfg, batch, seq),
        "flash": family.flash_calls(cfg, batch, seq),
        "family": family.facts(cfg, batch, seq),
        "routing": routing or None,
    }
    if open_at is None:
        del closing["open_at"]
    with open(os.path.join(scratch, f"life_{group}_{life}.json"), "w") as f:
        json.dump(closing, f)


def _raw_after(
    cell: Dict[str, Any], family: Any, cfg: Any, batches: List[Any],
    ft_losses: List[Optional[float]], p: Dict[str, Any],
) -> Dict[str, Any]:
    """The raw loop in this same process, from the same seed, for a few
    steps after a traced one-group run: its rate by the same estimator
    (``ft_over_raw``'s base) and its first losses against the
    transaction's."""
    import optax

    from benchmark import estimator
    from benchmark.traffic import raw as raw_kind

    batch, seq = cell["sizes"]["batch"], cell["sizes"]["seq"]
    params, _, opt_state = common.make_state(
        family, cfg, cell["seed"], 0, batch, seq, p["pool"], optax.adamw(1e-3)
    )
    step = raw_kind.build_step(family, cfg)
    log = common.StepLog(common.null_span)
    raw_kind.loop(
        step, params, opt_state, batches, log, common.null_span,
        lambda l: len(l.records) >= p["raw_steps"] + 4,
    )
    log.drain()
    losses = log.loss_values()
    numbers = estimator.window(
        log.records[3:], float("inf"), family.tokens_per_step(batch, seq)
    )
    pairs = list(zip(ft_losses[:5], losses[:5]))
    return {
        "tokens_per_s": numbers and numbers["tokens_per_s"],
        "first_losses": losses[:5], "ft_first_losses": ft_losses[:5],
        "first_losses_match": len(pairs) == 5 and all(
            a is not None and abs(a - b) <= family.LOSS_RTOL * abs(b)
            for a, b in pairs
        ),
    }


def _routing_reader(family: Any, cfg: Any, state: Any, batches: List[Any]) -> Any:
    """``() -> {name: numbers}``: the family's ``routing`` of the state as
    it stands when called, over the pool's batches, as one jitted program;
    blocks until the numbers are on the host. The program is built at the
    first call. An untraced run, the only kind that reports ``setup_s``,
    makes that call after its window has closed, beside the reference's: in
    set-up the program's load and one read before the window cost 2.0 s of
    26.8, three quarters of what ``setup_s`` may move (PERF.md section 6, PR
    42). A traced run calls it first in its warm-up, before the traced steps."""
    import jax
    import jax.numpy as jnp

    program = jax.jit(lambda params, pool: family.routing(cfg, params, jnp.stack(pool)))

    def read() -> Dict[str, Any]:
        return {
            k: v.tolist() for k, v in jax.device_get(program(state.params, batches)).items()
        }

    return read


def _read_float(path: str) -> float:
    with open(path) as f:
        return float(f.read())


def _plain(value: Any) -> Any:
    """JSON-able copy of a stats dict (numbers and strings kept)."""
    if value is None:
        return None
    return {
        k: v for k, v in value.items()
        if isinstance(v, (int, float, str, bool)) or v is None
    }
