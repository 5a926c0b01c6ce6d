"""Process-level helpers for entry scripts: where the persistent compile
cache lives, and the hot-spare standby start line.

Backend selection is ``JAX_PLATFORMS`` alone: ``tpu`` makes a missing
chip an initialisation error instead of JAX's own quiet CPU fallback,
``cpu`` is what the tests and the Quickstart use.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional

# The one compile cache of a checkout: every entry point — chip_smoke.py,
# examples/train_ddp.py, the launcher's children, benchmark/run.py —
# resolves here, so a restarted group (or the next run) finds what the
# last one compiled. The path is part of JAX's cache key; it must not
# move with the job, the pid or the time.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def standby_gate() -> None:
    """Hot-spare start line. When ``TORCHFT_STANDBY_FILE`` is set, the
    process is a pre-warmed STANDBY: call this after imports and jit
    warm-up but BEFORE creating the Manager (a standby must not join
    quorums or heartbeat), and it blocks until the supervisor activates
    the process by creating the file. No-op for normal processes.

    This is the process-level analog of ``WorldSizeMode.FIXED_WITH_SPARES``:
    a cold restart pays interpreter + library import + compile before it
    can heal (32.8 s before a restarted group is ready on the chip,
    ROADMAP S5); a promoted standby pays none of it. The launcher's
    ``--hot-spare`` mode manages the standby lifecycle
    (torchft_tpu.launcher).

    Deployment constraint: the standby warms up on ITS OWN resources.
    On a host whose accelerator is exclusively owned by the primary
    (single-chip TPU hosts), a standby cannot warm the same chip — run
    standbys on separate hosts (the per-host-per-group topology this
    framework targets) or accept cold restarts there.

    If the supervisor dies without activating us (hard kill: its cleanup
    never runs), exit instead of leaking a fully-warmed parked process.

    Reaching the gate means warm-up is COMPLETE, so a ``<path>.warm``
    marker is touched on entry: the supervisor reads it to tell a
    fully-warmed spare from one still importing/compiling — the
    warm-deadline re-arm policy (a half-warmed spare on a saturated host
    gets its idle priority lifted so the NEXT kill finds it parked here,
    not mid-import) and promotion logging both key off it.

    On return the process's start-up record (startup.py) begins again:
    its ``ready`` is then what the promotion cost."""
    path = os.environ.get("TORCHFT_STANDBY_FILE")
    if not path:
        return
    import sys
    import time

    try:
        open(path + ".warm", "w").close()
    except OSError:
        pass  # marker is advisory; the gate still works without it
    supervisor = os.getppid()
    while not os.path.exists(path):
        if os.getppid() != supervisor:
            sys.exit(0)  # orphaned: supervisor is gone, nobody can promote us
        time.sleep(0.05)
    # The start-up record begins again here: ``ready`` is what the
    # promotion cost, not the standby's idle life. The launcher writes
    # which restart this is and when it saw the death into the file.
    from . import startup

    restart, died_at = 0, None
    try:
        with open(path) as f:
            fields = f.read().split()
        restart, died_at = int(fields[0]), float(fields[1])
    except (OSError, ValueError, IndexError):
        pass  # activated by hand: an empty file
    startup.record().promoted(restart, died_at)


def standby_should_warm() -> bool:
    """Whether a standby should run the full AOT warm-up before parking
    (``FTTrainState.warm`` + ``HostCollectives.prewarm``): default yes —
    promotion is then quorum join + weight fetch only. Set
    ``TORCHFT_STANDBY_WARM=0`` to park right after imports instead (e.g.
    when the warm-up itself would fight the primary for a single
    accelerator)."""
    return os.environ.get("TORCHFT_STANDBY_WARM", "1") != "0"


def standby_warm_deadline_s() -> float:
    """How long a supervisor lets a niced standby warm before lifting it
    to normal priority (``TORCHFT_STANDBY_WARM_DEADLINE_S``, default 20).
    On a saturated host an idle-priority warm-up can starve forever —
    the round-3/round-5 hot-spare regression: every promotion found a
    HALF-warmed spare and paid the full import+compile on the heal
    critical path. Lifting after a bounded grace costs a few seconds of
    measured contention once per re-arm; an unwarmed spare costs ~15 s on
    EVERY subsequent kill of that group."""
    try:
        return float(os.environ.get("TORCHFT_STANDBY_WARM_DEADLINE_S", "20"))
    except ValueError:
        return 20.0


def heal_boost_nice() -> int:
    """Nice-level boost (``TORCHFT_HEAL_BOOST``, default 5; ``0``
    disables) a PRIVILEGED supervisor gives a cold-restarting worker
    while it heals, de-boosting at its first committed step (or a 60 s
    hard cap). Rationale: on a shared host the restarting member is the
    cohort's degraded one — survivors keep committing without it — so a
    bounded slice of their CPU during the heal shortens the window the
    cohort runs without redundancy; measured on a 2-CPU 4-group box it
    roughly halves the cold import+compile path. Supervisors must gate
    it on the same capability probe as standby renicing (boosting needs
    CAP_SYS_NICE / root / RLIMIT_NICE)."""
    try:
        return max(0, int(os.environ.get("TORCHFT_HEAL_BOOST", "5")))
    except ValueError:
        return 5


def compilation_cache_dir(
    environ: Optional[Mapping[str, str]] = None,
) -> Optional[str]:
    """The cache directory this program must set in code, or None when
    ``JAX_COMPILATION_CACHE_DIR`` already places the cache from outside
    (JAX reads that variable itself; the program then sets no other)."""
    environ = os.environ if environ is None else environ
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    return COMPILE_CACHE_DIR


def apply_compilation_cache_env() -> None:
    """Turns on JAX's persistent compilation cache: at
    ``JAX_COMPILATION_CACHE_DIR`` where that is set, else at the fixed
    in-checkout :data:`COMPILE_CACHE_DIR`.

    Heal latency on a restarted replica is dominated by process restart +
    re-jit, not weight transfer; with the cache on, the restarted process
    loads the executables its predecessor compiled."""
    import jax

    from . import startup

    startup.listen()  # the cache's hits and misses, from its first
    path = compilation_cache_dir()
    if path is not None:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every executable: the default thresholds skip sub-second
    # compiles, but at heal time even those are re-paid under restart
    # contention.
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
