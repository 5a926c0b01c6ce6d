"""A process's way to its first commit, timed from inside the program.

After every restart an operator asks how long it took until the group
committed again, and whether it was the backend, a compile that missed
the cache, the rendezvous or the transfer. One record a process answers
it: the intervals below, seconds on the host's monotonic clock, closed at
the Manager's first vote that passed and logged there in one line
(``ready in 31.2 s: spawn_to_import 14.9, ...``, INFO, logger
``torchft_tpu.manager``). It needs no capture: the record is the key
``process`` of ``manager.metrics().snapshot()``, ``counters`` and
``timers_s`` in ``Metrics.snapshot()``'s shapes, each interval a timer of
one sample (read its ``total_s``).

- ``spawn_to_import``: the process's start to the first line of
  ``import torchft_tpu``. The launcher stamps the child's environment
  with the wall time of its ``Popen`` (``SPAWN_STAMP``); a process
  started by hand reads its own start from the OS. Which of this and the
  next holds the backend's start is the trainer's order of imports: a
  trainer that brings the backend up before it imports the package has
  it here, one that imports the package first (the benchmark's worker,
  through ``platform.apply_compilation_cache_env``) has it in the next.
- ``import_to_manager``: to the entry of ``Manager.__init__``: the
  package's own import, then the trainer's set-up (weights, the step's
  compile).
- ``manager_init``: ``Manager.__init__`` whole (native manager,
  checkpoint server, store): the span ``torchft::startup/manager_init``.
- ``first_quorum``: the FIRST sample of the manager's timer ``quorum``
  plus the first ``reconfigure`` (the first ``torchft::quorum`` of a
  capture): in the timers they are one sample of 512 each.
- ``heal``: the first ``heal_fetch`` plus the first ``heal_apply``, 0
  where the life began without one.
- ``first_step``: what is left of ``Manager.__init__``'s return to the
  first commit: a start line, the first gradient, its exchange, the vote,
  and every step that did not commit before one did.
- ``ready``: the process's start to the first vote that passed; the six
  above sum to it.
- ``death_to_spawn`` (a restarted life only): from the launcher seeing
  the old process dead to its ``Popen`` of this one, or to this standby's
  promotion. The log line of such a life says it, and which restart of
  its group it is (the counter ``restart``): ``ready in 9.8 s (restart 2,
  0.051 s after the death was seen): ...``.

After a hot spare's promotion (``platform.standby_gate()`` returns) the
record starts again there, so ``ready`` is what the promotion cost and
not the standby's idle life.

**Compiles.** JAX states each compile as it happens (``jax.monitoring``)
and ``listen()`` files it in the record's ``Metrics``: the timer
``compile`` (one sample a program, compiled or loaded from the persistent
cache, for the whole life of the process), the counters ``compiles``,
``compile_cache_hits``, ``compile_cache_misses`` and ``recompiles``: a
function this process has compiled before, compiled again AFTER the first
commit, logged at WARNING with its name, the seconds and the manager's
step - the answer to "which step recompiled" that a 40 s step otherwise
hides in a percentile. A ``<lambda>`` never counts. Before the first
commit nothing does: a start-up compiles one name at many shapes by
design (a loss a case, JAX's own ``jit(add)`` a shape), and a process
that builds no Manager never asks which step it was; past it JAX's own
eager programs count like any other (``jnp.ones`` at a new shape is a
compile inside that step). Up to the first
commit the same events also sum into ``startup_compile`` (seconds),
``startup_cache_hits`` and ``startup_cache_misses``.

The package imports ``jax`` lazily everywhere, and listening does not
change that: ``listen()`` registers where the process already holds
``jax`` - at the package's import, else at the package's first own use of
it (``platform.apply_compilation_cache_env``, ``FTTrainState.__init__``,
``profiling.span``, so ``Manager.__init__``'s) - once, and never in the
launcher's parent or the lighthouse. A trainer
that compiles before it first touches the package loses those compiles.
"""

from __future__ import annotations

import time

_IMPORTED = time.monotonic()  # torchft_tpu/__init__.py imports this module first

import contextlib  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, Iterator, Optional, Tuple  # noqa: E402

from .metrics import Metrics  # noqa: E402
from .profiling import timed_span  # noqa: E402

logger = logging.getLogger(__name__)

# Set by the launcher on every child, never by a user:
# "<launcher pid> <Popen unix s> <restart> [<death seen unix s>]".
SPAWN_STAMP = "TORCHFT_SPAWN_STAMP"

_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def spawn_stamp(restart: int = 0, died_at: Optional[float] = None) -> str:
    """``SPAWN_STAMP``'s value for a child this process is about to
    start: now, which restart of its group it is, and when the old
    process was seen dead (both on this process's wall clock)."""
    stamp = f"{os.getpid()} {time.time()!r} {restart}"
    return stamp if died_at is None else f"{stamp} {died_at!r}"


def _read_stamp() -> Optional[Tuple[float, int, Optional[float]]]:
    """(spawned, restart, died) of the launcher's stamp; None where there
    is none or it was set for another process (a grandchild inherits its
    parent's environment, not its start)."""
    fields = os.environ.get(SPAWN_STAMP, "").split()
    try:
        if int(fields[0]) != os.getppid():
            return None
        died = float(fields[3]) if len(fields) > 3 else None
        return float(fields[1]), int(fields[2]), died
    except (IndexError, ValueError):
        return None


def _os_age_s() -> float:
    """Seconds since the OS started this process (``/proc``, to a clock
    tick); 0.0 where it cannot say."""
    try:
        with open("/proc/self/stat") as f:
            # the fields after "pid (comm)": starttime is the 22nd in all
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class StartupRecord:
    """One life's record (the module's docstring). ``started`` and
    ``imported`` are stamps of ``time.monotonic()``."""

    def __init__(
        self,
        started: float,
        imported: float,
        restart: int = 0,
        death_to_spawn: Optional[float] = None,
    ) -> None:
        self.metrics = Metrics()  # the process's own: compiles, intervals
        self._lock = threading.Lock()
        self._started = started
        self._imported = imported
        self._entered: Optional[float] = None  # Manager.__init__'s entry
        self._built: Optional[float] = None  # and its return
        self._closed = False
        # compile seconds and counts while the record is open
        self._sums: Dict[str, float] = {
            "compile_s": 0.0, "cache_hits": 0, "cache_misses": 0,
        }
        self._compiled: Dict[str, int] = {}  # fun_name: times, the life's
        # the manager's Metrics, for the step a recompile is logged with
        self._stepper: Optional[Metrics] = None
        self._restarted(restart, death_to_spawn)

    def _restarted(self, restart: int, death_to_spawn: Optional[float]) -> None:
        """This life is the ``restart``-th of its group (0: the first),
        begun ``death_to_spawn`` seconds after the last one's death."""
        self._restart = restart
        self._death_to_spawn = death_to_spawn
        if restart:
            self.metrics.incr("restart", restart)
        if death_to_spawn is not None:
            self.metrics.record("death_to_spawn", death_to_spawn)

    @classmethod
    def of_this_process(cls) -> "StartupRecord":
        stamp = _read_stamp()
        if stamp is None:
            return cls(min(_IMPORTED, time.monotonic() - _os_age_s()), _IMPORTED)
        spawned, restart, died = stamp
        return cls(
            min(_IMPORTED, time.monotonic() - (time.time() - spawned)),
            _IMPORTED, restart,
            None if died is None else spawned - died,
        )

    def promoted(self, restart: int = 0, died_at: Optional[float] = None) -> None:
        """The standby this process was is now the group's primary: the
        record starts again here (``platform.standby_gate``), as the
        ``restart``-th life of its group. What the life has compiled
        stays."""
        with self._lock:
            self._started = self._imported = time.monotonic()
            self._entered = self._built = None
            self._sums = dict.fromkeys(self._sums, 0)
        # a standby's own stamp has neither
        self._restarted(restart, None if died_at is None else time.time() - died_at)

    @contextlib.contextmanager
    def manager_init(self) -> Iterator[None]:
        """Around ``Manager.__init__``: the first one of an open record
        is its ``manager_init``."""
        first = self._entered is None and not self._closed
        if first:
            self._entered = time.monotonic()
        with timed_span("torchft::startup/manager_init"):
            yield
        if first:
            self._built = time.monotonic()

    def bind(self, metrics: Metrics) -> None:
        """``metrics`` is the Manager's: its snapshot carries this record
        as ``process``, and its ``step`` dates a recompile."""
        metrics.process = self
        self._stepper = metrics

    def close(self, metrics: Metrics) -> Optional[str]:
        """At the first vote that passed: files the intervals (the first
        samples from ``metrics``, the Manager's) and returns the log
        line; None where the record was closed before."""
        now = time.monotonic()
        with self._lock:
            if self._closed:
                return None
            self._closed = True
            sums = self._sums
        entered = self._imported if self._entered is None else self._entered
        built = entered if self._built is None else self._built
        first = metrics.first_sample
        parts = {
            "spawn_to_import": self._imported - self._started,
            "import_to_manager": entered - self._imported,
            "manager_init": built - entered,
            "first_quorum": first("quorum") + first("reconfigure"),
            "heal": first("heal_fetch") + first("heal_apply"),
        }
        ready = now - self._started
        parts["first_step"] = ready - sum(parts.values())
        for name, seconds in parts.items():
            self.metrics.record(name, seconds)
        self.metrics.record("ready", ready)
        self.metrics.record("startup_compile", sums["compile_s"])
        for name in ("cache_hits", "cache_misses"):
            self.metrics.incr("startup_" + name, sums[name])
        life = ""  # which life of its group this is, where not the first
        if self._restart:
            life = f" (restart {self._restart}"
            if self._death_to_spawn is not None:
                life += f", {self._death_to_spawn:.3f} s after the death was seen"
            life += ")"
        return (
            f"ready in {ready:.1f} s{life}: "
            f"spawn_to_import {parts['spawn_to_import']:.1f}, "
            f"import_to_manager {parts['import_to_manager']:.1f} "
            f"(compile {sums['compile_s']:.1f} s, "
            f"{sums['cache_hits']} hits, {sums['cache_misses']} misses), "
            f"manager_init {parts['manager_init']:.2f}, "
            f"first_quorum {parts['first_quorum']:.2f}, "
            f"heal {parts['heal']:.1f}, first_step {parts['first_step']:.1f}"
        )

    def snapshot(self) -> Dict[str, Any]:
        snap = self.metrics.snapshot()
        return {"counters": snap["counters"], "timers_s": snap["timers_s"]}

    # -- JAX's compile events (any thread that compiles) --

    def compiled(self, fun_name: str, seconds: float) -> None:
        self.metrics.record("compile", seconds)
        self.metrics.incr("compiles")
        with self._lock:
            closed = self._closed
            if not closed:
                self._sums["compile_s"] += seconds
            times = self._compiled[fun_name] = self._compiled.get(fun_name, 0) + 1
        if closed and times > 1 and "<lambda>" not in fun_name:
            self.metrics.incr("recompiles")
            step = None if self._stepper is None else self._stepper.step
            logger.warning(
                "recompiled %s in %.3f s at step %s: compile %d of it in "
                "this process", fun_name, seconds, step, times,
            )

    def cache(self, hit: bool) -> None:
        name = "cache_hits" if hit else "cache_misses"
        self.metrics.incr("compile_" + name)
        with self._lock:
            if not self._closed:
                self._sums[name] += 1


_record: Optional[StartupRecord] = None
_listening = False
_lock = threading.Lock()  # the record's making and the registration


def record() -> StartupRecord:
    """This process's record."""
    global _record
    if _record is None:
        with _lock:
            if _record is None:
                _record = StartupRecord.of_this_process()
    return _record


# JAX calls the two below inside its own compile, on whichever thread
# compiles: like the Profiler, they must not take down training.


def _on_duration(event: str, seconds: float, **kwargs: Any) -> None:
    if event != _COMPILE:
        return
    try:
        record().compiled(str(kwargs.get("fun_name", "")), seconds)
    except Exception:  # noqa: BLE001
        logger.exception("start-up record: a compile went uncounted")


def _on_event(event: str, **kwargs: Any) -> None:
    if event != _HIT and event != _MISS:
        return
    try:
        record().cache(event == _HIT)
    except Exception:  # noqa: BLE001
        logger.exception("start-up record: a cache event went uncounted")


def listen() -> None:
    """Registers the two listeners with ``jax.monitoring``, once, where
    this process holds ``jax``; a process that does not compiles nothing."""
    global _listening
    if _listening or "jax" not in sys.modules:
        return
    with _lock:
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
