"""Lightweight step-level metrics for the fault-tolerance runtime.

The reference's only progress metric is ``batches_committed``
(reference torchft/manager.py:642-653); observability is otherwise logs +
the dashboard. This module closes the SURVEY.md §5 tracing gap with
in-process counters/timers the Manager feeds at the transaction's
boundaries — no external dependencies, negligible overhead (a deque append
per event), and a one-call JSON-able snapshot for progress loops,
dashboards, or tests::

    manager.metrics().snapshot()
    # {"counters": {"commits": 98, "aborts": 2, "heals": 1, ...},
    #  "timers_s": {"quorum": {"n":100,"p50":0.0012,"p90":0.003,...}, ...},
    #  "process": {"counters": {"compiles": 9, "recompiles": 0, ...},
    #              "timers_s": {"ready": {"n":1,"total_s":31.2,...}, ...}}}

(``process``: the way to the first commit and every compile, startup.py).

``Metrics.timed(name)`` is the step path's span primitive: one ``with``
records the timer ``name`` here and shows as ``torchft::<name>`` in an
active profiler capture (profiling.py), stamped with ``Metrics.step``.
"""

from __future__ import annotations

import threading
import time
from collections import defaultdict, deque
from typing import Any, Dict, Optional

from .profiling import timed_span


class _Timer:
    """Bounded reservoir of durations with percentile snapshots."""

    def __init__(self, maxlen: int = 512) -> None:
        self._samples: deque = deque(maxlen=maxlen)
        self.count = 0
        self.total_s = 0.0

    def record(self, seconds: float) -> None:
        self._samples.append(seconds)
        self.count += 1
        self.total_s += seconds

    def first(self) -> float:
        """The first duration ever recorded; 0.0 where there is none or
        the reservoir has rolled past it."""
        if not self.count or self.count != len(self._samples):
            return 0.0
        return self._samples[0]

    def snapshot(self) -> Dict[str, float]:
        samples = sorted(self._samples)
        if not samples:
            return {"n": 0}

        def pct(p: float) -> float:
            return samples[min(int(p * len(samples)), len(samples) - 1)]

        return {
            "n": self.count,
            "total_s": round(self.total_s, 6),
            "p50": round(pct(0.50), 6),
            "p90": round(pct(0.90), 6),
            "max": round(samples[-1], 6),
        }


class _EventWindow:
    """Bounded reservoir of event timestamps with a trailing-window rate.

    The rolling-rate primitive behind signals like the churn estimate
    (reconfigures per minute): ``mark()`` appends a monotonic timestamp,
    ``rate_per_min(window_s)`` counts events inside the trailing window
    and divides by the window actually OBSERVED — a process younger than
    the window divides by its own age, so early-life rates aren't
    diluted toward zero by time that never happened."""

    def __init__(self, maxlen: int = 512) -> None:
        self._stamps: deque = deque(maxlen=maxlen)
        self._born = time.monotonic()
        self.count = 0

    def mark(self) -> None:
        self._stamps.append(time.monotonic())
        self.count += 1

    def rate_per_min(self, window_s: float = 600.0) -> float:
        now = time.monotonic()
        cutoff = now - window_s
        n = sum(1 for t in self._stamps if t >= cutoff)
        observed = min(window_s, now - self._born)
        if self._stamps and len(self._stamps) == self._stamps.maxlen:
            # Reservoir rolled over: the window may predate the oldest
            # retained stamp; never divide by time we can't account for.
            observed = min(observed, now - self._stamps[0])
        return 0.0 if observed <= 0 else n * 60.0 / observed

    def snapshot(self, window_s: float = 600.0) -> Dict[str, float]:
        return {
            "n": self.count,
            "rate_per_min": round(self.rate_per_min(window_s), 6),
        }


class Metrics:
    """Thread-safe counters + timers + event windows. All methods are
    cheap enough for the hot path; reading is lock-held but O(window)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = defaultdict(int)
        self._timers: Dict[str, _Timer] = {}
        self._events: Dict[str, _EventWindow] = {}
        # the owner's step (the Manager keeps it current): the stat that
        # pairs a ``timed`` span on any thread with the trainer's step
        self.step: Optional[int] = None
        # the process's start-up record (startup.py), where the owner is
        # a Manager: what the snapshot carries as ``process``
        self.process: Optional[Any] = None

    def incr(self, name: str, by: int = 1) -> None:
        with self._lock:
            self._counters[name] += by

    def record(self, name: str, seconds: float) -> None:
        with self._lock:
            timer = self._timers.get(name)
            if timer is None:
                timer = self._timers[name] = _Timer()
            timer.record(seconds)

    def first_sample(self, name: str) -> float:
        """The first duration the timer ``name`` ever recorded; 0.0 where
        it has none, or has rolled past it (512 samples)."""
        with self._lock:
            timer = self._timers.get(name)
            return 0.0 if timer is None else timer.first()

    def mark(self, name: str) -> None:
        """Records one occurrence of a timestamped event (for rolling
        rates — counters answer "how many ever", this answers "how often
        lately")."""
        with self._lock:
            window = self._events.get(name)
            if window is None:
                window = self._events[name] = _EventWindow()
            window.mark()

    def rate_per_min(self, name: str, window_s: float = 600.0) -> float:
        """Trailing-window rate (events/min) of a ``mark``ed event; 0.0
        for a name never marked."""
        with self._lock:
            window = self._events.get(name)
            return 0.0 if window is None else window.rate_per_min(window_s)

    def declare(self, *names: str) -> None:
        """Creates the timers ``names`` empty, so that a region which has
        not run yet reads ``{"n": 0}`` in the snapshot instead of being
        absent (a wait that never had to wait is a finding, not a gap)."""
        with self._lock:
            for name in names:
                self._timers.setdefault(name, _Timer())

    def timed(
        self, name: str, span: Optional[str] = None, **stats: int
    ) -> "_TimedBlock":
        """One timed region, two sinks: the timer ``name`` and the
        profiler span ``torchft::<name>``, over the same statements.
        ``span`` names the span otherwise, for a region that belongs
        under another's name (``send_checkpoint/stage``); ``stats`` go
        on the span beside the step."""
        return _TimedBlock(self, name, span or name, stats)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            snap = {
                "counters": dict(self._counters),
                "timers_s": {
                    name: t.snapshot() for name, t in self._timers.items()
                },
                "events": {
                    name: w.snapshot() for name, w in self._events.items()
                },
            }
        if self.process is not None:
            snap["process"] = self.process.snapshot()
        return snap


class _TimedBlock(timed_span):
    def __init__(
        self, metrics: Metrics, name: str, span: str, stats: Dict[str, int]
    ) -> None:
        super().__init__("torchft::" + span, metrics.step, **stats)
        self._metrics = metrics
        self._name = name

    def __exit__(self, *exc: object) -> None:
        super().__exit__(*exc)
        self._metrics.record(self._name, self.seconds)
