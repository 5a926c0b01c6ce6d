"""Tracing and profiling: the one span primitive of the step path, and
the windowed profiler capture.

The reference has no tracing subsystem (SURVEY.md section 5); this module
is ours. A timed region of the program is written once, as one ``with``,
and shows under one name in every sink:

- ``span("torchft::<name>", step)`` is a ``jax.profiler.TraceAnnotation``:
  a host event on the profiler's clock (the device trace's), carrying the
  manager's step as the stat ``step``. With no capture active it is a
  flag test.
- ``timed_span(name, step)`` is that span with one clock pair around the
  same statements: ``seconds`` is what the host-side sink files. The
  three below are built on it.
- ``Metrics.timed("<name>")`` (metrics.py) enters ``torchft::<name>`` and
  records the same seconds under the timer ``<name>``.
- a collective's op context (``OpStatsMixin._op``, collectives.py) enters
  ``torchft::<op>`` with ``torchft::<op>/<phase>`` nested in it and
  records the same seconds under the ``pop_op_stats()`` keys: ``op_s``
  for the op, the phase's name for a phase (``pack``, ``ready`` - the
  DEVICE still computing what the op will read -, ``d2h``, ``host_copy``,
  ``ring``, ``h2d``), all on the exchange thread.
- the state transfer (checkpointing.py): on the healer's quorum thread,
  nested in ``torchft::heal_fetch``, ``/meta``, ``/stream`` and ``/h2d``
  (``/striped``, ``/single`` on the pickled fallbacks), filed in the
  transport's ``last_fetch_stats`` as ``meta_s``, ``fetch_s``, ``h2d_s``;
  on the donor's SERVING threads ``torchft::send_checkpoint/stage`` and
  one ``torchft::send_checkpoint/serve`` a range, filed in the owning
  manager's timers ``send_stage``, ``send_serve`` and counter
  ``send_bytes``. They share ``torchft::send_checkpoint``'s name and
  step, not its interval: the quorum thread only publishes.

The names an operator sees in an XProf capture, and the ``Metrics``
timer or op-stats key each equals, are tabled in docs/OPERATIONS.md
("Profiling"). On the device side the model's layers, the optimizer and
the flash kernels carry ``jax.named_scope`` / kernel names instead
(models/transformer.py, train_state.py, ops/flash_attention.py).

A capture is started by whoever wants one: ``jax.profiler.start_trace``
directly, or the step-windowed ``Profiler`` below::

    prof = Profiler(logdir="/tmp/trace", start_step=10, num_steps=5)
    manager = Manager(..., profiler=prof)   # or prof.on_step(step) by hand

or with no code, ``TORCHFT_PROFILE_DIR=/tmp/trace TORCHFT_PROFILE_START=10
TORCHFT_PROFILE_STEPS=5 python train.py``. ``jax.profiler`` is imported
on first use: the launcher's parent and the lighthouse hold no JAX.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Optional

from . import startup  # half made here (it imports us): used at call time only

logger = logging.getLogger(__name__)

_ENV_DIR = "TORCHFT_PROFILE_DIR"
_ENV_START = "TORCHFT_PROFILE_START"
_ENV_STEPS = "TORCHFT_PROFILE_STEPS"


def span(name: str, step: Optional[int] = None, **stats: int):
    """Named host-track span; shows up in an active jax profiler capture
    under ``name``, with ``step`` (where given) and ``stats`` (a range's
    ``bytes``) as stats of the event.

    Usage: ``with span("torchft::quorum", step): ...``
    """
    import jax.profiler

    if not startup._listening:  # the package's first own use of jax
        startup.listen()
    if step is None:
        return jax.profiler.TraceAnnotation(name, **stats)
    return jax.profiler.TraceAnnotation(name, step=step, **stats)


class timed_span:
    """``span(name, step)`` and one ``perf_counter`` pair over the same
    statements: after the ``with``, ``seconds`` is what the span covers
    in a capture, for the sink a run without a capture reads."""

    def __init__(
        self, name: str, step: Optional[int] = None, **stats: int
    ) -> None:
        self._span = span(name, step, **stats)
        self.seconds = 0.0

    def __enter__(self) -> "timed_span":
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._span.__exit__(*exc)


class Profiler:
    """Windowed jax profiler capture keyed on the manager's step counter.

    The capture starts when ``on_step(step)`` first sees
    ``step >= start_step`` and stops ``num_steps`` steps later (or at
    ``shutdown()``). Thread-safe; start/stop failures are logged, never
    raised — profiling must not take down training.
    """

    def __init__(
        self,
        logdir: str,
        start_step: int = 1,
        num_steps: int = 5,
    ) -> None:
        self.logdir = logdir
        self.start_step = start_step
        self.num_steps = num_steps
        self._lock = threading.Lock()
        self._state = "idle"  # idle -> active -> done
        self._stop_after: Optional[int] = None

    @classmethod
    def from_env(cls) -> Optional["Profiler"]:
        """Build from TORCHFT_PROFILE_* env vars; None when unset."""
        logdir = os.environ.get(_ENV_DIR)
        if not logdir:
            return None
        return cls(
            logdir,
            start_step=int(os.environ.get(_ENV_START, "1")),
            num_steps=int(os.environ.get(_ENV_STEPS, "5")),
        )

    def on_step(self, step: int) -> None:
        """Advance the capture window; called once per training step."""
        with self._lock:
            if self._state == "idle" and step >= self.start_step:
                self._start(step)
            elif (
                self._state == "active"
                and self._stop_after is not None
                and step >= self._stop_after
            ):
                self._stop()

    def shutdown(self) -> None:
        """Flush an in-flight capture (e.g. at trainer exit)."""
        with self._lock:
            if self._state == "active":
                self._stop()

    @property
    def state(self) -> str:
        return self._state

    # -- internal (lock held) --

    def _start(self, step: int) -> None:
        import jax.profiler

        try:
            jax.profiler.start_trace(self.logdir)
        except Exception as e:  # noqa: BLE001 - observability must not kill
            logger.warning("profiler start failed: %s", e)
            self._state = "done"
            return
        self._state = "active"
        # Window from the step the capture ACTUALLY started at — a replica
        # that resumes/heals past start_step still profiles num_steps.
        self._stop_after = step + self.num_steps
        logger.info(
            "profiling %d steps to %s", self.num_steps, self.logdir
        )

    def _stop(self) -> None:
        import jax.profiler

        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            logger.warning("profiler stop failed: %s", e)
        self._state = "done"
        logger.info("profile written to %s", self.logdir)
