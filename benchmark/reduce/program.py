"""What the program's own sinks hold, from a run's facts, for the readers
of ``layer_metrics/``: a ``Metrics`` timer of group 0's manager, a named
kernel or a ``jax.named_scope`` path in the trace. Each returns None where
the program has no such timer or name (the parent of the PR that added
it), and the metric is then left out.

A timer is the host-side sink of the span ``torchft::<name>``
(torchft_tpu/profiling.py): the same ``with``, the same seconds, read
here without a trace.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def timer_p50_ms(facts: Dict[str, Any], name: str) -> Optional[float]:
    """Median milliseconds a call of the timer ``name`` (the last 512
    calls at most, so the first quorum's rendezvous and the warm-up's
    compiles do not weigh: the window alone has over a hundred steps)."""
    timer = (facts.get("manager_metrics") or {}).get("timers_s", {}).get(name)
    if not timer or not timer.get("n"):
        return None
    return timer["p50"] * 1e3


def wait_ms(facts: Dict[str, Any], name: str) -> Optional[float]:
    """Median milliseconds a step the trainer thread was blocked in the
    wait ``name``. The program samples only the calls that really block
    (a step asks several times, and a settled future holds nobody), so
    where fewer than half of the steps blocked at all the median step
    waited 0; else it is the median blocking call."""
    metrics = facts.get("manager_metrics") or {}
    timer = metrics.get("timers_s", {}).get(name)
    if timer is None:
        return None
    counters = metrics.get("counters", {})
    steps = counters.get("commits", 0) + counters.get("aborts", 0)
    if 2 * timer["n"] < steps or not timer["n"]:
        return 0.0
    return timer["p50"] * 1e3


def kernel_ms(facts: Dict[str, Any], name: str) -> Optional[float]:
    """Device milliseconds a traced step in the Mosaic kernel ``name``:
    every call of it, from the trace's ``kernels_s``. None where the run
    was not traced, the trace has no such table, or no such kernel ran."""
    kernels_s = (facts.get("trace") or {}).get("kernels_s")
    if not kernels_s or not kernels_s.get(name):
        return None
    return kernels_s[name] / facts["trace"]["steps"] * 1e3


def scope_ms(
    facts: Dict[str, Any], path: str = "", direction: Optional[str] = None
) -> Optional[float]:
    """Device milliseconds a traced step in the operations whose scope
    path (``reduce/spans.py``: ``attn/qk_norm``, ``mlp/moe/experts``)
    holds ``path`` as a run of whole names - ``moe`` finds ``mlp/moe/
    router`` and not ``mlp/moe_gate`` - in one ``direction`` (``forward``,
    ``backward``, ``optimizer``, ``unscoped``) or in all. The empty path
    is every operation of the direction. None where the trace has no such
    table or nothing ran under the path."""
    paths_s = (facts.get("trace") or {}).get("paths_s")
    if not paths_s:
        return None
    seconds = sum(
        s
        for which, paths in paths_s.items() if direction in (None, which)
        for found, s in paths.items() if f"/{path}/" in f"/{found}/" or not path
    )
    return seconds / facts["trace"]["steps"] * 1e3 if seconds else None
