"""Group 0's start-up record from a run's facts, for the readers of
``layer_metrics/startup_*``: the key ``process`` of its manager's
``Metrics`` snapshot (``torchft_tpu/startup.py``: the process's way from
its spawn to its first commit, each interval a timer of one sample, and
JAX's compile events up to there). None where the program keeps no such
record (the parent of the PR that added it), and the metric is left out.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def record(facts: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"seconds": {interval: s}, "counters": {name: n}}`` of a record
    that closed (the first commit came); None else."""
    process = (facts.get("manager_metrics") or {}).get("process")
    if not process or not process.get("timers_s", {}).get("ready", {}).get("n"):
        return None
    return {
        "seconds": {
            name: timer["total_s"]
            for name, timer in process["timers_s"].items() if timer.get("n")
        },
        "counters": process.get("counters", {}),
    }
