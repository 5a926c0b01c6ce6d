"""From per-step timestamps to the end-to-end metrics. Pure Python.

A step record is ``{"t": seconds on the host's monotonic clock when the
step was known complete, "committed": bool, "participants": int}``. A
window is a pair of such timestamps, so both of its edges are step
boundaries and no step is half counted.
"""

from __future__ import annotations

import math
import statistics
from typing import Any, Dict, Optional, Sequence


def close_index(times: Sequence[float], t_open: float, seconds: float) -> int:
    """Index of the last timestamp at or before ``t_open + seconds``
    (-1 when none is)."""
    last = -1
    for i, t in enumerate(times):
        if t <= t_open + seconds:
            last = i
    return last


def percentile(values: Sequence[float], share: float) -> float:
    """Nearest rank: the smallest value with at least ``share`` of the
    sample at or below it."""
    ordered = sorted(values)
    return ordered[max(1, math.ceil(len(ordered) * share)) - 1]


def window(
    steps: Sequence[Dict[str, Any]], seconds: float, tokens_per_step: int
) -> Optional[Dict[str, Any]]:
    """The window's numbers from ONE group's step records, the stamp that
    opens the window first. The window closes at the last stamp at or
    before ``seconds`` after the first.

    ``tokens_per_s`` is plain: the tokens of every step COMMITTED in the
    window (a commit carries the tokens of each group whose gradients it
    averaged; a step that did not commit carries none) over the whole
    window, stalls and failed steps included. ``step_p90_ms`` is the 90th
    percentile of the stamp-to-stamp intervals, ``step_median_ms`` their
    median: a single stall moves the rate and not the median, a slower
    step moves both."""
    times = [s["t"] for s in steps]
    last = close_index(times, times[0], seconds) if times else -1
    if last < 1:
        return None
    inside = steps[1:last + 1]
    gaps = [b - a for a, b in zip(times[:last], times[1:last + 1])]
    batches = sum(s["participants"] for s in inside if s["committed"])
    return {
        "tokens_per_s": batches * tokens_per_step / (times[last] - times[0]),
        "step_p90_ms": percentile(gaps, 0.9) * 1e3,
        "step_median_ms": statistics.median(gaps) * 1e3,
        "intervals": len(gaps),
        "group_commits": batches,
        "t_close": times[last],
        "window_s": times[last] - times[0],
    }
