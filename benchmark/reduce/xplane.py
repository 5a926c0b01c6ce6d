"""From the profiler's ``.xplane.pb`` to device busy and idle time, time
per operation, and the idle gaps named by what the host was doing.

What a v5e trace holds (looked at by hand, PERF.md section 6, PR 23):
one plane ``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA
Modules``, ``XLA Ops`` and ``Async XLA Ops``, and one plane
``/host:CPU`` with a line per host thread, where
``jax.profiler.TraceAnnotation`` spans (``bench::*`` from the
benchmark's files, ``torchft::*`` from the program) sit among the
runtime's own events. Both planes count nanoseconds from the start of
the profile. An ``XLA Ops`` event is named by its whole HLO line,
``%fusion.5 = f32[32,1023,50257]{...} fusion(...)``; a Pallas kernel is
a ``custom-call`` with ``custom_call_target="tpu_custom_call"``.

Busy is the union of the ``XLA Ops`` intervals (asynchronous copies run
beside them and are not counted); the window runs from the first
operation's start to the last one's end, so the profiler's own start-up
and shutdown are outside it.
"""

from __future__ import annotations

import gzip
import re
from typing import Any, Dict, Iterable, List, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench::", "torchft::")
SHORT_GAP_NS = 20_000
TOP = 10

Event = Tuple[str, float, float]  # name, start_ns, duration_ns


def read_planes(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """``{plane: {line: [(name, start_ns, duration_ns)]}}`` of an
    ``.xplane.pb`` (or ``.xplane.pb.gz``) file, with nothing but JAX."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(
                (e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events
            )
    return planes


def op_label(hlo_line: str) -> str:
    """``%transpose_jvp___.18 = (f32[384,1024,64]{..}, ..) custom-call(``
    -> ``transpose_jvp___ f32[384,1024,64] custom-call``: the operation
    without its serial number, its first output and its kind, so that the
    same operation of every layer and every step falls under one label."""
    head, _, rest = hlo_line.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    shape = re.search(r"[a-z]+\d*\[[\d,]*\]", rest)
    kind = re.search(r"\)?\s([a-z][\w\-]*)\(", rest)
    return " ".join(
        x for x in (base, shape and shape.group(0), kind and kind.group(1)) if x
    )


def is_mosaic_call(hlo_line: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in hlo_line


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def name_gap(gap: Tuple[float, float], spans: List[Event]) -> str:
    """The innermost host span (the one that started last) that was open
    at the middle of the gap."""
    middle = (gap[0] + gap[1]) / 2
    best = None
    for name, start, duration in spans:
        if start <= middle <= start + duration and (best is None or start > best[1]):
            best = (name, start)
    return best[0] if best else "host (no span)"


def reduce_planes(planes: Dict[str, Dict[str, List[Event]]]) -> Dict[str, Any]:
    """Busy seconds and window seconds averaged over the chips that ran
    anything, seconds per operation label, seconds in Mosaic custom
    calls, and idle seconds by host span."""
    spans = [
        e for line in planes.get(HOST_PLANE, {}).values() for e in line
        if e[0].startswith(SPAN_PREFIXES)
    ]
    chips = []
    for plane_name, lines in planes.items():
        ops = lines.get(OPS_LINE, []) if plane_name.startswith(DEVICE_PLANE) else []
        if not ops:
            continue
        busy = union((start, start + duration) for _, start, duration in ops)
        per_op: Dict[str, float] = {}
        mosaic_ns, mosaic_calls = 0.0, 0
        for name, _, duration in ops:
            label = op_label(name)
            per_op[label] = per_op.get(label, 0.0) + duration
            if is_mosaic_call(name):
                mosaic_ns += duration
                mosaic_calls += 1
        gaps: Dict[str, float] = {}
        for (_, end), (start, _) in zip(busy, busy[1:]):
            label = (
                "between ops (<20us)" if start - end < SHORT_GAP_NS
                else name_gap((end, start), spans)
            )
            gaps[label] = gaps.get(label, 0.0) + start - end
        chips.append({
            "busy_ns": sum(end - start for start, end in busy),
            "window_ns": busy[-1][1] - busy[0][0],
            "per_op": per_op, "gaps": gaps,
            "mosaic_ns": mosaic_ns, "mosaic_calls": mosaic_calls,
        })
    if not chips:
        raise ValueError("the trace holds no device operation")

    def mean(key: str) -> float:
        return sum(c[key] for c in chips) / len(chips) / 1e9

    def top(key: str) -> List[List[Any]]:
        total: Dict[str, float] = {}
        for c in chips:
            for label, ns in c[key].items():
                total[label] = total.get(label, 0.0) + ns / len(chips) / 1e9
        return [list(kv) for kv in sorted(total.items(), key=lambda kv: -kv[1])]

    return {
        "chips": len(chips),
        "busy_s": mean("busy_ns"),
        "window_s": mean("window_ns"),
        "mosaic_s": mean("mosaic_ns"),
        "mosaic_calls": sum(c["mosaic_calls"] for c in chips) // len(chips),
        "device_ops": top("per_op")[:TOP],
        "idle_gaps": top("gaps")[:TOP],
    }


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_planes(read_planes(path))
