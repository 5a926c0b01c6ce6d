#!/usr/bin/env python3
"""From the profiler's ``.xplane.pb`` to the program's own names: host
seconds per ``torchft::*`` / ``bench::*`` span, and device seconds per
scope class, per scope path and per Mosaic kernel. ``common.Tracer.reduce``
keeps the three device tables of every traced run (``facts["trace"]``:
``scopes_s``, ``paths_s``, ``kernels_s``); by hand:

    python3 benchmark/reduce/spans.py <trace.xplane.pb[.gz]> [--steps N]

What a v5e trace holds beyond what ``xplane.py`` reads (looked at by hand,
PR 24): a host span made with ``TraceAnnotation(name, step=n)`` comes back
under its bare name with ``step`` as a stat of the event; an ``XLA Ops``
event is named by its HLO line, and the *metadata* of that name carries
the stat ``tf_op`` - JAX's ``op_name``, the scope path, e.g.
``jit(loss_and_grads)/transpose(jvp(attn))/flash_bwd/pallas_call:``.
``jax.profiler.ProfileData`` shows an event's own stats and not its
metadata's, so this file reads the protobuf's wire format itself (the
fields of ``XSpace`` it needs, nothing installed).

Scope classes, from the names the program gives with ``jax.named_scope``
and from nothing else - no list of a model's scopes is kept here:
``backward`` is anything under JAX's ``transpose(...)``; ``optimizer``
anything under a scope called ``optimizer`` (the one name the training
state gives its update, train_state.py); ``forward`` anything else that
is under a name at all; ``unscoped`` the rest: operations the compiler
made under no name (the bf16 compute copy of the masters, copies of
parameters and of arguments, asynchronous copy and slice starts). A
program that has no names - the parent of PR 24 - reads as ``backward`` /
``unscoped`` and kernels ``jvp__`` / ``transpose_jvp___``: nothing here
raises on it.

Scope paths keep what the classes fold away: ``attn/qk_norm``,
``mlp/moe/experts``, ``attn/flash_fwd``, each under its class. A path is
what the program named and nothing else (``scope_path``), so a model
whose layers are of several kinds is told apart by the names it gives
them. A fusion is filed where its root is, because that is the ``tf_op``
the compiler gives it.

One kind of operation loses its names in the compiler: a Mosaic kernel
that XLA itself puts in place of an operation (``jax.lax.ragged_dot``
becomes ``ragged-dot-none`` with the ``tf_op`` ``ragged-dot-none:``, no
``jit(..)/`` before it; a ``pallas_call`` keeps its path). Left unscoped,
a step's largest matmuls would be in neither pass. Such a kernel is filed
under the class of the last named operation that started before it on
the same chip - the pass the step was in - with the kernel's name for its
path (``paths_s["forward"]["ragged-dot-none"]``). That holds as far as
the compiler runs a pass's operations together; the kernel's own seconds
are in ``kernels_s`` whatever its class.

Self time of a span is its duration less that of the spans nested
directly in it on the same thread.
"""

from __future__ import annotations

import gzip
import json
import re
import struct
import sys
from typing import Any, Dict, Iterator, List, Optional, Tuple

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench::", "torchft::")
CLASSES = ("forward", "backward", "optimizer", "unscoped")

# -- the protobuf wire format, as far as XSpace needs it --------------------
# XSpace{planes=1}; XPlane{name=2, lines=3, event_metadata=4, stat_metadata=5}
# XLine{name=2, timestamp_ns=3, events=4}; XEvent{metadata_id=1, offset_ps=2,
# duration_ps=3, stats=4}; XStat{metadata_id=1, double=2, uint64=3, int64=4,
# str=5, ref=7}; XEventMetadata{id=1, name=2, stats=5}; XStatMetadata{id=1,
# name=2}; a map entry is {key=1, value=2}.


def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field, raw bytes for fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = bytes(buf[i:i + 8])
            i += 8
        elif wire == 5:
            value = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wire} is not in an XSpace")
        yield key >> 3, value


def _text(view: memoryview) -> str:
    return bytes(view).decode("utf-8", "replace")


def _stat(buf: memoryview, stat_names: Dict[int, str]) -> Tuple[str, Any]:
    name, value = "", None
    for field, v in _fields(buf):
        if field == 1:
            name = stat_names.get(v, str(v))
        elif field == 2:
            value = struct.unpack("<d", v)[0]
        elif field in (3, 4):
            value = v - (1 << 64) if field == 4 and v >> 63 else v
        elif field == 5:
            value = _text(v)
        elif field == 7:
            value = stat_names.get(v, "")
    return name, value


def _plane(buf: memoryview) -> Dict[str, Any]:
    name = ""
    lines: List[memoryview] = []
    event_meta: List[memoryview] = []
    stat_names: Dict[int, str] = {}
    for field, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 3:
            lines.append(v)
        elif field == 4:
            event_meta.append(v)
        elif field == 5:
            entry = dict(_fields(v))
            meta = dict(_fields(entry[2]))
            stat_names[entry.get(1, 0)] = _text(meta.get(2, memoryview(b"")))
    names: Dict[int, str] = {}
    scopes: Dict[int, str] = {}
    for entry_buf in event_meta:
        entry = dict(_fields(entry_buf))
        key = entry.get(1, 0)
        for field, v in _fields(entry[2]):
            if field == 2:
                names[key] = _text(v)
            elif field == 5:
                stat, value = _stat(v, stat_names)
                if stat == "tf_op":
                    scopes[key] = value
    out_lines = []
    for line_buf in lines:
        line_name, t0_ns, events = "", 0, []
        for field, v in _fields(line_buf):
            if field == 2:
                line_name = _text(v)
            elif field == 3:
                t0_ns = v
            elif field == 4:
                meta_id = offset_ps = duration_ps = 0
                stats: Dict[str, Any] = {}
                for f, x in _fields(v):
                    if f == 1:
                        meta_id = x
                    elif f == 2:
                        offset_ps = x
                    elif f == 3:
                        duration_ps = x
                    elif f == 4:
                        k, val = _stat(x, stat_names)
                        stats[k] = val
                events.append({
                    "name": names.get(meta_id, ""),
                    "scope": scopes.get(meta_id, ""),
                    "start_ns": t0_ns + offset_ps / 1e3,
                    "duration_ns": duration_ps / 1e3,
                    "stats": stats,
                })
        out_lines.append({"name": line_name, "events": events})
    return {"name": name, "lines": out_lines}


def read_planes(path: str) -> List[Dict[str, Any]]:
    """The planes of an ``.xplane.pb`` (or ``.gz``): each a name and its
    lines, each line a name and its events (``name``, ``scope`` - the
    ``tf_op`` of the name's metadata -, ``start_ns``, ``duration_ns``,
    ``stats``)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = memoryview(f.read())
    return [_plane(v) for field, v in _fields(data) if field == 1]


# -- the reduction ------------------------------------------------------------


def scope_class(scope: str) -> str:
    """``forward`` / ``backward`` / ``optimizer`` / ``unscoped`` of an
    operation's scope path (its ``tf_op``): by ``transpose(``, by the name
    ``optimizer``, by whether the program named it at all."""
    if "transpose(" in scope:
        return "backward"
    names = scope_path(scope).split("/")
    if "optimizer" in names:
        return "optimizer"
    return "forward" if names != [""] else "unscoped"


def scope_path(scope: str) -> str:
    """``attn/qk_norm`` of ``jit(loss_and_grads)/transpose(jvp(attn))/
    qk_norm/mul:``: the ``jax.named_scope`` names between the program's
    own ``jit(..)`` and the primitive, a transformation's wrapping
    (``jvp(..)``, ``transpose(..)``) taken off. It ends where the names
    do: at an inner function (``jit(silu)``), an einsum's equation, or the
    primitive, which is the last part. ``""`` for an operation under no
    name, and for an argument's name (``masters['embed']:``)."""
    parts = scope.partition(";")[0].rstrip(":").split("/")[:-1]
    while parts and re.fullmatch(r"p?jit\(.*\)", parts[0]):
        parts.pop(0)
    names: List[str] = []
    for part in parts:
        m = re.fullmatch(r"(?:(?!p?jit\()[A-Za-z_]\w*\()*([A-Za-z_][\w\-.]*)\)*", part)
        if not m:
            break
        names.append(m.group(1))
    return "/".join(names)


def kernel_name(hlo_line: str) -> Optional[str]:
    """``flash_fwd`` of ``%flash_fwd.12 = ... custom-call(...),
    custom_call_target="tpu_custom_call"``; None for any other line."""
    if 'custom_call_target="tpu_custom_call"' not in hlo_line:
        return None
    head = hlo_line.partition(" = ")[0].strip().lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def host_spans(plane: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One entry per thread and span name: calls, total and self seconds,
    and the total by the ``step`` the spans carry (those that carry one)."""
    out = []
    for line in plane["lines"]:
        events = sorted(
            (e for e in line["events"] if e["name"].startswith(SPAN_PREFIXES)),
            key=lambda e: (e["start_ns"], -e["duration_ns"]),
        )
        by_name: Dict[str, Dict[str, Any]] = {}
        stack: List[Tuple[float, Dict[str, Any]]] = []  # (end, entry of the open span)
        for e in events:
            end = e["start_ns"] + e["duration_ns"]
            while stack and stack[-1][0] <= e["start_ns"]:
                stack.pop()
            entry = by_name.setdefault(e["name"], {
                "thread": line["name"], "name": e["name"], "n": 0,
                "total_s": 0.0, "self_s": 0.0, "by_step": {},
            })
            entry["n"] += 1
            entry["total_s"] += e["duration_ns"] / 1e9
            entry["self_s"] += e["duration_ns"] / 1e9
            if stack:  # nested directly in the span on top
                stack[-1][1]["self_s"] -= e["duration_ns"] / 1e9
            step = e["stats"].get("step")
            if step is not None:
                entry["by_step"][step] = (
                    entry["by_step"].get(step, 0.0) + e["duration_ns"] / 1e9
                )
            stack.append((end, entry))
        out.extend(by_name.values())
    return out


def device_seconds(plane: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Seconds of one chip's ``XLA Ops``: ``scopes`` by scope class,
    ``paths`` by ``"<class> <scope path>"`` (every operation in exactly
    one, so a class is the sum of its paths) and ``kernels`` by Mosaic
    kernel name. A kernel under no name goes where the last named
    operation before it went, under its own name (the module's docstring)."""
    classes = dict.fromkeys(CLASSES, 0.0)
    paths: Dict[str, float] = {}
    kernels: Dict[str, float] = {}
    for line in plane["lines"]:
        if line["name"] != OPS_LINE:
            continue
        running = "unscoped"  # the class of the last named operation
        for e in sorted(line["events"], key=lambda e: e["start_ns"]):
            seconds = e["duration_ns"] / 1e9
            which, path = scope_class(e["scope"]), scope_path(e["scope"])
            kernel = kernel_name(e["name"])
            if kernel:
                kernels[kernel] = kernels.get(kernel, 0.0) + seconds
            if which != "unscoped":
                running = which
            elif kernel and "/" not in e["scope"]:  # the compiler's own name
                which, path = running, kernel
            classes[which] += seconds
            key = f"{which} {path}"
            paths[key] = paths.get(key, 0.0) + seconds
    return {"scopes": classes, "paths": paths, "kernels": kernels}


def reduce_planes(planes: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``spans`` (host_spans of the host plane), ``steps`` (the step
    stats seen, sorted), and - averaged over the chips that ran anything -
    ``scopes_s``, ``kernels_s`` and ``paths_s`` (``{class: {scope path:
    seconds}}``). Seconds are totals over the capture; the caller divides
    by the steps it traced."""
    spans: List[Dict[str, Any]] = []
    chips = []
    for plane in planes:
        if plane["name"] == HOST_PLANE:
            spans = host_spans(plane)
        elif plane["name"].startswith(DEVICE_PLANE):
            seconds = device_seconds(plane)
            if sum(seconds["scopes"].values()) > 0:
                chips.append(seconds)

    def mean(table: str) -> Dict[str, float]:
        keys = sorted({k for c in chips for k in c[table]})
        return {k: sum(c[table].get(k, 0.0) for c in chips) / len(chips) for k in keys}

    paths_s: Dict[str, Dict[str, float]] = {c: {} for c in CLASSES} if chips else {}
    for key, seconds in mean("paths").items():
        which, _, path = key.partition(" ")
        paths_s[which][path] = seconds

    return {
        "chips": len(chips),
        "steps": sorted({s for e in spans for s in e["by_step"]}),
        "spans": spans,
        "scopes_s": mean("scopes"),
        "kernels_s": mean("kernels"),
        "paths_s": paths_s,
    }


def reduce_file(path: str) -> Dict[str, Any]:
    return reduce_planes(read_planes(path))


def main(argv: List[str]) -> int:
    if not argv or argv[0].startswith("-"):
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    steps = int(argv[argv.index("--steps") + 1]) if "--steps" in argv else 1
    r = reduce_file(argv[0])
    per_step = {
        "chips": r["chips"], "steps_seen": r["steps"], "divided_by": steps,
        "scopes_ms": {k: v / steps * 1e3 for k, v in r["scopes_s"].items()},
        "kernels_ms": {k: v / steps * 1e3 for k, v in r["kernels_s"].items()},
        "paths_ms": {
            c: {k: v / steps * 1e3 for k, v in paths.items()}
            for c, paths in r["paths_s"].items()
        },
        "spans_ms": [
            {"thread": e["thread"], "name": e["name"], "n": e["n"],
             "total_ms": e["total_s"] / steps * 1e3,
             "self_ms": e["self_s"] / steps * 1e3}
            for e in sorted(r["spans"], key=lambda e: -e["total_s"])
        ],
    }
    print(json.dumps(per_step, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
