"""Shared int8 wire-format kernels for the quantized compression modes.

ONE implementation of the per-leaf symmetric int8 quantization with error
feedback and of the member-wise dequantize-average, shared by
:class:`~torchft_tpu.ddp.PipelinedDDP` (``compress="int8"/"q8"``) and
:class:`~torchft_tpu.local_sgd.AsyncDiLoCo` (same modes): the two classes
must stay WIRE-COMPATIBLE (a DDP member and a DiLoCo member never share a
ring op, but the {q, scale} payload convention, the scale floor, and the
participant-divisor discipline are one protocol), so the numerics live in
one place.

Reference parity: none — the reference ships gradients uncompressed
(torch DDP's compressed comm hooks are the upstream analog).

Hot-path siblings: the native comm plan executes this same arithmetic in
C++ (``plan_pack_ef``, collectives.cc), and
:mod:`torchft_tpu.ops.quantize_kernels` executes it as Pallas kernels ON
DEVICE with a device-resident carry — so on the plan transport this
jitted host implementation is off the per-step path entirely (it remains
the wire contract's executable spec, and the int8 allgather transport
still runs it). All three are pinned bit-identical to the FMA-free numpy
oracle :func:`np_quantize_ef` by tests/test_comm_plan.py and
tests/test_device_pack.py, and on the chip by ``chip_smoke.py``.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np


def np_quantize_codes(
    leaf: np.ndarray, res: np.ndarray
) -> Tuple[np.ndarray, np.ndarray, np.float32]:
    """``(d, q, scale)`` of the pure-numpy EF reference: the EF-adjusted
    payload ``d = leaf + res``, its integer codes (as f32) and the wire
    scale — NaN, with zero codes, when the leaf is not finite."""
    d = (leaf.astype(np.float32) + res).astype(np.float32)
    absmax = np.max(np.abs(d)) if d.size else np.float32(0)
    if not np.isfinite(absmax):
        return d, np.zeros_like(d), np.float32(np.nan)
    scale = np.maximum(np.float32(absmax) / np.float32(127.0),
                       np.float32(1e-12))
    return d, np.clip(np.round(d / scale), -127, 127).astype(np.float32), scale


def np_quantize_ef(
    leaf: np.ndarray, res: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """``(dq, new_res)``: the pure-numpy, FMA-free reference of the EF
    quantization that the jitted, native and Pallas implementations are
    all tested against. (The jitted jax version may differ from it at the
    last ulp of the residual — XLA contracts ``d - q*scale`` into an fma —
    which is exactly why the plan's native EF is the wire contract.)"""
    d, q, scale = np_quantize_codes(leaf, res)
    if not np.isfinite(scale):
        nan = np.float32(np.nan)
        return np.full_like(d, nan), np.full_like(d, nan)
    dq = (q * scale).astype(np.float32)
    return dq, (d - dq).astype(np.float32)


def quantize_with_feedback(tree: Any, residual: Any) -> Dict[str, Any]:
    """Per-leaf symmetric int8 quantization with error feedback.

    For each leaf: ``d = leaf(f32) + residual``; ``scale = max(|d|)/127``
    (floored at 1e-12 so an all-zero leaf stays representable);
    ``q = clip(round(d/scale))`` int8; ``dq = q*scale`` (what is actually
    shipped, leaf-wise); ``res = d - dq`` (the carry the CALLER owns —
    restore it on aborted steps, reset it on heals).

    Traceable (callers jit it). Returns ``{"q", "scale", "dq", "res"}``,
    each a tree shaped like ``tree`` (dict-keyed ``tree_transpose``, so
    input pytrees containing tuples can never be mis-split).
    """
    import jax
    import jax.numpy as jnp

    def leaf(l: Any, r: Any) -> Dict[str, Any]:
        d = l.astype(jnp.float32) + r
        scale = jnp.maximum(jnp.max(jnp.abs(d)) / 127.0, 1e-12)
        q = jnp.clip(jnp.round(d / scale), -127, 127).astype(jnp.int8)
        dq = q.astype(jnp.float32) * scale
        return {"q": q, "scale": scale, "dq": dq, "res": d - dq}

    packed = jax.tree_util.tree_map(leaf, tree, residual)
    return jax.tree_util.tree_transpose(
        jax.tree_util.tree_structure(tree),
        jax.tree_util.tree_structure(
            {"q": 0, "scale": 0, "dq": 0, "res": 0}
        ),
        packed,
    )


def make_dequant_average() -> Any:
    """Jitted member-wise dequantize-then-average for gathered
    ``{"q", "scale"}`` entries: ``avg = sum_i(q_i * scale_i) / n``.

    ``n`` must be the PARTICIPANT count, not the cohort size —
    non-participating (healing/spare) entries arrive zeroed from
    ``Manager.allgather`` and must not dilute the divisor. Callers cache
    one jitted fn per cohort size (the entry-list length is part of the
    trace).
    """
    import jax
    import jax.numpy as jnp

    def combine(entries: Any, n: Any) -> Any:
        acc = None
        for e in entries:
            dq = jax.tree_util.tree_map(
                lambda q, s: q.astype(jnp.float32) * s, e["q"], e["scale"]
            )
            acc = (
                dq if acc is None
                else jax.tree_util.tree_map(jnp.add, acc, dq)
            )
        return jax.tree_util.tree_map(lambda a: a / n, acc)

    return jax.jit(combine)
