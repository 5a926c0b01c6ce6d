"""Pallas wire-compression kernels: quantize/dequantize/cast ON DEVICE.

Every compressed wire used to pack on the HOST after a full-f32
device-to-host transfer, so compression saved network bytes but never the
device-link leg (``pop_op_stats`` reports it as ``d2h``). These kernels
emit the packed wire buffer on the accelerator, so d2h bytes scale with
the WIRE size, not the f32 size:

- :func:`quantize_q8` / :func:`quantize_q8_ef`: symmetric per-leaf int8
  quantization (absmax/127 scale, floored at 1e-12), the EF variant with
  the error-feedback residual carried as a DEVICE-RESIDENT f32 array that
  never crosses the link. The (q, scale) pair is the pre-packed leaf
  payload the native CommPlan decodes into its f32 staging
  (``plan_execute_pre``), replacing both the host-side
  ``quantize.quantize_with_feedback`` jit and the native
  ``plan_pack_ef`` on the hot path.
- :func:`cast_bf16`: round-to-nearest-even f32 -> bf16, the bf16 wire's
  pack cast (bit-identical to the native ``f32_to_bf16``; the existing
  plan tests pin jax's cast == the native cast).
- :func:`dequantize_q8`: the exact inverse decode (q * scale), for the
  allgather-transport payloads and the kernel round-trip oracle.

Numerics contract (the bit-identity oracle in tests/test_device_pack.py):
``quantize_q8_ef`` reproduces the FMA-free numpy EF reference — and
therefore the native ``plan_pack_ef`` — BIT FOR BIT: ``d = x + res``;
``scale = max(max|d|/127, 1e-12)``; ``q = clip(round_half_even(d/scale))``;
``dq = q * scale``; ``res' = d - dq``. The residual subtraction is wrapped
in ``jax.lax.optimization_barrier`` so XLA cannot contract ``d - q*scale``
into an fma (the documented last-ulp divergence of the jitted jax EF).
A non-finite leaf poisons its ENTIRE payload and carry — scale and the
new residual become NaN while the int8 codes zero, so the decode
``0 * NaN`` reproduces the host EF's whole-leaf NaN propagation.

Off-TPU the kernels run under ``interpret=True`` (the flash-attention
discipline), so CPU tier-1 exercises the identical code path; on TPU the
same bodies compile to Mosaic. Shapes are arbitrary: inputs flatten and
zero-pad to (rows, 128) lane tiles — padding is absmax-neutral (|0| never
raises a finite absmax) and its residual stays exactly 0.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# Rows per grid block: 256x128 f32 = 128 KiB per VMEM buffer, and a
# multiple of every dtype's sublane tile floor (f32 8, bf16 16, int8 32).
_BLOCK_ROWS = 256
# Scale floor, shared with quantize.quantize_with_feedback and the native
# plan_pack_ef: an all-zero leaf stays representable.
_SCALE_FLOOR = 1e-12
# The per-leaf scale rides scalar memory: the kernels read it once per
# block as a scalar operand, which is what SMEM is for.
_SCALAR_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _pick_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _grid_shape(n: int, interpret: bool) -> Tuple[int, int]:
    """(padded rows, block rows) for an n-element flat payload.

    Compiled (TPU): _BLOCK_ROWS-row VMEM blocks once the payload
    outgrows one (rows padded to the block multiple; the 32-row floor
    covers the int8 sublane tile). Interpret mode: ALWAYS one block —
    the interpreter's grid loop carries the full output through a
    dynamic_update_slice per step, so a multi-block grid costs
    O(grid x payload) copying while a single block has no VMEM ceiling
    to respect."""
    rows = _cdiv(max(n, 1), _LANES)
    if interpret or rows <= _BLOCK_ROWS:
        rows_pad = _cdiv(rows, 32) * 32
        return rows_pad, rows_pad
    return _cdiv(rows, _BLOCK_ROWS) * _BLOCK_ROWS, _BLOCK_ROWS


def _to_tiles(x: jax.Array, rows_pad: int) -> jax.Array:
    flat = x.reshape(-1).astype(jnp.float32)
    total = rows_pad * _LANES
    return jnp.pad(flat, (0, total - flat.size)).reshape(rows_pad, _LANES)


def _split_scale(s: jax.Array) -> jax.Array:
    """(3,) f32 ``[s, s_hi, s_lo]``: the scale and its 12-bit mantissa
    halves, split by masking (exact; ``s_hi + s_lo == s``). Computed in
    XLA because Mosaic has no scalar bitcast; the kernel reads the three
    words from SMEM."""
    bits = jax.lax.bitcast_convert_type(s, jnp.uint32)
    s_hi = jax.lax.bitcast_convert_type(
        bits & jnp.uint32(0xFFFFF000), jnp.float32
    )
    return jnp.stack([s, s_hi, s - s_hi])


def _round32_mul(qf, s_hi, s_lo):
    """round_f32(qf * s), immune to fma contraction — the decode the ring
    peers run is a plain single-rounded f32 multiply, and the residual
    needs ``d - round32(qf*s)`` with TWO roundings; a compiler-contracted
    ``fma(-qf, s, d)`` rounds once and drifts the carry at the last ulp
    (XLA's loop fusion contracts straight through optimization_barrier on
    CPU). With ``s`` split into 12-bit mantissa halves (_split_scale),
    both partial products are EXACT (|qf| <= 127 has <= 7 significand
    bits, each half <= 12), so the single f32 add performs the one
    rounding — and contracting either multiply into an fma cannot change
    an exact product's value."""
    return qf * s_hi + qf * s_lo


def _quant_kernel(d_ref, scale_ref, q_ref, res_out_ref):
    # d_ref already holds the EF-adjusted payload (x + res, one exact
    # elementwise add). scale_ref holds [s, s_hi, s_lo] for the RAW scale
    # max(absmax/127, floor): finite for a finite leaf, NaN/Inf when the
    # leaf diverged. On the poison path the codes zero and the caller's
    # NaN scale carries the signal (0 * NaN decodes to NaN on every
    # element — the host EF's whole-leaf propagation); the residual
    # poisons here.
    s = scale_ref[0]
    d = d_ref[...]
    v = jnp.clip(jnp.round(d / s), -127.0, 127.0)
    qf = jnp.where(jnp.isfinite(v), v, 0.0)
    q_ref[...] = qf.astype(jnp.int8)
    res_out_ref[...] = jnp.where(
        jnp.isfinite(s),
        d - _round32_mul(qf, scale_ref[1], scale_ref[2]),
        jnp.nan,
    )


def _quantize_tiles(
    tiles: jax.Array, res_tiles: jax.Array, block: int, interpret: bool
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(q tiles int8, scale (), res tiles f32). `scale` is the FINAL wire
    scale: NaN when the leaf is non-finite."""
    rows = tiles.shape[0]
    # The EF-adjusted payload, computed ONCE and fed to both passes — the
    # absmax (and therefore the scale) is over d = x + res, not x. One
    # exact elementwise f32 add, identical to the oracle's.
    d = tiles + res_tiles
    # Plain XLA reduce, not a kernel: Mosaic cannot store a scalar into a
    # VMEM block, and a full-payload max is one fused pass either way.
    # max() propagates NaN/Inf — the non-finite signal the scale
    # computation below turns into a NaN scale.
    absmax = jnp.max(jnp.abs(d))
    # The denominator is made DATA-DEPENDENT (0*x cannot be folded away
    # for floats — x may be NaN/Inf) because XLA compiles division by a
    # LITERAL constant into a reciprocal multiply under jit, which
    # mis-rounds ~1/3 of scales by one ulp and would break bit-identity
    # with the native EF's true `absmax / 127.0f` division. As a bonus a
    # non-finite absmax NaNs the denominator, which NaNs the scale — the
    # poison signal either way.
    denom = jnp.float32(127.0) + 0.0 * absmax
    scale_raw = jnp.maximum(absmax / denom, _SCALE_FLOOR)  # NaN if hot
    q, res_out = pl.pallas_call(
        _quant_kernel,
        grid=(rows // block,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            _SCALAR_SPEC,
        ],
        out_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, _LANES), jnp.int8),
            jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(d, _split_scale(scale_raw))
    scale = jnp.where(jnp.isfinite(scale_raw), scale_raw, jnp.nan)
    return q, scale, res_out


def quantize_q8_ef(
    x: jax.Array, res: jax.Array, *, interpret: Optional[bool] = None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Symmetric int8 quantization with error feedback, on device.

    ``x``: any float leaf (upcast to f32 like the native EF); ``res``: the
    f32 carry, same shape. Returns ``(q int8, scale f32 scalar, new_res
    f32)``, each shaped like ``x`` (scale a 0-d array). The caller owns
    the carry: keep it on device, restore/zero it under the same
    heal/abort discipline as ``plan_reset_feedback``. Traceable — callers
    jit it (the device packer does)."""
    interpret = _pick_interpret(interpret)
    n = x.size
    if n == 0:
        return (jnp.zeros(x.shape, jnp.int8), jnp.float32(_SCALE_FLOOR),
                jnp.zeros(x.shape, jnp.float32))
    rows_pad, block = _grid_shape(n, interpret)
    q, scale, res_out = _quantize_tiles(
        _to_tiles(x, rows_pad), _to_tiles(res, rows_pad), block, interpret
    )
    return (
        q.reshape(-1)[:n].reshape(x.shape),
        scale,
        res_out.reshape(-1)[:n].reshape(x.shape),
    )


def quantize_q8(
    x: jax.Array, *, interpret: Optional[bool] = None
) -> Tuple[jax.Array, jax.Array]:
    """EF-free symmetric int8 quantization: ``(q, scale)`` for payloads
    with no carry (e.g. the int8 allgather transport). Same scale/round/
    poison semantics as :func:`quantize_q8_ef` with a zero residual."""
    q, scale, _ = quantize_q8_ef(
        x, jnp.zeros(x.shape, jnp.float32), interpret=interpret
    )
    return q, scale


def _dequant_kernel(q_ref, scale_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * scale_ref[0]


def dequantize_q8(
    q: jax.Array, scale: jax.Array, *, interpret: Optional[bool] = None
) -> jax.Array:
    """Exact decode ``q * scale`` (the native plan_pack_pre_range's
    arithmetic), on device. A NaN scale poisons the whole leaf."""
    interpret = _pick_interpret(interpret)
    n = q.size
    if n == 0:
        return jnp.zeros(q.shape, jnp.float32)
    rows_pad, block = _grid_shape(n, interpret)
    flat = q.reshape(-1)
    tiles = jnp.pad(flat, (0, rows_pad * _LANES - n)).reshape(
        rows_pad, _LANES
    )
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(rows_pad // block,),
        in_specs=[
            pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
            _SCALAR_SPEC,
        ],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.float32),
        interpret=interpret,
    )(tiles, jnp.asarray(scale, jnp.float32).reshape(1))
    return out.reshape(-1)[:n].reshape(q.shape)


def _cast_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...].astype(jnp.bfloat16)


def cast_bf16(
    x: jax.Array, *, interpret: Optional[bool] = None
) -> jax.Array:
    """f32 -> bf16 with round-to-nearest-even, on device: the bf16 wire's
    pack cast, emitting the 2-byte words that cross the device link."""
    interpret = _pick_interpret(interpret)
    n = x.size
    if n == 0:
        return jnp.zeros(x.shape, jnp.bfloat16)
    rows_pad, block = _grid_shape(n, interpret)
    out = pl.pallas_call(
        _cast_kernel,
        grid=(rows_pad // block,),
        in_specs=[pl.BlockSpec((block, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_pad, _LANES), jnp.bfloat16),
        interpret=interpret,
    )(_to_tiles(x, rows_pad))
    return out.reshape(-1)[:n].reshape(x.shape)
