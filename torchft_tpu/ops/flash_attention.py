"""Fused causal attention as a pallas TPU kernel (FlashAttention-2 style).

The dense attention path in ``models/transformer.py`` materializes the
(B, H, S, S) score matrix in HBM — at seq 2048 that is the single largest
activation of the step and a pure HBM-bandwidth tax. This kernel keeps
each (query-block × key-block) score tile in VMEM, runs the online-softmax
recurrence (the same one ``context_parallel.ring_attention`` uses across
devices, here across VMEM tiles within one device), and writes only the
(S, D) output plus an (S,) logsumexp residual for the backward pass.

Backward is ONE fused kernel (not the two-kernel FlashAttention-2 split):
the grid walks key blocks; dk/dv accumulate in VMEM per key block, and dq
accumulates into a full-row f32 output block that pallas keeps resident
across the sequential TPU grid (revisited index map — grid steps on TPU
execute in order, so read-modify-write accumulation is deterministic).
Fusing matters because this shape is VPU-bound, not MXU-bound (head_dim
64: each S×S exp pass costs more than the matmuls it feeds): the split
design recomputes probabilities twice per tile pair — once for dq, once
for dk/dv — and the fused kernel computes them once, cutting the
dominant exp/elementwise work ~in half and the matmul count 7→5 per
tile. The softmax scale is folded into q OUTSIDE the kernel (exact for
power-of-two scales, e.g. head_dim 64 → 0.125), removing the per-tile
S×S scale multiplies; autodiff of the fold rescales dq automatically.

The causal path splits every tile loop into UNMASKED interior tiles plus
one masked diagonal tile (requires block_q == block_k, the auto default):
strictly-below-diagonal tiles are fully live, so the interior body skips
the iota/compare/select mask passes entirely. The fast path also uses a
finite -1e30 mask value instead of -inf, which removes every
``isfinite`` guard from the online-softmax recurrence: with at least one
live key per query row (guaranteed on the causal path — every row
attends at least its own position; padded query rows attend earlier live
keys), ``exp(-1e30 - m)`` underflows to exactly 0 and the recurrence
needs no special cases. The backward kernels apply NO padding mask at
all: padded k/v rows are zeros, so padded-column score/probability
garbage contributes exactly 0 to dq (``ds @ k`` hits zero rows) and only
to dk/dv rows that are sliced off; padded query rows carry zero
cotangents. The general path (sliding window, unequal blocks,
non-causal) keeps per-tile masks.

Design notes (pallas_guide.md):
- all matmuls request ``preferred_element_type=float32`` so the MXU
  accumulates in f32 regardless of the bf16 inputs;
- iota is always 2D (``broadcasted_iota``) — 1D iota does not lower;
- blocks always span the full head dim, satisfying Mosaic's "divisible by
  128 OR equal to the array dim" lane rule without padding D (padding to
  128 lanes would double the QK FLOPs at the flagship head_dim of 64);
  arbitrary sequence lengths ARE padded — up to the block multiple, with
  padded keys masked in-kernel and padded queries carrying zero
  cotangents;
- causal kernels bound their inner ``fori_loop`` by the block diagonal so
  masked-out tiles are never computed (dynamic trip counts lower to
  ``while_loop``).

Off-TPU the same kernels run under ``interpret=True`` so CPU tests and the
virtual-device dryrun exercise the identical code path.

Reference parity: none — the reference has no fused kernels (SURVEY.md
§2.3: its compute path is plain torch ops + NCCL). This is the
"pallas kernels for the hot ops" part of the TPU-first mandate.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

_NEG_INF = float("-inf")
# Finite mask value for the fast (split-diagonal) path: large enough that
# exp(_NEG_LARGE - m) underflows to exactly 0 for any live row max m
# (|m| <= ~1e4 in practice), small enough to stay exact in f32.
_NEG_LARGE = -1e30


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    """MXU matmul keeping the inputs' (bf16) dtype, f32 accumulation —
    casting inputs to f32 first would run the MXU at f32 rate (~8x slower
    on v5e)."""
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_nt(a: jax.Array, b: jax.Array) -> jax.Array:
    """a @ b.T via dot_general dimension numbers — Mosaic contracts the
    shared minor dim directly instead of materializing b.T (an explicit
    .T is a per-tile VMEM relayout pass)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_tn(a: jax.Array, b: jax.Array) -> jax.Array:
    """a.T @ b without materializing a.T (contract the major dims)."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _tile_mask(
    q_start, k_start, block_q: int, block_k: int, kv_len: int,
    causal: bool, padded: bool, window: Optional[int] = None,
):
    """Validity mask for one (block_q, block_k) score tile, or None when
    every position is live. Shared by the forward and both backward
    kernels so the mask semantics cannot drift apart. ``window`` w keeps
    only keys with q_pos - k_pos < w (sliding-window / local attention)."""
    if not (causal or padded or window is not None):
        return None
    k_pos = k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    ok = k_pos < kv_len if padded else True
    if causal or window is not None:
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        if causal:
            ok = (q_pos >= k_pos) & ok
        if window is not None:
            ok = (q_pos - k_pos < window) & ok
    return ok


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _split_diag(causal: bool, window, block_q: int, block_k: int) -> bool:
    """True when the tile loops may run as unmasked-interior + one masked
    diagonal tile (see module docstring). Requires equal blocks so the
    diagonal tile of query block qi is exactly key block qi."""
    return causal and window is None and block_q == block_k


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *,
    causal: bool, block_q: int, block_k: int, num_k: int,
    kv_len: int, window,
):
    # q arrives PRE-SCALED by sm_scale (folded outside the kernel), so
    # s = q @ k.T is the final score with no per-tile S x S multiply.
    qi = pl.program_id(1)
    q = q_ref[0]  # (block_q, D), input dtype
    D = q.shape[-1]
    padded = kv_len < num_k * block_k
    fast = _split_diag(causal, window, block_q, block_k)
    neg = _NEG_LARGE if fast else _NEG_INF

    def tile(j, carry, masked: bool):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot_nt(q, k_blk)  # (block_q, block_k) f32
        if masked:
            ok = _tile_mask(
                qi * block_q, j * block_k, block_q, block_k, kv_len,
                causal, padded, window,
            )
            if ok is not None:
                s = jnp.where(ok, s, neg)
        m_new = jnp.maximum(m, s.max(axis=-1))
        if fast:
            # every query row has >= 1 live key (causal: its own position,
            # or for zero-padded query rows any earlier live key), so
            # m_new is finite after the first processed tile and the
            # -inf/isfinite guards of the general path are dead weight:
            # exp(_NEG_LARGE - m_new) underflows to exactly 0.
            p = jnp.exp(s - m_new[:, None])
            corr = jnp.exp(m - m_new)
        else:
            # rows with every key masked keep m = -inf; guard
            # exp(-inf - -inf)
            safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(
                jnp.isfinite(s), jnp.exp(s - safe_m[:, None]), 0.0
            )
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + _dot_f32(
            p.astype(v_blk.dtype), v_blk
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((block_q,), neg, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
        jnp.zeros((block_q, D), jnp.float32),
    )
    if fast:
        # interior tiles j < qi are fully below the causal diagonal (and
        # never reach padded key columns: cols < qi*block_k < kv_len), so
        # they run with no mask at all; the diagonal tile j == qi carries
        # the causal mask and (in the last row block) the padding mask.
        m, l, acc = jax.lax.fori_loop(
            0, qi, lambda j, c: tile(j, c, False), init
        )
        m, l, acc = tile(qi, (m, l, acc), True)
    else:
        num_k_live = _cdiv(kv_len, block_k)  # skip fully-padded key blocks
        if causal:
            # key blocks strictly above the block diagonal are fully masked
            hi = jnp.minimum(
                num_k_live, ((qi + 1) * block_q + block_k - 1) // block_k
            )
        else:
            hi = num_k_live
        lo = 0
        if window is not None:
            # key blocks fully left of the sliding window are masked for
            # every query row in this block
            lo = jnp.maximum(0, (qi * block_q - window + 1) // block_k)
        m, l, acc = jax.lax.fori_loop(
            lo, hi, lambda j, c: tile(j, c, True), init
        )
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse rides a full-row (1, 1, S) block revisited across the sequential
    # qi grid dim (a (1, block_q) 2D block violates Mosaic's (8, 128) tile
    # floor); each step writes its slice
    if fast:
        # m is finite for every row (see tile()); no -inf bookkeeping
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = m + jnp.log(l_safe)
    else:
        lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = jnp.where(
            jnp.isfinite(m), m + jnp.log(l_safe), _NEG_INF
        )


def _flash_fwd_call(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool, block_q: int, block_k: int,
    interpret: bool, kv_len: int, window,
):
    """q (pre-scaled)/k/v: (BH, S_pad, D) -> out (BH, S_pad, D),
    lse (BH, 1, S_pad) f32. Positions >= kv_len are zero padding, masked
    out of every softmax."""
    BH, S, D = q.shape
    num_q, num_k = _cdiv(S, block_q), _cdiv(S, block_k)
    kernel = functools.partial(
        _fwd_kernel, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k, kv_len=kv_len,
        window=window,
    )
    row = pl.BlockSpec((1, S, D), lambda bh, qi: (bh, 0, 0))
    qspec = pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH, num_q),
        in_specs=[qspec, row, row],
        out_specs=[
            qspec,
            pl.BlockSpec((1, 1, S), lambda bh, qi: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",  # the kernel's name in a device trace
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, *,
    causal: bool, block_q: int, block_k: int, num_q: int,
    kv_len: int, window,
):
    ki = pl.program_id(1)
    k_blk = k_ref[0]  # (block_k, D), input dtype
    v_blk = v_ref[0]
    D = k_blk.shape[-1]
    # Padded QUERY rows need no mask here: their cotangent (do) and delta
    # are zero, so ds and p.T @ do vanish (their lse is finite on both
    # paths — causal padded query rows attend earlier live keys — so p
    # stays finite and 0 * p cannot produce NaN). On the general path,
    # padded KEY columns are masked; the fast path drops that mask too:
    # p/ds garbage in padded columns lands only in dk/dv ROWS that the
    # caller slices off (each dk/dv row is a column-wise independent sum),
    # so masking them buys nothing.
    padded = kv_len < q_ref.shape[1]  # static: S_pad > kv_len
    fast = _split_diag(causal, window, block_q, block_k)

    # dq accumulates into a REVISITED full-row f32 output block: the TPU
    # grid is sequential, so every ki step of one bh row sees the same
    # resident VMEM block; zero it on the first step.
    @pl.when(ki == 0)
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def tile(i, carry, masked: bool):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = _dot_nt(q_blk, k_blk)  # q pre-scaled by sm_scale
        p = jnp.exp(s - lse[:, None])
        if masked:
            ok = _tile_mask(
                i * block_q, ki * block_k, block_q, block_k, kv_len,
                causal, padded and not fast, window,
            )
            if ok is not None:
                p = jnp.where(ok, p, 0.0)
        dv_new = dv + _dot_tn(p.astype(do_blk.dtype), do_blk)
        dp = _dot_nt(do_blk, v_blk)
        ds = (p * (dp - delta[:, None])).astype(q_blk.dtype)  # one cast,
        dk_new = dk + _dot_tn(ds, q_blk)                      # used twice
        dq_ref[0, pl.ds(i * block_q, block_q), :] += _dot_f32(ds, k_blk)
        return dk_new, dv_new

    init = (
        jnp.zeros((block_k, D), jnp.float32),
        jnp.zeros((block_k, D), jnp.float32),
    )
    if fast:
        # diagonal tile i == ki carries the causal mask; query blocks
        # i > ki are fully below the diagonal (every q_pos >= every
        # k_pos), so they run unmasked.
        dk, dv = tile(ki, init, True)
        dk, dv = jax.lax.fori_loop(
            ki + 1, num_q, lambda i, c: tile(i, c, False), (dk, dv)
        )
    else:
        if causal:
            # query blocks strictly below the block diagonal see none of
            # this key block
            lo = (ki * block_k) // block_q
        else:
            lo = 0
        hi = num_q
        if window is not None:
            # query blocks fully right of the window (q_min - k_max >= w)
            # see none of this key block
            hi = jnp.minimum(
                num_q, ((ki + 1) * block_k - 1 + window) // block_q + 1
            )
        dk, dv = jax.lax.fori_loop(
            lo, hi, lambda i, c: tile(i, c, True), init
        )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_call(
    q, k, v, o, lse, do, *,
    causal: bool, block_q: int, block_k: int,
    interpret: bool, kv_len: int, window,
):
    BH, S, D = q.shape
    num_q, num_k = _cdiv(S, block_q), _cdiv(S, block_k)
    # delta_i = sum_d do_id * o_id — one fused elementwise+reduce, not worth
    # a kernel
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]  # (BH, 1, S) — same full-row layout as lse

    row3 = pl.BlockSpec((1, S, D), lambda bh, i: (bh, 0, 0))
    row2 = pl.BlockSpec((1, 1, S), lambda bh, i: (bh, 0, 0))
    kblk3 = pl.BlockSpec((1, block_k, D), lambda bh, i: (bh, i, 0))

    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, causal=causal,
            block_q=block_q, block_k=block_k, num_q=num_q, kv_len=kv_len,
            window=window,
        ),
        grid=(BH, num_k),
        in_specs=[row3, kblk3, kblk3, row3, row2, row2],
        out_specs=[row3, kblk3, kblk3],
        out_shape=[
            # dq is the revisited f32 accumulator (cast to q.dtype below)
            jax.ShapeDtypeStruct((BH, S, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd",
    )(q, k, v, do, lse, delta)
    return dq.astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# custom-vjp plumbing on the (BH, S, D) canonical layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg, q, k, v):
    out, _ = _flash_fwd_res(cfg, q, k, v)
    return out


def _flash_fwd_res(cfg, q, k, v):
    causal, block_q, block_k, interpret, kv_len, window = cfg
    out, lse = _flash_fwd_call(
        q, k, v, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        kv_len=kv_len, window=window,
    )
    # Name the kernel outputs so a jax.checkpoint policy can SAVE them:
    # the vjp needs (out, lse) as residuals, and with both saved the remat
    # backward's forward replay prunes the fwd pallas launch entirely
    # (q/k/v are re-derived from the cheap qkv projection instead).
    # checkpoint_name is the identity outside a policy-remat context.
    from jax.ad_checkpoint import checkpoint_name

    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd_res(cfg, res, g):
    causal, block_q, block_k, interpret, kv_len, window = cfg
    q, k, v, out, lse = res
    return _flash_bwd_call(
        q, k, v, out, lse, g, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
        kv_len=kv_len, window=window,
    )


_flash.defvjp(_flash_fwd_res, _flash_bwd_res)


def _pick_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh: Any = None,
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused multi-head causal attention.

    Args:
        q, k, v: (B, S, H, head_dim), any float dtype.
        causal: apply the autoregressive mask.
        window: sliding-window (local) attention — each query attends
            only the most recent ``window`` keys (q_pos - k_pos < window);
            tiles fully outside the window are skipped by the loop
            bounds, so computed tiles scale with S*window instead of
            S^2/2 (wall-clock gains show once S/window is large).
            Requires ``causal``.
        sm_scale: score scale; default ``head_dim ** -0.5``. The scale
            is folded into ``q`` OUTSIDE the kernel as one f32 multiply
            rounded back to the input dtype (it removes a per-tile
            (S_q, S_k) multiply from every kernel). For POWER-OF-TWO
            scales — any power-of-two head_dim, e.g. 64 -> 0.125 — the
            fold is exact in every float dtype. CAVEAT: a
            non-power-of-two ``sm_scale`` with bf16/f16 inputs rounds
            each scaled q element once (<= 1/2 ulp; ~0.4% relative at
            bf16) BEFORE the scores are formed, so scores are not
            bit-equal to an unfused baseline that scales the f32
            logits. Numerically benign for training; pass f32 q/k/v or
            a power-of-two scale when exactness matters.
        block_q, block_k: VMEM tile sizes; clamped to S, and on real TPU
            rounded UP to 128-multiples (Mosaic's lane-aligned store
            requirement — a requested 64 runs as 128 on hardware;
            interpret mode honors small blocks exactly). Default auto:
            (512, 512) when the sublane-padded sequence length reaches
            2048, else (128, 128). The choice dates from an earlier
            runtime and is not measured on the current chip (ROADMAP
            S4); standalone kernel sweeps rank tiles differently from
            whole-step timings (fusion/VMEM interactions) — trust the
            latter when re-tuning.
        interpret: force pallas interpret mode; default: on iff the backend
            is not TPU (CPU tests / virtual-device dryruns).
        mesh/batch_axis/head_axis: when ``mesh`` is given the kernel runs
            per shard under ``shard_map`` with batch split over
            ``batch_axis`` and heads over ``head_axis`` (a pallas call is a
            single custom op XLA cannot partition on its own).
    Returns:
        (B, S, H, head_dim) attention output, dtype of q.
    """
    B, S, H, D = q.shape
    if sm_scale is None:
        sm_scale = D ** -0.5
    if window is not None:
        if not causal:
            raise ValueError(
                "window requires causal=True (one-sided local attention)"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")

    if mesh is not None:
        spec = P(batch_axis, None, head_axis, None)
        local = functools.partial(
            flash_attention, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            window=window,
        )
        # check_vma=False: pallas out_shapes carry no varying-mesh-axes
        # annotation, which the new shard_map VMA typing would reject
        return shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    interp = _pick_interpret(interpret)
    # Auto tile sizes (see docstring); arbitrary S is
    # handled by zero-padding the sequence up to the block multiple —
    # padded keys are masked in-kernel, padded queries carry zero
    # cotangents, so numerics are exact.
    # Tile choice keys on the PADDED sublane length, not raw S:
    # language-model training slices the last token off (tokens[:, :-1]),
    # so the flagship in-model sequence is 2047 — a raw-S `>= 2048` test
    # would drop it onto the 128-tile path, while sequences just over a
    # power of two would pay ~50% padding on the large-tile path.
    # On hardware the lse row is sliced along the LANE dim in block_q-wide
    # stores, so blocks must be 128-multiples (Mosaic rejects misaligned
    # vector stores — observed at S=99 on v5e); interpret mode only needs
    # the 8-sublane floor, and the CPU tests use small blocks.
    unit = 8 if interp else 128
    s8 = _cdiv(S, unit) * unit
    if s8 >= 2048:
        auto_q, auto_k = 512, 512
    else:
        auto_q, auto_k = 128, 128
    block_q = min(block_q or auto_q, s8)
    block_k = min(block_k or auto_k, s8)
    if not interp:
        block_q = _cdiv(block_q, 128) * 128
        block_k = _cdiv(block_k, 128) * 128
    base = block_q * block_k // math.gcd(block_q, block_k)
    S_pad = _cdiv(S, base) * base

    # (B, S, H, D) -> (B*H, S_pad, D). Blocks always span the full head
    # dim, so Mosaic's "divisible by 128 OR equal to the array dim" lane
    # rule is satisfied without padding D (padding to 128 lanes would
    # double the QK FLOPs at the flagship head_dim of 64).
    def to_rows(x):
        x = x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
        if S_pad != S:
            x = jnp.pad(x, ((0, 0), (0, S_pad - S), (0, 0)))
        return x

    cfg = (
        bool(causal), block_q, block_k, interp, S,
        None if window is None else int(window),
    )
    # sm_scale folded into q OUTSIDE the custom_vjp: one cheap (S, D)
    # multiply replaces a per-tile (S_q, S_k) multiply in every kernel,
    # and autodiff of this fold rescales dq automatically (exact for
    # power-of-two scales — head_dim 64 gives 0.125). The product is
    # computed with an f32 scalar so the scale itself is never quantized
    # to bf16; only the single product rounding remains.
    q_scaled = (q * jnp.float32(sm_scale)).astype(q.dtype)
    out = _flash(cfg, to_rows(q_scaled), to_rows(k), to_rows(v))
    return out[:, :S].reshape(B, H, S, D).transpose(0, 2, 1, 3)
