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
tile. The softmax scale is folded into q, never into the scores (exact
for power-of-two scales, e.g. head_dim 64 → 0.125), removing the per-tile
S×S scale multiplies: outside the kernels on the three-array entry, where
autodiff of the fold rescales dq, and inside them, once a resident block
and once on dq, on the fused-projection entry.

TWO ENTRIES over the same kernel bodies; what selects one is what the
caller has in hand, never a flag. ``flash_attention_qkv`` takes a fused
projection ``x @ wqkv`` of (B, S, 3*H*D) and returns (B, S, H*D): the
dense models' call (``transformer._attention_impl``, and ``models/moe.py``
through it). Its kernels address heads inside that layout - a grid step
takes one 128-lane block of the q third (two heads of 64, one of 128) and
the blocks at the same place in the k and v thirds, and the backward
writes the (B, S, 3*H*D) cotangent as one array - so nothing is
transposed, split, concatenated or scaled in HBM between ``x @ wqkv`` and
``out @ wo``; the softmax scale is applied inside, once a resident block.
Around the (B*H, S, 64) form those copies were 16-27 ms of a 232-243 ms
GPT-2 step and 2.6-3.0 GB of HBM, rows of 64 padded to 128 lanes (PERF.md
section 6, PRs 27 and 28). ``flash_attention`` takes q, k, v of
(B, S, H, D), KEEPS the transposes to (B*H, S, D) and the scale folded
outside, and is every other caller's: ``models/olmoe.py`` (q and k leave
QK-norm and RoPE fusions that write any layout for nothing, and a head of
128 columns is a full lane block either way), Ulysses context
parallelism, the ``mesh`` / ``shard_map`` form, windows, non-causal
calls, and whatever the fused layout cannot express (an odd count of
64-wide heads, other head sizes, more than one resident block), which
``flash_attention_qkv`` sends there itself, from shapes.

The causal path (``block_q`` a multiple of ``block_k``; with a window,
below) runs a TWO-LEVEL schedule. A grid step holds a resident block of
``block_q`` rows - the whole sequence up to 2048 positions - cut into
row groups of ``block_k``. Inside the block every trip count is static: a row group
meets all keys left of its diagonal in ONE wide unmasked tile and then
its diagonal sub-tile, so the online-softmax recurrence runs at most
twice a row and the compiler sees straight-line code. Blocks to the left
of the resident one (none when it is the whole sequence) run in one
dynamic loop of full-width unmasked tiles. The backward is the same
schedule with keys resident and the scores computed TRANSPOSED, (keys,
queries): lse and delta live along lanes and broadcast over the key
sublanes for free, and p.T @ do, ds.T @ q are plain matmuls; with one
resident block dq leaves the kernel finished, in the input dtype. On the
v5e at S 1024, head_dim 64 this replaced 128 x 128 tiles in a ``while``
loop: 14.9 + 25.0 ms a gpt2-small step in the two kernels against
71.6 + 95.1 (PERF.md section 6, PR 25).

The diagonal sub-tile is a STAIRCASE of chunks of edge ``e`` along the
stationary operand, each meeting only the rows that can see it: key chunk
j meets the row group's rows from j * e on (forward), query chunk i the
sub-block's keys up to (i + 1) * e (backward). The sub-blocks wholly
above the diagonal are never computed and only the e x e blocks ON it are
masked; row groups, wide tiles and the dynamic loop are as they were,
every slice a static multiple of 128 rows. With ``e = block_k`` the
sub-tile is one chunk under one mask, bit for bit the kernels of before;
a head at S 1024, (1024, 512) then computes 786,432 scores, 589,824 at
``e`` 128, for a causal half of 524,800 (``_scores_computed``). ``e``
follows the shapes, per kernel (``_auto_edges``): the backward, bound by
the MXU, takes 128 and 24.8 -> 19.5 ms a gpt2-small step; the
forward, whose time is its longest piece's and not its area's, stays
whole at head size 64 (PERF.md section 6, PR 35).

A causal call WITH A WINDOW runs the same schedule cut to the band
``q_pos - k_pos < window`` (``_banded``, from shapes alone: the blocks
nest, the window is whole sub-tiles and shorter than the padded
sequence). A row group then meets three kinds of piece and no loop:
its diagonal sub-tile FIRST, the staircase above; the ``window /
block_k - 1`` key blocks before it, wholly inside the band, unmasked; and
the sub-tile ``window`` keys back, visible strictly above its own
diagonal, as the MIRRORED staircase (key chunk j meets the rows up to
(j + 1) * e, the e x e block on the window's edge alone masked). One
body a kernel, its key origins from ``program_id``; the first row groups
of the sequence (the last key blocks, in the backward) lack the pieces
that would start before key 0 (past the last query) and skip them
(``_when``). At S 8192, window 1024 a head computes 8,847,360 scores at
``e`` 128 and 9,830,400 at 256 - on sub-tiles of 512 as on the 1024
that ``_auto_tiles`` chooses, where the band is the two staircases alone
- for a band of 7,864,832, where the general kernels' 45 masked tiles
of 512 x 512 are 11,796,480 (``_scores_computed``): Mellum2's three
sliding layers, 5.25 + 7.30 ms a layer in the general kernels and
3.53 + 4.88 on the band (PERF.md section 6, PR 37).

A call under the BLOCK-DIFFUSION mask (``block_mask`` = (B, L): a clean
and a noised copy of one sequence in the 2 L rows, a query seeing later
keys of its own block; not causal) runs the same two-level schedule laid
over that mask where its shapes tile (``_blocked``, from shapes alone:
the blocks nest, a copy is whole resident blocks with nothing padded, a
sub-tile holds whole diffusion blocks). The mask is the causal one
twice, in steps of B: a clean row group sees the clean keys left of its
diagonal whole and its diagonal sub-tile under ``blk(k) <= blk(q)``; the
NOISED row group of the same positions sees the SAME clean keys whole,
the diagonal sub-tile under ``blk(k) < blk(q)``, and of its own copy the
B x B blocks on the diagonal alone; the clean rows' noised quadrant is
empty. So a grid step holds BOTH copies' row groups of its positions (q
and out seen as (BH, 2, L, D), which moves nothing): one dynamic loop of
wide unmasked tiles serves both, each takes its staircase with the
e x e blocks ON the diagonal under a triangle of blocks, and a noised
row group first meets its own ``e`` keys a strip - the one piece the
causal schedule lacks, block-diagonal - so that every row has a live key
in its first piece (the first noised block sees no clean key at all) and
the finite mask value needs no guard. The backward is the same with keys
resident: a clean key block meets both copies' queries from its diagonal
on, under the two staircases; a noised one its own positions' queries, a
chunk against the same chunk. Still ONE ``flash_fwd`` and ONE
``flash_bwd`` call a layer. At L 4096, B 4, head size 128 - on
(1024, 1024), edges 256 | 128 - a head computes 18,874,368 scores
forward and 17,825,792 backward for ``L^2 + L B`` = 16,793,600 visible
pairs (``block_scores_computed``: 1.124, 1.061), 5.03 + 8.21 ms a layer
of 64 head-rows where the general kernels' walk took 10.02 + 15.07
(PERF.md section 6, PR 47).

A VALUE WIDTH OF ITS OWN. Latent attention (``models/olmoe.py``,
``mla_mixer``) has q and k 192 wide (128 and 64 rotated) beside v at 128.
On the ``nested`` schedule the two causal kernels read both widths from
their refs: q, k, dq and dk and their blocks are ``d_qk`` wide, v, the
output, dO, dv, the forward's accumulator and delta's sum ``d_v`` wide
(``Schedule.d_v``, set where v's width is not q's; the tiles and edges are
chosen by q's). Through ``flash_attention_rows`` alone; every other
schedule and entry holds q, k and v to one width (``_require_one_width``).
Until PR 54 the mixer padded all three to 256 lanes: exact, and 14 passes
of a 128-wide operand through the MXU a pair of positions where (192, 128)
takes 11 (QK^T, dq and dk still two: 192 is a tile and a half).

WHICH schedule a call runs is decided once, by ``_tiles``, from shapes
alone, and carried as a ``Schedule`` whose ``kind`` the calls that build
the kernels, the fused entry and the counter read: the two-level one
whole (``nested``), cut to a band (``banded``), laid over the block mask
(``blocked``), or the GENERAL kernels - non-causal calls, blocks that do
not nest, a window that is no multiple of ``block_k`` or as long as the
sequence, a block mask whose copy ends inside a tile, whose diffusion
block straddles a sub-tile's edge or whose length is padded. Those keep
one (block_q, block_k) tile a loop step under ``_tile_mask``, -inf and
its guards, the loop bounded by what the mask hides from a whole tile:
the block diagonal (under a block mask, B - 1 past it: with the clean
copy first every visible key is at most B - 1 past its query) and the
window's far edge. A dynamic trip count lowers to ``while_loop``: nothing
is unrolled or overlapped across trips, which is what the two-level
schedule avoids. No cell runs them, and no block mask a deployment trains
on: any power-of-two B up to 1,024 at a length of whole tiles is
``blocked``.

The causal path also uses a finite -1e30 mask value instead of -inf,
which removes every ``isfinite`` guard from the online-softmax
recurrence: with at least one live key per query row in its first tile
(every row attends key 0, or itself where a window puts the diagonal
first; padded query rows attend earlier live keys),
``exp(-1e30 - m)`` underflows to exactly 0 and the recurrence needs no
special cases. It applies NO padding mask at all: causality already
hides a padded key from every live query; in the backward padded k/v
rows are zeros, so padded-column score/probability garbage contributes
exactly 0 to dq (``ds @ k`` hits zero rows) and only to dk/dv rows that
are sliced off; padded query rows carry zero cotangents.

Design notes (pallas_guide.md):
- all matmuls request ``preferred_element_type=float32`` so the MXU
  accumulates in f32 regardless of the bf16 inputs;
- iota is always 2D (``broadcasted_iota``) — 1D iota does not lower;
- blocks always span the full head dim, satisfying Mosaic's "divisible by
  128 OR equal to the array dim" lane rule without padding D (padding to
  128 lanes would double the QK FLOPs at the flagship head_dim of 64);
  arbitrary sequence lengths ARE padded — up to the block multiple, with
  padded keys masked in-kernel and padded queries carrying zero
  cotangents.

Off-TPU the same kernels run under ``interpret=True`` so CPU tests and the
virtual-device dryrun exercise the identical code path.

Reference parity: none — the reference has no fused kernels (SURVEY.md
§2.3: its compute path is plain torch ops + NCCL). This is the
"pallas kernels for the hot ops" part of the TPU-first mandate.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

_NEG_INF = float("-inf")
# Finite mask value for the two-level causal schedule: large enough that
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


def _blocks_in(x, size: int):
    """``x // size`` of non-negative int32 positions (a value or a traced
    scalar): a shift where ``size`` is a power of two, as a diffusion
    block's length is in practice."""
    if size & (size - 1) == 0:
        return x >> (size.bit_length() - 1)
    return jax.lax.div(x, jnp.int32(size))


def _tile_mask(
    q_start, k_start, block_q: int, block_k: int, kv_len: int,
    causal: bool, padded: bool, window: Optional[int] = None,
    block: Optional[Tuple[int, int]] = None,
):
    """Validity mask for one (block_q, block_k) score tile, or None when
    every position is live. Shared by the general forward and backward
    kernels so the mask semantics cannot drift apart. ``window`` w keeps
    only keys with q_pos - k_pos < w (sliding-window / local attention).
    ``block`` (B, L) is the block-diffusion mask over a clean copy (rows
    0..L-1) and a noised copy (rows L..2L-1) of one sequence in blocks of
    B (``flash_attention``: ``block_mask``): two compares a pair on codes
    computed a row and a column - a clean key carries its block's number,
    a noised key that number past every clean one; a query the last clean
    block it sees (its own, or the one before where it is noised) and the
    one noised code it sees (its own block's; none where it is clean). A
    padded key (past 2L; L is whole blocks) carries a code no live query
    sees, so this mask needs no ``padded`` term."""
    if block is not None:
        size, length = block
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        past = _cdiv(length, size) + 1  # above every clean block's number
        q_noised, k_noised = q_pos >= length, k_pos >= length
        q_blk = _blocks_in(q_pos - jnp.where(q_noised, length, 0), size)
        k_code = _blocks_in(k_pos - jnp.where(k_noised, length, 0), size) + jnp.where(
            k_noised, past, 0
        )
        q_clean_up_to = q_blk - q_noised.astype(jnp.int32)
        q_noised_is = jnp.where(q_noised, q_blk + past, -1)
        return (k_code <= q_clean_up_to) | (k_code == q_noised_is)
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


# Two heads of 64 columns in one body hold two heads' temporaries: at
# 2048 resident positions that is 18.8 MB of VMEM where one head fits the
# 16 MiB a kernel gets unasked (20.2 MB at head size 256). The chip has
# 128 MiB.
_QKV_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=32 * 2**20)

# Mosaic's scoped VMEM limit for a call that names none.
_VMEM_DEFAULT = 16 * 2**20


def _resident_params(*blocks, wide_body: bool = False) -> dict:
    """``compiler_params`` of a three-array call, from the blocks
    (``(shape, dtype)`` each) its specs keep in VMEM, each with its second
    buffer. Up to 8,192 / head_dim x 128 rows a resident row - every shape
    until PR 36 - they leave the body a quarter of the default limit and
    more, and the call names no limit (its lowered text is what it was).
    Past that the limit is the buffers plus the default for the body: at
    8,192 positions of head size 128 the backward's resident q, dO and f32
    dq rows are 16.1 MiB buffered and the compiler refused the call by
    1 MiB (compiled for a described v5e, PR 36). ``wide_body``: a body
    that a quarter of the default does not hold, so the limit is always
    named - the ``banded`` and ``blocked`` kinds (PR 37: (2048, 1024) was
    refused by 180 KB in the step, not alone) and SEVERAL resident blocks
    of over 512 rows (PR 52: ``nested`` on 1024 rows, by 188 KB in a step)."""
    def lanes(width: int) -> int:
        # a row past one tile of 128 lanes holds whole tiles: 192 takes 256
        return width if width <= 128 else _cdiv(width, 128) * 128

    buffers = 2 * sum(
        math.prod(shape[:-1]) * lanes(shape[-1]) * jnp.dtype(dtype).itemsize
        for shape, dtype in blocks
    )
    if buffers <= 3 * _VMEM_DEFAULT // 4 and not wide_body:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=buffers + _VMEM_DEFAULT
    )}


def _traced_once(*static_argnames: str):
    """For the functions that build a ``pallas_call``: an inlined
    ``jax.jit`` leaves the lowered module as it was (every call site
    still gets its own Mosaic call) and gives the tracing cache, so a
    model of 24 layers traces each kernel body once for its shape and not
    24 times - seconds of every set-up, warm or cold (PERF.md section 6,
    PR 28)."""
    return functools.partial(
        jax.jit, inline=True, static_argnames=static_argnames
    )


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _nested(causal: bool, window, block_q: int, block_k: int) -> bool:
    """True when the call runs the two-level causal schedule (module
    docstring): a resident block of ``block_q`` rows cut into square
    sub-tiles of edge ``block_k``."""
    return causal and window is None and block_q % block_k == 0


def _banded(causal: bool, window, block_q: int, block_k: int, s_pad: int) -> bool:
    """True when a causal call WITH a window runs the two-level schedule
    cut to the band (module docstring): the blocks nest, the window is
    whole sub-tiles and shorter than the padded sequence. From shapes
    alone; every other windowed call keeps the general kernels."""
    return (
        causal and window is not None and block_q % block_k == 0
        and window % block_k == 0 and window < s_pad
    )


def _blocked(block, block_q: int, block_k: int, s_pad: int) -> bool:
    """True when a call under the block-diffusion mask ``block`` (B, L)
    runs the two-level schedule laid over that mask (module docstring):
    the blocks nest, each copy is whole resident blocks with nothing
    padded, and a sub-tile holds whole diffusion blocks, so that every
    staircase's edge can. From shapes alone; every other ``block_mask``
    call keeps the general kernels."""
    if block is None:
        return False
    size, length = block
    return (
        block_q % block_k == 0 and length % block_q == 0
        and 2 * length == s_pad and block_k % size == 0
    )


class Schedule(NamedTuple):
    """A call as ``_tiles`` decides it, once: which of the four schedules
    it runs - the two-level static one whole (``nested``), cut to a
    window's band (``banded``) or laid over the block-diffusion mask
    (``blocked``), or the ``general`` kernels - on which blocks and
    staircase edges ((forward, backward); None on the general kernels),
    with what the kernels are built from beside them. The static argument
    of ``_flash``; nothing after ``_tiles`` asks the predicates again."""

    kind: str
    block_q: int
    block_k: int
    s_pad: int
    edges: Optional[Tuple[int, int]]
    causal: bool
    interpret: bool
    kv_len: int
    window: Optional[int]
    block: Optional[Tuple[int, int]]
    # v's last width where it is not q's and k's (latent attention: q.k 192
    # beside v 128); the ``nested`` kernels alone take one
    d_v: Optional[int] = None

    @property
    def one_resident_block(self) -> bool:
        """Nested, the whole padded sequence resident: what the fused
        entry's kernels run, and where the backward writes dq finished."""
        return self.kind == "nested" and self.block_q == self.s_pad


def _when(live, piece, state):
    """``piece(state)`` where ``live`` holds, else ``state`` as it is:
    how the banded schedule leaves out the pieces that the first query
    blocks (forward) and the last key blocks (backward) do not have.
    ``live`` is a Python bool where the block's place is static."""
    if isinstance(live, bool):
        return piece(state) if live else state
    return jax.lax.cond(live, piece, lambda state: state, state)


def _triangle(n: int, queries_first: bool, size: int = 1, strict: bool = False):
    """(n, n) bool causal mask of a diagonal sub-tile (its first query
    and first key are the same position): query >= key, with queries
    along rows or, for transposed scores, along columns. In diffusion
    blocks of ``size`` (a divisor of n) the steps are blocks: a query
    sees the keys of its own block and of earlier ones, or (``strict``)
    of earlier ones alone."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    if size != 1:
        rows, cols = _blocks_in(rows, size), _blocks_in(cols, size)
    queries, keys = (rows, cols) if queries_first else (cols, rows)
    return queries > keys if strict else queries >= keys


def _block_masks(edge: int, block_mask, queries_first: bool):
    """The masks of the pieces that a schedule's edges cut, by the copy
    of the queries (None: a causal call's one triangle; 0 clean, 1
    noised under ``block_mask``), and the mask ON the diagonal of the
    noised copy's own quadrant (None without one): a query sees the keys
    of its own diffusion block, the later ones among them - what the
    clean triangle shows and the strict one does not."""
    if block_mask is None:
        return {None: _triangle(edge, queries_first)}, None
    size, _ = block_mask
    clean = _triangle(edge, queries_first, size)
    noised = _triangle(edge, queries_first, size, strict=True)
    return {0: clean, 1: noised}, clean & ~noised


def _copy_index(copy, rows, lanes):
    """The index of ``rows`` and ``lanes`` in a grid step's block of a
    causal call (``copy`` None: (1, rows, D)) or, under a block mask, of
    ``copy`` in its block of both copies ((1, 2, rows, D))."""
    return (0, rows, lanes) if copy is None else (0, copy, rows, lanes)


def _copy_rows(block_mask, copy, pos):
    """Where position ``pos`` of ``copy`` sits among a call's rows: as
    it is in a causal call (``copy`` None) and in the clean copy (0),
    past the clean copy's L rows in the noised one (1)."""
    return block_mask[1] + pos if copy else pos


def _head_lanes(h: int, head_dim: int, heads: int):
    """Where head ``h`` sits among the lanes of a block that holds
    ``heads`` heads side by side (a static slice; the whole block when
    it holds one)."""
    return slice(None) if heads == 1 else pl.ds(h * head_dim, head_dim)


def _scaled(q: jax.Array, sm_scale: Optional[float]) -> jax.Array:
    """q times the softmax scale as the three-array entry folds it in
    outside the kernels: an f32 product rounded back once. ``None``: q
    arrives scaled."""
    if sm_scale is None:
        return q
    return (q * jnp.float32(sm_scale)).astype(q.dtype)


def _rows(x: jax.Array, lo: int, hi: int) -> jax.Array:
    """Rows [lo, hi) of a value (static, multiples of the sublane tile);
    the value itself when that is all of it."""
    return x if (lo, hi) == (0, x.shape[0]) else x[lo:hi]


def _stack(parts) -> jax.Array:
    """The parts one under another (a lone part as it is)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _add_rows(total: jax.Array, part: jax.Array, lo: int) -> jax.Array:
    """``total`` with ``part`` added to its rows from ``lo`` on: how a
    piece of the staircase meets an accumulator of the whole sub-tile."""
    hi = lo + part.shape[0]
    return _stack([
        *([total[:lo]] if lo else []),
        _rows(total, lo, hi) + part,
        *([total[hi:]] if hi < total.shape[0] else []),
    ])


def _fwd_causal_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *,
    block_q: int, block_k: int, num_blocks: int, edge: int,
    heads: int = 1, sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None,
):
    """Two-level causal forward. Grid step (bh, qi) holds ``block_q``
    query rows as ``block_q // block_k`` row groups, each a straight line
    of at most two tiles over the block's own keys - every key left of
    the group's diagonal in ONE wide unmasked tile, then the diagonal
    sub-tile as a staircase of key chunks of ``edge`` - after one dynamic
    loop over the key blocks left of the resident one (none when it is
    the whole sequence). No padding mask: a padded key is only ever
    visible to padded query rows, which are sliced off.

    The staircase: key chunk j of the diagonal sub-tile meets the group's
    rows [j * edge, block_k) in one matmul, the chunk the stationary
    operand, and only its top ``edge`` x ``edge`` block, which is ON the
    diagonal, is masked: what lies wholly above the diagonal is never
    computed (``_scores_computed``). The recurrence still runs once a
    row for the sub-tile. ``edge == block_k`` is one chunk: the whole
    sub-tile under one mask.

    The three-array entry hands over one head a step, q pre-scaled
    (``heads`` 1, ``sm_scale`` None). The fused-projection entry hands
    over a 128-lane block of the projection, ``heads`` heads side by side
    (two at head size 64), and the scale: each head runs the same
    schedule on its own lanes, q scaled once a resident row group.

    With a ``window`` (``_banded``: n_win whole sub-tiles of ``block_k``)
    the schedule is cut to the band. Row group g of the sequence meets,
    in this order: its DIAGONAL sub-tile first, the staircase as above
    (every row meets itself there, so every row has a live key in its
    first piece and the finite mask value needs no guard; the window's
    edge first would not do: its last row sees none of it); the
    ``n_win - 1`` key blocks before it, wholly inside the band, unmasked;
    and the sub-tile ``window`` keys back, visible strictly above ITS
    diagonal, as the mirrored staircase: key chunk j meets the group's
    rows [0, (j + 1) * edge) and only its last ``edge`` x ``edge``
    block, ON the window's edge, is masked. No dynamic loop: one body,
    its key origins from ``program_id``; the first ``n_win`` row groups
    of the sequence have fewer pieces (``_when``).

    Under ``block_mask`` (B, L; ``_blocked``) a grid step holds the row
    groups of ``block_q`` positions TWICE: the clean copy's and the
    noised copy's, in a (1, 2, block_q, D) block of the rows seen as
    (BH, 2, L, D). Both meet the same clean keys: the blocks to the left
    in the one dynamic loop, a wide unmasked tile, and the diagonal
    sub-tile as the staircase above whose steps are diffusion blocks -
    ``blk(k) <= blk(q)`` on the masked block for the clean rows,
    ``blk(k) < blk(q)`` for the noised ones. Of its own copy a noised row
    group sees its diffusion blocks alone: the ``own`` pieces, chunk j of
    its own ``edge`` keys (L further on) meeting strip j and no other,
    under the block-diagonal mask. They come FIRST: the first noised
    block sees no clean key at all, every noised row sees itself there,
    so every row has a live key in its first piece as above."""
    n_sub = block_q // block_k
    # q and k share a width, v and the output theirs (the same, but for a
    # ``nested`` call whose value is narrower: ``Schedule.d_v``)
    D, d_v = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    # a static origin when there is one block: every slice is static
    block = 0 if num_blocks == 1 else pl.program_id(1)
    q0 = block * block_q
    triangles, own = _block_masks(edge, block_mask, True)
    # the row groups a step holds: (copy, r), a causal call's copy None
    groups = [(copy, r) for copy in triangles for r in range(n_sub)]

    def one_head(h: int, lanes):
        q_rows = [
            _scaled(q_ref[_copy_index(copy, pl.ds(r * block_k, block_k), lanes)], sm_scale)
            for copy, r in groups
        ]

        def tile(
            q_blk, state, k_start, width: int, stair: Optional[str] = None,
            tri=triangles.get(None),
        ):
            """One step of the recurrence for a row group, whose state is
            its strips' (m, l, acc): over ``width`` keys in one unmasked
            piece, or over a sub-tile of ``block_k`` keys in a
            staircase's pieces - the group's own keys (``diagonal``),
            those ``window`` back (``edge``) or the noised copy's at the
            group's positions (``own``), the blocks an edge cuts under
            ``tri``. A strip's statistics stay
            (edge, 1) values as the reductions leave them, the same in
            every lane: stacked or sliced they would have to be spread
            over the lanes again for every block they meet."""
            chunk = edge if stair else width
            pieces = width // chunk

            def met(j: int) -> Tuple[int, int]:
                """The group's rows that piece j meets: from the chunk's
                first query on, up to the last query that sees it, or
                all; of the own quadrant, the chunk's own rows."""
                if stair == "diagonal":
                    return j * edge, block_k
                if stair == "own":
                    return j * edge, (j + 1) * edge
                return 0, ((j + 1) * edge if stair == "edge" else block_k)

            def reach(i: int) -> range:
                """The pieces that meet strip i, the masked one last on
                the diagonal and first on the window's edge."""
                if stair == "diagonal":
                    return range(i + 1)
                if stair == "own":
                    return range(i, i + 1)
                return range(i if stair == "edge" else 0, pieces)

            v_chunks, s = [], []
            for j in range(pieces):
                keys = pl.ds(k_start + j * chunk, chunk)
                v_chunks.append(v_ref[0, keys, lanes])
                # (rows met, chunk) f32
                s.append(_dot_nt(_rows(q_blk, *met(j)), k_ref[0, keys, lanes]))

            def blocks(vals, i: int):
                """Strip i's (edge, chunk) block of every piece that
                reaches it."""
                out = []
                for j in reach(i):
                    lo = i * edge - met(j)[0]
                    out.append(_rows(vals[j], lo, lo + edge))
                return out

            new_state, p = [], []
            for i, (m, l, acc) in enumerate(state):
                s_i = blocks(s, i)
                if stair in ("diagonal", "own"):
                    s_i[-1] = jnp.where(tri, s_i[-1], _NEG_LARGE)
                elif stair == "edge":  # visible strictly above: key > query
                    s_i[0] = jnp.where(tri, _NEG_LARGE, s_i[0])
                # every row has a live key in its first tile (key 0, or
                # itself in the band), so m is finite from then on and
                # exp(_NEG_LARGE - m) is exactly 0: no -inf guards
                m_new = jnp.maximum(
                    m, functools.reduce(jnp.maximum, s_i).max(axis=-1, keepdims=True)
                )
                p_i = [jnp.exp(x - m_new) for x in s_i]
                corr = jnp.exp(m - m_new)
                l_new = l * corr + functools.reduce(jnp.add, p_i).sum(
                    axis=-1, keepdims=True
                )
                p.append(p_i)
                new_state.append((m_new, l_new, acc * corr))
            # a piece's matmul takes its strips' blocks one under another:
            # the chunk of v is the stationary operand, the stream as long
            # as the piece
            pv = [
                _dot_f32(
                    _stack([
                        p_i[reach(i).index(j)]
                        for i, p_i in enumerate(p) if j in reach(i)
                    ]).astype(v_j.dtype),
                    v_j,
                )
                for j, v_j in enumerate(v_chunks)
            ]
            return tuple(
                (m, l, functools.reduce(jnp.add, [acc, *blocks(pv, i)]))
                for i, (m, l, acc) in enumerate(new_state)
            )

        strips = block_k // edge
        state = [((
            jnp.full((edge, 1), _NEG_LARGE, jnp.float32),
            jnp.zeros((edge, 1), jnp.float32),
            jnp.zeros((edge, d_v), jnp.float32),
        ),) * strips] * len(groups)
        for g, (copy, r) in enumerate(groups):
            if copy:  # the noised rows' own blocks, before any clean key
                state[g] = tile(
                    q_rows[g], state[g],
                    _copy_rows(block_mask, copy, q0 + r * block_k), block_k, "own", own,
                )
        if window is None and num_blocks > 1:
            def interior(j, state):
                return tuple(
                    tile(q_rows[g], state[g], j * block_q, block_q)
                    for g in range(len(groups))
                )

            state = list(jax.lax.fori_loop(
                0, pl.program_id(1), interior, tuple(state)
            ))
        for g, (copy, r) in enumerate(groups):
            st, q_blk = state[g], q_rows[g]
            if window is None:
                if r:
                    st = tile(q_blk, st, q0, r * block_k)
                st = tile(
                    q_blk, st, q0 + r * block_k, block_k, "diagonal", triangles[copy]
                )
            else:
                group = block * n_sub + r  # of the sequence's row groups
                st = tile(q_blk, st, group * block_k, block_k, "diagonal")
                for back in range(1, window // block_k + 1):
                    st = _when(
                        r >= back or group >= back,  # static where r tells
                        lambda st, back=back: tile(
                            q_blk, st, (group - back) * block_k, block_k,
                            "edge" if back * block_k == window else None,
                        ),
                        st,
                    )
            for i, (m, l, acc) in enumerate(st):
                rows = r * block_k + i * edge
                o_ref[_copy_index(copy, pl.ds(rows, edge), lanes)] = (
                    acc / l
                ).astype(o_ref.dtype)
                # lse rides a full-row (1, 1, S) block revisited across the
                # sequential qi grid dim; each strip writes its slice
                lse_ref[h, 0, pl.ds(_copy_rows(block_mask, copy, q0 + rows), edge)] = (
                    m + jnp.log(l)
                )[:, 0]

    for h in range(heads):
        one_head(h, _head_lanes(h, D, heads))


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *,
    causal: bool, block_q: int, block_k: int, num_k: int,
    kv_len: int, window, block=None,
):
    """The general forward (sliding window, non-causal, blocks that do
    not nest, a block-diffusion mask whose shapes do not tile): one
    (block_q, block_k) tile a loop step, per-tile masks.
    q arrives PRE-SCALED by sm_scale (folded outside the kernel), so
    s = q @ k.T is the final score with no per-tile S x S multiply.

    Under ``block`` (B, L) the sweep is the causal one, a block wider: a
    row whose visible keys all lie in a later tile - the first noised
    block sees only itself - passes the tiles before it under the
    guards."""
    qi = pl.program_id(1)
    q = q_ref[0]  # (block_q, D), input dtype
    D = q.shape[-1]
    padded = kv_len < num_k * block_k

    def tile(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot_nt(q, k_blk)  # (block_q, block_k) f32
        ok = _tile_mask(
            qi * block_q, j * block_k, block_q, block_k, kv_len,
            causal, padded, window, block,
        )
        if ok is not None:
            s = jnp.where(ok, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # rows with every key masked keep m = -inf; guard
        # exp(-inf - -inf)
        safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m[:, None]), 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[:, None] + _dot_f32(
            p.astype(v_blk.dtype), v_blk
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((block_q,), _NEG_INF, jnp.float32),
        jnp.zeros((block_q,), jnp.float32),
        jnp.zeros((block_q, D), jnp.float32),
    )
    num_k_live = _cdiv(kv_len, block_k)  # skip fully-padded key blocks
    if causal:
        # key blocks strictly above the block diagonal are fully masked
        hi = jnp.minimum(
            num_k_live, ((qi + 1) * block_q + block_k - 1) // block_k
        )
    elif block is not None:
        # under the block mask a visible key is at most B - 1 past its
        # query
        hi = jnp.minimum(
            num_k_live, ((qi + 1) * block_q + block[0] - 1 + block_k - 1) // block_k
        )
    else:
        hi = num_k_live
    lo = 0
    if window is not None:
        # key blocks fully left of the sliding window are masked for
        # every query row in this block
        lo = jnp.maximum(0, (qi * block_q - window + 1) // block_k)
    m, l, acc = jax.lax.fori_loop(lo, hi, tile, init)
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse rides a full-row (1, 1, S) block revisited across the sequential
    # qi grid dim (a (1, block_q) 2D block violates Mosaic's (8, 128) tile
    # floor); each step writes its slice
    lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = jnp.where(
        jnp.isfinite(m), m + jnp.log(l_safe), _NEG_INF
    )


@_traced_once("schedule")
def _flash_fwd_call(q: jax.Array, k: jax.Array, v: jax.Array, *, schedule: Schedule):
    """q (pre-scaled)/k/v: (BH, S_pad, D) -> out (BH, S_pad, D),
    lse (BH, 1, S_pad) f32. Positions >= kv_len are zero padding, masked
    out of every softmax. Where ``schedule.d_v`` says so v and out are
    ``d_v`` wide (their blocks and the kernel's accumulator with them) and
    D is q's and k's width alone."""
    BH, S, D = q.shape
    d_v = schedule.d_v or D
    kind, block_q, block_k = schedule.kind, schedule.block_q, schedule.block_k
    num_q, num_k = _cdiv(S, block_q), _cdiv(S, block_k)
    blocked = kind == "blocked"
    qspec = pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0))
    ospec = pl.BlockSpec((1, block_q, d_v), lambda bh, qi: (bh, qi, 0))
    if blocked:
        # a grid step holds both copies' row groups of the same positions:
        # q and out as (BH, 2, L, D), which moves nothing
        num_q = S // 2 // block_q
        q = q.reshape(BH, 2, S // 2, D)
        qspec = ospec = pl.BlockSpec((1, 2, block_q, D), lambda bh, qi: (bh, 0, qi, 0))
    if kind != "general":
        kernel = functools.partial(
            _fwd_causal_kernel, block_q=block_q, block_k=block_k,
            num_blocks=num_q, edge=schedule.edges[0], window=schedule.window,
            block_mask=schedule.block if blocked else None,
        )
    else:
        kernel = functools.partial(
            _fwd_kernel, causal=schedule.causal,
            block_q=block_q, block_k=block_k, num_k=num_k, kv_len=schedule.kv_len,
            window=schedule.window, block=schedule.block,
        )
    def row(width: int):
        return pl.BlockSpec((1, S, width), lambda bh, qi: (bh, 0, 0))

    held = 2 * block_q if blocked else block_q  # query rows a step holds
    out, lse = pl.pallas_call(
        kernel,
        grid=(BH, num_q),
        in_specs=[qspec, row(D), row(d_v)],
        out_specs=[
            ospec,
            pl.BlockSpec((1, 1, S), lambda bh, qi: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:-1] + (d_v,), q.dtype),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ],
        interpret=schedule.interpret,
        name="flash_fwd",  # the kernel's name in a device trace
        **_resident_params(
            ((held, D), q.dtype), ((S, D), k.dtype), ((S, d_v), v.dtype),
            ((held, d_v), q.dtype), ((S,), jnp.float32),
            wide_body=kind in ("banded", "blocked") or (num_q > 1 and block_q > 512),
        ),
    )(q, k, v)
    return out.reshape(BH, S, D) if blocked else out, lse


def _qkv_blocks(shape: Tuple[int, int, int], n_heads: int):
    """How the fused-projection kernels cut a (B, S_pad, 3*H*D) array:
    the grid (batch row, lane block), a lane block's columns and heads,
    the BlockSpec of a step's block in the q, k or v third (0, 1, 2; the
    first also places a (B, S_pad, H*D) array's block) and that of its
    heads' rows of a (B*H, 1, S_pad) array."""
    B, S, width = shape
    hd = width // 3
    D = hd // n_heads
    lanes = _qkv_lanes(n_heads, D)
    n_lane = hd // lanes
    third = lambda t: pl.BlockSpec(
        (1, S, lanes), lambda b, j: (b, 0, t * n_lane + j)
    )
    row = pl.BlockSpec(
        (lanes // D, 1, S), lambda b, j: (b * n_lane + j, 0, 0)
    )
    return (B, n_lane), lanes, lanes // D, third, row


@_traced_once("n_heads", "sm_scale", "block_k", "edge", "interpret")
def _flash_fwd_qkv_call(
    qkv: jax.Array, *, n_heads: int, sm_scale: float,
    block_k: int, edge: int, interpret: bool,
):
    """qkv (B, S_pad, 3*H*D), the fused projection as it leaves its
    matmul -> out (B, S_pad, H*D), lse (B*H, 1, S_pad) f32. Causal, the
    whole padded sequence one resident block. A grid step takes one block
    of ``lanes`` columns - the narrowest Mosaic's lane rule allows, two
    heads at head size 64, one at 128 - of q, and the blocks at the same
    place in the k and v thirds of the same array: heads are on the grid,
    and the body does not grow with their count."""
    B, S, width = qkv.shape
    grid, _, heads, third, row = _qkv_blocks(qkv.shape, n_heads)
    kernel = functools.partial(
        _fwd_causal_kernel, block_q=S, block_k=block_k, num_blocks=1,
        edge=edge, heads=heads, sm_scale=sm_scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[third(0), third(1), third(2)],
        out_specs=[third(0), row],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, width // 3), qkv.dtype),
            jax.ShapeDtypeStruct((B * n_heads, 1, S), jnp.float32),
        ],
        compiler_params=_QKV_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",  # as the three-array call: trace readers match it
    )(qkv, qkv, qkv)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_causal_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, *,
    block_q: int, block_k: int, num_blocks: int, edge: int,
    heads: int = 1, sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None,
):
    """Two-level causal backward, the forward's schedule with the roles
    swapped: grid step (bh, ki) holds ``block_q`` KEY rows as sub-blocks
    of ``block_k``; each meets its diagonal query sub-tile as a staircase
    of query chunks of ``edge``, then every other query of the block in
    ONE wide unmasked tile, then the query blocks below in one dynamic
    loop.

    Scores are computed TRANSPOSED, (keys, queries): lse and delta live
    along lanes and broadcast over the key sublanes for free, and
    p.T @ do, ds.T @ q are plain matmuls. No padding mask (see
    ``_flash_bwd_call``).

    The staircase, the forward's mirrored: query chunk i of the diagonal
    sub-tile meets the sub-block's key rows [0, (i + 1) * edge), the
    chunk the stationary operand of four matmuls, and only the last
    ``edge`` of those rows, the block ON the diagonal, are masked. s, p,
    dp and ds exist for those pieces alone, so the five matmuls shrink
    together; the fifth, dq, takes the pieces of ds a KEY strip at a
    time, that strip of k stationary. ``edge == block_k`` is one chunk:
    the whole sub-tile under one mask.

    ``heads`` and ``sm_scale`` as in ``_fwd_causal_kernel``; with a scale
    (the fused-projection entry: one resident block) q is scaled once,
    whole, and dq once as it is written - what autodiff of the outside
    fold does to the three-array entry's dq.

    With a ``window`` (``_banded``) key sub-block g of the sequence meets
    query block g under the staircase, the ``window / block_k - 1`` query
    blocks after it unmasked, and the block ``window`` further on under
    the MIRRORED staircase (visible where key > query, counted from the
    sub-tile's corner): query chunk i meets the key rows from i * edge
    on, the first ``edge`` of them, ON the window's edge, masked. No
    recurrence, so no order to keep, and no dynamic loop; the last key
    blocks of the sequence have fewer pieces (``_when``). Every piece
    past the diagonal adds its dq into the revisited f32 row.

    Under ``block_mask`` (B, L; ``_blocked``) a grid step holds the key
    sub-blocks of ``block_q`` positions twice, the clean copy's and the
    noised copy's, in (1, 2, block_q, D) blocks of k, v, dk and dv seen
    as (BH, 2, L, D); q, dO and dq stay whole rows. A CLEAN key sub-block
    meets BOTH copies' queries at every step of the schedule: the blocks
    below in the one loop, the wide tile, and the diagonal sub-tile under
    two staircases whose steps are diffusion blocks, ``blk(k) <=
    blk(q)`` for the clean queries and ``blk(k) < blk(q)`` for the noised
    ones. A NOISED key sub-block meets its own positions' noised queries
    alone, ``edge`` of them at a time against the same ``edge`` keys
    under the block-diagonal mask. dq is the revisited f32 row."""
    n_sub = block_q // block_k
    one_block = num_blocks == 1
    assert sm_scale is None or one_block
    # k, dq and dk at q's width, dO and dv at v's (``_fwd_causal_kernel``)
    D, d_v = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    block = 0 if one_block else pl.program_id(1)
    k0 = block * block_q
    triangles, own = _block_masks(edge, block_mask, False)
    copies = list(triangles)  # of the queries: None in a causal call
    # the key sub-blocks a step holds, (copy, c): the clean ones, which
    # have the schedule, first
    groups = [(copy, c) for copy in copies for c in range(n_sub)]
    # dq is the revisited accumulator, not written finished by one step
    shared_dq = window is not None or not one_block or block_mask is not None

    def one_head(h: int, lanes):
        k_rows, v_rows = (
            [
                ref[_copy_index(copy, pl.ds(c * block_k, block_k), lanes)]
                for copy, c in groups
            ]
            for ref in (k_ref, v_ref)
        )
        q_all = None if sm_scale is None else _scaled(q_ref[0, :, lanes], sm_scale)

        def tile(
            c: int, q_start, width: int, chunk: Optional[int] = None,
            mirrored: bool = False, tri_t=triangles.get(None), alone: bool = False,
        ):
            """(dk, dv) of key sub-block c from its meeting with the
            queries [q_start, q_start + width), and that meeting's ds
            transposed, (keys, queries), for their dq. All of the
            sub-block's keys, unmasked; or, for query chunk ``chunk`` of
            a staircase (``edge`` queries), the key rows it can see: up
            to the chunk's own positions, those under the mask
            ``tri_t``, or (``mirrored``, the window's edge) from them
            on; or (``alone``, the noised copy's own quadrant) the
            chunk's own positions and no other."""
            if chunk is None:
                lo, hi = 0, block_k
            elif alone:
                lo, hi = chunk * edge, (chunk + 1) * edge
            elif mirrored:
                lo, hi = chunk * edge, block_k
            else:
                lo, hi = 0, (chunk + 1) * edge
            keys = hi - lo
            if q_all is None:
                q_blk = q_ref[0, pl.ds(q_start, width), lanes]
            else:  # one block: q_start is static
                q_blk = q_all[q_start:q_start + width]
            do_blk = do_ref[0, pl.ds(q_start, width), lanes]
            lse = lse_ref[h, :, pl.ds(q_start, width)]  # (1, width)
            delta = delta_ref[h, :, pl.ds(q_start, width)]
            k_blk, v_blk = _rows(k_rows[c], lo, hi), _rows(v_rows[c], lo, hi)
            s_t = _dot_nt(k_blk, q_blk)  # (keys, queries); q scaled
            p_t = jnp.exp(s_t - lse)
            if mirrored:  # visible strictly below the corner: key > query
                p_t = _stack([
                    jnp.where(tri_t, 0.0, _rows(p_t, 0, edge)),
                    *([p_t[edge:]] if keys > edge else []),
                ])
            elif chunk is not None:
                p_t = _stack([
                    *([p_t[:keys - edge]] if keys > edge else []),
                    jnp.where(tri_t, _rows(p_t, keys - edge, keys), 0.0),
                ])
            dv = _dot_f32(p_t.astype(do_blk.dtype), do_blk)
            dp_t = _dot_nt(v_blk, do_blk)
            ds_t = (p_t * (dp_t - delta)).astype(q_blk.dtype)  # one cast,
            return _dot_f32(ds_t, q_blk), dv, ds_t              # used twice

        def staircase(
            c: int, chunk_start, dk, dv, dq, mirrored: bool = False,
            tri_t=triangles.get(None),
        ):
            """``dk``, ``dv`` of key sub-block c and ``dq`` of a query
            block, whose chunk i starts at ``chunk_start(i)``, each with
            what their meeting under a staircase adds: the diagonal
            sub-tile's, or (``mirrored``) the window's edge's. (A
            callable, so that the diagonal's origins lower to the
            operations they were before the window had a staircase.)"""
            # a query chunk at a time, each over the keys it can see
            ds_chunks = []
            for i in range(stairs):
                dk_i, dv_i, ds_t = tile(c, chunk_start(i), edge, i, mirrored, tri_t)
                first = i * edge if mirrored else 0
                dk, dv = _add_rows(dk, dk_i, first), _add_rows(dv, dv_i, first)
                ds_chunks.append(ds_t)
            # its dq a KEY strip at a time, the strip of k the stationary
            # operand once for every chunk that sees it (a chunk at a time
            # would load each strip again for every chunk below it); the
            # strip that every chunk sees first: the whole row group's dq
            for j in reversed(range(stairs)) if mirrored else range(stairs):
                chunks = range(j + 1) if mirrored else range(j, stairs)
                ds_t = []
                for i in chunks:  # rows of strip j in chunk i's piece
                    lo = (j - i) * edge if mirrored else j * edge
                    ds_t.append(ds_chunks[i][lo:lo + edge])
                dq_j = _dot_tn(
                    ds_t[0] if len(ds_t) == 1 else jnp.concatenate(ds_t, axis=1),
                    k_rows[c][j * edge:(j + 1) * edge],
                )
                if len(chunks) == stairs:
                    dq = dq + dq_j
                else:
                    dq = _add_rows(dq, dq_j, 0 if mirrored else j * edge)
            return dk, dv, dq

        def at(copy, pos):
            return _copy_rows(block_mask, copy, pos)

        zeros = jnp.zeros((block_k, D), jnp.float32)
        zeros_v = zeros if d_v == D else jnp.zeros((block_k, d_v), jnp.float32)
        dks, dvs = [zeros] * len(k_rows), [zeros_v] * len(k_rows)
        if shared_dq:
            # dq accumulates into a REVISITED full-row f32 output block: the
            # TPU grid is sequential, so every ki step of one bh row sees the
            # same resident VMEM block; zero it on the first step.
            @pl.when(pl.program_id(1) == 0)
            def _init_dq():
                dq_ref[...] = jnp.zeros_like(dq_ref)

        if window is None and not one_block:
            def below(i, carry):
                dks, dvs = map(list, carry)
                for copy in copies:
                    dq = 0.0
                    for c in range(n_sub):
                        dk, dv, ds_t = tile(c, at(copy, i * block_q), block_q)
                        dks[c], dvs[c] = dks[c] + dk, dvs[c] + dv
                        dq = dq + _dot_tn(ds_t, k_rows[c])
                    dq_ref[0, pl.ds(at(copy, i * block_q), block_q), lanes] += dq
                return tuple(dks), tuple(dvs)

            # the key sub-blocks that have the schedule: all of a causal
            # call's, the clean copy's
            dks[:n_sub], dvs[:n_sub] = jax.lax.fori_loop(
                pl.program_id(1) + 1, num_blocks, below,
                (tuple(dks[:n_sub]), tuple(dvs[:n_sub])),
            )
        # of the block's own query row groups, by their copy, f32
        dqs = {copy: [0.0] * n_sub for copy in copies}
        stairs = block_k // edge
        for c in range(n_sub):
            # the diagonal sub-tile, under each copy's staircase
            for copy in copies:
                dks[c], dvs[c], dqs[copy][c] = staircase(
                    c, lambda i, copy=copy: at(copy, k0 + c * block_k + i * edge),
                    dks[c], dvs[c], dqs[copy][c], tri_t=triangles[copy],
                )
            if window is not None:
                group = block * n_sub + c  # of the sequence's key sub-blocks

                def meet(acc, ahead: int):
                    """Key sub-block c and the query block ``ahead``
                    further on: unmasked, or the window's edge."""
                    q_start = (group + ahead) * block_k
                    if ahead * block_k == window:
                        dk, dv, dq = staircase(
                            c, lambda i: q_start + i * edge, *acc, 0.0, True
                        )
                    else:
                        dk, dv, ds_t = tile(c, q_start, block_k)
                        dk, dv = acc[0] + dk, acc[1] + dv
                        dq = _dot_tn(ds_t, k_rows[c])
                    dq_ref[0, pl.ds(q_start, block_k), lanes] += dq
                    return dk, dv

                for ahead in range(1, window // block_k + 1):
                    dks[c], dvs[c] = _when(
                        # static where the block holds those queries too
                        c + ahead < n_sub or group + ahead < num_blocks * n_sub,
                        functools.partial(meet, ahead=ahead),
                        (dks[c], dvs[c]),
                    )
            elif c < n_sub - 1:  # then every query of the block below it
                count = n_sub - 1 - c
                for copy in copies:
                    dk, dv, ds_t = tile(
                        c, at(copy, k0 + (c + 1) * block_k), count * block_k
                    )
                    dks[c], dvs[c] = dks[c] + dk, dvs[c] + dv
                    dq = _dot_tn(ds_t, k_rows[c])
                    for r in range(count):
                        dqs[copy][c + 1 + r] = (
                            dqs[copy][c + 1 + r] + dq[r * block_k:(r + 1) * block_k]
                        )
        if block_mask is not None:
            # the noised copy's key sub-blocks: their own positions' noised
            # queries, a chunk against the same chunk of keys
            for c in range(n_sub):
                g = n_sub + c
                for i in range(stairs):
                    lo = i * edge
                    dk, dv, ds_t = tile(
                        g, at(1, k0 + c * block_k + lo), edge, i, tri_t=own, alone=True
                    )
                    dks[g], dvs[g] = _add_rows(dks[g], dk, lo), _add_rows(dvs[g], dv, lo)
                    dqs[1][c] = _add_rows(
                        dqs[1][c], _dot_tn(ds_t, k_rows[g][lo:lo + edge]), lo
                    )
        for g, (copy, r) in enumerate(groups):
            rows = pl.ds(at(copy, k0 + r * block_k), block_k)
            if shared_dq:
                dq_ref[0, rows, lanes] += dqs[copy][r]
            else:  # every key of the row is here: dq is final
                dq = dqs[copy][r]
                dq = dq if sm_scale is None else dq * jnp.float32(sm_scale)
                dq_ref[0, rows, lanes] = dq.astype(dq_ref.dtype)
            held = _copy_index(copy, pl.ds(r * block_k, block_k), lanes)
            dk_ref[held] = dks[g].astype(dk_ref.dtype)
            dv_ref[held] = dvs[g].astype(dv_ref.dtype)

    for h in range(heads):
        one_head(h, _head_lanes(h, D, heads))


def _bwd_qkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dqkv_ref, dq_buf, dk_buf, dv_buf, sems, **schedule,
):
    """The backward of the fused-projection entry: ``_bwd_causal_kernel``
    on a lane block of the q, k and v thirds, its three results written
    into ONE cotangent array. A ``pallas_call`` output has one block a
    grid step and this needs three, a third of the array apart, so the
    array stays in HBM and the kernel sends the blocks itself, from two
    VMEM slots: a step's copies run under the next step's compute and
    are awaited when their slot comes round again (the TPU grid is
    sequential), the last two at the last step."""
    n_lane = pl.num_programs(1)
    b, j = pl.program_id(0), pl.program_id(1)
    step = b * n_lane + j
    slot = step % 2
    lanes = q_ref.shape[-1]

    def copies(slot):
        return [
            pltpu.make_async_copy(
                buf.at[slot],
                dqkv_ref.at[b, :, pl.ds((third * n_lane + j) * lanes, lanes)],
                sems.at[slot, third],
            )
            for third, buf in enumerate((dq_buf, dk_buf, dv_buf))
        ]

    def wait(slot):
        # a wait reads only the semaphore and the size of the copy
        for copy in copies(slot):
            copy.wait()

    pl.when(step >= 2)(lambda: wait(slot))
    _bwd_causal_kernel(
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
        *(buf.at[pl.ds(slot, 1)] for buf in (dq_buf, dk_buf, dv_buf)),
        **schedule,
    )
    for copy in copies(slot):
        copy.start()
    last = step == pl.num_programs(0) * n_lane - 1
    pl.when(last & (step >= 1))(lambda: wait(1 - slot))
    pl.when(last)(lambda: wait(slot))


def _bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, *,
    causal: bool, block_q: int, block_k: int, num_q: int,
    kv_len: int, window, block=None,
):
    """The general backward: one (block_q, block_k) tile a loop step,
    the forward's sweep transposed under the same per-tile masks."""
    ki = pl.program_id(1)
    k_blk = k_ref[0]  # (block_k, D), input dtype
    v_blk = v_ref[0]
    D = k_blk.shape[-1]
    # Padded QUERY rows need no mask here: their cotangent (do) and delta
    # are zero, so ds and p.T @ do vanish (their lse is finite — causal
    # padded query rows attend earlier live keys — so p stays finite and
    # 0 * p cannot produce NaN). Padded KEY columns are masked.
    padded = kv_len < q_ref.shape[1]  # static: S_pad > kv_len

    # dq accumulates into a REVISITED full-row f32 output block: the TPU
    # grid is sequential, so every ki step of one bh row sees the same
    # resident VMEM block; zero it on the first step.
    @pl.when(ki == 0)
    def _init_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def tile(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = _dot_nt(q_blk, k_blk)  # q pre-scaled by sm_scale
        p = jnp.exp(s - lse[:, None])
        ok = _tile_mask(
            i * block_q, ki * block_k, block_q, block_k, kv_len,
            causal, padded, window, block,
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
    if causal:
        # query blocks strictly below the block diagonal see none of
        # this key block
        lo = (ki * block_k) // block_q
    elif block is not None:
        # under the block mask a visible query is at most B - 1 before
        # its key
        lo = jnp.maximum(ki * block_k - (block[0] - 1), 0) // block_q
    else:
        lo = 0
    hi = num_q
    if window is not None:
        # query blocks fully right of the window (q_min - k_max >= w)
        # see none of this key block
        hi = jnp.minimum(
            num_q, ((ki + 1) * block_k - 1 + window) // block_q + 1
        )
    dk, dv = jax.lax.fori_loop(lo, hi, tile, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@_traced_once("schedule")
def _flash_bwd_call(q, k, v, o, lse, do, *, schedule: Schedule):
    BH, S, D = q.shape
    d_v = schedule.d_v or D  # v's, o's, dO's and dv's; D is q's, k's, dq's and dk's
    kind, block_q, block_k = schedule.kind, schedule.block_q, schedule.block_k
    num_q = S // block_q
    blocked = kind == "blocked"
    # delta_i = sum_d do_id * o_id — one fused elementwise+reduce, not worth
    # a kernel
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]  # (BH, 1, S) — same full-row layout as lse

    def row3(width: int):
        return pl.BlockSpec((1, S, width), lambda bh, i: (bh, 0, 0))

    row2 = pl.BlockSpec((1, 1, S), lambda bh, i: (bh, 0, 0))
    if kind != "general":
        # The causal schedule applies NO padding mask: padded k/v rows
        # are zeros, so padded-column score/probability garbage adds
        # exactly 0 to dq (``ds @ k`` hits zero rows) and only reaches
        # dk/dv ROWS that the caller slices off; padded query rows carry
        # zero cotangents.
        key_rows = block_q  # the resident block is a key block here
        if blocked:  # a step holds both copies' key blocks (below)
            num_q = S // 2 // block_q
        kernel = functools.partial(
            _bwd_causal_kernel, block_q=block_q, block_k=block_k,
            num_blocks=num_q, edge=schedule.edges[1], window=schedule.window,
            block_mask=schedule.block if blocked else None,
        )
    else:
        key_rows = block_k
        kernel = functools.partial(
            _bwd_kernel, causal=schedule.causal,
            block_q=block_q, block_k=block_k, num_q=num_q, kv_len=schedule.kv_len,
            window=schedule.window, block=schedule.block,
        )
    def kblk3(width: int):
        if blocked:
            return pl.BlockSpec((1, 2, key_rows, width), lambda bh, i: (bh, 0, i, 0))
        return pl.BlockSpec((1, key_rows, width), lambda bh, i: (bh, i, 0))

    if blocked:
        # k, v, dk and dv as (BH, 2, L, D), which moves nothing: the clean
        # and the noised key block of the same positions a grid step
        k, v = k.reshape(BH, 2, S // 2, D), v.reshape(BH, 2, S // 2, D)
    held = 2 * key_rows if blocked else key_rows  # key rows a step holds
    # over several key blocks dq is the revisited f32 accumulator (cast to
    # q.dtype below); one block writes it finished
    dq_dtype = q.dtype if schedule.one_resident_block else jnp.float32

    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(BH, S // held),
        in_specs=[row3(D), kblk3(D), kblk3(d_v), row3(d_v), row2, row2],
        out_specs=[row3(D), kblk3(D), kblk3(d_v)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), dq_dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=schedule.interpret,
        name="flash_bwd",
        **_resident_params(
            ((S, D), q.dtype), ((S, d_v), do.dtype), ((S, D), dq_dtype),
            ((S,), jnp.float32), ((S,), jnp.float32),
            *[((held, D), k.dtype), ((held, d_v), v.dtype)] * 2,
            wide_body=kind in ("banded", "blocked") or (num_q > 1 and block_q > 512),
        ),
    )(q, k, v, do, lse, delta)
    if blocked:
        dk, dv = dk.reshape(BH, S, D), dv.reshape(BH, S, D)
    return dq.astype(q.dtype), dk, dv


@_traced_once("n_heads", "sm_scale", "block_k", "edge", "interpret")
def _flash_bwd_qkv_call(
    qkv, o, lse, do, *, n_heads: int, sm_scale: float,
    block_k: int, edge: int, interpret: bool,
):
    """The cotangent of ``_flash_fwd_qkv_call``'s qkv, (B, S_pad, 3*H*D),
    written by the one kernel into one array (``_bwd_qkv_kernel``)."""
    B, S, width = qkv.shape
    grid, lanes, heads, third, row = _qkv_blocks(qkv.shape, n_heads)
    # delta in the kernels' own (B*H, 1, S) form, as lse: 4 bytes a row
    # and head, XLA's to transpose
    delta = jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            B, S, n_heads, -1
        ),
        axis=-1,
    ).transpose(0, 2, 1).reshape(B * n_heads, 1, S)
    kernel = functools.partial(
        _bwd_qkv_kernel, block_q=S, block_k=block_k, num_blocks=1,
        edge=edge, heads=heads, sm_scale=sm_scale,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[third(0), third(1), third(2), third(0), row, row],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        scratch_shapes=[pltpu.VMEM((2, S, lanes), qkv.dtype)] * 3
        + [pltpu.SemaphoreType.DMA((2, 3))],
        compiler_params=_QKV_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd",
    )(qkv, qkv, qkv, do, lse, delta)


# ---------------------------------------------------------------------------
# custom-vjp plumbing on the (BH, S, D) canonical layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(schedule: Schedule, q, k, v):
    out, _ = _flash_fwd_res(schedule, q, k, v)
    return out


def _flash_fwd_res(schedule: Schedule, q, k, v):
    out, lse = _named_residuals(*_flash_fwd_call(q, k, v, schedule=schedule))
    return out, (q, k, v, out, lse)


def _flash_bwd_res(schedule: Schedule, res, g):
    return _flash_bwd_call(*res, g, schedule=schedule)


_flash.defvjp(_flash_fwd_res, _flash_bwd_res)


def _named_residuals(out, lse):
    """Name the kernel outputs so a jax.checkpoint policy can SAVE them:
    the vjp needs (out, lse) as residuals, and with both saved the remat
    backward's forward replay prunes the fwd pallas launch entirely
    (q/k/v are re-derived from the cheap qkv projection instead).
    checkpoint_name is the identity outside a policy-remat context."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(out, "flash_out"), checkpoint_name(lse, "flash_lse")


# The same plumbing on the fused projection's own layout: one array in,
# one cotangent out, nothing transposed, split or concatenated between.


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_qkv(cfg, qkv):
    out, _ = _flash_qkv_fwd_res(cfg, qkv)
    return out


def _flash_qkv_fwd_res(cfg, qkv):
    n_heads, sm_scale, block_k, edges, interpret = cfg
    out, lse = _named_residuals(*_flash_fwd_qkv_call(
        qkv, n_heads=n_heads, sm_scale=sm_scale, block_k=block_k,
        edge=edges[0], interpret=interpret,
    ))
    return out, (qkv, out, lse)


def _flash_qkv_bwd_res(cfg, res, g):
    n_heads, sm_scale, block_k, edges, interpret = cfg
    return (_flash_bwd_qkv_call(
        *res, g, n_heads=n_heads, sm_scale=sm_scale, block_k=block_k,
        edge=edges[1], interpret=interpret,
    ),)


_flash_qkv.defvjp(_flash_qkv_fwd_res, _flash_qkv_bwd_res)


def _pick_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _auto_tiles(
    seq: int, head_dim: int, interpret: bool, nested: bool = True,
    window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None,
) -> Tuple[int, int]:
    """The (block_q, block_k) a call runs when it names none, from what
    it can see: the sequence length, the head size, whether the kernels
    are interpreted, whether the whole sequence may be the one resident
    block (``nested``: causal, no window), a ``window``, a ``block_mask``.
    Measured on the v5e inside the whole step (PERF.md section 6).

    Tiles key on the PADDED length, not raw S: training slices the last
    token off (tokens[:, :-1]), so an in-model sequence is 1023 or 2047.
    On hardware the lse row is sliced along the LANE dim in block-wide
    stores, so blocks are 128-multiples (Mosaic rejects misaligned vector
    stores - observed at S=99 on v5e); interpret mode needs 8 sublanes.

    Nested, up to 2048 positions: the whole sequence is one resident
    block, cut into the largest sub-tiles of 512, 256 or 128 that divide
    it. At S 1024, head_dim 64 that is (1024, 512): 14.9 + 25.0 ms a
    gpt2-small step in the two kernels against 17.1 + 23.6 at (1024, 256),
    19.9 + 23.9 at (1024, 128), and 71.6 + 95.1 for the (128, 128) tiles
    in a dynamic loop that it replaces; head_dim 128 ranks them the same
    (smaller row groups compute less above the diagonal and lose more by
    shorter streams; ``_auto_edges`` cuts the diagonal sub-tile alone).

    Nested, past 2048 positions: resident blocks of 1024 rows in two row
    groups of 512 where the sequence padded to 512 is whole such blocks,
    else (512, 512), which pads no further: every such call's tiles
    until PR 52, and a wider head's still. Ms a step in the kernel inside
    ``ouro-ft1``'s step (S 4096, D 128, 32 head-rows, 48 forward and 24
    backward calls, edges 256 | 128; my chip runs, PR 52), forward |
    backward: (512, 512) 65.92 | 53.86, (1024, 512) 57.72 | 50.32,
    (1024, 1024) 60.43 | 49.33, (2048, 1024) 54.96 | 48.03: the pairs
    computed depend on the edge alone (``_scores_computed``), the same
    work in 4 grid steps and 6 loop trips a head for 8 and 28. The
    kernels alone, ms a call, same order: S 8192, D 128, 64 head-rows
    9.53 | 17.39, 8.41 | 16.11, 8.96 | 15.98, 8.08 | 15.13; 256 lanes, 8
    head-rows 2.02 | 4.20, 1.88 | 3.94, 1.93 | 3.94, not run. NOT taken:
    (1024, 1024), 1.0 ms of that step better backward, 2.7 worse forward
    (blocks a kernel, as ``edges`` are, would buy the 1.0); (2048, 1024),
    four times the body (9.5 s to compile a pair for 2.6), 1.3 s more of
    that cell's warm set-up; 256 lanes, 0.44 ms of ``ling3-ft1``'s step for
    9.7 s of its cold one. Since PR 54 the latent cells' q.k is 192 wide
    beside v at 128 and still over 128, so on (512, 512): 16 head-rows
    alone 3.50 | 7.20 ms where (1024, 512) reads 3.25 | 6.77 and compiles
    a pair in 5.6 s for 1.8 (my chip run, PR 54): 3.4 ms of ``dsv2lite-ft1``'s
    step, left to a PR that weighs it against that cell's set-up. The
    general path keeps (512, 512) or (128, 128).

    A window shorter than the sequence: the LARGEST sub-tile of 1024,
    512, 256 or 128 that divides it, one a resident block, so that the
    band is as few pieces as can be. Mellum2's layer (S 8192, D 128,
    window 1024; ms a layer, forward | backward, PR 37): (1024, 1024)
    3.53 | 4.88, (1024, 512) 4.23 | 5.58, (512, 512) 4.48 | 6.07, the
    general kernels 5.25 | 7.30: a grid step costs about a 512 x 512
    tile's time whatever it holds, and short pieces fill the MXUs worse;
    (2048, 1024), 3.37 | 4.55, NOT taken: twice the body, a second more of
    every set-up. A window that none divides keeps the general tiles.

    Under ``block_mask`` (B, L): the LARGEST sub-tile of 1024, 512, 256
    or 128 that divides a copy and holds whole diffusion blocks, one a
    resident block (``_blocked`` then holds, nothing padded). SDAR's layer
    (L 4096, B 4, D 128; ms a layer, PR 47): (1024, 1024) 5.03 | 8.21,
    (1024, 512) 4.89 | 8.28, (512, 512) 5.62 | 9.10, the general walk
    10.02 | 15.07. A mask that none fits keeps the general tiles."""
    # head sizes rank the tiles alike (PRs 25, 52); 256 lanes compile longer
    unit = 8 if interpret else 128
    s_pad = _cdiv(seq, unit) * unit
    if nested and s_pad <= 2048:
        return s_pad, next(
            (sub for sub in (512, 256, 128) if s_pad % sub == 0), s_pad
        )
    if nested and head_dim <= 128 and _cdiv(s_pad, 512) % 2 == 0:  # pads alike
        return 1024, 512
    if window is not None and window < s_pad:
        for sub in (1024, 512, 256, 128):
            if window % sub == 0:
                return sub, sub
    if block_mask is not None:
        for sub in (1024, 512, 256, 128):
            if _blocked(block_mask, sub, sub, s_pad):
                return sub, sub
    return (512, 512) if s_pad >= 2048 else (128, 128)


def _auto_edges(block_k: int, head_dim: int) -> Tuple[int, int]:
    """The edge of the chunks that the diagonal sub-tile's staircase is
    cut into, (forward, backward), for a call that names none: from the
    sub-tile's edge and the head size, as ``_auto_tiles`` chooses the
    sub-tile; the sub-tile whole where the edge does not divide it.
    Measured on the v5e inside the whole training step (PERF.md section
    6, PR 35; ms a step in the kernel, forward | backward):

        edge    S 1024, D 64, (1024, 512)    S 4096, D 128, (512, 512)
        whole       16.24 | 24.81                2.84 | 4.86
        256         16.78 | 22.50                2.74 | 4.80
        128         17.63 | 21.31, 19.54         2.80 | 4.74, 4.70

    (the second backward figure with the staircase's dq taken a key strip
    at a time, as committed; the others a chunk at a time). The backward
    is bound by the MXU (93% of its issue slots whole, by the compiler's
    own schedule), so the area it leaves out is time it saves: 128, the
    finest the lanes allow, at either head size. The forward is not: its
    matmul and softmax phases alternate, a phase's pieces go to one MXU
    each, so its time is that of the LONGEST piece and the staircase's
    shorter ones save nothing, while every strip adds a reduction to
    wait for. At head size 64 it stays whole; at 128 an edge of 256 is
    the best of the three by a little. Other shapes run the nearest
    regime's choice, not measured.

    A call under the block-diffusion mask takes the same edges (SDAR's
    layer, L 4096, B 4, D 128, on (1024, 1024); ms a layer; my chip run,
    PR 47): the forward 5.03 at 256 and 4.92 at 128, whose eight strips a
    row group are half as much body again to trace and lower in every
    set-up for 0.4 ms of a step of 280; the backward 8.21 at 128 (on
    (512, 512): 9.10 at 128, 9.28 at 256)."""
    forward = 256 if head_dim >= 128 and block_k % 256 == 0 else block_k
    backward = 128 if block_k % 128 == 0 else block_k
    return forward, backward


def _scores_computed(
    s_pad: int, block_q: int, block_k: int, edge: int,
    window: Optional[int] = None,
) -> int:
    """Scores (query, key) pairs a head computes on the two-level causal
    schedule, forward or backward (one is the other transposed): the
    engagement figure of a schedule that is static. Per resident block:
    ``block_q`` squared for every block to its left, a wide tile of
    ``r * block_k`` keys for row group r, and the diagonal sub-tile's
    staircase, chunk j of ``edge`` keys meeting ``block_k - j * edge``
    rows: ``block_k * (block_k + edge) / 2``. The causal half is
    ``s_pad * (s_pad + 1) / 2``. At S 1024, (1024, 512): 786,432 with
    ``edge`` 512 (the whole sub-tile, until PR 35), 655,360 at 256,
    589,824 at 128, for a causal half of 524,800; at S 4096, (512, 512):
    9,437,184, 8,912,896, 8,650,752 for 8,390,656.

    With a ``window`` (``_banded``), per row group g of ``block_k`` rows:
    the diagonal's staircase, ``min(g, window / block_k - 1)`` whole
    sub-tiles, and from group ``window / block_k`` on the window's edge,
    a staircase of the same area as the diagonal's. At S 8192, window
    1024, (512, 512): 11,796,480 with the sub-tiles whole (the general
    kernels' 45 tiles of 512 x 512), 9,830,400 at ``edge`` 256, 8,847,360
    at 128, for a band of 7,864,832 (1.50, 1.25, 1.125 times). An edge
    of 1 leaves the band and, of every window-edge sub-tile, the
    ``block_k`` pairs ON the edge: masked, but in a block that is met."""
    stair = block_k * (block_k + edge) // 2
    if window is not None:
        groups, n_win = s_pad // block_k, window // block_k
        whole = sum(min(g, n_win - 1) for g in range(groups))
        return (2 * groups - n_win) * stair + whole * block_k * block_k
    blocks, n_sub = s_pad // block_q, block_q // block_k
    left = block_q * block_q * (blocks * (blocks - 1) // 2)
    wide = block_k * block_k * (n_sub * (n_sub - 1) // 2)
    return left + blocks * (wide + n_sub * stair)


def block_scores_computed(
    block_mask: Tuple[int, int], head_dim: int, *, backward: bool = False,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> int:
    """(query, key) pairs ONE head computes in a ``flash_attention`` call
    under ``block_mask`` (B, L), in its forward kernel or its
    ``backward``, on the schedule that call would run (``_tiles``' answer
    for it). ``blocked``: each copy's causal schedule of L rows
    (``_scores_computed``) and the own quadrant's L / edge chunks of
    ``edge`` x ``edge``. Else the tiles of the general kernels' sweep -
    every key tile up to B - 1 past a query tile's last row, every query
    tile from B - 1 before a key tile's first - times a tile's area. The
    mask shows L^2 + L B pairs; the rest is what whole pieces cost on the
    edges of the three visible regions, and on the general kernels the
    noised rows' hidden clean keys and most of their own quadrant. At
    L 4096, B 4, head size 128: 18,874,368 forward (edge 256) and
    17,825,792 backward (128) for 16,793,600 (1.124, 1.061); the sweep on
    (512, 512) would be 151 tiles, 39,583,744 (2.36)."""
    size, length = block_mask
    schedule = _tiles(
        2 * length, head_dim, _pick_interpret(interpret), block_q, block_k,
        None, causal=False, block_mask=block_mask,
    )
    block_q, block_k, s_pad = schedule.block_q, schedule.block_k, schedule.s_pad
    if schedule.kind == "blocked":
        edge = schedule.edges[1 if backward else 0]
        return 2 * _scores_computed(length, block_q, block_k, edge) + length * edge
    num_q, num_k = s_pad // block_q, s_pad // block_k
    if backward:  # ``_bwd_kernel``'s loop: key tile ki meets query tiles lo..
        tiles = sum(
            num_q - max(ki * block_k - (size - 1), 0) // block_q for ki in range(num_k)
        )
    else:  # ``_fwd_kernel``'s: query tile qi meets key tiles ..hi
        live = _cdiv(2 * length, block_k)
        tiles = sum(
            min(live, _cdiv((qi + 1) * block_q + size - 1, block_k)) for qi in range(num_q)
        )
    return tiles * block_q * block_k


def _tiles(
    seq: int, head_dim: int, interpret: bool,
    block_q: Optional[int], block_k: Optional[int],
    block_diag: Optional[int], causal: bool = True,
    window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None,
    value_dim: Optional[int] = None,
) -> Schedule:
    """The ``Schedule`` of a call, and the one place that decides it: the
    blocks it names, else ``_auto_tiles``, clamped to the sequence and
    rounded to what the hardware stores; the padded length; and from
    those shapes alone which schedule it runs (``_blocked``, ``_nested``,
    ``_banded``, else the general kernels). Arbitrary S is handled by
    zero-padding the sequence up to the block multiple: padded keys are
    masked in-kernel (on the causal schedule only padded queries can see
    them), padded queries carry zero cotangents, so numerics are exact.
    The edges are (forward, backward) where the call runs the two-level
    schedule (``_auto_edges``, or ``block_diag`` for both), else None.
    Under ``block_mask`` an edge holds whole diffusion blocks: one of
    ``_auto_edges``' that does not is the whole sub-tile, which does.
    ``head_dim`` is q's and k's last width; ``value_dim``, v's, is carried
    (``d_v``) where it is another, and chooses nothing: the tiles and the
    edges are those of ``head_dim``."""
    auto_q, auto_k = _auto_tiles(
        seq, head_dim, interpret, nested=causal and window is None,
        window=window if causal else None, block_mask=block_mask,
    )
    unit = 8 if interpret else 128
    s8 = _cdiv(seq, unit) * unit
    block_q = min(block_q or auto_q, s8)
    block_k = min(block_k or auto_k, s8)
    if not interpret:
        block_q = _cdiv(block_q, 128) * 128
        block_k = _cdiv(block_k, 128) * 128
    base = block_q * block_k // math.gcd(block_q, block_k)
    s_pad = _cdiv(seq, base) * base
    decided = functools.partial(
        Schedule, block_q=block_q, block_k=block_k, s_pad=s_pad, causal=causal,
        interpret=interpret, kv_len=seq, window=window, block=block_mask,
        d_v=None if value_dim in (None, head_dim) else value_dim,
    )
    if _nested(causal, window, block_q, block_k):
        kind = "nested"
    elif _banded(causal, window, block_q, block_k, s_pad):
        kind = "banded"
    elif _blocked(block_mask, block_q, block_k, s_pad):
        kind = "blocked"
    else:
        return decided(kind="general", edges=None)
    edges = _auto_edges(block_k, head_dim)
    if kind == "blocked":
        edges = tuple(e if e % block_mask[0] == 0 else block_k for e in edges)
    if block_diag:
        edge = min(block_diag, block_k)
        if not interpret:
            edge = _cdiv(edge, 128) * 128
        if block_k % edge:
            raise ValueError(
                f"block_diag {edge} does not divide block_k {block_k}"
            )
        if kind == "blocked" and edge % block_mask[0]:
            raise ValueError(
                f"block_diag {edge} does not hold whole blocks of {block_mask[0]}"
            )
        edges = (edge, edge)
    return decided(kind=kind, edges=edges)


def _qkv_lanes(n_heads: int, head_dim: int) -> Optional[int]:
    """Columns of the block one grid step of the fused-projection kernels
    takes, or None where the projection cannot be cut so. Mosaic's lane
    rule wants a block's last dimension a multiple of 128 (or the whole
    array's): a head of 128 columns or a multiple is a block by itself,
    two heads of 64 share one, and an odd count of those leaves half a
    block over."""
    if head_dim % 128 == 0:
        return head_dim
    if head_dim == 64 and n_heads % 2 == 0:
        return 128
    return None


def flash_attention_qkv(
    qkv: jax.Array,
    n_heads: int,
    *,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_diag: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal multi-head attention on a fused projection, in its layout.

    Args:
        qkv: (B, S, 3 * n_heads * head_dim): ``x @ wqkv``, the q columns
            first, then k, then v, each head's columns together.
        sm_scale, block_q, block_k, block_diag, interpret: as
            ``flash_attention``. The
            scale is applied inside the kernels, rounded as the fold
            outside would round it (bit-equal at head size 64).
    Returns:
        (B, S, n_heads * head_dim), ready for the out projection.

    The kernels address heads inside that layout (``_flash_fwd_qkv_call``),
    so no transpose, split, pad-to-128-lanes or concatenate is left
    between the two projections, forward or backward. What the layout
    cannot express goes through ``flash_attention`` on the split arrays,
    decided from shapes alone: a head size that is neither 64 nor a
    multiple of 128, an odd count of 64-wide heads, blocks that leave the
    sequence in more than one resident block (over 2048 positions) or do
    not nest."""
    B, S, width = qkv.shape
    head_dim = width // (3 * n_heads)
    if width != 3 * n_heads * head_dim:
        raise ValueError(
            f"a fused projection is three equal thirds, q, k and v of {n_heads} heads"
            f" of one width: {width} columns are not (a value width of its own goes"
            " to flash_attention_rows)"
        )
    if sm_scale is None:
        sm_scale = head_dim ** -0.5
    interp = _pick_interpret(interpret)
    schedule = _tiles(S, head_dim, interp, block_q, block_k, block_diag)
    if _qkv_lanes(n_heads, head_dim) is None or not schedule.one_resident_block:
        q, k, v = (
            t.reshape(B, S, n_heads, head_dim)
            for t in jnp.split(qkv, 3, axis=-1)
        )
        return flash_attention(
            q, k, v, sm_scale=sm_scale, block_q=schedule.block_q,
            block_k=schedule.block_k, block_diag=block_diag, interpret=interp,
        ).reshape(B, S, n_heads * head_dim)
    if schedule.s_pad != S:
        qkv = jnp.pad(qkv, ((0, 0), (0, schedule.s_pad - S), (0, 0)))
    out = _flash_qkv(
        (n_heads, float(sm_scale), schedule.block_k, schedule.edges, interp), qkv
    )
    return out[:, :S]


def _require_one_width(
    q: jax.Array, k: jax.Array, v: jax.Array, schedule: Optional[Schedule] = None
) -> None:
    """Refuses, before anything is traced, the widths no kernel is built
    for. k always has q's last width. v has it too, but for a call on the
    ``nested`` ``schedule`` (causal, no window, no block mask, blocks that
    nest), whose two kernels take v, the output, dO and dv at a width of
    their own (``Schedule.d_v``). Every other kernel's blocks are cut at
    q's width: a narrower value through them compiled, ran and answered NaN
    in dq and dk on the chip (PERF.md section 6, PR 50), so a caller
    without a schedule - ``flash_attention``'s (B, S, H, D) form and,
    one array, ``flash_attention_qkv`` - is held to one width for all."""
    d, d_k, d_v = q.shape[-1], k.shape[-1], v.shape[-1]
    if d_k != d:
        raise ValueError(f"q and k must share one last width: q has {d}, k {d_k}")
    if d_v != d and (schedule is None or schedule.kind != "nested"):
        raise ValueError(
            f"v's last width ({d_v}) may differ from q's ({d}) in flash_attention_rows"
            " on the nested causal schedule alone: this call "
            + ("takes one width" if schedule is None else f"is {schedule.kind}")
            + " (pad the narrower with zeros)"
        )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_diag: Optional[int] = None,
    interpret: Optional[bool] = None,
    mesh: Any = None,
    batch_axis: Optional[str] = "data",
    head_axis: Optional[str] = None,
    window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """Fused multi-head causal attention.

    Args:
        q, k, v: (B, S, H, head_dim), any float dtype.
        causal: apply the autoregressive mask.
        window: sliding-window (local) attention — each query attends
            only the most recent ``window`` keys (q_pos - k_pos < window);
            computed tiles scale with S*window instead of S^2/2.
            Requires ``causal``. Runs the two-level schedule cut to the
            band where the shapes allow (``_banded``; module docstring),
            else the general kernels, which skip the tiles wholly
            outside the window by their loop bounds.
        block_mask: ``(B, L)``: the block-diffusion mask (Arriola et al.,
            arXiv:2503.09573) over the 2 L positions of a call - a CLEAN
            copy of a sequence of L tokens in rows 0..L-1 and a NOISED
            copy in rows L..2L-1, both cut into blocks of B (``blk(i) = i
            // B``; L is whole blocks). A clean query at i sees the clean
            keys j with ``blk(j) <= blk(i)`` and nothing noised; a noised
            query at i sees the clean keys with ``blk(j) < blk(i)`` and
            the noised keys with ``blk(j) == blk(i)``: L^2 + L B pairs a
            head, later keys of a query's own block among them, so the
            call is not ``causal`` and says so. Runs the two-level
            schedule over both copies where the shapes tile
            (``_blocked``: the tiles ``_auto_tiles`` chooses where they
            can), else the general kernels. ``block_scores_computed``
            counts what either computes; on both a hidden pair adds
            exactly 0, forward and backward.
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
        block_diag: edge of the chunks the two-level schedule cuts its
            diagonal sub-tile (and a window's edge) into, for both
            kernels; a divisor of
            ``block_k``, rounded as the blocks are. Default:
            ``_auto_edges``, from ``block_k`` and the head size.
        block_q, block_k: VMEM tile sizes; clamped to S, and on real TPU
            rounded UP to 128-multiples (Mosaic's lane-aligned store
            requirement — a requested 64 runs as 128 on hardware;
            interpret mode honors small blocks exactly). On the
            two-level schedule (``block_q`` a multiple of ``block_k``;
            ``_tiles`` decides) ``block_q`` rows are resident, in row
            groups and sub-tiles of ``block_k``. Default:
            ``_auto_tiles``, measured on the v5e inside the whole
            training step.
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
    _require_one_width(q, k, v)
    if sm_scale is None:
        sm_scale = D ** -0.5
    if mesh is not None:
        spec = P(batch_axis, None, head_axis, None)
        local = functools.partial(
            flash_attention, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, block_diag=block_diag,
            interpret=interpret, window=window, block_mask=block_mask,
        )
        # check_vma=False: pallas out_shapes carry no varying-mesh-axes
        # annotation, which the new shard_map VMA typing would reject
        return shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    # (B, S, H, D) -> (B*H, S, D). Blocks always span the full head
    # dim, so Mosaic's "divisible by 128 OR equal to the array dim" lane
    # rule is satisfied without padding D (padding to 128 lanes would
    # double the QK FLOPs at the flagship head_dim of 64). This entry
    # KEEPS these transposes (and the one back): its callers' q, k, v
    # come out of other fusions - RoPE, an all-to-all, a shard - that
    # write the rows wherever they are told. A fused projection goes to
    # ``flash_attention_qkv``, which has none, and a caller whose own
    # fusion writes the rows to ``flash_attention_rows``.
    def to_rows(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)

    # sm_scale folded into q OUTSIDE the custom_vjp: one cheap (S, D)
    # multiply replaces a per-tile (S_q, S_k) multiply in every kernel,
    # and autodiff of this fold rescales dq automatically (exact for
    # power-of-two scales — head_dim 64 gives 0.125). The product is
    # computed with an f32 scalar so the scale itself is never quantized
    # to bf16; only the single product rounding remains.
    q_scaled = (q * jnp.float32(sm_scale)).astype(q.dtype)
    out = flash_attention_rows(
        to_rows(q_scaled), to_rows(k), to_rows(v), causal=causal,
        block_q=block_q, block_k=block_k, block_diag=block_diag,
        interpret=interpret, window=window, block_mask=block_mask,
    )
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def flash_attention_rows(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    block_diag: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
    block_mask: Optional[Tuple[int, int]] = None,
) -> jax.Array:
    """``flash_attention`` in the kernels' own layout, both ways: q, k, v
    are (B*H, S, head_dim) rows, one head of one sequence after another,
    the softmax scale ALREADY in q; the output and the three cotangents
    are rows too. For a caller whose own fusion writes the rows and reads
    their cotangents (``models/olmoe.py``: the q/k pass), so that no
    transpose stands between it and the kernels. ``window`` and
    ``block_mask`` as ``flash_attention`` checks them; the tiles are the
    same ``_tiles``, of q's width. k has q's last width. v may have ANOTHER
    (latent attention: q.k 192 beside v 128; ``models/olmoe.py``,
    ``mla_mixer``) where the call is causal with no window and its blocks
    nest: the output and dv are then as wide as v, and no lane of zeros is
    multiplied. On any other schedule that is refused
    (``_require_one_width``)."""
    _, S, D = q.shape
    if window is not None:
        if not causal:
            raise ValueError(
                "window requires causal=True (one-sided local attention)"
            )
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if block_mask is not None:
        size, length = block_mask = (int(block_mask[0]), int(block_mask[1]))
        if causal or window is not None:
            raise ValueError("block_mask is its own mask: causal=False and no window")
        if size < 1 or length % size or S != 2 * length:
            raise ValueError(
                f"block_mask {block_mask}: want 2 x L = {S} positions in whole blocks"
            )
    schedule = _tiles(
        S, D, _pick_interpret(interpret), block_q, block_k, block_diag, bool(causal),
        None if window is None else int(window), block_mask, v.shape[-1],
    )
    _require_one_width(q, k, v, schedule)
    if schedule.s_pad != S:
        q, k, v = (
            jnp.pad(x, ((0, 0), (0, schedule.s_pad - S), (0, 0))) for x in (q, k, v)
        )
    return _flash(schedule, q, k, v)[:, :S]
