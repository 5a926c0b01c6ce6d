"""The gated delta rule with a decay a CHANNEL, in chunks, and the short
causal convolution that feeds it: the mixer of a Kimi-Delta-Attention layer
(Kimi Linear, arXiv:2510.26692; the delta rule in chunks, Yang et al.,
arXiv:2406.06484). Plain ``jax.numpy``: the first form of the scan is XLA's
(ROADMAP R5, S14); a Pallas kernel is measured against it
(``kda_scan_roofline``).

Per head, with a state ``S`` (d_k x d_v), ``S_0 = 0``, and per position a
query ``q_t``, a key ``k_t``, a value ``v_t``, a log-decay ``g_t <= 0`` a
channel of the key (``a_t = exp(g_t)``) and a step ``beta_t``::

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

*In chunks.* Write ``u_t = beta_t (v_t - S_{t-1}^T Diag(a_t) k_t)``, so that
``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, and inside a chunk of C positions
that starts from ``S`` let ``G_t`` be the running sum of ``g`` (a vector of
d_k). Unrolled, ``S_t = Diag(e^{G_t}) S + sum_{s<=t} Diag(e^{G_t - G_s}) k_s
u_s^T``, and the ``u`` of a chunk solve ONE unit lower-triangular system::

    (I + A) U = beta (V - (K e^G) S),   A_ts = beta_t sum_c k_tc k_sc e^{G_tc - G_sc}  (s < t)
    O = (Q e^G) S + B U,                B_ts = sum_c q_tc k_sc e^{G_tc - G_sc}          (s <= t)
    S' = Diag(e^{G_C}) S + (K e^{G_C - G})^T U

``A``, ``B`` and the system's solution against ``beta V`` and ``beta K e^G``
(``U = U_0 - W S``) are computed for every chunk at once, as matrix products,
and so is what a chunk does to the state as a whole: with ``K' = K e^{G_C -
G}``, ``S' = (Diag(e^{G_C}) - K'^T W) S + K'^T U_0``. One ``lax.scan`` over the
chunks carries ``S`` through ONE product a trip (``_across_chunks``) and stacks
every chunk's starting state; ``U`` and the outputs follow for all chunks at
once. No loop runs over positions. ``G`` itself is the product of ``g`` with
the chunk's causal mask at the ``highest`` precision: float32's running sums,
which the TPU's windowed reduction gives ten times slower.

*The system is inverted, once.* ``T = (I + A)^-1`` (``_unit_lower_inverse``):
inside the diagonal blocks of ``_SUB`` a row at a time, ``T_r = e_r -
sum_{j<r} A_rj T_j``, in float32 multiply-adds (a loop of 15 trips for all
blocks at once); then two inverted blocks
``T_1, T_2`` joined by ``L`` below the diagonal make the inverse of the block
of twice the size, ``[[T_1, 0], [-T_2 L T_1, T_2]]``, for all pairs at once
as ``T - T L T`` - the public ``fla`` kernels' structure (``solve_tril``). Its
products run at the ``highest`` precision whatever the default: a rounding of
``T`` is one of every solution made with it, and they are 2 GFLOP a layer of
``ling3-ft1``. Then ``U_0 | W = T (beta V | beta K e^G)``, and no
triangular-solve call is left in the op.

*The backward pass is written here* (``gated_delta_rule`` is a
``jax.custom_vjp``). The forward runs once and KEEPS, beside its five
arguments, for every chunk: the starting state ``S`` (d_k x d_v), ``U``, the
solution ``U_0 | W``, ``T`` and ``B`` - what the serial loop and the system
made, 0.20 GB a layer at 8,192 positions, 8 heads of 128 (the states 67 MB,
the solution 67, ``U`` 34, ``T`` and ``B`` 17 each). ``G``, the masks and the
decay factors of rows and columns are cheap, chunk-parallel functions of the
arguments and are built again. With ``dO`` the output's cotangent and ``dS'``
that of a chunk's closing state (0 after the last), a REVERSE ``lax.scan``
over the chunks carries, again through one product a trip::

    dS = (Diag(e^{G_C}) - W^T K') dS' + (Q e^G)^T dO - W^T B^T dO

and stacks ``dS'``; no state is computed again. The rest is for all chunks at
once: ``dU = B^T dO + K' dS'``. Through the system, with ``X = U_0 | W`` and
``dX = dU | -dU S^T``: ``d rhs = T^T dX`` and ``dA = -tril(d rhs X^T, -1)``,
whence ``dV = beta d rhs_V`` and the parts of ``dK``, ``dbeta`` and ``dG`` that
``beta K e^G`` carries. Through the output and the closing state: ``dB = tril(dO
U^T)``, ``d(Q e^G) = dO S^T``, ``d(K e^{G_C - G}) = U dS'^T``, and the
carried factor's ``dG_C = e^{G_C} sum_v dS' S``. Through the pairs
(``_pairs_backward``), from each sub-chunk's reference point as the forward:
``d rows_tc = sum_s dP_ts k_sc e^{G_tc - G_sc}`` for the two row sets ``beta
K`` and ``Q`` with ``dP = dA, dB``, ``d cols_sc = sum_t dP_ts rows_tc
e^{G_tc - G_sc}``, and the decays' own part needs no further product: ``dG +=
sum rows d rows - K d cols``. Last ``dg`` is the running sum of ``dG`` from
the chunk's end back to the position. Each cotangent returns in its
argument's type.

*The decay a channel is what makes this hard.* ``e^{G_t - G_s}`` is no
product of a factor of ``t`` and a factor of ``s`` over a whole chunk: at the
decay's lower bound of -5 a position, ``e^{-G}`` passes float32's largest
number after 17 positions. So a chunk is cut into SUB-CHUNKS of ``_SUB``
positions, each with a reference point ``R`` in its middle (``G`` after half
of it): a row ``t`` of sub-chunk ``i`` carries ``e^{G_t - R_i}`` and a column
``s`` carries ``e^{R_i - G_s}``, each within ``e^{+-_SUB/2 x 5}`` where both
lie in the sub-chunk, the column's under 1 where ``s`` lies before it, and
held to ``e^{_CAP}`` where it lies after - pairs the causal mask removes
anyway. ``G`` stays float32 throughout. A sub-chunk of 16 under a bound of
-5 keeps every exponent inside +-40; the lowest log-decay a position the
sub-chunk can carry at all is ``LEAST_LOG_DECAY``, and the op cannot see
what produced its ``g``: whoever bounds the decay holds the bound to it
(``models/olmoe.py``: ``Kda``).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

# positions a chunk: one triangular system of this size a chunk and head
# (its cost grows with the square), and the state crosses ``S / _CHUNK``
# serial steps of the scan. Taken from the start; no other was timed
_CHUNK = 64
# positions a sub-chunk (it divides ``_CHUNK``): what one reference point
# serves. Half of it times the decay's bound is the largest exponent a
# causal pair's factor reaches (module docstring): 40 at a bound of -5
_SUB = 16
# the largest exponent a masked (non-causal) pair's column factor may keep
_CAP = 80.0
# the lowest log-decay a position and channel that ``_SUB`` carries. A
# causal pair's two factors reach ``e^{+-(_SUB / 2) x bound}``, and the small
# one multiplies a component of q or k: past ``e^-64`` a component of 1e-9
# leaves float32's normal numbers and the pair loses its digits silently
# (read on the CPU: exact to 1e-8 at -9 a position, 2% off at -10)
LEAST_LOG_DECAY = -64.0 / (_SUB // 2)
# the chunk systems' inverse, the solutions made with it and the decays'
# running sums take float32's products whatever the caller's default: the
# parent's triangular solve and running sum were float32 inside, and these are
# 6 GFLOP a layer of ``ling3-ft1``
_EXACT = jax.lax.Precision.HIGHEST


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """A depthwise causal convolution over positions, a channel at a time
    and with no bias: ``x`` (B, S, C), ``w`` (taps, C), ``y_t = sum_j w_j
    x_{t - (taps - 1) + j}`` with ``x`` zero before the sequence (the last
    tap meets the position itself). Float32 sums, returned in float32."""
    taps, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(padded[:, j:j + S] * w[j] for j in range(taps))


def _chunks(x: jax.Array) -> jax.Array:
    """(B, S, H, ...) -> (N, B, H, _CHUNK, ...) in float32, the sequence
    padded with zeros to N whole chunks."""
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, -x.shape[1] % _CHUNK)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape(x.shape[0], -1, _CHUNK, *x.shape[2:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _sides(
    rows: Sequence[jax.Array], k: jax.Array, G: jax.Array
) -> Tuple[List[jax.Array], jax.Array, jax.Array, jax.Array]:
    """The two sides of a chunk's pairs from each sub-chunk's reference
    point (module docstring): every row set with a row's ``e^{G_t - R_i}``
    (..., C / _SUB, _SUB, d), and ``k`` with, for every sub-chunk of rows,
    each column's ``e^{R_i - G_s}`` held to ``e^{_CAP}`` (..., C / _SUB, C,
    d); then the two factors themselves."""
    C, d = G.shape[-2:]
    Gs = G.reshape(G.shape[:-2] + (C // _SUB, _SUB, d))
    ref = Gs[..., _SUB // 2 - 1, :][..., None, :]  # (..., C / _SUB, 1, d)
    row_factor = jnp.exp(Gs - ref)
    column_factor = jnp.exp(jnp.minimum(ref - G[..., None, :, :], _CAP))
    lefts = [r.reshape(Gs.shape) * row_factor for r in rows]
    return lefts, k[..., None, :, :] * column_factor, row_factor, column_factor


def _pairs(rows: Sequence[jax.Array], k: jax.Array, G: jax.Array) -> List[jax.Array]:
    """``sum_c rows_tc k_sc e^{G_tc - G_sc}`` for every pair (t, s) of a
    chunk and each of the row sets in ``rows`` (..., C, d), (..., C, C):
    sub-chunk by sub-chunk of rows, each against all C columns from its own
    reference point (module docstring); the columns with their factors serve
    every row set. Pairs with ``s`` after ``t``'s sub-chunk come out wrong
    and finite or infinite; the caller masks."""
    lefts, right, _, _ = _sides(rows, k, G)
    return [
        jnp.einsum("...itc,...isc->...its", left, right).reshape(G.shape[:-1] + G.shape[-2:-1])
        for left in lefts
    ]


def _pairs_backward(
    d_pairs: Sequence[jax.Array], rows: Sequence[jax.Array], k: jax.Array, G: jax.Array
) -> Tuple[List[jax.Array], jax.Array]:
    """The cotangents of ``_pairs``'s ``rows`` and, as far as the column
    side carries it, ``k``, from the MASKED cotangents of its results (a
    pair the caller masked out brings 0 here, whatever its factors were):
    ``d_rows_tc = sum_s d_ts k_sc e^{G_tc - G_sc}`` a row set and ``d_cols_sc
    = sum_t d_ts rows_tc e^{G_tc - G_sc}`` summed over the row sets. The
    decays' own cotangent follows from the two without another product:
    ``sum rows d_rows - k d_cols``."""
    lefts, right, row_factor, column_factor = _sides(rows, k, G)
    d_pairs = [d.reshape(row_factor.shape[:-1] + d.shape[-1:]) for d in d_pairs]
    d_rows = [
        (jnp.einsum("...its,...isc->...itc", d, right) * row_factor).reshape(G.shape)
        for d in d_pairs
    ]
    d_right = sum(jnp.einsum("...its,...itc->...isc", d, left) for d, left in zip(d_pairs, lefts))
    return d_rows, jnp.sum(d_right * column_factor, axis=-3)


def _unit_lower_inverse(A: jax.Array) -> jax.Array:
    """``(I + A)^-1`` of a strictly lower ``A`` (..., C, C) by forward
    substitution over blocks of ``_SUB`` (module docstring). The diagonal
    blocks go a row at a time, ``X_r = e_r - sum_{j<r} A_rj X_j``, as
    float32 multiply-adds and no matrix product; the blocks below them by
    ``T <- T - T L T`` with ``L`` the part of ``A`` that joins two inverted
    blocks into one of twice the size, at the ``highest`` precision
    whatever the caller's default."""
    C = A.shape[-1]
    n = C // _SUB
    blocks = A.reshape(A.shape[:-2] + (n, _SUB, n, _SUB))
    D = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)
    unit = jnp.eye(_SUB, dtype=A.dtype)

    def one_row(r: jax.Array, X: jax.Array) -> jax.Array:
        # rows r and after are still the unit matrix's, and A_rj is 0 for them
        below = jax.lax.dynamic_index_in_dim(D, r, axis=D.ndim - 2, keepdims=False)
        row = unit[r] - jnp.sum(below[..., :, None] * X, axis=-2)
        return jax.lax.dynamic_update_index_in_dim(X, row, r, axis=X.ndim - 2)

    # a loop and not 15 unrolled rows: a fifth of the op's compile time
    X = jax.lax.fori_loop(1, _SUB, one_row, jnp.broadcast_to(unit, D.shape))
    T = (X[..., :, :, None, :] * jnp.eye(n, dtype=A.dtype)[:, None, :, None]).reshape(A.shape)
    block = jnp.arange(C) // _SUB
    size = 1
    while size < n:
        joins = (block[:, None] // (2 * size) == block[None, :] // (2 * size)) & (
            block[:, None] // size > block[None, :] // size
        )
        L = jnp.where(joins, A, 0.0)
        T = T - jnp.matmul(jnp.matmul(T, L, precision=_EXACT), T, precision=_EXACT)
        size *= 2
    return T


def _t(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


def _in_chunks(q, k, v, g, beta):
    """The five arguments in chunks (``_chunks``: positions that leave the
    state as it is pad the last), with what every chunk's algebra starts from:
    the two causal masks and the decays' running sums ``G`` - as a product
    with the mask at ``highest``, float32's sums; the TPU's windowed
    reduction took 1.0 ms a layer of ``ling3-ft1`` for the same numbers."""
    q, k, v, g, beta = (_chunks(x) for x in (q, k, v, g, beta))
    position = jnp.arange(_CHUNK)
    before = position[:, None] > position[None, :]  # s < t
    to_now = before | jnp.eye(_CHUNK, dtype=bool)
    G = jnp.einsum("ts,...sc->...tc", to_now.astype(jnp.float32), g, precision=_EXACT)
    return q, k, v, beta[..., None], G, before, to_now


def _from_chunks(x: jax.Array, S: int, dtype) -> jax.Array:
    """(N, B, H, C, ...) -> (B, S, H, ...), the padding dropped."""
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 1), 2, 3)  # (B, N, C, H, ...)
    return x.reshape(x.shape[0], -1, *x.shape[3:])[:, :S].astype(dtype)


def _across_chunks(mixed: jax.Array, fresh: jax.Array, last: jax.Array, reverse: bool) -> jax.Array:
    """The serial part, either way: ``X' = Diag(e^{G_C}) X - mixed X +
    fresh`` from ``X = 0`` chunk after chunk (from the last back under
    ``reverse``), one product a trip; every chunk's ``X`` BEFORE its
    update, stacked."""

    def one_chunk(carry: jax.Array, xs: Tuple[jax.Array, ...]) -> Tuple[jax.Array, jax.Array]:
        mixed, fresh, carried = xs
        return carried * carry - mixed @ carry + fresh, carry

    return jax.lax.scan(
        one_chunk, jnp.zeros_like(fresh[0]), (mixed, fresh, _t(jnp.exp(last))), reverse=reverse
    )[1]


@jax.custom_vjp
def gated_delta_rule(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array
) -> jax.Array:
    """The recurrence of the module docstring from ``S_0 = 0``, in chunks.

    Args:
        q, k: (B, S, H, d_k); v: (B, S, H, d_v); any float type.
        g: (B, S, H, d_k) float32 log-decays, each within
           ``[LEAST_LOG_DECAY, 0]``.
        beta: (B, S, H).
    Any S: the sequence is padded to whole chunks with positions that leave
    the state as it is.

    Returns:
        (B, S, H, d_v) in ``v``'s type. Everything between is float32.

    The backward pass is this module's own (``_backward``, module
    docstring): the forward runs once, keeps every chunk's inverse, solution
    and starting state, and each gradient comes back in its argument's
    type."""
    return _forward(q, k, v, g, beta)[0]


def _forward(q, k, v, g, beta):
    arguments, S, dtype = (q, k, v, g, beta), q.shape[1], v.dtype
    q, k, v, beta, G, before, to_now = _in_chunks(q, k, v, g, beta)
    step_pairs, query_pairs = _pairs([beta * k, q], k, G)
    T = _unit_lower_inverse(jnp.where(before, step_pairs, 0.0))
    to_here = jnp.where(to_now, query_pairs, 0.0)
    decayed, last = jnp.exp(G), G[..., -1:, :]  # last: (N, B, H, 1, d_k)
    solved = jnp.matmul(
        T, jnp.concatenate([beta * v, beta * k * decayed], axis=-1), precision=_EXACT
    )
    u_0, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    k_out = k * jnp.exp(last - G)
    states = _across_chunks(_t(k_out) @ w, _t(k_out) @ u_0, last, reverse=False)
    u = u_0 - w @ states
    out = (q * decayed) @ states + to_here @ u
    return _from_chunks(out, S, dtype), (arguments, states, u, solved, T, to_here)


def _backward(kept, d_out):
    (q, k, v, g, beta), states, u, solved, T, to_here = kept
    S, types, width = q.shape[1], [x.dtype for x in (q, k, v, g, beta)], v.shape[-1]
    q, k, v, beta, G, before, to_now = _in_chunks(q, k, v, g, beta)
    d_out = _chunks(d_out)
    decayed, last = jnp.exp(G), G[..., -1:, :]
    from_here = jnp.exp(last - G)
    q_in, k_out, w = q * decayed, k * from_here, solved[..., width:]
    # the reverse loop, then every chunk's d_u from its closing state's cotangent
    d_u = _t(to_here) @ d_out
    d_states = _across_chunks(_t(w) @ k_out, _t(q_in) @ d_out - _t(w) @ d_u, last, reverse=True)
    d_u = d_u + k_out @ d_states
    # through the solution: (I + A) solved = rhs
    d_rhs = jnp.matmul(
        _t(T), jnp.concatenate([d_u, -d_u @ _t(states)], axis=-1), precision=_EXACT
    )
    d_rhs_v, d_rhs_w = d_rhs[..., :width], d_rhs[..., width:]
    d_step_pairs = jnp.where(before, -d_rhs @ _t(solved), 0.0)
    # through the output and the closing state
    d_query_pairs = jnp.where(to_now, d_out @ _t(u), 0.0)
    d_q_in, d_k_out = d_out @ _t(states), u @ _t(d_states)
    d_last = jnp.sum(d_k_out * k_out, axis=-2, keepdims=True) + jnp.exp(last) * _t(
        jnp.sum(d_states * states, axis=-1, keepdims=True)
    )
    # through the pairs, from each sub-chunk's reference point as ``_pairs``
    rows = [beta * k, q]
    (d_step_rows, d_query_rows), d_cols = _pairs_backward(
        [d_step_pairs, d_query_pairs], rows, k, G
    )
    d_step_k = decayed * d_rhs_w + d_step_rows  # of ``beta k``, both of its uses
    d_G = (
        rows[0] * d_step_k + rows[1] * d_query_rows - k * d_cols
        + d_q_in * q_in - d_k_out * k_out
    )
    d_G = d_G.at[..., -1:, :].add(d_last)
    d_q = d_q_in * decayed + d_query_rows
    d_k = beta * d_step_k + d_k_out * from_here + d_cols
    d_beta = jnp.sum(d_rhs_v * v, axis=-1) + jnp.sum(k * d_step_k, axis=-1)
    d_g = jax.lax.cumsum(d_G, axis=d_G.ndim - 2, reverse=True)
    return tuple(
        _from_chunks(x, S, dtype)
        for x, dtype in zip((d_q, d_k, beta * d_rhs_v, d_g, d_beta), types)
    )


gated_delta_rule.defvjp(_forward, _backward)
