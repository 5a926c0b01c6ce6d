"""The gated delta rule with a decay a CHANNEL, in chunks, and the short
causal convolution that feeds it: the mixer of a Kimi-Delta-Attention layer
(Kimi Linear, arXiv:2510.26692; the delta rule in chunks, Yang et al.,
arXiv:2406.06484). Plain ``jax.numpy``: the first form of the scan is XLA's
(ROADMAP R5); a Pallas kernel is measured against it
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
(``U = U_0 - W S``) are computed for every chunk at once, as matrix products;
one ``lax.scan`` over the chunks carries ``S`` through three products a
chunk. No loop runs over positions.

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

from typing import Tuple

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


def causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """A depthwise causal convolution over positions, a channel at a time
    and with no bias: ``x`` (B, S, C), ``w`` (taps, C), ``y_t = sum_j w_j
    x_{t - (taps - 1) + j}`` with ``x`` zero before the sequence (the last
    tap meets the position itself). Float32 sums, returned in float32."""
    taps, S = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return sum(padded[:, j:j + S] * w[j] for j in range(taps))


def _chunks(x: jax.Array, chunk: int) -> jax.Array:
    """(B, S, H, ...) -> (S / chunk, B, H, chunk, ...)."""
    B, S, H = x.shape[:3]
    x = x.reshape(B, S // chunk, chunk, H, *x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)


def _pairs(rows: jax.Array, k: jax.Array, G: jax.Array) -> jax.Array:
    """``sum_c rows_rtc k_sc e^{G_tc - G_sc}`` for every pair (t, s) of a
    chunk and each of the R row sets stacked first in ``rows`` (R, ..., C,
    d), (R, ..., C, C): sub-chunk by sub-chunk of rows, each against all C
    columns from its own reference point (module docstring); the column
    factors are computed once for all R. Pairs with ``s`` after ``t``'s
    sub-chunk come out wrong and finite or infinite; the caller masks."""
    C, d = G.shape[-2:]
    split = G.shape[:-2] + (C // _SUB, _SUB, d)
    Gs = G.reshape(split)
    ref = Gs[..., _SUB // 2 - 1, :][..., None, :]  # (..., C / _SUB, 1, d)
    left = rows.reshape(rows.shape[:1] + split) * jnp.exp(Gs - ref)
    right = k[..., None, :, :] * jnp.exp(jnp.minimum(ref - G[..., None, :, :], _CAP))
    out = jnp.einsum("r...itc,...isc->r...its", left, right)
    return out.reshape(rows.shape[:-1] + (C,))


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

    The backward pass is autodiff's through the chunked form, which is
    computed again from these five arguments when it comes to it
    (``jax.checkpoint``): kept, a layer's chunk matrices and the column
    factors of every sub-chunk are some hundreds of MB at 8,192 positions."""
    return jax.checkpoint(_chunked)(q, k, v, g, beta)


def _chunked(q, k, v, g, beta):
    S, dtype, chunk = q.shape[1], v.dtype, _CHUNK
    pad = -S % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    q, k, v, g, beta = (_chunks(x.astype(jnp.float32), chunk) for x in (q, k, v, g, beta))
    beta = beta[..., None]  # (N, B, H, C, 1)
    G = jnp.cumsum(g, axis=-2)
    position = jnp.arange(chunk)
    before = position[:, None] > position[None, :]  # s < t
    step_pairs, query_pairs = _pairs(jnp.stack([beta * k, q]), k, G)
    A = jnp.where(before, step_pairs, 0.0)
    to_here = jnp.where(before | jnp.eye(chunk, dtype=bool), query_pairs, 0.0)
    decayed = jnp.exp(G)
    solved = jax.lax.linalg.triangular_solve(
        A + jnp.eye(chunk, dtype=A.dtype),
        jnp.concatenate([beta * v, beta * k * decayed], axis=-1),
        left_side=True, lower=True, unit_diagonal=True,
    )
    u_0, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    last = G[..., -1:, :]  # (N, B, H, 1, d_k)

    def one_chunk(state: jax.Array, xs: Tuple[jax.Array, ...]) -> Tuple[jax.Array, jax.Array]:
        u_0, w, q_in, pairs, k_out, carried = xs
        u = u_0 - w @ state
        out = q_in @ state + pairs @ u
        return carried * state + jnp.swapaxes(k_out, -1, -2) @ u, out

    state = jnp.zeros(q.shape[1:3] + (k.shape[-1], v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(one_chunk, state, (
        u_0, w, q * decayed, to_here, k * jnp.exp(last - G),
        jnp.swapaxes(jnp.exp(last), -1, -2),
    ))
    out = jnp.moveaxis(jnp.moveaxis(out, 0, 1), 2, 3)  # (B, N, C, H, d_v)
    return out.reshape(out.shape[0], -1, *out.shape[3:])[:, :S].astype(dtype)
