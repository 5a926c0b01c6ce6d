"""The state-space recurrence of a Mamba-2 mixer, in chunks (state space
duality, SSD: Dao and Gu, arXiv:2405.21060). Plain ``jax.numpy``: the first
form of the scan is XLA's (ROADMAP R5), as ``ops/delta_rule.py``'s was; a
Pallas kernel is measured against it (``ssm_scan_roofline``). Nothing of a
model is here: no projection, no convolution, no norm.

Per head, with a state ``S`` (P x n), ``S_0 = 0``, and per position an input
``x_t`` (P), a step ``dt_t > 0`` and, shared by the heads of a GROUP (one
group of all the heads, or ``G`` groups of ``H / G`` consecutive heads each:
head ``h`` reads group ``h // (H / G)``), an input map ``B_t`` (n) and an
output map ``C_t`` (n); per head a rate ``A < 0`` and a skip ``D``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

The decay is ONE number a head and position (the delta rule's is a vector a
channel, and it has a rank-one correction besides: neither is here, so no
chunk solves a system).

*In chunks* of ``chunk`` positions. With ``a_t = dt_t A`` and ``G_t`` the
running sum of ``a`` from the chunk's first position to ``t``, both ends
counted, a chunk that starts from ``S`` gives::

    Y      = ((C B^T) * L) (dt X)  +  e^G (C S^T)  +  D X,   L_ts = e^{G_t - G_s}  (s <= t), else 0
    S_next = e^{G_last} S  +  sum_s e^{G_last - G_s} dt_s x_s B_s^T

The first term and every chunk's own state (the sum) are computed for all
chunks at once, as matrix products; ONE ``lax.scan`` over the chunks carries
``S`` under the chunk's whole decay ``e^{G_last}`` and stacks every chunk's
starting state; the second term follows for all chunks at once. No loop runs
over positions.

*What is computed in which type.* Every exponent here is a sum of ``a_r <=
0`` over a span inside one chunk, so every factor lies in (0, 1] and float32
carries it without the delta rule's sub-chunks. ``a``, its running sums (a
product with the chunk's causal mask at the ``highest`` precision: float32's
sums, which the TPU's windowed reduction gives ten times slower, PERF.md
section 6, PR 51), the factors and the carried state are float32 whatever
comes in. The FOUR LARGE PRODUCTS - ``C B^T``; the masked, decayed scores
with ``dt X``; a chunk's own state; ``C`` with the starting states - multiply
in the type ``x`` came in (``cfg.dtype``, bf16, in the step: the decayed
scores, ``dt x`` and the starting states are rounded to it once) and add in
float32, as the published Triton kernels of this recurrence do. In float32
inputs they are float32 products, and the op then agrees with the
position-by-position recurrence to 1e-5 of the largest output
(``tests/test_granite.py``); in bf16 to 1e-2, the rounding of its inputs. A
float32 product on the TPU at the default precision is a bf16 product that
reads twice the bytes, so nothing is gained by widening them, and the
comparison that decides the benchmark's ``correct`` holds the step at these
types (``benchmark/reference_granite.py``: its tolerances were read with them).
``G_t - G_s`` is a difference of two running sums: its absolute error is that
of the sums (1e-7 of |G|, which stays under a few thousand), which is the
relative error of ``L_ts`` - far under a bf16 rounding.

The backward pass is autodiff's through this form. A layer that holds this op
is recomputed in the backward pass where memory is short
(``OlmoeConfig.recompute_layers``; the stack's save policy, ``STACK_KEPT`` in
``models/olmoe.py``, names nothing of this op, so its forward runs again), so
what the forward keeps lives for one layer: the decays (B, S / chunk, H,
chunk, chunk) in float32 are the largest, 0.27 GB at 4,096 positions and 64
heads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_EXACT = jax.lax.Precision.HIGHEST


def _chunks(x: jax.Array, chunk: int) -> jax.Array:
    """(B, S, ...) -> (B, S / chunk, chunk, ...), zeros after the sequence."""
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % chunk)) + ((0, 0),) * (x.ndim - 2))
    return x.reshape(x.shape[0], -1, chunk, *x.shape[2:])


def ssd_scan(
    x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array, C: jax.Array,
    D: jax.Array, chunk: int = 256,
) -> jax.Array:
    """The recurrence of the module docstring from ``S_0 = 0``, in chunks.

    Args:
        x: (B, S, H, P), any float type: the type the large products run in.
        dt: (B, S, H), the positive steps (float32 is what a caller should
            bring; it is widened here).
        A: (H,) the negative rates; D: (H,) the skips.
        B, C: (B, S, n), one group: the same for every head; or (B, S, G, n),
            a map a group, ``G`` dividing ``H`` (``C B^T`` is then taken a
            group, and a head's products read its group's).
        chunk: positions a chunk.
    Any S: the sequence is padded to whole chunks with positions of step 0,
    which leave the state as it is.

    Returns:
        (B, S, H, P) in ``x``'s type."""
    S, dtype, f32 = x.shape[1], x.dtype, jnp.float32
    dt = dt.astype(f32)
    # the heads as the einsums below name them: ``h``, or under a group axis
    # ``gk`` - group ``g``'s ``k``-th head - beside the maps' own ``g``
    grouped = B.ndim == 4
    hd, gr = ("gk", "g") if grouped else ("h", "")

    def by_group(t: jax.Array) -> jax.Array:
        """(B, N, H, ...) -> (B, N, G, H / G, ...) under a group axis."""
        return t.reshape(t.shape[:2] + (B.shape[2], -1) + t.shape[3:]) if grouped else t

    # (B, N, H, chunk, ...): a head's chunk is one matrix of every product
    xs = by_group(jnp.moveaxis(_chunks(x, chunk), 3, 2))  # (B, N, H, chunk, P)
    dts = by_group(jnp.moveaxis(_chunks(dt, chunk), 3, 2))  # (B, N, H, chunk)
    a = dts * A.astype(f32).reshape(dts.shape[2:-1])[..., None]
    Bs, Cs = _chunks(B.astype(dtype), chunk), _chunks(C.astype(dtype), chunk)  # (B, N, chunk, [G,] n)
    position = jnp.arange(chunk)
    to_now = position[:, None] >= position[None, :]  # s <= t
    G = jnp.einsum(f"ts,bn{hd}s->bn{hd}t", to_now.astype(f32), a, precision=_EXACT)
    last = G[..., -1:]  # (B, N, H, 1)

    # inside a chunk: the scores a group's heads share, each head's decays on them
    scores = jnp.einsum(f"bnt{gr}c,bns{gr}c->bn{gr}ts", Cs, Bs, preferred_element_type=f32)
    decays = jnp.exp(jnp.where(to_now, G[..., :, None] - G[..., None, :], -jnp.inf))
    stepped = xs.astype(f32) * dts[..., None]  # dt x, (B, N, H, chunk, P)
    y = jnp.einsum(
        f"bn{hd}ts,bn{hd}sp->bn{hd}tp", (jnp.expand_dims(scores, -3) * decays).astype(dtype),
        stepped.astype(dtype), preferred_element_type=f32,
    )

    # a chunk's own state, then the serial part: S before every chunk
    own = jnp.einsum(
        f"bn{hd}sp,bns{gr}c->bn{hd}pc",
        (stepped * jnp.exp(last - G)[..., None]).astype(dtype), Bs,
        preferred_element_type=f32,
    )  # (B, N, H, P, n)

    def one_chunk(state: jax.Array, xs):
        own, carried = xs
        return carried[..., None] * state + own, state

    _, states = jax.lax.scan(
        one_chunk, jnp.zeros_like(own[:, 0]),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(jnp.exp(last), 1, 0)),
    )
    states = jnp.moveaxis(states, 0, 1)  # (B, N, H, P, n)
    y = y + jnp.exp(G)[..., None] * jnp.einsum(
        f"bnt{gr}c,bn{hd}pc->bn{hd}tp", Cs, states.astype(dtype), preferred_element_type=f32
    )
    y = y + D.astype(f32).reshape(dts.shape[2:-1])[..., None, None] * xs.astype(f32)
    y = y.reshape(y.shape[:2] + (-1,) + y.shape[-2:])  # the groups' heads side by side again
    y = jnp.moveaxis(y, 2, 3)  # (B, N, chunk, H, P)
    return y.reshape(y.shape[0], -1, *y.shape[3:])[:, :S].astype(dtype)
