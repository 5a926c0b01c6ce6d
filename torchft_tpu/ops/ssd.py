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

*The backward pass is written here* (``ssd_scan`` is a ``jax.custom_vjp``, as
``ops/delta_rule.py``'s op). The forward runs ONCE a step and KEEPS its six
arguments and, under a ``checkpoint_name`` each, what the serial loop made -
every chunk's starting state, ``ssd_states`` (B, S / chunk, H, P, n) in
``x``'s type, as the forward rounds them before their product with ``C`` -
and its output, ``ssd_y`` (B, S, H, P). A layer that holds this op is
recomputed in the backward pass where memory is short
(``OlmoeConfig.recompute_layers``); the stack's save policy names both
(``STACK_KEPT`` in ``models/olmoe.py``), so the recomputed layer makes the
arguments again (the map and the convolution, which it runs anyway), reads the
kept output into the gated norm and never runs this forward a second time.
The states kept cost 17 MB a layer of ``granite4h-ft1`` and 67 MB of
``nemotron3n-ft1``; rebuilt in the backward from ``own`` and the loop they
cost 0.11 and 0.69 ms a layer more (PERF.md section 6, PR 61), so they are
kept. ``G``, the scores ``C B^T``, the decays ``L`` and the decayed scores
``M = (C B^T) * L`` are cheap, chunk-parallel functions of the arguments and
are BUILT AGAIN; no ``y`` product and no ``own`` is.

With ``dY`` the output's cotangent, ``X' = dt X``, and ``dS'`` the cotangent
of a chunk's closing state (0 after the last), per chunk::

    dX'  = M^T dY  +  e^{G_last - G} (B dS'^T)                dM = dY X'^T
    d(C B^T) = sum over a group's heads of  dM * L            (then dC, dB as products with B, C)
    dC  += sum_h (e^G dY) S^T ,    dB += sum_h (e^{G_last - G} X')^T dS'^T
    dS   = e^{G_last} dS'  +  (e^G dY)^T C                    (ONE reverse ``lax.scan``)

and ``dx = dt dX' + D dY``, ``d dt = <dX', x> + A da``, ``dA = sum dt da``,
``dD = sum <dY, x>`` a head. *The running sums' cotangent* ``dG`` needs no
(chunk x chunk) array of its own: every exponent is ``G_t - G_s``, so with
``W = dM * M`` below the diagonal (on it the exponent is 0 whatever ``G``
is)::

    dG_t  =  sum_s W_ts  -  sum_s W_st                        (the decays: rows less columns)
          +  e^{G_t} <dY_t, C_t S^T>                          (the carried term)
          -  e^{G_last - G_t} <(B dS'^T)_t, X'_t>             (a chunk's own state)
    dG_last += sum_s e^{G_last - G_s} <(B dS'^T)_s, X'_s>  +  e^{G_last} <dS', S>

and ``da`` is ``dG``'s running sum from the chunk's end back to the position
(a product with the mask at ``_EXACT``, as ``G`` is). ``W``'s row and column
sums are taken of the SAME float32 numbers, so the pairs that both lie after
a position cancel to float32's rounding in ``da`` as they do under autodiff;
the cheaper identities of the public Mamba-2 kernels - rows as ``<dY_t,
y_t>`` from the kept output, columns as ``<X'_s, dX'_s>`` - cancel only to
the rounding of a bf16 ``y`` and lost 5% of ``d dt`` under fast decays
(``tests/test_granite.py``), so they are not used. What autodiff paid for is
what XLA now fuses away: ``dM``, its product with the decays summed over a
group's heads and ``W``'s two sums are ONE fusion around the ``dY X'^T``
product, and ``M`` is built inside the ``M^T dY`` product's, so no (chunk x
chunk) array a head goes through HBM in the backward at all (read in the
compiled program for a v5e, and on the chip: PERF.md section 5).

*Types in the backward*: as the forward's. ``G`` and every factor float32 at
``_EXACT`` - the precision the FORWARD was traced at, handed to the backward
as a static argument, so a caller that plants another (``benchmark/
controls_granite.py``) plants it in both passes; the five large products
(``M^T dY``, ``dY X'^T``, and the three with the states or their cotangents)
multiply in ``x``'s type and add in float32; ``dM`` and ``d(C B^T)`` are
rounded to ``x``'s type once, where autodiff rounded them (the cotangent of a
value that was cast); the loop carries ``dS`` in float32. Each cotangent
returns in its argument's type.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_EXACT = jax.lax.Precision.HIGHEST


def _chunks(x: jax.Array, chunk: int) -> jax.Array:
    """(B, S, ...) -> (B, S / chunk, chunk, ...), zeros after the sequence."""
    x = jnp.pad(x, ((0, 0), (0, -x.shape[1] % chunk)) + ((0, 0),) * (x.ndim - 2))
    return x.reshape(x.shape[0], -1, chunk, *x.shape[2:])


def _from_chunks(x: jax.Array, like: jax.Array) -> jax.Array:
    """(B, N, chunk, ...) -> ``like``'s (B, S, ...) and type, the padding dropped."""
    x = x.reshape(x.shape[0], -1, *x.shape[3:])[:, :like.shape[1]]
    return x.reshape(like.shape).astype(like.dtype)


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
        (B, S, H, P) in ``x``'s type.

    The backward pass is this module's own (``_backward``, module
    docstring): each cotangent comes back in its argument's type.
    ``_EXACT`` is read HERE, when the forward is traced, for both passes."""
    return _scan(chunk, _EXACT, x, dt, A, B, C, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan(chunk: int, exact: Any, x, dt, A, B, C, D) -> jax.Array:
    return _forward(chunk, exact, x, dt, A, B, C, D)[0]


class _Chunked:
    """The arguments in chunks with what both passes build from them: a
    head's chunk is one matrix of every product, (B, N, H, chunk, ...), and
    under a group axis (B, N, G, H / G, chunk, ...) beside the maps' (B, N,
    chunk, G, n). ``hd`` and ``gr`` are the heads and the maps' group as the
    einsums name them: ``h`` and nothing, or ``gk`` - group ``g``'s ``k``-th
    head - and ``g``."""

    def __init__(self, chunk: int, exact: Any, x, dt, A, B, C, D):
        f32, self.dtype = jnp.float32, x.dtype
        self.groups = B.shape[2] if B.ndim == 4 else None
        self.hd, self.gr = ("gk", "g") if self.groups else ("h", "")
        self.x = self.heads_first(x, chunk)  # (B, N, H, chunk, P)
        self.dt = self.heads_first(dt.astype(f32), chunk)  # (B, N, H, chunk)
        self.A, self.D = (v.astype(f32).reshape(self.dt.shape[2:-1])[..., None] for v in (A, D))
        self.B, self.C = _chunks(B.astype(x.dtype), chunk), _chunks(C.astype(x.dtype), chunk)
        position = jnp.arange(chunk)
        self.to_now = position[:, None] >= position[None, :]  # s <= t
        # the decays' running sums, both ends counted, and a chunk's whole
        self.G = self.einsum(
            "ts,bnHs->bnHt", self.to_now.astype(f32), self.dt * self.A, precision=exact
        )
        self.last = self.G[..., -1:]  # (B, N, H, 1)
        self.stepped = self.x.astype(f32) * self.dt[..., None]  # dt x

    def heads_first(self, t: jax.Array, chunk: int) -> jax.Array:
        """(B, S, H, ...) -> (B, N, H, chunk, ...), the heads by group."""
        t = jnp.moveaxis(_chunks(t, chunk), 3, 2)
        return t.reshape(t.shape[:2] + (self.groups, -1) + t.shape[3:]) if self.groups else t

    def positions_first(self, t: jax.Array, like: jax.Array) -> jax.Array:
        """(B, N, H, chunk, ...) -> ``like``'s (B, S, H, ...) and type."""
        # the groups' heads side by side again
        t = t.reshape(t.shape[:2] + (-1,) + t.shape[-(like.ndim - 2):])
        return _from_chunks(jnp.moveaxis(t, 2, 3), like)

    def einsum(self, spec: str, *operands: jax.Array, **how: Any) -> jax.Array:
        """``jnp.einsum`` with ``H`` for the heads and ``G`` for the maps'
        group in ``spec``; float32 sums unless ``how`` says otherwise."""
        how.setdefault("preferred_element_type", jnp.float32)
        return jnp.einsum(spec.replace("H", self.hd).replace("G", self.gr), *operands, **how)

    def decayed_scores(self) -> Tuple[jax.Array, jax.Array]:
        """Inside a chunk: the scores ``C B^T`` a group's heads share, (B, N,
        [G,] 1, chunk, chunk), and each head's decays ``L`` to lay on them."""
        scores = self.einsum("bntGc,bnsGc->bnGts", self.C, self.B)
        G = self.G
        decays = jnp.exp(jnp.where(self.to_now, G[..., :, None] - G[..., None, :], -jnp.inf))
        return jnp.expand_dims(scores, -3), decays

    def across(self, fresh: jax.Array, reverse: bool, dtype: Any) -> jax.Array:
        """The serial part, either way: ``X' = e^{G_last} X + fresh`` from ``X
        = 0`` chunk after chunk (from the last back under ``reverse``), in
        float32; every chunk's ``X`` BEFORE its update, stacked as ``fresh``
        is and in ``dtype`` (rounded as it is stacked: no float32 copy of the
        stack is written and read again)."""

        def one_chunk(carry: jax.Array, xs):
            fresh, carried = xs
            return carried[..., None] * carry + fresh, carry.astype(dtype)

        return jnp.moveaxis(jax.lax.scan(
            one_chunk, jnp.zeros_like(fresh[:, 0]),
            (jnp.moveaxis(fresh, 1, 0), jnp.moveaxis(jnp.exp(self.last), 1, 0)), reverse=reverse,
        )[1], 0, 1)


def _forward(chunk: int, exact: Any, *arguments: jax.Array):
    c = _Chunked(chunk, exact, *arguments)
    x, dtype, f32 = arguments[0], c.dtype, jnp.float32
    scores, decays = c.decayed_scores()
    y = c.einsum("bnHts,bnHsp->bnHtp", (scores * decays).astype(dtype), c.stepped.astype(dtype))
    # a chunk's own state, then the serial part: S before every chunk
    own = c.einsum(
        "bnHsp,bnsGc->bnHpc", (c.stepped * jnp.exp(c.last - c.G)[..., None]).astype(dtype), c.B
    )  # (B, N, H, P, n)
    states = checkpoint_name(c.across(own, reverse=False, dtype=dtype), "ssd_states")
    y = y + jnp.exp(c.G)[..., None] * c.einsum("bntGc,bnHpc->bnHtp", c.C, states)
    y = c.positions_first(y + c.D[..., None] * c.x.astype(f32), x)
    return checkpoint_name(y, "ssd_y"), (arguments, states)


def _backward(chunk: int, exact: Any, kept, d_y: jax.Array):
    arguments, states = kept
    c = _Chunked(chunk, exact, *arguments)
    dtype, f32 = c.dtype, jnp.float32
    x32, G, last = c.x.astype(f32), c.G, c.last
    d_y = c.heads_first(d_y.astype(dtype), chunk)
    wide = d_y.astype(f32)
    # inside a chunk, through ``y = M (dt x)``: M again, M^T dy and dM
    scores, decays = c.decayed_scores()
    d_stepped = c.einsum("bnHts,bnHtp->bnHsp", (scores * decays).astype(dtype), d_y)
    d_pairs = c.einsum(
        "bnHtp,bnHsp->bnHts", d_y, c.stepped.astype(dtype), preferred_element_type=dtype
    )
    d_decays = d_pairs.astype(f32) * decays
    d_scores = jnp.sum(d_decays, axis=-3).astype(dtype)  # over a group's heads
    d_C = c.einsum("bnGts,bnsGc->bntGc", d_scores, c.B)
    d_B = c.einsum("bnGts,bntGc->bnsGc", d_scores, c.C)
    # the exponents' cotangent ``W = dM * M`` below the diagonal (on it the
    # exponent is 0 whatever the sums are), by rows less by columns
    W = jnp.tril(d_decays * scores, -1)
    d_G = jnp.sum(W, axis=-1) - jnp.sum(W, axis=-2)
    # through the carried term and the serial part
    grown = jnp.exp(G)
    carried = (grown[..., None] * wide).astype(dtype)
    d_C = d_C + c.einsum("bnHtp,bnHpc->bntGc", carried, states)
    d_G = d_G + grown * jnp.sum(wide * c.einsum("bntGc,bnHpc->bnHtp", c.C, states), axis=-1)
    d_closing = c.across(c.einsum("bnHtp,bntGc->bnHpc", carried, c.C), reverse=True, dtype=f32)
    d_whole = jnp.exp(last) * jnp.sum(d_closing * states.astype(f32), axis=(-2, -1))[..., None]
    # through a chunk's own state
    out, d_closing = jnp.exp(last - G), d_closing.astype(dtype)
    d_own = c.einsum("bnHpc,bnsGc->bnHsp", d_closing, c.B)
    d_B = d_B + c.einsum(
        "bnHsp,bnHpc->bnsGc", (c.stepped * out[..., None]).astype(dtype), d_closing
    )
    d_out = jnp.sum(d_own * c.stepped, axis=-1) * out
    d_G = (d_G - d_out).at[..., -1:].add(d_whole + jnp.sum(d_out, axis=-1, keepdims=True))
    # the running sums back to their terms, then every argument's own
    d_a = c.einsum("ts,bnHt->bnHs", c.to_now.astype(f32), d_G, precision=exact)
    d_stepped = d_stepped + d_own * out[..., None]
    d_x = d_stepped * c.dt[..., None] + c.D[..., None] * wide
    d_dt = d_a * c.A + jnp.sum(d_stepped * x32, axis=-1)
    # a head: over the batch, the chunks and a chunk's positions (and channels)
    d_A, d_D = jnp.sum(d_a * c.dt, axis=(0, 1, -1)), jnp.sum(wide * x32, axis=(0, 1, -2, -1))
    x, dt, A, B, C, D = arguments
    return (
        c.positions_first(d_x, x), c.positions_first(d_dt, dt),
        d_A.reshape(A.shape).astype(A.dtype), _from_chunks(d_B, B), _from_chunks(d_C, C),
        d_D.reshape(D.shape).astype(D.dtype),
    )


_scan.defvjp(_forward, _backward)
