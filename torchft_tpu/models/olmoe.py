"""OLMoE: a decoder whose every layer is RoPE / QK-norm attention and a
dropless mixture of SwiGLU experts (Muennighoff et al., arXiv:2409.02060;
``transformers``' ``modeling_olmoe.py``).

For activations ``x`` (B, S, D), per layer, pre-norm::

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))

- Attention: ``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` - QK-norm over
  the whole D-wide projection, before the split into heads, each with its
  own scale - and ``v = x Wv``; heads of ``head_dim``; rotary embedding
  with rotate-half pairing (``i`` with ``i + head_dim / 2``) on q and k;
  causal softmax attention scaled by ``head_dim ** -0.5``; ``out Wo``.
  No biases.
- MoE: router logits ``r = x Wg``, ``p = softmax(r)`` over all experts in
  float32, the ``experts_per_token`` largest ``p`` kept with their values
  as weights, not renormalised; expert ``e`` is
  ``W_down,e (silu(W_gate,e x) * W_up,e x)``; the output is the weighted
  sum over the token's experts. Dropless: every one of the N x K claims is
  computed, whatever the load - the claims are sorted by expert, their
  rows gathered, three grouped matmuls run over the ragged groups
  (``jax.lax.ragged_dot``) and the rows are brought back to their tokens.
  (``moe.py`` is the other dispatch the repo has: capacity slots, claims
  over capacity dropped, the form its expert-parallel mesh tests rest on.)
- Loss: next-token cross entropy + ``balance_coef`` x balance loss +
  ``z_coef`` x router z-loss. Balance loss as ``transformers``'
  ``load_balancing_loss_func``: ``E sum_e f_e P_e`` with ``f_e`` the share
  of the tokens that chose ``e`` (summed over the K choices) and ``P_e``
  the mean of ``p[:, e]``; z-loss the mean of ``logsumexp(r) ** 2``; all
  three means over every token of the step and every layer.
- Embedding without a position table, final RMSNorm, and a readout matrix
  of its own (untied).

Pure-functional like the dense family: f32 master parameters in a pytree,
matmuls in ``cfg.dtype``; the router's matmul, softmax and top-k stay in
float32, because a rounding there changes which experts a token gets.

What is DATA in the configuration, with OLMoE's form as every default, so
that a second sparse decoder is a configuration and not a copy of this
file (``models/mellum.py`` is one):

- a KIND per layer (``layer_kinds``: ``AttentionKind``): a sliding window
  or none, YaRN's blend of the rotary frequencies or none, and a name,
  under which the layer's attention is scoped (``attn/<name>/..``);
- grouped-query attention (``n_kv_heads`` key/value heads, each shared by
  ``n_heads / n_kv_heads`` consecutive query heads and repeated to them
  before the kernel), a head size that is stated and not derived
  (``head_dim``), QK-norm over each head (``qk_norm_per_head``);
- the top-K weights divided by their sum (``renormalize_top_k``);
- ONE RANK'S SHARE of an expert-parallel layer (``held_experts``: first,
  count): the router still scores all ``n_experts``, the weights are
  (held, d, f), and the layer returns the part of the result its own
  experts give - what the absent experts would add is left out, and no
  code stands in for them or their exchange. The share does the work of
  the claims it holds (``_held_share``, ``_held_experts``), in plain XLA
  operations and no grouped kernel: an expert that many tokens chose (more
  than ``L`` of them: ``_share_buffer``, from shapes alone, about 1.25
  times an expert's load under even routing) is applied to every token in
  place, a token that did not choose it weighted 0; the claims on the
  other held experts are laid out, expert after expert and each group from
  a tile boundary on, in one buffer of ``R`` rows in tiles of ``T`` (``R``
  one and a half times the claims the held experts expect), where they fit
  whatever the routing, and a loop that ends with the tiles IN USE
  multiplies each tile by its expert's weights. No claim is dropped and no
  form is chosen by hand; ``moe_layer``'s ``held_dense_layers`` is the
  share of the layer's held experts that were applied to every token.
  ``_held_dense`` - every held expert on every token - is what this
  replaced in the step and what the tests hold it to;
- the block's other variations: a feed-forward KIND per layer
  (``dense_ff``: the experts, or one dense SwiGLU of a stated width,
  ``W_down (silu(W_gate x) * W_up x)``, under the scope ``mlp`` alone),
  attention without QK-norm (``qk_norm``), and a sandwich of norms
  (``sandwich_norms``): ``h = x + N2(Attn(N1(x)))``, ``y = h + N4(FF(N3(h)))``;
- a LOOPED stack (``passes`` = T > 1; ``models/ouro.py`` is one): the
  layers run T times on the SAME weights, ``u_t = Stack(h_{t-1})``, ``h_0``
  the embedding; the final norm closes every pass and its OUTPUT is what
  the next pass starts from, ``h_t = RMSNorm_f(u_t)``. Every pass ends in
  an exit: logits ``z_t = h_t W_out`` (the one readout) and a gate ``g_t =
  sigmoid(w_g . h_t + b_g)``. A position leaves at exit ``t`` with
  probability ``p_t = g_t prod_{j<t} (1 - g_j)``, at the last with what
  is left, ``p_T = prod_{j<T} (1 - g_j)``, and the training loss is the
  mean over positions of ``sum_t p_t CE_t - exit_entropy_coef x H(p)``,
  ``CE_t`` the next-token cross entropy of exit ``t`` and ``H`` the
  entropy of ``p`` over the T exits, in float32 (plus the router losses
  over every pass where a layer has experts). ``_looped`` says how it
  runs: one ``lax.scan`` over the passes, each pass recomputed in the
  backward pass, the weights' gradient summed over the passes in float32.
  T = 1 is the plain model above: no gate, no loop.
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import flash_attention
from .transformer import _dense_init, _rmsnorm, next_token_loss, next_token_losses


@dataclass(frozen=True)
class Yarn:
    """YaRN's blend of rotary frequencies (Peng et al., arXiv:2309.00071,
    as ``transformers``' ``_compute_yarn_parameters`` has it): pair ``i``
    turns at ``(1 - r_i) f_i + r_i f_i / factor`` with ``f_i`` the plain
    frequency and ``r_i`` a ramp from 0 at the pair that turns
    ``beta_fast`` times over ``original_positions`` to 1 at the pair that
    turns ``beta_slow`` times; cos and sin are both multiplied by
    ``attention_factor``."""

    factor: float
    original_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


@dataclass(frozen=True)
class AttentionKind:
    """What one layer's attention is: ``window`` keys back (``q_pos -
    k_pos < window``) or all of them, and YaRN's blend of the rotary
    frequencies or the plain ones. ``name`` is the ``jax.named_scope`` the
    layer's attention runs under, inside ``attn``; the unnamed kind is
    OLMoE's and adds no scope."""

    name: Optional[str] = None
    window: Optional[int] = None
    yarn: Optional[Yarn] = None


@dataclass(frozen=True)
class OlmoeConfig:
    """The model's description; the defaults are OLMoE-1B-7B's
    ``config.json`` and the paper's two loss weights."""

    vocab_size: int = 50304
    d_model: int = 2048
    n_heads: int = 16
    n_layers: int = 16
    n_experts: int = 64
    experts_per_token: int = 8
    expert_width: int = 1024
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    balance_coef: float = 0.01
    z_coef: float = 0.001
    dtype: Any = jnp.bfloat16  # activation/matmul dtype; params stay f32
    # what OLMoE does not vary (module docstring); None is OLMoE's form
    n_kv_heads: Optional[int] = None  # n_heads
    head_dim: Optional[int] = None  # d_model / n_heads
    qk_norm_per_head: bool = False
    layer_kinds: Optional[Tuple[AttentionKind, ...]] = None  # all unnamed
    renormalize_top_k: bool = False
    held_experts: Optional[Tuple[int, int]] = None  # (first, count): all
    qk_norm: bool = True  # False: q and k go to the rotary embedding as projected
    dense_ff: Optional[Tuple[Optional[int], ...]] = None  # a layer's dense width; None: experts
    sandwich_norms: bool = False
    passes: int = 1  # T: how many times the stack runs, on the same weights
    exit_entropy_coef: float = 0.0  # what the entropy of the exits is worth (T > 1)

    def __post_init__(self) -> None:
        if self.head_dim is None:
            if self.d_model % self.n_heads:
                raise ValueError("d_model is no multiple of n_heads: state head_dim")
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        first, count = self.held
        if self.n_heads % self.kv_heads:
            raise ValueError("n_heads is no multiple of n_kv_heads")
        if len(self.kinds) != self.n_layers or len(self.ff) != self.n_layers:
            raise ValueError("layer_kinds or dense_ff names another number of layers than n_layers")
        if self.passes < 1:
            raise ValueError("the stack runs at least once")
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"held_experts {self.held_experts} lie outside the {self.n_experts}")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def kinds(self) -> Tuple[AttentionKind, ...]:
        return self.layer_kinds or (AttentionKind(),) * self.n_layers

    @property
    def ff(self) -> Tuple[Optional[int], ...]:
        """Per layer, the width of its dense SwiGLU, or None for experts."""
        return self.dense_ff or (None,) * self.n_layers

    @property
    def expert_layers(self) -> int:
        return sum(width is None for width in self.ff)

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the experts this rank holds in every layer."""
        return self.held_experts or (0, self.n_experts)


def tiny_olmoe_config() -> OlmoeConfig:
    """Small config for tests and CPU rehearsals."""
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, n_experts=8,
        experts_per_token=2, expert_width=32,
    )


def init_params(cfg: OlmoeConfig, key: jax.Array) -> Dict[str, Any]:
    """f32 master params; matmuls cast to cfg.dtype at use."""
    d, f, e = cfg.d_model, cfg.expert_width, cfg.n_experts
    q_width, kv_width = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    held = cfg.held[1]
    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = d ** -0.5

    def ones(width: int = d) -> jax.Array:
        return jnp.ones((width,), jnp.float32)

    blocks = []
    for i, width in enumerate(cfg.ff):
        bk = jax.random.split(keys[2 + i], 8)
        block = {
            "ln1": {"scale": ones()},
            "attn": {
                "wq": _dense_init(bk[0], (d, q_width), scale),
                "wk": _dense_init(bk[1], (d, kv_width), scale),
                "wv": _dense_init(bk[2], (d, kv_width), scale),
                "wo": _dense_init(bk[3], (q_width, d), q_width ** -0.5),
            },
            "ln2": {"scale": ones()},
        }
        if cfg.qk_norm:
            block["attn"].update(
                q_norm=ones(cfg.head_dim if cfg.qk_norm_per_head else q_width),
                k_norm=ones(cfg.head_dim if cfg.qk_norm_per_head else kv_width),
            )
        if cfg.sandwich_norms:  # the second norm of each sublayer
            block.update(ln1_post={"scale": ones()}, ln2_post={"scale": ones()})
        if width is None:
            block["moe"] = {
                "router": _dense_init(bk[4], (d, e), scale),
                "w_gate": _dense_init(bk[5], (held, d, f), scale),
                "w_up": _dense_init(bk[6], (held, d, f), scale),
                "w_down": _dense_init(bk[7], (held, f, d), f ** -0.5),
            }
        else:
            block["mlp"] = {
                "w_gate": _dense_init(bk[5], (d, width), scale),
                "w_up": _dense_init(bk[6], (d, width), scale),
                "w_down": _dense_init(bk[7], (width, d), width ** -0.5),
            }
        blocks.append(block)
    params = {
        "embed": _dense_init(keys[0], (cfg.vocab_size, d), scale),
        "blocks": blocks,
        "ln_f": {"scale": ones()},
        "readout": _dense_init(keys[1], (d, cfg.vocab_size), scale),
    }
    if cfg.passes > 1:  # the exits' gate: one map of d to 1, with a bias
        gate_key = jax.random.fold_in(key, 2 + cfg.n_layers)
        params["exit_gate"] = {
            "w": _dense_init(gate_key, (d,), scale), "b": jnp.zeros((), jnp.float32),
        }
    return params


def _yarn_ramp(yarn: Yarn, theta: float, head_dim: int) -> jax.Array:
    """``r_i`` of ``Yarn`` for the ``head_dim / 2`` pairs: 0 up to the pair
    ``low``, 1 from the pair ``high`` on, linear between."""

    def pair_that_turns(times: float) -> float:
        return head_dim * math.log(
            yarn.original_positions / (times * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_that_turns(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(yarn.beta_slow)), head_dim - 1)
    pairs = jnp.arange(head_dim // 2, dtype=jnp.float32)
    return jnp.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)


def rope(x: jax.Array, theta: float, yarn: Optional[Yarn] = None) -> jax.Array:
    """Rotary embedding of ``x`` (B, S, H, head_dim) at positions 0..S-1:
    the pair (``i``, ``i + head_dim / 2``) turns by ``pos * theta ** (-2 i
    / head_dim)``, or by ``yarn``'s blend of that frequency (``Yarn``).
    Computed in float32, rounded once."""
    S, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if yarn is not None:
        ramp = _yarn_ramp(yarn, theta, 2 * half)
        inv_freq = (1.0 - ramp) * inv_freq + ramp * inv_freq / yarn.factor
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq  # (S, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return turned.astype(x.dtype)


def attention(
    cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array,
    kind: AttentionKind = AttentionKind(),
) -> jax.Array:
    B, S, _ = x.shape
    h, kv, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    q, k, v = (x @ p[w].astype(cfg.dtype) for w in ("wq", "wk", "wv"))
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            if cfg.qk_norm_per_head:  # each head's own dh, one scale for all
                q, k = q.reshape(B, S, h, dh), k.reshape(B, S, kv, dh)
            q = _rmsnorm(q, p["q_norm"], cfg.rms_norm_eps)
            k = _rmsnorm(k, p["k_norm"], cfg.rms_norm_eps)
    q, k, v = (t.reshape(B, S, n, dh) for t, n in ((q, h), (k, kv), (v, kv)))
    with jax.named_scope("rope"):
        q, k = (rope(t, cfg.rope_theta, kind.yarn) for t in (q, k))
    if kv != h:
        # query head i meets key/value head i // (h / kv): each is repeated
        # to its query heads (a grouped kernel would read it once)
        k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
    # the fused kernel everywhere: compiled on a TPU, interpreted elsewhere
    out = flash_attention(q, k, v, window=kind.window)
    return out.reshape(B, S, h * dh) @ p["wo"].astype(cfg.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_claims(tokens: jax.Array, order: jax.Array, inverse: jax.Array, k: int) -> jax.Array:
    """The row of its token for every claim, in sorted order: claim ``c``
    (token-major) belongs to token ``c // k``. The cotangent undoes the
    sort (``inverse``, a gather) and sums each token's ``k`` rows in
    float32, where autodiff alone would write a scatter-add of N x k
    rows. This and ``_unsort`` are worth their lines: at OLMoE-1B-7B's
    widths, 16,384 tokens a step on a v5e, the step takes 207.95 ms with
    them and 223.21 ms with plain indexing (PERF.md section 6, PR 26)."""
    return tokens[order // k]


def _to_claims_fwd(tokens, order, inverse, k):
    return tokens[order // k], inverse


def _to_claims_bwd(k, inverse, g):
    per_token = g[inverse].reshape(-1, k, g.shape[-1])
    return jnp.sum(per_token, axis=1, dtype=jnp.float32).astype(g.dtype), None, None


_to_claims.defvjp(_to_claims_fwd, _to_claims_bwd)


@jax.custom_vjp
def _unsort(rows: jax.Array, order: jax.Array, inverse: jax.Array) -> jax.Array:
    """``rows[inverse]``: the sorted claims' rows back in token-major
    order. ``order`` and ``inverse`` are each other's inverse
    permutation, so the cotangent is a gather too (``g[order]``)."""
    return rows[inverse]


def _unsort_fwd(rows, order, inverse):
    return rows[inverse], order


def _unsort_bwd(order, g):
    return g[order], None, None


_unsort.defvjp(_unsort_fwd, _unsort_bwd)


def _experts(
    cfg: OlmoeConfig, p: Dict[str, Any], rows: jax.Array, group_sizes: jax.Array
) -> jax.Array:
    """``W_down,e (silu(W_gate,e x) * W_up,e x)`` of every row, the rows
    of expert ``e`` being the ``group_sizes[e]`` that follow those of the
    experts before it: three grouped matmuls."""

    def grouped(lhs: jax.Array, w: jax.Array) -> jax.Array:
        return jax.lax.ragged_dot(lhs, w.astype(cfg.dtype), group_sizes)

    hidden = jax.nn.silu(grouped(rows, p["w_gate"])) * grouped(rows, p["w_up"])
    return grouped(hidden, p["w_down"])


def _held_dense(
    cfg: OlmoeConfig, p: Dict[str, Any], tokens: jax.Array,
    weights: jax.Array, chosen: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer's output, (N, D) float32, and
    how many of the N x K claims they hold: every held expert applied to
    every token and kept, times its weight, where the token chose it.
    Exact and dropless by construction; its work is N x held rows whatever
    the routing. The step runs ``_held_share``; this is the form the tests
    hold it to, output and gradients."""
    first, held = cfg.held
    with jax.named_scope("dispatch"):
        # (N, held): the token's weight on each held expert, 0 where it
        # chose another
        mine = (chosen - first)[:, :, None] == jnp.arange(held)
        gate = jnp.sum(jnp.where(mine, weights[:, :, None], 0.0), axis=1)
    with jax.named_scope("experts"):
        def into(w: str) -> jax.Array:  # (held, N, f)
            return jnp.einsum("nd,edf->enf", tokens, p[w].astype(cfg.dtype))

        hidden = jax.nn.silu(into("w_gate")) * into("w_up")
    with jax.named_scope("combine"):
        hidden = (hidden * gate.T[:, :, None]).astype(cfg.dtype)
    with jax.named_scope("experts"):
        y = jnp.einsum(
            "enf,efd->nd", hidden, p["w_down"].astype(cfg.dtype),
            preferred_element_type=jnp.float32,
        )
    return y, jnp.sum(mine)


# The widest tile of a share's buffer, in rows: a multiple of the MXU's 128
# at which a tile's matmul against its expert's (d, f) weights stays above
# the v5e's ridge of 240 FLOP a byte (2 T d f operations over 2 (d f + T d +
# T f) bytes: 285 at Mellum2's 2304 x 896). A smaller step falls under it.
_TILE_ROWS = 512


def _share_buffer(cfg: OlmoeConfig, n: int) -> Tuple[int, int, int]:
    """(R, T, L) of a held share over ``n`` tokens, from shapes alone. A
    tile is ``T`` rows of one expert: the largest power of two up to
    ``_TILE_ROWS`` at which the padding of every group to a tile boundary,
    under ``held x T`` rows, stays within a quarter of the claims the held
    experts EXPECT under even routing, ``n K held / E`` (``4 T E <= n K``).
    ``R`` rows, in whole tiles, hold one and a half times the expected
    claims. An expert with more than ``L`` claims is HEAVY and applied to
    every token in place; ``L`` is the most at which ``held`` light groups,
    each padded to a tile boundary, fit ``R`` whatever the routing, so no
    claim is ever without a row: about 1.25 times an expert's expected
    load. (A row of the tile loop costs about five times a row of a
    matmul over all tokens on a v5e, its two scatter-adds above all:
    PERF.md section 6, PR 40; an expert that a fifth of the tokens chose
    is cheaper applied to all.)"""
    E, K, held = cfg.n_experts, cfg.experts_per_token, cfg.held[1]
    tile = 8
    while tile < _TILE_ROWS and 8 * tile * E <= n * K:
        tile *= 2
    rows = -(-3 * n * K * held // (2 * E * tile)) * tile
    return rows, tile, max(rows // held - (tile - 1), 0)


def _tile(stack: jax.Array, c: jax.Array) -> jax.Array:
    return jax.lax.dynamic_index_in_dim(stack, c, 0, keepdims=False)


def _swiglu(cfg: OlmoeConfig, into: jax.Array) -> jax.Array:
    """``silu(gate) * up`` of the (rows, 2 f) products of gate and up."""
    f = cfg.expert_width
    return jax.nn.silu(into[:, :f]) * into[:, f:]


# What ``_held_share`` hands the experts beside the tokens and the weights:
# the light experts' buffer - (tiles, T) the token of every row (one past N
# where it has none) and its claim (expert-major: claim e N + n is token n's
# on held expert e; held N where the row has none), (tiles,) the expert of
# every tile, how many tiles are in use - and the heavy experts: (held,) the
# held experts with the heavy ones first, and how many those are.
_Layout = Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(
    cfg: OlmoeConfig, tokens: jax.Array, w_in: jax.Array, w_down: jax.Array,
    gate: jax.Array, layout: _Layout,
) -> jax.Array:
    """The held experts' part of the layer's output, (N, D) float32, from
    the tokens, gate and up side by side (``w_in`` (held, D, 2 f)), the
    weights down, the (held, N) weight of every token on every held expert
    (0 where it chose another) and ``_held_share``'s layout. Two loops,
    each as long as the routing makes it:

    - over the light experts' tiles in use: a tile's rows gathered from
      ``tokens``, gate and up in one matmul, the claim's weight applied
      where ``_held_dense`` applies it, down, the tile's float32 rows
      added to their tokens (one scatter-add a tile, inside the loop:
      eight tiles at a time in a loop of their own took twice as long a
      row on a v5e, PERF.md section 6, PR 40);
    - over the heavy experts: the same arithmetic on ALL tokens in place,
      no gather and no scatter-add, a token that did not choose the expert
      weighted 0.

    The backward pass is the same two loops, written out below. It keeps
    the tiles' (tiles, T, 2 f) products of gate and up and computes a
    heavy expert's again: kept, they are the dense form's 0.47 GB a layer
    in a buffer that exists whatever the routing."""
    return _held_experts_fwd(cfg, tokens, w_in, w_down, gate, layout)[0]


def _into(cfg: OlmoeConfig, rows: jax.Array, w_in: jax.Array, e: jax.Array) -> jax.Array:
    return jnp.dot(rows, _tile(w_in, e), preferred_element_type=jnp.float32).astype(cfg.dtype)


def _held_experts_fwd(cfg, tokens, w_in, w_down, gate, layout):
    token_of_row, claim_of_row, tile_expert, used, heavy_first, heavy = layout
    with jax.named_scope("dispatch"):
        weight_of_row = gate.reshape(-1).at[claim_of_row].get(mode="fill", fill_value=0.0)

    def out_of(into, weight, e):  # (rows, D) float32
        with jax.named_scope("experts"):
            hidden = _swiglu(cfg, into)
        with jax.named_scope("combine"):  # rounded as ``_held_dense`` rounds it
            hidden = (hidden * weight[:, None]).astype(cfg.dtype)
        with jax.named_scope("experts"):
            return jnp.dot(hidden, _tile(w_down, e), preferred_element_type=jnp.float32)

    def tile(c, carry):
        y, kept = carry  # (N, D) float32, (tiles, T, 2 f)
        e, of_tile = tile_expert[c], token_of_row[c]
        with jax.named_scope("dispatch"):
            rows = tokens.at[of_tile].get(mode="clip")  # no token: weight 0
        with jax.named_scope("experts"):
            into = _into(cfg, rows, w_in, e)
        out = out_of(into, weight_of_row[c], e)
        with jax.named_scope("combine"):  # a row of no token is dropped
            y = y.at[of_tile].add(out, mode="drop")
        return y, jax.lax.dynamic_update_index_in_dim(kept, into, c, 0)

    def expert(j, y):
        e = heavy_first[j]
        with jax.named_scope("experts"):
            into = _into(cfg, tokens, w_in, e)
        return y + out_of(into, gate[e], e)

    y, kept = jax.lax.fori_loop(0, used, tile, (
        jnp.zeros(tokens.shape, jnp.float32),
        jnp.zeros(token_of_row.shape + w_in.shape[2:], cfg.dtype),
    ))
    y = jax.lax.fori_loop(0, heavy, expert, y)
    return y, (tokens, w_in, w_down, gate, layout, kept)


def _held_experts_bwd(cfg, res, g):
    tokens, w_in, w_down, gate, layout, kept = res
    token_of_row, claim_of_row, tile_expert, used, heavy_first, heavy = layout
    g = g.astype(cfg.dtype)  # what the matmuls below multiply in
    with jax.named_scope("dispatch"):
        weight_of_row = gate.reshape(-1).at[claim_of_row].get(mode="fill", fill_value=0.0)

    def back(rows, into, weight, g_out, e, d_in, d_down):
        """One group of rows of expert ``e``: the gradient of its rows
        (float32) and of its weights on them, the expert's weight
        gradients added in ``d_in`` and ``d_down``."""
        with jax.named_scope("experts"):
            plain, undo = jax.vjp(functools.partial(_swiglu, cfg), into)
        with jax.named_scope("combine"):
            hidden = (plain * weight[:, None]).astype(cfg.dtype)
        with jax.named_scope("experts"):
            g_hidden = jnp.dot(g_out, _tile(w_down, e).T, preferred_element_type=jnp.float32)
            d_down_e = _tile(d_down, e) + jnp.dot(
                hidden.T, g_out, preferred_element_type=jnp.float32
            )
        with jax.named_scope("combine"):
            d_weight = jnp.sum(g_hidden * plain.astype(jnp.float32), axis=1)
            g_plain = (g_hidden * weight[:, None]).astype(cfg.dtype)
        with jax.named_scope("experts"):
            g_into, = undo(g_plain)
            g_rows = jnp.dot(g_into, _tile(w_in, e).T, preferred_element_type=jnp.float32)
            d_in_e = _tile(d_in, e) + jnp.dot(
                rows.T, g_into, preferred_element_type=jnp.float32
            )
        return (
            g_rows, d_weight,
            jax.lax.dynamic_update_index_in_dim(d_in, d_in_e, e, 0),
            jax.lax.dynamic_update_index_in_dim(d_down, d_down_e, e, 0),
        )

    def tile(c, carry):
        d_tokens, d_in, d_down, d_weights = carry
        e, of_tile = tile_expert[c], token_of_row[c]
        with jax.named_scope("combine"):
            g_out = g.at[of_tile].get(mode="clip")  # (T, D)
        with jax.named_scope("dispatch"):
            rows = tokens.at[of_tile].get(mode="clip")
        g_rows, d_weight, d_in, d_down = back(
            rows, _tile(kept, c), weight_of_row[c], g_out, e, d_in, d_down
        )
        with jax.named_scope("dispatch"):
            d_tokens = d_tokens.at[of_tile].add(g_rows, mode="drop")
        return d_tokens, d_in, d_down, jax.lax.dynamic_update_index_in_dim(d_weights, d_weight, c, 0)

    def expert(j, carry):
        d_tokens, d_in, d_down, d_gate = carry
        e = heavy_first[j]
        with jax.named_scope("experts"):
            into = _into(cfg, tokens, w_in, e)
        g_rows, d_weight, d_in, d_down = back(tokens, into, gate[e], g, e, d_in, d_down)
        return d_tokens + g_rows, d_in, d_down, jax.lax.dynamic_update_index_in_dim(d_gate, d_weight, e, 0)

    d_tokens, d_in, d_down, d_weights = jax.lax.fori_loop(0, used, tile, (
        jnp.zeros(tokens.shape, jnp.float32), jnp.zeros(w_in.shape, jnp.float32),
        jnp.zeros(w_down.shape, jnp.float32), jnp.zeros(token_of_row.shape, jnp.float32),
    ))
    with jax.named_scope("dispatch"):  # a light claim's weight; a heavy expert's below
        d_gate = jnp.zeros((gate.size,), jnp.float32).at[claim_of_row].add(
            d_weights, mode="drop"
        ).reshape(gate.shape)
    d_tokens, d_in, d_down, d_gate = jax.lax.fori_loop(
        0, heavy, expert, (d_tokens, d_in, d_down, d_gate)
    )
    return (
        d_tokens.astype(tokens.dtype), d_in.astype(w_in.dtype),
        d_down.astype(w_down.dtype), d_gate, None,
    )


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _held_share(
    cfg: OlmoeConfig, p: Dict[str, Any], tokens: jax.Array,
    weights: jax.Array, chosen: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The held experts' part of the layer's output, (N, D) float32, how
    many of the N x K claims they hold, and how many of the held experts
    were heavy.

    An expert with more than ``L`` claims (``_share_buffer``) is applied to
    every token in place. The claims on the others are laid out in one
    buffer of ``R`` rows, expert after expert, each expert's group from a
    tile boundary on and in the order of its tokens: a cumulative count
    per expert gives a claim its row, no sort; they fit whatever the
    routing. The work follows the tiles in use and the heavy experts, not
    N x held rows (``_held_experts``); no claim is dropped and none is
    computed twice."""
    N, K = chosen.shape
    first, held = cfg.held
    rows, tile, light_up_to = _share_buffer(cfg, N)
    with jax.named_scope("dispatch"):
        mine = (chosen - first)[:, :, None] == jnp.arange(held)  # (N, K, held)
        gate = jnp.sum(jnp.where(mine, weights[:, :, None], 0.0), axis=1).T  # (held, N)
        hit = jnp.any(mine, axis=1).T  # (held, N): the token chose the expert
        is_heavy = jnp.sum(hit, axis=1) > light_up_to
        light = hit & ~is_heavy[:, None]
        rank = jnp.cumsum(light, axis=1, dtype=jnp.int32)  # 1 for its first claim
        padded = -(-rank[:, -1] // tile) * tile
        ends = jnp.cumsum(padded)
        claim = jnp.arange(held * N, dtype=jnp.int32).reshape(held, N)
        # a claim's row; no row (past the buffer, each its own) for the rest
        row_of_claim = jnp.where(light, (ends - padded)[:, None] + rank - 1, rows + claim)
        claim_of_row = jnp.full((rows,), held * N, jnp.int32).at[
            row_of_claim.reshape(-1)
        ].set(claim.reshape(-1), mode="drop", unique_indices=True)
        # a row's token; past N, each its own, where it has none
        token_of_row = jnp.where(
            claim_of_row < held * N, claim_of_row % N, N + jnp.arange(rows)
        )
        tile_expert = jnp.minimum(
            jnp.sum(jnp.arange(0, rows, tile)[:, None] >= ends, axis=1), held - 1
        )
        heavy_first = jnp.argsort(~is_heavy, stable=True).astype(jnp.int32)
        heavy = jnp.sum(is_heavy, dtype=jnp.int32)
    with jax.named_scope("experts"):
        w_in = jnp.concatenate([p["w_gate"], p["w_up"]], axis=-1).astype(cfg.dtype)
    y = _held_experts(cfg, tokens, w_in, p["w_down"].astype(cfg.dtype), gate, (
        token_of_row.reshape(-1, tile), claim_of_row.reshape(-1, tile),
        tile_expert, ends[-1] // tile, heavy_first, heavy,
    ))
    return y, jnp.sum(hit), heavy


def _every_expert(
    cfg: OlmoeConfig, p: Dict[str, Any], tokens: jax.Array,
    weights: jax.Array, chosen: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """The whole layer where every expert is held, (N, D) float32, and how
    many claims each expert got: the claims sorted by expert, three
    grouped matmuls over all N x K rows, each row back to its token."""
    N, D = tokens.shape
    E, K = cfg.n_experts, cfg.experts_per_token

    with jax.named_scope("dispatch"):
        # claims are numbered token-major (claim c belongs to token c // K);
        # sorted by expert, each expert's rows are one contiguous group
        expert_of_claim = chosen.reshape(N * K)
        order = jnp.argsort(expert_of_claim, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.zeros((E,), jnp.int32).at[expert_of_claim].add(1)
        rows = _to_claims(tokens, order, inverse, K)  # (N * K, D)

    with jax.named_scope("experts"):
        out_rows = _experts(cfg, p, rows, group_sizes)  # (N * K, D)

    with jax.named_scope("combine"):
        back = _unsort(out_rows, order, inverse).reshape(N, K, D)
        y = jnp.einsum(
            "nkd,nk->nd", back, weights, preferred_element_type=jnp.float32
        )
    return y, group_sizes


def moe_layer(
    cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dropless top-K routed SwiGLU experts, or the held experts' share of
    them (``cfg.held_experts``, ``_held_share``: tile by tile over the
    claims held, an expert that many tokens chose over all of them).

    Args:
        x: (B, S, D) activations.
    Returns:
        the (B, S, D) output, and the router's sums over this layer's
        tokens for the auxiliary losses: ``claims`` (E,) how many tokens
        chose each expert, ``probs`` (E,) the sum of ``p[:, e]``, ``z``
        the sum of ``logsumexp(r) ** 2``; and ``held_claims``, how many of
        the N x K claims fell on an expert held here (all of them where
        every expert is), and ``held_dense_layers``, the share of the held
        experts that were applied to every token (0.0 to 1.0; summed over
        the layers, how many layers' worth of the dense form a step ran).
    """
    B, S, D = x.shape
    N, E, K = B * S, cfg.n_experts, cfg.experts_per_token
    tokens = x.reshape(N, D)

    with jax.named_scope("router"):
        # true float32 (on a TPU a default-precision f32 matmul runs in
        # bf16 passes): a rounding here changes which experts a token gets
        logits = jnp.dot(
            tokens.astype(jnp.float32), p["router"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )  # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, chosen = jax.lax.top_k(probs, K)  # (N, K)
        if cfg.renormalize_top_k:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    if cfg.held_experts is None:
        y, claims = _every_expert(cfg, p, tokens, weights, chosen)
        held_claims, heavy = N * K, 0
    else:
        y, held_claims, heavy = _held_share(cfg, p, tokens, weights, chosen)
        claims = jnp.zeros((E,), jnp.int32).at[chosen.reshape(N * K)].add(1)

    stats = {
        "claims": claims.astype(jnp.float32),
        "probs": jnp.sum(probs, axis=0),
        "z": jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "held_claims": jnp.asarray(held_claims, jnp.float32),
        "held_dense_layers": jnp.asarray(heavy, jnp.float32) / cfg.held[1],
    }
    return y.reshape(B, S, D).astype(x.dtype), stats


def dense_mlp(cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """``W_down (silu(W_gate x) * W_up x)``: a layer's dense SwiGLU."""
    hidden = jax.nn.silu(x @ p["w_gate"].astype(cfg.dtype)) * (x @ p["w_up"].astype(cfg.dtype))
    return hidden @ p["w_down"].astype(cfg.dtype)


def _block(
    cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array,
    kind: AttentionKind = AttentionKind(), width: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    # the dense family's scope names (transformer._block) with the new
    # mechanisms nested in them: attn/qk_norm, attn/rope, mlp/moe/router,
    # mlp/moe/dispatch, mlp/moe/experts, mlp/moe/combine; a named kind of
    # layer puts its name between: attn/sliding/rope, attn/full/flash_fwd;
    # a dense feed-forward (``width``) is ``mlp`` alone and has no sums.
    # Metadata only.
    eps = cfg.rms_norm_eps

    def second(y: jax.Array, name: str) -> jax.Array:
        """A sublayer's output through the sandwich's second norm."""
        return _rmsnorm(y, p[name]["scale"], eps) if cfg.sandwich_norms else y

    of_kind = jax.named_scope(kind.name) if kind.name else contextlib.nullcontext()
    with jax.named_scope("attn"), of_kind:
        y = attention(cfg, p["attn"], _rmsnorm(x, p["ln1"]["scale"], eps), kind)
        x = x + second(y, "ln1_post")
    with jax.named_scope("mlp"):
        if width is not None:
            y = dense_mlp(cfg, p["mlp"], _rmsnorm(x, p["ln2"]["scale"], eps))
            return x + second(y, "ln2_post"), None
        with jax.named_scope("moe"):
            y, stats = moe_layer(cfg, p["moe"], _rmsnorm(x, p["ln2"]["scale"], eps))
            return x + second(y, "ln2_post"), stats


def _stack(
    cfg: OlmoeConfig, blocks: Any, x: jax.Array
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The layers in order: (B, S, D) -> (the last block's output, the
    routers' sums over the layers that have experts; None where none has)."""
    total = None
    for kind, width, p in zip(cfg.kinds, cfg.ff, blocks):
        x, stats = _block(cfg, p, x, kind, width)
        if stats is not None:
            total = stats if total is None else jax.tree_util.tree_map(jnp.add, total, stats)
    return x, total


def _embed(cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


def _hidden(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """tokens (B, S) int32 -> (the last block's output (B, S, D), the
    router's sums over every layer and token), the stack run once."""
    return _stack(cfg, params["blocks"], _embed(cfg, params, tokens))


def _looped(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array,
    exit_of: Callable[[jax.Array], Any],
) -> Tuple[jax.Array, Any, Optional[Dict[str, jax.Array]]]:
    """The stack ``cfg.passes`` times on the same weights. Returns, each
    with the passes as its first axis, the gates' logits (T, B, S) in
    float32 and ``exit_of(h_t)``, what the caller wants of every exit -
    computed INSIDE the pass, so that a pass's logits do not outlive it -
    and the routers' sums over every pass and layer (None without experts).

    One ``lax.scan`` over the passes with the weights closed over: the
    compiled program holds one stack however many times it runs. The body
    is under ``jax.checkpoint``: the backward pass keeps ``h_t`` alone
    between the passes and computes one pass's activations again when it
    comes to it, so the memory is one pass's whatever T is. The weights'
    gradient is the sum over the passes, and a scan sums the cotangent of
    what it closes over in that value's own type; the stack's weights are
    therefore handed in widened to float32 (a bf16 compute copy comes back
    as it was at every use, ``astype(cfg.dtype)``), each pass's gradient is
    added in float32 and the sum rounded once, as a framework that keeps
    float32 ``.grad`` under bf16 autocast sums it. What ``exit_of`` closes
    over - the readout - is NOT widened: its T contributions are added in
    the type it comes in, bf16 for a bf16 compute copy (widened too, the
    gradient program of ``ouro-2.6b-l6`` takes 7.61 GB of temporaries for
    7.00 by the compiler's memory analysis, PR 43). Scopes: ``loop`` holds
    the scan, the layers' ``attn`` and ``mlp`` inside it as ever, with
    ``exits`` (the gate) and whatever ``exit_of`` names."""
    blocks, ln_f, gate = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.float32),
        (params["blocks"], params["ln_f"]["scale"], params["exit_gate"]),
    )

    def one_pass(h: jax.Array, _: None) -> Tuple[jax.Array, Any]:
        u, stats = _stack(cfg, blocks, h)
        h = _rmsnorm(u, ln_f, cfg.rms_norm_eps)
        with jax.named_scope("exits"):
            logit = jnp.einsum(
                "bsd,d->bs", h, gate["w"].astype(h.dtype),
                preferred_element_type=jnp.float32,
            ) + gate["b"]
        return h, (logit, exit_of(h), stats)

    h = _embed(cfg, params, tokens)
    with jax.named_scope("loop"):
        _, (logits, exits, stats) = jax.lax.scan(
            # a scan's body is not CSE'd with its backward pass
            jax.checkpoint(one_pass, prevent_cse=False), h, None, length=cfg.passes,
        )
    if stats is not None:
        stats = jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0), stats)
    return logits, exits, stats


def exit_log_probs(gate_logits: jax.Array) -> jax.Array:
    """``log p_t`` (T, ...) of the exit distribution from the gates' logits
    ``a_t`` (T, ...), float32: ``p_t = g_t prod_{j<t} (1 - g_j)`` below the
    last exit, which takes what is left, ``p_T = prod_{j<T} (1 - g_j)``; its
    own gate is not asked. In logarithms, ``log g = log_sigmoid(a)`` and
    ``log (1 - g) = log_sigmoid(-a)``, so a gate driven to 0 or 1 gives a
    small number and no ``log 0``."""
    asked = gate_logits[:-1]
    none = jnp.zeros_like(gate_logits[:1])
    stayed = jnp.concatenate([none, jnp.cumsum(jax.nn.log_sigmoid(-asked), axis=0)])
    return stayed + jnp.concatenate([jax.nn.log_sigmoid(asked), none])


def _readout_product(
    cfg: OlmoeConfig, params: Dict[str, Any], x: jax.Array
) -> jax.Array:
    """Final norm, then the untied readout matmul: logits (..., V) in
    ``cfg.dtype``, the type the product is computed in. ``forward`` widens
    it to float32; ``loss_fn`` hands it to ``next_token_loss`` as it is."""
    x = _rmsnorm(x, params["ln_f"]["scale"], cfg.rms_norm_eps)
    return x @ params["readout"].astype(cfg.dtype)


def forward(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (B, S) int32 -> (logits (B, S, vocab) f32, the router's
    sums over every layer and token). A looped model (``cfg.passes`` = T >
    1) gives every exit's logits, (T, B, S, vocab), and with the sums
    ``exit_probs`` (T,): the exit distribution's mean over the positions."""
    if cfg.passes == 1:
        x, total = _hidden(cfg, params, tokens)
        with jax.named_scope("readout"):
            logits = _readout_product(cfg, params, x).astype(jnp.float32)
        return logits, total

    def exit_of(h: jax.Array) -> jax.Array:  # the pass's norm is the final norm
        with jax.named_scope("readout"):
            return (h @ params["readout"].astype(cfg.dtype)).astype(jnp.float32)

    gates, logits, total = _looped(cfg, params, tokens, exit_of)
    with jax.named_scope("exits"):
        exit_probs = jnp.mean(jnp.exp(exit_log_probs(gates)), axis=(1, 2))
    return logits, dict(total or {}, exit_probs=exit_probs)


def aux_losses(
    cfg: OlmoeConfig, stats: Dict[str, jax.Array], n_tokens: int
) -> Tuple[jax.Array, jax.Array]:
    """(balance loss, router z-loss) from the router's sums over
    ``n_tokens`` tokens a layer with experts and pass."""
    n = float(n_tokens * cfg.expert_layers * cfg.passes)
    balance = cfg.n_experts * jnp.sum((stats["claims"] / n) * (stats["probs"] / n))
    return balance, stats["z"] / n


def _exits_loss(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """A looped model's loss over its exits (module docstring) and the
    routers' sums: each pass hands out its exit's cross entropy a position
    (``next_token_losses``: the readout's product unwidened, read once)
    and its gate's logit, two (B, S) float32 arrays."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    def exit_of(h: jax.Array) -> jax.Array:
        with jax.named_scope("readout"):
            logits = h @ params["readout"].astype(cfg.dtype)
        return next_token_losses(logits, targets)

    gates, nll, stats = _looped(cfg, params, inputs, exit_of)
    with jax.named_scope("exits"):
        log_p = exit_log_probs(gates)
        p = jnp.exp(log_p)
        expected = jnp.sum(p * nll, axis=0)
        entropy = -jnp.sum(p * log_p, axis=0)
        return jnp.mean(expected - cfg.exit_entropy_coef * entropy), stats


def loss_fn(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array
) -> jax.Array:
    """Next-token cross entropy - over the exits, where the model is looped
    (``_exits_loss``) - + the two weighted router losses where a layer has
    experts. The loss reads the readout's product in ``cfg.dtype``,
    unwidened (``next_token_loss``)."""
    inputs = tokens[:, :-1]
    if cfg.passes > 1:
        loss, stats = _exits_loss(cfg, params, tokens)
    else:
        x, stats = _hidden(cfg, params, inputs)
        with jax.named_scope("readout"):
            logits = _readout_product(cfg, params, x)
    if stats is not None:  # a layer has experts
        with jax.named_scope("loss"), jax.named_scope("aux"):
            balance, z = aux_losses(cfg, stats, inputs.size)
    if cfg.passes == 1:  # after the router losses, where it has always been traced
        loss = next_token_loss(logits, tokens[:, 1:])
    if stats is None:
        return loss
    return loss + cfg.balance_coef * balance + cfg.z_coef * z
