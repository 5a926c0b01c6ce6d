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
  code stands in for them or their exchange (``_held_dense``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import flash_attention
from .transformer import _dense_init, _rmsnorm, next_token_loss


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

    def __post_init__(self) -> None:
        if self.head_dim is None:
            if self.d_model % self.n_heads:
                raise ValueError("d_model is no multiple of n_heads: state head_dim")
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        first, count = self.held
        if self.n_heads % self.kv_heads:
            raise ValueError("n_heads is no multiple of n_kv_heads")
        if len(self.kinds) != self.n_layers:
            raise ValueError("layer_kinds names another number of layers than n_layers")
        if first < 0 or count < 1 or first + count > self.n_experts:
            raise ValueError(f"held_experts {self.held_experts} lie outside the {self.n_experts}")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def kinds(self) -> Tuple[AttentionKind, ...]:
        return self.layer_kinds or (AttentionKind(),) * self.n_layers

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
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[2 + i], 8)
        blocks.append({
            "ln1": {"scale": ones()},
            "attn": {
                "wq": _dense_init(bk[0], (d, q_width), scale),
                "wk": _dense_init(bk[1], (d, kv_width), scale),
                "wv": _dense_init(bk[2], (d, kv_width), scale),
                "wo": _dense_init(bk[3], (q_width, d), q_width ** -0.5),
                "q_norm": ones(cfg.head_dim if cfg.qk_norm_per_head else q_width),
                "k_norm": ones(cfg.head_dim if cfg.qk_norm_per_head else kv_width),
            },
            "ln2": {"scale": ones()},
            "moe": {
                "router": _dense_init(bk[4], (d, e), scale),
                "w_gate": _dense_init(bk[5], (held, d, f), scale),
                "w_up": _dense_init(bk[6], (held, d, f), scale),
                "w_down": _dense_init(bk[7], (held, f, d), f ** -0.5),
            },
        })
    return {
        "embed": _dense_init(keys[0], (cfg.vocab_size, d), scale),
        "blocks": blocks,
        "ln_f": {"scale": ones()},
        "readout": _dense_init(keys[1], (d, cfg.vocab_size), scale),
    }


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
    the routing."""
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
    them (``cfg.held_experts``, ``_held_dense``).

    Args:
        x: (B, S, D) activations.
    Returns:
        the (B, S, D) output, and the router's sums over this layer's
        tokens for the auxiliary losses: ``claims`` (E,) how many tokens
        chose each expert, ``probs`` (E,) the sum of ``p[:, e]``, ``z``
        the sum of ``logsumexp(r) ** 2``; and ``held_claims``, how many of
        the N x K claims fell on an expert held here (all of them where
        every expert is).
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
        held_claims = N * K
    else:
        y, held_claims = _held_dense(cfg, p, tokens, weights, chosen)
        claims = jnp.zeros((E,), jnp.int32).at[chosen.reshape(N * K)].add(1)

    stats = {
        "claims": claims.astype(jnp.float32),
        "probs": jnp.sum(probs, axis=0),
        "z": jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2),
        "held_claims": jnp.asarray(held_claims, jnp.float32),
    }
    return y.reshape(B, S, D).astype(x.dtype), stats


def _block(
    cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array,
    kind: AttentionKind = AttentionKind(),
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    # the dense family's scope names (transformer._block) with the new
    # mechanisms nested in them: attn/qk_norm, attn/rope, mlp/moe/router,
    # mlp/moe/dispatch, mlp/moe/experts, mlp/moe/combine; a named kind of
    # layer puts its name between: attn/sliding/rope, attn/full/flash_fwd.
    # Metadata only.
    eps = cfg.rms_norm_eps
    of_kind = jax.named_scope(kind.name) if kind.name else contextlib.nullcontext()
    with jax.named_scope("attn"), of_kind:
        x = x + attention(cfg, p["attn"], _rmsnorm(x, p["ln1"]["scale"], eps), kind)
    with jax.named_scope("mlp"), jax.named_scope("moe"):
        y, stats = moe_layer(cfg, p["moe"], _rmsnorm(x, p["ln2"]["scale"], eps))
        return x + y, stats


def _hidden(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (B, S) int32 -> (the last block's output (B, S, D), the
    router's sums over every layer and token)."""
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
    total = None
    for kind, p in zip(cfg.kinds, params["blocks"]):
        x, stats = _block(cfg, p, x, kind)
        total = stats if total is None else jax.tree_util.tree_map(jnp.add, total, stats)
    return x, total


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
    sums over every layer and token)."""
    x, total = _hidden(cfg, params, tokens)
    with jax.named_scope("readout"):
        logits = _readout_product(cfg, params, x).astype(jnp.float32)
    return logits, total


def aux_losses(
    cfg: OlmoeConfig, stats: Dict[str, jax.Array], n_tokens: int
) -> Tuple[jax.Array, jax.Array]:
    """(balance loss, router z-loss) from the router's sums over
    ``n_tokens`` tokens a layer."""
    n = float(n_tokens * cfg.n_layers)
    balance = cfg.n_experts * jnp.sum((stats["claims"] / n) * (stats["probs"] / n))
    return balance, stats["z"] / n


def loss_fn(
    cfg: OlmoeConfig, params: Dict[str, Any], tokens: jax.Array
) -> jax.Array:
    """Next-token cross entropy + the two weighted router losses. The
    loss reads the readout's product in ``cfg.dtype``, unwidened
    (``next_token_loss``)."""
    inputs = tokens[:, :-1]
    x, stats = _hidden(cfg, params, inputs)
    with jax.named_scope("readout"):
        logits = _readout_product(cfg, params, x)
    with jax.named_scope("loss"), jax.named_scope("aux"):
        balance, z = aux_losses(cfg, stats, inputs.size)
    return (
        next_token_loss(logits, tokens[:, 1:])
        + cfg.balance_coef * balance + cfg.z_coef * z
    )
