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
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops import flash_attention
from .transformer import _dense_init, _rmsnorm, next_token_loss


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

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def tiny_olmoe_config() -> OlmoeConfig:
    """Small config for tests and CPU rehearsals."""
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, n_experts=8,
        experts_per_token=2, expert_width=32,
    )


def init_params(cfg: OlmoeConfig, key: jax.Array) -> Dict[str, Any]:
    """f32 master params; matmuls cast to cfg.dtype at use."""
    d, f, e = cfg.d_model, cfg.expert_width, cfg.n_experts
    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = d ** -0.5

    def ones() -> jax.Array:
        return jnp.ones((d,), jnp.float32)

    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[2 + i], 8)
        blocks.append({
            "ln1": {"scale": ones()},
            "attn": {
                "wq": _dense_init(bk[0], (d, d), scale),
                "wk": _dense_init(bk[1], (d, d), scale),
                "wv": _dense_init(bk[2], (d, d), scale),
                "wo": _dense_init(bk[3], (d, d), scale),
                "q_norm": ones(),
                "k_norm": ones(),
            },
            "ln2": {"scale": ones()},
            "moe": {
                "router": _dense_init(bk[4], (d, e), scale),
                "w_gate": _dense_init(bk[5], (e, d, f), scale),
                "w_up": _dense_init(bk[6], (e, d, f), scale),
                "w_down": _dense_init(bk[7], (e, f, d), f ** -0.5),
            },
        })
    return {
        "embed": _dense_init(keys[0], (cfg.vocab_size, d), scale),
        "blocks": blocks,
        "ln_f": {"scale": ones()},
        "readout": _dense_init(keys[1], (d, cfg.vocab_size), scale),
    }


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of ``x`` (B, S, H, head_dim) at positions 0..S-1:
    the pair (``i``, ``i + head_dim / 2``) turns by ``pos * theta ** (-2 i
    / head_dim)``. Computed in float32, rounded once."""
    S, half = x.shape[1], x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq  # (S, half)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return turned.astype(x.dtype)


def attention(cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    B, S, D = x.shape
    q, k, v = (x @ p[w].astype(cfg.dtype) for w in ("wq", "wk", "wv"))
    with jax.named_scope("qk_norm"):
        q = _rmsnorm(q, p["q_norm"], cfg.rms_norm_eps)
        k = _rmsnorm(k, p["k_norm"], cfg.rms_norm_eps)
    q, k, v = (t.reshape(B, S, cfg.n_heads, cfg.head_dim) for t in (q, k, v))
    with jax.named_scope("rope"):
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    # the fused kernel everywhere: compiled on a TPU, interpreted elsewhere
    out = flash_attention(q, k, v)
    return out.reshape(B, S, D) @ p["wo"].astype(cfg.dtype)


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


def moe_layer(
    cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dropless top-K routed SwiGLU experts.

    Args:
        x: (B, S, D) activations.
    Returns:
        the (B, S, D) output, and the router's sums over this layer's
        tokens for the auxiliary losses: ``claims`` (E,) how many tokens
        chose each expert, ``probs`` (E,) the sum of ``p[:, e]``, ``z``
        the sum of ``logsumexp(r) ** 2``.
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
        weights, chosen = jax.lax.top_k(probs, K)  # (N, K), not renormalised

    with jax.named_scope("dispatch"):
        # claims are numbered token-major (claim c belongs to token c // K);
        # sorted by expert, each expert's rows are one contiguous group
        expert_of_claim = chosen.reshape(N * K)
        order = jnp.argsort(expert_of_claim, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.zeros((E,), jnp.int32).at[expert_of_claim].add(1)
        rows = _to_claims(tokens, order, inverse, K)  # (N * K, D)

    with jax.named_scope("experts"):
        def grouped(lhs: jax.Array, w: jax.Array) -> jax.Array:
            return jax.lax.ragged_dot(lhs, w.astype(cfg.dtype), group_sizes)

        hidden = jax.nn.silu(grouped(rows, p["w_gate"])) * grouped(rows, p["w_up"])
        out_rows = grouped(hidden, p["w_down"])  # (N * K, D)

    with jax.named_scope("combine"):
        back = _unsort(out_rows, order, inverse).reshape(N, K, D)
        y = jnp.einsum(
            "nkd,nk->nd", back, weights, preferred_element_type=jnp.float32
        )

    stats = {
        "claims": group_sizes.astype(jnp.float32),
        "probs": jnp.sum(probs, axis=0),
        "z": jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return y.reshape(B, S, D).astype(x.dtype), stats


def _block(
    cfg: OlmoeConfig, p: Dict[str, Any], x: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    # the dense family's scope names (transformer._block) with the new
    # mechanisms nested in them: attn/qk_norm, attn/rope, mlp/moe/router,
    # mlp/moe/dispatch, mlp/moe/experts, mlp/moe/combine. Metadata only.
    eps = cfg.rms_norm_eps
    with jax.named_scope("attn"):
        x = x + attention(cfg, p["attn"], _rmsnorm(x, p["ln1"]["scale"], eps))
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
    for p in params["blocks"]:
        x, stats = _block(cfg, p, x)
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
