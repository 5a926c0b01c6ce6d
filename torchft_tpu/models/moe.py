"""Mixture-of-Experts transformer: the expert-parallel (EP) model family.

TPU-first MoE in the GShard/Switch mold — everything is static-shaped and
einsum-dispatched so XLA can tile it onto the MXU and insert the
all-to-alls from sharding annotations alone:

- router: top-k gating over ``n_experts`` with a capacity cap per expert
  (tokens over capacity are dropped — their combine weight is zero — the
  standard static-shape TPU trade),
- dispatch/combine are dense one-hot einsums (no gather/scatter, no
  dynamic shapes),
- expert weights are stacked ``(E, ...)`` and sharded over an ``expert``
  mesh axis (P("expert", ...)); the dispatched activations are
  sharding-constrained to the same axis, so GSPMD materializes the
  token->expert all-to-all over ICI — no hand-written collectives,
- the load-balance auxiliary loss (mean gate fraction x mean routed
  fraction, scaled by E) keeps routing from collapsing.

Composes with the rest of the parallel stack: the ``expert`` axis lives
inside a replica group's slice mesh next to ``data``/``model`` axes, and
the cross-replica-group fault-tolerance dimension stays host-side exactly
as for the dense flagship (SURVEY.md §2.3: the intra-group mesh is opaque
to the FT layer — reference process_group.py:1310-1341 leaves intra-group
dims to the user; here EP is a first-class intra-group option).

The reference has no MoE/EP anywhere (SURVEY.md §2.3 "EP: absent").
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .transformer import (
    TransformerConfig,
    _attention,
    _dense_init,
    _rmsnorm,
    attn_sublayer_init,
    attn_sublayer_specs,
    backbone_init,
    backbone_specs,
    embed_tokens,
    mlp_apply,
    mlp_init,
    mlp_specs,
    next_token_loss,
    readout,
    remat_wrap,
)


@dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    router_k: int = 2          # experts per token
    capacity_factor: float = 1.25
    aux_coef: float = 1e-2     # load-balance loss weight
    # every block's MLP is an MoE layer when True; else alternate blocks
    # (dense, moe, dense, ...) like most production MoE stacks
    moe_every_block: bool = False

    def capacity(self, n_tokens: int) -> int:
        cap = int(self.capacity_factor * self.router_k * n_tokens
                  / self.n_experts)
        return max(1, min(cap, n_tokens))

    def is_moe_block(self, i: int) -> bool:
        return self.moe_every_block or (i % 2 == 1)


def tiny_moe_config() -> MoEConfig:
    return MoEConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=128, n_experts=4, router_k=2,
    )


def init_params(cfg: MoEConfig, key: jax.Array) -> Dict[str, Any]:
    """Same skeleton as the dense flagship; MoE blocks carry stacked
    expert weights + a router instead of a single MLP."""
    keys = jax.random.split(key, 2 + cfg.n_layers)
    scale = cfg.d_model ** -0.5

    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[2 + i], 5)
        block = attn_sublayer_init(cfg, bk[0], bk[1])
        if cfg.is_moe_block(i):
            block["moe"] = {
                "router": _dense_init(
                    bk[4], (cfg.d_model, cfg.n_experts), scale
                ),
                "wi": _dense_init(
                    bk[2], (cfg.n_experts, cfg.d_model, cfg.d_ff), scale
                ),
                "wo": _dense_init(
                    bk[3], (cfg.n_experts, cfg.d_ff, cfg.d_model),
                    cfg.d_ff ** -0.5,
                ),
            }
        else:
            block["mlp"] = mlp_init(cfg, bk[2], bk[3])
        blocks.append(block)
    params = backbone_init(cfg, keys[0], keys[1])
    params["blocks"] = blocks
    return params


def param_sharding_rules(cfg: MoEConfig) -> Dict[str, Any]:
    """Experts over the ``expert`` axis, their inner dims over ``model``
    (EP x TP); dense layers Megatron-style as in the flagship."""
    blocks = []
    for i in range(cfg.n_layers):
        block = attn_sublayer_specs()
        if cfg.is_moe_block(i):
            block["moe"] = {
                "router": P(),
                "wi": P("expert", None, "model"),
                "wo": P("expert", "model", None),
            }
        else:
            block["mlp"] = mlp_specs()
        blocks.append(block)
    rules = backbone_specs()
    rules["blocks"] = blocks
    return rules


def _constraint(x: jax.Array, cfg: MoEConfig, spec: P) -> jax.Array:
    # cp_mesh doubles as the EP mesh, but it may be a CP/TP-only mesh
    # (flash/ring attention) with no "expert" axis — then EP constraints
    # are skipped and the experts stay replicated.
    if cfg.cp_mesh is not None and all(
        ax is None or ax in cfg.cp_mesh.axis_names for ax in spec
    ):
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(cfg.cp_mesh, spec)
        )
    return x


def moe_layer(
    cfg: MoEConfig, p: Dict[str, Any], x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed expert MLP.

    Args:
        x: (B, S, D) activations.
    Returns:
        ((B, S, D) output, scalar load-balance aux loss).
    """
    B, S, D = x.shape
    N = B * S
    E, K = cfg.n_experts, cfg.router_k
    C = cfg.capacity(N)
    tokens = x.reshape(N, D)

    # Router in f32 for a stable softmax.
    logits = (tokens.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)  # (N, K)

    # Position of each (token, k) routing choice within its expert's
    # capacity buffer: running count of earlier claims on that expert.
    # one_hot: (N, K, E); claims are ordered token-major then k.
    one_hot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    flat = one_hot.reshape(N * K, E)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(N, K, E)
    pos_in_expert = jnp.sum(pos * one_hot, axis=-1)  # (N, K)
    keep = pos_in_expert < C  # over-capacity claims dropped

    # Renormalize the kept gates so each token's weights sum to 1.
    gates = gate_vals * keep
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # combine[n, e, c] = gate weight of token n in slot c of expert e
    slot_oh = jax.nn.one_hot(
        pos_in_expert.astype(jnp.int32), C, dtype=jnp.float32
    ) * keep[..., None]  # (N, K, C)
    combine = jnp.einsum("nke,nkc->nec", one_hot * gates[..., None], slot_oh)
    dispatch = jnp.einsum(
        "nke,nkc->nec", one_hot, slot_oh
    )  # 0/1 dispatch mask

    # Token -> expert all-to-all: the dispatched activations are
    # constrained onto the expert axis; GSPMD inserts the collective.
    xe = jnp.einsum(
        "nec,nd->ecd", dispatch.astype(cfg.dtype), tokens.astype(cfg.dtype)
    )
    xe = _constraint(xe, cfg, P("expert", None, None))
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"].astype(cfg.dtype))
    h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(cfg.dtype))
    ye = _constraint(ye, cfg, P("expert", None, None))
    out = jnp.einsum("nec,ecd->nd", combine.astype(cfg.dtype), ye)

    # Switch-style load balance: E * sum_e (token fraction routed to e) *
    # (mean router prob of e); minimized by the uniform router.
    frac_routed = jnp.mean(one_hot[:, 0, :], axis=0)  # top-1 assignment
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_routed * mean_prob)
    return out.reshape(B, S, D).astype(x.dtype), aux


def _block(
    cfg: MoEConfig, i: int, p: Dict[str, Any], x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    # the dense family's scope names (transformer._block): metadata only
    with jax.named_scope("attn"):
        x = x + _attention(cfg, p["attn"], _rmsnorm(x, p["ln1"]["scale"]))
    with jax.named_scope("mlp"):
        h = _rmsnorm(x, p["ln2"]["scale"])
        if cfg.is_moe_block(i):
            y, aux = moe_layer(cfg, p["moe"], h)
            return x + y, aux
        return x + mlp_apply(cfg, p["mlp"], h), jnp.float32(0.0)


def forward(
    cfg: MoEConfig, params: Dict[str, Any], tokens: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) int32 -> (logits (B, S, vocab) f32, aux loss)."""
    x = embed_tokens(cfg, params, tokens)
    aux_total = jnp.float32(0.0)
    block = remat_wrap(cfg, _block, static_argnums=(0, 1))
    for i, p in enumerate(params["blocks"]):
        x, aux = block(cfg, i, p, x)
        aux_total = aux_total + aux
    return readout(cfg, params, x), aux_total


def loss_fn(
    cfg: MoEConfig, params: Dict[str, Any], tokens: jax.Array
) -> jax.Array:
    """Next-token cross entropy + load-balance aux."""
    logits, aux = forward(cfg, params, tokens[:, :-1])
    return next_token_loss(logits, tokens[:, 1:]) + cfg.aux_coef * aux
