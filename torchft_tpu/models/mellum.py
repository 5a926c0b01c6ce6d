"""Mellum2 (JetBrains, ``Mellum2-12B-A2.5B``): a sparse decoder whose
layers alternate three of sliding-window attention with one of full
attention, as a CONFIGURATION of the sparse family in ``olmoe.py``. This
file holds no mathematics: ``olmoe.init_params``, ``forward``
and ``loss_fn`` serve it, and ``make_train_step`` takes it as it takes
OLMoE's.

For activations ``x`` (B, S, 2304), per layer, pre-norm, RMSNorm eps
1e-6, no biases: ``h = x + Attn_l(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``.

- ``Attn_l``: 32 query heads over 4 key/value heads of 128 (32 x 128 =
  4096 columns, not the model's 2304); RMSNorm of q and of k over each
  head's 128 with a learned scale; rotary embedding, rotate-half pairing,
  base 500,000 - the plain frequencies on ``sliding_attention`` layers,
  YaRN's blend (factor 16 over 8,192 original positions, beta 32 and 1,
  cos and sin times 1.2772588722239782) on ``full_attention`` ones; query
  head ``h`` meets key/value head ``h // 8``; causal scores ``q.k /
  sqrt(128)``, on sliding layers also ``q_pos - k_pos < 1024``.
- ``MoE``: softmax over 64 router logits in float32, the 8 largest kept
  and divided by their sum; SiLU-gated experts of width 896; no shared
  expert. Every layer is sparse.
- Embedding without a position table, final RMSNorm, untied readout.
  Loss: next-token cross entropy + ``router_aux_loss_coef`` x the balance
  loss, no z-loss.

A rank of an expert-parallel deployment holds ``held_experts`` of each
layer (``olmoe._held_share``) and its rows of the vocabulary.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence, Tuple

from .olmoe import AttentionKind, OlmoeConfig, Yarn, forward, init_params, loss_fn

__all__ = [
    "forward", "init_params", "layer_kinds", "loss_fn", "mellum2_config",
    "tiny_mellum_config",
]


def layer_kinds(
    layer_types: Sequence[str], window: int, yarn: Yarn
) -> Tuple[AttentionKind, ...]:
    """The program's kinds from a config.json's ``layer_types``:
    ``sliding_attention`` is the window with the plain frequencies (scope
    ``attn/sliding``), ``full_attention`` every key with ``yarn``'s
    (``attn/full``)."""
    kinds = {
        "sliding_attention": AttentionKind("sliding", window),
        "full_attention": AttentionKind("full", None, yarn),
    }
    return tuple(kinds[t] for t in layer_types)


def mellum2_config(
    config: Mapping[str, Any], held_experts: Optional[Tuple[int, int]] = None,
    balance_coef: float = 0.001, z_coef: float = 0.0,
) -> OlmoeConfig:
    """The program's configuration from the keys of a Mellum2
    ``config.json`` (the published one is copied whole into
    ``benchmark/configs/mellum2-12b-a2.5b-l4-ep8.json``; the numbers live
    there and nowhere in this package): ``num_experts`` is the router's
    width, the layers are the first ``num_hidden_layers`` of
    ``layer_types``, and ``held_experts`` a rank's share of each. The loss
    weights are no key of that file."""
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default"
    assert full["rope_theta"] == sliding["rope_theta"]
    assert set(config["mlp_layer_types"]) == {"sparse"} and config["norm_topk_prob"]
    yarn = Yarn(
        factor=float(full["factor"]),
        original_positions=full["original_max_position_embeddings"],
        beta_fast=float(full["beta_fast"]), beta_slow=float(full["beta_slow"]),
        attention_factor=full["attention_factor"],
    )
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=config["num_hidden_layers"],
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rope_theta=float(sliding["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        balance_coef=balance_coef,
        z_coef=z_coef,
        qk_norm_per_head=True,
        renormalize_top_k=True,
        held_experts=held_experts,
        layer_kinds=layer_kinds(
            config["layer_types"][:config["num_hidden_layers"]],
            config["sliding_window"], yarn,
        ),
    )


def tiny_mellum_config(
    held_experts: Optional[Tuple[int, int]] = (0, 2)
) -> OlmoeConfig:
    """Small config for tests and CPU rehearsals: one period of the two
    kinds, 4 query heads over 2 key/value heads of 32 (128 columns for a
    model width of 64), a window of 16, 2 of 8 experts held."""
    yarn = Yarn(factor=16.0, original_positions=64, beta_fast=4.0,
                beta_slow=1.0, attention_factor=1.2772588722239782)
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32,
        n_layers=4, n_experts=8, experts_per_token=2, expert_width=32,
        rope_theta=10000.0, rms_norm_eps=1e-6, balance_coef=0.001, z_coef=0.0,
        qk_norm_per_head=True, renormalize_top_k=True,
        held_experts=held_experts,
        layer_kinds=layer_kinds(
            ("sliding_attention",) * 3 + ("full_attention",), 16, yarn
        ),
    )
