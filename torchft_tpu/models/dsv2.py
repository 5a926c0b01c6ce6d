"""DeepSeek-V2 (deepseek-ai, ``DeepSeek-V2-Lite``, ``model_type``
``deepseek_v2``; arXiv:2405.04434): a sparse decoder whose EVERY layer's
mixer is latent attention (MLA) - as a CONFIGURATION of the sparse family
in ``olmoe.py``. This file holds numbers and no mathematics of the model:
``olmoe.init_params``, ``forward`` and ``loss_fn`` serve it, and
``make_train_step`` takes it as it takes OLMoE's.

Pre-norm residual blocks, RMSNorm eps 1e-6, no biases: ``x = x +
MLA(N1(x))``, ``x = x + FF(N2(x))``; FF is one dense SwiGLU in the first
``first_k_dense_replace`` published layers, else the expert layer. With
``u`` the normed input and ``H`` heads:

- *MLA* (``olmoe.mla_mixer``), ``q_lora_rank`` null: ``q = W_q u``
  (``qk_nope_head_dim + qk_rope_head_dim`` a head); ``[c, k_r] = W_kva u``
  (``kv_lora_rank + qk_rope_head_dim``), ``c`` normed, ``[k_nope, v] = W_kvb
  c`` a head, ``k = [k_nope, k_r]`` with the one ``k_r`` for every head. NO
  norm of q or k and NO output gate (``Mla(qk_norm=False, gated=False)``).
  The rotary embedding of the last ``qk_rope_head_dim`` in interleaved
  pairs (the published code de-interleaves and rotates halves: the same
  scores) at YaRN's blend of the frequencies (``rope_scaling``), cos and
  sin times ``m(mscale) / m(mscale_all_dim)`` with ``m(s) = 0.1 s
  ln(factor) + 1``; causal softmax attention at ``(nope + rope) ** -0.5 x
  m(mscale_all_dim) ** 2``; ``y = W_o o``.
- *Experts* (``olmoe.moe_layer``): ``p = softmax(W_r u)`` over all
  ``n_routed_experts`` in float32, the ``num_experts_per_tok`` largest kept
  with their values as weights, NOT renormalised (``norm_topk_prob``
  false), times ``routed_scaling_factor`` (1); the held experts' part of
  the sum plus ``n_shared_experts`` shared SwiGLUs of the experts' width,
  which are one SwiGLU of their summed width (``shared_width``), whole on
  every rank.
- Loss: next-token cross entropy + ``aux_loss_alpha`` x the balance loss a
  sequence and a layer (``seq_aux``: ``olmoe.aux_losses`` under
  ``seq_balance``); no z-loss.

What the catalog's row of the ``config.json`` leaves open is ``assumed``
and listed, the first to doubt first, in
``benchmark/configs/dsv2-lite-l5-ep8.json``.
"""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional, Sequence, Tuple

from .olmoe import AttentionKind, Mla, OlmoeConfig, Yarn, forward, init_params, loss_fn

__all__ = [
    "dsv2_config", "forward", "init_params", "latent_kind", "loss_fn", "tiny_dsv2_config",
]


def latent_kind(config: Mapping[str, Any]) -> AttentionKind:
    """Every layer's kind (scope ``attn/mla``) from the keys of a
    DeepSeek-V2 ``config.json``: the latent's and the rotated key's widths,
    YaRN's four numbers, and the two factors the ``rope_scaling`` section's
    ``mscale`` and ``mscale_all_dim`` stand for."""
    scaling = config["rope_scaling"]
    assert scaling["type"] == "yarn"

    def m(s: float) -> float:
        return 0.1 * s * math.log(scaling["factor"]) + 1.0

    return AttentionKind(
        "mla",
        yarn=Yarn(
            factor=float(scaling["factor"]),
            original_positions=scaling["original_max_position_embeddings"],
            beta_fast=float(scaling["beta_fast"]), beta_slow=float(scaling["beta_slow"]),
            attention_factor=m(scaling["mscale"]) / m(scaling["mscale_all_dim"]),
        ),
        mixer=Mla(
            latent=config["kv_lora_rank"], rope_dim=config["qk_rope_head_dim"],
            qk_norm=False, gated=False, softmax_factor=m(scaling["mscale_all_dim"]) ** 2,
        ),
    )


def dsv2_config(
    config: Mapping[str, Any], layers: Sequence[int],
    held_experts: Optional[Tuple[int, int]] = None, aux_alpha: float = 0.0,
) -> OlmoeConfig:
    """The program's configuration from the keys of a DeepSeek-V2
    ``config.json`` (the catalog's row of the published one is copied whole
    into ``benchmark/configs/dsv2-lite-l5-ep8.json``; the numbers live there
    and nowhere in this package) for the PUBLISHED layers ``layers`` (a
    layer is dense or sparse by its published index). ``n_routed_experts``
    is the router's width and ``held_experts`` a rank's share of each
    layer. The balance loss's weight is a key the catalog's row leaves out."""
    assert config["q_lora_rank"] is None and not config["attention_bias"]
    assert config["scoring_func"] == "softmax" and config["topk_method"] == "greedy"
    assert config["n_group"] == config["topk_group"] == config["moe_layer_freq"] == 1
    assert not config["norm_topk_prob"] and config["routed_scaling_factor"] == 1
    assert config["seq_aux"] and not config["tie_word_embeddings"]
    assert config["qk_nope_head_dim"] == config["v_head_dim"]
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["v_head_dim"],
        n_layers=len(layers),
        n_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        balance_coef=aux_alpha,
        z_coef=0.0,
        held_experts=held_experts,
        layer_kinds=(latent_kind(config),) * len(layers),
        dense_ff=tuple(
            config["intermediate_size"] if i < config["first_k_dense_replace"] else None
            for i in layers
        ),
        shared_width=config["n_shared_experts"] * config["moe_intermediate_size"],
        seq_balance=True,
    )


def tiny_dsv2_config(held_experts: Optional[Tuple[int, int]] = (0, 4)) -> OlmoeConfig:
    """Small config for tests and CPU rehearsals: a dense layer and two
    sparse ones, every mixer latent attention - 2 heads of 32 + 8 rotated, a
    latent of 16, YaRN by 4 over 16 original positions - 4 of 16 experts
    held, 3 a token, two shared experts of 32."""
    kind = latent_kind({"kv_lora_rank": 16, "qk_rope_head_dim": 8, "rope_scaling": {
        "type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
        "beta_fast": 4, "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
    }})
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=2, head_dim=32, n_layers=3,
        n_experts=16, experts_per_token=3, expert_width=32, rope_theta=10000.0,
        rms_norm_eps=1e-6, balance_coef=1e-3, z_coef=0.0, held_experts=held_experts,
        layer_kinds=(kind,) * 3, dense_ff=(96, None, None), shared_width=64,
        seq_balance=True,
    )
