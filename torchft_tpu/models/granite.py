"""Granite 4.0-H (ibm-granite, ``granite-4.0-h-micro``, ``model_type``
``granitemoehybrid``; 2025-10): a dense hybrid decoder - a Mamba-2
state-space mixer in nine layers of ten, grouped-query softmax attention
WITHOUT a position signal in the tenth, muP's four multipliers, a tied
readout - as a CONFIGURATION of the family in ``olmoe.py``. This file holds
numbers and no mathematics of the model: ``olmoe.init_params``, ``forward``
and ``loss_fn`` serve it, and ``make_train_step`` takes it as it takes
OLMoE's.

With ``h`` the residual stream, ``N`` an RMSNorm with a learned scale
(``rms_norm_eps``) and ``m_r`` = ``residual_multiplier``: ``h_0 =
embedding_multiplier x E[tokens]``; a layer ``a = h + m_r Mixer(N1(h))``,
``h' = a + m_r FF(N2(a))``; logits ``N_f(h_L) E^T / logits_scaling`` with
``E`` the tied embedding; next-token cross entropy in float32 and no
auxiliary term (``num_local_experts`` 0: every feed-forward is the
``shared_mlp``, one SwiGLU of ``shared_intermediate_size``).

- *Attention* (``layer_types[i] == "attention"``; ``olmoe.attention``):
  ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value
  heads of ``hidden_size / num_attention_heads``, no bias, no norm of q or k,
  NO rotary embedding (``position_embedding_type`` ``nope``), causal softmax of
  ``attention_multiplier x q.k`` - not ``head_dim ** -0.5``.
- *Mamba-2* (``"mamba"``; ``olmoe.mamba2_mixer``): ``mamba_n_heads`` heads of
  ``mamba_d_head``, a state of ``mamba_d_state``, ``mamba_n_groups`` 1,
  ``mamba_d_conv`` taps with a bias, chunks of ``mamba_chunk_size``.

What the catalog's row of the ``config.json`` leaves open is ``assumed``
and listed, the first to doubt first, in
``benchmark/configs/granite4-h-micro-l10-v8.json``.
"""

from __future__ import annotations

from typing import Any, Mapping, Sequence, Tuple

from .olmoe import AttentionKind, Mamba2, OlmoeConfig, forward, init_params, loss_fn

__all__ = [
    "TINY_CONFIG", "forward", "granite_config", "init_params", "layer_kinds", "loss_fn",
    "tiny_granite_config",
]


def layer_kinds(
    config: Mapping[str, Any], layers: Sequence[int]
) -> Tuple[AttentionKind, ...]:
    """The program's kinds of the PUBLISHED layers ``layers`` by
    ``layer_types``: the state-space mixer (scope ``attn/mamba``) or softmax
    attention with no rotation at ``attention_multiplier`` (``attn/nope``)."""
    mamba = AttentionKind("mamba", mixer=Mamba2(
        state=config["mamba_d_state"], conv_taps=config["mamba_d_conv"],
        chunk=config["mamba_chunk_size"], inner_heads=config["mamba_n_heads"],
        inner_head_dim=config["mamba_d_head"],
    ))
    nope = AttentionKind(
        "nope", rotary=False, softmax_scale=float(config["attention_multiplier"])
    )
    kinds = {"mamba": mamba, "attention": nope}
    return tuple(kinds[config["layer_types"][i]] for i in layers)


def granite_config(
    config: Mapping[str, Any], layers: Sequence[int], recompute_layers: bool = False,
) -> OlmoeConfig:
    """The program's configuration from the keys of a Granite 4.0-H
    ``config.json`` (the catalog's row of the published one is copied whole
    into ``benchmark/configs/granite4-h-micro-l10-v8.json``; the numbers live
    there and nowhere in this package) for the PUBLISHED layers ``layers``.
    ``recompute_layers`` is the deployment's, not the model's: whether a
    layer's activations are kept or computed again."""
    assert config["position_embedding_type"] == "nope" and not config["attention_bias"]
    assert config["num_local_experts"] == 0 and config["tie_word_embeddings"]
    assert config["mamba_n_groups"] == 1 and config["mamba_conv_bias"]
    assert not config["mamba_proj_bias"] and config["normalization_function"] == "rmsnorm"
    assert config["mamba_n_heads"] * config["mamba_d_head"] == (
        config["mamba_expand"] * config["hidden_size"]
    )
    assert config["hidden_act"] == "silu"
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        n_layers=len(layers),
        rope_theta=float(config["rope_theta"]),  # no layer rotates: unread
        rms_norm_eps=config["rms_norm_eps"],
        balance_coef=0.0, z_coef=0.0,
        qk_norm=False,
        layer_kinds=layer_kinds(config, layers),
        dense_ff=(config["shared_intermediate_size"],) * len(layers),
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        tied_readout=True,
        recompute_layers=recompute_layers,
    )


# the keys of a ``config.json`` at a size for tests and CPU rehearsals: two
# state-space layers, the attention layer, one more state-space layer; 4 query
# heads over 2 key/value heads of 16; Mamba 8 heads of 16, a state of 16,
# chunks of 16
TINY_CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "rope_theta": 10000, "rms_norm_eps": 1e-5,
    "shared_intermediate_size": 96, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_chunk_size": 16, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "hidden_act": "silu", "position_embedding_type": "nope",
    "attention_bias": False, "num_local_experts": 0, "tie_word_embeddings": True,
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 8,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
}


def tiny_granite_config(recompute_layers: bool = False) -> OlmoeConfig:
    """``TINY_CONFIG``'s four layers as the program's configuration."""
    return granite_config(TINY_CONFIG, layers=range(4), recompute_layers=recompute_layers)
