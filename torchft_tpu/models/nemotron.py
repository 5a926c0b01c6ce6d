"""Nemotron 3 Nano (nvidia, ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``,
``model_type`` ``nemotron_h``; 2025-12): a hybrid sparse decoder whose every
layer is ONE sublayer - a Mamba-2 state-space mixer with grouped maps, a
grouped-query softmax attention WITHOUT a position signal, or a
sigmoid-routed mixture of UNGATED ``relu ** 2`` experts beside a shared one -
as a CONFIGURATION of the family in ``olmoe.py``. This file holds numbers and
no mathematics of the model: ``olmoe.init_params``, ``forward`` and
``loss_fn`` serve it, and ``make_train_step`` takes it as it takes OLMoE's.

With ``h`` the residual stream and ``N`` an RMSNorm with a learned scale
(``layer_norm_epsilon``): ``h_0 = E[tokens]``; layer ``i`` is ``h' = h +
Mixer_i(N_i(h))`` with ``Mixer_i`` by character ``i`` of
``hybrid_override_pattern`` - ``M``, ``*`` or ``E`` - and no second sublayer;
logits ``N_f(h_L) R^T`` with an untied readout ``R``; next-token cross
entropy in float32 plus the routers' balance term.

- *Mamba-2* (``M``; ``olmoe.mamba2_mixer``): ``mamba_num_heads`` heads of
  ``mamba_head_dim`` over a state of ``ssm_state_size``, ``n_groups`` groups
  of input and output maps (head ``h`` reads group ``h // (heads /
  n_groups)``), ``conv_kernel`` taps with a bias, chunks of ``chunk_size``,
  the gated RMSNorm's statistic a group's channels at a time. ``expand`` is
  not read: the inner width is heads x head_dim.
- *Attention* (``*``; ``olmoe.attention``): ``num_attention_heads`` query
  heads over ``num_key_value_heads`` key/value heads of ``head_dim``, no
  bias, no norm of q or k, NO rotary embedding, causal softmax at ``head_dim
  ** -0.5``.
- *Experts* (``E``; ``olmoe.moe_layer``): ``s = sigmoid(W_r u)`` over all
  ``n_routed_experts`` in float32; the ``num_experts_per_tok`` best on ``s +
  bias`` (``n_group`` = ``topk_group`` = 1: no group is closed); weights the
  chosen ``s`` (no bias) over their sum, times ``routed_scaling_factor``; an
  expert is ``W_down relu(W_up u) ** 2`` (``mlp_hidden_act`` ``relu2``: two
  matrices, no gate), the held experts' part of the sum plus one shared
  expert of ``moe_shared_expert_intermediate_size``, whole on every rank.
  The selection bias moves as ``models/ling.py`` says (``olmoe._bias_pull``,
  ``ling.bias_steps``).

What the catalog's row of the ``config.json`` leaves open is ``assumed`` and
listed, the first to doubt first, in
``benchmark/configs/nemotron3-nano-l9-ep16.json``.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence, Tuple

from .olmoe import (
    AttentionKind, Mamba2, OlmoeConfig, SigmoidRouter, forward, init_params, loss_fn,
)

__all__ = [
    "TINY_CONFIG", "forward", "init_params", "layer_kinds", "loss_fn", "nemotron_config",
    "sublayers", "tiny_nemotron_config",
]


def layer_kinds(
    config: Mapping[str, Any], layers: Sequence[int]
) -> Tuple[AttentionKind, ...]:
    """The program's kinds of the PUBLISHED layers ``layers`` by the
    pattern's characters: ``M`` the state-space mixer (scope ``attn/mamba``),
    ``*`` softmax attention with no rotation (``attn/nope``); an ``E`` layer
    has no mixer (``sublayers``) and its kind, the unnamed one, is not read."""
    mamba = AttentionKind("mamba", mixer=Mamba2(
        state=config["ssm_state_size"], conv_taps=config["conv_kernel"],
        chunk=config["chunk_size"], inner_heads=config["mamba_num_heads"],
        inner_head_dim=config["mamba_head_dim"], groups=config["n_groups"],
    ))
    kinds = {"M": mamba, "*": AttentionKind("nope", rotary=False), "E": AttentionKind()}
    return tuple(kinds[config["hybrid_override_pattern"][i]] for i in layers)


def sublayers(config: Mapping[str, Any], layers: Sequence[int]) -> Tuple[str, ...]:
    """The ONE sublayer each of the PUBLISHED layers ``layers`` is: its mixer
    (``M``, ``*``) or its feed-forward (``E``)."""
    return tuple(
        "ff" if config["hybrid_override_pattern"][i] == "E" else "mixer" for i in layers
    )


def nemotron_config(
    config: Mapping[str, Any], layers: Sequence[int],
    held_experts: Optional[Tuple[int, int]] = None, balance_coef: float = 0.0,
    recompute_layers: bool = False,
) -> OlmoeConfig:
    """The program's configuration from the keys of a Nemotron-H
    ``config.json`` (the catalog's row of the published one is copied whole
    into ``benchmark/configs/nemotron3-nano-l9-ep16.json``; the numbers live
    there and nowhere in this package) for the PUBLISHED layers ``layers``.
    ``n_routed_experts`` is the router's width and ``held_experts`` a rank's
    share of each sparse layer; the loss weight is no key of that file;
    ``recompute_layers`` is the deployment's, not the model's."""
    assert config["mlp_hidden_act"] == "relu2" and config["mamba_hidden_act"] == "silu"
    assert not config["attention_bias"] and not config["mlp_bias"] and not config["use_bias"]
    assert not config["mamba_proj_bias"] and config["use_conv_bias"]
    assert config["norm_topk_prob"] and config["n_shared_experts"] == 1
    assert not config["tie_word_embeddings"] and config["sliding_window"] is None
    assert set(config["hybrid_override_pattern"]) <= set("M*E")
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=len(layers),
        n_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),  # no layer rotates: unread
        rms_norm_eps=config["layer_norm_epsilon"],
        balance_coef=balance_coef,
        z_coef=0.0,
        qk_norm=False,
        renormalize_top_k=True,
        held_experts=held_experts,
        layer_kinds=layer_kinds(config, layers),
        sublayers=sublayers(config, layers),
        ff_activation="relu2",
        router=SigmoidRouter(
            groups=config["n_group"], kept=config["topk_group"],
            scale=float(config["routed_scaling_factor"]),
        ),
        shared_width=config["moe_shared_expert_intermediate_size"],
        recompute_layers=recompute_layers,
    )


# the keys of a ``config.json`` at a size for tests and CPU rehearsals: the
# pattern ``MEM*E``; Mamba 8 heads of 16 in 2 groups, a state of 16, chunks of
# 16; 4 query heads over 2 key/value heads of 16; 8 experts of width 24, 2 a
# token, a shared expert of 48
TINY_CONFIG = {
    "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000,
    "layer_norm_epsilon": 1e-5, "hybrid_override_pattern": "MEM*E",
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 16, "n_groups": 2, "mamba_hidden_act": "silu",
    "mamba_proj_bias": False, "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "mlp_bias": False, "use_bias": False, "attention_bias": False,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1, "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "tie_word_embeddings": False, "sliding_window": None,
}


def tiny_nemotron_config(
    held_experts: Optional[Tuple[int, int]] = (0, 2), recompute_layers: bool = False,
) -> OlmoeConfig:
    """``TINY_CONFIG``'s five layers as the program's configuration, 2 of
    the 8 experts held."""
    return nemotron_config(
        TINY_CONFIG, layers=range(5), held_experts=held_experts, balance_coef=1e-4,
        recompute_layers=recompute_layers,
    )
