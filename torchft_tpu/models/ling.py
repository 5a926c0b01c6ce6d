"""Ling 3.0 (inclusionAI, ``Ling-3.0-flash``, ``model_type``
``bailing_hybrid``): a sparse decoder whose layers come in groups of six,
five of Kimi Delta Attention (Kimi Linear, arXiv:2510.26692) and one of
latent attention (DeepSeek-V2's MLA, arXiv:2405.04434), with DeepSeek-V3's
expert layer (arXiv:2412.19437: a sigmoid router under a selection bias that
no gradient owns, group-limited top-k, a shared expert) after leading dense
layers - as a CONFIGURATION of the sparse family in ``olmoe.py``. This file
holds no mathematics of the model: ``olmoe.init_params``, ``forward`` and
``loss_fn`` serve it, and ``make_train_step`` takes it as it takes OLMoE's.
What it has of its own is the optimizer's part of the selection bias
(``bias_steps``).

Pre-norm residual blocks, RMSNorm eps 1e-6, no biases: ``x = x +
Mixer(N1(x))``, ``x = x + FF(N2(x))``. Published layer ``i`` is MLA where
``(i + 1) % layer_group_size == 0``, else KDA; FF is one dense SwiGLU in the
first ``first_k_dense_replace`` layers, else the expert layer. With ``u`` the
normed input and ``H`` heads of 128:

- *KDA* (``olmoe.kda_mixer``): ``q, k, v = SiLU(conv4(W u))``, ``conv4`` a
  depthwise causal convolution of 4 taps; ``q`` and ``k`` divided by their L2
  norm a head, ``q`` times ``128 ** -0.5``; a log-decay a CHANNEL of the key,
  ``g = -5 sigmoid(exp(A_h) (W_f u + b))``, and a step ``beta = sigmoid(W_b
  u)`` a head; the state ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1}
  + beta_t k_t v_t^T``, ``o_t = S_t^T q_t`` (``ops/delta_rule.py``, in
  chunks); ``y = W_o (RMSNorm_head(o) * sigmoid(W_g u))``, the gate a channel.
- *MLA* (``olmoe.mla_mixer``): ``q = W_q u`` (192 a head: 128 unrotated, 64
  rotated); ``[c, k_r] = W_kva u`` (512 + 64), ``c`` normed, ``[k_nope, v] =
  W_kvb c`` (128 + 128 a head), ``k = [k_nope, k_r]`` with the one ``k_r``
  for every head; RMSNorm of q and of k over each head's 192 with a learned
  scale, then the rotary embedding of the last 64 in interleaved pairs, base
  6e6; causal softmax attention at ``192 ** -0.5``; ``y = W_o (o *
  sigmoid(W_gate u))``, the gate one number a head.
- *Experts* (``olmoe.moe_layer``): ``s = sigmoid(W_r u)`` over all 512 in
  float32; chosen on ``s + bias``: the 512 in 8 groups, a group's mark the sum
  of its two best, the best 4 groups kept, the 8 best inside them; weights the
  chosen ``s`` (no bias) divided by their sum, times 2.5; the held experts'
  part of the sum plus one shared SwiGLU expert, whole on every rank.
- *The bias* gets no gradient from the loss: after a step it moves against
  its expert's excess load, ``bias_e -= gamma sign(load_e - mean load)``. The
  loss hands the excess load out AS the bias's gradient
  (``olmoe._bias_pull``), so it crosses replica groups, is averaged, voted on
  and applied or dropped with the step like any gradient, and the bias is a
  leaf of the parameters; ``bias_steps`` is the optimizer that turns that
  gradient into the step above and gives every other leaf to the caller's.
- Loss: next-token cross entropy + a balance loss over the routers' summed
  scores (``assumed`` in the configuration's file); no z-loss. The config's
  one multi-token-prediction module is left out: its published loss weight
  (``mtp_loss_scaling_factor``) is 0.

What the ``config.json`` leaves open is ``assumed`` and listed, the first to
doubt first, in ``benchmark/configs/ling3-flash-l6-ep64.json``.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from .olmoe import (
    AttentionKind, Kda, Mla, OlmoeConfig, SigmoidRouter, forward, init_params, loss_fn,
)

__all__ = [
    "bias_steps", "forward", "init_params", "is_selection_bias", "layer_kinds",
    "ling_config", "loss_fn", "tiny_ling_config",
]


def layer_kinds(
    config: Mapping[str, Any], layers: Sequence[int]
) -> Tuple[AttentionKind, ...]:
    """The program's kinds of the PUBLISHED layers ``layers``: the last of
    every ``layer_group_size`` is latent attention (scope ``attn/mla``), the
    others KDA (``attn/kda``)."""
    kda = AttentionKind("kda", mixer=Kda(
        taps=config["short_conv_kernel_size"], floor=float(config["kda_lower_bound"]),
    ))
    mla = AttentionKind("mla", mixer=Mla(
        latent=config["kv_lora_rank"], rope_dim=config["qk_rope_head_dim"],
    ))
    return tuple(mla if (i + 1) % config["layer_group_size"] == 0 else kda for i in layers)


def ling_config(
    config: Mapping[str, Any], layers: Sequence[int],
    held_experts: Optional[Tuple[int, int]] = None,
    held_heads: Optional[int] = None, balance_coef: float = 0.0,
) -> OlmoeConfig:
    """The program's configuration from the keys of a Ling 3.0
    ``config.json`` (the published one is copied whole into
    ``benchmark/configs/ling3-flash-l6-ep64.json``; the numbers live there
    and nowhere in this package) for the PUBLISHED layers ``layers`` (a
    layer is dense or sparse, KDA or MLA, by its published index).
    ``num_experts`` is the router's width and ``held_experts`` a rank's share
    of each layer; ``held_heads`` how many of the ``num_attention_heads`` a
    rank holds (all). The loss weight is no key of that file."""
    assert config["score_function"] == "sigmoid" and config["topk_method"] == "noaux_tc"
    assert config["moe_router_enable_expert_bias"] and config["norm_topk_prob"]
    assert config["q_lora_rank"] is None and config["rope_interleave"]
    assert config["kda_safe_gate"] and config["no_kda_lora"] and config["linear_silu"]
    assert config["num_shared_experts"] == 1 and not config["tie_word_embeddings"]
    assert config["qk_nope_head_dim"] == config["v_head_dim"] == config["head_dim"]
    assert config["use_qk_norm"] and config["group_norm_size"] == 1
    assert config["gated_attention_proj_granularity_type"] == "head_wise"
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=held_heads or config["num_attention_heads"],
        head_dim=config["head_dim"],
        n_layers=len(layers),
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        balance_coef=balance_coef,
        z_coef=0.0,
        renormalize_top_k=True,
        held_experts=held_experts,
        layer_kinds=layer_kinds(config, layers),
        dense_ff=tuple(
            config["intermediate_size"] if i < config["first_k_dense_replace"] else None
            for i in layers
        ),
        router=SigmoidRouter(
            groups=config["n_group"], kept=config["topk_group"],
            scale=float(config["routed_scaling_factor"]),
        ),
        shared_width=config["moe_shared_expert_intermediate_size"],
    )


def tiny_ling_config(
    held_experts: Optional[Tuple[int, int]] = (0, 4), held_heads: int = 2,
) -> OlmoeConfig:
    """Small config for tests and CPU rehearsals: a dense KDA layer, a
    sparse KDA layer and a sparse MLA layer, 2 heads of 32 (24 + 8 rotated
    in MLA, a latent of 16), 4 of 16 experts held, in 4 groups of which 2
    are kept, 2 a token."""
    kda = AttentionKind("kda", mixer=Kda())
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=held_heads, head_dim=32, n_layers=3,
        n_experts=16, experts_per_token=2, expert_width=32, rope_theta=10000.0,
        rms_norm_eps=1e-6, balance_coef=1e-4, z_coef=0.0, renormalize_top_k=True,
        held_experts=held_experts,
        layer_kinds=(kda, kda, AttentionKind("mla", mixer=Mla(latent=16, rope_dim=8))),
        dense_ff=(96, None, None), router=SigmoidRouter(groups=4, kept=2, scale=2.5),
        shared_width=32,
    )


def is_selection_bias(path: Tuple[Any, ...]) -> bool:
    """Whether the leaf at ``path`` of a parameter tree (``jax.tree_util``'s
    key path) is a router's selection bias: ``blocks[i]["moe"]["bias"]``."""
    keys = [getattr(k, "key", None) for k in path]
    return keys[-2:] == ["moe", "bias"]


def bias_steps(tx: optax.GradientTransformation, gamma: float = 1e-3) -> optax.GradientTransformation:
    """The optimizer of a model with selection biases: every bias leaf
    steps by ``-gamma sign(g)``, ``g`` its expert's excess load as the
    gradient tree carries it (``olmoe._bias_pull``), with no moment and no
    decay; every other leaf is ``tx``'s. Handed to ``FTTrainState`` like any
    optax transformation: the bias moves with the committed update and not
    on an abort, and its state is none."""
    def labels(params: Any) -> Any:
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "bias" if is_selection_bias(path) else "rest", params
        )

    sign_step = optax.stateless(
        lambda updates, _: jax.tree_util.tree_map(lambda g: -gamma * jnp.sign(g), updates)
    )
    return optax.multi_transform({"bias": sign_step, "rest": tx}, labels)
