"""SDAR (JetLM, ``SDAR-30B-A3B-Chat``; "SDAR: A Synergistic Diffusion-
AutoRegression Paradigm for Scalable Sequence Generation", arXiv:2510.06303;
its training objective is block diffusion, BD3-LM, Arriola et al.,
arXiv:2503.09573): a Qwen3-MoE decoder trained to denoise blocks, as a
CONFIGURATION of the sparse family in ``olmoe.py``. This file holds no
mathematics: ``olmoe.init_params``, ``forward`` and ``loss_fn`` serve it, and
``make_train_step`` takes it as it takes OLMoE's.

*The network* (the ``config.json``'s keys are Qwen3-MoE's): ``h = E[x]``
(151,936 x 2,048, no position table); every layer ``a = x + Attn(N1(x))``,
``y = a + MoE(N2(a))``, RMSNorm eps 1e-6 with learned scales; logits
``N_f(h) W_out``, untied.

- ``Attn``: q 2,048 -> 32 heads of 128, k and v 2,048 -> 4 heads of 128, no
  bias; RMSNorm of q and of k over each head's 128 with one learned scale
  each, BEFORE the rotary embedding (rotate-half, base 1,000,000) at the
  position's STATED index; query head j meets key/value head j // 8; scores
  ``q.k / sqrt(128)`` under the mask M below; ``W_o`` 4,096 -> 2,048.
- ``MoE``: router 2,048 -> 128 in float32, softmax over the 128, the top 8,
  their weights divided by their sum (``norm_topk_prob``); expert ``W_down
  (silu(W_gate x) * W_up x)`` of width 768; no shared expert.

*The objective* (block diffusion with an absorbing mask state). A sequence
``x`` of L tokens in blocks of B: ``blk(i) = i // B``. Draw ``t ~ U[eps, 1]``
a sequence and ``m_i ~ Bernoulli(t)`` a position; ``x~_i = MASK`` where
``m_i``, else ``x_i``. The stack runs on 2 L positions, a clean copy c
(``x``) and after it a noised copy n (``x~``), BOTH at rotary positions
0..L-1. The mask M:

- a query of c at i sees the keys of c at j with ``blk(j) <= blk(i)``, and
  nothing of n;
- a query of n at i sees the keys of c at j with ``blk(j) < blk(i)`` and the
  keys of n at j with ``blk(j) == blk(i)``.

That is ``L (L + B) / 2 + L (L - B) / 2 + L B = L^2 + L B`` visible pairs a
head. The loss: with ``z_i`` the logits of copy n at i, ``(1 / (batch L))
sum_seq (1 / t) sum_i m_i CE(z_i, x_i)`` in float32 - position i's OWN token,
no shift - plus ``router_aux_loss_coef`` x the routers' balance loss over the
2 L positions (the routers see both copies); no z-loss. ``(t, m)`` come from
the batch (``olmoe._noise``).

What no key of the ``config.json`` says (the logits not shifted, ``t`` a
sequence, eps, the weight 1 / t, B, the mask token's id, the aux weight) is
``assumed`` and listed, the first to doubt first, in
``benchmark/configs/sdar-30b-a3b-l4-ep8.json``.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Tuple

from .olmoe import AttentionKind, OlmoeConfig, forward, init_params, loss_fn

__all__ = ["forward", "init_params", "loss_fn", "sdar_config", "tiny_sdar_config"]


def sdar_config(
    config: Mapping[str, Any], block: int, mask_token_id: int,
    held_experts: Optional[Tuple[int, int]] = None, balance_coef: float = 0.001,
    noise_seed: int = 0, noise_floor: float = 1e-3,
) -> OlmoeConfig:
    """The program's configuration from the keys of an SDAR ``config.json``
    (the published one is copied whole into ``benchmark/configs/
    sdar-30b-a3b-l4-ep8.json``; the numbers live there and nowhere in this
    package): ``num_experts`` is the router's width and ``held_experts`` a
    rank's share of each layer. The block, the mask token, the noise and the
    loss weight are no keys of that file."""
    layers = config["num_hidden_layers"]
    assert config["decoder_sparse_step"] == 1 and not config["mlp_only_layers"]
    assert not config["use_sliding_window"] and config["rope_scaling"] is None
    assert not config["tie_word_embeddings"] and not config["attention_bias"]
    assert config["norm_topk_prob"] and config["hidden_act"] == "silu"
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=layers,
        n_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        balance_coef=balance_coef,
        z_coef=0.0,
        qk_norm_per_head=True,
        renormalize_top_k=True,
        held_experts=held_experts,
        layer_kinds=(AttentionKind("block", block=block),) * layers,
        diffusion_block=block,
        mask_token_id=mask_token_id,
        noise_seed=noise_seed,
        noise_floor=noise_floor,
    )


def tiny_sdar_config(
    held_experts: Optional[Tuple[int, int]] = (0, 2), block: int = 4
) -> OlmoeConfig:
    """Small config for tests and CPU rehearsals: two layers of 4 query
    heads over 2 key/value heads of 32, 2 of 8 experts held, blocks of 4,
    the vocabulary's last row the mask token."""
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_dim=32,
        n_layers=2, n_experts=8, experts_per_token=2, expert_width=32,
        rope_theta=10000.0, rms_norm_eps=1e-6, balance_coef=0.001, z_coef=0.0,
        qk_norm_per_head=True, renormalize_top_k=True, held_experts=held_experts,
        layer_kinds=(AttentionKind("block", block=block),) * 2,
        diffusion_block=block, mask_token_id=255,
    )
