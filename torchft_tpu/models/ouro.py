"""Ouro (ByteDance, ``Ouro-2.6B``; Zhu et al., "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): a dense decoder whose whole
stack of layers runs ``total_ut_steps`` times on the SAME weights, every
pass ending in an exit, as a CONFIGURATION of the sparse family in
``olmoe.py``. This file holds no mathematics: ``olmoe.init_params``,
``forward`` and ``loss_fn`` serve it, and ``make_train_step`` takes it as it
takes OLMoE's.

Tokens (B, S) -> ``h_0 = E[tokens]`` (49,152 x 2,048, no position table).
For t = 1..T, T = 4: ``u_t = Stack(h_{t-1})``, the ``num_hidden_layers``
layers in order and the same weights at every t; ``h_t = RMSNorm_f(u_t)``
(eps 1e-6, a learned scale): the final norm closes every pass and its
OUTPUT is what the next pass starts from.

- A layer, a sandwich of four RMSNorms with learned scales:
  ``a = x + N2(Attn(N1(x)))``, ``y = a + N4(FF(N3(a)))``.
- ``Attn``: 16 heads of 128 over 2,048 columns, 16 key/value heads, no
  bias, NO QK-norm; rotary embedding, rotate-half pairing, base 1,000,000;
  causal scores ``q.k / sqrt(128)``; every layer full attention.
- ``FF(x) = W_down (silu(W_gate x) * W_up x)``, width 5,632, no bias.
- Exit t: logits ``z_t = h_t W_out`` (untied, one readout for all exits)
  and a gate ``g_t = sigmoid(w_g . h_t + b_g)``. A position leaves at exit
  t with ``p_t = g_t prod_{j<t} (1 - g_j)`` for t < T and ``p_T =
  prod_{j<T} (1 - g_j)``, which sums to 1.
- Training loss (the paper's stage-I objective): the mean over positions
  of ``sum_t p_t CE_t - beta H(p)``, ``CE_t`` the next-token cross entropy
  of exit t and ``H`` the entropy of ``p`` over the exits, in float32.
  ``early_exit_threshold`` is inference's rule (1: never early); training
  always runs the T passes.

What no key of the ``config.json`` says (the carried norm, the sandwich,
the gate's form, beta) is ``assumed`` and listed, the first to doubt first,
in ``benchmark/configs/ouro-2.6b-l6.json``.
"""

from __future__ import annotations

from typing import Any, Mapping

from .olmoe import OlmoeConfig, forward, init_params, loss_fn

__all__ = ["forward", "init_params", "loss_fn", "ouro_config", "tiny_ouro_config"]


def ouro_config(config: Mapping[str, Any], exit_entropy_coef: float) -> OlmoeConfig:
    """The program's configuration from the keys of an Ouro ``config.json``
    (the published one is copied whole into ``benchmark/configs/
    ouro-2.6b-l6.json``; the numbers live there and nowhere in this
    package). ``exit_entropy_coef``, the loss's beta, is no key of that
    file."""
    layers = config["num_hidden_layers"]
    assert set(config["layer_types"][:layers]) == {"full_attention"}
    assert not config["use_sliding_window"] and config["rope_scaling"] is None
    assert not config["tie_word_embeddings"] and config["hidden_act"] == "silu"
    return OlmoeConfig(
        vocab_size=config["vocab_size"],
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        n_layers=layers,
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        qk_norm=False,
        dense_ff=(config["intermediate_size"],) * layers,
        sandwich_norms=True,
        passes=config["total_ut_steps"],
        exit_entropy_coef=exit_entropy_coef,
    )


def tiny_ouro_config(passes: int = 4) -> OlmoeConfig:
    """Small config for tests and CPU rehearsals: two layers of 4 heads of
    16 and a SwiGLU of 96, run ``passes`` times."""
    return OlmoeConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, rope_theta=10000.0,
        rms_norm_eps=1e-6, qk_norm=False, dense_ff=(96, 96), sandwich_norms=True,
        passes=passes, exit_entropy_coef=0.05,
    )
