"""Family ``dense_lm``: the dense decoder the program has
(``torchft_tpu.models.transformer``), sized by a GPT-2 ``config.json``.

A family gives the harness the program's configuration built from the
file of sizes, the weights from a seed, the loss, the plain reference's
training run (``benchmark/reference.py``) with the tolerances set beside
it, the tokens and operations of one step, how many Mosaic custom calls
the lowered step holds, and whatever else its readers want in a run's
facts (``common.FAMILY_STATES``).
"""

from __future__ import annotations

from typing import Any, Dict

# how close the measured step's first losses and first gradient norm must
# come to the reference's; the values and their reason are reference.py's
from benchmark.reference import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes, unchanged:
    full depth, full width, the published context."""
    from torchft_tpu.models import TransformerConfig

    return TransformerConfig(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["n_embd"],
        n_heads=sizes["n_head"],
        n_layers=sizes["n_layer"],
        d_ff=sizes["n_inner"] or 4 * sizes["n_embd"],
        max_seq_len=sizes["n_positions"],
        use_flash=True,
    )


def init(cfg: Any, key: Any) -> Any:
    from torchft_tpu.models import init_params

    return init_params(cfg, key)


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    from torchft_tpu.models import loss_fn

    return loss_fn(cfg, params, tokens)


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch."""
    from benchmark import reference

    return reference.train(cfg.n_heads, params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def matmul_params(cfg: Any) -> int:
    """Weights that multiply an activation: per layer 4 d^2 (fused QKV
    and the out projection) and 2 d d_ff, and the tied readout d x V
    once (the embedding lookup multiplies nothing)."""
    d = cfg.d_model
    return cfg.n_layers * (4 * d * d + 2 * d * cfg.d_ff) + d * cfg.vocab_size


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require;
    recomputation (the flash backward's second QK^T) is not counted. The
    loss runs the model on ``seq - 1`` positions. 6 N per position for
    the weights; causal attention is QK^T and PV at half the square,
    2 x 2 S d / 2 forward and twice that backward: 6 S d a layer."""
    s = seq - 1
    per_position = 6 * matmul_params(cfg) + 6 * s * cfg.d_model * cfg.n_layers
    return float(batch * s * per_position)


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step (gradient
    step or fused step alike: the update has none): every layer is one
    flash forward and one fused flash backward, and nothing else of this
    model is a kernel."""
    return 2 * cfg.n_layers


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """Nothing beyond what every family gives: no reader asks more."""
    return {}


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, float]:
    """What one step's flash-attention custom calls (forward and the
    fused backward, every layer) require, from shapes: 2 matmuls forward
    and 4 backward over the causal half of S x S, and the bytes of q, k,
    v, out (forward) and q, k, v, out, d_out, dq, dk, dv (backward) in
    bf16 plus the f32 log-sum-exp written once and read once."""
    s, h, dh = seq - 1, cfg.n_heads, cfg.head_dim
    matmul = 2 * s * s * dh / 2  # one S x S x D matmul, causal half
    tensor = s * h * dh * 2
    lse = s * h * 4
    return {
        "calls": 2 * cfg.n_layers,
        "flops": batch * cfg.n_layers * h * 6 * matmul,
        "bytes": batch * cfg.n_layers * (12 * tensor + 2 * lse),
    }
