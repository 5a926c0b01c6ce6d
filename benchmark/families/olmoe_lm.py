"""Family ``olmoe_lm``: the OLMoE decoder the program has
(``torchft_tpu.models.olmoe``: RoPE / QK-norm attention, dropless top-K
SwiGLU experts), sized by an OLMoE ``config.json``.

Like ``dense_lm``, it gives the harness the program's configuration from
the file of sizes, the weights from a seed, the loss, the plain
reference's training run (``benchmark/reference_olmoe.py``) with the
tolerances set beside it, the tokens and operations of one step and the
Mosaic calls its lowered text holds; and, in ``facts``, ``expert_matmuls``:
what a step's grouped expert matmuls require, for the ``moe_*`` readers.

On the TPU ``jax.lax.ragged_dot`` compiles to Mosaic custom calls of
XLA's own (``ragged-dot-*``), so in this family ``flash_calls`` counts
EVERY Mosaic call of a traced step - the two flash kernels and the
grouped matmuls with their metadata calls - and ``flash_roofline`` there
is the roofline share of all of them together (``layer_metrics/
flash_roofline.py`` holds the trace to this count). They become Mosaic
only in the compiler: the LOWERED text, which ``lowered_mosaic_calls``
speaks of, still says ``ragged_dot``.
"""

from __future__ import annotations

from typing import Any, Dict

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_olmoe.py says what they are and why
from benchmark.reference_olmoe import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's module, imported as the family loads: a checkout whose
# program lacks this model fails here, as soon as a worker has its backend
# (the launcher then exits and the run with it: 33 s on the chip, PR 26)
from torchft_tpu.models import olmoe

# Mosaic custom calls the TPU compiler emits for one layer's grouped
# matmuls, forward and backward: the nine ``ragged-dot-none`` kernels and
# the two ``ragged-dot-metadata`` calls that lay out their groups (one for
# the six that keep the rows, one for the three weight gradients), as
# counted in the gradient step compiled for the v5e.
RAGGED_CALLS_PER_LAYER = 9 + 2


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes, unchanged
    but for the depth the file gives (``num_hidden_layers``)."""
    return olmoe.OlmoeConfig(
        vocab_size=sizes["vocab_size"],
        d_model=sizes["hidden_size"],
        n_heads=sizes["num_attention_heads"],
        n_layers=sizes["num_hidden_layers"],
        n_experts=sizes["num_experts"],
        experts_per_token=sizes["num_experts_per_tok"],
        expert_width=sizes["intermediate_size"],
        rope_theta=float(sizes["rope_theta"]),
        rms_norm_eps=sizes["rms_norm_eps"],
        balance_coef=sizes["assumed"]["router_aux_loss_coef"],
        z_coef=sizes["assumed"]["router_z_loss_coef"],
    )


# How far the experts of a layer stand apart at the start, between 0 (64
# copies of one expert) and 1 (64 independent draws, the program's own
# ``init_params``). Read on the v5e (PERF.md section 6, PR 26, calls 12 and
# 14): the sound program's errors in losses 1 and 2 against the float32
# reference, rms and largest, and how many runs of a program whose
# dispatch sends every claim to the NEXT expert still read ``correct``:
#   0     1.4e-5  3.1e-5   10 of 10  (which expert a row met is invisible)
#   0.25  2.0e-5  5.3e-5    5 of 10
#   0.5   4.1e-5  1.2e-4    1 of 22
#   1     1.1e-4  2.4e-4    0 of 10  (and the SOUND program only 8 of 10:
#                                     2e-4 is reference.py's bound, set on
#                                     a dense model)
EXPERT_SPREAD = 0.5


def init(cfg: Any, key: Any) -> Any:
    """The program's seeded weights, with the experts of a layer drawn
    closer to each other than independent draws are: expert ``e`` is
    ``sqrt(1 - a^2) x shared + a x own_e`` with ``a = EXPERT_SPREAD``,
    ``own_e`` the program's own draw and ``shared`` one more seeded expert
    (the scale of every entry stays the program's). A departure, stated
    in the configuration file. Why: a top-8 choice is discrete, and one
    token in eighteen picks another eighth expert in the bf16 program than
    in the float32 reference, whatever the router's precision; with
    independent experts that token meets another function, the bf16
    gradient is 5% off, and the dense model's bound, which this family
    keeps (``reference_olmoe.py``), fails one run in eight. With copies it never
    fails, and never sees a row sent to the wrong expert either. Between
    the two the bound stands at 4.8 times the sound program's rms error
    and a misrouted dispatch is outside it in 21 runs of 22."""
    import jax

    params = olmoe.init_params(cfg, key)
    shared = olmoe.init_params(cfg, jax.random.fold_in(key, 1))
    own, common = EXPERT_SPREAD, (1.0 - EXPERT_SPREAD ** 2) ** 0.5
    for block, other in zip(params["blocks"], shared["blocks"]):
        for name in ("w_gate", "w_up", "w_down"):
            block["moe"][name] = common * other["moe"][name][:1] + own * block["moe"][name]
    return params


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return olmoe.loss_fn(cfg, params, tokens)


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch."""
    from benchmark import reference_olmoe

    return reference_olmoe.train(cfg, params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def matmul_params(cfg: Any) -> int:
    """Weights one position multiplies: per layer 4 d^2 of attention, the
    router's d x E and the 3 d f of each of its K experts; the readout
    d x V once (the embedding lookup multiplies nothing)."""
    d = cfg.d_model
    layer = (
        4 * d * d + d * cfg.n_experts
        + cfg.experts_per_token * 3 * d * cfg.expert_width
    )
    return cfg.n_layers * layer + d * cfg.vocab_size


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require;
    recomputation is not counted. 6 N per position for the weights it
    multiplies; causal attention is QK^T and PV at half the square:
    6 S d a layer."""
    s = seq - 1
    per_position = 6 * matmul_params(cfg) + 6 * s * cfg.d_model * cfg.n_layers
    return float(batch * s * per_position)


def expert_matmuls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one step's grouped expert matmuls require, from shapes. A
    layer has three forward (gate, up, down) and for each of them two
    backward (the rows' gradient, the weights' gradient): nine, each
    2 x rows x d x f operations over rows = positions x K claims. Bytes,
    bf16: each reads or writes one rows x d and one rows x f matrix and
    the experts' E x d x f weights (the weights' gradient reads the two
    row matrices and writes the weights). ``calls_by_output`` says how
    many of the calls write which shape (the trace names an operation by
    its output), ``rows`` and ``width`` the shape of the claims' rows that
    the dispatch and the combine move."""
    rows = batch * (seq - 1) * cfg.experts_per_token
    d, f, e = cfg.d_model, cfg.expert_width, cfg.n_experts
    calls = 9 * cfg.n_layers
    return {
        "calls": calls,
        "flops": float(calls * 2 * rows * d * f),
        "bytes": float(calls * 2 * (rows * d + rows * f + e * d * f)),
        "calls_by_output": {
            f"{rows},{f}": 3 * cfg.n_layers,   # gate, up, d hidden
            f"{rows},{d}": 3 * cfg.n_layers,   # down, d rows (gate), d rows (up)
            f"{e},{d},{f}": 2 * cfg.n_layers,  # d W_gate, d W_up
            f"{e},{f},{d}": 1 * cfg.n_layers,  # d W_down
        },
        "rows": rows,
        "width": d,
    }


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: the flash
    forward and fused backward of every layer. The grouped matmuls are
    ``ragged_dot`` there and become Mosaic calls only in the TPU compiler
    (module docstring), so the traced step's thirteen are two here."""
    return 2 * cfg.n_layers


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``moe_*`` readers want of this family, kept in a run's
    facts under ``family``."""
    return {"expert_matmuls": expert_matmuls(cfg, batch, seq)}


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls require: the
    flash-attention forward and fused backward of every layer (2 matmuls
    forward and 4 backward over the causal half of S x S; q, k, v, out
    forward and q, k, v, out, d_out, dq, dk, dv backward in bf16, the f32
    log-sum-exp written once and read once) AND the grouped expert
    matmuls, which are Mosaic calls too (module docstring)."""
    s, h, dh = seq - 1, cfg.n_heads, cfg.head_dim
    matmul = 2 * s * s * dh / 2  # one S x S x D matmul, causal half
    tensor = s * h * dh * 2
    lse = s * h * 4
    experts = expert_matmuls(cfg, batch, seq)
    return {
        "calls": (2 + RAGGED_CALLS_PER_LAYER) * cfg.n_layers,
        "flops": batch * cfg.n_layers * h * 6 * matmul + experts["flops"],
        "bytes": batch * cfg.n_layers * (12 * tensor + 2 * lse) + experts["bytes"],
    }
