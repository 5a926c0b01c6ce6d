"""Family ``ouro_lm``: the Ouro decoder as the program runs it
(``torchft_tpu.models.ouro``, a configuration of the sparse family in
``models/olmoe.py``: a stack of dense sandwich-norm layers run
``total_ut_steps`` = T times on the same weights, every pass ending in an
exit, a loss over the T exits), sized by an Ouro ``config.json``.

Like the other families it gives the harness everything in
``common.FAMILY_STATES``. What a reader of its numbers must know:

- THE LOOP'S BODY IS LOWERED ONCE AND RUN T TIMES. The program runs the
  passes as one ``lax.scan`` whose body is under ``jax.checkpoint``
  (``olmoe._looped``), so the lowered gradient step holds two loop bodies:
  the forward one (a pass: every layer's ``flash_fwd``) and the backward
  one (the same pass computed AGAIN - the recomputation - and its backward
  pass: every layer's ``flash_fwd`` and ``flash_bwd``).
  ``lowered_mosaic_calls`` counts that text, 3 a layer whatever T is;
  ``flash_calls`` counts what a TRACED step runs, 3 a layer and pass.
- ``flops_per_step`` is the model's own work and no more: T passes of the
  stack and T readouts, forward and backward, and causal attention T
  times. The recomputed forward pass (a third again of the stack's
  operations; the exits' logits are recomputed with it) is NOT counted, so
  ``mfu`` is model FLOPs over the device's time, as in every cell.
- ``flash_calls`` gives the kernels' REQUIRED operations and bytes for the
  calls the step runs, the recomputed forward kernels among them (they run,
  and ``flash_roofline`` divides by their time).
- ``routing`` (optional in ``common.py``) is the mechanism's counter: the
  exit distribution's mean over a batch's positions, ``exit_p1`` ..
  ``exit_pT``, for each batch of the pool under the state as it stands, by
  the program's own forward pass. Nothing timed is touched.
"""

from __future__ import annotations

from typing import Any, Dict

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_ouro.py says what they are and why
from benchmark.reference_ouro import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's module, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 43) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import ouro

# Mosaic calls a layer a pass: ``flash_fwd`` in the forward scan, and
# ``flash_fwd`` again (recomputed) with ``flash_bwd`` in the backward scan
FLASH_CALLS_PER_LAYER = 3


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes, unchanged but
    for the depth the file gives (``num_hidden_layers``)."""
    return ouro.ouro_config(sizes, sizes["assumed"]["exit_entropy_coef"])


def init(cfg: Any, key: Any) -> Any:
    return ouro.init_params(cfg, key)


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return ouro.loss_fn(cfg, params, tokens)


def routing(cfg: Any, params: Any, tokens: Any) -> Dict[str, Any]:
    """Where the exit mass stands under ``params`` on each of the pool's
    batches ``tokens`` (int32[pool, batch, seq]): the program's own forward
    pass (``ouro.forward``), a batch at a time at the step's own shapes and
    in the step's own types (the bf16 copy of the masters), for its
    ``exit_probs``; the logits are not asked for. Arrays of (pool,), one
    number a batch: ``exit_p1`` .. ``exit_pT``, which sum to 1."""
    import jax
    import jax.numpy as jnp

    compute = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, params
    )
    probs = jax.lax.map(
        lambda b: ouro.forward(cfg, compute, b[:, :-1])[1]["exit_probs"], tokens
    )  # (pool, T)
    return {f"exit_p{t + 1}": probs[:, t] for t in range(cfg.passes)}


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch."""
    from benchmark import reference_ouro

    return reference_ouro.train(cfg, params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def stack_matmul_params(cfg: Any) -> int:
    """Weights one position multiplies in one pass of the stack: a layer's
    four projections and its SwiGLU's three matrices."""
    d = cfg.d_model
    return sum(4 * d * cfg.n_heads * cfg.head_dim + 3 * d * width for width in cfg.ff)


def parameters(cfg: Any) -> int:
    """Every weight: the layers' matrices and four norms each, the
    embedding, the readout, the final norm and the gate (d + 1)."""
    d = cfg.d_model
    return (
        stack_matmul_params(cfg) + cfg.n_layers * 4 * d
        + 2 * cfg.vocab_size * d + d + d + 1
    )


def matmul_params(cfg: Any) -> int:
    """Weights one position multiplies in a step's forward pass: the stack
    T times and, T times, the readout's d x V and the gate's d (the
    embedding lookup multiplies nothing)."""
    d = cfg.d_model
    return cfg.passes * (stack_matmul_params(cfg) + d * cfg.vocab_size + d)


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require;
    the recomputation by pass is NOT counted (module docstring). 6 N per
    position for the weights it multiplies, each as often as it multiplies
    them (``matmul_params``); causal attention is QK^T and PV at half the
    square: 6 S d a layer and pass."""
    s = seq - 1
    attention = 6 * s * cfg.n_heads * cfg.head_dim * cfg.n_layers * cfg.passes
    return float(batch * s * (6 * matmul_params(cfg) + attention))


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: the two loop
    bodies once each, whatever T is (module docstring)."""
    return FLASH_CALLS_PER_LAYER * cfg.n_layers


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What a reader may want of this family beside the shared facts."""
    return {
        "parameters": parameters(cfg), "passes": cfg.passes,
        "layer_applications": cfg.passes * cfg.n_layers,
    }


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls require: per layer and
    pass the flash forward kernel TWICE (the pass, and the pass recomputed
    in the backward scan) and the fused backward once. A forward is 2
    matmuls over the causal half of S x S and reads q, k, v, writes out and
    the f32 log-sum-exp; the backward is 4 matmuls and reads q, k, v, out,
    d_out and the log-sum-exp, writes dq, dk, dv; all bf16 but the
    log-sum-exp."""
    s, h, dh = seq - 1, cfg.n_heads, cfg.head_dim
    matmul = 2 * s * s * dh / 2  # one S x S x D matmul, causal half
    tensor = s * h * dh * 2
    lse = s * h * 4
    applications = cfg.passes * cfg.n_layers
    return {
        "calls": FLASH_CALLS_PER_LAYER * applications,
        "flops": batch * applications * h * (2 * 2 + 4) * matmul,
        "bytes": batch * applications * (2 * (4 * tensor + lse) + 8 * tensor + lse),
    }
