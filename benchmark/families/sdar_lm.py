"""Family ``sdar_lm``: the SDAR decoder as the program runs it
(``torchft_tpu.models.sdar``, a configuration of the sparse family in
``models/olmoe.py``: grouped-query attention under the block-diffusion mask
over a clean and a noised copy of every sequence, a rank's share of
softmax-routed SwiGLU experts, the diffusion loss over the masked
positions), sized by an SDAR ``config.json`` and the deployment and the
objective its file states.

Like ``mellum_lm`` it gives the harness everything in
``common.FAMILY_STATES`` and the optional ``routing``; what the two share -
a rank's parameters, its expected claims, the held experts' matmuls from
shapes - is ``mellum_lm``'s, loaded by name and called, not copied. What is
this family's own: a step's positions are TWICE its tokens (both copies run
the stack; the readout and the loss run over the noised one), every token
is a target (``tokens_per_step``: the bound is of the tokens a step trains
on, of which the loss reads the masked ones), and attention's work is the
mask's ``L^2 + L B`` pairs a head (``required_pairs``), which
``flash_calls`` and ``flops_per_step`` count whatever the kernels sweep;
what they do sweep is the program's own count (``block_scores_computed``),
kept beside it in ``facts`` for ``block_scores_ratio``.

A traced step's Mosaic calls are the two flash kernels of every layer, as
``mellum_lm``'s; the held share is plain XLA and ITS COST FOLLOWS THE
ROUTING, so this family's cell is on ``step_p90_routed_ms`` and every run
prints its ``routing``: with ``mellum_lm``'s two readings the share of
positions the batch's noise masked (``masked_share``).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import common

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_sdar.py says what they are and why
from benchmark.reference_sdar import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's modules, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 46) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import sdar
from torchft_tpu.ops import block_scores_computed

_mellum = common.load_by_name("families", "mellum_lm")
attention_params = _mellum.attention_params
parameters = _mellum.parameters
expected_held_claims = _mellum.expected_held_claims
# the one departure in the seeded weights, mellum_lm's and for its reason:
# the router's columns drawn at 4 times the program's scale, so that what
# the held experts add answers to the router (the configuration file lists it)
ROUTER_SPREAD = _mellum.ROUTER_SPREAD
init = _mellum.init


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes, the deployment
    and what the file assumes of the objective: ``num_experts`` is how many
    this rank HOLDS (the ``num_experts`` from ``deployment.rank`` x
    ``num_experts`` on), the router's width is ``published.num_experts``,
    and the mask token is the LAST row of this rank's slice of the
    vocabulary (the source's id lies outside the slice)."""
    held, assumed = sizes["num_experts"], sizes["assumed"]
    return sdar.sdar_config(
        dict(sizes, num_experts=sizes["published"]["num_experts"]),
        block=assumed["block_length"],
        mask_token_id=sizes["vocab_size"] - 1,
        held_experts=(sizes["deployment"]["rank"] * held, held),
        balance_coef=assumed["router_aux_loss_coef"],
        noise_seed=assumed["noise_seed"],
        noise_floor=assumed["noise_floor"],
    )


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return sdar.loss_fn(cfg, params, tokens)


def positions_per_step(batch: int, seq: int) -> int:
    """Positions the stack runs a step: a clean and a noised copy of every
    sequence of ``seq`` tokens."""
    return batch * 2 * seq


def routing(cfg: Any, params: Any, tokens: Any) -> Dict[str, Any]:
    """What routing and what noise the step runs under ``params`` on each of
    the pool's batches ``tokens`` (int32[pool, batch, seq]): the program's
    own forward pass (``sdar.forward``), a batch at a time at the step's
    own shapes and in the step's own types, for ``moe_layer``'s sums and
    ``masked_share``; the logits are not asked for, so the readout is never
    computed. Arrays of (pool,), one number a batch: ``held_claims``, the
    claims the layers put on held experts over the expected ones;
    ``heavy_experts``, how many of the layers' held experts were applied to
    every position; ``masked_share``, the share of the batch's tokens that
    its noise masked (a sequence's is its ``t``: a batch of two spreads
    widely)."""
    import jax
    import jax.numpy as jnp

    compute = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, params
    )
    sums = jax.lax.map(lambda b: sdar.forward(cfg, compute, b)[1], tokens)
    expected = expected_held_claims(cfg, positions_per_step(*tokens.shape[1:]))
    return {
        "held_claims": sums["held_claims"] / (cfg.n_layers * expected),
        "heavy_experts": sums["held_dense_layers"] * cfg.held[1],
        "masked_share": sums["masked_share"],
    }


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch."""
    from benchmark import reference_sdar

    return reference_sdar.train(cfg, params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Tokens one step trains on: every one of a sequence's ``seq`` tokens
    is a target of the objective (no shift)."""
    return batch * seq


def required_pairs(cfg: Any, seq: int) -> int:
    """(query, key) pairs the mask shows one head of one sequence of
    ``seq`` = L tokens: ``L (L + B) / 2`` clean under clean, ``L (L - B) /
    2`` clean under noised, ``L B`` noised under noised."""
    return seq * seq + seq * cfg.diffusion_block


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require of
    this chip and no more (no recomputation, no tile's hidden pairs): 6 N
    per position of the STACK for the weights it multiplies there - the
    projections, the router and the 3 d f of its expected held claims - 6 d
    V per TOKEN for the readout, which runs over the noised copy alone, and
    12 x head_dim a visible pair, head, sequence and layer."""
    d = cfg.d_model
    layer = (
        attention_params(cfg) + d * cfg.n_experts
        + expected_held_claims(cfg, 1) * 3 * d * cfg.expert_width
    )
    return float(
        positions_per_step(batch, seq) * 6 * cfg.n_layers * layer
        + batch * seq * 6 * d * cfg.vocab_size
        + batch * cfg.n_layers * cfg.n_heads * 12 * cfg.head_dim * required_pairs(cfg, seq)
    )


def held_expert_matmuls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """``mellum_lm.held_expert_matmuls`` over this family's positions (it
    counts a sequence's inputs as one fewer than its tokens)."""
    return _mellum.held_expert_matmuls(cfg, 1, positions_per_step(batch, seq) + 1)


def block_flash(cfg: Any, seq: int) -> Dict[str, int]:
    """One head of one sequence under the block mask: the pairs the mask
    shows and the pairs the program's kernels compute for such a call on
    the chip (``block_scores_computed``: the tiles its walk meets, forward;
    the backward walks the same tiles transposed)."""
    return {
        "required_pairs": required_pairs(cfg, seq),
        "computed_pairs": block_scores_computed(
            (cfg.diffusion_block, seq), cfg.head_dim, interpret=False
        ),
    }


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: the flash
    forward and fused backward of every layer; the held share has none."""
    return 2 * cfg.n_layers


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``moe_held_*`` readers and ``block_scores_ratio`` want of
    this family, kept in a run's facts under ``family``."""
    return {
        "held_expert_matmuls": held_expert_matmuls(cfg, batch, seq),
        "block_flash": block_flash(cfg, seq),
        "parameters": parameters(cfg),
    }


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls - the flash pair of every
    layer - REQUIRE under the mask: 2 matmuls forward and 4 backward over
    the visible pairs; q and out forward and q, out, d_out, dq backward at
    the query heads' width, k and v forward and k, v, dk, dv backward at the
    KEY/VALUE heads' (the program repeats them to the query heads and the
    kernels read that), all bf16 over the 2 L rows; the f32 log-sum-exp
    written once and read once."""
    s, h, g, dh = 2 * seq, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    return {
        "calls": 2 * cfg.n_layers,
        "flops": float(cfg.n_layers * batch * h * 6 * 2 * required_pairs(cfg, seq) * dh),
        "bytes": float(cfg.n_layers * batch * (6 * s * h * dh * 2 + 6 * s * g * dh * 2 + 2 * s * h * 4)),
    }
