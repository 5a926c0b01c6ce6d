"""Family ``nemotron_lm``: the Nemotron 3 Nano decoder as the program runs it
(``torchft_tpu.models.nemotron``, a configuration of the family in
``models/olmoe.py``: every layer ONE sublayer - a Mamba-2 state-space mixer
with grouped maps and a gated norm a group, a grouped-query attention layer
with no position signal, or a rank's share of sigmoid-routed UNGATED ``relu
** 2`` experts beside a shared one - an untied readout, every layer
recomputed in the backward pass), sized by a Nemotron-H ``config.json`` and
the deployment its file states.

Like ``granite_lm`` and ``ling_lm`` it gives the harness everything in
``common.FAMILY_STATES`` and the optional ``routing``; what they share - a
rank's expected claims, the parameters counted from the tree, a mixer's
weights, the attention layers' flash calls and how often they run - is
theirs, loaded by name and called, not copied (``granite_lm`` counts every
layer's mixer, so it is handed the layers that HAVE one: ``_mixers``). What
a reader of its numbers must know:

- EVERY LAYER IS COMPUTED TWICE (``olmoe._stack`` under
  ``recompute_layers``). ``flops_per_step`` is the model's REQUIRED work and
  no more - one forward and its backward: 6 N a position for the weights it
  multiplies with the held experts at their EXPECTED claims, the attention
  layer's causal scores, the state-space recurrence - so ``mfu`` is model
  FLOPs over the device's time, and the recomputation shows as what it costs
  (``layer_recompute_ms``).
- The step's only Mosaic calls are the ONE attention layer's: ``flash_fwd``,
  the recomputed ``flash_fwd`` and ``flash_bwd`` - 3, in the lowered text
  and in a traced step alike. The scan and the held share are plain XLA.
- ``held_expert_matmuls`` is counted HERE: an ungated expert is two matrices,
  so a sparse layer has two forward matmuls over the held claims and for each
  two backward - SIX, not ``mellum_lm``'s nine - over the sparse layers alone.
- ``ssm_scan_work`` is what the RECURRENCE requires with its groups of maps,
  from shapes and blind to what implements it (``ssm_scan_roofline`` reads
  low by construction: chunk algebra, recomputation).
- The held share's COST FOLLOWS THE ROUTING: the cell is on
  ``step_p90_routed_ms`` and every run prints its ``routing``.
- The reference takes every number of the model from the PUBLISHED keys of
  the configuration's file (``reference_nemotron``'s module docstring):
  ``build`` keeps them by the configuration it returned.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from benchmark import common

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_nemotron.py says what they are and why
from benchmark.reference_nemotron import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's module, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 60) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import nemotron

_ling = common.load_by_name("families", "ling_lm")
_granite = common.load_by_name("families", "granite_lm")
parameters = _ling.parameters
expected_held_claims = _ling.expected_held_claims
mixer_matmul_params = _granite.mixer_matmul_params

# the published keys each configuration was built from, by the configuration
# ``build`` returned (``dsv2_lm._published_rope`` says why by the
# configuration and not "the last built")
_published: Dict[Any, Dict[str, Any]] = {}


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes and the
    deployment: the layers are the PUBLISHED layers ``deployment.layers``
    (their one sublayer by the pattern's character), ``n_routed_experts`` is
    how many this rank HOLDS (those from ``deployment.rank`` x that on; the
    router's width is ``published.n_routed_experts``), every layer recomputed
    in the backward pass where ``deployment.recompute_layers`` says so."""
    deployment, held = sizes["deployment"], sizes["n_routed_experts"]
    whole = dict(sizes, n_routed_experts=sizes["published"]["n_routed_experts"])
    cfg = nemotron.nemotron_config(
        whole, layers=deployment["layers"],
        held_experts=(deployment["rank"] * held, held),
        balance_coef=sizes["assumed"]["balance_coef"],
        recompute_layers=deployment["recompute_layers"],
    )
    _published[cfg] = dict(sizes)
    return cfg


def init(cfg: Any, key: Any) -> Any:
    """The program's own seeded weights; the reference is given the same tree."""
    return nemotron.init_params(cfg, key)


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return nemotron.loss_fn(cfg, params, tokens)


def routing(cfg: Any, params: Any, tokens: Any) -> Dict[str, Any]:
    """What routing the step runs under ``params`` on each of the pool's
    batches ``tokens`` (int32[pool, batch, seq]): the program's own forward
    pass, a batch at a time at the step's own shapes and in the step's own
    types, for ``moe_layer``'s sums (``ling_lm.routing``'s four readings:
    ``held_claims`` over the expected ones, ``heavy_experts`` of the sparse
    layers' held ones, ``load_max`` over the mean, ``bias_max``)."""
    import jax
    import jax.numpy as jnp

    compute = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, params
    )
    sums = jax.lax.map(lambda b: nemotron.forward(cfg, compute, b[:, :-1])[1], tokens)
    positions = tokens.shape[1] * (tokens.shape[2] - 1)
    bias_max = jnp.max(jnp.stack([
        jnp.max(jnp.abs(b["moe"]["bias"])) for b in params["blocks"] if "moe" in b
    ]))
    return {
        "held_claims": sums["held_claims"]
        / (cfg.expert_layers * expected_held_claims(cfg, positions)),
        "heavy_experts": sums["held_dense_layers"] * cfg.held[1],
        "load_max": jnp.max(sums["claims"], axis=-1) / jnp.mean(sums["claims"], axis=-1),
        "bias_max": jnp.broadcast_to(bias_max, sums["held_claims"].shape),
    }


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch, from the
    published keys that ``cfg`` was built from: a configuration ``build`` did
    not make is refused, since nothing says what numbers it stands for."""
    from benchmark import reference_nemotron

    if cfg not in _published:
        raise ValueError(
            "nemotron_lm.reference_train wants a configuration that nemotron_lm.build "
            "returned: the reference reads the published keys it was built from"
        )
    sizes = _published[cfg]
    return reference_nemotron.train(sizes, sizes["deployment"], params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def layers_of(cfg: Any) -> Dict[str, int]:
    """How many of the layers are state-space, attention and sparse."""
    mixers = _mixers(cfg)
    ssm = _granite.ssm_layers(mixers)
    return {"ssm": ssm, "attention": mixers.n_layers - ssm, "sparse": cfg.expert_layers}


def _mixers(cfg: Any) -> Any:
    """``cfg`` cut to the layers that have a mixer, each with both sublayers
    again: the list ``granite_lm``'s counts of attention layers walk."""
    kinds = tuple(kind for kind, part in zip(cfg.kinds, cfg.parts) if part != "ff")
    return dataclasses.replace(
        cfg, n_layers=len(kinds), layer_kinds=kinds, sublayers=None, dense_ff=None
    )


def matmul_params(cfg: Any) -> float:
    """Weights one position multiplies on THIS rank in a step's forward pass:
    a mixer layer's mixer; a sparse layer's router, shared expert (two
    matrices) and the two matrices of its EXPECTED held claims; the readout's
    d x V once (the embedding's lookup multiplies nothing)."""
    d = cfg.d_model
    total = float(d * cfg.vocab_size)
    for kind, part in zip(cfg.kinds, cfg.parts):
        if part != "ff":
            total += mixer_matmul_params(cfg, kind)
        else:
            total += (
                d * cfg.n_experts + 2 * d * cfg.shared_width
                + expected_held_claims(cfg, 1) * 2 * d * cfg.expert_width
            )
    return total


# what one position of one head of the state-space RECURRENCE does to an
# element of its state of P x n, forward: the decay (1), the rank-one update
# (2: the product and the sum), S C (2); the backward pass is counted as
# twice the forward, as every matmul of ``flops_per_step`` is
_RECURRENCE_OPS = 5


def ssm_scan_work(cfg: Any, batch: int, seq: int) -> Dict[str, float]:
    """What the recurrence REQUIRES of a step's state-space layers, from
    shapes, whatever implements it: FLOPs ``3 x 5 P n`` a head, position and
    layer (``_RECURRENCE_OPS``, both passes); bytes a position and layer:
    forward x (bf16), dt (float32), B and C (bf16, ``groups`` maps of n each)
    read and y written, backward those and y's cotangent read and the four
    cotangents written, once each. A chunked form multiplies more than this
    and a recomputed layer runs its forward twice, so the scan's share of
    this reads under 100 by construction."""
    s, layers = seq - 1, 0
    flops = bytes_ = 0.0
    for kind, part in zip(cfg.kinds, cfg.parts):
        if part == "ff" or kind.mixer is None:
            continue
        m = kind.mixer
        h, p, n = m.inner_heads, m.inner_head_dim, m.state
        ins = h * p * 2 + h * 4 + 2 * m.groups * n * 2  # x; dt; B, C
        flops += batch * s * h * 3 * _RECURRENCE_OPS * p * n
        bytes_ += batch * s * (ins + h * p * 2 + ins + h * p * 2 + ins)
        layers += 1
    return {"flops": flops, "bytes": bytes_, "layers": layers}


def attention_flash(cfg: Any, batch: int, seq: int) -> Dict[str, float]:
    """What the flash kernels of a step's attention layers REQUIRE, ONE
    forward and one backward a layer, and ``forward``, the forward kernel's
    part alone, which a recomputed layer runs twice: ``granite_lm``'s count
    (q, out at the query heads' width, k, v at the KEY/VALUE heads')."""
    return _granite.attention_flash(_mixers(cfg), batch, seq)


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step REQUIRE of this
    chip and no more (no recomputed layer, no chunk algebra, no padded tile):
    6 N per position for the weights it multiplies (``matmul_params``), the
    attention layer's causal pairs and the recurrence."""
    return float(
        batch * (seq - 1) * 6 * matmul_params(cfg)
        + attention_flash(cfg, batch, seq)["flops"]
        + ssm_scan_work(cfg, batch, seq)["flops"]
    )


def held_expert_matmuls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one step's matmuls over the held experts REQUIRE, from shapes,
    for an UNGATED expert: a sparse layer has two forward (up, down) and for
    each two backward (the rows' gradient, the weights'): SIX, each 2 x rows
    x d x f operations. Bytes, bf16: each reads or writes one rows x d and
    one rows x f matrix and the held experts' held x d x f weights. The keys
    are ``mellum_lm.held_expert_matmuls``'s, for the same readers: ``flops``
    and ``bytes`` at ``rows``, the EXPECTED held claims a layer, and their
    terms for a reader that knows the rows a run realised."""
    rows = expected_held_claims(cfg, batch * (seq - 1))
    d, f, held = cfg.d_model, cfg.expert_width, cfg.held[1]
    calls = 6 * cfg.expert_layers
    per_row, weights = float(calls * 2 * (d + f)), float(calls * 2 * held * d * f)
    return {
        "calls": calls,
        "flops": float(calls * 2 * rows * d * f),
        "bytes": per_row * rows + weights,
        "flops_per_row": float(calls * 2 * d * f),
        "bytes_per_row": per_row,
        "bytes_weights": weights,
        "rows": rows,
    }


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: every attention
    layer's flash forward as often as it runs (twice where the stack is
    recomputed a layer) and its fused backward; the state-space scan and the
    held share have none."""
    return _granite.lowered_mosaic_calls(_mixers(cfg))


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``ssm_*``, ``attn_*`` and ``moe_held_*`` readers want of this
    family, kept in a run's facts under ``family``."""
    mamba = next(
        kind.mixer for kind, part in zip(cfg.kinds, cfg.parts)
        if part != "ff" and kind.mixer is not None
    )
    return {
        "ssm_scan_work": ssm_scan_work(cfg, batch, seq),
        "ssm_scan": {
            "heads": mamba.inner_heads, "head_dim": mamba.inner_head_dim,
            "state": mamba.state, "groups": mamba.groups, "chunk": mamba.chunk,
            "positions": batch * (seq - 1),
        },
        "attention_flash": attention_flash(cfg, batch, seq),
        "held_expert_matmuls": held_expert_matmuls(cfg, batch, seq),
        "layers": layers_of(cfg),
        "parameters": parameters(cfg),
    }


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls require: per attention
    layer the flash pair and, where the stack is recomputed a layer, the
    forward kernel once more (``granite_lm.flash_calls``)."""
    return _granite.flash_calls(_mixers(cfg), batch, seq)
