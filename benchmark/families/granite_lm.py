"""Family ``granite_lm``: the Granite 4.0-H decoder as the program runs it
(``torchft_tpu.models.granite``, a configuration of the family in
``models/olmoe.py``: a Mamba-2 state-space mixer nine layers in ten, one
grouped-query attention layer with no position signal at the config's own
softmax scale, a scaled residual stream, a tied readout, every layer
recomputed in the backward pass), sized by a Granite 4.0-H ``config.json``
and the deployment its file states.

Like the other families it gives the harness everything in
``common.FAMILY_STATES``. What a reader of its numbers must know:

- EVERY LAYER IS COMPUTED TWICE. The stack is recomputed a layer
  (``olmoe._stack`` under ``recompute_layers``): the backward pass runs each
  layer's forward again before its backward. ``flops_per_step`` is the
  model's REQUIRED work and no more - one forward and its backward: 6 N a
  position for the weights it multiplies, the attention layer's causal
  scores, the state-space recurrence - so ``mfu`` is model FLOPs over the
  device's time, as in every cell, and the recomputation shows as what it
  costs (``layer_recompute_ms``).
- The step's only Mosaic calls are the attention layer's: ``flash_fwd`` in
  the forward pass, ``flash_fwd`` again (recomputed) and ``flash_bwd`` in
  the backward pass - 3 an attention layer, in the lowered text and in a
  traced step alike (``forward_kernels`` + 1); ``flash_calls`` gives the
  kernels' REQUIRED operations and bytes for the calls the step runs, the
  recomputed forward kernel among them. The scan is plain XLA.
- ``ssm_scan_work`` is what the RECURRENCE requires, from shapes and blind
  to what implements it: ``ssm_scan_roofline`` divides its least time by the
  scan's traced time, recomputation and chunk algebra included, and so
  reads low by construction.
- The seeded weights are the program's but for the attention layer's maps to
  q and k, drawn ``ATTENTION_SPREAD`` times wider so that ``correct`` can see
  that layer at all (the constant's comment).
- The reference takes every number of the model from the PUBLISHED keys of
  the configuration's file, not from the program's configuration
  (``reference_granite``'s module docstring): ``build`` keeps them by the
  configuration it returned.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import common

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_granite.py says what they are and why
from benchmark.reference_granite import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's module, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 58) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import granite

_ling = common.load_by_name("families", "ling_lm")
parameters = _ling.parameters
causal_pairs = _ling.causal_pairs

# the published keys and layers each configuration was built from, by the
# configuration ``build`` returned (``dsv2_lm._published_rope`` says why by
# the configuration and not "the last built")
_published: Dict[Any, Dict[str, Any]] = {}


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes and the
    deployment: the layers are the PUBLISHED layers ``deployment.layers``
    (state-space or attention by ``layer_types``), every one recomputed in
    the backward pass where ``deployment.recompute_layers`` says so."""
    deployment = sizes["deployment"]
    cfg = granite.granite_config(
        sizes, layers=deployment["layers"],
        recompute_layers=deployment["recompute_layers"],
    )
    _published[cfg] = {"keys": dict(sizes), "layers": list(deployment["layers"])}
    return cfg


# The seeded maps to q and k of the attention layer are drawn this many times
# the program's scale (a departure the configuration's file lists, as
# ``mellum_lm.ROUTER_SPREAD`` is): at the program's scale a score is
# ``attention_multiplier x q.k`` = 0.125 of a standard normal, the softmax over
# 4,096 keys is uniform to a few percent, and neither the config's softmax
# scale nor a rotary embedding applied moves three losses and a global norm -
# ``correct`` saw neither on 0 seeds of 12 (PERF.md section 6, PR 58, has the
# readings at 1 and at this draw). Times 4 on both maps a score is 2 of a
# standard normal, a trained layer's spread. Program and reference get the
# one tree.
ATTENTION_SPREAD = 4.0


def init(cfg: Any, key: Any) -> Any:
    """The program's own seeded weights with the attention layers' ``wq`` and
    ``wk`` times ``ATTENTION_SPREAD``; the reference is given the same tree."""
    params = granite.init_params(cfg, key)
    return dict(params, blocks=[
        dict(b, attn=dict(b["attn"], **{
            w: b["attn"][w] * ATTENTION_SPREAD for w in ("wq", "wk")
        })) if kind.mixer is None else b
        for kind, b in zip(cfg.kinds, params["blocks"])
    ])


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return granite.loss_fn(cfg, params, tokens)


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch, from the
    published keys that ``cfg`` was built from: a configuration ``build`` did
    not make is refused, since nothing says what numbers it stands for."""
    from benchmark import reference_granite

    if cfg not in _published:
        raise ValueError(
            "granite_lm.reference_train wants a configuration that granite_lm.build "
            "returned: the reference reads the published keys it was built from"
        )
    made = _published[cfg]
    return reference_granite.train(made["keys"], made["layers"], params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def ssm_layers(cfg: Any) -> int:
    return sum(kind.mixer is not None for kind in cfg.kinds)


def mixer_matmul_params(cfg: Any, kind: Any) -> int:
    """Weights one position multiplies in a layer's mixer. Mamba-2: the one
    map in, the convolution's taps, ``wo``. Attention: q and ``wo`` at the
    query heads' width, k and v at the key/value heads'."""
    d = cfg.d_model
    if kind.mixer is None:
        return 2 * d * cfg.n_heads * cfg.head_dim + 2 * d * cfg.kv_heads * cfg.head_dim
    m = kind.mixer
    return (
        d * (m.inner + m.convolved + m.inner_heads) + m.conv_taps * m.convolved + m.inner * d
    )


def matmul_params(cfg: Any) -> int:
    """Weights one position multiplies in a step's forward pass: per layer
    its mixer's and the SwiGLU's 3 d f; the tied readout's d x V once (the
    embedding's lookup multiplies nothing)."""
    d = cfg.d_model
    return d * cfg.vocab_size + sum(
        mixer_matmul_params(cfg, kind) + 3 * d * width
        for kind, width in zip(cfg.kinds, cfg.ff)
    )


# what one position of one head of the state-space RECURRENCE does to an
# element of its state of P x n, forward: the decay (1), the rank-one update
# (2: the product and the sum), S C (2); the backward pass is counted as
# twice the forward, as every matmul of ``flops_per_step`` is
_RECURRENCE_OPS = 5


def ssm_scan_work(cfg: Any, batch: int, seq: int) -> Dict[str, float]:
    """What the recurrence REQUIRES of a step's state-space layers, from
    shapes, whatever implements it: FLOPs ``3 x 5 P n`` a head, position and
    layer (``_RECURRENCE_OPS``, both passes); bytes a position and layer:
    forward x (bf16), dt (float32), B and C (bf16) read and y written,
    backward those and y's cotangent read and the four cotangents written,
    once each. A chunked form multiplies more than this (the pairs inside a
    chunk) and a recomputed layer runs its forward twice, so the scan's share
    of this reads under 100 by construction."""
    s, layers = seq - 1, 0
    flops = bytes_ = 0.0
    for kind in cfg.kinds:
        if kind.mixer is None:
            continue
        m = kind.mixer
        h, p, n = m.inner_heads, m.inner_head_dim, m.state
        ins = h * p * 2 + h * 4 + 2 * n * 2  # x; dt; B, C
        flops += batch * s * h * 3 * _RECURRENCE_OPS * p * n
        bytes_ += batch * s * (ins + h * p * 2 + ins + h * p * 2 + ins)
        layers += 1
    return {"flops": flops, "bytes": bytes_, "layers": layers}


def attention_flash(cfg: Any, batch: int, seq: int) -> Dict[str, float]:
    """What the flash kernels of a step's attention layers REQUIRE, ONE
    forward and one backward a layer: 2 matmuls forward and 4 backward over
    the causal pairs; q and out forward and q, out, d_out, dq backward at the
    query heads' width, k and v forward and k, v, dk, dv backward at the
    KEY/VALUE heads' (``mellum_lm.kind_flash``'s count), all bf16; the f32
    log-sum-exp written once and read once. ``forward`` is the forward
    kernel's part alone, which a recomputed layer runs twice."""
    s, h, g, dh = seq - 1, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    layers = cfg.n_layers - ssm_layers(cfg)
    forward = {
        "flops": float(layers * batch * h * 2 * 2 * causal_pairs(s) * dh),
        "bytes": float(layers * batch * (2 * s * h * dh * 2 + 2 * s * g * dh * 2 + s * h * 4)),
    }
    return {
        "layers": layers, "forward": forward,
        "flops": float(layers * batch * h * 6 * 2 * causal_pairs(s) * dh),
        "bytes": float(
            layers * batch * (6 * s * h * dh * 2 + 6 * s * g * dh * 2 + 2 * s * h * 4)
        ),
    }


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step REQUIRE and no
    more (no recomputed layer, no chunk algebra): 6 N per position for the
    weights it multiplies (``matmul_params``), the attention layers' causal
    pairs (``attention_flash``) and the recurrence (``ssm_scan_work``)."""
    return float(
        batch * (seq - 1) * 6 * matmul_params(cfg)
        + attention_flash(cfg, batch, seq)["flops"]
        + ssm_scan_work(cfg, batch, seq)["flops"]
    )


def forward_kernels(cfg: Any) -> int:
    """How often an attention layer's ``flash_fwd`` runs a step: once, and
    once more with its layer where the stack is recomputed a layer."""
    return 2 if cfg.recompute_layers else 1


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: every attention
    layer's flash forward as often as it runs (``forward_kernels``) and its
    fused backward; the state-space scan has none."""
    return (forward_kernels(cfg) + 1) * (cfg.n_layers - ssm_layers(cfg))


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``ssm_*`` and ``attn_*`` readers want of this family, kept in
    a run's facts under ``family``: the scan's shapes and required work."""
    mamba = next(kind.mixer for kind in cfg.kinds if kind.mixer is not None)
    return {
        "ssm_scan_work": ssm_scan_work(cfg, batch, seq),
        "ssm_scan": {
            "heads": mamba.inner_heads, "head_dim": mamba.inner_head_dim,
            "state": mamba.state, "chunk": mamba.chunk, "positions": batch * (seq - 1),
        },
        "attention_flash": attention_flash(cfg, batch, seq),
        "parameters": parameters(cfg),
    }


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls require: per attention
    layer the flash pair and, where the stack is recomputed a layer, the
    forward kernel once more (it runs, and ``flash_roofline`` divides by its
    time)."""
    flash = attention_flash(cfg, batch, seq)
    again = forward_kernels(cfg) - 1
    return {
        "calls": lowered_mosaic_calls(cfg),
        "flops": flash["flops"] + again * flash["forward"]["flops"],
        "bytes": flash["bytes"] + again * flash["forward"]["bytes"],
    }
