"""Family ``ling_lm``: the Ling 3.0 decoder as the program runs it
(``torchft_tpu.models.ling``, a configuration of the sparse family in
``models/olmoe.py``: layers in groups of five Kimi-Delta-Attention mixers and
one of latent attention, a rank's share of the heads, a dense SwiGLU layer
before the sparse ones, a rank's share of sigmoid-routed SwiGLU experts under
a selection bias and group-limited top-k, a shared expert), sized by a Ling
3.0 ``config.json`` and the deployment its file states.

Like ``mellum_lm`` it gives the harness everything in
``common.FAMILY_STATES`` and the optional ``routing``; what the two share -
a rank's expected claims, the held experts' matmuls from shapes - is
``mellum_lm``'s, loaded by name and called, not copied. What is this
family's own: the parameters are counted from the tree the program builds
(the mixers are of two kinds and a layer is dense or sparse), a step's
required operations are counted mixer by mixer (``flops_per_step``), and
the two yardsticks of what a later kernel is measured by are here, from
shapes and from nothing an implementation chose: ``kda_scan_work`` (the
delta rule's RECURRENCE) and ``kind_flash`` (latent attention's flash pair
at q.k 192 and v 128, whatever width the program pads to).

A traced step's Mosaic calls are the flash pair of the ONE latent-attention
layer: the delta rule's scan is plain XLA (``ops/delta_rule.py``) and so is
the held share, whose COST FOLLOWS THE ROUTING - this family's cell is on
``step_p90_routed_ms`` and every run prints its ``routing``: with
``mellum_lm``'s two readings, the largest selection bias in size and the
most loaded expert's claims over the mean.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import common

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_ling.py says what they are and why
from benchmark.reference_ling import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's modules, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 50) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import ling
from torchft_tpu.models.olmoe import Kda, Mla

_mellum = common.load_by_name("families", "mellum_lm")
expected_held_claims = _mellum.expected_held_claims


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes and the
    deployment: the layers are the PUBLISHED layers ``deployment.layers``
    (dense or sparse, KDA or MLA by their published index, so the published
    ``first_k_dense_replace`` decides), ``num_experts`` is how many this
    rank HOLDS (the ``num_experts`` from ``deployment.rank`` x
    ``num_experts`` on; the router's width is ``published.num_experts``)
    and ``num_attention_heads`` how many heads it holds."""
    published, assumed = sizes["published"], sizes["assumed"]
    held = sizes["num_experts"]
    whole = dict(sizes, **{
        k: published[k] for k in ("num_experts", "first_k_dense_replace")
    })
    return ling.ling_config(
        whole, layers=sizes["deployment"]["layers"],
        held_experts=(sizes["deployment"]["rank"] * held, held),
        held_heads=sizes["num_attention_heads"],
        balance_coef=assumed["seq_aux_alpha"] / sizes["num_experts_per_tok"],
    )


def init(cfg: Any, key: Any) -> Any:
    """The program's own seeded weights; the reference is given the same tree."""
    return ling.init_params(cfg, key)


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return ling.loss_fn(cfg, params, tokens)


def routing(cfg: Any, params: Any, tokens: Any) -> Dict[str, Any]:
    """What routing the step runs under ``params`` on each of the pool's
    batches ``tokens`` (int32[pool, batch, seq]): the program's own forward
    pass (``ling.forward``), a batch at a time at the step's own shapes and
    in the step's own types, for ``moe_layer``'s sums; the logits are not
    asked for, so the readout is never computed. Arrays of (pool,), one
    number a batch: ``held_claims``, the claims the sparse layers put on
    held experts over the expected ones; ``heavy_experts``, how many of the
    layers' held experts were applied to every token; ``load_max``, the most
    loaded expert's claims over the mean, of all the router's experts and
    the sparse layers' summed claims; ``bias_max``, the largest selection
    bias in size (of the state, the same for every batch)."""
    import jax
    import jax.numpy as jnp

    compute = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, params
    )
    sums = jax.lax.map(lambda b: ling.forward(cfg, compute, b[:, :-1])[1], tokens)
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
    (int32[steps, batch, seq]), one plain AdamW update a batch."""
    from benchmark import reference_ling

    return reference_ling.train(cfg, params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def parameters(cfg: Any) -> int:
    """Every weight this rank holds, counted from the tree the program
    builds for ``cfg`` (shapes only; nothing is drawn)."""
    import jax

    tree = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    return sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))


def mixer_matmul_params(cfg: Any, kind: Any) -> int:
    """Weights one position multiplies in a layer's mixer on this rank. KDA:
    the five maps to the held heads' columns, ``wo``, the step's map and
    the three convolutions' taps. MLA: q, the map down, the map up, ``wo``,
    the gate's map."""
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    if isinstance(kind.mixer, Kda):
        return 6 * d * h * dh + d * h + 3 * kind.mixer.taps * h * dh
    latent, r = kind.mixer.latent, kind.mixer.rope_dim
    return d * h * (dh + r) + d * (latent + r) + latent * h * 2 * dh + h * dh * d + d * h


def matmul_params(cfg: Any) -> float:
    """Weights one position multiplies on THIS rank: per layer its mixer's
    and either the dense SwiGLU's 3 d f or the router, the shared expert
    and the 3 d f of its EXPECTED held claims; the readout's d x V once."""
    d = cfg.d_model
    total = float(d * cfg.vocab_size)
    for kind, width in zip(cfg.kinds, cfg.ff):
        total += mixer_matmul_params(cfg, kind)
        if width is not None:
            total += 3 * d * width
        else:
            total += (
                d * cfg.n_experts + 3 * d * cfg.shared_width
                + expected_held_claims(cfg, 1) * 3 * d * cfg.expert_width
            )
    return total


def causal_pairs(s: int) -> int:
    return s * (s + 1) // 2


def kda_layers(cfg: Any) -> int:
    return sum(isinstance(kind.mixer, Kda) for kind in cfg.kinds)


# what one position of one head of the delta rule's RECURRENCE does to its
# state of d_k x d_v, forward: the decay (1), k^T S (2), the rank-one
# update (2: the product and the sum), S^T q (2); the backward pass is
# counted as twice the forward, as every matmul of ``flops_per_step`` is
_RECURRENCE_OPS = 7


def kda_scan_work(cfg: Any, batch: int, seq: int) -> Dict[str, float]:
    """What the delta rule's recurrence REQUIRES of a step's KDA layers,
    from shapes, whatever implements it: FLOPs ``3 x 7 d_k d_v`` a head,
    position and layer (``_RECURRENCE_OPS``, both passes); bytes: forward
    q, k, v (bf16), g and beta (float32) read and o written, backward those
    and o's cotangent read and the five cotangents written, once each. A
    chunked form multiplies more than this (the pairs inside a chunk, the
    triangular system), so its share of this reads under 100 by
    construction."""
    s, h, dh = seq - 1, cfg.n_heads, cfg.head_dim
    rows = batch * s * h * kda_layers(cfg)
    ins = 3 * dh * 2 + dh * 4 + 4  # q, k, v; g; beta
    return {
        "flops": float(rows * 3 * _RECURRENCE_OPS * dh * dh),
        "bytes": float(rows * (ins + dh * 2 + ins + dh * 2 + ins)),
    }


def kind_flash(cfg: Any, batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """By kind of layer (the name its mixer is scoped under), what the two
    flash kernels of a step's latent-attention layers REQUIRE at q.k
    ``head_dim + rope_dim`` and v ``head_dim``, the causal half: QK^T and PV
    forward and their four products backward, ``6 (qk + v)`` a pair and
    head; q, k, v, out forward and q, k, v, out, d_out, dq, dk, dv backward
    in bf16 at their own widths, the f32 log-sum-exp written once and read
    once. What the program pads to is not counted."""
    s, h, dh = seq - 1, cfg.n_heads, cfg.head_dim
    out: Dict[str, Dict[str, float]] = {}
    for kind in cfg.kinds:
        if not isinstance(kind.mixer, Mla):
            continue
        qk = dh + kind.mixer.rope_dim
        entry = out.setdefault(kind.name, {"layers": 0, "flops": 0.0, "bytes": 0.0})
        entry["layers"] += 1
        entry["flops"] += batch * h * 6 * (qk + dh) * causal_pairs(s)
        entry["bytes"] += batch * s * h * (2 * (6 * qk + 6 * dh) + 2 * 4)
    return out


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require of
    this chip and no more (no recomputation, no chunk algebra, no padded
    lane): 6 N per position for the weights it multiplies
    (``matmul_params``), latent attention's pairs (``kind_flash``) and the
    delta rule's recurrence (``kda_scan_work``)."""
    return float(
        batch * (seq - 1) * 6 * matmul_params(cfg)
        + sum(k["flops"] for k in kind_flash(cfg, batch, seq).values())
        + kda_scan_work(cfg, batch, seq)["flops"]
    )


def held_expert_matmuls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """``mellum_lm.held_expert_matmuls`` over this family's SPARSE layers
    (it counts every layer of its own model, all of them sparse)."""
    import dataclasses

    sparse = dataclasses.replace(
        cfg, n_layers=cfg.expert_layers, dense_ff=None,
        layer_kinds=tuple(k for k, w in zip(cfg.kinds, cfg.ff) if w is None),
    )
    return _mellum.held_expert_matmuls(sparse, batch, seq)


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: the flash
    forward and fused backward of every latent-attention layer; the delta
    rule's scan and the held share have none."""
    return 2 * (cfg.n_layers - kda_layers(cfg))


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``attn_mla_*``, ``kda_*`` and ``moe_held_*`` readers want of
    this family, kept in a run's facts under ``family``."""
    return {
        "kind_flash": kind_flash(cfg, batch, seq),
        "kda_scan_work": kda_scan_work(cfg, batch, seq),
        "held_expert_matmuls": held_expert_matmuls(cfg, batch, seq),
        "parameters": parameters(cfg),
    }


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls require: the flash pair
    of every latent-attention layer (``kind_flash``), the only kernels of
    the step."""
    flash = kind_flash(cfg, batch, seq).values()
    return {
        "calls": lowered_mosaic_calls(cfg),
        "flops": sum(k["flops"] for k in flash),
        "bytes": sum(k["bytes"] for k in flash),
    }
