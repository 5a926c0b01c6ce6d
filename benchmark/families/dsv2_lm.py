"""Family ``dsv2_lm``: the DeepSeek-V2 decoder as the program runs it
(``torchft_tpu.models.dsv2``, a configuration of the sparse family in
``models/olmoe.py``: latent attention in EVERY layer, unnormed and ungated
under YaRN, a dense SwiGLU layer before the sparse ones, a rank's share of
softmax-routed SwiGLU experts beside two shared experts, a balance loss a
sequence and a layer), sized by a DeepSeek-V2 ``config.json`` and the
deployment its file states.

Like ``ling_lm`` it gives the harness everything in ``common.FAMILY_STATES``
and the optional ``routing``. What it shares with the two families before
it is theirs, loaded by name and called, not copied: a rank's expected
claims (``mellum_lm``), the parameters counted from the tree the program
builds, latent attention's flash pair at the widths the model REQUIRES
(``kind_flash``: q.k 192 and v 128, whatever the program pads to), the
causal pairs and the held experts' matmuls over the sparse layers
(``ling_lm``). Its own: what one position multiplies (``matmul_params``: no
gate's map, a dense layer, a shared width of two experts).

A traced step's Mosaic calls are the flash pair of every layer, ten; the
held share is plain XLA and its COST FOLLOWS THE ROUTING, so the cell is on
``step_p90_routed_ms`` and every run prints its ``routing``: ``mellum_lm``'s
two readings, the most loaded expert's claims over the mean, and
``balance``, the balance loss as the program computes it, averaged over the
sparse layers (1.0 at even routing).
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark import common

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_dsv2.py says what they are and why
from benchmark.reference_dsv2 import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's module, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 53) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import dsv2

_ling = common.load_by_name("families", "ling_lm")
expected_held_claims = _ling.expected_held_claims
parameters = _ling.parameters
kind_flash = _ling.kind_flash
held_expert_matmuls = _ling.held_expert_matmuls

# the published ``rope_scaling`` each configuration was built from, by the
# configuration ``build`` returned: the reference derives YaRN's frequencies
# and both factors from it again, not from what the program made of it
# (``reference_dsv2``'s module docstring). By the configuration and not "the
# last built": a rehearsal's sizes and a cell's in one process keep each its
# own, and equal configurations were built from equal numbers
_published_rope: Dict[Any, Dict[str, Any]] = {}


def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes and the
    deployment: the layers are the PUBLISHED layers ``deployment.layers``
    (dense or sparse by their published index), ``n_routed_experts`` is how
    many this rank HOLDS (those from ``deployment.rank`` x
    ``n_routed_experts`` on; the router's width is
    ``published.n_routed_experts``)."""
    held = sizes["n_routed_experts"]
    cfg = dsv2.dsv2_config(
        dict(sizes, n_routed_experts=sizes["published"]["n_routed_experts"]),
        layers=sizes["deployment"]["layers"],
        held_experts=(sizes["deployment"]["rank"] * held, held),
        aux_alpha=sizes["assumed"]["aux_loss_alpha"],
    )
    _published_rope[cfg] = dict(sizes["rope_scaling"])
    return cfg


def init(cfg: Any, key: Any) -> Any:
    """The program's own seeded weights; the reference is given the same tree."""
    return dsv2.init_params(cfg, key)


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return dsv2.loss_fn(cfg, params, tokens)


def routing(cfg: Any, params: Any, tokens: Any) -> Dict[str, Any]:
    """What routing the step runs under ``params`` on each of the pool's
    batches ``tokens`` (int32[pool, batch, seq]): the program's own forward
    pass (``dsv2.forward``), a batch at a time at the step's own shapes and
    in the step's own types, for ``moe_layer``'s sums; the logits are not
    asked for, so the readout is never computed. Arrays of (pool,), one
    number a batch: ``held_claims``, the claims the sparse layers put on
    held experts over the expected ones; ``heavy_experts``, how many of the
    layers' held experts were applied to every token; ``load_max``, the most
    loaded expert's claims over the mean, of all the router's experts and
    the sparse layers' summed claims; ``balance``, ``E sum_e f_e P_e`` a
    sequence, averaged over the sparse layers (1.0 at even routing)."""
    import jax
    import jax.numpy as jnp

    compute = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, params
    )
    sums = jax.lax.map(lambda b: dsv2.forward(cfg, compute, b[:, :-1])[1], tokens)
    positions = tokens.shape[1] * (tokens.shape[2] - 1)
    return {
        "held_claims": sums["held_claims"]
        / (cfg.expert_layers * expected_held_claims(cfg, positions)),
        "heavy_experts": sums["held_dense_layers"] * cfg.held[1],
        "load_max": jnp.max(sums["claims"], axis=-1) / jnp.mean(sums["claims"], axis=-1),
        "balance": sums["seq_balance"] / cfg.expert_layers,
    }


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch, under the
    published ``rope_scaling`` that ``cfg`` was built from: a configuration
    ``build`` did not make is refused, since nothing says what numbers its
    factors were derived from."""
    from benchmark import reference_dsv2

    if cfg not in _published_rope:
        raise ValueError(
            "dsv2_lm.reference_train wants a configuration that dsv2_lm.build returned: "
            "the reference derives YaRN from the published rope_scaling it was built from"
        )
    return reference_dsv2.train(cfg, params, batches, _published_rope[cfg])


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def mixer_matmul_params(cfg: Any, kind: Any) -> int:
    """Weights one position multiplies in a layer's latent attention: q,
    the map down, the map up, ``wo``; no gate's map."""
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    latent, r = kind.mixer.latent, kind.mixer.rope_dim
    return d * h * (dh + r) + d * (latent + r) + latent * h * 2 * dh + h * dh * d


def matmul_params(cfg: Any) -> float:
    """Weights one position multiplies on THIS rank: per layer its mixer's
    and either the dense SwiGLU's 3 d f or the router, the shared experts
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


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require of
    this chip and no more (no recomputation, no padded lane): 6 N per
    position for the weights it multiplies (``matmul_params``) and latent
    attention's pairs, ``6 (qk + v)`` a causal pair and head
    (``kind_flash``)."""
    return float(
        batch * (seq - 1) * 6 * matmul_params(cfg)
        + sum(k["flops"] for k in kind_flash(cfg, batch, seq).values())
    )


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: the flash
    forward and fused backward of every layer; the held share has none."""
    return 2 * cfg.n_layers


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``attn_mla_*`` and ``moe_held_*`` readers want of this
    family, kept in a run's facts under ``family``."""
    return {
        "kind_flash": kind_flash(cfg, batch, seq),
        "held_expert_matmuls": held_expert_matmuls(cfg, batch, seq),
        "parameters": parameters(cfg),
    }


def flash_calls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one traced step's Mosaic custom calls require: the flash pair
    of every layer (``kind_flash``), the only kernels of the step."""
    flash = kind_flash(cfg, batch, seq).values()
    return {
        "calls": lowered_mosaic_calls(cfg),
        "flops": sum(k["flops"] for k in flash),
        "bytes": sum(k["bytes"] for k in flash),
    }
