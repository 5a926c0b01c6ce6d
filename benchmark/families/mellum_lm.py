"""Family ``mellum_lm``: the Mellum2 decoder as the program runs it
(``torchft_tpu.models.mellum``, a configuration of the sparse family in
``models/olmoe.py``: three sliding-window layers to one full layer of
grouped-query attention, a rank's share of softmax-routed SwiGLU experts),
sized by a Mellum2 ``config.json`` and the deployment its file states.

Like ``olmoe_lm`` it gives the harness everything in
``common.FAMILY_STATES``; in ``facts`` it keeps what the seven readers of
the ``attn_*`` and ``moe_held_*`` metrics want: the flash kernels' least
work by kind of layer and the held experts' matmuls, both from shapes
(``kind_flash``, ``held_expert_matmuls``). The functions that count
operations and bytes live here; the readers only multiply and divide.

A traced step's Mosaic calls (``flash_calls``) are the two flash kernels
of every layer and nothing else: a rank's share (``olmoe._held_share``,
since PR 40) is plain XLA and does the work of the claims it holds - the
tiles its light experts' claims fill, and every token by each expert that
more than about 1.25 times its even load of tokens chose - so ITS COST
FOLLOWS THE ROUTING. That is why this family states the optional
``routing`` (``common.py``): the program's own forward pass over the pool's
batches with ``moe_layer``'s sums, which the generator runs on the state
after the window and, in a traced run, on both sides of the traced steps,
so that every run says what routing it timed and the held share's roofline
reads the rows that were claimed. The timed program is not touched, and
``routing`` calls nothing of the program that its modules do not export
(``mellum.forward``; the sums are those ``moe_layer`` documents).

The routing is the seed's own and it is not a deployment's even share
(PERF.md section 6, PRs 40 and 42). At the seed's draw a layer's held
claims read 0.6 to 1.4 of the expected: from random weights attention's
output carries each SEQUENCE's own mean, so an expert's claims differ by a
fifth (layer 0) to four fifths (layer 3) from one batch of two sequences to
the next, and no labelling of which experts the rank holds makes the
batches alike (one was built and measured). Under the generator's AdamW at
1e-3 every router then collapses onto whole experts within ten steps and
goes on moving between them all window long: this rank's routers see the
held experts' gradient alone. So the cell's step is held under a metric of
its own with a wide bound (``step_p90_routed_ms``, PERF.md section 2).
"""

from __future__ import annotations

from typing import Any, Dict

# how close the measured step's first losses and first gradient norm must
# come to the reference's; reference_mellum.py says what they are and why
from benchmark.reference_mellum import GRAD_NORM_RTOL, LOSS_RTOL  # noqa: F401

# the program's modules, imported as the family loads: a checkout whose
# program lacks this model (the parent of PR 36) fails here, as soon as a
# worker has its backend
from torchft_tpu.models import mellum, olmoe

def build(sizes: Dict[str, Any]) -> Any:
    """The program's configuration from the published sizes and the
    deployment: ``num_experts`` is how many this rank HOLDS, the router's
    width is ``published.num_experts``, and the rank's experts are the
    ``num_experts`` from ``deployment.rank`` x ``num_experts`` on. The
    layers are the first ``num_hidden_layers`` of ``layer_types``."""
    held = sizes["num_experts"]
    return mellum.mellum2_config(
        dict(sizes, num_experts=sizes["published"]["num_experts"]),
        held_experts=(sizes["deployment"]["rank"] * held, held),
        balance_coef=sizes["assumed"]["router_aux_loss_coef"],
        z_coef=sizes["assumed"]["router_z_loss_coef"],
    )


# ONE departure in the seeded weights (the configuration file lists it), for
# the comparison that decides ``correct`` and not for the step's time, which
# does not move with a weight's value: the router's columns are drawn at 4
# times the program's scale, so that the softmax over the 64 experts is
# peaked as a trained router's is (the top-8 hold about half of the mass, the
# first of them most of that). At the program's own scale every token's 8
# weights are near an eighth each, what the held experts add hardly depends
# on the router, and a step on float8 weights or with the next rank's experts
# read like the sound program's own rounding (PERF.md section 6, PR 36, has
# the readings of five draws; ``reference_mellum.py`` the limits).
ROUTER_SPREAD = 4.0


def init(cfg: Any, key: Any) -> Any:
    """The program's own seeded weights with the router's columns times
    ``ROUTER_SPREAD``; the reference is given the same tree."""
    params = olmoe.init_params(cfg, key)
    return dict(params, blocks=[
        dict(b, moe=dict(b["moe"], router=b["moe"]["router"] * ROUTER_SPREAD))
        for b in params["blocks"]
    ])


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return mellum.loss_fn(cfg, params, tokens)


def routing(cfg: Any, params: Any, tokens: Any) -> Dict[str, Any]:
    """What routing the step runs under ``params`` on each of the pool's
    batches ``tokens`` (int32[pool, batch, seq]): the program's own forward
    pass (``mellum.forward``), a batch at a time at the step's own shapes
    and in the step's own types (the bf16 copy of the masters), for
    ``moe_layer``'s sums over a step's layers; the logits are not asked
    for, so the readout is never computed. Arrays of (pool,), one number
    a batch: ``held_claims``, the claims the layers put on held experts
    over the expected ones; ``heavy_experts``, how many of the layers'
    held experts were applied to every token."""
    import jax
    import jax.numpy as jnp

    compute = jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, params
    )
    sums = jax.lax.map(lambda b: mellum.forward(cfg, compute, b[:, :-1])[1], tokens)
    positions = tokens.shape[1] * (tokens.shape[2] - 1)
    return {
        "held_claims": sums["held_claims"]
        / (cfg.n_layers * expected_held_claims(cfg, positions)),
        "heavy_experts": sums["held_dense_layers"] * cfg.held[1],
    }


def reference_train(cfg: Any, params: Any, batches: Any) -> Any:
    """The plain reference's losses and gradient norms over ``batches``
    (int32[steps, batch, seq]), one plain AdamW update a batch."""
    from benchmark import reference_mellum

    return reference_mellum.train(cfg, params, batches)


def tokens_per_step(batch: int, seq: int) -> int:
    """Positions one step trains on: a sequence of ``seq`` tokens is
    ``seq - 1`` inputs, each with the next token as its target."""
    return batch * (seq - 1)


def attention_params(cfg: Any) -> int:
    """Weights of one layer's four projections: q and out over all the
    query heads' columns, k and v over the key/value heads'."""
    q_width, kv_width = cfg.n_heads * cfg.head_dim, cfg.kv_heads * cfg.head_dim
    return cfg.d_model * (2 * q_width + 2 * kv_width)


def parameters(cfg: Any) -> int:
    """Every weight this rank holds: per layer the projections, the two
    QK-norm scales and the two layer norms, the router over ALL experts
    and the held experts' three matrices; the embedding, the final norm
    and the readout over the rank's rows of the vocabulary."""
    d, f = cfg.d_model, cfg.expert_width
    layer = (
        attention_params(cfg) + 2 * cfg.head_dim + 2 * d
        + d * cfg.n_experts + cfg.held[1] * 3 * d * f
    )
    return cfg.n_layers * layer + 2 * cfg.vocab_size * d + d


def expected_held_claims(cfg: Any, positions: int) -> float:
    """Claims a rank expects of ``positions`` x K under even routing."""
    return positions * cfg.experts_per_token * cfg.held[1] / cfg.n_experts


def scores_seen(cfg: Any, kind: Any, s: int) -> int:
    """(query, key) pairs one head of one sequence attends: the causal
    half, or under a window ``w`` the band ``q_pos - k_pos < w``."""
    w = s if kind.window is None else min(kind.window, s)
    return w * (w + 1) // 2 + (s - w) * w


def matmul_params(cfg: Any) -> float:
    """Weights one position multiplies on THIS rank: per layer the
    projections, the router and the 3 d f of its EXPECTED held claims (K x
    held / experts of them: one, at 8 of 64 under top-8); the readout's
    d x V once (the embedding lookup multiplies nothing)."""
    d = cfg.d_model
    layer = (
        attention_params(cfg) + d * cfg.n_experts
        + expected_held_claims(cfg, 1) * 3 * d * cfg.expert_width
    )
    return cfg.n_layers * layer + d * cfg.vocab_size


def flops_per_step(cfg: Any, batch: int, seq: int) -> float:
    """Operations the forward and backward passes of one step require of
    this chip and no more; recomputation (the held share's rows, the flash
    backward's second QK^T) is not counted. 6 N per position for the
    weights it multiplies; attention's QK^T and PV over the pairs each
    kind of layer sees (``scores_seen``), forward and twice that backward:
    12 x head_dim a pair, head and sequence."""
    s = seq - 1
    pairs = sum(scores_seen(cfg, kind, s) for kind in cfg.kinds)
    return float(
        batch * s * 6 * matmul_params(cfg)
        + batch * cfg.n_heads * 12 * cfg.head_dim * pairs
    )


def held_expert_matmuls(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What one step's matmuls over the held experts REQUIRE, from shapes:
    a layer has three forward (gate, up, down) and for each two backward
    (the rows' gradient, the weights'): nine, each 2 x rows x d x f
    operations. Bytes, bf16: each reads or writes one rows x d and one
    rows x f matrix and the held experts' held x d x f weights. ``flops``
    and ``bytes`` are those at ``rows``, the EXPECTED held claims a layer;
    ``flops_per_row``, ``bytes_per_row`` and ``bytes_weights`` are their
    terms, for a reader that knows the rows a run realised (its
    ``routing``). What the share MULTIPLIES for them since PR 40 (whole
    tiles of its light experts' claims, every position by a heavy expert)
    follows the routing and is not to be had from shapes."""
    positions = batch * (seq - 1)
    rows = expected_held_claims(cfg, positions)
    d, f, held = cfg.d_model, cfg.expert_width, cfg.held[1]
    calls = 9 * cfg.n_layers
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


def kind_flash(cfg: Any, batch: int, seq: int) -> Dict[str, Dict[str, float]]:
    """By kind of layer (the name its attention is scoped under), what
    the two flash kernels of a step's layers of that kind require: 2
    matmuls forward and 4 backward over the pairs the kind sees; q and out
    forward and q, out, d_out, dq backward at the query heads' width, k
    and v forward and k, v, dk, dv backward at the KEY/VALUE heads' (what
    the model requires: the program repeats them to the query heads and
    the kernels read that), all bf16; the f32 log-sum-exp written once and
    read once."""
    s, h, g, dh = seq - 1, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    out: Dict[str, Dict[str, float]] = {}
    for kind in cfg.kinds:
        entry = out.setdefault(kind.name, {"layers": 0, "flops": 0.0, "bytes": 0.0})
        entry["layers"] += 1
        entry["flops"] += batch * h * 6 * 2 * scores_seen(cfg, kind, s) * dh
        entry["bytes"] += batch * (6 * s * h * dh * 2 + 6 * s * g * dh * 2 + 2 * s * h * 4)
    return out


def lowered_mosaic_calls(cfg: Any) -> int:
    """``tpu_custom_call``s in the text of the lowered step: the flash
    forward and fused backward of every layer; the held share has none."""
    return 2 * cfg.n_layers


def facts(cfg: Any, batch: int, seq: int) -> Dict[str, Any]:
    """What the ``attn_*`` and ``moe_held_*`` readers want of this family,
    kept in a run's facts under ``family``."""
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
        "calls": 2 * cfg.n_layers,
        "flops": sum(k["flops"] for k in flash),
        "bytes": sum(k["bytes"] for k in flash),
    }
