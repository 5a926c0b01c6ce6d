"""The plain reference of the ``mellum_lm`` block and of its training step:
float32 ``jax.numpy`` from the tokens to the loss, dense masked attention
a head at a time, every held expert applied to EVERY token and kept where
the token chose it; no kernel, no sort, no grouped matmul, no bf16 copy,
AdamW written out with ``reference.py``'s constants. Written from the
equations below (the published ``config.json`` of
JetBrains/Mellum2-12B-A2.5B-Instruct and, where it is silent, the
``assumed`` list of the configuration file), not from the program's code:
it imports nothing of ``torchft_tpu.models`` and reads the configuration's
attributes by name only.

For activations ``x`` (S, D) of one sequence, per layer ``l``, pre-norm:

    h = x + Attn_l(RMSNorm(x))        y = h + MoE(RMSNorm(h))

``Attn_l``: ``q = x Wq`` (H heads of ``dh``), ``k = x Wk``, ``v = x Wv``
(G heads each); RMSNorm of q and of k over each head's ``dh`` values with a
learned scale shared by the heads; rotary embedding of q and k, the pair
(``i``, ``i + dh / 2``) turning by ``pos x f_i``: on a layer with a window
``f_i = theta ** (-2 i / dh)``, on a full layer YaRN's
``(1 - r_i) f_i + r_i f_i / factor`` with ``r_i = clip((i - low) / (high -
low), 0, 1)``, ``low = floor(dh ln(L / (beta_fast 2 pi)) / (2 ln theta))``,
``high = ceil(dh ln(L / (beta_slow 2 pi)) / (2 ln theta))``, ``L`` the
original positions, and cos and sin both times ``attention_factor``; query
head ``j`` meets key/value head ``j // (H / G)``; scores ``q.k /
sqrt(dh)`` for ``k_pos <= q_pos`` and, with a window ``w``, ``q_pos -
k_pos < w``; softmax; ``out Wo``.

``MoE``: ``r = x Wg`` over ALL E experts, ``p = softmax(r)``, the K largest
kept and divided by their sum; this rank holds experts ``first .. first +
held``, whose weights are the (held, d, f) arrays it is given, and adds
``gate_e x W_down,e (silu(W_gate,e x) * W_up,e x)`` for each of them; what
the absent experts would add is left out, and that partial result goes on.

Loss: mean next-token cross entropy + ``balance_coef`` x ``E sum_e f_e
P_e`` (``f_e`` the share of tokens that chose ``e`` over all E, summed over
their K choices, ``P_e`` the mean of ``p[:, e]``; over every token of the
step and every layer) + ``z_coef`` x the mean squared log-sum-exp of
``r`` (0 in the published model).

Memory at the real sizes (8,192 positions): a head's scores are 268 MB, so
attention runs a head of a sequence at a time, the readout a sequence at a
time and the experts one at a time, each recomputed in the backward pass,
and so is every layer as a whole (``jax.checkpoint``), which changes no
number: three steps then fit beside 5.4 GB of float32 weights, moments
and gradients (``train`` says why they are a loop).

Callers wrap the call in ``jax.default_matmul_precision("highest")``.

TOLERANCES: ``LOSS_RTOL`` and ``GRAD_NORM_RTOL`` below, from this model's
own readings on the v5e (PERF.md section 6, PR 36, has the table); the
comment beside them says what each stands between and what ``correct``
cannot see at any limit.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

from benchmark import reference

# Read on the v5e at the cell's sizes, 2 sequences a step, with the family's
# seeded weights (``mellum_lm.ROUTER_SPREAD``: the router drawn x 4), against
# this file (my chip runs, PR 36, calls 19 and 21; PERF.md section 6 has the
# table and the four other draws that were read): the sound program on 18
# seeds - 15 on the generator's own path (``mixed_precision_grad`` +
# ``FTTrainState.apply_gradients``, and the fused raw step beside it), 3
# through ``run.py`` - and each control on 10.
#
# GRAD_NORM_RTOL stands between two readings. Below it: the sound
# program's first gradient norm, 1.2e-4 to 1.5e-3 (4e-3 is 2.7 times the
# largest). Above it: the next rank's experts (8-15 for 0-7), 8.8e-3 to
# 0.11 on 10 seeds of 10 (2.2 times the limit at the least); the weights
# through float8 e4m3, 5.4e-3 to 3.7e-2 on 9 seeds of 10 - the tenth read
# 9.0e-4 with losses of 7.5e-4 at most and passes; the top-8 not
# renormalised, 5.5e-3 to 1.1e-2 (3 of 3). Not read at this draw, and far
# above it at the two draws beside it (router x 1, and x 4 with the
# experts' down projections x 0.134; ``wo`` is the program's in all three):
# the window ignored (0.13-0.25), YaRN left off the full layer and
# attention_factor left out (0.029-0.040), head h meeting key/value head
# h % 4 (0.0079-0.053 on 5 seeds of 6, 1.6e-3 once). UNSEEN at any limit: a
# window off by one (4.6e-5 to 1.0e-3) and QK-norm over the whole
# projection (2.2e-4 to 3.5e-3), which
# ``tests/test_mellum.py::test_a_wrong_term_is_caught`` sees on the CPU in
# float32, as it does every fault above.
#
# LOSS_RTOL is NOT where this cell tells a wrong step from a sound one, and
# cannot be: the harness holds every loss to ONE limit - the three against
# this file and, in a traced run, the first five of the transaction's loop
# against the fused loop's (``traffic/ft_sync.py``: ``first_losses_match``)
# - so the latest, most chaotic reading sets it. Against this file the
# sound program's losses 0 and 1 read at most 1.4e-5 and 9.0e-5, loss 2 up
# to 7.3e-4; between the two bf16 loops, the same arithmetic fused two
# ways, loss 3 parts by up to 1.7e-3 and loss 4 by up to 3.7e-3 (16
# readings: from random weights each update is lr x sign(g), and a token
# whose eighth and ninth experts lie a rounding apart meets a HELD expert
# in one program and none in the other). 1.2e-2 is 3.2 times that. The
# controls' losses read 7.2e-3 at most, so no loss refuses one. Held a step
# at a time, losses 0 and 1 would stand at the accepted cells' 2e-4 with
# 2.2 times of room, under float8's 2.3e-4 and 7.5e-4 at the most but not
# at the least (2.6e-6, 2.4e-5): PERF.md section 7 asks the next
# ``benchmark`` issue for a limit a step, the loops under a limit of their
# own, and the parameters' change after the three steps.
LOSS_RTOL = 1.2e-2
GRAD_NORM_RTOL = 4e-3


def _rmsnorm(x: Any, scale: Any, eps: float) -> Any:
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _frequencies(kind: Any, theta: float, dh: int) -> Tuple[Any, float]:
    """The ``dh / 2`` pairs' turns a position and the factor on cos and
    sin, of one kind of layer."""
    import jax.numpy as jnp

    i = jnp.arange(dh // 2)
    plain = 1.0 / theta ** (2.0 * i / dh)
    if kind.yarn is None:
        return plain, 1.0
    y = kind.yarn

    def pair(turns: float) -> float:
        return dh * math.log(y.original_positions / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(y.beta_fast)), 0)
    high = min(math.ceil(pair(y.beta_slow)), dh - 1)
    r = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - r) * plain + r * plain / y.factor, y.attention_factor


def _rope(x: Any, freq: Any, factor: float) -> Any:
    """``x`` (S, heads, dh): each head's vector rotated, pair by pair, by
    its position times the pair's frequency."""
    import jax.numpy as jnp

    s, _, dh = x.shape
    half = dh // 2
    angle = jnp.arange(s)[:, None, None] * freq  # (S, 1, half)
    cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg: Any, kind: Any, x: Any, blk: Any) -> Any:
    """One sequence ``x`` (S, D), a query head at a time."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, g, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rmsnorm((x @ blk["wq"]).reshape(s, h, dh), blk["q_norm"], cfg.rms_norm_eps)
    k = _rmsnorm((x @ blk["wk"]).reshape(s, g, dh), blk["k_norm"], cfg.rms_norm_eps)
    v = (x @ blk["wv"]).reshape(s, g, dh)
    freq, factor = _frequencies(kind, cfg.rope_theta, dh)
    q, k = _rope(q, freq, factor), _rope(k, freq, factor)
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]  # q_pos - k_pos
    seen = back >= 0
    if kind.window is not None:
        seen = seen & (back < kind.window)

    @jax.checkpoint
    def head(j: Any, qj: Any) -> Any:
        kj, vj = k[:, j // (h // g)], v[:, j // (h // g)]  # (S, dh) each
        scores = jnp.where(seen, qj @ kj.T / math.sqrt(dh), -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        return (probs / jnp.sum(probs, axis=-1, keepdims=True)) @ vj

    heads = jax.lax.map(lambda a: head(*a), (jnp.arange(h), q.swapaxes(0, 1)))
    return heads.swapaxes(0, 1).reshape(s, h * dh) @ blk["wo"]


def _moe(cfg: Any, x: Any, blk: Any) -> Tuple[Any, Any]:
    """All the step's tokens ``x`` (N, D). Returns the held experts' part
    of the layer's output and ``(f, P, z)``: the share of tokens that
    chose each of the E experts, the mean router probability of each, and
    the mean squared log-sum-exp of the router's logits."""
    import jax
    import jax.numpy as jnp

    first, held = cfg.held_experts or (0, cfg.n_experts)
    r = x @ blk["router"]
    top = jnp.max(r, axis=-1, keepdims=True)
    lse = top[:, 0] + jnp.log(jnp.sum(jnp.exp(r - top), axis=-1))
    p = jnp.exp(r - lse[:, None])
    # the K-th largest probability of each token is its threshold
    kth = jnp.sort(p, axis=-1)[:, -cfg.experts_per_token][:, None]
    chose = p >= kth  # (N, E)
    gate = jnp.where(chose, p, 0.0)
    if cfg.renormalize_top_k:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    @jax.checkpoint
    def expert(w_gate: Any, w_up: Any, w_down: Any, g: Any) -> Any:
        a = x @ w_gate
        return g[:, None] * ((a / (1.0 + jnp.exp(-a)) * (x @ w_up)) @ w_down)

    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None), jnp.zeros_like(x),
        (blk["w_gate"], blk["w_up"], blk["w_down"], gate[:, first:first + held].T),
    )
    f = jnp.mean(chose.astype(jnp.float32), axis=0)
    return out, (f, jnp.mean(p, axis=0), jnp.mean(lse * lse))


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    """The training loss of ``tokens`` (int32[batch, seq]) under float32
    ``params``: the model runs on the first ``seq - 1`` positions and
    predicts the last ``seq - 1``."""
    import jax
    import jax.numpy as jnp

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    b, s = inputs.shape
    x = params["embed"][inputs]  # (B, S, D)
    f = p_mean = z = 0.0

    def layer(kind: Any, blk: Any, x: Any) -> Any:
        h = _rmsnorm(x, blk["ln1"]["scale"], cfg.rms_norm_eps)
        x = x + jax.lax.map(lambda xs: _attention(cfg, kind, xs, blk["attn"]), h)
        h = _rmsnorm(x, blk["ln2"]["scale"], cfg.rms_norm_eps)
        y, router = _moe(cfg, h.reshape(b * s, -1), blk["moe"])
        return x + y.reshape(x.shape), router

    for kind, blk in zip(cfg.layer_kinds, params["blocks"]):
        # a layer's activations are recomputed in the backward pass too
        x, (f_l, p_l, z_l) = jax.checkpoint(layer, static_argnums=0)(kind, blk, x)
        f, p_mean, z = f + f_l, p_mean + p_l, z + z_l
    layers = len(params["blocks"])
    balance = cfg.n_experts * jnp.sum((f / layers) * (p_mean / layers))

    @jax.checkpoint
    def sequence_nll(xs: Any, ts: Any) -> Any:
        logits = _rmsnorm(xs, params["ln_f"]["scale"], cfg.rms_norm_eps) @ params["readout"]
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        return -jnp.sum(jnp.take_along_axis(logp, ts[:, None], axis=-1))

    nll = jnp.sum(jax.lax.map(lambda a: sequence_nll(*a), (x, targets)))
    return nll / (b * s) + cfg.balance_coef * balance + cfg.z_coef * z / layers


def train(cfg: Any, params: Any, batches: Any) -> Tuple[Any, Any]:
    """Plain AdamW from ``params`` over ``batches`` (int32[steps, batch,
    seq]), one update a batch. Returns each step's loss and gradient
    norm, both taken before its update: ``(f32[steps], f32[steps])``."""
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    lr, b1, b2, eps, decay = (
        reference.LEARNING_RATE, reference.B1, reference.B2, reference.EPS,
        reference.WEIGHT_DECAY,
    )
    params = tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    zeros = tree_map(jnp.zeros_like, params)

    # Scanned, not unrolled as reference_olmoe.train is: unrolled, the three
    # steps compile to a 181 MB executable (0.78 GB of code), which alone
    # fills the 192 MiB compile cache the chip's machine allows, so every
    # run of the cell evicted its own programs (set-up 50-79 s against 24-27;
    # PERF.md section 6, PR 36). One step's code is a third of that. The
    # loop double-buffers the state: 13.5 GB of temporaries against 10.3 by
    # the compiler's memory analysis, on a chip the window has left empty.
    def step(state: Any, batch: Any) -> Any:
        p, m, v = state
        t, tokens = batch
        value, g = jax.value_and_grad(lambda q: loss(cfg, q, tokens))(p)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        m = tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        p = tree_map(
            lambda p, m, v: p - lr * (
                (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + decay * p
            ),
            p, m, v,
        )
        return (p, m, v), (value, norm)

    ts = jnp.arange(1, len(batches) + 1, dtype=jnp.float32)
    _, (losses, norms) = jax.lax.scan(step, (params, zeros, zeros), (ts, jnp.asarray(batches)))
    return losses, norms
