"""The plain reference of the ``olmoe_lm`` block and of its training step:
float32 ``jax.numpy`` from the tokens to the loss, dense attention, no
kernel, no sort, no grouped matmul, no bf16 copy, and AdamW written out
with ``reference.py``'s constants. Written from the equations (the OLMoE paper,
arXiv:2409.02060, and ``transformers``' ``modeling_olmoe.py``), not from
the program's code, and sharing no dispatch with it: every expert is
applied to EVERY token and its output kept where the token chose it.

For activations ``x`` (S, D) of one sequence, per layer:

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))

``q = RMSNorm(x Wq)``, ``k = RMSNorm(x Wk)`` over the whole D-wide
projection, ``v = x Wv``; heads; rotary embedding on q and k (the pair
``i``, ``i + head_dim / 2`` turns by ``pos * theta ** (-2 i / head_dim)``);
causal softmax attention scaled by ``head_dim ** -0.5``; ``out Wo``.
``r = x Wg``, ``p = softmax(r)``, the K largest ``p`` kept with their
values as weights, not renormalised; expert ``e`` is ``W_down,e
(silu(W_gate,e x) * W_up,e x)``. Loss: mean next-token cross entropy +
``balance_coef`` x ``E sum_e f_e P_e`` + ``z_coef`` x
``mean(logsumexp(r) ** 2)``, with ``f_e`` the share of tokens that chose
``e`` (summed over their K choices), ``P_e`` the mean of ``p[:, e]``, all
over every token of the step and every layer.

Memory, at the real sizes on one 16 GB chip: the weights, two moments and
one gradient tree are 10 GB of float32, so the activations may not be
kept. Attention runs a head of a sequence at a time, the readout a
sequence at a time and the experts one at a time, each recomputed in the backward pass (``jax.checkpoint``),
which changes no number; the router's statistics are still taken over
the whole step's tokens.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.

TOLERANCES (``LOSS_RTOL``, ``GRAD_NORM_RTOL`` below, which
``common.check_first_steps`` reads through the family) are
``reference.py``'s values, 2e-4 and 1e-2. They are this family's to set
now; they stay the dense model's because the experts' start was chosen
to meet them (``families/olmoe_lm.EXPERT_SPREAD``), and a wider bound
with independent experts is a change of the cell that needs its own
readings (PERF.md section 7). They were set on a dense model, and one
thing is new here: the top-K choice is discrete. The program's router sees activations that came through
bf16 arithmetic, so for the tokens whose K-th and (K+1)-th probabilities
lie closer than that rounding - one in eighteen at these sizes - it
picks another expert than this reference does, with nearly the same
weight. The router's own precision is not the cause and not the cure: a
router held in float32 from unrounded inputs leaves 5.3% of 5.6%, and a
router in bf16 reads no differently on the chip. With independent random
experts that token meets another function, the bf16 gradient is 5% off
this one (GPT-2: 0.17%), AdamW's first step moves 2% of all entries the
other way, and losses 1 and 2 stand up to 2.7e-4 from these: over the
bound in one run of eight. The benchmark's weights therefore draw the
experts of a layer closer together (``families/olmoe_lm.init``, a
departure the configuration file states); what these bounds then see
and do not see - a misrouted dispatch in nine runs of ten; not the
router's type, not a dropped eighth claim, not weights rounded to eight
bits - is measured in PERF.md section 6, PR 26. Which expert a row meets
is held exactly, in float32 on independent experts, by
``tests/test_olmoe.py``.
"""

from __future__ import annotations

from typing import Any, Tuple

from benchmark import reference

# the dense model's values, kept (docstring, TOLERANCES): at EXPERT_SPREAD
# 0.5 the sound program's losses 1 and 2 stand 4.1e-5 rms, 1.24e-4 at most,
# from this reference over 22 seeds, a misrouted dispatch outside in 21
LOSS_RTOL = reference.LOSS_RTOL
GRAD_NORM_RTOL = reference.GRAD_NORM_RTOL


def _rmsnorm(x: Any, scale: Any, eps: float) -> Any:
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x: Any, theta: float) -> Any:
    """``x`` (S, heads, head_dim): each head's vector is rotated, pair by
    pair, by its position times the pair's frequency."""
    import jax.numpy as jnp

    s, _, dh = x.shape
    half = dh // 2
    freq = 1.0 / theta ** (2.0 * jnp.arange(half) / dh)
    angle = jnp.arange(s)[:, None, None] * freq  # (S, 1, half)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(angle) - b * jnp.sin(angle),
         b * jnp.cos(angle) + a * jnp.sin(angle)], axis=-1,
    )


def _attention(cfg: Any, x: Any, blk: Any) -> Any:
    """One sequence ``x`` (S, D), a head at a time."""
    import jax
    import jax.numpy as jnp

    s, d = x.shape
    h, dh = cfg.n_heads, d // cfg.n_heads
    q = _rmsnorm(x @ blk["wq"], blk["q_norm"], cfg.rms_norm_eps).reshape(s, h, dh)
    k = _rmsnorm(x @ blk["wk"], blk["k_norm"], cfg.rms_norm_eps).reshape(s, h, dh)
    v = (x @ blk["wv"]).reshape(s, h, dh)
    q, k = _rope(q, cfg.rope_theta), _rope(k, cfg.rope_theta)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def head(qkv: Any) -> Any:
        q, k, v = qkv  # (S, head_dim) each
        scores = jnp.where(causal, q @ k.T / jnp.sqrt(float(dh)), -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        return (probs / jnp.sum(probs, axis=-1, keepdims=True)) @ v

    heads = jax.lax.map(head, tuple(t.swapaxes(0, 1) for t in (q, k, v)))
    return heads.swapaxes(0, 1).reshape(s, d) @ blk["wo"]


def _moe(cfg: Any, x: Any, blk: Any) -> Tuple[Any, Any]:
    """All the step's tokens ``x`` (N, D). Returns the layer's output and
    ``(f, P, z)``: the share of tokens that chose each expert, the mean
    router probability of each, and the mean squared log-sum-exp of the
    router's logits."""
    import jax
    import jax.numpy as jnp

    r = x @ blk["router"]
    top = jnp.max(r, axis=-1, keepdims=True)
    lse = top[:, 0] + jnp.log(jnp.sum(jnp.exp(r - top), axis=-1))
    p = jnp.exp(r - lse[:, None])
    # the K-th largest probability of each token is its threshold
    kth = jnp.sort(p, axis=-1)[:, -cfg.experts_per_token][:, None]
    chose = p >= kth  # (N, E)
    gate = jnp.where(chose, p, 0.0)

    @jax.checkpoint
    def expert(w_gate: Any, w_up: Any, w_down: Any, g: Any) -> Any:
        a = x @ w_gate
        return g[:, None] * ((a / (1.0 + jnp.exp(-a)) * (x @ w_up)) @ w_down)

    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None), jnp.zeros_like(x),
        (blk["w_gate"], blk["w_up"], blk["w_down"], gate.T),
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
    for blk in params["blocks"]:
        h = _rmsnorm(x, blk["ln1"]["scale"], cfg.rms_norm_eps)
        x = x + jax.lax.map(lambda xs: _attention(cfg, xs, blk["attn"]), h)
        h = _rmsnorm(x, blk["ln2"]["scale"], cfg.rms_norm_eps)
        y, (f_l, p_l, z_l) = _moe(cfg, h.reshape(b * s, -1), blk["moe"])
        x = x + y.reshape(x.shape)
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

    # The steps are unrolled, not scanned: inside a loop over steps the
    # TPU compiler keeps the zero-initialised gradient accumulators alive
    # across iterations and double-buffers the state (19 GB at the real
    # sizes by its memory analysis, against 13 GB unrolled).
    m = v = tree_map(jnp.zeros_like, params)
    p, losses, norms = params, [], []
    for t, tokens in enumerate(batches, start=1):
        value, g = jax.value_and_grad(lambda q: loss(cfg, q, tokens))(p)
        losses.append(value)
        norms.append(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g))))
        m = tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        p = tree_map(
            lambda p, m, v: p - lr * (
                (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + decay * p
            ),
            p, m, v,
        )
    return jnp.stack(losses), jnp.stack(norms)
