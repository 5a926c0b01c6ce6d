"""The plain reference of the ``ling_lm`` block and of its training step:
float32 ``jax.numpy`` from the tokens to the loss, the delta rule a POSITION
at a time, latent attention as a dense masked softmax a head at a time, the
router and its groups written out, every held expert applied to EVERY token
and kept where the token chose it; no kernel, no chunk algebra, no tile, no
bf16 copy, AdamW written out with ``reference.py``'s constants. Written from
the equations below (the published ``config.json`` of
inclusionAI/Ling-3.0-flash and, where it is silent, the ``assumed`` list of
the configuration file), not from the program's code: it imports nothing of
``torchft_tpu`` and reads the configuration's attributes and the weights'
names only.

Per layer, pre-norm, RMSNorm eps 1e-6: ``x = x + Mixer(N1(x))``, ``x = x +
FF(N2(x))``; a layer's kind carries ``mixer`` (with ``taps`` and ``floor``:
KDA; with ``latent`` and ``rope_dim``: MLA); ``dense_ff[i]`` is the width of
layer i's dense SwiGLU or None for experts. ``H`` heads of ``dh`` are held.

*KDA* (``_kda``), ``u`` the normed input, per position t and held head:
``q, k, v = SiLU(conv(W u))`` with ``conv_t = sum_j w_j x_{t-(taps-1)+j}`` a
channel (zeros before the sequence); ``q = q / |q| / sqrt(dh)``, ``k = k /
|k|`` (``|.|`` with 1e-6 under the root); ``g = floor x sigmoid(exp(A) (W_f u
+ b))`` a channel of the key, ``a = exp(g)``; ``beta = sigmoid(W_b u)``; ``S_t
= (I - beta k k^T) Diag(a) S_{t-1} + beta k v^T`` from ``S_0 = 0``; ``o = S_t^T
q``; ``y = W_o (RMSNorm(o) x sigmoid(W_g u))``, the norm over a head's ``dh``
with one learned scale. One ``lax.scan`` over the positions carries the
convolution's last inputs and ``S``; blocks of it are recomputed in the
backward pass.

*MLA* (``_mla``): ``q = W_q u`` (``dh + r`` a head); ``[c, k_r] = W_kva u``;
``c = RMSNorm(c)``; ``[k_nope, v] = W_kvb c`` (``2 dh`` a head); ``k =
[k_nope, k_r]``, ``k_r`` the same for every head; RMSNorm of q and of k over
each head's ``dh + r`` with a learned scale; the last ``r`` of both rotated,
the pair (2 i, 2 i + 1) by ``pos x theta ** (-2 i / r)``; causal softmax of
``q.k / sqrt(dh + r)``; ``y = W_o (o x sigmoid(W_gate u))``, one gate a head.

*Experts* (``_moe``): ``s = sigmoid(u W_r)`` over all E; on ``s + bias``: the
experts in ``groups`` groups, a group's mark the sum of its two largest, the
``kept`` groups of the largest marks, inside them the K largest; weights the
chosen ``s`` divided by their sum times ``router.scale``; the held experts'
part of the weighted sum, plus the shared SwiGLU of ``u``.

*The bias* has no gradient of the loss. Its entry in the gradient tree is
its expert's excess load, ``claims_e / (N K) - 1 / E`` (``grads``), which the
optimizer then steps like any leaf: the benchmark's generator owns the
optimizer, AdamW for every leaf (the configuration's ``departures``).

Loss: mean next-token cross entropy + ``balance_coef`` x ``E sum_e f_e P_e``,
``f_e`` the share of tokens that chose ``e`` (summed over the K choices) and
``P_e`` the mean of ``s_e / sum s``, both over every token and sparse layer.

Memory at the real sizes (1 sequence of 8,192 positions, 507.7 M parameters):
every layer, every head of MLA and every expert is recomputed in the
backward pass; the steps are a Python loop (``reference_ouro.train`` says
why: a scanned state of 6.1 GB is double-buffered).

Callers wrap the call in ``jax.default_matmul_precision("highest")``.

TOLERANCES: ``LOSS_RTOL`` and ``GRAD_NORM_RTOL`` below, from this model's own
readings on the v5e (PERF.md section 6, PR 50, has the table).
"""

from __future__ import annotations

import math
from typing import Any, List, Tuple

from benchmark import reference

# Read on the v5e at the cell's sizes (1 sequence of 8,193 tokens) with the
# family's seeded weights - the program's own: no departure in them - against
# this file (my chip runs, PR 50; PERF.md section 6 has the readings): the
# sound program on 14 runs of 13 seeds through ``run.py``, and the sound
# program and seven controls, each one wrong term planted in the PROGRAM, on
# 12 of those seeds through ``benchmark/controls_ling.py``, which holds each
# to this file by the harness's own comparison (``common.check_first_steps``)
# at the two limits below.
#
# GRAD_NORM_RTOL. Below it: the sound program's first gradient norm, 5.2e-4
# to 7.0e-3 (median 3.1e-3). That is three times ``mellum2-ft1``'s sound
# reading and for a reason of this model's own: q and k of five layers are
# divided by their L2 norm over 128 channels AFTER a bf16 projection, a
# convolution and a SiLU, so a bf16 rounding of the projection is carried
# into every score of the delta rule undamped, and the gradient through
# ``x / |x|`` scales with ``1 / |x|``. 1.4e-2 is twice the largest reading.
# Above it, each refused on 12 seeds of 12: the weights through float8 e4m3
# (``reduce_precision``), 3.0e-2 to 1.06e-1 (twice the limit at the least);
# the decay's bound at -1 for -5, 0.29 to 0.36; conv4 left out, 0.43 to 0.61.
# NOT seen at any limit here, 0 seeds of 12: the next rank's experts (8-15
# for 0-7), 4.1e-4 to 1.2e-2 - a norm does not know WHICH experts a rank
# holds, only how loaded they are, and the router drawn x 4
# (``mellum_lm.ROUTER_SPREAD``, the cure for the softmax routers) does not
# change that here: PERF.md section 6 has the reading; MLA without its
# causal mask, 7.0e-4 to 7.9e-3 - one layer in six, and from random weights
# attention averages thousands of keys nearly evenly (``sdar-ft1`` found the
# same); the bias left out of selection - step 0's norm is the sound
# program's to the last bit (the bias is 0 there) and losses 1 and 2 part
# from it by 2.9e-4 at most (the bias is 1e-3 to 2e-3 by then). One control
# cannot be planted: heads 8-15 for 0-7 (nothing depends on a head's index).
# ``tests/test_ling.py::test_a_wrong_term_is_caught`` sees every one of these
# on the CPU in float32, with a bias of 0.3.
#
# LOSS_RTOL is ``mellum2-ft1``'s and ``sdar-ft1``'s, the accepted routed
# cells', and for their reason: the harness holds every loss to ONE limit,
# the three against this file (the sound program's read 1.3e-4 at most at
# step 0, 1.1e-3 at step 1, 4.1e-3 at step 2: three times of room) and, in a
# traced run, the first five of the transaction's loop against the fused
# loop's (read twice: 1.2e-3 at most, at step 2). IT HAS NO UPPER READING:
# no control above reads past it (float8 weights 4.7e-3 to 8.9e-3, the
# decay's bound 9.1e-3 at most) - from random weights on random tokens the
# loss stands near log V + 0.5 whatever a layer computes, and three steps do
# not move it. The harness asks every family for one (``FAMILY_STATES``) and
# holds every cell to it, so it cannot be left out; what it would catch is a
# step gone wrong by a tenth of a nat. PERF.md section 7 asks for the repair.
LOSS_RTOL = 1.2e-2
GRAD_NORM_RTOL = 1.4e-2

# positions of the delta rule's scan that are recomputed together
_BLOCK = 64


def _rmsnorm(x: Any, scale: Any, eps: float) -> Any:
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _sigmoid(x: Any) -> Any:
    """``1 / (1 + exp(-x))`` as ``(1 + tanh(x / 2)) / 2``: the same number,
    with no ``exp`` that overflows at ``x < -88`` (the decay's argument
    reaches -100 at the cell's size) and turns the gradient into inf / inf."""
    import jax.numpy as jnp

    return 0.5 * (1.0 + jnp.tanh(0.5 * x))


def _swiglu(x: Any, w: Any) -> Any:
    a = x @ w["w_gate"]
    return (a * _sigmoid(a) * (x @ w["w_up"])) @ w["w_down"]


def delta_rule(q: Any, k: Any, v: Any, a: Any, beta: Any) -> Any:
    """``o`` (S, H, dv) of one sequence from ``q, k, a`` (S, H, dk), ``v``
    (S, H, dv) and ``beta`` (S, H), a position at a time."""
    import jax
    import jax.numpy as jnp

    def position(state: Any, x: Any) -> Any:
        qt, kt, vt, at, bt = x
        state = at[:, :, None] * state
        seen = jnp.einsum("hk,hkv->hv", kt, state)
        state = state + bt[:, None, None] * kt[:, :, None] * (vt - seen)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    s = q.shape[0]
    block = max(b for b in range(1, _BLOCK + 1) if s % b == 0)

    @jax.checkpoint
    def positions(state: Any, xs: Any) -> Any:
        return jax.lax.scan(position, state, xs)

    blocks = tuple(x.reshape((s // block, block) + x.shape[1:]) for x in (q, k, v, a, beta))
    state = jnp.zeros(k.shape[1:] + v.shape[2:], jnp.float32)
    _, out = jax.lax.scan(positions, state, blocks)
    return out.reshape((s,) + out.shape[2:])


def conv(x: Any, w: Any) -> Any:
    """``y_t = sum_j w_j x_{t - (taps - 1) + j}`` of ``x`` (S, C), a channel
    at a time, ``x`` zero before the sequence."""
    import jax.numpy as jnp

    taps, s = w.shape[0], x.shape[0]
    rows = jnp.arange(s)
    y = jnp.zeros_like(x)
    for j in range(taps):
        at = rows - (taps - 1) + j
        y = y + jnp.where((at >= 0)[:, None], x[jnp.maximum(at, 0)], 0.0) * w[j]
    return y


def _kda(cfg: Any, kind: Any, u: Any, w: Any) -> Any:
    """One sequence ``u`` (S, D)."""
    import jax.numpy as jnp

    s, h, dh = u.shape[0], cfg.n_heads, cfg.head_dim

    def heads(x: Any) -> Any:
        return x.reshape(s, h, dh)

    def unit(x: Any) -> Any:
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = (
        conv(u @ w[m], w[c]) for m, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v"))
    )
    q, k, v = (heads(x * _sigmoid(x)) for x in (q, k, v))
    q, k = unit(q) / math.sqrt(dh), unit(k)
    rate = jnp.exp(w["decay_a"])[:, None]
    g = kind.mixer.floor * _sigmoid(rate * (heads(u @ w["w_decay"]) + w["decay_bias"].reshape(h, dh)))
    beta = _sigmoid(u @ w["w_beta"])
    o = delta_rule(q, k, v, jnp.exp(g), beta)
    o = _rmsnorm(o, w["o_norm"], cfg.rms_norm_eps) * _sigmoid(heads(u @ w["w_gate"]))
    return o.reshape(s, h * dh) @ w["wo"]


def _rotated(x: Any, theta: float) -> Any:
    """``x`` (S, heads, r): the pair (2 i, 2 i + 1) of each head's vector
    turned by its position times ``theta ** (-2 i / r)``."""
    import jax.numpy as jnp

    s, _, r = x.shape
    freq = 1.0 / theta ** (2.0 * jnp.arange(r // 2) / r)
    angle = jnp.arange(s)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def _mla(cfg: Any, kind: Any, u: Any, w: Any, causal: bool = True) -> Any:
    """One sequence ``u`` (S, D), a head at a time."""
    import jax
    import jax.numpy as jnp

    s, h, dh = u.shape[0], cfg.n_heads, cfg.head_dim
    latent, r = kind.mixer.latent, kind.mixer.rope_dim
    q = (u @ w["wq"]).reshape(s, h, dh + r)
    down = u @ w["w_kva"]
    c = _rmsnorm(down[:, :latent], w["kv_norm"], cfg.rms_norm_eps)
    up = (c @ w["w_kvb"]).reshape(s, h, 2 * dh)
    k_r = jnp.broadcast_to(down[:, None, latent:], (s, h, r))
    k = jnp.concatenate([up[..., :dh], k_r], axis=-1)
    v = up[..., dh:]
    q = _rmsnorm(q, w["q_norm"], cfg.rms_norm_eps)
    k = _rmsnorm(k, w["k_norm"], cfg.rms_norm_eps)
    q = jnp.concatenate([q[..., :dh], _rotated(q[..., dh:], cfg.rope_theta)], axis=-1)
    k = jnp.concatenate([k[..., :dh], _rotated(k[..., dh:], cfg.rope_theta)], axis=-1)
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :] if causal else True

    @jax.checkpoint
    def head(qj: Any, kj: Any, vj: Any) -> Any:
        scores = jnp.where(seen, qj @ kj.T / math.sqrt(dh + r), -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        return (probs / jnp.sum(probs, axis=-1, keepdims=True)) @ vj

    o = jax.lax.map(lambda a: head(*a), tuple(x.swapaxes(0, 1) for x in (q, k, v)))
    o = o.swapaxes(0, 1) * _sigmoid(u @ w["w_gate"])[:, :, None]
    return o.reshape(s, h * dh) @ w["wo"]


def choice(cfg: Any, score: Any, bias: Any) -> Any:
    """(N, E) bool: the K experts each token chooses on ``score + bias``
    inside its ``kept`` best groups."""
    import jax.numpy as jnp

    n, e = score.shape
    pick = score + bias
    groups, kept = cfg.router.groups, cfg.router.kept
    grouped = pick.reshape(n, groups, e // groups)
    mark = jnp.sum(jnp.sort(grouped, axis=-1)[..., -2:], axis=-1)  # (N, groups)
    among = mark >= jnp.sort(mark, axis=-1)[:, -kept][:, None]
    pick = jnp.where(among[:, :, None], grouped, -jnp.inf).reshape(n, e)
    return pick >= jnp.sort(pick, axis=-1)[:, -cfg.experts_per_token][:, None]


def _moe(cfg: Any, x: Any, w: Any, with_bias: bool = True) -> Tuple[Any, Any]:
    """All the step's tokens ``x`` (N, D). Returns the held experts' part
    of the layer's output with the shared expert's, and ``(f, P, claims)``:
    the share of tokens that chose each of the E experts, the mean of each
    expert's score over the scores' sum, and how many tokens chose each."""
    import jax
    import jax.numpy as jnp

    first, held = cfg.held_experts or (0, cfg.n_experts)
    score = _sigmoid(x @ w["router"])
    chose = choice(cfg, score, w["bias"] if with_bias else 0.0)
    gate = jnp.where(chose, score, 0.0)
    gate = cfg.router.scale * gate / jnp.sum(gate, axis=-1, keepdims=True)

    @jax.checkpoint
    def expert(w_gate: Any, w_up: Any, w_down: Any, g: Any) -> Any:
        return g[:, None] * _swiglu(x, {"w_gate": w_gate, "w_up": w_up, "w_down": w_down})

    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None), jnp.zeros_like(x),
        (w["w_gate"], w["w_up"], w["w_down"], gate[:, first:first + held].T),
    )
    claims = jnp.sum(chose.astype(jnp.float32), axis=0)
    share = score / jnp.sum(score, axis=-1, keepdims=True)
    return out + _swiglu(x, w["shared"]), (claims / x.shape[0], jnp.mean(share, axis=0), claims)


def loss_and_claims(cfg: Any, params: Any, tokens: Any) -> Tuple[Any, List[Any]]:
    """The training loss of ``tokens`` (int32[batch, seq]) under float32
    ``params`` - the model runs on the first ``seq - 1`` positions and
    predicts the last ``seq - 1`` - and, a sparse layer, how many tokens
    chose each of the E experts."""
    import jax
    import jax.numpy as jnp

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    b, s = inputs.shape
    x = params["embed"][inputs]  # (B, S, D)
    f = p_mean = 0.0
    claims = []

    def layer(i: int, blk: Any, x: Any) -> Any:
        kind = cfg.layer_kinds[i]
        mixer = _kda if hasattr(kind.mixer, "taps") else _mla
        h = _rmsnorm(x, blk["ln1"]["scale"], cfg.rms_norm_eps)
        x = x + jax.lax.map(lambda us: mixer(cfg, kind, us, blk["attn"]), h)
        h = _rmsnorm(x, blk["ln2"]["scale"], cfg.rms_norm_eps)
        if cfg.dense_ff[i] is not None:
            return x + _swiglu(h, blk["mlp"]), None
        y, router = _moe(cfg, h.reshape(b * s, -1), blk["moe"])
        return x + y.reshape(x.shape), router

    for i, blk in enumerate(params["blocks"]):
        # a layer's activations are recomputed in the backward pass too
        x, router = jax.checkpoint(layer, static_argnums=0)(i, blk, x)
        if router is not None:
            f, p_mean = f + router[0], p_mean + router[1]
            claims.append(router[2])
    layers = len(claims)
    balance = cfg.n_experts * jnp.sum((f / layers) * (p_mean / layers))

    @jax.checkpoint
    def sequence_nll(xs: Any, ts: Any) -> Any:
        logits = _rmsnorm(xs, params["ln_f"]["scale"], cfg.rms_norm_eps) @ params["readout"]
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        return -jnp.sum(jnp.take_along_axis(logp, ts[:, None], axis=-1))

    nll = jnp.sum(jax.lax.map(lambda a: sequence_nll(*a), (x, targets)))
    return nll / (b * s) + cfg.balance_coef * balance, claims


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    return loss_and_claims(cfg, params, tokens)[0]


def grads(cfg: Any, params: Any, tokens: Any) -> Tuple[Any, Any]:
    """The loss and the gradient tree a step's optimizer is handed: the
    loss's gradient of every weight, and for every selection bias (of which
    the loss has none) its expert's excess load, ``claims_e / (N K) - 1 / E``."""
    import jax

    (value, claims), g = jax.value_and_grad(
        lambda p: loss_and_claims(cfg, p, tokens), has_aux=True
    )(params)
    claims = iter(claims)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    blocks = [
        blk if "moe" not in blk else dict(blk, moe=dict(
            blk["moe"],
            bias=next(claims) / (n * cfg.experts_per_token) - 1.0 / cfg.n_experts,
        ))
        for blk in g["blocks"]
    ]
    return value, dict(g, blocks=blocks)


def train(cfg: Any, params: Any, batches: Any) -> Tuple[Any, Any]:
    """Plain AdamW from ``params`` over ``batches`` (int32[steps, batch,
    seq]), one update a batch, every leaf alike (the biases too: module
    docstring). Returns each step's loss and gradient norm, both taken
    before its update: ``(f32[steps], f32[steps])``. A Python loop, as
    ``reference_ouro.train`` is and for its reason."""
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    lr, b1, b2, eps, decay = (
        reference.LEARNING_RATE, reference.B1, reference.B2, reference.EPS,
        reference.WEIGHT_DECAY,
    )
    p = tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    m = v = tree_map(jnp.zeros_like, p)
    losses, norms = [], []
    for t, tokens in enumerate(batches, start=1):
        value, g = grads(cfg, p, tokens)
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
