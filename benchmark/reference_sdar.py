"""The plain reference of the ``sdar_lm`` block and of its training step:
float32 ``jax.numpy`` from the tokens to the loss, the 2 L x 2 L mask
written out pair by pair, dense masked attention a head at a time, every
held expert applied to EVERY token and kept where the token chose it; no
kernel, no sort, no tile, no bf16 copy, AdamW written out with
``reference.py``'s constants. Written from the equations below (the
published ``config.json`` of JetLM/SDAR-30B-A3B-Chat and, where it is
silent, the ``assumed`` list of the configuration file), not from the
program's code: it imports nothing of ``torchft_tpu`` and reads the
configuration's attributes by name only. The expert layer's equations are
Mellum2's (both are Qwen3-MoE's), so ``_moe`` and ``_rmsnorm`` are
``reference_mellum.py``'s, imported and not copied.

A sequence ``x`` of L tokens in blocks of B (``blk(i) = i // B``), noise
``t`` a sequence and ``m_i`` a position (``noise``), ``x~_i`` the mask token
where ``m_i`` and else ``x_i``. The stack runs on the 2 L positions of the
clean copy c = ``x`` and, after it, the noised copy n = ``x~``, both at
rotary positions 0..L-1. Per layer, pre-norm:

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))

``Attn``: ``q = x Wq`` (H heads of ``dh``), ``k = x Wk``, ``v = x Wv`` (G
heads each); RMSNorm of q and of k over each head's ``dh`` values with a
learned scale shared by the heads; rotary embedding of q and k, the pair
(``i``, ``i + dh / 2``) turning by ``pos x theta ** (-2 i / dh)``; query
head ``j`` meets key/value head ``j // (H / G)``; scores ``q.k / sqrt(dh)``
where the mask M shows the pair (``visible``):

- a query of c at i sees the keys of c at j with ``blk(j) <= blk(i)``, and
  nothing of n;
- a query of n at i sees the keys of c at j with ``blk(j) < blk(i)`` and
  the keys of n at j with ``blk(j) == blk(i)``;

softmax; ``out Wo``. ``MoE``: ``reference_mellum._moe`` (softmax over ALL E
experts, the K largest divided by their sum, this rank's held experts'
part of the result).

Loss: with ``z_i`` the logits ``RMSNorm_f(y) W_out`` of copy n at i,
``(1 / (batch L)) sum_seq (1 / t) sum_i m_i CE(z_i, x_i)`` - the position's
OWN token, no shift, the masked positions alone, taken from ``m`` and never
from comparing ids - + ``balance_coef`` x ``E sum_e f_e P_e`` over every one
of the 2 L positions of the step and every layer.

THE NOISE IS DATA, as the weights are: ``noise`` below is this file's own
copy of the draw the program makes from the batch - the same ``jax.random``
calls on the same checksum of the tokens - because a reference that drew
other noise would be the reference of another batch. What it is held to is
everything DONE with ``(t, m)``.

Memory at the real sizes (2 sequences of 8,192 positions): a head's scores
are 268 MB, so attention runs a head of a sequence at a time, the readout a
sequence at a time and the experts one at a time, each recomputed in the
backward pass, and so is every layer as a whole (``jax.checkpoint``), which
changes no number; the steps are a ``lax.scan`` (``reference_mellum.train``
says why).

Callers wrap the call in ``jax.default_matmul_precision("highest")``.

TOLERANCES: ``LOSS_RTOL`` and ``GRAD_NORM_RTOL`` below, from this model's
own readings on the v5e (PERF.md section 6, PR 46, has the table); the
comment beside them says what each stands between and what ``correct``
cannot see at any limit.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

from benchmark import reference
from benchmark.reference_mellum import _moe, _rmsnorm

# Read on the v5e at the cell's sizes, 2 sequences a step, with the family's
# seeded weights (the router drawn x 4), against this file (my chip runs, PR
# 46, calls 1 and 2; PERF.md section 6 has the table): the sound program on
# 16 seeds - 8 through ``run.py``, 8 on the generator's own path
# (``mixed_precision_grad`` + ``FTTrainState.apply_gradients``) - and each
# control on those 8, planted in the program where it has a switch (the
# rotary positions, the loss's weight, which experts the weights are said to
# be, the weights through float8) and in this file where the mask is a matrix.
#
# GRAD_NORM_RTOL stands between two readings. Below it: the sound program's
# first gradient norm, 7.0e-5 to 4.7e-3 (median 1.4e-3; 8e-3 is 1.7 times
# the largest: a batch of two sequences weighs one of them by 1 / t, and the
# discrete routing of 131,072 claims a layer moves with a rounding, so this
# cell's sound reading is three times ``mellum2-ft1``'s). Above it: the
# weights through float8 e4m3 (``reduce_precision``: at this scale most of
# them flush), 0.80 to 0.98 on 8 seeds of 8; the weight 1 / t left out, 0.35
# to 0.95 (8 of 8); the noised copy's queries under a plain causal mask,
# 6.8e-3 to 0.12 (7 of 8); the next rank's experts (16-31 for 0-15), 6.3e-3
# to 0.12 (7 of 8); the noised copy at rotary positions L..2L-1, 5.7e-4 to
# 5.9e-2 (5 of 8). NOT seen here on most seeds: the noised copy seeing the
# clean keys of its OWN block (``<=`` for ``<``), 4.7e-5 to 8.3e-3 (1 of 8):
# from random weights attention averages thousands of keys nearly evenly,
# and four more among them move the output like a rounding; a trained model
# would read the answer there. ``tests/test_sdar.py`` sees it, exactly, in the
# kernels (a noised query's output is unmoved, bit for bit, by a clean key of
# its own block) and in float32 against this file
# (``test_a_wrong_term_is_caught``), as it does every fault above.
#
# LOSS_RTOL is ``mellum2-ft1``'s, the accepted routed cell's, and for its
# reason: the harness holds every loss to ONE limit, the three against this
# file (the sound program's read 4.9e-5 at most at step 0, 5.6e-4 at step 1,
# 1.5e-3 at step 2: eight times of room) and, in a traced run, the first five
# of the transaction's loop against the fused loop's, the same arithmetic
# fused two ways under a routing that a rounding moves (read once: 5.5e-5 at
# step 4). No loss refuses a control but the two gross ones (float8 on 3
# seeds of 8, the weight on 8); at 2e-3 the losses would refuse the causal
# mask, the positions and the next rank's experts on 6 of 8 each and the
# sound program on none of 16, which a limit a step would allow (PERF.md
# section 7).
LOSS_RTOL = 1.2e-2
GRAD_NORM_RTOL = 8e-3


def noise(cfg: Any, tokens: Any) -> Tuple[Any, Any]:
    """``t`` (batch,) uniform on [noise_floor, 1] and ``m`` (batch, L), each
    position masked with probability ``t``: the program's draw, call for
    call (module docstring) - a key from ``noise_seed`` and a checksum of
    the tokens (each token + 1 times an odd number of its place, summed in
    uint32), split in two."""
    import jax
    import jax.numpy as jnp

    words = tokens.reshape(-1).astype(jnp.uint32)
    place = jnp.arange(words.size, dtype=jnp.uint32)
    checksum = jnp.sum(
        (words + 1) * (place * jnp.uint32(2654435761) + jnp.uint32(40503)), dtype=jnp.uint32
    )
    t_key, m_key = jax.random.split(
        jax.random.fold_in(jax.random.PRNGKey(cfg.noise_seed), checksum)
    )
    t = jax.random.uniform(t_key, tokens.shape[:1], jnp.float32, cfg.noise_floor, 1.0)
    return t, jax.random.uniform(m_key, tokens.shape, jnp.float32) < t[:, None]


def positions(length: int) -> Any:
    """The rotary position of each of the 2 L rows: both copies count from 0."""
    import jax.numpy as jnp

    return jnp.concatenate([jnp.arange(length), jnp.arange(length)])


def visible(length: int, block: int) -> Any:
    """M, (2 L, 2 L) bool, a row a query and a column a key, the clean copy
    in 0..L-1 and the noised copy in L..2L-1: the two sentences of the
    module docstring, pair by pair."""
    import jax.numpy as jnp

    row = jnp.arange(2 * length)
    noised = row >= length
    blk = jnp.where(noised, row - length, row) // block
    q_noised, k_noised = noised[:, None], noised[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    clean_query = ~q_noised & ~k_noised & (k_blk <= q_blk)
    noised_query = q_noised & (
        (~k_noised & (k_blk < q_blk)) | (k_noised & (k_blk == q_blk))
    )
    return clean_query | noised_query


def weight(t: Any) -> Any:
    """What a sequence's masked positions weigh in the loss: ``1 / t``."""
    return 1.0 / t


def _rope(x: Any, pos: Any, theta: float) -> Any:
    """``x`` (S, heads, dh): each head's vector rotated, pair by pair, by
    its row's position times the pair's frequency."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half) / half)
    angle = pos[:, None, None] * freq  # (S, 1, half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(cfg: Any, x: Any, blk: Any) -> Any:
    """One sequence's two copies ``x`` (2 L, D), a query head at a time."""
    import jax
    import jax.numpy as jnp

    s = x.shape[0]
    h, g, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _rmsnorm((x @ blk["wq"]).reshape(s, h, dh), blk["q_norm"], cfg.rms_norm_eps)
    k = _rmsnorm((x @ blk["wk"]).reshape(s, g, dh), blk["k_norm"], cfg.rms_norm_eps)
    v = (x @ blk["wv"]).reshape(s, g, dh)
    pos = positions(s // 2)
    q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
    seen = visible(s // 2, cfg.diffusion_block)

    @jax.checkpoint
    def head(j: Any, qj: Any) -> Any:
        kj, vj = k[:, j // (h // g)], v[:, j // (h // g)]  # (S, dh) each
        scores = jnp.where(seen, qj @ kj.T / math.sqrt(dh), -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        return (probs / jnp.sum(probs, axis=-1, keepdims=True)) @ vj

    heads = jax.lax.map(lambda a: head(*a), (jnp.arange(h), q.swapaxes(0, 1)))
    return heads.swapaxes(0, 1).reshape(s, h * dh) @ blk["wo"]


def loss(cfg: Any, params: Any, tokens: Any) -> Any:
    """The training loss of ``tokens`` (int32[batch, L]) under float32
    ``params``: every token is a target, the masked ones are read."""
    import jax
    import jax.numpy as jnp

    t, m = noise(cfg, tokens)
    b, length = tokens.shape
    both = jnp.concatenate([tokens, jnp.where(m, cfg.mask_token_id, tokens)], axis=1)
    x = params["embed"][both]  # (B, 2 L, D)
    f = p_mean = 0.0

    def layer(blk: Any, x: Any) -> Any:
        h = _rmsnorm(x, blk["ln1"]["scale"], cfg.rms_norm_eps)
        x = x + jax.lax.map(lambda xs: _attention(cfg, xs, blk["attn"]), h)
        h = _rmsnorm(x, blk["ln2"]["scale"], cfg.rms_norm_eps)
        y, router = _moe(cfg, h.reshape(b * 2 * length, -1), blk["moe"])
        return x + y.reshape(x.shape), router

    for blk in params["blocks"]:
        # a layer's activations are recomputed in the backward pass too
        x, (f_l, p_l, _) = jax.checkpoint(layer)(blk, x)
        f, p_mean = f + f_l, p_mean + p_l
    layers = len(params["blocks"])
    balance = cfg.n_experts * jnp.sum((f / layers) * (p_mean / layers))

    @jax.checkpoint
    def sequence_nll(xs: Any, ts: Any, ms: Any) -> Any:
        logits = _rmsnorm(xs, params["ln_f"]["scale"], cfg.rms_norm_eps) @ params["readout"]
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        own = jnp.take_along_axis(logp, ts[:, None], axis=-1)[:, 0]
        return -jnp.sum(jnp.where(ms, own, 0.0))

    nll = jax.lax.map(lambda a: sequence_nll(*a), (x[:, length:], tokens, m))
    return jnp.sum(weight(t) * nll) / (b * length) + cfg.balance_coef * balance


def train(cfg: Any, params: Any, batches: Any) -> Tuple[Any, Any]:
    """Plain AdamW from ``params`` over ``batches`` (int32[steps, batch,
    L]), one update a batch. Returns each step's loss and gradient norm,
    both taken before its update: ``(f32[steps], f32[steps])``. A scan, as
    ``reference_mellum.train`` is and for its reasons."""
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    lr, b1, b2, eps, decay = (
        reference.LEARNING_RATE, reference.B1, reference.B2, reference.EPS,
        reference.WEIGHT_DECAY,
    )
    params = tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    zeros = tree_map(jnp.zeros_like, params)

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
