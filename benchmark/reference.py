"""The plain reference of the ``dense_lm`` block and of its training
step: float32 ``jax.numpy`` from the tokens to the loss, dense attention,
no kernel, no bf16 copy, and AdamW written out. Written from the
equations, not from the program's code: token plus learned position
embedding; per layer ``x += Attn(RMSNorm(x))`` and
``x += W_o gelu_tanh(W_i RMSNorm(x))``; causal softmax attention over
``n_heads`` heads of a fused QKV projection; final RMSNorm and the tied
readout; mean next-token cross entropy; ``m, v`` moments with bias
correction and decoupled weight decay. Departures from GPT-2 proper are
the program's and are listed in the configuration files.

Callers wrap the call in ``jax.default_matmul_precision("highest")``: on
a TPU a float32 matmul otherwise runs in bf16 passes.

TOLERANCES. The system computes in a bf16 copy of the f32 masters with
f32 accumulation; the reference keeps f32 throughout. Each bf16 rounding
is 2^-9 relative, and the loss is a mean over 10^4 positions of f32
log-softmaxes of logits that each carry a few such roundings, so the two
losses differ in the fifth or sixth digit; the gradient norm sums ~10^8
squared entries and differs by about one part in 10^3. After an update
the two sides no longer hold the same weights - AdamW's first steps move
every entry by about the learning rate in the direction of its
gradient's sign, so the entries whose gradient is smaller than its bf16
error go opposite ways - but those are the entries the loss depends on
least, and the losses stay as close. Read on the v5e at the real sizes
over a whole batch (PERF.md section 6, PR 23): losses 0, 1 and 2 within
1.2e-5 relative, the gradient norm of step 0 within 1.7e-3; on one
sequence, earlier, 3.2e-5 and 1.6e-3. The bounds below are about six
times the largest reading. What they catch: a model computed in a
narrower type than bf16, a missing term of the block, a mask off by one,
a gradient scaled wrongly; an optimizer that skips its updates or scales
them wrongly (at gpt2-small the reference's losses 1 and 2 stand 7.7e-4
and 4.2e-3 relative below those of a reference that never updates, four
and twenty times the bound). What they cannot catch: a wrong ``b2`` (the
bias correction cancels it over three steps), and the masters' own type
(a bf16 master rounds an update of 1e-3 away, but over three steps that
is inside the tolerance), so the harness checks that separately: every
master and optimizer leaf must be float32 (``masters_are_f32``).
"""

from __future__ import annotations

from typing import Any, Tuple

LOSS_RTOL = 2e-4
GRAD_NORM_RTOL = 1e-2

# optax.adamw(1e-3) with its defaults, which is what every cell trains with
LEARNING_RATE, B1, B2, EPS, WEIGHT_DECAY = 1e-3, 0.9, 0.999, 1e-8, 1e-4


def _rmsnorm(x: Any, scale: Any) -> Any:
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)) * scale


def _gelu_tanh(x: Any) -> Any:
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(n_heads: int, x: Any, blk: Any) -> Any:
    import jax.numpy as jnp

    b, s, d = x.shape
    dh = d // n_heads
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    h = _rmsnorm(x, blk["ln1"]["scale"])
    qkv = h @ blk["attn"]["wqkv"]
    q, k, v = (
        qkv[..., i * d:(i + 1) * d].reshape(b, s, n_heads, dh) for i in range(3)
    )
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    scores = jnp.where(causal, scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, d)
    x = x + attn @ blk["attn"]["wo"]
    h = _rmsnorm(x, blk["ln2"]["scale"])
    return x + _gelu_tanh(h @ blk["mlp"]["wi"]) @ blk["mlp"]["wo"]


def stacked(params: Any) -> Any:
    """The same weights in float32 with the layers' blocks stacked along a
    new first axis, so that one program of one block serves every layer
    (a twelfth of the code to compile, the same numbers)."""
    import jax
    import jax.numpy as jnp

    params = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    return dict(
        params,
        blocks=jax.tree_util.tree_map(lambda *a: jnp.stack(a), *params["blocks"]),
    )


def loss(n_heads: int, params: Any, tokens: Any) -> Any:
    """Mean next-token cross entropy of ``tokens`` (int32[batch, seq]) under
    ``stacked`` weights: the model runs on the first ``seq - 1`` positions
    and predicts the last ``seq - 1``. Each block is recomputed in the
    backward pass (``jax.checkpoint``), which changes no number and keeps
    one layer's S x S scores in memory instead of every layer's."""
    import jax
    import jax.numpy as jnp

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs] + params["pos_embed"][:inputs.shape[1]]
    block = jax.checkpoint(lambda x, blk: (_block(n_heads, x, blk), None))
    x, _ = jax.lax.scan(block, x, params["blocks"])
    logits = _rmsnorm(x, params["ln_f"]["scale"]) @ params["embed"].T
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss_and_grads(n_heads: int, params: Any, tokens: Any) -> Tuple[Any, Any]:
    """Loss and gradient of a whole batch, one sequence at a time (every
    sequence has as many positions, so the mean of the sequences' means
    is the batch's mean)."""
    import jax
    import jax.numpy as jnp

    def one(total: Any, sequence: Any) -> Tuple[Any, None]:
        value, grads = jax.value_and_grad(
            lambda p: loss(n_heads, p, sequence[None])
        )(params)
        return jax.tree_util.tree_map(jnp.add, total, (value, grads)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(one, zero, tokens)
    return jax.tree_util.tree_map(lambda a: a / tokens.shape[0], total)


def train(n_heads: int, params: Any, batches: Any) -> Tuple[Any, Any]:
    """Plain AdamW from ``params`` over ``batches`` (int32[steps, batch,
    seq]), one update a batch. Returns each step's loss and gradient
    norm, both taken before its update: ``(f32[steps], f32[steps])``."""
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    params = stacked(params)

    def step(carry: Any, xs: Any) -> Any:
        p, m, v = carry
        t, tokens = xs
        value, g = loss_and_grads(n_heads, p, tokens)
        norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree_util.tree_leaves(g)))
        m = tree_map(lambda m, g: B1 * m + (1 - B1) * g, m, g)
        v = tree_map(lambda v, g: B2 * v + (1 - B2) * g * g, v, g)
        p = tree_map(
            lambda p, m, v: p - LEARNING_RATE * (
                (m / (1 - B1 ** t)) / (jnp.sqrt(v / (1 - B2 ** t)) + EPS)
                + WEIGHT_DECAY * p
            ),
            p, m, v,
        )
        return (p, m, v), (value, norm)

    zeros = tree_map(jnp.zeros_like, params)
    t = jnp.arange(1, batches.shape[0] + 1, dtype=jnp.float32)
    _, out = jax.lax.scan(step, (params, zeros, zeros), (t, batches))
    return out
