"""The plain reference of the ``granite_lm`` model and of its training step:
float32 ``jax.numpy`` from the tokens to the loss, the state-space mixer as
its recurrence POSITION BY POSITION (a ``lax.scan`` over the positions, no
chunk, no decay matrix), attention as a dense masked softmax a head at a
time, the four multipliers and the tied readout written out, no kernel, no
bf16 copy, AdamW written out with ``reference.py``'s constants. Written from
the equations below (the published ``config.json`` of
ibm-granite/granite-4.0-h-micro; Mamba-2, arXiv:2405.21060; where both are
silent, the ``assumed`` list of the configuration file), not from the
program's code: it imports nothing of ``torchft_tpu`` (neither
``models/olmoe.py`` nor ``ops/ssd.py``) and reads the weights by their names
only. Every number of the model - the widths, the four multipliers, the
layers' types, eps - it takes from the PUBLISHED keys its callers hand it
(``published``: the configuration file's own keys), not from what
``models/granite.py`` made of them: a wrong factor there parts the two.

With ``h`` the residual stream, ``N`` an RMSNorm with a learned scale (eps
``rms_norm_eps``) and ``m_r`` = ``residual_multiplier``::

    h_0 = embedding_multiplier x E[tokens]
    a = h + m_r Mixer_i(N1(h))        h' = a + m_r FF(N2(a))
    logits = (N_f(h_L) E^T) / logits_scaling        (E the one tied matrix)

``FF(u) = W_down (silu(W_gate u) * W_up u)``. The loss is the mean next-token
cross entropy; no auxiliary term.

*Attention* (``layer_types[i] == "attention"``; ``_attention``): ``q, k, v =
W_q u, W_k u, W_v u`` - ``num_attention_heads`` / ``num_key_value_heads`` /
``num_key_value_heads`` heads of ``hidden_size / num_attention_heads`` - no
bias, no norm, NO rotary embedding; scores ``attention_multiplier x q.k`` for
``k_pos <= q_pos``, softmax, key/value head ``j`` serving the query heads ``g
j .. g j + g - 1`` (``g`` their ratio); ``y = W_o concat_h(o)``.

*Mamba-2* (``"mamba"``; ``_mamba``), ``H`` = ``mamba_n_heads`` heads of ``P``
= ``mamba_d_head``, a state of ``n`` = ``mamba_d_state``, one group: ``[z |
xBC | dt] = W_in u`` (``H P | H P + 2 n | H``); ``xBC = silu(conv(xBC) +
b)``, ``conv`` depthwise and causal over ``mamba_d_conv`` positions (the last
tap meets the position itself); ``[x | B | C] = xBC`` (``H P | n | n``); ``dt
= softplus(dt + dt_bias)`` a head; ``A = -exp(a_log)``; per head, from ``S_0
= 0`` (P x n)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``y = N_g(y * silu(z))``, ``N_g`` an RMSNorm over all ``H P`` channels at once
(the gate BEFORE the norm); ``out = W_o y``.

Memory at the real sizes (1 sequence of 4,096 positions, 772 M parameters):
float32 weights, gradients and two moments are 12.35 GB of the chip's 16, so
this is the tightest program of the cell and each choice below is for room
and changes no number. It runs after the window, when the measured state is
freed. Every layer is recomputed in the backward pass (``jax.checkpoint``);
consecutive layers of one type are ONE scanned body over their stacked
weights (``stacked``: five state-space layers, the attention layer, four
more), which also keeps the compiled program small; the recurrence runs in
blocks of ``_BLOCK`` positions, each recomputed in the backward pass (kept
whole, 4,096 states of 2 MB a layer would be 8.6 GB: a block keeps its 64
starting states and one block's steps, 0.3 GB); attention runs a head at a
time (a head's scores are 67 MB) and the readout under a checkpoint (the
logits are 0.2 GB). The three steps are one ``lax.scan`` whose carried state
(weights and two moments) is updated in place.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.

TOLERANCES: ``LOSS_RTOL`` and ``GRAD_NORM_RTOL`` below, from this model's own
readings on the v5e (PERF.md section 6, PR 58, has the table).
"""

from __future__ import annotations

from typing import Any, List, Mapping, Sequence, Tuple

from benchmark import reference

# Read on the v5e at the cell's sizes (1 sequence of 4,097 tokens) with the
# family's seeded weights - the program's own but for the attention layer's
# ``wq`` and ``wk``, drawn ``granite_lm.ATTENTION_SPREAD`` = 4 times wider -
# against this file's equations (my chip runs, PR 58, call 6; PERF.md section
# 6 has the table): the sound program and nine controls, each one wrong term
# planted in the PROGRAM, on 12 seeds through ``benchmark/controls_granite.py``,
# which holds each to this file by the harness's own comparison
# (``common.check_first_steps``), and the sound program again on every run of
# the cell through ``run.py``.
#
# GRAD_NORM_RTOL stands between two readings. Below it: the sound program's
# first gradient norm, 8.4e-6 to 8.6e-5 on the 12 seeds (median 4.8e-5; 6.4e-6
# to 1.1e-4 on the cell's own 18 runs of calls 7 and 8; 1.3e-5 to 9.3e-5 on 26
# seeds at the program's own draw): a dense model - no router
# for a rounding to tip - whose scan's decays, running sums and carried state
# are float32. Above it: the weights through float8 e4m3
# (``reduce_precision``), the nearest precision below the bf16 the
# configuration states, 3.3e-2 to 3.7e-2, refused on 12 seeds of 12. 1.5e-3
# is 14 times the largest sound reading (fresh seeds read higher, so the
# wider room is above) and a twentieth of the least float8 reading. Also
# refused on 12 of 12 by the norm: ``residual_multiplier`` left at 1 (0.21 to
# 0.23), ``head_dim ** -0.5`` for ``attention_multiplier`` (0.39 to 0.41: at
# the family's draw a score is 2 of a standard normal, and 16 at the wrong
# scale), the norm before the gate (3.8e-2 to 4.6e-2), ``D`` left out (4.2e-2
# to 8.4e-2), ``dt_bias`` left out (0.31 to 0.44).
#
# LOSS_RTOL is this family's own, and it is the ONE limit that sees the
# optimizer's update: step 0's norm cannot, and a state left unchanged between
# steps (``controls_granite.py``: ``no optimizer update``) shows only in losses
# 1 and 2. The harness holds TWO comparisons to it, and the larger of their
# sound readings is the one it has to clear. Against this file: the sound
# program's worst of three losses, 1.5e-6 to 1.9e-5 on the controls' 12 seeds
# and 3.5e-6 to 1.8e-5 on the cell's own 18 runs of calls 7 and 8 (2.8e-5 the
# largest of 26 seeds at the program's own draw). Against the fused loop, in a traced
# run (``traffic/ft_sync.py``: ``first_losses_match``): the first FIVE losses
# of the transaction's loop - the same arithmetic as two programs or as one,
# two compilations whose roundings drift apart update by update - 2.8e-6 to
# 2.2e-5 on eleven traced seeds of calls 7 and 8 and **4.96e-5 on the twelfth**
# (at its fifth loss; 1.6e-5 the largest of eight traced seeds before). Above: the unchanged
# state's worst loss, 1.0e-4 to 6.6e-4 on 12 seeds (7.0e-5 the least at the
# program's own draw). 7e-5 is the geometric mean of 4.96e-5 and 1.0e-4: 1.4
# times over the one and under the other, 3.7 times the largest reading
# against this file at this draw, and the unchanged state is refused on 12 seeds of 12.
# (The accepted dense cells' 2e-4 let it pass on 3 seeds of 12, REVIEW.md, PR
# 58; 5e-5, set before call 7, would have passed that sixth seed by 0.7%.)
# float8 weights are over it too on 12 of 12 (1.1e-4 to 5.0e-4). It is a
# narrow place to stand - an update moves a loss on the NEXT batch of random
# tokens by a few 1e-4, and five updates' roundings by up to 5e-5 - and a
# number of ``check_first_steps`` that saw the update itself, with
# ``first_losses_match`` on a limit of its own, would give both room (ROADMAP
# W14(g), (i); PERF.md section 7).
#
# NOT seen, and at no limit that leaves the sound program room: a rotary
# embedding applied (0 seeds of 12: its worst loss 2.8e-5 to 6.2e-5 lies above
# every reading against this file but astride the fused loop's 4.96e-5, its
# norm 4.0e-5 to 3.1e-4; at a draw of 8 it is refused on 3 seeds of 3, norm
# 3.4e-3 to 4.8e-3, but a score is then 8 of a standard normal, a softmax on
# one key, and the sound program's own norm reads up to 2.6e-4), and the decays'
# running sums at the default precision, one bf16 pass (0 of 12: worst loss
# 3.9e-6 to 2.2e-5, norm 1.7e-6 to 6.8e-5 - the steps ``dt A`` rounded to bf16
# move a decay by what the bf16 products around it already do).
# ``tests/test_granite.py`` holds both on the CPU in float32; PERF.md section 7
# says what a ``benchmark`` issue could do about them.
LOSS_RTOL = 7e-5
GRAD_NORM_RTOL = 1.5e-3

# positions a block of the recurrence (module docstring: memory, no number)
_BLOCK = 64


def _rmsnorm(x: Any, scale: Any, eps: float) -> Any:
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _sigmoid(x: Any) -> Any:
    """``1 / (1 + exp(-x))`` as ``(1 + tanh(x / 2)) / 2``: no ``exp`` that
    overflows in the gradient (``reference_ling._sigmoid`` says where it did)."""
    import jax.numpy as jnp

    return 0.5 * (1.0 + jnp.tanh(0.5 * x))


def _silu(x: Any) -> Any:
    return x * _sigmoid(x)


def _softplus(x: Any) -> Any:
    """``ln(1 + exp(x))`` without the overflow: ``max(x, 0) + ln(1 + exp(-|x|))``."""
    import jax.numpy as jnp

    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _attention(pub: Mapping[str, Any], u: Any, w: Any) -> Any:
    """One sequence ``u`` (S, D), a query head at a time."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    h, kv = pub["num_attention_heads"], pub["num_key_value_heads"]
    dh = pub["hidden_size"] // h
    q = (u @ w["wq"]).reshape(s, h, dh)
    k = (u @ w["wk"]).reshape(s, kv, dh)
    v = (u @ w["wv"]).reshape(s, kv, dh)
    serves = jnp.arange(h) // (h // kv)  # query head i reads key/value head i // g
    k, v = k[:, serves], v[:, serves]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scale = pub["attention_multiplier"]

    @jax.checkpoint
    def head(qj: Any, kj: Any, vj: Any) -> Any:
        scores = jnp.where(seen, qj @ kj.T * scale, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        return (probs / jnp.sum(probs, axis=-1, keepdims=True)) @ vj

    o = jax.lax.map(lambda a: head(*a), tuple(x.swapaxes(0, 1) for x in (q, k, v)))
    return o.swapaxes(0, 1).reshape(s, h * dh) @ w["wo"]


def recurrence(x: Any, dt: Any, a: Any, b: Any, c: Any, d: Any) -> Any:
    """``y`` (S, H, P) of the state-space recurrence, position by position:
    ``x`` (S, H, P), ``dt`` (S, H), ``a`` and ``d`` (H,), ``b`` and ``c`` (S,
    n). In blocks of ``_BLOCK`` positions for the backward pass's memory
    alone; positions of step 0 pad the last block and leave the state be."""
    import jax
    import jax.numpy as jnp

    s, h, p = x.shape
    pad = -s % _BLOCK

    def blocks(t: Any) -> Any:
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(-1, _BLOCK, *t.shape[1:])

    def position(state: Any, now: Any) -> Tuple[Any, Any]:
        x_t, dt_t, b_t, c_t = now
        state = (
            jnp.exp(dt_t * a)[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        )
        return state, jnp.sum(state * c_t, axis=-1) + d[:, None] * x_t

    @jax.checkpoint
    def block(state: Any, these: Any) -> Tuple[Any, Any]:
        return jax.lax.scan(position, state, these)

    _, y = jax.lax.scan(
        block, jnp.zeros((h, p, b.shape[-1]), jnp.float32),
        tuple(blocks(t) for t in (x, dt, b, c)),
    )
    return y.reshape(-1, h, p)[:s]


def _mamba(pub: Mapping[str, Any], u: Any, w: Any) -> Any:
    """One sequence ``u`` (S, D)."""
    import jax.numpy as jnp

    s = u.shape[0]
    h, p, n = pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"]
    taps, inner = pub["mamba_d_conv"], h * p
    into = u @ w["w_in"]
    z, xbc, dt = into[:, :inner], into[:, inner:2 * inner + 2 * n], into[:, 2 * inner + 2 * n:]
    before = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = _silu(sum(before[j:j + s] * w["conv"][j] for j in range(taps)) + w["conv_bias"])
    x, b, c = xbc[:, :inner], xbc[:, inner:inner + n], xbc[:, inner + n:]
    y = recurrence(
        x.reshape(s, h, p), _softplus(dt + w["dt_bias"]), -jnp.exp(w["a_log"]), b, c, w["d"]
    )
    y = _rmsnorm(y.reshape(s, inner) * _silu(z), w["norm"], pub["rms_norm_eps"])
    return y @ w["wo"]


_MIXERS = {"attention": _attention, "mamba": _mamba}


def types_of(pub: Mapping[str, Any], layers: Sequence[int]) -> List[str]:
    return [pub["layer_types"][i] for i in layers]


def stacked(pub: Mapping[str, Any], layers: Sequence[int], params: Any) -> Any:
    """``params`` with ``blocks`` as RUNS: consecutive layers of one type,
    each leaf of a run the layers' leaves stacked on a new first axis.
    ``loss_of_runs`` scans a run with ONE body (``reference_dsv2.stacked``
    says why); every leaf's numbers are the given tree's."""
    import jax
    import jax.numpy as jnp

    types, runs = types_of(pub, layers), []
    for i, blk in enumerate(params["blocks"]):
        if runs and types[i] == types[i - 1]:
            runs[-1].append(blk)
        else:
            runs.append([blk])
    return dict(params, blocks=[
        jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves), *run) for run in runs
    ])


def loss_of_runs(pub: Mapping[str, Any], layers: Sequence[int], params: Any, tokens: Any) -> Any:
    """``loss`` of ``stacked`` parameters."""
    import jax
    import jax.numpy as jnp

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    eps, m_r = pub["rms_norm_eps"], pub["residual_multiplier"]
    x = pub["embedding_multiplier"] * params["embed"][inputs]  # (B, S, D)
    types = types_of(pub, layers)

    def layer(mixer: Any, x: Any, blk: Any) -> Tuple[Any, None]:
        u = _rmsnorm(x, blk["ln1"]["scale"], eps)
        x = x + m_r * jax.lax.map(lambda us: mixer(pub, us, blk["attn"]), u)
        u, mlp = _rmsnorm(x, blk["ln2"]["scale"], eps), blk["mlp"]
        return x + m_r * ((_silu(u @ mlp["w_gate"]) * (u @ mlp["w_up"])) @ mlp["w_down"]), None

    first = 0
    for run in params["blocks"]:
        # a layer's activations are recomputed in the backward pass; the
        # layers of a run are alike in everything but their numbers
        mixer = _MIXERS[types[first]]
        x, _ = jax.lax.scan(
            jax.checkpoint(lambda x, blk, mixer=mixer: layer(mixer, x, blk)), x, run
        )
        first += jax.tree_util.tree_leaves(run)[0].shape[0]

    @jax.checkpoint
    def sequence_nll(xs: Any, ts: Any) -> Any:
        logits = _rmsnorm(xs, params["ln_f"]["scale"], eps) @ params["embed"].T
        logits = logits / pub["logits_scaling"]
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        return -jnp.sum(jnp.take_along_axis(logp, ts[:, None], axis=-1))

    nll = jnp.sum(jax.lax.map(lambda a: sequence_nll(*a), (x, targets)))
    return nll / inputs.size


def loss(pub: Mapping[str, Any], layers: Sequence[int], params: Any, tokens: Any) -> Any:
    """The training loss of ``tokens`` (int32[batch, seq]) under float32
    ``params`` (the program's tree, by its names): the model runs on the
    first ``seq - 1`` positions and predicts the last ``seq - 1``. ``pub``
    holds the published keys, ``layers`` the published layers that are run."""
    return loss_of_runs(pub, layers, stacked(pub, layers, params), tokens)


def grads(pub: Mapping[str, Any], layers: Sequence[int], params: Any, tokens: Any) -> Tuple[Any, Any]:
    """The loss and its gradient of every weight."""
    import jax

    return jax.value_and_grad(lambda p: loss(pub, layers, p, tokens))(params)


def train(
    pub: Mapping[str, Any], layers: Sequence[int], params: Any, batches: Any
) -> Tuple[Any, Any]:
    """Plain AdamW from ``params`` over ``batches`` (int32[steps, batch,
    seq]), one update a batch. Returns each step's loss and gradient norm,
    both taken before its update: ``(f32[steps], f32[steps])``. The state is
    held as ``stacked`` has it, which changes no number of any leaf; the
    steps are one scanned body (``reference_dsv2.train`` says why)."""
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    lr, b1, b2, eps, decay = (
        reference.LEARNING_RATE, reference.B1, reference.B2, reference.EPS,
        reference.WEIGHT_DECAY,
    )
    p = stacked(pub, layers, tree_map(lambda a: jnp.asarray(a, jnp.float32), params))
    zeros = tree_map(jnp.zeros_like, p)

    def step(state: Any, batch: Any) -> Any:
        p, m, v = state
        t, tokens = batch
        value, g = jax.value_and_grad(lambda q: loss_of_runs(pub, layers, q, tokens))(p)
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
    _, (losses, norms) = jax.lax.scan(step, (p, zeros, zeros), (ts, jnp.asarray(batches)))
    return losses, norms
