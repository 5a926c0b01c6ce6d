"""The plain reference of the ``nemotron_lm`` model and of its training step:
float32 ``jax.numpy`` from the tokens to the loss, the state-space mixer as
its recurrence POSITION BY POSITION with its groups (a ``lax.scan`` over the
positions, no chunk, no decay matrix), attention as a dense masked softmax a
head at a time, the sigmoid router's choice written out, each held expert
applied to EVERY token, no kernel, no bf16 copy, AdamW written out with
``reference.py``'s constants. Written from the equations below (the published
``config.json`` of nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type``
``nemotron_h``; Mamba-2, arXiv:2405.21060; DeepSeek-V3's router,
arXiv:2412.19437; where they are silent, the ``assumed`` list of the
configuration file), not from the program's code: it imports nothing of
``torchft_tpu`` (neither ``models/olmoe.py`` nor ``ops/ssd.py``) and reads the
weights by their names only. Every number of the model - the widths, the
pattern, the groups, the router's width and scale, eps - it takes from the
PUBLISHED keys its callers hand it (``pub``: the configuration file's own
keys, ``pub["published"]`` where a key was reduced), not from what
``models/nemotron.py`` made of them: a wrong factor there parts the two.

With ``h`` the residual stream and ``N`` an RMSNorm with a learned scale (eps
``layer_norm_epsilon``), EVERY LAYER IS ONE SUBLAYER::

    h_0 = E[tokens]
    h' = h + Mixer_i(N_i(h))      Mixer_i by hybrid_override_pattern[i]: M, * or E
    logits = N_f(h_L) R            (R the untied readout)

*Mamba-2* (``M``; ``_mamba``), ``H`` = ``mamba_num_heads`` heads of ``P`` =
``mamba_head_dim``, a state of ``n`` = ``ssm_state_size``, ``G`` = ``n_groups``
groups: ``[z | xBC | dt] = W_in u`` (``H P | H P + 2 G n | H``); ``xBC =
silu(conv(xBC) + b)``, ``conv`` depthwise and causal over ``conv_kernel``
positions (the last tap meets the position itself); ``[x | B | C] = xBC``
(``H P | G n | G n``); ``dt = softplus(dt + dt_bias)`` a head; ``A =
-exp(a_log)``; head ``h`` reads group ``g = h // (H / G)``; from ``S_0 = 0``
(P x n)::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{t,g}^T        y_t = S_t C_{t,g} + D_h x_t

``y = N_g(y * silu(z))``: the gate BEFORE the norm, the norm's statistic over
each group's ``H P / G`` channels, one learned scale of ``H P``; ``out = W_o y``.

*Attention* (``*``; ``_attention``): ``q, k, v = W_q u, W_k u, W_v u`` -
``num_attention_heads`` / ``num_key_value_heads`` / ``num_key_value_heads``
heads of ``head_dim`` - no bias, no norm, NO rotary embedding; scores
``head_dim ** -0.5 q.k`` for ``k_pos <= q_pos``, softmax, key/value head ``j``
serving the query heads ``g j .. g j + g - 1`` (``g`` their ratio); ``y = W_o
concat_h(o)``.

*Experts* (``E``; ``_moe``): ``s = sigmoid(u W_r)`` over all ``E`` =
``published.n_routed_experts``; the ``K`` = ``num_experts_per_tok`` largest
``s + bias`` (``n_group`` = ``topk_group`` = 1: no group is closed); weights
the chosen ``s`` WITHOUT the bias, over their sum (+ 1e-20), times
``routed_scaling_factor``; ``F(u) = W_down relu(W_up u) ** 2``, no gate and no
bias; the part of ``sum_k w_k F_{e_k}(u)`` that the HELD experts give
(``n_routed_experts`` of them from ``deployment.rank`` x that on) plus the
shared expert ``F_shared(u)``, whole.

*The bias* has no gradient of the loss. Its entry in the gradient tree is its
expert's excess load, ``claims_e / (N K) - 1 / E`` (``grads``), which the
optimizer then steps like any leaf: the benchmark's generator owns the
optimizer, AdamW for every leaf (the configuration's ``departures``).

Loss: mean next-token cross entropy + ``assumed.balance_coef`` x ``E sum_e f_e
P_e``, ``f_e`` the share of tokens that chose ``e`` (summed over the K
choices) and ``P_e`` the mean of ``s_e / sum s``, both over every token and
sparse layer.

Memory at the real sizes (1 sequence of 8,192 positions, 667 M parameters):
float32 weights, gradients and two moments are 10.67 GB of the chip's 16, so
this is the tightest program of the cell and each choice below is for room
and changes no number. It runs after the window, when the measured state is
freed. Every layer is recomputed in the backward pass (``jax.checkpoint``);
the recurrence runs in blocks of ``_BLOCK`` positions, each recomputed in the
backward pass (kept whole, 8,192 states of 2 MB a layer would be 17 GB: a
block keeps its 128 starting states and one block's steps, 0.4 GB); attention
runs a head at a time (a head's scores are 268 MB); the held experts are ONE
scanned body, each recomputed (an expert's hidden rows are 61 MB); the
readout under a checkpoint (the logits are 0.5 GB). The three steps are one
``lax.scan`` whose carried state (weights and two moments) is updated in
place, which also keeps the compiled program to one step's code.

Callers wrap the call in ``jax.default_matmul_precision("highest")``.

TOLERANCES: ``LOSS_RTOL`` and ``GRAD_NORM_RTOL`` below, from this model's own
readings on the v5e (PERF.md section 6, PR 60, has the table).
"""

from __future__ import annotations

from typing import Any, List, Mapping, Sequence, Tuple

from benchmark import reference

# Read on the v5e at the cell's sizes (1 sequence of 8,193 tokens) with the
# family's seeded weights - the program's own: no departure in them - against
# this file's equations (my chip runs, PR 60, calls A and B; PERF.md section 6
# has the table): the sound program and eleven controls, each one wrong term
# planted in the PROGRAM, on 12 seeds through
# ``benchmark/controls_nemotron.py``, which holds each to this file by the
# harness's own comparison (``common.check_first_steps``), and the sound
# program again on every run of the cell through ``run.py``.
#
# GRAD_NORM_RTOL stands between two readings. Below it: the sound program's
# first gradient norm, 6.5e-7 to 1.9e-4 on the 12 seeds (median 6.6e-5) and
# 2.4e-5 to 2.6e-4 on the cell's own 13 runs of calls A, B and C (13 further
# seeds): the routed experts carry a twentieth
# of the weights a position multiplies and step 0's choice of six experts is
# the float32 router's on bf16 activations, so few choices tip. Above it: a
# gated SiLU expert for ``relu ** 2``, 5.2e-3 to 8.0e-3, and the square left
# out, 1.8e-2 to 2.8e-2, each refused on 12 seeds of 12. 1.5e-3 is 5.8 times
# the largest of the 25 sound readings (fresh seeds read higher, so the wider
# room is above them; their geometric mean with 5.2e-3 is 1.2e-3) and 3.5
# times under the least reading of the gated expert. Also refused by the norm:
# the norm over all 4,096 channels for the norm a group on 11 seeds of 12
# (5.6e-4 to 1.7e-2), one group of B and C for eight on 10 (6.0e-4 to
# 1.6e-2), the top-6 not renormalised on 11, float8 weights on 10 (4.1e-4 to
# 1.1e-2: the loss refuses all twelve).
#
# LOSS_RTOL stands between two readings too, and the harness holds TWO
# comparisons to it. Against this file, the worst of three losses: 1.3e-4 to
# 4.9e-4 on the controls' 12 seeds and 3.3e-5 to 8.1e-4 on the cell's own 13 runs
# (always the third loss: two AdamW updates at 1e-3 on a state whose routers
# move by the rate whatever the gradient's size). Against the fused loop, in
# a traced run (``traffic/ft_sync.py``: ``first_losses_match``), the first
# FIVE losses of the transaction's loop: 1.3e-4 to 9.5e-4 on six traced seeds (the
# largest at the fifth loss). Above: the weights
# through float8 e4m3 (``reduce_precision``), the nearest precision below the
# bf16 the configuration states, 1.3e-2 to 2.0e-2, and a state left unchanged
# between steps, 3.3e-2 to 4.4e-2, each refused on 12 seeds of 12; the top-6
# not renormalised, 8.1e-3 to 2.0e-2, on 12 of 12. 5e-3 is 5.3 times the largest
# sound reading of either comparison and 2.6 times under float8's least (the
# geometric mean of 9.5e-4 and 1.3e-2 is 3.5e-3; the room above the readings is
# the wider because two bf16 loops of a routed model part update by update:
# ``dsv2lite-ft1`` read 3e-3 to 4e-3 there by its fifth loss, PERF.md section
# 6, PR 53). The accepted routed cells' 1.2e-2 would let float8 weights pass
# on seeds that read 1.3e-2: this cell's precision DOES move its loss, so its
# limit stands between the two readings and is not theirs.
#
# NOT seen at these limits (PERF.md section 7): a rotary embedding applied (0
# seeds of 12: one layer in nine, scores of 1 of a standard normal), the bias
# added to the weights (0: the bias is 0 at step 0 and 1e-3 after an update),
# ``routed_scaling_factor`` left out (2), the next rank's experts (2).
# ``tests/test_nemotron.py`` holds every one of them on the CPU in float32.
LOSS_RTOL = 5e-3
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


def _relu2(u: Any, w_up: Any, w_down: Any) -> Any:
    """``W_down relu(W_up u) ** 2``: the ungated feed-forward, two matrices."""
    import jax.numpy as jnp

    hidden = jnp.maximum(u @ w_up, 0.0)
    return (hidden * hidden) @ w_down


def _attention(pub: Mapping[str, Any], u: Any, w: Any) -> Any:
    """One sequence ``u`` (S, D), a query head at a time."""
    import jax
    import jax.numpy as jnp

    s = u.shape[0]
    h, kv, dh = pub["num_attention_heads"], pub["num_key_value_heads"], pub["head_dim"]
    q = (u @ w["wq"]).reshape(s, h, dh)
    k = (u @ w["wk"]).reshape(s, kv, dh)
    v = (u @ w["wv"]).reshape(s, kv, dh)
    serves = jnp.arange(h) // (h // kv)  # query head i reads key/value head i // g
    k, v = k[:, serves], v[:, serves]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scale = dh ** -0.5

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
    G, n), head ``h`` reading the maps of group ``h // (H / G)``. In blocks
    of ``_BLOCK`` positions for the backward pass's memory alone; positions
    of step 0 pad the last block and leave the state be."""
    import jax
    import jax.numpy as jnp

    s, h, p = x.shape
    group_of = jnp.arange(h) // (h // b.shape[1])
    pad = -s % _BLOCK

    def blocks(t: Any) -> Any:
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        return t.reshape(-1, _BLOCK, *t.shape[1:])

    def position(state: Any, now: Any) -> Tuple[Any, Any]:
        x_t, dt_t, b_t, c_t = now  # (H, P), (H,), (G, n), (G, n)
        state = (
            jnp.exp(dt_t * a)[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None, :]
        )
        return state, jnp.sum(state * c_t[group_of][:, None, :], axis=-1) + d[:, None] * x_t

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
    h, p, n = pub["mamba_num_heads"], pub["mamba_head_dim"], pub["ssm_state_size"]
    g, taps, inner = pub["n_groups"], pub["conv_kernel"], h * p
    into = u @ w["w_in"]
    z, xbc, dt = into[:, :inner], into[:, inner:2 * inner + 2 * g * n], into[:, 2 * inner + 2 * g * n:]
    before = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), xbc.dtype), xbc])
    xbc = _silu(sum(before[j:j + s] * w["conv"][j] for j in range(taps)) + w["conv_bias"])
    x, b, c = xbc[:, :inner], xbc[:, inner:inner + g * n], xbc[:, inner + g * n:]
    y = recurrence(
        x.reshape(s, h, p), _softplus(dt + w["dt_bias"]), -jnp.exp(w["a_log"]),
        b.reshape(s, g, n), c.reshape(s, g, n), w["d"],
    )
    # the gate before the norm; a statistic a GROUP's channels
    y = (y.reshape(s, inner) * _silu(z)).reshape(s, g, inner // g)
    y = _rmsnorm(y, w["norm"].reshape(g, inner // g), pub["layer_norm_epsilon"])
    return y.reshape(s, inner) @ w["wo"]


def routed(pub: Mapping[str, Any], x: Any, w: Any) -> Tuple[Any, Any, Any]:
    """The router on all the step's tokens ``x`` (N, D): (N, E) the weight of
    every expert for every token (0 where the token did not choose it), (N, E)
    bool the choice, (N, E) each expert's score over the scores' sum."""
    import jax.numpy as jnp

    assert pub["n_group"] == 1 and pub["topk_group"] == 1  # no group is closed
    score = _sigmoid(x @ w["router"])
    pick = score + w["bias"]
    chose = pick >= jnp.sort(pick, axis=-1)[:, -pub["num_experts_per_tok"]][:, None]
    gate = jnp.where(chose, score, 0.0)
    if pub["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = pub["routed_scaling_factor"] * gate
    return gate, chose, score / jnp.sum(score, axis=-1, keepdims=True)


def _moe(pub: Mapping[str, Any], rank: int, x: Any, w: Any) -> Tuple[Any, Any]:
    """All the step's tokens ``x`` (N, D). Returns the held experts' part of
    the layer's output with the shared expert's, and ``(f, P, claims)``: the
    share of tokens that chose each of the E experts, the mean of each
    expert's score over the scores' sum, and how many tokens chose each."""
    import jax
    import jax.numpy as jnp

    held = pub["n_routed_experts"]
    gate, chose, share = routed(pub, x, w)

    @jax.checkpoint
    def expert(w_up: Any, w_down: Any, g: Any) -> Any:
        return g[:, None] * _relu2(x, w_up, w_down)

    out, _ = jax.lax.scan(
        lambda acc, e: (acc + expert(*e), None), jnp.zeros_like(x),
        (w["w_up"], w["w_down"], gate[:, rank * held:(rank + 1) * held].T),
    )
    claims = jnp.sum(chose.astype(jnp.float32), axis=0)
    out = out + _relu2(x, w["shared"]["w_up"], w["shared"]["w_down"])
    return out, (claims / x.shape[0], jnp.mean(share, axis=0), claims)


def pattern_of(pub: Mapping[str, Any], layers: Sequence[int]) -> List[str]:
    return [pub["hybrid_override_pattern"][i] for i in layers]


def layer(pub: Mapping[str, Any], rank: int, kind: str, blk: Any, x: Any) -> Tuple[Any, Any]:
    """One published layer of character ``kind`` on the stream ``x`` (B, S,
    D): ``x + Mixer(N(x))``, and a sparse layer's ``(f, P, claims)``."""
    import jax

    eps = pub["layer_norm_epsilon"]
    if kind == "E":
        u = _rmsnorm(x, blk["ln2"]["scale"], eps)
        y, router = _moe(pub, rank, u.reshape(-1, u.shape[-1]), blk["moe"])
        return x + y.reshape(x.shape), router
    mixer = {"M": _mamba, "*": _attention}[kind]
    u = _rmsnorm(x, blk["ln1"]["scale"], eps)
    return x + jax.lax.map(lambda us: mixer(pub, us, blk["attn"]), u), None


def loss_and_claims(
    pub: Mapping[str, Any], deployment: Mapping[str, Any], params: Any, tokens: Any
) -> Tuple[Any, List[Any]]:
    """The training loss of ``tokens`` (int32[batch, seq]) under float32
    ``params`` (the program's tree, by its names) - the model runs on the
    first ``seq - 1`` positions and predicts the last ``seq - 1`` - and, a
    sparse layer, how many tokens chose each of the E experts. ``pub`` holds
    the published keys, ``deployment`` the published ``layers`` that are run
    and this chip's ``rank`` among those that share a layer."""
    import jax
    import jax.numpy as jnp

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inputs]  # (B, S, D)
    f = p_mean = 0.0
    claims = []
    for kind, blk in zip(pattern_of(pub, deployment["layers"]), params["blocks"]):
        # a layer's activations are recomputed in the backward pass too
        x, router = jax.checkpoint(
            lambda blk, x, kind=kind: layer(pub, deployment["rank"], kind, blk, x)
        )(blk, x)
        if router is not None:
            f, p_mean = f + router[0], p_mean + router[1]
            claims.append(router[2])
    experts = pub["published"]["n_routed_experts"]
    balance = experts * jnp.sum((f / len(claims)) * (p_mean / len(claims)))

    @jax.checkpoint
    def sequence_nll(xs: Any, ts: Any) -> Any:
        logits = _rmsnorm(xs, params["ln_f"]["scale"], pub["layer_norm_epsilon"]) @ params["readout"]
        logits = logits - jnp.max(logits, axis=-1, keepdims=True)
        logp = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
        return -jnp.sum(jnp.take_along_axis(logp, ts[:, None], axis=-1))

    nll = jnp.sum(jax.lax.map(lambda a: sequence_nll(*a), (x, targets)))
    return nll / inputs.size + pub["assumed"]["balance_coef"] * balance, claims


def loss(pub: Mapping[str, Any], deployment: Mapping[str, Any], params: Any, tokens: Any) -> Any:
    return loss_and_claims(pub, deployment, params, tokens)[0]


def grads(
    pub: Mapping[str, Any], deployment: Mapping[str, Any], params: Any, tokens: Any
) -> Tuple[Any, Any]:
    """The loss and the gradient tree a step's optimizer is handed: the
    loss's gradient of every weight, and for every selection bias (of which
    the loss has none) its expert's excess load, ``claims_e / (N K) - 1 / E``."""
    import jax

    (value, claims), g = jax.value_and_grad(
        lambda p: loss_and_claims(pub, deployment, p, tokens), has_aux=True
    )(params)
    claims = iter(claims)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    k, e = pub["num_experts_per_tok"], pub["published"]["n_routed_experts"]
    blocks = [
        blk if "moe" not in blk else dict(
            blk, moe=dict(blk["moe"], bias=next(claims) / (n * k) - 1.0 / e)
        )
        for blk in g["blocks"]
    ]
    return value, dict(g, blocks=blocks)


def train(
    pub: Mapping[str, Any], deployment: Mapping[str, Any], params: Any, batches: Any
) -> Tuple[Any, Any]:
    """Plain AdamW from ``params`` over ``batches`` (int32[steps, batch,
    seq]), one update a batch, every leaf alike (the biases too: module
    docstring). Returns each step's loss and gradient norm, both taken
    before its update: ``(f32[steps], f32[steps])``. The steps are one
    scanned body whose carried state is updated in place (module docstring)."""
    import jax
    import jax.numpy as jnp

    tree_map = jax.tree_util.tree_map
    lr, b1, b2, eps, decay = (
        reference.LEARNING_RATE, reference.B1, reference.B2, reference.EPS,
        reference.WEIGHT_DECAY,
    )
    p = tree_map(lambda a: jnp.asarray(a, jnp.float32), params)
    zeros = tree_map(jnp.zeros_like, p)

    def step(state: Any, batch: Any) -> Any:
        p, m, v = state
        t, tokens = batch
        value, g = grads(pub, deployment, p, tokens)
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
