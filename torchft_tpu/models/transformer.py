"""Decoder-only transformer LM: the flagship model for fault-tolerant
training demos and benchmarks.

Pure-functional (pytree params + jax fns), designed TPU-first:

- all matmuls are large, batched and bfloat16 (MXU-shaped; dims multiples
  of 128 at the flagship config),
- static shapes and compiler-friendly control flow only (no data-dependent
  Python branching under jit),
- Megatron-style tensor-parallel sharding rules over a ``model`` mesh axis
  (column-parallel QKV/up-projection, row-parallel out/down-projection),
  expressed as PartitionSpecs — XLA inserts the ICI collectives,
- batch sharded over a ``data`` mesh axis.

The reference has no model zoo (torchft wraps user models, train_ddp.py's
CNN is the only demo); this module is the analog of that demo model plus
the sharding contract the HSDP composition needs
(reference process_group.py:1310-1341 leaves intra-group dims to the user —
here the intra-group sharding is first-class).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16  # activation/matmul dtype; params stay f32
    # Context parallelism: when set, attention runs as ring attention with
    # the sequence sharded over this mesh axis (torchft_tpu.context_parallel)
    # instead of dense O(S^2) attention. cp_mesh carries the slice mesh into
    # the op (compared by identity, not traced); cp_head_axis names the
    # tensor-parallel axis heads are split over, if any.
    cp_seq_axis: Any = None
    cp_mesh: Any = None
    cp_batch_axis: Any = "data"
    cp_head_axis: Any = None
    # "ring" (k/v ppermute + online softmax) or "ulysses" (head/seq
    # all-to-alls around full-sequence attention — which then runs through
    # the fused pallas kernel when use_flash is set)
    cp_strategy: str = "ring"
    # Fused pallas flash attention (torchft_tpu.ops.flash_attention): no
    # S x S score matrix in HBM. Consumed by (a) the non-CP path — when
    # cp_mesh is set the kernel runs per-shard under shard_map with batch
    # over cp_batch_axis and heads over cp_head_axis — and (b) the
    # cp_strategy="ulysses" path, where each device's full-sequence
    # attention runs through the kernel. Ignored by cp_strategy="ring"
    # (that path fuses its own online-softmax loop).
    use_flash: bool = False
    # Sliding-window (local) attention width; requires use_flash (the
    # kernel skips out-of-window tiles). None = full causal attention.
    attn_window: Any = None
    # Rematerialize each block's activations in backward (jax.checkpoint):
    # trades ~1/3 extra FLOPs for O(n_layers) less HBM — the standard TPU
    # recipe for long-sequence / large-batch configs.
    remat: bool = False
    # With remat on, "save_attn" keeps each block's attention output AND
    # the flash kernel's (out, lse) residuals (cheap: O(B*S*D) per layer)
    # so the backward replay prunes the forward flash launch — the
    # standard pairing for the flash kernel under remat. On the dense
    # path it only saves the post-projection output (the softmax
    # internals are still recomputed: its vjp needs them either way).
    # None = full recompute.
    remat_policy: Any = None

    def __post_init__(self):
        if self.cp_strategy not in ("ring", "ulysses"):
            raise ValueError(
                f"cp_strategy must be 'ring' or 'ulysses', got "
                f"{self.cp_strategy!r}"
            )
        if self.remat_policy not in (None, "save_attn"):
            raise ValueError(
                f"remat_policy must be None or 'save_attn', got "
                f"{self.remat_policy!r}"
            )
        if self.attn_window is not None and not self.use_flash:
            raise ValueError(
                "attn_window requires use_flash=True (the dense and ring "
                "paths do not implement sliding windows)"
            )
        if self.attn_window is not None and self.cp_seq_axis is not None:
            raise ValueError(
                "attn_window is not implemented on the context-parallel "
                "paths (ring/ulysses take the attention branch before the "
                "flash kernel); unset cp_seq_axis or attn_window"
            )

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def tiny_config() -> TransformerConfig:
    """Small config for tests / virtual-device dry runs."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq_len=128,
    )


def big_config() -> TransformerConfig:
    """The 111M-parameter dense LM, the one configuration with a history
    on the chip: MXU-shaped (d_model 1024, 16 heads x 64, d_ff 4096), run
    at batch 16 x sequence 2048 with ``use_flash=True``. What
    ``chip_smoke.py``'s fleet phase trains. A stand-in, not a public
    architecture (ROADMAP D7)."""
    return TransformerConfig(
        vocab_size=8192, d_model=1024, n_heads=16, n_layers=8, d_ff=4096,
        max_seq_len=2048,
    )


def _dense_init(k, shape, s):
    return jax.random.normal(k, shape, jnp.float32) * s


def attn_sublayer_init(
    cfg: TransformerConfig, k_qkv: jax.Array, k_o: jax.Array
) -> Dict[str, Any]:
    """ln1 + attention weights; shared by the dense and MoE families."""
    scale = cfg.d_model ** -0.5
    return {
        "ln1": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
        "attn": {
            # fused QKV, column-parallel over the model axis
            "wqkv": _dense_init(k_qkv, (cfg.d_model, 3 * cfg.d_model), scale),
            # out projection, row-parallel
            "wo": _dense_init(k_o, (cfg.d_model, cfg.d_model), scale),
        },
        "ln2": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
    }


def mlp_init(
    cfg: TransformerConfig, k_i: jax.Array, k_o: jax.Array
) -> Dict[str, Any]:
    scale = cfg.d_model ** -0.5
    return {
        "wi": _dense_init(k_i, (cfg.d_model, cfg.d_ff), scale),
        "wo": _dense_init(k_o, (cfg.d_ff, cfg.d_model), cfg.d_ff ** -0.5),
    }


def backbone_init(
    cfg: TransformerConfig, k_embed: jax.Array, k_pos: jax.Array
) -> Dict[str, Any]:
    """embed / pos_embed / ln_f — the non-block params both families
    share."""
    scale = cfg.d_model ** -0.5
    return {
        "embed": jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.d_model), jnp.float32
        ) * scale,
        "pos_embed": jax.random.normal(
            k_pos, (cfg.max_seq_len, cfg.d_model), jnp.float32
        ) * 0.01,
        "ln_f": {"scale": jnp.ones((cfg.d_model,), jnp.float32)},
    }


def backbone_specs() -> Dict[str, Any]:
    return {
        "embed": P(None, "model"),
        "pos_embed": P(),
        "ln_f": {"scale": P()},
    }


def embed_tokens(
    cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array
) -> jax.Array:
    """(B, S) int32 -> (B, S, D) activations in cfg.dtype."""
    S = tokens.shape[1]
    with jax.named_scope("embed"):
        x = params["embed"].astype(cfg.dtype)[tokens]
        return x + params["pos_embed"].astype(cfg.dtype)[:S]


def _readout_product(
    cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array
) -> jax.Array:
    """Final norm, then the weight-tied readout matmul: logits (..., V) in
    ``cfg.dtype``, the type the product is computed in. ``readout`` /
    ``forward`` return this widened to float32; ``loss_fn`` hands it to
    ``next_token_loss`` as it is."""
    return _rmsnorm(x, params["ln_f"]["scale"]) @ params["embed"].astype(cfg.dtype).T


def readout(
    cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array
) -> jax.Array:
    """Final norm + weight-tied readout: float32 logits, the ``cfg.dtype``
    product widened for whoever asks for logits (serving, a pipeline's
    last stage). ``loss_fn`` hands ``next_token_loss`` the product
    unwidened: a training step stores it in ``cfg.dtype`` either way, and
    read there the softmax needs no float32 copy. (Jitted alone, the TPU
    compiler folds this widening into the matmul and keeps the
    accumulator's extra bits; PERF.md section 7.)"""
    with jax.named_scope("readout"):
        return _readout_product(cfg, params, x).astype(jnp.float32)


def mlp_apply(
    cfg: TransformerConfig, p: Dict[str, Any], x: jax.Array
) -> jax.Array:
    h = jax.nn.gelu(x @ p["wi"].astype(cfg.dtype))
    return h @ p["wo"].astype(cfg.dtype)


def _position_losses(logits: jax.Array, targets: jax.Array) -> Any:
    """One sweep over the logits: each position's cross entropy in float32,
    and what the backward pass wants of it (the row maximum, the float32
    sum of exponentials)."""
    f32 = jnp.float32
    # a maximum rounds nothing, so it is taken in the logits' own type
    top = jnp.max(logits, axis=-1, keepdims=True)
    total = jnp.sum(jnp.exp(logits.astype(f32) - top.astype(f32)), axis=-1)
    lse = top[..., 0].astype(f32) + jnp.log(total)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - picked.astype(f32), top, total


def _softmax_less_target(res: Any, scale: jax.Array) -> jax.Array:
    """``(softmax(logits) - onehot(targets)) * scale`` in the logits' type,
    ``scale`` one number or one a position."""
    # probabilities as exp(l - max) / sum, not exp(l - lse): normalised by
    # the sum itself a row adds up to 1 whatever the device's log rounds to
    logits, targets, top, total = res
    f32 = jnp.float32
    hit = (
        jax.lax.broadcasted_iota(targets.dtype, logits.shape, logits.ndim - 1)
        == targets[..., None]
    )
    grad = jnp.exp(logits.astype(f32) - top.astype(f32)) * (scale / total)[..., None]
    at_hit = scale[..., None] if scale.ndim else scale
    return (grad - jnp.where(hit, at_hit, 0.0)).astype(logits.dtype)


@jax.custom_vjp
def _cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    return _cross_entropy_fwd(logits, targets)[0]


def _cross_entropy_fwd(logits: jax.Array, targets: jax.Array) -> Any:
    losses, top, total = _position_losses(logits, targets)
    return jnp.mean(losses), (logits, targets, top, total)


def _cross_entropy_bwd(res: Any, g: jax.Array) -> Any:
    return _softmax_less_target(res, g / res[3].size), None


_cross_entropy.defvjp(_cross_entropy_fwd, _cross_entropy_bwd)


@jax.custom_vjp
def _cross_entropies(logits: jax.Array, targets: jax.Array) -> jax.Array:
    return _position_losses(logits, targets)[0]


def _cross_entropies_fwd(logits: jax.Array, targets: jax.Array) -> Any:
    losses, top, total = _position_losses(logits, targets)
    return losses, (logits, targets, top, total)


def _cross_entropies_bwd(res: Any, g: jax.Array) -> Any:
    return _softmax_less_target(res, g), None


_cross_entropies.defvjp(_cross_entropies_fwd, _cross_entropies_bwd)


def next_token_loss(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean cross entropy of ``logits`` (..., V) against int ``targets``
    (...), in float32 whatever float type the logits come in.

    The logits are read and kept in the type they were handed over in -
    float32 from ``readout`` / ``forward``, ``cfg.dtype`` from the training
    losses, which pass the readout matmul's product unwidened - and the
    cotangent comes back in that type. Each pass widens what it reads
    inside its own loop: forward a row maximum and a sum of exponentials
    (residuals: the logits as they came and, a position, the maximum and
    the float32 sum), backward one elementwise pass
    ``(exp(l - max) / sum - onehot) * g / N``. No float32 array of the
    logits' shape is stored unless the caller's logits are float32."""
    with jax.named_scope("loss"):
        return _cross_entropy(logits, targets)


def next_token_losses(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Each position's cross entropy (...), float32: ``next_token_loss``
    before its mean, by the same sweep and under the same rules - the
    logits read and kept in the type they came in, the cotangent (one
    number a position) back in that type. For a loss that weights the
    positions (``masked_token_loss``; ``olmoe._exits_nll`` runs the sweep itself)."""
    with jax.named_scope("loss"):
        return _cross_entropies(logits, targets)


def masked_token_loss(
    logits: jax.Array, targets: jax.Array, masked: jax.Array, weight: jax.Array
) -> jax.Array:
    """A masked-diffusion step's loss: the cross entropy of ``logits``
    (B, L, V) against the position's OWN token ``targets`` (B, L) at the
    positions ``masked`` (B, L) alone, each sequence's weighted by
    ``weight`` (B,), summed and divided by ALL B L positions; float32, by
    ``next_token_losses``' sweep and under its rules."""
    nll = next_token_losses(logits, targets)
    with jax.named_scope("loss"):
        return jnp.sum(jnp.where(masked, nll, 0.0) * weight[:, None]) / masked.size


def attn_sublayer_specs() -> Dict[str, Any]:
    """Megatron attention PartitionSpecs; shared with the MoE family."""
    return {
        "ln1": {"scale": P()},
        "attn": {
            "wqkv": P(None, "model"),  # column-parallel: heads split
            "wo": P("model", None),    # row-parallel: partial sums psum'd
        },
        "ln2": {"scale": P()},
    }


def mlp_specs() -> Dict[str, Any]:
    return {"wi": P(None, "model"), "wo": P("model", None)}


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    """f32 master params; matmuls cast to cfg.dtype at use."""
    keys = jax.random.split(key, 2 + cfg.n_layers)
    blocks = []
    for i in range(cfg.n_layers):
        bk = jax.random.split(keys[2 + i], 4)
        block = attn_sublayer_init(cfg, bk[0], bk[1])
        block["mlp"] = mlp_init(cfg, bk[2], bk[3])
        blocks.append(block)
    params = backbone_init(cfg, keys[0], keys[1])
    params["blocks"] = blocks
    return params


def param_sharding_rules(cfg: TransformerConfig) -> Dict[str, Any]:
    """PartitionSpecs (pytree matching init_params) for a mesh with a
    ``model`` axis: Megatron column/row parallelism. Replicated leaves get
    P() so every spec is explicit."""
    block = attn_sublayer_specs()
    block["mlp"] = mlp_specs()
    rules = backbone_specs()
    rules["blocks"] = [block] * cfg.n_layers
    return rules


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _attention(cfg: TransformerConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """Returns the attention sublayer output, checkpoint-named "attn_out"
    (identity outside a policy-remat context) so remat_policy="save_attn"
    works for every family that calls this — no per-family re-tagging."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(_attention_impl(cfg, p, x), "attn_out")


def _attention_impl(cfg: TransformerConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    B, S, D = x.shape
    qkv = x @ p["wqkv"].astype(cfg.dtype)  # (B, S, 3D)
    if (
        cfg.use_flash and cfg.cp_seq_axis is None and cfg.cp_mesh is None
        and cfg.attn_window is None
    ):
        # one device, full causal attention: the kernels read the heads
        # where the projection wrote them and write them where the out
        # projection reads them - no split, transpose or copy between
        from ..ops import flash_attention_qkv

        out = flash_attention_qkv(qkv, cfg.n_heads)
        return out @ p["wo"].astype(cfg.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_heads, cfg.head_dim)

    if cfg.cp_seq_axis is not None:
        # Context parallel: sequence sharded over the slice mesh's seq
        # axis, no S x S materialization. Strategy: k/v ring (ppermute) or
        # Ulysses all-to-alls (full-seq attention per head subset).
        from ..context_parallel import ring_attention, ulysses_attention

        if cfg.cp_strategy == "ulysses":
            out = ulysses_attention(
                q, k, v,
                mesh=cfg.cp_mesh,
                seq_axis=cfg.cp_seq_axis,
                batch_axis=cfg.cp_batch_axis,
                head_axis=cfg.cp_head_axis,
                use_flash=cfg.use_flash,
            ).reshape(B, S, D)
        else:
            out = ring_attention(
                q, k, v,
                mesh=cfg.cp_mesh,
                seq_axis=cfg.cp_seq_axis,
                batch_axis=cfg.cp_batch_axis,
                head_axis=cfg.cp_head_axis,
            ).reshape(B, S, D)
        return out @ p["wo"].astype(cfg.dtype)

    if cfg.use_flash:
        from ..ops import flash_attention

        out = flash_attention(
            q, k, v,
            mesh=cfg.cp_mesh,
            batch_axis=cfg.cp_batch_axis if cfg.cp_mesh is not None else None,
            head_axis=cfg.cp_head_axis,
            window=cfg.attn_window,
        ).reshape(B, S, D)
        return out @ p["wo"].astype(cfg.dtype)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (cfg.head_dim ** -0.5)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))
    scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(cfg.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, D)
    return out @ p["wo"].astype(cfg.dtype)


def _block(cfg: TransformerConfig, p: Dict[str, Any], x: jax.Array) -> jax.Array:
    # Scopes are metadata: they name the operations in a device trace
    # (``attn``, ``mlp``; ``embed``, ``readout`` and ``loss`` in their own
    # functions) and change no instruction. JAX wraps the backward pass's
    # copy of each in ``transpose(jvp(...))``.
    with jax.named_scope("attn"):
        x = x + _attention(cfg, p["attn"], _rmsnorm(x, p["ln1"]["scale"]))
    with jax.named_scope("mlp"):
        return x + mlp_apply(cfg, p["mlp"], _rmsnorm(x, p["ln2"]["scale"]))


def remat_wrap(cfg: TransformerConfig, fn, static_argnums=(0,)):
    """Apply cfg's remat settings to a block fn; shared by the dense and
    MoE families so remat_policy means the same thing in both."""
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "save_attn":
        policy = jax.checkpoint_policies.save_only_these_names(
            "attn_out", "flash_out", "flash_lse"
        )
        return jax.checkpoint(fn, static_argnums=static_argnums,
                              policy=policy)
    return jax.checkpoint(fn, static_argnums=static_argnums)


def _hidden(cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    """tokens (B, S) int32 -> the last block's output (B, S, D)."""
    x = embed_tokens(cfg, params, tokens)
    block = remat_wrap(cfg, _block)
    for p in params["blocks"]:
        x = block(cfg, p, x)
    return x


def forward(cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    """tokens (B, S) int32 -> logits (B, S, vocab) f32."""
    return readout(cfg, params, _hidden(cfg, params, tokens))


def loss_fn(cfg: TransformerConfig, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy over (B, S) int32 tokens. The loss reads
    the readout's product in ``cfg.dtype``, the width it was computed in
    (``next_token_loss``); ``forward`` is the same model with the logits
    widened to float32."""
    x = _hidden(cfg, params, tokens[:, :-1])
    with jax.named_scope("readout"):
        logits = _readout_product(cfg, params, x)
    return next_token_loss(logits, tokens[:, 1:])


def make_train_step(
    cfg: Any, tx: Any, bf16_params: bool = False
) -> Any:
    """ONE-program train step: loss, grad, and optimizer apply fused into
    a single jitted executable with buffer donation. ``cfg`` is this
    module's configuration or an ``OlmoeConfig`` (the benchmark's raw
    loop runs both), whose loss is ``olmoe.loss_fn``.

    Fusing saves the program-boundary cost of separate grad and apply
    programs (how much is not measured on the current chip). Use with
    ``LocalSGD.step_applied``-style window accounting — per-step
    cross-group work (the DDP ring) inherently needs the split programs.

    ``bf16_params``: classic mixed precision with a master copy — the
    gradient pass reads a bf16 working copy of the f32 params (one cast
    pass instead of a per-use cast; halves param/embed HBM read traffic
    and the gradient pytree), while the optimizer updates the f32 master,
    which ``params`` remains throughout. Forward numerics are identical
    to the default (the model casts weights to ``cfg.dtype`` at use
    anyway); what changes is gradient ACCUMULATION precision — multi-use
    cotangent sums run in bf16 — the standard mixed-precision trade.

    Returns ``step(params, opt_state, tokens) -> (params, opt_state,
    loss)``.
    """
    import optax

    from . import olmoe  # it imports this module

    model_loss = olmoe.loss_fn if isinstance(cfg, olmoe.OlmoeConfig) else loss_fn

    def one_step(params, opt_state, tokens):
        if bf16_params:
            compute_params = jax.tree_util.tree_map(
                lambda l: l.astype(jnp.bfloat16)
                if l.dtype == jnp.float32 else l,
                params,
            )
            loss, grads = jax.value_and_grad(
                lambda p: model_loss(cfg, p, tokens)
            )(compute_params)
        else:
            loss, grads = jax.value_and_grad(
                lambda p: model_loss(cfg, p, tokens)
            )(params)
        # the same scope as train_state.make_apply_fn's separate program,
        # so a device trace splits the fused step the same way
        with jax.named_scope("optimizer"):
            if bf16_params:
                # master update in f32 regardless of wire/grad dtype
                grads = jax.tree_util.tree_map(
                    lambda g, m: g.astype(m.dtype), grads, params
                )
            updates, new_opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), new_opt_state, loss

    return jax.jit(one_step, donate_argnums=(0, 1))
