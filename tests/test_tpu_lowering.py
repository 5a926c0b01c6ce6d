"""Cross-lowers every Pallas entry point for the TPU, from the CPU.

Interpret mode accepts programs Mosaic refuses (a scalar store into VMEM,
a scalar bitcast, a misaligned block), so the interpret-mode oracle tests
say nothing about whether a kernel BUILDS for the chip.
``jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` runs the
real Pallas->Mosaic lowering without a TPU backend; each case asserts the
lowered module carries the Mosaic custom call. What this cannot see is the
TPU compiler proper (VMEM budget, layout inference) — ``chip_smoke.py``'s
``kernels`` phase covers that on hardware.
"""

import sys

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu.ops import (
    cast_bf16,
    dequantize_q8,
    flash_attention,
    flash_attention_qkv,
    quantize_q8_ef,
)


def _mosaic_calls(fn, *args) -> int:
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text().count("tpu_custom_call")


_FLASH_SHAPES = [
    (256, None), (99, None), (2047, None), (1024, 256), (1024, None),
    (1023, None), (4096, None), (8192, 1024), (8192, None),
]


# Every (positions, window) at head sizes 64 and 128; the long causal
# calls also at 256 lanes (``ling3-ft1``'s latent attention as it was padded
# until PR 54; its own widths: ``test_two_widths_compile_for_the_chip``):
# each lowers at the blocks ``_auto_tiles`` gives its length.
@pytest.mark.parametrize(
    "head_dim,seq,window",
    [(head_dim, *shape) for head_dim in (64, 128) for shape in _FLASH_SHAPES]
    + [(256, 4096, None), (256, 8192, None)],
)
def test_flash_forward_and_backward_lower(head_dim, seq, window):
    q = jnp.ones((2, seq, 2, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(q, k, v, window=window, interpret=False)
        return out.astype(jnp.float32).sum()

    assert _mosaic_calls(loss, q, q, q) == 1
    # forward + the single fused backward kernel
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == 2


def _runs_the_static_schedule(length, block, tile=None, head_dim=128):
    """Whether a ``block_mask`` call of these shapes, on the chip's tiles
    (or sub-tiles of ``tile``), runs the two-level static schedule: the
    ``kind`` the call is handed."""
    flash = sys.modules["torchft_tpu.ops.flash_attention"]
    return flash._tiles(
        2 * length, head_dim, False, tile, tile, None, causal=False,
        block_mask=(block, length),
    ).kind == "blocked"


# (L, B, sub-tile, static): the benchmark's shape, which runs the two-level
# static schedule; one block a copy and one sub-tile (the staircases whole);
# a block that is no power of two (a vector division in the masks) on
# sub-tiles that hold it, which a call has to name; and three that keep the
# general sweep: such a block on the tiles a call chooses, which a copy's end
# and a block straddle; one block a copy, over four sub-tiles; a padded length
@pytest.mark.parametrize(
    "length,block,tile,static",
    [(4096, 4, None, True), (1024, 1024, None, True), (3072, 6, 384, True),
     (960, 6, None, False), (2048, 2048, None, False), (200, 8, None, False)],
)
def test_block_mask_forward_and_backward_lower(length, block, tile, static):
    assert _runs_the_static_schedule(length, block, tile) == static
    q = jnp.ones((2, 2 * length, 2, 128), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=False, block_mask=(block, length), interpret=False,
            block_q=tile, block_k=tile,
        )
        return out.astype(jnp.float32).sum()

    assert _mosaic_calls(loss, q, q, q) == 1
    assert _mosaic_calls(jax.grad(loss, argnums=(0, 1, 2)), q, q, q) == 2


# one element, a single ragged block, exactly one block, the big model's
# 1024x4096 leaf (128 blocks), and an odd multi-block length
@pytest.mark.parametrize(
    "shape", [(1,), (257,), (256, 128), (1024, 4096), (70001,)]
)
def test_wire_kernels_lower(shape):
    x = jnp.ones(shape, jnp.float32)
    q = jnp.ones(shape, jnp.int8)
    assert _mosaic_calls(
        lambda x, r: quantize_q8_ef(x, r, interpret=False), x, x
    ) == 1
    assert _mosaic_calls(
        lambda q: dequantize_q8(q, jnp.float32(0.5), interpret=False), q
    ) == 1
    assert _mosaic_calls(lambda x: cast_bf16(x, interpret=False), x) == 1


def _lowered_text(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def _qkv_grad_text(n_heads: int, head_dim: int = 64, **blocks) -> str:
    qkv = jnp.ones((2, 1024, 3 * n_heads * head_dim), jnp.bfloat16)

    def loss(qkv):
        out = flash_attention_qkv(qkv, n_heads, interpret=False, **blocks)
        return out.astype(jnp.float32).sum()

    return _lowered_text(jax.grad(loss), qkv)


def test_fused_projection_kernels_do_not_grow_with_the_head_count():
    """What refused PR 27 (PERF.md section 6): set-up time that grew with
    the model. The lowered text carries each kernel's serialized body, and
    tracing, lowering and compiling all follow its size; heads are on the
    grid, so 16 of them cost what 2 do, and there are two kernels."""
    two, sixteen = _qkv_grad_text(2), _qkv_grad_text(16)
    assert two.count("tpu_custom_call") == 2
    assert sixteen.count("tpu_custom_call") == 2
    assert len(sixteen) <= 1.2 * len(two), (len(two), len(sixteen))


@pytest.mark.parametrize("head_dim", [64, 128])
def test_fused_projection_body_is_at_most_twice_the_three_array_one(head_dim):
    """Two heads of 64 columns share a 128-lane block, so its body holds
    the three-array kernel's schedule twice; a 128-wide head once, plus
    the backward's own copies of dq, dk, dv into the one cotangent. (The
    serialized bodies carry their Python call stack, so the lengths move
    a few per cent with the caller.)"""
    q = jnp.ones((2, 1024, 2, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    three = _lowered_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    fused = _qkv_grad_text(2, head_dim)
    assert fused.count("tpu_custom_call") == three.count("tpu_custom_call") == 2
    assert len(fused) <= (1.7 if head_dim == 64 else 1.4) * len(three)


def test_staircase_body_is_bounded_against_the_whole_sub_tile():
    """The diagonal sub-tile as a staircase is more and smaller operations
    (PR 35): the fused gradient at S 1024 with the shapes' own edges (the
    backward's 128, the forward whole) against the sub-tile whole in both
    (``block_diag`` 512, the body until then). Cross-lowered here: 26.1 KB
    whole, 35.6 KB as the shapes choose, 49.8 KB with 128 in both; the TPU
    compiler takes the same 2-3 s for each. What grows set-up is a body
    that grows with the model (the two tests above); this one keeps the
    staircase from growing unseen."""
    whole, stairs = _qkv_grad_text(12, block_diag=512), _qkv_grad_text(12)
    assert whole.count("tpu_custom_call") == stairs.count("tpu_custom_call") == 2
    assert len(whole) < len(stairs) <= 1.5 * len(whole), (len(whole), len(stairs))


@pytest.mark.parametrize("seq,window", [(8192, 1024), (8192, 2048), (2047, 512)])
def test_band_body_is_bounded_against_the_whole_schedule(seq, window):
    """A window's band is the two-level schedule with static pieces in
    place of the dynamic loop (PR 37): Mellum2's sliding layer (a row
    group of 1024 a resident block, its two staircases) lowers to 39 KB
    where its full layer (one row group of 512 and the loop) is 25 KB.
    Tracing, lowering and loading follow that size in every run's set-up,
    warm or cold, once a layer; this keeps the band from growing unseen -
    by a window of more sub-tiles, or a body unrolled over the grid."""
    q = jnp.ones((1, seq, 2, 128), jnp.bfloat16)

    def grad_text(**kw):
        def loss(q, k, v):
            return flash_attention(q, k, v, interpret=False, **kw).astype(jnp.float32).sum()

        return _lowered_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)

    whole, band = grad_text(), grad_text(window=window)
    assert whole.count("tpu_custom_call") == band.count("tpu_custom_call") == 2
    assert len(band) <= 2 * len(whole), (len(whole), len(band))


def _block_pair(q, k, v):
    out = flash_attention(q, k, v, causal=False, block_mask=(4, 4096), interpret=False)
    return out.astype(jnp.float32).sum()


def test_block_schedule_body_is_bounded_against_the_causal_schedule():
    """The block-diffusion mask on the static schedule (PR 47) is the causal
    schedule with each piece twice - a clean and a noised row group of the
    same positions a grid step, one loop for both - and the own quadrant's
    diagonal chunks: at ``sdar-ft1``'s shape (L 4096, B 4, head size 128) it
    lowers to 58.9 KB - a row group of 1024, four strips of 256 forward and
    eight chunks of 128 backward - where the causal call over the same 8,192
    rows (row groups of 512) is 27.0 KB; on row groups of 512 it is 39.0 KB.
    Tracing, lowering and loading follow that size in every run's set-up;
    this keeps the body from growing unseen - by a resident block of more
    row groups, or pieces unrolled over the grid."""
    q = jnp.ones((1, 8192, 2, 128), jnp.bfloat16)

    def causal(q, k, v):
        return flash_attention(q, k, v, interpret=False).astype(jnp.float32).sum()

    whole, block = (
        _lowered_text(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
        for loss in (causal, _block_pair)
    )
    assert whole.count("tpu_custom_call") == block.count("tpu_custom_call") == 2
    assert len(whole) < len(block) <= 2.5 * len(whole), (len(whole), len(block))


# the benchmark's three attention shapes: gpt2-small and gpt2-medium through
# the fused entry, OLMoE (eight resident blocks a side) through the other
@pytest.mark.parametrize(
    "n_heads,head_dim,seq", [(12, 64, 1024), (16, 64, 1024), (16, 128, 4096)],
    ids=["gpt2-small", "gpt2-medium", "olmoe"],
)
def test_benchmark_shapes_gradient_holds_two_mosaic_calls(n_heads, head_dim, seq):
    if head_dim == 64:
        text = _qkv_grad_text(n_heads)
    else:
        q = jnp.ones((1, seq, n_heads, head_dim), jnp.bfloat16)
        text = _lowered_text(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, interpret=False)
            .astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ), q, q, q)
    assert text.count("tpu_custom_call") == 2
    assert text.count('kernel_name = "flash_fwd"') == 1
    assert text.count('kernel_name = "flash_bwd"') == 1


# The contract's file keeps its tiny sizes in its own ``TINY`` and is the
# benchmark's to edit; a configuration newer than it is handed over from
# here: four layers of the two kinds, 4 query heads over 2 key/value heads
# of the published 128, a window shorter than the sequence, 2 of 4 experts.
TINY_FROM_HERE = {
    "mellum2-12b-a2.5b-l4-ep8": {
        "vocab_size": 256, "hidden_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 4, "num_experts": 2,
        "num_experts_per_tok": 2, "moe_intermediate_size": 128,
        "sliding_window": 512, "batch": 2, "seq": 1025,
        "published": {"num_hidden_layers": 28, "num_experts": 4, "vocab_size": 2048},
    },
    # two layers under the block mask, 512 tokens a sequence (1,024 positions)
    "sdar-30b-a3b-l4-ep8": {
        "vocab_size": 256, "hidden_size": 256, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": 2, "num_experts": 2,
        "num_experts_per_tok": 2, "moe_intermediate_size": 128, "batch": 2, "seq": 512,
        "published": {"num_hidden_layers": 48, "num_experts": 4, "vocab_size": 2048},
    },
}


@pytest.mark.parametrize(
    "config",
    ["gpt2-small", "olmoe-1b-7b-l1", "mellum2-12b-a2.5b-l4-ep8", "sdar-30b-a3b-l4-ep8"],
)
def test_the_lowered_gradient_holds_what_the_family_states(config, monkeypatch):
    """The benchmark's own case (``benchmark/tests``, not part of tier-1
    by itself) for every family: the program its generators lower holds
    the Mosaic calls the family states, or every run of the cell is
    refused before it starts."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "tests", "test_family_contract.py",
    )
    spec = importlib.util.spec_from_file_location("benchmark_family_contract", path)
    contract = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(contract)
    if config in TINY_FROM_HERE:
        monkeypatch.setitem(contract.TINY, config, TINY_FROM_HERE[config])
    assert config in contract.TINY
    contract.test_the_lowered_gradient_holds_what_the_family_states(config, monkeypatch)


@pytest.mark.parametrize("n_heads,seq", [(2, 1025), (2, 1024), (3, 1025)])
def test_transformer_gradient_has_two_mosaic_calls_a_layer(n_heads, seq, monkeypatch):
    """The benchmark's own check (``common.require_mosaic``): exactly two
    Mosaic calls a layer in the gradient of the loss - no third kernel, no
    shared outlined function - through the fused entry (even heads), its
    padded form (1023 positions) and its three-array way out (odd)."""
    import sys

    from torchft_tpu.models import TransformerConfig, init_params, loss_fn

    fa = sys.modules["torchft_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_pick_interpret", lambda _i: False)
    cfg = TransformerConfig(
        vocab_size=256, d_model=64 * n_heads, n_heads=n_heads, n_layers=4,
        d_ff=128, max_seq_len=1024, use_flash=True,
    )
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, seq), jnp.int32)
    text = _lowered_text(jax.grad(lambda p, t: loss_fn(cfg, p, t)), params, tokens)
    assert text.count("tpu_custom_call") == 2 * cfg.n_layers


# -- the loss stores the readout's product in the width it was computed in ---
# (PR 30). ``loss_fn`` hands ``next_token_loss`` the bf16 product of the
# readout matmul; ``forward`` widens it for whoever asks for logits. The
# softmax still runs in float32, so float32 values of the logits' shape
# exist INSIDE the elementwise passes; what must not exist is one that is
# kept: a residual of the backward pass, an operand or result of a matmul,
# or (compiled for the chip) an array of the program's entry computation.


def _family(name):
    from torchft_tpu import models

    if name == "olmoe":
        cfg = models.tiny_olmoe_config()
        return (
            cfg, models.olmoe.init_params, models.olmoe.loss_fn,
            lambda c, p, t: models.olmoe.forward(c, p, t)[0],
        )
    cfg = models.tiny_config()
    return cfg, models.init_params, models.loss_fn, models.forward


def _bf16_copy(tree):
    return jax.tree_util.tree_map(
        lambda l: l.astype(jnp.bfloat16) if l.dtype == jnp.float32 else l, tree
    )


def _small_case(family):
    """(cfg, loss_fn, forward, bf16 compute copy, tokens (2, 17)) of a
    family at its tiny sizes: logits of shape (2, 16, vocab)."""
    cfg, init, loss_fn, forward = _family(family)
    compute = _bf16_copy(init(cfg, jax.random.PRNGKey(0)))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    return cfg, loss_fn, forward, compute, tokens


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations' parameters (pjit, custom_vjp, remat, scan)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _kept_f32(loss, compute, shape):
    """What ``value_and_grad(loss)`` keeps in float32 at ``shape``: the
    backward pass's residuals, and matmul operands and results."""
    _, pullback = jax.vjp(loss, compute)
    kept = [
        f"residual {leaf.dtype}{list(leaf.shape)}"
        for leaf in jax.tree_util.tree_leaves(pullback)
        if getattr(leaf, "shape", None) == shape and leaf.dtype == jnp.float32
    ]
    closed = jax.make_jaxpr(jax.value_and_grad(loss))(compute)
    for eqn in _equations(closed.jaxpr):
        if eqn.primitive.name == "dot_general":
            kept += [
                f"dot_general {v.aval.str_short()}"
                for v in (*eqn.invars, *eqn.outvars)
                if v.aval.shape == shape and v.aval.dtype == jnp.float32
            ]
    return kept


@pytest.mark.parametrize("family", ["dense", "olmoe"])
def test_the_training_loss_keeps_no_float32_logits(family):
    cfg, loss_fn, _, compute, tokens = _small_case(family)
    shape = (2, 16, cfg.vocab_size)
    assert _kept_f32(lambda p: loss_fn(cfg, p, tokens), compute, shape) == []


def test_the_guard_sees_the_formula_this_replaced():
    """Autodiff through ``log_softmax`` of the widened logits keeps a
    float32 array of their shape for the backward pass: the guard above
    must name it, or it guards nothing."""
    cfg, _, forward, compute, tokens = _small_case("dense")

    def parents_loss(p):
        logp = jax.nn.log_softmax(forward(cfg, p, tokens[:, :-1]), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))

    assert _kept_f32(parents_loss, compute, (2, 16, cfg.vocab_size)) != []


@pytest.mark.parametrize("family", ["dense", "olmoe"])
def test_forward_returns_the_product_widened(family):
    """float32 logits for whoever asks for them, each a bf16 value, and
    the loss of them is ``loss_fn``'s: one model, two widths of storage."""
    cfg, loss_fn, forward, compute, tokens = _small_case(family)
    logits = forward(cfg, compute, tokens[:, :-1])
    assert logits.dtype == jnp.float32 and logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(logits == logits.astype(jnp.bfloat16).astype(jnp.float32)))
    if family == "dense":  # OLMoE's loss adds the router's two terms
        from torchft_tpu.models.transformer import next_token_loss

        want = next_token_loss(logits, tokens[:, 1:])
        assert abs(float(loss_fn(cfg, compute, tokens)) - float(want)) <= 1e-6 * float(want)


def _compiled_text(lowered) -> str:
    """What the TPU compiler makes of ``lowered`` for the described chip.
    Such a compile is written to the persistent cache and cannot be read
    back without a chip: the cache is kept out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip for the TPU compiler; made in
    this fixture and nowhere at import, so every xdist worker collects
    the same tests and only the one that runs them loads the library."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the library says
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("family", ["dense", "olmoe"])
def test_the_compiled_gradient_stores_no_float32_logits(family, one_chip):
    """What the TPU compiler makes of it: an instruction of the entry
    computation is an array in HBM; those inside a fusion are not. The
    gradient step at sizes where the logits are the largest array (4 x 512
    x 8192) must hold them in bf16 only. The parent's held ``f32[B,S,V]
    fusion(...)``, the shifted logits, written for a gather of one value a
    row."""
    import dataclasses
    import re

    cfg, init, loss_fn, _ = _family(family)
    cfg = dataclasses.replace(
        cfg, vocab_size=8192, **({"max_seq_len": 512} if family == "dense" else {})
    )
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), params
    )
    tokens = jax.ShapeDtypeStruct((4, 513), jnp.int32, sharding=one_chip)

    def loss_and_grads(masters, tokens):
        return jax.value_and_grad(lambda q: loss_fn(cfg, q, tokens))(_bf16_copy(masters))

    text = _compiled_text(jax.jit(loss_and_grads).lower(params, tokens))
    entry = text[text.index("ENTRY"):]
    logits = re.findall(r"= \(?[^=]*?\b(f32|bf16)\[4,512,8192\]", entry)
    assert "bf16" in logits, "the logits are not an array of this program at all"
    assert "f32" not in logits, re.findall(r".*f32\[4,512,8192\].*", entry)[:3]


def test_the_block_schedule_compiles_for_the_chip(one_chip):
    """``sdar-ft1``'s flash pair (L 4096, B 4, head size 128; two heads, as
    the kernels hold one a grid step) through the TPU compiler for the
    described chip: a body that does not fit the VMEM it names, or a
    layout Mosaic accepts and the compiler does not, is refused here and
    not on the chip (about 4 s). What this cannot see is the last per
    cent of scoped VMEM inside the whole step (PR 37: 180 KB)."""
    q = jax.ShapeDtypeStruct((1, 8192, 2, 128), jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(jax.jit(jax.grad(_block_pair, argnums=(0, 1, 2))).lower(q, q, q))
    assert text.count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("seq", [8192, 4000])
def test_two_widths_compile_for_the_chip(seq, one_chip):
    """The latent cells' flash pair as they run it - q and k 192 lanes wide
    beside v at 128, 8,192 positions on sixteen resident blocks of 512 (and
    a length that pads), two head-rows - through the TPU compiler for the
    described chip: a 192-lane block is the array's whole last dimension,
    which Mosaic's lane rule allows, in a tile and a half of VMEM that
    ``_resident_params`` counts as two (about 2 s)."""
    from torchft_tpu.ops import flash_attention_rows

    q = jax.ShapeDtypeStruct((2, seq, 192), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((2, seq, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return flash_attention_rows(q, k, v, interpret=False).astype(jnp.float32).sum()

    text = _compiled_text(jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, v))
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    grads = jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2)), q, q, v)
    assert [g.shape for g in grads] == [(2, seq, 192), (2, seq, 192), (2, seq, 128)]


def test_the_ling_cells_step_compiles_for_the_chip_with_what_its_family_states(one_chip, monkeypatch):
    """``ling3-ft1``'s gradient step as the generators lower it
    (``mixed_precision_grad``), at the published head sizes (128, 64
    rotated, a latent of 512) and cut elsewhere - the six layers of the
    cell's period, 2 heads, 2 of 16 experts, 1,024 positions - through the
    TPU compiler for the described chip: the Mosaic calls are the ONE
    latent-attention layer's flash pair, q.k at 192 lanes and v at 128,
    which the family states (its count is no ``2 x layers``, so the benchmark's own case
    cannot be borrowed); the delta rule's scan, the triangular solve under
    it and the held share compile as plain XLA."""
    from benchmark import common

    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    sizes = common.load_json("configs", "ling3-flash-l6-ep64.json")
    sizes = {**sizes, **sizes["rehearsal"], "hidden_size": 256, "head_dim": 128,
             "qk_nope_head_dim": 128, "v_head_dim": 128, "qk_rope_head_dim": 64,
             "kv_lora_rank": 512, "num_experts": 2, "seq": 1025}
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    assert [type(k.mixer).__name__ for k in cfg.kinds] == ["Kda"] * 5 + ["Mla"]
    assert family.lowered_mosaic_calls(cfg) == 2

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), tree
        )

    params = on_chip(jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((1, sizes["seq"]), jnp.int32, sharding=one_chip)
    lowered = jax.jit(common.mixed_precision_grad(family, cfg)).lower(params, tokens)
    common.require_mosaic(lowered, 2, "ling3-ft1")
    assert 'kernel_name = "flash_fwd"' in lowered.as_text()
    text = _compiled_text(lowered)
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_the_dsv2_cells_step_compiles_for_the_chip_with_what_its_family_states(one_chip, monkeypatch):
    """``dsv2lite-ft1``'s gradient step as the generators lower it
    (``mixed_precision_grad``), at the published head sizes (128 + 64
    rotated, a latent of 512, YaRN's published numbers) and cut elsewhere -
    the cell's five layers, 2 heads, 2 of 16 experts, 1,024 positions -
    through the TPU compiler for the described chip: the Mosaic calls are
    the flash pair of EVERY layer, ten, q.k at 192 lanes and v at 128,
    which the family states; the held share and the balance loss a sequence compile as
    plain XLA."""
    from benchmark import common

    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    sizes = common.load_json("configs", "dsv2-lite-l5-ep8.json")
    published = sizes["rope_scaling"]
    sizes = {**sizes, **sizes["rehearsal"], "hidden_size": 256, "qk_nope_head_dim": 128,
             "v_head_dim": 128, "qk_rope_head_dim": 64, "kv_lora_rank": 512,
             "n_routed_experts": 2, "rope_scaling": published, "seq": 1025}
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    assert [type(k.mixer).__name__ for k in cfg.kinds] == ["Mla"] * 5
    assert cfg.ff == (96, None, None, None, None) and cfg.held == (0, 2)
    assert family.lowered_mosaic_calls(cfg) == 10

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), tree
        )

    params = on_chip(jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((1, sizes["seq"]), jnp.int32, sharding=one_chip)
    lowered = jax.jit(common.mixed_precision_grad(family, cfg)).lower(params, tokens)
    common.require_mosaic(lowered, 10, "dsv2lite-ft1")
    text = _compiled_text(lowered)
    assert text.count('custom_call_target="tpu_custom_call"') == 10


def test_the_granite_cells_step_compiles_for_the_chip_with_what_its_family_states(one_chip, monkeypatch):
    """``granite4h-ft1``'s gradient step as the generators lower it
    (``mixed_precision_grad``), at the published head sizes (attention 4
    query heads over 1 key/value head of 64 at 1/64; Mamba-2 heads of 64
    over a state of 128 in chunks of 256) and cut elsewhere - the rehearsal's
    four layers, a width of 256, 1,024 positions - through the TPU compiler
    for the described chip, every layer recomputed (``STACK_KEPT`` names no
    kernel's result, so its flash call too): the Mosaic calls are the
    ONE attention layer's ``flash_fwd``, its recomputed ``flash_fwd`` and its
    ``flash_bwd``, THREE, which the family states (its count is no ``2 x
    layers``, so the benchmark's own case cannot be borrowed) and which the
    compiler keeps apart (the recomputed forward call is not merged with the
    first); the state-space scan compiles as plain XLA."""
    from benchmark import common

    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    sizes = common.load_json("configs", "granite4-h-micro-l10-v8.json")
    sizes = {**sizes, **sizes["rehearsal"], "hidden_size": 256, "num_attention_heads": 4,
             "num_key_value_heads": 1, "attention_multiplier": 0.015625, "mamba_n_heads": 8,
             "mamba_d_head": 64, "mamba_d_state": 128, "mamba_chunk_size": 256, "seq": 1025}
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    assert [type(k.mixer).__name__ for k in cfg.kinds] == ["Mamba2", "Mamba2", "NoneType", "Mamba2"]
    assert cfg.recompute_layers and (cfg.head_dim, cfg.kinds[2].softmax_scale) == (64, 1 / 64)
    assert family.lowered_mosaic_calls(cfg) == 3

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), tree
        )

    params = on_chip(jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((1, sizes["seq"]), jnp.int32, sharding=one_chip)
    lowered = jax.jit(common.mixed_precision_grad(family, cfg)).lower(params, tokens)
    common.require_mosaic(lowered, 3, "granite4h-ft1")
    assert lowered.as_text().count('kernel_name = "flash_fwd"') == 2
    text = _compiled_text(lowered)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_the_nemotron_cells_step_compiles_for_the_chip_with_what_its_family_states(one_chip, monkeypatch):
    """``nemotron3n-ft1``'s gradient step as the generators lower it
    (``mixed_precision_grad``), at the published head sizes (attention 16
    query heads over 1 key/value head of 128, the published ratio; Mamba-2
    heads of 64 over a state of 128 in 2 groups, chunks of 128) and cut
    elsewhere - the rehearsal's five layers ``MEM*E``, a width of 256, 1,024
    positions, 2 of 8 experts held - through the TPU compiler for the
    described chip, every layer recomputed: a held share (a ``custom_vjp``
    whose loops follow the routing) and a grouped scan under ONE stack's
    checkpoints compile, and the Mosaic calls are the one attention layer's
    ``flash_fwd``, its recomputed ``flash_fwd`` and its ``flash_bwd``, THREE,
    which the family states; the scan and the share compile as plain XLA."""
    from benchmark import common

    monkeypatch.setattr(
        sys.modules["torchft_tpu.ops.flash_attention"], "_pick_interpret", lambda _i: False
    )
    sizes = common.load_json("configs", "nemotron3-nano-l9-ep16.json")
    sizes = {**sizes, **sizes["rehearsal"], "hidden_size": 256, "num_attention_heads": 16,
             "num_key_value_heads": 1, "head_dim": 128, "mamba_num_heads": 8,
             "mamba_head_dim": 64, "ssm_state_size": 128, "chunk_size": 128, "seq": 1025}
    family = common.load_family(sizes["family"])
    cfg = family.build(sizes)
    assert cfg.parts == ("mixer", "ff", "mixer", "mixer", "ff") and cfg.recompute_layers
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.held) == (16, 1, 128, (0, 2))
    assert family.lowered_mosaic_calls(cfg) == 3

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip), tree
        )

    params = on_chip(jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct((1, sizes["seq"]), jnp.int32, sharding=one_chip)
    lowered = jax.jit(common.mixed_precision_grad(family, cfg)).lower(params, tokens)
    common.require_mosaic(lowered, 3, "nemotron3n-ft1")
    assert lowered.as_text().count('kernel_name = "flash_fwd"') == 2
    text = _compiled_text(lowered)
    assert text.count('custom_call_target="tpu_custom_call"') == 3
