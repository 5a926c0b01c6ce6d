"""Numerics of the pallas flash-attention kernel vs dense reference.

Runs in interpret mode on the CPU test mesh (conftest pins JAX_PLATFORMS=cpu
with 8 virtual devices); on real TPU the same code compiles to Mosaic.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import flash_attention, flash_attention_qkv


def dense_attention(q, k, v, causal=True, sm_scale=None, window=None):
    B, S, H, D = q.shape
    if sm_scale is None:
        sm_scale = D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * sm_scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = (qpos >= kpos) if causal else jnp.ones((S, S), jnp.bool_)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def rand_qkv(key, shape, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


# The causal calls with block_q a multiple of block_k run the two-level
# schedule (resident block of block_q rows, sub-tiles of block_k):
# (64, 32) at S 128 has two blocks of two row groups (the dynamic loop over
# the blocks to the left, a wide unmasked tile, a diagonal sub-tile);
# (128, 32) is the whole sequence resident, all static, as on the chip at
# S 1024; 127 and 129 end just under and just over a block multiple.
NESTED = [
    (128, (64, 32)), (128, (128, 32)), (127, (64, 32)), (129, (64, 32)),
    (192, (64, 16)),
]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "S,blocks", [(128, (64, 64)), (96, (32, 32))] + NESTED
)
def test_forward_matches_dense(causal, S, blocks):
    q, k, v = rand_qkv(jax.random.PRNGKey(0), (2, S, 2, 32))
    out = flash_attention(
        q, k, v, causal=causal, block_q=blocks[0], block_k=blocks[1]
    )
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,blocks", [(64, (32, 32))] + NESTED)
def test_grads_match_dense(S, blocks):
    q, k, v = rand_qkv(jax.random.PRNGKey(1), (1, S, 2, 16))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, block_q=blocks[0], block_k=blocks[1])
        return jnp.sum(out ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_olmoe_shape_eight_blocks_a_side(dtype):
    """OLMoE's and Ouro's heads in the benchmark (H 16, D 128;
    chip_smoke's ``olmoe`` and ``ouro`` cases) at a small S: eight blocks
    a side, 256 positions in (32, 32) as 4096 in (512, 512), their tiles
    until PR 52. Forward and gradients against the dense path; in bf16
    at chip_smoke's tolerance."""
    q, k, v = rand_qkv(jax.random.PRNGKey(7), (2, 256, 16, 128), dtype)

    def loss(attend, q, k, v):
        out = attend(q, k, v).astype(jnp.float32)
        return jnp.sum(out ** 2), out

    flash = functools.partial(flash_attention, block_q=32, block_k=32)
    dense = lambda q, k, v: dense_attention(*(t.astype(jnp.float32) for t in (q, k, v)))
    grad = lambda f: jax.jit(jax.value_and_grad(
        functools.partial(loss, f), argnums=(0, 1, 2), has_aux=True
    ))
    (_, out), grads = grad(flash)(q, k, v)
    (_, ref), ref_grads = grad(dense)(q, k, v)
    tol = 1e-4 if dtype == jnp.float32 else 0.02
    for got, want, name in zip((out, *grads), (ref, *ref_grads), ("out", "dq", "dk", "dv")):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))
        assert err <= tol, (name, err)


# The long causal calls' form since PR 52, scaled down by 32: S 128 on
# resident blocks of 32 rows in two row groups of 16, as 4,096 positions
# on (1024, 512) - four blocks, the last one's loop over the blocks to its
# left three trips (the backward's first key block meets three query blocks
# below it), a wide tile and a diagonal sub-tile a row group - with the
# sub-tile a staircase of chunks of 8 and of 4 (256 forward and 128
# backward there); 125 ends inside the last block.
@pytest.mark.parametrize("edge", [8, 4])
@pytest.mark.parametrize("S", [128, 125])
def test_four_resident_blocks_of_two_row_groups_match_dense(S, edge):
    schedule = _module()._tiles(S, 16, True, 32, 16, edge)
    assert schedule.kind == "nested" and schedule.edges == (edge, edge)
    assert (schedule.s_pad // schedule.block_q, schedule.block_q // schedule.block_k) == (4, 2)
    q, k, v = rand_qkv(jax.random.PRNGKey(52), (2, S, 2, 16))

    def loss(attend, q, k, v):
        out = attend(q, k, v)
        return jnp.sum(out ** 2), out

    flash = functools.partial(flash_attention, block_q=32, block_k=16, block_diag=edge)
    grad = lambda f: jax.value_and_grad(
        functools.partial(loss, f), argnums=(0, 1, 2), has_aux=True
    )
    (_, out), grads = grad(flash)(q, k, v)
    (_, ref), ref_grads = grad(dense_attention)(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(grads, ref_grads, "qkv"):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


def test_noncausal_grads_match_dense():
    q, k, v = rand_qkv(jax.random.PRNGKey(5), (1, 64, 1, 16))
    gf = jax.grad(
        lambda q: jnp.sum(
            flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
        )
    )(q)
    gd = jax.grad(
        lambda q: jnp.sum(dense_attention(q, k, v, causal=False))
    )(q)
    np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)


def test_under_jit_bf16():
    q, k, v = rand_qkv(jax.random.PRNGKey(2), (2, 128, 4, 16), jnp.bfloat16)
    out = jax.jit(
        functools.partial(flash_attention, block_q=64, block_k=64)
    )(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(
        out.astype(np.float32), ref.astype(np.float32), atol=3e-2, rtol=3e-2
    )


def test_sharded_over_mesh_matches_dense():
    from torchft_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "model": 4})
    q, k, v = rand_qkv(jax.random.PRNGKey(3), (2, 64, 4, 16))
    out = flash_attention(
        q, k, v, mesh=mesh, batch_axis="data", head_axis="model",
        block_q=32, block_k=32,
    )
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_transformer_flash_matches_dense_path():
    import dataclasses

    from torchft_tpu.models import init_params, loss_fn, tiny_config

    cfg = tiny_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 65)),
        jnp.int32,
    )
    cfg_flash = dataclasses.replace(cfg, use_flash=True)
    l_dense = loss_fn(cfg, params, tokens)
    l_flash = loss_fn(cfg_flash, params, tokens)
    np.testing.assert_allclose(l_flash, l_dense, atol=1e-4, rtol=1e-4)

    g_dense = jax.grad(lambda p: loss_fn(cfg, p, tokens))(params)
    g_flash = jax.grad(lambda p: loss_fn(cfg_flash, p, tokens))(params)
    leaves_d = jax.tree_util.tree_leaves(g_dense)
    leaves_f = jax.tree_util.tree_leaves(g_flash)
    for a, b in zip(leaves_f, leaves_d):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("causal", [True, False])
def test_nondivisible_seq_is_padded_exactly(causal):
    # S=100 with 64-blocks: padded keys masked, padded query cotangents
    # zero — forward AND grads must match dense exactly
    q, k, v = rand_qkv(jax.random.PRNGKey(4), (1, 100, 2, 8))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
            ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_attention(q, k, v, causal=causal) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
        )


def dense_windowed(q, k, v, window):
    return dense_attention(q, k, v, causal=True, window=window)


@pytest.mark.parametrize("window", [1, 16, 40, 200])
def test_sliding_window_matches_dense(window):
    # windows smaller than / straddling / larger than the 32-blocks
    q, k, v = rand_qkv(jax.random.PRNGKey(7), (1, 128, 2, 16))
    out = flash_attention(
        q, k, v, window=window, block_q=32, block_k=32
    )
    ref = dense_windowed(q, k, v, window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sliding_window_grads_match_dense():
    q, k, v = rand_qkv(jax.random.PRNGKey(8), (1, 96, 2, 8))
    gf = jax.grad(
        lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, window=24, block_q=32, block_k=32) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    gd = jax.grad(
        lambda q, k, v: jnp.sum(dense_windowed(q, k, v, 24) ** 2),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(
            a, b, atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
        )


def test_window_requires_causal():
    q, k, v = rand_qkv(jax.random.PRNGKey(9), (1, 64, 1, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8)


def _dense_rows(q, k, v):
    """``dense_attention`` on rows (BH, S, width), q already scaled: v, and
    so the output, as wide as it likes."""
    return dense_attention(q[:, :, None], k[:, :, None], v[:, :, None], sm_scale=1.0)[:, :, 0]


# (q.k width, v width, S, blocks, edge): a tiny head whose two widths share
# one tile of lanes and the PUBLISHED latent head (192 = a whole tile and
# half of one, beside 128); the whole sequence one resident block, two row
# groups in one, four blocks of two row groups (the dynamic loop, dq the
# revisited row), a staircase finer than the sub-tile, and lengths that pad.
TWO_WIDTHS = [
    (40, 32, 96, None, None), (192, 128, 96, None, None),
    (40, 32, 128, (128, 32), None), (192, 128, 64, (64, 32), 16),
    (40, 32, 128, (32, 16), 8), (192, 128, 128, (32, 32), None),
    (40, 32, 125, (64, 32), 8), (192, 128, 99, (32, 16), None),
    (24, 56, 96, (32, 32), 16),  # and a value WIDER than q and k
]


@pytest.mark.parametrize(
    "d_qk,d_v,S,blocks,edge", TWO_WIDTHS, ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v)
)
def test_a_value_width_of_its_own_matches_dense(d_qk, d_v, S, blocks, edge):
    """The causal kernels with k at q's width and v, the output, dO and dv
    at another: values and the three gradients against the dense float32
    softmax at the true widths, each finite and as wide as its argument."""
    from torchft_tpu.ops import flash_attention_rows

    kw = dict(block_diag=edge)
    if blocks:
        kw.update(block_q=blocks[0], block_k=blocks[1])
    ks = jax.random.split(jax.random.PRNGKey(d_qk + S), 3)
    q = jax.random.normal(ks[0], (2, S, d_qk)) * d_qk ** -0.5
    k, v = jax.random.normal(ks[1], (2, S, d_qk)), jax.random.normal(ks[2], (2, S, d_v))
    schedule = _module()._tiles(
        S, d_qk, True, kw.get("block_q"), kw.get("block_k"), edge, value_dim=d_v
    )
    assert (schedule.kind, schedule.d_v) == ("nested", d_v)
    with jax.default_matmul_precision("highest"):
        got, want = flash_attention_rows(q, k, v, **kw), _dense_rows(q, k, v)
        grads = jax.grad(
            lambda *a: jnp.sum(jnp.cos(flash_attention_rows(*a, **kw))), argnums=(0, 1, 2)
        )(q, k, v)
        wants = jax.grad(lambda *a: jnp.sum(jnp.cos(_dense_rows(*a))), argnums=(0, 1, 2))(q, k, v)
    assert got.shape == v.shape
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    for name, a, b, like in zip("qkv", grads, wants, (q, k, v)):
        assert a.shape == like.shape and np.all(np.isfinite(a)), f"d{name}"
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


def test_equal_widths_carry_no_value_width():
    """v as wide as q is today's call: the schedule, the static argument of
    the kernels' builders, is the value it was."""
    fa = _module()
    plain = fa._tiles(8192, 128, False, None, None, None)
    assert plain == fa._tiles(8192, 128, False, None, None, None, value_dim=128)
    assert plain.d_v is None and plain._replace(d_v=64) != plain


# What no kernel is built for is refused before anything is traced. k always
# has q's width. v may have its own on the nested causal schedule through
# ``flash_attention_rows`` and nowhere else: under a window (banded or
# general), a block mask (blocked or general), non-causal, blocks that do
# not nest, the (B, S, H, D) form. (The fused projection is ONE array of
# three equal thirds: below.)
_ROWS, _HEADS = (2, 64, 256), (1, 64, 2, 256)
REFUSED = [
    ("rows-k", "rows", "k", {}, "q and k"),
    ("heads-k", "heads", "k", {}, "q and k"),
    ("heads-v", "heads", "v", {}, "takes one width"),
    ("heads-v-window", "heads", "v", {"window": 16}, "takes one width"),
    ("rows-k-window", "rows", "k", {"window": 32, "block_q": 32, "block_k": 16}, "q and k"),
    ("rows-v-banded", "rows", "v", {"window": 32, "block_q": 32, "block_k": 16}, "is banded"),
    ("rows-v-window-general", "rows", "v", {"window": 24, "block_q": 32, "block_k": 16}, "is general"),
    ("rows-v-blocked", "rows", "v",
     {"causal": False, "block_mask": (4, 32), "block_q": 16, "block_k": 16}, "is blocked"),
    ("rows-v-block-mask-general", "rows", "v", {"causal": False, "block_mask": (4, 32)}, "is general"),
    ("rows-v-noncausal", "rows", "v", {"causal": False}, "is general"),
    ("rows-v-blocks-do-not-nest", "rows", "v", {"block_q": 16, "block_k": 32}, "is general"),
]


@pytest.mark.parametrize("case,entry,narrow,kw,match", REFUSED, ids=[c[0] for c in REFUSED])
def test_a_key_or_value_of_another_width_is_refused(case, entry, narrow, kw, match, monkeypatch):
    """A value of 128 beside q.k of 256 through kernels whose blocks were
    all cut at q's width compiled and ran on the chip and answered NaN in dq
    and dk (PERF.md section 6, PR 50). The nested causal kernels now take
    v at its own width; every other combination raises, and no kernel's
    builder is reached."""
    from torchft_tpu.ops import flash_attention_rows

    fa = _module()
    for name in ("_flash_fwd_call", "_flash_bwd_call", "_flash"):
        monkeypatch.setattr(fa, name, lambda *a, **k: pytest.fail("a kernel was built"))
    q = jnp.zeros(_ROWS if entry == "rows" else _HEADS, jnp.float32)
    args = {"q": q, "k": q, "v": q, narrow: q[..., :128]}
    call = flash_attention_rows if entry == "rows" else flash_attention
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda q, k, v: call(q, k, v, **kw), args["q"], args["k"], args["v"])


@pytest.mark.parametrize("width", [2 * (192 + 192 + 128), 2 * 3 * 64 + 2])
def test_the_fused_entry_takes_three_equal_thirds(width):
    """``flash_attention_qkv`` cuts ONE array into q, k and v of one width:
    a projection whose last width is no ``3 x heads x head_dim`` - a latent
    layer's q, k and v side by side - is refused, not cut somewhere."""
    with pytest.raises(ValueError, match="three equal"):
        jax.eval_shape(
            lambda qkv: flash_attention_qkv(qkv, 2), jnp.zeros((1, 64, width), jnp.float32)
        )


def test_transformer_attn_window():
    import dataclasses

    from torchft_tpu.models import init_params, loss_fn, tiny_config

    cfg = dataclasses.replace(tiny_config(), use_flash=True, attn_window=16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 65)),
        jnp.int32,
    )
    l_win = float(loss_fn(cfg, params, tokens))
    l_full = float(
        loss_fn(dataclasses.replace(cfg, attn_window=None), params, tokens)
    )
    assert np.isfinite(l_win) and abs(l_win - l_full) > 1e-6  # window bites

    with pytest.raises(ValueError, match="use_flash"):
        dataclasses.replace(tiny_config(), attn_window=16)
    # windowing is not implemented on the CP paths: must refuse, not
    # silently train full-attention
    with pytest.raises(ValueError, match="context-parallel"):
        dataclasses.replace(
            tiny_config(), use_flash=True, attn_window=16,
            cp_seq_axis="seq",
        )


def _module():
    import sys

    # the function of the same name shadows the module in torchft_tpu.ops
    return sys.modules["torchft_tpu.ops.flash_attention"]


# (S, interpret) -> tiles of a causal call without a window, at every
# head size: up to 2048 padded positions the whole sequence is resident
# and cut into the largest sub-tile of 512 / 256 / 128 that divides it
# (PERF.md section 6, PR 25); the general path keeps the tiles of before.
@pytest.mark.parametrize("head_dim", [64, 128, 256])
@pytest.mark.parametrize(
    "S,interpret,want",
    [
        (99, False, (128, 128)), (256, False, (256, 256)),
        (1023, False, (1024, 512)), (1024, False, (1024, 512)),
        (1025, False, (1152, 128)), (2047, False, (2048, 512)),
        (2048, False, (2048, 512)),
        (99, True, (104, 104)), (64, True, (64, 64)),
        (1024, True, (1024, 512)),
    ],
)
def test_auto_tiles(S, head_dim, interpret, want):
    fa = _module()
    assert fa._auto_tiles(S, head_dim, interpret) == want
    # a window or a non-causal call keeps the general path and its tiles
    general = (512, 512) if S >= 2047 else (128, 128)
    assert fa._auto_tiles(S, head_dim, interpret, nested=False) == general


# Past 2048 padded positions, at head sizes 64 and 128: resident blocks of
# 1024 rows in two row groups of 512 where they pad the sequence no
# further than the (512, 512) of before does - 4,096 and 8,192 positions,
# the cells' lengths, with and without the token the loss slices off - and
# (512, 512) where they would: 2,560 and 4,608 positions are five and nine
# blocks of 512. 256 lanes (``ling3-ft1``'s latent attention as it is
# padded) keep (512, 512) at every length: the larger block buys 0.44 ms
# of that step for 9.7 s of its cold set-up (PERF.md section 6, PR 52).
@pytest.mark.parametrize("head_dim", [64, 128, 256])
@pytest.mark.parametrize(
    "S,interpret,want",
    [
        (4095, False, (1024, 512)), (4096, False, (1024, 512)),
        (8191, False, (1024, 512)), (8192, False, (1024, 512)),
        (3000, False, (1024, 512)), (2560, False, (512, 512)),
        (4608, False, (512, 512)), (4097, False, (512, 512)),
        (4096, True, (1024, 512)), (2056, True, (512, 512)),
    ],
)
def test_auto_tiles_past_one_resident_block(S, head_dim, interpret, want):
    fa = _module()
    assert fa._auto_tiles(S, head_dim, interpret) == (want if head_dim <= 128 else (512, 512))
    assert fa._auto_tiles(S, head_dim, interpret, nested=False) == (512, 512)
    # whatever the rule answers pads the sequence as (512, 512) did
    schedule = fa._tiles(S, head_dim, interpret, None, None, None)
    assert schedule.kind == "nested" and not schedule.one_resident_block
    assert schedule.s_pad == -(-S // 512) * 512


# (S, window) -> tiles of a causal call with a window: the largest
# sub-tile of 1024 / 512 / 256 / 128 that divides the window, one a
# resident block (PERF.md section 6, PR 37); a window that none divides,
# or as long as the sequence, keeps the general path's tiles.
@pytest.mark.parametrize("interpret", [False, True])
@pytest.mark.parametrize(
    "S,window,want",
    [
        (8192, 1024, (1024, 1024)), (2047, 512, (512, 512)),
        (1024, 256, (256, 256)), (3000, 1024, (1024, 1024)),
        (4096, 384, (128, 128)), (8192, 4096, (1024, 1024)),
        (8192, 1000, (512, 512)), (8192, 8192, (512, 512)),
        (1024, 1024, (128, 128)), (1023, 2048, (128, 128)),
    ],
)
def test_auto_tiles_of_a_window(S, window, interpret, want):
    fa = _module()
    assert fa._auto_tiles(S, 128, interpret, nested=False, window=window) == want
    schedule = fa._tiles(S, 128, interpret, None, None, None, True, window)
    assert (schedule.block_q, schedule.block_k) == want
    assert schedule.s_pad % schedule.block_q == 0
    banded = window % schedule.block_k == 0 and window < S
    assert schedule.kind == ("banded" if banded else "general")
    assert (schedule.edges is not None) == banded


# What each cell of BENCHMARK.json runs today, asked of ``_tiles`` at the
# cell's published attention shapes as the chip is asked (``interpret``
# False): (in-model positions, head size, window, block mask) -> the kind
# and the tiles. A PR that moves a cell onto another schedule changes this
# table and says so. Last, the three block-mask shapes of
# ``tests/test_tpu_lowering.py`` that do not tile: the general kernels.
@pytest.mark.parametrize(
    "cell,S,head_dim,window,block_mask,want",
    [
        ("gpt2s-ft1", 1024, 64, None, None, ("nested", 1024, 512, (512, 128))),
        ("gpt2m-ft1", 1024, 64, None, None, ("nested", 1024, 512, (512, 128))),
        ("gpt2s-raw", 1024, 64, None, None, ("nested", 1024, 512, (512, 128))),
        ("gpt2m-raw", 1024, 64, None, None, ("nested", 1024, 512, (512, 128))),
        ("olmoe-ft1", 4096, 128, None, None, ("nested", 1024, 512, (256, 128))),
        ("ouro-ft1", 4096, 128, None, None, ("nested", 1024, 512, (256, 128))),
        ("mellum2-ft1-sliding", 8192, 128, 1024, None, ("banded", 1024, 1024, (256, 128))),
        ("mellum2-ft1-full", 8192, 128, None, None, ("nested", 1024, 512, (256, 128))),
        ("sdar-ft1", 8192, 128, None, (4, 4096), ("blocked", 1024, 1024, (256, 128))),
        # latent attention: q.k at 192 and v at 128, each at its own width
        ("ling3-ft1-mla", 8192, (192, 128), None, None, ("nested", 512, 512, (256, 128))),
        ("dsv2lite-ft1-mla", 8192, (192, 128), None, None, ("nested", 512, 512, (256, 128))),
        # one NoPE layer in ten: 32 query rows a sequence at head size 64
        ("granite4h-ft1-nope", 4096, 64, None, None, ("nested", 1024, 512, (512, 128))),
        # one NoPE layer in nine: 32 query rows a sequence (16 a key/value head) at 128
        ("nemotron3n-ft1-nope", 8192, 128, None, None, ("nested", 1024, 512, (256, 128))),
        ("a-block-over-a-tile", 1920, 128, None, (6, 960), ("general", 128, 128, None)),
        ("one-block-a-copy", 4096, 128, None, (2048, 2048), ("general", 512, 512, None)),
        ("a-padded-length", 400, 128, None, (8, 200), ("general", 128, 128, None)),
    ],
)
def test_the_schedule_each_cell_runs(cell, S, head_dim, window, block_mask, want):
    d_qk, d_v = head_dim if isinstance(head_dim, tuple) else (head_dim, head_dim)
    schedule = _module()._tiles(
        S, d_qk, False, None, None, None, block_mask is None, window, block_mask, d_v
    )
    assert schedule[:3] + (schedule.edges,) == want, cell
    # a value width of its own engages where a cell's v is not as wide as its q
    assert schedule.d_v == (None if d_v == d_qk else d_v), cell
    assert schedule.s_pad == -(-S // 128) * 128 and schedule.kv_len == S
    # the fused entry (the dense cells') asks the same value
    assert schedule.one_resident_block == cell.startswith("gpt2")


# What a call runs falls out of its shapes (S 64 here; under a block mask
# two copies of L 32): the two-level schedule whole, the same cut to a
# window's band or laid over the block mask, or the general kernels. The
# ``kind`` that ``_tiles`` hands the call, and the kernel traced for it in
# BOTH directions.
@pytest.mark.parametrize(
    "kw,want",
    [
        ({}, "nested"),
        ({"window": 32, "block_q": 32, "block_k": 16}, "banded"),
        ({"window": 16, "block_q": 16, "block_k": 16}, "banded"),
        ({"window": 32, "block_q": 64, "block_k": 16}, "banded"),  # one block
        ({"window": 16}, "general"),  # (64, 64) tiles: no multiple of them
        ({"window": 24, "block_q": 32, "block_k": 16}, "general"),
        ({"window": 64, "block_q": 32, "block_k": 32}, "general"),  # the whole sequence
        ({"window": 32, "block_q": 16, "block_k": 32}, "general"),  # do not nest
        ({"causal": False}, "general"),
        ({"block_q": 16, "block_k": 32}, "general"),  # do not nest
        ({"block_mask": (4, 32), "block_q": 16, "block_k": 16}, "blocked"),
        ({"block_mask": (4, 32), "block_q": 32, "block_k": 16}, "blocked"),  # two row groups
        ({"block_mask": (4, 32)}, "general"),  # (64, 64) tiles: a copy ends inside
        ({"block_mask": (32, 32), "block_q": 16, "block_k": 16}, "general"),  # B over a sub-tile
        ({"block_mask": (4, 32), "block_q": 16, "block_k": 32}, "general"),  # do not nest
    ],
    ids=lambda v: ("-".join(f"{k}{n}" for k, n in v.items()) or "plain")
    if isinstance(v, dict) else v,
)
def test_which_kernels_a_call_takes(kw, want, monkeypatch):
    fa = _module()
    took, handed = set(), []
    for name in ("_fwd_causal_kernel", "_bwd_causal_kernel", "_fwd_kernel", "_bwd_kernel"):
        def spy(*a, _real=getattr(fa, name), **kernel_kw):
            if "causal_kernel" not in _real.__name__:
                kind = "general"
            elif kernel_kw.get("window") is not None:
                kind = "banded"
            else:
                kind = "nested" if kernel_kw.get("block_mask") is None else "blocked"
            took.add((_real.__name__, kind))
            return _real(*a, **kernel_kw)

        monkeypatch.setattr(fa, name, spy)
    real_tiles = fa._tiles
    monkeypatch.setattr(
        fa, "_tiles", lambda *a, **k: handed.append(real_tiles(*a, **k)) or handed[-1]
    )
    if "block_mask" in kw:
        kw = {"causal": False, **kw}
    # a head size no other test has: the calls that build a kernel are
    # traced once a shape
    q, k, v = rand_qkv(jax.random.PRNGKey(11), (1, 64, 1, 24))
    jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, **kw) ** 2))(q)
    prefix = "_" if want == "general" else "_causal_"
    assert took == {(f"_fwd{prefix}kernel", want), (f"_bwd{prefix}kernel", want)}
    assert [schedule.kind for schedule in handed] == [want]


# ---------------------------------------------------------------------------
# a window on the two-level schedule: the band (PR 37)
# ---------------------------------------------------------------------------


# (S, window, blocks, edge). Row group g of ``block_k`` rows meets its
# diagonal staircase, min(g, window / block_k - 1) unmasked sub-tiles and,
# from group window / block_k on, the window's edge as the mirrored
# staircase: 8 groups at (256, 64, (32, 32)) are the first two, short of
# pieces, and six interior ones; an edge of ``block_k`` is the sub-tiles
# whole; window 32 = ``block_k`` has no unmasked tile, 96 has two; (64, 32)
# is two row groups a resident block; 250 and 100 end inside a block
# (padded); (128, 32) at S 128 is ONE resident block, every place static.
BANDED = [
    (256, 64, (32, 32), 8), (256, 64, (32, 32), 32), (256, 32, (32, 32), 16),
    (250, 64, (64, 32), 16), (192, 96, (64, 32), 32), (128, 64, (128, 32), 16),
    (100, 32, (32, 32), 16), (256, 128, (64, 64), 8),
]
_banded_id = lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v)


def _band_spy(monkeypatch):
    """The (window, edge) the two-level kernels are built with."""
    fa = _module()
    built = []
    for name in ("_fwd_causal_kernel", "_bwd_causal_kernel"):
        def spy(*a, _real=getattr(fa, name), **kw):
            built.append((kw.get("window"), kw["edge"]))
            return _real(*a, **kw)

        monkeypatch.setattr(fa, name, spy)
    return built


@pytest.mark.parametrize("S,window,blocks,edge", BANDED, ids=_banded_id)
def test_band_forward_matches_dense(S, window, blocks, edge, monkeypatch):
    built = _band_spy(monkeypatch)
    q, k, v = rand_qkv(jax.random.PRNGKey(S + window), (2, S, 2, 40))
    out = flash_attention(
        q, k, v, window=window, block_q=blocks[0], block_k=blocks[1], block_diag=edge
    )
    assert built == [(window, edge)]
    np.testing.assert_allclose(out, dense_windowed(q, k, v, window), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,window,blocks,edge", BANDED, ids=_banded_id)
def test_band_grads_match_dense(S, window, blocks, edge, monkeypatch):
    built = _band_spy(monkeypatch)
    q, k, v = rand_qkv(jax.random.PRNGKey(S + window + 1), (1, S, 2, 24))

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v) ** 2)

    flash = functools.partial(
        flash_attention, window=window, block_q=blocks[0], block_k=blocks[1],
        block_diag=edge,
    )
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(
        loss(functools.partial(dense_windowed, window=window)), argnums=(0, 1, 2)
    )(q, k, v)
    assert built == [(window, edge)] * 2
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


# window 64 in (32, 32) sub-tiles, chunks of 8: the key ``window`` back from
# query row 64 is key 0 (the first row group that has a window's edge), from
# 96 a sub-tile's first, from 104 a chunk's first, from 100 and 131 inside a
# chunk, from 127 a sub-tile's last, from 71 a chunk's last; rows 40 and 63
# still see key 0; 255 is the last row.
@pytest.mark.parametrize("row", [40, 63, 64, 71, 96, 100, 104, 127, 131, 255])
def test_band_is_exact_at_its_edges(row):
    """Query ``row`` sees keys (row - window, row] and no other: a loss on
    that row alone leaves a cotangent on exactly those keys and values -
    key ``row - window`` exactly 0, key ``row - window + 1`` not (a mask one
    off, or a chunk of the window's edge that met a row it should not,
    shows here; the benchmark's ``correct`` does not see it: PERF.md
    section 6) - and the row's output does not change with the others."""
    S, window = 256, 64
    attend = functools.partial(
        flash_attention, window=window, block_q=32, block_k=32, block_diag=8
    )
    q, k, v = rand_qkv(jax.random.PRNGKey(row), (1, S, 2, 16))
    one_row = lambda k, v: attend(q, k, v)[:, row]
    out, pullback = jax.vjp(one_row, k, v)
    dk, dv = pullback(jnp.ones_like(out))
    keys = np.arange(S)
    seen = (keys <= row) & (row - keys < window)
    for name, grad in (("dk", dk), ("dv", dv)):
        moved = np.any(np.asarray(grad) != 0, axis=-1)  # (1, S, heads)
        np.testing.assert_array_equal(
            moved, np.broadcast_to(seen[None, :, None], moved.shape), err_msg=name
        )
    hidden = jnp.asarray(~seen)[None, :, None, None]
    np.testing.assert_array_equal(
        one_row(jnp.where(hidden, 7.0, k), jnp.where(hidden, -7.0, v)), out
    )


# ---------------------------------------------------------------------------
# the fused-projection entry: qkv (B, S, 3*H*D) in, (B, S, H*D) out
# ---------------------------------------------------------------------------


def _fused_calls(monkeypatch):
    """The list that grows by one with every call that takes the fused
    layout's kernels (and not the three-array way out)."""
    fa = _module()
    took = []
    real = fa._flash_qkv
    monkeypatch.setattr(
        fa, "_flash_qkv", lambda *a: took.append("fused") or real(*a)
    )
    return took


def _split_heads(qkv, n_heads):
    B, S, width = qkv.shape
    return tuple(
        t.reshape(B, S, n_heads, width // (3 * n_heads))
        for t in jnp.split(qkv, 3, axis=-1)
    )


# Head size 64 with an even count of heads (two heads a 128-lane block),
# with an odd count (the entry splits and takes the three-array path, by
# shape), head size 128 (one head a block); one position short of a block
# multiple (padded) and on it.
@pytest.mark.parametrize("S", [1023, 1024])
@pytest.mark.parametrize(
    "n_heads,head_dim,fused", [(2, 64, True), (3, 64, False), (2, 128, True)],
    ids=["h2d64", "h3d64-split", "h2d128"],
)
def test_qkv_entry_matches_plain_attention(n_heads, head_dim, fused, S, monkeypatch):
    took = _fused_calls(monkeypatch)
    qkv = jax.random.normal(
        jax.random.PRNGKey(12), (1, S, 3 * n_heads * head_dim), jnp.float32
    )

    def loss(attend, qkv):
        out = attend(qkv)
        return jnp.sum(out ** 2), out

    def plain(qkv):
        return dense_attention(*_split_heads(qkv, n_heads)).reshape(out_shape)

    out_shape = (1, S, n_heads * head_dim)
    grad = lambda f: jax.value_and_grad(functools.partial(loss, f), has_aux=True)
    (_, out), dqkv = grad(lambda x: flash_attention_qkv(x, n_heads))(qkv)
    (_, ref), ref_dqkv = grad(plain)(qkv)
    assert bool(took) == fused
    assert out.shape == out_shape and dqkv.shape == qkv.shape
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(
        jnp.split(dqkv, 3, axis=-1), jnp.split(ref_dqkv, 3, axis=-1), "qkv"
    ):
        np.testing.assert_allclose(
            got, want, atol=1e-4, rtol=1e-4, err_msg=f"d{name}"
        )


@pytest.mark.parametrize("S", [255, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_in_kernel_scale_is_the_outside_fold_bit_for_bit(dtype, S):
    """Head size 64: the scale is 0.125, a power of two, so scaling q
    inside the kernels (and dq as it is written) gives the bits of the
    three-array entry's fold outside them."""
    n_heads = 4
    qkv = jax.random.normal(jax.random.PRNGKey(13), (2, S, 3 * n_heads * 64), dtype)

    def split(qkv):
        out = flash_attention(*_split_heads(qkv, n_heads))
        return out.reshape(out.shape[0], S, n_heads * 64)

    def run(attend):
        return jax.value_and_grad(
            lambda x: jnp.sum(attend(x).astype(jnp.float32) ** 2)
        )(qkv)

    (loss_f, g_f), (loss_s, g_s) = run(lambda x: flash_attention_qkv(x, n_heads)), run(split)
    np.testing.assert_array_equal(
        flash_attention_qkv(qkv, n_heads).astype(np.float32),
        split(qkv).astype(np.float32),
    )
    assert float(loss_f) == float(loss_s)
    np.testing.assert_array_equal(g_f.astype(np.float32), g_s.astype(np.float32))


@pytest.mark.parametrize(
    "n_heads,fused", [(2, True), (3, False)], ids=["h2-fused", "h3-split"]
)
def test_transformer_loss_and_gradient_equal_flash_on_and_off(n_heads, fused, monkeypatch):
    """Head size 64, as every GPT-2 width has it: ``use_flash`` through
    the fused-projection entry (and, with an odd count of heads, through
    its three-array way out) against the dense path."""
    from torchft_tpu.models import TransformerConfig, init_params, loss_fn

    took = _fused_calls(monkeypatch)
    cfg = TransformerConfig(
        vocab_size=256, d_model=64 * n_heads, n_heads=n_heads, n_layers=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
    )
    cfg_flash = dataclasses.replace(cfg, use_flash=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (2, 129)), jnp.int32
    )
    grad = lambda c: jax.value_and_grad(lambda p: loss_fn(c, p, tokens))(params)
    (l_dense, g_dense), (l_flash, g_flash) = grad(cfg), grad(cfg_flash)
    assert len(took) == (cfg.n_layers if fused else 0)
    np.testing.assert_allclose(l_flash, l_dense, atol=1e-5, rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_flash), jax.tree_util.tree_leaves(g_dense)
    ):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# the diagonal sub-tile as a staircase of chunks (PR 35)
# ---------------------------------------------------------------------------


def _attend(entry, n_heads, **blocks):
    """qkv (B, S, 3*H*D) -> (B, S, H*D) through either entry."""
    if entry == "fused":
        return lambda qkv: flash_attention_qkv(qkv, n_heads, **blocks)

    def three(qkv):
        out = flash_attention(*_split_heads(qkv, n_heads), **blocks)
        return out.reshape(*qkv.shape[:2], -1)

    return three


def _out_and_cotangent(attend, qkv):
    def loss(x):
        out = attend(x)
        return jnp.sum(out ** 2), out

    (_, out), dqkv = jax.value_and_grad(loss, has_aux=True)(qkv)
    return out, dqkv


# S 1024 and 1023 (padded) run (1024, 512): two row groups, a wide tile
# and two staircases; S 512 is one row group, the staircase alone. An
# edge of 512 is ``block_k``, the whole sub-tile under one mask: the
# kernels of before. The last case cuts the sequence into four resident
# blocks, so the dynamic loop over the blocks to the left meets the
# staircase (the fused entry sends it to the three-array kernels itself).
@pytest.mark.parametrize(
    "S,blocks",
    [(S, {"block_diag": e}) for S in (1024, 1023, 512) for e in (128, 256, 512)]
    + [(1024, {"block_q": 256, "block_k": 256, "block_diag": 128})],
    ids=lambda v: v if isinstance(v, int) else "-".join(
        f"{k[6:]}{n}" for k, n in v.items()
    ),
)
@pytest.mark.parametrize("head_dim", [64, 128], ids=["d64", "d128"])
@pytest.mark.parametrize("entry", ["fused", "three"])
def test_staircase_matches_plain_attention(entry, head_dim, S, blocks, monkeypatch):
    fa = _module()
    n_heads = 2 if head_dim == 64 else 1
    took = _fused_calls(monkeypatch)
    edges = []  # what the four calls that build a kernel are handed
    for name in ("_flash_fwd_call", "_flash_bwd_call", "_flash_fwd_qkv_call", "_flash_bwd_qkv_call"):
        def spy(*a, _real=getattr(fa, name), **kw):
            edges.extend(kw["schedule"].edges if "schedule" in kw else [kw["edge"]])
            return _real(*a, **kw)

        monkeypatch.setattr(fa, name, spy)
    qkv = jax.random.normal(
        jax.random.PRNGKey(35), (1, S, 3 * n_heads * head_dim), jnp.float32
    )
    out, dqkv = _out_and_cotangent(_attend(entry, n_heads, **blocks), qkv)
    ref, ref_dqkv = _out_and_cotangent(
        lambda x: dense_attention(*_split_heads(x, n_heads)).reshape(out.shape), qkv
    )
    assert bool(took) == (entry == "fused" and "block_q" not in blocks)
    assert len(edges) >= 2 and set(edges) == {blocks["block_diag"]}
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    for got, want, name in zip(
        jnp.split(dqkv, 3, axis=-1), jnp.split(ref_dqkv, 3, axis=-1), "qkv"
    ):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("queries_first", [True, False], ids=["forward", "transposed"])
def test_the_causal_pieces_trace_the_text_they_traced_before_the_block_mask(queries_first):
    """PR 47 taught the causal kernels' helpers the block-diffusion mask
    (``_triangle``'s steps of a block and its strict form, a mask a copy,
    a copy's rows). With their defaults, and with the causal values said
    aloud, they trace the operations the causal kernels always had: two
    iotas and ONE compare, queries against keys, for the triangle; one
    triangle under the key None for a call with no ``block_mask``; a
    position handed back as it came, with no operation on it."""
    fa = _module()

    def plain(n):  # ``_triangle`` as it stood
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        return rows >= cols if queries_first else cols >= rows

    want = str(jax.make_jaxpr(lambda: plain(128))())
    assert str(jax.make_jaxpr(lambda: fa._triangle(128, queries_first))()) == want
    assert str(jax.make_jaxpr(
        lambda: fa._triangle(128, queries_first, size=1, strict=False)
    )()) == want
    assert str(jax.make_jaxpr(
        lambda: fa._block_masks(128, None, queries_first)[0][None]
    )()) == want
    masks, own = fa._block_masks(128, None, queries_first)
    assert list(masks) == [None] and own is None
    # a block of 1 is the causal triangle too; the strict one is not
    np.testing.assert_array_equal(fa._triangle(16, queries_first, 1), plain(16))
    assert not bool(jnp.any(jnp.diagonal(fa._triangle(16, queries_first, 1, strict=True))))
    pos = jnp.int32(7)
    assert fa._copy_rows(None, None, pos) is pos and fa._copy_rows((4, 64), 0, pos) is pos
    assert int(fa._copy_rows((4, 64), 1, pos)) == 71
    # and the kernels whole: a causal call's schedule says no mask aloud,
    # and the causal kernel is built with none
    q = jnp.ones((2, 256, 16), jnp.float32)
    schedule = fa._tiles(256, 16, True, 256, 128, 64)
    assert schedule == fa.Schedule(
        "nested", 256, 128, 256, (64, 64), causal=True, interpret=True, kv_len=256,
        window=None, block=None,
    )
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda: fa._flash_fwd_call(q, q, q, schedule=schedule)
    )())


@pytest.mark.parametrize("j", [127, 128, 255, 256, 511, 512])
@pytest.mark.parametrize("entry", ["fused", "three"])
def test_staircase_is_causal_at_the_chunk_edges(entry, j):
    """Key j lies on an edge of a chunk, of a sub-tile or just inside one:
    rows before it must not see it. The cotangent of every key from j on,
    from a loss on the query rows before j, is exactly 0 - a chunk that
    met a row it should not, or a mask one off, leaves a number there -
    and those rows' output does not change with the keys."""
    n_heads, S = 2, 1024
    attend = _attend(entry, n_heads, block_diag=128)
    qkv = jax.random.normal(jax.random.PRNGKey(j), (1, S, 3 * n_heads * 64), jnp.float32)
    early = lambda x: attend(x)[:, :j]
    out, pullback = jax.vjp(early, qkv)
    (dqkv,) = pullback(jnp.ones_like(out))
    dq, dk, dv = jnp.split(dqkv, 3, axis=-1)
    assert not np.any(np.asarray(dk[:, j:])) and not np.any(np.asarray(dv[:, j:]))
    assert np.all(np.any(np.asarray(dk[:, :j]) != 0, axis=-1))  # the others do count
    late = jnp.arange(S)[None, :, None] >= j
    k_v = jnp.arange(qkv.shape[-1])[None, None, :] >= qkv.shape[-1] // 3
    np.testing.assert_array_equal(early(jnp.where(late & k_v, 7.0, qkv)), out)


# (block_k, head size) -> the staircase's edges (forward, backward), from
# this PR's whole-step measurements (PERF.md section 6, PR 35): the
# backward the finest the lanes allow, the forward whole at head size 64
# and 256 at 128; a sub-tile the edge does not divide stays whole.
@pytest.mark.parametrize(
    "block_k,head_dim,want",
    [
        (512, 64, (512, 128)), (512, 128, (256, 128)), (256, 64, (256, 128)),
        (256, 128, (256, 128)), (128, 128, (128, 128)), (32, 64, (32, 32)),
        (384, 128, (384, 128)),
    ],
)
def test_auto_edges(block_k, head_dim, want):
    fa = _module()
    assert fa._auto_edges(block_k, head_dim) == want
    assert all(block_k % e == 0 for e in want)


def test_staircase_edge_divides_the_sub_tile():
    q, k, v = rand_qkv(jax.random.PRNGKey(3), (1, 128, 1, 8))
    with pytest.raises(ValueError, match="block_diag"):
        flash_attention(q, k, v, block_q=64, block_k=64, block_diag=24)
    # the general path has no diagonal sub-tile to cut
    flash_attention(q, k, v, causal=False, block_q=64, block_k=64, block_diag=24)


# the benchmark's shapes: GPT-2's (1024, 512), OLMoE's (512, 512) at S 4096
# and Mellum2's window of 1024 at S 8192, at the whole sub-tile (the kernels
# until PR 35; with a window the area of the general kernels' 45 tiles) and
# both edges; then the long causal calls at 4,096 and 8,192 positions on the
# blocks of before PR 52 and of after it, at the edges both run: the pairs
# are the same (the area depends on the staircase's edge alone)
@pytest.mark.parametrize(
    "S,block_q,block_k,edge,window,want",
    [
        (1024, 1024, 512, 512, None, 786_432), (1024, 1024, 512, 256, None, 655_360),
        (1024, 1024, 512, 128, None, 589_824), (4096, 512, 512, 512, None, 9_437_184),
        (4096, 512, 512, 256, None, 8_912_896), (4096, 512, 512, 128, None, 8_650_752),
        (1024, 1024, 128, 128, None, 589_824), (1024, 128, 128, 128, None, 589_824),
        (8192, 512, 512, 512, 1024, 11_796_480), (8192, 512, 512, 256, 1024, 9_830_400),
        (8192, 512, 512, 128, 1024, 8_847_360), (8192, 1024, 512, 128, 1024, 8_847_360),
        (2048, 512, 512, 128, 512, 1_146_880), (1024, 128, 128, 128, 256, 344_064),
        (4096, 1024, 512, 256, None, 8_912_896), (4096, 1024, 512, 128, None, 8_650_752),
        (8192, 512, 512, 256, None, 34_603_008), (8192, 1024, 512, 256, None, 34_603_008),
        (8192, 512, 512, 128, None, 34_078_720), (8192, 1024, 512, 128, None, 34_078_720),
    ],
)
def test_scores_computed(S, block_q, block_k, edge, window, want):
    """The engagement figure: the area a head computes, from the schedule
    alone. It never falls under what the mask leaves - the causal half,
    or the band ``q - k < window`` of it, 7,864,832 pairs at Mellum2's
    shape - which an edge of 1 would reach, but for the pairs ON the
    window's edge: masked, in blocks that are met."""
    fa = _module()
    assert fa._scores_computed(S, block_q, block_k, edge, window) == want
    finest = fa._scores_computed(S, block_q, block_k, 1, window)
    if window is None:
        assert finest == S * (S + 1) // 2 <= want
    else:
        band = sum(min(q + 1, window) for q in range(S))
        assert band + (S - window) == finest <= want
        assert (S, window) != (8192, 1024) or band == 7_864_832


# (cell, in-model positions, head size): every causal call without a
# window that a cell makes. What its schedule computes today, forward and
# backward, is what (512, 512) computed at the same edges - and any other
# resident block: the same work, no pair left out (PR 52).
@pytest.mark.parametrize(
    "cell,S,head_dim",
    [
        ("gpt2", 1024, 64), ("olmoe-ft1", 4096, 128), ("ouro-ft1", 4096, 128),
        ("mellum2-ft1-full", 8192, 128), ("ling3-ft1-mla", 8192, 256),
    ],
)
def test_scores_computed_do_not_depend_on_the_resident_block(cell, S, head_dim):
    fa = _module()
    schedule = fa._tiles(S, head_dim, False, None, None, None)
    assert schedule.kind == "nested", cell
    for edge in schedule.edges:
        today = fa._scores_computed(schedule.s_pad, schedule.block_q, schedule.block_k, edge)
        for block_q, block_k in ((512, 512), (1024, 512), (1024, 1024), (2048, 1024)):
            if schedule.s_pad % block_q == 0:
                assert fa._scores_computed(schedule.s_pad, block_q, block_k, edge) == today
