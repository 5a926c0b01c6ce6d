"""SDAR as a configuration of the sparse family (torchft_tpu.models.sdar over
models/olmoe.py: block-diffusion training) against its plain reference
(benchmark/reference_sdar.py), at tiny sizes on the CPU, seeded weights: two
layers of 4 query heads over 2 key/value heads of 32, 2 of 8 experts held,
sequences of 32 tokens in blocks of 4 - 64 positions in the stack, a clean
and a noised copy.

TOLERANCES, and why. In float32 the program and the reference compute the
same mathematics in another order (flash tiles under the mask against a
dense softmax under the mask written out pair by pair; the held share's
tiles against the reference's loop over the held experts; a custom backward
pass of the cross entropy against autodiff's), so they differ by float32
rounding alone: measured here at 2.1e-7 relative on the loss and 1.4e-6 of
its largest entry on the worst gradient leaf. The loss is held to 1e-5 and
every gradient leaf to 1e-4: some ten to a hundred times what was measured,
and far under what the smallest wrong term costs
(``test_a_wrong_term_is_caught``). The kernels against dense attention,
float32: 1.2e-6 at the most, held to 1e-5. In bf16 (the configuration's
precision) the tiny model's loss is a weighted sum over a few dozen masked
positions: held to 4e-4 and 1e-2 on the gradient norm (``tests/
test_olmoe.py``'s bounds).
"""

import dataclasses
import json
import os
import sys
from datetime import timedelta

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import common, reference, reference_mellum, reference_sdar
from torchft_tpu import (
    FTTrainState,
    HostCollectives,
    Lighthouse,
    Manager,
    OptimizerWrapper,
)
from torchft_tpu.models import mellum, olmoe, ouro, sdar
from torchft_tpu.ops import block_scores_computed, flash_attention

flash_module = sys.modules["torchft_tpu.ops.flash_attention"]

BF16 = sdar.tiny_sdar_config()
F32 = dataclasses.replace(BF16, dtype=jnp.float32)
LOSS_RTOL_F32, GRAD_RTOL_F32, KERNEL_ATOL_F32 = 1e-5, 1e-4, 1e-5
LOSS_RTOL_BF16, GRAD_NORM_RTOL_BF16 = 4e-4, 1e-2


def _sizes():
    path = os.path.join(
        os.path.dirname(__file__), "..", "benchmark", "configs", "sdar-30b-a3b-l4-ep8.json"
    )
    with open(path) as f:
        return json.load(f)


def _weights(cfg=F32, seed=0):
    return sdar.init_params(cfg, jax.random.PRNGKey(seed))


def _tokens(cfg=F32, batch=2, seq=32, seed=1):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch, seq), 0, cfg.vocab_size, jnp.int32
    )


def _reference(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: reference_sdar.loss(cfg, p, tokens))(params)


def _program(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: sdar.loss_fn(cfg, p, tokens))(params)


def _norm(tree):
    return float(jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(tree)
    )))


def _leaf_errors(got, want):
    return jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / max(float(jnp.max(jnp.abs(b))), 1e-30)),
        got, want,
    ))


# ---------------------------------------------------------------------------
# the program against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_f32_loss_and_gradients_match_the_reference(seed):
    params, tokens = _weights(seed=seed), _tokens(seed=seed + 1)
    loss, grads = _program(F32, params, tokens)
    want, want_grads = _reference(F32, params, tokens)
    assert abs(float(loss) - float(want)) <= LOSS_RTOL_F32 * float(want)
    assert max(_leaf_errors(grads, want_grads)) <= GRAD_RTOL_F32
    # every leaf has a gradient to compare: the mask token's row and the
    # clean copy's rows of the embedding among them
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(want_grads))


def test_bf16_path_matches_the_reference_at_what_bf16_earns():
    params, tokens = _weights(), _tokens(batch=4)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: sdar.loss_fn(BF16, p, tokens)))(compute)
    want, want_grads = _reference(F32, params, tokens)
    assert abs(float(loss) - float(want)) <= LOSS_RTOL_BF16 * float(want)
    assert abs(_norm(grads) - _norm(want_grads)) <= GRAD_NORM_RTOL_BF16 * _norm(want_grads)


SOUND_MASK = reference_sdar.visible


def _causal_for_noised(length, block):
    """The noised copy's queries under a plain causal mask: the keys of
    either copy at their own position and before it."""
    row = jnp.arange(2 * length)
    q, k = row[:, None], row[None, :]
    return jnp.where(q >= length, k % length <= q % length, SOUND_MASK(length, block))


def _own_block_leaks(length, block):
    """``<=`` for ``<``: a noised query sees the clean keys of its OWN block."""
    row = jnp.arange(2 * length)
    q, k = row[:, None], row[None, :]
    leak = (q >= length) & (k < length) & ((k // block) == ((q - length) // block))
    return SOUND_MASK(length, block) | leak


WRONG = {
    "causal_mask_on_the_noised_copy": ("visible", _causal_for_noised),
    "the_noised_copy_sees_its_own_blocks_clean_keys": ("visible", _own_block_leaks),
    "the_noised_copy_at_positions_after_the_clean": (
        "positions", lambda length: jnp.arange(2 * length),
    ),
    "the_weight_left_out": ("weight", lambda t: jnp.ones_like(t)),
}


@pytest.mark.parametrize("wrong", sorted(WRONG) + ["the_next_ranks_experts"])
def test_a_wrong_term_is_caught(wrong, monkeypatch):
    """Each fault of the chip's controls, planted in the REFERENCE (for the
    experts: in which of them it is told the weights are), moves the
    float32 loss and the gradient far past the limits the sound pair is
    held to."""
    params, tokens = _weights(), _tokens()
    loss, grads = _program(F32, params, tokens)
    cfg = F32
    if wrong == "the_next_ranks_experts":
        cfg = dataclasses.replace(F32, held_experts=(2, 2))
    else:
        monkeypatch.setattr(reference_sdar, *WRONG[wrong])
    want, want_grads = _reference(cfg, params, tokens)
    assert abs(float(loss) - float(want)) > 10 * LOSS_RTOL_F32 * float(want)
    assert max(_leaf_errors(grads, want_grads)) > 10 * GRAD_RTOL_F32


# ---------------------------------------------------------------------------
# the mask in the kernels
# ---------------------------------------------------------------------------


def _visible(length, block):
    """M by the two sentences, in numpy: rows 0..L-1 clean, L..2L-1 noised."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for q in range(2 * length):
        for k in range(2 * length):
            qb, kb = (q % length) // block, (k % length) // block
            if q < length:
                seen[q, k] = k < length and kb <= qb
            else:
                seen[q, k] = kb < qb if k < length else kb == qb
    return seen


def _dense(q, k, v, seen):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(seen, s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


def _qkv(length, heads=2, dh=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (1, 2 * length, heads, dh), jnp.float32) for k in keys]


# (L, B, block_q, block_k, block_diag, static): the shapes that TILE run the
# two-level static schedule (``static``) - blocks inside sub-tiles; B = 1; two
# row groups a resident block (32, 16), with B the edge (16), a finer edge (8)
# and the whole one; B = L = the one sub-tile of a copy; one resident block a
# copy; three of them (the loop runs 0, 1 and 2 trips) - and every other
# keeps the general kernels' sweep: B = L over several tiles (the own-quadrant
# tiles shown whole) and B over a sub-tile; a copy that ends inside a tile (L 24 on
# tiles of 16); a block that straddles a tile's edge (B 6 on tiles of 8 and
# 16; B 12 on 8 and 16); a padded length (2 L = 40 on tiles of 16); blocks
# that do not nest (8, 16), two row groups of queries a key tile
CASES = [
    (32, 4, 16, 16, None, True), (32, 1, 16, 16, None, True), (64, 4, 32, 16, None, True),
    (64, 16, 32, 16, None, True), (64, 4, 32, 16, 8, True), (64, 8, 32, 16, 16, True),
    (16, 16, 16, 16, None, True), (32, 2, 32, 8, 4, True), (48, 4, 16, 16, 8, True),
    (32, 32, 16, 16, None, False), (16, 16, 8, 8, None, False),
    (24, 4, 16, 16, None, False), (24, 6, 16, 8, None, False), (20, 4, 16, 16, None, False),
    (48, 12, 8, 16, None, False), (32, 4, 8, 16, None, False),
]


def _schedule(length, block, block_q=None, block_k=None, head_dim=16, interpret=True):
    """(block_q, block_k, static): the tiles such a call runs, and whether
    on the static schedule - ``_tiles``' answer, which the call and the
    counter read."""
    schedule = flash_module._tiles(
        2 * length, head_dim, interpret, block_q, block_k, None, causal=False,
        block_mask=(block, length),
    )
    assert schedule.kind in ("blocked", "general")
    assert (schedule.kind == "blocked") == (schedule.edges is not None)
    return schedule.block_q, schedule.block_k, schedule.kind == "blocked"


@pytest.mark.parametrize("length,block,block_q,block_k,block_diag,static", CASES)
def test_the_block_mask_is_dense_attention_under_the_mask(
    length, block, block_q, block_k, block_diag, static, monkeypatch
):
    q, k, v = _qkv(length, seed=length * block)
    seen = _visible(length, block)
    assert seen.sum() == length * length + length * block
    assert _schedule(length, block, block_q, block_k)[-1] == static
    # which path THIS call takes: the schedule it is handed
    handed = []
    real_tiles = flash_module._tiles
    monkeypatch.setattr(
        flash_module, "_tiles", lambda *a, **kw: handed.append(real_tiles(*a, **kw)) or handed[-1]
    )

    def flash(q, k, v):
        return flash_attention(
            q, k, v, causal=False, block_mask=(block, length), block_q=block_q,
            block_k=block_k, block_diag=block_diag,
        )

    weights = jax.random.normal(jax.random.PRNGKey(7), q.shape)

    def both(attend):  # the output and the gradients of a weighted sum of it
        def run(q, k, v):
            out, pull = jax.vjp(attend, q, k, v)
            return out, pull(weights)
        return jax.jit(run)(q, k, v)

    out, grads = both(flash)
    want_out, want = both(lambda *a: _dense(*a, seen))
    np.testing.assert_allclose(out, want_out, rtol=0, atol=KERNEL_ATOL_F32)
    for a, b in zip(grads, want):
        np.testing.assert_allclose(a, b, rtol=0, atol=KERNEL_ATOL_F32 * max(1.0, float(jnp.max(jnp.abs(b)))))
    assert {schedule.kind for schedule in handed} == {"blocked" if static else "general"}
    # either schedule computes whole pieces: never fewer pairs than the mask
    # shows, and the backward's sweep is the forward's transposed on square
    # tiles where nothing is padded (the static schedule's: at one edge)
    fwd, bwd = (
        block_scores_computed((block, length), 16, block_q=block_q, block_k=block_k, backward=b)
        for b in (False, True)
    )
    assert fwd >= seen.sum() and bwd >= seen.sum()
    if static or (block_q == block_k and (2 * length) % block_q == 0):
        assert fwd == bwd
    if static and block_diag is None:
        # the static schedule's pieces, counted by hand: each copy's causal
        # schedule and the own quadrant's diagonal chunks of one edge
        edge = block_k
        groups = length // block_k
        left = sum(r * block_k * block_k for r in range(groups))
        stairs = groups * sum(block_k - j * edge for j in range(block_k // edge)) * edge
        assert fwd == 2 * (left + stairs) + length * edge


L, B = 16, 4
# the tiles of the three exactness tests below: two that run the static
# schedule (one row group a resident block, and two) and one that keeps the
# general kernels' sweep (blocks that do not nest)
TILES = {"static": (8, 8), "static_two_row_groups": (16, 8), "sweep": (8, 16)}
_tiles_now = TILES["static"]


@pytest.fixture(params=sorted(TILES))
def tiles(request, monkeypatch):
    monkeypatch.setattr(sys.modules[__name__], "_tiles_now", TILES[request.param])
    assert _schedule(L, B, *TILES[request.param])[-1] == request.param.startswith("static")


def _flash(q, k, v):
    block_q, block_k = _tiles_now
    return flash_attention(
        q, k, v, causal=False, block_mask=(B, L), block_q=block_q, block_k=block_k
    )


def _moved(key_at, rows):
    """Whether the output's ``rows`` move at all, bit for bit, when the key
    and the value at ``key_at`` are replaced; and the rows' gradient there."""
    q, k, v = _qkv(L)
    other_k, other_v = k.at[0, key_at].add(3.0), v.at[0, key_at].add(-2.0)
    before, after = _flash(q, k, v)[0, rows], _flash(q, other_k, other_v)[0, rows]
    dk, dv = jax.grad(lambda k, v: jnp.sum(_flash(q, k, v)[0, rows] ** 2), argnums=(0, 1))(k, v)
    touched = bool(jnp.any(dk[0, key_at] != 0) or jnp.any(dv[0, key_at] != 0))
    return bool(jnp.any(before != after)), touched


def test_a_noised_query_sees_the_clean_keys_of_earlier_blocks_alone(tiles):
    """Exact, forward and backward: the noised copy's row of position 9
    (block 2) is unmoved, bit for bit, by the clean keys of its OWN block
    (8..11) - the answer does not leak - and of later ones, and moved by
    the block before (4..7)."""
    row = L + 9
    for own in (8, 9, 11, 12, 15):
        assert _moved(own, row) == (False, False), own
    for earlier in (0, 4, 7):
        assert _moved(earlier, row) == (True, True), earlier
    # of its own copy it sees its own block alone, later keys among them
    for k, seen in ((L + 8, True), (L + 11, True), (L + 7, False), (L + 12, False)):
        assert _moved(k, row) == (seen, seen), k


def test_a_clean_query_sees_no_noised_key(tiles):
    rows = slice(0, L)
    for k in range(L, 2 * L):
        assert _moved(k, rows) == (False, False), k
    # and the clean keys of its own block, later ones among them
    assert _moved(11, 8) == (True, True) and _moved(12, 8) == (False, False)


def test_the_first_noised_block_sees_only_itself(tiles):
    """Rows whose visible keys all lie in one tile, and not the first the
    sweep meets: the softmax over the block's own four noised keys."""
    q, k, v = _qkv(L)
    rows = slice(L, L + B)
    got = _flash(q, k, v)[0, rows]
    s = jnp.einsum("qhd,khd->hqk", q[0, rows], k[0, rows]) / 4.0
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v[0, rows])
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
    for clean in range(L):
        assert _moved(clean, rows) == (False, False), clean


def test_the_call_says_what_it_cannot_be():
    q, k, v = _qkv(L)
    with pytest.raises(ValueError, match="causal=False"):
        flash_attention(q, k, v, block_mask=(B, L))
    with pytest.raises(ValueError, match="whole blocks"):
        flash_attention(q, k, v, causal=False, block_mask=(3, L))
    with pytest.raises(ValueError, match="whole blocks"):
        flash_attention(q, k, v, causal=False, block_mask=(B, L // 2))


def test_the_schedule_at_the_cells_shape(monkeypatch):
    """L 4096, B 4 runs the static schedule on the chip's (1024, 1024) tiles:
    each copy's causal schedule (4 resident blocks: 6 whole tiles and 4
    staircases) and the own quadrant's 4,096 / edge diagonal chunks, at the
    forward's edge of 256 and the backward's of 128: 1.124 and 1.061 times
    the mask's 16,793,600 pairs; the count follows the edge and not the tiles.
    A shape that does not tile - B 2048, which no sub-tile holds - runs the
    general kernels' sweep on (512, 512): every key tile up to B - 1 past a
    query tile's last row, 190 of the 256 tiles (the mask shows 96 whole); so
    would the cell's shape with the predicate held off: 151 tiles, the causal
    half of the 8,192 rows (136) and the tile past each diagonal."""
    assert _schedule(4096, 4, head_dim=128, interpret=False) == (1024, 1024, True)
    assert _schedule(4096, 2048, head_dim=128, interpret=False) == (512, 512, False)
    whole, stair = 1024 * 1024, lambda edge, tile=1024: tile * (tile + edge) // 2
    for backward, edge, pairs in ((False, 256, 18_874_368), (True, 128, 17_825_792)):
        assert block_scores_computed(
            (4, 4096), 128, backward=backward, interpret=False
        ) == 2 * (6 * whole + 4 * stair(edge)) + 4096 * edge == pairs
        assert block_scores_computed(
            (4, 4096), 128, backward=backward, interpret=False, block_q=512, block_k=512
        ) == 2 * (28 * 512 * 512 + 8 * stair(edge, 512)) + 4096 * edge == pairs
        assert block_scores_computed(
            (2048, 4096), 128, backward=backward, interpret=False
        ) == 190 * 512 * 512 >= 96 * 512 * 512 == 4096 * 4096 + 4096 * 2048
    family = common.load_family("sdar_lm")
    cfg = family.build(_sizes())
    assert family.block_flash(cfg, 4096) == {
        "required_pairs": 16_793_600, "computed_pairs": 18_874_368,
    }
    monkeypatch.setattr(flash_module, "_blocked", lambda *_: False)
    for backward in (False, True):
        assert block_scores_computed(
            (4, 4096), 128, backward=backward, interpret=False
        ) == 151 * 512 * 512


# ---------------------------------------------------------------------------
# the objective
# ---------------------------------------------------------------------------


def test_the_loss_on_planted_noise_by_hand(monkeypatch):
    """No shift, the masked positions alone, each sequence by 1 / t, over
    all the positions: from the program's own logits of the noised copy."""
    cfg = dataclasses.replace(F32, balance_coef=0.0)
    params, tokens = _weights(cfg), _tokens(cfg)
    t = jnp.asarray([0.25, 0.8], jnp.float32)
    m = jnp.zeros(tokens.shape, bool).at[0, jnp.asarray([1, 5, 6, 30])].set(True)
    m = m.at[1, ::2].set(True)
    monkeypatch.setattr(olmoe, "_noise", lambda cfg, tokens: (t, m))
    with jax.default_matmul_precision("highest"):
        loss = float(sdar.loss_fn(cfg, params, tokens))
        logits, sums = sdar.forward(cfg, params, tokens)
    assert logits.shape == tokens.shape + (cfg.vocab_size,)
    assert float(sums["masked_share"]) == 20 / 64
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1), np.float64)
    by_hand = 0.0
    for b, i in zip(*np.nonzero(np.asarray(m))):
        by_hand -= logp[b, i, int(tokens[b, i])] / float(t[b])
    assert abs(loss - by_hand / tokens.size) <= 1e-5 * loss
    # the answer does not leak: a masked position's own token (sequence 0,
    # position 5, block 1) reaches the logits of LATER blocks alone, through
    # their view of its clean copy; blocks 0 and 1 do not move with it
    other = tokens.at[0, 5].set((tokens[0, 5] + 1) % 255)
    with jax.default_matmul_precision("highest"):
        moved, _ = sdar.forward(cfg, params, other)
    scale = float(jnp.max(jnp.abs(logits)))
    np.testing.assert_allclose(moved[0, :8], logits[0, :8], rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(moved[1], logits[1], rtol=0, atol=1e-5 * scale)
    assert float(jnp.max(jnp.abs(moved[0, 8:] - logits[0, 8:]))) > 1e-3 * scale


def test_the_noise_is_a_function_of_the_batch():
    tokens = _tokens()
    t, m = olmoe._noise(F32, tokens)
    again_t, again_m = jax.jit(lambda x: olmoe._noise(F32, x))(tokens)
    np.testing.assert_array_equal(t, again_t)
    np.testing.assert_array_equal(m, again_m)
    # the reference's own copy of the draw is the same draw
    ref_t, ref_m = reference_sdar.noise(F32, tokens)
    np.testing.assert_array_equal(t, ref_t)
    np.testing.assert_array_equal(m, ref_m)
    assert t.shape == (2,) and m.shape == tokens.shape and m.dtype == bool
    assert bool(jnp.all((t >= F32.noise_floor) & (t <= 1.0)))
    # other tokens, another order of the same tokens, another seed: other noise
    for other in (tokens.at[1, 7].add(1), tokens[::-1]):
        assert not np.array_equal(olmoe._noise(F32, other)[0], t)
    reseeded = dataclasses.replace(F32, noise_seed=1)
    assert not np.array_equal(olmoe._noise(reseeded, tokens)[0], t)
    # a sequence's share of masked positions follows its t
    long = _tokens(batch=8, seq=4096, seed=3)
    t, m = olmoe._noise(F32, long)
    np.testing.assert_allclose(jnp.mean(m, axis=1), t, atol=0.03)


def test_a_diffusion_configuration_says_what_it_needs():
    with pytest.raises(ValueError, match="diffusion_block"):
        dataclasses.replace(F32, diffusion_block=None)
    with pytest.raises(ValueError, match="diffusion_block"):
        dataclasses.replace(F32, layer_kinds=None)
    with pytest.raises(ValueError, match="mask token"):
        dataclasses.replace(F32, mask_token_id=None)
    with pytest.raises(ValueError, match="mask token"):
        dataclasses.replace(F32, mask_token_id=256)
    with pytest.raises(ValueError, match="not looped"):
        dataclasses.replace(F32, passes=2)
    assert dataclasses.replace(F32, mask_token_id=0).mask_token_id == 0  # row 0 is a row


def test_stated_positions_are_the_old_rope_at_the_default():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 3, 16), jnp.float32)
    yarn = mellum.tiny_mellum_config().kinds[-1].yarn
    for y in (None, yarn):
        old = olmoe.rope(x, 10000.0, y)
        np.testing.assert_array_equal(olmoe.rope(x, 10000.0, y, jnp.arange(24)), old)
        # both copies count from 0: the second half turns as the first does
        twice = olmoe.rope(jnp.concatenate([x, x], axis=1), 10000.0, y, jnp.tile(jnp.arange(24), 2))
        np.testing.assert_array_equal(twice[:, 24:], old)
        np.testing.assert_array_equal(twice[:, :24], old)


@pytest.mark.parametrize("model", ["olmoe", "mellum2", "ouro"])
def test_the_other_configurations_lower_to_the_parents_text(model, monkeypatch):
    """A configuration without the new fields takes the old path: the
    lowered text of its loss's gradient is the text with the rotation's
    tables as a model without stated positions asks for them (none
    stated), the flash kernels as such a model calls them (no
    ``block_mask``, ``causal`` never named) and no noise drawn."""
    cfg = {
        "olmoe": olmoe.tiny_olmoe_config(), "mellum2": mellum.tiny_mellum_config(),
        "ouro": ouro.tiny_ouro_config(),
    }[model]
    params = olmoe.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, cfg.vocab_size, jnp.int32)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)

    def lowered():
        return jax.jit(jax.grad(lambda p: olmoe.loss_fn(cfg, p, tokens))).lower(compute).as_text()

    text = lowered()
    calls = []
    tables, rows = olmoe.rotary_tables, olmoe.flash_attention_rows

    def counted_from_zero(S, head_dim, theta, yarn=None, positions=None):
        assert positions is None
        return tables(S, head_dim, theta, yarn)

    def as_a_causal_model_calls_it(q, k, v, *, window=None):
        calls.append(window)
        return rows(q, k, v, window=window)

    def no_noise(*_):
        raise AssertionError("a next-token model draws no noise")

    monkeypatch.setattr(olmoe, "rotary_tables", counted_from_zero)
    monkeypatch.setattr(olmoe, "flash_attention_rows", as_a_causal_model_calls_it)
    monkeypatch.setattr(olmoe, "_noise", no_noise)
    again = lowered()
    assert len(calls) >= cfg.n_layers
    assert again == text


# ---------------------------------------------------------------------------
# a rank's share at this cell's shapes
# ---------------------------------------------------------------------------


def test_the_eight_ranks_shares_add_up():
    """The parts of one layer of 16 experts that 8 ranks of two experts each
    give, on the 2 L rows of both copies, add up to what the uncut reference
    gives for the whole layer."""
    cfg = dataclasses.replace(F32, held_experts=None, n_layers=1, layer_kinds=F32.kinds[:1], n_experts=16)
    p = _weights(cfg)["blocks"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = reference_mellum._moe(cfg, x.reshape(-1, cfg.d_model), p)
        parts, held_claims = [], 0.0
        for first in range(0, 16, 2):
            held = dataclasses.replace(cfg, held_experts=(first, 2))
            mine = dict(p, **{w: p[w][first:first + 2] for w in ("w_gate", "w_up", "w_down")})
            y, s = olmoe.moe_layer(held, mine, x)
            parts.append(y)
            held_claims += float(s["held_claims"])
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(sum(parts).reshape(want.shape), want, rtol=0, atol=1e-5 * scale)
    assert held_claims == 2 * 64 * cfg.experts_per_token


def test_the_published_configuration_is_the_rank_it_says():
    sizes = _sizes()
    family = common.load_family("sdar_lm")
    cfg = family.build(sizes)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.held) == (128, 8, (0, 16))
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.expert_width) == (2048, 32, 4, 128, 768)
    assert (cfg.diffusion_block, cfg.mask_token_id, cfg.vocab_size) == (4, 18991, 18992)
    assert all(kind == olmoe.AttentionKind("block", block=4) for kind in cfg.kinds)
    assert sizes["published"] == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert list(sizes["reduced"]) == ["num_hidden_layers", "num_experts", "vocab_size"]
    # every other key of the source as the catalog's row has it
    assert (sizes["rope_theta"], sizes["moe_intermediate_size"], sizes["intermediate_size"]) == (1000000, 768, 6144)
    batch, seq = sizes["batch"], sizes["seq"]
    # 16,384 positions in the stack: tiles of 256 rows, a buffer of 24,576,
    # an expert heavy from 1,281 claims (mellum2-ft1: 512, 24,576, 2,561)
    assert olmoe._share_buffer(cfg, family.positions_per_step(batch, seq)) == (24576, 256, 1281)
    assert family.expected_held_claims(cfg, family.positions_per_step(batch, seq)) == 16384
    assert family.tokens_per_step(batch, seq) == 8192
    layer = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 + 16 * 3 * 2048 * 768 + 2 * 128 + 2 * 2048
    assert family.parameters(cfg) == 4 * layer + 2 * 18992 * 2048 + 2048
    assert round(family.parameters(cfg) / 1e6, 1) == 456.3
    flops = family.flops_per_step(cfg, batch, seq)
    stack = 16384 * 6 * 4 * (18_874_368 + 262_144 + 4_718_592)
    readout = 8192 * 6 * 2048 * 18992
    attention = 2 * 4 * 32 * 12 * 128 * 16_793_600
    assert flops == stack + readout + attention
    flash = family.flash_calls(cfg, batch, seq)
    assert flash["calls"] == family.lowered_mosaic_calls(cfg) == 8
    assert flash["flops"] == attention
    rows = family.facts(cfg, batch, seq)["held_expert_matmuls"]["rows"]
    assert rows == 16384


def test_the_three_readers_read_what_the_program_names_and_nothing_else():
    """``attn_block_ms``, ``diffusion_head_ms`` and ``block_scores_ratio`` on
    a run's facts: a path is counted once whichever of the names it holds,
    and a program or a family without the mechanism (the parent's; every
    other cell's) reads None, as does an untraced run."""
    read = {
        name: common.load_by_name("layer_metrics", name).read
        for name in ("attn_block_ms", "diffusion_head_ms", "block_scores_ratio")
    }
    paths = {
        "forward": {
            "attn/block/flash_fwd": 0.04, "attn/block": 0.02, "noise": 0.001,
            "readout": 0.003, "loss": 0.0005, "mlp/moe/router": 0.001,
        },
        "backward": {"attn/block/flash_bwd": 0.06, "readout": 0.008, "loss/aux": 0.0005},
    }
    facts = {
        "trace": {"paths_s": paths, "steps": 2},
        "family": {"block_flash": {"required_pairs": 16_793_600, "computed_pairs": 20_971_520}},
    }
    assert read["attn_block_ms"](facts) == pytest.approx(60.0)
    assert read["diffusion_head_ms"](facts) == pytest.approx(6.5)
    assert read["block_scores_ratio"](facts) == pytest.approx(1.2487804878)
    untraced = dict(facts, trace=None)
    assert read["attn_block_ms"](untraced) is None and read["diffusion_head_ms"](untraced) is None
    assert read["block_scores_ratio"](untraced) == pytest.approx(1.2487804878)
    other = {"trace": {"paths_s": {"forward": {"attn/full/flash_fwd": 0.01, "readout": 0.01}}, "steps": 2},
             "family": {"kind_flash": {}}}
    assert all(reader(other) is None for reader in read.values())


# ---------------------------------------------------------------------------
# through the step transaction, which changes nothing
# ---------------------------------------------------------------------------


def test_three_adamw_steps_through_optimizer_wrapper_match_the_reference():
    """A one-member Manager, OptimizerWrapper and FTTrainState around the
    float32 program: its first three losses are the reference's own training
    run's. The step is a function of (state, batch): nothing of the noise
    rides the state, and a step tried AGAIN on the same batch gives the same
    loss and gradient."""
    params, batches = _weights(), jnp.stack([_tokens(seed=s) for s in (1, 2, 3)])
    with jax.default_matmul_precision("highest"):
        want, _ = jax.jit(lambda p, b: reference_sdar.train(F32, p, b))(params, batches)

    state = FTTrainState(params, optax.adamw(reference.LEARNING_RATE))
    grad_fn = jax.jit(jax.value_and_grad(lambda p, t: sdar.loss_fn(F32, p, t)))
    lighthouse = Lighthouse(bind="[::]:0", min_replicas=1)
    collectives = HostCollectives(timeout=timedelta(seconds=30))
    manager = Manager(
        collectives=collectives, load_state_dict=state.load_state_dict,
        state_dict=state.state_dict, min_replica_size=1,
        timeout=timedelta(seconds=30), quorum_timeout=timedelta(seconds=60),
        lighthouse_addr=lighthouse.address(), replica_id="sdar_test",
    )
    optimizer = OptimizerWrapper(manager, state)
    losses = []
    try:
        with jax.default_matmul_precision("highest"):
            first = grad_fn(state.params, batches[0])
            for tokens in batches:
                optimizer.zero_grad()
                loss, grads = grad_fn(state.params, tokens)
                if not losses:  # the step tried again is the step
                    assert float(loss) == float(first[0])
                    for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(first[1])):
                        np.testing.assert_array_equal(a, b)
                assert optimizer.step(manager.allreduce(grads).wait())
                losses.append(float(loss))
    finally:
        manager.shutdown()
        collectives.shutdown()
        lighthouse.shutdown()
    np.testing.assert_allclose(losses, want, rtol=2e-5)
    assert set(state.state_dict()) == set(FTTrainState(params, optax.adamw(1e-3)).state_dict())


def test_make_train_step_takes_the_configuration():
    """``models.make_train_step`` (the raw loop's fused step) serves a
    diffusion model as it serves OLMoE: one loss for the family, the same
    noise for the same batch."""
    from torchft_tpu.models import make_train_step

    tokens, tx, params = _tokens(), optax.adamw(1e-3), _weights()
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    want = float(jax.jit(lambda p, t: sdar.loss_fn(BF16, p, t))(compute, tokens))
    _, _, loss = make_train_step(BF16, tx, bf16_params=True)(params, tx.init(params), tokens)
    assert abs(float(loss) - want) <= LOSS_RTOL_BF16 * want
