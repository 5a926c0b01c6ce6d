"""The ``mellum_lm`` family's counts against numbers written out by hand at
the cell's sizes, and its seven readers on facts built by hand and on a
recorded trace that has none of their scopes.

    python3 -m pytest benchmark/tests -q        (by hand and in rehearsal;
                                                 not part of tier-1)
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402

family = common.load_family("mellum_lm")
READERS = (
    "attn_sliding_ms", "attn_full_ms", "attn_sliding_flash_roofline",
    "attn_full_flash_roofline", "moe_held_expert_ms", "moe_held_expert_roofline",
    "moe_held_dispatch_ms",
)
BATCH, SEQ = 2, 8193
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return common.load_by_name("layer_metrics", name)


@pytest.fixture(scope="module")
def cfg():
    contract, entry = common.load_cell("mellum2-ft1")
    assert (entry["sizes"]["batch"], entry["sizes"]["seq"]) == (BATCH, SEQ)
    assert (entry["chips"], entry["traffic"]) == (1, "ft-sync-1")
    for name in READERS:
        metric = next(m for m in contract["per_layer"] if m["name"] == name)
        # since PR 42 the cell's step is held by a metric of its own
        assert metric["workloads"] == ["mellum2-ft1"] and metric["moves"] == "step_p90_routed_ms"
    return family.build(entry["sizes"])


def test_the_configuration_is_the_share_its_file_states(cfg):
    assert (cfg.n_layers, cfg.vocab_size, cfg.n_experts, cfg.held) == (4, 12288, 64, (0, 8))
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2304, 32, 4, 128)
    assert (cfg.expert_width, cfg.experts_per_token) == (896, 8)
    assert [(k.name, k.window) for k in cfg.kinds] == [("sliding", 1024)] * 3 + [("full", None)]
    assert cfg.kinds[0].yarn is None and cfg.kinds[3].yarn.factor == 16.0
    assert cfg.kinds[3].yarn.attention_factor == 1.2772588722239782
    assert cfg.renormalize_top_k and cfg.qk_norm_per_head and cfg.z_coef == 0.0
    sizes = common.load_json("configs", "mellum2-12b-a2.5b-l4-ep8.json")
    # every published list is kept whole; the program runs its first four
    assert len(sizes["layer_types"]) == len(sizes["mlp_layer_types"]) == 28
    assert sizes["published"] == {"num_hidden_layers": 28, "num_experts": 64, "vocab_size": 98304}
    assert sorted(sizes["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]


def test_the_seeded_weights_are_the_programs_but_for_the_routers_spread():
    """``init`` is ``olmoe.init_params`` with the router's columns times
    ``ROUTER_SPREAD``, and the configuration file lists the departure."""
    import jax
    import numpy as np

    from torchft_tpu.models import mellum, olmoe

    tiny = mellum.tiny_mellum_config()
    key = jax.random.PRNGKey(7)
    got, own = family.init(tiny, key), olmoe.init_params(tiny, key)
    assert family.ROUTER_SPREAD == 4.0
    for mine, theirs in zip(got["blocks"], own["blocks"]):
        np.testing.assert_allclose(mine["moe"]["router"], 4.0 * theirs["moe"]["router"])
        mine, theirs = (dict(b, moe={k: v for k, v in b["moe"].items() if k != "router"}) for b in (mine, theirs))
        jax.tree_util.tree_map(np.testing.assert_array_equal, mine, theirs)
    for leaf in ("embed", "readout"):
        np.testing.assert_array_equal(got[leaf], own[leaf])
    departures = common.load_json("configs", "mellum2-12b-a2.5b-l4-ep8.json")["departures"]
    assert [d.split(":")[0] for d in departures] == ["router"]


def test_the_cell_is_on_every_accepted_metric_whose_layer_it_runs():
    contract, _ = common.load_cell("mellum2-ft1")
    listed = {m["name"] for m in contract["per_layer"] if "mellum2-ft1" in m.get("workloads", ())}
    # the metrics it shares with other cells under the name that moves ITS
    # end-to-end metric (PR 42: ``<name>.routed``, read by ``<name>``'s file)
    shared = {
        "quorum_ms", "commit_vote_ms", "ft_over_raw", "optimizer_step_host_ms",
        "quorum_wait_ms", "exposed_wait_ms", "flash_fwd_ms", "flash_bwd_ms",
        "forward_ms", "backward_ms", "optimizer_ms", "step_median_ms",
        "step_device_ms", "mfu", "flash_roofline", "peak_hbm_gb", "window_tokens_per_s",
    }
    assert listed == set(READERS) | {name + ".routed" for name in shared}
    by_name = {m["name"]: m for m in contract["per_layer"]}
    for name in shared:
        ours, theirs = by_name[name + ".routed"], by_name[name]
        assert ours["workloads"] == ["mellum2-ft1"] and "mellum2-ft1" not in theirs["workloads"]
        assert (ours["moves"], theirs["moves"]) == ("step_p90_routed_ms", "step_p90_ms")
        assert all(ours[k] == theirs[k] for k in ("unit", "better", "source", "layer"))
    e2e = {m["name"]: m for m in contract["end_to_end"]}
    assert e2e["step_p90_routed_ms"]["workloads"] == ["mellum2-ft1"]
    assert "mellum2-ft1" not in e2e["step_p90_ms"]["workloads"] and len(e2e["step_p90_ms"]["workloads"]) == 5
    assert e2e["step_p90_ms"]["bound"] == 0.01 and "workloads" not in e2e["setup_s"]


def test_parameters(cfg):
    # a layer: q and out 2304 x 4096 each, k and v 2304 x 512 each; the
    # router 2304 x 64; 8 experts of 3 x 2304 x 896; two QK-norm scales of
    # 128 and two layer norms of 2304
    attention, router, expert = 21_233_664, 147_456, 6_193_152
    assert attention == 2304 * (2 * 4096 + 2 * 512) and expert == 3 * 2304 * 896
    assert family.attention_params(cfg) == attention
    layer = attention + router + 8 * expert + 2 * 128 + 2 * 2304
    assert layer == 70_931_200
    # embedding and readout 12,288 x 2304 each, the final norm
    assert family.parameters(cfg) == 4 * layer + 2 * 28_311_552 + 2304 == 340_350_208


def test_flops_per_step(cfg):
    # per position: the projections, the router and ONE expected held claim
    # (8 x 8 / 64) a layer, the readout once
    per_position = 4 * (21_233_664 + 147_456 + 6_193_152) + 28_311_552
    assert family.matmul_params(cfg) == per_position == 138_608_640
    assert family.expected_held_claims(cfg, 2 * 8192) == 16384
    # pairs a head attends: the causal half, and the band of 1024
    full, sliding = 33_558_528, 7_864_832
    assert full == 8192 * 8193 // 2 and sliding == 1024 * 1025 // 2 + 7168 * 1024
    assert [family.scores_seen(cfg, k, 8192) for k in cfg.kinds] == [sliding] * 3 + [full]
    weights = 2 * 8192 * 6 * per_position
    scores = 2 * 32 * 12 * 128 * (full + 3 * sliding)
    assert (weights, scores) == (13_625_783_746_560, 5_618_370_871_296)
    assert family.flops_per_step(cfg, BATCH, SEQ) == weights + scores  # 19.24 TFLOP
    assert family.tokens_per_step(BATCH, SEQ) == 16384
    # the cut's distortion: attention's projections and scores 73% of the
    # step, the held experts 13%, the readout 14%
    total = weights + scores
    assert 0.72 < (16384 * 6 * 4 * 21_233_664 + scores) / total < 0.73
    assert 0.12 < 16384 * 6 * 4 * 6_193_152 / total < 0.13
    assert 0.14 < 16384 * 6 * 28_311_552 / total < 0.15


def test_held_expert_matmuls(cfg):
    e = family.held_expert_matmuls(cfg, BATCH, SEQ)
    assert (e["calls"], e["rows"]) == (36, 16384)
    assert e["flops"] == 36 * 2 * 16384 * 2304 * 896  # 2.44 TFLOP a step
    assert e["bytes"] == 36 * 2 * (16384 * 2304 + 16384 * 896 + 8 * 2304 * 896) == 4_963_958_784
    # its terms, for a reader that knows the rows a run realised
    assert e["flops_per_row"] * e["rows"] == e["flops"]
    assert e["bytes_per_row"] * e["rows"] + e["bytes_weights"] == e["bytes"]
    # what the share multiplies since PR 40 follows the routing: not stated
    assert "computed_flops" not in e
    # compute-bound at 2,048 rows an expert: 12.36 ms against 6.06 ms
    assert e["flops"] / V5E["bf16_flops_per_s"] > e["bytes"] / V5E["hbm_bytes_per_s"]


def test_flash_calls(cfg):
    kinds = family.kind_flash(cfg, BATCH, SEQ)
    assert set(kinds) == {"sliding", "full"}
    assert (kinds["sliding"]["layers"], kinds["full"]["layers"]) == (3, 1)
    # a sequence: 6 matmuls of 2 x pairs x 128 a head; q-sized arrays 6 x
    # 8192 x 4096 x 2 B, key/value-sized 6 x 8192 x 512 x 2 B, lse 2 x 8192
    # x 32 x 4 B; two sequences a step
    assert kinds["full"]["flops"] == 2 * 32 * 12 * 128 * 33_558_528 == 3_298_937_536_512
    assert kinds["sliding"]["flops"] == 2 * 3 * 32 * 12 * 128 * 7_864_832 == 2_319_433_334_784
    layer_bytes = 2 * (402_653_184 + 50_331_648 + 2_097_152)
    assert kinds["full"]["bytes"] == layer_bytes and kinds["sliding"]["bytes"] == 3 * layer_bytes
    # the step's only Mosaic calls: two flash kernels a layer (the held
    # share is plain XLA)
    flash = family.flash_calls(cfg, BATCH, SEQ)
    assert flash["calls"] == family.lowered_mosaic_calls(cfg) == 8
    assert flash["flops"] == 3_298_937_536_512 + 2_319_433_334_784
    assert flash["bytes"] == 4 * layer_bytes
    assert set(family.facts(cfg, BATCH, SEQ)) == {"kind_flash", "held_expert_matmuls", "parameters"}


# -- the readers ------------------------------------------------------------


def facts_of(cfg, paths_s, kernels_s):
    return {
        "trace": {"steps": 5, "paths_s": paths_s, "kernels_s": kernels_s},
        "peaks": V5E, "family": family.facts(cfg, BATCH, SEQ),
    }


def test_the_readers_on_facts_built_by_hand(cfg):
    facts = facts_of(cfg, {
        "forward": {
            "attn/sliding": 0.030, "attn/sliding/qk_norm": 0.005, "attn/sliding/flash_fwd": 0.045,
            "attn/full": 0.010, "attn/full/rope": 0.002, "attn/full/flash_fwd": 0.040,
            "mlp/moe/dispatch": 0.004, "mlp/moe/combine": 0.006,
            "mlp/moe/experts": 0.020, "mlp/moe/router": 0.001,
        },
        "backward": {
            "attn/sliding/flash_bwd": 0.075, "attn/full/flash_bwd": 0.060, "attn/full": 0.020,
            "mlp/moe/dispatch": 0.007, "mlp/moe/experts": 0.040,
            "embed/dispatch": 0.5,  # no ``moe`` before it
        },
        "optimizer": {"optimizer": 0.060}, "unscoped": {"": 0.010},
    }, {"flash_fwd": 0.085, "flash_bwd": 0.135})
    ms = lambda name: reader(name).read(facts)  # noqa: E731
    assert ms("attn_sliding_ms") == pytest.approx((0.030 + 0.005 + 0.045 + 0.075) / 5 * 1e3)
    assert ms("attn_full_ms") == pytest.approx((0.010 + 0.002 + 0.040 + 0.060 + 0.020) / 5 * 1e3)
    assert ms("moe_held_expert_ms") == pytest.approx(12.0)
    assert ms("moe_held_dispatch_ms") == pytest.approx((0.004 + 0.006 + 0.007) / 5 * 1e3)
    # sliding: 2.3194 TFLOP at 197 TFLOP/s is 11.774 ms of the 24 traced
    assert ms("attn_sliding_flash_roofline") == pytest.approx(100 * 11.7738 / 24.0, rel=1e-4)
    assert ms("attn_full_flash_roofline") == pytest.approx(100 * 16.7459 / 20.0, rel=1e-4)
    # the held share's is over the claims the run counted around its traced
    # steps (test_mellum_routing.py), and not read where it counted none
    assert ms("moe_held_expert_roofline") is None
    around = {end: {"held_claims": [0.5] * 8} for end in ("traced", "open")}
    facts = dict(facts, routing=around, trace=dict(facts["trace"], batches=[0, 1, 2, 3, 4]))
    assert ms("moe_held_expert_roofline") == pytest.approx(100 * 12.3617 / 2 / 12.0, rel=1e-4)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_on_a_trace_without_its_scope(name, cfg):
    """The parent's program, and every other family's, has no ``attn/
    sliding``, no ``attn/full`` and no held share: the reader returns None
    and does not raise - on the recorded dense trace, with and without this
    family's facts, and on a run that was not traced."""
    trace = common.reduce_trace(os.path.join(HERE, "tiny_v5e_spans.xplane.pb.gz"))
    dense = {"trace": dict(trace, steps=3), "peaks": V5E, "family": {}}
    assert reader(name).read(dense) is None
    ours = dict(dense, family=family.facts(cfg, BATCH, SEQ))
    assert reader(name).read(ours) is None
    assert reader(name).read({"trace": None, "peaks": V5E, "family": ours["family"]}) is None
    assert reader(name).read({"trace": None, "peaks": None}) is None
