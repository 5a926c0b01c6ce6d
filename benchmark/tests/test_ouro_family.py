"""The ``ouro_lm`` family's contract and counts against numbers written out
by hand at the cell's sizes, the text its gradient step lowers to (the
loop's body once), and its three readers on facts built by hand and on a
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

family = common.load_family("ouro_lm")
READERS = ("loop_stack_ms", "loop_recompute_ms", "exit_heads_ms")
SHARED = (
    "step_median_ms", "step_device_ms", "mfu", "flash_roofline", "flash_fwd_ms",
    "flash_bwd_ms", "peak_hbm_gb", "forward_ms", "backward_ms", "optimizer_ms",
    "window_tokens_per_s", "quorum_ms", "commit_vote_ms", "ft_over_raw",
    "quorum_wait_ms", "exposed_wait_ms", "optimizer_step_host_ms",
)
BATCH, SEQ = 2, 4097
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return common.load_by_name("layer_metrics", name)


@pytest.fixture(scope="module")
def cfg():
    contract, entry = common.load_cell("ouro-ft1")
    assert (entry["sizes"]["batch"], entry["sizes"]["seq"]) == (BATCH, SEQ)
    assert (entry["chips"], entry["traffic"], entry["config"]) == (1, "ft-sync-1", "ouro-2.6b-l6")
    return family.build(entry["sizes"])


def test_the_family_states_the_whole_contract_and_the_exit_masses():
    assert all(hasattr(family, what) for what in common.FAMILY_STATES)
    assert callable(family.routing)  # optional in common.py
    # the limits are this model's own readings (reference_ouro.py), not inherited
    assert (family.LOSS_RTOL, family.GRAD_NORM_RTOL) == (3e-3, 6e-3)


def test_the_configuration_is_the_cut_its_file_states(cfg):
    assert (cfg.n_layers, cfg.passes, cfg.vocab_size) == (6, 4, 49152)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (2048, 16, 16, 128)
    assert cfg.ff == (5632,) * 6 and cfg.expert_layers == 0
    assert not cfg.qk_norm and cfg.sandwich_norms and cfg.exit_entropy_coef == 0.05
    sizes = common.load_json("configs", "ouro-2.6b-l6.json")
    assert len(sizes["layer_types"]) == 48  # the published list, whole
    assert sizes["published"] == {"num_hidden_layers": 48}
    assert sorted(sizes["reduced"]) == ["num_hidden_layers"] and sizes["departures"] == []
    # the assumptions, the first to doubt first
    assert list(sizes["assumed"])[:5] == [
        "carried_norm", "sandwich_norm", "exit_gate", "loss", "exit_entropy_coef",
    ]
    contract, _ = common.load_cell("ouro-ft1")
    entry = next(c for c in contract["configs"] if c["name"] == "ouro-2.6b-l6")
    assert entry["reduced"] == ["num_hidden_layers"] and entry["source"] == sizes["source"]


def test_the_cell_is_on_the_lists_the_issue_names():
    contract, _ = common.load_cell("ouro-ft1")
    listed = {m["name"] for m in contract["per_layer"] if "ouro-ft1" in m.get("workloads", ())}
    assert listed == set(READERS) | set(SHARED)
    by_name = {m["name"]: m for m in contract["per_layer"]}
    for name in READERS:
        m = by_name[name]
        assert m["workloads"] == ["ouro-ft1"] and m["moves"] == "step_p90_ms"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "ms", "lower", "device_trace", "model step"
        )
    e2e = {m["name"]: m for m in contract["end_to_end"]}
    assert e2e["step_p90_ms"]["workloads"][-1] == "ouro-ft1" and e2e["step_p90_ms"]["bound"] == 0.01
    assert "ouro-ft1" not in e2e["step_p90_routed_ms"]["workloads"]
    # a ``why`` is one line of at most 200 characters, or the file is refused
    whys = [e["why"] for e in contract["configs"] + contract["workloads"] if "ouro" in e["name"]]
    assert len(whys) == 2 and all(len(why) <= 200 and "\n" not in why for why in whys)


def test_parameters(cfg):
    # a layer: four projections of 2048 x 2048, a SwiGLU of 3 x 2048 x 5632,
    # four norms of 2048
    attention, swiglu = 16_777_216, 34_603_008
    assert attention == 4 * 2048 * 2048 and swiglu == 3 * 2048 * 5632
    assert family.stack_matmul_params(cfg) == 6 * (attention + swiglu) == 308_281_344
    layer = attention + swiglu + 4 * 2048
    assert layer == 51_388_416
    # embedding and untied readout 49,152 x 2048 each, the final norm, the gate
    assert family.parameters(cfg) == 6 * layer + 2 * 100_663_296 + 2048 + 2049 == 509_661_185
    assert family.facts(cfg, BATCH, SEQ) == {
        "parameters": 509_661_185, "passes": 4, "layer_applications": 24,
    }


def test_flops_per_step(cfg):
    # a position multiplies, forward: the stack 4 times, the readout 4
    # times, the gate 4 times
    per_position = 4 * (308_281_344 + 100_663_296 + 2048)
    assert family.matmul_params(cfg) == per_position == 1_635_786_752
    # causal attention: 6 S d a layer and pass
    attention = 6 * 4096 * 2048 * 6 * 4
    assert attention == 1_207_959_552
    want = 2 * 4096 * (6 * per_position + attention)
    assert family.flops_per_step(cfg, BATCH, SEQ) == want == 90_297_795_084_288  # 90.3 TFLOP
    assert family.tokens_per_step(BATCH, SEQ) == 8192
    # the recomputed forward pass is NOT in it: it would add a third of
    # the stack's 6 N and of attention, 2 N a position and pass more
    recomputed = 2 * 4096 * (2 * per_position + attention // 3)
    assert 0.33 < recomputed / want < 0.34
    # the cut's distortion: four readouts are 24% of the matmul operations
    # at six layers and 4% at 48
    assert 0.24 < 4 * 100_663_296 / per_position < 0.25
    assert 0.03 < 4 * 100_663_296 / (4 * (8 * 308_281_344 + 100_663_296)) < 0.04


def test_flash_calls(cfg):
    flash = family.flash_calls(cfg, BATCH, SEQ)
    # what a TRACED step runs: a layer and pass, the forward kernel in the
    # forward scan, and again (recomputed) with the backward kernel in the
    # backward scan; what the lowered TEXT holds: the two bodies once
    assert flash["calls"] == 3 * 6 * 4 == 72
    assert family.lowered_mosaic_calls(cfg) == 3 * 6 == 18
    # one matmul over the causal half of 4096 x 4096 x 128, a head: 2 a
    # forward, 4 the backward: 2 + 2 + 4 a layer and pass
    matmul = 2 * 4096 * 4096 * 128 // 2
    assert flash["flops"] == 2 * 24 * 16 * 8 * matmul == 13_194_139_533_312
    # q-sized arrays 4096 x 2048 x 2 B: 4 a forward, 8 the backward; the
    # log-sum-exp 4096 x 16 x 4 B once a call
    tensor, lse = 16_777_216, 262_144
    assert flash["bytes"] == 2 * 24 * (16 * tensor + 3 * lse) == 12_922_650_624
    # compute-bound: 67.0 ms of matmuls against 15.8 ms of traffic
    assert flash["flops"] / V5E["bf16_flops_per_s"] > 4 * flash["bytes"] / V5E["hbm_bytes_per_s"]


def test_the_lowered_gradient_holds_the_loops_two_bodies_once(monkeypatch):
    """The generators' own check (``require_mosaic``) on the program they
    lower, cross-lowered here for the TPU at a size that lowers in seconds:
    three kernels a layer whether the stack runs 4 times or 2, and the text
    holds one ``while`` a scan."""
    import jax
    import jax.numpy as jnp

    import torchft_tpu.ops  # noqa: F401

    fa = sys.modules["torchft_tpu.ops.flash_attention"]
    monkeypatch.setattr(fa, "_pick_interpret", lambda _i: False)
    tiny = {"vocab_size": 256, "hidden_size": 256, "num_attention_heads": 2,
            "num_key_value_heads": 2, "intermediate_size": 384, "num_hidden_layers": 2}
    for passes in (4, 2):
        sizes = {**common.load_json("configs", "ouro-2.6b-l6.json"), **tiny,
                 "total_ut_steps": passes}
        cfg = family.build(sizes)
        params = jax.eval_shape(lambda: family.init(cfg, jax.random.PRNGKey(0)))
        tokens = jax.ShapeDtypeStruct((2, 1025), jnp.int32)
        lowered = jax.jit(common.mixed_precision_grad(family, cfg)).trace(
            params, tokens
        ).lower(lowering_platforms=("tpu",))
        assert family.lowered_mosaic_calls(cfg) == 6
        assert family.flash_calls(cfg, 2, 1025)["calls"] == 6 * passes
        common.require_mosaic(lowered, 6, f"ouro x {passes}")
        assert lowered.as_text().count("stablehlo.while") == 2  # forward, backward


def test_routing_is_the_exit_distributions_mean_a_batch():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import ouro

    cfg = ouro.tiny_ouro_config()
    params = family.init(cfg, jax.random.PRNGKey(3))
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, params, ouro.init_params(cfg, jax.random.PRNGKey(3))
    )
    pool = jax.random.randint(jax.random.PRNGKey(4), (3, 2, 33), 0, cfg.vocab_size, jnp.int32)
    masses = jax.jit(lambda p, t: family.routing(cfg, p, t))(params, pool)
    assert sorted(masses) == ["exit_p1", "exit_p2", "exit_p3", "exit_p4"]
    assert all(v.shape == (3,) for v in masses.values())
    np.testing.assert_allclose(sum(masses.values()), 1.0, atol=1e-5)
    compute = jax.tree_util.tree_map(lambda l: l.astype(jnp.bfloat16), params)
    want = ouro.forward(cfg, compute, pool[1][:, :-1])[1]["exit_probs"]
    np.testing.assert_allclose([float(masses[f"exit_p{t}"][1]) for t in (1, 2, 3, 4)], want, atol=2e-3)


# -- the readers ------------------------------------------------------------

BODY = "loop/while/body/closed_call"


def facts_of(paths_s):
    return {"trace": {"steps": 5, "paths_s": paths_s}, "peaks": V5E, "family": {}}


def test_the_readers_on_facts_built_by_hand():
    facts = facts_of({
        "forward": {
            "embed": 0.001,
            "loop": 0.020,  # the scan's glue around its body
            f"{BODY}/attn": 0.100, f"{BODY}/attn/flash_fwd": 0.040, f"{BODY}/mlp": 0.200,
            BODY: 0.010,  # the norm that closes a pass
            f"{BODY}/exits": 0.002, f"{BODY}/readout": 0.030, f"{BODY}/loss": 0.008,
            "exits": 0.001,
        },
        "backward": {
            "loop": 0.010,
            f"{BODY}/checkpoint/rematted_computation/attn": 0.100,
            f"{BODY}/checkpoint/rematted_computation/attn/flash_fwd": 0.040,
            f"{BODY}/checkpoint/rematted_computation/mlp": 0.200,
            f"{BODY}/checkpoint/rematted_computation": 0.010,
            f"{BODY}/checkpoint/rematted_computation/readout": 0.030,
            f"{BODY}/checkpoint/rematted_computation/loss": 0.008,
            f"{BODY}/checkpoint/attn": 0.250, f"{BODY}/checkpoint/attn/flash_bwd": 0.090,
            f"{BODY}/checkpoint/mlp": 0.400, f"{BODY}/checkpoint": 0.020,
            f"{BODY}/checkpoint/readout": 0.060, f"{BODY}/checkpoint/loss": 0.010,
            f"{BODY}/checkpoint/exits": 0.003,
            "exits": 0.002, "embed": 0.004,
        },
        # the two scans' own ``while`` events, which enclose their bodies', come unnamed
        "optimizer": {"optimizer": 0.100}, "unscoped": {"": 6.050},
    })
    ms = lambda name: reader(name).read(facts)  # noqa: E731
    stack = (0.100 + 0.040 + 0.200 + 0.010) * 2 + 0.250 + 0.090 + 0.400 + 0.020 + 0.020 + 0.010
    assert ms("loop_stack_ms") == pytest.approx(stack / 5 * 1e3)
    assert ms("loop_recompute_ms") == pytest.approx((0.100 + 0.040 + 0.200 + 0.010) / 5 * 1e3)
    exits = (0.002 + 0.030 + 0.008 + 0.001) + (0.030 + 0.008 + 0.060 + 0.010 + 0.003 + 0.002)
    assert ms("exit_heads_ms") == pytest.approx(exits / 5 * 1e3)
    # the stack and the exits share nothing, and neither holds the scans' own events
    assert ms("loop_stack_ms") + ms("exit_heads_ms") < 6.0 / 5 * 1e3


@pytest.mark.parametrize("name", READERS)
def test_a_reader_finds_nothing_on_a_trace_without_its_scope(name):
    """The parent's program, and every other family's, has no ``loop`` and no
    ``exits``: the reader returns None and does not raise - on the recorded
    dense trace (which HAS ``readout`` and ``loss``), and on a run that was
    not traced."""
    trace = common.reduce_trace(os.path.join(HERE, "tiny_v5e_spans.xplane.pb.gz"))
    assert any("readout" in p for p in trace["paths_s"]["forward"])
    dense = {"trace": dict(trace, steps=3), "peaks": V5E, "family": {}}
    assert reader(name).read(dense) is None
    assert reader(name).read({"trace": None, "peaks": V5E, "family": {}}) is None
    assert reader(name).read({"trace": None, "peaks": None}) is None
