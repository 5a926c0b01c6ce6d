"""The ``olmoe_lm`` family's counts against hand-computed ones at the
published sizes, and the readers of the expert matmuls on facts built by
hand.

    python3 -m pytest benchmark/tests -q        (by hand and in rehearsal;
                                                 not part of tier-1)
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import common  # noqa: E402

family = common.load_by_name("families", "olmoe_lm")
expert_ms = common.load_by_name("layer_metrics", "moe_expert_ms")
expert_roofline = common.load_by_name("layer_metrics", "moe_expert_roofline")
dispatch_ms = common.load_by_name("layer_metrics", "moe_dispatch_ms")

BATCH, SEQ = 4, 4097
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def cfg():
    _, entry = common.load_cell("olmoe-ft1")
    assert (entry["sizes"]["batch"], entry["sizes"]["seq"]) == (BATCH, SEQ)
    return family.build(entry["sizes"])


def test_flops_per_step(cfg):
    # per position: attention 4 x 2048^2, router 2048 x 64, eight experts
    # of 3 x 2048 x 1024, readout 2048 x 50304
    attention, router = 16_777_216, 131_072
    experts, readout = 8 * 6_291_456, 103_022_592
    assert (attention, experts) == (4 * 2048 ** 2, 50_331_648)
    assert family.matmul_params(cfg) == attention + router + experts + readout
    per_position = 6 * 170_262_528 + 6 * 4096 * 2048
    assert family.flops_per_step(cfg, BATCH, SEQ) == 16384 * per_position
    assert family.tokens_per_step(BATCH, SEQ) == 16384
    # the cut's distortion: the readout is 60% of the weights' operations
    assert 0.60 < readout / 170_262_528 < 0.61


def test_expert_matmuls(cfg):
    e = family.expert_matmuls(cfg, BATCH, SEQ)
    rows = 16384 * 8
    assert e["calls"] == 9
    assert e["flops"] == 9 * 2 * rows * 2048 * 1024  # 4.95 TFLOP a step
    # each call moves a rows x 2048 and a rows x 1024 matrix and the 64
    # experts' 2048 x 1024 weights, in bf16
    assert e["bytes"] == 9 * 2 * (rows * 2048 + rows * 1024 + 64 * 2048 * 1024)
    # compute-bound at 2,048 rows an expert: 25.1 ms against 12.5 ms
    assert e["flops"] / 197e12 > e["bytes"] / 819e9


def test_flash_calls_count_every_mosaic_call(cfg):
    f = family.flash_calls(cfg, BATCH, SEQ)
    e = family.expert_matmuls(cfg, BATCH, SEQ)
    # flash forward and backward, nine grouped matmuls, two metadata calls
    assert f["calls"] == 2 + 9 + 2
    flash_flops = 4 * 16 * 6 * (2 * 4096 * 4096 * 128 / 2)
    tensor, lse = 4096 * 16 * 128 * 2, 4096 * 16 * 4
    assert f["flops"] == flash_flops + e["flops"]
    assert f["bytes"] == 4 * (12 * tensor + 2 * lse) + e["bytes"]
    # calls, flops, bytes and nothing else: what the readers of the expert
    # matmuls want is the family's entry in the facts
    assert set(f) == {"calls", "flops", "bytes"}
    assert family.facts(cfg, BATCH, SEQ) == {"expert_matmuls": e}
    # the thirteen are the traced step's; its lowered text holds two
    assert family.lowered_mosaic_calls(cfg) == 2


def test_init_draws_the_experts_closer():
    """The benchmark's experts are the program's own draws mixed with one
    shared expert at ``EXPERT_SPREAD``: same scale, pairwise correlation
    ``1 - spread^2``, every one different; everything else is the
    program's seeded init."""
    import jax
    import numpy as np

    from torchft_tpu.models import olmoe

    tiny = olmoe.tiny_olmoe_config()
    got, plain = family.init(tiny, jax.random.PRNGKey(3)), olmoe.init_params(tiny, jax.random.PRNGKey(3))
    a = family.EXPERT_SPREAD
    assert 0 < a < 1
    for mine, theirs in zip(got["blocks"], plain["blocks"]):
        for name in ("w_gate", "w_up", "w_down"):
            w, own = np.asarray(mine["moe"][name]), np.asarray(theirs["moe"][name])
            assert w.shape == own.shape
            assert np.std(w) == pytest.approx(np.std(own), rel=0.05)
            flat = w.reshape(w.shape[0], -1)
            pairs = np.corrcoef(flat)[np.triu_indices(w.shape[0], 1)]
            assert pairs.mean() == pytest.approx(1 - a * a, abs=0.05)
            # what is left after the program's own draw is taken out is the
            # same shared expert for all of them
            shared = (w - a * own) / np.sqrt(1 - a * a)
            np.testing.assert_allclose(shared, np.broadcast_to(shared[:1], shared.shape), atol=1e-6)
        np.testing.assert_array_equal(mine["moe"]["router"], theirs["moe"]["router"])
    np.testing.assert_array_equal(got["embed"], plain["embed"])


def _facts(ops, cfg, steps=5):
    return {
        "trace": {"device_ops": ops, "steps": steps},
        "peaks": V5E,
        "flash": family.flash_calls(cfg, BATCH, SEQ),
        "family": family.facts(cfg, BATCH, SEQ),
    }


def test_readers_present(cfg):
    facts = _facts([
        ["fusion f32[2,4096,50304] fusion", 0.060],
        ["ragged-dot-none bf16[131072,1024] custom-call", 0.125],
        ["ragged-dot-none bf16[64,2048,1024] custom-call", 0.075],
        ["ragged-dot-metadata s32[65] custom-call", 0.001],
        ["flash_bwd f32[32,4096,128] custom-call", 0.020],
    ], cfg)
    # five of the nine calls are visible (three write rows x 1024, two
    # write the 64 x 2048 x 1024 gradients): 200 ms over 5 steps, scaled
    # by nine fifths to the whole layer
    assert expert_ms.read(facts) == pytest.approx(40.0 * 9 / 5)
    least_ms = family.expert_matmuls(cfg, BATCH, SEQ)["flops"] / 197e12 * 1e3
    assert expert_roofline.read(facts) == pytest.approx(100 * least_ms / 72.0)
    assert 30 < expert_roofline.read(facts) < 40
    # a label that falls under the tenth place moves neither by much
    fewer = dict(facts, trace=dict(facts["trace"], device_ops=facts["trace"]["device_ops"][:2]))
    assert expert_ms.read(fewer) == pytest.approx(25.0 * 9 / 3)
    assert dispatch_ms.read(facts) is None  # no claims x width fusion in these


def test_dispatch_reader(cfg):
    facts = _facts([
        ["fusion bf16[131072,2048] fusion", 0.100],   # the four gathers
        ["fusion bf16[131072,1024] fusion", 0.020],   # SiLU: not dispatch
        ["fusion f32[131072,2048] fusion", 0.030],    # not the compute type's rows
        ["add_any bf16[131072,2048] add", 0.010],     # the rows' gradient sum
        ["ragged-dot-none bf16[131072,2048] custom-call", 0.090],
    ], cfg)
    assert dispatch_ms.read(facts) == pytest.approx(20.0)
    assert dispatch_ms.read({"trace": None}) is None
    dense = dict(facts, flash={"calls": 24, "flops": 1.0, "bytes": 1.0}, family={})
    assert dispatch_ms.read(dense) is None


def test_readers_absent(cfg):
    no_kernel = _facts([["fusion f32[2,4096,50304] fusion", 0.060]], cfg)
    assert expert_ms.read(no_kernel) is None
    assert expert_roofline.read(no_kernel) is None
    assert expert_ms.read({"trace": None}) is None
    assert expert_roofline.read({"trace": None}) is None
    # a family that counts no expert matmuls (dense_lm's facts)
    dense = _facts([["ragged-dot-none bf16[8,8] custom-call", 0.05]], cfg)
    dense["flash"], dense["family"] = {"calls": 24, "flops": 1.0, "bytes": 1.0}, {}
    assert expert_ms.read(dense) is None and expert_roofline.read(dense) is None
    # a grouped matmul of a shape the family did not count
    odd = _facts([["ragged-dot-none bf16[7,7] custom-call", 0.05]], cfg)
    assert expert_ms.read(odd) is None and expert_roofline.read(odd) is None


def test_a_wrong_count_reads_over_100(cfg):
    """The reader does not clamp: matmuls faster than the chip's peak
    allows (three of the nine in 6 ms a step against their 8.4 ms floor)
    read over 100%, which says the count or the time is wrong."""
    facts = _facts([["ragged-dot-none bf16[131072,1024] custom-call", 0.030]], cfg)
    assert expert_roofline.read(facts) > 100


def test_rehearsal_1_on_the_cpu():
    """README.md, rehearsal 1, for the new cell: tiny sizes, every path."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "olmoe-ft1", "--seed", "7", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=900,
    )
    assert out.returncode in (0, 1), out.stderr[-3000:]  # 1: `correct` false
    assert "REHEARSAL on the CPU" in out.stdout
    checks = json.loads(next(
        line for line in out.stdout.splitlines() if line.startswith("checks ")
    )[len("checks "):])
    # the tiny model is held to the real sizes' tolerances and may miss them
    # where two trajectories are compared - ``reference`` (README.md) and,
    # for this family, ``first_losses_match_raw``: a token that swaps an
    # expert moves a 256-position loss by more than 2e-4 within five steps
    loose = ("reference", "first_losses_match_raw")
    assert all(ok for name, ok in checks.items() if name not in loose), checks
    assert set(loose) <= set(checks) and checks["every_step_committed"]
