"""The reduction to the program's own names (``reduce/spans.py``), checked
on a small trace recorded on the v5e after PR 24 named things, and the
new readers reached end to end in a CPU rehearsal.

    python3 -m pytest benchmark/tests -q        (by hand and in rehearsal;
                                                 not part of tier-1)

``tiny_v5e_spans.xplane.pb.gz``: three traced steps (the manager's steps
2, 3, 4) of the README loop at the tiny size (2 layers, batch 2 x 128) on
a TPU v5 lite, one group under a Manager - so every step has one
``torchft::quorum`` (quorum thread), ``torchft::allreduce_dispatch``,
``torchft::optimizer_step`` with ``torchft::commit_vote`` and
``torchft::apply_gradients`` nested in it - and, inside a
``bench::ring_pair`` span, a two-member host ring in the same process
averaging the same gradient tree: ``torchft::allreduce`` with its four
phases on each member's exchange thread, member 0's stamped with the
step. The quorum was settled before anyone asked, so there is no
``torchft::quorum_wait``. ``pop_op_stats()`` of member 0 for the three
steps, read in the recording process: d2h 0.711549 + 0.772280 + 0.688850
ms, ring 0.692609 + 0.865300 + 1.026960 ms. What it must hold is known
from how it was made, not from the reduction.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.reduce import spans, xplane  # noqa: E402

TRACE = os.path.join(HERE, "tiny_v5e_spans.xplane.pb.gz")
OLD_TRACE = os.path.join(HERE, "tiny_v5e.xplane.pb.gz")  # PR 23: no names yet


@pytest.fixture(scope="module")
def reduced():
    return spans.reduce_file(TRACE)


def named(reduced, name):
    return [e for e in reduced["spans"] if e["name"] == name]


def test_spans_of_the_step_transaction(reduced):
    assert reduced["steps"] == [2, 3, 4]
    for name in (
        "torchft::quorum", "torchft::allreduce_dispatch",
        "torchft::optimizer_step", "torchft::commit_vote",
        "torchft::apply_gradients", "bench::ring_pair",
    ):
        (entry,) = named(reduced, name)
        assert entry["n"] == 3, name
    assert not named(reduced, "torchft::quorum_wait")
    # the manager's spans carry its step, one of each a step
    (quorum,) = named(reduced, "torchft::quorum")
    assert sorted(quorum["by_step"]) == [2, 3, 4]
    assert sum(quorum["by_step"].values()) == pytest.approx(quorum["total_s"])
    # self time: the vote and the update's dispatch nest in optimizer_step
    (step,) = named(reduced, "torchft::optimizer_step")
    (vote,) = named(reduced, "torchft::commit_vote")
    (apply,) = named(reduced, "torchft::apply_gradients")
    assert step["thread"] == vote["thread"] == apply["thread"]
    assert step["self_s"] == pytest.approx(
        step["total_s"] - vote["total_s"] - apply["total_s"]
    )
    assert 0 < step["self_s"] < 0.2 * step["total_s"]
    assert vote["self_s"] == vote["total_s"]


def test_exchange_phases_nest_in_the_op(reduced):
    ops = named(reduced, "torchft::allreduce")
    assert len(ops) == 2 and all(e["n"] == 3 for e in ops)  # two members
    stamped = [e for e in ops if e["by_step"]]
    assert len(stamped) == 1 and sorted(stamped[0]["by_step"]) == [2, 3, 4]
    for phase in ("pack", "d2h", "ring", "h2d"):
        entries = named(reduced, f"torchft::allreduce/{phase}")
        assert len(entries) == 2 and all(e["n"] == 3 for e in entries)
    # member 0: the span and the op-stats key are one `with` on two clocks
    mine = [
        e for e in reduced["spans"]
        if e["name"].startswith("torchft::allreduce/") and e["by_step"]
    ]
    seconds = {e["name"].rsplit("/", 1)[1]: e["total_s"] for e in mine}
    assert seconds["d2h"] == pytest.approx(
        (0.711549 + 0.772280 + 0.688850) / 1e3, rel=0.05
    )
    assert seconds["ring"] == pytest.approx(
        (0.692609 + 0.865300 + 1.026960) / 1e3, rel=0.05
    )
    assert stamped[0]["self_s"] == pytest.approx(
        stamped[0]["total_s"] - sum(seconds.values())
    )


def test_device_seconds_by_scope_and_kernel(reduced):
    old = xplane.reduce_file(TRACE)  # the reducer the benchmark already had
    assert reduced["chips"] == old["chips"] == 1
    assert set(reduced["kernels_s"]) == {"flash_fwd", "flash_bwd"}
    # 3 steps x 2 layers x (forward + fused backward), the same events
    assert old["mosaic_calls"] == 12
    assert sum(reduced["kernels_s"].values()) == pytest.approx(
        old["mosaic_s"], rel=1e-3
    )
    assert reduced["kernels_s"]["flash_bwd"] > reduced["kernels_s"]["flash_fwd"]
    # every operation falls in exactly one class
    ops = xplane.read_planes(TRACE)["/device:TPU:0"][xplane.OPS_LINE]
    # (ProfileData rounds each of these ~1000 tiny events to whole ns)
    assert sum(reduced["scopes_s"].values()) == pytest.approx(
        sum(d for _, _, d in ops) / 1e9, rel=5e-3
    )
    assert all(reduced["scopes_s"][c] > 0 for c in spans.CLASSES)
    # a kernel's seconds are inside its scope class
    assert reduced["scopes_s"]["forward"] > reduced["kernels_s"]["flash_fwd"]
    assert reduced["scopes_s"]["backward"] > reduced["kernels_s"]["flash_bwd"]


def test_a_program_without_the_names_reduces_and_does_not_raise():
    r = spans.reduce_file(OLD_TRACE)
    assert r["steps"] == [] and r["chips"] == 1
    assert set(r["kernels_s"]) == {"jvp__", "transpose_jvp___"}
    assert r["scopes_s"]["forward"] == r["scopes_s"]["optimizer"] == 0.0
    assert r["scopes_s"]["backward"] > 0 and r["scopes_s"]["unscoped"] > 0
    assert {e["name"] for e in r["spans"]} == {
        "bench::fused_step_dispatch", "bench::wait",
    }


def test_self_time_by_hand():
    def event(name, start, duration, **stats):
        return {"name": name, "scope": "", "start_ns": start,
                "duration_ns": duration, "stats": stats}

    plane = {"name": spans.HOST_PLANE, "lines": [
        {"name": "main", "events": [
            event("torchft::b", 10.0, 30.0, step=1),   # in a
            event("bench::a", 0.0, 100.0),
            event("torchft::c", 15.0, 5.0, step=1),    # in b
            event("torchft::b", 50.0, 20.0, step=2),   # in a
            event("runtime noise", 0.0, 1000.0),       # no span of ours
            event("torchft::b", 200.0, 10.0, step=2),  # alone
        ]},
        {"name": "exchange", "events": [event("torchft::b", 12.0, 4.0, step=1)]},
    ]}
    got = {(e["thread"], e["name"]): e for e in spans.host_spans(plane)}
    a, b, c = (got[("main", n)] for n in ("bench::a", "torchft::b", "torchft::c"))
    assert (a["n"], a["total_s"], a["self_s"]) == (1, 100e-9, pytest.approx(50e-9))
    assert (b["n"], b["total_s"], b["self_s"]) == (3, 60e-9, pytest.approx(55e-9))
    assert b["by_step"] == {1: 30e-9, 2: pytest.approx(30e-9)}
    assert (c["total_s"], c["self_s"]) == (5e-9, 5e-9)
    # another thread nests in nothing of this one
    assert got[("exchange", "torchft::b")]["self_s"] == 4e-9


@pytest.mark.parametrize("scope, want", [
    ("jit(loss_and_grads)/jvp(attn)/flash_fwd/pallas_call:", "forward"),
    ("jit(loss_and_grads)/transpose(jvp(attn))/flash_bwd/pallas_call:", "backward"),
    ("jit(one_step)/jvp(loss)/jit(log_softmax)/sub:", "forward"),
    ("jit(apply)/optimizer/add:", "optimizer"),
    ("jit(one_step)/optimizer/add:", "optimizer"),
    ("jit(forward)/mlp/dot_general:", "forward"),
    ("jit(loss_and_grads)/convert_element_type:", "unscoped"),
    ("masters['blocks'][9]['mlp']['wi']", "unscoped"),  # an argument's name
    ("jit(apply)/add:", "unscoped"),  # an executable cached before the names
    ("", "unscoped"),
    # a family names its layers as it likes: no list of scopes decides
    ("jit(loss_and_grads)/jvp(kda)/chunk_scan/pallas_call:", "forward"),
    ("jit(loss_and_grads)/transpose(jvp(kda))/chunk_scan/pallas_call:", "backward"),
    ("jit(loss_and_grads)/jvp(mla)/kv_up/dot_general:", "forward"),
    ("jit(loss_and_grads)/jvp(blocks)/3/ffn/jit(silu)/logistic:", "forward"),
    ("jit(one_step)/jit(main)/optimizer/adamw/mul:", "optimizer"),
    ("jit(loss_and_grads)/jvp()/slice:", "unscoped"),
    ("ragged-dot-none:", "unscoped"),  # alone: its neighbours decide (device_seconds)
])
def test_scope_class(scope, want):
    assert spans.scope_class(scope) == want


def test_kernel_name():
    assert spans.kernel_name(
        "%flash_bwd.7 = (f32[384,1024,64]{2,1,0}) custom-call(bf16[2]{0} %x), "
        'custom_call_target="tpu_custom_call"'
    ) == "flash_bwd"
    assert spans.kernel_name("%fusion.5 = f32[2]{0} fusion(f32[2]{0} %a)") is None


# -- the new readers, end to end on CPU processes ----------------------------

NEW_FT = {"optimizer_step_host_ms", "quorum_wait_ms", "exposed_wait_ms"}


@pytest.mark.parametrize("cell, want", [
    ("gpt2s-ft1", NEW_FT),
    ("gpt2m-raw", set()),
])
def test_rehearsal_reaches_every_new_reader(cell, want):
    """A CPU trace has no device plane, so the device metrics are left out
    (``flash_fwd_ms`` / ``flash_bwd_ms`` among them); the readers of the
    program's timers all report."""
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000007", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    line = done.stdout.strip().splitlines()[-1]
    result = json.loads(line.partition("not a measurement: ")[2])
    assert want <= set(result["metrics"]), result["metrics"]
    assert not {"flash_fwd_ms", "flash_bwd_ms"} & set(result["metrics"])
