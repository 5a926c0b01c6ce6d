"""The trace reduction, checked on a small trace recorded on the v5e.

    python3 -m pytest benchmark/tests -q        (by hand and in rehearsal;
                                                 not part of tier-1)

``tiny_v5e.xplane.pb.gz``: three steps of the fused raw step at the tiny
size (2 layers, batch 2 x 128), each dispatched inside a
``bench::fused_step_dispatch`` span and waited for inside ``bench::wait``,
recorded on a TPU v5 lite by PR 23's first chip call. What it must
hold is known from how it was made, not from the reduction.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark.reduce import xplane  # noqa: E402

TRACE = os.path.join(HERE, "tiny_v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def planes():
    return xplane.read_planes(TRACE)


def test_planes_and_lines(planes):
    assert xplane.OPS_LINE in planes["/device:TPU:0"]
    host = [e for line in planes[xplane.HOST_PLANE].values() for e in line]
    assert sum(e[0] == "bench::fused_step_dispatch" for e in host) == 3
    assert sum(e[0] == "bench::wait" for e in host) == 3


def test_reduction(planes):
    r = xplane.reduce_planes(planes)
    ops = planes["/device:TPU:0"][xplane.OPS_LINE]
    first = min(s for _, s, _ in ops)
    last = max(s + d for _, s, d in ops)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx((last - first) / 1e9)
    # busy is a union: no more than the window, no more than the plain sum
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] <= sum(d for _, _, d in ops) / 1e9 + 1e-12
    # every gap is named, and busy + gaps is the window
    gaps = sum(s for _, s in r["idle_gaps"])
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"], rel=1e-6)
    # 3 steps x 2 layers x (flash forward + fused backward)
    assert r["mosaic_calls"] == 12
    assert 0 < r["mosaic_s"] < r["busy_s"]
    # a tiny step is dispatch-bound: the device waits while the host
    # dispatches the next step
    assert "bench::fused_step_dispatch" in dict(r["idle_gaps"])
    assert len(r["device_ops"]) <= xplane.TOP


def test_an_empty_trace_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_planes({"/host:CPU": {"python3": []}})


def test_op_label():
    line = (
        "%transpose_jvp___.18 = (f32[384,1024,64]{2,1,0:T(8,128)}, "
        "bf16[384,1024,64]{2,1,0}) custom-call(bf16[384,1024,64]{2,1,0} %x), "
        'custom_call_target="tpu_custom_call"'
    )
    assert xplane.op_label(line) == "transpose_jvp___ f32[384,1024,64] custom-call"
    assert xplane.is_mosaic_call(line)
    assert xplane.op_label(
        "%fusion.5 = f32[32,1023,50257]{1,2,0:T(8,128)} fusion(bf16[2]{0} %a)"
    ) == "fusion f32[32,1023,50257] fusion"


def test_union_and_gap_names():
    assert xplane.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    spans = [("bench::outer", 0.0, 10.0), ("bench::inner", 4.0, 2.0)]
    assert xplane.name_gap((4.5, 5.5), spans) == "bench::inner"
    assert xplane.name_gap((7.0, 8.0), spans) == "bench::outer"
    assert xplane.name_gap((20.0, 21.0), spans) == "host (no span)"
