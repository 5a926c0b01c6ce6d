"""Two-tier (topology-aware) collectives tests.

The hierarchical schedule — intra-region reduce-scatter -> intra allgather
-> inter-region ring among one leader per region -> chunk-pipelined intra
broadcast — is composed from the SAME native rs/ag stripe bodies as the
flat ring, and its determinism contract is the strongest in the data
plane: results must be bit-identical across members, across runs, and
against a NUMPY TWO-TIER ORACLE that replays the exact reduction tree
(per-stripe/per-chunk ring order, per-hop q8 encode/decode, leader-side
bf16 rounding, per-leaf EF at the leader). The sum ORDER deliberately
differs from the flat ring, so flat-vs-hier is tolerance-checked, never
bit-compared.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import ml_dtypes
import numpy as np
import pytest

from torchft_tpu._native import Store
from torchft_tpu.collectives import (
    DummyCollectives,
    HostCollectives,
    ReduceOp,
    _effective_stripes,
)

BF16 = np.dtype(ml_dtypes.bfloat16)
F32 = np.float32


@pytest.fixture
def store():
    s = Store()
    yield s
    s.shutdown()


def _make_ring(store, regions, prefix="h0", stripes=1, stripes_inter=None,
               timeout=timedelta(seconds=20), world=None, hosts=None):
    world = world if world is not None else len(
        regions if regions is not None else hosts
    )
    cols = [
        HostCollectives(timeout=timeout, stripes=stripes,
                        stripes_inter=stripes_inter or 0)
        for _ in range(world)
    ]
    addr = f"{store.address()}/{prefix}"
    with ThreadPoolExecutor(max_workers=world) as ex:
        for f in [
            ex.submit(cols[r].configure, addr, r, world, regions, hosts)
            for r in range(world)
        ]:
            f.result()
    return cols


def _run_all(cols, fn):
    results = [None] * len(cols)
    errors = []

    def run(r):
        try:
            results[r] = fn(r, cols[r])
        except Exception as e:  # noqa: BLE001
            errors.append((r, e))

    threads = [threading.Thread(target=run, args=(r,)) for r in range(len(cols))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0][1]
    return results


# ---- the numpy two-tier oracle ----
#
# Mirrors the native schedule loop for loop: chunk_range partitioning,
# rs/ag accumulation order, the q8 wire's per-hop encode/decode (np.rint =
# std::nearbyint under round-to-nearest-even), the leader's bf16 cast
# (ml_dtypes rounds to nearest even like the native +0x7FFF+lsb path), and
# the per-leaf EF quantization at the leader. All arithmetic in f32.


def _chunk_range(count, ws, c):
    q, r = divmod(count, ws)
    start = c * q + min(c, r)
    return start, q + (1 if c < r else 0)


def _ring_rs(bufs):
    """In-place ring reduce-scatter over a list of same-length f32 views
    (one per tier rank), replaying the native accumulation order."""
    ws = len(bufs)
    count = bufs[0].size
    for t in range(ws - 1):
        sends = []
        for r in range(ws):
            s, l = _chunk_range(count, ws, (r - t) % ws)
            sends.append(bufs[r][s:s + l].copy())
        for r in range(ws):
            s, l = _chunk_range(count, ws, (r - t - 1) % ws)
            bufs[r][s:s + l] += sends[(r - 1) % ws]


def _ring_ag(bufs):
    """In-place ring allgather of the owned (fully-reduced) chunks."""
    ws = len(bufs)
    count = bufs[0].size
    for t in range(ws - 1):
        sends = []
        for r in range(ws):
            s, l = _chunk_range(count, ws, (r + 1 - t) % ws)
            sends.append(bufs[r][s:s + l].copy())
        for r in range(ws):
            s, l = _chunk_range(count, ws, (r - t) % ws)
            bufs[r][s:s + l] = sends[(r - 1) % ws]


def _q8_enc(chunk):
    """Native q8_encode mirror: (int8-grid codes as f32, f32 scale)."""
    if chunk.size and not np.all(np.isfinite(chunk)):
        return np.zeros_like(chunk), np.float32("nan")
    absmax = np.float32(np.max(np.abs(chunk))) if chunk.size else np.float32(0)
    scale = np.float32(absmax / np.float32(127.0)) if absmax > 0 else np.float32(1.0)
    q = np.clip(np.rint(chunk / scale), -127.0, 127.0).astype(F32)
    return q, scale


def _ring_rs_q8(bufs):
    ws = len(bufs)
    count = bufs[0].size
    for t in range(ws - 1):
        wires = []
        for r in range(ws):
            s, l = _chunk_range(count, ws, (r - t) % ws)
            wires.append(_q8_enc(bufs[r][s:s + l]))
        for r in range(ws):
            s, l = _chunk_range(count, ws, (r - t - 1) % ws)
            q, scale = wires[(r - 1) % ws]
            bufs[r][s:s + l] += scale * q


def _ring_ag_q8(bufs):
    """Owner quantizes its reduced chunk once; everyone (owner included)
    adopts the decoded codes."""
    ws = len(bufs)
    count = bufs[0].size
    for c in range(ws):
        s, l = _chunk_range(count, ws, c)
        owner = (c - 1) % ws
        q, scale = _q8_enc(bufs[owner][s:s + l])
        decoded = scale * q
        for r in range(ws):
            bufs[r][s:s + l] = decoded


def _striped(bufs, eff, phase):
    """Applies a ring phase independently per stripe sub-range (the native
    per-stripe partition)."""
    count = bufs[0].size
    for s in range(eff):
        st, ln = _chunk_range(count, eff, s)
        if ln:
            phase([b[st:st + ln] for b in bufs])


def hier_oracle(datas, regions, stripes=1, stripes_inter=None, wire=None,
                divisor=None, leader_ef_residuals=None, leaf_sizes=None,
                hosts=None):
    """The full hierarchical schedule in numpy; returns the per-member
    results (bit-identical across members by construction, like the
    native op).

    ``hosts`` (optional, one label per rank) adds the THIRD tier: members
    sharing a (region, host) pair first ring-reduce among themselves
    (host rs + ag, the shm tier's arithmetic), the intra tier then spans
    only HOST LEADERS, and the final adoption chain (member -> host
    leader -> region leader) collapses to "every member adopts its
    region leader's bytes" — the same adoption the two-tier oracle ends
    with.

    ``leader_ef_residuals``: dict region->f32 carry array — enables the
    q8ef PLAN semantics (per-leaf EF applied to the REGION sum at the
    leader before the quantized inter hop; ``leaf_sizes`` partitions the
    flat payload into leaves). Mutated in place across calls, mirroring
    the plan's persistent carry.
    """
    stripes_inter = stripes_inter or stripes
    count = datas[0].size
    bufs = [np.array(d, dtype=F32) for d in datas]
    eff_intra = _effective_stripes(count * 4, stripes)
    esz = 1 if wire in ("q8", "q8ef") else 2 if wire == "bf16" else 4
    eff_inter = _effective_stripes(count * esz, stripes_inter)

    if regions is None:
        regions = [""] * len(datas)
    members = {}
    for r, g in enumerate(regions):
        members.setdefault(g, []).append(r)
    leaders = sorted(m[0] for m in members.values())

    if hosts is not None:
        # Host tier first: ring rs + ag within each (region, host) group
        # (the host stripe partition is the intra one by construction).
        host_groups = {}
        for r in range(len(datas)):
            host_groups.setdefault((regions[r], hosts[r]), []).append(r)
        for mem in host_groups.values():
            if len(mem) > 1:
                sub = [bufs[r] for r in mem]
                _striped(sub, eff_intra, _ring_rs)
                _striped(sub, eff_intra, _ring_ag)
        # The intra tier spans HOST LEADERS only.
        members = {}
        seen = set()
        for r, g in enumerate(regions):
            k = (g, hosts[r])
            if k in seen:
                continue
            seen.add(k)
            members.setdefault(g, []).append(r)

    # intra reduce-scatter + allgather (full precision, fast links)
    for mem in members.values():
        if len(mem) > 1:
            sub = [bufs[r] for r in mem]
            _striped(sub, eff_intra, _ring_rs)
            _striped(sub, eff_intra, _ring_ag)

    # leader-side EF (plan q8ef): d = region_sum + carry; per-leaf
    # quantize on the 1e-12-floored scale; carry = d - dq; ship dq.
    if leader_ef_residuals is not None:
        assert wire == "q8ef" and leaf_sizes is not None
        for g, mem in members.items():
            res = leader_ef_residuals[g]
            buf = bufs[mem[0]]
            off = 0
            for n in leaf_sizes:
                d = buf[off:off + n] + res[off:off + n]
                absmax = np.float32(np.max(np.abs(d))) if n else np.float32(0)
                scale = np.maximum(
                    np.float32(absmax / np.float32(127.0)), np.float32(1e-12)
                )
                q = np.clip(np.rint(d / scale), -127.0, 127.0).astype(F32)
                dq = q * scale
                res[off:off + n] = d - dq
                buf[off:off + n] = dq
                off += n

    # inter ring among leaders (the only slow-link traffic)
    if len(leaders) > 1:
        lead = [bufs[r] for r in leaders]
        if wire in ("q8", "q8ef"):
            _striped(lead, eff_inter, _ring_rs_q8)
            _striped(lead, eff_inter, _ring_ag_q8)
        elif wire == "bf16":
            wide = [b.astype(BF16) for b in lead]

            def rs_bf16(views):
                ws = len(views)
                n = views[0].size
                for t in range(ws - 1):
                    sends = []
                    for r in range(ws):
                        s, l = _chunk_range(n, ws, (r - t) % ws)
                        sends.append(views[r][s:s + l].copy())
                    for r in range(ws):
                        s, l = _chunk_range(n, ws, (r - t - 1) % ws)
                        a = views[r][s:s + l].astype(F32)
                        b = sends[(r - 1) % ws].astype(F32)
                        views[r][s:s + l] = (a + b).astype(BF16)

            _striped(wide, eff_inter, rs_bf16)
            _striped(wide, eff_inter, _ring_ag)
            for i, r in enumerate(leaders):
                bufs[r][:] = wide[i].astype(F32)
        else:
            _striped(lead, eff_inter, _ring_rs)
            _striped(lead, eff_inter, _ring_ag)

    # broadcast: every member adopts its region leader's bytes verbatim
    out = []
    for r, g in enumerate(regions):
        out.append(bufs[members[g][0]].copy())
    if divisor is not None:
        out = [o / np.float32(divisor) for o in out]
    return out


REGION_LAYOUTS = [
    ["a", "a", "b", "b"],            # even, 2 regions
    ["a", "a", "a", "b", "b"],       # uneven
    ["a", "b", "c"],                 # singleton regions (pure leader ring)
    ["x", "y", "x", "y", "x"],       # interleaved ranks, uneven
]


class TestHierOracle:
    @pytest.mark.parametrize("regions", REGION_LAYOUTS)
    @pytest.mark.parametrize("wire", [None, "bf16", "q8"])
    def test_bit_identity_against_numpy_two_tier_oracle(
        self, store, regions, wire
    ):
        W = len(regions)
        rng = np.random.default_rng(7)
        datas = [
            (rng.standard_normal(997) * (r + 1)).astype(np.float32)
            for r in range(W)
        ]
        expect = hier_oracle(datas, regions, wire=wire)
        cols = _make_ring(store, regions, prefix=f"o_{wire}")
        res = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(datas[r].copy(), wire=wire).wait(),
        )
        for r in range(W):
            np.testing.assert_array_equal(
                np.asarray(res[r]), expect[r],
                err_msg=f"rank {r} diverged from the two-tier oracle",
            )
        for c in cols:
            c.shutdown()

    def test_multi_stripe_partition_matches_oracle(self, store):
        # Payload big enough that effective_stripes(count*4, 2) == 2: the
        # oracle replays the same per-stripe partition or this fails.
        regions = ["a", "a", "b", "b"]
        count = 40_000  # 160 KB > 2 * kMinStripeBytes
        datas = [
            np.linspace(-r - 1, r + 1, count, dtype=np.float32)
            for r in range(4)
        ]
        assert _effective_stripes(count * 4, 2) == 2
        expect = hier_oracle(datas, regions, stripes=2, wire="q8")
        cols = _make_ring(store, regions, prefix="o_s2", stripes=2)
        res = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(datas[r].copy(), wire="q8").wait(),
        )
        for r in range(4):
            np.testing.assert_array_equal(np.asarray(res[r]), expect[r])
        for c in cols:
            c.shutdown()

    def test_inter_stripe_knob_matches_oracle(self, store):
        # stripes_inter != stripes: the inter phase re-stripes on its own
        # knob; the oracle must agree on BOTH partitions.
        regions = ["a", "a", "b"]
        count = 70_000
        datas = [np.full(count, 0.125 * (r + 1), np.float32) + np.arange(
            count, dtype=np.float32) / 777 for r in range(3)]
        expect = hier_oracle(datas, regions, stripes=1, stripes_inter=4)
        cols = _make_ring(store, regions, prefix="o_si", stripes=1,
                          stripes_inter=4)
        res = _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        for r in range(3):
            np.testing.assert_array_equal(np.asarray(res[r]), expect[r])
        for c in cols:
            c.shutdown()

    def test_avg_divisor_matches_oracle(self, store):
        regions = ["a", "b", "b"]
        datas = [np.arange(100, dtype=np.float32) + r for r in range(3)]
        expect = hier_oracle(datas, regions, divisor=3.0)
        cols = _make_ring(store, regions, prefix="o_avg")
        res = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(
                datas[r].copy(), ReduceOp.AVG
            ).wait(),
        )
        for r in range(3):
            np.testing.assert_array_equal(np.asarray(res[r]), expect[r])
        for c in cols:
            c.shutdown()


class TestHierBasics:
    def test_no_region_map_is_flat_only(self, store):
        cols = _make_ring(store, regions=None, prefix="flat", world=2)
        assert not cols[0].hier_capable()
        with pytest.raises(RuntimeError, match="region map|two-tier"):
            _run_all(
                cols,
                lambda r, c: c.allreduce_hier(
                    np.ones(4, np.float32)
                ).wait(),
            )
        for c in cols:
            c.shutdown()

    def test_single_region_map_is_flat_only(self, store):
        cols = _make_ring(store, ["same", "same"], prefix="one")
        assert not cols[0].hier_capable()
        for c in cols:
            c.shutdown()

    def test_partially_labeled_map_is_flat_only(self, store):
        cols = _make_ring(store, ["a", ""], prefix="part")
        assert not cols[0].hier_capable()
        for c in cols:
            c.shutdown()

    def test_flat_ops_coexist_with_hier(self, store):
        # The flat ring is still there: the adaptive probe runs flat and
        # hier candidates against ONE configure.
        regions = ["a", "a", "b"]
        cols = _make_ring(store, regions, prefix="coex")
        assert all(c.hier_capable() for c in cols)
        data = [np.arange(50, dtype=np.float32) * (r + 1) for r in range(3)]
        flat = _run_all(cols, lambda r, c: c.allreduce(data[r]).wait())
        np.testing.assert_array_equal(np.asarray(flat[0]), sum(data))
        hier = _run_all(
            cols, lambda r, c: c.allreduce_hier(data[r].copy()).wait()
        )
        # Different summation tree: tolerance-equal to flat, never assumed
        # bit-equal (documented contract).
        np.testing.assert_allclose(
            np.asarray(hier[0]), np.asarray(flat[0]), rtol=1e-5
        )
        for c in cols:
            c.shutdown()

    def test_hier_wire_requires_f32_sum(self, store):
        cols = _make_ring(store, ["a", "b"], prefix="wv")
        with pytest.raises(ValueError, match="unsupported hier wire"):
            cols[0].allreduce_hier(np.ones(4, np.float32), wire="q8ef")
        with pytest.raises(ValueError, match="SUM/AVG"):
            cols[0].allreduce_hier(
                np.ones(4, np.float32), ReduceOp.MAX, wire="q8"
            )
        for c in cols:
            c.shutdown()

    def test_per_tier_stats_and_measured_inter_bytes(self, store):
        # The accounting satellite: per-tier phase keys + MEASURED tx
        # bytes. For the leader of a ring of L regions, each inter phase
        # ships (L-1)/L of the payload (+ per-hop q8 scales / op
        # headers): the whole point of the topology, verified from the
        # duplex counters, not a formula.
        regions = ["a", "a", "a", "a", "b", "b", "b", "b"]
        L, count = 2, 50_000
        cols = _make_ring(store, regions, prefix="stats")
        datas = [np.full(count, float(r + 1), np.float32) for r in range(8)]
        _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        st = [c.pop_op_stats()[-1] for c in cols]
        for r, s in enumerate(st):
            assert s["op"] == "allreduce_hier"
            for k in ("intra_rs_s", "intra_ag_s", "inter_ring_s",
                      "intra_bcast_s", "tiers"):
                assert k in s, f"rank {r} missing {k}"
        payload = count * 4
        expect_phase = payload * (L - 1) // L
        for r in (0, 4):  # the two leaders
            inter = st[r]["tiers"]["inter"]
            assert inter["leader"]
            assert inter["world"] == L
            for phase_key in ("rs_tx_bytes", "ag_tx_bytes"):
                measured = inter[phase_key]
                assert expect_phase <= measured <= expect_phase * 1.02 + 256, (
                    f"leader {r} {phase_key}={measured}, expected ~"
                    f"{expect_phase}"
                )
        for r in (1, 2, 3, 5, 6, 7):  # non-leaders never touch the DCN
            assert st[r]["tiers"]["inter"]["tx_bytes"] == 0
            assert not st[r]["tiers"]["inter"]["leader"]
            assert st[r]["tiers"]["intra"]["tx_bytes"] > 0
        for c in cols:
            c.shutdown()

    def test_dummy_fake_mirrors_capability_rule(self):
        d = DummyCollectives(world_size=2)
        d.configure("s", 0, 2, regions=["a", "b"])
        assert d.hier_capable()
        out = d.allreduce_hier({"x": np.ones(3, np.float32)}).wait()
        np.testing.assert_array_equal(out["x"], np.ones(3, np.float32))
        d.configure("s", 0, 2, regions=["a", "a"])
        assert not d.hier_capable()
        with pytest.raises(RuntimeError):
            d.allreduce_hier({"x": np.ones(3, np.float32)})


class TestHierPlans:
    def test_plan_matches_bulk_hier_bit_for_bit(self, store):
        regions = ["a", "a", "b", "b", "c"]
        rng = np.random.default_rng(3)
        trees = [
            {
                "w": rng.standard_normal((31, 7)).astype(np.float32),
                "b": rng.standard_normal(13).astype(np.float32),
            }
            for _ in range(5)
        ]
        cols = _make_ring(store, regions, prefix="pb")
        bulk = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(
                trees[r], ReduceOp.SUM, divisor=4.0
            ).wait(),
        )
        plan = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=4.0, hier=True
            ).wait(),
        )
        for r in range(5):
            for k in ("w", "b"):
                np.testing.assert_array_equal(
                    np.asarray(plan[r][k]), np.asarray(bulk[r][k])
                )
        # cross-member identity on the plan path too
        for r in range(1, 5):
            for k in ("w", "b"):
                np.testing.assert_array_equal(
                    np.asarray(plan[r][k]), np.asarray(plan[0][k])
                )
        for c in cols:
            c.shutdown()

    def test_plan_q8ef_multi_step_carry_matches_oracle(self, store):
        # The leader-side error-feedback carry, over several windows: the
        # oracle maintains per-REGION residuals and replays the per-leaf
        # EF quantization + quantized inter ring + broadcast, bit for bit.
        regions = ["a", "a", "b"]
        leaf_sizes = [60, 37]
        rng = np.random.default_rng(11)
        cols = _make_ring(store, regions, prefix="ef")
        residuals = {
            g: np.zeros(sum(leaf_sizes), np.float32) for g in ("a", "b")
        }
        for step in range(4):
            flats = [
                rng.standard_normal(sum(leaf_sizes)).astype(np.float32)
                * (0.1 + step)
                for _ in range(3)
            ]
            trees = [
                {"l0": f[:leaf_sizes[0]], "l1": f[leaf_sizes[0]:]}
                for f in flats
            ]
            expect = hier_oracle(
                flats, regions, wire="q8ef",
                leader_ef_residuals=residuals, leaf_sizes=leaf_sizes,
            )
            res = _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    trees[r], ReduceOp.SUM, wire="q8ef", hier=True
                ).wait(),
            )
            for r in range(3):
                got = np.concatenate(
                    [np.asarray(res[r]["l0"]), np.asarray(res[r]["l1"])]
                )
                np.testing.assert_array_equal(
                    got, expect[r], err_msg=f"step {step} rank {r}"
                )
        for c in cols:
            c.shutdown()

    def test_plan_reset_feedback_covers_hier_carry(self, store):
        regions = ["a", "b"]
        tree = {"x": np.linspace(-3, 5, 50, dtype=np.float32)}
        cols = _make_ring(store, regions, prefix="rst")

        def sync(r, c):
            return np.asarray(
                c.plan_allreduce(
                    tree, ReduceOp.SUM, wire="q8ef", hier=True
                ).wait()["x"]
            )

        first = _run_all(cols, sync)
        _run_all(cols, sync)  # advances the leader carries
        for c in cols:
            c.plan_reset_feedback()
        after_reset = _run_all(cols, sync)
        # a zeroed carry reproduces the fresh-plan first step exactly
        np.testing.assert_array_equal(after_reset[0], first[0])
        for c in cols:
            c.shutdown()

    def test_hier_plan_on_flat_ring_raises(self, store):
        cols = _make_ring(store, regions=None, prefix="pf", world=2)
        with pytest.raises(RuntimeError, match="hier-capable"):
            _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    np.ones(8, np.float32), ReduceOp.SUM, hier=True
                ).wait(),
            )
        for c in cols:
            c.shutdown()

    def test_hier_plan_stats_carry_tier_breakdown(self, store):
        regions = ["a", "a", "b"]
        tree = np.ones(60_000, np.float32)
        cols = _make_ring(store, regions, prefix="ps")
        _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                tree * (r + 1), ReduceOp.SUM, hier=True
            ).wait(),
        )
        st = cols[0].pop_op_stats()[-1]
        assert st["op"] == "plan_allreduce" and st["hier"] is True
        assert st["tiers"]["inter"]["leader"]
        assert st["py_staging_allocs"] == 0
        assert st["buckets"], "per-bucket plan stats missing on the hier path"
        for c in cols:
            c.shutdown()


class TestHierFaults:
    def test_leader_death_errors_all_tiers_and_recovers(self, store):
        # Kill the leader of region b mid-collective: its inter peer (the
        # region-a leader) AND its own intra members must all error within
        # one op deadline — never the full timeout — and a reconfigure of
        # the survivors commits the next op (step-granularity recovery).
        regions = ["a", "a", "b", "b"]
        cols = _make_ring(store, regions, prefix="kill",
                          timeout=timedelta(seconds=30))
        victim = 2  # leader of region b
        data = np.ones(2_000_000, np.float32)

        # ~8 MB payload through loopback finishes in well under a second;
        # the shutdown timer fires mid-op only if the op is still alive,
        # so also pace the op down via a barrier-free big payload and an
        # early timer.
        threading.Timer(0.05, cols[victim].shutdown).start()
        t0 = time.perf_counter()
        errors = []

        def run(r):
            try:
                cols[r].allreduce_hier(data.copy()).wait()
            except Exception as e:  # noqa: BLE001
                errors.append((r, e))

        threads = [
            threading.Thread(target=run, args=(r,))
            for r in range(4) if r != victim
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        # Either the op raced the kill and finished, or EVERY survivor
        # errored; a partial outcome (some members stuck) is the failure
        # mode this test exists to catch.
        assert len(errors) in (0, 3), f"partial failure: {errors}"
        assert elapsed < 25, "survivors blocked toward the full timeout"

        # recovery: survivors reconfigure on a fresh prefix and commit
        survivors = [cols[0], cols[1], cols[3]]
        new_regions = ["a", "a", "b"]
        addr = f"{store.address()}/kill2"
        with ThreadPoolExecutor(max_workers=3) as ex:
            for f in [
                ex.submit(survivors[i].configure, addr, i, 3, new_regions)
                for i in range(3)
            ]:
                f.result()
        small = [np.arange(40, dtype=np.float32) * (i + 1) for i in range(3)]
        res = _run_all(
            survivors, lambda i, c: c.allreduce_hier(small[i].copy()).wait()
        )
        expect = hier_oracle(small, new_regions)
        np.testing.assert_array_equal(np.asarray(res[0]), expect[0])
        for c in survivors:
            c.shutdown()

    def test_nonleader_abort_propagates_ring_wide(self, store):
        regions = ["a", "a", "b", "b"]
        cols = _make_ring(store, regions, prefix="ab",
                          timeout=timedelta(seconds=30))
        data = np.ones(2_000_000, np.float32)
        threading.Timer(0.05, cols[3].abort).start()  # non-leader of b
        errors = []

        def run(r):
            try:
                cols[r].allreduce_hier(data.copy()).wait()
            except Exception as e:  # noqa: BLE001
                errors.append((r, e))

        t0 = time.perf_counter()
        threads = [threading.Thread(target=run, args=(r,)) for r in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert time.perf_counter() - t0 < 25
        # WHO errors depends on which phase the abort lands in (members a
        # phase past the victim's tier may legitimately complete: e.g.
        # region a finishes once the inter ring is done, while the
        # victim's region still fails its broadcast). The invariant is
        # that NOBODY blocks toward the full timeout — the elapsed bound
        # above — and that errors are real ring failures, not hangs.
        for _, e in errors:
            assert isinstance(e, RuntimeError)
        for c in cols:
            c.shutdown()


class TestManagerRegionPlumbing:
    def test_region_label_flows_quorum_to_two_tier_data_plane(self):
        # TORCHFT_REGION-style labels ride QuorumMember through the
        # lighthouse, come back as the quorum's region map, and configure
        # the host ring's two-tier schedule: the full control-plane ->
        # data-plane path, end to end, with a managed allreduce_hier on
        # top of it.
        from torchft_tpu import Lighthouse, Manager

        lighthouse = Lighthouse(min_replicas=2, join_timeout_ms=100)
        results = {}
        errors = []
        barrier = threading.Barrier(2)

        def replica(idx, region):
            store = Store()
            hc = HostCollectives(timeout=timedelta(seconds=20))
            manager = None
            try:
                state_box = {"params": 0}
                manager = Manager(
                    collectives=hc,
                    # Step-0 initial weight sync: the non-primary replica
                    # heals from the primary, so real callbacks are needed.
                    load_state_dict=lambda s: state_box.update(s),
                    state_dict=lambda: dict(state_box),
                    min_replica_size=2,
                    use_async_quorum=False,
                    rank=0,
                    world_size=1,
                    store_addr=store.address(),
                    lighthouse_addr=lighthouse.address(),
                    region=region,
                    replica_id=f"hier{idx}",
                    timeout=timedelta(seconds=20),
                    quorum_timeout=timedelta(seconds=20),
                )
                barrier.wait(timeout=20)
                manager.start_quorum()
                tree = {"g": np.full(64, float(idx + 1), np.float32)}
                out = manager.allreduce_hier(tree).wait()
                committed = manager.should_commit()
                results[idx] = {
                    "regions": manager.replica_regions(),
                    "hier_capable": manager.hier_capable(),
                    "avg": np.asarray(out["g"]).copy(),
                    "committed": committed,
                }
            except Exception as e:  # noqa: BLE001
                errors.append((idx, e))
            finally:
                if manager is not None:
                    manager.shutdown()
                hc.shutdown()
                store.shutdown()

        threads = [
            threading.Thread(target=replica, args=(0, "east")),
            threading.Thread(target=replica, args=(1, "west")),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lighthouse.shutdown()
        assert not errors, errors
        for idx in (0, 1):
            r = results[idx]
            assert sorted(r["regions"]) == ["east", "west"]
            assert r["hier_capable"]
            assert r["committed"]
            # AVG of 1.0 and 2.0 across the two regions
            np.testing.assert_allclose(r["avg"], np.full(64, 1.5), rtol=1e-6)
        np.testing.assert_array_equal(results[0]["avg"], results[1]["avg"])

    def test_unlabeled_cohort_latches_hier_dispatch(self):
        # No TORCHFT_REGION anywhere: the quorum's map is all-empty, the
        # data plane stays flat, and the managed hier dispatch LATCHES
        # (sentinel discipline) — the step discards, nothing crashes, and
        # the next flat step commits again.
        from torchft_tpu import Lighthouse, Manager

        lighthouse = Lighthouse(min_replicas=1, join_timeout_ms=50)
        store = Store()
        hc = HostCollectives(timeout=timedelta(seconds=10))
        manager = Manager(
            collectives=hc,
            load_state_dict=None,
            state_dict=None,
            min_replica_size=1,
            use_async_quorum=False,
            rank=0,
            world_size=1,
            store_addr=store.address(),
            lighthouse_addr=lighthouse.address(),
            region="",
            replica_id="solo",
            timeout=timedelta(seconds=10),
        )
        try:
            manager.start_quorum()
            assert not manager.hier_capable()
            # Solo cohort: world 1 — allreduce_hier degenerates to the
            # identity and must NOT latch (a single member has no slow
            # links to optimize but also nothing to get wrong).
            out = manager.allreduce_hier(
                {"g": np.ones(8, np.float32)}
            ).wait()
            np.testing.assert_array_equal(
                np.asarray(out["g"]), np.ones(8, np.float32)
            )
            assert manager.should_commit()
        finally:
            manager.shutdown()
            hc.shutdown()
            store.shutdown()
            lighthouse.shutdown()


# ---- the shared-memory host (third) tier ----

HOST_LAYOUTS = [
    # (regions, hosts) — co-hosted pairs inside 2 regions
    (["a", "a", "b", "b"], ["h0", "h0", "h1", "h1"]),
    # uneven: a 3-member host group + a singleton + a pair
    (["a", "a", "a", "b", "b"], ["h0", "h0", "h0", "h1", "h1"]),
    # hosts straddle nothing: one host per region member (degenerates to
    # the pure two-tier schedule — host tier world 1 everywhere)
    (["a", "a", "b"], ["h0", "h1", "h2"]),
    # single-region cohort grouped by host only (no inter tier at all)
    (None, ["h0", "h0", "h1", "h1"]),
]


# Rank 1 of a two-member co-hosted ring: one op to prove the shm tier is
# up, then it parks until the test SIGKILLs it.
_COHOSTED_PEER = """
import sys, time
from datetime import timedelta
import numpy as np
from torchft_tpu.collectives import HostCollectives, ReduceOp
col = HostCollectives(timeout=timedelta(seconds=60), stripes=1)
col.configure(sys.argv[1], 1, 2, None, ["hK", "hK"])
col.allreduce_hier(np.ones(1 << 16, np.float32), ReduceOp.SUM).wait()
print("ready", flush=True)
time.sleep(600)
"""


class TestShmTier:
    """The zero-copy intra-host tier: shm rings below the region tiers,
    bit-identity pinned against the three-tier numpy oracle, the
    loopback-TCP fallback as the control, and the segment-lifecycle /
    abort contracts."""

    def _live(self):
        from torchft_tpu._native import _lib

        return int(_lib.tft_shm_live_count())

    @pytest.mark.parametrize("layout", HOST_LAYOUTS)
    @pytest.mark.parametrize("wire", [None, "bf16", "q8"])
    def test_bit_identity_against_three_tier_oracle(self, store, layout,
                                                    wire):
        regions, hosts = layout
        W = len(hosts)
        rng = np.random.default_rng(11)
        datas = [
            (rng.standard_normal(997) * (r + 1)).astype(np.float32)
            for r in range(W)
        ]
        expect = hier_oracle(datas, regions, wire=wire, hosts=hosts)
        cols = _make_ring(store, regions, prefix=f"shm_{wire}", hosts=hosts)
        res = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(datas[r].copy(), wire=wire).wait(),
        )
        for r in range(W):
            np.testing.assert_array_equal(
                np.asarray(res[r]), expect[r],
                err_msg=f"rank {r} diverged from the three-tier oracle",
            )
        for c in cols:
            c.shutdown()

    def test_multi_stripe_three_tier_matches_oracle(self, store):
        regions, hosts = ["a", "a", "b", "b"], ["h0", "h0", "h1", "h1"]
        rng = np.random.default_rng(13)
        # > 2 * 16384 f32 elements so effective_stripes picks 2
        datas = [
            (rng.standard_normal(40_000) * (r + 1)).astype(np.float32)
            for r in range(4)
        ]
        expect = hier_oracle(datas, regions, stripes=2, wire="q8",
                             hosts=hosts)
        cols = _make_ring(store, regions, prefix="shm_s2", stripes=2,
                          hosts=hosts)
        res = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(datas[r].copy(), wire="q8").wait(),
        )
        for r in range(4):
            np.testing.assert_array_equal(np.asarray(res[r]), expect[r])
        for c in cols:
            c.shutdown()

    def test_tcp_fallback_matches_shm_bit_for_bit(self, store, monkeypatch):
        # TORCHFT_HC_SHM=0: same geometry over loopback TCP. The schedule
        # (and therefore every bit) must be identical — transport is not
        # arithmetic.
        regions, hosts = None, ["h0", "h0", "h1", "h1"]
        rng = np.random.default_rng(17)
        datas = [
            (rng.standard_normal(997) * (r + 1)).astype(np.float32)
            for r in range(4)
        ]
        cols = _make_ring(store, regions, prefix="shm_on", hosts=hosts)
        assert [c.host_tier_transport() for c in cols] == ["shm"] * 4
        res_shm = _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        for c in cols:
            c.shutdown()

        monkeypatch.setenv("TORCHFT_HC_SHM", "0")
        cols = _make_ring(store, regions, prefix="tcp_fb", hosts=hosts)
        assert [c.host_tier_transport() for c in cols] == ["tcp"] * 4
        res_tcp = _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        for r in range(4):
            np.testing.assert_array_equal(
                np.asarray(res_shm[r]), np.asarray(res_tcp[r])
            )
        # and both match the oracle
        expect = hier_oracle(datas, regions, hosts=hosts)
        np.testing.assert_array_equal(np.asarray(res_tcp[0]), expect[0])
        for c in cols:
            c.shutdown()

    def test_hosts_only_cohort_is_hier_capable(self, store):
        # No region labels at all: >= 2 co-hosted members still make the
        # hierarchical schedule available (host rings + a host-leader
        # ring are two real tiers).
        cols = _make_ring(store, None, prefix="honly",
                          hosts=["h0", "h0", "h1"])
        assert all(c.hier_capable() for c in cols)
        assert cols[0].host_tier_transport() == "shm"
        assert cols[2].host_tier_transport() == "none"  # singleton host
        res = _run_all(
            cols,
            lambda r, c: c.allreduce_hier(
                np.full(64, float(r + 1), np.float32)
            ).wait(),
        )
        for r in range(3):
            np.testing.assert_array_equal(
                np.asarray(res[r]), np.full(64, 6.0, np.float32)
            )
        for c in cols:
            c.shutdown()

    def test_plan_q8ef_carry_matches_three_tier_oracle(self, store):
        # The leader-side EF carry discipline is UNCHANGED by the host
        # tier: the region leader quantizes the region sum (which now
        # includes the host-tier reduction) against its persistent
        # residual before the inter hop.
        regions, hosts = ["a", "a", "b", "b"], ["h0", "h0", "h1", "h1"]
        rng = np.random.default_rng(23)
        leaf_sizes = [300, 197]
        count = sum(leaf_sizes)
        cols = _make_ring(store, regions, prefix="shm_ef", hosts=hosts)
        residuals = {g: np.zeros(count, F32) for g in ("a", "b")}
        for it in range(3):
            datas = [
                (rng.standard_normal(count) * (r + 1) * (it + 1)).astype(
                    np.float32
                )
                for r in range(4)
            ]
            expect = hier_oracle(
                datas, regions, wire="q8ef", hosts=hosts,
                leader_ef_residuals=residuals, leaf_sizes=leaf_sizes,
            )
            res = _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    {"a": datas[r][:300].copy(), "b": datas[r][300:].copy()},
                    ReduceOp.SUM, wire="q8ef", hier=True,
                ).wait(),
            )
            for r in range(4):
                got = np.concatenate(
                    [np.asarray(res[r]["a"]), np.asarray(res[r]["b"])]
                )
                np.testing.assert_array_equal(
                    got, expect[r], err_msg=f"iter {it} rank {r}"
                )
        for c in cols:
            c.shutdown()

    def test_segments_owned_by_configure_generation(self, store):
        base = self._live()
        hosts = ["h0", "h0"]
        cols = _make_ring(store, None, prefix="gen0", world=2, hosts=hosts)
        # 2 members x 1 stripe x (1 tx + 1 rx) handles
        assert self._live() == base + 4
        # reconfigure under a fresh prefix: old generation torn down, new
        # one stands — the count must not grow
        addr = f"{store.address()}/gen1"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, 2, None, hosts)
                for r in range(2)
            ]:
                f.result()
        assert self._live() == base + 4
        # reconfigure WITHOUT hosts: the host tier (and every segment)
        # must be gone
        addr = f"{store.address()}/gen2"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
            ]:
                f.result()
        assert self._live() == base
        for c in cols:
            c.shutdown()

    def test_cohosted_abort_wakes_peer_within_deadline(self, store):
        # One co-hosted member aborts mid-collective: its peers must
        # error promptly (the poisoned ring magic is the shm FIN), not
        # wait out a long deadline.
        hosts = ["h0", "h0", "h0"]
        cols = _make_ring(store, None, prefix="abrt", world=3, hosts=hosts,
                          timeout=timedelta(seconds=60))
        data = np.ones(1 << 20, np.float32)
        start = time.perf_counter()
        errs = []

        def run(r, c):
            if r == 2:
                time.sleep(0.15)
                c.abort()
                return "aborted"
            try:
                return c.allreduce_hier(data.copy()).wait()
            except Exception as e:  # noqa: BLE001
                errs.append((r, e, time.perf_counter() - start))
                return None

        _run_all(cols, run)
        assert len(errs) == 2, "both survivors must error"
        for _, _, dt in errs:
            assert dt < 30.0, f"survivor blocked {dt:.1f}s (deadline leak)"
        for c in cols:
            c.shutdown()

    def test_cohosted_sigkill_surfaces_within_deadline(self, store):
        # A SIGKILLed co-hosted peer closes no socket and poisons no ring
        # magic: the survivor's only signal is the pid-liveness probe its
        # blocked shm waiter runs each futex slice. The death must surface
        # well inside the op deadline, and the survivor must be able to
        # reconfigure and commit alone.
        hosts = ["hK", "hK"]
        addr = f"{store.address()}/cokill"
        child = subprocess.Popen(
            [sys.executable, "-c", _COHOSTED_PEER, addr],
            stdout=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        col = HostCollectives(timeout=timedelta(seconds=60), stripes=1)
        try:
            col.configure(addr, 0, 2, None, hosts)
            assert col.hier_capable()
            data = np.ones(1 << 16, np.float32)
            out = col.allreduce_hier(data.copy(), ReduceOp.SUM).wait()
            np.testing.assert_array_equal(out, data * 2)
            assert child.stdout.readline().strip() == "ready"
            os.kill(child.pid, signal.SIGKILL)
            child.wait(timeout=30)
            start = time.perf_counter()
            with pytest.raises(Exception):  # noqa: B017 - any ring error
                col.allreduce_hier(data.copy(), ReduceOp.SUM).wait()
            dt = time.perf_counter() - start
            assert dt < 20.0, f"survivor blocked {dt:.1f}s of a 60 s deadline"
            col.configure(f"{store.address()}/cokill_alone", 0, 1)
            np.testing.assert_array_equal(
                col.allreduce(data.copy(), ReduceOp.SUM).wait(), data
            )
        finally:
            if child.poll() is None:
                child.kill()
            child.wait()
            col.shutdown()

    def test_stale_frame_detected_as_wire_corruption(self, store):
        # The shm_ring bit_flip fault replays a stale frame sequence; the
        # consumer must surface the typed WireCorruption verdict (the
        # latch -> vote-discard contract), never reduce yesterday's bytes.
        from torchft_tpu._native import WireCorruption, _lib

        hosts = ["h0", "h0"]
        cols = _make_ring(store, None, prefix="stale", world=2, hosts=hosts,
                          timeout=timedelta(seconds=15))
        plan = {
            "seed": 7,
            "rules": [{
                "seam": "shm_ring", "kind": "bit_flip", "member": 0,
                "min_op": 0, "max_op": -1, "permille": 1000, "one_shot": 1,
            }],
        }
        _lib.tft_fault_arm(json.dumps(plan).encode())
        try:
            errs = []

            def run(r, c):
                try:
                    return c.allreduce_hier(
                        np.ones(256, np.float32)
                    ).wait()
                except Exception as e:  # noqa: BLE001
                    errs.append(e)
                    return None

            _run_all(cols, run)
            assert errs, "the stale frame went undetected"
            assert any(
                isinstance(e, WireCorruption)
                or "stale frame" in str(e)
                for e in errs
            ), f"wrong verdict: {errs}"
        finally:
            _lib.tft_fault_disarm()
        for c in cols:
            c.shutdown()


class TestManagerHostPlumbing:
    def test_host_label_flows_quorum_to_shm_tier(self, monkeypatch):
        # TORCHFT_HOST rides QuorumMember like region does: two co-hosted
        # replica groups (same explicit host label) come back in
        # replica_hosts, Manager.configure hands the map to the data
        # plane, and the shm host tier stands up end to end.
        from torchft_tpu import Lighthouse, Manager

        monkeypatch.setenv("TORCHFT_HOST", "testhost0")
        lighthouse = Lighthouse(min_replicas=2, join_timeout_ms=100)
        results = {}
        errors = []
        barrier = threading.Barrier(2)

        def replica(idx):
            store = Store()
            hc = HostCollectives(timeout=timedelta(seconds=20))
            manager = None
            try:
                state_box = {"params": 0}
                manager = Manager(
                    collectives=hc,
                    # Step-0 initial weight sync: the non-primary replica
                    # heals from the primary, so real callbacks are needed.
                    load_state_dict=lambda s: state_box.update(s),
                    state_dict=lambda: dict(state_box),
                    min_replica_size=2,
                    use_async_quorum=False,
                    rank=0,
                    world_size=1,
                    store_addr=store.address(),
                    lighthouse_addr=lighthouse.address(),
                    replica_id=f"hostplumb{idx}",
                    timeout=timedelta(seconds=20),
                    quorum_timeout=timedelta(seconds=20),
                )
                barrier.wait(timeout=20)
                manager.start_quorum()
                tree = {"g": np.full(64, float(idx + 1), np.float32)}
                out = manager.allreduce_hier(tree).wait()
                committed = manager.should_commit()
                results[idx] = {
                    "hosts": manager.replica_hosts(),
                    "hier_capable": manager.hier_capable(),
                    "transport": hc.host_tier_transport(),
                    "avg": np.asarray(out["g"]).copy(),
                    "committed": committed,
                }
            except Exception as e:  # noqa: BLE001
                errors.append((idx, e))
            finally:
                if manager is not None:
                    manager.shutdown()
                hc.shutdown()
                store.shutdown()

        threads = [
            threading.Thread(target=replica, args=(i,)) for i in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        lighthouse.shutdown()
        assert not errors, errors
        for idx in (0, 1):
            r = results[idx]
            assert r["hosts"] == ["testhost0"] * 2
            assert r["hier_capable"]
            assert r["transport"] == "shm"
            assert r["committed"]
            # AVG of 1.0 and 2.0 across the two co-hosted groups
            np.testing.assert_allclose(r["avg"], np.full(64, 1.5), rtol=1e-6)
        np.testing.assert_array_equal(results[0]["avg"], results[1]["avg"])
