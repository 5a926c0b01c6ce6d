"""Persistent comm-plan tests.

The plan path's contract: ONE GIL-released native call per step, zero
Python-side staging allocation after warmup, and results BIT-IDENTICAL to
the legacy managed path for every wire — the plan executes the identical
per-group stripe partition through the same native ring bodies, so these
tests are the oracle that the shared-schedule claim stays true as either
path evolves.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu._native import Store
from torchft_tpu.collectives import (
    DummyCollectives,
    HostCollectives,
    ReduceOp,
)
from torchft_tpu.quantize import np_quantize_ef as _np_quantize_ef


@pytest.fixture
def store():
    s = Store()
    yield s
    s.shutdown()


def _make_ring(store, world_size, prefix, stripes=1,
               timeout=timedelta(seconds=15)):
    cols = [
        HostCollectives(timeout=timeout, stripes=stripes)
        for _ in range(world_size)
    ]
    addr = f"{store.address()}/{prefix}"
    with ThreadPoolExecutor(max_workers=world_size) as ex:
        for f in [
            ex.submit(cols[r].configure, addr, r, world_size)
            for r in range(world_size)
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

    threads = [
        threading.Thread(target=run, args=(r,)) for r in range(len(cols))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0][1]
    return results


def _trees(world_size, rng_seed=7):
    """Mixed-dtype trees with uneven leaf sizes: the flat counts divide
    evenly by neither world size nor stripe count, so ring chunks AND
    stripe sub-ranges (= plan buckets) land on uneven tails."""
    import ml_dtypes

    rng = np.random.default_rng(rng_seed)
    base = {
        "w": rng.standard_normal(100003).astype(np.float32),
        "v": rng.standard_normal((13, 7)).astype(np.float64),
        "b": (rng.integers(-16, 16, 1001) * 0.125).astype(ml_dtypes.bfloat16),
        "n": rng.integers(-100, 100, 41).astype(np.int64),
    }
    return [
        {k: v * (r + 1) for k, v in base.items()} for r in range(world_size)
    ]


class TestPlanBitIdentity:
    @pytest.mark.parametrize("world_size", [2, 3, 5])
    @pytest.mark.parametrize("stripes", [1, 4])
    def test_native_wire_matches_legacy(self, store, world_size, stripes):
        cols = _make_ring(
            store, world_size, f"p_{world_size}_{stripes}", stripes
        )
        trees = _trees(world_size)
        div = float(world_size)
        legacy = _run_all(
            cols,
            lambda r, c: c.allreduce(trees[r], ReduceOp.SUM, divisor=div)
            .wait(),
        )
        plan = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=div
            ).wait(),
        )
        for leg, pl in zip(legacy, plan):
            for k in leg:
                assert (
                    np.asarray(leg[k]).tobytes() == np.asarray(pl[k]).tobytes()
                ), f"leaf {k}: plan != legacy bitwise"
        # and across ranks (the determinism oracle, extended to the plan)
        for other in plan[1:]:
            for k in other:
                assert np.asarray(plan[0][k]).tobytes() == np.asarray(
                    other[k]
                ).tobytes()
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("stripes", [1, 4])
    def test_q8_wire_matches_legacy(self, store, stripes):
        cols = _make_ring(store, 3, f"pq8_{stripes}", stripes)
        rng = np.random.default_rng(3)
        base = rng.standard_normal(100003).astype(np.float32)
        trees = [{"g": base * (r + 1)} for r in range(3)]
        legacy = _run_all(
            cols,
            lambda r, c: c.allreduce(
                trees[r], ReduceOp.SUM, divisor=3.0, wire="q8"
            ).wait(),
        )
        plan = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=3.0, wire="q8"
            ).wait(),
        )
        for leg, pl in zip(legacy, plan):
            assert np.asarray(leg["g"]).tobytes() == np.asarray(
                pl["g"]
            ).tobytes()
        for c in cols:
            c.shutdown()

    def test_bf16_wire_matches_legacy_cast_composition(self, store):
        # wire="bf16"'s legacy equivalent is ddp's compress="bf16": cast
        # f32 leaves to bf16, ride the native bf16 ring, cast back.
        import ml_dtypes

        cols = _make_ring(store, 3, "pbf", stripes=2)
        rng = np.random.default_rng(5)
        base = rng.standard_normal(70001).astype(np.float32)
        trees = [{"g": base * (r + 1)} for r in range(3)]
        cast = [
            {"g": t["g"].astype(ml_dtypes.bfloat16)} for t in trees
        ]
        legacy = _run_all(
            cols,
            lambda r, c: c.allreduce(cast[r], ReduceOp.SUM, divisor=3.0)
            .wait(),
        )
        plan = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=3.0, wire="bf16"
            ).wait(),
        )
        for leg, pl in zip(legacy, plan):
            got = np.asarray(pl["g"])
            assert got.dtype == np.float32  # decoded back to the leaf dtype
            want = np.asarray(leg["g"]).astype(np.float32)
            assert got.tobytes() == want.tobytes()
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("world_size", [2, 3])
    def test_q8ef_matches_numpy_ef_plus_legacy_q8(self, store, world_size):
        # The error-feedback oracle, run over several steps so the carry
        # itself is proven bit-identical (a drifting residual would
        # surface as a diverging quantization within a few steps).
        cols = _make_ring(store, world_size, f"pef_{world_size}", stripes=4)
        rng = np.random.default_rng(11)
        N = 70001
        res = [
            {"w": np.zeros(N, np.float32), "b": np.zeros(33, np.float32)}
            for _ in range(world_size)
        ]
        div = float(world_size)
        for step in range(5):
            grads = [
                {
                    "w": rng.standard_normal(N).astype(np.float32),
                    "b": rng.standard_normal(33).astype(np.float32) * 7,
                }
                for _ in range(world_size)
            ]
            legacy_dq = []
            for r in range(world_size):
                dqt = {}
                for k in grads[r]:
                    dq, nr = _np_quantize_ef(grads[r][k], res[r][k])
                    dqt[k] = dq
                    res[r][k] = nr
                legacy_dq.append(dqt)
            leg = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    legacy_dq[r], ReduceOp.SUM, divisor=div, wire="q8"
                ).wait(),
            )
            plan = _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    grads[r], ReduceOp.SUM, divisor=div, wire="q8ef"
                ).wait(),
            )
            for k in ("w", "b"):
                assert np.asarray(leg[0][k]).tobytes() == np.asarray(
                    plan[0][k]
                ).tobytes(), f"step {step} leaf {k}: EF diverged"
        for c in cols:
            c.shutdown()

    def test_q8ef_reset_feedback_restarts_carry(self, store):
        cols = _make_ring(store, 2, "pefreset")
        rng = np.random.default_rng(2)
        grads = [
            {"w": rng.standard_normal(5001).astype(np.float32) * (r + 1)}
            for r in range(2)
        ]

        def sync(r, c):
            return c.plan_allreduce(
                grads[r], ReduceOp.SUM, divisor=2.0, wire="q8ef"
            ).wait()

        first = _run_all(cols, sync)
        _run_all(cols, sync)  # advances the carry
        _run_all(cols, lambda r, c: c.plan_reset_feedback())
        again = _run_all(cols, sync)  # carry zeroed -> same as the first
        assert np.asarray(first[0]["w"]).tobytes() == np.asarray(
            again[0]["w"]
        ).tobytes()
        for c in cols:
            c.shutdown()


class TestPlanLifecycle:
    def test_q8_nonfinite_poisons_all_members(self, store):
        # The fused q8 poisoning contract holds on the plan path too: a
        # NaN/Inf leaf must come out non-finite on EVERY member.
        cols = _make_ring(store, 3, "ppoison")
        rng = np.random.default_rng(17)
        base = rng.standard_normal(400).astype(np.float32)

        def op(r, c):
            arr = base * (r + 1)
            if r == 0:
                arr = arr.copy()
                arr[7] = np.nan
                arr[250] = np.inf
            return c.plan_allreduce(
                {"w": arr}, ReduceOp.SUM, wire="q8"
            ).wait()

        results = _run_all(cols, op)
        for out in results:
            got = np.asarray(out["w"])
            assert np.isnan(got[7])
            assert np.isnan(got[250])
        for other in results[1:]:
            assert np.asarray(results[0]["w"]).tobytes() == np.asarray(
                other["w"]
            ).tobytes()
        for c in cols:
            c.shutdown()

    def test_zero_python_staging_allocs_and_bucket_stats(self, store):
        cols = _make_ring(store, 2, "pstats", stripes=4)
        rng = np.random.default_rng(0)
        # > 4 * 64 KiB so the payload stripes into 4 buckets
        tree = {"g": rng.standard_normal(200003).astype(np.float32)}
        trees = [tree, {"g": tree["g"] * 2}]

        def sync(r, c):
            return c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=2.0
            ).wait()

        _run_all(cols, sync)  # warmup (plan build)
        cols[0].pop_op_stats()
        _run_all(cols, sync)
        _run_all(cols, sync)
        stats = [
            s for s in cols[0].pop_op_stats() if s["op"] == "plan_allreduce"
        ]
        assert len(stats) == 2
        for st in stats:
            # the zero-allocation contract after warmup
            assert st["py_staging_allocs"] == 0
            assert st["bytes"] == tree["g"].nbytes
            assert st["buckets"], "plan stats must carry per-bucket phases"
            assert len(st["buckets"]) == 4  # 4 stripes -> 4 buckets
            for b in st["buckets"]:
                assert {"group", "stripe", "bytes", "pack_s", "ring_s",
                        "unpack_s"} <= set(b)
            assert sum(b["bytes"] for b in st["buckets"]) == tree["g"].nbytes
        for c in cols:
            c.shutdown()

    def test_plan_survives_repeat_and_reconfigure(self, store):
        # Same signature reuses the cached plan; a reconfigure (new
        # quorum) invalidates and transparently rebuilds it — and the
        # rebuilt plan is correct for the NEW membership.
        cols = _make_ring(store, 3, "precfg")
        tree = {"g": np.ones(10007, np.float32)}

        out = _run_all(
            cols,
            lambda r, c: c.plan_allreduce({"g": tree["g"] * (r + 1)}).wait(),
        )
        np.testing.assert_array_equal(
            np.asarray(out[0]["g"]), np.full(10007, 6.0)
        )
        assert len(cols[0]._plans) == 1

        survivors = cols[:2]
        addr = f"{store.address()}/precfg2"
        _run_all(survivors, lambda r, c: c.configure(addr, r, 2))
        assert cols[0]._plans == {}  # cache dropped with the old ring
        out = _run_all(
            survivors,
            lambda r, c: c.plan_allreduce({"g": tree["g"] * (r + 1)}).wait(),
        )
        np.testing.assert_array_equal(
            np.asarray(out[0]["g"]), np.full(10007, 3.0)
        )
        for c in cols:
            c.shutdown()

    def test_stale_native_plan_id_errors(self, store):
        # The native side must reject an id from before a reconfigure
        # (its layout baked in the old ring) instead of executing it.
        import ctypes

        from torchft_tpu._native import _lib

        cols = _make_ring(store, 2, "pstale")
        tree = {"g": np.ones(4096, np.float32)}
        _run_all(cols, lambda r, c: c.plan_allreduce(
            {"g": tree["g"] * (r + 1)}).wait())
        plan = next(iter(cols[0]._plans.values()))
        stale_id = plan.plan_id
        addr = f"{store.address()}/pstale2"
        _run_all(cols, lambda r, c: c.configure(addr, r, 2))
        out = ctypes.c_void_p()
        rc = _lib.tft_plan_stats_json(
            cols[0]._handle, stale_id, ctypes.byref(out)
        )
        assert rc != 0  # unknown/invalidated plan
        for c in cols:
            c.shutdown()

    def test_abort_during_plan_execute_wakes_all_stripes(self, store):
        # Peer death mid-plan-execute must wake EVERY stripe worker
        # promptly (one surfaced error, not one timeout per stripe), and
        # a fresh configure restores plan service.
        cols = [
            HostCollectives(timeout=timedelta(seconds=30), stripes=4)
            for _ in range(2)
        ]
        addr = f"{store.address()}/pabort"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
            ]:
                f.result()
        big = {"g": np.ones(1 << 20, np.float32)}  # 4 MB -> 4 stripes
        w = cols[0].plan_allreduce(big)
        threading.Timer(0.3, cols[1].shutdown).start()
        start = time.monotonic()
        with pytest.raises(RuntimeError):
            w.wait(timeout=timedelta(seconds=20))
        elapsed = time.monotonic() - start
        assert elapsed < 5.0, (
            f"plan abort took {elapsed:.1f}s — a stripe worker sat out "
            "its own timeout instead of being woken"
        )
        fresh = HostCollectives(timeout=timedelta(seconds=30), stripes=4)
        addr2 = f"{store.address()}/pabort2"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[0].configure, addr2, 0, 2),
                ex.submit(fresh.configure, addr2, 1, 2),
            ]:
                f.result()
        pair = [cols[0], fresh]
        outs = _run_all(
            pair,
            lambda r, c: c.plan_allreduce(
                {"g": np.ones(1 << 18, np.float32)}
            ).wait(),
        )
        for o in outs:
            np.testing.assert_array_equal(o["g"], np.full(1 << 18, 2.0))
        for c in pair:
            c.shutdown()

    def test_unsupported_dtype_falls_back_to_legacy(self, store):
        # f16 is not a native ring dtype: the plan path must serve the
        # tree through the legacy path with identical semantics (and
        # remember the verdict instead of re-attempting the build).
        cols = _make_ring(store, 2, "pfall")
        trees = [
            {"h": np.ones(257, np.float16) * (r + 1)} for r in range(2)
        ]
        out = _run_all(
            cols, lambda r, c: c.plan_allreduce(trees[r]).wait()
        )
        np.testing.assert_array_equal(
            np.asarray(out[0]["h"], np.float32), np.full(257, 3.0)
        )
        key = next(iter(cols[0]._plans))
        assert cols[0]._plans[key] is None  # cached "unsupported" verdict
        with pytest.raises(ValueError, match="q8"):
            cols[0].plan_allreduce(trees[0], wire="q8").wait()
        for c in cols:
            c.shutdown()

    def test_world_size_one_identity_and_divisor(self):
        col = HostCollectives()
        col.configure("ignored:0/q", 0, 1)
        tree = {"g": np.arange(10, dtype=np.float32)}
        out = col.plan_allreduce(tree, ReduceOp.SUM, divisor=2.0).wait()
        np.testing.assert_array_equal(out["g"], tree["g"] / 2.0)
        # AVG + explicit divisor is ambiguous and must raise loudly (the
        # legacy path's contract) — never silently replace the caller's
        # participant divisor with world_size
        with pytest.raises(ValueError, match="divisor"):
            col.plan_allreduce(tree, ReduceOp.AVG, divisor=2.0)
        col.shutdown()

    def test_dummy_plan_allreduce(self):
        d = DummyCollectives(world_size=4)
        out = d.plan_allreduce({"g": np.full(3, 8.0)}, ReduceOp.AVG).wait()
        np.testing.assert_array_equal(out["g"], np.full(3, 2.0))


class TestManagedPlanDiscipline:
    """Manager.plan_allreduce's error contract: failure -> None + latch ->
    commit vote discards (the plan's persistent buffers mean there is no
    meaningful 'as contributed' tree to fall back to)."""

    def _manager(self, collectives):
        from torchft_tpu import Lighthouse
        from torchft_tpu.manager import Manager

        lighthouse = Lighthouse(
            bind="[::]:0", min_replicas=1, join_timeout_ms=200,
            quorum_tick_ms=50, heartbeat_timeout_ms=2000,
        )
        store = Store()
        manager = Manager(
            collectives=collectives,
            load_state_dict=lambda s: None,
            state_dict=lambda: {},
            min_replica_size=1,
            rank=0,
            world_size=1,
            use_async_quorum=False,
            timeout=timedelta(seconds=10),
            store_addr=store.address(),
            lighthouse_addr=lighthouse.address(),
            replica_id="plan_test",
        )
        return manager, store, lighthouse

    def test_happy_path_averages(self):
        manager, store, lighthouse = self._manager(
            DummyCollectives(world_size=1)
        )
        try:
            manager.start_quorum()
            out = manager.plan_allreduce({"g": np.full(4, 6.0)}).wait()
            np.testing.assert_array_equal(out["g"], np.full(4, 6.0))
            assert manager.should_commit()
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()

    def test_failure_resolves_none_and_discards_step(self):
        class FailingPlans(DummyCollectives):
            def plan_allreduce(self, tree, op=ReduceOp.SUM, divisor=None,
                               wire=None):
                raise RuntimeError("ring down")

        manager, store, lighthouse = self._manager(FailingPlans(world_size=1))
        try:
            manager.start_quorum()
            out = manager.plan_allreduce({"g": np.ones(4)}).wait()
            assert out is None  # no 'as contributed' fallback exists
            assert manager.errored() is not None
            assert not manager.should_commit()
            # next step starts clean and can commit again
            manager.start_quorum()
            assert manager.errored() is None
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()
