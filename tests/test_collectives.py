"""Collectives layer tests.

Mirrors the reference's process-group test strategy
(reference torchft/process_group_test.py): multi-rank collectives run as
threads in one process against a real Store, the Dummy fake is exercised
directly, and reconfiguration / peer-death behavior is asserted.
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
    Work,
)


@pytest.fixture
def store():
    s = Store()
    yield s
    s.shutdown()


def _make_ring(store, world_size, prefix="q0", timeout=timedelta(seconds=10)):
    """Configure world_size HostCollectives concurrently; returns the list."""
    cols = [HostCollectives(timeout=timeout) for _ in range(world_size)]
    addr = f"{store.address()}/{prefix}"
    with ThreadPoolExecutor(max_workers=world_size) as ex:
        futs = [
            ex.submit(cols[r].configure, addr, r, world_size)
            for r in range(world_size)
        ]
        for f in futs:
            f.result()
    return cols


def _run_all(cols, fn):
    """Runs fn(rank, collectives) on every rank concurrently."""
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


class TestHostCollectives:
    @pytest.mark.parametrize("world_size", [2, 3, 5])
    def test_allreduce_sum(self, store, world_size):
        cols = _make_ring(store, world_size)
        data = [
            np.arange(17, dtype=np.float32) * (r + 1) for r in range(world_size)
        ]
        expect = sum(data)
        results = _run_all(cols, lambda r, c: c.allreduce(data[r]).wait())
        for out in results:
            np.testing.assert_array_equal(out, expect)
        for c in cols:
            c.shutdown()

    def test_allreduce_bitwise_identical_across_ranks(self, store):
        # The determinism oracle: reduction order is identical on every rank
        # (reference manager_integ_test.py:279-282 demands bit-identical
        # state after recovery).
        cols = _make_ring(store, 4)
        rng = np.random.default_rng(0)
        data = [rng.standard_normal(1001).astype(np.float32) for _ in range(4)]
        results = _run_all(cols, lambda r, c: c.allreduce(data[r]).wait())
        for out in results[1:]:
            assert out.tobytes() == results[0].tobytes()
        for c in cols:
            c.shutdown()

    def test_allreduce_avg_and_ops(self, store):
        cols = _make_ring(store, 2)
        data = [np.array([2.0, 8.0], np.float32), np.array([4.0, 2.0], np.float32)]
        avg = _run_all(cols, lambda r, c: c.allreduce(data[r], ReduceOp.AVG).wait())
        np.testing.assert_array_equal(avg[0], [3.0, 5.0])
        mx = _run_all(cols, lambda r, c: c.allreduce(data[r], ReduceOp.MAX).wait())
        np.testing.assert_array_equal(mx[0], [4.0, 8.0])
        mn = _run_all(cols, lambda r, c: c.allreduce(data[r], ReduceOp.MIN).wait())
        np.testing.assert_array_equal(mn[0], [2.0, 2.0])
        prod = _run_all(
            cols, lambda r, c: c.allreduce(data[r], ReduceOp.PRODUCT).wait()
        )
        np.testing.assert_array_equal(prod[0], [8.0, 16.0])
        for c in cols:
            c.shutdown()

    def test_allreduce_pytree_mixed_dtypes(self, store):
        cols = _make_ring(store, 2)
        trees = [
            {
                "w": np.ones((3, 4), np.float32) * (r + 1),
                "b": np.ones(5, np.float64) * (r + 1),
                "n": np.array([r + 1], np.int64),
            }
            for r in range(2)
        ]
        results = _run_all(cols, lambda r, c: c.allreduce(trees[r]).wait())
        for out in results:
            np.testing.assert_array_equal(out["w"], np.ones((3, 4)) * 3)
            np.testing.assert_array_equal(out["b"], np.ones(5) * 3)
            np.testing.assert_array_equal(out["n"], [3])
            assert out["w"].dtype == np.float32
            assert out["b"].dtype == np.float64
            assert out["n"].dtype == np.int64
        for c in cols:
            c.shutdown()

    def test_allreduce_bfloat16_native_wire(self, store):
        # bf16 ships natively (2 bytes on the wire — half the DCN bytes of
        # an f32 upcast); reduction math is f32 per hop, rounded to nearest
        # even back to bf16. These values are bf16-exact, so the sum is too.
        import ml_dtypes

        cols = _make_ring(store, 3)
        data = [
            np.full(7, 0.125 * (r + 1), dtype=ml_dtypes.bfloat16) for r in range(3)
        ]
        results = _run_all(cols, lambda r, c: c.allreduce(data[r]).wait())
        for out in results:
            assert out.dtype == ml_dtypes.bfloat16
            np.testing.assert_array_equal(
                out.astype(np.float32), np.full(7, 0.75, np.float32)
            )
        for c in cols:
            c.shutdown()

    def test_allreduce_bfloat16_rounds_per_hop(self, store):
        # Inexact sums round per ring hop (the documented bf16 tradeoff);
        # results remain bit-identical across ranks.
        import ml_dtypes

        cols = _make_ring(store, 2)
        data = [
            np.full(5, 1.0 + r * 0.00390625, dtype=ml_dtypes.bfloat16)
            for r in range(2)
        ]
        results = _run_all(cols, lambda r, c: c.allreduce(data[r]).wait())
        expected = (
            data[0].astype(np.float32) + data[1].astype(np.float32)
        ).astype(ml_dtypes.bfloat16)
        for out in results:
            assert out.dtype == ml_dtypes.bfloat16
            np.testing.assert_array_equal(out, expected)
        for c in cols:
            c.shutdown()

    def test_allreduce_jax_arrays(self, store):
        import jax.numpy as jnp

        cols = _make_ring(store, 2)
        data = [jnp.arange(6, dtype=jnp.float32) * (r + 1) for r in range(2)]
        results = _run_all(cols, lambda r, c: c.allreduce(data[r]).wait())
        import jax

        for out in results:
            assert isinstance(out, jax.Array)
            np.testing.assert_array_equal(
                np.asarray(out), np.arange(6, dtype=np.float32) * 3
            )
        for c in cols:
            c.shutdown()

    def test_allreduce_pipelined_chunks_match_single_shot(self, store):
        # The overlap pipeline (chunked d2h/ring/h2d) must be bit-identical
        # to the unchunked path and to the analytic expectation.
        import jax.numpy as jnp

        cols = [
            HostCollectives(
                timeout=timedelta(seconds=10),
                pipeline_chunks=4,
                pipeline_min_bytes=0,  # force the pipeline even when tiny
            )
            for _ in range(2)
        ]
        addr = f"{store.address()}/q0"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
            ]:
                f.result()
        rng = np.random.default_rng(5)
        base = rng.standard_normal(10_007).astype(np.float32)  # odd size
        data = [
            {"w": jnp.asarray(base * (r + 1)), "b": jnp.asarray(base[:33])}
            for r in range(2)
        ]
        results = _run_all(
            cols, lambda r, c: c.allreduce(data[r], ReduceOp.AVG).wait()
        )
        expect_w = (base * 1 + base * 2) / 2
        for out in results:
            np.testing.assert_array_equal(np.asarray(out["w"]), expect_w)
            np.testing.assert_array_equal(np.asarray(out["b"]), base[:33])
        assert np.asarray(results[0]["w"]).tobytes() == np.asarray(
            results[1]["w"]
        ).tobytes()
        for c in cols:
            c.shutdown()

    def test_mismatched_pipeline_config_fails_fast(self, store):
        # The chunk schedule is part of the wire contract; disagreeing
        # members must error at configure, not silently desync gradients.
        cols = [
            HostCollectives(
                timeout=timedelta(seconds=10),
                connect_timeout=timedelta(seconds=5),  # rank 0's rendezvous
                pipeline_chunks=chunks,                # times out solo
            )
            for chunks in (4, 8)
        ]
        addr = f"{store.address()}/q0"
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
            ]
            with pytest.raises(RuntimeError, match="pipeline config mismatch"):
                futs[1].result()
        for c in cols:
            c.shutdown()

    def test_allgather(self, store):
        cols = _make_ring(store, 3)
        results = _run_all(
            cols,
            lambda r, c: c.allgather(
                {"x": np.full(4, r, np.float32), "y": np.array([r], np.int64)}
            ).wait(),
        )
        for out in results:
            assert len(out) == 3
            for r, tree in enumerate(out):
                np.testing.assert_array_equal(tree["x"], np.full(4, r))
                np.testing.assert_array_equal(tree["y"], [r])
        for c in cols:
            c.shutdown()

    def test_allreduce_q8_quantized_ring(self, store):
        # wire="q8": int8 chunks + per-chunk scales, dequant-accumulated
        # per hop; bytes constant in world size (round-3 verdict #9).
        # Results must be (a) within int8 quantization error of the exact
        # sum and (b) BIT-IDENTICAL across ranks (phase-2 circulates
        # owner-quantized codes verbatim).
        import jax.numpy as jnp

        cols = _make_ring(store, 3)
        rng = np.random.default_rng(7)
        base = {
            "w": rng.standard_normal((300,)).astype(np.float32),
            "b": rng.standard_normal((5, 7)).astype(np.float32) * 10.0,
        }

        def op(r, c):
            tree = {
                "w": jnp.asarray(base["w"] * (r + 1)),
                "b": jnp.asarray(base["b"] * (r + 1)),
            }
            return c.allreduce(tree, ReduceOp.AVG, wire="q8").wait()

        results = _run_all(cols, op)
        exact = {k: v * (1 + 2 + 3) / 3 for k, v in base.items()}
        for out in results:
            for k in base:
                got = np.asarray(out[k])
                assert got.dtype == np.float32
                # error bound: per-hop requantization at absmax/127 per
                # chunk; 3 ranks -> a few quantization steps of slack
                tol = 6 * np.abs(exact[k]).max() / 127
                np.testing.assert_allclose(got, exact[k], atol=tol)
        for a, b in zip(results[0:1] * 2, results[1:]):
            for k in base:
                np.testing.assert_array_equal(
                    np.asarray(a[k]), np.asarray(b[k])
                )
        # SUM with divisor composes; MIN/MAX must be rejected
        with pytest.raises(ValueError, match="SUM/AVG"):
            cols[0].allreduce(base, ReduceOp.MAX, wire="q8")
        for c in cols:
            c.shutdown()

    def test_allreduce_q8_nonfinite_poisons_all_members(self, store):
        # A NaN/Inf leaf entering the quantized wire must come out NaN on
        # EVERY member: q8_encode ships a NaN scale for a chunk holding any
        # non-finite value (native/src/collectives.cc), because clamping to
        # int8 range would otherwise encode a diverged model as healthy
        # finite codes and hide the blow-up from every peer.
        import jax.numpy as jnp

        cols = _make_ring(store, 3)
        rng = np.random.default_rng(11)
        base = rng.standard_normal(400).astype(np.float32)

        def op(r, c):
            arr = base * (r + 1)
            if r == 0:
                arr = arr.copy()
                arr[7] = np.nan    # lands in ring chunk 0
                arr[250] = np.inf  # lands in a different ring chunk
            return c.allreduce(
                {"w": jnp.asarray(arr)}, ReduceOp.SUM, wire="q8"
            ).wait()

        results = _run_all(cols, op)
        for out in results:
            got = np.asarray(out["w"])
            assert np.isnan(got[7]), "NaN leaf must poison its chunk"
            assert np.isnan(got[250]), "Inf leaf must poison its chunk"
        # poisoned results stay bit-identical across ranks (NaN included)
        for other in results[1:]:
            assert np.asarray(results[0]["w"]).tobytes() == np.asarray(
                other["w"]
            ).tobytes()
        for c in cols:
            c.shutdown()

    def test_op_schedule_pipeline_bit_identical_across_buckets(self, store):
        # The CROSS-BUFFER op-schedule pipeline (bucket i+1's d2h streams
        # while bucket i rides the ring) must be bit-identical to the
        # non-pipelined path for a mixed-dtype tree, and must record the
        # per-bucket phase breakdown in pop_op_stats.
        import jax.numpy as jnp

        import ml_dtypes

        rng = np.random.default_rng(9)
        base_f32 = rng.standard_normal(5003).astype(np.float32)
        # bf16-exact values so the analytic cross-path comparison is exact
        base_bf16 = (rng.integers(-16, 16, 1001) * 0.125).astype(
            ml_dtypes.bfloat16
        )
        base_i32 = rng.integers(-100, 100, 777, dtype=np.int32)

        def tree(r):
            return {
                "w": jnp.asarray(base_f32 * (r + 1)),
                "b": jnp.asarray(base_bf16) * (r + 1),
                "n": jnp.asarray(base_i32 * (r + 1)),
            }

        outs = {}
        for chunks in (1, 4):
            cols = [
                HostCollectives(
                    timeout=timedelta(seconds=10),
                    pipeline_chunks=chunks,
                    pipeline_min_bytes=0,  # force the pipeline even when tiny
                )
                for _ in range(2)
            ]
            addr = f"{store.address()}/sched{chunks}"
            with ThreadPoolExecutor(max_workers=2) as ex:
                for f in [
                    ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
                ]:
                    f.result()
            results = _run_all(cols, lambda r, c: c.allreduce(tree(r)).wait())
            for k in ("w", "b", "n"):
                assert np.asarray(results[0][k]).tobytes() == np.asarray(
                    results[1][k]
                ).tobytes()
            if chunks == 4:
                stats = [
                    st for st in cols[0].pop_op_stats()
                    if st["op"] == "allreduce"
                ]
                assert stats, "device-packed allreduce must record op stats"
                buckets = stats[-1]["buckets"]
                assert len(buckets) == 3  # one per dtype bucket (f32/f64/i32)
                assert stats[-1]["chunks"] == 3 * 4  # every bucket chunked
            outs[chunks] = results[0]
            for c in cols:
                c.shutdown()
        for k in ("w", "b", "n"):
            assert np.asarray(outs[1][k]).tobytes() == np.asarray(
                outs[4][k]
            ).tobytes()

    def test_abort_under_striping_wakes_all_stripes(self, store):
        # Killing a peer mid-op with stripes > 1 must wake EVERY stripe
        # thread (one surfaced error, within seconds, not one timeout per
        # stripe), and the instance must reconfigure cleanly afterward.
        cols = [
            HostCollectives(timeout=timedelta(seconds=30), stripes=4)
            for _ in range(2)
        ]
        addr = f"{store.address()}/striped"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
            ]:
                f.result()
        big = np.ones(1 << 20, np.float32)  # 4 MB -> 4 effective stripes
        w = cols[0].allreduce(big.copy())
        threading.Timer(0.3, cols[1].shutdown).start()  # peer dies mid-op
        start = time.monotonic()
        with pytest.raises(RuntimeError):
            w.wait(timeout=timedelta(seconds=20))
        elapsed = time.monotonic() - start
        assert elapsed < 5.0, (
            f"striped abort took {elapsed:.1f}s — a stripe thread sat out "
            "its own timeout instead of being woken"
        )
        # A fresh configure against a new partner restores service, and the
        # op after it runs all 4 stripes (per-stripe timings prove it).
        fresh = HostCollectives(timeout=timedelta(seconds=30), stripes=4)
        addr2 = f"{store.address()}/striped2"
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(cols[0].configure, addr2, 0, 2),
                ex.submit(fresh.configure, addr2, 1, 2),
            ]
            for f in futs:
                f.result()
        pair = [cols[0], fresh]
        outs = _run_all(
            pair,
            lambda r, c: c.allreduce(np.ones(1 << 18, np.float32)).wait(),
        )
        for o in outs:
            np.testing.assert_array_equal(o, np.full(1 << 18, 2.0))
        assert len(cols[0]._last_stripe_seconds()) == 4
        for c in pair:
            c.shutdown()

    def test_allgather_device_packed_jax_leaves(self, store):
        # All-jax-leaf trees take the device-packed path (one transfer per
        # exact dtype, byte-preserving): without it a quantized {q, scale}
        # payload costs one device round-trip PER LEAF. int8 must NOT be
        # upcast on the wire.
        import jax.numpy as jnp

        cols = _make_ring(store, 3)

        def op(r, c):
            payload = {
                "q": {
                    "a": jnp.full((6,), r - 1, jnp.int8),
                    "b": jnp.full((2, 3), 2 * r, jnp.int8),
                },
                "scale": {
                    "a": jnp.float32(0.5 + r),
                    "b": jnp.float32(1.5 * r),
                },
                "extra_bf16": jnp.full((4,), r, jnp.bfloat16),
            }
            return c.allgather(payload).wait()

        results = _run_all(cols, op)
        for out in results:
            assert len(out) == 3
            for r, tree in enumerate(out):
                assert tree["q"]["a"].dtype == jnp.int8
                np.testing.assert_array_equal(
                    np.asarray(tree["q"]["a"]), np.full((6,), r - 1)
                )
                np.testing.assert_array_equal(
                    np.asarray(tree["q"]["b"]), np.full((2, 3), 2 * r)
                )
                np.testing.assert_allclose(
                    float(tree["scale"]["a"]), 0.5 + r
                )
                np.testing.assert_allclose(
                    float(tree["scale"]["b"]), 1.5 * r
                )
                assert tree["extra_bf16"].dtype == jnp.bfloat16
                np.testing.assert_array_equal(
                    np.asarray(tree["extra_bf16"].astype(jnp.float32)),
                    np.full((4,), r, np.float32),
                )
        for c in cols:
            c.shutdown()

    def test_broadcast(self, store):
        cols = _make_ring(store, 3)
        data = [np.full(8, r, np.float32) for r in range(3)]
        results = _run_all(cols, lambda r, c: c.broadcast(data[r], root=1).wait())
        for out in results:
            np.testing.assert_array_equal(out, np.full(8, 1.0))
        for c in cols:
            c.shutdown()

    def test_barrier(self, store):
        cols = _make_ring(store, 3)
        results = _run_all(cols, lambda r, c: c.barrier().wait())
        assert results == [None, None, None]
        for c in cols:
            c.shutdown()

    def test_world_size_one_is_local(self):
        c = HostCollectives()
        c.configure("ignored:0/q", 0, 1)
        out = c.allreduce(np.arange(3, dtype=np.float32)).wait()
        np.testing.assert_array_equal(out, np.arange(3))
        assert c.allgather(np.ones(2))._future.result() is not None
        c.shutdown()

    def test_reconfigure_to_new_membership(self, store):
        # Quorum change: 3 ranks -> 2 ranks under a new prefix (the
        # per-quorum namespacing of reference manager.py:470-477).
        cols = _make_ring(store, 3, prefix="q1")
        results = _run_all(
            cols, lambda r, c: c.allreduce(np.ones(4, np.float32)).wait()
        )
        np.testing.assert_array_equal(results[0], np.full(4, 3.0))

        survivors = cols[:2]
        addr = f"{store.address()}/q2"
        _run_all(survivors, lambda r, c: c.configure(addr, r, 2))
        results = _run_all(
            survivors, lambda r, c: c.allreduce(np.ones(4, np.float32)).wait()
        )
        np.testing.assert_array_equal(results[0], np.full(4, 2.0))
        for c in cols:
            c.shutdown()

    def test_peer_death_unblocks_with_error(self, store):
        # A dead peer must surface as an error on survivors, not a hang —
        # the property the reference's Baby-process isolation provides
        # (reference process_group.py:303-307).
        cols = _make_ring(store, 2, timeout=timedelta(seconds=30))
        cols[1].shutdown()  # rank 1 dies
        with pytest.raises(RuntimeError):
            cols[0].allreduce(np.ones(1024, np.float32)).wait()
        cols[0].shutdown()

    def test_ring_failure_propagates_to_all_members(self, store):
        # One member's death must fail EVERY member's in-flight op within
        # milliseconds (each failing member shuts its ring sockets down,
        # sweeping EOF around the ring) — not just its direct neighbors.
        # Otherwise non-adjacent members block on the full op timeout and a
        # majority of survivors can never reach the next quorum to heal.
        cols = _make_ring(store, 4, timeout=timedelta(seconds=30))
        big = np.ones(1 << 20, np.float32)
        works = [cols[r].allreduce(big.copy()) for r in range(3)]
        threading.Timer(0.3, cols[3].shutdown).start()  # rank 3 dies mid-op
        start = time.monotonic()
        for w in works:
            with pytest.raises(RuntimeError):
                w.wait(timeout=timedelta(seconds=20))
        elapsed = time.monotonic() - start
        assert elapsed < 5.0, f"failure took {elapsed:.1f}s to propagate"
        # The ring is down until reconfigured: ops fail fast, no hang.
        with pytest.raises(RuntimeError):
            cols[0].allreduce(np.ones(4, np.float32)).wait()
        # A fresh configure (new prefix, as a new quorum provides) restores
        # service for the survivors.
        addr = f"{store.address()}/q_rebuilt"
        with ThreadPoolExecutor(max_workers=3) as ex:
            futs = [
                ex.submit(cols[r].configure, addr, r, 3) for r in range(3)
            ]
            for f in futs:
                f.result()
        out = _run_all(
            cols[:3], lambda r, c: c.allreduce(np.ones(8, np.float32)).wait()
        )
        for o in out:
            np.testing.assert_array_equal(o, np.full(8, 3.0))
        for c in cols[:3]:
            c.shutdown()

    def test_abort_unblocks_inflight_op(self, store):
        cols = _make_ring(store, 2, timeout=timedelta(seconds=30))
        # rank 1 never participates; rank 0's allreduce blocks until abort.
        w = cols[0].allreduce(np.ones(4, np.float32))
        threading.Timer(0.2, cols[0].abort).start()
        with pytest.raises(RuntimeError):
            w.wait(timeout=timedelta(seconds=10))
        for c in cols:
            c.shutdown()

    def test_op_timeout(self, store):
        cols = _make_ring(store, 2, timeout=timedelta(milliseconds=200))
        # rank 1 never joins the op: rank 0 times out.
        with pytest.raises(TimeoutError):
            cols[0].allreduce(np.ones(4, np.float32)).wait()
        for c in cols:
            c.shutdown()

    def test_ops_execute_in_submission_order(self, store):
        cols = _make_ring(store, 2)
        works = [[], []]

        def submit(r, c):
            for i in range(5):
                works[r].append(c.allreduce(np.full(3, float(i), np.float32)))
            return [w.wait() for w in works[r]]

        results = _run_all(cols, submit)
        for r in range(2):
            for i, out in enumerate(results[r]):
                np.testing.assert_array_equal(out, np.full(3, 2.0 * i))
        for c in cols:
            c.shutdown()


class TestWork:
    def test_then_chains_and_propagates_errors(self):
        d = DummyCollectives()
        w = d.allreduce(np.ones(2)).then(lambda t: t * 2)
        np.testing.assert_array_equal(w.wait(), np.full(2, 2.0))

        from concurrent.futures import Future

        f = Future()
        f.set_exception(ValueError("boom"))
        w2 = Work(f).then(lambda t: t)
        assert isinstance(w2.exception(), ValueError)


class TestDummyCollectives:
    def test_semantics(self):
        d = DummyCollectives(rank=1, world_size=3)
        assert d.size() == 3 and d.rank() == 1
        t = {"a": np.ones(2)}
        out = d.allreduce(t).wait()
        np.testing.assert_array_equal(out["a"], t["a"])
        assert len(d.allgather(t).wait()) == 3
        d.configure("x:0/p", 0, 2)
        assert d.configure_count == 1 and d.size() == 2


class TestOpMismatchDetection:
    """Size/dtype-mismatched collective ops must error immediately, not
    deadlock with the smaller member done and the larger one blocked on a
    full kernel buffer (the failure mode behind the bench's wedged diloco
    sync: a 6-layer tree reduced against a 2-layer zeros tree)."""

    def test_mismatched_sizes_error_fast(self, store):
        cols = _make_ring(store, 2, prefix="mismatch")
        with ThreadPoolExecutor(max_workers=2) as ex:
            f0 = ex.submit(
                lambda: cols[0].allreduce(np.ones(1 << 20, np.float32)).wait()
            )
            f1 = ex.submit(
                lambda: cols[1].allreduce(np.ones(1 << 10, np.float32)).wait()
            )
            start = time.monotonic()
            for f in (f0, f1):
                with pytest.raises(RuntimeError, match="mismatch|desync|ring"):
                    f.result(timeout=15)
            assert time.monotonic() - start < 10
        for c in cols:
            c.shutdown()

    def test_mismatched_dtype_error_fast(self, store):
        import jax.numpy as jnp

        cols = _make_ring(store, 2, prefix="mismatch_dt")
        with ThreadPoolExecutor(max_workers=2) as ex:
            f0 = ex.submit(
                lambda: cols[0].allreduce(np.ones(256, np.float32)).wait()
            )
            f1 = ex.submit(
                lambda: cols[1]
                .allreduce(jnp.ones(256, jnp.bfloat16))
                .wait()
            )
            for f in (f0, f1):
                with pytest.raises(RuntimeError, match="mismatch|desync|ring"):
                    f.result(timeout=15)
        for c in cols:
            c.shutdown()


class TestShardedCollectives:
    """First-class reduce_scatter / allgather_into: the decomposed pair
    must be bit-identical to the fused allreduce (the determinism oracle
    extended to the sharded-weight-update schedule), the shard layout must
    tile the payload exactly, and abort must wake every stripe thread."""

    def _make_ring(self, store, world_size, prefix, stripes=1):
        cols = [
            HostCollectives(timeout=timedelta(seconds=15), stripes=stripes)
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

    def _trees(self, world_size, dtype=np.float32):
        # Uneven leaf sizes: the flat count is NOT divisible by 2, 3, or 5
        # (ring chunks and stripe sub-ranges both land on uneven
        # boundaries, exercising the near-equal-chunk padding arithmetic).
        rng = np.random.RandomState(7)
        base = {
            "a": rng.randn(4099).astype(dtype),
            "b": rng.randn(13, 7).astype(dtype),
        }
        return [
            {k: (v * (r + 1)).copy() for k, v in base.items()}
            for r in range(world_size)
        ]

    @pytest.mark.parametrize("world_size", [2, 3, 5])
    @pytest.mark.parametrize("stripes", [1, 4])
    def test_bit_identical_to_fused_f32(self, store, world_size, stripes):
        cols = self._make_ring(
            store, world_size, f"shf32_{world_size}_{stripes}", stripes
        )
        trees = self._trees(world_size)
        fused = _run_all(
            cols, lambda r, c: c.allreduce(trees[r], ReduceOp.SUM).wait()
        )

        def decomposed(r, c):
            sh = c.reduce_scatter(trees[r], ReduceOp.SUM).wait()
            return c.allgather_into(sh).wait()

        dec = _run_all(cols, decomposed)
        for f, d in zip(fused, dec):
            for k in f:
                np.testing.assert_array_equal(np.asarray(f[k]), np.asarray(d[k]))
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("stripes", [1, 4])
    def test_bit_identical_to_fused_bf16(self, store, stripes):
        import ml_dtypes

        bf16 = np.dtype(ml_dtypes.bfloat16)
        cols = self._make_ring(store, 3, f"shbf_{stripes}", stripes)
        trees = self._trees(3, dtype=bf16)
        fused = _run_all(
            cols, lambda r, c: c.allreduce(trees[r], ReduceOp.SUM).wait()
        )

        def decomposed(r, c):
            sh = c.reduce_scatter(trees[r], ReduceOp.SUM).wait()
            return c.allgather_into(sh).wait()

        dec = _run_all(cols, decomposed)
        for f, d in zip(fused, dec):
            for k in f:
                np.testing.assert_array_equal(
                    np.asarray(f[k]).view(np.uint16),
                    np.asarray(d[k]).view(np.uint16),
                )
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("world_size", [2, 3])
    @pytest.mark.parametrize("stripes", [1, 4])
    def test_bit_identical_to_fused_q8(self, store, world_size, stripes):
        # grid_shard=True replays the fused op's phase-2 owner
        # quantize+decode on the owned shard, so RS+AG must reproduce the
        # fused q8 allreduce bit-for-bit, stripes or not.
        cols = self._make_ring(
            store, world_size, f"shq8_{world_size}_{stripes}", stripes
        )
        trees = self._trees(world_size)
        fused = _run_all(
            cols,
            lambda r, c: c.allreduce(trees[r], ReduceOp.SUM, wire="q8").wait(),
        )

        def decomposed(r, c):
            sh = c.reduce_scatter(
                trees[r], ReduceOp.SUM, wire="q8", grid_shard=True
            ).wait()
            return c.allgather_into(sh).wait()

        dec = _run_all(cols, decomposed)
        for f, d in zip(fused, dec):
            for k in f:
                np.testing.assert_array_equal(np.asarray(f[k]), np.asarray(d[k]))
        for c in cols:
            c.shutdown()

    def test_reduce_scatter_q8_nonfinite_poisons_shard(self, store):
        # The split-op mirror of the fused q8 poisoning contract
        # (ADVICE #4): a NaN/Inf leaf entering the quantized
        # reduce-scatter wire must poison the reduced shard on every
        # member — q8_encode ships a NaN scale for any non-finite chunk,
        # and clamping instead would hide a diverged model behind
        # healthy-looking int8 codes. Wire-crossing chunks decode to NaN
        # (NaN scale); the POISONING member's own chunk keeps its raw
        # Inf/NaN — it accumulates in f32 and never re-rides the lossy
        # wire. Either way the divergence must surface as non-finite.
        cols = self._make_ring(store, 3, "q8poison")
        rng = np.random.default_rng(13)
        base = rng.standard_normal(600).astype(np.float32)

        def op(r, c):
            arr = base * (r + 1)
            if r == 1:
                arr = arr.copy()
                arr[5] = np.nan
                arr[400] = np.inf
            return c.reduce_scatter(
                {"w": arr}, ReduceOp.SUM, wire="q8"
            ).wait()

        shards = _run_all(cols, op)
        poisoned = [False] * 3
        for r, sh in enumerate(shards):
            name = next(iter(sh.values))
            got = np.asarray(sh.values[name])
            # reassemble this rank's global positions and check the ones
            # covering the poisoned elements
            for (start, ln), off in zip(
                sh.ranges[name],
                np.cumsum([0] + [l for _, l in sh.ranges[name]][:-1]),
            ):
                seg = got[off:off + ln]
                for idx in (5, 400):
                    if start <= idx < start + ln:
                        assert not np.isfinite(seg[idx - start]), (
                            f"rank {r}: poisoned element {idx} decoded "
                            "finite from the q8 reduce-scatter wire"
                        )
                        poisoned[r] = True
        assert any(poisoned), "test bug: no shard covered a poisoned index"
        for c in cols:
            c.shutdown()

    def test_ungridded_q8_shard_beats_fused_loss(self, store):
        # Production mode (grid_shard=False): the owned shard skips the
        # lossy phase-2 quantization entirely, so its values must match
        # the EXACT f32 reduction — strictly better than the fused op.
        cols = self._make_ring(store, 2, "shq8exact")
        trees = self._trees(2)
        exact = _run_all(
            cols, lambda r, c: c.allreduce(trees[r], ReduceOp.SUM).wait()
        )

        def rs(r, c):
            return c.reduce_scatter(trees[r], ReduceOp.SUM, wire="q8").wait()

        shards = _run_all(cols, rs)
        for r, sh in enumerate(shards):
            name = next(iter(sh.values))
            flat_exact = np.concatenate(
                [np.asarray(exact[r][k]).ravel() for k in ("a", "b")]
            )
            got = np.asarray(sh.values[name])
            want = np.concatenate(
                [flat_exact[s: s + l] for s, l in sh.ranges[name]]
            )
            # q8 wire is lossy in transit (per-hop requant of partials) but
            # the owned chunk accumulates in f32: error stays at the int8
            # class of each chunk, far under 1% of the dynamic range here
            np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("world_size", [2, 3, 5])
    @pytest.mark.parametrize("stripes", [1, 4])
    def test_shard_ranges_tile_payload(self, store, world_size, stripes):
        # The per-rank owned ranges must partition [0, count) exactly:
        # disjoint, complete, and consistent across uneven world sizes and
        # stripe counts (the padding arithmetic of near-equal chunks).
        cols = self._make_ring(
            store, world_size, f"tile_{world_size}_{stripes}", stripes
        )
        count, esize = 4099 + 13 * 7, 4
        from torchft_tpu.collectives import _effective_stripes

        eff = _effective_stripes(count * esize, stripes)
        cover = np.zeros(count, np.int32)
        for r in range(world_size):
            for s, ln in cols[r]._shard_ranges(count, esize, eff):
                cover[s: s + ln] += 1
        np.testing.assert_array_equal(cover, np.ones(count, np.int32))
        for c in cols:
            c.shutdown()

    def test_bf16_param_wire_bit_identical_across_ranks(self, store):
        # The sharded outer sync's parameter leg: f32 shards allgathered
        # over a bf16 wire. Every member (shard owners included) must end
        # with the identical decoded bf16 words.
        cols = self._make_ring(store, 3, "bfwire", stripes=2)
        trees = self._trees(3)

        def sync(r, c):
            sh = c.reduce_scatter(trees[r], ReduceOp.AVG).wait()
            return c.allgather_into(sh, wire="bf16").wait()

        outs = _run_all(cols, sync)
        for o in outs[1:]:
            for k in o:
                np.testing.assert_array_equal(
                    np.asarray(outs[0][k]), np.asarray(o[k])
                )
        # and the values are the bf16 rounding of the exact average
        import ml_dtypes

        exact = _run_all(
            cols, lambda r, c: c.allreduce(trees[r], ReduceOp.AVG).wait()
        )
        for k in exact[0]:
            want = (
                np.asarray(exact[0][k])
                .astype(ml_dtypes.bfloat16)
                .astype(np.float32)
            )
            np.testing.assert_allclose(
                np.asarray(outs[0][k]), want, rtol=1e-6, atol=1e-6
            )
        for c in cols:
            c.shutdown()

    def test_world_size_one_roundtrip(self):
        col = HostCollectives()
        col.configure("ignored", 0, 1)
        tree = {"w": np.arange(10, dtype=np.float32)}
        sh = col.reduce_scatter(tree, ReduceOp.AVG).wait()
        name = next(iter(sh.values))
        assert sh.counts[name] == 10 and sh.ranges[name] == [(0, 10)]
        out = col.allgather_into(sh).wait()
        np.testing.assert_array_equal(out["w"], tree["w"])
        col.shutdown()

    def test_abort_under_reduce_scatter_wakes_all_stripes(self, store):
        # Mirror of test_abort_under_striping_wakes_all_stripes for the
        # split op: peer death mid-reduce-scatter must wake every stripe
        # thread promptly, and a fresh configure restores service.
        cols = [
            HostCollectives(timeout=timedelta(seconds=30), stripes=4)
            for _ in range(2)
        ]
        addr = f"{store.address()}/rs_striped"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, 2) for r in range(2)
            ]:
                f.result()
        big = {"g": np.ones(1 << 20, np.float32)}  # 4 MB -> 4 stripes
        w = cols[0].reduce_scatter(big)
        threading.Timer(0.3, cols[1].shutdown).start()
        start = time.monotonic()
        with pytest.raises(RuntimeError):
            w.wait(timeout=timedelta(seconds=20))
        elapsed = time.monotonic() - start
        assert elapsed < 5.0, (
            f"striped reduce_scatter abort took {elapsed:.1f}s — a stripe "
            "thread sat out its own timeout instead of being woken"
        )
        fresh = HostCollectives(timeout=timedelta(seconds=30), stripes=4)
        addr2 = f"{store.address()}/rs_striped2"
        with ThreadPoolExecutor(max_workers=2) as ex:
            for f in [
                ex.submit(cols[0].configure, addr2, 0, 2),
                ex.submit(fresh.configure, addr2, 1, 2),
            ]:
                f.result()
        pair = [cols[0], fresh]

        def roundtrip(r, c):
            sh = c.reduce_scatter({"g": np.ones(1 << 18, np.float32)}).wait()
            return c.allgather_into(sh).wait()

        outs = _run_all(pair, roundtrip)
        for o in outs:
            np.testing.assert_array_equal(o["g"], np.full(1 << 18, 2.0))
        for c in pair:
            c.shutdown()

    def test_dummy_roundtrip(self):
        d = DummyCollectives()
        tree = {"w": np.arange(6, dtype=np.float32)}
        sh = d.reduce_scatter(tree, ReduceOp.SUM, divisor=2.0).wait()
        out = d.allgather_into(sh).wait()
        np.testing.assert_allclose(out["w"], tree["w"] / 2.0)
