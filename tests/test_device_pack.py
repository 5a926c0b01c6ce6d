"""Device-side wire compression: pre-packed CommPlan leaves end to end.

The contract under test: a DEVICE-packed plan sync (Pallas kernels emit
the wire encoding on the accelerator, the native plan decodes pre-packed
group buffers) is BIT-IDENTICAL to the host-packed plan sync on every
wire — including across a MIXED ring where one member device-packs and
the other host-packs (pack placement is a local choice, `prepacked` is
deliberately excluded from the plan signature hash) — while the
device-link leg carries wire-sized bytes (`d2h_bytes` in pop_op_stats).
The q8 EF carry lives device-resident and must obey the same
multi-step/reset/heal discipline as the native carry (oracle: the
FMA-free numpy EF + legacy q8 ring, the PR-3 reference).

Runs under JAX_PLATFORMS=cpu with interpret-mode kernels; skips with the
precise probe failure where Pallas cannot execute (not a blanket skip).
"""

import threading
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.quantize import np_quantize_ef as _np_quantize_ef
from test_quantize_kernels import _pallas_probe

_SKIP = _pallas_probe()
if _SKIP is not None:
    pytest.skip(_SKIP, allow_module_level=True)

import jax.numpy as jnp  # noqa: E402

from torchft_tpu._native import Store  # noqa: E402
from torchft_tpu.collectives import (  # noqa: E402
    DummyCollectives,
    HostCollectives,
    ReduceOp,
    _q8_wire_overhead,
)


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


def _jax_trees(world_size, seed=7):
    """Mixed-size jax trees (uneven flat counts: ring chunks and stripe
    buckets land on uneven tails)."""
    rng = np.random.default_rng(seed)
    base = {
        "w": rng.standard_normal(100003).astype(np.float32),
        "v": rng.standard_normal((13, 7)).astype(np.float32),
        "b": rng.standard_normal(33).astype(np.float32) * 7,
    }
    return [
        {k: jnp.asarray(v * (r + 1)) for k, v in base.items()}
        for r in range(world_size)
    ]


class TestDeviceVsHostPackBitIdentity:
    @pytest.mark.parametrize("world_size", [2, 3])
    @pytest.mark.parametrize("stripes", [1, 4])
    @pytest.mark.parametrize("wire", [None, "bf16", "q8ef"])
    def test_device_pack_matches_host_pack(
        self, store, world_size, stripes, wire
    ):
        cols = _make_ring(
            store, world_size, f"dp_{world_size}_{stripes}_{wire}", stripes
        )
        trees = _jax_trees(world_size)
        div = float(world_size)
        host = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=div, wire=wire,
                device_pack=False,
            ).wait(),
        )
        dev = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=div, wire=wire,
                device_pack=True,
            ).wait(),
        )
        for h, d in zip(host, dev):
            for k in h:
                assert np.asarray(h[k]).tobytes() == np.asarray(
                    d[k]
                ).tobytes(), f"wire {wire} leaf {k}: device != host pack"
        for other in dev[1:]:
            for k in other:
                assert np.asarray(dev[0][k]).tobytes() == np.asarray(
                    other[k]
                ).tobytes()
        # both modes actually ran what they claim
        stats = [
            s for s in cols[0].pop_op_stats()
            if s["op"] == "plan_allreduce"
        ]
        assert [s["device_pack"] for s in stats] == [False, True]
        for c in cols:
            c.shutdown()

    def test_mixed_ring_interoperates(self, store):
        # Pack placement is NOT part of the wire contract: rank 0
        # device-packs while rank 1 host-packs, and results stay
        # bit-identical across the ring (prepacked is excluded from the
        # plan signature hash by design).
        cols = _make_ring(store, 2, "dp_mixed", stripes=4)
        trees = _jax_trees(2)
        out = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=2.0, wire="q8ef",
                device_pack=(r == 0),
            ).wait(),
        )
        for k in out[0]:
            assert np.asarray(out[0][k]).tobytes() == np.asarray(
                out[1][k]
            ).tobytes(), f"leaf {k}: mixed ring desynced"
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("world_size", [2, 3])
    def test_q8ef_multi_step_matches_numpy_oracle(self, store, world_size):
        # The device-resident carry over multiple steps vs the FMA-free
        # numpy EF + legacy q8 ring — the PR-3 oracle, now with the
        # quantization running as Pallas kernels on the device.
        cols = _make_ring(store, world_size, f"dpef_{world_size}", stripes=4)
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
            dev = _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    {k: jnp.asarray(v) for k, v in grads[r].items()},
                    ReduceOp.SUM, divisor=div, wire="q8ef",
                    device_pack=True,
                ).wait(),
            )
            for k in ("w", "b"):
                assert np.asarray(leg[0][k]).tobytes() == np.asarray(
                    dev[0][k]
                ).tobytes(), f"step {step} leaf {k}: device EF diverged"
        for c in cols:
            c.shutdown()

    def test_reset_feedback_zeroes_device_carry(self, store):
        cols = _make_ring(store, 2, "dpreset")
        rng = np.random.default_rng(2)
        grads = [
            {"w": jnp.asarray(
                rng.standard_normal(5001).astype(np.float32) * (r + 1)
            )}
            for r in range(2)
        ]

        def sync(r, c):
            return c.plan_allreduce(
                grads[r], ReduceOp.SUM, divisor=2.0, wire="q8ef",
                device_pack=True,
            ).wait()

        first = _run_all(cols, sync)
        _run_all(cols, sync)  # advances the device-resident carry
        _run_all(cols, lambda r, c: c.plan_reset_feedback())
        again = _run_all(cols, sync)  # carry zeroed -> same as step one
        assert np.asarray(first[0]["w"]).tobytes() == np.asarray(
            again[0]["w"]
        ).tobytes()
        for c in cols:
            c.shutdown()

    def test_reconfigure_resets_device_carry_and_rebuilds_plan(self, store):
        # configure() drops native plans (and their carries); the device
        # packer survives but its carry must zero in the same moment, or
        # a device-packing member would diverge from a host-packing one
        # after the first membership change.
        cols = _make_ring(store, 2, "dprecfg")
        rng = np.random.default_rng(4)
        grads = [
            {"w": jnp.asarray(
                rng.standard_normal(7001).astype(np.float32) * (r + 1)
            )}
            for r in range(2)
        ]

        def sync(r, c):
            return c.plan_allreduce(
                grads[r], ReduceOp.SUM, divisor=2.0, wire="q8ef",
                device_pack=True,
            ).wait()

        first = _run_all(cols, sync)
        _run_all(cols, sync)
        addr = f"{store.address()}/dprecfg2"
        _run_all(cols, lambda r, c: c.configure(addr, r, 2))
        again = _run_all(cols, sync)  # fresh plan + zero carry
        assert np.asarray(first[0]["w"]).tobytes() == np.asarray(
            again[0]["w"]
        ).tobytes()
        for c in cols:
            c.shutdown()

    def test_nonfinite_poisons_all_members_through_device_pack(self, store):
        cols = _make_ring(store, 3, "dppoison")
        rng = np.random.default_rng(17)
        base = rng.standard_normal(400).astype(np.float32)

        def op(r, c):
            arr = base * (r + 1)
            if r == 0:
                arr = arr.copy()
                arr[7] = np.nan
            return c.plan_allreduce(
                {"w": jnp.asarray(arr)}, ReduceOp.SUM, wire="q8ef",
                device_pack=True,
            ).wait()

        results = _run_all(cols, op)
        for out in results:
            # the NaN scale poisons rank 0's whole leaf, and the q8
            # wire's NaN-scale encode propagates it to every member
            assert np.all(np.isnan(np.asarray(out["w"])))
        for c in cols:
            c.shutdown()

    def test_world_size_one_device_pack(self):
        col = HostCollectives()
        col.configure("ignored:0/dq", 0, 1)
        tree = {"g": jnp.arange(10, dtype=jnp.float32)}
        out = col.plan_allreduce(
            tree, ReduceOp.SUM, divisor=2.0, wire="bf16", device_pack=True
        ).wait()
        import ml_dtypes

        want = (np.arange(10, dtype=np.float32)
                .astype(ml_dtypes.bfloat16).astype(np.float32) / 2.0)
        np.testing.assert_array_equal(np.asarray(out["g"]), want)
        col.shutdown()


class TestDevicePackAccounting:
    def test_d2h_bytes_scale_with_wire(self, store):
        cols = _make_ring(store, 2, "dpacct", stripes=4)
        trees = _jax_trees(2)
        total = sum(int(np.prod(s or (1,)))
                    for s, _ in ((l.shape, None)
                                 for l in trees[0].values()))

        def sync(wire, device_pack):
            return _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    trees[r], ReduceOp.SUM, divisor=2.0, wire=wire,
                    device_pack=device_pack,
                ).wait(),
            )

        for wire in (None, "bf16", "q8ef"):
            sync(wire, False)
            sync(wire, True)
        stats = [
            s for s in cols[0].pop_op_stats()
            if s["op"] == "plan_allreduce"
        ]
        by = {(s["wire"], s["device_pack"]): s for s in stats}
        f32_bytes = by[(None, False)]["bytes"]
        assert total * 4 == f32_bytes
        # host pack always reads full-width leaves off the device
        for wire in (None, "bf16", "q8ef"):
            assert by[(wire, False)]["d2h_bytes"] == f32_bytes
        # device pack: d2h == what the wire actually needs
        assert by[(None, True)]["d2h_bytes"] == f32_bytes
        assert by[("bf16", True)]["d2h_bytes"] == f32_bytes // 2
        n_leaves = len(trees[0])
        q8 = by[("q8ef", True)]["d2h_bytes"]
        assert q8 == total + 4 * n_leaves  # int8 codes + scale sidecar
        assert q8 <= 0.3 * f32_bytes  # the tentpole ratio
        # honest q8 wire accounting: sidecar + header counted
        assert by[("q8ef", True)]["wire_bytes"] > total
        for c in cols:
            c.shutdown()

    def test_plain_q8_wire_refuses_device_pack(self, store):
        # wire="q8" ships RAW f32 into the quantized ring (host-pack
        # contract); quantizing at the device boundary would change its
        # numerics, so device_pack silently serves it via host pack.
        cols = _make_ring(store, 2, "dpq8plain")
        trees = _jax_trees(2)
        _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, divisor=2.0, wire="q8",
                device_pack=True,
            ).wait(),
        )
        st = [
            s for s in cols[0].pop_op_stats()
            if s["op"] == "plan_allreduce"
        ][-1]
        assert st["device_pack"] is False
        for c in cols:
            c.shutdown()

    def test_numpy_leaves_fall_back_to_host_pack(self, store):
        cols = _make_ring(store, 2, "dpnumpy")
        trees = [{"w": np.ones(4096, np.float32) * (r + 1)}
                 for r in range(2)]
        out = _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                trees[r], ReduceOp.SUM, wire="q8ef", device_pack=True
            ).wait(),
        )
        np.testing.assert_allclose(
            np.asarray(out[0]["w"]), np.full(4096, 3.0), rtol=1e-2
        )
        st = [
            s for s in cols[0].pop_op_stats()
            if s["op"] == "plan_allreduce"
        ][-1]
        assert st["device_pack"] is False
        for c in cols:
            c.shutdown()

    def test_env_knob_resolution(self, store, monkeypatch):
        cols = _make_ring(store, 2, "dpenv")
        trees = _jax_trees(2)

        def sync():
            return _run_all(
                cols,
                lambda r, c: c.plan_allreduce(
                    trees[r], ReduceOp.SUM, wire="bf16"
                ).wait(),
            )

        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "on")
        sync()
        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "off")
        sync()
        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "auto")
        sync()  # auto on a CPU backend = host pack (no device link)
        stats = [
            s for s in cols[0].pop_op_stats()
            if s["op"] == "plan_allreduce"
        ]
        assert [s["device_pack"] for s in stats] == [True, False, False]
        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "bogus")
        with pytest.raises(ValueError, match="TORCHFT_DEVICE_PACK"):
            cols[0].plan_allreduce(trees[0], ReduceOp.SUM).wait()
        for c in cols:
            c.shutdown()


class TestDdpPlumbing:
    def test_pipelined_ddp_device_pack_setting(self):
        from torchft_tpu.ddp import _resolve_device_pack_setting

        assert _resolve_device_pack_setting("on") is True
        assert _resolve_device_pack_setting("off") is False
        assert _resolve_device_pack_setting("auto") is None
        assert _resolve_device_pack_setting(True) is True
        with pytest.raises(ValueError, match="TORCHFT_DEVICE_PACK"):
            _resolve_device_pack_setting("sideways")

    def test_adaptive_candidates_gain_devpack_under_auto(self, monkeypatch):
        from torchft_tpu.ddp import AdaptiveDDP

        class _Mgr:
            pass

        class _State:
            params = {}

        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "auto")
        ddp = AdaptiveDDP(_Mgr(), _State(), lambda *a: (0.0, {}))
        assert "plan_devpack" in ddp._candidates
        assert ddp._candidates.index("plan_devpack") \
            == ddp._candidates.index("plan") + 1
        assert ddp._candidates[0] == "blocking"  # tie-break order intact

        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "off")
        ddp = AdaptiveDDP(_Mgr(), _State(), lambda *a: (0.0, {}))
        assert "plan_devpack" not in ddp._candidates

        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "on")
        ddp = AdaptiveDDP(_Mgr(), _State(), lambda *a: (0.0, {}))
        # pinned on: "plan" itself device-packs, no extra candidate —
        # even under TORCHFT_DDP_MODE=auto (the default here): host pack
        # is only pinned while a devpack candidate is in the race
        assert "plan_devpack" not in ddp._candidates
        assert ddp._plan_device_pack() is True

        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "auto")
        ddp = AdaptiveDDP(_Mgr(), _State(), lambda *a: (0.0, {}))
        assert ddp._plan_device_pack() is False  # contrast vs plan_devpack

        monkeypatch.setenv("TORCHFT_DEVICE_PACK", "off")
        ddp = AdaptiveDDP(_Mgr(), _State(), lambda *a: (0.0, {}))
        assert ddp._plan_device_pack() is False

    def test_decide_locks_blocking_on_candidate_list_mismatch(self):
        # A peer with a DIFFERENT candidate list (mismatched
        # TORCHFT_DEVICE_PACK under auto, or no Pallas kernels) gathers a
        # probe vector of a different length: no cohort-agreed argmin
        # exists, so _decide must lock the safe default instead of
        # crashing on the shape mismatch.
        import numpy as np

        from torchft_tpu.collectives import _completed
        from torchft_tpu.ddp import AdaptiveDDP

        class _M:
            def allgather(self, tree):
                return _completed([
                    tree,
                    {"probe_t": np.array([1.0, 2.0, 3.0])},  # 3 != 4
                ])

            def errored(self):
                return None

            def metrics(self):
                class _N:
                    def record(self, *a):
                        pass

                    def incr(self, *a):
                        pass

                return _N()

        ddp = AdaptiveDDP.__new__(AdaptiveDDP)
        ddp._manager = _M()
        ddp._candidates = ["blocking", "plan", "plan_devpack", "pipelined"]
        ddp._probe_t = [[0.2], [0.1], [0.1], [0.1]]
        ddp._auto = True
        ddp._mode = None
        ddp._probe_qid = 1
        ddp._decision_qid = None
        ddp.decision = None
        ddp._decide()
        assert ddp.mode == "blocking"

    def test_manager_plan_allreduce_passthrough(self):
        # DummyCollectives accepts (and ignores) device_pack — the
        # wrapper call shape works end to end through the manager layer.
        d = DummyCollectives(world_size=4)
        out = d.plan_allreduce(
            {"g": np.full(3, 8.0)}, ReduceOp.AVG, device_pack=True
        ).wait()
        np.testing.assert_array_equal(out["g"], np.full(3, 2.0))

    def test_pipelined_ddp_end_to_end_device_pack(self):
        # Solo manager + real HostCollectives: the plan transport with
        # device_pack="on" commits steps and advances the model.
        import jax

        from torchft_tpu import Lighthouse
        from torchft_tpu.ddp import PipelinedDDP
        from torchft_tpu.manager import Manager
        from torchft_tpu.train_state import FTTrainState

        lighthouse = Lighthouse(
            bind="[::]:0", min_replicas=1, join_timeout_ms=200,
            quorum_tick_ms=50, heartbeat_timeout_ms=2000,
        )
        store = Store()
        collectives = HostCollectives(timeout=timedelta(seconds=10))
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
            replica_id="devpack_e2e",
        )
        try:
            import optax

            params = {"w": jnp.ones((4,), jnp.float32)}
            state = FTTrainState(params, optax.sgd(0.1))

            def grad_fn(p, x):
                loss = jnp.sum((p["w"] * x) ** 2)
                return loss, jax.grad(
                    lambda q: jnp.sum((q["w"] * x) ** 2)
                )(p)

            ddp = PipelinedDDP(
                manager, state, grad_fn, compress="q8",
                transport="plan", device_pack="on",
            )
            x = jnp.ones((4,), jnp.float32)
            for _ in range(3):
                ddp.step(x)
            assert ddp.flush() is True
            assert manager.current_step() == 3
            assert not np.array_equal(
                np.asarray(state.params["w"]), np.ones(4)
            )
            st = [
                s for s in collectives.pop_op_stats()
                if s["op"] == "plan_allreduce"
            ]
            assert st and all(s["device_pack"] for s in st)
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()
