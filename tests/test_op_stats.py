"""pop_op_stats accounting tests.

The per-op phase breakdown (pack / d2h / ring / h2d, bytes, per-bucket
and per-stripe detail) is the ONLY signal that tells a slow transfer from
a slow wire on a degraded link — per-step DDP diagnosis depends on it —
yet until this file nothing asserted its accounting. Covers the
device-packed bulk path, the chunk-pipelined op schedule, the q8 wire,
the plan path's per-bucket stats, and — since the accounting contract
went cross-backend (OpStatsMixin) — the XLA and isolated-XLA backends'
parity keys (``op`` / ``bytes`` / ``d2h_bytes`` on every path), so
AdaptiveDDP probe comparisons and diagnosis tooling read one schema no
matter which data plane served the op.
"""

import os
import subprocess
import sys
import textwrap
import threading
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu._native import Store
from torchft_tpu.collectives import HostCollectives, ReduceOp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store():
    s = Store()
    yield s
    s.shutdown()


def _ring(store, prefix, world_size=2, **kwargs):
    cols = [
        HostCollectives(timeout=timedelta(seconds=15), **kwargs)
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
            errors.append(e)

    threads = [
        threading.Thread(target=run, args=(r,)) for r in range(len(cols))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


class TestDevicePackedStats:
    def test_allreduce_phases_bytes_and_buckets(self, store):
        import jax.numpy as jnp

        cols = _ring(store, "st0", pipeline_chunks=1)
        tree = {
            "w": jnp.ones(5003, jnp.float32),
            "n": jnp.ones(777, jnp.int32),
        }
        _run_all(cols, lambda r, c: c.allreduce(tree).wait())
        stats = [
            s for s in cols[0].pop_op_stats() if s["op"] == "allreduce"
        ]
        assert len(stats) == 1
        st = stats[0]
        # every phase of the d2h -> ring -> h2d pipeline is accounted
        for key in ("pack", "d2h", "ring", "h2d"):
            assert key in st and st[key] >= 0.0
        assert st["bytes"] == 5003 * 4 + 777 * 4
        # d2h_bytes is its own key on every path: native dtypes cross
        # the device link at full width here
        assert st["d2h_bytes"] == st["bytes"]
        assert set(st["buckets"]) == {"float32", "int32"}
        for name, b in st["buckets"].items():
            assert b["bytes"] > 0
            assert "stripe_s" in b and "stripe_wall" in b
        # drained: a second pop is empty
        assert cols[0].pop_op_stats() == []
        for c in cols:
            c.shutdown()

    def test_allreduce_waits_are_split_by_cause(self, store):
        """What PR 39 put in the entry: ``ready`` (the device still
        computing), the op's own ``op_s``, ``ring_transport`` (the wire
        without the wait for peers) and ``d2h_calls``; the phases lie
        inside the op and the wire inside the ring."""
        import jax.numpy as jnp

        cols = _ring(store, "st_split", pipeline_chunks=4, pipeline_min_bytes=0)
        tree = {
            "w": jnp.ones(10007, jnp.float32),
            "n": jnp.ones(501, jnp.int32),
        }
        try:
            _run_all(cols, lambda r, c: c.allreduce(tree).wait())
            (st,) = [
                s for s in cols[0].pop_op_stats() if s["op"] == "allreduce"
            ]
        finally:
            for c in cols:
                c.shutdown()
        for key in ("ready", "op_s", "ring_transport", "d2h_calls"):
            assert key in st, key
        phases = sum(st[k] for k in ("pack", "ready", "d2h", "ring", "h2d"))
        assert 0 < phases <= st["op_s"]
        assert 0 < st["ring_transport"] <= st["ring"]
        # the buckets' own sum, kept for its readers
        assert st["ring_transport"] == pytest.approx(
            sum(b["stripe_wall"] for b in st["buckets"].values())
        )
        assert st["d2h_calls"] == st["chunks"] == 8  # one blocking read a chunk

    def test_chunk_pipelined_chunk_count_and_bytes(self, store):
        import jax.numpy as jnp

        cols = _ring(store, "st1", pipeline_chunks=4, pipeline_min_bytes=0)
        tree = {
            "w": jnp.ones(10007, jnp.float32),
            "n": jnp.ones(501, jnp.int32),
        }
        _run_all(cols, lambda r, c: c.allreduce(tree).wait())
        st = [
            s for s in cols[0].pop_op_stats() if s["op"] == "allreduce"
        ][-1]
        assert st["chunks"] == 2 * 4  # both dtype buckets chunked 4-way
        # chunking must not double-count bytes: bucket sums == totals
        assert st["bytes"] == 10007 * 4 + 501 * 4
        assert st["d2h_bytes"] == st["bytes"]  # chunk-pipelined path too
        assert (
            sum(b["bytes"] for b in st["buckets"].values()) == st["bytes"]
        )
        # phase sums over buckets equal the op-level phase totals
        for phase in ("d2h", "ring", "h2d"):
            assert st[phase] == pytest.approx(
                sum(b[phase] for b in st["buckets"].values())
            )
        for c in cols:
            c.shutdown()

    def test_q8_wire_bytes_quarter_of_device_bytes(self, store):
        import jax.numpy as jnp

        from torchft_tpu.collectives import (
            _effective_stripes,
            _q8_wire_overhead,
        )

        cols = _ring(store, "st2")
        tree = {"w": jnp.ones(8192, jnp.float32)}
        _run_all(
            cols, lambda r, c: c.allreduce(tree, wire="q8").wait()
        )
        st = [
            s for s in cols[0].pop_op_stats() if s["op"] == "allreduce_q8"
        ][-1]
        assert st["bytes"] == 8192 * 4  # f32 crosses the device link
        assert st["d2h_bytes"] == 8192 * 4  # host pack: f32 d2h leg
        # ~1 byte/elem rides TCP PLUS the honest overhead: one f32 scale
        # per (stripe, ring chunk) per quantized phase + the op header
        eff = _effective_stripes(8192, cols[0]._stripes)
        assert st["wire_bytes"] == 8192 + _q8_wire_overhead(eff, 2)
        assert st["wire_bytes"] > 8192  # the sidecar is not free
        for c in cols:
            c.shutdown()

    def test_stats_window_is_bounded_at_256(self, store):
        cols = _ring(store, "st3")
        for _ in range(300):
            cols[0]._record_op_stats({"op": "x"})
        assert len(cols[0].pop_op_stats()) == 256
        for c in cols:
            c.shutdown()


class TestShardedStats:
    def test_reduce_scatter_and_allgather_into_stats(self, store):
        cols = _ring(store, "st4", world_size=2, stripes=2)
        tree = {"g": np.ones(50021, np.float32)}

        def sync(r, c):
            sh = c.reduce_scatter(tree, ReduceOp.SUM).wait()
            return c.allgather_into(sh).wait()

        _run_all(cols, sync)
        stats = cols[0].pop_op_stats()
        rs = [s for s in stats if s["op"] == "reduce_scatter"][-1]
        ag = [s for s in stats if s["op"] == "allgather_into"][-1]
        assert rs["bytes"] == 50021 * 4
        # the shard leg scales with 1/world: strictly smaller than full
        assert 0 < rs["shard_bytes"] < rs["bytes"]
        assert rs["wire_bytes"] == rs["bytes"]  # f32 wire
        # numpy input: nothing crossed a device link on either op
        assert rs["d2h_bytes"] == 0
        assert ag["d2h_bytes"] == 0
        assert ag["bytes"] == 50021 * 4
        for st in (rs, ag):
            assert "ring" in st and "stripe_s" in st
        for c in cols:
            c.shutdown()

    def test_sharded_d2h_bytes_with_jax_leaves(self, store):
        import jax.numpy as jnp

        from torchft_tpu.collectives import _q8_wire_overhead

        cols = _ring(store, "st4j", world_size=2, stripes=2)
        tree = {"g": jnp.ones(50021, jnp.float32)}

        def sync(r, c):
            sh = c.reduce_scatter(tree, ReduceOp.SUM, wire="q8").wait()
            return c.allgather_into(sh).wait()

        _run_all(cols, sync)
        stats = cols[0].pop_op_stats()
        rs = [s for s in stats if s["op"] == "reduce_scatter"][-1]
        ag = [s for s in stats if s["op"] == "allgather_into"][-1]
        # the full tree crosses down once; only the owned shard returns
        assert rs["d2h_bytes"] == 50021 * 4
        assert 0 < ag["d2h_bytes"] == rs["shard_bytes"]
        # q8 reduce-scatter runs ONE quantized phase: sidecar + header
        from torchft_tpu.collectives import _effective_stripes

        eff = _effective_stripes(50021, 2)  # q8: 1 byte/element
        assert rs["wire_bytes"] == 50021 + _q8_wire_overhead(
            eff, 2, phases=1
        )
        for c in cols:
            c.shutdown()


class TestIsolatedBackendStats:
    def test_iso_entries_carry_the_parity_keys(self, store):
        # The isolated backend drains through the SAME pop_op_stats
        # contract as the host ring: op / bytes / d2h_bytes on every
        # entry, plus its child-side wall and measured reduction path.
        import jax.numpy as jnp

        from torchft_tpu.isolated_xla import IsolatedXLACollectives

        cols = [
            IsolatedXLACollectives(timeout=timedelta(seconds=20))
            for _ in range(2)
        ]
        addr = f"{store.address()}/isostats"
        try:
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(
                    lambda r: cols[r].configure(addr, r, 2), range(2)
                ))
                list(ex.map(
                    lambda r: cols[r].allreduce(
                        {"w": jnp.ones(2048, jnp.float32)}, ReduceOp.AVG
                    ).wait(),
                    range(2),
                ))
            stats = cols[0].pop_op_stats()
            ar = [s for s in stats if s["op"] == "allreduce"][-1]
            assert ar["backend"] == "iso"
            assert ar["bytes"] >= 2048 * 4
            assert ar["d2h_bytes"] == 2048 * 4  # the jax leaf's d2h leg
            assert ar["path"] in ("psum", "store")
            for key in ("pack", "d2h", "ring", "h2d", "child_s"):
                assert key in ar and ar[key] >= 0.0
            cfg = [s for s in stats if s["op"] == "configure"][-1]
            assert {"spawn_s", "child_init_s", "rendezvous_s"} <= set(cfg)
        finally:
            for c in cols:
                c.shutdown()


_XLA_STATS_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    sys.path.insert(0, {repo!r})
    import jax, numpy as np, jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")
    from datetime import timedelta
    from torchft_tpu import XLACollectives
    from torchft_tpu.collectives import ReduceOp

    rank = int(sys.argv[1]); store_addr = sys.argv[2]
    xc = XLACollectives(timeout=timedelta(seconds=60),
                        connect_timeout=timedelta(seconds=60))
    xc.configure(store_addr + "/q0", rank, 2)
    xc.allreduce({{"w": jnp.ones(1024, jnp.float32)}}, ReduceOp.SUM).wait()
    xc.allgather(jnp.ones(16, jnp.float32)).wait()
    xc.broadcast(jnp.ones(16, jnp.float32)).wait()
    stats = xc.pop_op_stats()
    ops = [s["op"] for s in stats]
    assert "allreduce" in ops and "allgather" in ops and "broadcast" in ops, ops
    ar = [s for s in stats if s["op"] == "allreduce"][-1]
    assert ar["backend"] == "xla"
    assert ar["bytes"] == 1024 * 4
    assert ar["d2h_bytes"] == 1024 * 4  # host-backed results: localize fetch
    for key in ("pack", "ring", "h2d"):
        assert key in ar
    ag = [s for s in stats if s["op"] == "allgather"][-1]
    assert ag["d2h_bytes"] == 16 * 4 * 2  # every member's row fetched
    assert xc.pop_op_stats() == []
    print("XLA-STATS-OK")
    xc.shutdown()
    """
).format(repo=REPO)


class TestXLABackendStats:
    def test_xla_entries_carry_the_parity_keys(self, store):
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _XLA_STATS_WORKER, str(r),
                 store.address()],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for r in range(2)
        ]
        try:
            outs = [p.communicate(timeout=180)[0] for p in procs]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        for p, out in zip(procs, outs):
            assert p.returncode == 0, out
            assert "XLA-STATS-OK" in out


class TestPlanStats:
    def test_plan_bucket_accounting_matches_payload(self, store):
        cols = _ring(store, "st5", world_size=2, stripes=4)
        rng = np.random.default_rng(1)
        tree = {
            "a": rng.standard_normal(150001).astype(np.float32),
            "b": rng.standard_normal(33).astype(np.float64),
        }
        trees = [tree, {k: v * 2 for k, v in tree.items()}]

        def sync(r, c):
            return c.plan_allreduce(trees[r], ReduceOp.SUM).wait()

        _run_all(cols, sync)  # warmup: plan build
        cols[0].pop_op_stats()
        _run_all(cols, sync)
        st = [
            s for s in cols[0].pop_op_stats()
            if s["op"] == "plan_allreduce"
        ][-1]
        total = 150001 * 4 + 33 * 8
        assert st["bytes"] == total
        # host pack: full-width leaves are what the device link reads
        assert st["d2h_bytes"] == total
        assert st["device_pack"] is False
        assert st["py_staging_allocs"] == 0  # the zero-allocation contract
        assert st["plan_execs"] == 2
        # per-bucket bytes tile the payload exactly — each bucket is one
        # stripe sub-range of its group
        assert sum(b["bytes"] for b in st["buckets"]) == total
        groups = {b["group"] for b in st["buckets"]}
        assert len(groups) == 2  # f32 group striped, f64 group tiny
        for b in st["buckets"]:
            for key in ("pack_s", "ring_s", "unpack_s"):
                assert b[key] >= 0.0
        for c in cols:
            c.shutdown()


class TestShardedPlanLegStats:
    """The per-step ZeRO plan's honest wire accounting: the grad
    reduce-scatter leg and the param allgather leg bill as SEPARATE
    phase keys, each with its own wire_bytes/d2h_bytes, and the plan's
    per-bucket detail tags each bucket with its leg — the data the
    "wins memory/FLOPs, not bytes" caveat of docs/OPERATIONS.md is read
    from."""

    def _sharded_step(self, c, tree, wire=None, ag_wire=None):
        sh = c.plan_reduce_scatter(
            tree, ReduceOp.SUM, divisor=2.0, wire=wire, ag_wire=ag_wire
        ).wait()
        return c.plan_allgather_into(sh, wire=ag_wire).wait()

    def test_f32_legs_bill_separately(self, store):
        cols = _ring(store, "shst", world_size=2, stripes=2)
        tree = {"g": np.ones(50021, np.float32)}
        _run_all(
            cols, lambda r, c: self._sharded_step(c, tree)
        )  # warmup: plan build
        cols[0].pop_op_stats()
        _run_all(cols, lambda r, c: self._sharded_step(c, tree))
        stats = cols[0].pop_op_stats()
        rs = [s for s in stats if s["op"] == "plan_reduce_scatter"][-1]
        ag = [s for s in stats if s["op"] == "plan_allgather_into"][-1]
        assert rs["bytes"] == ag["bytes"] >= 50021 * 4
        # f32 on both legs: each leg's wire carries the full payload once
        assert rs["wire_bytes"] == rs["bytes"]
        assert ag["wire_bytes"] == ag["bytes"]
        # the shard leg scales with 1/world: strictly smaller than full
        assert 0 < rs["shard_bytes"] < rs["bytes"]
        # numpy input: nothing crossed a device link on either leg
        assert rs["d2h_bytes"] == 0 and ag["d2h_bytes"] == 0
        assert rs["py_staging_allocs"] == 0  # zero-allocation contract
        # per-leg bucket tags: the rs entry's window holds grad-leg
        # buckets only; the ag entry appends the param leg's after them,
        # so the pair reads as one step.
        assert {b["leg"] for b in rs["buckets"]} == {1}
        assert {b["leg"] for b in ag["buckets"]} == {1, 2}
        for st in (rs, ag):
            for key in ("d2h", "ring", "h2d"):
                assert st[key] >= 0.0
        for c in cols:
            c.shutdown()

    def test_q8_rs_bf16_ag_wire_bytes(self, store):
        import jax.numpy as jnp

        cols = _ring(store, "shstq", world_size=2, stripes=2)
        tree = {"g": jnp.ones(50021, jnp.float32)}
        _run_all(
            cols,
            lambda r, c: self._sharded_step(
                c, tree, wire="q8", ag_wire="bf16"
            ),
        )
        stats = cols[0].pop_op_stats()
        rs = [s for s in stats if s["op"] == "plan_reduce_scatter"][-1]
        ag = [s for s in stats if s["op"] == "plan_allgather_into"][-1]
        # q8 grad leg: ~1 byte/element + sidecar/header overhead —
        # strictly between a quarter and half of the f32 bill
        assert rs["bytes"] // 4 <= rs["wire_bytes"] < rs["bytes"] // 2
        # bf16 param leg: exactly half the f32 bill
        assert ag["wire_bytes"] == ag["bytes"] // 2
        # jax leaves: the full tree crosses down on the grad leg; only
        # the updated shard crosses down on the param leg
        assert rs["d2h_bytes"] == rs["bytes"]
        assert 0 < ag["d2h_bytes"] == rs["shard_bytes"]
        for c in cols:
            c.shutdown()


class TestHierStats:
    """The two-tier schedule's accounting: per-tier phase keys
    (intra_rs_s / inter_ring_s / intra_ag_s / intra_bcast_s) and per-tier
    MEASURED tx bytes (duplex's per-connection counters, summed) — the
    numbers that make the inter-tier byte reduction directly observable
    instead of modeled."""

    def _hier_ring(self, store, regions, **kwargs):
        cols = [
            HostCollectives(timeout=timedelta(seconds=15), **kwargs)
            for _ in regions
        ]
        addr = f"{store.address()}/hier"
        with ThreadPoolExecutor(max_workers=len(regions)) as ex:
            for f in [
                ex.submit(cols[r].configure, addr, r, len(regions), regions)
                for r in range(len(regions))
            ]:
                f.result()
        return cols

    def test_bulk_hier_per_tier_keys_and_bytes(self, store):
        regions = ["a", "a", "b", "b"]
        count = 30_000
        cols = self._hier_ring(store, regions)
        datas = [np.full(count, float(r + 1), np.float32) for r in range(4)]
        _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        stats = [c.pop_op_stats()[-1] for c in cols]
        payload = count * 4
        for r, st in enumerate(stats):
            assert st["op"] == "allreduce_hier"
            assert st["bytes"] == payload
            for k in ("intra_rs_s", "intra_ag_s", "inter_ring_s",
                      "intra_bcast_s"):
                assert k in st
            # total wire bill = measured intra + inter traffic
            tiers = st["tiers"]
            assert st["wire_bytes"] == (
                tiers["intra"]["tx_bytes"] + tiers["inter"]["tx_bytes"]
            )
        # leaders (ranks 0, 2): each inter ring phase ships (L-1)/L of the
        # payload — here L=2, so N/2 per phase, measured within a couple
        # percent (op headers + a q8-free wire have no other overhead)
        for r in (0, 2):
            inter = stats[r]["tiers"]["inter"]
            for k in ("rs_tx_bytes", "ag_tx_bytes"):
                assert payload // 2 <= inter[k] <= payload // 2 + 512
        # non-leaders never send on the inter tier
        for r in (1, 3):
            assert stats[r]["tiers"]["inter"]["tx_bytes"] == 0
        for c in cols:
            c.shutdown()

    def test_q8_inter_wire_quarters_the_slow_link(self, store):
        # wire="q8": the inter hop ships ~1 byte/element + per-chunk
        # scales; intra stays full f32. The measured ratio is the
        # tentpole's bytes story in one assert.
        regions = ["a", "a", "b", "b"]
        count = 40_000
        cols = self._hier_ring(store, regions)
        datas = [
            np.linspace(0, 1, count, dtype=np.float32) * (r + 1)
            for r in range(4)
        ]
        _run_all(
            cols,
            lambda r, c: c.allreduce_hier(datas[r].copy(), wire="q8").wait(),
        )
        st = cols[0].pop_op_stats()[-1]
        inter = st["tiers"]["inter"]
        f32_phase = count * 4 // 2  # what the f32 inter wire would ship
        assert inter["rs_tx_bytes"] < f32_phase * 0.30, (
            f"q8 inter phase shipped {inter['rs_tx_bytes']} B, f32 would "
            f"ship {f32_phase}"
        )
        for c in cols:
            c.shutdown()

    def test_hier_plan_entry_carries_tier_breakdown(self, store):
        regions = ["a", "b", "b"]
        cols = self._hier_ring(store, regions)
        tree = {"g": np.ones(9_000, np.float32)}
        _run_all(
            cols,
            lambda r, c: c.plan_allreduce(
                tree, ReduceOp.SUM, divisor=3.0, hier=True
            ).wait(),
        )
        st = cols[0].pop_op_stats()[-1]
        assert st["op"] == "plan_allreduce"
        assert st["hier"] is True
        assert st["py_staging_allocs"] == 0
        for k in ("intra_rs_s", "inter_ring_s", "intra_ag_s",
                  "intra_bcast_s", "tiers", "buckets"):
            assert k in st
        assert st["wire_bytes"] == (
            st["tiers"]["intra"]["tx_bytes"]
            + st["tiers"]["inter"]["tx_bytes"]
        )
        for c in cols:
            c.shutdown()


class TestShmTierStats:
    """The third (intra-host shm) tier's accounting contract: shm hops
    record phase TIME but contribute ZERO tx/wire bytes (nothing is
    handed to the kernel), the TCP tiers' measured bytes are unchanged by
    the host tier's presence, and d2h accounting is transport-blind."""

    def _ring(self, store, regions, hosts, prefix, **kwargs):
        world = len(hosts if hosts is not None else regions)
        cols = [
            HostCollectives(timeout=timedelta(seconds=15), **kwargs)
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

    def test_shm_hops_record_time_but_zero_wire_bytes(self, store):
        regions = ["a", "a", "b", "b"]
        hosts = ["h0", "h0", "h1", "h1"]
        count = 30_000
        cols = self._ring(store, regions, hosts, "shmstats")
        datas = [np.full(count, float(r + 1), np.float32) for r in range(4)]
        _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        payload = count * 4
        for r, st in enumerate(c.pop_op_stats()[-1] for c in cols):
            assert st["op"] == "allreduce_hier"
            # shm phase keys present and the phases really ran
            for k in ("shm_rs_s", "shm_ag_s", "shm_bcast_s"):
                assert k in st, f"rank {r} missing {k}"
            host = st["tiers"]["host"]
            assert host["transport"] == "shm"
            assert host["world"] == 2
            assert host["rs_s"] > 0 and host["ag_s"] > 0
            # honest zero-tx accounting: the shm tier hands NOTHING to
            # the kernel...
            assert host["tx_bytes"] == 0
            # ...while the ring movement is still measured (rs + ag + the
            # broadcast all move ~payload each within the 2-member group,
            # plus 16-byte frame headers)
            assert host["shm_bytes"] > payload
            # and wire_bytes (the kernel bill) is exactly the TCP tiers'
            assert st["wire_bytes"] == (
                st["tiers"]["intra"]["tx_bytes"]
                + st["tiers"]["inter"]["tx_bytes"]
            )
        for c in cols:
            c.shutdown()

    def test_tcp_tiers_unchanged_by_host_tier(self, store):
        # The inter (region-leader) tier's measured slow-link bill must
        # be IDENTICAL with and without the host tier below it: the host
        # tier changes where the region sum is computed, not what crosses
        # the slow links. (With one host per region the intra tier is
        # empty in the hosted config — each region's lone host group IS
        # the region — so the comparison pins the inter tier.)
        regions = ["a", "a", "b", "b"]
        count = 30_000
        datas = [np.full(count, float(r + 1), np.float32) for r in range(4)]

        cols = self._ring(store, regions, None, "nohost")
        _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        flat_stats = [c.pop_op_stats()[-1] for c in cols]
        for c in cols:
            c.shutdown()

        cols = self._ring(store, regions, ["h0", "h0", "h1", "h1"], "hosted")
        _run_all(
            cols, lambda r, c: c.allreduce_hier(datas[r].copy()).wait()
        )
        host_stats = [c.pop_op_stats()[-1] for c in cols]
        for c in cols:
            c.shutdown()

        for r in range(4):
            a = flat_stats[r]["tiers"]["inter"]
            b = host_stats[r]["tiers"]["inter"]
            for k in ("tx_bytes", "rs_tx_bytes", "ag_tx_bytes", "world"):
                assert a[k] == b[k], (
                    f"rank {r} inter[{k}] drifted: {a[k]} vs {b[k]}"
                )

    def test_d2h_bytes_identical_across_shm_and_tcp_schedules(
        self, store, monkeypatch
    ):
        # d2h accounting is transport-blind: the device->host leg happens
        # before any tier runs, so the shm and loopback-TCP host tiers
        # must bill identical d2h_bytes for identical trees.
        import jax.numpy as jnp

        hosts = ["h0", "h0"]
        count = 4096

        def measure(prefix):
            cols = self._ring(store, None, hosts, prefix)
            tree = {"g": jnp.ones((count,), jnp.float32)}
            _run_all(cols, lambda r, c: c.allreduce_hier(dict(tree)).wait())
            out = [c.pop_op_stats()[-1] for c in cols]
            for c in cols:
                c.shutdown()
            return out

        shm_stats = measure("d2h_shm")
        assert shm_stats[0]["tiers"]["host"]["transport"] == "shm"
        monkeypatch.setenv("TORCHFT_HC_SHM", "0")
        tcp_stats = measure("d2h_tcp")
        assert tcp_stats[0]["tiers"]["host"]["transport"] == "tcp"
        for r in range(2):
            assert shm_stats[r]["d2h_bytes"] == count * 4
            assert shm_stats[r]["d2h_bytes"] == tcp_stats[r]["d2h_bytes"]
            assert shm_stats[r]["bytes"] == tcp_stats[r]["bytes"]
            # the TCP fallback's host hops DO hit the kernel — the
            # honest contrast to the shm tier's zero
            assert tcp_stats[r]["tiers"]["host"]["tx_bytes"] > 0
            assert shm_stats[r]["tiers"]["host"]["tx_bytes"] == 0
