"""IsolatedXLACollectives: the compiled data plane in a disposable child.

The subsystem's contracts, layered:

- shm segments: native lifecycle (creator unlinks, attachments don't),
  cross-process visibility, the live-handle leak oracle;
- layout: the native ``tft_shm_layout_json`` authority matches the Python
  ``_plan_groups`` mirror positionally (the invariant that lets parent
  and child lay out the same bytes independently);
- monitored channel: a dead child surfaces within a liveness interval,
  child exceptions re-raise in the parent with the child traceback;
- the backend end-to-end ON THIS HOST via the store-fallback reduction
  (the capability probe's measured verdict where CPU jax has no compiled
  multi-process path): multi-member ops in threads, bit-identity against
  the host ring, kill-and-respawn reconfigure, mid-op child SIGKILL;
- manager + AdaptiveDDP integration: managed ``None``-default latching,
  the ``xla_iso`` candidate, and never-beat-by-crash (an un-spawnable
  child records sentinels, the cohort locks a runnable schedule).

The compiled-psum path itself (bit-identity vs the in-process
``XLACollectives``) needs a CPU multiprocess collectives backend and is
gated like every other gloo test.
"""

import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu import _native
from torchft_tpu.collectives import (
    HostCollectives,
    ReduceOp,
    _plan_groups,
)
from torchft_tpu.isolated_xla import (
    ChildDiedError,
    IsolatedXLACollectives,
    _MonitoredChannel,
    _apply_child_env,
    _child_env,
    _sig_layout,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store():
    s = _native.Store()
    yield s
    s.shutdown()


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


def _iso_ring(store, prefix, world, timeout_s=30):
    cols = [
        IsolatedXLACollectives(
            timeout=timedelta(seconds=timeout_s),
            connect_timeout=timedelta(seconds=30),
        )
        for _ in range(world)
    ]
    addr = f"{store.address()}/{prefix}"
    _run_all(cols, lambda r, c: c.configure(addr, r, world))
    return cols


class TestShmSegments:
    def test_create_attach_visibility_and_leak_oracle(self):
        base = _native.shm_live_count()
        seg = _native.ShmSegment.create("tft_test_seg_a", 8192)
        view = np.frombuffer(seg.buffer(), np.float32)
        view[:3] = [1.5, 2.5, 3.5]
        att = _native.ShmSegment.attach("tft_test_seg_a", 8192)
        got = np.frombuffer(att.buffer(), np.float32)
        np.testing.assert_array_equal(got[:3], [1.5, 2.5, 3.5])
        # writes travel the other way too (same kernel pages)
        got[3] = 9.0
        assert view[3] == 9.0
        assert _native.shm_live_count() == base + 2
        del view, got
        att.close()
        seg.close()
        assert _native.shm_live_count() == base

    def test_attach_missing_and_short_segment_fail(self):
        with pytest.raises(RuntimeError, match="shm_open"):
            _native.ShmSegment.attach("tft_test_never_created", 4096)
        seg = _native.ShmSegment.create("tft_test_seg_small", 4096)
        try:
            # attaching at a LARGER size must fail loudly, not SIGBUS
            with pytest.raises(RuntimeError, match="smaller"):
                _native.ShmSegment.attach("tft_test_seg_small", 8192)
        finally:
            seg.close()

    def test_creator_unlinks_attacher_does_not(self):
        seg = _native.ShmSegment.create("tft_test_seg_own", 4096)
        att = _native.ShmSegment.attach("tft_test_seg_own", 4096)
        att.close()  # attachment close must NOT remove the name
        att2 = _native.ShmSegment.attach("tft_test_seg_own", 4096)
        att2.close()
        seg.close()  # creator close unlinks
        with pytest.raises(RuntimeError, match="shm_open"):
            _native.ShmSegment.attach("tft_test_seg_own", 4096)

    def test_unlink_is_idempotent(self):
        _native.shm_unlink("tft_test_seg_gone")  # never created: no error
        seg = _native.ShmSegment.create("tft_test_seg_unl", 4096)
        _native.shm_unlink("tft_test_seg_unl")
        _native.shm_unlink("tft_test_seg_unl")
        seg.close()  # creator's unlink finds the name gone: still fine


class TestShmLayout:
    def _sig(self, specs):
        return tuple((shape, np.dtype(dt)) for shape, dt in specs)

    @pytest.mark.parametrize("wire_name,wire_code", [
        (None, 0), ("bf16", 1), ("q8", 2), ("q8ef", 3),
    ])
    def test_native_layout_matches_python_plan_groups(
        self, wire_name, wire_code
    ):
        # The invariant both sides of the shm boundary depend on: native
        # tft_shm_layout_json groups leaves exactly like the Python
        # _plan_groups mirror (plan_build's first-appearance order), so
        # parent-built views and child-built views address one layout.
        import ml_dtypes

        sig = self._sig([
            ((7, 3), np.float32),
            ((5,), ml_dtypes.bfloat16 if wire_name in (None, "bf16")
             else np.float32),
            ((2, 2), np.float32),
        ])
        counts = [int(np.prod(s)) for s, _ in sig]
        from torchft_tpu.collectives import _NATIVE_DTYPES

        codes = [_NATIVE_DTYPES[dt] for _, dt in sig]
        native = _native.shm_layout(counts, codes, wire_code)
        groups = _plan_groups(sig, wire_name)
        assert len(native["groups"]) == len(groups)
        for ng, (gdt, idxs) in zip(native["groups"], groups):
            assert ng["dtype"] == _NATIVE_DTYPES[gdt]
            assert ng["count"] == sum(counts[i] for i in idxs)
        # per-leaf group assignment and elem offsets match the mirror
        for i, nl in enumerate(native["leaves"]):
            gdt, idxs = groups[nl["group"]]
            assert i in idxs
            expect_off = sum(counts[j] for j in idxs[: idxs.index(i)])
            assert nl["off"] == expect_off

    def test_group_bases_are_64_aligned_and_total_covers(self):
        lay = _native.shm_layout([3, 5, 7], [2, 0, 2], 0)  # i32,f32,i32
        for g in lay["groups"]:
            assert g["offset"] % 64 == 0
        last = lay["groups"][-1]
        dt = {0: 4, 1: 8, 2: 4, 3: 8, 4: 2}[last["dtype"]]
        assert lay["total_bytes"] >= last["offset"] + last["count"] * dt

    def test_q8_wire_rejects_int_leaves(self):
        with pytest.raises(RuntimeError, match="q8"):
            _native.shm_layout([4], [2], 2)  # i32 leaf on the q8 wire

    def test_empty_and_bad_inputs(self):
        with pytest.raises(RuntimeError, match="empty"):
            _native.shm_layout([], [], 0)
        with pytest.raises(RuntimeError, match="wire"):
            _native.shm_layout([4], [0], 9)


class _FakeChild:
    """Socketpair-backed stand-in for the child side of the channel."""

    def __init__(self):
        self.parent_sock, self.child_sock = socket.socketpair()
        self.rc = None

    def alive(self):
        return self.rc

    def reply(self, payload: bytes):
        self.child_sock.sendall(payload)

    def die(self, rc=-9):
        self.rc = rc
        self.child_sock.close()


class TestMonitoredChannel:
    def test_roundtrip_and_child_error_reraise(self):
        fake = _FakeChild()
        ch = _MonitoredChannel(fake.parent_sock, fake.alive)
        ch.send({"cmd": "x"})
        fake.reply(b'{"ok": true}\n')
        assert ch.recv(5.0) == {"ok": True}
        fake.reply(
            b'{"error": "ValueError: boom", "tb": "Traceback...child"}\n'
        )
        with pytest.raises(RuntimeError, match="boom") as ei:
            ch.recv(5.0)
        assert "child traceback" in str(ei.value)
        ch.close()
        fake.child_sock.close()

    def test_child_death_beats_the_op_timeout(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_ISO_LIVENESS_MS", "20")
        fake = _FakeChild()
        ch = _MonitoredChannel(fake.parent_sock, fake.alive)
        threading.Timer(0.1, fake.die).start()
        t0 = time.perf_counter()
        with pytest.raises(ChildDiedError):
            ch.recv(30.0)  # would be a 30 s hang without liveness polling
        assert time.perf_counter() - t0 < 5.0
        ch.close()

    def test_timeout_without_death(self):
        fake = _FakeChild()
        ch = _MonitoredChannel(fake.parent_sock, fake.alive)
        with pytest.raises(TimeoutError):
            ch.recv(0.3)
        ch.close()
        fake.child_sock.close()


class TestChildEnvContract:
    def test_child_env_is_parent_env_plus_repo_pythonpath(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_ENV_PROBE", "x1")
        env = _child_env()
        assert env["TORCHFT_ENV_PROBE"] == "x1"
        assert REPO in env["PYTHONPATH"].split(os.pathsep)

    def test_apply_child_env_replaces_not_merges(self):
        # Regression: zygote-forked children used to MERGE the shipped
        # env on top of the zygote's startup snapshot, so a variable
        # unset in the parent since the zygote started (JAX_PLATFORMS,
        # TORCHFT_*) still reached the child — diverging from the
        # classic-spawn semantics _spawn_child promises.
        snap = dict(os.environ)
        try:
            os.environ["TORCHFT_STALE_VAR"] = "zombie"
            desired = dict(snap)
            desired.pop("TORCHFT_STALE_VAR", None)
            desired["TORCHFT_FRESH_VAR"] = "new"
            _apply_child_env(desired)
            assert "TORCHFT_STALE_VAR" not in os.environ
            assert os.environ.get("TORCHFT_FRESH_VAR") == "new"
        finally:
            os.environ.clear()
            os.environ.update(snap)


class TestIsolatedBackendStorePath:
    """End-to-end on this host: the capability probe lands on the store
    fallback (no compiled CPU multiprocess path), which exercises the
    whole parent half — shm staging, monitored channel, kill/respawn —
    against real children."""

    def test_allreduce_tree_sum_avg_int_and_host_ring_identity(self, store):
        import jax.numpy as jnp

        cols = _iso_ring(store, "q0", 2)
        try:
            assert all(c.reduction_path() == "store" or
                       c.reduction_path() == "psum" for c in cols)
            tree = lambda r: {  # noqa: E731
                "w": jnp.arange(33, dtype=jnp.float32) * (r + 1) * 0.37,
                "b": np.arange(5, dtype=np.int32) * (r + 1),
            }
            outs = _run_all(
                cols,
                lambda r, c: c.allreduce(tree(r), ReduceOp.SUM).wait(),
            )
            # members agree bitwise
            np.testing.assert_array_equal(
                np.asarray(outs[0]["w"]), np.asarray(outs[1]["w"])
            )
            np.testing.assert_array_equal(
                np.asarray(outs[0]["b"]), np.asarray(outs[1]["b"])
            )
            # ... and match the HOST RING bitwise on W=2 (two-operand
            # sums are order-free in IEEE, so the oracle is exact)
            hcs = [HostCollectives(timeout=timedelta(seconds=15))
                   for _ in range(2)]
            addr = f"{store.address()}/hr0"
            _run_all(hcs, lambda r, c: c.configure(addr, r, 2))
            houts = _run_all(
                hcs, lambda r, c: c.allreduce(tree(r), ReduceOp.SUM).wait()
            )
            np.testing.assert_array_equal(
                np.asarray(outs[0]["w"]), np.asarray(houts[0]["w"])
            )
            np.testing.assert_array_equal(
                np.asarray(outs[0]["b"]), np.asarray(houts[0]["b"])
            )
            for c in hcs:
                c.shutdown()
            # AVG: int leaves floor-divide in their own dtype
            avg = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    jnp.full((3,), 3.0 + r), ReduceOp.AVG
                ).wait(),
            )
            assert np.allclose(np.asarray(avg[0]), 3.5)
            iavg = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    np.full((2,), 3 + r, np.int32), ReduceOp.AVG
                ).wait(),
            )
            assert iavg[0].dtype == np.int32 and int(iavg[0][0]) == 3
        finally:
            for c in cols:
                c.shutdown()

    def test_world3_members_identical_and_close_to_ring(self, store):
        import jax.numpy as jnp

        cols = _iso_ring(store, "q3", 3)
        try:
            rng = np.random.default_rng(7)
            base = rng.standard_normal(257).astype(np.float32)
            outs = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    jnp.asarray(base * (r + 1)), ReduceOp.AVG
                ).wait(),
            )
            np.testing.assert_array_equal(
                np.asarray(outs[0]), np.asarray(outs[1])
            )
            np.testing.assert_array_equal(
                np.asarray(outs[0]), np.asarray(outs[2])
            )
            np.testing.assert_allclose(
                np.asarray(outs[0]), base * 2.0, rtol=1e-6
            )
        finally:
            for c in cols:
                c.shutdown()

    def test_slot_recycling_never_serves_stale_payloads(self, store):
        # Regression: store.get only waits for key EXISTENCE, so once the
        # payload slots recycle (op n and op n-window share keys) a
        # member one op ahead of a laggy peer could read the peer's
        # window-old payload and silently corrupt the reduction. The
        # per-(slot, rank) version key forbids it: run 3x the window of
        # sequential ops with per-op distinct values, one member lagging
        # so the other is always ahead at the version poll, and assert
        # every single op's value.
        import jax.numpy as jnp

        from torchft_tpu.isolated_xla import _STORE_SLOTS

        cols = _iso_ring(store, "qstale", 2)
        try:
            nops = 3 * _STORE_SLOTS
            def run(r, c):
                outs = []
                for op in range(nops):
                    if r == 1:
                        time.sleep(0.03)  # the laggy member
                    outs.append(
                        np.asarray(c.allreduce(
                            jnp.full((64,), float((op + 1) * (r + 1))),
                            ReduceOp.SUM,
                        ).wait())
                    )
                return outs

            results = _run_all(cols, run)
            for op in range(nops):
                want = float((op + 1) * 3)  # (op+1)*1 + (op+1)*2
                for r in range(2):
                    assert np.allclose(results[r][op], want), (
                        op, r, results[r][op][0], want
                    )
        finally:
            for c in cols:
                c.shutdown()

    def test_allgather_broadcast_barrier(self, store):
        import jax.numpy as jnp

        cols = _iso_ring(store, "q1", 2)
        try:
            def ops(r, c):
                g = c.allgather(jnp.full((4,), float(r * 10 + 1))).wait()
                b = c.broadcast(jnp.full((2,), float(r)), root=1).wait()
                c.barrier().wait()
                return g, b

            outs = _run_all(cols, ops)
            for g, b in outs:
                assert np.allclose(np.asarray(g[0]), 1.0)
                assert np.allclose(np.asarray(g[1]), 11.0)
                assert np.allclose(np.asarray(b), 1.0)
        finally:
            for c in cols:
                c.shutdown()

    def test_reconfigure_is_kill_and_respawn(self, store):
        import jax.numpy as jnp

        cols = _iso_ring(store, "q2", 2)
        try:
            pids = [c.child_pid() for c in cols]
            assert all(p is not None for p in pids)
            # parent-side device arrays survive untouched (no in-process
            # runtime teardown happens): hold one across the reconfigure
            keep = jnp.arange(16, dtype=jnp.float32) * 1.25
            keep_host = np.asarray(keep).copy()
            addr = f"{store.address()}/q2b"
            _run_all(cols, lambda r, c: c.configure(addr, r, 2))
            new_pids = [c.child_pid() for c in cols]
            assert all(
                n is not None and n != p for n, p in zip(new_pids, pids)
            ), (pids, new_pids)
            # the old children are really gone — SIGKILLed children stay
            # kill(0)-visible zombies until the zygote's reaper tick
            # collects them, so poll with a deadline instead of asserting
            # instantaneous disappearance
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and any(
                _pid_alive(p) for p in pids
            ):
                time.sleep(0.05)
            assert all(not _pid_alive(p) for p in pids), pids
            np.testing.assert_array_equal(np.asarray(keep), keep_host)
            outs = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    jnp.full((8,), 2.0), ReduceOp.SUM
                ).wait(),
            )
            assert np.allclose(np.asarray(outs[0]), 4.0)
        finally:
            for c in cols:
                c.shutdown()

    def test_mid_op_child_kill_fails_fast_then_respawn_recovers(self, store):
        import jax.numpy as jnp

        cols = _iso_ring(store, "q4", 2, timeout_s=6)
        try:
            victim_pid = cols[1].child_pid()
            os.kill(victim_pid, signal.SIGKILL)
            t0 = time.perf_counter()
            errors = [None, None]

            def op(r, c):
                try:
                    c.allreduce(jnp.ones((4,)), ReduceOp.SUM).wait()
                except Exception as e:  # noqa: BLE001
                    errors[r] = e

            _run_all(cols, op)
            elapsed = time.perf_counter() - t0
            # the killed member fails within a liveness interval; the
            # survivor within one op deadline — never the runtime
            # heartbeat's minutes
            assert isinstance(errors[1], ChildDiedError), errors
            assert errors[0] is not None, "survivor must not hang"
            assert elapsed < 15.0, elapsed
            # step-granularity recovery: the next configure respawns and
            # the cohort reduces again
            addr = f"{store.address()}/q4b"
            _run_all(cols, lambda r, c: c.configure(addr, r, 2))
            outs = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    jnp.full((4,), 1.0), ReduceOp.SUM
                ).wait(),
            )
            assert np.allclose(np.asarray(outs[0]), 2.0)
        finally:
            for c in cols:
                c.shutdown()

    @pytest.mark.parametrize("fresh_rank", [0, 1])
    def test_elastic_join_fresh_member_configures_uniformly(
        self, store, fresh_rank
    ):
        # Regression: the capability probe and the /child rendezvous are
        # cohort-wide, so a cohort with MIXED path hints — an elastic
        # joiner's fresh parent sends none while incumbents hint the
        # known verdict — used to strand one side alone in a collective
        # the other never joins (the joiner wedged for the full
        # connect+op deadline, and its parent's configure failed on
        # every retry since _path never locked). Rank 0 now rendezvouses
        # ONE decision through the store; both orderings must configure
        # cleanly and land on the same path.
        import jax.numpy as jnp

        cols = _iso_ring(store, f"qjoin{fresh_rank}", 2, timeout_s=8)
        old = None
        try:
            # the member at fresh_rank "restarts": a brand-new backend
            # with no memory of the locked path (path_hint=None)
            old = cols[fresh_rank]
            cols[fresh_rank] = IsolatedXLACollectives(
                timeout=timedelta(seconds=8),
                connect_timeout=timedelta(seconds=20),
            )
            addr = f"{store.address()}/qjoin{fresh_rank}b"
            _run_all(cols, lambda r, c: c.configure(addr, r, 2))
            assert cols[0].reduction_path() == cols[1].reduction_path()
            outs = _run_all(
                cols,
                lambda r, c: c.allreduce(
                    jnp.full((4,), float(r + 1)), ReduceOp.SUM
                ).wait(),
            )
            assert np.allclose(np.asarray(outs[0]), 3.0)
            assert np.allclose(np.asarray(outs[1]), 3.0)
        finally:
            if old is not None:
                old.shutdown()
            for c in cols:
                c.shutdown()

    def test_superseded_configure_never_installs_its_child(self):
        # Regression: a configure whose caller already gave up (outer
        # timeout -> the next quorum's configure ran its entry kill)
        # used to keep running, install its late child, flip
        # _aborted=False, and leak the child untracked on the stale
        # quorum prefix. The generation token makes the stale install
        # kill the child and raise instead.
        c = IsolatedXLACollectives(
            timeout=timedelta(seconds=10),
            connect_timeout=timedelta(seconds=20),
        )
        real_spawn = c._spawn_and_connect_detached
        try:
            gate = threading.Event()
            release = threading.Event()
            spawned_pids = []

            def slow_spawn():
                gate.set()
                assert release.wait(timeout=30)
                out = real_spawn()
                spawned_pids.append(out[0].pid)
                return out

            c._spawn_and_connect_detached = slow_spawn
            with c._child_lock:
                c._cfg_gen += 1
                gen = c._cfg_gen
            errors = []

            def stale_configure():
                try:
                    c._take_or_spawn_child(gen)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            t = threading.Thread(target=stale_configure)
            t.start()
            assert gate.wait(timeout=10)
            with c._child_lock:
                c._cfg_gen += 1  # the newer configure's entry kill ran
            release.set()
            t.join(timeout=60)
            assert not t.is_alive()
            assert errors and "superseded" in str(errors[0]), errors
            assert c.child_pid() is None, "stale child must not install"
            # ... and the late child is really reaped, not leaked
            assert spawned_pids
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and _pid_alive(
                spawned_pids[0]
            ):
                time.sleep(0.05)
            assert not _pid_alive(spawned_pids[0]), spawned_pids
        finally:
            c._spawn_and_connect_detached = real_spawn
            c.shutdown()

    def test_outer_configure_timeout_covers_inner_deadlines(self):
        # Regression: the outer configure bound was connect+op while the
        # inner work can legitimately take spawn accept (<= connect) +
        # hello (<= connect) + configure reply (<= connect+op) — a slow
        # but healthy configure was abandoned mid-flight.
        c = IsolatedXLACollectives(
            timeout=timedelta(seconds=7),
            connect_timeout=timedelta(seconds=11),
        )
        try:
            assert c._outer_configure_timeout_s() >= 3 * 11 + 7
        finally:
            c.shutdown()

    def test_segment_regrow_evicts_all_staging_views(self):
        # Regression: regenerating a segment unmapped the old pages
        # while _staging still held OTHER signatures' numpy views into
        # them (use-after-unmap; the generation check only rejected the
        # entries on their next lookup, it did not drop the views).
        c = IsolatedXLACollectives()
        try:
            c._staging_for((((8,), np.dtype(np.float32)),), 1)
            c._staging_for((((4,), np.dtype(np.int32)),), 1)
            assert len(c._staging) == 2
            # a signature larger than the segment forces regeneration
            c._staging_for((((1 << 15,), np.dtype(np.float32)),), 1)
            assert len(c._staging) == 1, (
                "stale-generation staging (dangling views into the "
                "unmapped segment) must be evicted, not retained"
            )
            assert all(g == c._seg_gen for g, _ in c._staging.values())
            c.shutdown()
            assert c._staging == {}
        finally:
            c.shutdown()

    def test_shutdown_reaps_children_and_segments(self, store):
        base = _native.shm_live_count()
        cols = _iso_ring(store, "q5", 2)
        import jax.numpy as jnp

        _run_all(
            cols,
            lambda r, c: c.allreduce(jnp.ones((4,)), ReduceOp.SUM).wait(),
        )
        pids = [c.child_pid() for c in cols]
        for c in cols:
            c.shutdown()
        assert _native.shm_live_count() == base
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if all(not _pid_alive(p) for p in pids):
                break
            time.sleep(0.05)
        assert all(not _pid_alive(p) for p in pids), pids

    def test_solo_world_short_circuits_without_child(self, store):
        import jax.numpy as jnp

        c = IsolatedXLACollectives(timeout=timedelta(seconds=10))
        try:
            c.configure(f"{store.address()}/solo", 0, 1)
            assert c.reduction_path() == "solo"
            assert c.child_pid() is None
            out = c.allreduce(jnp.full((3,), 4.0), ReduceOp.AVG).wait()
            assert np.allclose(np.asarray(out), 4.0)
            assert c.allgather({"x": jnp.ones(2)}).wait()[0]["x"].shape == (2,)
        finally:
            c.shutdown()

    def test_op_stats_parity_keys(self, store):
        import jax.numpy as jnp

        cols = _iso_ring(store, "q6", 2)
        try:
            _run_all(
                cols,
                lambda r, c: c.allreduce(
                    {"w": jnp.ones(100, jnp.float32)}, ReduceOp.SUM
                ).wait(),
            )
            stats = cols[0].pop_op_stats()
            cfg = [s for s in stats if s["op"] == "configure"]
            ar = [s for s in stats if s["op"] == "allreduce"]
            assert cfg and ar
            assert cfg[0]["backend"] == "iso"
            for key in ("spawn_s", "child_init_s", "rendezvous_s", "path"):
                assert key in cfg[0]
            st = ar[-1]
            # the cross-backend accounting contract: op/bytes/d2h_bytes
            assert st["bytes"] >= 400
            assert st["d2h_bytes"] == 400  # one f32 jax leaf crossed d2h
            for key in ("pack", "d2h", "ring", "h2d", "child_s", "path"):
                assert key in st
            assert cols[0].pop_op_stats() == []  # drained
        finally:
            for c in cols:
                c.shutdown()


def _pid_alive(pid):
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False


class TestManagerIso:
    def _managers(self, n, store_list, lighthouse, iso=True):
        from torchft_tpu.manager import Manager

        managers = []
        for i in range(n):
            managers.append(
                Manager(
                    collectives=HostCollectives(
                        timeout=timedelta(seconds=15)
                    ),
                    iso_collectives=IsolatedXLACollectives(
                        timeout=timedelta(seconds=15),
                        connect_timeout=timedelta(seconds=20),
                    ) if iso else None,
                    load_state_dict=lambda s: None,
                    state_dict=lambda: {},
                    min_replica_size=n,
                    rank=0,
                    world_size=1,
                    use_async_quorum=False,
                    timeout=timedelta(seconds=15),
                    quorum_timeout=timedelta(seconds=30),
                    store_addr=store_list[i].address(),
                    lighthouse_addr=lighthouse.address(),
                    replica_id=f"iso_integ_{i}",
                )
            )
        return managers

    def test_iso_allreduce_through_managers(self):
        import jax.numpy as jnp

        from torchft_tpu import Lighthouse

        lighthouse = Lighthouse(
            bind="[::]:0", min_replicas=2, join_timeout_ms=2000,
            quorum_tick_ms=50, heartbeat_timeout_ms=5000,
        )
        stores = [_native.Store() for _ in range(2)]
        managers = self._managers(2, stores, lighthouse)
        try:
            def step(i, m):
                m.start_quorum()
                out = m.iso_allreduce(
                    {"g": jnp.full((6,), float(i + 1))}
                ).wait()
                committed = m.should_commit()
                return out, committed

            with ThreadPoolExecutor(max_workers=2) as ex:
                results = list(
                    ex.map(lambda im: step(*im), enumerate(managers))
                )
            for out, committed in results:
                assert committed, "clean iso step must commit"
                assert np.allclose(np.asarray(out["g"]), 1.5), out
        finally:
            for m in managers:
                m.shutdown()
            for s in stores:
                s.shutdown()
            lighthouse.shutdown()

    def test_child_death_latches_none_and_next_step_recovers(self):
        # The managed discipline the tentpole names: child death -> None
        # + latch -> vote discards -> forced reconfigure respawns -> the
        # NEXT step commits. No parent process restarts.
        import jax.numpy as jnp

        from torchft_tpu import Lighthouse

        lighthouse = Lighthouse(
            bind="[::]:0", min_replicas=2, join_timeout_ms=2000,
            quorum_tick_ms=50, heartbeat_timeout_ms=5000,
        )
        stores = [_native.Store() for _ in range(2)]
        managers = self._managers(2, stores, lighthouse)
        try:
            barrier = threading.Barrier(2)

            def run(i, m):
                outcomes = []
                for step in range(3):
                    m.start_quorum()
                    if step == 1 and i == 0:
                        # murder our own child mid-step, pre-dispatch
                        pid = m.iso_collectives().child_pid()
                        if pid is not None:
                            os.kill(pid, signal.SIGKILL)
                    work = m.iso_allreduce(
                        {"g": jnp.full((4,), float(i + 1))}
                    )
                    out = work.wait()
                    committed = m.should_commit()
                    outcomes.append((out is None, committed))
                    barrier.wait(timeout=60)
                return outcomes

            with ThreadPoolExecutor(max_workers=2) as ex:
                futs = [
                    ex.submit(run, i, m) for i, m in enumerate(managers)
                ]
                res = [f.result(timeout=120) for f in futs]
            # step 0: clean commit everywhere
            assert res[0][0] == (False, True)
            assert res[1][0] == (False, True)
            # step 1: the killed member resolves None and the COHORT
            # discards (AND-vote)
            assert res[0][1][0] is True, "dead child must default to None"
            assert res[0][1][1] is False and res[1][1][1] is False
            # step 2: forced reconfigure respawned the child; commits
            assert res[0][2] == (False, True), res[0]
            assert res[1][2] == (False, True), res[1]
        finally:
            for m in managers:
                m.shutdown()
            for s in stores:
                s.shutdown()
            lighthouse.shutdown()


class TestAdaptiveIsoCandidate:
    def _solo_manager(self, iso):
        from torchft_tpu import Lighthouse
        from torchft_tpu.manager import Manager

        lighthouse = Lighthouse(
            bind="[::]:0", min_replicas=1, join_timeout_ms=200,
            quorum_tick_ms=50, heartbeat_timeout_ms=2000,
        )
        store = _native.Store()
        manager = Manager(
            collectives=HostCollectives(timeout=timedelta(seconds=10)),
            iso_collectives=iso,
            load_state_dict=lambda s: None,
            state_dict=lambda: {},
            min_replica_size=1,
            rank=0,
            world_size=1,
            use_async_quorum=False,
            timeout=timedelta(seconds=10),
            store_addr=store.address(),
            lighthouse_addr=lighthouse.address(),
            replica_id="adaptive_iso",
        )
        return manager, store, lighthouse

    def _grad_fn(self, params, x):
        import jax
        import jax.numpy as jnp

        def loss(p):
            return jnp.mean((x @ p["w"]) ** 2)

        value, grads = jax.value_and_grad(loss)(params)
        return value, grads

    def _state(self):
        import jax.numpy as jnp
        import optax

        from torchft_tpu.train_state import FTTrainState

        return FTTrainState({"w": jnp.ones((8, 8), jnp.float32)}, optax.sgd(0.1))

    def test_candidate_joins_only_with_iso_plane(self):
        from torchft_tpu.ddp import AdaptiveDDP

        iso = IsolatedXLACollectives(timeout=timedelta(seconds=10))
        manager, store, lighthouse = self._solo_manager(iso)
        try:
            ddp = AdaptiveDDP(
                manager, self._state(), self._grad_fn, device_pack="off"
            )
            assert "xla_iso" in ddp._candidates
            # int8 compress has no iso transport: candidate dropped
            ddp8 = AdaptiveDDP(
                manager, self._state(), self._grad_fn, compress="int8",
                device_pack="off",
            )
            assert "xla_iso" not in ddp8._candidates
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()

    def test_no_iso_plane_no_candidate(self):
        from torchft_tpu.ddp import AdaptiveDDP

        manager, store, lighthouse = self._solo_manager(None)
        try:
            ddp = AdaptiveDDP(
                manager, self._state(), self._grad_fn, device_pack="off"
            )
            assert "xla_iso" not in ddp._candidates
            with pytest.raises(ValueError, match="iso_collectives"):
                AdaptiveDDP(
                    manager, self._state(), self._grad_fn, mode="xla_iso"
                )
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()

    def test_probe_with_iso_locks_and_trains(self):
        import jax.numpy as jnp

        from torchft_tpu.ddp import AdaptiveDDP

        iso = IsolatedXLACollectives(timeout=timedelta(seconds=10))
        manager, store, lighthouse = self._solo_manager(iso)
        try:
            state = self._state()
            ddp = AdaptiveDDP(
                manager, state, self._grad_fn, probe_steps=2,
                device_pack="off",
            )
            x = jnp.ones((4, 8), jnp.float32)
            for _ in range(10):
                ddp.step(x)
            ddp.flush()
            assert ddp.mode is not None
            assert "xla_iso" in ddp.decision["probe_s"]
            assert manager.current_step() == 10
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()

    def test_unspawnable_child_never_wins(self, monkeypatch):
        # The never-beat-by-crash acceptance: spawning is broken, every
        # xla_iso probe step errors (configure failure -> unusable plane
        # -> latch), the candidate records sentinels, and the cohort
        # locks a RUNNABLE schedule. The primary plane is unaffected.
        import jax.numpy as jnp

        from torchft_tpu import isolated_xla
        from torchft_tpu.ddp import AdaptiveDDP

        def no_spawn(connect):
            raise RuntimeError("injected: no child for you")

        monkeypatch.setattr(isolated_xla, "_spawn_child", no_spawn)
        iso = IsolatedXLACollectives(
            timeout=timedelta(seconds=5),
            connect_timeout=timedelta(seconds=5),
        )

        # world_size 1 takes the solo path (no child) and would never
        # exercise the spawn: force the child path by pretending the
        # world is bigger at the iso plane only. Patch configure to
        # always raise instead — the un-spawnable-child presentation the
        # manager actually sees.
        def broken_configure(store_addr, rank, world_size):
            raise RuntimeError("injected: child unspawnable")

        monkeypatch.setattr(iso, "configure", broken_configure)
        manager, store, lighthouse = self._solo_manager(iso)
        try:
            state = self._state()
            ddp = AdaptiveDDP(
                manager, state, self._grad_fn, probe_steps=2,
                device_pack="off",
            )
            x = jnp.ones((4, 8), jnp.float32)
            for _ in range(14):
                ddp.step(x)
            ddp.flush()
            assert ddp.mode is not None, "probe must terminate"
            assert ddp.mode != "xla_iso", (
                "a candidate whose child cannot spawn must never win"
            )
            assert ddp.decision["probe_s"]["xla_iso"] >= 1e8
            # the primary plane kept training through it
            assert manager.current_step() >= 8
        finally:
            manager.shutdown()
            store.shutdown()
            lighthouse.shutdown()


# ---------------------------------------------------------------------------
# compiled-psum path: needs the CPU multiprocess collectives backend
# ---------------------------------------------------------------------------

_WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
    sys.path.insert(0, {repo!r})
    import numpy as np
    from datetime import timedelta
    rank = int(sys.argv[1]); store_addr = sys.argv[2]
    from torchft_tpu import IsolatedXLACollectives
    from torchft_tpu.collectives import ReduceOp

    iso = IsolatedXLACollectives(timeout=timedelta(seconds=60),
                                 connect_timeout=timedelta(seconds=60))
    iso.configure(store_addr + "/iso0", rank, 2)
    assert iso.reduction_path() == "psum", iso.reduction_path()

    import jax, jax.numpy as jnp
    tree = {{"a": jnp.arange(1000, dtype=jnp.float32) * (rank + 1) * 0.31,
            "b": jnp.ones((7, 3), jnp.float32) * (rank + 1)}}
    got = iso.allreduce(tree, ReduceOp.AVG).wait()

    # in-process XLACollectives oracle over the SAME cohort (fresh
    # prefix): bit-identity is structural (the child RUNS XLACollectives)
    from torchft_tpu import XLACollectives
    xc = XLACollectives(timeout=timedelta(seconds=60),
                        connect_timeout=timedelta(seconds=60))
    xc.configure(store_addr + "/xla0", rank, 2)
    want = xc.allreduce(tree, ReduceOp.AVG).wait()
    for k in tree:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k

    # membership change mid-run: kill-and-respawn, then identical again
    iso.configure(store_addr + "/iso1", rank, 2)
    got2 = iso.allreduce(tree, ReduceOp.SUM).wait()
    want2 = xc.allreduce(tree, ReduceOp.SUM).wait()
    for k in tree:
        assert np.array_equal(np.asarray(got2[k]), np.asarray(want2[k])), k
    print("PSUM-OK")
    iso.shutdown(); xc.shutdown()
    """
).format(repo=REPO)


class TestIsolatedPsumPath:
    def test_psum_bit_identity_vs_inprocess_xla(self):
        store = _native.Store()
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _WORKER, str(r), store.address()],
                env=dict(os.environ, JAX_PLATFORMS="cpu"),
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for r in range(2)
        ]
        try:
            outs = [p.communicate(timeout=240)[0] for p in procs]
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            store.shutdown()
        for p, out in zip(procs, outs):
            assert p.returncode == 0, out
            assert "PSUM-OK" in out
