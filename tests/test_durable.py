"""Durable checkpointer v2: async sharded snapshots, WAL-fenced manifest
commits, torn-tail discipline, and no-donor restore across fleet widths."""

import json
import os
import struct
import threading
from datetime import timedelta

import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu import (
    DistributedSampler,
    DummyCollectives,
    DurableCheckpointer,
    FTTrainState,
    Lighthouse,
    LocalDirStore,
    Manager,
    ManifestLog,
    StatefulDataLoader,
    Store,
)
from torchft_tpu.durable import shard_bounds, store_from_env

# ---------------------------------------------------------------------------
# live-manager rig (single member, real commit boundary)


@pytest.fixture
def rig():
    lighthouse = Lighthouse(
        bind="[::]:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=50, heartbeat_timeout_ms=1000,
    )
    store = Store()

    def make_manager(state):
        return Manager(
            collectives=DummyCollectives(world_size=1),
            load_state_dict=state.load_state_dict,
            state_dict=state.state_dict,
            min_replica_size=1,
            rank=0,
            world_size=1,
            use_async_quorum=False,
            timeout=timedelta(seconds=10),
            store_addr=store.address(),
            lighthouse_addr=lighthouse.address(),
            replica_id="durable_test",
        )

    yield make_manager
    store.shutdown()
    lighthouse.shutdown()


def _train(manager, state, ckpt, steps, save=True):
    for _ in range(steps):
        manager.start_quorum()
        grads = {"w": jnp.full((4,), 0.1, jnp.float32)}
        avg = manager.allreduce(grads).wait()
        assert manager.should_commit()
        updates, state.opt_state = state.tx.update(
            avg, state.opt_state, state.params
        )
        state.params = optax.apply_updates(state.params, updates)
        if save:
            ckpt.maybe_save()


def _no_tmp_litter(root):
    for dirpath, _, files in os.walk(root):
        for f in files:
            assert ".tmp" not in f, os.path.join(dirpath, f)


def test_save_restore_roundtrip(rig, tmp_path):
    state = FTTrainState({"w": jnp.ones((4,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    sampler = DistributedSampler(
        dataset_len=64, replica_group=0, num_replica_groups=1
    )
    loader = StatefulDataLoader(sampler, batch_size=4)
    for _ in range(3):
        next(loader)
    ckpt = DurableCheckpointer(
        str(tmp_path), manager, state, loader=loader, every=2, keep=2
    )
    try:
        _train(manager, state, ckpt, 5)  # snapshots at steps 2 and 4
        assert ckpt.flush(30)
        params_after = np.asarray(state.params["w"])
        assert manager.current_step() == 5
        assert ckpt.committed_steps() == [2, 4]
        _no_tmp_litter(tmp_path)
    finally:
        ckpt.close()
        manager.shutdown()

    # fresh process equivalent: new state/manager/loader restore at step 4
    state2 = FTTrainState(
        {"w": jnp.zeros((4,), jnp.float32)}, optax.sgd(1.0)
    )
    manager2 = rig(state2)
    loader2 = StatefulDataLoader(sampler, batch_size=4)
    ckpt2 = DurableCheckpointer(
        str(tmp_path), manager2, state2, loader=loader2, every=2
    )
    try:
        assert ckpt2.restore_latest() == 4
        assert manager2.current_step() == 4
        # restored params = params at step 4 (one step behind final)
        np.testing.assert_allclose(
            np.asarray(state2.params["w"]), params_after + 0.1, atol=1e-6
        )
        # same replica id -> per-member loader position comes back
        assert loader2.state_dict() == loader.state_dict()
        stats = ckpt2.last_restore_stats
        assert stats is not None and stats["world"] == 1
        assert stats["dropped_tail_bytes"] == 0
        # every bucket of the cold restore is timed: a resume that cannot
        # say where its seconds went cannot be tuned
        for bucket in ("manifest_read_s", "shard_fetch_s", "reshard_s",
                       "h2d_s"):
            assert stats[bucket] >= 0.0, bucket
    finally:
        ckpt2.close()
        manager2.shutdown()


def test_commit_hook_drives_captures(rig, tmp_path):
    # register_hook=True: no maybe_save call anywhere in the loop — the
    # Manager commit hook fires the capture at the commit boundary.
    state = FTTrainState({"w": jnp.ones((4,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    ckpt = DurableCheckpointer(
        str(tmp_path), manager, state, every=2, register_hook=True
    )
    try:
        _train(manager, state, ckpt, 4, save=False)
        assert ckpt.flush(30)
        assert ckpt.committed_steps() == [2, 4]
        assert [r["step"] for r in ckpt.snapshots] == [2, 4]
    finally:
        ckpt.close()
        manager.shutdown()


def test_restore_empty_dir_is_none(rig, tmp_path):
    state = FTTrainState({"w": jnp.ones((2,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    ckpt = DurableCheckpointer(str(tmp_path), manager, state)
    try:
        assert ckpt.restore_latest() is None
    finally:
        ckpt.close()
        manager.shutdown()


def test_no_tmp_litter_and_retention(rig, tmp_path):
    state = FTTrainState({"w": jnp.ones((4,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    ckpt = DurableCheckpointer(
        str(tmp_path), manager, state, every=1, keep=1
    )
    try:
        _train(manager, state, ckpt, 3)
        assert ckpt.flush(30)
        assert ckpt.committed_steps() == [3]  # keep=1 retired 1 and 2
        snap_dirs = sorted((tmp_path / "snap").iterdir())
        assert len(snap_dirs) == 1, snap_dirs  # retired objects deleted
        _no_tmp_litter(tmp_path)
    finally:
        ckpt.close()
        manager.shutdown()


def test_no_resave_at_same_step_after_abort(rig, tmp_path):
    # current_step only advances on COMMIT: if the loop calls maybe_save
    # again at the same boundary step (after an aborted step), the good
    # snapshot must NOT be re-captured with drifted loader position.
    state = FTTrainState({"w": jnp.ones((4,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    ckpt = DurableCheckpointer(str(tmp_path), manager, state, every=1)
    try:
        _train(manager, state, ckpt, 1)  # commit step 1, capture
        assert ckpt.flush(30)
        assert len(ckpt.snapshots) == 1
        assert ckpt.maybe_save() is None  # same step again: no re-capture
        assert len(ckpt.snapshots) == 1
    finally:
        ckpt.close()
        manager.shutdown()


def test_restore_arms_same_step_guard(rig, tmp_path):
    # The re-save guard must survive a restore: an aborted first
    # post-restore step at the boundary must not republish the set.
    state = FTTrainState({"w": jnp.ones((4,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    ckpt = DurableCheckpointer(str(tmp_path), manager, state, every=1)
    try:
        _train(manager, state, ckpt, 1)
        assert ckpt.flush(30)
    finally:
        ckpt.close()
        manager.shutdown()

    state2 = FTTrainState({"w": jnp.zeros((4,), jnp.float32)}, optax.sgd(1.0))
    manager2 = rig(state2)
    ckpt2 = DurableCheckpointer(str(tmp_path), manager2, state2, every=1)
    try:
        assert ckpt2.restore_latest() == 1
        assert ckpt2.maybe_save() is None  # restored step: guard armed
    finally:
        ckpt2.close()
        manager2.shutdown()


# ---------------------------------------------------------------------------
# multi-member fleet rig (fake managers over one shared store)


class _FakeManager:
    def __init__(self, rank, world, replica_id, quorum_id=1):
        self._rank, self._world = rank, world
        self._rid = replica_id
        self._step, self._bc, self._qid = 0, 0, quorum_id

    def current_step(self):
        return self._step

    def quorum_id(self):
        return self._qid

    def participating_rank(self):
        return self._rank

    def num_participants(self):
        return self._world

    def replica_id(self):
        return self._rid

    def state_dict(self):
        return {"step": self._step, "batches_committed": self._bc}

    def load_state_dict(self, sd):
        self._step = sd["step"]
        self._bc = sd["batches_committed"]

    def add_commit_hook(self, hook):
        pass


class _RepState:
    """Replicated user state: numpy params + f32 opt_state (the bf16
    wire's target) — every member holds identical leaves."""

    def __init__(self, seed=0, n=256):
        rng = np.random.RandomState(seed)
        self.sd = {
            "params": {"w": rng.randn(n).astype(np.float32)},
            "opt_state": {"m": rng.randn(n).astype(np.float32)},
        }

    def state_dict(self):
        return self.sd

    def load_state_dict(self, sd):
        import jax

        self.sd = jax.tree_util.tree_map(np.asarray, sd)


def _fleet(root, world, store=None, **kw):
    store = store or LocalDirStore(str(root))
    kw.setdefault("commit_timeout_s", 20.0)
    mgrs = [_FakeManager(r, world, f"rep{r}") for r in range(world)]
    states = [_RepState(0) for _ in range(world)]
    cps = [
        DurableCheckpointer(
            str(root), mgrs[r], states[r], store=store, **kw
        )
        for r in range(world)
    ]
    return store, mgrs, states, cps


def _fleet_step(mgrs, cps, step):
    for m in mgrs:
        m._step = step
        m._bc = step * len(mgrs)
    return [c.maybe_save() for c in cps]


def test_shard_bytes_scale_inverse_w(tmp_path):
    # per-member durable bytes ~ total/W: the 1/W headline
    totals = {}
    for world in (1, 2, 4):
        root = tmp_path / f"w{world}"
        _, mgrs, _, cps = _fleet(root, world, every=1)
        _fleet_step(mgrs, cps, 1)
        assert all(c.flush(30) for c in cps)
        rows = [c.snapshots[0] for c in cps]
        assert rows[0]["committed"], rows  # rank 0 runs the committer
        assert cps[0].committed_steps() == [1]
        total = rows[0]["total_bytes"]
        for r in rows:
            assert abs(r["shard_bytes"] - total // world) <= world
        totals[world] = sum(r["shard_bytes"] for r in rows)
        for c in cps:
            c.close()
    # whole-stream bytes written once regardless of W (no W-way
    # redundancy): sums equal across widths
    assert len(set(totals.values())) == 1, totals


def test_restore_across_widths_bit_identical(tmp_path):
    # W_old=3 snapshot; cold fleets of W_new in {1, 2, 4} all rebuild
    # the FULL tree bit-identically — the reshard oracle for the durable
    # tier: re-partitioning at any W_new starts from identical bytes, so
    # shard_bounds(total, W_new) ranges of the rebuilt stream tile into
    # exactly the original stream.
    store, mgrs, states, cps = _fleet(tmp_path, 3, every=1)
    _fleet_step(mgrs, cps, 1)
    assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    want = states[0].sd

    for w_new in (1, 2, 4):
        mgr = _FakeManager(0, w_new, f"cold{w_new}")
        st = _RepState(seed=99)  # different until restored
        cp = DurableCheckpointer(str(tmp_path), mgr, st, store=store)
        assert cp.restore_latest() == 1
        assert mgr._step == 1 and mgr._bc == 3
        np.testing.assert_array_equal(
            st.sd["params"]["w"], want["params"]["w"]
        )
        # opt_state rode the bf16 wire: equals the bf16 roundtrip of the
        # original (params stay exact under protect-params)
        import ml_dtypes

        np.testing.assert_array_equal(
            st.sd["opt_state"]["m"],
            want["opt_state"]["m"]
            .astype(np.dtype(ml_dtypes.bfloat16))
            .astype(np.float32),
        )
        cp.close()


def test_raw_wire_restores_opt_state_exact(tmp_path):
    store, mgrs, states, cps = _fleet(tmp_path, 2, every=1, wire=None)
    _fleet_step(mgrs, cps, 1)
    assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    st = _RepState(seed=5)
    cp = DurableCheckpointer(
        str(tmp_path), _FakeManager(0, 1, "cold"), st, store=store
    )
    assert cp.restore_latest() == 1
    np.testing.assert_array_equal(
        st.sd["opt_state"]["m"], states[0].sd["opt_state"]["m"]
    )
    cp.close()


class _GatedStore(LocalDirStore):
    """Blocks shard-payload writes until released: pins the writer
    thread mid-snapshot so the trainer can run ahead (overlap) or the
    quorum can move (abort) while the set is in flight."""

    def __init__(self, root):
        super().__init__(root)
        self.gate = threading.Event()

    def put_from(self, name, write_fn):
        if "/shard_" in name and name.endswith(".bin"):
            assert self.gate.wait(30), f"gate never released for {name}"
        return super().put_from(name, write_fn)


def test_snapshot_purity_while_writer_overlaps(tmp_path):
    # The donation/aliasing guard: a snapshot captured at step N must
    # never contain step N+1..N+k tensors even though the writer only
    # runs AFTER those steps mutated the live state in place.
    store = _GatedStore(str(tmp_path))
    _, mgrs, states, cps = _fleet(tmp_path, 2, store=store, every=1)
    want_w = states[0].sd["params"]["w"].copy()
    want_m = states[0].sd["opt_state"]["m"].copy()
    _fleet_step(mgrs, cps, 1)  # capture queued; writer gated
    # steps 2..4 mutate the SAME buffers in place (worst-case aliasing)
    for k in range(3):
        for st in states:
            st.sd["params"]["w"] += 1.0
            st.sd["opt_state"]["m"] *= -1.0
    store.gate.set()
    assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()

    st = _RepState(seed=7)
    cp = DurableCheckpointer(
        str(tmp_path), _FakeManager(0, 1, "cold"), st, store=store
    )
    assert cp.restore_latest() == 1
    np.testing.assert_array_equal(st.sd["params"]["w"], want_w)
    import ml_dtypes

    np.testing.assert_array_equal(
        st.sd["opt_state"]["m"],
        want_m.astype(np.dtype(ml_dtypes.bfloat16)).astype(np.float32),
    )


def test_zero_copy_pins_survive_functional_updates(tmp_path):
    # zero_copy=True captures uncompressed jax leaves as pinned
    # zero-copy views — no owning host copy at the commit boundary. The
    # trainer then REPLACES its arrays functionally (the only update
    # style the knob is sound for) and drops every reference to the
    # step-1 arrays; the pins must keep those buffers alive until the
    # gated writer finally ships them.
    import gc

    import jax.numpy as jnp

    class _JaxState:
        def __init__(self):
            self.sd = {
                "params": {"w": jnp.arange(512, dtype=jnp.float32)},
                "opt_state": {"m": jnp.ones(512, dtype=jnp.float32)},
            }

        def state_dict(self):
            return self.sd

        def load_state_dict(self, sd):
            import jax

            self.sd = jax.tree_util.tree_map(np.asarray, sd)

    store = _GatedStore(str(tmp_path))
    mgrs = [_FakeManager(r, 2, f"rep{r}") for r in range(2)]
    states = [_JaxState() for _ in range(2)]
    cps = [
        DurableCheckpointer(
            str(tmp_path), mgrs[r], states[r], store=store, every=1,
            wire=None, zero_copy=True, commit_timeout_s=20.0,
        )
        for r in range(2)
    ]
    want_w = np.asarray(states[0].sd["params"]["w"]).copy()
    want_m = np.asarray(states[0].sd["opt_state"]["m"]).copy()
    _fleet_step(mgrs, cps, 1)  # capture queued; writer gated
    for st in states:  # functional replacement, old arrays unreferenced
        st.sd = {
            "params": {"w": st.sd["params"]["w"] * -3.0},
            "opt_state": {"m": st.sd["opt_state"]["m"] + 9.0},
        }
    gc.collect()
    store.gate.set()
    assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()

    cold = _JaxState()
    cp = DurableCheckpointer(
        str(tmp_path), _FakeManager(0, 1, "cold"), cold, store=store,
        wire=None,
    )
    assert cp.restore_latest() == 1
    np.testing.assert_array_equal(cold.sd["params"]["w"], want_w)
    np.testing.assert_array_equal(cold.sd["opt_state"]["m"], want_m)
    cp.close()


def test_quorum_change_mid_snapshot_aborts(tmp_path):
    # A quorum move invalidates an in-flight set (its W no longer tiles
    # the fleet): the set must abort, never commit, and leave no
    # published marker behind.
    store = _GatedStore(str(tmp_path))
    _, mgrs, states, cps = _fleet(tmp_path, 2, store=store, every=1)
    dirs = _fleet_step(mgrs, cps, 1)  # in flight under quorum_id=1
    assert all(dirs)
    for m in mgrs:
        m._qid = 2  # membership moved
    for m in mgrs:
        m._step = 2
    aborted_dir = dirs[0]
    _ = [c.maybe_save() for c in cps]  # fences old set, captures new
    store.gate.set()
    assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    assert cps[0].committed_steps() == [2]
    assert cps[0].snapshots[0]["aborted"], cps[0].snapshots
    assert cps[0].snapshots[1]["committed"]
    # the aborted set published no markers and no manifest record
    assert not store.list(aborted_dir + "/") or all(
        not n.endswith(".json") for n in store.list(aborted_dir + "/")
    )
    records, _ = ManifestLog(store).replay()
    assert all(r.get("dir") != aborted_dir for r in records)


def test_committer_timeout_abandons_partial_set(tmp_path):
    # One member never writes its shard (died mid-step): rank 0's
    # committer must give up at the deadline and the set must stay
    # invisible to restore.
    store = LocalDirStore(str(tmp_path))
    mgrs = [_FakeManager(r, 2, f"rep{r}") for r in range(2)]
    states = [_RepState(0) for _ in range(2)]
    # only rank 0 exists; rank 1's shard never appears
    cp = DurableCheckpointer(
        str(tmp_path), mgrs[0], states[0], store=store, every=1,
        commit_timeout_s=0.3,
    )
    mgrs[0]._step = 1
    assert cp.maybe_save()
    assert cp.flush(30)
    cp.close()
    assert cp.snapshots[0]["aborted"]
    assert not cp.snapshots[0]["committed"]
    assert cp.committed_steps() == []
    st = _RepState(seed=3)
    cp2 = DurableCheckpointer(
        str(tmp_path), _FakeManager(0, 1, "cold"), st, store=store
    )
    assert cp2.restore_latest() is None
    cp2.close()


def test_manifest_truncate_sweep_never_yields_torn_commit(tmp_path):
    # The wal_write crash-mid-append discipline against the manifest:
    # truncate the log at EVERY byte inside the last commit record — the
    # torn record must never win; restore always falls back to the
    # previous committed set.
    store, mgrs, states, cps = _fleet(tmp_path, 2, every=1, keep=10)
    for step in (1, 2):
        _fleet_step(mgrs, cps, step)
        assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    mpath = tmp_path / "MANIFEST.log"
    raw = mpath.read_bytes()
    frame = struct.Struct("<II")
    pos, bounds = 0, []
    while pos + frame.size <= len(raw):
        ln, _ = frame.unpack_from(raw, pos)
        bounds.append(pos)
        pos += frame.size + ln
    last = bounds[-1]
    for cut in range(last + 1, len(raw)):
        mpath.write_bytes(raw[:cut])
        st = _RepState(seed=11)
        cp = DurableCheckpointer(
            str(tmp_path), _FakeManager(0, 1, "cold"), st, store=store
        )
        assert cp.restore_latest() == 1, cut
        assert cp.last_restore_stats["dropped_tail_bytes"] == cut - last
        cp.close()
    mpath.write_bytes(raw)


def test_corrupt_shard_falls_back_to_older_set(tmp_path):
    store, mgrs, states, cps = _fleet(tmp_path, 2, every=1, keep=10)
    for step in (1, 2):
        _fleet_step(mgrs, cps, step)
        assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    # flip one payload byte of the NEWEST set's shard 1
    newest = cps[0].latest_path()
    path = tmp_path / newest / "shard_0001.bin"
    blob = bytearray(path.read_bytes())
    blob[len(blob) // 2] ^= 0xFF
    path.write_bytes(bytes(blob))
    st = _RepState(seed=13)
    cp = DurableCheckpointer(
        str(tmp_path), _FakeManager(0, 1, "cold"), st, store=store
    )
    assert cp.restore_latest() == 1  # CRC catches it; older set wins
    cp.close()


def test_corrupt_meta_falls_back_to_older_set(tmp_path):
    store, mgrs, states, cps = _fleet(tmp_path, 2, every=1, keep=10)
    for step in (1, 2):
        _fleet_step(mgrs, cps, step)
        assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    newest = cps[0].latest_path()
    path = tmp_path / newest / "meta.pkl"
    blob = bytearray(path.read_bytes())
    blob[0] ^= 0xFF
    path.write_bytes(bytes(blob))
    st = _RepState(seed=17)
    cp = DurableCheckpointer(
        str(tmp_path), _FakeManager(0, 1, "cold"), st, store=store
    )
    assert cp.restore_latest() == 1
    cp.close()


def test_shard_bounds_tile():
    for total in (0, 1, 7, 100, 1 << 20):
        for world in (1, 2, 3, 7, 16):
            b = shard_bounds(total, world)
            assert b[0] == 0 and b[-1] == total
            assert all(b[i] <= b[i + 1] for i in range(world))
    with pytest.raises(ValueError):
        shard_bounds(10, 0)


def test_localdirstore_api(tmp_path):
    s = LocalDirStore(str(tmp_path))
    s.put("a/b/x.bin", b"hello")
    assert s.exists("a/b/x.bin") and s.get("a/b/x.bin") == b"hello"
    assert s.read_range("a/b/x.bin", 1, 3) == b"ell"
    s.append("log", b"12")
    s.append("log", b"34")
    assert s.get("log") == b"1234"
    s.put("a/c.bin", b"z")
    assert s.list("a/") == ["a/b/x.bin", "a/c.bin"]
    s.delete_prefix("a/b/")
    assert s.list("a/") == ["a/c.bin"]
    assert not os.path.exists(tmp_path / "a" / "b")  # empty dirs pruned
    s.delete("missing")  # no-op
    for bad in ("../evil", "a/../../evil", "", "."):
        with pytest.raises(ValueError):
            s.put(bad, b"x")


def test_manifest_log_compaction(tmp_path):
    s = LocalDirStore(str(tmp_path))
    log = ManifestLog(s)
    for i in range(10):
        log.append({"t": "commit", "step": i, "dir": f"d{i}"})
    records, dropped = log.replay()
    assert len(records) == 10 and dropped == 0
    log.compact(records[-2:])
    records2, dropped2 = log.replay()
    assert [r["step"] for r in records2] == [8, 9] and dropped2 == 0


def test_staging_cap_skips_capture(tmp_path):
    # With the writer pinned and a tiny staging budget, the next capture
    # must be SKIPPED (dropped), never block the trainer.
    store = _GatedStore(str(tmp_path))
    _, mgrs, states, cps = _fleet(
        tmp_path, 1, store=store, every=1, max_staging_mb=0.0001
    )
    _fleet_step(mgrs, cps, 1)  # in flight, gated
    _fleet_step(mgrs, cps, 2)  # exceeds the cap -> skipped
    store.gate.set()
    assert all(c.flush(30) for c in cps)
    for c in cps:
        c.close()
    rows = cps[0].snapshots
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["committed"] and rows[1]["skipped"]
    assert cps[0].committed_steps() == [1]


def test_sync_mode_commits_inline(tmp_path):
    _, mgrs, states, cps = _fleet(tmp_path, 2, every=1, mode="sync")
    for m in mgrs:
        m._step = 1
    # rank 0 last: its inline committer polls for rank 1's marker, which
    # in sync mode only exists once rank 1's save already returned
    assert cps[1].maybe_save()
    assert cps[0].maybe_save()
    # no flush needed: sync mode returns only after the manifest commit
    for c in cps:
        c.close()
    assert cps[0].committed_steps() == [1]
    assert cps[0].snapshots[0]["committed"]


def test_store_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv("TORCHFT_DURABLE_STORE", raising=False)
    s = store_from_env(str(tmp_path / "d"))
    assert isinstance(s, LocalDirStore) and s.root == str(tmp_path / "d")
    monkeypatch.setenv("TORCHFT_DURABLE_STORE", f"file:{tmp_path}/e")
    assert store_from_env("x").root == str(tmp_path / "e")
    monkeypatch.setenv("TORCHFT_DURABLE_STORE", "s3://bucket/prefix")
    with pytest.raises(ValueError):
        store_from_env("x")


def test_marker_consistency_rejected(tmp_path):
    # A marker claiming a different (step, quorum_id, total) than the
    # set it sits in must abort the commit (defense against a stale
    # writer racing a re-used directory name).
    store = LocalDirStore(str(tmp_path))
    mgr = _FakeManager(0, 2, "rep0")
    st = _RepState(0)
    cp = DurableCheckpointer(
        str(tmp_path), mgr, st, store=store, every=1, commit_timeout_s=2.0
    )
    mgr._step = 1
    d = None
    # forge rank 1's marker with a mismatched total BEFORE capture so
    # the committer sees both markers immediately
    from torchft_tpu.durable import snapshot_dir

    d = snapshot_dir(1, 1, 2)
    store.put(
        f"{d}/shard_0001.json",
        json.dumps({
            "v": 1, "step": 1, "quorum_id": 1, "rank": 1, "world": 2,
            "begin": 0, "end": 1, "nbytes": 1, "crc": "00000000",
            "wire": "bf16", "total": 999999, "name": f"{d}/shard_0001.bin",
        }).encode(),
    )
    assert cp.maybe_save() == d
    assert cp.flush(30)
    cp.close()
    assert cp.snapshots[0]["aborted"]
    assert cp.committed_steps() == []


def test_ctor_registers_durable_restore(tmp_path):
    # Constructing the checkpointer wires the manager's cold-start
    # fallback (restore-time donor/durable arbitration) — and managers
    # without the hook (this file's _FakeManager) keep working.
    class _Registering(_FakeManager):
        def __init__(self):
            super().__init__(0, 1, "rep0")
            self.registered = None

        def set_durable_restore(self, fn):
            self.registered = fn

    mgr = _Registering()
    cp = DurableCheckpointer(str(tmp_path), mgr, _RepState(0))
    assert mgr.registered == cp.restore_latest
    cp.close()

    plain = _FakeManager(0, 1, "rep1")
    cp2 = DurableCheckpointer(str(tmp_path), plain, _RepState(0))
    cp2.close()


# ---------------------------------------------------------------------------
# the phases on the one primitive: a span in a capture, the stats key of the
# same seconds, and the Manager's timer where there is a Manager


def test_every_phase_is_a_span_and_the_stats_key_of_the_same_seconds(rig, tmp_path):
    from test_profiling import _captured, _one

    state = FTTrainState({"w": jnp.ones((64,), jnp.float32)}, optax.sgd(1.0))
    manager = rig(state)
    ckpt = DurableCheckpointer(str(tmp_path / "d"), manager, state, every=1, mode="async")
    restored = FTTrainState({"w": jnp.zeros((64,), jnp.float32)}, optax.sgd(1.0))
    made = []

    def body():
        manager.start_quorum()
        assert manager.should_commit()
        assert ckpt.maybe_save() is not None
        assert ckpt.flush(timeout=30)
        manager.shutdown()  # the fresh process's manager is the only one alive
        manager2 = rig(restored)
        made.append((manager2, DurableCheckpointer(str(tmp_path / "d"), manager2, restored)))
        assert made[0][1].restore_latest(device_put=True) == 1

    try:
        events = _captured(tmp_path / "trace", body)
    finally:
        ckpt.close()
        for manager2, ckpt2 in made:
            ckpt2.close()
            manager2.shutdown()
    (row,) = ckpt.snapshots
    stats = ckpt2.last_restore_stats
    assert row["committed"]
    save = {"capture": row["stall_s"], "write": row["write_s"], "commit": row["commit_s"]}
    for phase, seconds in save.items():
        event = _one(events, f"torchft::durable_save/{phase}")
        assert event[4] == {"step": 1}, phase
        assert seconds == pytest.approx((event[3] - event[2]) / 1e9, abs=2e-3), phase
    # the writer's two phases on its own thread, after the trainer's stall
    capture, write = (_one(events, f"torchft::durable_save/{p}") for p in ("capture", "write"))
    assert write[0] != capture[0] and write[2] >= capture[3]
    # the same seconds in the Manager's timers
    timers = manager.metrics().snapshot()["timers_s"]
    for phase, seconds in save.items():
        assert timers[f"durable_{phase}"]["n"] == 1
        assert timers[f"durable_{phase}"]["total_s"] == pytest.approx(seconds, abs=2e-6)
    load = {
        "fetch": stats["shard_fetch_s"], "reshard": stats["reshard_s"],
        "h2d": stats["h2d_s"],
    }
    for phase, seconds in load.items():
        event = _one(events, f"torchft::durable_restore/{phase}")
        assert event[4] == {"step": 1}, phase
        assert seconds == pytest.approx((event[3] - event[2]) / 1e9, abs=2e-3), phase
    # two replays: the first manager's cold-start consult, on its quorum
    # thread, found an empty manifest; the restore's own is the later one
    consult, replay = sorted(
        (e for e in events if e[1] == "torchft::durable_restore/replay"), key=lambda e: e[2]
    )
    assert consult[0] != replay[0] and replay[4] == {}
    # manifest_read_s keeps its meaning: the replay and the meta blob's read
    manifest = _one(events, "torchft::durable_restore/manifest")
    assert manifest[4] == {"step": 1}
    assert stats["manifest_read_s"] == pytest.approx(
        (replay[3] - replay[2] + manifest[3] - manifest[2]) / 1e9, abs=4e-3
    )
    assert "replay_s" not in stats  # no key the callers did not have


def test_sync_mode_stalls_for_the_whole_pipeline(tmp_path):
    # one capture span over the capture, the write and the commit; a
    # manager with no Metrics (a stub) files no timer and needs none
    store, mgrs, states, cps = _fleet(tmp_path, 1, mode="sync", every=1)
    (saved,) = _fleet_step(mgrs, cps, 1)
    assert saved is not None
    (row,) = cps[0].snapshots
    assert row["committed"] and cps[0]._metrics is None
    assert row["stall_s"] >= row["write_s"] + row["commit_s"] > 0.0
    cps[0].close()


def test_the_file_keeps_no_clock_of_its_own():
    import torchft_tpu.durable as durable

    with open(durable.__file__) as f:
        assert "perf_counter" not in f.read()
