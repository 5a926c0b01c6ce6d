"""Profiling subsystem: windowed jax profiler capture + spans.

Closes SURVEY.md §5's tracing gap; the reference has no analog, so these
tests pin OUR contract: captures are step-windowed, env-configurable,
failure-tolerant, spans are no-ops without an active session, and one
timed region shows under one name in every sink (the profiler span
``torchft::<name>`` with the step as a stat, and the ``Metrics`` timer or
the ``pop_op_stats`` keys).
"""

import glob
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from datetime import timedelta
from unittest.mock import MagicMock, patch

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu._native import Store, StoreClient
from torchft_tpu.collectives import HostCollectives, ReduceOp, Work
from torchft_tpu.metrics import Metrics
from torchft_tpu.profiling import Profiler, span


def test_span_noop_without_capture():
    with span("torchft::test"):
        pass
    with span("torchft::test", 3):
        jnp.ones(4).sum()


def test_windowed_capture_writes_trace(tmp_path):
    logdir = str(tmp_path / "trace")
    prof = Profiler(logdir, start_step=2, num_steps=2)
    assert prof.state == "idle"
    prof.on_step(0)
    prof.on_step(1)
    assert prof.state == "idle"
    prof.on_step(2)  # starts
    assert prof.state == "active"
    with span("torchft::quorum", 2):
        jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    prof.on_step(3)
    assert prof.state == "active"  # stop_after = start + num = 4
    prof.on_step(4)  # stops
    assert prof.state == "done"
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), "no trace files written"
    # further steps are no-ops
    prof.on_step(5)
    assert prof.state == "done"


def test_late_start_still_captures_num_steps(tmp_path):
    # a replica resuming at step 100 with start_step=10 must still get a
    # num_steps-wide window, not stop on the next step
    prof = Profiler(str(tmp_path / "late"), start_step=10, num_steps=5)
    prof.on_step(100)
    assert prof.state == "active"
    prof.on_step(101)
    prof.on_step(104)
    assert prof.state == "active"
    prof.on_step(105)
    assert prof.state == "done"


def test_shutdown_flushes_active_capture(tmp_path):
    logdir = str(tmp_path / "trace2")
    prof = Profiler(logdir, start_step=0, num_steps=100)
    prof.on_step(0)
    assert prof.state == "active"
    prof.shutdown()
    assert prof.state == "done"
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files)


def test_from_env(monkeypatch, tmp_path):
    assert Profiler.from_env() is None
    monkeypatch.setenv("TORCHFT_PROFILE_DIR", str(tmp_path))
    monkeypatch.setenv("TORCHFT_PROFILE_START", "7")
    monkeypatch.setenv("TORCHFT_PROFILE_STEPS", "3")
    prof = Profiler.from_env()
    assert prof is not None
    assert prof.logdir == str(tmp_path)
    assert prof.start_step == 7
    assert prof.num_steps == 3


def test_double_start_is_swallowed(tmp_path):
    # a second Profiler starting while one is active must log, not raise
    a = Profiler(str(tmp_path / "a"), start_step=0, num_steps=10)
    b = Profiler(str(tmp_path / "b"), start_step=0, num_steps=10)
    a.on_step(0)
    b.on_step(0)  # jax only allows one trace; failure must be swallowed
    a.shutdown()
    b.shutdown()
    assert a.state == "done"
    assert b.state == "done"


# -- one primitive, one name in every sink --------------------------------


def _captured(logdir, body):
    """Runs ``body`` under a CPU capture; returns the ``torchft::*`` and
    ``test::*`` host events as (thread line, name, start_ns, end_ns,
    stats)."""
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(str(logdir), "plugins", "profile", "*", "*.xplane.pb")
    )
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(("torchft::", "test::")):
                    events.append((
                        i, e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats),
                    ))
    return events


def _one(events, name):
    found = [e for e in events if e[1] == name]
    assert len(found) == 1, (name, [e[1] for e in events])
    return found[0]


def test_timed_writes_the_timer_and_a_span_carrying_the_step(tmp_path):
    metrics = Metrics()
    metrics.step = 7

    def body():
        with metrics.timed("x"):
            jnp.ones(4).block_until_ready()

    event = _one(_captured(tmp_path, body), "torchft::x")  # the bare name
    assert event[4] == {"step": 7}
    timer = metrics.snapshot()["timers_s"]["x"]
    assert timer["n"] == 1
    # the same statements on two clocks
    assert timer["total_s"] == pytest.approx((event[3] - event[2]) / 1e9, abs=2e-3)


def test_work_wait_span_is_on_the_callers_thread(tmp_path):
    metrics = Metrics()
    plain, managed = Future(), Future()

    def body():
        releasers = [
            threading.Timer(0.02, plain.set_result, (1,)),
            threading.Timer(0.06, managed.set_result, (2,)),
        ]
        with span("test::caller"):
            for r in releasers:
                r.start()
            assert Work(plain).wait() == 1
            assert Work(managed, metrics).then(lambda v: v + 1).wait() == 3
            for r in releasers:
                r.join()

    events = _captured(tmp_path, body)
    caller = _one(events, "test::caller")
    waits = [e for e in events if e[1] == "torchft::work_wait"]
    assert len(waits) == 2
    for wait in waits:
        assert wait[0] == caller[0]  # the thread that called wait()
        assert caller[2] <= wait[2] and wait[3] <= caller[3]
    # a Work that knows its manager's Metrics also feeds the timer; a
    # wait on finished work is no sample
    assert Work(managed, metrics).wait() == 2
    assert metrics.snapshot()["timers_s"]["work_wait"]["n"] == 1


def test_wait_quorum_span_is_on_the_callers_thread(tmp_path):
    from torchft_tpu._native import QuorumResult
    from torchft_tpu.collectives import DummyCollectives
    from torchft_tpu.manager import MANAGER_ADDR_KEY, REPLICA_ID_KEY, Manager

    store = Store()
    client = StoreClient(store.address())
    client.set(MANAGER_ADDR_KEY, b"mock://manager")
    client.set(REPLICA_ID_KEY, b"testrep")
    with patch("torchft_tpu.manager.ManagerClient") as cls:
        result = QuorumResult(
            quorum_id=1, replica_rank=0, replica_world_size=2,
            recover_src_manager_address="", recover_src_rank=None,
            recover_dst_ranks=[], store_address="localhost:0", max_step=0,
            max_rank=0, max_world_size=2, heal=False,
        )
        # slow enough that the caller really waits: a settled quorum holds
        # nobody and is no sample
        cls.return_value.quorum.side_effect = (
            lambda **_: (time.sleep(0.05), result)[1]
        )
        manager = Manager(
            collectives=DummyCollectives(), load_state_dict=None,
            state_dict=None, min_replica_size=2, rank=1, world_size=2,
            timeout=timedelta(seconds=10), store_addr=store.address(),
            checkpoint_transport=MagicMock(
                metadata=MagicMock(return_value="meta")
            ),
        )
        manager.load_state_dict({"step": 5, "batches_committed": 0})
        try:
            def body():
                with span("test::caller"):
                    manager.start_quorum()
                    manager.wait_quorum()

            events = _captured(tmp_path, body)
        finally:
            manager.shutdown()
            store.shutdown()
    caller = _one(events, "test::caller")
    wait = _one(events, "torchft::quorum_wait")
    quorum = _one(events, "torchft::quorum")
    assert wait[0] == caller[0] and quorum[0] != caller[0]
    assert wait[4] == quorum[4] == {"step": 5}
    timers = manager.metrics().snapshot()["timers_s"]
    assert timers["quorum_wait"]["n"] == 1 and timers["quorum"]["n"] == 1


def _ring_pair(store, prefix, **kwargs):
    cols = [
        HostCollectives(timeout=timedelta(seconds=15), **kwargs)
        for _ in range(2)
    ]
    addr = f"{store.address()}/{prefix}"
    with ThreadPoolExecutor(max_workers=2) as ex:
        for f in [ex.submit(cols[r].configure, addr, r, 2) for r in range(2)]:
            f.result()
    return cols


def _tree():
    return {"a": jnp.ones((64, 8), jnp.float32), "b": jnp.ones((32,), jnp.float32)}


# op -> (the call on one member, the keys the hand-timed code recorded and,
# on a line of their own, what PR 39 put beside them; every entry also has
# the op's own seconds, ``op_s``)
_CONVERTED_OPS = {
    "allreduce": (
        lambda c: c.allreduce(_tree(), ReduceOp.SUM).wait(),
        {"op", "bytes", "d2h_bytes", "chunks", "pack", "d2h", "ring", "h2d",
         "buckets",
         "ready", "d2h_calls", "ring_transport"},
    ),
    "allreduce_q8": (
        lambda c: c.allreduce(_tree(), ReduceOp.SUM, wire="q8").wait(),
        {"op", "bytes", "wire_bytes", "d2h_bytes", "d2h", "ring", "h2d",
         "stripe_s"},
    ),
    "allgather": (
        lambda c: c.allgather(_tree()).wait(),
        {"op", "bytes", "d2h_bytes", "pack", "d2h", "host_copy", "ring",
         "h2d", "stripe_s",
         "ready", "d2h_calls"},
    ),
    "reduce_scatter": (
        lambda c: c.reduce_scatter(_tree(), ReduceOp.SUM).wait(),
        {"op", "bytes", "shard_bytes", "wire_bytes", "d2h_bytes", "d2h",
         "ring", "h2d", "stripe_s"},
    ),
    "allgather_into": (
        lambda c: c.allgather_into(
            c.reduce_scatter(_tree(), ReduceOp.SUM).wait()
        ).wait(),
        {"op", "bytes", "wire_bytes", "d2h_bytes", "ring", "h2d", "stripe_s"},
    ),
    "plan_allreduce": (
        lambda c: c.plan_allreduce(_tree()).wait(),
        {"op", "wire", "device_pack", "bytes", "wire_bytes", "d2h_bytes",
         "d2h", "ring", "buckets", "py_staging_allocs", "plan_execs"},
    ),
    "plan_reduce_scatter": (
        lambda c: c.plan_reduce_scatter(_tree()).wait(),
        {"op", "wire", "bytes", "shard_bytes", "wire_bytes", "d2h_bytes",
         "d2h", "ring", "h2d", "buckets", "py_staging_allocs", "plan_execs"},
    ),
    "plan_allgather_into": (
        lambda c: c.plan_allgather_into(
            c.plan_reduce_scatter(_tree()).wait()
        ).wait(),
        {"op", "wire", "bytes", "wire_bytes", "d2h_bytes", "d2h", "ring",
         "h2d", "buckets", "plan_execs"},
    ),
}


@pytest.mark.parametrize("op", sorted(_CONVERTED_OPS))
def test_op_context_phases_nest_and_keep_the_old_keys(tmp_path, op):
    call, keys = _CONVERTED_OPS[op]
    store = Store()
    cols = _ring_pair(store, f"prof_{op}")
    cols[0].trace_step = 11
    try:
        def body():
            with ThreadPoolExecutor(max_workers=2) as ex:
                for f in [ex.submit(call, c) for c in cols]:
                    f.result()

        events = _captured(tmp_path, body)
        entries = [s for s in cols[0].pop_op_stats() if s["op"] == op]
    finally:
        for c in cols:
            c.shutdown()
        store.shutdown()
    assert len(entries) == 1 and set(entries[0]) == keys | {"op_s"}
    entry = entries[0]
    # member 0's op span (the one stamped with its step) and what nests in it
    (whole,) = [
        e for e in events
        if e[1] == f"torchft::{op}" and e[4].get("step") == 11
    ]
    phases = [
        e for e in events
        if e[1].startswith(f"torchft::{op}/") and e[0] == whole[0]
        and whole[2] <= e[2] and e[3] <= whole[3]
    ]
    names = {e[1].rsplit("/", 1)[1] for e in phases}
    assert names == keys & {"pack", "ready", "d2h", "host_copy", "ring", "h2d"}
    assert all(e[4] == {"step": 11} for e in phases)
    # the phases' sum is within the op, on both clocks
    assert sum(e[3] - e[2] for e in phases) <= whole[3] - whole[2]
    recorded = sum(entry[n] for n in names)
    assert recorded <= entry["op_s"]
    # the op's own seconds are its span's: the same statements on two clocks
    assert entry["op_s"] == pytest.approx((whole[3] - whole[2]) / 1e9, abs=5e-3)
    assert recorded == pytest.approx(
        sum(e[3] - e[2] for e in phases) / 1e9, abs=5e-3
    )


def test_timed_span_reads_what_its_span_covers(tmp_path):
    from torchft_tpu.profiling import timed_span

    def body():
        with timed_span("torchft::heal_fetch/meta", 3, bytes=5) as t:
            jnp.ones(4).block_until_ready()
        body.seconds = t.seconds

    event = _one(_captured(tmp_path, body), "torchft::heal_fetch/meta")
    assert event[4] == {"step": 3, "bytes": 5}
    assert body.seconds == pytest.approx((event[3] - event[2]) / 1e9, abs=2e-3)


def test_timed_files_one_timer_under_anothers_span_name(tmp_path):
    metrics = Metrics()
    metrics.step = 9

    def body():
        with metrics.timed("send_serve", span="send_checkpoint/serve", bytes=64):
            pass

    events = _captured(tmp_path, body)
    assert _one(events, "torchft::send_checkpoint/serve")[4] == {
        "step": 9, "bytes": 64,
    }
    assert not [e for e in events if e[1] == "torchft::send_serve"]
    assert metrics.snapshot()["timers_s"]["send_serve"]["n"] == 1


def test_allreduce_ready_parts_the_devices_wait_from_the_link(tmp_path):
    """``ready`` sits between ``pack`` and the first ``d2h``, nested in
    the op with the step, and holds the wait for the device: a gradient
    still being computed when the op starts is in ``ready``, not ``d2h``."""
    store = Store()
    cols = _ring_pair(store, "prof_ready", pipeline_chunks=1)
    cols[0].trace_step = 4
    slow = jax.jit(lambda x: jax.lax.fori_loop(
        0, 300, lambda _, a: jnp.tanh(a @ a) * 0.5 + 0.1, x
    ))
    slow(jnp.eye(256)).block_until_ready()  # compiled outside the op
    try:
        def body():
            trees = [{"a": slow(jnp.eye(256))} for _ in cols]
            with ThreadPoolExecutor(max_workers=2) as ex:
                for f in [
                    ex.submit(lambda c, t: c.allreduce(t).wait(), c, t)
                    for c, t in zip(cols, trees)
                ]:
                    f.result()

        events = _captured(tmp_path, body)
        (entry,) = [s for s in cols[0].pop_op_stats() if s["op"] == "allreduce"]
    finally:
        for c in cols:
            c.shutdown()
        store.shutdown()
    (whole,) = [
        e for e in events
        if e[1] == "torchft::allreduce" and e[4].get("step") == 4
    ]

    def phase(name):
        (found,) = [
            e for e in events
            if e[1] == f"torchft::allreduce/{name}" and e[0] == whole[0]
            and whole[2] <= e[2] and e[3] <= whole[3]
        ]
        return found

    pack, ready, d2h = phase("pack"), phase("ready"), phase("d2h")
    assert ready[4] == {"step": 4}
    assert pack[3] <= ready[2] and ready[3] <= d2h[2]
    assert entry["ready"] == pytest.approx((ready[3] - ready[2]) / 1e9, abs=5e-3)
    # the device's work is in ``ready``; the read after it is a copy
    assert entry["ready"] > entry["d2h"]


# -- JAX's compile events, heard by the process's start-up record ------------


@pytest.fixture
def record(monkeypatch):
    """A fresh start-up record in place of the process's own, with the
    listeners on (once a process, whichever test comes first)."""
    from torchft_tpu import startup

    fresh = startup.StartupRecord(started=time.monotonic(), imported=time.monotonic())
    monkeypatch.setattr(startup, "_record", fresh)
    startup.listen()
    return fresh


def _planted_step(x):
    return x * 2 + 1


def test_a_program_compiled_once_is_one_sample_and_no_recompile(record, caplog):
    x = jnp.ones(3)
    assert record.close(Metrics()) is not None  # past the first commit
    with caplog.at_level("WARNING", logger="torchft_tpu.startup"):
        jax.jit(_planted_step)(x).block_until_ready()
        jax.jit(_planted_step)(x).block_until_ready()  # jit's own cache
    assert record._compiled["jit(_planted_step)"] == 1
    snap = record.snapshot()
    assert snap["counters"]["compiles"] == snap["timers_s"]["compile"]["n"] >= 1
    assert "recompiles" not in snap["counters"] and not caplog.records
    # the persistent cache is off in the tests: nothing asked, nothing missed
    assert set(snap) == {"counters", "timers_s"}
    assert not {"compile_cache_hits", "compile_cache_misses"} & set(snap["counters"])


def test_a_second_shape_is_a_recompile_logged_with_the_step(record, caplog):
    manager_metrics = Metrics()
    manager_metrics.step = 41
    record.bind(manager_metrics)
    step = jax.jit(_planted_step)
    # made before the commit: past it JAX's own eager programs count too
    # (``jnp.ones`` at a new shape is a compile inside that step)
    first, second = jnp.ones(17), jnp.ones(19)  # shapes no other test compiles
    with caplog.at_level("WARNING", logger="torchft_tpu.startup"):
        step(first).block_until_ready()
        assert record.close(manager_metrics) is not None  # the first commit
        assert not caplog.records
        step(second).block_until_ready()  # the planted recompile
    assert record._compiled["jit(_planted_step)"] == 2
    assert record.snapshot()["counters"]["recompiles"] == 1
    (warning,) = caplog.records
    text = warning.getMessage()
    assert "jit(_planted_step)" in text and "step 41" in text and " s at " in text
    # and the manager's snapshot carries the record
    assert manager_metrics.snapshot()["process"]["counters"]["recompiles"] == 1


def test_a_lambda_never_counts_and_nothing_does_before_the_first_commit(record, caplog):
    with caplog.at_level("WARNING", logger="torchft_tpu.startup"):
        for n in (3, 5, 7):  # a start-up: one name at many shapes, by design
            jax.jit(_planted_step)(jnp.ones((n, 2))).block_until_ready()
            (jnp.ones(n) + jnp.arange(n)).block_until_ready()  # jit(add) a shape
        inputs = [jnp.ones(n) for n in (3, 5, 7)]
        assert record.close(Metrics()) is not None
        for x in inputs:
            jax.jit(lambda x: x - 1)(x).block_until_ready()
    snap = record.snapshot()
    assert snap["counters"]["compiles"] >= 9  # all of them are compiles
    assert record._compiled["jit(_planted_step)"] == 3
    assert "recompiles" not in snap["counters"] and not caplog.records


def test_a_listener_that_fails_logs_and_does_not_raise_into_the_compile(
    record, monkeypatch, caplog
):
    def broken(*_):
        raise RuntimeError("planted")

    monkeypatch.setattr(record, "compiled", broken)
    monkeypatch.setattr(record, "cache", broken)
    with caplog.at_level("ERROR", logger="torchft_tpu.startup"):
        # JAX calls these inside jit: profiling must not take down training
        jax.jit(_planted_step)(jnp.ones((2, 23))).block_until_ready()
        from torchft_tpu import startup

        startup._on_event(startup._MISS)
    assert len(caplog.records) >= 2
    assert all("uncounted" in r.getMessage() for r in caplog.records)


def test_compiles_up_to_the_first_commit_are_the_startups(record):
    jax.jit(_planted_step)(jnp.ones(11)).block_until_ready()
    before = record.snapshot()["timers_s"]["compile"]
    assert record.close(Metrics()) is not None
    jax.jit(_planted_step)(jnp.ones(13)).block_until_ready()  # after the commit
    snap = record.snapshot()
    assert snap["timers_s"]["compile"]["n"] > before["n"]  # the life's timer goes on
    assert snap["timers_s"]["startup_compile"] == {
        "n": 1, **{k: pytest.approx(before["total_s"], abs=2e-6) for k in ("total_s", "p50", "p90", "max")}
    }
    assert snap["counters"]["startup_cache_misses"] == 0


@pytest.mark.parametrize("program, listening, has_jax", [
    ("import torchft_tpu.launcher", False, False),
    ("import torchft_tpu.manager; from torchft_tpu import FTTrainState", False, False),
    ("import jax; import torchft_tpu", True, True),
    ("import torchft_tpu; import jax; from torchft_tpu.profiling import span; span('x')", True, True),
    ("import torchft_tpu; from torchft_tpu.platform import apply_compilation_cache_env as on; on()", True, True),
    ("import torchft_tpu; import jax; torchft_tpu.FTTrainState(1, None, 1)", True, True),
], ids=["launcher", "manager", "jax_first", "span", "cache_env", "train_state"])
def test_who_listens(program, listening, has_jax):
    """The launcher's parent holds no ``jax`` and registers nothing; a
    trainer is heard from the package's import if it holds ``jax`` by
    then, else from the package's first own use of it."""
    import subprocess
    import sys

    check = (
        f"{program}; import sys; from torchft_tpu import startup; "
        f"assert ('jax' in sys.modules) is {has_jax}, sorted(sys.modules); "
        f"assert startup._listening is {listening}"
    )
    if listening:
        check += (
            "; from jax._src import monitoring; "
            "assert startup._on_event in monitoring.get_event_listeners(); "
            "assert startup._on_duration in monitoring.get_event_duration_listeners(); "
            "startup.listen(); assert monitoring.get_event_listeners().count(startup._on_event) == 1"
        )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c", check], env=env, capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert done.returncode == 0, done.stderr[-2000:]
