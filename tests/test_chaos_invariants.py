"""Chaos-plane invariants: the seeded fault engine, the CRC-guarded wire,
the stall verdict, heal-range integrity, and the replayable step-
transaction harness (a fixed small seed set — the CI gate; the broad
seeded sweep lives in scripts/chaos_run.py).

The load-bearing claims proven here:

- DETERMINISM: a FaultPlan is a pure function of its seed; the native
  engine's firing decisions replay from (seed, plan).
- DETECTION: a wire bit flip (or stream-desyncing duplicate) on any ring
  path with TORCHFT_WIRE_CRC on raises the typed WireCorruption — and
  with CRC off the same flip commits silently (the gap the CRC closes,
  pinned as a test so the motivation stays true).
- ZERO ADDED COST OFF: with CRC off the wire carries EXACTLY the
  pre-CRC byte count (measured per-tier tx, not a model), and on it
  carries exactly +4 bytes per frame — the single-branch contract.
- STALL VERDICT: a SIGSTOPped child surfaces as ChildStalledError
  within the stall grace, never the op timeout masquerade.
- TRANSACTION INVARIANTS: seeded schedules over a real multi-member TCP
  fleet commit no step under mixed quorum epochs, end bit-identical,
  never commit a corrupted step, and recover to a clean commit.
"""

import os
import signal
import socket
import subprocess
import sys
import threading
import time
from datetime import timedelta

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "scripts"))

import torchft_tpu._native as _native  # noqa: E402
from torchft_tpu._native import Store, WireCorruption  # noqa: E402
from torchft_tpu.chaos import (  # noqa: E402
    ChaosInjector,
    FaultEvent,
    FaultPlan,
    HealFaultProxy,
    splitmix64,
)
from torchft_tpu.collectives import HostCollectives  # noqa: E402
from torchft_tpu.isolated_xla import (  # noqa: E402
    ChildDiedError,
    ChildStalledError,
    _MonitoredChannel,
)

import chaos_run  # noqa: E402


@pytest.fixture(autouse=True)
def _disarm_after():
    yield
    _native.fault_disarm()


@pytest.fixture
def store():
    s = Store()
    yield s
    s.shutdown()


def _make_ring(store, n, prefix, crc, stripes=1, timeout_s=10):
    cols = [
        HostCollectives(
            timeout=timedelta(seconds=timeout_s),
            stripes=stripes,
            wire_crc=crc,
        )
        for _ in range(n)
    ]
    threads = [
        threading.Thread(
            target=cols[r].configure,
            args=(f"{store.address()}/{prefix}", r, n),
        )
        for r in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return cols


def _run_all(cols, fn):
    out = [None] * len(cols)
    errs = [None] * len(cols)

    def run(r):
        try:
            out[r] = fn(cols[r], r)
        except Exception as e:  # noqa: BLE001 - the errors ARE the data
            errs[r] = e

    threads = [
        threading.Thread(target=run, args=(r,)) for r in range(len(cols))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out, errs


class TestFaultPlan:
    def test_random_is_deterministic_in_seed(self):
        a = FaultPlan.random(123, steps=10, members=4)
        b = FaultPlan.random(123, steps=10, members=4)
        c = FaultPlan.random(124, steps=10, members=4)
        assert a == b
        assert a != c

    def test_json_roundtrip(self):
        plan = FaultPlan.random(7, steps=6, members=3,
                                seams=("ring_send", "net_send"))
        assert FaultPlan.from_json(plan.to_json()) == plan

    def test_step_zero_stays_clean(self):
        for seed in range(20):
            plan = FaultPlan.random(seed, steps=5, members=2)
            assert all(e.step >= 1 for e in plan.events)

    def test_native_rules_cover_only_native_seams(self):
        plan = FaultPlan(
            seed=1,
            events=(
                FaultEvent(2, "ring_send", "bit_flip", 0),
                FaultEvent(2, "child", "sigstop", 1),
            ),
        )
        rules = plan.native_rules(2)
        assert len(rules) == 1 and rules[0]["seam"] == "ring_send"
        assert rules[0]["max_fires"] == 1 and rules[0]["permille"] == 1000

    def test_fingerprint_replays(self):
        plan = FaultPlan.random(55, steps=8, members=2)
        fp = plan.fingerprint()
        assert FaultPlan.from_json(fp["plan"]) == plan
        assert fp["seed"] == 55

    def test_splitmix64_matches_native_backoff_mixer(self):
        # Same constants as native mix64 (net.cc splitmix64): pin a known
        # value so the two streams can never drift silently.
        assert splitmix64(0) == 0xE220A8397B1DCDAF


class TestNativeFaultEngine:
    def test_arm_disarm_states(self):
        assert not _native.fault_armed()
        _native.fault_arm({"seed": 1, "rules": [
            {"seam": "ring_send", "kind": "drop"}]})
        assert _native.fault_armed()
        _native.fault_arm({"seed": 1, "rules": []})
        assert not _native.fault_armed()  # empty rules = disarmed
        _native.fault_disarm()
        stats = _native.fault_stats()
        assert stats["fired_total"] == 0

    def test_bad_plan_raises(self):
        with pytest.raises(RuntimeError, match="unknown seam"):
            _native.fault_arm({"seed": 1, "rules": [
                {"seam": "nope", "kind": "drop"}]})
        with pytest.raises(RuntimeError, match="unknown kind"):
            _native.fault_arm({"seed": 1, "rules": [
                {"seam": "ring_send", "kind": "nope"}]})

    def test_permille_zero_never_fires(self, store):
        cols = _make_ring(store, 2, "pz", crc=True)
        _native.fault_arm({"seed": 3, "rules": [
            {"seam": "ring_send", "kind": "bit_flip", "permille": 0}]})
        out, errs = _run_all(
            cols,
            lambda c, r: c.allreduce(
                {"w": np.ones(256, dtype=np.float32)}
            ).wait(),
        )
        assert all(e is None for e in errs), errs
        assert _native.fault_stats()["fired_total"] == 0
        for c in cols:
            c.shutdown()


class TestWireCrc:
    def test_crc32c_known_vector(self):
        assert _native.crc32c(b"123456789") == 0xE3069283
        assert _native.crc32c_combine([b"1234", b"56789"]) == 0xE3069283
        assert _native.crc32c(memoryview(bytearray(b"123456789"))) == (
            0xE3069283
        )

    @pytest.mark.parametrize("path,wire", [
        ("legacy", None),
        ("legacy", "q8"),
        ("plan", None),
        ("plan", "bf16"),
        ("plan", "q8"),
    ])
    def test_clean_ops_bit_identical_crc_on(self, store, path, wire):
        """CRC is pure framing: results with the guarded wire match the
        raw wire bit for bit on every encoding and both schedule paths."""
        tree = {"w": (np.arange(4096) % 17).astype(np.float32)}
        results = {}
        for crc in (False, True):
            cols = _make_ring(store, 2, f"id{int(crc)}{path}{wire}", crc=crc)
            if path == "legacy":
                fn = lambda c, r: c.allreduce(dict(tree), wire=wire).wait()
            else:
                fn = lambda c, r: c.plan_allreduce(
                    dict(tree), wire=wire
                ).wait()
            out, errs = _run_all(cols, fn)
            assert all(e is None for e in errs), errs
            assert out[0]["w"].tobytes() == out[1]["w"].tobytes()
            results[crc] = out[0]["w"].tobytes()
            for c in cols:
                c.shutdown()
        assert results[False] == results[True]

    def test_bit_flip_detected_with_crc(self, store):
        cols = _make_ring(store, 2, "bf", crc=True)
        _native.fault_arm({"seed": 42, "rules": [
            {"seam": "ring_send", "kind": "bit_flip", "member": 0,
             "max_fires": 1}]})
        out, errs = _run_all(
            cols,
            lambda c, r: c.allreduce(
                {"w": np.ones(2048, dtype=np.float32)}
            ).wait(),
        )
        stats = _native.fault_stats()
        assert stats["fired"].get("ring_send:bit_flip") == 1
        assert any(isinstance(e, WireCorruption) for e in errs if e), errs
        for c in cols:
            c.shutdown()

    def test_bit_flip_silent_without_crc(self, store):
        """The motivating gap, pinned: with CRC off the same flip decodes
        cleanly and COMMITS wrong bytes — the one failure the vote cannot
        catch. If this test ever fails, the raw wire grew a payload check
        and the CRC knob's rationale needs rewriting."""
        cols = _make_ring(store, 2, "bfoff", crc=False)
        _native.fault_arm({"seed": 42, "rules": [
            {"seam": "ring_send", "kind": "bit_flip", "member": 0,
             "max_fires": 1}]})
        out, errs = _run_all(
            cols,
            lambda c, r: c.allreduce(
                {"w": np.ones(2048, dtype=np.float32)}
            ).wait(),
        )
        assert all(e is None for e in errs), errs
        corrupted = (
            out[0]["w"].tobytes() != out[1]["w"].tobytes()
            or not np.all(out[0]["w"] == 1.0)
        )
        assert corrupted
        for c in cols:
            c.shutdown()

    @pytest.mark.parametrize("path,wire", [
        ("legacy", None),
        ("legacy", "q8"),
        ("plan", None),
        ("plan", "bf16"),
        ("plan", "q8"),
        ("hier", None),
    ])
    def test_bit_flip_detected_every_wire_and_path(self, store, path, wire):
        """The acceptance matrix: a mid-ring payload bit flip is
        DETECTED on every wire encoding and schedule path — the step
        errors (latch -> vote discard), never a clean commit of
        poisoned bytes."""
        regions = ["r0", "r1"] if path == "hier" else None
        cols = [
            HostCollectives(timeout=timedelta(seconds=10), stripes=1,
                            wire_crc=True)
            for _ in range(2)
        ]
        threads = [
            threading.Thread(
                target=cols[r].configure,
                args=(f"{store.address()}/m{path}{wire}", r, 2, regions),
            )
            for r in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _native.fault_arm({"seed": 11, "rules": [
            {"seam": "ring_send", "kind": "bit_flip", "member": 0,
             "max_fires": 1}]})
        tree = {"w": np.ones(8192, dtype=np.float32)}
        if path == "legacy":
            fn = lambda c, r: c.allreduce(dict(tree), wire=wire).wait()
        elif path == "plan":
            fn = lambda c, r: c.plan_allreduce(dict(tree), wire=wire).wait()
        else:
            fn = lambda c, r: c.allreduce_hier(dict(tree)).wait()
        out, errs = _run_all(cols, fn)
        stats = _native.fault_stats()
        assert stats["fired"].get("ring_send:bit_flip") == 1, stats
        fails = [e for e in errs if e is not None]
        assert fails, f"flip committed cleanly on {path}/{wire}"
        texts = " | ".join(str(e) for e in fails)
        assert (
            "wire corruption" in texts or "protocol desync" in texts
        ), texts
        for c in cols:
            c.shutdown()

    def test_bit_flip_typed_detection_survives_striping(self, store):
        """With stripes > 1 the corrupted stripe's shutdown makes its
        SIBLINGS die with generic socket errors; the TYPED
        WireCorruption must still be the error the victim member
        surfaces (run_striped prefers it over stripe order) — the
        detection ledger cannot depend on which stripe lost the race."""
        cols = _make_ring(store, 2, "bfstr", crc=True, stripes=4)
        _native.fault_arm({"seed": 21, "rules": [
            {"seam": "ring_send", "kind": "bit_flip", "member": 0,
             "max_fires": 1}]})
        # large enough that all 4 stripes are active (>= 64 KiB each)
        out, errs = _run_all(
            cols,
            lambda c, r: c.allreduce(
                {"w": np.ones(1 << 17, dtype=np.float32)}
            ).wait(),
        )
        assert _native.fault_stats()["fired"].get("ring_send:bit_flip") == 1
        assert any(isinstance(e, WireCorruption) for e in errs if e), [
            f"{type(e).__name__}: {e}" for e in errs if e
        ]
        for c in cols:
            c.shutdown()

    def test_duplicate_detected_with_crc(self, store):
        cols = _make_ring(store, 2, "dup", crc=True)
        _native.fault_arm({"seed": 8, "rules": [
            {"seam": "ring_send", "kind": "duplicate", "member": 1,
             "max_fires": 1}]})
        out, errs = _run_all(
            cols,
            lambda c, r: c.allreduce(
                {"w": np.ones(4096, dtype=np.float32)}
            ).wait(),
        )
        # the shifted stream must surface as a typed integrity/desync
        # error somewhere in the ring — never a clean commit
        assert any(e is not None for e in errs)
        texts = " | ".join(str(e) for e in errs if e)
        assert "wire corruption" in texts or "protocol desync" in texts
        for c in cols:
            c.shutdown()

    def test_crc_mismatch_fails_fast_at_negotiation(self, store):
        cols = [
            HostCollectives(timeout=timedelta(seconds=5), stripes=1,
                            wire_crc=(r == 0))
            for r in range(2)
        ]
        out, errs = _run_all(
            cols,
            lambda c, r: c.configure(f"{store.address()}/mix", r, 2),
        )
        assert any(
            e is not None and "mismatch" in str(e) for e in errs
        ), errs
        for c in cols:
            c.shutdown()

    def test_header_desync_error_names_the_edge(self, store):
        """The enriched protocol-desync error: tier, peer address, op
        kind and op index — a W=8 fleet log must name the guilty edge."""
        cols = _make_ring(store, 2, "hdr", crc=False)
        _native.fault_arm({"seed": 4, "rules": [
            {"seam": "ring_hdr", "kind": "bit_flip", "member": 0,
             "max_fires": 1}]})
        out, errs = _run_all(
            cols,
            lambda c, r: c.allreduce(
                {"w": np.ones(128, dtype=np.float32)}
            ).wait(),
        )
        texts = [str(e) for e in errs if e is not None]
        assert texts, "header corruption surfaced nowhere"
        desync = [t for t in texts if "protocol desync" in t]
        assert desync, texts
        for key in ("tier=", "prev_peer=", "op_kind=", "op_index="):
            assert key in desync[0], desync[0]
        for c in cols:
            c.shutdown()


class TestCrcAccounting:
    def test_crc_off_adds_zero_wire_bytes_and_on_adds_4_per_frame(
        self, store
    ):
        """The single-branch contract, proven on MEASURED bytes: with
        CRC off the inter tier ships exactly the analytic pre-CRC byte
        count (header 24B + one chunk per rs/ag hop), and with CRC on
        exactly 4 more per frame (3 frames here: header, rs hop, ag
        hop). Any hidden cost in the off path would break the equality,
        not a tolerance."""
        count = 1024  # f32 elems; W=2 chunks of 512
        analytic_off = 24 + (count // 2) * 4 + (count // 2) * 4
        measured = {}
        for crc in (False, True):
            cols = [
                HostCollectives(timeout=timedelta(seconds=10), stripes=1,
                                wire_crc=crc)
                for _ in range(2)
            ]
            threads = [
                threading.Thread(
                    target=cols[r].configure,
                    args=(f"{store.address()}/acct{int(crc)}", r, 2,
                          ["r0", "r1"]),
                )
                for r in range(2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert cols[0].hier_capable()
            out, errs = _run_all(
                cols,
                lambda c, r: c.allreduce_hier(
                    {"w": np.ones(count, dtype=np.float32)}
                ).wait(),
            )
            assert all(e is None for e in errs), errs
            measured[crc] = cols[0]._last_hier_dict()["inter_tx_bytes"]
            for c in cols:
                c.shutdown()
        assert measured[False] == analytic_off
        assert measured[True] == analytic_off + 4 * 3


class _Sleeper:
    """A real child process for the monitored-channel verdict tests."""

    def __enter__(self):
        self.proc = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(120)"]
        )
        a, b = socket.socketpair()
        self.sock_a = a
        self.sock_b = b
        self.channel = _MonitoredChannel(
            a, self.proc.poll, pid=self.proc.pid
        )
        return self

    def __exit__(self, *exc):
        try:
            self.proc.kill()
            self.proc.wait(timeout=5)
        except Exception:
            pass
        self.sock_a.close()
        self.sock_b.close()


class TestStallVerdict:
    def test_sigstop_surfaces_as_stall_within_grace(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_ISO_STALL_MS", "300")
        with _Sleeper() as s:
            os.kill(s.proc.pid, signal.SIGSTOP)
            t0 = time.monotonic()
            with pytest.raises(ChildStalledError, match="STALLED"):
                s.channel.recv(timeout_s=10.0)
            took = time.monotonic() - t0
            os.kill(s.proc.pid, signal.SIGCONT)
        # verdict at the grace, not the 10 s deadline
        assert took < 5.0, took

    def test_running_child_times_out_not_stalls(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_ISO_STALL_MS", "300")
        with _Sleeper() as s:
            with pytest.raises(TimeoutError):
                s.channel.recv(timeout_s=0.8)

    def test_dead_child_is_died_not_stalled(self):
        with _Sleeper() as s:
            s.proc.kill()
            s.proc.wait(timeout=5)
            with pytest.raises(ChildDiedError) as ei:
                s.channel.recv(timeout_s=5.0)
            assert not isinstance(ei.value, ChildStalledError)

    def test_brief_stop_within_grace_is_not_a_verdict(self, monkeypatch):
        """A SIGSTOP/SIGCONT pulse shorter than the grace (a debugger
        attach, a cgroup freeze blip) must NOT kill the child's op."""
        monkeypatch.setenv("TORCHFT_ISO_STALL_MS", "2000")
        with _Sleeper() as s:
            os.kill(s.proc.pid, signal.SIGSTOP)

            def cont():
                time.sleep(0.3)
                os.kill(s.proc.pid, signal.SIGCONT)
                time.sleep(0.2)
                s.sock_b.sendall(b'{"ok": 1}\n')

            t = threading.Thread(target=cont)
            t.start()
            msg = s.channel.recv(timeout_s=10.0)
            t.join()
            assert msg == {"ok": 1}


class TestHealRangeCrc:
    def _publish(self, nbytes=1 << 16):
        from torchft_tpu.checkpointing import CheckpointServer

        srv = CheckpointServer(timeout=timedelta(seconds=10))
        state = {
            "params": {
                "w": np.arange(nbytes // 4, dtype=np.float32)
            }
        }
        srv.send_checkpoint(
            [1], step=1, state_dict=state, timeout=timedelta(seconds=10)
        )
        return srv, state

    def test_range_header_matches_body(self):
        import urllib.parse
        import urllib.request

        srv, _state = self._publish()
        try:
            base = srv.address()
            with urllib.request.urlopen(
                f"{base}1/stream/0/2/none/1", timeout=10
            ) as resp:
                want = resp.headers["X-TFT-Crc32c"]
                body = resp.read()
            assert want is not None
            assert int(want, 16) == _native.crc32c(body)
        finally:
            srv.shutdown()

    def test_corrupted_range_detected_and_fallback_correct(self):
        import urllib.parse

        from torchft_tpu.checkpointing import CheckpointServer

        srv, state = self._publish()
        parts = urllib.parse.urlparse(srv.address())
        proxy = HealFaultProxy(
            f"{parts.scheme}://{parts.netloc}",
            mode="bit_flip",
            only_paths=("/stream/",),
            max_faults=1,
        )
        try:
            out, stats = CheckpointServer._fetch(
                proxy.address() + parts.path + "1",
                timeout=timedelta(seconds=15),
            )
            assert proxy.faults_fired == 1
            # detected -> NOT the stream path; bytes still exact
            assert stats["path"] != "stream"
            np.testing.assert_array_equal(
                out["params"]["w"], state["params"]["w"]
            )
        finally:
            proxy.shutdown()
            srv.shutdown()


class TestTransactionInvariants:
    """The CI chaos-invariant gate: fixed small seeds through the REAL
    fleet harness (scripts/chaos_run.py), one schedule per data-plane
    configuration. The broad random sweep (more seeds, every seam, the
    policy fleet, the iso probes) is scripts/chaos_run.py's full run."""

    def _flip_plan(self, member=0, step=2):
        return FaultPlan(
            seed=7,
            events=(
                FaultEvent(step, "ring_send", "bit_flip", member),
            ),
        )

    def test_ddp_bit_flip_discarded_then_recovers(self):
        rec = chaos_run.run_schedule(
            7, "ddp", groups=2, steps=4, plan=self._flip_plan(),
            deadline_s=120,
        )
        assert rec["crc_detections"] >= 1
        assert rec["silent_commits"] == 0
        assert rec["liveness_ok"] and rec["bit_identity_ok"]

    def test_plan_path_seeded_schedule(self):
        rec = chaos_run.run_schedule(
            1031, "plan", groups=2, steps=4,
            plan=FaultPlan(
                seed=1031,
                events=(
                    FaultEvent(1, "ring_send", "bit_flip", 1),
                    FaultEvent(2, "ring_send", "drop", 0),
                ),
            ),
            deadline_s=120,
        )
        assert rec["crc_detections"] >= 1
        assert rec["epoch_purity_ok"] and rec["bit_identity_ok"]

    @pytest.mark.slow
    def test_hier_seeded_schedule(self):
        rec = chaos_run.run_schedule(
            9000, "hier", groups=4, steps=6,
            plan=FaultPlan(
                seed=9000,
                events=(
                    FaultEvent(2, "ring_send", "bit_flip", 0),
                    FaultEvent(3, "ring_send", "partition", 2),
                ),
            ),
            deadline_s=240,
        )
        assert rec["crc_detections"] >= 1
        assert rec["liveness_ok"]

    @pytest.mark.slow
    def test_random_seeds_ddp(self):
        for seed in (101, 202):
            rec = chaos_run.run_schedule(
                seed, "ddp", groups=3, steps=6, deadline_s=240
            )
            assert rec["silent_commits"] == 0
