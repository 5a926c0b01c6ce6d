"""Lighthouse HTTP dashboard + launcher tests.
Dashboard parity with reference templates/ + src/lighthouse.rs:320-437."""

import sys
import urllib.request
from datetime import timedelta

import pytest

from torchft_tpu._native import (
    Lighthouse,
    Manager,
    ManagerClient,
    Store,
)
from torchft_tpu.launcher import launch, replica_group_spec


def _get(url: str) -> str:
    with urllib.request.urlopen(url, timeout=10) as f:
        return f.read().decode()


class TestDashboard:
    def test_index_and_status(self):
        lh = Lighthouse(bind="[::]:0", min_replicas=1)
        try:
            base = lh.address()
            index = _get(base + "/")
            assert "lighthouse" in index
            status = _get(base + "/status")
            assert "Quorum" in status

            # With a live member, status shows its card and heartbeat age.
            store = Store()
            m = Manager(
                "dash_rep", lh.address(), "localhost", "[::]:0",
                store.address(), 1,
            )
            client = ManagerClient(m.address())
            client.quorum(0, 3, "md", timeout=timedelta(seconds=10))
            status = _get(base + "/status")
            assert "dash_rep" in status
            assert "Kill" in status
            assert "Heartbeats" in status
            # Quorum age + event log (reference templates/status.html shows
            # the quorum's live state; heal/membership transitions logged).
            assert ", age " in status
            assert "Events" in status
            assert "quorum 1: 1 member" in status
            m.shutdown()
            store.shutdown()
        finally:
            lh.shutdown()

    def test_kill_unknown_replica_404(self):
        lh = Lighthouse(bind="[::]:0", min_replicas=1)
        try:
            req = urllib.request.Request(
                lh.address() + "/replica/nope/kill", method="POST"
            )
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=10)
            assert e.value.code == 404
        finally:
            lh.shutdown()

    def test_unknown_path_404(self):
        lh = Lighthouse(bind="[::]:0", min_replicas=1)
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(lh.address() + "/bogus", timeout=10)
            assert e.value.code == 404
        finally:
            lh.shutdown()


class TestLauncher:
    def test_spec_env_plumbing(self):
        spec = replica_group_spec(
            ["python", "x.py"], 1, 4, "http://lh:1", env={"EXTRA": "1"}
        )
        assert spec["env"]["REPLICA_GROUP_ID"] == "1"
        assert spec["env"]["NUM_REPLICA_GROUPS"] == "4"
        assert spec["env"]["TORCHFT_LIGHTHOUSE"] == "http://lh:1"
        assert spec["env"]["EXTRA"] == "1"
        assert spec["max_restarts"] == 10

    def test_launch_restarts_failed_group(self, tmp_path):
        # Each group fails once (marker file), then succeeds: the supervisor
        # must restart it (the reference's torchelastic max_restarts role).
        script = tmp_path / "flaky.py"
        script.write_text(
            "import os, sys\n"
            "marker = os.path.join(\n"
            "    os.path.dirname(os.path.abspath(__file__)),\n"
            "    'marker_' + os.environ['REPLICA_GROUP_ID'],\n"
            ")\n"
            "if not os.path.exists(marker):\n"
            "    open(marker, 'w').close()\n"
            "    sys.exit(1)\n"
            "sys.exit(0)\n"
        )
        rc = launch(
            [sys.executable, str(script)],
            num_replica_groups=2,
            lighthouse_addr="http://unused:1",
            max_restarts=2,
        )
        assert rc == 0
        assert (tmp_path / "marker_0").exists()
        assert (tmp_path / "marker_1").exists()

    def test_launch_hot_spare_promotion(self, tmp_path):
        # --hot-spare policy: the dead primary is replaced by PROMOTING
        # the pre-warmed standby (which was parked in standby_gate), not
        # by a cold restart. The promoted process proves it came through
        # the gate by writing a marker only standbys write.
        import os

        script = tmp_path / "spare.py"
        script.write_text(
            "import os, sys\n"
            f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
            "from torchft_tpu.platform import standby_gate\n"
            "d = os.path.dirname(os.path.abspath(__file__))\n"
            "if os.environ.get('TORCHFT_STANDBY_FILE'):\n"
            "    standby_gate()\n"
            "    open(os.path.join(d, 'promoted'), 'w').close()\n"
            "    sys.exit(0)\n"
            "if not os.path.exists(os.path.join(d, 'died')):\n"
            "    open(os.path.join(d, 'died'), 'w').close()\n"
            "    sys.exit(1)\n"
            "sys.exit(0)\n"
        )
        rc = launch(
            [sys.executable, str(script)],
            num_replica_groups=1,
            lighthouse_addr="http://unused:1",
            max_restarts=2,
            hot_spare=True,
        )
        assert rc == 0
        assert (tmp_path / "died").exists()
        assert (tmp_path / "promoted").exists()

    def test_a_spawned_child_is_stamped_and_a_restart_says_so(self, tmp_path, caplog):
        # The start-up record of a launcher's child counts from the
        # launcher's Popen (startup.SPAWN_STAMP, set by the launcher and
        # by nobody else); a restarted life also knows which restart it is
        # and how long after the death it was spawned, and the parent
        # logs one line a restart with it. The parent holds no JAX.
        import json
        import os

        from torchft_tpu import startup

        script = tmp_path / "stamped.py"
        script.write_text(
            "import json, os, sys, time\n"
            f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
            "time.sleep(0.2)  # the trainer's own imports before the package's\n"
            "from torchft_tpu import startup\n"
            "from torchft_tpu.metrics import Metrics\n"
            "record = startup.record()\n"
            "record.close(Metrics())\n"
            "d = os.path.dirname(os.path.abspath(__file__))\n"
            "life = len([n for n in os.listdir(d) if n.startswith('life_')])\n"
            "with open(os.path.join(d, f'life_{life}.json'), 'w') as f:\n"
            "    json.dump({'stamp': os.environ[startup.SPAWN_STAMP], 'ppid': os.getppid(),\n"
            "               'jax': 'jax' in sys.modules, **record.snapshot()}, f)\n"
            "sys.exit(1 if life == 0 else 0)\n"
        )
        with caplog.at_level("INFO", logger="torchft_tpu.launcher"):
            rc = launch(
                [sys.executable, str(script)], num_replica_groups=1,
                lighthouse_addr="http://unused:1", max_restarts=2,
            )
        assert rc == 0
        first, second = (
            json.loads((tmp_path / f"life_{i}.json").read_text()) for i in (0, 1)
        )
        for life in (first, second):
            assert life["ppid"] == os.getpid() == int(life["stamp"].split()[0])
            assert not life["jax"]
            # the interpreter's start and the sleep, from the Popen on
            assert 0.2 <= life["timers_s"]["spawn_to_import"]["total_s"] < 30.0
        assert len(first["stamp"].split()) == 3 and first["stamp"].split()[2] == "0"
        assert "death_to_spawn" not in first["timers_s"] and "restart" not in first["counters"]
        assert second["counters"]["restart"] == 1 and len(second["stamp"].split()) == 4
        assert 0.0 <= second["timers_s"]["death_to_spawn"]["total_s"] < 5.0
        (line,) = [r.getMessage() for r in caplog.records if "after its death" in r.getMessage()]
        assert line.startswith("replica_group_0: restart 1 spawned ")
        assert startup.SPAWN_STAMP not in os.environ  # the children's, not ours

    def test_a_hand_started_process_reads_its_start_from_the_os(self, monkeypatch):
        import os
        import time

        from torchft_tpu import startup

        # no stamp, a stamp set for another process (a grandchild inherits
        # the environment, not the start), a stamp that is no stamp
        for stamp in (None, f"{os.getppid() + 1} {time.time()!r} 0", "x y", ""):
            if stamp is None:
                monkeypatch.delenv(startup.SPAWN_STAMP, raising=False)
            else:
                monkeypatch.setenv(startup.SPAWN_STAMP, stamp)
            assert startup._read_stamp() is None
            record = startup.StartupRecord.of_this_process()
            age = record._imported - record._started
            # this test process has lived a while, and the OS knows it
            assert 0.0 < age < 24 * 3600.0
            assert age == pytest.approx(
                startup._os_age_s() - (time.monotonic() - startup._IMPORTED), abs=0.05
            )
        # our parent's stamp: spawned 3 s before the package's import
        then = time.time() - (time.monotonic() - startup._IMPORTED) - 3.0
        monkeypatch.setenv(
            startup.SPAWN_STAMP, f"{os.getppid()} {then!r} 2 {then - 0.5!r}"
        )
        spawned, restart, died = startup._read_stamp()
        assert restart == 2 and spawned - died == pytest.approx(0.5, abs=1e-3)
        record = startup.StartupRecord.of_this_process()
        assert record._imported - record._started == pytest.approx(3.0, abs=0.05)
        snap = record.snapshot()
        assert snap["counters"] == {"restart": 2}
        assert snap["timers_s"]["death_to_spawn"]["total_s"] == pytest.approx(0.5, abs=1e-3)

    def test_a_promotion_starts_the_record_again(self, tmp_path, monkeypatch):
        # standby_gate() returns at the promotion: ``ready`` is then what
        # the promotion cost, not the standby's idle life; the launcher
        # wrote which restart this is and when it saw the death.
        import time

        from torchft_tpu import startup
        from torchft_tpu.metrics import Metrics
        from torchft_tpu.platform import standby_gate

        now = time.monotonic()
        idle = startup.StartupRecord(started=now - 500.0, imported=now - 499.0)
        idle.compiled("jit(step)", 7.0)  # the standby's warm-up
        with idle.manager_init():  # and a Manager built before the gate
            pass
        monkeypatch.setattr(startup, "_record", idle)
        gate = tmp_path / "gate"
        gate.write_text(f"3 {time.time() - 0.25!r}")
        monkeypatch.setenv("TORCHFT_STANDBY_FILE", str(gate))
        standby_gate()
        assert (tmp_path / "gate.warm").exists()
        line = idle.close(Metrics())
        snap = idle.snapshot()
        seconds = {k: v["total_s"] for k, v in snap["timers_s"].items()}
        assert seconds["ready"] < 5.0 and seconds["spawn_to_import"] == 0.0
        # the Manager of the idle life is not this life's: no negative interval
        assert seconds["import_to_manager"] == seconds["manager_init"] == 0.0
        assert seconds["death_to_spawn"] == pytest.approx(0.25, abs=0.05)
        assert snap["counters"] == {
            "restart": 3, "compiles": 1, "startup_cache_hits": 0, "startup_cache_misses": 0,
        }
        # the warm-up's compile is the life's, not this start-up's
        assert seconds["startup_compile"] == 0.0 and seconds["compile"] == 7.0
        assert idle._compiled == {"jit(step)": 1} and "compile 0.0 s" in line
        # the line says which life this is: what `restart` is read for
        assert line.startswith("ready in ") and " s (restart 3, 0.2" in line
        assert " s after the death was seen): spawn_to_import 0.0, " in line
        # activated by hand, with an empty file: the record starts again all the same
        gate.write_text("")
        again = startup.StartupRecord(started=now - 500.0, imported=now - 499.0)
        monkeypatch.setattr(startup, "_record", again)
        standby_gate()
        assert again._started >= now and again.snapshot()["counters"] == {}
        assert again.close(Metrics()).startswith("ready in 0.0 s: spawn_to_import")

    def test_supervised_standby_warm_marker(self, tmp_path):
        # standby_warm keys off the <standby_file>.warm marker that
        # standby_gate touches on arrival — the signal the warm-deadline
        # re-arm policy (lift a starving warm-up back to normal priority)
        # and promotion logging both read.
        from torchft_tpu.launcher import _Supervised

        s = _Supervised(spec={"name": "g0"})
        assert s.standby_warm() is False  # no standby file yet
        s.standby_file = str(tmp_path / "gate")
        assert s.standby_warm() is False  # armed but still warming
        (tmp_path / "gate.warm").write_text("")
        assert s.standby_warm() is True

    def test_launch_gives_up_after_max_restarts(self, tmp_path):
        script = tmp_path / "fail.py"
        script.write_text("import sys; sys.exit(3)\n")
        rc = launch(
            [sys.executable, str(script)],
            num_replica_groups=1,
            lighthouse_addr="http://unused:1",
            max_restarts=1,
        )
        assert rc == 1


class TestRenicePriorityProbe:
    """Spawn-time setpriority capability probe (VERDICT item 4): standbys
    only warm at nice 19 when the supervisor can lift a promoted one back
    to 0 — never leave a promoted worker training at idle priority."""

    def test_cap_sys_nice_in_capeff_allows(self):
        from torchft_tpu.launcher import _can_lift_priority

        # CAP_SYS_NICE is bit 23
        assert _can_lift_priority(
            status_text="Name:\tx\nCapEff:\t0000000000800000\n",
            rlimit_nice=0,
        )

    def test_no_cap_no_rlimit_denies(self):
        from torchft_tpu.launcher import _can_lift_priority

        assert not _can_lift_priority(
            status_text="Name:\tx\nCapEff:\t0000000000000000\n",
            rlimit_nice=0,
        )

    def test_root_without_cap_sys_nice_denies(self, monkeypatch):
        # The kernel's can_nice() is capability-based: root in a
        # --cap-drop SYS_NICE container cannot lift a niced child, and
        # euid 0 must NOT short-circuit the CapEff verdict.
        import torchft_tpu.launcher as launcher_mod

        monkeypatch.setattr(launcher_mod.os, "geteuid", lambda: 0)
        assert not launcher_mod._can_lift_priority(
            status_text="Name:\tx\nCapEff:\t0000000000000000\n",
            rlimit_nice=0,
        )
        # euid 0 only decides when no capability info exists at all
        assert launcher_mod._can_lift_priority(
            status_text="Name:\tx\n", rlimit_nice=0
        )

    def test_rlimit_nice_allowance_allows(self):
        from torchft_tpu.launcher import _can_lift_priority

        # soft RLIMIT_NICE of 20 admits raising priority to nice 0
        assert _can_lift_priority(
            status_text="Name:\tx\nCapEff:\t0000000000000000\n",
            rlimit_nice=20,
        )
        assert not _can_lift_priority(
            status_text="Name:\tx\nCapEff:\t0000000000000000\n",
            rlimit_nice=19,
        )
        # RLIM_INFINITY reads as -1: unlimited allowance, must allow
        assert _can_lift_priority(
            status_text="Name:\tx\nCapEff:\t0000000000000000\n",
            rlimit_nice=-1,
        )

    def test_unprivileged_supervisor_never_nices_standby(
        self, tmp_path, monkeypatch
    ):
        # With the probe forced to "cannot lift", the standby must warm
        # at the supervisor's own niceness (NOT 19) so a promotion never
        # yields a permanently-deprioritized primary.
        import os

        import torchft_tpu.launcher as launcher_mod

        monkeypatch.setattr(launcher_mod, "_can_lift_priority", lambda: False)
        script = tmp_path / "spare_nice.py"
        script.write_text(
            "import os, sys\n"
            f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
            "from torchft_tpu.platform import standby_gate\n"
            "d = os.path.dirname(os.path.abspath(__file__))\n"
            "if os.environ.get('TORCHFT_STANDBY_FILE'):\n"
            "    nice = os.nice(0)\n"
            "    standby_gate()\n"
            "    with open(os.path.join(d, 'promoted_nice'), 'w') as f:\n"
            "        f.write(str(nice))\n"
            "    sys.exit(0)\n"
            "if not os.path.exists(os.path.join(d, 'died')):\n"
            "    open(os.path.join(d, 'died'), 'w').close()\n"
            "    sys.exit(1)\n"
            "sys.exit(0)\n"
        )
        rc = launcher_mod.launch(
            [sys.executable, str(script)],
            num_replica_groups=1,
            lighthouse_addr="http://unused:1",
            max_restarts=2,
            hot_spare=True,
        )
        assert rc == 0
        base_nice = os.nice(0)
        promoted_nice = int((tmp_path / "promoted_nice").read_text())
        assert promoted_nice == base_nice, (
            f"promoted standby ran at nice {promoted_nice} (supervisor "
            f"{base_nice}): an unliftable supervisor must not warm "
            "standbys at idle priority"
        )
