"""Lease-based membership: pure semantics, backoff schedule, status view.

The lease layer generalizes heartbeats (a heartbeat is a lease of
``heartbeat_timeout_ms``), adds batched renewal + explicit departs, and
replaces the fixed-interval heartbeat hammer with jittered renewal +
exponential backoff. Pure functions are driven through the JSON C-API entry
points (torchft_tpu._native); live-server behavior through Lighthouse +
LeaseClient.
"""

import threading
import time
from datetime import timedelta

import pytest

from torchft_tpu import _native
from torchft_tpu._native import (
    LeaseClient,
    Lighthouse,
    backoff_ms,
    depart_apply,
    jittered_interval_ms,
    lease_apply,
    quorum_compute,
    quorum_step,
)
from torchft_tpu.lighthouse import fetch_status


def member(replica_id, step=1, **kw):
    m = {
        "replica_id": replica_id,
        "address": f"addr_{replica_id}",
        "store_address": f"store_{replica_id}",
        "step": step,
        "world_size": 1,
        "shrink_only": False,
        "force_reconfigure": False,
    }
    m.update(kw)
    return m


def entry(replica_id, ttl_ms=0, participating=False, **kw):
    return {
        "replica_id": replica_id,
        "ttl_ms": ttl_ms,
        "participating": participating,
        "member": member(replica_id, **kw),
    }


def opts(min_replicas=1, join_timeout_ms=0, heartbeat_timeout_ms=5000):
    return {
        "min_replicas": min_replicas,
        "join_timeout_ms": join_timeout_ms,
        "quorum_tick_ms": 10,
        "heartbeat_timeout_ms": heartbeat_timeout_ms,
    }


EMPTY = {
    "participants": {},
    "heartbeats": {},
    "lease_ttls": {},
    "prev_quorum": None,
    "quorum_id": 0,
}


class TestBackoffSchedule:
    def test_deterministic(self):
        for f in range(1, 8):
            assert backoff_ms(f, 100, 10000, 42) == backoff_ms(f, 100, 10000, 42)

    def test_zero_failures_no_delay(self):
        assert backoff_ms(0, 100, 10000, 1) == 0
        assert backoff_ms(-3, 100, 10000, 1) == 0

    def test_exponential_growth_and_cap(self):
        # Jitter is +-50%, so compare against the raw exponential envelope:
        # every delay for failure k lies in [0.5, 1.5) * min(base*2^(k-1), max)
        # and never exceeds max.
        base, cap = 100, 10000
        for seed in range(20):
            for f in range(1, 12):
                raw = min(base * 2 ** (f - 1), cap)
                d = backoff_ms(f, base, cap, seed)
                assert 0.5 * raw <= d <= cap, (seed, f, d, raw)
                assert d <= 1.5 * raw, (seed, f, d, raw)

    def test_overflow_immune(self):
        # 1000 consecutive failures must still yield a sane capped delay.
        d = backoff_ms(1000, 100, 10000, 7)
        assert 0 < d <= 10000

    def test_jitter_spreads_seeds(self):
        # The whole point: different groups (seeds) retry at different times.
        delays = {backoff_ms(3, 100, 10000, seed) for seed in range(50)}
        assert len(delays) > 25

    def test_interval_jitter_bounds(self):
        for seed in range(10):
            for tick in range(10):
                d = jittered_interval_ms(1000, seed, tick)
                assert 750 <= d < 1250
        # and it actually varies across ticks
        assert len({jittered_interval_ms(1000, 1, t) for t in range(20)}) > 5


class TestLeaseSemantics:
    def test_renewal_grants_ttl(self):
        s = lease_apply(EMPTY, [entry("a", ttl_ms=2000)], now_ms=1000)
        assert s["heartbeats"]["a"] == 1000
        assert s["lease_ttls"]["a"] == 2000
        o = opts()
        # alive until grant + ttl, not grant + heartbeat_timeout
        assert quorum_compute(2999, s, o)["reason"].count("[1 heartbeating]")
        assert "[0 heartbeating]" in quorum_compute(3000, s, o)["reason"]

    def test_default_ttl_is_heartbeat_timeout(self):
        s = lease_apply(EMPTY, [entry("a", ttl_ms=0)], now_ms=0)
        assert "a" not in s["lease_ttls"]
        o = opts(heartbeat_timeout_ms=5000)
        assert "[1 heartbeating]" in quorum_compute(4999, s, o)["reason"]
        assert "[0 heartbeating]" in quorum_compute(5000, s, o)["reason"]

    def test_participating_registers(self):
        s = lease_apply(EMPTY, [entry("a", ttl_ms=1000, participating=True)], 5)
        assert s["participants"]["a"]["joined_ms"] == 5
        r = quorum_step(10, 10, s, opts())
        assert r["quorum"] is not None
        assert [m["replica_id"] for m in r["quorum"]["participants"]] == ["a"]
        assert r["changed"] and r["quorum"]["quorum_id"] == 1

    def test_renewal_preserves_joined_ms(self):
        # The join-timeout clock must not be reset by every renewal, or a
        # straggler wait could never elapse under steady renewal traffic.
        s = lease_apply(EMPTY, [entry("a", ttl_ms=1000, participating=True)], 5)
        s = lease_apply(s, [entry("a", ttl_ms=1000, participating=True)], 500)
        assert s["participants"]["a"]["joined_ms"] == 5
        assert s["heartbeats"]["a"] == 500

    def test_expiry_vs_explicit_depart(self):
        # Lease expiry: the member stays healthy until its TTL runs out.
        # Explicit depart: gone immediately, including its participant slot.
        o = opts()
        s = lease_apply(
            EMPTY,
            [entry("a", 1000, True), entry("b", 1000, True)],
            now_ms=0,
        )
        r = quorum_step(10, 10, s, o)
        assert len(r["quorum"]["participants"]) == 2

        # b silently dies: still in quorums until t=1000
        s = lease_apply(r["state"], [entry("a", 1000, True), entry("b", 1000, True)], 20)
        r_mid = quorum_step(999, 999, dict(s), o)
        assert len(r_mid["quorum"]["participants"]) == 2
        # ... but a's renewals keep it alive past b's expiry
        s2 = lease_apply(dict(s), [entry("a", 1000, True)], 900)
        r_exp = quorum_step(1100, 1100, s2, o)
        assert [m["replica_id"] for m in r_exp["quorum"]["participants"]] == ["a"]
        assert r_exp["changed"]

        # explicit depart removes b IMMEDIATELY (no TTL wait)
        s3 = lease_apply(
            r["state"], [entry("a", 1000, True), entry("b", 1000, True)], 20
        )
        s3 = depart_apply(s3, "b")
        assert "b" not in s3["heartbeats"] and "b" not in s3["participants"]
        r_dep = quorum_step(30, 30, s3, o)
        assert [m["replica_id"] for m in r_dep["quorum"]["participants"]] == ["a"]

    def test_prune_keeps_output_invariant(self):
        # Members dead >= 10 TTLs are pruned from state, and pruning never
        # changes the quorum output (they were unhealthy either way).
        s = lease_apply(EMPTY, [entry("dead", 100), entry("live", 100, True)], 0)
        s = lease_apply(s, [entry("live", 100, True)], 2000)
        r = quorum_step(2050, 2050, s, opts())
        assert "dead" not in r["state"]["heartbeats"]
        assert [m["replica_id"] for m in r["quorum"]["participants"]] == ["live"]


class TestLiveLeases:
    def test_batch_renew_forms_quorum(self):
        with Lighthouse(min_replicas=1, join_timeout_ms=100) as lh:
            c = LeaseClient(lh.address())
            qid = c.renew(
                [entry("g0", 2000, True), entry("g1", 2000, True)],
                timeout=timedelta(seconds=10),
            )
            assert qid == 1
            st = lh.status_json()
            assert st["quorum_id"] == 1
            got = sorted(
                m["replica_id"] for m in st["quorum"]["participants"]
            )
            assert got == ["g0", "g1"]

    def test_status_json_fields(self):
        with Lighthouse(min_replicas=1, join_timeout_ms=100) as lh:
            c = LeaseClient(lh.address())
            c.renew([entry("g0", 3000, True)])
            st = lh.status_json()
            assert st["role"] == "flat"
            assert st["quorum_id"] == 1
            (m,) = st["members"]
            assert m["replica_id"] == "g0"
            assert m["ttl_ms"] == 3000
            assert 0 < m["lease_remaining_ms"] <= 3000
            assert {"total", "computed", "last_compute_us"} <= set(st["tick"])
            assert st["regions"] == []
            assert isinstance(st["open_conns"], int)

    def test_status_json_over_http_matches(self):
        # The satellite contract: the JSON view is served NEXT TO the HTML
        # dashboard and is what lighthouse.fetch_status consumes.
        with Lighthouse(min_replicas=1, join_timeout_ms=100) as lh:
            c = LeaseClient(lh.address())
            c.renew([entry("g0", 3000, True)])
            st = fetch_status(lh.address())
            assert st["role"] == "flat" and st["quorum_id"] == 1
            assert st["members"][0]["replica_id"] == "g0"

    def test_depart_removes_immediately(self):
        with Lighthouse(min_replicas=1, join_timeout_ms=100) as lh:
            c = LeaseClient(lh.address())
            c.renew([entry("g0", 60000, True), entry("g1", 60000, True)])
            c.depart("g1")
            st = lh.status_json()
            assert [m["replica_id"] for m in st["members"]] == ["g0"]

    def test_idle_ticks_skip_compute(self):
        # Between quorum rounds (no registered participants) the tick loop
        # must not rescan membership — that is the lease replacement for the
        # O(groups)-per-tick heartbeat scan.
        with Lighthouse(
            min_replicas=1, join_timeout_ms=100, quorum_tick_ms=20
        ) as lh:
            c = LeaseClient(lh.address())
            c.renew([entry("g0", 60000, True)])  # quorum forms, participants clear
            deadline = time.monotonic() + 5
            while lh.status_json()["quorum_id"] != 1:
                assert time.monotonic() < deadline
                time.sleep(0.01)
            t0 = lh.status_json()["tick"]
            time.sleep(0.5)
            t1 = lh.status_json()["tick"]
            assert t1["total"] - t0["total"] >= 10  # loop kept running
            assert t1["computed"] - t0["computed"] <= 1  # but did ~no scans

    def test_heartbeat_and_renew_share_connection(self):
        with Lighthouse(min_replicas=1, join_timeout_ms=100) as lh:
            c = LeaseClient(lh.address())
            c.heartbeat("hb-only")
            c.renew([entry("g0", 2000)])
            st = lh.status_json()
            ids = sorted(m["replica_id"] for m in st["members"])
            assert ids == ["g0", "hb-only"]
            # one persistent connection for all three verbs
            c.depart("g0")


class TestManagerBackoffIntegration:
    def test_dead_lighthouse_not_hammered(self):
        # A manager whose lighthouse dies must space its renewal attempts
        # out exponentially. We can't intercept the native loop directly, so
        # assert the schedule contract the loop is built on plus the
        # manager's survival: it keeps serving while renewals back off.
        lh = Lighthouse(min_replicas=1, join_timeout_ms=100)
        addr = lh.address()
        m = _native.Manager(
            "bk", addr, "localhost", "[::]:0", "127.0.0.1:1", 1,
            heartbeat_interval=timedelta(milliseconds=50),
            connect_timeout=timedelta(seconds=5),
        )
        lh.shutdown()
        time.sleep(0.6)  # several failed renewals' worth
        # still alive and shut down cleanly (no wedge in the backoff path)
        m.shutdown()
