"""Resilient data plane under injected faults: stale-socket recovery,
owner-death forwarded puts, takeover races, torn writes, circuit-breaker
degradation, background reclamation, heartbeat pause, lease corruption,
and a zero-data-loss soak of real host processes under a mixed fault
schedule ending in a hard host kill."""

from __future__ import annotations

import multiprocessing as mp
import os
import threading
import time
import traceback
import zlib

import numpy as np
import pytest

from repro.core.dstore import DistributedStore, LeaseLost, NotOwner
from repro.core.resilience import CircuitBreaker, CircuitOpen
from repro.core.store import ReadMode, WriteMode
from repro.core.tiers import IntegrityError, TierError
from repro.runtime.failure import ChaosInjector, SimulatedFailure

MB = 2**20
TTL = 1.0

# Fault-injection suites wait out real lease TTLs and retry backoffs; CI
# runs `-m slow` in its own step with a wider per-test timeout.  The soak
# at the end bounds its own waits and stays in the fast step.
slow = pytest.mark.slow


def _shard(host_id: int, root, **kw) -> DistributedStore:
    kw.setdefault("mem_capacity_bytes", 8 * MB)
    kw.setdefault("block_bytes", 256 * 1024)
    kw.setdefault("n_pfs_servers", 2)
    kw.setdefault("stripe_bytes", 128 * 1024)
    kw.setdefault("lease_ttl_s", TTL)
    kw.setdefault("auto_gossip", False)
    kw.setdefault("auto_reclaim", False)  # opt in per test for determinism
    return DistributedStore(host_id, str(root), **kw)


def _silence(d: DistributedStore) -> None:
    """Emulate a dead host: heartbeats stop, the transport goes away, but
    nothing is closed cleanly (no lease release, no flush)."""
    d.registry.stop()
    d.server.close()


@slow
class TestStaleConnectionRecovery:
    def test_read_survives_peer_transport_restart(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs")
        try:
            data = os.urandom(700 * 1024)
            a.put("f", data)
            assert b.get("f") == data  # opens the persistent connection
            hot_before = b.stats.peer_hot_blocks
            a.restart_peer_server()  # same port; b's socket is now dead
            assert b.get("f") == data  # detect on send, reconnect once
            assert b.stats.peer_reconnects >= 1
            assert b.stats.peer_hot_blocks > hot_before  # served hot again
        finally:
            a.close()
            b.close()

    def test_forwarded_put_is_never_blind_resent(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs")
        try:
            a.put("f", b"v0" * 1024)
            assert b.get("f") == b"v0" * 1024  # b now holds a's connection
            a.restart_peer_server()
            # The stale socket fails on send; the non-idempotent path must
            # not resend on the same client — it re-resolves the (still
            # valid) lease and retries on a fresh connection.
            b.put("f", b"v1" * 1024)
            assert a.get("f") == b"v1" * 1024
            assert b.stats.forwarded_puts == 1
            assert a.stats.forwarded_puts_served == 1  # applied exactly once
        finally:
            a.close()
            b.close()


@slow
class TestOwnerDiedForwardedPut:
    def test_put_lands_via_takeover_when_owner_dies_before_send(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs")
        try:
            a.put("f", b"old" * 1024)
            assert b.get("f") == b"old" * 1024
            _silence(a)  # dies with a still-valid lease on "f"
            # b's lease view says "live owner a": the forwarded put fails on
            # the wire, and the retry loop re-resolves until a's heartbeat
            # lapses — then claims and writes locally.  No PeerUnreachable
            # escapes to the caller.
            new = b"new" * 2048
            b.put("f", new)
            assert b.stats.takeovers == 1
            assert b.leases.read("f").owner == 2
            assert b.get("f") == new
        finally:
            a.close()
            b.close()

    def test_put_redirects_to_new_owner_after_server_side_fencing(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs")
        c = _shard(3, tmp_path / "pfs")
        try:
            a.put("f", b"x" * 1024)
            _silence(a)
            time.sleep(TTL * 1.4)
            assert c.get("f") == b"x" * 1024  # c takes the lease over
            # b still has a's lease cached fresh=True re-reads, so force the
            # redirect path: the lease now names c, and b forwards there.
            b.put("f", b"y" * 1024)
            assert c.get("f") == b"y" * 1024
            assert c.stats.forwarded_puts_served == 1
        finally:
            a.close()
            b.close()
            c.close()


@slow
class TestTakeoverRace:
    def test_exactly_one_winner_across_racing_hosts(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs")
        c = _shard(3, tmp_path / "pfs")
        try:
            names = [f"k/{i}" for i in range(4)]
            for n in names:
                a.put(n, n.encode() * 512)
            _silence(a)
            time.sleep(TTL * 1.4)
            outcomes: dict[str, list[int]] = {n: [] for n in names}

            def race(d: DistributedStore) -> None:
                for n in names:
                    try:
                        d._ensure_owned(n)
                        outcomes[n].append(d.host_id)
                    except NotOwner:
                        pass

            ts = [threading.Thread(target=race, args=(d,)) for d in (b, c)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            for n in names:
                assert len(outcomes[n]) == 1, outcomes  # one winner per file
                assert b.leases.read(n).owner == outcomes[n][0]
            assert b.stats.takeovers + c.stats.takeovers == len(names)
            # no torn sidecar locks left behind
            locks = [f for f in os.listdir(b.leases.dir) if f.endswith(".lock")]
            assert locks == []
            for n in names:
                winner = b if outcomes[n][0] == 2 else c
                assert winner.get(n) == n.encode() * 512
        finally:
            a.close()
            b.close()
            c.close()

    def test_crash_mid_takeover_leaves_lock_then_recovers(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        chaos = ChaosInjector()
        chaos.arm("lease.takeover.locked", "crash", count=1)
        b = _shard(2, tmp_path / "pfs", chaos=chaos)
        try:
            a.put("f", b"z" * 4096)
            _silence(a)
            time.sleep(TTL * 1.4)
            with pytest.raises(SimulatedFailure):
                b.get("f")  # crashes holding the sidecar lock
            lock = b.leases._path("f") + ".lock"
            assert os.path.exists(lock)  # the torn state takeover guards against
            # While the lock is fresh, takeover is blocked (the taker might
            # still be alive inside it) — the claim resolves to the stale
            # lease and the caller sees NotOwner, not a hang.
            with pytest.raises(NotOwner):
                b._ensure_owned("f")
            time.sleep(TTL * 1.2)  # lock goes stale (taker died inside)
            assert b.get("f") == b"z" * 4096  # breaks the lock, takes over
            assert b.stats.takeovers == 1
            assert not os.path.exists(lock)
        finally:
            a.close()
            b.close()


@slow
class TestTornWrites:
    def test_torn_stripe_write_raises_and_retry_heals(self, tmp_path):
        chaos = ChaosInjector()
        chaos.arm("pfs.write_unit", "torn_write", frac=0.3, count=1)
        a = _shard(1, tmp_path / "pfs", chaos=chaos)
        try:
            data = os.urandom(700 * 1024)
            with pytest.raises(IntegrityError):
                a.put("f", data)  # write-through: the torn unit surfaces
            a.put("f", data)  # fault budget spent: full rewrite lands
            assert a.get("f") == data
            assert a.store.get("f", mode=ReadMode.PFS_BYPASS) == data  # durable
        finally:
            a.close()

    def test_silent_torn_write_is_convicted_by_crc_on_read(self, tmp_path):
        chaos = ChaosInjector()
        chaos.arm("pfs.write_unit", "torn_write", frac=0.5, count=1, silent=True)
        a = _shard(1, tmp_path / "pfs", chaos=chaos)
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data)  # silent: corruption lands on the PFS tier
            assert a.get("f") == data  # memory tier still holds good bytes
            with pytest.raises(IntegrityError):
                a.store.get("f", mode=ReadMode.PFS_BYPASS)  # manifest convicts
        finally:
            a.close()

    def test_interrupted_overwrite_heals_on_rewrite(self, tmp_path):
        chaos = ChaosInjector()
        a = _shard(1, tmp_path / "pfs", chaos=chaos)
        try:
            v1 = os.urandom(300 * 1024)
            a.put("f", v1)
            chaos.arm("pfs.write_unit", "torn_write", frac=0.4, count=1)
            v2 = os.urandom(300 * 1024)
            with pytest.raises(IntegrityError):
                a.put("f", v2)  # dies between the table update and the CRC publish
            # While the overwrite is unacked there is no valid copy of the
            # torn block: the unverifiable resident bytes are quarantined
            # (never served, never flushed down) and the short PFS stripe
            # is convicted — the read surfaces that honestly...
            with pytest.raises(IntegrityError):
                a.get("f")
            a.put("f", v2)  # the writer's retry
            assert a.get("f") == v2  # ...and the retry heals everything
            assert a.store.get("f", mode=ReadMode.PFS_BYPASS) == v2
        finally:
            a.close()

    def test_stale_resident_copy_falls_back_to_durable(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data)
            st = a.store
            bkey = next(iter(st._blocks))
            meta = st._blocks[bkey]
            stale = os.urandom(meta.length)  # rotted resident bytes
            st.mem.delete(bkey)
            st.mem.put(bkey, stale)
            meta.verified = False
            # The bad copy is quarantined and the read falls through to the
            # durable PFS copy instead of raising (self-healing read path).
            assert a.get("f") == data
            assert st.stats.integrity_failures >= 1
            assert a.get("f") == data  # the re-promoted copy verifies clean
        finally:
            a.close()

    def test_async_writeback_flush_retries_through_torn_write(self, tmp_path):
        chaos = ChaosInjector()
        chaos.arm("pfs.write_unit", "torn_write", frac=0.3, count=1)
        a = _shard(1, tmp_path / "pfs", chaos=chaos)
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data, mode=WriteMode.ASYNC_WRITEBACK)
            a.store.drain()  # first flush tears, the bounded retry lands it
            assert a.store.stats.flush_retries >= 1
            assert a.store.get("f", mode=ReadMode.PFS_BYPASS) == data
        finally:
            a.close()


@slow
class TestCircuitBreaker:
    def test_open_circuit_degrades_reads_to_cold_then_recovers(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        chaos = ChaosInjector()
        # Exactly threshold drops: the breaker opens, later requests
        # short-circuit without consuming fault budget.
        chaos.arm("peer.request", "drop", count=3)
        b = _shard(2, tmp_path / "pfs", chaos=chaos, breaker_reset_s=0.5)
        try:
            data = os.urandom(700 * 1024)  # 3 blocks at 256 KiB
            a.put("f", data)
            assert b.get("f") == data  # degraded, not failed
            assert b.stats.peer_cold_blocks == 3  # every block came cold
            assert b.stats.peer_hot_blocks == 0
            assert b.stats.circuit_short_circuits > 0
            assert b.stats.cold_fallback_reads > 0
            assert b.tier_stats()["dstore"]["circuit_states"][1] == CircuitBreaker.OPEN
            time.sleep(0.6)  # reset window: half-open probe admitted
            assert b.get("f") == data
            assert b.stats.peer_hot_blocks == 3  # probe succeeded, hot again
            assert b.tier_stats()["dstore"]["circuit_states"][1] == CircuitBreaker.CLOSED
        finally:
            a.close()
            b.close()

    def test_request_delay_fault_is_absorbed_by_reads(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        chaos = ChaosInjector()
        chaos.arm("peer.request", "delay", delay_s=0.02, count=4)
        b = _shard(2, tmp_path / "pfs", chaos=chaos)
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data)
            assert b.get("f") == data  # slow, but correct and hot
            assert b.stats.peer_hot_blocks > 0
        finally:
            a.close()
            b.close()


@slow
class TestBackgroundReclamation:
    def test_reclaimer_adopts_and_warms_dead_hosts_files(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs", auto_reclaim=True, reclaim_interval_s=0.25)
        try:
            names = [f"k/{i}" for i in range(3)]
            blobs = {n: bytes([i % 251]) * (300 * 1024 + i) for i, n in enumerate(names)}
            for n in names:
                a.put(n, blobs[n])
            a.publish_gossip()  # the hot map that orders reclamation
            _silence(a)
            deadline = time.monotonic() + TTL * 4
            while time.monotonic() < deadline and b.stats.reclaimed_files < len(names):
                time.sleep(0.05)
            assert b.stats.reclaimed_files == len(names)
            assert b.stats.takeovers == len(names)
            assert len(b.stats.recovery_events) == len(names)
            for n in names:
                assert b.leases.read(n).owner == 2
                # pre-warmed: the first read after failure is a memory hit
                assert b.store.resident_fraction(n) == 1.0
                assert b.get(n) == blobs[n]
            # the reads above were all owner-local (no inline takeover)
            assert b.stats.takeovers == len(names)
        finally:
            a.close()
            b.close()

    def test_reclaim_now_is_rate_limited_and_ordered_hottest_first(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs", reclaim_max_files=2)
        try:
            sizes = {"cold/x": 300 * 1024, "hot/y": 600 * 1024, "hot/z": 450 * 1024}
            for n, sz in sizes.items():
                a.put(n, b"d" * sz)
                a.get(n)  # residency makes the gossip hot map
            a.publish_gossip()
            _silence(a)
            time.sleep(TTL * 1.4)
            first = b.reclaim_now()
            assert len(first) == 2  # rate limit holds
            assert first == ["hot/y", "hot/z"]  # hottest (by gossip) first
            second = b.reclaim_now()
            assert second == ["cold/x"]
            assert b.reclaim_now() == []  # nothing left to adopt
            assert b.stats.reclaimed_files == 3
        finally:
            a.close()
            b.close()

    def test_no_dead_hosts_means_no_work(self, tmp_path):
        a = _shard(1, tmp_path / "pfs")
        b = _shard(2, tmp_path / "pfs")
        try:
            a.put("f", b"x" * 1024)
            assert b.reclaim_now() == []
            assert b.stats.takeovers == 0
        finally:
            a.close()
            b.close()


@slow
class TestReadChaosAndSelfHealing:
    """DESIGN.md §15: read-side chaos (bit rot, lost server dirs) against
    the replicated cold tier — read-any failover, quarantine interplay,
    scrub-driven repair, and repair-event gossip."""

    def test_bit_flip_fault_fails_over_and_is_counted(self, tmp_path):
        chaos = ChaosInjector.from_specs(["pfs.read_unit:bit_flip,replica=0,count=1"])
        a = _shard(1, tmp_path / "pfs", chaos=chaos, replication=2)
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data)
            # the flip rots replica 0 on disk mid-read; read-any serves the
            # survivor bit-identically
            assert a.store.get("f", mode=ReadMode.PFS_BYPASS) == data
            assert chaos.fired_count("pfs.read_unit", "bit_flip") == 1
            assert a.store.pfs.stats.degraded_reads >= 1
            # the rot is persistent: the convicted replica stays convicted
            blks = [a.store._bkey("f", i) for i in range(a.store._files["f"].n_blocks)]
            bad = [blk for blk in blks if a.store.pfs.verify(blk)]
            assert bad, "flipped replica should fail verification on disk"
            for blk in bad:
                a.store.pfs.repair(blk)
                assert a.store.pfs.verify(blk) == []
        finally:
            a.close()

    def test_server_down_where_filter_picks_victim_and_scrub_re_replicates(self, tmp_path):
        w = _shard(1, tmp_path / "pfs", replication=2)
        blobs = {f"k/{i}": os.urandom(200 * 1024 + i) for i in range(3)}
        try:
            for n, blob in blobs.items():
                w.put(n, blob)
        finally:
            w.close()
        # reopen under chaos: the first PFS touch wipes server_01 whole —
        # puts already landed, so the loss hits a populated namespace
        chaos = ChaosInjector.from_specs(["pfs.server_down:server_down,server=1,count=1"])
        assert chaos._faults[0].where == {"server": 1}  # from_specs where-grammar
        a = _shard(1, tmp_path / "pfs", chaos=chaos, replication=2,
                   scrub_interval_s=3600.0)
        try:
            assert a.store.get("k/0", mode=ReadMode.PFS_BYPASS) == blobs["k/0"]
            assert chaos.fired_count("pfs.server_down", "server_down") == 1
            # zero acked bytes lost while degraded...
            for n, blob in blobs.items():
                assert a.store.get(n, mode=ReadMode.PFS_BYPASS) == blob
            assert a.store.pfs.stats.degraded_reads >= 1
            # ...and the scrubber drains the loss to full re-replication
            a.store.scrubber.scrub_until_clean()
            for blk in a.store.pfs.keys():
                assert a.store.pfs.verify(blk) == []
            assert a.stats.scrub_repairs >= 1
        finally:
            a.close()

    def test_quarantined_memory_and_rotten_primary_served_from_survivor(self, tmp_path):
        """Satellite regression: memory copy corrupt AND primary PFS
        replica corrupt — the read must still be bit-identical (quarantine
        falls through to durable, read-any skips the rotten primary), and
        repair heals in place."""
        a = _shard(1, tmp_path / "pfs", replication=2, scrub_interval_s=3600.0)
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data)
            st = a.store
            bkey = next(iter(st._blocks))
            meta = st._blocks[bkey]
            st.mem.delete(bkey)
            st.mem.put(bkey, os.urandom(meta.length))  # rotted resident copy
            meta.verified = False
            pfs = st.pfs
            for unit, _off, _ln in pfs._iter_units(pfs.size_of(bkey)):
                p = pfs._stripe_path(bkey, unit, 0)
                with open(p, "r+b") as fh:  # rot every primary replica too
                    fh.seek(7)
                    b = fh.read(1)
                    fh.seek(7)
                    fh.write(bytes([b[0] ^ 0xFF]))
            assert pfs.verify(bkey) != []  # rot is real before the read
            assert a.get("f") == data  # bit-identical from the survivors
            assert st.stats.integrity_failures >= 1  # quarantine convicted mem
            assert pfs.stats.degraded_reads >= 1  # read-any skipped replica 0
            # the degraded read enqueued the key; the scrubber thread wakes
            # immediately (repairs jump the interval) — wait for the heal
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline and pfs.verify(bkey) != []:
                time.sleep(0.01)
            assert pfs.verify(bkey) == []
            assert st.scrubber.stats.queue_repairs >= 1
            assert a.get("f") == data
        finally:
            a.close()

    def test_repair_events_ride_gossip(self, tmp_path):
        a = _shard(1, tmp_path / "pfs", replication=2, scrub_interval_s=3600.0)
        b = _shard(2, tmp_path / "pfs", replication=2, scrub_interval_s=3600.0)
        try:
            data = os.urandom(200 * 1024)
            a.put("f", data)
            bkey = next(iter(a.store._blocks))
            os.remove(a.store.pfs._stripe_path(bkey, 0, 0))
            a.scrub_now()
            assert a.stats.scrub_repairs == 1
            a.publish_gossip()
            seen = b.cluster_repairs()
            assert any(ev["key"] == bkey for ev in seen.get(1, []))
        finally:
            a.close()
            b.close()

    def test_scrub_ownership_partitions_by_lease(self, tmp_path):
        a = _shard(1, tmp_path / "pfs", replication=2, scrub_interval_s=3600.0)
        b = _shard(2, tmp_path / "pfs", replication=2, scrub_interval_s=3600.0)
        try:
            a.put("mine", os.urandom(64 * 1024))
            b.put("yours", os.urandom(64 * 1024))
            mine_blocks = set(a.store.pfs.keys())
            owned_a = {k for k in mine_blocks if a._scrub_owns(k)}
            owned_b = {k for k in mine_blocks if b._scrub_owns(k)}
            assert owned_a | owned_b == mine_blocks  # every block scrubbed...
            assert owned_a.isdisjoint(owned_b)  # ...by exactly one host
        finally:
            a.close()
            b.close()

    def test_replication_geometry_must_agree_across_hosts(self, tmp_path):
        a = _shard(1, tmp_path / "pfs", replication=2)
        try:
            with pytest.raises(ValueError, match="geometry"):
                _shard(2, tmp_path / "pfs", replication=1)
        finally:
            a.close()


@slow
class TestHeartbeatAndLeaseFaults:
    def test_heartbeat_pause_gets_host_fenced(self, tmp_path):
        chaos = ChaosInjector()
        # after=1 lets the initial publish() land, then every renew is
        # skipped — a partitioned host that keeps running.
        chaos.arm("registry.renew", "heartbeat_pause", after=1)
        a = _shard(1, tmp_path / "pfs", chaos=chaos)
        b = _shard(2, tmp_path / "pfs")
        try:
            data = os.urandom(300 * 1024)
            a.put("f", data)
            time.sleep(TTL * 1.5)  # the paused heartbeat lapses
            assert b.get("f") == data  # b adopts the orphan
            assert b.stats.takeovers == 1
            with pytest.raises(LeaseLost):
                a.put("f", b"stale" * 100)  # the partitioned host is fenced
        finally:
            a.close()
            b.close()

    def test_corrupted_lease_self_heals_on_reclaim(self, tmp_path):
        chaos = ChaosInjector()
        chaos.arm("lease.write", "corrupt", count=1)
        a = _shard(1, tmp_path / "pfs", chaos=chaos)
        try:
            data = b"d" * 4096
            with pytest.raises(LeaseLost):
                a.put("f", data)  # the claim's lease file was scribbled
            assert a.stats.lease_lost == 1
            a.put("f", data)  # re-claim breaks the garbage lease
            assert a.get("f") == data
            assert a.leases.read("f").owner == 1
        finally:
            a.close()


# ---------------------------------------------------------------------------
# Zero-data-loss soak: host processes under a fault schedule and a hard kill
# ---------------------------------------------------------------------------

SOAK_HOSTS = 3
SOAK_RECLAIMER = 0  # the only host running the reclamation thread
SOAK_VICTIM = SOAK_HOSTS - 1  # os._exit after the quiesce: no flush, no lease release
SOAK_TTL_S = 1.5
SOAK_WAIT_S = 120.0  # bounds every wait, so a hung host fails the test
SOAK = dict(
    files_per_host=8,
    file_bytes=1 * MB,
    write_bytes=256 * 1024,
    mem_per_host=24 * MB,
    block_bytes=256 * 1024,
    fault_rounds=2,
    writes_per_round=3,  # new files per host per round
    updates_per_round=2,  # forwarded re-writes of a peer's files per round
)
# the victim's leases the reclaimer adopts: its base files and its fault-phase writes
SOAK_VICTIM_FILES = SOAK["files_per_host"] + SOAK["fault_rounds"] * SOAK["writes_per_round"]


def _soak_payload(name: str, version: int, nbytes: int) -> bytes:
    """Deterministic versioned payload: any process can regenerate it."""
    seed = (zlib.adler32(name.encode()) + 0x9E3779B1 * version) & 0xFFFFFFFF
    return np.random.default_rng(seed).integers(0, 256, size=nbytes, dtype=np.uint8).tobytes()


def _soak_base_names() -> list[str]:
    return [f"soak/data_{i:04d}" for i in range(SOAK_HOSTS * SOAK["files_per_host"])]


def _soak_owned(h: int) -> list[str]:
    names = _soak_base_names()
    return [n for i, n in enumerate(names) if i * SOAK_HOSTS // len(names) == h]


def _soak_chaos_name(h: int, r: int, j: int) -> str:
    return f"chaos/h{h}_r{r}_w{j}"


def _soak_updated() -> set[str]:
    """Base files re-written in the fault phase, each by one host every
    round, so the final version is the last round's."""
    return {n for h in range(SOAK_HOSTS) for n in _soak_owned(h)[: SOAK["updates_per_round"]]}


def _soak_all_names() -> list[str]:
    return _soak_base_names() + [
        _soak_chaos_name(h, r, j)
        for h in range(SOAK_HOSTS)
        for r in range(SOAK["fault_rounds"])
        for j in range(SOAK["writes_per_round"])
    ]


def _soak_expected(name: str) -> bytes:
    if name.startswith("chaos/"):
        return _soak_payload(name, 1, SOAK["write_bytes"])
    version = SOAK["fault_rounds"] if name in _soak_updated() else 0
    return _soak_payload(name, version, SOAK["file_bytes"])


def _soak_retry(call, *args, attempts: int = 10):
    """App-level retry: a put is acked only when it returns.  Torn stripes,
    exhausted forwarded puts and fencing races raise; the count-bounded
    schedule guarantees that a retry lands."""
    for a in range(attempts):
        try:
            return call(*args)
        except (TierError, CircuitOpen):
            if a == attempts - 1:
                raise
            time.sleep(0.02 * (a + 1))


def _soak_host(idx, root, barrier, queue, victim_dead, reclaimed, done) -> None:
    """One host of the soak, in its own spawned process: setup, fault
    phase, quiesce check (a barrier after each), then the victim dies and
    every survivor re-reads the whole final state."""
    chaos = ChaosInjector(seed=0xC0 + idx)
    d = None
    try:
        d = _shard(
            idx + 1,
            root,
            mem_capacity_bytes=SOAK["mem_per_host"],
            block_bytes=SOAK["block_bytes"],
            n_pfs_servers=4,
            stripe_bytes=256 * 1024,
            lease_ttl_s=SOAK_TTL_S,
            auto_gossip=True,
            chaos=chaos,
            auto_reclaim=idx == SOAK_RECLAIMER,
            reclaim_interval_s=0.25,
            reclaim_max_files=SOAK_VICTIM_FILES,
            reclaim_warm_bytes=256 * MB,
        )
        names = _soak_base_names()
        for n in _soak_owned(idx):
            d.put(n, _soak_payload(n, 0, SOAK["file_bytes"]))
        d.publish_gossip()
        barrier.wait(timeout=SOAK_WAIT_S)

        chaos.arm("peer.connect", "drop", count=2)  # brief partition
        chaos.arm("peer.request", "delay", prob=0.3, delay_s=0.02, count=20)
        chaos.arm("peer.request", "drop", prob=0.2, count=6)
        chaos.arm("peer.serve", "drop", prob=0.1, count=4)  # server-side frame loss
        chaos.arm("pfs.write_unit", "torn_write", frac=0.5, prob=0.25, count=4)
        rng = np.random.default_rng(0x5EED + idx)
        updated = _soak_updated()
        peer = (idx + 1) % SOAK_HOSTS  # whose files this host updates by forwarded puts
        acked = bad = 0
        for r in range(SOAK["fault_rounds"]):
            writes = [
                (n, _soak_payload(n, 1, SOAK["write_bytes"]))
                for n in (_soak_chaos_name(idx, r, j) for j in range(SOAK["writes_per_round"]))
            ]
            writes += [
                (n, _soak_payload(n, r + 1, SOAK["file_bytes"]))
                for n in _soak_owned(peer)[: SOAK["updates_per_round"]]
            ]
            order = rng.permutation(len(names))
            stride = max(1, len(order) // len(writes))
            for k, i in enumerate(order):
                data = _soak_retry(d.get, names[i])
                # an updated file is mid-transition cluster-wide until the quiesce
                if names[i] not in updated and data != _soak_payload(names[i], 0, SOAK["file_bytes"]):
                    bad += 1
                if k % stride == 0 and writes:
                    _soak_retry(d.put, *writes.pop())
                    acked += 1
            while writes:
                _soak_retry(d.put, *writes.pop())
                acked += 1
        barrier.wait(timeout=SOAK_WAIT_S)

        every = _soak_all_names()
        bad += sum(d.get(n) != _soak_expected(n) for n in every)
        d.publish_gossip()  # a fresh hot map orders the reclaim
        queue.put(("phases", idx, acked, bad, chaos.fired_count()))
        barrier.wait(timeout=SOAK_WAIT_S)

        if idx == SOAK_VICTIM:
            queue.close()
            queue.join_thread()
            os._exit(0)
        if idx == SOAK_RECLAIMER:
            victim_dead.wait(timeout=SOAK_WAIT_S)
            deadline = time.monotonic() + SOAK_WAIT_S
            while time.monotonic() < deadline and d.stats.reclaimed_files < SOAK_VICTIM_FILES:
                time.sleep(0.05)
            reclaimed.set()
        else:
            reclaimed.wait(timeout=SOAK_WAIT_S)
        bad_after = sum(d.get(n) != _soak_expected(n) for n in every)
        queue.put(("after", idx, len(every), bad_after, d.stats.reclaimed_files))
        done.wait(timeout=SOAK_WAIT_S)  # serve peers until every survivor has read
    except BaseException:
        queue.put(("error", idx, traceback.format_exc()))
        try:
            barrier.abort()  # peers fail fast instead of waiting out the barrier
        except Exception:
            pass
    finally:
        if d is not None and idx != SOAK_VICTIM:
            d.close()


def _soak_message(queue, deadline: float) -> tuple:
    msg = queue.get(timeout=max(0.0, deadline - time.monotonic()))
    if msg[0] == "error":
        raise AssertionError(f"host {msg[1]} failed:\n{msg[2]}")
    return msg


def test_acked_writes_survive_fault_schedule_and_host_kill(tmp_path):
    """Three host processes share one PFS root under mixed reads and
    writes while partitions, delayed and dropped peer requests, dropped
    server frames and torn stripe writes fire; then one host dies by
    ``os._exit``.  Every acked write re-reads bit-identically from every
    host at the quiesce, and from both survivors after the kill, the
    victim's files through background reclamation."""
    ctx = mp.get_context("spawn")
    barrier = ctx.Barrier(SOAK_HOSTS + 1)
    queue = ctx.Queue()
    victim_dead, reclaimed, done = ctx.Event(), ctx.Event(), ctx.Event()
    root = str(tmp_path / "pfs")
    procs = [
        ctx.Process(
            target=_soak_host,
            args=(i, root, barrier, queue, victim_dead, reclaimed, done),
            name=f"soak-host{i}",
        )
        for i in range(SOAK_HOSTS)
    ]
    deadline = time.monotonic() + SOAK_WAIT_S
    phases, after = {}, {}
    for p in procs:
        p.start()
    try:
        for _ in range(3):  # setup, fault phase, quiesce
            try:
                barrier.wait(timeout=max(0.0, deadline - time.monotonic()))
            except threading.BrokenBarrierError:
                _soak_message(queue, deadline)  # raises with the host's traceback
                raise
        while len(phases) < SOAK_HOSTS:
            msg = _soak_message(queue, deadline)
            phases[msg[1]] = msg[2:]
        procs[SOAK_VICTIM].join(timeout=max(0.0, deadline - time.monotonic()))
        assert not procs[SOAK_VICTIM].is_alive()
        victim_dead.set()
        while len(after) < SOAK_HOSTS - 1:
            msg = _soak_message(queue, deadline)
            after[msg[1]] = msg[2:]
    finally:
        done.set()
        for p in procs:
            p.join(timeout=30)
            if p.is_alive():
                p.terminate()

    acked = sum(m[0] for m in phases.values())
    fired = sum(m[2] for m in phases.values())
    assert fired > 0, "the fault schedule never fired"
    assert acked > 0, "no write was acked under faults"
    assert acked == SOAK_HOSTS * SOAK["fault_rounds"] * (SOAK["writes_per_round"] + SOAK["updates_per_round"])
    bad = {h: m[1] for h, m in phases.items()}
    assert bad == dict.fromkeys(range(SOAK_HOSTS), 0), f"acked writes read back wrong per host: {bad}"
    assert sorted(after) == [h for h in range(SOAK_HOSTS) if h != SOAK_VICTIM]
    for checked, bad, _ in after.values():
        assert checked == len(_soak_all_names())
        assert bad == 0, "an acked write did not re-read bit-identically after the kill"
    assert after[SOAK_RECLAIMER][2] >= SOAK_VICTIM_FILES
