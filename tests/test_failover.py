"""Replicated shards + heartbeat failure detector (ISSUE 7): reads to a
dead peer transparently fail over to its replica chain — no stalled
epoch, no kErrPeerLost until ALL R holders are gone — and the
control-plane heartbeat marks a dead peer suspected in O(interval), so
failover routing costs no data-path deadline burn.

Timing discipline (the house style of test_failure/test_fault): every
wall-clock assert allows ~10x the configured budget, and detection
waits are event-driven polls with a hard deadline.
"""

import threading
import time
import uuid

import numpy as np
import pytest

from ddstore_tpu import DDStore, DDStoreError, ThreadGroup, fault_configure
from ddstore_tpu.binding import ERR_PEER_LOST, FAILOVER_STAT_KEYS

pytestmark = pytest.mark.tier1_required

# Small budgets so a dead-peer ladder costs seconds, not minutes; the
# asserted bounds below derive from these.
_BUDGETS = {
    "DDSTORE_CONNECT_TIMEOUT_S": "1",
    "DDSTORE_READ_TIMEOUT_S": "2",
    "DDSTORE_RETRY_MAX": "2",
    "DDSTORE_RETRY_BASE_MS": "20",
    "DDSTORE_OP_DEADLINE_S": "3",
    "DDSTORE_BARRIER_TIMEOUT_S": "20",
}


def _set_budgets(monkeypatch, replication=2, heartbeat_ms=0, **extra):
    for k, v in _BUDGETS.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("DDSTORE_REPLICATION", str(replication))
    monkeypatch.setenv("DDSTORE_HEARTBEAT_MS", str(heartbeat_ms))
    for k, v in extra.items():
        monkeypatch.setenv(k, v)


def _build_stores(world, backend, rows=8, dim=4):
    """One DDStore per rank over a ThreadGroup (construction and add
    are collective -> threads). Shards are rank-stamped (rank+1)."""
    name = uuid.uuid4().hex
    stores = {}
    errs = []

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            s = DDStore(g, backend=backend)
            s.add("v", np.full((rows, dim), rank + 1, np.float64))
            stores[rank] = s
        except Exception as e:  # noqa: BLE001
            errs.append((rank, repr(e)))

    ts = [threading.Thread(target=worker, args=(r,))
          for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
    assert not errs, errs
    assert len(stores) == world
    return stores


def _close_all(stores):
    # Abrupt native close (no barriers): some members may already be
    # dead by design in these tests.
    for s in stores.values():
        s._native.close()


def _expect(stores, rows, world, dim=4):
    idx = np.arange(world * rows)
    want = (idx // rows + 1)[:, None] * np.ones((1, dim))
    return idx, want


def test_replica_set_chain_placement(monkeypatch):
    """Replica chain: rank r hosts mirrors of the NEXT R-1 ranks, so
    owner o's holders are [o, o-1, ..., o-R+1] mod world; mirrors are
    filled at add (one per hosted owner, full shard bytes)."""
    _set_budgets(monkeypatch, replication=2)
    stores = _build_stores(3, "local")
    try:
        s = stores[0]
        assert s.replication == 2
        assert s.replica_set(1) == [1, 0]
        assert s.replica_set(0) == [0, 2]
        fo = s.failover_stats()
        assert set(fo) == set(FAILOVER_STAT_KEYS)
        # rank 0 mirrors owner 1: one fill of rows*dim*8 bytes.
        assert fo["mirror_fills"] == 1
        assert fo["mirror_bytes"] == 8 * 4 * 8
        assert fo["replica_giveups"] == 0
    finally:
        _close_all(stores)


def test_replication_default_off_is_inert(monkeypatch):
    """R=1 (default) opt-out contract: no mirrors, no heartbeat thread,
    no failover counters — the pre-replication tree byte-for-byte."""
    monkeypatch.delenv("DDSTORE_REPLICATION", raising=False)
    monkeypatch.delenv("DDSTORE_HEARTBEAT_MS", raising=False)
    stores = _build_stores(2, "local")
    try:
        s = stores[0]
        assert s.replication == 1
        assert s.replica_set(1) == [1]
        fo = s.failover_stats()
        assert fo["replication"] == 1
        assert fo["hb_active"] == 0 and fo["hb_pings"] == 0
        assert all(fo[k] == 0 for k in FAILOVER_STAT_KEYS
                   if k != "replication"), fo
    finally:
        _close_all(stores)


def test_mark_suspect_short_circuits_without_ladder(monkeypatch):
    """A suspected peer's rows are served from its replica WITHOUT any
    transient-retry ladder engaging (zero deadline burn) — and bytes
    stay correct because mirrors hold the owner's exact shard."""
    _set_budgets(monkeypatch, replication=2)
    stores = _build_stores(2, "local", rows=8)
    try:
        s0 = stores[0]
        before = s0.fault_stats()
        s0.mark_suspect(1)
        idx, want = _expect(stores, 8, 2)
        got = s0.get_batch("v", idx)
        np.testing.assert_array_equal(got, want)
        after = s0.fault_stats()
        fo = s0.failover_stats()
        assert fo["suspect_skips"] >= 1
        assert fo["failover_reads"] >= 1 and fo["failover_bytes"] > 0
        # No ladder: the detector verdict routed the read, the retry
        # machinery never engaged.
        assert after["retry_transient"] == before["retry_transient"]
        assert after["retry_giveups"] == before["retry_giveups"]
        # Un-suspecting restores primary routing.
        s0.mark_suspect(1, suspected=False)
        assert s0.suspected_peers() == []
        np.testing.assert_array_equal(s0.get_batch("v", idx), want)
    finally:
        _close_all(stores)


def test_failover_after_peer_close_tcp(monkeypatch):
    """The tentpole path over the wire transport: a peer's store torn
    down abruptly (listener closed, shards gone — the in-process stand-
    in for a dead rank) and every global row stays readable on both a
    LOCAL-mirror holder and a remote reader, with kErrPeerLost never
    raised. First contact burns one bounded ladder (heartbeat off here:
    detection comes from the data path), then the suspect latch routes
    every later read straight to the replica."""
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=0)
    stores = _build_stores(3, "tcp", rows=8)
    try:
        idx, want = _expect(stores, 8, 3)
        for r in (0, 2):
            np.testing.assert_array_equal(
                stores[r].get_batch("v", idx), want)
        stores[1]._native.close()  # rank 1 dies; holder of its shard = rank 0
        t0 = time.monotonic()
        got = stores[0].get_batch("v", idx)
        elapsed = time.monotonic() - t0
        np.testing.assert_array_equal(got, want)
        fo = stores[0].failover_stats()
        assert fo["failover_reads"] >= 1
        assert fo["replica_giveups"] == 0
        assert stores[0].suspected_peers() == [1]
        # Bounded: one ladder (deadline 3s + one attempt's own
        # timeouts), x3 CPU-noise margin.
        assert elapsed < 3 * (3 + 1 + 2), elapsed
        # Remote failover: rank 2 reads owner-1 rows from rank 0's
        # mirror over the wire.
        np.testing.assert_array_equal(stores[2].get_batch("v", idx),
                                      want)
        assert stores[2].failover_stats()["failover_reads"] >= 1
        # Latched: the next read must not burn another ladder.
        g0 = stores[0].fault_stats()["retry_giveups"]
        np.testing.assert_array_equal(stores[0].get_batch("v", idx),
                                      want)
        assert stores[0].fault_stats()["retry_giveups"] == g0
    finally:
        _close_all(stores)


def test_cma_leg_gated_on_suspect_oracle(monkeypatch):
    """ISSUE 11 satellite (the CMA-masks-failover gap): a SUSPECTED
    peer's still-mapped /dev/shm shard must not keep serving silently.
    With the gate, the CMA leg skips suspected owners, the wire leaf's
    oracle check surfaces kErrPeerLost immediately, and at R=1 the
    classified error reaches the caller instead of stale-but-plausible
    bytes. Pre-gate, this read SUCCEEDED via the mapped shm — exactly
    the masking the failover tests force DDSTORE_CMA=0 against."""
    _set_budgets(monkeypatch, replication=1)
    monkeypatch.setenv("DDSTORE_CMA", "1")
    stores = _build_stores(2, "tcp", rows=8)
    try:
        s0 = stores[0]
        idx = np.arange(8, 16)
        want = np.full((8, 4), 2.0)
        np.testing.assert_array_equal(s0.get_batch("v", idx), want)
        # The gate test is vacuous unless the fast path actually engaged.
        assert s0.cma_ops > 0
        s0.mark_suspect(1)
        with pytest.raises(DDStoreError) as ei:
            s0.get_batch("v", idx)
        assert ei.value.code == ERR_PEER_LOST
        # Un-suspecting restores the fast path (the peer is alive).
        s0.mark_suspect(1, suspected=False)
        np.testing.assert_array_equal(s0.get_batch("v", idx), want)
    finally:
        _close_all(stores)


def test_failover_with_cma_enabled(monkeypatch):
    """ISSUE 11 satellite, replica half: with the CMA fast path ON and
    R=2, a suspected owner's rows route to the replica chain on every
    leg — the still-mapped shm no longer masks failover, and the bytes
    stay correct because the mirror holds the owner's exact shard."""
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=0)
    monkeypatch.setenv("DDSTORE_CMA", "1")
    stores = _build_stores(3, "tcp", rows=8)
    try:
        s0 = stores[0]
        idx, want = _expect(stores, 8, 3)
        np.testing.assert_array_equal(s0.get_batch("v", idx), want)
        assert s0.cma_ops > 0
        fo0 = s0.failover_stats()
        s0.mark_suspect(1)
        np.testing.assert_array_equal(s0.get_batch("v", idx), want)
        fo = s0.failover_stats()
        assert fo["suspect_skips"] > fo0["suspect_skips"]
        assert fo["failover_reads"] > fo0["failover_reads"]
    finally:
        _close_all(stores)


def test_peer_lost_only_when_all_holders_gone(monkeypatch):
    """kErrPeerLost now means the whole replica set is gone: with R=2
    and BOTH the owner and its mirror holder dead, the classified error
    (naming the lost rows) finally surfaces — and replica_giveups
    records it."""
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=0)
    stores = _build_stores(3, "tcp", rows=8)
    try:
        idx, want = _expect(stores, 8, 3)
        np.testing.assert_array_equal(stores[2].get_batch("v", idx),
                                      want)
        # Owner 1's chain is [1, 0]: kill both.
        stores[1]._native.close()
        stores[0]._native.close()
        with pytest.raises(DDStoreError) as ei:
            stores[2].get_batch("v", idx)
        assert ei.value.code == ERR_PEER_LOST
        assert "mirror holder" in str(ei.value)
        assert stores[2].failover_stats()["replica_giveups"] >= 1
        # Rank 2's own rows and its hosted mirror of owner 0 are still
        # readable — owner 0's chain [0, 2] has a live holder.
        got = stores[2].get_batch("v", np.arange(8))
        np.testing.assert_array_equal(got, want[:8])
    finally:
        _close_all(stores)


def test_detector_marks_dead_peer_within_heartbeat_budget(monkeypatch):
    """Satellite: detection-latency bound. The heartbeat marks a dead
    peer suspected in ~HEARTBEAT_MS * SUSPECT_N — asserted at 10x
    margin (CPU noise), which is still 100x under the default
    OP_DEADLINE ladder the data path would otherwise burn."""
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=0)
    stores = _build_stores(2, "tcp", rows=4)
    try:
        hb_ms, suspect_n = 50, 3
        stores[0].heartbeat_configure(hb_ms, suspect_n)
        # Let the detector reach steady state (peer healthy).
        deadline = time.monotonic() + 5
        while stores[0].failover_stats()["hb_pings"] < 2:
            assert time.monotonic() < deadline, "heartbeat never ran"
            time.sleep(0.01)
        assert stores[0].suspected_peers() == []
        stores[1]._native.close()
        t0 = time.monotonic()
        while 1 not in stores[0].suspected_peers():
            assert time.monotonic() - t0 < 10, \
                "detector never suspected the dead peer"
            time.sleep(0.005)
        detect_s = time.monotonic() - t0
        # Worst case per round: one failed ping costs up to the ping
        # timeout (== interval, floored at 50 ms) + the interval sleep;
        # suspect_n rounds, x10 margin.
        budget_s = suspect_n * 2 * max(0.05, hb_ms / 1e3)
        assert detect_s <= 10 * budget_s, (detect_s, budget_s)
        # The point of the detector: it beats the data-path ladder
        # (default OP_DEADLINE_S=300) by orders of magnitude.
        assert detect_s < float(_BUDGETS["DDSTORE_OP_DEADLINE_S"])
        fo = stores[0].failover_stats()
        assert fo["hb_suspects_raised"] >= 1 and fo["hb_failures"] >= 1
    finally:
        _close_all(stores)


def test_heartbeat_frames_draw_no_data_path_faults(monkeypatch):
    """Satellite: fault-injector scope. Ping frames must not consume
    data-path fault draws — an identical seeded read sequence produces
    IDENTICAL injector counters with the detector off vs hammering at
    25 ms. (Seeded chaos determinism from PR 4 would silently shift
    under any control-plane draw otherwise.)"""
    _set_budgets(monkeypatch, replication=1, heartbeat_ms=0)
    monkeypatch.setenv("DDSTORE_CMA", "0")  # draws live in the TCP serve loop
    stores = _build_stores(2, "tcp", rows=16)
    try:
        idx = np.arange(16, 32)  # rank 1's rows: every read crosses the wire

        def run_sequence():
            fault_configure("delay:1.0:1", seed=77)
            for _ in range(10):
                stores[0].get_batch("v", idx)
            checks = stores[0].fault_stats()
            fault_configure("", 0)
            return checks["fault_checks"], checks["injected_delay"]

        base = run_sequence()
        assert base[0] > 0  # the sequence does draw on the data path
        stores[0].heartbeat_configure(25, 3)
        stores[1].heartbeat_configure(25, 3)
        time.sleep(0.3)  # pings in flight while the sequence re-runs
        with_hb = run_sequence()
        assert stores[0].failover_stats()["hb_pings"] > 0
        assert with_hb == base, (base, with_hb)
    finally:
        _close_all(stores)


def test_update_refresh_at_epoch_begin(monkeypatch):
    """Mirrors refresh at the epoch fence: rows updated by the owner
    become failover-visible after the next epoch_begin — the paper's
    update/epoch_begin contract extended to replicas. The refresh is
    content-version-GATED: a fence with no update since the last pull
    costs one control read per mirror, not a whole-shard pull."""
    _set_budgets(monkeypatch, replication=2)
    stores = _build_stores(2, "local", rows=4)
    try:
        fills0 = stores[0].failover_stats()["mirror_fills"]
        # No-update fence: the seq gate skips the pull entirely.
        for s in stores.values():
            s.epoch_begin()
        for s in stores.values():
            s.epoch_end()
        assert stores[0].failover_stats()["mirror_fills"] == fills0
        stores[1].update("v", np.full((4, 4), 99.0))
        for s in stores.values():
            s.epoch_begin()
        assert stores[0].failover_stats()["mirror_fills"] == fills0 + 1
        stores[0].mark_suspect(1)
        got = stores[0].get_batch("v", np.arange(4, 8))
        np.testing.assert_array_equal(got, np.full((4, 4), 99.0))
        for s in stores.values():
            s.epoch_end()
    finally:
        _close_all(stores)


def test_data_path_verdict_outlives_successful_pings(monkeypatch):
    """A data-path ladder verdict must not be erased by the very next
    successful ping (a peer can answer pings while its data path is
    dead — 100% injected resets, a blackholed data port): clearing
    needs SUSPECT_N consecutive successes, so the failover steady state
    holds instead of re-burning a ladder every heartbeat interval. The
    flip side — a LIVE peer wrongly retired by the failover's naming
    fallback — is restored after those same N successes."""
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=0)
    stores = _build_stores(2, "tcp", rows=4)
    try:
        hb_ms, n = 40, 3
        stores[0].heartbeat_configure(hb_ms, n)
        deadline = time.monotonic() + 5
        while stores[0].failover_stats()["hb_pings"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        # Ladder verdict against a peer whose pings all SUCCEED.
        stores[0].mark_suspect(1)
        # One interval later (pings succeeding) it must STILL be
        # suspected — the verdict holds through early successes...
        time.sleep(hb_ms / 1e3 * 1.5)
        assert stores[0].suspected_peers() == [1]
        # ...and after >= N consecutive successes it clears (x10-margin
        # deadline, event-driven poll).
        deadline = time.monotonic() + 10 * (n * 2 * hb_ms / 1e3)
        while stores[0].suspected_peers():
            assert time.monotonic() < deadline, \
                "verdict never cleared by consecutive ping successes"
            time.sleep(0.01)
    finally:
        _close_all(stores)


@pytest.mark.parametrize("traced", [False, True],
                         ids=["untraced", "flight-recorder"])
def test_readahead_epoch_survives_mid_epoch_death(traced, monkeypatch):
    """Tentpole composition: a readahead loader epoch with windows in
    flight keeps delivering byte-identical batches through a peer death
    — the window's native run reads fail over inside the store, the
    degraded ladder never engages, and summary()["failover"] shows the
    reroutes. With ddtrace on, the death leaves its story: the suspect
    verdict snapshots the flight recorder by itself, and a snapshot
    taken after the epoch names the dead peer, the verdict and every
    replica-rerouted read."""
    from ddstore_tpu import binding, obs
    from ddstore_tpu.data import DistributedSampler, ShardedDataset
    from ddstore_tpu.data.loader import DeviceLoader

    if traced:
        binding.trace_configure(1)
        binding.trace_reset()
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=25,
                 DDSTORE_HEARTBEAT_SUSPECT_N="2", DDSTORE_CMA="0")
    world, num, dim, batch = 3, 384, 4, 16
    name = uuid.uuid4().hex
    stores = {}
    errs = []
    result = {}

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            s = DDStore(g, backend="tcp")
            stores[rank] = s
            rng = np.random.default_rng(3)
            data = rng.standard_normal((num, dim)).astype(np.float32)
            ds = ShardedDataset(s, data)
            if rank == 0:
                sampler = DistributedSampler(num, world=1, rank=0,
                                             seed=5)

                def epoch(kill_at=None):
                    loader = DeviceLoader(ds, sampler, batch_size=batch,
                                          mesh=None,
                                          readahead_windows=2,
                                          readahead_window_batches=4)
                    out = []
                    for i, b in enumerate(loader):
                        out.append(b.copy())
                        if kill_at is not None and i == kill_at:
                            stores[1]._native.close()
                        if kill_at is not None:
                            time.sleep(0.02)  # let detection land mid-epoch
                    return out, loader

                ref, _ = epoch()
                chaos, loader = epoch(kill_at=2)
                assert len(ref) == len(chaos)
                for a, b in zip(ref, chaos):
                    np.testing.assert_array_equal(a, b)
                result["summary"] = loader.metrics.summary()
                result["failover"] = s.failover_stats()
                result["faults"] = s.fault_stats()
                if traced:
                    result["auto_flights"] = \
                        binding.trace_stats()["flight_dumps"]
                    binding.trace_flight("manual", 0)
                    result["flight"] = binding.trace_flight_dump()
        except Exception as e:  # noqa: BLE001
            errs.append((rank, repr(e)))

    ts = [threading.Thread(target=worker, args=(r,))
          for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    try:
        assert not errs, errs
        fo = result["failover"]
        assert fo["failover_reads"] >= 1, fo
        assert fo["replica_giveups"] == 0, fo
        summary = result["summary"]
        assert summary["failover"]["failover_reads"] >= 1, summary
        # The degraded ladder never fired: windows completed through
        # the death via native failover, not per-batch refetch.
        assert summary.get("faults", {}).get("windows_retried", 0) == 0
        if traced:
            assert result["auto_flights"] > 0  # verdict snapshotted
            fl = result["flight"]
            tree = obs.span_tree(fl, max_spans=1 << 20)
            assert "suspect (peer=1" in tree       # verdict named
            assert "dead_owner=1" in tree          # reroutes named
            rerouted = int((fl["type"] == binding.TRACE_TYPE_CODES[
                "failover"]).sum())
            assert rerouted >= fo["failover_reads"]  # every one of them
    finally:
        if traced:
            binding.trace_configure(0)
            binding.trace_reset()
        _close_all(stores)


def test_failover_metrics_delta_and_gauges():
    """PipelineMetrics failover source: counters are per-epoch deltas,
    gauges (replication/hb_active/suspected_now) report live."""
    from ddstore_tpu.utils.metrics import PipelineMetrics

    feed = {k: 0 for k in FAILOVER_STAT_KEYS}
    feed.update(replication=2, failover_reads=5, hb_active=1)
    m = PipelineMetrics()
    m.set_failover_source(lambda: dict(feed))
    m.epoch_start()
    feed.update(failover_reads=9, suspect_skips=3, suspected_now=1)
    m.epoch_end()
    out = m.summary()["failover"]
    assert out["failover_reads"] == 4      # delta
    assert out["suspect_skips"] == 3
    assert out["replication"] == 2         # gauge
    assert out["suspected_now"] == 1       # gauge, live value
