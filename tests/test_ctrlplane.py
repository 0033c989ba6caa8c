"""Failure-aware control plane (ISSUE 12): detector-integrated
barriers, crash-consistent collectives, control-plane chaos.

Every control collective used to trust all peers to show up: a rank
SIGKILLed mid-fence stalled the whole pod for DDSTORE_BARRIER_TIMEOUT_S
(default 300 s) per dissemination round even though the PR 7 heartbeat
knew the peer was dead in ~0.06 s. These tests pin the new contract:

* Barriers (TCP dissemination AND LocalGroup counting) consult the
  HealthMonitor suspect oracle while waiting — a dead member aborts the
  wait in O(heartbeat) with the classified ERR_PEER_LOST naming it.
* Multi-step collectives are crash-consistent: an aborted fence rolls
  back (re-enterable, mirrors keep last-good bytes), a failed add
  unwinds its registration, a mid-placement snapshot death unwinds the
  already-placed pins.
* The control-plane injector arm (ctrl-reset/ctrl-delay/ctrl-stall)
  draws from its OWN seeded counter domain — data-plane schedules are
  bit-identical with the arm present or absent — and injected control
  faults are absorbed by the bounded ControlRetry contract.

Timing discipline (house style of test_failure/test_failover): every
wall-clock assert allows ~10x the configured budget; detection waits
are event-driven polls with a hard deadline.
"""

import threading
import time
import uuid

import numpy as np
import pytest

from ddstore_tpu import DDStore, DDStoreError, ThreadGroup, fault_configure
from ddstore_tpu.binding import ERR_PEER_LOST, ERR_TRANSPORT

pytestmark = pytest.mark.tier1_required

# Small budgets so failure paths cost seconds, not minutes; asserted
# bounds derive from these.
_BUDGETS = {
    "DDSTORE_CONNECT_TIMEOUT_S": "1",
    "DDSTORE_READ_TIMEOUT_S": "2",
    "DDSTORE_RETRY_MAX": "2",
    "DDSTORE_RETRY_BASE_MS": "20",
    "DDSTORE_OP_DEADLINE_S": "3",
    "DDSTORE_BARRIER_TIMEOUT_S": "60",
    "DDSTORE_CONTROL_TIMEOUT_MS": "500",
    "DDSTORE_CONTROL_RETRY_MAX": "2",
}


def _set_budgets(monkeypatch, replication=1, heartbeat_ms=0, **extra):
    for k, v in _BUDGETS.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv("DDSTORE_REPLICATION", str(replication))
    monkeypatch.setenv("DDSTORE_HEARTBEAT_MS", str(heartbeat_ms))
    for k, v in extra.items():
        monkeypatch.setenv(k, v)


def _build_stores(world, backend, rows=8, dim=4, epoch_collective=False):
    """One DDStore per rank over a ThreadGroup; shards rank-stamped."""
    name = uuid.uuid4().hex
    stores = {}
    errs = []

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            s = DDStore(g, backend=backend,
                        epoch_collective=epoch_collective)
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
    for s in stores.values():
        try:
            s._native.close()
        except Exception:  # noqa: BLE001 — some members die by design
            pass


def _run_collective(stores, ranks, fn):
    """Run fn(store) on the given ranks concurrently; returns
    {rank: "ok" | error code}."""
    out = {}

    def body(rank):
        try:
            fn(stores[rank])
            out[rank] = "ok"
        except DDStoreError as e:
            out[rank] = e.code

    ts = [threading.Thread(target=body, args=(r,)) for r in ranks]
    for t in ts:
        t.start()
    for t in ts:
        t.join(90)
    assert not any(t.is_alive() for t in ts), "collective hung"
    return out


def test_tcp_barrier_abort_within_detector_bound(monkeypatch):
    """Tentpole: a dead member aborts the TCP dissemination barrier in
    O(heartbeat) with ERR_PEER_LOST naming it — never the flat
    DDSTORE_BARRIER_TIMEOUT_S (60 s here) the pre-detector tree slept
    out. Asserted at the 10x-margin detector bound, orders of magnitude
    under the barrier timeout."""
    _set_budgets(monkeypatch, replication=2, heartbeat_ms=0,
                 DDSTORE_CMA="0")
    stores = _build_stores(3, "tcp")
    try:
        hb_ms, suspect_n = 50, 2
        stores[0].heartbeat_configure(hb_ms, suspect_n)
        deadline = time.monotonic() + 5
        while stores[0].failover_stats()["hb_pings"] < 2:
            assert time.monotonic() < deadline, "heartbeat never ran"
            time.sleep(0.01)
        stores[1]._native.close()
        t0 = time.monotonic()
        with pytest.raises(DDStoreError) as ei:
            stores[0].barrier()
        elapsed = time.monotonic() - t0
        assert ei.value.code == ERR_PEER_LOST
        # The classify names the dead member and the recover handoff.
        assert "rank 1" in str(ei.value)
        assert "elastic.recover" in str(ei.value)
        budget_s = suspect_n * 2 * max(0.05, hb_ms / 1e3)
        assert elapsed <= 10 * budget_s, (elapsed, budget_s)
        assert elapsed < float(_BUDGETS["DDSTORE_BARRIER_TIMEOUT_S"])
        assert stores[0].fault_stats()["last_error_peer"] == 1
        # No giveup counted: the detector beat the budget, not burned it.
        assert stores[0].fault_stats()["retry_giveups"] == 0
    finally:
        _close_all(stores)


def test_tcp_barrier_timeout_without_suspect_stays_transport(monkeypatch):
    """Contract guard: slow is not dead. A peer that simply never
    arrives (no detector verdict, heartbeat off) still times out with
    the generic transport error, not a fabricated peer-lost."""
    _set_budgets(monkeypatch, DDSTORE_BARRIER_TIMEOUT_S="1",
                 DDSTORE_CMA="0")
    stores = _build_stores(2, "tcp")
    try:
        t0 = time.monotonic()
        with pytest.raises(DDStoreError) as ei:
            stores[0].barrier()  # rank 1 never calls barrier
        elapsed = time.monotonic() - t0
        assert ei.value.code == ERR_TRANSPORT
        assert elapsed < 10 * 1.0, elapsed
    finally:
        _close_all(stores)


def test_local_barrier_errors_promptly_on_closed_store(monkeypatch):
    """Satellite: LocalGroup::Barrier on a peer whose store closed
    mid-wait (the in-process kill vehicle) errors promptly with the
    classified ERR_PEER_LOST naming the dead member — it must not
    sleep out the 120 s group timeout, and needs NO heartbeat (the
    registered-then-unregistered state is the AliveOrPending truth
    Ping already uses)."""
    _set_budgets(monkeypatch)
    stores = _build_stores(2, "local")
    try:
        stores[1]._native.close()
        t0 = time.monotonic()
        with pytest.raises(DDStoreError) as ei:
            stores[0].barrier()
        elapsed = time.monotonic() - t0
        assert ei.value.code == ERR_PEER_LOST
        assert "rank 1" in str(ei.value)
        assert elapsed < 5, elapsed
        assert stores[0].fault_stats()["last_error_peer"] == 1
        # The abort feeds the shared suspect registry: subsequent data
        # reads short-circuit the corpse instead of burning a ladder.
        assert stores[0].suspected_peers() == [1]
    finally:
        _close_all(stores)


def test_fence_abort_rolls_back_and_reenters(monkeypatch):
    """Tentpole crash-consistency: an epoch fence aborted by a suspect
    verdict rolls back the fence state machine — the NEXT epoch_begin
    re-enters cleanly (never kErrEpochState), and after the suspicion
    clears the whole group completes the fence at the same tag (the
    aborted attempt's arrivals were withdrawn, so the re-entered
    barrier cannot release early on stale counts)."""
    _set_budgets(monkeypatch)
    stores = _build_stores(3, "local", epoch_collective=True)
    try:
        # Deterministic suspect vehicle: ranks 0 and 1 both declare
        # rank 2 dead (rank 2 is alive and never enters the fence).
        stores[0].mark_suspect(2)
        stores[1].mark_suspect(2)
        t0 = time.monotonic()
        out = _run_collective(stores, (0, 1),
                              lambda s: s.epoch_begin())
        assert out == {0: ERR_PEER_LOST, 1: ERR_PEER_LOST}, out
        assert time.monotonic() - t0 < 10
        # Re-enter while still suspected: classified abort again, NOT
        # the kErrEpochState half-state the un-rolled-back fence gave.
        out = _run_collective(stores, (0, 1),
                              lambda s: s.epoch_begin())
        assert out == {0: ERR_PEER_LOST, 1: ERR_PEER_LOST}, out
        # Clear the verdicts: the full group completes begin AND end.
        stores[0].mark_suspect(2, suspected=False)
        stores[1].mark_suspect(2, suspected=False)
        out = _run_collective(stores, (0, 1, 2),
                              lambda s: s.epoch_begin())
        assert out == {0: "ok", 1: "ok", 2: "ok"}, out
        out = _run_collective(stores, (0, 1, 2),
                              lambda s: s.epoch_end())
        assert out == {0: "ok", 1: "ok", 2: "ok"}, out
    finally:
        _close_all(stores)


def test_fence_reset_realigns_divergent_fence_state(monkeypatch):
    """elastic.recover's fence realignment hook: a fence abort need not
    be unanimous over the TCP dissemination barrier (a victim that
    partially disseminated its notifies can let some survivors complete
    the fence others aborted), so recover() calls fence_reset() on
    every rank — force-closing the state machine so an open fence on a
    completed-rank never wedges the first post-recovery epoch on
    kErrEpochState. Pinned at the unit level: an open fence + reset +
    re-enter works; reset is idempotent."""
    ERR_EPOCH_STATE = -5  # kErrEpochState (store.h)

    _set_budgets(monkeypatch)
    stores = _build_stores(2, "local", epoch_collective=True)
    try:
        out = _run_collective(stores, (0, 1), lambda s: s.epoch_begin())
        assert out == {0: "ok", 1: "ok"}, out
        # Rank 0 is mid-fence (the divergent "completed" state); a
        # second begin is the half-state error...
        with pytest.raises(DDStoreError) as ei:
            stores[0].epoch_begin()
        assert ei.value.code == ERR_EPOCH_STATE
        # ...and the recovery hook force-closes it (idempotent).
        stores[0].fence_reset()
        stores[0].fence_reset()
        stores[1].fence_reset()
        out = _run_collective(stores, (0, 1), lambda s: s.epoch_begin())
        assert out == {0: "ok", 1: "ok"}, out
        out = _run_collective(stores, (0, 1), lambda s: s.epoch_end())
        assert out == {0: "ok", 1: "ok"}, out
    finally:
        _close_all(stores)


def test_aborted_fence_keeps_last_good_mirror_bytes(monkeypatch):
    """Crash-consistency of the fence's mirror refresh: an aborted
    epoch_begin skips the refresh, so the mirror keeps the LAST GOOD
    bytes — exactly the copy failover serves for the (suspected-dead)
    owner. After the suspicion clears, a completed fence refreshes the
    mirror and the update becomes failover-visible."""
    _set_budgets(monkeypatch, replication=2)
    stores = _build_stores(2, "local", rows=4, epoch_collective=True)
    try:
        old = np.full((4, 4), 2.0)  # rank 1's original stamp
        new = np.full((4, 4), 99.0)
        stores[1].update("v", new)
        stores[0].mark_suspect(1)
        with pytest.raises(DDStoreError) as ei:
            stores[0].epoch_begin()
        assert ei.value.code == ERR_PEER_LOST
        # Failover read of owner 1's rows: the mirror still holds the
        # pre-update bytes (the refresh never ran at the aborted fence).
        got = stores[0].get_batch("v", np.arange(4, 8))
        np.testing.assert_array_equal(got, old)
        # Clear the verdict; a COMPLETED fence refreshes the mirror.
        stores[0].mark_suspect(1, suspected=False)
        out = _run_collective(stores, (0, 1),
                              lambda s: s.epoch_begin())
        assert out == {0: "ok", 1: "ok"}, out
        stores[0].mark_suspect(1)
        got = stores[0].get_batch("v", np.arange(4, 8))
        np.testing.assert_array_equal(got, new)
        stores[0].mark_suspect(1, suspected=False)
        out = _run_collective(stores, (0, 1), lambda s: s.epoch_end())
        assert out == {0: "ok", 1: "ok"}, out
    finally:
        _close_all(stores)


def test_add_rollback_on_failed_fence(monkeypatch):
    """Crash-consistency: add()'s barrier→replicate→barrier tail rolls
    the registration back when a fence fails — native variable freed,
    metadata dropped, no half-registered name poisoning later
    collectives — and a retried add() after "recovery" succeeds."""
    _set_budgets(monkeypatch)
    stores = _build_stores(2, "local")
    try:
        orig = DDStore.barrier

        def failing_barrier(self):
            raise DDStoreError(ERR_PEER_LOST,
                               "stub: peer died mid-fence")

        monkeypatch.setattr(DDStore, "barrier", failing_barrier)
        out = _run_collective(
            stores, (0, 1),
            lambda s: s.add("w", np.ones((3, 2))))
        assert out == {0: ERR_PEER_LOST, 1: ERR_PEER_LOST}, out
        monkeypatch.setattr(DDStore, "barrier", orig)
        for r in range(2):
            assert "w" not in stores[r].variables()
        # Native registry rolled back too: the retried add re-registers
        # (a stale native entry would classify kErrExists here).
        out = _run_collective(
            stores, (0, 1),
            lambda s: s.add("w", np.ones((3, 2))))
        assert out == {0: "ok", 1: "ok"}, out
        got = stores[0].get_batch("w", np.arange(6))
        np.testing.assert_array_equal(got, np.ones((6, 2)))
    finally:
        _close_all(stores)


def test_partial_pin_unwind_on_mid_placement_death(monkeypatch):
    """Crash-consistency: rank-by-rank snapshot-pin placement meeting a
    dead peer unwinds the already-placed pins (all-or-nothing) — no
    stranded pins that would keep copy-on-publish RAM alive forever on
    the surviving ranks — and classifies the death as ERR_PEER_LOST
    promptly (the dead store is recognized without the 30 s bootstrap
    grace)."""
    _set_budgets(monkeypatch)
    stores = _build_stores(3, "local")
    try:
        stores[2]._native.close()  # placement order is 0 (local), 1, 2
        t0 = time.monotonic()
        with pytest.raises(DDStoreError) as ei:
            stores[0].attach("eval", snapshot=True)
        elapsed = time.monotonic() - t0
        assert ei.value.code == ERR_PEER_LOST
        assert "unwound" in str(ei.value)
        assert elapsed < 10, elapsed
        # The pin placed on rank 1 (and rank 0's own) was rolled back.
        for r in (0, 1):
            assert stores[r].snapshot_stats()["active_snapshots"] == 0
        # The surviving writer is unencumbered: updates keep NO copies
        # for the unwound snapshot.
        stores[1].update("v", np.full((8, 4), 7.0))
        assert stores[1].snapshot_stats()["kept_versions"] == 0
    finally:
        _close_all(stores)


def test_injector_ctrl_domain_is_separate(monkeypatch):
    """Satellite determinism pin: the ctrl injector arm draws from its
    OWN seeded counter domain. The same seeded data-read sequence
    produces IDENTICAL data-plane fault counters with the ctrl arm
    armed or absent — while the armed run's control traffic (snapshot
    pin placement) does consume ctrl-domain draws."""
    _set_budgets(monkeypatch, DDSTORE_CMA="0")
    stores = _build_stores(2, "tcp", rows=16)
    try:
        idx = np.arange(16, 32)  # rank 1's rows: every read on the wire

        def run_sequence(spec):
            fault_configure(spec, seed=77)
            for _ in range(10):
                stores[0].get_batch("v", idx)
            # Control traffic: one snapshot acquire+release round trip
            # per peer (ctrl-delay:1.0 injects on every one, yet the
            # bounded control contract still lands the pins).
            h = stores[0].attach("eval", snapshot=True)
            h.detach()
            fs = stores[0].fault_stats()
            fault_configure("", 0)
            return fs

        base = run_sequence("delay:1.0:1")
        assert base["fault_checks"] > 0
        assert base["ctrl_checks"] == 0
        armed = run_sequence("delay:1.0:1,ctrl-delay:1.0:1")
        for k in ("fault_checks", "injected_reset", "injected_trunc",
                  "injected_delay", "injected_stall",
                  "injected_corrupt"):
            assert armed[k] == base[k], (k, base[k], armed[k])
        assert armed["ctrl_checks"] > 0
        assert armed["ctrl_injected"] > 0
    finally:
        _close_all(stores)


@pytest.mark.parametrize("spec,seed,acquires", [
    # Seed 7 at p=0.3: hits at draw positions 0/3/7 (early — the
    # injected>0 assert can't go vacuous) and no long hit runs.
    ("ctrl-reset:0.3", 7, 1),
    # Every control arm at once, a dozen acquires: a stall of 800 ms is
    # latency under the 1000 ms per-attempt deadline, not a lost attempt.
    ("ctrl-reset:0.3,ctrl-delay:0.2:5,ctrl-stall:0.05:800", 77, 12),
], ids=["reset", "reset-delay-stall-x12"])
def test_ctrl_faults_absorbed_by_control_retry(spec, seed, acquires,
                                               monkeypatch):
    """Control-plane chaos, absorbed: with ctrl-reset firing on ~30% of
    control round trips, collective epoch fences (whose mirror refresh
    rides kOpVarSeq probes) and snapshot acquire/release still succeed
    — the bounded ControlRetry redials through the injected resets, and
    a var-seq probe that exhausts its budget degrades to the safe
    unconditional pull, never a failed fence. Data-plane draws stay
    ZERO (scope pin) and no retry giveups fire. Margins: retry budget
    6 means a pin/unpin fails only on 7 consecutive hits (p^7 ≈ 2e-4;
    thread interleaving shifts which DRAW POSITION each op lands on, so
    the schedule must be safe at any alignment, not just seed-lucky)."""
    _set_budgets(monkeypatch, replication=2, DDSTORE_CMA="0",
                 DDSTORE_CONTROL_RETRY_MAX="6",
                 DDSTORE_CONTROL_TIMEOUT_MS="1000")
    stores = _build_stores(2, "tcp", rows=4, epoch_collective=True)
    try:
        new = np.full((4, 4), 42.0)
        fault_configure(spec, seed=seed)
        stores[1].update("v", new)
        for _ in range(3):
            out = _run_collective(stores, (0, 1),
                                  lambda s: s.epoch_begin())
            assert out == {0: "ok", 1: "ok"}, out
            out = _run_collective(stores, (0, 1),
                                  lambda s: s.epoch_end())
            assert out == {0: "ok", 1: "ok"}, out
        for _ in range(acquires):  # every one lands: none may raise
            h = stores[0].attach("eval", snapshot=True)
            h.detach()
        fs = stores[0].fault_stats()
        fault_configure("", 0)
        assert fs["ctrl_injected"] > 0, fs
        assert fs["fault_checks"] == 0, fs  # data domain untouched
        assert fs["retry_giveups"] == 0, fs
        # The update became failover-visible through the chaos: the
        # fence's (retried) refresh landed the new bytes in the mirror.
        stores[0].mark_suspect(1)
        got = stores[0].get_batch("v", np.arange(4, 8))
        np.testing.assert_array_equal(got, new)
        stores[0].mark_suspect(1, suspected=False)
    finally:
        _close_all(stores)


def test_ctrl_spec_rejects_meaningless_arms():
    """Spec hygiene: the control plane has no payload to truncate or
    corrupt — ctrl-trunc/ctrl-corrupt are malformed, and the malformed
    spec must be refused loudly (a silently-dropped arm would make a
    chaos run vacuously green)."""
    for bad in ("ctrl-trunc:0.1", "ctrl-corrupt:0.1",
                "ctrl-bogus:0.1"):
        with pytest.raises(DDStoreError):
            fault_configure(bad, seed=1)
    # Well-formed mixed specs parse (and disarm cleanly).
    fault_configure("reset:0.1,ctrl-reset:0.2,ctrl-stall:0.1:50", 9)
    fault_configure("", 0)


def test_control_knobs_registered():
    """The new control-plane knobs ride the mechanically-enforced
    registry (ddlint's knob detector gates on it)."""
    from ddstore_tpu.sched.knobs import REGISTRY

    for env in ("DDSTORE_CONTROL_TIMEOUT_MS",
                "DDSTORE_CONTROL_RETRY_MAX"):
        assert env in REGISTRY, env
        assert REGISTRY[env].kind == "config"
