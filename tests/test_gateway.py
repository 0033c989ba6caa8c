"""Serving gateway (ISSUE 19): multiplexed ephemeral-reader sessions,
histogram-driven admission control, lease-reaped resources, graceful
drain.

Contracts pinned here:

* OFF STATE (``DDSTORE_GATEWAY=0``, the default) is inert: one relaxed
  load per read, no counters moving — and an armed-but-unpressured
  gateway is byte- AND seeded-fault-counter-identical to the off tree
  (the gate never consumes injector draws);
* attach/lease lifecycle: a session's snapshot pin, quota reservation
  and lane share are released at detach, and — the SIGKILL contract —
  at lease expiry within O(lease), counted in ``gateway_stats`` and
  ``snapshot_stats()["reclaimed_pins"]``;
* admission ordering under pressure: over-share reads DEFER first
  (bounded queue, deadline-aware), then REJECT with the non-fatal
  ``ERR_ADMISSION`` carrying a retry-after hint, while the protected
  (SLO-ruled) tenant keeps flowing;
* drain: stops admitting, sheds with ``ERR_ADMISSION``, sticky until
  re-enabled; a drain on a gateway-off store is a no-op success;
* the client session honors retry-after with bounded seeded-jitter
  backoff (``DDSTORE_GW_RETRY_MAX``), then surfaces the error;
* stranded-pin TTL reclaim works with the gateway OFF
  (``DDSTORE_SNAP_PIN_TTL_MS`` — satellite 1);
* ``ctrl-conndrop:p`` is a control-domain-only injector arm: the bare
  ``conndrop`` spec is refused, armed runs keep data-plane schedules
  and bytes identical and replay deterministically;
* per-epoch deltas surface in ``metrics.summary()["gateway"]`` and the
  new knobs ride the mechanically-enforced registry.

Everything runs on in-process backends (ThreadGroup TCP / local) —
tier-1 required, no accelerator, no skip paths.
"""

import threading
import time
import uuid

import numpy as np
import pytest

from ddstore_tpu import DDStore, DDStoreError, ThreadGroup, fault_configure
from ddstore_tpu.binding import (ERR_ADMISSION, GATEWAY_GAUGE_KEYS,
                                 GATEWAY_STAT_KEYS)
from ddstore_tpu.gateway import GatewaySession
from ddstore_tpu.utils.metrics import PipelineMetrics

pytestmark = pytest.mark.tier1_required

ROWS, DIM = 96, 8


@pytest.fixture(autouse=True)
def _hygiene():
    """Injector disarmed after every test (process-global); per-test
    stores die with their gateways."""
    yield
    fault_configure("", 0)


@pytest.fixture(autouse=True)
def _wire_only(monkeypatch):
    """Force remote reads onto the TCP wire (the injector's domain)
    with tight retry budgets — same regime the ddmetrics suite pins."""
    monkeypatch.setenv("DDSTORE_CMA", "0")
    monkeypatch.setenv("DDSTORE_TCP_LANES", "1")
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "4")
    monkeypatch.setenv("DDSTORE_RETRY_BASE_MS", "2")
    monkeypatch.setenv("DDSTORE_OP_DEADLINE_S", "30")


def _local_store(**kw):
    return DDStore(backend="local", **kw)


def _arm(s, **kw):
    """Gateway on with tight, test-friendly timings."""
    cfg = dict(enabled=1, lease_ms=150, defer_ms=20, queue_cap=8,
               admit_margin_pct=80)
    cfg.update(kw)
    s.gateway_configure(**cfg)


def _pressurize(s):
    """Make GatewayPressure() true deterministically: protect the
    default tenant with an unmeetable objective, then record one real
    sample into its live histogram — any op's p99 bucket upper bound
    is >> 1 ns * margin."""
    s.set_tenant_slos("p99:1ns")
    s.get_batch("v", np.arange(4))  # protected: always admitted


def _run_pair(body0, world=2, env=None, monkeypatch=None):
    """Two-rank ThreadGroup TCP store; rank r's shard is all (r+1).
    Rank 0 runs ``body0(store)``; errors from either rank propagate."""
    if env:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    name = uuid.uuid4().hex
    errors = []
    result = {}

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            with DDStore(g, backend="tcp") as s:
                s.add("v", np.full((ROWS, DIM), rank + 1, np.float32))
                if rank == 0:
                    result["out"] = body0(s)
                s.barrier()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(180)
    if env:
        for k in env:
            monkeypatch.delenv(k, raising=False)
    if errors:
        raise errors[0][1]
    assert not any(t.is_alive() for t in ts), "rank thread hung"
    return result.get("out")


# -- off state ---------------------------------------------------------------

def test_gateway_off_inert():
    """Default-off: reads flow, nothing counts, no summary section."""
    with _local_store() as s:
        s.add("v", np.arange(ROWS * DIM, dtype=np.float32)
              .reshape(ROWS, DIM))
        pm = PipelineMetrics()
        pm.set_gateway_source(s.gateway_stats)
        pm.epoch_start()
        s.get_batch("v", np.arange(32))
        gs = s.gateway_stats()
        assert set(gs) == set(GATEWAY_STAT_KEYS)
        assert gs["enabled"] == 0 and gs["admitted"] == 0
        assert gs["sessions"] == 0 and gs["deferred"] == 0
        assert s.snapshot_stats()["reclaimed_pins"] == 0
        pm.epoch_end()
        assert "gateway" not in pm.summary()
        # Drain on an off gateway: clean no-op success (elastic
        # recover calls this unconditionally when stats say enabled).
        assert s.gateway_drain(deadline_ms=10) is True


def _seeded_workload(s, gw_on):
    """Deterministic scatter reads under a seeded fault schedule; with
    the gateway armed (but unpressured — no SLO rules), the admission
    gate must not perturb bytes or injector draws either way."""
    if gw_on:
        _arm(s)
    fault_configure("reset:0.3,delay:0.1:2", 77)
    try:
        outs = []
        rng = np.random.default_rng(3)
        for _ in range(12):
            idx = rng.integers(0, 2 * ROWS, 96)
            outs.append(s.get_batch("v", idx).copy())
        fs = s.fault_stats()
    finally:
        fault_configure("", 0)
    counters = {k: fs[k] for k in
                ("fault_checks", "injected_reset", "injected_trunc",
                 "injected_delay", "injected_stall")}
    if gw_on:
        assert s.gateway_stats()["admitted"] >= 12  # the gate DID run
    return np.concatenate(outs), counters


def test_gateway_off_state_seeded_fault_identity(monkeypatch):
    """Off vs armed-and-admitting: byte-identical data AND identical
    injector counters — admission consults histograms and its own
    queue, never the data path or the fault-draw schedule."""
    out_off, fs_off = _run_pair(lambda s: _seeded_workload(s, False),
                                monkeypatch=monkeypatch)
    out_on, fs_on = _run_pair(lambda s: _seeded_workload(s, True),
                              monkeypatch=monkeypatch)
    np.testing.assert_array_equal(out_off, out_on)
    assert fs_off == fs_on, (fs_off, fs_on)
    assert fs_on["injected_reset"] > 0  # the schedule actually injected


# -- sessions & leases -------------------------------------------------------

def test_attach_detach_releases_pins_and_quota():
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        s.set_tenant_quota("eval", 1 << 20)
        _arm(s)
        t0 = s._native.tenant_stats("eval")
        token = s._native.gateway_attach(tenant="eval",
                                         with_snapshot=True,
                                         quota_bytes=4096)
        assert token > 0
        gs = s.gateway_stats()
        assert gs["sessions"] == 1 and gs["attaches"] == 1
        assert s.snapshot_stats()["active_snapshots"] == 1
        assert s._native.tenant_stats("eval")["bytes"] == t0["bytes"] + 4096
        s._native.gateway_renew(token)
        assert s.gateway_stats()["renewals"] == 1
        s._native.gateway_detach(token)
        gs = s.gateway_stats()
        assert gs["sessions"] == 0 and gs["detaches"] == 1
        assert s.snapshot_stats()["active_snapshots"] == 0
        assert s._native.tenant_stats("eval")["bytes"] == t0["bytes"]


def test_lease_expiry_reaps_pins_quota_and_session():
    """The SIGKILL contract: a session that stops renewing loses its
    lease, and the reap releases pins + quota atomically with the
    session — within O(lease)."""
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        s.set_tenant_quota("eval", 1 << 20)
        _arm(s, lease_ms=60)
        token = s._native.gateway_attach(tenant="eval",
                                         with_snapshot=True,
                                         quota_bytes=4096)
        assert token > 0
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            s.gateway_reap()  # deterministic hook; the background
            gs = s.gateway_stats()  # reaper races it harmlessly
            if gs["sessions"] == 0:
                break
            time.sleep(0.02)
        gs = s.gateway_stats()
        assert gs["sessions"] == 0 and gs["expired"] >= 1
        assert s.snapshot_stats()["active_snapshots"] == 0
        assert s._native.tenant_stats("eval")["bytes"] == 0
        # Late detach from the zombie client: clean no-op.
        with pytest.raises(DDStoreError):
            s._native.gateway_renew(token)


def test_gateway_session_renews_and_closes():
    with _local_store() as s:
        s.add("v", np.arange(ROWS * DIM, dtype=np.float32)
              .reshape(ROWS, DIM))
        _arm(s, lease_ms=90)
        with s.gateway_session(tenant="eval") as sess:
            assert isinstance(sess, GatewaySession)
            got = sess.get_batch("v", [1, 5, 9])
            np.testing.assert_array_equal(
                got, np.arange(ROWS * DIM, dtype=np.float32)
                .reshape(ROWS, DIM)[[1, 5, 9]])
            got = sess.get("v", 3, 2)
            assert got.shape == (2, DIM)
            sess.renew()
            assert sess.alive()
        gs = s.gateway_stats()
        assert gs["attaches"] == 1 and gs["detaches"] == 1
        assert gs["sessions"] == 0
        assert not sess.alive()
        sess.close()  # idempotent


def test_remote_attach_over_control_connection(monkeypatch):
    """kOpAttach/kOpLease/kOpDetach ride the dedicated control
    connection: rank 0 opens a session on rank 1's gateway."""

    def body(s):
        _arm(s)  # ranks configure independently; rank 1 armed below
        token = s._native.gateway_attach(target=1, tenant="eval",
                                         quota_bytes=256)
        assert token > 0
        assert (token >> 32) == 1  # minted by the serving rank
        s._native.gateway_renew(token, target=1)
        s._native.gateway_detach(token, target=1)
        return True

    assert _run_pair(body, env={"DDSTORE_GATEWAY": "1"},
                     monkeypatch=monkeypatch) is True


# -- admission ---------------------------------------------------------------

def test_admission_defer_then_reject_ordering():
    """Under sustained pressure an over-share read defers first, then
    is rejected with ERR_ADMISSION + a retry-after hint; the protected
    tenant keeps flowing the whole time."""
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s, defer_ms=20)
        _pressurize(s)
        base = s.gateway_stats()
        assert base["deferred"] == 0 and base["rejected"] == 0
        eval_view = s.attach("eval")
        t0 = time.monotonic()
        with pytest.raises(DDStoreError) as ei:
            eval_view.get_batch("v", np.arange(8))
        waited = time.monotonic() - t0
        assert ei.value.code == ERR_ADMISSION
        assert getattr(ei.value, "retry_after_ms", 0) > 0
        assert "defer" in str(ei.value)
        gs = s.gateway_stats()
        assert gs["deferred"] >= 1, "must defer before rejecting"
        assert gs["rejected"] >= 1
        assert gs["last_retry_after_ms"] > 0
        assert waited >= 0.015  # actually sat out the defer window
        # Protected tenant (has the SLO rule): still admitted.
        s.get_batch("v", np.arange(8))
        assert s.gateway_stats()["admitted"] > base["admitted"]


def test_protected_tenant_flows_under_adversarial_overshare():
    """An over-share tenant hammering the gate is shed; every one of
    the protected tenant's interleaved reads is admitted."""
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s, defer_ms=5)
        _pressurize(s)
        eval_view = s.attach("eval")
        shed = 0
        for _ in range(6):
            with pytest.raises(DDStoreError) as ei:
                eval_view.get_batch("v", np.arange(16))
            assert ei.value.code == ERR_ADMISSION
            shed += 1
            s.get_batch("v", np.arange(16))  # protected: flows
        gs = s.gateway_stats()
        assert shed == 6
        assert gs["rejected"] >= 6
        # Every protected read after arming was admitted, none shed:
        # admitted >= 1 (pressurize) + 6 interleaved + 0 rejections
        # charged to the protected path (rejected counts the eval ones).
        assert gs["admitted"] >= 7


def test_admission_clears_when_pressure_clears():
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s, defer_ms=5)
        _pressurize(s)
        eval_view = s.attach("eval")
        with pytest.raises(DDStoreError):
            eval_view.get_batch("v", np.arange(8))
        s.set_tenant_slos("")  # rules gone -> nobody is protected,
        got = eval_view.get_batch("v", np.arange(8))  # nobody sheds
        assert got.shape == (8, DIM)


# -- drain -------------------------------------------------------------------

def test_drain_semantics():
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s)
        s.set_tenant_slos("p99:1s")  # a protected tenant exists
        assert s.gateway_drain(deadline_ms=200) is True
        gs = s.gateway_stats()
        assert gs["draining"] == 1
        # Draining sheds EVERYONE, protected tenants included, and
        # refuses new attaches with the same non-fatal class.
        with pytest.raises(DDStoreError) as ei:
            s.get_batch("v", np.arange(4))
        assert ei.value.code == ERR_ADMISSION
        with pytest.raises(DDStoreError) as ei:
            s.gateway_session(tenant="eval")
        assert ei.value.code == ERR_ADMISSION
        assert s.gateway_stats()["drain_sheds"] >= 1
        # Sticky until explicitly re-enabled.
        s.gateway_configure(enabled=1)
        assert s.gateway_stats()["draining"] == 0
        s.get_batch("v", np.arange(4))


def test_elastic_recover_drains_gateway():
    """The recover path's quiesce hook: drain sheds, the post-barrier
    re-enable reopens (unit-level — the full swap runs in
    test_elastic)."""
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s)
        if s.gateway_stats()["enabled"]:
            assert s.gateway_drain(deadline_ms=500) is True
        assert s.gateway_stats()["draining"] == 1
        s.gateway_configure(enabled=1)  # recover() post-barrier step
        assert s.gateway_stats()["draining"] == 0
        with s.gateway_session(tenant="eval") as sess:
            sess.get_batch("v", [0, 1])


# -- client backoff ----------------------------------------------------------

def test_session_retry_after_backoff_then_giveup():
    """ERR_ADMISSION inside a session: bounded seeded-jitter retries
    honoring the hint, then the error surfaces with the hint attached."""
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s, defer_ms=5)
        sess = s.gateway_session(tenant="eval", max_retries=2, seed=11)
        _pressurize(s)
        t0 = time.monotonic()
        with pytest.raises(DDStoreError) as ei:
            sess.get_batch("v", np.arange(8))
        elapsed = time.monotonic() - t0
        assert ei.value.code == ERR_ADMISSION
        st = sess.stats()
        assert st["admission_retries"] == 2
        assert st["admission_giveups"] == 1
        assert st["backoff_s"] > 0
        assert elapsed >= st["backoff_s"]  # the sleeps really happened
        # Same seed -> same jitter draws (the reproducibility pin).
        sess2 = s.gateway_session(tenant="eval", max_retries=2, seed=11)
        with pytest.raises(DDStoreError):
            sess2.get_batch("v", np.arange(8))
        assert sess2.stats()["backoff_s"] == pytest.approx(
            st["backoff_s"], rel=0.5)  # hints may differ; jitter seeded
        sess.close()
        sess2.close()


def test_retry_max_env_default(monkeypatch):
    monkeypatch.setenv("DDSTORE_GW_RETRY_MAX", "1")
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s, defer_ms=5)
        sess = s.gateway_session(tenant="eval")
        assert sess.max_retries == 1
        _pressurize(s)
        with pytest.raises(DDStoreError):
            sess.get_batch("v", np.arange(8))
        assert sess.stats()["admission_retries"] == 1
        sess.close()


# -- stranded-pin TTL (gateway off) ------------------------------------------

def test_pin_ttl_reclaims_stranded_pin_with_gateway_off():
    """Satellite 1: a client-held snapshot pin whose holder vanished is
    reclaimed by TTL alone — no gateway, no lease — and counted in the
    snapshot_stats gauge."""
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        s.gateway_configure(pin_ttl_ms=50)  # enabled stays 0
        assert s.gateway_stats()["enabled"] == 0
        h = s.attach("eval", snapshot=True)
        assert s.snapshot_stats()["active_snapshots"] == 1
        time.sleep(0.08)
        # The pin-TTL reaper thread (cadence ttl/2) may beat the
        # manual pass — either way the pin must be gone and counted.
        s.gateway_reap()
        st = s.snapshot_stats()
        assert st["active_snapshots"] == 0
        assert st["reclaimed_pins"] == 1
        # A fresh pin under TTL age is NOT touched.
        h2 = s.attach("eval", snapshot=True)
        assert s.gateway_reap() == 0
        st = s.snapshot_stats()
        assert st["active_snapshots"] == 1 and st["reclaimed_pins"] == 1
        h2.detach()
        h.detach()  # stale handle: release of a reaped pin is benign


# -- ctrl-conndrop chaos -----------------------------------------------------

def test_conndrop_is_ctrl_only():
    """The bare data-plane spelling is malformed (a data lane has
    reset for that); only ctrl-conndrop parses."""
    with pytest.raises(DDStoreError):
        fault_configure("conndrop:0.5", seed=1)
    fault_configure("ctrl-conndrop:0.5", seed=1)
    fault_configure("", 0)


def _conndrop_workload(s):
    """Gateway sessions + reads under seeded control-connection drops:
    renewals/attaches may fail transiently (the lease absorbs them) but
    reads stay byte-exact and giveup-free."""
    fault_configure("ctrl-conndrop:0.4", seed=5)
    try:
        outs = []
        for i in range(6):
            token = 0
            try:
                token = s._native.gateway_attach(target=1,
                                                 tenant="eval")
            except DDStoreError:
                pass  # dropped mid-attach: the lease reaps server-side
            outs.append(s.get_batch("v", np.arange(ROWS,
                                                   ROWS + 32)).copy())
            if token > 0:
                try:
                    s._native.gateway_detach(token, target=1)
                except DDStoreError:
                    pass
        fs = s.fault_stats()
        # The arm fired, in its OWN counter domain: data-plane draws
        # and injections untouched.
        assert fs["ctrl_checks"] > 0
        assert fs["injected_reset"] == 0 and fs["injected_trunc"] == 0
        counters = (fs["ctrl_checks"], fs["ctrl_injected"],
                    fs["retry_giveups"])
    finally:
        fault_configure("", 0)
    return np.concatenate(outs), counters


def _conndrop_concurrent_readers(s, readers=16):
    """Ephemeral reader threads across BOTH ranks' gateways while
    control connections are hard-closed under them: a refused attach is
    retried (a shed control op is not data loss), every read is
    byte-exact, and no session gives up on admission."""
    bad, failed, giveups = [], [], []
    lock = threading.Lock()

    def reader(i):
        sess = None
        for _ in range(8):
            try:
                sess = s.gateway_session(tenant=f"eph{i % 4}",
                                         target=i % 2, seed=500 + i)
                break
            except DDStoreError:
                continue
        if sess is None:
            with lock:
                failed.append(i)
            return
        try:
            rng = np.random.default_rng(1000 + i)
            for _ in range(3):
                idx = rng.integers(0, 2 * ROWS, 32)
                want = (idx // ROWS + 1)[:, None] * np.ones((1, DIM))
                if not np.array_equal(sess.get_batch("v", idx), want):
                    with lock:
                        bad.append(i)
        finally:
            with lock:
                giveups.append(sess.stats()["admission_giveups"])
            sess.close()

    # A lease that outlives the run: under drops every renewal may
    # fail, and expiry is the reap tests' subject, not this one's.
    s.gateway_configure(lease_ms=30000)
    fault_configure("ctrl-conndrop:0.25", seed=37)
    try:
        ts = [threading.Thread(target=reader, args=(i,))
              for i in range(readers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        fs = s.fault_stats()  # before the disarm resets the counters
    finally:
        fault_configure("", 0)
    assert not any(t.is_alive() for t in ts), "reader hung"
    assert not bad and not failed, (bad, failed)
    assert len(giveups) == readers and sum(giveups) == 0, giveups
    assert fs["ctrl_injected"] > 0 and fs["retry_giveups"] == 0, fs
    assert fs["injected_reset"] == 0 and fs["injected_trunc"] == 0, fs
    return True


@pytest.mark.parametrize("concurrent", [False, True],
                         ids=["serial-twice", "16-readers-2-gateways"])
def test_ctrl_conndrop_deterministic_and_byte_exact(concurrent,
                                                    monkeypatch):
    if concurrent:
        assert _run_pair(_conndrop_concurrent_readers,
                         env={"DDSTORE_GATEWAY": "1"},
                         monkeypatch=monkeypatch) is True
        return
    out1, c1 = _run_pair(_conndrop_workload,
                         env={"DDSTORE_GATEWAY": "1"},
                         monkeypatch=monkeypatch)
    out2, c2 = _run_pair(_conndrop_workload,
                         env={"DDSTORE_GATEWAY": "1"},
                         monkeypatch=monkeypatch)
    np.testing.assert_array_equal(out1, np.full_like(out1, 2.0))
    np.testing.assert_array_equal(out1, out2)
    assert c1 == c2, (c1, c2)  # same seed, same schedule
    assert c1[1] > 0  # ctrl_injected: drops actually happened
    assert c1[2] == 0  # zero giveups


# -- metrics & knobs ---------------------------------------------------------

def test_summary_gateway_deltas():
    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        _arm(s)
        pm = PipelineMetrics()
        pm.set_gateway_source(s.gateway_stats)
        pm.epoch_start()
        with s.gateway_session(tenant="eval") as sess:
            sess.get_batch("v", np.arange(8))
        pm.epoch_end()
        gw = pm.summary()["gateway"]
        assert gw["enabled"] == 1
        assert gw["attaches"] == 1 and gw["detaches"] == 1
        assert gw["admitted"] >= 1
        for k in GATEWAY_GAUGE_KEYS:
            assert k in gw
        # Second epoch, no activity: deltas reset to zero.
        pm.epoch_start()
        pm.epoch_end()
        gw = pm.summary()["gateway"]
        assert gw["attaches"] == 0 and gw["admitted"] == 0


def test_planner_sees_admission_pressure():
    from ddstore_tpu.sched.planner import Scheduler

    with _local_store() as s:
        s.add("v", np.ones((ROWS, DIM), np.float32))
        sched = Scheduler(s, enabled=True)
        r0 = sched.replans
        sched.on_admission_pressure(deferred=3, rejected=0)
        sched.on_admission_pressure(deferred=0, rejected=2)
        assert sched.replans == r0 + 2
        assert any(r.startswith("admission:deferred")
                   for r in sched.reasons)
        assert any(r.startswith("admission:rejected")
                   for r in sched.reasons)


def test_gateway_knobs_registered():
    from ddstore_tpu.sched.knobs import REGISTRY

    for env in ("DDSTORE_GATEWAY", "DDSTORE_GW_LEASE_MS",
                "DDSTORE_GW_DEFER_MS", "DDSTORE_GW_QUEUE",
                "DDSTORE_GW_ADMIT_MARGIN", "DDSTORE_GW_LANE_SHARE",
                "DDSTORE_GW_RETRY_MAX", "DDSTORE_SNAP_PIN_TTL_MS"):
        assert env in REGISTRY, env
        assert REGISTRY[env].kind == "config"
