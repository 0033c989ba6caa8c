"""Chaos-hardened remote reads (ISSUE 4): deterministic fault injection,
transient-error classification + bounded retry with backoff, and the
degraded-mode data pipeline.

Contracts pinned here:

* the injector is DETERMINISTIC — a seeded schedule reproduces exact
  fault/retry counters across two identical runs (the property that
  makes chaos regressions diffable from counters alone);
* transient faults (connection reset, truncated frame, stalled serve
  loop, in-process read failures) are ABSORBED: epochs complete
  byte-identical with nonzero retry counters and zero give-ups;
* permanent owner death is CLASSIFIED: the bounded retry budget
  exhausts into ``kErrPeerLost`` (-10) naming the dead owner and the
  lost rows — never a hang, never a bare transport error;
* the pipeline degrades by LADDER: a failed readahead window is retried
  once at per-batch granularity; an unrecoverable engine falls back to
  per-batch fetch with the reason chain recorded.

Everything runs on the in-process backends (ThreadGroup local + TCP) —
tier-1 required, no accelerator, no skip paths.
"""

import threading
import types
import uuid

import numpy as np
import pytest

from ddstore_tpu import (DDStore, DDStoreError, NativeStore, ThreadGroup,
                         fault_configure)
from ddstore_tpu.binding import ERR_PEER_LOST, ERR_TRANSPORT

pytestmark = pytest.mark.tier1_required


@pytest.fixture(autouse=True)
def _disarm_injector():
    """Every test leaves the process-global injector disarmed."""
    yield
    fault_configure("", 0)


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch):
    """Keep backoff cheap and budgets tight for every test here."""
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "8")
    monkeypatch.setenv("DDSTORE_RETRY_BASE_MS", "2")
    monkeypatch.setenv("DDSTORE_OP_DEADLINE_S", "30")


def _run_pair(body0, world=2, backend="local", rows=64, dim=4,
              monkeypatch=None, env=None):
    """Two-rank ThreadGroup store; rank r's shard is all (r+1). Rank 0
    runs ``body0(store)``; errors from either rank propagate."""
    if env:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    name = uuid.uuid4().hex
    errors = []
    result = {}

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            with DDStore(g, backend=backend) as s:
                s.add("v", np.full((rows, dim), rank + 1, np.float32))
                if rank == 0:
                    result["out"] = body0(s)
                s.barrier()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(120)
    if errors:
        raise errors[0][1]
    assert not any(t.is_alive() for t in ts), "rank thread hung"
    return result.get("out")


def test_fault_spec_rejects_garbage():
    for bad in ("reset", "bogus:0.1", "reset:1.5", "reset:0.1:xx",
                "reset:0.9,trunc:0.9"):  # probabilities sum > 1
        with pytest.raises(DDStoreError):
            fault_configure(bad, 1)
    # and a good one round-trips
    fault_configure("reset:0.01,trunc:0.005,delay:0.02:50,stall:0.002", 42)
    fault_configure("", 0)


def test_injector_determinism_exact_counters(monkeypatch):
    """Satellite: a seeded fault schedule produces EXACT, reproducible
    fault_stats counters across two identical runs. The workload is
    strictly serial (scalar gets, one connection per peer) so the draw
    sequence — not just the totals — is deterministic."""
    monkeypatch.setenv("DDSTORE_CMA", "0")          # wire path only
    monkeypatch.setenv("DDSTORE_CONNS_PER_PEER", "1")  # serial frames

    def run_once(s):
        fault_configure("reset:0.15,trunc:0.05,delay:0.1:2", seed=99)
        for i in range(60):
            got = s.get("v", 64 + (i % 64))  # remote rows on rank 1
            assert (got == 2).all()
        fs = s.fault_stats()
        fault_configure("", 0)
        return fs

    fs1 = _run_pair(run_once, backend="tcp", monkeypatch=monkeypatch)
    fs2 = _run_pair(run_once, backend="tcp", monkeypatch=monkeypatch)
    assert fs1 == fs2, (fs1, fs2)
    assert fs1["fault_checks"] >= 60
    assert fs1["injected_reset"] + fs1["injected_trunc"] > 0
    assert fs1["retry_attempts"] > 0
    assert fs1["retry_giveups"] == 0


def test_tcp_chaos_batches_byte_identical(monkeypatch):
    """Resets + truncations + delays on the TCP serve loop: batched
    reads come back byte-identical, transparently retried."""
    monkeypatch.setenv("DDSTORE_CMA", "0")

    def body(s):
        rng = np.random.default_rng(7)
        idxs = [rng.integers(0, 128, size=96) for _ in range(12)]
        clean = [s.get_batch("v", i).copy() for i in idxs]
        fault_configure("reset:0.15,trunc:0.1,delay:0.1:2", seed=4)
        chaos = [s.get_batch("v", i).copy() for i in idxs]
        fs = s.fault_stats()
        fault_configure("", 0)
        for a, b in zip(clean, chaos):
            np.testing.assert_array_equal(a, b)
        return fs

    fs = _run_pair(body, backend="tcp", rows=64, monkeypatch=monkeypatch)
    assert fs["injected_reset"] + fs["injected_trunc"] > 0
    assert fs["retry_giveups"] == 0


def test_stall_trips_client_timeout_then_retry(monkeypatch):
    """A stalled serve loop (sleep > DDSTORE_READ_TIMEOUT_S) is a
    transient: the client times out, resets the lane, retries, and the
    data still arrives intact."""
    monkeypatch.setenv("DDSTORE_CMA", "0")
    monkeypatch.setenv("DDSTORE_READ_TIMEOUT_S", "1")

    def body(s):
        fault_configure("stall:0.5:1500", seed=2)
        for i in range(6):
            got = s.get("v", 64 + i)
            assert (got == 2).all()
        fs = s.fault_stats()
        fault_configure("", 0)
        return fs

    fs = _run_pair(body, backend="tcp", monkeypatch=monkeypatch)
    assert fs["injected_stall"] >= 1, fs
    assert fs["retry_attempts"] >= 1, fs
    assert fs["retry_giveups"] == 0, fs


def test_permanent_loss_classified_with_owner_and_rows(monkeypatch):
    """Give-up path: 100% failure exhausts the bounded budget into
    kErrPeerLost, and the store layer names the dead owner AND the lost
    rows — the elastic.recover handoff."""
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "1")

    def body(s):
        fault_configure("reset:1.0", seed=1)
        with pytest.raises(DDStoreError) as ei:
            s.get_batch("v", np.arange(64, 80))
        fault_configure("", 0)
        return ei.value

    err = _run_pair(body, backend="local", monkeypatch=monkeypatch)
    assert err.code == ERR_PEER_LOST
    msg = str(err)
    assert "owner rank 1" in msg and "elastic.recover" in msg, msg
    assert "64" in msg  # the lost rows are named


def test_absent_peer_fault_stats_name_the_peer(monkeypatch):
    """No injector at all: a peer that never existed exhausts the retry
    budget the same way (dial refused = transient each attempt) and the
    counters name it."""
    monkeypatch.setenv("DDSTORE_CONNECT_TIMEOUT_S", "1")
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "1")
    monkeypatch.setenv("DDSTORE_OP_DEADLINE_S", "3")
    ns = NativeStore.create_tcp(0, 2, 0)
    try:
        ns.set_peers(["127.0.0.1", "127.0.0.1"], [ns.server_port, 1])
        ns.add("v", np.ones((4, 2)), [4, 4], copy=True)
        out = np.empty((1, 2))
        with pytest.raises(DDStoreError) as ei:
            ns.get("v", out, 5, 1)
        assert ei.value.code == ERR_PEER_LOST
        fs = ns.fault_stats()
        assert fs["retry_giveups"] == 1 and fs["last_error_peer"] == 1
    finally:
        ns.close()


def test_rank_filter_scopes_injection(monkeypatch):
    """DDSTORE_FAULT_RANKS semantics: faults fire only when the listed
    ranks SERVE, and filtered ranks consume no draws (the targeted
    rank's schedule is independent of other traffic)."""
    def body(s):
        # Filter to rank 0 (the reader itself): remote reads are served
        # by rank 1, so nothing fires and nothing is drawn.
        fault_configure("reset:1.0", seed=3, ranks=[0])
        got = s.get_batch("v", np.arange(64, 96))
        assert (got == 2).all()
        quiet = s.fault_stats()
        # Re-aim at rank 1: now every read to it fails until give-up.
        fault_configure("reset:1.0", seed=3, ranks=[1])
        raised = False
        try:
            s.get_batch("v", np.arange(64, 96))
        except DDStoreError as e:
            raised = e.code == ERR_PEER_LOST
        fault_configure("", 0)
        return quiet, raised

    quiet, raised = _run_pair(body, backend="local",
                              monkeypatch=monkeypatch)
    assert quiet["fault_checks"] == 0 and quiet["injected_reset"] == 0
    assert raised


def _mk_flaky_store(store, fail_windows):
    """Store proxy whose read_runs_async handles fail transiently for
    the first ``fail_windows`` windows — the Python-level injection the
    degraded-mode units key on (deterministic, no probabilities)."""

    class FailingOnce:
        def __init__(self, real):
            self._real = real
            self.done_mono_s = None

        def wait(self, timeout=None):
            self._real.release()
            raise DDStoreError(ERR_TRANSPORT, "injected window failure")

        def release(self):
            self._real.release()

        def done(self):
            return self._real.done()

    class Flaky:
        def __init__(self):
            self._left = fail_windows

        def __getattr__(self, k):
            return getattr(store, k)

        def read_runs_async(self, *a, **kw):
            h = store.read_runs_async(*a, **kw)
            if self._left > 0:
                self._left -= 1
                return FailingOnce(h)
            return h

    return Flaky()


def _loader_dataset(store, flaky):
    from ddstore_tpu.data import ShardedDataset

    data = np.arange(512 * 8, dtype=np.float32).reshape(512, 8)
    ds = ShardedDataset(store, data)
    proxy = types.SimpleNamespace(store=flaky, data_var=ds.data_var,
                                  label_var=None, fetch=ds.fetch,
                                  thread_safe=True)
    return ds, proxy


def test_window_retry_per_batch_granularity():
    """Degraded mode, rung 1: a transiently failed window fetch is
    retried ONCE at per-batch granularity — the epoch completes
    byte-identical, the retry is visible in summary()["faults"], and no
    async ticket leaks."""
    from ddstore_tpu.data import DistributedSampler
    from ddstore_tpu.data.loader import DeviceLoader

    with DDStore(backend="local") as s:
        ds, proxy = _loader_dataset(s, _mk_flaky_store(s, fail_windows=1))
        sampler = DistributedSampler(512, world=1, rank=0, seed=3)
        ref = [b.copy() for b in DeviceLoader(
            ds, sampler, batch_size=32, readahead_windows=2,
            readahead_window_batches=4)]
        loader = DeviceLoader(proxy, sampler, batch_size=32,
                              readahead_windows=2,
                              readahead_window_batches=4)
        got = [b.copy() for b in loader]
        assert len(got) == len(ref) == 16
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        f = loader.metrics.summary()["faults"]
        assert f["windows_retried"] == 1
        assert f["window_batch_refetches"] == 4
        assert f["readahead_degraded"] == 0
        assert loader.readahead_fallback_reason is None
        assert s.async_pending() == 0


def test_unrecoverable_engine_degrades_to_per_batch():
    """Degraded mode, rung 2: when the window retry ALSO fails, the
    loader abandons the engine mid-epoch and finishes per-batch, with
    the reason chain recorded — the epoch still completes
    byte-identical."""
    from ddstore_tpu.data import DistributedSampler
    from ddstore_tpu.data.loader import DeviceLoader

    with DDStore(backend="local") as s:
        flaky = _mk_flaky_store(s, fail_windows=10 ** 9)

        # the per-batch window retry must fail too: poison get_batch on
        # the PROXY (the engine's store) while dataset.fetch keeps using
        # the real store.
        def bad_get_batch(*a, **kw):
            raise DDStoreError(ERR_TRANSPORT, "injected batch failure")

        flaky.get_batch = bad_get_batch
        ds, proxy = _loader_dataset(s, flaky)
        sampler = DistributedSampler(512, world=1, rank=0, seed=3)
        ref = [b.copy() for b in DeviceLoader(
            ds, sampler, batch_size=32, readahead_windows=2,
            readahead_window_batches=4)]
        loader = DeviceLoader(proxy, sampler, batch_size=32,
                              readahead_windows=2,
                              readahead_window_batches=4)
        got = [b.copy() for b in loader]
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        f = loader.metrics.summary()["faults"]
        assert f["readahead_degraded"] == 1
        assert loader.readahead_fallback_reason.startswith(
            "degraded mid-epoch")
        assert s.async_pending() == 0


def test_peer_lost_from_engine_is_fatal():
    """Permanent owner death inside the readahead path surfaces (no
    silent per-batch fallback): kErrPeerLost propagates out of the
    loader."""
    from ddstore_tpu.data import DistributedSampler
    from ddstore_tpu.data.loader import DeviceLoader

    with DDStore(backend="local") as s:
        flaky = _mk_flaky_store(s, fail_windows=10 ** 9)

        def lost_get_batch(*a, **kw):
            raise DDStoreError(ERR_PEER_LOST, "owner rank 1 unreachable")

        flaky.get_batch = lost_get_batch
        ds, proxy = _loader_dataset(s, flaky)
        sampler = DistributedSampler(512, world=1, rank=0, seed=3)
        loader = DeviceLoader(proxy, sampler, batch_size=32,
                              readahead_windows=2,
                              readahead_window_batches=4)
        with pytest.raises(DDStoreError) as ei:
            list(loader)
        assert ei.value.code == ERR_PEER_LOST
        assert s.async_pending() == 0


@pytest.mark.parametrize("lanes", [None, "4"],
                         ids=["lanes-default", "lanes-4-pinned"])
def test_chaos_loader_epoch_tcp(lanes, monkeypatch):
    """Acceptance slice at tier-1 scale: a multi-owner TCP store under
    mixed injected faults completes a full loader epoch (host path AND
    readahead) byte-identical vs the fault-free run, with nonzero retry
    counters and zero give-ups — on the default lanes and with four
    striped lanes pinned (faults must be absorbed with the multi-lane
    transport active, not only on the single-connection path)."""
    from ddstore_tpu.data import DistributedSampler, ShardedDataset
    from ddstore_tpu.data.loader import DeviceLoader

    monkeypatch.setenv("DDSTORE_CMA", "0")
    if lanes is not None:
        monkeypatch.setenv("DDSTORE_TCP_LANES", lanes)
        monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")
    world = 2
    name = uuid.uuid4().hex
    errors = []
    out = {}

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            rng = np.random.default_rng(5)
            data = rng.standard_normal((2048, 16)).astype(np.float32)
            with DDStore(g, backend="tcp") as s:
                ds = ShardedDataset(s, data)
                if rank == 0:
                    sampler = DistributedSampler(2048, world=1, rank=0,
                                                 seed=11)

                    def epoch(ra):
                        return [b.copy() for b in DeviceLoader(
                            ds, sampler, batch_size=128,
                            readahead_windows=ra,
                            readahead_window_batches=4)]

                    ref = epoch(0)
                    fault_configure("reset:0.05,trunc:0.02,delay:0.05:2",
                                    seed=21)
                    chaos_pb = epoch(0)
                    chaos_ra = epoch(2)
                    fs = s.fault_stats()
                    fault_configure("", 0)
                    assert len(ref) == len(chaos_pb) == len(chaos_ra)
                    for a, b in zip(ref, chaos_pb):
                        np.testing.assert_array_equal(a, b)
                    for a, b in zip(ref, chaos_ra):
                        np.testing.assert_array_equal(a, b)
                    out.update(fs)
                    out["max_lanes"] = s.lane_state()["max_lanes"]
                s.barrier()
        except Exception as e:  # noqa: BLE001
            errors.append((rank, e))

    ts = [threading.Thread(target=worker, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(180)
    if errors:
        raise errors[0][1]
    assert not any(t.is_alive() for t in ts), "rank thread hung"
    injected = (out["injected_reset"] + out["injected_trunc"]
                + out["injected_delay"])
    assert injected > 0, out
    assert out["retry_giveups"] == 0, out
    if lanes is not None:
        assert out["max_lanes"] == int(lanes), out


def test_soak_chaos_mode():
    """Satellite: the tiering soak's fault-schedule mode — a sampled
    epoch over a 2-rank mmap-backed store completes with every batch
    verified byte-identical against the backing files, under injected
    transient faults."""
    from ddstore_tpu.utils.soak import mmap_soak

    m = mmap_soak(rows=200_000, batch=4096, nbatches=8,
                  fault_spec="reset:0.25,delay:0.2:2", fault_seed=13)
    assert m["sentinels_ok"], m
    assert m["faults_ok"], m
    assert m["fault_injected"] > 0, m
    assert m["fault_giveups"] == 0, m


def test_retry_deadline_override_bounds_giveup(monkeypatch):
    """Satellite (ISSUE 5): set_retry_deadline bounds THIS store's
    transient-retry give-up, overriding a much larger env deadline —
    the timed half of the shared-budget contract, with a 10x margin so
    backoff-tail jitter and CPU noise cannot flake it."""
    monkeypatch.setenv("DDSTORE_CMA", "0")
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "1000")
    monkeypatch.setenv("DDSTORE_RETRY_BASE_MS", "20")
    monkeypatch.setenv("DDSTORE_OP_DEADLINE_S", "30")  # env: huge

    import time as _time

    def body(s):
        # Every serve by rank 1 resets: permanently dead from the
        # reader's point of view, but the process stays up so dials are
        # instant (the timing measures the retry budget, not connect
        # timeouts).
        fault_configure("reset:1.0", seed=9, ranks=[1])
        s.set_retry_deadline(0.3)
        t0 = _time.monotonic()
        err = None
        try:
            s.get_batch("v", np.arange(64, 80))
        except DDStoreError as e:
            err = e
        elapsed = _time.monotonic() - t0
        s.set_retry_deadline(0.0)
        fault_configure("", 0)
        return err, elapsed

    err, elapsed = _run_pair(body, backend="tcp", rows=64,
                             monkeypatch=monkeypatch)
    assert err is not None and err.code == ERR_PEER_LOST, err
    # Without the override the giveup would burn toward the 30s env
    # deadline (RETRY_MAX never binds at 1000); with it, 0.3s budget +
    # one backoff tail. 3s = 10x the override, 1/10th the env deadline.
    assert elapsed <= 3.0, \
        f"give-up took {elapsed:.2f}s: set_retry_deadline not applied"


def test_dead_owner_refetch_shares_window_deadline(monkeypatch):
    """Satellite (ISSUE 5): a permanently dead owner inside the
    readahead path surfaces kErrPeerLost within ~1x OP_DEADLINE, not
    ~2x — the per-batch refetch runs on whatever budget the window's
    own give-up left over, instead of a fresh full deadline per refetch
    chunk (the PR 4 worst case). Asserted on the MECHANISM (the engine
    hands the refetch the reduced remainder and clears it after), which
    is deterministic; the wall-clock bound itself is covered with a
    wide margin by test_retry_deadline_override_bounds_giveup."""
    from ddstore_tpu.data.readahead import EpochReadahead

    deadline = 2.0
    monkeypatch.setenv("DDSTORE_CMA", "0")
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "1000")  # deadline governs
    monkeypatch.setenv("DDSTORE_RETRY_BASE_MS", "20")
    monkeypatch.setenv("DDSTORE_OP_DEADLINE_S", str(deadline))

    def body(s):
        calls = []

        class Spy:
            def __getattr__(self, k):
                return getattr(s, k)

            def set_retry_deadline(self, seconds):
                calls.append(float(seconds))
                s.set_retry_deadline(seconds)

        fault_configure("reset:1.0", seed=9, ranks=[1])
        batches = [np.arange(64, 96), np.arange(96, 128)]
        err = None
        try:
            with EpochReadahead(Spy(), "v", iter(batches),
                                window_batches=2, depth=1) as ra:
                ra.get_batch(0)
        except DDStoreError as e:
            err = e
        fault_configure("", 0)
        assert s.async_pending() == 0
        return err, calls

    err, calls = _run_pair(body, backend="tcp", rows=64,
                           monkeypatch=monkeypatch)
    assert err is not None and err.code == ERR_PEER_LOST, err
    # The engine set the refetch budget exactly once, to the window's
    # REMAINDER — here exactly the floor min(2, 0.25*deadline): the
    # deadline-governed give-up consumed the whole window budget — and
    # never a fresh full deadline; cleared on the error path.
    assert len(calls) == 2, calls
    assert calls[0] == min(2.0, 0.25 * deadline), calls
    assert calls[1] == 0.0, calls


def test_async_error_path_releases_ticket():
    """Satellite (error-path audit): a failed async batched read frees
    its scratch and releases its ticket — async_pending()==0 afterwards
    (the ASan variant of this scenario runs in test_sanitizers)."""
    with DDStore(backend="local") as s:
        s.add("v", np.arange(64, dtype=np.float32).reshape(32, 2))
        h = s.get_batch_async("v", np.array([1, 1, 7, 10 ** 9]))
        with pytest.raises(DDStoreError):
            h.wait()
        assert s.async_pending() == 0
        # and a repeat wait re-raises instead of returning unfilled bytes
        with pytest.raises(DDStoreError):
            h.wait()
