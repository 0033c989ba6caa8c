"""Multi-lane striped TCP transport (ISSUE 5): lane configuration, the
adaptive lane autotuner, per-lane byte accounting, and surviving-lane
stripe retry.

Contracts pinned here:

* ``DDSTORE_TCP_LANES`` sizes the per-peer lane pool (legacy alias
  ``DDSTORE_CONNS_PER_PEER`` still honored); ``=1`` is the exact old
  single-connection contract — bytes and error codes identical;
* a striped read deals its bytes round-robin across the engaged lanes
  (per-peer per-lane counters balanced, sum == bytes moved);
* the autotuner ramps 1, 2, 4, ... and PARKS once per-lane throughput
  stops scaling (warm-window measurement in the adaptive router's
  style); ``DDSTORE_TCP_LANES_AUTOTUNE=0`` pins the full pool;
* a transient fault on one lane retries only that stripe, on a
  surviving lane — chaos semantics (injected > 0, give-ups == 0,
  byte-identical results) are unchanged from the single-lane tree;
* the lane ledger surfaces in ``PipelineMetrics`` ``bytes_moved``.

Everything runs on in-process ThreadGroup TCP stores — tier-1 required,
no accelerator, no skip paths.
"""

import threading
import uuid

import numpy as np
import pytest

from ddstore_tpu import DDStore, ThreadGroup, fault_configure
from ddstore_tpu.utils.metrics import PipelineMetrics

pytestmark = pytest.mark.tier1_required


@pytest.fixture(autouse=True)
def _disarm_injector():
    yield
    fault_configure("", 0)


@pytest.fixture(autouse=True)
def _wire_path_only(monkeypatch):
    """Every test here targets the TCP/UDS lane path."""
    monkeypatch.setenv("DDSTORE_CMA", "0")
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "8")
    monkeypatch.setenv("DDSTORE_RETRY_BASE_MS", "2")


def _seeded_shard(rank, rows=8, row_elems=1 << 19):
    """A shard no two rows of which agree: a stripe that lands on the
    wrong peer OR at the wrong lane offset cannot read as correct."""
    return np.random.default_rng(3 + rank).standard_normal(
        (rows, row_elems))


def _run_pair(body0, world=2, rows=8, row_elems=1 << 19, seeded=False):
    """Two-rank ThreadGroup TCP store with BIG rows (4 MiB) so remote
    reads cross the striping threshold; rank r's shard is all (r+1), or
    ``_seeded_shard(r)``. Rank 0 runs ``body0(store)``."""
    name = uuid.uuid4().hex
    errors = []
    result = {}

    def worker(rank):
        try:
            g = ThreadGroup(name, rank, world)
            with DDStore(g, backend="tcp") as s:
                s.add("v", _seeded_shard(rank, rows, row_elems) if seeded
                      else np.full((rows, row_elems), rank + 1,
                                   np.float64))
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
    if errors:
        raise errors[0][1]
    assert not any(t.is_alive() for t in ts), "rank thread hung"
    return result.get("out")


def test_single_lane_is_the_old_contract(monkeypatch):
    """DDSTORE_TCP_LANES=1: one connection per peer, no striping, and
    the read is byte-identical to the shard contents."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "1")

    def body(s):
        got = s.get("v", 8, 8)
        assert (got == 2).all()
        st = s.lane_state()
        lb = s.lane_bytes()
        return st, lb

    st, lb = _run_pair(body)
    assert st["max_lanes"] == 1 and st["active_lanes"] == 1
    assert st["parked"] is True  # 1-lane pools park at construction
    assert len(lb) == 1 and lb[0] == 8 * (1 << 19) * 8


@pytest.mark.parametrize("seeded", [False, True],
                         ids=["rank-stamp", "seeded-oracle"])
def test_forced_lanes_stripe_and_balance(seeded, monkeypatch):
    """Pinned 4-lane striping (autotune off): a bulk remote read deals
    round-robin across all four lanes, bytes balanced, result exact —
    against the rank stamp, and against a per-row seeded oracle, where
    the striped read, a scattered batch with duplicates and the windowed
    readahead delivery of the same batches must all return exactly the
    owner's bytes."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "4")
    monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")

    def body(s):
        got = s.get("v", 8, 8)
        if not seeded:
            assert (got == 2).all()
            return s.lane_state(), s.lane_bytes(), s.lane_bytes(1)
        np.testing.assert_array_equal(got, _seeded_shard(1))
        st, lb, lb1 = s.lane_state(), s.lane_bytes(), s.lane_bytes(1)
        from ddstore_tpu.data.readahead import EpochReadahead

        oracle = np.concatenate([_seeded_shard(r) for r in range(2)])
        eq = [np.array([9, 3, 9, 14, 3]), np.array([15, 0, 8, 12])]
        with EpochReadahead(s, "v", iter(eq), window_batches=2,
                            depth=2) as ra:
            for i, b in enumerate(eq):
                np.testing.assert_array_equal(ra.get_batch(i, idx=b),
                                              oracle[b])
                np.testing.assert_array_equal(s.get_batch("v", b),
                                              oracle[b])
        assert s.async_pending() == 0
        return st, lb, lb1

    st, lb, lb1 = _run_pair(body, seeded=seeded)
    assert st["max_lanes"] == 4 and st["active_lanes"] == 4
    assert st["autotune"] is False and st["parked"] is True
    total = 8 * (1 << 19) * 8
    assert len(lb) == 4 and sum(lb) == total
    assert all(b > 0 for b in lb), lb
    # round-robin equal-chunk dealing balances a power-of-two read
    assert max(lb) <= 2 * min(lb), lb
    assert lb1 == lb  # only peer 1 was read


def test_legacy_conns_per_peer_alias(monkeypatch):
    monkeypatch.delenv("DDSTORE_TCP_LANES", raising=False)
    monkeypatch.setenv("DDSTORE_CONNS_PER_PEER", "3")
    monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")

    def body(s):
        got = s.get("v", 8, 4)
        assert (got == 2).all()
        return s.lane_state()

    st = _run_pair(body)
    assert st["max_lanes"] == 3 and st["active_lanes"] == 3


def test_autotuner_ramps_and_parks(monkeypatch):
    """The tuner measures striped bulk reads at 1, 2, 4 lanes (one
    warm-up + two clean windows per level) and parks on the best level;
    results stay exact throughout the ramp."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "4")
    monkeypatch.delenv("DDSTORE_TCP_LANES_AUTOTUNE", raising=False)

    def body(s):
        states = []
        for _ in range(16):
            got = s.get("v", 8, 8)
            assert (got == 2).all()
            states.append(s.lane_state())
            if states[-1]["parked"]:
                break
        return states

    states = _run_pair(body)
    assert states[0]["autotune"] is True
    assert states[0]["parked"] is False
    assert states[0]["active_lanes"] == 1  # ramp starts at 1 lane
    final = states[-1]
    assert final["parked"] is True, final
    assert 1 <= final["active_lanes"] <= 4
    assert final["samples"] >= 2
    assert final["best_bw_bytes_per_s"] > 0


def test_scatter_class_has_its_own_tuner(monkeypatch):
    """Bulk stripes and scatter dealing need not share a lane optimum,
    so each class
    parks independently — scatter-only traffic must never inherit the
    bulk verdict, and vice versa."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "2")
    monkeypatch.delenv("DDSTORE_TCP_LANES_AUTOTUNE", raising=False)

    def body(s):
        rng = np.random.default_rng(0)
        for _ in range(16):
            idx = rng.integers(4096, 8192, size=256)
            got = s.get_batch("v", idx)
            assert (got == 2).all()
            st = s.lane_state()
            if st["scatter_parked"]:
                break
        return st

    st = _run_pair(body, rows=4096, row_elems=64)
    assert st["scatter_parked"] is True, st
    assert 1 <= st["scatter_active_lanes"] <= 2
    # no bulk traffic flowed: the bulk tuner must still be measuring
    assert st["parked"] is False, st


def test_lane_fault_retries_on_surviving_lane(monkeypatch):
    """Chaos on the lane path: injected resets mid-stripe retry only
    the failed stripe (on the next lane of the set) — reads stay
    byte-identical, retries fire, nothing gives up."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "4")
    monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")

    def body(s):
        clean = [s.get("v", 16 + i, 4).copy() for i in range(4)]
        fault_configure("reset:0.25,trunc:0.1", seed=7, ranks=[1])
        chaos = [s.get("v", 16 + i, 4) for i in range(4)]
        fs = s.fault_stats()
        fault_configure("", 0)
        for a, b in zip(clean, chaos):
            np.testing.assert_array_equal(a, b)
        return fs

    fs = _run_pair(body, rows=16)
    assert fs["injected_reset"] + fs["injected_trunc"] > 0, fs
    assert fs["retry_attempts"] > 0, fs
    assert fs["retry_giveups"] == 0, fs


@pytest.mark.parametrize("lanes", ["1", "4"])
def test_seeded_fault_counters_deterministic(lanes, monkeypatch):
    """Acceptance: fault counters under a seeded spec are deterministic
    on BOTH the 1-lane and the N-lane path. The workload stripes into
    one single-op frame per lane, so the number of draws (and therefore
    every counter) is a pure function of the seeded schedule regardless
    of lane/thread interleaving."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", lanes)
    monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")

    def run_once(s):
        fault_configure("reset:0.2,delay:0.1:2", seed=42, ranks=[1])
        for i in range(6):
            got = s.get("v", 16 + 2 * (i % 4), 2)
            assert (got == 2).all()
        fs = s.fault_stats()
        fault_configure("", 0)
        return fs

    fs1 = _run_pair(run_once, rows=16)
    fs2 = _run_pair(run_once, rows=16)
    # backoff_ms carries per-lane deterministic JITTER (salted by lane
    # index), and which lane consumes a faulting draw is an interleaving
    # fact — every decision COUNTER must still reproduce exactly.
    for fs in (fs1, fs2):
        fs.pop("retry_backoff_ms")
    assert fs1 == fs2, (fs1, fs2)
    assert fs1["fault_checks"] > 0
    assert fs1["retry_giveups"] == 0


def test_stripe_failure_releases_async_tickets(monkeypatch):
    """All stripes released on failure: a striped async read against a
    dead budget (100% resets, RETRY_MAX=0) surfaces its error and
    leaves async_pending() == 0 — no leaked scratch or tickets."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "4")
    monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "0")

    from ddstore_tpu import DDStoreError

    def body(s):
        fault_configure("reset:1.0", seed=3, ranks=[1])
        h = s.get_batch_async("v", np.arange(16, 24))
        raised = False
        try:
            h.wait()
        except DDStoreError:
            raised = True
        fault_configure("", 0)
        assert raised
        return s.async_pending()

    pending = _run_pair(body, rows=16)
    assert pending == 0


def test_lane_ledger_in_pipeline_metrics(monkeypatch):
    """The per-lane ledger rides PipelineMetrics: per-epoch lane deltas,
    tcp_lanes_used, and utilization land in bytes_moved()."""
    monkeypatch.setenv("DDSTORE_TCP_LANES", "4")
    monkeypatch.setenv("DDSTORE_TCP_LANES_AUTOTUNE", "0")

    def body(s):
        m = PipelineMetrics()
        m.set_lane_source(s.lane_bytes)
        m.epoch_start()
        got = s.get("v", 8, 8)
        assert (got == 2).all()
        m.epoch_end()
        return m.summary()

    summary = _run_pair(body)
    moved = summary["bytes_moved"]
    assert moved["tcp_lanes_used"] == 4, moved
    assert sum(moved["lane_bytes"]) == 8 * (1 << 19) * 8
    assert 0.5 <= moved["lane_utilization"] <= 1.0, moved
