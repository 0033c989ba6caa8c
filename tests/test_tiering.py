"""Disk/NVMe tiering: mmap-backed shards served from page cache, and
in-place spill of a RAM shard to a file-backed mapping — the host↔NVMe
capability of BASELINE.md's billion-edge config (absent in the reference,
which doubles RAM at registration, ddstore.hpp:43-49)."""

import threading

import numpy as np
import pytest

from ddstore_tpu import DDStore, DDStoreError, ThreadGroup


def _run_threads(world, body):
    errs = []

    def wrap(r):
        try:
            body(r)
        except Exception as e:  # pragma: no cover
            errs.append((r, e))

    ts = [threading.Thread(target=wrap, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs


def test_add_mmap_single(tmp_path):
    data = np.arange(400, dtype=np.float32).reshape(100, 4)
    path = tmp_path / "shard.bin"
    data.tofile(path)
    with DDStore(backend="local") as s:
        s.add_mmap("m", str(path), np.float32, (4,))
        assert s.total_rows("m") == 100
        np.testing.assert_array_equal(s.get("m", 7, 3), data[7:10])
        np.testing.assert_array_equal(s.get_batch("m", [0, 99, 42]),
                                      data[[0, 99, 42]])
        with pytest.raises(DDStoreError):
            s.update("m", np.zeros((1, 4), np.float32))


def test_add_mmap_rplus_update(tmp_path):
    data = np.zeros((10, 2), np.float64)
    path = tmp_path / "rw.bin"
    data.tofile(path)
    with DDStore(backend="local") as s:
        s.add_mmap("m", str(path), np.float64, (2,), mode="r+")
        s.update("m", np.ones((3, 2)), row_offset=4)
        got = s.get("m", 4, 3)
        assert (got == 1).all()


def test_mmap_multirank_rank_stamp(tmp_path):
    world, rows, dim = 4, 64, 8
    name = f"mm-{tmp_path.name}"

    def body(rank):
        g = ThreadGroup(name, rank, world)
        path = tmp_path / f"shard{rank}.bin"
        np.full((rows, dim), rank + 1, np.float64).tofile(path)
        with DDStore(g, backend="local") as s:
            s.add_mmap("m", str(path), np.float64, (dim,))
            rng = np.random.default_rng(rank)
            idx = rng.integers(0, world * rows, size=32)
            got = s.get_batch("m", idx)
            for i, row in zip(idx, got):
                assert (row == int(i) // rows + 1).all()
            s.barrier()

    _run_threads(world, body)


def test_spill_to_disk_multirank(tmp_path):
    """Spill mid-run: values identical, remote reads still served, update
    refused afterwards."""
    world, rows, dim = 4, 32, 4
    name = f"sp-{tmp_path.name}"

    def body(rank):
        g = ThreadGroup(name, rank, world)
        with DDStore(g, backend="local") as s:
            s.add("v", np.full((rows, dim), rank + 1, np.float32))
            before = s.get_batch("v", np.arange(world * rows))
            p = s.spill_to_disk("v", str(tmp_path / "spill"))
            assert p.endswith(f".r{rank}.bin")
            after = s.get_batch("v", np.arange(world * rows))
            np.testing.assert_array_equal(before, after)
            with pytest.raises(DDStoreError):
                s.update("v", np.zeros((1, dim), np.float32))
            s.barrier()

    _run_threads(world, body)


def test_spill_with_concurrent_reader(tmp_path):
    """The spill_to_disk contract (VERDICT r2 weak #5): a reader hammering
    the spilling rank's shard throughout the swap never sees an error or
    a wrong value — the RAM->mmap rebind is atomic under the store lock,
    with no free/re-add window."""
    import time

    world, rows, dim = 2, 512, 8
    name = f"spc-{tmp_path.name}"
    stop = threading.Event()
    read_errs = []
    reads = [0]

    def body(rank):
        g = ThreadGroup(name, rank, world)
        with DDStore(g, backend="local") as s:
            s.add("v", np.full((rows, dim), rank + 1, np.float64))
            reader = None
            if rank == 1:
                def hammer():
                    try:
                        while not stop.is_set():
                            # rank 0's shard, mid-spill on rank 0
                            row = s.get("v", 5)[0]
                            assert (row == 1.0).all(), row
                            reads[0] += 1
                    except Exception as e:  # pragma: no cover
                        read_errs.append(e)

                reader = threading.Thread(target=hammer)
                reader.start()
            s.spill_to_disk("v", str(tmp_path / "spill"))
            if rank == 1:
                time.sleep(0.05)  # keep reading after the swap too
                stop.set()
                reader.join()
            assert (s.get("v", 5)[0] == 1.0).all()
            s.barrier()

    _run_threads(world, body)
    assert not read_errs, read_errs
    assert reads[0] > 0


def test_spill_ragged_values(tmp_path):
    """Tiering composes with ragged variables: spill the values var, the
    index var stays hot in RAM."""
    with DDStore(backend="local") as s:
        samples = [np.full((i + 1, 2), i, np.float32) for i in range(5)]
        s.add_ragged("g", samples)
        s.spill_to_disk("g/values", str(tmp_path / "spill"))
        for i, want in enumerate(samples):
            np.testing.assert_array_equal(s.get_ragged("g", i), want)


# -- ISSUE 13: first-class tier API + hot-row cache ------------------------


def test_add_file_cold_tier_api(tmp_path):
    """add_file(tier="cold") is the first-class cold registration: the
    shard flows through the normal registry (reads identical), the tier
    is recorded natively (cold gauges), and update() refuses with an
    error NAMING the tier."""
    data = np.arange(800, dtype=np.float32).reshape(100, 8)
    path = tmp_path / "shard.bin"
    data.tofile(path)
    with DDStore(backend="local") as s:
        s.add_file("m", str(path), np.float32, (8,))
        assert s.var_tier("m") == "cold"
        st = s.tiering_stats()
        assert st["cold_vars"] == 1 and st["cold_bytes"] == data.nbytes
        np.testing.assert_array_equal(s.get_batch("m", [0, 99, 42]),
                                      data[[0, 99, 42]])
        with pytest.raises(DDStoreError, match="cold-tier"):
            s.update("m", np.zeros((1, 8), np.float32))
        # tier="hot" loads into RAM: updatable, no cold gauge.
        s.add_file("h", str(path), np.float32, (8,), tier="hot")
        assert s.var_tier("h") == "hot"
        s.update("h", np.zeros((1, 8), np.float32))
        assert s.tiering_stats()["cold_vars"] == 1


def test_hot_cache_prefetch_hit_evict_and_metrics():
    """The hot-row cache round trip: prefetch fills asynchronously,
    get/get_batch serve warmed rows from RAM (byte-identical, counted),
    eviction returns the budget, and summary()["tiering"] reports the
    deltas + hit rate through PipelineMetrics."""
    import time

    from ddstore_tpu.utils.metrics import PipelineMetrics

    with DDStore(backend="local") as s:
        data = np.random.default_rng(0).standard_normal(
            (512, 16)).astype(np.float32)
        s.add("v", data)
        s.tier_configure(1 << 20)
        m = PipelineMetrics()
        m.set_tiering_source(s.tiering_stats)
        m.epoch_start()
        s.cache_prefetch("v", np.arange(100, 200), window=7)
        deadline = time.time() + 10
        while s.tiering_stats()["cache_fills"] < 1:
            assert time.time() < deadline, s.tiering_stats()
            time.sleep(0.005)
        # Single-row get AND batched get both consult the cache.
        np.testing.assert_array_equal(s.get("v", 150, 10),
                                      data[150:160])
        np.testing.assert_array_equal(
            s.get_batch("v", np.arange(100, 200)), data[100:200])
        st = s.tiering_stats()
        assert st["cache_hits"] >= 2 and st["cache_entries"] == 1, st
        assert st["cache_bytes"] == 100 * 16 * 4, st
        # A partially-covered run is a MISS (correct bytes via the
        # normal path), never a partial serve.
        np.testing.assert_array_equal(
            s.get_batch("v", np.arange(150, 250)), data[150:250])
        assert s.tiering_stats()["cache_misses"] >= 1
        assert s.cache_evict(7) == 1
        st = s.tiering_stats()
        assert st["cache_entries"] == 0 and st["cache_bytes"] == 0, st
        m.epoch_end()
        tg = m.summary()["tiering"]
        assert tg["cache_fills"] == 1 and tg["cache_evictions"] == 1
        assert tg["cache_hit_rate"] > 0
        assert s.async_pending() == 0


def test_hot_cache_update_invalidates():
    """Cache coherence: an update() drops the variable's warmed
    entries inside the exclusive section — a post-update read can
    never be served pre-update bytes."""
    import time

    with DDStore(backend="local") as s:
        s.add("v", np.full((64, 4), 1.0, np.float32))
        s.tier_configure(1 << 20)
        s.cache_prefetch("v", np.arange(64), window=0)
        deadline = time.time() + 10
        while s.tiering_stats()["cache_fills"] < 1:
            assert time.time() < deadline
            time.sleep(0.005)
        s.update("v", np.full((64, 4), 2.0, np.float32))
        assert s.tiering_stats()["cache_entries"] == 0
        assert (s.get_batch("v", np.arange(64)) == 2.0).all()


def test_cache_disabled_inert_under_seeded_faults():
    """The inertness pin (PR 7/9/10/11 discipline): with the hot cache
    disabled and no cold vars, an identical seeded chaos schedule
    produces byte- and fault-counter-identical results whether the
    tiering knobs were never touched or explicitly zeroed/evicted —
    the tiering tree adds no draws, no locks, no behavior."""
    from ddstore_tpu import fault_configure

    def run(arm_tiering):
        name = f"in-{arm_tiering}"
        world, rows = 2, 32
        out = {}

        def body(rank):
            g = ThreadGroup(name, rank, world)
            with DDStore(g, backend="local") as s:
                s.add("v", np.full((rows, 8), rank + 1.0, np.float64))
                if arm_tiering and rank == 0:
                    s.tier_configure(0)  # explicit off + evict
                    s.cache_evict(-1)
                    s.tiering_stats()
                s.barrier()
                if rank == 0:
                    fault_configure("reset:0.3,delay:0.2:1", seed=9)
                    try:
                        got = [s.get_batch(
                            "v", np.arange(world * rows))
                            for _ in range(6)]
                    finally:
                        fs = s.fault_stats()
                        fault_configure("", 0)
                    out["got"] = np.stack(got)
                    out["faults"] = {
                        k: v for k, v in fs.items()
                        if k.startswith(("fault_", "injected_"))}
                s.barrier()

        _run_threads(world, body)
        return out

    a, b = run(False), run(True)
    np.testing.assert_array_equal(a["got"], b["got"])
    assert a["faults"] == b["faults"], (a["faults"], b["faults"])


@pytest.mark.parametrize("cold", [False, True],
                         ids=["hot-local", "cold-tcp-over-budget"])
def test_readahead_warms_cache_and_evicts_on_consumption(
        cold, tmp_path, monkeypatch):
    """The tentpole integration: EpochReadahead plans ahead, warms the
    cache with upcoming windows' row lists, the window reads hit RAM,
    and consumption-keyed eviction drains every entry by close(). The
    cold case is the deployment the cache exists for: file-backed cold
    shards read over the wire (CMA off), a whole epoch of a dataset
    TWICE the hot budget, every batch equal to a per-rank seeded oracle
    and no fill failing on the way."""
    from ddstore_tpu.data.readahead import EpochReadahead

    world, rows, dim = 2, 2048, 8
    name = f"warm-ra-{cold}"
    stats = {}
    if cold:
        rows, dim = 8192, 64
        monkeypatch.setenv("DDSTORE_CMA", "0")
        monkeypatch.setenv("DDSTORE_HEARTBEAT_MS", "0")
    budget = world * rows * dim * 4 // 2 if cold else 64 << 20

    def shard(rank):
        if not cold:
            return np.full((rows, dim), rank + 1.0, np.float32)
        return np.random.default_rng(300 + rank).standard_normal(
            (rows, dim)).astype(np.float32)

    def body(rank):
        g = ThreadGroup(name, rank, world)
        with DDStore(g, backend="tcp" if cold else "local") as s:
            if cold:
                path = str(tmp_path / f"shard{rank}.bin")
                shard(rank).tofile(path)
                s.add_file("v", path, np.float32, (dim,), tier="cold")
            else:
                s.add("v", shard(rank))
            s.tier_configure(budget)
            s.barrier()
            if rank == 0:
                rng = np.random.default_rng(4)
                if cold:  # one whole epoch: every row exactly once
                    perm = rng.permutation(world * rows)
                    batches = [perm[i:i + 128]
                               for i in range(0, len(perm), 128)]
                else:
                    batches = [rng.integers(0, world * rows, size=128)
                               for _ in range(24)]
                full = np.concatenate([shard(r) for r in range(world)])
                eng = EpochReadahead(s, "v", list(batches),
                                     window_batches=8 if cold else 4,
                                     depth=2)
                for i, b in enumerate(batches):
                    np.testing.assert_array_equal(
                        eng.get_batch(i, b), full[b])
                eng.close()
                stats.update(s.tiering_stats())
                stats["pending"] = s.async_pending()
            s.barrier()

    _run_threads(world, body)
    assert stats["cache_fills"] >= 4, stats
    assert stats["cache_hits"] > 0, stats
    assert stats["cache_entries"] == 0 and stats["cache_bytes"] == 0, \
        stats
    assert stats["pending"] == 0
    if cold:
        assert stats["cold_vars"] == 1, stats
        assert stats["cache_max_bytes"] == budget, stats
        assert stats["cache_fill_failures"] == 0, stats


def test_cold_placement_for_mirrors_and_kept_copies(tmp_path):
    """Mirror fills and snapshot kept copies LAND COLD under the
    per-tenant placement policy: the cold ledger grows, failover
    serves byte-identical from the cold mirror, and a snapshot stays
    byte-stable from a cold kept copy."""
    import os

    env = {"DDSTORE_REPLICATION": "2",
           "DDSTORE_TIER_COLD_DIR": str(tmp_path)}
    backup = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    world, rows = 2, 32
    name = f"cold-{tmp_path.name}"
    out = {}
    try:
        def body(rank):
            g = ThreadGroup(name, rank, world)
            with DDStore(g, backend="local") as s:
                s.set_tier_placement("", True)  # default tenant: cold
                data = np.full((rows, 8), rank + 1.0, np.float64)
                s.add("v", data)
                s.barrier()
                if rank == 0:
                    st = s.tiering_stats()
                    # rank 0 hosts rank 1's mirror, cold-placed.
                    out["cold_bytes"] = st["cold_bytes"]
                    # Failover read served from the cold mirror.
                    s.mark_suspect(1)
                    got = s.get_batch("v",
                                      np.arange(rows, 2 * rows))
                    assert (got == 2.0).all()
                    assert s.failover_stats()["failover_reads"] >= 1
                    s.mark_suspect(1, False)
                s.barrier()
                # Snapshot kept copy lands cold too.
                snap = s.attach("eval", snapshot=True) if rank == 0 \
                    else None
                s.barrier()
                s.update("v", np.full((rows, 8), 9.0, np.float64))
                s.barrier()
                if rank == 0:
                    got = snap.get("v", 0, rows)
                    assert (got == 1.0).all()  # pinned pre-update
                    out["cold_after_keep"] = \
                        s.tiering_stats()["cold_bytes"]
                    snap.detach()
                s.barrier()

        _run_threads(world, body)
    finally:
        for k, v in backup.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    mirror_bytes = rows * 8 * 8
    assert out["cold_bytes"] >= mirror_bytes, out
    assert out["cold_after_keep"] >= out["cold_bytes"] + mirror_bytes, \
        out


def test_cache_trace_events_pinned():
    """ddtrace: fill/hit/evict events land under the tiering hooks
    (the acceptance pin for the trace half of the observability)."""
    import time

    from ddstore_tpu import binding

    binding.trace_configure(1)
    binding.trace_reset()
    try:
        with DDStore(backend="local") as s:
            s.add("v", np.arange(256, dtype=np.float32).reshape(32, 8))
            s.tier_configure(1 << 20)
            s.cache_prefetch("v", np.arange(32), window=1)
            deadline = time.time() + 10
            while s.tiering_stats()["cache_fills"] < 1:
                assert time.time() < deadline
                time.sleep(0.005)
            s.get_batch("v", np.arange(8, 24))
            s.cache_evict(1)
            events = binding.trace_dump()
            kinds = {binding.TRACE_TYPES.get(int(e["type"]), "?")
                     for e in events}
            assert {"cache_fill", "cache_hit",
                    "cache_evict"} <= kinds, kinds
    finally:
        binding.trace_configure(0)
        binding.trace_reset()


def test_tenant_quota_charges_cache_and_returns_on_evict():
    """The cache is QUOTA-CHARGED: a configured tenant's warmed bytes
    count against its byte budget until eviction, and an over-budget
    tenant's prefetch is skipped (advisory), never kErrQuota."""
    import time

    with DDStore(backend="local") as s:
        data = np.zeros((64, 16), np.float32)
        shard = data.nbytes
        # Quota configured BEFORE add so the shard itself reserves —
        # headroom then covers exactly one 16-row cache entry.
        s.set_tenant_quota("", shard + 16 * 16 * 4)
        s.add("v", data)
        s.tier_configure(1 << 20)
        assert s.tenant_stats()[""]["bytes"] == shard
        s.cache_prefetch("v", np.arange(16), window=1)
        deadline = time.time() + 10
        while s.tiering_stats()["cache_fills"] < 1:
            assert time.time() < deadline
            time.sleep(0.005)
        assert s.tenant_stats()[""]["bytes"] == shard + 16 * 16 * 4
        # Over budget now: the next prefetch is skipped, counted, and
        # nothing raises.
        before = s.tiering_stats()["cache_over_budget"]
        s.cache_prefetch("v", np.arange(32, 64), window=2)
        assert s.tiering_stats()["cache_over_budget"] == before + 1
        assert s.tiering_stats()["cache_entries"] == 1
        s.cache_evict(-1)
        assert s.tenant_stats()[""]["bytes"] == shard


def test_mmap_soak_1e8_rows(tmp_path):
    """Scale proof for tiering + the index plane (VERDICT r4 next #5):
    a 10^8-row mmap-backed shard (sparse file — BASELINE config-5 row
    counts without config-5 disk) is Feistel-sampled in batched gets
    while RSS stays bounded by the pages actually touched, nowhere near
    the reference's copy-everything-into-RAM behavior
    (ddstore.hpp:43-49). Stamped sentinel rows pin read correctness at
    far offsets; a full scan is deliberately NOT done (bounded time).
    The harness (ddstore_tpu.utils.soak) is shared with the fault and
    corruption soaks of test_fault.py and test_integrity.py."""
    from ddstore_tpu.utils.soak import mmap_soak

    m = mmap_soak(rows=100_000_000, batch=65536, nbatches=32,
                  directory=str(tmp_path))
    assert m["sentinels_ok"]
    assert m["rows_sampled"] == 32 * 65536
    # Registration must NOT copy the shard (that is the whole point).
    assert m["rss_add_delta_mb"] < 200, m
    # RSS bound: touched pages (<= 2M distinct rows over 195k file
    # pages => at most the 800 MB file) + slack, NOT O(row count).
    assert m["rss_delta_mb"] < 1500, m
    # Usefulness floor: well above one-row-at-a-time latency territory.
    assert m["rows_per_s"] > 50_000, m
