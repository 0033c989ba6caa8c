"""Epoch-window readahead (ISSUE 3 tentpole): window planner units,
byte-identical equivalence against per-batch ``get_batch`` (duplicates,
ragged, multi-owner), loader epoch equivalence across ring depths, and
the cancellation contract (mid-epoch teardown leaves no in-flight async
reads).

Tier-1 REQUIRED, no skip paths: everything runs under
``JAX_PLATFORMS=cpu`` on the conftest's virtual mesh — no chip or
same-host peer is involved, so a missing accelerator can never skip
the equivalence contracts these tests pin.
"""

import threading
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax

# Everything in this module runs on the conftest virtual mesh — no
# skipif may ever be added here (see the marker's description).
pytestmark = pytest.mark.tier1_required

from ddstore_tpu import DDStore, DDStoreError, SingleGroup, ThreadGroup
from ddstore_tpu.data import (DeviceLoader, DistributedSampler,
                              EpochReadahead, ShardedDataset,
                              plan_epoch_windows, plan_window)
from ddstore_tpu.parallel import make_mesh
from ddstore_tpu.utils.metrics import PipelineMetrics


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"dp": 8})


class TestWindowPlanner:
    # Multi-owner table: 3 owners with different shard sizes.
    STARTS = np.array([0, 10, 30, 64], np.int64)

    def test_run_lists_per_peer(self):
        # Rows 3,5 (owner 0), 11,12,13 (owner 1, one run), 63 (owner 2).
        plan = plan_window(self.STARTS,
                           [np.array([5, 3, 3, 12]),
                            np.array([13, 11, 63, 5])])
        np.testing.assert_array_equal(plan.rows, [3, 5, 11, 12, 13, 63])
        assert plan.n_runs == 4  # [3] [5] [11..13] [63]
        np.testing.assert_array_equal(plan.runs_per_peer, [2, 1, 1])
        # Owner boundary splits a row-adjacent pair: rows 29,30 are
        # adjacent but owned by ranks 1 and 2 — two runs.
        plan = plan_window(self.STARTS, [np.array([29, 30])])
        assert plan.n_runs == 2
        np.testing.assert_array_equal(plan.runs_per_peer, [0, 1, 1])

    def test_duplicate_dedup_across_window(self):
        # Row 5 appears in BOTH batches and twice in the first: fetched
        # once for the whole window, replicated by the gather.
        plan = plan_window(self.STARTS,
                           [np.array([5, 7, 5]), np.array([5, 9])])
        assert plan.rows.size == 3 and plan.dup_rows == 2
        np.testing.assert_array_equal(plan.batch_slice(0), [0, 1, 0])
        np.testing.assert_array_equal(plan.batch_slice(1), [0, 2])

    def test_window_boundary_exactness(self):
        # 5 batches into windows of 2: [2, 2, 1], batch bounds partition
        # each window's request span exactly, short tail included.
        batches = [np.arange(i, i + 4) for i in range(5)]
        plans = plan_epoch_windows(self.STARTS, iter(batches), 2)
        assert [p.n_batches for p in plans] == [2, 2, 1]
        for w, p in enumerate(plans):
            assert p.n_requested == sum(
                b.size for b in batches[2 * w:2 * w + 2])
            for b in range(p.n_batches):
                sel = p.batch_slice(b)
                np.testing.assert_array_equal(p.rows[sel],
                                              batches[2 * w + b])

    def test_rejects_bad_input(self):
        with pytest.raises(ValueError):
            plan_window(self.STARTS, [])
        with pytest.raises(IndexError):
            plan_window(self.STARTS, [np.array([64])])
        with pytest.raises(ValueError):
            plan_epoch_windows(self.STARTS, [np.arange(4)], 0)


class TestAsyncBinding:
    def test_wait_result_and_error(self):
        with DDStore(SingleGroup(), backend="local") as s:
            data = np.arange(40, dtype=np.float32).reshape(20, 2)
            s.add("v", data)
            h = s.get_batch_async("v", [3, 1, 3])
            np.testing.assert_array_equal(h.wait(), data[[3, 1, 3]])
            assert h.done_mono_s is not None
            assert s.async_pending() == 0
            # A failed read surfaces at wait AND frees its ticket.
            bad = s.get_batch_async("v", [99])
            with pytest.raises(DDStoreError):
                bad.wait()
            assert s.async_pending() == 0
            # release() without wait is the non-raising teardown barrier.
            h2 = s.get_batch_async("v", np.arange(20))
            h2.release()
            assert s.async_pending() == 0


class TestEngineEquivalence:
    def test_fixed_width_duplicates(self):
        rng = np.random.default_rng(0)
        data = rng.normal(size=(300, 5)).astype(np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            batches = [rng.integers(0, 300, size=32) for _ in range(7)]
            m = PipelineMetrics()
            with EpochReadahead(s, ds.data_var, iter(batches),
                                window_batches=3, depth=2,
                                metrics=m) as ra:
                for i, b in enumerate(batches):
                    np.testing.assert_array_equal(
                        ra.get_batch(i, idx=b), s.get_batch(ds.data_var, b))
            assert s.async_pending() == 0
            ras = m.readahead_summary()
            assert ras["windows"] == 3
            assert ras["dup_rows"] > 0  # 96-row windows over 300 rows

    def test_ragged(self):
        rng = np.random.default_rng(1)
        samples = [np.full((i % 5 + 1, 2), i, np.float32)
                   for i in range(30)]
        with DDStore(SingleGroup(), backend="local") as s:
            s.add_ragged("g", samples)
            batches = [rng.integers(0, 30, size=8) for _ in range(5)]
            with EpochReadahead(s, "g", iter(batches), window_batches=2,
                                depth=2) as ra:
                for i, b in enumerate(batches):
                    v, l = ra.get_batch(i, idx=b)
                    wv, wl = s.get_ragged_batch("g", b)
                    np.testing.assert_array_equal(l, wl)
                    np.testing.assert_array_equal(v, wv)
            assert s.async_pending() == 0

    def test_multi_owner_rank_stamp(self):
        """4 in-process owners: every windowed row must arrive stamped
        with its owner, byte-identical to per-batch get_batch."""
        world, rows = 4, 64
        name = uuid.uuid4().hex
        errors = []

        def body(rank):
            try:
                g = ThreadGroup(name, rank, world)
                with DDStore(g, backend="local") as s:
                    shard = (np.arange(rows) + rank * rows).astype(
                        np.float64).reshape(rows, 1)
                    s.add("v", shard)
                    s.barrier()
                    if rank == 0:
                        rng = np.random.default_rng(2)
                        batches = [rng.integers(0, world * rows, size=16)
                                   for _ in range(6)]
                        m = PipelineMetrics()
                        with EpochReadahead(s, "v", iter(batches),
                                            window_batches=2, depth=2,
                                            metrics=m) as ra:
                            for i, b in enumerate(batches):
                                np.testing.assert_array_equal(
                                    ra.get_batch(i, idx=b),
                                    s.get_batch("v", b))
                        assert s.async_pending() == 0
                        ras = m.readahead_summary()
                        # 3 remote owners saw runs; window accounting
                        # recorded the per-peer fan-out.
                        assert ras["peer_lists"] > 0
                        assert ras["remote_runs"] > 0
                    s.barrier()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=body, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
        assert not errors, errors

    def test_out_of_order_consumers_recycle_slots_safely(self):
        """Concurrent consumers can finish window w+1's gathers before
        window w's last one — the ring must never hand window w+depth a
        slot whose previous owner is still live (the in-order floor)."""
        rng = np.random.default_rng(3)
        data = rng.normal(size=(256, 4)).astype(np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            for _ in range(10):
                batches = [rng.integers(0, 256, size=32)
                           for _ in range(8)]
                with EpochReadahead(s, ds.data_var, iter(batches),
                                    window_batches=2, depth=2) as ra, \
                        ThreadPoolExecutor(max_workers=3) as ex:
                    futs = [ex.submit(ra.get_batch, i, b)
                            for i, b in enumerate(batches)]
                    for i, f in enumerate(futs):
                        np.testing.assert_array_equal(
                            f.result(), data[batches[i]])
            assert s.async_pending() == 0

    def test_issuer_error_releases_inflight_reads(self):
        """A window whose SECOND variable fails at issue time (after the
        first variable's read is already in flight) must not leak the
        in-flight ticket — it was never registered, so only the issuer's
        error path can release it."""
        data = np.zeros((64, 2), np.float32)
        labels = np.arange(64, dtype=np.int32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data, labels)
            orig = s.read_runs_async
            calls = {"n": 0}

            def flaky(name, *a, **k):
                calls["n"] += 1
                if calls["n"] == 2:  # the label var of window 0
                    raise RuntimeError("boom")
                return orig(name, *a, **k)

            s.read_runs_async = flaky
            try:
                ra = EpochReadahead(s, ds.data_var,
                                    iter([np.arange(8)]),
                                    label_var=ds.label_var,
                                    window_batches=1)
                with pytest.raises(RuntimeError, match="boom"):
                    ra.get_batch(0)
                ra.close()
                assert s.async_pending() == 0
            finally:
                del s.read_runs_async

    def test_replay_divergence_is_loud(self):
        data = np.zeros((64, 2), np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            with EpochReadahead(s, ds.data_var,
                                iter([np.arange(8)]),
                                window_batches=1) as ra:
                with pytest.raises(RuntimeError, match="replay"):
                    ra.get_batch(0, idx=np.arange(8) + 1)


class TestLoaderReadahead:
    def _epochs(self, ds, mesh=None, **kw):
        samp = DistributedSampler(len(ds), 1, 0, seed=11)
        samp.set_epoch(3)
        ld = DeviceLoader(ds, samp, batch_size=32, mesh=mesh, workers=2,
                          **kw)
        return [jax.tree_util.tree_map(np.asarray, b) for b in ld], ld

    def test_epoch_equivalence_all_depths(self):
        rng = np.random.default_rng(4)
        data = rng.normal(size=(256, 3)).astype(np.float32)
        labels = np.arange(256, dtype=np.int32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data, labels)
            base, _ = self._epochs(ds)
            for k in (1, 2, 4):
                got, ld = self._epochs(ds, readahead_windows=k,
                                       readahead_window_batches=2)
                assert ld._readahead_ready, ld.readahead_fallback_reason
                assert len(got) == len(base)
                for (bx, by), (gx, gy) in zip(base, got):
                    np.testing.assert_array_equal(bx, gx)
                    np.testing.assert_array_equal(by, gy)
                assert ld.metrics.summary()["readahead"]["windows"] == 4
            assert s.async_pending() == 0

    def test_collective_composition(self, mesh):
        """readahead × device_collective: window staging feeds the ICI
        exchange's send buffers — byte-identical to the plain path."""
        rng = np.random.default_rng(5)
        data = rng.normal(size=(256, 3)).astype(np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            base, _ = self._epochs(ds, mesh=mesh)
            got, ld = self._epochs(ds, mesh=mesh, device_collective=True,
                                   readahead_windows=2,
                                   readahead_window_batches=2)
            assert ld._readahead_ready and ld._collective_ready, (
                ld.readahead_fallback_reason,
                ld.collective_fallback_reason)
            for b, g in zip(base, got):
                np.testing.assert_array_equal(b, g)
            moved = ld.metrics.bytes_moved()
            assert moved["bytes_over_ici"] > 0
            assert s.async_pending() == 0

    def test_cancellation_leaves_no_inflight_reads(self):
        """Mid-epoch loader teardown: the engine's close() must wait
        out and release every in-flight async read."""
        rng = np.random.default_rng(6)
        data = rng.normal(size=(512, 4)).astype(np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            samp = DistributedSampler(len(ds), 1, 0, seed=12)
            ld = DeviceLoader(ds, samp, batch_size=32, workers=2,
                              readahead_windows=2,
                              readahead_window_batches=2)
            it = iter(ld)
            next(it)
            it.close()  # generator finally: ra.close() + pool join
            assert s.async_pending() == 0

    def test_fallback_reasons(self):
        data = np.zeros((128, 2), np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            samp = DistributedSampler(len(ds), 1, 0)
            # Bare callable dataset: no store/data_var.
            ld = DeviceLoader(lambda i: data[i], samp, batch_size=16,
                              readahead_windows=2)
            assert not ld._readahead_ready
            assert "store" in ld.readahead_fallback_reason
            # Unsized sampler (a bare iterator).
            ld = DeviceLoader(ds, iter(range(128)), batch_size=16,
                              readahead_windows=2)
            assert not ld._readahead_ready
            assert "sized" in ld.readahead_fallback_reason
            # The fallback still yields correct batches per-batch.
            batch = next(iter(ld))
            np.testing.assert_array_equal(batch, data[:16])

            # Sized but one-shot (iter(s) is s): not replayable.
            class _OneShot:
                def __init__(self):
                    self._it = iter(range(128))

                def __len__(self):
                    return 128

                def __iter__(self):
                    return self

                def __next__(self):
                    return next(self._it)

            ld = DeviceLoader(ds, _OneShot(), batch_size=16,
                              readahead_windows=2)
            assert not ld._readahead_ready
            assert "one-shot" in ld.readahead_fallback_reason
            assert s.async_pending() == 0
