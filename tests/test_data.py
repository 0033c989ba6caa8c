"""Dataset adapter, sampler, and device loader tests."""

import numpy as np
import pytest

from ddstore_tpu import DDStore, SingleGroup
from ddstore_tpu.data import DeviceLoader, DistributedSampler, ShardedDataset
from ddstore_tpu.data.dataset import nsplit


class TestNsplit:
    def test_even(self):
        assert nsplit(12, 4) == [3, 3, 3, 3]

    def test_remainder_spread(self):
        assert nsplit(14, 4) == [4, 4, 3, 3]
        assert sum(nsplit(14, 4)) == 14

    def test_more_parts_than_rows(self):
        assert nsplit(2, 4) == [1, 1, 0, 0]


class TestDistributedSampler:
    def test_partition_disjoint_and_complete(self):
        total, world = 103, 4
        samplers = [DistributedSampler(total, world, r, seed=7)
                    for r in range(world)]
        chunks = [s.epoch_indices() for s in samplers]
        # Equal counts on every rank (fence alignment requirement,
        # SURVEY §3.3).
        assert len({len(c) for c in chunks}) == 1
        allidx = np.concatenate(chunks)
        # Padded by wrapping: every index covered at least once.
        assert set(allidx) == set(range(total))

    def test_epoch_changes_order(self):
        s = DistributedSampler(64, 2, 0, seed=1)
        s.set_epoch(0)
        e0 = s.epoch_indices()
        s.set_epoch(1)
        e1 = s.epoch_indices()
        assert not np.array_equal(e0, e1)
        s.set_epoch(0)
        np.testing.assert_array_equal(s.epoch_indices(), e0)  # deterministic

    def test_no_shuffle_is_strided(self):
        s = DistributedSampler(8, 2, 1, shuffle=False)
        np.testing.assert_array_equal(s.epoch_indices(), [1, 3, 5, 7])

    def test_total_smaller_than_world(self):
        # Wrap-padding must keep every rank at num_samples even when the
        # dataset is smaller than the world (fence-alignment regression).
        total, world = 3, 8
        chunks = [DistributedSampler(total, world, r, seed=0).epoch_indices()
                  for r in range(world)]
        assert all(len(c) == 1 for c in chunks)
        assert set(np.concatenate(chunks)) == {0, 1, 2}

    def test_drop_last(self):
        s = DistributedSampler(10, 4, 0, drop_last=True)
        assert len(s) == 2
        assert len(s.epoch_indices()) == 2


class TestShardedDataset:
    def test_single_rank_roundtrip(self, rng):
        with DDStore(SingleGroup(), backend="local") as store:
            data = rng.standard_normal((50, 3, 4)).astype(np.float32)
            labels = rng.integers(0, 10, size=50).astype(np.int32)
            ds = ShardedDataset(store, data, labels)
            assert len(ds) == 50
            x, y = ds[17]
            np.testing.assert_array_equal(x, data[17])
            assert y == labels[17]
            xb, yb = ds.fetch([3, 1, 41])
            np.testing.assert_array_equal(xb, data[[3, 1, 41]])
            np.testing.assert_array_equal(yb, labels[[3, 1, 41]])

    def test_sample_major_indexing(self, rng):
        # Regression for the reference's disp=1 trap (distdataset.py:63,84):
        # index i must return sample i, not flat element i.
        with DDStore(SingleGroup(), backend="local") as store:
            data = np.arange(20 * 784, dtype=np.float32).reshape(20, 784)
            ds = ShardedDataset(store, data)
            np.testing.assert_array_equal(ds[5], data[5])

    def test_no_labels(self, rng):
        with DDStore(SingleGroup(), backend="local") as store:
            data = rng.standard_normal((10, 4)).astype(np.float64)
            ds = ShardedDataset(store, data)
            np.testing.assert_array_equal(ds.fetch([2, 2, 9]),
                                          data[[2, 2, 9]])


class TestDeviceLoaderHost:
    def _make(self, store, n=64, dim=8, **kw):
        data = np.arange(n * dim, dtype=np.float32).reshape(n, dim)
        labels = np.arange(n, dtype=np.int64)
        ds = ShardedDataset(store, data, labels)
        sampler = DistributedSampler(n, 1, 0, seed=3)
        return data, labels, DeviceLoader(ds, sampler, **kw)

    def test_host_mode_batches(self):
        with DDStore(SingleGroup(), backend="local") as store:
            data, labels, loader = self._make(store, batch_size=16, mesh=None)
            batches = list(loader)
            assert len(batches) == 4 == len(loader)
            for xb, yb in batches:
                assert xb.shape == (16, 8)
                np.testing.assert_array_equal(xb, data[yb])  # label == index

    def test_epoch_covers_everything(self):
        with DDStore(SingleGroup(), backend="local") as store:
            data, labels, loader = self._make(store, batch_size=16)
            seen = np.concatenate([yb for _, yb in loader])
            assert set(seen) == set(range(64))

    def test_drop_last_static_shapes(self):
        with DDStore(SingleGroup(), backend="local") as store:
            data, labels, loader = self._make(store, n=70, batch_size=16)
            shapes = {xb.shape for xb, _ in loader}
            assert shapes == {(16, 8)}

    def test_producer_error_surfaces(self):
        with DDStore(SingleGroup(), backend="local") as store:
            data = np.zeros((8, 2), np.float32)
            ds = ShardedDataset(store, data)
            loader = DeviceLoader(ds, [0, 1, 99], batch_size=1,
                                  drop_last=False)
            from ddstore_tpu import DDStoreError
            with pytest.raises(DDStoreError):
                list(loader)

    def test_worker_defaults(self):
        # Store-backed datasets get parallel fetch; a bare callable is
        # serialized unless it opts in (ADVICE r1 #3 / VERDICT r2 weak #6).
        with DDStore(SingleGroup(), backend="local") as store:
            _, _, loader = self._make(store, batch_size=16)
            assert loader.workers == 2
        unsafe = lambda idx: np.zeros((len(idx), 2), np.float32)
        assert DeviceLoader(unsafe, [0, 1], batch_size=1).workers == 1
        safe = lambda idx: np.zeros((len(idx), 2), np.float32)
        safe.thread_safe = True
        assert DeviceLoader(safe, [0, 1], batch_size=1).workers == 2

        # A non-callable dataset declaring itself unsafe wins too.
        class Unsafe:
            thread_safe = False

            def fetch(self, idx):
                return np.zeros((len(idx), 2), np.float32)

            def __len__(self):
                return 2

        assert DeviceLoader(Unsafe(), [0, 1], batch_size=1).workers == 1
        # An explicit value is an explicit declaration either way.
        assert DeviceLoader(unsafe, [0, 1], batch_size=1,
                            workers=3).workers == 3

    def test_stateful_transform_serialized(self):
        # A non-reentrant transform must never be entered concurrently
        # even with workers > 1 (transforms are serialized by default).
        import threading
        import time as _time

        busy = threading.Event()
        calls = []

        def transform(batch):
            assert not busy.is_set(), "transform entered concurrently"
            busy.set()
            _time.sleep(0.005)
            calls.append(len(batch[0]))
            busy.clear()
            return batch

        with DDStore(SingleGroup(), backend="local") as store:
            _, _, loader = self._make(store, batch_size=8,
                                      transform=transform, workers=4,
                                      prefetch=8)
            n = sum(1 for _ in loader)
            assert n == 8 and len(calls) == 8
            assert loader._transform_lock is not None

    def test_threadsafe_transform_not_locked(self):
        t = lambda b: b
        t.thread_safe = True
        with DDStore(SingleGroup(), backend="local") as store:
            _, _, loader = self._make(store, batch_size=8, transform=t,
                                      workers=4)
            assert loader._transform_lock is None

    def test_metrics_populated(self):
        with DDStore(SingleGroup(), backend="local") as store:
            _, _, loader = self._make(store, batch_size=16)
            for _ in loader:
                pass
            s = loader.metrics.summary()
            assert s["host_fetch"]["count"] == 4
            assert 0.0 <= s["loader_wait_share"] <= 1.0
            assert "input_pipeline_efficiency" not in s
            assert "stage_enqueue" in s and "device_put" not in s


class TestDeviceLoaderJax:
    def test_sharded_device_batches(self):
        import jax
        from ddstore_tpu.parallel import make_mesh

        mesh = make_mesh({"dp": 8})
        with DDStore(SingleGroup(), backend="local") as store:
            n, dim = 64, 8
            data = np.arange(n * dim, dtype=np.float32).reshape(n, dim)
            labels = np.arange(n, dtype=np.int64)
            ds = ShardedDataset(store, data, labels)
            sampler = DistributedSampler(n, 1, 0, seed=3)
            loader = DeviceLoader(ds, sampler, batch_size=16, mesh=mesh)
            for xb, yb in loader:
                assert isinstance(xb, jax.Array)
                assert xb.shape == (16, dim)
                # Sharded over dp: 8 shards of 2 rows each.
                assert len(xb.sharding.device_set) == 8
                np.testing.assert_array_equal(np.asarray(xb),
                                              data[np.asarray(yb)])
