"""Device-collective fetch (ISSUE 2 tentpole): owner-partition planner
units and byte-identical equivalence against the host ``get_batch`` path
on the 8-device virtual CPU mesh.

Tier-1 REQUIRED, no skip paths: everything here runs under
``JAX_PLATFORMS=cpu`` on the conftest's virtual mesh — no chip or
same-host peer is involved, so a missing accelerator can never skip
the equivalence contract these tests pin (rank-stamp / byte-identity
incl. duplicates and ragged rows).
"""

import threading
import uuid

import numpy as np
import pytest

import jax

# Everything in this module runs on the conftest virtual mesh — no
# skipif may ever be added here (see the marker's description).
pytestmark = pytest.mark.tier1_required

from ddstore_tpu import DDStore, SingleGroup, ThreadGroup
from ddstore_tpu.data import (DeviceLoader, DistributedSampler,
                              ShardedDataset, device_fetch_batch,
                              device_fetch_ragged_batch,
                              host_bytes_over_dcn, plan_device_fetch)
from ddstore_tpu.parallel import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"dp": 8})


def _simulate_exchange(plan, staged):
    """Numpy oracle of exchange_rows: all_to_all block transpose +
    per-destination inverse permutation."""
    d, cap, per = plan.n_shards, plan.cap, plan.per_shard
    out = np.empty((plan.idx.size,) + staged.shape[1:], staged.dtype)
    for dst in range(d):
        # Destination dst receives block dst from every source, in
        # source order — exactly lax.all_to_all(tiled=False) semantics.
        recv = np.concatenate([
            staged[s * (d * cap) + dst * cap:
                   s * (d * cap) + (dst + 1) * cap] for s in range(d)])
        for j in range(per):
            out[dst * per + j] = recv[plan.inv[dst * per + j]]
    return out


class TestPlanner:
    # Uneven multi-owner table: 4 owners with different shard sizes.
    STARTS = np.array([0, 10, 30, 33, 64], np.int64)

    def test_owner_partition_and_order(self):
        rng = np.random.default_rng(0)
        idx = rng.integers(0, 64, size=32)
        plan = plan_device_fetch(self.STARTS, idx, 8)
        assert plan.n_owners == 4 and plan.shards_per_owner == 2
        # Every position lands with its true owner...
        want_owner = np.searchsorted(self.STARTS, idx, "right") - 1
        np.testing.assert_array_equal(plan.owner, want_owner)
        # ...and each owner's shards send only that owner's rows.
        np.testing.assert_array_equal(plan.src // 2, plan.owner)
        # owner_positions is a partition of [0, B).
        got = np.sort(np.concatenate(plan.owner_positions))
        np.testing.assert_array_equal(got, np.arange(32))

    def test_send_counts_and_cap(self):
        rng = np.random.default_rng(1)
        idx = rng.integers(0, 64, size=64)
        plan = plan_device_fetch(self.STARTS, idx, 8)
        # Column sums: every destination receives exactly its slice.
        np.testing.assert_array_equal(plan.send_counts.sum(axis=0),
                                      np.full(8, plan.per_shard))
        # Static capacity bound holds for ANY ownership pattern.
        assert plan.send_counts.max() <= plan.cap
        assert plan.cap == -(-plan.per_shard // plan.shards_per_owner)

    def test_worst_case_skew_fits_cap(self):
        # Every requested row owned by owner 1 (rows 10..29): the whole
        # batch funnels through 2 source shards and still fits cap.
        idx = np.full(32, 15, np.int64)
        plan = plan_device_fetch(self.STARTS, idx, 8)
        assert plan.send_counts.max() <= plan.cap
        staged = np.zeros((plan.staged_rows, 1), np.float64)
        staged[plan.staged_pos, 0] = idx.astype(np.float64)
        np.testing.assert_array_equal(
            _simulate_exchange(plan, staged)[:, 0], idx)

    def test_inverse_perm_reconstructs_batch(self):
        rng = np.random.default_rng(2)
        idx = rng.integers(0, 64, size=48)  # duplicates likely
        plan = plan_device_fetch(self.STARTS, idx, 8)
        staged = np.zeros((plan.staged_rows, 2), np.float32)
        staged[plan.staged_pos] = np.stack(
            [idx, idx * 3], axis=1).astype(np.float32)
        got = _simulate_exchange(plan, staged)
        np.testing.assert_array_equal(got[:, 0], idx.astype(np.float32))
        np.testing.assert_array_equal(got[:, 1], (idx * 3).astype(np.float32))

    def test_ledger(self):
        idx = np.arange(32, dtype=np.int64)
        plan = plan_device_fetch(self.STARTS, idx, 8)
        led = plan.bytes_ledger(16)
        assert led["bytes_over_dcn"] == 0
        assert led["bytes_local_get"] == 32 * 16
        assert led["bytes_over_ici"] == 8 * 7 * plan.cap * 16
        assert led["rows_over_ici"] == \
            plan.send_counts.sum() - np.trace(plan.send_counts)

    def test_rejects_bad_geometry(self):
        with pytest.raises(ValueError):
            plan_device_fetch(self.STARTS, np.arange(30), 8)  # 30 % 8
        with pytest.raises(ValueError):  # 3 owners don't divide 8 shards
            plan_device_fetch(np.array([0, 10, 30, 64]), np.arange(8), 8)
        with pytest.raises(ValueError):
            plan_device_fetch(self.STARTS, np.empty(0, np.int64), 8)
        with pytest.raises(IndexError):
            plan_device_fetch(self.STARTS, np.full(4, 64, np.int64), 4)

    def test_tight_cap_overflow_raises(self):
        idx = np.full(32, 15, np.int64)  # max skew
        with pytest.raises(ValueError):
            plan_device_fetch(self.STARTS, idx, 8, cap=1)
        # A generous explicit cap still plans fine.
        plan = plan_device_fetch(self.STARTS, idx, 8, cap=4)
        assert plan.cap == 4


class TestDeviceEquivalence:
    def test_single_owner_shuffled_batch(self, mesh):
        rng = np.random.default_rng(3)
        data = rng.normal(size=(500, 7)).astype(np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            s.add("v", data)
            idx = rng.integers(0, 500, size=64)  # duplicates included
            out = device_fetch_batch(s, "v", idx, mesh)
            assert out.sharding.spec == jax.P("dp")
            np.testing.assert_array_equal(np.asarray(out), data[idx])

    def test_multi_owner_rank_stamp(self, mesh):
        """4 in-process owners x 8 shards: every row must arrive stamped
        with its owner, byte-identical to the host path."""
        world, rows, dim = 4, 64, 5
        name = uuid.uuid4().hex
        errors = []

        def body(rank):
            try:
                g = ThreadGroup(name, rank, world)
                with DDStore(g, backend="local") as s:
                    shard = (np.arange(rows) + rank * rows).astype(
                        np.float64).reshape(rows, 1) * np.ones((1, dim))
                    s.add("v", shard)
                    s.barrier()
                    if rank == 0:
                        rng = np.random.default_rng(4)
                        for _ in range(3):
                            idx = rng.integers(0, world * rows, size=32)
                            want = s.get_batch("v", idx)
                            got = device_fetch_batch(s, "v", idx, mesh)
                            np.testing.assert_array_equal(
                                np.asarray(got), want)
                    s.barrier()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=body, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(180)
        assert not errors, errors

    def test_ragged_batch(self, mesh):
        rng = np.random.default_rng(5)
        samples = [np.full((i % 6 + 1, 3), i, np.float32)
                   for i in range(40)]
        with DDStore(SingleGroup(), backend="local") as s:
            s.add_ragged("g", samples)
            idx = rng.integers(0, 40, size=16)  # duplicates included
            padded, lens = device_fetch_ragged_batch(s, "g", idx, mesh,
                                                     max_len=6)
            values, want_lens = s.get_ragged_batch("g", idx)
            np.testing.assert_array_equal(lens, want_lens)
            pos = 0
            padded = np.asarray(padded)
            for j, l in enumerate(want_lens):
                np.testing.assert_array_equal(
                    padded[j, :l], values[pos:pos + int(l)])
                assert (padded[j, l:] == 0).all()
                pos += int(l)


class TestLoaderCollective:
    def _epoch(self, loader):
        return [jax.tree_util.tree_map(np.asarray, b) for b in loader]

    def test_epoch_equivalence_and_ledger(self, mesh):
        rng = np.random.default_rng(6)
        data = rng.normal(size=(512, 4)).astype(np.float32)
        labels = np.arange(512, dtype=np.int32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data, labels)

            def loader(collective):
                samp = DistributedSampler(len(ds), 1, 0, seed=9)
                samp.set_epoch(2)
                return DeviceLoader(ds, samp, batch_size=64, mesh=mesh,
                                    workers=1,
                                    device_collective=collective)

            host, coll = loader(False), loader(True)
            assert coll._collective_ready, coll.collective_fallback_reason
            for (hx, hy), (cx, cy) in zip(self._epoch(host),
                                          self._epoch(coll)):
                np.testing.assert_array_equal(hx, cx)
                np.testing.assert_array_equal(hy, cy)
            moved = coll.metrics.bytes_moved()
            assert moved["bytes_local_get"] > 0
            assert moved["bytes_over_ici"] > 0
            assert moved["bytes_over_dcn"] == 0
            # Host path on a single-owner store: nothing crosses DCN
            # either, and the collective counters stay zero.
            hmoved = host.metrics.bytes_moved()
            assert hmoved["bytes_local_get"] == 0
            assert hmoved["bytes_over_ici"] == 0

    def test_fallback_reasons(self, mesh):
        data = np.zeros((128, 2), np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            samp = DistributedSampler(len(ds), 1, 0)
            # No mesh: host path.
            ld = DeviceLoader(ds, samp, batch_size=16,
                              device_collective=True)
            assert not ld._collective_ready
            assert "mesh" in ld.collective_fallback_reason
            # Host transform: host path.
            ld = DeviceLoader(ds, samp, batch_size=16, mesh=mesh,
                              transform=lambda x: x,
                              device_collective=True)
            assert not ld._collective_ready
            assert "transform" in ld.collective_fallback_reason
            # Batch not divisible by shards: host path.
            ld = DeviceLoader(ds, samp, batch_size=12, mesh=mesh,
                              device_collective=True)
            assert not ld._collective_ready
            assert "divisible" in ld.collective_fallback_reason
            # A bare callable dataset: host path.
            ld = DeviceLoader(lambda i: data[i], samp, batch_size=16,
                              mesh=mesh, device_collective=True)
            assert not ld._collective_ready
            # The fallback still yields correct batches.
            batch = next(iter(ld))
            assert np.asarray(batch).shape == (16, 2)

    def test_host_dcn_ledger_multi_owner(self):
        """Host-path ledger: remote-owned rows count as DCN bytes."""
        world, rows, dim = 4, 16, 3
        name = uuid.uuid4().hex
        errors = []

        def body(rank):
            try:
                g = ThreadGroup(name, rank, world)
                with DDStore(g, backend="local") as s:
                    s.add("v", np.zeros((rows, dim), np.float32))
                    s.barrier()
                    if rank == 0:
                        # 8 remote rows + 8 local rows.
                        idx = np.concatenate([np.arange(rows, rows + 8),
                                              np.arange(8)])
                        dcn = host_bytes_over_dcn(s, "v", idx)
                        assert dcn == 8 * dim * 4, dcn
                    s.barrier()
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=body, args=(r,))
              for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert not errors, errors
