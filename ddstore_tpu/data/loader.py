"""Prefetching device loader: store → host batch → sharded device arrays.

The reference's hot loop fetches every sample synchronously inside
``DataLoader.__next__`` with zero prefetch and zero batching
(num_workers=0, two blocking one-sided reads per sample — SURVEY §3.2/§3.3,
called out in §7 as the anti-pattern to fix). Here the loader:

* draws whole batches of indices from the sampler,
* fetches them with one coalesced, multi-peer ``get_batch``,
* stages them to devices with a sharded transfer
  (``jax.make_array_from_process_local_data`` — each DP shard receives its
  slice directly),
* runs fetch+stage on a background thread, `prefetch` batches deep, so
  host I/O overlaps device compute (double buffering by default),
* records, on the host clock, the consumer's wait and the fetch and
  stage-enqueue latencies (``PipelineMetrics``), and writes the same three
  as spans into a profiler trace when one is being taken:
  ``ddstore:wait_batch`` (the consumer blocked on the next batch),
  ``ddstore:fetch`` (plan, remote reads and copy of one batch) and
  ``ddstore:stage`` (the ENQUEUE of its host-to-device transfers; no
  worker blocks on a transfer, so the span does not hold the transfer's
  end). The spans of one batch share ``batch``, its number in the epoch.
"""

from __future__ import annotations

import itertools
import os
import random
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, Optional

import numpy as np

from ..binding import ERR_ADMISSION, ERR_PEER_LOST, DDStoreError
from ..utils.metrics import PipelineMetrics
from ..utils.profile import annotate

try:  # the loader is importable without jax for host-only use
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
except Exception:  # pragma: no cover
    jax = None


class _PendingExchange:
    """A staged collective fetch whose device exchange still needs the
    consumer thread (single-thread collective dispatch discipline)."""

    __slots__ = ("finalize",)

    def __init__(self, finalize: Callable):
        self.finalize = finalize


class DeviceLoader:
    """Iterate device-ready (sharded) batches from a store-backed dataset.

    Parameters
    ----------
    dataset: object with ``fetch(indices) -> array | tuple`` and ``__len__``
        (e.g. :class:`ShardedDataset`), or a bare callable.
    sampler: iterable of global indices for THIS rank's epoch (e.g.
        :class:`DistributedSampler`).
    batch_size: per-process batch size. With a mesh, must divide by the
        number of addressable devices on the batch axis.
    mesh / spec: optional device staging target. If given, batches are
        device arrays sharded over ``spec`` (default: leading dim over
        axis "dp"); if None, numpy batches are yielded (host-only mode).
    prefetch: how many batches are kept in flight ahead of the consumer.
    workers: fetch+stage worker threads. One worker pipelines host IO
        against device compute; more overlap multiple batches' host paths
        with each other — needed to keep small/fast models fed (ctypes
        releases the GIL during store reads, and staging is mostly
        off-GIL transfer work, so threads genuinely parallelize).
        Default (None): 2 for store-backed datasets (whose ``fetch`` is
        thread-safe by construction), 1 for a bare callable unless it
        declares ``thread_safe = True``. Passing an explicit ``workers``
        value is the caller's declaration that ``dataset.fetch`` is safe
        at that concurrency.
    drop_last: drop the trailing partial batch (keeps shapes static for
        jit — recompile-free epochs).
    device_collective: stage batches with the device-collective fetch
        (``data/device_fetch.py``): one purely local ``get_batch`` per
        host + an on-device ``all_to_all`` over ICI delivers every row
        to its destination DP shard — remote rows never cross DCN and
        the batch is device_put exactly once. Requires a mesh, the
        default ``P(axis)`` spec, no host transform, and a store-backed
        dataset exposing ``data_var``; anything else falls back to the
        host path with the reason in ``collective_fallback_reason``.
    readahead_windows: > 0 enables epoch-window readahead
        (``data/readahead.py``): the sampler's whole epoch is sliced
        into windows of ``readahead_window_batches`` batches, each
        window's rows fetched as ONE sorted deduplicated bulk read per
        variable through the native async engine into a preallocated
        staging ring of this many buffers — window N+1 stays in flight
        over the transport while window N is consumed, and per-batch
        delivery is an in-RAM gather. Composes with both the host path
        and ``device_collective`` (window staging happens before the
        ICI exchange). Needs a store-backed dataset (``store`` +
        fixed-width ``data_var``) and a *sized, replayable* sampler
        (two iterations yield identical indices — every
        ``DistributedSampler`` qualifies; a one-shot generator does
        not); otherwise the loader falls back to per-batch fetch with
        the reason in ``readahead_fallback_reason``.
    readahead_window_batches: window size W in batches (default 8).
        Bigger windows coalesce better (denser rows per peer shard →
        longer stripe-shaped runs) at the cost of staging memory:
        ``readahead_windows × W × batch_size`` rows per variable.
    transform: optional host-side function applied to each fetched batch.
        With workers > 1 the transform is serialized under a lock (fetch
        and staging still run in parallel), so stateful transforms — e.g.
        one sharing a np.random.Generator — are race-free by default.
        Note the lock guarantees exclusion, not order: workers reach the
        transform in fetch-completion order, so a shared RNG is consumed
        in a run-dependent sequence — for bit-deterministic augmentation
        pass workers=1. Mark the transform ``thread_safe = True`` (or
        pass ``transform_thread_safe=True``) to let it run concurrently.
    """

    def __init__(self, dataset, sampler: Iterable[int], batch_size: int,
                 mesh: Optional["Mesh"] = None, axis: str = "dp",
                 prefetch: int = 4, drop_last: bool = True,
                 transform: Optional[Callable] = None,
                 spec: Optional["PartitionSpec"] = None,
                 workers: Optional[int] = None,
                 transform_thread_safe: bool = False,
                 device_collective: bool = False,
                 readahead_windows: int = 0,
                 readahead_window_batches: int = 8):
        self.dataset = dataset
        self.sampler = sampler
        self.batch_size = int(batch_size)
        self.mesh = mesh
        self.axis = axis
        self.prefetch = max(1, int(prefetch))
        if workers is None:
            # Store-backed datasets expose fetch() whose reads go through
            # the native core (thread-safe by construction), so objects
            # default to 2 workers — but an explicit thread_safe attribute
            # on the dataset wins in either direction; bare callables
            # default to a single worker unless they opt in.
            fetch_safe = getattr(dataset, "thread_safe",
                                 not callable(dataset))
            workers = 2 if fetch_safe else 1
        self.workers = max(1, int(workers))
        self.drop_last = drop_last
        self.transform = transform
        self._transform_lock = None
        if (transform is not None and self.workers > 1
                and not transform_thread_safe
                and not getattr(transform, "thread_safe", False)):
            self._transform_lock = threading.Lock()
        self.metrics = PipelineMetrics()
        # Store-backed datasets expose their DDStore; wiring its planner
        # counters in gives every epoch summary the scatter-read plan view
        # (runs/peer, coalesce ratio, dedup hits) alongside the latencies.
        store = getattr(dataset, "store", None)
        if store is not None and hasattr(store, "plan_stats"):
            self.metrics.set_plan_source(store.plan_stats)
        if store is not None and hasattr(store, "fault_stats"):
            # Epoch summaries carry the fault/retry ledger next to the
            # plan view: summary()["faults"] is how a chaos run proves
            # "faults absorbed, zero give-ups" from the record alone.
            self.metrics.set_fault_source(store.fault_stats)
        if store is not None and hasattr(store, "failover_stats"):
            # Replicated-read failover ledger: summary()["failover"]
            # shows per-epoch reroutes/suspects/mirror traffic — an R>1
            # epoch that lost a rank proves "replicas served, zero
            # give-ups" from the record alone.
            self.metrics.set_failover_source(store.failover_stats)
        if store is not None and hasattr(store, "tenant_stats"):
            # Multi-tenant ledger: summary()["tenants"] carries each
            # tenant's per-epoch quota rejections, admission/deferral
            # counts and read/served traffic — a shared-service epoch
            # proves its QoS behavior from the record alone. Inert
            # (empty) on single-tenant stores.
            self.metrics.set_tenant_source(store.tenant_stats)
        if store is not None and hasattr(store, "trace_summary"):
            # ddtrace: summary()["trace"] carries this epoch's event
            # captures/drops, flight-recorder activity and measured
            # span-latency percentiles whenever tracing is on (inert —
            # and absent from the summary — while it is off). The
            # begin snapshot uses the cheap counters-only source.
            self.metrics.set_trace_source(
                store.trace_summary,
                getattr(store, "trace_stats", None))
        if store is not None and hasattr(store, "integrity_stats"):
            # Integrity ledger: summary()["integrity"] carries this
            # epoch's verified reads/bytes, mismatch/retry/failover
            # ladder activity and scrub results whenever verification
            # or scrubbing is in force (inert — and absent from the
            # summary — while both are off).
            self.metrics.set_integrity_source(store.integrity_stats)
        if store is not None and hasattr(store, "tiering_stats"):
            # Tiered-storage ledger: summary()["tiering"] carries this
            # epoch's hot-cache hit/miss/fill/evict deltas and the
            # cold-tier gauges whenever the cache is armed or a cold
            # variable is registered (inert — and absent from the
            # summary — otherwise).
            self.metrics.set_tiering_source(store.tiering_stats)
        if store is not None and hasattr(store, "metrics_snapshot"):
            # ddmetrics: summary()["latency"] carries this epoch's
            # live p50/p90/p99 per (class, route, peer, tenant) from
            # the always-on native histograms — no tracing required.
            self.metrics.set_latency_source(store.metrics_snapshot)
        if store is not None and hasattr(store, "slo_summary"):
            # SLO monitor: summary()["slo"] carries the per-epoch
            # evaluation/breach ledger; the epoch boundary below
            # evaluates the objectives and fires the scheduler's
            # replan trigger per breached tenant (inert with no SLOs
            # configured).
            self.metrics.set_slo_source(store.slo_summary)
        if store is not None and hasattr(store, "gateway_stats"):
            # Serving gateway: summary()["gateway"] carries this
            # epoch's admission/lease deltas (admitted/deferred/
            # rejected, attach/expiry churn) whenever the gateway is
            # armed (absent from the summary when off).
            self.metrics.set_gateway_source(store.gateway_stats)
        if store is not None and hasattr(store, "lane_bytes"):
            # Per-lane byte deltas land in summary()["bytes_moved"]
            # (lane_bytes / tcp_lanes_used / lane_utilization): whether
            # striped reads actually spread across the lane pool is
            # diagnosable from the epoch record alone.
            self.metrics.set_lane_source(store.lane_bytes)
        # Cost-model scheduler (ddstore_tpu.sched): plans route x lanes
        # x readahead depth x async width jointly from the shared
        # measurement substrate, replacing the knobs' independent
        # tuners whenever it has confident samples. Created even when
        # DDSTORE_SCHED=0 (disabled it never pins anything) so
        # summary()["sched"] always states the enablement
        # (tests/test_sched.py reads it). User env pins freeze their
        # knobs; the planner plans the rest.
        self.sched = None
        if store is not None and hasattr(store, "sched_cells"):
            from ..sched.planner import Scheduler

            nvars = 1 + (1 if getattr(dataset, "label_var", None)
                         else 0)
            # requested_depth 0 = this loader runs no readahead: the
            # scheduler then plans route/lanes only and leaves the
            # depth/width knobs (and the store's other async users)
            # alone.
            self.sched = Scheduler(store, nvars=nvars,
                                   requested_depth=int(readahead_windows))
            self.metrics.set_sched_source(self.sched.snapshot)
        if mesh is not None and jax is None:  # pragma: no cover
            raise RuntimeError("jax unavailable but mesh given")
        # `spec` overrides the default leading-dim-over-`axis` layout, e.g.
        # P("dp", "sp") to stage sequence-sharded token windows directly in
        # the layout the train step's in_shardings demand.
        if spec is None:
            spec = PartitionSpec(axis)
        self._sharding = (NamedSharding(mesh, spec)
                         if mesh is not None else None)
        # Device-collective staging (`device_collective=True`): each
        # host reads only the rows it OWNS (one purely local get_batch),
        # stages them sharded, and an on-device all_to_all over ICI
        # delivers every row to its destination DP shard — the permuted
        # batch never rides DCN or the double host->device bounce. Falls
        # back to the host path automatically when the prerequisites
        # don't hold (no mesh, custom spec/transform, a dataset without
        # store+data_var, or a batch geometry the planner rejects);
        # `collective_fallback_reason` records why.
        self.device_collective = bool(device_collective)
        self.collective_fallback_reason: Optional[str] = None
        self._collective_ready = False
        if self.device_collective:
            self._collective_ready = self._collective_usable(
                dataset, mesh, axis, spec, transform)
        # Epoch-window readahead (`readahead_windows=K`): whole-epoch
        # read planning + bulk window fetches through the native async
        # engine, per-batch delivery as in-RAM gathers. Usability is
        # checked once here; the engine itself is per-epoch (built in
        # __iter__, closed in its finally — mid-epoch teardown waits
        # out and releases every in-flight native read).
        self.readahead_windows = max(0, int(readahead_windows))
        self.readahead_window_batches = max(1,
                                            int(readahead_window_batches))
        self.readahead_fallback_reason: Optional[str] = None
        self._readahead_ready = False
        # Staging ring handed from epoch to epoch (reallocating +
        # re-faulting the window buffers every epoch costs real time).
        self._ra_ring = None
        if self.readahead_windows > 0:
            self._readahead_ready = self._readahead_usable()
        # Mid-epoch degradation latch: once a readahead window fails
        # even its per-batch retry (a TRANSIENT failure — permanent
        # owner death raises instead), every worker of this epoch stops
        # consulting the engine and falls back to per-batch fetch. Reset
        # per epoch — a fresh engine gets a fresh chance. The lock makes
        # the latch-and-count a single step (racing workers must not
        # double-count the degradation event).
        self._ra_degraded = threading.Event()
        self._ra_degrade_mu = threading.Lock()
        # Gateway admission deferrals back off with seeded jitter (the
        # same reproducibility contract as the native retry ladder's
        # DDSTORE_FAULT_SEED); the lock serializes racing prefetch
        # workers over the shared PRNG.
        self._admission_rng = random.Random(
            int(os.environ.get("DDSTORE_FAULT_SEED", "0") or 0))
        self._admission_mu = threading.Lock()

    def _readahead_usable(self) -> bool:
        store = getattr(self.dataset, "store", None)
        data_var = getattr(self.dataset, "data_var", None)
        reason = None
        if store is None or data_var is None:
            reason = "dataset exposes no store/data_var"
        elif store.is_ragged(data_var):
            # The engine itself handles ragged windows, but a ragged
            # dataset's fetch() does sample packing the loader cannot
            # reproduce from raw rows — per-batch path keeps it exact.
            reason = "ragged data_var (dataset.fetch packs samples)"
        elif not hasattr(self.sampler, "__len__"):
            reason = "sampler is not sized"
        elif iter(self.sampler) is self.sampler:
            reason = ("sampler is a one-shot iterator (readahead "
                      "replays the epoch; two iterations must yield "
                      "identical indices)")
        if reason is not None:
            self.readahead_fallback_reason = reason
            return False
        return True

    def _collective_usable(self, dataset, mesh, axis, spec,
                           transform) -> bool:
        reason = None
        store = getattr(dataset, "store", None)
        if mesh is None or jax is None:
            reason = "no mesh/ICI available"
        elif spec != PartitionSpec(axis):
            reason = f"custom spec {spec} (exchange delivers P({axis!r}))"
        elif transform is not None:
            reason = "host-side transform set"
        elif store is None or getattr(dataset, "data_var", None) is None:
            reason = "dataset exposes no store/data_var"
        elif axis not in mesh.shape:
            reason = f"mesh has no {axis!r} axis"
        elif jax.process_count() > 1:
            # Single-controller only for now: multi-process staging
            # (per-host local slices) is not yet wired — see
            # device_fetch.exchange_staged.
            reason = "multi-process mesh (single-controller only)"
        else:
            d = int(mesh.shape[axis])
            if self.batch_size % d:
                reason = (f"batch {self.batch_size} not divisible by "
                          f"{d} shards")
            elif d % store.world:
                reason = (f"{d} shards not divisible by store world "
                          f"{store.world}")
        if reason is not None:
            self.collective_fallback_reason = reason
            return False
        return True

    def _record_host_dcn(self, idx: np.ndarray) -> None:
        """Host-path side of the bytes-moved ledger: rows owned by other
        ranks ride the DCN transport (plus labels when present)."""
        from .device_fetch import host_bytes_over_dcn

        store = getattr(self.dataset, "store", None)
        data_var = getattr(self.dataset, "data_var", None)
        if store is None or data_var is None:
            return
        dcn = host_bytes_over_dcn(store, data_var, idx)
        label_var = getattr(self.dataset, "label_var", None)
        if label_var is not None:
            dcn += host_bytes_over_dcn(store, label_var, idx)
        self.metrics.add_bytes(bytes_over_dcn=dcn)

    def _fetch_collective(self, idx: np.ndarray, seq: int = 0,
                          ra=None):
        """Host half of the collective staging, on a WORKER thread:
        plan + local reads + send-buffer fill. Returns a thunk the
        consumer thread runs to dispatch the exchange — collective
        program launches from concurrent threads interleave across the
        per-device executors and deadlock (see
        ``device_fetch.StagedFetch``), so the exchange must ride the
        same thread as the train step. Raises ValueError for geometries
        the planner rejects (caller falls back per batch). With a
        readahead engine (``ra``), the send buffers are filled from the
        staged window instead of per-owner store reads — window staging
        happens per host BEFORE the ICI exchange."""
        from .device_fetch import (exchange_staged, plan_device_fetch,
                                   stage_batch)

        store = self.dataset.store
        data_var = self.dataset.data_var
        d = int(self.mesh.shape[self.axis])
        with self.metrics.fetch.timed(), \
                annotate("ddstore:device_fetch", batch=seq, rows=len(idx)):
            plan = plan_device_fetch(store.row_starts(data_var), idx, d)
            # Consume the window delivery only once the plan is viable —
            # a ValueError above falls back to the host path, which will
            # consume this seq itself.
            rows = ra.batch_rows(seq, idx=idx) if ra is not None else []
            staged = [stage_batch(store, data_var, idx, d, plan=plan,
                                  metrics=self.metrics,
                                  rows=rows[0] if rows else None)]
            label_var = getattr(self.dataset, "label_var", None)
            if label_var is not None:
                # Labels share the plan: same indices, same shard split
                # (ShardedDataset registers both with one nsplit).
                staged.append(stage_batch(
                    store, label_var, idx, d, plan=plan,
                    metrics=self.metrics,
                    rows=rows[1] if len(rows) > 1 else None))

        def finalize():
            with self.metrics.stage.timed(), \
                    annotate("ddstore:device_exchange", batch=seq):
                out = [exchange_staged(sf, self.mesh, self.axis)
                       for sf in staged]
            return out[0] if len(out) == 1 else tuple(out)

        return _PendingExchange(finalize)

    # -- internals ---------------------------------------------------------

    def _index_batches(self) -> Iterator[np.ndarray]:
        it = iter(self.sampler)
        while True:
            idx = list(itertools.islice(it, self.batch_size))
            if not idx:
                return
            if len(idx) < self.batch_size and self.drop_last:
                return
            yield np.asarray(idx, dtype=np.int64)

    def _admission_backoff(self, e: BaseException) -> None:
        """Honor a serving-gateway retry-after hint: one bounded,
        seeded-jitter sleep before this batch falls to the per-batch
        path. Deferral is flow control, not failure — no ladder latch,
        no replan. Jitter is drawn from a loader-local PRNG seeded off
        ``DDSTORE_FAULT_SEED`` so chaos runs stay reproducible."""
        hint_ms = int(getattr(e, "retry_after_ms", 0) or 0)
        sleep_s = min(max(hint_ms, 1), 1000) / 1000.0
        with self._admission_mu:
            sleep_s *= 0.5 + self._admission_rng.random()
        time.sleep(sleep_s)

    def _degrade_readahead(self, e: BaseException) -> None:
        """Latch the per-epoch readahead degradation (idempotent across
        racing workers — first failure wins) and record the reason
        chain."""
        with self._ra_degrade_mu:
            if self._ra_degraded.is_set():
                return
            self._ra_degraded.set()
            self.readahead_fallback_reason = f"degraded mid-epoch: {e}"
            self.metrics.add_fault_event(readahead_degraded=1)
        if self.sched is not None:
            # Ladder engagement is a regime change: replan (outside the
            # latch lock — the replan takes the scheduler's own lock).
            self.sched.on_degradation("readahead")

    def _fetch(self, idx: np.ndarray, seq: int = 0, ra=None):
        if ra is not None and self._ra_degraded.is_set():
            ra = None
        if self._collective_ready:
            try:
                return self._fetch_collective(idx, seq, ra)
            except ValueError:
                # A geometry this batch can't satisfy (e.g. a short
                # trailing batch with drop_last=False): host path for
                # this batch only.
                pass
            except DDStoreError as e:
                # Degradation ladder, collective rung: a TRANSIENT
                # staging failure (native retries + the engine's window
                # retry already ran) drops THIS batch to the host path
                # below. Permanent owner death is fatal — surface it
                # (it names the dead owner; elastic.recover is next).
                if e.code == ERR_PEER_LOST:
                    if self.sched is not None:
                        self.sched.on_degradation("peer_lost")
                    raise
                if e.code == ERR_ADMISSION:
                    # Defer, not peer-lost: the serving gateway shed
                    # this read to protect another tenant's SLO.
                    # Nothing died and nothing is broken — honor the
                    # retry-after hint, retry THIS batch per-batch, and
                    # leave the epoch's readahead/collective machinery
                    # armed (no degradation latch, no replan trigger).
                    self.metrics.add_fault_event(
                        admission_deferred_batches=1)
                    self._admission_backoff(e)
                    ra = None  # this batch only; the latch stays clear
                else:
                    if self.collective_fallback_reason is None:
                        self.collective_fallback_reason = \
                            f"degraded mid-epoch: {e}"
                    self.metrics.add_fault_event(
                        collective_batch_fallbacks=1)
                    if self.sched is not None:
                        self.sched.on_degradation("collective")
                    if ra is not None:
                        # The engine raised before any window delivery
                        # for this seq (batch_rows fails before marking
                        # delivered), so the host path must not consult
                        # it either — it would re-raise the same error.
                        self._degrade_readahead(e)
                        ra = None
        with self.metrics.fetch.timed(), \
                annotate("ddstore:fetch", batch=seq, rows=len(idx)):
            batch = None
            if ra is not None:
                try:
                    # Window delivery: an in-RAM gather from the staged
                    # window (the engine recorded the transport-side
                    # bytes once per window, dedup included — no
                    # per-batch DCN accounting here).
                    batch = ra.get_batch(seq, idx=idx)
                except DDStoreError as e:
                    # Ladder, readahead rung: transient window failure
                    # that survived the engine's own per-batch retry —
                    # the rest of the epoch runs per-batch. Fatal codes
                    # surface.
                    if e.code == ERR_PEER_LOST:
                        if self.sched is not None:
                            self.sched.on_degradation("peer_lost")
                        raise
                    if e.code == ERR_ADMISSION:
                        # Defer, not peer-lost: back off per the
                        # gateway's hint and serve this one batch from
                        # the host path — the readahead engine stays
                        # armed for the rest of the epoch.
                        self.metrics.add_fault_event(
                            admission_deferred_batches=1)
                        self._admission_backoff(e)
                    else:
                        self._degrade_readahead(e)
            if batch is None:
                batch = (self.dataset(idx) if callable(self.dataset)
                         else self.dataset.fetch(idx))
                self._record_host_dcn(idx)
        if self.transform is not None:
            if self._transform_lock is not None:
                with self._transform_lock:
                    batch = self.transform(batch)
            else:
                batch = self.transform(batch)
        if self._sharding is None:
            return batch
        with self.metrics.stage.timed(), \
                annotate("ddstore:stage", batch=seq, rows=len(idx)) as span:
            if span.is_enabled():  # counted only while somebody traces
                span.set_metadata(bytes=sum(
                    np.asarray(x).nbytes
                    for x in jax.tree_util.tree_leaves(batch)))
            put = lambda x: jax.make_array_from_process_local_data(
                self._sharding, np.ascontiguousarray(x))
            # tree_map preserves container types (tuples, NamedTuple
            # batches like GraphBatch, dicts) while staging every leaf.
            return jax.tree_util.tree_map(put, batch)

    def _make_readahead(self):
        """Per-epoch readahead engine over a SECOND, independent replay
        of the sampler (the engine verifies both replays agree batch by
        batch). None when readahead is off or fell back."""
        if not self._readahead_ready:
            return None
        from .readahead import EpochReadahead

        # Check the ring OUT for this iterator (restored at teardown):
        # two overlapping iterators of one loader must never share
        # staging buffers — the second allocates its own.
        ring, self._ra_ring = self._ra_ring, None
        # The DEPTH knob is the scheduler's: the user's readahead_windows
        # is the requested ceiling (and the ring budget); the planner
        # may run shallower when the core budget says deeper windows
        # cannot fetch concurrently anyway. DDSTORE_READAHEAD_DEPTH
        # pins it.
        depth = self.readahead_windows
        if self.sched is not None:
            depth = self.sched.planned_depth(self.readahead_windows)
        return EpochReadahead(
            self.dataset.store, self.dataset.data_var,
            self._index_batches(),
            label_var=getattr(self.dataset, "label_var", None),
            window_batches=self.readahead_window_batches,
            depth=depth, metrics=self.metrics,
            ring=ring, sched=self.sched)

    def __iter__(self):
        # Ordered worker pool: index batches are submitted in order and
        # futures consumed in submission order, so parallel fetch+stage
        # never reorders the epoch's batch stream. Early exit (break) is
        # safe: shutdown waits for in-flight fetches, then the readahead
        # engine's close() releases every in-flight async read, so a
        # subsequent store teardown can't race either.
        self.metrics.epoch_start()
        self._ra_degraded.clear()  # fresh epoch, fresh engine, fresh chance
        # Liveness sweep at the epoch boundary: newly suspected peers
        # fire the store's peer listeners (the scheduler replans its
        # routes/lanes off the dead peer BEFORE this epoch's plan is
        # applied below, instead of at the first deadline burn).
        check_health = getattr(getattr(self.dataset, "store", None),
                               "check_health", None)
        if check_health is not None:
            try:
                check_health()
            except Exception:
                pass  # liveness polling must never fail an epoch
        if self.sched is not None:
            # Epoch-boundary replan BEFORE the engine is built: the
            # planned depth/width govern this epoch's ring and
            # admission, and the route/lane pins land before the first
            # fetch.
            self.sched.on_epoch()
        ex = ThreadPoolExecutor(max_workers=self.workers,
                                thread_name_prefix="ddstore-loader")
        futs = deque()
        ra = self._make_readahead()
        try:
            it = enumerate(self._index_batches())
            for seq, idx in itertools.islice(it, self.prefetch):
                futs.append(ex.submit(self._fetch, idx, seq, ra))
            # Futures complete in submission order, so the batch the
            # consumer waits for is the count of those it has taken.
            taken = 0
            while futs:
                t0 = time.perf_counter()
                with annotate("ddstore:wait_batch", batch=taken):
                    item = futs.popleft().result()
                    if isinstance(item, _PendingExchange):
                        # Collective dispatch happens HERE, on the
                        # consumer thread — the only thread launching
                        # collective programs (the train step is its
                        # other client).
                        item = item.finalize()
                self.metrics.wait.record(time.perf_counter() - t0)
                taken += 1
                nxt = next(it, None)
                if nxt is not None:
                    futs.append(ex.submit(self._fetch, nxt[1], nxt[0],
                                          ra))
                yield item
        finally:
            for f in futs:
                f.cancel()
            if ra is not None:
                # Wake any worker blocked on a window BEFORE joining the
                # pool: shutdown(wait=True) on a worker waiting for a
                # ring slot that will never free would deadlock.
                ra.close()
                self._ra_ring = ra.ring  # reuse next epoch
            ex.shutdown(wait=True)
            # SLO evaluation at the epoch boundary ("per epoch
            # window"), BEFORE the metrics freeze so this epoch's
            # summary()["slo"] carries its own verdict. A breach has
            # already dumped the flight recorder natively; here it
            # closes the observe->react loop by replanning the
            # breached tenant's routes/lanes/shares.
            self._check_slos()
            self._check_admission_pressure()
            self.metrics.epoch_end()

    def _check_slos(self) -> None:
        """Evaluate the store's tenant latency SLOs over the epoch
        window that just ended and fire one scheduler replan per
        breached tenant (the PR 6 degradation path). Inert — one cheap
        native call returning nothing — while no SLOs are configured;
        never fails the epoch."""
        store = getattr(self.dataset, "store", None)
        if store is None or not hasattr(store, "evaluate_slos"):
            return
        try:
            breaches = store.evaluate_slos()
        except Exception:
            return  # observability must never fail an epoch
        if self.sched is not None:
            for b in breaches:
                self.sched.on_degradation(f"slo:{b['tenant']}")

    def _check_admission_pressure(self) -> None:
        """Feed the epoch's gateway deferred/rejected deltas to the
        planner as defer pressure (one replan, not one per deferral —
        admission events inside the epoch only sleep and retry). Inert
        with the gateway off; never fails the epoch."""
        if self.sched is None:
            return
        try:
            gw = self.metrics.gateway_summary()
            deferred = int(gw.get("deferred", 0))
            rejected = int(gw.get("rejected", 0))
        except Exception:
            return  # observability must never fail an epoch
        if deferred or rejected:
            self.sched.on_admission_pressure(deferred, rejected)

    def __len__(self) -> int:
        n = len(self.sampler)
        return n // self.batch_size if self.drop_last else \
            (n + self.batch_size - 1) // self.batch_size
