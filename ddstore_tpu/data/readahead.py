"""Epoch-window readahead: plan whole-epoch reads, fetch them as bulk
stripes, hide the transport behind compute.

The training hot path is "any rank reads any row" under a
``DistributedSampler`` permutation — but the permutation for the WHOLE
epoch is known before the first batch is fetched, and neither the
reference nor the per-batch scatter engine exploits that: even after
coalescing, a per-batch scatter read tops out well below the bulk-stripe
path (r05: cma_batch 5.04 vs cma_stripe 9.56 GB/s), because a single
batch's rows are sparse in every peer's shard, so runs stay short. This
module closes that gap by planning over a *window* of W batches at once:

* :func:`plan_window` merges the window's batches into one sorted,
  deduplicated row list — W× denser in each peer's shard, so the native
  scatter planner coalesces it into a few long, offset-sorted,
  stripe-shaped runs per peer (and every run is *direct*: sorted input
  means output order == shard order, no scratch staging);
* :class:`EpochReadahead` keeps a ring of ``depth`` preallocated window
  staging buffers filled through the native async engine
  (``store.get_batch_async`` → ``dds_get_batch_async`` on the store's
  background pool) — window N+1 is always in flight over the transport
  while window N is consumed, hiding DCN latency behind compute;
* per-batch delivery is a cheap in-RAM gather from the staged window
  (exact request order; duplicate rows are fetched once per window and
  replicated by the gather; ragged samples ride the existing two-round
  ragged fetch per window and are re-split per batch).

``DeviceLoader(readahead_windows=K)`` wires this under both the host
path and the device-collective path (window staging happens before the
ICI exchange); the engine is also usable standalone over a raw store
(``tests/test_readahead.py`` drives it so).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ..binding import (DEFAULT_OP_DEADLINE_S, ERR_PEER_LOST,
                       ERR_TRANSPORT, DDStoreError, trace_emit,
                       trace_enabled, trace_flight, trace_new_span)

__all__ = ["WindowPlan", "plan_window", "plan_epoch_windows",
           "EpochReadahead"]


class WindowPlan:
    """Pure-numpy plan for one readahead window of consecutive batches.

    ``rows`` is the window's sorted, deduplicated row set — the shape the
    native scatter planner coalesces best (sorted input also makes every
    run *direct*, reading straight into the staging buffer). ``gather``
    maps each requested position (batches concatenated in epoch order)
    to its row's slot in ``rows``; ``bounds[b]:bounds[b+1]`` is batch
    ``b``'s span, so per-batch delivery is ``staged[gather[lo:hi]]`` —
    duplicates (within AND across the window's batches) are fetched once
    and replicated by the gather.
    """

    __slots__ = ("rows", "gather", "bounds", "batches", "owner",
                 "run_starts", "runs_per_peer")

    def __init__(self, rows: np.ndarray, gather: np.ndarray,
                 bounds: np.ndarray, batches: List[np.ndarray],
                 owner: np.ndarray, run_starts: np.ndarray,
                 runs_per_peer: np.ndarray):
        self.rows = rows
        self.gather = gather
        self.bounds = bounds
        self.batches = batches
        self.owner = owner          # owner rank of each unique row
        self.run_starts = run_starts  # first index of each coalesced run
        self.runs_per_peer = runs_per_peer  # runs landing on each rank

    @property
    def n_batches(self) -> int:
        return len(self.batches)

    @property
    def n_requested(self) -> int:
        """Rows requested by the window's batches (duplicates counted)."""
        return int(self.bounds[-1])

    @property
    def dup_rows(self) -> int:
        """Duplicate requests served by the in-RAM gather instead of a
        second fetch (dedup ACROSS the whole window, not per batch)."""
        return self.n_requested - int(self.rows.size)

    @property
    def n_runs(self) -> int:
        """Contiguous stripe-shaped runs the window fetch decomposes
        into (matches the native planner: sorted dedup'd rows coalesce
        identically on both sides of the boundary)."""
        return int(self.run_starts.size)

    def batch_slice(self, b: int) -> np.ndarray:
        """Gather indices (into ``rows``/the staged buffer) for batch
        ``b``, in that batch's exact request order."""
        return self.gather[int(self.bounds[b]):int(self.bounds[b + 1])]


def plan_window(row_starts, batches: Sequence) -> WindowPlan:
    """Plan one window: merge ``batches`` (index arrays, epoch order)
    into the sorted-unique fetch list plus the per-batch gather map, and
    derive the run decomposition against the owner table ``row_starts``
    (:meth:`DDStore.row_starts`)."""
    bl = [np.ascontiguousarray(b, dtype=np.int64).reshape(-1)
          for b in batches]
    if not bl or not sum(b.size for b in bl):
        raise ValueError("plan_window: empty window")
    cat = np.concatenate(bl)
    starts = np.ascontiguousarray(row_starts, dtype=np.int64)
    if cat.min() < 0 or cat.max() >= starts[-1]:
        raise IndexError(f"plan_window: index out of range "
                         f"[0, {int(starts[-1])})")
    rows, gather = np.unique(cat, return_inverse=True)
    bounds = np.concatenate(
        ([0], np.cumsum([b.size for b in bl]))).astype(np.int64)
    owner = (np.searchsorted(starts, rows, side="right") - 1).astype(
        np.int64)
    # A run breaks where rows stop being adjacent or the owner changes —
    # the same decomposition the native scatter planner arrives at, so
    # runs_per_peer here IS the per-window transport fan-out.
    brk = np.r_[True, (np.diff(rows) != 1) | (owner[1:] != owner[:-1])]
    run_starts = np.flatnonzero(brk).astype(np.int64)
    runs_per_peer = np.bincount(owner[run_starts],
                                minlength=len(starts) - 1)
    return WindowPlan(rows, gather.astype(np.int64), bounds, bl, owner,
                      run_starts, runs_per_peer)


def plan_epoch_windows(row_starts, batches: Iterable,
                       window_batches: int) -> List[WindowPlan]:
    """Slice an epoch's batch stream into windows of ``window_batches``
    and plan each (the eager helper — the engine plans lazily)."""
    if window_batches <= 0:
        raise ValueError(f"window_batches must be positive, got "
                         f"{window_batches}")
    it = iter(batches)
    plans = []
    while True:
        chunk = list(itertools.islice(it, window_batches))
        if not chunk:
            return plans
        plans.append(plan_window(row_starts, chunk))


#: Per-process engine id counter: each engine's cache-prefetch window
#: ids live in their own 2^32 range, so two engines (or two epochs)
#: sharing one store can never alias each other's hot-cache entries.
_ENGINE_IDS = itertools.count(1)


class _Window:
    __slots__ = ("plan", "slot", "handles", "bufs", "ragged", "futures",
                 "delivered", "ready", "ready_mu", "t_issue", "span",
                 "wnum", "warmed")

    def __init__(self, plan: WindowPlan, slot: int):
        self.plan = plan
        self.slot = slot
        self.span = 0   # ddtrace span id of this window (0 = untraced)
        self.wnum = 0   # global window number
        self.warmed = False  # hot-cache prefetch issued at plan time
        self.handles: Dict[str, object] = {}   # var -> AsyncBatchRead
        self.bufs: Dict[str, np.ndarray] = {}  # var -> staged view
        self.futures: Dict[str, object] = {}   # var -> Future (ragged)
        self.ragged: Dict[str, Tuple[np.ndarray, np.ndarray, np.ndarray]] \
            = {}                               # var -> (values, lens, offs)
        self.delivered = 0
        self.ready = threading.Event()
        self.ready_mu = threading.Lock()
        self.t_issue = 0.0


class EpochReadahead:
    """Background window-fetch engine over a store variable (plus an
    optional co-variable sharing the same indices, e.g. labels).

    ``batches`` is the epoch's batch stream for THIS rank (index
    arrays, consumed lazily W at a time). The engine keeps up to
    ``depth`` windows staged or in flight: each window's sorted-unique
    row list is issued as ONE native async ``get_batch`` per variable
    into a preallocated ring buffer, and consumers call
    :meth:`get_batch`/:meth:`batch_rows` with the global batch number —
    strictly increasing consumption (the loader's contract) recycles
    ring slots and triggers the next window's issue.

    Ragged variables ride the existing ragged fetch (two batched rounds
    per window on a background thread) and are re-split per batch —
    same bulk-window shape on the wire, same per-batch delivery
    contract as :meth:`DDStore.get_ragged_batch`.

    Teardown (:meth:`close`, also the loader's mid-epoch cancellation
    path) blocks until every in-flight native read has completed and
    releases every ticket — ``store.async_pending()`` is 0 afterwards.
    """

    def __init__(self, store, data_var: str, batches: Iterable,
                 label_var: Optional[str] = None, window_batches: int = 8,
                 depth: int = 2, metrics=None,
                 max_window_rows: Optional[int] = None,
                 ring: Optional[Dict[str, List[np.ndarray]]] = None,
                 sched=None):
        if window_batches <= 0:
            raise ValueError("window_batches must be positive")
        if depth <= 0:
            raise ValueError("depth must be positive")
        self.store = store
        self.window_batches = int(window_batches)
        self.depth = int(depth)
        self.metrics = metrics
        # Cost-model scheduler (sched/planner.Scheduler): each window's
        # fetch leg feeds its host-side measurement substrate. The
        # epoch's first window is marked `cold` — it pays ring
        # first-touch and lane dials, the host-side analogue of the
        # native tuners' dial-tainted windows.
        self.sched = sched
        self._windows_fed = 0
        self._batch_iter: Iterator = iter(batches)
        self._vars = [data_var] + ([label_var] if label_var else [])
        self._ragged = {v: store.is_ragged(v) for v in self._vars}
        anchor = f"{data_var}/index" if self._ragged[data_var] else data_var
        self._row_starts = store.row_starts(anchor)
        self._row_bytes = {
            v: store.row_nbytes(f"{v}/index" if self._ragged[v] else v)
            for v in self._vars}
        # Fixed-width variables sharing the anchor's owner table ride
        # the O(runs) native path (read_runs_async): the planner's run
        # lists execute verbatim, no native re-plan over 10^5+ rows. A
        # co-variable with a different row partition (not the
        # ShardedDataset case) falls back to get_batch_async.
        self._use_runs = {
            v: (not self._ragged[v]
                and np.array_equal(store.row_starts(v),
                                   self._row_starts))
            for v in self._vars}

        # Preallocated staging ring: depth buffers per fixed-width var,
        # each sized for the worst case (no duplicates in the window).
        # Memory cost = depth × Σ_var max_window_rows × row_bytes — the
        # knob README documents. Ragged windows allocate per fetch (the
        # element total is data-dependent).
        self._max_rows = int(max_window_rows) if max_window_rows else None
        self._ring: Dict[str, List[np.ndarray]] = {}
        # `ring`: staging buffers handed over from a previous engine
        # (the loader reuses them epoch to epoch). Worth real time on
        # first-touch-expensive kernels: a fresh 2x64 MB ring faults in
        # page by page DURING the first windows' fetch writes otherwise.
        self._provided_ring = ring
        self._exec = None
        if any(self._ragged.values()):
            from concurrent.futures import ThreadPoolExecutor
            self._exec = ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="ddstore-readahead")

        # Tiered-storage warming: when the store's hot-row cache is
        # armed (DDSTORE_TIER_CACHE_BYTES > 0), the issuer plans up to
        # `_prefetch` windows AHEAD of issue and hands each plan's row
        # list to store.cache_prefetch — a free lookahead (the plan
        # exists before the window is issued), so by the time window w
        # is issued its cold rows are already staged in RAM and the
        # window read is an in-RAM gather. Eviction is keyed on window
        # consumption (_mark_delivered). Window ids are scoped per
        # engine so epochs/engines never alias entries.
        self._warm = False
        self._prefetch = 0
        self._wid_base = next(_ENGINE_IDS) << 32
        self._warmed: set = set()
        if hasattr(store, "tiering_stats") and \
                hasattr(store, "cache_prefetch"):
            try:
                self._warm = int(store.tiering_stats().get(
                    "cache_max_bytes", 0)) > 0
            except Exception:  # noqa: BLE001 — advisory capability probe
                self._warm = False
        if self._warm:
            self._prefetch = self._default_prefetch()
            self._warm = self._prefetch > 0

        self._planned: "deque" = deque()  # (wnum, plan) awaiting issue
        self._plan_next = 0               # next window number to plan
        self._iter_done = False           # batch iterator exhausted

        self._mu = threading.Lock()
        self._cond = threading.Condition(self._mu)
        # Serializes degraded-window refetches: each one sets the
        # store's shared retry-deadline override, and two windows
        # failing concurrently (depth >= 2 under chaos, out-of-order
        # loader workers) would otherwise clobber each other's budget
        # mid-refetch — one window's floor aborting the other's healthy
        # retry, or one's clear handing the other a fresh full
        # deadline. Refetches contend for the same faulty peers anyway;
        # running them one at a time costs nothing correct.
        self._refetch_mu = threading.Lock()
        self._win: Dict[int, _Window] = {}
        self._next_issue = 0
        # Ring-slot recycling keys on IN-ORDER consumption: concurrent
        # loader workers can finish window w+1's gathers before window
        # w's last one, so a plain consumed-count would let window
        # w+depth overwrite w's still-live slot. `_floor` is the lowest
        # not-fully-consumed window; window w may issue only when
        # w < floor + depth (its slot's previous owner, w - depth, is
        # then provably consumed).
        self._floor = 0
        self._done_wins: set = set()
        self._exhausted = False
        self._closed = False
        self._error: Optional[BaseException] = None
        # Window planning (sort/unique over W batches of indices) is
        # real work — on a dedicated issuer thread it overlaps batch
        # consumption like the fetches themselves do, instead of
        # stalling the consumer that happened to deliver a window's
        # last batch.
        self._issuer = threading.Thread(target=self._issue_loop,
                                        name="ddstore-readahead-plan",
                                        daemon=True)
        self._issuer.start()

    # -- issue ------------------------------------------------------------

    def _alloc_ring(self, first_plan: WindowPlan) -> None:
        # Sized on first issue: the worst-case window is W × the first
        # window's batch size (all batches full, zero duplicates). An
        # explicit max_window_rows overrides (e.g. a caller with known
        # short batches).
        per_batch = max(int(b.size) for b in first_plan.batches)
        cap = self._max_rows or per_batch * self.window_batches
        prov = self._provided_ring or {}
        for v in self._vars:
            if self._ragged[v]:
                continue
            m = self.store._require(v)
            bufs = prov.get(v)
            if (bufs and len(bufs) >= self.depth
                    and all(b.dtype == m.dtype
                            and tuple(b.shape[1:]) == m.sample_shape
                            and b.shape[0] >= cap for b in bufs)):
                self._ring[v] = list(bufs[: self.depth])
                continue
            self._ring[v] = [
                np.empty((cap,) + m.sample_shape, m.dtype)
                for _ in range(self.depth)]
            for b in self._ring[v]:
                # Eager first-touch on the issuer thread: one memset
                # pass now instead of a page fault per 4 KiB inside the
                # timed fetch writes (gVisor faults are expensive).
                b.fill(0)
        self._max_rows = cap

    def _default_prefetch(self) -> int:
        """Requested warm-ahead depth: the DDSTORE_TIER_PREFETCH_DEPTH
        pin, else 2 (the scheduler refines it against the cache budget
        and its per-tier cells once the first plan's geometry is
        known)."""
        v = os.environ.get("DDSTORE_TIER_PREFETCH_DEPTH", "").strip()
        if v:
            try:
                return max(0, int(v))
            except ValueError:
                pass
        return 2

    def _refine_prefetch(self, plan: WindowPlan) -> None:
        """First plan: let the cost-model scheduler size the warm-ahead
        depth from the real window geometry vs the cache budget (and
        its measured hot-hit / cold-miss cells). A user pin wins inside
        planned_prefetch; sched-less engines keep the default."""
        if self.sched is None or \
                not hasattr(self.sched, "planned_prefetch"):
            return
        wbytes = sum(int(plan.rows.size) * rb
                     for rb in self._row_bytes.values())
        try:
            cache = int(self.store.tiering_stats().get(
                "cache_max_bytes", 0))
            self._prefetch = max(0, int(self.sched.planned_prefetch(
                self._prefetch, wbytes, cache, self.depth)))
        except Exception:  # noqa: BLE001 — advisory sizing only
            pass
        if self._prefetch == 0:
            self._warm = False

    def _warm_window(self, w: int, plan: WindowPlan) -> bool:
        """Hand window ``w``'s row list to the store's hot cache
        (advisory: any failure just leaves the window cold)."""
        warmed = False
        for v in self._vars:
            if self._ragged[v]:
                continue
            try:
                self.store.cache_prefetch(v, plan.rows,
                                          window=self._wid_base + w)
                warmed = True
            except Exception:  # noqa: BLE001 — reads stay correct cold
                return warmed
        if warmed:
            self._warmed.add(w)
        return warmed

    def _issue_loop(self) -> None:
        """Issuer thread: PLAN up to ``1 + prefetch`` windows ahead
        (warming the hot cache with each plan's row list the moment it
        exists) and ISSUE the head window as soon as its ring slot's
        previous owner (window ``w - depth``) is consumed. Planning and
        warming happen OUTSIDE the engine lock — consumers gathering
        from staged windows never wait on a sort."""
        while True:
            with self._mu:
                while True:
                    if self._closed or self._error is not None:
                        return
                    cap = 1 + (self._prefetch if self._warm else 0)
                    can_plan = (not self._iter_done
                                and len(self._planned) < cap)
                    can_issue = (bool(self._planned) and self._next_issue
                                 < self._floor + self.depth)
                    if can_plan or can_issue:
                        break
                    if self._iter_done and not self._planned:
                        self._exhausted = True
                        self._cond.notify_all()
                        return
                    self._cond.wait()
            try:
                # Issue first (the fetch should be in flight before the
                # next plan's sort runs), then top up the plan buffer.
                if can_issue:
                    if not self._issue_one():
                        return
                elif not self._plan_one():
                    continue  # iterator exhausted: loop decides the end
            except BaseException as e:  # noqa: BLE001
                with self._mu:
                    self._error = e
                    self._cond.notify_all()
                return

    def _plan_one(self) -> bool:
        """Plan (and cache-warm) the next window; False when the batch
        iterator is exhausted."""
        chunk = list(itertools.islice(self._batch_iter,
                                      self.window_batches))
        if not chunk:
            with self._mu:
                self._iter_done = True
                self._cond.notify_all()
            return False
        plan = plan_window(self._row_starts, chunk)
        if not self._ring and not all(self._ragged.values()):
            self._alloc_ring(plan)
        w = self._plan_next
        self._plan_next = w + 1
        if self._warm:
            if w == 0:
                self._refine_prefetch(plan)
            if self._warm:
                self._warm_window(w, plan)
        with self._mu:
            self._planned.append((w, plan))
            self._cond.notify_all()
        return True

    def _issue_one(self) -> bool:
        """Issue the head planned window into its ring slot; False when
        the engine closed mid-issue (tickets already released) or the
        issue failed (error latched)."""
        with self._mu:
            w, plan = self._planned.popleft()
        win = None
        try:
            win = _Window(plan, w % self.depth)
            n = int(plan.rows.size)
            if self._max_rows is not None and n > self._max_rows:
                raise ValueError(
                    f"readahead window {w} needs {n} staging rows "
                    f"but the ring was sized for {self._max_rows} "
                    f"(batches grew mid-epoch?)")
            win.wnum = w
            win.warmed = w in self._warmed
            if trace_enabled():
                # ddtrace: one span per window — issue/ready/stall
                # events group under it in the merged trace, next
                # to the native async-read spans its fetches mint.
                rank = int(getattr(self.store, "rank", -1))
                win.span = trace_new_span(rank)
                trace_emit("window_issue", win.span, rank, w, n,
                           sum(n * rb
                               for rb in self._row_bytes.values()))
            win.t_issue = time.monotonic()
            for v in self._vars:
                if self._ragged[v]:
                    win.futures[v] = self._exec.submit(
                        self._fetch_ragged, v, plan.rows)
                else:
                    buf = self._ring[v][win.slot][:n]
                    if self._use_runs[v]:
                        tgt, soff, doff, nb = self._runs_for(v, plan)
                        win.handles[v] = self.store.read_runs_async(
                            v, buf, tgt, soff, doff, nb)
                    else:
                        win.handles[v] = self.store.get_batch_async(
                            v, plan.rows, out=buf)
                    win.bufs[v] = buf
        except BaseException as e:  # noqa: BLE001
            # A partially-issued window (e.g. the label variable's
            # issue raised after the data read went in flight) must
            # not leak its tickets: the window was never registered
            # in _win, so close() cannot release them — and a leaked
            # in-flight read would keep writing into a ring buffer a
            # caller may hand to the next epoch's engine.
            if win is not None:
                for h in win.handles.values():
                    h.release()
                for f in win.futures.values():
                    try:
                        f.result()
                    except BaseException:  # noqa: BLE001
                        pass
            with self._mu:
                self._error = e
                self._cond.notify_all()
            return False
        with self._mu:
            if self._closed:
                # close() ran mid-issue: this window is not in
                # _win, so release its reads here.
                handles = list(win.handles.values())
            else:
                self._win[w] = win
                self._next_issue = w + 1
                handles = None
            self._cond.notify_all()
        if handles is not None:
            for h in handles:
                h.release()
            return False
        return True

    def _runs_for(self, var: str, plan: WindowPlan):
        """The window's coalesced runs as native byte spans: targets,
        source offsets (within each owner's shard), destination offsets
        (dense pack in sorted-row order — gather indices match), and
        lengths."""
        rb = self._row_bytes[var]
        rs = plan.run_starts
        lens = np.diff(np.r_[rs, plan.rows.size])
        tgt = plan.owner[rs]
        src_off = (plan.rows[rs] - self._row_starts[tgt]) * rb
        return tgt, src_off, rs * rb, lens * rb

    def _fetch_ragged(self, var: str, rows: np.ndarray):
        """Ragged window fetch on the background thread; the completion
        timestamp feeds the producer-idle accounting."""
        out = self.store.get_ragged_batch(var, rows)
        return out, time.monotonic()

    # -- readiness / accounting -------------------------------------------

    def _ensure_ready(self, win: _Window) -> None:
        if win.ready.is_set():
            return
        with win.ready_mu:
            if win.ready.is_set():
                return
            t0 = time.monotonic()
            try:
                done_ts = self._wait_window(win)
            except DDStoreError as e:
                if e.code not in (ERR_TRANSPORT, ERR_PEER_LOST):
                    # Data error (out of range, missing var): retrying
                    # cannot fix it. Latch so every consumer fails fast.
                    with self._mu:
                        self._error = e
                        self._cond.notify_all()
                    raise
                # Liveness sweep first: with shard replication in force
                # the window read normally fails over INSIDE the native
                # layer and never reaches this branch, but a loss that
                # did surface here should latch the suspect view before
                # the refetch — its get_batch chunks then short-circuit
                # the dead owner straight onto replicas (only the lost
                # rows reroute; live owners' chunks read normally), so
                # the window completes without another ladder burn.
                check = getattr(self.store, "check_health", None)
                if check is not None:
                    try:
                        check()
                    except Exception:  # noqa: BLE001
                        pass  # liveness polling must not mask the retry
                # Degraded mode: the bulk window fetch failed after the
                # native layer's own retries — retry ONCE at per-batch
                # granularity before surfacing. The refetch shares the
                # WINDOW's OP_DEADLINE budget rather than getting a
                # fresh one: against a permanently dead owner the window
                # give-up already burned ~1x the deadline, and a fresh
                # per-chunk budget would double the time to the
                # classified kErrPeerLost raise. Whatever the window
                # left over (floored so a transient blip still gets a
                # real retry) is the refetch's whole allowance. The
                # override is per-STORE (other ranks'/stores' budgets
                # in this process are untouched) and cleared on every
                # exit path; stores without the knob (test proxies)
                # just run the refetch on the full budget.
                deadline = DEFAULT_OP_DEADLINE_S
                try:
                    deadline = float(
                        os.environ.get("DDSTORE_OP_DEADLINE_S", "")
                        or DEFAULT_OP_DEADLINE_S)
                except ValueError:
                    pass
                set_deadline = getattr(self.store, "set_retry_deadline",
                                       None)
                try:
                    with self._refetch_mu:
                        # Remaining budget computed INSIDE the lock:
                        # waiting behind another window's refetch is
                        # part of this window's elapsed time.
                        elapsed = time.monotonic() - win.t_issue
                        remaining = max(min(2.0, 0.25 * deadline),
                                        deadline - elapsed)
                        try:
                            if set_deadline is not None:
                                set_deadline(remaining)
                            done_ts = self._refetch_window(win)
                        finally:
                            if set_deadline is not None:
                                set_deadline(0.0)
                except DDStoreError as e2:
                    # Window give-up: the bulk fetch AND its per-batch
                    # refetch both failed — snapshot every thread's
                    # last events before surfacing (the native layer
                    # already snapshotted on a surfaced kErrPeerLost;
                    # this covers the plain-transport give-up too).
                    if trace_enabled():
                        trace_flight("window_giveup",
                                     int(getattr(self.store, "rank",
                                                 -1)))
                    with self._mu:
                        self._error = e2
                        self._cond.notify_all()
                    raise
            t1 = time.monotonic()
            self._account(win, stall_s=t1 - t0,
                          idle_s=max(0.0, t0 - done_ts),
                          fetch_s=max(0.0, done_ts - win.t_issue))
            win.ready.set()

    def _wait_window(self, win: _Window) -> float:
        """Wait out every variable's window fetch; returns the latest
        completion timestamp. On ANY failure every still-pending native
        ticket is released before the error propagates (``async_pending``
        contributed by this window is 0 afterwards — no worker is left
        writing into a ring buffer the retry path is about to refill)."""
        done_ts = win.t_issue
        try:
            for v in self._vars:
                if self._ragged[v]:
                    (values, lens), ts = win.futures[v].result()
                    offs = np.concatenate(
                        ([0], np.cumsum(lens))).astype(np.int64)
                    win.ragged[v] = (values, lens, offs)
                    done_ts = max(done_ts, ts)
                else:
                    h = win.handles[v]
                    h.wait()  # fills the ring buffer, releases the ticket
                    if h.done_mono_s:
                        done_ts = max(done_ts, h.done_mono_s)
            return done_ts
        except BaseException:
            for h in win.handles.values():
                h.release()  # idempotent; blocks until the worker is out
            # Ragged futures are the same hazard in executor form: an
            # orphaned in-flight window fetch would keep hammering the
            # (possibly faulty) peers concurrently with the retry's
            # fresh fetch. Await them too; their own errors are
            # subsumed by the one propagating.
            for f in win.futures.values():
                try:
                    f.result()
                except BaseException:  # noqa: BLE001
                    pass
            raise

    def _refetch_window(self, win: _Window) -> float:
        """Per-batch-granularity retry of a transiently failed window:
        re-fetch every variable's sorted row list in ``n_batches``
        synchronous chunks straight into the staging buffers. A chunk
        failure propagates (already classified/augmented by the store
        layer — kErrPeerLost names the dead owner and the lost rows)."""
        m = self.metrics
        if m is not None and hasattr(m, "add_fault_event"):
            m.add_fault_event(windows_retried=1)
        rows = win.plan.rows
        nchunks = max(1, win.plan.n_batches)
        refetches = 0
        for v in self._vars:
            if self._ragged[v]:
                (values, lens), _ = self._fetch_ragged(v, rows)
                offs = np.concatenate(
                    ([0], np.cumsum(lens))).astype(np.int64)
                win.ragged[v] = (values, lens, offs)
                refetches += 1
                continue
            buf = win.bufs[v]
            for span in np.array_split(np.arange(rows.size), nchunks):
                if span.size == 0:
                    continue
                lo, hi = int(span[0]), int(span[-1]) + 1
                self.store.get_batch(v, rows[lo:hi], out=buf[lo:hi])
                refetches += 1
        if m is not None and hasattr(m, "add_fault_event"):
            m.add_fault_event(window_batch_refetches=refetches)
        return time.monotonic()

    def _account(self, win: _Window, stall_s: float, idle_s: float,
                 fetch_s: float) -> None:
        wbytes = sum(int(win.plan.rows.size) * rb
                     for rb in self._row_bytes.values())
        if win.span:
            rank = int(getattr(self.store, "rank", -1))
            trace_emit("window_ready", win.span, rank, win.wnum,
                       wbytes, int(fetch_s * 1e6))
            if stall_s > 1e-4:
                trace_emit("window_stall", win.span, rank, win.wnum, 0,
                           int(stall_s * 1e6))
        if self.sched is not None and fetch_s > 0.0:
            self.sched.observe_window(wbytes, fetch_s,
                                      cold=self._windows_fed == 0)
            if self._warm and hasattr(self.sched, "observe_tier"):
                # Per-tier read cells: a warmed window's fetch leg is
                # the hot-hit regime (in-RAM gather), an unwarmed one
                # the cold-miss regime — the cost model plans the
                # prefetch depth from exactly these two cells.
                self.sched.observe_tier(wbytes, fetch_s,
                                        warmed=win.warmed,
                                        cold=self._windows_fed == 0)
            self._windows_fed += 1
        m = self.metrics
        if m is None or not hasattr(m, "add_window"):
            return
        plan = win.plan
        rank = self.store.rank
        remote = plan.owner[plan.run_starts] != rank
        remote_rows = int((plan.owner != rank).sum())
        nbytes = sum(int(plan.rows.size) * rb
                     for rb in self._row_bytes.values())
        m.add_window(
            rows_requested=plan.n_requested,
            rows_unique=int(plan.rows.size),
            dup_rows=plan.dup_rows,
            runs=plan.n_runs,
            remote_runs=int(remote.sum()),
            peer_lists=int((plan.runs_per_peer
                            [np.arange(len(plan.runs_per_peer)) != rank]
                            > 0).sum()),
            window_bytes=nbytes,
            wait_s=stall_s, idle_s=idle_s, fetch_s=fetch_s)
        if hasattr(m, "add_bytes"):
            # Transport-side ledger, once per window: remote-owned
            # unique rows cross DCN (per-batch fetch would have moved
            # them again for every duplicate).
            dcn = sum(remote_rows * rb for rb in self._row_bytes.values())
            m.add_bytes(bytes_over_dcn=dcn)

    # -- consume ----------------------------------------------------------

    def _window_for(self, seq: int) -> Tuple[_Window, int]:
        w, b = divmod(int(seq), self.window_batches)
        with self._mu:
            while (w >= self._next_issue and not self._exhausted
                   and not self._closed and self._error is None):
                # Our window's ring slot is still owned by an earlier
                # window — wait for consumption to free it.
                self._cond.wait()
            if self._error is not None:
                raise self._error
            if self._closed:
                raise RuntimeError("readahead engine closed")
            win = self._win.get(w)
            if win is None:
                raise IndexError(f"batch {seq}: window {w} not available "
                                 f"(epoch exhausted or already consumed)")
        self._ensure_ready(win)
        return win, b

    def _verify(self, win: _Window, b: int, idx) -> None:
        # The engine replays the sampler independently of the loader; a
        # sampler that is not replay-deterministic would silently deliver
        # the wrong rows — make that loud instead.
        if idx is not None and not np.array_equal(
                np.asarray(idx, dtype=np.int64).reshape(-1),
                win.plan.batches[b]):
            raise RuntimeError(
                "readahead: sampler replay diverged from the loader's "
                "batch stream (the sampler must be replayable: two "
                "iterations yielding identical indices)")

    def _mark_delivered(self, seq: int) -> None:
        w = int(seq) // self.window_batches
        evict = None
        with self._mu:
            win = self._win.get(w)
            if win is None:
                return
            win.delivered += 1
            if win.delivered >= win.plan.n_batches:
                del self._win[w]
                self._done_wins.add(w)
                while self._floor in self._done_wins:
                    self._done_wins.discard(self._floor)
                    self._floor += 1
                # Eviction keyed on window CONSUMPTION: the warmed
                # entries served their window's fetch; the budget goes
                # back to the windows streaming in behind it.
                if w in self._warmed:
                    self._warmed.discard(w)
                    evict = self._wid_base + w
                self._cond.notify_all()  # wake the issuer (slot freed)
        if evict is not None:
            try:
                self.store.cache_evict(evict)
            except Exception:  # noqa: BLE001 — eviction is advisory
                pass

    def get_batch(self, seq: int, idx=None):
        """Deliver batch ``seq`` (global batch number) from its staged
        window: data rows, or ``(data, labels)`` with a co-variable —
        the same contract as ``ShardedDataset.fetch``. For a ragged
        data variable, returns ``(values, lengths)`` like
        ``get_ragged_batch``. ``idx``, when given, is checked against
        the engine's replay of the sampler."""
        win, b = self._window_for(seq)
        self._verify(win, b, idx)
        out = tuple(self._gather(win, v, b) for v in self._vars)
        self._mark_delivered(seq)
        return out[0] if len(out) == 1 else out

    def batch_rows(self, seq: int, idx=None) -> List[np.ndarray]:
        """Deliver batch ``seq`` as raw row arrays, one per variable, in
        batch order — the device-collective path's staging source (rows
        land in the padded send buffer instead of a host batch)."""
        win, b = self._window_for(seq)
        self._verify(win, b, idx)
        out = [self._gather(win, v, b) for v in self._vars]
        self._mark_delivered(seq)
        return out

    def _gather(self, win: _Window, var: str, b: int):
        sel = win.plan.batch_slice(b)
        if not self._ragged[var]:
            # take() over fancy indexing: same semantics, measurably
            # faster row gather on this hot path.
            return win.bufs[var].take(sel, axis=0)
        values, lens, offs = win.ragged[var]
        out_lens = lens[sel]
        total = int(out_lens.sum())
        if total == 0:
            return (np.empty((0,) + values.shape[1:], values.dtype),
                    out_lens.astype(np.int64))
        prefix = np.concatenate(([0], np.cumsum(out_lens)[:-1]))
        pos = (np.repeat(offs[sel] - prefix, out_lens)
               + np.arange(total, dtype=np.int64))
        return values.take(pos, axis=0), out_lens.astype(np.int64)

    @property
    def ring(self) -> Dict[str, List[np.ndarray]]:
        """The staging buffers, for handoff to the next epoch's engine
        (``EpochReadahead(..., ring=prev.ring)``) — skips reallocation
        AND refaulting of the (potentially large) windows. Only read
        this after :meth:`close`."""
        return dict(self._ring)

    # -- teardown ----------------------------------------------------------

    def close(self) -> None:
        """Cancel the epoch: block until every in-flight native read has
        finished, release every ticket, wake blocked consumers. After
        close, ``store.async_pending()`` contributed by this engine is
        0. Idempotent."""
        with self._mu:
            if self._closed:
                return
            self._closed = True
            wins = list(self._win.values())
            self._win.clear()
            self._cond.notify_all()
        # The issuer may be mid-plan/issue: it observes _closed at
        # registration time and releases its own window's reads.
        self._issuer.join()
        for win in wins:
            for h in win.handles.values():
                h.release()
        if self._exec is not None:
            self._exec.shutdown(wait=True)
        # Drop every hot-cache entry this engine warmed (consumed
        # windows already evicted themselves; this sweeps the planned-
        # ahead tail of a cancelled epoch, returning its quota bytes).
        for w in sorted(self._warmed):
            try:
                self.store.cache_evict(self._wid_base + w)
            except Exception:  # noqa: BLE001 — advisory teardown sweep
                pass
        self._warmed.clear()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
