"""Unified cost-model scheduler (ISSUE 6): the shared warm-window
measurement substrate, the joint route x lanes x depth x width planner,
the pin semantics that keep every PR 1-5 contract intact, and the
knob-registry drift guard.

The EWMA-parity class is the refactor's safety net: the router's and
lane tuner's sample hygiene was extracted into ONE implementation
(native/measure.h, mirrored by sched/measure.py); the parity test
replays randomized fold traces through a verbatim port of the OLD
router logic and through the substrate-backed model and requires
bit-equal estimates and identical routing flips.
"""

import os
import re
import threading
import uuid

import numpy as np
import pytest

from ddstore_tpu import DDStore, DDStoreError, SingleGroup, ThreadGroup
from ddstore_tpu.data import DeviceLoader, DistributedSampler, ShardedDataset
from ddstore_tpu.sched import (WARM_EWMA_ALPHA, WARM_MAX_COLD_SKIPS,
                               WARM_MIN_SAMPLES, ColdSkipBudget, CostModel,
                               Fold, ProbeDiscard, SampleSet, Scheduler,
                               WarmStat, fold_warm_sample, pinned_knobs)
from ddstore_tpu.sched.knobs import REGISTRY
from ddstore_tpu.sched.planner import scheduler_enabled

pytestmark = pytest.mark.tier1_required

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Substrate hygiene units (the shared contract, rule by rule).
# ---------------------------------------------------------------------------

class TestWarmWindowHygiene:
    def test_first_window_discarded(self):
        s = WarmStat()
        assert fold_warm_sample(s, 100.0) is Fold.DROP_WARMUP
        assert s.ewma == 0.0 and s.n == 0 and s.warmed
        assert fold_warm_sample(s, 100.0) is Fold.FOLDED
        assert s.ewma == 100.0 and s.n == 1

    def test_dial_taint_discard_is_bounded(self):
        s = WarmStat()
        b = ColdSkipBudget()
        for i in range(WARM_MAX_COLD_SKIPS):
            assert fold_warm_sample(s, 1.0, cold=True, budget=b) is \
                Fold.DROP_COLD, i
        # Budget exhausted: the tainted number beats having none — the
        # next cold window is treated like a clean one (and becomes the
        # warm-up discard).
        assert fold_warm_sample(s, 1.0, cold=True, budget=b) is \
            Fold.DROP_WARMUP
        assert fold_warm_sample(s, 1.0, cold=True, budget=b) is Fold.FOLDED

    def test_dial_taint_only_while_unseeded(self):
        s = WarmStat()
        b = ColdSkipBudget()
        fold_warm_sample(s, 10.0)           # warm-up
        fold_warm_sample(s, 10.0)           # seeds the EWMA
        # A cold window AFTER the cell is seeded folds normally (the
        # native rule: `cold && n == 0`).
        assert fold_warm_sample(s, 20.0, cold=True, budget=b) is Fold.FOLDED
        assert b.skips == 0
        assert s.ewma == pytest.approx(15.0)

    def test_budget_is_per_tuner_not_per_cell(self):
        ss = SampleSet()
        # Spend the whole budget on knob 1...
        for _ in range(WARM_MAX_COLD_SKIPS):
            assert ss.fold("lanes", 0, 1, 100, 1.0, cold=True) is \
                Fold.DROP_COLD
        # ...then knob 2 of the SAME tuner gets no fresh budget.
        assert ss.fold("lanes", 0, 2, 100, 1.0, cold=True) is \
            Fold.DROP_WARMUP
        # A different tuner (other class) has its own budget.
        assert ss.fold("lanes", 1, 1, 100, 1.0, cold=True) is Fold.DROP_COLD

    def test_probe_pair_discard_consumed_once(self):
        s = WarmStat()
        s.warmed = True
        d = ProbeDiscard(armed=True)
        assert fold_warm_sample(s, 5.0, discard=d) is Fold.DROP_PROBE
        assert not d.armed
        assert fold_warm_sample(s, 5.0, discard=d) is Fold.FOLDED

    def test_ewma_alpha(self):
        s = WarmStat()
        s.warmed = True
        fold_warm_sample(s, 8.0)
        fold_warm_sample(s, 4.0)
        assert s.ewma == pytest.approx(
            WARM_EWMA_ALPHA * 8.0 + (1 - WARM_EWMA_ALPHA) * 4.0)
        assert s.n == WARM_MIN_SAMPLES


# ---------------------------------------------------------------------------
# EWMA parity with the router's pre-refactor behavior.
# ---------------------------------------------------------------------------

class _OldRoute:
    """Verbatim port of the OLD tcp_transport.cc RecordRouteSample
    (pre-substrate): the ground truth the shared implementation must
    reproduce exactly."""

    def __init__(self, hysteresis=1.25):
        self.cma_bw = self.tcp_bw = 0.0
        self.cma_n = self.tcp_n = 0
        self.cold_skips = 0
        self.discard_probe = False
        self.cma_warmed = self.tcp_warmed = False
        self.via_tcp = False
        self.calibrated = False
        self.crossovers = 0
        self.h = hysteresis

    def record(self, via_tcp, bw, cold):
        if bw <= 0:
            return
        if cold and (self.tcp_n if via_tcp else self.cma_n) == 0 \
                and self.cold_skips < 4:
            self.cold_skips += 1
            return
        if via_tcp:
            if not self.tcp_warmed:
                self.tcp_warmed = True
                return
        else:
            if not self.cma_warmed:
                self.cma_warmed = True
                return
        if self.discard_probe and via_tcp != self.via_tcp:
            self.discard_probe = False
            return
        if via_tcp:
            self.tcp_n += 1
            self.tcp_bw = bw if self.tcp_bw == 0.0 \
                else 0.5 * self.tcp_bw + 0.5 * bw
        else:
            self.cma_n += 1
            self.cma_bw = bw if self.cma_bw == 0.0 \
                else 0.5 * self.cma_bw + 0.5 * bw
        if self.cma_bw == 0.0 or self.tcp_bw == 0.0:
            return
        if not self.calibrated and self.cma_n >= 2 and self.tcp_n >= 2:
            self.calibrated = True
            to_tcp = not self.via_tcp and self.tcp_bw > self.cma_bw
            to_cma = self.via_tcp and self.cma_bw > self.tcp_bw
        else:
            to_tcp = not self.via_tcp and self.tcp_bw > self.h * self.cma_bw
            to_cma = self.via_tcp and self.cma_bw > self.h * self.tcp_bw
        if to_tcp or to_cma:
            self.via_tcp = to_tcp
            self.crossovers += 1


class _NewRoute:
    """The refactored router: identical DECISION logic, hygiene
    delegated to the shared substrate — mirrors the new
    RecordRouteSample in tcp_transport.cc line for line."""

    def __init__(self, hysteresis=1.25):
        self.cma = WarmStat()
        self.tcp = WarmStat()
        self.budget = ColdSkipBudget()
        self.probe = ProbeDiscard()
        self.via_tcp = False
        self.calibrated = False
        self.crossovers = 0
        self.h = hysteresis

    def record(self, via_tcp, bw, cold):
        if bw <= 0:
            return
        cell = self.tcp if via_tcp else self.cma
        discard = self.probe if via_tcp != self.via_tcp else None
        if fold_warm_sample(cell, bw, cold=cold, budget=self.budget,
                            discard=discard) is not Fold.FOLDED:
            return
        if self.cma.ewma == 0.0 or self.tcp.ewma == 0.0:
            return
        if not self.calibrated and self.cma.n >= WARM_MIN_SAMPLES \
                and self.tcp.n >= WARM_MIN_SAMPLES:
            self.calibrated = True
            to_tcp = not self.via_tcp and self.tcp.ewma > self.cma.ewma
            to_cma = self.via_tcp and self.cma.ewma > self.tcp.ewma
        else:
            to_tcp = not self.via_tcp and \
                self.tcp.ewma > self.h * self.cma.ewma
            to_cma = self.via_tcp and \
                self.cma.ewma > self.h * self.tcp.ewma
        if to_tcp or to_cma:
            self.via_tcp = to_tcp
            self.crossovers += 1


class TestEwmaParity:
    @pytest.mark.parametrize("seed", [0, 7, 42, 1234])
    def test_randomized_traces_bit_equal(self, seed):
        rng = np.random.default_rng(seed)
        old = _OldRoute(hysteresis=1.10)
        new = _NewRoute(hysteresis=1.10)
        for step in range(600):
            via_tcp = bool(rng.integers(2))
            bw = float(rng.uniform(0.5, 20.0)) * 1e9
            cold = bool(rng.random() < 0.15)
            if rng.random() < 0.1:
                # Arm the probe-pair discard in both models, exactly as
                # RouteViaTcp's phase-30 decision does.
                old.discard_probe = True
                new.probe.armed = True
            old.record(via_tcp, bw, cold)
            new.record(via_tcp, bw, cold)
            assert old.cma_bw == new.cma.ewma, step
            assert old.tcp_bw == new.tcp.ewma, step
            assert old.cma_n == new.cma.n and old.tcp_n == new.tcp.n
            assert old.cold_skips == new.budget.skips
            assert old.via_tcp == new.via_tcp
            assert old.crossovers == new.crossovers
            assert old.calibrated == new.calibrated

    def test_single_native_hygiene_implementation_remains(self):
        """Acceptance grep: the duplicated discard/taint/EWMA blocks are
        gone from tcp_transport.cc — both tuners call the substrate's
        FoldWarmSample, and the only EWMA-fold expression in native/
        lives in measure.h."""
        native = os.path.join(REPO, "ddstore_tpu", "native")
        fold_impls = []
        for fn in os.listdir(native):
            if not (fn.endswith(".cc") or fn.endswith(".h")):
                continue
            with open(os.path.join(native, fn)) as f:
                text = f.read()
            # The EWMA fold idiom (0.5 * est + 0.5 * sample, any
            # spelling with the alpha constant or literal).
            if re.search(r"ewma\s*=[^;]*Alpha", text) or \
                    re.search(r"=\s*0\.5\s*\*[^;]*\+\s*0\.5\s*\*", text):
                fold_impls.append(fn)
        assert fold_impls == ["measure.h"], (
            f"warm-window EWMA fold must live ONLY in measure.h; found "
            f"in {fold_impls}")
        with open(os.path.join(native, "tcp_transport.cc")) as f:
            tcp = f.read()
        assert tcp.count("FoldWarmSample") >= 3, (
            "router + lane tuner (incl. the pinned-width path) must "
            "consume the shared substrate")
        # The old per-tuner warm-up/taint state is gone.
        for gone in ("cma_warmed", "tcp_warmed", "t.warmed", "t.bw[",
                     "t.n["):
            assert gone not in tcp, gone


# ---------------------------------------------------------------------------
# Planner units (canned samples; no store).
# ---------------------------------------------------------------------------

def _lane_cells(meas):
    """{lanes: (ewma, n)} -> the planner's cell-row dict shape."""
    return {l: {"ewma_bps": bw, "n": n} for l, (bw, n) in meas.items()}


class TestCostModel:
    def test_measured_scatter_collapse_avoided(self):
        """The PR 5 scatter result from canned samples: 4 lanes measured
        at 0.33x of 1 lane — the model must choose 1 lane, no special
        case."""
        m = CostModel(cores=2, peers=3)
        cells = _lane_cells({1: (6.4e9, 3), 2: (4.0e9, 2),
                             4: (2.1e9, 3)})
        assert m.best_lanes(cells) == 1

    def test_core_budget_caps_extrapolation(self):
        """Only 1 lane measured, 2 cores, 3 peers: the 1-lane fan-out
        already oversubscribes the box, so widening is predicted to
        gain exactly nothing and the plan stays at 1 lane — the
        no-headroom regime FALLS OUT of the model."""
        m = CostModel(cores=2, peers=3)
        cells = _lane_cells({1: (6.4e9, 3), 2: (0.0, 0), 4: (0.0, 0)})
        assert m.core_budget_gain(1, 4) == 1.0
        assert m.best_lanes(cells) == 1

    def test_extrapolation_pays_with_idle_cores(self):
        """Same samples on a 96-core host: the core budget covers the
        extra streams, the linear extrapolation wins, the plan widens."""
        m = CostModel(cores=96, peers=3)
        cells = _lane_cells({1: (6.4e9, 3), 2: (0.0, 0), 4: (0.0, 0)})
        assert m.core_budget_gain(1, 4) == pytest.approx(4.0)
        assert m.best_lanes(cells) == 4

    def test_unmeasured_cells_alone_plan_nothing(self):
        m = CostModel(cores=8, peers=3)
        assert m.best_lanes(_lane_cells({1: (0.0, 0), 4: (0.0, 1)})) \
            is None
        assert m.best_lanes({}) is None

    def test_width_depth_close_over_core_budget(self):
        lo = CostModel(cores=2, peers=3)
        assert lo.plan_width(nvars=2, depth_req=4) == 1  # no headroom
        assert lo.plan_depth(4, 1) == 2
        hi = CostModel(cores=96, peers=3)
        assert hi.plan_width(nvars=2, depth_req=4) == 6
        assert hi.plan_depth(4, 6) == 4  # requested is the ceiling


class _FakeStore:
    """Records every pin the planner applies; returns canned cells."""

    world = 4

    def __init__(self, cells=None):
        self._cells = cells or []
        self.calls = []
        self.listeners = []

    def sched_cells(self):
        return list(self._cells)

    def sched_pin_route(self, cls, mode):
        self.calls.append(("route", cls, mode))

    def sched_pin_lanes(self, cls, lanes):
        self.calls.append(("lanes", cls, lanes))

    def set_async_width(self, n):
        self.calls.append(("width", n))

    def add_peer_listener(self, cb):
        self.listeners.append(cb)


def _rows(route=(), lanes=()):
    rows = []
    for cls, knob, bw, n in route:
        rows.append({"source": 0, "cls": cls, "knob": knob,
                     "ewma_bps": bw, "n": n})
    for cls, knob, bw, n in lanes:
        rows.append({"source": 1, "cls": cls, "knob": knob,
                     "ewma_bps": bw, "n": n})
    return rows


class TestScheduler:
    def _clean_env(self, monkeypatch):
        for var in ("DDSTORE_TCP_LANES", "DDSTORE_CONNS_PER_PEER",
                    "DDSTORE_TCP_LANES_AUTOTUNE", "DDSTORE_ASYNC_THREADS",
                    "DDSTORE_CMA_BULK", "DDSTORE_CMA_SCATTER",
                    "DDSTORE_READAHEAD_DEPTH", "DDSTORE_SCHED"):
            monkeypatch.delenv(var, raising=False)

    def test_joint_plan_from_canned_samples(self, monkeypatch):
        self._clean_env(monkeypatch)
        st = _FakeStore(_rows(
            route=[(0, 0, 5e9, 3), (0, 1, 8e9, 3),     # bulk: tcp wins
                   (1, 0, 2e9, 3), (1, 1, 1e9, 3)],    # scatter: cma
            lanes=[(0, 1, 3e9, 3), (0, 4, 2e9, 3),     # bulk: 1 lane
                   (1, 1, 6e9, 3), (1, 4, 2e9, 3)]))   # scatter: 1 lane
        sch = Scheduler(st, nvars=2, requested_depth=4, enabled=True)
        plan = sch.on_epoch()
        assert plan.route == {"bulk": "tcp", "scatter": "cma"}
        assert plan.lanes == {"bulk": 1, "scatter": 1}
        assert plan.engaged
        assert ("route", 0, 1) in st.calls and ("route", 1, 0) in st.calls
        assert ("lanes", 0, 1) in st.calls and ("lanes", 1, 1) in st.calls
        assert plan.predicted_gbps["bulk"] > 0
        snap = sch.snapshot()
        assert snap["engaged"] and snap["replans"] == 1
        assert snap["plan"]["depth"] == plan.depth

    def test_pin_semantics_freeze_knobs(self, monkeypatch):
        """Every PR 1-5 env knob is a PIN: the planner must not touch a
        user-frozen knob (that is what keeps the lanes=1 identity and
        chaos determinism contracts intact under the scheduler)."""
        self._clean_env(monkeypatch)
        monkeypatch.setenv("DDSTORE_TCP_LANES", "1")
        monkeypatch.setenv("DDSTORE_ASYNC_THREADS", "2")
        monkeypatch.setenv("DDSTORE_CMA_SCATTER", "0")
        st = _FakeStore(_rows(
            route=[(1, 0, 9e9, 3), (1, 1, 1e9, 3)],  # cma 9x faster...
            lanes=[(0, 1, 1e9, 3), (0, 4, 9e9, 3)]))  # ...4 lanes 9x
        sch = Scheduler(st, nvars=1, requested_depth=4, enabled=True)
        plan = sch.on_epoch()
        # Pinned knobs: untouched despite the samples saying otherwise.
        assert plan.pins["lanes_bulk"] == 1
        assert plan.pins["route_scatter"] == "tcp"
        assert plan.pins["width"] == 2
        assert not any(c[0] == "lanes" for c in st.calls)
        assert not any(c == ("route", 1, 0) for c in st.calls)
        assert not any(c[0] == "width" for c in st.calls)
        # The unpinned route_bulk is still planned (released to -1 here:
        # no bulk route samples).
        assert ("route", 0, -1) in st.calls

    def test_depth_pin_env(self, monkeypatch):
        self._clean_env(monkeypatch)
        monkeypatch.setenv("DDSTORE_READAHEAD_DEPTH", "3")
        sch = Scheduler(_FakeStore(), nvars=1, requested_depth=8,
                        enabled=True)
        sch.on_epoch()
        assert sch.planned_depth(8) == 3

    def test_replan_on_degradation_and_peer_change(self, monkeypatch):
        self._clean_env(monkeypatch)
        st = _FakeStore()
        sch = Scheduler(st, enabled=True)
        assert sch.replans == 0
        sch.on_degradation("readahead")
        assert sch.replans == 1 and sch.reasons == ["degraded:readahead"]
        # The scheduler registered itself for peer-topology changes.
        assert st.listeners
        st.listeners[0]()
        assert sch.replans == 2 and sch.reasons[-1] == "peer_change"

    def test_route_replan_has_hysteresis(self, monkeypatch):
        """The first route verdict is a raw argmax (one-shot
        calibration), but an applied pin is only overturned past the
        class's hysteresis band — a bare argmax re-applied per epoch
        would flap between near-equal paths."""
        self._clean_env(monkeypatch)
        st = _FakeStore(_rows(route=[(1, 0, 1.0e9, 3), (1, 1, 1.05e9, 3)]))
        sch = Scheduler(st, enabled=True)
        assert sch.on_epoch().route["scatter"] == "tcp"
        # Near-equal reversal inside the 1.10x scatter band: hold.
        st._cells = _rows(route=[(1, 0, 1.08e9, 3), (1, 1, 1.0e9, 3)])
        assert sch.on_epoch().route["scatter"] == "tcp"
        # Decisive reversal: flip.
        st._cells = _rows(route=[(1, 0, 1.5e9, 3), (1, 1, 1.0e9, 3)])
        assert sch.on_epoch().route["scatter"] == "cma"

    def test_no_readahead_owner_plans_no_depth_width(self, monkeypatch):
        """requested_depth=0 (the owner runs no readahead pipeline):
        the scheduler must leave depth AND admission width alone — a
        readahead-less loader must not throttle the store's other
        async users."""
        self._clean_env(monkeypatch)
        st = _FakeStore()
        sch = Scheduler(st, nvars=1, requested_depth=0, enabled=True)
        plan = sch.on_epoch()
        assert plan.depth is None and plan.width is None
        assert not any(c[0] == "width" for c in st.calls)

    def test_peer_listener_is_weak(self, monkeypatch):
        """A dead scheduler (abandoned loader) must not keep replanning
        on peer changes — the listener holds a weakref."""
        import gc

        self._clean_env(monkeypatch)
        st = _FakeStore()
        sch = Scheduler(st, enabled=True)
        assert st.listeners
        del sch
        gc.collect()
        st.listeners[0]()  # dead ref: must be a no-op, not a replan

    def test_disabled_scheduler_never_pins(self, monkeypatch):
        self._clean_env(monkeypatch)
        monkeypatch.setenv("DDSTORE_SCHED", "0")
        assert not scheduler_enabled()
        st = _FakeStore(_rows(lanes=[(0, 1, 1e9, 3), (0, 4, 9e9, 3)]))
        sch = Scheduler(st, enabled=None)
        sch.on_epoch()
        assert st.calls == []
        assert sch.snapshot()["enabled"] is False

    def test_observe_window_feeds_substrate(self, monkeypatch):
        self._clean_env(monkeypatch)
        sch = Scheduler(_FakeStore(), requested_depth=2, enabled=True)
        sch.observe_window(1 << 20, 0.001, cold=True)   # taint: dropped
        sch.observe_window(1 << 20, 0.001)              # warm-up
        sch.observe_window(1 << 20, 0.001)              # folds
        assert sch.snapshot()["measured_window_gbps"] > 0


# ---------------------------------------------------------------------------
# Native round-trip: pins, cells, admission width (TCP ThreadGroup).
# ---------------------------------------------------------------------------

class TestNativeSchedPlumbing:
    def test_pins_cells_width_roundtrip(self):
        name = uuid.uuid4().hex
        world = 2
        errors = []
        res = {}

        def body(rank):
            try:
                g = ThreadGroup(name, rank, world)
                with DDStore(g, backend="tcp") as s:
                    shard = np.full((64, 4), rank, np.float32)
                    s.add("v", shard)
                    s.barrier()
                    if rank == 0:
                        res["cells"] = s.sched_cells()
                        pool = s.lane_state()["max_lanes"]
                        # A width outside what the API takes is
                        # refused, not swallowed; 64 is taken and the
                        # pool clamps it.
                        with pytest.raises(DDStoreError):
                            s.sched_pin_lanes(0, 99)
                        s.sched_pin_lanes(0, 64)
                        with pytest.raises(DDStoreError):
                            s.sched_pin_route(1, 2)
                        s.sched_pin_route(1, 0)
                        st = s.lane_state()
                        res["pinned_active"] = st["active_lanes"]
                        res["pinned_parked"] = st["parked"]
                        res["pool"] = pool
                        # Admission width: override + ladder default.
                        res["w_default"] = s.async_width
                        s.set_async_width(3)
                        res["w_set"] = s.async_width
                        s.set_async_width(0)
                        res["w_restored"] = s.async_width
                        # Reads still byte-correct under pins, and the
                        # admission gate completes every async ticket
                        # even at width 1.
                        s.set_async_width(1)
                        idx = np.arange(64, 128)
                        np.testing.assert_array_equal(
                            s.get_batch("v", idx), np.ones((64, 4)))
                        hs = [s.get_batch_async("v", idx)
                              for _ in range(4)]
                        for h in hs:
                            np.testing.assert_array_equal(
                                h.wait(), np.ones((64, 4)))
                        assert s.async_pending() == 0
                        s.set_async_width(0)
                        # A peer update releases the planner pins and
                        # fires the DDStore peer listeners.
                        fired = []
                        s.add_peer_listener(lambda: fired.append(1))
                        host, port = s._endpoints[1]
                        s.update_peer(1, host, port)
                        assert fired == [1]
                        # A collected scheduler's listener is pruned on
                        # the next peer update (long-lived stores must
                        # not grow one dead closure per loader).
                        import gc
                        tmp = Scheduler(s, enabled=True)
                        n0 = len(s._peer_listeners)
                        del tmp
                        gc.collect()
                        s.update_peer(1, host, port)
                        assert len(s._peer_listeners) == n0 - 1
                        assert fired == [1, 1]
                        res["post_update_state"] = s.lane_state()
                        np.testing.assert_array_equal(
                            s.get_batch("v", idx), np.ones((64, 4)))
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
        # Cells: 4 route cells (2 classes x 2 paths) + one lane cell per
        # tuner level per class.
        kinds = {(c["source"], c["cls"], c["knob"])
                 for c in res["cells"]}
        assert {(0, 0, 0), (0, 0, 1), (0, 1, 0), (0, 1, 1)} <= kinds
        assert any(c["source"] == 1 for c in res["cells"])
        assert res["pinned_active"] == res["pool"]  # 64 clamped
        assert res["pinned_parked"] is True
        ladder = 4 if (os.cpu_count() or 1) >= 8 else \
            (2 if (os.cpu_count() or 1) >= 4 else 1)
        assert res["w_default"] == ladder
        assert res["w_set"] == 3 and res["w_restored"] == ladder

    def test_env_width_still_pins_default(self, monkeypatch):
        monkeypatch.setenv("DDSTORE_ASYNC_THREADS", "5")
        with DDStore(SingleGroup(), backend="local") as s:
            assert s.async_width == 5


# ---------------------------------------------------------------------------
# Loader epoch byte-identity, planner on vs off.
# ---------------------------------------------------------------------------

class TestLoaderIdentity:
    def _epochs(self, ds, **kw):
        samp = DistributedSampler(len(ds), 1, 0, seed=21)
        samp.set_epoch(1)
        ld = DeviceLoader(ds, samp, batch_size=32, workers=2, **kw)
        out = []
        for _ in range(2):  # two epochs: the planner replans between
            out.append([np.asarray(b) for b in ld])
        return out, ld

    def test_loader_without_readahead_keeps_store_width(self,
                                                        monkeypatch):
        monkeypatch.delenv("DDSTORE_ASYNC_THREADS", raising=False)
        monkeypatch.setenv("DDSTORE_SCHED", "1")
        data = np.zeros((128, 2), np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            default_w = s.async_width
            ds = ShardedDataset(s, data)
            samp = DistributedSampler(len(ds), 1, 0, seed=3)
            ld = DeviceLoader(ds, samp, batch_size=32, workers=1)
            for _ in ld:
                pass
            sched = ld.metrics.summary()["sched"]
            assert sched["plan"]["depth"] is None
            assert sched["plan"]["width"] is None
            assert s.async_width == default_w

    def test_planner_on_off_byte_identical(self, monkeypatch):
        rng = np.random.default_rng(9)
        data = rng.normal(size=(256, 3)).astype(np.float32)
        with DDStore(SingleGroup(), backend="local") as s:
            ds = ShardedDataset(s, data)
            monkeypatch.setenv("DDSTORE_SCHED", "0")
            base, ld0 = self._epochs(ds, readahead_windows=2,
                                     readahead_window_batches=2)
            assert ld0.metrics.summary()["sched"]["enabled"] is False
            monkeypatch.setenv("DDSTORE_SCHED", "1")
            got, ld1 = self._epochs(ds, readahead_windows=2,
                                    readahead_window_batches=2)
            sched = ld1.metrics.summary()["sched"]
            assert sched["enabled"] and sched["replans"] >= 2
            assert sched["plan"]["depth"] is not None
            for be, ge in zip(base, got):
                assert len(be) == len(ge) > 0
                for b, g in zip(be, ge):
                    np.testing.assert_array_equal(b, g)
            assert s.async_pending() == 0


# ---------------------------------------------------------------------------
# Knob-registry drift guard (ISSUE 6 satellite).
# ---------------------------------------------------------------------------

def test_every_documented_knob_is_registered():
    """Knob-registry drift guarding now lives in ONE place: the static
    analyzer's `knob-registry` detector (ISSUE 8), which checks every
    getenv/os.environ READ site (C++ and Python) AND every DDSTORE_*
    var documented in README/MIGRATION against REGISTRY. This test
    delegates to it so the scheduler suite still fails loudly on knob
    drift without duplicating the rule (the old README/MIGRATION-only
    grep lived here)."""
    from ddstore_tpu.analysis import contracts
    drift = contracts.check_knob_registry(REPO)
    assert drift == [], "\n".join(f.render() for f in drift)


def test_registered_pins_map_to_planned_knobs():
    from ddstore_tpu.sched.knobs import PLANNED_KNOBS
    for k in REGISTRY.values():
        if k.kind == "pin":
            assert k.pins, k.env
            for p in k.pins:
                assert p in PLANNED_KNOBS, (k.env, p)
        else:
            assert k.kind == "config", k.env


def test_pinned_knobs_parsing():
    env = {"DDSTORE_TCP_LANES": "4", "DDSTORE_CMA_BULK": "1",
           "DDSTORE_ASYNC_THREADS": "2", "DDSTORE_READAHEAD_DEPTH": "3"}
    pins = pinned_knobs(env)
    assert pins == {"route_bulk": "cma", "lanes_bulk": 4,
                    "lanes_scatter": 4, "width": 2, "depth": 3}
    assert pinned_knobs({"DDSTORE_TCP_LANES_AUTOTUNE": "0"}) == \
        {"lanes_bulk": "pool", "lanes_scatter": "pool"}
    assert pinned_knobs({}) == {}
