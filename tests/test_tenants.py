"""Multi-tenant store service (ISSUE 9): tenant namespaces over the one
variable registry, byte/var quotas with a distinct non-fatal rejection
class, share-weighted async admission, and read-only snapshot epochs
that make the paper's `update` path a safe online write API.

The default tenant "" is the bare registry — the whole pre-tenancy tree
must stay byte- and error-code-identical with tenancy inert (no attach,
no tenant envs), seeded fault counters included; that identity is
pinned here the same way PR 7 pinned DDSTORE_REPLICATION=1.
"""

import threading
import uuid

import numpy as np
import pytest

from ddstore_tpu import DDStore, DDStoreError, ThreadGroup, fault_configure
from ddstore_tpu.binding import (ERR_PEER_LOST, ERR_QUOTA,
                                 TENANT_GAUGE_KEYS, TENANT_STAT_KEYS)
from ddstore_tpu.tenant import (TenantHandle, parse_quota_spec,
                                parse_share_spec, scoped_name, share_split)

pytestmark = pytest.mark.tier1_required

NUM, DIM = 16, 8


def run_ranks(world, fn, timeout=120):
    """Run fn(rank, group) on `world` threads; re-raise the first
    failure (house pattern of test_store_threads)."""
    name = uuid.uuid4().hex
    errors = [None] * world
    results = [None] * world

    def runner(r):
        try:
            results[r] = fn(r, ThreadGroup(name, r, world))
        except BaseException as e:  # noqa: BLE001
            errors[r] = e

    threads = [threading.Thread(target=runner, args=(r,), daemon=True)
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    for e in errors:
        if e is not None:
            raise e
    assert not any(t.is_alive() for t in threads), "rank thread hung"
    return results


def stamp(rank, salt=0, num=NUM, dim=DIM):
    """Deterministic rank+salt-stamped shard: any fetched row betrays
    both its owner and which published version it came from."""
    return np.full((num, dim), (salt * 100) + rank + 1, dtype=np.float64)


# -- default-tenant identity --------------------------------------------------

def test_default_tenant_is_inert_and_byte_identical(monkeypatch):
    """With tenancy unused (no attach, no tenant envs) the tree is the
    pre-change tree: bare native names, NO tenant ledger rows, no
    summary()["tenants"] section, and a seeded fault-injected TCP read
    sequence draws the EXACT pre-change injector schedule — counter-
    for-counter — with the same error-free results."""
    monkeypatch.delenv("DDSTORE_TENANT_QUOTAS", raising=False)
    monkeypatch.delenv("DDSTORE_TENANT_SHARES", raising=False)
    monkeypatch.setenv("DDSTORE_CMA", "0")  # draws live in the TCP serve loop
    monkeypatch.setenv("DDSTORE_RETRY_MAX", "4")
    monkeypatch.setenv("DDSTORE_RETRY_BASE_MS", "5")

    def body(rank, group):
        with DDStore(group, backend="tcp") as s:
            s.add("v", stamp(rank))
            s.barrier()
            if rank == 0:
                # Bare name in the native registry — the scoped-name
                # machinery never touched the default path.
                assert s._native.query("v")["total_rows"] == 2 * NUM
                # Zero ledger rows: not even the default tenant is
                # tracked until explicitly configured.
                assert s._native.tenant_names() == []
                idx = np.arange(NUM, 2 * NUM)  # all remote: every read
                fault_configure("reset:0.25", seed=123)  # crosses wire
                try:
                    for _ in range(6):
                        got = s.get_batch("v", idx)
                finally:
                    checks = s.fault_stats()
                    fault_configure("", 0)
                np.testing.assert_array_equal(got, stamp(1))
                # The pinned PRE-CHANGE injector schedule for this
                # seeded sequence (seed 123, 6 batched reads, reset
                # p=0.25), verified identical on the pre-tenancy tree:
                # any extra native draw — a tenant lookup consuming
                # entropy, a changed op sequence — shifts these.
                assert checks["fault_checks"] == 7
                assert checks["injected_reset"] == 1
                assert checks["retry_transient"] == 1
                assert checks["retry_reconnects"] == 1
            s.barrier()

    run_ranks(2, body)


def test_metrics_summary_has_no_tenant_section_by_default():
    """A single-tenant epoch record is unchanged: no "tenants" key."""
    from ddstore_tpu.utils.metrics import PipelineMetrics

    m = PipelineMetrics()
    m.set_tenant_source(lambda: {})
    m.epoch_start()
    m.epoch_end()
    assert "tenants" not in m.summary()


# -- namespaces ---------------------------------------------------------------

def test_namespace_isolation_and_shared_default_reads():
    """Two tenants cannot see, read, update, or free each other's
    variables; both can read the shared default namespace; the default
    registry never shows scoped names to the root handle's API."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("shared", stamp(rank))
            a = s.attach("job-a")
            b = s.attach("job-b")
            a.add("ds", stamp(rank, salt=1))
            b.add("ds", stamp(rank, salt=2))
            # Same user name, disjoint native variables.
            np.testing.assert_array_equal(a.get("ds", 0)[0],
                                          stamp(0, salt=1)[0])
            np.testing.assert_array_equal(b.get("ds", 0)[0],
                                          stamp(0, salt=2)[0])
            # Shared default namespace readable from every handle...
            np.testing.assert_array_equal(a.get("shared", 0)[0],
                                          stamp(0)[0])
            # ...but not writable through a tenant handle.
            with pytest.raises(DDStoreError, match="cross-tenant"):
                a.update("shared", stamp(rank, salt=9))
            # Cross-tenant names don't exist for the other handle.
            a.free("ds")
            s.barrier()
            np.testing.assert_array_equal(b.get("ds", 0)[0],
                                          stamp(0, salt=2)[0])
            with pytest.raises(KeyError, match="refused"):
                a.get("other-only", 0)
            with pytest.raises(DDStoreError, match="refused"):
                b.free("not-mine-either")
            s.barrier()

    run_ranks(2, body)


def test_tenant_namespace_is_shared_across_handles_and_snapshots():
    """A named tenant's namespace belongs to the TENANT, not to one
    handle object: a second attach resolves variables the first handle
    registered, and a snapshot handle of that tenant pins the tenant's
    own variables like any other data."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            a = s.attach("job-a")
            a.add("ds", stamp(rank, salt=1))
            a2 = s.attach("job-a")
            np.testing.assert_array_equal(a2.get("ds", 0)[0],
                                          stamp(0, salt=1)[0])
            snap = None
            if rank == 0:
                snap = s.attach("job-a", snapshot=True)
            s.barrier()
            a.update("ds", stamp(rank, salt=2))
            s.barrier()
            # Fresh handles see the new bytes; the snapshot stays on
            # its pinned version of the TENANT variable.
            np.testing.assert_array_equal(a2.get("ds", 0)[0],
                                          stamp(0, salt=2)[0])
            if rank == 0:
                np.testing.assert_array_equal(snap.get("ds", 0)[0],
                                              stamp(0, salt=1)[0])
                snap.detach()
            s.barrier()

    run_ranks(2, body)


def test_default_quota_configured_after_add_releases_only_reserved():
    """Configuring the default tenant BETWEEN add and free must not
    return budget that was never reserved: freeing a pre-quota
    variable leaves the ledger exactly covering the tracked ones, so
    an over-budget add is still refused."""
    shard = NUM * DIM * 8  # one rank shard, bytes

    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("v1", stamp(rank))  # registered before any tracking
            s.set_tenant_quota("", max_bytes=2 * shard)
            s.add("v2", stamp(rank, salt=1))  # reserves one shard
            s.free("v1")  # never reserved -> must release NOTHING
            # ("" never appears in tenant_names()'s CSV: ask natively.)
            st = s._native.tenant_stats("")
            assert st["bytes"] == shard and st["vars"] == 1
            s.add("v3", stamp(rank, salt=2))  # exactly fills the budget
            with pytest.raises(DDStoreError) as ei:
                s.add("v4", stamp(rank, salt=3))
            assert ei.value.code == ERR_QUOTA
            s.barrier()

    run_ranks(1, body)


def test_uneven_shard_quota_verdict_agrees_across_ranks():
    """Admission charges every rank the LARGEST rank's shard bytes, so
    an uneven collective add is refused (or admitted) on EVERY rank —
    never half-registered with a stranded shard on the rank that
    happened to fit."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.set_tenant_quota("t", max_bytes=(3 * NUM * DIM * 8) // 2)
            h = s.attach("t")
            rows = 2 * NUM if rank == 0 else NUM // 2  # 2.0x vs 0.25x
            with pytest.raises(DDStoreError) as ei:
                h.add("uneven", np.full((rows, DIM), rank + 1.0))
            assert ei.value.code == ERR_QUOTA  # on BOTH ranks
            # The refusal was clean everywhere: the documented recovery
            # (smaller shards, same name) works on every rank.
            h.add("uneven", stamp(rank))
            s.barrier()

    run_ranks(2, body)


def test_tenant_label_validation_covers_runtime_setters():
    """Labels that would corrupt the names-CSV / env-spec / native
    scoping formats are refused at EVERY entry point keyed by a tenant
    label, not just attach(); the spec parsers skip them."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            for bad in ("a,b", "a=b", "x:y", "c\x02d", "\x03s"):
                with pytest.raises(ValueError):
                    s.set_tenant_quota(bad, 1)
                with pytest.raises(ValueError):
                    s.set_tenant_share(bad, 2)
                with pytest.raises(ValueError):
                    s.set_tenant_lane_budget(bad, 1)
            s.barrier()

    run_ranks(1, body)
    assert parse_share_spec("ok=2,b\x02ad=3") == {"ok": 2}
    assert parse_quota_spec("ok=64,b\x02ad=128") == {"ok": (64, -1)}


def test_quota_spec_suffix_never_bricks_a_tenant(monkeypatch):
    """A bare trailing ':' in DDSTORE_TENANT_QUOTAS means UNLIMITED
    vars; junk after the values skips the entry (both matching the
    Python parser) — neither may parse as quota_vars=0, which would
    refuse the tenant's every registration."""
    monkeypatch.setenv("DDSTORE_TENANT_QUOTAS",
                       f"a={4 * NUM * DIM * 8}:,b=10:x,c=10x")

    def body(rank, group):
        with DDStore(group, backend="local") as s:
            h = s.attach("a")
            h.add("v1", stamp(rank))
            h.add("v2", stamp(rank))  # vars unlimited; bytes budget ok
            assert s._native.tenant_stats("a")["quota_vars"] == -1
            for skipped in ("b", "c"):  # malformed entries: no quota
                assert s._native.tenant_stats(skipped)["quota_bytes"] \
                    == -1
            s.barrier()

    run_ranks(1, body)
    assert parse_quota_spec("a=64:,b=10:x,c=10x") == {"a": (64, -1)}


def test_snapshot_pins_scope_to_reader_namespace():
    """attach(T, snapshot=True) pins the shared default namespace and
    T's OWN variables — never another tenant's: an unrelated tenant's
    update traffic must not materialize kept copies the handle could
    never read."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            a = s.attach("A")
            a.add("big", stamp(rank, salt=1))
            snap_b = s.attach("B", snapshot=True)
            s.barrier()
            a.update("big", stamp(rank, salt=2))
            s.barrier()
            # A's publish kept nothing for B's snapshot.
            assert s.snapshot_stats()["kept_versions"] == 0
            np.testing.assert_array_equal(a.get("big", 0)[0],
                                          stamp(0, salt=2)[0])
            snap_b.detach()
            s.barrier()

    run_ranks(2, body)


def test_free_readd_under_live_snapshot_never_aliases_stale_pin():
    """free() drops a variable's snapshot PINS along with its kept
    copies: a later add() under the same name restarts at update_seq 0,
    which would otherwise alias the stale pin and serve (and even
    copy-on-publish) the NEW generation's bytes as "pinned". After
    free + re-add the snapshot degrades to current bytes — the
    registered-after-the-pin semantics."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("data", stamp(rank, salt=1))
            ev = s.attach("eval", snapshot=True)
            s.free("data")
            s.add("data", stamp(rank, salt=9))
            np.testing.assert_array_equal(ev.get("data", 0)[0],
                                          stamp(0, salt=9)[0])
            # Unpinned now (that is the point): sync before the next
            # publish so the salt-9 read above cannot race it.
            s.barrier()
            s.update("data", stamp(rank, salt=10))
            s.barrier()
            # No pin survived the free: the update kept NO copy for the
            # old snapshot id, and the snapshot read serves current.
            assert s.snapshot_stats()["kept_versions"] == 0
            np.testing.assert_array_equal(ev.get("data", 0)[0],
                                          stamp(0, salt=10)[0])
            ev.detach()
            s.barrier()

    run_ranks(2, body)


def test_duplicate_add_at_quota_is_exists_not_quota():
    """An at-budget tenant re-adding an EXISTING name gets the
    pre-tenancy answer (exists), not a spurious quota rejection
    telling it to free variables — and no quota_rejections tick."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.set_tenant_quota("capped", max_bytes=NUM * DIM * 8)
            c = s.attach("capped")
            c.add("ok", stamp(rank))  # exactly fills the budget
            with pytest.raises(DDStoreError) as ei:
                c.add("ok", stamp(rank))
            assert ei.value.code != ERR_QUOTA
            assert "exists" in str(ei.value).lower()
            assert s._native.tenant_stats("capped")["quota_rejections"] \
                == 0
            s.barrier()

    run_ranks(1, body)


def test_default_tenant_row_visible_and_reads_ledger_under_reader():
    """(a) A configured default tenant's ledger row survives the
    tenant_names() CSV (the leading-separator encoding); (b) a named
    tenant's SYNC bulk reads of the shared default namespace ledger
    under the READING tenant — the same as_tenant rule the async
    admission gate and the QoS lane budgets apply."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("shared", stamp(rank))
            s.set_tenant_quota("", max_bytes=-1, max_vars=-1)
            assert "" in s._native.tenant_names()
            assert "" in s.tenant_stats()
            ev = s.attach("eval")
            before = s._native.tenant_stats("eval")["read_bytes"]
            ev.get_batch("shared", np.arange(2 * NUM))
            after = s._native.tenant_stats("eval")["read_bytes"]
            assert after - before == 2 * NUM * DIM * 8
            ev.get("shared", 0)  # single-row leg ledgers too
            assert s._native.tenant_stats("eval")["read_bytes"] \
                - after == DIM * 8
            s.barrier()

    run_ranks(2, body)


def test_scoped_names_cannot_be_forged_from_user_strings():
    """The native separators are control characters and the Python
    boundary rejects them in BOTH var names and tenant labels, so no
    user string can alias another namespace."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            with pytest.raises(ValueError, match="control"):
                s.add("\x02evil\x02x", stamp(rank))
            with pytest.raises(ValueError, match="control"):
                s.attach("bad\x02tenant")
            with pytest.raises(ValueError, match="reserved"):
                s.attach("a=b")
        return True

    run_ranks(1, body)
    assert scoped_name("", "x") == "x"  # default tenant = bare name
    assert scoped_name("t", "x") == "\x02t\x02x"


# -- quotas -------------------------------------------------------------------

def test_quota_rejection_is_its_own_nonfatal_class(monkeypatch):
    """An over-budget add is refused with ERR_QUOTA — a code distinct
    from ERR_PEER_LOST (nothing died), the store keeps serving, and
    freeing returns the budget so the next add is admitted."""
    monkeypatch.setenv("DDSTORE_TENANT_QUOTAS",
                       f"capped={3 * NUM * DIM * 8}:2")

    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("base", stamp(rank))
            c = s.attach("capped")
            c.add("ok", stamp(rank))
            with pytest.raises(DDStoreError) as ei:
                c.add("too-big", np.zeros((4 * NUM, DIM)))
            assert ei.value.code == ERR_QUOTA
            assert ei.value.code != ERR_PEER_LOST
            assert "quota" in str(ei.value).lower()
            # Non-fatal: the store (and the tenant's admitted var)
            # still serve, and the rejection is ledger-visible.
            np.testing.assert_array_equal(c.get("ok", 0)[0], stamp(0)[0])
            st = s.tenant_stats()["capped"]
            assert st["quota_rejections"] >= 1
            assert st["vars"] == 1
            assert st["bytes"] == NUM * DIM * 8
            # Var-count half of the budget (quota_vars=2: "ok" + one).
            c.add("two", stamp(rank))
            with pytest.raises(DDStoreError) as ei2:
                c.add("three", stamp(rank))
            assert ei2.value.code == ERR_QUOTA
            # Free returns the budget atomically.
            c.free("two")
            s.barrier()
            c.add("three", stamp(rank))
            s.barrier()

    run_ranks(2, body)


def test_quota_and_share_spec_parsers():
    assert parse_quota_spec("a=100:2,b=5") == {"a": (100, 2),
                                               "b": (5, -1)}
    assert parse_quota_spec("bad,=x,c=1:1") == {"c": (1, 1)}
    assert parse_share_spec("a=3,b=1,junk,c=0") == {"a": 3, "b": 1}
    # The exact native admission rule: max(1, total * share / sum).
    assert share_split(8, {"busy": 7, "capped": 1}) == {"busy": 7,
                                                        "capped": 1}
    assert share_split(2, {"a": 1, "b": 1, "c": 6}) == {"a": 1, "b": 1,
                                                        "c": 1}


def test_async_admission_share_defers_not_rejects():
    """With shares configured, a tenant over its bound DEFERS (ticket
    contract unchanged — every read completes) and the deferral is
    ledger-visible; the other tenant's admissions proceed."""
    rows = 4096  # ~2 MB per read: submissions overlap their service

    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.set_async_width(2)
            s.set_tenant_share("fg", 3)
            s.set_tenant_share("bg", 1)
            fg, bg = s.attach("fg"), s.attach("bg")
            fg.add("ds", stamp(rank, salt=1, num=rows))
            bg.add("ds", stamp(rank, salt=2, num=rows))
            idx = np.arange(2 * rows)
            want_bg = np.concatenate([stamp(r, salt=2, num=rows)
                                      for r in range(2)])
            want_fg = np.concatenate([stamp(r, salt=1, num=rows)
                                      for r in range(2)])
            # bg bound = max(1, 2*1/4) = 1: a burst of 8 concurrent bg
            # reads overflows it whenever any two overlap. Whether a
            # given burst overlaps is scheduler timing — retry bursts
            # (bounded) until the gate visibly deferred; every read
            # completes correctly either way (defer-not-reject).
            submitted = 0
            for _ in range(50):
                h = [bg.get_batch_async("ds", idx) for _ in range(8)]
                g = [fg.get_batch_async("ds", idx) for _ in range(2)]
                submitted += 10
                for hh in h:
                    np.testing.assert_array_equal(hh.wait(), want_bg)
                for gg in g:
                    np.testing.assert_array_equal(gg.wait(), want_fg)
                if s.tenant_stats()["bg"]["async_deferred"] >= 1:
                    break
            assert s.async_pending() == 0
            st = s.tenant_stats()
            assert st["bg"]["async_deferred"] >= 1
            assert st["bg"]["async_admitted"] + \
                st["fg"]["async_admitted"] == submitted
            s.barrier()

    run_ranks(2, body)


# -- snapshot epochs ----------------------------------------------------------

@pytest.mark.parametrize("backend", ["local", "tcp"])
def test_snapshot_reader_stable_across_update_fence(backend, monkeypatch):
    """The online-update contract on both serving legs: a snapshot
    handle's reads are byte-stable across a concurrent writer's
    update + epoch fence, current readers see the new bytes, and the
    kept version exists only while pinned."""
    monkeypatch.setenv("DDSTORE_CMA", "0")  # tcp leg: resolve on the wire
    gates = {g: threading.Barrier(2) for g in ("pinned", "updated")}

    def body(rank, group):
        with DDStore(group, backend=backend) as s:
            s.add("data", stamp(rank, salt=1))
            ev = None
            if rank == 0:
                ev = s.attach(tenant="eval", snapshot=True)
            gates["pinned"].wait()
            s.epoch_begin()
            s.update("data", stamp(rank, salt=2))
            s.epoch_end()
            gates["updated"].wait()
            idx = np.arange(2 * NUM)
            want_v1 = np.concatenate([stamp(r, salt=1) for r in range(2)])
            want_v2 = np.concatenate([stamp(r, salt=2) for r in range(2)])
            if rank == 0:
                np.testing.assert_array_equal(ev.get_batch("data", idx),
                                              want_v1)
                # Both ranks hold a kept version for their own shard.
                assert s.snapshot_stats()["kept_versions"] == 1
                assert s.snapshot_stats()["active_snapshots"] == 1
                ev.detach()
                np.testing.assert_array_equal(ev.get_batch("data", idx),
                                              want_v2)
            np.testing.assert_array_equal(s.get_batch("data", idx),
                                          want_v2)
            s.barrier()
            # Last detach reclaimed the kept copy on EVERY rank.
            st = s.snapshot_stats()
            assert st["kept_versions"] == 0 and st["kept_bytes"] == 0
            assert st["active_snapshots"] == 0
            s.barrier()

    run_ranks(2, body)


def test_last_detach_reclaims_kept_version():
    """Two snapshots pinning the same version share one kept copy;
    releasing one keeps it, releasing the LAST reclaims it — on every
    rank (the pins were placed store-wide by the acquire)."""
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("data", stamp(rank, salt=1))
            s1 = s2 = None
            if rank == 0:
                s1 = s.attach("r1", snapshot=True)
                s2 = s.attach("r2", snapshot=True)
            s.barrier()
            s.update("data", stamp(rank, salt=2))
            # Copy-on-publish: ONE kept copy (per rank, of its own
            # shard) serves both pins.
            assert s.snapshot_stats()["kept_versions"] == 1
            assert s.snapshot_stats()["kept_bytes"] == NUM * DIM * 8
            s.barrier()
            if rank == 0:
                np.testing.assert_array_equal(
                    s1.get_batch("data", np.arange(2 * NUM)),
                    np.concatenate([stamp(r, salt=1) for r in range(2)]))
                s1.detach()
                # The surviving snapshot still pins the version —
                # everywhere (release round trips are synchronous).
                assert s.snapshot_stats()["kept_versions"] == 1
                np.testing.assert_array_equal(
                    s2.get("data", NUM)[0], stamp(1, salt=1)[0])
                s2.detach()
            s.barrier()
            st = s.snapshot_stats()
            assert st["kept_versions"] == 0 and st["kept_bytes"] == 0
            s.barrier()

    run_ranks(2, body)


def test_snapshot_handle_is_read_only():
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("data", stamp(rank))
            snap = s.attach(snapshot=True)
            for call in (lambda: snap.add("x", stamp(rank)),
                         lambda: snap.update("data", stamp(rank)),
                         lambda: snap.free("data")):
                with pytest.raises(DDStoreError, match="read-only"):
                    call()
            # Unpinned vars registered AFTER the acquire don't exist in
            # the snapshot view (the pin set is acquire-time).
            s.add("later", stamp(rank, salt=3))
            np.testing.assert_array_equal(snap.get("data", 0)[0],
                                          stamp(0)[0])
            snap.detach()
            s.barrier()

    run_ranks(2, body)


def test_snapshot_pins_are_per_tenant_ledger_visible():
    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.add("data", stamp(rank))
            if rank == 0:
                ev = s.attach("eval", snapshot=True)
            s.barrier()
            # The pin gauge is store-wide visible: the acquire placed
            # one pin (for tenant "eval") on EVERY rank.
            assert s.tenant_stats()["eval"]["snapshot_pins"] == 1
            s.barrier()
            if rank == 0:
                ev.detach()
            s.barrier()
            assert s.tenant_stats()["eval"]["snapshot_pins"] == 0
            s.barrier()

    run_ranks(2, body)


# -- metrics ------------------------------------------------------------------

def test_tenant_metrics_delta_and_gauges():
    """PipelineMetrics tenant source: counters are per-epoch deltas,
    gauges (quota_*/bytes/vars/snapshot_pins/share) report live; a
    tenant appearing mid-epoch deltas against zero."""
    from ddstore_tpu.utils.metrics import PipelineMetrics

    assert set(TENANT_GAUGE_KEYS) == set(PipelineMetrics.TENANT_GAUGES)
    feed = {"busy": dict(zip(TENANT_STAT_KEYS, [0] * len(TENANT_STAT_KEYS)))}
    feed["busy"].update(share=7, reads=10, read_bytes=1000, bytes=512)
    m = PipelineMetrics()
    m.set_tenant_source(lambda: {t: dict(v) for t, v in feed.items()})
    m.epoch_start()
    feed["busy"].update(reads=25, read_bytes=4000, async_admitted=3)
    feed["capped"] = dict(zip(TENANT_STAT_KEYS,
                              [0] * len(TENANT_STAT_KEYS)))
    feed["capped"].update(quota_rejections=2, quota_bytes=4096, share=1)
    m.epoch_end()
    out = m.summary()["tenants"]
    assert out["busy"]["reads"] == 15          # delta
    assert out["busy"]["read_bytes"] == 3000   # delta
    assert out["busy"]["async_admitted"] == 3
    assert out["busy"]["share"] == 7           # gauge
    assert out["busy"]["bytes"] == 512         # gauge, raw
    assert out["capped"]["quota_rejections"] == 2  # vs implicit zero
    assert out["capped"]["quota_bytes"] == 4096


def test_live_store_tenant_ledger_deltas():
    """End-to-end: an epoch's summary()["tenants"] rows carry the
    epoch's OWN traffic (read deltas), with quota gauges raw."""
    from ddstore_tpu.utils.metrics import PipelineMetrics

    def body(rank, group):
        with DDStore(group, backend="local") as s:
            s.set_tenant_quota("job", max_bytes=1 << 20)
            j = s.attach("job")
            j.add("ds", stamp(rank))
            m = PipelineMetrics()
            m.set_tenant_source(s.tenant_stats)
            idx = np.arange(2 * NUM)
            j.get_batch("ds", idx)  # pre-epoch traffic: excluded
            m.epoch_start()
            for _ in range(3):
                j.get_batch("ds", idx)
            m.epoch_end()
            row = m.summary()["tenants"]["job"]
            assert row["reads"] == 3
            assert row["read_bytes"] == 3 * idx.size * DIM * 8
            assert row["quota_bytes"] == 1 << 20  # gauge
            assert row["vars"] == 1
            s.barrier()

    run_ranks(2, body)


# -- scheduler / planner cells ------------------------------------------------

def test_planner_emits_tenant_budget_cells():
    """With shares configured the joint plan grows per-tenant
    width/lane cells (share_split of the planned width and lanes);
    without shares the plan is unchanged (no tenants key content)."""
    from ddstore_tpu.sched.planner import Scheduler

    class FakeStore:
        backend = "tcp"
        async_width = 8
        world = 2

        def __init__(self):
            self.lane_budgets = {}

        def sched_cells(self):
            return []

        def sched_pin_route(self, cls, mode):
            pass

        def sched_pin_lanes(self, cls, lanes):
            pass

        def set_async_width(self, width):
            pass

        def tenant_stats(self):
            return {"busy": {"share": 7}, "capped": {"share": 1}}

        def lane_state(self):
            return {"max_lanes": 4}

        def set_tenant_lane_budget(self, tenant, lanes):
            self.lane_budgets[tenant] = lanes

    st = FakeStore()
    sched = Scheduler(store=st, enabled=True)
    plan = sched.replan("unit")
    # The budgets are share_split cells of the JOINT plan's width/lane
    # choices (whatever the cost model picked), not a fourth tuner.
    shares = {"busy": 7, "capped": 1}
    exp_w = share_split(max(1, int(plan.width or st.async_width)),
                        shares)
    assert {t: b["width"] for t, b in plan.tenants.items()} == exp_w
    assert plan.tenants["busy"]["lanes"] >= \
        plan.tenants["capped"]["lanes"] == 1
    assert st.lane_budgets == {t: b["lanes"]
                               for t, b in plan.tenants.items()}
    # snapshot() carries the cells for the epoch record.
    snap = sched.snapshot()
    assert snap["plan"]["tenants"] == plan.tenants

    class NoShares(FakeStore):
        def tenant_stats(self):
            # share gauge 0 = the tenant is ledger-visible (quota or
            # traffic) but never ran SetTenantShare — the gate is off.
            return {"": {"share": 0}}

    assert Scheduler(store=NoShares(), enabled=True).compute([]) \
        .tenants == {}

    class BrokenBudget(FakeStore):
        def set_tenant_lane_budget(self, tenant, lanes):
            raise RuntimeError("closed native handle")

    # A failed budget application is a REAL error: surfaced as a
    # warning, and the budgets alone never flip the plan to engaged.
    with pytest.warns(RuntimeWarning, match="not applied"):
        Scheduler(store=BrokenBudget(), enabled=True).replan("unit")
