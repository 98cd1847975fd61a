"""Paginated LIST, continue tokens, bounded-staleness reads, and chained
replication fencing — the PR-18 read-plane contracts (ISSUE 18).

Reference shapes: apiserver list chunking (``limit``/``continue`` pinned
to a resourceVersion snapshot, expired tokens 410 Gone into a fresh
walk — staging/apiserver/pkg/storage/etcd3/store.go), the watch cache's
``resourceVersion=0`` bounded-staleness serve (cacher.go), and client-go
Reflector paging its relist through the chunked LIST (reflector.go,
pager.go). The continue token additionally carries the store's list
GENERATION: seqs renumber densely on snapshot loads (crash recovery,
replica bootstrap/resync), so a cursor minted before a load would
silently skip or duplicate entries where deletions had left seq gaps —
the server 410s the mismatch instead.
"""

from __future__ import annotations

import json
import urllib.error
import urllib.request

import pytest

pytest.importorskip("jax")

from kubetpu.api import codec
from kubetpu.api.wrappers import make_node, make_pod
from kubetpu.apiserver import APIServer, RemoteStore
from kubetpu.apiserver.remote import RemoteUnavailableError
from kubetpu.client.informers import NODES, PODS
from kubetpu.store.memstore import MemStore
from kubetpu.store.replication import (
    FollowerReplicator,
    LeaderLease,
)
from kubetpu.telemetry.rules import default_rules


def _native_available() -> bool:
    from kubetpu.native import store_core

    return store_core() is not None


CORES = [
    pytest.param(False, id="pycore"),
    pytest.param(
        None, id="native",
        marks=pytest.mark.skipif(
            not _native_available(), reason="native core unbuildable"
        ),
    ),
]

WIRES = ["json", "binary"]


def _get_json(url: str) -> dict:
    with urllib.request.urlopen(url) as r:
        return json.loads(r.read())


def _get_code(url: str) -> int:
    """The HTTP status of a GET (errors included)."""
    try:
        with urllib.request.urlopen(url) as r:
            return r.status
    except urllib.error.HTTPError as e:
        return e.code


def _walk_pages(base: str, kind: str, limit: int, between=None):
    """Drive the raw paged protocol: returns (keys in walk order,
    resourceVersion reported by the FIRST page — the pinned snapshot,
    page count). ``between(page_no)`` runs after each truncated page —
    the churn-injection seam."""
    keys, pages, tok, rv = [], 0, "", None
    while True:
        u = f"{base}/apis/{kind}?limit={limit}"
        if tok:
            u += "&continue=" + tok
        body = _get_json(u)
        pages += 1
        if rv is None:
            rv = body["resourceVersion"]
        # every page reports the walk's PINNED snapshot rv, not the tip
        assert body["resourceVersion"] == rv
        keys += [it["key"] for it in body["items"]]
        tok = body.get("continue", "")
        if not tok:
            return keys, rv, pages
        if between is not None:
            between(pages)


# ------------------------------------------------------- paged walk parity

@pytest.mark.parametrize("native", CORES)
@pytest.mark.parametrize("wire", WIRES)
def test_paged_walk_matches_unpaged(native, wire):
    """A RemoteStore relist through N bounded pages returns exactly the
    unpaged list — same keys, same order, same objects, same rv — on
    both cores and both wire codecs, and records the walk's shape."""
    store = MemStore(native=native)
    srv = APIServer(store).start()
    try:
        for i in range(12):
            store.create(NODES, f"n{i:02d}", make_node(f"n{i:02d}"))
        store.create(PODS, "ns/p0", make_pod("p0"))

        rs = RemoteStore(srv.url, wire=wire)
        rs.LIST_PAGE_LIMIT = 5
        items, rv = rs.list(NODES)
        direct, drv = store.list(NODES)
        assert [k for k, _ in items] == [k for k, _ in direct]
        assert [o for _, o in items] == [o for _, o in direct]
        assert rv == drv
        assert rs.last_relist["pages"] == 3
        assert rs.last_relist["bytes"] > rs.last_relist["max_page_bytes"] > 0
        assert rs.relist_stats == {
            "relists": 1, "pages": 3,
            "bytes": rs.last_relist["bytes"],
            "max_page_bytes": rs.last_relist["max_page_bytes"],
        }

        # limit=0 is the unpaged escape hatch — identical result
        items0, rv0 = rs.list(NODES, limit=0)
        assert items0 == items and rv0 == rv

        # selectors ride the walk (the page seam parses them once)
        sel, _ = rs.list(NODES, field_selector="metadata.name=n03")
        assert [k for k, _ in sel] == ["n03"]
    finally:
        srv.close()


@pytest.mark.parametrize("native", CORES)
def test_continue_token_walk_is_gapless_under_churn(native):
    """Mid-walk creates/updates/deletes never duplicate a key and never
    drop an object that existed for the WHOLE walk — the seq-ordered
    cursor contract (updates keep their seq, so a churned object is not
    re-delivered; deletions cannot shift the cursor past a survivor)."""
    store = MemStore(native=native)
    srv = APIServer(store).start()
    try:
        names = [f"n{i:02d}" for i in range(20)]
        for n in names:
            store.create(NODES, n, make_node(n))

        deleted, created = [], []

        def churn(page_no):
            # delete one early entry (already walked) and one late entry
            # (not yet walked), update a mid entry, create a fresh one
            victim_lo, victim_hi = f"n{page_no:02d}", f"n{19 - page_no:02d}"
            for v in (victim_lo, victim_hi):
                if store.get(NODES, v)[0] is not None:
                    store.delete(NODES, v)
                    deleted.append(v)
            obj, rv = store.get(NODES, "n10")
            if obj is not None:
                store.update(NODES, "n10", obj, expect_rv=rv)
            fresh = f"x{page_no}"
            store.create(NODES, fresh, make_node(fresh))
            created.append(fresh)

        keys, _rv, pages = _walk_pages(srv.url, NODES, 4, between=churn)
        assert pages > 3
        assert len(keys) == len(set(keys)), "duplicate key in paged walk"
        survivors = set(names) - set(deleted)
        assert survivors <= set(keys), (
            "paged walk dropped an object that existed for the whole walk"
        )
        assert set(keys) <= set(names) | set(created)
    finally:
        srv.close()


@pytest.mark.parametrize("native", CORES)
def test_mid_walk_create_excluded_by_snapshot_cut(native):
    """An object created AFTER the walk's first page never splices into a
    later page: page 1 captures the store's seq high-water mark and the
    continue token carries it, so the walk is a membership-consistent cut
    of the keyspace as of the pinned snapshot (creations get fresh,
    higher seqs and fall outside the bound)."""
    store = MemStore(native=native)
    srv = APIServer(store).start()
    try:
        names = [f"n{i:02d}" for i in range(17)]
        for n in names:
            store.create(NODES, n, make_node(n))

        def late_create(page_no):
            store.create(NODES, f"zzz-late-{page_no}", make_node("z"))

        keys, rv, pages = _walk_pages(srv.url, NODES, 5, between=late_create)
        assert pages > 2
        assert not any(k.startswith("zzz-late") for k in keys), (
            "snapshot cut violated: mid-walk creation spliced into a page"
        )
        assert sorted(keys) == sorted(names)
        # the pinned rv predates every mid-walk creation
        assert rv < store.resource_version
        # a FRESH walk (new bound) sees the late arrivals
        keys2, _rv2, _ = _walk_pages(srv.url, NODES, 5)
        assert set(keys2) > set(names)
    finally:
        srv.close()


# -------------------------------------------------- token expiry: 410 paths

def test_expired_token_410s_and_fresh_walk_recovers():
    """A token whose snapshot rv fell behind the event ring's compaction
    horizon earns 410 Gone; an immediate fresh walk succeeds."""
    store = MemStore(history=4)
    srv = APIServer(store).start()
    try:
        for i in range(10):
            store.create(NODES, f"n{i}", make_node(f"n{i}"))
        first = _get_json(f"{srv.url}/apis/{NODES}?limit=3")
        tok = first["continue"]
        # churn past the tiny ring: the snapshot can no longer promise a
        # gapless resume
        for _ in range(8):
            obj, rv = store.get(NODES, "n0")
            store.update(NODES, "n0", obj, expect_rv=rv)
        assert store.compacted_through > first["resourceVersion"]
        assert _get_code(
            f"{srv.url}/apis/{NODES}?limit=3&continue={tok}"
        ) == 410
        keys, _rv, pages = _walk_pages(srv.url, NODES, 3)
        assert sorted(keys) == sorted(f"n{i}" for i in range(10))
        assert pages == 4
    finally:
        srv.close()


def test_malformed_token_400s_not_410():
    """Garbage tokens are the CLIENT's bug (400) — distinct from the 410
    an expired-but-well-formed token earns, so a retry loop cannot
    hammer a permanently-bad token through the relist path."""
    store = MemStore()
    srv = APIServer(store).start()
    try:
        store.create(NODES, "n0", make_node("n0"))
        assert _get_code(
            f"{srv.url}/apis/{NODES}?limit=1&continue=%21%21not-b64%21%21"
        ) == 400
    finally:
        srv.close()


@pytest.mark.parametrize("native", CORES)
@pytest.mark.parametrize("wire", WIRES)
def test_token_across_wal_crash_recovery_410s(tmp_path, native, wire):
    """THE renumbering hazard: recovery's snapshot load renumbers seqs
    densely, so a pre-crash token held across deletions' seq gaps would
    silently SKIP survivors if resumed by raw cursor. The generation
    stamp turns that into a loud 410 — and the fresh walk is complete."""
    d = str(tmp_path / "wal")
    store = MemStore(persistence=d, native=native, wal_wire=wire)
    srv = APIServer(store).start()
    try:
        for i in range(10):
            store.create(NODES, f"n{i:02d}", make_node(f"n{i:02d}"))
        # seq gaps BEFORE the cursor position: after renumbering, the
        # raw cursor would land past n06/n07 and skip them
        store.delete(NODES, "n02")
        store.delete(NODES, "n03")
        first = _get_json(f"{srv.url}/apis/{NODES}?limit=4")
        tok = first["continue"]
        assert [it["key"] for it in first["items"]] == [
            "n00", "n01", "n04", "n05",
        ]
    finally:
        srv.close()
        store.close()

    store2 = MemStore(persistence=d, native=native, wal_wire=wire)
    srv2 = APIServer(store2).start()
    try:
        # the rv check alone would ADMIT this token (nothing compacted):
        # only the generation stamp knows the seqs renumbered
        assert first["resourceVersion"] >= store2.compacted_through
        assert _get_code(
            f"{srv2.url}/apis/{NODES}?limit=4&continue={tok}"
        ) == 410
        keys, _rv, _pages = _walk_pages(srv2.url, NODES, 4)
        assert keys == [
            "n00", "n01", "n04", "n05", "n06", "n07", "n08", "n09",
        ]
    finally:
        srv2.close()
        store2.close()


def test_replica_resync_bumps_list_generation():
    """A replica snapshot load renumbers seqs — the generation must
    change so outstanding follower-read tokens 410; ordinary writes
    leave it alone (tokens survive any amount of normal churn)."""
    store = MemStore()
    g0 = store.list_generation
    store.create(NODES, "n0", make_node("n0"))
    obj, rv = store.get(NODES, "n0")
    store.update(NODES, "n0", obj, expect_rv=rv)
    store.delete(NODES, "n0")
    assert store.list_generation == g0

    follower = MemStore(follower=True)
    f0 = follower.list_generation
    follower.load_replica_snapshot(
        [(NODES, "n0", make_node("n0"), 3)], 3,
    )
    assert follower.list_generation != f0


def test_continue_token_codec_round_trip():
    tok = codec.encode_continue(123, 45, 678, 910)
    assert codec.decode_continue(tok) == (123, 45, 678, 910)
    with pytest.raises(ValueError, match="malformed continue token"):
        codec.decode_continue("!!!")
    with pytest.raises(ValueError, match="malformed continue token"):
        # well-formed base64, wrong version tag
        import base64

        codec.decode_continue(
            base64.urlsafe_b64encode(b"v9:1:2:3:4").decode().rstrip("=")
        )
    with pytest.raises(ValueError, match="malformed continue token"):
        # a pre-bound (4-field) token is malformed now, not misread
        import base64

        codec.decode_continue(
            base64.urlsafe_b64encode(b"v1:1:2:3").decode().rstrip("=")
        )


# -------------------------------------------- RemoteStore relist behaviors

def test_remote_mid_walk_410_restarts_one_fresh_walk():
    """A token that expires BETWEEN pages (compaction overtook the
    snapshot mid-walk) restarts exactly one fresh walk inside
    RemoteStore.list — the reflector sees a complete result, not an
    exception, and the stats count both walks' pages."""
    store = MemStore(history=4)
    srv = APIServer(store).start()
    try:
        for i in range(12):
            store.create(NODES, f"n{i:02d}", make_node(f"n{i:02d}"))
        rs = RemoteStore(srv.url, wire="json")
        rs.LIST_PAGE_LIMIT = 4
        inner = rs._list_page_request
        state = {"calls": 0}

        def churn_after_first_page(path):
            state["calls"] += 1
            if state["calls"] == 2:      # first continue-bearing request
                for _ in range(8):
                    obj, rv = store.get(NODES, "n00")
                    store.update(NODES, "n00", obj, expect_rv=rv)
            return inner(path)

        rs._list_page_request = churn_after_first_page
        items, rv = rs.list(NODES)
        assert [k for k, _ in items] == sorted(
            f"n{i:02d}" for i in range(12)
        )
        assert rv == store.list(NODES)[1]
        # page 1, the 410'd page 2, then a fresh 3-page walk
        assert rs.last_relist["pages"] == 3
        assert state["calls"] >= 5
    finally:
        srv.close()


def test_remote_list_retry_budget_and_reason_counter():
    """List-path transport failures retry under their own capped-jitter
    budget and land in apiserver_client_reconnects_total{reason="list"}
    — then surface as RemoteUnavailableError, not a hang."""
    rs = RemoteStore("http://127.0.0.1:1", wire="json")
    rs.LIST_RETRY_BUDGET = 2
    rs.BACKOFF_BASE_S = 0.01
    with pytest.raises(RemoteUnavailableError):
        rs.list(NODES)
    assert rs.reconnect_counts.get("list") == 2
    assert 'reason="list"' in rs.reconnect_metrics_text()


# ------------------------------------- bounded staleness + chained fencing

def _mk_leader():
    ls = MemStore()
    leader = APIServer(ls)
    leader.attach_replication(
        LeaderLease(ls, "test-leader", lease_duration_s=5.0)
    )
    leader.start()
    return ls, leader


def _mk_follower(leader_url, index, upstream_url=""):
    fs = MemStore(follower=True)
    srv = APIServer(fs)
    rep = FollowerReplicator(
        fs, leader_url, self_url="", replica_index=index,
        poll_timeout_s=0.5, elect=False, upstream_url=upstream_url,
    )
    srv.attach_replication(rep)
    srv.start()
    return fs, srv, rep


def _wait_until(fn, timeout=10.0, what=""):
    import time

    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if fn():
            return
        time.sleep(0.05)
    pytest.fail(f"timeout waiting for {what}")


def test_rv0_list_lag_surfaced_and_bounded():
    """rv=0 on a follower serves the local cache bit-identically to an
    exact read of the same state, surfaces the replication lag as
    ``store_list_lag_records`` (a series the leader never emits — the
    sentinel's list-lag rule stays dormant there), and 503s a client
    whose declared maxLagRecords the lag exceeds."""
    ls, leader = _mk_leader()
    fs, fsrv, frep = _mk_follower(leader.url, 1)
    try:
        for i in range(5):
            ls.create(NODES, f"n{i}", make_node(f"n{i}"))
        _wait_until(
            lambda: fs.resource_version >= ls.resource_version,
            what="follower convergence",
        )
        # the injected apply stall: tailing halted with shipped records
        # unapplied — status() reports the stuck lag
        frep.close()
        frep.lag_records = 7

        body0 = urllib.request.urlopen(
            f"{fsrv.url}/apis/{NODES}?resourceVersion=0"
        ).read()
        exact = urllib.request.urlopen(f"{fsrv.url}/apis/{NODES}").read()
        assert body0 == exact
        met = urllib.request.urlopen(f"{fsrv.url}/metrics").read().decode()
        assert "store_list_lag_records 7" in met
        lmet = urllib.request.urlopen(
            f"{leader.url}/metrics"
        ).read().decode()
        assert "store_list_lag_records" not in lmet

        assert _get_code(
            f"{fsrv.url}/apis/{NODES}?resourceVersion=0&maxLagRecords=3"
        ) == 503
        assert _get_code(
            f"{fsrv.url}/apis/{NODES}?resourceVersion=0&maxLagRecords=7"
        ) == 200
    finally:
        fsrv.close()
        leader.close()


def test_list_lag_sentinel_rule_shape():
    """The list-lag alert reads its threshold off the rule table (AL001)
    and watches the follower-only series — dormant wherever the series
    is absent (leader/unreplicated apiservers)."""
    rules = {r.name: r for r in default_rules()}
    r = rules["list-lag"]
    assert r.series == "store_list_lag_records"
    assert r.threshold == 500.0 and r.direction == "above"
    assert r.for_intervals >= 2


def test_chained_follower_and_stale_epoch_fence():
    """A chained follower (B tails A tails leader) converges through the
    chain, the leader's log egress stays ONE follower's worth, and a
    chain link shipping a FENCED epoch is refused loudly (StaleEpochError
    → fall back to tailing the leader) — then convergence resumes."""
    ls, leader = _mk_leader()
    fa_store, fa_srv, fa_rep = _mk_follower(leader.url, 1)
    fb_store, fb_srv, fb_rep = _mk_follower(
        leader.url, 2, upstream_url=fa_srv.url,
    )
    try:
        for i in range(10):
            ls.create(NODES, f"n{i:02d}", make_node(f"n{i:02d}"))
        _wait_until(
            lambda: fb_store.resource_version >= ls.resource_version,
            what="chain convergence",
        )
        st = fb_rep.status()
        assert st["upstream"] == fa_srv.url.rstrip("/")
        assert st["upstreamFallbacks"] == 0
        # one stream off the leader regardless of two followers
        assert leader.metrics.replication_bytes_total("log") > 0
        assert fa_srv.metrics.replication_bytes_total("log") > 0

        # fence: B has observed a fresher epoch than the chain serves —
        # the next ship off A must be refused, dropping B to the leader
        with fb_rep._mu:
            fb_rep.observed_epoch += 1
        _wait_until(
            lambda: fb_rep.status()["upstreamFallbacks"] >= 1,
            what="stale-epoch fallback",
        )
        assert fb_rep.stale_refusals >= 1
        assert fb_rep.status()["upstream"] == ""

        # un-fence (the real epoch catches up) and prove liveness
        with fb_rep._mu:
            fb_rep.observed_epoch -= 1
        for i in range(10, 15):
            ls.create(NODES, f"n{i:02d}", make_node(f"n{i:02d}"))
        _wait_until(
            lambda: fb_store.resource_version >= ls.resource_version,
            timeout=15.0, what="post-fallback convergence",
        )
        met = urllib.request.urlopen(f"{fb_srv.url}/metrics").read().decode()
        assert "store_replication_upstream_fallbacks_total" in met
    finally:
        fb_srv.close()
        fa_srv.close()
        leader.close()
