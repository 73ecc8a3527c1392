"""Control-plane semantics suite (PR 3): compound short-circuit ordering,
session-table thread safety, metadata/capability leases (expiry under
clock skew, background renewal, cross-session invalidation), truncate
punch + unlink reclaim, stat envelope hygiene, and the round-trip budgets
the compound+lease path is built to hit (cycle ≤ 2, warm open == 0,
control bytes < 1% of data-plane bytes — the paper's design point).
"""
import threading
import time

import pytest

from repro.core.client import ROS2Client
from repro.core.control_plane import ControlPlane
from repro.core.data_plane import MemoryRegistry
from repro.core.dfs import BLOCK, DFSError, DFSMeta
from repro.core.media import make_nvme_array
from repro.core.metadata_cache import MetadataCache
from repro.core.object_store import ObjectStore


def make_cp(meta_lease_s=30.0):
    store = ObjectStore(make_nvme_array(2))
    reg = MemoryRegistry("srv")
    cp = ControlPlane(store, reg, {"t": "s"}, meta_lease_s=meta_lease_s)
    cp.bind_dfs(DFSMeta(store))
    return cp, reg


# ---------------------------------------------------------------------------
# Compound RPC semantics


def test_compound_short_circuit_ordering():
    cp, _ = make_cp()
    sid = cp.rpc("connect", tenant="t", secret="s")["session_id"]
    r = cp.rpc("compound", session_id=sid, ops=[
        {"method": "create", "args": {"path": "/a"}},
        {"method": "lookup", "args": {"path": "/missing"}},
        {"method": "create", "args": {"path": "/b"}},   # must NOT run
    ])
    assert r["ok"]                       # the compound itself executed
    assert len(r["results"]) == 2        # stopped AT the failing op
    assert r["results"][0]["ok"] and r["results"][0]["path"] == "/a"
    assert not r["results"][1]["ok"] and "ENOENT" in r["results"][1]["error"]
    assert r["completed"] == 1
    # ordering respected, short-circuit honored: /b was never created
    assert not cp.rpc("lookup", session_id=sid, path="/b")["ok"]
    assert cp.rpc("lookup", session_id=sid, path="/a")["ok"]


def test_compound_connect_establishes_implicit_session():
    cp, reg = make_cp()
    mr = reg.register(1024, "t")
    before = cp.rpc_count
    r = cp.rpc("compound", ops=[
        {"method": "connect", "args": {"tenant": "t", "secret": "s"}},
        {"method": "mount", "args": {"pool": "p", "container": "c"}},
        {"method": "grant_rkey", "args": {"region_id": mr.region_id}},
    ])
    assert cp.rpc_count == before + 1            # ONE round-trip, three ops
    assert r["completed"] == 3
    assert r["session_id"] == r["results"][0]["session_id"]
    assert r["results"][1]["mount_id"] >= 1
    assert r["results"][2]["rkey"]
    assert cp.compound_ops == 3


def test_compound_rejects_nesting_and_unknown_methods():
    cp, _ = make_cp()
    r = cp.rpc("compound", ops=[{"method": "compound", "args": {"ops": []}}])
    assert not r["results"][0]["ok"]
    r = cp.rpc("compound", ops=[{"method": "bogus", "args": {}}])
    assert not r["results"][0]["ok"] and r["completed"] == 0


# ---------------------------------------------------------------------------
# Session-table thread safety (the _sessions race fix)


def test_concurrent_connect_disconnect_stress():
    cp, _ = make_cp()
    errors = []

    def churn():
        try:
            for _ in range(200):
                r = cp.rpc("connect", tenant="t", secret="s")
                assert r["ok"]
                sid = r["session_id"]
                # a reader between connect and disconnect (_session path)
                assert cp.rpc("readdir", session_id=sid, path="/")["ok"]
                assert cp.rpc("disconnect", session_id=sid)["ok"]
        except Exception as e:           # noqa
            errors.append(e)

    threads = [threading.Thread(target=churn) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert cp._sessions == {}            # every session torn down cleanly


# ---------------------------------------------------------------------------
# Leases: expiry under clock skew, renewal, invalidation


def test_meta_lease_expires_early_under_skew_margin():
    cp, _ = make_cp(meta_lease_s=10.0)
    sid = cp.rpc("connect", tenant="t", secret="s")["session_id"]
    now = [0.0]
    cache = MetadataCache(cp, sid, skew_margin=0.25, clock=lambda: now[0])
    cache.put_meta("/x", {"oid": 5, "size": 0}, ttl_s=10.0)
    now[0] = 7.4                         # inside the skew-guarded window
    assert cache.get_meta("/x") is not None
    now[0] = 7.6                         # nominal lease has 2.4s left, but
    assert cache.get_meta("/x") is None  # the skew margin already killed it
    assert cache.stats.expiries == 1


def test_rkey_renewal_extends_lease_in_place():
    cp, reg = make_cp()
    sid = cp.rpc("connect", tenant="t", secret="s")["session_id"]
    mr = reg.register(256, "t")
    g = cp.rpc("grant_rkey", session_id=sid, region_id=mr.region_id,
               ttl_s=0.05)
    token = g["rkey"]
    now = [0.0]
    cache = MetadataCache(cp, sid, skew_margin=0.25, clock=lambda: now[0])
    cache.put_rkey(token, ttl_s=0.05)
    expires_at_grant = reg._rkeys[token].expires_at
    now[0] = 0.04                        # inside the margin -> renew due
    assert not cache.rkey_fresh(token)
    assert cache.renew_due() == 1
    assert cache.rkey_fresh(token)       # fresh again, SAME token
    assert reg._rkeys[token].expires_at > expires_at_grant   # in place
    # revoked keys are not resurrectable by renewal
    cp.rpc("revoke_rkey", session_id=sid, rkey=token)
    now[0] = 0.08                        # back inside the margin
    assert cache.renew_due() == 0        # server refuses the renewal
    assert not cache.rkey_fresh(token)   # dropped from the lease watch


def test_background_renewal_keeps_data_plane_alive():
    """With the lease layer, a short-TTL rkey is renewed BEFORE expiry and
    the data plane never observes a lapsed capability."""
    c = ROS2Client(mode="host", transport="rdma", rkey_ttl_s=0.1,
                   renew_interval_s=0.02, scrub_interval_s=None)
    fd = c.open("/f", create=True)
    c.pwrite(fd, b"x" * 1024, 0)
    time.sleep(0.3)                      # several TTLs of idle time
    assert c.cache.stats.rkey_renewals > 0
    c.pwrite(fd, b"y" * 1024, 0)         # would AccessError without renewal
    assert c.pread(fd, 1024, 0) == b"y" * 1024
    c.close()


def test_dpu_housekeeping_runs_renewal():
    c = ROS2Client(mode="dpu", transport="rdma", rkey_ttl_s=0.1,
                   renew_interval_s=0.02, scrub_interval_s=None)
    fd = c.open("/f", create=True)
    time.sleep(0.25)
    assert c.dpu.housekeeping_runs > 0   # renewal ran on an Arm core
    c.pwrite(fd, b"z" * 512, 0)
    assert c.pread(fd, 512, 0) == b"z" * 512
    c.close()


def test_cross_session_invalidation():
    """A mutation by session B recalls session A's lease on the path."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    # second session with its own cache + DFS client on the same server
    from repro.core.dfs import DFSClient
    r = c.control.rpc("connect", tenant="default", secret="secret")
    sid_b = r["session_id"]
    cache_b = MetadataCache(c.control, sid_b)
    dfs_b = DFSClient(c.control, c.io, sid_b, cache=cache_b)

    fd = c.open("/shared", create=True)
    c.pwrite(fd, b"a" * 100, 0)
    c.close_fd(fd)
    assert c.stat("/shared")["size"] == 100      # A holds a lease now
    inv_before = c.cache.stats.invalidations

    dfs_b.truncate("/shared", 10)                # B mutates -> lease recall
    assert c.cache.stats.invalidations == inv_before + 1
    st = c.stat("/shared")                       # A refetches, no staleness
    assert st["size"] == 10
    # and the other direction: A's flush recalls B's lease
    b_inv = cache_b.stats.invalidations
    fd = c.open("/shared")
    c.pwrite(fd, b"b" * 500, 0)
    c.close_fd(fd)                               # piggybacked set_size
    assert cache_b.stats.invalidations > b_inv
    assert dfs_b.stat("/shared")["size"] == 500
    c.close()


def test_cross_tenant_renewal_does_not_touch_the_lease():
    """The tenant check must run BEFORE the lease is extended: a denied
    renewal that still moved expires_at would let any tenant keep a
    foreign capability alive."""
    store = ObjectStore(make_nvme_array(2))
    reg = MemoryRegistry("srv")
    cp = ControlPlane(store, reg, {"a": "sa", "b": "sb"})
    cp.bind_dfs(DFSMeta(store))
    sid_a = cp.rpc("connect", tenant="a", secret="sa")["session_id"]
    sid_b = cp.rpc("connect", tenant="b", secret="sb")["session_id"]
    mr = reg.register(64, "a")
    tok = cp.rpc("grant_rkey", session_id=sid_a, region_id=mr.region_id,
                 ttl_s=1.0)["rkey"]
    expires = reg._rkeys[tok].expires_at
    r = cp.rpc("renew_rkey", session_id=sid_b, rkey=tok, ttl_s=9999.0)
    assert not r["ok"] and "protection" in r["error"]
    assert reg._rkeys[tok].expires_at == expires     # lease untouched


def test_create_of_existing_path_recalls_no_leases():
    """create-as-open of an existing file is a namespace no-op; other
    sessions' leases on the path stay valid (warm opens stay free)."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    r = c.control.rpc("connect", tenant="default", secret="secret")
    from repro.core.dfs import DFSClient
    sid_b = r["session_id"]
    cache_b = MetadataCache(c.control, sid_b)
    dfs_b = DFSClient(c.control, c.io, sid_b, cache=cache_b)
    fd = c.open("/keep", create=True)
    c.close_fd(fd)
    inv = c.cache.stats.invalidations
    fd_b = dfs_b.open("/keep", create=True)          # no-op create
    assert c.cache.stats.invalidations == inv        # A's lease survives
    n = c.control.rpc_count
    fd = c.open("/keep")                             # still 0 round-trips
    assert c.control.rpc_count == n
    dfs_b.close(fd_b)
    c.close()


def test_write_after_unlink_is_stale_not_a_leak():
    """A write on an fd that outlived its unlink must not resurrect an
    orphan object (extents nobody can ever reclaim) — it fails ESTALE-
    style, and close_fd afterwards does not raise."""
    from repro.core.object_store import StorageError
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    base = _used(c)
    fd = c.open("/orphan", create=True)
    c.pwrite(fd, b"d" * 4096, 0)
    c.unlink("/orphan")
    with pytest.raises(StorageError):
        c.pwrite(fd, b"late" * 1024, 0)
    assert _used(c) == base                          # nothing leaked
    c.close_fd(fd)                                   # must not raise
    c.close()


def test_flush_tolerates_enoent_and_flushes_the_rest():
    """A second session unlinking a file mid-delegation must not wedge the
    flush of OTHER files' pending sizes."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    fd1 = c.open("/f1", create=True)
    fd2 = c.open("/f2", create=True)
    c.pwrite(fd1, b"a" * 100, 0)
    c.pwrite(fd2, b"b" * 200, 0)
    # another session unlinks /f1 underneath our delegation
    sid_b = c.control.rpc("connect", tenant="default",
                          secret="secret")["session_id"]
    assert c.control.rpc("unlink", session_id=sid_b, path="/f1")["ok"]
    assert c.dfs.flush_meta() == 1                   # /f2 still landed
    assert c.stat("/f2")["size"] == 200
    c.close()


# ---------------------------------------------------------------------------
# Truncate punch + unlink reclaim (control-path correctness fixes)


def _used(c):
    for d in c.devices:
        d.writeback()
    return sum(d.used_bytes() for d in c.devices)


def test_truncate_shrinks_and_punches_blocks():
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    base = _used(c)
    fd = c.open("/t", create=True)
    data = bytes(range(256)) * ((3 * BLOCK) // 256)
    c.pwrite(fd, data, 0)
    c.fsync(fd)
    assert _used(c) - base == 3 * BLOCK * 2          # 2 replicas
    half = BLOCK + BLOCK // 2
    ent = c.truncate("/t", half)
    assert ent["size"] == half
    assert c.stat("/t")["size"] == half              # exact, not max()'d
    assert _used(c) - base == half * 2               # blocks punched
    # re-grow: punched range reads zeros, never resurrected bytes
    c.pwrite(fd, b"Q", 3 * BLOCK - 1)
    got = c.pread(fd, 3 * BLOCK, 0)
    assert got[:half] == data[:half]
    assert got[half:-1] == bytes(3 * BLOCK - 1 - half)
    assert got[-1:] == b"Q"
    c.close_fd(fd)
    c.close()


def test_truncate_punches_unflushed_delegated_writes():
    """Regression: with the size delegation the server's namespace size
    lags the written extents — truncate must punch by what the backing
    object HOLDS, not by the (stale) recorded size."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    fd = c.open("/lag", create=True)
    c.pwrite(fd, b"z" * (2 * BLOCK + 5), 0)   # size still delegated locally
    c.truncate("/lag", BLOCK)                 # server thinks size == 0 here
    assert c.stat("/lag")["size"] == BLOCK
    assert c.pread(fd, BLOCK + 5, 0) == b"z" * BLOCK + bytes(5)
    assert _used(c) == BLOCK * 2              # blocks 1,2 punched anyway
    c.close()


def test_truncate_grow_sets_exact_size():
    c = ROS2Client(mode="dpu", transport="rdma", scrub_interval_s=None)
    fd = c.open("/g", create=True)
    c.pwrite(fd, b"x" * 10, 0)
    c.truncate("/g", 1000)
    assert c.stat("/g")["size"] == 1000
    assert c.pread(fd, 990, 10) == bytes(990)        # hole reads zeros
    c.close()


def test_unlink_reclaims_engine_capacity():
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    base = _used(c)
    fd = c.open("/u", create=True)
    c.pwrite(fd, b"d" * (2 * BLOCK), 0)
    c.close_fd(fd)
    assert _used(c) - base == 2 * BLOCK * 2
    c.unlink("/u")
    assert _used(c) == base                          # capacity reclaimed
    with pytest.raises(DFSError):
        c.dfs.open("/u")
    # recreate: a fresh object, no stale extents
    fd = c.open("/u", create=True)
    assert c.pread(fd, 100, 0) == bytes(100)
    c.close()


# ---------------------------------------------------------------------------
# Envelope hygiene + round-trip budgets


@pytest.mark.parametrize("mode", ["host", "dpu"])
def test_stat_returns_only_metadata(mode):
    c = ROS2Client(mode=mode, transport="rdma", scrub_interval_s=None)
    fd = c.open("/s", create=True)
    c.pwrite(fd, b"m" * 42, 0)
    st = c.stat("/s")
    assert set(st) == {"oid", "is_dir", "size", "path"}   # no envelope leak
    assert st["size"] == 42 and st["path"] == "/s"
    assert st["is_dir"] is False
    ent = c.truncate("/s", 7)                        # same audit for others
    assert set(ent) == {"oid", "is_dir", "size"}
    c.close()


@pytest.mark.parametrize("mode", ["host", "dpu"])
def test_cycle_round_trip_budget(mode):
    """open→pwrite×3→close ≤ 2 RPCs (cold), warm-cache open at 0."""
    c = ROS2Client(mode=mode, transport="rdma", scrub_interval_s=None)
    n0 = c.control.rpc_count
    fd = c.open("/cyc", create=True)
    for i in range(3):
        c.pwrite(fd, b"w" * 4096, i * 4096)
    c.close_fd(fd)
    assert c.control.rpc_count - n0 <= 2             # vs ≥4 on legacy
    n1 = c.control.rpc_count
    fd = c.open("/cyc")                              # warm-cache open
    assert c.control.rpc_count == n1
    c.close_fd(fd)                                   # nothing pending: free
    assert c.control.rpc_count == n1
    c.close()


def test_control_bytes_stay_under_one_percent_of_data():
    """The paper's design point, measured end to end INCLUDING bring-up:
    compound + leases keep control traffic <1% of data-plane bytes."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    fd = c.open("/ratio", create=True)
    chunk = bytes(1 * BLOCK)
    for i in range(8):
        c.pwritev(fd, [chunk], i * BLOCK)
    for i in range(8):
        c.pread(fd, BLOCK, i * BLOCK)
    c.close_fd(fd)
    data_bytes = c.io.stats.bytes_moved
    assert data_bytes >= 16 * BLOCK
    assert c.control.rpc_bytes < 0.01 * data_bytes
    assert c.control.rpc_count <= 4      # bring-up + open + flush (+ slack)
    c.close()
