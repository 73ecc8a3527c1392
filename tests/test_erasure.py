"""Erasure-coded redundancy: the ec(k,p) pool-map class, GF(256)
Reed-Solomon striping of k data + p parity cells across distinct targets,
k+1 ack quorum with background stragglers, degraded reads reconstructing
from any k clean survivors, dirty-cell ledgers, and marker-driven rebuild
that regenerates ONLY the lost cells through the heal throttle."""
import sys
import threading

import numpy as np
import pytest

from repro.core.client import ROS2Client
from repro.core.dfs import AKEY, BLOCK
from repro.core.object_store import (EC_DIRTY_AKEY, EC_STRIPE_BYTES,
                                     StorageError, placement_order)


def _payload(n, seed=0):
    return bytes(np.random.default_rng(seed).integers(0, 256, n,
                                                      dtype=np.uint8))


def _client(n_targets=4, ec=(2, 1), **kw):
    kw.setdefault("scrub_interval_s", None)
    return ROS2Client(mode="host", transport="rdma", n_targets=n_targets,
                      ec=ec, **kw)


def _flush(c):
    for t in c.cluster.targets:
        for d in t.store.devices:
            if d.alive:
                d.writeback()


def _media_bytes(c):
    _flush(c)
    return sum(d.bytes_written for t in c.cluster.targets
               for d in t.store.devices)


def _cells_by_target(c):
    """{tid: {(oid, dkey, cell_index), ...}} straight from extent state."""
    _k, _p, cs = c.io._ec
    out = {}
    for tid, cont in c.ccontainer._per_target.items():
        for oid, obj in list(cont._objects.items()):
            with obj._lock:
                items = {dk: list(exts) for (dk, ak), exts
                         in obj._extents.items() if ak == AKEY}
            for dk, exts in items.items():
                for e in exts:
                    out.setdefault(tid, set()).add((oid, dk, e.offset // cs))
    return out


def _dirty_union(c, n_cells):
    """The fleet-wide dirty-cell ledger union: {(oid, dkey): {cells}}."""
    out = {}
    for cont in c.ccontainer._per_target.values():
        for oid, obj in list(cont._objects.items()):
            for dk in obj.dkeys(EC_DIRTY_AKEY):
                marks = obj.fetch(dk, EC_DIRTY_AKEY, 0, n_cells)
                cells = {i for i, b in enumerate(marks) if b}
                if cells:
                    out.setdefault((oid, dk), set()).update(cells)
    return out


def _assert_rings_whole(c):
    """Leak check: once writebacks land, every donated lease has dropped,
    every ring slot is back on the free list, no rkey grant outlived its
    op (the fault-suite invariants, EC edition)."""
    import time
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        _flush(c)
        if all(not s.ring.donated_slots() for s in c.io.sessions.values()):
            break
        time.sleep(0.005)
    for s in c.io.sessions.values():
        assert not s.ring.donated_slots(), "donated slot leases leaked"
        with s.ring._cv:
            assert sorted(s.ring._free) == list(range(s.ring.n_slots))
    assert not c.client_registry._rkeys, "client rkey grant leaked"


# ---------------------------------------------------------------------------
# redundancy class plumbing


def test_pool_map_serves_ec_class_and_router_adopts():
    c = _client()
    m = c.cluster.pool_map.describe()
    assert m["redundancy"]["pool0/cont0"]["ec"] == {
        "k": 2, "p": 1, "cell_bytes": EC_STRIPE_BYTES // 2}
    assert c.io._ec == (2, 1, EC_STRIPE_BYTES // 2)
    # EC forces single-copy cells: redundancy comes from parity, not
    # replica fan-out (the media-byte economics depend on it)
    assert c.ccontainer.params.get("replication") == 1
    c.close()


def test_ec_rejects_bad_geometry():
    with pytest.raises(ValueError):
        _client(n_targets=2, ec=(2, 1))       # n < k + p
    with pytest.raises(ValueError):
        _client(n_targets=4, ec=(3, 1))       # stripe not divisible by k


# ---------------------------------------------------------------------------
# healthy-path striping


@pytest.mark.parametrize("inline_encryption", [False, True])
def test_ec_roundtrip_bit_exact(inline_encryption):
    """Aligned, unaligned (read-modify-write through parity), cell-
    boundary-crossing and vectored I/O all roundtrip bit-exactly — with
    inline encryption the parity is computed over the MEDIA image, so
    ciphertext economics and plaintext fidelity hold at once."""
    c = _client(inline_encryption=inline_encryption)
    cs = c.io._ec[2]
    fd = c.open("/f", create=True)
    shadow = bytearray(_payload(3 * BLOCK, 0))      # materialize: hole-free
    c.pwrite(fd, bytes(shadow), 0)
    writes = [(0, 2 * BLOCK, 1),                    # stripe-aligned
              (cs - 7, 15, 2),                      # crosses a cell seam
              (BLOCK + 100, cs, 3),                 # partial: RMW parity
              (2 * BLOCK + 5, BLOCK - 5, 4)]        # tail fragment
    for off, ln, seed in writes:
        data = _payload(ln, seed)
        c.pwrite(fd, data, off)
        shadow[off:off + ln] = data
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    # vectored both ways across a stripe boundary
    data = _payload(BLOCK, 5)
    c.pwritev(fd, [data[:100], data[100:]], BLOCK - 50)
    shadow[BLOCK - 50:2 * BLOCK - 50] = data
    parts = c.preadv(fd, [200, BLOCK - 200], BLOCK - 50)
    assert b"".join(parts) == data
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    ctr = c.io.data_path_counters()               # drains stragglers
    assert ctr["ec"]["degraded_reads"] == 0       # healthy: no decode
    assert not c.io._ec_pending
    _assert_rings_whole(c)
    c.close()


def test_ec_cells_land_on_distinct_targets_in_placement_order():
    c = _client()
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(4 * BLOCK, 7), 0)
    _flush(c)
    by_target = _cells_by_target(c)
    placed = {}                                   # (oid, dkey) -> {cell: tid}
    for tid, cells in by_target.items():
        for oid, dk, cell in cells:
            assert (oid, dk) not in placed or cell not in placed[(oid, dk)]
            placed.setdefault((oid, dk), {})[cell] = tid
    n = len(c.cluster.targets)
    for (oid, dk), cells in placed.items():
        assert sorted(cells) == list(range(k + p))         # all k+p present
        assert len(set(cells.values())) == k + p           # distinct targets
        order = placement_order(n, oid, dk)
        for cell, tid in cells.items():
            assert tid == order[cell]                      # slot == identity
    c.close()


def test_ec_media_bytes_half_of_replication3_at_equal_redundancy():
    """ec(2,1) and replication-3 both survive any single failure, but the
    stripe writes 1.5x the logical bytes where the replica fan-out writes
    3x — the media-byte economics that justify the parity math."""
    span = 8 * BLOCK
    data = _payload(span, 11)
    cec = _client()
    fd = cec.open("/f", create=True)
    cec.pwrite(fd, data, 0)
    ec_bytes = _media_bytes(cec)
    cec.close()
    crep = ROS2Client(mode="host", transport="rdma", n_targets=4,
                      replication=3, scrub_interval_s=None)
    fd = crep.open("/f", create=True)
    crep.pwrite(fd, data, 0)
    rep_bytes = _media_bytes(crep)
    crep.close()
    assert ec_bytes >= 1.5 * span                 # k data + p parity cells
    assert rep_bytes >= 3 * span                  # three full replicas
    assert ec_bytes <= 0.6 * rep_bytes


# ---------------------------------------------------------------------------
# degraded reads


def test_ec_degraded_read_is_bit_exact_and_counted():
    c = _client()
    fd = c.open("/f", create=True)
    data = _payload(3 * BLOCK + 12345, 21)
    c.pwrite(fd, data, 0)
    c.cluster.fail_target(2)
    assert c.pread(fd, len(data), 0) == data      # any k survivors suffice
    ctr = c.io.data_path_counters()
    assert ctr["ec"]["degraded_reads"] >= 1
    assert ctr["ec"]["reconstructions"] >= 1
    _assert_rings_whole(c)
    c.close()


def test_ec_unrecoverable_below_k_survivors():
    """More than p failures is a hard error on BOTH paths — the write
    refuses before moving a byte (no torn stripe), the read refuses
    instead of fabricating bytes."""
    c = _client(n_targets=3)                      # every stripe uses all 3
    fd = c.open("/f", create=True)
    data = _payload(2 * BLOCK, 31)
    c.pwrite(fd, data, 0)
    c.cluster.fail_target(1)
    c.cluster.fail_target(2)
    with pytest.raises(StorageError):
        c.pwrite(fd, _payload(BLOCK, 32), 0)
    with pytest.raises(StorageError):
        c.pread(fd, len(data), 0)
    _assert_rings_whole(c)                        # error exits stay leak-free
    c.close()


# ---------------------------------------------------------------------------
# rebuild: dirty markers -> regenerate exactly the lost cells


def test_ec_outage_writes_mark_dirty_and_rebuild_regenerates_only_lost():
    c = _client()
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    base = _payload(6 * BLOCK, 41)
    c.pwrite(fd, base, 0)
    c.cluster.fail_target(1)
    fresh = _payload(4 * BLOCK, 42)
    c.pwrite(fd, fresh, 0)                        # cells homed on 1 dropped
    shadow = fresh + base[len(fresh):]
    dirty = _dirty_union(c, k + p)
    lost = sum(len(v) for v in dirty.values())
    assert lost >= 1                              # the outage marked cells
    n = len(c.cluster.targets)
    for (oid, dk), cells in dirty.items():        # ...and ONLY cells homed
        order = placement_order(n, oid, dk)       #    on the down target
        assert {order[i] for i in cells} == {1}
    before = c.cluster.stats.ec_rebuilt_cells
    c.cluster.recover_target(1)
    assert c.cluster.stats.ec_rebuilt_cells - before == lost
    assert not _dirty_union(c, k + p)             # ledgers cleared + punched
    for cont in c.ccontainer._per_target.values():
        for _oid, obj in list(cont._objects.items()):
            assert not obj.dkeys(EC_DIRTY_AKEY)
    degraded = c.io.data_path_counters()["ec"]["degraded_reads"]
    assert c.pread(fd, len(shadow), 0) == shadow  # healthy read, no decode
    ctr = c.io.data_path_counters()
    assert ctr["ec"]["degraded_reads"] == degraded
    assert ctr["ec"]["rebuilt_cells"] == c.cluster.stats.ec_rebuilt_cells
    c.close()


class _FakePacer:
    idle_aware = True

    def __init__(self, budgets, max_deferrals=2):
        self.budgets = list(budgets)
        self.max_deferrals = max_deferrals

    def idle_budget(self):
        return self.budgets.pop(0) if self.budgets else 0


def test_ec_rebuild_heals_through_throttle():
    """Cell regeneration rides the same idle-aware heal budget as replica
    re-replication: under sustained foreground load it DEFERS (counted),
    then the starvation floor drives it to completion anyway."""
    c = _client()
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(2 * BLOCK, 51), 0)
    c.cluster.fail_target(1)
    data = _payload(2 * BLOCK, 52)
    c.pwrite(fd, data, 0)
    assert _dirty_union(c, 3)
    c.cluster.heal_pause_s = 0.0005
    c.cluster.heal_pacer = _FakePacer([], max_deferrals=2)
    c.cluster.recover_target(1)
    assert c.cluster.stats.ec_rebuilt_cells >= 1
    assert c.cluster.stats.heal_deferrals >= 2
    assert c.cluster.stats.heal_floor_grants >= 1
    assert c.pread(fd, len(data), 0) == data
    c.close()


# ---------------------------------------------------------------------------
# delta-parity RMW: partial writes move deltas, not stripes


def _oid(c):
    return sorted({o for cont in c.ccontainer._per_target.values()
                   for o in cont._objects})[0]


def test_ec_delta_kernel_matches_full_reencode_sweep():
    """Deterministic stand-in for the hypothesis property (which skips
    when hypothesis is absent): across every shipped geometry, xoring
    ec_parity_delta of the touched cells into the old parity equals a
    full re-encode, for single-cell, multi-cell and sub-window
    overwrites."""
    from repro.kernels.rs_parity import ec_parity_delta
    from repro.kernels.rs_parity.ref import rs_encode_np
    rng = np.random.default_rng(0)
    for k, p in [(2, 1), (4, 2), (8, 3)]:
        size = 193
        cells = rng.integers(0, 256, (k, size), dtype=np.uint8)
        parity = rs_encode_np(cells, p)
        for touched, lo, hi in [([0], 0, size),           # whole cell
                                ([k - 1], 17, 40),        # sub-window
                                (list(range(k))[:max(1, k - 1)], 5, size)]:
            new = cells.copy()
            deltas = np.zeros((len(touched), size), np.uint8)
            for r, i in enumerate(touched):
                fresh = rng.integers(0, 256, hi - lo, dtype=np.uint8)
                deltas[r, lo:hi] = new[i, lo:hi] ^ fresh
                new[i, lo:hi] = fresh
            pd = np.asarray(ec_parity_delta(k, p, touched, deltas))
            np.testing.assert_array_equal(parity ^ pd, rs_encode_np(new, p))
            cells, parity = new, parity ^ pd              # chain updates


@pytest.mark.parametrize("inline_encryption", [False, True])
def test_ec_delta_rmw_partial_write_counted_and_bit_exact(inline_encryption):
    """A sub-stripe overwrite of a clean stripe rides the delta path:
    only the touched cells' old bytes are fetched (delta_bytes_saved
    counts the k*cs - fetched the full-path RMW would have read), the
    parity targets apply xor deltas in place, and the result is
    indistinguishable from a full re-encode — including under inline
    encryption (deltas are computed over the MEDIA image) and under a
    subsequent degraded read that decodes THROUGH the delta'd parity."""
    c = _client(n_targets=8, ec=(4, 2),
                inline_encryption=inline_encryption,
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    shadow = bytearray(_payload(2 * BLOCK, 81))
    c.pwrite(fd, bytes(shadow), 0)
    assert c.io.ec_delta_writes == 0              # full-stripe: full path
    writes = [(0, cs, 82),                        # one aligned cell
              (cs - 9, 20, 83),                   # crosses a cell seam
              (BLOCK + 33, 2 * cs, 84)]           # second stripe, two cells
    for off, ln, seed in writes:
        data = _payload(ln, seed)
        c.pwrite(fd, data, off)
        shadow[off:off + ln] = data
    ctr = c.io.data_path_counters()["ec"]
    assert ctr["delta_writes"] == len(writes)
    assert ctr["delta_fallbacks"] == 0
    # the one-cell overwrite alone saves (k-1) cells of old-data fetch
    assert ctr["delta_bytes_saved"] >= (k - 1) * cs
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    # the delta'd parity must be REAL parity: drop a touched data cell's
    # target and reconstruct through it
    order = c.io._ec_order(_oid(c), 0)
    c.cluster.fail_target(order[0])
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    assert c.io.data_path_counters()["ec"]["reconstructions"] >= 1
    _assert_rings_whole(c)
    c.close()


def test_parity_coefficient_cache_counts_hits_in_router_counters():
    """After one warm delta write, N more writes into the same data cell
    reuse its device-resident coefficient matrix: `parity_coeff_hits`
    rises by N, `parity_coeff_misses` stays, and both keys pass the
    counter registry."""
    from repro.core import counters_registry
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(BLOCK, 90), 0)
    c.pwrite(fd, _payload(4096, 91), 8192)             # warm: cell 0
    before = c.io.data_path_counters()
    n = 5
    for i in range(n):
        c.pwrite(fd, _payload(4096, 92 + i), 4096 * (i % 3))
    after = c.io.data_path_counters()
    counters_registry.verify(after)
    assert after["ec"]["delta_writes"] - before["ec"]["delta_writes"] == n
    assert after["ec"]["parity_coeff_hits"] \
        - before["ec"]["parity_coeff_hits"] == n
    assert after["ec"]["parity_coeff_misses"] \
        == before["ec"]["parity_coeff_misses"]
    # every EC write so far (one full, n + 1 delta) had a data cell in
    # flight before its parity wait: all targets are up
    assert after["ec"]["parity_overlap_writes"] == n + 2
    assert after["ec"]["parity_serial_writes"] == 0
    c.close()


def _hold_parity(monkeypatch, c, fail=None):
    """Stand in for both parity calls: the pending result's `__array__`
    waits (5 s at most) until a data cell's `writev` has started, then
    returns the real parity, or raises `fail`. Returns the event log."""
    from repro.kernels.rs_parity import ops as rs
    started = threading.Event()
    log = []

    class Held:
        def __init__(self, out):
            self.out = out

        def __array__(self, dtype=None, copy=None):
            assert started.wait(5.0), "the parity wait held back every cell"
            log.append("parity")
            if fail is not None:
                raise fail
            return np.asarray(self.out, dtype)

    for name in ("ec_encode", "ec_parity_delta"):
        monkeypatch.setattr(rs, name, lambda *a, real=getattr(rs, name),
                            **kw: Held(real(*a, **kw)))
    for s in c.io.sessions.values():
        def writev(oid, offset, buffers, real=s.writev):
            log.append("writev")
            started.set()
            return real(oid, offset, buffers)
        monkeypatch.setattr(s, "writev", writev)
    return started, log


def _stripe_cells(c, b, oid=None):
    """The k + p stored cells of stripe `b`, raw, after stragglers."""
    k, p, cs = c.io._ec
    c.io._ec_drain()
    oid = _oid(c) if oid is None else oid
    order = c.io._ec_order(oid, b)
    return np.stack([c.io.sessions[order[i]].fetch_cell(oid, b, i * cs, cs)
                     for i in range(k + p)])


def _assert_stripe_is_encode(c, b, block, oid=None):
    from repro.kernels.rs_parity import ref
    k, p, cs = c.io._ec
    data = np.frombuffer(block, np.uint8).reshape(k, cs)
    want = np.concatenate([data, ref.rs_encode_np(data, p)])
    np.testing.assert_array_equal(_stripe_cells(c, b, oid), want)


def test_ec_data_cells_move_before_the_parity_wait(monkeypatch):
    """A delta write and a full-stripe write each start a data cell's
    `writev` before their parity result is released (the stand-in's
    `__array__` blocks until one has started, so the parity-first order
    times out). The stored stripe is the reference encode, and both
    writes count as overlapped."""
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    fd = c.open("/f", create=True)
    shadow = bytearray(_payload(BLOCK, 95))
    c.pwrite(fd, bytes(shadow), 0)
    before = c.io.data_path_counters()["ec"]
    started, log = _hold_parity(monkeypatch, c)
    for off, ln, seed in ((8192, 4096, 96), (0, BLOCK, 97)):
        started.clear()
        log.clear()
        data = _payload(ln, seed)
        c.pwrite(fd, data, off)
        shadow[off:off + ln] = data
        assert log.index("writev") < log.index("parity")
    monkeypatch.undo()
    after = c.io.data_path_counters()["ec"]
    assert after["delta_writes"] - before["delta_writes"] == 1
    assert after["parity_overlap_writes"] \
        - before["parity_overlap_writes"] == 2
    assert after["parity_serial_writes"] == before["parity_serial_writes"]
    _assert_stripe_is_encode(c, 0, bytes(shadow))
    assert c.pread(fd, BLOCK, 0) == bytes(shadow)
    c.close()


def test_ec_concurrent_writers_share_each_parity_under_fast_switching():
    """Ten writers, more than the router pool's eight workers, each make
    full-stripe and delta writes to a file of their own at once, with
    the interpreter switching threads every microsecond. A write's
    parity jobs share one result that only the device computes, so no
    job waits on another: every writer ends within its join timeout,
    every stripe is the reference encode, and the overlap counters add
    up to the writes made."""
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    n, blocks, small = 10, 2, 4
    fds = [c.open(f"/w{i}", create=True) for i in range(n)]
    shadows = [bytearray(blocks * BLOCK) for _ in range(n)]
    before = c.io.data_path_counters()["ec"]
    errs = []

    def writer(i):
        rng = np.random.default_rng(200 + i)
        try:
            for b in range(blocks):
                data = _payload(BLOCK, 300 + 10 * i + b)
                c.pwrite(fds[i], data, b * BLOCK)
                shadows[i][b * BLOCK:(b + 1) * BLOCK] = data
            for j in range(small):
                off = int(rng.integers(0, blocks * BLOCK // 4096)) * 4096
                data = _payload(4096, 400 + 10 * i + j)
                c.pwrite(fds[i], data, off)
                shadows[i][off:off + 4096] = data
        except Exception as e:   # noqa: BLE001 - reported below
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(n)]
    old_interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old_interval)
    assert not any(t.is_alive() for t in threads)
    assert errs == []
    after = c.io.data_path_counters()["ec"]
    assert after["delta_writes"] - before["delta_writes"] == n * small
    assert after["parity_overlap_writes"] \
        - before["parity_overlap_writes"] == n * (blocks + small)
    assert after["parity_serial_writes"] == before["parity_serial_writes"]
    for i, fd in enumerate(fds):
        oid = c.dfs._open[fd].oid
        for b in range(blocks):
            _assert_stripe_is_encode(
                c, b, bytes(shadows[i][b * BLOCK:(b + 1) * BLOCK]), oid)
        assert c.pread(fd, blocks * BLOCK, 0) == bytes(shadows[i])
    c.close()


@pytest.mark.parametrize("path", ["delta", "full"])
def test_ec_parity_failure_ledgers_parity_and_heals(monkeypatch, path):
    """A parity call that raises (not a StorageError) after the data
    cells moved fails the write with StorageError and leaves exactly the
    p parity cells in the stripe's ledger. The next full write heals the
    stripe: ledger empty, every cell the reference encode, and the bytes
    read back with two targets down."""
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(BLOCK, 100), 0)
    off, ln = (8192, 4096) if path == "delta" else (0, BLOCK)
    _hold_parity(monkeypatch, c, fail=RuntimeError("device lost"))
    with pytest.raises(StorageError) as err:
        c.pwrite(fd, _payload(ln, 101), off)
    assert isinstance(err.value.__cause__, RuntimeError)
    monkeypatch.undo()
    c.io._ec_drain()
    assert list(_dirty_union(c, k + p).values()) == [set(range(k, k + p))]
    block = _payload(BLOCK, 102)
    c.pwrite(fd, block, 0)
    assert _dirty_union(c, k + p) == {}
    _assert_stripe_is_encode(c, 0, block)
    order = c.io._ec_order(_oid(c), 0)
    c.cluster.fail_target(order[0])
    c.cluster.fail_target(order[1])
    assert c.pread(fd, BLOCK, 0) == block
    c.close()


def _record_cell_threads(monkeypatch, c):
    """Wrap every session's cell verbs to log (verb, thread ident) as
    the router calls them: `writev` lands a data cell, `update_cell` a
    parity cell (or a heal rewrite), `xor_apply` a parity delta."""
    log = []
    for s in c.io.sessions.values():
        for verb in ("writev", "update_cell", "xor_apply"):
            def call(*a, verb=verb, real=getattr(s, verb), **kw):
                log.append((verb, threading.get_ident()))
                return real(*a, **kw)
            monkeypatch.setattr(s, verb, call)
    return log


def _router_pool_idents():
    return {t.ident for t in threading.enumerate()
            if t.name.startswith("cluster-router")}


def test_ec_op_thread_commits_data_cells_and_pool_lands_parity(monkeypatch):
    """A full-stripe write and a 4 KiB delta write on ec(4,2): every
    data cell's `writev` runs on the writing thread, every parity cell
    on a router-pool thread, `op_thread_cells` / `pool_cells` rise by
    k / p and by 1 / p, and the stored stripe is the reference encode."""
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    k, p, _cs = c.io._ec
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(BLOCK, 110), 0)
    log = _record_cell_threads(monkeypatch, c)
    shadow = bytearray(_payload(BLOCK, 111))
    me = threading.get_ident()
    for off, ln, parity_verb, n_data in ((0, BLOCK, "update_cell", k),
                                         (8192, 4096, "xor_apply", 1)):
        before = c.io.data_path_counters()["ec"]
        log.clear()
        data = bytes(shadow) if ln == BLOCK else _payload(ln, 112)
        c.pwrite(fd, data, off)
        shadow[off:off + ln] = data
        c.io._ec_drain()
        after = c.io.data_path_counters()["ec"]
        assert after["op_thread_cells"] - before["op_thread_cells"] == n_data
        assert after["pool_cells"] - before["pool_cells"] == p
        pool = _router_pool_idents()
        assert [t for v, t in log if v == "writev"] == [me] * n_data
        par = [t for v, t in log if v == parity_verb]
        assert len(par) == p and set(par) <= pool and me not in par
        assert {v for v, _t in log} == {"writev", parity_verb}
    monkeypatch.undo()
    _assert_stripe_is_encode(c, 0, bytes(shadow))
    assert c.pread(fd, BLOCK, 0) == bytes(shadow)
    c.close()


def test_ec_serial_order_lands_free_cells_on_op_thread_after_parity(
        monkeypatch):
    """With one data cell's target down the write takes the serial
    order: the op thread waits for the parity rows, then commits the
    k - 1 reachable data cells itself while the pool lands the p parity
    cells. Exactly the down cell is left dirty, and the stripe reads
    back bit-exact through the outage and after recovery."""
    from repro.kernels.rs_parity import ops as rs
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    k, p, _cs = c.io._ec
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(BLOCK, 120), 0)
    order = c.io._ec_order(_oid(c), 0)
    c.cluster.fail_target(order[1])
    log = _record_cell_threads(monkeypatch, c)

    class Logged:
        def __init__(self, out):
            self.out = out

        def __array__(self, dtype=None, copy=None):
            out = np.asarray(self.out, dtype)
            log.append(("parity", threading.get_ident()))
            return out

    monkeypatch.setattr(rs, "ec_encode", lambda *a, real=rs.ec_encode,
                        **kw: Logged(real(*a, **kw)))
    before = c.io.data_path_counters()["ec"]
    block = _payload(BLOCK, 121)
    c.pwrite(fd, block, 0)
    c.io._ec_drain()
    monkeypatch.undo()
    after = c.io.data_path_counters()["ec"]
    me = threading.get_ident()
    verbs = [v for v, _t in log]
    assert log[0] == ("parity", me)
    assert [t for v, t in log if v == "writev"] == [me] * (k - 1)
    par = [t for v, t in log if v == "update_cell"]
    assert len(par) == p and set(par) <= _router_pool_idents()
    assert verbs.count("parity") == 1
    assert after["parity_serial_writes"] - before["parity_serial_writes"] \
        == 1
    assert after["op_thread_cells"] - before["op_thread_cells"] == k - 1
    assert after["pool_cells"] - before["pool_cells"] == p
    assert list(_dirty_union(c, k + p).values()) == [{1}]
    assert c.pread(fd, BLOCK, 0) == block
    c.cluster.recover_target(order[1])
    assert _dirty_union(c, k + p) == {}
    _assert_stripe_is_encode(c, 0, block)
    c.close()


@pytest.mark.parametrize("path", ["delta", "full"])
def test_ec_parity_dispatch_failure_ledgers_parity(monkeypatch, path):
    """A parity call that raises at dispatch, before any cell has moved,
    still ledgers the p parity cells dirty and fails the write with
    StorageError from the original error; the next full write heals
    the stripe to the reference encode."""
    from repro.kernels.rs_parity import ops as rs
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    k, p, _cs = c.io._ec
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(BLOCK, 130), 0)
    off, ln = (8192, 4096) if path == "delta" else (0, BLOCK)

    def lost(*_a, **_kw):
        raise RuntimeError("device lost")

    for name in ("ec_encode", "ec_parity_delta"):
        monkeypatch.setattr(rs, name, lost)
    with pytest.raises(StorageError) as err:
        c.pwrite(fd, _payload(ln, 131), off)
    assert isinstance(err.value.__cause__, RuntimeError)
    monkeypatch.undo()
    c.io._ec_drain()
    assert list(_dirty_union(c, k + p).values()) == [set(range(k, k + p))]
    block = _payload(BLOCK, 132)
    c.pwrite(fd, block, 0)
    assert _dirty_union(c, k + p) == {}
    _assert_stripe_is_encode(c, 0, block)
    c.close()


def test_ec_delta_falls_back_when_parity_target_down():
    """The delta path needs every touched-data and parity target UP (it
    xors in place; there is no quorum to hide behind). With a parity
    target down the write degrades to the counted full re-encode path:
    delta_fallbacks bumps, the dirty marker lands, and rebuild heals."""
    c = _client(n_targets=8, ec=(4, 2),
                domains=["a", "a", "b", "b", "c", "c", "d", "d"])
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    base = _payload(BLOCK, 91)
    c.pwrite(fd, base, 0)
    ptid = c.io._ec_order(_oid(c), 0)[k]          # first parity home
    c.cluster.fail_target(ptid)
    patch = _payload(cs, 92)
    c.pwrite(fd, patch, 0)                        # full path, parity marked
    shadow = patch + base[cs:]
    ctr = c.io.data_path_counters()["ec"]
    assert ctr["delta_writes"] == 0
    assert ctr["delta_fallbacks"] == 1
    assert _dirty_union(c, k + p)                 # outage marked the cell
    c.cluster.recover_target(ptid)
    assert not _dirty_union(c, k + p)
    assert c.pread(fd, len(shadow), 0) == shadow
    # healthy again: the next partial write rides the delta path
    patch2 = _payload(cs, 93)
    c.pwrite(fd, patch2, cs)
    shadow = shadow[:cs] + patch2 + shadow[2 * cs:]
    assert c.io.data_path_counters()["ec"]["delta_writes"] == 1
    assert c.pread(fd, len(shadow), 0) == shadow
    _assert_rings_whole(c)
    c.close()


def test_ec_delta_skips_dirty_stripes_and_data_outages():
    """A touched DATA cell's target being down forces the counted
    fallback; a pre-dirty stripe skips the delta path silently (parity
    on media no longer matches the data, so xor-applying a delta would
    compound the lie — and heal-on-write reconstructs the image anyway,
    so a delta was never eligible). Correctness survives the heal."""
    c = _client()                                 # ec(2,1) @ 4
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    base = _payload(BLOCK, 95)
    c.pwrite(fd, base, 0)
    order = c.io._ec_order(_oid(c), 0)
    c.cluster.fail_target(order[0])               # data home for cell 0
    patch = _payload(100, 96)
    c.pwrite(fd, patch, 10)                       # touched-data outage
    shadow = bytearray(base)
    shadow[10:110] = patch
    assert c.io.ec_delta_fallbacks == 1
    assert c.io.ec_delta_writes == 0
    patch2 = _payload(50, 97)                     # stripe now pre-dirty:
    c.pwrite(fd, patch2, cs + 5)                  # heal-on-write, delta
    shadow[cs + 5:cs + 55] = patch2               # never eligible — NOT
    assert c.io.ec_delta_fallbacks == 1           # counted as a fallback
    assert c.io.ec_delta_writes == 0
    c.cluster.recover_target(order[0])
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    c.close()


def test_parity_scrub_catches_torn_stripe_and_resync_reheals():
    """The scrubber's EC leg decode-checks stripes against their stored
    parity — the one check that sees a TORN stripe (a parity row that no
    longer derives from its data cells, with NO dirty marker: the damage
    a silent partial write or a mis-applied delta would leave). The
    mismatching row is re-marked dirty, the next resync re-encodes it,
    and degraded reads decode correctly through the healed parity."""
    c = _client()
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    data = _payload(2 * BLOCK, 85)
    c.pwrite(fd, data, 0)
    c.io._ec_drain()
    before = c.cluster.stats.scrub_parity_checks
    out = c.scrubber.scrub_once()
    assert out["parity_checks"] >= 1              # healthy stripes verify
    assert out["parity_mismatches"] == 0
    assert c.cluster.stats.scrub_parity_checks > before
    # tear stripe 0: clobber its parity cell, leaving NO marker behind
    oid = _oid(c)
    order = c.io._ec_order(oid, 0)
    c.io.sessions[order[k]].update_cell(
        oid, 0, k * cs, np.zeros(cs, np.uint8))
    out = c.scrubber.scrub_once()
    assert out["parity_mismatches"] >= 1
    assert c.cluster.stats.scrub_parity_mismatches >= 1
    dirty = _dirty_union(c, k + p)                # parity row re-marked:
    assert any(k <= i < k + p                     # rebuild is owed
               for cells in dirty.values() for i in cells)
    c.cluster.resync()                            # re-encodes the row
    assert not _dirty_union(c, k + p)
    assert c.scrubber.scrub_once()["parity_mismatches"] == 0
    c.cluster.fail_target(order[0])               # decode THROUGH the
    assert c.pread(fd, len(data), 0) == data      # healed parity
    assert c.io.data_path_counters()["ec"]["reconstructions"] >= 1
    c.close()


# ---------------------------------------------------------------------------
# wide geometries on the 8-16-target fleet


_WIDE = [((4, 2), 8, ["a", "a", "b", "b", "c", "c", "d", "d"]),
         ((8, 3), 12, ["a", "b", "c", "d"] * 3)]


@pytest.mark.parametrize("ec,n,doms", _WIDE,
                         ids=["ec42_at_8", "ec83_at_12"])
def test_ec_wide_geometry_roundtrip_degraded_rebuild(ec, n, doms):
    """ec(4,2)@8 and ec(8,3)@12 end-to-end: bit-exact roundtrip through
    partial (delta) writes, degraded reads from any k survivors with up
    to p targets down, and marker-driven rebuild after an outage
    write."""
    c = _client(n_targets=n, ec=ec, domains=doms)
    k, p, cs = c.io._ec
    assert (k, p) == ec and cs == EC_STRIPE_BYTES // k
    fd = c.open("/f", create=True)
    shadow = bytearray(_payload(2 * BLOCK + 12345, 71))
    c.pwrite(fd, bytes(shadow), 0)
    patch = _payload(cs + 77, 72)                 # partial: delta path
    c.pwrite(fd, patch, cs // 2)
    shadow[cs // 2:cs // 2 + len(patch)] = patch
    assert c.io.ec_delta_writes >= 1
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    # p concurrent failures among stripe 0's own homes still decode
    order = c.io._ec_order(_oid(c), 0)
    for tid in order[:p]:
        c.cluster.fail_target(tid)
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    ctr = c.io.data_path_counters()["ec"]
    assert ctr["degraded_reads"] >= 1 and ctr["reconstructions"] >= p
    # outage write marks the down homes; recovery rebuilds only those
    fresh = _payload(BLOCK, 73)
    c.pwrite(fd, fresh, 0)
    shadow[:len(fresh)] = fresh
    dirty = _dirty_union(c, k + p)
    assert dirty
    for (oid, dk), cells in dirty.items():
        homes = {placement_order(n, oid, dk, tuple(doms))[i] for i in cells}
        assert homes <= set(order[:p])
    for tid in order[:p]:
        c.cluster.recover_target(tid)
    assert not _dirty_union(c, k + p)
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    _assert_rings_whole(c)
    c.close()


@pytest.mark.parametrize("ec,n,doms", _WIDE,
                         ids=["ec42_at_8", "ec83_at_12"])
def test_ec_delta_overwrite_wire_budget(ec, n, doms):
    """A one-cell overwrite of a clean stripe moves (1 new + 1 old + p
    parity) cells over the wire — never the k-cell stripe read of the
    full re-encode. This is the budget the chip metric
    `wire_bytes_per_user_byte.small` rests on."""
    c = _client(n_targets=n, ec=ec, domains=doms)
    k, p, cs = c.io._ec
    fd = c.open("/f", create=True)
    shadow = bytearray(_payload(2 * BLOCK, 74))
    c.pwrite(fd, bytes(shadow), 0)
    before = c.io.data_path_counters()            # drains stragglers
    cell = _payload(cs, 75)
    c.pwrite(fd, cell, 0)
    after = c.io.data_path_counters()
    shadow[:cs] = cell
    wire = after["transport"]["bytes_moved"] \
        - before["transport"]["bytes_moved"]
    assert wire <= (2 + p) * cs + cs // 8, (wire, (2 + p) * cs)
    assert after["ec"]["delta_writes"] - before["ec"]["delta_writes"] == 1
    assert after["ec"]["delta_bytes_saved"] \
        - before["ec"]["delta_bytes_saved"] >= (k - 1) * cs
    assert c.pread(fd, len(shadow), 0) == bytes(shadow)
    c.close()


def test_ec_wide_geometry_rejects_undersized_fleet():
    with pytest.raises(ValueError):
        _client(n_targets=5, ec=(4, 2))           # n < k + p
    with pytest.raises(ValueError):
        _client(n_targets=10, ec=(8, 3))


def test_ec_add_target_placement_repair_rehomes_cells():
    c = _client()
    fd = c.open("/f", create=True)
    data = _payload(8 * BLOCK, 61)
    c.pwrite(fd, data, 0)
    _flush(c)
    before = {(oid, dk, cell): tid
              for tid, cells in _cells_by_target(c).items()
              for (oid, dk, cell) in cells}
    c.add_target()                                # rebalances on the way in
    after = {(oid, dk, cell): tid
             for tid, cells in _cells_by_target(c).items()
             for (oid, dk, cell) in cells}
    assert sorted(after) == sorted(before)        # same cells, no dupes
    moved = sum(after[key] != before[key] for key in before)
    assert moved >= 1                             # jump-hash moved ~1/5
    # every cell now lives at its NEW placement home, nowhere else
    n = len(c.cluster.targets)
    k, p, cs = c.io._ec
    for tid, cells in _cells_by_target(c).items():
        for oid, dk, cell in cells:
            assert placement_order(n, oid, dk)[cell] == tid
    assert c.pread(fd, len(data), 0) == data
    ctr = c.io.data_path_counters()
    assert ctr["ec"]["degraded_reads"] == 0       # repair, not reconstruction
    c.close()
