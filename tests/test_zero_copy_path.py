"""Zero-copy hot-path tests (PR 2): keystream cache + fused apply_into
(bit-identical to the stream-cipher Pallas oracle at arbitrary offsets),
verified-extent cache invalidation under overwrite / aggregation / rebuild
/ device fail-recover, MediaScrubber honesty, staging-ring buffer donation
(a donated slot is never reused until media releases its lease), direct
preadv iovec fill, and the end-to-end copy accounting."""
import threading

import numpy as np
import pytest

from repro.core.client import ROS2Client, SlotLease, _StagingRing
from repro.core.dfs import AKEY, BLOCK
from repro.core.media import make_nvme_array
from repro.core.object_store import MediaScrubber, ObjectStore
from repro.core.smartnic import KEYSTREAM_PAGE, InlineCrypto
from repro.distributed.fault import FailureInjector


def _payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def _store(n=4, repl=2, aggregate=False):
    store = ObjectStore(make_nvme_array(n))
    # the bare engine defaults to verify-every-read (seed semantics);
    # these tests exercise the opt-in verified cache
    cont = store.create_pool("p").create_container(
        "c", replication=repl, aggregate=aggregate, verified_cache=True)
    return store, cont


# ---------------------------------------------------------------------------
# InlineCrypto: fused apply_into == stream-cipher Pallas kernel oracle


def _oracle_keystream(key, nonce, offset, n):
    """Keystream bytes [offset, offset+n) via the pure-jnp kernel oracle."""
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.stream_cipher.ref import cipher_ref
    nw = (offset + n + 3) // 4
    words = np.asarray(cipher_ref(jnp.zeros(nw, jnp.uint32),
                                  key=key, nonce=nonce))
    return words.astype("<u4").view(np.uint8)[offset:offset + n]


@pytest.mark.parametrize("n,offset", [
    (1, 0), (5, 3), (4096, 0), (1000, 4097),
    (300, KEYSTREAM_PAGE - 7),          # straddles a keystream page
    (2 * KEYSTREAM_PAGE + 11, 13),      # multi-page
])
def test_apply_into_matches_stream_cipher_oracle(n, offset):
    c = InlineCrypto(0xC0FFEE)
    data = np.frombuffer(_payload(n, seed=n + offset), np.uint8)
    dst = np.empty(n, np.uint8)
    c.apply_into(dst, data, nonce=42, offset=offset)
    expect = data ^ _oracle_keystream(0xC0FFEE, 42, offset, n)
    np.testing.assert_array_equal(dst, expect)
    # in-place form and the allocating form agree
    buf = data.copy()
    c.apply_into(buf, buf, nonce=42, offset=offset)
    np.testing.assert_array_equal(buf, dst)
    np.testing.assert_array_equal(c.apply(data, nonce=42, offset=offset),
                                  dst)


def test_apply_into_property_arbitrary_offsets():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(1, 3000), st.integers(0, 3 * KEYSTREAM_PAGE))
    @settings(max_examples=25, deadline=None)
    def prop(n, offset):
        c = InlineCrypto(7)
        data = np.frombuffer(_payload(n, seed=1), np.uint8)
        out = c.apply(data, nonce=9, offset=offset)
        np.testing.assert_array_equal(
            out, data ^ _oracle_keystream(7, 9, offset, n))

    prop()


def test_apply_accepts_memoryview_and_bytes_without_copy():
    c = InlineCrypto(1)
    raw = _payload(2000, seed=3)
    a = c.apply(np.frombuffer(raw, np.uint8), nonce=5)
    b = c.apply(memoryview(raw), nonce=5)
    d = c.apply(raw, nonce=5)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, d)
    # roundtrip through a memoryview input
    np.testing.assert_array_equal(c.apply(memoryview(bytes(a)), nonce=5),
                                  np.frombuffer(raw, np.uint8))


def test_high_oid_nonces_do_not_collide():
    """Nonce bits >= 32 fold into the key (fmix32), so streams whose
    nonces agree mod 2^32 — oids 4096 apart at the same block — never
    share a keystream (the seed's 64-bit nonce space, preserved)."""
    c = InlineCrypto(5)
    low = c.keystream(64, nonce=1 << 20)
    high = c.keystream(64, nonce=4097 << 20)     # == 1<<20 mod 2^32
    assert not np.array_equal(low, high)
    # and folding is involutive for decrypt: same nonce -> same stream
    np.testing.assert_array_equal(high, c.keystream(64, nonce=4097 << 20))


def test_scrubber_auto_started_bounds_silent_corruption():
    """The client starts the MediaScrubber with the verified cache: a
    block corrupted AFTER a verified read is revoked from the cache by the
    next scrub cycle, and reads reroute to the clean replica again."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    assert c.scrubber._thread is None            # explicit opt-out honored
    c.close()
    c = ROS2Client(mode="host", transport="rdma", n_devices=2)
    assert c.scrubber._thread is not None        # honest-cache default
    fd = c.open("/scrub", create=True)
    c.pwrite(fd, b"y" * 4096, 0)
    assert c.pread(fd, 4096, 0) == b"y" * 4096   # warm the cache
    inj = FailureInjector(c.store)
    assert inj.corrupt_block(c.devices[0].name)
    c.scrubber.scrub_once()                      # deterministic cycle
    assert c.pread(fd, 4096, 0) == b"y" * 4096
    c.close()


def test_keystream_cache_hits_and_disabled_identity():
    warm = InlineCrypto(2)
    cold = InlineCrypto(2, cache_bytes=0)
    data = np.frombuffer(_payload(1 << 20, seed=4), np.uint8)
    first = warm.apply(data, nonce=11)
    gen_after_first = warm.stats.keystream_bytes_generated
    second = warm.apply(data, nonce=11)
    np.testing.assert_array_equal(first, second)
    # steady state: zero PRF regeneration, pure cache hits
    assert warm.stats.keystream_bytes_generated == gen_after_first
    assert warm.stats.cache_hits >= data.size // KEYSTREAM_PAGE
    # cache off == cache on, bit for bit; but regenerates every time
    np.testing.assert_array_equal(cold.apply(data, nonce=11), first)
    assert cold.stats.keystream_bytes_generated >= data.size


# ---------------------------------------------------------------------------
# Verified-extent cache: warm-read skip + every invalidation edge


def test_vcache_warm_read_skips_checksum():
    store, cont = _store()
    obj = cont.object(1)
    obj.update("0", "data", 0, _payload(1 << 16))
    obj.fetch("0", "data", 0, 1 << 16)           # cold: verifies + caches
    computed = store.stats.checksum_bytes
    for _ in range(3):
        obj.fetch("0", "data", 0, 1 << 16)       # warm: skips the csum
    assert store.stats.checksum_bytes == computed
    assert store.stats.checksum_skipped_bytes >= 3 * (1 << 16)
    assert store.stats.verify_hits >= 3


def test_vcache_invalidated_on_overwrite_aggregation():
    store, cont = _store(aggregate=True)
    obj = cont.object(1)
    obj.update("0", "data", 0, b"old" * 100)
    obj.fetch("0", "data", 0, 300)
    old_keys = [(n, k) for e in obj._extents[("0", "data")]
                for n, k in e.block_keys.items()]
    assert any(cont.vcache.check(n, k, store.device(n).generation)
               for n, k in old_keys)
    obj.update("0", "data", 0, b"new" * 100)     # fully covers -> retires
    # a stale cache can never vouch for a retired extent
    for n, k in old_keys:
        assert not cont.vcache.check(n, k, store.device(n).generation)
    assert obj.fetch("0", "data", 0, 300) == b"new" * 100


def test_stale_cache_never_serves_retired_extent_after_reclaim():
    store, cont = _store(aggregate=True)
    obj = cont.object(1)
    tracked = None
    for i in range(cont.AGGREGATE_GRACE_EPOCHS + 3):
        obj.update("0", "data", 0, bytes([i]) * 64)
        obj.fetch("0", "data", 0, 64)
        if tracked is None:
            tracked = [(n, k) for e in obj._extents[("0", "data")]
                       for n, k in e.block_keys.items()]
    # first version: blocks reclaimed after the grace window AND cache
    # entries gone — the retired extent is unreachable by construction
    for n, k in tracked:
        assert not cont.vcache.check(n, k, store.device(n).generation)
        with pytest.raises(KeyError):
            store.device(n).read(k)


def test_vcache_invalidated_on_device_fail_recover():
    store, cont = _store(n=2, repl=2)
    obj = cont.object(1)
    obj.update("0", "data", 0, _payload(4096, seed=1))
    obj.fetch("0", "data", 0, 4096)
    name, key = next(iter(obj._extents[("0", "data")][0].block_keys.items()))
    dev = store.device(name)
    assert cont.vcache.check(name, key, dev.generation)
    gen = dev.generation
    dev.fail()
    dev.recover()
    # generation moved: the pre-failure verification no longer counts
    assert dev.generation != gen
    assert not cont.vcache.check(name, key, dev.generation)
    computed = store.stats.checksum_bytes
    obj.fetch("0", "data", 0, 4096)              # re-verifies some replica
    assert store.stats.checksum_bytes > computed or \
        store.stats.checksum_skipped_bytes > 0


def test_vcache_invalidated_on_rebuild():
    store, cont = _store(n=3, repl=2)
    obj = cont.object(9)
    for i in range(5):
        obj.update(str(i), "data", 0, bytes([i]) * 32)
        obj.fetch(str(i), "data", 0, 32)
    victim = store.devices[0].name
    victim_keys = [(n, k) for lst in obj._extents.values() for e in lst
                   for n, k in e.block_keys.items() if n == victim]
    store.fail_device(victim)
    store.rebuild(victim)
    for n, k in victim_keys:
        assert not cont.vcache.check(n, k, store.device(n).generation)
    store.fail_device(store.devices[1].name)
    for i in range(5):
        assert obj.fetch(str(i), "data", 0, 32) == bytes([i]) * 32


def test_scrubber_revokes_corrupted_cache_entries():
    store, cont = _store(n=2, repl=2)
    obj = cont.object(3)
    obj.update("0", "data", 0, b"x" * 64)
    obj.fetch("0", "data", 0, 64)                # both-replica warm state
    inj = FailureInjector(store)
    assert inj.corrupt_block(store.devices[0].name)
    scrub = MediaScrubber(store).scrub_once()
    # if the corrupted replica was the cached one, the scrubber revoked it
    assert scrub["scanned_bytes"] > 0
    assert obj.fetch("0", "data", 0, 64) == b"x" * 64
    # after the scrub + reroute, every subsequent read is clean too
    assert obj.fetch("0", "data", 0, 64) == b"x" * 64


def test_scrubber_budget_bounds_work():
    store, cont = _store()
    obj = cont.object(1)
    for i in range(8):
        obj.update(str(i), "data", 0, _payload(1 << 16, seed=i))
        obj.fetch(str(i), "data", 0, 1 << 16)
    s = MediaScrubber(store, budget_bytes=2 << 16)
    out = s.scrub_once()
    assert out["scanned_bytes"] <= 2 << 16
    # successive cycles rotate through the rest of the cache
    total = out["scanned_bytes"]
    for _ in range(8):
        total += s.scrub_once()["scanned_bytes"]
    assert total >= 8 * (1 << 16)


# ---------------------------------------------------------------------------
# Staging-ring donation: the no-aliasing lease protocol


def test_donated_slot_not_reused_until_media_releases_lease():
    c = ROS2Client(mode="host", transport="rdma", n_staging_slots=8)
    fd = c.open("/don", create=True)
    c.pwrite(fd, _payload(2 * BLOCK, seed=1), 0)
    ring = c.io.ring
    donated = ring.donated_slots()
    assert len(donated) == 2                     # both blocks' slots leased
    with ring._cv:
        free = list(ring._free)
    assert not set(donated) & set(free)          # leased slots NOT free
    # media releases the leases (writeback) -> slots return to the ring
    for dev in c.devices:
        dev.writeback()
    assert ring.donated_slots() == []
    with ring._cv:
        assert set(donated) <= set(ring._free)
    # the written-back bytes survive slot reuse intact
    c.pwrite(fd, _payload(2 * BLOCK, seed=2), 2 * BLOCK)
    assert c.pread(fd, 2 * BLOCK, 0) == _payload(2 * BLOCK, seed=1)
    c.close()


def test_ring_pressure_reclaims_leases_write_only_workload():
    """Writing far more blocks than staging slots must not deadlock: ring
    pressure triggers media writeback, and every byte lands correctly."""
    c = ROS2Client(mode="host", transport="rdma", n_staging_slots=4)
    fd = c.open("/press", create=True)
    data = _payload(16 * BLOCK, seed=3)
    c.pwrite(fd, data, 0)                        # 16 blocks through 4 slots
    assert c.io.ring.reclaims > 0
    assert c.pread(fd, len(data), 0) == data
    c.close()


def test_concurrent_writers_donation_no_aliasing():
    c = ROS2Client(mode="host", transport="rdma", n_staging_slots=4)
    fds = [c.open(f"/t{i}", create=True) for i in range(2)]
    datas = [_payload(8 * BLOCK, seed=10 + i) for i in range(2)]
    errs = []

    def writer(i):
        try:
            c.dfs.pwrite(fds[i], datas[i], 0)
        except Exception as e:   # noqa
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errs
    for i in (0, 1):
        assert c.pread(fds[i], 8 * BLOCK, 0) == datas[i]
    c.close()


def test_update_many_abort_releases_donated_leases():
    c = ROS2Client(mode="host", transport="rdma", n_staging_slots=8,
                   replication=1)
    fd = c.open("/abort", create=True)
    calls = {"n": 0}
    originals = {d.name: d.write for d in c.devices}

    def failing_write(dev):
        def w(key, data, lease=None, pre_pinned=False):
            calls["n"] += 1
            if calls["n"] > 1:
                raise IOError("injected media failure")
            return originals[dev.name](key, data, lease=lease,
                                       pre_pinned=pre_pinned)
        return w

    for d in c.devices:
        d.write = failing_write(d)
    with pytest.raises(Exception):
        c.pwrite(fd, _payload(3 * BLOCK, seed=5), 0)
    for d in c.devices:
        d.write = originals[d.name]
    # aborted batch: every donated lease must be back (no pinned slots)
    assert c.io.ring.donated_slots() == []
    with c.io.ring._cv:
        assert sorted(c.io.ring._free) == list(range(8))
    # ring still fully usable
    ok = _payload(2 * BLOCK, seed=6)
    c.pwrite(fd, ok, 0)
    assert c.pread(fd, 2 * BLOCK, 0) == ok
    c.close()


def test_slot_lease_refcounting_unit():
    ring = _StagingRing.__new__(_StagingRing)   # lease mechanics only
    returned = []
    ring._return_slot = returned.append
    lease = SlotLease(ring, 3)
    lease.pin()
    lease.pin()                                  # two replica attachments
    lease._op_release()
    assert returned == [] and lease.active
    lease.unpin()
    assert returned == [] and lease.active
    lease.unpin()                                # last pin -> slot returns
    assert returned == [3] and not lease.active


# ---------------------------------------------------------------------------
# preadv direct iovec fill + copy accounting


def test_preadv_fills_iovecs_without_contiguous_blob():
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/v", create=True)
    data = _payload(2 * BLOCK + 300, seed=7)
    c.pwrite(fd, data, 0)

    def no_read(*a, **k):
        raise AssertionError("preadv must not materialize a contiguous read")

    c.io.read = no_read
    sizes = [BLOCK + 10, 17, BLOCK + 273]
    got = c.preadv(fd, sizes, 0)
    assert [len(g) for g in got] == sizes
    assert b"".join(got) == data
    c.close()


def test_zero_copy_write_path_has_zero_post_splice_copies():
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/zc", create=True)
    data = _payload(4 * BLOCK, seed=8)
    c.pwrite(fd, data, 0)
    ctr = c.io.data_path_counters()
    # transport: exactly one splice per byte; engine/media: zero host copies
    assert ctr["transport"]["copy_bytes"] == ctr["transport"]["bytes_moved"]
    assert ctr["client"]["host_copy_bytes"] == 0
    assert ctr["media"]["host_copy_bytes"] == 0
    assert ctr["media"]["donated_bytes"] == 4 * BLOCK * 2   # both replicas
    c.close()


def test_encrypted_zero_copy_roundtrip_and_keystream_cache():
    c = ROS2Client(mode="host", transport="rdma", inline_encryption=True)
    fd = c.open("/enc", create=True)
    data = _payload(2 * BLOCK + 999, seed=9)
    c.pwrite(fd, data, 0)
    assert c.pread(fd, len(data), 0) == data
    gen0 = c.io.crypto.stats.keystream_bytes_generated
    for _ in range(2):
        assert c.pread(fd, len(data), 0) == data
    # warm re-reads decrypt from cached keystream pages: no regeneration
    assert c.io.crypto.stats.keystream_bytes_generated == gen0
    # ciphertext at rest on every replica
    for dev in c.devices:
        dev.writeback()
        for blk in dev._blocks.values():
            assert data[:64] not in blk
    c.close()


_ENC_TOPOLOGIES = {
    "single": dict(),
    "rp2": dict(n_targets=4, replication=2),
    "ec42": dict(n_targets=8, ec=(4, 2),
                 domains=["a", "a", "b", "b", "c", "c", "d", "d"]),
}


def _stored_bytes(c, oid, b, bo, n):
    """Media bytes of block `b` at [bo, bo+n), fetched straight from the
    engine that holds them: no transport, no decrypt."""
    io = c.io
    if not hasattr(io, "sessions"):
        cont = io.container
    elif io._ec is not None:
        _k, _p, cs = io._ec
        cont = io.sessions[io._ec_order(oid, b)[bo // cs]].container
    else:
        cont = io.sessions[io._route_block(oid, b)].container
    return cont.object(oid).fetch(str(b), AKEY, bo, n)


@pytest.mark.parametrize("topology", list(_ENC_TOPOLOGIES))
def test_encrypted_unaligned_write_decrypts_at_block_offsets(topology):
    """Inline encryption keys every byte by (oid, block) nonce and its
    block-ABSOLUTE offset: a writev that starts mid-block stores
    ciphertext at the offsets the stream cipher oracle gives, and reads
    whose ranges start mid-block — through `read`, `read_into` and
    `readv_into` — decrypt at those same offsets, on a single target, a
    routed RP_2 fleet and a routed ec(4,2) fleet."""
    c = ROS2Client(mode="host", transport="rdma", inline_encryption=True,
                   scrub_interval_s=None, **_ENC_TOPOLOGIES[topology])
    fd = c.open("/enc-unaligned", create=True)
    oid = c.dfs._handle(fd).oid
    base = 12345                                 # not block-aligned
    data = _payload(2 * BLOCK + 777, seed=13)
    c.pwritev(fd, [data[:BLOCK - 100], data[BLOCK - 100:BLOCK + 5000],
                   data[BLOCK + 5000:]], base)
    if hasattr(c.io, "sessions"):
        c.io.data_path_counters()                # land EC stragglers
    # ciphertext at rest: block b's bytes at bo are keyed (oid<<20 | b, bo)
    end = base + len(data)
    for b in range(base // BLOCK, (end - 1) // BLOCK + 1):
        bo = max(base, b * BLOCK) - b * BLOCK
        n = min(64, end - b * BLOCK - bo)
        plain = np.frombuffer(data, np.uint8)[b * BLOCK + bo - base:][:n]
        want = plain ^ _oracle_keystream(0xC0FFEE, oid * (1 << 20) + b,
                                         bo, n)
        assert _stored_bytes(c, oid, b, bo, n) == want.tobytes(), b
    for off, n in [(base + 1, 4096), (BLOCK - 50, 300),
                   (BLOCK + 7, BLOCK), (2 * BLOCK + 3, 700)]:
        want = data[off - base:off - base + n]
        assert c.io.read(oid, off, n) == want, off
        dst = c.register_region(n)
        assert c.io.read_into(oid, off, n, dst, 0) == n
        assert bytes(dst.buf) == want, off
        bufs = [np.empty(7, np.uint8), np.empty(n - 7, np.uint8)]
        assert c.io.readv_into(oid, off, bufs) == n
        assert b"".join(x.tobytes() for x in bufs) == want, off
    c.close()
