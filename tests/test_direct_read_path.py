"""PR-4 one-copy read path + quorum-ack writes: direct-splice reads are
bit-identical to the staged path (including extents straddling destination
spans), never touch the staging ring, and respect destination
capabilities; quorum writes return at majority with stragglers landing in
the background, post-ack failures demoting + re-replicating; the batched
DeviceDirectSink packs tensors into slots (one device_put per dtype
group of a slot, no session leak); the MediaScrubber ties its budget to
device idle time."""
import threading
import time

import numpy as np
import pytest

from repro.core.client import ROS2Client
from repro.core.data_plane import AccessError
from repro.core.dfs import AKEY, BLOCK
from repro.core.media import make_nvme_array
from repro.core.object_store import MediaScrubber, ObjectStore, StorageError


def _payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# ---------------------------------------------------------------------------
# Direct splice: correctness, structure, capability


def test_direct_read_bit_identical_to_staged_property():
    """Property test (seeded randomized cases): multi-extent overlays read
    through the direct splice into registered destinations — with windows
    and destination splits chosen so extents and blocks straddle
    destination spans — must be bit-identical to the staged path AND to
    the shadow ground truth."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    fd = c.open("/prop", create=True)
    span = 2 * BLOCK + 4096
    shadow = bytearray(span)
    rng = np.random.default_rng(0)
    # overlapping writes at awkward offsets -> multi-version extent overlay
    for i in range(12):
        off = int(rng.integers(0, span - 100))
        n = int(rng.integers(1, min(span - off, BLOCK + 999)))
        data = _payload(n, seed=100 + i)
        c.pwrite(fd, data, off)
        shadow[off:off + n] = data
    assert c.io.direct_reads

    def one_case(off, n, cuts):
        sizes, prev = [], 0
        for cut in sorted(cuts) + [n]:
            if cut > prev:
                sizes.append(cut - prev)
                prev = cut
        direct = b"".join(c.preadv(fd, sizes, off))
        c.io.direct_reads = False            # same client, staged path
        try:
            staged = b"".join(c.preadv(fd, sizes, off))
        finally:
            c.io.direct_reads = True
        assert direct == staged == bytes(shadow[off:off + n]), (off, sizes)

    # adversarial corners: destination cuts right at block/extent edges
    one_case(BLOCK - 3, 7, [3])              # split straddling a block edge
    one_case(0, span, [1, BLOCK, BLOCK + 1, 2 * BLOCK])
    one_case(BLOCK + 4090, 10, [5])
    for case in range(40):
        off = int(rng.integers(0, span - 2))
        n = int(rng.integers(1, min(span - off, BLOCK + 7)))
        cuts = [int(x) for x in
                rng.integers(1, max(2, n), size=int(rng.integers(0, 4)))]
        one_case(off, n, cuts)
    c.close()


def test_steady_state_reads_zero_staging_acquires():
    """The structural PR-4 claim: a steady-state RDMA read NEVER acquires
    a staging-ring slot and never pays the engine->ring bounce — every
    byte lands by server-initiated placement."""
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/zring", create=True)
    data = _payload(4 * BLOCK + 12345, seed=1)
    c.pwrite(fd, data, 0)
    sink = c.register_region(len(data))
    acquires0 = c.io.ring.acquires
    assert c.pread(fd, len(data), 0) == data
    c.pread_into(fd, len(data), 0, sink, 0)
    assert b"".join(c.preadv(fd, [BLOCK, BLOCK + 45, 300], 7)) == \
        data[7:7 + 2 * BLOCK + 345]
    ctr = c.io.data_path_counters()
    assert c.io.ring.acquires == acquires0       # ring untouched by reads
    assert ctr["staging"]["bounce_bytes"] == 0   # no engine->ring copy
    assert ctr["transport"]["placements"] >= 3   # server-initiated ops
    assert ctr["transport"]["copy_bytes"] == ctr["transport"]["bytes_moved"]
    c.close()


def test_tcp_path_still_stages():
    """The ring stays for TCP (no server-initiated placement without
    RDMA) — and the bounce is COUNTED."""
    c = ROS2Client(mode="host", transport="tcp")
    fd = c.open("/staged", create=True)
    data = _payload(2 * BLOCK, seed=2)
    c.pwrite(fd, data, 0)
    a0 = c.io.ring.acquires
    assert c.pread(fd, len(data), 0) == data
    assert c.io.ring.acquires > a0
    assert c.io.data_path_counters()["staging"]["bounce_bytes"] \
        == len(data)
    c.close()


def test_revoked_dst_rkey_cannot_receive_direct_splice():
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/cap", create=True)
    data = _payload(BLOCK, seed=3)
    c.pwrite(fd, data, 0)
    sink = c.register_region(BLOCK)
    c.pread_into(fd, BLOCK, 0, sink, 0)          # grant + first placement
    token = c.io._dst_rkey(sink)                 # the cached capability
    sink.buf[:] = 7                              # sentinel
    c.client_registry.revoke(token)
    with pytest.raises(AccessError):
        c.pread_into(fd, BLOCK, 0, sink, 0)
    assert bytes(sink.buf) == b"\x07" * BLOCK    # nothing landed
    c.close()


def test_transient_read_capabilities_do_not_accumulate():
    """Every pread()/preadv() grants a placement rkey on its transient
    destination MR; the grant must die with the registration — neither
    the client registry's key table nor the NIC translation cache may
    grow per op."""
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/leak2", create=True)
    data = _payload(64 * 1024, seed=12)
    c.pwrite(fd, data, 0)
    c.pread(fd, 1024, 0)                         # settle steady state
    keys0 = len(c.client_registry._rkeys)
    cache0 = len(c.io.xport._rkey_cache)
    for _ in range(50):
        assert c.pread(fd, 4096, 0) == data[:4096]
        c.preadv(fd, [512, 512], 0)
    assert len(c.client_registry._rkeys) == keys0
    assert len(c.io.xport._rkey_cache) == cache0
    c.close()


def test_persistent_dst_rkey_renewed_before_expiry():
    """A persistent destination's placement lease is renewed IN PLACE
    (same token — NIC translation caches stay valid) when a read finds it
    inside the expiry margin, so long-lived sinks never hard-fault on
    TTL; a revoked key is never resurrected by the renewal path."""
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/renew", create=True)
    data = _payload(4096, seed=13)
    c.pwrite(fd, data, 0)
    sink = c.register_region(4096)
    c.pread_into(fd, 4096, 0, sink, 0)
    token = c.io._dst_rkey(sink)
    # push the lease to its last second, registry and cache both
    rk = c.client_registry._rkeys[token]
    rk.expires_at = time.monotonic() + 1.0
    with c.io._dst_rkey_lock:
        c.io._dst_rkeys[sink.region_id] = (token, sink,
                                           time.monotonic() + 1.0)
    c.pread_into(fd, 4096, 0, sink, 0)           # triggers in-place renew
    assert bytes(sink.buf) == data
    assert c.io._dst_rkey(sink) == token         # SAME token, renewed
    assert rk.expires_at > time.monotonic() + 1000
    # revocation wins over renewal, even from inside the margin
    c.client_registry.revoke(token)
    rk.expires_at = time.monotonic() + 1.0
    with c.io._dst_rkey_lock:
        c.io._dst_rkeys[sink.region_id] = (token, sink,
                                           time.monotonic() + 1.0)
    with pytest.raises(AccessError):
        c.pread_into(fd, 4096, 0, sink, 0)
    c.close()


def test_cross_tenant_dst_cannot_receive_direct_splice():
    c = ROS2Client(mode="host", transport="rdma", tenant="tenantA",
                   secret="sA")
    fd = c.open("/xt", create=True)
    c.pwrite(fd, _payload(4096, seed=4), 0)
    evil = c.client_registry.register(4096, "tenantB")   # other PD
    with pytest.raises(AccessError):
        c.io.read_into(3, 0, 4096, evil, 0)
    c.close()


# ---------------------------------------------------------------------------
# Quorum-ack replica commit


def _quorum_store(n=4, repl=3, quorum=None):
    store = ObjectStore(make_nvme_array(n))
    cont = store.create_pool("p").create_container(
        "c", replication=repl, verified_cache=True, write_quorum=quorum)
    return store, cont


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


def test_quorum_write_returns_before_straggler_lands():
    c = ROS2Client(mode="host", transport="rdma", n_devices=3,
                   replication=3)                # majority quorum = 2
    straggler = c.devices[0]
    straggler.commit_delay_s = 0.5
    fd = c.open("/q", create=True)
    data = _payload(BLOCK, seed=5)
    t0 = time.monotonic()
    c.pwrite(fd, data, 0)
    elapsed = time.monotonic() - t0
    assert elapsed < 0.4, f"write waited for the straggler ({elapsed:.2f}s)"
    st = c.store.stats
    assert st.quorum_acks >= 1
    # reads are served from the fast majority immediately
    assert c.pread(fd, BLOCK, 0) == data
    # the straggler commit completes in the background
    assert _wait(lambda: c.store.stats.background_commits >= 1)
    straggler.commit_delay_s = 0.0
    obj = c.container.object(c.dfs._open[fd].oid)
    ext = obj._extents[("0", AKEY)][0]
    assert _wait(lambda: ext.pending is None or ext.pending.complete)
    assert len(ext.block_keys) == 3              # full width restored
    assert straggler.read(ext.block_keys[straggler.name]) is not None
    c.close()


def test_full_fanout_quorum_waits_for_every_replica():
    """write_quorum=replication restores wait-for-all semantics: the op
    pays the straggler's latency."""
    c = ROS2Client(mode="host", transport="rdma", n_devices=3,
                   replication=3, write_quorum=3)
    c.devices[0].commit_delay_s = 0.2
    fd = c.open("/full", create=True)
    t0 = time.monotonic()
    c.pwrite(fd, _payload(4096, seed=6), 0)
    assert time.monotonic() - t0 >= 0.2
    assert c.store.stats.quorum_acks == 0
    c.devices[0].commit_delay_s = 0.0
    c.close()


def test_post_ack_replica_failure_demotes_and_rereplicates():
    store, cont = _quorum_store(n=4, repl=3, quorum=2)
    obj = cont.object(1)
    targets = [d for d in cont.placement(1, "0") if d.alive][:3]
    victim = targets[-1]
    orig_write = victim.write
    gate = threading.Event()

    def slow_failing_write(key, data, lease=None, pre_pinned=False):
        gate.wait(5.0)                           # fail AFTER the ack
        raise IOError("injected straggler media failure")

    victim.write = slow_failing_write
    data = _payload(1 << 16, seed=7)
    obj.update("0", AKEY, 0, data)               # returns at quorum 2/3
    assert victim.name in obj._extents[("0", AKEY)][0].block_keys
    gate.set()                                   # now the straggler dies
    assert _wait(lambda: store.stats.replica_demotions >= 1)
    victim.write = orig_write
    ext = obj._extents[("0", AKEY)][0]
    assert victim.name not in ext.block_keys     # demoted
    # re-replicated onto the spare: width back at 3, and the data survives
    # both original fast replicas failing
    assert _wait(lambda: len(ext.block_keys) == 3)
    for d in targets[:2]:
        d.fail()
    assert obj.fetch("0", AKEY, 0, len(data)) == data
    store.close()


def test_punch_racing_straggler_commit_leaks_no_blocks():
    store, cont = _quorum_store(n=3, repl=3, quorum=2)
    straggler = store.devices[2]
    if straggler not in cont.placement(1, "0")[:3]:
        straggler = cont.placement(1, "0")[0]
    straggler.commit_delay_s = 0.2
    obj = cont.object(1)
    obj.update("0", AKEY, 0, _payload(4096, seed=8))
    obj.punch("0", AKEY)                         # free while in flight
    straggler.commit_delay_s = 0.0
    # the late write must delete its own block, not resurrect the extent
    assert _wait(lambda: sum(len(d._blocks) for d in store.devices) == 0)
    assert obj.fetch("0", AKEY, 0, 4096) == b"\x00" * 4096
    store.close()


def test_straggler_device_failure_releases_lease_exactly_once():
    """A device that dies while its donated-lease background commit is in
    flight must release the pre-pin exactly once (a double unpin would
    free the slot twice and corrupt the ring free list)."""
    c = ROS2Client(mode="host", transport="rdma", n_devices=3,
                   replication=3, n_staging_slots=4)
    straggler = c.devices[0]
    straggler.commit_delay_s = 0.15
    fd = c.open("/dl", create=True)
    data = _payload(BLOCK, seed=11)
    c.pwrite(fd, data, 0)                        # returns at quorum 2/3
    straggler.fail()                             # dies mid-commit
    straggler.commit_delay_s = 0.0
    assert _wait(lambda: c.store.stats.replica_demotions >= 1)
    for d in c.devices:
        d.writeback()                            # land surviving donations
    ring = c.io.ring
    assert _wait(lambda: ring.donated_slots() == [])
    with ring._cv:
        free = sorted(ring._free)
    assert free == list(range(4)), f"corrupt free list: {free}"
    assert c.pread(fd, BLOCK, 0) == data
    c.close()


def test_quorum_failure_below_threshold_aborts_batch():
    store, cont = _quorum_store(n=3, repl=3, quorum=3)
    for d in store.devices[:2]:
        d.fail()                                 # only 1 of 3 can land
    obj = cont.object(1)
    # quorum capped at live target count (1): succeeds degraded
    obj.update("0", AKEY, 0, b"x" * 64)
    assert obj.fetch("0", AKEY, 0, 64) == b"x" * 64
    store.close()


# ---------------------------------------------------------------------------
# Batched device-direct placement


@pytest.mark.parametrize("mode", ["host", "dpu"])
def test_read_tensors_batched_matches_and_packs(mode):
    from repro.core.device_direct import DeviceDirectSink
    c = ROS2Client(mode=mode, transport="rdma")
    rng = np.random.default_rng(9)
    tensors = [rng.standard_normal((32, 16)).astype(np.float32),
               rng.integers(-100, 100, (64,), dtype=np.int32),
               rng.standard_normal((8, 8, 3)).astype(np.float32),
               rng.integers(0, 255, (100,)).astype(np.uint8),
               rng.standard_normal((128,)).astype(np.float32)]
    reqs = []
    for i, t in enumerate(tensors):
        fd = c.open(f"/tensors{i}", create=True)
        c.pwrite(fd, t.tobytes(), 0)
        reqs.append((fd, 0, t.shape, t.dtype))
    with DeviceDirectSink(c, slot_bytes=8192, n_slots=2) as sink:
        got = sink.read_tensors(reqs)
        assert len(got) == len(tensors)
        for g, t in zip(got, tensors):
            np.testing.assert_array_equal(np.asarray(g), t)
        # the batching claim: strictly fewer device transfers than tensors
        assert sink.stats.device_puts < len(tensors)
        # one slot, one host->device transfer per dtype group in it
        assert sink.stats.batches == 1
        assert sink.stats.device_puts == len({t.dtype for t in tensors})
        assert sink.stats.reads == len(tensors)
    c.close()


def test_read_tensors_mixed_widths_group_by_dtype():
    """Odd-length narrow tensors between wide ones: each dtype group is
    laid out aligned to its itemsize and carved without a bitcast."""
    import ml_dtypes
    from repro.core.device_direct import DeviceDirectSink
    c = ROS2Client(mode="host", transport="rdma")
    rng = np.random.default_rng(11)
    tensors = [rng.integers(0, 255, (37,)).astype(np.uint8),
               rng.standard_normal((3, 5)).astype(ml_dtypes.bfloat16),
               rng.standard_normal((7, 3)).astype(np.float32),
               rng.integers(0, 255, (5, 3)).astype(np.uint8),
               rng.standard_normal((9,)).astype(ml_dtypes.bfloat16),
               rng.integers(-9, 9, (11,), dtype=np.int32)]
    reqs = []
    for i, t in enumerate(tensors):
        fd = c.open(f"/mixed{i}", create=True)
        c.pwrite(fd, t.tobytes(), 0)
        reqs.append((fd, 0, t.shape, t.dtype))
    with DeviceDirectSink(c, slot_bytes=4096, n_slots=2) as sink:
        got = sink.read_tensors(reqs)
        for g, t in zip(got, tensors):
            assert g.dtype == t.dtype and g.shape == t.shape
            np.testing.assert_array_equal(
                np.asarray(g).view(np.uint8), t.view(np.uint8))
        assert sink.stats.batches == 1
        assert sink.stats.device_puts == 4    # u8, bf16, f32, i32 groups
    c.close()


def test_read_tensors_slot_wrap_reuses_ring_safely():
    from repro.core.device_direct import DeviceDirectSink
    c = ROS2Client(mode="host", transport="rdma")
    rng = np.random.default_rng(10)
    tensors = [rng.integers(0, 1 << 30, (700,), dtype=np.int32)
               for _ in range(9)]                # ~2.7 KiB each
    fd = c.open("/wrap", create=True)
    reqs = []
    off = 0
    for t in tensors:
        c.pwrite(fd, t.tobytes(), off)
        reqs.append((fd, off, t.shape, t.dtype))
        off += t.nbytes
    sink = DeviceDirectSink(c, slot_bytes=3000, n_slots=2)
    got = sink.read_tensors(reqs)                # 9 banks through 2 slots
    for g, t in zip(got, tensors):
        np.testing.assert_array_equal(np.asarray(g), t)
    assert sink.stats.batches == 9
    sink.close()
    c.close()


def test_sink_reuses_client_session_and_close_revokes():
    from repro.core.device_direct import DeviceDirectSink
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/leak", create=True)
    arr = np.arange(256, dtype=np.int32)
    c.pwrite(fd, arr.tobytes(), 0)
    sessions0 = len(c.control._sessions)
    rpc0 = c.control.rpc_count
    sink = DeviceDirectSink(c, slot_bytes=arr.nbytes, n_slots=2)
    # the leak this fixes: a raw connect RPC opening a second session
    assert len(c.control._sessions) == sessions0
    assert c.control.rpc_count == rpc0
    got = sink.read_tensor(fd, 0, arr.shape, np.int32)
    np.testing.assert_array_equal(np.asarray(got), arr)
    ring = sink.ring
    sink.close()
    sink.close()                                 # idempotent
    # capability and registration died with the sink
    with pytest.raises(AccessError):
        c.io.read_into(c.dfs._open[fd].oid, 0, arr.nbytes, ring, 0)
    c.close()


# ---------------------------------------------------------------------------
# Idle-aware MediaScrubber


def test_scrubber_budget_tied_to_device_idle_time():
    store = ObjectStore(make_nvme_array(2))
    cont = store.create_pool("p").create_container(
        "c", replication=2, verified_cache=True)
    obj = cont.object(1)
    for i in range(4):
        obj.update(str(i), AKEY, 0, _payload(1 << 16, seed=i))
        obj.fetch(str(i), AKEY, 0, 1 << 16)      # warm the verified cache
    clock = [0.0]
    s = MediaScrubber(store, budget_bytes=1 << 20, idle_aware=True,
                      util_threshold=0.5, clock=lambda: clock[0])
    s.device_utilization()                       # prime the sampler
    # idle second: full budget, the paced cycle scrubs
    clock[0] += 1.0
    out = s.run_paced_cycle()
    assert out["scanned_bytes"] > 0
    assert s.deferred_cycles == 0
    # saturated second: foreground moved >= threshold of modeled capacity
    cap = sum(d.perf.read_bw for d in store.devices)
    store.devices[0].bytes_read += int(0.8 * cap)
    clock[0] += 1.0
    out = s.run_paced_cycle()
    assert out["scanned_bytes"] == 0             # scrubbing is NOT free now
    assert s.deferred_cycles == 1
    # partially loaded: budget squeezed but nonzero
    store.devices[0].bytes_read += int(0.1 * cap)
    clock[0] += 1.0
    assert 0 < s.idle_budget() < s.budget_bytes
    # idle again: full budget restored
    clock[0] += 1.0
    assert s.idle_budget() == s.budget_bytes
    store.close()


def test_scrubber_starvation_bounded_under_sustained_load():
    """Sustained foreground load may defer paced cycles, but only
    `max_deferrals` in a row — then a floor-budget cycle runs anyway, so
    the silent-corruption window stays bounded."""
    store = ObjectStore(make_nvme_array(2))
    cont = store.create_pool("p").create_container(
        "c", replication=2, verified_cache=True)
    obj = cont.object(1)
    obj.update("0", AKEY, 0, _payload(1 << 16, seed=20))
    obj.fetch("0", AKEY, 0, 1 << 16)
    clock = [0.0]
    s = MediaScrubber(store, budget_bytes=1 << 20, idle_aware=True,
                      max_deferrals=3, clock=lambda: clock[0])
    s.device_utilization()
    cap = sum(d.perf.read_bw for d in store.devices)
    for cycle in range(3):
        store.devices[0].bytes_read += int(2 * cap)   # saturated
        clock[0] += 1.0
        assert s.run_paced_cycle()["scanned_bytes"] == 0
    assert s.deferred_cycles == 3
    store.devices[0].bytes_read += int(2 * cap)       # STILL saturated
    clock[0] += 1.0
    out = s.run_paced_cycle()                         # floor cycle fires
    assert out["scanned_bytes"] > 0
    assert s.deferred_cycles == 3                     # counter reset path
    store.close()


def test_direct_scrub_once_stays_unconditional():
    """Deterministic test/benchmark calls keep working under load."""
    store = ObjectStore(make_nvme_array(2))
    cont = store.create_pool("p").create_container(
        "c", replication=2, verified_cache=True)
    obj = cont.object(1)
    obj.update("0", AKEY, 0, b"z" * 4096)
    obj.fetch("0", AKEY, 0, 4096)
    s = MediaScrubber(store, idle_aware=True)
    store.devices[0].bytes_read += 10 ** 12      # "loaded"
    assert s.scrub_once()["scanned_bytes"] > 0   # explicit call scrubs
    store.close()
