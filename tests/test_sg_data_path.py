"""Scatter-gather data-path tests: vectored transport counters, rkey
cache security, staging-ring concurrency (the no-global-lock assertion),
extent sort invariants, epoch aggregation, batched doorbells, and the
engine checksum <-> fletcher Pallas oracle consistency."""
import threading

import numpy as np
import pytest

from repro.core.client import ROS2Client
from repro.core.data_plane import (AccessError, MemoryRegistry, MTU,
                                   RDMATransport, TCPTransport)
from repro.core.dfs import BLOCK
from repro.core.media import checksum, make_nvme_array
from repro.core.object_store import ObjectStore


def _payload(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


# ---------------------------------------------------------------------------
# Vectored transport counters


def test_sg_counters_rdma_one_rendezvous_per_preadv():
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/sg", create=True)
    data = _payload(4 * BLOCK)
    c.pwrite(fd, data, 0)                        # 1 writev = 1 SG op
    s = c.io.stats
    assert s.sg_ops == 1
    assert s.descriptors == 4                    # one per 1 MiB block
    assert s.rendezvous == 1                     # ONE RTS/CTS for the bulk op
    assert s.rkey_resolves == 1                  # first translation only
    got = c.pread(fd, len(data), 0)              # 1 direct placement op
    assert got == data
    assert s.sg_ops == 2
    assert s.placements == 1                     # server-initiated splice
    assert s.descriptors == 8
    assert s.rendezvous == 2                     # still 1 per vectored op
    # the read translated its DESTINATION rkey (a different capability
    # than the staging rkey): one more resolve, still one per region ever
    assert s.rkey_resolves == 2
    assert s.copy_bytes == s.bytes_moved         # exactly 1 copy per byte
    # a second read over the same destination region: every translation
    # (staging + destination) now comes from the NIC cache
    dst = c.register_region(len(data))
    c.pread_into(fd, len(data), 0, dst, 0)
    c.pread_into(fd, len(data), 0, dst, 0)
    assert s.rkey_resolves == 3                  # dst region granted once
    assert s.rkey_cache_hits >= 1
    assert s.copy_bytes == s.bytes_moved         # STILL 1 copy per byte
    c.close()


def test_sg_counters_tcp_two_copies_per_byte():
    c = ROS2Client(mode="host", transport="tcp")
    fd = c.open("/sg", create=True)
    data = _payload(2 * BLOCK, seed=1)
    c.pwrite(fd, data, 0)
    got = c.pread(fd, len(data), 0)
    assert got == data
    s = c.io.stats
    assert s.sg_ops == 2
    assert s.copy_bytes == 2 * s.bytes_moved     # kernel staging: 2 copies
    assert s.segments == 2 * -(-BLOCK // MTU) * 2  # MTU frames per block
    # sendmsg iovec batching: ONE request message per bulk op (the
    # descriptor list ships as a single msghdr), data still double-copied
    assert s.control_msgs == s.sg_ops
    assert s.sendmsg_batches == s.sg_ops
    c.close()


def test_rkey_cache_respects_revocation_and_expiry():
    cli, srv = MemoryRegistry("cli"), MemoryRegistry("srv")
    dst = srv.register(64 * 1024, "t")
    src = cli.register(64 * 1024, "t")
    x = RDMATransport(cli, srv)
    rk = srv.grant(dst, "rw")
    iov = [(0, src, 0, 4096), (8192, src, 4096, 4096)]
    x.write_sg(rk.token, "t", iov)               # populates the cache
    assert x.stats.rkey_resolves == 1
    x.write_sg(rk.token, "t", iov)
    assert x.stats.rkey_cache_hits == 1
    srv.revoke(rk.token)                         # cache hit must still bite
    with pytest.raises(AccessError):
        x.write_sg(rk.token, "t", iov)
    rk2 = srv.grant(dst, "rw", ttl_s=-1.0)
    with pytest.raises(AccessError):
        x.read_sg(rk2.token, "t", iov)
    # out-of-bounds descriptor rejected even on a cached translation
    rk3 = srv.grant(dst, "rw")
    with pytest.raises(AccessError):
        x.write_sg(rk3.token, "t", [(64 * 1024 - 16, src, 0, 4096)])


def test_rkey_cache_invalidated_on_deregister():
    cli, srv = MemoryRegistry("cli"), MemoryRegistry("srv")
    dst = srv.register(64 * 1024, "t")
    src = cli.register(64 * 1024, "t")
    x = RDMATransport(cli, srv)
    rk = srv.grant(dst, "rw")
    iov = [(0, src, 0, 4096)]
    x.write_sg(rk.token, "t", iov)               # cached translation
    srv.deregister(dst)                          # MPT invalidation on dereg
    with pytest.raises(AccessError):
        x.write_sg(rk.token, "t", iov)


def test_inline_crypto_partial_block_reads():
    """Reads of sub-ranges that differ from the write's block split must
    decrypt with block-absolute keystream offsets."""
    c = ROS2Client(mode="host", transport="rdma", inline_encryption=True)
    fd = c.open("/pc", create=True)
    data = _payload(BLOCK + 4096, seed=9)
    c.pwrite(fd, data, 0)                        # written as (bo=0) blocks
    # read windows at offsets the write never used as block boundaries
    for off, n in [(4096, 4096), (100, 37), (BLOCK - 10, 30), (0, 1)]:
        assert c.pread(fd, n, off) == data[off:off + n], (off, n)
    c.close()


def test_pwritev_multi_buffer_no_hidden_copies():
    """Multi-buffer writev registers each buffer (no concatenation copy):
    the transport counters account for every byte moved exactly once."""
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/mb", create=True)
    bufs = [_payload(BLOCK - 7, seed=10), _payload(BLOCK + 99, seed=11),
            _payload(51, seed=12)]
    total = sum(len(b) for b in bufs)
    c.pwritev(fd, bufs, 0)
    s = c.io.stats
    assert s.sg_ops == 1
    assert s.copy_bytes == s.bytes_moved == total  # 1 counted copy per byte
    assert s.descriptors >= 3                    # per (block, buffer) overlap
    assert c.pread(fd, total, 0) == b"".join(bufs)
    c.close()


def test_tcp_concurrent_streams_no_kernel_buffer_corruption():
    """Two streams through the shared bounded kernel buffer at once: the
    per-segment slice accounting must keep them isolated."""
    cli, srv = MemoryRegistry("cli"), MemoryRegistry("srv")
    x = TCPTransport(cli, srv)
    n = 2 * 1024 * 1024
    srcs = [cli.register(np.full(n, 17, np.uint8), "t"),
            cli.register(np.full(n, 42, np.uint8), "t")]
    dsts = [srv.register(n, "t"), srv.register(n, "t")]
    errs = []

    def stream(i):
        try:
            for _ in range(5):
                x.write(dsts[i], 0, srcs[i], 0, n)
        except Exception as e:  # noqa
            errs.append(e)

    threads = [threading.Thread(target=stream, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    np.testing.assert_array_equal(dsts[0].buf, srcs[0].buf)
    np.testing.assert_array_equal(dsts[1].buf, srcs[1].buf)


# ---------------------------------------------------------------------------
# Staging-ring concurrency (the acceptance assertion: no global-lock
# serialization — asserted structurally, not by timing)


def test_dpu_16_workers_sustain_4_concurrent_preads():
    c = ROS2Client(mode="dpu", transport="rdma", n_dpu_cores=16)
    fd = c.open("/conc", create=True)
    data = _payload(16 * BLOCK, seed=2)
    c.pwrite(fd, data, 0)
    # every direct-splice block fill rendezvouses at a 4-party barrier: if
    # a global lock serialized the preads, fewer than 4 readers could ever
    # be inside the engine fill at once and the barrier would break
    barrier = threading.Barrier(4, timeout=60)
    orig = c.io._fill_direct

    def hooked(obj, oid, b, bo, ln, subs):
        barrier.wait()
        orig(obj, oid, b, bo, ln, subs)

    c.io._fill_direct = hooked
    tags = [c.submit_read(fd, 4 * BLOCK, i * 4 * BLOCK) for i in range(4)]
    done = c.dpu.wait_all(tags, timeout=120)
    c.io._fill_direct = orig
    for i, tag in enumerate(tags):
        assert done[tag].ok, done[tag].error
        assert done[tag].result == data[i * 4 * BLOCK:(i + 1) * 4 * BLOCK]
    assert c.io.max_concurrent_reads >= 4
    c.close()


def test_host_threads_concurrent_preads_make_progress():
    c = ROS2Client(mode="host", transport="rdma", n_staging_slots=8)
    fd = c.open("/t", create=True)
    data = _payload(8 * BLOCK, seed=3)
    c.pwrite(fd, data, 0)
    out = {}

    def reader(i):
        out[i] = c.pread(fd, 4 * BLOCK, i * 4 * BLOCK)

    threads = [threading.Thread(target=reader, args=(i,)) for i in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert out[0] == data[:4 * BLOCK]
    assert out[1] == data[4 * BLOCK:]
    c.close()


# ---------------------------------------------------------------------------
# Extent-sort invariant + epoch aggregation


def test_extent_insert_sorted_matches_shadow_after_1k_overwrites():
    store = ObjectStore(make_nvme_array(4))
    cont = store.create_pool("p").create_container("c")
    obj = cont.object(1)
    span = 4096
    rng = np.random.default_rng(7)
    ops = []
    for epoch in range(1, 1001):
        off = int(rng.integers(0, span - 64))
        size = int(rng.integers(1, 64))
        ops.append((epoch, off, rng.integers(0, 256, size,
                                             dtype=np.uint8).tobytes()))
    shuffled = list(ops)
    rng.shuffle(shuffled)
    for epoch, off, data in shuffled:            # out-of-order arrival
        obj.update("0", "data", off, data, epoch=epoch)
    shadow = bytearray(span)
    for _, off, data in ops:                     # epoch-order replay
        shadow[off:off + len(data)] = data
    got = obj.fetch("0", "data", 0, span)
    assert got == bytes(shadow)
    out = np.empty(span, np.uint8)               # fetch_into agrees
    obj.fetch_into("0", "data", 0, span, out)
    assert out.tobytes() == bytes(shadow)


def test_epoch_aggregation_prunes_and_preserves_reads():
    store = ObjectStore(make_nvme_array(2))
    cont = store.create_pool("p").create_container("c", aggregate=True)
    obj = cont.object(1)
    for i in range(32):
        obj.update("0", "data", 0, bytes([i]) * 256)
    exts = obj._extents[("0", "data")]
    assert len(exts) < 32                        # superseded versions pruned
    assert obj.fetch("0", "data", 0, 256) == bytes([31]) * 256
    # device blocks beyond the grace window were reclaimed
    live_blocks = sum(len(d._blocks) for d in store.devices)
    assert live_blocks <= len(exts) * cont.replication \
        + cont.AGGREGATE_GRACE_EPOCHS * cont.replication


def test_aggregated_client_roundtrip_after_many_overwrites():
    c = ROS2Client(mode="host", transport="rdma")
    fd = c.open("/agg", create=True)
    final = None
    for i in range(10):
        final = _payload(2 * BLOCK + 999, seed=i)
        c.pwrite(fd, final, 0)
    assert c.pread(fd, len(final), 0) == final
    c.close()


# ---------------------------------------------------------------------------
# Vectored DFS API + batched control plane


@pytest.mark.parametrize("mode", ["host", "dpu"])
def test_pwritev_preadv_roundtrip_one_set_size_rpc(mode):
    c = ROS2Client(mode=mode, transport="rdma")
    fd = c.open("/v", create=True)
    bufs = [_payload(BLOCK + 10, seed=4), _payload(17, seed=5),
            _payload(2 * BLOCK, seed=6)]
    before = c.control.rpc_count
    n = c.pwritev(fd, bufs, 0)
    assert n == sum(len(b) for b in bufs)
    # the size delegation (PR 3) holds the update locally: the writev
    # itself is RPC-free, and ONE piggybacked set_size lands at close
    assert c.control.rpc_count == before
    got = c.preadv(fd, [len(b) for b in bufs], 0)
    assert got == bufs
    assert c.dfs.stat("/v")["size"] == n        # local delegation overlay
    c.close_fd(fd)
    assert c.control.rpc_count == before + 1    # the piggybacked flush
    assert c.dfs.stat("/v")["size"] == n        # durable on the server
    c.close()


# ---------------------------------------------------------------------------
# Batched doorbells


def test_submit_many_single_doorbell():
    from repro.core.smartnic import DPURuntime
    dpu = DPURuntime(n_cores=4)
    dpu.register("sq", lambda x: x * x)
    dpu.start()
    before = dpu.doorbells
    tags = dpu.submit_many([("sq", {"x": i}) for i in range(8)])
    assert dpu.doorbells == before + 1           # one SQ crossing for 8 ops
    done = dpu.wait_all(tags)
    assert [done[t].result for t in tags] == [i * i for i in range(8)]
    for i in range(8):                           # scalar submits: 1 each
        dpu.submit("sq", x=i)
    assert dpu.doorbells == before + 9
    dpu.drain(8)
    dpu.stop()


# ---------------------------------------------------------------------------
# Engine checksum == fletcher Pallas kernel oracle


@pytest.mark.parametrize("n", [0, 1, 3, 4, 100, 4096, 8193])
def test_engine_checksum_matches_fletcher_oracle(n):
    fletcher_ref = pytest.importorskip("repro.kernels.fletcher.ref")
    data = _payload(n, seed=n)
    assert checksum(data) == fletcher_ref.fletcher_np(data)
