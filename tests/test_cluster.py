"""Multi-target cluster layer: versioned pool map, jump-consistent
placement, striped per-target data-plane sessions, stale-map
refresh-and-retry, routing stability under target add, cross-target
re-replication, hedged extent reads, and the offloaded write checksum."""
import threading
import time

import numpy as np
import pytest

from repro.core.client import ROS2Client, merge_counters
from repro.core.dfs import AKEY, BLOCK
from repro.core.media import make_nvme_array
from repro.core.object_store import (ObjectStore, StorageCluster,
                                     TargetDownError, jump_hash,
                                     placement_order)


def _payload(n, seed=0):
    return bytes(np.random.default_rng(seed).integers(0, 256, n,
                                                      dtype=np.uint8))


def _wait(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return False


# ---------------------------------------------------------------------------
# Placement: deterministic, balanced, minimally disruptive


def test_jump_hash_deterministic_and_in_range():
    for n in (1, 2, 3, 7):
        for k in range(100):
            b = jump_hash(k * 0x9E3779B97F4A7C15, n)
            assert 0 <= b < n
            assert b == jump_hash(k * 0x9E3779B97F4A7C15, n)


def test_placement_order_covers_all_targets():
    order = placement_order(4, 123, "17")
    assert sorted(order) == [0, 1, 2, 3]
    assert order == placement_order(4, 123, "17")     # stable


def test_placement_stability_under_target_add():
    """Jump-consistent hashing: growing 2 -> 3 targets moves only ~1/3 of
    the keys (bounded well under a full reshuffle), and every unmoved key
    keeps its exact primary."""
    keys = [(oid, str(b)) for oid in (100, 101, 102) for b in range(100)]
    before = {k: placement_order(2, *k)[0] for k in keys}
    after = {k: placement_order(3, *k)[0] for k in keys}
    moved = sum(before[k] != after[k] for k in keys)
    assert moved / len(keys) < 0.5            # ~1/3 expected, never half
    for k in keys:
        if before[k] != after[k]:
            assert after[k] == 2              # keys only move to the NEW one


def test_placement_spreads_blocks():
    primaries = {placement_order(2, 100, str(b))[0] for b in range(64)}
    assert primaries == {0, 1}


@pytest.mark.parametrize("n,doms", [
    (2, None), (8, ("a", "a", "b", "b", "c", "c", "d", "d"))],
    ids=["flat_2", "domains_8"])
def test_placement_population_spread_near_linear(n, doms):
    """Striped reads scale with the fleet only as far as primaries
    spread: over a 64-object x 64-block population no target is primary
    for more than 1 / (0.8 n) of the blocks (>= 0.8x linear), with or
    without fault-domain labels."""
    counts = [0] * n
    for oid in range(1, 65):
        for b in range(64):
            counts[placement_order(n, oid, str(b), doms)[0]] += 1
    assert max(counts) / (64 * 64) <= 1 / (0.8 * n), counts


def test_router_memoizes_placement_and_invalidates_on_map_change():
    """The router computes placement_order once per (oid, dkey) and
    serves repeats from an LRU keyed off the adopted map version: a
    re-read of the same blocks is all cache hits, and a membership
    change (target add) drops the cache so every block re-routes against
    the NEW fleet — the rebalance and correctness proof rides the
    existing add-target test; here the counter proves the memoization
    actually fired and the invalidation actually emptied it."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/memo", create=True)
    data = _payload(6 * BLOCK, seed=19)
    c.pwrite(fd, data, 0)
    assert c.pread(fd, len(data), 0) == data
    hits = c.io.placement_cache_hits
    assert hits >= 6                          # re-read served from cache
    assert c.io.data_path_counters()["cluster"]["placement_cache_hits"] \
        == c.io.placement_cache_hits
    c.add_target()                            # map version bump: the
    assert c.pread(fd, len(data), 0) == data  # adopt drops the cache and
    for key, order in c.io._place_cache.items():   # every route is re-
        assert sorted(order) == [0, 1, 2]     # computed on the NEW fleet
    assert c.pread(fd, len(data), 0) == data  # ...then hits again
    assert c.io.placement_cache_hits > hits
    c.close()


# ---------------------------------------------------------------------------
# Striped data path through the router


@pytest.mark.parametrize("transport", ["rdma", "tcp"])
def test_striped_roundtrip_and_fleet_counters(transport):
    c = ROS2Client(mode="host", transport=transport, n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(5 * BLOCK + 12345, seed=1)
    c.pwrite(fd, data, 0)
    assert c.pread(fd, len(data), 0) == data
    # blocks really striped: every target's container holds extents
    held = [sum(len(lst) for o in c.ccontainer.target(t.target_id)
                ._objects.values() for lst in o._extents.values())
            for t in c.cluster.targets]
    assert all(h > 0 for h in held), held
    # counters merged fleet-wide: engine checksum bytes covers all targets
    dpc = c.io.data_path_counters()
    assert dpc["engine"]["checksum_bytes"] >= len(data)
    assert dpc["cluster"]["targets"] == 2
    assert dpc["cluster"]["targets_up"] == 2
    c.close()


def test_striped_readv_into_and_preadv():
    c = ROS2Client(mode="host", transport="rdma", n_targets=3,
                   scrub_interval_s=None)
    fd = c.open("/v", create=True)
    data = _payload(3 * BLOCK, seed=2)
    c.pwritev(fd, [data[:BLOCK], data[BLOCK:]], 0)
    parts = c.preadv(fd, [BLOCK // 2, BLOCK, len(data) - 3 * BLOCK // 2],
                     0)
    assert b"".join(parts) == data
    c.close()


def test_merge_counters_sums_numeric_leaves():
    a = {"x": 1, "sub": {"y": 2.5, "name": "a"}}
    b = {"x": 2, "sub": {"y": 1.5, "z": 1}, "w": 4}
    m = merge_counters([a, b])
    assert m == {"x": 3, "sub": {"y": 4.0, "name": "a", "z": 1}, "w": 4}


# ---------------------------------------------------------------------------
# Pool-map lifecycle: stale refresh-and-retry, push invalidation, add


def test_stale_map_refresh_and_retry():
    """A LOST invalidation (notify=False) leaves the router routing to a
    dead target; the session rejects with TargetDownError and the router
    recovers with exactly ONE get_pool_map refresh + one re-route — not a
    failure."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(4 * BLOCK, seed=3)
    c.pwrite(fd, data, 0)
    c.cluster.fail_target(1, notify=False)    # map bumps, push "lost"
    refreshes0 = c.io.map_refreshes
    data2 = _payload(4 * BLOCK, seed=4)
    c.pwrite(fd, data2, 0)                    # stale route -> refresh+retry
    assert c.io.target_retries == 1
    assert c.io.map_refreshes == refreshes0 + 1
    assert c.pread(fd, len(data2), 0) == data2
    # everything now lands on the surviving target
    t0 = c.ccontainer.target(0)
    n0 = sum(len(lst) for o in t0._objects.values()
             for lst in o._extents.values())
    assert n0 >= 4
    c.close()


def test_map_push_invalidation_avoids_the_trip():
    """With the push DELIVERED, the router refreshes before routing: the
    op never hits the dead target at all (no retry)."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    c.pwrite(fd, _payload(2 * BLOCK, seed=5), 0)
    c.cluster.fail_target(1)                  # push received
    assert c.io.map_invalidations >= 1
    c.pwrite(fd, _payload(2 * BLOCK, seed=6), 0)
    assert c.io.target_retries == 0
    c.close()


def test_target_add_discovers_session_and_routes():
    """Runtime target ADD: the map push marks the router stale, the next
    op refreshes, a session for the new target is built lazily (staging
    rkey granted via one RPC), and new writes stripe onto it. Pre-add data
    stays fully readable: the keys jump-hash moves to the newcomer
    (~1/(n+1)) are REBALANCED onto it by the add, the rest never move."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/old", create=True)
    old = _payload(6 * BLOCK, seed=7)
    c.pwrite(fd, old, 0)
    tid = c.add_target()
    assert tid == 2
    assert c.pread(fd, len(old), 0) == old    # rebalance kept every byte
    # a big new file reaches the new target too
    fd2 = c.open("/new", create=True)
    new = _payload(8 * BLOCK, seed=8)
    c.pwrite(fd2, new, 0)
    assert tid in c.io.sessions               # session built lazily
    assert c.pread(fd2, len(new), 0) == new
    held = sum(len(lst)
               for o in c.ccontainer.target(tid)._objects.values()
               for lst in o._extents.values())
    assert held > 0                           # newcomer actually serves
    c.close()


def test_add_target_refused_on_unrouted_client():
    """A single-target client's io is the bare session pinned to target 0;
    growing the fleet under it would rebalance blocks somewhere it can
    never route to — refused up front, data untouched."""
    c = ROS2Client(mode="host", transport="rdma", scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(2 * BLOCK, seed=42)
    c.pwrite(fd, data, 0)
    with pytest.raises(RuntimeError, match="routed client"):
        c.add_target()
    assert c.pread(fd, len(data), 0) == data
    c.close()


def test_get_pool_map_rpc_serves_redundancy_class():
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   replication=2, scrub_interval_s=None)
    r = c.control.rpc("get_pool_map", session_id=c.session_id)
    assert r["ok"]
    assert len(r["targets"]) == 2
    assert r["redundancy"]["pool0/cont0"]["replication"] == 2
    v0 = r["version"]
    c.cluster.fail_target(1)
    r2 = c.control.rpc("get_pool_map", session_id=c.session_id)
    assert r2["version"] > v0
    assert [t["up"] for t in sorted(r2["targets"],
                                    key=lambda t: t["target_id"])] \
        == [True, False]
    c.close()


# ---------------------------------------------------------------------------
# Cross-target re-replication + post-recovery resync


def test_cross_target_rereplication_after_post_ack_demotion():
    """A post-ack replica failure whose engine has NO spare device left
    escalates to the cluster: the extent is re-homed on a peer target, so
    redundancy is restored fleet-wide instead of silently degrading."""
    cluster = StorageCluster(n_targets=2, n_devices=2)
    cc = cluster.create_pool("p").create_container(
        "c", replication=2, verified_cache=True, write_quorum=1)
    cont = cc.target(0)
    obj = cont.object(1)
    targets = [d for d in cont.placement(1, "0") if d.alive][:2]
    victim = targets[-1]
    orig_write = victim.write
    gate = threading.Event()

    def slow_failing_write(key, data, lease=None, pre_pinned=False):
        gate.wait(5.0)                        # fail AFTER the quorum ack
        raise IOError("injected straggler media failure")

    victim.write = slow_failing_write
    data = _payload(1 << 16, seed=9)
    obj.update("0", AKEY, 0, data)            # returns at quorum 1/2
    gate.set()
    assert _wait(lambda: cluster.stats.cross_target_rereplications >= 1)
    victim.write = orig_write
    # the extent was demoted locally (no spare in a 2-device engine)...
    ext = obj._extents[("0", AKEY)][0]
    assert victim.name not in ext.block_keys
    # ...and re-homed on the PEER target, fully readable there
    peer = cc.target(1).peek_object(1)
    assert peer is not None
    assert peer.fetch("0", AKEY, 0, len(data)) == data
    cluster.close()


def test_recover_resync_moves_outage_writes_home():
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(4 * BLOCK, seed=10)
    c.pwrite(fd, data, 0)
    c.cluster.fail_target(1)
    data2 = _payload(4 * BLOCK, seed=11)
    c.pwrite(fd, data2, 0)                    # all blocks land on target 0
    moved = c.cluster.recover_target(1)
    assert moved >= 1                         # failover writes went home
    assert c.pread(fd, len(data2), 0) == data2
    # the recovered target again holds its placement-primary blocks
    oid = c.dfs.stat("/f")["oid"]
    homes = {b: placement_order(2, oid, str(b))[0] for b in range(4)}
    t1 = c.ccontainer.target(1).peek_object(oid)
    assert t1 is not None
    for b, home in homes.items():
        if home == 1:
            assert (str(b), AKEY) in t1._extents
    c.close()


def test_fleetwide_unlink_and_truncate():
    """DFS metadata ops fan out across targets: truncate punches striped
    blocks wherever they live; unlink reclaims capacity on every engine
    (tombstoned fleet-wide)."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(4 * BLOCK, seed=12)
    c.pwrite(fd, data, 0)
    c.close_fd(fd)
    c.truncate("/f", BLOCK)                   # blocks 1..3 punched
    fd = c.open("/f")
    assert c.pread(fd, BLOCK, 0) == data[:BLOCK]
    assert c.pread(fd, BLOCK, 2 * BLOCK) == b"\x00" * BLOCK
    c.unlink("/f")
    for t in c.cluster.targets:
        used = sum(d.used_bytes() for d in t.store.devices)
        assert used == 0, (t.target_id, used)
    c.close()


# ---------------------------------------------------------------------------
# Extent-level hedged reads


def test_hedged_read_races_second_replica():
    store = ObjectStore(make_nvme_array(4))
    cont = store.create_pool("p").create_container("c", replication=2)
    obj = cont.object(1)
    data = _payload(1 << 16, seed=13)
    obj.update("0", AKEY, 0, data)
    ext = obj._extents[("0", AKEY)][0]
    primary = next(iter(ext.block_keys))
    store.device(primary).read_delay_s = 0.2
    # hedging OFF: the read pays the straggler
    t0 = time.monotonic()
    assert obj.fetch("0", AKEY, 0, len(data)) == data
    assert time.monotonic() - t0 >= 0.2
    assert store.stats.hedges_issued == 0
    # hedging ON: the second replica wins at extent granularity
    store.hedge_timeout_s = 0.02
    t0 = time.monotonic()
    assert obj.fetch("0", AKEY, 0, len(data)) == data
    assert time.monotonic() - t0 < 0.15
    assert store.stats.hedges_issued == 1
    assert store.stats.hedges_won == 1
    store.device(primary).read_delay_s = 0.0
    store.close()


def test_hedged_read_fast_primary_never_hedges():
    store = ObjectStore(make_nvme_array(4))
    store.hedge_timeout_s = 0.1
    cont = store.create_pool("p").create_container("c", replication=2)
    obj = cont.object(1)
    data = _payload(4096, seed=14)
    obj.update("0", AKEY, 0, data)
    for _ in range(5):
        assert obj.fetch("0", AKEY, 0, len(data)) == data
    assert store.stats.hedges_issued == 0
    store.close()


def test_client_hedge_config_reaches_every_target():
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   hedge_timeout_s=0.05, scrub_interval_s=None)
    assert all(t.store.hedge_timeout_s == 0.05 for t in c.cluster.targets)
    c.configure_hedged_reads(None)
    assert all(t.store.hedge_timeout_s is None for t in c.cluster.targets)
    c.close()


# ---------------------------------------------------------------------------
# Offloaded write-path checksum (quorum fan-out)


def test_checksum_offloaded_on_quorum_fanout():
    store = ObjectStore(make_nvme_array(3))
    cont = store.create_pool("p").create_container("c", replication=3)
    obj = cont.object(1)                      # majority quorum: 2 < 3
    data = _payload(1 << 16, seed=15)
    obj.update("0", AKEY, 0, data)
    assert store.stats.checksum_offloads == 1
    assert store.stats.checksum_bytes >= len(data)
    # the stored csum is the real one: a verified read passes, and a
    # corrupted replica is detected
    assert obj.fetch("0", AKEY, 0, len(data)) == data
    ext = obj._extents[("0", AKEY)][0]
    name, key = next(iter(ext.block_keys.items()))
    dev = store.device(name)
    dev._blocks[key] = bytes(len(data))       # silent corruption
    assert obj.fetch("0", AKEY, 0, len(data)) == data   # rerouted replica
    store.close()


def test_checksum_stays_inline_at_replication_two():
    """The replication-2 default commits inline (quorum == width): no
    offload, no change to its latency profile — the satellite's gate."""
    store = ObjectStore(make_nvme_array(4))
    cont = store.create_pool("p").create_container("c", replication=2)
    obj = cont.object(1)
    obj.update("0", AKEY, 0, _payload(1 << 16, seed=16))
    assert store.stats.checksum_offloads == 0
    assert store.stats.checksum_bytes >= 1 << 16
    store.close()


# ---------------------------------------------------------------------------
# The whole stack, routed: dpu mode + direct-read gates on 2 targets


def test_dpu_mode_two_targets_roundtrip():
    c = ROS2Client(mode="dpu", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(3 * BLOCK, seed=17)
    c.pwrite(fd, data, 0)
    assert c.pread(fd, len(data), 0) == data
    c.close()


def test_routed_warm_reads_translate_once_and_skip_checksums():
    """On an 8-target four-domain fleet every target serves placements,
    each bulk op pays ONE rendezvous, each session translates each of its
    two regions (staging ring, read sink) once ever, and a warm re-read
    runs no checksum: the verified-extent cache is on for the client."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=8,
                   domains=["a", "a", "b", "b", "c", "c", "d", "d"],
                   scrub_interval_s=None)
    fd = c.open("/warm", create=True)
    data = _payload(32 * BLOCK, seed=19)
    for off in range(0, len(data), 8 * BLOCK):
        c.pwrite(fd, data[off:off + 8 * BLOCK], off)
    sink = c.register_region(len(data))
    c.pread_into(fd, len(data), 0, sink, 0)      # cold: verifies + caches
    warm = c.io.data_path_counters()
    c.pread_into(fd, len(data), 0, sink, 0)
    after = c.io.data_path_counters()
    assert bytes(sink.buf) == data
    assert after["engine"]["checksum_bytes"] \
        == warm["engine"]["checksum_bytes"]
    assert after["engine"]["checksum_skipped_bytes"] \
        - warm["engine"]["checksum_skipped_bytes"] >= len(data)
    tr = after["transport"]
    assert tr["rendezvous"] == tr["sg_ops"]
    assert tr["rkey_resolves"] <= 2 * 8
    assert all(s.stats.placed_bytes > 0 for s in c.io.sessions.values())
    c.close()


def test_striped_direct_reads_keep_one_copy_zero_acquires():
    """The PR-4 one-copy read gates survive striping: a routed read over 2
    targets still places engine bytes straight into caller memory — zero
    staging acquires, bounce-free."""
    c = ROS2Client(mode="host", transport="rdma", n_targets=2,
                   scrub_interval_s=None)
    fd = c.open("/f", create=True)
    data = _payload(4 * BLOCK, seed=18)
    c.pwrite(fd, data, 0)
    sink = c.register_region(len(data))
    before = c.io.data_path_counters()
    c.pread_into(fd, len(data), 0, sink, 0)
    after = c.io.data_path_counters()
    assert bytes(sink.buf) == data
    assert after["staging"]["acquires"] == before["staging"]["acquires"]
    assert after["staging"]["bounce_bytes"] \
        == before["staging"]["bounce_bytes"]
    placed = after["transport"]["placed_bytes"] \
        - before["transport"]["placed_bytes"]
    assert placed == len(data)
    c.close()
