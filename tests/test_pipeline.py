"""Data-pipeline tests: determinism, coverage, disjointness, elastic
resharding, hedged reads, stall accounting."""
import time

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.client import ROS2Client
from repro.data.pipeline import (Assignment, ROS2TokenLoader, coverage_check,
                                 read_meta, write_token_shards)


@pytest.fixture
def corpus_client():
    # per test, so the leak witness tracks the client and closes it: the
    # loader's reads lazily start the client's dispatch pool, whose
    # threads live until the client closes
    client = ROS2Client(mode="host", transport="rdma")
    tokens = np.arange(40_000, dtype=np.int32) % 997
    write_token_shards(client, "/data", tokens, shard_tokens=4096)
    return client, tokens


def test_meta_roundtrip(corpus_client):
    client, tokens = corpus_client
    meta = read_meta(client, "/data")
    assert meta["total_tokens"] == tokens.size
    assert meta["n_shards"] == -(-tokens.size // 4096)


def test_loader_contents_match_corpus(corpus_client):
    client, tokens = corpus_client
    ld = ROS2TokenLoader(client, "/data", global_batch=4, seq_len=33)
    b = ld.next_batch()
    assert b["tokens"].shape == (4, 33)
    # each row must be a contiguous corpus slice with labels shifted by one
    for row_t, row_l in zip(b["tokens"], b["labels"]):
        start = int(row_t[0])  # corpus is arange % 997: recover index mod 997
        np.testing.assert_array_equal(row_l[:-1], row_t[1:])
        # verify against the actual corpus (find the sample boundary)
        matches = np.where(tokens[:-34] == row_t[0])[0]
        assert any((tokens[m:m + 33] == row_t).all()
                   and tokens[m + 33] == row_l[-1]
                   for m in matches if m % 34 == 0)
    ld.close()


def test_sample_spans_shard_boundary(corpus_client):
    client, tokens = corpus_client
    # seq 127 -> sample_tokens 128; shard=4096 tokens => every 32nd sample
    # spans a boundary... use odd seq to force unaligned spans
    ld = ROS2TokenLoader(client, "/data", global_batch=2, seq_len=100)
    for _ in range(4):
        b = ld.next_batch()
        for row_t in b["tokens"]:
            m = np.where(tokens[:-101] == row_t[0])[0]
            assert any((tokens[i:i + 100] == row_t).all() for i in m)
    ld.close()


def test_rank_disjointness_and_determinism(corpus_client):
    client, _ = corpus_client
    lds = [ROS2TokenLoader(client, "/data", global_batch=8, seq_len=31,
                           dp_rank=r, dp_size=4, seed=7) for r in range(4)]
    batches = [ld.next_batch() for ld in lds]
    rows = np.concatenate([b["tokens"] for b in batches])
    assert len(np.unique(rows[:, 0], axis=0)) >= 7   # near-certainly distinct
    # determinism: a fresh loader with the same seed yields the same batch
    ld2 = ROS2TokenLoader(client, "/data", global_batch=8, seq_len=31,
                          dp_rank=0, dp_size=4, seed=7)
    np.testing.assert_array_equal(ld2.next_batch()["tokens"],
                                  batches[0]["tokens"])
    for ld in lds + [ld2]:
        ld.close()


@given(st.integers(1, 8), st.integers(1, 4))
@settings(max_examples=20, deadline=None)
def test_assignment_coverage(dp_size, mult):
    gb = dp_size * mult
    assert coverage_check(n_samples=gb * 5 + 3, global_batch=gb,
                          dp_size=dp_size)


def test_elastic_reshard_preserves_coverage():
    # 4 ranks -> 2 ranks mid-epoch: the union of what the 2 survivors read
    # from the reshard point equals the full global batches
    n, gb = 64, 8
    a_before = [Assignment(n, gb, r, 4, 0, 0) for r in range(4)]
    a_after = [Assignment(n, gb, r, 2, 0, 0) for r in range(2)]
    step = 3
    got = np.concatenate([a.samples_for_step(step) for a in a_after])
    want = np.concatenate([a.samples_for_step(step) for a in a_before])
    assert set(got) == set(want)                     # same global batch
    assert len(np.unique(got)) == gb                 # no duplication


def test_loader_reshard_runtime(corpus_client):
    client, _ = corpus_client
    ld = ROS2TokenLoader(client, "/data", global_batch=4, seq_len=15,
                         dp_rank=0, dp_size=1)
    ld.next_batch()
    ld.reshard(dp_rank=1, dp_size=2)
    b = ld.next_batch()
    assert b["tokens"].shape == (2, 15)              # local batch shrank
    ld.close()


def test_hedged_reads_fire_on_straggler():
    """hedge_timeout_s arms EXTENT-level hedging in the engine: the
    primary replica's device stalls, _read_extent races the second
    replica's target, and hedges_won counts at extent granularity."""
    client = ROS2Client(mode="host", transport="rdma")
    tokens = np.arange(4096, dtype=np.int32) % 997   # ONE shard, one extent
    write_token_shards(client, "/hedge", tokens, shard_tokens=4096)
    # stall the extent's PRIMARY replica device (first in replica order)
    oid = client.dfs.stat("/hedge/shard-00000")["oid"]
    obj = client.container.object(oid)
    ext = obj._extents[("0", "data")][0]
    primary = next(iter(ext.block_keys))
    client.store.device(primary).read_delay_s = 0.2
    ld = ROS2TokenLoader(client, "/hedge", global_batch=1, seq_len=15,
                         hedge_timeout_s=0.02)
    b = ld.next_batch()
    assert b["tokens"].shape == (1, 15)
    assert ld.hedges_issued >= 1
    assert ld.hedges_won >= 1
    ld.close()
    client.store.device(primary).read_delay_s = 0.0
    client.close()


def test_hedged_reads_whole_op_fallback(corpus_client):
    """A client without engine-level hedging keeps the old whole-op
    duplication (first completion wins)."""
    client, _ = corpus_client
    slow = {"n": 0}

    def delay_hook(shard, off, tag):
        # primary attempt of the first read stalls; the hedge (tag=1) wins
        if tag == 0 and slow["n"] == 0:
            slow["n"] += 1
            time.sleep(0.4)

    class NoEngineHedge:
        """Duck-typed view of the client hiding configure_hedged_reads."""
        def __init__(self, c):
            self._c = c

        def __getattr__(self, name):
            if name == "configure_hedged_reads":
                raise AttributeError(name)
            return getattr(self._c, name)

    ld = ROS2TokenLoader(NoEngineHedge(client), "/data", global_batch=1,
                         seq_len=15, hedge_timeout_s=0.05,
                         read_delay_hook=delay_hook)
    b = ld.next_batch()
    assert b["tokens"].shape == (1, 15)
    assert ld.hedges_issued >= 1
    assert ld.hedges_won >= 1
    ld.close()


def test_stall_accounting(corpus_client):
    client, _ = corpus_client
    ld = ROS2TokenLoader(client, "/data", global_batch=2, seq_len=15,
                         prefetch=2)
    t0 = time.monotonic()
    for _ in range(3):
        ld.next_batch()
        time.sleep(0.05)       # "compute": prefetch should hide read time
    m = ld.metrics()
    assert m["stall_s"] < (time.monotonic() - t0)
    assert m["bytes_read"] > 0
    ld.close()


def test_loader_survives_concurrent_bulk_checkpoint():
    """Regression (found by the 300-step 100M run): a large checkpoint
    save sharing the DPU data plane must not starve loader reads past
    their timeout — checkpoint writes are chunked and the producer
    retries transient stalls."""
    import jax.numpy as jnp
    from repro.core.client import ROS2Client
    from repro.distributed.checkpoint import ROS2CheckpointManager

    client = ROS2Client(mode="dpu", transport="rdma")
    tokens = np.arange(60_000, dtype=np.int32) % 523
    write_token_shards(client, "/data", tokens, shard_tokens=8192)
    ld = ROS2TokenLoader(client, "/data", global_batch=2, seq_len=64,
                         prefetch=2)
    mgr = ROS2CheckpointManager(client, "/ckpt", asynchronous=True)
    big = {"w": jnp.ones((24, 1 << 20), jnp.float32)}      # 96 MB payload
    mgr.save(1, big)                                       # async, in flight
    for _ in range(6):                                     # reads interleave
        b = ld.next_batch(timeout=60.0)
        assert b["tokens"].shape == (2, 64)
    mgr.wait()
    assert not ld.failed
    step, got = mgr.restore(big)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got["w"]).ravel()[:4],
                                  np.ones(4, np.float32))
    ld.close()
    client.close()
