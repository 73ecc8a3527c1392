"""Bring-up smoke of the store's device path on a TPU.

    python3 chip_smoke.py              # phases 0-4 on one chip
    python3 chip_smoke.py --chips 4    # sharded weight placement, 4 chips

One process, no children: a chip belongs to one process at a time.
Each phase drives the store through the entry points its users call and
checks the result against a plain reference; any failed check raises
and the script exits non-zero.

  0. device      the first device must be a TPU (never carries on on the
                 CPU); the persistent compile cache is set up.
  1. ingest      dense-100m at full width, dpu/rdma client: a seeded
                 corpus written as token shards, the loader's batches
                 checked against numpy slices of the corpus taken from
                 the `Assignment` permutation, optimizer steps on the
                 chip with a finite loss.
  2. checkpoint  params + Adam state saved into the store and restored
                 bit-identical to the device state.
  3. ec          ec(4,2) on 8 targets in four fault domains: seeded
                 bytes written and read back, stored parity against the
                 numpy oracle, a delta-path sub-cell overwrite, a read
                 with two targets down, rebuild after an outage write,
                 a parity scrub, and the parity kernel compiled (not
                 interpreted).
  4. placement   every parameter leaf stored as a file and landed in HBM
                 through `DeviceDirectSink.read_tensors`.

`--chips 4` runs only the sharded placement of the dense-100m weights
onto a 4-chip `NamedSharding`, compared shard by shard with
`jax.device_put` of the host reference.

Wall times printed here are host-clock bring-up timings, not benchmark
numbers. The last line of stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, List

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.common.compile_cache import enable_compile_cache  # noqa: E402
from repro.common.config import TrainConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.client import ROS2Client  # noqa: E402
from repro.core.device_direct import DeviceDirectSink  # noqa: E402
from repro.core.dfs import BLOCK  # noqa: E402
from repro.data.pipeline import (Assignment, ROS2TokenLoader,  # noqa: E402
                                 write_token_shards)
from repro.distributed.checkpoint import ROS2CheckpointManager  # noqa: E402
from repro.kernels.fletcher import ops as fletcher_ops  # noqa: E402
from repro.kernels.rs_parity import ops as rs_ops  # noqa: E402
from repro.kernels.rs_parity import ref as rs_ref  # noqa: E402
from repro.kernels.stream_cipher import ops as cipher_ops  # noqa: E402
from repro.launch.mesh import make_host_mesh_ctx  # noqa: E402
from repro.models.api import ModelAPI  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.train.optimizer import init_adam  # noqa: E402
from repro.train.trainer import make_train_step  # noqa: E402

ARCH = "dense-100m"
STEPS, BATCH, SEQ = 5, 8, 256       # batch x seq of examples/train_100m_ros2.py
CORPUS_TOKENS = 16 << 20            # 64 MiB of int32 tokens
EC_BYTES = 256 << 20
SLOT_BYTES = 128 << 20
EC_DOMAINS = ("a", "a", "b", "b", "c", "c", "d", "d")


class SmokeError(AssertionError):
    """A phase's result disagrees with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def same_bits(a, b) -> bool:
    """Bit-identical arrays (NaN payloads included)."""
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def on_device(arr, dev) -> bool:
    return arr.devices() == {dev}


class Compiles:
    """Counts backend compiles (persistent-cache hits included) and
    persistent-cache hits through jax.monitoring."""

    def __init__(self) -> None:
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@contextmanager
def timed(name: str, times: Dict[str, float]):
    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    yield
    times[name] = time.perf_counter() - t0
    print(f"[{name}] ok", flush=True)


# ---------------------------------------------------------------------------
# phase 0


def device_phase(chips: int) -> jax.Device:
    devs = jax.devices()
    d0 = devs[0]
    print(f"[device] platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (first device is "
                         f"{d0.platform}); refusing to run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    print(f"[device] compile cache: {enable_compile_cache()}", flush=True)
    return d0


def assert_kernels_compiled() -> None:
    """On the chip no kernel may take its interpret branch."""
    for mod in (rs_ops, fletcher_ops, cipher_ops):
        check(mod._interpret_default() is False,
              f"{mod.__name__} would run in interpret mode")


# ---------------------------------------------------------------------------
# phase 1


def ingest_train_phase(client, *, arch: str, n_tokens: int, batch: int,
                       seq: int, steps: int, seed: int):
    """Token shards -> loader -> jitted train step. Returns (params, opt,
    losses)."""
    cfg = get_config(arch)
    api = ModelAPI(cfg)
    mctx = make_host_mesh_ctx(cfg)
    corpus = np.random.default_rng(seed).integers(
        0, cfg.vocab, n_tokens, dtype=np.int32)
    write_token_shards(client, "/data", corpus)
    tcfg = TrainConfig(lr=1e-3, total_steps=steps, warmup_steps=1)
    step_fn = jax.jit(make_train_step(api, tcfg, mctx))
    params = init_params(api.param_defs(), jax.random.PRNGKey(seed),
                         jnp.dtype(cfg.param_dtype))
    opt = init_adam(params)
    loader = ROS2TokenLoader(client, "/data", global_batch=batch,
                             seq_len=seq, seed=seed, prefetch=2,
                             hedge_timeout_s=0.5)
    sample = seq + 1
    ref_asg = Assignment(n_tokens // sample, batch, 0, 1, seed, 0)
    check(ref_asg.steps_per_epoch() >= steps, "corpus too small for steps")
    losses: List[float] = []
    try:
        for t in range(steps):
            got = loader.next_batch()
            idx = ref_asg.samples_for_step(t)
            rows = corpus[idx[:, None] * sample + np.arange(sample)]
            check(np.array_equal(got["tokens"], rows[:, :-1])
                  and np.array_equal(got["labels"], rows[:, 1:]),
                  f"loader batch {t} differs from the corpus slices")
            t0 = time.perf_counter()
            params, opt, metrics = step_fn(params, opt, got)
            loss = float(metrics["loss"])
            print(f"[ingest] step {t} loss={loss:.4f} "
                  f"host_s={time.perf_counter() - t0:.6f}", flush=True)
            check(bool(np.isfinite(loss)), f"loss {loss} at step {t}")
            losses.append(loss)
    finally:
        loader.close()
    return params, opt, losses


# ---------------------------------------------------------------------------
# phase 2


def checkpoint_phase(client, state, step: int) -> int:
    """Save + wait + restore; every leaf bit-identical to the device
    state. Returns the bytes written."""
    ckpt = ROS2CheckpointManager(client, "/ckpt", keep=1)
    ckpt.save(step, state)
    ckpt.wait()
    got_step, restored = ckpt.restore(state)
    check(got_step == step, f"restored step {got_step}, saved {step}")
    want = jax.tree.leaves(state)
    got = jax.tree.leaves(restored)
    check(len(want) == len(got), "restored tree has other leaves")
    for i, (w, g) in enumerate(zip(want, got)):
        check(same_bits(g, np.asarray(w)), f"checkpoint leaf {i} differs")
    return ckpt.bytes_written


# ---------------------------------------------------------------------------
# phase 3


def _stripe_cells(c, oid: int, b: int, k: int, p: int,
                  cs: int) -> np.ndarray:
    """The k+p stored cells of stripe b, read from their home targets."""
    order = c.io._ec_order(oid, b)
    return np.stack([c.io.sessions[order[i]].fetch_cell(oid, b, i * cs, cs)
                     for i in range(k + p)])


def _expected_cells(shadow: bytearray, b: int, k: int, p: int,
                    cs: int) -> np.ndarray:
    data = np.frombuffer(bytes(shadow[b * BLOCK:(b + 1) * BLOCK]),
                         np.uint8).reshape(k, cs)
    return np.concatenate([data, rs_ref.rs_encode_np(data, p)])


def ec_phase(*, nbytes: int, seed: int, samples: int = 8) -> Dict[str, int]:
    c = ROS2Client(mode="host", transport="rdma", n_targets=8, ec=(4, 2),
                   domains=EC_DOMAINS, scrub_interval_s=None)
    try:
        return _ec_checks(c, nbytes=nbytes, seed=seed, samples=samples)
    finally:
        c.close()


def _ec_checks(c, *, nbytes: int, seed: int, samples: int) -> Dict[str, int]:
    k, p, cs = c.io._ec
    rng = np.random.default_rng(seed)
    n_stripes = -(-nbytes // BLOCK)
    shadow = bytearray(rng.bytes(nbytes))
    fd = c.open("/ec", create=True)
    chunk = 16 * BLOCK
    for off in range(0, nbytes, chunk):
        c.pwrite(fd, bytes(shadow[off:off + chunk]), off)
    check(c.pread(fd, nbytes, 0) == bytes(shadow), "EC readback differs")
    oid = c.stat("/ec")["oid"]
    c.io._ec_drain()
    # stored parity of sampled full stripes against the numpy oracle
    full = nbytes // BLOCK
    for b in rng.choice(full, size=min(samples, full), replace=False):
        b = int(b)
        check(np.array_equal(_stripe_cells(c, oid, b, k, p, cs),
                             _expected_cells(shadow, b, k, p, cs)),
              f"stripe {b} cells differ from the rs_parity oracle")
    # sub-cell overwrite takes the delta-parity path
    d0 = c.io.ec_delta_writes
    off = 5 * BLOCK + cs // 2 + 123
    patch = rng.bytes(4096)
    c.pwrite(fd, patch, off)
    shadow[off:off + len(patch)] = patch
    check(c.io.ec_delta_writes > d0, "sub-cell overwrite skipped the "
          "delta path")
    check(c.pread(fd, 2 * BLOCK, 5 * BLOCK)
          == bytes(shadow[5 * BLOCK:7 * BLOCK]), "delta readback differs")
    c.io._ec_drain()
    check(np.array_equal(_stripe_cells(c, oid, 5, k, p, cs),
                         _expected_cells(shadow, 5, k, p, cs)),
          "delta-updated parity differs from the oracle")
    # two targets down: every read decodes from survivors
    order0 = c.io._ec_order(oid, 0)
    down = order0[:2]
    r0 = c.io.ec_reconstructions
    for tid in down:
        c.cluster.fail_target(tid)
    check(c.pread(fd, nbytes, 0) == bytes(shadow), "degraded read differs")
    recon = c.io.ec_reconstructions - r0
    check(recon > 0, "degraded read reconstructed nothing")
    # an outage write drops the cells homed on the down targets; rebuild
    # must regenerate exactly those
    fresh = rng.bytes(4 * BLOCK)
    c.pwrite(fd, fresh, 0)
    shadow[:len(fresh)] = fresh
    c.io._ec_drain()
    rebuilt0 = c.cluster.stats.ec_rebuilt_cells
    for tid in down:
        c.cluster.recover_target(tid, resync=True)
    rebuilt = c.cluster.stats.ec_rebuilt_cells - rebuilt0
    check(rebuilt > 0, "recover_target rebuilt no cell")
    for b in range(4):
        order = c.io._ec_order(oid, b)
        lost = [i for i in range(k + p) if order[i] in down]
        want = _expected_cells(shadow, b, k, p, cs)
        for i in lost:
            got = c.io.sessions[order[i]].fetch_cell(oid, b, i * cs, cs)
            check(np.array_equal(got, want[i]),
                  f"rebuilt cell {i} of stripe {b} differs")
    check(c.pread(fd, nbytes, 0) == bytes(shadow), "post-rebuild read "
          "differs")
    scrub = c.scrubber.scrub_parity(n_stripes * (k + p) * cs)
    check(scrub["parity_checks"] >= n_stripes,
          f"scrub checked {scrub['parity_checks']} of {n_stripes} stripes")
    check(scrub["parity_mismatches"] == 0,
          f"scrub found {scrub['parity_mismatches']} parity mismatches")
    return {"stripes": n_stripes, "delta_writes": c.io.ec_delta_writes,
            "reconstructions": recon, "rebuilt_cells": rebuilt,
            "scrub_checks": scrub["parity_checks"]}


def parity_lowering_text(k: int = 4, p: int = 2) -> str:
    """The parity program as the data path dispatches it on the chip."""
    cs = BLOCK // k
    mat = jnp.asarray(rs_ref.cauchy_matrix(k, p))
    cells = jnp.zeros((k, cs), jnp.uint8)
    return rs_ops._gf_matmul.lower(
        mat, cells, m=p, s=k,
        tile=rs_ops._effective_tile(cs, rs_ops.K.DEFAULT_TILE, False),
        interpret=False).as_text()


# ---------------------------------------------------------------------------
# phase 4


def store_leaves(client, root: str, leaves) -> list:
    """Write each array as one file; returns read_tensors requests."""
    client.mkdir(root)
    reqs = []
    for i, a in enumerate(leaves):
        fd = client.open(f"{root}/leaf-{i:03d}", create=True)
        client.pwrite(fd, a.tobytes(), 0)
        reqs.append((fd, 0, a.shape, a.dtype))
    return reqs


def placement_phase(client, host_leaves, dev, *, slot_bytes: int) -> int:
    reqs = store_leaves(client, "/weights", host_leaves)
    with DeviceDirectSink(client, slot_bytes=slot_bytes, n_slots=2) as sink:
        got = sink.read_tensors(reqs)
        batches = sink.stats.batches
    for i, (g, w) in enumerate(zip(got, host_leaves)):
        check(on_device(g, dev), f"leaf {i} landed on {g.devices()}")
        check(same_bits(g, w), f"leaf {i} placed bytes differ")
    return batches


def sharded_placement_phase(client, host_leaves, mesh) -> None:
    """read_tensors under a 4-chip NamedSharding against device_put of
    the host reference under the same sharding, shard by shard."""
    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    reqs = store_leaves(client, "/weights", host_leaves)
    with DeviceDirectSink(client, slot_bytes=SLOT_BYTES, n_slots=2,
                          devices=list(mesh.devices.flat)) as sink:
        got = sink.read_tensors(reqs, sharding=sharding)
    for i, (g, w) in enumerate(zip(got, host_leaves)):
        want = jax.device_put(w, sharding)
        check(g.sharding.is_equivalent_to(want.sharding, g.ndim),
              f"leaf {i} sharding {g.sharding} != {want.sharding}")
        gs = sorted(g.addressable_shards, key=lambda s: s.device.id)
        ws = sorted(want.addressable_shards, key=lambda s: s.device.id)
        check(len(gs) == len(ws) == len(mesh.devices.flat),
              f"leaf {i} has {len(gs)} shards")
        for a, b in zip(gs, ws):
            check(a.device == b.device and a.index == b.index,
                  f"leaf {i} shard on {a.device} at {a.index} != "
                  f"{b.device} at {b.index}")
            check(same_bits(a.data, b.data),
                  f"leaf {i} shard on {a.device} differs")
        print(f"[sharded] leaf {i} shape={w.shape} devices="
              f"{sorted(d.id for d in g.devices())}", flush=True)


# ---------------------------------------------------------------------------


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def run_one_chip(dev, seed: int, times: Dict[str, float]) -> None:
    client = ROS2Client(mode="dpu", transport="rdma")
    try:
        with timed("ingest", times):
            params, opt, losses = ingest_train_phase(
                client, arch=ARCH, n_tokens=CORPUS_TOKENS, batch=BATCH,
                seq=SEQ, steps=STEPS, seed=seed)
            for leaf in jax.tree.leaves((params, opt)):
                check(on_device(leaf, dev), "train state left the chip")
        with timed("checkpoint", times):
            nbytes = checkpoint_phase(client, {"params": params, "opt": opt},
                                      STEPS)
            print(f"[checkpoint] bytes={nbytes}", flush=True)
        with timed("ec", times):
            ec = ec_phase(nbytes=EC_BYTES, seed=seed)
            print(f"[ec] {json.dumps(ec)}", flush=True)
            check("tpu_custom_call" in parity_lowering_text(),
                  "parity program has no compiled kernel")
        with timed("placement", times):
            host = [np.asarray(x) for x in jax.tree.leaves(params)]
            batches = placement_phase(client, host, dev,
                                      slot_bytes=SLOT_BYTES)
            print(f"[placement] leaves={len(host)} "
                  f"bytes={sum(h.nbytes for h in host)} slots={batches}",
                  flush=True)
    finally:
        client.close()


def run_four_chips(seed: int, times: Dict[str, float]) -> None:
    devs = jax.devices()[:4]
    mesh = jax.make_mesh((4,), ("x",), devices=devs,
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = get_config(ARCH)
    params = init_params(ModelAPI(cfg).param_defs(),
                         jax.random.PRNGKey(seed),
                         jnp.dtype(cfg.param_dtype))
    host = [np.asarray(x) for x in jax.tree.leaves(params)]
    del params
    client = ROS2Client(mode="dpu", transport="rdma")
    try:
        with timed("sharded", times):
            sharded_placement_phase(client, host, mesh)
    finally:
        client.close()
    for d in devs:
        print(f"[sharded] device {d.id} peak_bytes_in_use={peak_bytes(d)}",
              flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = device_phase(args.chips)
    compiles = Compiles()
    assert_kernels_compiled()
    times: Dict[str, float] = {}
    if args.chips == 4:
        run_four_chips(args.seed, times)
    else:
        run_one_chip(dev, args.seed, times)
    for name, secs in times.items():
        print(f"[timing] {name}_s={secs:.6f} (host clock, bring-up timing, "
              f"not a benchmark number)")
    print(f"[compiles] backend_compiles={compiles.compiles} "
          f"persistent_cache_hits={compiles.cache_hits}")
    print(f"[memory] peak_bytes_in_use={peak_bytes(dev)}")
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
