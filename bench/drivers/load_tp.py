"""Closed-loop tensor-parallel weight loads: a serving replica that no
single chip holds cold-starts across the chips of one host.

Set-up builds the mesh of the configuration's `tensor_parallel` chips
and the sink with one ring per chip, then writes every tensor of the
checkpoint into the store as one file each: `load.checkpoint_tensors`'s
layout plus the `.bias` every LayerNorm1P norm stores beside its
`.weight`, filled with weights drawn from the seed (`load.weight_bytes`,
one generator per 64 MiB chunk, spawned from the seed, drawn on several
threads while the tensors already drawn are written). The checkpoint is
written through the store's DFS client on the host, as the job that
saved it would write it; the replica loads it through its DPU client.
Each tensor's sharding follows the configuration's `tp_split`: the dim a
module's weight is split on over the mesh (Megatron's column-, row- and
vocab-parallel layouts), replicated when the module is not listed.

The window loads the whole checkpoint across the chips again and again
through `DeviceDirectSink.read_tensors(reqs, sharding=...)`, one load at
a time; each load's arrays are dropped once the next load is ready. A
load's user bytes are the checkpoint's bytes, counted once however many
chips hold them.

The comparison (bench/reference_tp.py): every shard in HBM of the last
load and of one load drawn from the seed among the first few, against
the bytes written, sliced in numpy by the sharding; every shard on a
device the sharding names for it; and every stored extent of the weight
files held by as many replicas as the configuration states.

Traffic keys: "sample_first_loads" (the drawn load is one of these).
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from bench import deploy, reference_tp
from bench.drivers import load
from bench.drivers.load import checkpoint_tensors, weight_bytes

AXIS = "tp"
CHUNK = 64 << 20                 # bytes drawn by one generator
THREADS = 8                      # threads drawing the weights


def tp_checkpoint_tensors(cfg: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """`checkpoint_tensors` with each norm's `.bias` after its `.weight`,
    as Hugging Face's NemotronForCausalLM stores its LayerNorm1P norms."""
    out = []
    for name, shape in checkpoint_tensors(cfg):
        out.append((name, shape))
        if name.endswith("norm.weight"):
            out.append((name[:-len("weight")] + "bias", shape))
    return out


def tensor_sharding(cfg: Dict, mesh, name: str, ndim: int):
    """The tensor's sharding: split on `tp_split[module]` over the mesh,
    replicated when its module is not listed."""
    dim = cfg["tp_split"].get(name.split(".")[-2])
    spec = [None] * ndim
    if dim is not None:
        spec[dim] = AXIS
    return NamedSharding(mesh, PartitionSpec(*spec))


def seeded_weights(seed: int, nbytes: int, dtype, pool):
    """`nbytes` of weights from `seed`, drawn on `pool`: chunk j of CHUNK
    bytes by `load.weight_bytes` from the j-th generator spawned from the
    seed. Returns the buffer and `ready(lo, hi)`, which waits until bytes
    [lo, hi) are drawn."""
    out = np.empty(nbytes, np.uint8)
    seqs = np.random.SeedSequence(seed).spawn(-(-nbytes // CHUNK))

    def fill(j: int) -> None:
        lo, hi = j * CHUNK, min(nbytes, (j + 1) * CHUNK)
        out[lo:hi] = weight_bytes(np.random.default_rng(seqs[j]), hi - lo,
                                  dtype)
    futs = [pool.submit(fill, j) for j in range(len(seqs))]

    def ready(lo: int, hi: int) -> None:
        for f in futs[lo // CHUNK:-(-hi // CHUNK)]:
            f.result()
    return out, ready


def device_peaks(devices) -> Dict[str, int]:
    """peak_bytes_in_use of each device, by id."""
    return {str(d.id): int((d.memory_stats() or {}).get(
        "peak_bytes_in_use", 0)) for d in devices}


class Driver(load.Driver):
    def __init__(self, config: Dict, traffic: Dict, seed: int, devices):
        super().__init__(config, traffic, seed, devices)
        tp = int(config["tensor_parallel"])
        if len(devices) != tp:
            raise ValueError(f"tensor_parallel={tp} needs {tp} devices, "
                             f"got {len(devices)}")
        self.devices = list(devices)
        self.kept: Dict[int, list] = {}
        self.last: Tuple[int, list] = (-1, [])

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        from repro.core.device_direct import DeviceDirectSink

        cfg = self.cfg
        self.mesh = jax.make_mesh((len(self.devices),), (AXIS,),
                                  devices=self.devices,
                                  axis_types=(jax.sharding.AxisType.Auto,))
        self.client = deploy.make_client(cfg)
        # before any data: a program without per-device rings stops here
        self.sink = DeviceDirectSink(self.client,
                                     slot_bytes=cfg["sink_slot_bytes"],
                                     n_slots=cfg["sink_slots"],
                                     devices=self.devices)
        dtype = jnp.dtype(cfg["torch_dtype"])
        specs = tp_checkpoint_tensors(cfg)
        sizes = [int(np.prod(s)) * dtype.itemsize for _n, s in specs]
        t = time.perf_counter()
        self.client.mkdir(load.ROOT_DIR)
        self.host, self.paths, self.reqs, self.shardings = [], [], [], []
        with ThreadPoolExecutor(THREADS) as pool:
            blob, ready = seeded_weights(self.seed, sum(sizes), dtype, pool)
            off = 0
            for (name, shape), n in zip(specs, sizes):
                ready(off, off + n)
                raw = blob[off:off + n]
                off += n
                path = f"{load.ROOT_DIR}/{name}"
                fd = self.client.open(path, create=True)
                if self.client.dfs.pwrite(fd, raw, 0) != n:
                    raise IOError(f"short write of {path}")
                self.host.append(raw.view(dtype).reshape(shape))
                self.paths.append(path)
                self.reqs.append((fd, 0, shape, dtype))
                self.shardings.append(tensor_sharding(cfg, self.mesh, name,
                                                      len(shape)))
        self.load_bytes = sum(sizes)
        self.phases = {"data_and_store_write_s": time.perf_counter() - t}
        self.sink.read_tensors(self.reqs, sharding=self.shardings)  # warm-up
        self.phases["warmup_s"] = time.perf_counter() - t
        first = int(self.traffic["sample_first_loads"])
        self.keep_index = int(
            np.random.default_rng([self.seed, 1]).integers(0, first))
        self.n = 0

    # -- window --------------------------------------------------------------
    def step(self) -> int:
        arrs = self.sink.read_tensors(self.reqs, sharding=self.shardings)
        if self.n == self.keep_index:
            self.kept[self.n] = arrs
        self.last = (self.n, arrs)       # the previous load is dropped here
        self.n += 1
        return self.load_bytes

    # -- comparison ----------------------------------------------------------
    def check(self):
        loads = dict(self.kept)
        if self.last[0] >= 0:
            loads[self.last[0]] = self.last[1]
        peaks = device_peaks(self.devices)     # the window's, before ours
        with ThreadPoolExecutor(1) as pool:    # host-only, beside the
            replicas = pool.submit(self._replica_check)   # device compare
            found = [reference_tp.compare(arr, want, sh)
                     for arrs in loads.values()
                     for arr, want, sh in zip(arrs, self.host,
                                              self.shardings)]
        checks = {"tensor_bytes_differing": (sum(d for d, _m in found), 0),
                  "replica_extents_bad": (replicas.result(), 0),
                  "shard_device_mismatch": (sum(m for _d, m in found), 0)}
        info = {"loads_compared": sorted(loads), "setup": self.phases,
                "tensors_per_load": len(self.host),
                "bytes_per_load": self.load_bytes,
                "peak_bytes_in_use": peaks}
        return checks, info
