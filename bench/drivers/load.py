"""Closed-loop weight loads: a serving replica cold-starts its weights.

Set-up writes every tensor of the configuration's checkpoint into the
store as one file each, in the checkpoint's own layout (one tensor per
layer and projection, as a Hugging Face checkpoint stores them), filled
with weights drawn from the seed (`weight_bytes`). The window then
loads the whole checkpoint into HBM again and again through
`DeviceDirectSink.read_tensors`, one load at a time; each load's arrays
are dropped once the next load is ready.

The comparison: every byte in HBM of the last load and of one load drawn
from the seed among the first few, against the bytes written; and every
stored extent of the weight files held by as many replicas as the
configuration states, with identical bytes.

Traffic keys: "sample_first_loads" (the drawn load is one of these).
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax.numpy as jnp
import numpy as np

from bench import data, deploy, reference

ROOT_DIR = "/weights"


def checkpoint_tensors(cfg: Dict) -> List[Tuple[str, Tuple[int, ...]]]:
    """(name, shape) of every tensor of a decoder-only checkpoint, in
    file order; names and [out, in] shapes as Hugging Face stores them."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    gated = cfg["hidden_act"] in ("silu", "swiglu", "gelu")
    out = [("model.embed_tokens.weight", (v, h))]
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out += [(p + "self_attn.q_proj.weight", (nh * hd, h)),
                (p + "self_attn.k_proj.weight", (nkv * hd, h)),
                (p + "self_attn.v_proj.weight", (nkv * hd, h)),
                (p + "self_attn.o_proj.weight", (h, nh * hd))]
        if gated:
            out.append((p + "mlp.gate_proj.weight", (f, h)))
        out += [(p + "mlp.up_proj.weight", (f, h)),
                (p + "mlp.down_proj.weight", (h, f))]
        out += [(p + "input_layernorm.weight", (h,)),
                (p + "post_attention_layernorm.weight", (h,))]
    out.append(("model.norm.weight", (h,)))
    if not cfg["tie_word_embeddings"]:
        out.append(("lm_head.weight", (v, h)))
    return out


def weight_bytes(rng: np.random.Generator, nbytes: int,
                 dtype: np.dtype) -> np.ndarray:
    """`nbytes` of weights drawn from `rng`: finite, normal bf16 values
    (`data.bf16_weight_bytes`); uniform bytes for other dtypes."""
    if dtype == jnp.dtype(jnp.bfloat16):
        return data.bf16_weight_bytes(rng, nbytes)
    return data.random_bytes(rng, nbytes)


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.client = None
        self.sink = None

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        from repro.core.device_direct import DeviceDirectSink

        cfg = self.cfg
        dtype = jnp.dtype(cfg["torch_dtype"])
        specs = checkpoint_tensors(cfg)
        sizes = [int(np.prod(s)) * dtype.itemsize for _n, s in specs]
        t = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        blob = weight_bytes(rng, sum(sizes), dtype)
        self.phases = {"data_s": time.perf_counter() - t}
        self.client = deploy.make_client(cfg)
        self.client.mkdir(ROOT_DIR)
        self.host, self.paths, self.reqs = [], [], []
        off = 0
        for (name, shape), n in zip(specs, sizes):
            raw = blob[off:off + n]
            off += n
            path = f"{ROOT_DIR}/{name}"
            fd = self.client.open(path, create=True)
            if self.client.pwrite(fd, raw, 0) != n:
                raise IOError(f"short write of {path}")
            self.host.append(raw.view(dtype).reshape(shape))
            self.paths.append(path)
            self.reqs.append((fd, 0, shape, dtype))
        self.load_bytes = sum(sizes)
        self.phases["store_write_s"] = time.perf_counter() - t
        self.sink = DeviceDirectSink(self.client,
                                     slot_bytes=cfg["sink_slot_bytes"],
                                     n_slots=cfg["sink_slots"])
        self.sink.read_tensors(self.reqs)   # warm-up
        self.phases["warmup_s"] = time.perf_counter() - t
        first = int(self.traffic["sample_first_loads"])
        self.keep_index = int(
            np.random.default_rng([self.seed, 1]).integers(0, first))
        self.n = 0
        self.kept: Dict[int, list] = {}
        self.last: Tuple[int, list] = (-1, [])

    # -- window --------------------------------------------------------------
    def step(self) -> int:
        arrs = self.sink.read_tensors(self.reqs)
        if self.n == self.keep_index:
            self.kept[self.n] = arrs
        self.last = (self.n, arrs)       # the previous load is dropped here
        self.n += 1
        return self.load_bytes

    def span_points(self):
        from repro.core import device_direct
        return [(self.client, "pread_into_many", "splice", None, False),
                (device_direct, "_carve_packed", "carve", None, False)]

    def counters(self) -> Dict:
        from bench.counters import flatten_counters
        return flatten_counters(self.client.io.data_path_counters())

    # -- comparison ----------------------------------------------------------
    def check(self):
        loads = dict(self.kept)
        if self.last[0] >= 0:
            loads[self.last[0]] = self.last[1]
        differing = sum(reference.bytes_differing(np.asarray(arr), want)
                        for arrs in loads.values()
                        for arr, want in zip(arrs, self.host))
        checks = {"tensor_bytes_differing": (differing, 0),
                  "replica_extents_bad": (self._replica_check(), 0)}
        info = {"loads_compared": sorted(loads), "setup": self.phases,
                "tensors_per_load": len(self.host),
                "bytes_per_load": self.load_bytes}
        return checks, info

    def _replica_check(self) -> int:
        """Stored extents of the weight files with fewer replicas than the
        configuration states, or replicas whose bytes differ."""
        need = self.cfg["redundancy"]["replicas"]
        bad = 0
        for path in self.paths:
            oid = self.client.stat(path)["oid"]
            for t in self.client.cluster.targets:
                cont = self.client.ccontainer.target(t.target_id)
                obj = cont.peek_object(oid)
                if obj is None:
                    continue
                with obj._lock:
                    exts = [e for lst in obj._extents.values() for e in lst]
                for ext in exts:
                    keys = dict(ext.block_keys)
                    data = [cont.store.device(n).read(k)
                            for n, k in keys.items()]
                    if len(keys) < need or any(d != data[0]
                                               for d in data[1:]):
                        bad += 1
        return bad

    def close(self) -> None:
        self.kept.clear()
        self.last = (-1, [])
        if self.sink is not None:
            self.sink.close()
        if self.client is not None:
            self.client.close()
