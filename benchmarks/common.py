"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict, List, Sequence

RESULTS = Path(__file__).resolve().parent.parent / "results" / "bench"

GiB = 1024 ** 3
MiB = 1024 ** 2
KiB = 1024


def save_json(name: str, payload) -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    p = RESULTS / f"{name}.json"
    p.write_text(json.dumps(payload, indent=1, default=float))
    return p


def table(title: str, headers: Sequence[str], rows: List[Sequence]) -> str:
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(headers)]
    out = [f"== {title} =="]
    out.append("  ".join(str(h).rjust(w) for h, w in zip(headers, widths)))
    for r in rows:
        out.append("  ".join(str(c).rjust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def heatmap(title: str, row_label: str, col_label: str,
            row_vals, col_vals, grid) -> str:
    headers = [f"{row_label}\\{col_label}"] + [str(c) for c in col_vals]
    rows = [[str(r)] + [f"{grid[i][j]:.2f}" for j in range(len(col_vals))]
            for i, r in enumerate(row_vals)]
    return table(title, headers, rows)


def gib(x: float) -> float:
    return x / GiB


def fmt_rate(bps: float) -> str:
    return f"{bps / GiB:.2f} GiB/s"


def device_direct_compare(client, n_tensors: int, tensor_bytes: int,
                          slot_bytes: int, n_slots: int = 4,
                          trials: int = 1, path: str = "/dd-tensors",
                          seed: int = 0) -> Dict[str, float]:
    """Single-vs-batched device-direct harness (train_ingest's rdma leg):
    write
    `n_tensors` float32 tensors to one DFS file, warm the sink (jit
    compiles + caches), then time `read_tensor` per tensor against one
    `read_tensors` batch, min over `trials`."""
    import numpy as np
    from repro.core.device_direct import DeviceDirectSink

    n_elems = tensor_bytes // 4
    rng = np.random.default_rng(seed)
    fd = client.open(path, create=True)
    reqs = []
    for i in range(n_tensors):
        t = rng.standard_normal(n_elems).astype(np.float32)
        client.pwrite(fd, t.tobytes(), i * tensor_bytes)
        reqs.append((fd, i * tensor_bytes, (n_elems,), np.float32))
    with DeviceDirectSink(client, slot_bytes=slot_bytes,
                          n_slots=n_slots) as s:
        s.read_tensors(reqs)                  # warm jit + caches
        for fd_, off, shape, dt in reqs[:4]:
            s.read_tensor(fd_, off, shape, dt)
        single_s, batched_s = [], []
        for _ in range(trials):
            t0 = time.perf_counter()
            for fd_, off, shape, dt in reqs:
                s.read_tensor(fd_, off, shape, dt)
            single_s.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            s.read_tensors(reqs)
            batched_s.append(time.perf_counter() - t0)
        return {"single_tensors_per_s": n_tensors / min(single_s),
                "batched_tensors_per_s": n_tensors / min(batched_s),
                "batched_speedup": min(single_s) / min(batched_s),
                "device_puts_total": s.stats.device_puts,
                "batches": s.stats.batches}
