"""fio-style write jobs on one file: numjobs=1, iodepth=1, closed loop.

Traffic keys, named after fio's own:
  rw                 write | randwrite
  bs_bytes           bytes per operation
  size_bytes         the file (fio size=); set-up creates it and sets its
                     size with no data written, as fio lays out a file for
                     a write job, so an unwritten range reads zeros
  buffer_pool_bytes  distinct payloads the writes cycle through
  schedule_ops       random offsets drawn up front (randwrite)
  check_stripes      stripes the comparison draws from the seed

rw=write walks the file and wraps; each pass writes other payloads to
the same offsets, so a write that did not land reads back wrong.
rw=randwrite draws bs-aligned offsets uniformly from the seed.

The comparison, once the window has closed and in-flight cell writes
have drained, covers `check_stripes` of the stripes the window wrote,
drawn from the seed, and the last one written: each read back through
`pread` against the acknowledged bytes (zeros where nothing was
written), and its k + p cells fetched from their home targets against
the benchmark's own GF(256) encode of those bytes.
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from bench import data, deploy, reference

PATH = "/fio.0"


class Driver:
    def __init__(self, config: Dict, traffic: Dict, seed: int, devices):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.rw = traffic["rw"]
        if self.rw not in ("write", "randwrite"):
            raise ValueError(f"unknown fio rw={self.rw!r}")
        self.bs = int(traffic["bs_bytes"])
        self.size = int(traffic["size_bytes"])
        red = config["redundancy"]
        self.k, self.p, self.stripe = red["k"], red["p"], red["stripe_bytes"]
        if self.size % self.bs or self.size % self.stripe:
            raise ValueError("size_bytes must be a multiple of bs_bytes "
                             "and of the stripe")
        self.client = None

    # -- set-up --------------------------------------------------------------
    def setup(self) -> None:
        tr = self.traffic
        t = time.perf_counter()
        rng = np.random.default_rng(self.seed)
        self.pool = data.random_bytes(rng, int(tr["buffer_pool_bytes"]))
        self.n_bufs = len(self.pool) // self.bs
        self.n_blocks = self.size // self.bs
        self.offsets = None
        if self.rw == "randwrite":
            self.offsets = rng.integers(0, self.n_blocks,
                                        int(tr["schedule_ops"])) * self.bs
        # the payload each bs block holds now: an index into the pool, or
        # -1 for a block never written
        self.holds = np.full(self.n_blocks, -1, np.int32)
        self.touched = np.zeros(self.size // self.stripe, bool)
        self.phases = {"data_s": time.perf_counter() - t}
        self.client = deploy.make_client(self.cfg)
        self.fd = self.client.open(PATH, create=True)
        self.client.truncate(PATH, self.size)
        self.i = 0
        self.last_off = 0
        self.step()                      # warm-up: the window's one shape
        self.phases["warmup_s"] = time.perf_counter() - t

    # -- window --------------------------------------------------------------
    def step(self) -> int:
        i = self.i
        self.i += 1
        if self.offsets is None:
            off = (i % self.n_blocks) * self.bs
        else:
            off = int(self.offsets[i % len(self.offsets)])
        j = (i + i // self.n_blocks) % self.n_bufs
        buf = self.pool[j * self.bs:(j + 1) * self.bs]
        if self.client.pwrite(self.fd, buf, off) != self.bs:
            raise IOError(f"short write at {off}")
        self.holds[off // self.bs] = j
        self.touched[off // self.stripe:
                     (off + self.bs - 1) // self.stripe + 1] = True
        self.last_off = off
        return self.bs

    def span_points(self):
        from bench import kernels
        from repro.kernels.rs_parity import ops as rs
        return [(rs, name, f"rs_parity.{name}",
                 lambda a, kw, name=name: kernels.rs_call_bytes(name, a, kw),
                 True)
                for name in ("ec_encode", "ec_parity_delta")]

    def counters(self) -> Dict:
        from bench.counters import flatten_counters
        return flatten_counters(self.client.io.data_path_counters())

    # -- comparison ----------------------------------------------------------
    def expected(self, lo: int, hi: int) -> np.ndarray:
        """The acknowledged bytes of the file range [lo, hi)."""
        out = np.zeros(hi - lo, np.uint8)
        for blk in range(lo // self.bs, (hi - 1) // self.bs + 1):
            j = int(self.holds[blk])
            if j < 0:
                continue
            a, b = max(lo, blk * self.bs), min(hi, (blk + 1) * self.bs)
            src = j * self.bs - blk * self.bs
            out[a - lo:b - lo] = self.pool[src + a:src + b]
        return out

    def sample(self) -> np.ndarray:
        """The stripes compared: `check_stripes` drawn from the seed among
        those written, and the last one written."""
        written = np.flatnonzero(self.touched)
        n = min(int(self.traffic["check_stripes"]), len(written))
        rng = np.random.default_rng([self.seed, 2])
        pick = rng.choice(written, n, replace=False)
        last = self.last_off // self.stripe
        return np.unique(np.append(pick, last))

    def check(self):
        self.client.io._ec_drain()
        readback = cells_bad = 0
        stripes = self.sample()
        oid = self.client.stat(PATH)["oid"]
        for b in (int(b) for b in stripes):
            lo = b * self.stripe
            want = self.expected(lo, lo + self.stripe)
            got = np.frombuffer(self.client.pread(self.fd, self.stripe, lo),
                                np.uint8)
            readback += reference.bytes_differing(got, want)
            cells_bad += self._cells_differing(oid, b, want)
        checks = {"readback_bytes_differing": (readback, 0),
                  "stored_cells_differing": (cells_bad, 0)}
        info: Dict = {"ops": self.i, "stripes_written":
                      int(self.touched.sum()),
                      "stripes_compared": len(stripes),
                      "setup": self.phases}
        return checks, info

    def _cells_differing(self, oid: int, b: int, stripe_bytes) -> int:
        """Cells of stripe `b` on their home targets that differ from the
        reference encode of its acknowledged bytes."""
        cs = self.stripe // self.k
        io = self.client.io
        want = reference.stripe_cells(stripe_bytes, self.k, self.p)
        order = io._ec_order(oid, b)
        return sum(not np.array_equal(
            io.sessions[order[i]].fetch_cell(oid, b, i * cs, cs), want[i])
            for i in range(self.k + self.p))

    def close(self) -> None:
        if self.client is not None:
            self.client.close()
