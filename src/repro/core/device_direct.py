"""Device-direct placement: the GPUDirect-RDMA analogue for TPU/JAX.

The paper (§3.5) outlines optional GPU placement: the application registers
GPU buffers, the control plane conveys the descriptors (addresses, sizes,
rkeys) to the DPU/server, and on reads the storage server RDMA-writes
straight into GPU memory — same control/data-plane split, no DAOS engine
changes.

TPU adaptation (post-PR-4): there is no peer-to-peer PCIe write into TPU
HBM from here, so the minimal-copy equivalent is a *pinned, registered
host ring* the server places into DIRECTLY — `place_sg` validates the
ring's write-scoped rkey and the engine scatters the verified extent
overlay straight into the ring slots (the server-initiated "NIC DMA";
since PR 4 there is no staging bounce anywhere on this path) — followed by
the host->HBM DMA of a `jax.device_put` from pinned memory.

Two placement shapes:

  * `read_tensor`: one tensor, one slot, one device transfer — the
    latency-sensitive single-fetch.
  * `read_tensors`: BATCHED placement for LLM ingest (weight shards,
    token batches). Tensors are packed back-to-back into ring slots; each
    slot costs one vectored splice batch (`pread_into_many` — a single
    DPU doorbell in dpu mode) and one `jax.device_put` per dtype in the
    packed slot instead of one per tensor, with per-tensor arrays carved
    on-device (slice + reshape — no host copies). The ring is
    double-buffered: while slot k's host->device DMA is in flight, slot
    k+1's splice proceeds, so placement and device transfer overlap
    across the batch.

The ring registration is persistent: registered once at construction, its
placement rkey granted once PER PLACING SESSION and served from the NIC
translation cache for every subsequent read — on a multi-target client
the sink rides the cluster router unchanged: each engine target's session
grants its own capability on the shared ring, block ranges stripe across
targets, and `close()` retires the capability on every session. The capability leg is faithful: a revoked or
cross-tenant destination rkey cannot receive a direct splice (tests assert
it), and `close()` revokes the capability with the registration so a stale
NIC cache entry can never land bytes in recycled memory. The sink rides
the owning client's session — it issues NO control RPCs of its own
(constructing one used to leak a second, never-disconnected session)."""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import tracing


@partial(jax.jit, static_argnums=(1,))
def _carve_packed(groups: Tuple[jax.Array, ...],
                  layout: Tuple) -> Tuple[jax.Array, ...]:
    """Carve every tensor of a packed slot out of its on-device buffers in
    ONE dispatched (and layout-cached) computation. Each slot arrives as
    one flat array per dtype (`groups`), so a tensor is a slice + reshape
    of its group: no bitcast, and so no narrow (n, itemsize) intermediate,
    which the TPU would lay out on (8, 128) tiles at up to 128x the slot's
    bytes. `layout` is a static tuple of (group, start_elem, shape);
    steady-state ingest reuses layouts, so this compiles once per pack
    shape."""
    out = []
    for g, start, shape in layout:
        n = int(np.prod(shape))
        out.append(groups[g][start:start + n].reshape(shape))
    return tuple(out)


def _pack_slot(pack) -> Tuple[List[Tuple[np.dtype, int, int]], list]:
    """Lay one slot's tensors out grouped by dtype, each group contiguous
    and every tensor aligned to its itemsize, so each group's byte range
    is a plain `view(dtype)` of the ring. `pack` is [(ix, fd, off, shape,
    dtype, size)]. Returns ([(dtype, start_byte, end_byte)] per group,
    [(ix, fd, off, size, pos, group, start_elem, shape)] per tensor)."""
    order: List[np.dtype] = []
    for *_x, np_dtype, _size in pack:
        if np_dtype not in order:
            order.append(np_dtype)
    groups, placed, used = [], [], 0
    for g, np_dtype in enumerate(order):
        used = -(-used // np_dtype.itemsize) * np_dtype.itemsize
        g0 = used
        for ix, fd, off, shape, dt, size in pack:
            if dt == np_dtype:
                placed.append((ix, fd, off, size, used, g,
                               (used - g0) // np_dtype.itemsize, shape))
                used += size
        groups.append((np_dtype, g0, used))
    return groups, placed


@dataclass
class DirectStats:
    reads: int = 0
    bytes: int = 0
    device_puts: int = 0
    batches: int = 0               # packed slots shipped by read_tensors


class DeviceDirectSink:
    """A ring of registered slots the data plane lands tensors in."""

    def __init__(self, client, slot_bytes: int, n_slots: int = 4):
        self.client = client
        self.slot_bytes = int(slot_bytes)
        self.n_slots = int(n_slots)
        # persistent registration: one region, one (cached) placement rkey
        self.ring = client.register_region(self.slot_bytes * self.n_slots)
        # the sink rides the client's established session/capability path;
        # a raw `connect` here would leak an undisconnected second session
        # and bypass the compound/MetadataCache accounting
        self._sid = client.session_id
        self.stats = DirectStats()
        self._free = list(range(self.n_slots))
        self._cv = threading.Condition()
        # slot -> jax arrays whose device DMA still sources from it; the
        # wait happens at slot REUSE (in _acquire), so up to n_slots
        # placements + transfers stay in flight at once
        self._inflight: dict = {}
        self._closed = False

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Tear down the sink: revoke the placement capability and drop
        the ring registration (idempotent)."""
        if self._closed:
            return
        self._closed = True
        self.client.io.drop_dst_rkey(self.ring)
        self.client.client_registry.deregister(self.ring)

    def __enter__(self) -> "DeviceDirectSink":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- slot lifecycle ------------------------------------------------------
    def _acquire(self, op: int = 0) -> int:
        with tracing.span("ros2.place.slot_wait", op=op) as sp:
            with self._cv:
                while not self._free:
                    self._cv.wait()
                slot = self._free.pop()
                pending = self._inflight.pop(slot, None)
            sp.set_metadata(slot=slot)
            if pending is not None:
                # the slot's previous tensors must be materialized before
                # its ring memory can be refilled (the DMA source is still
                # live)
                jax.block_until_ready(pending)
        return slot

    def _release(self, slot: int) -> None:
        with self._cv:
            self._free.append(slot)
            self._cv.notify()

    # -- the device-direct read ----------------------------------------------
    def read_tensor(self, fd: int, offset: int, shape: Tuple[int, ...],
                    dtype, *, sharding: Optional[Any] = None) -> jax.Array:
        """Read a tensor's bytes from DFS straight into a ring slot, then a
        single device transfer. Raises if the tensor exceeds slot size."""
        np_dtype = np.dtype(dtype)
        size = int(np.prod(shape)) * np_dtype.itemsize
        if size > self.slot_bytes:
            raise ValueError(f"tensor {size}B exceeds slot {self.slot_bytes}B")
        slot = self._acquire()
        try:
            base = slot * self.slot_bytes
            self.client.pread_into(fd, size, offset, self.ring, base)
            view = self.ring.buf[base:base + size].view(np_dtype)
            view = view.reshape(shape)
            arr = jax.device_put(view, sharding)   # pinned-host -> device DMA
            arr.block_until_ready()
            self.stats.reads += 1
            self.stats.bytes += size
            self.stats.device_puts += 1
            return arr
        finally:
            self._release(slot)

    # -- batched placement ----------------------------------------------------
    def read_tensors(self, reqs: Sequence[Tuple[int, int, Tuple, Any]], *,
                     sharding: Optional[Any] = None) -> List[jax.Array]:
        """Batched device-direct placement: `reqs` is [(fd, offset, shape,
        dtype), ...]. Tensors are packed back-to-back into ring slots; per
        slot this costs ONE vectored splice batch (`pread_into_many` — a
        single DPU doorbell in dpu mode) and one `jax.device_put` per dtype
        present in the slot, with per-tensor arrays carved on-device. Double-buffered: slot k+1's
        splice overlaps slot k's host->device DMA; a slot is only reused
        after its carved tensors materialized (so the DMA source is never
        overwritten in flight). With `sharding`, carved tensors are
        re-placed onto it (one extra device-side put per tensor — the host
        path stays batched). Returns arrays in request order."""
        parsed = [(fd, off, tuple(shape), np.dtype(dtype))
                  for fd, off, shape, dtype in reqs]
        total = 0
        for _fd, _off, shape, np_dtype in parsed:
            size = int(np.prod(shape)) * np_dtype.itemsize
            if size > self.slot_bytes:
                raise ValueError(
                    f"tensor {size}B exceeds slot {self.slot_bytes}B")
            total += size
        op = tracing.next_op_id()
        with tracing.span("ros2.place.load", op=op, bytes=total):
            return self._place(parsed, sharding, op)

    def _place(self, parsed, sharding, op: int) -> List[jax.Array]:
        """`read_tensors` after its checks: pack, splice, put and carve
        slot by slot, then wait for the whole batch."""
        out: List[Optional[jax.Array]] = [None] * len(parsed)
        i = 0
        while i < len(parsed):
            # greedy pack: as many consecutive tensors as fit in one slot
            # (bytes plus each dtype group's worst-case alignment pad)
            pack, need, dtypes = [], 0, set()
            while i < len(parsed):
                fd, off, shape, np_dtype = parsed[i]
                size = int(np.prod(shape)) * np_dtype.itemsize
                pad = 0 if np_dtype in dtypes else np_dtype.itemsize - 1
                if pack and need + size + pad > self.slot_bytes:
                    break
                pack.append((i, fd, off, shape, np_dtype, size))
                need += size + pad
                dtypes.add(np_dtype)
                i += 1
            groups, placed = _pack_slot(pack)
            slot = self._acquire(op)        # blocks iff the slot's previous
            try:                            # tensors are still in flight
                base = slot * self.slot_bytes
                with tracing.span("ros2.place.splice", op=op,
                                  bytes=sum(p[3] for p in placed)):
                    self.client.pread_into_many(
                        [(fd, size, off, base + pos)
                         for _ix, fd, off, size, pos, *_rest in placed],
                        self.ring)
                # one host->device DMA per dtype group, typed on the host
                with tracing.span("ros2.place.put", op=op,
                                  bytes=sum(g1 - g0 for _dt, g0, g1
                                            in groups)):
                    packed = tuple(
                        jax.device_put(self.ring.buf[base + g0:base + g1]
                                       .view(np_dtype))
                        for np_dtype, g0, g1 in groups)
                layout = tuple((g, start, shape)
                               for *_x, g, start, shape in placed)
                with tracing.span("ros2.place.carve", op=op):
                    carved = _carve_packed(packed, layout)
                for (ix, *_rest), arr in zip(placed, carved):
                    if sharding is not None:
                        arr = jax.device_put(arr, sharding)
                        self.stats.device_puts += 1
                    out[ix] = arr
                self.stats.device_puts += len(groups)
                self.stats.batches += 1
                self.stats.reads += len(placed)
                self.stats.bytes += sum(p[3] for p in placed)
                # hand the slot back immediately; the NEXT user of this
                # slot blocks on these arrays (in _acquire) before
                # refilling it, so up to n_slots pipelines overlap
                with self._cv:
                    self._inflight[slot] = [out[p[0]] for p in placed]
            finally:
                self._release(slot)
        # the returned batch is fully materialized (callers may mutate or
        # re-read the files immediately)
        with tracing.span("ros2.place.drain", op=op):
            jax.block_until_ready([a for a in out if a is not None])
        return out


def staged_read_tensor(client, fd: int, offset: int, shape, dtype,
                       *, sharding=None) -> jax.Array:
    """The host-mediated baseline the paper's design removes: pread() into
    transient buffers, materialize an array, then device transfer. Used by
    benchmarks/tests to count the copies device-direct saves."""
    np_dtype = np.dtype(dtype)
    size = int(np.prod(shape)) * np_dtype.itemsize
    data = client.pread(fd, size, offset)                  # staged copies
    host = np.frombuffer(data, np_dtype).reshape(shape).copy()
    arr = jax.device_put(host, sharding)
    arr.block_until_ready()
    return arr
