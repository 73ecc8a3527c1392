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
(constructing one used to leak a second, never-disconnected session).

Sharded placement (`read_tensors(reqs, sharding=...)`) lands a tensor-
parallel checkpoint across chips. The sink holds one ring per device it
was built with (`devices=`), and each device's pipeline (splice ->
device_put -> carve) runs on a thread of its own over only that device's
bytes, so one chip's host->device DMA overlaps another's splice and no
byte lands on a chip that does not hold it. A shard's bytes come from the
sharding's `devices_indices_map`. A shard that is one contiguous range of
the file (a dim-0 split, a replicated tensor) is spliced as it is. A
strided shard (a split of a later dim, as the row-parallel projections of
Megatron tensor parallelism) is read as contiguous row blocks, one per
device, so every stored byte is read once; one jitted program over the
mesh then moves the blocks' columns to their owners (an all-to-all over
the chips' interconnect). A piece larger than a slot is placed in row
chunks over successive slots, each written into its preallocated output
by a donated in-place update."""
from __future__ import annotations

import functools
import threading
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
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


@partial(jax.jit, static_argnums=(1,))
def _first_rows(rows: jax.Array, n_rows: int) -> jax.Array:
    """An array of `n_rows` rows on `rows`' own device, `rows` at its top
    and zeros below. (`jnp.zeros(..., device=d)` would fill the array on
    the default device and copy it to `d`.)"""
    out = jnp.zeros((n_rows,) + rows.shape[1:], rows.dtype)
    return jax.lax.dynamic_update_slice_in_dim(out, rows, 0, axis=0)


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(out: jax.Array, rows: jax.Array, row0) -> jax.Array:
    """`out` with `rows` written over it from row `row0` on. `out` is
    donated, so the update lands in its own buffer."""
    return jax.lax.dynamic_update_slice_in_dim(out, rows, row0, axis=0)


def _exchange_rows(*blocks):
    """The identity on values. Jitted with the requested shardings as its
    `out_shardings` and fed tensors laid out in row blocks, it is the
    exchange: the compiler moves each block's column blocks to the chips
    that own them (one all-to-all per tensor)."""
    return blocks


@functools.lru_cache(maxsize=64)
def _exchange_program(shardings: Tuple) -> Any:
    return jax.jit(_exchange_rows, out_shardings=shardings)


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


def _box_shape(shape: Tuple[int, ...], index: Tuple) -> Tuple[int, ...]:
    """The shape of the box `index` (slices, as `devices_indices_map`
    gives them) of an array of `shape`."""
    return tuple(len(range(*sl.indices(n))) for sl, n in zip(index, shape))


def _byte_range(shape: Tuple[int, ...], itemsize: int,
                index: Tuple) -> Optional[Tuple[int, int]]:
    """(first byte, bytes) of the box `index` of a C-order array of
    `shape`, or None when the box is not one contiguous range: that is
    when a dim before the innermost split one spans more than one index."""
    bounds = [sl.indices(n)[:2] for sl, n in zip(index, shape)]
    k = len(shape)
    while k > 0 and bounds[k - 1] == (0, shape[k - 1]):
        k -= 1                      # whole innermost dims join the run
    if any(hi - lo != 1 for lo, hi in bounds[:max(k - 1, 0)]):
        return None
    stride, start = itemsize, 0
    for (lo, _hi), n in zip(reversed(bounds), reversed(shape)):
        start += lo * stride
        stride *= n
    return start, itemsize * int(np.prod(_box_shape(shape, index)))


def _row_blocks(sharding, shape: Tuple[int, ...]):
    """The layout a strided shard is read in: the same mesh, dim 0 split
    over every mesh axis `sharding` splits on and the other dims whole,
    so each device's block is one contiguous range of the file."""
    if not isinstance(sharding, jax.sharding.NamedSharding):
        raise ValueError(f"shards of {shape} under {sharding} are strided "
                         f"in the file; only a NamedSharding is read as "
                         f"row blocks and exchanged")
    axes: List[str] = []
    for entry in sharding.spec:
        if entry is not None:
            axes += [entry] if isinstance(entry, str) else list(entry)
    spec = jax.sharding.PartitionSpec(tuple(axes),
                                      *([None] * (len(shape) - 1)))
    return jax.sharding.NamedSharding(sharding.mesh, spec)


@dataclass(frozen=True)
class _Piece:
    """Bytes one device splices for one output: a whole tensor or shard,
    or, with `row0` set, one chunk of rows of one larger than a slot."""
    ix: int                         # the request it belongs to
    fd: int
    off: int
    shape: Tuple[int, ...]
    dtype: np.dtype
    row0: Optional[int] = None      # a chunk's first row in its output
    whole: Tuple[int, ...] = ()     # a chunk's output shape

    @property
    def size(self) -> int:
        return int(np.prod(self.shape)) * self.dtype.itemsize


def _pieces(ix: int, fd: int, off: int, shape: Tuple[int, ...],
            dtype: np.dtype, slot_bytes: int) -> List[_Piece]:
    """The tensor as one piece, or as row chunks that each fit a slot."""
    size = int(np.prod(shape)) * dtype.itemsize
    if size <= slot_bytes:
        return [_Piece(ix, fd, off, shape, dtype)]
    row = size // shape[0]
    per = slot_bytes // row
    if per == 0:
        raise ValueError(f"a row of {shape} {dtype} ({row}B) exceeds slot "
                         f"{slot_bytes}B")
    return [_Piece(ix, fd, off + r * row,
                   (min(per, shape[0] - r),) + tuple(shape[1:]), dtype,
                   r, tuple(shape))
            for r in range(0, shape[0], per)]


@dataclass
class DirectStats:
    reads: int = 0                 # pieces spliced (tensor, shard, chunk)
    bytes: int = 0
    device_puts: int = 0
    batches: int = 0               # packed slots shipped by read_tensors


class _Ring:
    """One device's registered slots and the tensors still in flight
    from them."""

    def __init__(self, region, device, n_slots: int):
        self.region = region
        self.device = device                # None: JAX's default device
        self.label = "default" if device is None else str(device.id)
        self.free = list(range(n_slots))
        self.cv = threading.Condition()
        # slot -> jax arrays whose device DMA still sources from it; the
        # wait happens at slot REUSE (in _acquire), so up to n_slots
        # placements + transfers stay in flight at once
        self.inflight: dict = {}


class DeviceDirectSink:
    """A ring of registered slots the data plane lands tensors in: one
    ring per device, of `n_slots` slots of `slot_bytes` each. `devices`
    names the devices sharded placement may place on (default: one ring,
    on JAX's default device)."""

    def __init__(self, client, slot_bytes: int, n_slots: int = 4,
                 devices: Optional[Sequence[Any]] = None):
        self.client = client
        self.slot_bytes = int(slot_bytes)
        self.n_slots = int(n_slots)
        # persistent registration: one region per device, each with one
        # (cached) placement rkey
        self._rings: Dict[Any, _Ring] = {
            d: _Ring(client.register_region(self.slot_bytes * self.n_slots),
                     d, self.n_slots)
            for d in (list(devices) if devices is not None else [None])}
        self._home = next(iter(self._rings.values()))   # unsharded reads
        self.ring = self._home.region
        # the sink rides the client's established session/capability path;
        # a raw `connect` here would leak an undisconnected second session
        # and bypass the compound/MetadataCache accounting
        self._sid = client.session_id
        self.stats = DirectStats()
        self._stats_lock = threading.Lock()
        self._pool: Optional[ThreadPoolExecutor] = None
        self._closed = False

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Tear down the sink: revoke the placement capabilities and drop
        the ring registrations (idempotent)."""
        if self._closed:
            return
        self._closed = True
        if self._pool is not None:
            self._pool.shutdown(wait=True)
        for ring in self._rings.values():
            self.client.io.drop_dst_rkey(ring.region)
            self.client.client_registry.deregister(ring.region)

    def __enter__(self) -> "DeviceDirectSink":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- slot lifecycle ------------------------------------------------------
    def _acquire(self, ring: _Ring, op: int = 0) -> int:
        with tracing.span("ros2.place.slot_wait", op=op) as sp:
            with ring.cv:
                while not ring.free:
                    ring.cv.wait()
                slot = ring.free.pop()
                pending = ring.inflight.pop(slot, None)
            sp.set_metadata(slot=slot)
            if pending is not None:
                # the slot's previous tensors must be materialized before
                # its ring memory can be refilled (the DMA source is still
                # live)
                jax.block_until_ready(pending)
        return slot

    def _release(self, ring: _Ring, slot: int) -> None:
        with ring.cv:
            ring.free.append(slot)
            ring.cv.notify()

    def _ring_of(self, device) -> _Ring:
        ring = self._rings.get(device)
        if ring is None:
            raise ValueError(
                f"the sharding places on {device}; the sink has slots on "
                f"{[r.label for r in self._rings.values()]} (devices=)")
        return ring

    # -- the device-direct read ----------------------------------------------
    def read_tensor(self, fd: int, offset: int, shape: Tuple[int, ...],
                    dtype, *, sharding: Optional[Any] = None) -> jax.Array:
        """Read a tensor's bytes from DFS straight into a ring slot, then a
        single device transfer. Raises if the tensor exceeds slot size."""
        np_dtype = np.dtype(dtype)
        size = int(np.prod(shape)) * np_dtype.itemsize
        if size > self.slot_bytes:
            raise ValueError(f"tensor {size}B exceeds slot {self.slot_bytes}B")
        ring = self._home
        slot = self._acquire(ring)
        try:
            base = slot * self.slot_bytes
            self.client.pread_into(fd, size, offset, ring.region, base)
            view = ring.region.buf[base:base + size].view(np_dtype)
            view = view.reshape(shape)
            arr = jax.device_put(view, sharding)   # pinned-host -> device DMA
            arr.block_until_ready()
            self.stats.reads += 1
            self.stats.bytes += size
            self.stats.device_puts += 1
            return arr
        finally:
            self._release(ring, slot)

    # -- batched placement ----------------------------------------------------
    def read_tensors(self, reqs: Sequence[Tuple[int, int, Tuple, Any]], *,
                     sharding: Optional[Any] = None) -> List[jax.Array]:
        """Batched device-direct placement: `reqs` is [(fd, offset, shape,
        dtype), ...]. Tensors are packed back-to-back into ring slots; per
        slot this costs ONE vectored splice batch (`pread_into_many` — a
        single DPU doorbell in dpu mode) and one `jax.device_put` per dtype
        present in the slot, with per-tensor arrays carved on-device.
        Double-buffered: slot k+1's splice overlaps slot k's host->device
        DMA; a slot is only reused after its carved tensors materialized
        (so the DMA source is never overwritten in flight). A tensor larger
        than a slot is placed in row chunks over successive slots.

        `sharding` (one `jax.sharding.Sharding`, or one per request, over
        devices the sink was built with) places each tensor across chips:
        each device's ring receives only that device's share of the
        tensor's bytes and its pipeline runs on its own thread; strided
        shards are read as row blocks and exchanged between the chips
        (module docstring). Without it, tensors land on the default
        device. Returns arrays in request order, every one in HBM."""
        parsed = [(fd, off, tuple(shape), np.dtype(dtype))
                  for fd, off, shape, dtype in reqs]
        if sharding is None or isinstance(sharding, jax.sharding.Sharding):
            shardings = None if sharding is None else [sharding] * len(parsed)
        else:
            shardings = list(sharding)
            if len(shardings) != len(parsed):
                raise ValueError(f"{len(shardings)} shardings for "
                                 f"{len(parsed)} tensors")
        total = sum(int(np.prod(shape)) * np_dtype.itemsize
                    for _fd, _off, shape, np_dtype in parsed)
        op = tracing.next_op_id()
        with tracing.span("ros2.place.load", op=op, bytes=total):
            if shardings is None:
                return self._place_whole(parsed, total, op)
            return self._place_sharded(parsed, shardings, op)

    def _place_whole(self, parsed, total: int, op: int) -> List[jax.Array]:
        ring = self._home
        pieces = [p for ix, (fd, off, shape, np_dtype) in enumerate(parsed)
                  for p in _pieces(ix, fd, off, shape, np_dtype,
                                   self.slot_bytes)]
        placed = self._pipeline(ring, pieces, op)
        out = [placed[ix] for ix in range(len(parsed))]
        self._drain(out, op)
        self.client.io.placement.note(ring.label, landed=total)
        return out

    def _place_sharded(self, parsed, shardings, op: int) -> List[jax.Array]:
        """Each device's pieces through its own ring, the per-device
        arrays joined into global ones, strided shards exchanged."""
        plans: Dict[_Ring, List[_Piece]] = {}
        landed: Dict[str, int] = {}
        layouts = []                # per request: the layout it is read in
        for ix, ((fd, off, shape, np_dtype), sh) in enumerate(
                zip(parsed, shardings)):
            read, boxes = sh, sh.devices_indices_map(shape)
            ranges = {d: _byte_range(shape, np_dtype.itemsize, box)
                      for d, box in boxes.items()}
            for d, box in boxes.items():
                label = self._ring_of(d).label
                landed[label] = landed.get(label, 0) + (
                    int(np.prod(_box_shape(shape, box))) * np_dtype.itemsize)
            if any(r is None for r in ranges.values()):
                read = _row_blocks(sh, shape)
                boxes = read.devices_indices_map(shape)
                ranges = {d: _byte_range(shape, np_dtype.itemsize, box)
                          for d, box in boxes.items()}
            layouts.append(read)
            for d, box in boxes.items():
                plans.setdefault(self._ring_of(d), []).extend(_pieces(
                    ix, fd, off + ranges[d][0], _box_shape(shape, box),
                    np_dtype, self.slot_bytes))
        placed = self._run_pipelines(plans, op)
        out = [jax.make_array_from_single_device_arrays(
                   shape, read, [placed[d][ix]
                                 for d in read.devices_indices_map(shape)])
               for ix, ((_fd, _off, shape, _dt), read)
               in enumerate(zip(parsed, layouts))]
        moves = [ix for ix, read in enumerate(layouts)
                 if read is not shardings[ix]]
        if moves:
            devs = sorted({d.id for ix in moves
                           for d in shardings[ix].device_set})
            with tracing.span("ros2.place.exchange", op=op,
                              dev="+".join(map(str, devs)),
                              bytes=sum(out[ix].nbytes for ix in moves)):
                moved = _exchange_program(
                    tuple(shardings[ix] for ix in moves))(
                        *[out[ix] for ix in moves])
            for ix, arr in zip(moves, moved):
                out[ix] = arr
        self._drain(out, op)
        for label, n in landed.items():
            self.client.io.placement.note(label, landed=n)
        return out

    def _run_pipelines(self, plans: Dict[_Ring, List[_Piece]],
                       op: int) -> Dict[Any, Dict[int, jax.Array]]:
        """Every ring's pipeline at once, one thread per device."""
        if len(plans) == 1:
            ((ring, pieces),) = plans.items()
            return {ring.device: self._pipeline(ring, pieces, op)}
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=len(self._rings),
                                            thread_name_prefix="place")
        futs = {ring.device: self._pool.submit(self._pipeline, ring,
                                               pieces, op)
                for ring, pieces in plans.items()}
        wait(futs.values())         # no pipeline left running on an error
        return {d: f.result() for d, f in futs.items()}

    def _pipeline(self, ring: _Ring, pieces: List[_Piece],
                  op: int) -> Dict[int, jax.Array]:
        """One device's placement: pack, splice, put and carve slot by
        slot; a chunk is written into its output, which the first chunk
        allocates on the device. Returns {request index: array}."""
        out: Dict[int, jax.Array] = {}
        i = 0
        while i < len(pieces):
            # greedy pack: as many consecutive pieces as fit in one slot
            # (bytes plus each dtype group's worst-case alignment pad)
            pack, need, dtypes = [], 0, set()
            while i < len(pieces):
                p = pieces[i]
                pad = 0 if p.dtype in dtypes else p.dtype.itemsize - 1
                if pack and need + p.size + pad > self.slot_bytes:
                    break
                pack.append((i, p.fd, p.off, p.shape, p.dtype, p.size))
                need += p.size + pad
                dtypes.add(p.dtype)
                i += 1
            groups, placed = _pack_slot(pack)
            nbytes = sum(q[3] for q in placed)
            slot = self._acquire(ring, op)  # blocks iff the slot's previous
            try:                            # tensors are still in flight
                base = slot * self.slot_bytes
                with tracing.span("ros2.place.shard", op=op, dev=ring.label,
                                  bytes=nbytes):
                    with tracing.span("ros2.place.splice", op=op,
                                      bytes=nbytes):
                        self.client.pread_into_many(
                            [(fd, size, off, base + pos)
                             for _pi, fd, off, size, pos, *_rest in placed],
                            ring.region)
                    # one host->device DMA per dtype group, typed on the host
                    with tracing.span("ros2.place.put", op=op,
                                      bytes=sum(g1 - g0 for _dt, g0, g1
                                                in groups)):
                        packed = tuple(
                            jax.device_put(ring.region.buf[base + g0:
                                                           base + g1]
                                           .view(np_dtype), ring.device)
                            for np_dtype, g0, g1 in groups)
                    layout = tuple((g, start, shape)
                                   for *_x, g, start, shape in placed)
                    with tracing.span("ros2.place.carve", op=op):
                        carved = _carve_packed(packed, layout)
                    for (pi, *_rest), arr in zip(placed, carved):
                        p = pieces[pi]
                        if p.row0 is None:
                            out[p.ix] = arr
                        elif p.row0 == 0:   # a tensor's chunks come in order
                            out[p.ix] = _first_rows(arr, p.whole[0])
                        else:
                            out[p.ix] = _write_rows(out[p.ix], arr, p.row0)
                with self._stats_lock:
                    self.stats.device_puts += len(groups)
                    self.stats.batches += 1
                    self.stats.reads += len(placed)
                    self.stats.bytes += nbytes
                self.client.io.placement.note(ring.label, spliced=nbytes)
                # hand the slot back immediately; the NEXT user of this
                # slot blocks on these arrays (in _acquire) before
                # refilling it, so up to n_slots pipelines overlap
                with ring.cv:
                    ring.inflight[slot] = list(carved)
            finally:
                self._release(ring, slot)
        return out

    def _drain(self, out: List[jax.Array], op: int) -> None:
        # the returned batch is fully materialized (callers may mutate or
        # re-read the files immediately)
        with tracing.span("ros2.place.drain", op=op):
            jax.block_until_ready(out)


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
