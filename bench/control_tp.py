"""Read the tensor-parallel load cell's comparison with the placed arrays
broken on purpose, and once as they are.

    python3 bench/control_tp.py --seed 5 --loads 2

One process and one set-up: the cell's driver (bench/drivers/load_tp.py)
writes the checkpoint once; then, with each fault below in the program's
place in turn and once with none, it runs `--loads` loads and reads the
comparison (bench/reference_tp.py). Prints one JSON line per run:
{"fault", "correct", "checks"}. Exits 1 when a fault comes out correct or
the sound run does not. Needs the cell's TPU chips, as bench/run.py
does; the benchmark's own runs never install a fault.

  zeroed        one chip's shard of one sharded tensor reads zeros
  flipped       one byte of one chip's shard of it flipped
  wrong_device  its shards on the chips in another order: every shard's
                bytes intact, on a device the sharding does not name for
                them
  fp8           the plain reference loader (`pread`, then `jax.device_put`
                under the same sharding) after a round trip through
                float8_e4m3fn, the precision below the configuration's bf16
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path
from typing import Callable

import numpy as np

CELL = "nemotron-4-15b.load-tp4"
FAULTS = ("zeroed", "flipped", "wrong_device", "fp8")


def _fp8_read_tensors(self, reqs, *, sharding=None):
    import jax
    import jax.numpy as jnp

    shardings = [sharding] * len(reqs) \
        if isinstance(sharding, jax.sharding.Sharding) else list(sharding)
    out = []
    for (fd, off, shape, dtype), sh in zip(reqs, shardings):
        dtype = np.dtype(dtype)
        n = int(np.prod(shape)) * dtype.itemsize
        host = np.frombuffer(self.client.pread(fd, n, off), dtype)
        low = host.astype(jnp.float8_e4m3fn).astype(dtype).reshape(shape)
        out.append(jax.device_put(low, sh))
    jax.block_until_ready(out)
    return out


def _break(arr, kind: str):
    """`arr` (a NamedSharding array split over two or more devices) with
    the fault `kind` in its second device's shard or its device order."""
    import jax
    from jax.sharding import Mesh, NamedSharding

    shards = sorted(arr.addressable_shards, key=lambda s: s.device.id)
    datas = [s.data for s in shards]
    if kind == "wrong_device":
        mesh = arr.sharding.mesh
        devs = list(mesh.devices.flat)
        devs[0], devs[1] = devs[1], devs[0]
        swapped = NamedSharding(
            Mesh(np.array(devs).reshape(mesh.devices.shape), mesh.axis_names),
            arr.sharding.spec)
        return jax.make_array_from_single_device_arrays(arr.shape, swapped,
                                                        datas)
    host = np.array(datas[1])
    flat = host.reshape(-1).view(np.uint8)
    if kind == "zeroed":
        flat[:] = 0
    else:
        flat[7] ^= 0x10
    datas[1] = jax.device_put(host, shards[1].device)
    return jax.make_array_from_single_device_arrays(arr.shape, arr.sharding,
                                                    datas)


def install(kind: str) -> Callable[[], None]:
    """Put fault `kind` in `DeviceDirectSink.read_tensors`'s place;
    returns the function that undoes it."""
    from repro.core.device_direct import DeviceDirectSink

    real = DeviceDirectSink.read_tensors
    if kind == "fp8":
        broken = _fp8_read_tensors
    elif kind in FAULTS:
        def broken(self, reqs, *, sharding=None):
            out = real(self, reqs, sharding=sharding)
            i = next(i for i, a in enumerate(out)
                     if not a.sharding.is_fully_replicated)
            out[i] = _break(out[i], kind)
            return out
    else:
        raise KeyError(f"no fault named {kind!r}")
    DeviceDirectSink.read_tensors = broken

    def undo() -> None:
        DeviceDirectSink.read_tensors = real
    return undo


def check_loads(driver, loads: int):
    """`loads` loads through the driver's sink, then its comparison."""
    driver.n, driver.kept, driver.last = 0, {}, (-1, [])
    for _ in range(loads):
        driver.step()
    checks, _info = driver.check()
    return all(v <= lim for v, lim in checks.values()), checks


def main(argv=None) -> int:
    root = Path(__file__).resolve().parent.parent
    os.environ.setdefault("TPU_LOG_DIR", str(root / ".bench_tpu_logs"))
    sys.path[0] = str(root)
    sys.path.insert(1, str(root / "src"))
    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--loads", type=int, default=2)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.entry(spec["workloads"], CELL)
    try:
        devices = harness.require_devices(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(e.code, file=sys.stderr)
        return 2
    traffic = harness.load_traffic(cell["traffic"])
    driver = harness.load_driver(traffic).Driver(
        harness.load_config(spec, cell["config"]), traffic, args.seed,
        devices)
    wrong = 0
    try:
        driver.setup()
        for kind in (None,) + FAULTS:
            undo = install(kind) if kind else (lambda: None)
            try:
                correct, checks = check_loads(driver, args.loads)
            finally:
                undo()
            wrong += correct == bool(kind)
            print(json.dumps({"fault": kind, "correct": correct,
                              "checks": {k: {"value": v, "limit": lim}
                                         for k, (v, lim) in checks.items()}}),
                  flush=True)
    finally:
        driver.close()
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
