"""Sharded device-direct placement across four devices: each device's
ring receives only its shard of a tensor-parallel checkpoint, and every
shard in its device's memory is the written tensor's bytes at the box
the sharding names, bit for bit (numpy slicing by `devices_indices_map`
is the reference).

Needs four devices, so the placements run in one subprocess with four
host placeholder devices (this pytest process keeps its one-device
view); each case is a test of its own here."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r"""
import json, os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, ml_dtypes, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.client import ROS2Client
from repro.core.device_direct import DeviceDirectSink

devs = jax.devices()[:4]
tp = jax.make_mesh((4,), ("tp",), devices=devs,
                   axis_types=(jax.sharding.AxisType.Auto,))
grid = jax.make_mesh((2, 2), ("x", "y"), devices=devs,
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
bf16 = ml_dtypes.bfloat16
SLOT = 1024
# name -> (shape, dtype, sharding); slot of 1 KiB, 2 slots per device
CASES = {
    "column": ((64, 6), bf16, NamedSharding(tp, P("tp", None))),
    "row": ((8, 64), bf16, NamedSharding(tp, P(None, "tp"))),
    "vocab_larger_than_slot": ((256, 24), bf16,
                               NamedSharding(tp, P("tp", None))),
    "replicated": ((24,), bf16, NamedSharding(tp, P())),
    "row_larger_than_slot": ((64, 96), bf16, NamedSharding(tp, P(None, "tp"))),
    "strided_middle_dim": ((8, 12, 6), np.float32,
                           NamedSharding(tp, P(None, "tp", None))),
    "two_axis_tiles": ((8, 12), np.int32, NamedSharding(grid, P("x", "y"))),
    "uneven_chunks_odd_width": ((12, 7), bf16,
                                NamedSharding(tp, P("tp", None))),
}
report = {}


def out(case, ok, why=""):
    report[case] = {"ok": bool(ok), "why": why}


def exact(arr, host, sh):
    if arr.sharding != sh or arr.shape != host.shape:
        return f"sharding {arr.sharding} shape {arr.shape}"
    boxes = sh.devices_indices_map(host.shape)
    if {s.device for s in arr.addressable_shards} != set(boxes):
        return "devices differ"
    for s in arr.addressable_shards:
        want = np.ascontiguousarray(host[boxes[s.device]])
        got = np.asarray(s.data)
        if got.tobytes() != want.tobytes():
            return f"shard on {s.device} differs"
    return ""


rng = np.random.default_rng(20261018)
c = ROS2Client(mode="dpu", transport="rdma", n_targets=8, replication=2,
               domains=list("aabbccdd"), scrub_interval_s=None)
c.mkdir("/w")
hosts, reqs, shs = {}, {}, {}
for i, (name, (shape, dt, sh)) in enumerate(CASES.items()):
    n = int(np.prod(shape)) * np.dtype(dt).itemsize
    raw = rng.bit_generator.random_raw(-(-n // 8)).view(np.uint8)[:n]
    if dt is bf16:                      # finite normal bf16, as the cells
        w = raw.view(np.uint16) & np.uint16(0x87FF) | np.uint16(0x3800)
        raw = w.view(np.uint8)
    host = raw.view(dt).reshape(shape)
    fd = c.open(f"/w/{name}", create=True)
    c.pwrite(fd, host.tobytes(), 0)
    hosts[name], reqs[name], shs[name] = host, (fd, 0, shape, dt), sh

sink = DeviceDirectSink(c, slot_bytes=SLOT, n_slots=2, devices=devs)
for name in CASES:
    before = c.io.data_path_counters()["placement"]
    (arr,) = sink.read_tensors([reqs[name]], sharding=shs[name])
    after = c.io.data_path_counters()["placement"]
    why = exact(arr, hosts[name], shs[name])
    boxes = shs[name].devices_indices_map(hosts[name].shape)
    for d in devs:
        k = str(d.id)
        spliced = after["spliced_bytes"].get(k, 0) - \
            before["spliced_bytes"].get(k, 0)
        landed = after["landed_bytes"].get(k, 0) - \
            before["landed_bytes"].get(k, 0)
        held = hosts[name][boxes[d]].nbytes if d in boxes else 0
        if not why and (spliced != held or landed != held):
            why = f"device {k} spliced {spliced} landed {landed} holds {held}"
    out(name, not why, why)

# all of them in one load, one sharding per request, twice through the
# same rings: the second load refills slots the first load used
names = list(CASES)
for rep in range(2):
    got = sink.read_tensors([reqs[n] for n in names],
                            sharding=[shs[n] for n in names])
    bad = [n for n, a in zip(names, got) if exact(a, hosts[n], shs[n])]
    out(f"one_load_all_layouts_{rep}", not bad, str(bad))

# no device-to-device copy: every byte lands on a device that holds it
# (the exchange is a collective inside one program, not such a copy);
# set process-wide, since the pipelines run on threads of their own
jax.config.update("jax_transfer_guard_device_to_device", "disallow")
try:
    got = sink.read_tensors([reqs[n] for n in names],
                            sharding=[shs[n] for n in names])
    bad = [n for n, a in zip(names, got) if exact(a, hosts[n], shs[n])]
    out("no_device_to_device_copy", not bad, str(bad))
except Exception as e:
    out("no_device_to_device_copy", False, str(e)[:300])
finally:
    jax.config.update("jax_transfer_guard_device_to_device", "allow")

# one sharding for every request
col = NamedSharding(tp, P("tp", None))
got = sink.read_tensors([reqs["column"], reqs["vocab_larger_than_slot"]],
                        sharding=col)
out("one_sharding_for_all",
    not exact(got[0], hosts["column"], col)
    and not exact(got[1], hosts["vocab_larger_than_slot"], col))

# without a sharding: the whole tensor on the default device, as before
(whole,) = sink.read_tensors([reqs["row_larger_than_slot"]])
out("unsharded_on_the_default_device",
    whole.devices() == {jax.devices()[0]}
    and np.asarray(whole).tobytes() == hosts["row_larger_than_slot"].tobytes())

# the four pipelines' shared counters under fast thread switching: 64
# small tensors, each slot a handful of them, no update lost
import sys
small = []
for i in range(64):
    a = rng.integers(0, 1 << 30, (8, 12), dtype=np.int32)
    fd = c.open(f"/w/small{i}", create=True)
    c.pwrite(fd, a.tobytes(), 0)
    small.append((a, (fd, 0, a.shape, a.dtype)))
before = (sink.stats.bytes, sink.stats.reads,
          c.io.data_path_counters()["placement"])
old_interval = sys.getswitchinterval()
sys.setswitchinterval(1e-6)
try:
    got = sink.read_tensors([r for _a, r in small], sharding=col)
finally:
    sys.setswitchinterval(old_interval)
after = c.io.data_path_counters()["placement"]
moved = {k: sum(after[k].get(str(d.id), 0) - before[2][k].get(str(d.id), 0)
                for d in devs) for k in ("spliced_bytes", "landed_bytes")}
total = sum(a.nbytes for a, _r in small)
out("counts_hold_under_fast_thread_switching",
    all(not exact(g, a, col) for g, (a, _r) in zip(got, small))
    and sink.stats.bytes - before[0] == total
    and sink.stats.reads - before[1] == 4 * len(small)
    and moved == {"spliced_bytes": total, "landed_bytes": total},
    f"{moved} {total}")

# refusals, raised before any byte moves: 4 chips cannot split 6 columns
try:
    sink.read_tensors([reqs["column"]],
                      sharding=NamedSharding(tp, P(None, "tp")))
    out("refuses_a_sharding_jax_refuses", False, "no error")
except ValueError as e:
    out("refuses_a_sharding_jax_refuses", True, str(e)[:80])
two = DeviceDirectSink(c, slot_bytes=SLOT, n_slots=2, devices=devs[:2])
try:
    two.read_tensors([reqs["column"]], sharding=col)
    out("refuses_a_device_without_slots", False, "no error")
except ValueError as e:
    out("refuses_a_device_without_slots", "slots" in str(e), str(e)[:80])
finally:
    two.close()

# the spans of one traced sharded load
d = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions()
opts.python_tracer_level = 0
opts.host_tracer_level = 2
jax.profiler.start_trace(d, profiler_options=opts)
try:
    sink.read_tensors([reqs["row"], reqs["column"]],
                      sharding=[shs["row"], shs["column"]])
finally:
    jax.profiler.stop_trace()
from jax.profiler import ProfileData
import glob
xplane = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[0]
events = [(ev.name, dict(ev.stats))
          for plane in ProfileData.from_file(xplane).planes
          if plane.name.startswith("/host:")
          for line in plane.lines for ev in line.events
          if ev.name.startswith("ros2.place.")]
ex = [s for n, s in events if n == "ros2.place.exchange"]
out("exchange_span_carries_devices_and_bytes",
    len(ex) == 1 and set(ex[0]) == {"op", "dev", "bytes"}
    and ex[0]["dev"] == "0+1+2+3" and ex[0]["bytes"] == hosts["row"].nbytes,
    str(ex))
sh = [s for n, s in events if n == "ros2.place.shard"]
out("shard_spans_on_every_device",
    {str(s["dev"]) for s in sh} == {"0", "1", "2", "3"}
    and sum(s["bytes"] for s in sh) == hosts["row"].nbytes
    + hosts["column"].nbytes and all(set(s) == {"op", "dev", "bytes"}
                                     for s in sh), str(sh))
sink.close()
c.close()
print("REPORT " + json.dumps(report))
"""

CASES = [
    "column", "row", "vocab_larger_than_slot", "replicated",
    "row_larger_than_slot", "strided_middle_dim", "two_axis_tiles",
    "uneven_chunks_odd_width", "one_load_all_layouts_0",
    "one_load_all_layouts_1", "no_device_to_device_copy",
    "one_sharding_for_all",
    "unsharded_on_the_default_device", "refuses_a_sharding_jax_refuses",
    "refuses_a_device_without_slots",
    "counts_hold_under_fast_thread_switching",
    "exchange_span_carries_devices_and_bytes",
    "shard_spans_on_every_device",
]


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("REPORT ")]
    assert line, r.stdout[-2000:]
    return json.loads(line[-1][len("REPORT "):])


def test_every_case_ran(report):
    assert set(report) == set(CASES)


@pytest.mark.parametrize("case", CASES)
def test_sharded_placement(report, case):
    assert report[case]["ok"], report[case]["why"]
