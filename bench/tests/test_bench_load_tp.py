"""The tensor-parallel load cell (nemotron-4-15b.load-tp4): its
configuration's arithmetic and layout, its reference and readers, and
tiny runs of the whole cell on four host placeholder devices: sound, it
comes out correct; with one shard zeroed, one byte flipped, one tensor's
shards on the wrong devices, or the reference loader in the precision
below bf16, it does not.

The runs need four devices, so they share one subprocess (this pytest
process keeps its one-device view)."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness, reference_tp, tracing
from bench.drivers import load_tp

ROOT = Path(__file__).resolve().parents[2]
CELL = "nemotron-4-15b.load-tp4"


@pytest.fixture
def cfg():
    spec = harness.load_spec()
    return harness.load_config(spec, harness.entry(spec["workloads"],
                                                   CELL)["config"])


def _count(cfg, layers):
    specs = load_tp.tp_checkpoint_tensors(dict(cfg, num_hidden_layers=layers))
    params = sum(int(np.prod(s)) for _n, s in specs)
    return len(specs), params


def test_configuration_numbers_are_its_tensors(cfg):
    n, params = _count(cfg, cfg["num_hidden_layers"])
    assert (n, params, 2 * params) == (cfg["as_run"]["tensors"],
                                       cfg["as_run"]["parameters"],
                                       cfg["as_run"]["bf16_bytes"])
    pub = cfg["published"]
    assert _count(cfg, pub["num_hidden_layers"]) == (pub["tensors"],
                                                     pub["parameters"])
    assert 2 * pub["parameters"] == pub["bf16_bytes"]
    assert 4 * cfg["as_run"]["bf16_bytes_per_chip"] == \
        cfg["as_run"]["bf16_bytes"]


def test_megatron_layout(cfg):
    import jax
    mesh = jax.make_mesh((1,), (load_tp.AXIS,), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,))
    split = {}
    for name, shape in load_tp.tp_checkpoint_tensors(cfg):
        spec = tuple(load_tp.tensor_sharding(cfg, mesh, name,
                                             len(shape)).spec)
        dims = [d for d, e in enumerate(spec) if e is not None]
        split[name.split(".")[-2] + "." + name.split(".")[-1]] = dims
        for d in dims:              # the split divides over the chips
            assert shape[d] % cfg["tensor_parallel"] == 0, name
    assert split["embed_tokens.weight"] == split["lm_head.weight"] == [0]
    for m in ("q_proj", "k_proj", "v_proj", "up_proj"):
        assert split[f"{m}.weight"] == [0]
    assert split["o_proj.weight"] == split["down_proj.weight"] == [1]
    for norm in ("input_layernorm", "post_attention_layernorm", "norm"):
        assert split[f"{norm}.weight"] == split[f"{norm}.bias"] == []
    assert "gate_proj.weight" not in split      # relu2: no gate


@pytest.mark.parametrize("dtype", [np.uint8, "bfloat16", np.float32])
@pytest.mark.parametrize("n, flips", [(8, []), (64, [0, 63]),
                                      (1000, [5, 997, 999]),
                                      (4096, list(range(0, 4096, 7)))])
def test_bytes_differing_counts_every_byte(n, flips, dtype):
    """Counted on the device: every flipped byte, in every lane."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    rng = np.random.default_rng(n)
    raw = rng.integers(0, 256, n, dtype=np.uint8)
    want = raw.view(dtype).reshape(2, -1)
    got = raw.copy()
    got[flips] ^= 0x5A
    got = jax.device_put(got.view(dtype).reshape(2, -1))
    assert reference_tp.bytes_differing(got, want) == len(flips)
    assert reference_tp.bytes_differing(got[:1], want) == n


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def _run(planes, devices, user_bytes=10 ** 9, counters=None):
    run = harness.Run(cell=CELL, config={}, traffic={}, device_kind="TPU",
                      user_bytes=user_bytes, counters=counters or {})
    run.trace = tracing.reduce_planes(planes, devices)
    return run


HOST = NS(name="/host:CPU", lines=[
    NS(name="python", events=[_ev("bench.window", 0, 1000)]),
    NS(name="place-0", events=[_ev("ros2.place.shard", 0, 200, dev=0),
                               _ev("ros2.place.shard", 100, 200, dev=0)]),
    NS(name="place-1", events=[_ev("ros2.place.shard", 0, 100, dev=1),
                               _ev("ros2.place.shard", 900, 500, dev=1)])])


def _device(i, *events):
    return NS(name=f"/device:TPU:{i}",
              lines=[NS(name="XLA Ops", events=list(events)),
                     NS(name="XLA Modules", events=list(events))])


def test_chip_place_skew_reads_each_chips_union():
    skew = harness.load_metric("chip_place_skew.tp4")
    # dev 0: [0, 300) = 300 ns; dev 1: 100 + [900, 1000) = 200 ns
    secs = skew.device_seconds([HOST], (0, 1000))
    assert secs == {"0": 300e-9, "1": 200e-9}


def test_exchange_ms_per_GB_averages_over_the_chips():
    read = harness.load_metric("exchange_ms_per_GB.tp4").read
    planes = [HOST] + [_device(i, _ev("jit__exchange_rows(7)", 10, 400),
                               _ev("jit__carve_packed(3)", 500, 100))
                       for i in range(4)]
    # 400 ns on each of 4 chips, per 1 GB: 4e-4 ms/GB
    assert read(_run(planes, range(4))) == pytest.approx(4e-4)
    carve_only = [HOST] + [_device(i, _ev("jit__carve_packed(3)", 0, 9))
                           for i in range(4)]
    assert read(_run(carve_only, range(4))) is None


def test_shard_read_amplification_sums_over_devices():
    read = harness.load_metric("shard_read_amplification.tp4").read
    run = harness.Run(cell=CELL, config={}, traffic={}, device_kind="TPU")
    assert read(run) is None
    run.counters = {f"placement.{k}.{d}": n for d in "0123"
                    for k, n in (("spliced_bytes", 30), ("landed_bytes", 20))}
    assert read(run) == pytest.approx(1.5)


# -- the whole cell, tiny, on four placeholder devices ------------------------
SCRIPT = r"""
import io, json, os, sys, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [sys.argv[1], os.path.join(sys.argv[1], "src")]
from pathlib import Path
import jax
from bench import control_tp, harness

TINY = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 256, "sink_slot_bytes": 4 << 10, "sink_slots": 2}
spec = harness.load_spec()
cell = harness.entry(spec["workloads"], "nemotron-4-15b.load-tp4")
harness.TRACE_DIR = Path(sys.argv[2])


def run(trace=False, seed=2 ** 31 + 11):
    config = harness.load_config(spec, cell["config"])
    config.update(TINY)
    return harness.run_cell(spec, cell, config,
                            harness.load_traffic(cell["traffic"]), seed,
                            0.5, trace, jax.devices()[:4],
                            time.perf_counter(), log=io.StringIO())


# the reference alone: shards missing, or at boxes the sharding does not
# name (a float32 (64, 8) tensor, 2,048 bytes, split over 4 devices)
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from bench import reference_tp
mesh = lambda n: jax.make_mesh((n,), ("tp",), devices=jax.devices()[:n],
                               axis_types=(jax.sharding.AxisType.Auto,))
want = np.arange(512, dtype=np.float32).reshape(64, 8)
rows = NamedSharding(mesh(4), P("tp", None))
reference = {name: reference_tp.compare(jax.device_put(want, sh), want, rows)
             for name, sh in [
                 ("sound", rows),
                 ("two_devices", NamedSharding(mesh(2), P("tp", None))),
                 ("replicated", NamedSharding(mesh(4), P()))]}

out = {"reference": reference, "sound": run(), "traced": run(trace=True)}
for fault in control_tp.FAULTS:
    undo = control_tp.install(fault)
    try:
        out[fault] = run()
    finally:
        undo()
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT),
                        str(tmp_path_factory.mktemp("trace"))], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULTS ")]
    assert line, r.stdout[-2000:]
    return json.loads(line[-1][len("RESULTS "):])


@pytest.mark.parametrize("layout, want", [
    ("sound", [0, 0]),
    # two devices hold 32-row boxes: their 2 x 512 bytes differ, as do
    # the 2 x 512 the missing devices should hold; both shards misplaced
    ("two_devices", [2048, 2]),
    ("replicated", [2048, 4]),
])
def test_reference_counts_missing_and_misplaced_shards(results, layout,
                                                       want):
    assert results["reference"][layout] == want


@pytest.mark.parametrize("run", ["sound", "traced"])
def test_tiny_run_is_correct(results, run):
    res = results[run]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 1 and res["failed"] == 0
    assert set(res["checks"]) == {"tensor_bytes_differing",
                                  "replica_extents_bad",
                                  "shard_device_mismatch", "ops_failed"}
    assert all(c["limit"] == 0 for c in res["checks"].values())
    assert res["device"]["count"] == 4


def test_tiny_run_reports_its_metrics(results):
    assert set(results["sound"]["metrics"]) == {"throughput_GBps",
                                                "setup_s"}
    m = results["traced"]["metrics"]
    # on the CPU: counters and host spans only (no device trace)
    assert m["shard_read_amplification.tp4"]["value"] == 1.0
    assert m["chip_place_skew.tp4"]["value"] >= 1.0
    assert m["copies_per_byte"]["value"] > 0


@pytest.mark.parametrize("fault, check", [
    ("zeroed", "tensor_bytes_differing"),
    ("flipped", "tensor_bytes_differing"),
    ("wrong_device", "shard_device_mismatch"),
    ("fp8", "tensor_bytes_differing"),
])
def test_fault_is_not_correct(results, fault, check):
    res = results[fault]
    assert not res["correct"]
    assert res["checks"][check]["value"] > 0
    if fault == "flipped":          # one byte, in the two loads compared
        assert res["checks"][check]["value"] <= 2
