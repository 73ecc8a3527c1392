"""Shared set-up of the benchmark's own tests: the checkout root and
`src/` on the import path, and tiny versions of the cells for CPU runs
(the Pallas kernels take their interpret branch there)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 250, "sink_slot_bytes": 64 << 10, "sink_slots": 2,
}
TINY_FIO = {"size_bytes": 8 << 20, "schedule_ops": 4096, "check_stripes": 4}


def _read(rel: str):
    return json.loads((ROOT / rel).read_text())


@pytest.fixture
def spec():
    return _read("BENCHMARK.json")


@pytest.fixture
def tiny():
    """(cell, config, traffic) of a named cell, cut to a CPU size."""
    from bench import harness

    def make(cell_name: str, **traffic_over):
        spec = harness.load_spec()
        cell = harness.entry(spec["workloads"], cell_name)
        config = harness.load_config(spec, cell["config"])
        traffic = harness.load_traffic(cell["traffic"])
        if traffic["driver"] == "load":
            config.update(TINY_MODEL)
        else:
            traffic.update(TINY_FIO)
            traffic["buffer_pool_bytes"] = min(
                traffic["buffer_pool_bytes"], 4 * traffic["bs_bytes"])
        traffic.update(traffic_over)
        return spec, cell, config, traffic
    return make


@pytest.fixture
def run_tiny(tiny):
    """Drive a whole run of a tiny cell on the CPU; returns the result."""
    import io
    import time

    import jax
    from bench import harness

    def go(cell_name: str, seed: int = 20260, seconds: float = 0.5,
           trace: bool = False, **traffic_over):
        spec, cell, config, traffic = tiny(cell_name, **traffic_over)
        return harness.run_cell(spec, cell, config, traffic, seed, seconds,
                                trace, jax.devices()[:1],
                                time.perf_counter(), log=io.StringIO())
    return go
