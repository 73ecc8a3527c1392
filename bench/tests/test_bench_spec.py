"""BENCHMARK.json against the contract it is held to, every entry
resolving to its files by name, and a new configuration, traffic mix and
metric added as new files plus new entries, with no existing file
edited."""
import json
import re
import shutil

import pytest

from bench import harness

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_bounds(spec):
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in spec[k]]
    assert all(NAME.fullmatch(n) for n in names)
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.fullmatch(m["unit"]) and m["better"] in ("lower",
                                                             "higher")
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
    assert len(json.dumps(spec)) < 64 * 1024


def test_every_cell_resolves(spec):
    pairs = set()
    for cell in spec["workloads"]:
        assert cell["chips"] in (1, 4)
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        config = harness.load_config(spec, cell["config"])
        entry = harness.entry(spec["configs"], cell["config"])
        assert config["name"] == cell["config"]
        assert config["reduced"] == entry["reduced"]
        assert config["source"] == entry["source"]
        assert entry["file"].startswith("bench/configs/")
        traffic = harness.load_traffic(cell["traffic"])
        assert hasattr(harness.load_driver(traffic), "Driver")
        e2e = harness.end_to_end_of(spec, cell["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        layer = harness.per_layer_of(spec, cell["name"])
        assert layer
        for m in e2e + layer:
            assert callable(harness.load_metric(m["name"]).read)
        for m in layer:
            assert m["moves"] in {x["name"] for x in e2e}
    used = {c["config"] for c in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}


def test_metrics_listed_by_cell_exist(spec):
    cells = {c["name"] for c in spec["workloads"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


@pytest.fixture
def checkout_copy(tmp_path, monkeypatch):
    """A copy of BENCHMARK.json and bench/ the harness reads instead."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(harness.SPEC, root / "BENCHMARK.json")
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    monkeypatch.setattr(harness, "SPEC", root / "BENCHMARK.json")
    return root


def test_added_by_new_files_and_entries(checkout_copy):
    import io
    import time

    import jax

    root = checkout_copy
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    # a new configuration: the same fleet over the TCP data plane
    cfg = json.loads((root / "bench/configs/ec4p2-8t.json").read_text())
    cfg["name"] = "ec4p2-8t-tcp"
    cfg["transport"] = "tcp"
    (root / "bench/configs/ec4p2-8t-tcp.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "ec4p2-8t-tcp", "source": cfg["source"],
                            "file": "bench/configs/ec4p2-8t-tcp.json",
                            "reduced": [], "why": "the TCP data plane"})
    # a new traffic mix: 2 MiB writes, data only
    (root / "bench/traffic/write-2m.json").write_text(json.dumps({
        "driver": "fio", "rw": "write", "bs_bytes": 2 << 20,
        "size_bytes": 8 << 20, "buffer_pool_bytes": 8 << 20,
        "check_stripes": 4, "trace_seconds": 1}))
    # a new metric: its own reader file
    (root / "bench/metrics/ops_total.py").write_text(
        "def read(run):\n    return float(run.ops)\n")
    spec["end_to_end"].append({"name": "ops_total", "unit": "ops",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["ec4p2.write-2m-tcp"]})
    spec["workloads"].append({"name": "ec4p2.write-2m-tcp",
                              "config": "ec4p2-8t-tcp",
                              "traffic": "write-2m", "chips": 1,
                              "why": "new"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = {p: p.read_bytes() for p in before}
    assert after == before                 # nothing that existed changed

    spec = harness.load_spec()
    cell = harness.entry(spec["workloads"], "ec4p2.write-2m-tcp")
    res = harness.run_cell(
        spec, cell, harness.load_config(spec, cell["config"]),
        harness.load_traffic(cell["traffic"]), 7, 0.3, False,
        jax.devices()[:1], time.perf_counter(), log=io.StringIO())
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "ops_total"}
    assert res["metrics"]["ops_total"]["value"] >= 1
