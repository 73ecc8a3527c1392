"""The entry point refuses to measure anything off a TPU, and the peaks
table refuses a chip it does not know."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness, peaks


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ec4p2.write-1m",
         "--seed", str(2 ** 31 + 17), "--seconds", "1", "--trace", "0",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_run_refuses_without_a_tpu():
    p = _run(harness.ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_refuses_in_a_bare_checkout(tmp_path):
    """Only BENCHMARK.json and bench/: no program to measure."""
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.SPEC, tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_require_devices_refuses_cpu():
    with pytest.raises(harness.NoAccelerator):
        harness.require_devices(1)


def test_peaks_known_and_unknown():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")


def test_trace_result_names_the_device(run_tiny):
    res = run_tiny("ec4p2.randwrite-4k", trace=True)
    dev = res["device"]
    assert set(dev) >= {"platform", "kind", "count", "memory_peak_bytes",
                        "busy_s", "window_s"}
    assert dev["platform"] == "cpu"
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no TPU plane: no idle share, no roofline is made up
    assert "device_idle_share.small" not in res["metrics"]
    assert res["metrics"]["wire_bytes_per_user_byte.small"]["value"] == 4.0
    json.dumps(res)
