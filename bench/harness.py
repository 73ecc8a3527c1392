"""Run one cell of BENCHMARK.json: set-up, measured window, comparison.

Everything a cell is made of is found by name, so a later configuration,
traffic mix or metric is new files plus new entries:

  BENCHMARK.json                 cells, metrics and bounds
  <configs[].file>               the deployment, e.g. bench/configs/*.json
  bench/traffic/<traffic>.json   the traffic mix; its "driver" key names
  bench/drivers/<driver>.py      the general generator that reads it
  bench/metrics/<metric>.py      one reader per metric: read(run) -> float
                                 or None when it finds nothing to read

A run: refuse to go on without a TPU with the cell's chips; turn on the
persistent compile cache; build the deployment and its data from the
seed and warm up the window's shapes (all of it `setup_s`); measure a
closed loop for `--seconds` (with `--trace 1`, for the mix's
"trace_seconds" under the profiler); read the metrics; compare what the
window produced with the plain reference; print the compared numbers
beside their limits as the last lines of stderr, and one JSON object as
the last line of stdout.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import sys
import time
import traceback
from contextlib import nullcontext
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
SPEC = ROOT / "BENCHMARK.json"
TRACE_DIR = ROOT / ".bench_trace"


class NoAccelerator(SystemExit):
    """No TPU, or fewer chips than the cell asks for: no result."""


# -- finding things by name ---------------------------------------------------
def load_spec(path: Optional[Path] = None) -> Dict:
    return json.loads(Path(path or SPEC).read_text())


def entry(entries: Sequence[Dict], name: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no entry named {name!r}")


def load_config(spec: Dict, name: str) -> Dict:
    return json.loads((ROOT / entry(spec["configs"], name)["file"])
                      .read_text())


def load_traffic(name: str) -> Dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def load_driver(traffic: Dict):
    return importlib.import_module(f"bench.drivers.{traffic['driver']}")


def load_metric(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '__')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end_of(spec: Dict, cell: str) -> List[Dict]:
    return [m for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_of(spec: Dict, cell: str) -> List[Dict]:
    e2e = {m["name"] for m in end_to_end_of(spec, cell)}
    return [m for m in spec["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


# -- the device ---------------------------------------------------------------
def require_devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"bench: no TPU (first device is "
                            f"{devs[0].platform}); no result")
    if len(devs) < chips:
        raise NoAccelerator(f"bench: the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}; no result")
    return devs[:chips]


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# -- one run ------------------------------------------------------------------
@dataclass
class Run:
    """What the metric readers read."""
    cell: str
    config: Dict
    traffic: Dict
    device_kind: str
    setup_s: float = 0.0
    window_s: float = 0.0
    ops: int = 0
    failed: int = 0
    user_bytes: int = 0
    latencies_s: List[float] = field(default_factory=list)
    counters: Dict = field(default_factory=dict)   # flat window delta
    trace: Optional[object] = None                 # tracing.Reduced
    calls: Dict[str, List[int]] = field(default_factory=dict)
    first_error: str = ""


def measure(driver, run: Run, seconds: float, trace: bool) -> None:
    """The closed loop: one operation at a time until `seconds` have
    passed; the window ends when its last operation completes."""
    import jax
    from bench import tracing

    recorder = tracing.SpanRecorder() if trace else None
    span = (lambda name: jax.profiler.TraceAnnotation(name)) if trace \
        else (lambda name: nullcontext())
    if recorder is not None:
        for owner, attr, name, nbytes, block in driver.span_points():
            recorder.wrap(owner, attr, name, nbytes, block)
    try:
        with tracing.profile(TRACE_DIR) if trace else nullcontext():
            with span(tracing.WINDOW_SPAN):
                start = time.perf_counter()
                deadline = start + seconds
                while True:
                    t = time.perf_counter()
                    try:
                        with span("bench.op"):
                            n = driver.step()
                    except Exception:   # a failed operation is counted
                        run.failed += 1
                        if not run.first_error:
                            run.first_error = traceback.format_exc()
                    else:
                        run.ops += 1
                        run.user_bytes += n
                    end = time.perf_counter()
                    run.latencies_s.append(end - t)
                    if end >= deadline:
                        break
        run.window_s = end - start
    finally:
        if recorder is not None:
            recorder.restore()
            run.calls = dict(recorder.calls)


def run_cell(spec: Dict, cell: Dict, config: Dict, traffic: Dict,
             seed: int, seconds: float, trace: bool, devices,
             t0: float, compiles=None, log=sys.stderr) -> Dict:
    """One run of one cell; returns the result object."""
    import jax
    from bench import counters as ctr
    from bench import tracing

    compiles = compiles or ctr.Compiles()
    driver = load_driver(traffic).Driver(config, traffic, seed, devices)
    run = Run(cell=cell["name"], config=config, traffic=traffic,
              device_kind=devices[0].device_kind)
    window = min(seconds, traffic.get("trace_seconds", seconds)) \
        if trace else seconds
    try:
        t_driver = time.perf_counter()
        driver.setup()
        t_ready = time.perf_counter()
        gc.collect()
        before = driver.counters()
        c0 = compiles.compiles
        run.setup_s = time.perf_counter() - t0
        measure(driver, run, window, trace)
        c1 = compiles.compiles
        run.counters = ctr.delta_counters(before, driver.counters())
        peak = memory_peak(devices)
        if trace:
            run.trace = tracing.reduce_trace(
                tracing.find_xplane(TRACE_DIR), [d.id for d in devices])
        entries = per_layer_of(spec, cell["name"]) if trace \
            else end_to_end_of(spec, cell["name"])
        metrics = {}
        for m in entries:
            value = load_metric(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        checks, info = driver.check()
    finally:
        driver.close()
    checks["ops_failed"] = (run.failed, 0)
    print(f"[bench] cell={cell['name']} seed={seed} trace={int(trace)} "
          f"setup_s={run.setup_s} (to driver {t_driver - t0}, driver "
          f"{t_ready - t_driver}) window_s={run.window_s} ops={run.ops} "
          f"failed={run.failed} user_bytes={run.user_bytes}", file=log)
    if run.first_error:
        print(f"[bench] first failed operation:\n{run.first_error}", file=log)
    print(f"[compiles] in_window={c1 - c0} total={compiles.compiles} "
          f"persistent_cache_hits={compiles.cache_hits}", file=log)
    print(f"[memory] peak_bytes_in_use={peak}", file=log)
    print(f"[counters] {json.dumps(run.counters, sort_keys=True)}", file=log)
    print(f"[info] {json.dumps(info, sort_keys=True)}", file=log)
    all_devs = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(all_devs),
              "memory_peak_bytes": peak}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": run.ops + run.failed, "failed": run.failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    for name, (v, lim) in checks.items():
        print(f"[check] {name}={v} limit={lim}", file=log)
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, (v, lim) in checks.items()}
    return result


def parse_args(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    spec = load_spec()
    cell = entry(spec["workloads"], args.workload)
    try:
        devices = require_devices(int(cell["chips"]))
    except NoAccelerator as e:
        print(e.code, file=sys.stderr)
        return 2
    import jax
    from repro.common.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"[bench] compile cache: {cache}", file=sys.stderr)
    result = run_cell(spec, cell, load_config(spec, cell["config"]),
                      load_traffic(cell["traffic"]), args.seed,
                      args.seconds, bool(args.trace), devices, t0)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
