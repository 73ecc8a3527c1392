"""Read a cell's compared numbers on several seeds, with or without its
control in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5
    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 --control 0

One process, so the seeds share its compiled programs. Each seed runs
the cell's set-up, a window of `--seconds` and the comparison, with the
control of bench/controls.py installed (`--control 1`, the default) or
the program as it is (`--control 0`), and prints one JSON line:
{"seed", "control", "correct", "checks"}. The exit code is 1 when a
control comes out correct (a comparison that cannot fail) or a sound run
does not. Needs a TPU, as bench/run.py does; the benchmark's own runs
never run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_tpu_logs"))
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import controls, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.entry(spec["workloads"], args.workload)
    try:
        devices = harness.require_devices(int(cell["chips"]))
    except harness.NoAccelerator as e:
        print(e.code, file=sys.stderr)
        return 2
    import jax
    from repro.common.compile_cache import enable_compile_cache
    from bench.counters import Compiles
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    config = harness.load_config(spec, cell["config"])
    traffic = harness.load_traffic(cell["traffic"])
    compiles = Compiles()
    wrong = 0
    undo = controls.install(controls.control_for(traffic)) \
        if args.control else (lambda: None)
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            res = harness.run_cell(spec, cell, config, traffic, seed,
                                   args.seconds, False, devices,
                                   time.perf_counter(), compiles=compiles)
            wrong += res["correct"] == bool(args.control)
            print(json.dumps({"seed": seed, "control": args.control,
                              "correct": res["correct"],
                              "checks": res["checks"]}), flush=True)
    finally:
        undo()
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
