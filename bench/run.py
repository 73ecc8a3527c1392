"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Exits 2, printing no result, unless JAX
sees a TPU with as many chips as the cell asks for. `--trace 0` prints
the cell's end-to-end metrics, `--trace 1` its per-layer metrics from a
profiled window. The last line of stdout is one JSON object; the last
lines of stderr are the numbers compared with the plain reference, each
beside its limit. See bench/harness.py.
"""
import os
import sys
import time

T0 = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".bench_tpu_logs"))
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
