"""The store's chip benchmark: one cell of BENCHMARK.json per run.

Entry point: `python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`. See `bench/harness.py` for how a cell's
configuration, traffic mix and metrics are found by name.
"""
