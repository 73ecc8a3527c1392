"""Readers that several metric files share, and the names by which the
trace reduction finds the program's device work."""
from __future__ import annotations

# The Pallas GF(256) kernel as the device trace names it: the custom
# call inside the jitted `_gf_matmul` of kernels/rs_parity/ops.py.
RS_KERNEL = r'^%_gf_matmul\S* = .*custom_call_target="tpu_custom_call"'
# The placement carve program as the device trace names it.
CARVE_PROGRAM = r"carve_packed"
# The spans the harness puts around the parity leg (bench/drivers/fio.py).
PARITY_CALLS = ("rs_parity.ec_encode", "rs_parity.ec_parity_delta")


def device_idle_share(run):
    """100 x (1 - busy / window), busy averaged over the cell's devices."""
    t = run.trace
    if t is None or not t.busy_ns or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def parity_leg_share(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not any(run.calls.get(c)
                                               for c in PARITY_CALLS):
        return None
    return 100.0 * t.span_seconds(PARITY_CALLS) / t.window_s
