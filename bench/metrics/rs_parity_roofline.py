"""The GF(256) parity kernel's share of its roofline: the least time the
chip's HBM bandwidth allows for the algorithm's u8 bytes of every call
in the traced window (bench/kernels.py), over the kernel's device time
in the trace. Bound by bytes: the kernel does no floating-point work."""
from bench.peaks import peaks_for
from bench.readers import PARITY_CALLS, RS_KERNEL


def read(run):
    if run.trace is None:
        return None
    kernel_s = run.trace.op_seconds(RS_KERNEL)
    nbytes = sum(sum(run.calls.get(c, [])) for c in PARITY_CALLS)
    if kernel_s <= 0 or nbytes <= 0:
        return None
    bound_s = nbytes / peaks_for(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * bound_s / kernel_s
