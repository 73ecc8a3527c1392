"""Device time of the placement carve program (device_direct's
_carve_packed) in the traced window, per GB landed in HBM."""
from bench.readers import CARVE_PROGRAM


def read(run):
    if run.trace is None or run.user_bytes <= 0:
        return None
    carve_s = run.trace.module_seconds(CARVE_PROGRAM)
    if carve_s <= 0:
        return None
    return carve_s * 1e3 / (run.user_bytes / 1e9)
