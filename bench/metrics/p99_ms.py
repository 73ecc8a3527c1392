"""99th percentile, over every operation of the window (failed ones
included), of its time from submit to completion on the client's side."""
import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 99)) * 1e3
