"""User bytes completed in the window (landed in HBM and ready, or
acknowledged), over the window's time, in GB/s (1e9 B)."""


def read(run):
    if run.window_s <= 0 or run.ops == 0:
        return None
    return run.user_bytes / run.window_s / 1e9
