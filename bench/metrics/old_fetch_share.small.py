"""Share of the traced window in the delta-parity path's fetch of the
old bytes under each write (`ros2.ec.old_fetch`)."""
from bench import program_spans


def read(run):
    return program_spans.window_share(run, "ros2.ec.old_fetch")
