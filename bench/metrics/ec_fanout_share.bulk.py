"""Share of the traced window in the EC cell fan-out (`ros2.ec.fanout`:
from the first cell job's submit until the write's quorum, or until all
jobs on the delta path)."""
from bench import program_spans


def read(run):
    return program_spans.window_share(run, "ros2.ec.fanout")
