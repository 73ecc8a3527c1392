"""Placement's wait for host-to-device transfers: the union of the
program's `ros2.place.slot_wait` (a ring slot's previous tensors must be
in HBM before the slot is refilled) and `ros2.place.drain` (the load's
final wait), in ms per GB landed in HBM in the traced window."""
from bench import program_spans

WAITS = ("ros2.place.slot_wait", "ros2.place.drain")


def read(run):
    ps = program_spans.of_run(run)
    if ps is None or not ps.has(*WAITS):
        return None
    return program_spans.ms_per_GB(run, ps.program_seconds(WAITS))
