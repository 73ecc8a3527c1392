"""Share of the traced window in the EC dirty-cell ledger
(`ros2.ec.ledger`: reading, marking or clearing the markers on every up
target)."""
from bench import program_spans


def read(run):
    return program_spans.window_share(run, "ros2.ec.ledger")
