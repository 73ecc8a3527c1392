"""Share of the traced window in which no operation ran on the cell's
devices, averaged over them (bulk-transfer cells)."""
from bench.readers import device_idle_share as read  # noqa: F401
