"""Device time of the exchange program (device_direct's jitted
`_exchange_rows`: the all-to-all that moves the row-parallel tensors'
column blocks to the chips that own them) in the traced window,
averaged over the cell's chips, in ms per GB landed in HBM."""

EXCHANGE_PROGRAM = r"exchange_rows"


def read(run):
    t = run.trace
    if t is None or not t.busy_ns or run.user_bytes <= 0:
        return None
    seconds = t.module_seconds(EXCHANGE_PROGRAM) / len(t.busy_ns)
    if seconds <= 0:
        return None
    return seconds * 1e3 / (run.user_bytes / 1e9)
