"""Bytes the transport moved over the window (new data, old bytes
fetched for the parity delta, parity deltas shipped) per user byte
written, from the store's own counters."""


def read(run):
    moved = run.counters.get("transport.bytes_moved", 0)
    if moved <= 0 or run.user_bytes <= 0:
        return None
    return moved / run.user_bytes
