"""Bytes placement spliced from the store into the chips' ring slots over
the bytes it landed in their HBM, both summed over the chips (the
store's `placement` counters, window deltas). 1.00 when each chip's
share of every tensor is read once; above 1 when a byte is read more
often than it is held."""


def _total(counters, key: str) -> int:
    prefix = f"placement.{key}."
    return sum(v for k, v in counters.items() if k.startswith(prefix))


def read(run):
    landed = _total(run.counters, "landed_bytes")
    if landed <= 0:
        return None
    return _total(run.counters, "spliced_bytes") / landed
