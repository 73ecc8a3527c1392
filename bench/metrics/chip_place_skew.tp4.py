"""Whether one chip sets the pace of a sharded load: the largest chip's
time in `ros2.place.shard` (the union of its spans, one chip's share of a
slot from splice to carve, clipped to the traced window) over the mean
of that time across the cell's chips. 1.0 when every chip is busy as
long as the others."""
from collections import defaultdict

from bench import harness, tracing

SPAN = "ros2.place.shard"


def device_seconds(planes, window):
    """{dev stat: seconds} of the union of each device's SPAN events of
    the host planes, clipped to `window` (ns)."""
    ivs = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == SPAN:
                    s = int(ev.start_ns)
                    ivs[str(dict(ev.stats).get("dev"))].append(
                        (s, s + int(ev.duration_ns)))
    lo, hi = window
    return {d: tracing.total(tracing.union(tracing.clip(v, lo, hi))) / 1e9
            for d, v in ivs.items()}


def read(run):
    if run.trace is None:
        return None
    try:
        xplane = tracing.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    secs = device_seconds(ProfileData.from_file(str(xplane)).planes,
                          run.trace.window)
    if not secs or max(secs.values()) <= 0:
        return None
    mean = sum(secs.values()) / max(len(secs), len(run.trace.busy_ns))
    return max(secs.values()) / mean
