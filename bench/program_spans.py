"""The program's own spans in a `--trace 1` run, on the device trace's
clock.

The store emits host spans named `ros2.*` at its layer boundaries
(`repro.core.tracing`; they are on while the profiler records). They land
in the same `.xplane.pb` that `bench/tracing.py` reduces for the
harness's `bench.*` spans. This module reads that file again for the
`ros2.*` spans and hands them to the same reduction (`reduce_planes`)
under the harness's prefix, so the window, the union of intervals and
the labelling of idle time follow exactly the rules of
`bench/tracing.py`:

  * `program_seconds(names)`: the window's time covered by any of the
    named spans (their union);
  * `program_minus(a, b)`: the union of `a` less the union of `b`;
  * `idle_by_program_span()`: the device's idle time, each part labelled
    by the most specific `ros2.*` span covering it (the span name with
    the least total time), the rest `no program span`.

A reader finds the spans through `of_run(run)`, which returns None when
the run was not traced or the program emitted no `ros2.*` span.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, Iterable, List, Optional, Tuple

from bench import tracing

PREFIX = "ros2."
NO_SPAN = "no program span"
Interval = Tuple[int, int]


@dataclass
class ProgramSpans:
    window: Interval
    spans: Dict[str, List[Interval]]        # ros2 span -> clipped intervals
    stats: Dict[str, List[dict]]            # ros2 span -> stats per event
    idle_ns: Dict[str, int] = field(default_factory=dict)  # label -> ns
    n_devices: int = 1

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def has(self, *names: str) -> bool:
        return any(self.spans.get(n) for n in names)

    def _union(self, names: Iterable[str]) -> List[Interval]:
        return tracing.union(iv for n in names for iv in self.spans.get(n,
                                                                        []))

    def program_seconds(self, names: Iterable[str]) -> float:
        """Seconds of the window covered by any of the named spans."""
        return tracing.total(self._union(names)) / 1e9

    def program_minus(self, a: Iterable[str], b: Iterable[str]) -> float:
        """Seconds covered by a span of `a` and by no span of `b`."""
        ua = self._union(a)
        inside = tracing._intersect(ua, self._union(b))
        return tracing.total(tracing._subtract(ua, inside)) / 1e9

    def idle_by_program_span(self, top: int = 10) -> List[list]:
        """[label, seconds] for the `top` labels of the device's idle time
        (seconds averaged over the cell's devices)."""
        idle = sorted(self.idle_ns.items(), key=lambda kv: -kv[1])[:top]
        return [[k, v / self.n_devices / 1e9] for k, v in idle]


def reduce_planes(planes, device_ids: Iterable[int]) -> Optional[
        ProgramSpans]:
    """The `ros2.*` spans of `planes` (as `tracing.reduce_planes` takes
    them; a host event may carry `.stats`), or None when there are none."""
    planes = list(planes)           # the profiler's planes iterate once
    host, stats = [], {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == tracing.WINDOW_SPAN:
                    host.append(ev)
                elif ev.name.startswith(PREFIX):
                    host.append(SimpleNamespace(
                        name=tracing.SPAN_PREFIX + ev.name,
                        start_ns=ev.start_ns, duration_ns=ev.duration_ns))
                    stats.setdefault(ev.name, []).append(
                        dict(getattr(ev, "stats", ())))
    if not stats:
        return None
    proxy = [SimpleNamespace(name="/host:program", lines=[
        SimpleNamespace(name="ros2", events=host)])]
    proxy += [p for p in planes if not p.name.startswith("/host:")]
    r = tracing.reduce_planes(proxy, device_ids)
    spans = {n[len(tracing.SPAN_PREFIX):]: ivs for n, ivs in r.spans.items()
             if n != tracing.WINDOW_SPAN}
    idle = {(NO_SPAN if k == "no harness span" else k): v
            for k, v in r.idle_ns.items()}
    return ProgramSpans(window=r.window, spans=spans, stats=stats,
                        idle_ns=idle, n_devices=max(1, len(r.busy_ns)))


def reduce_trace(xplane: Path, device_ids: Iterable[int]) -> Optional[
        ProgramSpans]:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(xplane)).planes,
                         device_ids)


def of_run(run) -> Optional[ProgramSpans]:
    """The program's spans in the run's traced window, read once per run
    from the trace the harness reduced; None if there are none."""
    if run.trace is None:
        return None
    if not hasattr(run, "program_spans"):
        from bench import harness
        try:
            xplane = tracing.find_xplane(harness.TRACE_DIR)
        except FileNotFoundError:
            run.program_spans = None
        else:
            run.program_spans = reduce_trace(xplane, list(run.trace.busy_ns))
    return run.program_spans


def window_share(run, *names: str) -> Optional[float]:
    """100 x the union of the named spans over the traced window."""
    ps = of_run(run)
    if ps is None or not ps.has(*names) or ps.window_s <= 0:
        return None
    return 100.0 * ps.program_seconds(names) / ps.window_s


def ms_per_GB(run, seconds: Optional[float]) -> Optional[float]:
    """Milliseconds per GB of user bytes completed in the window."""
    if seconds is None or run.user_bytes <= 0:
        return None
    return seconds * 1e3 / (run.user_bytes / 1e9)
