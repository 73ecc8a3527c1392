"""Device trace and host spans: what a `--trace 1` run reads.

`profile()` runs the JAX profiler over the traced window with the Python
tracer off (it would record every Python call of the store and slow its
host path several times over); host `TraceAnnotation` spans stay on.

`SpanRecorder` wraps named attributes of the program (a module function
or one object's method) so that each call opens a host span
`bench.<name>` on the profiler's clock and records the algorithm bytes
of the call. It is installed only around the traced window.

`reduce_trace()` reads the profiler's `.xplane.pb` into a `Reduced`:
  * the window: the `bench.window` host span;
  * per device `/device:TPU:<n>` of the cell: busy time as the union of
    the operation intervals on its "XLA Ops" line, clipped to the window;
  * device time by operation name and by program ("XLA Modules");
  * the harness's host spans by name;
  * the device's idle time, each part labelled by the most specific host
    span that covers it.
"""
from __future__ import annotations

import re
import shutil
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax

Interval = Tuple[int, int]
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")


@contextmanager
def profile(log_dir: Path):
    """Profile the enclosed block into `log_dir` (emptied first)."""
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


class SpanRecorder:
    """Host spans around program calls, for the traced window only."""

    def __init__(self) -> None:
        self.calls: Dict[str, List[int]] = defaultdict(list)
        self._undo: List[Tuple[object, str, object, bool]] = []

    def wrap(self, owner, attr: str, name: str,
             nbytes: Optional[Callable[[tuple, dict], int]] = None,
             block: bool = False) -> None:
        """Replace `owner.attr` with a spanned call. `block` waits for the
        device result inside the span, so the span covers the round trip
        the caller would wait for next anyway."""
        orig = getattr(owner, attr)
        span = SPAN_PREFIX + name
        calls = self.calls[name]

        def spanned(*args, **kwargs):
            with jax.profiler.TraceAnnotation(span):
                out = orig(*args, **kwargs)
                if block:
                    jax.block_until_ready(out)
            calls.append(nbytes(args, kwargs) if nbytes else 0)
            return out

        own = attr in vars(owner)
        setattr(owner, attr, spanned)
        self._undo.append((owner, attr, orig, own))

    def restore(self) -> None:
        while self._undo:
            owner, attr, orig, own = self._undo.pop()
            if own:
                setattr(owner, attr, orig)
            else:
                delattr(owner, attr)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi) that no interval of `busy` (disjoint,
    sorted) covers."""
    out, cur = [], lo
    for a, b in busy:
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


@dataclass
class Reduced:
    window: Interval
    busy_ns: Dict[int, int]                      # device id -> busy ns
    op_ns: Dict[str, int]                        # device op -> ns (all devs)
    module_ns: Dict[str, int]                    # device program -> ns
    spans: Dict[str, List[Interval]]             # host span -> intervals
    idle_ns: Dict[str, int] = field(default_factory=dict)  # label -> ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the cell's devices."""
        if not self.busy_ns:
            return 0.0
        return sum(self.busy_ns.values()) / len(self.busy_ns) / 1e9

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the operations whose name matches."""
        rx = re.compile(pattern)
        return sum(v for k, v in self.op_ns.items() if rx.search(k)) / 1e9

    def module_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.module_ns.items()
                   if rx.search(k)) / 1e9

    def span_seconds(self, names: Iterable[str]) -> float:
        """Seconds of the window covered by any of the named host spans."""
        ivs = [iv for n in names for iv in self.spans.get(SPAN_PREFIX + n,
                                                          [])]
        return total(union(ivs)) / 1e9

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        """The device operations that took most time (by HLO instruction
        and opcode), and idle time by what the host was doing."""
        n_dev = max(1, len(self.busy_ns))
        by_op: Dict[str, int] = defaultdict(int)
        for name, ns in self.op_ns.items():
            by_op[short_op_name(name)] += ns
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / n_dev / 1e9] for k, v in idle]}


_HLO_OP = re.compile(r"^(%\S+) = .*?\b([a-z][a-z0-9-]*)\(")


def short_op_name(name: str) -> str:
    """`%reshape.1 = bf16[...]{...} reshape(...)` -> `%reshape.1 reshape`."""
    m = _HLO_OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:100]


def _label_gaps(idle: List[Interval],
                spans: Dict[str, List[Interval]]) -> Dict[str, int]:
    """Attribute idle time to host spans: each part of a gap goes to the
    most specific span covering it (the span name with the least total
    time), the rest to "no harness span"."""
    merged = {name[len(SPAN_PREFIX):]: union(ivs)
              for name, ivs in spans.items() if name != WINDOW_SPAN}
    order = sorted(merged, key=lambda n: total(merged[n]))
    out: Dict[str, int] = defaultdict(int)
    left = union(idle)
    for n in order:
        covered = _intersect(left, merged[n])
        if covered:
            out[n] += total(covered)
            left = _subtract(left, covered)
    if left:
        out["no harness span"] += total(left)
    return dict(out)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """`a` minus `b`, where `b` lies inside `a` (both disjoint, sorted)."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][0] < hi:
            if b[j][0] > cur:
                out.append((cur, b[j][0]))
            cur = max(cur, b[j][1])
            j += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def reduce_trace(xplane: Path, device_ids: Iterable[int]) -> Reduced:
    """Reduce one profiler trace file to what the per-layer metrics
    read."""
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(str(xplane)).planes,
                         device_ids)


def reduce_planes(planes, device_ids: Iterable[int]) -> Reduced:
    """`planes`: each with `.name` and `.lines`; a line with `.name` and
    `.events`; an event with `.name`, `.start_ns` and `.duration_ns`."""
    want = set(device_ids)
    spans: Dict[str, List[Interval]] = defaultdict(list)
    dev_ops: Dict[int, List[Tuple[int, int, str]]] = {}
    dev_mods: Dict[int, List[Tuple[int, int, str]]] = {}
    for plane in planes:
        m = _DEVICE_PLANE.fullmatch(plane.name)
        if m is None:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            s = int(ev.start_ns)
                            spans[ev.name].append(
                                (s, s + int(ev.duration_ns)))
            continue
        dev = int(m.group(1))
        if dev not in want:
            continue
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get(OPS_LINE)
        ops = [] if op_line is None else [
            (int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name)
            for e in op_line.events]
        mod_line = lines.get(MODULES_LINE)
        mods = [] if mod_line is None else [
            (int(e.start_ns), int(e.start_ns) + int(e.duration_ns), e.name)
            for e in mod_line.events]
        dev_ops[dev] = ops or mods
        dev_mods[dev] = mods
    if WINDOW_SPAN not in spans:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo = min(a for a, _b in spans[WINDOW_SPAN])
    hi = max(b for _a, b in spans[WINDOW_SPAN])
    busy_ns: Dict[int, int] = {}
    op_ns: Dict[str, int] = defaultdict(int)
    module_ns: Dict[str, int] = defaultdict(int)
    idle: List[Interval] = []
    for dev in sorted(dev_ops):
        ops = dev_ops[dev]
        busy = union(clip([(a, b) for a, b, _n in ops], lo, hi))
        busy_ns[dev] = total(busy)
        idle += gaps(busy, lo, hi)
        for a, b, name in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_ns[name] += b - a
        for a, b, name in dev_mods.get(dev, []):
            a, b = max(a, lo), min(b, hi)
            if b > a:
                module_ns[name] += b - a
    clipped = {n: clip(ivs, lo, hi) for n, ivs in spans.items()}
    return Reduced(window=(lo, hi), busy_ns=busy_ns, op_ns=dict(op_ns),
                   module_ns=dict(module_ns), spans=clipped,
                   idle_ns=_label_gaps(idle, clipped))
