"""The store's own host spans (core/tracing.py): off, they cost a shared
no-op and no profiler call; on, one ec(4,2) full-stripe write, one 4 KiB
delta write and one small device-direct load recorded on a CPU profiler
trace carry every declared span, and the ids tie the spans of one
request together across threads."""
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core import counters_registry, tracing
from repro.core.client import ROS2Client
from repro.core.device_direct import DeviceDirectSink


@pytest.fixture
def switch(monkeypatch):
    """The switch in its default mode (following the profiler), restored
    to it whatever the test does."""
    monkeypatch.setattr(tracing, "_forced", None)
    monkeypatch.setattr(tracing, "_on", False)
    return tracing


class _Recorder:
    """Stands in for TraceAnnotation and records every use of it."""
    calls: list = []

    def __init__(self, name, **ids):
        self.calls.append(("span", name))

    @classmethod
    def is_enabled(cls):
        cls.calls.append(("is_enabled",))
        return False


def test_off_returns_the_shared_noop_without_a_profiler_call(
        switch, monkeypatch):
    monkeypatch.setattr(tracing, "TraceAnnotation", _Recorder)
    _Recorder.calls = []
    switch.disable()
    op = switch.next_op_id()
    assert switch.next_op_id() == op + 1
    with switch.span("ros2.ec.write", op=op) as sp:
        sp.set_metadata(path="full")
    assert switch.span("ros2.engine") is switch.NOOP
    assert _Recorder.calls == []


def test_default_follows_the_profiler(switch, monkeypatch):
    monkeypatch.setattr(tracing, "TraceAnnotation", _Recorder)
    _Recorder.calls = []
    switch.next_op_id()
    assert switch.span("ros2.engine") is switch.NOOP
    switch.next_op_id()
    # one look per request, none per span
    assert _Recorder.calls == [("is_enabled",), ("is_enabled",)]


def test_enable_turns_spans_on_without_a_profiler(switch, monkeypatch):
    monkeypatch.setattr(tracing, "TraceAnnotation", _Recorder)
    _Recorder.calls = []
    switch.enable()
    switch.next_op_id()
    assert isinstance(switch.span("ros2.engine", verb="fetch"), _Recorder)
    assert _Recorder.calls == [("span", "ros2.engine")]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Host events named ros2.* of one traced window: [(name, stats)]."""
    from jax.profiler import ProfileData

    ec = ROS2Client(mode="host", n_targets=8, ec=(4, 2),
                    domains=list("aabbccdd"), scrub_interval_s=None)
    dpu = ROS2Client(mode="dpu", n_targets=4, scrub_interval_s=None)
    sink = None
    try:
        fd = ec.open("/f", create=True)
        ec.pwrite(fd, np.ones(1 << 20, np.uint8).tobytes(), 0)  # warm
        g = dpu.open("/w", create=True)
        dpu.pwrite(g, np.arange(1 << 16, dtype=np.uint8).tobytes(), 0)
        sink = DeviceDirectSink(dpu, slot_bytes=32 << 10, n_slots=2)
        reqs = [(g, 0, (16 << 10,), np.uint8),
                (g, 16 << 10, (16 << 10,), np.uint8),
                (g, 32 << 10, (32 << 10,), np.uint8)]
        sink.read_tensors(reqs)                                  # warm
        ec.io._ec_drain()          # no straggler of the warm-up in the trace
        d = tmp_path_factory.mktemp("trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(d), profiler_options=opts)
        try:
            ec.pwrite(fd, np.full(1 << 20, 3, np.uint8).tobytes(), 1 << 20)
            ec.pwrite(fd, np.full(4096, 5, np.uint8).tobytes(), 8192)
            arrs = sink.read_tensors(reqs)
            dpu.open("/w2", create=True)
        finally:
            jax.profiler.stop_trace()
        ec.io._ec_drain()
        assert np.asarray(arrs[2])[0] == 0
        xplane = next(d.glob("plugins/profile/*/*.xplane.pb"))
        events = [(ev.name, dict(ev.stats))
                  for plane in ProfileData.from_file(str(xplane)).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("ros2.")]
    finally:
        if sink is not None:
            sink.close()
        dpu.close()
        ec.close()
    return events


# Emitted only when a load spans several devices; the four-device trace
# of tests/test_sharded_placement.py records it.
ACROSS_DEVICES = {"ros2.place.exchange"}


def test_every_declared_span_is_recorded(recorded):
    assert {n for n, _s in recorded} == counters_registry.SPANS - \
        ACROSS_DEVICES


def test_spans_carry_their_stats(recorded):
    want = {
        "ros2.place.load": {"op", "bytes"},
        "ros2.place.slot_wait": {"op", "slot"},
        "ros2.place.splice": {"op", "bytes"},
        "ros2.place.put": {"op", "bytes"},
        "ros2.place.carve": {"op"},
        "ros2.place.drain": {"op"},
        "ros2.place.shard": {"op", "dev", "bytes"},
        "ros2.place.exchange": {"op", "dev", "bytes"},
        "ros2.dpu.call": {"tag", "verb"},
        "ros2.dpu.exec": {"tag", "verb"},
        "ros2.router.sq_wait": {"tid"},
        "ros2.router.batch": {"tid", "runs"},
        "ros2.target": {"tid", "verb", "bytes"},
        "ros2.engine": {"verb", "bytes"},
        "ros2.control.rpc": {"method"},
        "ros2.ec.write": {"op", "block", "path"},
        "ros2.ec.place": {"op"},
        "ros2.ec.ledger": {"op", "verb", "targets"},
        "ros2.ec.drain": {"op", "pending"},
        "ros2.ec.old_fetch": {"op", "bytes"},
        "ros2.ec.parity.submit": {"op", "bytes"},
        "ros2.ec.parity.wait": {"op"},
        "ros2.ec.fanout": {"op", "cells"},
        "ros2.ec.cell": {"op", "cell", "tid"},
    }
    assert set(want) == counters_registry.SPANS
    for name, stats in recorded:
        assert set(stats) == want[name], name
    writes = {s["block"]: s for n, s in recorded if n == "ros2.ec.write"}
    assert writes[1]["path"] == "full" and writes[0]["path"] == "delta"
    (load,) = [s for n, s in recorded if n == "ros2.place.load"]
    assert load["bytes"] == 64 << 10
    assert sum(s["bytes"] for n, s in recorded
               if n == "ros2.place.splice") == 64 << 10


def test_cells_carry_the_op_of_their_write(recorded):
    writes = {s["op"]: s for n, s in recorded if n == "ros2.ec.write"}
    cells = defaultdict(list)
    for n, s in recorded:
        if n == "ros2.ec.cell":
            cells[s["op"]].append(s["cell"])
    assert set(cells) == set(writes)
    full = [op for op, s in writes.items() if s["path"] == "full"]
    delta = [op for op, s in writes.items() if s["path"] == "delta"]
    assert sorted(cells[full[0]]) == list(range(6))   # k + p cells
    assert sorted(cells[delta[0]]) == [0, 4, 5]       # touched + parity
    for n, s in recorded:
        if n.startswith("ros2.ec.") and n != "ros2.ec.write":
            assert s["op"] in writes, n


def test_dpu_exec_carries_the_tag_of_its_call(recorded):
    calls = {s["tag"]: s["verb"] for n, s in recorded
             if n == "ros2.dpu.call"}
    execs = {s["tag"]: s["verb"] for n, s in recorded
             if n == "ros2.dpu.exec"}
    assert calls and calls == execs
    assert set(calls.values()) == {"read_into_many", "open"}
