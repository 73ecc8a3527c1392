"""The trace reduction: interval union, busy time, device time by name,
host spans and labelled idle gaps, on a small synthetic trace and on a
trace recorded here on the CPU."""
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import pytest

from bench import tracing


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def line(name, *events):
    return NS(name=name, events=list(events))


def plane(name, *lines):
    return NS(name=name, lines=list(lines))


def test_union_gaps_clip():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == \
        [(0, 4), (5, 10)]
    assert tracing.gaps([(0, 4), (5, 10)], 0, 12) == [(4, 5), (10, 12)]
    assert tracing.gaps([], 3, 7) == [(3, 7)]
    assert tracing.clip([(0, 4), (6, 20)], 2, 10) == [(2, 4), (6, 10)]
    assert tracing.total([(2, 4), (6, 10)]) == 6


SYNTH = [
    plane("/host:CPU",
          line("python",
               ev("bench.window", 100, 1000),
               ev("bench.op", 100, 500), ev("bench.op", 600, 500),
               ev("bench.rs_parity.ec_encode", 150, 200),
               ev("not ours", 0, 5000)),
          line("worker", ev("bench.splice", 700, 300))),
    plane("/device:TPU:0",
          line("XLA Modules", ev("jit__carve_packed(1)", 700, 200),
               ev("jit__gf_matmul", 200, 100)),
          line("XLA Ops",
               ev("rs_matmul_tiles", 200, 100),       # inside the window
               ev("copy.1", 250, 100),                 # overlaps it
               ev("carve-fusion", 700, 200),
               ev("late", 1050, 200))),                # clipped at 1100
    plane("/device:TPU:1", line("XLA Ops", ev("x", 100, 1000))),
]


def test_reduce_synthetic_trace():
    r = tracing.reduce_planes(SYNTH, [0])
    assert r.window == (100, 1100)
    assert r.window_s == pytest.approx(1000e-9)
    # busy = [200, 350) + [700, 900) + [1050, 1100) = 150 + 200 + 50
    assert r.busy_ns == {0: 400}
    assert r.busy_s == pytest.approx(400e-9)
    assert r.op_ns["late"] == 50
    assert r.op_seconds("rs_matmul") == pytest.approx(100e-9)
    assert r.module_seconds("carve_packed") == pytest.approx(200e-9)
    assert r.span_seconds(["rs_parity.ec_encode"]) == pytest.approx(200e-9)
    assert r.span_seconds(["op"]) == pytest.approx(1000e-9)
    # idle [100,200) [350,700) [900,1050): encode covers [150,200),
    # splice [900,1000), op the rest of [100,1100); [100,150) has op
    assert r.idle_ns == {"rs_parity.ec_encode": 50, "splice": 100,
                         "op": 450}
    bd = r.breakdown()
    assert bd["device_ops"][0] == ["carve-fusion", 200e-9]
    assert bd["idle_gaps"][0] == ["op", 450e-9]


def test_idle_outside_every_span():
    planes = [plane("/host:CPU", line("python", ev("bench.window", 0, 100),
                                      ev("bench.op", 20, 30))),
              plane("/device:TPU:0", line("XLA Ops", ev("k", 40, 20)))]
    r = tracing.reduce_planes(planes, [0])
    # idle [0,40) and [60,100); op covers [20,40)
    assert r.idle_ns == {"op": 20, "no harness span": 60}


def test_reduce_averages_over_the_cells_devices():
    r = tracing.reduce_planes(SYNTH, [0, 1])
    assert r.busy_ns == {0: 400, 1: 1000}
    assert r.busy_s == pytest.approx(700e-9)


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tracing.reduce_planes([plane("/host:CPU", line("python"))], [0])


def test_span_recorder_wraps_and_restores():
    class Thing:
        def f(self, x):
            return x + 1

    t = Thing()
    mod = NS(g=lambda x: x * 2)
    rec = tracing.SpanRecorder()
    rec.wrap(t, "f", "f", nbytes=lambda a, kw: a[0])
    rec.wrap(mod, "g", "g")
    assert t.f(3) == 4 and mod.g(3) == 6
    assert rec.calls == {"f": [3], "g": [0]}
    rec.restore()
    assert "f" not in vars(t) and mod.g(5) == 10


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with tracing.profile(tmp_path):
        with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
            with jax.profiler.TraceAnnotation("bench.op"):
                f(x).block_until_ready()
    r = tracing.reduce_trace(tracing.find_xplane(tmp_path), [0])
    assert r.window_s > 0
    assert r.span_seconds(["op"]) > 0
    assert r.busy_ns == {}           # no TPU plane on the CPU
