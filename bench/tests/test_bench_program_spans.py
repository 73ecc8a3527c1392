"""The program's `ros2.*` spans in a traced run: kept with their stats,
unions and differences over the window, idle time labelled by the most
specific program span, the harness's own reduction unchanged by them, and
the seven readers (None when their spans are absent)."""
from types import SimpleNamespace as NS

import pytest

from bench import harness, program_spans, tracing


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def line(name, *events):
    return NS(name=name, events=list(events))


def plane(name, *lines):
    return NS(name=name, lines=list(lines))


DEVICE = plane("/device:TPU:0",
               line("XLA Ops", ev("k1", 200, 100), ev("k2", 700, 200)))
# window [100, 1100); device busy [200, 300) and [700, 900), so idle is
# [100, 200) [300, 700) [900, 1100)
SYNTH = [
    plane("/host:CPU",
          line("python",
               ev("bench.window", 100, 1000),
               ev("bench.op", 100, 1000),
               ev("ros2.ec.write", 100, 900, op=1, block=0, path="full"),
               ev("ros2.ec.ledger", 150, 100, op=1, targets=8),
               ev("ros2.ec.fanout", 400, 400, op=1, cells=6),
               ev("ros2.dpu.call", 900, 150, tag=3, verb="read")),
          line("pool-1",
               ev("ros2.ec.cell", 450, 200, op=1, cell=0, tid="t2")),
          line("arm0",
               ev("ros2.dpu.exec", 950, 50, tag=3, verb="read"))),
    DEVICE,
]


# the EC spans of SYNTH alone
EC_ONLY = [plane("/host:CPU", line(
    "python", *[e for e in SYNTH[0].lines[0].events
                if not e.name.startswith("ros2.dpu.")]),
    SYNTH[0].lines[1]), DEVICE]


def test_program_spans_kept_with_their_stats():
    ps = program_spans.reduce_planes(SYNTH, [0])
    assert ps.window == (100, 1100)
    assert set(ps.spans) == {"ros2.ec.write", "ros2.ec.ledger",
                             "ros2.ec.fanout", "ros2.dpu.call",
                             "ros2.ec.cell", "ros2.dpu.exec"}
    assert ps.stats["ros2.ec.cell"] == [{"op": 1, "cell": 0, "tid": "t2"}]
    assert ps.stats["ros2.ec.write"][0]["path"] == "full"
    assert ps.spans["ros2.dpu.call"] == [(900, 1050)]


def test_unions_and_differences():
    ps = program_spans.reduce_planes(SYNTH, [0])
    assert ps.program_seconds(["ros2.ec.fanout"]) == pytest.approx(400e-9)
    # fanout [400, 800) with its cell [450, 650) inside: still 400
    assert ps.program_seconds(["ros2.ec.fanout", "ros2.ec.cell"]) == \
        pytest.approx(400e-9)
    assert ps.program_seconds(["ros2.ec.ledger", "ros2.ec.fanout"]) == \
        pytest.approx(500e-9)
    # dpu call [900, 1050) less its exec [950, 1000)
    assert ps.program_minus(["ros2.dpu.call"], ["ros2.dpu.exec"]) == \
        pytest.approx(100e-9)
    assert ps.program_minus(["ros2.dpu.call"], ["ros2.ec.ledger"]) == \
        pytest.approx(150e-9)
    assert ps.program_seconds(["ros2.engine"]) == 0.0


def test_idle_by_program_span_nests_across_threads():
    ps = program_spans.reduce_planes(SYNTH, [0])
    # idle [100,200): ledger [150,200), write [100,150)
    # idle [300,700): cell (another thread) [450,650), fanout [400,450)
    #   and [650,700), write [300,400)
    # idle [900,1100): exec [950,1000), call [900,950) and [1000,1050),
    #   nothing after 1050
    assert ps.idle_ns == {"ros2.ec.ledger": 50, "ros2.ec.write": 150,
                          "ros2.ec.cell": 200, "ros2.ec.fanout": 100,
                          "ros2.dpu.exec": 50, "ros2.dpu.call": 100,
                          program_spans.NO_SPAN: 50}
    top = ps.idle_by_program_span(top=2)
    assert top == [["ros2.ec.cell", 200e-9], ["ros2.ec.write", 150e-9]]


def test_planes_that_iterate_once():
    """The profiler's `planes` is a one-shot iterator: the device planes
    must still reach the idle labelling."""
    ps = program_spans.reduce_planes(iter(SYNTH), [0])
    assert ps.idle_ns == program_spans.reduce_planes(SYNTH, [0]).idle_ns


def test_no_program_span_reads_none():
    planes = [plane("/host:CPU", line("python", ev("bench.window", 0, 10),
                                      ev("bench.op", 0, 10))), DEVICE]
    assert program_spans.reduce_planes(planes, [0]) is None


def test_harness_reduction_unchanged_by_program_spans():
    without = [plane("/host:CPU", line(
        "python", *[e for e in SYNTH[0].lines[0].events
                    if not e.name.startswith("ros2.")])), DEVICE]
    a = tracing.reduce_planes(SYNTH, [0])
    b = tracing.reduce_planes(without, [0])
    assert a.breakdown() == b.breakdown()
    assert a.idle_ns == b.idle_ns == {"op": 700}
    assert a.spans == b.spans and a.busy_ns == b.busy_ns


def _run(user_bytes=10 ** 9, planes=SYNTH):
    run = harness.Run(cell="c", config={}, traffic={}, device_kind="TPU",
                      user_bytes=user_bytes)
    run.trace = tracing.reduce_planes(planes, [0])
    run.program_spans = program_spans.reduce_planes(planes, [0])
    return run


LOAD = [plane("/host:CPU", line(
    "python", ev("bench.window", 0, 10 ** 6),
    ev("ros2.place.load", 0, 10 ** 6, op=1, bytes=1),
    ev("ros2.place.slot_wait", 1000, 3000, op=1, slot=0),
    ev("ros2.place.drain", 2000, 4000, op=1),
    ev("ros2.dpu.call", 10 ** 4, 10 ** 5, tag=1, verb="read_into_many")),
    line("arm0", ev("ros2.dpu.exec", 2 * 10 ** 4, 5 * 10 ** 4, tag=1,
                    verb="read_into_many"))), DEVICE]


@pytest.mark.parametrize("name, planes, want", [
    # slot_wait [1000, 4000) U drain [2000, 6000) = 5 us, per 1 GB
    ("h2d_wait_ms_per_GB", LOAD, 5e-3),
    # call 100 us less exec 50 us, per 1 GB
    ("dpu_hop_ms_per_GB", LOAD, 5e-2),
    ("ec_fanout_share.bulk", SYNTH, 40.0),
    ("ec_fanout_share.small", SYNTH, 40.0),
    ("ec_ledger_share.bulk", SYNTH, 10.0),
    ("ec_ledger_share.small", SYNTH, 10.0),
    ("old_fetch_share.small", SYNTH, None),
])
def test_readers(name, planes, want):
    value = harness.load_metric(name).read(_run(planes=planes))
    assert value == (None if want is None else pytest.approx(want))
    # spans of another cell only: nothing to read
    other = EC_ONLY if planes is LOAD else LOAD
    assert harness.load_metric(name).read(_run(planes=other)) is None


@pytest.mark.parametrize("name", [
    "h2d_wait_ms_per_GB", "dpu_hop_ms_per_GB", "ec_fanout_share.bulk",
    "ec_ledger_share.small", "old_fetch_share.small"])
def test_readers_without_a_trace_read_none(name):
    run = harness.Run(cell="c", config={}, traffic={}, device_kind="TPU")
    assert harness.load_metric(name).read(run) is None


@pytest.mark.parametrize("cell, names", [
    ("granite-3-2b.load", {"h2d_wait_ms_per_GB", "dpu_hop_ms_per_GB"}),
    ("ec4p2.randwrite-4k", {"ec_fanout_share.small",
                            "ec_ledger_share.small",
                            "old_fetch_share.small"}),
])
def test_traced_tiny_run_reads_the_program_spans(run_tiny, cell, names,
                                                 tmp_path, monkeypatch):
    """On the CPU: the readers find the program's spans in the trace the
    harness recorded (the values are CPU times, not device metrics)."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    res = run_tiny(cell, trace=True)
    assert res["correct"], res["checks"]
    assert names <= set(res["metrics"])
    assert all(res["metrics"][n]["value"] > 0 for n in names)
