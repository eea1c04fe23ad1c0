"""The reduction of program spans (``harness.spans``): a synthetic trace
with spans four deep, 300 children under one span and a second thread,
and the small recorded chip trace, on which every figure of
``trace.reduce`` stays as it was."""
import json
from dataclasses import asdict
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from harness import spans, trace

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "small_replay.xplane.pb.gz"
#: ``trace.reduce`` of the recorded trace, as the reducer gave it when
#: the trace was committed
RECORDED_REDUCED = DATA / "small_replay.reduced.json"


def _ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def _profile():
    main = [_ev("bench.window", 0, 100_000),
            _ev("bench.pass_boundary", -500, 1000),
            _ev("bench.request", 1000, 98_000),
            _ev("repro.query.flows", 2000, 96_000, request=1, keys=500,
                paths=100)]
    # 300 children under repro.query.flows, one with a child of its own
    main += [_ev("repro.query.launch", 2100 + 300 * k, 100, h2d_bytes=k,
                 keys=2) for k in range(300)]
    main.append(_ev("repro.fleet.sync", 20_720, 20))
    other = [_ev("repro.replay.epoch_packet", 10_000, 20_000, hit=0,
                 packets=7)]
    host = NS(name="/host:CPU", lines=[NS(name="python", events=main),
                                       NS(name="worker", events=other)])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[_ev("fusion.2", 0, 1500),
                                   _ev("fusion.2", 40_000, 50),
                                   _ev("copy", 60_000, 10),
                                   _ev("fusion.3", 95_000, 5000)])])
    return NS(planes=[host, dev])


def test_reduce_synthetic_spans():
    red = spans.reduce(_profile())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(6560e-6)
    # gap [1500, 40000): its midpoint 20750 lies in the launch that
    # starts at 20700 (its child ended at 20740) and in the other
    # thread's longer span; gaps [40050, 60000) and [60010, 95000) fall
    # between launches, under repro.query.flows alone
    assert red.idle_by_span == pytest.approx({
        "repro.query.launch": 38_500e-6, "repro.query.flows": 54_940e-6})
    assert red.idle_gaps_n == {"repro.query.launch": 1,
                               "repro.query.flows": 2}
    assert red.span_n["repro.query.launch"] == 300
    assert red.span_s["bench.pass_boundary"] == pytest.approx(500e-6)
    assert red.self_s == pytest.approx({
        "bench.pass_boundary": 500e-6,
        "bench.request": 2000e-6,
        "repro.query.flows": (96_000 - 300 * 100) * 1e-6,
        "repro.query.launch": (300 * 100 - 20) * 1e-6,
        "repro.fleet.sync": 20e-6,
        # another thread: nothing on the main thread is its child
        "repro.replay.epoch_packet": 20_000e-6})
    assert red.counters["repro.query.launch"] == {
        "h2d_bytes": sum(range(300)), "keys": 600}
    assert red.counters["repro.query.flows"] == {
        "request": 1, "keys": 500, "paths": 100}
    assert red.op_s == pytest.approx({"fusion.2": 1550e-6, "copy": 10e-6,
                                      "fusion.3": 5000e-6})
    # the same idle time split by what each instant of it overlaps; the
    # other thread's span is shorter than repro.query.flows, so it is the
    # innermost where no launch runs
    assert red.idle_self_s == pytest.approx({
        "bench.request": 500e-6, "repro.query.launch": 29_970e-6,
        "repro.fleet.sync": 20e-6, "repro.replay.epoch_packet": 13_400e-6,
        "repro.query.flows": 49_550e-6})
    assert sum(red.idle_self_s.values()) == pytest.approx(
        red.window_s - red.busy_s)
    top = red.breakdown()[0]
    assert top == ["repro.query.flows", pytest.approx(49_550e-6),
                   pytest.approx(66_000e-6), pytest.approx(54_940e-6), 2, 1]


def test_idle_outside_every_span():
    p = _profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[:1]
    p.planes[0].lines[1].events = []
    red = spans.reduce(p)
    assert set(red.idle_by_span) == {spans.NO_SPAN}
    assert red.idle_gaps_n[spans.NO_SPAN] == 3
    assert red.idle_self_s == pytest.approx({spans.NO_SPAN: 93_440e-6})


def test_reduce_without_window_is_empty():
    p = _profile()
    p.planes[0].lines[0].events = p.planes[0].lines[0].events[1:]
    red = spans.reduce(p)
    assert red.window_s == 0 and not red.span_s and not red.idle_by_span


def test_recorded_trace_reduces_as_before():
    """Every figure of ``trace.reduce`` on the recorded chip trace, and
    the same figures from ``spans.reduce``; the trace holds the harness's
    spans only."""
    want = json.loads(RECORDED_REDUCED.read_text())
    profile = trace.load(str(RECORDED))
    assert asdict(trace.reduce(profile)) == want
    red = spans.reduce(profile)
    got = asdict(red)
    assert {k: got[k] for k in want if k != "module_s"} == \
        {k: v for k, v in want.items() if k != "module_s"}
    assert sum(red.idle_self_s.values()) == pytest.approx(
        red.window_s - red.busy_s)
    # the replay packs its epochs, then runs the window: both nest in
    # the pass, and nothing nests in them
    for name in ("bench.pack", "bench.run_window"):
        assert red.self_s[name] == pytest.approx(red.span_s[name])
    assert red.self_s["bench.pass"] == pytest.approx(
        red.span_s["bench.pass"] - red.span_s["bench.pack"]
        - red.span_s["bench.run_window"])
    assert spans.readings(red, windows=4) == {}


def _spans(**kw):
    return spans.Spans(**kw)


@pytest.mark.parametrize("name,red,per,value", [
    ("replay.csr_pack_ms_per_window",
     _spans(span_s={"repro.fleet.pack_csr": 0.2}), 4, 50.0),
    ("replay.sync_wait_ms_per_window",
     _spans(span_s={"repro.fleet.sync": 0.08}), 4, 20.0),
    ("replay.csr_pad_share",
     _spans(counters={"repro.fleet.pack_csr": {"packets": 900,
                                               "slots": 1000,
                                               "slots_live": 950}}),
     4, 10.0),
    ("query.host_prep_ms_per_request",
     _spans(span_s={"repro.query.prep": 2.0}), 2, 1000.0),
    ("query.device_calls_per_request",
     _spans(span_n={"repro.query.launch": 1536}), 2, 768.0),
    ("query.h2d_bytes_per_request",
     _spans(counters={"repro.query.launch": {"h2d_bytes": 1e7,
                                             "keys": 9}}), 2, 5e6),
])
def test_readings(name, red, per, value):
    read, unit = spans.READINGS[name]
    assert read(red, per) == pytest.approx(value)
    assert spans.readings(red, **{unit: per})[name] == pytest.approx(value)
    # nothing to read where the span is missing
    assert read(spans.Spans(), per) is None
    assert name not in spans.readings(spans.Spans(), **{unit: per})


def test_readings_need_the_count_they_divide_by():
    red = _spans(span_s={"repro.fleet.sync": 0.08})
    assert spans.readings(red) == {}
