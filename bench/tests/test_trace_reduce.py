"""The reduction from a profiler trace to busy and idle time, per-op and
per-module time and labelled idle gaps: on hand-made events with numbers
worked out by hand, and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest

from bench import trace_reduce as TR
from bench.trace_reduce import Event

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, s, e):
    return Event(plane, line, name, s, e)


EVENTS = [
    ev(HOST, "python", "bench:window", 0.0, 10.0),
    ev(HOST, "python", "bench:serve", 1.0, 4.0),
    ev(HOST, "python", "bench:serve", 6.0, 9.0),
    ev(HOST, "python", "dispatch", 4.5, 5.5),
    ev(DEV, "XLA Modules", "jit_step(1)", 1.0, 3.0),
    ev(DEV, "XLA Modules", "jit_step(1)", 6.5, 8.5),
    ev(DEV, "XLA Ops", "fusion", 1.0, 2.0),
    ev(DEV, "XLA Ops", "dot", 1.5, 3.0),       # overlaps fusion
    ev(DEV, "XLA Ops", "dot", 6.5, 8.5),
]


def test_union_and_clip():
    assert TR.union([(3, 4), (1, 2), (1.5, 2.5)]) == [(1, 2.5), (3, 4)]
    assert TR.clip([(0, 5)], [(1, 2), (4, 6)]) == [(1, 2), (4, 5)]
    assert TR.length([(1, 2.5), (3, 4)]) == 2.5


def test_busy_and_idle_share():
    # busy union: [1, 3] and [6.5, 8.5] = 4 s of the 10 s window
    assert TR.busy_s(EVENTS, [(0, 10)]) == pytest.approx(4.0)
    assert TR.idle_share(EVENTS, [(0, 10)]) == pytest.approx(0.6)
    # inside the serve spans [1, 4] and [6, 9]: busy 2 + 2 of 6
    assert TR.idle_share(EVENTS, [(1, 4), (6, 9)]) == pytest.approx(1 / 3)


def test_op_and_module_time():
    r = TR.reduce_events(EVENTS)
    assert r["ops"] == pytest.approx({"fusion": 1.0, "dot": 3.5})
    assert r["modules"] == pytest.approx({"jit_step(1)": 4.0})
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(4.0)
    assert r["breakdown"]["device_ops"][0] == ["dot", pytest.approx(3.5)]
    assert r["spans"]["bench:serve"] == [(1.0, 4.0), (6.0, 9.0)]


def test_idle_gaps_labelled_by_host_span():
    gaps = TR.idle_gaps(EVENTS, (0.0, 10.0))
    # gaps: [3, 6.5] 3.5 s (middle 4.75: under "dispatch"), [0, 1] and
    # [8.5, 10] (under the window span only)
    assert gaps[0] == ("dispatch", pytest.approx(3.5))
    assert gaps[1] == ("bench:window", pytest.approx(1.5))
    assert gaps[2] == ("bench:window", pytest.approx(1.0))


def test_no_device_events_reads_nothing():
    host_only = [e for e in EVENTS if e.plane == HOST]
    assert TR.idle_share(host_only, [(0, 10)]) is None
    assert TR.reduce_events(host_only)["busy_s"] == 0.0


RECORDED = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


def test_recorded_v5e_trace():
    """Three calls of jit(lambda x: tanh(x @ x) @ x) on a 1024 x 1024 bf16
    array, each inside a ``bench:serve`` annotation, on a TPU v5 lite.
    By hand from the trace's events (ns): each call is copy-start (14, 13,
    14), copy-done (3), the first fusion (11573, 11572, 11573) and the
    second (12615, 12616, 12617), separated by 1-2 ns; busy 24205 + 24204
    + 24207 = 72616 ns. The modules last 24215, 24216 and 24217 ns."""
    events = TR.load(str(RECORDED))
    assert TR.device_ids(events) == ["/device:TPU:0"]
    r = TR.reduce_events(events)
    assert r["busy_s"] == pytest.approx(72616e-9, abs=1e-12)
    assert r["modules"] == pytest.approx(
        {"jit__lambda(18084989565708003084)": 72648e-9}, abs=1e-12)
    assert r["ops"]["%fusion"] == pytest.approx(37848e-9, abs=1e-12)
    assert r["ops"]["%convolution_tanh_fusion"] == \
        pytest.approx(34718e-9, abs=1e-12)
    assert len(r["spans"]["bench:serve"]) == 3
    # the traced window runs from the first event to the last
    assert r["window_s"] == pytest.approx(0.010611926, abs=1e-9)
    # the longest gap lies between the calls, where the host had closed
    # its serve span: only the window's annotation covers it
    name, secs = r["breakdown"]["idle_gaps"][0]
    assert name == "bench:window" and 0.0035 < secs < 0.0040
    assert [TR.op_code(e.name) for e in TR.ops(events)][:4] == \
        ["copy-start", "copy-done", "fusion", "fusion"]
