"""The readers of the program's own spans, on hand-made trace events:
``decode_call_ms.serve`` counts only the decode calls (modules that start
inside ``serve.decode``), and ``decode_host_ms.serve`` is the mean
``serve.token`` span, or nothing where there is none."""
from types import SimpleNamespace

import pytest

from bench import harness as H
from bench.trace_reduce import HOST_PLANE, MODULES_LINE, Event

DEV = "/device:TPU:0"


def reader(name):
    return H.load_module(H.BENCH / "metrics" / f"{name}.py",
                         f"reader_{name.replace('.', '_')}").read


def host(name, start, end):
    return Event(HOST_PLANE, "python", name, start, end)


def module(name, start, end):
    return Event(DEV, MODULES_LINE, name, start, end)


def run_of(events):
    return SimpleNamespace(device_trace={"events": events})


def serve_step(t0, prefill_calls=3, decode_calls=4):
    """One serve step: prefill calls of 10 ms inside serve.prefill, then
    decode calls of 4 ms, each with a 0.1 ms sampling module, inside
    serve.decode."""
    ev, t = [host("serve.prefill", t0, t0 + 0.011 * prefill_calls)], t0
    for _ in range(prefill_calls):
        ev.append(module("jit_decode_step(7)", t, t + 0.010))
        t += 0.011
    ev.append(host("serve.decode", t, t + 0.0045 * decode_calls + 0.001))
    for _ in range(decode_calls):
        ev.append(host("serve.token", t, t + 0.002))
        ev.append(module("jit_decode_step(7)", t, t + 0.004))
        ev.append(module("jit_argmax(3)", t + 0.004, t + 0.0041))
        t += 0.0045
    return ev


def test_decode_call_counts_only_calls_inside_serve_decode():
    read = reader("decode_call_ms.serve")
    events = serve_step(0.0) + serve_step(1.0, prefill_calls=5)
    assert read(run_of(events)) == pytest.approx(4.0)
    # a module that starts inside serve.prefill and runs past its end is
    # still a prefill call
    late = [module("jit_decode_step(7)", 0.032, 0.060)]
    assert read(run_of(events + late)) == pytest.approx(4.0)


def test_decode_call_reads_nothing_without_the_program_spans():
    read = reader("decode_call_ms.serve")
    no_spans = [e for e in serve_step(0.0) if e.plane == DEV]
    assert read(run_of(no_spans)) is None
    assert read(SimpleNamespace(device_trace=None)) is None


def test_decode_host_is_the_mean_token_span():
    read = reader("decode_host_ms.serve")
    events = serve_step(0.0) + [host("serve.token", 2.0, 2.005)]
    assert read(run_of(events)) == pytest.approx((4 * 2.0 + 5.0) / 5)


def test_decode_host_reads_none_not_zero_without_token_spans():
    read = reader("decode_host_ms.serve")
    events = [e for e in serve_step(0.0) if e.name != "serve.token"]
    assert read(run_of(events)) is None
    assert read(SimpleNamespace(device_trace=None)) is None
