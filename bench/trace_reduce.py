"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

A trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData`` into plain events: (plane, line, name, start,
end) in seconds on the profiler's clock, which host and device planes
share. From those:

* ``busy``: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped
  to given windows; the idle share over the windows is 1 - busy / length.
* ``op_time`` and ``module_time``: device seconds per operation name and
  per jitted module (``XLA Modules`` line). Until the program names its
  steps, a jitted function is found by the name XLA gives its module
  (``jit_<function name>``).
* ``idle_gaps``: the longest gaps between busy intervals, each labelled
  with the innermost host event (``/host:CPU``) that covers its middle:
  what the host was doing while the device waited.

The harness's own host spans are ``jax.profiler.TraceAnnotation`` events
named ``bench:<step>`` (``bench/kinds/``), so the windows of one kind of
step come from the same trace.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


def trace_options():
    """Profiler options for a benchmark window: host annotations and JAX's
    own host events, no Python function tracer (it would slow the host it
    measures), no HLO protos."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 2
    o.enable_hlo_proto = False
    return o


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
    return out


def find_trace(trace_dir: Path) -> str:
    found = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


# ----------------------------------------------------------------------
# intervals
# ----------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Sequence[Interval],
         windows: Sequence[Interval]) -> List[Interval]:
    """The parts of ``intervals`` inside ``windows`` (both merged first)."""
    a, w = union(intervals), union(windows)
    out, j = [], 0
    for s, e in a:
        while j < len(w) and w[j][1] <= s:
            j += 1
        k = j
        while k < len(w) and w[k][0] < e:
            lo, hi = max(s, w[k][0]), min(e, w[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


# ----------------------------------------------------------------------
# the trace's content
# ----------------------------------------------------------------------

def device_ids(events: Sequence[Event]) -> List[str]:
    return sorted({e.plane for e in events if DEVICE_PLANE.match(e.plane)})


def ops(events: Sequence[Event], plane: Optional[str] = None) -> List[Event]:
    return [e for e in events if e.line == OPS_LINE
            and DEVICE_PLANE.match(e.plane)
            and (plane is None or e.plane == plane)]


def modules(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if e.line == MODULES_LINE
            and DEVICE_PLANE.match(e.plane)]


def host_spans(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if e.plane == HOST_PLANE and e.dur > 0]


def busy_s(events: Sequence[Event], windows: Sequence[Interval]) -> float:
    """Seconds in which some operation ran on a device, inside
    ``windows``, averaged over the devices traced."""
    planes = device_ids(events)
    if not planes:
        return 0.0
    return sum(length(clip([(e.start, e.end) for e in ops(events, p)],
                           windows)) for p in planes) / len(planes)


def idle_share(events: Sequence[Event],
               windows: Sequence[Interval]) -> Optional[float]:
    total = length(union(windows))
    if total <= 0 or not device_ids(events):
        return None
    return 1.0 - busy_s(events, windows) / total


CONTAINERS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """An XLA op event is named by its HLO instruction
    (``%fusion.3 = f32[...] fusion(...), ...``): keep the instruction's
    name."""
    return name.split(" = ", 1)[0]


def op_code(name: str) -> str:
    """The HLO opcode of an op event's instruction text ("" if none)."""
    rest = name.split(" = ", 1)
    if len(rest) < 2:
        return ""
    m = re.search(r"\s([a-z][a-z0-9_-]*)\(", rest[1])
    return m.group(1) if m else ""


def time_by_name(evs: Iterable[Event]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for e in evs:
        out[e.name] = out.get(e.name, 0.0) + e.dur
    return out


def op_time(events: Sequence[Event]) -> Dict[str, float]:
    """Device seconds per op, averaged over the devices traced; control
    flow (while, conditional, call) is left out, since the ops it runs are
    counted themselves."""
    planes = device_ids(events)
    out: Dict[str, float] = {}
    for e in ops(events):
        if op_code(e.name) in CONTAINERS:
            continue
        k = op_name(e.name)
        out[k] = out.get(k, 0.0) + e.dur / len(planes)
    return out


def idle_gaps(events: Sequence[Event], window: Interval,
              top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps between busy intervals of the first device
    inside ``window``, each named by the innermost host event covering its
    middle."""
    planes = device_ids(events)
    if not planes:
        return []
    busy = clip([(e.start, e.end) for e in ops(events, planes[0])],
                [window])
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = host_spans(events)
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        cover = [h for h in host if h.start <= mid <= h.end]
        name = min(cover, key=lambda h: h.dur).name if cover else "(none)"
        out.append((name, e - s))
    return out


def reduce_events(events: Sequence[Event], top: int = 10) -> Dict:
    """The trace's summary: traced window, busy seconds, per-op and
    per-module device time, host spans by name, breakdown."""
    dev = ops(events) + modules(events)
    host = host_spans(events)
    if not dev:
        return {"window_s": 0.0, "busy_s": 0.0, "ops": {}, "modules": {},
                "spans": {}, "events": list(events),
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    # the traced window: from the first event to the last, host or device
    seen = dev + host
    window = (min(e.start for e in seen), max(e.end for e in seen))
    op_t = op_time(events)
    top_ops = sorted(op_t.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": window[1] - window[0],
            "busy_s": busy_s(events, [window]),
            "ops": op_t,
            "modules": time_by_name(modules(events)),
            "spans": {n: [(h.start, h.end) for h in host if h.name == n]
                      for n in {h.name for h in host
                                if h.name.startswith("bench:")}},
            "events": list(events),
            "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                          "idle_gaps": [[n, d] for n, d in
                                        idle_gaps(events, window, top)]}}


def reduce_dir(trace_dir: Path) -> Dict:
    return reduce_events(load(find_trace(trace_dir)))
