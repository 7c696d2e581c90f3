"""Reduce a profiler trace of the measured window to the device numbers.

``record(log_dir)`` traces with the Python tracer off and the host tracer
at its critical level: host spans come from ``jax.profiler.TraceAnnotation``
and the runtime's own top-level ones (``PjitFunction(...)``, ``DevicePut``,
``np.asarray(jax.Array)``), not its internal detail, so a whole measured
window stays small enough to read;
``load(log_dir)`` reads the ``.xplane.pb`` it wrote into a plain
:class:`Trace`, which ``Trace.to_json``/``from_json`` keep in a file so the
reduction can be checked on a recorded trace without a chip.

``reduce(trace, window)`` takes the host span named ``window`` as the
measured window and gives, over it:

  * ``busy_s``: the union of the intervals in which an operation ran on the
    device, averaged over the devices in the trace;
  * ``op_s``: each device operation's summed self time (less the time of
    the operations nested in it, as a ``while`` holds its body), by the
    HLO instruction's name, averaged over the devices;
  * ``gaps``: the device's idle gaps, each attributed to what the host was
    doing at its middle: the innermost host span on the window's thread
    that covers it.

Only operation lines count as device work: the line of each device plane
that holds the operations (``XLA Ops``), not those that repeat them at a
coarser grain (modules, steps).  Stats are not read: on a long window they
cost minutes, and the names say what the reduction needs.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Tuple

#: the device planes' prefix and the name of their operation line
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
#: host planes' prefix
HOST_PREFIX = "/host:"

Event = Tuple[str, float, float]   # name, start (ns), duration (ns)


@dataclass
class Trace:
    #: plane name -> line name -> events
    planes: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"planes": {p: {l: [list(e) for e in evs]
                               for l, evs in lines.items()}
                           for p, lines in self.planes.items()}}

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        return cls({p: {l: [(e[0], float(e[1]), float(e[2])) for e in evs]
                        for l, evs in lines.items()}
                    for p, lines in obj["planes"].items()})


@contextlib.contextmanager
def record(log_dir: str) -> Iterator[None]:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def short_name(name: str) -> str:
    """A device op's name is its HLO instruction (``%fusion.3 = f32[..]
    fusion(...), kind=kOutput, ...``); keep the instruction's own name."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(log_dir: str) -> Trace:
    """The device operation lines and the host lines of the one
    ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {paths}")
    data = ProfileData.from_file(paths[0])
    out = Trace()
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith(HOST_PREFIX):
            continue
        lines = out.planes.setdefault(plane.name, {})
        for i, line in enumerate(plane.lines):
            if device and line.name != OPS_LINE:
                continue
            # host threads may share a name
            key = line.name if line.name not in lines else f"{line.name} {i}"
            lines[key] = [(short_name(ev.name) if device else ev.name,
                                 float(ev.start_ns), float(ev.duration_ns))
                                for ev in line.events]
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def self_times(events: List[Event]) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self time)`` of each event: its duration less
    that of the events nested in it (a ``while`` holds its body's ops)."""
    out: List[List] = []
    stack: List[int] = []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and out[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= dur
        out.append([name, start, end, dur])
        stack.append(len(out) - 1)
    return [tuple(o) for o in out]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    devices: int
    op_s: Dict[str, float]
    #: (what the host was doing, seconds) for every idle gap
    gaps: List[Tuple[str, float]]

    def top_ops(self, n: int = 10) -> List[List]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        """Idle time summed by what the host was doing, largest first."""
        by: Dict[str, float] = {}
        for label, s in self.gaps:
            by[label] = by.get(label, 0.0) + s
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:n]]


def _find_window(trace: Trace, window: str):
    for plane, lines in trace.planes.items():
        if not plane.startswith(HOST_PREFIX):
            continue
        for evs in lines.values():
            for name, start, dur in evs:
                if name == window:
                    return evs, start, start + dur
    raise RuntimeError(f"no host span {window!r} in the trace")


def _host_labels(evs: List[Event], times: List[float], window: str
                 ) -> List[str]:
    """For each of ``times``, the innermost span of ``evs`` (one thread's,
    so nested) that covers it, or ``window``: one sweep over both."""
    spans = sorted(((start, start + dur, name) for name, start, dur in evs
                    if name != window), key=lambda e: (e[0], -e[1]))
    labels: Dict[int, str] = {}
    stack: List[Tuple[float, float, str]] = []
    i = 0
    for k in sorted(range(len(times)), key=times.__getitem__):
        t = times[k]
        while i < len(spans) and spans[i][0] <= t:
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        labels[k] = stack[-1][2] if stack else window
    return [labels[k] for k in range(len(times))]


def reduce(trace: Trace, window: str) -> Reduced:
    host_evs, w0, w1 = _find_window(trace, window)
    host_evs = [e for e in host_evs if e[1] < w1 and e[1] + e[2] > w0]
    op_s: Dict[str, float] = {}
    busy_ns = 0.0
    gaps = []
    devices = [p for p in trace.planes if p.startswith(DEVICE_PREFIX)
               and trace.planes[p].get(OPS_LINE)]
    for plane in devices:
        spans = []
        for name, start, end, own in self_times(trace.planes[plane][OPS_LINE]):
            a, b = max(start, w0), min(end, w1)
            if b <= a:
                continue
            spans.append((a, b))
            own *= (b - a) / (end - start)
            op_s[name] = op_s.get(name, 0.0) + own * 1e-9
        busy = union(spans)
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        labels = _host_labels(host_evs, [(a + b) / 2 for a, b in idle],
                              window)
        gaps += [(label, (b - a) * 1e-9)
                 for label, (a, b) in zip(labels, idle)]
    n = max(1, len(devices))
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   devices=len(devices),
                   op_s={k: v / n for k, v in op_s.items()}, gaps=gaps)


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)
