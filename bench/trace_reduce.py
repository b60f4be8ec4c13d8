"""From a profiler trace to the numbers the per-layer metrics read.

``load_dir`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two kinds of event, on one clock:

  * device operations: the ``XLA Ops`` line of every ``/device:TPU:<n>``
    plane, named by the head of their HLO text (``%fusion.12 = s32[...]
    fusion(...)``);
  * the harness's host spans (``jax.profiler.TraceAnnotation``): ``pump``,
    ``backend.step``, ``balancer.step``, ``traffic``, ``referee`` and the
    ``window`` span that brackets the traced part of the window.

``summarize`` clips everything to the ``window`` span and reduces it:
device busy time is the union of the operations' intervals on each chip,
averaged over the chips; an idle gap is an interval in which no chip runs
an operation, and is named after the innermost host span open at its
middle. The events also round-trip through JSON (``dump_events``/
``read_events``), which is how the tests keep a small recorded trace.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, NamedTuple, Tuple

WINDOW_SPAN = "window"
HOST_SPANS = ("pump", "backend.step", "balancer.step", "traffic", "referee")
DEVICE_LINE = "XLA Ops"
NAME_CHARS = 120     # an op's name is its HLO text: keep its head
TOP = 10


class Event(NamedTuple):
    kind: str        # "device" or "host"
    plane: str       # device plane name, or "host"
    name: str
    start: float     # ns
    end: float       # ns


def load_dir(trace_dir: Path) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    wanted = set(HOST_SPANS) | {WINDOW_SPAN}
    events: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for e in line.events:
                    events.append(Event("device", plane.name,
                                        e.name[:NAME_CHARS], e.start_ns,
                                        e.end_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in wanted:
                        events.append(Event("host", "host", e.name,
                                            e.start_ns, e.end_ns))
    return events


def dump_events(events: List[Event], path: Path) -> None:
    Path(path).write_text(json.dumps([list(e) for e in events]))


def read_events(path: Path) -> List[Event]:
    return [Event(*e) for e in json.loads(Path(path).read_text())]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # per chip, averaged over chips
    chips: int
    op_seconds: Dict[str, float]        # per chip, averaged over chips
    op_counts: Dict[str, float]         # per chip, averaged over chips
    span_seconds: Dict[str, float]
    span_counts: Dict[str, int]
    gaps: List[Tuple[str, float]]       # the longest idle gaps, as
                                        # (host span open, seconds)

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def summarize(events: List[Event]) -> TraceSummary:
    wins = [e for e in events if e.kind == "host" and e.name == WINDOW_SPAN]
    if not wins:
        raise ValueError("the trace has no 'window' span")
    w0, w1 = wins[0].start, wins[0].end

    def clip(e):
        return max(e.start, w0), min(e.end, w1)

    planes = sorted({e.plane for e in events if e.kind == "device"})
    if not planes:
        raise ValueError("the trace has no device operations")
    busy = 0.0
    op_s: Dict[str, float] = {}
    op_n: Dict[str, float] = {}
    all_iv: List[Tuple[float, float]] = []
    for p in planes:
        iv = []
        for e in events:
            if e.kind != "device" or e.plane != p:
                continue
            a, b = clip(e)
            if b <= a:
                continue
            iv.append((a, b))
            op_s[e.name] = op_s.get(e.name, 0.0) + (b - a) / 1e9
            op_n[e.name] = op_n.get(e.name, 0.0) + 1
        u = _union(iv)
        busy += sum(b - a for a, b in u) / 1e9
        all_iv.extend(u)
    k = len(planes)
    op_s = {n: s / k for n, s in op_s.items()}
    op_n = {n: c / k for n, c in op_n.items()}

    spans = [e for e in events if e.kind == "host" and e.name in HOST_SPANS]
    span_s: Dict[str, float] = {}
    span_n: Dict[str, int] = {}
    for e in spans:
        a, b = clip(e)
        if b <= a:
            continue
        span_s[e.name] = span_s.get(e.name, 0.0) + (b - a) / 1e9
        span_n[e.name] = span_n.get(e.name, 0) + 1

    holes: List[Tuple[float, float]] = []
    t = w0
    for a, b in _union(all_iv) + [(w1, w1)]:
        if a > t:
            holes.append((t, a))
        t = max(t, b)
    holes.sort(key=lambda h: h[0] - h[1])
    gaps: List[Tuple[str, float]] = []
    for a, b in holes[:TOP]:
        mid = (a + b) / 2
        open_ = [e for e in spans if e.start <= mid < e.end]
        name = (min(open_, key=lambda e: e.end - e.start).name
                if open_ else "no span")
        gaps.append((name, (b - a) / 1e9))
    return TraceSummary(window_s=(w1 - w0) / 1e9, busy_s=busy / k, chips=k,
                        op_seconds=op_s, op_counts=op_n, span_seconds=span_s,
                        span_counts=span_n, gaps=gaps)
