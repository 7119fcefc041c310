"""The profiler trace of a run, and its reduction to what the metrics read.

``Profiler`` opens and closes a ``jax.profiler`` trace into a directory under
the checkout. ``load_xplane`` turns the ``.xplane.pb`` it leaves into plain
lists; ``reduce_trace`` turns those into a ``TraceSummary``: per-device busy
time (the union of the intervals in which an operation ran), the operations
by self time, collective time, and every idle gap attributed to the harness
span the host was in. ``tests/test_trace_reduction.py`` checks the reduction
against a small recorded trace.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import time
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.traced_window"
SHORT_GAP_NS = 20_000
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE,
)
# Lines of a device plane that hold something other than single operations.
NOT_OP_LINES = ("step", "module", "traceme", "framework", "source", "name scope")

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

_HLO = re.compile(r"^%?([\w.\-]+) = (\(?)(\w+)\[([\d,]*)\]")


def short_name(text: str) -> str:
    """A TPU trace names an operation by its whole HLO instruction. Keep
    the instruction's name without its number, and the type and shape of
    what it produces: ``fusion_bf16_12288_16_2_128``."""
    m = _HLO.match(text)
    if not m:
        return re.sub(r"\.\d+$", "", text.split(" = ")[0].lstrip("%"))[:80]
    base = re.sub(r"\.\d+$", "", m.group(1))
    if m.group(2):
        return f"{base}_tuple"
    dims = m.group(4).replace(",", "_")
    return f"{base}_{m.group(3)}_{dims}" if dims else f"{base}_{m.group(3)}"



class Profiler:
    """One profiler trace, written under ``directory`` (emptied first).

    ``start`` starts the profiler; the caller then lets the program run a
    step, because the first device work after a start pays the profiler's own
    start-up; ``open_window`` then opens the traced window (an annotation on
    the trace's clock), and ``stop`` closes it and writes the trace, which
    takes seconds to a minute with what the host tracer recorded: callers
    stop after their measured window. With ``host_tracer_level`` 0 nothing of
    the host is recorded, the annotation included, and the caller cuts the
    window from the device's own events (``whole_steps``).
    """

    def __init__(self, directory: str, host_tracer_level: int = 1):
        self.directory = directory
        self.host_tracer_level = host_tracer_level  # 0: the device alone
        self.t0 = self.t1 = 0.0
        self._annotation = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # spans come from TraceAnnotation
        # 1: the harness's annotations and the runtime's own events.
        options.host_tracer_level = self.host_tracer_level
        jax.profiler.start_trace(self.directory, profiler_options=options)

    @property
    def window_open(self) -> bool:
        return self._annotation is not None

    def open_window(self) -> None:
        import jax

        self._annotation = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()

    def stop(self) -> str:
        import jax

        self.t1 = time.perf_counter()
        self._annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()
        found = glob.glob(
            os.path.join(self.directory, "**", "*.xplane.pb"), recursive=True)
        if not found:
            raise RuntimeError(f"the profiler left no trace in {self.directory}")
        return max(found, key=os.path.getmtime)


def load_xplane(path: str) -> dict:
    """The trace as ``{"planes": [{"name", "lines": [{"name", "events":
    [[name, start_ns, duration_ns], ...]}]}]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            shorten = plane.name.startswith("/device:")
            events = [
                [short_name(ev.name) if shorten else ev.name,
                 int(ev.start_ns), int(ev.duration_ns)]
                for ev in line.events
            ]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over devices
    devices: int
    ops: List[Tuple[str, float]]  # (name xN, self seconds) mean over devices
    op_self_s: Dict[str, float]  # name -> self seconds, summed over devices
    collective_s: float  # mean over devices
    idle_gaps: List[Tuple[str, float]]  # (host span, seconds) on device 0
    host_spans: Dict[str, List[Tuple[int, int]]]  # name -> [(start, end)] ns


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _self_times(events: Sequence[Event]) -> List[Tuple[str, int]]:
    """(name, self ns) of each event on one line: its duration less the
    children nested inside it (a ``while`` and the ops of its body)."""
    out: List[Tuple[str, int]] = []
    stack: List[List] = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, end, dur])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def _op_lines(plane: dict) -> List[dict]:
    named = [l for l in plane["lines"] if l["name"].lower() == "xla ops"]
    if named:
        return named
    return [
        l for l in plane["lines"]
        if not any(w in l["name"].lower() for w in NOT_OP_LINES)
    ]


def _clip(events: Sequence[Event], w0: int, w1: int) -> List[Event]:
    out = []
    for name, start, dur in events:
        s, e = max(start, w0), min(start + dur, w1)
        if e > s:
            out.append((name, s, e - s))
    return out


def _cpu_stand_in(xplane: dict) -> List[dict]:
    """The CPU rehearsal has no device plane: XLA's CPU worker threads stand
    in for one, so that the reduction's code runs. Not a device number."""
    events = [
        ev for p in xplane["planes"] if p["name"] == "/host:CPU"
        for l in p["lines"] if l["name"].startswith("tf_XLA")
        for ev in l["events"]
        if not ev[0].startswith(("Thread", "Thunk", "end: "))
    ]
    if not events:
        return []
    return [{"name": "/device:CPU-stand-in",
             "lines": [{"name": "XLA Ops", "events": events}]}]


def whole_steps(xplane: dict) -> Tuple[Optional[Tuple[int, int]], int]:
    """A window of whole steps for a trace that holds one program launched
    over and over and no annotation (the host tracer was off): from the second
    launch on the first device's ``XLA Modules`` line, since the first follows
    the profiler's start-up, to the last, whose own work falls outside. So
    the window holds as many busy stretches as gaps. Returns the window and
    the number of steps in it, or ``(None, 0)`` where the trace has no such
    line or fewer than three launches."""
    for plane in sorted(xplane["planes"], key=lambda p: p["name"]):
        if not plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            if line["name"].lower() != "xla modules" or not line["events"]:
                continue
            names = [ev[0] for ev in line["events"]]
            program = max(set(names), key=names.count)
            starts = sorted(ev[1] for ev in line["events"] if ev[0] == program)
            if len(starts) >= 3:
                return (starts[1], starts[-1]), len(starts) - 2
    return None, 0


def reduce_trace(xplane: dict, span_names: Sequence[str],
                 cpu_rehearsal: bool = False,
                 window: Optional[Tuple[int, int]] = None) -> TraceSummary:
    device_planes = [
        p for p in xplane["planes"]
        if p["name"].startswith("/device:") and _op_lines(p)
        and any(l["events"] for l in _op_lines(p))
    ]
    if not device_planes and cpu_rehearsal:
        device_planes = _cpu_stand_in(xplane)
    if not device_planes:
        raise RuntimeError(
            "no device plane with operations in the trace: planes "
            + ", ".join(p["name"] for p in xplane["planes"]))
    wanted = set(span_names) | {WINDOW_SPAN}
    host_spans: Dict[str, List[Tuple[int, int]]] = {}
    for plane in xplane["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name in wanted:
                    host_spans.setdefault(name, []).append((start, start + dur))
    all_ops = [
        ev for p in device_planes for l in _op_lines(p) for ev in l["events"]]
    annotated = host_spans.pop(WINDOW_SPAN, None)
    if window is not None:
        w0, w1 = window
    elif annotated:
        # The driver waits for the device before it closes the annotation,
        # so the work dispatched inside it has run inside it.
        w0, w1 = annotated[0]
    else:
        w0 = min(s for _, s, _ in all_ops)
        w1 = max(s + d for _, s, d in all_ops)

    busy_ns = 0
    collective_ns = 0
    op_self: Dict[str, int] = {}
    op_count: Dict[str, int] = {}
    gaps0: List[Tuple[int, int]] = []
    for i, plane in enumerate(device_planes):
        intervals = []
        for line in _op_lines(plane):
            events = _clip(line["events"], w0, w1)
            intervals += [(s, s + d) for _, s, d in events]
            for name, self_ns in _self_times(events):
                op_self[name] = op_self.get(name, 0) + self_ns
                op_count[name] = op_count.get(name, 0) + 1
                if COLLECTIVE.search(name):
                    collective_ns += self_ns
        merged = _union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        if i == 0:
            edge = w0
            for s, e in merged:
                if s > edge:
                    gaps0.append((edge, s))
                edge = max(edge, e)
            if w1 > edge:
                gaps0.append((edge, w1))

    n = len(device_planes)
    ops = sorted(
        ((f"{name}__x{op_count[name] // n or 1}", ns / n / 1e9)
         for name, ns in op_self.items()),
        key=lambda kv: -kv[1],
    )
    flat = sorted(
        (s, e, name) for name, rows in host_spans.items() for s, e in rows)
    by_span: Dict[str, int] = {}
    for g0, g1 in gaps0:
        if g1 - g0 < SHORT_GAP_NS:
            key = "_gaps_under_20_us_"
        else:
            mid = (g0 + g1) // 2
            # the innermost (shortest) harness span the host was in
            inside = [(e - s, name) for s, e, name in flat if s <= mid < e]
            key = min(inside)[1] if inside else "_no_host_span_"
        by_span[key] = by_span.get(key, 0) + (g1 - g0)
    return TraceSummary(
        window_s=(w1 - w0) / 1e9,
        busy_s=busy_ns / n / 1e9,
        devices=n,
        ops=ops,
        op_self_s={k: v / 1e9 for k, v in op_self.items()},
        collective_s=collective_ns / n / 1e9,
        idle_gaps=sorted(
            ((k, v / 1e9) for k, v in by_span.items()), key=lambda kv: -kv[1]),
        host_spans=host_spans,
    )


def breakdown(summary: Optional[TraceSummary]) -> Optional[dict]:
    if summary is None:
        return None
    return {
        "device_ops": [[n, s] for n, s in summary.ops[:10]],
        "idle_gaps": [[n, s] for n, s in summary.idle_gaps[:10]],
    }
