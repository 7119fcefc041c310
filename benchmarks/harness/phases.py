"""The program's own host phases, read from its ``Tracer`` and laid over the
run's device trace, so that the device's idle time gets the name of what the
host was doing.

What a reader of ``ctx`` needs to know:

* **The slices.** The program writes a slice (``ph: X``) for every phase of
  ``engine.step`` (``schedule``, ``prefill`` > ``prefill.chunk``, ``dispatch``
  > ``dispatch.key`` / ``.stage`` / ``.launch``, ``readback`` > ``.wait`` /
  ``.resolve``, all inside ``step``) and of the Trainer's loop (``epoch`` >
  ``loader.index``, ``loader.stack``, ``step`` > ``put_batch`` +
  ``step.dispatch``, ``epoch.loss_fetch``); ``obs/tracer.py`` says which
  module writes which. In serving they are ``ctx["engine_events"]``, which the
  driver empties when the window opens: the window's steps. In training they
  are ``process_tracer().events``: the traced epoch is the last thing the
  Trainer ran, so the newest ``epoch`` slice is the traced epoch's and the
  ``len(ctx["epoch_rates"])`` before it are the window's (a window of 33
  epochs is 1,500 events of the ring's 65,536). ``host_ms_per_step`` reads
  the window's, as the harness's own spans do, and needs no trace.
* **The clock.** Every slice carries ``perf_counter_ns``; the trace counts
  nanoseconds from the profiler's start. In serving the harness's
  ``engine.step`` annotation is in the trace and its ``perf_counter`` twin in
  ``ctx["spans"].rows``: the offset is exact. A training trace holds the
  device alone, so there the offset comes from the trace's own
  ``profile_start_time`` (Unix nanoseconds, on the ``Task Environment``
  plane) and one reading of ``time.time_ns()`` beside
  ``time.perf_counter_ns()``. Every traced serving run works the offset out
  both ways and prints the difference.
* **The idle time.** The run's trace (the newest ``*.xplane.pb`` under
  ``.bench_scratch/<cell>/trace/``: the context holds its summary, not its
  path) is read once more, the first device's idle gaps inside the traced
  window are cut out, and each gap is SPLIT among the innermost slices that
  lie over it. ``reduce_trace`` names a whole gap by the span at its middle,
  which is right for the harness's few long spans and wrong here: one gap
  between two decode programs lies over ``readback.resolve``, ``schedule``,
  32 ``dispatch.key`` and ``dispatch.stage``. ``idle_ms_per_step`` reads the
  split; time under no slice is ``_no_host_span_``, time under ``step`` or
  ``epoch`` but under none of their children goes by their names.

One pass serves all readers: the result is kept in ``ctx["phases"]``. Every
function returns ``None`` where there is nothing to read: a program without
these slices (the parent of the PR that added them), a run on the CPU (no
device plane).
"""

from __future__ import annotations

import glob
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from harness import stats
from harness.trace import WINDOW_SPAN, _op_lines, _union, load_xplane, whole_steps

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
START_STAT = "profile_start_time"

Slice = Tuple[str, int, int, dict]  # name, perf_counter start ns, dur ns, args


def say(text: str) -> None:
    print(f"[bench] phases: {text}", flush=True)


# ------------------------------------------------------------- the slices


def slices_of(events) -> List[Slice]:
    """The slices among a tracer's events, on ``perf_counter``."""
    out = []
    for e in events:
        args = e.get("args") or {}
        if e.get("ph") == "X" and "perf_counter_ns" in args:
            out.append((e["name"], int(args["perf_counter_ns"]),
                        int(e["dur"] * 1e3), args))
    return out


def all_slices(ctx) -> List[Slice]:
    """Every slice the run's tracer holds: the engine's in serving, the
    process's in training; none from a program without them."""
    if "engine_events" in ctx:
        return slices_of(ctx["engine_events"])
    try:
        from distributed_pytorch_tpu.obs.tracer import process_tracer
    except ImportError:  # a program from before the process's tracer
        return []
    return slices_of(process_tracer().events)


def window_slices(ctx) -> List[Slice]:
    """The slices of the steps the host-side metrics are read over: the
    window's, which in training are the epochs before the traced one (as many
    as the window counted: the same epochs the harness's spans timed), and
    where there are none the traced epoch."""
    rows = all_slices(ctx)
    if "engine_events" in ctx:
        return rows
    epochs = [r for r in rows if r[0] == "epoch"]
    counted = len(ctx.get("epoch_rates") or ())
    chosen = epochs[-1 - counted:-1] or epochs[-1:]
    return [r for r in rows if any(
        e0 <= r[1] and r[1] + r[2] <= e0 + dur for _, e0, dur, _ in chosen)]


def host_ms_per_step(ctx, names: Sequence[str]) -> Optional[float]:
    """Summed time of the slices called ``names`` over the number of ``step``
    slices: mean host milliseconds a step."""
    if "host_ms" not in ctx:
        ctx["host_ms"] = _host_ms(window_slices(ctx))
    mine = [ctx["host_ms"][n] for n in names if n in ctx["host_ms"]]
    return sum(mine) if mine else None


def _host_ms(rows: List[Slice]) -> Dict[str, float]:
    steps = sum(r[0] == "step" for r in rows)
    if not steps:
        return {}
    total: Dict[str, int] = {}
    for name, _, dur, _ in rows:
        total[name] = total.get(name, 0) + dur
    out = {n: v / steps / 1e6 for n, v in total.items()}
    say(f"host ms a step over {steps} steps: " + ", ".join(
        f"{n} {v:.3f}" for n, v in sorted(out.items(), key=lambda kv: -kv[1])))
    keys: Dict[int, List[Tuple[int, int]]] = {}
    for name, start, dur, args in rows:
        if name == "dispatch.key":
            keys.setdefault(args["step"], []).append((start, dur))
    if keys:
        firsts = [min(v)[1] / 1e6 for v in keys.values()]
        others = [d / 1e6 for v in keys.values() for _, d in sorted(v)[1:]]
        say(f"dispatch.key: the first of a step takes {stats.median(firsts):.3f} "
            f"ms (median; p90 {stats.percentile(firsts, 90):.3f}), every other "
            f"{stats.median(others or [0.0]):.3f} ms (median)")
    return out


def admit_wait_ms_p50(ctx) -> Optional[float]:
    """Median time from a request's ``b`` to its first ``admit``, over the
    requests that were submitted and admitted inside the window."""
    born: Dict[int, float] = {}
    waits: Dict[int, float] = {}
    for e in ctx.get("engine_events") or ():
        if e.get("cat") != "request":
            continue
        if e["ph"] == "b":
            born[e["id"]] = e["ts"]
        elif e["name"] == "admit" and e["id"] in born:
            waits.setdefault(e["id"], (e["ts"] - born[e["id"]]) / 1e3)
    if not waits:
        return None
    return stats.median(list(waits.values()))


# -------------------------------------------------------------- the clock


def newest_trace() -> Optional[str]:
    found = glob.glob(os.path.join(
        ROOT, ".bench_scratch", "*", "trace", "**", "*.xplane.pb"),
        recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def offset_from_annotations(xplane: dict, ctx) -> Optional[int]:
    """Trace nanoseconds less ``perf_counter`` nanoseconds, from the
    harness's ``engine.step`` annotations and their twins in
    ``ctx["spans"].rows`` (the ``perf_counter`` is read just before the
    annotation opens: exact to a microsecond)."""
    t0, t1 = ctx["traced"]
    twins = sorted(
        a for n, a, b in ctx["spans"].rows
        if n == "engine.step" and a >= t0 and b <= t1)
    in_trace = sorted(
        start for p in xplane["planes"] if not p["name"].startswith("/device:")
        for l in p["lines"] for n, start, _ in l["events"] if n == "engine.step")
    if not twins or len(twins) != len(in_trace):
        return None
    return int(stats.median(
        [s - a * 1e9 for s, a in zip(in_trace, twins)]))


def offset_from_start_time(path: str) -> Optional[int]:
    """The same offset from the trace's own start on the wall clock and one
    pair of readings of the wall clock and ``perf_counter`` (the tightest of
    five). The two clocks drift apart by parts in a million, and the pair is
    read within minutes of the trace."""
    from jax.profiler import ProfileData

    started = None
    for plane in ProfileData.from_file(path).planes:
        for key, value in plane.stats:
            if key == START_STAT:
                started = int(value)
    if started is None:
        return None
    pairs = []
    for _ in range(5):
        p0 = time.perf_counter_ns()
        wall = time.time_ns()
        p1 = time.perf_counter_ns()
        pairs.append((p1 - p0, wall - (p0 + p1) // 2))
    wall_less_perf = min(pairs)[1]
    return wall_less_perf - started


# ---------------------------------------------------------- the idle time


def innermost(spans: Sequence[Tuple[str, int, int]]) -> List[Tuple[int, int, str]]:
    """``spans`` (name, start, end; nested by containment, as one thread's
    context managers are) flattened to disjoint, sorted stretches ``(start,
    end, name)`` that carry the innermost span's name."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[str, int, int]] = []  # the open spans, outermost first
    t = 0
    ordered = sorted(spans, key=lambda s: (s[1], s[1] - s[2]))
    for span in ordered + [("", max((s[2] for s in spans), default=0), 0)]:
        start = span[1]
        while stack and stack[-1][2] <= start:
            name, _, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end
        if stack and start > t:
            out.append((t, start, stack[-1][0]))
        t = max(t, start)
        stack.append(span)
    return out


def split_idle(xplane: dict, spans: Sequence[Tuple[str, int, int]],
               window: Tuple[int, int]) -> Optional[Dict[str, int]]:
    """Idle nanoseconds of the first device inside ``window``, by the
    innermost of ``spans`` (on the trace's clock) over each stretch of
    them; ``_no_host_span_`` where there is none."""
    devices = sorted(
        (p for p in xplane["planes"] if p["name"].startswith("/device:")
         and any(l["events"] for l in _op_lines(p))),
        key=lambda p: p["name"])
    if not devices:
        return None
    w0, w1 = window
    busy = _union([
        (max(s, w0), min(s + d, w1))
        for l in _op_lines(devices[0]) for _, s, d in l["events"]
        if s < w1 and s + d > w0])
    gaps, edge = [], w0
    for s, e in busy:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if w1 > edge:
        gaps.append((edge, w1))

    out: Dict[str, int] = {}

    def add(name: str, ns: int) -> None:
        out[name] = out.get(name, 0) + ns

    stretches = innermost(spans)
    first = 0  # stretches before this one end before the gaps still to come
    for g0, g1 in gaps:
        while first < len(stretches) and stretches[first][1] <= g0:
            first += 1
        t = g0
        for a, b, name in stretches[first:]:
            if a >= g1:
                break
            a, b = max(a, g0), min(b, g1)
            if a > t:
                add("_no_host_span_", a - t)
            add(name, b - a)
            t = b
        if g1 > t:
            add("_no_host_span_", g1 - t)
    return out


def reduced(ctx) -> Optional[dict]:
    """``{"idle_ns": {span: ns}, "steps": n}`` of the traced window, made
    once a run and kept in ``ctx["phases"]``; ``None`` with no device plane,
    no slices or no way onto the trace's clock."""
    if "phases" not in ctx:
        ctx["phases"] = _reduce(ctx)
    return ctx["phases"]


def _reduce(ctx) -> Optional[dict]:
    path = newest_trace()
    serving = "engine_events" in ctx
    rows = all_slices(ctx)
    if path is None or not rows or ctx.get("device_kind") == "cpu":
        return None
    t_read = time.perf_counter()
    xplane = load_xplane(path)
    by_start = offset_from_start_time(path)
    if serving:
        offset = offset_from_annotations(xplane, ctx)
        if offset is not None and by_start is not None:
            say(f"clock check: the trace's start time puts perf_counter "
                f"{(by_start - offset) / 1e3:+.1f} us from where the "
                f"engine.step annotations put it")
        window = next(
            ((s, s + d) for p in xplane["planes"]
             if not p["name"].startswith("/device:")
             for l in p["lines"] for n, s, d in l["events"]
             if n == WINDOW_SPAN), None)
        steps = None
    else:
        offset = by_start
        window, steps = whole_steps(xplane)
    if offset is None or window is None:
        return None
    spans = [(n, p + offset, p + offset + d) for n, p, d, _ in rows]
    idle = split_idle(xplane, spans, window)
    if idle is None:
        return None
    if steps is None:
        steps = sum(n == "step" and window[0] <= a and b <= window[1]
                    for n, a, b in spans)
    if not steps:
        return None
    total = sum(idle.values())
    parts = ", ".join(
        f"{n} {v / steps / 1e6:.3f}" for n, v in
        sorted(idle.items(), key=lambda kv: -kv[1]))
    say(f"device idle {total / steps / 1e6:.3f} ms a step over {steps} traced "
        f"steps, by innermost span (ms a step): {parts}; second pass over "
        f"the trace {time.perf_counter() - t_read:.1f}s")
    return {"idle_ns": idle, "steps": steps}


def idle_ms_per_step(ctx, names: Sequence[str]) -> Optional[float]:
    """Idle milliseconds a traced step whose innermost slice is one of
    ``names``."""
    got = reduced(ctx)
    if got is None:
        return None
    return sum(got["idle_ns"].get(n, 0) for n in names) / got["steps"] / 1e6
