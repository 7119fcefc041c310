"""Set-up, split: where the time from the process's start to the opening of
the window goes, read from the set-up slices the program writes into its
process tracer (``obs/tracer.py`` lists them and who writes each).

What a reader of ``ctx`` needs to know:

* **The stretch.** It starts where the ``process.start`` slice starts: the
  process's own start as the OS has it, a few tens of milliseconds before
  ``run.py`` reads the clock that ``setup_s`` counts from. It ends where the
  window opens: the start of the harness's first recorded span
  (``ctx["spans"].rows``: both drivers record from ``t_open`` on).
* **The times are disjoint**, so that they add up to the stretch. Every
  instant of it belongs to the first of these that lies over it: a
  ``backend.open`` slice; the backend part of a ``compile`` slice (its last
  ``backend_s``: XLA compiling, or the persistent cache loading); the rest of
  a ``compile`` slice (JAX tracing and lowering, and its own Python between
  the parts: host work no cache saves); ``engine.init`` (the last before
  the window); what follows it (the warm-up requests' own run);
  ``trainer.init`` and that Trainer's first ``epoch``; and nothing: the interpreter, the imports, the
  benchmark's own weights and images. A ``compile`` slice that ends after
  the window has opened belongs to the window and is left out.
* **The counts** are of the ``compile`` slices of the stretch: how many, and
  how many asked the persistent cache and were not served.

One pass serves all readers: the result is kept in ``ctx["setup"]``. Every
function returns ``None`` where there is nothing to read: a program that
writes no set-up slices (the parent of the PR that added them).
"""

from __future__ import annotations

import sys
from typing import Dict, List, Optional, Sequence, Tuple

from harness.phases import Slice, slices_of
from harness.trace import _union

Interval = Tuple[int, int]

#: The times, in the order in which they claim the stretch.
TIMES = ("backend_open_s", "backend_compile_s", "trace_lower_s",
         "engine_init_s", "warm_run_s", "first_epoch_run_s", "unattributed_s")


def say(text: str) -> None:
    print(f"[bench] setup: {text}", flush=True)


def _less(intervals: Sequence[Interval], claimed: Sequence[Interval]) -> List[Interval]:
    """The union of ``intervals`` less ``claimed`` (a sorted union)."""
    out: List[Interval] = []
    for start, end in _union(intervals):
        for c0, c1 in claimed:
            if c1 <= start:
                continue
            if c0 >= end:
                break
            if c0 > start:
                out.append((start, c0))
            start = max(start, c1)
        if start < end:
            out.append((start, end))
    return out


def split(kept: Sequence[Slice], ring: Sequence[Slice],
          opened_ns: int) -> Optional[Dict[str, Optional[float]]]:
    """The times (seconds) and counts of the stretch that ends at
    ``opened_ns``, from the set-up slices ``kept`` and the ring's slices
    (for the Trainer's first ``epoch``); a time is ``None`` where the slice
    it reads was never written, the whole where the process's start was
    not."""
    started = next((s for n, s, _, _ in kept if n == "process.start"), None)
    if started is None or opened_ns <= started:
        return None

    def named(name: str, rows: Sequence[Slice] = kept) -> List[Interval]:
        return [(max(s, started), min(s + d, opened_ns))
                for n, s, d, _ in rows if n == name and s < opened_ns]

    compiles = [(s, s + d, a) for n, s, d, a in kept
                if n == "compile" and started <= s and s + d <= opened_ns]
    backend = [(max(s, e - int(a["backend_s"] * 1e9)), e) for s, e, a in compiles]
    # Whose first step the window holds: the last engine or Trainer made
    # before it (a process makes one; the tests' process, one a cell).
    engine = sorted(named("engine.init"))[-1:]
    trainer = sorted(named("trainer.init"))[-1:]
    if engine and trainer:
        engine, trainer = (
            (engine, []) if engine > trainer else ([], trainer))
    first_epoch = []
    if trainer:
        first_epoch = sorted(
            i for i in named("epoch", ring) if i[0] >= trainer[0][1])[:1]
    claims = {
        "backend_open_s": named("backend.open") or None,
        "backend_compile_s": backend,
        "trace_lower_s": [(s, b0) for (s, _, _), (b0, _) in zip(compiles, backend)],
        "engine_init_s": engine or None,
        "warm_run_s": [(engine[0][1], opened_ns)] if engine else None,
        "first_epoch_run_s": trainer + first_epoch if trainer else None,
        "unattributed_s": [(started, opened_ns)],
    }
    out: Dict[str, Optional[float]] = {}
    claimed: List[Interval] = []
    for name in TIMES:
        if claims[name] is None:
            out[name] = None
            continue
        mine = _less(claims[name], claimed)
        out[name] = sum(e - s for s, e in mine) / 1e9
        claimed = _union(claimed + mine)
    out["programs_compiled"] = len(compiles)
    out["cache_misses"] = sum(a["cache"] == "miss" for _, _, a in compiles)
    out["written"] = sum(bool(a.get("written")) for _, _, a in compiles)
    out["stretch_s"] = (opened_ns - started) / 1e9
    return out


def reduced(ctx) -> Optional[dict]:
    """:func:`split` of this run, made once and kept in ``ctx["setup"]``."""
    if "setup" not in ctx:
        ctx["setup"] = _reduce(ctx)
    return ctx["setup"]


def _reduce(ctx) -> Optional[dict]:
    try:
        from distributed_pytorch_tpu.obs.tracer import process_tracer
    except ImportError:  # a program from before the process's tracer
        return None
    tracer = process_tracer()
    kept = getattr(tracer, "setup_events", None)
    rows = ctx["spans"].rows
    if not kept or not rows:
        return None
    opened_s = min(start for _, start, _ in rows)
    got = split(slices_of(kept), slices_of(tracer.events), int(opened_s * 1e9))
    if got is None:
        return None
    parts = ", ".join(
        f"{name[:-2]} {got[name]:.3f}" for name in TIMES if got[name] is not None)
    # run.py's own clock, where run.py is the process: what setup_s counts from
    t_start = getattr(sys.modules.get("__main__"), "T_START", None)
    by_harness = ("" if t_start is None else
                  f" (setup_s by the harness's clock {opened_s - t_start:.3f})")
    say(f"{got['stretch_s']:.3f} s from the process's start to the window's "
        f"opening{by_harness}, in seconds: {parts}; {got['programs_compiled']} "
        f"programs compiled, {got['cache_misses']} of them missed the "
        f"persistent cache, {got['written']} written to it")
    slow = sorted(
        (s for s in slices_of(kept)
         if s[0] == "compile" and s[1] + s[2] <= opened_s * 1e9),
        key=lambda s: -s[2])[:5]
    say("its longest compiles: " + "; ".join(
        f"{a['fun_name']} {d / 1e9:.2f} s (trace {a['trace_s']:.2f}, lower "
        f"{a['lower_s']:.2f}, backend {a['backend_s']:.2f}, {a['cache']})"
        for _, _, d, a in slow))
    return got


def read(ctx, name: str) -> Optional[float]:
    """One of ``TIMES``, ``programs_compiled`` or ``cache_misses`` of this
    run."""
    got = reduced(ctx)
    return None if got is None else got[name]
