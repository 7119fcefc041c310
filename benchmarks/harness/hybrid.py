"""What the readers of a ``serve_hybrid`` cell share: the configuration's
reference module (its counts), the engine steps of the traced window, and the
device time of the Mamba mixers' conv and scan operations.

**How the mixers' operations are recognised.** The device trace names an
operation by its whole HLO instruction and carries no scope (a v5e trace has
the lines ``XLA Modules``, ``XLA Ops``, ``Async XLA Ops`` and no name-scope
line), and ``harness/trace.py``'s short names drop the shapes of everything
that returns a tuple, which the scan's loop and its fused body do. So the
whole names are read, and an operation on an ``XLA Ops`` line counts as the
mixers' when a shape among its RESULTS ends in ``[.., N, d_inner]`` (the scan
state: the ``while`` that carries it through a prefill chunk with everything
inside it, the fused one-token update of the decode step) or in ``[.., K-1,
d_inner]`` (the conv's tail: the fusion that shifts it, which in the decode
step is the conv itself). Their time is the union of their intervals inside
the traced window, so a loop and the body inside it count once. NOT counted:
elementwise work of the mixers that XLA fuses into a neighbouring projection
without either shape among its results (a prefill chunk's conv and SiLU, the
gate). Counted though not meant: where a prefill chunk is exactly ``N``
tokens long, its mixers' other elementwise results ``[1, N, d_inner]``
(microseconds each, and the mixers' own). ``tests/test_ssm_readers.py`` pins
all this on a recorded trace.

**One reading of the trace.** Such a cell's trace holds 570,000 device events
a second (a prefill chunk's scan is a loop, and every operation of every
iteration is an event). ``TraceOnce`` stands in ``harness/trace.py``'s
``load_xplane``'s place for the driver and for ``harness/phases.py``: it
reads a file once however many ask for it, and in that pass keeps what the
readers here need of the whole names.

Every function returns ``None`` (or an empty list) where there is nothing to
read: a configuration without Mamba layers, a program without such
operations, a run on the CPU.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import time
from typing import Iterable, List, Optional, Tuple

from harness.trace import WINDOW_SPAN, _union, short_name

HERE = os.path.dirname(os.path.abspath(__file__))
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def reference_for(cfg: dict):
    """The configuration's reference module, by path (as ``run.py`` loads
    it): the counts live there."""
    name = "bench_reference_" + cfg["reference"]
    if name in sys.modules:
        return sys.modules[name]
    path = os.path.join(
        os.path.dirname(HERE), "reference", cfg["reference"] + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def traced_steps(ctx) -> Optional[List[dict]]:
    """The plans of the engine steps that STARTED inside the traced window
    (``drivers/serve.py``'s rows: ``decode_rows``, ``decode_context``,
    ``prefill_tokens``, ``prefill_context``, ``prefill_keys``), each with
    ``prefill_chunks``: how many ``prefill.chunk`` slices the engine wrote
    during the step, every one a program of its own and a stretch of one
    request. By their start, not by lying wholly inside: the window is a
    few dozen steps, and leaving out the step that straddles each edge would
    under-count the work; counting the one that straddles the end for the
    one that straddles the start does not."""
    if "traced" not in ctx or not ctx.get("step_rows"):
        return None
    t0, t1 = ctx["traced"]
    chunk_starts = sorted(
        e["args"]["perf_counter_ns"] for e in ctx.get("engine_events") or ()
        if e["name"] == "prefill.chunk" and e.get("ph") == "X")
    steps = []
    for plan, (s0, s1) in zip(ctx["counters"]["plans"], ctx["step_rows"]):
        if t0 <= s0 < t1:
            chunks = sum(s0 * 1e9 <= c < s1 * 1e9 for c in chunk_starts)
            steps.append(dict(plan, prefill_chunks=chunks))
    return steps or None


def result_types(text: str) -> str:
    """The result type (or tuple of types) of an HLO instruction's text:
    what stands between ``=`` and the opcode. Layouts nest parentheses
    (``{2,1,0:T(8,128)(2,1)S(1)}``), so a tuple is closed by counting."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return ""
    if not rest.startswith("("):
        return rest.split(" ", 1)[0]
    depth = 0
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return rest[: i + 1]
    return rest


def is_ssm_op(text: str, d_inner: int, d_state: int, d_conv: int) -> bool:
    """Whether the HLO instruction ``text`` is one of the mixers' conv or
    scan operations (module docstring)."""
    tails = ((d_state, d_inner), (d_conv - 1, d_inner))
    for m in _SHAPE.finditer(result_types(text)):
        dims = tuple(int(x) for x in m.group(1).split(",") if x)
        if dims[-2:] in tails:
            return True
    return False


def mixer_sizes(cfg: dict) -> Tuple[int, int, int]:
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_d_conv"])


def clipped_union(spans: Iterable[Tuple[int, int]],
                  window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """``spans`` (start, duration) as merged intervals inside ``window``."""
    w0, w1 = window
    clipped = ((max(s, w0), min(s + d, w1)) for s, d in spans)
    return _union([(s, e) for s, e in clipped if e > s])


def ssm_intervals(events, cfg: dict, window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The merged intervals, clipped to ``window``, of the mixers' operations
    among ``events`` ([name, start_ns, duration_ns] with whole HLO names)."""
    sizes = mixer_sizes(cfg)
    verdict = {}
    mine = []
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = is_ssm_op(name, *sizes)
        if verdict[name]:
            mine.append((start, dur))
    return clipped_union(mine, window)


class TraceOnce:
    """``harness/trace.py``'s ``load_xplane`` (the same dict), reading a file
    once however often it is asked for, and keeping from that pass, for the
    readers here: ``window``, the annotated window in trace nanoseconds, and
    ``ssm``, the (start, duration) of the mixers' operations on the first
    device's ``XLA Ops`` line. Short names and verdicts are worked out once a
    distinct name, not once an event."""

    def __init__(self, cfg: dict):
        self.sizes = mixer_sizes(cfg) if "mamba_d_state" in cfg else None
        self.path = self.xplane = self.window = None
        self.ssm: List[Tuple[int, int]] = []
        self.read_s = 0.0  # what the one reading took

    def __call__(self, path: str) -> dict:
        if path != self.path:
            t0 = time.perf_counter()
            self.xplane, self.path = self._load(path), path
            self.read_s = time.perf_counter() - t0
        return self.xplane

    def _load(self, path: str) -> dict:
        from jax.profiler import ProfileData

        known = {}  # whole name -> (short name, one of the mixers')
        by_device = {}
        self.window = None
        planes = []
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith("/device:")
            lines = []
            for line in plane.lines:
                mine = ([] if device and self.sizes
                        and line.name.lower() == "xla ops" else None)
                events = []
                for ev in line.events:
                    name, start, dur = ev.name, int(ev.start_ns), int(ev.duration_ns)
                    if device:
                        if name not in known:
                            known[name] = (
                                short_name(name),
                                bool(self.sizes) and is_ssm_op(name, *self.sizes))
                        name, is_mine = known[name]
                        if is_mine and mine is not None:
                            mine.append((start, dur))
                    elif name == WINDOW_SPAN and self.window is None:
                        self.window = (start, start + dur)
                    events.append([name, start, dur])
                if mine:
                    by_device[plane.name] = mine
                lines.append({"name": line.name, "events": events})
            planes.append({"name": plane.name, "lines": lines})
        self.ssm = by_device[min(by_device)] if by_device else []
        return {"planes": planes}


def ssm_device_seconds(ctx) -> Optional[float]:
    """Device seconds of the mixers' conv and scan operations inside the
    traced window, from what the driver kept of its ``TraceOnce``
    (``ctx["ssm_ops"]``: the window and the operations' spans)."""
    if "ssm_device_s" not in ctx:
        window, spans = ctx.get("ssm_ops") or (None, ())
        merged = clipped_union(spans, window) if window else []
        ctx["ssm_device_s"] = (
            sum(e - s for s, e in merged) / 1e9 if merged else None)
    return ctx["ssm_device_s"]
