"""What the readers of a ``serve_window_moe`` cell share: the device time of
the window layers' attention in the traced window, and the engine's counters
of the window group.

**How the operations are recognised.** As ``harness/hybrid.py`` says, a v5e
device trace names an operation by its whole HLO instruction and carries no
scope. By name and shape:

* ``decode``: the windowed K/V decode kernel, a custom call the program names
  (``%attention._window_paged_decode_step...``: ``ops/paged_attention.py``
  ``KV_WINDOW_KERNEL``; the full layers' calls are
  ``attention._paged_decode_step`` and are not counted);
* ``rest``, the prefill pieces' windowed reads: an operation with a result
  one of whose sizes is the tokens of a piece's short table (``piece_pages x
  page``: 41 x 16 = 656 at the published sizes): the gather of a window
  layer's K and V pages into ``[1, 656, G, dh]``, and the scores, the softmax
  and the weights ``[.., 656]`` over them. No other operation of such a
  program has that size (a prefill piece's widths are multiples of 64 up to
  512, the full layers' gathered views are ``max_seq_len`` long).

Their time is the union of their intervals inside the traced window. NOT
counted: the projections, the heads' norms and the rotation, the scatter of
the new K and V into the pools (results shaped like the pools or like
``[tokens, heads, dh]``, which no shape tells from a full layer's), and the
weighted sum where XLA fuses it into the out projection.
``tests/test_window_readers.py`` pins all this on a recorded trace.

Every function returns ``None`` (or empty lists) where there is nothing to
read: a program without such operations, a run on the CPU, a program whose
tracer lacks the counters.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from harness.hybrid import clipped_union, traced_steps
from harness.moe_hybrid import newest_trace, result_shapes

KERNEL = "attention._window_paged_decode_step"


def piece_tokens(cfg: dict) -> int:
    """Key positions of a prefill piece's short table: ``window_group_pages``
    of the engine's chunk (``ops/paged_attention.py``), times the page."""
    engine = cfg["assumed"]["engine"]
    page = engine["page_size"]
    span = cfg["sliding_window"] + engine["max_prefill_chunk"] - 2
    return (1 + -(-span // page)) * page


def window_layers(cfg: dict) -> int:
    return sum(1 for w in cfg["sliding_windows"] if w)


def kind_of(text: str, keys: int) -> Optional[str]:
    """``"decode"``, ``"rest"`` or ``None`` for the HLO instruction ``text``
    (module docstring)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if name.startswith(KERNEL):
        return "decode"
    if any(keys in dims for dims in result_shapes(text)):
        return "rest"
    return None


def classify(events, cfg: dict) -> Dict[str, List[Tuple[int, int]]]:
    """``events`` ([whole name, start_ns, duration_ns]) by kind. A verdict is
    worked out once a distinct name."""
    keys = piece_tokens(cfg)
    verdict: Dict[str, Optional[str]] = {}
    out: Dict[str, List[Tuple[int, int]]] = {"decode": [], "rest": []}
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = kind_of(name, keys)
        if verdict[name]:
            out[verdict[name]].append((start, dur))
    return out


def read_ops(directory: str, cfg: dict) -> dict:
    """The newest trace under ``directory``: the first device's ``XLA Ops``
    line, classified, and the annotated window. Empty where there is no
    trace or no device plane."""
    from harness.trace import WINDOW_SPAN

    t0 = time.perf_counter()
    out = {"decode": [], "rest": [], "events": 0, "window": None}
    path = newest_trace(directory)
    if path is not None:
        from jax.profiler import ProfileData

        planes = list(ProfileData.from_file(path).planes)
        for plane in planes:
            if plane.name.startswith("/device:") or out["window"]:
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        out["window"] = (
                            int(ev.start_ns),
                            int(ev.start_ns) + int(ev.duration_ns))
                        break
        for plane in sorted(
                (p for p in planes if p.name.startswith("/device:")),
                key=lambda p: p.name):
            line = next(
                (l for l in plane.lines if l.name.lower() == "xla ops"), None)
            if line is None:
                continue
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events]
            out.update(classify(events, cfg), events=len(events))
            break
    out["read_s"] = time.perf_counter() - t0
    return out


def device_seconds(ctx, *kinds: str) -> Optional[float]:
    """Device seconds of the operations of ``kinds`` (``"decode"``,
    ``"rest"``) inside the traced window, from what the driver kept
    (``ctx["swa_kv_ops"]``)."""
    ops = ctx.get("swa_kv_ops") or {}
    window = ops.get("window")
    spans = [s for kind in kinds for s in ops.get(kind, ())]
    if not window or not spans:
        return None
    merged = clipped_union(spans, window)
    return sum(e - s for s, e in merged) / 1e9 if merged else None


def step_slices(ctx, traced_only: bool = False) -> List[dict]:
    """The ``args`` of the engine's ``step`` slices that carry the window
    group's counters (``window_pages_held``): all the window's, or those that
    started inside the traced stretch."""
    t0, t1 = (ctx.get("traced") or (0.0, float("inf"))) if traced_only else (
        0.0, float("inf"))
    out = []
    for e in ctx.get("engine_events") or ():
        args = e.get("args") or {}
        if (e["name"] == "step" and e.get("ph") == "X"
                and "window_pages_held" in args
                and t0 * 1e9 <= args["perf_counter_ns"] < t1 * 1e9):
            out.append(args)
    return out


def traced_window_tokens(ctx) -> Optional[int]:
    """``decode_window_tokens_visible`` summed over the ``step`` slices that
    started inside the traced window; ``None`` where the program writes
    none."""
    if "traced" not in ctx:
        return None
    steps = [a for a in step_slices(ctx, traced_only=True)
             if "decode_window_tokens_visible" in a]
    if not steps:
        return None
    return sum(a["decode_window_tokens_visible"] for a in steps)


__all__ = [
    "KERNEL", "classify", "device_seconds", "kind_of", "piece_tokens",
    "read_ops", "step_slices", "traced_steps", "traced_window_tokens",
    "window_layers",
]
