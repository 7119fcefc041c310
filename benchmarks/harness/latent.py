"""What the readers of a ``serve_latent_moe`` cell share: the device time of
the latent attention's operations in the traced window, and the engine's
counters of the traced steps' decode dispatches.

**How the operations are recognised.** As ``harness/hybrid.py`` says, a v5e
device trace names an operation by its whole HLO instruction and carries no
scope. By name:

* ``decode``: the latent decode kernel, a custom call the program names
  (``%attention._latent_decode_step...``: ``ops/paged_attention.py``);
* ``rest``, the prefill-side operations: a ``%while`` (the walk over a row's
  pages a block at a time, ``models/mla.py``'s ``_attend_blocks``: such a
  model's programs hold no other loop, the experts' grouped products being
  kernels; everything the loop runs lies inside its interval), and outside
  the loops an operation with a result whose last size is the pool's width
  (``r + dr`` rounded up to whole lanes: the write of the new tokens'
  ``[c | k_pe]`` into the pool, the pool's page copy of copy-on-write).

Their time is the union of their intervals inside the traced window. NOT
counted: the projections (``W_q``, ``W_kva``, the absorbed products ``q~`` and
``W_uv``, ``W_o``), which XLA fuses with their neighbours and which no result
shape tells from any other ``[tokens, d_model]`` product.
``tests/test_latent_readers.py`` pins all this on a recorded trace.

Every function returns ``None`` (or empty lists) where there is nothing to
read: a program without such operations, a run on the CPU, a program whose
tracer lacks the counters.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from harness.hybrid import clipped_union, traced_steps
from harness.moe_hybrid import newest_trace, result_shapes

KERNEL = "attention._latent_decode_step"
LANES = 128


def pool_width(cfg: dict) -> int:
    width = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    return -(-width // LANES) * LANES


def kind_of(text: str, width: int) -> Optional[str]:
    """``"decode"``, ``"rest"`` or ``None`` for the HLO instruction ``text``
    (module docstring)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if name.startswith(KERNEL):
        return "decode"
    if name.startswith("while"):
        return "rest"
    if any(dims and dims[-1] == width for dims in result_shapes(text)):
        return "rest"
    return None


def classify(events, cfg: dict) -> Dict[str, List[Tuple[int, int]]]:
    """``events`` ([whole name, start_ns, duration_ns]) by kind: the (start,
    duration) of the decode kernel's calls and of the other operations of
    the latent attention. A verdict is worked out once a distinct name."""
    width = pool_width(cfg)
    verdict: Dict[str, Optional[str]] = {}
    out: Dict[str, List[Tuple[int, int]]] = {"decode": [], "rest": []}
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = kind_of(name, width)
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
    (``ctx["mla_ops"]``)."""
    ops = ctx.get("mla_ops") or {}
    window = ops.get("window")
    spans = [s for kind in kinds for s in ops.get(kind, ())]
    if not window or not spans:
        return None
    merged = clipped_union(spans, window)
    return sum(e - s for s, e in merged) / 1e9 if merged else None


def traced_decode_counters(ctx) -> Optional[dict]:
    """The engine's ``decode_kv_tokens_visible`` and ``_distinct`` summed over
    the ``step`` slices that started inside the traced window, with
    ``steps``, how many of them carried both. ``None`` where the program
    writes no ``decode_kv_tokens_distinct``."""
    if "traced" not in ctx:
        return None
    t0, t1 = ctx["traced"]
    total = {"visible": 0, "distinct": 0, "steps": 0}
    for e in ctx.get("engine_events") or ():
        args = e.get("args") or {}
        if (e["name"] != "step" or e.get("ph") != "X"
                or "decode_kv_tokens_distinct" not in args
                or not t0 * 1e9 <= args["perf_counter_ns"] < t1 * 1e9):
            continue
        total["visible"] += args["decode_kv_tokens_visible"]
        total["distinct"] += args["decode_kv_tokens_distinct"]
        total["steps"] += 1
    return total if total["steps"] else None


__all__ = [
    "classify", "device_seconds", "kind_of", "pool_width", "read_ops",
    "traced_decode_counters", "traced_steps",
]
