"""What the readers of a ``serve_hybrid_moe`` cell share: the device time of
the Mamba-2 mixers' operations and of the expert layers' operations in the
traced window, and the routing counters of the traced steps.

**How the operations are recognised.** As ``harness/hybrid.py`` says, a v5e
device trace names an operation by its whole HLO instruction and carries no
scope. Two things are named by the compiler itself: the expert layers' grouped
products (``jax.lax.ragged_dot`` becomes a Mosaic kernel whose instruction is
``ragged-dot-...``, with a ``ragged-dot-metadata`` kernel before it) whoever
calls them. Everything else is read from the RESULT shapes of the whole
names, with the sizes of the configuration:

* the mixers' (``ssd``): a result that ends in ``[.., H, P, N]`` (the state:
  the decode step's fused update, a prefill chunk's block states and the
  scan over the block borders) or in ``[.., K-1, H P + 2 G N]`` (the conv's
  tail: the fusion that shifts it, which in the decode step is the conv
  itself), or that is the blocked evaluation's decay matrix (``[.., L, L,
  G, H/G]``, or ``[.., H, L, L]`` as XLA lays it out) or one of its products
  (``[.., L, G, H/G, P]``);
* the expert layers' (``moe``): a name that holds ``ragged-dot``; a result
  whose last size is the router's width or ``top_k`` on a ``[tokens, ..]``
  array of rank 2 (scores, chosen scores, gates, experts); a result with
  ``tokens x top_k`` rows of ``d_model``, ``d_ff`` or ``2 d_ff`` columns or of
  rank 1 (the sorted pairs: keys, order, the gathered rows, the activation,
  the weighted rows), for the token counts a program of the engine can have
  (the slot table's rows, and every power-of-two chunk); ``[held, pairs, 1]``
  (a chunk of a few tokens: the compiler writes its product as a mask a held
  expert); the counts a routed and a held expert (rank 1, the router's width
  or the held count, or one more).

Their time is the union of their intervals inside the traced window. NOT
counted: elementwise work XLA fuses into a neighbouring projection without
one of these shapes among its results (the gate and the gated norm of a
mixer, the shared expert, which is a dense MLP). ``tests/test_moe_readers.py``
pins all this on a recorded trace.

Every function returns ``None`` (or empty lists) where there is nothing to
read: a program without such operations, a run on the CPU.
"""

from __future__ import annotations

import glob
import os
import time
from typing import Dict, List, Optional, Tuple

from harness.hybrid import _SHAPE, clipped_union, result_types, traced_steps


def sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    engine = cfg["assumed"]["engine"]
    chunk, tokens = engine["max_prefill_chunk"], {engine["max_slots"]}
    while chunk >= 1:
        tokens.add(chunk)
        chunk //= 2
    top_k = cfg["num_experts_per_tok"]
    return dict(
        state=(heads, p, n), tail=(cfg["mamba_d_conv"] - 1, heads * p + 2 * g * n),
        heads=(g, heads // g), head_dim=p,
        router=cfg.get("num_local_experts_published", cfg["num_local_experts"]),
        top_k=top_k, tokens=frozenset(tokens), held=cfg["num_local_experts"],
        pairs=frozenset(t * top_k for t in tokens),
        widths=frozenset((cfg["hidden_size"], cfg["intermediate_size"],
                          2 * cfg["intermediate_size"])),
    )


def result_shapes(text: str) -> List[Tuple[int, ...]]:
    return [tuple(int(x) for x in m.group(1).split(",") if x)
            for m in _SHAPE.finditer(result_types(text))]


def is_ssd_op(text: str, s: dict) -> bool:
    """Whether the HLO instruction ``text`` is one of the Mamba-2 mixers'
    conv, state or block-evaluation operations (module docstring)."""
    g, r = s["heads"]
    for dims in result_shapes(text):
        if dims[-3:] == s["state"] or dims[-2:] == s["tail"]:
            return True
        if len(dims) >= 4 and dims[-2:] == (g, r) and dims[-3] == dims[-4]:
            return True  # the decay matrix [.., L, L, G, R]
        if len(dims) >= 4 and dims[-3:] == (g, r, s["head_dim"]):
            return True  # [.., L, G, R, P]
        if len(dims) >= 3 and dims[-1] == dims[-2] and dims[-3] == g * r:
            return True  # the decay matrix as XLA lays it out: [.., H, L, L]
    return False


def is_moe_op(text: str, s: dict) -> bool:
    """Whether the HLO instruction ``text`` is one of the expert layers'
    routing, sorting, gathering, grouped-product or combining operations
    (module docstring)."""
    if "ragged-dot" in text.split(" = ", 1)[0]:
        return True
    for dims in result_shapes(text):
        if len(dims) == 2 and dims[0] in s["tokens"] and dims[1] in (
                s["router"], s["top_k"]):
            return True
        if dims and dims[0] in s["pairs"] and (
                len(dims) == 1
                or (len(dims) == 2 and dims[1] in s["widths"])):
            return True
        if len(dims) == 3 and dims[0] == s["held"] and dims[1] in s["pairs"]:
            return True  # a few rows: the product as a mask a held expert
        if len(dims) == 1 and dims[0] in (
                s["router"], s["router"] + 1, s["held"], s["held"] + 1):
            return True  # the counts a routed and a held expert
    return False


def newest_trace(directory: str) -> Optional[str]:
    found = glob.glob(
        os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def classify(events, s: dict) -> Dict[str, List[Tuple[int, int]]]:
    """``events`` ([whole name, start_ns, duration_ns]) by kind: the
    (start, duration) of the mixers' (``ssd``) and the expert layers'
    (``moe``). A verdict is worked out once a distinct name."""
    verdict: Dict[str, Optional[str]] = {}
    out: Dict[str, List[Tuple[int, int]]] = {"ssd": [], "moe": []}
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = ("moe" if is_moe_op(name, s)
                             else "ssd" if is_ssd_op(name, s) else None)
        if verdict[name]:
            out[verdict[name]].append((start, dur))
    return out


def read_ops(directory: str, cfg: dict) -> dict:
    """The newest trace under ``directory``: the first device's ``XLA Ops``
    line, classified. Empty where there is no trace or no device plane."""
    t0 = time.perf_counter()
    out = {"ssd": [], "moe": [], "events": 0}
    path = newest_trace(directory)
    if path is not None:
        from jax.profiler import ProfileData

        planes = sorted(
            (p for p in ProfileData.from_file(path).planes
             if p.name.startswith("/device:")), key=lambda p: p.name)
        for plane in planes:
            line = next(
                (l for l in plane.lines if l.name.lower() == "xla ops"), None)
            if line is None:
                continue
            events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                      for ev in line.events]
            out.update(classify(events, sizes(cfg)), events=len(events))
            break
    out["read_s"] = time.perf_counter() - t0
    return out


def device_seconds(ctx, kind: str) -> Optional[float]:
    """Device seconds of the ``ssd`` or ``moe`` operations inside the traced
    window, from what the driver kept (``ctx["ssd_ops"]``,
    ``ctx["moe_ops"]``: the window and the operations' spans)."""
    key = f"{kind}_device_s"
    if key not in ctx:
        window, spans = ctx.get(f"{kind}_ops") or (None, ())
        merged = clipped_union(spans, window) if window and spans else []
        ctx[key] = sum(e - s for s, e in merged) / 1e9 if merged else None
    return ctx[key]


def traced_routing(ctx) -> Optional[dict]:
    """The engine's ``moe.routing`` counters summed over the steps that
    started inside the traced window (``harness/hybrid.py``'s
    ``traced_steps`` rule), with ``steps``, how many of them carried
    counters. ``None`` where the program writes none."""
    if "traced" not in ctx or not ctx.get("step_rows"):
        return None
    events = ctx.get("engine_events") or ()
    t0, t1 = ctx["traced"]
    inside = {
        e["args"]["step"] for e in events
        if e["name"] == "step" and e.get("ph") == "X"
        and t0 * 1e9 <= e["args"]["perf_counter_ns"] < t1 * 1e9}
    total: Dict[str, float] = {}
    steps = 0
    for e in events:
        if e["name"] != "moe.routing" or e["args"].get("step") not in inside:
            continue
        steps += 1
        for k, v in e["args"].items():
            if k.startswith("moe_"):
                total[k] = total.get(k, 0) + v
    return dict(total, steps=steps) if steps else None


__all__ = [
    "classify", "device_seconds", "is_moe_op", "is_ssd_op", "read_ops",
    "sizes", "traced_routing", "traced_steps",
]
