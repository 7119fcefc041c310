"""What the readers of a ``serve_cca_moe`` cell share: the device time, in the
traced window, of the CCA sublayers' operations, of the K/V decode kernel's
calls and of the top-1 expert sublayers' operations, and the engine's counters
of the traced steps.

**How the operations are recognised.** As ``harness/hybrid.py`` says, a v5e
device trace names an operation by its whole HLO instruction and carries no
scope, so an operation is told by its name where the program or the compiler
names it and by its RESULT types otherwise, with the sizes of the
configuration (``H`` query heads on ``G`` KV heads of ``D``, ``C = (H + G) D``
channels, router width ``R``, ``E`` experts of width ``f``):

* ``kernel``: the K/V decode kernel, a custom call the program names
  (``%attention._paged_decode_step...``);
* ``moe``, the expert sublayers': a name that holds ``ragged-dot`` (the
  grouped products); a float32 result ``[tokens, R]`` (the router network and
  its carry), ``[tokens, E]`` or ``[tokens, 1]`` of rank 2 (scores,
  probabilities, the chosen expert and its gate), ``[E]`` or ``[E + 1]`` (the
  counts an expert); a result whose last size is ``2 f`` (the gated
  activation's input); and, in a decode program, the gathered and the weighted
  rows ``[room, d]``, ``room`` the slot table's rows rounded up to a power of
  two (128 for 96: no other array of the program has that many rows);
* ``cca``, steps 2-6 of the sublayer and what feeds them: a result whose last
  size is ``C`` (``u``, ``a``, the tails, the state leaf ``conv_state``); one
  with two neighbouring sizes ``(n, m)``, ``n`` one of ``H + G``, ``H``, ``G``
  and ``m`` one of ``D``, the rotated width and its half (the second
  convolution however the compiler lays it out, the mean, the norms, the
  rotation's halves, the shifted value, the pools' writes, a prefill piece's
  walk over its blocks); ``[tokens, n]`` of rank 2 (the L2 norms' sums); a
  bf16 result whose last size is ``H D`` or ``G D`` (the projections into q,
  k and v where the compiler leaves them a result of their own, and the
  prefetch of their weights); or the float32 state leaf ``[rows, G D / 2]``.

Their time is the union of their intervals inside the traced window. NOT
counted anywhere: ``W_o``, the block's norms and residual merges, the head;
and under ``moe`` NOT the gathered and weighted rows of a prefill piece, whose
``[width, d]`` no shape tells from the residual stream's (``f`` is ``d`` at the
published sizes). ``tests/test_cca_readers.py`` pins all this on a recorded
trace.

Every function returns ``None`` (or empty lists) where there is nothing to
read: a program without such operations, a run on the CPU, a program whose
tracer lacks the counters.
"""

from __future__ import annotations

import re
import time
from typing import Dict, List, Optional, Tuple

from harness.hybrid import clipped_union, result_types, traced_steps
from harness.moe_hybrid import newest_trace, traced_routing

KERNEL = "attention._paged_decode_step"
_TYPED = re.compile(r"(\w+)\[([\d,]*)\]")


def sizes(cfg: dict) -> dict:
    h, g, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    engine = cfg["assumed"]["engine"]
    slots = engine["max_slots"]
    tokens = {slots} | set(range(64, engine["max_prefill_chunk"] + 1, 64))
    rotary = int(round(
        d * cfg["rope_parameters"]["hybrid"]["partial_rotary_factor"]))
    return dict(
        chans=(h + g) * d, q=h * d, kv=g * d, half=g * d // 2,
        heads=frozenset(
            (n, m) for n in (h + g, h, g) for m in (d, rotary, rotary // 2)),
        head_counts=frozenset([h + g, h, g]),
        router=cfg["router_hidden_size"], experts=cfg["num_experts"],
        wide=2 * cfg["moe_intermediate_size"], d_model=cfg["hidden_size"],
        tokens=frozenset(tokens), rows=frozenset([slots, 1]),
        room=1 << (slots - 1).bit_length(), slots=slots,
    )


def typed_results(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(dtype, dims)`` of every result of an HLO instruction's text."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in _TYPED.finditer(result_types(text))]


def kind_of(text: str, s: dict) -> Optional[str]:
    """``"kernel"``, ``"moe"``, ``"cca"`` or ``None`` for the HLO instruction
    ``text`` (module docstring)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    if name.startswith(KERNEL):
        return "kernel"
    if "ragged-dot" in name:
        return "moe"
    results = typed_results(text)
    for dtype, dims in results:
        if not dims:
            continue
        if len(dims) == 1 and dims[0] in (s["experts"], s["experts"] + 1):
            return "moe"
        if len(dims) == 2 and dims[0] in s["tokens"] and (
                dims[1] in (s["experts"], 1)
                or (dims[1] == s["router"] and dtype == "f32")):
            return "moe"
        if len(dims) >= 2 and dims[-1] == s["wide"]:
            return "moe"
        if (len(dims) == 2 and dims == (s["room"], s["d_model"])
                and s["room"] != s["slots"]):
            return "moe"
    for dtype, dims in results:
        if not dims:
            continue
        if dims[-1] == s["chans"] or any(
                pair in s["heads"] for pair in zip(dims, dims[1:])):
            return "cca"
        if (len(dims) == 2 and dims[0] in s["tokens"]
                and dims[1] in s["head_counts"]):
            return "cca"
        if dtype == "bf16" and len(dims) >= 2 and dims[-1] in (s["q"], s["kv"]):
            return "cca"
        if dtype == "f32" and len(dims) == 2 and dims[0] in s["rows"] and (
                dims[1] == s["half"]):
            return "cca"
    return None


def classify(events, cfg: dict) -> Dict[str, List[Tuple[int, int]]]:
    """``events`` ([whole name, start_ns, duration_ns]) by kind. A verdict is
    worked out once a distinct name."""
    s = sizes(cfg)
    verdict: Dict[str, Optional[str]] = {}
    out: Dict[str, List[Tuple[int, int]]] = {"kernel": [], "cca": [], "moe": []}
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = kind_of(name, s)
        if verdict[name]:
            out[verdict[name]].append((start, dur))
    return out


def read_ops(directory: str, cfg: dict) -> dict:
    """The newest trace under ``directory``: the first device's ``XLA Ops``
    line, classified, and the annotated window. Empty where there is no trace
    or no device plane."""
    from harness.trace import WINDOW_SPAN

    t0 = time.perf_counter()
    out = {"kernel": [], "cca": [], "moe": [], "events": 0, "window": None}
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
    """Device seconds of the operations of ``kinds`` (``"kernel"``, ``"cca"``,
    ``"moe"``) inside the traced window, from what the driver kept
    (``ctx["cca_ops"]``)."""
    ops = ctx.get("cca_ops") or {}
    window = ops.get("window")
    spans = [s for kind in kinds for s in ops.get(kind, ())]
    if not window or not spans:
        return None
    merged = clipped_union(spans, window)
    return sum(e - s for s, e in merged) / 1e9 if merged else None


def traced_visible_tokens(ctx) -> Optional[int]:
    """``decode_kv_tokens_visible`` (``pos + 1`` a decoded row: the keys a
    layer's decode attention has to read) summed over the engine's ``step``
    slices that started inside the traced window; ``None`` where the program
    writes none."""
    if "traced" not in ctx:
        return None
    t0, t1 = ctx["traced"]
    total, found = 0, False
    for e in ctx.get("engine_events") or ():
        args = e.get("args") or {}
        if (e["name"] == "step" and e.get("ph") == "X"
                and "decode_kv_tokens_visible" in args
                and t0 * 1e9 <= args["perf_counter_ns"] < t1 * 1e9):
            total += args["decode_kv_tokens_visible"]
            found = True
    return total if found else None


__all__ = [
    "KERNEL", "classify", "device_seconds", "kind_of", "read_ops", "sizes",
    "traced_routing", "traced_steps", "traced_visible_tokens", "typed_results",
]
