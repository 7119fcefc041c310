"""What the readers of a ``serve_sparse_latent_moe`` cell share: the device
time of the learned sparse attention's and of the windowed latent attention's
operations in the traced window, and the engine's counters of the traced
steps' decode dispatches.

**How the operations are recognised** (a v5e device trace names an operation
by its whole HLO instruction and carries no scope: ``harness/hybrid.py``). By
name, the three kernels the program names (``ops/paged_attention.py``):

* ``index``: ``%attention._index_scores...``, a decode row's index scores
  over its index-key pages;
* ``sparse``: ``%attention._sparse_latent_decode_step...``, the absorbed
  attention over the selected tokens' gathered latents;
* ``window``: ``%attention._window_latent_decode_step...``, a sliding layer's
  decode over the pages that meet its window.

By result shape, XLA's own operations between them and on the prefill side:

* ``gather``: the gather of the selected tokens' latents out of a full
  layer's pool, a result ``[slots, index_topk, pool width]`` (or, as the
  compiler flattens it, ``[slots x index_topk, pool width]``): the sparse
  kernel's read of the pool, done by XLA before it;
* ``select``: any other operation outside a loop with a result that has a
  size of the table's tokens (``max_seq_len`` in whole pages: the scores, the
  ordered bits, the counts and masks of the exact top-k; also flattened over
  the slots), of ``index_topk`` or of ``slots x index_topk`` (the selected
  positions and what finds them);
* ``full_rest``: a ``%while`` that does not carry a sliding layer's pool (the
  prefill pieces' index scores, selection and masked walk over the rows'
  pages, the decode's 32 counts of the top-k and its search of the positions),
  and outside the loops the write of a full layer's pool rows (a result whose
  last size is a full layer's pool width or the index keys');
* ``window_rest``: a ``%while`` whose carried values hold an array of the
  sliding layers' pool width (a prefill piece's walk over its window's
  blocks), and the write of a sliding layer's pool rows.

NOT counted: the projections, which XLA fuses with their neighbours.
``tests/test_dsa_readers.py`` pins this on instruction texts.

Every function returns ``None`` (or empty lists) where there is nothing to
read: a program without such operations, a run on the CPU, a program whose
tracer lacks the counters.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from harness.hybrid import clipped_union, traced_steps
from harness.moe_hybrid import newest_trace, result_shapes

KERNELS = {
    "attention._index_scores": "index",
    "attention._sparse_latent_decode_step": "sparse",
    "attention._window_latent_decode_step": "window",
}
KINDS = ("index", "select", "gather", "sparse", "full_rest", "window",
         "window_rest")
LANES = 128


def _lanes(width: int) -> int:
    return -(-width // LANES) * LANES


def sizes(cfg: dict) -> dict:
    engine = cfg["assumed"]["engine"]
    page = engine["page_size"]
    table = -(-engine["max_seq_len"] // page) * page
    slots, topk = engine["max_slots"], cfg["index_topk"]
    return {
        "table": table, "topk": topk,
        # What marks an operation of the selection: a size of its result.
        "select": {table, slots * table, topk, slots * topk},
        "full": _lanes(cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
        "index": _lanes(cfg["index_head_dim"]),
        "sliding": _lanes(cfg["swa_kv_lora_rank"] + cfg["swa_qk_rope_head_dim"]),
    }


def kind_of(text: str, s: dict) -> Optional[str]:
    """One of ``KINDS`` or ``None`` for the HLO instruction ``text`` (module
    docstring)."""
    name = text.split(" = ", 1)[0].lstrip("%")
    for kernel, kind in KERNELS.items():
        if name.startswith(kernel):
            return kind
    shapes = result_shapes(text)
    if name.startswith("while"):
        carries_sliding = any(
            dims and dims[-1] == s["sliding"] for dims in shapes)
        return "window_rest" if carries_sliding else "full_rest"
    for dims in shapes:
        if s["select"] & set(dims):
            return "gather" if dims[-1] == s["full"] else "select"
    for dims in shapes:
        if dims and dims[-1] == s["sliding"]:
            return "window_rest"
        if len(dims) >= 3 and dims[-1] in (s["full"], s["index"]) and (
                s["index"] != dims[-1] or len(dims) == 3 and dims[1] < 64):
            return "full_rest"
    return None


def classify(events, cfg: dict) -> Dict[str, List[Tuple[int, int]]]:
    """``events`` ([whole name, start_ns, duration_ns]) by kind. A verdict is
    worked out once a distinct name."""
    s = sizes(cfg)
    verdict: Dict[str, Optional[str]] = {}
    out: Dict[str, List[Tuple[int, int]]] = {kind: [] for kind in KINDS}
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = kind_of(name, s)
        if verdict[name]:
            out[verdict[name]].append((start, dur))
    return out


def read_ops(directory: str, cfg: dict) -> dict:
    """The newest trace under ``directory``: the first device's ``XLA Ops``
    line, classified, and the annotated window. Empty where there is no
    trace or no device plane."""
    from harness.trace import WINDOW_SPAN

    t0 = time.perf_counter()
    out = {kind: [] for kind in KINDS}
    out.update(events=0, span=None)
    path = newest_trace(directory)
    if path is not None:
        from jax.profiler import ProfileData

        planes = list(ProfileData.from_file(path).planes)
        for plane in planes:
            if plane.name.startswith("/device:") or out["span"]:
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        out["span"] = (
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
    """Device seconds of the operations of ``kinds`` inside the traced
    window, from what the driver kept (``ctx["dsa_ops"]``)."""
    ops = ctx.get("dsa_ops") or {}
    window = ops.get("span")  # the annotated window's (start, end)
    spans = [s for kind in kinds for s in ops.get(kind, ())]
    if not window or not spans:
        return None
    merged = clipped_union(spans, window)
    return sum(e - s for s, e in merged) / 1e9 if merged else None


COUNTERS = {
    "visible": "decode_kv_tokens_visible",
    "scored": "decode_index_tokens_scored",
    "scored_distinct": "decode_index_tokens_scored_distinct",
    "selected": "decode_kv_tokens_selected",
    "window_visible": "decode_window_tokens_visible",
    "window_read": "decode_window_tokens_read",
}


def step_counters(ctx, traced_only: bool = True) -> Optional[dict]:
    """The engine's counters of what its decode dispatches read
    (``COUNTERS``) summed over the ``step`` slices (those that started inside
    the traced window, or all the window's), with ``steps``, how many carried
    them. ``None`` where the program writes no
    ``decode_index_tokens_scored``."""
    if traced_only and "traced" not in ctx:
        return None
    t0, t1 = ctx.get("traced") or (0.0, float("inf"))
    total = dict.fromkeys(COUNTERS, 0)
    total["steps"] = 0
    for e in ctx.get("engine_events") or ():
        args = e.get("args") or {}
        if (e["name"] != "step" or e.get("ph") != "X"
                or "decode_index_tokens_scored" not in args):
            continue
        if traced_only and not t0 * 1e9 <= args["perf_counter_ns"] < t1 * 1e9:
            continue
        for key, name in COUNTERS.items():
            total[key] += args.get(name, 0)
        total["steps"] += 1
    return total if total["steps"] else None


def layer_counts(cfg: dict) -> Tuple[int, int]:
    kinds = cfg["layer_types"]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


__all__ = [
    "KINDS", "classify", "device_seconds", "kind_of", "layer_counts",
    "read_ops", "sizes", "step_counters", "traced_steps",
]
