"""What the readers of a ``serve_linear_hybrid`` cell share: the device time
of the gated-delta mixers' operations in the traced window, by kind, and the
engine's state counters of the traced steps.

**How the operations are recognised.** As ``harness/hybrid.py`` says, a v5e
device trace names an operation by its whole HLO instruction and carries no
scope. Three kinds:

* ``step``: the decode kernel, BY NAME: an instruction whose name holds
  ``gated_delta_step`` (the ``pallas_call`` is named
  ``linear_attention._gated_delta_step``, ``ops/linear_attention.py``).
* ``blocks``: the blocked prefill, which is plain XLA operations, by the
  RESULT shapes of the whole names, with the sizes of the configuration and
  the block length ``L`` 64: a result ``[.., H, L, x]`` with ``x`` one of
  ``L`` (the decay and Gram matrices and the system's), ``d_k``, ``d_v`` or
  ``d_k + d_v`` (the blocks' ``q``, ``k``, ``v``, ``u``, the system's
  right-hand sides and results), ``[.., H, L]`` (the cumulated decays), ``[..,
  H, .., s, s]`` with ``s <= L`` (the system's inverse, by halves: ``ops/
  linear_attention.py`` ``unit_lower_inverse``), or the state as the
  recurrence carries it, ``[.., H, d_k, d_v]`` (the scan over the blocks with
  everything inside it).
* ``rest``: the rest of the mixers that a shape gives away: the conv (a
  result whose last size is the conv's channels ``2 H d_k + H d_v``, or its
  tail ``[.., K-1, channels]``), the heads' ``q, k, v, o`` and their norms
  (``[.., H, d_k]``, ``[.., H, d_v]``), the state in the layout it is kept in
  (``[.., H / p, d_k, p d_v]``: a prefill piece's gather and scatter of its
  slot's row) and the kernel's row operands in that layout (``[.., H / p, p
  d_v]``).

Their time is the union of their intervals inside the traced window. NOT
counted: the seven projections (``W_q`` .. ``W_o``: matrix products that XLA
may fuse with a neighbouring elementwise operation; a fusion whose results
show none of the shapes above is not the mixers' to this reader), and the
gate's SiLU where XLA fuses it into ``W_g``'s or ``W_o``'s product. Counted
though not meant: nothing known at the published sizes (30 heads of 96 / 192
share no size with the full layers' 30 heads of 128); at other sizes a full
layer's result ``[.., H, d_k]`` would be (``benchmarks/tests/test_gdn_readers.py`` pins
the rules on a recorded trace).

Every function returns ``None`` (or empty lists) where there is nothing to
read: a program without such operations or counters (this cell's parent), a
run on the CPU.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

from harness.hybrid import clipped_union, traced_steps
from harness.moe_hybrid import newest_trace, result_shapes

KINDS = ("step", "blocks", "rest")
STEP_KERNEL = "gated_delta_step"
BLOCK = 64
LANES = 128


def sizes(cfg: dict) -> dict:
    heads = cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    pack = next(
        (p for p in (1, 2, 4, 8)
         if heads % p == 0 and (p * dv) % LANES == 0), 1)
    return dict(
        heads=heads, dk=dk, dv=dv, block=BLOCK,
        channels=heads * (2 * dk + dv),
        taps=cfg["linear_conv_kernel_dim"] - 1,
        packed=(heads // pack, dk, pack * dv),
    )


def op_kind(text: str, s: dict) -> Optional[str]:
    """Which of ``KINDS`` the HLO instruction ``text`` belongs to, or
    ``None`` (module docstring)."""
    if STEP_KERNEL in text.split(" = ", 1)[0]:
        return "step"
    h, dk, dv, ell = s["heads"], s["dk"], s["dv"], s["block"]
    rest = False
    for dims in result_shapes(text):
        if len(dims) >= 3 and dims[-3:-1] == (h, ell) and dims[-1] in (
                ell, dk, dv, dk + dv):
            return "blocks"
        if len(dims) >= 2 and dims[-2:] == (h, ell):
            return "blocks"
        if len(dims) >= 3 and dims[-1] == dims[-2] <= ell and h in dims[:-2]:
            return "blocks"  # the system's inverse, by halves: [.., H, g, s, s]
        if dims[-3:] == (h, dk, dv):
            return "blocks"
        if dims and dims[-1] == s["channels"]:
            rest = True
        elif dims[-3:] == s["packed"] or dims[-2:] in (
                (h, dk), (h, dv), (s["packed"][0], s["packed"][2])):
            rest = True
    return "rest" if rest else None


def classify(events, s: dict) -> Dict[str, List[Tuple[int, int]]]:
    """``events`` ([whole name, start_ns, duration_ns]) by kind: the (start,
    duration) of each. A verdict is worked out once a distinct name."""
    verdict: Dict[str, Optional[str]] = {}
    out: Dict[str, List[Tuple[int, int]]] = {kind: [] for kind in KINDS}
    for name, start, dur in events:
        if name not in verdict:
            verdict[name] = op_kind(name, s)
        if verdict[name]:
            out[verdict[name]].append((start, dur))
    return out


def read_ops(directory: str, cfg: dict) -> dict:
    """The newest trace under ``directory``: the first device's ``XLA Ops``
    line, classified. Empty where there is no trace or no device plane."""
    t0 = time.perf_counter()
    out = {kind: [] for kind in KINDS}
    out["events"] = 0
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


def device_seconds(ctx, kinds=KINDS) -> Optional[float]:
    """Device seconds of the operations of ``kinds`` inside the traced
    window, from what the driver kept (``ctx["gdn_ops"]``: the window and the
    operations' spans by kind). ``None`` where there are none."""
    window, ops = ctx.get("gdn_ops") or (None, {})
    spans = [span for kind in kinds for span in ops.get(kind, ())]
    merged = clipped_union(spans, window) if window and spans else []
    return sum(e - s for s, e in merged) / 1e9 if merged else None


def traced_events(ctx, name: str) -> List[dict]:
    """The ``args`` of the engine's ``name`` slices that started inside the
    traced window."""
    if "traced" not in ctx:
        return []
    t0, t1 = ctx["traced"]
    return [
        e["args"] for e in ctx.get("engine_events") or ()
        if e["name"] == name and e.get("ph") == "X"
        and t0 * 1e9 <= e["args"]["perf_counter_ns"] < t1 * 1e9]


def state_bytes_moved(ctx) -> Optional[int]:
    """The traced steps' ``state_bytes_moved`` (the engine's count by the
    decode kernel's own rule). ``None`` from a program without the counter."""
    steps = [a for a in traced_events(ctx, "step") if "state_bytes_moved" in a]
    return sum(a["state_bytes_moved"] for a in steps) if steps else None


def traced_pieces(ctx) -> List[int]:
    """``state_blocks`` of every prefill piece the traced steps ran: blocks a
    gated-delta layer evaluated for it. Empty without the counter."""
    return [a["state_blocks"] for a in traced_events(ctx, "prefill.chunk")
            if "state_blocks" in a]


__all__ = [
    "classify", "device_seconds", "op_kind", "read_ops", "sizes",
    "state_bytes_moved", "traced_pieces", "traced_steps",
]
