#!/usr/bin/env python3
"""Cut a small recorded trace with WHOLE operation names out of a
``serve_hybrid`` run's profile, for ``test_ssm_readers.py``.

    python3 benchmarks/tests/record_ssm_trace.py <trace.xplane.pb> <out.json> [ms] [skip_ms]

Keeps ``ms`` milliseconds (default 60) of the first device's ``XLA Ops`` line
from ``skip_ms`` (default 0) into the traced window on. A name is cut down to
what ``harness/hybrid.py`` reads of it: the instruction's own name, its result
types without their layouts, and its opcode. Of the operations INSIDE a
``while`` only every 64th is kept (a prefill chunk's scan is one ``while`` a
layer, and its body's operations are nine tenths of a trace's events); the
loop's own event covers them all. Outside the loops, an operation under 10 us
is kept only if it is one of the mixers'.
"""

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.hybrid import is_ssm_op, result_types  # noqa: E402
from harness.trace import WINDOW_SPAN  # noqa: E402

SIZES = (5120, 16, 4)  # d_inner, N, K of the configuration recorded
_LAYOUT = re.compile(r"\{[^{}]*\}")


def cut(text: str) -> str:
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    results = result_types(text)
    opcode = rest[len(results):].strip().split("(", 1)[0]
    return f"{head} = {_LAYOUT.sub('', results)} {opcode}(...)"


def raw_device_events(path: str):
    """``(window, events)`` of a trace file: the annotated window in trace
    nanoseconds and the first device's ``XLA Ops`` events with their whole
    names."""
    from jax.profiler import ProfileData

    window, events = None, []
    for plane in sorted(ProfileData.from_file(path).planes, key=lambda p: p.name):
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and not events and line.name.lower() == "xla ops":
                events = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                          for ev in line.events]
            elif not device and window is None:
                window = next(
                    ((int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                     for ev in line.events if ev.name == WINDOW_SPAN), None)
    return window, events


def main() -> None:
    source, target = sys.argv[1], sys.argv[2]
    keep_ns = int(float(sys.argv[3]) * 1e6) if len(sys.argv) > 3 else 60_000_000
    skip_ns = int(float(sys.argv[4]) * 1e6) if len(sys.argv) > 4 else 0
    window, events = raw_device_events(source)
    start = window[0] + skip_ns
    end = start + keep_ns
    kept, loop_end, inside = [], -1, 0
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if s >= end or s + d <= start:
            continue
        if s < loop_end:  # inside a while
            inside += 1
            if inside % 64:
                continue
        elif " while(" in n:
            loop_end = s + d
        elif d < 10_000 and not is_ssm_op(n, *SIZES):
            continue
        kept.append(
            [cut(n), max(s, start) - start, min(s + d, end) - max(s, start)])
    with open(target, "w") as f:
        json.dump({"window": [0, keep_ns], "events": kept}, f,
                  separators=(",", ":"))
    print(f"{target}: {len(kept)} events")


if __name__ == "__main__":
    main()
