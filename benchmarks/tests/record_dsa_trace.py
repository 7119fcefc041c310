#!/usr/bin/env python3
"""Cut a small recorded trace with WHOLE operation names out of a
``serve_sparse_latent_moe`` run's profile, for ``test_dsa_readers.py``, and
say where the traced window's device time went.

    python3 benchmarks/tests/record_dsa_trace.py <trace.xplane.pb or a directory> <config.json> <out.json> [ms] [skip_ms]

Keeps ``ms`` milliseconds (default 150: a few engine steps) of the first
device's ``XLA Ops`` line from ``skip_ms`` into the traced window on, as
``record_latent_trace.py`` does: a name is cut down to what ``harness/dsa.py``
reads of it; an operation under 10 us is kept only if it is one of the
recognised kinds and not inside a loop. Beside the events the file keeps
``summary``: over the WHOLE traced window, the device time of each kind
(outside loops' insides), and the thirty names, recognised or not, that took
most of it.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import dsa  # noqa: E402
from harness.moe_hybrid import newest_trace  # noqa: E402
from record_ssm_trace import cut, raw_device_events  # noqa: E402


def main() -> None:
    source, config, target = sys.argv[1:4]
    keep_ns = int(float(sys.argv[4]) * 1e6) if len(sys.argv) > 4 else 150_000_000
    skip_ns = int(float(sys.argv[5]) * 1e6) if len(sys.argv) > 5 else 0
    if os.path.isdir(source):
        source = newest_trace(source)
    with open(config) as f:
        sizes = dsa.sizes(json.load(f))
    window, events = raw_device_events(source)
    start = window[0] + skip_ns
    end = start + keep_ns
    kept, dropped, dropped_ns, loop_end = [], 0, 0, -1
    by_kind, by_name, whole_loop_end = {}, {}, -1
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        kind = dsa.kind_of(n, sizes)
        if window[0] <= s < window[1] and s >= whole_loop_end:
            # Outside any loop's inside: a loop's time is the loop's.
            by_kind[kind or "other"] = by_kind.get(kind or "other", 0) + d
            short = cut(n).split(" = ")[0].rstrip(".0123456789")
            shape = cut(n).split(" = ")[-1][:60]
            by_name.setdefault((short, kind or "other", shape), [0, 0])
            by_name[(short, kind or "other", shape)][0] += d
            by_name[(short, kind or "other", shape)][1] += 1
            if " while(" in n:
                whole_loop_end = max(whole_loop_end, s + d)
        if s >= end or s + d <= start:
            continue
        if d < 10_000 and (kind is None or s < loop_end):
            dropped, dropped_ns = dropped + 1, dropped_ns + d
            continue
        if " while(" in n:
            loop_end = max(loop_end, s + d)
        kept.append(
            [cut(n), max(s, start) - start, min(s + d, end) - max(s, start)])
    top = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:30]
    summary = {
        "window_ms": (window[1] - window[0]) / 1e6,
        "ms_by_kind": {k: v / 1e6 for k, v in sorted(by_kind.items())},
        "top": [[name, kind, shape, ns / 1e6, calls]
                for (name, kind, shape), (ns, calls) in top]}
    os.makedirs(os.path.dirname(os.path.abspath(target)), exist_ok=True)
    with open(target, "w") as f:
        json.dump({"window": [0, keep_ns], "events": kept,
                   "dropped": [dropped, dropped_ns], "summary": summary}, f,
                  separators=(",", ":"))
    print(f"{target}: {len(kept)} events kept, {dropped} dropped")
    print(json.dumps(summary["ms_by_kind"]))


if __name__ == "__main__":
    main()
