#!/usr/bin/env python3
"""Cut a small recorded trace with WHOLE operation names out of a
``serve_cca_moe`` run's profile, for ``test_cca_readers.py``.

    python3 benchmarks/tests/record_cca_trace.py <trace dir or .xplane.pb> <config.json> <out.json> [ms] [skip_ms]

Keeps ``ms`` milliseconds (default 60: a few engine steps) of the first
device's ``XLA Ops`` line from ``skip_ms`` (default 0) into the traced window
on. A name is cut down to what ``harness/cca.py`` reads of it: the
instruction's own name, its result types without their layouts, and its
opcode. An operation under 2 us is kept only if it is one of the kinds that
file reads. Beside the events the file keeps how many were dropped and their
summed time.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import cca  # noqa: E402
from harness.moe_hybrid import newest_trace  # noqa: E402
from record_ssm_trace import cut, raw_device_events  # noqa: E402


def main() -> None:
    source, config, target = sys.argv[1:4]
    keep_ns = int(float(sys.argv[4]) * 1e6) if len(sys.argv) > 4 else 60_000_000
    skip_ns = int(float(sys.argv[5]) * 1e6) if len(sys.argv) > 5 else 0
    if os.path.isdir(source):
        source = newest_trace(source)
    with open(config) as f:
        sizes = cca.sizes(json.load(f))
    span, events = raw_device_events(source)
    start = span[0] + skip_ns
    end = start + keep_ns
    kept, dropped, dropped_ns = [], 0, 0
    for n, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        if s >= end or s + d <= start:
            continue
        if d < 2_000 and cca.kind_of(n, sizes) is None:
            dropped, dropped_ns = dropped + 1, dropped_ns + d
            continue
        kept.append(
            [cut(n), max(s, start) - start, min(s + d, end) - max(s, start)])
    with open(target, "w") as f:
        json.dump({"window": [0, keep_ns], "events": kept,
                   "dropped": [dropped, dropped_ns]}, f, separators=(",", ":"))
    print(f"{target}: {len(kept)} events kept, {dropped} dropped")


if __name__ == "__main__":
    main()
