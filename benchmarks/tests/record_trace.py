#!/usr/bin/env python3
"""Cut a small recorded trace out of a run's profile, for
``test_trace_reduction.py``.

    python3 benchmarks/tests/record_trace.py <trace.xplane.pb> <out.json> [ms]

Keeps the first ``ms`` milliseconds (default 250) of the traced window: the
device planes' operation lines and, from the host planes, the harness's own
spans. Operation names are already shortened by ``load_xplane``.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.trace import WINDOW_SPAN, load_xplane  # noqa: E402

SPANS = ("engine.step", "client.poll", "client.submit", "loader.next",
         "train.put_batch", "train.step", "train.epoch", WINDOW_SPAN)


def main() -> None:
    source, target = sys.argv[1], sys.argv[2]
    keep_ns = int(float(sys.argv[3]) * 1e6) if len(sys.argv) > 3 else 250_000_000
    xplane = load_xplane(source)
    opened = min(
        s for p in xplane["planes"] for l in p["lines"]
        for n, s, d in l["events"] if n == WINDOW_SPAN)
    # from the first device operation of the traced window on
    start = min(
        s for p in xplane["planes"] if p["name"].startswith("/device:")
        for l in p["lines"] if l["name"] == "XLA Ops"
        for n, s, d in l["events"] if s >= opened)
    end = start + keep_ns
    planes = []
    for plane in xplane["planes"]:
        device = plane["name"].startswith("/device:")
        lines = []
        for line in plane["lines"]:
            if device and line["name"] != "XLA Ops":
                continue
            events = [
                [n, max(s, start), min(s + d, end) - max(s, start)]
                for n, s, d in line["events"]
                if s < end and s + d > start and (device or n in SPANS)
            ]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    with open(target, "w") as f:
        json.dump({"planes": planes}, f, separators=(",", ":"))
    print(f"{target}: {sum(len(l['events']) for p in planes for l in p['lines'])} events")


if __name__ == "__main__":
    main()
