#!/usr/bin/env python3
"""Where a traced serving run's device time went, by operation name: the
first device's ``XLA Ops`` line inside the annotated window, summed by the
instruction's name less its number (kernels keep theirs), the fifty largest.

    python3 benchmarks/tests/summarise_trace.py <trace dir or .xplane.pb>

A tool for ``PERF.md`` section 5, read by no metric.
"""

import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.moe_hybrid import newest_trace  # noqa: E402
from record_ssm_trace import cut, raw_device_events  # noqa: E402


def main() -> None:
    source = sys.argv[1]
    if os.path.isdir(source):
        source = newest_trace(source)
    window, events = raw_device_events(source)
    total = collections.Counter()
    calls = collections.Counter()
    inside = [(n, s, d) for n, s, d in events
              if window is None or window[0] <= s < window[1]]
    for name, _, dur in inside:
        text = cut(name)
        head, _, rest = text.partition(" = ")
        key = re.sub(r"\.\d+$", "", head.lstrip("%")) + " = " + rest[:90]
        total[key] += dur
        calls[key] += 1
    span = (window[1] - window[0]) if window else 0
    print(f"{len(inside)} events in a window of {span / 1e6:.1f} ms; "
          f"summed {sum(total.values()) / 1e6:.1f} ms (loops count their "
          f"bodies twice)")
    for key, ns in total.most_common(50):
        print(f"{ns / 1e6:9.2f} ms {calls[key]:6d} calls "
              f"{ns / calls[key] / 1e3:9.1f} us  {key}")


if __name__ == "__main__":
    main()
