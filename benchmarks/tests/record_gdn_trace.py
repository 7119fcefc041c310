#!/usr/bin/env python3
"""Cut a small recorded trace with WHOLE operation names out of a
``serve_linear_hybrid`` run's profile, for ``test_gdn_readers.py``, and say
where the traced window's device time went.

    python3 benchmarks/tests/record_gdn_trace.py <trace.xplane.pb or a directory> <config.json> <out.json> [ms] [skip_ms]

``record_dsa_trace.py``'s cut (150 ms of the first device's ``XLA Ops`` line
and a ``summary`` of the whole traced window: the device time of each kind,
and the thirty names that took most of it), with ``harness/linear.py``'s kinds
in the place of ``harness/dsa.py``'s; an operation of no kind is ``other``
there.
"""

import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import record_dsa_trace  # noqa: E402
from harness import linear  # noqa: E402

if __name__ == "__main__":
    record_dsa_trace.dsa = types.SimpleNamespace(
        sizes=linear.sizes, kind_of=linear.op_kind)
    record_dsa_trace.main()
