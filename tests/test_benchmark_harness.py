"""The benchmark's own fast tests, run by tier-1.

``benchmarks/tests/`` is collected by no command the driver runs, and the
trace reduction it tests decides every per-layer metric: a package change
that renames a slice would break a metric's reader unseen. This file puts
``benchmarks/`` on ``sys.path`` as ``benchmarks/tests/conftest.py`` does and
hands pytest the cases of the twelve fast files there, each as a case of a
class named after its file, so two files may each have a fixture ``spec``.
The rehearsal files stay outside, for their 40-90 s each.

``tests/conftest.py`` gives 8 virtual devices where theirs gives 4. Every
case passes under 8 (the toy four-chip cell takes the first four), so none
is left out.
"""

import importlib.util
import inspect
import os
import sys

from _pytest.fixtures import getfixturemarker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

FILES = ("test_phases", "test_ssm_readers", "test_trace_reduction", "test_control",
         "test_kernel_readers", "test_moe_readers", "test_prefill_readers",
         "test_latent_readers", "test_setup_readers", "test_dsa_readers",
         "test_gdn_readers", "test_window_readers")


def cases_of(stem):
    """A class holding ``benchmarks/tests/<stem>.py``'s tests and fixtures as
    static methods: pytest calls them as the plain functions they are."""
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{stem}", os.path.join(BENCH, "tests", f"{stem}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    body = {
        name: staticmethod(obj) for name, obj in vars(module).items()
        if (name.startswith("test_") and inspect.isfunction(obj))
        or getfixturemarker(obj) is not None}
    return type("Test" + stem.removeprefix("test_").title().replace("_", ""),
                (), body)


for _stem in FILES:
    _cls = cases_of(_stem)
    globals()[_cls.__name__] = _cls
