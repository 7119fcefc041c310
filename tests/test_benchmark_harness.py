"""The benchmark's own fast tests, run by tier-1.

``benchmarks/tests/`` is collected by no command the driver runs, and the
trace reduction it tests decides every per-layer metric: a package change
that renames a slice would break a metric's reader unseen. This file puts
``benchmarks/`` on ``sys.path`` as ``benchmarks/tests/conftest.py`` does and
hands pytest the cases of the thirteen fast files there, each as a case of a
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
         "test_gdn_readers", "test_window_readers", "test_cca_readers")

#: A reader file may pin ``BENCHMARK.json`` as ITS PR left it (how many cells
#: there are, that its own is the last of every list). A PR adds to the
#: benchmark and edits no file of it, so such a file is shown the benchmark
#: less what later PRs appended: the cells after its own, the configurations
#: and metrics that only they use, and their names in every list. That they
#: only APPENDED is thereby part of what it tests.
AS_ITS_PR_LEFT_IT = {"test_window_readers": "k-exaone-236b-mixed-queue"}


def as_left_after(spec: dict, cell: str) -> dict:
    names = [w["name"] for w in spec["workloads"]]
    later = set(names[names.index(cell) + 1:])
    kept = [w for w in spec["workloads"] if w["name"] not in later]
    used = {w["config"] for w in kept}

    def without_later(metric):
        if "workloads" not in metric:
            return metric
        return dict(metric, workloads=[
            n for n in metric["workloads"] if n not in later])

    return dict(
        spec, workloads=kept,
        configs=[c for c in spec["configs"] if c["name"] in used],
        end_to_end=[without_later(m) for m in spec["end_to_end"]],
        per_layer=[m for m in map(without_later, spec["per_layer"])
                   if m.get("workloads", True)])


class _JsonAsLeft:
    """``json`` for a module of :data:`AS_ITS_PR_LEFT_IT`: ``load`` of
    ``BENCHMARK.json`` gives :func:`as_left_after`, everything else is
    ``json``'s."""

    def __init__(self, cell):
        self._cell = cell

    def __getattr__(self, name):
        import json

        return getattr(json, name)

    def load(self, f, **kw):
        import json

        out = json.load(f, **kw)
        if os.path.basename(getattr(f, "name", "")) == "BENCHMARK.json":
            out = as_left_after(out, self._cell)
        return out


def cases_of(stem):
    """A class holding ``benchmarks/tests/<stem>.py``'s tests and fixtures as
    static methods: pytest calls them as the plain functions they are."""
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_tests_{stem}", os.path.join(BENCH, "tests", f"{stem}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if stem in AS_ITS_PR_LEFT_IT:
        module.json = _JsonAsLeft(AS_ITS_PR_LEFT_IT[stem])
    body = {
        name: staticmethod(obj) for name, obj in vars(module).items()
        if (name.startswith("test_") and inspect.isfunction(obj))
        or getfixturemarker(obj) is not None}
    return type("Test" + stem.removeprefix("test_").title().replace("_", ""),
                (), body)


for _stem in FILES:
    _cls = cases_of(_stem)
    globals()[_cls.__name__] = _cls
