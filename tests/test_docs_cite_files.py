"""The documents cite files that exist.

Every back-quoted token of a document that looks like a path of this
repository (``*.py``, ``*.sh``, ``*.json``, ``*.jsonl``, ``*.cpp``, ``*.md``,
with a directory or bare, with or without ``:lines``) has to name a tracked
file: from the checkout's root, from the package, from ``benchmarks/`` or from
the document's own directory. ``CHANGES.md``, ``ROADMAP.md`` and ``PERF.md``
are history and are not cases.
"""

import fnmatch
import functools
import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = [
    "README.md",
    "docs/ARCHITECTURE.md",
    "docs/MIGRATING.md",
    "benchmarks/README.md",
    ".claude/skills/verify/SKILL.md",
]
PATH = re.compile(
    r"`([\w.][\w./-]*\.(?:py|sh|jsonl|json|cpp|md))(?::[\d,-]+)?`")
# The source paper's scripts (SURVEY.md §0), which the documents map from.
REFERENCE = {
    "single_gpu.py", "multigpu.py", "multigpu_torchrun.py",
    "multinode_torchrun.py", "multigpu_profile.py", "utils.py",
    "sbatch_run.sh", "slurm/sbatch_run.sh",
}
# Names the router's journal gives the files it writes into its directory.
WRITTEN_AT_RUN_TIME = {"journal-NNNNNN.jsonl", "router_recovery_flight.json"}


with open(os.path.join(ROOT, ".gitignore")) as _f:
    IGNORE_RULES = [line.strip() for line in _f if line.strip()]


@functools.cache
def tracked_files():
    try:
        out = subprocess.run(
            ["git", "ls-files"], cwd=ROOT, check=True, capture_output=True,
            text=True).stdout.split("\n")
        return {p for p in out if os.path.exists(os.path.join(ROOT, p))}
    except (OSError, subprocess.CalledProcessError):  # an unpacked archive
        return {
            os.path.relpath(os.path.join(base, name), ROOT)
            for base, _, names in os.walk(ROOT) for name in names}


def ignored(path):
    """Whether ``.gitignore`` names ``path``: an output, made when asked."""
    return any(
        path.startswith(rule) if rule.endswith("/")
        else fnmatch.fnmatch(os.path.basename(path), rule)
        for rule in IGNORE_RULES)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_cited_path_names_a_tracked_file(document):
    tracked = tracked_files()
    with open(os.path.join(ROOT, document)) as f:
        cited = set(PATH.findall(f.read()))
    assert cited, f"{document} cites no file: the pattern has gone blind"
    here = os.path.dirname(document)
    bases = ("", "distributed_pytorch_tpu", "benchmarks", here)
    missing = sorted(
        path for path in cited - REFERENCE - WRITTEN_AT_RUN_TIME
        if not ignored(path) and not any(
            os.path.normpath(os.path.join(base, path)) in tracked
            for base in bases))
    assert not missing, f"{document} cites files that do not exist: {missing}"
