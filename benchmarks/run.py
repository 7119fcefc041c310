#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it, under any
of the directories in its ``paths``:

    configs/<config>.json     the sizes as run (``file`` in BENCHMARK.json);
                              its ``kind`` picks ``drivers/<kind>.py`` and its
                              ``reference`` picks ``reference/<reference>.py``
    traffic/<traffic>.json    the parameters of the mix
    metrics/<metric>.py       ``read(ctx)`` -> number, or None for "nothing
                              to read here"; a quantity split by a last
                              suffix (``x.serve``, ``x.train``) may share
                              ``metrics/x.py``

The last line of standard output is the result object; everything before it is
for people. With ``--trace 0`` the metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics (and ``breakdown``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def say(text: str) -> None:
    print(f"[bench] {text}", flush=True)


def find(paths: List[str], relative: str) -> str:
    """The first ``<path>/<relative>`` that exists among the benchmark's
    directories."""
    for base in paths:
        candidate = os.path.join(ROOT, base, relative)
        if os.path.exists(candidate):
            return candidate
    raise FileNotFoundError(
        f"{relative} is in none of the benchmark's directories {paths}")


def find_reader(paths: List[str], metric: str) -> str:
    """``metrics/<metric>.py``, or the one of the metric less its last
    suffix, which the cells of a split quantity share."""
    try:
        return find(paths, f"metrics/{metric}.py")
    except FileNotFoundError:
        return find(paths, f"metrics/{metric.rpartition('.')[0]}.py")


def load_module(path: str):
    name = "bench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    spec = importlib.util.spec_from_file_location(name, path)
    if name in sys.modules:
        return sys.modules[name]
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # dataclasses look their module up there
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """What a driver is handed: one cell, one seed, one run."""

    name: str
    config: dict
    traffic: dict
    reference: Any
    driver: Any  # the loaded drivers/<kind>.py, for hooks
    chips: int
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    say: Callable[[str], None]
    hooks: Dict[str, Any]
    allow_cpu: bool = False  # the CPU rehearsal tests only

    def stamp(self) -> dict:
        from harness.device import stamp

        return stamp(self.devices)

    def scratch(self, name: str) -> str:
        """A directory for what a run leaves behind, inside the checkout
        (``.gitignore`` lists it)."""
        return os.path.join(ROOT, ".bench_scratch", self.name, name)


def reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def run_cell(
    workload: str, seed: int, seconds: float, trace: bool, *,
    spec: Optional[dict] = None, allow_cpu: bool = False,
    hooks: Optional[Dict[str, Any]] = None, t_start: float = T_START,
) -> dict:
    """Run the cell and return the result object (the last line's dict)."""
    spec = spec or load_json(os.path.join(ROOT, "BENCHMARK.json"))
    paths = spec["paths"]
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
    entry = cells[workload]
    config_entry = next(c for c in spec["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(find(paths, f"traffic/{entry['traffic']}.json"))
    driver = load_module(find(paths, f"drivers/{config['kind']}.py"))
    reference = load_module(find(paths, f"reference/{config['reference']}.py"))

    from harness.device import open_device
    from harness.trace import breakdown

    devices, cache_dir = open_device(entry["chips"], allow_cpu=allow_cpu)
    say(f"cell {workload}: config {entry['config']}, traffic "
        f"{entry['traffic']}, {entry['chips']} chip(s), seed {seed}, "
        f"{seconds}s, trace {int(trace)}; compile cache {cache_dir}")
    cell = Cell(
        name=workload, config=config, traffic=traffic, reference=reference,
        driver=driver,
        chips=entry["chips"], seed=seed, seconds=seconds, trace=trace,
        t_start=t_start, devices=devices, say=say, hooks=hooks or {},
        allow_cpu=allow_cpu,
    )
    out = driver.run(cell)

    metrics: Dict[str, dict] = {}
    if not trace:
        for m in spec["end_to_end"]:
            if reports(m, workload) and m["name"] in out["values"]:
                metrics[m["name"]] = {
                    "value": out["values"][m["name"]], "unit": m["unit"]}
    else:
        ctx = out["context"]
        for m in spec["per_layer"]:
            if not reports(m, workload):
                continue
            reader = load_module(find_reader(paths, m["name"]))
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result = {
        "correct": out["correct"], "attempted": out["attempted"],
        "failed": out["failed"], "metrics": metrics, "device": out["device"],
    }
    if trace:
        summary = out["context"]["trace"]
        result["device"]["busy_s"] = summary.busy_s
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = breakdown(summary)
    return result


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    say(f"the run took {time.perf_counter() - T_START:.1f}s in all")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
