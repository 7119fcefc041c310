"""The device a run is on: refuse anything but the accelerator the cell asks
for, stamp every result with it, and keep the compile cache where the
program's own ``enable_compile_cache()`` puts it."""

from __future__ import annotations

import os
import sys


class NoAccelerator(SystemExit):
    """Raised (exit code 3, no result line) when the cell cannot run here."""


def refuse(reason: str) -> "NoAccelerator":
    print(f"benchmarks: {reason}", file=sys.stderr, flush=True)
    return NoAccelerator(3)


def open_device(chips: int, *, allow_cpu: bool = False):
    """Place the compile cache, open the backend, and return the devices the
    cell runs on. ``allow_cpu`` exists for the rehearsal tests only; run.py
    never sets it."""
    if not allow_cpu and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        raise refuse("JAX_PLATFORMS=cpu: no accelerator to measure on")
    from distributed_pytorch_tpu.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    # Small programs (the weight generators, the page copy) are cheap to
    # compile but not free; cache them too so a warm run compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be opened
        raise refuse(f"JAX found no device: {e}")
    if not allow_cpu and devices[0].platform == "cpu":
        raise refuse("JAX found no accelerator (platform is cpu)")
    if len(devices) < chips:
        raise refuse(f"the cell needs {chips} chip(s), JAX sees {len(devices)}")
    return devices[:chips], cache_dir


def stamp(devices) -> dict:
    """``device`` of the result line, as JAX reports it. ``memory_peak_bytes``
    is the peak on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }
