"""Roofline attribution: how far is each compiled program from the chip?

``goodput.py`` answers "what fraction of wall-clock was productive";
this module answers the harder hardware question: for each compiled
program in the :class:`~.xla.ProgramLedger`, is it COMPUTE-bound or
BANDWIDTH-bound, what is the roofline-implied step-time floor, and what
fraction of that roof does the measured step time achieve? That
achieved-fraction gauge is the before/after number a kernel PR (the
ROADMAP's Pallas paged-attention item) gets judged against.

The classic roofline model (Williams et al., CACM 2009):

* arithmetic intensity ``I = flops / bytes`` (FLOPs per HBM byte moved);
* the machine balance ("ridge point") is ``peak_flops / peak_bw``;
* attainable FLOP/s is ``min(peak_flops, I * peak_bw)`` — programs left
  of the ridge are bandwidth-bound, right of it compute-bound;
* the implied time floor for one invocation is
  ``max(flops / peak_flops, bytes / peak_bw)`` — whichever resource is
  saturated sets the clock.

Inputs, all already on hand:

* **bytes** per program from the ledger's ``memory_analysis()``:
  argument + output + temp bytes — the HBM traffic floor for one call
  (weights and KV stream in as arguments every step, which is exactly
  why decode is bandwidth-bound);
* **flops** per program from ``cost_analysis()``, falling back to the
  analytic decode-FLOPs model via ``fallback_flops_fn`` when XLA reports
  0 (the CPU backend's cost analysis omits flops — same limitation the
  goodput MFU path works around);
* **peaks** from :data:`~.goodput.PEAK_BF16_FLOPS` and the
  :data:`HBM_BYTES_PER_SEC` table below (public spec-sheet HBM bandwidth
  per chip, substring-matched on ``device_kind`` exactly like
  :func:`~.goodput.peak_flops_per_chip`);
* **measured step time** from the TSDB's ``step_wall_seconds`` series,
  so achieved-fraction tracks the same window the dashboards show.

Host-side float arithmetic only — no device work, zero cost when off.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

from .goodput import peak_flops_per_chip, peak_for_device

# Peak HBM bandwidth per chip by generation, bytes/second (public spec
# sheets).
# An unknown TPU kind is an error; other devices get DEFAULT_HBM_BW (see
# goodput.peak_for_device).
HBM_BYTES_PER_SEC = {
    "v6": 1640e9,
    "v5p": 2765e9,
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v4": 1228e9,
    "v3": 900e9,
    "v2": 700e9,
}
DEFAULT_HBM_BW = 819e9

# Ledger-name prefixes of programs whose hot loop is a hand-written fused
# kernel rather than plain XLA — program_rows tags these so a bench (or a
# /statusz reader) can attribute an achieved_fraction delta to the kernel
# instead of eyeballing program names. The paged decode program compiles
# under "decode_step_paged" exactly when InferenceEngine(paged_kernel=...)
# is on.
FUSED_PROGRAM_PREFIXES = ("decode_step_paged",)


def hbm_bandwidth_per_chip(device) -> float:
    """Peak HBM bytes/sec for a jax device, by kind substring (mirrors
    :func:`~.goodput.peak_flops_per_chip`)."""
    return peak_for_device(HBM_BYTES_PER_SEC, device, DEFAULT_HBM_BW)


def roofline_point(
    flops: float, hbm_bytes: float, peak_flops: float, peak_bw: float
) -> dict:
    """Pure roofline math for one program invocation, as
    :class:`RooflineModel` applies it.

    Returns intensity (flops/byte), the machine balance (ridge point),
    the bound classification, the implied time floor in seconds, and the
    attainable FLOP/s at this intensity. Degenerate inputs (no flops, no
    bytes, or unconfigured peaks) classify as "unknown" with a 0 floor.
    """
    flops = max(0.0, float(flops))
    hbm_bytes = max(0.0, float(hbm_bytes))
    compute_s = flops / peak_flops if peak_flops > 0 else 0.0
    memory_s = hbm_bytes / peak_bw if peak_bw > 0 else 0.0
    floor_s = max(compute_s, memory_s)
    intensity = flops / hbm_bytes if hbm_bytes > 0 else float("inf")
    ridge = peak_flops / peak_bw if peak_bw > 0 else float("inf")
    if floor_s <= 0.0:
        bound = "unknown"
    elif compute_s >= memory_s:
        bound = "compute"
    else:
        bound = "bandwidth"
    attainable = (
        min(peak_flops, intensity * peak_bw)
        if hbm_bytes > 0
        else peak_flops
    )
    return {
        "flops": flops,
        "hbm_bytes": hbm_bytes,
        "intensity_flops_per_byte": intensity,
        "ridge_flops_per_byte": ridge,
        "bound": bound,
        "compute_floor_s": compute_s,
        "memory_floor_s": memory_s,
        "floor_s": floor_s,
        "attainable_flops_per_sec": attainable,
    }


class RooflineModel:
    """Joins the program ledger's per-program bytes/FLOPs with the chip
    peaks and the TSDB's measured step time (see module doc).

    ``fallback_flops_fn(record) -> float`` supplies analytic FLOPs for
    programs whose ``cost_analysis`` read 0; the engine passes a closure
    over its decode-FLOPs model. ``window_s`` is the trailing window the
    achieved-fraction gauge averages measured step time over.

    The registered gauges are read inside every per-step TSDB sample, so
    they serve from a ``cache_ttl_s`` cache of the ledger sweep (the
    program mix changes on compile events, not per step); :meth:`report`
    always recomputes exactly.
    """

    def __init__(
        self,
        ledger,
        timeseries=None,
        *,
        device=None,
        peak_flops: Optional[float] = None,
        peak_bw: Optional[float] = None,
        fallback_flops_fn: Optional[Callable[[object], float]] = None,
        window_s: float = 60.0,
        cache_ttl_s: float = 2.0,
    ):
        self.ledger = ledger
        self.timeseries = timeseries
        self.peak_flops = (
            float(peak_flops)
            if peak_flops is not None
            else peak_flops_per_chip(device)
        )
        self.peak_bw = (
            float(peak_bw)
            if peak_bw is not None
            else hbm_bandwidth_per_chip(device)
        )
        self.device_kind = getattr(device, "device_kind", "unknown")
        self.fallback_flops_fn = fallback_flops_fn
        self.window_s = float(window_s)
        self.cache_ttl_s = float(cache_ttl_s)
        self._gauge_cache: Optional[dict] = None
        self._gauge_cache_t = 0.0

    # ------------------------------------------------------------- analysis

    def _program_flops(self, record) -> float:
        if record.flops > 0.0:
            return float(record.flops)
        if self.fallback_flops_fn is not None:
            try:
                return max(0.0, float(self.fallback_flops_fn(record)))
            except Exception:
                return 0.0
        return 0.0

    def program_rows(self) -> List[dict]:
        """One roofline row per ledgered (program, signature), call-count
        weighted ordering (hottest first)."""
        rows = []
        for record in self.ledger.programs.values():
            hbm_bytes = (
                record.argument_bytes
                + record.output_bytes
                + record.temp_bytes
            )
            point = roofline_point(
                self._program_flops(record),
                hbm_bytes,
                self.peak_flops,
                self.peak_bw,
            )
            point["name"] = record.name
            point["calls"] = record.calls
            point["flops_source"] = (
                "cost_analysis" if record.flops > 0.0 else "analytic"
            )
            point["fused_kernel"] = record.name.startswith(
                FUSED_PROGRAM_PREFIXES
            )
            rows.append(point)
        rows.sort(key=lambda r: -r["calls"])
        return rows

    def step_floor_s(self) -> float:
        """Roofline-implied floor for ONE engine step: the per-call floor
        of every program, weighted by its share of calls (programs ride
        different step shapes, so the call-weighted mix approximates the
        steady-state step). Zero until something is ledgered."""
        rows = self.program_rows()
        total_calls = sum(r["calls"] for r in rows)
        if total_calls <= 0:
            return 0.0
        return sum(r["floor_s"] * r["calls"] for r in rows) / total_calls

    def measured_step_s(self) -> Optional[float]:
        """Trailing-window mean of the TSDB's measured step wall time."""
        if self.timeseries is None:
            return None
        return self.timeseries.avg_over_time(
            "step_wall_seconds", self.window_s
        )

    def achieved_fraction(self) -> float:
        """floor / measured ∈ (0, 1]: 1.0 means the step runs AT the
        roofline (the hardware can go no faster for this program mix);
        0.0 until both a floor and a measurement exist."""
        floor = self.step_floor_s()
        measured = self.measured_step_s()
        if not floor or not measured or measured <= 0.0:
            return 0.0
        return min(1.0, floor / measured)

    def dominant_bound(self) -> str:
        """Bound classification of the step mix: whichever side claims
        the larger call-weighted share of the floor."""
        rows = self.program_rows()
        compute = sum(r["compute_floor_s"] * r["calls"] for r in rows)
        memory = sum(r["memory_floor_s"] * r["calls"] for r in rows)
        if compute <= 0.0 and memory <= 0.0:
            return "unknown"
        return "compute" if compute >= memory else "bandwidth"

    # ------------------------------------------------------------ reporting

    def report(self) -> dict:
        """The ``/statusz`` roofline block."""
        return {
            "device_kind": self.device_kind,
            "peak_flops_per_sec": self.peak_flops,
            "peak_hbm_bytes_per_sec": self.peak_bw,
            "ridge_flops_per_byte": (
                self.peak_flops / self.peak_bw if self.peak_bw else 0.0
            ),
            "step_floor_s": self.step_floor_s(),
            "measured_step_s": self.measured_step_s(),
            "achieved_fraction": self.achieved_fraction(),
            "dominant_bound": self.dominant_bound(),
            "programs": self.program_rows(),
        }

    def _cached_sweep(self) -> dict:
        """Ledger sweep (floor + bound) behind a TTL — the gauges below
        run inside every per-step registry snapshot, and the program mix
        only changes on compile events."""
        now = time.monotonic()
        if (
            self._gauge_cache is None
            or now - self._gauge_cache_t >= self.cache_ttl_s
        ):
            self._gauge_cache = {
                "step_floor_s": self.step_floor_s(),
                "bandwidth_bound": float(
                    self.dominant_bound() == "bandwidth"
                ),
            }
            self._gauge_cache_t = now
        return self._gauge_cache

    def register_into(self, registry) -> None:
        def achieved() -> float:
            floor = self._cached_sweep()["step_floor_s"]
            measured = self.measured_step_s()
            if not floor or not measured or measured <= 0.0:
                return 0.0
            return min(1.0, floor / measured)

        registry.gauge_fn(
            "roofline_achieved_fraction",
            achieved,
            help="Roofline step-time floor over measured step time",
        )
        registry.gauge_fn(
            "roofline_step_floor_seconds",
            lambda: self._cached_sweep()["step_floor_s"],
            help="Call-weighted roofline-implied step-time floor",
        )
        registry.gauge_fn(
            "roofline_bandwidth_bound",
            lambda: self._cached_sweep()["bandwidth_bound"],
            help="1 when the step mix is HBM-bandwidth-bound",
        )


__all__ = [
    "HBM_BYTES_PER_SEC",
    "DEFAULT_HBM_BW",
    "FUSED_PROGRAM_PREFIXES",
    "hbm_bandwidth_per_chip",
    "roofline_point",
    "RooflineModel",
]
