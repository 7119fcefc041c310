"""Online perf-regression detection: notice the slowdown, name the phase.

The TSDB remembers how step time evolved; this module watches it evolve
and fires when the level SHIFTS. A stray slow step is noise (GC pause,
OS jitter); a sustained shift — a recompile settling on a worse layout,
a stuck DMA path, a chaos ``slow_program`` stall — is an incident, and
the operator's first question is always "which phase got slow?".

The hard part of watching a SERVING engine is that step wall time moves
with load: a step decoding 8 rows is legitimately slower than one
decoding 2, and an open-loop arrival ramp shifts the level for entirely
healthy reasons. So the detector STRATIFIES: observations are keyed by
step composition (the decode-row count, pure-decode steps only — steps
that ran prefill are skipped, their cost depends on chunk length), and
each stratum carries its own baseline and CUSUM. A load change merely
moves traffic between strata; a PROGRAM-level slowdown — the thing worth
paging about — shifts every stratum it touches and fires inside the
first one that accumulates enough evidence.

Detector: per (stratum, series), a windowed one-sided CUSUM over an EWMA
baseline. Each tick is O(watched + phases) — same discipline as
``slo.py``'s sliding windows; an O(history) rescan per step is exactly
the observability tax this stack refuses to pay:

* each stratum's baseline initializes ROBUSTLY — median and MAD of its
  first ``min_samples`` observations — so a compile spike landing inside
  the window cannot anchor "normal" orders of magnitude too high; after
  warm-up, mean/variance track by slow EWMA (``baseline_alpha``) to
  self-calibrate to each deployment's jitter;
* the CUSUM statistic accumulates exceedance above a drift allowance of
  ``k`` baseline sigmas, WINSORIZED at ``clip`` sigmas per tick and
  LEAKY at rate ``leak``:
  ``S <- max(0, leak*S + min(x - mean - k*scale, clip*scale))``, firing
  when ``S > h*scale`` — the classic page-level change-point rule, with
  the clip chosen below the threshold so ONE arbitrarily large spike (a
  mid-run recompile) cannot fire alone, a sustained large shift crossing
  within ``ceil(h/clip)`` steps (2 at the defaults), and the leak
  keeping barely-over-allowance trickles (decode cost creeping with KV
  length) from accumulating to a page over a long run;
* while S is rising the baseline FREEZES (updating it with regressed
  samples would teach the detector that slow is normal and mask the
  shift).

Firing fans out like every alert in this stack: a registry counter
bumps, the flight recorder keeps a ``perf_regression`` event, and the
tracer drops an instant so the waterfall shows WHEN the shift landed.
Attribution: at fire time the detector compares every per-phase series'
fast-window mean IN THE FIRING STRATUM against its own frozen baseline
and blames the phase with the largest absolute level shift — for a
chaos ``slow_program`` stall of phase P, that is P by construction,
which is what the seeded drill in ``tools/serving_smoke.sh perfwatch``
asserts.

After firing, the detector re-baselines the firing stratum onto the new
level (the shift is now "normal"; a second regression on top should
fire again) and latches a firing gauge until :meth:`acknowledge`.
"""

from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Tuple


class _CusumSeries:
    """O(1)/tick one-sided CUSUM with a robust warm-up and EWMA baseline
    for one series (amortized: the warm-up's one median costs
    O(min_samples log min_samples), once)."""

    __slots__ = (
        "name", "mean", "var", "cusum", "n", "alpha", "k", "h", "clip",
        "leak", "rel_floor", "min_samples", "_warmup",
    )

    def __init__(
        self, name: str, *, alpha: float, k: float, h: float,
        clip: float, leak: float, rel_floor: float, min_samples: int,
    ):
        self.name = name
        self.mean = 0.0
        self.var = 0.0
        self.cusum = 0.0
        self.n = 0
        self.alpha = alpha
        self.k = k
        self.h = h
        self.clip = clip
        self.leak = leak
        self.rel_floor = rel_floor
        self.min_samples = max(2, min_samples)
        self._warmup: Optional[List[float]] = []

    def std(self) -> float:
        return math.sqrt(max(self.var, 0.0))

    def _scale(self) -> float:
        # Floor the scale at ``rel_floor`` of the mean: near-zero-jitter
        # warm-ups (synthetic clocks, idle phases) can't make every sample
        # look like an infinite-sigma shift, and sub-floor wiggle around
        # the mean — KV growth across a generation, allocator jitter — is
        # serving weather, not an incident (chronic slow drift is the SLO
        # monitor's beat; this detector hunts level SHIFTS).
        return max(self.std(), 1e-9, self.rel_floor * abs(self.mean))

    def push(self, x: float) -> bool:
        """Feed one sample; True when the CUSUM crosses the threshold."""
        x = float(x)
        self.n += 1
        if self._warmup is not None:
            # Warm-up: collect, then anchor the baseline on median/MAD —
            # robust to compile-dominated steps, which run orders of
            # magnitude over steady state.
            self._warmup.append(x)
            if len(self._warmup) >= self.min_samples:
                vals = sorted(self._warmup)
                med = vals[len(vals) // 2]
                mad = sorted(abs(v - med) for v in vals)[len(vals) // 2]
                self.mean = med
                self.var = (1.4826 * mad) ** 2  # MAD -> sigma, normal
                self._warmup = None
            return False
        scale = self._scale()
        # Winsorize the per-tick increment: one arbitrarily large spike
        # (a mid-run recompile) contributes at most clip*scale < h*scale,
        # so firing needs a SUSTAINED shift. The statistic also LEAKS
        # (``S <- leak*S`` before each increment): a marginal trickle of
        # exceedance saturates at ``inc/(1-leak)`` instead of growing
        # without bound, so only shifts whose per-tick exceedance tops
        # ``(1-leak)*h*scale`` can ever cross — a big shift clips through
        # in ``ceil(h/clip)`` ticks, barely-over-allowance drift never
        # does.
        exceed = min(x - self.mean - self.k * scale, self.clip * scale)
        self.cusum = max(0.0, self.leak * self.cusum + exceed)
        if self.cusum > self.h * scale:
            return True
        if exceed < 0.0:
            # Only track the baseline while the statistic is quiet — a
            # rising S means the level may have shifted; freezing keeps
            # the regressed samples out of "normal".
            delta = x - self.mean
            self.mean += self.alpha * delta
            self.var = (1 - self.alpha) * (self.var + self.alpha * delta**2)
        return False

    def rebaseline(self, x: float) -> None:
        """Adopt the current level as the new normal (post-fire); no
        re-warm-up — the detector is live and the level is known."""
        self.mean = x
        self.var = 0.0
        self.cusum = 0.0
        self._warmup = None

    def state(self) -> dict:
        return {
            "mean": self.mean,
            "std": self.std(),
            "cusum": self.cusum,
            "samples": self.n,
            "warming_up": self._warmup is not None,
        }


class RegressionDetector:
    """Watches step-time + TPOT per decode-row stratum (and the
    per-phase series for blame).

    Tunables: ``k`` (drift allowance, baseline sigmas — shifts smaller
    than this never accumulate), ``h`` (decision threshold, sigmas of
    accumulated exceedance), ``clip`` (per-tick increment cap, sigmas;
    keep ``clip < h`` or a single spike can fire), ``leak`` (CUSUM decay
    per tick — bounds what barely-over-allowance drift can accumulate,
    see :class:`_CusumSeries`), ``rel_floor`` (scale floor as a fraction
    of the baseline mean: shifts below ~``rel_floor`` of the level are
    below this detector's beat — KV growth across a generation moves
    step time that much legitimately; chronic percent-scale degradation
    belongs to the SLO monitor), ``min_samples`` (per-stratum median/MAD
    warm-up length before any alarm), ``baseline_alpha`` (EWMA rate;
    smaller = steadier baseline), ``phase_alpha`` (fast-window EWMA used
    only for attribution).
    ``max_strata`` bounds memory: beyond that many distinct decode-row
    counts, new compositions are ignored (each stratum is a handful of
    ~100-byte series objects; a serving engine has at most ``max_slots``
    strata, so the cap is a safety net, not a working limit). Defaults
    catch a sustained ~2x step-time shift within ~2-4 post-shift steps
    at steady batch while staying quiet through CPU-backend jitter,
    isolated mid-run compile spikes, AND open-loop load ramps — the
    seeded-drill budget asserted in tests and
    ``tools/serving_smoke.sh perfwatch``.
    """

    WATCHED = ("step_wall_seconds", "tpot_step_seconds")

    def __init__(
        self,
        *,
        k: float = 1.0,
        h: float = 4.0,
        clip: float = 3.0,
        leak: float = 0.9,
        rel_floor: float = 0.25,
        min_samples: int = 8,
        baseline_alpha: float = 0.05,
        phase_alpha: float = 0.3,
        max_strata: int = 64,
        flight=None,
        tracer=None,
    ):
        self.flight = flight
        self.tracer = tracer
        self.max_strata = max_strata
        self._mk = lambda name: _CusumSeries(
            name, alpha=baseline_alpha, k=k, h=h, clip=clip, leak=leak,
            rel_floor=rel_floor, min_samples=min_samples,
        )
        # Keyed by (decode_rows, series name) / (decode_rows, phase).
        self._watch: Dict[Tuple[int, str], _CusumSeries] = {}
        self._phase_base: Dict[Tuple[int, str], _CusumSeries] = {}
        self._phase_fast: Dict[Tuple[int, str], float] = {}
        self._strata: set = set()
        self.phase_alpha = phase_alpha
        self.steps = 0
        self.skipped_steps = 0
        self.firing = False
        self.alerts = 0
        self.events: List[dict] = []
        self.last_attribution: Optional[str] = None

    # -------------------------------------------------------------- feeding

    def observe(
        self,
        *,
        step_wall_seconds: float,
        tpot_step_seconds: Optional[float] = None,
        decode_rows: int = 0,
        prefill_tokens: int = 0,
        phases: Optional[Dict[str, float]] = None,
    ) -> Optional[dict]:
        """One engine step. Returns the alert event when the detector
        fires this tick, else None. O(watched series + phases).

        Only pure-decode steps are compared (``prefill_tokens == 0``,
        ``decode_rows > 0``): prefill cost scales with chunk length, so
        mixed steps have no stationary level to hold them against. Those
        steps are counted in ``skipped_steps`` — a run that is all
        prefill is a run the detector honestly cannot watch, and the
        counter says so.
        """
        self.steps += 1
        if prefill_tokens > 0 or decode_rows <= 0:
            self.skipped_steps += 1
            return None
        stratum = int(decode_rows)
        if stratum not in self._strata:
            if len(self._strata) >= self.max_strata:
                self.skipped_steps += 1
                return None
            self._strata.add(stratum)
        phases = phases or {}
        for name, dt in phases.items():
            key = (stratum, name)
            base = self._phase_base.get(key)
            if base is None:
                base = self._phase_base[key] = self._mk(
                    f"phase_{name}@rows{stratum}"
                )
                self._phase_fast[key] = float(dt)
            base.push(float(dt))
            fast = self._phase_fast[key]
            self._phase_fast[key] = (
                fast + self.phase_alpha * (float(dt) - fast)
            )

        fired_on = None
        values = {"step_wall_seconds": step_wall_seconds}
        if tpot_step_seconds is not None:
            values["tpot_step_seconds"] = tpot_step_seconds
        for name, value in values.items():
            key = (stratum, name)
            series = self._watch.get(key)
            if series is None:
                series = self._watch[key] = self._mk(
                    f"{name}@rows{stratum}"
                )
            if series.push(value) and fired_on is None:
                fired_on = name
        if fired_on is None:
            return None
        return self._fire(stratum, fired_on, values)

    # -------------------------------------------------------------- firing

    def _attribute(self, stratum: int) -> Optional[str]:
        """Blame the phase whose fast level shifted most above its
        baseline in the FIRING stratum, in absolute seconds (relative
        shifts over-blame microscopic phases whose baseline is near
        zero; other strata saw different load, not this incident)."""
        worst, worst_shift = None, 0.0
        for (rows, name), base in self._phase_base.items():
            if rows != stratum:
                continue
            if base._warmup is not None:
                continue  # no trusted baseline yet — can't blame it
            shift = self._phase_fast[(rows, name)] - base.mean
            if shift > worst_shift:
                worst, worst_shift = name, shift
        return worst

    def _fire(
        self, stratum: int, series: str, values: Dict[str, float]
    ) -> dict:
        self.alerts += 1
        self.firing = True
        phase = self._attribute(stratum)
        self.last_attribution = phase
        watch = self._watch[(stratum, series)]
        event = {
            "t": time.time(),
            "step": self.steps,
            "series": series,
            "decode_rows": stratum,
            # How many comparable samples this stratum had ever seen at
            # fire time — drills subtract the injection-time count to get
            # detection latency in the detector's own information units
            # (skipped prefill steps can't count against it).
            "stratum_samples": watch.n,
            "value": values[series],
            "baseline_mean": watch.mean,
            "baseline_std": watch.std(),
            "attributed_phase": phase,
        }
        self.events.append(event)
        if len(self.events) > 64:
            del self.events[0]
        if self.flight is not None:
            try:
                self.flight.record(
                    "perf_regression",
                    series=series,
                    value=values[series],
                    baseline_mean=event["baseline_mean"],
                    attributed_phase=phase,
                )
            except Exception:
                pass
        if self.tracer is not None and getattr(self.tracer, "enabled", False):
            try:
                self.tracer.instant(
                    "perf_regression", series=series, phase=str(phase)
                )
            except Exception:
                pass
        # The shifted level is the new normal IN THIS STRATUM; re-arm for
        # the NEXT shift. Other strata keep their evidence — a program
        # regression should fire there too, and counts as further alerts.
        for name, value in values.items():
            self._watch[(stratum, name)].rebaseline(value)
        for (rows, name), base in self._phase_base.items():
            if rows == stratum:
                base.rebaseline(self._phase_fast[(rows, name)])
        return event

    def acknowledge(self) -> None:
        """Clear the firing latch (alert count stays — it is monotonic)."""
        self.firing = False

    # ------------------------------------------------------------ reporting

    def state(self) -> dict:
        """The ``/statusz`` block."""
        return {
            "steps": self.steps,
            "skipped_steps": self.skipped_steps,
            "strata": sorted(self._strata),
            "alerts": self.alerts,
            "firing": self.firing,
            "last_attribution": self.last_attribution,
            "watched": {
                s.name: s.state() for s in self._watch.values()
            },
            "phases": {
                base.name: {
                    "baseline_mean": base.mean,
                    "fast_mean": self._phase_fast[key],
                }
                for key, base in self._phase_base.items()
            },
            "events": list(self.events[-8:]),
        }

    def register_into(self, registry) -> None:
        registry.counter_fn(
            "perf_regressions_total",
            lambda: float(self.alerts),
            help="Sustained perf-level shifts detected by CUSUM",
        )
        registry.gauge_fn(
            "perf_regression_firing",
            lambda: float(self.firing),
            help="1 after a perf regression until acknowledged",
        )


__all__ = ["RegressionDetector"]
