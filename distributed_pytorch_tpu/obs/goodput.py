"""Goodput / MFU accounting: where the wall-clock actually goes.

Two halves, one module:

* **The FLOPs model** — the package's one analytic formula for model
  FLOPs. Training cost is the PaLM-style
  ``3 * (2 * non-embedding-params * tokens + attention)`` with exact
  causal (and sliding-window) attention terms; decode cost is the
  forward-only per-token marginal at a given KV context length.

* **:class:`GoodputTracker`** — decomposes engine wall-clock, step by
  step, into *productive* time and named waste buckets
  (:data:`WASTE_KINDS`): speculative tokens the verifier rejected,
  re-prefill of KV lost to preemption, re-prefill after a
  snapshot/restore, token-budget under-utilization while requests queue,
  and in-process drain downtime. Attribution is proportional: a step's
  non-idle time splits over its work units (prefill tokens + decode
  positions), so a step that proposed 4 speculative tokens and kept 1
  charges 3 units of its span to ``spec_rejected``. From the same feed it
  derives tokens/sec/device and MFU (emitted tokens x decode
  FLOPs-per-token over elapsed x peak FLOPs), surfaced in
  ``registry.snapshot()`` and per-step tracer gauges.

Everything here is host-side float arithmetic on numbers the engine
already has — no device work, no extra syncs.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import numpy as np

# ----------------------------------------------------------------- peak FLOPs

# Peak bf16 FLOP/s per chip by generation (public spec sheets). Used as the
# MFU denominator. A TPU whose kind matches no entry is an error; the CPU
# and kind-less test devices get DEFAULT_PEAK (the goodput accounting tests
# run there and need a denominator, not a meaningful one).
PEAK_BF16_FLOPS = {
    "v6": 918e12,
    "v5p": 459e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}
DEFAULT_PEAK = 197e12


def peak_for_device(table: Dict[str, float], device, default: float) -> float:
    """``table``'s entry whose key is a substring of ``device.device_kind``.
    An unknown kind raises on a TPU (a chip measured against another
    chip's peak is a wrong number, not a conservative one) and returns
    ``default`` anywhere else."""
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in table.items():
        if key in kind:
            return peak
    if getattr(device, "platform", "") == "tpu" or kind.startswith("tpu"):
        raise ValueError(
            f"no peak recorded for TPU device kind "
            f"{getattr(device, 'device_kind', '')!r}; add it to the table "
            f"(known: {sorted(table)})"
        )
    return default


def peak_flops_per_chip(device) -> float:
    """Peak bf16 FLOP/s for a jax device, by kind substring."""
    return peak_for_device(PEAK_BF16_FLOPS, device, DEFAULT_PEAK)


# ---------------------------------------------------------------- FLOPs model

# ResNet-50 forward cost at 224x224 (the standard ~4.09 GFLOPs figure);
# training steps cost ~3x forward (fwd + 2x bwd).
RESNET50_FWD_FLOPS_PER_IMAGE = 4.09e9


def resnet50_train_flops(batch: int) -> float:
    """Analytic FLOPs for one ResNet-50 training step at 224x224."""
    return 3.0 * RESNET50_FWD_FLOPS_PER_IMAGE * batch


def causal_attention_flops(
    *,
    n_layers: int,
    n_heads: int,
    head_dim: int,
    seq_len: int,
    batch: int,
    window: Optional[int] = None,
) -> float:
    """Forward FLOPs of the attention score+value matmuls, exact for the
    causal mask: query position i attends to ``min(i+1, window)`` keys.
    The factor 4 is 2 matmuls (QK^T and PV) x 2 FLOPs per MAC."""
    if window:
        w = int(window)
        if seq_len <= w:
            per_q = seq_len * (seq_len + 1) / 2
        else:
            per_q = w * (w + 1) / 2 + (seq_len - w) * w
    else:
        per_q = seq_len**2 / 2
    return n_layers * 4.0 * batch * n_heads * per_q * head_dim


def transformer_train_flops(
    *,
    n_params: int,
    embed_params: int,
    n_layers: int,
    n_heads: int,
    head_dim: int,
    seq_len: int,
    batch: int,
    window: Optional[int] = None,
) -> float:
    """Analytic FLOPs for one transformer LM training step: PaLM-style
    ``6 * non-embedding-params * tokens`` (2 per MAC, x3 for fwd+bwd) plus
    the exact causal attention term, also x3."""
    tokens = batch * seq_len
    attn_fwd = causal_attention_flops(
        n_layers=n_layers,
        n_heads=n_heads,
        head_dim=head_dim,
        seq_len=seq_len,
        batch=batch,
        window=window,
    )
    return 3.0 * (2.0 * (n_params - embed_params) * tokens + attn_fwd)


def transformer_decode_flops_per_token(
    *,
    n_params: int,
    embed_params: int,
    n_layers: int,
    n_heads: int,
    head_dim: int,
    context_len: int,
) -> float:
    """Forward-only marginal cost of decoding one token against a KV cache
    of ``context_len`` positions: ``2 * non-embedding-params`` for the
    matmuls plus the attention read over the cache."""
    attn = 4.0 * n_layers * n_heads * head_dim * context_len
    return 2.0 * float(n_params - embed_params) + attn


def count_params(params) -> int:
    """Total scalar count of a jax pytree of arrays (host-side)."""
    import jax

    return sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
    )


# ------------------------------------------------------------------- tracker

WASTE_KINDS = (
    "spec_rejected",
    "preempt_rework",
    "restore_reprefill",
    "budget_idle",
    "drain_downtime",
)


class GoodputTracker:
    """Per-step wall-clock decomposition into productive vs wasted time.

    Feed it one :meth:`note_step` per engine step. The step's span splits:

    * ``budget_idle`` — the fraction of the token budget left unused while
      requests were queued (a full budget or an empty queue charges zero);
    * the remainder splits proportionally over the step's work units
      (prefill tokens + decode positions): units re-computing KV the
      engine already had go to ``preempt_rework`` / ``restore_reprefill``,
      speculative positions the verifier rejected go to ``spec_rejected``,
      and the rest is productive.

    ``note_drain`` / ``note_restore`` bracket in-process drain downtime
    (a restore in a fresh process has no visible gap to measure).
    """

    def __init__(
        self,
        *,
        flops_per_token: float = 0.0,
        peak_flops_per_device: float = 0.0,
        n_devices: int = 1,
        clock: Callable[[], float] = time.perf_counter,
    ):
        self.flops_per_token = float(flops_per_token)
        self.peak_flops_per_device = float(peak_flops_per_device)
        self.n_devices = max(1, int(n_devices))
        self._clock = clock
        self.reset()

    def reset(self) -> None:
        """Zero all accumulators (bench warm-up boundary)."""
        self.productive_s = 0.0
        self.wasted: Dict[str, float] = {k: 0.0 for k in WASTE_KINDS}
        self.steps = 0
        self.tokens = 0
        self._drain_t0: Optional[float] = None

    # ------------------------------------------------------------ feeding

    def note_step(
        self,
        dt_s: float,
        *,
        prefill_tokens: int = 0,
        decode_positions: int = 0,
        emitted_tokens: int = 0,
        spec_proposed: int = 0,
        rework: Optional[Dict[str, int]] = None,
        budget_used: int = 0,
        token_budget: int = 0,
        queue_depth: int = 0,
    ) -> None:
        """Attribute one engine step's wall-clock span.

        ``rework`` maps waste kind -> prefill tokens re-computing KV the
        engine had before a preemption or snapshot (a subset of
        ``prefill_tokens``). ``spec_proposed`` is the total speculative
        positions verified this step; ``emitted_tokens`` the tokens kept.
        """
        dt_s = max(0.0, float(dt_s))
        self.steps += 1
        self.tokens += int(emitted_tokens)

        idle_s = 0.0
        if token_budget > 0 and queue_depth > 0:
            fill = min(1.0, budget_used / token_budget)
            idle_s = dt_s * (1.0 - fill)
            self.wasted["budget_idle"] += idle_s

        span = dt_s - idle_s
        units = int(prefill_tokens) + int(decode_positions)
        if units <= 0:
            self.productive_s += span
            return
        per_unit = span / units

        wasted_units = 0
        if rework:
            for kind, n_tokens in rework.items():
                n = min(int(n_tokens), units - wasted_units)
                if n <= 0:
                    continue
                self.wasted[kind] += n * per_unit
                wasted_units += n
        rejected = max(0, int(spec_proposed) - int(emitted_tokens))
        rejected = min(rejected, units - wasted_units)
        if rejected > 0:
            self.wasted["spec_rejected"] += rejected * per_unit
            wasted_units += rejected

        self.productive_s += (units - wasted_units) * per_unit

    def note_drain(self) -> None:
        """Mark the start of an in-process drain (downtime clock starts)."""
        self._drain_t0 = self._clock()

    def note_restore(self) -> None:
        """Close the drain-downtime window opened by :meth:`note_drain`;
        a restore into a fresh process (no matching drain) is a no-op."""
        if self._drain_t0 is not None:
            self.wasted["drain_downtime"] += max(
                0.0, self._clock() - self._drain_t0
            )
            self._drain_t0 = None

    # ----------------------------------------------------------- reporting

    def wasted_total_s(self) -> float:
        return sum(self.wasted.values())

    def fraction(self) -> float:
        """Productive share of attributed time; 1.0 before any feed."""
        total = self.productive_s + self.wasted_total_s()
        if total <= 0.0:
            return 1.0
        return self.productive_s / total

    def mfu(self) -> float:
        """Achieved model FLOPs over peak, from emitted tokens x the
        decode FLOPs-per-token model; 0.0 when the model is unconfigured."""
        total = self.productive_s + self.wasted_total_s()
        peak = self.peak_flops_per_device * self.n_devices
        if total <= 0.0 or peak <= 0.0 or self.flops_per_token <= 0.0:
            return 0.0
        return (self.tokens * self.flops_per_token) / (total * peak)

    def tokens_per_sec_per_device(self) -> float:
        total = self.productive_s + self.wasted_total_s()
        if total <= 0.0:
            return 0.0
        return self.tokens / total / self.n_devices

    def report(self) -> dict:
        """Flat dict for bench rows / ``stats()``."""
        return {
            "steps": self.steps,
            "tokens": self.tokens,
            "productive_s": self.productive_s,
            "wasted_s": dict(self.wasted),
            "wasted_total_s": self.wasted_total_s(),
            "goodput_fraction": self.fraction(),
            "tokens_per_sec_per_device": self.tokens_per_sec_per_device(),
            "mfu": self.mfu(),
        }

    def register_into(self, registry) -> None:
        """Expose the accounting through a MetricsRegistry (pull-based, so
        snapshots always see current values)."""
        registry.counter_fn(
            "goodput_productive_seconds_total",
            lambda: self.productive_s,
            help="Wall-clock attributed to productive work",
        )
        for kind in WASTE_KINDS:
            registry.counter_fn(
                f"goodput_wasted_{kind}_seconds_total",
                lambda k=kind: self.wasted[k],
                help=f"Wall-clock wasted on {kind}",
            )
        registry.counter_fn(
            "goodput_wasted_seconds_total",
            self.wasted_total_s,
            help="Total wall-clock attributed to waste",
        )
        registry.gauge_fn(
            "goodput_fraction",
            self.fraction,
            help="Productive share of attributed wall-clock",
        )
        registry.gauge_fn(
            "goodput_tokens_per_sec_per_device",
            self.tokens_per_sec_per_device,
            help="Emitted tokens per second per device",
        )
        registry.gauge_fn(
            "goodput_mfu",
            self.mfu,
            help="Model FLOPs utilization vs peak",
        )
