"""Admission control + serving metrics.

The engine's front door. Two jobs:

* **Backpressure**: a bounded waiting queue (``QueueFull`` the moment it
  overflows — callers shed load or retry, the engine never buffers
  unboundedly) and an up-front feasibility check (``RequestTooLong`` for
  requests that could never fit the block table even on an empty cache —
  rejecting at submit beats preempt-thrashing forever at runtime). The
  optional ``max_queue_tokens`` budget bounds queued PREFILL WORK rather
  than request count, and counts only uncached tokens: a thousand requests
  sharing a cached system prompt cost their tails, not their full prompts,
  so prefix caching directly raises sustainable admission rate.
* **Latency accounting**: per-request TTFT (submit -> first generated
  token), TPOT (mean inter-token time past the first), and e2e latency,
  recorded into bounded :class:`~distributed_pytorch_tpu.metrics
  .ReservoirHistogram` reservoirs with p50/p95/p99 export, plus exact
  throughput counters. TTFT is additionally split by prefix-cache outcome
  (hit = any prompt tokens served from cache at first admission) via a
  :class:`~distributed_pytorch_tpu.metrics.ReservoirGroup`, the number the
  bench prints to show cache hits shaving prefill out of first-token
  latency.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, Optional

from distributed_pytorch_tpu.metrics import ReservoirGroup, ReservoirHistogram
from distributed_pytorch_tpu.serving.scheduler import Request, SamplingParams


class AdmissionError(RuntimeError):
    """Base class: the request was NOT accepted."""


class QueueFull(AdmissionError):
    """Waiting queue at capacity — backpressure; retry later."""


class RequestTooLong(AdmissionError):
    """prompt + max_new_tokens can never fit the per-sequence block table."""


class EngineDraining(AdmissionError):
    """The engine is draining (or closed) — no new work is accepted.

    Distinct from :class:`QueueFull` on purpose: a full queue means "retry
    here, later"; a draining engine means "retry ELSEWHERE, now" (the
    load balancer should route to a live replica)."""


class AdmissionController:
    """Bounded-queue gate in front of the scheduler."""

    def __init__(
        self,
        *,
        max_queue: int,
        max_request_tokens: int,
        max_queue_tokens: Optional[int] = None,
        recent_rejections_max: int = 32,
    ):
        if max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if recent_rejections_max < 1:
            raise ValueError(
                "recent_rejections_max must be >= 1, got "
                f"{recent_rejections_max}"
            )
        self.max_queue = max_queue
        self.max_request_tokens = max_request_tokens
        self.max_queue_tokens = max_queue_tokens
        self.accepted = 0
        self.rejected_queue_full = 0
        self.rejected_too_long = 0
        self.rejected_draining = 0
        self.cached_tokens_admitted = 0
        # Typed tenancy (promoted out of the opaque ``metadata`` dict):
        # per-tenant admission counts, the billing-grade view of who is
        # actually getting through the gate.
        self.accepted_by_tenant: Dict[str, int] = {}
        self.draining = False
        # Last few rejections, keyed by the fleet-wide trace_id when the
        # caller supplied one: a request that never got past this gate has
        # no spans anywhere, so this ring is the only place ``/requestz``
        # can point at to explain a missing trace. Bounded at
        # ``recent_rejections_max`` entries (each a small dict — tens of
        # bytes), so a rejection storm costs O(recent_rejections_max)
        # memory, never O(rejections); the same eviction contract as the
        # trace sampler's ``max_kept``.
        self.recent_rejections: "collections.deque[dict]" = (
            collections.deque(maxlen=recent_rejections_max)
        )

    def close(self) -> None:
        """Stop admitting — first act of the drain protocol (and of engine
        close). Idempotent."""
        self.draining = True

    def reopen(self) -> None:
        self.draining = False

    def check(
        self,
        prompt_len: int,
        params: SamplingParams,
        queue_len: int,
        *,
        cached_tokens: int = 0,
        queued_uncached_tokens: int = 0,
        tenant_id: str = "anon",
        trace_id: Optional[str] = None,
    ) -> None:
        """Raise an :class:`AdmissionError` subclass iff the request must be
        rejected; otherwise count it accepted. ``cached_tokens`` is the
        prefix-cache match for this prompt at submit time;
        ``queued_uncached_tokens`` the uncached prefill work already
        waiting — both feed the optional queue-token budget.
        ``tenant_id`` keys the per-tenant accepted counter (fair-share
        policy itself lives a layer up, in the front door); ``trace_id``
        stamps rejections into :attr:`recent_rejections` so a trace that
        never produced a span is still explainable."""
        if self.draining:
            self.rejected_draining += 1
            raise self._reject(
                EngineDraining(
                    "engine is draining; no new requests accepted"
                ),
                "draining", tenant_id, trace_id,
            )
        if prompt_len < 1:
            self.rejected_too_long += 1
            raise self._reject(
                RequestTooLong(
                    "empty prompt: generation is conditioned on at least "
                    "one token (offline generate() has the same contract "
                    "— a zero-length row's position 0 is never decided)"
                ),
                "too_long", tenant_id, trace_id,
            )
        total = prompt_len + params.max_new_tokens
        if total > self.max_request_tokens:
            self.rejected_too_long += 1
            raise self._reject(
                RequestTooLong(
                    f"prompt ({prompt_len}) + max_new_tokens "
                    f"({params.max_new_tokens}) = {total} exceeds the "
                    f"per-sequence cache capacity {self.max_request_tokens}"
                ),
                "too_long", tenant_id, trace_id,
            )
        if queue_len >= self.max_queue:
            self.rejected_queue_full += 1
            raise self._reject(
                QueueFull(
                    f"waiting queue at capacity ({self.max_queue}); "
                    "retry later"
                ),
                "queue_full", tenant_id, trace_id,
            )
        if self.max_queue_tokens is not None:
            incoming = max(0, prompt_len - 1 - cached_tokens)
            if queued_uncached_tokens + incoming > self.max_queue_tokens:
                self.rejected_queue_full += 1
                raise self._reject(
                    QueueFull(
                        f"queued uncached prefill work "
                        f"({queued_uncached_tokens} + {incoming} tokens) "
                        f"exceeds budget {self.max_queue_tokens}; retry "
                        "later"
                    ),
                    "queue_full", tenant_id, trace_id,
                )
        self.accepted += 1
        self.cached_tokens_admitted += cached_tokens
        self.accepted_by_tenant[tenant_id] = (
            self.accepted_by_tenant.get(tenant_id, 0) + 1
        )

    def _reject(
        self,
        exc: AdmissionError,
        reason: str,
        tenant_id: str,
        trace_id: Optional[str],
    ) -> AdmissionError:
        self.recent_rejections.append(
            {
                "reason": reason,
                "tenant_id": tenant_id,
                "trace_id": trace_id,
                "detail": str(exc),
            }
        )
        return exc

    def status(self) -> Dict[str, object]:
        """The ``/statusz`` admission block: every rejection counter plus
        the live draining flag (``/healthz`` derives its verdict from the
        same flag) and the recent-rejection ring (trace_id-stamped, so a
        trace that died at the gate is still accounted for)."""
        out: Dict[str, object] = dict(self.counters())
        out["draining"] = self.draining
        out["recent_rejections"] = list(self.recent_rejections)
        return out

    def counters(self) -> Dict[str, int]:
        return {
            "accepted": self.accepted,
            "rejected_queue_full": self.rejected_queue_full,
            "rejected_too_long": self.rejected_too_long,
            "rejected_draining": self.rejected_draining,
            "cached_tokens_admitted": self.cached_tokens_admitted,
        }

    def register_into(self, registry) -> None:
        """Expose the admission counters through a
        :class:`~distributed_pytorch_tpu.obs.MetricsRegistry`."""
        registry.counter_fn("admission_accepted_total", lambda: self.accepted)
        registry.counter_fn(
            "admission_rejected_queue_full_total",
            lambda: self.rejected_queue_full,
        )
        registry.counter_fn(
            "admission_rejected_too_long_total",
            lambda: self.rejected_too_long,
        )
        registry.counter_fn(
            "admission_rejected_draining_total",
            lambda: self.rejected_draining,
        )
        registry.counter_fn(
            "cached_tokens_admitted_total",
            lambda: self.cached_tokens_admitted,
        )


class ServingMetrics:
    """TTFT / TPOT / e2e reservoirs + exact throughput counters.

    ``speculative=True`` labels this engine's TPOT samples "spec" in the
    mode split (so a spec-on and a spec-off run over the same workload can
    be compared reservoir-to-reservoir) and is the mode whose verify
    rounds feed :meth:`observe_verify` — per-round acceptance fraction and
    emitted-token reservoirs plus exact proposed/accepted counters, the
    numbers that say whether the draft is earning its keep."""

    def __init__(
        self, reservoir_capacity: int = 1024, speculative: bool = False
    ):
        self.speculative = speculative
        self.ttft = ReservoirHistogram(reservoir_capacity, seed=1)
        self.tpot = ReservoirHistogram(reservoir_capacity, seed=2)
        self.e2e = ReservoirHistogram(reservoir_capacity, seed=3)
        # TTFT by prefix-cache outcome at the request's FIRST admission:
        # "hit" iff any prompt tokens came from device-resident trie
        # pages, else "host" iff any were staged up from the host page
        # tier, else "miss". Device wins ties — a request served by both
        # tiers already had the cheaper device hit.
        self.ttft_by_source = ReservoirGroup(
            ("hit", "host", "miss"), reservoir_capacity, seed=4
        )
        # Speculative-verify quality: per-round acceptance fraction (of
        # gamma proposals) and tokens emitted per verify (1..gamma).
        self.spec = ReservoirGroup(
            ("acceptance_rate", "tokens_per_verify"),
            reservoir_capacity,
            seed=10,
        )
        self.tpot_by_mode = ReservoirGroup(
            ("spec", "plain"), reservoir_capacity, seed=20
        )
        self.verify_rounds = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.tokens_generated = 0
        self.requests_completed = 0
        self.engine_steps = 0
        self._start = time.perf_counter()

    def observe_step(self, new_tokens: int) -> None:
        self.engine_steps += 1
        self.tokens_generated += new_tokens

    def observe_verify(
        self, accepted: int, emitted: int, gamma: int
    ) -> None:
        """One speculative verify round: ``accepted`` of ``gamma`` draft
        proposals survived, ``emitted`` tokens entered the sequence
        (accepted + the correction, capped at gamma)."""
        self.verify_rounds += 1
        self.draft_proposed += gamma
        self.draft_accepted += accepted
        self.spec.record("acceptance_rate", accepted / gamma)
        self.spec.record("tokens_per_verify", float(emitted))

    def observe_finished(self, req: Request) -> None:
        self.requests_completed += 1
        if req.first_token_time is not None:
            ttft = req.first_token_time - req.submit_time
            self.ttft.record(ttft)
            if (req.cached_prompt_tokens or 0) > 0:
                source = "hit"
            elif (req.host_prompt_tokens or 0) > 0:
                source = "host"
            else:
                source = "miss"
            self.ttft_by_source.record(source, ttft)
            if req.finish_time is not None:
                self.e2e.record(req.finish_time - req.submit_time)
                if req.n_generated > 1:
                    tpot = (
                        req.finish_time - req.first_token_time
                    ) / (req.n_generated - 1)
                    self.tpot.record(tpot)
                    self.tpot_by_mode.record(
                        "spec" if self.speculative else "plain", tpot
                    )

    @staticmethod
    def register_into(registry, get) -> None:
        """Register the serving counters and latency reservoirs into a
        :class:`~distributed_pytorch_tpu.obs.MetricsRegistry`. ``get`` is a
        zero-arg callable returning the CURRENT metrics object — the bench
        replaces ``engine.metrics`` wholesale after warm-up, so every
        resolver goes through ``get()`` at snapshot time rather than
        capturing one instance."""
        registry.counter_fn("engine_steps_total", lambda: get().engine_steps)
        registry.counter_fn(
            "tokens_generated_total", lambda: get().tokens_generated
        )
        registry.counter_fn(
            "requests_completed_total", lambda: get().requests_completed
        )
        registry.counter_fn(
            "verify_rounds_total", lambda: get().verify_rounds
        )
        registry.counter_fn(
            "draft_tokens_proposed_total", lambda: get().draft_proposed
        )
        registry.counter_fn(
            "draft_tokens_accepted_total", lambda: get().draft_accepted
        )
        registry.gauge_fn(
            "uptime_seconds", lambda: time.perf_counter() - get()._start
        )
        registry.gauge_fn(
            "tokens_per_sec",
            lambda: get().snapshot()["tokens_per_sec"],
        )
        registry.reservoir("ttft_seconds", lambda: get().ttft)
        registry.reservoir("tpot_seconds", lambda: get().tpot)
        registry.reservoir("e2e_seconds", lambda: get().e2e)
        registry.reservoir(
            "ttft_seconds_by_source",
            lambda: get().ttft_by_source,
            label="source",
        )
        registry.reservoir(
            "tpot_seconds_by_mode", lambda: get().tpot_by_mode, label="mode"
        )
        registry.reservoir(
            "spec_per_verify", lambda: get().spec, label="stat"
        )

    def snapshot(self) -> Dict[str, float]:
        """One flat dict: counters + tokens/s + per-metric percentiles —
        the payload the smoke test asserts non-empty."""
        elapsed = time.perf_counter() - self._start
        out: Dict[str, float] = {
            "engine_steps": self.engine_steps,
            "tokens_generated": self.tokens_generated,
            "requests_completed": self.requests_completed,
            "elapsed_s": elapsed,
            "tokens_per_sec": (
                self.tokens_generated / elapsed if elapsed > 0 else 0.0
            ),
        }
        out.update(self.ttft.summary("ttft_s_"))
        out.update(self.ttft_by_source.summary("ttft_s_"))
        out.update(self.tpot.summary("tpot_s_"))
        out.update(self.tpot_by_mode.summary("tpot_s_"))
        out.update(self.e2e.summary("e2e_s_"))
        if self.speculative or self.verify_rounds:
            out["verify_rounds"] = self.verify_rounds
            out["draft_tokens_proposed"] = self.draft_proposed
            out["draft_tokens_accepted"] = self.draft_accepted
            out["spec_acceptance_rate"] = (
                self.draft_accepted / self.draft_proposed
                if self.draft_proposed
                else 0.0
            )
            out.update(self.spec.summary("spec_"))
        return out
