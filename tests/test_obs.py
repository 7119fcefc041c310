"""Unified observability layer tests: the request-lifecycle Tracer and
engine step timeline (Perfetto trace_event export), the NullTracer
zero-cost-when-disabled contract, and the MetricsRegistry
(counters/gauges/reservoirs, JSON snapshot, Prometheus text exposition,
cross-host merge) — plus the engine integration acceptance criteria:
tracing on/off yields bitwise-identical tokens, per-request span count
equals completed requests, and registry counters equal engine ground truth.
"""

import json
import math
import time

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.metrics import ReservoirGroup, ReservoirHistogram
from distributed_pytorch_tpu.obs import (
    MetricsRegistry,
    NULL_TRACER,
    NullTracer,
    Tracer,
)
from distributed_pytorch_tpu.obs.flight import FlightRecorder
from distributed_pytorch_tpu.obs.tracer import (
    perf_counter_offset_us,
    process_tracer,
)
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams


class FakeClock:
    """Deterministic tracer clock: advances a fixed tick per call."""

    def __init__(self, tick: float = 0.001):
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t


# ------------------------------------------------------------------ tracer


class TestTracer:
    def test_step_slice_records_duration_and_gauges(self):
        tr = Tracer(clock=FakeClock())
        tr.begin_step()
        tr.end_step(queue_depth=3, pages_free=7)
        steps = [e for e in tr.events if e["name"] == "step"]
        assert len(steps) == 1
        (step,) = steps
        assert step["ph"] == "X" and step["dur"] > 0
        assert step["args"]["step"] == 0
        assert step["args"]["queue_depth"] == 3
        counters = [e for e in tr.events if e["ph"] == "C"]
        assert {e["name"] for e in counters} == {"queue_depth", "pages_free"}
        tr.begin_step()
        tr.end_step()
        assert [
            e for e in tr.events if e["name"] == "step"
        ][1]["args"]["step"] == 1

    def test_phase_slices_nest_inside_step(self):
        tr = Tracer(clock=FakeClock())
        tr.begin_step()
        with tr.phase("schedule"):
            pass
        with tr.phase("dispatch"):
            with tr.phase("stage"):
                pass
        tr.end_step()
        phases = {
            e["name"]: e for e in tr.events
            if e["ph"] == "X" and e["name"] != "step"
        }
        assert set(phases) == {"schedule", "dispatch", "stage"}
        assert all(e["args"]["step"] == 0 for e in phases.values())
        # nesting is by time containment: stage inside dispatch
        d, s = phases["dispatch"], phases["stage"]
        assert d["ts"] <= s["ts"]
        assert s["ts"] + s["dur"] <= d["ts"] + d["dur"]

    def test_request_span_lifecycle(self):
        tr = Tracer(clock=FakeClock())
        tr.request_begin(7, prompt_len=5, max_new_tokens=4)
        tr.request_event(7, "admit", slot=0, hit=False, cached_tokens=0)
        tr.request_event(7, "decode_token", n_generated=1)
        tr.request_end(7, n_generated=4, preempt_count=0)
        assert tr.spans_opened == 1 and tr.spans_closed == 1
        phs = [e["ph"] for e in tr.events]
        assert phs == ["b", "n", "n", "e"]
        assert all(e["id"] == 7 for e in tr.events)
        assert all(e["cat"] == "request" for e in tr.events)
        begin = tr.events[0]
        assert begin["args"]["prompt_len"] == 5

    def test_to_perfetto_is_json_with_named_lanes(self):
        tr = Tracer(clock=FakeClock())
        tr.begin_step()
        tr.instant("page_evict", page=3)
        tr.end_step()
        doc = json.loads(json.dumps(tr.to_perfetto()))
        assert "traceEvents" in doc
        names = {
            e["args"]["name"]
            for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"
        }
        assert names == {"engine", "requests"}
        assert any(e.get("ph") == "i" for e in doc["traceEvents"])

    def test_save_writes_loadable_trace(self, tmp_path):
        tr = Tracer(clock=FakeClock())
        tr.begin_step()
        tr.end_step()
        path = tr.save(str(tmp_path / "sub" / "trace.json"))
        with open(path) as f:
            doc = json.load(f)
        assert doc["traceEvents"]

    def test_null_tracer_is_inert(self):
        assert NULL_TRACER.enabled is False
        assert isinstance(NULL_TRACER, NullTracer)
        NULL_TRACER.begin_step()
        NULL_TRACER.end_step(anything=1)
        NULL_TRACER.request_begin(0, x=1)
        NULL_TRACER.request_event(0, "admit")
        NULL_TRACER.request_end(0)
        NULL_TRACER.instant("evict")
        with NULL_TRACER.phase("schedule"):
            pass  # usable as a context manager, records nothing
        assert not hasattr(NULL_TRACER, "events")


# ---------------------------------------------------------------- registry


class TestMetricsRegistry:
    def test_counters_and_gauges_push_and_pull(self):
        reg = MetricsRegistry(namespace="t")
        c = reg.counter("requests_total")
        c.inc()
        c.inc(2)
        g = reg.gauge("depth")
        g.set(5.0)
        state = {"steps": 7}
        reg.counter_fn("steps_total", lambda: state["steps"])
        snap = reg.snapshot()
        assert snap["counters"] == {
            "t_requests_total": 3, "t_steps_total": 7,
        }
        assert snap["gauges"] == {"t_depth": 5.0}
        state["steps"] = 9  # pull-based: re-resolved at snapshot time
        assert reg.snapshot()["counters"]["t_steps_total"] == 9

    def test_duplicate_name_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError):
            reg.counter_fn("x_total", lambda: 0)
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_reservoir_summary_and_labeled_series(self):
        reg = MetricsRegistry(namespace="s")
        h = ReservoirHistogram(64, seed=0)
        for v in (1.0, 2.0, 3.0):
            h.record(v)
        reg.reservoir("ttft_seconds", h)
        grp = ReservoirGroup(("hit", "miss"), 64, seed=1)
        grp.record("hit", 0.5)
        reg.reservoir("ttft_seconds_by_source", grp, label="source")
        snap = reg.snapshot()
        res = snap["reservoirs"]["s_ttft_seconds"]
        assert res["count"] == 3 and res["p50"] == 2.0
        series = snap["reservoirs"]["s_ttft_seconds_by_source"]
        assert series["label"] == "source"
        assert series["series"]["hit"]["count"] == 1
        assert series["series"]["miss"] == {"count": 0}  # empty: no NaNs

    def test_snapshot_is_json_serializable(self):
        reg = MetricsRegistry(namespace="s")
        reg.reservoir("empty_seconds", ReservoirHistogram(8))
        reg.gauge("g", 1.5)
        json.dumps(reg.snapshot(include_state=True))  # must not raise

    def test_resolver_survives_object_replacement(self):
        """A caller may swap engine.metrics wholesale after warm-up — a
        callable-registered reservoir must follow the swap."""
        holder = {"h": ReservoirHistogram(8)}
        holder["h"].record(1.0)
        reg = MetricsRegistry()
        reg.reservoir("lat_seconds", lambda: holder["h"])
        assert reg.snapshot()["reservoirs"]["lat_seconds"]["count"] == 1
        holder["h"] = ReservoirHistogram(8)  # the reset
        assert reg.snapshot()["reservoirs"]["lat_seconds"] == {"count": 0}

    def test_prometheus_text_exposition(self):
        reg = MetricsRegistry(namespace="srv")
        reg.counter("reqs_total").inc(4)
        reg.gauge("depth", 2.0)
        h = ReservoirHistogram(64)
        h.record(1.0)
        h.record(3.0)
        reg.reservoir("ttft_seconds", h)
        grp = ReservoirGroup(("hit", "miss"), 64)
        grp.record("hit", 0.25)
        reg.reservoir("ttft_by_source", grp, label="source")
        text = reg.prometheus_text()
        assert "# TYPE srv_reqs_total counter" in text
        assert "srv_reqs_total 4" in text
        assert "# TYPE srv_depth gauge" in text
        assert "# TYPE srv_ttft_seconds summary" in text
        assert 'srv_ttft_seconds{quantile="0.5"} 2.0' in text
        assert "srv_ttft_seconds_sum 4.0" in text
        assert "srv_ttft_seconds_count 2" in text
        assert 'srv_ttft_by_source{source="hit",quantile="0.5"} 0.25' in text
        # empty labels emit _count 0, never NaN quantile samples
        assert 'srv_ttft_by_source{source="miss",quantile' not in text
        assert "nan" not in text.lower()

    def test_cross_host_merge(self):
        """Counters sum, reservoir percentiles come from the UNION of the
        hosts' sample streams (not averaged per-host percentiles)."""

        def host(seed, lo):
            reg = MetricsRegistry(namespace="srv")
            reg.counter("reqs_total").inc(10)
            h = ReservoirHistogram(256, seed=seed)
            for v in range(lo, lo + 100):
                h.record(float(v))
            reg.reservoir("lat_seconds", h)
            grp = ReservoirGroup(("hit", "miss"), 256, seed=seed)
            grp.record("hit", float(lo))
            reg.reservoir("lat_by_source", grp, label="source")
            return reg.snapshot(include_state=True)

        # the wire is JSON: round-trip each host's payload
        snaps = [
            json.loads(json.dumps(host(1, 0))),
            json.loads(json.dumps(host(2, 100))),
        ]
        merged = MetricsRegistry.merge(snaps)
        assert merged["counters"]["srv_reqs_total"] == 20
        lat = merged["reservoirs"]["srv_lat_seconds"]
        assert lat["count"] == 200
        assert lat["min"] == 0.0 and lat["max"] == 199.0
        assert abs(lat["p50"] - 99.5) < 1e-9  # union, under capacity: exact
        by_src = merged["reservoirs"]["srv_lat_by_source"]
        assert by_src["series"]["hit"]["count"] == 2
        assert by_src["series"]["miss"] == {"count": 0}
        # merged payload re-merges (associative surface for tree gathers)
        again = MetricsRegistry.merge([merged, merged])
        assert again["counters"]["srv_reqs_total"] == 40


class TestTracerClockAndRing:
    @pytest.mark.parametrize("n", [1, 3, 8])
    def test_a_bounded_tracer_drops_the_oldest(self, n):
        tr = Tracer(clock=FakeClock(), max_events=n)
        for i in range(20):
            with tr.phase("work", i=i):
                pass
            assert len(tr.events) <= n
        assert [e["args"]["i"] for e in tr.events] == list(range(20 - n, 20))
        # the export takes the ring as it takes the list
        names = [e["name"] for e in tr.to_perfetto()["traceEvents"]]
        assert names.count("work") == n

    def test_a_tracer_built_by_hand_stays_unbounded(self):
        tr = Tracer(clock=FakeClock())
        for _ in range(100):
            tr.instant("mark")
        assert isinstance(tr.events, list) and len(tr.events) == 100

    def test_every_event_is_placed_on_the_clock_from_events_alone(self):
        clock = FakeClock()
        for _ in range(7):
            clock()  # the tracer is not built at the clock's zero
        tr = Tracer(clock=clock)
        read_at = {}  # index in events -> the clock when it was written

        tr.request_begin(3)
        read_at[len(tr.events) - 1] = clock.t
        tr.begin_step()
        step_t0 = clock.t
        with tr.phase("dispatch"):
            phase_t0 = clock.t
            tr.request_event(3, "decode_token")
            read_at[len(tr.events) - 1] = clock.t
        read_at[len(tr.events) - 1] = phase_t0
        tr.instant("evict")
        read_at[len(tr.events) - 1] = clock.t
        tr.end_step(decode_rows=1)
        read_at[len(tr.events) - 2] = step_t0  # the slice, then its gauge

        offset = perf_counter_offset_us(tr.events)
        assert offset is not None
        for i, t in read_at.items():
            assert tr.events[i]["ts"] + offset == pytest.approx(
                t * 1e6, abs=1e-3
            ), tr.events[i]["name"]
        slices = [e for e in tr.events if e["ph"] == "X"]
        assert {e["name"] for e in slices} == {"step", "dispatch"}
        for e in slices:  # each slice says so itself, too
            assert e["args"]["perf_counter_ns"] == pytest.approx(
                (e["ts"] + offset) * 1e3, abs=1
            )
        assert perf_counter_offset_us([tr.events[0]]) is None  # no slice

    def test_a_phase_takes_counts_at_its_start_and_at_its_end(self):
        tr = Tracer(clock=FakeClock())
        with tr.phase("readback.resolve", rows=4) as span:
            span.note(finished=1)
        assert span.seconds == pytest.approx(0.001)
        (event,) = tr.events
        assert event["dur"] == pytest.approx(1000.0)
        assert event["args"]["rows"] == 4 and event["args"]["finished"] == 1
        with NULL_TRACER.phase("readback.resolve", rows=4) as span:
            span.note(finished=1)  # off stays off

    def test_the_process_has_one_bounded_tracer(self):
        tr = process_tracer()
        assert tr is process_tracer() and tr.enabled
        assert tr.events.maxlen == 65_536


# ------------------------------------------------------- engine integration


def _tiny_engine(tracer=None, **kw):
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        dtype=jnp.float32,
    )
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("token_budget", 16)
    kw.setdefault("max_prefill_chunk", 8)
    return InferenceEngine(model, params, tracer=tracer, **kw)


PROMPTS = [[5, 7, 11, 2, 9, 3], [1, 4, 8], [2, 2, 3, 17, 40], [6, 1, 9, 9]]


def _draft_kw():
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    draft = TransformerLM(
        vocab_size=48, d_model=8, n_layers=1, n_heads=2, d_ff=16,
        dtype=jnp.float32,
    )
    params = draft.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return dict(draft_model=draft, draft_params=params, gamma=2)


def _run_all(eng):
    ids = [
        eng.submit(p, SamplingParams(max_new_tokens=6)) for p in PROMPTS
    ]
    eng.run()
    return [eng.poll(r).generated for r in ids]


class TestEngineObservability:
    @pytest.mark.parametrize(
        "mode", ["overlap", "sync", "speculative", "accounted"]
    )
    def test_tracing_does_not_change_tokens(self, mode, dispatcher_off):
        """Acceptance: with tracing enabled and the set-up timeline's
        dispatcher installed, greedy outputs are bitwise-identical to the
        untraced engine of a process without the dispatcher, and the trace
        holds every child slice inside its parent: one ``dispatch.key`` a
        launch, covering all of the step's decode rows."""
        kw = {
            "overlap": dict,
            "sync": lambda: dict(overlap=False),
            "speculative": _draft_kw,
            "accounted": lambda: dict(flight=FlightRecorder(64)),
        }[mode]
        from distributed_pytorch_tpu.obs.xla import install_dispatcher

        def compiles_since(mark):
            """The ``compile`` slices the process's tracer took since."""
            pt = process_tracer()
            return [e for e in pt.setup_events + list(pt.events)
                    if e["name"] == "compile"
                    and e["args"]["perf_counter_ns"] >= mark]

        mark = time.perf_counter_ns()
        with dispatcher_off():
            plain = _run_all(_tiny_engine(**kw()))
            assert not compiles_since(mark)
        assert install_dispatcher()
        tr = Tracer()
        traced = _run_all(_tiny_engine(tracer=tr, **kw()))
        assert traced == plain
        # the second engine's programs are new jit objects: JAX lowered and
        # compiled them again, and each wrote its slice
        assert compiles_since(mark)
        assert not [e for e in tr.events if e["name"] == "compile"]

        slices = [e for e in tr.events if e["ph"] == "X"]
        by_step = {}
        for e in slices:
            by_step.setdefault(e["args"]["step"], []).append(e)

        def inside(child, parent):
            return (
                parent["ts"] <= child["ts"]
                and child["ts"] + child["dur"]
                <= parent["ts"] + parent["dur"]
            )

        seen = set()
        for step, rows in by_step.items():
            (whole,) = [e for e in rows if e["name"] == "step"]
            for e in rows:
                if e is whole:
                    continue
                assert inside(e, whole), (step, e["name"])
                seen.add(e["name"])
                parent_name, dot, _ = e["name"].partition(".")
                if dot:
                    assert any(
                        p["name"] == parent_name and inside(e, p)
                        for p in rows
                    ), (step, e["name"])
            keys = [e for e in rows if e["name"] == "dispatch.key"]
            decode_rows = whole["args"]["decode_rows"]
            assert len(keys) == (1 if decode_rows else 0)
            assert sum(e["args"]["rows"] for e in keys) == decode_rows
            launches = [e for e in rows if e["name"] == "dispatch.launch"]
            assert len(launches) == len(keys)
        assert seen >= {
            "schedule", "prefill", "prefill.chunk", "dispatch",
            "dispatch.key", "dispatch.stage", "dispatch.launch", "readback",
            "readback.wait", "readback.resolve",
        }
        stage = next(e for e in slices if e["name"] == "dispatch.stage")
        assert stage["args"]["rows"] > 0 and stage["args"]["bytes"] > 0
        resolved = [e for e in slices if e["name"] == "readback.resolve"]
        assert sum(e["args"]["finished"] for e in resolved) == len(PROMPTS)
        chunks = [e for e in slices if e["name"] == "prefill.chunk"]
        assert sum(e["args"]["tokens"] for e in chunks) == sum(
            e["args"]["prefill_tokens"] for e in slices
            if e["name"] == "step"
        ) > 0

    def test_the_accounting_path_sees_the_key_slice(self):
        """``dispatch.key`` is one slice a launch, so the accounting path
        times it like ``dispatch``'s other children, tracer or none."""
        eng = _tiny_engine(flight=FlightRecorder(64), timeseries=True)
        _run_all(eng)
        names = {
            n for n in eng.timeseries.series_names() if n.startswith("phase_")
        }
        assert "phase_dispatch.stage_seconds" in names
        assert "phase_readback.wait_seconds" in names
        assert "phase_dispatch.key_seconds" in names

    def test_span_count_equals_completed_requests(self, tmp_path):
        tr = Tracer()
        eng = _tiny_engine(tracer=tr)
        _run_all(eng)
        completed = eng.metrics.requests_completed
        assert completed == len(PROMPTS)
        assert tr.spans_opened == completed
        assert tr.spans_closed == completed
        doc = json.load(open(eng.save_trace(str(tmp_path / "t.json"))))
        begins = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "b" and e.get("cat") == "request"
        ]
        ends = [
            e for e in doc["traceEvents"]
            if e.get("ph") == "e" and e.get("cat") == "request"
        ]
        assert len(begins) == completed and len(ends) == completed
        # the step timeline is there too: step slices and phase slices
        assert any(
            e.get("ph") == "X" and e.get("name") == "step"
            for e in doc["traceEvents"]
        )
        assert any(
            e.get("ph") == "X" and e.get("name") == "schedule"
            for e in doc["traceEvents"]
        )
        # every request span carries an admit event
        admits = [
            e for e in doc["traceEvents"] if e.get("name") == "admit"
        ]
        assert {e["id"] for e in admits} == {e["id"] for e in begins}

    def test_registry_counters_match_engine_ground_truth(self):
        eng = _tiny_engine(tracer=Tracer())
        tokens = _run_all(eng)
        snap = eng.registry.snapshot()
        c = snap["counters"]
        assert c["serving_requests_completed_total"] == len(PROMPTS)
        assert c["serving_tokens_generated_total"] == sum(
            len(t) for t in tokens
        )
        assert c["serving_engine_steps_total"] == (
            eng.metrics.engine_steps
        )
        assert c["serving_admission_accepted_total"] == len(PROMPTS)
        # drained engine: no pages referenced, everything free or idle
        g = snap["gauges"]
        assert g["serving_pages_referenced"] == 0
        assert g["serving_running_requests"] == 0
        assert (
            snap["reservoirs"]["serving_ttft_seconds"]["count"]
            == len(PROMPTS)
        )
        # and the Prometheus rendering carries the same counter
        assert (
            f"serving_requests_completed_total {len(PROMPTS)}"
            in eng.registry.prometheus_text()
        )

    def test_save_trace_requires_tracer(self, tmp_path):
        eng = _tiny_engine()
        with pytest.raises(RuntimeError):
            eng.save_trace(str(tmp_path / "t.json"))

    def test_step_gauges_on_timeline(self):
        tr = Tracer()
        eng = _tiny_engine(tracer=tr)
        _run_all(eng)
        steps = [e for e in tr.events if e["name"] == "step"]
        assert steps, "no step slices recorded"
        args = steps[0]["args"]
        for key in (
            "decode_rows", "prefill_programs", "prefill_tokens",
            "prefill_width",
            "budget_utilization", "queue_depth", "running_requests",
            "pages_free", "pages_referenced", "pages_cached_idle",
        ):
            assert key in args, f"step gauge {key} missing"
        assert all(
            0.0 <= e["args"]["budget_utilization"] <= 1.0 for e in steps
        )
        assert not math.isnan(args["budget_utilization"])
