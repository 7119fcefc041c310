"""Production-observability tests: the crash-dump FlightRecorder (ring
buffer, postmortem dumps, Perfetto replay), the SLO burn-rate monitor
(latency + rate objectives, rising-edge alerting, registry/tracer/flight
fan-out), goodput/MFU accounting (per-step waste attribution, the shared
FLOPs model), the registry's HELP/escape/read accessors — and the engine
integration acceptance criteria: with recorder + SLO monitor + goodput
all enabled, greedy outputs are bitwise-identical to the all-off engine;
chaos faults and unhandled run() exceptions leave a postmortem dump; a
snapshot/restore cycle attributes nonzero waste to restore re-prefill.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import chaos
from distributed_pytorch_tpu.metrics import ReservoirGroup, ReservoirHistogram
from distributed_pytorch_tpu.obs import (
    FlightRecorder,
    GoodputTracker,
    MetricsRegistry,
    NULL_FLIGHT_RECORDER,
    NullFlightRecorder,
    SLObjective,
    SLOMonitor,
    Tracer,
    causal_attention_flops,
    default_serving_objectives,
    peak_flops_per_chip,
    replay_to_tracer,
    transformer_decode_flops_per_token,
    transformer_train_flops,
)
from distributed_pytorch_tpu.obs.goodput import DEFAULT_PEAK, WASTE_KINDS
from distributed_pytorch_tpu.serving import (
    InferenceEngine,
    SamplingParams,
    restore_engine,
    snapshot_engine,
)


class FakeClock:
    """Deterministic clock: advances a fixed tick per call."""

    def __init__(self, tick: float = 0.001):
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t


# --------------------------------------------------------- flight recorder


class TestFlightRecorder:
    def test_ring_drops_oldest_and_counts(self):
        fr = FlightRecorder(capacity=3, clock=FakeClock())
        for i in range(5):
            fr.record("step", step=i)
        assert fr.recorded == 5 and fr.dropped == 2
        events = fr.events()
        assert [e["step"] for e in events] == [2, 3, 4]  # oldest fell off
        assert all(e["kind"] == "step" for e in events)
        # timestamps are seconds since construction, strictly increasing
        ts = [e["t"] for e in events]
        assert ts == sorted(ts) and ts[0] >= 0.0

    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_dump_document_shape_without_path(self):
        fr = FlightRecorder(capacity=8, clock=FakeClock())
        fr.record("admit", req_id=1)
        doc = fr.dump("manual", extra={"registry": {"counters": {}}})
        assert doc["version"] == 1
        assert doc["reason"] == "manual"
        assert doc["recorded"] == 1 and doc["dropped"] == 0
        assert doc["capacity"] == 8
        assert doc["events"][0]["kind"] == "admit"
        assert doc["extra"]["registry"] == {"counters": {}}
        assert fr.dumps == 1

    def test_dump_writes_atomically(self, tmp_path):
        target = tmp_path / "sub" / "postmortem.json"
        fr = FlightRecorder(capacity=8, path=str(target), clock=FakeClock())
        fr.record("step", step=0, dur_s=0.01)
        fr.dump("chaos:kill")
        with open(target) as f:
            doc = json.load(f)
        assert doc["reason"] == "chaos:kill"
        assert doc["events"][0]["step"] == 0
        # no .tmp leftovers from the atomic replace
        assert all(
            ".tmp." not in name for name in os.listdir(target.parent)
        )
        # a second dump overwrites in place (latest reason wins)
        fr.dump("close")
        assert json.load(open(target))["reason"] == "close"

    def test_null_recorder_is_inert(self):
        assert NULL_FLIGHT_RECORDER.enabled is False
        assert isinstance(NULL_FLIGHT_RECORDER, NullFlightRecorder)
        NULL_FLIGHT_RECORDER.record("anything", x=1)
        assert NULL_FLIGHT_RECORDER.dump("reason") is None
        assert not hasattr(NULL_FLIGHT_RECORDER, "events")


class TestReplayToTracer:
    def _dump(self):
        fr = FlightRecorder(capacity=16, clock=FakeClock(0.01))
        fr.record("admit", req_id=1, slot=0)
        fr.record("step", step=0, dur_s=0.005, emitted_tokens=2)
        fr.record("chaos_fault", fault_kind="kill_mid_verify", step=1)
        return fr.dump("chaos:kill_mid_verify")

    def test_replay_produces_valid_chrome_trace(self):
        tracer = replay_to_tracer(self._dump())
        doc = json.loads(json.dumps(tracer.to_perfetto()))
        events = doc["traceEvents"]
        steps = [e for e in events if e.get("ph") == "X"]
        assert len(steps) == 1
        assert steps[0]["name"] == "step" and steps[0]["dur"] > 0
        assert steps[0]["args"]["emitted_tokens"] == 2
        instants = {
            e["name"] for e in events if e.get("ph") == "i"
        }
        assert {"admit", "chaos_fault"} <= instants
        # lane metadata came along from to_perfetto()
        assert any(e.get("ph") == "M" for e in events)

    def test_replay_accepts_json_text_and_path(self, tmp_path):
        doc = self._dump()
        by_text = replay_to_tracer(json.dumps(doc))
        path = tmp_path / "dump.json"
        path.write_text(json.dumps(doc))
        by_path = replay_to_tracer(str(path))
        by_dict = replay_to_tracer(doc)
        assert (
            len(by_text.events) == len(by_path.events) == len(by_dict.events)
        )

    def test_replay_into_existing_tracer(self):
        tr = Tracer(clock=FakeClock())
        out = replay_to_tracer(self._dump(), tracer=tr)
        assert out is tr and tr.events

    def test_replay_rejects_non_dump(self):
        with pytest.raises(ValueError):
            replay_to_tracer({"not": "a dump"})


# ------------------------------------------------------- registry accessors


class TestRegistryAccessors:
    def test_read_counter_gauge_and_quantile(self):
        reg = MetricsRegistry(namespace="srv")
        reg.counter("reqs_total").inc(4)
        reg.gauge("depth", 2.5)
        h = ReservoirHistogram(64, seed=0)
        for v in (1.0, 2.0, 3.0):
            h.record(v)
        reg.reservoir("lat_seconds", h)
        # both name forms resolve: registered and namespace-qualified
        assert reg.read_counter("reqs_total") == 4
        assert reg.read_counter("srv_reqs_total") == 4
        assert reg.read_gauge("depth") == 2.5
        assert reg.read_quantile("lat_seconds", 0.5) == 2.0

    def test_read_quantile_labeled(self):
        reg = MetricsRegistry(namespace="srv")
        grp = ReservoirGroup(("hit", "miss"), 64, seed=1)
        grp.record("hit", 0.25)
        reg.reservoir("ttft_by_source", grp, label="source")
        assert reg.read_quantile(
            "ttft_by_source", 0.5, label_value="hit"
        ) == 0.25
        # empty series and unknown labels read as NaN, not KeyError
        assert math.isnan(
            reg.read_quantile("ttft_by_source", 0.5, label_value="miss")
        )
        assert math.isnan(
            reg.read_quantile("ttft_by_source", 0.5, label_value="nope")
        )
        with pytest.raises(ValueError):
            reg.read_quantile("ttft_by_source", 0.5)  # label required

    def test_prometheus_help_lines_precede_type(self):
        reg = MetricsRegistry(namespace="srv")
        reg.counter("reqs_total", help="Total requests admitted")
        reg.gauge("depth", 1.0)
        text = reg.prometheus_text()
        assert "# HELP srv_reqs_total Total requests admitted" in text
        assert text.index("# HELP srv_reqs_total") < text.index(
            "# TYPE srv_reqs_total counter"
        )
        # metrics registered without help fall back to their own name
        assert "# HELP srv_depth srv_depth" in text

    def test_prometheus_escapes_help_and_labels(self):
        reg = MetricsRegistry(namespace="srv")
        reg.counter("weird_total", help="line1\nline2 back\\slash")
        grp = ReservoirGroup(('he"llo\n', ), 8)
        grp.record('he"llo\n', 1.0)
        reg.reservoir("lat by source!", grp, label="the source")
        text = reg.prometheus_text()
        assert "# HELP srv_weird_total line1\\nline2 back\\\\slash" in text
        # label-unsafe metric name sanitized, label value escaped
        assert "srv_lat_by_source_" in text
        assert 'the_source="he\\"llo\\n"' in text
        assert "\nline2" not in text  # no raw newline mid-HELP


# ------------------------------------------------------------- SLO monitor


class TestSLObjective:
    def test_exactly_one_form_required(self):
        with pytest.raises(ValueError):
            SLObjective(name="both", metric="m", threshold_s=1.0,
                        bad_counter="b", total_counter="t")
        with pytest.raises(ValueError):
            SLObjective(name="neither")
        with pytest.raises(ValueError):
            SLObjective(name="no_thresh", metric="m")
        with pytest.raises(ValueError):
            SLObjective(name="no_total", bad_counter="b")
        with pytest.raises(ValueError):
            SLObjective(name="bad_budget", metric="m", threshold_s=1.0,
                        budget=0.0)
        with pytest.raises(ValueError):
            SLObjective(name="windows", metric="m", threshold_s=1.0,
                        fast_window_s=10.0, slow_window_s=5.0)
        assert SLObjective(
            name="ok", metric="m", threshold_s=1.0
        ).kind == "latency"
        assert SLObjective(
            name="ok2", bad_counter="b", total_counter="t"
        ).kind == "rate"

    def test_default_serving_objectives_shape(self):
        objs = default_serving_objectives()
        assert [o.name for o in objs] == [
            "ttft_p95", "tpot_p50", "expired_rate"
        ]
        assert objs[0].kind == "latency" and objs[2].kind == "rate"


class TestSLOMonitor:
    def _latency_setup(self, threshold_s, **obj_kw):
        reg = MetricsRegistry()
        hist = ReservoirHistogram(64, seed=0)
        reg.reservoir("lat_seconds", hist)
        obj = SLObjective(
            name="lat_p50", metric="lat_seconds", quantile=0.5,
            threshold_s=threshold_s, budget=0.1,
            fast_window_s=2.0, slow_window_s=8.0, **obj_kw,
        )
        mon = SLOMonitor(reg, [obj])
        return reg, hist, mon

    def test_latency_alert_fires_once_on_rising_edge(self):
        reg, hist, mon = self._latency_setup(0.1)
        # empty reservoir: quantile is NaN -> not bad, nothing fires
        assert mon.tick(now=0.0) == []
        hist.record(0.5)  # p50 = 0.5 > 0.1: every later sample is bad
        fired = []
        for i in range(1, 10):
            fired += mon.tick(now=float(i))
        assert fired == ["lat_p50"]  # rising edge counted exactly once
        snap = reg.snapshot()
        assert snap["counters"]["slo_lat_p50_alerts_total"] == 1
        assert snap["gauges"]["slo_lat_p50_firing"] == 1.0
        assert snap["gauges"]["slo_lat_p50_burn_fast"] >= 2.0
        st = mon.state()["lat_p50"]
        assert st["firing"] and st["kind"] == "latency"
        assert st["alerts"] == 1

    def test_loose_objective_stays_quiet(self):
        reg, hist, mon = self._latency_setup(10.0)
        hist.record(0.5)  # p50 well under the threshold
        for i in range(10):
            assert mon.tick(now=float(i)) == []
        snap = reg.snapshot()
        assert snap["counters"]["slo_lat_p50_alerts_total"] == 0
        assert snap["gauges"]["slo_lat_p50_firing"] == 0.0
        assert not mon.state()["lat_p50"]["firing"]

    def test_alert_lands_in_tracer_and_flight(self):
        reg = MetricsRegistry()
        hist = ReservoirHistogram(8, seed=0)
        hist.record(1.0)
        reg.reservoir("lat_seconds", hist)
        tracer = Tracer(clock=FakeClock())
        flight = FlightRecorder(capacity=16, clock=FakeClock())
        mon = SLOMonitor(
            reg,
            [SLObjective(name="lat", metric="lat_seconds",
                         threshold_s=0.1, fast_window_s=1.0,
                         slow_window_s=4.0)],
            tracer=tracer, flight=flight,
        )
        for i in range(5):
            mon.tick(now=float(i))
        instants = [
            e for e in tracer.events if e["name"] == "slo_alert"
        ]
        assert len(instants) == 1
        assert instants[0]["args"]["objective"] == "lat"
        alerts = [e for e in flight.events() if e["kind"] == "slo_alert"]
        assert len(alerts) == 1 and alerts[0]["burn_fast"] > 0

    def test_rate_objective_fires_on_error_burst(self):
        reg = MetricsRegistry()
        bad = reg.counter("expired_total")
        total = reg.counter("accepted_total")
        mon = SLOMonitor(
            reg,
            [SLObjective(name="errs", bad_counter="expired_total",
                         total_counter="accepted_total", budget=0.1,
                         fast_window_s=2.0, slow_window_s=8.0)],
        )
        # healthy traffic: requests flow, nothing expires, never fires
        for i in range(5):
            total.inc(10)
            assert mon.tick(now=float(i)) == []
        # burst: half of everything expires -> burn >> thresholds
        fired = []
        for i in range(5, 12):
            total.inc(10)
            bad.inc(5)
            fired += mon.tick(now=float(i))
        assert fired == ["errs"]
        assert reg.snapshot()["counters"]["slo_errs_alerts_total"] == 1
        assert mon.state()["errs"]["burn_fast"] > 2.0

    def test_rate_objective_quiet_without_traffic(self):
        reg = MetricsRegistry()
        reg.counter("expired_total")
        reg.counter("accepted_total")
        mon = SLOMonitor(
            reg,
            [SLObjective(name="errs", bad_counter="expired_total",
                         total_counter="accepted_total")],
        )
        for i in range(5):  # zero denominators never divide or fire
            assert mon.tick(now=float(i)) == []

    def test_min_interval_rate_limits_ticks(self):
        reg, hist, mon = self._latency_setup(0.1)
        mon.min_interval_s = 10.0
        hist.record(1.0)
        mon.tick(now=0.0)
        assert mon.ticks == 1
        mon.tick(now=5.0)  # inside the interval: skipped
        assert mon.ticks == 1
        mon.tick(now=15.0)
        assert mon.ticks == 2

    def test_duplicate_objective_names_rejected(self):
        reg = MetricsRegistry()
        reg.reservoir("lat_seconds", ReservoirHistogram(8))
        objs = [
            SLObjective(name="x", metric="lat_seconds", threshold_s=1.0),
            SLObjective(name="x", metric="lat_seconds", threshold_s=2.0),
        ]
        with pytest.raises(ValueError):
            SLOMonitor(reg, objs)


# ---------------------------------------------------------------- goodput


class TestGoodputTracker:
    def test_fully_productive_step(self):
        t = GoodputTracker()
        t.note_step(1.0, prefill_tokens=10, budget_used=10,
                    token_budget=10, queue_depth=1)
        assert t.productive_s == pytest.approx(1.0)
        assert t.wasted_total_s() == 0.0
        assert t.fraction() == pytest.approx(1.0)

    def test_budget_idle_charged_only_under_queue_pressure(self):
        t = GoodputTracker()
        # half-used budget with a queue: half the span is idle waste
        t.note_step(1.0, prefill_tokens=5, budget_used=5,
                    token_budget=10, queue_depth=3)
        assert t.wasted["budget_idle"] == pytest.approx(0.5)
        assert t.productive_s == pytest.approx(0.5)
        # same shape with an empty queue: nothing to admit, no waste
        t2 = GoodputTracker()
        t2.note_step(1.0, prefill_tokens=5, budget_used=5,
                     token_budget=10, queue_depth=0)
        assert t2.wasted["budget_idle"] == 0.0
        assert t2.productive_s == pytest.approx(1.0)

    def test_spec_rejected_attribution(self):
        t = GoodputTracker()
        # 8 speculative positions verified, 5 kept: 3/8 of the span wasted
        t.note_step(1.0, decode_positions=8, emitted_tokens=5,
                    spec_proposed=8, budget_used=8, token_budget=8,
                    queue_depth=1)
        assert t.wasted["spec_rejected"] == pytest.approx(3 / 8)
        assert t.productive_s == pytest.approx(5 / 8)
        assert t.tokens == 5

    def test_rework_charged_before_spec(self):
        t = GoodputTracker()
        t.note_step(
            1.0, prefill_tokens=10, decode_positions=0,
            rework={"restore_reprefill": 4}, budget_used=10,
            token_budget=10, queue_depth=1,
        )
        assert t.wasted["restore_reprefill"] == pytest.approx(0.4)
        assert t.productive_s == pytest.approx(0.6)
        # rework is capped at the step's work units
        t2 = GoodputTracker()
        t2.note_step(1.0, prefill_tokens=4,
                     rework={"preempt_rework": 100})
        assert t2.wasted["preempt_rework"] == pytest.approx(1.0)
        assert t2.productive_s == 0.0

    def test_zero_work_step_is_productive(self):
        t = GoodputTracker()
        t.note_step(0.5)
        assert t.productive_s == pytest.approx(0.5)

    def test_drain_downtime_brackets(self):
        clock = FakeClock(0.5)
        t = GoodputTracker(clock=clock)
        t.note_restore()  # restore without drain (fresh process): no-op
        assert t.wasted["drain_downtime"] == 0.0
        t.note_drain()
        t.note_restore()
        assert t.wasted["drain_downtime"] == pytest.approx(0.5)

    def test_mfu_and_throughput(self):
        t = GoodputTracker(flops_per_token=100.0,
                           peak_flops_per_device=1000.0, n_devices=2)
        t.note_step(1.0, decode_positions=5, emitted_tokens=5,
                    budget_used=5, token_budget=5, queue_depth=0)
        # 5 tokens x 100 flops over 1s x 2000 peak
        assert t.mfu() == pytest.approx(0.25)
        assert t.tokens_per_sec_per_device() == pytest.approx(2.5)
        rep = t.report()
        assert set(rep) == {
            "steps", "tokens", "productive_s", "wasted_s",
            "wasted_total_s", "goodput_fraction",
            "tokens_per_sec_per_device", "mfu",
        }
        assert set(rep["wasted_s"]) == set(WASTE_KINDS)

    def test_register_into_registry(self):
        t = GoodputTracker(flops_per_token=1.0, peak_flops_per_device=1.0)
        reg = MetricsRegistry(namespace="srv")
        t.register_into(reg)
        t.note_step(1.0, prefill_tokens=2, budget_used=2,
                    token_budget=4, queue_depth=1)
        snap = reg.snapshot()
        assert snap["counters"][
            "srv_goodput_productive_seconds_total"
        ] == pytest.approx(0.5)
        assert snap["counters"][
            "srv_goodput_wasted_budget_idle_seconds_total"
        ] == pytest.approx(0.5)
        assert snap["gauges"]["srv_goodput_fraction"] == pytest.approx(0.5)
        assert "srv_goodput_mfu" in snap["gauges"]

    def test_reset_zeroes_everything(self):
        t = GoodputTracker()
        t.note_step(1.0, prefill_tokens=1)
        t.reset()
        assert t.steps == 0 and t.tokens == 0
        assert t.productive_s == 0.0 and t.wasted_total_s() == 0.0
        assert t.fraction() == 1.0


class TestFlopsModel:
    def test_causal_attention_matches_bruteforce(self):
        for seq, window in ((16, None), (16, 4), (16, 32), (7, 7)):
            per_q_brute = float(
                np.minimum(np.arange(seq) + 1, window or seq).sum()
            )
            # brute force counts keys per query; the closed form halves
            # the full square, so compare through the same public call
            got = causal_attention_flops(
                n_layers=2, n_heads=3, head_dim=5, seq_len=seq,
                batch=4, window=window,
            )
            if window:
                want = 2 * 4.0 * 4 * 3 * per_q_brute * 5
            else:
                want = 2 * 4.0 * 4 * 3 * (seq**2 / 2) * 5
            assert got == pytest.approx(want), (seq, window)

    def test_windowed_closed_form_equals_key_count(self):
        # the windowed closed form must equal sum(min(i+1, w))
        for seq, w in ((10, 3), (10, 10), (10, 15), (3, 1)):
            brute = float(np.minimum(np.arange(seq) + 1, w).sum())
            got = causal_attention_flops(
                n_layers=1, n_heads=1, head_dim=1, seq_len=seq,
                batch=1, window=w,
            )
            assert got == pytest.approx(4.0 * brute), (seq, w)

    def test_train_flops_dominated_by_param_term(self):
        flops = transformer_train_flops(
            n_params=1_000_000, embed_params=100_000, n_layers=2,
            n_heads=4, head_dim=8, seq_len=128, batch=2,
        )
        tokens = 2 * 128
        assert flops > 3.0 * 2.0 * 900_000 * tokens  # attention adds more
        # the attention term is exactly the causal helper's
        attn = causal_attention_flops(
            n_layers=2, n_heads=4, head_dim=8, seq_len=128, batch=2,
        )
        assert flops == pytest.approx(
            3.0 * (2.0 * 900_000 * tokens + attn)
        )

    def test_decode_flops_grow_with_context(self):
        kw = dict(n_params=1_000_000, embed_params=100_000,
                  n_layers=2, n_heads=4, head_dim=8)
        short = transformer_decode_flops_per_token(context_len=16, **kw)
        long = transformer_decode_flops_per_token(context_len=1024, **kw)
        assert long > short > 2.0 * 900_000

    class Dev:
        def __init__(self, kind):
            self.device_kind = kind

    def test_peak_flops_lookup(self):
        Dev = self.Dev
        assert peak_flops_per_chip(Dev("TPU v5p")) == 459e12
        assert peak_flops_per_chip(Dev("TPU v5e")) == 197e12
        assert peak_flops_per_chip(Dev("TPU v4")) == 275e12
        assert peak_flops_per_chip(Dev("cpu")) == DEFAULT_PEAK
        assert peak_flops_per_chip(object()) == DEFAULT_PEAK

    def test_peak_flops_table(self):
        """The v5e by the name its runtime gives it, and a TPU the table does
        not know: an error, never another chip's peak."""
        assert peak_flops_per_chip(self.Dev("TPU v5 lite")) == 197e12
        with pytest.raises(ValueError, match="TPU v99"):
            peak_flops_per_chip(self.Dev("TPU v99"))


# ------------------------------------------------------ engine integration


def _tiny_engine(**kw):
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
    return InferenceEngine(model, params, **kw)


PROMPTS = [[5, 7, 11, 2, 9, 3], [1, 4, 8], [2, 2, 3, 17, 40], [6, 1, 9, 9]]


def _run_all(eng):
    ids = [
        eng.submit(p, SamplingParams(max_new_tokens=6)) for p in PROMPTS
    ]
    eng.run()
    return [eng.poll(r).generated for r in ids]


def _arm(plan):
    os.environ[chaos.ENV_VAR] = json.dumps(plan)
    chaos._reset()


def _disarm():
    os.environ.pop(chaos.ENV_VAR, None)
    chaos._reset()


class TestEngineProductionObservability:
    def test_all_obs_on_token_parity(self):
        """Acceptance: recorder + SLO monitor + goodput + tracer all on,
        greedy outputs bitwise-identical to the all-off engine."""
        plain = _run_all(_tiny_engine())
        eng = _tiny_engine(
            tracer=Tracer(),
            flight=FlightRecorder(capacity=1024),
            slo=default_serving_objectives(),
            goodput=True,
        )
        assert _run_all(eng) == plain
        # and all three subsystems actually observed the run
        assert eng.flight.recorded > 0
        rep = eng.goodput.report()
        assert rep["steps"] > 0 and rep["tokens"] > 0
        assert rep["productive_s"] > 0.0
        assert eng.slo.ticks > 0
        snap = eng.registry.snapshot()
        assert "serving_goodput_fraction" in snap["gauges"]
        assert "serving_slo_ttft_p95_alerts_total" in snap["counters"]
        assert snap["counters"]["serving_flight_events_recorded_total"] > 0

    def test_stats_carries_goodput(self):
        eng = _tiny_engine(goodput=True)
        _run_all(eng)
        s = eng.stats()
        assert 0.0 <= s["goodput_fraction"] <= 1.0
        assert s["goodput_productive_s"] > 0.0

    def test_flight_records_engine_lifecycle(self, tmp_path):
        path = str(tmp_path / "pm.json")
        eng = _tiny_engine(flight=FlightRecorder(capacity=1024, path=path))
        _run_all(eng)
        kinds = {e["kind"] for e in eng.flight.events()}
        assert {"step", "admit", "retire"} <= kinds
        eng.close()  # close() dumps a postmortem automatically
        doc = json.load(open(path))
        assert doc["reason"] == "close"
        assert "registry" in doc["extra"]

    def test_unhandled_run_exception_dumps_postmortem(self, tmp_path):
        path = str(tmp_path / "pm.json")
        eng = _tiny_engine(
            flight=FlightRecorder(capacity=256, path=path), goodput=True
        )
        eng.submit(PROMPTS[0], SamplingParams(max_new_tokens=4))

        def boom():
            raise RuntimeError("injected step failure")

        eng._step_impl = boom
        with pytest.raises(RuntimeError, match="injected step failure"):
            eng.run()
        doc = json.load(open(path))
        assert doc["reason"] == "exception"
        exc_events = [
            e for e in doc["events"] if e["kind"] == "exception"
        ]
        assert exc_events and "injected" in exc_events[0]["error"]
        assert "goodput" in doc["extra"]

    def test_chaos_fault_dumps_before_raising(self, tmp_path):
        path = str(tmp_path / "pm.json")
        _arm({"faults": [
            {"kind": "kill_mid_verify", "at_step": 2, "mode": "raise"}
        ]})
        try:
            eng = _tiny_engine(
                flight=FlightRecorder(capacity=256, path=path)
            )
            ids = [
                eng.submit(p, SamplingParams(max_new_tokens=6))
                for p in PROMPTS
            ]
            assert ids
            with pytest.raises(chaos.InjectedFault):
                eng.run()
        finally:
            _disarm()
        doc = json.load(open(path))
        # the chaos observer dumped first (reason chaos:...), then run()'s
        # crash handler overwrote with the final exception dump — the
        # chaos_fault event survives in the ring either way.
        assert doc["reason"] == "exception"
        kinds = [e["kind"] for e in doc["events"]]
        assert "chaos_fault" in kinds
        fault = next(
            e for e in doc["events"] if e["kind"] == "chaos_fault"
        )
        assert fault["fault_kind"] == "kill_mid_verify"
        assert eng.flight.dumps == 2  # chaos dump + exception dump
        # and the dump replays into a loadable trace
        tracer = replay_to_tracer(str(path))
        assert json.loads(json.dumps(tracer.to_perfetto()))["traceEvents"]

    def test_restore_attributes_reprefill_waste(self, tmp_path):
        """A snapshot/restore cycle must charge the re-prefill of
        already-committed KV to restore_reprefill."""
        eng = _tiny_engine(max_slots=2, goodput=True)
        ids = [
            eng.submit(p, SamplingParams(max_new_tokens=8))
            for p in PROMPTS + [[9, 9, 1, 2], [4, 4, 4]]
        ]
        for _ in range(4):
            eng.step()
        snap = snapshot_engine(eng)
        assert snap.requests, "drill degenerate: nothing to restore"
        assert any(r.kv_committed > 0 for r in snap.requests), (
            "no committed KV at the snapshot"
        )

        fresh = _tiny_engine(max_slots=2, goodput=True)
        restored = restore_engine(fresh, snap)
        assert restored
        fresh.run()
        for rid in restored:
            assert fresh.poll(rid).finished
        rep = fresh.goodput.report()
        assert rep["wasted_s"]["restore_reprefill"] > 0.0
        assert rep["goodput_fraction"] < 1.0
        assert ids  # silence unused warning

    def test_preemption_attributes_rework(self):
        """A preempted-and-readmitted request re-prefills its generated
        KV; goodput charges that span to preempt_rework."""
        # 9-page pool under 4 slots x staggered waves: decode exhausts the
        # pool mid-flight and the scheduler must preempt (seeded, so the
        # preemption count is deterministic on this config).
        eng = _tiny_engine(num_pages=9, goodput=True)
        rng = np.random.default_rng(0)
        for _wave in range(4):
            for _ in range(2):
                prompt = rng.integers(
                    0, 48, int(rng.integers(3, 10))
                ).tolist()
                eng.submit(
                    prompt,
                    SamplingParams(
                        max_new_tokens=int(rng.integers(4, 9))
                    ),
                )
            for _ in range(3):
                eng.step()
        eng.run()
        assert eng.scheduler.preemptions > 0, "drill degenerate: no preempt"
        rep = eng.goodput.report()
        assert rep["wasted_s"]["preempt_rework"] > 0.0
