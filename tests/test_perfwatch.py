"""Performance-observatory engine integration: token parity with the
TSDB + roofline + CUSUM detector on, the seeded ``slow_program`` drill
firing within budget and blaming the stalled phase, the ``/timeseries``
and ``/graphz`` introspection endpoints, and the bounded-eviction
contracts of the admission rejection ring and the trace sampler.

The parity invariant is the headline (same bar as every other
observability layer in this repo): the observatory may time, bucket and
test every step, but it must never change a greedy token. The drill
mirrors ``tools/serving_smoke.sh perfwatch``
at unit scale — and, like it, warms the decode stratum BEFORE arming
the stall: a stratum first seen mid-stall anchors its median/MAD
baseline on stalled samples and honestly reports "normal".
All on CPU (conftest pins JAX_PLATFORMS=cpu).
"""

import json
import os
import urllib.error

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu import chaos
from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.obs.disttrace import TraceSampler
from distributed_pytorch_tpu.obs.server import scrape
from distributed_pytorch_tpu.obs.timeseries import TimeSeriesDB
from distributed_pytorch_tpu.serving import (
    AdmissionController,
    InferenceEngine,
    RequestTooLong,
    SamplingParams,
)

VOCAB = 48


def tiny_lm():
    return TransformerLM(
        vocab_size=VOCAB, d_model=16, n_layers=1, n_heads=2, d_ff=32,
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


ENGINE_KW = dict(
    max_slots=4, max_seq_len=32, page_size=4, token_budget=32,
    max_prefill_chunk=8, debug=True,
)

PROMPTS = [[5, 7, 11, 2, 1], [6, 1, 9], [40, 41, 3], [3, 3, 3, 3, 8]]


def make_engine(model, params, **kw):
    opts = dict(ENGINE_KW)
    opts.update(kw)
    return InferenceEngine(model, params, **opts)


def run_batch(eng, max_new=8):
    ids = [
        eng.submit(p, SamplingParams(max_new_tokens=max_new))
        for p in PROMPTS
    ]
    eng.run()
    return [list(eng.requests[i].generated) for i in ids]


def _disarm():
    os.environ.pop(chaos.ENV_VAR, None)
    chaos._reset()


# ------------------------------------------------------------------ parity


class TestObservatoryParity:
    def test_tokens_bitwise_identical_with_observatory_on(
        self, model_and_params
    ):
        model, params = model_and_params
        eng_off = make_engine(model, params)
        ref = run_batch(eng_off)
        eng_off.close()

        eng = make_engine(model, params, timeseries=True, xla_ledger=True)
        assert run_batch(eng) == ref
        # ...and every subsystem actually observed the run.
        st = eng.timeseries.status()
        assert st["series"] > 0 and st["samples_taken"] > 0
        assert eng.regress.steps > 0
        assert eng.roofline is not None
        rep = eng.roofline.report()
        assert rep["programs"], "roofline saw no ledger programs"
        eng.close()

    def test_observatory_off_by_default(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params)
        assert eng.timeseries is None
        assert eng.regress is None
        assert eng.roofline is None
        eng.close()

    def test_engine_accepts_injected_db(self, model_and_params):
        model, params = model_and_params
        db = TimeSeriesDB(raw_capacity=16)
        eng = make_engine(model, params, timeseries=db)
        assert eng.timeseries is db
        run_batch(eng)
        assert db.status()["samples_taken"] > 0
        eng.close()


# ------------------------------------------------------------------- drill


class TestRegressionDrill:
    def test_seeded_stall_fires_within_budget_blaming_phase(
        self, model_and_params
    ):
        """Clean pass warms the decode strata and must end quiet; the
        armed pass stalls ``dispatch`` persistently and the detector must
        fire within the sample budget, blame dispatch, and the stall must
        not change a single token (a sleep is not a sample)."""
        model, params = model_and_params
        eng = make_engine(model, params, timeseries=True)
        _disarm()
        try:
            ref = run_batch(eng, max_new=12)
            assert eng.regress.alerts == 0, eng.regress.events

            os.environ[chaos.ENV_VAR] = json.dumps({
                "faults": [{
                    "kind": "slow_program",
                    "phase": "dispatch",
                    "duration": 0.05,
                    "at_step": 3,
                }],
            })
            chaos._reset()  # re-arm from the env (also clears observers)
            injected = {}

            def observer(kind, step, mode):
                if kind == "slow_program" and "regress_step" not in injected:
                    injected["regress_step"] = eng.regress.steps + 1

            chaos.add_fault_observer(observer)
            try:
                assert run_batch(eng, max_new=12) == ref
            finally:
                chaos.remove_fault_observer(observer)
        finally:
            _disarm()
            eng.close()

        assert eng.regress.alerts >= 1
        event = eng.regress.events[-1]
        assert event["attributed_phase"] == "dispatch"
        assert eng.regress.last_attribution == "dispatch"
        # Latency in raw detector steps from the first stalled step; the
        # warm stratum needs only the CUSUM crossing (2 ticks at the
        # default clip/h), slack for prefill-mixed steps at batch start.
        latency = event["step"] - injected["regress_step"] + 1
        assert 1 <= latency <= 10, (latency, event)
        assert event["stratum_samples"] > 0

    def test_acknowledge_clears_firing(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params, timeseries=True)
        _disarm()
        try:
            run_batch(eng, max_new=12)
            os.environ[chaos.ENV_VAR] = json.dumps({
                "faults": [{
                    "kind": "slow_program",
                    "phase": "schedule",
                    "duration": 0.05,
                    "at_step": 2,
                }],
            })
            chaos._reset()
            run_batch(eng, max_new=12)
        finally:
            _disarm()
        assert eng.regress.firing
        eng.regress.acknowledge()
        assert not eng.regress.firing
        assert eng.regress.alerts >= 1  # history survives the ack
        eng.close()


# --------------------------------------------------------------- endpoints


class TestTimeseriesEndpoints:
    @pytest.fixture(scope="class")
    def served(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params, timeseries=True, xla_ledger=True)
        run_batch(eng)
        server = eng.serve()
        yield eng, server
        eng.close()

    def test_timeseries_json_and_filter(self, served):
        _eng, server = served
        doc = scrape(server.url, "/timeseries")
        assert doc["series"], "empty TSDB dump"
        name = sorted(doc["series"])[0]
        one = scrape(server.url, f"/timeseries?series={name}")
        assert set(one["series"]) == {name}
        assert one["series"][name]["points"], "selected series has no points"

    def test_graphz_sparklines(self, served):
        _eng, server = served
        html = scrape(server.url, "/graphz")
        assert isinstance(html, str)
        assert "performance observatory" in html
        assert any(c in html for c in "▁▂▃▄▅▆▇█")

    def test_404_without_tsdb(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params)
        run_batch(eng)
        server = eng.serve()
        try:
            with pytest.raises(urllib.error.HTTPError):
                scrape(server.url, "/timeseries")
            with pytest.raises(urllib.error.HTTPError):
                scrape(server.url, "/graphz")
        finally:
            eng.close()


# ---------------------------------------------------- bounded-ring satellites


class TestRejectionRingEviction:
    def test_ring_evicts_oldest_at_configured_bound(self):
        adm = AdmissionController(
            max_queue=4, max_request_tokens=16, recent_rejections_max=4
        )
        for i in range(6):
            with pytest.raises(RequestTooLong):
                adm.check(
                    prompt_len=100,
                    params=SamplingParams(max_new_tokens=1),
                    queue_len=0,
                    trace_id=f"t{i}",
                )
        ring = list(adm.recent_rejections)
        assert len(ring) == 4  # storm cost is O(max), never O(rejections)
        assert [r["trace_id"] for r in ring] == ["t2", "t3", "t4", "t5"]
        assert adm.rejected_too_long == 6  # counters keep the true total

    def test_default_bound_and_validation(self):
        adm = AdmissionController(max_queue=4, max_request_tokens=16)
        assert adm.recent_rejections.maxlen == 32
        with pytest.raises(ValueError):
            AdmissionController(
                max_queue=4, max_request_tokens=16, recent_rejections_max=0
            )

    def test_trace_sampler_shares_the_eviction_contract(self):
        smp = TraceSampler(head_rate=1.0, max_kept=2)
        for t in ("t1", "t2", "t3"):
            assert smp.note_end(t)
        assert smp.kept_ids() == ["t2", "t3"]
        assert smp.evicted == 1
        assert "t1" in smp.drain_drops()  # evictee queued for pruning
