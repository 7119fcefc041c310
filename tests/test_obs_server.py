"""Observability-wire tests: the per-engine introspection server, the
strict Prometheus text-format grammar checker, device-truth XLA program
accounting, the recompile sentinel, and the fleet tooling riding the wire
(``obs_top`` rendering).

The invariant under test throughout: observability OFF keeps the fast
path; observability ON (server scraped from another thread mid-run,
ledger, armed sentinel) keeps greedy tokens bitwise-identical.
"""

import json
import socket
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu.generation import generate
from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.obs import (
    ExpositionError,
    FlightRecorder,
    MetricsRegistry,
    Tracer,
    validate_exposition,
)
from distributed_pytorch_tpu.obs.server import scrape
from distributed_pytorch_tpu.serving import InferenceEngine, SamplingParams


def tiny_lm(**kw):
    return TransformerLM(
        vocab_size=48, d_model=16, n_layers=2, n_heads=2, d_ff=32,
        dtype=jnp.float32, **kw,
    )


@pytest.fixture(scope="module")
def model_and_params():
    model = tiny_lm()
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def make_engine(model, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 4)
    kw.setdefault("token_budget", 16)
    kw.setdefault("max_prefill_chunk", 8)
    return InferenceEngine(model, params, **kw)


def offline_greedy(model, params, prompt, max_new):
    out = generate(
        model, params, jnp.asarray([prompt], jnp.int32),
        max_new_tokens=max_new, temperature=0.0, rng=jax.random.PRNGKey(0),
    )
    return np.asarray(out)[0, len(prompt):].tolist()


# ------------------------------------------------- prometheus text grammar


GOOD = (
    "# HELP engine_steps_total engine steps\n"
    "# TYPE engine_steps_total counter\n"
    "engine_steps_total 42\n"
    "# HELP queue_depth requests waiting\n"
    "# TYPE queue_depth gauge\n"
    "queue_depth 3\n"
    "# HELP ttft_seconds ttft\n"
    "# TYPE ttft_seconds summary\n"
    'ttft_seconds{quantile="0.5"} 0.01\n'
    'ttft_seconds{quantile="0.99"} 0.05\n'
    "ttft_seconds_sum 1.5\n"
    "ttft_seconds_count 100\n"
)


class TestPromTextGrammar:
    def test_valid_document_parses(self):
        fams = validate_exposition(GOOD)
        assert set(fams) == {
            "engine_steps_total", "queue_depth", "ttft_seconds"
        }
        assert fams["engine_steps_total"].type == "counter"
        assert fams["ttft_seconds"].type == "summary"
        # quantile samples + _sum + _count all land in the summary family
        assert len(fams["ttft_seconds"].samples) == 4

    def test_missing_trailing_newline(self):
        with pytest.raises(ExpositionError, match="newline"):
            validate_exposition(GOOD.rstrip("\n"))

    def test_sample_without_type(self):
        with pytest.raises(ExpositionError):
            validate_exposition("loose_metric 1\n")

    def test_help_after_type_rejected(self):
        bad = (
            "# HELP x help\n"
            "# TYPE x counter\n"
            "# HELP x late help\n"
            "x 1\n"
        )
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_family_must_be_contiguous(self):
        bad = (
            "# HELP a a\n# TYPE a counter\na 1\n"
            "# HELP b b\n# TYPE b counter\nb 2\n"
            "a 3\n"  # reopens a closed family
        )
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_bad_metric_name(self):
        with pytest.raises(ExpositionError):
            validate_exposition("# HELP 9bad x\n# TYPE 9bad counter\n9bad 1\n")

    def test_reserved_label_name(self):
        bad = '# HELP x x\n# TYPE x counter\nx{__secret="1"} 1\n'
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_duplicate_label_name(self):
        bad = '# HELP x x\n# TYPE x counter\nx{a="1",a="2"} 1\n'
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_bad_escape_in_label_value(self):
        bad = '# HELP x x\n# TYPE x counter\nx{a="tab\\t"} 1\n'
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_legal_escapes_parse(self):
        ok = (
            "# HELP x x\n# TYPE x counter\n"
            'x{a="q\\"uote",b="back\\\\slash",c="new\\nline"} 1\n'
        )
        fams = validate_exposition(ok)
        labels = fams["x"].samples[0][1]
        assert labels["a"] == 'q"uote'
        assert labels["b"] == "back\\slash"
        assert labels["c"] == "new\nline"

    def test_counter_rejects_suffixed_sample(self):
        bad = "# HELP x x\n# TYPE x counter\nx 1\nx_sum 2\n"
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_summary_rejects_bucket(self):
        bad = "# HELP x x\n# TYPE x summary\nx_bucket 1\n"
        with pytest.raises(ExpositionError):
            validate_exposition(bad)

    def test_bad_float_value(self):
        with pytest.raises(ExpositionError):
            validate_exposition("# HELP x x\n# TYPE x gauge\nx notanumber\n")

    def test_special_float_values(self):
        ok = (
            "# HELP x x\n# TYPE x gauge\n"
            'x{k="a"} NaN\nx{k="b"} +Inf\n'
        )
        validate_exposition(ok)

    def test_live_registry_output_is_valid(self):
        reg = MetricsRegistry(namespace="t")
        reg.counter_fn("events_total", lambda: 7)
        reg.gauge_fn("depth", lambda: 2.5)
        fams = validate_exposition(reg.prometheus_text())
        assert "t_events_total" in fams and "t_depth" in fams


# --------------------------------------------------------- server endpoints


@pytest.fixture(scope="class")
def served_engine(model_and_params):
    """One engine + running server shared across the read-only endpoint
    tests (compiles once; every test only GETs)."""
    model, params = model_and_params
    eng = make_engine(
        model, params, tracer=Tracer(), flight=FlightRecorder(capacity=256),
        xla_ledger=True,
    )
    rid = eng.submit([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=4))
    eng.run()
    assert eng.poll(rid).finished
    server = eng.serve()
    yield eng, server
    eng.close()


class TestIntrospectionServer:
    def test_serve_is_idempotent(self, served_engine):
        eng, server = served_engine
        assert eng.serve() is server

    def test_metrics_valid_under_strict_grammar(self, served_engine):
        _eng, server = served_engine
        body = scrape(server.url, "/metrics")
        fams = validate_exposition(body)
        assert "serving_engine_steps_total" in fams
        assert "serving_ttft_seconds" in fams
        assert "serving_xla_programs" in fams
        assert "serving_engine_recompiles_total" in fams

    def test_healthz_live(self, served_engine):
        _eng, server = served_engine
        with urllib.request.urlopen(server.url + "/healthz") as resp:
            assert resp.status == 200
            assert json.loads(resp.read())["status"] == "live"

    def test_statusz_shape(self, served_engine):
        eng, server = served_engine
        doc = scrape(server.url, "/statusz")
        for key in (
            "health", "engine", "queue_depth", "running_requests",
            "requests", "pages", "admission", "latency", "xla",
            "recompile_sentinel",
        ):
            assert key in doc, key
        assert doc["health"] == "live"
        assert doc["engine"]["steps"] == eng.metrics.engine_steps
        names = {p["name"] for p in doc["xla"]["programs"]}
        assert "decode_step" in names

    def test_trace_and_postmortem_served(self, served_engine):
        _eng, server = served_engine
        trace = scrape(server.url, "/trace")
        assert "traceEvents" in trace
        post = scrape(server.url, "/postmortem")
        assert post["reason"] == "postmortem_endpoint"

    def test_index_and_404(self, served_engine):
        _eng, server = served_engine
        index = scrape(server.url, "/")
        assert "/metrics" in index["endpoints"]
        with pytest.raises(urllib.error.HTTPError):
            scrape(server.url, "/nope")

    def test_snapshot_roundtrip_renders_valid_text(self, served_engine):
        eng, server = served_engine
        snap = scrape(server.url, "/snapshot")
        text = MetricsRegistry.render_snapshot(snap)
        fams = validate_exposition(text)
        assert "serving_engine_steps_total" in fams


class TestHealthTransitions:
    def test_live_draining_closed(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params)
        server = eng.serve()
        assert scrape(server.url, "/healthz")["status"] == "live"
        eng.stop_admission()
        # scrape() treats the 503 as an answer, not an error
        assert scrape(server.url, "/healthz")["status"] == "draining"
        assert eng.health() == "draining"
        url = server.url
        eng.close()  # stops the server too
        assert eng.health() == "closed"
        with pytest.raises(Exception):
            urllib.request.urlopen(url + "/healthz", timeout=1)


class TestServerParity:
    def test_tokens_identical_with_server_scraped_mid_run(
        self, model_and_params
    ):
        """The acceptance criterion: a server attached and hammered from
        another thread while the engine steps changes nothing about the
        greedy token streams."""
        model, params = model_and_params
        prompts = [[1, 2, 3], [7, 5, 4, 6], [9, 8], [3, 1, 4, 1, 5]]
        refs = [offline_greedy(model, params, p, 6) for p in prompts]

        eng = make_engine(model, params, xla_ledger=True)
        server = eng.serve()
        stop = threading.Event()
        seen = {"n": 0, "errors": 0}

        def hammer():
            # Generous timeout: a step that hits an XLA compile holds the
            # registry lock for seconds, and a scrape must WAIT there (that
            # blocking is the consistency guarantee), not error out.
            while not stop.is_set():
                try:
                    validate_exposition(
                        scrape(server.url, "/metrics", timeout=60.0)
                    )
                    scrape(server.url, "/statusz", timeout=60.0)
                    seen["n"] += 1
                except Exception:
                    seen["errors"] += 1

        thread = threading.Thread(target=hammer, daemon=True)
        thread.start()
        try:
            ids = [
                eng.submit(p, SamplingParams(max_new_tokens=6))
                for p in prompts
            ]
            eng.run()
            got = [eng.poll(r).generated for r in ids]
        finally:
            stop.set()
            thread.join(timeout=10)
        assert got == refs
        assert seen["n"] > 0 and seen["errors"] == 0
        eng.close()


class TestMergeRemote:
    def test_two_engines_aggregate_over_http(self, model_and_params):
        model, params = model_and_params
        engines = [make_engine(model, params) for _ in range(2)]
        servers = [eng.serve() for eng in engines]
        try:
            for eng in engines:
                rid = eng.submit(
                    [1, 2, 3], SamplingParams(max_new_tokens=3)
                )
                eng.run()
                assert eng.poll(rid).finished
            merged = MetricsRegistry.merge_remote(
                [srv.url for srv in servers]
            )
            total = sum(
                eng.metrics.tokens_generated for eng in engines
            )
            assert merged["counters"]["serving_tokens_generated_total"] == (
                total
            )
            text = MetricsRegistry.render_snapshot(merged)
            fams = validate_exposition(text)
            assert float(
                fams["serving_tokens_generated_total"].samples[0][2]
            ) == float(total)
            # reservoirs merge exactly: sample counts add across engines
            n_ttft = sum(eng.metrics.ttft.count for eng in engines)
            count = [
                float(val)
                for name, _labels, val in fams["serving_ttft_seconds"].samples
                if name.endswith("_count")
            ]
            assert count == [float(n_ttft)]
        finally:
            for eng in engines:
                eng.close()


# --------------------------------------------- xla ledger + recompile watch


class TestProgramLedger:
    def test_device_truth_recorded(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params, xla_ledger=True)
        rid = eng.submit([3, 1, 4, 1, 5], SamplingParams(max_new_tokens=4))
        eng.run()
        assert eng.poll(rid).finished
        names = {name for (name, _sig) in eng.xla.programs}
        assert "decode_step" in names
        assert any(n.startswith("prefill_step_c") for n in names)
        for rec in eng.xla.programs.values():
            assert rec.compile_seconds > 0
            assert rec.calls >= 1
        decode = next(
            rec for (name, _), rec in eng.xla.programs.items()
            if name == "decode_step"
        )
        assert decode.flops and decode.flops > 0
        assert decode.argument_bytes > 0
        # transfers were counted both ways, live bytes tracked
        assert eng.xla.bytes_h2d_total > 0 and eng.xla.bytes_d2h_total > 0
        assert eng.xla.live_bytes > 0
        meta = eng.xla.metadata()
        assert meta["bytes_h2d_total"] == eng.xla.bytes_h2d_total
        assert len(meta["programs"]) == len(eng.xla.programs)
        eng.close()

    def test_ledger_off_is_fast_path(self, model_and_params):
        model, params = model_and_params
        eng = make_engine(model, params)
        assert eng.xla is None and eng.sentinel is None
        with pytest.raises(RuntimeError, match="xla_ledger"):
            eng.arm_recompile_sentinel()
        eng.close()


class TestRecompileSentinel:
    def test_zero_at_steady_state_and_trip_on_new_shape(
        self, model_and_params
    ):
        model, params = model_and_params
        eng = make_engine(
            model, params, flight=FlightRecorder(capacity=256),
            xla_ledger=True,
        )
        # Warm: decode + prefill chunks for short prompts.
        warm = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
        eng.run()
        assert eng.poll(warm).finished
        sentinel = eng.arm_recompile_sentinel()
        assert sentinel.armed

        # Steady state: same shapes, zero trips across the whole run.
        rid = eng.submit([4, 5, 6], SamplingParams(max_new_tokens=3))
        eng.run()
        assert eng.poll(rid).finished
        assert sentinel.count == 0
        assert not sentinel.firing

        # A prompt of another length compiles nothing: every prefill width
        # exists since the first piece ran (two pieces here, 8 and 4).
        big = eng.submit(
            list(range(1, 14)), SamplingParams(max_new_tokens=2)
        )
        eng.run()
        assert eng.poll(big).finished
        assert sentinel.count == 0

        # A program the engine builds only when it is first needed, the
        # page copy of a copy-on-write, is a fresh XLA compile: exactly
        # what the sentinel exists to catch. Two continuations extend the
        # same cached partial page (one full page of 4, one token more).
        hist = [4, 5, 6] + eng.poll(rid).generated[:2]
        conts = [eng.submit(hist + [t], SamplingParams(max_new_tokens=2))
                 for t in (9, 17)]
        eng.run()
        assert all(eng.poll(c).finished for c in conts)
        assert eng.scheduler.cow_copies >= 1
        assert sentinel.count >= 1
        assert sentinel.firing
        assert any(
            "copy_page" in trip["program"] for trip in sentinel.trips
        )
        # ...and the trip is on the record everywhere it should be:
        assert eng.registry.read_counter("engine_recompiles_total") == (
            sentinel.count
        )
        events = [
            ev for ev in eng.flight.events() if ev["kind"] == "recompile"
        ]
        assert len(events) == sentinel.count
        status = sentinel.status()
        assert status["firing"] and status["count"] == sentinel.count
        sentinel.acknowledge()
        assert not sentinel.firing and sentinel.count >= 1
        # A compilation the ledger never saw trips with JAX's own name of
        # the program and the parts of its ``compile`` slice.
        seen = sentinel.count

        def a_stray_program(x):
            return x * 2 + 1

        jax.jit(a_stray_program)(jnp.ones(7))
        stray = [t for t in sentinel.trips[seen:]
                 if t["program"] == "jit(a_stray_program)"]
        assert len(stray) == 1 and stray[0]["source"] == "monitoring"
        assert stray[0]["cache"] in ("off", "miss", "hit")
        assert stray[0]["compile_seconds"] > 0 and stray[0]["trace_s"] > 0
        assert stray[0]["lower_s"] > 0
        assert sentinel.firing
        assert not any(t["program"] == "unattributed" for t in sentinel.trips)
        eng.close()
        assert not sentinel.armed  # close() disarms


# ------------------------------------------------------------ obs_top tool


class TestObsTop:
    STATUS = {
        "health": "live",
        "queue_depth": 2,
        "running_requests": 3,
        "pages": {
            "pages_free": 10, "pages_referenced": 5, "pages_cached_idle": 1,
        },
        "latency": {
            "ttft_p50_s": 0.012, "tpot_p50_s": 0.0015,
            "tpot_p95_s": 0.002, "tokens_per_sec": 123.4,
        },
        "recompile_sentinel": {"count": 1, "firing": True},
        "slo": {"firing": ["ttft_p95"]},
        "requests": [
            {
                "req_id": 7, "phase": "decoding", "slot": 0, "age_s": 1.5,
                "prompt_len": 30, "len_cached": 24, "generated": 9,
                "preempt_count": 0,
            },
        ],
    }

    def test_render_frame_plain(self):
        from tools.obs_top import render_frame

        frame = render_frame(
            [("http://e1:80", self.STATUS), ("http://e2:80", None)],
            color=False,
        )
        assert "e1:80" in frame and "e2:80" in frame
        assert "live" in frame and "down" in frame
        assert "10/5/1" in frame  # pages free/ref/idle
        assert "ttft_p95" in frame  # firing SLO surfaces by name
        assert "decoding" in frame  # request table rendered
        assert "\x1b" not in frame  # no ANSI in plain mode

    def test_render_frame_handles_empty_latency(self):
        from tools.obs_top import render_frame

        doc = {"health": "live", "queue_depth": 0, "running_requests": 0}
        frame = render_frame([("http://e:80", doc)], color=False)
        assert "live" in frame

    def test_render_frame_host_tier_columns(self):
        from tools.obs_top import render_frame

        doc = dict(self.STATUS)
        doc["hostkv"] = {
            "hostkv_pages_resident": 7,
            "hostkv_pages_capacity": 48,
        }
        # Cumulative spill counter climbing 4096 B/s; no fetch series yet
        # (the fetch cell must degrade to '-' like any missing series).
        ts = {
            "series": {
                "serving_hostkv_spill_bytes_total": {
                    "kind": "counter",
                    "points": [[0.0, 0.0], [1.0, 4096.0], [2.0, 12288.0]],
                },
            }
        }
        frame = render_frame(
            [("http://e1:80", doc)],
            color=False,
            timeseries={"http://e1:80": ts},
        )
        assert "HOST r/c" in frame and "7/48" in frame
        assert "SPILL B/s" in frame and "FETCH B/s" in frame
        # The rate sparkline renders deltas, so the climbing counter shows
        # two cells (4096 then 8192 B/s), not a monotone ramp of totals.
        lines = frame.splitlines()
        row = next(ln for ln in lines if "e1:80" in ln)
        assert "▁" in row and "█" in row  # distinct rate levels rendered

    def test_render_frame_without_host_tier_shows_dash(self):
        from tools.obs_top import render_frame

        frame = render_frame([("http://e1:80", self.STATUS)], color=False)
        row = next(
            ln for ln in frame.splitlines() if "e1:80" in ln
        )
        assert " - " in row  # HOST r/c cell degrades to '-'


class TestScrapeHardening:
    """scrape() must never wedge its caller: a peer that accepts the TCP
    connection and then never answers — the classic half-dead replica —
    has to raise within the configured timeout budget, and transient
    transport blips get exactly the bounded retry, nothing more."""

    @staticmethod
    def _black_hole():
        """A socket that accepts (kernel backlog) and never responds."""
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(5)
        return srv

    def test_accept_but_never_respond_raises_bounded(self):
        srv = self._black_hole()
        url = f"http://127.0.0.1:{srv.getsockname()[1]}"
        try:
            t0 = time.monotonic()
            with pytest.raises(OSError):
                scrape(url, "/snapshot", timeout=0.2, retries=1,
                       backoff_s=0.05)
            elapsed = time.monotonic() - t0
            # (retries+1) * timeout + backoff, with generous slack — the
            # point is "seconds, not forever".
            assert elapsed < 3.0
        finally:
            srv.close()

    def test_merge_remote_dead_peer_raises_bounded(self):
        srv = self._black_hole()
        url = f"http://127.0.0.1:{srv.getsockname()[1]}"
        try:
            t0 = time.monotonic()
            with pytest.raises(OSError):
                MetricsRegistry.merge_remote(
                    [url], timeout=0.2, retries=1, backoff_s=0.05
                )
            assert time.monotonic() - t0 < 3.0
        finally:
            srv.close()

    def test_retry_recovers_after_transport_blip(self):
        """First connection reset before any response; the bounded retry
        lands on a healthy answer."""
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(5)
        url = f"http://127.0.0.1:{srv.getsockname()[1]}"

        def serve():
            conn, _ = srv.accept()
            conn.close()  # blip: reset with no HTTP response
            conn, _ = srv.accept()
            conn.recv(65536)
            body = b'{"ok": true}'
            conn.sendall(
                b"HTTP/1.1 200 OK\r\n"
                b"Content-Type: application/json\r\n"
                b"Content-Length: " + str(len(body)).encode() + b"\r\n"
                b"\r\n" + body
            )
            conn.close()

        thread = threading.Thread(target=serve, daemon=True)
        thread.start()
        try:
            doc = scrape(url, "/snapshot", timeout=2.0, retries=1,
                         backoff_s=0.01)
            assert doc == {"ok": True}
        finally:
            thread.join(timeout=5)
            srv.close()

    def test_http_error_is_answered_not_retried(self, served_engine):
        """A served error page comes from a live server: no retry, and
        /healthz 503 still returns its JSON verdict."""
        _, server = served_engine
        with pytest.raises(urllib.error.HTTPError):
            scrape(server.url, "/nope", retries=3)
