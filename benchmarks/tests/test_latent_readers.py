"""The readers of a ``serve_latent_moe`` cell's per-layer metrics: HOW the
latent attention's operations are recognised (``harness/latent.py``), the
readers' arithmetic on counters a test can reckon by hand, and both on a
recorded trace where there is one (``data/latent_trace_recorded.json``: some
engine steps of ``deepseek-v2-lite-docqa``'s traced window on the v5e, cut by
``record_latent_trace.py``)."""

import importlib.util
import json
import os
import types

import pytest

from harness import hybrid, latent, peaks

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
RECORDED = os.path.join(HERE, "data", "latent_trace_recorded.json")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs", "deepseek-v2-lite.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("text, kind", [
    # the kernel, named by the program, as the compiler numbers it
    ("%attention._latent_decode_step.27 = bf16[32,16,512]{2,1,0:T(8,128)(2,1)S(1)} "
     "custom-call(...)", "decode"),
    ("%attention._latent_decode_step = bf16[32,16,512] custom-call(...)", "decode"),
    # a prefill piece's walk over its row's pages, a block at a time
    ("%while.3 = (s32[], f32[1,16,64], f32[1,16,64], f32[1,16,64,512]) "
     "while(...)", "rest"),
    # the new tokens' [c | k_pe | 0] written into the pool; the page copy
    ("%scatter_fusion.5 = bf16[10241,16,640]{2,1,0:T(8,128)(2,1)} fusion(...)",
     "rest"),
    ("%dynamic-update-slice_fusion.2 = bf16[10241,16,640] fusion(...)", "rest"),
    # the absorbed query padded to the pool's lanes
    ("%fusion.9 = bf16[32,16,640] fusion(...)", "rest"),
    # NOT the latent attention's: the other kernel, the experts, projections
    ("%attention._paged_decode_step.3 = bf16[32,24,128] custom-call(...)", None),
    ("%ragged-dot-none.7 = f32[192,2816] custom-call(...)", None),
    ("%fusion.88 = bf16[32,2048] fusion(...)", None),
    ("%convert_reduce_fusion.4 = f32[32,102400] fusion(...)", None),
    ("%fusion.12 = bf16[32,16,576] fusion(...)", None),
])
def test_how_an_operation_is_recognised(cfg, text, kind):
    assert latent.pool_width(cfg) == 640
    assert latent.kind_of(text, 640) == kind


def step(t_s, **args):
    return {"name": "step", "ph": "X",
            "args": dict(args, perf_counter_ns=int(t_s * 1e9))}


def test_decode_roofline_share_by_hand(cfg):
    """Two traced steps of 32 rows over 8,800 visible tokens each, of which
    140,000 are distinct: by bytes 140,000 x 1,152 B / 819 GB/s = 196.9 us a
    layer and step, by FLOPs 281,600 x 16 x 1,088 x 2 / 197 TFLOP/s = 49.8
    us: memory binds. 27 layers, 2 steps, over 20 ms of kernel time."""
    ctx = dict(
        cfg=cfg, device_kind="TPU v5 lite", traced=(10.0, 11.0),
        engine_events=[
            step(9.5, decode_kv_tokens_visible=1, decode_kv_tokens_distinct=1),
            step(10.1, decode_kv_tokens_visible=281_600,
                 decode_kv_tokens_distinct=140_000),
            step(10.5, decode_kv_tokens_visible=281_600,
                 decode_kv_tokens_distinct=140_000),
            step(10.7, decode_rows=0),  # a step that dispatched no decode
        ],
        mla_ops={"window": (0, 10**9), "decode": [(0, 12_000_000), (5 * 10**8, 8_000_000)],
                 "rest": [(10**8, 1_000_000)]})
    counted = latent.traced_decode_counters(ctx)
    assert counted == {"visible": 563_200, "distinct": 280_000, "steps": 2}
    least = 27 * 280_000 * 1152 / 819e9
    assert reader("mla.decode_roofline_share").read(ctx) == pytest.approx(
        100 * least / 0.020)
    ref = hybrid.reference_for(cfg)
    assert (ref.latent_decode_flops(cfg, 563_200) / peaks.peaks_for(
        "TPU v5 lite")["bf16_flops"]) < 280_000 * 1152 / 819e9


def test_device_ms_per_step_is_the_union_over_the_traced_steps(cfg):
    ctx = dict(
        traced=(0.0, 1.0), step_rows=[(0.1, 0.2), (0.3, 0.4), (1.5, 1.6)],
        counters={"plans": [{"decode_rows": 1}] * 3}, engine_events=[],
        mla_ops={"window": (0, 10**9),
                 "decode": [(0, 4_000_000), (2_000_000, 4_000_000)],
                 "rest": [(5_000_000, 3_000_000), (2 * 10**9, 10**6)]})
    # [0, 6) and [5, 8) ms merge to 8 ms; the last lies outside the window;
    # two steps started in it.
    assert reader("mla.device_ms_per_step").read(ctx) == pytest.approx(4.0)


def test_prefix_hit_share_counts_first_admissions(cfg):
    def admit(prompt, cached, **kw):
        return {"name": "admit", "args": dict(
            kw, prompt_tokens=prompt, cached_tokens=cached)}

    events = [admit(8256, 8192), admit(5000, 4976),
              admit(300, 320, readmission=True),  # its own pages: left out
              {"name": "admit", "args": {"cached_tokens": 5}}]  # an old program's
    assert reader("kv.prefix_hit_share").read(
        {"engine_events": events}) == pytest.approx(
            100 * (8192 + 4976) / (8256 + 5000))


@pytest.mark.parametrize("name", [
    "mla.device_ms_per_step", "mla.decode_roofline_share",
    "kv.prefix_hit_share"])
def test_nothing_to_read_is_none(cfg, name):
    """The parent's program, a CPU run: no counters, no operations."""
    ctx = dict(cfg=cfg, device_kind="TPU v5 lite", traced=(0.0, 1.0),
               step_rows=[(0.1, 0.2)], counters={"plans": [{}]},
               engine_events=[step(0.1, decode_kv_tokens_visible=5),
                              {"name": "admit", "args": {"cached_tokens": 0}}],
               mla_ops={"window": None, "decode": [], "rest": []})
    assert reader(name).read(ctx) is None
    assert reader(name).read({"cfg": cfg}) is None


def test_serve_min_bytes_counts_no_more_cached_tokens_than_the_pool(cfg):
    """32 rows x 8,800 tokens is 281,600 contexts but 163,840 tokens of pool:
    rows that share a document could be served by one read of it."""
    ref = hybrid.reference_for(cfg)
    assert ref.pool_tokens(cfg) == 163_840
    at_pool = ref.serve_min_bytes(cfg, 32, 0, 163_840, 0)
    assert ref.serve_min_bytes(cfg, 32, 0, 281_600, 0) == at_pool
    assert ref.serve_min_bytes(cfg, 32, 0, 100_000, 0) < at_pool
    assert ref.latent_bytes_per_token(cfg) == 27 * 1152
    # The weights a step of 32 rows reads: all but 0.34 of 8 held experts.
    assert ref.experts_reached(cfg, 32) == pytest.approx(7.657, abs=1e-3)
    assert ref.held_parameters(cfg) == pytest.approx(3.111e9, rel=1e-3)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no trace was recorded on the chip yet")
def test_the_recorded_trace_holds_the_kernel_and_the_rest(cfg):
    with open(RECORDED) as f:
        recorded = json.load(f)
    ops = latent.classify(recorded["events"], cfg)
    window = tuple(recorded["window"])
    decode = hybrid.clipped_union(ops["decode"], window)
    # One call of the kernel a layer and decode program: whole programs of 27.
    assert len(ops["decode"]) >= 27
    names = {n.split(" = ")[0].split(".")[0] for n, _, _ in recorded["events"]
             if latent.kind_of(n, 640) == "decode"}
    assert names == {"%attention"}
    busy = sum(e - s for s, e in decode)
    assert 0 < busy < window[1] - window[0]
    ctx = dict(traced=(0.0, 1.0), step_rows=[(0.5, 0.6)],
               counters={"plans": [{}]}, engine_events=[],
               mla_ops=dict(ops, window=window))
    ms = reader("mla.device_ms_per_step").read(ctx)
    assert ms >= busy / 1e6
