"""Mesh-sharded serving tests: the exactness contract of the tentpole.

The conftest forces 8 virtual CPU devices, so every geometry the ISSUE
names runs here: (1,1) must be BITWISE identical to the unsharded engine
(the sharded factories must add no annotation the unsharded path lacks),
and (1,8)/(2,4) must be greedy-token identical across the full toggle
matrix (prefix_cache x overlap x speculative) — sharded reductions may
reorder float accumulation, argmax must not care at these scales. Plus
the satellites that ride the mesh: snapshot geometry fingerprinting,
head-divisibility refusal, mesh gauges/labels, and pool-named allocator
leak messages.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from distributed_pytorch_tpu.models.transformer import TransformerLM
from distributed_pytorch_tpu.obs import Tracer
from distributed_pytorch_tpu.serving import (
    EngineSnapshot,
    InferenceEngine,
    PagedBlockAllocator,
    SamplingParams,
    drain_engine,
    make_serving_mesh,
    mesh_fingerprint,
    restore_engine,
)
from distributed_pytorch_tpu.serving.mesh import validate_kv_heads

# Every sharded dim divisible by 8: n_heads 8 (head_dim 4), d_model 32,
# d_ff 64, vocab 64 — so the same model serves every geometry up to 1x8.
MESH_LM = dict(
    vocab_size=64, d_model=32, n_layers=2, n_heads=8, d_ff=64,
    dtype=jnp.float32,
)

PROMPTS = [[1, 2, 3, 4], [5, 6, 7], [1, 2, 3, 9, 10]]
MAX_NEW = 5

ENGINE_KW = dict(
    max_slots=4, max_seq_len=32, page_size=8, token_budget=32,
    max_prefill_chunk=16,
)


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(**MESH_LM)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def draft_and_params():
    draft = TransformerLM(
        vocab_size=64, d_model=16, n_layers=1, n_heads=8, d_ff=32,
        dtype=jnp.float32,
    )
    dparams = draft.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return draft, dparams


def run_engine(model, params, *, mesh=None, prefix=True, overlap=True,
               spec=None, temperature=0.0, seed=0, tracer=None):
    kw = dict(ENGINE_KW)
    if spec is not None:
        draft, dparams = spec
        kw.update(draft_model=draft, draft_params=dparams, gamma=3)
    eng = InferenceEngine(
        model, params, mesh=mesh, prefix_cache=prefix, overlap=overlap,
        tracer=tracer, **kw,
    )
    ids = [
        eng.submit(
            p,
            SamplingParams(
                max_new_tokens=MAX_NEW, temperature=temperature, seed=seed
            ),
        )
        for p in PROMPTS
    ]
    eng.run()
    out = [eng.poll(i).generated for i in ids]
    eng.close()
    return out, eng


@pytest.fixture(scope="module")
def baseline_greedy(model_and_params):
    """Unsharded greedy output — the single truth every geometry and every
    toggle combination must reproduce (toggle-invariance of the unsharded
    engine itself is pinned by test_serving.py)."""
    out, _ = run_engine(*model_and_params)
    return out


# ------------------------------------------------------------ (1,1) bitwise


class TestMeshOneByOne:
    def test_greedy_bitwise(self, model_and_params, baseline_greedy):
        out, eng = run_engine(*model_and_params, mesh=make_serving_mesh(1, 1))
        assert out == baseline_greedy
        assert eng.mesh_fingerprint == "1x1"

    def test_sampled_bitwise(self, model_and_params):
        """temperature > 0 draws through the same categorical — a (1,1)
        mesh must reproduce the exact sampled stream, not just argmax."""
        base, _ = run_engine(*model_and_params, temperature=0.9, seed=7)
        out, _ = run_engine(
            *model_and_params, mesh=make_serving_mesh(1, 1),
            temperature=0.9, seed=7,
        )
        assert out == base


# ------------------------------------------------- toggle matrix, 1x8 / 2x4


@pytest.mark.parametrize("shape", [(1, 8), (2, 4)], ids=["1x8", "2x4"])
@pytest.mark.parametrize("prefix", [False, True], ids=["nocache", "cache"])
@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
class TestMeshToggleMatrix:
    def test_greedy_parity_plain(
        self, model_and_params, baseline_greedy, shape, prefix, overlap
    ):
        out, eng = run_engine(
            *model_and_params, mesh=make_serving_mesh(*shape),
            prefix=prefix, overlap=overlap,
        )
        assert out == baseline_greedy
        # decode + the ONE prefill width a cap of 16 has (+ copy_page
        # where a shared page is extended)
        assert eng._sharded_programs >= 2

    def test_greedy_parity_speculative(
        self, model_and_params, draft_and_params, baseline_greedy, shape,
        prefix, overlap,
    ):
        out, eng = run_engine(
            *model_and_params, mesh=make_serving_mesh(*shape),
            prefix=prefix, overlap=overlap, spec=draft_and_params,
        )
        assert out == baseline_greedy
        assert eng.speculative


# -------------------------------------------------------- elastic round-trip


class TestShardedElastic:
    def _mid_run_snapshot(self, model, params, mesh):
        eng = InferenceEngine(model, params, mesh=mesh, **ENGINE_KW)
        ids = [
            eng.submit(p, SamplingParams(max_new_tokens=MAX_NEW))
            for p in PROMPTS
        ]
        for _ in range(3):
            eng.step()
        snap = drain_engine(eng)
        eng.close()
        return snap, ids

    def test_drain_restore_roundtrip(
        self, model_and_params, baseline_greedy
    ):
        model, params = model_and_params
        snap, ids = self._mid_run_snapshot(
            model, params, make_serving_mesh(2, 4)
        )
        assert snap.mesh == "2x4"
        # Codec round-trip preserves the fingerprint.
        snap = EngineSnapshot.from_json(snap.to_json())
        assert snap.mesh == "2x4"
        eng2 = InferenceEngine(
            model, params, mesh=make_serving_mesh(2, 4), **ENGINE_KW
        )
        restored = restore_engine(eng2, snap)
        assert set(restored) == {r.req_id for r in snap.requests}
        eng2.run()
        out = [eng2.poll(i).generated for i in ids]
        eng2.close()
        assert out == baseline_greedy

    def test_restore_refuses_geometry_mismatch(self, model_and_params):
        model, params = model_and_params
        snap, _ = self._mid_run_snapshot(
            model, params, make_serving_mesh(2, 4)
        )
        eng_unsharded = InferenceEngine(model, params, **ENGINE_KW)
        with pytest.raises(ValueError, match="2x4 mesh.*1x1"):
            restore_engine(eng_unsharded, snap)
        eng_unsharded.close()

    def test_snapshot_backcompat_missing_mesh_field(self):
        """Version-1 snapshots written before mesh sharding existed carry
        no ``mesh`` key; they must decode as unsharded, not crash."""
        snap = EngineSnapshot(
            version=1, page_size=8, max_seq_len=32, top_k=0, top_p=0.0,
            speculative=False, next_id=0, requests=(),
        )
        doc = json.loads(snap.to_json())
        del doc["mesh"]
        old = EngineSnapshot.from_json(json.dumps(doc))
        assert old.mesh == "1x1"


# ------------------------------------------------------------- validation


class TestMeshValidation:
    def test_head_divisibility_refused(self, model_and_params):
        bad = TransformerLM(
            vocab_size=64, d_model=32, n_layers=1, n_heads=2, d_ff=64,
            dtype=jnp.float32,
        )
        bp = bad.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
        with pytest.raises(ValueError, match="Hkv.*model"):
            InferenceEngine(
                bad, bp, mesh=make_serving_mesh(1, 8), **ENGINE_KW
            )

    def test_validate_kv_heads_direct(self):
        mesh = make_serving_mesh(1, 8)
        good = TransformerLM(**MESH_LM)
        validate_kv_heads(good, mesh)  # no raise
        validate_kv_heads(good, None)  # unsharded: never raises

    def test_mesh_needs_enough_devices(self):
        with pytest.raises(ValueError, match="devices"):
            make_serving_mesh(4, 4)

    def test_fingerprint(self):
        assert mesh_fingerprint(None) == "1x1"
        assert mesh_fingerprint(make_serving_mesh(2, 4)) == "2x4"


# ------------------------------------------------------------ observability


class TestMeshObservability:
    def test_axis_gauges_sharded(self, model_and_params):
        model, params = model_and_params
        eng = InferenceEngine(
            model, params, mesh=make_serving_mesh(2, 4), **ENGINE_KW
        )
        i = eng.submit([1, 2, 3], SamplingParams(max_new_tokens=3))
        eng.run()
        g = eng.registry.snapshot()["gauges"]
        assert g["serving_data_axis_size"] == 2
        assert g["serving_model_axis_size"] == 4
        assert g["serving_mesh_2x4_info"] == 1.0
        # decode + at least one prefill bucket (programs are lazily
        # compiled — copy_page only exists once a CoW copy happens).
        assert g["serving_sharded_program_count"] >= 2
        assert eng.poll(i).finished
        eng.close()

    def test_axis_gauges_unsharded(self, model_and_params):
        eng = InferenceEngine(*model_and_params, **ENGINE_KW)
        g = eng.registry.snapshot()["gauges"]
        assert g["serving_data_axis_size"] == 1
        assert g["serving_model_axis_size"] == 1
        assert g["serving_sharded_program_count"] == 0
        assert g["serving_mesh_1x1_info"] == 1.0
        eng.close()

    def test_tracer_process_name_carries_mesh(self, model_and_params):
        model, params = model_and_params
        tracer = Tracer()
        out, _ = run_engine(
            model, params, mesh=make_serving_mesh(2, 4), tracer=tracer
        )
        meta = tracer.to_perfetto()["traceEvents"][0]
        assert meta["name"] == "process_name"
        assert meta["args"]["name"] == "engine [mesh 2x4]"

    def test_tracer_process_name_unsharded_unchanged(self, model_and_params):
        tracer = Tracer()
        run_engine(*model_and_params, tracer=tracer)
        meta = tracer.to_perfetto()["traceEvents"][0]
        assert meta["args"]["name"] == "engine"


# -------------------------------------------------- allocator pool naming


class TestAllocatorPoolNames:
    def test_quiescent_message_names_pools(self):
        alloc = PagedBlockAllocator(4)
        alloc.pool_names = ("target", "draft")
        alloc.allocate(2)
        with pytest.raises(AssertionError, match="target/draft"):
            alloc.assert_quiescent()

    def test_default_single_pool_name(self):
        alloc = PagedBlockAllocator(4)
        alloc.allocate(1)
        with pytest.raises(AssertionError, match=r"pool\(s\) target"):
            alloc.assert_quiescent()

    def test_engine_wires_pool_names(
        self, model_and_params, draft_and_params
    ):
        eng = InferenceEngine(*model_and_params, **ENGINE_KW)
        assert eng.allocator.pool_names == ("target",)
        eng.close()
        draft, dparams = draft_and_params
        eng = InferenceEngine(
            *model_and_params, draft_model=draft, draft_params=dparams,
            gamma=2, **ENGINE_KW,
        )
        assert eng.allocator.pool_names == ("target", "draft")
        eng.close()
