"""Weight-only int8 quantization: numerics, tree mapping, decode parity."""

import pytest
import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np
import optax

from distributed_pytorch_tpu.models import TransformerLM
from distributed_pytorch_tpu.ops.quant import (
    QuantTensor,
    TRANSFORMER_QUANT_RULES,
    dequantize,
    dequantize_pytree,
    quantize_int8,
    quantize_pytree,
    quantized_bytes,
)


def tiny_lm(**kw):
    return TransformerLM(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64, **kw
    )


def lm_params(model=None, seed=0):
    model = model or tiny_lm()
    tokens = jnp.zeros((2, 16), jnp.int32)
    return model.init(jax.random.PRNGKey(seed), tokens)["params"]


def trained_tiny_lm(steps=30):
    """Tiny LM trained on a repeating pattern so logits carry real margins
    (random-init params have near-tie argmax that quantization noise flips).
    Returns (model, params, the training sequences)."""
    from distributed_pytorch_tpu.training.losses import (
        softmax_cross_entropy_loss,
    )
    from distributed_pytorch_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    model = tiny_lm()
    seq = np.tile(np.arange(16, dtype=np.int32), (8, 2))  # [8, 32]
    inputs, targets = seq[:, :-1], seq[:, 1:]
    state = create_train_state(model, optax.adam(1e-2), inputs)
    step = make_train_step(
        model.apply, optax.adam(1e-2), softmax_cross_entropy_loss
    )
    for _ in range(steps):
        state, _ = step(state, (jnp.asarray(inputs), jnp.asarray(targets)))
    return model, state.params, seq


class TestQuantizeInt8:
    def test_roundtrip_error_bound(self):
        rng = np.random.default_rng(0)
        w = (rng.standard_normal((256, 128)) * 0.05).astype(np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))
        assert qt.q.dtype == jnp.int8
        assert qt.scale.shape == (1, 128)
        back = np.asarray(dequantize(qt, jnp.float32))
        rel_rms = np.sqrt(np.mean((back - w) ** 2)) / np.sqrt(np.mean(w**2))
        assert rel_rms < 0.01

    def test_per_channel_scales_are_independent(self):
        # One huge column must not blow up the quantization of the others.
        w = np.full((64, 4), 0.01, np.float32)
        w[:, 3] = 100.0
        qt = quantize_int8(jnp.asarray(w), (0,))
        back = np.asarray(dequantize(qt, jnp.float32))
        np.testing.assert_allclose(back[:, 0], w[:, 0], rtol=0.01)
        np.testing.assert_allclose(back[:, 3], w[:, 3], rtol=0.01)

    def test_zero_channel_safe(self):
        w = np.zeros((16, 3), np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))
        assert np.all(np.isfinite(np.asarray(qt.scale)))
        np.testing.assert_array_equal(np.asarray(dequantize(qt)), 0)

    def test_3d_contract_dims(self):
        rng = np.random.default_rng(1)
        w = (rng.standard_normal((32, 4, 8)) * 0.1).astype(np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))  # QKV-style [d_model, H, Dh]
        assert qt.scale.shape == (1, 4, 8)
        qt2 = quantize_int8(jnp.asarray(w), (0, 1))  # out-style contraction
        assert qt2.scale.shape == (1, 1, 8)


class TestQuantizePytree:
    @pytest.mark.slow
    def test_rules_match_matmul_kernels_only(self):
        params = lm_params()
        qtree = quantize_pytree(params, TRANSFORMER_QUANT_RULES)
        flat = jtu.tree_flatten_with_path(
            qtree, is_leaf=lambda x: isinstance(x, QuantTensor)
        )[0]
        quantized_paths = {
            "/".join(str(getattr(e, "key", e)) for e in path)
            for path, leaf in flat
            if isinstance(leaf, QuantTensor)
        }
        assert any("attention/query/kernel" in p for p in quantized_paths)
        assert any("mlp/up/kernel" in p for p in quantized_paths)
        assert any("lm_head/kernel" in p for p in quantized_paths)
        # Embedding, biases and LayerNorm params pass through untouched.
        assert not any("embed" in p for p in quantized_paths)
        assert not any("bias" in p for p in quantized_paths)
        assert not any("ln_" in p for p in quantized_paths)

    @pytest.mark.slow
    def test_dequantize_pytree_restores_structure_and_values(self):
        params = lm_params()
        qtree = quantize_pytree(params)
        back = dequantize_pytree(qtree, jnp.float32)
        assert jtu.tree_structure(back) == jtu.tree_structure(params)
        for (path, a), (_, b) in zip(
            jtu.tree_flatten_with_path(params)[0],
            jtu.tree_flatten_with_path(back)[0],
        ):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            denom = np.sqrt(np.mean(a**2)) or 1.0
            assert np.sqrt(np.mean((a - b) ** 2)) / denom < 0.01, path

    def test_memory_reduction(self):
        qtree = quantize_pytree(lm_params())
        q_bytes, orig = quantized_bytes(qtree)
        assert q_bytes < 0.3 * orig  # ~4x minus the scale overhead


class TestQuantizedDecodeParity:
    @pytest.mark.slow
    def test_greedy_decode_matches_f32(self):
        """Weight-only int8 on a trained-ish model: greedy continuations must
        match the full-precision path token for token (quant noise ~0.3% RMS
        is far below typical logit margins on a structured task)."""
        from distributed_pytorch_tpu.generation import generate

        model, params, seq = trained_tiny_lm()
        prompt = jnp.asarray(seq[:2, :8], jnp.int32)
        full = generate(model, params, prompt, 12)
        quant = generate(model, params, prompt, 12, quantize=True)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(quant))

    def test_prequantized_tree_accepted(self):
        from distributed_pytorch_tpu.generation import generate

        model = tiny_lm()
        params = lm_params(model)
        prompt = jnp.asarray(
            np.random.default_rng(3).integers(0, 64, (2, 6)), jnp.int32
        )
        fresh = generate(model, params, prompt, 5, quantize=True)
        pre = generate(
            model, quantize_pytree(params), prompt, 5, quantize=True
        )
        np.testing.assert_array_equal(np.asarray(fresh), np.asarray(pre))

    @pytest.mark.slow
    def test_quantized_tensor_parallel_decode_parity(self):
        """int8 decode composes with megatron TP shardings: the int8 kernels
        keep the kernel's placement, the per-channel scales drop the
        contracted axes, and the tokens match the unquantized single-device
        run of the same quantized weights."""
        from jax.sharding import NamedSharding
        from distributed_pytorch_tpu.generation import generate
        from distributed_pytorch_tpu.parallel.mesh import make_mesh
        from distributed_pytorch_tpu.parallel.partitioning import (
            TRANSFORMER_TP_RULES,
            make_param_specs,
        )

        model = tiny_lm()
        params = lm_params(model)
        prompt = jnp.asarray(
            np.random.default_rng(11).integers(0, 64, (4, 5)), jnp.int32
        )
        single = generate(model, params, prompt, 6, quantize=True)

        mesh = make_mesh({"data": 4, "tensor": 2})
        specs = make_param_specs(params, TRANSFORMER_TP_RULES, mesh=mesh)
        shardings = jtu.tree_map(lambda s: NamedSharding(mesh, s), specs)
        sharded = generate(
            model,
            params,
            prompt,
            6,
            quantize=True,
            mesh=mesh,
            param_shardings=shardings,
        )
        np.testing.assert_array_equal(np.asarray(sharded), np.asarray(single))


class TestQuantizedKVCache:
    @pytest.mark.slow
    def test_int8_cache_greedy_parity(self):
        """Per-(token, head) int8 KV cache: greedy continuations on a trained
        model match the bf16-cache path token for token."""
        from distributed_pytorch_tpu.generation import generate

        model, params, seq = trained_tiny_lm()
        prompt = jnp.asarray(seq[:2, :8], jnp.int32)
        full = generate(model, params, prompt, 12)
        q = generate(model, params, prompt, 12, quantized_cache=True)
        np.testing.assert_array_equal(np.asarray(full), np.asarray(q))

    def test_cache_buffers_are_int8(self):
        model = tiny_lm().clone(decode=True, quantized_cache=True)
        cache = model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 12), jnp.int32)
        )["cache"]
        flat = jtu.tree_flatten_with_path(cache)[0]
        kinds = {
            "/".join(str(getattr(e, "key", e)) for e in path): leaf
            for path, leaf in flat
        }
        k = next(v for p, v in kinds.items() if p.endswith("cached_key"))
        s = next(v for p, v in kinds.items() if p.endswith("key_scale"))
        assert k.dtype == jnp.int8 and k.shape == (2, 12, 4, 8)
        assert s.dtype == jnp.float32 and s.shape == (2, 12, 4)

    @pytest.mark.slow
    def test_composes_with_weight_quant_and_mesh(self):
        from distributed_pytorch_tpu.generation import generate
        from distributed_pytorch_tpu.parallel.mesh import make_mesh

        model, params, seq = trained_tiny_lm()
        prompt = jnp.asarray(seq[:8, :8], jnp.int32)
        single = generate(
            model, params, prompt, 8, quantize=True, quantized_cache=True
        )
        mesh = make_mesh({"data": 8})
        sharded = generate(
            model, params, prompt, 8, quantize=True, quantized_cache=True,
            mesh=mesh,
        )
        np.testing.assert_array_equal(np.asarray(single), np.asarray(sharded))


class TestDecodeByteAccounting:
    """Structural proof (no hardware needed): XLA's own cost analysis of
    the compiled decode program shows the int8 KV cache reads fewer bytes —
    a storage-level saving, so it holds on every backend. (The WEIGHT-quant
    traffic saving is fusion-dependent — the CPU backend materializes the
    dequantized weights instead of fusing the convert into the dot — so a CPU
    byte count cannot verify it.) The fori_loop body is counted once, so this
    is per-step traffic."""

    @staticmethod
    def _body_bytes(model, params, batch, total_len):
        from distributed_pytorch_tpu.generation import _compiled_run

        decode = model.clone(decode=True)
        abstract = jax.eval_shape(
            decode.init,
            jax.random.PRNGKey(0),
            jnp.zeros((batch, total_len), jnp.int32),
        )["cache"]
        cache = jtu.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), abstract)
        tokens = jnp.zeros((batch, total_len), jnp.int32)
        lengths = jnp.full((batch,), 4, jnp.int32)
        rng = jax.random.PRNGKey(0)
        run = _compiled_run(decode, total_len, 0.0, 0)
        analysis = run.lower(
            params, tokens, cache, lengths, rng
        ).compile().cost_analysis()
        if isinstance(analysis, list):
            analysis = analysis[0]
        return float(analysis["bytes accessed"])

    @pytest.mark.slow
    def test_int8_cache_cuts_program_bytes(self):
        # The cache dominates this shape (tiny model, B=4, T=256 -> ~2 MB of
        # bf16 KV cache vs ~100 KB of weights).
        kw = dict(
            vocab_size=128, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            dtype=jnp.bfloat16,
        )
        params = TransformerLM(**kw).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        bf16 = jtu.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            params,
        )
        full = self._body_bytes(
            TransformerLM(**kw), bf16, batch=4, total_len=256
        )
        quant = self._body_bytes(
            TransformerLM(**kw, quantized_cache=True), bf16, batch=4,
            total_len=256,
        )
        assert quant < 0.75 * full, (quant, full)


class TestQuantMatmulKernel:
    """Pallas int8-weight matmul: the kernel's VMEM dequant must match the
    XLA dequant + matmul reference (interpret mode runs the real kernel
    logic on CPU)."""

    def _case(self, b, k, n, block_n=128, seed=0):
        from distributed_pytorch_tpu.ops.quant_matmul import quant_matmul

        rng = np.random.default_rng(seed)
        x = jnp.asarray(
            rng.standard_normal((b, k)) * 0.5, jnp.float32
        )
        w = (rng.standard_normal((k, n)) * 0.05).astype(np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))
        ref = x @ dequantize(qt, jnp.float32)
        out = quant_matmul(x, qt, block_n=block_n, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_matches_dequant_reference(self):
        self._case(b=8, k=256, n=256)

    def test_row_padding(self):
        self._case(b=3, k=128, n=256)  # B below the f32 sublane multiple

    def test_multi_block(self):
        self._case(b=8, k=128, n=512, block_n=128)

    def test_fallback_on_indivisible_n(self):
        from distributed_pytorch_tpu.ops.quant_matmul import quant_matmul

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
        w = (rng.standard_normal((64, 96)) * 0.1).astype(np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))
        out = quant_matmul(x, qt, block_n=512)  # 96 % 512 != 0 -> XLA path
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(x @ dequantize(qt, jnp.float32)),
            rtol=1e-5,
        )

    def test_rejects_wrong_quant_layout(self):
        import pytest as _pytest

        from distributed_pytorch_tpu.ops.quant_matmul import quant_matmul

        w = jnp.ones((8, 4, 4), jnp.float32)
        qt = quantize_int8(w, (0,))
        with _pytest.raises(ValueError, match="2-D"):
            quant_matmul(jnp.ones((2, 8), jnp.float32), qt)


class TestMoEQuantCoverage:
    """Round-3 ADVICE: MoE expert kernels are the bulk of an MoE model's
    params — the rules must cover them, and generate(quantize=True) must
    report, not hide, poor rule coverage."""

    def _moe_params(self):
        model = TransformerLM(
            vocab_size=64, d_model=32, n_layers=2, n_heads=4, d_ff=64,
            n_experts=4, moe_every=1,
        )
        tokens = jnp.zeros((2, 16), jnp.int32)
        return model.init(jax.random.PRNGKey(0), tokens)["params"]

    @pytest.mark.slow
    def test_expert_kernels_quantized(self):
        from distributed_pytorch_tpu.ops.quant import quant_coverage

        params = self._moe_params()
        qtree = quantize_pytree(params, TRANSFORMER_QUANT_RULES)
        flat = jtu.tree_flatten_with_path(
            qtree, is_leaf=lambda x: isinstance(x, QuantTensor)
        )[0]
        quantized_paths = {
            "/".join(str(getattr(e, "key", e)) for e in path)
            for path, leaf in flat
            if isinstance(leaf, QuantTensor)
        }
        assert any("moe/up_kernel" in p for p in quantized_paths)
        assert any("moe/down_kernel" in p for p in quantized_paths)
        # The float32-softmax router stays full precision.
        assert not any("router" in p for p in quantized_paths)
        # With experts covered, the matched fraction is the bulk of params.
        assert quant_coverage(qtree) > 0.5

    def test_expert_quant_numerics(self):
        params = self._moe_params()
        qtree = quantize_pytree(params, TRANSFORMER_QUANT_RULES)
        back = dequantize_pytree(qtree, jnp.float32)
        for (path, a), (_, b) in zip(
            jtu.tree_flatten_with_path(params)[0],
            jtu.tree_flatten_with_path(back)[0],
        ):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            denom = np.sqrt(np.mean(a**2)) or 1.0
            assert np.sqrt(np.mean((a - b) ** 2)) / denom < 0.01, path

    def test_coverage_warning_on_unmatched_tree(self):
        import warnings

        from distributed_pytorch_tpu.generation import generate

        model = tiny_lm()
        # A param tree whose paths the rules cannot match (as if from a
        # model family the rule table doesn't know).
        foreign = {"encoder": {"w_in": jnp.ones((32, 64), jnp.float32)}}
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            try:
                generate(
                    model,
                    foreign,
                    jnp.zeros((1, 4), jnp.int32),
                    1,
                    quantize=True,
                )
            except Exception:
                pass  # apply fails on the foreign tree; the warning fires first
        assert any("matched only" in str(w.message) for w in caught)


class TestQuantMatmulKTiling:
    """K is tiled (grid dim 1) with in-place accumulation; shapes no tile
    divides fall back to the XLA path (round-3 ADVICE: whole-K-in-VMEM)."""

    def _ref_and_out(self, b, k, n, **kw):
        from distributed_pytorch_tpu.ops.quant_matmul import quant_matmul

        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((b, k)) * 0.5, jnp.float32)
        w = (rng.standard_normal((k, n)) * 0.05).astype(np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))
        ref = x @ dequantize(qt, jnp.float32)
        out = quant_matmul(x, qt, interpret=True, **kw)
        return np.asarray(ref), np.asarray(out)

    def test_multiple_k_tiles(self):
        # 384 = 3 x 128: smallest candidate divides, so 3 accumulation steps.
        ref, out = self._ref_and_out(b=4, k=384, n=512, block_n=128)
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)

    def test_large_k_tile_selection(self):
        # 2048 divides: single biggest tile; exercises candidate ordering.
        ref, out = self._ref_and_out(b=2, k=2048, n=128, block_n=128)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_fallback_on_unaligned_k(self):
        from distributed_pytorch_tpu.ops.quant_matmul import quant_matmul

        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((4, 100)), jnp.float32)
        w = (rng.standard_normal((100, 128)) * 0.1).astype(np.float32)
        qt = quantize_int8(jnp.asarray(w), (0,))
        out = quant_matmul(x, qt, block_n=128)  # 100 has no 128-mult tile
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(x @ dequantize(qt, jnp.float32)),
            rtol=1e-5,
        )
