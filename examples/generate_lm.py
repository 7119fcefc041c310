"""Text generation from a trained (or randomly initialized) TransformerLM.

The inference-side rung — no reference analog (the reference ladder stops at
training, SURVEY.md §0); a complete framework needs the sampling path. The
whole decode is ONE compiled ``lax.fori_loop`` (generation.py): greedy or
temperature/top-k sampling, ragged prompts, KV caches updated in place.

Flags tour:
  --snapshot PATH     load params from a training snapshot (else seeded init)
  --quantize          weight-only int8 decode (ops/quant.py): ~half the
                      weight HBM traffic; greedy outputs typically identical
  --speculative       draft-model speculative decode (speculative.py):
                      gamma-token proposals verified in one chunked target
                      forward; greedy-exact, prints acceptance stats
  --fake_devices N    run on N virtual CPU devices; with N > 1 the decode is
                      sharded over a data mesh (batch + KV caches P("data"))

Run:  python examples/generate_lm.py --batch 4 --new_tokens 32 [--quantize]
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import numpy as np


def maybe_data_mesh(args, jax):
    """The shared mesh-gating rule for every decode branch: shard when
    multi-device AND the batch divides; otherwise say so out loud (a
    silent single-device fallback would contradict the --fake_devices
    help's sharding promise)."""
    if jax.device_count() <= 1:
        return None
    if args.batch % jax.device_count() != 0:
        print(
            f"[generate_lm] batch {args.batch} does not divide over "
            f"{jax.device_count()} devices - decoding SINGLE-device",
            flush=True,
        )
        return None
    from distributed_pytorch_tpu.parallel.mesh import make_mesh

    return make_mesh()


def main(args):
    import jax
    import jax.numpy as jnp

    from distributed_pytorch_tpu.generation import generate
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(
        vocab_size=args.vocab,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=args.n_heads,
        n_kv_heads=args.n_kv_heads,
        attention_window=args.window,
        rope_scale=args.rope_scale,
        rope_theta=args.rope_theta,
        d_ff=4 * args.d_model,
        dtype=jnp.float32 if args.f32 else jnp.bfloat16,
    )
    params = model.init(
        jax.random.PRNGKey(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    if args.snapshot:
        from distributed_pytorch_tpu.checkpoint import load_snapshot
        from distributed_pytorch_tpu.training.train_step import TrainState

        template = TrainState(
            params=params, model_state={}, opt_state=(), step=jnp.zeros((), jnp.int32)
        )
        state, _ = load_snapshot(args.snapshot, template)
        params = state.params

    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, args.vocab, (args.batch, args.prompt_len)), jnp.int32
    )

    if args.speculative:
        # No silent flag drops: speculation (greedy or sampled — the
        # temperature/top_k/top_p flags pass through) runs full-precision,
        # single-device or data-mesh-sharded (multi-device batches shard
        # below like plain decode).
        dropped = [
            name
            for name, active in (
                ("--beam", args.beam > 0),
                ("--length_penalty", args.length_penalty != 0),
                ("--quantize", args.quantize),
                ("--quantized_cache", args.quantized_cache),
            )
            if active
        ]
        if dropped:
            raise SystemExit(
                f"--speculative is full-precision decode; "
                f"incompatible with {', '.join(dropped)}"
            )
        # Speculative decode against a width/depth-reduced draft sharing
        # the vocabulary (randomly initialized here — a real draft would
        # be trained/distilled; acceptance statistics show the machinery
        # either way, and the OUTPUT is exactly the target's own decode by
        # construction: greedy-exact at temperature 0, target-distributed
        # rejection sampling above it — see speculative.py).
        from distributed_pytorch_tpu.speculative import speculative_generate

        draft = model.clone(
            d_model=max(args.d_model // 4, 8),
            n_layers=max(args.n_layers // 2, 1),
            d_ff=max(args.d_model, 32),
        )
        draft_params = draft.init(
            jax.random.PRNGKey(args.seed + 1), jnp.zeros((1, 8), jnp.int32)
        )["params"]
        spec_mesh = maybe_data_mesh(args, jax)
        gamma = 4 if args.gamma is None else args.gamma
        out, stats = speculative_generate(
            model, params, draft, draft_params, prompt, args.new_tokens,
            gamma=gamma, return_stats=True,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, rng=jax.random.PRNGKey(args.seed),
            mesh=spec_mesh,
        )
        out = np.asarray(out)
        rounds = int(stats["rounds"])
        adv = int(stats["positions_advanced"])
        for row in range(min(args.batch, 4)):
            ids = out[row]
            print(
                f"[row {row}] prompt={ids[:args.prompt_len].tolist()} "
                f"-> continuation={ids[args.prompt_len:].tolist()}"
            )
        print(
            f"speculative: {rounds} target chunk-forwards for {adv} "
            f"positions (mean accepted chunk {adv / max(rounds, 1):.2f} "
            f"of gamma={gamma})"
        )
        return

    if args.beam:
        from distributed_pytorch_tpu.generation import beam_search

        # Same no-silent-flag-drops contract as --speculative above.
        blocked = [
            name
            for name, active in (
                ("sampling flags (deterministic search)",
                 args.temperature > 0 or args.top_k > 0
                 or 0 < args.top_p < 1),
                ("--gamma (speculative-only)", args.gamma is not None),
                ("--quantize", args.quantize),
                ("--quantized_cache", args.quantized_cache),
            )
            if active
        ]
        if blocked:
            raise SystemExit(
                f"--beam is full-precision deterministic search; incompatible with {', '.join(blocked)}"
            )
        beam_mesh = maybe_data_mesh(args, jax)
        out, scores = beam_search(
            model, params, prompt, args.new_tokens, beam_size=args.beam,
            length_penalty=args.length_penalty, mesh=beam_mesh,
        )
        out, scores = np.asarray(out), np.asarray(scores)
        for row in range(min(args.batch, 2)):
            for k in range(min(args.beam, 3)):
                ids = out[row, k]
                print(
                    f"[row {row} beam {k}] score={scores[row, k]:.3f} "
                    f"-> {ids[args.prompt_len:].tolist()}"
                )
        print(
            f"beam search: {args.batch}x{args.beam} beams x "
            f"{args.new_tokens} tokens"
        )
        return

    mesh = maybe_data_mesh(args, jax)
    out = generate(
        model,
        params,
        prompt,
        args.new_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        mesh=mesh,
        quantize=args.quantize,
        quantized_cache=args.quantized_cache,
    )
    out = np.asarray(out)
    for row in range(min(args.batch, 4)):
        ids = out[row]
        print(
            f"[row {row}] prompt={ids[:args.prompt_len].tolist()} "
            f"-> continuation={ids[args.prompt_len:].tolist()}"
        )
    parts = []
    if args.quantize:
        parts.append("int8 weights")
    if args.quantized_cache:
        parts.append("int8 KV cache")
    mode = " + ".join(parts) if parts else "full precision"
    where = f"{jax.device_count()}-device mesh" if mesh else "single device"
    print(f"generated {args.batch}x{args.new_tokens} tokens ({mode}, {where})")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="LM generation (inference rung)")
    parser.add_argument("--vocab", type=int, default=256)
    parser.add_argument("--d_model", type=int, default=128)
    parser.add_argument("--n_layers", type=int, default=4)
    parser.add_argument("--n_heads", type=int, default=4)
    parser.add_argument(
        "--n_kv_heads", type=int, default=0,
        help="grouped-query attention: K/V heads (0 = n_heads/MHA, 1 = "
        "MQA); the decode cache stores only these",
    )
    parser.add_argument(
        "--window", type=int, default=0,
        help="sliding-window attention: each position attends the last W "
        "tokens only (0 = full causal)",
    )
    parser.add_argument(
        "--rope_scale", type=float, default=1.0,
        help="RoPE linear position interpolation (context extension): "
        "positions divided by this factor",
    )
    parser.add_argument(
        "--rope_theta", type=float, default=10000.0,
        help="RoPE frequency base (raise for NTK-style context extension)",
    )
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--prompt_len", type=int, default=8)
    parser.add_argument("--new_tokens", type=int, default=32)
    parser.add_argument("--temperature", type=float, default=0.0,
                        help="0 = greedy argmax")
    parser.add_argument("--top_k", type=int, default=0)
    parser.add_argument("--top_p", type=float, default=0.0,
                        help="nucleus sampling: keep the smallest token set "
                        "reaching this cumulative mass (0 or >=1 disables)")
    parser.add_argument("--speculative", action="store_true",
                        help="speculative decode with a reduced draft model "
                        "(speculative.py): greedy by default, modified "
                        "rejection sampling with --temperature (exactly "
                        "target-distributed either way); prints acceptance "
                        "stats")
    parser.add_argument("--gamma", type=int, default=None,
                        help="speculative proposal chunk length (default 4)")
    parser.add_argument("--beam", type=int, default=0,
                        help="beam_search with this many beams (prints "
                        "top sequences + true log-prob scores)")
    parser.add_argument("--length_penalty", type=float, default=0.0)
    parser.add_argument("--quantize", action="store_true",
                        help="weight-only int8 decode")
    parser.add_argument("--quantized_cache", action="store_true",
                        help="int8 KV cache (halves long-context decode memory)")
    parser.add_argument("--f32", action="store_true",
                        help="float32 compute instead of the bf16 default")
    parser.add_argument("--snapshot", default=None,
                        help="load params from a training snapshot")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args)
