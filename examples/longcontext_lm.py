"""Rung 6 — beyond the reference ladder: long-context LM training with
sequence parallelism (ring attention, or ulysses all-to-all via --sp_mode).

The reference has no attention code at all (SURVEY.md §5: "sequence length is
not a concept in this codebase"); this rung exercises the framework machinery
the reference never reaches: a ``data x sequence`` mesh, batch sharded on
``data``, sequence dim sharded on ``sequence``, K/V shards rotating over the
ICI ring inside each attention layer (``ops/attention.py::ring_attention``)
so per-chip attention memory stays O(T / n_sequence_chips).

Run:  python examples/longcontext_lm.py --steps 20 --seq_len 2048 \
          --data_parallel 2 --sequence_parallel 4 --fake_devices 8
      # the all-to-all strategy (needs n_heads divisible by SP size):
      python examples/longcontext_lm.py --sp_mode ulysses ...
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time


def main(args):
    import jax
    import numpy as np
    import optax

    from distributed_pytorch_tpu.models import TransformerLM
    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    from distributed_pytorch_tpu.parallel.sharding import replicated_sharding
    from distributed_pytorch_tpu.training.losses import softmax_cross_entropy_loss
    from distributed_pytorch_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    mesh = make_mesh(
        {"data": args.data_parallel, "sequence": args.sequence_parallel}
    )
    print(f"mesh: {dict(mesh.shape)} over {mesh.devices.size} devices", flush=True)

    model = TransformerLM(
        vocab_size=args.vocab_size,
        d_model=args.d_model,
        n_layers=args.n_layers,
        n_heads=args.n_heads,
        d_ff=4 * args.d_model,
        remat=args.remat != "none",
        remat_policy="full" if args.remat == "none" else args.remat,
        mesh=mesh,
        sequence_axis="sequence",
        sequence_mode=args.sp_mode,
        fused_head_chunk=args.fused_head_chunk,
    )
    optimizer = optax.adamw(3e-4)
    fused = args.fused_head_chunk > 0

    rng = np.random.default_rng(0)
    tokens = rng.integers(
        0, args.vocab_size, (args.batch_size, args.seq_len + 1), dtype=np.int32
    )
    inputs, targets = tokens[:, :-1], tokens[:, 1:]

    state = create_train_state(model, optimizer, inputs)
    state = jax.device_put(state, replicated_sharding(mesh))
    # With the fused head the model consumes targets and returns the scalar
    # loss itself; the [B*T, vocab] logits tensor is never materialized.
    step = make_train_step(
        model.apply,
        optimizer,
        (lambda out, _: out) if fused else softmax_cross_entropy_loss,
        mesh=mesh,
        apply_takes_targets=fused,
    )

    # The batch is sharded over "data"; inside each attention layer the
    # sequence dim is re-sharded over "sequence" by the shard_map.
    from distributed_pytorch_tpu.parallel.sharding import put_global_batch

    batch = put_global_batch(mesh, (inputs, targets))
    state, loss = step(state, batch)  # compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, loss = step(state, batch)
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0
    tok_per_s = args.steps * args.batch_size * args.seq_len / dt
    print(
        f"loss={float(loss):.4f}  {args.steps} steps in {dt:.2f}s  "
        f"({tok_per_s:,.0f} tokens/s)",
        flush=True,
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="long-context LM with ring attention")
    parser.add_argument("--steps", default=10, type=int)
    parser.add_argument("--seq_len", default=2048, type=int)
    parser.add_argument("--batch_size", default=2, type=int, help="global batch")
    parser.add_argument("--vocab_size", default=1024, type=int)
    parser.add_argument("--d_model", default=128, type=int)
    parser.add_argument("--n_layers", default=2, type=int)
    parser.add_argument("--n_heads", default=4, type=int)
    parser.add_argument("--data_parallel", default=2, type=int)
    parser.add_argument("--sequence_parallel", default=4, type=int)
    parser.add_argument(
        "--sp_mode", default="ring", choices=["ring", "ulysses"],
        help="sequence-parallel strategy: ring (K/V rotation, O(T/sp) "
        "memory) or ulysses (all-to-all seq->heads, local full-T flash)",
    )
    parser.add_argument(
        "--remat", default="none", choices=["none", "full", "mlp"],
        help="rematerialization: none (flash keeps activations linear in T — "
        "fastest, measured +18%% over full at T=8k), mlp (recompute only the "
        "d_ff activations), full (whole block; re-runs flash fwd in backward)",
    )
    parser.add_argument("--fused_head_chunk", default=0, type=int,
                        help=">0: fused LM-head cross-entropy with this vocab "
                        "chunk size (never materializes the logits)")
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices instead of real chips")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args)
