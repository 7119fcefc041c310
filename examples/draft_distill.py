"""Draft distillation: make speculative decoding actually fast.

Speculative decoding's speedup is the mean accepted chunk length, and that
is a property of how well the DRAFT predicts the TARGET — a random draft
accepts ~0 and degenerates to serial decode with extra overhead
(examples/generate_lm.py --speculative shows the machinery, not a win).
This rung closes the loop the way a real deployment does: distill a small
draft against the target's own next-token distributions (forward KL,
teacher logits computed on the fly), then measure the acceptance statistic
rise through ``speculative_generate(return_stats=True)``.

Run:  python examples/draft_distill.py --fake_devices 8    # CPU CI rig
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


def main(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_pytorch_tpu import ShardedLoader, Trainer
    from distributed_pytorch_tpu.models import TransformerLM
    from distributed_pytorch_tpu.speculative import speculative_generate
    from distributed_pytorch_tpu.training.losses import (
        softmax_cross_entropy_loss,
    )
    from distributed_pytorch_tpu.utils.data import ArrayDataset

    from examples.lora_finetune import token_stream  # the toy Markov data

    rng = np.random.default_rng(args.seed)
    vocab = 64
    target = TransformerLM(
        vocab_size=vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=4, d_ff=4 * args.d_model, dtype=jnp.float32,
    )
    draft = TransformerLM(
        vocab_size=vocab, d_model=args.d_model // 4, n_layers=1,
        n_heads=2, d_ff=args.d_model, dtype=jnp.float32,
    )

    # 1) Train the target on the toy distribution.
    data = token_stream(rng, args.n_train, args.seq, vocab, shift=1)
    loader = ShardedLoader(ArrayDataset(data[:, :-1], data[:, 1:]),
                           args.batch_size)
    trainer = Trainer(target, loader, optax.adam(1e-2), save_every=0,
                      loss_fn=softmax_cross_entropy_loss)
    trainer.train(args.target_epochs)
    # Host snapshot: the jitted step donates its state buffers.
    target_params = jax.tree_util.tree_map(np.asarray, trainer.state.params)

    prompts = jnp.asarray(
        token_stream(rng, args.eval_batch, 8, vocab, shift=1)
    )

    def acceptance(draft_params):
        _, stats = speculative_generate(
            target, target_params, draft, draft_params, prompts,
            args.new_tokens, gamma=args.gamma, return_stats=True,
        )
        return float(stats["positions_advanced"]) / max(int(stats["rounds"]), 1)

    draft_params = draft.init(
        jax.random.PRNGKey(args.seed + 1),
        jnp.zeros((1, 8), jnp.int32),
    )["params"]
    before = acceptance(draft_params)

    # 2) Distill: forward KL(target || draft) on the training sequences,
    # teacher logits computed on the fly (training/distill.py).
    from distributed_pytorch_tpu.training.distill import make_distill_step

    inputs = jnp.asarray(data[:, :-1])
    opt = optax.adam(1e-2)
    opt_state = opt.init(draft_params)
    distill_step = make_distill_step(target, draft, opt)
    steps_per_epoch = len(inputs) // args.batch_size
    if steps_per_epoch == 0:
        raise SystemExit(
            f"--batch_size {args.batch_size} exceeds --n_train "
            f"{args.n_train}: distillation would silently no-op"
        )
    for epoch in range(args.distill_epochs):
        order = np.random.default_rng(epoch).permutation(len(inputs))
        loss = None
        for i in range(steps_per_epoch):
            idx = order[i * args.batch_size : (i + 1) * args.batch_size]
            draft_params, opt_state, loss = distill_step(
                draft_params, opt_state, inputs[idx], target_params
            )
        print(f"distill epoch {epoch}: kl={float(loss):.4f}", flush=True)

    after = acceptance(draft_params)
    n_t = sum(x.size for x in jax.tree_util.tree_leaves(target_params))
    n_d = sum(x.size for x in jax.tree_util.tree_leaves(draft_params))
    print(
        f"mean accepted chunk (gamma={args.gamma}): random draft "
        f"{before:.2f} -> distilled {after:.2f} "
        f"(draft is {n_d / n_t:.1%} of the target's {n_t:,} params; each "
        f"accepted chunk replaces that many serial target steps with one "
        f"chunked forward)"
    )


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="draft distillation rung")
    parser.add_argument("--d_model", default=64, type=int)
    parser.add_argument("--n_layers", default=2, type=int)
    parser.add_argument("--seq", default=16, type=int)
    parser.add_argument("--n_train", default=2048, type=int)
    parser.add_argument("--batch_size", default=64, type=int)
    parser.add_argument("--target_epochs", default=3, type=int)
    parser.add_argument("--distill_epochs", default=3, type=int)
    parser.add_argument("--eval_batch", default=8, type=int)
    parser.add_argument("--new_tokens", default=32, type=int)
    parser.add_argument("--gamma", default=4, type=int)
    parser.add_argument("--seed", default=0, type=int)
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args)
