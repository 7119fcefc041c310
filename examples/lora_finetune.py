"""LoRA fine-tuning rung: adapt a frozen TransformerLM with rank-r deltas.

No reference analog (the reference stops at from-scratch training);
parameter-efficient fine-tuning is the standard way a real fleet adapts a
pretrained model, and on a mesh its payoff is distributed: gradients, Adam
moments, and checkpoint deltas shrink to the adapter tree, so the grad
all-reduce and ZeRO-sharded state scale with rank x (m+n) per kernel, not
m x n (training/lora.py).

The script "pretrains" a small LM on one token distribution, then LoRA-
fine-tunes it on a shifted distribution with the base frozen — printing
the trainable-parameter ratio, per-epoch loss, and a before/after eval
showing the adapters (not the base) absorbed the shift. The merged export
then drives generation.generate.

Run:  python examples/lora_finetune.py --fake_devices 8   # CPU CI rig
      python examples/lora_finetune.py --rank 16          # real TPU
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


def token_stream(rng, n, seq, vocab, *, shift):
    """Markov-ish toy data: next token = current + shift (mod vocab) with
    noise — a distribution a tiny LM learns quickly, and whose ``shift``
    is the knob fine-tuning must absorb."""
    import numpy as np

    x = rng.integers(0, vocab, (n, 1), np.int32)
    rows = [x]
    for _ in range(seq - 1):
        nxt = (rows[-1] + shift) % vocab
        noise = rng.integers(0, vocab, nxt.shape, np.int32)
        take = rng.random(nxt.shape) < 0.1
        rows.append(np.where(take, noise, nxt).astype(np.int32))
    return np.concatenate(rows, axis=1)


def main(args):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from distributed_pytorch_tpu import (
        LoraModel,
        ShardedLoader,
        Trainer,
        generate,
        make_mesh,
    )
    from distributed_pytorch_tpu.models import TransformerLM
    from distributed_pytorch_tpu.training.losses import (
        softmax_cross_entropy_loss,
    )
    from distributed_pytorch_tpu.utils.data import ArrayDataset

    rng = np.random.default_rng(args.seed)
    vocab = 64
    model = TransformerLM(
        vocab_size=vocab, d_model=args.d_model, n_layers=args.n_layers,
        n_heads=4, d_ff=4 * args.d_model, dtype=jnp.float32,
    )
    mesh = make_mesh() if jax.device_count() > 1 else None

    def eval_loss(apply_params, seqs):
        logits = model.apply({"params": apply_params}, jnp.asarray(seqs[:, :-1]))
        return float(
            softmax_cross_entropy_loss(logits, jnp.asarray(seqs[:, 1:]))
        )

    # 1) "Pretrain" on shift=+1 data (full-parameter training).
    pre = token_stream(rng, args.n_train, args.seq, vocab, shift=1)
    loader = ShardedLoader(
        ArrayDataset(pre[:, :-1], pre[:, 1:]), args.batch_size
    )
    trainer = Trainer(model, loader, optax.adam(1e-2), save_every=0,
                      mesh=mesh, loss_fn=softmax_cross_entropy_loss)
    trainer.train(args.pretrain_epochs)
    # Host-side copy: the jitted step DONATES its state, so the pretrained
    # device buffers are consumed by fine-tuning's first step — anything we
    # want to compare against afterwards must be snapshotted now.
    base_params = jax.tree_util.tree_map(np.asarray, trainer.state.params)

    # 2) LoRA fine-tune on shift=+3 data; the base stays frozen.
    wrapped = LoraModel(model, rank=args.rank)
    ft = token_stream(rng, args.n_train, args.seq, vocab, shift=3)
    ft_loader = ShardedLoader(
        ArrayDataset(ft[:, :-1], ft[:, 1:]), args.batch_size
    )
    ft_trainer = Trainer(
        wrapped, ft_loader, optax.adam(1e-2), save_every=0, mesh=mesh,
        loss_fn=softmax_cross_entropy_loss,
    )
    # Start from the pretrained base, not a fresh init.
    ft_trainer.state = ft_trainer.state.replace(
        model_state={**ft_trainer.state.model_state, "lora_base": base_params}
    )
    n_base = sum(x.size for x in jax.tree_util.tree_leaves(base_params))
    n_adapt = sum(
        x.size for x in jax.tree_util.tree_leaves(ft_trainer.state.params)
    )
    print(
        f"trainable: {n_adapt:,} adapter params over a frozen {n_base:,}-param "
        f"base ({n_adapt / n_base:.1%}) at rank {args.rank}"
    )
    eval_seqs = token_stream(rng, 256, args.seq, vocab, shift=3)
    before = eval_loss(base_params, eval_seqs)
    ft_trainer.train(args.epochs)

    merged = wrapped.merged_params(ft_trainer.state)
    after = eval_loss(merged, eval_seqs)
    # The frozen base must be bit-identical after fine-tuning.
    unchanged = all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(
            jax.tree_util.tree_leaves(
                ft_trainer.state.model_state["lora_base"]
            ),
            jax.tree_util.tree_leaves(base_params),
        )
    )
    print(
        f"shifted-distribution eval loss: base {before:.4f} -> "
        f"LoRA-merged {after:.4f} (base frozen: {unchanged})"
    )

    out = np.asarray(
        generate(model, merged, jnp.asarray(eval_seqs[:2, :4]), 8)
    )
    print(f"merged-export generation: {out[0].tolist()}")


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="LoRA fine-tuning rung")
    parser.add_argument("--rank", default=8, type=int)
    parser.add_argument("--d_model", default=64, type=int)
    parser.add_argument("--n_layers", default=2, type=int)
    parser.add_argument("--seq", default=16, type=int)
    parser.add_argument("--n_train", default=2048, type=int)
    parser.add_argument("--batch_size", default=64, type=int,
                        help="global batch size")
    parser.add_argument("--pretrain_epochs", default=3, type=int)
    parser.add_argument("--epochs", default=3, type=int)
    parser.add_argument("--seed", default=0, type=int)
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args)
