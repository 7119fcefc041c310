"""Beyond-parity showcase — composing every parallelism axis the framework
supports on one host: DP x TP (megatron param sharding) x SP (ring attention)
on a TransformerLM, then DP x EP (mixture-of-experts) and DP x PP (GPipe
pipeline) variants.

The reference ladder stops at data parallelism (SURVEY.md §2b); this script is
where the additional axes become user-visible. Everything is placement
annotations over the same jitted train step — no model code changes between
configurations.

Run:  python examples/parallelism_4d.py --steps 10 --fake_devices 8
"""

import os
import sys

# Make the repo importable when run as `python tools/x.py` / `python examples/x.py`
# (sys.path[0] is the script's dir, not the repo root).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


def run_config(name, model, mesh, rules, tokens, steps, batch_spec=None):
    import jax
    import numpy as np
    import optax
    from jax.sharding import PartitionSpec as P  # noqa: F401

    from distributed_pytorch_tpu.parallel.partitioning import (
        make_param_specs,
        make_state_shardings,
        shard_train_state,
    )
    from distributed_pytorch_tpu.parallel.sharding import (
        put_global_batch,
        replicated_sharding,
    )
    from distributed_pytorch_tpu.training.losses import softmax_cross_entropy_loss
    from distributed_pytorch_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    optimizer = optax.adam(1e-3)
    state = create_train_state(model, optimizer, inputs)
    if rules:
        specs = make_param_specs(state.params, rules, mesh=mesh)
        shardings = make_state_shardings(mesh, state, specs)
    else:
        shardings = replicated_sharding(mesh)
    state = shard_train_state(state, shardings)
    step = make_train_step(
        model.apply, optimizer, softmax_cross_entropy_loss,
        mesh=mesh,
        state_sharding=shardings if rules else None,
        batch_spec=batch_spec,
    )
    batch = put_global_batch(mesh, (inputs, targets), spec=batch_spec)
    losses = []
    for _ in range(steps):
        state, loss = step(state, batch)
        losses.append(float(loss))
    print(
        f"[{name}] mesh={dict(mesh.shape)} "
        f"loss {losses[0]:.4f} -> {losses[-1]:.4f}",
        flush=True,
    )


def main(steps: int):
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from distributed_pytorch_tpu.models import (
        PipelinedTransformerLM,
        TransformerLM,
    )
    from distributed_pytorch_tpu.models.moe import MOE_EP_RULES
    from distributed_pytorch_tpu.parallel.mesh import make_mesh
    from distributed_pytorch_tpu.parallel.partitioning import TRANSFORMER_TP_RULES
    from distributed_pytorch_tpu.parallel.pipeline import PIPELINE_STAGE_RULES

    n = jax.device_count()
    assert n % 4 == 0, f"need a multiple of 4 devices, have {n}"
    dp = n // 4
    rng = np.random.default_rng(0)

    # --- DP x SP x TP: long-context ring attention + megatron shards ------
    mesh = make_mesh({"data": dp, "sequence": 2, "tensor": 2})
    lm = TransformerLM(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        mesh=mesh, sequence_axis="sequence",
    )
    tokens = rng.integers(0, 256, (4 * dp, 129), dtype=np.int32)
    run_config(
        "dp x sp x tp", lm, mesh, TRANSFORMER_TP_RULES, tokens, steps,
        batch_spec=P("data", "sequence"),
    )

    # --- DP x SP(ulysses) x TP: the all-to-all SP strategy ---------------
    # SAME mesh and SAME tokens as the ring block above, only
    # sequence_mode="ulysses" (two all-to-alls redistribute seq->heads;
    # needs (n_heads / tp) % sp == 0 — here 4/2 = 2 local heads over
    # sp=2), so the two strategies' printed losses are directly
    # comparable.
    uly = TransformerLM(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        mesh=mesh, sequence_axis="sequence", sequence_mode="ulysses",
    )
    run_config(
        "dp x sp(ulysses) x tp", uly, mesh, TRANSFORMER_TP_RULES, tokens,
        steps, batch_spec=P("data", "sequence"),
    )

    # --- DP x EP: mixture-of-experts over the expert axis -----------------
    mesh = make_mesh({"data": dp, "expert": 4})
    moe = TransformerLM(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        n_experts=4, moe_every=2, mesh=mesh,
    )
    tokens = rng.integers(0, 256, (4 * dp, 65), dtype=np.int32)
    run_config("dp x ep", moe, mesh, MOE_EP_RULES, tokens, steps)

    # --- DP x PP: GPipe pipeline over the stage axis ----------------------
    mesh = make_mesh({"data": dp, "stage": 4})
    pp = PipelinedTransformerLM(
        vocab_size=256, d_model=64, n_stages=4, layers_per_stage=1,
        n_heads=4, d_ff=128, num_microbatches=4, mesh=mesh,
    )
    tokens = rng.integers(0, 256, (8 * dp, 65), dtype=np.int32)
    run_config("dp x pp", pp, mesh, PIPELINE_STAGE_RULES, tokens, steps)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description="composed-parallelism showcase")
    parser.add_argument("--steps", default=10, type=int)
    parser.add_argument("--fake_devices", default=0, type=int,
                        help="debug: present N virtual CPU devices instead of real chips")
    args = parser.parse_args()
    from distributed_pytorch_tpu.utils.platform import init_platform
    init_platform(args.fake_devices)
    main(args.steps)
