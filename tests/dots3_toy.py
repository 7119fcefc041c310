"""What the learned-sparse-attention tests share: the plain reference and the
benchmark driver's ``build_program``, loaded by path as ``benchmarks/run.py``
loads them (there is no second copy of either), and one toy configuration."""

import numpy as np

from hybrid_toy import ROOT, load_by_path  # noqa: F401

reference = load_by_path("benchmarks/reference/dots3_note.py")
driver = load_by_path("benchmarks/drivers/serve_sparse_latent_moe.py")

#: Four layers as the model lays them out: full + dense, sliding, sliding,
#: full, with every width another number so that a slice taken at the wrong
#: place shows. Full layers: 4 heads, keys of 8 + 4, values of 6, a latent of
#: 12 behind a query latent of 20, an indexer of 3 heads of 8 that keeps 7
#: positions. Sliding layers: 2 heads, keys of 10 + 4, ranks 24 and 16, a
#: window of 9 positions, another rotary base. 8 routed experts (top 3 by
#: sigmoid score plus a correction bias, gates renormalised) beside one
#: shared. float32 throughout, so that what is compared is the arithmetic's
#: order and nothing else.
TOY = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=4, first_k_dense_replace=1, moe_layer_freq=1,
    layer_types=["full_attention", "sliding_attention", "sliding_attention",
                 "full_attention"],
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=20,
    kv_lora_rank=12, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=6,
    rope_theta=80000.0, swa_num_attention_heads=2, swa_num_key_value_heads=2,
    swa_q_lora_rank=16, swa_kv_lora_rank=24, swa_qk_nope_head_dim=10,
    swa_qk_rope_head_dim=4, swa_v_head_dim=6, swa_rope_theta=5000.0,
    sliding_window_size=9, index_head_dim=8, index_n_heads=3, index_topk=7,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=3, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="noaux_tc", routed_scaling_factor=1, rope_scaling=None,
    vocab_size=96, rms_norm_eps=1e-5, tie_word_embeddings=False,
    torch_dtype="float32", initializer_range=0.3,
)
SEED = 2**31 + 17

# As deepseek_toy's: the program and the reference run the same float32
# arithmetic in another order, carried through 4 layers: logits of order 4
# measured up to 4e-5 apart. A selection or a routing choice that flips on a
# last bit would read far more (the toy's scores lie further apart than
# that), the planted faults read 1e-3 and more.
LOGIT_TOL = 1e-4


def share(held):
    """The toy with only experts ``held = (lo, hi)`` on this chip."""
    lo, hi = held
    return dict(TOY, n_routed_experts=hi - lo, experts_held=[lo, hi],
                n_routed_experts_published=TOY["n_routed_experts"])


def slice_experts(weights, held):
    """``weights`` (all experts held) cut to the share ``held``."""
    lo, hi = held
    layers = [dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
              if "we_in" in w else w for w in weights["layers"]]
    return dict(weights, layers=layers)


def toy_program(cfg=None, weights=None):
    cfg = cfg or TOY
    weights = weights or reference.make_weights(cfg, SEED)
    model, params = driver.build_program(cfg, weights)
    return weights, model, params


def tokens(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], size=n).tolist()
