"""What the window-group tests share: the plain reference and the benchmark
driver's ``build_program``, loaded by path as ``benchmarks/run.py`` loads them
(there is no second copy of either), and one toy configuration of the SAME
shape as ``k-exaone-236b-a23b``."""

import numpy as np

from hybrid_toy import ROOT, load_by_path  # noqa: F401

reference = load_by_path("benchmarks/reference/exaone_moe.py")
driver = load_by_path("benchmarks/drivers/serve_window_moe.py")

#: Eight layers, ``LLLG`` twice: sliding layers with a window of 8 (on pages
#: of 4: three pages a decode row) and full layers, 8 query heads of 6 on 2 KV
#: heads (heads x head size = 48, NOT the hidden 32; 4:1 grouping), an RMSNorm
#: a head, the rotation on the sliding layers alone; layer 0 dense, then 16
#: sigmoid-routed experts (top 4, gates renormalised and x 2.5) beside one
#: shared. float32 throughout, so that what is compared is the arithmetic's
#: order and nothing else.
TOY = dict(
    hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
    num_hidden_layers=8, first_k_dense_replace=1, head_dim=6,
    num_attention_heads=8, num_key_value_heads=2,
    layer_types=["sliding_attention"] * 3 + ["full_attention"]
    + ["sliding_attention"] * 3 + ["full_attention"],
    sliding_window=8, sliding_windows=[8, 8, 8, 0, 8, 8, 8, 0],
    mlp_layer_types=["dense"] + ["sparse"] * 7,
    num_experts=16, num_shared_experts=1, num_experts_per_tok=4,
    norm_topk_prob=True, scoring_func="sigmoid", routed_scaling_factor=2.5,
    n_group=1, topk_group=1, vocab_size=96, rms_norm_eps=1e-5,
    rope_parameters=dict(rope_theta=10000.0, rope_type="default"),
    tie_word_embeddings=False, torch_dtype="float32", initializer_range=0.3,
    assumed=dict(router_bias_std=0.1),
)
SEED = 2**31 + 17

# Logits here are of order 1; the program and the reference run the same
# float32 arithmetic in another order (an online softmax over blocks of pages
# against one softmax a block of queries, a grouped product against every
# expert on every token) through 8 layers.
LOGIT_TOL = 2e-5


def share(held, vocab=None):
    """The toy with only experts ``held = (lo, hi)`` on this chip and, where
    given, the first ``vocab`` rows of its vocabulary."""
    lo, hi = held
    return dict(TOY, num_experts=hi - lo, experts_held=[lo, hi],
                num_experts_published=TOY["num_experts"],
                vocab_size=vocab or TOY["vocab_size"])


def slice_share(weights, held, vocab=None):
    """``weights`` (all experts, the whole vocabulary) cut to the share."""
    lo, hi = held
    layers = [dict(w, we_in=w["we_in"][lo:hi], we_out=w["we_out"][lo:hi])
              if "we_in" in w else w for w in weights["layers"]]
    out = dict(weights, layers=layers)
    if vocab:
        out["embed"] = weights["embed"][:vocab]
        out["head"] = weights["head"][:, :vocab]
    return out


def toy_program(cfg=None, weights=None, **changed):
    cfg = cfg or TOY
    weights = weights or reference.make_weights(cfg, SEED)
    model, params = driver.build_program(cfg, weights, **changed)
    return weights, model, params


def tokens(n: int, seed: int = 0, vocab: int = 0):
    return np.random.default_rng(seed).integers(
        1, vocab or TOY["vocab_size"], size=n).tolist()
