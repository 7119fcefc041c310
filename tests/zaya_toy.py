"""What the CCA tests share: the plain reference and the benchmark driver's
``build_program``, loaded by path as ``benchmarks/run.py`` loads them (there is
no second copy of either), and one toy configuration of the SAME shape as
``zaya1-8b``."""

import numpy as np

from hybrid_toy import load_by_path

reference = load_by_path("benchmarks/reference/zaya.py")
driver = load_by_path("benchmarks/drivers/serve_cca_moe.py")

#: Four layers of CCA (8 query heads on 2 KV heads of 8: heads x head size =
#: 64, NOT the hidden 32; two taps each convolution; the first 4 of a head's 8
#: dimensions rotated) and 8 experts of which a router network of width 16
#: chooses ONE, carrying its state from layer to layer; a tied head. float32
#: throughout, so that what is compared is the arithmetic's order and nothing
#: else.
TOY = dict(
    hidden_size=32, moe_intermediate_size=24, num_hidden_layers=4,
    layer_types=["hybrid"] * 4, head_dim=8, num_attention_heads=8,
    num_key_value_heads=2, cca_time0=2, cca_time1=2, num_experts=8,
    num_experts_per_tok=1, router_hidden_size=16, partial_rotary_factor=0.5,
    rope_parameters=dict(
        hybrid=dict(partial_rotary_factor=0.5, rope_theta=10000.0,
                    rope_type="default"), rope_type="default"),
    sliding_window=None, vocab_size=96, rms_norm_eps=1e-5,
    tie_word_embeddings=True, torch_dtype="float32", initializer_range=0.3,
    assumed={},
)
SEED = 2**31 + 23

# Logits here are of order 1; the program and the reference run the same
# float32 arithmetic in another order (an online softmax over blocks of pages
# against one softmax a block of queries, a grouped product against every
# expert on every token) through 4 layers.
LOGIT_TOL = 2e-5


def toy_program(cfg=None, weights=None, **changed):
    cfg = cfg or TOY
    weights = weights or reference.make_weights(cfg, SEED)
    model, params = driver.build_program(cfg, weights, **changed)
    return weights, model, params


def tokens(n: int, seed: int = 0):
    return np.random.default_rng(seed).integers(
        1, TOY["vocab_size"], size=n).tolist()
