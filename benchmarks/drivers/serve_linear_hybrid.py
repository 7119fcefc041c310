"""Driver for configurations of kind ``serve_linear_hybrid``: a decoder whose
layers are gated delta-rule linear-attention mixers with full-attention layers
between them (the ``olmo_hybrid`` family: ``layer_types`` of
``linear_attention`` and ``full_attention``, the Olmo 2 block with its norms
on each sublayer's OUTPUT and QK-norm), served through the program's normal
``InferenceEngine``.

Its own are ``build_program`` (the program's model for such a configuration
and its parameter tree, filled with the reference's weights) and the
comparison of matrix states ``[H, d_k, d_v]`` (``scan_states``,
``state_gaps``). The closed loop, the counting rule, the window, the trace and
the comparison of the served tokens are ``drivers/serve.py``'s, and the state
probe after the window, the one reading of the trace for ``serve.py`` and
``phases.py`` (``TraceOnce``) and the memory split are
``drivers/serve_hybrid.py``'s: ``run`` loads a private copy of that file,
rebinds the names above in it and calls its ``run``, the way
``drivers/serve_hybrid_moe.py`` does. Round that call it adds:

* **Two numbers from the one probe** (a 1,024-token prompt, 384 decoded
  tokens, through the same engine and programs after the window), each under
  its own limit of the traffic file's ``check``; ``correct`` is both and the
  served tokens' two (``logit_gap``, ``mean_gap`` over ``check.requests``
  counted requests padded to ``check.pad_tokens_to``). ``state_gap``
  (``state_gap_limit``): the first gated-delta layer's ``|S - S_ref| /
  |S_ref|`` over all its heads, as ``serve_hybrid.py`` judges it (that file
  prints it as "the first Mamba layer's scan state": the line is its own; here
  the state is the delta rule's matrix). ``state_gap_memory``
  (``state_gap_memory_limit``): the same ratio a head, the mean over the
  quarter of the layer's heads whose ``alpha`` is largest by their weights
  (``SLOW_SHARE``: they remember longest) less the mean over all its heads,
  which is where a state kept in fewer bits shows and the whole layer's number
  does not.
* **The field test first.** A program whose ``LAYER_TYPES`` lack
  ``gated_delta`` cannot build this model: ``run`` exits non-zero before
  anything is built (this cell's parent does).
* **A longer traced stretch** than a ``serve_hybrid`` cell's:
  ``TRACE_SECONDS`` of the window's end (the blocked evaluation has no loop
  over tokens).
* **The mixers' operations**, read from the trace file for
  ``harness/linear.py``'s readers (``ctx["gdn_ops"]``): one more pass over the
  first device's ``XLA Ops`` line, with whole names. That file says how they
  are recognised and what it misses.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 3.0  # of the window's end that a traced run traces
LAYER_TYPE = "gated_delta"  # the program's name for a linear_attention layer


def layer_types(cfg: dict) -> tuple:
    """The program's name for each layer that is run."""
    return tuple(
        "attention" if kind == "full_attention" else LAYER_TYPE
        for kind in cfg["layer_types"][: cfg["num_hidden_layers"]])


def build_program(cfg: dict, weights: dict, **overrides):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads).
    ``overrides`` are ``TransformerLM`` fields (the controls' and the tests':
    a model made wrong in one way)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    kinds = layer_types(cfg)
    fields = dict(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], dtype=jnp.dtype(cfg["torch_dtype"]),
        n_kv_heads=cfg["num_key_value_heads"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="gated_silu",
        use_bias=False, rope=False, layer_types=kinds,
        norm_placement="output", qk_norm=True,
        linear_n_heads=cfg["linear_num_value_heads"],
        linear_d_k=cfg["linear_key_head_dim"],
        linear_d_v=cfg["linear_value_head_dim"],
        linear_d_conv=cfg["linear_conv_kernel_dim"],
        linear_neg_eigval=cfg["linear_allow_neg_eigval"],
    )
    fields.update(overrides)
    model = TransformerLM(**fields)
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
        "lm_head": {"kernel": weights["w_head"],
                    "bias": jnp.zeros((cfg["vocab_size"],), jnp.float32)},
    }
    for i, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        layer = {
            "ln_attn": {"scale": w["ln1_g"]},
            "ln_mlp": {"scale": w["ln2_g"]},
            "mlp": {"gate": {"kernel": w["w_gate"]},
                    "up": {"kernel": w["w_up"]},
                    "down": {"kernel": w["w_down"]}},
        }
        if kind == "attention":
            layer["attention"] = {
                "query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]}, "out": {"kernel": w["wo"]},
                "q_norm": {"scale": w["qn_g"]}, "k_norm": {"scale": w["kn_g"]},
            }
        else:
            layer["gated_delta"] = {
                "q_proj": {"kernel": w["w_q"]}, "k_proj": {"kernel": w["w_k"]},
                "v_proj": {"kernel": w["w_v"]}, "g_proj": {"kernel": w["w_g"]},
                "a_proj": {"kernel": w["w_a"]}, "b_proj": {"kernel": w["w_b"]},
                "conv_kernel": w["conv_w"], "A_log": w["a_log"],
                "dt_bias": w["dt_bias"], "norm": {"scale": w["on_g"]},
                "o_proj": {"kernel": w["w_o"]},
            }
        params[f"block_{i}"] = layer
    return model, params


_HYBRID = "bench_drivers_serve_hybrid_for_linear"


def _hybrid():
    """A private copy of ``drivers/serve_hybrid.py`` with this file's model
    and state comparison in the place of its own, loaded once."""
    module = sys.modules.get(_HYBRID)
    if module is None:
        spec = importlib.util.spec_from_file_location(
            _HYBRID, os.path.join(HERE, "serve_hybrid.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[_HYBRID] = module
        spec.loader.exec_module(module)
        module.build_program = build_program
        module.state_gaps = state_gaps
        module.TRACE_SECONDS = TRACE_SECONDS
    return module


def reference_logits(cell, weights, r, sample, **kw):
    """``serve.py``'s, for ``control.py``'s ``serve_control``."""
    return _hybrid().reference_logits(cell, weights, r, sample, **kw)


def scan_states(cache, slot: int, *, heads: int):
    """``[gated-delta layers, H, d_k, d_v]``: slot ``slot``'s state in every
    gated-delta layer of an engine's ``cache`` tree, in layer order, out of
    the lane-packed layout the program keeps it in."""
    import jax
    import numpy as np

    from distributed_pytorch_tpu.models.gated_delta import head_states

    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] == "scan_state":
            layer = next(int(k[6:]) for k in keys if k.startswith("block_"))
            found[layer] = np.asarray(head_states(leaf[slot], heads), np.float32)
    return np.stack([found[i] for i in sorted(found)])


#: The ``1 / SLOW_SHARE`` of a layer's heads that remember longest are read
#: apart (``drivers/serve_hybrid_moe.py`` says why: a state kept in fewer
#: bits is rounded once a token, and the roundings add up over as many
#: tokens as a head remembers).
SLOW_SHARE = 4


def longest_memories(layer: dict):
    """The heads of a gated-delta layer that remember longest, by its
    weights: a head forgets at ``exp(A_log) softplus(dt_bias)`` a token (the
    input moves the step round that)."""
    import numpy as np

    rate = np.exp(np.asarray(layer["a_log"], np.float64)) * np.logaddexp(
        0.0, np.asarray(layer["dt_bias"], np.float64))
    return np.argsort(rate, kind="stable")[: max(1, len(rate) // SLOW_SHARE)]


def state_gaps(cell, weights, probe: dict, **kw) -> list:
    """How far the program's states lie from the reference's over the same
    tokens: ``|S - S_ref| / |S_ref|`` (Frobenius over all its heads) of every
    gated-delta layer. Leaves in ``probe`` ``state_gap_memory`` (the first
    such layer: the same ratio a head, the mean over its
    ``longest_memories`` less the mean over all its heads)."""
    import numpy as np

    want = np.asarray(cell.reference.final_states(
        cell.config, weights, probe["tokens"], **kw))  # [layers, H, d_k, d_v]
    got = probe["states"]
    norm = lambda a, last: np.linalg.norm(  # noqa: E731
        a.reshape(a.shape[:-last] + (-1,)), axis=-1)
    heads = (norm(got - want, 2) / norm(want, 2))[0]  # of the first: [H]
    slow = longest_memories(
        next(w for w in weights["layers"] if "a_log" in w))
    probe["state_gap_memory"] = float(heads[slow].mean() - heads.mean())
    cell.say(
        f"correct: a head of the first gated-delta layer's state [H, d_k, "
        f"d_v] lies {heads.mean():.6f} (mean) and {heads.max():.6f} (worst) "
        f"from the reference's, the {len(slow)} of {len(heads)} that remember "
        f"longest {heads[slow].mean():.6f}: {probe['state_gap_memory']:+.6f} "
        f"farther (limit {cell.traffic['check']['state_gap_memory_limit']})")
    return (norm(got - want, 3) / norm(want, 3)).tolist()


def run(cell):
    """Run one cell through ``serve_hybrid.py``'s ``run`` (module docstring
    says what is put round it)."""
    from distributed_pytorch_tpu.models import transformer

    if LAYER_TYPE not in getattr(transformer, "LAYER_TYPES", ()):
        # A program from before the gated-delta mixer (this cell's parent):
        # fail before anything is built.
        raise SystemExit(
            f"this program's LAYER_TYPES lack {LAYER_TYPE!r}: it cannot "
            "build a serve_linear_hybrid configuration")
    hybrid = _hybrid()
    hybrid.scan_states = functools.partial(
        scan_states, heads=cell.config["linear_num_value_heads"])
    probes = []
    probe_state = hybrid.probe_state

    def probe_and_keep(*args):
        probes.append(probe_state(*args))
        return probes[-1]

    hybrid.probe_state = probe_and_keep
    try:
        out = hybrid.run(cell)
    finally:
        hybrid.probe_state = probe_state
    limit = cell.traffic["check"]["state_gap_memory_limit"]
    out["correct"] = bool(
        out["correct"] and probes
        and probes[-1].get("state_gap_memory", float("inf")) <= limit)
    if probes and "after_probe" in cell.hooks:  # control_linear_hybrid.py
        cell.hooks["after_probe"](probes[-1])
    ctx = out["context"]
    if ctx is not None:
        from harness import linear

        window, _ = ctx["ssm_ops"]
        ops = linear.read_ops(cell.scratch("trace"), cell.config)
        ctx["gdn_ops"] = window, ops
        cell.say(
            f"operations: {len(ops['step'])} calls of the decode kernel, "
            f"{len(ops['blocks'])} operations of the blocked prefill and "
            f"{len(ops['rest'])} more of the gated-delta mixers (conv, "
            f"norms, layout) among {ops['events']} device events, read in "
            f"{ops['read_s']:.1f}s")
    return out
