"""Driver for configurations of kind ``serve_hybrid_moe``: a decoder whose
layers are Mamba-2 mixers with a few attention layers between them and whose
every feed-forward is routed experts plus a shared one (the
``granitemoehybrid`` family), served through the program's normal
``InferenceEngine``, holding the chip's share of the experts.

Its own are ``build_program`` (the program's model for such a configuration
and its parameter tree, filled with the reference's weights), the comparison
of ``[H, P, N]`` states (``scan_states``, ``state_gaps``) and the comparison
of the routing (``routing_gap``). The closed loop, the counting rule, the
window, the trace and the comparison of the served tokens are
``drivers/serve.py``'s, and the state probe after the window, the one
reading of the trace for ``serve.py`` and ``phases.py`` (``TraceOnce``) and
the memory split are ``drivers/serve_hybrid.py``'s: ``run`` loads a private
copy of that file, rebinds the names above in it and calls its ``run``.
Round that call it adds:

* **Three numbers from the one probe**, each under its own limit of the
  traffic file's ``check``, and ``correct`` is all of them and the served
  tokens' two. ``state_gap`` (``state_gap_limit``): the first Mamba-2
  layer's state over all its heads, as ``serve_hybrid.py`` judges it.
  ``state_gap_memory`` (``state_gap_memory_limit``): how much farther that
  layer's heads that remember longest lie than its heads on average
  (``SLOW_SHARE``), which is where a state kept in fewer bits shows and
  the whole layer's number does not. ``routing_gap``
  (``routing_gap_limit``): the share of the routed (token, layer, expert)
  triples of the probe's decoded tokens on which the program's routers, as
  the engine's programs report them (``routing_counts``, kept a step while
  the probe runs), and the reference's, on its own activations, differ.
* **The field test first.** A program whose ``TransformerLM`` has no
  ``ffn_types`` cannot build this model: ``run`` exits non-zero before
  anything is built (this cell's parent does).
* **A longer traced stretch.** The blocked evaluation has no loop over
  tokens, so such a cell's trace holds far fewer device events a second than
  a ``serve_hybrid`` cell's: ``TRACE_SECONDS`` of the window's end, not 1.
* **The mixers' and the experts' operations**, read from the trace file for
  ``harness/moe_hybrid.py``'s readers (``ctx["ssd_ops"]``, ``ctx["moe_ops"]``):
  one more pass over the first device's ``XLA Ops`` line, with whole names.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 3.0  # of the window's end that a traced run traces


def layer_types(cfg: dict) -> tuple:
    """The program's name for each layer that is run."""
    return tuple(
        "attention" if kind == "attention" else "mamba2"
        for kind in cfg["layer_types"][: cfg["num_hidden_layers"]])


def build_program(cfg: dict, weights: dict):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    kinds = layer_types(cfg)
    n_router = cfg.get("num_local_experts_published", cfg["num_local_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["num_local_experts"]))
    model = TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["intermediate_size"], dtype=jnp.dtype(cfg["torch_dtype"]),
        n_kv_heads=cfg["num_key_value_heads"],
        tie_embeddings=cfg["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="gated_silu",
        use_bias=False, rope=False, layer_types=kinds,
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_n_groups=cfg["mamba_n_groups"],
        attention_multiplier=cfg["attention_multiplier"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        ffn_types=("routed",) * len(kinds), routed_experts=n_router,
        routed_top_k=cfg["num_experts_per_tok"], experts_held=held,
        shared_d_ff=cfg["shared_intermediate_size"],
    )
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
    }
    for i, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        layer = {
            "ln_attn": {"scale": w["ln1_g"]},
            "ln_mlp": {"scale": w["ln2_g"]},
            "experts": {"router_kernel": w["router"],
                        "in_kernel": w["we_in"], "out_kernel": w["we_out"]},
            "shared_mlp": {"gate": {"kernel": w["ws_gate"]},
                           "up": {"kernel": w["ws_up"]},
                           "down": {"kernel": w["ws_down"]}},
        }
        if kind == "attention":
            layer["attention"] = {
                "query": {"kernel": w["wq"]}, "key": {"kernel": w["wk"]},
                "value": {"kernel": w["wv"]}, "out": {"kernel": w["wo"]},
            }
        else:
            layer["mamba"] = {
                "in_proj": {"kernel": w["w_in"]},
                "conv_kernel": w["conv_w"], "conv_bias": w["conv_b"],
                "dt_bias": w["dt_bias"], "A_log": w["a_log"],
                "D": w["d_skip"], "norm": {"scale": w["norm_g"]},
                "out_proj": {"kernel": w["w_out"]},
            }
        params[f"block_{i}"] = layer
    return model, params


_HYBRID = "bench_drivers_serve_hybrid_for_moe"


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
        module.scan_states = scan_states
        module.state_gaps = state_gaps
        module.TRACE_SECONDS = TRACE_SECONDS
    return module


def probe_with_routing(probe_state, engine, cell, serve) -> dict:
    """``serve_hybrid.py``'s probe, and beside what it returns ``routing``:
    the routing counts ``[layers, n_router]`` of every program the engine
    ran for it, in the order it ran them."""
    import numpy as np

    programs = []
    step = engine.step

    def step_and_keep():
        finished = step()
        programs.extend(engine.routing_counts)
        return finished

    engine.step = step_and_keep
    try:
        probe = probe_state(engine, cell, serve)
    finally:
        del engine.step
    probe["routing"] = [np.asarray(counts) for counts in programs]
    return probe


def reference_logits(cell, weights, r, sample, **kw):
    """``serve.py``'s, for ``control.py``'s ``serve_control``."""
    return _hybrid().reference_logits(cell, weights, r, sample, **kw)


def scan_states(cache, slot: int):
    """``[mamba layers, H, P, N]``: slot ``slot``'s state in every Mamba-2
    layer of an engine's ``cache`` tree, in layer order."""
    import jax
    import numpy as np

    found = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] == "scan_state":
            layer = next(int(k[6:]) for k in keys if k.startswith("block_"))
            found[layer] = np.asarray(leaf[slot], np.float32)
    return np.stack([found[i] for i in sorted(found)])


#: The ``1 / SLOW_SHARE`` of a layer's heads that remember longest are read
#: apart. A state kept in fewer bits is rounded once a decoded token, and the
#: roundings add up over as many tokens as a head remembers, while what bf16
#: projections leave is the same in every head. So over the whole layer the
#: two are of one size (0.0043-0.0067 against 0.0057-0.0084), and these heads
#: lie as far as the layer's heads on average (+-0.0007) unless the state is
#: kept in bfloat16 (0.005-0.010 farther; my chip runs, PR 31:
#: ``traffic/chat_closed_c64.json``).
SLOW_SHARE = 8


def longest_memories(layer: dict):
    """The heads of a Mamba-2 layer that remember longest, by its weights:
    a head forgets at ``exp(A_log) softplus(dt_bias)`` a token (the input
    moves ``dt`` round that)."""
    import numpy as np

    a_log = np.asarray(layer["a_log"], np.float64)
    rate = np.exp(a_log) * np.logaddexp(0.0, np.asarray(layer["dt_bias"], np.float64))
    return np.argsort(rate, kind="stable")[: max(1, len(rate) // SLOW_SHARE)]


def state_gaps(cell, weights, probe: dict, **kw) -> list:
    """How far the program's states lie from the reference's over the same
    tokens: ``|h - h_ref| / |h_ref|`` (Frobenius over all its heads) of every
    Mamba-2 layer, as ``serve_hybrid.py`` judges the first of. Leaves in
    ``probe`` the two numbers more that ``run`` judges: ``state_gap_memory``
    (the first Mamba-2 layer: the same ratio a head, the mean over its
    ``longest_memories`` less the mean over all its heads) and, where the
    probe has the programs' ``routing``, ``routing_gap``."""
    import numpy as np

    want = np.asarray(cell.reference.final_states(
        cell.config, weights, probe["tokens"], **kw))  # [layers, H, P, N]
    got = probe["states"]
    norm = lambda a, last: np.linalg.norm(  # noqa: E731
        a.reshape(a.shape[:-last] + (-1,)), axis=-1)
    heads = (norm(got - want, 2) / norm(want, 2))[0]  # of the first: [H]
    slow = longest_memories(
        next(w for w in weights["layers"] if "a_log" in w))
    probe["state_gap_memory"] = float(heads[slow].mean() - heads.mean())
    cell.say(
        f"correct: a head of the first Mamba-2 layer lies {heads.mean():.6f} "
        f"(mean) and {heads.max():.6f} (worst) from the reference's, the "
        f"{len(slow)} of {len(heads)} that remember longest {heads[slow].mean():.6f}: "
        f"{probe['state_gap_memory']:+.6f} farther (limit "
        f"{cell.traffic['check']['state_gap_memory_limit']})")
    if "routing" in probe:
        probe["routing_gap"] = routing_gap(cell, weights, probe, **kw)
    return (norm(got - want, 3) / norm(want, 3)).tolist()


def routing_gap(cell, weights, probe: dict, **kw) -> float:
    """The share of the routed (token, layer, expert) triples on which the
    program's routers and the reference's differ, over the probe's tokens
    that a program carried alone (the decoded ones: there a program's counts
    ARE its token's experts). The reference routes on its own activations:
    it is not told what the program chose."""
    import numpy as np

    cfg = cell.config
    want = np.asarray(cell.reference.routing_at(
        cfg, weights, probe["tokens"], **kw)).astype(np.int64)  # [layers, T, E]
    top_k = cfg["num_experts_per_tok"]
    differ = np.zeros(len(want))
    start = alone = 0
    for counts in probe["routing"]:  # [layers, E], in the order of the tokens
        tokens = int(counts[0].sum()) // top_k
        if tokens == 1:
            differ += np.abs(counts - want[:, start]).sum(axis=-1) / 2
            alone += 1
        start += tokens
    if start != want.shape[1] or not alone:
        raise RuntimeError(
            f"the probe's programs routed {start} tokens, {alone} of them "
            f"alone; the probe consumed {want.shape[1]}")
    layers = differ / (alone * top_k)
    cell.say(
        f"correct: of the routed (token, layer, expert) triples of the "
        f"probe's {alone} tokens that a program carried alone "
        f"{layers.mean():.6f} differ from the reference's own (limit "
        f"{cell.traffic['check']['routing_gap_limit']}); "
        f"a layer: " + " ".join(f"{g:.4f}" for g in layers))
    return float(layers.mean())


def run(cell):
    """Run one cell through ``serve_hybrid.py``'s ``run`` (module docstring
    says what is put round it)."""
    from distributed_pytorch_tpu.models.transformer import TransformerLM

    if "ffn_types" not in getattr(TransformerLM, "__dataclass_fields__", {}):
        # A program from before the routed feed-forward (this cell's
        # parent): fail before anything is built.
        raise SystemExit(
            "this program's TransformerLM has no ffn_types: it cannot "
            "build a serve_hybrid_moe configuration")
    hybrid = _hybrid()
    probes = []
    probe_state = hybrid.probe_state

    def probe_and_keep(*args):
        probes.append(probe_with_routing(probe_state, *args))
        return probes[-1]

    hybrid.probe_state = probe_and_keep
    try:
        out = hybrid.run(cell)
    finally:
        hybrid.probe_state = probe_state
    check = cell.traffic["check"]
    out["correct"] = bool(out["correct"] and probes and all(
        probes[-1].get(key, float("inf")) <= check[f"{key}_limit"]
        for key in ("state_gap_memory", "routing_gap")))
    if probes and "after_probe" in cell.hooks:  # control_hybrid_moe.py
        cell.hooks["after_probe"](probes[-1])
    ctx = out["context"]
    if ctx is not None:
        from harness import moe_hybrid

        window, _ = ctx["ssm_ops"]
        ops = moe_hybrid.read_ops(cell.scratch("trace"), cell.config)
        ctx["ssd_ops"] = window, ops["ssd"]
        ctx["moe_ops"] = window, ops["moe"]
        cell.say(
            f"operations: {len(ops['ssd'])} of the Mamba-2 mixers' and "
            f"{len(ops['moe'])} of the expert layers' among {ops['events']} "
            f"device events, read in {ops['read_s']:.1f}s")
        routing = moe_hybrid.traced_routing(ctx)
        if routing:
            held, absent = routing["moe_pairs_held"], routing["moe_pairs_absent"]
            cell.say(
                f"routing: {routing['moe_programs']} programs of "
                f"{routing['steps']} traced steps routed {held} pairs to held "
                f"experts and {absent} to absent ones (held share "
                f"{held / (held + absent):.4f}); {routing['moe_experts_hit']} "
                f"held experts were reached, summed over programs and layers")
    return out
