"""Driver for configurations of kind ``serve_sparse_latent_moe``: a decoder
whose layers are latent attention of TWO kinds (``layer_types``: full layers
with an indexer that selects the ``index_topk`` cached tokens a query attends
to, sliding layers with a window and sizes of their own), whose
feed-forwards are one dense layer, then sigmoid-routed experts plus a shared
one (``dots3_note``), served through the program's normal ``InferenceEngine``
with the prefix trie ON, holding the chip's share of the experts.

Everything about the loop, the documents, the warm-up, the window, the trace
and the served tokens' comparison is ``drivers/serve_latent_moe.py``'s (which
loads ``drivers/serve.py``): ``run`` loads a private copy of that file, rebinds
what differs and calls its ``run``
(``benchmarks/README.serve_sparse_latent_moe.md``). What differs:

* **The field test**: a program whose ``LAYER_TYPES`` has no
  ``"latent_sparse"`` cannot build this model; ``run`` exits non-zero before
  anything is built (this cell's parent does).
* **``build_program``**: the program's model for such a configuration (two
  latent variants, the third gating rule) and its parameter tree, filled with
  the reference's weights.
* **The probe** reads more: beside the routing, the probe's latent pages of
  the FIRST FULL and of the FIRST SLIDING layer, its index-key pages of the
  first full layer, and, for every decoded token, the positions each full
  layer selected (the engine's ``selected_positions``).
* **``GAPS``**, each under ``check.<name>_limit``: ``routing_gap``;
  ``latent_gap``, ``latent_gap_sliding`` and ``latent_gap_last`` (the first
  full, the first sliding and the last layer's pages against the reference's
  ``[c | k_r]``: the last layer's input holds what every layer before it did,
  a sliding layer's attention among it, which no earlier reading sees); ``index_gap`` (the index-key pages against the
  reference's ``k^I``); ``selection_gap`` (the share of the probe's (decoded
  token, full layer, selected position) triples that are not in the
  reference's own top ``index_topk`` for that token and layer) and
  ``selection_gap_first`` (at the FIRST full layer alone, whose input is the
  embeddings and whose selection only rounding moves: the larger of that share
  and of the share of the reference's selection that the program did not
  select, which is what a program that selects fewer reads).
* **The experts go to the host before the reference runs**: a 49,664-token
  pass of the reference needs the room (6.5 GB of its own beside what is
  resident), the window is over and the engine closed. The reference's layer
  programs take them from there, a layer at a time.
* **The operations of the new kernels**, read from the trace file for
  ``harness/dsa.py``'s readers (``ctx["dsa_ops"]``).
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_SECONDS = 3.0  # of the window's end that a traced run traces
GAPS = ("routing_gap", "latent_gap", "latent_gap_sliding", "latent_gap_last",
        "index_gap", "selection_gap", "selection_gap_first")
LAYER_TYPE = {"full_attention": "latent_sparse",
              "sliding_attention": "latent_window"}
_BASE = "bench_drivers_serve_latent_moe_for_sparse"


def _base():
    """A private copy of ``drivers/serve_latent_moe.py``, loaded once."""
    module = sys.modules.get(_BASE)
    if module is None:
        spec = importlib.util.spec_from_file_location(
            _BASE, os.path.join(HERE, "serve_latent_moe.py"))
        module = importlib.util.module_from_spec(spec)
        sys.modules[_BASE] = module
        spec.loader.exec_module(module)
    return module


def reference_logits(cell, weights, r, sample, **kw):
    """``serve.py``'s, for ``control.py``'s ``serve_control``."""
    return _base().reference_logits(cell, weights, r, sample, **kw)


def latent_variant(cfg: dict, prefix: str, **more) -> tuple:
    """``LatentAttention``'s sizes for the layers whose keys carry ``prefix``
    (``""`` the full layers', ``"swa_"`` the sliding ones')."""
    sizes = dict(
        n_heads=cfg[prefix + "num_attention_heads"],
        kv_lora_rank=cfg[prefix + "kv_lora_rank"],
        q_lora_rank=cfg[prefix + "q_lora_rank"],
        qk_nope_head_dim=cfg[prefix + "qk_nope_head_dim"],
        qk_rope_head_dim=cfg[prefix + "qk_rope_head_dim"],
        v_head_dim=cfg[prefix + "v_head_dim"],
        rope_theta=float(cfg[prefix + "rope_theta"]),
        lora_rescale=bool(cfg["apply_mla_qkv_lora_rescale"]),
        gate=cfg[prefix + "attention_gate_type"] == "headwise", **more)
    return tuple(sorted(sizes.items()))


def build_program(cfg: dict, weights: dict):
    """The program's model and its parameter tree, filled with the
    benchmark's weights (the same device arrays the reference reads)."""
    import jax.numpy as jnp

    from distributed_pytorch_tpu.models.transformer import TransformerLM

    kinds = _base().layer_kinds(cfg)
    n_router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    held = tuple(cfg.get("experts_held") or (0, cfg["n_routed_experts"]))
    if (cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]
            or cfg["routed_scaling_factor"] != 1 or cfg.get("rope_scaling")):
        raise ValueError(
            "only renormalised sigmoid scores at a scaling factor of 1 and "
            "plain rotary frequencies are built")
    model = TransformerLM(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        d_ff=cfg["moe_intermediate_size"], dense_d_ff=cfg["intermediate_size"],
        dtype=jnp.dtype(cfg["torch_dtype"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm="rmsnorm", norm_eps=cfg["rms_norm_eps"], mlp="gated_silu",
        use_bias=False, rope_theta=float(cfg["rope_theta"]),
        layer_types=tuple(LAYER_TYPE[t] for t in cfg["layer_types"]),
        latent_variants=(
            ("latent_sparse", latent_variant(
                cfg, "", index_heads=cfg["index_n_heads"],
                index_dim=cfg["index_head_dim"],
                index_top_k=cfg["index_topk"])),
            ("latent_window", latent_variant(
                cfg, "swa_", window=cfg["sliding_window_size"])),
        ),
        ffn_types=kinds, routed_experts=n_router,
        routed_top_k=cfg["num_experts_per_tok"], experts_held=held,
        shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        routed_gating="sigmoid_biased",
    )
    params = {
        "embed": {"embedding": weights["embed"]},
        "ln_final": {"scale": weights["lnf_g"]},
        "lm_head": {"kernel": weights["head"],
                    "bias": jnp.zeros((cfg["vocab_size"],), jnp.float32)},
    }
    for i, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        mla = {"q_a": {"kernel": w["wqa"]}, "q_norm": {"scale": w["qn_g"]},
               "query": {"kernel": w["wqb"]}, "kv_a": {"kernel": w["wkva"]},
               "kv_norm": {"scale": w["kvn_g"]}, "kv_b": w["wkvb"],
               "gate": {"kernel": w["wg"]}, "out": {"kernel": w["wo"]}}
        if "wiq" in w:
            mla.update(
                index_q={"kernel": w["wiq"]}, index_k={"kernel": w["wik"]},
                index_k_norm={"scale": w["ikn_g"], "bias": w["ikn_b"]},
                index_w={"kernel": w["wiw"]})
        layer = {"ln_attn": {"scale": w["ln1_g"]},
                 "ln_mlp": {"scale": w["ln2_g"]}, "mla": mla}
        if kind == "dense":
            layer["mlp"] = {"gate": {"kernel": w["w_gate"]},
                            "up": {"kernel": w["w_up"]},
                            "down": {"kernel": w["w_down"]}}
        else:
            layer["experts"] = {"router_kernel": w["router"],
                                "router_bias": w["router_b"],
                                "in_kernel": w["we_in"],
                                "out_kernel": w["we_out"]}
            layer["shared_mlp"] = {"gate": {"kernel": w["ws_gate"]},
                                   "up": {"kernel": w["ws_up"]},
                                   "down": {"kernel": w["ws_down"]}}
        params[f"block_{i}"] = layer
    return model, params


def layers_of(cfg: dict, layer_type: str) -> list:
    return [i for i, t in enumerate(cfg["layer_types"]) if t == layer_type]


def pool_rows(cache, name: str, pages) -> dict:
    """layer -> float32 ``[len(pages) * page, width]``: the rows of physical
    ``pages``, in order, of every layer's pool called ``name``."""
    import jax
    import numpy as np

    ids = np.asarray(pages, np.int32)
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        keys = [str(getattr(k, "key", "")) for k in path]
        if keys[-1] == name:
            layer = next(int(k[6:]) for k in keys if k.startswith("block_"))
            out[layer] = np.asarray(leaf[ids], np.float32).reshape(
                len(ids) * leaf.shape[1], -1)
    return out


def probe(engine, cell, serve) -> dict:
    """One request through the engine as the window left it
    (``serve_latent_moe.py``'s probe: the median document, a prefix hit, a
    question, ``check.probe_output`` decoded tokens), and what this kind reads
    from it (module docstring)."""
    import numpy as np

    from distributed_pytorch_tpu.serving import SamplingParams

    base = _base()
    cfg, traffic = cell.config, cell.traffic
    engine.finish_inflight()
    for rid, req in list(engine.requests.items()):
        if not req.done:
            engine.cancel(rid)
    events = getattr(engine.tracer, "events", [])  # none on the null tracer
    kept = len(events)
    lengths = traffic["documents"]
    doc = sorted(range(len(lengths)), key=lambda i: lengths[i])[len(lengths) // 2]
    check = traffic["check"]
    prompt = base.document_tokens(
        serve, cell.seed, traffic, doc, cfg["vocab_size"]
    ) + serve.prompt_tokens(
        cell.seed, base.PROBE_INDEX, check["probe_question"], cfg["vocab_size"])
    want = check["probe_output"]
    rid = engine.submit(prompt, SamplingParams(max_new_tokens=want))
    req = engine.requests[rid]
    programs, pages, selected = [], [], {}
    while not engine.poll(rid).finished:
        at = req.len_cached
        engine.step()
        programs.extend(engine.routing_counts)
        if req.table.pages:
            pages = list(req.table.pages)
        for chosen in getattr(engine, "selected_positions", ()):
            # The probe is alone in the engine: a decode program that ran
            # fed its token at position ``at``.
            if req.slot is not None:
                selected[at] = np.asarray(chosen)[:, req.slot]
    status = engine.poll(rid)
    if status.state != "finished" or len(status.generated) != want:
        raise RuntimeError(f"the probe ended {status.state}")
    del events[kept:]
    tokens = prompt + status.generated[:-1]
    full, sliding = (layers_of(cfg, t)[0]
                     for t in ("full_attention", "sliding_attention"))
    latents = pool_rows(engine.cache, "cached_latent", pages)
    index = pool_rows(engine.cache, "cached_index", pages)
    return {
        "tokens": tokens, "document": doc, "prompt": len(prompt),
        "cached": int(req.cached_prompt_tokens or 0),
        "routing": [np.asarray(counts) for counts in programs],
        "layers": (full, sliding),
        "latents": [latents[i][: len(tokens)] for i in sorted(latents)],
        "index_keys": index[full][: len(tokens)],
        "selected": selected,
    }


def relative_gap(got, want) -> float:
    import numpy as np

    want = np.asarray(want, np.float32)
    got = np.asarray(got, np.float32)[..., : want.shape[-1]]
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def selection_gap(cell, want, probed: dict, rows) -> tuple:
    """``(selection_gap, selection_gap_first)``: the share of the probe's
    (decoded token, full layer, selected position) triples that are not in
    the reference's own selection for that token and layer; and at the first
    full layer the larger of that share and of the share of the reference's
    own selection that the program did not select. ``want`` is bool ``[full
    layers, rows, T]`` at the positions ``rows``."""
    import numpy as np

    want = np.asarray(want)
    tokens = 0
    outside, chosen, missed, wanted = (np.zeros(want.shape[0]) for _ in range(4))
    for i, position in enumerate(rows):
        got = probed["selected"].get(position)
        if got is None:
            continue
        tokens += 1
        for layer, positions in enumerate(got):
            positions = positions[positions >= 0]
            inside = int(want[layer, i, positions].sum())
            outside[layer] += len(positions) - inside
            chosen[layer] += len(positions)
            missed[layer] += int(want[layer, i].sum()) - inside
            wanted[layer] += int(want[layer, i].sum())
    if not chosen.sum():
        raise RuntimeError("the probe's programs reported no selection")
    check = cell.traffic["check"]
    gap = float(outside.sum() / chosen.sum())
    first = float(max(outside[0] / chosen[0], missed[0] / max(wanted[0], 1)))
    cell.say(
        f"correct: of the {int(chosen.sum())} (decoded token, full layer, "
        f"selected position) triples of the probe's {tokens} decoded tokens "
        f"{gap:.6f} are not in the reference's own top "
        f"{cell.config['index_topk']} (limit {check['selection_gap_limit']}); "
        "a layer: " + " ".join(
            f"{g:.4f}" for g in outside / np.maximum(chosen, 1))
        + f"; at the first full layer {outside[0] / chosen[0]:.6f} are not in "
        f"it and {missed[0] / max(wanted[0], 1):.6f} of it were not selected "
        f"(the larger under the limit {check['selection_gap_first_limit']})")
    return gap, first


def decode_rows(probed: dict) -> list:
    """The positions a decode program fed of a probe's tokens: the prompt's
    last token (a prefill stops short of it) and every served token but the
    last, which is never fed."""
    return list(range(probed["prompt"] - 1, len(probed["tokens"])))


def probe_gaps(cell, weights, probed: dict, **kw) -> dict:
    """Every one of ``GAPS`` of a probe, from ONE pass of the reference over
    its tokens (padded as the served tokens' comparison pads, so that the
    layers' compiled programs are shared)."""
    import numpy as np

    base = _base()
    check = cell.traffic["check"]
    n = len(probed["tokens"])
    rows = decode_rows(probed)
    want = cell.reference.probe_at(
        cell.config, weights, probed["tokens"], rows,
        pad_tokens_to=check["pad_tokens_to"],
        pad_rows_to=check["probe_output"], **kw)
    full, sliding = probed["layers"]
    by_layer = [relative_gap(got, ref)
                for got, ref in zip(probed["latents"], want["latents"])]
    gaps = {
        "latent_gap": by_layer[full], "latent_gap_sliding": by_layer[sliding],
        "latent_gap_last": by_layer[-1],
        "index_gap": relative_gap(probed["index_keys"], want["index_keys"][0]),
    }
    cell.say(
        f"correct: the probe's pages ({probed['cached']} of its {n} positions "
        f"served by the trie) lie {gaps['latent_gap']:.6f} (first full layer, "
        f"[c | k_r]), {gaps['latent_gap_sliding']:.6f} (first sliding layer) "
        f"and {gaps['index_gap']:.6f} (first full layer, index keys) from the "
        f"reference's (limits {check['latent_gap_limit']}, "
        f"{check['latent_gap_sliding_limit']}, {check['index_gap_limit']}); "
        "every layer's [c | k_r]: " + " ".join(f"{g:.4f}" for g in by_layer)
        + f" (the last under the limit {check['latent_gap_last_limit']})")
    # serve_latent_moe.py's routing_gap reads routing [layers, T, E] from the
    # probe's first served position on: hand it the decoded rows alone.
    routed = np.zeros(
        (np.asarray(want["routing"]).shape[0], n, want["routing"].shape[-1]),
        bool)
    routed[:, rows] = np.asarray(want["routing"])
    gaps["routing_gap"] = base.routing_gap(cell, routed, probed)
    gaps["selection_gap"], gaps["selection_gap_first"] = selection_gap(
        cell, want["selected"], probed, rows)
    return gaps


def experts_to_host(weights: dict) -> None:
    """Move every layer's routed experts to host memory, in place, and free
    their device buffers (module docstring)."""
    import numpy as np

    for w in weights["layers"]:
        for name in ("we_in", "we_out"):
            if name in w and not isinstance(w[name], np.ndarray):
                held = w[name]
                w[name] = np.asarray(held)
                held.delete()


def run(cell):
    """Run one cell through ``serve_latent_moe.py``'s ``run`` (module
    docstring says what is put round it)."""
    from distributed_pytorch_tpu.models import transformer

    if "latent_sparse" not in getattr(transformer, "LAYER_TYPES", ()):
        # A program from before learned sparse attention (this cell's
        # parent): fail before anything is built.
        raise SystemExit(
            "this program's LAYER_TYPES has no 'latent_sparse': it cannot "
            "build a serve_sparse_latent_moe configuration")
    base = _base()
    serve = base._serve()
    base.TRACE_SECONDS = TRACE_SECONDS
    base.GAPS = GAPS
    base.build_program = build_program
    base.probe = probe
    base.probe_gaps = probe_gaps
    check_against_reference = serve.check_against_reference

    def check_with_room(cell, weights, sample):
        experts_to_host(weights)
        return check_against_reference(cell, weights, sample)

    serve.check_against_reference = check_with_room
    try:
        out = base.run(cell)
    finally:
        serve.check_against_reference = check_against_reference
    ctx = out["context"]
    if ctx is not None:
        from harness import dsa

        ops = dsa.read_ops(cell.scratch("trace"), cell.config)
        ctx["dsa_ops"] = ops
        cell.say(
            "operations: " + ", ".join(
                f"{len(ops[kind])} {kind}" for kind in dsa.KINDS)
            + f" among {ops['events']} device events, read in "
            f"{ops['read_s']:.1f}s")
    return out
