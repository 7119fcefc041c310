"""Plain reference for a decoder-only LM of the ``exaone_moe`` family
(LGAI-EXAONE/K-EXAONE-236B-A23B ``config.json``): grouped-query attention in
every layer, three ``sliding_attention`` layers (a window of
``sliding_window`` positions) to every ``full_attention`` one, a dense
gated-SiLU feed-forward in the first ``first_k_dense_replace`` layers and
sigmoid-routed experts plus shared ones in the rest.

With ``H`` query heads on ``G`` KV heads of ``dh = head_dim`` (``H dh`` is NOT
the hidden size), RMSNorm epsilon ``rms_norm_eps``, no biases, for layer ``l``
with window ``W_l`` (``sliding_windows[l]``; 0: unbounded)::

    x      = E[tok]
    y      = rmsnorm(x)                                          a layer
    q_h    = rmsnorm_dh(y W_q[:, h]) * g_q;   k_g = rmsnorm_dh(y W_k[:, g]) * g_k
    v_g    = y W_v[:, g]
    q, k   <- R(q, pos), R(k, pos)              where W_l > 0;  as they are where W_l = 0
    s_h(t, u) = q_h(t) . k_{h // (H / G)}(u) / sqrt(dh),   max(0, t - W_l + 1) <= u <= t
    x      = x + concat_h(softmax_u(s_h) v_{h // (H / G)}) W_o
    x      = x + ffn(rmsnorm(x))
    logits = rmsnorm(x_L) W_head                                 untied

* the window holds ``W_l`` positions WITH the query's own;
* ``R`` is rotary over the whole head at base ``rope_theta``, dimensions paired
  by halves;
* feed-forward of layer ``l < first_k_dense_replace``: ``W_down (silu(W_gate n)
  * W_up n)`` of width ``intermediate_size``. Of every other layer: ``s =
  sigmoid(W_r n)`` over all ``n_router`` scores; the ``num_experts_per_tok``
  experts of largest ``s_e + b_e`` (``b`` the router's correction bias, used
  for the CHOICE alone; one group: ``n_group`` = ``topk_group`` = 1); gates
  ``g_e = routed_scaling_factor * s_e / sum_chosen s`` (``norm_topk_prob``);
  ``sum_e g_e E_e(n) + S(n)``, ``E_e`` a gated SiLU of
  ``moe_intermediate_size`` and ``S`` ONE gated SiLU of ``num_shared_experts``
  times that width.

What a serving system caches a token and layer is the normed, rotated ``k`` and
``v``: ``2 G dh`` numbers (4,096 B in bf16 at the published sizes); a full
layer keeps every position's, a sliding layer needs the last ``W_l``.
``probe_at`` returns them for the comparison of the program's pages.

**The chip's share.** The configuration may hold a range of the experts
(``experts_held = [lo, hi]`` of ``num_experts_published``) and the first
``vocab_size`` rows of the published vocabulary: the router keeps its
published width and top-k, pairs routed to absent experts are left out, and
the partial sum (plus the shared expert, whole) goes on to the next layer,
here exactly as in the program; token ids and logits are over the held rows.

Departures from the published description, all of them, each listed under the
configuration file's ``assumed`` with what to change if it is wrong: (a) the
RMSNorm on q and k is a HEAD's, before the rotation (EXAONE 4.0's); (b) the
rotation is on the sliding layers alone, the full layers attend on the normed
projections as they are (EXAONE 4.0's hybrid rule; the config gives one
``rope_theta`` and no key a layer); (c) a block's norms stand on each
sublayer's INPUT; rotary dimensions are paired by halves; the multi-token
prediction module is left out (it adds a draft, never a token's value);
weights are this file's own layout; the experts' gate and up projections are
one ``[held, d, 2 f]`` array with the gate half first; attention is computed a
block of ``QUERY_BLOCK`` queries at a time and the dense feed-forward a block
of ``ROW_BLOCK`` tokens at a time (which changes no value) so that a request of
12,800 tokens fits beside the program's weights.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no kernels, no batching. It imports nothing of the program under test. Every
projection, the router, the experts and the attention's two products go
through the ``einsum`` it is handed (``control.py`` hands it the int8 one);
the softmaxes, the sigmoid and the norms do not.

Weight layout: ``embed [V, d]``, ``head [d, V]``, ``lnf_g [d]``; every layer
``ln1_g ln2_g [d]``, ``wq [d, H, dh]``, ``wk wv [d, G, dh]``, ``qn_g kn_g
[dh]``, ``wo [H, dh, d]``; a dense layer ``w_gate w_up [d, f0]``, ``w_down
[f0, d]``; an expert layer ``router [d, n_router]``, ``router_bias
[n_router]`` (float32), ``we_in [held, d, 2 f]``, ``we_out [held, f, d]``,
``ws_gate ws_up [d, fs]``, ``ws_down [fs, d]``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 128  # queries a block of the attention: [H, 128, T] scores
ROW_BLOCK = 1024  # tokens a block of the dense feed-forward


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def dims(cfg: dict) -> dict:
    n_router = cfg.get("num_experts_published", cfg["num_experts"])
    lo, hi = cfg.get("experts_held") or (0, cfg["num_experts"])
    if hi - lo != cfg["num_experts"] or not 0 <= lo < hi <= n_router:
        raise ValueError(
            f"experts_held {lo}..{hi} is not {cfg['num_experts']} of "
            f"{n_router} experts")
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise ValueError("only one expert group is written")
    if cfg.get("scoring_func") != "sigmoid" or not cfg.get("norm_topk_prob"):
        raise ValueError("only renormalised sigmoid scores are written")
    layers = cfg["num_hidden_layers"]
    windows = list(cfg["sliding_windows"])
    kinds = list(cfg["mlp_layer_types"])
    if len(windows) != layers or len(kinds) != layers:
        raise ValueError("sliding_windows and mlp_layer_types name every layer")
    if any(kind not in ("dense", "sparse") for kind in kinds):
        raise ValueError(f"mlp_layer_types {sorted(set(kinds))} is not written")
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        g=cfg["num_key_value_heads"], dh=cfg["head_dim"],
        v=cfg["vocab_size"], layers=layers, windows=windows, kinds=kinds,
        f0=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        n_router=n_router, lo=lo, hi=hi, held=hi - lo,
        top_k=cfg["num_experts_per_tok"],
        theta=float(cfg["rope_parameters"]["rope_theta"]),
        scale=float(cfg["routed_scaling_factor"]),
    )


def layer_kinds(cfg: dict) -> list:
    """``"dense"`` or ``"sparse"`` for each layer, by its feed-forward."""
    return dims(cfg)["kinds"]


def layer_shapes(cfg: dict, kind: str) -> dict:
    s = dims(cfg)
    d, h, g, dh = s["d"], s["h"], s["g"], s["dh"]
    shapes = {
        "ln1_g": (d,), "ln2_g": (d,), "wq": (d, h, dh), "wk": (d, g, dh),
        "wv": (d, g, dh), "qn_g": (dh,), "kn_g": (dh,), "wo": (h, dh, d)}
    if kind == "dense":
        shapes.update(w_gate=(d, s["f0"]), w_up=(d, s["f0"]), w_down=(s["f0"], d))
    else:
        shapes.update(
            router=(d, s["n_router"]), router_bias=(s["n_router"],),
            we_in=(s["held"], d, 2 * s["f"]), we_out=(s["held"], s["f"], d),
            ws_gate=(d, s["fs"]), ws_up=(d, s["fs"]), ws_down=(s["fs"], d))
    return shapes


def _draw(key, shapes: dict, std: float, bias_std: float, dtype) -> dict:
    """Norm scales 1 + 0.02 noise; the router's correction bias float32 at
    ``bias_std``; everything else normal at ``std``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            x = (1.0 + 0.02 * jax.random.normal(k, shape, F32)).astype(dtype)
        elif name == "router_bias":
            x = bias_std * jax.random.normal(k, shape, F32)
        else:
            x = (std * jax.random.normal(k, shape, F32)).astype(dtype)
        out[name] = x
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the default device: one compiled program per kind
    of layer, called once a layer."""
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg.get("initializer_range", 0.02))
    bias_std = float(cfg["assumed"].get("router_bias_std", 0.02))
    s = dims(cfg)
    draw = {
        kind: jax.jit(functools.partial(
            _draw, shapes=layer_shapes(cfg, kind), std=std,
            bias_std=bias_std, dtype=dtype))
        for kind in sorted(set(s["kinds"]))
    }
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "head": (s["d"], s["v"]),
                       "lnf_g": (s["d"],)}, std=std, bias_std=bias_std,
        dtype=dtype))
    key = seed_key(seed)
    weights = ends(jax.random.fold_in(key, 0))
    weights["layers"] = [
        draw[kind](jax.random.fold_in(key, 1 + i))
        for i, kind in enumerate(s["kinds"])
    ]
    return weights


# ------------------------------------------------------------------ the model


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, theta: float):
    """``R`` over the last axis of ``x [T, heads, dh]`` at positions
    ``0..T-1``, the whole head, paired by halves."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = (jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs)[:, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(y, w, *, window: int, cfg: dict, einsum):
    """The attention of a layer with window ``window`` (0: none) over ``y [T,
    d]``: its output, and what a cache would hold of every position, the
    normed (and, in a sliding layer, rotated) ``k`` and ``v`` ``[2, T, G,
    dh]``."""
    s = dims(cfg)
    t, h, g, dh = y.shape[0], s["h"], s["g"], s["dh"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm(einsum("td,dhk->thk", y, w["wq"]), w["qn_g"], eps)
    k = rms_norm(einsum("td,dgk->tgk", y, w["wk"]), w["kn_g"], eps)
    v = einsum("td,dgk->tgk", y, w["wv"])
    if window:
        q, k = rope(q, s["theta"]), rope(k, s["theta"])
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    qs = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(-1, qb, g, h // g, dh)
    keys = jnp.arange(t)

    def one(xs):
        qx, start = xs  # [qb, G, H / G, dh]
        scores = einsum("qgrk,ugk->grqu", qx, k) * dh**-0.5
        rows = start + jnp.arange(qb)
        seen = keys[None, :] <= rows[:, None]
        if window:
            seen &= keys[None, :] > rows[:, None] - window
        scores = jnp.where(seen, scores, -jnp.inf)
        return einsum("grqu,ugk->qgrk", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one, (qs, jnp.arange(0, t + pad, qb)))
    out = out.reshape(t + pad, h, dh)[:t]
    return einsum("thk,hkd->td", out, w["wo"]), jnp.stack([k, v])


def router_gates(n, w, *, cfg: dict, einsum):
    """``[T, n_router]`` gates: ``routed_scaling_factor * s_e / sum_chosen s``
    at each token's ``top_k`` experts of largest ``s + bias``, zero everywhere
    else; and those experts ``[T, top_k]``."""
    s = dims(cfg)
    scores = jax.nn.sigmoid(einsum("td,de->te", n, w["router"]))
    _, experts = jax.lax.top_k(scores + w["router_bias"], s["top_k"])
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    gates = s["scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    rows = jnp.arange(n.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, experts].set(gates), experts


def routed_experts(n, w, *, cfg: dict, einsum):
    """The held experts' part of the routed layer over ``n [T, d]``: every
    held expert on every token, weighted by its gate (zero where the token
    was not routed to it), an expert at a time. And bool ``[T, n_router]``:
    the experts, held or not, that each token was routed to."""
    s = dims(cfg)
    gates, experts = router_gates(n, w, cfg=cfg, einsum=einsum)
    rows = jnp.arange(n.shape[0])[:, None]
    routed = jnp.zeros(gates.shape, bool).at[rows, experts].set(True)

    def one(total, xs):
        w_in, w_out, gate = xs
        g, u = jnp.split(einsum("td,df->tf", n, w_in.astype(F32)), 2, axis=-1)
        out = einsum("tf,fd->td", silu(g) * u, w_out.astype(F32))
        return total + gate[:, None] * out, None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (w["we_in"], w["we_out"], gates[:, s["lo"]:s["hi"]].T))
    return total, routed


def gated_mlp(n, w_gate, w_up, w_down, einsum):
    """``W_down (silu(W_gate n) * W_up n)``, ``ROW_BLOCK`` tokens at a time
    (the dense layer's ``[T, 18432]`` products of a long request would not fit
    beside the program's weights)."""
    t = n.shape[0]
    rb = min(ROW_BLOCK, t)
    pad = -t % rb

    def one(rows):
        gated = silu(einsum("td,df->tf", rows, w_gate)) * einsum(
            "td,df->tf", rows, w_up)
        return einsum("tf,fd->td", gated, w_down)

    out = jax.lax.map(
        one, jnp.pad(n, [(0, pad), (0, 0)]).reshape(-1, rb, n.shape[1]))
    return out.reshape(t + pad, -1)[:t]


_KEPT_AS_STORED = ("we_in", "we_out")


def block(x, w, *, kind: str, window: int, cfg: dict, einsum=jnp.einsum):
    """One layer over ``x [T, d]`` (float32): its output, the ``k`` and ``v``
    a cache would hold ``[2, T, G, dh]``, and the experts each token was
    routed to (``None`` from a dense layer)."""
    w = {k: v if k in _KEPT_AS_STORED else v.astype(F32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    mixed, kv = attention(
        rms_norm(x, w["ln1_g"], eps), w, window=window, cfg=cfg, einsum=einsum)
    x = x + mixed
    n = rms_norm(x, w["ln2_g"], eps)
    if kind == "dense":
        return x + gated_mlp(
            n, w["w_gate"], w["w_up"], w["w_down"], einsum), kv, None
    fed, routed = routed_experts(n, w, cfg=cfg, einsum=einsum)
    shared = gated_mlp(n, w["ws_gate"], w["ws_up"], w["ws_down"], einsum)
    return x + fed + shared, kv, routed


def _cfg_key(cfg: dict) -> str:
    return json.dumps(
        {k: v for k, v in cfg.items() if k != "assumed"}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, einsum):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]
    s = dims(cfg)

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(w_head, g, x, rows):
        y = rms_norm(x[rows], g.astype(F32), eps)
        return einsum("rd,dv->rv", y, w_head.astype(F32))

    layers = {
        (kind, window): jax.jit(functools.partial(
            block, kind=kind, window=window, cfg=cfg, einsum=einsum))
        for kind, window in set(zip(s["kinds"], s["windows"]))
    }
    return jax.jit(embed), layers, jax.jit(head)


def _through_layers(cfg, weights, tokens, einsum, keep_kv=()):
    """``tokens`` through every layer: the last hidden state ``[T, d]``, the
    ``[k, v]`` of the layers in ``keep_kv``, every expert layer's routing,
    and the head's program."""
    embed, layers, head = _programs(_cfg_key(cfg), einsum)
    s = dims(cfg)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    n = len(weights["layers"])
    keep = {i % n for i in keep_kv}
    kept, routing = {}, []
    for i, w in enumerate(weights["layers"]):
        x, kv, routed = layers[s["kinds"][i], s["windows"][i]](x, w)
        if i in keep:
            kept[i] = kv
        if routed is not None:
            routing.append(routed)
    return x, [kept[i % n] for i in keep_kv], routing, head


def routing_at(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum):
    """The experts this reference routes each of ``tokens`` to, expert layer
    by expert layer, on its own activations: bool ``[expert layers, T,
    n_router]``, ``top_k`` true a token and layer, held or not."""
    with jax.default_matmul_precision("highest"):
        _, _, routing, _ = _through_layers(cfg, weights, list(tokens), einsum)
    return jnp.stack(routing)


def probe_at(cfg: dict, weights: dict, tokens, layers=(0, 3), *,
             einsum=jnp.einsum):
    """What a cache would hold of every position of ``tokens`` in the given
    layers (``[k, v]``, float32 ``[len(layers), 2, T, G, dh]``), and
    ``routing_at``'s ``[expert layers, T, n_router]``, from ONE pass."""
    with jax.default_matmul_precision("highest"):
        _, kept, routing, _ = _through_layers(
            cfg, weights, list(tokens), einsum, keep_kv=tuple(layers))
    return jnp.stack(kept), jnp.stack(routing)


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of ONE token
    sequence ``tokens [T]`` (row ``p`` predicts token ``p + 1``). Layer by
    layer, each layer's weights upcast inside its own program. ``pad_*_to``
    pad the sequence (at its end: causal attention carries nothing backwards,
    and a token's experts do not look at other tokens) and the rows, so that
    one compiled program serves requests of every length."""
    tokens, rows = list(tokens), list(rows)
    if not rows:
        raise ValueError("no row to score")
    n = len(rows)
    tokens += [0] * (pad_tokens_to - len(tokens))
    rows += [rows[-1]] * (pad_rows_to - n)
    with jax.default_matmul_precision("highest"):
        x, _, _, head = _through_layers(cfg, weights, tokens, einsum)
        return head(weights["head"], weights["lnf_g"], x,
                    jnp.asarray(rows, jnp.int32))[:n]


def forward(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum):
    """Float32 logits ``[T, V]`` at every position: the CPU tests' full
    forward."""
    return logits_at(cfg, weights, tokens, range(len(tokens)), einsum=einsum)


# ------------------------------------------------------------------ the counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against: in a layer's attention, in the
    dense feed-forward, in the shared expert and the router of an expert
    layer, in ONE routed expert, and in the output head."""
    s = dims(cfg)
    d = s["d"]
    return {
        "attention": 2 * d * s["h"] * s["dh"] + 2 * d * s["g"] * s["dh"],
        "dense": 3 * d * s["f0"], "shared": 3 * d * s["fs"],
        "router": d * s["n_router"], "expert": 3 * d * s["f"],
        "head": d * s["v"],
    }


def held_parameters(cfg: dict) -> int:
    """Every parameter this share holds: the matrices, the norms' scales, the
    routers' correction biases, the embedding and the untied head."""
    s = dims(cfg)
    p = matmul_params(cfg)
    norms = 2 * s["d"] + 2 * s["dh"]
    sparse = (p["attention"] + p["shared"] + p["router"] + s["n_router"]
              + s["held"] * p["expert"])
    n_dense = s["kinds"].count("dense")
    return (n_dense * (p["attention"] + p["dense"] + norms)
            + (s["layers"] - n_dense) * (sparse + norms)
            + 2 * p["head"] + s["d"])


def kv_bytes_per_token_layer(cfg: dict, bytes_per_value: int = 2) -> int:
    """What the architecture caches a token and layer: ``k`` and ``v`` on
    ``G`` heads of ``dh`` (4,096 B in bf16 at the published sizes)."""
    s = dims(cfg)
    return 2 * s["g"] * s["dh"] * bytes_per_value


def experts_reached(cfg: dict, tokens: float) -> float:
    """Held experts of a layer that ``tokens`` tokens reach, at their
    expectation under even routing."""
    s = dims(cfg)
    return s["held"] * (1.0 - (1.0 - s["top_k"] / s["n_router"]) ** tokens)


def window_layers(cfg: dict) -> int:
    return sum(w > 0 for w in dims(cfg)["windows"])


def window_decode_min_bytes(cfg: dict, visible_tokens: float,
                            bytes_per_value: int = 2) -> float:
    """Least bytes the window layers' decode attention has to read, ALL of
    them: ``k`` and ``v`` of the tokens inside the rows' windows
    (``visible_tokens``: a layer's sum over the rows of ``min(pos + 1,
    W)``), once a layer."""
    return float(visible_tokens) * kv_bytes_per_token_layer(
        cfg, bytes_per_value) * window_layers(cfg)


def window_decode_flops(cfg: dict, visible_tokens: float) -> float:
    """FLOPs of the window layers' decode attention, all of them, over
    ``visible_tokens`` (query, key) pairs a layer: every head's ``dh`` wide
    score and ``dh`` wide weighted sum."""
    s = dims(cfg)
    return 4.0 * s["h"] * s["dh"] * float(visible_tokens) * window_layers(cfg)


def _window_share(cfg: dict, context: float, queries: float) -> float:
    """Of ``context`` (query, key) pairs of ``queries`` queries, what a window
    layer keeps at most: its window a query."""
    window = max(w for w in dims(cfg)["windows"])
    return min(float(context), float(queries) * window)


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through this share of the
    model when their queries attend to ``context_tokens`` keys in all (in a
    full layer; a sliding layer's queries to their windows at most) and
    ``logits_rows`` go through the head. The routed pairs on held experts are
    taken at their expectation under even routing."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n_dense = s["kinds"].count("dense")
    n_sparse = s["layers"] - n_dense
    pairs = s["top_k"] * s["held"] / s["n_router"]
    dense = 2.0 * new_tokens * (
        s["layers"] * p["attention"] + n_dense * p["dense"]
        + n_sparse * (p["shared"] + p["router"] + pairs * p["expert"]))
    sliding = window_layers(cfg)
    attn = 4.0 * s["h"] * s["dh"] * (
        (s["layers"] - sliding) * context_tokens
        + sliding * _window_share(cfg, context_tokens, new_tokens))
    return dense + attn + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move, however many programs the
    engine makes of it: every held weight a token of the step reaches, ONCE
    (the held experts at ``experts_reached``; the embedding's rows, not its
    table); ``k`` and ``v`` of the cached tokens read, at the published 4,096
    B a token and layer: the whole context ``kv_tokens_read`` (the SUM of the
    rows' contexts) in a full layer, ``min(pos + 1, W)`` a row in a sliding
    one (taken as ``min(context, rows * W)``: the most the windows can hold);
    and the new tokens' writes in every layer. ``prefill_chunks`` is not
    used: nothing here is read once a chunk."""
    s = dims(cfg)
    p = matmul_params(cfg)
    new = decode_rows + prefill_tokens
    n_dense = s["kinds"].count("dense")
    n_sparse = s["layers"] - n_dense
    weights = (
        s["layers"] * p["attention"] + n_dense * p["dense"]
        + n_sparse * (p["shared"] + p["router"]
                      + experts_reached(cfg, new) * p["expert"])
        + p["head"] + new * s["d"])
    sliding = window_layers(cfg)
    # A prefill piece's rows are one request's: its queries' windows overlap,
    # so they read at most W + tokens - 1 keys; counted as the decode rows'
    # windows plus the new tokens themselves (written below).
    cached = ((s["layers"] - sliding) * float(kv_tokens_read)
              + sliding * _window_share(cfg, kv_tokens_read, max(decode_rows, 1)))
    return (bytes_per_param * weights
            + kv_bytes_per_token_layer(cfg) * (cached + s["layers"] * new))
