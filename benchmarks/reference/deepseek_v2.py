"""Plain reference for a decoder-only LM of the ``deepseek_v2`` family (HF
``modeling_deepseek.py``, ``q_lora_rank`` null): multi-head latent attention
in every layer, a dense gated-SiLU feed-forward in the first
``first_k_dense_replace`` layers and routed experts plus shared ones in the
rest.

With ``H`` heads, ``dn = qk_nope_head_dim``, ``dr = qk_rope_head_dim``, ``dv =
v_head_dim``, ``r = kv_lora_rank``, RMSNorm epsilon ``rms_norm_eps``, no
biases::

    x      = E[tok]
    y      = rmsnorm(x)                                  a layer
    q      = y W_q            H heads of [q_nope (dn) | q_pe (dr)];  q_pe <- R(q_pe, pos)
    [c_raw | kpe_raw] = y W_kva;   c = rmsnorm(c_raw);   k_pe = R(kpe_raw, pos)
    [k_nope_h | v_h]  = c W_kvb[:, h]
    s_h(t, u) = a (q_nope_h(t) . k_nope_h(u) + q_pe_h(t) . k_pe(u)),  u <= t
    x      = x + concat_h(softmax_u(s_h) v_h) W_o
    x      = x + ffn(rmsnorm(x))
    logits = rmsnorm(x_L) W_head                         untied

This is the EXPANDED form and the only one here: keys and values are rebuilt
from the latent for every position, and nothing is absorbed or cached. What a
serving system caches a token and layer is ``[c | k_pe]`` (``r + dr`` numbers);
``probe_at`` returns it for the comparison of the program's pages.

* ``R`` is rotary over ``dr`` dimensions with YaRN (``rope_scaling``: ``factor``
  ``s``, ``original_max_position_embeddings`` ``L``, ``beta_fast``,
  ``beta_slow``): ``f_i = theta ** (-i / (dr / 2))``; ``corr(n) = dr ln(L / (2
  pi n)) / (2 ln theta)``; ``low = floor(corr(beta_fast))``, ``high =
  ceil(corr(beta_slow))``; ``m_i = 1 - clip((i - low) / (high - low), 0, 1)``;
  ``f'_i = f_i m_i + (f_i / s)(1 - m_i)``. cos and sin are multiplied by ``g(s,
  mscale) / g(s, mscale_all_dim)``, ``g(s, m) = 0.1 m ln s + 1``; the score
  scale is ``a = (dn + dr) ** -0.5 g(s, mscale_all_dim) ** 2``.
* feed-forward of layer ``i < first_k_dense_replace``: ``W_down (silu(W_gate
  n) * W_up n)`` of width ``intermediate_size``. Of every other layer
  (``moe_layer_freq`` 1): ``p = softmax(W_r n)`` over ALL ``n_router`` scores;
  the ``num_experts_per_tok`` largest ``p_e`` (``topk_method`` greedy, one
  group); gates are those ``p_e`` times ``routed_scaling_factor``, NOT
  renormalised (``norm_topk_prob`` false; true divides them by their sum);
  ``sum_e p_e E_e(n) + S(n)``, ``E_e`` a gated SiLU of ``moe_intermediate_size``
  and ``S`` ONE gated SiLU of ``n_shared_experts`` times that width (the
  shared experts side by side).

**The chip's share.** The configuration may hold a range of the experts
(``experts_held = [lo, hi]`` of ``n_routed_experts_published``): the router
keeps its published width and top-k, pairs routed to absent experts are left
out, and the partial sum (plus the shared experts, whole) goes on to the next
layer, here exactly as in the program.

Departures from the published modelling code, all of them: rotary dimensions
are paired by halves (``x[:dr/2]`` with ``x[dr/2:]``) where the checkpoint
pairs them interleaved and permutes them before rotating: with seeded weights
a fixed permutation of ``dr`` columns of ``W_q`` and ``W_kva``; weights are
this file's own layout; the experts' gate and up projections are one ``[held,
d, 2 f]`` array with the gate half first; attention is computed a block of
``QUERY_BLOCK`` queries at a time (which changes no value) so that a request
of 15,000 tokens fits one chip.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache,
no kernels, no batching. It imports nothing of the program under test. Every
projection, the router, the experts and the attention's two products go
through the ``einsum`` it is handed (``control.py`` hands it the int8 one);
the softmaxes and the norms do not.

Weight layout: ``embed [V, d]``, ``head [d, V]``, ``lnf_g [d]``; every layer
``ln1_g ln2_g [d]``, ``wq [d, H, dn + dr]``, ``wkva [d, r + dr]``, ``kvn_g
[r]``, ``wkvb [r, H, dn + dv]``, ``wo [H, dv, d]``; a dense layer ``w_gate
w_up [d, f0]``, ``w_down [f0, d]``; an expert layer ``router [d, n_router]``,
``we_in [held, d, 2 f]``, ``we_out [held, f, d]``, ``ws_gate ws_up [d, fs]``,
``ws_down [fs, d]``.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512  # queries a block of the attention


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def dims(cfg: dict) -> dict:
    n_router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    if hi - lo != cfg["n_routed_experts"] or not 0 <= lo < hi <= n_router:
        raise ValueError(
            f"experts_held {lo}..{hi} is not {cfg['n_routed_experts']} of "
            f"{n_router} experts")
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("q_lora_rank: only the direct query projection is written")
    if cfg.get("moe_layer_freq", 1) != 1 or cfg.get("n_group", 1) != 1:
        raise ValueError("only moe_layer_freq 1 and one expert group are written")
    if cfg.get("scoring_func", "softmax") != "softmax":
        raise ValueError(f"scoring_func {cfg['scoring_func']!r} is not written")
    return dict(
        d=cfg["hidden_size"], h=cfg["num_attention_heads"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], r=cfg["kv_lora_rank"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        f0=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        n_router=n_router, lo=lo, hi=hi, held=hi - lo,
        top_k=cfg["num_experts_per_tok"],
    )


def layer_kinds(cfg: dict) -> list:
    """``"dense"`` or ``"moe"`` for each layer, by its feed-forward."""
    s = dims(cfg)
    return ["dense" if i < s["dense"] else "moe" for i in range(s["layers"])]


def layer_shapes(cfg: dict, kind: str) -> dict:
    s = dims(cfg)
    d, h = s["d"], s["h"]
    shapes = {
        "ln1_g": (d,), "ln2_g": (d,), "wq": (d, h, s["dn"] + s["dr"]),
        "wkva": (d, s["r"] + s["dr"]), "kvn_g": (s["r"],),
        "wkvb": (s["r"], h, s["dn"] + s["dv"]), "wo": (h, s["dv"], d)}
    if kind == "dense":
        shapes.update(w_gate=(d, s["f0"]), w_up=(d, s["f0"]), w_down=(s["f0"], d))
    else:
        shapes.update(
            router=(d, s["n_router"]), we_in=(s["held"], d, 2 * s["f"]),
            we_out=(s["held"], s["f"], d), ws_gate=(d, s["fs"]),
            ws_up=(d, s["fs"]), ws_down=(s["fs"], d))
    return shapes


def _draw(key, shapes: dict, std: float, dtype) -> dict:
    """Norm scales 1 + 0.02 noise; everything else normal at ``std``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):
            x = 1.0 + 0.02 * jax.random.normal(k, shape, F32)
        else:
            x = std * jax.random.normal(k, shape, F32)
        out[name] = x.astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the default device: one compiled program per kind
    of layer, called once a layer."""
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg.get("initializer_range", 0.02))
    s = dims(cfg)
    draw = {
        kind: jax.jit(functools.partial(
            _draw, shapes=layer_shapes(cfg, kind), std=std, dtype=dtype))
        for kind in ("dense", "moe")
    }
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "head": (s["d"], s["v"]),
                       "lnf_g": (s["d"],)}, std=std, dtype=dtype))
    key = seed_key(seed)
    weights = ends(jax.random.fold_in(key, 0))
    weights["layers"] = [
        draw[kind](jax.random.fold_in(key, 1 + i))
        for i, kind in enumerate(layer_kinds(cfg))
    ]
    return weights


# ------------------------------------------------------------------ the model


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def silu(x):
    return x * jax.nn.sigmoid(x)


def yarn_g(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(cfg: dict) -> tuple:
    """``(low, high)`` of the module docstring."""
    rs, dr, theta = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]

    def corr(n):
        return dr * math.log(
            rs["original_max_position_embeddings"] / (n * 2 * math.pi)
        ) / (2 * math.log(theta))

    return (max(math.floor(corr(rs["beta_fast"])), 0),
            min(math.ceil(corr(rs["beta_slow"])), dr - 1))


def rope_frequencies(cfg: dict):
    """``f'_i`` (``f_i`` where the configuration has no ``rope_scaling``),
    float32 ``[dr / 2]``."""
    half = cfg["qk_rope_head_dim"] // 2
    f = cfg["rope_theta"] ** (-jnp.arange(half, dtype=F32) / half)
    rs = cfg.get("rope_scaling")
    if not rs:
        return f
    low, high = yarn_range(cfg)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / max(high - low, 1e-3), 0, 1)
    m = 1.0 - ramp
    return f * m + (f / rs["factor"]) * (1.0 - m)


def rope_multiplier(cfg: dict) -> float:
    rs = cfg.get("rope_scaling")
    if not rs:
        return 1.0
    return yarn_g(rs["factor"], rs.get("mscale", 1.0)) / yarn_g(
        rs["factor"], rs.get("mscale_all_dim", 0.0) or 0.0)


def score_scale(cfg: dict) -> float:
    a = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        a *= yarn_g(rs["factor"], rs["mscale_all_dim"]) ** 2
    return a


def rope(x, cfg: dict):
    """``R`` over the last axis of ``x [T, ..., dr]`` at positions ``0..T-1``,
    paired by halves."""
    half = x.shape[-1] // 2
    pos = jnp.arange(x.shape[0], dtype=F32)
    angles = pos[:, None] * rope_frequencies(cfg)
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    mult = rope_multiplier(cfg)
    cos, sin = jnp.cos(angles) * mult, jnp.sin(angles) * mult
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(y, w, *, cfg: dict, einsum):
    """The latent attention over ``y [T, d]``, expanded: its output and what
    a cache would hold of every position, ``[c | k_pe] [T, r + dr]``."""
    s = dims(cfg)
    t, dn, r = y.shape[0], s["dn"], s["r"]
    q = einsum("td,dhk->thk", y, w["wq"])
    q_nope, q_pe = q[..., :dn], rope(q[..., dn:], cfg)
    lat = einsum("td,de->te", y, w["wkva"])
    c = rms_norm(lat[:, :r], w["kvn_g"], cfg["rms_norm_eps"])
    k_pe = rope(lat[:, r:], cfg)
    kv = einsum("tr,rhn->thn", c, w["wkvb"])
    k_nope, v = kv[..., :dn], kv[..., dn:]
    a = score_scale(cfg)
    qb = min(QUERY_BLOCK, t)
    pad = -t % qb
    blocks = lambda z: jnp.pad(  # noqa: E731
        z, [(0, pad)] + [(0, 0)] * (z.ndim - 1)).reshape((-1, qb) + z.shape[1:])
    keys = jnp.arange(t)

    def one(xs):
        qn, qp, start = xs
        scores = (einsum("qhn,khn->hqk", qn, k_nope)
                  + einsum("qhd,kd->hqk", qp, k_pe)) * a
        rows = start + jnp.arange(qb)
        scores = jnp.where(keys[None, :] <= rows[:, None], scores, -jnp.inf)
        return einsum("hqk,khv->qhv", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(
        one, (blocks(q_nope), blocks(q_pe), jnp.arange(0, t + pad, qb)))
    out = out.reshape((t + pad,) + out.shape[2:])[:t]
    return (einsum("thv,hvd->td", out, w["wo"]),
            jnp.concatenate([c, k_pe], axis=-1))


def router_gates(n, w_router, *, cfg: dict, einsum):
    """``[T, n_router]`` gates: the softmax over all scores at each token's
    ``top_k`` largest, zero everywhere else."""
    s = dims(cfg)
    probs = jax.nn.softmax(einsum("td,de->te", n, w_router), axis=-1)
    best, experts = jax.lax.top_k(probs, s["top_k"])
    if cfg.get("norm_topk_prob"):
        best = best / (jnp.sum(best, axis=-1, keepdims=True) + 1e-20)
    best = best * cfg.get("routed_scaling_factor", 1.0)
    rows = jnp.arange(n.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, experts].set(best), experts


def routed_experts(n, w, *, cfg: dict, einsum):
    """The held experts' part of the routed layer over ``n [T, d]``: every
    held expert on every token, weighted by its gate (zero where the token
    was not routed to it), an expert at a time. And bool ``[T, n_router]``:
    the experts, held or not, that each token was routed to."""
    s = dims(cfg)
    gates, experts = router_gates(n, w["router"], cfg=cfg, einsum=einsum)
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
    gated = silu(einsum("td,df->tf", n, w_gate)) * einsum("td,df->tf", n, w_up)
    return einsum("tf,fd->td", gated, w_down)


_KEPT_AS_STORED = ("we_in", "we_out")


def block(x, w, *, kind: str, cfg: dict, einsum=jnp.einsum):
    """One layer over ``x [T, d]`` (float32): its output, the latent a cache
    would hold ``[T, r + dr]``, and the experts each token was routed to
    (``None`` from a dense layer)."""
    w = {k: v if k in _KEPT_AS_STORED else v.astype(F32) for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    mixed, latent = attention(
        rms_norm(x, w["ln1_g"], eps), w, cfg=cfg, einsum=einsum)
    x = x + mixed
    n = rms_norm(x, w["ln2_g"], eps)
    if kind == "dense":
        return x + gated_mlp(
            n, w["w_gate"], w["w_up"], w["w_down"], einsum), latent, None
    fed, routed = routed_experts(n, w, cfg=cfg, einsum=einsum)
    shared = gated_mlp(n, w["ws_gate"], w["ws_up"], w["ws_down"], einsum)
    return x + fed + shared, latent, routed


def _cfg_key(cfg: dict) -> str:
    return json.dumps(
        {k: v for k, v in cfg.items() if k != "assumed"}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, einsum):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(w_head, g, x, rows):
        y = rms_norm(x[rows], g.astype(F32), eps)
        return einsum("rd,dv->rv", y, w_head.astype(F32))

    layers = {
        kind: jax.jit(functools.partial(
            block, kind=kind, cfg=cfg, einsum=einsum))
        for kind in ("dense", "moe")
    }
    return jax.jit(embed), layers, jax.jit(head)


def _through_layers(cfg, weights, tokens, einsum, keep_latents=()):
    """``tokens`` through every layer: the last hidden state ``[T, d]``, the
    latents of the layers in ``keep_latents``, every expert layer's routing,
    and the head's program."""
    embed, layers, head = _programs(_cfg_key(cfg), einsum)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    n = len(weights["layers"])
    keep = {i % n for i in keep_latents}
    latents, routing = {}, []
    for i, (kind, w) in enumerate(zip(layer_kinds(cfg), weights["layers"])):
        x, latent, routed = layers[kind](x, w)
        if i in keep:
            latents[i] = latent
        if routed is not None:
            routing.append(routed)
    return x, [latents[i % n] for i in keep_latents], routing, head


def routing_at(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum):
    """The experts this reference routes each of ``tokens`` to, expert layer
    by expert layer, on its own activations: bool ``[expert layers, T,
    n_router]``, ``top_k`` true a token and layer, held or not."""
    with jax.default_matmul_precision("highest"):
        _, _, routing, _ = _through_layers(cfg, weights, list(tokens), einsum)
    return jnp.stack(routing)


def probe_at(cfg: dict, weights: dict, tokens, layers=(0, -1), *,
             einsum=jnp.einsum):
    """What a cache would hold of every position of ``tokens`` in the given
    layers (``[c | k_pe]``, float32 ``[len(layers), T, r + dr]``), and
    ``routing_at``'s ``[expert layers, T, n_router]``, from ONE pass."""
    with jax.default_matmul_precision("highest"):
        _, latents, routing, _ = _through_layers(
            cfg, weights, list(tokens), einsum, keep_latents=tuple(layers))
    return jnp.stack(latents), jnp.stack(routing)


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


# ------------------------------------------------------------------ the counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against: in a layer's attention (the
    up-projection ``W_kvb`` counted once a NEW token, as the absorbed form
    applies it; the expanded form applies it once a cached token), in the
    dense feed-forward, in the shared experts and the router of an expert
    layer, in ONE routed expert, and in the output head."""
    s = dims(cfg)
    d, h = s["d"], s["h"]
    return {
        "attention": (d * h * (s["dn"] + s["dr"]) + d * (s["r"] + s["dr"])
                      + s["r"] * h * (s["dn"] + s["dv"]) + h * s["dv"] * d),
        "dense": 3 * d * s["f0"], "shared": 3 * d * s["fs"],
        "router": d * s["n_router"], "expert": 3 * d * s["f"],
        "head": d * s["v"],
    }


def held_parameters(cfg: dict) -> int:
    """Every parameter this share holds: the matrices, the norms' scales,
    the embedding and the untied head."""
    s = dims(cfg)
    p = matmul_params(cfg)
    norms = 2 * s["d"] + s["r"]
    moe = p["attention"] + p["shared"] + p["router"] + s["held"] * p["expert"]
    return (s["dense"] * (p["attention"] + p["dense"] + norms)
            + (s["layers"] - s["dense"]) * (moe + norms)
            + 2 * p["head"] + s["d"])


def latent_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """What the architecture caches a token, over all layers: ``r + dr``
    numbers a layer (1,152 B in bf16 at the published sizes), whatever a
    program pads it to."""
    s = dims(cfg)
    return s["layers"] * (s["r"] + s["dr"]) * bytes_per_value


def pool_tokens(cfg: dict) -> int:
    """Tokens the configuration's page pool can hold (the null page not
    counted)."""
    engine = cfg["assumed"]["engine"]
    return (engine["num_pages"] - 1) * engine["page_size"]


def latent_decode_flops(cfg: dict, visible_tokens: float) -> float:
    """FLOPs of ONE layer's absorbed decode attention over ``visible_tokens``
    (query, key) pairs summed over the rows: every head's ``r + dr`` wide
    score and ``r`` wide weighted sum."""
    s = dims(cfg)
    return 2.0 * s["h"] * (2 * s["r"] + s["dr"]) * visible_tokens


def latent_decode_min_bytes(cfg: dict, distinct_tokens: float,
                            bytes_per_value: int = 2) -> float:
    """Least bytes ONE layer's decode attention has to read: the latent of
    every DISTINCT cached token among the rows' pages, once (rows that share
    a document could be served by one read of it)."""
    s = dims(cfg)
    return float(distinct_tokens) * (s["r"] + s["dr"]) * bytes_per_value


def experts_reached(cfg: dict, tokens: float) -> float:
    """Held experts of a layer that ``tokens`` tokens reach, at their
    expectation under even routing."""
    s = dims(cfg)
    return s["held"] * (1.0 - (1.0 - s["top_k"] / s["n_router"]) ** tokens)


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through this share of the
    model when their queries attend to ``context_tokens`` keys in all and
    ``logits_rows`` go through the head. Attention a (query, key) pair is
    counted in the cheaper of the two forms, the expanded one's ``2 H (dn +
    dr + dv)`` without its expansion: a floor under both. The routed pairs on
    held experts are taken at their expectation under even routing."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n_moe = s["layers"] - s["dense"]
    pairs = s["top_k"] * s["held"] / s["n_router"]
    dense = 2.0 * new_tokens * (
        s["layers"] * p["attention"] + s["dense"] * p["dense"]
        + n_moe * (p["shared"] + p["router"] + pairs * p["expert"]))
    attn = 2.0 * s["layers"] * s["h"] * (s["dn"] + s["dr"] + s["dv"]) * context_tokens
    return dense + attn + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move, however many programs the
    engine makes of it: every weight a token of the step reaches, once (the
    held experts at ``experts_reached``; the embedding's rows, not its
    table); the latent of the cached tokens read, and one written a new
    token. ``kv_tokens_read`` is the SUM of the rows' contexts; rows that
    share a document could be served by one read of it, so no more cached
    tokens are counted than the pool can hold (``pool_tokens``): the floor
    stays one whatever a later kernel shares. ``prefill_chunks`` is not used:
    nothing here is read once a chunk."""
    s = dims(cfg)
    p = matmul_params(cfg)
    new = decode_rows + prefill_tokens
    n_moe = s["layers"] - s["dense"]
    weights = (
        s["layers"] * p["attention"] + s["dense"] * p["dense"]
        + n_moe * (p["shared"] + p["router"]
                   + experts_reached(cfg, new) * p["expert"])
        + p["head"] + new * s["d"])
    cached = min(float(kv_tokens_read), float(pool_tokens(cfg))) + new
    return bytes_per_param * weights + latent_bytes_per_token(cfg) * cached
