"""Plain reference for the language model of ``dots3_note``
(``dots-studio/dots3-note-prev``): latent attention in every layer, of two
kinds laid out by ``layer_types``, a dense gated-SiLU feed-forward in the first
``first_k_dense_replace`` layers and sigmoid-routed experts plus a shared one
in the rest. The vision and audio towers and the MTP module are not part of
the catalog's ``config`` and are not here.

``x`` is a block's RMSNorm'd input (epsilon ``rms_norm_eps``), blocks are
pre-norm residual blocks, no biases but the index key's LayerNorm's::

    full layer (H heads, dn, dr, dv, rank r, query rank rq, rotary base theta)
      c_q = rmsnorm(x W_qa) a_q           [q_n | q_r]_h = c_q W_qb[:, h]
      [c_raw | kr_raw] = x W_kva          c = rmsnorm(c_raw) a_kv
      k_r = R(kr_raw, pos)   q_r = R(q_r, pos)      [k_n | v]_h = c W_kvb[:, h]
      indexer (H_I heads of d_I):
        q^I_h = c_q W_Iq[:, h]   k^I = layernorm(x W_Ik)   w = x W_Iw
        R on the first dr of q^I_h and of k^I
        I(t, s) = sum_h w_{t,h} H_I ** -0.5 relu(q^I_{t,h} . k^I_s) d_I ** -0.5
        S_t = the index_topk positions s <= t of largest I(t, s)  (all, t < index_topk)
      p_h(t, .) = softmax over s in S_t of (q_n . k_n + q_r . k_r) (dn + dr) ** -0.5
      g = sigmoid(x W_g)  (H numbers)      y = concat_h(g_h sum_s p_h v_h) W_o
    sliding layer (the swa_* sizes): the same without the indexer,
      S_t = the sliding_window_size positions (t - window, t], its own among them

``a_q = sqrt(d / rq)`` and ``a_kv = sqrt(d / r)`` under
``apply_mla_qkv_lora_rescale`` (fixed scalars after the two norms), 1 without.
What a serving system caches a token is ``[c | k_r]`` in every layer and
``k^I`` in a full layer; ``probe_at`` returns both.

Feed-forward of a layer ``i >= first_k_dense_replace``: ``s = sigmoid(x W_r)``
over ALL ``n_router`` experts; the ``num_experts_per_tok`` experts of largest
``s_e + b_e`` (``b`` the correction bias, used for the choice alone;
``topk_method`` ``noaux_tc`` with one group); ``g_e = s_e / sum of the chosen
s`` (``norm_topk_prob``) times ``routed_scaling_factor``; ``sum_e g_e E_e(x) +
S(x)``, gated SiLUs of ``moe_intermediate_size`` (``S``: ``n_shared_experts``
times that).

**The chip's share** is ``reference/deepseek_v2.py``'s: ``experts_held = [lo,
hi]`` of ``n_routed_experts_published``; pairs routed to absent experts are
left out and the partial sum goes on, here as in the program.

Readings the configuration does not settle are the configuration file's
``assumed`` (a)-(f); rotary dimensions are paired by halves.

**In blocks, so that 49,664 tokens fit.** Nothing below changes a value:
per-token work runs ``QUERY_BLOCK`` tokens at a time; a full layer's selection
is worked out ``INDEX_BLOCK`` queries at a time and kept as packed bits;
attention runs ``HEAD_GROUP`` heads at a time, ``QUERY_BLOCK`` queries at a
time, against the keys up to the end of the query's SEGMENT (``SEGMENTS`` of
them, ``INDEX_SEGMENTS`` for the selection: the causal half is not computed
past it) or, in a sliding layer, against
the ``QUERY_BLOCK + window`` keys that can meet the block's windows; an expert
runs on the tokens routed to it, ``EXPERT_ROWS`` at a time (a token not routed
to it has gate 0). The LAST layer is computed only at the query blocks that
hold the asked rows. Every projection, the router, the experts, the indexer's
and the attention's products go through the ``einsum`` handed in
(``control_*.py`` hands the int8 one); norms, softmaxes, sigmoids and the
selection do not. float32 at ``highest`` matmul precision; it imports nothing
of the program under test.

Weight layout: ``embed [V, d]``, ``head [d, V]``, ``lnf_g [d]``; every layer
``ln1_g ln2_g [d]``, ``wqa [d, rq]``, ``qn_g [rq]``, ``wqb [rq, H, dn + dr]``,
``wkva [d, r + dr]``, ``kvn_g [r]``, ``wkvb [r, H, dn + dv]``, ``wg [d, H]``,
``wo [H, dv, d]``; a full layer also ``wiq [rq, H_I, d_I]``, ``wik [d, d_I]``,
``ikn_g ikn_b [d_I]``, ``wiw [d, H_I]``; the feed-forwards as
``reference/deepseek_v2.py`` plus ``router_b [n_router]`` (float32).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512  # tokens a block of per-token work, queries a block of attention
INDEX_BLOCK = 64  # queries a block of the selection
HEAD_GROUP = 8  # heads a pass of the attention
SEGMENTS = 4  # key ranges a full layer's attention cuts its queries into
INDEX_SEGMENTS = 2  # and its selection (every segment is a program to compile)
EXPERT_ROWS = 2048  # routed tokens an expert runs at a time


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def attention_dims(cfg: dict, kind: str) -> dict:
    """A layer's attention sizes, ``kind`` ``"full"`` or ``"sliding"``."""
    p = "" if kind == "full" else "swa_"
    d = cfg["hidden_size"]
    out = dict(
        h=cfg[p + "num_attention_heads"], dn=cfg[p + "qk_nope_head_dim"],
        dr=cfg[p + "qk_rope_head_dim"], dv=cfg[p + "v_head_dim"],
        r=cfg[p + "kv_lora_rank"], rq=cfg[p + "q_lora_rank"],
        theta=float(cfg[p + "rope_theta"]), d=d,
        window=0 if kind == "full" else cfg["sliding_window_size"],
        hi=cfg["index_n_heads"] if kind == "full" else 0,
        di=cfg["index_head_dim"] if kind == "full" else 0,
        topk=cfg["index_topk"] if kind == "full" else 0,
    )
    rescale = cfg.get("apply_mla_qkv_lora_rescale")
    out["a_q"] = math.sqrt(d / out["rq"]) if rescale else 1.0
    out["a_kv"] = math.sqrt(d / out["r"]) if rescale else 1.0
    return out


def dims(cfg: dict) -> dict:
    n_router = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    lo, hi = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    if hi - lo != cfg["n_routed_experts"] or not 0 <= lo < hi <= n_router:
        raise ValueError(
            f"experts_held {lo}..{hi} is not {cfg['n_routed_experts']} of "
            f"{n_router} experts")
    if cfg.get("moe_layer_freq", 1) != 1 or cfg.get("n_group", 1) != 1:
        raise ValueError("only moe_layer_freq 1 and one expert group are written")
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("only renormalised sigmoid scores are written")
    if cfg["attention_gate_type"] != "headwise" or (
            cfg["swa_attention_gate_type"] != "headwise"):
        raise ValueError("only head-wise gates are written")
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return dict(
        d=cfg["hidden_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"], dense=cfg["first_k_dense_replace"],
        f0=cfg["intermediate_size"], f=cfg["moe_intermediate_size"],
        fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        n_router=n_router, lo=lo, hi=hi, held=hi - lo,
        top_k=cfg["num_experts_per_tok"],
        full=attention_dims(cfg, "full"), sliding=attention_dims(cfg, "sliding"),
    )


def layer_kinds(cfg: dict) -> list:
    """``(attention, feed-forward)`` for each layer: ``"full"`` or
    ``"sliding"``, ``"dense"`` or ``"moe"``."""
    s = dims(cfg)
    names = {"full_attention": "full", "sliding_attention": "sliding"}
    return [(names[t], "dense" if i < s["dense"] else "moe")
            for i, t in enumerate(cfg["layer_types"])]


def layer_shapes(cfg: dict, kind: tuple) -> dict:
    s = dims(cfg)
    a, d = s[kind[0]], s["d"]
    shapes = {
        "ln1_g": (d,), "ln2_g": (d,), "wqa": (d, a["rq"]), "qn_g": (a["rq"],),
        "wqb": (a["rq"], a["h"], a["dn"] + a["dr"]),
        "wkva": (d, a["r"] + a["dr"]), "kvn_g": (a["r"],),
        "wkvb": (a["r"], a["h"], a["dn"] + a["dv"]), "wg": (d, a["h"]),
        "wo": (a["h"], a["dv"], d)}
    if kind[0] == "full":
        shapes.update(
            wiq=(a["rq"], a["hi"], a["di"]), wik=(d, a["di"]),
            ikn_g=(a["di"],), ikn_b=(a["di"],), wiw=(d, a["hi"]))
    if kind[1] == "dense":
        shapes.update(w_gate=(d, s["f0"]), w_up=(d, s["f0"]), w_down=(s["f0"], d))
    else:
        shapes.update(
            router=(d, s["n_router"]), router_b=(s["n_router"],),
            we_in=(s["held"], d, 2 * s["f"]), we_out=(s["held"], s["f"], d),
            ws_gate=(d, s["fs"]), ws_up=(d, s["fs"]), ws_down=(s["fs"], d))
    return shapes


#: Of the seeded correction bias. Sigmoid scores lie in 0..1 and a bias an
#: expert is a standing preference: at 0.1 the tokens that reach THIS chip's 32
#: experts were 0.6 to 1.8 times their share from seed to seed (and the cell's
#: ``tpot_ms_p50`` spread 2.4%: PERF.md section 6); at 0.02, 0.9 to 1.2 times.
ROUTER_BIAS_STD = 0.02


def _draw(key, shapes: dict, std: float, dtype) -> dict:
    """Norm scales 1 + 0.02 noise, the LayerNorm's bias 0.02 noise, the
    router's correction bias ``ROUTER_BIAS_STD`` noise in float32; everything
    else normal at ``std``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        noise = jax.random.normal(k, shape, F32)
        if name == "router_b":
            out[name] = ROUTER_BIAS_STD * noise
        elif name.endswith("_g"):
            out[name] = (1.0 + 0.02 * noise).astype(dtype)
        elif name.endswith("_b"):
            out[name] = (0.02 * noise).astype(dtype)
        else:
            out[name] = (std * noise).astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the default device: one compiled program per kind
    of layer, called once a layer."""
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg.get("initializer_range", 0.02))
    s = dims(cfg)
    kinds = layer_kinds(cfg)
    draw = {
        kind: jax.jit(functools.partial(
            _draw, shapes=layer_shapes(cfg, kind), std=std, dtype=dtype))
        for kind in set(kinds)
    }
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "head": (s["d"], s["v"]),
                       "lnf_g": (s["d"],)}, std=std, dtype=dtype))
    key = seed_key(seed)
    weights = ends(jax.random.fold_in(key, 0))
    weights["layers"] = [
        draw[kind](jax.random.fold_in(key, 1 + i))
        for i, kind in enumerate(kinds)
    ]
    return weights


# ------------------------------------------------------------------ the model


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


def silu(x):
    return x * jax.nn.sigmoid(x)


def rope(x, pos, theta: float):
    """``R`` over the last axis of ``x [T, ..., D]`` at positions ``pos [T]``,
    paired by halves, base ``theta``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = pos.astype(F32)[:, None] * freqs
    angles = angles.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def rope_first(x, pos, theta: float, width: int):
    """``R`` on the first ``width`` numbers of the last axis, the rest as is."""
    return jnp.concatenate(
        [rope(x[..., :width], pos, theta), x[..., width:]], axis=-1)


def in_blocks(fn, arrays, block: int):
    """``fn`` over ``block`` rows at a time of ``arrays`` (tuples of ``[T,
    ...]``; ``T`` a multiple of ``block``) with the block's first row's
    index; the results' blocks joined again."""
    t = arrays[0].shape[0]
    cut = lambda z: z.reshape((t // block, block) + z.shape[1:])  # noqa: E731
    out = jax.lax.map(
        lambda xs: fn(xs[0], *xs[1]),
        (jnp.arange(0, t, block), tuple(cut(z) for z in arrays)))
    join = lambda z: z.reshape((t,) + z.shape[2:])  # noqa: E731
    return jax.tree_util.tree_map(join, out)


def per_token(y, w, a: dict, eps: float, einsum):
    """What attention needs of every position of ``y [T, d]`` (the block's
    normed input): ``c_q``, ``[c | k_r]``, the gate and, in a full layer,
    ``k^I`` and ``w``."""

    def one(start, yb):
        pos = start + jnp.arange(yb.shape[0])
        c_q = rms_norm(einsum("td,dr->tr", yb, w["wqa"]), w["qn_g"], eps) * a["a_q"]
        lat = einsum("td,de->te", yb, w["wkva"])
        c = rms_norm(lat[:, :a["r"]], w["kvn_g"], eps) * a["a_kv"]
        k_r = rope(lat[:, a["r"]:], pos, a["theta"])
        out = {"c_q": c_q, "latent": jnp.concatenate([c, k_r], axis=-1),
               "gate": jax.nn.sigmoid(einsum("td,dh->th", yb, w["wg"]))}
        if a["topk"]:
            out["index_in"] = c_q  # the indexer's queries read the query's latent
            k_i = layer_norm(
                einsum("td,di->ti", yb, w["wik"]), w["ikn_g"], w["ikn_b"], eps)
            out["k_i"] = rope_first(k_i, pos, a["theta"], a["dr"])
            out["w_i"] = einsum("td,dh->th", yb, w["wiw"])
        return out

    return in_blocks(one, (y,), min(QUERY_BLOCK, y.shape[0]))


def segments(n_blocks: int, parts: int) -> list:
    """``(first block, blocks)`` of the at most ``parts`` runs of query blocks
    a full layer's attention, or its selection, is cut into."""
    edges = sorted({-(-i * n_blocks // parts) for i in range(parts + 1)})
    return [(lo, hi - lo) for lo, hi in zip(edges, edges[1:])]


def selection_bits(tok: dict, w, a: dict, q0, q_len: int, einsum):
    """The indexer's choice for the ``q_len`` queries from position ``q0`` on
    (``q0`` traced; whole ``INDEX_BLOCK`` s), over all ``T`` keys: packed bits
    ``[q_len, T / 8]``, bit ``s`` of row ``t`` set iff ``s`` is in ``S_t``."""
    t = tok["k_i"].shape[0]
    ib = min(INDEX_BLOCK, q_len)
    k = min(a["topk"], t)

    def over(keys_end: int, offset: int = 0):
        k_i = tok["k_i"][:keys_end]
        kk = min(k, keys_end)

        def one(start, c_q, w_i):
            pos = q0 + offset + start + jnp.arange(ib)
            q_i = rope_first(
                einsum("tr,rhd->thd", c_q, w["wiq"]), pos, a["theta"], a["dr"])
            s = jax.nn.relu(einsum("qhd,kd->qhk", q_i, k_i)) * a["di"] ** -0.5
            score = jnp.einsum("qhk,qh->qk", s, w_i * a["hi"] ** -0.5)
            score = jnp.where(
                jnp.arange(keys_end)[None, :] <= pos[:, None], score, -jnp.inf)
            best, where = jax.lax.top_k(score, kk)
            rows = jnp.arange(ib)[:, None]
            chosen = jnp.zeros((ib, t), bool).at[rows, where].set(
                best > -jnp.inf)
            return jnp.packbits(chosen, axis=-1)

        return one

    c_q = jax.lax.dynamic_slice_in_dim(tok["index_in"], q0, q_len)
    w_i = jax.lax.dynamic_slice_in_dim(tok["w_i"], q0, q_len)
    if q_len != t:  # the last layer's few blocks: against every key
        return in_blocks(over(t), (c_q, w_i), ib)
    parts = []
    for lo, n in segments(t // ib, INDEX_SEGMENTS):
        rows = slice(lo * ib, (lo + n) * ib)
        part = in_blocks(
            over((lo + n) * ib, lo * ib), (c_q[rows], w_i[rows]), ib)
        parts.append(part)
    return jnp.concatenate(parts)


def attention(y, w, *, a: dict, eps: float, einsum, q0=0, q_len=None):
    """A layer's attention for the ``q_len`` queries from position ``q0`` on
    (``None``: all) over ``y [T, d]``, the block's normed input: ``[q_len,
    d]``, what a cache would hold of every position, and the selection's
    packed bits (``None`` in a sliding layer)."""
    t = y.shape[0]
    q_len = t if q_len is None else q_len
    whole = q_len == t
    tok = per_token(y, w, a, eps, einsum)
    dn, dr, r, window = a["dn"], a["dr"], a["r"], a["window"]
    c, k_r = tok["latent"][:, :r], tok["latent"][:, r:]
    bits = selection_bits(tok, w, a, q0, q_len, einsum) if a["topk"] else None
    qb = min(QUERY_BLOCK, q_len)
    scale = (dn + dr) ** -0.5
    take = lambda z: jax.lax.dynamic_slice_in_dim(z, q0, q_len)  # noqa: E731
    c_q, gate = take(tok["c_q"]), take(tok["gate"])
    span = qb + (-(-window // qb)) * qb  # keys a sliding block can meet

    def heads(total, ws):
        wqb, wkvb, wo, cols = ws  # HEAD_GROUP heads' share; cols: their gates
        kv = einsum("tr,rhn->thn", c, wkvb)
        k_n, v = kv[..., :dn], kv[..., dn:]

        def over(keys_end: int, offset: int = 0):
            def one(start, c_qb, gb, *more):
                start = start + offset
                pos = q0 + start + jnp.arange(qb)
                q = einsum("tr,rhk->thk", c_qb, wqb)
                q_n, q_r = q[..., :dn], rope(q[..., dn:], pos, a["theta"])
                if window:
                    first = jnp.clip(q0 + start + qb - span, 0, t - min(span, t))
                    size = min(span, t)
                    cut = lambda z: jax.lax.dynamic_slice_in_dim(  # noqa: E731
                        z, first, size)
                    kn, vv, kr = cut(k_n), cut(v), cut(k_r)
                    keys = first + jnp.arange(size)
                else:
                    kn, vv, kr = k_n[:keys_end], v[:keys_end], k_r[:keys_end]
                    keys = jnp.arange(keys_end)
                scores = (einsum("qhn,khn->hqk", q_n, kn)
                          + einsum("qhd,kd->hqk", q_r, kr)) * scale
                seen = keys[None, :] <= pos[:, None]
                if window:
                    seen &= keys[None, :] > pos[:, None] - window
                if more:
                    seen &= jnp.unpackbits(
                        more[0], axis=-1)[:, :keys_end].astype(bool)
                scores = jnp.where(seen[None], scores, -jnp.inf)
                out = einsum("hqk,khv->qhv", jax.nn.softmax(scores, axis=-1), vv)
                return einsum("qhv,hvd->qd", out * gb[:, cols, None], wo)

            return one

        more = () if bits is None else (bits,)
        if window or not whole:
            out = in_blocks(over(t), (c_q, gate) + more, qb)
        else:
            out = jnp.concatenate([
                in_blocks(
                    over((lo + n) * qb, lo * qb),
                    tuple(z[lo * qb:(lo + n) * qb] for z in (c_q, gate) + more),
                    qb)
                for lo, n in segments(t // qb, SEGMENTS)])
        return total + out, None

    g = math.gcd(HEAD_GROUP, a["h"])
    groups = lambda z, axis: jnp.moveaxis(  # noqa: E731
        z.reshape(z.shape[:axis] + (a["h"] // g, g) + z.shape[axis + 1:]),
        axis, 0)
    total, _ = jax.lax.scan(
        heads, jnp.zeros((q_len, a["d"]), F32),
        (groups(w["wqb"], 1), groups(w["wkvb"], 1), groups(w["wo"], 0),
         jnp.arange(a["h"]).reshape(-1, g)))
    kept = {"latent": tok["latent"]}
    if a["topk"]:
        kept["k_i"] = tok["k_i"]
    return total, kept, bits


def route(n, w, *, cfg: dict, einsum):
    """``[T, n_router]`` gates (zero at the experts a token was not routed
    to) and bool ``[T, n_router]``: the experts it was routed to."""
    s = dims(cfg)
    score = jax.nn.sigmoid(einsum("td,de->te", n, w["router"]))
    _, experts = jax.lax.top_k(score + w["router_b"].astype(F32), s["top_k"])
    rows = jnp.arange(n.shape[0])[:, None]
    routed = jnp.zeros(score.shape, bool).at[rows, experts].set(True)
    chosen = jnp.where(routed, score, 0.0)
    gates = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return gates * cfg.get("routed_scaling_factor", 1.0), routed


def routed_experts(n, w, *, cfg: dict, einsum):
    """The held experts' part of the routed layer over ``n [T, d]``, an expert
    at a time on the tokens routed to it, ``EXPERT_ROWS`` at a time (tokens not
    routed to it come last and carry gate 0): ``[T, d]`` and the routing."""
    s = dims(cfg)
    gates, routed = route(n, w, cfg=cfg, einsum=einsum)
    t = n.shape[0]
    rows_at = min(EXPERT_ROWS, t)

    def one(total, xs):
        w_in, w_out, gate = xs  # gate [T]: 0 where not routed here
        order = jnp.argsort(gate == 0, stable=True)  # routed tokens first
        count = jnp.sum(gate != 0)

        def chunk(i, total):
            rows = jax.lax.dynamic_slice_in_dim(
                jnp.pad(order, (0, rows_at)), i * rows_at, rows_at)
            rows = jnp.minimum(rows, t - 1)
            live = (i * rows_at + jnp.arange(rows_at)) < count
            g, u = jnp.split(
                einsum("td,df->tf", n[rows], w_in.astype(F32)), 2, axis=-1)
            out = einsum("tf,fd->td", silu(g) * u, w_out.astype(F32))
            weight = jnp.where(live, gate[rows], 0.0)
            return total.at[rows].add(weight[:, None] * out)

        return jax.lax.fori_loop(0, -(-count // rows_at), chunk, total), None

    total, _ = jax.lax.scan(
        one, jnp.zeros_like(n),
        (w["we_in"], w["we_out"], gates[:, s["lo"]:s["hi"]].T))
    return total, routed


def gated_mlp(n, w_gate, w_up, w_down, einsum):
    def one(_, nb):
        gated = silu(einsum("td,df->tf", nb, w_gate)) * einsum(
            "td,df->tf", nb, w_up)
        return einsum("tf,fd->td", gated, w_down)

    return in_blocks(one, (n,), min(QUERY_BLOCK, n.shape[0]))


_KEPT_AS_STORED = ("we_in", "we_out")


def block(x, w, q0, *, kind: tuple, cfg: dict, einsum=jnp.einsum, q_len=None):
    """One layer over ``x [T, d]`` (float32). With ``q_len`` (the last layer)
    only the ``q_len`` positions from ``q0`` on are computed and returned.
    Its output, what a cache would hold of every position, the experts each
    computed token was routed to (``None`` from a dense layer) and the
    selection's packed bits (``None`` from a sliding layer)."""
    w = {k: v if k in _KEPT_AS_STORED else jnp.asarray(v).astype(F32)
         for k, v in w.items()}
    eps = cfg["rms_norm_eps"]
    a = dims(cfg)[kind[0]]
    y = in_blocks(lambda _, xb: rms_norm(xb, w["ln1_g"], eps), (x,),
                  min(QUERY_BLOCK, x.shape[0]))
    mixed, kept, bits = attention(
        y, w, a=a, eps=eps, einsum=einsum, q0=q0, q_len=q_len)
    if q_len is not None:
        x = jax.lax.dynamic_slice_in_dim(x, q0, q_len)
    x = x + mixed
    n = rms_norm(x, w["ln2_g"], eps)
    if kind[1] == "dense":
        fed, routed = gated_mlp(n, w["w_gate"], w["w_up"], w["w_down"], einsum), None
    else:
        fed, routed = routed_experts(n, w, cfg=cfg, einsum=einsum)
        fed = fed + gated_mlp(n, w["ws_gate"], w["ws_up"], w["ws_down"], einsum)
    return x + fed, kept, routed, bits


def _cfg_key(cfg: dict) -> str:
    return json.dumps(
        {k: v for k, v in cfg.items() if k != "assumed"}, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str, einsum, tail: int):
    cfg = json.loads(cfg_key)
    eps = cfg["rms_norm_eps"]

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(w_head, g, x, rows):
        y = rms_norm(x[rows], g.astype(F32), eps)
        return einsum("rd,dv->rv", y, w_head.astype(F32))

    layers = {
        (kind, last): jax.jit(functools.partial(
            block, kind=kind, cfg=cfg, einsum=einsum,
            q_len=tail if last else None))
        for kind in set(layer_kinds(cfg)) for last in (False, True)
    }
    return jax.jit(embed), layers, jax.jit(head)


def _tail(t: int, n_rows: int) -> int:
    """Positions of the last layer that are computed: whole query blocks that
    hold ``n_rows`` consecutive rows wherever they start, or all ``t``."""
    qb = min(QUERY_BLOCK, t)
    return min(t, (-(-n_rows // qb) + 1) * qb)


def _through_layers(cfg, weights, tokens, rows, einsum):
    """``tokens`` (whole query blocks) through every layer, the last one at
    the blocks that hold ``rows`` (consecutive positions): the last hidden
    state at ``rows``, every layer's cache rows, the routing at ``rows`` and
    the full layers' selections at ``rows``, and the head's program."""
    t = len(tokens)
    if t % min(QUERY_BLOCK, t) or t % 8:
        raise ValueError(f"{t} tokens are no whole blocks of {QUERY_BLOCK}")
    tail = _tail(t, len(rows))
    embed, layers, head = _programs(_cfg_key(cfg), einsum, tail)
    x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    rows = jnp.asarray(rows, jnp.int32)
    qb = min(QUERY_BLOCK, t)
    q0 = jnp.minimum(rows[0] // qb * qb, t - tail)
    kinds = layer_kinds(cfg)
    caches, routing, chosen = [], [], []
    for i, (kind, w) in enumerate(zip(kinds, weights["layers"])):
        last = i == len(kinds) - 1
        x, kept, routed, bits = layers[(kind, last)](
            x, w, q0 if last else jnp.int32(0))
        at = rows - q0 if last else rows
        caches.append(kept)
        if routed is not None:
            routing.append(routed[at])
        if bits is not None:
            chosen.append(jnp.unpackbits(bits[at], axis=-1)[:, :t].astype(bool))
    return x[rows - q0], caches, routing, chosen, head


def _padded(tokens, rows, pad_tokens_to: int, pad_rows_to: int):
    """``tokens`` padded at their end to whole blocks (of ``QUERY_BLOCK``
    past that many, else of ``INDEX_BLOCK`` or 8) and ``rows`` (consecutive
    positions) widened to ``pad_rows_to`` consecutive positions that hold
    them: ``(tokens, rows, where the asked rows start among them, how many
    were asked)``."""
    tokens, rows = list(tokens), list(rows)
    if not rows or rows != list(range(rows[0], rows[0] + len(rows))):
        raise ValueError("the rows to score are consecutive positions")
    n = len(rows)
    width = max(pad_tokens_to, len(tokens))
    unit = next(u for u in (QUERY_BLOCK, INDEX_BLOCK, 8) if width > u or u == 8)
    width = -(-width // unit) * unit
    tokens += [0] * (width - len(tokens))
    count = min(max(pad_rows_to, n), width)
    start = max(0, min(rows[0], width - count))
    return tokens, list(range(start, start + count)), rows[0] - start, n


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0):
    """Float32 logits ``[len(rows), V]`` at the consecutive positions ``rows``
    of ONE token sequence (row ``p`` predicts token ``p + 1``). ``pad_*_to``
    pad the sequence (at its end: nothing is carried backwards) and the rows,
    so that one compiled program serves requests of every length."""
    tokens, rows, skip, n = _padded(tokens, rows, pad_tokens_to, pad_rows_to)
    with jax.default_matmul_precision("highest"):
        x, _, _, _, head = _through_layers(cfg, weights, tokens, rows, einsum)
        return head(weights["head"], weights["lnf_g"], x,
                    jnp.arange(len(rows)))[skip:skip + n]


def probe_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
             pad_tokens_to: int = 0, pad_rows_to: int = 0) -> dict:
    """From ONE pass over ``tokens``: what a cache would hold of every
    position, layer by layer (``latents``: a list of ``[T, r + dr]``;
    ``index_keys``: the full layers' ``[T, d_I]``), and at the consecutive
    positions ``rows`` the routing (bool ``[expert layers, rows, n_router]``)
    and the full layers' selections (bool ``[full layers, rows, T]``: ``S_t``
    by this reference's own scores)."""
    n_tokens = len(tokens)
    tokens, rows, skip, n = _padded(tokens, rows, pad_tokens_to, pad_rows_to)
    with jax.default_matmul_precision("highest"):
        _, caches, routing, chosen, _ = _through_layers(
            cfg, weights, tokens, rows, einsum)
    return {
        "latents": [c["latent"][:n_tokens] for c in caches],
        "index_keys": [c["k_i"][:n_tokens] for c in caches if "k_i" in c],
        "routing": jnp.stack(routing)[:, skip:skip + n],
        "selected": jnp.stack(chosen)[:, skip:skip + n, :n_tokens],
    }


def routing_at(cfg: dict, weights: dict, tokens, *, einsum=jnp.einsum):
    """The experts this reference routes each of ``tokens`` to, expert layer
    by expert layer, on its own activations: bool ``[expert layers, T,
    n_router]``."""
    tokens = list(tokens)
    return probe_at(cfg, weights, tokens, list(range(len(tokens))),
                    einsum=einsum)["routing"]


# ------------------------------------------------------------------ the counts


def matmul_params(cfg: dict) -> dict:
    """Parameters a token multiplies against: in a full and in a sliding
    layer's attention (``W_kvb`` once a NEW token, as the absorbed form
    applies it; a full layer's with its indexer's three matrices), in the
    dense feed-forward, in the shared expert and the router of an expert
    layer, in ONE routed expert, and in the output head."""
    s = dims(cfg)
    d = s["d"]

    def attention_of(a):
        n = (d * a["rq"] + a["rq"] * a["h"] * (a["dn"] + a["dr"])
             + d * (a["r"] + a["dr"]) + a["r"] * a["h"] * (a["dn"] + a["dv"])
             + d * a["h"] + a["h"] * a["dv"] * d)
        if a["topk"]:
            n += a["rq"] * a["hi"] * a["di"] + d * a["di"] + d * a["hi"]
        return n

    return {
        "full": attention_of(s["full"]), "sliding": attention_of(s["sliding"]),
        "dense": 3 * d * s["f0"], "shared": 3 * d * s["fs"],
        "router": d * s["n_router"], "expert": 3 * d * s["f"],
        "head": d * s["v"],
    }


def layer_counts(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {
        "full": sum(k[0] == "full" for k in kinds),
        "sliding": sum(k[0] == "sliding" for k in kinds),
        "dense": sum(k[1] == "dense" for k in kinds),
        "moe": sum(k[1] == "moe" for k in kinds),
    }


def held_parameters(cfg: dict) -> int:
    """Every parameter this share holds: the matrices, the norms' scales and
    biases, the correction biases, the embedding and the untied head."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n = layer_counts(cfg)
    norms = {
        kind: 2 * s["d"] + s[kind]["rq"] + s[kind]["r"] + 2 * s[kind]["di"]
        for kind in ("full", "sliding")}
    return (n["full"] * (p["full"] + norms["full"])
            + n["sliding"] * (p["sliding"] + norms["sliding"])
            + n["dense"] * p["dense"]
            + n["moe"] * (p["shared"] + p["router"] + s["n_router"]
                          + s["held"] * p["expert"])
            + 2 * p["head"] + s["d"])


def cache_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> dict:
    """What the architecture caches a token, a layer of each kind: a full
    layer's latent ``r + dr`` and index key ``d_I``, a sliding layer's latent;
    and over all layers (``total``)."""
    s = dims(cfg)
    n = layer_counts(cfg)
    out = {
        "full_latent": (s["full"]["r"] + s["full"]["dr"]) * bytes_per_value,
        "index_key": s["full"]["di"] * bytes_per_value,
        "sliding_latent": (
            s["sliding"]["r"] + s["sliding"]["dr"]) * bytes_per_value,
    }
    out["total"] = (n["full"] * (out["full_latent"] + out["index_key"])
                    + n["sliding"] * out["sliding_latent"])
    return out


def pool_tokens(cfg: dict) -> int:
    """Tokens the configuration's page pool can hold (the null page not
    counted)."""
    engine = cfg["assumed"]["engine"]
    return (engine["num_pages"] - 1) * engine["page_size"]


def index_scores_flops(cfg: dict, visible_tokens: float) -> float:
    """FLOPs of ONE full layer's index scoring over ``visible_tokens``
    (query, key) pairs: ``H_I`` heads' ``d_I`` wide products."""
    a = dims(cfg)["full"]
    return 2.0 * a["hi"] * a["di"] * visible_tokens


def index_scores_min_bytes(cfg: dict, distinct_tokens: float,
                           bytes_per_value: int = 2) -> float:
    """Least bytes ONE full layer's index scoring has to read: the index key
    of every DISTINCT cached token among the rows' pages, once."""
    return float(distinct_tokens) * dims(cfg)["full"]["di"] * bytes_per_value


def sparse_decode_flops(cfg: dict, selected_tokens: float) -> float:
    """FLOPs of ONE full layer's absorbed attention over ``selected_tokens``
    (query, selected key) pairs: every head's ``r + dr`` wide score and ``r``
    wide weighted sum."""
    a = dims(cfg)["full"]
    return 2.0 * a["h"] * (2 * a["r"] + a["dr"]) * selected_tokens


def sparse_decode_min_bytes(cfg: dict, selected_tokens: float,
                            bytes_per_value: int = 2) -> float:
    """Least bytes ONE full layer's sparse attention has to read: the latent
    of every selected token (rows select apart: nothing is shared)."""
    a = dims(cfg)["full"]
    return float(selected_tokens) * (a["r"] + a["dr"]) * bytes_per_value


def window_decode_flops(cfg: dict, window_tokens: float) -> float:
    """FLOPs of ONE sliding layer's absorbed attention over ``window_tokens``
    (query, key) pairs."""
    a = dims(cfg)["sliding"]
    return 2.0 * a["h"] * (2 * a["r"] + a["dr"]) * window_tokens


def window_decode_min_bytes(cfg: dict, window_tokens: float,
                            bytes_per_value: int = 2) -> float:
    """Least bytes ONE sliding layer's decode attention has to read: the
    latent of every key inside a row's window."""
    a = dims(cfg)["sliding"]
    return float(window_tokens) * (a["r"] + a["dr"]) * bytes_per_value


def experts_reached(cfg: dict, tokens: float) -> float:
    """Held experts of a layer that ``tokens`` tokens reach, at their
    expectation under even routing."""
    s = dims(cfg)
    return s["held"] * (1.0 - (1.0 - s["top_k"] / s["n_router"]) ** tokens)


def _attended(cfg: dict, new_tokens: float, context_tokens: float) -> dict:
    """(query, key) pairs a step's ``new_tokens`` queries with
    ``context_tokens`` visible keys in all attend to, a layer of each kind, at
    most: a full layer scores every visible key and attends to ``index_topk``
    of them a query, a sliding layer to its window."""
    s = dims(cfg)
    return {
        "scored": context_tokens,
        "full": min(context_tokens, new_tokens * s["full"]["topk"]),
        "sliding": min(context_tokens, new_tokens * s["sliding"]["window"]),
    }


def serve_flops(cfg: dict, new_tokens: int, context_tokens: float,
                logits_rows: int) -> float:
    """FLOPs to push ``new_tokens`` positions through this share of the model
    when their queries see ``context_tokens`` keys in all and ``logits_rows``
    go through the head. A (query, key) pair of the attention is counted in
    the cheaper form (expanded, without its expansion) and only where the
    model attends (``_attended``): a floor. The routed pairs on held experts
    are taken at their expectation under even routing."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n = layer_counts(cfg)
    pairs = s["top_k"] * s["held"] / s["n_router"]
    dense = 2.0 * new_tokens * (
        n["full"] * p["full"] + n["sliding"] * p["sliding"]
        + n["dense"] * p["dense"]
        + n["moe"] * (p["shared"] + p["router"] + pairs * p["expert"]))
    seen = _attended(cfg, new_tokens, context_tokens)
    attn = sum(
        2.0 * n[kind] * s[kind]["h"] * (
            s[kind]["dn"] + s[kind]["dr"] + s[kind]["dv"]) * seen[kind]
        for kind in ("full", "sliding"))
    attn += n["full"] * index_scores_flops(cfg, seen["scored"])
    return dense + attn + 2.0 * p["head"] * logits_rows


def serve_min_bytes(cfg: dict, decode_rows: int, prefill_tokens: int,
                    kv_tokens_read: float, prefill_chunks: int,
                    bytes_per_param: int = 2) -> float:
    """Least bytes one engine STEP has to move: every weight a token of the
    step reaches, once (the held experts at ``experts_reached``; the
    embedding's rows, not its table); of the cache, what the model attends to
    (``_attended``: the index keys of the cached tokens scored, no more of
    them than the pool holds, the latents of the selected and of the windows'
    tokens) and one row written a new token and layer. ``prefill_chunks`` is
    not used."""
    s = dims(cfg)
    p = matmul_params(cfg)
    n = layer_counts(cfg)
    new = decode_rows + prefill_tokens
    weights = (
        n["full"] * p["full"] + n["sliding"] * p["sliding"]
        + n["dense"] * p["dense"]
        + n["moe"] * (p["shared"] + p["router"]
                      + experts_reached(cfg, new) * p["expert"])
        + p["head"] + new * s["d"])
    each = cache_bytes_per_token(cfg)
    seen = _attended(cfg, new, float(kv_tokens_read))
    cached = (
        n["full"] * (each["index_key"] * min(seen["scored"], pool_tokens(cfg))
                     + each["full_latent"] * seen["full"])
        + n["sliding"] * each["sliding_latent"] * seen["sliding"]
        + each["total"] * new)
    return bytes_per_param * weights + cached
