"""Plain reference for a decoder-only LM of the StarCoder2 family.

LayerNorm -> attention (biases, RoPE in the rotate-half layout, grouped KV
heads, causal sliding window) -> residual -> LayerNorm -> MLP (biases,
tanh-GELU) -> residual; final LayerNorm; logits from the tied embedding.
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: no cache, no
kernels, no batching. It imports nothing of the program under test.

It also makes the weights, from the seed, on the device, in the type they are
served in. The driver hands the same arrays to the program; the reference
upcasts one layer at a time, so it fits beside them.

Weight layout (this file's own): ``embed [V, d]``; per layer ``ln1_g ln1_b
[d]``, ``wq [d, H, D]``, ``bq [H, D]``, ``wk wv [d, Hkv, D]``, ``bk bv
[Hkv, D]``, ``wo [H, D, d]``, ``bo [d]``, ``ln2_g ln2_b [d]``, ``w_up
[d, F]``, ``b_up [F]``, ``w_down [F, d]``, ``b_down [d]``; ``lnf_g lnf_b [d]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from a seed of any size (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(
        d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
        f=cfg["intermediate_size"], v=cfg["vocab_size"],
        layers=cfg["num_hidden_layers"],
    )


def layer_shapes(cfg: dict) -> dict:
    s = dims(cfg)
    d, h, hkv, hd, f = s["d"], s["h"], s["hkv"], s["hd"], s["f"]
    return {
        "ln1_g": (d,), "ln1_b": (d,),
        "wq": (d, h, hd), "bq": (h, hd),
        "wk": (d, hkv, hd), "bk": (hkv, hd),
        "wv": (d, hkv, hd), "bv": (hkv, hd),
        "wo": (h, hd, d), "bo": (d,),
        "ln2_g": (d,), "ln2_b": (d,),
        "w_up": (d, f), "b_up": (f,),
        "w_down": (f, d), "b_down": (d,),
    }


def _draw(key, shapes: dict, std: float, dtype) -> dict:
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_g"):  # LayerNorm scale: 1 + noise
            x = 1.0 + 0.02 * jax.random.normal(k, shape, F32)
        elif name.startswith("b") or name.endswith("_b"):
            x = 0.02 * jax.random.normal(k, shape, F32)
        else:
            x = std * jax.random.normal(k, shape, F32)
        out[name] = x.astype(dtype)
    return out


def make_weights(cfg: dict, seed: int) -> dict:
    """Seeded weights on the default device: one compiled program per kind
    of tensor group, called once a layer."""
    dtype = jnp.dtype(cfg.get("torch_dtype", "bfloat16"))
    std = float(cfg.get("initializer_range", 0.02))
    s = dims(cfg)
    layer = jax.jit(functools.partial(
        _draw, shapes=layer_shapes(cfg), std=std, dtype=dtype))
    ends = jax.jit(functools.partial(
        _draw, shapes={"embed": (s["v"], s["d"]), "lnf_g": (s["d"],),
                       "lnf_b": (s["d"],)}, std=std, dtype=dtype))
    key = seed_key(seed)
    weights = ends(jax.random.fold_in(key, 0))
    weights["layers"] = [
        layer(jax.random.fold_in(key, 1 + i)) for i in range(s["layers"])
    ]
    return weights


# ------------------------------------------------------------------ forward


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def rope(x, positions, theta):
    """Rotate-half RoPE over ``x [T, H, D]`` at ``positions [T]``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = positions.astype(F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def run_epsilon(cfg: dict) -> float:
    """LayerNorm's epsilon as the cell runs it: ``assumed.norm_epsilon_run``
    where the file states a departure from the published ``norm_epsilon``."""
    return cfg.get("assumed", {}).get("norm_epsilon_run", cfg["norm_epsilon"])


def block(x, w, *, cfg: dict, eps: float, einsum=jnp.einsum):
    """One layer over ``x [T, d]`` (float32). Every matmul goes through
    ``einsum``."""
    w = {k: v.astype(F32) for k, v in w.items()}
    theta = cfg["rope_theta"]
    window = cfg.get("sliding_window") or 0
    t = x.shape[0]
    pos = jnp.arange(t)

    y = layer_norm(x, w["ln1_g"], w["ln1_b"], eps)
    q = einsum("td,dhk->thk", y, w["wq"]) + w["bq"]
    k = einsum("td,dhk->thk", y, w["wk"]) + w["bk"]
    v = einsum("td,dhk->thk", y, w["wv"]) + w["bv"]
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    h, hkv, hd = q.shape[1], k.shape[1], q.shape[2]
    qg = q.reshape(t, hkv, h // hkv, hd)
    scores = einsum("qgrk,sgk->grqs", qg, k) * hd**-0.5
    visible = pos[None, :] <= pos[:, None]
    if window:
        visible &= pos[None, :] > pos[:, None] - window
    scores = jnp.where(visible[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("grqs,sgk->qgrk", probs, v).reshape(t, h, hd)
    x = x + einsum("thk,hkd->td", att, w["wo"]) + w["bo"]

    y = layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    up = gelu_tanh(einsum("td,df->tf", y, w["w_up"]) + w["b_up"])
    return x + einsum("tf,fd->td", up, w["w_down"]) + w["b_down"]


@functools.lru_cache(maxsize=None)
def _programs(cfg_items: tuple, eps: float, einsum):
    cfg = dict(cfg_items)

    def embed(table, tokens):
        return table[tokens].astype(F32)

    def head(table, g, b, x, rows):
        y = layer_norm(x[rows], g.astype(F32), b.astype(F32), eps)
        return einsum("rd,vd->rv", y, table.astype(F32))

    return (jax.jit(embed),
            jax.jit(functools.partial(block, cfg=cfg, eps=eps, einsum=einsum)),
            jax.jit(head))


def logits_at(cfg: dict, weights: dict, tokens, rows, *, einsum=jnp.einsum,
              pad_tokens_to: int = 0, pad_rows_to: int = 0):
    """Float32 logits ``[len(rows), V]`` at positions ``rows`` of ONE token
    sequence ``tokens [T]`` (row ``p`` predicts token ``p + 1``). Layer by
    layer, each layer's weights upcast inside its own program; every matmul
    goes through ``einsum``. ``pad_*_to`` pad the sequence (at
    its end: causal attention never sees it) and the rows, so that one
    compiled program serves requests of every length."""
    tokens, rows = list(tokens), list(rows)
    if not rows:
        raise ValueError("no row to score")
    n = len(rows)
    tokens += [0] * (pad_tokens_to - len(tokens))
    rows += [rows[-1]] * (pad_rows_to - n)
    scalars = tuple(sorted(
        (k, v) for k, v in cfg.items()
        if isinstance(v, (int, float, str, bool))))
    embed, layer, head = _programs(scalars, run_epsilon(cfg), einsum)
    with jax.default_matmul_precision("highest"):
        x = embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
        for w in weights["layers"]:
            x = layer(x, w)
        return head(weights["embed"], weights["lnf_g"], weights["lnf_b"], x,
                    jnp.asarray(rows, jnp.int32))[:n]
