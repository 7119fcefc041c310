"""Plain reference for ResNet-50 training (He et al. 2015, the bottleneck
net with the stride on the 3x3, as torchvision builds it), in float32 at
``highest`` precision: forward, softmax cross-entropy, gradients, SGD with
momentum. It imports nothing of the program under test.

Layout: NHWC images, HWIO kernels. Weights are a flat dict keyed
``<module>/<leaf>`` (``conv_init/kernel``, ``stage2_block1/bn3/scale``,
``head/bias``), which the driver folds into the program's tree.

Departures from torchvision, stated: the three stride-2 3x3 convolutions pad
as XLA's ``SAME`` does (nothing before, one after) where torchvision pads one
on each side, because that is the convolution ``models/resnet.py`` runs;
BatchNorm statistics are taken over the whole global batch. Each bottleneck
block is rematerialised in the backward pass, so that 256 float32 images fit
on one chip; that changes memory, not results.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def seed_key(seed: int) -> jax.Array:
    seed = int(seed)
    return jnp.asarray(np.array(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], dtype=np.uint32))


def blocks(cfg: dict) -> List[Tuple[str, int, int]]:
    """(name, filters, stride) of every bottleneck block, in order."""
    out = []
    for stage, n in enumerate(cfg["stage_sizes"]):
        for b in range(n):
            stride = 2 if stage > 0 and b == 0 else 1
            out.append((f"stage{stage + 1}_block{b + 1}",
                        cfg["num_filters"] * 2**stage, stride))
    return out


def param_shapes(cfg: dict) -> Dict[str, tuple]:
    f0, exp = cfg["num_filters"], cfg["bottleneck_expansion"]
    shapes = {"conv_init/kernel": (7, 7, 3, f0)}
    for leaf in ("scale", "bias"):
        shapes[f"bn_init/{leaf}"] = (f0,)
    cin = f0
    for name, f, _stride in blocks(cfg):
        convs = [("conv1", (1, 1, cin, f), "bn1"), ("conv2", (3, 3, f, f), "bn2"),
                 ("conv3", (1, 1, f, f * exp), "bn3")]
        if cin != f * exp or _stride != 1:
            convs.append(("conv_proj", (1, 1, cin, f * exp), "bn_proj"))
        for conv, shape, bn in convs:
            shapes[f"{name}/{conv}/kernel"] = shape
            shapes[f"{name}/{bn}/scale"] = (shape[-1],)
            shapes[f"{name}/{bn}/bias"] = (shape[-1],)
        cin = f * exp
    shapes["head/kernel"] = (cin, cfg["num_classes"])
    shapes["head/bias"] = (cfg["num_classes"],)
    return shapes


def _draw(key, shapes: Dict[str, tuple]) -> Dict[str, jax.Array]:
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("/kernel"):
            fan_in = int(np.prod(shape[:-1]))
            gain = 1.0 if name.startswith("head/") else 2.0
            out[name] = jnp.sqrt(gain / fan_in) * jax.random.normal(k, shape, F32)
        elif name.endswith("/scale"):
            out[name] = jnp.ones(shape, F32)
        else:
            out[name] = jnp.zeros(shape, F32)
    return out


def make_weights(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """Seeded float32 parameters, made on the device in one compiled call:
    He-normal kernels (fan-in), a lecun-normal head, BatchNorm scale 1 and
    bias 0."""
    make = jax.jit(functools.partial(_draw, shapes=param_shapes(cfg)))
    return make(seed_key(seed))


def n_params(cfg: dict) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(cfg).values())


def forward_macs_per_sample(cfg: dict) -> int:
    """Multiply-adds of one image's forward pass, counted layer by layer
    from the shapes (convolutions and the head; ``SAME`` padding, so a
    stride-s convolution leaves ceil(size / s) pixels a side). 4.09e9 for
    the 50-layer net at 224x224, as counts of torchvision's model give."""
    shapes = param_shapes(cfg)
    up = lambda size, stride: -(-size // stride)  # noqa: E731
    size = up(cfg["image_size"], 2)  # conv_init, stride 2
    macs = size * size * int(np.prod(shapes["conv_init/kernel"]))
    size = up(size, 2)  # the 3x3 max pool, stride 2
    for name, _f, stride in blocks(cfg):
        out = up(size, stride)
        for conv_name, at in (("conv1", size), ("conv2", out), ("conv3", out),
                              ("conv_proj", out)):
            kernel = shapes.get(f"{name}/{conv_name}/kernel")
            if kernel:
                macs += at * at * int(np.prod(kernel))
        size = out
    return macs + int(np.prod(shapes["head/kernel"]))


def train_flops_per_sample(cfg: dict) -> float:
    """Forward and backward (twice the forward) at two FLOPs a
    multiply-add. (``obs/goodput.py:83`` counts a multiply-add as one.)"""
    return 3.0 * 2.0 * forward_macs_per_sample(cfg)


def train_min_bytes_per_step(cfg: dict, samples_on_chip: int) -> float:
    """The least a chip must move in one step, whatever the schedule: read
    the float32 parameters and momentum and write both back, and read its
    share of the float32 images once. Activations are not counted: a
    schedule may keep or recompute them."""
    image = cfg["image_size"] ** 2 * 3 * 4.0
    return 4.0 * n_params(cfg) * 4 + samples_on_chip * image


# ------------------------------------------------------------------ forward


def conv(x, kernel, stride, padding):
    return jax.lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def batch_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def bottleneck(x, p, stride, eps, conv):
    def cbn(y, conv_name, bn_name, s):
        y = conv(y, p[f"{conv_name}/kernel"], s, "SAME")
        return batch_norm(y, p[f"{bn_name}/scale"], p[f"{bn_name}/bias"], eps)

    y = jax.nn.relu(cbn(x, "conv1", "bn1", 1))
    y = jax.nn.relu(cbn(y, "conv2", "bn2", stride))
    y = cbn(y, "conv3", "bn3", 1)
    if "conv_proj/kernel" in p:
        x = cbn(x, "conv_proj", "bn_proj", stride)
    return jax.nn.relu(x + y)


def loss_fn(params, images, labels, *, cfg: dict, conv=conv):
    """Mean softmax cross-entropy of the net over one batch. ``conv`` is the
    convolution every layer runs (the head too, as a 1x1 over one pixel)."""
    eps = cfg["bn_epsilon"]
    x = conv(images.astype(F32), params["conv_init/kernel"], 2,
             ((3, 3), (3, 3)))
    x = jax.nn.relu(batch_norm(
        x, params["bn_init/scale"], params["bn_init/bias"], eps))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    for name, _f, stride in blocks(cfg):
        p = {k[len(name) + 1:]: v for k, v in params.items()
             if k.startswith(name + "/")}
        x = jax.checkpoint(
            functools.partial(bottleneck, stride=stride, eps=eps, conv=conv)
        )(x, p)
    x = jnp.mean(x, axis=(1, 2))
    logits = conv(
        x[:, None, None, :], params["head/kernel"][None, None], 1, "VALID"
    )[:, 0, 0, :] + params["head/bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), -1)
    return -jnp.mean(picked)


def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}


def train_steps(cfg: dict, params: Dict[str, jax.Array], batches: Sequence,
                *, learning_rate: float, momentum: float, devices=None,
                loss=loss_fn) -> dict:
    """Follow ``len(batches)`` steps of SGD with momentum from ``params``.
    Returns each step's loss, the per-leaf norm of the first gradient and the
    per-leaf norm of the parameters' change after the last step. With several
    ``devices`` the batch is split over them (the statistics stay global)."""

    def step(p, m, images, labels):
        value, g = jax.value_and_grad(loss)(p, images, labels, cfg=cfg)
        m = {k: g[k] + momentum * m[k] for k in p}
        p = {k: p[k] - learning_rate * m[k] for k in p}
        return p, m, value, leaf_norms(g)

    put = lambda batch: batch  # noqa: E731
    if devices is not None and len(devices) > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.asarray(devices), ("data",))
        rows = NamedSharding(mesh, P("data"))
        everywhere = NamedSharding(mesh, P())
        params = jax.device_put(params, everywhere)
        put = lambda batch: jax.device_put(batch, rows)  # noqa: E731
    step = jax.jit(step, donate_argnums=(0, 1))
    start = {k: jnp.array(v) for k, v in params.items()}
    p = {k: jnp.array(v) for k, v in params.items()}
    m = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for images, labels in batches:
            images, labels = put((jnp.asarray(images), jnp.asarray(labels)))
            p, m, value, norms = step(p, m, images, labels)
            losses.append(float(value))
            if first is None:
                first = {k: float(v) for k, v in norms.items()}
    change = leaf_norms({k: p[k] - start[k] for k in p})
    return {
        "losses": losses,
        "first_grad_norms": first,
        "param_change_norms": {k: float(v) for k, v in change.items()},
    }
