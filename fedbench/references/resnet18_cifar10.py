"""The plain reference of ``resnet18_cifar10``: ResNet-18 (He et al.
2015, Table 1, 18-layer column) as its configuration file cuts it, in
float32 ``jax.numpy`` over the program's parameter tree.

Follows ``fedbench/configs/resnet18_cifar10.json``'s ``reduced_why``: a
3x3 stride-1 stem with no max-pool on 32x32x3 inputs, basic blocks of
two 3x3 convolutions, a 1x1 projection shortcut where the stride or the
width changes (the first block of stages 2 to 4, stride 2), global mean
pool, one dense layer; GroupNorm for BatchNorm, here as the textbook
mean and variance of a ``[n, h, w, g, c/g]`` view (Wu & He 2018, eq.
1-3; eps 1e-5), which the program no longer computes that way (PR 25).
One departure from the paper's own code, the program's: a stride-2 3x3
convolution pads as XLA's ``SAME`` does, one row and column after the
map and none before. Imports nothing of ``baton_tpu``; no ``vmap``, no
``custom_vjp``, no kernel.
"""

import jax
import jax.numpy as jnp

from fedbench.reference import masked_mean_cross_entropy


def _conv(x, w, stride, cast):
    """NHWC x HWIO, output size ceil(size / stride)."""
    pads = []
    for size, k in zip(x.shape[1:3], w.shape[:2]):
        total = max((-(-size // stride) - 1) * stride + k - size, 0)
        pads.append((total // 2, total - total // 2))
    return jax.lax.conv_general_dilated(
        cast(x), cast(w), (stride, stride), pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)


def _group_norm(x, p, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = min(groups, c)
    v = x.reshape(n, h, w, g, c // g)
    mean = jnp.mean(v, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean((v - mean) ** 2, axis=(1, 2, 4), keepdims=True)
    v = (v - mean) / jnp.sqrt(var + eps)
    return v.reshape(n, h, w, c) * p["scale"] + p["bias"]


def _block(x, p, stride, groups, cast):
    out = _conv(x, p["conv1"], stride, cast)
    out = jnp.maximum(_group_norm(out, p["gn1"], groups), 0.0)
    out = _group_norm(_conv(out, p["conv2"], 1, cast), p["gn2"], groups)
    if "proj" in p:
        x = _group_norm(_conv(x, p["proj"], stride, cast), p["gn_proj"],
                        groups)
    return jnp.maximum(out + x, 0.0)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``
    (the configuration file, ``tiny.sizes`` laid over it in a
    rehearsal): ``x [n, px, px, 3]``, ``y [n]``, ``mask [n]``. ``cast``
    is applied to both operands of every convolution and of the dense
    layer: the identity, or the control's rounding to a lower precision
    (``fedbench/reference.py::rounded_to``)."""
    groups = config["norm_groups"]
    blocks = config["blocks_per_stage"]

    def logits(params, x):
        x = _conv(x.astype(jnp.float32), params["stem"],
                  config["stem_stride"], cast)
        x = jnp.maximum(_group_norm(x, params["gn_stem"], groups), 0.0)
        for s, n_blocks in enumerate(blocks):
            for b in range(n_blocks):
                stride = 2 if (b == 0 and s > 0) else 1
                x = _block(x, params[f"s{s}b{b}"], stride, groups, cast)
        pooled = jnp.mean(x, axis=(1, 2))
        return jnp.matmul(cast(pooled), cast(params["fc"]["w"]),
                          precision="highest") + params["fc"]["b"]

    def loss(params, x, y, mask):
        return masked_mean_cross_entropy(logits(params, x), y, mask)

    return loss
