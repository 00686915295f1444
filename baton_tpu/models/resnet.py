"""ResNet-18 with GroupNorm — the flagship model (BASELINE config 2).

The reference framework never ships a real vision model (its demo model is
a 10->1 linear layer, reference demo.py:15-49); ResNet-18/CIFAR-10 is the
driver-set north-star workload. Design choices for TPU + federation:

* **GroupNorm, not BatchNorm**: BN running stats don't aggregate under
  client drift (see :meth:`baton_tpu.core.model.FedModel.from_flax`), and
  GN keeps the model a pure function of (params, batch) — vmappable over
  thousands of simulated clients with no mutable collections.
* **NHWC + optional bfloat16 compute**: convs lower to MXU-tiled
  ``conv_general_dilated``; params stay fp32 (FedAvg accumulates in
  fp32), activations/weights are cast to ``compute_dtype`` per-apply.
  GroupNorm keeps that layout: per-channel moments over ``(h, w)`` with
  ``C`` minor, groups formed on ``[b, C]``; a ``c/g``-minor view of the
  activation does not fit the TPU's 8 x 128 tiles and costs a transpose.
* **CIFAR stem** (3x3, stride 1, no maxpool) by default; ``imagenet_stem``
  switches to 7x7/stride-2 + maxpool for 224px inputs (ViT-sized runs).
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from baton_tpu.core.losses import softmax_cross_entropy
from baton_tpu.core.model import FedModel

STAGE_WIDTHS: Tuple[int, ...] = (64, 128, 256, 512)
BLOCKS_PER_STAGE_18: Tuple[int, ...] = (2, 2, 2, 2)
BLOCKS_PER_STAGE_34: Tuple[int, ...] = (3, 4, 6, 3)


def _he(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) * jnp.sqrt(2.0 / fan_in)


def _conv_init(key, kh, kw, cin, cout):
    return _he(key, (kh, kw, cin, cout), kh * kw * cin)


def _gn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def _conv_direct(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x,
        w.astype(x.dtype),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _conv_im2col(x, w, stride=1):
    """SAME conv as (shifted slices -> concat) + one ``dot_general``.

    Why this exists: the flagship workload vmaps the model over a client
    axis with PER-CLIENT weights. ``vmap`` of ``conv_general_dilated``
    with a batched rhs lowers to a C-group grouped convolution, whose
    small per-group contractions leave the MXU mostly idle (recorded
    at ~8% MFU on v5e; not measured on the current stack). This
    formulation keeps every FLOP in a plain matmul: patch extraction
    is kh*kw strided slices
    (pure data movement, weight-independent — vmap leaves it untouched),
    and the contraction [B*OH*OW, kh*kw*Cin] x [kh*kw*Cin, Cout] becomes
    an MXU-tiled *batched* matmul under client-vmap. The kh*kw-fold
    activation blowup is transient (fused/freed by XLA) and is the price
    of dense MXU tiles.

    Numerics: identical contraction order per output element up to
    floating-point reassociation; tests pin it to the direct conv within
    dtype tolerance (tests/test_resnet.py).
    """
    kh, kw, cin, cout = w.shape
    cols = [xs for _, _, xs in _shifted_views(x, kh, kw, stride)]
    patches = jnp.concatenate(cols, axis=-1)  # [B, OH, OW, kh*kw*Cin]
    wm = w.astype(x.dtype).reshape(kh * kw * cin, cout)
    return jax.lax.dot_general(patches, wm, (((3,), (0,)), ((), ())))


def _shifted_views(x, kh, kw, stride):
    """Yield ``(i, j, shifted_view)`` for each kernel tap of a SAME
    conv: the strided slice of the padded input that tap (i, j)
    multiplies. Shared padding/slice arithmetic for the im2col and
    shift-GEMM lowerings."""
    b, h, wd, _ = x.shape
    oh = -(-h // stride)
    ow = -(-wd // stride)
    ph = max((oh - 1) * stride + kh - h, 0)
    pw = max((ow - 1) * stride + kw - wd, 0)
    xp = jnp.pad(
        x, ((0, 0), (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2), (0, 0))
    )
    for i in range(kh):
        for j in range(kw):
            yield i, j, xp[:, i : i + (oh - 1) * stride + 1 : stride,
                           j : j + (ow - 1) * stride + 1 : stride, :]


def _conv_shift(x, w, stride=1):
    """SAME conv as a sum of kh*kw shifted plain matmuls
    (``y = sum_ij shift(x, i, j) @ w[i, j]`` — the kn2row/shift-GEMM
    decomposition).

    Same motivation as :func:`_conv_im2col` (per-client weights under
    vmap must lower to batched matmuls, not C-group grouped
    convolutions) but WITHOUT im2col's kh*kw-fold patch
    materialization: each term reads a shifted view of ``x`` and
    contracts only over Cin, so peak activation HBM stays at the direct
    conv's level (the im2col wave-32 kernel's 19.2 GiB static plan
    exceeded the v5e's capacity — measured live, r4). The trade: kh*kw
    matmuls with K = Cin instead of one with K = kh*kw*Cin — smaller
    MXU tiles on the 64-channel stem, full-size from stage 2 on.

    Numerics: per output element the same multiply-adds as the direct
    conv, reassociated. The kh*kw partial products are accumulated in
    fp32 regardless of compute dtype (``preferred_element_type``) — a
    bf16 running sum would round at every inter-term add, drifting far
    past reassociation noise — and cast back once at return. Pinned
    against the direct conv in fp32 AND bf16 in tests/test_resnet.py.
    """
    kh, kw, _, _ = w.shape
    wm = w.astype(x.dtype)
    out = None
    for i, j, xs in _shifted_views(x, kh, kw, stride):
        term = jax.lax.dot_general(
            xs, wm[i, j], (((3,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = term if out is None else out + term
    return out.astype(x.dtype)


# module-level dispatch table so `conv_impl` stays a plain string in the
# model factory signature (hashable, serializable into configs)
_CONV_IMPLS = {"direct": _conv_direct, "im2col": _conv_im2col,
               "shift": _conv_shift}


@jax.named_scope("conv")
def _conv(x, w, stride=1, impl="direct"):
    return _CONV_IMPLS[impl](x, w, stride)


@jax.named_scope("norm")
def _group_norm(x, p, n_groups=32, eps=1e-5):
    """GroupNorm over NHWC; float32 statistics whatever the compute dtype.

    The statistics are per-channel sums over ``(h, w)`` with ``C`` left
    minor, folded into groups on the ``[b, C]`` result. A ``[b, h, w, g,
    c/g]`` view has a minor dimension of 2..16 where the TPU tiles
    8 x 128: XLA transposes every activation to reduce over it, forward
    and backward (71 % of the ResNet wave; PERF.md, PR 25). The backward
    is written out so that its residuals are ``x`` in its own dtype plus
    ``[b, C]`` statistics and it reduces per channel too.
    """
    _, h, w, c = x.shape
    g = min(n_groups, c)
    per_group = c // g
    n = h * w * per_group

    def group_mean(per_channel):
        # [b, C] sums over (h, w) -> each channel's group mean, back on
        # [b, C]: the one view by group there is, on b * C numbers.
        # Divide before the repeat: a group total repeated and scaled
        # afterwards XLA rewrote into a reduce-window over c/g, 4 ms a wave
        b = per_channel.shape[0]
        m = jnp.sum(per_channel.reshape(b, g, per_group), axis=-1) / n
        return jnp.repeat(m, per_group, axis=-1)

    def centre(x, mean):
        return x.astype(jnp.float32) - mean[:, None, None, :]

    def fwd(x, scale, bias):
        # two passes, each a per-channel reduction over (h, w): the sum,
        # then the second moment about the group's mean (a one-pass form
        # about a guessed centre was 2 % faster a wave and up to 2e-3 off
        # where the guess is an outlier: PERF.md, PR 25)
        mean = group_mean(jnp.sum(x, axis=(1, 2), dtype=jnp.float32))
        d = centre(x, mean)
        var = group_mean(jnp.sum(d * d, axis=(1, 2)))
        rstd = jax.lax.rsqrt(var + eps)
        y = d * (rstd * scale)[:, None, None, :] + bias
        return y.astype(x.dtype), (x, scale, mean, rstd)

    def bwd(res, dy):
        x, scale, mean, rstd = res
        dy = dy.astype(jnp.float32)
        d = centre(x, mean)
        s_dy = jnp.sum(dy, axis=(1, 2))
        s_dyx = jnp.sum(dy * d, axis=(1, 2)) * rstd  # sum of dy * xhat
        k_dy = rstd * scale
        k_d = -rstd * rstd * group_mean(s_dyx * scale)
        k_1 = -rstd * group_mean(s_dy * scale)
        dx = (dy * k_dy[:, None, None, :] + d * k_d[:, None, None, :]
              + k_1[:, None, None, :])
        return dx.astype(x.dtype), jnp.sum(s_dyx, axis=0), jnp.sum(s_dy, axis=0)

    norm = jax.custom_vjp(lambda *args: fwd(*args)[0])
    norm.defvjp(fwd, bwd)
    return norm(x, p["scale"], p["bias"])


def _block_init(key, cin, cout, stride):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(k1, 3, 3, cin, cout),
        "gn1": _gn_init(cout),
        "conv2": _conv_init(k2, 3, 3, cout, cout),
        "gn2": _gn_init(cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(k3, 1, 1, cin, cout)
        p["gn_proj"] = _gn_init(cout)
    return p


def _block_apply(x, p, stride, n_groups, impl="direct"):
    out = _conv(x, p["conv1"], stride, impl)
    out = jax.nn.relu(_group_norm(out, p["gn1"], n_groups))
    out = _conv(out, p["conv2"], 1, impl)
    out = _group_norm(out, p["gn2"], n_groups)
    with jax.named_scope("shortcut"):
        if "proj" in p:
            x = _group_norm(_conv(x, p["proj"], stride, impl), p["gn_proj"],
                            n_groups)
        out = out + x
    return jax.nn.relu(out)


def resnet_model(
    blocks_per_stage: Sequence[int] = BLOCKS_PER_STAGE_18,
    n_classes: int = 10,
    channels: int = 3,
    n_groups: int = 32,
    width_multiplier: int = 1,
    imagenet_stem: bool = False,
    compute_dtype=jnp.float32,
    conv_impl: str = "direct",
    name: str = "resnet18",
) -> FedModel:
    if conv_impl not in _CONV_IMPLS:
        raise ValueError(
            f"conv_impl must be one of {sorted(_CONV_IMPLS)}, got "
            f"{conv_impl!r}"
        )
    if len(blocks_per_stage) > len(STAGE_WIDTHS):
        raise ValueError(
            f"at most {len(STAGE_WIDTHS)} stages supported, got "
            f"{len(blocks_per_stage)}"
        )
    widths = [w * width_multiplier for w in STAGE_WIDTHS]

    def stride_of(s, b):
        return 2 if (b == 0 and s > 0) else 1

    def init(rng):
        keys = jax.random.split(rng, 2 + sum(blocks_per_stage))
        it = iter(keys)
        stem_kh = 7 if imagenet_stem else 3
        params = {
            "stem": _conv_init(next(it), stem_kh, stem_kh, channels, widths[0]),
            "gn_stem": _gn_init(widths[0]),
        }
        cin = widths[0]
        for s, (n_blocks, cout) in enumerate(zip(blocks_per_stage, widths)):
            for b in range(n_blocks):
                params[f"s{s}b{b}"] = _block_init(
                    next(it), cin, cout, stride_of(s, b)
                )
                cin = cout
        params["fc"] = {
            "w": _he(next(it), (cin, n_classes), cin),
            "b": jnp.zeros((n_classes,), jnp.float32),
        }
        return params

    def apply(params, batch, rng):
        x = batch["x"].astype(compute_dtype)
        stem_stride = 2 if imagenet_stem else 1
        # scopes: stem, one a block under its parameter key, head
        with jax.named_scope("stem"):
            x = _conv(x, params["stem"], stem_stride, conv_impl)
            x = jax.nn.relu(_group_norm(x, params["gn_stem"], n_groups))
            if imagenet_stem:
                x = jax.lax.reduce_window(
                    x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                    "SAME"
                )
        for s, n_blocks in enumerate(blocks_per_stage):
            for b in range(n_blocks):
                with jax.named_scope(f"s{s}b{b}"):
                    x = _block_apply(x, params[f"s{s}b{b}"], stride_of(s, b),
                                     n_groups, conv_impl)
        with jax.named_scope("head"):
            x = jnp.mean(x, axis=(1, 2))
            logits = (x.astype(jnp.float32) @ params["fc"]["w"]
                      + params["fc"]["b"])
        return logits

    def per_example_loss(params, batch, rng):
        return softmax_cross_entropy(apply(params, batch, rng), batch, rng)

    return FedModel(init=init, apply=apply, per_example_loss=per_example_loss, name=name)


def resnet18_cifar_model(
    n_classes: int = 10, compute_dtype=jnp.float32, conv_impl: str = "direct",
    name: str = "resnet18_cifar"
) -> FedModel:
    """ResNet-18 for 32x32 inputs — the north-star/bench model.

    ``conv_impl="im2col"`` reformulates every conv as patch slices + a
    batched matmul — the MXU-friendly lowering for vmapped per-client
    training (see :func:`_conv_im2col`).
    """
    return resnet_model(
        BLOCKS_PER_STAGE_18,
        n_classes=n_classes,
        compute_dtype=compute_dtype,
        conv_impl=conv_impl,
        name=name,
    )
