"""ResNet (GroupNorm) model: shapes, param count, and a federated round.

Uses a narrow 2-stage variant so CPU tests stay fast; the full
resnet18_cifar_model is exercised for param-count/shape only.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models.resnet import (
    _group_norm, resnet_model, resnet18_cifar_model)
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim


def _tiny_resnet():
    return resnet_model(blocks_per_stage=(1, 1), n_classes=10, n_groups=8,
                        name="resnet_tiny")


def test_resnet18_param_count_and_logits():
    model = resnet18_cifar_model()
    params = model.init(jax.random.key(0))
    n = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    # torchvision resnet18 has 11.69M params (BN); GN has identical
    # scale/bias shapes, CIFAR stem drops the 7x7 stem in favour of 3x3.
    assert 10_500_000 < n < 12_000_000
    batch = {"x": jnp.zeros((2, 32, 32, 3)), "y": jnp.zeros((2,), jnp.int32)}
    logits = model.apply(params, batch, jax.random.key(1))
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_resnet_bf16_compute():
    model = resnet_model(blocks_per_stage=(1,), n_groups=8,
                         compute_dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0))
    batch = {"x": jnp.zeros((2, 16, 16, 3)), "y": jnp.zeros((2,), jnp.int32)}
    logits = model.apply(params, batch, jax.random.key(1))
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32  # head promotes back to fp32
    # params stay fp32 for aggregation
    assert all(p.dtype == jnp.float32 for p in jax.tree_util.tree_leaves(params))


def test_resnet_federated_round_runs(nprng):
    model = _tiny_resnet()
    params = model.init(jax.random.key(0))
    datasets = []
    for _ in range(4):
        n = int(nprng.integers(6, 12))
        x = nprng.normal(size=(n, 16, 16, 3)).astype(np.float32)
        y = nprng.integers(0, 10, size=(n,)).astype(np.int32)
        datasets.append({"x": x, "y": y})
    data, n_samples = stack_client_datasets(datasets, batch_size=8)
    data = {k: jnp.asarray(v) for k, v in data.items()}

    sim = FedSim(model, batch_size=8, learning_rate=0.05)
    res = sim.run_round(params, data, jnp.asarray(n_samples),
                        jax.random.key(3), n_epochs=1)
    assert np.isfinite(float(res.loss_history[0]))
    # aggregated params differ from the broadcast global
    diff = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), res.params, params
    )
    assert max(jax.tree_util.tree_leaves(diff)) > 0


def test_im2col_conv_matches_direct():
    """The MXU-friendly im2col lowering must be numerically equivalent to
    lax.conv_general_dilated for every (stride, kernel, channel) shape
    the ResNet uses — including the 1x1 projection and strided blocks."""
    from baton_tpu.models.resnet import _conv_direct, _conv_im2col

    key = jax.random.key(3)
    for kh, cin, cout, stride, hw in [
        (3, 3, 16, 1, 32),   # stem
        (3, 16, 16, 1, 32),  # body
        (3, 16, 32, 2, 32),  # strided stage entry
        (1, 16, 32, 2, 32),  # strided 1x1 projection
        (3, 8, 8, 2, 9),     # odd spatial size: SAME padding asymmetry
        (7, 3, 16, 2, 33),   # imagenet stem shape
    ]:
        kx, kw_ = jax.random.split(jax.random.fold_in(key, kh * cin * stride))
        x = jax.random.normal(kx, (2, hw, hw, cin), jnp.float32)
        w = jax.random.normal(kw_, (kh, kh, cin, cout), jnp.float32)
        ref = _conv_direct(x, w, stride)
        got = _conv_im2col(x, w, stride)
        assert got.shape == ref.shape, (kh, cin, cout, stride, hw)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_im2col_resnet_vmapped_grads_match(nprng):
    """Full per-client path: vmapped value_and_grad of the tiny ResNet is
    the same function under either conv lowering (the production switch
    for raising MXU occupancy must not change the training math)."""
    m_direct = resnet_model(blocks_per_stage=(1,), n_classes=4, n_groups=4)
    m_im2col = resnet_model(blocks_per_stage=(1,), n_classes=4, n_groups=4,
                            conv_impl="im2col")
    params = m_direct.init(jax.random.key(0))
    x = jnp.asarray(nprng.normal(size=(3, 2, 8, 8, 3)), jnp.float32)
    y = jnp.asarray(nprng.integers(0, 4, size=(3, 2)), jnp.int32)

    def mean_loss(model, p, xb, yb):
        return jnp.mean(model.per_example_loss(
            p, {"x": xb, "y": yb}, jax.random.key(1)))

    def per_client(model):
        f = lambda p, xb, yb: jax.value_and_grad(
            lambda pp: mean_loss(model, pp, xb, yb))(p)
        return jax.vmap(f, in_axes=(None, 0, 0))(params, x, y)

    loss_d, grad_d = per_client(m_direct)
    loss_i, grad_i = per_client(m_im2col)
    np.testing.assert_allclose(loss_i, loss_d, rtol=1e-5, atol=1e-5)
    for gd, gi in zip(jax.tree_util.tree_leaves(grad_d),
                      jax.tree_util.tree_leaves(grad_i)):
        np.testing.assert_allclose(gi, gd, rtol=5e-4, atol=5e-4)


def test_cnn_im2col_matches_direct(nprng):
    """The CNN shares the conv-lowering switch; both impls must be the
    same function through a vmapped per-client grad."""
    from baton_tpu.models.cnn import cnn_mnist_model

    m_d = cnn_mnist_model(image_size=8, channels=1, width=4)
    m_i = cnn_mnist_model(image_size=8, channels=1, width=4,
                          conv_impl="im2col")
    params = m_d.init(jax.random.key(0))
    x = jnp.asarray(nprng.normal(size=(3, 2, 8, 8, 1)), jnp.float32)
    y = jnp.asarray(nprng.integers(0, 10, size=(3, 2)), jnp.int32)

    def per_client(model):
        f = lambda p, xb, yb: jax.value_and_grad(lambda pp: jnp.mean(
            model.per_example_loss(pp, {"x": xb, "y": yb},
                                   jax.random.key(1))))(p)
        return jax.vmap(f, in_axes=(None, 0, 0))(params, x, y)

    loss_d, grad_d = per_client(m_d)
    loss_i, grad_i = per_client(m_i)
    np.testing.assert_allclose(loss_i, loss_d, rtol=1e-5, atol=1e-5)
    for gd, gi in zip(jax.tree_util.tree_leaves(grad_d),
                      jax.tree_util.tree_leaves(grad_i)):
        np.testing.assert_allclose(gi, gd, rtol=5e-4, atol=5e-4)


def test_shift_conv_matches_direct():
    """The shift-GEMM lowering (sum of kh*kw shifted plain matmuls —
    batched-matmul MFU without im2col's kh*kw activation blowup) must be
    numerically equivalent to lax.conv_general_dilated for every shape
    the ResNet uses."""
    from baton_tpu.models.resnet import _conv_direct, _conv_shift

    key = jax.random.key(5)
    for kh, cin, cout, stride, hw in [
        (3, 3, 16, 1, 32),   # stem
        (3, 16, 16, 1, 32),  # body
        (3, 16, 32, 2, 32),  # strided stage entry
        (1, 16, 32, 2, 32),  # strided 1x1 projection
        (3, 8, 8, 2, 9),     # odd spatial size: SAME padding asymmetry
        (7, 3, 16, 2, 33),   # imagenet stem shape
    ]:
        kx, kw_ = jax.random.split(jax.random.fold_in(key, kh * cin * stride))
        x = jax.random.normal(kx, (2, hw, hw, cin), jnp.float32)
        w = jax.random.normal(kw_, (kh, kh, cin, cout), jnp.float32)
        ref = _conv_direct(x, w, stride)
        got = _conv_shift(x, w, stride)
        assert got.shape == ref.shape, (kh, cin, cout, stride, hw)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_shift_resnet_vmapped_grads_match(nprng):
    """Per-client vmapped value_and_grad is the same function under the
    shift lowering (mirror of the im2col parity test)."""
    m_direct = resnet_model(blocks_per_stage=(1,), n_classes=4, n_groups=4)
    m_shift = resnet_model(blocks_per_stage=(1,), n_classes=4, n_groups=4,
                           conv_impl="shift")
    params = m_direct.init(jax.random.key(0))
    x = jnp.asarray(nprng.normal(size=(3, 2, 8, 8, 3)), jnp.float32)
    y = jnp.asarray(nprng.integers(0, 4, size=(3, 2)), jnp.int32)

    def mean_loss(model, p, xb, yb):
        return jnp.mean(model.per_example_loss(
            p, {"x": xb, "y": yb}, jax.random.key(1)))

    def per_client(model):
        f = lambda p, xb, yb: jax.value_and_grad(
            lambda pp: mean_loss(model, pp, xb, yb))(p)
        return jax.vmap(f, in_axes=(None, 0, 0))(params, x, y)

    loss_d, grad_d = per_client(m_direct)
    loss_s, grad_s = per_client(m_shift)
    np.testing.assert_allclose(loss_s, loss_d, rtol=1e-5, atol=1e-5)
    for gd, gs in zip(jax.tree_util.tree_leaves(grad_d),
                      jax.tree_util.tree_leaves(grad_s)):
        np.testing.assert_allclose(gs, gd, rtol=5e-4, atol=5e-4)


def test_shift_conv_bf16_accumulation():
    """In the dtype the flagship actually trains in (bf16 compute),
    shift-GEMM must match the direct conv to bf16-level tolerance: its
    kh*kw partial products accumulate in fp32, so the only divergence
    is the final-cast rounding, not 9 (or 49) compounding bf16 adds."""
    from baton_tpu.models.resnet import _conv_direct, _conv_shift

    key = jax.random.key(11)
    for kh, cin, cout, stride, hw in [
        (3, 64, 64, 1, 32),
        (7, 3, 64, 2, 33),   # 49-tap imagenet stem: worst accumulation
    ]:
        kx, kw_ = jax.random.split(jax.random.fold_in(key, kh * cin))
        x = jax.random.normal(kx, (2, hw, hw, cin), jnp.bfloat16)
        w = jax.random.normal(kw_, (kh, kh, cin, cout), jnp.float32)
        ref = np.asarray(_conv_direct(x, w, stride), np.float32)
        got = np.asarray(_conv_shift(x, w, stride), np.float32)
        # bf16 has ~2-3 decimal digits; both sides accumulate in fp32
        # internally so they agree to one final-rounding ulp
        scale = np.maximum(np.abs(ref), 1.0)
        np.testing.assert_allclose(got / scale, ref / scale, atol=2e-2)


# ---------------------------------------------------------------- GroupNorm
def _group_norm_5d(x, p, n_groups=32, eps=1e-5):
    """The formulation ``_group_norm`` had until PR 25, kept as the
    oracle: float32 statistics over a ``[b, h, w, g, c/g]`` view."""
    b, h, w, c = x.shape
    g = min(n_groups, c)
    xf = x.astype(jnp.float32).reshape(b, h, w, g, c // g)
    mean = jnp.mean(xf, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xf, axis=(1, 2, 4), keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    xf = xf.reshape(b, h, w, c)
    return (xf * p["scale"] + p["bias"]).astype(x.dtype)


def _vmapped_value_and_grad(norm, x, p, t):
    """``(y, dx, dscale, dbias)`` of ``norm`` under a client ``vmap``
    with per-client ``scale``/``bias``; the cotangent is ``t``."""
    def loss(x, p):
        y = jax.vmap(norm)(x, p)
        return jnp.sum(y.astype(jnp.float32) * t), y

    (_, y), (dx, dp) = jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True)(x, p)
    return y, dx, dp["scale"], dp["bias"]


def _group_norm_case(c, h, w, dtype, shift, clients=3, b=4):
    k = jax.random.split(jax.random.key(c * 100 + h), 4)
    x = (jax.random.normal(k[0], (clients, b, h, w, c)) + shift).astype(dtype)
    p = {"scale": 1.0 + 0.3 * jax.random.normal(k[1], (clients, c)),
         "bias": 0.3 * jax.random.normal(k[2], (clients, c))}
    t = jax.random.normal(k[3], (clients, b, h, w, c))
    return x, p, t


@pytest.mark.parametrize(
    "c,h,w,dtype,shift,tol",
    [
        (32, 4, 4, jnp.float32, 0.0, 1e-4),     # c/g = 1
        (64, 8, 8, jnp.float32, 0.0, 1e-4),     # c/g = 2: stem and stage 0
        (128, 4, 6, jnp.float32, 0.0, 1e-4),    # c/g = 4, h != w
        (512, 2, 2, jnp.float32, 0.0, 1e-4),    # c/g = 16
        (64, 8, 8, jnp.bfloat16, 0.0, 2e-2),
        (512, 2, 2, jnp.bfloat16, 0.0, 2e-2),
        # mean / std = 100: a careless E[x^2] - E[x]^2 in float32 fails here
        (64, 8, 8, jnp.float32, 100.0, 1e-3),
    ],
    ids=["cg1", "cg2", "cg4_h_ne_w", "cg16", "cg2_bf16", "cg16_bf16",
         "cg2_shifted_mean"],
)
def test_group_norm_matches_the_5d_formulation(c, h, w, dtype, shift, tol):
    x, p, t = _group_norm_case(c, h, w, dtype, shift)
    got = _vmapped_value_and_grad(_group_norm, x, p, t)
    want = _vmapped_value_and_grad(_group_norm_5d, x, p, t)
    for name, u, v in zip(("y", "dx", "dscale", "dbias"), got, want):
        assert u.dtype == v.dtype and u.shape == v.shape, name
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        assert np.max(np.abs(u - v)) <= tol * np.max(np.abs(v)), name


def _group_sized_minor_dims(norm, c=64, n_groups=32):
    """Shapes of the intermediates, anywhere in the jaxpr of the vmapped
    value-and-grad of ``norm``, that hold at least ``x.size`` elements
    with a last dimension of ``c / g``."""
    x, p, t = _group_norm_case(c, 8, 8, jnp.bfloat16, 0.0)
    minor = c // n_groups
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                shape = getattr(v.aval, "shape", ())
                if (shape and shape[-1] == minor
                        and int(np.prod(shape)) >= x.size):
                    found.append((eqn.primitive.name, tuple(shape)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(partial(_vmapped_value_and_grad, norm))(x, p, t).jaxpr)
    return found


def test_group_norm_never_views_the_activation_by_group():
    # the statistics are per-channel sums in [b, h, w, C], folded into
    # groups on [b, C]: a minor dimension of c/g (2 here, on 8 x 128
    # tiles) is what made XLA transpose every activation on the TPU
    assert _group_sized_minor_dims(_group_norm) == []
    # and the walk does see such a view where there is one
    assert _group_sized_minor_dims(_group_norm_5d)
