"""Image inputs: ``{"kind": "image", "shape": [h, w, c], "n_classes":
k}``. Standard-normal pixels; the label is the argmax of a seeded random
projection of the pixels, a fixed function of the input that a
convolutional network can learn. Rows past a client's ``n_samples`` are
zero. One jitted call on the device."""

import math
from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _images(shape, n_classes, n_clients, capacity, n_samples, key):
    kx, kp = jax.random.split(key)
    x = jax.random.normal(kx, (n_clients, capacity) + shape, jnp.float32)
    proj = jax.random.normal(kp, (math.prod(shape), n_classes), jnp.float32)
    y = jnp.argmax(x.reshape(n_clients, capacity, -1) @ proj, axis=-1)
    real = jnp.arange(capacity)[None, :] < n_samples[:, None]
    x = jnp.where(real.reshape(real.shape + (1,) * len(shape)), x, 0.0)
    return {"x": x, "y": jnp.where(real, y, 0).astype(jnp.int32)}


def make(spec, n_clients, capacity, seq_len, n_samples, key):
    return _images(tuple(spec["shape"]), int(spec["n_classes"]), n_clients,
                   capacity, n_samples, key)
