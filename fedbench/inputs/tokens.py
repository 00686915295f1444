"""Token sequences with a class label: ``{"kind": "tokens", "vocab": v,
"n_classes": k}``. Uniform token ids of the cell's ``seq_len``; the
label is the first token's id modulo the class count, a fixed function
of the input that a first-token-pooled encoder can learn. Rows past a
client's ``n_samples`` are zero. One jitted call on the device."""

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _tokens(vocab, n_classes, seq_len, n_clients, capacity, n_samples, key):
    x = jax.random.randint(key, (n_clients, capacity, seq_len), 0, vocab,
                           jnp.int32)
    real = jnp.arange(capacity)[None, :] < n_samples[:, None]
    x = jnp.where(real[..., None], x, 0)
    return {"x": x, "y": (x[..., 0] % n_classes).astype(jnp.int32)}


def make(spec, n_clients, capacity, seq_len, n_samples, key):
    return _tokens(int(spec["vocab"]), int(spec["n_classes"]), int(seq_len),
                   n_clients, capacity, n_samples, key)
