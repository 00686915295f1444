"""Next-token inputs over the whole vocabulary: ``{"kind": "causal_lm",
"vocab": v, "stride": s}``. A sequence starts at a uniform token id and
steps by ``s`` modulo the vocabulary; the labels ``y [C, capacity, l]``
are the inputs shifted by one position, so the next token is a fixed
function of the current one and a falling loss can be required. Rows
past a client's ``n_samples`` are zero. One jitted call on the device
(the generator of ``tests/fedbench/fixtures/next_token``)."""

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _sequences(vocab, stride, seq_len, n_clients, capacity, n_samples, key):
    first = jax.random.randint(key, (n_clients, capacity, 1), 0, vocab,
                               jnp.int32)
    tokens = (first + stride * jnp.arange(seq_len + 1)) % vocab
    real = (jnp.arange(capacity)[None, :] < n_samples[:, None])[..., None]
    tokens = jnp.where(real, tokens, 0)
    return {"x": tokens[..., :-1], "y": tokens[..., 1:]}


def make(spec, n_clients, capacity, seq_len, n_samples, key):
    return _sequences(int(spec["vocab"]), int(spec["stride"]), int(seq_len),
                      n_clients, capacity, n_samples, key)
