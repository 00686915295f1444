"""The gated delta rule under ``FedSim``'s nesting at test sizes on the
CPU: the chunked form under a client ``vmap``, and a masked row of
zeros that costs a local step nothing. (Split from
``test_hybrid_decoder.py`` by mixer, PR 52, the functions as they were:
the shared inputs are ``_hybrid_decoder_shared.py``'s.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models.delta_rule import chunked_delta_rule
from baton_tpu.models.llama import decoder_lora_model

from _hybrid_decoder_shared import (
    _hybrid,
    _token_by_token,
    _scan_inputs,
    _close,
)


def test_chunked_delta_rule_under_a_client_vmap():
    """Vmapped over a client axis, values and gradients are each
    client's own."""
    args = _scan_inputs(3, (3, 2), 10)

    def loss(*a):
        return jnp.sum(jnp.sin(chunked_delta_rule(*a, 4)))

    with jax.default_matmul_precision("highest"):
        got_o = jax.vmap(lambda *a: chunked_delta_rule(*a, 4))(*args)
        got_g = jax.vmap(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)
        for c in range(3):
            own = tuple(a[c] for a in args)
            _close(got_o[c], _token_by_token(*own))
            want_g = jax.grad(
                lambda *a: jnp.sum(jnp.sin(_token_by_token(*a))),
                argnums=(0, 1, 2, 3, 4))(*own)
            for g, w in zip(got_g, want_g):
                _close(g[c], w)


def test_a_masked_row_of_zeros_costs_a_step_nothing():
    """A padded row (token 0 throughout, mask 0) gives a finite loss and
    leaves the step's gradient what the real rows alone give."""
    model = decoder_lora_model(_hybrid(), compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=2, b_std=0.02)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (3, 11), 1, 96)
    real = {"x": x[:, :-1], "y": x[:, 1:]}
    padded = {"x": jnp.concatenate([real["x"], jnp.zeros((1, 10), jnp.int32)]),
              "y": jnp.concatenate([real["y"], jnp.zeros((1, 10), jnp.int32)]),
              "mask": jnp.asarray([1.0, 1.0, 1.0, 0.0])}

    def grad(batch):
        return jax.value_and_grad(lambda lora: model.masked_loss(
            {"base": params["base"], "lora": lora}, batch, None))(
                params["lora"])

    (want, want_g), (got, got_g) = grad(real), grad(padded)
    assert np.isfinite(np.asarray(model.per_example_loss(
        params, padded, None))).all()
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        _close(g, w, rtol=1e-5)
