"""Character-level LSTM language model (the FedAvg-paper Shakespeare
workload).

The reference's model zoo is a single linear regressor (reference
demo.py:15-49); this model covers the *canonical* federated-learning
benchmark family the original FedAvg paper established — a stacked
character LSTM where each client is one Shakespeare speaking role — so
users of classic FL baselines find their workload here.

TPU-first construction:

* The recurrence is a single ``lax.scan`` over time carrying ``(h, c)``
  for all layers — one compiled loop, no Python timestep unrolling, and
  the whole multi-epoch local-training run still fuses into the
  framework's scan-of-scans (core/training.py).
* Each step's gate computation is ONE ``[B, E+H] @ [E+H, 4H]`` matmul
  per layer (inputs and hidden concatenated, all four gates fused), the
  layout XLA tiles best on the MXU — not four separate small matmuls.
* Params are fp32; activations run in ``compute_dtype`` with the cell
  state kept fp32 (the additive ``c`` path is where bf16 error
  accumulates over long sequences); gate nonlinearities in fp32.
* Forget-gate bias initialized to 1.0 (the standard trick so gradients
  flow through the cell path at init).

Batches: ``{"x": int32[B, L] chars, "y": int32[B, L] next chars,
"loss_mask"?: [B, L]}`` — the same contract as the decoder LM
(models/llama.py), so partitioners/recipes compose unchanged.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from baton_tpu.core.model import FedModel
from baton_tpu.models.transformer import (
    dense_init,
    normal_init,
    matmul,
    next_token_loss,
)


@dataclasses.dataclass(frozen=True)
class LSTMConfig:
    vocab_size: int = 90      # printable-ASCII Shakespeare alphabet
    d_embed: int = 8          # FedAvg-paper char embedding is tiny
    d_hidden: int = 256
    n_layers: int = 2

    @classmethod
    def shakespeare(cls, **kw) -> "LSTMConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "LSTMConfig":
        """Test-sized config (CI / CPU-mesh tests)."""
        defaults = dict(vocab_size=32, d_embed=4, d_hidden=16, n_layers=2)
        defaults.update(kw)
        return cls(**defaults)


def _cell_init(key, d_in: int, d_hidden: int):
    # one fused kernel for all four gates: [d_in + d_hidden, 4*d_hidden]
    bias = jnp.zeros((4 * d_hidden,), jnp.float32)
    bias = bias.at[d_hidden:2 * d_hidden].set(1.0)  # forget gate
    return {
        "kernel": dense_init(key, d_in + d_hidden, 4 * d_hidden),
        "bias": bias,
    }


def _cell_step(p, x, h, c, compute_dtype):
    """One LSTM step: x [B, d_in], h [B, H], c fp32 [B, H]."""
    z = jnp.concatenate([x, h], axis=-1) @ p["kernel"].astype(x.dtype)
    z = z.astype(jnp.float32) + p["bias"]
    i, f, g, o = jnp.split(z, 4, axis=-1)
    c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h = (jax.nn.sigmoid(o) * jnp.tanh(c)).astype(compute_dtype)
    return h, c


def lstm_lm_model(
    config: Optional[LSTMConfig] = None,
    compute_dtype=jnp.float32,
    name: str = "lstm_lm",
) -> FedModel:
    cfg = config or LSTMConfig.shakespeare()

    def init(rng):
        keys = jax.random.split(rng, cfg.n_layers + 2)
        layers = []
        d_in = cfg.d_embed
        for i in range(cfg.n_layers):
            layers.append(_cell_init(keys[1 + i], d_in, cfg.d_hidden))
            d_in = cfg.d_hidden
        return {
            "embed": normal_init(keys[0], (cfg.vocab_size, cfg.d_embed), 0.1),
            "layers": layers,
            "out": dense_init(keys[-1], cfg.d_hidden, cfg.vocab_size),
        }

    def _hidden(params, batch):
        """The top layer's hidden states [B, L, H]."""
        ids = batch["x"]
        b, l = ids.shape
        x = params["embed"][ids].astype(compute_dtype)  # [B, L, E]

        h0 = jnp.zeros((cfg.n_layers, b, cfg.d_hidden), compute_dtype)
        c0 = jnp.zeros((cfg.n_layers, b, cfg.d_hidden), jnp.float32)

        def step(carry, x_t):
            h, c = carry
            inp = x_t
            hs, cs = [], []
            for i, layer in enumerate(params["layers"]):
                h_i, c_i = _cell_step(layer, inp, h[i], c[i], compute_dtype)
                hs.append(h_i)
                cs.append(c_i)
                inp = h_i
            return (jnp.stack(hs), jnp.stack(cs)), inp

        # scan over time: xs [L, B, E] -> top-layer hiddens [L, B, H]
        _, top = jax.lax.scan(step, (h0, c0), x.swapaxes(0, 1))
        return top.swapaxes(0, 1)  # [B, L, H]

    def apply(params, batch, rng):
        """Next-char logits fp32 [B, L, V]."""
        return matmul(_hidden(params, batch), params["out"], jnp.float32)

    def per_example_loss(params, batch, rng):
        tok_loss = next_token_loss(_hidden(params, batch), params["out"],
                                   batch["y"])  # [B, L]
        loss_mask = batch.get("loss_mask")
        if loss_mask is None:
            return jnp.mean(tok_loss, axis=-1)
        m = loss_mask.astype(jnp.float32)
        return jnp.sum(tok_loss * m, axis=-1) / jnp.maximum(
            jnp.sum(m, axis=-1), 1.0
        )

    return FedModel(init=init, apply=apply, per_example_loss=per_example_loss,
                    name=name, aux=cfg)
