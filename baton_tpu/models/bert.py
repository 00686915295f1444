"""BERT-style text encoder/classifier (BASELINE config 3: AG-News FedProx).

The reference has no NLP models at all (its model zoo is one linear layer,
reference demo.py:15-49); this encoder exists for the driver-set federated
fine-tune workloads. TPU-first choices:

* **Pre-LN** blocks (norm before attn/MLP) + a final LayerNorm: unlike
  the original post-LN BERT this trains stably without LR warmup games —
  important when thousands of simulated clients each run short local
  schedules from a common init.
* Learned absolute position embeddings, single segment (no token-type
  table; AG-News classification is single-sequence).
* First-token ("[CLS]") pooling through a tanh pooler head.
* Padding handled as an additive attention bias built from
  ``batch["attn_mask"]`` ([B, L], 1 = real token); absent mask = all real.

Batches: ``{"x": int32[B, L], "attn_mask"?: [B, L], "y": int32[B]}``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from baton_tpu.core.losses import softmax_cross_entropy
from baton_tpu.core.model import FedModel
from baton_tpu.models.transformer import (
    AttentionFn,
    dense_init,
    default_attention,
    layer_norm,
    ln_init,
    normal_init,
    padding_bias,
    prenorm_block_apply,
    prenorm_block_init,
)


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_len: int = 128
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    n_classes: int = 4  # AG-News

    @classmethod
    def base(cls, **kw) -> "BertConfig":
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw) -> "BertConfig":
        """Test-sized config (CI / CPU-mesh tests)."""
        defaults = dict(
            vocab_size=128, max_len=16, d_model=32, n_layers=2, n_heads=4,
            d_ff=64, n_classes=4,
        )
        defaults.update(kw)
        return cls(**defaults)


def bert_classifier_model(
    config: Optional[BertConfig] = None,
    compute_dtype=jnp.float32,
    attention_fn: AttentionFn = default_attention,
    name: str = "bert_classifier",
    remat: bool = False,
) -> FedModel:
    """``remat=True`` wraps each encoder block in ``jax.checkpoint`` —
    the backward pass recomputes block activations instead of storing
    them, the same HBM/FLOPs trade the Llama decoder makes
    (models/llama.py::llama_lm_model). Long-sequence FedProx fine-tunes
    (config 3) use it to fit larger cohorts per wave."""
    cfg = config or BertConfig.base()

    def init(rng):
        keys = jax.random.split(rng, cfg.n_layers + 4)
        params = {
            "tok_emb": normal_init(keys[0], (cfg.vocab_size, cfg.d_model), 0.02),
            "pos_emb": normal_init(keys[1], (cfg.max_len, cfg.d_model), 0.02),
            "blocks": [
                prenorm_block_init(keys[2 + i], cfg.d_model, cfg.n_heads, cfg.d_ff)
                for i in range(cfg.n_layers)
            ],
            "ln_f": ln_init(cfg.d_model),
            "pooler": {
                "w": dense_init(keys[-2], cfg.d_model, cfg.d_model),
                "b": jnp.zeros((cfg.d_model,), jnp.float32),
            },
            "head": {
                "w": dense_init(keys[-1], cfg.d_model, cfg.n_classes),
                "b": jnp.zeros((cfg.n_classes,), jnp.float32),
            },
        }
        return params

    def apply(params, batch, rng):
        ids = batch["x"]
        b, l = ids.shape
        with jax.named_scope("embed"):
            x = params["tok_emb"][ids] + params["pos_emb"][:l]
            x = x.astype(compute_dtype)
        attn_mask = batch.get("attn_mask")
        bias = None if attn_mask is None else padding_bias(attn_mask)

        def _block(blk, x, bias):
            return prenorm_block_apply(blk, x, cfg.n_heads, bias=bias,
                                       attention_fn=attention_fn)

        block_fn = jax.checkpoint(_block) if remat else _block
        for i, blk in enumerate(params["blocks"]):
            with jax.named_scope(f"block{i}"):
                x = block_fn(blk, x, bias)
        with jax.named_scope("head"):
            x = layer_norm(x, params["ln_f"])
            cls = x[:, 0, :].astype(jnp.float32)
            pooled = jnp.tanh(
                cls @ params["pooler"]["w"] + params["pooler"]["b"])
            return pooled @ params["head"]["w"] + params["head"]["b"]

    def per_example_loss(params, batch, rng):
        return softmax_cross_entropy(apply(params, batch, rng), batch, rng)

    return FedModel(init=init, apply=apply, per_example_loss=per_example_loss,
                    name=name, aux=cfg)
