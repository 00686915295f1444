"""The plain reference of ``bert_base``: a BERT-base encoder (Devlin et
al. 2018, section 3) with a sequence-classification head, as its
configuration file cuts it, in float32 ``jax.numpy`` over the program's
parameter tree.

Follows ``fedbench/configs/bert_base.json``'s ``reduced_why``: token plus
learned position embeddings (no segment table, no embedding LayerNorm,
no dropout); pre-LN blocks, ``x + attention(LN(x))`` then ``x +
MLP(LN(x))``, of softmax attention over ``num_attention_heads`` heads and
a GELU feed-forward; a final LayerNorm; a tanh pooler on the first token
and a linear head; the four attention projections carry no bias,
LayerNorm's eps is 1e-6 and GELU is its tanh approximation
(``attention_bias``, ``layer_norm_eps``, ``hidden_act`` there). Imports
nothing of ``baton_tpu``; no ``vmap``, no ``custom_vjp``, no kernel.
"""

import math

import jax.numpy as jnp

from fedbench.reference import masked_mean_cross_entropy


def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(p, x, heads, _mm):
    n, l, d = x.shape

    def split(w):  # [n, l, d] -> [n, heads, l, d / heads]
        return _mm(x, w).reshape(n, l, heads, d // heads).transpose(0, 2, 1, 3)

    q, k, v = split(p["wq"]), split(p["wk"]), split(p["wv"])
    scores = _mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d // heads)
    weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    out = _mm(weights / jnp.sum(weights, axis=-1, keepdims=True), v)
    return _mm(out.transpose(0, 2, 1, 3).reshape(n, l, d), p["wo"])


def _mlp(p, x, _mm):
    return _mm(_gelu(_mm(x, p["w1"]) + p["b1"]), p["w2"]) + p["b2"]


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``
    (the configuration file, ``tiny.sizes`` laid over it in a
    rehearsal): ``x [n, l]`` token ids, ``y [n]``, ``mask [n]``. ``cast``
    is applied to both operands of every matrix product: the identity,
    or the control's rounding to a lower precision
    (``fedbench/reference.py::rounded_to``)."""
    heads = config["num_attention_heads"]

    def _mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision="highest")

    def logits(params, ids):
        x = params["tok_emb"][ids] + params["pos_emb"][:ids.shape[1]]
        for p in params["blocks"]:
            x = x + _attention(p["attn"], _layer_norm(x, p["ln1"]), heads,
                               _mm)
            x = x + _mlp(p["mlp"], _layer_norm(x, p["ln2"]), _mm)
        first = _layer_norm(x, params["ln_f"])[:, 0, :]
        pooled = jnp.tanh(_mm(first, params["pooler"]["w"])
                          + params["pooler"]["b"])
        return _mm(pooled, params["head"]["w"]) + params["head"]["b"]

    def loss(params, x, y, mask):
        return masked_mean_cross_entropy(logits(params, x), y, mask)

    return loss
