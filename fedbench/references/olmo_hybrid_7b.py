"""The plain reference of ``olmo_hybrid_7b``: a stage of Olmo-Hybrid-7B
(``layer_types``: three gated delta-rule layers, then one of full
attention) as a frozen base with low-rank adapters, in float32
``jax.numpy`` at ``precision="highest"`` over the program's parameter
tree ``{"base": ..., "lora": {path: {"a", "b"}}}``.

Every block is ``x + mixer(RMSNorm(x))`` then ``x + MLP(RMSNorm(x))``,
the MLP ``W_down(SiLU(W_gate x) * W_up x)``. With ``x`` the normalised
input, a linear-attention layer computes per head (30 of key size 96 and
value size 192), token by token::

    q, k, v = SiLU(conv4(W_q x)), SiLU(conv4(W_k x)), SiLU(conv4(W_v x))
    q, k    = q / |q| / sqrt(d_k),  k / |k|
    beta    = 2 sigmoid(W_b x),  alpha = exp(-exp(A_log) softplus(W_a x + dt_bias))
    S_t     = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,   S_0 = 0
    o_t     = S_t q_t
    y       = W_o [RMSNorm_head(o_t) * SiLU(W_g x)]

as one ``lax.scan`` over the positions of those lines (Yang, Kautz,
Hatamizadeh, arXiv:2412.06464 equation 10 with Grazzi et al.'s,
arXiv:2411.12537, factor 2 on ``beta``), which shares nothing with the
program's chunked form. A full-attention layer is causal softmax
attention over 30 heads of 128, no bias, no rotary embedding, the whole
query and key projections RMS-normalised before the split into heads.
A projection with an adapter is ``x W + s (x A) B``. The loss is the
masked mean next-token cross-entropy, head and loss in blocks of
tokens.

Departures from the published description, each also under ``assumed``
in ``fedbench/configs/olmo_hybrid_7b.json``: the norms stand before
each sub-layer (the family's OLMo 2 normalises a sub-layer's output),
and what the config does not give (the convolutions without bias, the
output norm's scale one vector for all heads, eps 1e-6 in the l2
normalisation) follows the paper's public code. Each frozen weight is
cast to float32 where it is used, a layer and a block of the loss are
under ``jax.checkpoint`` (no arithmetic changes; a float32 copy of the
bfloat16 base, forward and backward, does not fit a chip beside it).
Imports nothing of ``baton_tpu``; no ``vmap``, no ``custom_vjp`` or
``custom_jvp`` (SiLU, sigmoid, softplus and softmax are written out),
no kernel.
"""

import math

import jax
import jax.numpy as jnp

LOSS_BLOCK = 256  # tokens whose float32 logits are held at a time
F32 = jnp.float32


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _conv4_silu(x, taps):
    """Causal depthwise convolution over positions: ``x [n, l, ch]``,
    ``taps [4, ch]``, the last tap on the current token."""
    taps = taps.astype(F32)
    n_taps, l = taps.shape[0], x.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((x.shape[0], n_taps - 1, x.shape[2]), F32), x], axis=1)
    return _silu(sum(padded[:, j:j + l] * taps[j] for j in range(n_taps)))


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product (the identity, or
    the control's rounding: ``fedbench/reference.py::rounded_to``)."""
    layers = config["num_hidden_layers"]
    kinds = config["layer_types"][:layers]
    heads = config["num_attention_heads"]
    lin_heads = config["linear_num_value_heads"]
    d_k, d_v = config["linear_key_head_dim"], config["linear_value_head_dim"]
    eps = config["rms_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    neg = 2.0 if config["linear_allow_neg_eigval"] else 1.0

    def _mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision="highest")

    def _ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision="highest")

    def projector(weights, lora, prefix, x):
        """``name -> x W + s (x A) B`` (the adapter where ``lora`` has
        one for ``<prefix>/<name>``)."""
        def proj(name, inp=x):
            y = _mm(inp, weights[name].astype(F32))
            ab = lora.get(f"{prefix}/{name}")
            if ab is not None:
                y = y + scale * _mm(_mm(inp, ab["a"]), ab["b"])
            return y
        return proj

    def linear_attention(p, lora, prefix, x):
        n, l, _ = x.shape
        proj = projector(p, lora, prefix, x)

        def heads_of(name, conv, d):
            return _conv4_silu(proj(name), p[conv]).reshape(n, l, lin_heads, d)

        q, k = heads_of("wq", "conv_q", d_k), heads_of("wk", "conv_k", d_k)
        v = heads_of("wv", "conv_v", d_v)
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            / math.sqrt(d_k)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        beta = neg * _sigmoid(proj("wb"))
        alpha = jnp.exp(-jnp.exp(p["a_log"].astype(F32)) * _softplus(
            proj("wa") + p["dt_bias"].astype(F32)))

        def token(state, at):  # state [n, heads, d_v, d_k]
            q_t, k_t, v_t, a_t, b_t = at
            a_t, b_t = a_t[..., None, None], b_t[..., None, None]
            seen = _ein("nhvk,nhk->nhv", state, k_t)
            state = a_t * (state - b_t * seen[..., :, None] * k_t[..., None, :]) \
                + b_t * v_t[..., :, None] * k_t[..., None, :]
            return state, _ein("nhvk,nhk->nhv", state, q_t)

        by_position = tuple(jnp.moveaxis(a, 1, 0)
                            for a in (q, k, v, alpha, beta))
        _, o = jax.lax.scan(token, jnp.zeros((n, lin_heads, d_v, d_k), F32),
                            by_position)
        o = _rms_norm(jnp.moveaxis(o, 0, 1), p["norm_o"], eps)
        gated = o.reshape(n, l, lin_heads * d_v) * _silu(proj("wg"))
        return proj("wo", gated)

    def full_attention(p, lora, prefix, x):
        n, l, d = x.shape
        proj = projector(p, lora, prefix, x)

        def split(y):
            return y.reshape(n, l, heads, -1).transpose(0, 2, 1, 3)

        q = split(_rms_norm(proj("wq"), p["q_norm"]["scale"], eps))
        k = split(_rms_norm(proj("wk"), p["k_norm"]["scale"], eps))
        v = split(proj("wv"))
        scores = _ein("nhqd,nhkd->nhqk", q, k) / math.sqrt(q.shape[-1])
        causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
        scores = jnp.where(causal, scores, -1e30)
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        out = _ein("nhqk,nhkd->nhqd", weights, v)
        return proj("wo", out.transpose(0, 2, 1, 3).reshape(n, l, -1))

    def mlp(p, lora, prefix, x):
        proj = projector(p, lora, prefix, x)
        return proj("w_down", _silu(proj("w_gate")) * proj("w_up"))

    def block(kind, index):
        mixer, name = ((linear_attention, "linear_attn")
                       if kind == "linear_attention"
                       else (full_attention, "attn"))

        def apply(p, lora, x):
            prefix = f"blocks/{index}"
            x = x + mixer(p[name], lora, f"{prefix}/{name}",
                          _rms_norm(x, p["norm_attn"]["scale"], eps))
            return x + mlp(p["mlp"], lora, f"{prefix}/mlp",
                           _rms_norm(x, p["norm_mlp"]["scale"], eps))

        return jax.checkpoint(apply)

    blocks = [block(kind, i) for i, kind in enumerate(kinds)]

    @jax.checkpoint
    def token_losses(head, x, y):
        logits = _mm(x, head.astype(F32))
        top = jnp.max(logits, axis=-1, keepdims=True)
        logz = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
        return logz - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]

    def loss(params, x, y, mask):
        base, lora = params["base"], params["lora"]
        h = base["tok_emb"][x].astype(F32)
        for apply, p in zip(blocks, base["blocks"]):
            h = apply(p, lora, h)
        h = _rms_norm(h, base["norm_f"]["scale"], eps)
        l = x.shape[1]
        per_token = jnp.concatenate(
            [token_losses(base["lm_head"], h[:, s:s + LOSS_BLOCK],
                          y[:, s:s + LOSS_BLOCK])
             for s in range(0, l, LOSS_BLOCK)], axis=1)
        return jnp.sum(jnp.mean(per_token, axis=1) * mask) / jnp.sum(mask)

    return loss
