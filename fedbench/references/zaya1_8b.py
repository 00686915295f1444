"""The plain reference of ``zaya1_8b``: the first pipeline stage of
ZAYA1-8B (ten blocks, each compressed convolutional attention and an
expert layer that holds all 16 experts), a frozen base with low-rank
adapters, in float32 ``jax.numpy`` at ``precision="highest"`` over the
program's parameter tree ``{"base": ..., "lora": {path: {"a", "b"}}}``.

A block, with ``x [L, 2048]`` the stream and ``r`` the routers' state
(zeros into the first block), ``d = 128``, 8 query heads on 2 key
heads, ``g = 4`` query heads a key head::

    h  = RMSNorm(x)
    x1 = a_x (x  + b_x) + a_y (CCA(h) + b_y)         merge_attn, a channel
    h  = RMSNorm(x1)
    m, r' = MoE(h, r)
    x2 = a_x (x1 + b_x) + a_y (m      + b_y)         merge_mlp

*Compressed convolutional attention* (Zyphra, arXiv:2510.04476)::

    q~ = h W_q  [L, 8, 128]      k~ = h W_k  [L, 2, 128]
    c  = conv1(conv0([q~ ; k~]))  over the 1,280 joined channels, two
         zeros before the sequence, neither convolution padded again:
         conv0 depthwise, 2 taps, bias; conv1 ten groups (a head, 128 ->
         128), 2 taps, bias; both through lax.conv_general_dilated
    q^, k^ = split(c)
    q  = q^ + (q~ + repeat_g(k~)) / 2
    k  = k^ + (mean over each group of g query heads of q~ + k~) / 2
    v  = [h_t W_v1 ; h_{t-1} W_v2]    key head 0 the token's own values,
                                      head 1 the token before's, h_{-1} = 0
    q  = sqrt(d) q / |q|,  k = temp_j sqrt(d) k / |k|     a head
    the first 64 channels of each head turned (theta 5e6, i with i + 32)
    o  = softmax_causal(q k^T / sqrt(d)) v,  query head i on key head i // g
    CCA(h) = o W_o                    [1024 -> 2048]

over a block of ``QUERY_BLOCK`` queries at a time against the whole
sequence, masked (a block is under ``jax.checkpoint``: at 8,192 tokens
the ``[8, L, L]`` scores whole are 2 GiB).

*Expert layer* (ZAYA1's router, arXiv:2511.17127)::

    r'     = h W_in + b_in + state_scale * r                 [L, 256]
    logits = W3 gelu(W2 gelu(W1 RMSNorm(r') + b1) + b2)      17 outputs
    p      = softmax(logits)
    e      = argmax(p + router_bias)        the first of equal ones
    m      = p_e E_e(h) where e < 16, else 0       (the 17th is the skip)
    E(h)   = (SiLU(h w_gate) * (h w_up)) w_down

a Python loop over the 16 experts, each computing every token, masked
by an explicit ``where`` on the choice. A projection with an adapter is
``x W + s (x A) B``. The head is the embedding table itself,
``RMSNorm(x) E^T``; the loss is the masked mean next-token cross-entropy
over the held slice of the vocabulary, head and loss in blocks of
tokens.

What the config.json leaves open is under ``assumed`` in
``fedbench/configs/zaya1_8b.json``. Each frozen weight is cast to
float32 where it is used; a block, one expert of it, a block of
queries and a block of the loss are under ``jax.checkpoint`` (no
arithmetic changes). Imports nothing of ``baton_tpu``; no ``vmap``, no
``custom_vjp`` or ``custom_jvp`` (SiLU, GELU, sigmoid and softmax are
written out), no grouped product, no sort, no kernel, and the
convolutions are convolutions, not the program's slices and products.
"""

import jax
import jax.numpy as jnp

LOSS_BLOCK = 256   # tokens whose float32 logits are held at a time
QUERY_BLOCK = 1024  # queries whose [8, block, L] scores are held at a time
F32 = jnp.float32


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x * 2.0 ** -0.5))


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product and convolution
    (the identity, or the control's rounding:
    ``fedbench/reference.py::rounded_to``)."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    group = hq // hkv
    rot = int(d * config["partial_rotary_factor"])
    theta = config["rope_theta"]
    eps = config["rms_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    n_experts = config["num_experts"]
    frequencies = theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)

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

    def convolved(p, u):
        """``u [n, l, C]`` through the two causal convolutions: zeros
        before the sequence for both, then each unpadded."""
        t0, t1 = p["conv0_w"].shape[0], p["conv1_w"].shape[0]
        c = u.shape[-1]
        u = jnp.pad(u, ((0, 0), (t0 + t1 - 2, 0), (0, 0)))
        # depthwise: a kernel [taps, 1, C], C groups of one channel
        y = jax.lax.conv_general_dilated(
            cast(u), cast(p["conv0_w"].astype(F32)[:, None, :]), (1,),
            "VALID", dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=c, precision="highest") + p["conv0_b"]
        # a group a head: [taps, heads, d, d] -> [taps, d, heads * d]
        w1 = p["conv1_w"].astype(F32)
        taps, heads = w1.shape[:2]
        kernel = jnp.transpose(w1, (0, 2, 1, 3)).reshape(taps, d, heads * d)
        return jax.lax.conv_general_dilated(
            cast(y), cast(kernel), (1,), "VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=heads, precision="highest") + p["conv1_b"]

    def turned(x):
        """The first ``rot`` channels of ``x [n, heads, l, d]`` turned
        by their position's angles, channel ``i`` paired with ``i + rot
        / 2``."""
        angle = jnp.arange(x.shape[2], dtype=F32)[:, None] * frequencies
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2, kept = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, kept], axis=-1)

    def unit(x):
        return d ** 0.5 * x / jnp.sqrt(
            jnp.sum(x * x, axis=-1, keepdims=True) + d * 1e-12)

    @jax.checkpoint
    def attended(q, k, v, start):
        """Queries ``q [n, hkv, group, block, d]`` from position
        ``start`` on against every key ``k, v [n, hkv, l, d]``."""
        scores = _ein("nhgqd,nhkd->nhgqk", q, k) * d ** -0.5
        seen = (start + jnp.arange(q.shape[3]))[:, None] \
            >= jnp.arange(k.shape[2])[None, :]
        scores = jnp.where(seen, scores, -1e30)
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return _ein("nhgqk,nhkd->nhgqd", weights, v)

    def compressed_attention(p, lora, prefix, h):
        n, l, _ = h.shape
        proj = projector(p, lora, prefix, h)
        q_lat, k_lat = proj("linear_q"), proj("linear_k")
        mixed = convolved(p, jnp.concatenate([q_lat, k_lat], axis=-1))
        q_lat = q_lat.reshape(n, l, hkv, group, d)
        k_lat = k_lat.reshape(n, l, hkv, 1, d)
        q = mixed[..., :hq * d].reshape(n, l, hkv, group, d) \
            + 0.5 * (q_lat + k_lat)
        k = mixed[..., hq * d:].reshape(n, l, hkv, 1, d) \
            + 0.5 * (jnp.mean(q_lat, axis=3, keepdims=True) + k_lat)
        q = turned(unit(q).reshape(n, l, hq, d).transpose(0, 2, 1, 3))
        k = turned((unit(k) * p["temp"][:, None, None])
                   .reshape(n, l, hkv, d).transpose(0, 2, 1, 3))
        before = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :l]
        v = jnp.stack([proj("val_proj1"), proj("val_proj2", before)], axis=1)
        q = q.reshape(n, hkv, group, l, d)
        out = jnp.concatenate(
            [attended(q[:, :, :, s:s + QUERY_BLOCK], k, v, s)
             for s in range(0, l, QUERY_BLOCK)], axis=3)
        return proj("o_proj", out.reshape(n, hq, l, d).transpose(0, 2, 1, 3)
                    .reshape(n, l, hq * d))

    @jax.checkpoint
    def one_expert(w_gate, w_up, w_down, x):
        return _mm(_silu(_mm(x, w_gate.astype(F32)))
                   * _mm(x, w_up.astype(F32)), w_down.astype(F32))

    def expert_layer(p, h, state):
        r = p["router"]
        state = _mm(h, r["w_in"]) + r["b_in"] + r["state_scale"] * state
        z = _rms_norm(state, r["norm"], eps)
        z = _gelu(_mm(z, r["w1"]) + r["b1"])
        z = _gelu(_mm(z, r["w2"]) + r["b2"])
        logits = _mm(z, r["w3"])
        prob = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        prob = prob / jnp.sum(prob, axis=-1, keepdims=True)
        chosen = jnp.argmax(prob + p["router_bias"], axis=-1)
        weight = jnp.take_along_axis(prob, chosen[..., None], axis=-1)
        y = jnp.zeros_like(h)
        for e in range(n_experts):  # output n_experts is the skip: nothing
            y = y + jnp.where(
                (chosen == e)[..., None],
                weight * one_expert(p["w_gate"][e], p["w_up"][e],
                                    p["w_down"][e], h), 0.0)
        return y, state

    def merged(m, x, y):
        return m["a_x"] * (x + m["b_x"]) + m["a_y"] * (y + m["b_y"])

    def block(index):
        def apply(p, lora, x, state):
            x = merged(p["merge_attn"], x, compressed_attention(
                p["cca"], lora, f"blocks/{index}/cca",
                _rms_norm(x, p["norm_attn"]["scale"], eps)))
            y, state = expert_layer(
                p["mlp"], _rms_norm(x, p["norm_mlp"]["scale"], eps), state)
            return merged(p["merge_mlp"], x, y), state

        return jax.checkpoint(apply)

    blocks = [block(i) for i in range(config["num_hidden_layers"])]

    @jax.checkpoint
    def token_losses(table, x, y):
        logits = _ein("nld,vd->nlv", x, table.astype(F32))
        top = jnp.max(logits, axis=-1, keepdims=True)
        logz = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
        return logz - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]

    def loss(params, x, y, mask):
        base, lora = params["base"], params["lora"]
        h = base["tok_emb"][x].astype(F32)
        state = jnp.zeros(x.shape + (config["router_hidden_size"],), F32)
        for apply, p in zip(blocks, base["blocks"]):
            h, state = apply(p, lora, h, state)
        h = _rms_norm(h, base["norm_f"]["scale"], eps)
        l = x.shape[1]
        per_token = jnp.concatenate(
            [token_losses(base["tok_emb"], h[:, s:s + LOSS_BLOCK],
                          y[:, s:s + LOSS_BLOCK])
             for s in range(0, l, LOSS_BLOCK)], axis=1)
        return jnp.sum(jnp.mean(per_token, axis=1) * mask) / jnp.sum(mask)

    return loss
