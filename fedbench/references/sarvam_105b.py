"""The plain reference of ``sarvam_105b``: the first pipeline stage of
sarvam-105b (a leading dense layer, then expert layers) as one
expert-parallel rank holds it, a frozen base with low-rank adapters, in
float32 ``jax.numpy`` at ``precision="highest"`` over the program's
parameter tree ``{"base": ..., "lora": {path: {"a", "b"}}}``.

Every block is ``x + mixer(RMSNorm(x))`` then ``x + ff(RMSNorm(x))``.
The mixer is latent attention (DeepSeek-V2, arXiv:2405.04434 section
2.1), with ``h`` the normalised input::

    q            = h W_q                     64 heads of 192 = 128 | 64 rotary
    c            = h W_kv_a                  512 latent | 64 rotary key
    [k_nope | v] = RMSNorm(c[:512]) W_kv_b   64 heads of 128 + 128
    k            = k_nope | c[512:]          the rotary key shared by all heads
    q, k         = RMSNorm_192(q), RMSNorm_192(k)   (use_qk_norm), then the
                   rotation of the last 64 channels at deepseek_yarn's frequencies
    y            = W_o softmax(q k^T 192^-1/2 m^2, causal) v,   m = 0.1 ln 40 + 1

over whole heads: the ``[L, L]`` scores of every head at once. The
first layer's feed-forward is a SwiGLU of width 16,384. An expert layer
(DeepSeek-V3, arXiv:2412.19437 section 2.1) scores a token against all
128 experts, ``s = sigmoid(h W_r)``, chooses the 8 largest of ``s + b``
and weighs them ``g_i = 2.5 s_i / sum of the chosen s``; this rank
holds ``num_experts`` of them from ``first_expert_held`` on, and its
result is ``sum over chosen i held here of g_i E_i(h) + E_shared(h)``:
a Python loop over the held experts, each computing every token,
masked by ``g``. A choice that falls on an expert held elsewhere adds
nothing. A projection with an adapter is ``x W + s (x A) B``. The loss
is the masked mean next-token cross-entropy over the held slice of the
vocabulary, head and loss in blocks of tokens.

What the config.json leaves open is under ``assumed`` in
``fedbench/configs/sarvam_105b.json``. Each frozen weight is cast to
float32 where it is used; a layer, one expert of it and a block of the
loss are under ``jax.checkpoint`` (no arithmetic changes: a float32 copy
of one expert layer's stacks, forward and backward, does not fit a chip
beside the bfloat16 base). Imports nothing of ``baton_tpu``; no
``vmap``, no ``custom_vjp`` or ``custom_jvp`` (SiLU, sigmoid and softmax
are written out, the 8 largest are found by counting), no grouped
product, no kernel.
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


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def yarn_frequencies(dim, theta, scaling):
    """The ``dim / 2`` rotary frequencies of ``deepseek_yarn``: channel
    ``i`` turns at ``theta ** (-2i / dim)``; channels that turn more
    than ``beta_fast`` times over the original length keep that, those
    that turn fewer than ``beta_slow`` times are slowed by ``factor``,
    and between the two the blend is linear in ``i``."""
    original = scaling["original_max_position_embeddings"]

    def channel_turning(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(channel_turning(scaling["beta_fast"])), 0)
    high = min(math.ceil(channel_turning(scaling["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        slowed = min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
        out.append(plain * (1.0 - slowed) + plain / scaling["factor"] * slowed)
    return jnp.asarray(out, F32)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product (the identity, or
    the control's rounding: ``fedbench/reference.py::rounded_to``)."""
    heads = config["num_attention_heads"]
    rank = config["kv_lora_rank"]
    nope, rot, d_v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    eps = config["rms_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    dense_layers = config["first_k_dense_replace"]
    held, first = config["num_experts"], config["first_expert_held"]
    top_k = config["num_experts_per_tok"]
    routed_scale = config["routed_scaling_factor"]
    scaling = config["rope_scaling"]
    frequencies = yarn_frequencies(rot, config["rope_theta"], scaling)
    m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1.0
    softmax_scale = (nope + rot) ** -0.5 * m * m

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

    def rotate(x):
        """The last ``rot`` channels of ``x [n, heads, l, .]`` turned by
        their position's angles, channel ``i`` paired with ``i + rot /
        2``."""
        angle = jnp.arange(x.shape[2], dtype=F32)[:, None] * frequencies
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        kept, x1, x2 = (x[..., :nope], x[..., nope:nope + rot // 2],
                        x[..., nope + rot // 2:])
        return jnp.concatenate(
            [kept, x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def latent_attention(p, lora, prefix, x):
        n, l, _ = x.shape
        proj = projector(p, lora, prefix, x)

        def split(y):
            return y.reshape(n, l, heads, -1).transpose(0, 2, 1, 3)

        q = split(proj("wq"))
        c = proj("wkv_a")
        latent = _rms_norm(c[..., :rank], p["kv_norm"]["scale"], eps)
        kv = split(proj("wkv_b", latent))
        shared = jnp.broadcast_to(c[:, None, :, rank:], (n, heads, l, rot))
        k = jnp.concatenate([kv[..., :nope], shared], axis=-1)
        v = kv[..., nope:]
        if config["use_qk_norm"]:
            q = _rms_norm(q, p["q_norm"]["scale"], eps)
            k = _rms_norm(k, p["k_norm"]["scale"], eps)
        scores = _ein("nhqd,nhkd->nhqk", rotate(q), rotate(k)) * softmax_scale
        causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
        scores = jnp.where(causal, scores, -1e30)
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        out = _ein("nhqk,nhkd->nhqd", weights, v)
        return proj("wo", out.transpose(0, 2, 1, 3).reshape(n, l, -1))

    def mlp(p, lora, prefix, x):
        proj = projector(p, lora, prefix, x)
        return proj("w_down", _silu(proj("w_gate")) * proj("w_up"))

    @jax.checkpoint
    def one_expert(w_gate, w_up, w_down, x):
        return _mm(_silu(_mm(x, w_gate.astype(F32)))
                   * _mm(x, w_up.astype(F32)), w_down.astype(F32))

    def expert_layer(p, lora, prefix, x):
        s = _sigmoid(_mm(x, p["router"]))
        biased = s + p["router_bias"]
        # an expert is chosen where fewer than top_k others score higher
        # (an equal score counts for the one of lower index, as a
        # stable sort would have it)
        index = jnp.arange(s.shape[-1])
        higher = (biased[..., None, :] > biased[..., :, None]) | (
            (biased[..., None, :] == biased[..., :, None])
            & (index[None, :] < index[:, None]))
        chosen = jnp.sum(higher, axis=-1) < top_k
        g = routed_scale * jnp.where(chosen, s, 0.0) / jnp.sum(
            jnp.where(chosen, s, 0.0), axis=-1, keepdims=True)
        y = mlp(p["shared"], lora, f"{prefix}/shared", x)
        for e in range(held):
            y = y + g[..., first + e, None] * one_expert(
                p["w_gate"][e], p["w_up"][e], p["w_down"][e], x)
        return y

    def block(index):
        ff = mlp if index < dense_layers else expert_layer

        def apply(p, lora, x):
            prefix = f"blocks/{index}"
            x = x + latent_attention(
                p["mla"], lora, f"{prefix}/mla",
                _rms_norm(x, p["norm_attn"]["scale"], eps))
            return x + ff(p["mlp"], lora, f"{prefix}/mlp",
                          _rms_norm(x, p["norm_mlp"]["scale"], eps))

        return jax.checkpoint(apply)

    blocks = [block(i) for i in range(config["num_hidden_layers"])]

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
