"""The plain reference of ``mellum2_12b``: the first pipeline stage of
Mellum2-12B-A2.5B (whole periods of three windowed layers and one full
layer, every layer's feed-forward 64 small experts of which a token
takes 8), a frozen base with low-rank adapters, in float32 ``jax.numpy``
at ``precision="highest"`` over the program's parameter tree ``{"base":
..., "lora": {path: {"a", "b"}}}``.

Every block is ``x + mixer(RMSNorm(x))`` then ``x + experts(RMSNorm(x))``.
With ``h`` the normalised input, both kinds of mixer are::

    q = h W_q   [L, 32, 128]      k = h W_k, v = h W_v   [L, 4, 128]
    q, k turned over the whole head, channel i paired with i + 64
    o = softmax(q k^T / sqrt(128) over the keys a query sees) v
    y = o W_o                     query head i on key-value head i // 8

A **sliding** layer's query at position ``t`` sees the keys ``s`` with
``0 <= t - s < sliding_window`` (itself and the 1,023 before it) and its
angles are ``t * 500000 ** (-2i / 128)``. A **full** layer's query sees
every ``s <= t``; its frequencies are ``yarn``'s (each channel a blend of
its plain frequency and the same over ``factor``, by a linear ramp
between the channels that turn ``beta_fast`` and ``beta_slow`` times
over the original 8,192 positions) and its ``cos`` and ``sin`` are
multiplied by ``attention_factor`` at every length. Both are read from
the published ``rope_parameters``, by the layer's kind. The scores are
made a block of ``QUERY_BLOCK`` queries at a time against every key, one
block after the other (``lax.map``), and what a query does not see is
masked.

The expert layer: ``z = h W_r`` (64 logits); the 8 largest are chosen
(found by counting, an equal logit to the lower index); their weights
are ``exp(z_e) / sum over the chosen of exp(z)`` (``norm_topk_prob``: a
softmax over all 64 renormalised over the chosen is the same numbers);
``y = sum over the chosen of w_e W_down,e (silu(h W_gate,e) * h
W_up,e)``: a loop (``lax.scan``) over the held experts, each computing
every token, masked by ``w``. A choice that falls on an expert held
elsewhere (``first_expert_held``, the experts' leading axis) adds
nothing. A projection with an adapter is ``x W + s (x A) B``. The loss
is the masked mean next-token cross-entropy, head and loss in blocks of
tokens.

What the config.json leaves open is under ``assumed`` in
``fedbench/configs/mellum2_12b.json``. Each frozen weight is cast to
float32 where it is used; a layer, one expert of it, a block of queries
and a block of the loss are under ``jax.checkpoint`` (no arithmetic
changes: a client of 8,192 tokens then fits beside the bfloat16 base).
Imports nothing of ``baton_tpu``; no ``vmap``, no ``custom_vjp`` or
``custom_jvp`` (SiLU and both softmaxes are written out), no grouped
product, no sort, no kernel.
"""

import math

import jax
import jax.numpy as jnp

LOSS_BLOCK = 256   # tokens whose float32 logits are held at a time
QUERY_BLOCK = 512  # queries whose [32, block, L] scores are held at a time
F32 = jnp.float32


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def rotary_table(length, dim, group):
    """``(cos, sin)``, each ``[length, dim / 2]``, of one kind of layer
    from its group of the published ``rope_parameters``: ``default`` is
    ``t * theta ** (-2i / dim)``; ``yarn`` blends each channel's plain
    frequency with the same over ``factor`` and multiplies ``cos`` and
    ``sin`` by ``attention_factor``."""
    theta = float(group["rope_theta"])
    plain = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    by = 1.0
    if group["rope_type"] == "yarn":
        original = group["original_max_position_embeddings"]

        def channel_turning(turns):
            return dim * math.log(original / (turns * 2 * math.pi)) \
                / (2 * math.log(theta))

        low = max(math.floor(channel_turning(group["beta_fast"])), 0)
        high = min(math.ceil(channel_turning(group["beta_slow"])), dim - 1)
        slowed = [min(max((i - low) / max(high - low, 0.001), 0.0), 1.0)
                  for i in range(dim // 2)]
        plain = [f * (1.0 - s) + f / group["factor"] * s
                 for f, s in zip(plain, slowed)]
        by = group["attention_factor"]
    elif group["rope_type"] != "default":
        raise ValueError(f"unknown rope_type {group['rope_type']!r}")
    angle = jnp.arange(length, dtype=F32)[:, None] * jnp.asarray(plain, F32)
    return by * jnp.cos(angle), by * jnp.sin(angle)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product (the identity, or
    the control's rounding: ``fedbench/reference.py::rounded_to``)."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    group = hq // hkv
    window = config["sliding_window"]
    eps = config["rms_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    top_k = config["num_experts_per_tok"]
    first = config.get("first_expert_held", 0)
    kinds = config["layer_types"][:config["num_hidden_layers"]]

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

    def turned(x, cos, sin):
        """``x [n, heads, l, d]`` turned by its positions' angles,
        channel ``i`` paired with ``i + d / 2``."""
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

    def attended(sliding):
        @jax.checkpoint
        def block(q, k, v, start):
            """Queries ``q [n, hkv, group, block, d]`` from position
            ``start`` on against every key ``k, v [n, hkv, l, d]``."""
            scores = _ein("nhgqd,nhkd->nhgqk", q, k) * d ** -0.5
            t = (start + jnp.arange(q.shape[3]))[:, None]
            s = jnp.arange(k.shape[2])[None, :]
            seen = s <= t
            if sliding:
                seen = seen & (t - s < window)
            scores = jnp.where(seen, scores, -1e30)
            weights = jnp.exp(scores - jnp.max(scores, axis=-1,
                                               keepdims=True))
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            return _ein("nhgqk,nhkd->nhgqd", weights, v)

        return block

    def attention(kind):
        sliding = kind == "sliding_attention"
        if not sliding and kind != "full_attention":
            raise ValueError(f"unknown layer type {kind!r}")
        core = attended(sliding)

        def apply(p, lora, prefix, h):
            n, l, _ = h.shape
            proj = projector(p, lora, prefix, h)
            cos, sin = rotary_table(l, d, config["rope_parameters"][kind])

            def split(y, heads):
                return y.reshape(n, l, heads, d).transpose(0, 2, 1, 3)

            q = turned(split(proj("wq"), hq), cos, sin).reshape(
                n, hkv, group, l, d)
            k = turned(split(proj("wk"), hkv), cos, sin)
            v = split(proj("wv"), hkv)
            # one block of queries after the other (a loop the compiler
            # may not run side by side: 16 blocks' scores at once are
            # 8 GiB); a length the block does not divide is one block
            block = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
            blocks = jnp.moveaxis(
                q.reshape(n, hkv, group, l // block, block, d), 3, 0)
            out = jax.lax.map(
                lambda one: core(one[0], k, v, one[1]),
                (blocks, jnp.arange(0, l, block)))
            out = jnp.moveaxis(out, 0, 3)
            out = out.reshape(n, hq, l, d).transpose(0, 2, 1, 3)
            return proj("wo", out.reshape(n, l, hq * d))

        return apply

    @jax.checkpoint
    def one_expert(w_gate, w_up, w_down, w_e, x):
        """Every token through one expert, weighted by the token's
        weight for it ``w_e [n, l]`` (zero where it was not chosen)."""
        return w_e[..., None] * _mm(
            _silu(_mm(x, w_gate.astype(F32))) * _mm(x, w_up.astype(F32)),
            w_down.astype(F32))

    def expert_layer(p, x):
        z = _mm(x, p["router"])
        # an expert is chosen where fewer than top_k others score higher
        # (an equal logit counts for the one of lower index, as a stable
        # sort would have it)
        index = jnp.arange(z.shape[-1])
        higher = (z[..., None, :] > z[..., :, None]) | (
            (z[..., None, :] == z[..., :, None])
            & (index[None, :] < index[:, None]))
        chosen = jnp.sum(higher, axis=-1) < top_k
        e = jnp.where(chosen, jnp.exp(z - jnp.max(z, axis=-1, keepdims=True)),
                      0.0)
        w = e / jnp.sum(e, axis=-1, keepdims=True)
        held = p["w_gate"].shape[0]
        w_held = jnp.moveaxis(w[..., first:first + held], -1, 0)

        def add_one(y, one):
            return y + one_expert(*one, x), None

        y, _ = jax.lax.scan(add_one, jnp.zeros_like(x),
                            (p["w_gate"], p["w_up"], p["w_down"], w_held))
        return y

    def block(index):
        mixer = attention(kinds[index])
        key = {"sliding_attention": "sliding_attn",
               "full_attention": "attn"}[kinds[index]]

        def apply(p, lora, x):
            prefix = f"blocks/{index}"
            x = x + mixer(p[key], lora, f"{prefix}/{key}",
                          _rms_norm(x, p["norm_attn"]["scale"], eps))
            return x + expert_layer(
                p["mlp"], _rms_norm(x, p["norm_mlp"]["scale"], eps))

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
