"""The plain reference of ``glm_5``: the first pipeline stage of GLM-5 (a
leading dense layer, then expert layers) as one expert-parallel rank
holds it, a frozen base with low-rank adapters, in float32
``jax.numpy`` at ``precision="highest"`` over the program's parameter
tree ``{"base": ..., "lora": {path: {"a", "b"}}}``.

Every block is ``x + mixer(RMSNorm(x))`` then ``x + ff(RMSNorm(x))``.
The mixer is latent attention with a compressed query (DeepSeek-V2,
arXiv:2405.04434 section 2.1) whose queries choose their keys
(DeepSeek-V3.2-Exp's sparse attention, equations 1 and 2), with ``h``
the normalised input::

    c_q          = RMSNorm(h W_qa)           2,048, the queries' latent
    q            = c_q W_qb                  64 heads of 256 = 192 | 64 rotary
    c            = h W_kv_a                  512 latent | 64 rotary key
    [k_nope | v] = RMSNorm(c[:512]) W_kv_b   64 heads of 192 + 256
    k            = k_nope | c[512:]          the rotary key shared by all heads
    q, k           the last 64 channels turned at theta ** (-2i / 64)

    q_I          = c_q W_Iq                  32 index heads of 128
    k_I          = LayerNorm(h W_Ik)         one index key of 128 a token
    w            = h W_Iw 32^-1/2 128^-1/2   a weight an index head
    q_I, k_I       the first 64 channels turned by the same angles
    I[t, s]      = sum_j w[t, j] max(q_I[t, j] . k_I[s], 0)      s <= t
    S_t          = the index_topk keys s <= t of largest I[t, s], equal
                   scores to the lower index; all of them while t + 1 <=
                   index_topk: ``jax.lax.top_k`` over a query's row
    y            = W_o softmax over s in S_t of (q k^T 256^-1/2) v

over whole heads, ``HEAD_GROUP`` of them at a time (a Python loop over
the groups: their columns of ``W_qb`` and ``W_kv_b``, their rows of
``W_o``, their parts summed), a block of ``QUERY_BLOCK`` queries at a
time against all the keys, in a ``lax.map`` over the blocks: the
``[heads, block, L]`` scores of the index and ``[group, block, L]`` of
the attention are what is held, no ``[64, L, L]``; four clients' probe
at 8,192 tokens has to fit a chip beside the bfloat16 base. One set
``S_t`` a query, a ``[L, L]`` bool, serves all 64 heads; it is made of
integers, so no gradient passes through the index. The first layer's feed-forward is a SwiGLU of
width 12,288. An expert layer (DeepSeek-V3, arXiv:2412.19437 section
2.1) scores a token against all 256 experts, ``s = sigmoid(h W_r)``,
chooses the 8 largest of ``s + b`` and weighs them ``g_i = 2.5 s_i / sum
of the chosen s``; this rank holds ``n_routed_experts`` of them from
``first_expert_held`` on, and its result is ``sum over chosen i held
here of g_i E_i(h) + E_shared(h)``: a Python loop over the held experts,
each computing every token, masked by ``g``. A choice that falls on an
expert held elsewhere adds nothing. A projection with an adapter is ``x
W + s (x A) B``; the indexer has none. The loss is the masked mean
next-token cross-entropy over the held slice of the vocabulary, head
and loss in blocks of tokens.

What the config.json leaves open is under ``assumed`` in
``fedbench/configs/glm_5.json``. Each frozen weight is cast to float32
where it is used; a layer, a block of its queries, one expert of it and
a block of the loss are under ``jax.checkpoint`` (no arithmetic
changes). Imports nothing of ``baton_tpu``; no ``vmap``, no
``custom_vjp`` or ``custom_jvp`` (SiLU, sigmoid, ReLU and softmax are
written out, the router's 8 largest are found by counting), no grouped
product, no kernel.
"""

import jax
import jax.numpy as jnp

LOSS_BLOCK = 256   # tokens whose float32 logits are held at a time
QUERY_BLOCK = 256  # queries whose scores against their keys are held
HEAD_GROUP = 16    # heads whose queries, keys and values are held
F32 = jnp.float32


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32) \
        + p["bias"].astype(F32)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product (the identity, or
    the control's rounding: ``fedbench/reference.py::rounded_to``)."""
    heads = config["num_attention_heads"]
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    nope, rot, d_v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    i_heads, i_dim, topk = (config["index_n_heads"], config["index_head_dim"],
                            config["index_topk"])
    eps = config["rms_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    dense_layers = config["first_k_dense_replace"]
    held, first = config["n_routed_experts"], config["first_expert_held"]
    top_k = config["num_experts_per_tok"]
    routed_scale = config["routed_scaling_factor"]
    theta = config["rope_parameters"]["rope_theta"]
    frequencies = jnp.asarray(
        [theta ** (-2.0 * i / rot) for i in range(rot // 2)], F32)
    softmax_scale = (nope + rot) ** -0.5

    def _mm(a, b):
        return jnp.matmul(cast(a), cast(b), precision="highest")

    def _ein(spec, a, b):
        return jnp.einsum(spec, cast(a), cast(b), precision="highest")

    def projector(weights, lora, prefix, x):
        """``name -> x W + s (x A) B`` (the adapter where ``lora`` has
        one for ``<prefix>/<name>``); with ``cols`` the columns of the
        result that belong to some heads, with ``rows`` the part of the
        result that comes from some heads' rows of ``W``."""
        def proj(name, inp=x, cols=slice(None), rows=slice(None)):
            y = _mm(inp, weights[name][rows, cols].astype(F32))
            ab = lora.get(f"{prefix}/{name}")
            if ab is not None:
                y = y + scale * _mm(_mm(inp, ab["a"][rows]), ab["b"][:, cols])
            return y
        return proj

    def turn(x, start):
        """Channels ``start`` to ``start + rot`` of ``x [..., l, .]``
        turned by their position's angles, channel ``i`` of them paired
        with ``i + rot / 2``."""
        angle = jnp.arange(x.shape[-2], dtype=F32)[:, None] * frequencies
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        half = start + rot // 2
        x1, x2 = x[..., start:half], x[..., half:start + rot]
        return jnp.concatenate(
            [x[..., :start], x1 * cos - x2 * sin, x2 * cos + x1 * sin,
             x[..., start + rot:]], axis=-1)

    def in_blocks(y, block):
        """``[n, h, l, ...] -> [l / block, n, h, block, ...]``."""
        l = y.shape[2]
        assert l % block == 0, (l, block)
        return jnp.moveaxis(y.reshape(
            y.shape[:2] + (l // block, block) + y.shape[3:]), 2, 0)

    def chosen_keys(ix, x, c_q):
        """``[n, l, l]`` bool: the keys each query chose, by the index
        scores of a block of queries against all the keys and
        ``jax.lax.top_k`` over each query's row."""
        n, l, _ = x.shape
        q_i = turn(_mm(c_q, ix["wq"].astype(F32)).reshape(
            n, l, i_heads, i_dim).transpose(0, 2, 1, 3), 0)
        k_i = turn(_layer_norm(_mm(x, ix["wk"].astype(F32)), ix["k_norm"],
                               eps), 0)
        w = _mm(x, ix["w_heads"].astype(F32)).transpose(0, 2, 1) \
            * (i_heads ** -0.5 * i_dim ** -0.5)
        block = min(QUERY_BLOCK, l)

        def of_block(a):
            first_query, q_b, w_b = a          # [n, i_heads, b, .], [.., b]
            hit = jnp.maximum(_ein("nhqd,nkd->nhqk", q_b, k_i), 0.0)
            index = jnp.sum(hit * w_b[..., None], axis=1)       # [n, b, l]
            at = first_query + jnp.arange(block)
            seen = at[:, None] >= jnp.arange(l)[None, :]
            # 0.0 and -0.0 are one score: equal scores go to the lower
            # index
            index = jnp.where(seen, jnp.where(index == 0, 0.0, index),
                              -jnp.inf)
            _, best = jax.lax.top_k(index, min(topk, l))
            return jnp.zeros((n, block, l), bool).at[
                jnp.arange(n)[:, None, None],
                jnp.arange(block)[None, :, None], best].set(True) & seen

        chosen = jax.lax.map(of_block, (jnp.arange(0, l, block),
                                        in_blocks(q_i, block),
                                        in_blocks(w, block)))
        return jnp.moveaxis(chosen, 0, 1).reshape(n, l, l)

    @jax.checkpoint
    def block_of_queries(q, chosen, k, v):
        """``q [n, g, b, 256]`` and ``chosen [n, b, l]`` of ``b`` queries
        against all the keys ``k, v [n, g, l, .]`` of ``g`` heads."""
        scores = _ein("nhqd,nhkd->nhqk", q, k) * softmax_scale
        scores = jnp.where(chosen[:, None], scores, -1e30)
        weights = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return _ein("nhqk,nhkd->nhqd", weights, v)

    def group_of_heads(first_head, group, prefix):
        """The part of the mixer's result that ``group`` whole heads
        from ``first_head`` on give: their queries, keys and values out
        of the two latents, their attention over the chosen keys, their
        rows of ``W_o``."""
        def cols(width):
            return slice(first_head * width, (first_head + group) * width)

        @jax.checkpoint
        def apply(p, lora, c_q, latent, rotary_key, chosen):
            n, l, _ = c_q.shape
            proj = projector(p, lora, prefix, None)

            def split(y):
                return y.reshape(n, l, group, -1).transpose(0, 2, 1, 3)

            q = turn(split(proj("wq_b", c_q, cols=cols(nope + rot))), nope)
            kv = split(proj("wkv_b", latent, cols=cols(nope + d_v)))
            shared = jnp.broadcast_to(rotary_key[:, None],
                                      (n, group, l, rot))
            k = turn(jnp.concatenate([kv[..., :nope], shared], axis=-1), nope)
            v = kv[..., nope:]
            block = min(QUERY_BLOCK, l)
            # a loop over the blocks of queries, so that its backward
            # adds the keys' and values' cotangents into one array
            out = jax.lax.map(
                lambda a: block_of_queries(a[0], a[1], k, v),
                (in_blocks(q, block), jnp.moveaxis(
                    chosen.reshape(n, l // block, block, l), 1, 0)))
            # [l / block, n, g, block, .]
            out = jnp.moveaxis(out, 0, 2).reshape(n, group, l, d_v)
            return proj("wo", out.transpose(0, 2, 1, 3).reshape(n, l, -1),
                        rows=cols(d_v))

        return apply

    def latent_attention(p, lora, prefix, x):
        proj = projector(p, lora, prefix, x)
        c_q = _rms_norm(proj("wq_a"), p["q_a_norm"]["scale"], eps)
        c = proj("wkv_a")
        latent = _rms_norm(c[..., :rank], p["kv_norm"]["scale"], eps)
        chosen = chosen_keys(p["indexer"], x, c_q)
        group = min(HEAD_GROUP, heads)
        assert heads % group == 0, (heads, group)
        y = 0.0
        for first_head in range(0, heads, group):
            y = y + group_of_heads(first_head, group, prefix)(
                p, lora, c_q, latent, c[..., rank:], chosen)
        return y

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
