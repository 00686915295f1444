"""The plain reference of ``command_a_plus``: one tensor- and expert-
parallel rank of the first pipeline stage of command-a-plus-05-2026 (one
period of three windowed layers and a full one, the rank's share of the
heads, the first held routed experts of 128 and all four shared experts),
a frozen base with low-rank adapters, in float32 ``jax.numpy`` at
``precision="highest"`` over the program's parameter tree ``{"base":
..., "lora": {path: {"a", "b"}}}``.

Every block is **parallel**: one norm, the mixer and the expert layer
both read it, one add::

    h = LN(x)                     x <- x + attention(h) + experts(h)
    LN(x) = (x - mean(x)) / sqrt(var(x) + layer_norm_eps) * scale

(a LayerNorm with a learned scale and no bias; once more before the
head). With ``h`` the normalised input, both kinds of mixer are::

    q = h W_q   [L, Hq, 128]      k = h W_k, v = h W_v   [L, Hkv, 128]
    o = softmax(q k^T / sqrt(128) over the keys a query sees) v
    a = o W_o                     query head i on key-value head i // 16

A **sliding** layer's query at position ``t`` sees the keys ``s`` with
``0 <= t - s < sliding_window``, and its q and k are turned over the
whole head in **adjacent pairs**: channels ``2i`` and ``2i + 1`` by the
angle ``t * rope_theta ** (-2i / 128)`` (``rope_gptj``). A **full**
layer's query sees every ``s <= t`` and its q and k are **not turned at
all**. The scores are made a block of ``QUERY_BLOCK`` queries at a time
against every key, one block after the other (``lax.map``), and what a
query does not see is masked.

The expert layer, on the same ``h``: ``z = h W_r`` (128 logits); ``s =
1 / (1 + exp(-z))``; the 8 largest ``s`` are chosen (found by counting,
an equal score to the lower index); their weights are ``s_e / sum over
the chosen of s``; ``r = sum over the chosen that are held of w_e E_e(h)``,
``E(h) = W_down (silu(h W_gate) * h W_up)``: a loop (``lax.scan``) over
the held experts, each computing every token, masked by ``w``. A choice
that falls on an expert held elsewhere (``first_held_expert``, the
experts' leading axis) adds nothing. The **four shared experts** are
computed one by one, each from its own slice of columns of the wide
``shared/w_gate`` and ``shared/w_up`` and of rows of ``shared/w_down``
(and of the adapters' wide factor), and then **averaged**: ``m = r +
(S_1(h) + S_2(h) + S_3(h) + S_4(h)) / 4``. A projection with an adapter
is ``x W + s (x A) B``. The loss is the masked mean next-token
cross-entropy over the tied table, ``logit_scale`` times the logits,
head and loss in blocks of tokens.

What the config.json leaves open is under ``assumed`` in
``fedbench/configs/command_a_plus.json``. Each frozen weight is cast to
float32 where it is used; a layer, one expert of it (routed or shared),
a block of queries and a block of the loss are under ``jax.checkpoint``
(no arithmetic changes: a client of 8,192 tokens then fits beside the
bfloat16 base). Imports nothing of ``baton_tpu``; no ``vmap``, no
``custom_vjp`` or ``custom_jvp`` (SiLU, the sigmoid and the softmax are
written out), no grouped product, no sort, no kernel.
"""

import jax
import jax.numpy as jnp

LOSS_BLOCK = 256   # tokens whose float32 logits are held at a time
QUERY_BLOCK = 512  # queries whose [heads, block, L] scores are held at a time
F32 = jnp.float32


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _layer_norm(x, scale, eps):
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    return centred / jnp.sqrt(var + eps) * scale.astype(F32)


def rotary_table(length, dim, theta):
    """``(cos, sin)``, each ``[length, dim / 2]``: ``t * theta ** (-2i /
    dim)``."""
    freq = jnp.asarray([float(theta) ** (-2.0 * i / dim)
                        for i in range(dim // 2)], F32)
    angle = jnp.arange(length, dtype=F32)[:, None] * freq
    return jnp.cos(angle), jnp.sin(angle)


def turned_adjacent(x, cos, sin):
    """``x [..., l, d]`` turned by its positions' angles, channel ``2i``
    paired with ``2i + 1``: ``y[2i] = x[2i] cos_i - x[2i+1] sin_i``,
    ``y[2i+1] = x[2i+1] cos_i + x[2i] sin_i``. The pair's other channel
    comes by a product with the ``[d, d]`` matrix that holds ``-1`` at
    ``(2i + 1, 2i)`` and ``1`` at ``(2i, 2i + 1)`` (exact in float32; an
    axis of two channels would be padded to a whole tile on the chip)."""
    d = x.shape[-1]
    i = jnp.arange(0, d, 2)
    swap = jnp.zeros((d, d), F32).at[i + 1, i].set(-1.0).at[i, i + 1].set(1.0)
    other = jnp.matmul(x, swap, precision="highest")
    return x * jnp.repeat(cos, 2, axis=-1) + other * jnp.repeat(sin, 2,
                                                                  axis=-1)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product (the identity, or
    the control's rounding: ``fedbench/reference.py::rounded_to``)."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    group = hq // hkv
    window = config["sliding_window"]
    eps = config["layer_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    top_k = config["num_experts_per_tok"]
    n_shared = config["num_shared_experts"]
    width = config["intermediate_size"]
    first = config.get("first_held_expert", 0)
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    if config["position_embedding_type"] != "rope_gptj":
        raise ValueError("the reference turns adjacent pairs (rope_gptj), "
                         f"not {config['position_embedding_type']!r}")
    if config["shared_expert_combination_strategy"] != "average":
        raise ValueError("the reference averages the shared experts")

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

            def split(y, heads):
                return y.reshape(n, l, heads, d).transpose(0, 2, 1, 3)

            q, k = split(proj("wq"), hq), split(proj("wk"), hkv)
            if sliding:  # a full layer's q and k are not turned
                cos, sin = rotary_table(l, d, config["rope_theta"])
                q = turned_adjacent(q, cos, sin)
                k = turned_adjacent(k, cos, sin)
            q = q.reshape(n, hkv, group, l, d)
            v = split(proj("wv"), hkv)
            # one block of queries after the other (a loop the compiler
            # may not run side by side); a length the block does not
            # divide is one block
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
        """Every token through one routed expert, weighted by the
        token's weight for it ``w_e [n, l]`` (zero where it was not
        chosen)."""
        return w_e[..., None] * _mm(
            _silu(_mm(x, w_gate.astype(F32))) * _mm(x, w_up.astype(F32)),
            w_down.astype(F32))

    def routed(p, x):
        s = 1.0 / (1.0 + jnp.exp(-_mm(x, p["router"])))
        # an expert is chosen where fewer than top_k others score higher
        # (an equal score counts for the one of lower index, as a stable
        # sort would have it)
        index = jnp.arange(s.shape[-1])
        higher = (s[..., None, :] > s[..., :, None]) | (
            (s[..., None, :] == s[..., :, None])
            & (index[None, :] < index[:, None]))
        chosen = jnp.where(jnp.sum(higher, axis=-1) < top_k, s, 0.0)
        w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
        held = p["w_gate"].shape[0]
        w_held = jnp.moveaxis(w[..., first:first + held], -1, 0)

        def add_one(y, one):
            return y + one_expert(*one, x), None

        y, _ = jax.lax.scan(add_one, jnp.zeros_like(x),
                            (p["w_gate"], p["w_up"], p["w_down"], w_held))
        return y

    def shared(p, lora, prefix, x):
        """The mean of the ``n_shared`` shared experts, each computed
        apart from its slice of the wide matrices (expert ``j`` holds
        columns ``j width`` to ``(j + 1) width`` of ``w_gate`` and
        ``w_up`` and those rows of ``w_down``; the adapters' wide factor
        is cut the same way, the narrow one is shared)."""
        def cut(name, j, axis):
            at = slice(j * width, (j + 1) * width)
            w = p[name][:, at] if axis == 1 else p[name][at]
            ab = lora.get(f"{prefix}/{name}")
            if ab is None:
                return w, None, None
            if axis == 1:
                return w, ab["a"], ab["b"][:, at]
            return w, ab["a"][at], ab["b"]

        @jax.checkpoint
        def one_shared(gate, up, down, x):
            def proj(inp, w, a, b):
                y = _mm(inp, w.astype(F32))
                return y if a is None else y + scale * _mm(_mm(inp, a), b)
            return proj(_silu(proj(x, *gate)) * proj(x, *up), *down)

        total = jnp.zeros_like(x)
        for j in range(n_shared):
            total = total + one_shared(cut("w_gate", j, 1), cut("w_up", j, 1),
                                       cut("w_down", j, 0), x)
        return total / n_shared

    def block(index):
        mixer = attention(kinds[index])
        key = {"sliding_attention": "sliding_attn",
               "full_attention": "attn"}[kinds[index]]

        def apply(p, lora, x):
            prefix = f"blocks/{index}"
            h = _layer_norm(x, p["norm"]["scale"], eps)
            a = mixer(p[key], lora, f"{prefix}/{key}", h)
            m = routed(p["mlp"], h) + shared(
                p["mlp"]["shared"], lora, f"{prefix}/mlp/shared", h)
            return x + a + m

        return jax.checkpoint(apply)

    blocks = [block(i) for i in range(config["num_hidden_layers"])]

    @jax.checkpoint
    def token_losses(table, x, y):
        logits = config["logit_scale"] * _ein("nld,vd->nlv", x,
                                              table.astype(F32))
        top = jnp.max(logits, axis=-1, keepdims=True)
        logz = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
        return logz - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]

    def loss(params, x, y, mask):
        base, lora = params["base"], params["lora"]
        h = base["tok_emb"][x].astype(F32)
        for apply, p in zip(blocks, base["blocks"]):
            h = apply(p, lora, h)
        h = _layer_norm(h, base["norm_f"]["scale"], eps)
        l = x.shape[1]
        per_token = jnp.concatenate(
            [token_losses(base["tok_emb"], h[:, s:s + LOSS_BLOCK],
                          y[:, s:s + LOSS_BLOCK])
             for s in range(0, l, LOSS_BLOCK)], axis=1)
        return jnp.sum(jnp.mean(per_token, axis=1) * mask) / jnp.sum(mask)

    return loss
