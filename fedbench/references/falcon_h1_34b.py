"""The plain reference of ``falcon_h1_34b``: the first pipeline stage of
Falcon-H1-34B (six blocks, each a Mamba-2 state-space branch and an
attention branch side by side, then a SwiGLU MLP), a frozen base with
low-rank adapters, in float32 ``jax.numpy`` at ``precision="highest"``
over the program's parameter tree ``{"base": ..., "lora": {path: {"a",
"b"}}}``. Every multiplier is the configuration's, applied where the
``falcon_h1`` modelling code applies it.

A block, with ``x [L, 5120]`` the stream::

    u = RMSNorm(x)                                     eps 1e-5
    x = x + SSM(u) + Attention(u)
    h = RMSNorm(x)
    x = x + mlp_multipliers[1] W_down(SiLU(mlp_multipliers[0] W_gate h) * W_up h)

*The state-space branch* (Dao & Gu, arXiv:2405.21060; 32 heads of 128,
a state of 256 x 128 a head, ``B`` and ``C`` in 2 groups, head ``h``
reading group ``h // 16``)::

    p          = (W_in (ssm_in_multiplier u)) * m      m: ssm_multipliers laid over z | x | B | C | dt
    z, xBC, dt = split(p)                              4096 | 5120 | 32
    xBC        = SiLU(conv(xBC) + bias)                causal, depthwise, 4 taps: lax.conv_general_dilated
    xs, B, C   = split(xBC)                            4096 | 512 | 512
    delta_t    = softplus(dt_t + dt_bias),  a_t = exp(-exp(A_log) delta_t)      a head
    S_t        = a_t S_{t-1} + delta_t B_t xs_t^T      S_0 = 0
    y_t        = C_t^T S_t + D xs_t
    y          = RMSNorm over each group's 2,048 channels of (y * SiLU(z)), times norm
    SSM(u)     = ssm_out_multiplier W_out y

as a ``lax.scan`` over the positions of those three lines, **token by
token**, which shares nothing with the program's chunked form. The scan
is nested, ``SCAN_BLOCK`` tokens under ``jax.checkpoint`` inside a scan
over the blocks (no arithmetic changes: every token's 4.2 MB state of a
layer, 4,096 times, would not fit beside the base); a tail shorter than
a block is filled with tokens that leave the state alone (``delta = 0``).

*The attention branch* (20 query heads on 4 key-value heads of 128,
which is not 5120 / 20)::

    q, k, v      = W_q u', key_multiplier W_k u', W_v u'        u' = attention_in_multiplier u
    all 128 channels of q and k turned (theta 1e11, channel i with i + 64, no scaling)
    o            = softmax_causal(q k^T / sqrt(128)) v,  query head i on key head i // 5
    Attention(u) = attention_out_multiplier W_o o

over a block of ``QUERY_BLOCK`` queries at a time against the whole
sequence, masked. The stream starts at ``embedding_multiplier E[id]``;
the logits are ``lm_head_multiplier W_head RMSNorm(x)``, untied; the
loss is the masked mean next-token cross-entropy over the held slice of
the vocabulary, head and loss in blocks of tokens. A projection with an
adapter is ``x W + s (x A) B``.

Departures from the published code, each also under ``assumed`` in
``fedbench/configs/falcon_h1_34b.json``: the published code clamps
``delta`` to ``time_step_limit`` (0, inf), which changes nothing and is
left out; it computes the recurrence in chunks through a fused kernel
and this file computes the recurrence itself. Each frozen weight is cast
to float32 where it is used; a block, a block of tokens of the scan, a
block of queries and a block of the loss are under ``jax.checkpoint``.
Imports nothing of ``baton_tpu``; no ``vmap``, no ``custom_vjp`` or
``custom_jvp`` (SiLU, softplus and softmax are written out), no kernel,
and the convolution is a convolution, not the program's slices and
products.
"""

import jax
import jax.numpy as jnp

LOSS_BLOCK = 256    # tokens whose float32 logits are held at a time
QUERY_BLOCK = 1024  # queries whose [20, block, L] scores are held at a time
SCAN_BLOCK = 64     # tokens of the recurrence under one checkpoint
F32 = jnp.float32


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(F32)


def make_loss(config, cast=lambda a: a):
    """``loss(params, x, y, mask) -> scalar`` at the sizes of ``config``:
    ``x, y [n, l]`` token ids and next tokens, ``mask [n]``. ``cast`` is
    applied to both operands of every matrix product and of the
    convolution (the identity, or the control's rounding:
    ``fedbench/reference.py::rounded_to``)."""
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    group = hq // hkv
    heads, width = config["mamba_n_heads"], config["mamba_d_head"]
    states, groups = config["mamba_d_state"], config["mamba_n_groups"]
    d_ssm, bc = heads * width, groups * states
    eps = config["rms_norm_eps"]
    scale = config["lora_alpha"] / config["lora_rank"]
    on_gate, on_down = config["mlp_multipliers"]
    on_parts = jnp.concatenate([
        jnp.full((n,), m, F32) for n, m in zip(
            (d_ssm, d_ssm, bc, bc, heads), config["ssm_multipliers"])])
    frequencies = float(config["rope_theta"]) ** (
        -jnp.arange(0, d, 2, dtype=F32) / d)

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

    def token(decay_skip, state, at):
        """One position of the recurrence: ``state [n, heads, states,
        width]``."""
        a_log, skip = decay_skip
        xs, b_t, c_t, delta = at
        b_t, c_t = (jnp.repeat(g, heads // groups, axis=1) for g in (b_t, c_t))
        a_t = jnp.exp(-jnp.exp(a_log.astype(F32)) * delta)
        state = a_t[..., None, None] * state \
            + delta[..., None, None] * b_t[..., :, None] * xs[..., None, :]
        return state, _ein("nhs,nhsp->nhp", c_t, state) \
            + skip.astype(F32)[:, None] * xs

    def recurrence(p, xs, b_mat, c_mat, delta):
        """``y [n, l, heads, width]`` from a zero state."""
        n, l = xs.shape[:2]
        block = min(SCAN_BLOCK, l)
        fill = -l % block

        def by_block(a):  # [n, l, ...] -> [blocks, block, n, ...]
            a = jnp.pad(a, ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
            a = jnp.moveaxis(a, 1, 0)
            return a.reshape((-1, block) + a.shape[1:])

        @jax.checkpoint
        def tokens(state, ats):
            return jax.lax.scan(
                lambda s, at: token((p["a_log"], p["d"]), s, at), state, ats)

        _, y = jax.lax.scan(
            tokens, jnp.zeros((n, heads, states, width), F32),
            tuple(by_block(a) for a in (xs, b_mat, c_mat, delta)))
        return jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :l]

    def state_space(p, lora, prefix, u):
        n, l, _ = u.shape
        proj = projector(p, lora, prefix, u)
        parts = proj("in_proj", config["ssm_in_multiplier"] * u) * on_parts
        z, xbc, dt = (parts[..., :d_ssm], parts[..., d_ssm:-heads],
                      parts[..., -heads:])
        taps = p["conv_w"].shape[0]
        # depthwise: a kernel [taps, 1, channels], a group a channel
        xbc = jax.lax.conv_general_dilated(
            cast(jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))),
            cast(p["conv_w"].astype(F32)[:, None, :]), (1,), "VALID",
            dimension_numbers=("NWC", "WIO", "NWC"),
            feature_group_count=xbc.shape[-1], precision="highest")
        xbc = _silu(xbc + p["conv_b"].astype(F32))
        y = recurrence(
            p, xbc[..., :d_ssm].reshape(n, l, heads, width),
            xbc[..., d_ssm:d_ssm + bc].reshape(n, l, groups, states),
            xbc[..., d_ssm + bc:].reshape(n, l, groups, states),
            _softplus(dt + p["dt_bias"].astype(F32)))
        y = (y.reshape(n, l, d_ssm) * _silu(z)).reshape(n, l, groups, -1)
        y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y.reshape(n, l, d_ssm) * p["norm"].astype(F32)
        return config["ssm_out_multiplier"] * proj("out_proj", y)

    def turned(x):
        """``x [n, heads, l, d]`` turned by its positions' angles,
        channel ``i`` paired with ``i + d / 2``."""
        angle = jnp.arange(x.shape[2], dtype=F32)[:, None] * frequencies
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1)

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

    def attention(p, lora, prefix, u):
        n, l, _ = u.shape
        proj = projector(p, lora, prefix,
                         config["attention_in_multiplier"] * u)

        def split(y, h):
            return y.reshape(n, l, h, d).transpose(0, 2, 1, 3)

        q = turned(split(proj("wq"), hq)).reshape(n, hkv, group, l, d)
        k = turned(split(config["key_multiplier"] * proj("wk"), hkv))
        v = split(proj("wv"), hkv)
        out = jnp.concatenate(
            [attended(q[:, :, :, s:s + QUERY_BLOCK], k, v, s)
             for s in range(0, l, QUERY_BLOCK)], axis=3)
        out = out.reshape(n, hq, l, d).transpose(0, 2, 1, 3)
        return config["attention_out_multiplier"] * proj(
            "wo", out.reshape(n, l, hq * d))

    def mlp(p, lora, prefix, h):
        proj = projector(p, lora, prefix, h)
        return on_down * proj(
            "w_down", _silu(on_gate * proj("w_gate")) * proj("w_up"))

    def block(index):
        def apply(p, lora, x):
            prefix = f"blocks/{index}"
            u = _rms_norm(x, p["norm_attn"]["scale"], eps)
            pair = p["parallel"]
            x = x + state_space(pair["ssm"], lora, f"{prefix}/parallel/ssm", u) \
                + attention(pair["attention"], lora,
                            f"{prefix}/parallel/attention", u)
            return x + mlp(p["mlp"], lora, f"{prefix}/mlp",
                           _rms_norm(x, p["norm_mlp"]["scale"], eps))

        return jax.checkpoint(apply)

    blocks = [block(i) for i in range(config["num_hidden_layers"])]

    @jax.checkpoint
    def token_losses(head, x, y):
        logits = config["lm_head_multiplier"] * _mm(x, head.astype(F32))
        top = jnp.max(logits, axis=-1, keepdims=True)
        logz = top[..., 0] + jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1))
        return logz - jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]

    def loss(params, x, y, mask):
        base, lora = params["base"], params["lora"]
        h = config["embedding_multiplier"] * base["tok_emb"][x].astype(F32)
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
