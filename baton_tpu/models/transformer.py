"""Shared transformer building blocks for the baton_tpu model zoo.

The reference ships no transformer (its only model is a 10->1 linear
layer, reference demo.py:15-49); BASELINE configs 3-5 (BERT/AG-News
FedProx, Llama-class LoRA instruction-tune, ViT-B/16 DP cross-silo) are
driver-set workloads that need one. These blocks are written TPU-first:

* **Everything is einsum/matmul** on [B, L, D]-shaped activations so XLA
  tiles the projections and the attention contractions onto the MXU;
  params stay fp32 (FedAvg accumulates fp32), activations are cast to a
  ``compute_dtype`` (bf16 on TPU) per-apply, norms/softmax in fp32.
* **Static shapes only** — causal masking is a static ``L x L`` bound
  inside the kernel, padding is a dynamic length vector turned into an
  additive bias; no data-dependent control flow, so the whole model jits
  and vmaps over a simulated-client axis.
* **Injectable attention kernel**: every model takes an ``attention_fn``
  with the signature of :func:`dot_product_attention` so the dense
  kernel can be swapped for a fused/blockwise kernel or ring attention
  over a sequence mesh axis without touching model code.
* **GQA layout** [B, H, L, Dh] with an explicit kv-head axis: K/V heads
  are broadcast to query groups by reshape, not materialized repeats.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental.layout import Layout, with_layout_constraint

# attention_fn(q, k, v, bias, causal) -> out
#   q [B, Hq, L, Dh], k/v [B, Hkv, L, Dh], bias None or [B, 1, 1, L] additive
AttentionFn = Callable[..., jax.Array]


# ---------------------------------------------------------------------------
# initializers


def normal_init(key, shape, stddev):
    return jax.random.normal(key, shape, jnp.float32) * stddev


def dense_init(key, d_in, d_out, stddev=None):
    """[d_in, d_out] fan-in scaled normal (stddev 1/sqrt(d_in) default)."""
    if stddev is None:
        stddev = d_in ** -0.5
    return normal_init(key, (d_in, d_out), stddev)


@dataclasses.dataclass(frozen=True)
class Multipliers:
    """The fixed scalars a maximal-update parametrisation puts on a
    model's projections (Falcon-H1's ``*_multiplier`` keys), applied
    where the published code applies them. A multiplier of 1 adds no op
    (:func:`scaled`). A draw from the seed stands for trained weights,
    and the multipliers were tuned with the model's own initial
    deviations: every multiplied matrix is drawn at its usual deviation
    over its multiplier, so that the multiplied projection has the scale
    the other models' have."""

    embedding: float = 1.0  # on the looked-up rows of the table
    lm_head: float = 1.0  # on the logits
    key: float = 1.0  # on the key projection's output
    attention_in: float = 1.0  # on an attention branch's input
    attention_out: float = 1.0  # and on its output
    ssm_in: float = 1.0  # on a state-space branch's input
    ssm_out: float = 1.0  # and on its output
    # on the five parts of its input projection: z, x, B, C, dt
    ssm: tuple = (1.0,) * 5
    mlp: tuple = (1.0, 1.0)  # on the gate's pre-activation, on the output

    def __post_init__(self):  # a JSON list hashes as a tuple
        for name in ("ssm", "mlp"):
            object.__setattr__(self, name, tuple(getattr(self, name)))


def scaled(x, multiplier: float):
    """``multiplier * x`` in float32, in ``x``'s dtype; ``x`` itself at
    a multiplier of 1 (a bfloat16 product would round the multiplier to
    eight bits too, the same way for every entry)."""
    if multiplier == 1:
        return x
    return (x.astype(jnp.float32) * multiplier).astype(x.dtype)


def ln_init(d):
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def rms_init(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# norms (fp32 stats regardless of compute dtype)


def layer_normalize(x, p, eps=1e-6):
    """LayerNorm over the last axis under no scope of its own (see
    :func:`rms_normalize`); a ``p`` without a ``bias`` has a scale
    alone."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps) * p["scale"]
    if "bias" in p:
        xf = xf + p["bias"]
    return xf.astype(x.dtype)


layer_norm = jax.named_scope("norm")(layer_normalize)


def rms_normalize(x, scale, eps=1e-6):
    """RMSNorm over the last axis under no scope of its own: a norm
    inside a mixer counts as the mixer's."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


@jax.named_scope("norm")
def rms_norm(x, p, eps=1e-6):
    return rms_normalize(x, p["scale"], eps)


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE)


def rope_angles(seq_len: int, head_dim: int, theta: float = 10000.0,
                yarn: Optional[dict] = None):
    """Returns (cos, sin) each [L, Dh/2], fp32. ``theta`` may be an
    integer past 32 bits (a configuration file's 100000000000).

    ``yarn``: the published group of a ``yarn`` rotation (``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``): the frequencies are :func:`yarn_inv_freq`'s
    and ``cos`` and ``sin`` are multiplied by ``attention_factor`` (so a
    score of two turned vectors by its square), at every length: the
    scaling is static, as the public ``yarn`` initialisation has it."""
    if yarn is None:
        inv_freq = 1.0 / (
            float(theta)
            ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
        )
    else:
        inv_freq = yarn_inv_freq(
            head_dim, float(theta), yarn["factor"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"])
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    ang = jnp.outer(pos, inv_freq)  # [L, Dh/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is None:
        return cos, sin
    return yarn["attention_factor"] * cos, yarn["attention_factor"] * sin


ROPE_PAIRS = ("split", "adjacent")


def apply_rope(x, cos, sin, pairs: str = "split"):
    """Rotate pairs of channels. x [B, H, L, Dh]; cos/sin [L, Dh/2].
    ``pairs``: ``"split"`` turns channel ``i`` with ``i + Dh/2``,
    ``"adjacent"`` channel ``2i`` with ``2i + 1``, both by the angle
    ``i``, on the channels where they lie (each channel times its
    pair's cosine plus its neighbour times the signed sine: no axis of
    two is made)."""
    xf = x.astype(jnp.float32)
    if pairs == "adjacent":
        even = jnp.arange(x.shape[-1]) % 2 == 0
        other = jnp.where(even, jnp.roll(xf, -1, axis=-1),
                          jnp.roll(xf, 1, axis=-1))
        sin = jnp.repeat(sin, 2, axis=-1)
        return (xf * jnp.repeat(cos, 2, axis=-1)
                + other * jnp.where(even, -sin, sin)).astype(x.dtype)
    if pairs != "split":
        raise ValueError(f"unknown rope pairs {pairs!r}: {ROPE_PAIRS}")
    x1, x2 = jnp.split(xf, 2, axis=-1)
    # broadcast [L, Dh/2] over [B, H, L, Dh/2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.concatenate([r1, r2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention


def dot_product_attention(q, k, v, bias=None, causal=False, window=None):
    """Dense scaled-dot-product attention with GQA.

    q [B, Hq, L, Dh]; k, v [B, Hkv, L, Dh] with Hq % Hkv == 0. Softmax in
    fp32; the two contractions are einsums XLA maps onto the MXU. ``bias``
    is additive, broadcastable to [B, Hq, L, L] (padding uses -inf-like
    large negatives). With ``window`` (causal attention alone) a query
    sees itself and the ``window - 1`` keys before it.
    """
    b, hq, l, dh = q.shape
    hkv = k.shape[1]
    scale = dh ** -0.5
    if hq != hkv:
        q = q.reshape(b, hkv, hq // hkv, l, dh)
        scores = jnp.einsum("bhgqd,bhkd->bhgqk", q, k) * scale
        scores = scores.reshape(b, hq, l, l)
    else:
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias
    if causal:
        ql = jnp.arange(l)
        seen = ql[:, None] >= ql[None, :]
        if window is not None:
            seen = seen & (ql[:, None] - ql[None, :] < window)
        scores = jnp.where(seen, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if hq != hkv:
        probs = probs.reshape(b, hkv, hq // hkv, l, l)
        out = jnp.einsum("bhgqk,bhkd->bhgqd", probs, v)
        return out.reshape(b, hq, l, dh)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# Dense attention materializes a [B, Hq, Lq, Lk] fp32 score tensor; at
# or past _FLASH_MIN_LEN keys, or past the score-tensor budget, the
# Pallas flash kernel takes over. Both are constants: the threshold has
# not been swept on the current stack (ROADMAP S5 / D6).
_FLASH_MIN_LEN = 4096
_DENSE_SCORES_BUDGET_BYTES = 512 * 1024 ** 2


def dense_gives_way(backend: str, batch: int, heads: int, lq: int,
                    lk: int) -> bool:
    """Whether :func:`default_attention` leaves the dense path for the
    flash kernel (given a bias the kernel takes): on a TPU, at or past
    ``_FLASH_MIN_LEN`` keys or past the score-tensor budget."""
    return backend == "tpu" and (
        lk >= _FLASH_MIN_LEN
        or 4 * batch * heads * lq * lk > _DENSE_SCORES_BUDGET_BYTES)


def default_attention(q, k, v, bias=None, causal=False, window=None):
    """Backend-dispatching attention — the model zoo's default kernel.

    On TPU, long sequences route to the Pallas flash-attention kernel
    (ops/flash_attention.py): O(L·block) memory instead of the dense
    [B, H, L, L] score tensor, fused online softmax, same numerics
    (fp32 softmax, GQA). Short sequences stay on the dense path, and so
    does every non-TPU backend (CPU tests would hit the interpreted
    Pallas kernel). Which side is faster at which length is not
    measured on the current stack.

    The dispatch happens at trace time (shapes and
    ``jax.default_backend()`` are ordinary Python), so the jitted
    program contains exactly one kernel — there is no runtime branch.
    A ``bias`` that is not the standard per-key [B, 1, 1, L] padding
    bias falls back to the dense kernel, which accepts anything
    broadcastable to [B, Hq, L, L]. A ``window`` goes to whichever side
    is taken, by the rule a call without one is sent: the kernel skips
    by it, the dense side masks.
    """
    b, hq, lq, _ = q.shape
    lk = k.shape[2]
    # a call without a window passes none on: an attention of the
    # caller's own need not know the word
    windowed = {} if window is None else {"window": window}
    if dense_gives_way(jax.default_backend(), b, hq, lq, lk) and (
            bias is None or bias.shape == (b, 1, 1, lk)):
        from baton_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, bias=bias, causal=causal, **windowed)
    return dot_product_attention(q, k, v, bias=bias, causal=causal,
                                 **windowed)


def attention_is_kernel(attention_fn, backend: str, batch: int, heads: int,
                        length: int) -> bool:
    """Whether causal self-attention over ``length`` tokens by
    ``attention_fn`` is the flash kernel: :func:`default_attention`
    where dense gives way (no bias stands in the kernel's way there,
    and a window does not move the rule: the kernel takes one); an
    attention of the caller's is not known to be one."""
    return attention_fn is default_attention and dense_gives_way(
        backend, batch, heads, length, length)


def padding_bias(mask, dtype=jnp.float32):
    """[B, L] 1/0 validity mask -> additive [B, 1, 1, L] attention bias."""
    return ((1.0 - mask.astype(jnp.float32)) * -1e30)[:, None, None, :].astype(dtype)


def mha_init(key, d_model, n_heads, n_kv_heads=None, head_dim=None,
             out_std=None, qk_norm: bool = False, qk_aligned: float = 0.0):
    """Fused QKV-per-role projection params for (G)MQA attention.
    ``qk_norm`` adds the RMSNorm scales of the whole query and key
    projections (all heads together, before the split into heads).
    ``qk_aligned`` is the share of a query head's projection that is
    its key head's (``wq_i = a wk_j + sqrt(1 - a^2) own``, the deviation
    kept): a token's score of its own key then has the mean ``a
    sqrt(head_dim)`` among scores of deviation 1. Random weights stand
    for trained ones, and a trained head scores a token's own key high;
    with independent draws a query's softmax over a thousand random keys
    is flat, its own key weighs a thousandth, and adapters on the
    projections get next to no gradient that does not average out. At 0
    the draws are independent, as they were."""
    n_kv = n_kv_heads or n_heads
    dh = head_dim or d_model // n_heads
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": dense_init(kq, d_model, n_heads * dh),
        "wk": dense_init(kk, d_model, n_kv * dh),
        "wv": dense_init(kv, d_model, n_kv * dh),
        "wo": dense_init(ko, n_heads * dh, d_model, stddev=out_std),
    }
    if qk_aligned:
        own = p["wq"].reshape(d_model, n_kv, n_heads // n_kv, dh)
        shared = p["wk"].reshape(d_model, n_kv, 1, dh)
        p["wq"] = (qk_aligned * shared + (1 - qk_aligned ** 2) ** 0.5 * own
                   ).reshape(d_model, n_heads * dh)
    if qk_norm:
        p["q_norm"] = rms_init(n_heads * dh)
        p["k_norm"] = rms_init(n_kv * dh)
    return p


def multi_head_attention(
    p,
    x,
    n_heads: int,
    n_kv_heads: Optional[int] = None,
    bias=None,
    causal: bool = False,
    rope: Optional[tuple] = None,
    attention_fn: AttentionFn = default_attention,
    key_multiplier: float = 1.0,
    window: Optional[int] = None,
    core_scope: Optional[str] = None,
    rope_pairs: str = "split",
):
    """Multi-head attention over x [B, L, D] -> [B, L, D] under no
    scope of its own; a head is as wide as ``wq`` makes it (``d_model /
    n_heads`` or not). With ``window`` a query sees itself and the
    ``window - 1`` keys before it (``attention_fn`` is handed it only
    then). ``core_scope`` names the scope ``attention_fn`` is called
    under, for a model that tells its cores apart; None opens none.
    ``rope_pairs``: the channels ``rope`` turns together
    (:func:`apply_rope`)."""
    b, l, _ = x.shape
    n_kv = n_kv_heads or n_heads
    dh = p["wq"].shape[1] // n_heads

    def proj(w, h, norm=None, multiplier=1.0):
        y = scaled(x @ w.astype(x.dtype), multiplier)
        if norm is not None:
            y = rms_norm(y, norm)
        return y.reshape(b, l, h, dh).transpose(0, 2, 1, 3)  # [B, H, L, Dh]

    # a "q_norm" / "k_norm" in the params is that projection's RMSNorm
    q = proj(p["wq"], n_heads, p.get("q_norm"))
    k = proj(p["wk"], n_kv, p.get("k_norm"), key_multiplier)
    v = proj(p["wv"], n_kv)
    if rope is not None:
        cos, sin = rope
        q, k = (apply_rope(q, cos, sin, rope_pairs),
                apply_rope(k, cos, sin, rope_pairs))
    windowed = {} if window is None else {"window": window}
    with (jax.named_scope(core_scope) if core_scope
          else contextlib.nullcontext()):
        out = attention_fn(q, k, v, bias=bias, causal=causal, **windowed)
    out = out.transpose(0, 2, 1, 3).reshape(b, l, n_heads * dh)
    return out @ p["wo"].astype(x.dtype)


mha_apply = jax.named_scope("attention")(multi_head_attention)


# ---------------------------------------------------------------------------
# latent attention (MLA: DeepSeek-V2, arXiv:2405.04434 section 2.1)


@dataclasses.dataclass(frozen=True)
class IndexerConfig:
    """The lightning indexer's sizes (DeepSeek-V3.2-Exp's sparse
    attention): ``heads`` index queries of ``dim`` channels a token
    against one index key of ``dim`` shared by them, the first
    ``rope_dim`` channels of both rotated; a query attends the ``topk``
    keys of its causal prefix that score highest."""

    heads: int = 32
    dim: int = 128
    topk: int = 2048
    rope_dim: int = 64


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Latent attention's sizes: keys and values come from a normalised
    latent of ``kv_rank`` channels, one rotary key of ``rope_dim`` is
    shared by all heads; queries and keys are ``nope_dim + rope_dim``
    wide a head, values ``v_dim`` (narrower than the keys, 192 / 128,
    or as wide, 256 / 256). With ``q_rank`` the queries come from a
    normalised latent of their own; with ``indexer`` a query attends
    the keys a learned index chose for it (:func:`index_scores`,
    :func:`select_keys`), one set a query for all heads. Neither is in
    the parameters or the program of a configuration that names none.

    The widths decide how the mixer lays out what it makes
    (:func:`mla_qk_layout`): ``nope_dim + rope_dim`` of 192 rides the
    flash kernel's sublanes, so q and k are made ``[B, H, 192, L]``;
    256 is whole lane tiles and they are made ``[B, H, L, 256]``, the
    rotary 64 in lanes 192 to 255. The projections' weights are cut at
    ``nope_dim`` a head (:func:`cut_columns`), never their outputs."""

    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    # RMSNorm of each head's query and key over their whole width, one
    # scale vector for all heads, before the rotation
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # the published ``rope_scaling`` group of a ``deepseek_yarn`` model
    # (``factor``, ``original_max_position_embeddings``, ``beta_fast``,
    # ``beta_slow``, ``mscale``, ``mscale_all_dim``), or None for plain
    # rotary frequencies
    rope_scaling: Optional[tuple] = None
    # queries a block of the core where it is plain JAX (off a TPU, or a
    # length the Pallas kernel's blocks do not divide: ``causal_core``),
    # and of the index scores anywhere: a block's float32 scores against
    # its causal prefix of keys are held at a time, never ``[L, L]`` a
    # head
    block: int = 512
    # the queries' latent (``wq_a``, an RMSNorm, ``wq_b``); None: one
    # projection ``wq``
    q_rank: Optional[int] = None
    indexer: Optional[IndexerConfig] = None
    norm_eps: float = 1e-6

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):  # a JSON group, hashable
            if self.rope_scaling.get("type") != "deepseek_yarn":
                raise ValueError(f"unknown rope_scaling {self.rope_scaling}")
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.indexer is not None \
                and self.indexer.rope_dim != self.rope_dim:
            raise NotImplementedError(
                "the indexer's rotary width is the keys': both are turned "
                "by one table of angles")

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    def selects(self, length: int) -> bool:
        """Whether a sequence of ``length`` has a query that may not see
        its whole causal prefix."""
        return self.indexer is not None and self.indexer.topk < length

    @property
    def yarn(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def softmax_scale(self) -> float:
        """``qk_dim ** -0.5``, under YaRN times ``m ** 2`` with ``m = 0.1
        mscale_all_dim ln(factor) + 1``."""
        m = 1.0
        if self.yarn and self.yarn.get("mscale_all_dim"):
            m = 0.1 * self.yarn["mscale_all_dim"] \
                * math.log(self.yarn["factor"]) + 1.0
        return self.qk_dim ** -0.5 * m * m


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float):
    """``deepseek_yarn``'s ``dim / 2`` rotary frequencies: each a blend
    of ``theta ** (-2i / dim)`` and the same over ``factor``, by a
    linear ramp between the two correction dims (the channels that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_len``
    positions): fast channels keep their frequency, slow ones are
    interpolated."""
    def correction_dim(turns):
        return dim * math.log(original_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    base = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def mla_rope_angles(seq_len: int, cfg: MLAConfig):
    """``(cos, sin)``, each ``[L, rope_dim / 2]``, of the shared rotary
    key and the queries' rotary part."""
    yarn = cfg.yarn
    if yarn:
        if yarn.get("mscale", 1) != yarn.get("mscale_all_dim", 1):
            raise NotImplementedError(
                "mscale != mscale_all_dim scales cos and sin: full "
                "attention's rotation does (rope_angles, attention_factor), "
                "latent attention's has had no configuration that needs it")
        inv_freq = yarn_inv_freq(
            cfg.rope_dim, cfg.rope_theta, yarn["factor"],
            yarn["original_max_position_embeddings"], yarn["beta_fast"],
            yarn["beta_slow"])
    else:
        inv_freq = cfg.rope_theta ** (
            -jnp.arange(0, cfg.rope_dim, 2, dtype=jnp.float32) / cfg.rope_dim)
    ang = jnp.outer(jnp.arange(seq_len, dtype=jnp.float32), inv_freq)
    return jnp.cos(ang), jnp.sin(ang)


def mla_init(key, d_model: int, n_heads: int, cfg: MLAConfig, out_std=None):
    kq, ka, kb, ko = jax.random.split(key, 4)
    if cfg.q_rank is None:
        p = {"wq": dense_init(kq, d_model, n_heads * cfg.qk_dim)}
    else:
        p = {"wq_a": dense_init(kq, d_model, cfg.q_rank),
             "q_a_norm": rms_init(cfg.q_rank),
             "wq_b": dense_init(jax.random.fold_in(kq, 1), cfg.q_rank,
                                n_heads * cfg.qk_dim)}
    p.update({
        "wkv_a": dense_init(ka, d_model, cfg.kv_rank + cfg.rope_dim),
        "kv_norm": rms_init(cfg.kv_rank),
        "wkv_b": dense_init(kb, cfg.kv_rank,
                            n_heads * (cfg.nope_dim + cfg.v_dim)),
        "wo": dense_init(ko, n_heads * cfg.v_dim, d_model, stddev=out_std),
    })
    if cfg.qk_norm:
        p["q_norm"] = rms_init(cfg.qk_dim)
        p["k_norm"] = rms_init(cfg.qk_dim)
    if cfg.indexer is not None:
        ix = cfg.indexer
        kiq, kik, kiw = jax.random.split(jax.random.fold_in(key, 1), 3)
        p["indexer"] = {
            # index queries come from the queries' latent where there is one
            "wq": dense_init(kiq, cfg.q_rank or d_model, ix.heads * ix.dim),
            "wk": dense_init(kik, d_model, ix.dim),
            "k_norm": ln_init(ix.dim),
            "w_heads": dense_init(kiw, d_model, ix.heads),
        }
    return p


# blocks (queries, keys) of the flash kernel under ``causal_core``: the
# fastest on a v5e at [4, 64, 2048, 192 / 128] (PERF.md section 5, PR 34)
# and at [1, 64, 8192, 256 / 256] with a choice of keys (PR 39, forward,
# ms a call: 1,024 x 1,024 18.65; 512 x 1,024 20.77; 1,024 x 2,048 20.09;
# 2,048 x 1,024 19.33; 17.82 without the choice)
_CORE_KERNEL_BLOCKS = (1024, 1024)


def core_runs_the_kernel(backend: str, length: int, block: int) -> bool:
    """Whether :func:`causal_core` is one call of the Pallas kernel: on
    a TPU, over more than one of the kernel's blocks, which divide the
    length. Anywhere else (the CPU of the tests, a length that would be
    padded) it is the blocked plain computation."""
    return backend == "tpu" and length > block and length % block == 0


def core_is_the_kernel(backend: str, length: int) -> bool:
    """:func:`core_runs_the_kernel` at the blocks the core gives the
    kernel."""
    return core_runs_the_kernel(backend, length, max(_CORE_KERNEL_BLOCKS))


def blocked_causal_core(q, k, v, scale: float, block: int, chosen=None):
    """:func:`causal_core` in plain JAX: a block of ``block`` queries at
    a time against its causal prefix of keys. A block is under
    ``jax.checkpoint``: the backward recomputes its scores, so no
    ``[L, L]`` tensor is held a head, forward or backward. ``L <=
    block`` is the plain computation."""
    l = q.shape[2]

    def one(qb, kb, vb, cb, start):
        hq, hkv = qb.shape[1], kb.shape[1]
        scores, mix = "bhqd,bhkd->bhqk", "bhqk,bhkd->bhqd"
        if hq != hkv:
            # grouped heads: query head i on key-value head i // group,
            # the group an axis beside the heads, no key repeated
            qb = qb.reshape(qb.shape[0], hkv, hq // hkv, *qb.shape[2:])
            scores, mix = "bhgqd,bhkd->bhgqk", "bhgqk,bhkd->bhgqd"
            cb = None if cb is None else cb[:, None]
        s = jnp.einsum(scores, qb, kb,
                       preferred_element_type=jnp.float32) * scale
        seen = (start + jnp.arange(qb.shape[-2]))[:, None] \
            >= jnp.arange(kb.shape[2])[None, :]
        if cb is not None:
            seen = seen & (cb[:, None] != 0)
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        out = jnp.einsum(mix, p.astype(vb.dtype), vb)
        return out.reshape(out.shape[0], hq, *out.shape[-2:])

    if l <= block:
        return one(q, k, v, chosen, 0)
    one = jax.checkpoint(one, static_argnums=(4,))
    return jnp.concatenate(
        [one(q[:, :, s:s + block], k[:, :, :s + block], v[:, :, :s + block],
             None if chosen is None else chosen[:, s:s + block, :s + block],
             s) for s in range(0, l, block)], axis=2)


def _causal_core(q, k, v, scale: float, block: int, chosen=None):
    """Causal softmax attention ``[B, H, L, Dv]`` of ``q [B, H, L,
    Dk]``, ``k [B, Hkv, L, Dk]`` and ``v [B, Hkv, L, Dv]`` (``Dv`` as
    wide as ``Dk`` or narrower; ``H`` a multiple of ``Hkv``: query head
    ``i`` attends key-value head ``i // (H / Hkv)``), the scores and
    the softmax in float32, the probabilities
    cast to ``v``'s dtype. With ``chosen [B, L, L]`` (nonzero: query
    ``t`` may see key ``s``; one choice for all heads,
    :func:`chosen_keys`) the softmax runs over the chosen keys of the
    causal prefix alone; every query has to have chosen one.

    On a TPU (:func:`core_runs_the_kernel`) it is one call of
    ``ops/flash_attention.py``: a tile of scores lives in VMEM, forward
    and backward, and the kernel's ``custom_vjp`` keeps ``q, k, v``, the
    choice, the output and the log-sum-exp, no float ``[L, L]`` (the
    last two under names that a decoder block's checkpoint saves:
    ``llama.py::_checkpointed_block``); it
    visits every causal tile and masks what was not chosen. Elsewhere
    it is :func:`blocked_causal_core` in blocks of ``block`` queries."""
    block_q, block_k = _CORE_KERNEL_BLOCKS
    if core_is_the_kernel(jax.default_backend(), q.shape[2]):
        from baton_tpu.ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=block_q, block_k=block_k,
                               chosen=chosen)
    return blocked_causal_core(q, k, v, scale, block, chosen)


# one core under the scope of the mixer that calls it
causal_core = jax.named_scope("mla_core")(_causal_core)
cca_core = jax.named_scope("cca_core")(_causal_core)


# ------------------------------------------------- the keys a query chose
# (DeepSeek-V3.2-Exp's sparse attention: a lightning indexer scores every
# causal pair, a query attends its ``topk`` best keys)


@jax.named_scope("indexer")
def index_scores(p, x, q_in, cfg: MLAConfig, rope):
    """The index scores ``I [B, L, L]`` in float32, ``-inf`` where key
    ``s`` lies after query ``t``::

        I[t, s] = sum_j w[t, j] ReLU(q_I[t, j] . k_I[s])

    with ``q_I = q_in W_q`` (``heads`` index queries of ``dim``), ``k_I
    = LayerNorm(x W_k)`` (one key a token), ``w = x W_heads heads^-1/2
    dim^-1/2``, the first ``rope_dim`` channels of queries and key
    turned by ``rope``. The products are of ``x``'s dtype and summed in
    float32, a block of ``cfg.block`` queries against its causal prefix
    at a time (``[heads, block, L]`` is what is held). ``p`` is the
    mixer's ``indexer`` group, ``q_in`` the queries' latent (``x``
    where there is none)."""
    ix = cfg.indexer
    b, l, _ = x.shape
    cos, sin = rope

    def turned(y):  # [..., L, dim]
        return jnp.concatenate(
            [apply_rope(y[..., :ix.rope_dim], cos, sin),
             y[..., ix.rope_dim:]], axis=-1)

    q = turned((q_in @ p["wq"].astype(x.dtype))
               .reshape(b, l, ix.heads, ix.dim).transpose(0, 2, 1, 3))
    k = turned(layer_normalize(x @ p["wk"].astype(x.dtype), p["k_norm"],
                               cfg.norm_eps))
    w = jnp.einsum("bld,dh->bhl", x, p["w_heads"].astype(x.dtype),
                   preferred_element_type=jnp.float32) \
        * (ix.heads ** -0.5 * ix.dim ** -0.5)
    rows = []
    for s in range(0, l, cfg.block):
        e = min(s + cfg.block, l)
        hit = jax.nn.relu(jnp.einsum(
            "bhqd,bkd->bhqk", q[:, :, s:e], k[:, :e],
            preferred_element_type=jnp.float32))
        score = jnp.sum(hit * w[:, :, s:e, None], axis=1)
        seen = jnp.arange(s, e)[:, None] >= jnp.arange(e)[None, :]
        score = jnp.where(seen, score, -jnp.inf)
        rows.append(jnp.pad(score, ((0, 0), (0, 0), (0, l - e)),
                            constant_values=-jnp.inf))
    return jnp.concatenate(rows, axis=1)


def _ordered_bits(scores):
    """Float32 as uint32 in the same order (no NaN; -0.0, which a sum
    of nothing but -0.0 is, counts as the 0.0 it equals)."""
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, 0.0, scores), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


@jax.named_scope("index_select")
def select_keys(scores, topk: int):
    """``(tau, cut)``, each ``[B, L]``: query ``t`` chooses the ``topk``
    keys of largest ``scores[t]`` among its causal prefix (``scores
    [B, L, L]``, ``-inf`` past it), equal scores to the lower index,
    and all of the prefix while it holds no more than ``topk``. The
    choice is the keys that score above ``tau`` and those that score
    ``tau`` at an index up to ``cut`` (:func:`chosen_keys`): exactly
    ``min(t + 1, topk)`` a query.

    ``tau`` is found without a sort, by bisection over the ordered bits
    of a float: 32 counting passes over ``scores`` fix the ``topk``-th
    largest value bit by bit from the top, ``log2 L`` more the index at
    which the keys that equal it run out (a sort of ``[L, L]`` is some
    ``log2(L)^2 / 2`` compare-exchange passes, each read and written:
    PERF.md section 6, PR 39, has both on the chip)."""
    b, l, _ = scores.shape
    keys = _ordered_bits(scores)
    at = jnp.arange(l, dtype=jnp.int32)

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    def value_bit(i, found):
        trial = found | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = count(keys >= trial[..., None]) >= topk
        return jnp.where(enough, trial, found)

    kth = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((b, l), jnp.uint32))
    equal = keys == kth[..., None]
    wanted = topk - count(keys > kth[..., None])
    n_bits = max(l - 1, 1).bit_length()

    def index_bit(i, found):
        trial = found | (jnp.int32(1 << (n_bits - 1)) >> i)
        short = count(equal & (at < trial[..., None])) < wanted
        return jnp.where(short, trial, found)

    cut = jax.lax.fori_loop(0, n_bits, index_bit,
                            jnp.zeros((b, l), jnp.int32))
    tau = jax.lax.bitcast_convert_type(
        jnp.where(kth >> 31 == 1, kth & jnp.uint32(0x7fffffff), ~kth),
        jnp.float32)
    whole = at < topk  # the prefix of query t holds t + 1 keys
    return (jnp.where(whole, -jnp.inf, tau),
            jnp.where(whole, l - 1, cut))


@jax.named_scope("index_select")
def chosen_keys(scores, tau, cut):
    """``[B, L, L]`` int8, 1 where query ``t`` chose key ``s``: what
    :func:`select_keys` found, laid over the causal rule."""
    at = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    t, c = tau[..., None], cut[..., None]
    chosen = (scores > t) | ((scores == t) & (at <= c))
    return (chosen & (at[:, None] >= at[None, :])).astype(jnp.int8)


def _a_client_at_a_time(fn):
    """``fn`` as a ``custom_vmap`` function whose rule is a ``lax.map``
    over the mapped axis: under ``FedSim``'s client ``vmap`` the arrays
    of one client are live at a time (at 8,192 tokens the queries, keys
    and values of four clients are 3 x 1.07 GB a layer and direction),
    and what carries no client axis (the frozen base) is read where it
    lies."""
    wrapped = custom_vmap(fn)

    @wrapped.def_vmap
    def rule(axis_size, in_batched, *args):
        flat, tree = jax.tree_util.tree_flatten(args)
        mapped = jax.tree_util.tree_leaves(in_batched)

        def one(rows):
            rows = iter(rows)
            return fn(*jax.tree_util.tree_unflatten(tree, [
                next(rows) if m else a for a, m in zip(flat, mapped)]))

        out = jax.lax.map(one, [a for a, m in zip(flat, mapped) if m])
        return out, jax.tree_util.tree_map(lambda _: True, out)

    return wrapped


def cut_columns(w, n_heads: int, widths, made=None):
    """``w [in, n_heads * sum(widths)]`` seen as ``[in, n_heads,
    sum(widths)]`` and cut along the last axis: ``[in, n_heads *
    width]`` a part, or ``n_heads * made[i]`` where ``made`` names a
    wider part: zero columns after each head's own."""
    heads = w.reshape(w.shape[0], n_heads, sum(widths))
    edges = [sum(widths[:i]) for i in range(len(widths) + 1)]
    parts = []
    for start, width, wide in zip(edges, widths, made or widths):
        part = heads[..., start:start + width]
        if wide != width:
            part = jnp.pad(part, ((0, 0), (0, 0), (0, wide - width)))
        parts.append(part.reshape(w.shape[0], -1))
    return parts


def contract_heads(x, w, n_heads: int):
    """``x [B, H, L, v]`` times ``w [H * v, n]`` seen as ``[H, v, n]``:
    ``[B, L, n]``, contracted over ``(head, channel)`` where ``x``
    lies. A weight that applies itself (``lora.py::Adapted``) does."""
    apply_to = getattr(w, "heads_apply_to", None)
    if apply_to is not None:
        return apply_to(x, n_heads)
    return jnp.einsum("bhlv,hvn->bln", x,
                      w.reshape(n_heads, -1, w.shape[-1]))


def _projected_parts(x, w, n_heads: int, widths, sequence_minor=(),
                     made=None):
    """``x [B, L, in]`` through the parts of ``w``'s columns
    (:func:`cut_columns`, with its ``made``; a weight that applies
    itself cuts itself and makes its low-rank product once for all
    parts: ``lora.py::Adapted.columns``), a product a part: ``[B, H,
    L, width]``, or for a part that ``sequence_minor`` names ``[B, H,
    width, L]``, the sequence minor in memory too. The weight is cut,
    never the activation: a part comes out of its product as its
    consumer reads it, and an adapter's term beside it in the same
    layout."""
    w = w.astype(x.dtype)

    def into(transposed: bool):
        def product(x, part, preferred_element_type=None):
            y = jnp.einsum("bld,dhk->bhkl" if transposed else "bld,dhk->bhlk",
                           x, part.reshape(part.shape[0], n_heads, -1),
                           preferred_element_type=preferred_element_type)
            # XLA's layout assignment does not carry the kernel's
            # layout back through the product on its own
            return with_layout_constraint(
                y, Layout(major_to_minor=(0, 1, 2, 3))) if transposed else y
        return product

    products = [into(i in sequence_minor) for i in range(len(widths))]
    if hasattr(w, "columns"):
        low = w.low_rank(x)
        return [part.apply_to(x, low=low, product=product)
                for part, product in zip(w.columns(n_heads, widths, made),
                                         products)]
    return [product(x, part) for part, product
            in zip(cut_columns(w, n_heads, widths, made), products)]


def mla_qk_layout(cfg: MLAConfig, backend: str, length: int) -> str:
    """How :func:`_mla` lays out the queries and keys it makes:
    ``"channel_major"``, ``[B, H, Dk, L]``, where its core is the flash
    kernel and that kernel takes them so (a width that does not fill
    its last lane tile, 192: ``flash_attention._keys_ride_sublanes``),
    else ``"row_major"``, ``[B, H, L, Dk]``, as the kernel takes whole
    lane tiles (256) and the plain blocked core anything."""
    from baton_tpu.ops.flash_attention import _keys_ride_sublanes

    return ("channel_major" if core_is_the_kernel(backend, length)
            and _keys_ride_sublanes(cfg.qk_dim) else "row_major")


def _mla(p, x, rope, choice, n_heads: int, cfg: MLAConfig, pre_norm=None):
    """The mixer itself: ``(y, choice)``. ``choice`` is ``(tau, cut)``
    of :func:`select_keys`, made here where it is None and the
    sequence has a query that chooses (``cfg.selects``), else None.

    Each operand of the core is made once, by a product that writes
    what the core reads (:func:`mla_qk_layout`: q and k ``[B, H, Dk,
    L]`` where the kernel takes them with the sequence minor, else
    ``[B, H, L, Dk]``; v ``[B, H, L, Dv]``):

    * the frozen weights are cut, not the activations: ``wq`` (or
      ``wq_b``), seen as ``[in, H, nope + rope]``, gives ``q_nope`` and
      ``q_rope``, and ``wkv_b``, seen as ``[kv_rank, H, nope + v]``,
      gives ``k_nope`` and ``v`` (:func:`_projected_parts`);
    * the rotary parts are turned alone, always with the sequence
      minor (``[B, H, rope, L]``: the rotation's halves are sublane
      tiles and every pass is full across the lanes): ``q_rope``, and
      the shared rotary key once, at ``[B, 1, rope, L]``, before it is
      given to the heads. Under ``qk_norm`` a head's statistic is the
      sum of its two parts' squares, and the head's scalar multiplies
      the once-turned shared key (a scalar commutes with the rotation;
      the norm's scale vector is applied before it);
    * the matrix unit puts the turned rotary channels where they
      belong: a product with the ``[rope, Dk]`` matrix that holds a one
      at ``(i, nope + i)`` writes them into channels ``nope ...`` of an
      array of q's or k's shape, exactly (one term a sum), in the
      core's layout, and the nope part, padded and under ``qk_norm``
      normed, is added in that product's epilogue: no concatenation,
      no array whose minor dimension is a rotary half. Where nothing
      stands between ``k_nope``'s product and k (no ``qk_norm``) that
      product is ``Dk`` wide itself (zero columns of the weight) and
      the shared key is added in its epilogue;
    * ``wo`` reads the core's output where it lies
      (:func:`contract_heads`)."""
    if pre_norm is not None:
        x = rms_normalize(x, pre_norm["scale"], cfg.norm_eps)
    l = x.shape[1]
    nope, rope_dim = cfg.nope_dim, cfg.rope_dim
    channel_major = mla_qk_layout(
        cfg, jax.default_backend(), l) == "channel_major"
    # the channels' axis of q and k in the core's layout
    axis = -2 if channel_major else -1
    cos, sin = (a.T for a in rope)  # [rope / 2, L]: against [.., rope, L]

    def widened(y):
        """The nope part with zeros for the rotary channels."""
        pad = [(0, 0)] * y.ndim
        pad[axis] = (0, rope_dim)
        return jnp.pad(y, pad)

    def nope_scale(scale):
        """A norm's scale vector against :func:`widened`'s array: its
        nope entries, zeros for the rotary channels."""
        scale = jnp.pad(scale[:nope], (0, rope_dim))
        return scale[:, None] if channel_major else scale

    def turned(y):
        """:func:`apply_rope`'s rotation of a rotary part ``[.., rope,
        L]``, in float32."""
        y1, y2 = (half.astype(jnp.float32)
                  for half in jnp.split(y, 2, axis=-2))
        return jnp.concatenate([y1 * cos - y2 * sin, y2 * cos + y1 * sin],
                               axis=-2)

    def placed(y):
        """``y [B, h, rope, L]`` as channels ``nope ...`` of an array
        of the core's layout, zeros before them: exact on the matrix
        unit (every sum has one term)."""
        place = jnp.eye(rope_dim, cfg.qk_dim, k=nope, dtype=y.dtype)
        return jnp.einsum(
            "bhkl,kn->bhnl" if channel_major else "bhkl,kn->bhln", y, place,
            precision=jax.lax.Precision.HIGHEST)

    def squares(y, over):
        return jnp.sum(y * y, axis=over, keepdims=True)

    def head_scalars(y_nope, y_rope):
        """The reciprocal root of each head's mean square over both its
        parts, against the nope part in the core's layout and against
        the rotary part ``[B, h, 1, L]``."""
        nope_squares = squares(y_nope.astype(jnp.float32), axis)
        if not channel_major:
            nope_squares = nope_squares.swapaxes(2, 3)
        r = jax.lax.rsqrt(
            (nope_squares + squares(y_rope.astype(jnp.float32), -2))
            / cfg.qk_dim + cfg.norm_eps)
        return (r if channel_major else r.swapaxes(2, 3)), r

    # the parts made with the sequence minor: the rotary ones always
    minor = (0, 1) if channel_major else (1,)
    if cfg.q_rank is None:
        q_in, wq = x, p["wq"]
    else:
        q_in = rms_normalize(x @ p["wq_a"].astype(x.dtype),
                             p["q_a_norm"]["scale"], cfg.norm_eps)
        wq = p["wq_b"]
    q_nope, q_rope = _projected_parts(q_in, wq, n_heads, (nope, rope_dim),
                                      minor)
    c = x @ p["wkv_a"].astype(x.dtype)
    latent = rms_normalize(c[..., :cfg.kv_rank], p["kv_norm"]["scale"],
                           cfg.norm_eps)
    # the shared rotary key, [B, 1, rope, L]
    k_rope = c[:, None, :, cfg.kv_rank:].swapaxes(2, 3).astype(jnp.float32)
    if cfg.qk_norm:
        # the values are the core's row-major operand in either layout
        k_nope, v = _projected_parts(latent, p["wkv_b"], n_heads,
                                     (nope, cfg.v_dim), minor[:-1])
        q_scale, k_scale = p["q_norm"]["scale"], p["k_norm"]["scale"]
        r, r_rope = head_scalars(q_nope, q_rope)
        # (the normed rotary part is rounded before it is turned, as
        # the normed query was)
        q_rope = (q_rope.astype(jnp.float32) * r_rope
                  * q_scale[nope:, None]).astype(x.dtype)
        q = (widened(q_nope).astype(jnp.float32) * r
             * nope_scale(q_scale)).astype(x.dtype) \
            + placed(turned(q_rope).astype(x.dtype))
        r, _ = head_scalars(k_nope, k_rope)
        k = ((widened(k_nope).astype(jnp.float32) * nope_scale(k_scale)
              + placed(turned(k_rope * k_scale[nope:, None]))) * r).astype(
                  x.dtype)
    else:
        k_nope, v = _projected_parts(
            latent, p["wkv_b"], n_heads, (nope, cfg.v_dim), minor[:-1],
            made=(cfg.qk_dim, cfg.v_dim))
        q = widened(q_nope) + placed(turned(q_rope).astype(x.dtype))
        k = k_nope + placed(turned(k_rope).astype(x.dtype))
    if channel_major:  # views the kernel turns back for nothing
        q, k = q.swapaxes(2, 3), k.swapaxes(2, 3)

    chosen = None
    if cfg.selects(l):
        # nothing differentiates the index or the choice (V3.2 trains
        # its indexer by a loss of its own, on detached inputs; over a
        # frozen base nothing trains it)
        scores = index_scores(
            *jax.lax.stop_gradient((p["indexer"], x, q_in)), cfg, rope)
        if choice is None:
            choice = select_keys(scores, cfg.indexer.topk)
        chosen = chosen_keys(scores, *choice)
    out = causal_core(q, k, v, cfg.softmax_scale, cfg.block, chosen)
    return contract_heads(out, p["wo"].astype(x.dtype), n_heads), choice


@functools.lru_cache(maxsize=None)
def _choosing_mla(n_heads: int, cfg: MLAConfig):
    """:func:`_mla` where queries choose their keys, as a ``custom_vjp``
    whose two directions run a client at a time. The forward keeps the
    mixer's inputs and the choice (a threshold and an index a query),
    nothing else; the backward makes the mixer again with that choice
    (the index scores are computed again, nothing is selected twice)
    and differentiates it. A decoder block therefore leaves this mixer
    out of its ``remat`` (``llama.py``)."""
    forward = _a_client_at_a_time(
        lambda p, x, rope, pre_norm: _mla(p, x, rope, None, n_heads, cfg,
                                          pre_norm))

    def pullback(p, x, rope, pre_norm, choice, dy):
        _, back = jax.vjp(
            lambda p, x, pre_norm: _mla(p, x, rope, choice, n_heads, cfg,
                                        pre_norm)[0], p, x, pre_norm)
        return back(dy)

    backward = _a_client_at_a_time(pullback)

    @jax.custom_vjp
    def apply(p, x, rope, pre_norm):
        return forward(p, x, rope, pre_norm)[0]

    def fwd(p, x, rope, pre_norm):
        y, choice = forward(p, x, rope, pre_norm)
        return y, (p, x, rope, pre_norm, choice)

    def bwd(res, dy):
        d_p, d_x, d_norm = backward(*res, dy)
        return d_p, d_x, None, d_norm

    apply.defvjp(fwd, bwd)
    return apply


@jax.named_scope("latent_attention")
def mla_apply(p, x, n_heads: int, cfg: MLAConfig, rope, pre_norm=None):
    """Latent attention over ``x [B, L, D] -> [B, L, D]``, causal.
    ``rope`` is :func:`mla_rope_angles`'s pair. The rotation pairs
    channel ``i`` of the rotary part with ``i + rope_dim / 2``
    (:func:`apply_rope`). ``pre_norm``: the RMSNorm that stands before
    the mixer, applied here (so that a mixer that keeps its own inputs
    for the backward keeps the block's and not a normalised copy too).

    q, k and v are made once each, where the core reads them
    (:func:`_mla`): the nope and the rotary parts of q, ``k_nope`` and
    v are a product each of a part of a weight's columns, the rotary
    parts are turned alone and the shared rotary key once, the matrix
    unit places the turned channels beside the nope part in the core's
    layout, and ``wo`` contracts over ``(head, channel)`` of the core's
    output; no wide activation is cut, joined, padded to the lanes or
    copied into another layout on the way.

    Where the configuration has an indexer and the sequence is longer
    than its ``topk`` (``cfg.selects``), a query attends the keys
    :func:`select_keys` chose for it, the mixer runs a client at a time
    under a client ``vmap`` and recomputes itself in the backward
    (:func:`_choosing_mla`). Anywhere else this is the mixer it was."""
    if cfg.selects(x.shape[1]):
        return _choosing_mla(n_heads, cfg)(p, x, rope, pre_norm)
    return _mla(p, x, rope, None, n_heads, cfg, pre_norm)[0]


def mla_core_is_kernel(cfg: MLAConfig, backend: str, length: int) -> bool:
    """Whether :func:`mla_apply`'s core is the flash kernel where a
    checkpoint around the caller sees it: not where queries choose
    (:func:`_choosing_mla` keeps its own residuals behind a
    ``custom_vjp``), else where :func:`causal_core` is the kernel."""
    return not cfg.selects(length) and core_is_the_kernel(backend, length)


# ---------------------------------------------------------------------------
# compressed convolutional attention (CCA: Zyphra, arXiv:2510.04476)


@dataclasses.dataclass(frozen=True)
class CCAConfig:
    """Attention that never leaves its latent: ``n_heads`` query heads
    and ``n_kv_heads`` key heads of ``head_dim`` come from one
    projection each (``n_heads * head_dim`` is narrower than the
    model), are mixed along the sequence by two causal convolutions
    over the joined channels (``time0`` taps a channel, then ``time1``
    taps a head), and the output projection widens the heads' outputs
    to the model again. The first ``rope_dim`` channels of a head are
    rotated."""

    n_heads: int = 8
    n_kv_heads: int = 2
    head_dim: int = 128
    time0: int = 2
    time1: int = 2
    rotary_factor: float = 0.5
    rope_theta: float = 5e6
    # queries a block of the core where it is plain JAX
    # (:func:`blocked_causal_core`)
    block: int = 512

    def __post_init__(self):
        if self.n_kv_heads != 2 or self.n_heads % 2:
            raise NotImplementedError(
                "the two value heads are the token's own values and the "
                "token before's: two key-value heads, an even number of "
                "query heads")

    @property
    def rope_dim(self) -> int:
        return int(self.head_dim * self.rotary_factor)

    @property
    def latent_q(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def latent_kv(self) -> int:
        return self.n_kv_heads * self.head_dim


def cca_init(key, d_model: int, cfg: CCAConfig, out_std=None):
    """The convolutions' biases are drawn at deviation 0.02, not zeros:
    a test has to tell a term that is applied from one left out. The
    keys' temperatures are drawn uniform in [1.5, 4.5]: random weights
    stand for trained ones and trained attention is peaked. The scores
    of random unit-length queries and keys have the temperature's
    deviation; near 1 the softmax over thousands of keys is flat, a
    query's output is the mean of its prefix's values and next to no
    gradient reaches the mixer's adapters."""
    kq, kk, kv1, kv2, ko, kc0, kc1, kb0, kb1, kt = jax.random.split(key, 10)
    d, heads = cfg.head_dim, cfg.n_heads + cfg.n_kv_heads
    return {
        "linear_q": dense_init(kq, d_model, cfg.latent_q),
        "linear_k": dense_init(kk, d_model, cfg.latent_kv),
        "val_proj1": dense_init(kv1, d_model, d),
        "val_proj2": dense_init(kv2, d_model, d),
        "o_proj": dense_init(ko, cfg.latent_q, d_model, stddev=out_std),
        # a channel's taps, oldest first; then a head's [d, d] a tap
        "conv0_w": normal_init(kc0, (cfg.time0, heads * d),
                               cfg.time0 ** -0.5),
        "conv0_b": normal_init(kb0, (heads * d,), 0.02),
        "conv1_w": normal_init(kc1, (cfg.time1, heads, d, d),
                               (cfg.time1 * d) ** -0.5),
        "conv1_b": normal_init(kb1, (heads * d,), 0.02),
        "temp": jax.random.uniform(kt, (cfg.n_kv_heads,), jnp.float32,
                                   1.5, 4.5),
    }


def _shifted(y, by: int = 1):
    """``y [B, L, ...]`` a token later: row ``t`` holds row ``t - by``,
    the first ``by`` rows zeros."""
    pad = [(0, 0)] * y.ndim
    pad[1] = (by, 0)
    return jnp.pad(y, pad)[:, :y.shape[1]]


def cca_convolve(p, u, cfg: CCAConfig):
    """The two causal convolutions over ``u [B, L, C]`` (``C`` the
    joined query and key channels) in float32, the second one's products
    of operands in ``u``'s dtype: ``time0 - 1 + time1 -
    1`` zeros stand before the sequence, then, neither padded again,

        y[t] = sum_j w0[j] * u[t - (time0 - 1) + j] + b0   a channel
        z[t] = sum_j y[t - (time1 - 1) + j] W1[j] + b1     a head, [d, d]

    so the second one's oldest tap sees ``b0`` before the sequence, as
    a padded input through two unpadded convolutions gives it. Written
    as slices of the padded sequence, products and one ``einsum`` a
    head with the taps joined in the contraction, not as ``lax.conv``:
    under a client ``vmap`` that would be a grouped convolution over
    the clients."""
    b, l, c = u.shape
    d, dtype = cfg.head_dim, u.dtype
    t0, t1 = cfg.time0, cfg.time1
    u = jnp.pad(u.astype(jnp.float32), ((0, 0), (t0 + t1 - 2, 0), (0, 0)))
    w0 = p["conv0_w"].astype(jnp.float32)
    n = l + t1 - 1
    y = sum(w0[j] * u[:, j:j + n] for j in range(t0)) + p["conv0_b"]
    # the heads lead: a batched product as every backend has it
    y = jnp.moveaxis(y.reshape(b, n, c // d, d), 2, 0)
    taps = jnp.concatenate([y[:, :, j:j + l] for j in range(t1)], axis=-1)
    w1 = jnp.moveaxis(p["conv1_w"], 0, 1).reshape(c // d, t1 * d, d)
    z = jnp.einsum("hblk,hkd->hbld", taps.astype(dtype), w1.astype(dtype),
                   preferred_element_type=jnp.float32)
    return jnp.moveaxis(z, 0, 2).reshape(b, l, c) + p["conv1_b"]


# under the root of a head's squared length: a guard for a vector of
# zeros, far under any latent's
_UNIT_EPS = 1e-12


@jax.named_scope("compressed_attention")
def cca_apply(p, x, cfg: CCAConfig, rope):
    """Compressed convolutional attention over ``x [B, L, D] -> [B, L,
    D]``, causal; ``rope`` is ``rope_angles(L, cfg.rope_dim,
    cfg.rope_theta)``. With ``g`` query heads a key head::

        q~, k~ = x W_q, x W_k                        the latents
        q^, k^ = split(conv1(conv0([q~ ; k~])))      :func:`cca_convolve`
        q = q^ + (q~ + repeat_g(k~)) / 2
        k = k^ + (mean over its g query heads of q~ + k~) / 2
        v = [x_t W_v1 ; x_{t-1} W_v2]                a head each
        q, k = sqrt(d) q / |q|,  temp sqrt(d) k / |k|   a head, float32
        the first rope_dim channels of q and k rotated
        y = softmax_causal(q k^T / sqrt(d)) v W_o    query head i on key
                                                     head i // g

    Everything between the projections is ``latent_q`` or ``latent_kv``
    wide. The core is :func:`cca_core` (the flash kernel with grouped
    heads on a TPU, the blocked plain computation elsewhere)."""
    b, l, _ = x.shape
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q_lat = x @ p["linear_q"].astype(x.dtype)
    k_lat = x @ p["linear_k"].astype(x.dtype)
    v_own = x @ p["val_proj1"].astype(x.dtype)
    v_before = x @ p["val_proj2"].astype(x.dtype)
    with jax.named_scope("cca_mix"):
        mixed = cca_convolve(p, jnp.concatenate([q_lat, k_lat], -1), cfg)
        q_lat = q_lat.astype(jnp.float32).reshape(b, l, hkv, hq // hkv, d)
        k_lat = k_lat.astype(jnp.float32).reshape(b, l, hkv, 1, d)
        q = mixed[..., :hq * d].reshape(q_lat.shape) \
            + 0.5 * (q_lat + k_lat)
        k = mixed[..., hq * d:].reshape(k_lat.shape) \
            + 0.5 * (jnp.mean(q_lat, axis=3, keepdims=True) + k_lat)

        def unit(y):  # sqrt(d) y / |y|
            return y * jax.lax.rsqrt(
                jnp.mean(y * y, axis=-1, keepdims=True) + _UNIT_EPS)

        def heads(y, n):  # [B, n, L, d], the rotary part turned
            y = y.reshape(b, l, n, d).transpose(0, 2, 1, 3)
            return jnp.concatenate(
                [apply_rope(y[..., :cfg.rope_dim], *rope),
                 y[..., cfg.rope_dim:]], axis=-1).astype(x.dtype)

        q = heads(unit(q), hq)
        k = heads(unit(k) * p["temp"][:, None, None], hkv)
        # h_{t-1} W_v2 is (h W_v2) a token later
        v = jnp.stack([v_own, _shifted(v_before)], axis=1)
    out = cca_core(q, k, v, d ** -0.5, cfg.block)
    out = out.transpose(0, 2, 1, 3).reshape(b, l, hq * d)
    return out @ p["o_proj"].astype(x.dtype)


def cca_core_is_kernel(backend: str, length: int) -> bool:
    """Whether :func:`cca_apply`'s core, :func:`cca_core`, is the flash
    kernel."""
    return core_is_the_kernel(backend, length)


# ---------------------------------------------------------------------------
# MLPs


def gelu_mlp_init(key, d_model, d_ff):
    k1, k2 = jax.random.split(key)
    return {
        "w1": dense_init(k1, d_model, d_ff),
        "b1": jnp.zeros((d_ff,), jnp.float32),
        "w2": dense_init(k2, d_ff, d_model),
        "b2": jnp.zeros((d_model,), jnp.float32),
    }


@jax.named_scope("mlp")
def gelu_mlp_apply(p, x):
    h = x @ p["w1"].astype(x.dtype) + p["b1"].astype(x.dtype)
    h = jax.nn.gelu(h)
    return h @ p["w2"].astype(x.dtype) + p["b2"].astype(x.dtype)


def swiglu_init(key, d_model, d_ff, multipliers=(1.0, 1.0)):
    """``multipliers``: :func:`swiglu`'s, each drawn against
    (:class:`Multipliers`)."""
    kg, ku, kd = jax.random.split(key, 3)
    on_gate, on_down = multipliers
    return {
        "w_gate": dense_init(kg, d_model, d_ff, d_model ** -0.5 / on_gate),
        "w_up": dense_init(ku, d_model, d_ff),
        "w_down": dense_init(kd, d_ff, d_model, d_ff ** -0.5 / on_down),
    }


def swiglu(p, x, multipliers=(1.0, 1.0)):
    """``on_down ((silu(on_gate (x W_gate)) * (x W_up)) W_down)``."""
    on_gate, on_down = multipliers
    g = jax.nn.silu(scaled(x @ p["w_gate"].astype(x.dtype), on_gate))
    u = x @ p["w_up"].astype(x.dtype)
    return scaled((g * u) @ p["w_down"].astype(x.dtype), on_down)


swiglu_apply = jax.named_scope("mlp")(swiglu)


# ---------------------------------------------------------------------------
# pre-LN encoder block (shared by BERT and ViT)


def prenorm_block_init(key, d_model, n_heads, d_ff):
    ka, km = jax.random.split(key)
    return {
        "ln1": ln_init(d_model),
        "attn": mha_init(ka, d_model, n_heads),
        "ln2": ln_init(d_model),
        "mlp": gelu_mlp_init(km, d_model, d_ff),
    }


def prenorm_block_apply(p, x, n_heads, bias=None,
                        attention_fn: AttentionFn = default_attention):
    x = x + mha_apply(p["attn"], layer_norm(x, p["ln1"]), n_heads,
                      bias=bias, attention_fn=attention_fn)
    return x + gelu_mlp_apply(p["mlp"], layer_norm(x, p["ln2"]))


# ---------------------------------------------------------------------------
# per-token LM loss (used by llama.py and lstm.py; model-generic)


def matmul(x, w, preferred_element_type=None):
    """``x [..., d] @ w [d, n]`` with ``w`` in ``x``'s dtype and the
    result in ``preferred_element_type`` (float32 for a head's logits:
    bf16 operands, fp32 accumulation). A weight that applies itself
    (``models/lora.py::Adapted``) does."""
    w = w.astype(x.dtype)
    apply_to = getattr(w, "apply_to", None)
    if apply_to is not None:
        return apply_to(x, preferred_element_type)
    return jnp.matmul(x, w, preferred_element_type=preferred_element_type)


def _cross_entropy_parts(logits, labels):
    """:func:`per_token_cross_entropy` with what it was made of: the
    loss ``[B, L]``, the float32 logits, their log-sum-exp ``[B, L]``
    and the mask ``[B, L, V]`` of each token's own id."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    own = ids == labels[..., None]
    ll = jnp.sum(jnp.where(own, logits, 0.0), axis=-1)
    return logz - ll, logits, logz, own


def per_token_cross_entropy(logits, labels):
    """logits [B, L, V], labels int32 [B, L] -> fp32 [B, L].

    ``labels`` are ids in ``[0, V)``. A token's own logit is picked by
    comparing an iota over the vocabulary with its label and summing
    the one term left, not by a gather: a gather's transpose is a
    scatter, which a TPU runs on a flat copy of the whole ``[B, L, V]``
    cotangent, while the comparison's is a ``where`` that fuses into
    the softmax's gradient. A label outside ``[0, V)`` matches no id,
    so its logit reads 0 and the loss is the log-sum-exp alone (the
    gather filled with NaN past the end and wrapped a negative one)."""
    return _cross_entropy_parts(logits, labels)[0]


# the float32 logits the head and the loss hold at a time: at a
# six-figure vocabulary ``[B, L, V]`` whole is the activation peak of a
# step (and under a client ``vmap`` once a client)
_LOGITS_BLOCK_BYTES = 128 * 1024 ** 2


def logits_held(b: int, l: int, vocab: int) -> int:
    """The bytes of float32 logits :func:`next_token_loss` holds at a
    time over ``[b, l]`` tokens."""
    return min(4 * b * l * vocab, _LOGITS_BLOCK_BYTES)


def tied_logits(x, table):
    """``x [..., D]`` against the embedding ``table [V, D]`` itself,
    float32: the head of a model whose embeddings are tied, contracted
    over the table's second axis where it lies (no ``[D, V]`` copy)."""
    return jnp.einsum("...d,vd->...v", x, table.astype(x.dtype),
                      preferred_element_type=jnp.float32)


# what the last call of ``next_token_loss`` was traced as: the products
# over a block's logits that its gradient makes, 2 where the head took
# no gradient, 3 where it did, None where no gradient of a blocked loss
# was traced (one block, or values alone)
_LOSS_TRACED = {"head_products": None}


def head_products_a_block():
    """What the last :func:`next_token_loss` traced learned of its
    gradient (a model's ``span_attrs`` read it straight after their
    call): 2, 3 or None."""
    return _LOSS_TRACED["head_products"]


@jax.named_scope("lm_loss")
def next_token_loss(x, w_head, labels, tied: bool = False,
                    multiplier: float = 1.0):
    """Per-token cross-entropy ``[B, L]`` (fp32) of the head ``x [B, L,
    D] @ w_head [D, V]`` (``tied``: ``w_head`` is the embedding ``[V,
    D]``, :func:`tied_logits`; the logits times ``multiplier``,
    :class:`Multipliers`) against ``labels [B, L]``, what
    :func:`per_token_cross_entropy` gives for the whole ``[B, L, V]``
    logits, computed in blocks of tokens: a ``lax.scan`` over the
    fewest equal blocks whose logits stay under
    ``_LOGITS_BLOCK_BYTES``. One block (a small vocabulary) is the
    plain computation. ``labels`` are ids in ``[0, V)`` (one outside
    reads a logit of 0: :func:`per_token_cross_entropy`); the tail
    block's padding carries label 0, which is in range.

    The gradient is a ``custom_vjp`` that looks at one thing, whether
    ``w_head`` is being differentiated. Where it is not (a frozen head
    under adapters), a token's loss depends on its own row of ``x``
    alone, so ``dx`` is the cotangent ``g [B, L]`` times a row that
    needs nothing of the backward: the forward's scan makes ``(softmax
    - onehot) @ w_head^T`` beside each block's logits and keeps it, one
    float32 array of ``x``'s shape, and the backward is that array times
    ``g``: two products over a block's logits and one log-sum-exp, no
    block made again. Where the head takes a gradient, rows with
    different ``g`` mix in it: each block is under ``jax.checkpoint``
    and the backward recomputes its logits instead of keeping every
    block's, three products."""
    b, l, d = x.shape
    _LOSS_TRACED["head_products"] = None

    def logits(xb, w):
        return scaled(tied_logits(xb, w) if tied
                      else matmul(xb, w, jnp.float32), multiplier)

    def block(xb, yb, w, slope=False):
        tok, z, logz, own = _cross_entropy_parts(logits(xb, w), yb)
        if not slope:
            return tok
        # the loss's derivative by the logits, in the stream's dtype as
        # the head's other operands are, back through the head
        p = jnp.exp(z - logz[..., None])
        p = jnp.where(own, p - 1.0, p).astype(xb.dtype)
        back = jnp.einsum("...v,vd->...d" if tied else "...v,dv->...d", p,
                          w.astype(xb.dtype),
                          preferred_element_type=jnp.float32)
        return tok, back * multiplier

    vocab = w_head.shape[0 if tied else 1]
    n = min(l, -(-(4 * b * l * vocab) // _LOGITS_BLOCK_BYTES))
    if n <= 1:
        return block(x, labels, w_head)
    t = -(-l // n)

    def blocks(a):  # [B, L, ...] -> [n, B, t, ...], the tail padded
        if n * t != l:
            a = jnp.pad(a, ((0, 0), (0, n * t - l)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(b, n, t, *a.shape[2:]), 1, 0)

    def whole(a):  # and back, the padding cut off again
        return jnp.moveaxis(a, 0, 1).reshape(b, n * t, *a.shape[3:])[:, :l]

    def over_blocks(fn, xs, w, ys):
        _, out = jax.lax.scan(lambda _, xy: (None, fn(*xy, w)), None,
                              (blocks(xs), blocks(ys)))
        return jax.tree.map(whole, out)

    def primal(xs, w, ys):
        return over_blocks(jax.checkpoint(block), xs, w, ys)

    def fwd(xs, w, ys):
        takes_gradient = any(a.perturbed for a in jax.tree.leaves(w))
        xs, w, ys = jax.tree.map(lambda a: a.value, (xs, w, ys))
        # a weight that applies itself offers no product back to the
        # stream: the checkpointed blocks differentiate it as it is
        if takes_gradient or hasattr(w, "apply_to"):
            _LOSS_TRACED["head_products"] = 3
            tok, pull = jax.vjp(lambda xs, w: primal(xs, w, ys), xs, w)
            return tok, (None, pull)
        _LOSS_TRACED["head_products"] = 2
        tok, back = over_blocks(
            functools.partial(block, slope=True), xs, w, ys)
        return tok, (back, None)

    def bwd(kept, g):
        back, pull = kept
        if isinstance(g, jax.custom_derivatives.SymbolicZero):
            return None, None, None
        if pull is not None:
            return (*pull(g), None)
        return (g[..., None] * back).astype(x.dtype), None, None

    loss = jax.custom_vjp(primal)
    loss.defvjp(fwd, bwd, symbolic_zeros=True)
    return loss(x, w_head, labels)
