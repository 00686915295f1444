"""Decoder-only LM (BASELINE config 4: LoRA instruction-tune).

The reference has no language models (reference demo.py:15-49 is its whole
zoo); this decoder exists for the driver-set federated LoRA workload.
Architecture is the modern decoder recipe — RMSNorm pre-norm, RoPE,
SwiGLU MLP, grouped-query attention, untied output head — built from the
TPU-first blocks in :mod:`baton_tpu.models.transformer`. ``layer_types``
makes it a hybrid: each layer's mixer is one of :data:`MIXERS` (full
attention, the gated delta rule's linear attention with a recurrent
state, latent attention in a low-rank latent whose queries may choose
their keys, compressed convolutional attention, a Mamba-2 state-space
branch and an attention branch side by side on one normed input, full
attention's projections under a window of the last ``window`` keys), in
the pattern the configuration gives. The table is all this module
knows of a mixer: a further one is one more entry. With ``moe`` the layers after the
first ``first_dense_layers`` replace their SwiGLU by the expert layer of
:mod:`baton_tpu.models.moe`, which holds ``moe.experts_held`` of the
router's experts and computes their part; where its router carries a
state (``moe.router_hidden``) a block takes and hands on two streams,
the tokens' and the routers', and the first block is handed zeros. With
``residual_merge`` a sub-layer's output joins the stream by a learned
affine merge a channel and not a plain add; with ``tie_embeddings`` the
head is the embedding table itself. A block is sequential, ``x +
mixer(N(x))`` then ``x + feed_forward(N'(x))`` with a norm of its own
before each, or with ``parallel_block`` it holds one norm and computes
``x + mixer(N(x)) + feed_forward(N(x))``, the norm made once and one add;
``norm`` says whether the decoder's norms are RMSNorms or LayerNorms with
a scale and no bias; beside windowed layers that rotate, the full layers
may take no rotation (``full_layer_rope``), and ``rope_pairs`` says which
channels a rotation turns together. ``multipliers`` are the fixed
scalars a model publishes for its projections, the embedding and the
logits (:class:`~baton_tpu.models.transformer.Multipliers`; at 1, as
every other model has them, they add no op). A block is traced once a
kind (of mixer and of feed-forward), whatever the depth, and with
``remat`` once more a kind where the trailing blocks keep their
products (:func:`blocks_kept`).

* params fp32 / activations ``compute_dtype`` (bf16 on TPU), norms,
  softmax and the experts' router in fp32;
* causal masking is static inside the attention kernel; an optional
  per-token ``loss_mask`` weights the LM loss (instruction tuning
  masks the prompt);
* ``attention_fn`` is injectable — dense, fused-blockwise, or ring
  attention over a sequence mesh axis all fit behind the same signature;
* for federation, pair with :func:`baton_tpu.models.lora.lora_wrap` and
  ``trainable=lora_trainable`` so simulated clients carry only the
  adapter pytree (see :func:`llama_lora_target` for the standard
  attention-projection targeting, :func:`projection_lora_target` for
  every projection of the mixers and MLPs, and
  :func:`decoder_lora_model` for the two put together over a base held
  in ``param_dtype``);
* the loss never holds ``[B, L, V]`` logits whole, and over a head that
  takes no gradient (a base under adapters) it makes ``dx`` in its
  forward (:func:`baton_tpu.models.transformer.next_token_loss`).

Batches: ``{"x": int32[B, L] inputs, "y": int32[B, L] next-token targets,
"loss_mask"?: [B, L] 1.0 = token counts toward the loss}``. The
per-example loss is the per-sequence mean over unmasked tokens — [B],
as the framework contract requires (core/model.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.extend
import jax.numpy as jnp

from baton_tpu.core.model import FedModel, clients_in_wave
from baton_tpu.core.partition import path_str
from baton_tpu.models.delta_rule import gated_delta_apply, gated_delta_init
from baton_tpu.models.lora import lora_wrap
from baton_tpu.models.moe import (
    MoEConfig, expert_tiles, moe_apply, moe_apply_with_state, moe_init,
    rows_bound)
from baton_tpu.models.state_space import SSMConfig, mamba2_apply, mamba2_init
from baton_tpu.models.transformer import (
    ROPE_PAIRS, AttentionFn, CCAConfig, MLAConfig, Multipliers,
    attention_is_kernel, cca_apply, cca_core_is_kernel, cca_init,
    default_attention, dense_init, head_products_a_block, layer_norm,
    logits_held, matmul, mha_apply, mha_init, mla_apply, mla_core_is_kernel,
    mla_init, mla_qk_layout, mla_rope_angles, multi_head_attention,
    next_token_loss, normal_init, rms_init, rms_norm, rope_angles, scaled,
    swiglu_apply, swiglu_init, tied_logits)

# a decoder's norm by ``LlamaConfig.norm``: both hold a ``scale`` alone
_NORMS = {"rms": rms_norm, "layer": layer_norm}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    max_len: int = 8192
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    # of a full-attention head; None: ``d_model // n_heads``
    head_dim: Optional[int] = None
    d_ff: int = 14336
    # None: no rotary embedding (position comes from the recurrent
    # layers of a hybrid)
    rope_theta: Optional[float] = 500000.0
    # the published ``yarn`` group of the full-attention layers'
    # rotation (``factor``, ``original_max_position_embeddings``,
    # ``beta_fast``, ``beta_slow``, ``attention_factor``:
    # ``transformer.rope_angles``); None: plain frequencies. A windowed
    # layer's are always plain
    rope_yarn: Optional[tuple] = None
    # a windowed layer's query sees itself and the ``window - 1`` keys
    # before it; the full-attention layers beside it see their prefix
    window: Optional[int] = None
    # the expert layer (models/moe.py) that stands for the SwiGLU of
    # width ``d_ff`` in every layer after the first
    # ``first_dense_layers``; its own width is ``moe.d_ff``
    moe: Optional[MoEConfig] = None
    first_dense_layers: int = 0
    # the sizes of latent attention, of compressed convolutional
    # attention and of a state-space branch
    mla: Optional[MLAConfig] = None
    cca: Optional[CCAConfig] = None
    ssm: Optional[SSMConfig] = None
    # RMSNorm of the whole query and key projections in full attention
    qk_norm: bool = False
    # the share of a query head's draw that is its key head's, in full
    # and windowed attention (``transformer.mha_init``); 0: independent
    qk_aligned: float = 0.0
    # the mixer of each layer, a key of ``MIXERS``; the first
    # ``n_layers`` entries count (a depth cut keeps the published
    # list). None: one mixer everywhere, latent attention with ``mla``,
    # else full attention
    layer_types: Optional[Tuple[str, ...]] = None
    # the linear-attention (gated delta rule) layers' heads
    linear_n_heads: int = 0
    linear_key_dim: int = 0
    linear_value_dim: int = 0
    linear_allow_neg_eigval: bool = True
    linear_chunk: int = 64
    # deviation of the embedded tokens (the table's initial normal times
    # ``multipliers.embedding``): the scale of the residual stream the
    # blocks' outputs are added to
    embed_std: float = 0.02
    # the RMSNorms before each sub-layer and before the head (a latent
    # mixer's own norms have ``mla.norm_eps``)
    norm_eps: float = 1e-6
    # a sub-layer's output ``y`` joins the stream ``x`` as ``a_x (x +
    # b_x) + a_y (y + b_y)``, four frozen float32 vectors a sub-layer,
    # and not as ``x + y``
    residual_merge: bool = False
    # the head is the embedding table transposed; no ``lm_head`` leaf
    tie_embeddings: bool = False
    multipliers: Multipliers = Multipliers()
    # a block holds one norm, ``norm``, and is ``x + mixer(N(x)) +
    # feed_forward(N(x))``; False: ``norm_attn`` before the mixer,
    # ``norm_mlp`` before the feed-forward, one after the other
    parallel_block: bool = False
    # the norm of a block and the one before the head, at ``norm_eps``:
    # ``"rms"``, or ``"layer"``, a LayerNorm with a scale and no bias
    norm: str = "rms"
    # False: a full-attention layer takes no rotation while the windowed
    # layers beside it keep ``rope_theta``
    full_layer_rope: bool = True
    # the channels a rotation of full or windowed attention turns
    # together: ``"split"`` ``(i, i + head_dim / 2)`` or ``"adjacent"``
    # ``(2i, 2i + 1)`` (``transformer.apply_rope``)
    rope_pairs: str = "split"

    def __post_init__(self):
        if self.layer_types is not None:  # a JSON list hashes as a tuple
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if isinstance(self.rope_yarn, dict):  # a JSON group, hashable
            object.__setattr__(self, "rope_yarn",
                               tuple(sorted(self.rope_yarn.items())))
        if self.norm not in _NORMS:
            raise ValueError(f"unknown norm {self.norm!r}: {sorted(_NORMS)}")
        if self.rope_pairs not in ROPE_PAIRS:
            raise ValueError(
                f"unknown rope_pairs {self.rope_pairs!r}: {ROPE_PAIRS}")

    def kind_of(self, layer: int) -> str:
        if self.layer_types:
            return self.layer_types[layer]
        return "latent_attention" if self.mla else "full_attention"

    def has_experts(self, layer: int) -> bool:
        return self.moe is not None and layer >= self.first_dense_layers

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        """Test-sized config (CI / CPU-mesh tests)."""
        defaults = dict(
            vocab_size=256, max_len=32, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=128, rope_theta=10000.0,
        )
        defaults.update(kw)
        return cls(**defaults)


def llama_lora_target(path: str, leaf) -> bool:
    """LoRA target predicate: the attention projections (wq/wk/wv/wo) —
    the standard adapter placement for instruction tuning."""
    return path.rsplit("/", 1)[-1] in ("wq", "wk", "wv", "wo")


@dataclasses.dataclass(frozen=True)
class Mixer:
    """What a decoder block and its model ask of a mixer, and all they
    ask; ``cfg`` is the :class:`LlamaConfig`. One that keeps its inputs
    recomputes itself in the backward from them: it stands outside the
    block's checkpoint, and its ``apply`` takes the stream itself and
    ``pre_norm=``, the norm before it. ``core_is_kernel`` is defined
    beside the mixer's own dispatch, in the mixer's module."""

    key: str  # its name in the block's parameters
    init: Callable[..., Any]  # (rng, cfg, out_std) -> its parameters
    # (p, h, cfg, rope, attention_fn) -> [B, L, D] from the normed stream
    apply: Callable[..., jax.Array]
    # its 2-D leaves that LoRA adapts, by their path under ``key``
    projections: Tuple[str, ...]
    # (cfg, length) -> its rotation's (cos, sin), or None
    rope: Callable[..., Optional[tuple]] = lambda cfg, length: None
    keeps_its_inputs: Callable[..., bool] = lambda cfg, length: False
    # (cfg, backend, batch, length, attention_fn): its core is the flash
    # kernel there, whose two outputs the block's checkpoint then keeps
    core_is_kernel: Callable[..., bool] = lambda *where: False
    facts: Callable[..., tuple] = lambda cfg: ()  # for baton.round's span
    # (cfg, length) -> what a trace learns of its sequences, for the same
    seen: Callable[..., dict] = lambda cfg, length: {}


def _attention_init(rng, cfg, out_std):
    """Full attention's parameters, each projection drawn against the
    multipliers on its way (:class:`Multipliers`)."""
    m = cfg.multipliers
    p = mha_init(rng, cfg.d_model, cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                 head_dim=cfg.head_dim, out_std=out_std / m.attention_out,
                 qk_norm=cfg.qk_norm, qk_aligned=cfg.qk_aligned)
    for name, by in (("wq", m.attention_in), ("wk", m.attention_in * m.key),
                     ("wv", m.attention_in)):
        if by != 1:
            p[name] = p[name] / by
    return p


def _attention_apply(p, h, cfg, rope, attention_fn):
    m = cfg.multipliers
    # beside windowed layers the core has a scope of its own, to be told
    # from theirs; no other configuration's names move
    named = {} if cfg.window is None else {"core_scope": "full_core"}
    return scaled(mha_apply(
        p, scaled(h, m.attention_in), cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
        causal=True, rope=rope, attention_fn=attention_fn,
        key_multiplier=m.key, rope_pairs=cfg.rope_pairs, **named),
        m.attention_out)


def _attention_rope(cfg, length):
    # None: position comes from the recurrent layers of a hybrid, or
    # from the windowed layers beside a full layer that takes none
    if cfg.rope_theta is None or not cfg.full_layer_rope:
        return None
    return rope_angles(length, cfg.head_dim, cfg.rope_theta,
                       cfg.rope_yarn and dict(cfg.rope_yarn))


def _attention_facts(cfg):
    return () if cfg.rope_yarn is None else (
        ("rope_yarn_factor", dict(cfg.rope_yarn)["factor"]),)


def _attention_is_kernel(cfg, backend, batch, length, attention_fn):
    return attention_is_kernel(attention_fn, backend, batch, cfg.n_heads,
                               length)


@jax.named_scope("sliding_attention")
def _sliding_apply(p, h, cfg, rope, attention_fn):
    """Full attention's projections under a window: a query sees itself
    and the ``cfg.window - 1`` keys before it. The core is whatever
    ``attention_fn`` makes of the window (the flash kernel skips by it,
    the dense path masks), under a scope of its own."""
    return multi_head_attention(
        p, h, cfg.n_heads, n_kv_heads=cfg.n_kv_heads, causal=True, rope=rope,
        attention_fn=attention_fn, window=cfg.window,
        core_scope="window_core", rope_pairs=cfg.rope_pairs)


def _layers_of(cfg, apply) -> int:
    return sum(_mixer(cfg.kind_of(i)).apply is apply
               for i in range(cfg.n_layers))


def _sliding_facts(cfg):
    return (("window", cfg.window),
            ("window_layers", _layers_of(cfg, _sliding_apply)),
            ("full_layers", _layers_of(cfg, _attention_apply)))


def _sliding_seen(cfg, length):
    """The tiles a head's forward kernel runs on in a windowed and in a
    full layer over ``length`` tokens, by the kernel's own rule, and
    the steps its grid takes to reach them (a windowed layer's runs
    over the band, a full layer's over the sequence)."""
    from baton_tpu.ops.flash_attention import grid_steps, tiles_visited

    return {"window_tiles": tiles_visited(length, cfg.window),
            "causal_tiles": tiles_visited(length),
            "window_grid_steps": grid_steps(length, cfg.window),
            "causal_grid_steps": grid_steps(length)}


def _parallel_init(rng, cfg, out_std):
    k_ssm, k_attn = jax.random.split(rng)
    return {"ssm": mamba2_init(k_ssm, cfg.d_model, cfg.ssm, cfg.multipliers,
                               out_std),
            "attention": _attention_init(k_attn, cfg, out_std)}


@jax.named_scope("parallel_mixer")
def _parallel_apply(p, h, cfg, rope, attention_fn):
    """A state-space branch and an attention branch over the same
    normed input, each under its multipliers, summed."""
    return mamba2_apply(p["ssm"], h, cfg.ssm, cfg.multipliers) \
        + _attention_apply(p["attention"], h, cfg, rope, attention_fn)


MIXERS = {
    "full_attention": Mixer(
        key="attn", init=_attention_init, apply=_attention_apply,
        projections=("wq", "wk", "wv", "wo"), rope=_attention_rope,
        core_is_kernel=_attention_is_kernel, facts=_attention_facts),
    "linear_attention": Mixer(
        key="linear_attn",
        init=lambda rng, cfg, out_std: gated_delta_init(
            rng, cfg.d_model, cfg.linear_n_heads, cfg.linear_key_dim,
            cfg.linear_value_dim, out_std=out_std),
        apply=lambda p, h, cfg, rope, attention_fn: gated_delta_apply(
            p, h, cfg.linear_n_heads, cfg.linear_chunk,
            cfg.linear_allow_neg_eigval),
        # not the gates' ``wa`` / ``wb``, not the convolutions
        projections=("wq", "wk", "wv", "wg", "wo")),
    "latent_attention": Mixer(
        key="mla",
        init=lambda rng, cfg, out_std: mla_init(
            rng, cfg.d_model, cfg.n_heads, cfg.mla, out_std=out_std),
        apply=lambda p, h, cfg, rope, attention_fn, pre_norm=None: mla_apply(
            p, h, cfg.n_heads, cfg.mla, rope, pre_norm=pre_norm),
        projections=("wq", "wq_a", "wq_b", "wkv_a", "wkv_b", "wo"),
        rope=lambda cfg, length: mla_rope_angles(length, cfg.mla),
        # where queries choose their keys: ``transformer._choosing_mla``
        keeps_its_inputs=lambda cfg, length: cfg.mla.selects(length),
        core_is_kernel=lambda cfg, backend, batch, length, fn:
        mla_core_is_kernel(cfg.mla, backend, length),
        # how the mixer laid out the queries and keys it made
        seen=lambda cfg, length: {"mla_qk_layout": mla_qk_layout(
            cfg.mla, jax.default_backend(), length)}),
    "compressed_attention": Mixer(
        key="cca",
        init=lambda rng, cfg, out_std: cca_init(
            rng, cfg.d_model, cfg.cca, out_std=out_std),
        apply=lambda p, h, cfg, rope, attention_fn: cca_apply(
            p, h, cfg.cca, rope),
        projections=("linear_q", "linear_k", "val_proj1", "val_proj2",
                     "o_proj"),
        rope=lambda cfg, length: rope_angles(length, cfg.cca.rope_dim,
                                             cfg.cca.rope_theta),
        core_is_kernel=lambda cfg, backend, batch, length, fn:
        cca_core_is_kernel(backend, length),
        facts=lambda cfg: (
            ("latent_q", cfg.cca.latent_q), ("latent_kv", cfg.cca.latent_kv),
            ("conv_taps", f"{cfg.cca.time0}+{cfg.cca.time1}"))),
    # two mixers over one normed input: the rotation, the kernel and its
    # kept outputs are the attention branch's
    "parallel_ssm_attention": Mixer(
        key="parallel", init=_parallel_init, apply=_parallel_apply,
        # not the convolution, not ``a_log``, ``d``, ``dt_bias`` or a norm
        projections=("ssm/in_proj", "ssm/out_proj", "attention/wq",
                     "attention/wk", "attention/wv", "attention/wo"),
        rope=_attention_rope, core_is_kernel=_attention_is_kernel,
        facts=lambda cfg: (
            ("ssm_heads", cfg.ssm.n_heads), ("ssm_state", cfg.ssm.d_state),
            ("ssm_groups", cfg.ssm.n_groups), ("ssm_chunk", cfg.ssm.chunk),
            ("conv_taps", cfg.ssm.conv_taps)),
        seen=lambda cfg, length: {
            "ssm_chunks": -(-length // min(cfg.ssm.chunk, length))}),
    # full attention's parameters under a key of their own (a block's
    # kind is the structure of its parameters), its rule for the kernel,
    # plain rotary frequencies whatever the full layers' are
    "sliding_attention": Mixer(
        key="sliding_attn",
        init=lambda rng, cfg, out_std: mha_init(
            rng, cfg.d_model, cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, out_std=out_std,
            qk_aligned=cfg.qk_aligned),
        apply=_sliding_apply, projections=("wq", "wk", "wv", "wo"),
        rope=lambda cfg, length: rope_angles(length, cfg.head_dim,
                                             cfg.rope_theta),
        core_is_kernel=_attention_is_kernel, facts=_sliding_facts,
        seen=_sliding_seen),
}

_MLP_PROJECTIONS = ("w_gate", "w_up", "w_down")  # a shared expert's too


def _mixer(kind: str) -> Mixer:
    if kind not in MIXERS:
        raise ValueError(
            f"unknown layer type {kind!r}: MIXERS holds {sorted(MIXERS)}")
    return MIXERS[kind]


def projection_lora_target(path: str, leaf) -> bool:
    """LoRA target predicate: the 2-D leaves each mixer of
    :data:`MIXERS` lists as its ``projections``, by their path under its
    ``key`` (``wq``, or ``ssm/in_proj`` where the mixer is a pair), and
    those of the MLPs and of a shared expert; not the embedding, the
    head, an expert layer's router (a matrix or an MLP) or its 3-D
    stacks of routed experts."""
    return getattr(leaf, "ndim", 2) == 2 and (
        path.rsplit("/", 1)[-1] in _MLP_PROJECTIONS or any(
            f"/{path}".endswith(f"/{m.key}/{name}")
            for m in MIXERS.values() for name in m.projections))


def _block_init(key, cfg: LlamaConfig, kind: Optional[str] = None,
                experts: bool = False):
    """A block holds its mixer (``kind``, a key of :data:`MIXERS`; None:
    the first layer's) under that mixer's ``key``; an expert layer's
    ``mlp`` holds a ``router``; with ``residual_merge`` the block holds
    ``merge_attn`` and ``merge_mlp``; a parallel block holds one
    ``norm`` where a sequential one holds ``norm_attn`` and
    ``norm_mlp``."""
    ka, km = jax.random.split(key)
    if experts:
        mlp = moe_init(km, cfg.d_model, cfg.d_ff, cfg.moe)
    else:
        mlp = swiglu_init(km, cfg.d_model, cfg.d_ff, cfg.multipliers.mlp)
    m = _mixer(kind or cfg.kind_of(0))
    mixer = {m.key: m.init(
        ka, cfg, cfg.d_model ** -0.5 / (2 * cfg.n_layers) ** 0.5)}
    if cfg.residual_merge:
        k1, k2 = jax.random.split(jax.random.fold_in(key, 2))
        mixer.update(merge_attn=_merge_init(k1, cfg.d_model),
                     merge_mlp=_merge_init(k2, cfg.d_model))
    if cfg.parallel_block:
        return {"norm": rms_init(cfg.d_model), **mixer, "mlp": mlp}
    return {"norm_attn": rms_init(cfg.d_model), **mixer,
            "norm_mlp": rms_init(cfg.d_model), "mlp": mlp}


def _merge_init(key, d: int):
    """A trained merge lies near ``a = 1``, ``b = 0``; the scales are
    drawn uniform in [0.5, 1.5] and the shifts at deviation 0.02, so
    that a test can tell a merge from a plain add."""
    ks, kb = jax.random.split(key)
    a_x, a_y = jax.random.uniform(ks, (2, d), jnp.float32, 0.5, 1.5)
    b_x, b_y = normal_init(kb, (2, d), 0.02)
    return {"a_x": a_x, "b_x": b_x, "a_y": a_y, "b_y": b_y}


def _joined(p, name: str, x, y):
    """The sub-layer's output ``y`` joined to the stream ``x``: the
    block's affine merge ``name`` where it holds one, in float32, else
    the plain add."""
    if name not in p:
        return x + y
    m = p[name]
    return (m["a_x"] * (x.astype(jnp.float32) + m["b_x"])
            + m["a_y"] * (y.astype(jnp.float32) + m["b_y"])).astype(x.dtype)


def _normed(x, scale, cfg: LlamaConfig):
    return _NORMS[cfg.norm](x, scale, cfg.norm_eps)


def _mixer_of(p) -> Mixer:
    # the kind of a block is the structure of its parameters
    return next(m for m in MIXERS.values() if m.key in p)


def _mix(p, x, cfg: LlamaConfig, rope, attention_fn: AttentionFn):
    m = _mixer_of(p)
    if m.keeps_its_inputs(cfg, x.shape[1]):
        y = m.apply(p[m.key], x, cfg, rope, attention_fn,
                    pre_norm=p["norm_attn"])
    else:
        y = m.apply(p[m.key], _normed(x, p["norm_attn"], cfg), cfg, rope,
                    attention_fn)
    return _joined(p, "merge_attn", x, y)


def _fed_forward(p, h, r, cfg: LlamaConfig):
    """``(y, r)``: the feed-forward of the normed stream ``h``, and the
    routers' state, which an expert layer whose router carries one
    (``r`` not None) takes from the layer before and hands to the
    next."""
    if r is not None:
        return moe_apply_with_state(p["mlp"], h, r, cfg.moe)
    if "router" in p["mlp"]:
        return moe_apply(p["mlp"], h, cfg.moe), r
    return swiglu_apply(p["mlp"], h, cfg.multipliers.mlp), r


def _feed_forward(p, x, r, cfg: LlamaConfig):
    """``(x, r)``: the stream after the feed-forward and the routers'
    state after it."""
    y, r = _fed_forward(p, _normed(x, p["norm_mlp"], cfg), r, cfg)
    return _joined(p, "merge_mlp", x, y), r


def _block_apply(p, x, r, cfg: LlamaConfig, rope, attention_fn: AttentionFn):
    """A sequential block, or with ``cfg.parallel_block`` the parallel
    one: the mixer and the feed-forward read one normed stream and
    their outputs join the stream in one add."""
    if not cfg.parallel_block:
        return _feed_forward(p, _mix(p, x, cfg, rope, attention_fn), r, cfg)
    h = _normed(x, p["norm"], cfg)
    m = _mixer_of(p)
    y, r = _fed_forward(p, h, r, cfg)
    return x + m.apply(p[m.key], h, cfg, rope, attention_fn) + y, r


def _block_facts(cfg: LlamaConfig) -> tuple:
    """What ``baton.round`` says of a block that is not the sequential
    RMSNorm one with every layer rotated by halves, and of the heads it
    was built with (a rank of a deployment holds its share of them)."""
    said = {"parallel_block": True} if cfg.parallel_block else {}
    if cfg.norm != "rms":
        said["norm"] = cfg.norm
    if not cfg.full_layer_rope:
        said["full_layer_rope"] = "none"
    if cfg.rope_pairs != "split":
        said["rope_pairs"] = cfg.rope_pairs
    if said:
        said["heads_held"] = f"{cfg.n_heads}+{cfg.n_kv_heads}"
    return tuple(said.items())


# the operations whose results a kept block saves for its backward:
# the products, the recurrences' scans and the kernels, what a second
# forward spends its time on. What lies between them (elementwise
# passes, norms, reductions, reshapes) the backward makes again where it
# reads it, which costs a pass over memory and halves what a block holds
_PRODUCTS = frozenset({
    "dot_general", "ragged_dot_general", "conv_general_dilated", "scan",
    "while", "cond", "pallas_call", "sort", "gather", "scatter",
    "scatter-add", "dynamic_slice", "dynamic_update_slice", "cumsum",
    "cumprod", "cummax", "cumlogsumexp", "triangular_solve", "top_k"})


def _products_saveable(prim, *_, **__) -> bool:
    return prim.name in _PRODUCTS


def _checkpointed_block(keeps: Optional[bool] = False):
    """:func:`_block_apply` as a ``remat`` model runs it: nothing of a
    block survives to its backward but a flash kernel's two outputs,
    where its attention is one (as much memory again as the block's
    input or so, and the dearest thing in a block to make again: a
    second run of the forward kernel). Where none runs nothing carries
    those names and the checkpoint is the bare one. With ``keeps`` the
    checkpoint saves every product (:data:`_PRODUCTS`) and its backward
    makes no product again: a trailing block whose residuals fit the
    device (:func:`blocks_kept`). With ``keeps=None`` it saves
    everything, which no block runs: the plan reads a block's whole
    backward off it. Whatever the policy, ``jax.checkpoint`` keeps one
    trace of the block's forward a kind of block."""
    from baton_tpu.ops.flash_attention import KEPT_OUTPUTS

    policies = jax.checkpoint_policies
    return jax.checkpoint(
        _block_apply, static_argnums=(3, 5),
        policy=policies.everything_saveable if keeps is None
        else _products_saveable if keeps
        else policies.save_only_these_names(*KEPT_OUTPUTS))


def _tiled_bytes(aval) -> int:
    """An array's bytes as the chip lays it out: of its axes longer
    than 1 the two minor ones padded to a tile, ``(8, 128)`` of 32-bit
    entries and as many bytes of narrower ones."""
    item = jnp.dtype(aval.dtype).itemsize
    shape = [n for n in aval.shape if n != 1]
    if shape:
        shape[-1] = -(-shape[-1] // 128) * 128
    if len(shape) > 1:
        rows = 8 * max(1, 4 // item)
        shape[-2] = -(-shape[-2] // rows) * rows
    return math.prod(shape) * item


def residual_bytes(fn, p, *rest) -> int:
    """The bytes the backward of ``fn(p, *rest)`` holds of its forward,
    the parameters ``p`` themselves apart (they are held whatever is
    differentiated), as the chip lays them out: the residuals of its
    linearisation by every argument, read from one abstract trace,
    nothing compiled. A ``jax.checkpoint`` inside ``fn``, or ``fn``
    itself being one, counts as what its policy saves; a ``custom_vjp``
    as what its forward rule hands on."""
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (p, *rest))
    jaxpr = jax.make_jaxpr(
        lambda *args: jax.linearize(fn, *args)[1])(*shapes).jaxpr
    given = set(jaxpr.invars[:len(jax.tree_util.tree_leaves(p))])
    kept = {v for v in jaxpr.outvars
            if isinstance(v, jax.extend.core.Var) and v not in given}
    return sum(_tiled_bytes(v.aval) for v in kept)


def plan_bytes(k: int, fixed: int, loss: int, checkpointed: Sequence[int],
               kept: Sequence[int], whole: Sequence[int]) -> int:
    """An estimate, made to err high, of the bytes a training step's
    plan holds with the last ``k`` blocks keeping their products. The
    step holds ``fixed`` throughout and ``checkpointed[i]`` of every
    block that keeps nothing; ``kept[i]`` is what a block holds that
    keeps its products and ``whole[i]`` what its backward holds of its
    forward in all, every intermediate a derivative reads. Two moments
    compete for the peak. After the forward every kept block's
    ``kept[i]`` is held, and beside them the larger of ``loss``, what
    the loss holds while it runs, and the last block's backward, which
    makes the rest of that block's ``whole`` again. In the backward of
    a checkpointed block no kept block holds anything (they are the
    trailing ones, their backward came first and a block's residuals go
    as it ends) and that block's ``whole`` lies there made again."""
    first = len(kept) - k
    after_forward = sum(kept[first:]) + max(
        loss, whole[-1] - kept[-1] if k else 0)
    made_again = max(whole[:first], default=0)
    return fixed + sum(checkpointed[:first]) + max(after_forward, made_again)


def blocks_kept(budget: Optional[int], fixed: int, loss: int,
                checkpointed: Sequence[int], kept: Sequence[int],
                whole: Sequence[int]) -> int:
    """How many trailing blocks run with their products kept: the
    largest ``k`` whose :func:`plan_bytes` is within ``budget``; 0
    without a budget (a device
    :func:`baton_tpu.utils.profiling.hbm_budget_gb` does not know), or
    where no ``k`` fits: the program with every block checkpointed."""
    if budget is not None:
        for k in range(len(kept), 0, -1):
            if plan_bytes(k, fixed, loss, checkpointed, kept,
                          whole) <= budget:
                return k
    return 0


def _plan_budget_bytes() -> Optional[int]:
    """The plan budget of the device the program is traced for, the one
    ``FedSim.auto_wave_size`` holds a wave's plan to; None for a device
    the table does not hold (the CPU)."""
    from baton_tpu.utils.profiling import hbm_budget_gb

    try:
        return int(hbm_budget_gb(jax.devices()[0]) * 2 ** 30)
    except ValueError:
        return None


def core_outputs_kept(cfg: LlamaConfig, backend: str, batch: int, length: int,
                      attention_fn: AttentionFn = default_attention) -> int:
    """The blocks whose checkpoint (``remat=True``) keeps a flash
    kernel's output and log-sum-exp for its backward, on sequences of
    ``length`` tokens, ``batch`` at a time: those whose mixer stands
    under the block's checkpoint and says its core is the kernel there
    (none does on the CPU)."""
    return sum(
        _mixer(cfg.kind_of(i)).core_is_kernel(cfg, backend, batch, length,
                                              attention_fn)
        for i in range(cfg.n_layers))


class _ModelFacts(tuple):
    """A decoder's ``FedModel.span_attrs``: the configuration's facts,
    which it equals and hashes as, and after them, when read, what the
    model's last trace learned from its batch (``seen``: a model meets
    its sequences' length only where it is traced)."""

    def __new__(cls, static, seen: dict):
        self = super().__new__(cls, static)
        self.seen = seen
        return self

    def __iter__(self):
        yield from super().__iter__()
        yield from self.seen.items()


def llama_lm_model(
    config: Optional[LlamaConfig] = None,
    compute_dtype=jnp.float32,
    attention_fn: AttentionFn = default_attention,
    name: str = "llama_lm",
    remat: bool = False,
    param_dtype=jnp.float32,
) -> FedModel:
    """``remat=True`` wraps each decoder block in ``jax.checkpoint``:
    the backward pass makes a block's forward again instead of storing
    its activations, which cuts activation memory from O(L·n_layers) to
    O(L) and costs a second forward, what makes long-sequence /
    large-model training (config 4) fit HBM; a flash kernel's output
    and log-sum-exp are kept (:func:`_checkpointed_block`). The trade
    is made only as far as the device's memory asks: on a device whose
    plan budget is known (``profiling.hbm_budget_gb``) the trailing
    blocks whose products fit beside the rest of the step keep them
    and make none again (:func:`blocks_kept`, from the batch's shapes
    and the clients of the wave where the model is traced; nothing a
    caller sets), and ``baton.round`` says how many (``blocks_kept``)
    and on what (``kept_block_bytes``, ``plan_estimate_bytes``). On
    any other device (the CPU) every block is checkpointed.
    ``param_dtype`` is the dtype ``init``
    gives the matrices and an expert layer's 3-D stacks (a base that
    stays frozen is held in bfloat16); vectors (norm scales, the linear layers'
    ``a_log`` and ``dt_bias``, a router's bias) and the router itself
    are float32."""
    cfg = config or LlamaConfig()
    mixers = [_mixer(cfg.kind_of(i)) for i in range(cfg.n_layers)]
    # made once a model: ``jax.checkpoint`` caches its trace on the
    # function and the arguments' structure, so blocks of one kind share
    # one trace whatever the depth
    block_fn = _checkpointed_block() if remat else _block_apply
    # a mixer that keeps its own inputs for the backward stands outside
    # the block's checkpoint, which would make it a third time
    ff_fn = (jax.checkpoint(_feed_forward, static_argnums=(3,)) if remat
             else _feed_forward)
    # the trailing blocks whose residuals fit the device (``_kept``)
    # keep their products: the same checkpoints, no product made again
    block_keeps = _checkpointed_block(keeps=True)
    ff_keeps = jax.checkpoint(
        _feed_forward, static_argnums=(3,), policy=_products_saveable)
    # and with everything kept, for the plan to read (``_kept``)
    block_whole = _checkpointed_block(keeps=None)
    ff_whole = jax.checkpoint(
        _feed_forward, static_argnums=(3,),
        policy=jax.checkpoint_policies.everything_saveable)
    stateful = cfg.moe is not None and cfg.moe.router_hidden is not None
    on = cfg.multipliers
    seen = {}  # what ``_hidden`` learned of the batch it was last traced on
    # a client's bytes of a block as it is checkpointed, as it keeps its
    # products and whole, by the block's kind and the batch's shape
    # (``_kept``): a program's later traces read what its first found
    parts = {}
    if stateful and cfg.first_dense_layers:
        raise NotImplementedError(
            "a router's state runs through every layer: no dense layer "
            "stands among expert layers whose router carries one")
    if cfg.parallel_block and cfg.residual_merge:
        raise NotImplementedError(
            "a parallel block joins the stream by one plain add, no merge")

    def init(rng):
        keys = jax.random.split(rng, cfg.n_layers + 2)
        table_std = cfg.embed_std / on.embedding
        params = {
            "tok_emb": normal_init(keys[0], (cfg.vocab_size, cfg.d_model),
                                   table_std),
            "blocks": [
                _block_init(keys[1 + i], cfg, cfg.kind_of(i),
                            cfg.has_experts(i))
                for i in range(cfg.n_layers)
            ],
            "norm_f": rms_init(cfg.d_model),
        }
        if cfg.tie_embeddings:
            # the table is the head too: the last norm takes the stream
            # to the scale at which its logits have the deviation an
            # untied head's have, 1, as a trained scale would
            params["norm_f"]["scale"] /= (
                table_std * on.lm_head * cfg.d_model ** 0.5)
        else:
            params["lm_head"] = dense_init(
                keys[-1], cfg.d_model, cfg.vocab_size,
                cfg.d_model ** -0.5 / on.lm_head)
        # a router, one matrix or an MLP's leaves, stays float32
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a.astype(param_dtype) if a.ndim >= 2
            and "/router/" not in path_str(path) + "/" else a, params)

    def _kept(params, ids, ropes) -> int:
        """How many trailing blocks keep their products on sequences
        ``ids`` (:func:`blocks_kept`), with what the choice rests on
        for ``baton.round``; 0 and no estimate on a device without a
        plan budget. The plan is made from shapes alone. A block's part
        is read off an abstract trace a kind of block
        (:func:`residual_bytes`): as it is checkpointed, as it keeps
        and with everything kept, times the clients of the wave,
        and what it keeps a quarter more: compiled for a v5e the
        hybrid's kept blocks held 0.97 to 1.16 times the count (PERF.md
        section 5). Held throughout: the parameters once and a client
        the float32 leaves' value, gradient, update and weighted sum
        (what a client trains is float32, a frozen base is not), and
        the stream at both ends of the blocks, in its own dtype and as
        the loss's float32 ``dx``. The loss holds a block of logits,
        its softmax and the cast of it
        (:func:`~baton_tpu.models.transformer.next_token_loss`)."""
        budget = _plan_budget_bytes()
        if budget is None:
            return 0
        b, l = ids.shape
        clients = clients_in_wave()
        x = jax.ShapeDtypeStruct((b, l, cfg.d_model), compute_dtype)
        r = (jax.ShapeDtypeStruct((b, l, cfg.moe.router_hidden), jnp.float32)
             if stateful else None)

        def part(m, blk):
            inner = m.keeps_its_inputs(cfg, l)
            kind = (m, b, l, jax.tree_util.tree_structure(blk), tuple(
                a.shape for a in jax.tree_util.tree_leaves(blk)))
            if kind not in parts:
                parts[kind] = tuple(
                    residual_bytes(
                        lambda p, x, r, rope: fn(p, x, r, cfg) if inner
                        else fn(p, x, r, cfg, rope, attention_fn),
                        blk, x, r, ropes[m])
                    for fn in ((ff_fn, ff_keeps, ff_whole) if inner
                               else (block_fn, block_keeps, block_whole)))
            return parts[kind]

        sizes = [part(m, blk) for m, blk in zip(mixers, params["blocks"])]
        checkpointed = [clients * size[0] for size in sizes]
        kept = [clients * size[1] * 5 // 4 for size in sizes]
        whole = [max(clients * size[2], keeps)
                 for size, keeps in zip(sizes, kept)]
        leaves = jax.tree_util.tree_leaves(params)
        stream = _tiled_bytes(x)
        fixed = sum(a.size * a.dtype.itemsize for a in leaves) + clients * (
            4 * sum(a.size * 4 for a in leaves if a.dtype == jnp.float32)
            + 4 * stream + 2 * stream * 4 // x.dtype.itemsize)
        loss = clients * 3 * logits_held(b, l, params["tok_emb"].shape[0])
        plan = (fixed, loss, checkpointed, kept, whole)
        k = blocks_kept(budget, *plan)
        seen.update(kept_block_bytes=max(kept),
                    plan_estimate_bytes=plan_bytes(k, *plan))
        return k

    def _hidden(params, batch):
        """The final norm's output ``[B, L, D]``."""
        ids = batch["x"]
        l = ids.shape[1]
        seen["core_outputs_kept"] = 0 if not remat else core_outputs_kept(
            cfg, jax.default_backend(), *ids.shape, attention_fn)
        # a layer's angles are its kind's, made once a kind a trace
        ropes = {m: m.rope(cfg, l) for m in dict.fromkeys(mixers)}
        if remat:
            seen["blocks_kept"] = _kept(params, ids, ropes)
        kept_from = cfg.n_layers - seen.get("blocks_kept", 0)
        for m in ropes:
            seen.update(m.seen(cfg, l))
        if cfg.moe is not None:
            seen.update(expert_tiles(cfg.d_model, cfg.moe.d_ff or cfg.d_ff,
                                     compute_dtype))
        with jax.named_scope("embed"):
            x = scaled(params["tok_emb"][ids], on.embedding).astype(
                compute_dtype)
        # the routers' state: the first layer's router is handed zeros,
        # so that every layer is one kind of block
        r = (jnp.zeros(ids.shape + (cfg.moe.router_hidden,), jnp.float32)
             if stateful else None)
        for i, (m, blk) in enumerate(zip(mixers, params["blocks"])):
            rope = ropes[m]
            with jax.named_scope(f"block{i}"):
                if m.keeps_its_inputs(cfg, l):
                    if cfg.parallel_block:
                        raise NotImplementedError(
                            "a parallel block stands whole under its "
                            "checkpoint: no mixer that keeps its inputs")
                    x, r = (ff_keeps if i >= kept_from else ff_fn)(
                        blk, _mix(blk, x, cfg, rope, attention_fn), r, cfg)
                else:
                    x, r = (block_keeps if i >= kept_from else block_fn)(
                        blk, x, r, cfg, rope, attention_fn)
        return _normed(x, params["norm_f"], cfg)

    def apply(params, batch, rng):
        """Returns next-token logits [B, L, V] (fp32): bf16 operands,
        fp32 accumulation — the vocab projection is the model's largest
        matmul, keep it on the fast MXU path."""
        x = _hidden(params, batch)
        if cfg.tie_embeddings:
            return scaled(tied_logits(x, params["tok_emb"]), on.lm_head)
        return scaled(matmul(x, params["lm_head"], jnp.float32), on.lm_head)

    def per_example_loss(params, batch, rng):
        x = _hidden(params, batch)
        tok_loss = next_token_loss(
            x, params["tok_emb" if cfg.tie_embeddings else "lm_head"],
            batch["y"], tied=cfg.tie_embeddings,
            multiplier=on.lm_head)  # [B, L]
        # which side of the loss's gradient this trace took, where it
        # took one: the rule has run by the time the call returns
        if (products := head_products_a_block()) is not None:
            seen["head_products_a_block"] = products
        loss_mask = batch.get("loss_mask")
        if loss_mask is None:
            return jnp.mean(tok_loss, axis=-1)
        m = loss_mask.astype(jnp.float32)
        return jnp.sum(tok_loss * m, axis=-1) / jnp.maximum(
            jnp.sum(m, axis=-1), 1.0
        )

    # what an expert layer holds, and the sorted rows it handles at a
    # time for every 1,024 tokens it sorts together (it grows with them)
    facts = () if cfg.moe is None else (
        ("experts_held", cfg.moe.held), ("experts_total", cfg.moe.n_experts),
        ("routed_rows_bound", rows_bound(1024 * cfg.moe.top_k, cfg.moe.held,
                                         cfg.moe.router_outputs)))
    if cfg.moe is not None and cfg.moe.router_scores != "sigmoid":
        facts += (("router_scores", cfg.moe.router_scores),)
    if cfg.moe is not None and cfg.moe.skip:
        facts += (("router_outputs", cfg.moe.router_outputs),
                  ("skip_expert", cfg.moe.n_experts))
    if cfg.moe is not None and cfg.moe.shared_combine != "sum":
        facts += (("shared_experts", cfg.moe.n_shared),
                  ("shared_combine", cfg.moe.shared_combine))
    for m in dict.fromkeys(mixers):
        facts += m.facts(cfg)
    facts += _block_facts(cfg)
    return FedModel(init=init, apply=apply, per_example_loss=per_example_loss,
                    name=name, aux=cfg, span_attrs=_ModelFacts(facts, seen))


def decoder_lora_model(
    config: LlamaConfig,
    compute_dtype=jnp.bfloat16,
    param_dtype=jnp.bfloat16,
    rank: int = 16,
    alpha: Optional[float] = None,
    b_std: float = 0.0,
    remat: bool = True,
) -> FedModel:
    """The decoder as a frozen base held in ``param_dtype`` with rank-
    ``rank`` adapters on every projection of its mixers and MLPs
    (:func:`projection_lora_target`), applied to activations; train it
    with ``FedSim(..., trainable=lora_trainable)``. ``remat`` is
    :func:`llama_lm_model`'s: every block under a checkpoint, of which
    the trailing ones keep their products where the device has room."""
    base = llama_lm_model(config, compute_dtype=compute_dtype, remat=remat,
                          param_dtype=param_dtype, name="decoder_lm")
    return lora_wrap(base, rank=rank, alpha=alpha,
                     target=projection_lora_target, b_std=b_std)
