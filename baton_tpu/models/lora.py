"""LoRA — low-rank adapter fine-tuning for federated models.

BASELINE config 4 (Llama-class LoRA federated instruction-tune): clients
train and ship only rank-r adapter factors; the base model is frozen and
replicated once. In the reference's architecture this would still ship
the full state_dict every round (manager.py:77-86); here the adapter-only
payload composes with :class:`baton_tpu.core.partition.ParamPartition` so
the per-client vmap axis carries just the adapters — the difference
between C×8B and C×a-few-MB of HBM.

For every targeted 2-D weight ``W [in,out]`` the effective weight is
``W + (alpha/rank)·A@B`` with ``A [in,r]`` normal / ``B [r,out]`` zeros
(so step 0 is exactly the base model). The wrapped model's params are
``{"base": ..., "lora": {path: {"a","b"}}}``.

Training applies the adapters to **activations**: ``apply`` hands the
wrapped model its base tree with every targeted leaf replaced by an
:class:`Adapted` weight, which stands where the model's code has ``W``
and computes ``x W + s (x A) B`` for ``x @ W`` (and ``W[ids] + s A[ids]
B`` for an embedding lookup). No ``W + s A B`` is ever built, so under
the client ``vmap`` the base stays the one array ``ParamPartition``
holds for all clients, in the dtype it was initialised in (a frozen
bfloat16 base is cast nowhere), and only the rank-r products carry a
client axis. Any model whose hot weights are 2-D leaves used through
``@``, :func:`baton_tpu.models.transformer.matmul` or a row lookup gets
LoRA without modifying its code. :func:`merge_lora` materialises ``W +
s A B`` for deployment.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from baton_tpu.core.model import FedModel
from baton_tpu.core.partition import path_str
from baton_tpu.models.transformer import contract_heads, cut_columns

TargetPredicate = Callable[[str, Any], bool]


@dataclasses.dataclass(frozen=True)
class LoraSpec:
    """Rank/alpha of a wrapped model, stored on ``FedModel.aux`` so the
    training-time scale and the deploy-time merge cannot diverge."""

    rank: int
    alpha: float

    @property
    def scale(self) -> float:
        return self.alpha / self.rank


@jax.tree_util.register_pytree_node_class
class Adapted:
    """A weight ``W [in, out]`` with its adapter factors, standing where
    a model's code has ``W``: ``x @ adapted`` is ``x W + s (x A) B``,
    ``adapted[ids]`` the adapted rows, ``astype`` casts ``W`` alone (the
    factors follow the activations' dtype when they are used)."""

    def __init__(self, w, a, b, scale: float):
        self.w, self.a, self.b, self.scale = w, a, b, scale

    def tree_flatten(self):
        return (self.w, self.a, self.b), self.scale

    @classmethod
    def tree_unflatten(cls, scale, children):
        return cls(*children, scale)

    shape = property(lambda self: self.w.shape)
    ndim = property(lambda self: self.w.ndim)
    dtype = property(lambda self: self.w.dtype)

    def astype(self, dtype):
        return Adapted(self.w.astype(dtype), self.a, self.b, self.scale)

    def low_rank(self, x):
        """``x A``: what every column of ``x @ adapted`` shares."""
        with jax.named_scope("adapter"):
            return jnp.matmul(x, self.a.astype(x.dtype))

    def apply_to(self, x, preferred_element_type=None, low=None,
                 product=jnp.matmul):
        """``x @ adapted``; ``low`` is :meth:`low_rank` of ``x`` where
        the caller has it already (the parts of :meth:`columns` share
        one), ``product`` what makes both products (a caller that wants
        ``x @ W`` in another layout says how, and the adapter's term
        comes out beside it in the same)."""
        y = product(x, self.w,
                    preferred_element_type=preferred_element_type)
        if low is None:
            low = self.low_rank(x)
        with jax.named_scope("adapter"):
            return y + self.scale * product(
                low, self.b.astype(x.dtype),
                preferred_element_type=preferred_element_type)

    def columns(self, n_heads: int, widths, made=None):
        """The weight's columns seen as ``[in, n_heads, sum(widths)]``
        and cut along the last axis into parts of ``widths``
        (:func:`~baton_tpu.models.transformer.cut_columns`, with its
        ``made``): an ``Adapted [in, n_heads * width]`` a part, ``W``
        and ``B`` cut alike, ``A`` and the scale shared. ``x @ part``
        is the same columns of ``x @ whole`` bit for bit (dropping
        output columns reorders no contraction), so a model cuts a
        frozen weight, which costs nothing a token, where it would cut
        an activation."""
        return [Adapted(w, self.a, b, self.scale)
                for w, b in zip(cut_columns(self.w, n_heads, widths, made),
                                cut_columns(self.b, n_heads, widths, made))]

    def heads_apply_to(self, x, n_heads: int):
        """``x [B, H, L, v]`` through the weight's rows seen as ``[H, v,
        out]``: ``[B, L, out]``, the contraction over ``(head,
        channel)`` where ``x`` lies, ``A``'s rows seen the same way."""
        y = contract_heads(x, self.w, n_heads)
        with jax.named_scope("adapter"):
            low = contract_heads(x, self.a.astype(x.dtype), n_heads)
            return y + self.scale * jnp.matmul(low, self.b.astype(x.dtype))

    def __rmatmul__(self, x):
        return self.apply_to(x)

    def __getitem__(self, index):
        with jax.named_scope("adapter"):
            rows = self.scale * (self.a[index] @ self.b)
        return self.w[index] + rows.astype(self.w.dtype)

    def merged(self):
        """``W + s A B`` itself: for a table that is added whole (a
        position embedding), where there is no activation to adapt."""
        return self.w + (self.scale * (self.a @ self.b)).astype(self.w.dtype)

    def __add__(self, other):
        return self.merged() + other

    __radd__ = __add__


def default_target(path: str, leaf) -> bool:
    """Adapt every 2-D matrix leaf (matmul weights; biases/norms are 1-D)."""
    return hasattr(leaf, "ndim") and leaf.ndim == 2


def lora_trainable(path: str, leaf) -> bool:
    """Partition predicate selecting adapter leaves of a wrapped model."""
    return path.startswith("lora/")


def _lora_paths(base_params, target: TargetPredicate):
    path_leaves, _ = jax.tree_util.tree_flatten_with_path(base_params)
    return [
        (path_str(p), l.shape) for p, l in path_leaves if target(path_str(p), l)
    ]


def merge_lora_model(model: FedModel, params):
    """Materialize deploy params for a :func:`lora_wrap`-ped model, using
    the exact scale it was trained with (``model.aux``)."""
    spec = model.aux
    if not isinstance(spec, LoraSpec):
        raise ValueError(f"{model.name} is not a lora_wrap-ped model")
    return merge_lora(params, spec.alpha, spec.rank)


def merge_lora(params, alpha: float, rank: int):
    """Materialize effective base params: ``W += (alpha/rank)·A@B``.

    Prefer :func:`merge_lora_model`, which cannot drift from the
    training-time scale."""
    scale = alpha / rank
    lora = params["lora"]
    path_leaves, treedef = jax.tree_util.tree_flatten_with_path(params["base"])
    merged = []
    for p, leaf in path_leaves:
        key = path_str(p)
        if key in lora:
            ab = lora[key]["a"] @ lora[key]["b"]
            leaf = leaf + (scale * ab).astype(leaf.dtype)
        merged.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, merged)


def lora_wrap(
    model: FedModel,
    rank: int = 8,
    alpha: Optional[float] = None,
    target: TargetPredicate = default_target,
    name: Optional[str] = None,
    b_std: float = 0.0,
) -> FedModel:
    """Wrap ``model`` with LoRA adapters on every targeted 2-D weight.

    Use with ``FedSim(..., trainable=lora_trainable)`` so only adapters
    are per-client/aggregated. ``model.init`` supplies the base weights;
    load pretrained weights by overwriting ``params["base"]`` after init.
    ``b_std`` draws ``B`` from a normal of that deviation instead of
    zeros: with ``B = 0`` the first step's gradient of ``A`` is zero, so
    a one-step comparison that has to exercise both factors sets it.
    """
    if alpha is None:
        alpha = 2.0 * rank
    spec = LoraSpec(rank=rank, alpha=float(alpha))

    def init(rng):
        base_rng, lora_rng = jax.random.split(rng)
        base = model.init(base_rng)
        specs = _lora_paths(base, target)
        if not specs:
            raise ValueError("LoRA target predicate matched no 2-D leaves")
        keys = jax.random.split(lora_rng, len(specs))
        adapters = {}
        for k, (path, shape) in zip(keys, specs):
            fan_in, fan_out = shape
            adapters[path] = {
                "a": jax.random.normal(k, (fan_in, rank), jnp.float32)
                / jnp.sqrt(fan_in),
                "b": b_std * jax.random.normal(
                    jax.random.fold_in(k, 1), (rank, fan_out), jnp.float32)
                if b_std else jnp.zeros((rank, fan_out), jnp.float32),
            }
        return {"base": base, "lora": adapters}

    def adapted(params):
        """The base tree with an :class:`Adapted` weight at every
        targeted leaf."""
        lora = params["lora"]
        path_leaves, treedef = jax.tree_util.tree_flatten_with_path(
            params["base"])
        return jax.tree_util.tree_unflatten(treedef, [
            Adapted(leaf, lora[key]["a"], lora[key]["b"], spec.scale)
            if (key := path_str(p)) in lora else leaf
            for p, leaf in path_leaves])

    def apply(params, batch, rng):
        return model.apply(adapted(params), batch, rng)

    def per_example_loss(params, batch, rng):
        return model.per_example_loss(adapted(params), batch, rng)

    return FedModel(
        init=init,
        apply=apply,
        per_example_loss=per_example_loss,
        name=name or f"{model.name}_lora{rank}",
        aux=spec,
        span_attrs=model.span_attrs,
    )
