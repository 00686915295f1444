"""The hybrid decoder's blocks and its adapters at test sizes on the
CPU: the layer pattern decides each block's mixer, a block of one kind
is traced once whatever the depth, adapters on activations against the
merged weight, the base kept as the arrays it was given, the
``baton.round`` span's byte counts and kept kernel outputs. (The delta
rule's own tests are ``test_hybrid_delta_rule.py`` and
``test_hybrid_delta_rule_clients.py``, the next-token loss's
``test_hybrid_loss.py``: split by mixer, PR 52, the functions as they
were.)"""

import contextlib
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import delta_rule, llama, transformer
from baton_tpu.models.bert import BertConfig, bert_classifier_model
from baton_tpu.models.llama import (
    LlamaConfig,
    decoder_lora_model,
    llama_lm_model,
    projection_lora_target,
)
from baton_tpu.models.lora import lora_trainable, lora_wrap, merge_lora_model
from baton_tpu.models.lstm import LSTMConfig, lstm_lm_model
from baton_tpu.models.mlp import mlp_classifier_model
from baton_tpu.models.vit import ViTConfig, vit_model
from baton_tpu.parallel import engine
from baton_tpu.parallel.engine import FedSim

from _hybrid_decoder_shared import _hybrid, _close, _equations


def test_the_layer_pattern_decides_each_blocks_mixer():
    cfg = _hybrid(n_layers=8)
    params = jax.eval_shape(llama_lm_model(cfg).init, jax.random.key(0))
    kinds = ["linear_attn" if "linear_attn" in b else "attn"
             for b in params["blocks"]]
    assert kinds == (["linear_attn"] * 3 + ["attn"]) * 2
    assert set(params["blocks"][3]["attn"]) == {"wq", "wk", "wv", "wo",
                                                "q_norm", "k_norm"}
    lin = params["blocks"][0]["linear_attn"]
    assert lin["wq"].shape == (64, 32) and lin["wv"].shape == (64, 64)
    assert lin["conv_k"].shape == (4, 32) and lin["a_log"].shape == (4,)
    with pytest.raises(ValueError):
        llama_lm_model(LlamaConfig.tiny(
            layer_types=("sliding",) * 2)).init(jax.random.key(0))


def test_a_base_in_bfloat16_keeps_its_vectors_in_float32():
    model = decoder_lora_model(_hybrid(), rank=2)
    params = jax.eval_shape(model.init, jax.random.key(0))
    dtypes = {leaf.ndim >= 2: set() for leaf in
              jax.tree_util.tree_leaves(params["base"])}
    for leaf in jax.tree_util.tree_leaves(params["base"]):
        dtypes[leaf.ndim >= 2].add(leaf.dtype)
    assert dtypes == {True: {jnp.dtype(jnp.bfloat16)},
                      False: {jnp.dtype(jnp.float32)}}
    assert {a.dtype for a in jax.tree_util.tree_leaves(params["lora"])} == {
        jnp.dtype(jnp.float32)}
    # every projection of the mixers and MLPs, nothing else
    assert len(params["lora"]) == 3 * (5 + 3) + (4 + 3)
    assert not [k for k in params["lora"]
                if k.rsplit("/", 1)[-1] in ("wa", "wb", "tok_emb", "lm_head")
                or "conv" in k]
    assert projection_lora_target("blocks/0/linear_attn/wg", None)
    assert not projection_lora_target("blocks/0/linear_attn/conv_q", None)


def test_a_block_is_traced_once_a_kind_whatever_the_depth(monkeypatch):
    """Eight layers, six of them linear: under ``remat`` the mixer of
    each kind runs its Python once in a trace of the loss."""
    calls = {"linear": 0, "full": 0}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return wrapper

    monkeypatch.setattr(llama, "gated_delta_apply",
                        counted("linear", delta_rule.gated_delta_apply))
    monkeypatch.setattr(llama, "mha_apply",
                        counted("full", transformer.mha_apply))
    model = decoder_lora_model(_hybrid(n_layers=8), compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=2, remat=True)
    params = jax.eval_shape(model.init, jax.random.key(0))
    batch = {"x": jnp.zeros((2, 8), jnp.int32), "y": jnp.zeros((2, 8), jnp.int32)}
    jax.make_jaxpr(lambda p: model.per_example_loss(p, batch, None))(params)
    assert calls == {"linear": 1, "full": 1}


# ------------------------------------------------- adapters on activations
def _models():
    cfg = _hybrid()
    lstm = LSTMConfig.tiny(vocab_size=32)
    return {
        "mlp": (mlp_classifier_model(8, (16,), 4), None,
                lambda key: {"x": jax.random.normal(key, (5, 8)),
                             "y": jnp.zeros((5,), jnp.int32)}),
        "hybrid": (llama_lm_model(cfg), projection_lora_target,
                   lambda key: {"x": jax.random.randint(key, (2, 10), 0, 96),
                                "y": jnp.zeros((2, 10), jnp.int32)}),
        "decoder_every_matrix": (
            llama_lm_model(LlamaConfig.tiny()), None,
            lambda key: {"x": jax.random.randint(key, (2, 10), 0, 256),
                         "y": jnp.zeros((2, 10), jnp.int32)}),
        # a position table sliced by rows (``pos_emb[:l]``)
        "bert_every_matrix": (
            bert_classifier_model(BertConfig.tiny()), None,
            lambda key: {"x": jax.random.randint(key, (3, 10), 0, 128),
                         "y": jnp.zeros((3,), jnp.int32)}),
        # a position table added whole: nothing to adapt but the table
        "vit_every_matrix": (
            vit_model(ViTConfig.tiny()), None,
            lambda key: {"x": jax.random.normal(key, (2, 16, 16, 3)),
                         "y": jnp.zeros((2,), jnp.int32)}),
        "lstm_every_matrix": (
            lstm_lm_model(lstm), None,
            lambda key: {"x": jax.random.randint(key, (2, 10), 0, 32),
                         "y": jnp.zeros((2, 10), jnp.int32)}),
    }


@pytest.mark.parametrize("name", sorted(_models()))
def test_adapters_on_activations_equal_the_merged_weight(name):
    """``x W + s (x A) B`` (and for a table ``W[ids] + s A[ids] B``)
    against the model run on ``merge_lora``'s ``W + s A B``, outputs and
    the gradients of both factors, to float32 rounding."""
    base, target, make_batch = _models()[name]
    kw = {} if target is None else {"target": target}
    model = lora_wrap(base, rank=3, b_std=0.05, **kw)
    params = model.init(jax.random.key(0))
    batch = make_batch(jax.random.key(1))

    def merged_loss(lora):
        whole = merge_lora_model(model, {"base": params["base"], "lora": lora})
        return jnp.mean(base.per_example_loss(whole, batch, None))

    def adapted_loss(lora):
        return jnp.mean(model.per_example_loss(
            {"base": params["base"], "lora": lora}, batch, None))

    with jax.default_matmul_precision("highest"):
        want_out = base.apply(merge_lora_model(model, params), batch, None)
        got_out = model.apply(params, batch, None)
        want, want_g = jax.value_and_grad(merged_loss)(params["lora"])
        got, got_g = jax.value_and_grad(adapted_loss)(params["lora"])
    _close(got_out, want_out, rtol=1e-4)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        _close(g, w, rtol=1e-4)
    assert any(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree_util.tree_leaves(got_g))


def test_no_merged_weight_is_built_in_training():
    """The loss's program has no array of a base weight's shape with a
    client axis in front: under the client ``vmap`` only the rank-r
    products are per client."""
    model = decoder_lora_model(_hybrid(), compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=2, remat=False)
    params = model.init(jax.random.key(0))
    clients = 3
    lora = jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (clients,) + a.shape), params["lora"])
    batch = {"x": jnp.zeros((clients, 2, 8), jnp.int32),
             "y": jnp.zeros((clients, 2, 8), jnp.int32)}

    def wave(lora, base, batch):
        return jax.vmap(lambda lo, b: jax.grad(lambda q: jnp.mean(
            model.per_example_loss({"base": base, "lora": q}, b, None)))(lo),
            in_axes=(0, 0))(lora, batch)

    jaxpr = jax.make_jaxpr(wave)(lora, params["base"], batch)
    weights = {leaf.shape for leaf in
               jax.tree_util.tree_leaves(params["base"]) if leaf.ndim == 2}
    made = {v.aval.shape for eqn in _equations(jaxpr.jaxpr)
            for v in eqn.outvars if hasattr(v.aval, "shape")}
    assert not {(clients,) + w for w in weights} & made
    assert not {(clients,) + w[::-1] for w in weights} & made


class _Recorder:
    def __init__(self):
        self.opened = []

    def __call__(self, name, **attrs):
        recorder = self

        class Span(contextlib.AbstractContextManager):
            def __enter__(self):
                recorder.opened.append((name, attrs))
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **more):
                attrs.update(more)

        return Span()


def test_a_round_hands_the_base_back_and_says_what_it_weighs(monkeypatch):
    """After ``FedSim.run_round`` with ``trainable=lora_trainable`` every
    base leaf is the very array that went in (bfloat16, no cast, no
    copy), the adapters moved, and ``baton.round`` carries the bytes held
    once and the bytes held a client."""
    recorder = _Recorder()
    monkeypatch.setattr(engine, "annotate", recorder)
    model = decoder_lora_model(_hybrid(), rank=2, b_std=0.02)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (3, 2, 11), 0, 96)
    data = {"x": x[..., :-1], "y": x[..., 1:]}
    sim = FedSim(model, batch_size=1, learning_rate=0.05,
                 trainable=lora_trainable)
    res = sim.run_round(params, data, np.asarray([2, 2, 1], np.int32),
                        jax.random.key(2), n_epochs=1,
                        collect_client_losses=False)
    assert np.isfinite(float(res.loss_history[-1]))
    before = jax.tree_util.tree_leaves(params["base"])
    after = jax.tree_util.tree_leaves(res.params["base"])
    assert len(before) == len(after)
    assert all(a is b for a, b in zip(before, after))
    assert all(float(jnp.max(jnp.abs(a - b))) > 0 for a, b in zip(
        jax.tree_util.tree_leaves(res.params["lora"]),
        jax.tree_util.tree_leaves(params["lora"])))
    name, attrs = recorder.opened[0]
    assert name == "baton.round"
    assert attrs["frozen_bytes"] == sum(a.nbytes for a in before)
    assert attrs["trainable_bytes"] == sum(
        a.nbytes for a in jax.tree_util.tree_leaves(params["lora"]))


def test_a_round_says_how_many_blocks_keep_a_cores_outputs(monkeypatch):
    """The model meets its sequences' length where it is traced, after
    the first round's span has its facts; every later round says how
    many blocks keep a kernel's outputs. None here: no kernel on the
    CPU."""
    recorder = _Recorder()
    monkeypatch.setattr(engine, "annotate", recorder)
    model = decoder_lora_model(_hybrid(), rank=2, b_std=0.02)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (2, 1, 11), 0, 96)
    sim = FedSim(model, batch_size=1, learning_rate=0.05,
                 trainable=lora_trainable)
    for i in range(2):
        sim.run_round(params, {"x": x[..., :-1], "y": x[..., 1:]},
                      np.asarray([1, 1], np.int32), jax.random.key(2 + i),
                      n_epochs=1, collect_client_losses=False)
    first, second = (attrs for name, attrs in recorder.opened
                     if name == "baton.round")
    assert "core_outputs_kept" not in first
    assert second["core_outputs_kept"] == 0


@pytest.mark.parametrize("backend,batch,length,kept", [
    ("tpu", 1, 1024, 0),     # olmo_hybrid_c4_l1024: 134 MB of scores, dense
    ("tpu", 1, 4096, 2),     # the two full-attention layers of eight
    ("tpu", 64, 1024, 2),    # 1 GiB of scores: past the dense budget
    ("cpu", 1, 4096, 0),
])
def test_full_attention_keeps_a_kernels_outputs_where_dense_gives_way(
        backend, batch, length, kept):
    from baton_tpu.models.llama import core_outputs_kept
    from baton_tpu.models.transformer import dot_product_attention

    cfg = _hybrid(n_layers=8)
    assert core_outputs_kept(cfg, backend, batch, length) == kept
    # an attention of the caller's is not known to be a kernel
    assert core_outputs_kept(cfg, backend, batch, length,
                             dot_product_attention) == 0
