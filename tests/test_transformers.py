"""Transformer model family: BERT encoder, Llama-class decoder, ViT.

Coverage: attention-kernel numerics (GQA vs naive repeat, padding bias,
causal masking, RoPE norm preservation); shape/dtype contracts of every
model; LM loss masking; a federated round on each family; Llama + LoRA
(the BASELINE config-4 composition).
"""

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.models.bert import BertConfig, bert_classifier_model
from baton_tpu.models.llama import (
    LlamaConfig,
    llama_lm_model,
    llama_lora_target,
)
from baton_tpu.models.lora import lora_trainable, lora_wrap
from baton_tpu.models.transformer import (
    apply_rope,
    dot_product_attention,
    padding_bias,
    rope_angles,
)
from baton_tpu.models.vit import ViTConfig, vit_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim


# ---------------------------------------------------------------------------
# attention kernel numerics


def _naive_attention(q, k, v, bias=None, causal=False):
    """Reference oracle: explicitly repeat kv heads, plain softmax."""
    b, hq, l, dh = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    scores = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(dh)
    scores = scores.astype(jnp.float32)
    if bias is not None:
        scores = scores + bias
    if causal:
        idx = jnp.arange(l)
        scores = jnp.where(idx[:, None] >= idx[None, :], scores, -1e30)
    return jax.nn.softmax(scores, axis=-1).astype(v.dtype) @ v


def test_gqa_matches_naive_repeat(nprng):
    b, hq, hkv, l, dh = 2, 8, 2, 6, 4
    q = jnp.asarray(nprng.normal(size=(b, hq, l, dh)), jnp.float32)
    k = jnp.asarray(nprng.normal(size=(b, hkv, l, dh)), jnp.float32)
    v = jnp.asarray(nprng.normal(size=(b, hkv, l, dh)), jnp.float32)
    out = dot_product_attention(q, k, v)
    # the grouped reshape maps query head h to kv head h // rep; the
    # naive repeat maps kv head j to query heads [j*rep, (j+1)*rep) —
    # identical assignment, so outputs must agree elementwise
    oracle = _naive_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-6)


def test_causal_masking(nprng):
    b, h, l, dh = 1, 2, 5, 4
    q = jnp.asarray(nprng.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(nprng.normal(size=(b, h, l, dh)), jnp.float32)
    v = jnp.asarray(nprng.normal(size=(b, h, l, dh)), jnp.float32)
    out1 = dot_product_attention(q, k, v, causal=True)
    # position t must not see positions > t: perturbing the future
    # changes nothing
    k2 = k.at[:, :, -1].set(99.0)
    v2 = v.at[:, :, -1].set(99.0)
    out2 = dot_product_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :, :-1]),
                               np.asarray(out2[:, :, :-1]), rtol=1e-6)


def test_padding_bias_excludes_padded_keys(nprng):
    b, h, l, dh = 1, 2, 6, 4
    q = jnp.asarray(nprng.normal(size=(b, h, l, dh)), jnp.float32)
    k = jnp.asarray(nprng.normal(size=(b, h, l, dh)), jnp.float32)
    v = jnp.asarray(nprng.normal(size=(b, h, l, dh)), jnp.float32)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0]], jnp.float32)
    out = dot_product_attention(q, k, v, bias=padding_bias(mask))
    # changing masked-out keys/values must not change the output
    k2 = k.at[:, :, 4:].set(7.0)
    v2 = v.at[:, :, 4:].set(-7.0)
    out2 = dot_product_attention(q, k2, v2, bias=padding_bias(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), rtol=1e-6)


def test_rope_preserves_norm_and_relative_position(nprng):
    l, dh = 8, 8
    cos, sin = rope_angles(l, dh)
    x = jnp.asarray(nprng.normal(size=(1, 1, l, dh)), jnp.float32)
    r = apply_rope(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(r), axis=-1),
        rtol=1e-5,
    )
    # q.k after RoPE depends only on relative offset: shift both by one
    q = jnp.asarray(nprng.normal(size=(1, 1, l, dh)), jnp.float32)
    k = jnp.asarray(nprng.normal(size=(1, 1, l, dh)), jnp.float32)
    qr, kr = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    dots = np.asarray(jnp.einsum("bhqd,bhkd->bhqk", qr, kr))
    # place the same vectors one position later
    q2 = jnp.roll(q, 1, axis=2)
    k2 = jnp.roll(k, 1, axis=2)
    q2r, k2r = apply_rope(q2, cos, sin), apply_rope(k2, cos, sin)
    dots2 = np.asarray(jnp.einsum("bhqd,bhkd->bhqk", q2r, k2r))
    np.testing.assert_allclose(dots[0, 0, 2, 1], dots2[0, 0, 3, 2], rtol=1e-4)


# ---------------------------------------------------------------------------
# model contracts


def test_bert_shapes_and_round(nprng):
    cfg = BertConfig.tiny()
    model = bert_classifier_model(cfg)
    params = model.init(jax.random.key(0))
    batch = {
        "x": jnp.asarray(nprng.integers(0, cfg.vocab_size, size=(3, cfg.max_len)),
                         jnp.int32),
        "attn_mask": jnp.ones((3, cfg.max_len), jnp.float32),
        "y": jnp.zeros((3,), jnp.int32),
    }
    logits = model.apply(params, batch, jax.random.key(1))
    assert logits.shape == (3, cfg.n_classes)
    assert logits.dtype == jnp.float32
    losses = model.per_example_loss(params, batch, jax.random.key(1))
    assert losses.shape == (3,)

    datasets = []
    for _ in range(4):
        n = int(nprng.integers(6, 12))
        datasets.append({
            "x": nprng.integers(0, cfg.vocab_size, size=(n, cfg.max_len)).astype(np.int32),
            "y": nprng.integers(0, cfg.n_classes, size=(n,)).astype(np.int32),
        })
    data, n_samples = stack_client_datasets(datasets, batch_size=8)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    sim = FedSim(model, batch_size=8, learning_rate=0.01)
    res = sim.run_round(params, data, jnp.asarray(n_samples),
                        jax.random.key(2), n_epochs=1)
    assert np.isfinite(float(res.loss_history[0]))


def test_llama_lm_loss_masking(nprng):
    cfg = LlamaConfig.tiny()
    model = llama_lm_model(cfg)
    params = model.init(jax.random.key(0))
    l = cfg.max_len
    batch = {
        "x": jnp.asarray(nprng.integers(0, cfg.vocab_size, size=(2, l)), jnp.int32),
        "y": jnp.asarray(nprng.integers(0, cfg.vocab_size, size=(2, l)), jnp.int32),
        "loss_mask": jnp.ones((2, l), jnp.float32),
    }
    logits = model.apply(params, batch, jax.random.key(1))
    assert logits.shape == (2, l, cfg.vocab_size)
    full = model.per_example_loss(params, batch, jax.random.key(1))
    assert full.shape == (2,)
    # masking out half the tokens changes the per-sequence mean unless the
    # per-token losses happen to be equal — and must ignore target values
    # under the masked positions entirely
    half = jnp.concatenate(
        [jnp.ones((2, l // 2)), jnp.zeros((2, l - l // 2))], axis=1
    ).astype(jnp.float32)
    batch_garbage = dict(batch, loss_mask=half,
                         y=batch["y"].at[:, l // 2:].set(0))
    batch_clean = dict(batch, loss_mask=half)
    l1 = model.per_example_loss(params, batch_clean, jax.random.key(1))
    l2 = model.per_example_loss(params, batch_garbage, jax.random.key(1))
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-6)


def test_llama_causality_end_to_end(nprng):
    cfg = LlamaConfig.tiny()
    model = llama_lm_model(cfg)
    params = model.init(jax.random.key(0))
    x = jnp.asarray(nprng.integers(0, cfg.vocab_size, size=(1, cfg.max_len)),
                    jnp.int32)
    batch = {"x": x, "y": x}
    logits = model.apply(params, batch, jax.random.key(1))
    x2 = x.at[0, -1].set((x[0, -1] + 1) % cfg.vocab_size)
    logits2 = model.apply(params, {"x": x2, "y": x2}, jax.random.key(1))
    np.testing.assert_allclose(np.asarray(logits[:, :-1]),
                               np.asarray(logits2[:, :-1]), rtol=1e-5)


def test_llama_lora_federated_round(nprng):
    """BASELINE config 4 in miniature: Llama + LoRA on attention
    projections, adapters-only aggregation."""
    cfg = LlamaConfig.tiny()
    base = llama_lm_model(cfg)
    model = lora_wrap(base, rank=2, target=llama_lora_target)
    params = model.init(jax.random.key(0))

    datasets = []
    for _ in range(2):
        n = int(nprng.integers(4, 8))
        toks = nprng.integers(0, cfg.vocab_size, size=(n, cfg.max_len)).astype(np.int32)
        datasets.append({"x": toks, "y": toks})
    data, n_samples = stack_client_datasets(datasets, batch_size=4)
    data = {k: jnp.asarray(v) for k, v in data.items()}

    sim = FedSim(model, batch_size=4, learning_rate=0.01,
                 trainable=lora_trainable)
    res = sim.run_round(params, data, jnp.asarray(n_samples),
                        jax.random.key(2), n_epochs=1)
    assert np.isfinite(float(res.loss_history[0]))
    # base weights byte-identical, at least one adapter leaf moved
    for a, b in zip(jax.tree_util.tree_leaves(res.params["base"]),
                    jax.tree_util.tree_leaves(params["base"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    moved = [
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(res.params["lora"]),
                        jax.tree_util.tree_leaves(params["lora"]))
    ]
    assert max(moved) > 0


def test_vit_shapes_and_round(nprng):
    cfg = ViTConfig.tiny()
    model = vit_model(cfg)
    params = model.init(jax.random.key(0))
    batch = {
        "x": jnp.asarray(nprng.normal(size=(2, 16, 16, 3)), jnp.float32),
        "y": jnp.zeros((2,), jnp.int32),
    }
    logits = model.apply(params, batch, jax.random.key(1))
    assert logits.shape == (2, cfg.n_classes)

    datasets = []
    for _ in range(2):
        n = int(nprng.integers(5, 9))
        datasets.append({
            "x": nprng.normal(size=(n, 16, 16, 3)).astype(np.float32),
            "y": nprng.integers(0, 10, size=(n,)).astype(np.int32),
        })
    data, n_samples = stack_client_datasets(datasets, batch_size=4)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    sim = FedSim(model, batch_size=4, learning_rate=0.01)
    res = sim.run_round(params, data, jnp.asarray(n_samples),
                        jax.random.key(2), n_epochs=1)
    assert np.isfinite(float(res.loss_history[0]))


def test_vit_b16_param_count():
    model = vit_model(ViTConfig.b16())
    # count without materializing: eval_shape avoids allocating 86M params
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 85_000_000 < n < 88_000_000  # ViT-B/16 is ~86.6M


def test_bert_base_param_count():
    model = bert_classifier_model(BertConfig.base())
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    # BERT-base encoder ~110M minus the token-type table/tied head
    assert 100_000_000 < n < 115_000_000


def _grad_allclose(model_a, model_b, params, batch):
    """loss+grad equality between two builds of the same architecture."""
    key = jax.random.key(2)

    def loss(m):
        return lambda p: m.per_example_loss(p, batch, key).mean()

    l0, g0 = jax.value_and_grad(loss(model_a))(params)
    l1, g1 = jax.value_and_grad(loss(model_b))(params)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_bert_remat_matches_no_remat():
    # remat is a pure scheduling choice: loss and grads must be identical
    # (matches the Llama seam test, tests/test_hybrid_tp.py)
    cfg = BertConfig.tiny()
    params = bert_classifier_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(3)
    batch = {
        "x": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, cfg.max_len)),
                         jnp.int32),
        "attn_mask": jnp.asarray(
            rng.integers(0, 2, (4, cfg.max_len)), jnp.float32
        ).at[:, 0].set(1.0),
        "y": jnp.asarray(rng.integers(0, cfg.n_classes, (4,)), jnp.int32),
    }
    _grad_allclose(bert_classifier_model(cfg),
                   bert_classifier_model(cfg, remat=True), params, batch)


def test_vit_remat_matches_no_remat():
    cfg = ViTConfig.tiny()
    params = vit_model(cfg).init(jax.random.key(0))
    rng = np.random.default_rng(4)
    batch = {
        "x": jnp.asarray(rng.normal(size=(
            4, cfg.image_size, cfg.image_size, cfg.channels)), jnp.float32),
        "y": jnp.asarray(rng.integers(0, cfg.n_classes, (4,)), jnp.int32),
    }
    _grad_allclose(vit_model(cfg), vit_model(cfg, remat=True), params, batch)
