"""The hybrid decoder's mechanisms at test sizes on the CPU: the chunked
gated delta rule against the recurrence written token by token (values
and gradients, lengths that are and are not a multiple of the chunk,
keys that correlate, under a client ``vmap``); the chunk's inverse
against float64 and what its trace holds; a block of one kind traced
once whatever the depth; adapters on activations against the merged weight, the base kept
as the arrays it was given; the next-token loss in blocks against the
unblocked one; the ``baton.round`` span's byte counts."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import delta_rule, llama, transformer
from baton_tpu.models.bert import BertConfig, bert_classifier_model
from baton_tpu.models.delta_rule import chunked_delta_rule, gated_delta_init
from baton_tpu.models.llama import (
    LlamaConfig,
    decoder_lora_model,
    llama_lm_model,
    projection_lora_target,
)
from baton_tpu.models.lora import (
    lora_trainable,
    lora_wrap,
    merge_lora_model,
)
from baton_tpu.models.lstm import LSTMConfig, lstm_lm_model
from baton_tpu.models.mlp import mlp_classifier_model
from baton_tpu.models.transformer import (
    next_token_loss,
    per_token_cross_entropy,
)
from baton_tpu.models.vit import ViTConfig, vit_model
from baton_tpu.parallel import engine
from baton_tpu.parallel.engine import FedSim

PERIOD = ("linear_attention",) * 3 + ("full_attention",)


def _hybrid(n_layers=4, chunk=4, **kw):
    return LlamaConfig.tiny(
        vocab_size=96, max_len=32, d_model=64, n_layers=n_layers, n_heads=4,
        n_kv_heads=4, d_ff=128, rope_theta=None, qk_norm=True,
        layer_types=PERIOD * 2, linear_n_heads=4, linear_key_dim=8,
        linear_value_dim=16, linear_chunk=chunk, **kw)


def _token_by_token(q, k, v, g, beta):
    """``S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``: one ``lax.scan`` over the positions."""
    b, l, h, d_k = q.shape

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.einsum("bhd,bhde->bhe", k_t, state)
        state = state + jnp.einsum("bhd,bhe->bhde", k_t * b_t[..., None],
                                   v_t - seen)
        return state, jnp.einsum("bhd,bhde->bhe", q_t, state)

    by_position = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, h, d_k, v.shape[-1])),
                        by_position)
    return jnp.moveaxis(o, 0, 1)


def _scan_inputs(seed, lead, l, h=3, d_k=8, d_v=16):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    return (unit(jax.random.normal(ks[0], lead + (l, h, d_k))) * d_k ** -0.5,
            unit(jax.random.normal(ks[1], lead + (l, h, d_k))),
            jax.random.normal(ks[2], lead + (l, h, d_v)),
            -1.5 * jax.random.uniform(ks[3], lead + (l, h)),
            2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], lead + (l, h))))


def _keyed_inputs(keys, seed, lead, l, h=3, d_k=8, d_v=16):
    """``_scan_inputs`` with keys, gates and decays a trained mixer can
    produce and uncorrelated draws never do: ``correlated`` keys lie
    within 0.3 of one direction a head, ``beta = 1.9``, a decay within
    1 % of 1; ``identical`` keys are all the first unit vector, ``beta =
    2``, no decay (the chunk's ``A`` is exactly 2 everywhere below the
    diagonal, the largest the mixer can make)."""
    q, k, v, g, beta = _scan_inputs(seed, lead, l, h, d_k, d_v)
    if keys == "random":
        return q, k, v, g, beta
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    k0, k1 = jax.random.split(jax.random.key(seed + 100))
    direction = unit(jax.random.normal(k0, lead + (1, h, d_k)))
    if keys == "correlated":
        k = unit(direction + 0.3 * k)
        return (q, k, v, jnp.log1p(-0.01 * jax.random.uniform(k1, g.shape)),
                jnp.full_like(beta, 1.9))
    assert keys == "identical"
    return (q, jnp.zeros_like(k).at[..., 0].set(1.0), v, jnp.zeros_like(g),
            jnp.full_like(beta, 2.0))


def _chunk_matrix(k, g, beta):
    """``I + A`` of one chunk a batch element and head, in float64 on the
    host: ``A[t, s] = beta_t (k_t . k_s) alpha_(s+1) ... alpha_t`` below
    the diagonal. ``[B, H, L, L]`` from ``k [B, L, H, d_k]``."""
    k, g, beta = (np.moveaxis(np.asarray(a, np.float64), 1, 2)
                  for a in (k, g, beta))
    since = np.cumsum(g, axis=-1)
    decay = np.exp(since[..., :, None] - since[..., None, :])
    a = np.einsum("bhtd,bhsd->bhts", k * beta[..., None], k) * decay
    return np.tril(a, -1) + np.eye(a.shape[-1])


def _close(got, want, rtol=2e-5):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-12)
    assert float(jnp.max(jnp.abs(got - want))) <= rtol * scale


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _value_and_grads(fn):
    return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                              argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("length,chunk", [(16, 4), (10, 4), (7, 64),
                                          (130, 64), (64, 64)])
def test_chunked_delta_rule_is_the_recurrence_token_by_token(length, chunk):
    """Values and all five gradients; 10 and 130 tokens leave a tail
    chunk that is padded, 7 tokens are one chunk shorter than 64."""
    args = _scan_inputs(length, (2,), length)
    with jax.default_matmul_precision("highest"):
        want_o = _token_by_token(*args)
        got_o = chunked_delta_rule(*args, chunk)
        want, want_g = _value_and_grads(_token_by_token)(*args)
        got, got_g = _value_and_grads(
            lambda *a: chunked_delta_rule(*a, chunk))(*args)
    assert got_o.shape == want_o.shape == (2, length, 3, 16)
    _close(got_o, want_o)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        _close(g, w)


@pytest.mark.parametrize("keys,rtol", [("correlated", 2e-5),
                                       ("identical", 2e-4)])
def test_chunked_delta_rule_on_keys_that_correlate(keys, rtol):
    """Two chunks of 64 whose ``I + A`` is far from the identity, values
    and all five gradients. With identical keys the recurrence itself is
    ill-conditioned, the float32 token-by-token scan no less: that limit
    says no worse than forward substitution, not exact."""
    args = _keyed_inputs(keys, 5, (2,), 128)
    with jax.default_matmul_precision("highest"):
        want_o = _token_by_token(*args)
        got_o = chunked_delta_rule(*args, 64)
        _, want_g = _value_and_grads(_token_by_token)(*args)
        _, got_g = _value_and_grads(
            lambda *a: chunked_delta_rule(*a, 64))(*args)
    _close(got_o, want_o, rtol)
    for g, w in zip(got_g, want_g):
        _close(g, w, rtol)


@pytest.mark.parametrize("size", [1, 4, 7, 24, 50, 64])
@pytest.mark.parametrize("keys", ["random", "correlated", "identical"])
def test_the_chunk_inverse_against_float64(keys, size):
    """``_unit_lower_inverse`` of one chunk's ``I + A`` against
    ``numpy.linalg.inv`` in float64, to 1e-5 of the inverse's largest
    entry: a single row, the tests' chunk of 4, three sizes that are no
    power of two (7, 24 and 50 are padded to 8, 32 and 64) and the
    cell's 64."""
    _, k, _, g, beta = _keyed_inputs(keys, size, (2,), size)
    a = _chunk_matrix(k, g, beta).astype(np.float32)
    want = np.linalg.inv(a.astype(np.float64))
    got = np.asarray(delta_rule._unit_lower_inverse(jnp.asarray(a)))
    assert got.shape == a.shape and got.dtype == np.float32
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    assert not np.triu(got, 1).any()


def test_the_gradient_inverts_a_chunk_once_by_products_at_highest(monkeypatch):
    """What no CPU run shows by value. The trace of value and gradients
    (activations in bfloat16, as the cell runs them) holds no
    ``triangular_solve``; its products of two float32 operands are ``T
    rhs`` and the two of the hand-written backward (every other product
    has an operand in bfloat16) and each says ``Precision.HIGHEST``
    itself, because a TPU's default rounds float32 operands to bfloat16;
    the inverse holds no ``dot_general`` at all (its levels are sums of
    elementwise products, float32 on any backend); and the backward
    reuses the forward's inverse."""
    calls = []
    inverse = delta_rule._unit_lower_inverse
    monkeypatch.setattr(delta_rule, "_unit_lower_inverse",
                        lambda a: calls.append(a.shape) or inverse(a))
    q, k, v, g, beta = _keyed_inputs("correlated", 1, (2,), 128)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
    traced = jax.make_jaxpr(jax.value_and_grad(
        lambda *a: jnp.sum(chunked_delta_rule(*a, 64).astype(jnp.float32)),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert calls == [(2, 3, 2, 64, 64)]
    eqns = list(_equations(traced.jaxpr))
    assert "triangular_solve" not in {e.primitive.name for e in eqns}
    in_float32 = [
        e for e in eqns if e.primitive.name == "dot_general"
        and all(x.aval.dtype == jnp.float32 for x in e.invars)]
    assert len(in_float32) == 3
    for e in in_float32:
        precision = e.params["precision"]
        pair = precision if isinstance(precision, tuple) else (precision,) * 2
        assert all(p == jax.lax.Precision.HIGHEST for p in pair), e
    alone = jax.make_jaxpr(inverse)(jnp.zeros((2, 64, 64)))
    assert "dot_general" not in {
        e.primitive.name for e in _equations(alone.jaxpr)}


def test_chunked_delta_rule_under_a_client_vmap():
    """Vmapped over a client axis, values and gradients are each
    client's own."""
    args = _scan_inputs(3, (3, 2), 10)

    def loss(*a):
        return jnp.sum(jnp.sin(chunked_delta_rule(*a, 4)))

    with jax.default_matmul_precision("highest"):
        got_o = jax.vmap(lambda *a: chunked_delta_rule(*a, 4))(*args)
        got_g = jax.vmap(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args)
        for c in range(3):
            own = tuple(a[c] for a in args)
            _close(got_o[c], _token_by_token(*own))
            want_g = jax.grad(
                lambda *a: jnp.sum(jnp.sin(_token_by_token(*a))),
                argnums=(0, 1, 2, 3, 4))(*own)
            for g, w in zip(got_g, want_g):
                _close(g[c], w)


def test_a_masked_row_of_zeros_costs_a_step_nothing():
    """A padded row (token 0 throughout, mask 0) gives a finite loss and
    leaves the step's gradient what the real rows alone give."""
    model = decoder_lora_model(_hybrid(), compute_dtype=jnp.float32,
                               param_dtype=jnp.float32, rank=2, b_std=0.02)
    params = model.init(jax.random.key(0))
    x = jax.random.randint(jax.random.key(1), (3, 11), 1, 96)
    real = {"x": x[:, :-1], "y": x[:, 1:]}
    padded = {"x": jnp.concatenate([real["x"], jnp.zeros((1, 10), jnp.int32)]),
              "y": jnp.concatenate([real["y"], jnp.zeros((1, 10), jnp.int32)]),
              "mask": jnp.asarray([1.0, 1.0, 1.0, 0.0])}

    def grad(batch):
        return jax.value_and_grad(lambda lora: model.masked_loss(
            {"base": params["base"], "lora": lora}, batch, None))(
                params["lora"])

    (want, want_g), (got, got_g) = grad(real), grad(padded)
    assert np.isfinite(np.asarray(model.per_example_loss(
        params, padded, None))).all()
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, w in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        _close(g, w, rtol=1e-5)


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


# ----------------------------------------------------- the loss in blocks
@pytest.mark.parametrize("length", [12, 13])
@pytest.mark.parametrize("masked", [False, True])
def test_the_loss_in_blocks_is_the_unblocked_loss(length, masked, monkeypatch):
    """Values and gradients (of the hidden states and of the head), with
    blocks that divide the length and with a padded tail block; through
    the decoder with and without a ``loss_mask``."""
    b, d, v = 3, 16, 50
    kx, kw, ky = jax.random.split(jax.random.key(length), 3)
    x = jax.random.normal(kx, (b, length, d))
    w = jax.random.normal(kw, (d, v)) * d ** -0.5
    y = jax.random.randint(ky, (b, length), 0, v)
    weight = jnp.arange(1.0, b * length + 1).reshape(b, length)

    def unblocked(x, w):
        return per_token_cross_entropy(x @ w, y)

    def through(fn):
        return jax.value_and_grad(lambda x, w: jnp.sum(fn(x, w) * weight),
                                  argnums=(0, 1))(x, w)

    with jax.default_matmul_precision("highest"):
        want_tok, (want, want_g) = unblocked(x, w), through(unblocked)
        # one block's budget: 4 tokens of 3 rows -> 3 blocks (12), 4 (13)
        monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                            4 * b * 4 * v)
        blocked = lambda x, w: next_token_loss(x, w, y)  # noqa: E731
        text = str(jax.make_jaxpr(blocked)(x, w))
        assert "scan" in text and f"f32[{b},{length},{v}]" not in text
        got_tok, (got, got_g) = blocked(x, w), through(blocked)
    assert got_tok.shape == (b, length)
    _close(got_tok, want_tok, rtol=1e-6)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, wg in zip(got_g, want_g):
        _close(g, wg, rtol=1e-5)

    # the decoder's per-example loss, blocked and not
    model = llama_lm_model(LlamaConfig.tiny(vocab_size=v))
    params = model.init(jax.random.key(1))
    batch = {"x": y, "y": jnp.roll(y, -1, axis=1)}
    if masked:
        batch["loss_mask"] = (jnp.arange(length) < length // 2).astype(
            jnp.float32)[None].repeat(b, 0)
    got_loss = model.per_example_loss(params, batch, None)
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 2 ** 40)
    want_loss = model.per_example_loss(params, batch, None)
    np.testing.assert_allclose(np.asarray(got_loss), np.asarray(want_loss),
                               rtol=1e-5)


def test_block_count_follows_the_shapes():
    """The published head (100,352 ids) at 1,024 tokens a row is four
    blocks of 256; a small vocabulary is one block and no scan."""
    small = str(jax.make_jaxpr(lambda x, w: next_token_loss(
        x, w, jnp.zeros((2, 16), jnp.int32)))(
            jnp.zeros((2, 16, 8)), jnp.zeros((8, 96))))
    assert "scan" not in small
    big = jax.make_jaxpr(lambda x, w: next_token_loss(
        x, w, jnp.zeros((1, 1024), jnp.int32)))(
            jax.ShapeDtypeStruct((1, 1024, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((64, 100352), jnp.bfloat16))
    scans = [e for e in _equations(big.jaxpr) if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 4


@pytest.mark.parametrize("under_vmap", [False, True])
@pytest.mark.parametrize("shape", [(2, 16, 128), (4, 61, 203)])
def test_a_tokens_own_logit_by_comparison_is_the_gathers(shape, under_vmap):
    """Values and the gradient with respect to the logits against
    ``logz - take_along_axis``, at an aligned and a misaligned shape;
    a label outside ``[0, V)`` reads a logit of 0."""
    v = shape[-1]
    kl, ky, kg = jax.random.split(jax.random.key(v), 3)
    logits = 3.0 * jax.random.normal(kl, shape)
    labels = jax.random.randint(ky, shape[:-1], 0, v)
    weight = jax.random.normal(kg, shape[:-1])

    def gathered(logits, labels):
        own = jnp.take_along_axis(logits, labels[..., None], axis=-1)
        return jax.nn.logsumexp(logits, axis=-1) - own[..., 0]

    wrap = jax.vmap if under_vmap else (lambda fn: fn)
    ours, gathers = wrap(per_token_cross_entropy), wrap(gathered)

    def gradient(fn):
        return jax.grad(lambda z: jnp.sum(fn(z, labels) * weight))(logits)

    np.testing.assert_allclose(
        np.asarray(ours(logits, labels)),
        np.asarray(gathers(logits, labels)), rtol=0, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(gradient(ours)), np.asarray(gradient(gathers)),
        rtol=0, atol=1e-6)

    outside = labels.at[0, 0].set(v).at[-1, -1].set(-1)
    tok = np.asarray(ours(logits, outside))
    logz = np.asarray(jax.nn.logsumexp(logits, axis=-1))
    assert tok[0, 0] == logz[0, 0] and tok[-1, -1] == logz[-1, -1]
    np.testing.assert_array_equal(
        tok.ravel()[1:-1], np.asarray(ours(logits, labels)).ravel()[1:-1])


@pytest.mark.parametrize("scanned", [False, True])
@pytest.mark.parametrize("tied", [False, True])
def test_the_losss_gradient_lowers_without_a_scatter(tied, scanned,
                                                     monkeypatch):
    """A gather's transpose is a scatter-add into the whole block of
    logits; the comparison's is a ``where``."""
    b, l, d, v = 2, 12, 16, 50
    if scanned:  # blocks of 4 tokens
        monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                            4 * b * 4 * v)
    y = jnp.zeros((b, l), jnp.int32)

    def loss(x, w):
        return jnp.sum(next_token_loss(x, w, y, tied=tied))

    args = jnp.zeros((b, l, d)), jnp.zeros((v, d) if tied else (d, v))
    assert ("scan" in str(jax.make_jaxpr(loss)(*args))) == scanned
    lowered = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).as_text()
    assert "dot_general" in lowered and "scatter" not in lowered


def _eqns(jaxpr, name):
    return [e for e in _equations(jaxpr) if e.primitive.name == name]


@pytest.mark.parametrize("under_vmap", [False, True],
                         ids=["plain", "clients_under_vmap"])
@pytest.mark.parametrize("length,blocks", [(12, 1), (13, 4)],
                         ids=["one_block", "blocks_with_a_padded_tail"])
@pytest.mark.parametrize("head", ["frozen", "differentiated"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_the_losss_gradient_on_either_side_is_the_unblocked_losss(
        tied, head, length, blocks, under_vmap, monkeypatch):
    """Values, ``dx`` and (where the head takes a gradient) ``dW``
    against ``per_token_cross_entropy(x @ w, y)`` at ``highest``, under
    a per-token weight as a ``loss_mask`` gives and a multiplier on the
    logits; a label outside ``[0, V)`` reads a logit of 0 on both
    sides of the gradient too."""
    c, b, d, v, multiplier = 3, 2, 16, 50, 1.5
    kx, kw, ky, kg = jax.random.split(jax.random.key(length), 4)
    x = jax.random.normal(kx, (c, b, length, d))
    w = jax.random.normal(kw, (v, d) if tied else (d, v)) * d ** -0.5
    y = jax.random.randint(ky, (c, b, length), 0, v)
    y = y.at[0, 0, 0].set(v).at[-1, -1, -1].set(-1)
    weight = jax.random.uniform(kg, (c, b, length)) * (
        jnp.arange(length) % 3 != 1)
    if not under_vmap:
        x, y, weight = x[0], y[0], weight[0]
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                        4 * b * 4 * v if blocks > 1 else 2 ** 40)

    def unblocked(x, w, y):
        return per_token_cross_entropy(
            multiplier * (x @ (w.T if tied else w)), y)

    def blocked(x, w, y):
        return next_token_loss(x, w, y, tied=tied, multiplier=multiplier)

    def through(fn):
        over = (jax.vmap(fn, in_axes=(0, None, 0)) if under_vmap else fn)
        return jax.value_and_grad(
            lambda x, w: jnp.sum(over(x, w, y) * weight),
            argnums=(0, 1) if head == "differentiated" else 0)(x, w)

    x1, y1 = (x[0], y[0]) if under_vmap else (x, y)  # one client's
    with jax.default_matmul_precision("highest"):
        assert ("scan" in str(jax.make_jaxpr(blocked)(x1, w, y1))) == (
            blocks > 1)
        want, want_g = through(unblocked)
        got, got_g = through(blocked)
        tok = blocked(x1, w, y1)
        logz = jax.nn.logsumexp(
            multiplier * (x1 @ (w.T if tied else w)), axis=-1)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for g, wg in zip(jax.tree_util.tree_leaves(got_g),
                     jax.tree_util.tree_leaves(want_g)):
        assert g.shape == wg.shape and g.dtype == wg.dtype
        _close(g, wg, rtol=1e-5)
    assert float(tok[0, 0]) == pytest.approx(float(logz[0, 0]), rel=1e-6)


@pytest.mark.parametrize("under_vmap", [False, True],
                         ids=["plain", "clients_under_vmap"])
@pytest.mark.parametrize("head,dots,said", [("frozen", 2, 2),
                                            ("differentiated", 4, 3)])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_a_frozen_heads_gradient_is_made_in_the_forward(tied, head, dots, said,
                                                        under_vmap,
                                                        monkeypatch):
    """With the head closed over, the gradient of the blocked loss is
    one scan of two products a block, the logits and ``(softmax -
    onehot)`` back through the head, and a multiply by the cotangent;
    with the head differentiated the blocks are checkpointed as they
    were: the logits, the logits again and ``dx``, and ``dW`` the
    fourth. ``head_products_a_block`` says which was traced, and
    nothing after a call that traced no gradient of a blocked loss."""
    c, b, l, d, v = 3, 2, 12, 16, 50
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 4 * b * 4 * v)
    y = jnp.zeros((c, b, l), jnp.int32)
    x, w = jnp.zeros((c, b, l, d)), jnp.zeros((v, d) if tied else (d, v))

    def client(x, w, y):
        return jnp.sum(next_token_loss(x, w, y, tied=tied))

    def loss(x, w):
        if under_vmap:
            return jnp.sum(jax.vmap(client, in_axes=(0, None, 0))(x, w, y))
        return client(x[0], w, y[0])

    if head == "frozen":
        grad = jax.grad(lambda x: loss(x, w))
        jaxpr = jax.make_jaxpr(grad)(x).jaxpr
    else:
        jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr
    assert transformer.head_products_a_block() == said
    assert len(_eqns(jaxpr, "dot_general")) == dots
    assert len(_eqns(jaxpr, "scan")) == (1 if head == "frozen" else 2)
    assert bool(_eqns(jaxpr, "remat2")) == (head != "frozen")
    if head == "frozen":  # the scan keeps one array of the stream's shape
        scan, = _eqns(jaxpr, "scan")
        shapes = [a.aval.shape for a in scan.outvars]
        lead = (3, c) if under_vmap else (3,)
        assert sorted(shapes) == sorted([lead + (b, 4), lead + (b, 4, d)])
    jax.make_jaxpr(lambda x: loss(x, w))(x)  # values alone
    assert transformer.head_products_a_block() is None
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 2 ** 40)
    jax.make_jaxpr(jax.grad(lambda x: loss(x, w)))(x)  # one block
    assert transformer.head_products_a_block() is None


def test_a_loss_that_takes_no_cotangent_hands_none_back(monkeypatch):
    """The blocked loss as an output nothing differentiates, beside one
    that is: the stream's gradient is the other output's alone."""
    b, l, d, v = 2, 12, 16, 50
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES", 4 * b * 4 * v)
    x = jax.random.normal(jax.random.key(0), (b, l, d))
    w = jax.random.normal(jax.random.key(1), (d, v))
    y = jnp.zeros((b, l), jnp.int32)
    g, tok = jax.grad(lambda x: (jnp.sum(x), next_token_loss(x, w, y)),
                      has_aux=True)(x)
    np.testing.assert_array_equal(np.asarray(g), np.ones((b, l, d)))
    _close(tok, per_token_cross_entropy(x @ w, y), rtol=1e-5)


@pytest.mark.parametrize("head,said", [("frozen", 2), ("trained", 3)])
def test_a_decoder_says_which_side_its_loss_was_traced_on(head, said,
                                                          monkeypatch):
    """``span_attrs`` gains ``head_products_a_block`` where a gradient
    of the blocked loss is traced: 2 under adapters, whose base holds
    the head, 3 where the whole model trains; a model whose loss is one
    block says nothing."""
    cfg = _hybrid(n_layers=2)
    model = (decoder_lora_model(cfg, rank=2, b_std=0.02) if head == "frozen"
             else llama_lm_model(cfg))
    params = model.init(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 13), 0, cfg.vocab_size)
    batch = {"x": ids[:, :-1], "y": ids[:, 1:]}

    def loss(trained, held):
        p = {**held, **trained} if held else trained
        return jnp.sum(model.per_example_loss(p, batch, None))

    trained, held = (({"lora": params["lora"]}, {"base": params["base"]})
                     if head == "frozen" else (params, None))
    jax.make_jaxpr(jax.grad(loss))(trained, held)
    assert "head_products_a_block" not in dict(model.span_attrs)
    monkeypatch.setattr(transformer, "_LOGITS_BLOCK_BYTES",
                        4 * 2 * 4 * cfg.vocab_size)
    jax.make_jaxpr(jax.grad(loss))(trained, held)
    assert dict(model.span_attrs)["head_products_a_block"] == said


def test_gated_delta_init_draws_the_gates_as_the_papers_code_does():
    p = gated_delta_init(jax.random.key(0), 64, 4, 8, 16)
    a = np.exp(np.asarray(p["a_log"]))
    assert ((a > 0) & (a < 16)).all()
    dt = np.log1p(np.exp(np.asarray(p["dt_bias"])))  # softplus
    assert ((dt > 9e-4) & (dt < 0.11)).all()
