"""The Mamba-2 state-space mixer (``models/state_space.py``) and the
block that runs it beside attention (``llama.MIXERS[
"parallel_ssm_attention"]``), at small sizes on the CPU in float32: the
chunked recurrence against the recurrence token by token (value and
every gradient, at lengths that are no multiple of the chunk, with
decays under which an unmasked exponential overflows); the branch
against an oracle written here, with ``D``, the convolution's bias, the
gated norm's grouping, the groups of ``B`` and ``C`` and each of the
branch's multipliers told apart from their absence; the pair's
parameters, adapters, facts and kept outputs; a head's width that is
not ``d_model / n_heads``; and that multipliers of 1 and the new
arguments' defaults leave the other models' programs as they were."""

import dataclasses
import hashlib
import json
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from baton_tpu.models import llama, state_space, transformer
from baton_tpu.models.llama import LlamaConfig, MIXERS
from baton_tpu.models.lora import lora_trainable
from baton_tpu.models.state_space import SSMConfig, chunked_ssd, mamba2_apply
from baton_tpu.models.transformer import Multipliers

REPO = pathlib.Path(__file__).resolve().parent.parent
KIND = "parallel_ssm_attention"
# Falcon-H1-34B's, as its configuration's file hands them to the
# program (``tests/fedbench/test_fedbench_falcon_h1.py`` holds the file
# to the published digits)
_FILE = json.loads((REPO / "fedbench" / "configs" / "falcon_h1_34b.json"
                    ).read_text())
PUBLISHED = Multipliers(**{
    name: _FILE[spec["$key"]] for name, spec in _FILE["builder"]["kwargs"][
        "config"]["kwargs"]["multipliers"]["kwargs"].items()})


def _token_by_token(x, delta, a, b_mat, c_mat, d_skip):
    """The recurrence as its three lines are written."""
    b, l, h, p = x.shape
    g, n = b_mat.shape[2:]

    def step(state, at):
        x_t, d_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(m, h // g, axis=1) for m in (b_t, c_t))
        state = jnp.exp(d_t * a)[..., None, None] * state \
            + d_t[..., None, None] * b_t[..., :, None] * x_t[..., None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state) \
            + d_skip[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((b, h, n, p)), tuple(
        jnp.moveaxis(m, 1, 0) for m in (x, delta, b_mat, c_mat)))
    return jnp.moveaxis(y, 0, 1)


def _operands(seed, b, l, h, p, g, n, steepest=16.0):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (b, l, h, p)),
            jax.random.uniform(ks[1], (b, l, h), minval=0.5, maxval=3.0),
            -jnp.linspace(0.01, steepest, h),
            jax.random.normal(ks[2], (b, l, g, n)),
            jax.random.normal(ks[3], (b, l, g, n)),
            jax.random.uniform(ks[4], (h,), minval=0.5, maxval=1.5)), \
        jax.random.normal(ks[5], (b, l, h, p))


@pytest.mark.parametrize("length,chunk", [(23, 8), (16, 8), (5, 8), (13, 1)])
def test_the_chunked_recurrence_is_the_recurrence(length, chunk):
    """Value and all six gradients. ``delta A`` reaches -48 a token: over
    a chunk of 8 the running sums differ by hundreds, and above the
    diagonal ``exp`` of that difference is infinite in float32."""
    args, weight = _operands(length, 2, length, 4, 8, 2, 6)
    assert float(jnp.max(args[1] * -args[2][-1])) * min(chunk, length - 1) \
        > 88.8 or chunk == 1
    got = chunked_ssd(*args, chunk)
    want = _token_by_token(*args)
    assert got.shape == want.shape == (2, length, 4, 8)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * weight), argnums=range(6))(
        *args) for f in (lambda *a: chunked_ssd(*a, chunk), _token_by_token)]
    for name, g, w in zip("x delta a B C D".split(), *grads):
        assert bool(jnp.isfinite(g).all()), name
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * float(
            jnp.max(jnp.abs(w))), name


def test_above_the_diagonal_the_exponent_overflows_on_these_decays():
    """What the mask is for: in the first chunk of the operands above,
    token i < j would read token j through ``exp(since_i - since_j)``,
    an exponent past what float32 holds; masked after the exponential
    alone that is ``inf * 0``. The function's value and gradients are
    finite on them."""
    args, weight = _operands(23, 2, 23, 4, 8, 2, 6)
    delta, a = args[1], args[2]
    since = jnp.cumsum((delta * a)[:, :8], axis=1)  # [B, C, H]
    gap = since[:, :, None] - since[:, None, :]
    above = float(jnp.max(jnp.where(
        jnp.triu(jnp.ones((8, 8), bool), 1)[None, :, :, None], gap, 0.0)))
    assert above > 88.8
    assert not bool(jnp.isfinite(jnp.exp(jnp.float32(above)) * 0.0))
    grads = jax.grad(lambda *a: jnp.sum(chunked_ssd(*a, 8) * weight),
                     argnums=range(6))(*args)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def _branch(seed=0, multipliers=Multipliers(), **sizes):
    cfg = SSMConfig(**{**dict(n_heads=4, head_dim=8, d_state=6, n_groups=2,
                              conv_taps=4, chunk=6), **sizes})
    p = state_space.mamba2_init(jax.random.key(seed), 24, cfg, multipliers,
                                out_std=0.1)
    u = jax.random.normal(jax.random.key(seed + 1), (2, 16, 24))
    return cfg, p, u


def _oracle(p, u, cfg, on=Multipliers(), groups=None, share_bc=True):
    """The branch as the module's docstring writes it, token by token,
    the convolution by ``lax.conv_general_dilated``."""
    b, l, _ = u.shape
    h, g, n, width = cfg.n_heads, cfg.n_groups, cfg.d_state, cfg.head_dim
    proj = ((on.ssm_in * u) @ p["in_proj"]) * cfg.spread(on.ssm)
    z, xbc, dt = jnp.split(proj, (cfg.d_ssm, sum(cfg.parts[:4])), axis=-1)
    xbc = jax.lax.conv_general_dilated(
        jnp.pad(xbc, ((0, 0), (cfg.conv_taps - 1, 0), (0, 0))),
        p["conv_w"][:, None, :], (1,), "VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=xbc.shape[-1]) + p["conv_b"]
    xbc = xbc * jax.nn.sigmoid(xbc)
    xs, b_mat, c_mat = jnp.split(xbc, (cfg.d_ssm, cfg.d_ssm + g * n), axis=-1)
    b_mat, c_mat = (m.reshape(b, l, g, n) for m in (b_mat, c_mat))
    if not share_bc:  # every head reads group 0
        b_mat, c_mat = (jnp.repeat(m[:, :, :1], g, axis=2)
                        for m in (b_mat, c_mat))
    y = _token_by_token(xs.reshape(b, l, h, width),
                        jax.nn.softplus(dt + p["dt_bias"]),
                        -jnp.exp(p["a_log"]), b_mat, c_mat, p["d"])
    y = y.reshape(b, l, cfg.d_ssm) * (z * jax.nn.sigmoid(z))
    y = y.reshape(b, l, groups or g, -1)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + cfg.norm_eps)
    return on.ssm_out * ((y.reshape(b, l, cfg.d_ssm) * p["norm"])
                         @ p["out_proj"])


def _apart(got, want) -> float:
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("on", [Multipliers(), PUBLISHED],
                         ids=["ones", "published"])
def test_the_branch_is_its_equations(on):
    cfg, p, u = _branch(multipliers=on)
    got = mamba2_apply(p, u, cfg, on)
    assert got.shape == u.shape
    assert _apart(got, _oracle(p, u, cfg, on)) < 1e-5
    grads = [jax.grad(lambda p, u: jnp.sum(f(p, u) ** 2), argnums=(0, 1))(
        p, u) for f in (lambda p, u: mamba2_apply(p, u, cfg, on),
                        lambda p, u: _oracle(p, u, cfg, on))]
    for g, w in zip(*(jax.tree_util.tree_leaves(t) for t in grads)):
        assert _apart(g, w) < 1e-4


@pytest.mark.parametrize("term", ["d", "conv_b", "norm", "dt_bias", "conv_w"])
def test_each_vector_of_the_branch_is_applied(term):
    """Taken out on the oracle's side (ones or zeros in its place), the
    comparison that held fails: ``init`` draws none of them at the
    value that leaves a term out."""
    cfg, p, u = _branch(seed=3)
    neutral = {"d": jnp.zeros, "conv_b": jnp.zeros, "norm": jnp.ones,
               "dt_bias": jnp.zeros, "conv_w": jnp.ones}[term]
    without = dict(p, **{term: neutral(p[term].shape)})
    assert _apart(mamba2_apply(p, u, cfg), _oracle(without, u, cfg)) > 1e-2


def test_the_gated_norm_is_over_a_group_and_heads_read_their_own_group():
    cfg, p, u = _branch(seed=4)
    got = mamba2_apply(p, u, cfg)
    assert _apart(got, _oracle(p, u, cfg)) < 1e-5
    assert _apart(got, _oracle(p, u, cfg, groups=1)) > 1e-3  # one norm
    assert _apart(got, _oracle(p, u, cfg, groups=4)) > 1e-3  # one a head
    assert _apart(got, _oracle(p, u, cfg, share_bc=False)) > 1e-2


@pytest.mark.parametrize("name", ["ssm_in", "ssm_out", "z", "x", "B", "C",
                                  "dt"])
def test_each_multiplier_of_the_branch_is_applied(name):
    """One multiplier set to 1 on the oracle's side alone."""
    cfg, p, u = _branch(seed=5, multipliers=PUBLISHED)
    got = mamba2_apply(p, u, cfg, PUBLISHED)
    assert _apart(got, _oracle(p, u, cfg, PUBLISHED)) < 1e-5
    five = list(PUBLISHED.ssm)
    if name in ("ssm_in", "ssm_out"):
        changed = dataclasses.replace(PUBLISHED, **{name: 1.0})
    else:
        five["z x B C dt".split().index(name)] = 1.0
        changed = dataclasses.replace(PUBLISHED, ssm=tuple(five))
    assert _apart(got, _oracle(p, u, cfg, changed)) > 1e-3


def test_a_draw_stands_against_its_multipliers():
    """Every multiplied projection has the scale it has at multipliers
    of 1: the draw's deviation is its usual one over the multiplier."""
    cfg = SSMConfig(n_heads=4, head_dim=16, d_state=16, n_groups=2)
    plain = state_space.mamba2_init(jax.random.key(0), 64, cfg, out_std=0.1)
    drawn = state_space.mamba2_init(jax.random.key(0), 64, cfg, PUBLISHED,
                                    out_std=0.1)
    on_columns = PUBLISHED.ssm_in * cfg.spread(PUBLISHED.ssm)
    np.testing.assert_allclose(drawn["in_proj"] * on_columns,
                               plain["in_proj"], rtol=1e-6)
    np.testing.assert_allclose(drawn["out_proj"] * PUBLISHED.ssm_out,
                               plain["out_proj"], rtol=1e-6)
    for name in ("conv_w", "conv_b", "a_log", "dt_bias", "d", "norm"):
        assert bool(jnp.all(drawn[name] == plain[name])), name
    # none at the value that would leave its term out
    assert float(jnp.min(jnp.abs(plain["d"]))) > 0.4
    assert float(jnp.max(jnp.abs(plain["norm"] - 1))) > 0.3
    assert float(jnp.max(jnp.abs(plain["conv_b"]))) > 0.3
    u = jax.random.normal(jax.random.key(1), (1, 32, 64))
    assert _apart(mamba2_apply(drawn, u, cfg, PUBLISHED),
                  mamba2_apply(plain, u, cfg)) < 1e-4
    cfg5 = LlamaConfig.tiny(n_heads=5, n_kv_heads=1, head_dim=8,
                            multipliers=PUBLISHED)
    attn = MIXERS["full_attention"].init(jax.random.key(2), cfg5, 0.1)
    ones = MIXERS["full_attention"].init(
        jax.random.key(2), dataclasses.replace(cfg5, multipliers=Multipliers()),
        0.1)
    np.testing.assert_allclose(attn["wk"] * PUBLISHED.key, ones["wk"],
                               rtol=1e-6)
    np.testing.assert_allclose(attn["wo"] * PUBLISHED.attention_out,
                               ones["wo"], rtol=1e-6)
    assert bool(jnp.all(attn["wq"] == ones["wq"]))
    mlp = transformer.swiglu_init(jax.random.key(3), 64, 96, PUBLISHED.mlp)
    ones = transformer.swiglu_init(jax.random.key(3), 64, 96)
    np.testing.assert_allclose(mlp["w_gate"] * PUBLISHED.mlp[0],
                               ones["w_gate"], rtol=1e-6)
    np.testing.assert_allclose(mlp["w_down"] * PUBLISHED.mlp[1],
                               ones["w_down"], rtol=1e-6)
    assert bool(jnp.all(mlp["w_up"] == ones["w_up"]))


def _pair_config(**kw) -> LlamaConfig:
    return LlamaConfig.tiny(**{**dict(
        vocab_size=96, n_layers=2, n_heads=5, n_kv_heads=1, head_dim=8,
        d_ff=96, rope_theta=100000000000, embed_std=1.0, norm_eps=1e-5,
        layer_types=(KIND,) * 2, multipliers=PUBLISHED,
        ssm=SSMConfig(n_heads=4, head_dim=8, d_state=6, n_groups=2,
                      chunk=6)), **kw})


def test_a_heads_width_is_the_configurations_own():
    assert LlamaConfig(d_model=5120, n_heads=20, head_dim=128).head_dim == 128
    assert LlamaConfig(d_model=5120, n_heads=20).head_dim == 256
    assert LlamaConfig.tiny().head_dim == 16
    cfg = _pair_config()
    block = llama._block_init(jax.random.key(0), cfg, KIND)
    attn = block["parallel"]["attention"]
    assert attn["wq"].shape == (64, 40) and attn["wo"].shape == (40, 64)
    assert attn["wk"].shape == attn["wv"].shape == (64, 8)
    cos, sin = MIXERS[KIND].rope(cfg, 16)
    assert cos.shape == sin.shape == (16, 4)
    assert bool(jnp.isfinite(cos).all())  # theta 1e11, an integer past 2**32
    x = jax.random.normal(jax.random.key(1), (2, 16, 64))
    y, _ = llama._block_apply(block, x, None, cfg, (cos, sin),
                              transformer.default_attention)
    assert y.shape == x.shape and bool(jnp.isfinite(y).all())


def test_the_pair_is_two_branches_over_one_normed_input():
    cfg = _pair_config()
    block = llama._block_init(jax.random.key(0), cfg, KIND)
    x = jax.random.normal(jax.random.key(1), (2, 16, 64))
    rope = MIXERS[KIND].rope(cfg, 16)
    got = llama._mix(block, x, cfg, rope, transformer.default_attention)
    u = transformer.rms_norm(x, block["norm_attn"], cfg.norm_eps)
    pair = block["parallel"]
    on = cfg.multipliers
    attended = on.attention_out * transformer.mha_apply(
        dict(pair["attention"], wk=pair["attention"]["wk"] * on.key),
        on.attention_in * u, 5, n_kv_heads=1, causal=True, rope=rope)
    want = x + _oracle(pair["ssm"], u, cfg.ssm, on) + attended
    assert _apart(got, want) < 1e-5
    # each branch is there, and under its own multiplier
    assert _apart(got, x + attended) > 1e-2
    assert _apart(got, want - attended) > 1e-2
    assert _apart(got, want + (1 / on.attention_out - 1) * attended) > 1e-2


def test_lora_reaches_the_nine_projections_of_a_block_and_nothing_else():
    cfg = _pair_config()
    model = llama.decoder_lora_model(cfg, compute_dtype=jnp.float32,
                                     param_dtype=jnp.float32, rank=4)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    assert sorted(shapes["lora"]) == sorted(
        f"blocks/{i}/{name}" for i in range(2) for name in (
            "parallel/ssm/in_proj", "parallel/ssm/out_proj",
            "parallel/attention/wq", "parallel/attention/wk",
            "parallel/attention/wv", "parallel/attention/wo",
            "mlp/w_gate", "mlp/w_up", "mlp/w_down"))
    for name in ("conv_w", "conv_b", "a_log", "d", "dt_bias", "norm"):
        leaf = shapes["base"]["blocks"][0]["parallel"]["ssm"][name]
        assert not llama.projection_lora_target(
            f"blocks/0/parallel/ssm/{name}", leaf), name
    # a branch's names under another mixer's key are not that mixer's
    assert not llama.projection_lora_target("blocks/0/attn/in_proj", None)
    assert not llama.projection_lora_target("blocks/0/parallel/wq", None)
    assert not llama.projection_lora_target("blocks/0/ssm/in_proj", None)
    assert llama.projection_lora_target("blocks/0/attn/wq", None)
    assert dict(model.span_attrs) == {
        "ssm_heads": 4, "ssm_state": 6, "ssm_groups": 2, "ssm_chunk": 6,
        "conv_taps": 4}


def test_the_pairs_kernel_and_kept_outputs_are_the_attention_branchs():
    cfg = _pair_config(n_layers=3, layer_types=(KIND,) * 3)
    mixer = MIXERS[KIND]
    assert not mixer.keeps_its_inputs(cfg, 4096)
    assert mixer.core_is_kernel(cfg, "tpu", 1, 4096,
                                transformer.default_attention)
    assert not mixer.core_is_kernel(cfg, "cpu", 1, 4096,
                                    transformer.default_attention)
    assert not mixer.core_is_kernel(cfg, "tpu", 1, 4096, lambda *a, **k: None)
    assert llama.core_outputs_kept(cfg, "tpu", 1, 4096) == 3
    assert llama.core_outputs_kept(cfg, "tpu", 1, 64) == 0
    assert mixer.seen(cfg, 16) == {"ssm_chunks": 3}
    assert mixer.seen(cfg, 4) == {"ssm_chunks": 1}


def _round(cfg, seed=0, length=16, remat=True):
    from baton_tpu.parallel.engine import FedSim

    model = llama.decoder_lora_model(cfg, compute_dtype=jnp.float32,
                                     param_dtype=jnp.float32, rank=4,
                                     b_std=0.02, remat=remat)
    params = model.init(jax.random.key(seed))
    first = jax.random.randint(jax.random.key(seed + 1), (3, 1, 1), 0,
                               cfg.vocab_size)
    tokens = (first + 7 * jnp.arange(length + 1)) % cfg.vocab_size
    data = {"x": tokens[..., :-1], "y": tokens[..., 1:]}
    sim = FedSim(model, batch_size=1, learning_rate=0.05,
                 trainable=lora_trainable)
    return model, sim, params, data, np.asarray([1, 1, 1], np.int32)


def test_rounds_of_the_pair_train_the_adapters_alone_and_the_loss_falls():
    model, sim, params, data, n = _round(_pair_config())
    losses, p = [], params
    for i in range(3):
        res = sim.run_round(p, data, n, jax.random.key(5 + i), n_epochs=1,
                            collect_client_losses=False)
        losses.append(float(res.loss_history[-1]))
        p = res.params
    assert losses[2] < losses[1] < losses[0]
    for a, b in zip(jax.tree_util.tree_leaves(params["base"]),
                    jax.tree_util.tree_leaves(p["base"])):
        assert a is b
    moved = [float(jnp.max(jnp.abs(a - b))) for a, b in zip(
        jax.tree_util.tree_leaves(params["lora"]),
        jax.tree_util.tree_leaves(p["lora"]))]
    assert len(moved) == 2 * 9 * 2 and min(moved) > 0
    assert dict(model.span_attrs)["ssm_chunks"] == 3
    assert dict(model.span_attrs)["core_outputs_kept"] == 0  # the CPU


def test_under_the_client_vmap_the_program_holds_no_convolution():
    """The convolution is slices and products: ``lax.conv`` under the
    client ``vmap`` would be a grouped convolution over the clients.
    The oracle's own convolution, compiled the same way, is one."""
    _, sim, params, data, n = _round(_pair_config())
    text = sim.lower_wave(params, data, n, jax.random.key(2), 1,
                          None).compile().as_text()
    assert "ssm_conv" in text and "ssd_scan" in text
    assert " convolution(" not in text
    cfg, p, u = _branch()
    oracle = jax.jit(jax.vmap(lambda u: _oracle(p, u[None], cfg))).lower(
        u).compile().as_text()
    assert " convolution(" in oracle


# ---------------------------------------------------------------------
# the other models' programs
DIGESTS = REPO / "tests" / "decoder_program_digests.json"
LOCATION = re.compile(
    r',? ?(source_file="[^"]*"|source_line=\d+|source_end_line=\d+'
    r'|source_column=\d+|source_end_column=\d+|stack_frame_id=\d+)')
UNCHANGED = ["olmo_hybrid_7b", "sarvam_105b", "glm_5", "zaya1_8b",
             "bert_base", "falcon_h1_34b", "mellum2_12b"]


def program_digest(name: str) -> dict:
    """The wave program of the configuration's first cell at ``tiny``
    sizes as JAX lowers it for the CPU, every instruction with its
    ``op_name`` scopes, source positions apart: its instruction count
    and a hash of the text."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from fedbench import manifest, run

    bench = manifest.load_manifest(str(REPO))
    cell = [w["name"] for w in bench["workloads"] if w["config"] == name][0]
    config = manifest.load_config(str(REPO), bench, name)
    job = run.job_of(manifest.load_workload(str(REPO), cell), True)
    # a function JAX jits inside (a key's split) keeps the op_name of
    # whoever traced it first in the process
    jax.clear_caches()
    _, params, n_samples, _, data, _, sim = run.build_cell(
        str(REPO), config, job, 1, 0, True)
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True  # the instructions' op_name scopes
    # names by position, not by how much the process has traced before
    options.canonicalize_instruction_names = True
    options.print_ids = False
    text = sim.lower_wave(
        params, data, n_samples, jax.random.key(0), job["local_epochs"],
        job["wave_size"]).compiler_ir(dialect="hlo").as_hlo_module(
            ).to_string(options)
    assert 'op_name="' in text
    lines = [LOCATION.sub("", line) for line in text.splitlines()
             if " = " in line]
    return {"instructions": len(lines), "sha256": hashlib.sha256(
        "\n".join(lines).encode()).hexdigest()}


@pytest.mark.parametrize("name", UNCHANGED)
def test_multipliers_of_one_leave_the_other_models_programs_alone(name):
    """``tests/decoder_program_digests.json`` holds what this function
    gave on the commit before the multipliers, the head's own width and
    the pair came (PR 44's tree: ``BATON_WRITE_DIGESTS=<file> python -m
    pytest tests/test_state_space.py -k programs_alone`` writes it): a
    multiplier of 1, a ``head_dim`` of ``d_model / n_heads`` and a mixer
    that learns nothing from its trace add no instruction to the
    programs the benchmark measures, and rename none (the roofline
    metrics read the names). ``falcon_h1_34b``'s entry was taken on PR
    47's tree, before the window, the ``yarn`` rotation and the softmax
    router came (PR 48), and is held with the others from then on. PR
    53's choice of the blocks that keep their products
    (``llama.blocks_kept``) did not move them: it rests on the device's
    plan budget, the CPU these programs are lowered for has none
    (``profiling.hbm_budget_gb``), so every block is checkpointed as it
    was and no estimate is traced; and the name PR 53 gave the engine's
    client ``vmap`` (``core.model.WAVE_AXIS``) is in no instruction. A
    PR that means to change one of these programs writes the file anew
    and says so."""
    got = program_digest(name)
    out = os.environ.get("BATON_WRITE_DIGESTS")
    if out:
        held = json.loads(pathlib.Path(out).read_text()) \
            if os.path.exists(out) else {}
        held[name] = got
        pathlib.Path(out).write_text(json.dumps(held, indent=1) + "\n")
        return
    assert got == json.loads(DIGESTS.read_text())[name]
