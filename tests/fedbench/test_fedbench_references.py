"""The plain references (``fedbench/references/<config>.py``) against
the program at each configuration's ``tiny`` sizes, float32 on both
sides, on the CPU: the loss and every gradient leaf; for ResNet also
the program's hand-written GroupNorm alone. And that a reference is
plain: no import of the program, no custom derivative rule."""

import ast
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from fedbench import data as cohort, manifest  # noqa: E402

BENCH = manifest.load_manifest(ROOT)
CONFIGS = [c["name"] for c in BENCH["configs"]]
# float32 on both sides, the CPU's products exact: what is left is the
# order of the sums through some twenty layers forward and back, a few
# float32 roundings (6e-8 each). 1e-4 of a leaf's largest entry is a
# hundred times that and a hundredth of what bfloat16 (4e-3) would give.
RTOL = 1e-4


def _float32_program(config):
    """The program's model at ``tiny`` sizes, computing in float32."""
    config = copy.deepcopy(config)
    config["builder"]["kwargs"]["compute_dtype"] = {"$dtype": "float32"}
    return manifest.build_model(config, tiny=True)


def _batch(config, seed, n=6, seq_len=8):
    """One client's batch of ``n`` rows, the last two masked out."""
    sizes = np.asarray([n], np.int32)
    made = cohort.make_cohort(ROOT, manifest.input_spec(config, True), sizes,
                              n, seq_len, cohort.data_key(seed))
    mask = jnp.asarray(np.arange(n) < n - 2, jnp.float32)
    return made["x"][0], made["y"][0], mask


def _worst_leaf(got, want):
    """Largest |got - want| over a leaf's largest |want|, over leaves."""
    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == jnp.float32
        worst = max(worst, float(jnp.max(jnp.abs(g - w)))
                    / max(float(jnp.max(jnp.abs(w))), 1e-12))
    return worst


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("name", CONFIGS)
def test_reference_loss_and_gradients_agree_with_the_program(name, seed):
    config = manifest.load_config(ROOT, BENCH, name)
    model = _float32_program(config)
    params = model.init(jax.random.key(seed))
    x, y, mask = _batch(config, seed + 10)
    loss = manifest.load_module(ROOT, "references", name).make_loss(
        manifest.sized(config, True))
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: model.masked_loss(p, {"x": x, "y": y, "mask": mask},
                                        None))(params)
        got_loss, got = jax.value_and_grad(loss)(params, x, y, mask)
    assert float(got_loss) == pytest.approx(float(want_loss), rel=RTOL)
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    assert _worst_leaf(got, want) <= RTOL
    # a masked-out row changes nothing
    x2 = x.at[-1].set(x[0])
    assert float(loss(params, x2, y, mask)) == pytest.approx(float(got_loss),
                                                             rel=1e-6)


@pytest.mark.parametrize("shape,groups", [((3, 8, 8, 64), 32),
                                          ((2, 4, 4, 128), 8),
                                          ((2, 5, 5, 16), 32)])
def test_textbook_group_norm_agrees_with_the_program_s_custom_vjp(shape,
                                                                  groups):
    """The program's ``_group_norm`` takes per-channel moments and has a
    hand-written backward (PR 25); until now only ``tests/test_resnet.py``'s
    5-D oracle stood outside of it."""
    from baton_tpu.models.resnet import _group_norm as program

    textbook = manifest.load_module(
        ROOT, "references", "resnet18_cifar10")._group_norm
    kx, ks, kb, kw = jax.random.split(jax.random.key(shape[-1]), 4)
    x = 3.0 * jax.random.normal(kx, shape, jnp.float32) + 1.5
    p = {"scale": 1.0 + 0.1 * jax.random.normal(ks, shape[-1:], jnp.float32),
         "bias": 0.1 * jax.random.normal(kb, shape[-1:], jnp.float32)}
    weight = jax.random.normal(kw, shape, jnp.float32)

    def through(norm):
        return jax.value_and_grad(
            lambda x, p: jnp.sum(norm(x, p, groups) * weight),
            argnums=(0, 1))(x, p)

    (want_y, want), (got_y, got) = through(program), through(textbook)
    assert float(got_y) == pytest.approx(float(want_y), rel=RTOL)
    assert _worst_leaf(got, want) <= RTOL


@pytest.mark.parametrize("name", CONFIGS)
def test_a_reference_is_plain(name):
    """No import of ``baton_tpu``, and no custom derivative rule anywhere
    in the loss (``jax.nn.softmax`` and ``relu`` carry one: the
    references write theirs out)."""
    path = os.path.join(ROOT, "fedbench", "references", f"{name}.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    imported = [n.module if isinstance(n, ast.ImportFrom) else a.name
                for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names]
    assert imported and not [m for m in imported if m.startswith("baton_tpu")]
    config = manifest.load_config(ROOT, BENCH, name)
    model = _float32_program(config)
    params = jax.eval_shape(model.init, jax.random.key(0))
    x, y, mask = _batch(config, 3)
    loss = manifest.load_module(ROOT, "references", name).make_loss(
        manifest.sized(config, True))
    text = str(jax.make_jaxpr(jax.grad(loss))(params, x, y, mask))
    assert "custom_vjp" not in text and "custom_jvp" not in text
