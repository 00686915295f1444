"""Local trainer: convergence, masking exactness, loss accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from baton_tpu.core.regularizers import fedprox
from baton_tpu.core.training import make_local_trainer
from baton_tpu.models.linear import linear_regression_model
from baton_tpu.ops.padding import pad_dataset, round_up
from baton_tpu.ops.privacy import DPConfig, dp_sgd_grads


def _linear_data(nprng, n=256, d=10):
    coef = nprng.standard_normal(d).astype(np.float32)
    x = nprng.standard_normal((n, d)).astype(np.float32)
    return {"x": x, "y": (x @ coef).astype(np.float32)}, coef


def test_local_training_reduces_loss(nprng):
    model = linear_regression_model(10)
    trainer = make_local_trainer(model, batch_size=32, learning_rate=0.01)
    data, _ = _linear_data(nprng)
    params = model.init(jax.random.key(0))
    p2, _, losses = trainer.train(
        {k: jnp.asarray(v) for k, v in params.items()},
        {k: jnp.asarray(v) for k, v in data.items()},
        jnp.int32(256),
        jax.random.key(1),
        8,
    )
    losses = np.asarray(losses)
    assert losses.shape == (8,)
    assert losses[-1] < losses[0] * 0.5


def test_padding_is_exactly_invisible(nprng):
    """Training on n real rows padded to capacity must equal training on
    the unpadded data with the same permutation statistics. We verify the
    gradient math directly: one epoch, full batch, so the update is
    deterministic given the mask."""
    model = linear_regression_model(4)
    n, cap = 8, 16
    data, _ = _linear_data(nprng, n=n, d=4)
    padded, n_samples = pad_dataset(data, cap)
    assert n_samples == n
    # poison the padding: if masking leaks, grads change
    poisoned = {k: v.copy() for k, v in padded.items()}
    poisoned["x"][n:] = 1e6
    poisoned["y"][n:] = -1e6

    trainer = make_local_trainer(model, batch_size=cap, learning_rate=0.01)
    params = model.init(jax.random.key(0))
    out_clean, _, loss_clean = trainer.train(
        params,
        {k: jnp.asarray(v) for k, v in padded.items()},
        jnp.int32(n),
        jax.random.key(1),
        1,
    )
    out_pois, _, loss_pois = trainer.train(
        params,
        {k: jnp.asarray(v) for k, v in poisoned.items()},
        jnp.int32(n),
        jax.random.key(1),
        1,
    )
    np.testing.assert_allclose(
        np.asarray(out_clean["w"]), np.asarray(out_pois["w"]), rtol=1e-6
    )
    np.testing.assert_allclose(float(loss_clean[0]), float(loss_pois[0]), rtol=1e-6)


def test_epoch_loss_is_exact_weighted_mean(nprng):
    """The per-epoch loss must be Σ loss_i / n over real samples — fixing
    the reference's biased running mean (utils.py:85-88: inputs [4,2,6]
    yield 4.75 there; the true mean is 4.0)."""
    model = linear_regression_model(2)
    # no training effect: lr=0 isolates the accounting
    trainer = make_local_trainer(
        model, optimizer=optax.sgd(0.0), batch_size=4
    )
    data, _ = _linear_data(nprng, n=12, d=2)
    params = {k: jnp.asarray(v) for k, v in model.init(jax.random.key(0)).items()}
    _, _, losses = trainer.train(
        params,
        {k: jnp.asarray(v) for k, v in data.items()},
        jnp.int32(12),
        jax.random.key(1),
        1,
    )
    per_ex = np.asarray(model.per_example_loss(params, data, jax.random.key(2)))
    np.testing.assert_allclose(float(losses[0]), per_ex.mean(), rtol=1e-5)


def test_zero_sample_client_is_noop():
    model = linear_regression_model(3)
    trainer = make_local_trainer(model, batch_size=4, learning_rate=0.1)
    params = model.init(jax.random.key(0))
    data = {
        "x": jnp.ones((8, 3), jnp.float32) * 100.0,
        "y": jnp.ones((8,), jnp.float32) * -100.0,
    }
    p2, _, losses = trainer.train(params, data, jnp.int32(0), jax.random.key(1), 2)
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(params["w"]))
    assert np.all(np.asarray(losses) == 0.0)


def _written_out_epochs(model, optimizer, batch_size, params, data, n_samples,
                        rng, n_epochs, regularizer=None, dp=None):
    """What ``LocalTrainer.train`` promises, as a Python loop: an epoch
    permutes the ``capacity`` rows and walks them in the fewest slices
    of at most ``batch_size`` rows, all of one size (a few masked rows
    of zeros more where that does not come out even), taking one
    optimizer step a slice on the masked mean loss, unless the slice
    holds no real row. Returns ``(params, opt_state, losses)``."""
    capacity = data["x"].shape[0]
    n_steps = -(-capacity // batch_size)
    step_rows = -(-capacity // n_steps)
    n_short = n_steps * step_rows - capacity
    anchor = params

    def data_loss_sum(p, batch, step_rng):
        return model.loss_and_count(p, batch, step_rng)[0]

    def objective(p, batch, step_rng):
        loss_sum, count = model.loss_and_count(p, batch, step_rng)
        loss = loss_sum / jnp.maximum(count, 1.0)
        if regularizer is not None:
            loss = loss + regularizer(p, anchor)
        return loss, (loss_sum, count)

    @jax.jit
    def step(p, opt_state, batch, step_rng):
        if dp is not None:
            grads, example_losses = dp_sgd_grads(
                data_loss_sum, p, batch, step_rng, dp, batch_size)
            loss_sum, count = jnp.sum(example_losses), jnp.sum(batch["mask"])
        else:
            (_, (loss_sum, count)), grads = jax.value_and_grad(
                objective, has_aux=True)(p, batch, step_rng)
        updates, new_state = optimizer.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), new_state, loss_sum, count

    p, opt_state, losses = params, optimizer.init(params), []
    for epoch_rng in jax.random.split(rng, n_epochs):
        perm_rng, step_rng = jax.random.split(epoch_rng)
        perm = jax.random.permutation(perm_rng, capacity)
        shuffled = {k: v[perm] for k, v in data.items()}
        shuffled["mask"] = (perm < n_samples).astype(jnp.float32)
        shuffled = {k: jnp.concatenate(
            [v, jnp.zeros((n_short,) + v.shape[1:], v.dtype)])
            for k, v in shuffled.items()}
        loss_sums, counts = [], []
        for lo in range(0, n_steps * step_rows, step_rows):
            batch = {k: v[lo:lo + step_rows] for k, v in shuffled.items()}
            step_rng, sub = jax.random.split(step_rng)
            new_p, new_state, loss_sum, count = step(p, opt_state, batch, sub)
            if count > 0:  # a slice of padding alone steps nothing
                p, opt_state = new_p, new_state
            loss_sums.append(loss_sum)
            counts.append(count)
        losses.append(jnp.sum(jnp.stack(loss_sums))
                      / jnp.maximum(jnp.sum(jnp.stack(counts)), 1.0))
    return p, opt_state, jnp.stack(losses)


def _last_step_rows(rng, capacity, batch_size):
    """The rows (as indices into the client's data) of the first
    epoch's last step."""
    n_steps = -(-capacity // batch_size)
    perm_rng, _ = jax.random.split(jax.random.split(rng, 1)[0])
    perm = np.asarray(jax.random.permutation(perm_rng, capacity))
    return perm[(n_steps - 1) * -(-capacity // n_steps):]


# (trainer arguments, n_samples or how the last step is to be filled,
# epochs, capacity, batch size). Capacity 8 at batch 5 is two steps of 4
# rows; 10 at batch 4 is three steps of 4 over two rows of zeros more.
STEP_CASES = {
    "sgd_all_real": ({}, 8, 1, 8, 5),
    "momentum": ({"optimizer": optax.sgd(0.05, momentum=0.9)}, 8, 1, 8, 5),
    "sgd_last_step_partly_padding": ({}, "partly", 1, 8, 5),
    "momentum_last_step_all_padding": (
        {"optimizer": optax.sgd(0.05, momentum=0.9)}, "wholly", 1, 8, 5),
    "no_samples": ({"optimizer": optax.sgd(0.05, momentum=0.9)}, 0, 2, 8, 5),
    "dp": ({"dp": DPConfig(clip_norm=1.0, noise_multiplier=0.5)}, 7, 1, 8, 5),
    "regularizer": ({"regularizer": fedprox(0.5)}, 8, 2, 8, 5),
    "two_epochs": ({"optimizer": optax.sgd(0.05, momentum=0.9)}, 6, 2, 8, 5),
    "rows_short_of_equal_steps": (
        {"optimizer": optax.sgd(0.05, momentum=0.9)}, 9, 2, 10, 4),
    "fewer_rows_than_a_batch": ({}, 3, 2, 3, 5),  # one step of 3 rows
}


@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_a_capacity_the_batch_does_not_divide_is_shared_equally(nprng, case):
    """A capacity the batch size does not divide (it was refused before
    ISSUE 30): as many steps as ``ceil(capacity / batch_size)``, the rows
    shared equally among them, bit for bit the loop written out above."""
    kw, n_samples, n_epochs, capacity, batch_size = STEP_CASES[case]
    rng = jax.random.key(3)
    if isinstance(n_samples, str):
        # the first key whose permutation leaves row 0 out of the last step
        rng = next(k for k in map(jax.random.key, range(3, 40))
                   if _last_step_rows(k, capacity, batch_size).min() > 0)
        last = _last_step_rows(rng, capacity, batch_size)
        # real rows come first, so the step's smallest index decides
        n_samples = int({"partly": np.sort(last)[1],
                         "wholly": last.min()}[n_samples])
        real_in_last = int((last < n_samples).sum())
        assert n_samples > 0
        assert real_in_last == (0 if case.endswith("all_padding") else 1)
    model = linear_regression_model(3)
    data, _ = _linear_data(nprng, n=capacity, d=3)
    data = {k: jnp.asarray(v) for k, v in data.items()}
    params = model.init(jax.random.key(0))
    optimizer = kw.get("optimizer", optax.sgd(0.05))
    trainer = make_local_trainer(model, batch_size=batch_size,
                                 **{"optimizer": optimizer, **kw})
    assert trainer.steps_per_round(capacity, n_epochs) == n_epochs * (
        -(-capacity // batch_size))
    anchor = params if "regularizer" in kw else None
    got = trainer.train(params, data, jnp.int32(n_samples), rng, n_epochs,
                        anchor)
    want = _written_out_epochs(
        model, optimizer, batch_size, params, data, n_samples, rng, n_epochs,
        regularizer=kw.get("regularizer"), dp=kw.get("dp"))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for x, y in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    if n_samples == 0:
        for x, y in zip(jax.tree_util.tree_leaves(got[0]),
                        jax.tree_util.tree_leaves(params)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert np.all(np.asarray(got[2]) == 0.0)
    else:
        assert not np.array_equal(np.asarray(got[0]["w"]),
                                  np.asarray(params["w"]))


def _epoch_equations(trainer, params, capacity):
    """Primitive names of the epoch's body (the outer scan's), in order."""
    data = {"x": jnp.ones((capacity, 3)), "y": jnp.ones((capacity,))}
    jaxpr = jax.make_jaxpr(
        lambda p, s, d, n, r: type(trainer).train_with_opt_state
        .__wrapped__(trainer, p, s, d, n, r, 1))(
        params, trainer.init_opt_state(params), data,
        jnp.int32(capacity), jax.random.key(1))
    (epochs,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    return epochs.params["jaxpr"].jaxpr.eqns


@pytest.mark.parametrize("capacity,steps,rows", [
    (8, 2, 4), (12, 3, 4),   # the batch divides: the steps it always took
    (6, 2, 3), (10, 3, 4), (3, 1, 3)])
def test_an_epoch_is_one_scan_over_steps_of_one_shape(capacity, steps, rows):
    """Whatever the capacity, the epoch is one scan and no step beside
    it (one traced step, so one compiled body: the set-up of a ragged
    capacity is that of a padded one); where the batch divides the
    capacity the trace is the one it was before ISSUE 30 (the guard on
    BERT's path, the HTTP worker's and the engines' beside ``FedSim``)."""
    model = linear_regression_model(3)
    trainer = make_local_trainer(model, batch_size=4)
    eqns = _epoch_equations(trainer, model.init(jax.random.key(0)), capacity)
    names = [e.primitive.name for e in eqns]
    assert names.count("scan") == 1 and "dot_general" not in names
    (scan,) = [e for e in eqns if e.primitive.name == "scan"]
    assert scan.params["length"] == steps
    x = [v.aval.shape for v in scan.invars if v.aval.shape[1:] == (rows, 3)]
    assert x == [(steps, rows, 3)]
    # after the scan, the epoch's loss and nothing else
    assert names[names.index("scan") + 1:] == [
        "reduce_sum", "max", "reduce_sum", "div"]
    if capacity % 4 == 0:  # not one equation more than it had
        assert names[:names.index("scan")] == [
            "random_split", "slice", "squeeze", "slice", "squeeze", "iota",
            "jit", "lt", "convert_element_type", "jit", "jit", "reshape",
            "reshape", "reshape"]


def test_round_up():
    assert round_up(7, 4) == 8
    assert round_up(8, 4) == 8
