"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip logic (shard_map over Mesh(('clients',))) is tested on the
CPU by splitting the host into 8 XLA devices (SURVEY §4d); the same
logic on real chips is chip_smoke.py's mesh phase. XLA_FLAGS must be set
before the backend initializes, and the tests always run on the CPU,
whatever JAX_PLATFORMS says.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def nprng():
    return np.random.default_rng(0)


def flash_kernels(fn, *args):
    """``(forward, backward)``: the flash kernels in ``fn``'s jaxpr at
    ``args``, whatever they are nested in. A forward kernel has two
    outputs, the output and the log-sum-exp; a backward kernel three or
    four gradients, or ``dq`` alone."""
    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield len(eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    outputs = list(calls(jax.make_jaxpr(fn)(*args).jaxpr))
    return outputs.count(2), len(outputs) - outputs.count(2)


def counter(metrics, name, default=0.0):
    """Read one counter from a Metrics registry (0.0 when never inc'd)."""
    return metrics.snapshot()["counters"].get(name, default)


@pytest.fixture
def assert_counter():
    """Shared metrics assertion: ``assert_counter(metrics, name, at_least=1)``
    or ``assert_counter(metrics, name, equals=2)`` with a readable diff
    listing every counter on failure (the ingest/backpressure tests all
    assert on counters; one helper keeps the failure output uniform)."""

    def check(metrics, name, at_least=None, equals=None):
        counters = metrics.snapshot()["counters"]
        got = counters.get(name, 0.0)
        if equals is not None:
            assert got == equals, (
                f"counter {name}={got}, wanted == {equals}; all={counters}"
            )
        else:
            want = 1.0 if at_least is None else at_least
            assert got >= want, (
                f"counter {name}={got}, wanted >= {want}; all={counters}"
            )
        return got

    return check
