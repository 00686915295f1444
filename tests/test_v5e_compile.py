"""Programs compiled for a TPU v5e that is described and not attached
(the TPU's compiler is installed; nothing runs, so these say what the
chip's compiler makes of a program and nothing of its time). The
topology is described inside a fixture, by the one worker that is given
this file: keep every such compile in this file."""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from baton_tpu.models.transformer import next_token_loss


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without the chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def test_the_tied_losss_backward_is_the_scan_alone(one_chip):
    """Two blocks of ``zaya1_c4_l8192``'s loss, four clients under
    ``vmap``: 482 tokens are no multiple of 8 and 65,568 ids none of
    128, so a scatter into a block's cotangent (the transpose of a
    gathered label logit) runs on a flat ``f32[126415104]`` copy that
    two more ``while`` loops fill and read back."""
    clients, length, d, vocab = 4, 964, 2048, 65568

    def loss(x, table, y):
        return jnp.sum(jax.vmap(
            lambda x, y: next_token_loss(x, table, y, tied=True))(x, y))

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.grad(loss)).lower(
        shaped((clients, 1, length, d), jnp.bfloat16),
        shaped((vocab, d), jnp.bfloat16),
        shaped((clients, 1, length), jnp.int32)).compile().as_text()
    block = clients * (length // 2) * vocab
    assert f"[{clients},{length // 2},{vocab}]" in text  # two blocks
    assert len(re.findall(r" while\(", text)) == 1
    assert " scatter(" not in text
    assert f"[{block}]" not in text
