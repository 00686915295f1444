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
    ``vmap``, value and gradient as a training step takes them: 482
    tokens are no multiple of 8 and 65,568 ids none of 128, so a
    scatter into a block's cotangent (the transpose of a gathered label
    logit) runs on a flat ``f32[126415104]`` copy that two more
    ``while`` loops fill and read back. The table takes no gradient, so
    the one loop is the forward's and holds two products over a block's
    ``[4, 482, 65568]``, the logits and ``(softmax - onehot)`` back
    through the table: no second loop makes the logits again."""
    clients, length, d, vocab = 4, 964, 2048, 65568

    def loss(x, table, y):
        return jnp.sum(jax.vmap(
            lambda x, y: next_token_loss(x, table, y, tied=True))(x, y))

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(jax.value_and_grad(loss)).lower(
        shaped((clients, 1, length, d), jnp.bfloat16),
        shaped((vocab, d), jnp.bfloat16),
        shaped((clients, 1, length), jnp.int32)).compile().as_text()
    block = clients * (length // 2) * vocab
    assert f"[{clients},{length // 2},{vocab}]" in text  # two blocks
    assert len(re.findall(r" while\(", text)) == 1
    assert " scatter(" not in text
    assert f"[{block}]" not in text
    products = re.findall(r" = (\S+?)\{[^ ]* convolution\(", text)
    assert sorted(products) == [f"f32[{clients},{length // 2},{d}]",
                                f"f32[{clients},{length // 2},{vocab}]"]


@pytest.mark.parametrize("kept,forwards", [(True, 1), (False, 2)],
                         ids=["the_blocks_policy", "a_bare_checkpoint"])
def test_a_block_that_keeps_the_cores_outputs_runs_the_forward_kernel_once(
        one_chip, kept, forwards):
    """The gradient of one checkpointed decoder block whose attention
    is the flash kernel at ``[4, 8, 2048, 128]``, four clients under
    ``vmap``. Under the checkpoint a ``remat`` model gives its blocks
    the program holds the forward kernel and the one backward kernel;
    under a bare ``jax.checkpoint`` a second forward kernel, the
    ``rematted_computation``'s, whose two outputs are the first's."""
    from baton_tpu.models import llama
    from baton_tpu.models.transformer import rope_angles
    from baton_tpu.ops.flash_attention import make_flash_attention_fn

    clients, length = 4, 2048
    cfg = llama.LlamaConfig(vocab_size=256, d_model=1024, n_layers=1,
                            n_heads=8, n_kv_heads=8, d_ff=512)
    attend = make_flash_attention_fn(interpret=False)
    rope = rope_angles(length, cfg.head_dim, cfg.rope_theta)
    block = (llama._checkpointed_block() if kept else jax.checkpoint(
        llama._block_apply, static_argnums=(3, 5)))

    def loss(p, x):
        def client(x):
            y, _ = block(p, x, None, cfg, rope, attend)
            # the next block needs the stream: the forward cannot go
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jnp.sum(jax.vmap(client)(x))

    p = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=one_chip),
        jax.eval_shape(lambda key: llama._block_init(key, cfg),
                       jax.random.key(0)))
    x = jax.ShapeDtypeStruct((clients, 1, length, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        p, x).compile().as_text()
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    backward = [k for k in kernels if "transpose(" in k
                and "rematted_computation" not in k]
    assert len(backward) == 1
    assert len(kernels) - len(backward) == forwards
    assert sum("rematted_computation" in k for k in kernels) == forwards - 1


def test_the_pairs_block_keeps_its_attention_branchs_kernel_outputs(one_chip):
    """The gradient of one checkpointed block of a state-space branch
    beside attention, four clients under ``vmap`` at 4,096 tokens: 5
    query heads on 1 key-value head of 128 (Falcon-H1's group of 5) go
    through the flash kernels, the block's checkpoint sees the kept
    output and log-sum-exp inside the pair (one forward kernel and the
    one backward kernel, no second forward), and the chunked recurrence
    compiles at a state of 256 x 128 in chunks of 128 with its scan the
    only loops."""
    from baton_tpu.models import llama, state_space
    from baton_tpu.models.transformer import default_attention, rope_angles

    clients, length = 4, 4096
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=512, n_layers=1, n_heads=5, n_kv_heads=1,
        head_dim=128, d_ff=512, rope_theta=100000000000,
        layer_types=("parallel_ssm_attention",),
        ssm=state_space.SSMConfig(n_heads=4, head_dim=128, d_state=256,
                                  n_groups=2, chunk=128))
    rope = rope_angles(length, cfg.head_dim, cfg.rope_theta)
    block = llama._checkpointed_block()

    def loss(p, x):
        def client(x):
            y, _ = block(p, x, None, cfg, rope, default_attention)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jnp.sum(jax.vmap(client)(x))

    p = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, jnp.bfloat16 if a.ndim == 2 else a.dtype,
            sharding=one_chip),
        jax.eval_shape(lambda key: llama._block_init(key, cfg),
                       jax.random.key(0)))
    x = jax.ShapeDtypeStruct((clients, 1, length, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:  # ``default_attention`` asks the backend where it is traced
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            p, x).compile().as_text()
    finally:
        jax.default_backend = backend
    kernels = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)
    assert len(kernels) == 2 and all("/attention/" in k for k in kernels)
    assert sum("transpose(" in k for k in kernels) == 1
    assert not any("rematted_computation" in k for k in kernels)
    loops = re.findall(r' while\(.*?op_name="([^"]*)"', text)
    assert loops and all("/ssd_scan/" in name for name in loops)
