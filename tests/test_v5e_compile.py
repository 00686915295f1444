"""Programs compiled for a TPU v5e that is described and not attached
(the TPU's compiler is installed; nothing runs, so these say what the
chip's compiler makes of a program and nothing of its time). The
topology is described inside a fixture, by the one worker that is given
this file: keep every such compile in this file."""

import os
import re
from functools import partial

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


# ---------------------------------------------------------------------------
# latent attention between its projections and its kernel (PR 47)

_HLO_SHAPE = re.compile(r"(pred|[su]\d+|bf16|f16|f32)\[([\d,]*)\]\{([\d,]*)")


def _materialised(text):
    """``(opcode, op_name, [(dtype, dims, minor dim)])`` of every
    instruction outside a fused computation that writes an array: what
    the program reads and writes in memory, not what a fusion holds in
    registers."""
    fused = False
    for line in text.splitlines():
        if not line.startswith(" "):
            fused = line.startswith("%fused_") or line.startswith("fused_")
            continue
        m = re.match(r"\s+(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if fused or not m or m.group(2) in (
                "parameter", "get-tuple-element", "tuple", "bitcast",
                "constant"):
            continue
        arrays = [(dtype, [int(d) for d in dims.split(",") if d],
                   [int(d) for d in order.split(",") if d])
                  for dtype, dims, order in _HLO_SHAPE.findall(m.group(1))]
        name = re.search(r'op_name="([^"]*)"', line)
        yield (m.group(2), name.group(1) if name else "",
               [(dtype, dims, dims[order[0]] if order else None)
                for dtype, dims, order in arrays], line)


_MLA_SHAPES = {
    # sarvam_105b's shape of configuration: a norm over each head's
    # query and key, no query latent, 128 + 64 / 128: q and k go to the
    # kernel with the sequence minor
    "sarvam": (dict(kv_rank=128, nope_dim=128, rope_dim=64, v_dim=128,
                    qk_norm=True), "channel_major"),
    # glm_5's: a query latent, no such norm, 192 + 64 / 256: row-major,
    # the rotary 64 in lanes 192 to 255
    "glm": (dict(kv_rank=128, nope_dim=192, rope_dim=64, v_dim=256,
                 q_rank=256, norm_eps=1e-5), "row_major"),
}


@pytest.mark.parametrize("shape", sorted(_MLA_SHAPES))
def test_latent_attention_makes_each_operand_of_its_core_once(one_chip,
                                                              shape):
    """The value and gradient of ``transformer._mla`` over a frozen
    bfloat16 base under adapters, two clients under ``vmap``, 4 heads
    of a 256-wide model at 2,048 tokens, compiled for the described
    v5e. Between the projections and the kernels the program writes

    * no ``copy`` of q's or k's size (the parent: 4 at GLM's shape,
      of the projections' outputs and of the core's output before
      ``wo``; at sarvam's shape its gradient program carried the
      kernel's sequence-minor layout back to the projections and held
      none, its forward-only program at the cell's full shapes held 2);
    * no array of half a rotary part's size or more whose minor
      dimension is 32 or 64: the rotary parts are made and turned with
      the sequence minor in either layout (the parent: 11 at GLM's
      shape, the rotation's halves ``[..., L, 32]`` four times padded
      to the lanes and ``f32[..., L, 64]`` among them);
    * no float32 array of q's size before the kernel, and after the
      backward kernel (whose own outputs are float32) none but, under
      ``qk_norm``, one each for q and k: the norm's backward needs the
      cotangent times the head's scalar twice, for a sum over the
      channels and for the nope part's gradient, and XLA writes it;
    * the shared rotary key turned once, at ``[1, 1, 32, L]`` a half:
      every multiplication of a rotary half (by the angles' cosines
      and sines) is of that shape or of q's half ``[1, H, 32, L]``,
      four of each;
    * at most 4 arrays of a rotary part's size or more that neither a
      product nor a kernel wrote in the forward and at most 6 in the
      backward (found: 3 and 5 at sarvam's shape, 0 and 4 at GLM's;
      the parent at these shapes: 12 and 10, 8 and 12)."""
    from baton_tpu.models import transformer
    from baton_tpu.models.lora import Adapted

    kw, layout = _MLA_SHAPES[shape]
    cfg = transformer.MLAConfig(block=512, **kw)
    clients, heads, d, length, rank = 2, 4, 256, 2048, 16
    assert transformer.mla_qk_layout(cfg, "tpu", length) == layout
    rope = transformer.mla_rope_angles(length, cfg)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    base = jax.eval_shape(
        lambda key: transformer.mla_init(key, d, heads, cfg),
        jax.random.key(0))
    base = jax.tree_util.tree_map(
        lambda a: shaped(a.shape, jnp.bfloat16 if a.ndim == 2 else a.dtype),
        base)
    lora = {name: (shaped((clients, w.shape[0], rank), jnp.float32),
                   shaped((clients, rank, w.shape[1]), jnp.float32))
            for name, w in base.items() if getattr(w, "ndim", 0) == 2}

    def mixer(lora, x, base):
        def client(lora, x):
            p = dict(base, **{name: Adapted(base[name], a, b, 2.0)
                              for name, (a, b) in lora.items()})
            return transformer._mla(p, x, rope, None, heads, cfg)[0]

        with jax.named_scope("latent_attention"):
            y = jax.vmap(client)(lora, x)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    x = shaped((clients, 1, length, d), jnp.bfloat16)
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:  # ``_mla`` asks the backend where it is traced
        turns = [eqn.outvars[0].aval.shape for eqn in jax.make_jaxpr(
            lambda x: transformer._mla(
                jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), base),
                x, rope, None, heads, cfg)[0])(
                    jnp.zeros((1, length, d), jnp.bfloat16)).jaxpr.eqns
                 if eqn.primitive.name == "mul"
                 and eqn.outvars[0].aval.shape[-2:] == (32, length)]
        text = jax.jit(jax.value_and_grad(mixer, argnums=(0, 1))).lower(
            lora, x, base).compile().as_text()
    finally:
        jax.default_backend = backend
    assert sorted(set(turns)) == [(1, 1, 32, length), (1, heads, 32, length)]
    assert turns.count((1, 1, 32, length)) == 4  # the shared key, once
    assert len(turns) == 8

    q_size = clients * heads * length * cfg.qk_dim
    part_size = clients * heads * length * cfg.rope_dim
    kernels = [name for opcode, name, _, line in _materialised(text)
               if "tpu_custom_call" in line]
    assert kernels and all("mla_core" in name for name in kernels)
    counted = {False: 0, True: 0}  # by direction: is it the backward's
    wide_floats = dict(counted)
    for opcode, name, arrays, line in _materialised(text):
        # not the kernels, and not what the compiler moves between its
        # memory spaces (asynchronous copies and their joins)
        if opcode == "custom-call" or opcode.endswith(("-start", "-done")):
            continue
        product = opcode in ("convolution", "dot") or (
            opcode == "fusion" and re.search(r"kind=k(Output|Conv)", line))
        backward = "transpose(" in name
        for dtype, dims, minor in arrays:
            size = 1
            for n in dims:
                size *= n
            assert not (opcode == "copy" and size == q_size), line
            if size >= part_size // 2:
                assert minor not in (32, 64), line
            wide_floats[backward] += dtype == "f32" and size == q_size
            if size >= part_size and not product:
                counted[backward] += 1
    assert wide_floats[False] == 0
    assert wide_floats[True] <= (2 if cfg.qk_norm else 0)
    assert counted[False] <= 4 and counted[True] <= 6, counted


def test_a_windowed_block_holds_no_square_and_its_kernels_clamp_both_sides(
        one_chip):
    """The gradient of one checkpointed windowed block at Mellum2's
    published attention widths (hidden 2,304, 32 query on 4 key-value
    heads of 128, a window of 1,024) over 8,192 tokens, and of the full
    block beside it: no array of the program is ``[8192, 8192]`` in its
    last two dims (the dense path would mask one), each block is the
    forward kernel and the one backward kernel, and the windowed block's
    grids run over the band at the blocks a windowed call gets (1,024
    x 1,024 under this window): 2 blocks of keys a block of queries
    forward and 2 blocks of queries a block of keys backward, where the
    full block's (512 x 1,024) run over the sequence's 8 and 16. The
    windowed kernels
    name their blocks of keys (forward) and of queries (backward) as
    the band's first, around ``window - 1``, plus the inner step, held
    by a ``min`` to its last: the DMAs stay inside the band. The full
    block's maps clamp the step itself, one side, as they did."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    from baton_tpu.models import llama
    from baton_tpu.models.transformer import rope_angles
    from baton_tpu.ops.flash_attention import make_flash_attention_fn

    length = 8192
    cfg = llama.LlamaConfig(
        vocab_size=256, d_model=2304, n_layers=2, n_heads=32, n_kv_heads=4,
        head_dim=128, d_ff=256, window=1024,
        layer_types=("sliding_attention", "full_attention"))
    attend = make_flash_attention_fn(interpret=False)
    rope = rope_angles(length, cfg.head_dim, cfg.rope_theta)
    block = llama._checkpointed_block()

    def kernels_of(kind):
        def loss(p, x):
            y, _ = block(p, x, None, cfg, rope, attend)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        p = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                           sharding=one_chip),
            jax.eval_shape(lambda key: llama._block_init(key, cfg, kind),
                           jax.random.key(0)))
        x = jax.ShapeDtypeStruct((1, length, cfg.d_model), jnp.bfloat16,
                                 sharding=one_chip)
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
            p, x).compile().as_text()
        assert not re.search(rf"\[(\d+,)*{length},{length}\]", text)
        bodies = re.findall(r'"body":"([A-Za-z0-9+/=]+)"', text)
        assert len(bodies) == 2  # one forward, one backward
        ctx = mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True  # ``stable_mosaic``
        out = {}
        with ctx:
            for body in bodies:
                asm = ir.Module.parse(base64.b64decode(
                    body)).operation.get_asm(enable_debug_info=False)
                name = re.search(r"module @(\w+)", asm).group(1)
                grid = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>",
                                 asm).group(1)
                # the index maps, one a ``func`` after the kernel's own
                out[name] = (tuple(map(int, grid.split(","))), [
                    f for f in asm.split("func.func\"()")[1:]
                    if 'sym_name = "transform_' in f])
        return out

    def held(maps):
        """An index map's ``min``, its ``max``, and whether it adds the
        inner grid index to something it computed: a band's offset."""
        return [("arith.minsi" in f, "arith.maxsi" in f,
                 bool(re.search(r'arith\.addi"\(%\d+, %arg3\)', f)))
                for f in maps]

    windowed, full = kernels_of("sliding_attention"), kernels_of(
        "full_attention")
    assert set(windowed) == set(full) == {"_fwd_kernel", "_bwd_dkv_kernel"}
    grids = {name: (windowed[name][0], full[name][0]) for name in windowed}
    assert grids == {"_fwd_kernel": ((1, 32, 8, 2), (1, 32, 16, 8)),
                     "_bwd_dkv_kernel": ((1, 32, 8, 2), (1, 32, 8, 16))}
    windowed, full = ({name: maps for name, (_, maps) in kernels.items()}
                      for kernels in (windowed, full))
    # forward: q, then k, v and the keys' bias by the block of keys (the
    # band's first is floored at block 0: the ``max``)
    assert held(windowed["_fwd_kernel"])[0] == (False, False, False)
    assert held(windowed["_fwd_kernel"])[1:4] == [(True, True, True)] * 3
    assert held(full["_fwd_kernel"])[1:4] == [(True, False, False)] * 3
    # backward (keys outer, queries inner): q, do, lse and delta by the
    # block of queries (a band's first is the causal bound's, no floor)
    assert held(windowed["_bwd_dkv_kernel"])[:4] == [(True, False, True)] * 4
    assert held(full["_bwd_dkv_kernel"])[:4] == [(False, True, False)] * 4
    for maps in windowed.values():
        assert any("value = 1023 : i32" in f for f in maps)
    for maps in full.values():
        assert not any("value = 1023 : i32" in f for f in maps)


# the grouped products of the five cells with experts: ``K x N``, the
# experts held, and the sorted rows one product handles there
# (``moe.rows_bound`` of the folded clients' assignments, or one
# client's where the fold would pass ``moe._FOLDED_ROWS_BYTES``)
_GROUPED_PRODUCTS = {
    "mellum2_c4_l8192-2304x896": (2304, 896, 64, 65536),
    "mellum2_c4_l8192-896x2304": (896, 2304, 64, 65536),
    "zaya1_c4_l8192-2048x2048": (2048, 2048, 16, 32768),
    "sarvam_105b_c4_l2048-4096x2048": (4096, 2048, 16, 16384),
    "sarvam_105b_c4_l2048-2048x4096": (2048, 4096, 16, 16384),
    "glm5_c4_l8192-6144x2048": (6144, 2048, 8, 16384),
    "glm5_c4_l8192-2048x6144": (2048, 6144, 8, 16384),
    "command_a_plus_c4_l8192-4096x4096": (4096, 4096, 8, 32768),
}


@pytest.mark.parametrize("k,n,held,rows", _GROUPED_PRODUCTS.values(),
                         ids=_GROUPED_PRODUCTS.keys())
def test_a_grouped_product_fits_the_chips_vmem_at_the_tiles_it_picks(
        one_chip, k, n, held, rows):
    """``grouped_matmul`` in bfloat16 at a cell's widths, rows and
    experts, against the stack and against the transposed stack: each
    is one Pallas kernel that the chip's compiler takes, so a tile past
    the scoped VMEM fails here and not on the chip; and the contraction
    is one tile, which is what keeps an expert's weights where they
    are across its row tiles."""
    from baton_tpu.models import moe

    assert moe.gmm_tiles(k, n, 2)[1] == k

    def shaped(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:  # ``grouped_matmul`` asks the backend where it is traced
        for transposed in (False, True):
            text = jax.jit(partial(
                moe.grouped_matmul, transpose_rhs=transposed)).lower(
                    shaped((rows, k)),
                    shaped((held, n, k) if transposed else (held, k, n)),
                    shaped((held,), jnp.int32)).compile().as_text()
            assert text.count('custom_call_target="tpu_custom_call"') == 1
    finally:
        jax.default_backend = backend


def test_kept_blocks_hold_no_more_than_the_plan_says(one_chip, monkeypatch):
    """Two blocks of ``olmo_hybrid_7b`` at the published widths (one of
    the gated delta rule, one of full attention) over the frozen
    bfloat16 base with rank-16 adapters, four clients of 1,024 tokens
    under the engine's ``vmap``, ``olmo_hybrid_c4_l1024``'s step: value
    and the adapters' gradients compiled with both blocks checkpointed
    and with both keeping their products. The estimate a model makes
    from shapes alone (``llama.plan_bytes``, what ``llama.blocks_kept``
    chooses by) is an upper bound where the compiler can be asked: of
    the whole plan both times, and the kept blocks add no more to the
    temporaries than the estimate holds them to keep."""
    import dataclasses
    import json
    import pathlib

    from baton_tpu.core.model import WAVE_AXIS
    from baton_tpu.models import llama
    from baton_tpu.utils import profiling

    root = pathlib.Path(__file__).resolve().parent.parent
    monkeypatch.syspath_prepend(str(root))
    from fedbench import manifest

    config = json.loads(
        (root / "fedbench" / "configs" / "olmo_hybrid_7b.json").read_text())
    cfg = manifest.resolve(config["builder"]["kwargs"]["config"], config)
    cfg = dataclasses.replace(cfg, n_layers=2,
                              layer_types=cfg.layer_types[2:4])
    assert cfg.layer_types == ("linear_attention", "full_attention")
    clients, length = 4, 1024
    # the model asks the backend and the device where it is traced
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setitem(profiling.HBM_BUDGET_GB, "cpu", 13.5)

    def step(model):
        def loss(lora, base, x, y):
            return jnp.sum(model.per_example_loss(
                {"base": base, "lora": lora}, {"x": x, "y": y}, None))

        return lambda lora, base, x, y: jax.vmap(
            jax.value_and_grad(loss), in_axes=(None, None, 0, 0),
            axis_name=WAVE_AXIS)(lora, base, x, y)

    plans = {}
    for k in (0, cfg.n_layers):
        monkeypatch.setattr(llama, "blocks_kept", lambda *plan, k=k: k)
        model = llama.decoder_lora_model(cfg)
        params = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip),
            jax.eval_shape(model.init, jax.random.key(0)))
        ids = jax.ShapeDtypeStruct((clients, 1, length), jnp.int32,
                                   sharding=one_chip)
        memory = jax.jit(step(model)).lower(
            params["lora"], params["base"], ids, ids).compile(
                ).memory_analysis()
        said = dict(model.span_attrs)
        assert said["blocks_kept"] == k
        plans[k] = (memory.temp_size_in_bytes, memory.argument_size_in_bytes
                    + memory.output_size_in_bytes, said)
    (temp, held, said), (temp_kept, _, said_kept) = plans[0], plans[2]
    assert temp + held <= said["plan_estimate_bytes"]
    assert temp_kept + held <= said_kept["plan_estimate_bytes"]
    assert 0 < temp_kept - temp <= 2 * said_kept["kept_block_bytes"]
