"""Long-context causal LM training with ring x flash sequence parallelism.

The long-context configuration this framework is built around
(SURVEY §5 "long-context / SP"): a Llama-class decoder whose attention
runs as ring attention over a Mesh(('seq',)) — K/V blocks rotate
between devices over ICI while each device keeps its sequence shard —
with each shard's block math executed by the Pallas flash kernel
(parallel/ring_attention.py::flash_ring_attention). Per-device attention
memory is O(L/N · block) instead of O(L²): sequence length scales with
the mesh, not with one chip's HBM.

Tiny scale trains a 2-layer model on an 8-way virtual CPU mesh (the
same code path the tests verify against the dense oracle); full scale
is sized for a real TPU slice. ``remat=True`` additionally wraps each
decoder block in jax.checkpoint, trading recompute for activation
memory — the standard long-context pairing.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from baton_tpu.core.training import make_local_trainer
from baton_tpu.models.llama import LlamaConfig, llama_lm_model
from baton_tpu.parallel.mesh import make_mesh
from baton_tpu.parallel.ring_attention import (
    make_flash_ring_attention_fn,
    make_ring_attention_fn,
    make_striped_attention_fn,
)


def run(n_devices=8, seq_len=64, n_steps=3, batch_size=2, lr=1e-2,
        config=None, remat=False, flash=True, striped=False, seed=0):
    """``striped=True`` uses the load-balanced causal layout
    (round-robin token sharding) instead of the contiguous ring — same
    exact math, but every shard does equal work per ring step instead of
    the tail shard gating it (parallel/ring_attention.py). NOTE: the
    striped path runs the DENSE ring kernel (there is no striped flash
    variant yet), so per-shard attention memory is O((L/N)^2) — size the
    sequence accordingly; ``flash`` is ignored when ``striped`` is
    set."""
    mesh = make_mesh(n_devices=n_devices, axis_names=("seq",))
    cfg = config or LlamaConfig.tiny(
        max_len=seq_len, n_heads=4, n_kv_heads=2, n_layers=2
    )
    if striped:
        if flash:
            print("note: striped layout uses the dense ring kernel "
                  "(no striped flash variant); flash ignored")
        attn = make_striped_attention_fn(mesh)
    elif flash:
        attn = make_flash_ring_attention_fn(mesh)
    else:
        attn = make_ring_attention_fn(mesh)
    model = llama_lm_model(cfg, attention_fn=attn, remat=remat)
    trainer = make_local_trainer(model, batch_size=batch_size,
                                 learning_rate=lr)

    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size,
                        size=(batch_size, cfg.max_len)).astype(np.int32)
    data = {"x": jnp.asarray(toks), "y": jnp.asarray(toks)}
    params = model.init(jax.random.key(seed))

    # one jitted multi-epoch run: optimizer state threads through every
    # step (a per-step trainer.train loop would re-init it each call)
    # and the program compiles once; n_samples counts data ROWS
    params, _, hist = trainer.train(
        params, data, jnp.asarray(batch_size),
        jax.random.key(seed + 1), n_steps,
    )
    losses = [float(x) for x in hist]
    for step, loss in enumerate(losses):
        print(f"epoch {step}: loss {loss:.4f} "
              f"(seq {cfg.max_len} over {n_devices}-way ring)")
    return losses


if __name__ == "__main__":
    from baton_tpu.utils.profiling import enable_compile_cache

    enable_compile_cache()
    p = argparse.ArgumentParser()
    p.add_argument("--scale", choices=["tiny", "full"], default="tiny")
    p.add_argument("--striped", action="store_true",
                   help="load-balanced causal layout (striped attention)")
    args = p.parse_args()
    if args.scale == "full":
        # a real TPU slice: ring x flash takes 32k tokens 8 ways; the
        # striped (dense-kernel) variant is sized down to keep each
        # shard's O((L/N)^2) score block in HBM
        seq = 8192 if args.striped else 32768
        run(n_devices=8, seq_len=seq, n_steps=5, batch_size=1,
            config=LlamaConfig(vocab_size=32000, max_len=seq,
                               d_model=512, n_heads=8, n_kv_heads=4,
                               n_layers=8, d_ff=1536),
            remat=True, striped=args.striped)
    else:
        losses = run(striped=args.striped)
        assert losses[-1] < losses[0], "loss should fall"
