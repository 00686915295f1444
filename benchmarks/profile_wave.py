"""Break down one bench round's cost on the TPU."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax, jax.numpy as jnp
import numpy as np

from baton_tpu.utils.profiling import enable_compile_cache

enable_compile_cache()
from baton_tpu.models.resnet import resnet18_cifar_model
from baton_tpu.ops.padding import stack_client_datasets
from baton_tpu.parallel.engine import FedSim

print("backend:", jax.default_backend())
rng = np.random.default_rng(0)
N_CLIENTS, SPC, BS = 32, 48, 32
datasets = [{"x": rng.normal(size=(SPC,32,32,3)).astype(np.float32),
             "y": rng.integers(0,10,size=(SPC,)).astype(np.int32)} for _ in range(N_CLIENTS)]
data, n_samples = stack_client_datasets(datasets, batch_size=BS)
data = {k: jax.device_put(jnp.asarray(v)) for k, v in data.items()}
n_samples = jnp.asarray(n_samples)

model = resnet18_cifar_model(compute_dtype=jnp.bfloat16)
params = model.init(jax.random.key(0))
sim = FedSim(model, batch_size=BS, learning_rate=0.05)

def t(label, f, iters=5):
    out = f(); jax.block_until_ready(out)
    t0=time.perf_counter()
    for _ in range(iters): out=f()
    jax.block_until_ready(out)
    ms=(time.perf_counter()-t0)/iters*1e3
    print(f"{label}: {ms:.1f} ms")
    return ms

# 1. plain forward loss, one batch of 1024 (32 clients x 32)
xb = data["x"][:, :BS].reshape(-1, 32, 32, 3)
yb = data["y"][:, :BS].reshape(-1)
@jax.jit
def fwd(params):
    losses = model.per_example_loss(params, {"x": xb, "y": yb}, jax.random.key(0))
    return jnp.sum(losses)
t("fwd loss batch1024", lambda: fwd(params))

# 2. fwd+bwd one batch of 1024 (shared params, ONE gradient)
@jax.jit
def fwdbwd(params):
    return jax.grad(lambda p: jnp.sum(model.per_example_loss(p, {"x": xb, "y": yb}, jax.random.key(0))))(params)
t("fwd+bwd batch1024 shared-params", lambda: fwdbwd(params))

# 3. vmapped per-client fwd+bwd (32 separate grads, batch 32 each)
@jax.jit
def vmapped_grads(params):
    def one(d):
        return jax.grad(lambda p: jnp.sum(model.per_example_loss(p, {"x": d["x"][:BS], "y": d["y"][:BS]}, jax.random.key(0))))(params)
    return jax.vmap(one)({"x": data["x"], "y": data["y"]})
t("vmap 32-client fwd+bwd (batch 32 each)", lambda: vmapped_grads(params), iters=3)

# 4. the full wave kernel (2 batches x 1 epoch incl shuffle + sgd)
def wave():
    return sim._wave_sums_vmap(params, None, data, n_samples,
                               jax.random.split(jax.random.key(1), N_CLIENTS), 1)
t("full wave (1 epoch, 2 steps)", wave, iters=3)
