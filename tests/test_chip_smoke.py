"""The honest device path: chip_smoke.py refuses to run off the chip
unless told to rehearse, the compile cache can be placed from outside,
and nothing on the path defaults where it should refuse (a mesh larger
than the host, a device the tables do not hold, an unaligned kernel
block)."""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
CHIP_SMOKE = REPO / "chip_smoke.py"


def _run_chip_smoke(*args, env=None, cwd=REPO, timeout=600):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("XLA_FLAGS", None)
    full_env.update(env or {})
    return subprocess.run(
        [sys.executable, str(CHIP_SMOKE), *args], capture_output=True,
        text=True, timeout=timeout, env=full_env, cwd=str(cwd))


def test_chip_smoke_without_a_chip_exits_nonzero_and_reports_nothing():
    proc = _run_chip_smoke()
    assert proc.returncode != 0
    # no result line, no phase line, no device metric of any kind
    assert proc.stdout.strip() == ""
    assert "needs platform 'tpu'" in proc.stderr
    assert "--rehearse-cpu" in proc.stderr


@pytest.mark.slow  # compiles a toy ResNet, a toy decoder and the flash
# kernel in interpret mode on XLA:CPU — tens of seconds
def test_chip_smoke_rehearsal_runs_every_phase(tmp_path):
    """--rehearse-cpu drives the same code path at toy sizes; every
    line and the JSON say ``rehearsal``. Two virtual devices, so the
    mesh phase is rehearsed too; the cache goes where the environment
    puts it."""
    cache = tmp_path / "cache"
    proc = _run_chip_smoke(
        "--rehearse-cpu",
        env={"JAX_COMPILATION_CACHE_DIR": str(cache),
             "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result == {"ok": True, "rehearsal": True,
                      "device": {"platform": "cpu", "kind": "cpu",
                                 "count": 2}}
    phase_lines = lines[:-1]
    assert all("rehearsal" in ln for ln in phase_lines)
    for phase in ("device", "fedsim_resnet18", "hybrid_lora", "moe_mla_lora",
                  "cca_lora", "ssm_lora", "window_lora", "parallel_lora",
                  "flash_kernel",
                  "http_round", "mesh", "cache"):
        assert any(f"phase={phase} " in ln for ln in phase_lines), phase
    # ("skipped:" is a phase that did not run; cca_lora counts the
    # tokens that skipped their expert)
    assert not any("skipped:" in ln for ln in phase_lines)
    assert any("phase=flash_kernel " in ln and "causal_core [1, 2, 2048, 24/16]"
               in ln for ln in phase_lines)
    # a rehearsal prints no time taken on the CPU under any name
    assert "not measured (rehearsal)" in proc.stdout
    assert any(cache.iterdir())


def test_compile_cache_is_placed_by_the_environment(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, JAX has read the variable
    itself: the function must not touch jax_compilation_cache_dir."""
    from baton_tpu.utils import profiling

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: pytest.fail(f"updated {name}")
        if name == "jax_compilation_cache_dir" else None)
    assert profiling.enable_compile_cache() == ("/some/dir", True)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch, tmp_path):
    """Unset, the cache is <repo>/.jax_cache resolved from __file__ —
    the same string from any working directory, never /tmp or a fresh
    name — and .gitignore lists it."""
    from baton_tpu.utils import profiling

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    seen = []
    try:
        for cwd in (REPO, tmp_path):
            monkeypatch.chdir(cwd)
            seen.append(profiling.enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == seen[-1][0]
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert seen[0] == seen[1] == (str(REPO / ".jax_cache"), False)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_make_mesh_refuses_more_devices_than_exist():
    from baton_tpu.parallel.mesh import make_mesh

    n = jax.device_count()
    assert make_mesh(n).devices.size == n
    with pytest.raises(ValueError, match="more devices than exist"):
        make_mesh(n + 1)
    with pytest.raises(ValueError, match="more devices than exist"):
        make_mesh(2, devices=jax.devices()[:1])


def test_peak_table_refuses_an_unknown_device_kind():
    """The live record keeps a reason in place of the number, and never
    falls back to some chip's peak."""
    from baton_tpu.obs.compute import compute_mfu, peak_flops_for

    peak, why = peak_flops_for("weird accelerator")
    assert peak is None and "weird accelerator" in why
    assert compute_mfu(197e12 / 4, 1.0, "TPU v5 lite") == (0.25, None)
    mfu, why = compute_mfu(1e12, 1.0, "weird accelerator")
    assert mfu is None and "weird accelerator" in why


def test_flash_blocks_stay_tile_aligned_off_the_interpreter():
    """The compiled (non-interpret) branch of _pick_blocks: blocks are
    the minor dim of the lse/db tiles and the second-minor of the score
    tile, so they stay >= 128 and multiples of 128 whatever the
    sequence; the padding they imply covers the sequence."""
    from baton_tpu.ops.flash_attention import _pick_blocks, _prepare_padding

    for lq, lk in ((1, 1), (7, 130), (100, 100), (128, 128), (129, 4096),
                   (197, 197), (512, 2048), (4096, 4096), (5000, 9000)):
        bq, bk = _pick_blocks(lq, lk, 512, 1024, interpret=False)
        assert 128 <= bq <= 512 and bq % 128 == 0, (lq, bq)
        assert 128 <= bk <= 1024 and bk % 128 == 0, (lk, bk)
        bq2, bk2, pad_q, pad_k = _prepare_padding(lq, lk, 512, 1024, False)
        assert (bq2, bk2) == (bq, bk)
        assert (lq + pad_q) % bq == 0 and (lk + pad_k) % bk == 0
    # interpret mode may shrink below a tile — CPU tests only
    assert _pick_blocks(16, 16, 512, 1024, interpret=True) == (16, 16)


def test_the_flash_phase_holds_the_cells_latent_core():
    """On the chip the phase runs the core of ``sarvam_105b_c4_l2048`` at
    its shape, values narrower than keys, against the blocked plain
    core; the rehearsal the same code over two of the core's blocks a
    side, interpreted."""
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(REPO))
    assert chip_smoke.CHIP.core_shape == (4, 64, 2048, 192, 128)
    env = chip_smoke.Env(sizes=chip_smoke.REHEARSAL, rehearsal=True,
                         platform="cpu", kind="cpu", count=1, cache_dir="",
                         cache_from_env=False)
    line = chip_smoke._latent_core(env)
    assert "causal_core [1, 2, 2048, 24/16] bf16" in line
    assert "not measured (rehearsal)" in line and " ms " not in line


def test_flash_interpret_is_decided_in_one_place(monkeypatch):
    """Interpreting on a TPU backend would report a kernel that never
    ran: refused. None means compiled there, interpreted on the CPU."""
    from baton_tpu.ops import flash_attention as fa

    assert fa._resolve_interpret(None) is True  # the CPU test backend
    assert fa._resolve_interpret(False) is False  # cross-lowering
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    assert fa._resolve_interpret(None) is False
    assert fa._resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        fa._resolve_interpret(True)
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError):
        fa._resolve_interpret(None)


def test_importing_the_package_initialises_no_backend():
    """One process for each chip: a parent that only imports the
    package must leave the chip to the child it starts."""
    code = (
        "import baton_tpu, baton_tpu.obs.compute, baton_tpu.utils.profiling\n"
        "import baton_tpu.server.http_manager, baton_tpu.server.http_worker\n"
        "import jax._src.xla_bridge as xb\n"
        "assert not xb._backends, dict(xb._backends)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, cwd=str(REPO), env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_compute_record_counts_the_chips_the_round_used(nprng):
    """Throughput per chip divides by the devices the round ran on: one
    without a mesh, however many the host has (the tests' eight). The
    mesh side is chip_smoke.py's mesh phase."""
    import jax.numpy as jnp

    from baton_tpu.data.synthetic import linear_client_data
    from baton_tpu.models.linear import linear_regression_model
    from baton_tpu.obs.compute import ComputeProbe
    from baton_tpu.ops.padding import stack_client_datasets
    from baton_tpu.parallel.engine import FedSim

    assert jax.device_count() == 8
    data, n = stack_client_datasets(
        [linear_client_data(nprng, min_batches=1, max_batches=1)
         for _ in range(2)], batch_size=32)
    sim = FedSim(linear_regression_model(10), batch_size=32)
    sim.run_round(sim.init(jax.random.key(0)),
                  {k: jnp.asarray(v) for k, v in data.items()},
                  jnp.asarray(n), jax.random.key(1))
    assert sim.last_compute["n_chips"] == 1
    assert sim.last_compute["device_kind"] == "cpu"
    # a backend with no allocator statistics is a reason, not an error
    gb, src, why = ComputeProbe._peak_hbm(jax.devices()[0])
    assert gb is None and src is None and "'cpu'" in why
