"""Chip bring-up invariants that the CPU can pin (ISSUE 21): importing the
package claims no backend, the compile cache is placed from outside or at
one fixed path, the peak table refuses unknown devices, chip_smoke.py
refuses to run without a TPU, the launcher keeps one process per chip host,
a topology may not leave chips idle, and the flash kernels run as a
shard_map island under a multi-device GSPMD trace."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, cwd=REPO, env=None, timeout=120):
    e = {**os.environ, "PYTHONPATH": REPO, **(env or {})}
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=e,
                          capture_output=True, text=True, timeout=timeout)


def test_import_initialises_no_backend():
    """A parent that imports the package (the launcher, spawn, a DataLoader
    worker) must not hold the chip its children need."""
    r = _run("import paddle_tpu, paddle_tpu.distributed.launch\n"
             "import paddle_tpu.distributed.spawn\n"
             "from jax._src import xla_bridge\n"
             "assert not xla_bridge.backends_are_initialized()\n"
             "print('clean')")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "clean" in r.stdout


def test_compile_cache_placement(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the helper sets no directory in code.
    Unset: one in-checkout path, the same from any working directory."""
    import jax

    from paddle_tpu.utils import compile_cache
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "p"))
        assert compile_cache.enable_compile_cache() == str(tmp_path / "p")
        assert jax.config.jax_compilation_cache_dir == saved[0]  # untouched
        assert compile_cache.child_env()["JAX_COMPILATION_CACHE_DIR"] == \
            str(tmp_path / "p")

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        in_checkout = os.path.join(REPO, ".jax_cache")
        for cwd in (REPO, str(tmp_path)):
            monkeypatch.chdir(cwd)
            assert compile_cache.enable_compile_cache() == in_checkout
            assert jax.config.jax_compilation_cache_dir == in_checkout
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          saved[1])


def test_peak_table_refuses_unknown_accelerator():
    from paddle_tpu.obs.flops import peak_flops
    assert peak_flops("TPU v5 lite", "tpu") == 197e12
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops("TPU v9", "tpu")


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "platform='cpu'" in r.stderr and "no accelerator" in r.stderr
    assert '"ok"' not in r.stdout


def test_launcher_keeps_one_process_per_chip_host(monkeypatch):
    from paddle_tpu.distributed import launch
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 4)
    launch.require_one_process_per_chip_host(1, env={})
    launch.require_one_process_per_chip_host(4, env={"JAX_PLATFORMS": "cpu"})
    with pytest.raises(SystemExit, match="one process"):
        launch.require_one_process_per_chip_host(4, env={})
    with pytest.raises(SystemExit, match="--devices"):
        launch.launch(["--devices", "0,1", "train.py"])
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: 0)
    launch.require_one_process_per_chip_host(4, env={})


def test_topology_may_not_leave_chips_idle(monkeypatch):
    """devices=None must use every accelerator chip; an explicit subset and
    virtual CPU devices are free to be smaller."""
    import jax

    from paddle_tpu.distributed.topology import build_mesh_from_dims
    assert build_mesh_from_dims({"data": 2, "model": 1}).size == 2  # cpu
    sub = build_mesh_from_dims({"data": 2}, devices=jax.devices()[:2])
    assert sub.size == 2

    class Chip:
        platform = "tpu"

    chips = [Chip() for _ in range(4)]
    monkeypatch.setattr(jax, "devices", lambda *a: chips)
    with pytest.raises(ValueError, match="2 of this process's 4"):
        build_mesh_from_dims({"data": 2, "model": 1})


def test_flash_runs_as_island_under_a_multi_device_trace():
    """Mosaic kernels cannot be partitioned by GSPMD (JAX refuses to lower
    them), so under `spmd_mesh` flash_attention shard_maps its kernels over
    the batch and head axes; the result and gradients are the reference's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops import attention as A
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(2, 2, 256, 32), jnp.float32)
               for _ in range(3))
    sh = NamedSharding(mesh, P("data", "model"))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))

    def flash_loss(q_, k_, v_):
        with A.spmd_mesh(mesh, "data"):
            o = A.flash_attention(q_, k_, v_, causal=True, block_q=128,
                                  block_k=128, force_pallas=True)
        return jnp.sum(o * o), o

    def ref_loss(q_, k_, v_):
        o = A._attention_reference(q_, k_, v_, True, 1.0 / np.sqrt(32))
        return jnp.sum(o * o), o

    (_, o_f), g_f = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    (_, o_r), g_r = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    assert o_f.sharding.is_equivalent_to(sh, 4)
    np.testing.assert_allclose(o_f, o_r, atol=2e-5)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(a, b, atol=2e-4)
