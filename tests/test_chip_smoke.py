"""chip_smoke.py rehearsed on the CPU at the tiny size: every phase must
run and the platform check must then fail; and the one compile-cache
function must put the cache where it is told, or always in one place."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                  if p])
    env.update(extra)
    return env


def test_cpu_rehearsal_runs_every_phase_then_fails_the_platform_check():
    proc = subprocess.run(
        [sys.executable, SMOKE, "--size", "tiny", "--phase-timeout", "300"],
        env=_env(), capture_output=True, text=True, timeout=600)
    out = proc.stdout
    assert proc.returncode != 0, out[-3000:]
    for phase in ("train", "serve", "paged", "kernels"):
        assert "phase %s: ok" % phase in out, (phase, out[-3000:],
                                               proc.stderr[-2000:])
    assert "interpret=True" in out  # the CPU rehearsal says so
    last = json.loads(out.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_full_width_without_a_chip_prints_no_result():
    proc = subprocess.run(
        [sys.executable, SMOKE], env=_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    assert '"ok"' not in proc.stdout


_ASK = ("import os, sys; from metaflow_tpu import device; "
        "print(os.getpid(), device.setup_compile_cache(), "
        "os.environ.get('JAX_COMPILATION_CACHE_DIR'), 'jax' in sys.modules)")


def _ask(cwd, **extra):
    out = subprocess.run(
        [sys.executable, "-c", _ASK], cwd=cwd, env=_env(**extra),
        capture_output=True, text=True, timeout=60, check=True).stdout
    pid, path, env_value, imported_jax = out.split()
    return int(pid), path, env_value, imported_jax


def test_compile_cache_is_placed_from_outside_or_in_one_fixed_place(
        tmp_path):
    # told where: that directory, and no other is set
    pid, path, env_value, _ = _ask(
        str(tmp_path), JAX_COMPILATION_CACHE_DIR="/x/cache")
    assert path == env_value == "/x/cache"

    # not told, and not CPU-pinned: one path inside the checkout, the
    # same from any working directory and process, handed to JAX (and to
    # child processes) through the variable JAX reads, without importing
    # JAX to do it
    fixed = os.path.join(REPO, ".tpuflow", "jax_cache")
    other = tmp_path / "elsewhere"
    other.mkdir()
    a = _ask(str(tmp_path), JAX_PLATFORMS="")
    b = _ask(str(other), JAX_PLATFORMS="")
    assert a[0] != b[0]
    assert a[1:] == b[1:] == (fixed, fixed, "False")

    # CPU-pinned (the tests): the same answer, but no cache is turned on
    assert _ask(str(tmp_path))[1:3] == (fixed, "None")


# ---------------------------------------------------------------------------
# metaflow_tpu/device.py: no quiet CPU, one process for each chip
# ---------------------------------------------------------------------------


def test_platform_is_cpu_only_when_pinned(monkeypatch):
    import pytest

    from metaflow_tpu import device

    assert device.platform() == "cpu" and not device.on_tpu()
    # the same CPU backend in a process nobody pinned: JAX's fallback
    monkeypatch.setattr(device, "cpu_pinned", lambda: False)
    with pytest.raises(device.NoAcceleratorError, match="not 'tpu'"):
        device.platform()


def test_trainer_and_server_refuse_a_quiet_cpu(monkeypatch):
    import jax
    import pytest

    from metaflow_tpu import device
    from metaflow_tpu.cmd.serve import build_engine
    from metaflow_tpu.models import llama
    from metaflow_tpu.ops.attention import attention
    from metaflow_tpu.spmd import MeshSpec, create_mesh
    from metaflow_tpu.training import make_trainer

    monkeypatch.setattr(device, "cpu_pinned", lambda: False)
    cfg = llama.LlamaConfig.tiny()
    with pytest.raises(device.NoAcceleratorError):
        make_trainer(jax.random.PRNGKey(0), cfg,
                     create_mesh(MeshSpec.dp(), n_devices=1), llama)
    with pytest.raises(device.NoAcceleratorError):
        build_engine({}, cfg)
    q = jax.numpy.zeros((1, 128, 2, 128))
    with pytest.raises(device.NoAcceleratorError):
        attention(q, q, q)  # 'auto' asks which device; 'xla' by name runs
    assert attention(q, q, q, impl="xla").shape == q.shape


def test_local_processes_cannot_share_a_chip(monkeypatch):
    import pytest

    from metaflow_tpu import device
    from metaflow_tpu.cmd.serve import serve_fleet
    from metaflow_tpu.exception import TpuFlowException

    device.refuse_chip_sharing(4, "a gang")  # CPU-pinned: fine
    monkeypatch.setattr(device, "cpu_pinned", lambda: False)
    device.refuse_chip_sharing(1, "one process")
    with pytest.raises(TpuFlowException, match="one process at a time"):
        device.refuse_chip_sharing(2, "The local gang of step *train*")
    with pytest.raises(TpuFlowException, match="--replicas"):
        serve_fleet("NoFlow/1", replicas=2)
    with pytest.raises(TpuFlowException, match="--prefill-workers"):
        serve_fleet("NoFlow/1", replicas=1, prefill_workers=1)


def test_chip_peaks_unknown_tpu_kind_is_an_error():
    import pytest

    from metaflow_tpu.training.metrics import hbm_gbps, peak_tflops

    assert (peak_tflops("TPU v5 lite"), hbm_gbps("TPU v5 lite")) == (
        197.0, 819.0)
    assert peak_tflops("cpu") is None and hbm_gbps("cpu") is None
    for table in (peak_tflops, hbm_gbps):
        with pytest.raises(ValueError, match="TPU v9x"):
            table("TPU v9x")


def test_flash_kernel_partition_over_a_mesh():
    import jax
    from jax.sharding import PartitionSpec as P

    from metaflow_tpu.ops.attention import _flash_partition
    from metaflow_tpu.spmd import MeshSpec, create_mesh

    q = jax.ShapeDtypeStruct((4, 256, 8, 128), "bfloat16")
    kv = jax.ShapeDtypeStruct((4, 256, 2, 128), "bfloat16")
    assert _flash_partition(None, q, kv) is None
    assert _flash_partition(
        create_mesh(MeshSpec.dp(), n_devices=1), q, kv) is None
    mesh = create_mesh(MeshSpec.fsdp_tp(2), n_devices=4)
    assert _flash_partition(mesh, q, kv) == P(("fsdp",), None, "tensor",
                                              None)
    odd = jax.ShapeDtypeStruct((3, 256, 8, 128), "bfloat16")
    assert _flash_partition(mesh, odd, kv) is False
    # eight-way tensor parallelism cannot split two kv heads
    assert _flash_partition(
        create_mesh(MeshSpec.fsdp_tp(8), n_devices=8), q, kv) is False


def test_on_a_tpu_a_shape_that_does_not_tile_says_so(monkeypatch):
    import pytest

    from metaflow_tpu import device
    from metaflow_tpu.ops.attention import auto_impl

    assert auto_impl(True, "attention", (1, 128, 2, 128)) == "xla"  # CPU
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    assert auto_impl(True, "attention", (1, 128, 2, 128)) == "flash"
    with pytest.warns(RuntimeWarning, match=r"\(1, 100, 2, 64\)"):
        assert auto_impl(False, "attention", (1, 100, 2, 64)) == "xla"
