"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax imports
(SURVEY.md §7: test multi-chip sharding without TPU hardware)."""

import os
import sys

# the tests run CPU-pinned (metaflow_tpu/device.py: a backend that is not
# the TPU is an error unless the process was pinned); subprocess flows
# inherit the pin
os.environ["JAX_PLATFORM_NAME"] = "cpu"
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (pytest -m 'not slow')")


@pytest.fixture()
def tpuflow_root(tmp_path, monkeypatch):
    """Isolated datastore/metadata root per test."""
    root = str(tmp_path / "tpuflow_root")
    monkeypatch.setenv("TPUFLOW_DATASTORE_SYSROOT_LOCAL", root)
    return root


@pytest.fixture()
def run_flow(tpuflow_root):
    """Helper: run a flow file as a subprocess against the isolated root."""
    import subprocess

    def _run(flow_file, *args, expect_fail=False, env_extra=None,
             prefix=None):
        env = dict(os.environ)
        env["TPUFLOW_DATASTORE_SYSROOT_LOCAL"] = tpuflow_root
        # hermetic per-test blob cache (the default /tmp/tpuflow_cache is
        # shared machine-wide, which is right in production but couples
        # tests through cache hits)
        env["TPUFLOW_CLIENT_CACHE"] = os.path.join(tpuflow_root, "blobcache")
        inherited = [
            p for p in env.get("PYTHONPATH", "").split(os.pathsep)
            if p
        ]
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
            + inherited
        )
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_PLATFORM_NAME"] = "cpu"
        if env_extra:
            env.update(env_extra)
        proc = subprocess.run(
            [sys.executable] + list(prefix or []) + [flow_file] + list(args),
            env=env,
            capture_output=True,
            text=True,
            timeout=300,
        )
        if not expect_fail and proc.returncode != 0:
            raise AssertionError(
                "flow failed (rc=%d)\nSTDOUT:\n%s\nSTDERR:\n%s"
                % (proc.returncode, proc.stdout, proc.stderr)
            )
        if expect_fail and proc.returncode == 0:
            raise AssertionError(
                "flow unexpectedly succeeded\nSTDOUT:\n%s" % proc.stdout
            )
        return proc

    return _run


FLOWS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "flows")


@pytest.fixture()
def flows_dir():
    return FLOWS_DIR
