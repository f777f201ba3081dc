# NOTE: deliberately NO --xla_force_host_platform_device_count here — smoke
# tests and benches must see the single real CPU device.  Tests that need a
# multi-device mesh spawn a subprocess with XLA_FLAGS set (see helpers).
import os
import subprocess
import sys

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(code: str, n_devices: int = 4) -> str:
    """Run python `code` in a fresh process with N host-platform devices."""
    env = dict(os.environ)
    # the child emulates host devices and must never reach for an
    # accelerator, which the parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess
