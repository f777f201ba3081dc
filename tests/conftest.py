# NOTE: deliberately NO --xla_force_host_platform_device_count here — smoke
# tests and benches must see the single real CPU device.  Tests that need a
# multi-device mesh spawn a subprocess with XLA_FLAGS set (see helpers).
import os
import subprocess
import sys

import numpy as np
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


# A session's posterior is the same to rounding whichever fleet, bucket
# rung, padding, minibatch spec or executor it runs in.  Those change the
# program, and XLA may then sum the same terms in another order, which
# moves a statistic by an ulp or so.  The VB map can amplify such a
# difference while the components are still separating (about 1.5x per
# iteration on 10-point nodes), so the bound is stated for runs of a few
# dozen iterations, which is what these tests make.  Over every such
# comparison in this suite the largest gap is 1.7e-13 of phi's largest
# magnitude (test_driver.py::test_scheduling_invariance_matmul_topologies,
# fleets of up to 24 iterations), so 1e-12 leaves about 6x headroom.  A single leaked padding point moves phi by
# 5e-3 (test_bucketed.py::test_leaked_padding_point_fails_same_posterior).
# Where the program is the same (split/resume, checkpoint restore, the
# same fleet under another slice length) the tests assert the same bits
# instead.  The subprocess scripts, which cannot import this module,
# inline the same expression.
SAME_POSTERIOR_RTOL = 1e-12


def assert_same_posterior(actual, desired, err_msg=""):
    """Assert max|actual - desired| <= 1e-12 * max|desired|."""
    actual, desired = np.asarray(actual), np.asarray(desired)
    assert actual.shape == desired.shape, (actual.shape, desired.shape)
    gap = float(np.max(np.abs(actual - desired)))
    bound = SAME_POSTERIOR_RTOL * float(np.max(np.abs(desired)))
    assert gap <= bound, f"{err_msg}: max gap {gap:.3e} > bound {bound:.3e}"


@pytest.fixture(scope="session")
def subproc():
    return run_subprocess
