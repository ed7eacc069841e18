import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
sys.path.insert(0, SRC)


def run_subprocess(code: str, n_devices: int = 1, timeout: int = 600):
    """Run a python snippet in a fresh process with N fake CPU devices.

    Multi-device tests must run out-of-process: the main pytest process keeps the
    default single device (per the dry-run isolation rule).  The child is held
    to the CPU: an accelerator belongs to one process at a time.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if n_devices > 1:
        env["XLA_FLAGS"] = (f"--xla_force_host_platform_device_count={n_devices} "
                            + env.get("XLA_FLAGS", ""))
    res = subprocess.run([sys.executable, "-c", code], env=env, timeout=timeout,
                         capture_output=True, text=True)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={res.returncode})\nstdout:\n{res.stdout}\n"
            f"stderr:\n{res.stderr[-4000:]}")
    return res.stdout


@pytest.fixture
def subproc():
    return run_subprocess
