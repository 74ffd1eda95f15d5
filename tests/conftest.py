import os
import subprocess
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import pytest  # noqa: E402


def run_py(code: str, n_devices: int = 8, timeout: int = 600) -> str:
    """Run a snippet in a fresh interpreter with N forced host devices
    (mesh-dependent tests can't share the main process's single device)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
        )
    return out.stdout


@pytest.fixture
def subproc():
    return run_py
