"""Shared fake-multi-device subprocess runner for tests.

jax locks the device count at first backend init, so multi-device cases run
in fresh subprocesses with ``--xla_force_host_platform_device_count`` set in
the environment *before* any jax import.  One copy here instead of one per
test module (test_dist / test_serve_sharded / test_train_compress).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 900,
            expect_returncode: int = 0) -> str:
    """Run ``code`` in a fresh fake-multi-device python.

    ``expect_returncode`` lets chaos tests assert a process *died the way it
    was killed* (e.g. ``-signal.SIGKILL`` for the kill-and-recover test)
    instead of exiting cleanly.
    """
    env = dict(os.environ)
    # The child is a fake-device CPU run by design; it must never contend
    # with the parent for an accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == expect_returncode, (
        f"returncode {out.returncode} != {expect_returncode}; "
        f"stderr:\n{out.stderr[-3000:]}")
    return out.stdout
