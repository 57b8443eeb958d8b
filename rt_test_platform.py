"""pytest plugin (loaded via addopts ``-p rt_test_platform``) that re-execs
the test run onto a virtual 8-device CPU JAX platform.

Why a plugin and not conftest: jax backends cannot be reconfigured once
initialized, and the device count is read from the environment at import.
A ``-p`` plugin imports during pytest plugin registration — before
pytest's output capture redirects fd 1 — so the replacement process
inherits the real stdout. (A conftest-time exec would write into the dead
process's capture file.)

Set RT_TESTS_KEEP_PLATFORM=1 to run tests on the real accelerator.
"""

import os
import sys


def _reexec_on_cpu():
    if os.environ.get("RT_TESTS_KEEP_PLATFORM"):
        return
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and os.environ.get("JAX_NUM_CPU_DEVICES") == "8"):
        return
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "8"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    os.execve(sys.executable,
              [sys.executable, "-m", "pytest"] + sys.argv[1:], env)


_reexec_on_cpu()
