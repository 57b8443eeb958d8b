"""pytest plugin (loaded via addopts ``-p rt_test_platform``) that re-execs
the test run onto a virtual 8-device CPU JAX platform.

Why a plugin and not conftest: jax backends cannot be reconfigured once
initialized, and the device count is read from the environment at import.
A ``-p`` plugin imports during pytest plugin registration — before
pytest's output capture redirects fd 1 — so the replacement process
inherits the real stdout. (A conftest-time exec would write into the dead
process's capture file.)

Tier-1's CPU programs are there to be right, not fast: almost all of the
suite's time is XLA's CPU backend (LLVM) building programs that then run for
milliseconds, so the same re-exec compiles them the cheap way (backend
optimisation level 0, LLVM's expensive passes off; PR 64: 13-20% of a
compile-heavy file). The compile for a described TPU (``tests/test_aot_*``)
is libtpu's and reads neither flag; every subprocess a test starts inherits
them, so a CPU timing from a test means even less than it did.

Set RT_TESTS_KEEP_PLATFORM=1 to run tests on the real accelerator: that
path gets none of the above.
"""

import os
import sys

#: what the re-exec appends to ``XLA_FLAGS``, each once
_CPU_XLA_FLAGS = ("--xla_force_host_platform_device_count=8",
                  "--xla_backend_optimization_level=0",
                  "--xla_llvm_disable_expensive_passes=true")


def _reexec_on_cpu():
    if os.environ.get("RT_TESTS_KEEP_PLATFORM"):
        return
    have = os.environ.get("XLA_FLAGS", "").split()
    missing = [flag for flag in _CPU_XLA_FLAGS if flag not in have]
    if (os.environ.get("JAX_PLATFORMS") == "cpu"
            and os.environ.get("JAX_NUM_CPU_DEVICES") == "8"
            and not missing):
        return
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_NUM_CPU_DEVICES"] = "8"
    env["XLA_FLAGS"] = " ".join(have + missing)
    os.execve(sys.executable,
              [sys.executable, "-m", "pytest"] + sys.argv[1:], env)


_reexec_on_cpu()
