"""ASAN/UBSAN pass over the rt_native C extension (reference: the bazel
``--config=asan``/``tsan`` CI builds, SURVEY.md §4)."""

import shutil
import subprocess
import sys

import pytest


def test_native_asan_ubsan_clean():
    if shutil.which("g++") is None:
        pytest.skip("no toolchain")
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.sanitize_native"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "asan+ubsan clean" in proc.stdout
