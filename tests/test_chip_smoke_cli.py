"""``chip_smoke.py`` runs on a TPU and nowhere else: in this sandbox, which
has none, it must exit non-zero and print no result line. (Its control flow
is rehearsed on the CPU in ``tests/test_zz_chip_smoke.py``.)"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("env,args", [
    ({"JAX_PLATFORMS": "cpu"}, []),           # held to the CPU
    ({"JAX_PLATFORMS": ""}, []),              # no TPU on this host
    ({"JAX_PLATFORMS": "cpu"}, ["--chips", "4"]),
])
def test_script_fails_without_a_tpu(env, args, tmp_path):
    """No retry, no CPU fallback, no result line."""
    import glob

    if not env["JAX_PLATFORMS"] and glob.glob("/dev/vfio/[0-9]*"):
        pytest.skip("this host has a TPU")
    full = {k: v for k, v in os.environ.items()
            if k not in ("RT_NUM_TPUS", "JAX_PLATFORMS")}
    full.update({k: v for k, v in env.items() if v})
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=full, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
