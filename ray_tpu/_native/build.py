"""Build the rt_native C extension in place.

Run: ``python -m ray_tpu._native.build``  (or it happens lazily on first
import through ``ray_tpu._native``). Uses g++ directly — no setuptools
machinery, no network. The .so lands next to this file; a content hash of
the source gates rebuilds.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys
import sysconfig

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "rt_native.cpp")
SO = os.path.join(_DIR, f"rt_native{sysconfig.get_config_var('EXT_SUFFIX')}")
STAMP = os.path.join(_DIR, ".build_hash")


def _src_hash() -> str:
    return hashlib.sha256(open(SRC, "rb").read()).hexdigest()


def build(force: bool = False, quiet: bool = True) -> str:
    """Compile if needed; returns the .so path. Raises on compile failure."""
    if (not force and os.path.exists(SO) and os.path.exists(STAMP)
            and open(STAMP).read().strip() == _src_hash()):
        return SO
    include = sysconfig.get_paths()["include"]
    # a clean checkout builds on first use, and a driver's workers may all
    # get there at once: each builds into a file of its own
    tmp = f"{SO}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O2", "-std=c++17", "-fPIC", "-shared", "-Wall",
        f"-I{include}", SRC, "-o", tmp,
    ]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        if not quiet:
            sys.stderr.write(proc.stderr)
        raise RuntimeError(f"rt_native build failed:\n{proc.stderr[-2000:]}")
    os.replace(tmp, SO)
    with open(STAMP, "w") as f:
        f.write(_src_hash())
    return SO


if __name__ == "__main__":
    print(build(force="--force" in sys.argv, quiet=False))
