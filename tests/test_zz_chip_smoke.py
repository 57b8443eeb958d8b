"""Rehearse ``chip_smoke.py`` without the chip.

The script takes no option that makes it run on the CPU: without a TPU it
fails (``tests/test_chip_smoke_cli.py`` holds it to that). What these tests
rehearse is its control flow: they call its phase functions at the ``"debug"``
preset under ``JAX_PLATFORMS=cpu`` (Pallas in interpret mode), the four-chip
path on virtual CPU devices, so that a wrong argument or a broken entry point
is found here and not on the chip's clock. A phase that passes here has run on the
CPU; only ``python chip_smoke.py`` on the chip says anything about the chip.

Each phase runs in a process of its own, as the script's do: a phase holds
its parent to not having imported JAX (on the chip such a parent would hold
the device its workers are granted), and a pytest worker that has run any
other file before this one has. Called in the worker's own process the three
passed alone and failed in every run of the whole suite.
"""

import json
import os
import subprocess
import sys

import pytest

import ray_tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PHASE = """
import json, sys
import chip_smoke, ray_tpu
out = getattr(chip_smoke, sys.argv[1])("debug", **json.loads(sys.argv[2]))
assert not ray_tpu.is_initialized()  # a phase stops what it starts
print("RESULT " + json.dumps(out, default=str))
"""


def _phase(name, **kwargs):
    """``chip_smoke.<name>("debug", **kwargs)`` in a fresh interpreter, its
    result read back. The limit is under the 300 s at which the suite's
    watchdog (``tests/conftest.py``) ends the whole worker: a phase that
    hangs fails its own test. Alone a phase takes 10-20 s."""
    done = subprocess.run(
        [sys.executable, "-c", _PHASE, name, json.dumps(kwargs)], cwd=REPO,
        capture_output=True, text=True, timeout=280)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-3000:]
    (line,) = [ln for ln in done.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.fixture
def fake_chips(monkeypatch):
    """Let the node offer ``n`` chips it does not have. The workers that are
    granted them inherit JAX_PLATFORMS=cpu, and see ``n`` virtual CPU
    devices where the test run itself has eight."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()

    def offer(n: int) -> None:
        monkeypatch.setenv("RT_NUM_TPUS", str(n))
        monkeypatch.setenv("JAX_NUM_CPU_DEVICES", str(n))
        monkeypatch.setenv(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}")

    yield offer


def test_serve_phase_control_flow(fake_chips):
    fake_chips(1)
    out = _phase("serve_phase", vocab=256, max_len=128, max_slots=4,
                 prompt_lens=(5, 17, 40, 60), new_tokens=16)
    assert out["requests"] == 6
    assert out["device"]["platform"] == "cpu"  # and so main() would fail it


def test_train_phase_control_flow(fake_chips):
    fake_chips(1)
    out = _phase("train_phase", vocab=256, batch=2, seq=32,
                 steps_per_launch=2, launches=3, save_at=2, loss_chunk=0)
    assert out["device"]["count"] == 1


def test_four_chip_phase_control_flow(fake_chips):
    """The worker is granted four (virtual) devices: auto_mesh shards the
    state fsdp x tp over them, the flash kernel runs per shard, and the
    losses agree with the one-device mesh."""
    fake_chips(4)
    out = _phase("train_phase", vocab=256, chips=4, batch=4, seq=32,
                 steps_per_launch=2, launches=3, save_at=None, loss_chunk=0,
                 compare_single=True)
    assert out["device"]["count"] == 4
