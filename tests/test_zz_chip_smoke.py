"""Rehearse ``chip_smoke.py`` without the chip.

The script takes no option that makes it run on the CPU: without a TPU it
fails (``tests/test_chip_smoke_cli.py`` holds it to that). What these tests
rehearse is its control flow: they import its phase functions and call them at the ``"debug"`` preset
under ``JAX_PLATFORMS=cpu`` (Pallas in interpret mode), the four-chip path on
virtual CPU devices, so that a wrong argument or a broken entry point is found
here and not on the chip's clock. A phase that passes here has run on the
CPU; only ``python chip_smoke.py`` on the chip says anything about the chip.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

import ray_tpu  # noqa: E402


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
    assert not ray_tpu.is_initialized()  # a phase stops what it starts


def test_serve_phase_control_flow(fake_chips):
    fake_chips(1)
    out = chip_smoke.serve_phase(
        "debug", vocab=256, max_len=128, max_slots=4,
        prompt_lens=(5, 17, 40, 60), new_tokens=16)
    assert out["requests"] == 6
    assert out["device"]["platform"] == "cpu"  # and so main() would fail it


def test_train_phase_control_flow(fake_chips):
    fake_chips(1)
    out = chip_smoke.train_phase(
        "debug", vocab=256, batch=2, seq=32, steps_per_launch=2, launches=3,
        save_at=2, loss_chunk=0)
    assert out["device"]["count"] == 1


def test_four_chip_phase_control_flow(fake_chips):
    """The worker is granted four (virtual) devices: auto_mesh shards the
    state fsdp x tp over them, the flash kernel runs per shard, and the
    losses agree with the one-device mesh."""
    fake_chips(4)
    out = chip_smoke.train_phase(
        "debug", vocab=256, chips=4, batch=4, seq=32, steps_per_launch=2,
        launches=3, save_at=None, loss_chunk=0, compare_single=True)
    assert out["device"]["count"] == 4
