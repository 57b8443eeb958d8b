"""``benchmark/run.py`` itself, as the driver starts it, in a temporary copy
with tiny configurations, on the CPU: without a TPU it prints no result and
fails; as a rehearsal it goes through the whole flow and says that it was
one. The cells rehearsed beyond ``tiny-train`` run on what the copy alone
holds: a third family, the serve drivers on a family that is not dense, and
arrivals in bursts."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_copy(str(tmp_path_factory.mktemp("bench")))


def test_no_tpu_no_result(root):
    done = _run(root, "--workload", "tiny-train", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "TPU" in done.stderr
    assert not [ln for ln in done.stdout.splitlines() if ln.startswith("{")]


def test_unknown_cell_is_an_error(root):
    done = _run(root, "--workload", "absent", "--seconds", "1")
    assert done.returncode != 0 and "no workload" in done.stderr


@pytest.mark.parametrize("cell,judged", [
    ("tiny-train", "train_tok_s_chip"),
    ("tiny-third-train", "train_tok_s_chip"),
    ("tiny-moe-decode", "out_tok_s"),
    ("tiny-bursty", "tpot_p75_ms"),
])
def test_rehearsal_prints_a_well_formed_line_that_cannot_pass(root, cell, judged):
    done = _run(root, "--workload", cell, "--seed", "4000000007",
                "--seconds", "3", "--trace", "0", "--rehearse")
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(line["metrics"]) == {judged, "setup_s"}
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["value"] > 0 and m["unit"]
    assert line["attempted"] > 0 and line["failed"] == 0
    # the only reason it is not correct is where it ran: the reference of
    # the cell's family agreed with what the program computed
    assert line["why_not_correct"] == ["ran on cpu x" + str(line["device"]["count"])
                                       + ", not on 1 TPU chip(s)"]
