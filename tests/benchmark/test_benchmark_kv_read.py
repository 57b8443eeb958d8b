"""``decode_kv_read_ratio``: the reader on recorded runs, with and without
the engine's counters (a program whose read has no bound records none, as
the parent of PR 32), where the metric is declared, and the whole flow on
the CPU: a traced rehearsal of the tiny decode cells reports it."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402

METRIC = "decode_kv_read_ratio"


@pytest.mark.parametrize("engine, value", [
    ({"kv_positions_read": 6 * 16 * 768, "kv_positions_live": 16 * 6 * 372},
     768 / 372),
    ({"kv_positions_read": 2048, "kv_positions_live": 2048}, 1.0),
    ({"kv_positions_read": 0, "kv_positions_live": 0}, None),
    ({"decode_wall_s": 3.2, "tokens": 4096}, None),  # the parent's summary
    (None, None),                                    # a training run
])
def test_the_reader_on_a_recorded_run(engine, value):
    run = {} if engine is None else {"engine": engine}
    got = spec.load_reader(METRIC)(run)
    assert got is None if value is None else got == pytest.approx(value)


def test_the_metric_is_declared_where_decode_sets_the_pace():
    (entry,) = [m for m in spec.load_benchmark()["per_layer"]
                if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "Step program",
        "moves": "out_tok_s",
        "workloads": ["mistral7b-serve-decode", "olmoe1b7b-serve-decode",
                      "granite4hmicro-serve-decode"]}
    assert spec.load_benchmark()["per_layer"][-1] == entry  # appended
    for cell in ("mistral7b-serve-chat", "mistral7b-train-4k",
                 "mixtral8x7b-train-4k-x4"):
        assert METRIC not in {m["name"] for m in spec.Cell(cell).per_layer}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_copy(str(tmp_path_factory.mktemp("bench-kv-read")))


@pytest.mark.parametrize("cell", ["tiny-decode", "tiny-moe-decode"])
def test_a_traced_rehearsal_reports_the_ratio(root, cell):
    """A dense and a sparse tiny cell through proxy, handle, replica and
    engine on the CPU: the result line carries the ratio, which is at least
    1 (every live position lies below the bound)."""
    assert METRIC in {m["name"] for m in spec.Cell(cell, root).per_layer}
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "4100000011", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=420)
    assert done.returncode == 3, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["failed"] == 0 and line["attempted"] > 0
    got = line["metrics"][METRIC]
    assert got["unit"] == "ratio" and got["value"] >= 1.0
