"""The per-layer metrics that read the engine's spans: each reader on a
hand-made ``run`` (its value, and nothing where the program has no such
key, as the parent commit has not), and the two serve rehearsals, traced,
reporting them."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec, trace  # noqa: E402

# what ``EngineRecorder.window_summary`` gives the benchmark of a window
ENGINE = {
    "tick_wall_s": 50.0, "decode_wall_s": 45.0, "window_completed": 100,
    "phase_s": {"admission": 0.25, "kv_restore": 0.05, "prefill": 4.5,
                "decode_step": 44.0, "token_delivery": 0.5, "record": 0.1},
    "decode_parts_s": {"decode_stage": 0.6, "decode_launch": 43.0,
                       "decode_book": 0.4},
    "tick_gap_p50_s": 0.0035, "tick_excess_s": 2.0, "launch_excess_s": 0.5,
    "queue_p50_s": 0.120, "queue_p90_s": 0.260,
    "front_in_p50_s": 0.004, "front_in_p90_s": 0.009,
    "pump_lag_p50_s": 0.0002, "pump_lag_p99_s": 0.003, "pump_lag_max_s": 0.9,
}
# metric -> (its value on ENGINE, the keys without which it reads nothing)
READERS = {
    "engine_admission_share": (0.5, ["decode_parts_s"]),
    "engine_prefill_fenced_share": (9.0, ["decode_parts_s"]),
    "engine_queue_p50_ms": (120.0, ["queue_p50_s"]),
    "engine_queue_p90_ms": (260.0, ["queue_p90_s"]),
    "front_in_p50_ms": (4.0, ["front_in_p50_s"]),
    "front_in_p90_ms": (9.0, ["front_in_p90_s"]),
    "chat_pump_lag_p99_ms": (3.0, ["pump_lag_p99_s"]),
    "decode_pump_lag_max_ms": (900.0, ["pump_lag_max_s"]),
    "decode_launch_gap_ms": (3.5, ["decode_wall_s"]),
    "decode_tick_excess_share": (4.0, ["tick_excess_s"]),
    "decode_launch_excess_share": (1.0, ["launch_excess_s"]),
}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_value(name):
    value, _ = READERS[name]
    assert spec.load_reader(name)({"engine": ENGINE}) == pytest.approx(value)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_finds_nothing_without_its_key(name):
    read = spec.load_reader(name)
    for key in READERS[name][1]:
        engine = {k: v for k, v in ENGINE.items() if k != key}
        assert read({"engine": engine}) is None
    assert read({"engine": {}}) is None and read({}) is None


def test_every_new_metric_is_an_entry_with_a_reader():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    for cell, prefix in (("mistral7b-serve-chat", ("engine_", "front_in", "chat_")),
                         ("mistral7b-serve-decode", ("decode_",))):
        mine = {m["name"] for m in spec.Cell(cell).per_layer}
        for name in READERS:
            assert entries[name]["source"] == "program_span"
            if name.startswith(prefix):
                assert name in mine, (cell, name)


def test_the_three_shares_sum_to_engine_prefill_share():
    run = {"engine": ENGINE}
    whole = spec.load_reader("engine_prefill_share")(run)
    parts = (spec.load_reader("engine_admission_share")(run)
             + spec.load_reader("engine_prefill_fenced_share")(run)
             + 100.0 * ENGINE["phase_s"]["kv_restore"] / ENGINE["tick_wall_s"])
    assert parts == pytest.approx(whole, abs=0.1)


def test_the_program_and_the_reduction_agree_on_the_prefix():
    from ray_tpu.util import recorder_core

    assert recorder_core.TRACE_PREFIX == trace.ANNOTATION_PREFIX


# ---- the rehearsals: the whole flow on the CPU, traced ----------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_copy(str(tmp_path_factory.mktemp("bench-spans")))


def _traced_rehearsal(root, cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "5",
         "--seconds", "4", "--trace", "1", "--rehearse"], cwd=root, env=env,
        capture_output=True, text=True, timeout=420)
    assert done.returncode == 3, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, expected", [
    ("tiny-chat", ["engine_admission_share", "engine_prefill_fenced_share",
                   "engine_queue_p50_ms", "engine_queue_p90_ms",
                   "front_in_p50_ms", "front_in_p90_ms",
                   "chat_pump_lag_p99_ms"]),
    ("tiny-decode", ["decode_pump_lag_max_ms", "decode_launch_gap_ms",
                     "decode_tick_excess_share",
                     "decode_launch_excess_share"]),
])
def test_traced_rehearsal_reports_the_span_metrics(root, cell, expected):
    line = _traced_rehearsal(root, cell)
    assert line["failed"] == 0 and line["attempted"] > 0
    metrics = line["metrics"]
    for name in expected:
        assert name in metrics, (name, sorted(metrics))
        assert metrics[name]["value"] >= 0.0 and metrics[name]["unit"]
    if cell == "tiny-chat":
        whole = metrics["engine_prefill_share"]["value"]
        assert (metrics["engine_admission_share"]["value"]
                + metrics["engine_prefill_fenced_share"]["value"]
                == pytest.approx(whole, abs=0.1))  # no prefix cache: no kv_restore
        assert 0.0 < metrics["front_in_p50_ms"]["value"] < 5000.0
