"""The per-layer metrics that read the program's record of set-up and
tear-down (``ray_tpu/util/lifecycle.py``): each reader on a record made by
hand (its value, and nothing where the program keeps no such record, as the
parent commit does not), and the chat and train rehearsals, traced, reporting
them and leaving no process behind."""

import json
import os
import subprocess
import sys
import uuid

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402

SERVE_ONLY = ["replica_start_s", "serve_healthy_wait_s", "decode_programs_s"]
EVERY_CELL = ["runtime_init_s", "worker_boot_s", "shutdown_s",
              "workers_killed", "procs_left"]
UNITS = {name: "count" if name in ("workers_killed", "procs_left") else "s"
         for name in SERVE_ONLY + EVERY_CELL}

# the engine's summary as ``run["engine"]`` carries it: one entry a program
ENGINE = {"decode_programs": [
    {"bucket": 1, "k": 1, "lower_s": 0.25, "compile_s": 0.5, "traffic_s": 0.125},
    {"bucket": 16, "k": 8, "lower_s": 0.5, "compile_s": 1.0, "traffic_s": 0.125}]}
VALUES = {"runtime_init_s": 1.25, "worker_boot_s": 2.5, "replica_start_s": 28.0,
          "serve_healthy_wait_s": 0.5, "decode_programs_s": 2.5,
          "shutdown_s": 3.75, "workers_killed": 1.0, "procs_left": 0.0}


class _Exited:
    def __init__(self, rc):
        self.pid, self.rc = 11, rc

    def poll(self):
        return self.rc


@pytest.fixture
def record(monkeypatch):
    """A session of the program's record, made by hand: the spans a serve
    cell leaves, a row that held a chip and was killed, one that did not."""
    from ray_tpu.util import lifecycle

    monkeypatch.setattr(lifecycle, "_rows", [])
    monkeypatch.setattr(lifecycle, "_session", f"test_{uuid.uuid4().hex[:8]}")
    t = 1_000.0
    lifecycle.record("init", t, t + 1.25)
    lifecycle.record("serve_run", t + 2, t + 30.5)
    lifecycle.record("healthy_wait", t + 30, t + 30.5, parent="serve_run")
    lifecycle.record("serve_shutdown", t + 90, t + 90.25)
    lifecycle.record("shutdown", t + 91, t + 94.5)
    chip = lifecycle.add_row(lifecycle.ProcRow(
        _Exited(None), "chipworker", chips=[0], kind="actor",
        session=lifecycle.session()))
    chip.t_spawn, chip.t_main, chip.t_ready = t + 3, t + 5, t + 5.5
    chip.t_term, chip.t_kill = t + 91, t + 94
    chip.proc.rc = -9
    plain = lifecycle.add_row(lifecycle.ProcRow(
        _Exited(None), "proxy", kind="actor", session=lifecycle.session()))
    plain.t_spawn, plain.t_ready, plain.t_exit_asked = t + 2, t + 2.25, t + 91
    plain.proc.rc = 0
    lifecycle.close_shutdown()
    return lifecycle


@pytest.mark.parametrize("name", sorted(VALUES))
def test_reader_value(record, name, capsys):
    assert spec.load_reader(name)({"engine": ENGINE}) == pytest.approx(VALUES[name])


@pytest.mark.parametrize("name", sorted(VALUES))
def test_reader_finds_nothing_without_the_record(name, monkeypatch):
    """A tree from before the record (the parent commit: no such module),
    and a run of which the record holds nothing."""
    read = spec.load_reader(name)
    from ray_tpu.util import lifecycle

    monkeypatch.setattr(lifecycle, "_session", f"none_{uuid.uuid4().hex[:8]}")
    assert read({}) is None and read({"engine": {"decode_programs": [
        {"bucket": 1, "k": 1}]}}) is None
    monkeypatch.delitem(sys.modules, "ray_tpu.util.lifecycle")
    assert read({}) is None and read({"engine": {}}) is None


def test_a_train_cell_has_no_serve_shutdown_to_add(record):
    from ray_tpu.util import lifecycle

    lifecycle._session = f"train_{uuid.uuid4().hex[:8]}"
    lifecycle.record("shutdown", 10.0, 13.5)
    assert spec.load_reader("shutdown_s")({}) == pytest.approx(3.5)


def test_every_new_metric_is_an_entry_with_a_reader():
    bench = spec.load_benchmark()
    entries = {m["name"]: m for m in bench["per_layer"]}
    serve = [w["name"] for w in bench["workloads"] if "-serve-" in w["name"]]
    assert len(serve) == 5
    for name, unit in UNITS.items():
        m = entries[name]
        assert m["unit"] == unit and m["better"] == "lower"
        assert m["moves"] == "setup_s"
        assert m["layer"] == "Process and device ownership"
        assert m["source"] == ("program_counter" if unit == "count"
                               else "program_span")
        assert m.get("workloads") == (serve if name in SERVE_ONLY else None)
    assert [m["name"] for m in bench["per_layer"][-8:]] == [
        "runtime_init_s", "worker_boot_s", "replica_start_s",
        "serve_healthy_wait_s", "decode_programs_s", "shutdown_s",
        "workers_killed", "procs_left"]
    for w in bench["workloads"]:
        mine = {m["name"] for m in spec.Cell(w["name"]).per_layer}
        assert set(EVERY_CELL) <= mine
        assert (set(SERVE_ONLY) <= mine) == (w["name"] in serve)


# ---- the rehearsals: the whole flow on the CPU, traced ----------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_copy(str(tmp_path_factory.mktemp("bench-lifecycle")))


def _tagged_alive(tag: str):
    """Processes that inherited the command's environment and are alive.
    (A ``pgrep`` for the runtime's names would see other ``xdist`` workers'
    clusters.)"""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if tag.encode() in f.read():
                    with open(f"/proc/{pid}/cmdline", "rb") as c:
                        found.append((int(pid), c.read().replace(b"\0", b" ")))
        except OSError:  # gone meanwhile, or not ours to read
            continue
    return found


@pytest.mark.parametrize("cell, expected", [
    ("tiny-chat", SERVE_ONLY + EVERY_CELL),
    ("tiny-train", EVERY_CELL),
])
def test_traced_rehearsal_reports_the_lifecycle_metrics(root, cell, expected):
    tag = f"RT_TEST_LIFECYCLE_{uuid.uuid4().hex}"
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{tag: "1"},
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "5",
         "--seconds", "4", "--trace", "1", "--rehearse"], cwd=root, env=env,
        capture_output=True, text=True, timeout=420)
    left = _tagged_alive(tag)  # the instant the command returns
    assert done.returncode == 3, done.stderr[-3000:]
    assert not left, left
    line = json.loads(done.stdout.strip().splitlines()[-1])
    metrics = line["metrics"]
    for name in expected:
        assert name in metrics, (name, sorted(metrics))
        assert metrics[name]["unit"] == UNITS[name]
        assert metrics[name]["value"] >= 0.0
    for name in set(SERVE_ONLY) - set(expected):
        assert name not in metrics
    assert metrics["procs_left"]["value"] == 0
    assert metrics["workers_killed"]["value"] == 0  # asked, and they went
    assert "rt-shutdown" not in done.stderr
    assert 0.0 < metrics["runtime_init_s"]["value"] < 5.0
    assert 0.0 < metrics["worker_boot_s"]["value"] < 30.0
    if cell == "tiny-chat":
        # the note's ``replica`` times the same thing from outside
        note = next(ln for ln in done.stdout.splitlines() if "setup:" in ln)
        outside = float(note.split("replica ")[1].split(",")[0])
        inside = (metrics["replica_start_s"]["value"]
                  + metrics["serve_healthy_wait_s"]["value"])
        assert inside == pytest.approx(outside, abs=0.2)
        assert metrics["decode_programs_s"]["value"] > 0.0
