"""The repository keeps one measurement system: ``BENCHMARK.json``,
``benchmark/`` and the driver's ledger.

PR 29 removed what stood beside it from before the chip (``bench.py``, three
scripts under ``ray_tpu/scripts/``, ``scripts/check_bench.py``, fifteen
``*_rNN.json`` records of CPU runs at the root, ``BASELINE.*``, ``ADVICE.md``,
``PARITY.md``). These tests keep it removed: no code, script, configuration
or README line names one of those files, and no record of a run lies at the
root again.
"""

import fnmatch
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what went: the pattern that names it, and a line that did
GONE = {
    "bench.py": (r"(?<!\w)bench\.py\b", "python bench.py --kv"),
    "kv_bench.py": (r"kv_bench", "ray_tpu/scripts/kv_bench.py"),
    "stream_bench.py": (r"stream_bench", "ray_tpu.scripts.stream_bench"),
    "scale_envelope.py": (r"scale[_-]envelope", "rt scale-envelope"),
    "check_bench.py": (r"check_bench", "scripts/check_bench.py"),
    "test_zz_bench_trajectory.py": (r"bench_trajectory",
                                    "tests/test_zz_bench_trajectory.py"),
    "BASELINE.md, BASELINE.json": (r"BASELINE\.(md|json)", "see BASELINE.md"),
    "ADVICE.md": (r"ADVICE\.md", "ADVICE.md"),
    "PARITY.md": (r"PARITY\.md", "PARITY.md"),
    "*_rNN.json": (r"\b(BENCH|BENCH_KV|BENCH_STREAM|ENGINE|MULTICHIP|RLHF|SCALE"
                   r"|TRAIN)_r\d\d", "MULTICHIP_r06.json"),
}

# Where a name may still stand. The directories a benchmark cell runs were
# fenced off from PR 29, comments included; their stale mentions are a debt
# listed in ROADMAP.md (Queue 3, D13). CHANGES.md, PERF.md and ROADMAP.md are
# history and are not read here at all. This file names what it guards.
FENCED = tuple(os.path.join("ray_tpu", d) + os.sep for d in (
    "train", "parallel", "data", "models", "ops", "serve", "cluster", "core",
    "_private", "collective", "util")) + ("benchmark" + os.sep,
                                          os.path.join("tests", "benchmark") + os.sep)
EXCEPT = (os.path.join("tests", "test_repo_records.py"),)


def _ignored():
    """The patterns of ``.gitignore``: what building, testing and running
    leave behind, and what the driver lays beside the checkout."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        return [ln.strip() for ln in f if ln.strip()]


def _read_lines():
    """(relative path, line number, line) of every ``*.py``, ``*.sh``,
    ``*.ini`` and ``README.md`` of the tree outside the exceptions."""
    not_the_tree = {".git"} | {p[:-1] for p in _ignored() if p.endswith("/")}
    out = []
    for where, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in not_the_tree]
        for name in files:
            if not (name.endswith((".py", ".sh", ".ini")) or name == "README.md"):
                continue
            rel = os.path.relpath(os.path.join(where, name), REPO)
            if rel.startswith(FENCED) or rel in EXCEPT:
                continue
            with open(os.path.join(REPO, rel), errors="replace") as f:
                out += [(rel, n, line) for n, line in enumerate(f, 1)]
    return out


@pytest.fixture(scope="module")
def lines():
    return _read_lines()


@pytest.mark.parametrize("gone", GONE)
def test_no_line_names_a_deleted_file(lines, gone):
    pattern = re.compile(GONE[gone][0])
    hits = [f"{rel}:{n}: {line.strip()}" for rel, n, line in lines
            if pattern.search(line)]
    assert not hits, f"{gone} was deleted by PR 29 and is still named:\n" \
        + "\n".join(hits)


def test_the_read_reaches_the_tree(lines):
    """The walk above sees the files it is meant to guard, and the patterns
    match what they are meant to match (a guard that reads nothing passes)."""
    seen = {rel for rel, _, _ in lines}
    assert {"README.md", "pytest.ini", "chip_smoke.py",
            os.path.join("scripts", "chaos_smoke.sh"),
            os.path.join("ray_tpu", "scripts", "cli.py")} <= seen
    assert not any(rel.startswith(FENCED) for rel in seen)
    for pattern, example in GONE.values():
        assert re.search(pattern, example), (pattern, example)
    assert not re.search(GONE["bench.py"][0], "ray_tpu/scripts/microbench.py")
    assert not re.search(GONE["*_rNN.json"][0], "RT_TRAIN_recorder")


def test_the_only_record_at_the_root_is_the_benchmark():
    """``BENCHMARK.json`` declares the cells; a run's numbers go to the
    driver's ledger (``PERF_LEDGER.jsonl``), not to a file at the root."""
    records = sorted(f for f in os.listdir(REPO) if f.endswith(".json")
                     and not any(fnmatch.fnmatch(f, pat) for pat in _ignored()))
    assert records == ["BENCHMARK.json"]
