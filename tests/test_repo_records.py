"""The repository keeps one measurement system: ``BENCHMARK.json``,
``benchmark/`` and the driver's ledger.

PR 29 removed what stood beside it from before the chip (``bench.py``, three
scripts under ``ray_tpu/scripts/``, ``scripts/check_bench.py``, fifteen
``*_rNN.json`` records of CPU runs at the root, ``BASELINE.*``, ``ADVICE.md``,
``PARITY.md``). These tests keep it removed: no code, script, configuration
or README line names one of those files, and no record of a run lies at the
root again.

PR 46 removed what had never run on the chip from the files every cell runs
(speculative decoding and the streamed twin of ``generate``, the static
serving control and the load generators ``benchmark/lib`` replaced, the first
of four step instruments with its CLI and dashboard tab) and the rotation
that chose tier-1's slow gates by the date. Its names are read for in the
fenced directories too: it took them out of there.
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

# what PR 46 took away, by its public names
GONE_EVERYWHERE = {
    "step_profiler": (r"step_profiler|RT_STEP_PROFILER",
                      "from ray_tpu.util import step_profiler"),
    "rt profile": (r"\brt profile\b|scripts[./]profile\b",
                   "rt profile --preset debug --mode train"),
    "rt_step_*": (r"rt_step_", "rt_step_time_seconds"),
    "generate_speculative": (r"generate_speculative|_compiled_speculative",
                             "G.generate_speculative(params, draft, prompt)"),
    "generate_stream": (r"generate_stream", "generate.generate_stream("),
    "StaticLLM": (r"static_llm_app|StaticLLM", "serve.static_llm_app(...)"),
    "cb_vs_static_load": (r"cb_vs_static_load",
                          "from ray_tpu.serve.llm import cb_vs_static_load"),
    "poisson_load": (r"poisson_load|http_token_request",
                     "poisson_load(fire, rps=rps)"),
    "bench_fused_vs_host": (r"bench_fused_vs_host", "bench_fused_vs_host()"),
    "debug_draft": (r"debug_draft", 'llama.PRESETS["debug_draft"]'),
    "the slow rotation": (r"SLOW_ROTATION|slow_rotation",
                          "RT_SLOW_ROTATION_KEY"),
}
GONE.update(GONE_EVERYWHERE)

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
    ``*.ini`` and ``README.md`` of the tree outside ``EXCEPT``, the fenced
    directories among them."""
    not_the_tree = {".git"} | {p[:-1] for p in _ignored() if p.endswith("/")}
    out = []
    for where, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in not_the_tree]
        for name in files:
            if not (name.endswith((".py", ".sh", ".ini")) or name == "README.md"):
                continue
            rel = os.path.relpath(os.path.join(where, name), REPO)
            if rel in EXCEPT:
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
    everywhere = gone in GONE_EVERYWHERE
    hits = [f"{rel}:{n}: {line.strip()}" for rel, n, line in lines
            if (everywhere or not rel.startswith(FENCED))
            and pattern.search(line)]
    assert not hits, f"{gone} was deleted and is still named:\n" \
        + "\n".join(hits)


def test_the_read_reaches_the_tree(lines):
    """The walk above sees the files it is meant to guard, and the patterns
    match what they are meant to match (a guard that reads nothing passes)."""
    seen = {rel for rel, _, _ in lines}
    assert {"README.md", "pytest.ini", "chip_smoke.py",
            os.path.join("scripts", "chaos_smoke.sh"),
            os.path.join("ray_tpu", "scripts", "cli.py")} <= seen
    assert os.path.join("ray_tpu", "models", "generate.py") in seen
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
