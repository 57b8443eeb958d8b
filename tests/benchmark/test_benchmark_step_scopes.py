"""The train step's scopes and its compiled memory in the benchmark (PR 54):
the five readers of one scope of ``jit_steps`` each and the reader of the
launch record's ``step_memory`` on a hand-made run, on a run that has nothing
for them (the parent's program, another model's step), and their entries in
``BENCHMARK.json``, found BY NAME and never by position."""

import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402

MISTRAL, MIXTRAL, TRINITY, KIMI, EVABYTE = (
    "mistral7b-train-4k", "mixtral8x7b-train-4k-x4", "trinitylarge-train-8k",
    "kimilinear-train-16k", "evabyte-train-16k")
TRAIN_CELLS = [MISTRAL, MIXTRAL, TRINITY, KIMI, EVABYTE]

# reader: (unit, source, the scope of ``jit_steps`` it reads, its cells)
ENTRIES = {
    "full_attn_time_share": ("%", "device_trace", "attn_full",
                             [MISTRAL, MIXTRAL, TRINITY]),
    "dense_ffn_time_share": ("%", "device_trace", "mlp",
                             [MISTRAL, EVABYTE, TRINITY, KIMI]),
    "loss_head_time_share": ("%", "device_trace", "loss_head", TRAIN_CELLS),
    "optimizer_time_share": ("%", "device_trace", "optimizer", TRAIN_CELLS),
    "train_unscoped_share": ("%", "device_trace", "other", TRAIN_CELLS),
    "step_compiled_peak_gib": ("GiB", "program_counter", None, TRAIN_CELLS)}
SHARES = sorted(name for name, e in ENTRIES.items() if e[2])

# four seconds of a dense step, every operation of it under a scope but a
# twentieth; a collective that completes no scoped product beside them
RUN = {"trace": {"busy_s": 4.0, "by_scope": {
    "jit_steps/attn_full": 1.0, "jit_steps/mlp": 2.0,
    "jit_steps/loss_head": 0.3, "jit_steps/optimizer": 0.4,
    "jit_steps/embed": 0.1, "jit_steps/other": 0.2,
    "jit_steps/other/collective": 0.5, "jit_rt_decode/other": 3.0}}}
READS = {"full_attn_time_share": 25.0, "dense_ffn_time_share": 50.0,
         "loss_head_time_share": 7.5, "optimizer_time_share": 10.0,
         "train_unscoped_share": 5.0}


@pytest.mark.parametrize("metric", SHARES)
def test_a_share_reads_its_scope_of_the_step(metric):
    read = spec.load_reader(metric)
    assert read(RUN) == pytest.approx(READS[metric])
    # no trace (an untraced run), a trace with no device time
    assert read({}) is None and read({"trace": None}) is None
    assert read({"trace": {"busy_s": 0.0, "by_scope": {}}}) is None
    # a served program's trace: no train step in it, nothing is said
    served = {"trace": {"busy_s": 4.0, "by_scope": {"jit_rt_decode/mlp": 3.0,
                                                    "jit_rt_decode/other": 1.0}}}
    assert read(served) is None


@pytest.mark.parametrize("metric", sorted(set(SHARES) - {"train_unscoped_share"}))
def test_a_step_without_the_scope_says_nothing(metric):
    """The parent's step, whose training blocks name nothing: all of it is
    ``other``, and a reader of a scope finds none and does not raise."""
    bare = {"trace": {"busy_s": 4.0, "by_scope": {"jit_steps/other": 3.6,
                                                  "jit_steps/flash_fwd": 0.4}}}
    assert spec.load_reader(metric)(bare) is None
    assert spec.load_reader("train_unscoped_share")(bare) == pytest.approx(90.0)


def test_a_step_named_through_and_through_reads_zero():
    named = {"trace": {"busy_s": 2.0, "by_scope": {"jit_steps/mlp": 2.0}}}
    assert spec.load_reader("train_unscoped_share")(named) == 0.0


@pytest.fixture
def recorded(monkeypatch):
    """The program's lifecycle record as a reader finds it."""
    def with_span(span):
        monkeypatch.setitem(sys.modules, "ray_tpu.util.lifecycle",
                            types.SimpleNamespace(
                                last=lambda name: span if name == "train_launches"
                                else None))
    return with_span


def test_the_compiled_peak_reads_the_launch_record(recorded):
    read = spec.load_reader("step_compiled_peak_gib")
    span = {"launches": 3, "steps": 12, "per_launch": [{}, {}, {}],
            "step_memory": {"peak_bytes": 15_794_000_000,
                            "temp_bytes": 9_830_000_000,
                            "argument_bytes": 5_110_000_000,
                            "output_bytes": 5_110_000_000,
                            "alias_bytes": 5_110_000_000}}
    recorded(span)
    assert read(RUN) == pytest.approx(15_794_000_000 / 2 ** 30)
    assert read({}) == pytest.approx(14.709, abs=1e-3)   # no trace needed
    # the parent's record has no such key; a backend that gives no account
    # leaves it out; a process that kept no record at all
    recorded({k: v for k, v in span.items() if k != "step_memory"})
    assert read(RUN) is None
    recorded({**span, "step_memory": {}})
    assert read(RUN) is None
    recorded(None)
    assert read(RUN) is None


def test_no_record_module_no_number(monkeypatch):
    monkeypatch.delitem(sys.modules, "ray_tpu.util.lifecycle", raising=False)
    assert spec.load_reader("step_compiled_peak_gib")(RUN) is None


@pytest.mark.parametrize("metric", sorted(ENTRIES))
def test_the_entry_is_what_the_reader_is(metric):
    unit, source, scope, cells = ENTRIES[metric]
    found = [m for m in spec.load_benchmark()["per_layer"]
             if m["name"] == metric]
    assert len(found) == 1, metric
    assert found[0] == {"name": metric, "unit": unit, "better": "lower",
                        "source": source, "layer": "Step program",
                        "moves": "train_tok_s_chip", "workloads": cells}
    path = os.path.join(lib.REPO, "benchmark", "layer_metrics", metric + ".py")
    with open(path) as f:
        text = f.read()
    assert (f'"{scope}"' in text or f"jit_steps/{scope}" in text) if scope \
        else "step_memory" in text


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_a_train_cell_reports_the_readers_listed_for_it(cell):
    names = {m["name"] for m in spec.Cell(cell).per_layer}
    mine = {name for name, e in ENTRIES.items() if cell in e[3]}
    assert names & set(ENTRIES) == mine
    assert {"loss_head_time_share", "optimizer_time_share",
            "train_unscoped_share", "step_compiled_peak_gib"} <= mine


def test_no_served_cell_reports_them():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        if w["name"] not in TRAIN_CELLS:
            names = {m["name"] for m in spec.metrics_of(bench, "per_layer",
                                                        w["name"])}
            assert not names & set(ENTRIES), w["name"]
    # none of the six is an entry of one cell alone
    assert all(len(ENTRIES[n][3]) >= 3 for n in ENTRIES)
