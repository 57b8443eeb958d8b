"""BENCHMARK.json and the files it names: cells resolve by name, bad names
and units are refused, configuration files keep their sources' widths, and
a cell, a configuration, a mix and a per-layer metric are each added by new
files and entries alone."""

import json
import os
import re
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import arithmetic, results, spec  # noqa: E402

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

# the published config.json of each source, as far as it fixes a width
PUBLISHED = {
    "mistral-7b-v0.3": {
        "vocab_size": 32768, "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_hidden_layers": 32, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": False, "sliding_window": None,
        "max_position_embeddings": 32768},
    "mixtral-8x7b-v0.1": {
        "vocab_size": 32000, "hidden_size": 4096, "intermediate_size": 14336,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_hidden_layers": 32, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "num_local_experts": 8, "num_experts_per_tok": 2,
        "router_aux_loss_coef": 0.02, "tie_word_embeddings": False,
        "sliding_window": None, "max_position_embeddings": 32768},
}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = spec.Cell(name)
    assert cell.config["name"] == cell.workload["config"]
    assert cell.traffic["driver"] in ("serve_open", "serve_closed", "train")
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer and set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert all(m["moves"] in e2e for m in cell.per_layer)
    assert 1 <= cell.n_layers() < cell.config["config"]["num_hidden_layers"]


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_configuration_keeps_its_sources_widths(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(lib.REPO, entry["file"])) as f:
        cfg = json.load(f)
    for key, value in PUBLISHED[name].items():
        assert cfg["config"][key] == value, key
    assert cfg["source"] == entry["source"] and cfg["source"].startswith("https://")
    assert list(cfg["reduced"]) == entry["reduced"] == ["num_hidden_layers"]
    assert cfg["assumed"] and cfg["deployment"]
    assert arithmetic.head_dim(cfg["config"]) == 128


def test_arithmetic_at_the_published_widths():
    dense, moe = spec.load_family("dense"), spec.load_family("moe")
    mistral = PUBLISHED["mistral-7b-v0.3"]
    assert dense.matmul_params(mistral, 1) == 218_103_808
    assert arithmetic.total_params(dense, mistral, 32) == 7_248_023_552  # "7.25B"
    mixtral = PUBLISHED["mixtral-8x7b-v0.1"]
    assert arithmetic.total_params(moe, mixtral, 32) == 46_702_792_704  # "46.7B"
    assert moe.matmul_params(mixtral, 1) == 394_297_344   # 2 of 8
    # 6 per matmul parameter and 6*s*heads*head_dim of causal attention
    assert arithmetic.train_flops_per_token(dense, mistral, 6, 4096) == pytest.approx(
        6 * (6 * 218_103_808 + 4096 * 32768) + 6 * 6 * 4096 * 4096)
    assert dense.cache_bytes_per_position(mistral, 16) == 65536
    with pytest.raises(KeyError):
        arithmetic.peaks("cpu")


# what the parent commit's arithmetic.py gave (PR 24), at the cells' depths:
# train_flops_per_token at s4096, weight_bytes, kv_bytes_per_position,
# total_params
@pytest.mark.parametrize("name,depth,flops,weights,cache,params", [
    ("mistral-7b-v0.3", 15, 21944598528.0, 6811803648, 61440, 3540119552),
    ("mistral-7b-v0.3", 6, 9261023232.0, 2885787648, 24576, 1577111552),
    ("mixtral-8x7b-v0.1", 3, 8185774080.0, 8969773056, 12288, 4615958528),
])
def test_the_moved_arithmetic_gives_what_it_gave(name, depth, flops, weights,
                                                 cache, params):
    cfg = spec.Cell(next(w["name"] for w in BENCH["workloads"]
                         if w["config"] == name)).config
    family, hf = spec.load_family(cfg["family"]), cfg["config"]
    assert depth in cfg["reduced"]["num_hidden_layers"].values()
    assert arithmetic.train_flops_per_token(family, hf, depth, 4096) == flops
    assert arithmetic.weight_bytes(family, hf, depth) == weights
    assert family.cache_bytes_per_position(hf, depth) == cache
    assert arithmetic.total_params(family, hf, depth) == params


def test_the_library_knows_no_architecture():
    """Nothing under ``benchmark/lib``, nor the two entry points, tells one
    family from another: a family's name, its configuration keys and the
    program's model modules appear in ``benchmark/families/`` alone. The one
    exception is ``served.py``'s way through ``ContinuousLLM``'s constructor,
    which only a program change can remove."""
    files = [os.path.join("benchmark", f) for f in ("run.py", "sweep.py")]
    files += [os.path.join("benchmark", "lib", f)
              for f in sorted(os.listdir(os.path.join(lib.REPO, "benchmark", "lib")))
              if f.endswith(".py")]
    banned = re.compile(r"num_local_experts|num_experts_per_tok|capacity_factor"
                        r"|intermediate_size|models import moe|models\.moe"
                        r"|[\"']dense[\"']|[\"']moe[\"']")
    for rel in files:
        with open(os.path.join(lib.REPO, rel)) as f:
            text = f.read()
        assert not banned.search(text), (rel, banned.search(text).group(0))
        if not rel.endswith("served.py"):
            assert "llama" not in text, rel
    assert sorted(os.listdir(os.path.join(lib.REPO, "benchmark", "families"))) \
        >= ["dense.py", "moe.py"]
    assert "flash" in spec.load_kernels()


def test_contract_shapes():
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert re.fullmatch(r".*_roofline", m["name"]) is None or m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200


def _edited(tmp_path, edit):
    root = lib.make_copy(str(tmp_path))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    edit(bench)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.mark.parametrize("edit,says", [
    (lambda b: b["workloads"][0].update(name="has space"), "a name is"),
    (lambda b: b["workloads"][0].update(name="a/b"), "a name is"),
    (lambda b: b["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda b: b["end_to_end"][0].update(unit="µs"), "unit"),
    (lambda b: b["per_layer"][0].update(moves="nothing"), "no end-to-end"),
    (lambda b: b["per_layer"][0].update(source="guess"), "source"),
    (lambda b: b["workloads"][0].update(config="absent"), "no configuration"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0], name="twin")),
     "two workloads"),
    (lambda b: [w.update(chips=4) for w in b["workloads"][:4]], "ask for 4 chips"),
    (lambda b: b["end_to_end"].pop(), "setup_s"),
], ids=["space", "slash", "unit-words", "unit-greek", "moves", "source",
        "config", "pair-twice", "four-chip-quota", "no-setup"])
def test_a_bad_benchmark_is_refused(tmp_path, edit, says):
    root = _edited(tmp_path, edit)
    with pytest.raises(spec.SpecError, match=says):
        spec.load_benchmark(root)


def test_a_bad_cell_name_or_a_missing_file_is_refused(tmp_path):
    root = lib.make_copy(str(tmp_path))
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.Cell("absent", root)
    with pytest.raises(spec.SpecError, match="no such file"):
        spec.Cell("tiny-filler-0", root)  # its traffic file was never written
    with pytest.raises(spec.SpecError, match="has no file"):
        spec.load_reader("absent_metric", root)
    with pytest.raises(spec.SpecError, match="has no file"):
        spec.load_family("absent-family", root)


def test_adding_an_architecture_a_cell_a_mix_and_a_metric_is_adding_files(tmp_path):
    """``make_copy`` adds three configurations, a family, a kernel, five
    mixes and eleven cells by writing new files and appending entries. Here
    a per-layer metric joins them the same way. No file that was there is
    touched, and BENCHMARK.json only gained: every list of the original is
    the beginning of the copy's."""
    before = {}
    for d, _, files in os.walk(os.path.join(lib.REPO, "benchmark")):
        for f in files:
            if not f.endswith(".pyc"):
                with open(os.path.join(d, f), "rb") as fh:
                    before[os.path.relpath(os.path.join(d, f), lib.REPO)] = fh.read()

    def add_metric(bench):
        bench["per_layer"].append({
            "name": "tokens_per_launch", "unit": "tokens", "better": "higher",
            "source": "program_counter", "layer": "Train driver",
            "moves": "train_tok_s_chip", "workloads": ["tiny-train"]})

    root = _edited(tmp_path, add_metric)
    with open(os.path.join(root, "benchmark/layer_metrics/tokens_per_launch.py"), "w") as f:
        f.write("def read(run):\n"
                "    t = run.get('train')\n"
                "    return t['tokens'] / t['launches'] if t else None\n")
    for rel, data in before.items():
        with open(os.path.join(root, rel), "rb") as fh:
            assert fh.read() == data, rel
    added = {os.path.relpath(os.path.join(d, f), root)
             for d, _, files in os.walk(os.path.join(root, "benchmark"))
             for f in files if not f.endswith(".pyc")} - set(before)
    assert {"benchmark/families/tiny-third.py", "benchmark/kernels/tiny-matmul.py",
            "benchmark/traffic/tiny-bursty.json",
            "benchmark/configs/tiny-third.json"} <= added

    with open(os.path.join(lib.REPO, "BENCHMARK.json")) as f:
        old = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        new = json.load(f)
    assert set(new) == set(old)
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[key]) >= len(old[key])
        for was, now in zip(old[key], new[key]):  # the original is a prefix
            assert set(now) == set(was)
            for field, value in was.items():
                if field == "workloads":
                    assert now[field][:len(value)] == value
                else:
                    assert now[field] == value, (key, was["name"], field)

    cell = spec.Cell("tiny-train", root)
    assert cell.config["name"] == "tiny-dense" and cell.n_layers() == 2
    assert "tokens_per_launch" in cell.readers
    run = {"train": {"tokens": 1024, "launches": 4, "span_s": 2.0, "seq": 64},
           "device": {"platform": "cpu", "kind": "cpu", "count": 1},
           "window_compiles": 0, "compile_s_at_window": 1.5}
    values = spec.layer_values(cell, run)
    assert values["tokens_per_launch"] == {"value": 256.0, "unit": "tokens"}
    assert values["compile_s"]["value"] == 1.5
    assert "mfu" not in values and "flash_roofline" not in values  # no chip
    assert "tokens_per_launch" not in spec.Cell("tiny-moe-x4", root).readers
    assert spec.Cell("tiny-moe-x4", root).chips == 4
    assert results.end_to_end_value("train_tok_s_chip", cell, run) == 512.0

    # the third family is the file of that name in the copy, and its
    # arithmetic is its own: the dense family cannot read its keys
    third = spec.Cell("tiny-third-train", root)
    assert third.family.__file__ == os.path.join(
        root, "benchmark/families/tiny-third.py")
    assert spec.Cell("tiny-moe-decode", root).family.__file__.startswith(root)
    hf = third.config["config"]
    assert third.family.matmul_params(hf, 2) == spec.load_family("dense").matmul_params(
        lib.TINY, 2)
    with pytest.raises(KeyError):
        spec.load_family("dense").matmul_params(hf, 2)
    tpu = {**run, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           "cell": {"config": third.config, "n_layers": 2, "family": third.family}}
    mfu = spec.load_reader("mfu", root)(tpu)
    assert mfu == pytest.approx(100.0 * 512.0 / 197e12 * arithmetic.train_flops_per_token(
        third.family, hf, 2, 64))


def test_percentile_metrics_are_read_by_name():
    cell = spec.Cell("mistral7b-serve-chat")
    run = {"client": {"ttft_ms": [float(i) for i in range(1, 101)],
                      "tpot_ms": [10.0, 20.0, 30.0, 40.0]}, "setup_s": 3.0}
    assert results.end_to_end_value("ttft_p90_ms", cell, run) == 90.0
    assert results.end_to_end_value("ttft_p75_ms", cell, run) == 75.0
    assert results.end_to_end_value("tpot_p50_ms", cell, run) == 20.0
    assert results.end_to_end_value("setup_s", cell, run) == 3.0
    assert results.end_to_end_value("out_tok_s", cell, run) is None
