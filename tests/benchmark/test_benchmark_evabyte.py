"""The multibyte_eva family (EvaByte) in the benchmark: the configuration
file's three copies of the published keys held to the catalog's row, its
arithmetic by hand, the cell and its mix, a tiny configuration of the family
rehearsed on the CPU through ``benchmark/run.py`` from a ``make_copy`` copy
(new files and entries only), the cell's three readers on a hand-made run and
on a run that has nothing for them, the cost file on a hand count, and the
chip check at a tiny size. Every entry this PR added is found BY NAME and by
membership, never by position."""

import json
import os
import subprocess
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import arithmetic, spec  # noqa: E402

CELL = "evabyte-train-16k"
CONFIG = "evabyte"
FAMILY = "multibyte_eva"
MIX = "pretrain-16k-bytes"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 4}
READERS = ("eva_attn_time_share", "eva_attn_roofline", "eva_tile_waste")

TINY = {"attention_class": "eva", "chunk_size": 4, "window_size": 32,
        "fp32_skip_add": True, "norm_add_unit_offset": True, "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 2, "num_pred_heads": 8,
        "rms_norm_eps": 1e-5, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320}
TINY_CELL = "tiny-evabyte-train"


@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


@pytest.fixture(scope="module")
def family():
    return spec.load_family(FAMILY)


def _entry(kind, name):
    found = [e for e in spec.load_benchmark()[kind] if e["name"] == name]
    assert len(found) == 1, (kind, name)
    return found[0]


# ---- the configuration file ------------------------------------------------------

def test_the_file_holds_the_published_keys_three_times(cfg):
    """``published`` verbatim; ``config`` as the harness and the family read
    it; the top level as run for the driver's check of a catalogued file.
    The copies differ in the depth alone."""
    published, run = cfg["published"], cfg["config"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert key in cfg and key in run, key
        assert run[key] == value and type(run[key]) is type(value), key
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] < value, key
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert set(run) == set(published)
    depth = cfg["reduced"]["num_hidden_layers"]
    assert (depth["train"], depth["published"]) == (4, 32) and depth["why"]
    assert "serve" not in depth          # no served block computes EVA
    assert "pipeline stages" in depth["why"] and "pipeline stages" in cfg["deployment"]
    for key in ("modeling_file", "pooling", "visibility", "prediction_heads",
                "initialisation", "mixedp_attn", "fp32_skip_add", "head_dim",
                "rope", "max_seq_length", "state_dtypes"):
        assert cfg["assumed"][key], key
    assert cfg["family"] == FAMILY and cfg["name"] == CONFIG


def test_published_is_the_catalogs_row(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "EvaByte" in line]
    row = next(r for r in rows if r["name"] == "EvaByte")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    entry = _entry("configs", CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["file"] == "benchmark/configs/evabyte.json"
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert len(entry["why"]) <= 200


def test_every_width_stands_as_published(cfg):
    run = cfg["config"]
    assert (run["hidden_size"], run["intermediate_size"], run["vocab_size"]) \
        == (4096, 11008, 320)
    assert (run["num_attention_heads"], run["num_key_value_heads"]) == (32, 32)
    assert (run["window_size"], run["chunk_size"], run["num_pred_heads"]) \
        == (2048, 16, 8)
    assert run["attention_class"] == "eva" and run["rope_theta"] == 100000
    assert run["norm_add_unit_offset"] and run["fp32_skip_add"]
    assert arithmetic.head_dim(run) == 128


def test_the_cut_by_hand(cfg, family):
    """821.3M parameters; 5.21 GFLOP a token at s 16384, of which the
    visible pairs are 0.29."""
    run, d, f = cfg["config"], 4096, 11008
    layer = 4 * d * d + 3 * d * f
    assert layer == 202_375_168
    extra_heads = 7 * d * 320
    assert family.matmul_params(run, 4, active_only=False) \
        == family.matmul_params(run, 4, active_only=True) \
        == 4 * layer + extra_heads == 818_675_712
    assert arithmetic.total_params(family, run, 4) \
        == 4 * layer + 320 * d + 8 * 320 * d + d + 4 * 2 * d == 821_334_016
    # eight windows: a causal half each, and 128 summaries a window before
    local, pooled = 8 * 2048 * 2049 // 2, 128 * 2048 * 28
    assert family.visible_pairs(16384, 2048, 16) == (local, pooled) \
        == (16_785_408, 7_340_032)
    madds = 4 * 4096 * (2 * (local + pooled) / 16384 + 2)
    assert family.attention_flops_per_token(run, 4, 16384) == madds \
        == 48_283_648
    assert arithmetic.train_flops_per_token(family, run, 4, 16384) \
        == 6 * (4 * layer + 8 * 320 * d) + 6 * madds == 5_209_620_480
    # a part-full last window: its queries see the whole windows before
    assert family.visible_pairs(5120, 2048, 16) == (
        2 * 2048 * 2049 // 2 + 1024 * 1025 // 2, 128 * (2048 + 2 * 1024))
    # a position of context before the window: a sixteenth of a summary
    assert family.cache_bytes_per_position(run, 4) == 4 * 2 * 4096 * 2 // 16


def test_the_programs_config_is_the_files(cfg, family):
    family.require_program()
    c = family.program_config(cfg, 4, max_seq_len=16384, attn_impl="flash",
                              loss_chunk=256)
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.d_ff, c.vocab_size,
            c.head_dim) == (4096, 32, 32, 11008, 320, 128)
    assert (c.attn_kind, c.eva_window, c.eva_chunk, c.n_pred_heads) \
        == ("eva", 2048, 16, 8)
    assert c.norm_unit_offset and c.residual_f32 and not c.tie_embeddings
    assert c.rope_theta == 100000.0 and c.norm_eps == 1e-5
    # what the harness counts, and beside it phi and mu
    assert c.num_params() == 821_334_016 + 4 * 2 * 32 * 128
    bad = {**cfg, "config": {**cfg["config"], "attention_class": "softmax"}}
    with pytest.raises(spec.SpecError, match="attention_class"):
        family.program_config(bad, 4, max_seq_len=16384)


def test_the_cell_and_its_mix():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.n_layers() == 4 and cell.phase == "train"
    assert cell.workload["config"] == CONFIG and cell.workload["traffic"] == MIX
    mix, like = cell.traffic, spec.Cell("kimilinear-train-16k").traffic
    assert sorted(mix) == sorted(like)         # pretrain-16k's keys
    assert (mix["batch"], mix["seq"], mix["steps_per_launch"]) == (1, 16384, 1)
    for key in ("driver", "attn_impl", "loss_chunk", "lr", "mesh",
                "warmup_launches", "max_launches_per_s", "data", "trace"):
        assert mix[key] == like[key], key
    assert mix["data"] == {"zipf_a": 1.1, "span_len": 64, "spans_per_row": 16}
    assert 0 < mix["loss_rel_tol"] <= 1e-3 and mix["loss_rel_tol_why"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"mfu", "data_wait_share", "launch_gap_share",
                     "train_device_idle_share", *READERS}
    # the local part does not run through flash.py, and nothing is sharded
    assert not names & {"flash_time_share", "flash_roofline",
                        "flash_band_roofline", "collective_exposed_share",
                        "kda_time_share", "moe_ffn_time_share"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_s_chip", "setup_s"}
    for name in READERS:
        m = _entry("per_layer", name)
        assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip"
    assert _entry("per_layer", "eva_attn_time_share")["layer"] == "Step program"
    assert _entry("per_layer", "eva_attn_roofline")["layer"] == "Kernels"
    assert (_entry("per_layer", "eva_tile_waste")["source"],
            _entry("per_layer", "eva_tile_waste")["unit"]) \
        == ("program_counter", "ratio")
    for name in ("eva_attn_time_share", "eva_attn_roofline"):
        assert (_entry("per_layer", name)["source"],
                _entry("per_layer", name)["unit"]) == ("device_trace", "%")
    for kind, name in (("end_to_end", "train_tok_s_chip"),
                       ("per_layer", "data_wait_share"),
                       ("per_layer", "launch_gap_share"), ("per_layer", "mfu"),
                       ("per_layer", "train_device_idle_share")):
        assert CELL in _entry(kind, name)["workloads"], name
    entry = _entry("workloads", CELL)
    assert len(entry["why"]) <= 200 and entry["chips"] == 1


def test_the_new_files_are_found_by_name():
    kernels = spec.load_kernels()
    assert "eva_attn" in kernels
    names = sorted(f for f in os.listdir(os.path.join(
        lib.REPO, "benchmark", "families")) if f.endswith(".py"))
    assert names[:2] == ["dense.py", "moe.py"] and FAMILY + ".py" in names
    for name in READERS:
        assert callable(spec.load_reader(name))
    assert os.path.exists(os.path.join(lib.REPO, "benchmark", "traffic",
                                       MIX + ".json"))


def test_the_reference_imports_nothing_of_the_program():
    """Nothing of ``ray_tpu`` outside ``require_program`` (which reads a
    source file), ``program_config`` and ``init_params`` (which hand the
    program its own config and weights)."""
    with open(os.path.join(lib.REPO, "benchmark", "families",
                           FAMILY + ".py")) as f:
        text = f.read()
    reference = text[text.index("# ---- the plain reference"):]
    assert "ray_tpu" not in reference.replace("``ray_tpu", "")
    assert "ops.eva" not in reference and "import ray_tpu" not in reference


# ---- a tiny configuration of the family, rehearsed ------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-evabyte")))
    path = "benchmark/configs/tiny-evabyte.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-evabyte", "family": FAMILY, "source": "test",
                   "config": TINY, "reduced": {}, "assumed": {}}, f)
    with open(os.path.join(root, "benchmark/traffic/tiny-train-eva.json"), "w") as f:
        json.dump({**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                   "loss_chunk": 32, "steps_per_launch": 1, "seq": 128,
                   "loss_rel_tol": 1e-3}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-evabyte", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-evabyte",
                               "traffic": "tiny-train-eva", "chips": 1,
                               "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(TINY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_cell_trains_and_agrees_with_its_reference(root):
    """A traced rehearsal: the program's first loss (bf16, the kernels in
    interpret mode) within the mix's limit of the family's reference's,
    through the same driver as the cell."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", TINY_CELL, "--seed",
         "4000000007", "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["why_not_correct"] == [
        "ran on cpu x" + str(line["device"]["count"]) + ", not on 1 TPU chip(s)"]
    assert {"data_wait_share", "launch_gap_share", "eva_tile_waste"} <= set(
        line["metrics"])
    assert line["metrics"]["eva_tile_waste"] == {"value": 1.0, "unit": "ratio"}
    # no device plane in a CPU trace: the trace's readers say nothing
    assert not {"eva_attn_time_share", "eva_attn_roofline", "mfu"} & set(
        line["metrics"])


def test_a_program_without_the_kind_fails_the_cell_at_once(root, tmp_path,
                                                           monkeypatch):
    """On the parent of PR 52 loading the cell raises in the parent process,
    in seconds, before a trainer is started: the cell's new readers ask the
    family as they are imported."""
    import ray_tpu

    family = spec.load_family(FAMILY, root)
    family.require_program()  # this checkout's program has the kind
    old = tmp_path / "ray_tpu"
    (old / "models").mkdir(parents=True)
    (old / "models" / "llama.py").write_text(
        "def attention_half(cfg, x, layer):\n    return x\n")
    monkeypatch.setattr(ray_tpu, "__file__", str(old / "__init__.py"))
    with pytest.raises(spec.SpecError, match="cannot run it"):
        family.require_program()
    with pytest.raises(spec.SpecError, match="mixer kind 'eva'"):
        spec.Cell(TINY_CELL, root)
    spec.Cell("tiny-train", root)  # the other cells load as before


# ---- the readers -----------------------------------------------------------------

RUN = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
       "trace": {"busy_s": 4.0, "window_s": 4.0, "by_scope": {
           "jit_steps/attn_eva": 0.6, "jit_steps/mlp": 2.8,
           "jit_steps/loss_head": 0.2, "jit_steps/other": 0.4}},
       "train": {"seq": 16384, "batch": 1, "steps": 50, "span_s": 50.0}}


def test_the_time_share_reads_its_scope():
    read = spec.load_reader("eva_attn_time_share")
    assert read(RUN) == pytest.approx(15.0)
    # the parent's run, or another model's: no such scope; nothing is said
    bare = {**RUN, "trace": {"busy_s": 4.0, "by_scope": {"jit_steps/other": 4.0}}}
    assert read(bare) is None
    assert read({"device": RUN["device"]}) is None
    assert read({**RUN, "trace": None}) is None


def test_the_tile_waste_reads_the_noted_plan(monkeypatch):
    from benchmark.lib import launch_record

    read = spec.load_reader("eva_tile_waste")
    record = {"launches": 3, "per_launch": [{}, {}, {}],
              "eva_plan": {"tiles_visited": 160, "tiles_needed": 80}}
    monkeypatch.setattr(launch_record, "totals", lambda: record)
    assert read(RUN) == 2.0
    # a program that notes no plan (the parent's), or keeps no record
    monkeypatch.setattr(launch_record, "totals",
                        lambda: {"launches": 3, "per_launch": [{}, {}, {}]})
    assert read(RUN) is None
    monkeypatch.setattr(launch_record, "totals", lambda: None)
    assert read(RUN) is None


def _call(kind, result, prefix="", suffix=".7"):
    return (f"%{prefix}eva_attn_{kind}_bh32_s4096_d128_w2048_c16{suffix} = "
            f"{result} custom-call(%constant.6, %copy.1, %copy.2, %copy.3), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


_Q = "bf16[32,4096,128]{2,1,0:T(8,128)(2,1)}"
_S = "bf16[32,128,128]{2,1,0:T(8,128)(2,1)}"
FWD = _call("fwd", f"({_Q}, f32[32,4096,1]{{2,1,0:T(8,128)}})")
DQ = _call("dq", _Q, prefix="transpose_jvp_", suffix="__.3")
DKV = _call("dkv", f"({_Q}, {_Q})", suffix=".3")
DSUM = _call("dsum", f"({_S}, {_S})")


def test_the_kernels_cost_against_a_hand_count_at_s4096():
    """Two windows: 2 x 2048 x 2049 / 2 local pairs, and the second
    window's 2,048 queries against the first's 128 summaries."""
    k = spec.load_kernels()["eva_attn"]
    local, pooled = 2 * 2048 * 2049 // 2, 2048 * 128
    assert k.visible_pairs(4096, 2048, 16) == (local, pooled) \
        == (4_196_352, 262_144)
    assert k.call_shape(FWD) == ("fwd", 32, 4096, 128, 2048, 16, 2)
    s, d, seen = 4096, 128, 128
    assert k.match(FWD) == (2 * 2.0 * d * 32 * (local + pooled),
                            32.0 * ((4 * s + 2 * seen) * d * 2 + s * 4))
    assert k.match(DQ) == (3 * 2.0 * d * 32 * (local + pooled),
                           32.0 * ((5 * s + 2 * seen) * d * 2 + 2 * s * 4))
    assert k.match(DKV) == (4 * 2.0 * d * 32 * local,
                            32.0 * (6 * s * d * 2 + 2 * s * 4))
    assert k.match(DSUM) == (4 * 2.0 * d * 32 * pooled,
                             32.0 * ((2 * s + 4 * seen) * d * 2 + 2 * s * 4))
    assert k.match(FWD)[0] == 73_047_998_464
    # a layer's four calls at the cell's shape: 24.1 MFLOP a token forward
    cell = sum(k.call_cost(kind, 32, 16384, 128, 2048, 16, 2)[0]
               for kind in ("fwd", "dq", "dkv", "dsum"))
    pairs = 16_785_408 + 7_340_032
    assert cell == (2 + 3 + 4) * 2.0 * 128 * 32 * pairs
    assert 2 * 2.0 * 128 * 32 * pairs / 16384 == pytest.approx(24.1e6, rel=2e-3)
    # by name alone: another kernel's event, a fusion, a flash call are not its
    assert k.match(FWD.replace("tpu_custom_call", "x")) is None
    assert k.match("%fusion.3 = bf16[32,4096,128]{2,1,0} fusion(%p)") is None
    assert k.match(FWD.replace("eva_attn_fwd", "flash_fwd")) is None
    band = spec.load_kernels()["flash_band"]
    assert band.match(FWD) is None


def test_the_roofline_reads_the_costed_calls():
    read = spec.load_reader("eva_attn_roofline")
    k = spec.load_kernels()["eva_attn"]
    calls = [(FWD, 4), (DQ, 4), (DKV, 4), (DSUM, 4)]
    flops = sum(n * k.match(c)[0] for c, n in calls)
    nbytes = sum(n * k.match(c)[1] for c, n in calls)
    run = {**RUN, "trace": {**RUN["trace"], "kernels": {"eva_attn": {
        "seconds": 2 * flops / 197e12, "flops": flops, "bytes": nbytes,
        "calls": 16}}}}
    assert read(run) == pytest.approx(50.0)
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    assert read({**run, "trace": {**RUN["trace"], "kernels": {}}}) is None
    assert read({**run, "trace": {**RUN["trace"], "kernels": {"eva_attn": {
        "seconds": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0}}}}) is None
    assert read({"device": RUN["device"]}) is None


# ---- the chip check, at a tiny size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cell(family):
    return types.SimpleNamespace(
        family=family, chips=1, n_layers=lambda: 2,
        config={"config": TINY, "assumed": {}},
        traffic={**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                 "loss_chunk": 32, "seq": 128, "loss_rel_tol": 1e-4})


def test_the_gradient_check_passes_tiny_and_sees_its_planted_fault(tiny_cell):
    """``evabyte_chip_check.py gradient`` as it runs on the chip, at the tiny
    widths over four windows: the program's gradients within its limit of
    the reference's, the planted backward beyond it."""
    import evabyte_chip_check as check

    out = check.gradient(tiny_cell, 4000000007, 128)
    assert out["plan"]["windows"] == 4 and out["ok"], out
    assert out["worst"]["program"] < out["tol"] < out["worst"]["no_summary_grad"]
    assert set(out["program"]) >= {"layers/eva_phi", "layers/eva_mu", "lm_head"}
    assert out["no_summary_grad"]["layers/eva_phi"] == pytest.approx(1.0)


def test_the_precision_check_judges_as_the_harness_does(tiny_cell):
    """The program's loss passes the mix's limit and the reference through
    ``float8_e5m2`` does not, by ``results.verdict``'s own comparison."""
    import evabyte_chip_check as check

    out = check.precision(tiny_cell, 4000000007)
    assert out["program_correct"] and not out["low_correct"], out
    assert out["ok"] and "not within" in out["low_why"][0]
    assert out["program_rel"] < out["loss_rel_tol"] < out["low_rel"]
