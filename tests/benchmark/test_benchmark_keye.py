"""The sparse_keye family (Keye-VL-2.0-30B-A3B's language model) in the
benchmark: the configuration file's three copies of the published keys held
to the catalog's row, the cut and its arithmetic by hand, the cell by name
and by membership, a checkout without the fields refused at once, a tiny
configuration of the family rehearsed on the CPU through
``benchmark/run.py`` from a ``make_copy`` copy (new files and entries only),
the cost file against a hand count, the cell's five readers on a hand-made
run and on a run that has nothing for them, and the chip check's two modes
at a tiny size."""

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

CELL = "keyevl2-train-16k"
CONFIG = "keye-vl-2.0-30b-a3b"
FAMILY = "sparse_keye"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 6, "num_experts": 16, "num_local_experts": 16,
           "vocab_size": 18992}
NEW_METRICS = ["sparse_attn_time_share", "index_time_share",
               "index_select_time_share", "flash_select_roofline",
               "index_chosen_share"]

TINY = {
    "head_dim": 16, "hidden_size": 32, "moe_intermediate_size": 24,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 2, 4], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 16},
    "tie_word_embeddings": False, "vocab_size": 256}
ASSUMED = {"capacity_factor": 1.25, "balance_coefficient": 0.008,
           "index_loss_coef": 1.0}
TINY_CELL = "tiny-keye-train"


@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


@pytest.fixture(scope="module")
def family():
    return spec.load_family(FAMILY)


# ---- the configuration file ------------------------------------------------------

def test_the_file_holds_the_published_keys_three_times(cfg):
    """``published`` verbatim; ``config`` with the chip's share as run; the
    top level as run for the driver's check of a catalogued file. The copies
    differ in the reduced keys alone, and ``sa_config`` and ``rope_scaling``
    are whole and as published in all three."""
    published, run = cfg["published"], cfg["config"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert key in cfg and key in run, key
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] < value, key
        else:
            assert cfg[key] == run[key] == value, key
            assert type(cfg[key]) is type(value), key
    assert run["num_experts"] == run["num_local_experts"] == 16
    assert run["vocab_size"] == 18992
    assert run["num_hidden_layers"] == published["num_hidden_layers"] == 48
    assert set(run) - set(published) == {"num_experts_published", "layers_run"}
    assert run["num_experts_published"] == published["num_experts"] \
        == published["num_local_experts"] == 128
    assert run["layers_run"] == [0, 1, 2, 3, 4, 5]
    reduced = cfg["reduced"]
    assert (reduced["num_hidden_layers"]["train"],
            reduced["num_hidden_layers"]["published"]) == (6, 48)
    for twin in ("num_experts", "num_local_experts"):
        assert (reduced[twin]["held"], reduced[twin]["published"]) == (16, 128)
    assert (reduced["vocab_size"]["held"], reduced["vocab_size"]["published"]) \
        == (18992, 151936)
    assert reduced["vocab_size"]["held"] * 8 == reduced["vocab_size"]["published"]
    assert all(r["why"] for r in reduced.values())
    assert "12.53 GiB" in reduced["num_hidden_layers"]["why"]
    assert "8 chips share each layer" in cfg["deployment"]
    assert "folds the first stage and the last" in cfg["deployment"]
    assert "vision tower is left out" in cfg["deployment"]


@pytest.mark.parametrize("key,says", [
    ("modeling_file", "Qwen3-MoE"), ("attention", "RMSNorm"),
    ("rope", "mrope_section 16 | 24 | 24"), ("indexer", "relu"),
    ("indexer", "LayerNorm"), ("indexer", "No FP8"), ("indexer", "Hadamard"),
    ("indexer_rope", "8 | 12 | 12"), ("choice", "ties at tau are all kept"),
    ("choice", "512"), ("index_loss", "KL"), ("index_loss_coef_why", "loss ="),
    ("norms", "pre-norm"), ("feed_forward", "no shared expert"),
    ("balance_term", "0.001 x 8 = 0.008"), ("capacity_factor_why", "1280"),
    ("initialisation", "--seed"), ("state_dtypes", "8 bytes")])
def test_every_departure_is_stated_under_assumed(cfg, key, says):
    """ISSUE 63's list of what the file has to say it assumed, an item a
    case."""
    assert says in cfg["assumed"][key], (key, cfg["assumed"][key])


def test_the_assumed_numbers(cfg):
    assert (cfg["assumed"]["index_loss_coef"],
            cfg["assumed"]["balance_coefficient"],
            cfg["assumed"]["capacity_factor"]) == (1.0, 0.008, 1.25)


def test_published_is_the_catalogs_row(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Keye-VL-2.0" in line]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    # every number of the row's config at the top level under its own key,
    # but for the four that are reduced; no width among those
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("moe_intermediate_size", 768), ("head_dim", 128),
    ("num_attention_heads", 32), ("num_key_value_heads", 4),
    ("num_experts_per_tok", 8), ("intermediate_size", 6144)])
def test_a_width_stands_as_published(cfg, key, value):
    assert cfg["config"][key] == cfg["published"][key] == cfg[key] == value


def test_the_nested_groups_stand_whole(cfg):
    run = cfg["config"]
    assert run["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert run["rope_scaling"] == {"mrope_section": [16, 24, 24],
                                   "rope_type": "default", "type": "default"}
    assert run["rope_theta"] == 10_000_000 and run["norm_topk_prob"] is True
    assert run["decoder_sparse_step"] == 1 and run["mlp_only_layers"] == []


def test_the_cut_by_hand(cfg, family):
    """659M parameters held, 8 bytes each while a step runs: 5.3 GB, a third
    of the chip before activations; the chosen pairs are 23.4% of the causal
    ones at s 16,384."""
    run, d = cfg["config"], 2048
    attention = 2 * d * 4096 + 2 * d * 512
    indexer = d * (16 * 64 + 64 + 16)
    expert, router = 3 * d * 768, d * 128
    assert (attention, indexer, expert, router) == (
        18_874_368, 2_260_992, 4_718_592, 262_144)
    assert family.attention_matmul_params(run) == attention
    assert family.indexer_matmul_params(run) == indexer
    layer = attention + indexer + router + 16 * expert
    assert layer == 96_894_976 and round(layer / 1e6, 1) == 96.9
    assert family.matmul_params(run, 6, active_only=False) == 6 * layer \
        == 581_369_856
    embed_head = 2 * 18992 * d
    assert embed_head == 77_791_232
    assert arithmetic.total_params(family, run, 6) \
        == 6 * layer + embed_head + d + 6 * 2 * d == 659_187_712
    assert round(659_187_712 * 8 / 1e9, 1) == 5.3
    assert 0.32 < 659_187_712 * 8 / 16e9 < 0.34
    # a token visits 8 * 16 / 128 = one routed expert's worth a layer
    active = 6 * (attention + indexer + router + expert)
    assert family.matmul_params(run, 6, active_only=True) == active \
        == 156_696_576
    # the choice: min(t + 1, 2048) a query, 31.5M of 134.2M causal pairs
    chosen, live = family.chosen_pairs(16384, 2048), 16384 * 16385 // 2
    assert (chosen, live) == (31_458_304, 134_225_920)
    assert round(100 * chosen / live, 1) == 23.4
    madds = 6 * (32 * 128 * chosen / 16384 * (2 + 1 / 3)
                 + 16 * 64 * 16385 / 2)
    assert family.attention_flops_per_token(run, 6, 16384) \
        == pytest.approx(madds)
    assert arithmetic.train_flops_per_token(family, run, 6, 16384) \
        == pytest.approx(6 * (active + d * 18992) + 6 * madds)
    # a held expert's rows a step: an eighth of its deployed load
    assert 16384 * 8 // 128 == 1024
    assert family.cache_bytes_per_position(run, 6) == 6 * 2 * (2 * 4 * 128 + 64)


def test_the_programs_config_is_the_files(cfg, family):
    family.require_program()
    c = family.program_config(cfg, 6, max_seq_len=16384, attn_impl="flash",
                              loss_chunk=256)
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim, c.d_ff,
            c.vocab_size) == (2048, 32, 4, 128, 768, 18992)
    assert (c.n_experts, c.experts_held, c.top_k, c.n_shared_experts,
            c.n_dense_layers) == (128, 16, 8, 0, 0)
    assert c.layer_kinds == ("sparse",) * 6 and c.period() == ("sparse",)
    assert (c.index_heads, c.index_head_dim, c.index_topk, c.index_loss_coef,
            c.rope_sections) == (16, 64, 2048, 1.0, (16, 24, 24))
    assert (c.router_score, c.balance, c.router_aux_coef, c.norm_eps,
            c.rope_theta) == ("softmax", "sequence", 0.008, 1e-6, 1e7)
    assert c.norm_topk_prob and c.qk_norm_head and not c.router_bias
    assert not c.sandwich_norm and not c.attn_gate and not c.hc_mult
    # what the harness counts, and beside it the head norms and the
    # indexer's LayerNorm
    assert c.num_params() == 659_187_712 + 6 * (2 * 128 + 2 * 64) \
        == 659_190_016
    # capacity from the published count: 1,280 rows for 1,024 expected
    assert int(c.capacity_factor * 16384 * c.top_k / c.n_experts) == 1280


def test_the_cell_and_its_mix(cfg):
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.n_layers() == 6 and cell.phase == "train"
    assert cell.workload["traffic"] == "pretrain-16k-ep8"
    mix, like = cell.traffic, spec.Cell("kimilinear-train-16k").traffic
    # pretrain-16k's keys and values, with an account and a limit of its own
    assert set(mix) == set(like)
    for key in mix:
        if key not in ("about", "loss_rel_tol", "loss_rel_tol_why"):
            assert mix[key] == like[key], key
    assert (mix["batch"], mix["seq"], mix["steps_per_launch"],
            mix["max_launches_per_s"]) == (1, 16384, 1, 4)
    assert "8-way expert-parallel" in mix["about"]
    assert "three equal position streams" in mix["about"]
    assert 0 < mix["loss_rel_tol"] <= 1e-3 and mix["loss_rel_tol_why"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"mfu", "data_wait_share", "launch_gap_share",
                     "train_device_idle_share", "moe_ffn_time_share",
                     "moe_held_share", "moe_drop_share", *NEW_METRICS}
    # kernels/flash.py goes by the result's shape and counts the full square
    assert not names & {"flash_time_share", "flash_roofline",
                        "flash_band_roofline", "flash_mla_roofline",
                        "collective_exposed_share", "kda_time_share"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_s_chip", "setup_s"}
    bench = spec.load_benchmark()
    mine = {m["name"]: m for m in bench["per_layer"]
            if CELL in m.get("workloads", ())}
    assert set(mine) >= set(NEW_METRICS)
    for name in NEW_METRICS:
        m = mine[name]
        assert m["workloads"] == [CELL] or CELL in m["workloads"]
        assert m["moves"] == "train_tok_s_chip" and m["unit"] == "%"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    assert mine["flash_select_roofline"]["layer"] == "Kernels"
    assert mine["index_chosen_share"]["source"] == "program_counter"
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200 and "more than its share" in entry["why"] \
        or "over its share" in entry["why"]
    assert len(bench["workloads"]) >= 12 and len(bench["configs"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) >= 1


def test_the_family_file_sorts_after_moe():
    names = sorted(f for f in os.listdir(os.path.join(
        lib.REPO, "benchmark", "families")) if f.endswith(".py"))
    assert names[:2] == ["dense.py", "moe.py"] and FAMILY + ".py" in names


# ---- the cost file ---------------------------------------------------------------

def test_the_cost_file_counts_the_chosen_pairs_from_the_calls_name():
    kernels = spec.load_kernels()
    cost = kernels["flash_select"]
    name = ("%jvp_flash_fwd_bh32_q16384_k16384_d128_c1_w0_t2048.3 = "
            "(bf16[32,16384,128]{2,1,0}, f32[32,16384,1]{2,1,0}) "
            "custom-call(...), custom_call_target=\"tpu_custom_call\"")
    pairs = sum(min(p + 1, 2048) for p in range(16384))
    assert pairs == cost.chosen_pairs(16384, 16384, True, 2048) == 31_458_304
    for kind, products, rows in (("fwd", 2, 4), ("dq", 3, 6), ("dkv", 4, 7)):
        flops, nbytes = cost.match(name.replace("_fwd_", f"_{kind}_").replace(
            "jvp_", "jvp_" if kind == "fwd" else "transpose_jvp_"))
        assert flops == products * 2.0 * 128 * 32 * pairs
        assert nbytes == rows * 16384 * 32 * 128 * 2 + 16384 * 16384
    # a short row keeps all its past; a call without a choice is not this
    # file's, and a call under one is neither the band's nor the square's
    assert cost.chosen_pairs(1000, 1000, True, 2048) == 1000 * 1001 // 2
    plain = name.replace("_t2048", "")
    assert cost.match(plain) is None
    assert kernels["flash_band"].match(plain) is not None
    assert kernels["flash_band"].match(name) is None
    assert kernels["flash_mla"].match(name) is None
    # a masked dense walk at the peak reads the chosen share of the square
    walked = 16384 * 16385 // 2
    assert 23 < 100 * pairs / walked < 24


# ---- a tiny configuration of the family, rehearsed ------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-keye")))
    path = "benchmark/configs/tiny-keye.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-keye", "family": FAMILY, "source": "test",
                   "config": TINY, "reduced": {}, "assumed": ASSUMED}, f)
    with open(os.path.join(root, "benchmark/traffic/tiny-train-ep.json"), "w") as f:
        json.dump({**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                   "loss_chunk": 16, "steps_per_launch": 1,
                   # (a query keeps 16 of at most 64 keys here: a key that
                   # bf16 scores flip is 1/16 of a row's output, where the
                   # cell's is 1/2048)
                   "loss_rel_tol": 5e-3}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-keye", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-keye",
                               "traffic": "tiny-train-ep", "chips": 1,
                               "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(TINY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_cell_trains_and_agrees_with_its_reference(root):
    """A traced rehearsal: the program's first loss (all three terms) within
    the mix's limit of the family's reference's, through the same driver as
    the cell; the counters reach the last line."""
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
    assert {"data_wait_share", "launch_gap_share", "moe_held_share",
            "index_chosen_share"} <= set(line["metrics"])
    # 4 of 16 held and a router that favours none
    assert 10 < line["metrics"]["moe_held_share"]["value"] < 45
    # a query keeps 16 of its past, min(t + 1, 16) a row of the mix's
    # length, and ties more (hundreds of steps at the mix's rate of 0.01
    # leave four heads of 8 many scores of exactly 0: 65% on this seed)
    seq = lib.TRAFFIC["tiny-train"]["seq"]
    least = 100 * sum(min(t + 1, 16) for t in range(seq)) / (seq * (seq + 1) / 2)
    assert least <= line["metrics"]["index_chosen_share"]["value"] < 100
    # no device plane in a CPU trace: the trace's readers say nothing
    assert not {"sparse_attn_time_share", "index_time_share",
                "index_select_time_share", "flash_select_roofline",
                "mfu"} & set(line["metrics"])


def test_a_program_without_the_fields_fails_the_cell_at_once(root, tmp_path,
                                                             monkeypatch):
    """On the parent of PR 63 loading the cell raises in the parent process,
    in seconds, before a trainer is started: the cell's new readers ask the
    family as they are imported."""
    import ray_tpu

    family = spec.load_family(FAMILY, root)
    family.require_program()  # this checkout's program has the fields
    old = tmp_path / "ray_tpu"
    (old / "models").mkdir(parents=True)
    (old / "models" / "moe.py").write_text(
        'ATTN_KINDS = ("window", "full", "kda", "mla")\n'
        "class MoEConfig:\n    hc_mult: int = 0\n    n_mtp_modules: int = 0\n")
    monkeypatch.setattr(ray_tpu, "__file__", str(old / "__init__.py"))
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(TINY_CELL, root)
    with pytest.raises(spec.SpecError, match="sparse_keye needs the config "
                                             "field 'index_heads'.*'sparse'"):
        family.require_program()
    spec.Cell("tiny-train", root)  # the other cells load as before
    (old / "models" / "moe.py").write_text(
        "class MoEConfig:\n    index_heads: int = 0\n    index_topk: int = 0\n")
    with pytest.raises(spec.SpecError, match="'rope_sections'"):
        family.require_program()


# ---- the readers -----------------------------------------------------------------

RUN = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
       "trace": {"busy_s": 4.0, "window_s": 4.0, "by_scope": {
           "jit_steps/attn_sparse": 1.6, "jit_steps/index_scores": 0.4,
           "jit_steps/index_select": 0.6, "jit_steps/index_loss": 0.8,
           "jit_steps/moe_experts": 0.3, "jit_steps/loss_head": 0.2,
           "jit_steps/other": 0.1},
           "kernels": {"flash_select": {
               "seconds": 1.0, "flops": 0.2 * 197e12, "bytes": 1e9,
               "calls": 54}}},
       "train": {"seq": 16384, "batch": 1, "steps": 30, "span_s": 50.0}}


@pytest.mark.parametrize("metric,share", [
    ("sparse_attn_time_share", 40.0), ("index_time_share", 45.0),
    ("index_select_time_share", 15.0)])
def test_a_time_share_reads_its_scopes(metric, share):
    read = spec.load_reader(metric)
    assert read(RUN) == pytest.approx(share)
    # the parent's run, or another model's: no such scope; nothing is said
    bare = {**RUN, "trace": {"busy_s": 4.0, "by_scope": {"jit_steps/other": 4.0}}}
    assert read(bare) is None
    assert read({"device": RUN["device"]}) is None
    assert read({**RUN, "trace": None}) is None


def test_two_of_the_three_index_scopes_are_still_a_share():
    part = {**RUN, "trace": {**RUN["trace"], "by_scope": {
        "jit_steps/index_scores": 0.4, "jit_steps/index_loss": 0.8}}}
    assert spec.load_reader("index_time_share")(part) == pytest.approx(30.0)
    assert spec.load_reader("index_select_time_share")(part) is None


def test_the_roofline_is_the_chosen_pairs_work_over_the_calls_time():
    read = spec.load_reader("flash_select_roofline")
    assert read(RUN) == pytest.approx(20.0)
    assert read({**RUN, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    assert read({**RUN, "trace": None}) is None
    assert read({**RUN, "trace": {**RUN["trace"], "kernels": {}}}) is None
    none = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0}
    assert read({**RUN, "trace": {**RUN["trace"], "kernels": {
        "flash_select": none}}}) is None


def test_the_chosen_share_reads_the_windows_counters(monkeypatch):
    from benchmark.lib import launch_record

    read = spec.load_reader("index_chosen_share")
    run = {"cell": {"traffic": {"warmup_launches": 2}}}
    rows = [{"index_pairs_live": 100, "index_pairs_chosen": 90}] * 2 + [
        {"index_pairs_live": 805_355_520, "index_pairs_chosen": 188_749_824,
         "index_rows_over_k": 0}] * 3 + [
        {"index_pairs_live": 100, "index_pairs_chosen": 1}]
    monkeypatch.setattr(launch_record, "totals",
                        lambda: {"launches": 6, "per_launch": rows})
    # six layers of 134,225,920 live and 31,458,304 chosen a step
    assert read(run) == pytest.approx(100 * 31_458_304 / 134_225_920)
    monkeypatch.setattr(launch_record, "totals",
                        lambda: {"launches": 6, "per_launch": [{}] * 6})
    assert read(run) is None
    monkeypatch.setattr(launch_record, "totals", lambda: None)
    assert read(run) is None


# ---- the chip check, at a tiny size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cell(family):
    return types.SimpleNamespace(
        family=family, chips=1, n_layers=lambda: 2,
        config={"config": TINY, "assumed": ASSUMED},
        traffic={**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                 "loss_chunk": 16, "loss_rel_tol": 5e-3})


def test_the_precision_check_judges_as_the_harness_does(tiny_cell):
    """The program's loss passes the mix's limit and the reference through
    ``float8_e5m2`` does not, by ``results.verdict``'s own comparison; with
    the program's own choices handed to the reference what is left is
    rounding."""
    import keye_chip_check as check

    out = check.precision(tiny_cell, 4000000007)
    assert out["program_correct"] and not out["low_correct"], out
    assert out["low_why"] and "first loss" in out["low_why"][0]
    assert out["program_rel_same_choice"] < 1e-3 < 5e-3 < out["low_rel"]
    assert len(out["rows_that_differ_a_layer"]) == 2
    assert out["counters"]["index_pairs_live"] == 2 * 64 * 65 // 2
    assert abs(out["program_index_loss"]
               - out["reference_same_choice"]["index_loss"]) < 1e-3


def test_the_gradient_check_passes_tiny_and_sees_its_planted_fault(tiny_cell):
    """``keye_chip_check.py gradient`` as it runs on the chip, at the tiny
    widths: the program's gradients within reach of the reference's under
    the program's own choice, the planted backward (the choice forgotten)
    far beyond; the two exact zeros."""
    import keye_chip_check as check

    out = check.gradient(tiny_cell, 4000000007, 64)
    # (at 32 wide over 64 tokens bf16's rounding is a larger share of a
    # gradient than at the cell's size: the chip's limit is GRAD_TOL)
    assert out["worst"] < 0.15 < out["fault_least"], out
    assert out["tol"] < out["fault_least"]
    assert out["zeros"] == {"trunk_from_index": 0.0, "indexer_from_ce": 0.0}
    assert {"x", "layers/router", "layers/sparse/wq",
            "layers/sparse/index_wq", "layers/sparse/index_k_norm_b"} \
        <= set(out["program"])
