"""The xing4_0 family (Xing4.0-29B-A4B) in the benchmark: the configuration
file's three copies of the published keys held to the catalog's row, the cut
and its arithmetic by hand, the cell by name and by membership, a checkout
without the fields refused at once, a tiny configuration of the family
rehearsed on the CPU through ``benchmark/run.py`` from a ``make_copy`` copy
(new files and entries only), the cell's three readers on a hand-made run and
on a run that has nothing for them, and the chip check's two modes at a tiny
size."""

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

CELL = "xing4-train-8k"
CONFIG = "xing4.0-29b-a4b"
FAMILY = "xingchen_xing4"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 16384}
NEW_METRICS = ["mhc_time_share", "mhc_stream_roofline", "mtp_time_share"]

TINY = {
    "first_k_dense_replace": 2, "hidden_size": 32, "intermediate_size": 64,
    "kv_lora_rank": 16, "q_lora_rank": 24, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "layers_run": [0, 2, 3],
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 6,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "vocab_size": 256}
ASSUMED = {"capacity_factor": 1.25, "balance_coefficient": 0.0,
           "mtp_weight": 0.3}
TINY_CELL = "tiny-xing4-train"


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
    differ in the reduced keys alone, and ``rope_scaling`` is whole and as
    published in all three."""
    published, run = cfg["published"], cfg["config"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert key in cfg and key in run, key
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] < value, key
        else:
            assert cfg[key] == run[key] == value, key
            assert type(cfg[key]) is type(value), key
    assert run["n_routed_experts"] == 8 and run["vocab_size"] == 16384
    assert run["num_hidden_layers"] == published["num_hidden_layers"] == 40
    assert set(run) - set(published) == {"n_routed_experts_published",
                                         "layers_run"}
    assert run["n_routed_experts_published"] \
        == published["n_routed_experts"] == 64
    reduced = cfg["reduced"]
    assert (reduced["num_hidden_layers"]["train"],
            reduced["num_hidden_layers"]["published"]) == (5, 40)
    assert (reduced["n_routed_experts"]["held"],
            reduced["n_routed_experts"]["published"]) == (8, 64)
    assert (reduced["vocab_size"]["held"], reduced["vocab_size"]["published"]) \
        == (16384, 131072)
    assert reduced["vocab_size"]["held"] * 8 == reduced["vocab_size"]["published"]
    assert all(r["why"] for r in reduced.values())
    assert "8 chips share each layer" in cfg["deployment"]
    assert "folds the first stage and the last" in cfg["deployment"]
    for key in ("modeling_file", "stream", "hyper_connection",
                "hyper_connection_init", "attention", "rope", "norms",
                "feed_forward", "router_bias", "prediction_module",
                "mtp_weight_why", "balance_term", "capacity_factor_why",
                "initialisation", "state_dtypes"):
        assert cfg["assumed"][key], key
    assert (cfg["assumed"]["mtp_weight"], cfg["assumed"]["balance_coefficient"],
            cfg["assumed"]["capacity_factor"]) == (0.3, 0.0, 1.25)


def test_published_is_the_catalogs_row(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Xing4.0" in line]
    row = next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    # every number of the row's config at the top level under its own key,
    # but for the three that are reduced; no width among those
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(REDUCED)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_layers_run_are_the_configs_own(cfg, family):
    """One of the two leading dense layers, counted once, and the four expert
    layers that follow them; the period is one layer."""
    run = cfg["config"]
    assert run["layers_run"] == [0, 2, 3, 4, 5]
    assert run["first_k_dense_replace"] == 2 and run["moe_layer_freq"] == 1
    assert family.layers_run(run, 5) == [0, 2, 3, 4, 5]
    assert family.dense_layers_run(run, 5) == 1
    assert family.dense_layers_run({**run, "layers_run": None}, 40) == 2
    with pytest.raises(spec.SpecError, match="6 layers asked of 5"):
        family.layers_run(run, 6)


def test_every_width_stands_as_published(cfg):
    run = cfg["config"]
    assert (run["hidden_size"], run["intermediate_size"],
            run["moe_intermediate_size"]) == (3584, 9216, 1024)
    assert (run["q_lora_rank"], run["kv_lora_rank"], run["qk_nope_head_dim"],
            run["qk_rope_head_dim"], run["v_head_dim"]) == (768, 512, 128, 64, 128)
    assert (run["num_attention_heads"], run["num_key_value_heads"],
            run["num_experts_per_tok"], run["n_shared_experts"]) == (32, 32, 4, 1)
    assert (run["hc_mult"], run["hc_sinkhorn_iters"], run["hc_eps"]) \
        == (4, 20, 1e-6)
    assert (run["mhc_h_res_clamp_min"], run["mhc_h_res_clamp_max"]) == (-30, 30)
    assert run["num_nextn_predict_layers"] == 1
    assert run["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def test_the_cut_by_hand(cfg, family):
    """913M parameters held, 8 bytes each while a step runs: 7.3 GB, 46% of
    the chip before activations; 4.51 GFLOP a token at s 8192."""
    run, d = cfg["config"], 3584
    mla = d * 768 + 768 * 6144 + d * 576 + 512 * 8192 + 4096 * d
    phi = 2 * 4 * d * 24
    assert (mla, phi) == (28_409_856, 688_128)
    assert family.mla_matmul_params(run) == mla
    assert family.hyper_matmul_params(run) == phi
    dense_ffn, expert, router = 3 * d * 9216, 3 * d * 1024, d * 64
    assert (dense_ffn, expert, router) == (99_090_432, 11_010_048, 229_376)
    layer, sparse = mla + phi, 9 * expert + router
    dense_layer, expert_layer = layer + dense_ffn, layer + sparse
    assert round(dense_layer / 1e6, 1) == 128.2
    assert round(expert_layer / 1e6, 1) == 128.4
    module = expert_layer + 2 * d * d
    assert round((module + 3 * d) / 1e6, 1) == 154.1
    layers = dense_layer + 4 * expert_layer + module
    assert family.matmul_params(run, 5, active_only=False) == layers \
        == 795_967_488
    embed_head = 2 * 16384 * d
    assert embed_head == 117_440_512
    assert arithmetic.total_params(family, run, 5) \
        == layers + embed_head + d + 5 * 2 * d == 913_447_424
    assert round(913_447_424 * 8 / 1e9, 1) == 7.3
    assert 0.45 < 913_447_424 * 8 / 16e9 < 0.47
    # a token visits 4 * 8 / 64 = 1/2 of a routed expert's worth a layer; the
    # module multiplies by the head once more
    visited = 1.5 * expert + router
    active = (5 * layer + dense_ffn + 4 * visited
              + layer + visited + 2 * d * d + d * 16384)
    assert family.matmul_params(run, 5, active_only=True) == active \
        == 441_810_944
    # scores at 192 and values at 128 over 4096 keys, five layers and the
    # module's
    madds = 6 * 32 * 320 * 4096
    assert family.attention_flops_per_token(run, 5, 8192) == madds == 251_658_240
    assert arithmetic.train_flops_per_token(family, run, 5, 8192) \
        == 6 * (active + d * 16384) + 6 * madds == 4_513_136_640
    # the least passes over the stream: 14 d forward and 23 d backward a
    # token and half layer in bf16, ten half layers
    assert family.hyper_stream_bytes_per_token(run, 5) \
        == 10 * 37 * d * 2 == 2_652_160
    assert 14 * d * 2 == 100_352   # ~100 KB a token and half layer forward
    assert family.cache_bytes_per_position(run, 5) == 5 * 576 * 2


def test_the_programs_config_is_the_files(cfg, family):
    family.require_program()
    c = family.program_config(cfg, 5, max_seq_len=8192, attn_impl="flash",
                              loss_chunk=256)
    assert (c.d_model, c.n_heads, c.d_ff, c.d_ff_dense, c.vocab_size) \
        == (3584, 32, 1024, 9216, 16384)
    assert (c.n_experts, c.experts_held, c.top_k, c.n_shared_experts) \
        == (64, 8, 4, 1)
    assert c.layer_kinds == ("mla",) * 5 and c.n_dense_layers == 1
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (768, 512, 128, 64, 128)
    assert c.mla_rope and tuple(c.mla_yarn) == (64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert (c.hc_mult, c.hc_sinkhorn_iters, c.hc_eps, c.hc_clamp) \
        == (4, 20, 1e-6, (-30.0, 30.0))
    assert (c.n_mtp_modules, c.mtp_weight) == (1, 0.3)
    assert (c.router_score, c.route_scale, c.balance, c.router_aux_coef,
            c.norm_eps) == ("sigmoid", 2.0, "sequence", 0.0, 1e-6)
    assert c.router_bias and c.norm_topk_prob and not c.sandwich_norm
    # what the harness counts, and beside it the hyper-connections' gains,
    # biases and scalars, the two inner norms a mixer, the module's three
    # norms less the two a layer the harness counts for it, and five biases
    # with their momentum
    extra = (6 * (2 * (4 * 3584 + 24 + 3) + 768 + 512) + 3 * 3584
             + 2 * 3584 + 5 * 2 * 64)
    assert c.num_params() == 913_447_424 + extra == 913_646_020
    # capacity from the published count: 640 rows for 512 expected
    assert int(c.capacity_factor * 8192 * c.top_k / c.n_experts) == 640


def test_the_cell_and_its_mix(cfg):
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.n_layers() == 5 and cell.phase == "train"
    assert cell.workload["traffic"] == "pretrain-8k-ep8"
    mix, like = cell.traffic, spec.Cell("trinitylarge-train-8k").traffic
    # pretrain-8k's keys and values, with an account and a limit of its own
    assert set(mix) == set(like)
    for key in mix:
        if key not in ("about", "loss_rel_tol", "loss_rel_tol_why"):
            assert mix[key] == like[key], key
    assert (mix["batch"], mix["seq"], mix["steps_per_launch"]) == (1, 8192, 2)
    assert "8-way expert-parallel" in mix["about"]
    assert 0 < mix["loss_rel_tol"] <= 1e-3 and mix["loss_rel_tol_why"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"mfu", "data_wait_share", "launch_gap_share",
                     "train_device_idle_share", "moe_ffn_time_share",
                     "moe_held_share", "moe_drop_share", "mla_attn_time_share",
                     "flash_mla_roofline", *NEW_METRICS}
    # kernels/flash.py goes by the result's shape and counts one width
    assert not names & {"flash_time_share", "flash_roofline",
                        "flash_band_roofline", "collective_exposed_share",
                        "kda_time_share"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_s_chip", "setup_s"}
    bench = spec.load_benchmark()
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == NEW_METRICS
    for m in mine:
        assert m["moves"] == "train_tok_s_chip" and m["unit"] == "%"
        assert m["source"] == "device_trace" and m["layer"] == "Step program"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200
    assert len(bench["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_family_file_sorts_after_moe():
    names = sorted(f for f in os.listdir(os.path.join(
        lib.REPO, "benchmark", "families")) if f.endswith(".py"))
    assert names[:2] == ["dense.py", "moe.py"] and FAMILY + ".py" in names


# ---- a tiny configuration of the family, rehearsed ------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-xing4")))
    path = "benchmark/configs/tiny-xing4.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-xing4", "family": FAMILY, "source": "test",
                   "config": TINY, "reduced": {}, "assumed": ASSUMED}, f)
    with open(os.path.join(root, "benchmark/traffic/tiny-train-ep.json"), "w") as f:
        json.dump({**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                   "loss_chunk": 16, "steps_per_launch": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-xing4", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-xing4",
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
    """A traced rehearsal: the program's first loss (both cross entropies)
    within the mix's limit of the family's reference's, through the same
    driver as the cell."""
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
    assert {"data_wait_share", "launch_gap_share", "moe_held_share"} <= set(
        line["metrics"])
    # three expert layers' choices are counted, the module's among them: 4
    # of 16 held and a router that favours none
    assert 10 < line["metrics"]["moe_held_share"]["value"] < 45
    # no device plane in a CPU trace: the trace's readers say nothing
    assert not {*NEW_METRICS, "mla_attn_time_share", "flash_mla_roofline",
                "mfu"} & set(line["metrics"])


def test_a_program_without_the_fields_fails_the_cell_at_once(root, tmp_path,
                                                             monkeypatch):
    """On the parent of PR 56 loading the cell raises in the parent process,
    in seconds, before a trainer is started: the cell's new readers ask the
    family as they are imported."""
    import ray_tpu

    family = spec.load_family(FAMILY, root)
    family.require_program()  # this checkout's program has the fields
    old = tmp_path / "ray_tpu"
    (old / "models").mkdir(parents=True)
    (old / "models" / "moe.py").write_text(
        'ATTN_KINDS = ("window", "full", "kda", "mla")\n'
        "class MoEConfig:\n    kv_lora_rank: int = 0\n    v_head_dim: int = 0\n")
    monkeypatch.setattr(ray_tpu, "__file__", str(old / "__init__.py"))
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(TINY_CELL, root)
    with pytest.raises(spec.SpecError, match="xingchen_xing4 needs the config "
                                             "field 'hc_mult'.*cannot run it"):
        family.require_program()
    spec.Cell("tiny-train", root)  # the other cells load as before
    # a source with three of the four is still refused, by the one it lacks
    (old / "models" / "moe.py").write_text(
        "class MoEConfig:\n    hc_mult: int = 0\n    n_mtp_modules: int = 0\n"
        "    q_lora_rank: int = 0\n")
    with pytest.raises(spec.SpecError, match="'mla_rope'"):
        family.require_program()


# ---- the readers -----------------------------------------------------------------

RUN = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
       "trace": {"busy_s": 4.0, "window_s": 4.0, "by_scope": {
           "jit_steps/hyper_mix": 0.4, "jit_steps/mtp": 0.6,
           "jit_steps/attn_mla": 1.6, "jit_steps/moe_experts": 0.8,
           "jit_steps/mlp": 0.2, "jit_steps/loss_head": 0.2,
           "jit_steps/other": 0.2},
           "kernels": {"flash_mla": {"seconds": 1.0, "flops": 1e12,
                                     "bytes": 1e9, "calls": 3 * 6 * 7.5}}},
       "train": {"seq": 8192, "batch": 1, "steps": 50, "span_s": 50.0}}


def _with_cell(run):
    cell = spec.Cell(CELL)
    return {**run, "cell": {"config": cell.config, "n_layers": 5,
                            "family": cell.family}}


def test_the_time_shares_read_their_scopes():
    mhc, mtp = spec.load_reader("mhc_time_share"), spec.load_reader(
        "mtp_time_share")
    assert mhc(RUN) == pytest.approx(10.0)
    assert mtp(RUN) == pytest.approx(15.0)
    # the parent's run, or another model's: no such scope; nothing is said
    bare = {**RUN, "trace": {"busy_s": 4.0, "by_scope": {"jit_steps/other": 4.0}}}
    for read in (mhc, mtp):
        assert read(bare) is None
        assert read({"device": RUN["device"]}) is None
        assert read({**RUN, "trace": None}) is None


def test_the_stream_roofline_is_the_least_bytes_over_the_scopes_time():
    """7.5 steps in the stretch by its flash calls (three a layer and step,
    six layers with the module's): 7.5 x 8192 tokens x 2,652,160 bytes at
    819 GB/s over the 0.4 s under ``hyper_mix``."""
    read = spec.load_reader("mhc_stream_roofline")
    least = 7.5 * 8192 * 2_652_160 / 819e9
    assert read(_with_cell(RUN)) == pytest.approx(100 * least / 0.4)
    assert 40 < read(_with_cell(RUN)) < 60
    assert read(_with_cell({**RUN, "device": {"platform": "cpu",
                                              "kind": "cpu"}})) is None
    assert read(_with_cell({**RUN, "trace": None})) is None
    no_scope = {**RUN["trace"], "by_scope": {"jit_steps/other": 4.0}}
    assert read(_with_cell({**RUN, "trace": no_scope})) is None
    no_calls = {**RUN["trace"], "kernels": {}}
    assert read(_with_cell({**RUN, "trace": no_calls})) is None


# ---- the chip check, at a tiny size ------------------------------------------------

@pytest.fixture(scope="module")
def tiny_cell(family):
    return types.SimpleNamespace(
        family=family, chips=1, n_layers=lambda: 3,
        config={"config": TINY, "assumed": ASSUMED},
        traffic={**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                 "loss_chunk": 16, "loss_rel_tol": 3e-4})


def test_the_gradient_check_passes_tiny_and_sees_its_planted_fault(tiny_cell):
    """``xing4_chip_check.py gradient`` as it runs on the chip, at the tiny
    widths: the program's gradients within its limit of the reference's, the
    planted backward (``H_res`` under ``stop_gradient``) beyond it."""
    import xing4_chip_check as check

    out = check.gradient(tiny_cell, 4000000007, 64)
    # (at 32 wide over 64 tokens bf16's rounding is a larger share of a
    # gradient than at the cell's size: the chip's limit is GRAD_TOL)
    assert out["worst"]["program"] < 0.15 < out["fault_least"] == 1.0, out
    assert out["tol"] < out["fault_least"]
    assert not any(k.endswith("/res") and "/hc_" not in k
                   for k in out["program"])
    assert {"rows", "layers/router", "dense_layers/hc_attn_b/res",
            "layers/hc_mlp_phi", "layers/mla/wq_a"} <= set(out["program"])
    assert out["plan"]["rows"] == 4 and out["plan"]["sinkhorn_iters"] == 6
    assert abs(out["loss"]["program"] - out["loss"]["reference"]) < 1e-3


def test_the_precision_check_judges_as_the_harness_does(tiny_cell):
    """The program's loss passes the mix's limit and the reference through
    ``float8_e5m2`` does not, by ``results.verdict``'s own comparison."""
    import xing4_chip_check as check

    out = check.precision(tiny_cell, 4000000007)
    assert out["program_correct"] and not out["low_correct"], out
    assert out["low_why"] and "first loss" in out["low_why"][0]
