"""The kimi_linear family (Kimi Linear) in the benchmark: the configuration
file's three copies of the published keys held to the catalog's row, its
arithmetic by hand, the cell and its mix, a tiny configuration of the family
rehearsed on the CPU through ``benchmark/run.py`` from a ``make_copy`` copy
(new files and entries only), the cell's four readers on a hand-made run and
on a run that has nothing for them, and the two cost files on hand counts."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import arithmetic, spec  # noqa: E402

CELL = "kimilinear-train-16k"
CONFIG = "kimi-linear-48b-a3b-instruct"
FAMILY = "moonshot_kimi_linear"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}

TINY = {
    "first_k_dense_replace": 1, "head_dim": 8, "hidden_size": 32,
    "intermediate_size": 64, "kv_lora_rank": 16,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "head_dim": 16,
        "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "moe_intermediate_size": 24, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 4,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_token": 2,
    "num_hidden_layers": 5, "layers_run": [1, 5, 6, 7, 8],
    "num_key_value_heads": 4, "num_shared_experts": 1, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "v_head_dim": 16, "vocab_size": 256}
TINY_CELL = "tiny-kimi-train"


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
    differ in the reduced keys alone, and ``linear_attn_config`` is whole
    and as published in all three."""
    published, run = cfg["published"], cfg["config"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert key in cfg and key in run, key
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] < value, key
        else:
            assert cfg[key] == run[key] == value, key
            assert type(cfg[key]) is type(value), key
    assert run["num_experts"] == 8 and run["vocab_size"] == 20480
    assert run["num_hidden_layers"] == published["num_hidden_layers"] == 27
    assert set(run) - set(published) == {"num_experts_published", "layers_run"}
    assert run["num_experts_published"] == published["num_experts"] == 256
    reduced = cfg["reduced"]
    assert (reduced["num_hidden_layers"]["train"],
            reduced["num_hidden_layers"]["published"]) == (5, 27)
    assert (reduced["num_experts"]["held"], reduced["num_experts"]["published"]) \
        == (8, 256)
    assert (reduced["vocab_size"]["held"], reduced["vocab_size"]["published"]) \
        == (20480, 163840)
    assert reduced["vocab_size"]["held"] * 8 == reduced["vocab_size"]["published"]
    assert all(r["why"] for r in reduced.values())
    assert "32 chips share each layer" in cfg["deployment"]
    for key in ("modeling_file", "kda_projections", "kda_decay", "kda_output",
                "kda_recurrence", "kda_dtypes", "mla", "rope", "norms",
                "router", "router_bias", "balance_term", "capacity_factor",
                "initialisation", "state_dtypes"):
        assert cfg["assumed"][key], key
    assert cfg["assumed"]["balance_coefficient"] == 0.0


def test_published_is_the_catalogs_row(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Kimi-Linear" in line]
    row = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    # every number of the row's config at the top level under its own key,
    # but for the three that are reduced
    for key, value in row["config"].items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(REDUCED)


def test_the_layers_run_are_the_configs_own(cfg, family):
    """``layers_run`` counts from 1, as ``linear_attn_config``'s lists do:
    the leading dense layer, then a whole period from a period boundary."""
    run = cfg["config"]
    kda = run["linear_attn_config"]
    assert run["layers_run"] == [1, 5, 6, 7, 8]
    assert sorted(kda["kda_layers"] + kda["full_attn_layers"]) \
        == list(range(1, 28))
    assert [i in kda["kda_layers"] for i in run["layers_run"]] \
        == [True, True, True, True, False]
    assert run["layers_run"][-1] in kda["full_attn_layers"]
    assert run["layers_run"][0] <= run["first_k_dense_replace"] \
        < run["layers_run"][1]
    assert (run["layers_run"][1] - 1) % 4 == 0   # a period starts at 1, 5, 9, ..
    assert family.kinds(run, 5) == ("kda",) * 4 + ("mla",)
    assert family.dense_layers_run(run, 5) == 1
    assert family.kinds({**run, "layers_run": None}, 27).count("mla") == 7


def test_every_width_stands_as_published(cfg):
    run = cfg["config"]
    assert (run["hidden_size"], run["intermediate_size"],
            run["moe_intermediate_size"]) == (2304, 9216, 1024)
    assert (run["kv_lora_rank"], run["qk_nope_head_dim"],
            run["qk_rope_head_dim"], run["v_head_dim"]) == (512, 128, 64, 128)
    assert run["linear_attn_config"]["head_dim"] == 128
    assert run["linear_attn_config"]["num_heads"] == 32
    assert (run["num_attention_heads"], run["num_experts_per_token"],
            run["num_shared_experts"]) == (32, 8, 1)
    assert run["q_lora_rank"] is None and run["mla_use_nope"] is True


def test_the_cut_by_hand(cfg, family):
    """602.2M parameters of matrices, embedding and norms; 2.59 GFLOP a token
    at s 16384."""
    run, d = cfg["config"], 2304
    kda = 4 * d * 4096 + 2 * (d * 128 + 128 * 4096) + d * 32
    mla = d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256 + 32 * 128 * d
    assert (kda, mla) == (39_460_864, 29_114_368)
    assert family.kda_matmul_params(run) == kda
    assert family.mla_matmul_params(run) == mla
    dense_ffn, expert, router = 3 * d * 9216, 3 * d * 1024, d * 256
    assert (dense_ffn, expert, router) == (63_700_992, 7_077_888, 589_824)
    layers = 4 * kda + mla + dense_ffn + 4 * (9 * expert + router)
    assert family.matmul_params(run, 5, active_only=False) == layers \
        == 507_822_080
    embed_head = 2 * 20480 * d
    assert embed_head == 94_371_840
    assert arithmetic.total_params(family, run, 5) \
        == layers + embed_head + d + 5 * 2 * d == 602_219_264
    # a token visits 8 * 8 / 256 = 1/4 of a routed expert's worth a layer
    active = 4 * kda + mla + dense_ffn + 4 * (1.25 * expert + router)
    assert family.matmul_params(run, 5, active_only=True) == active \
        == 288_407_552
    # an MLA layer's scores at 192 and values at 128 over 8192 keys; a KDA
    # layer's chunk form: five products of 64 x 128 and three of 128 x 128
    assert family.kda_madds_per_token(run) == 32 * 128 * (5 * 64 + 3 * 128) \
        == 2_883_584
    madds = 32 * 320 * 8192 + 4 * 2_883_584
    assert family.attention_flops_per_token(run, 5, 16384) == madds == 95_420_416
    assert arithmetic.train_flops_per_token(family, run, 5, 16384) \
        == 6 * (active + d * 20480) + 6 * madds == 2_586_083_328
    assert family.cache_bytes_per_position(run, 5) == 576 * 2


def test_the_programs_config_is_the_files(cfg, family):
    family.require_program()
    c = family.program_config(cfg, 5, max_seq_len=16384, attn_impl="flash",
                              loss_chunk=256)
    assert (c.d_model, c.n_heads, c.d_ff, c.d_ff_dense, c.vocab_size) \
        == (2304, 32, 1024, 9216, 20480)
    assert (c.n_experts, c.experts_held, c.top_k, c.n_shared_experts) \
        == (256, 8, 8, 1)
    assert c.layer_kinds == ("kda",) * 4 + ("mla",) and c.n_dense_layers == 1
    assert (c.kda_heads, c.kda_head_dim, c.kda_conv_taps) == (32, 128, 4)
    assert (c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim) == (512, 128, 64, 128)
    assert (c.router_score, c.route_scale, c.balance, c.router_aux_coef) \
        == ("sigmoid", 2.446, "sequence", 0.0)
    assert c.router_bias and c.norm_topk_prob and not c.sandwich_norm
    # what the harness counts, and beside it the taps, the biases, A_log,
    # the two inner norms and the four layers' bias with its momentum
    extra = (4 * (3 * 4 * 4096 + 4096 + 32 + 4096 + 128) + 512 + 4 * 2 * 256)
    assert c.num_params() == 602_219_264 + extra
    # capacity from the published count: 640 rows for 512 expected
    assert int(c.capacity_factor * 16384 * c.top_k / c.n_experts) == 640


def test_the_cell_and_its_mix(cfg):
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.n_layers() == 5 and cell.phase == "train"
    mix, like = cell.traffic, spec.Cell("trinitylarge-train-8k").traffic
    # one step a launch: a step is most of a second, nothing to amortize
    assert (mix["batch"], mix["seq"], mix["steps_per_launch"]) == (1, 16384, 1)
    for key in ("driver", "attn_impl", "loss_chunk", "lr", "mesh",
                "warmup_launches", "trace"):
        assert mix[key] == like[key], key
    # pretrain-8k's unigrams and copied spans, twice the spans for twice the row
    assert mix["data"] == {**like["data"], "spans_per_row": 16}
    assert mix["max_launches_per_s"] == 4   # rows enough for a step of 0.25 s
    assert 0 < mix["loss_rel_tol"] <= 1e-3 and mix["loss_rel_tol_why"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"mfu", "data_wait_share", "launch_gap_share",
                     "train_device_idle_share", "moe_ffn_time_share",
                     "moe_held_share", "moe_drop_share", "kda_time_share",
                     "mla_attn_time_share", "flash_mla_roofline"}
    # the recurrence's own scope lies inside attn_kda, where the reduction
    # does not look: no share of its roofline until it does (PERF.md 7)
    assert "kda_scan_roofline" not in names
    # kernels/flash.py goes by the result's shape and counts one width
    assert not names & {"flash_time_share", "flash_roofline",
                        "flash_band_roofline", "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_s_chip", "setup_s"}
    bench = spec.load_benchmark()
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "kda_time_share", "mla_attn_time_share", "flash_mla_roofline"]
    for m in mine:
        assert m["moves"] == "train_tok_s_chip" and m["unit"] == "%"
        assert m["source"] == "device_trace"
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert len(entry["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_family_file_sorts_after_moe():
    names = sorted(f for f in os.listdir(os.path.join(
        lib.REPO, "benchmark", "families")) if f.endswith(".py"))
    assert names[:2] == ["dense.py", "moe.py"] and FAMILY + ".py" in names


# ---- a tiny configuration of the family, rehearsed ------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-kimi")))
    path = "benchmark/configs/tiny-kimi.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-kimi", "family": FAMILY, "source": "test",
                   "config": TINY, "reduced": {},
                   "assumed": {"capacity_factor": 1.25,
                               "balance_coefficient": 0.0}}, f)
    with open(os.path.join(root, "benchmark/traffic/tiny-train-kda.json"), "w") as f:
        json.dump({**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                   "loss_chunk": 16, "steps_per_launch": 1}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-kimi", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-kimi",
                               "traffic": "tiny-train-kda", "chips": 1,
                               "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(TINY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_cell_trains_and_agrees_with_its_reference(root):
    """A traced rehearsal: the program's first loss within the mix's limit of
    the family's reference's (the recurrence a token at a time), through the
    same driver as the cell."""
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
    # one step a launch, as the cell: the harness's feed takes groups of one
    # and the driver's recorder stamps them (its spans and counters are read)
    assert {"data_wait_share", "launch_gap_share", "moe_held_share"} <= set(
        line["metrics"])
    # no device plane in a CPU trace: the trace's readers say nothing
    assert not {"kda_time_share", "mla_attn_time_share",
                "flash_mla_roofline", "mfu"} & set(line["metrics"])


def test_a_program_without_the_kind_fails_the_cell_at_once(root, tmp_path,
                                                           monkeypatch):
    """On the parent of PR 48 loading the cell raises in the parent process,
    in seconds, before a trainer is started: the cell's new readers ask the
    family as they are imported."""
    import ray_tpu

    family = spec.load_family(FAMILY, root)
    family.require_program()  # this checkout's program has the kind
    old = tmp_path / "ray_tpu"
    (old / "models").mkdir(parents=True)
    (old / "models" / "moe.py").write_text('ATTN_KINDS = ("window", "full")\n')
    monkeypatch.setattr(ray_tpu, "__file__", str(old / "__init__.py"))
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(TINY_CELL, root)
    spec.Cell("tiny-train", root)  # the other cells load as before


# ---- the readers -----------------------------------------------------------------

RUN = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
       "trace": {"busy_s": 4.0, "window_s": 4.0, "by_scope": {
           "jit_steps/attn_kda": 2.4,
           "jit_steps/attn_mla": 0.8, "jit_steps/moe_experts": 0.2,
           "jit_steps/mlp": 0.2, "jit_steps/loss_head": 0.2,
           "jit_steps/other": 0.2}},
       "train": {"seq": 16384, "batch": 1, "steps": 50, "span_s": 50.0}}


def _with_cell(run):
    cell = spec.Cell(CELL)
    return {**run, "cell": {"config": cell.config, "n_layers": 5,
                            "family": cell.family}}


def test_the_time_shares_read_their_scopes():
    kda, mla = spec.load_reader("kda_time_share"), spec.load_reader(
        "mla_attn_time_share")
    assert kda(RUN) == pytest.approx(60.0)      # the recurrence inside it
    assert mla(RUN) == pytest.approx(20.0)
    # the parent's run, or another model's: no such scope; nothing is said
    bare = {**RUN, "trace": {"busy_s": 4.0, "by_scope": {"jit_steps/other": 4.0}}}
    for read in (kda, mla):
        assert read(bare) is None
        assert read({"device": RUN["device"]}) is None
        assert read({**RUN, "trace": None}) is None


def test_the_scan_cost_by_hand():
    kernel = spec.load_kernels()["kda_scan"]
    hf = {"linear_attn_config": {"num_heads": 32, "head_dim": 128}}
    flops, nbytes = kernel.scan_cost(16384, hf)
    forward = 2 * 16384 * 32 * 128 * (5 * 64 + 3 * 128)
    assert flops == 3 * forward == 283_467_841_536
    rows = 16384 * 32 * (128 * 10 + 4)          # q k v bf16, g float32, beta
    out = 16384 * 32 * 128 * 2
    assert nbytes == 3 * rows + 3 * out == 2_422_210_560
    # a sequence of no whole number of chunks costs its padding
    assert kernel.scan_cost(100, hf)[0] == 3 * 2 * 128 * 32 * 128 * 704
    assert kernel.match("%fusion.3 = bf16[32,16384,128]{2,1,0} fusion(%p)") is None
    named = ("%kda_fwd_bh32_s16384_c64_k128_v128.3 = bf16[32,16384,128]{2,1,0} "
             'custom-call(%p), custom_call_target="tpu_custom_call"')
    assert kernel.match(named) == (flops / 3, nbytes / 3)
    assert kernel.match(named.replace("kda_fwd", "kda_bwd_dq")) \
        == (flops * 2 / 3, nbytes * 2 / 3)


def _call(kind, result, prefix="", suffix=".7"):
    return (f"%{prefix}flash_{kind}_bh32_q16384_k16384_d192v128_c1_w0{suffix} = "
            f"{result} custom-call(%constant.6, %copy.1, %copy.2, %copy.3), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


_Q = "bf16[32,16384,192]{2,1,0:T(8,128)(2,1)}"
_V = "bf16[32,16384,128]{2,1,0:T(8,128)(2,1)}"
FWD = _call("fwd", f"({_V}, f32[32,16384,1]{{2,1,0:T(8,128)}})")
DQ = _call("dq", _Q, prefix="transpose_jvp_", suffix="__.3")
DKV = _call("dkv", f"({_Q}, {_V})", suffix=".3")


def test_a_two_width_call_is_costed_by_both_widths():
    kernels = spec.load_kernels()
    mla, band = kernels["flash_mla"], kernels["flash_band"]
    pairs = 16384 * 16385 // 2
    assert mla.live_pairs(16384, 16384, True, 0) == pairs \
        == band.live_pairs(16384, 16384, True, 0)
    assert mla.call_shape(FWD) == ("fwd", 32, 16384, 16384, 192, 128, True, 0, 2)
    s = 16384
    assert mla.match(FWD) == (2.0 * (192 + 128) * 32 * pairs,
                              2.0 * 32 * (2 * s * 192 + 2 * s * 128))
    assert mla.match(DQ) == (2.0 * (2 * 192 + 128) * 32 * pairs,
                             2.0 * 32 * (3 * s * 192 + 3 * s * 128))
    assert mla.match(DKV) == (2.0 * (2 * 192 + 2 * 128) * 32 * pairs,
                              2.0 * 32 * (3 * s * 192 + 4 * s * 128))
    # at one width the two files count alike, and neither reads the other's
    one = FWD.replace("d192v128", "d128")
    assert mla.match(one) is None and band.match(one) is not None
    assert band.match(FWD) is None
    d = 128
    assert mla.call_cost("dkv", 32, s, s, d, d, True, 0, 2) \
        == band.call_cost("dkv", 32, s, s, d, True, 0, 2)
    assert mla.match(FWD.replace("tpu_custom_call", "x")) is None
    assert mla.match("%fusion.3 = bf16[32,16384,128]{2,1,0} fusion(%p)") is None


def test_the_mla_roofline_reads_the_costed_calls():
    read = spec.load_reader("flash_mla_roofline")
    mla = spec.load_kernels()["flash_mla"]
    calls = [(FWD, 4), (DQ, 4), (DKV, 4)]
    flops = sum(n * mla.match(c)[0] for c, n in calls)
    nbytes = sum(n * mla.match(c)[1] for c, n in calls)
    run = {**RUN, "trace": {**RUN["trace"], "kernels": {"flash_mla": {
        "seconds": 2 * flops / 197e12, "flops": flops, "bytes": nbytes,
        "calls": 12}}}}
    assert read(run) == pytest.approx(50.0)
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    assert read({**run, "trace": {**RUN["trace"], "kernels": {}}}) is None
    assert read({**run, "trace": {**RUN["trace"], "kernels": {"flash_mla": {
        "seconds": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0}}}}) is None
    assert read({"device": RUN["device"]}) is None


# ---- the chip check, at a tiny size ------------------------------------------------

def test_the_gradient_check_passes_tiny_and_sees_its_planted_faults():
    """``kimi_chip_check.py gradient`` as it runs on the chip, at 2 heads of
    16 over 2,048 tokens (four segments): the chunked form's gradients within
    its limit of the recurrence's, both planted backwards beyond it."""
    done = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      "kimi_chip_check.py"), "gradient",
         "--seq", "2048", "--heads", "2", "--width", "16", "--seed", "4000000007"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["plan"]["segments"] == 4 and line["ok"]
    assert line["worst"]["program"] < line["tol"] < min(
        line["worst"]["dropped_segment"], line["worst"]["cut_state"])
