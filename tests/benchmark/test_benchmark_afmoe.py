"""The afmoe family (Trinity) in the benchmark: the configuration file's three
copies of the published keys, its arithmetic by hand, a tiny configuration of
the family rehearsed on the CPU through ``benchmark/run.py`` from a
``make_copy`` copy (new files and entries only), the cell's five readers on a
recorded run and on a run that has nothing for them, and the flash kernels'
calls costed by their names."""

import filecmp
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

CELL = "trinitylarge-train-8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 25024}

TINY_AFMOE = {
    "head_dim": 16, "hidden_size": 32, "intermediate_size": 64,
    "layer_types": ["sliding_attention", "full_attention"] * 3,
    "load_balance_coeff": 5e-5, "moe_intermediate_size": 24,
    "mup_enabled": True, "num_attention_heads": 4, "num_dense_layers": 2,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "layers_run": [0, 2, 3], "num_key_value_heads": 2,
    "num_shared_experts": 1, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "route_norm": True, "route_scale": 2.448, "score_func": "sigmoid",
    "sliding_window": 8, "tie_word_embeddings": False, "vocab_size": 256}
TINY_CELL = "tiny-afmoe-train"


@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


# ---- the configuration file ------------------------------------------------------

def test_the_file_holds_the_published_keys_three_times(cfg):
    """``published`` verbatim; ``config`` with the chip's share as run, which
    is where the harness reads the vocabulary and the arithmetic the experts;
    the top level as run for the driver's check of a catalogued file. The
    copies differ in the reduced keys alone."""
    published, run = cfg["published"], cfg["config"]
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, value in published.items():
        assert key in cfg and key in run, key
        if key in REDUCED:
            assert cfg[key] == REDUCED[key] < value, key
        else:
            assert cfg[key] == run[key] == value, key
            assert type(cfg[key]) is type(value), key
    # config: the share as run; the depth as run is reduced's, as in every
    # other file (spec.Cell.n_layers reads it there)
    assert run["num_experts"] == 8 and run["vocab_size"] == 25024
    assert run["num_hidden_layers"] == published["num_hidden_layers"] == 60
    assert set(run) - set(published) == {"num_experts_published", "layers_run"}
    assert run["num_experts_published"] == published["num_experts"] == 256
    assert run["layers_run"] == [0, 8, 9, 10, 11]
    reduced = cfg["reduced"]
    assert reduced["num_hidden_layers"]["train"] == 5
    assert reduced["num_hidden_layers"]["published"] == 60
    assert (reduced["num_experts"]["held"], reduced["num_experts"]["published"]) \
        == (8, 256)
    assert (reduced["vocab_size"]["held"], reduced["vocab_size"]["published"]) \
        == (25024, 200192)
    assert all(r["why"] for r in reduced.values())
    assert "32 chips share each layer" in cfg["deployment"]
    for key in ("modeling_file", "attention_gate", "norms", "rope",
                "embedding_scale", "router_bias", "balance_term",
                "capacity_factor", "initialisation", "state_dtypes"):
        assert cfg["assumed"][key], key


def test_published_is_the_catalogs_row(cfg):
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if "Trinity-Large-Preview" in line]
    row = next(r for r in rows if r["name"] == "Trinity-Large-Preview")
    assert cfg["published"] == row["config"]
    assert cfg["source"] == row["source_url"]
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "trinity-large-preview")
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(REDUCED)


def test_every_width_stands_as_published(cfg):
    run = cfg["config"]
    assert (run["hidden_size"], run["intermediate_size"],
            run["moe_intermediate_size"], run["head_dim"]) == (3072, 12288, 3072, 128)
    assert (run["num_attention_heads"], run["num_key_value_heads"],
            run["num_experts_per_tok"], run["num_shared_experts"],
            run["sliding_window"]) == (48, 8, 4, 1, 4096)
    family = spec.load_family("trinity_afmoe")
    assert family.kinds(run, 5) == ("window",) * 4 + ("full",)
    assert family.dense_layers_run(run, 5) == 1
    # a whole period from a period boundary: published layers 8-11
    assert [run["layer_types"][i] for i in run["layers_run"][1:]] == \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert run["layers_run"][0] < run["num_dense_layers"] <= run["layers_run"][1]


def test_the_cut_by_hand(cfg):
    """1,603.9M parameters, 5.02 GFLOP a token at s 8192."""
    family, run = spec.load_family("trinity_afmoe"), cfg["config"]
    d = 3072
    attention = 3 * d * 6144 + 2 * d * 1024          # q, gate, o; k, v
    assert attention == 62_914_560
    dense_ffn, expert, router = 3 * d * 12288, 3 * d * 3072, d * 256
    assert (dense_ffn, expert, router) == (113_246_208, 28_311_552, 786_432)
    layers = 5 * attention + dense_ffn + 4 * (9 * expert + router)
    assert family.matmul_params(run, 5, active_only=False) == layers \
        == 1_450_180_608
    embed_head = 2 * 25024 * d
    assert embed_head == 153_747_456
    # the harness counts two norms a layer and the final one
    assert arithmetic.total_params(family, run, 5) \
        == layers + embed_head + d + 5 * 2 * d == 1_603_961_856
    # a token visits 4 * 8 / 256 = 1/8 of a routed expert's worth a layer
    active = 5 * attention + dense_ffn + 4 * ((1 + 0.125) * expert + router)
    assert family.matmul_params(run, 5, active_only=True) == active \
        == 558_366_720
    # keys a query sees at s 8192: 4096 - 4096^2 / 16384 = 3072 in a window
    # layer, 4096 in the full one
    assert family.mean_keys(8192, 4096) == 3072 and family.mean_keys(8192, None) == 4096
    keys = 4 * 3072 + 4096
    assert family.attention_flops_per_token(run, 5, 8192) == 2 * 6144 * keys
    assert arithmetic.train_flops_per_token(family, run, 5, 8192) \
        == 6 * (active + d * 25024) + 6 * 2 * 6144 * keys == 5_019_402_240
    assert family.cache_bytes_per_position(run, 5) == 2 * 5 * 8 * 128 * 2


def test_the_programs_config_is_the_files(cfg):
    family = spec.load_family("trinity_afmoe")
    family.require_program()
    c = family.program_config(cfg, 5, max_seq_len=8192, attn_impl="flash",
                              loss_chunk=256)
    assert (c.d_model, c.n_heads, c.n_kv_heads, c.head_dim) == (3072, 48, 8, 128)
    assert (c.d_ff, c.d_ff_dense, c.vocab_size) == (3072, 12288, 25024)
    assert (c.n_experts, c.experts_held, c.top_k, c.n_shared_experts) == (256, 8, 4, 1)
    assert c.layer_kinds == ("window",) * 4 + ("full",) and c.n_dense_layers == 1
    assert (c.sliding_window, c.router_score, c.route_scale, c.balance) \
        == (4096, "sigmoid", 2.448, "sequence")
    assert c.router_bias and c.qk_norm_head and c.attn_gate and c.sandwich_norm
    assert c.embedding_multiplier == pytest.approx(3072 ** 0.5)
    assert c.router_aux_coef == 5e-05 and c.capacity_factor == 1.25
    # what the harness counts, and the two more norms a layer, the QK gains
    # and the four layers' bias with its momentum that it does not
    assert c.num_params() == 1_603_961_856 + 5 * (2 * 3072 + 2 * 128) + 4 * 2 * 256
    # capacity from the published count: 160 rows for 128 expected
    assert int(c.capacity_factor * 8192 * c.top_k / c.n_experts) == 160


def test_the_cell_and_its_mix(cfg):
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.n_layers() == 5 and cell.phase == "train"
    mix, like = cell.traffic, spec.Cell("mistral7b-train-4k").traffic
    assert (mix["batch"], mix["seq"], mix["steps_per_launch"]) == (1, 8192, 2)
    for key in ("driver", "attn_impl", "loss_chunk", "lr", "mesh",
                "warmup_launches", "max_launches_per_s", "data", "trace"):
        assert mix[key] == like[key], key
    assert 0 < mix["loss_rel_tol"] <= like["loss_rel_tol"]
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"mfu", "flash_time_share", "data_wait_share",
                     "launch_gap_share", "train_device_idle_share",
                     "flash_band_roofline", "band_attn_time_share",
                     "moe_ffn_time_share", "moe_held_share", "moe_drop_share"}
    assert not names & {"flash_roofline", "collective_exposed_share"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tok_s_chip", "setup_s"}
    bench = spec.load_benchmark()
    for m in bench["per_layer"][-5:]:
        assert m["workloads"] == [CELL] and m["moves"] == "train_tok_s_chip"
        assert m["unit"] == "%"
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "flash_band_roofline", "band_attn_time_share", "moe_ffn_time_share",
        "moe_held_share", "moe_drop_share"]
    assert bench["workloads"][-1]["name"] == CELL
    assert len(bench["workloads"][-1]["why"]) <= 200


# ---- a tiny configuration of the family, rehearsed ------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-afmoe")))
    path = "benchmark/configs/tiny-afmoe.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-afmoe", "family": "trinity_afmoe", "source": "test",
                   "config": TINY_AFMOE, "reduced": {},
                   "assumed": {"capacity_factor": 1.25}}, f)
    with open(os.path.join(root, "benchmark/traffic/tiny-train-band.json"), "w") as f:
        json.dump({**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                   "loss_chunk": 16}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-afmoe", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": TINY_CELL, "config": "tiny-afmoe",
                               "traffic": "tiny-train-band", "chips": 1,
                               "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if CELL in m.get("workloads", ()):
                m["workloads"].append(TINY_CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_make_copy_leaves_every_existing_file_as_it_was(root):
    """The copy gains files and BENCHMARK.json gains entries; every file of
    ``benchmark/`` lies in it byte for byte."""
    compared = 0
    for folder, _, files in os.walk(os.path.join(lib.REPO, "benchmark")):
        if "__pycache__" in folder:
            continue
        for name in files:
            if name.endswith(".pyc"):
                continue
            mine = os.path.join(folder, name)
            theirs = os.path.join(root, os.path.relpath(mine, lib.REPO))
            assert filecmp.cmp(mine, theirs, shallow=False), mine
            compared += 1
    assert compared > 100
    with open(os.path.join(lib.REPO, "BENCHMARK.json")) as f:
        mine = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        theirs = json.load(f)
    for key in ("command", "paths", "run_seconds", "end_to_end"):
        strip = lambda ms: [{k: v for k, v in m.items() if k != "workloads"}
                            for m in ms] if key == "end_to_end" else ms
        assert strip(theirs[key]) == strip(mine[key]), key
    assert theirs["configs"][:len(mine["configs"])] == mine["configs"]
    assert theirs["workloads"][:len(mine["workloads"])] == mine["workloads"]


def test_the_tiny_cell_trains_and_agrees_with_its_reference(root):
    """A traced rehearsal: the program's first loss within the mix's limit
    of the afmoe reference's, and the routing counters on the line, from the
    worker's recorder through the trainer's ``train_launches`` span."""
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
    metrics = line["metrics"]
    # 4 of 16 experts held: a quarter of the assignments, give or take the
    # router's and the bias's leanings on random weights
    assert 10.0 < metrics["moe_held_share"]["value"] < 45.0
    # (at this mix's learning rate the tiny router soon crowds a few experts:
    # most of what lands here is beyond the 10 rows an expert may take)
    assert 0.0 <= metrics["moe_drop_share"]["value"] <= 100.0
    # no device plane in a CPU trace: the trace's readers say nothing
    assert not {"flash_band_roofline", "band_attn_time_share",
                "moe_ffn_time_share", "mfu"} & set(metrics)


def test_a_program_that_cannot_build_the_family_fails_the_cell_at_once(root, monkeypatch):
    """On the parent of PR 43 loading the cell raises in the parent process,
    in seconds, before a trainer is started: one of the cell's new readers
    asks the family as it is imported."""
    family = spec.load_family("trinity_afmoe", root)
    family.require_program()  # this checkout's program has both fields
    monkeypatch.setitem(family.NEEDS, "moe", "a_field_no_program_has")
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(TINY_CELL, root)
    spec.Cell("tiny-train", root)  # the other cells load as before


# ---- the readers -----------------------------------------------------------------

RUN = {"device": {"platform": "tpu", "kind": "TPU v5 lite"},
       "trace": {"busy_s": 4.0, "by_scope": {
           "jit_steps/attn_window": 1.6, "jit_steps/attn_full": 0.5,
           "jit_steps/moe_router": 0.1, "jit_steps/moe_dispatch": 0.1,
           "jit_steps/moe_experts": 0.3, "jit_steps/moe_combine": 0.1,
           "jit_steps/moe_shared": 0.4, "jit_steps/mlp": 0.2,
           "jit_steps/loss_head": 0.3, "jit_steps/other": 0.4}}}
# 2 warm-up launches whose routing is another story, 50 of the window, the
# probe: the readers count the 50
_LAUNCH = {"moe_assignments": 2 * 4 * 8192 * 4, "moe_held": 8_192,
           "moe_kept": 8_110, "moe_dropped": 82, "moe_max_expert_rows": 171}
_OTHER = {**_LAUNCH, "moe_held": 100_000, "moe_dropped": 90_000}
TOTALS = {"launches": 53, "steps": 106, "t0": 1.0, "t1": 51.0,
          "per_launch": [_OTHER] * 2 + [_LAUNCH] * 50 + [_OTHER],
          "moe_assignments": 106 * 4 * 8192 * 4, "moe_held": 709_600}
RUN["cell"] = {"traffic": {"warmup_launches": 2}}


@pytest.fixture
def recorded(monkeypatch):
    """The program's lifecycle record as the readers find it."""
    def with_span(span):
        monkeypatch.setitem(sys.modules, "ray_tpu.util.lifecycle",
                            types.SimpleNamespace(
                                last=lambda name: span if name == "train_launches"
                                else None))
    return with_span


@pytest.mark.parametrize("metric,value", [
    ("band_attn_time_share", 40.0),
    ("moe_ffn_time_share", 25.0),
    ("moe_held_share", 100 * 8_192 / (2 * 4 * 8192 * 4)),   # 3.125
    ("moe_drop_share", 100 * 82 / 8_192),
])
def test_a_reader_on_a_recorded_run(recorded, metric, value):
    recorded(TOTALS)
    read = spec.load_reader(metric)
    assert read(RUN) == pytest.approx(value)
    # the parent's run, or a dense model's: no such scope, no such span, no
    # such counters. The reader finds nothing and says so; it does not raise
    recorded(None)
    bare = {**RUN, "trace": {"busy_s": 4.0, "by_scope": {"jit_steps/other": 4.0}}}
    assert read(bare) is None
    assert read({"device": RUN["device"], "cell": RUN["cell"]}) is None
    recorded({"launches": 50, "steps": 100, "t0": 1.0, "t1": 51.0})
    assert read(bare) is None
    recorded({"launches": 50, "steps": 100, "t0": 1.0, "t1": 51.0,
              "per_launch": [{}] * 50})  # a dense model's launches
    assert read(bare) is None
    fake = sys.modules.pop("ray_tpu.util.lifecycle")
    try:
        assert read(bare) is None  # a program with no such module at all
    finally:
        sys.modules["ray_tpu.util.lifecycle"] = fake


# ---- the kernels by their names ---------------------------------------------------

def _call(kind, window, result, prefix="", suffix=".43"):
    return (f"%{prefix}flash_{kind}_bh48_q8192_k8192_d128_c1_w{window}{suffix} = "
            f"{result} custom-call(%constant.6, %copy.1, %copy.2, %copy.3), "
            f'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


_ROWS = "bf16[48,8192,128]{2,1,0:T(8,128)(2,1)S(1)}"
FWD = _call("fwd", 4096, f"({_ROWS}, f32[48,8192,1]{{2,1,0:T(8,128)S(1)}})")
DQ = _call("dq", 4096, _ROWS, prefix="transpose_jvp_", suffix="__.1")
DKV = _call("dkv", 0, f"({_ROWS}, {_ROWS})", suffix=".11")


def test_a_flash_call_is_costed_by_the_pairs_its_mask_leaves():
    kernels = spec.load_kernels()
    band = kernels["flash_band"]
    # s 8192, window 4096: a query at p sees min(p + 1, 4096) keys
    banded = 4096 * 4097 // 2 + 4096 * 4096
    full = 8192 * 8193 // 2
    assert band.live_pairs(8192, 8192, True, 4096) == banded == 25_167_872
    assert band.live_pairs(8192, 8192, True, 0) == full
    assert band.live_pairs(8192, 8192, True, 1 << 20) == full
    assert band.live_pairs(64, 64, True, 1) == 64
    assert band.live_pairs(16, 32, False, 0) == 512
    row = 48 * 8192 * 128 * 2
    assert band.call_shape(FWD) == ("fwd", 48, 8192, 8192, 128, True, 4096, 2)
    assert band.match(FWD) == (2 * 2.0 * 128 * 48 * banded, 4.0 * row)
    assert band.match(DQ) == (3 * 2.0 * 128 * 48 * banded, 6.0 * row)
    assert band.match(DKV) == (4 * 2.0 * 128 * 48 * full, 7.0 * row)
    # the accepted matcher goes by the result's shape and still knows all
    # three (``flash_time_share`` reads their seconds); it costs the banded
    # call as the full causal square, a third too many
    flash = kernels["flash"]
    assert flash.call_kind(FWD.lstrip("%"))[0] == "fwd"
    assert flash.call_kind(DQ.lstrip("%"))[0] == "dq"
    assert flash.call_kind(DKV.lstrip("%"))[0] == "dkv"
    assert flash.match(FWD)[0] / band.match(FWD)[0] == pytest.approx(4 / 3, rel=1e-3)
    assert flash.match(DKV)[0] / band.match(DKV)[0] == pytest.approx(1.0, rel=1e-3)
    # a checkout whose kernels carry the old names gives nothing to read
    old = FWD.replace("flash_fwd_bh48_q8192_k8192_d128_c1_w4096", "flash_fwd")
    assert band.match(old) is None and flash.match(old) is not None
    assert band.match(FWD.replace("tpu_custom_call", "x")) is None
    assert band.match("%fusion.3 = bf16[48,8192,128]{2,1,0} fusion(%p)") is None
    assert kernels["moe_gmm"].match(FWD) is None


def test_the_band_roofline_reads_the_costed_calls():
    read = spec.load_reader("flash_band_roofline")
    band = spec.load_kernels()["flash_band"]
    calls = [(FWD, 8), (DQ, 4), (DKV, 1)]
    flops = sum(n * band.match(c)[0] for c, n in calls)
    nbytes = sum(n * band.match(c)[1] for c, n in calls)
    run = {**RUN, "trace": {**RUN["trace"], "kernels": {"flash_band": {
        "seconds": 2 * flops / 197e12, "flops": flops, "bytes": nbytes,
        "calls": 13}}}}
    assert read(run) == pytest.approx(50.0)
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    # the parent's benchmark knows no such kernel, its program names no such
    # call: nothing to read either way
    assert read({**run, "trace": {**RUN["trace"], "kernels": {}}}) is None
    assert read({**run, "trace": {**RUN["trace"], "kernels": {"flash_band": {
        "seconds": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0}}}}) is None
    assert read({"device": RUN["device"]}) is None


# ---- the checks beside the first loss (afmoe_chip_check.py), at a tiny size ----------

@pytest.fixture(scope="module")
def tiny():
    """A stand-in for ``spec.Cell`` over the tiny configuration: window 8 at
    s 64, one dense layer and a period of two expert layers."""
    sys.path.insert(0, os.path.join(lib.REPO, "tests", "benchmark"))
    import afmoe_chip_check as check

    cell = types.SimpleNamespace(
        family=spec.load_family("trinity_afmoe"), chips=1,
        config={"config": TINY_AFMOE, "assumed": {"capacity_factor": 1.25}},
        traffic={**lib.TRAFFIC["tiny-train"], "attn_impl": "flash",
                 "loss_chunk": 16, "loss_rel_tol": 1e-3},
        n_layers=lambda: 3)
    return check, cell


def test_the_hidden_check_tells_a_band_from_none(tiny, monkeypatch):
    check, cell = tiny
    monkeypatch.setattr(check, "HIDDEN_TOL", 0.03)  # bf16 at 32 wide
    out = check.hidden(cell.family, cell.config, cell.traffic, 3, 11,
                       positions=(9, 20, 40, 63), judged_from=20)
    assert out["ok"], out
    for depth in ("depth2", "depth3"):
        assert max(out[depth]["program"]) < 0.03 < min(out[depth]["unbanded"][1:])
    # a program that forgot the band, in the program's place
    import dataclasses

    from ray_tpu.models import moe

    real = moe.forward_hidden
    monkeypatch.setattr(moe, "forward_hidden", lambda p, t, cfg: real(
        p, t, dataclasses.replace(cfg, sliding_window=1 << 20)))
    assert not check.hidden(cell.family, cell.config, cell.traffic, 3, 11,
                            positions=(9, 20, 40, 63), judged_from=20)["ok"]


def test_the_precision_control_goes_through_the_harness_comparison(tiny):
    check, cell = tiny
    out = check.precision(cell, 11)
    assert out["low_dtype"] == "float8_e5m2"
    assert out["program_rel"] < out["loss_rel_tol"] < out["low_rel"]
    assert out["program_correct"] and not out["low_correct"] and out["ok"]
    assert "is not within" in out["low_why"][0]


def test_the_routing_check_reads_the_steps_counters(tiny):
    check, cell = tiny
    out = check.routing(cell.family, cell.config, cell.traffic, 3, 11,
                        launches=3, rate=0.02)
    assert out["rate"] == 0.02 and out["expected_held_share"] == 25.0
    assert len(out["held_share"]) == 6 and out["ok"]
    assert all(0 < v < 60 for v in out["held_share"])
    assert out["bias_abs_max"] > 0
