"""The olmoe family through ``benchmark/run.py`` as the driver starts it: a
tiny configuration of it and a cell written into a ``make_copy`` copy (new
files and entries only), rehearsed on the CPU through the serve drivers; and
the ``moe_*`` readers and the grouped kernel's costs on a recorded run."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402

TINY_OLMOE = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
              "hidden_size": 64, "intermediate_size": 32,
              "max_position_embeddings": 256, "model_type": "olmoe",
              "norm_topk_prob": False, "num_attention_heads": 4,
              "num_experts": 8, "num_experts_per_tok": 3,
              "num_hidden_layers": 2, "num_key_value_heads": 4,
              "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
              "tie_word_embeddings": False, "vocab_size": 256}
CELL = "tiny-olmoe-decode"
LIKE = "olmoe1b7b-serve-decode"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-olmoe")))
    path = "benchmark/configs/tiny-olmoe.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-olmoe", "family": "olmoe", "source": "test",
                   "config": TINY_OLMOE, "reduced": {}, "assumed": {}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-olmoe", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-olmoe",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if LIKE in m.get("workloads", ()):
                m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def test_the_tiny_cell_is_served_and_agrees_with_its_reference(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4000000007", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"out_tok_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    # not correct for where it ran alone: every sampled token's logit lay
    # within the limit of the olmoe reference's best
    assert line["why_not_correct"] == [
        "ran on cpu x" + str(line["device"]["count"]) + ", not on 1 TPU chip(s)"]


def test_the_new_cell_reports_the_decode_cells_metrics_and_its_own(root):
    cell = spec.Cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"moe_experts_time_share", "moe_route_time_share",
                     "moe_row_waste", "moe_experts_touched_share",
                     "moe_load_max_over_mean", "decode_step_ms",
                     "decode_hbm_share"}
    dense = {m["name"] for m in spec.Cell("tiny-decode", root).per_layer}
    assert not {n for n in dense if n.startswith("moe_")}
    family = cell.family
    hf = cell.config["config"]
    # 2 layers of: 4 projections of 64 x 64, a 64 x 8 router and 8 (3
    # routed) experts of three 64 x 32 matrices
    assert family.matmul_params(hf, 2, active_only=False) == 2 * (
        4 * 64 * 64 + 64 * 8 + 8 * 3 * 64 * 32)
    assert family.matmul_params(hf, 2, active_only=True) == 2 * (
        4 * 64 * 64 + 64 * 8 + 3 * 3 * 64 * 32)
    assert family.cache_bytes_per_position(hf, 2) == 2 * 2 * 4 * 16 * 2


def test_the_configuration_file_holds_the_published_keys_at_its_top_level_too():
    """The driver's check of a catalogued configuration reads the published
    keys at the top level of the file (as run: a key ``reduced`` names holds
    the cell's value), the harness reads them under ``config`` (as
    published). The two copies may differ in the reduced keys alone."""
    cfg = spec.Cell(LIKE, lib.REPO).config
    assert sorted(cfg["reduced"]) == ["num_hidden_layers"]
    for key, value in cfg["config"].items():
        assert key in cfg, key
        if key == "num_hidden_layers":
            assert cfg[key] == cfg["reduced"][key]["serve"] < value
        else:
            assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["hidden_size"] == 2048 and cfg["intermediate_size"] == 1024
    assert cfg["num_experts"] == 64 and cfg["num_experts_per_tok"] == 8


def test_a_program_that_cannot_build_the_family_fails_the_cell_at_once(root, monkeypatch):
    """On a checkout whose program lacks a field the family's config needs
    (the parent of PR 26), loading the cell raises in the parent process,
    before a replica is deployed: ``serve.run`` would start a replica whose
    constructor raises again and again, and the run would hang."""
    family = spec.load_family("olmoe", root)
    family.require_program()  # this checkout's program has both
    monkeypatch.setitem(family.NEEDS, "moe", "a_field_no_program_has")
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(CELL, root)
    spec.Cell("tiny-decode", root)  # the other cells load as before


# what a traced run of the decode cell hands the readers: the trace's time
# by scope and the recorder's window (12 layers, 64 experts, top-8: four
# launches of 8 steps over 16 rows, one 64-token prefill beside them)
RUN = {
    "trace": {"busy_s": 4.0, "by_scope": {
        "jit_rt_decode/moe_experts": 2.0, "jit_rt_decode/moe_router": 0.2,
        "jit_rt_decode/moe_dispatch": 0.3, "jit_rt_decode/moe_combine": 0.1,
        "jit_rt_decode/attn": 1.0, "jit_rt_prefill/moe_experts": 0.3}},
    "engine": {
        "moe_assignments": 4 * 8 * 12 * 128 + 12 * 512,
        "moe_decode": {"moe_assignments": 4 * 8 * 12 * 128,
                       "moe_rows_computed": 4 * 8 * 12 * 128,
                       "moe_experts_touched": 4 * 8 * 12 * 56,
                       "moe_expert_slots": 4 * 8 * 12 * 64,
                       "moe_max_expert_rows": 9}},
}


@pytest.mark.parametrize("metric,value", [
    ("moe_experts_time_share", 50.0),
    ("moe_route_time_share", 15.0),
    ("moe_row_waste", 1.0),
    ("moe_experts_touched_share", 87.5),
    ("moe_load_max_over_mean", 4.5),
])
def test_a_moe_reader_on_a_recorded_run(metric, value):
    read = spec.load_reader(metric)
    assert read(RUN) == pytest.approx(value)
    # a dense model's run has neither the scopes nor the counters: the
    # reader finds nothing and says so, it does not raise
    assert read({"trace": {"busy_s": 4.0, "by_scope": {
        "jit_rt_decode/mlp": 2.0}}, "engine": {"occupancy": 1.0}}) is None
    assert read({"engine": {}}) is None


# ---- the grouped kernel: what an event says of itself, and its roofline ---------

SWIGLU = ('%moe_gmm_swiglu_e64_k2048_t16.11 = bf16[1024,1024]{1,0:T(8,128)(2,1)S(1)} '
          'custom-call(%while.146, %slice.166, %dynamic_slice.170, %fusion.6, '
          '%get-tuple-element.2215, /*index=5*/%get-tuple-element.2216), '
          'custom_call_target="tpu_custom_call"')
DOWN = ('%moe_gmm_e64_k1024_t16.11 = bf16[1024,2048]{1,0:T(8,128)(2,1)S(1)} '
        'custom-call(%while.146, %moe_gmm_swiglu_e64_k2048_t16.11), '
        'custom_call_target="tpu_custom_call"')


def test_the_grouped_kernel_is_costed_from_its_events_name():
    kernels = spec.load_kernels()
    assert "moe_gmm" in kernels
    match = kernels["moe_gmm"].match
    # a decode step's call: 64 tiles of 16 rows, of which at least 1024 -
    # 64 * 15 = 64 were routed; every expert's two [2048, 1024] matrices
    flops, nbytes = match(SWIGLU)
    assert flops == 2 * 2 * 64 * 2048 * 1024
    assert nbytes == 2 * (2 * 64 * 2048 * 1024 + 64 * (2048 + 1024))
    flops, nbytes = match(DOWN)
    assert flops == 2 * 64 * 1024 * 2048
    assert nbytes == 2 * (64 * 1024 * 2048 + 64 * (1024 + 2048))
    # neither another kernel's call nor a fusion that happens to be named so
    assert match(SWIGLU.replace("tpu_custom_call", "x")) is None
    assert match('%k.1 = bf16[32,4096,128]{2,1,0} custom-call(%q), '
                 'custom_call_target="tpu_custom_call"') is None
    assert kernels["flash"].match(SWIGLU) is None
    assert kernels["flash"].match(DOWN) is None


def test_the_kernels_roofline_counts_the_experts_that_were_read():
    read = spec.load_reader("moe_gmm_roofline")
    # 100 decode steps x 12 layers of both calls at the chip's full
    # bandwidth, had all 64 experts been read: 87.5% of them were
    calls = 100 * 12
    nbytes = calls * 2 * (3 * 64 * 2048 * 1024 + 64 * 2 * (2048 + 1024))
    flops = calls * 2 * 3 * 64 * 2048 * 1024
    run = {**RUN, "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "trace": {**RUN["trace"], "kernels": {"moe_gmm": {
               "seconds": nbytes / 819e9, "flops": flops, "bytes": nbytes,
               "calls": 2 * calls}}}}
    assert read(run) == pytest.approx(87.5)
    assert read({**run, "device": {"platform": "cpu", "kind": "cpu"}}) is None
    assert read({**run, "trace": {**RUN["trace"], "kernels": {}}}) is None
