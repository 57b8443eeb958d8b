"""The sambay family through ``benchmark/run.py`` as the driver starts it: a
tiny configuration of it and a cell written into a ``make_copy`` copy (new
files and entries only), rehearsed on the CPU through the serve drivers; the
configuration file's two copies; the family's arithmetic at the published
sizes; the new readers on a recorded run; and every line of prose of the
benchmark file inside the contract's 200 characters."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402

# windows of 8 inside max_len 96; 2 x (mamba, window), (mamba, full),
# (gmu, cross). Hidden 128 and eight layers, not the 64 and twelve of
# ``tests/test_sambay_serving.py``: there a bf16 engine's token lay up to
# 3.2% of the logits' scale under the float32 reference's best (the limit is
# 3.1%; 1.7% here over 960 positions), and this test asks for none outside
TINY_SAMBAY = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 256, "mb_per_layer": 2, "model_type": "phi4flash",
    "num_attention_heads": 8, "num_hidden_layers": 8, "num_key_value_heads": 4,
    "resid_pdrop": 0, "sliding_window": 8, "tie_word_embeddings": True,
    "mlp_bias": False, "lm_head_bias": False, "vocab_size": 256}
TINY_ASSUMED = {"softmax_scale": {"value": 16 ** -0.5},
                "mamba": {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 8}}
CELL = "tiny-sambay-reason"
LIKE = "phi4miniflash-serve-reason"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-sambay")))
    path = "benchmark/configs/tiny-sambay.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-sambay", "family": "sambay", "source": "test",
                   "config": TINY_SAMBAY, "reduced": {},
                   "assumed": TINY_ASSUMED}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-sambay", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-sambay",
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
    """Rehearsal (1) of PERF.md section 4 for the new family: eight layers of
    the five kinds through proxy, handle, replica and engine, answers past
    three windows."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=lib.REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4000000007", "--seconds", "3", "--trace", "0", "--rehearse"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 3, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"out_tok_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    # not correct for where it ran alone: every sampled token's logit lay
    # within the limit of the family's float32 reference's best
    assert line["why_not_correct"] == [
        "ran on cpu x" + str(line["device"]["count"]) + ", not on 1 TPU chip(s)"]


def test_the_new_cell_reports_the_decode_cells_metrics_and_its_own(root):
    cell = spec.Cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    own = {"sambay_decode_hbm_share", "window_attn_time_share",
           "shared_kv_time_share", "s6_update_roofline", "s6_scan_roofline",
           "prefill_depth_ratio", "kv_write_roofline"}
    assert names >= own | {"ssm_time_share", "state_copy_ratio", "decode_step_ms",
                           "engine_occupancy", "decode_kv_read_ratio",
                           "decode_device_idle_share", "peak_hbm_gib"}
    assert not names & {"decode_hbm_share", "hybrid_decode_hbm_share",
                        "ssm_update_roofline", "ssd_prefill_roofline"}
    for other in ("tiny-decode", "tiny-moe-decode"):
        assert not own & {m["name"] for m in spec.Cell(other, root).per_layer}
    real = spec.Cell(LIKE, lib.REPO)
    assert {m["name"] for m in real.per_layer} >= own
    assert {m["name"] for m in real.end_to_end} == {"out_tok_s", "setup_s"}


def test_the_configuration_file_holds_the_published_keys_at_its_top_level_too():
    """As Granite's file: the driver reads a catalogued configuration's
    published keys at the top level, the harness under ``config``. Nothing is
    reduced, so the two copies agree in every key."""
    cell = spec.Cell(LIKE, lib.REPO)
    cfg = cell.config
    assert cfg["reduced"] == {} and cell.n_layers() == 32
    assert len(cfg["config"]) == 17
    for key, value in cfg["config"].items():
        assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["intermediate_size"],
            cfg["vocab_size"], cfg["sliding_window"], cfg["mb_per_layer"]) == (
        2560, 40, 20, 10240, 200064, 512, 2)
    assumed = cfg["assumed"]
    assert assumed["mamba"]["dt_rank"] == 160 == -(-cfg["hidden_size"] // 16)
    assert assumed["head_dim"]["value"] == 64
    assert assumed["softmax_scale"]["value"] == 64 ** -0.5
    assert "CANNOT be stated" in assumed["attention_projection_bias"]["from"]
    bench = spec.load_benchmark(lib.REPO)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    family = spec.load_family("sambay")
    kinds = family.layer_types(cfg["config"], 32)
    assert kinds[:18] == ("mamba", "window") * 8 + ("mamba", "full")
    assert kinds[18:] == ("gmu", "cross") * 7
    mix = cell.traffic
    assert mix["app"] == {"max_slots": 64, "max_len": 2048, "kv_cache_bytes": 0}
    assert (mix["clients"], mix["prompt"]["values"], mix["answer"]["min"],
            mix["answer"]["max"]) == (96, [128, 384], 512, 1536)


def test_every_line_of_prose_in_the_benchmark_file_fits_the_contract():
    bench = spec.load_benchmark(lib.REPO)
    prose = [(f"{kind} {e['name']}: {key}", e[key])
             for kind in ("configs", "workloads", "per_layer")
             for e in bench[kind] for key in ("why", "layer", "source")
             if key in e]
    assert len(prose) > len(bench["configs"]) + len(bench["workloads"])
    for where, text in prose:
        assert 1 <= len(text) <= 200 and text.isprintable(), (where, len(text))


def test_the_familys_arithmetic_counts_what_init_params_makes():
    import jax

    family = spec.load_family("sambay")
    cfg = family.program_config(
        {"config": TINY_SAMBAY, "assumed": TINY_ASSUMED}, 8, max_seq_len=96)
    made = jax.eval_shape(lambda: family.init_params(jax.random.key(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(made)) == cfg.num_params()
    cell = spec.Cell(LIKE, lib.REPO)
    hf = cell.config["config"]
    real = family.program_config(cell.config, 32, max_seq_len=2048)
    made = jax.eval_shape(lambda: family.init_params(jax.random.key(0), real))
    leaves = sum(x.size for x in jax.tree.leaves(made))
    # 3.85 billion: the card says 3.8B
    assert family.total_params(hf, 32) == leaves == real.num_params() \
        == 3_852_562_944
    assert family.weight_bytes(hf, 32) == 2 * 3_852_562_944
    # a mamba layer's matrices 41.1M, an attention layer's 19.7M (a cross
    # layer's 13.1M), a gated memory unit's 26.2M, a feed-forward's 78.6M
    assert family.matmul_params(hf, 32) == (
        9 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
        + 9 * (2 * 2560 * 2560 + 2 * 2560 * 1280) + 7 * 2 * 2560 * 2560
        + 7 * 2 * 2560 * 5120 + 32 * 3 * 2560 * 10240)
    # 9 layers of a [5120, 16] float32 state and a [3, 5120] bf16 tail
    assert family.state_bytes_per_row(hf, 32) == 9 * (5120 * 16 * 4 + 3 * 5120 * 2) \
        == real.state_bytes_per_row() == 3_225_600
    # one layer's keys and values a position, read by eight layers
    assert family.cache_bytes_per_position(hf, 32) == 2 * 20 * 64 * 2 == 5120 \
        == real.kv_bytes_per_position()
    assert family.kv_readers(hf, 32) == 8
    # eight rings of 512 positions
    assert family.window_bytes_per_row(hf, 32) == 8 * 512 * 5120 \
        == real.window_bytes_per_row() == 20_971_520
    assert family.attention_flops_per_token(hf, 32, 1000) == (
        8 * 512 + 8 * 1000) * 40 * 64


def test_a_program_that_cannot_build_the_family_fails_the_cell_at_once(
        root, tmp_path, monkeypatch):
    """On the parent's ``ray_tpu/models`` (no ``sambay.py``) loading the cell
    raises in the parent process, before a replica is deployed; the other
    cells load as before."""
    family = spec.load_family("sambay", root)
    family.require_program()  # this checkout's program has the fields
    monkeypatch.setitem(family.NEEDS, "sambay", ("a_field_no_program_has",))
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(CELL, root)
    spec.Cell("tiny-decode", root)
    monkeypatch.undo()
    import ray_tpu

    models = tmp_path / "ray_tpu" / "models"
    models.mkdir(parents=True)
    with open(os.path.join(os.path.dirname(ray_tpu.__file__), "models",
                           "llama.py")) as f:
        (models / "llama.py").write_text(f.read())
    monkeypatch.setattr(ray_tpu, "__file__", str(tmp_path / "ray_tpu" / "x.py"))
    with pytest.raises(spec.SpecError, match="sambay.py"):
        family.require_program()


# what a traced run of the cell hands the readers: 32 layers, 128 slots, a
# 4 s traced stretch of a 50 s window in which every tick stepped the full
# bucket 8 times at 30 ms a step
HF = {"hidden_size": 2560, "num_attention_heads": 40, "num_key_value_heads": 20,
      "intermediate_size": 10240, "vocab_size": 200064, "sliding_window": 512,
      "mb_per_layer": 2, "tie_word_embeddings": True}
TICKS = [{"k": 8, "bucket": 128, "active": 128, "decode_step_s": 0.24}] * 200


def _run(**trace):
    return {
        "seconds": 50.0,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "cell": {"family": spec.load_family("sambay"), "n_layers": 32,
                 "config": {"config": HF},
                 "traffic": {"app": {"max_slots": 128}}},
        "trace": {"busy_s": 3.8, "window_s": 4.0, "by_scope": {
            "jit_rt_decode/attn_window": 0.5, "jit_rt_decode/attn_full": 0.2,
            "jit_rt_decode/attn_cross": 1.2, "jit_rt_decode/ssm_update": 0.2,
            "jit_rt_decode/mlp": 1.0, "jit_rt_prefill/ssm_scan": 0.1},
            "kernels": {
                "s6_update": {"seconds": 0.2, "flops": 1e9, "calls": 100,
                              "bytes": 0.1 * 819e9},
                "kv_write": {"seconds": 0.1, "flops": 1e6, "calls": 100,
                             "bytes": 0.002 * 819e9}}, **trace},
        "engine": {"ticks": TICKS, "occupancy": 1.0, "decode_wall_s": 50.0,
                   "prefill_layer_tokens": 17 * 256 * 100 + 15 * 100,
                   "prefill_layer_tokens_whole": 32 * 256 * 100,
                   "state_layout": {"kinds": {"mamba": 9, "window": 8, "full": 1,
                                              "gmu": 7, "cross": 7}},
                   "decode_programs": [
                       {"bucket": 1, "k": 8, "state_bytes": 128 * 9 * 327680,
                        "state_copy_bytes_per_step": 4 * 9 * 327680},
                       {"bucket": 128, "k": 8, "state_bytes": 128 * 9 * 327680,
                        "state_copy_bytes_per_step": 2 * 128 * 9 * 327680}]},
        "requests": [[128, 600]] * 50 + [[384, 600]] * 50,
    }


ROW, RINGS = 3_225_600, 20_971_520
LIVE = 256 + 300.5            # p + (t + 1) / 2
IN_WINDOW = (sum(min(128 + j + 1, 512) for j in range(600))
             + sum(min(384 + j + 1, 512) for j in range(600))) / 1200


@pytest.mark.parametrize("metric,value", [
    ("window_attn_time_share", 100 * 0.5 / 3.8),
    ("shared_kv_time_share", 100 * 1.4 / 3.8),
    ("ssm_time_share", 100 * 0.2 / 3.8),
    ("state_copy_ratio", 2.0),
    ("prefill_depth_ratio", (17 * 256 + 15) / (32 * 256)),
    # the traced calls' bytes at 819 GB/s over the time they took
    ("s6_update_roofline", 100 * 0.1 / 0.2),
    ("kv_write_roofline", 100 * 0.002 / 0.1),
    # weights; 128 rows' state twice, their live ring positions at 40,960
    # bytes each, their live shared positions at 5,120 bytes for 8 readers
    ("sambay_decode_hbm_share",
     100 * (2 * 3_852_562_944 + 128 * (2 * ROW + IN_WINDOW * RINGS / 512
                                       + LIVE * 5120 * 8))
     / (0.030 * 819e9)),
])
def test_a_reader_on_a_recorded_run(metric, value):
    assert spec.load_reader(metric)(_run()) == pytest.approx(value, rel=1e-9)


def test_the_scan_roofline_counts_the_scans_of_the_traced_stretch():
    kernels = spec.load_kernels()
    flops, nbytes = kernels["s6_scan"].scan_cost(384, HF)
    assert flops == 384 * (7.0 * 16 * 5120 + 5120)
    assert nbytes == 2.0 * 384 * (2 * 5120 + 32) + 4.0 * 384 * 5120 + 8.0 * 16 * 5120
    one, few = kernels["s6_scan"].scan_cost(128, HF)
    got = spec.load_reader("s6_scan_roofline")(_run())
    least = 9 * 50 * max((flops + one) / 197e12, (nbytes + few) / 819e9)
    assert (nbytes + few) / 819e9 > (flops + one) / 197e12  # bandwidth-bound
    assert got == pytest.approx(100 * least * (4 / 50) / 0.1, rel=1e-9)


def test_the_pallas_calls_are_costed_from_their_events_names():
    kernels = spec.load_kernels()
    update = ("%s6_update_r128_n16_c5120.20 = (f32[9,128,16,5120]{3,2,1,0}, "
              "f32[128,1,5120]{2,1,0}) custom-call(...), "
              'custom_call_target="tpu_custom_call"')
    flops, nbytes = kernels["s6_update"].match(update)
    assert nbytes == 2 * 4 * 128 * 16 * 5120 and flops == 7 * nbytes / 8
    write = ("%kv_write_r128_h10_t16_d128.19 = (bf16[8,128,10,512,128]{4,3,2,1,0}, "
             "bf16[8,128,10,512,128]{4,3,2,1,0}) custom-call(s32[1]{0} %a, "
             'bf16[8,128,10,512,128]{4,3,2,1,0} %b), custom_call_target="tpu_custom_call"')
    flops, nbytes = kernels["kv_write"].match(write)
    # two buffers, a tile of 16 positions a row read and written back
    assert nbytes == 2 * 2 * 2 * 128 * 10 * 16 * 128 and flops == nbytes / 4
    # (the flash file knows its calls by their results' shapes alone and
    # takes these for its own; no cell reports both)
    for name, kernel in kernels.items():
        if name not in ("s6_update", "flash"):
            assert kernel.match(update) is None, name
        if name not in ("kv_write", "flash"):
            assert kernel.match(write) is None, name
    assert kernels["s6_update"].match("%fusion.12 = f32[128,16,5120]") is None


def test_readers_find_nothing_where_the_program_has_no_such_layer():
    """A dense or Granite run (and the parent's program) has no window or
    shared-buffer scope, no such kernel and none of the new counters: every
    new reader returns None and raises nothing."""
    run = _run()
    run["cell"]["family"] = spec.load_family("dense")
    run["trace"]["by_scope"] = {"jit_rt_decode/mlp": 2.0, "jit_rt_decode/attn": 1.0}
    run["trace"]["kernels"] = {}
    run["engine"]["decode_programs"] = [{"bucket": 64, "k": 8,
                                         "cache_copy_bytes_per_step": 1}]
    for key in ("prefill_layer_tokens", "prefill_layer_tokens_whole",
                "state_layout"):
        del run["engine"][key]
    mine = ("sambay_decode_hbm_share", "window_attn_time_share",
            "shared_kv_time_share", "s6_update_roofline", "s6_scan_roofline",
            "prefill_depth_ratio", "kv_write_roofline")
    for metric in mine:
        assert spec.load_reader(metric)(run) is None, metric
    run["trace"] = None
    for metric in mine:
        assert spec.load_reader(metric)(run) is None, metric
