"""The ssm_hybrid family through ``benchmark/run.py`` as the driver
starts it: a tiny configuration of it and a cell written into a ``make_copy``
copy (new files and entries only), rehearsed on the CPU through the serve
drivers; the configuration file's two copies; the family's arithmetic; and
the new readers on a recorded run."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402

TINY_HYBRID = {
    "attention_bias": False, "attention_multiplier": 0.125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 128,
    "layer_types": ["mamba", "mamba", "attention", "mamba"] * 2,
    "logits_scaling": 8, "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 8,
    "mamba_proj_bias": False, "max_position_embeddings": 256,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 4, "num_experts_per_tok": 0, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 128, "tie_word_embeddings": True,
    "vocab_size": 256}
CELL = "tiny-hybrid-decode"
LIKE = "granite4hmicro-serve-decode"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = lib.make_copy(str(tmp_path_factory.mktemp("bench-granite")))
    path = "benchmark/configs/tiny-hybrid.json"
    with open(os.path.join(root, path), "w") as f:
        json.dump({"name": "tiny-hybrid", "family": "ssm_hybrid",
                   "source": "test", "config": TINY_HYBRID, "reduced": {},
                   "assumed": {}}, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": path, "reduced": [], "why": "test"})
    bench["workloads"].append({"name": CELL, "config": "tiny-hybrid",
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
    """Rehearsal (1) of PERF.md section 4 for the new family: eight layers
    of two periods through proxy, handle, replica and engine."""
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
    # within the limit of the family's float32 reference's best
    assert line["why_not_correct"] == [
        "ran on cpu x" + str(line["device"]["count"]) + ", not on 1 TPU chip(s)"]


def test_the_new_cell_reports_the_decode_cells_metrics_and_its_own(root):
    cell = spec.Cell(CELL, root)
    names = {m["name"] for m in cell.per_layer}
    assert names >= {"ssm_time_share", "ssm_update_roofline",
                     "ssd_prefill_roofline", "hybrid_decode_hbm_share",
                     "state_copy_ratio", "decode_step_ms", "engine_occupancy",
                     "decode_device_idle_share", "peak_hbm_gib"}
    assert "decode_hbm_share" not in names  # it knows no bytes a row
    for other in ("tiny-decode", "tiny-moe-decode"):
        theirs = {m["name"] for m in spec.Cell(other, root).per_layer}
        assert not theirs & {"ssm_time_share", "state_copy_ratio",
                             "hybrid_decode_hbm_share"}


def test_the_configuration_file_holds_the_published_keys_at_its_top_level_too():
    """As OLMoE's file: the driver reads a catalogued configuration's
    published keys at the top level, the harness under ``config``. Nothing
    is reduced here, so the two copies agree in every key."""
    cfg = spec.Cell(LIKE, lib.REPO).config
    assert cfg["reduced"] == {}
    assert spec.Cell(LIKE, lib.REPO).n_layers() == 40
    for key, value in cfg["config"].items():
        assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["layer_types"].count("attention") == 4
    assert [i for i, kind in enumerate(cfg["layer_types"])
            if kind == "attention"] == [5, 15, 25, 35]
    assert cfg["hidden_size"] == 2048 and cfg["mamba_d_state"] == 128
    assert cfg["assumed"]["state_dtype"] == "float32"
    bench = spec.load_benchmark(lib.REPO)
    entry = next(c for c in bench["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    mix = spec.Cell(LIKE, lib.REPO).traffic
    assert mix["app"] == {"max_slots": 64, "max_len": 2048, "kv_cache_bytes": 0}
    assert mix["clients"] == 96 and mix["prompt"]["values"] == [128, 384]


def test_every_line_of_prose_in_the_benchmark_file_fits_the_contract():
    """A ``why``, a ``layer`` and a ``source`` have 1 to 200 printable
    characters on one line: the driver refuses the file before any run
    otherwise (PR 31's first check: a configuration's ``why`` of 221), and
    the spec's own test holds only the cells' to it."""
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

    family = spec.load_family("ssm_hybrid")
    cfg = family.program_config({"config": TINY_HYBRID}, 8, max_seq_len=64)
    made = jax.eval_shape(lambda: family.init_params(jax.random.key(0), cfg))
    leaves = sum(x.size for x in jax.tree.leaves(made))
    assert family.total_params(TINY_HYBRID, 8) == leaves == cfg.num_params()
    hf = spec.Cell(LIKE, lib.REPO).config["config"]
    assert family.total_params(hf, 40) == 3_191_396_096
    assert family.weight_bytes(hf, 40) == 2 * 3_191_396_096
    # 36 layers of a [64, 64, 128] float32 state and a [3, 4352] bf16 tail
    assert family.state_bytes_per_row(hf, 40) == 36 * (
        64 * 64 * 128 * 4 + 3 * 4352 * 2) == 76_437_504
    # 4 layers' keys and values: 8 heads of 64 in bf16
    assert family.cache_bytes_per_position(hf, 40) == 2 * 4 * 8 * 64 * 2
    assert family.attention_flops_per_token(hf, 40, 1000) == 4 * 32 * 64 * 1000
    # one period of ten layers: nine mamba layers and one that attends
    assert family.matmul_params(hf, 10) == (
        9 * (2048 * 8512 + 4096 * 2048) + (2 * 2048 * 2048 + 2 * 2048 * 512)
        + 10 * 3 * 2048 * 8192)


def test_a_program_that_cannot_build_the_family_fails_the_cell_at_once(
        root, tmp_path, monkeypatch):
    """On the parent's ``ray_tpu/models`` (no ``hybrid.py``, no multipliers
    on ``LlamaConfig``) loading the cell raises in the parent process, before
    a replica is deployed; the other cells load as before."""
    family = spec.load_family("ssm_hybrid", root)
    family.require_program()  # this checkout's program has the fields
    monkeypatch.setitem(family.NEEDS, "hybrid", ("a_field_no_program_has",))
    with pytest.raises(spec.SpecError, match="cannot run it"):
        spec.Cell(CELL, root)
    spec.Cell("tiny-decode", root)
    # the parent's tree as it was: the module is not there at all
    import ray_tpu

    models = tmp_path / "ray_tpu" / "models"
    models.mkdir(parents=True)
    with open(os.path.join(os.path.dirname(ray_tpu.__file__), "models",
                           "moe.py")) as f:
        (models / "llama.py").write_text(f.read())  # a config without them
    monkeypatch.undo()
    monkeypatch.setattr(ray_tpu, "__file__", str(tmp_path / "ray_tpu" / "x.py"))
    with pytest.raises(spec.SpecError, match="hybrid.py"):
        family.require_program()


# what a traced run of the cell hands the readers: 40 layers, 64 slots, a
# 4 s traced stretch of a 50 s window in which every tick stepped the full
# bucket 8 times at 25 ms a step
HF = {"mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128,
      "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 256,
      "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
      "shared_intermediate_size": 8192, "vocab_size": 100352,
      "tie_word_embeddings": True,
      "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4}
ROW = 76_437_504
TICKS = [{"k": 8, "bucket": 64, "active": 64, "decode_step_s": 0.2}] * 250


def _run(**trace):
    return {
        "seconds": 50.0,
        "device": {"platform": "tpu", "kind": "TPU v5 lite"},
        "cell": {"family": spec.load_family("ssm_hybrid"), "n_layers": 40,
                 "config": {"config": HF},
                 "traffic": {"app": {"max_slots": 64}}},
        "trace": {"busy_s": 3.8, "window_s": 4.0, "kernels": {}, "by_scope": {
            "jit_rt_decode/ssm_update": 2.0, "jit_rt_decode/ssm_proj": 0.4,
            "jit_rt_decode/ssm_conv": 0.1, "jit_rt_decode/mlp": 1.0,
            "jit_rt_prefill/ssm_scan": 0.01, "jit_rt_prefill/ssm_proj": 0.02},
            **trace},
        "engine": {"ticks": TICKS, "occupancy": 1.0, "decode_wall_s": 50.0,
                   "ssm_scan_chunks": 36 * 400,
                   "decode_programs": [
                       {"bucket": 1, "k": 8, "state_bytes": 64 * 36 * 2 ** 21,
                        "state_copy_bytes_per_step": 3 * 36 * 2 ** 21},
                       {"bucket": 64, "k": 1, "state_bytes": 64 * 36 * 2 ** 21,
                        "state_copy_bytes_per_step": 4 * 64 * 36 * 2 ** 21},
                       {"bucket": 64, "k": 8, "state_bytes": 64 * 36 * 2 ** 21,
                        "state_copy_bytes_per_step": 2 * 64 * 36 * 2 ** 21}]},
        "requests": [[128, 500]] * 125 + [[384, 500]] * 125,
    }


@pytest.mark.parametrize("metric,value", [
    # 2.5 of 3.8 busy seconds under the three decode scopes
    ("ssm_time_share", 100 * 2.5 / 3.8),
    # 64 rows x 2000 steps x 4/50 traced, twice a row's bytes, at 819 GB/s,
    # over the 2.0 s under the scope
    ("ssm_update_roofline",
     100 * (64 * 2000 * 4 / 50 * 2 * ROW / 819e9) / 2.0),
    # the widest program: 2.0 x its rows' state
    ("state_copy_ratio", 2.0),
    # weights, 64 rows at 128 or 384 + 249.5 live positions, the state twice
    ("hybrid_decode_hbm_share",
     100 * (2 * 3_191_396_096 + 64 * (256 + 249.5) * 8192 + 64 * 2 * ROW)
     / (0.025 * 819e9)),
])
def test_a_reader_on_a_recorded_run(metric, value):
    assert spec.load_reader(metric)(_run()) == pytest.approx(value, rel=1e-9)


def test_the_prefill_roofline_counts_the_scans_of_the_traced_stretch():
    kernels = spec.load_kernels()
    flops, nbytes = kernels["ssd_scan"].scan_cost(384, HF)
    # 384 tokens: two chunks of 256 (the padding is computed)
    assert flops == 2.0 * 2 * 256 * (256 * 128 + 256 * 4096 + 2 * 4096 * 128)
    assert nbytes == 2.0 * 384 * (2 * 4096 + 256) + 4.0 * 384 * 64 + 4.0 * 2 ** 19
    one, _ = kernels["ssd_scan"].scan_cost(128, HF)
    assert one == 2.0 * 128 * (128 * 128 + 128 * 4096 + 2 * 4096 * 128)
    _, few = kernels["ssd_scan"].scan_cost(128, HF)
    got = spec.load_reader("ssd_prefill_roofline")(_run())
    # 125 prompts of each length in the window, 36 layers; the short
    # prompt's final state makes the sum bandwidth-bound
    least = 36 * 125 * max((flops + one) / 197e12, (nbytes + few) / 819e9)
    assert (nbytes + few) / 819e9 > (flops + one) / 197e12
    assert got == pytest.approx(100 * least * (4 / 50) / 0.01, rel=1e-9)


def test_the_pallas_update_is_costed_from_its_events_name():
    kernels = spec.load_kernels()
    name = ("%ssm_update_r64_h64_p64_n128.3 = (f32[36,64,64,64,128]{4,3,2,1,0}, "
            "f32[64,64,64,1]{3,2,1,0}) custom-call(...), "
            'custom_call_target="tpu_custom_call"')
    flops, nbytes = kernels["ssm_update"].match(name)
    assert nbytes == 2 * 4 * 64 * 64 * 64 * 128 and flops == 6 * nbytes / 8
    assert kernels["ssm_update"].match("%fusion.12 = f32[64,64,64,128]") is None
    assert kernels["ssd_scan"].match(name) is None
    # with such calls in the trace the roofline is theirs, exactly
    t = {"kernels": {"ssm_update": {"seconds": 1.0, "flops": 1e9,
                                    "bytes": 0.5 * 819e9, "calls": 100}}}
    assert spec.load_reader("ssm_update_roofline")(_run(**t)) \
        == pytest.approx(50.0)


def test_readers_find_nothing_where_the_program_has_no_such_layer():
    """A dense run (and the parent's program) has no ``ssm_*`` scope, no
    state counters and a family without ``state_bytes_per_row``: every new
    reader returns None and raises nothing."""
    run = _run()
    run["cell"]["family"] = spec.load_family("dense")
    run["trace"]["by_scope"] = {"jit_rt_decode/mlp": 2.0, "jit_rt_decode/attn": 1.0}
    run["engine"]["decode_programs"] = [{"bucket": 64, "k": 8,
                                         "cache_copy_bytes_per_step": 1}]
    del run["engine"]["ssm_scan_chunks"]
    for metric in ("ssm_time_share", "ssm_update_roofline", "ssd_prefill_roofline",
                   "hybrid_decode_hbm_share", "state_copy_ratio"):
        assert spec.load_reader(metric)(run) is None, metric
    run["trace"] = None
    for metric in ("ssm_time_share", "ssm_update_roofline", "ssd_prefill_roofline"):
        assert spec.load_reader(metric)(run) is None, metric
