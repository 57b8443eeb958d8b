"""A temporary copy of the benchmark with a tiny configuration of each
family, a third family, a kernel, a bursty mix and a cell for each driver
added to it: by new files and new entries only, which is how a later PR adds
a cell or an architecture."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY = {"attention_dropout": 0.0, "hidden_act": "silu", "hidden_size": 64,
        "intermediate_size": 128, "max_position_embeddings": 256,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
        "sliding_window": None, "tie_word_embeddings": False, "vocab_size": 256}
TINY_MOE = {**TINY, "num_local_experts": 4, "num_experts_per_tok": 2,
            "router_aux_loss_coef": 0.02}

# the dense block as another publisher might name its sizes: what only a
# family reads is renamed, what the harness reads (vocab_size, hidden_size,
# tie_word_embeddings, num_hidden_layers) is not
TINY_THIRD = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
              "tie_word_embeddings": False, "n_heads": 4, "n_kv_heads": 2,
              "ffn_dim": 128, "norm_eps": 1e-05, "rope_base": 10000.0}

CONFIGS = {
    "tiny-dense": {"name": "tiny-dense", "family": "dense", "source": "test",
                   "config": TINY, "reduced": {}, "assumed": {}},
    "tiny-moe": {"name": "tiny-moe", "family": "moe", "source": "test",
                 "config": TINY_MOE, "reduced": {},
                 "assumed": {"capacity_factor": 1.25}},
    "tiny-third": {"name": "tiny-third", "family": "tiny-third", "source": "test",
                   "config": TINY_THIRD, "reduced": {}, "assumed": {}},
}
_APP = {"max_slots": 4, "max_len": 96, "kv_cache_bytes": 0}
_TRAIN = {"driver": "train", "batch": 1, "seq": 64, "steps_per_launch": 2,
          "attn_impl": "xla", "loss_chunk": 0, "lr": 0.01, "mesh": None,
          "warmup_launches": 2, "max_launches_per_s": 400,
          "data": {"zipf_a": 1.1, "span_len": 8, "spans_per_row": 2},
          "loss_rel_tol": 0.01, "trace": {"start_s": 0.2, "seconds": 0.5}}
TRAFFIC = {
    "tiny-open": {"driver": "serve_open", "app": _APP, "rate_rps": 6, "lead_s": 1,
                  "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                             "grid": [8, 16, 32]},
                  "answer": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                             "min": 2, "max": 24},
                  "slo": {"ttft_ms": 1000, "tpot_ms": 80}, "min_requests": 5,
                  "reference_sample": 2, "trace": {"start_s": 0.5, "seconds": 1}},
    "tiny-closed": {"driver": "serve_closed", "app": _APP, "clients": 6,
                    "ramp_s": 0.5, "warm_s": 1, "min_request_s": 0.01,
                    "prompt": {"dist": "choice", "values": [8, 16]},
                    "answer": {"dist": "uniform", "min": 8, "max": 32},
                    "reference_sample": 2,
                    "trace": {"start_s": 0.5, "seconds": 1}},
    "tiny-train": _TRAIN,
    "tiny-train-x4": {**_TRAIN, "batch": 4, "mesh": {"tp": 1}},
}
TRAFFIC["tiny-bursty"] = {**TRAFFIC["tiny-open"],
                          "arrivals": {"dist": "gamma", "cv": 3}}
CELLS = [
    {"name": "tiny-chat", "config": "tiny-dense", "traffic": "tiny-open", "chips": 1},
    {"name": "tiny-decode", "config": "tiny-dense", "traffic": "tiny-closed", "chips": 1},
    {"name": "tiny-train", "config": "tiny-dense", "traffic": "tiny-train", "chips": 1},
    {"name": "tiny-moe-x4", "config": "tiny-moe", "traffic": "tiny-train-x4", "chips": 4},
    {"name": "tiny-bursty", "config": "tiny-dense", "traffic": "tiny-bursty", "chips": 1},
    {"name": "tiny-moe-decode", "config": "tiny-moe", "traffic": "tiny-closed", "chips": 1},
    {"name": "tiny-third-train", "config": "tiny-third", "traffic": "tiny-train", "chips": 1},
]
_LIKE = {"tiny-chat": "mistral7b-serve-chat", "tiny-decode": "mistral7b-serve-decode",
         "tiny-train": "mistral7b-train-4k", "tiny-moe-x4": "mixtral8x7b-train-4k-x4",
         "tiny-bursty": "mistral7b-serve-chat",
         "tiny-moe-decode": "mistral7b-serve-decode",
         "tiny-third-train": "mistral7b-train-4k"}

# benchmark/families/tiny-third.py: a whole family in one new file
THIRD_FAMILY = '''"""The dense block under another publisher's configuration keys."""

from benchmark.lib import arithmetic


def _hf(cfg_file):
    """The keys the shared reference pieces read, from this family's own."""
    c = cfg_file["config"]
    return {"num_attention_heads": c["n_heads"], "num_key_value_heads": c["n_kv_heads"],
            "rms_norm_eps": c["norm_eps"], "rope_theta": c["rope_base"]}


def program_config(cfg_file, n_layers, *, max_seq_len, attn_impl="xla", loss_chunk=0):
    import jax.numpy as jnp
    from ray_tpu.models import llama

    c = cfg_file["config"]
    return llama.LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"], n_layers=n_layers,
        n_heads=c["n_heads"], n_kv_heads=c["n_kv_heads"], d_ff=c["ffn_dim"],
        max_seq_len=max_seq_len, rope_theta=c["rope_base"], norm_eps=c["norm_eps"],
        tie_embeddings=c["tie_word_embeddings"], param_dtype=jnp.bfloat16,
        attn_impl=attn_impl, loss_chunk=loss_chunk)


def init_params(rng, cfg):
    from ray_tpu.models import llama

    return llama.init_params(rng, cfg)


def _block(x, layer, hf):
    from benchmark.lib import reference as ref

    x = ref.attention(x, layer, hf)
    h = ref.rms(x, layer["mlp_norm"], hf["rms_norm_eps"])
    return x + ref.swiglu(h, layer["w_gate"], layer["w_up"], layer["w_down"]), ref.F32(0)


def _static(cfg_file):
    return tuple(sorted(_hf(cfg_file).items()))


def logits(params, tokens, cfg_file):
    from benchmark.lib import reference

    return reference.logits(params, tokens, _block, _static(cfg_file))


def token_margins(params, tokens, following, cfg_file, rows=None):
    from benchmark.lib import reference

    return reference.token_margins(params, tokens, following, _block, _static(cfg_file))


def loss(params, tokens, cfg_file):
    from benchmark.lib import reference

    out = reference.loss(params, tokens, _block, _static(cfg_file))
    return {"loss": out["ce"], **out}


def _head_dim(c):
    return arithmetic.head_dim({"hidden_size": c["hidden_size"],
                                "num_attention_heads": c["n_heads"]})


def matmul_params(c, n_layers, active_only=True):
    d, hd = c["hidden_size"], _head_dim(c)
    attn = 2 * d * c["n_heads"] * hd + 2 * d * c["n_kv_heads"] * hd
    return n_layers * (attn + 3 * d * c["ffn_dim"])


def attention_flops_per_token(c, n_layers, seq):
    return n_layers * c["n_heads"] * _head_dim(c) * seq


def cache_bytes_per_position(c, n_layers, itemsize=2):
    return 2 * n_layers * c["n_kv_heads"] * _head_dim(c) * itemsize
'''

# benchmark/kernels/tiny-matmul.py: the [1024, 1024] x [1024, 1024] product of
# the committed trace's step, costed from the event's own shapes
MATMUL_KERNEL = '''"""A fused matrix product, by its HLO name."""

import re

_SHAPES = re.compile(r"bf16\\[(\\d+),(\\d+)\\]")


def match(event_name):
    if not event_name.startswith("%convolution_tanh_fusion"):
        return None
    (m, n), (_, k) = [(int(a), int(b)) for a, b in _SHAPES.findall(event_name)[:2]]
    return 2.0 * m * n * k, 2.0 * (m * n + m * k + k * n)
'''


def make_copy(root: str) -> str:
    """BENCHMARK.json and ``benchmark/`` copied under ``root``, the tiny
    configurations, the third family, the kernel, the mixes and the cells
    added. No copied file is edited but BENCHMARK.json, which gains entries."""
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        path = f"benchmark/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        bench["configs"].append({"name": name, "source": "test", "file": path,
                                 "reduced": [], "why": "test"})
    for name, mix in TRAFFIC.items():
        with open(os.path.join(root, f"benchmark/traffic/{name}.json"), "w") as f:
            json.dump(mix, f)
    for path, text in (("benchmark/families/tiny-third.py", THIRD_FAMILY),
                       ("benchmark/kernels/tiny-matmul.py", MATMUL_KERNEL)):
        with open(os.path.join(root, path), "w") as f:
            f.write(text)
    # the real cells keep one four-chip cell in four; the copy adds as many
    # one-chip cells again as it needs to keep that
    for cell in CELLS:
        bench["workloads"].append({**cell, "why": "test"})
    for i in range(4):
        bench["workloads"].append({
            "name": f"tiny-filler-{i}", "config": "tiny-dense",
            "traffic": f"tiny-filler-{i}", "chips": 1, "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] += [new for new, old in _LIKE.items()
                                   if old in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
