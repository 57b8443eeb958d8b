"""A temporary copy of the benchmark with a tiny configuration of each
family and a cell for each driver added to it: by new files and new entries
only, which is how a later PR adds a cell."""

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

CONFIGS = {
    "tiny-dense": {"name": "tiny-dense", "family": "dense", "source": "test",
                   "config": TINY, "reduced": {}, "assumed": {}},
    "tiny-moe": {"name": "tiny-moe", "family": "moe", "source": "test",
                 "config": TINY_MOE, "reduced": {},
                 "assumed": {"capacity_factor": 1.25}},
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
CELLS = [
    {"name": "tiny-chat", "config": "tiny-dense", "traffic": "tiny-open", "chips": 1},
    {"name": "tiny-decode", "config": "tiny-dense", "traffic": "tiny-closed", "chips": 1},
    {"name": "tiny-train", "config": "tiny-dense", "traffic": "tiny-train", "chips": 1},
    {"name": "tiny-moe-x4", "config": "tiny-moe", "traffic": "tiny-train-x4", "chips": 4},
]
_LIKE = {"tiny-chat": "mistral7b-serve-chat", "tiny-decode": "mistral7b-serve-decode",
         "tiny-train": "mistral7b-train-4k", "tiny-moe-x4": "mixtral8x7b-train-4k-x4"}


def make_copy(root: str) -> str:
    """BENCHMARK.json and ``benchmark/`` copied under ``root``, the tiny
    configurations, mixes and cells added. No copied file is edited but
    BENCHMARK.json, which gains entries."""
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
