"""The ``train`` driver: ``JaxTrainer`` -> ``StepDriver`` on a worker granted
the cell's chips, K fused steps a launch, the dataset shard feeding it.

The parent (``run``) makes the tokens from the seed and starts the trainer;
``train_loop`` runs in the worker that holds the chips and does everything
that needs them: the float32 reference on the first batch, the warm-up
launches, the window, the trace and its reduction.

Timing. Every launch is fenced by a host read of its loss, one launch behind
the dispatch, so that the device always has the next launch queued while the
host waits for this one. The window opens when the last warm-up launch
finishes. A launch counts if it finishes within ``--seconds`` of that, and
the rate is the counted tokens over the time to the last counted finish:
whole launches over the time they took, with no partial launch at either end
(a launch is seconds long, so counting by a fixed end would jitter by one).
"""

from __future__ import annotations

import tempfile
import time
from typing import Any, Callable, Dict, Iterator, List

import numpy as np

from benchmark.lib.spec import Cell


def synthetic_tokens(seed: int, vocab: int, rows: int, width: int,
                     data: Dict[str, Any]) -> np.ndarray:
    """[rows, width] int32 with something to learn: a Zipf unigram over a
    seeded permutation of the vocabulary, and spans copied from earlier in
    the same row."""
    rng = np.random.default_rng([seed, 0x70CE])
    p = 1.0 / np.arange(1, vocab + 1) ** float(data["zipf_a"])
    ranks = rng.choice(vocab, size=(rows, width), p=p / p.sum())
    tokens = rng.permutation(vocab)[ranks].astype(np.int32)
    span, copies = int(data["span_len"]), int(data["spans_per_row"])
    for row in tokens:
        for _ in range(copies):
            dst = int(rng.integers(span, width - span))
            src = int(rng.integers(0, dst - span + 1))
            row[dst:dst + span] = row[src:src + span]
    return tokens


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, on_chip: bool,
        t_process: float, trace_dir: str, say: Callable[[str], None]
        ) -> Dict[str, Any]:
    from ray_tpu import data as rt_data
    from ray_tpu.train import (FastPathConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    traffic, cfg_file = cell.traffic, cell.config
    k, batch, seq = traffic["steps_per_launch"], traffic["batch"], traffic["seq"]
    setup = {"runtime_s": time.perf_counter() - t_process}
    t = time.perf_counter()
    launches = int(traffic["warmup_launches"]) + int(
        seconds * traffic["max_launches_per_s"]) + 2
    tokens = synthetic_tokens(seed, cfg_file["config"]["vocab_size"],
                              launches * k * batch, seq + 1, traffic["data"])
    setup["tokens_s"] = time.perf_counter() - t
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "cfg_file": cfg_file, "n_layers": cell.n_layers(),
            "traffic": traffic, "seed": seed, "seconds": seconds,
            "trace": trace, "trace_dir": trace_dir, "chips": cell.chips},
        scaling_config=ScalingConfig(
            num_workers=1, tpu_chips_per_worker=cell.chips if on_chip else 0),
        run_config=RunConfig(
            storage_path=tempfile.mkdtemp(prefix="bench_train_"),
            fast_path=FastPathConfig(steps_per_launch=k)),
        datasets={"train": rt_data.from_numpy(tokens)}).fit()
    run_ = result.metrics["bench"]
    # the worker's clock started when its loop did; the parent's before that
    run_["setup_s"] = (run_.pop("t_window_open_wall") - time.time()
                       + time.perf_counter() - t_process)
    run_["setup"].update(setup)
    say(f"losses: first {run_['train']['first_loss']:.4f} (reference "
        f"{run_['reference']['loss']:.4f}), last {run_['train']['last_loss']:.4f}, "
        f"probe batch after {run_['train']['probe_loss_after']:.4f}")
    return run_


def train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``. Reports one dict, ``bench``."""
    t_loop = time.perf_counter()
    from ray_tpu.util.compile_cache import CompileCounter

    counter = CompileCounter()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    devices = jax.devices()[:config["chips"]]
    backend_init_s = time.perf_counter() - t_loop
    setup: Dict[str, float] = {}

    from ray_tpu import train
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.driver import StepDriver

    from benchmark.lib import model, spec, trace

    traffic, cfg_file = config["traffic"], config["cfg_file"]
    seconds = float(config["seconds"])
    k = train.get_fast_path().steps_per_launch
    batch, seq = traffic["batch"], traffic["seq"]
    family = spec.load_family(cfg_file["family"])
    cfg = family.program_config(cfg_file, config["n_layers"], max_seq_len=seq,
                                attn_impl=traffic["attn_impl"],
                                loss_chunk=traffic["loss_chunk"])
    optimizer = ts.default_optimizer(lr=traffic["lr"], warmup_steps=10,
                                     total_steps=10_000)
    if len(devices) > 1:
        mesh, _ = ts.auto_mesh(len(devices), devices, **(traffic["mesh"] or {}))
    else:
        mesh = make_mesh(MeshConfig(), devices)

    t = time.perf_counter()
    params, opt_state = ts.init_sharded_state(
        jax.random.key(config["seed"]), cfg, mesh, optimizer)
    jax.block_until_ready((params, opt_state))
    setup["weights_s"] = time.perf_counter() - t
    driver = StepDriver(cfg, optimizer, mesh=mesh)

    # the feed: on one device stacked [K, B, S+1] groups put on the device
    # ahead of the step; across devices host batches that the driver stacks
    # and places by its plan (data/iterator.py says which is for which)
    shard = train.get_dataset_shard("train")
    prestacked = len(devices) == 1
    if prestacked:
        feed = iter(shard.iter_jax_batches(
            batch_size=batch, drop_last=True, stack=k,
            prefetch_batches=train.get_fast_path().prefetch_batches))
    else:
        feed = iter(shard.iter_batches(batch_size=batch, drop_last=True))
    per_launch = 1 if prestacked else k  # batches the driver takes a launch
    first = [{"tokens": next(feed)["data"]} for _ in range(per_launch)]
    batch0 = np.asarray(first[0]["tokens"][0] if prestacked
                        else first[0]["tokens"])

    # the reference, before the state is donated: the loss of the first
    # step is the loss of the initial parameters on the first batch
    t = time.perf_counter()
    ref = family.loss(
        params, jax.device_put(jnp.asarray(batch0),
                               NamedSharding(mesh, PartitionSpec())), cfg_file)
    ref = {name: float(v) for name, v in ref.items()}
    setup["reference_check_s"] = time.perf_counter() - t

    state = {"stop": False, "t_zero": None, "trace_on": None, "trace_off": False}
    pending: List[Any] = []
    done: List[Dict[str, Any]] = []  # fenced launches: finish time and losses
    marks: Dict[str, Dict[str, Any]] = {}
    warmup = int(traffic["warmup_launches"])

    def fence(metrics) -> None:
        losses = np.asarray(metrics["loss"], np.float64).ravel()
        now = time.perf_counter()
        done.append({"t": now, "losses": losses})
        if len(done) == warmup:
            state["t_zero"] = now
            marks["open"] = {"wall": time.time(), **counter.snapshot()}
        t_zero = state["t_zero"]
        if t_zero is None or state["stop"]:
            return
        if now >= t_zero + seconds:
            state["stop"] = True
            marks["close"] = {"wall": time.time(), **counter.snapshot()}
        if config["trace"]:
            spec = traffic["trace"]
            if state["trace_on"] is None and now >= t_zero + min(
                    spec["start_s"], 0.4 * seconds):
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(config["trace_dir"],
                                         profiler_options=options)
                state["trace_on"] = now
            elif state["trace_on"] and not state["trace_off"] and \
                    now >= state["trace_on"] + min(spec["seconds"], 0.4 * seconds):
                jax.profiler.stop_trace()
                state["trace_off"] = True

    def on_launch(metrics) -> None:
        with jax.profiler.TraceAnnotation("bench:callback"):
            train.report({"loss": metrics["loss"]})  # what a user's loop does
            if pending:
                fence(pending.pop())
            pending.append(metrics)

    def groups() -> Iterator[Dict[str, Any]]:
        yield from first
        while not state["stop"]:
            for _ in range(per_launch):
                with jax.profiler.TraceAnnotation("bench:batch_fetch"):
                    item = next(feed, None)
                if item is None:
                    raise RuntimeError("the dataset ran out before the window "
                                       "did: raise max_launches_per_s")
                yield {"tokens": item["data"]}
        yield from first  # the probe: the first batch again, after training

    params, opt_state, _ = driver.run(params, opt_state, groups(),
                                      on_launch=on_launch, stacked=prestacked)
    fence(pending.pop())
    if state["trace_on"] and not state["trace_off"]:
        jax.profiler.stop_trace()
    reduced = None
    if config["trace"]:
        path = trace.newest_xplane(config["trace_dir"])
        reduced = path and trace.reduce_trace(path)

    t_zero = state["t_zero"]
    counted = [d for d in done[warmup:-1] if d["t"] <= t_zero + seconds]
    span = counted[-1]["t"] - t_zero if counted else 0.0
    every = np.concatenate([d["losses"] for d in done])
    rec = driver.recorder
    launches = [r for r in (rec.launches() if rec is not None else [])
                if "t_done" in r
                and marks["open"]["wall"] <= r["t"] < marks["close"]["wall"]]
    train.report({"bench": {
        "t_window_open_wall": marks["open"]["wall"],
        "setup": setup,
        "train": {
            "launches": len(counted), "steps": len(counted) * k,
            "tokens": len(counted) * k * batch * seq, "span_s": span,
            "first_loss": float(every[0]), "last_loss": float(every[-1 - k]),
            "probe_loss_after": float(done[-1]["losses"][0]),
            "finite": bool(np.isfinite(every).all()),
            "mesh": {a: int(n) for a, n in mesh.shape.items() if n > 1},
            "seq": seq, "batch": batch, "steps_per_launch": k},
        "recorder": {
            "span_s": (max(r["t_done"] for r in launches)
                       - min(r["t"] for r in launches)) if launches else 0.0,
            "data_wait_s": sum(r["phases"].get("data_wait", 0.0) for r in launches),
            "launch_gap_s": sum(r.get("gap_s", 0.0) for r in launches)},
        "reference": {"loss": ref["loss"], "ce": ref["ce"], "aux": ref["aux"]},
        "window_compiles": marks["close"]["programs"] - marks["open"]["programs"],
        "compile_s_at_window": marks["open"]["compile_s"],
        "trace": reduced,
        "device": {**model.device_facts(), **counter.snapshot(),
                   "backend_init_s": backend_init_s},
    }})
