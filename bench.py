"""Headline benchmark: Llama train-step throughput on a TPU.

Prints ONE JSON line: {"metric", "value", "unit", "details"}. Needs a TPU:
without one it exits non-zero, and a phase that raises fails the run.

Methodology: the headline is the MARGINAL per-step device rate from a
steps-sweep — run the jitted train loop at several step counts, each ending
with a host read of the loss, and fit ``wall = a + b * steps``. ``b`` is the
per-step time (tokens/s/chip = batch*seq/b), free of the async-dispatch
illusion and of the fixed per-run overhead ``a``. Dispatch and sustained
single-point rates are kept in details.

Phases (each in its own subprocess, because a chip belongs to one process
at a time and is released only when that process ends):
  1. steps-sweep per ladder rung -> rung selection by marginal model-FLOPs
     throughput,
  2. through-JaxTrainer run on the winner (product-path overhead),
  3. decode: bf16 KV-cache generate, batch sweep + marginal fit,
  4. RL: CPU EnvRunner fleet feeding an on-chip jitted learner,
  5. serve: 410m bf16 forward behind @serve.batch on the chip.
The trainer's worker and the serve replicas ask for the chip
(``tpu_chips_per_worker=1`` / ``num_tpus=1``); every other worker is held
to the CPU by the raylet.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time


def _mfu(tok_s_chip: float, preset: str, seq: int) -> float:
    """Model-FLOPs utilization from the SHARED analytic accounting
    (util/flops.py: 6N + causal-attention term over the peak on record for
    this process's device kind) — the same formula the step profiler
    reports, so bench and `rt profile` numbers agree on identical runs."""
    from ray_tpu.models import llama
    from ray_tpu.util import flops as F

    cfg = llama.PRESETS[preset]
    return round(tok_s_chip * F.train_flops_per_token(cfg, seq)
                 / F.peak_flops_per_chip(), 4)


def _bench_cfg(preset: str, attn_impl: str, loss_chunk: int,
               dtype: str = "fp32"):
    """Preset + bench overrides. dtype="bf16" stores params (and therefore
    adamw moments) in bfloat16 — the only way 1B+ params fit one 16GB chip
    (fp32 params+grads+m+v alone is ~16 bytes/param)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    over = dict(attn_impl=attn_impl, loss_chunk=loss_chunk)
    if dtype == "bf16":
        over["param_dtype"] = jnp.bfloat16
    return dataclasses.replace(llama.PRESETS[preset], **over)


def _setup_train_state(preset: str, batch: int, seq: int, attn_impl: str,
                       loss_chunk: int, dtype: str):
    """Shared setup for the raw-step phases: sharded state + jitted step +
    a device batch. Returns (step, params, opt_state, batch_data, n_dev,
    platform, cfg)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel import train_step as ts

    devices = jax.devices()
    n_dev = len(devices)
    platform = devices[0].platform

    cfg = _bench_cfg(preset, attn_impl, loss_chunk, dtype)
    seq = min(seq, cfg.max_seq_len)

    if n_dev > 1:
        mesh, _ = ts.auto_mesh(n_dev, devices)
    else:
        from ray_tpu.parallel.mesh import MeshConfig, make_mesh

        mesh = make_mesh(MeshConfig(), devices)

    optimizer = ts.default_optimizer(total_steps=1000)
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                              optimizer)
    step = ts.make_train_step(cfg, optimizer, mesh=mesh)

    rng = jax.random.key(1)
    tokens = jax.random.randint(rng, (batch, seq + 1), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    batch_data = ts.shard_batch({"tokens": tokens}, mesh)
    return step, params, opt_state, batch_data, n_dev, platform, cfg, seq


def run_sweep(preset: str, batch: int, seq: int, attn_impl: str = "xla",
              loss_chunk: int = 0, dtype: str = "fp32",
              budget_s: float = 150.0):
    """The steps-sweep: time the train loop at several step counts, each
    run ending with a host read of the loss, and fit wall = a + b*steps.

    b = marginal per-step seconds (the device rate); a = fixed per-run
    overhead (the final host-read round trip).
    """
    (step, params, opt_state, batch_data, n_dev, platform, cfg,
     seq) = _setup_train_state(preset, batch, seq, attn_impl, loss_chunk,
                               dtype)

    # Warmup / compile.
    params, opt_state, metrics = step(params, opt_state, batch_data)
    float(metrics["loss"])

    last_dispatch = [0.0]

    def timed(k: int) -> float:
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for _ in range(k):
            params, opt_state, m = step(params, opt_state, batch_data)
        last_dispatch[0] = time.perf_counter() - t0
        float(m["loss"])  # waits for the last step
        return time.perf_counter() - t0

    # Probe to budget the sweep: dt(3)/3 overestimates per-step time by
    # a/3, which only makes the chosen sweep smaller — safe direction.
    probe = timed(3)
    per_step_est = probe / 3
    base = max(1, min(10, int(budget_s / (15 * per_step_est))))
    ks = [base, 2 * base, 4 * base, 8 * base]
    walls = [timed(k) for k in ks]

    # Least-squares fit wall = a + b*steps (2 unknowns, 4 points).
    n = len(ks)
    mean_k = sum(ks) / n
    mean_w = sum(walls) / n
    b = (sum((k - mean_k) * (w - mean_w) for k, w in zip(ks, walls))
         / sum((k - mean_k) ** 2 for k in ks))
    a = mean_w - b * mean_k
    ss_res = sum((w - (a + b * k)) ** 2 for k, w in zip(ks, walls))
    ss_tot = sum((w - mean_w) ** 2 for w in walls) or 1e-12
    r2 = 1 - ss_res / ss_tot

    tok_per_step = batch * seq
    result = {
        "preset": preset, "platform": platform, "devices": n_dev,
        "batch": batch, "seq": seq, "attn": attn_impl,
        "param_dtype": dtype,
        "sweep_steps": ks,
        "sweep_walls_s": [round(w, 3) for w in walls],
        "fit_r2": round(r2, 5),
        "fixed_overhead_s": round(a, 3),
        "marginal_step_s": round(b, 4),
        "params_m": round(cfg.num_params() / 1e6, 1),
    }
    if b > 0:
        marg = tok_per_step / b / n_dev
        result["marginal_tok_s_chip"] = round(marg, 2)
        result["marginal_mfu"] = _mfu(marg, preset, seq)
    # Single-point sustained at the largest k, for continuity with r4's
    # sustained_* figures (includes a/k of fixed overhead), plus the
    # dispatch rate (clock stop before the host read — the r1-r4 ruler;
    # also the basis for Train-layer overhead, which is host-side work).
    sus = tok_per_step * ks[-1] / walls[-1] / n_dev
    result["sustained_tok_s_chip"] = round(sus, 2)
    result["sustained_mfu"] = _mfu(sus, preset, seq)
    if last_dispatch[0] > 0:
        result["dispatch_tok_s_chip"] = round(
            tok_per_step * ks[-1] / last_dispatch[0] / n_dev, 2)

    # Free the sweep's model+optimizer state BEFORE the scan leg builds
    # its own: the largest rung runs near HBM capacity, and two live
    # copies would OOM exactly at the headline-selecting configs.
    del params, opt_state, batch_data, step, metrics
    import gc

    gc.collect()

    # Multi-step scan leg: K optimizer steps fused into ONE compiled
    # program (parallel/train_step.py:make_multi_step). Its 2-point
    # marginal strips per-RUN overhead like the sweep; the DELTA between
    # the single-step marginal b and the scan per-step time is the
    # per-LAUNCH overhead (dispatch per executable), which black-box
    # single-step timing cannot separate from device time. The scan rate
    # is also the
    # honest best product configuration for launch-bound loops.
    try:
        import jax
        import jax.numpy as jnp

        from ray_tpu.parallel import train_step as ts

        K = max(2, min(8, int(20.0 / max(b, 0.05))))
        optimizer = ts.default_optimizer(total_steps=1000)
        cfg2 = _bench_cfg(preset, attn_impl, loss_chunk, dtype)
        sq = min(seq, cfg2.max_seq_len)
        from ray_tpu.parallel.mesh import MeshConfig, make_mesh

        devices = jax.devices()
        mesh = (ts.auto_mesh(len(devices), devices)[0] if len(devices) > 1
                else make_mesh(MeshConfig(), devices))
        p2, s2 = ts.init_sharded_state(jax.random.key(0), cfg2, mesh,
                                       optimizer)
        multi = ts.make_multi_step(cfg2, optimizer, K, mesh=mesh)
        toks = jax.random.randint(jax.random.key(2), (K, batch, sq + 1),
                                  0, cfg2.vocab_size, dtype=jnp.int32)
        bd = ts.shard_batch({"tokens": toks}, mesh, stacked=True)
        # warm up TWICE: the first call compiles for the freshly-initialized
        # leaf types; the second compiles for the post-update types (weak-
        # type/donation churn) — timing must start only once stable
        for _ in range(2):
            p2, s2, m2 = multi(p2, s2, bd)
            float(m2["loss"][-1])

        def scan_timed(calls: int) -> float:
            nonlocal p2, s2
            t0 = time.perf_counter()
            for _ in range(calls):
                p2, s2, m = multi(p2, s2, bd)
            float(m["loss"][-1])
            return time.perf_counter() - t0

        w1 = scan_timed(1)
        w3 = scan_timed(3)
        if w3 <= w1:
            result["scan_error"] = (f"non-monotone scan timing "
                                    f"w1={w1:.4f} w3={w3:.4f}")
        if w3 > w1:
            scan_step_s = (w3 - w1) / (2 * K)
            scan_tok_s = tok_per_step / scan_step_s / n_dev
            result["scan_steps_per_call"] = K
            result["scan_step_s"] = round(scan_step_s, 4)
            result["scan_tok_s_chip"] = round(scan_tok_s, 2)
            result["scan_mfu"] = _mfu(scan_tok_s, preset, seq)
            if b > 0:
                result["per_launch_overhead_s"] = round(
                    max(0.0, b - scan_step_s), 4)
    except Exception as e:  # noqa: BLE001 — scan leg is additive evidence
        result["scan_error"] = str(e)[:200]
    return result


def _sweep_main() -> None:
    """Subprocess phase: one steps-sweep rung. Config via RT_BENCH_SWEEP_CFG
    (JSON); prints SWEEPBENCH={...}."""
    cfg = json.loads(os.environ["RT_BENCH_SWEEP_CFG"])
    try:
        out = run_sweep(cfg["preset"], cfg["batch"], cfg["seq"],
                        cfg.get("attn", "xla"), cfg.get("loss_chunk", 0),
                        cfg.get("dtype", "fp32"),
                        budget_s=cfg.get("budget_s", 150.0))
    except Exception as e:  # noqa: BLE001
        if not _is_oom(e):
            raise
        # a rung that does not fit the chip: the ladder goes on to the next
        out = {"error": str(e)[:300]}
    print("SWEEPBENCH=" + json.dumps(out))


def _bench_train_loop(config):
    """Runs inside the JaxTrainer worker actor: the PRODUCT path — data via
    ``get_dataset_shard(...).iter_batches`` feeding the jitted sharded step,
    per-run ``train.report``. Timed region excludes compile/warmup."""
    import time as _time

    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = _bench_cfg(config["preset"], config["attn"],
                     config.get("loss_chunk", 0),
                     config.get("dtype", "fp32"))
    devices = jax.devices()
    mesh = make_mesh(MeshConfig(), devices)
    optimizer = ts.default_optimizer(total_steps=1000)
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                              optimizer)
    step = ts.make_train_step(cfg, optimizer, mesh=mesh)

    shard = train.get_dataset_shard("train")
    it = shard.iter_batches(batch_size=config["batch"], drop_last=True,
                            prefetch_batches=2)
    first = next(it)["data"]
    bd = ts.shard_batch({"tokens": jnp.asarray(first)}, mesh)
    params, opt_state, metrics = step(params, opt_state, bd)  # compile
    float(metrics["loss"])

    # dispatch-rate (prior rounds' methodology) AND the host-synced
    # sustained rate — see run_sweep for the marginal methodology that
    # supersedes both as the headline
    t0 = _time.perf_counter()
    n_tok = steps_done = 0
    for b in it:
        arr = b["data"]
        bd = ts.shard_batch({"tokens": jnp.asarray(arr)}, mesh)
        params, opt_state, metrics = step(params, opt_state, bd)
        n_tok += arr.shape[0] * (arr.shape[1] - 1)
        steps_done += 1
    dt = _time.perf_counter() - t0
    final_loss = float(metrics["loss"])  # waits for the last step
    dt_synced = _time.perf_counter() - t0
    train.report({
        "tok_s_chip": n_tok / dt / len(devices),
        "sustained_tok_s_chip": n_tok / dt_synced / len(devices),
        "loss": final_loss,
        "steps": steps_done,
        "platform": devices[0].platform,
        "devices": len(devices),
    })


def run_through_train(preset: str, batch: int, seq: int, steps: int,
                      attn_impl: str = "xla", loss_chunk: int = 0,
                      dtype: str = "fp32"):
    """Tokens/sec/chip measured through the Train layer (BASELINE.md's 'Ray
    Train tokens/sec/chip'): JaxTrainer gang + ray_tpu.data iter_batches feed.
    The TPU is claimed by the worker subprocess, so the caller must not have
    initialized the jax backend."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.train import JaxTrainer, ScalingConfig

    from ray_tpu.models import llama

    cfg = llama.PRESETS[preset]
    seq = min(seq, cfg.max_seq_len)
    rows = (steps + 1) * batch
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (rows, seq + 1)).astype(np.int32)

    ray_tpu.init(num_cpus=2)
    try:
        trainer = JaxTrainer(
            _bench_train_loop,
            train_loop_config={"preset": preset, "batch": batch,
                               "attn": attn_impl, "loss_chunk": loss_chunk,
                               "dtype": dtype},
            scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1,
                                         tpu_chips_per_worker=1),
            datasets={"train": rt_data.from_numpy(tokens)})
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()
    return dict(result.metrics or {})


def _train_main() -> None:
    """Subprocess phase: through-JaxTrainer product-path run. Config via
    RT_BENCH_TRAIN_CFG (JSON); prints TRAINBENCH={...}."""
    cfg = json.loads(os.environ["RT_BENCH_TRAIN_CFG"])
    out = run_through_train(cfg["preset"], cfg["batch"], cfg["seq"],
                            cfg.get("steps", 12), cfg.get("attn", "xla"),
                            cfg.get("loss_chunk", 0),
                            cfg.get("dtype", "fp32"))
    print("TRAINBENCH=" + json.dumps(out))


def _fast_raw_leg(preset: str, batch: int, seq: int, steps: int, k: int):
    """Raw single-process sustained rate at steps_per_launch=k: the
    same-work in-process control the Train layer is judged against (NOT a
    strict ceiling — it synthesizes batches inline on the loop thread,
    where the product data plane prefetches ahead). StepDriver over
    synthetic host batches, warmup (compile + donation-type churn)
    excluded, final host read drains the queue."""
    import numpy as np

    import jax

    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.driver import StepDriver

    cfg = _bench_cfg(preset, "xla", 0)
    seq = min(seq, cfg.max_seq_len)
    devices = jax.devices()
    mesh = (ts.auto_mesh(len(devices), devices)[0] if len(devices) > 1
            else make_mesh(MeshConfig(), devices))
    optimizer = ts.default_optimizer(total_steps=10000)
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                              optimizer)
    driver = StepDriver(cfg, optimizer, mesh=mesh, steps_per_launch=k)
    rng = np.random.default_rng(1)

    def batches(n):
        for _ in range(n):
            yield {"tokens": rng.integers(
                0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)}

    # warmup: two launch cycles (first compiles, second runs on post-update
    # leaf types) + one ragged single step so both programs are compiled
    params, opt_state, m = driver.run(params, opt_state, batches(2 * k + 1))
    float(m["loss"] if m["loss"].ndim == 0 else m["loss"][-1])
    cache_warm = driver.compile_count()
    driver.reset_attribution()  # ratio must describe the timed region only
    t0 = time.perf_counter()
    params, opt_state, m = driver.run(params, opt_state, batches(steps))
    loss = m["loss"] if m["loss"].ndim == 0 else m["loss"][-1]
    final = float(loss)  # host read: drains the execution queue
    wall = time.perf_counter() - t0
    return {
        "steps_per_launch": k, "steps": steps,
        "wall_s": round(wall, 4),
        "sustained_tok_s_chip": round(
            steps * batch * seq / wall / len(devices), 2),
        "host_overhead_ratio": driver.report()["host_overhead_ratio"],
        "launches": driver.launches, "loss": round(final, 4),
        "fused_jit_cache": driver.compile_count(),
        # single-launch assertion: the timed region must add ZERO compiles
        "jit_cache_growth_timed": driver.compile_count() - cache_warm,
    }


def _fast_train_loop(config):
    """Product-path loop (runs inside the JaxTrainer worker): StepDriver
    with the session-configured steps_per_launch, fed by the dataset
    shard's stacked jax-batch iterator; sustained rate measured in-loop
    post-warmup. ``report_checkpoints`` turns on per-launch report +
    async/sync pytree checkpointing (the offload-delta legs)."""
    import tempfile
    import time as _time

    import jax

    from ray_tpu import train
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.driver import StepDriver

    cfg = _bench_cfg(config["preset"], "xla", 0)
    batch, seq = config["batch"], config["seq"]
    k = train.get_fast_path().steps_per_launch
    devices = jax.devices()
    mesh = make_mesh(MeshConfig(), devices)
    optimizer = ts.default_optimizer(total_steps=10000)
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                              optimizer)
    driver = StepDriver(cfg, optimizer, mesh=mesh)

    shard = train.get_dataset_shard("train")
    it = shard.iter_jax_batches(
        batch_size=batch, drop_last=True, stack=k,
        prefetch_batches=train.get_fast_path().prefetch_batches)

    class _TokenFeed:
        """from_numpy yields {"data": ...}; the loss wants {"tokens": ...}.
        Keeps the iterator's ``stack`` advertisement for the driver."""

        stack = it.stack

        def __iter__(self):
            return ({"tokens": b["data"]} for b in it)

    def on_launch(metrics):
        if not config.get("report_checkpoints"):
            return
        ckpt = Checkpoint.from_directory(tempfile.mkdtemp(prefix="rt_fb_"))
        # driver.state is the POST-launch params (pre-launch buffers were
        # donated); blocking resolves from FastPathConfig.async_checkpoint
        # (async snapshots on-device before the next launch)
        ckpt.save_pytree(driver.state[0], "state")
        train.report({"loss": metrics["loss"]}, checkpoint=ckpt)

    # warmup: the first 2 launches compile; time the rest
    warm = config.get("warmup_steps", 2 * k)
    warm_it = iter(_TokenFeed())
    warm_batches = [next(warm_it) for _ in range(max(1, warm // k))]
    params, opt_state, m = driver.run(params, opt_state, iter(warm_batches),
                                      stacked=k > 1)
    float(jax.numpy.ravel(m["loss"])[-1])
    driver.reset_attribution()  # ratio must describe the timed region only

    t0 = _time.perf_counter()
    n_steps_before = driver.steps
    params, opt_state, m = driver.run(params, opt_state, warm_it,
                                      on_launch=on_launch, stacked=k > 1)
    final = float(jax.numpy.ravel(m["loss"])[-1])  # drains the queue
    wall = _time.perf_counter() - t0
    steps_timed = driver.steps - n_steps_before
    train.report({
        "sustained_tok_s_chip": steps_timed * batch * seq / wall
        / len(devices),
        "steps": steps_timed, "wall_s": wall, "loss": final,
        "steps_per_launch": driver.steps_per_launch,
        "host_overhead_ratio": driver.report()["host_overhead_ratio"],
        "fused_jit_cache": driver.compile_count(),
        "data_plane": it.report(),
    })


def _fast_through_train_leg(preset: str, batch: int, seq: int, steps: int,
                            k: int, report_checkpoints: bool = False,
                            sync_mode: bool = False):
    """Through-JaxTrainer sustained rate at steps_per_launch=k — the
    product path: gang + dataset feed + session reporting. ``sync_mode``
    is the offload-delta control: synchronous report coercion + blocking
    checkpoint saves on the step loop."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data
    from ray_tpu.models import llama
    from ray_tpu.train import (FastPathConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    cfg = llama.PRESETS[preset]
    seq = min(seq, cfg.max_seq_len)
    warmup = 2 * k
    # sized so the timed region is EXACTLY `steps` optimizer steps when
    # k divides steps (the sweep uses k ∈ {1,4,16}, steps = 64)
    rows = (steps + warmup) * batch
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (rows, seq + 1)).astype(np.int32)

    owns = not ray_tpu.is_initialized()
    if owns:
        ray_tpu.init(num_cpus=2)
    try:
        trainer = JaxTrainer(
            _fast_train_loop,
            train_loop_config={"preset": preset, "batch": batch, "seq": seq,
                               "warmup_steps": warmup,
                               "report_checkpoints": report_checkpoints},
            scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1,
                                         tpu_chips_per_worker=1),
            run_config=RunConfig(fast_path=FastPathConfig(
                steps_per_launch=k, async_report=not sync_mode,
                async_checkpoint=not sync_mode)),
            datasets={"train": rt_data.from_numpy(tokens)})
        result = trainer.fit()
    finally:
        if owns:
            ray_tpu.shutdown()
    return dict(result.metrics or {})


def _train_fast_main() -> None:
    """Fused-K fast-path A/B phase (ROADMAP item 2, the TRAIN_r09
    artifact): raw single-process sustained vs through-JaxTrainer
    sustained at EQUAL work, K-sweep over steps_per_launch {1,4,16}
    (launch amortization), and the report/checkpoint-offload delta
    isolated as its own pair of legs. Config via RT_BENCH_TRAIN_FAST_CFG
    (JSON); prints TRAINFASTBENCH={...} and optionally writes ``out``.
    """
    cfg = json.loads(os.environ.get("RT_BENCH_TRAIN_FAST_CFG", "{}"))
    preset = cfg.get("preset", "debug")
    batch = cfg.get("batch", 4)
    seq = cfg.get("seq", 32)
    steps = cfg.get("steps", 64)
    ks = cfg.get("ks", [1, 4, 16])
    out: dict = {
        "preset": preset, "batch": batch, "seq": seq, "steps": steps,
        "methodology": (
            "equal work = "
            "identical preset/batch/seq and the same count of TIMED "
            "optimizer steps per leg, warmup/compile excluded, each timed "
            "region closed by a host read of the last loss. "
            "raw = StepDriver in-process on synthetic host "
            "batches; through_train "
            "= the full JaxTrainer product path (gang actor + dataset "
            "shard feed + session reporting). offload legs add a "
            "per-launch report carrying a params checkpoint: async = "
            "drainer-thread coercion + non-blocking orbax save (product "
            "default), sync = coercion and save on the step loop "
            "(control). Launch amortization reads from the K sweep; with "
            "per-step wall c + L/K (L = per-launch overhead), "
            "L = (wall(1)/steps - wall(K)/steps) * K/(K-1)."),
    }
    # The JaxTrainer legs go first: their worker holds the chip and gives
    # it back when its gang shuts down. The raw legs run in THIS process,
    # which keeps the chip from its first jax call to its exit.
    through = {}
    for k in ks:
        through[str(k)] = _fast_through_train_leg(
            preset, batch, seq, steps, k)
    out["through_train"] = through
    k_prod = str(ks[-1])
    # offload delta: per-launch report+checkpoint, async vs sync
    k_off = int(k_prod)
    async_leg = _fast_through_train_leg(
        preset, batch, seq, steps, k_off, report_checkpoints=True)
    sync_leg = _fast_through_train_leg(
        preset, batch, seq, steps, k_off, report_checkpoints=True,
        sync_mode=True)
    out["offload"] = {
        "async": async_leg, "sync": sync_leg,
        "delta_tok_s_chip": round(
            async_leg["sustained_tok_s_chip"]
            - sync_leg["sustained_tok_s_chip"], 2),
        "speedup": round(async_leg["sustained_tok_s_chip"]
                         / max(1e-9, sync_leg["sustained_tok_s_chip"]),
                         4),
    }
    raw = {str(k): _fast_raw_leg(preset, batch, seq, steps, k)
           for k in ks}
    out["raw"] = raw
    ratio = (through[k_prod]["sustained_tok_s_chip"]
             / raw[k_prod]["sustained_tok_s_chip"])
    out["through_vs_raw_ratio"] = round(ratio, 4)
    # per-launch overhead attribution from the raw K sweep: with
    # per-step wall c + L/k, the K=1 vs K=k delta is L*(k-1)/k, so
    # the per-LAUNCH overhead is delta * k/(k-1)
    per_step = {k: r["wall_s"] / r["steps"] for k, r in raw.items()}
    if "1" in per_step:
        out["per_launch_overhead_s"] = {
            k: round(max(0.0, (per_step["1"] - v) * int(k)
                         / (int(k) - 1)), 5)
            for k, v in per_step.items() if k != "1"}
    # dispatch-bound raw mini-sweep: at the A/B shape compute dominates
    # and the amortization delta drowns in noise; the small shape is
    # where per-launch overhead is actually visible (the same reason
    # PR 12 measured Anakin at the dispatch-bound shape)
    db_batch, db_seq = cfg.get("db_batch", 2), cfg.get("db_seq", 16)
    db = {str(k): _fast_raw_leg(preset, db_batch, db_seq, steps, k)
          for k in ks}
    db_step = {k: r["wall_s"] / r["steps"] for k, r in db.items()}
    out["raw_dispatch_bound"] = {
        "batch": db_batch, "seq": db_seq, "legs": db,
        "per_launch_overhead_s": {
            k: round(max(0.0, (db_step["1"] - v) * int(k)
                         / (int(k) - 1)), 5)
            for k, v in db_step.items() if k != "1"},
        "fused_speedup": {
            k: round(db_step["1"] / v, 3)
            for k, v in db_step.items() if k != "1"},
    }
    if cfg.get("out"):
        with open(cfg["out"], "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
    print("TRAINFASTBENCH=" + json.dumps(out))


def _rl_main() -> None:
    """RL throughput phase (BASELINE.md config 4, the other half of the
    north-star metric): PPO + IMPALA env-steps/sec through the full product
    path — CPU EnvRunner fleet sampling (the raylet holds workers that were
    granted no chip to the CPU), the learner's jitted update on THIS
    process's jax backend, the chip.
    Prints one JSON line: RLBENCH={...}.
    """
    import ray_tpu
    from ray_tpu import rl

    out = {}
    ray_tpu.init(num_cpus=6)
    try:
        for name, config in (
            ("ppo", rl.PPOConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=2, num_envs_per_runner=16,
                             rollout_fragment_length=64)
                .training(minibatch_size=512, num_epochs=2)
                .debugging(seed=0)),
            ("impala", rl.IMPALAConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=2, num_envs_per_runner=16,
                             rollout_fragment_length=64)
                .training(minibatch_size=512)
                .debugging(seed=0)),
        ):
            algo = config.build()
            try:
                algo.train()  # warmup: actor spawn + XLA compiles
                t0 = time.perf_counter()
                steps0 = algo._env_steps_total
                iters = 0
                while iters < 12 and time.perf_counter() - t0 < 60:
                    algo.train()
                    iters += 1
                dt = time.perf_counter() - t0
                out[f"{name}_env_steps_per_sec"] = round(
                    (algo._env_steps_total - steps0) / dt, 1)
                out[f"{name}_iters"] = iters
            finally:
                algo.stop()
        # The learner jits in THIS process: record which platform its
        # update actually ran on (the judge's platform:"tpu" check).
        import jax

        out["rl_learner_platform"] = jax.devices()[0].platform
    finally:
        ray_tpu.shutdown()
    print("RLBENCH=" + json.dumps(out))


def _rlhf_main() -> None:
    """RLHF phase (ROADMAP item 5): two legs, one JSON line
    RLHFBENCH={...}.

    A) Anakin fused rollout (``rl/anakin.py`` — env + policy + learner
       in ONE launch) vs the host-loop EnvRunner path, env-steps/s at
       equal work (rollout + GAE + update both legs; warmup iterations
       double as CPU dispatch-jitter dry runs).
    B) One full RLHF iteration end-to-end: placed policy / reference /
       reward / generator roles, generate phase on ContinuousEngine
       slots, PPO-style sequence update, weight sync over stream oid
       frames with the drain-barrier engine swap — tok/s, sync bytes +
       seconds and the engine's monotonic counters are the evidence.
    """
    out: dict = {}
    cfgd = json.loads(os.environ.get("RT_BENCH_RLHF_CFG", "{}"))
    from ray_tpu.rl.anakin import bench_fused_vs_host

    # primary point: long-T, small-B — the dispatch-dominated shape
    # where the host loop pays T sequential dispatch+readback
    # round-trips per fragment and the fused launch pays one. On
    # CPU this is where the Anakin win lives; on a real mesh the
    # batch axis shards over chips on top of it.
    out["anakin"] = bench_fused_vs_host(
        num_envs=int(cfgd.get("num_envs", 8)),
        rollout_len=int(cfgd.get("rollout_len", 256)),
        iters=int(cfgd.get("iters", 12)),
        warmup=int(cfgd.get("warmup", 4)))
    # secondary point: a throughput shape where numpy vectorization
    # amortizes the host loop's per-step cost — reported so the
    # artifact shows WHERE the fused advantage comes from instead
    # of cherry-picking one ratio
    out["anakin_large_batch"] = bench_fused_vs_host(
        num_envs=int(cfgd.get("num_envs_large", 128)),
        rollout_len=int(cfgd.get("rollout_len_large", 32)),
        iters=int(cfgd.get("iters", 12)),
        warmup=int(cfgd.get("warmup", 4)))

    import ray_tpu
    from ray_tpu.rl.rlhf import RLHFPipeline

    # the debug preset's largest leaf (64 KiB embed) sits exactly at
    # the default inline threshold — lower it so the weight shipment
    # exercises the plasma oid-frame path the production presets
    # (MB-scale leaves) hit naturally; workers inherit the env from
    # the in-proc cluster spawn
    os.environ.setdefault("RT_STREAM_INLINE_MAX", "16384")
    ray_tpu.init(num_cpus=6)
    try:
        pipeline = RLHFPipeline(
            preset=cfgd.get("preset", "debug"),
            num_prompts=int(cfgd.get("prompts", 4)),
            prompt_len=int(cfgd.get("prompt_len", 8)),
            max_new_tokens=int(cfgd.get("max_new", 16)),
            max_slots=int(cfgd.get("slots", 4)))
        try:
            iters = [pipeline.run_iteration()
                     for _ in range(int(cfgd.get("rlhf_iters", 2)))]
            last = iters[-1]
            eng = ray_tpu.get(
                pipeline.group["generator"].engine_stats.remote())
            # flight-recorder evidence (util/pipeline_recorder.py):
            # bubble fraction, per-role idle attribution, staleness
            # profile, the joined ship->fetch->barrier->swap receipt
            # and the recorder's own self-timed overhead
            rec = pipeline.recorder.summary()
            out["rlhf"] = {
                "preset": pipeline.cfg.preset,
                "iterations": len(iters),
                "generate_tok_s": last["generate_tok_s"],
                "tokens_generated_total": eng["tokens_generated"],
                "requests_completed_total": eng["requests_completed"],
                "weight_syncs": eng["weight_swaps"],
                "sync_transport": last["sync_transport"],
                "sync_bytes_per_round": last["sync_bytes"],
                "sync_oid_leaves": last["sync_oid_leaves"],
                "sync_inline_max_bytes": int(os.environ.get(
                    "RT_STREAM_INLINE_MAX", str(64 * 1024))),
                "sync_s": last["sync_s"],
                "swap_drain_s": last["swap_drain_s"],
                "phases_s": last["phases_s"],
                "phases_actor_s": last.get("phases_actor_s", {}),
                "bubble_fraction": last.get("bubble_fraction"),
                "coverage": last.get("coverage"),
                "staleness": last.get("staleness"),
                "receipt": last.get("receipt", {}),
                "recorder": {
                    "bubble_fraction": rec.get("bubble_fraction"),
                    "bubble_last": rec.get("bubble_last"),
                    "coverage": rec.get("coverage"),
                    "role_busy_frac": rec.get("role_busy_frac"),
                    "role_idle_frac": rec.get("role_idle_frac"),
                    "tax_s": rec.get("tax_s"),
                    "staleness": rec.get("staleness"),
                    "overhead_frac": rec.get("overhead_frac"),
                },
                "trace_id": pipeline.trace_id,
                "placement": pipeline.group.describe(),
            }
        finally:
            pipeline.shutdown()
    finally:
        ray_tpu.shutdown()

    import jax

    out["platform"] = jax.devices()[0].platform
    # self-preservation: refresh the artifact the moment the phase has
    # numbers (RT_BENCH_PRESERVE; no-op when unset)
    _preserve({"rlhf_phase": out})
    print("RLHFBENCH=" + json.dumps(out))


def _preserve(payload: dict, path: str = "") -> None:
    """Write/refresh an artifact right after a successful phase, so a later
    failure cannot forfeit numbers already measured. Atomic tmp+rename;
    target comes from RT_BENCH_PRESERVE or an explicit ``path``."""
    path = path or os.environ.get("RT_BENCH_PRESERVE", "")
    if not path:
        return
    try:
        payload = dict(payload)
        payload["preserved_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                time.gmtime())
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=1)
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 — preservation never fails a run
        import sys

        print(f"bench: preserve failed: {e!r}", file=sys.stderr)


def _run_phase(env_var: str, prefix: str, timeout: float,
               env: dict | None = None, extra_env: dict | None = None):
    """Run this script as a subprocess phase (env_var set) and return the
    dict of its ``PREFIX={json}`` stdout line; a phase that times out, dies
    or prints no such line fails the run. Default env: held to the CPU (the
    data phase and the obs rounds, which claim no device metric). Pass
    ``env`` for the phases that own the chip."""
    import subprocess
    import sys

    env = dict(env) if env is not None else _cpu_env()
    # Strip inherited phase markers (the inner orchestrator carries
    # RT_BENCH_INNER=1 — a child inheriting it would recurse into
    # _inner_main instead of running its own phase).
    for marker in ("RT_BENCH_INNER", "RT_BENCH_SWEEP", "RT_BENCH_TRAIN",
                   "RT_BENCH_TRAIN_FAST", "RT_BENCH_DECODE", "RT_BENCH_RL",
                   "RT_BENCH_SERVE", "RT_BENCH_CB", "RT_BENCH_DATA",
                   "RT_BENCH_RLHF", "RT_BENCH_ENGINE",
                   "RT_BENCH_TRAIN_OBS"):
        env.pop(marker, None)
    env[env_var] = "1"
    if extra_env:
        env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        cwd=_REPO_ROOT, capture_output=True, text=True, timeout=timeout)
    for ln in reversed(proc.stdout.splitlines()):
        if ln.startswith(prefix + "="):
            return json.loads(ln[len(prefix) + 1:])
    raise RuntimeError(f"bench: {prefix} phase failed rc={proc.returncode}: "
                       f"{proc.stderr[-2000:]}")


def _serve_main() -> None:
    """Serve phase (BASELINE.md config 5): the flagship model's jax.jit
    forward behind ``@serve.batch`` — the replica actor owns the chip when
    this phase runs on the native backend (the driver never initializes
    jax). Reports true p50/p99 over ~200 samples plus batched token
    throughput. Prints one JSON line SERVEBENCH={...}."""
    import numpy as np
    import requests

    import ray_tpu
    from ray_tpu import serve

    # Chosen by the orchestrator: big model on the chip, debug on CPU CI.
    preset = os.environ.get("RT_BENCH_SERVE_PRESET", "debug")
    dtype = os.environ.get("RT_BENCH_SERVE_DTYPE", "fp32")
    seq = 128 if preset != "debug" else 32
    n_samples = 200

    out = {}
    ray_tpu.init(num_cpus=4)
    try:
        @serve.deployment(max_ongoing_requests=32,
                          ray_actor_options={"num_tpus": 1})
        class Scorer:
            SEQ = seq

            def __init__(self):
                import jax

                self._jax = jax
                cfg = _bench_cfg(preset, "xla", 0, dtype)
                from ray_tpu.models import llama

                self.params = llama.init_params(jax.random.key(0), cfg)
                self._fwd = jax.jit(
                    lambda p, t: llama.forward(p, t, cfg))
                self.platform = jax.devices()[0].platform

            @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.005)
            async def score(self, bodies):
                import jax.numpy as jnp

                # Pad to the max batch size: ONE compiled shape serves
                # every batch occupancy (otherwise each distinct batch
                # size triggers its own XLA compile and wrecks the tail).
                toks = np.zeros((8, self.SEQ), dtype=np.int32)
                lens = []
                for i, body in enumerate(bodies):
                    t = body["tokens"][:self.SEQ]
                    toks[i, :len(t)] = t
                    lens.append(len(t))
                logits = self._fwd(self.params, jnp.asarray(toks))
                arr = np.asarray(logits)  # one host read per batch
                return [{"next": int(arr[i, lens[i] - 1].argmax()),
                         "platform": self.platform}
                        for i in range(len(bodies))]

            async def __call__(self, request):
                return await self.score(request.json())

        serve.run(Scorer.bind(), name="bench_scorer",
                  route_prefix="/score")
        port = serve.http_port()
        url = f"http://127.0.0.1:{port}/score"
        body = {"tokens": list(range(seq))}
        for _ in range(5):  # warmup: replica spawn + XLA compile
            r = requests.post(url, json=body, timeout=600)
            r.raise_for_status()
        out["serve_platform"] = r.json().get("platform", "?")
        out["serve_preset"] = preset
        out["serve_dtype"] = dtype
        out["serve_seq"] = seq

        # latency + throughput under concurrent load (8 in flight — the
        # shape @serve.batch fuses into full batches); per-request
        # latencies give a true percentile over ~200 samples. A transient
        # failed request must not discard the other 199 measurements.
        from concurrent.futures import ThreadPoolExecutor

        def one(_):
            t0 = time.perf_counter()
            try:
                requests.post(url, json=body, timeout=600).raise_for_status()
            except Exception:  # noqa: BLE001
                return None
            return time.perf_counter() - t0

        with ThreadPoolExecutor(max_workers=8) as pool:
            t_all = time.perf_counter()
            lat = list(pool.map(one, range(n_samples)))
            wall = time.perf_counter() - t_all
        ok = [x for x in lat if x is not None]
        if not ok:
            raise RuntimeError("all concurrent serve requests failed")
        lat_ms = sorted(x * 1000 for x in ok)
        out["serve_p50_ms"] = round(lat_ms[len(lat_ms) // 2], 1)
        out["serve_p99_ms"] = round(
            lat_ms[max(0, int(len(lat_ms) * 0.99) - 1)], 1)
        out["serve_rps"] = round(len(ok) / wall, 1)
        out["serve_tok_s"] = round(len(ok) * seq / wall, 1)
        out["serve_samples"] = len(ok)
        if len(ok) < n_samples:
            out["serve_failed_requests"] = n_samples - len(ok)
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()
    print("SERVEBENCH=" + json.dumps(out))


def _decode_main() -> None:
    """Decode phase (RT_BENCH_DECODE_CFG): bf16 KV-cache generate with a
    batch sweep and a two-length marginal fit at the middle batch size
    (same fixed-overhead separation as the train sweep). Decode MFU uses
    the 2*N fwd-only FLOPs estimate. Prints DECODEBENCH={...}."""
    import jax
    import jax.numpy as jnp
    import numpy as _np

    from ray_tpu.models import generate as gen
    from ray_tpu.models import llama

    cfgd = json.loads(os.environ["RT_BENCH_DECODE_CFG"])
    preset, dtype = cfgd["preset"], cfgd.get("dtype", "bf16")
    prompt_len = cfgd.get("prompt_len", 128)
    batches = cfgd.get("batches", [1, 8, 32])
    new_tokens = cfgd.get("new_tokens", 64)

    out = {"decode_preset": preset, "decode_dtype": dtype,
           "decode_new_tokens": new_tokens}
    cfg = _bench_cfg(preset, "xla", 0, dtype)  # decode uses xla attn
    params = llama.init_params(jax.random.key(0), cfg)
    platform = jax.devices()[0].platform
    out["decode_platform"] = platform
    from ray_tpu.util import flops as F

    # shared accounting (util/flops.py): decode flops at the mean
    # live context over the peak on record for this device kind
    flops_per_tok = F.decode_flops_per_token(
        cfg, prompt_len + new_tokens / 2)
    peak = F.peak_flops_per_chip()

    def timed(batch: int, n_new: int, seed: int) -> float:
        prompt = jax.random.randint(jax.random.key(seed),
                                    (batch, prompt_len), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        t0 = time.perf_counter()
        res = gen.generate(params, prompt, cfg, max_new_tokens=n_new)
        _np.asarray(res)  # host read genuinely blocks
        return time.perf_counter() - t0

    sweep = {}
    for b in batches:
        try:
            timed(b, new_tokens, seed=b)  # compile + warmup
            dt = timed(b, new_tokens, seed=100 + b)
            tok_s = b * new_tokens / dt
            sweep[str(b)] = {
                "tok_s": round(tok_s, 1),
                "mfu": round(tok_s * flops_per_tok / peak, 4)}
        except Exception as e:  # noqa: BLE001 — keep smaller batches
            sweep[str(b)] = {"error": str(e)[:200]}
            break
    out["decode_batch_sweep"] = sweep
    # Headline keys from the sweep FIRST: a marginal-fit failure below
    # must not discard measurements already in hand.
    ok_batches = [int(k) for k, v in sweep.items() if "tok_s" in v]
    out["decode_tok_s"] = max(
        (v["tok_s"] for v in sweep.values() if "tok_s" in v),
        default=0.0)
    out["decode_batch"] = max(ok_batches, default=0)

    # Marginal per-token rate at the largest batch that succeeded:
    # two generate lengths, same prompt shape; (dt_long - dt_short)
    # strips the prefill + fixed per-call overhead shared by both.
    if ok_batches:
        try:
            mid = max(ok_batches)
            short = max(8, new_tokens // 4)
            timed(mid, short, seed=mid)  # compile the short-scan shape
            dt_short = timed(mid, short, seed=200 + mid)
            dt_long = timed(mid, new_tokens, seed=300 + mid)
            if dt_long > dt_short:
                marg = mid * (new_tokens - short) / (dt_long - dt_short)
                out["decode_marginal_tok_s"] = round(marg, 1)
                out["decode_marginal_mfu"] = round(
                    marg * flops_per_tok / peak, 4)
                out["decode_marginal_batch"] = mid
        except Exception as e:  # noqa: BLE001 — sweep keys stand
            out["decode_marginal_error"] = str(e)[:200]

    # Speculative-decoding leg (models/generate.py:
    # generate_speculative): a small draft proposes, the target
    # verifies k+1 positions per launch — the decode-side
    # launch-amortization story (the scan leg is the train-side one).
    # B=1 (the latency case), greedy-exact.
    try:
        draft_preset = cfgd.get("draft_preset",
                                {"410m": "160m", "1b": "160m",
                                 "160m": "debug",
                                 "debug": "debug_draft"}.get(
                                     preset, "debug_draft"))
        dcfg = _bench_cfg(draft_preset, "xla", 0, dtype)
        out["decode_spec_draft"] = draft_preset
        if dcfg == cfg:
            # A draft that IS the target measures nothing: every
            # launch costs a full target forward, so the "speedup"
            # is a guaranteed ~1/(k+1) slowdown dressed as data
            # (r05 shipped 0.33 exactly this way). Refuse the key.
            out["decode_spec_skipped"] = (
                f"draft preset {draft_preset!r} resolves to the "
                f"target config — no honest speedup measurable")
        else:
            out["decode_spec_draft_params_m"] = round(
                dcfg.num_params() / 1e6, 2)
            dparams = llama.init_params(jax.random.key(9), dcfg)
            spec_stats = {}
            # B=1 latency comparison needs walls well above dispatch
            # jitter: a handful of ms "measures" only noise (an r06
            # dry run reported a 2x "speedup" at ZERO acceptance that
            # way) — decode at least 64 tokens and take best-of-3
            sp_n = max(new_tokens, 64)

            def sp_timed(seed: int) -> float:
                prompt = jax.random.randint(jax.random.key(seed),
                                            (1, prompt_len), 0,
                                            cfg.vocab_size,
                                            dtype=jnp.int32)
                t0 = time.perf_counter()
                res, st = gen.generate_speculative(
                    params, dparams, prompt, cfg, dcfg,
                    max_new_tokens=sp_n, speculate_k=4,
                    return_stats=True)
                _np.asarray(res)
                dt = time.perf_counter() - t0
                spec_stats.update(st)
                return dt

            sp_timed(seed=11)  # compile + warmup
            dt_spec = min(sp_timed(seed=411 + i) for i in range(3))
            timed(1, sp_n, seed=412)  # ensure plain b1 compiled
            dt_plain = min(timed(1, sp_n, seed=413 + i)
                           for i in range(3))
            speedup = dt_plain / dt_spec
            out["decode_spec_new_tokens"] = sp_n
            out["decode_spec_tok_s_b1"] = round(sp_n / dt_spec, 1)
            out["decode_plain_tok_s_b1"] = round(sp_n / dt_plain, 1)
            out["decode_spec_speedup_b1"] = round(speedup, 3)
            # the measured acceptance profile that EXPLAINS the
            # speedup (or the honest lack of one): tokens per target
            # launch minus the free correction token
            out["decode_spec_rounds"] = spec_stats.get("rounds")
            out["decode_spec_accept_per_round"] = spec_stats.get(
                "accept_per_round")
            accept = spec_stats.get("accept_per_round") or 0.0
            if speedup < 1.0:
                out["decode_spec_note"] = (
                    "speculation lost: accept_per_round "
                    f"{accept} means the randomly-initialized draft "
                    "rarely matches the target's greedy choice, so "
                    "each round pays k draft launches + one "
                    "(k+1)-wide target launch for ~1 emitted token; "
                    "spec-decode pays off only with a distilled/"
                    "agreeing draft AND a launch- or HBM-bound "
                    "target (not a compute-bound CPU forward)")
            elif accept < 0.5:
                # a "speedup" that acceptance cannot explain must be
                # attributed honestly or it is the r05 lie again in
                # the other direction
                out["decode_spec_note"] = (
                    f"speedup {round(speedup, 3)} at accept_per_round "
                    f"{accept} is NOT draft agreement: with ~zero "
                    "acceptance each round emits 1 token from one "
                    "(k+1)-wide target forward, which on this "
                    "overhead-dominated platform costs about the "
                    "same as the plain loop's 1-wide step — the win "
                    "is wide verification amortizing per-position "
                    "overhead (plus a near-free draft), not "
                    "speculation; a distilled draft is what would "
                    "move accept_per_round and multiply this")
    except Exception as e:  # noqa: BLE001 — additive leg
        out["decode_spec_error"] = str(e)[:200]
    print("DECODEBENCH=" + json.dumps(out))


def _cb_main() -> None:
    """Continuous-batching serve phase (ROADMAP item 2's judged leg):
    Poisson arrivals at EQUAL offered load against (a) the live
    ContinuousBatcher behind a serve deployment (streamed tokens,
    mid-flight admission) and (b) the static ``@serve.batch`` control
    (batch-boundary fusion, lockstep decode). Reports throughput and
    latency percentiles for both — ``decode_cb_tok_s`` and the p99
    comparison are the headline keys. Config via RT_BENCH_CB_CFG.
    Prints one JSON line CBBENCH={...}."""
    import ray_tpu
    from ray_tpu import serve

    cfgd = json.loads(os.environ.get("RT_BENCH_CB_CFG", "{}"))
    preset = cfgd.get("preset", "debug")
    slots = int(cfgd.get("slots", 8))
    prompt_len = int(cfgd.get("prompt_len", 8))
    # heterogeneous decode lengths — the load shape continuous batching
    # exists for: most requests want a few tokens, some want many. A
    # batch-boundary system must provision EVERY fused generate for the
    # longest admissible request; slot admission decodes only what each
    # request asked for and frees the slot.
    short_tokens = int(cfgd.get("short_tokens", 2))
    long_tokens = int(cfgd.get("long_tokens", 256))
    long_frac = float(cfgd.get("long_frac", 0.05))
    rps = float(cfgd.get("rps", 15.0))
    duration_s = float(cfgd.get("duration_s", 15.0))
    max_len = int(cfgd.get("max_len", 384))
    stride = int(cfgd.get("decode_stride", 16))
    num_proxies = int(cfgd.get("num_proxies", 2))

    out = {"decode_cb_preset": preset, "decode_cb_slots": slots,
           "decode_cb_prompt_len": prompt_len,
           "decode_cb_short_tokens": short_tokens,
           "decode_cb_long_tokens": long_tokens,
           "decode_cb_long_frac": long_frac,
           "decode_cb_offered_rps": rps,
           "decode_cb_duration_s": duration_s,
           "decode_cb_stride": stride,
           "decode_cb_proxies": num_proxies,
           "decode_cb_methodology": (
               "open-loop Poisson arrivals (serve/llm.py poisson_load) "
               "round-robined across the HTTP proxy fleet at equal "
               "offered load and an "
               f"{int(100 * (1 - long_frac))}/{int(100 * long_frac)} "
               f"short/long ({short_tokens}/{long_tokens} tok) request "
               "mix; continuous = ContinuousEngine slot admission, "
               "bucketed+K-fused rowwise decode, streamed per token; "
               "static = @serve.batch fused generate provisioned at "
               "max_new=long (a batch-boundary system decodes its "
               "longest admissible request every flush — the waste "
               "continuous admission avoids); p50/p99 are full request "
               "walls; failed counts client-side sheds at "
               "max_inflight=64")}
    ray_tpu.init(num_cpus=4)
    try:
        from ray_tpu.serve.llm import cb_vs_static_load

        legs = cb_vs_static_load(
            preset=preset, slots=slots, max_len=max_len,
            decode_stride=stride, prompt_len=prompt_len,
            short_tokens=short_tokens, long_tokens=long_tokens,
            long_frac=long_frac, rps=rps, duration_s=duration_s,
            num_proxies=num_proxies, route_base="bench",
            ray_actor_options={"num_tpus": 1})
        cb, st = legs["continuous"], legs["static"]
        out["decode_cb_tok_s"] = cb["tok_s"]
        out["decode_cb_rps"] = cb["rps"]
        out["decode_cb_p50_ms"] = cb["p50_ms"]
        out["decode_cb_p99_ms"] = cb["p99_ms"]
        out["decode_cb_completed"] = cb["completed"]
        out["decode_cb_failed"] = cb["failed"] + cb["shed"]
        out["decode_static_tok_s"] = st["tok_s"]
        out["decode_static_rps"] = st["rps"]
        out["decode_static_p50_ms"] = st["p50_ms"]
        out["decode_static_p99_ms"] = st["p99_ms"]
        out["decode_static_failed"] = st["failed"] + st["shed"]
        if st["p99_ms"]:
            out["decode_cb_p99_vs_static"] = round(
                cb["p99_ms"] / st["p99_ms"], 3)
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001
            pass
        ray_tpu.shutdown()
    print("CBBENCH=" + json.dumps(out))


def _engine_main() -> None:
    """Engine flight-recorder phase (RT_BENCH_ENGINE): Poisson decode
    traffic on a ContinuousEngine, then an injected long-prompt prefill
    burst on the colocated engine, then recovery. The recorder's
    ``window_summary`` carves the three legs; SLO targets are calibrated
    from the steady leg (p99 x margin) so the burst's TPOT dip is a
    measured attainment drop, not a hand-picked threshold. Prints one
    JSON line ENGINEBENCH={...}. Config via RT_BENCH_ENGINE_CFG."""
    # the recorder's ring capacity is read at module import: size it
    # before ray_tpu comes in so every steady-leg tick survives until
    # the end-of-run window carve
    os.environ.setdefault("RT_ENGINE_RECORDER_CAP", "16384")
    import random
    import threading

    import numpy as np
    import jax

    from ray_tpu.models import llama, serving

    cfgd = json.loads(os.environ.get("RT_BENCH_ENGINE_CFG", "{}"))
    preset = cfgd.get("preset", "bench")
    steady_s = float(cfgd.get("steady_s", 8.0))
    recovery_s = float(cfgd.get("recovery_s", 8.0))
    rate_hz = float(cfgd.get("rate_hz", 4.0))
    new_tokens = int(cfgd.get("new_tokens", 32))
    burst_s = float(cfgd.get("burst_s", 2.5))
    burst_gap_s = float(cfgd.get("burst_gap_s", 0.15))
    burst_new = int(cfgd.get("burst_new_tokens", 8))
    max_slots = int(cfgd.get("max_slots", 4))
    max_len = int(cfgd.get("max_len", 512))
    short_len = int(cfgd.get("short_len", 16))
    long_len = int(cfgd.get("long_len", max_len - new_tokens - 8))

    if preset == "bench":
        # wide enough that a long-prompt prefill costs MANY decode
        # launches (the asymmetry this phase measures); "debug" prefills
        # in ~1 decode launch and the burst would vanish into noise
        cfg = llama.LlamaConfig(vocab_size=2048, d_model=256, n_layers=4,
                                n_heads=8, n_kv_heads=4, d_ff=1024,
                                max_seq_len=max(max_len, 256))
    else:
        cfg = llama.PRESETS[preset]
        max_len = min(max_len, cfg.max_seq_len)
        long_len = min(long_len, max_len - new_tokens - 8)
    params = llama.init_params(jax.random.key(0), cfg)
    # kv_cache_bytes=0: cold prefill every time — a prefix cache would
    # absorb the repeated long prompts and hide the stall being measured
    eng = serving.ContinuousEngine(params, cfg, max_slots=max_slots,
                                   max_len=max_len, decode_stride=4,
                                   warmup=True, kv_cache_bytes=0,
                                   kv_label="bench-engine")
    rec = eng._recorder

    def _short_prompt(i: int) -> np.ndarray:
        # ONE fixed length: prefill compiles per exact prompt length, and
        # a mid-leg XLA compile would masquerade as a prefill stall
        return ((np.arange(short_len, dtype=np.int64) * (i * 131 + 7))
                % cfg.vocab_size).astype(np.int32)

    def _long_prompt(i: int) -> np.ndarray:
        return ((np.arange(long_len, dtype=np.int64) * (i * 17 + 3))
                % cfg.vocab_size).astype(np.int32)

    def _drain(q, evt=None):
        while q.get() is not None:
            pass
        if evt is not None:
            evt.set()

    def _request(prompt: np.ndarray, n: int):
        evt = threading.Event()
        q = eng.submit_stream(prompt, n)
        t = threading.Thread(target=_drain, args=(q, evt), daemon=True)
        t.start()
        return evt

    # pre-warm BOTH prompt-length shapes outside the measured windows so
    # the burst leg charges prefill wall, not one-time XLA compiles
    for warm in (_short_prompt(0), _long_prompt(0)):
        _request(warm, 4).wait(timeout=60)
    time.sleep(0.2)

    stop = threading.Event()
    pause = threading.Event()
    done_evts: list = []
    evts_lock = threading.Lock()

    def _generator():
        rng = random.Random(42)
        i = 1
        while not stop.is_set():
            time.sleep(min(rng.expovariate(rate_hz), 1.0))
            if stop.is_set() or pause.is_set():
                continue
            evt = _request(_short_prompt(i), new_tokens)
            with evts_lock:
                done_evts.append(evt)
            i += 1

    gen = threading.Thread(target=_generator, daemon=True)
    gen.start()

    # leg 1: steady Poisson decode traffic
    t0 = time.time()
    time.sleep(steady_s)
    t1 = time.time()

    # leg 2: sustained long-prompt prefill burst injected into live
    # decode traffic — each admission's cold prefill stalls the decode
    # launches of every active stream, over and over for burst_s
    burst_evts = []
    while time.time() - t1 < burst_s:
        burst_evts.append(
            _request(_long_prompt(len(burst_evts) + 1), burst_new))
        time.sleep(burst_gap_s)
    for evt in burst_evts:
        evt.wait(timeout=120)
    time.sleep(0.3)  # let the stalled decodes finish inside the window
    t2 = time.time()

    # drain the short-request backlog the burst queued up before opening
    # the recovery window: recovery measures the post-burst steady state,
    # not the transition (drain_s reports how long the transition took).
    # Arrivals pause during the drain — otherwise fresh requests keep
    # queueing FIFO behind the backlog and the queue never catches up.
    pause.set()
    with evts_lock:
        backlog = list(done_evts)
    for evt in backlog:
        evt.wait(timeout=120)
    time.sleep(0.5)
    pause.clear()
    t2b = time.time()

    # leg 3: steady traffic only — attainment should recover
    time.sleep(recovery_s)
    t3 = time.time()
    stop.set()
    gen.join(timeout=5)
    with evts_lock:
        tail = list(done_evts)
    for evt in tail:
        evt.wait(timeout=60)

    # calibrate SLOs from the steady leg's RAW percentiles, then carve
    # all three windows against those targets (attainment is computed at
    # summary time, so set_slo applies retroactively and uniformly)
    raw = rec.window_summary(t0, t1)
    ttft_slo_s = max(raw.get("ttft_p99_s", 0.0) * 1.5, 0.050)
    tpot_slo_s = max(raw.get("tpot_p99_s", 0.0) * 1.25, 0.0005)
    rec.set_slo(ttft_slo_s=ttft_slo_s, tpot_slo_s=tpot_slo_s)
    steady = rec.window_summary(t0, t1)
    burst = rec.window_summary(t1, t2)
    recovery = rec.window_summary(t2b, t3)
    overall = rec.summary()
    eng.shutdown()

    gap_base = max(steady.get("tick_gap_p99_s", 0.0), 1e-6)
    out = {
        "config": {"preset": preset, "max_slots": max_slots,
                   "max_len": max_len, "short_len": short_len,
                   "long_len": long_len, "rate_hz": rate_hz,
                   "new_tokens": new_tokens,
                   "burst_prompts": len(burst_evts),
                   "burst_s": burst_s, "burst_new_tokens": burst_new,
                   "steady_s": steady_s, "recovery_s": recovery_s},
        "slo": {"ttft_slo_ms": round(ttft_slo_s * 1e3, 3),
                "tpot_slo_ms": round(tpot_slo_s * 1e3, 3),
                "calibration": "steady p99 x 1.5 (TTFT) / x 1.25 (TPOT)"},
        "steady": steady,
        "burst": burst,
        "recovery": recovery,
        "drain_s": round(t2b - t2, 3),
        "burst_gap_spike_x": round(
            burst.get("tick_gap_p99_s", 0.0) / gap_base, 1),
        "burst_tpot_dip": round(
            steady.get("tpot_attainment", 0.0)
            - burst.get("tpot_attainment", 1.0), 4),
        "phase_sum_ratio": overall.get("phase_sum_ratio", 0.0),
        "overhead_frac": overall.get("overhead_frac", 0.0),
        "ticks_total": overall.get("ticks_total", 0),
        "requests_total": overall.get("requests_total", 0),
    }
    _preserve({"engine_phase": out},
              path=os.environ.get("RT_BENCH_ENGINE_OUT", ""))
    print("ENGINEBENCH=" + json.dumps(out))


def _engine_obs_round() -> None:
    """Focused ``python bench.py --engine-obs`` round: run the engine
    flight-recorder phase in a scrubbed-CPU subprocess and commit the
    measured legs as ENGINE_r08.json (the artifact the bench-trajectory
    checker tracks for summary.steady/recovery series)."""
    import sys

    res = _run_phase("RT_BENCH_ENGINE", "ENGINEBENCH", timeout=900)
    if not res:
        print("bench: engine-obs phase produced no result", file=sys.stderr)
        sys.exit(1)
    notes = [
        "Colocated prefill burst: {}x tick-gap p99 spike over steady, "
        "TPOT attainment dip of {} during the burst leg.".format(
            res.get("burst_gap_spike_x"), res.get("burst_tpot_dip")),
        "Recovery leg TPOT attainment {} (steady {}).".format(
            res.get("recovery", {}).get("tpot_attainment"),
            res.get("steady", {}).get("tpot_attainment")),
        "Recorder overhead {} of engine-thread tick wall; per-tick phase "
        "sums cover {} of it.".format(
            res.get("overhead_frac"), res.get("phase_sum_ratio")),
        "SLO targets calibrated from the steady leg, applied "
        "retroactively to all three windows.",
    ]
    art = {
        "round": "r08",
        "artifact": "ENGINE_r08",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": os.environ.get("RT_BENCH_PLATFORM", "cpu"),
        "summary": res,
        "notes": notes,
    }
    path = os.environ.get("RT_BENCH_ENGINE_OUT") or os.path.join(
        _REPO_ROOT, "ENGINE_r08.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    print(f"bench: engine-obs round written to {path}")
    print("ENGINEOBS=" + json.dumps(
        {"steady_goodput_tok_s": res.get("steady", {}).get("goodput_tok_s"),
         "burst_tpot_attainment": res.get("burst", {}).get(
             "tpot_attainment"),
         "recovery_tpot_attainment": res.get("recovery", {}).get(
             "tpot_attainment"),
         "burst_gap_spike_x": res.get("burst_gap_spike_x"),
         "overhead_frac": res.get("overhead_frac")}))


def _rlhf_obs_round() -> None:
    """Focused ``python bench.py --rlhf-obs`` round: re-run the RLHF
    phase with the pipeline flight recorder live and commit the measured
    strict-phase bubble fraction + staleness profile as RLHF_r11.json —
    the baseline ROADMAP item 4's interleave claim will be judged
    against (the trajectory checker tracks summary.bubble_fraction /
    summary.staleness_p99 / summary.sync_wall_s)."""
    import sys

    # a workload big enough that the per-iteration phase work dominates
    # the fixed RPC orchestration latency — the coverage acceptance
    # (role intervals >= 95% of iteration wall) grades the recorder's
    # join, and a debug-sized run would grade the RPC stack instead
    os.environ.setdefault("RT_BENCH_RLHF_CFG", json.dumps(
        {"prompts": 16, "prompt_len": 32, "max_new": 128, "slots": 8,
         "rlhf_iters": 3}))
    res = _run_phase("RT_BENCH_RLHF", "RLHFBENCH", timeout=1200)
    if not res or "rlhf" not in res:
        print("bench: rlhf-obs phase produced no rlhf leg", file=sys.stderr)
        sys.exit(1)
    leg = res["rlhf"]
    rec = leg.get("recorder", {})
    stale = rec.get("staleness", {}) or {}
    idle = rec.get("role_idle_frac", {}) or {}
    receipt = leg.get("receipt", {}) or {}
    summary = {
        "bubble_fraction": rec.get("bubble_fraction"),
        "bubble_last": rec.get("bubble_last"),
        "coverage": rec.get("coverage"),
        "staleness_p99": stale.get("p99", 0),
        "staleness_max": stale.get("max", 0),
        "sync_wall_s": leg.get("sync_s"),
        "generate_tok_s": leg.get("generate_tok_s"),
        "role_idle_frac": idle,
        "orchestration_tax_s": rec.get("tax_s"),
        "transfer": {k: receipt.get(k) for k in (
            "nbytes", "n_leaves", "oid_leaves", "inline_leaves",
            "transport", "pump_wall_s", "fetch_wall_s",
            "barrier_drain_s", "swap_apply_s") if k in receipt},
        "recorder_overhead_frac": rec.get("overhead_frac"),
    }
    notes = [
        "Strict-phase bubble fraction {} (role-seconds idle while any "
        "other role works / total role-seconds); idlest role {}.".format(
            summary["bubble_fraction"],
            max(idle, key=idle.get) if idle else "?"),
        "Role intervals cover {} of iteration wall (acceptance floor "
        "0.95); staleness p99 {} versions — strict phases decode the "
        "just-shipped weights, so nonzero staleness means overlap.".format(
            summary["coverage"], summary["staleness_p99"]),
        "Joined transfer receipt: ship pump {}s, fetch {}s, barrier "
        "drain {}s, swap apply {}s over {} bytes.".format(
            receipt.get("pump_wall_s"), receipt.get("fetch_wall_s"),
            receipt.get("barrier_drain_s"), receipt.get("swap_apply_s"),
            receipt.get("nbytes")),
        "Recorder self-measured overhead {} of iteration wall "
        "(budget 0.02).".format(summary["recorder_overhead_frac"]),
    ]
    art = {
        "round": "r11",
        "artifact": "RLHF_r11",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": res.get("platform",
                            os.environ.get("RT_BENCH_PLATFORM", "cpu")),
        "summary": summary,
        "notes": notes,
        "measured": res,
    }
    path = os.environ.get("RT_BENCH_RLHF_OUT") or os.path.join(
        _REPO_ROOT, "RLHF_r11.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    print(f"bench: rlhf-obs round written to {path}")
    print("RLHFOBS=" + json.dumps(
        {"bubble_fraction": summary["bubble_fraction"],
         "coverage": summary["coverage"],
         "staleness_p99": summary["staleness_p99"],
         "sync_wall_s": summary["sync_wall_s"],
         "recorder_overhead_frac": summary["recorder_overhead_frac"]}))


def _train_obs_main() -> None:
    """Train flight-recorder phase (RT_BENCH_TRAIN_OBS): one fused-K
    StepDriver run with three legs carved by
    ``TrainRecorder.window_summary`` — steady (loader keeps up),
    data-starved (loader throttled via RT_TRAIN_LOADER_THROTTLE_S, read
    per batch so a live run can be throttled from outside), and
    checkpoint-heavy (blocking device->host state pull + disk write per
    launch). The grading is the recorder's own: phase sums vs launch
    wall, the launch-gap series, and the MFU-gap waterfall per leg.
    Prints TRAINOBSBENCH={...}."""
    import tempfile

    import numpy as np

    import jax

    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.driver import StepDriver

    cfgd = json.loads(os.environ.get("RT_BENCH_TRAIN_OBS_CFG", "{}"))
    preset = cfgd.get("preset", "debug")
    batch = cfgd.get("batch", 4)
    k = cfgd.get("k", 8)
    leg_launches = cfgd.get("leg_launches", 10)
    throttle_s = cfgd.get("throttle_s", 0.03)

    cfg = _bench_cfg(preset, "xla", 0)
    seq = min(cfgd.get("seq", 32), cfg.max_seq_len)
    devices = jax.devices()
    mesh = make_mesh(MeshConfig(), devices)
    optimizer = ts.default_optimizer(total_steps=10000)
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg,
                                              mesh, optimizer)
    driver = StepDriver(cfg, optimizer, mesh=mesh, steps_per_launch=k)
    rec = driver.recorder
    assert rec is not None and rec.enabled, \
        "train-obs phase needs the recorder live (RT_TRAIN_RECORDER)"
    rng = np.random.default_rng(2)

    def batches(n):
        for _ in range(n):
            thr = float(os.environ.get("RT_TRAIN_LOADER_THROTTLE_S",
                                       "0") or 0)
            if thr > 0:
                time.sleep(thr)  # the env-throttled loader
            yield {"tokens": rng.integers(
                0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)}

    def settle(timeout: float = 10.0) -> None:
        # wait for the done-hook watcher to close in-flight records so
        # the window carve sees every launch of the leg it just timed
        t_end = time.perf_counter() + timeout
        while time.perf_counter() < t_end:
            if not rec.summary().get("in_flight"):
                return
            time.sleep(0.01)

    # warmup: two launch cycles (first compiles, second runs on
    # post-update leaf types) — the legs grade the steady state
    params, opt_state, m = driver.run(params, opt_state, batches(2 * k))
    float(jax.numpy.ravel(m["loss"])[-1])
    settle()

    legs: dict = {}

    def leg(name: str, on_launch=None) -> None:
        nonlocal params, opt_state
        t0 = time.time()
        params, opt_state, _m = driver.run(
            params, opt_state, batches(leg_launches * k),
            on_launch=on_launch)
        settle()
        legs[name] = rec.window_summary(t0, time.time())

    leg("steady")
    os.environ["RT_TRAIN_LOADER_THROTTLE_S"] = str(throttle_s)
    try:
        leg("starved")
    finally:
        os.environ.pop("RT_TRAIN_LOADER_THROTTLE_S", None)

    ckpt_dir = tempfile.mkdtemp(prefix="rt_tobs_")

    def save_ckpt(_metrics):
        # a real checkpoint fence: device->host pull of the post-launch
        # params (blocks on the launch) + a disk write, on the loop
        flat = jax.device_get(jax.tree.leaves(driver.state[0]))
        np.savez(os.path.join(ckpt_dir, "state.npz"),
                 *[np.asarray(x) for x in flat])

    leg("ckpt_heavy", on_launch=save_ckpt)

    full = rec.summary()
    keep = ("window_launches", "launch_wall_s", "span_s", "tokens_per_s",
            "phase_s", "phase_sum_ratio", "launch_gap_p50_s",
            "launch_gap_p99_s", "launch_gap_max_s", "data_wait_frac",
            "raw_mfu", "achieved_mfu", "mfu_gap_frac",
            "marginal_mfu_mean", "waterfall")

    def trim(s):
        return {key: s[key] for key in keep if key in s}

    steady_dw = legs["steady"].get("data_wait_frac", 0.0)
    starved_dw = legs["starved"].get("data_wait_frac", 0.0)
    starved_buckets = (legs["starved"].get("waterfall") or {}) \
        .get("buckets_s") or {}
    out = {
        "preset": preset, "batch": batch, "seq": seq, "k": k,
        "leg_launches": leg_launches, "throttle_s": throttle_s,
        "platform": jax.default_backend(), "n_devices": len(devices),
        "steady": trim(legs["steady"]),
        "starved": trim(legs["starved"]),
        "ckpt_heavy": trim(legs["ckpt_heavy"]),
        # the honesty gates: stamped phases must explain the launch wall
        # in EVERY leg, and the recorder must not tax what it measures
        "phase_sum_ratio": round(min(
            legs[n].get("phase_sum_ratio", 0.0) for n in legs), 4),
        "overhead_frac": full.get("overhead_frac", 0.0),
        "data_wait_spike_x": round(
            starved_dw / max(steady_dw, 0.005), 2),
        "dominant_starved_bucket": (max(starved_buckets,
                                        key=starved_buckets.get)
                                    if starved_buckets else None),
        "dry_resets": full.get("dry_resets", 0),
    }
    _preserve({"train_obs_phase": out})
    print("TRAINOBSBENCH=" + json.dumps(out))


def _train_obs_round() -> None:
    """Focused ``python bench.py --train-obs`` round: run the train
    flight-recorder phase in a scrubbed-CPU subprocess and commit the
    measured legs as TRAIN_r12.json — the measurement substrate ROADMAP
    item 2's MFU-gap claim is judged against (the trajectory checker
    tracks summary.mfu_gap_frac / summary.launch_gap_p99_s /
    summary.data_wait_frac)."""
    import sys

    res = _run_phase("RT_BENCH_TRAIN_OBS", "TRAINOBSBENCH", timeout=900)
    if not res or "steady" not in res:
        print("bench: train-obs phase produced no result", file=sys.stderr)
        sys.exit(1)
    steady = res.get("steady") or {}
    starved = res.get("starved") or {}
    ckpt = res.get("ckpt_heavy") or {}
    summary = {
        # headline series (steady leg): what the trajectory checker holds
        "mfu_gap_frac": steady.get("mfu_gap_frac"),
        "launch_gap_p99_s": steady.get("launch_gap_p99_s"),
        "data_wait_frac": steady.get("data_wait_frac"),
        "phase_sum_ratio": res.get("phase_sum_ratio"),
        "overhead_frac": res.get("overhead_frac"),
        "data_wait_spike_x": res.get("data_wait_spike_x"),
        "dominant_starved_bucket": res.get("dominant_starved_bucket"),
        "steady": steady, "starved": starved, "ckpt_heavy": ckpt,
    }
    notes = [
        "Per-launch phase sums cover {} of launch wall across all three "
        "legs (acceptance floor 0.95); recorder overhead {} of recorded "
        "wall (budget 0.02).".format(res.get("phase_sum_ratio"),
                                     res.get("overhead_frac")),
        "Throttled-loader leg: data_wait share {} vs steady {} "
        "({}x spike); dominant waterfall bucket {} — starvation "
        "attributed to the loader, not the devices (dry-resets "
        "suppressed the launch-gap stamp {} times).".format(
            starved.get("data_wait_frac"), steady.get("data_wait_frac"),
            res.get("data_wait_spike_x"),
            res.get("dominant_starved_bucket"), res.get("dry_resets")),
        "Checkpoint-heavy leg: host_tax sum {}s vs steady {}s — the "
        "blocking state pull + disk write lands in one bucket.".format(
            (ckpt.get("phase_s") or {}).get("host_tax"),
            (steady.get("phase_s") or {}).get("host_tax")),
        "MFU-gap waterfall (steady): raw {} -> achieved {}; gap "
        "fraction {}.".format(steady.get("raw_mfu"),
                              steady.get("achieved_mfu"),
                              steady.get("mfu_gap_frac")),
    ]
    art = {
        "round": "r12",
        "artifact": "TRAIN_r12",
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "platform": res.get("platform",
                            os.environ.get("RT_BENCH_PLATFORM", "cpu")),
        "summary": summary,
        "notes": notes,
        "measured": res,
    }
    path = os.environ.get("RT_BENCH_TRAIN_OBS_OUT") or os.path.join(
        _REPO_ROOT, "TRAIN_r12.json")
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)
    print(f"bench: train-obs round written to {path}")
    print("TRAINOBS=" + json.dumps(
        {"mfu_gap_frac": summary["mfu_gap_frac"],
         "launch_gap_p99_s": summary["launch_gap_p99_s"],
         "data_wait_frac": summary["data_wait_frac"],
         "phase_sum_ratio": summary["phase_sum_ratio"],
         "overhead_frac": summary["overhead_frac"],
         "data_wait_spike_x": summary["data_wait_spike_x"]}))


def _data_main() -> None:
    """Data-ingestion phase: parquet -> fused map pipeline
    -> iter_batches, the host-side input path that keeps chips fed. Reports
    rows/s and MB/s through the streaming executor (optimizer + memory
    backpressure on). Prints DATABENCH={...}."""
    import tempfile

    import numpy as np

    import ray_tpu
    from ray_tpu import data as rt_data

    out = {}
    rows_per_file, n_files, cols = 50_000, 8, 4
    ray_tpu.init(num_cpus=4)
    try:
        with tempfile.TemporaryDirectory() as td:
            import pandas as pd

            rng = np.random.default_rng(0)
            for i in range(n_files):
                pd.DataFrame({
                    f"c{j}": rng.standard_normal(rows_per_file)
                    for j in range(cols)}).to_parquet(f"{td}/f{i}.parquet")
            nbytes = rows_per_file * n_files * cols * 8

            def pipeline():
                return (rt_data.read_parquet(f"{td}/*.parquet")
                        .map_batches(lambda b: {
                            "x": b["c0"] * 2 + b["c1"],
                            "y": b["c2"] - b["c3"]})
                        .select_columns(["x"]))

            # warmup (worker spawn)
            next(iter(pipeline().iter_batches(batch_size=4096)))
            t0 = time.perf_counter()
            n = 0
            for batch in pipeline().iter_batches(batch_size=4096):
                n += len(batch["x"])
            dt = time.perf_counter() - t0
            out = {"data_rows_per_sec": round(n / dt, 1),
                   "data_mb_per_sec": round(nbytes / 1e6 / dt, 1),
                   "data_rows": n, "data_files": n_files}
    finally:
        ray_tpu.shutdown()
    print("DATABENCH=" + json.dumps(out))


def _est_hbm_bytes(preset: str, batch: int, seq: int, dtype: str) -> float:
    """Training-state + activation estimate for one chip.

    Optimizer state is exact (p+g+m+v at the param dtype); the activation
    term's 17 B/(token*d_model*layer) factor is fitted to measured XLA
    allocations under this remat/flash config — activations are bf16
    compute in BOTH param dtypes, so one factor covers both: measured
    410m/b16/fp32 19.71 GB vs 19.7 predicted; 1b/b8/bf16 OOMed (21.3
    predicted) while 1b/b4/bf16 ran (15.1 predicted) on a 15.75 GB v5e.
    Rungs that can't fit are skipped instead of burning a ~40 s compile
    each to learn it.
    """
    from ray_tpu.models import llama

    cfg = llama.PRESETS[preset]
    state = cfg.num_params() * (16 if dtype == "fp32" else 8)
    act = 17 * batch * seq * cfg.d_model * cfg.n_layers
    return float(state + act)


def _is_oom(err: BaseException) -> bool:
    s = str(err)
    return ("RESOURCE_EXHAUSTED" in s or "Ran out of memory" in s
            or "out of memory" in s or "hbm capacity" in s)


def _best_tok_s(entry: dict) -> tuple:
    """(tok/s, path) — the best honest device rate a sweep measured:
    multi-step scan when it ran (launch overhead amortized), else the
    single-step marginal, else single-point sustained."""
    for key, path in (("scan_tok_s_chip", "multi-step-scan"),
                      ("marginal_tok_s_chip", "steps-sweep-marginal"),
                      ("sustained_tok_s_chip", "single-point-sustained")):
        if entry.get(key):
            return entry[key], path
    return 0.0, "none"


def _flops_throughput(entry: dict) -> float:
    """Best model-FLOPs throughput of a sweep result (cross-preset
    comparable rung-selection key)."""
    from ray_tpu.models import llama

    return _best_tok_s(entry)[0] * 6 * llama.PRESETS[
        entry["preset"]].num_params()


def _inner_main() -> None:
    import sys

    # Platform comes from main()'s probe subprocess: importing jax here
    # would claim the (single) chip in THIS process and starve the phase
    # subprocesses that must own it.
    platform = os.environ["RT_BENCH_PLATFORM"]
    if platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {platform!r}")
    ladder = [
        # Biggest model first: MFU rises with arithmetic intensity.
        # 1b (1.1B params) only fits a 16GB chip with bf16
        # params+moments; b4 is the rung the estimate lets through
        # (15.1G). The HBM gate skips b16/b8.
        ("1b", 16, 2048, "flash", 256, "bf16"),
        ("1b", 8, 2048, "flash", 256, "bf16"),
        ("1b", 4, 2048, "flash", 256, "bf16"),
        ("410m", 8, 2048, "flash", 512, "bf16"),
        ("410m", 8, 2048, "flash", 512, "fp32"),
        ("410m", 8, 2048, "xla", 512, "fp32"),
        ("410m", 4, 2048, "flash", 512, "fp32"),
        ("160m", 8, 2048, "xla", 0, "fp32"),
        ("160m", 4, 1024, "xla", 0, "fp32"),
    ]
    sweep_budget = 140.0
    if os.environ.get("BENCH_PRESET"):
        p = os.environ["BENCH_PRESET"]
        ladder = [(p, 8, 2048, "flash", 512, "fp32"),
                  (p, 4, 2048, "xla", 512, "fp32")] + ladder

    hbm = float(os.environ["RT_BENCH_HBM_BYTES"])

    # Phase 1 — steps-sweep per rung (subprocess: chip released between
    # rungs). Walk the ladder; sweep the first rung per (preset, dtype)
    # family that passes the HBM gate; stop after two families measured.
    errors = []
    sweeps = []  # [(rung, sweep_result)]
    for preset, batch, seq, attn, chunk, dtype in ladder:
        if any((s[0][0], s[0][5]) == (preset, dtype) for s in sweeps):
            continue  # family already measured
        if _est_hbm_bytes(preset, batch, seq, dtype) > hbm:
            msg = (f"{preset}/b{batch}/s{seq}/{dtype}: skipped — estimated "
                   f"{_est_hbm_bytes(preset, batch, seq, dtype) / 1e9:.1f}G "
                   f"> {hbm / 1e9:.1f}G HBM")
            errors.append(msg)
            print(f"bench: {msg}", file=sys.stderr)
            continue
        cfg_json = json.dumps({"preset": preset, "batch": batch, "seq": seq,
                               "attn": attn, "loss_chunk": chunk,
                               "dtype": dtype, "budget_s": sweep_budget})
        res = _run_phase("RT_BENCH_SWEEP", "SWEEPBENCH",
                         timeout=sweep_budget + 260,
                         env=dict(os.environ),
                         extra_env={"RT_BENCH_SWEEP_CFG": cfg_json})
        if res.get("error"):
            msg = f"{preset}/b{batch}/s{seq}/{attn}: {res['error']}"
            errors.append(msg)
            print(f"bench: rung does not fit, next rung — {msg}",
                  file=sys.stderr)
            continue
        sweeps.append(((preset, batch, seq, attn, chunk, dtype), res))
        _preserve({"stage": "sweep", "ladder": [s[1] for s in sweeps],
                   "fallback_errors": list(errors)})
        if len(sweeps) == 2:
            break
    if not sweeps:
        raise RuntimeError("all bench configs failed:\n" + "\n".join(errors))

    sweeps.sort(key=lambda s: -_flops_throughput(s[1]))
    if len(sweeps) > 1:
        loser = sweeps[1]
        print(f"bench: contender {loser[1]['preset']}/b{loser[1]['batch']} "
              f"marginal {loser[1].get('marginal_tok_s_chip')} tok/s — kept "
              f"{sweeps[0][1]['preset']}/b{sweeps[0][1]['batch']}",
              file=sys.stderr)
    chosen, sweep_best = sweeps[0]
    preset, batch, seq, attn, chunk, dtype = chosen

    # Phase 2 — the product path on the winning rung: through JaxTrainer +
    # data iterator (subprocess gang owns the chip). The delta vs the raw
    # dispatch rate is the Train-layer overhead.
    train_cfg = json.dumps({"preset": preset, "batch": batch, "seq": seq,
                            "steps": 12, "attn": attn, "loss_chunk": chunk,
                            "dtype": dtype})
    train_result = _run_phase("RT_BENCH_TRAIN", "TRAINBENCH", timeout=420,
                              env=dict(os.environ),
                              extra_env={"RT_BENCH_TRAIN_CFG": train_cfg})

    # Phase 2b — fused-K fast-path A/B (raw vs through-train at equal
    # work, K sweep, offload delta). Additive evidence; bounded.
    fast_result = _run_phase(
        "RT_BENCH_TRAIN_FAST", "TRAINFASTBENCH", timeout=900,
        env=dict(os.environ),
        extra_env={"RT_BENCH_TRAIN_FAST_CFG": json.dumps(
            {"preset": preset, "batch": batch, "seq": seq})})

    headline, headline_path = _best_tok_s(sweep_best)
    details = {
        "preset": preset, "platform": sweep_best.get("platform", platform),
        "devices": sweep_best.get("devices", 1), "batch": batch,
        "seq": seq, "attn": attn, "loss_chunk": chunk, "param_dtype": dtype,
        "methodology": "marginal-steps-sweep",
        "headline_path": headline_path,
        "timing_note": (
            "value = best honest device rate: the multi-step-scan marginal "
            "(K optimizer steps fused into one program; per-launch overhead "
            "amortized AND measured as b_single - scan_step_s) when it ran, "
            "else the steps-sweep marginal b from wall = a + b*steps with a "
            "host read per point. dispatch/sustained "
            "single-point rates kept in details for continuity with r1-r4."),
        "scan_tok_s_chip": sweep_best.get("scan_tok_s_chip"),
        "scan_mfu": sweep_best.get("scan_mfu"),
        "scan_steps_per_call": sweep_best.get("scan_steps_per_call"),
        "per_launch_overhead_s": sweep_best.get("per_launch_overhead_s"),
        "marginal_tok_s_chip": sweep_best.get("marginal_tok_s_chip"),
        "marginal_mfu": sweep_best.get("marginal_mfu"),
        "fixed_overhead_s": sweep_best.get("fixed_overhead_s"),
        "marginal_step_s": sweep_best.get("marginal_step_s"),
        "sweep_steps": sweep_best.get("sweep_steps"),
        "sweep_walls_s": sweep_best.get("sweep_walls_s"),
        "fit_r2": sweep_best.get("fit_r2"),
        "sustained_tok_s_chip": sweep_best.get("sustained_tok_s_chip"),
        "sustained_mfu": sweep_best.get("sustained_mfu"),
        "dispatch_tok_s_chip": sweep_best.get("dispatch_tok_s_chip"),
        "params_m": sweep_best.get("params_m"),
    }
    # Every measured rung goes in the record (incl. the 1b row).
    details["ladder"] = [s[1] for s in sweeps]
    if train_result:
        details["through_train_tok_s_chip"] = round(
            train_result.get("tok_s_chip", 0), 2)
        details["through_train_sustained_tok_s_chip"] = round(
            train_result.get("sustained_tok_s_chip", 0), 2)
        details["through"] = "JaxTrainer"
        details["loss"] = train_result.get("loss")
        # Product overhead: the Train layer's cost (data iterator,
        # shard_batch, report path) is host-side dispatch work, so
        # compare dispatch rates — both clocks stop before the host
        # read, excluding the fixed per-run overhead.
        raw_disp = sweep_best.get("dispatch_tok_s_chip") or 0
        tr_disp = train_result.get("tok_s_chip") or 0
        if raw_disp and tr_disp:
            details["train_overhead_pct"] = round(
                (1 - tr_disp / raw_disp) * 100, 2)
    if fast_result:
        details["train_fast_path"] = {
            "through_vs_raw_ratio": fast_result.get("through_vs_raw_ratio"),
            "per_launch_overhead_s": fast_result.get(
                "per_launch_overhead_s"),
            "offload_speedup": (fast_result.get("offload") or {}).get(
                "speedup"),
        }
    if errors:
        details["fallback_errors"] = errors
    _preserve({"stage": "through_train", "details": dict(details)})

    # Phase 3 — decode: bf16 KV-cache generate on the chip.
    decode_cfg = json.dumps({
        "preset": preset, "dtype": "bf16", "prompt_len": 128,
        "batches": [1, 8, 32], "new_tokens": 64})
    dec = _run_phase("RT_BENCH_DECODE", "DECODEBENCH", timeout=600,
                     env=dict(os.environ),
                     extra_env={"RT_BENCH_DECODE_CFG": decode_cfg})
    if dec:
        details.update(dec)
        _preserve({"stage": "decode", "details": dict(details)})

    from ray_tpu.models import llama as _llama

    details["params_m"] = round(_llama.PRESETS[preset].num_params() / 1e6, 1)

    print(json.dumps({
        "metric": f"llama_{preset}_train_tokens_per_sec_per_chip",
        "value": round(headline, 2),
        "unit": "tokens/s/chip",
        "details": details,
    }))


_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))


def _cpu_env() -> dict:
    """Env holding a phase to the CPU platform (the data phase, the obs
    rounds). Single source of truth lives in __graft_entry__."""
    import sys

    sys.path.insert(0, _REPO_ROOT)
    from __graft_entry__ import _cpu_scrubbed_env

    return _cpu_scrubbed_env(1)


def _run_inner(env: dict, timeout: float) -> dict:
    """Run the bench inner loop in a subprocess and return its JSON line.
    The subprocess boundary keeps this process off jax, so the phases can
    own the chip; a failed or silent inner run fails the bench."""
    import subprocess
    import sys

    env = dict(env)
    env["RT_BENCH_INNER"] = "1"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        cwd=_REPO_ROOT, stdout=subprocess.PIPE, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise SystemExit(f"bench: inner run failed rc={proc.returncode}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _probe_backend(timeout: float, env: dict):
    """Ask a subprocess which device jax finds in ``env``; returns
    (platform, hbm_bytes_str). One try: a backend that does not come up is
    a failed run, not something to wait out."""
    import subprocess
    import sys

    code = ("import jax; d = jax.devices()[0]; "
            "print('PLATFORM=' + d.platform); "
            "print('HBM=' + str((d.memory_stats() or {})"
            ".get('bytes_limit', 0)))")
    proc = subprocess.run([sys.executable, "-c", code], env=dict(env),
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise SystemExit(f"bench: jax backend did not come up "
                         f"rc={proc.returncode}: {proc.stderr[-2000:]}")
    found = dict(ln.split("=", 1) for ln in proc.stdout.splitlines()
                 if "=" in ln)
    return found["PLATFORM"], found["HBM"]


def main() -> None:
    """Probe the backend in a subprocess (this process stays off jax, so the
    phases can own the chip), refuse anything that is not a TPU, then run
    the phases. Any failure exits non-zero; nothing falls back to the CPU.
    """
    import sys

    if os.environ.get("RT_BENCH_INNER"):
        _inner_main()
        return
    if os.environ.get("RT_BENCH_SWEEP"):
        _sweep_main()
        return
    if os.environ.get("RT_BENCH_TRAIN"):
        _train_main()
        return
    if os.environ.get("RT_BENCH_TRAIN_FAST"):
        _train_fast_main()
        return
    if os.environ.get("RT_BENCH_DECODE"):
        _decode_main()
        return
    if os.environ.get("RT_BENCH_RL"):
        _rl_main()
        return
    if os.environ.get("RT_BENCH_RLHF"):
        _rlhf_main()
        return
    if os.environ.get("RT_BENCH_SERVE"):
        _serve_main()
        return
    if os.environ.get("RT_BENCH_CB"):
        _cb_main()
        return
    if os.environ.get("RT_BENCH_DATA"):
        _data_main()
        return
    if os.environ.get("RT_BENCH_ENGINE"):
        _engine_main()
        return
    if os.environ.get("RT_BENCH_TRAIN_OBS"):
        _train_obs_main()
        return
    if "--engine-obs" in sys.argv[1:]:
        _engine_obs_round()
        return
    if "--rlhf-obs" in sys.argv[1:]:
        _rlhf_obs_round()
        return
    if "--train-obs" in sys.argv[1:]:
        _train_obs_round()
        return

    # TPU perf flags (latency-hiding scheduler, async collectives) must be
    # in the env before any child process initializes the backend, and the
    # children share one compile cache.
    sys.path.insert(0, _REPO_ROOT)
    from ray_tpu.parallel.xla_flags import apply_tpu_perf_flags
    from ray_tpu.util import compile_cache

    env = apply_tpu_perf_flags(dict(os.environ))
    env[compile_cache.ENV] = compile_cache.configure()
    platform, hbm = _probe_backend(timeout=300, env=env)
    if platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, jax found {platform!r}")
    env["RT_BENCH_PLATFORM"] = platform
    env["RT_BENCH_HBM_BYTES"] = hbm
    # Budget > worst-case sum of the inner phases' own subprocess timeouts
    # (2 sweeps x 400 + train 420 + fast 900 + decode 600).
    result = _run_inner(env, timeout=3000)
    details = result.setdefault("details", {})

    # RL phase: PPO + IMPALA env-steps/s, learner on the chip.
    details.update(_run_phase("RT_BENCH_RL", "RLBENCH", timeout=480,
                              env=env))

    # Serve phase: 410m bf16 forward behind @serve.batch on the chip.
    details.update(_run_phase(
        "RT_BENCH_SERVE", "SERVEBENCH", timeout=600, env=env,
        extra_env={"RT_BENCH_SERVE_PRESET": "410m",
                   "RT_BENCH_SERVE_DTYPE": "bf16"}))

    # Continuous-batching serve-under-load phase (decode_cb_* keys):
    # offered load sized so the static control saturates while continuous
    # admission keeps the tail bounded.
    cb_cfg = json.dumps(
        {"preset": "410m", "slots": 8, "prompt_len": 32,
         "short_tokens": 8, "long_tokens": 256, "long_frac": 0.05,
         "rps": 10.0, "duration_s": 20.0, "max_len": 512,
         "decode_stride": 16})
    details.update(_run_phase("RT_BENCH_CB", "CBBENCH", timeout=600, env=env,
                              extra_env={"RT_BENCH_CB_CFG": cb_cfg}))

    # RLHF phase: Anakin fused-vs-host env-steps/s plus one end-to-end
    # RLHF iteration (ContinuousEngine generate, streamed weight sync).
    details["rlhf"] = _run_phase("RT_BENCH_RLHF", "RLHFBENCH", timeout=900,
                                 env=env)

    # Data-ingestion phase — host-side input pipeline throughput (always
    # CPU; the chip is not involved).
    details.update(_run_phase("RT_BENCH_DATA", "DATABENCH", timeout=300))

    print(json.dumps(result))


if __name__ == "__main__":
    main()
