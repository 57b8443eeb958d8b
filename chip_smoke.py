#!/usr/bin/env python3
"""Drive ray_tpu's main paths once on a real TPU, end to end.

    python3 chip_smoke.py            # one chip: a serve phase, then a train phase
    python3 chip_smoke.py --chips 4  # four chips: the sharded train step against one device

The quickest proof that the system still starts on the chip. It goes through
the entry points a user calls, at the published TinyLlama-1.1B width (the
``"1b"`` preset: 32000 x 2048 x 22 layers, 32/4 heads, 5632), with random
weights made from ``--seed``:

- *serve*: ``ray_tpu.init()`` -> ``serve.run(continuous_llm_app("1b", ...))``
  with the replica granted the chip -> HTTP requests through the proxy, a few
  at once, prompts of mixed length up to ~1k tokens, 64 new tokens each, every
  stream read to its end, one prompt sent again so that it takes the prefix
  cache's warm path; then, after the app is shut down and the chip released,
  that prompt's greedy tokens (cold and warm) are held against
  ``generate.generate`` and the training forward on the same weights in a
  ``num_tpus=1`` task.
- *train*: ``JaxTrainer`` -> ``StepDriver`` with ``steps_per_launch`` > 1, bf16
  parameters, the Pallas flash kernel, sequence 2048, one checkpoint save; the
  loss must be finite and fall on a repeated batch.
- ``--chips 4``: one train worker granted four chips, the same model sharded
  fsdp x tp by ``auto_mesh`` through the same trainer path, compared with the
  same seed and global batch on a one-device mesh in the same worker.

This process never imports JAX: a chip belongs to one process at a time, and
the workers that were granted it must be the only ones to open it. What the
device is comes from those workers. Without a TPU the run fails at once; there
is no CPU fallback. Everything worth reading (shapes, memory, compile seconds
and cache hits, loss values, token counts, which process opened which device)
is printed on the way; the last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and any phase that fails ends the run with its traceback and a non-zero code.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional, Sequence

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import cloudpickle  # noqa: E402

# the functions below that run inside workers travel by value, so a worker
# needs nothing but ray_tpu on its path
cloudpickle.register_pickle_by_value(sys.modules[__name__])

GIB = 1 << 30


def say(phase: str, text: str) -> None:
    print(f"[{phase}] {text}", flush=True)


# ---------------------------------------------------------------------------
# what runs inside the workers that hold the chip
# ---------------------------------------------------------------------------

def _device_report(counter=None) -> Dict[str, Any]:
    """The device as the process that holds it sees it."""
    import jax

    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    out = {"pid": os.getpid(), "platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices),
           "peak_bytes": [m.get("peak_bytes_in_use", 0) for m in stats],
           "bytes_limit": stats[0].get("bytes_limit", 0),
           "compile_cache": os.environ.get("JAX_COMPILATION_CACHE_DIR")}
    if counter is not None:
        out.update(counter.snapshot())
    return out


def _time_fence() -> Dict[str, float]:
    """Does ``block_until_ready`` wait for the device? Time a large matmul
    chain three ways: to the return of the call, to ``block_until_ready``,
    and to a host read of the result."""
    import jax
    import jax.numpy as jnp

    n, reps = 8192, 32
    a = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a: jax.lax.fori_loop(
        0, reps, lambda i, x: (x @ a) * (1.0 / n), a))
    float(f(a)[0, 0])  # compile the chain, and the read's own little program
    t0 = time.perf_counter()
    y = f(a)
    t_call = time.perf_counter() - t0
    y.block_until_ready()
    t_block = time.perf_counter() - t0
    float(y[0, 0])
    t_read = time.perf_counter() - t0
    return {"call_s": t_call, "block_s": t_block, "read_s": t_read,
            "tflops_by_block": reps * 2 * n ** 3 / t_block / 1e12}


def _reference_generate(preset: str, seed: int, prompt: List[int],
                        served: Dict[str, List[int]], max_len: int
                        ) -> Dict[str, Any]:
    """``num_tpus=1`` task, run once the serve app has released the chip.
    Two references on the same seeded weights the replica built, neither of
    them the engine's code: greedy ``generate.generate`` on the prompt, and
    ``llama.forward`` (the training forward, no cache) over the prompt and
    each answer the replica streamed for it. The second gives, for every
    streamed token, how far its logit lies below the best one at that
    position: 0 where the replica took the reference's argmax, a bf16
    rounding where two programs broke a near-tie differently (weights are
    random, so logits are nearly flat and near-ties are many), and several
    units where a token is simply wrong."""
    from ray_tpu.util.compile_cache import CompileCounter

    counter = CompileCounter()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import generate as G
    from ray_tpu.models import llama

    out: Dict[str, Any] = {}
    if jax.devices()[0].platform == "tpu":
        out["fence"] = _time_fence()
    cfg = llama.PRESETS[preset]
    params = llama.init_params(jax.random.key(seed), cfg)
    n_new = len(next(iter(served.values())))
    t0 = time.perf_counter()
    ref = np.asarray(G.generate(
        params, jnp.asarray([prompt], jnp.int32), cfg,
        max_new_tokens=n_new, max_len=max_len))[0].tolist()
    out["generate_s"] = time.perf_counter() - t0
    out["reference"] = ref

    forward = jax.jit(lambda p, t: llama.forward(p, t, cfg))
    out["answers"] = {}
    for name, toks in dict(served, reference=ref).items():
        ctx = jnp.asarray([prompt + toks[:-1]], jnp.int32)
        logits = np.asarray(forward(params, ctx)[0, len(prompt) - 1:])
        best = logits.max(axis=-1)
        got = logits[np.arange(len(toks)), toks]
        margin = best - got
        worst = int(margin.argmax())
        diff = [i for i, (a, b) in enumerate(zip(ref, toks)) if a != b]
        out["answers"][name] = {
            "finite": bool(np.isfinite(logits).all()),
            "not_argmax": int((margin > 0).sum()),
            "worst_margin": float(margin[worst]), "worst_at": worst,
            "logit_scale": float(np.abs(logits).max()),
            "parts_from_generate_at": diff[0] if diff else None,
            # at the first parting: the two candidates under the forward
            "parting": ({"generate_token": ref[diff[0]],
                         "generate_logit": float(logits[diff[0], ref[diff[0]]]),
                         "served_token": toks[diff[0]],
                         "served_logit": float(got[diff[0]])}
                        if diff else None)}
    out["device"] = _device_report(counter)
    return out


def _train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``: StepDriver over the dataset shard, fused K
    steps per launch, one checkpoint save, everything reported back through
    the session. With ``compare_single`` (the four-chip run) the same steps
    are then taken on a one-device mesh from the same seed and batches."""
    from ray_tpu.util.compile_cache import CompileCounter

    counter = CompileCounter()
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.context import mesh_scope
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.driver import StepDriver

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    k = train.get_fast_path().steps_per_launch
    batch, seq = config["batch"], config["seq"]
    # bf16 parameters (and so bf16 adamw moments) as the benchmark's train
    # cells set them, the flash kernel, the chunked loss
    cfg = dataclasses.replace(
        llama.PRESETS[config["preset"]], param_dtype=jnp.bfloat16,
        attn_impl="flash", loss_chunk=config["loss_chunk"],
        **({"n_layers": config["n_layers"]} if config.get("n_layers") else {}))
    seq = min(seq, cfg.max_seq_len)
    optimizer = ts.default_optimizer(lr=1e-3, warmup_steps=1, total_steps=50)

    shard = train.get_dataset_shard("train")
    prestacked = len(devices) == 1
    if prestacked:
        # the TPU feed path: stacked [K, B, S+1] groups put on the device
        # ahead of the step
        feed = shard.iter_jax_batches(
            batch_size=batch, drop_last=True, stack=k,
            prefetch_batches=train.get_fast_path().prefetch_batches)
    else:
        # across devices the driver stacks host batches itself and places
        # them by its sharding plan
        feed = shard.iter_batches(batch_size=batch, drop_last=True)
    batches = [{"tokens": b["data"]} for b in feed]

    def run(mesh, label: str, save_at: Optional[int]) -> Dict[str, Any]:
        params, opt_state = ts.init_sharded_state(
            jax.random.key(config["seed"]), cfg, mesh, optimizer)
        driver = StepDriver(cfg, optimizer, mesh=mesh)
        out: Dict[str, Any] = {"label": label, "mesh": dict(mesh.shape)}

        # Is the Pallas kernel in the compiled program? Lower the fused-K
        # step for the arguments it is about to get and read its text. (The
        # driver's own call compiles the same program again; with the
        # persistent cache on, that second compile is a cache hit.)
        group = batches[0] if prestacked else StepDriver._stack(batches[:k])
        t0 = time.perf_counter()
        with mesh_scope(mesh):
            compiled = driver._multi._jit.lower(
                params, opt_state, driver._place(group, stacked=True)).compile()
        out["lower_compile_s"] = time.perf_counter() - t0
        text = compiled.as_text()
        out["tpu_custom_calls"] = text.count("tpu_custom_call")
        out["collectives"] = {
            name: text.count(name + "(") + text.count(name + "-start(")
            for name in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")}
        mem = compiled.memory_analysis()
        out["program_bytes"] = (mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                + mem.temp_size_in_bytes
                                - mem.alias_size_in_bytes)
        del compiled, text
        if on_tpu and not out["tpu_custom_calls"]:
            raise RuntimeError(
                "attn_impl='flash' was asked for, but the compiled step holds "
                "no tpu_custom_call: the Pallas kernel is not in the program")

        # where the state lies: every sharded leaf on every device of the mesh
        leaves = jax.tree.leaves((params, opt_state))
        out["state_bytes"] = sum(x.nbytes for x in leaves)
        out["state_devices"] = sorted(
            {s.device.id for x in leaves for s in x.addressable_shards})
        out["matrices_not_on_every_device"] = sum(
            1 for x in leaves if x.ndim >= 2 and len(
                {s.device.id for s in x.addressable_shards}) < mesh.size)
        big = max(leaves, key=lambda x: x.nbytes)
        out["largest_leaf"] = {
            "shape": list(big.shape), "dtype": str(big.dtype),
            "shard_shape": list(big.addressable_shards[0].data.shape),
            "devices": sorted(s.device.id for s in big.addressable_shards)}
        jax.block_until_ready(leaves)
        out["bytes_in_use_after_init"] = [
            (d.memory_stats() or {}).get("bytes_in_use", 0)
            for d in mesh.devices.flat]

        losses: List[float] = []
        launch = [0]

        def on_launch(metrics):
            launch[0] += 1
            if launch[0] == save_at:
                ckpt = Checkpoint.from_directory(
                    tempfile.mkdtemp(prefix="rt_smoke_ckpt_"))
                t_save = time.perf_counter()
                ckpt.save_pytree(driver.state[0], "state")
                out["checkpoint_call_s"] = time.perf_counter() - t_save
                train.report({"loss": metrics["loss"]}, checkpoint=ckpt)
            else:
                train.report({"loss": metrics["loss"]})
            losses.append(metrics["loss"])

        t0 = time.perf_counter()
        params, opt_state, _m = driver.run(params, opt_state, iter(batches),
                                           on_launch=on_launch,
                                           stacked=prestacked)
        jax.block_until_ready((params, opt_state))
        out["run_s"] = time.perf_counter() - t0
        out["losses"] = [float(v) for m in losses
                         for v in np.asarray(m).ravel()]
        out["steps"], out["launches"] = driver.steps, driver.launches
        out["steps_per_launch"] = driver.steps_per_launch
        out["jit_programs"] = driver.compile_count()
        del params, opt_state, driver
        return out

    if len(devices) > 1:
        mesh, mesh_cfg = ts.auto_mesh(len(devices), devices)
    else:
        mesh = make_mesh(MeshConfig(), devices)
    runs = [run(mesh, f"{len(devices)}-device mesh", config.get("save_at"))]
    if config.get("compare_single"):
        runs.append(run(make_mesh(MeshConfig(), devices[:1]),
                        "one-device mesh", None))
    train.report({
        "runs": runs, "device": _device_report(counter),
        "cfg": {"preset": config["preset"], "n_layers": cfg.n_layers,
                "d_model": cfg.d_model, "params": cfg.num_params(),
                "param_dtype": str(jnp.dtype(cfg.param_dtype)),
                "attn_impl": cfg.attn_impl, "batch": batch, "seq": seq}})


# ---------------------------------------------------------------------------
# what the parent does: start the runtime, drive it, read what came back
# ---------------------------------------------------------------------------

def _start_runtime(phase: str, chips: int) -> Any:
    """``ray_tpu.init()`` with no arguments must offer the host's chips."""
    import ray_tpu
    from ray_tpu import _native

    from ray_tpu._native import build as native_build

    was_there = os.path.exists(native_build.SO)
    native = _native._load()
    say(phase, "native module: " + (
        f"{'found' if was_there else 'built here with g++'} and loaded "
        f"({os.path.basename(native.__file__)})" if native is not None
        else "not built; the Python path is in use"))
    ray_tpu.init()
    offered = ray_tpu.cluster_resources().get("TPU", 0)
    say(phase, f"ray_tpu.init(): cluster offers TPU: {offered:g}")
    if offered < chips:
        ray_tpu.shutdown()
        raise RuntimeError(
            f"this host offers {offered:g} TPU chip(s) and the run needs "
            f"{chips}: chip_smoke.py runs on a TPU and nowhere else")
    return ray_tpu


def _device_lines(session: str) -> List[str]:
    """The ``rt-device`` lines of every worker of this session (see
    ``worker_main._log_device_use``): which process brought up which JAX
    backend, what it compiled, how much device memory it reached."""
    from ray_tpu._private.config import get_config

    lines = []
    pattern = os.path.join(get_config().session_dir_root, session, "logs",
                           "worker-*.log")
    for path in sorted(glob.glob(pattern)):
        with open(path, errors="replace") as f:
            mine = [ln.strip() for ln in f if ln.startswith("rt-device:")]
        lines += [ln for ln in mine if "backend-init" in ln]
        lines += [ln for ln in mine if " use " in ln][-1:]  # the last one
    return lines


def _check_chip_owners(phase: str, session: str, device: Dict[str, Any],
                       holders: int) -> None:
    """Only the workers that were granted the chip may have opened it:
    ``holders`` of them in this phase, the one that reported ``device``
    among them; not the parent, not a worker that was granted none."""
    time.sleep(2.5)  # a worker logs within a second of touching its backend
    lines = _device_lines(session)
    for ln in lines:
        say(phase, ln)
    inits = [ln for ln in lines if "backend-init" in ln]
    on_tpu = [ln for ln in inits if "platform=tpu" in ln]
    ungranted = [ln for ln in on_tpu if "chips=-" in ln]
    if ungranted:
        raise RuntimeError(f"a worker granted no chip opened one: {ungranted}")
    if "jax" in sys.modules:
        raise RuntimeError("the parent process imported jax")
    pids = sorted(int(ln.split("pid=")[1].split()[0]) for ln in on_tpu)
    uses = [dict(kv.split("=") for kv in ln.split()[3:])
            for ln in lines if " use " in ln]
    say(phase, f"compiled by this phase's chip workers: "
               f"{sum(int(u.get('programs', 0)) for u in uses)} programs, "
               f"{sum(int(u.get('cache_hits', 0)) for u in uses)} from the "
               f"persistent cache, "
               f"{sum(float(u.get('compile_s', 0)) for u in uses):.1f}s in the "
               f"compiler or the cache")
    say(phase, f"{len(inits)} worker(s) brought up a JAX backend, {len(pids)} "
               f"of them a TPU's (pids {pids}, each granted its chips); the "
               f"parent (pid {os.getpid()}) never imported jax")
    if device["platform"] == "tpu" and (
            len(pids) != holders or device["pid"] not in pids):
        raise RuntimeError(f"expected {holders} chip holder(s), pid "
                           f"{device['pid']} among them, and found {pids}")


def _stream_request(url: str, prompt: List[int], n_new: int,
                    timeout_s: float) -> Dict[str, Any]:
    """POST one prompt and read the streamed answer, one JSON token a line,
    to its end."""
    body = json.dumps({"tokens": prompt, "max_new_tokens": n_new}).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    tokens: List[int] = []
    first = None
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        for line in resp:
            if not line.strip():
                continue
            if first is None:
                first = time.perf_counter() - t0
            tokens.append(int(json.loads(line)))
    return {"tokens": tokens, "first_token_s": first,
            "wall_s": time.perf_counter() - t0}


def serve_phase(preset: str = "1b", *, vocab: int = 32000,
                max_len: int = 2048, max_slots: int = 8,
                prompt_lens: Sequence[int] = (16, 130, 517, 1000),
                new_tokens: int = 64, seed: int = 0,
                request_timeout_s: float = 600.0) -> Dict[str, Any]:
    """proxy -> handle -> replica -> ContinuousEngine, then the reference."""
    import random

    phase = "serve"
    ray_tpu = _start_runtime(phase, 1)
    from ray_tpu import serve
    from ray_tpu.serve.llm import continuous_llm_app

    try:
        session = ray_tpu.global_worker()._require_backend().session_name
        rng = random.Random(seed)
        prompts = [[rng.randrange(1, vocab) for _ in range(n)]
                   for n in prompt_lens]
        t0 = time.perf_counter()
        serve.run(continuous_llm_app(
            preset, max_slots=max_slots, max_len=max_len, seed=seed,
            name="smoke", ray_actor_options={"num_tpus": 1}),
            name="smoke", route_prefix="/smoke",
            http_options=serve.HTTPOptions(port=0))
        say(phase, f"serve.run(continuous_llm_app({preset!r}, max_slots="
                   f"{max_slots}, max_len={max_len}), num_tpus=1): healthy "
                   f"after {time.perf_counter() - t0:.1f}s (device init, "
                   f"weights, warm-up compiles)")
        url = f"http://127.0.0.1:{serve.http_port()}/smoke/"

        # first wave: every prompt at once; second wave: the longest prompt
        # again (its pages are now in the prefix cache: the warm admission
        # path) beside a prompt nobody has sent
        results: Dict[int, Dict[str, Any]] = {}

        def fire(i: int, prompt: List[int]) -> None:
            results[i] = _stream_request(url, prompt, new_tokens,
                                         request_timeout_s)

        longest = max(range(len(prompts)), key=lambda i: len(prompts[i]))
        fresh = [rng.randrange(1, vocab) for _ in range(prompt_lens[0] + 7)]
        waves = [list(enumerate(prompts)),
                 [(len(prompts), prompts[longest]), (len(prompts) + 1, fresh)]]
        for n, wave in enumerate(waves):
            t0 = time.perf_counter()
            threads = [threading.Thread(target=fire, args=w) for w in wave]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for i, prompt in wave:
                if i not in results:
                    raise RuntimeError(f"request {i} failed (see above)")
                r = results[i]
                say(phase, f"wave {n + 1} request {i}: prompt {len(prompt)} "
                           f"tokens -> {len(r['tokens'])} streamed, first "
                           f"after {r['first_token_s']:.2f}s, done in "
                           f"{r['wall_s']:.2f}s")
                if len(r["tokens"]) != new_tokens:
                    raise RuntimeError(
                        f"request {i} streamed {len(r['tokens'])} tokens, "
                        f"asked for {new_tokens}")
                if not all(0 <= t < vocab for t in r["tokens"]):
                    raise RuntimeError(f"request {i}: token out of range")
            say(phase, f"wave {n + 1}: {len(wave)} concurrent requests, "
                       f"{len(wave) * new_tokens} tokens in "
                       f"{time.perf_counter() - t0:.2f}s (compiles included)")
        time.sleep(1.5)  # the controller polls its replicas once a second
        for app in serve.status().values():
            for name, dep in app["deployments"].items():
                say(phase, f"deployment {name!r}: {dep['replicas']} replica, "
                           f"window stats {dep['stats']}")
                if not dep["stats"].get("kv_hit_tokens"):
                    raise RuntimeError("the repeated prompt did not take the "
                                       "prefix cache's warm path")
        serve.shutdown()
        say(phase, "serve.shutdown(): the replica is gone, the chip is free")

        # the reference, in a task that is granted the chip in its turn
        served = {"cold": results[longest]["tokens"],
                  "warm (prefix cache)": results[len(prompts)]["tokens"]}
        ref = ray_tpu.get(ray_tpu.remote(num_tpus=1)(_reference_generate)
                          .remote(preset, seed, prompts[longest], served,
                                  max_len), timeout=900)
        dev = ref["device"]
        if "fence" in ref:
            f = ref["fence"]
            say(phase, f"fence on {dev['kind']}: 32 chained 8192^2 bf16 "
                       f"matmuls return from the call after "
                       f"{f['call_s'] * 1e3:.2f} ms, from block_until_ready "
                       f"after {f['block_s'] * 1e3:.1f} ms, from a host read "
                       f"after {f['read_s'] * 1e3:.1f} ms "
                       f"({f['tflops_by_block']:.0f} TFLOP/s by "
                       f"block_until_ready): block_until_ready waits")
            if f["block_s"] < 0.9 * f["read_s"]:
                raise RuntimeError("block_until_ready returned long before "
                                   "the host read: it does not wait")
        say(phase, f"reference task on prompt {len(prompts[longest])}: "
                   f"generate.generate {ref['generate_s']:.1f}s with compile; "
                   f"device {dev['platform']} {dev['kind']!r} x{dev['count']}"
                   f", peak {max(dev['peak_bytes']) / GIB:.2f} GiB of "
                   f"{dev['bytes_limit'] / GIB:.2f}; programs "
                   f"{dev.get('programs')}, cache hits {dev.get('cache_hits')}"
                   f", compile {dev.get('compile_s')}s; cache at "
                   f"{dev['compile_cache']}")
        for name, a in ref["answers"].items():
            where = a["parts_from_generate_at"]
            say(phase, f"{name}: {new_tokens - a['not_argmax']}/{new_tokens} "
                       f"tokens are the forward's argmax; worst token lies "
                       f"{a['worst_margin']:.4f} below it (new token "
                       f"{a['worst_at']}; logits reach {a['logit_scale']:.2f})"
                       f"; " + ("equal to generate.generate" if where is None
                                else f"parts from generate.generate at new "
                                     f"token {where}: {a['parting']}"))
            # eight bf16 steps at the size of the logits: a near-tie broken
            # the other way passes, a wrong token (units below) does not
            tol = a["logit_scale"] * 2.0 ** -5
            if not a["finite"] or a["worst_margin"] > tol:
                raise RuntimeError(
                    f"{name}: a streamed token lies {a['worst_margin']:.4f} "
                    f"below the reference's best logit (tolerance {tol:.4f})")
        _check_chip_owners(phase, session, dev, holders=2)  # replica, task
        return {"device": dev, "requests": len(results)}
    finally:
        try:
            serve.shutdown()
        finally:
            ray_tpu.shutdown()


def train_phase(preset: str = "1b", *, vocab: int = 32000, chips: int = 1,
                batch: int = 1, seq: int = 2048, steps_per_launch: int = 4,
                launches: int = 4, save_at: Optional[int] = 2,
                loss_chunk: int = 256, n_layers: Optional[int] = None,
                compare_single: bool = False, seed: int = 0
                ) -> Dict[str, Any]:
    """JaxTrainer -> StepDriver on a worker granted ``chips`` chips."""
    import numpy as np

    phase = "train" if not compare_single else f"train x{chips}"
    ray_tpu = _start_runtime(phase, chips)
    from ray_tpu import data as rt_data
    from ray_tpu.train import (FastPathConfig, JaxTrainer, RunConfig,
                               ScalingConfig)

    try:
        session = ray_tpu.global_worker()._require_backend().session_name
        # one batch, repeated for every step: the loss has to fall on it
        block = np.random.default_rng(seed).integers(
            0, vocab, (batch, seq + 1)).astype(np.int32)
        tokens = np.tile(block, (steps_per_launch * launches, 1))
        storage = tempfile.mkdtemp(prefix="rt_smoke_train_")
        t0 = time.perf_counter()
        result = JaxTrainer(
            _train_loop,
            train_loop_config={
                "preset": preset, "batch": batch, "seq": seq, "seed": seed,
                "loss_chunk": loss_chunk, "n_layers": n_layers,
                "save_at": save_at, "compare_single": compare_single},
            scaling_config=ScalingConfig(num_workers=1,
                                         tpu_chips_per_worker=chips),
            run_config=RunConfig(
                storage_path=storage,
                fast_path=FastPathConfig(steps_per_launch=steps_per_launch)),
            datasets={"train": rt_data.from_numpy(tokens)}).fit()
        wall = time.perf_counter() - t0
        final = result.metrics
        dev, cfg, runs = final["device"], final["cfg"], final["runs"]
        say(phase, f"JaxTrainer.fit(): {wall:.1f}s; model {cfg['preset']} "
                   f"{cfg['params'] / 1e6:.0f}M params, {cfg['n_layers']} "
                   f"layers x {cfg['d_model']}, {cfg['param_dtype']}, "
                   f"attn={cfg['attn_impl']}, batch {cfg['batch']} x seq "
                   f"{cfg['seq']}")
        if n_layers:
            say(phase, f"DEPTH CUT to {n_layers} layers (widths unchanged)")
        for r in runs:
            say(phase, f"{r['label']} {r['mesh']}: {r['launches']} launches "
                       f"x K={r['steps_per_launch']} = {r['steps']} steps in "
                       f"{r['run_s']:.2f}s (first launch compiles); "
                       f"tpu_custom_call in the compiled step: "
                       f"{r['tpu_custom_calls']}; collectives "
                       f"{r['collectives']}; step program "
                       f"{r['program_bytes'] / GIB:.2f} GiB/device by "
                       f"memory_analysis; lower+compile "
                       f"{r['lower_compile_s']:.1f}s; jit cache entries "
                       f"{r['jit_programs']}")
            say(phase, f"{r['label']}: state {r['state_bytes'] / GIB:.2f} GiB "
                       f"on devices {r['state_devices']}; largest leaf "
                       f"{r['largest_leaf']}; bytes in use after init "
                       f"{[round(b / GIB, 2) for b in r['bytes_in_use_after_init']]}"
                       f" GiB")
            say(phase, f"{r['label']}: losses "
                       f"{[round(v, 4) for v in r['losses']]}")
            if len(r["losses"]) != steps_per_launch * launches:
                raise RuntimeError(f"{r['label']}: {len(r['losses'])} losses "
                                   f"for {steps_per_launch * launches} steps")
            if not all(np.isfinite(r["losses"])):
                raise RuntimeError(f"{r['label']}: loss not finite")
            if not r["losses"][-1] < r["losses"][0]:
                raise RuntimeError(f"{r['label']}: loss did not fall on a "
                                   f"repeated batch")
            if r["steps_per_launch"] != steps_per_launch or \
                    r["launches"] != launches:
                raise RuntimeError(f"{r['label']}: the driver did not fuse "
                                   f"{steps_per_launch} steps per launch")
        say(phase, f"device {dev['platform']} {dev['kind']!r} x{dev['count']}"
                   f", peak per device "
                   f"{[round(b / GIB, 2) for b in dev['peak_bytes']]} GiB of "
                   f"{dev['bytes_limit'] / GIB:.2f}; programs "
                   f"{dev.get('programs')}, cache hits {dev.get('cache_hits')}"
                   f", compile {dev.get('compile_s')}s; cache at "
                   f"{dev['compile_cache']}")
        if save_at:
            saved = [m["checkpoint_path"] for m in result.metrics_history
                     if "checkpoint_path" in m]
            size = sum(os.path.getsize(os.path.join(d, f))
                       for p in saved for d, _, fs in os.walk(p) for f in fs)
            say(phase, f"checkpoint at launch {save_at}: {saved}, "
                       f"{size / GIB:.2f} GiB on disk; the save call held the "
                       f"step loop {runs[0].get('checkpoint_call_s', 0):.2f}s")
            if len(saved) != 1 or size < runs[0]["state_bytes"] / 4:
                raise RuntimeError("the checkpoint is not on disk")
        if compare_single:
            sharded, single = runs
            if sharded["state_devices"] != list(range(chips)) or \
                    sharded["matrices_not_on_every_device"]:
                raise RuntimeError(
                    f"the sharded state is not on every device: "
                    f"{sharded['matrices_not_on_every_device']} matrices of "
                    f"parameters or optimizer state lie on fewer")
            # (the CPU backend keeps no memory statistics)
            if dev["platform"] == "tpu" and \
                    min(sharded["bytes_in_use_after_init"]) < \
                    sharded["state_bytes"] / chips / 2:
                raise RuntimeError("a device of the mesh holds no share of "
                                   "the state")
            if chips > 1 and not any(sharded["collectives"].values()):
                raise RuntimeError("the sharded step holds no collective")
            worst = max(abs(a - b) / abs(b) for a, b in
                        zip(sharded["losses"], single["losses"]))
            say(phase, f"sharded against one device, same seed and global "
                       f"batch: largest relative loss difference "
                       f"{worst:.4f} over {len(single['losses'])} steps "
                       f"(bound 0.02, the CPU precedent's)")
            if worst > 2e-2:
                raise RuntimeError("the sharded step disagrees with the "
                                   "one-device step")
        _check_chip_owners(phase, session, dev, holders=1)
        return {"device": dev}
    finally:
        ray_tpu.shutdown()


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded train path against one device, "
                         "and no other phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={platforms!r} keeps JAX off the "
              f"TPU; this script runs on a TPU and nowhere else",
              file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    if args.chips == 1:
        devices = [serve_phase(seed=args.seed)["device"],
                   train_phase(seed=args.seed)["device"]]
    else:
        # global batch 2: one sequence per fsdp lane, and what still fits the
        # one-device mesh it is compared with
        devices = [train_phase(chips=4, batch=2, steps_per_launch=2,
                               launches=3, save_at=None, compare_single=True,
                               seed=args.seed)["device"]]
    for dev in devices:
        if dev["platform"] != "tpu" or dev["count"] != args.chips:
            print(f"chip_smoke: a phase ran on {dev['platform']} x"
                  f"{dev['count']}, not on {args.chips} TPU chip(s)",
                  file=sys.stderr)
            return 1
    say("done", f"all phases passed in {time.perf_counter() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[-1]["platform"], "kind": devices[-1]["kind"],
        "count": devices[-1]["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
