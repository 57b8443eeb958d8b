"""Continuous-batching LLM serving: the deployment that makes
``models/serving.ContinuousBatcher`` live, its static-batch control, and
the Poisson-arrival load driver the bench/envelope/smoke legs share.

No reference counterpart — Ray pairs with external engines (vLLM) for
this; here the engine is in-repo (``models/serving.py``) and the serve
layer's job is admission, streaming and telemetry:

  - ``ContinuousLLM`` hosts ONE :class:`ContinuousEngine` per replica.
    ``__call__`` admits the request (mid-flight — no batch boundary) and
    returns an async generator that yields each token the moment the
    engine samples it, so tokens flow through the replica stream pump and
    the proxy's ``_stream_response`` TTFT/inter-token path. Slot
    occupancy lands on the PR 8 ``rt_serve_batch_occupancy`` series
    (``fn="cb:<name>"``) plus the ``rt_serve_cb_slots_active`` gauge.
  - ``StaticLLM`` is the honest control: the SAME model behind
    ``@serve.batch`` — requests wait for batch formation, decode in
    lockstep, and respond only when the whole fused ``generate`` returns.
  - ``poisson_load`` drives open-loop Poisson arrivals against either and
    reports throughput + latency percentiles (the ``decode_cb_*`` bench
    keys and the chaos_smoke serve-load leg both use it).
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.serve.batching import batch as _serve_batch

__all__ = ["ContinuousLLM", "StaticLLM", "cb_vs_static_load",
           "continuous_llm_app", "static_llm_app", "poisson_load",
           "http_token_request"]


def _parse_request(request: Any) -> Dict[str, Any]:
    """Accept a ServeRequest (HTTP), a dict (handle call), or a JSON
    string; returns {"tokens": [...], "max_new_tokens": int}."""
    if hasattr(request, "json"):
        body = request.json()
    elif isinstance(request, (str, bytes)):
        body = json.loads(request)
    else:
        body = request
    if not isinstance(body, dict) or "tokens" not in body:
        raise ValueError("expected {'tokens': [...], 'max_new_tokens': n}")
    return body


class ContinuousLLM:
    """One continuous-batching engine per replica; streams token ids.

    Cache-aware by default: the engine retains completed slots' KV pages
    in a bytes-budgeted prefix cache (``kv_cache_bytes``; 0 disables), so
    shared-prefix admission prefills only the uncached suffix and TTFT
    collapses on hits. Residency is reported through ``kv_residency`` so
    the handle router can bias power-of-two choice toward the warm
    replica; hit/miss/eviction/bytes land on the ``rt_serve_kv_cache_*``
    series.
    """

    def __init__(self, preset: str = "debug", *, max_slots: int = 8,
                 max_len: int = 256, decode_stride: int = 8,
                 seed: int = 0, name: str = "",
                 kv_cache_bytes: int = 64 * 1024 * 1024,
                 sampling: bool = False):
        import jax

        from ray_tpu.models import llama
        from ray_tpu.models.serving import ContinuousEngine
        from ray_tpu.serve import obs

        self.preset = preset
        self._name = name or f"cb-{preset}"
        self.cfg = llama.PRESETS[preset]
        self.params = llama.init_params(jax.random.key(seed), self.cfg)
        tags = {"fn": f"cb:{self._name}"}
        gauge_tags = {"deployment": self._name}
        # counter snapshots: kv metrics are cumulative in the engine;
        # the tick publishes deltas so the Prometheus counters advance
        self._kv_seen = {"hits": 0, "misses": 0, "evictions": 0}
        self._kv_pub_lock = threading.Lock()

        def on_tick(active: int, slots: int) -> None:
            # the continuous-batching yardstick: fused rows per decode
            # step and the fraction of the slot budget they fill
            obs.batch_size_hist().observe(active, tags=tags)
            obs.batch_occupancy_hist().observe(active / max(1, slots),
                                               tags=tags)
            obs.cb_slots_gauge().set(active, tags=gauge_tags)
            self._publish_kv()

        self.engine = ContinuousEngine(self.params, self.cfg,
                                       max_slots=max_slots, max_len=max_len,
                                       decode_stride=decode_stride,
                                       on_tick=on_tick,
                                       kv_cache_bytes=kv_cache_bytes,
                                       kv_label=self._name,
                                       sampling=sampling)
        self._kv_push_s = float(os.environ.get("RT_KV_PUSH_S", "5"))
        if kv_cache_bytes and self._kv_push_s > 0:
            # @memkv/ pushes go through a blocking GCS RPC — NEVER from
            # on_tick: the tick callback runs on the engine thread, and
            # a multi-second kv_put stall there freezes admission AND
            # decode for every live slot (measured: warm-leg p99 went
            # 181ms -> 2.6s in the kv bench before this moved off-tick)
            threading.Thread(target=self._kv_push_loop,
                             name=f"kv-push:{self._name}",
                             daemon=True).start()

    def _kv_push_loop(self) -> None:
        """Throttled ``@memkv/`` snapshots so ``rt memory`` (any
        process) sees this replica's retained pages like it sees object
        ledgers. Dies with the engine (daemon; exits on shutdown);
        ``RT_KV_PUSH_S`` tunes the cadence (<= 0 disables)."""
        import ray_tpu

        while not self.engine.stopped():
            time.sleep(self._kv_push_s)
            try:
                from ray_tpu.util import memory as rt_memory

                if ray_tpu.is_initialized():
                    rt_memory.publish_kv_snapshot(
                        ray_tpu.global_worker()._require_backend())
            except Exception:  # noqa: BLE001 — telemetry best-effort
                pass

    def _publish_kv(self) -> None:
        """Engine-tick kv telemetry: counter deltas onto the
        ``rt_serve_kv_cache_*`` series (in-process metric writes only —
        the cross-process snapshot push lives on its own thread)."""
        kv = self.engine.kv_stats()
        if not kv:
            return
        from ray_tpu.serve import obs

        tags = {"deployment": self._name}
        with self._kv_pub_lock:
            d_hits = kv["hits"] - self._kv_seen["hits"]
            d_miss = kv["misses"] - self._kv_seen["misses"]
            d_evic = kv["evictions"] - self._kv_seen["evictions"]
            self._kv_seen = {"hits": kv["hits"], "misses": kv["misses"],
                             "evictions": kv["evictions"]}
        if d_hits > 0:
            obs.kv_cache_hits().inc(d_hits, tags=tags)
        if d_miss > 0:
            obs.kv_cache_misses().inc(d_miss, tags=tags)
        if d_evic > 0:
            obs.kv_cache_evictions().inc(d_evic, tags=tags)
        obs.kv_cache_bytes().set(kv["bytes"], tags=tags)

    def engine_stats(self) -> Dict[str, Any]:
        """Duck-typed surface the replica's ``stats_window`` picks up —
        slot occupancy and kv-cache stats travel to the controller,
        `rt serve status` and the autoscaler decision log."""
        return self.engine.stats()

    def kv_residency(self) -> List[str]:
        """Duck-typed surface the replica reports on every reply: the
        warm prefix digests the router matches request prompts against
        (cache-affinity routing)."""
        return self.engine.kv_residency()

    def check_health(self) -> None:
        """A dead engine thread must fail the replica health check so
        the controller replaces the replica instead of routing requests
        into a wedged engine."""
        self.engine.check_alive()

    async def __call__(self, request: Any):
        from ray_tpu.serve import obs

        body = _parse_request(request)
        prompt = body["tokens"]
        n_new = int(body.get("max_new_tokens", 16))
        temperature = float(body.get("temperature", 0.0))
        top_k = int(body.get("top_k", 0))
        sample_seed = int(body.get("seed", 0))
        # the request context is ambient here (handle_request runs the
        # callable under it) and carries the front's stamps
        req_ctx = obs.current_request_context()
        loop = asyncio.get_running_loop()
        aq: "asyncio.Queue" = asyncio.Queue()
        engine = self.engine

        def deliver(burst, t_emit):
            # one loop wakeup per engine TICK (token burst), not per
            # token — and no executor thread parks per stream (the
            # default pool has ~cpu+4 threads; a dozen concurrent
            # streams would starve it and serialize the whole replica).
            # How long this loop took to pick the burst up is the stream
            # pump's lag: the first boundary after the engine thread.
            engine.note_pump_lag(time.perf_counter() - t_emit)
            for tok in burst:
                aq.put_nowait(tok)

        handle = engine.submit_cb(
            prompt, n_new,
            lambda burst: loop.call_soon_threadsafe(
                deliver, burst, time.perf_counter()),
            temperature=temperature, top_k=top_k, seed=sample_seed,
            # the flight recorder parents the engine lifecycle span on
            # the serve request span — rt trace <rid> descends into
            # queue_wait/kv_restore/prefill/decode, with the cached and
            # prompt token counts beside them
            obs_ctx=req_ctx)

        async def stream():
            try:
                while True:
                    tok = await aq.get()
                    if tok is None:
                        return
                    yield tok
            finally:
                # client gone mid-stream: free the slot for the next
                # admission instead of decoding into the void
                engine.cancel(handle)

        return stream()


class StaticLLM:
    """The ``@serve.batch`` control: same model, batch-boundary batching.

    Shapes are static (prompt padded to ``prompt_pad``, always
    ``max_new`` decode steps) so ONE compiled program serves every
    flush; requests pay batch-formation wait plus the full fused
    ``generate`` of the slowest batch — exactly the head-of-line
    economics continuous batching removes. Note right-padding feeds pad
    garbage into the shared forward, so per-request token exactness is
    NOT claimed here (it is for ``ContinuousLLM``) — this class is the
    throughput/latency control, not a correctness reference.
    """

    def __init__(self, preset: str = "debug", *, max_batch: int = 8,
                 prompt_pad: int = 16, max_new: int = 16,
                 batch_wait_timeout_s: float = 0.02, seed: int = 0):
        import jax

        from ray_tpu.models import llama

        self.preset = preset
        self.cfg = llama.PRESETS[preset]
        self.params = llama.init_params(jax.random.key(seed), self.cfg)
        self.prompt_pad = prompt_pad
        self.max_new = max_new
        self.max_batch = max_batch
        # a PER-INSTANCE batched function: the decorator stores batch
        # config on the wrapper it returns, so decorating a method would
        # share one config across every instance in the process (a
        # second deployment's max_batch would clobber the first's)
        self._gen_batch = _serve_batch(
            max_batch_size=max_batch,
            batch_wait_timeout_s=batch_wait_timeout_s)(self._generate_batch)

    async def __call__(self, request: Any) -> List[int]:
        body = _parse_request(request)
        n_new = min(int(body.get("max_new_tokens", 16)), self.max_new)
        toks = await self._gen_batch(
            (list(body["tokens"])[: self.prompt_pad], n_new))
        return toks[:n_new]

    async def _generate_batch(self, items: List[Any]) -> List[List[int]]:
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.models import generate as G

        toks = np.zeros((self.max_batch, self.prompt_pad), dtype=np.int32)
        for i, (prompt, _) in enumerate(items):
            toks[i, : len(prompt)] = prompt
        out = G.generate(self.params, jnp.asarray(toks), self.cfg,
                         max_new_tokens=self.max_new)
        arr = np.asarray(out)
        return [arr[i].tolist() for i in range(len(items))]


def continuous_llm_app(preset: str = "debug", *, max_slots: int = 8,
                       max_len: int = 256, decode_stride: int = 8,
                       name: str = "CB",
                       max_ongoing_requests: Optional[int] = None,
                       autoscaling_config=None,
                       ray_actor_options: Optional[Dict] = None,
                       num_replicas: int = 1, seed: int = 0,
                       kv_cache_bytes: int = 64 * 1024 * 1024,
                       sampling: bool = False):
    """A ready-to-run continuous-batching Application. ``max_ongoing``
    defaults to 2x the slot count: the engine's pending queue absorbs a
    burst while slots drain, and the replica rejects beyond that.
    ``kv_cache_bytes=0`` disables prefix/KV reuse (the cold-prefill
    control the cache bench compares against)."""
    from ray_tpu import serve

    dep = serve.deployment(ContinuousLLM).options(
        name=name,
        num_replicas=None if autoscaling_config else num_replicas,
        max_ongoing_requests=max_ongoing_requests or 2 * max_slots,
        autoscaling_config=autoscaling_config,
        ray_actor_options=ray_actor_options)
    return dep.bind(preset, max_slots=max_slots, max_len=max_len,
                    decode_stride=decode_stride, seed=seed, name=name,
                    kv_cache_bytes=kv_cache_bytes, sampling=sampling)


def static_llm_app(preset: str = "debug", *, max_batch: int = 8,
                   prompt_pad: int = 16, max_new: int = 16,
                   batch_wait_timeout_s: float = 0.02, name: str = "Static",
                   max_ongoing_requests: int = 64, seed: int = 0,
                   ray_actor_options: Optional[Dict] = None):
    """The static ``@serve.batch`` control Application."""
    from ray_tpu import serve

    dep = serve.deployment(StaticLLM).options(
        name=name, max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options)
    return dep.bind(preset, max_batch=max_batch, prompt_pad=prompt_pad,
                    max_new=max_new,
                    batch_wait_timeout_s=batch_wait_timeout_s, seed=seed)


# ---------------------------------------------------------------------------
# Poisson-arrival load driver
# ---------------------------------------------------------------------------


def cb_vs_static_load(*, preset: str = "debug", slots: int = 8,
                      max_len: int = 384, decode_stride: int = 16,
                      prompt_len: int = 8, short_tokens: int = 2,
                      long_tokens: int = 256, long_frac: float = 0.05,
                      rps: float = 15.0, duration_s: float = 15.0,
                      num_proxies: int = 2, timeout_s: float = 240.0,
                      seed: int = 42,
                      route_base: str = "cbvs",
                      ray_actor_options: Optional[Dict] = None
                      ) -> Dict[str, Dict[str, Any]]:
    """THE continuous-vs-static comparison leg, shared by ``bench.py``
    (``decode_cb_*``), ``rt scale-envelope`` (``serve_under_load``) and
    ``scripts/chaos_smoke.sh``: open-loop Poisson arrivals round-robined
    over the proxy fleet at EQUAL offered load and a heterogeneous
    short/long decode-length mix, against (a) the live continuous-
    batching app and (b) the ``@serve.batch`` control provisioned at
    ``max_new=long_tokens`` (a batch-boundary system decodes its longest
    admissible request every flush — the waste slot admission avoids).
    One implementation so the three surfaces cannot drift apart on
    methodology; callers own their parameter sizing and assertions.

    Requires an initialized ray_tpu; deploys/tears down its own apps
    (``<route_base>-cb`` / ``<route_base>-static``), one after the other,
    each replica with ``ray_actor_options`` (``{"num_tpus": 1}`` puts it on
    the chip; a replica granted none runs on the CPU). Returns
    {"continuous": poisson_result, "static": poisson_result}.
    """
    import itertools

    from ray_tpu import serve

    prompt = list(range(1, prompt_len + 1))
    results: Dict[str, Dict[str, Any]] = {}
    for leg, app, route in (
        ("continuous",
         continuous_llm_app(preset, max_slots=slots, max_len=max_len,
                            decode_stride=decode_stride, name="CB",
                            max_ongoing_requests=4 * slots,
                            ray_actor_options=ray_actor_options),
         f"/{route_base}-cb"),
        ("static",
         static_llm_app(preset, max_batch=slots, prompt_pad=prompt_len,
                        max_new=long_tokens, name="Static",
                        max_ongoing_requests=4 * slots,
                        ray_actor_options=ray_actor_options),
         f"/{route_base}-static"),
    ):
        name = f"{route_base}-{leg}"
        serve.run(app, name=name, route_prefix=route,
                  http_options=serve.HTTPOptions(port=0,
                                                 num_proxies=num_proxies))
        ports = serve.proxy_ports()
        fires = {}
        for p in ports:
            for n in (short_tokens, long_tokens):
                fires[(p, n)] = http_token_request(
                    f"http://127.0.0.1:{p}{route}/", prompt, n,
                    timeout_s=timeout_s)
                fires[(p, n)]()  # warmup: replica spawn + XLA compiles
        rr = itertools.cycle(ports)
        # deterministic length SCHEDULE, consumed by fire order: the two
        # legs see the same short/long multiset and near-identical
        # ordering (worker-thread scheduling and client sheds can still
        # skew tail placement — per-arrival determinism would need index
        # plumbing through poisson_load)
        mix_rng = random.Random(7)
        schedule = [long_tokens if mix_rng.random() < long_frac
                    else short_tokens
                    for _ in range(int(rps * duration_s * 4) + 64)]
        counter = itertools.count()
        lock = threading.Lock()

        def fire():
            with lock:
                i = next(counter)
                port = next(rr)
            n = schedule[min(i, len(schedule) - 1)]
            return fires[(port, n)]()

        results[leg] = poisson_load(fire, rps=rps, duration_s=duration_s,
                                    seed=seed)
        serve.delete(name)
    return results


def http_token_request(url: str, prompt: List[int],
                       max_new_tokens: int,
                       timeout_s: float = 120.0) -> Callable[[], int]:
    """A request closure for :func:`poisson_load`: POSTs the prompt and
    reads the FULL response (streamed chunks or one JSON list); returns
    the number of generated tokens observed."""
    import urllib.request

    body = json.dumps({"tokens": prompt,
                       "max_new_tokens": max_new_tokens}).encode()

    def fire() -> int:
        req = urllib.request.Request(
            url, data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            payload = r.read()
        text = payload.decode().strip()
        if not text:
            return 0
        if text.startswith("["):
            return len(json.loads(text))
        return len(text.splitlines())

    return fire


def poisson_load(request_fn: Callable[[], int], *, rps: float,
                 duration_s: float, seed: int = 0,
                 max_inflight: int = 64) -> Dict[str, Any]:
    """Open-loop Poisson arrivals: fire ``request_fn`` at exponentially
    spaced instants for ``duration_s`` and report wall latencies.

    Open-loop matters: a closed loop (fire-when-done) lets a slow server
    hide its queueing by slowing the client down — here late requests
    keep arriving on schedule (up to ``max_inflight``), so p99 reflects
    what an independent client population would see.

    ``request_fn`` returns the token count, or ``(token_count,
    ttft_seconds)`` — the KV-cache bench's streamed closures report
    time-to-first-token, surfaced as ``ttft_p50_ms``/``ttft_p99_ms``.
    """
    from concurrent.futures import ThreadPoolExecutor

    rng = random.Random(seed)
    t = 0.0
    arrivals: List[float] = []
    while t < duration_s:
        t += rng.expovariate(rps)
        if t < duration_s:
            arrivals.append(t)
    lat: List[float] = []
    ttfts: List[float] = []
    toks = [0]
    failed = [0]
    shed = [0]
    lock = threading.Lock()
    sem = threading.Semaphore(max_inflight)

    def one() -> None:
        t0 = time.perf_counter()
        try:
            n = request_fn()
        except Exception:  # noqa: BLE001 — failure is a data point
            with lock:
                failed[0] += 1
            return
        finally:
            sem.release()
        dt = time.perf_counter() - t0
        ttft = None
        if isinstance(n, tuple):
            n, ttft = n
        with lock:
            lat.append(dt)
            toks[0] += n
            if ttft is not None:
                ttfts.append(ttft)

    t_start = time.perf_counter()
    with ThreadPoolExecutor(max_workers=max_inflight + 4) as pool:
        for at in arrivals:
            delay = t_start + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            if not sem.acquire(blocking=False):
                # the client budget is full: count the shed arrival
                # instead of silently converting open-loop to closed
                shed[0] += 1
                continue
            pool.submit(one)
    wall = time.perf_counter() - t_start
    lat.sort()
    ttfts.sort()

    def pct(vals: List[float], q: float) -> float:
        if not vals:
            return 0.0
        return vals[min(len(vals) - 1, int(q * (len(vals) - 1) + 0.5))]

    out = {"offered": len(arrivals),
           "offered_rps": round(len(arrivals) / duration_s, 2),
           "completed": len(lat), "failed": failed[0], "shed": shed[0],
           "wall_s": round(wall, 3),
           "rps": round(len(lat) / wall, 2),
           "tok_s": round(toks[0] / wall, 1),
           "tokens": toks[0],
           "p50_ms": round(pct(lat, 0.50) * 1e3, 1),
           "p99_ms": round(pct(lat, 0.99) * 1e3, 1)}
    if ttfts:
        out["ttft_p50_ms"] = round(pct(ttfts, 0.50) * 1e3, 1)
        out["ttft_p99_ms"] = round(pct(ttfts, 0.99) * 1e3, 1)
    return out
