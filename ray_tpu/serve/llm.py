"""Continuous-batching LLM serving: the deployment that makes
``models/serving.ContinuousBatcher`` live.

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
"""

from __future__ import annotations

import asyncio
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

__all__ = ["ContinuousLLM", "continuous_llm_app"]


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
