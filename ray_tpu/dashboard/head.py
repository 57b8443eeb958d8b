"""DashboardActor: aiohttp REST endpoints over the cluster's state.

Reference analogs: ``dashboard/head.py`` (aiohttp app + module routes),
``dashboard/state_aggregator.py`` + ``python/ray/util/state/api.py`` (the
State API), ``dashboard/modules/metrics`` (Prometheus). Routes:

  GET /api/version              build/version info
  GET /api/nodes                node table
  GET /api/actors               actor table
  GET /api/placement_groups     placement groups
  GET /api/tasks                recent task events
  GET /api/objects              object directory
  GET /api/errors               failure plane (categorized FailureEvents)
  GET /api/memory               memory plane (store usage + owner ledgers)
  GET /api/logs                 worker log rings (?node=&worker=&limit=)
  GET /api/jobs                 submitted jobs
  GET /api/serve/applications   serve app states
  GET /api/sched                placement decisions + cross-node balance
  GET /api/engine               engine flight-recorder snapshots
  GET /api/rlhf                 RLHF pipeline flight-recorder snapshots
  GET /api/cluster_resources    total/available
  GET /metrics                  Prometheus text page
  GET /-/healthz                liveness
"""

from __future__ import annotations

import asyncio
import json
from typing import Any, Dict, Optional

import ray_tpu


@ray_tpu.remote
class DashboardActor:
    def __init__(self):
        self._runner = None
        self._port: Optional[int] = None

    async def start(self, host: str, port: int) -> int:
        from aiohttp import web

        if self._port is not None:
            return self._port  # idempotent: already serving
        app = web.Application()
        app.router.add_get("/", self._index)
        app.router.add_get("/-/healthz", self._healthz)
        app.router.add_get("/api/version", self._version)
        app.router.add_get("/api/nodes", self._gcs_list("list_nodes"))
        app.router.add_get("/api/actors", self._gcs_list("list_actors"))
        app.router.add_get("/api/placement_groups",
                           self._gcs_list("list_placement_groups"))
        app.router.add_get("/api/tasks", self._gcs_list(
            "list_tasks", {"profile": "exclude"}))
        app.router.add_get("/api/objects", self._gcs_list("list_objects"))
        # the failure plane: categorized FailureEvents (death-cause
        # taxonomy, core/failure.py) straight off the GCS store
        app.router.add_get("/api/errors",
                           self._gcs_list("list_failure_events"))
        app.router.add_get("/api/memory", self._memory)
        app.router.add_get("/api/logs", self._logs)
        app.router.add_get("/api/cluster_resources", self._cluster_resources)
        app.router.add_get("/api/jobs", self._jobs)
        app.router.add_get("/api/serve/applications", self._serve_apps)
        app.router.add_get("/api/serve", self._serve_detail)
        # the placement-receipt plane: decision records + the cross-node
        # balance snapshot (GCS placement_events store / sched_balance)
        app.router.add_get("/api/sched", self._sched)
        # the engine plane: flight-recorder snapshots (@engine/ KV —
        # tick phases, request lifecycles, SLO/goodput rollups)
        app.router.add_get("/api/engine", self._engine)
        # the RLHF plane: pipeline flight-recorder snapshots (@rlhf/ KV —
        # per-role bubble attribution, staleness, transfer receipts)
        app.router.add_get("/api/rlhf", self._rlhf)
        # the train plane: StepDriver flight-recorder snapshots (@train/
        # KV — launch phase attribution, launch-gap/data-starvation
        # accounting, the MFU-gap waterfall)
        app.router.add_get("/api/train", self._train)
        app.router.add_get("/api/stacks", self._stacks)
        app.router.add_get("/metrics", self._metrics)
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, host, port)
        await site.start()
        self._port = site._server.sockets[0].getsockname()[1]
        return self._port

    async def stop(self) -> None:
        if self._runner is not None:
            await self._runner.cleanup()
            self._runner = None

    # -- handlers -------------------------------------------------------------
    async def _index(self, request):
        """The browser UI (reference: ``dashboard/client/`` React SPA —
        here a single static page over the same REST surface)."""
        from aiohttp import web

        from ray_tpu.dashboard.ui import INDEX_HTML

        return web.Response(text=INDEX_HTML, content_type="text/html")

    async def _healthz(self, request):
        from aiohttp import web

        return web.Response(text="ok")

    async def _version(self, request):
        from aiohttp import web

        import ray_tpu as rt

        return web.json_response({"version": getattr(rt, "__version__", "dev"),
                                  "framework": "ray_tpu"})

    def _backend(self):
        return ray_tpu.global_worker()._require_backend()

    def _gcs_list(self, method: str, extra: Optional[Dict] = None):
        async def handler(request):
            from aiohttp import web

            loop = asyncio.get_running_loop()
            payload = {"limit": int(request.query.get("limit", 1000)),
                       **(extra or {})}
            rows = await loop.run_in_executor(
                None, lambda: self._backend().io.run(
                    self._backend()._gcs.call(method, payload)))
            return web.json_response(rows, dumps=_dumps)

        return handler

    async def _cluster_resources(self, request):
        from aiohttp import web

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(
            None, lambda: self._backend().io.run(
                self._backend()._gcs.call("cluster_resources", {})))
        return web.json_response(out, dumps=_dumps)

    async def _jobs(self, request):
        from aiohttp import web

        from ray_tpu.job import list_jobs

        loop = asyncio.get_running_loop()
        jobs = await loop.run_in_executor(None, list_jobs)
        return web.json_response(jobs, dumps=_dumps)

    async def _serve_apps(self, request):
        """Serve application states (reference: dashboard serve module)."""
        from aiohttp import web

        def fetch():
            from ray_tpu import serve

            try:
                return serve.status()
            except RuntimeError:  # serve not running
                return {}

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _serve_detail(self, request):
        """The Serve tab's payload: applications with per-deployment
        windowed stats (ongoing / queue depth / p50 / p99 / QPS) plus the
        autoscaler decision-log tail (serve/controller.py)."""
        from aiohttp import web

        def fetch():
            from ray_tpu import serve

            try:
                return serve.detailed_status()
            except RuntimeError:  # serve not running
                return {"applications": {}, "decisions": []}

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _sched(self, request):
        """The Scheduling tab's payload: the placement decision feed (kind,
        chosen node, reason, candidate feature vectors) joined with the
        cross-node balance snapshot (per-node queued+running load + the
        imbalance CoV behind rt_sched_node_imbalance)."""
        from aiohttp import web

        limit = int(request.query.get("limit", 200))
        kind = request.query.get("kind")

        def fetch():
            backend = self._backend()

            async def run():
                payload: Dict[str, Any] = {"limit": limit}
                if kind:
                    payload["kind"] = kind
                decisions, balance = await asyncio.gather(
                    backend._gcs.call("list_placement_events", payload),
                    backend._gcs.call("sched_balance", {}))
                return {"decisions": decisions, "balance": balance}

            return backend.io.run(run())

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _engine(self, request):
        """The Engine tab's payload: every live ContinuousEngine's
        flight-recorder snapshot (util/engine_recorder.py drain pushes
        them to the ``@engine/`` KV) — summary SLO/goodput rollup plus
        the tick-phase and request-lifecycle record tails."""
        from aiohttp import web

        def fetch():
            backend = self._backend()

            async def run():
                keys = (await backend._gcs.call(
                    "kv_keys", {"prefix": "@engine/"})).get("keys") or []
                replies = await asyncio.gather(
                    *(backend._gcs.call("kv_get", {"key": k})
                      for k in sorted(keys)[:50]))
                engines = []
                for reply in replies:
                    raw = reply.get("value")
                    if not raw:
                        continue
                    try:
                        engines.append(json.loads(raw))
                    except ValueError:
                        continue
                return {"engines": engines}

            return backend.io.run(run())

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _rlhf(self, request):
        """The RLHF tab's payload: every live RLHF pipeline's
        flight-recorder snapshot (util/pipeline_recorder.py drain pushes
        them to the ``@rlhf/`` KV) — bubble fraction, per-role idle
        attribution, staleness profile, and the last transfer receipt."""
        from aiohttp import web

        def fetch():
            backend = self._backend()

            async def run():
                keys = (await backend._gcs.call(
                    "kv_keys", {"prefix": "@rlhf/"})).get("keys") or []
                replies = await asyncio.gather(
                    *(backend._gcs.call("kv_get", {"key": k})
                      for k in sorted(keys)[:50]))
                pipelines = []
                for reply in replies:
                    raw = reply.get("value")
                    if not raw:
                        continue
                    try:
                        pipelines.append(json.loads(raw))
                    except ValueError:
                        continue
                return {"pipelines": pipelines}

            return backend.io.run(run())

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _train(self, request):
        """The Train tab's payload: every StepDriver's flight-recorder
        snapshot (util/train_recorder.py drain pushes them to the
        ``@train/`` KV) — per-launch phase walls, launch-gap accounting
        and the MFU-gap waterfall. Snapshots survive the driver, so a
        finished run stays inspectable here until the cluster dies."""
        from aiohttp import web

        def fetch():
            backend = self._backend()

            async def run():
                keys = (await backend._gcs.call(
                    "kv_keys", {"prefix": "@train/"})).get("keys") or []
                replies = await asyncio.gather(
                    *(backend._gcs.call("kv_get", {"key": k})
                      for k in sorted(keys)[:50]))
                drivers = []
                for reply in replies:
                    raw = reply.get("value")
                    if not raw:
                        continue
                    try:
                        drivers.append(json.loads(raw))
                    except ValueError:
                        continue
                return {"drivers": drivers}

            return backend.io.run(run())

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _stacks(self, request):
        """Cluster-wide live Python stacks (py-spy-equivalent, reference:
        ``dashboard/modules/reporter/profile_manager.py``): every node's
        raylet asks its workers to snapshot ``sys._current_frames()``.
        ``?node_id=`` limits to one node."""
        from aiohttp import web

        want = request.query.get("node_id")
        timeout = float(request.query.get("timeout", 3.0))

        def fetch():
            backend = self._backend()

            async def one(n):
                try:
                    client = await backend._pool.get(n["address"])
                    return await asyncio.wait_for(
                        client.call("dump_stacks", {"timeout": timeout}),
                        timeout=timeout + 2.0)
                except Exception as e:  # noqa: BLE001 — partial is fine
                    return {"node_id": n["node_id"],
                            "unreachable": f"{type(e).__name__}: {e}"}

            async def run():
                nodes = await backend._gcs.call("list_nodes", {})
                targets = [n for n in nodes
                           if (not want or n["node_id"] == want)
                           and n.get("alive", True)]
                # all nodes concurrently: worst case is ONE timeout, not
                # num_nodes stacked timeouts
                return list(await asyncio.gather(*(one(n)
                                                   for n in targets)))

            return backend.io.run(run())

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _memory(self, request):
        """The Memory tab's payload: per-node store reports joined with
        the ownership ledgers + recent OOM post-mortems (util/memory.py)."""
        from aiohttp import web

        from ray_tpu.util.memory import memory_snapshot, oom_reports

        limit = int(request.query.get("limit", 200))

        def fetch():
            snap = memory_snapshot(limit=limit)
            try:
                snap["oom_kills"] = oom_reports()
            except Exception:  # noqa: BLE001 — partial payload is fine
                snap["oom_kills"] = []
            return snap

        loop = asyncio.get_running_loop()
        out = await loop.run_in_executor(None, fetch)
        return web.json_response(out, dumps=_dumps)

    async def _logs(self, request):
        """Worker log viewer: drains every raylet's bounded log ring
        (reference: the dashboard log endpoints over log_monitor state).
        ``?node=<id prefix>`` limits to one node, ``?worker=<id prefix>``
        to one worker, ``?limit=`` caps returned lines."""
        from aiohttp import web

        want_node = request.query.get("node")
        want_worker = request.query.get("worker")
        limit = int(request.query.get("limit", 500))

        def fetch():
            backend = self._backend()

            async def one(n):
                try:
                    client = await backend._pool.get(n["address"])
                    reply = await asyncio.wait_for(
                        client.call("poll_logs",
                                    {"after": 0, "timeout": 0.05}), 5.0)
                    return [{"node_id": n["node_id"], **e}
                            for e in reply.get("entries", ())]
                except Exception:  # noqa: BLE001 — partial view is fine
                    return []

            async def run():
                nodes = await backend._gcs.call("list_nodes", {})
                targets = [
                    n for n in nodes if n.get("alive", True)
                    and (not want_node
                         or n["node_id"].startswith(want_node))]
                chunks = await asyncio.gather(*(one(n) for n in targets))
                return [e for ch in chunks for e in ch]

            return backend.io.run(run())

        loop = asyncio.get_running_loop()
        entries = await loop.run_in_executor(None, fetch)
        if want_worker:
            entries = [e for e in entries
                       if str(e.get("worker_id", "")).startswith(
                           want_worker)]
        entries.sort(key=lambda e: (e.get("node_id", ""),
                                    e.get("seq", 0)))
        return web.json_response(entries[-limit:], dumps=_dumps)

    async def _metrics(self, request):
        """User metrics (pushed registries) + system series synthesized
        from cluster state at scrape time (reference: the metric_defs.cc
        built-ins exported by the per-node agent — here the dashboard IS
        the exporter, so the state API is the source of truth)."""
        from aiohttp import web

        from ray_tpu.util.metrics import metrics_text

        def fetch():
            text = metrics_text()
            try:
                text += system_metrics_text(self._backend())
            except Exception:  # noqa: BLE001 — user page still served
                pass
            return text

        loop = asyncio.get_running_loop()
        text = await loop.run_in_executor(None, fetch)
        return web.Response(text=text, content_type="text/plain")


def _dumps(obj: Any) -> str:
    return json.dumps(obj, default=str)


# System series synthesized per scrape; also the panel inventory for the
# generated Grafana dashboard (dashboard/grafana.py)
SYSTEM_METRICS = {
    "rt_nodes": ("gauge", "Cluster nodes by liveness"),
    "rt_actors": ("gauge", "Actors by state"),
    "rt_tasks": ("gauge", "Task events by state"),
    "rt_placement_groups": ("gauge", "Placement groups by state"),
    "rt_resource_total": ("gauge", "Cluster resource capacity"),
    "rt_resource_available": ("gauge", "Cluster resource availability"),
    "rt_objects_in_store": ("gauge", "Objects tracked in the directory"),
}


def system_metrics_text(backend) -> str:
    """Prometheus text for the framework's own state (nodes/actors/tasks/
    PGs/resources/objects), computed from the GCS at scrape time."""
    from collections import Counter as _Counter

    import asyncio as _asyncio

    async def gather():
        gcs = backend._gcs
        # concurrent: scrape latency is the MAX of the six calls, not
        # the sum (Prometheus scrapes every 10s)
        return await _asyncio.gather(
            gcs.call("list_nodes", {}),
            gcs.call("list_actors", {}),
            gcs.call("list_tasks", {"limit": 10_000}),
            gcs.call("list_placement_groups", {}),
            gcs.call("cluster_resources", {}),
            gcs.call("list_objects", {"limit": 100_000}))

    nodes, actors, tasks, pgs, res, objs = backend.io.run(gather())
    lines = []

    def emit(name, label_kv, value):
        labels = ",".join(f'{k}="{v}"' for k, v in label_kv)
        lines.append(f"{name}{{{labels}}} {value}"
                     if labels else f"{name} {value}")

    for name, (kind, desc) in SYSTEM_METRICS.items():
        lines.append(f"# HELP {name} {desc}")
        lines.append(f"# TYPE {name} {kind}")
        if name == "rt_nodes":
            alive = sum(1 for n in nodes if n.get("alive", True))
            emit(name, [("state", "alive")], alive)
            emit(name, [("state", "dead")], len(nodes) - alive)
        elif name == "rt_actors":
            for state, c in sorted(_Counter(
                    a.get("state", "?") for a in actors).items()):
                emit(name, [("state", state)], c)
        elif name == "rt_tasks":
            for state, c in sorted(_Counter(
                    t.get("state", "?") for t in tasks).items()):
                emit(name, [("state", state)], c)
        elif name == "rt_placement_groups":
            for state, c in sorted(_Counter(
                    p.get("state", "?") for p in pgs).items()):
                emit(name, [("state", state)], c)
        elif name == "rt_resource_total":
            for r, v in sorted((res.get("total") or {}).items()):
                emit(name, [("resource", r)], v)
        elif name == "rt_resource_available":
            for r, v in sorted((res.get("available") or {}).items()):
                emit(name, [("resource", r)], v)
        elif name == "rt_objects_in_store":
            emit(name, [], len(objs))
    return "\n".join(lines) + "\n"


_DASHBOARD_NAME = "RT_DASHBOARD"


def start_dashboard(host: str = "127.0.0.1", port: int = 0) -> int:
    """Start (or find) the dashboard actor; returns the HTTP port."""
    try:
        actor = ray_tpu.get_actor(_DASHBOARD_NAME, namespace="_rt_dashboard")
    except ValueError:
        actor = DashboardActor.options(
            name=_DASHBOARD_NAME, namespace="_rt_dashboard",
            lifetime="detached", num_cpus=0, max_concurrency=32).remote()
    return ray_tpu.get(actor.start.remote(host, port))
