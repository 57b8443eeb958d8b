"""Shared flight-recorder core: the substrate the engine (PR 18), RLHF
(PR 19) and train (PR 20) recorders are built on.

Three hot paths grew the same recorder shape independently — bounded
ring buffers appended under a microsecond lock, a daemon drain thread
shipping derived telemetry off the hot path on seq-watermarks, a compact
KV snapshot pushed every couple of seconds and deleted at close, and
recorder self-timing against a ≤2% overhead bar. This module extracts
that core once so the next plane inherits the discipline instead of
copying it:

  RecorderRegistry  per-module registry of live recorders (bounded at
                    64 — a leaked construct loop must not grow an
                    unbounded dict), backing each module's
                    ``live_recorders()``
  RecorderCore      the drain-side template: ``_ensure_drainer`` /
                    ``_drain_loop`` / ``drain_now`` / ``_drain_gcs`` /
                    ``close``, parameterized by class attrs
                    (``KV_PREFIX`` / ``DRAIN_S`` / ``THREAD_NAME`` /
                    ``REGISTRY``) and subclass hooks (``snapshot`` /
                    ``_drain_metrics`` / ``_build_events``; engine-only
                    ``_drain_spans``)
  cluster_backend   the "initialized runtime or None" probe every
                    drain pass makes
  span              one named extent on two clocks at once: the host's
                    ``perf_counter`` (summed into the caller's dict, what
                    a recorder keeps) and the profiler's (a
                    ``TraceAnnotation``, what a device trace is read
                    against; free while no trace runs)
  pct               nearest-rank percentile over a pre-sorted list

The hot-path discipline (the PR 15 ``@memkv/`` lesson, measured: a
blocking GCS push on the tick path froze admission AND decode, warm p99
181 ms → 2.6 s) stays the subclasses' job: record methods ONLY append
to bounded deques under ``_lock`` and accumulate their own wall into
``_overhead_s``; everything with I/O in it runs on the drain thread.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Tuple


def pct(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(q * (len(sorted_vals) - 1) + 0.5))
    return sorted_vals[idx]


#: What a span is called on the profiler's clock. ``benchmark/lib/trace.py``
#: (``ANNOTATION_PREFIX``) gives each idle gap of the device to the host
#: event under this prefix that overlaps it most, on any thread; that
#: reduction is the benchmark's, so the prefix changes there and here
#: together or not at all.
TRACE_PREFIX = "bench:"


class span:
    """``with span("prefill", phases):`` times the block and adds the
    seconds to ``phases["prefill"]``; for the same extent it holds a
    ``jax.profiler.TraceAnnotation`` named ``bench:prefill``, so a device
    trace taken meanwhile shows what the host was doing in each gap.
    Spans of one thread must follow one another and never nest: the
    reduction takes the largest overlap, so an enclosing span would own
    every gap. ``jax`` is used only where the process has imported it
    already; ``stats`` (small ints) become the event's stats in a trace
    and leave its name clean."""

    __slots__ = ("_name", "_into", "_ann", "_t0")

    def __init__(self, name: str, into: Optional[Dict[str, float]] = None,
                 **stats: int):
        self._name = name
        self._into = into
        jax = sys.modules.get("jax")
        self._ann = (jax.profiler.TraceAnnotation(TRACE_PREFIX + name, **stats)
                     if jax is not None else None)

    def __enter__(self) -> "span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self._into is not None:
            self._into[self._name] = self._into.get(self._name, 0.0) + dt


def cluster_backend() -> Optional[Any]:
    """The initialized cluster runtime's backend, or None — every drain
    pass starts with this probe so a recorder outside a cluster (unit
    tests, bare bench legs) costs nothing and raises nothing."""
    try:
        import ray_tpu

        if not ray_tpu.is_initialized():
            return None
        return ray_tpu.global_worker()._require_backend()
    except Exception:  # noqa: BLE001 — no runtime is a normal state
        return None


class RecorderRegistry:
    """Per-module registry of live recorders.

    Bounded: a pathological construct loop (a test fixture, a retrying
    driver) must not grow an unbounded id->recorder dict, so the oldest
    entry is evicted past ``cap``. Eviction only forgets the handle —
    the evicted recorder keeps recording and draining until closed.
    """

    def __init__(self, cap: int = 64):
        self._cap = int(cap)
        self._lock = threading.Lock()
        self._recorders: "OrderedDict[int, Any]" = \
            OrderedDict()  # rt: guarded-by(_lock)

    def register(self, rec: Any) -> None:
        with self._lock:
            self._recorders[id(rec)] = rec
            while len(self._recorders) > self._cap:
                self._recorders.popitem(last=False)

    def unregister(self, rec: Any) -> None:
        with self._lock:
            self._recorders.pop(id(rec), None)

    def live(self) -> List[Any]:
        """Every recorder registered in this process and not yet
        closed — the local stats paths and tests read through this."""
        with self._lock:
            return list(self._recorders.values())


class RecorderCore:
    """Drain-side template shared by every flight recorder.

    Subclasses set the class attrs, call ``_init_core(name)`` from
    ``__init__`` (after their own fields — it registers the recorder,
    which makes it visible to ``live_recorders()``), and implement:

      snapshot() -> dict                  the KV payload
      _drain_metrics() -> int             observe new records into
                                          ``util.metrics`` series
      _build_events(node, pid)            (events, advance_fn): GCS
                                          task-events for new records
                                          plus the watermark advance to
                                          run only on a successful push
      _drain_spans() -> Optional[int]     request-span join (engine
                                          only); None = no span plane,
                                          key omitted from drain counts
    """

    KV_PREFIX = "@rec/"
    DRAIN_S = 2.0
    THREAD_NAME = "rt-rec"
    REGISTRY: RecorderRegistry = RecorderRegistry()

    name: str

    def _init_core(self, name: str) -> None:
        self._lock = threading.Lock()
        self._overhead_s = 0.0  # rt: guarded-by(_lock)
        self._wall_total_s = 0.0  # rt: guarded-by(_lock)
        self._closed = False  # rt: guarded-by(_lock)
        self._drainer: Optional[threading.Thread] = None  # rt: guarded-by(_lock)
        self._kv_key = f"{self.KV_PREFIX}{os.uname().nodename}:" \
                       f"{os.getpid()}:{name}"
        self.REGISTRY.register(self)

    # -- subclass hooks ----------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:  # pragma: no cover - abstract
        raise NotImplementedError

    def _drain_metrics(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def _build_events(self, node: str, pid: int
                      ) -> Tuple[List[Dict[str, Any]], Callable[[], None]]:
        return [], lambda: None

    def _drain_spans(self) -> Optional[int]:
        return None

    # -- shared helpers ----------------------------------------------------

    def _snapshot_header(self) -> Dict[str, Any]:
        return {"t": time.time(), "name": self.name,
                "node": os.uname().nodename, "pid": os.getpid()}

    def _overhead_fields(self, out: Dict[str, Any]) -> None:
        """Stamp the self-timing triple every summary reports (the
        bench gates hold ``overhead_frac`` ≤ 2%)."""
        with self._lock:
            overhead = self._overhead_s
            wall = self._wall_total_s
        out["overhead_s"] = round(overhead, 6)
        out["recorded_wall_s"] = round(wall, 6)
        out["overhead_frac"] = round(overhead / wall, 6) \
            if wall > 0 else 0.0

    # -- drain side --------------------------------------------------------

    def _ensure_drainer(self) -> None:
        if self._drainer is not None and self._drainer.is_alive():
            return
        with self._lock:
            if self._closed or (self._drainer is not None
                                and self._drainer.is_alive()):
                return
            self._drainer = threading.Thread(
                target=self._drain_loop, daemon=True,
                name=f"{self.THREAD_NAME}:{self.name}")
            self._drainer.start()

    def _drain_loop(self) -> None:
        while True:
            time.sleep(self.DRAIN_S)
            with self._lock:
                if self._closed:
                    return
            try:
                self.drain_now()
            except Exception:  # noqa: BLE001 — observability must never
                pass           # take the instrumented loop down

    def drain_now(self) -> Dict[str, int]:
        """One drain pass (tests call this instead of waiting out the
        interval): metrics observation, span emission where the plane
        has one, the KV snapshot, and record events into the GCS
        task-event store."""
        counts = {"metrics": self._drain_metrics()}
        spans = self._drain_spans()
        if spans is not None:
            counts["spans"] = spans
        counts.update(self._drain_gcs())
        return counts

    def _drain_gcs(self) -> Dict[str, int]:
        """KV snapshot + timeline events; both best-effort, both skipped
        cleanly outside an initialized cluster runtime. Event watermarks
        advance only on a successful push — a flaky GCS re-sends, never
        drops."""
        out = {"kv": 0, "events": 0}
        backend = cluster_backend()
        if backend is None:
            return out
        try:
            if hasattr(backend, "kv_put"):
                backend.kv_put(self._kv_key,
                               json.dumps(self.snapshot()).encode())
                out["kv"] = 1
        except Exception:  # noqa: BLE001
            pass
        if not hasattr(backend, "_gcs"):
            return out
        events, advance = self._build_events(os.uname().nodename,
                                             os.getpid())
        if not events:
            return out
        try:
            backend.io.run(backend._gcs.call("task_events",
                                             {"events": events}))
            advance()
            out["events"] = len(events)
        except Exception:  # noqa: BLE001
            pass
        return out

    def close(self) -> None:
        """Stop the drain thread and drop the KV snapshot (the doctor
        must not grade a dead plane's numbers — same discipline as the
        serve controller's shutdown)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self.REGISTRY.unregister(self)
        try:
            backend = cluster_backend()
            if backend is not None and hasattr(backend, "kv_del"):
                backend.kv_del(self._kv_key)
        except Exception:  # noqa: BLE001
            pass
