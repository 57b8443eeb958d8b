"""Step profiler: per-step device-time breakdown over the JAX hot paths.

VERDICT's standing directive is "attack the MFU gap with a profile, not a
guess" — this is the instrument. The cluster plane already has metrics,
tracing, timeline, and stack capture; what was missing is a STEP-level lens
over the code that actually burns the chips (train steps, decode loops,
serve requests). Podracer (arXiv:2104.06272) shows TPU-side step accounting
— device vs host time, tokens/s, FLOP utilization — is what makes
throughput work tractable.

What one record holds, and how it is measured around ONE dispatched step
(``profiled_call``):

  wall_s      total host wall time for the step
  compile_s   first-call trace+compile time for this step's ``key`` (jit
              compiles synchronously inside the first call, so the first
              dispatch IS the compile; later calls record it as dispatch)
  dispatch_s  host time to enqueue the compiled program (launch overhead —
              the per-step cost ``make_multi_step`` amortizes)
  execute_s   host-sync stall: time blocked in the device fence after
              dispatch returned — the device-execution tail the host had
              to wait for
  launches    device dispatches this record covers (1 for a fused step,
              ``max_new_tokens`` for a streamed decode)
  tokens/flops  analytic accounting from ``util/flops.py`` → tokens_per_s
              and MFU against the platform's peak

The fence is ``jax.block_until_ready``, which waits for the device
(``chip_smoke.py`` times a large matmul against a host read to show it).

Records land in a bounded per-process ring buffer. ``drain()`` pushes them
into the GCS task-event store (the table ``ray_tpu.timeline()`` exports and
the dashboard lists), where each step becomes a span with ``step`` /
``compile`` / ``sync`` Perfetto lanes; a daemon drainer also ships them on
an interval, so serve replicas and remote workers need no explicit call. Every record also observes the
auto-registered ``rt_step_*`` histograms, which ride the existing
Prometheus push (``util/metrics.py``).

Enable with ``enable()`` or ``RT_STEP_PROFILER=1``; when disabled the hot
paths pay one predicate check per step and nothing else.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

_enabled = os.environ.get("RT_STEP_PROFILER", "") not in ("", "0", "false")
_CAP = int(os.environ.get("RT_STEP_PROFILER_CAP", "4096"))

_lock = threading.Lock()
_records: "deque[StepRecord]" = deque(maxlen=_CAP)
_seen_keys: set = set()
_seq = 0
_drained_seq = 0
_epoch = 0
_per_kind_step: Dict[str, int] = {}
# per-kind authoritative launch/step counters (PR 20 reconciliation):
# when a flight recorder owns the instrumentation point it registers a
# source here and summary(kind) reads ITS join, so `rt profile`'s st/ln
# column and `rt train stats` can never drift apart
_launch_sources: Dict[str, Any] = {}  # rt: guarded-by(_lock)


def register_launch_source(kind: str, fn: Any) -> None:
    """Register ``fn() -> Optional[{"launches": int, "steps": int}]`` as
    the authoritative launch/step counter for ``kind``. Idempotent; a
    source returning None (nothing recorded yet) defers back to the
    profiler's own records."""
    with _lock:
        _launch_sources[kind] = fn


def is_enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop buffered records and compile-key memory (tests; fresh runs).
    Bumps the drain epoch so a post-reset run's records get fresh event-
    store ids instead of overwriting the previous run's (seq restarts)."""
    global _seq, _drained_seq, _epoch
    with _lock:
        _records.clear()
        _seen_keys.clear()
        _per_kind_step.clear()
        _seq = 0
        _drained_seq = 0
        _epoch += 1


@dataclasses.dataclass
class StepRecord:
    kind: str            # "train" | "generate" | "speculative" | "decode" |
    #                      "prefill" | "serve" | caller-defined
    name: str            # preset / deployment / caller label
    step: int            # per-(process, kind) sequence number
    seq: int             # process-global sequence (drain watermark)
    t_start: float       # epoch seconds (timeline lane placement)
    wall_s: float
    compile_s: float
    dispatch_s: float
    execute_s: float
    launches: int
    tokens: int
    flops: float
    tokens_per_s: float
    mfu: float
    first_call: bool
    meta: Dict[str, Any]
    hbm_peak_bytes: int = 0  # max per-device peak HBM (0 = no accounting)
    # optimizer/model steps this record covers: a fused-K train launch has
    # launches=1, steps=K — the per-launch vs per-step attribution the
    # launch-amortization summary divides by
    steps: int = 1

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


# ---- measurement ------------------------------------------------------------

def _peak_total() -> Optional[float]:
    """Aggregate peak FLOP/s of this process's local devices; None on the
    CPU, which has no peak on record and so reports no MFU."""
    import jax

    from ray_tpu.util import flops as F

    if jax.default_backend() == "cpu":
        return None
    return F.peak_flops_per_chip() * max(1, jax.local_device_count())


def _hbm_peak_bytes() -> int:
    """Max per-device ``peak_bytes_in_use`` across local devices (the
    step record's peak-HBM column), refreshing the ``rt_hbm_used_bytes``
    live gauges on the way. One implementation — util/memory.py — owns
    device probing and gauge registration; backends without
    ``memory_stats`` (CPU) report 0."""
    try:
        from ray_tpu.util.memory import (
            device_memory_stats,
            publish_hbm_gauges,
        )

        stats = device_memory_stats()
        publish_hbm_gauges(stats)
        return max((d.get("peak_bytes_in_use") or d.get("bytes_in_use")
                    or 0 for d in stats), default=0)
    except Exception:  # noqa: BLE001 — profiling must never fail the step
        return 0


def record(kind: str, *, name: str = "", t_start: Optional[float] = None,
           wall_s: float, compile_s: float = 0.0, dispatch_s: float = 0.0,
           execute_s: float = 0.0, launches: int = 1, tokens: int = 0,
           flops: float = 0.0, first_call: bool = False,
           steps: int = 1,
           meta: Optional[Dict[str, Any]] = None) -> "StepRecord":
    """Append one step record (hot paths that time themselves — the serve
    replica — call this directly; JAX steps go through ``profiled_call``)."""
    global _seq
    tok_s = tokens / wall_s if wall_s > 0 and tokens else 0.0
    # (flops > 0 only comes from a jax step, so jax is there to ask)
    peak = _peak_total() if flops > 0 and wall_s > 0 else None
    mfu = flops / (wall_s * peak) if peak else 0.0
    hbm_peak = _hbm_peak_bytes()
    with _lock:
        _seq += 1
        step = _per_kind_step.get(kind, 0)
        _per_kind_step[kind] = step + 1
        rec = StepRecord(
            kind=kind, name=name, step=step, seq=_seq,
            t_start=time.time() - wall_s if t_start is None else t_start,
            wall_s=wall_s, compile_s=compile_s, dispatch_s=dispatch_s,
            execute_s=execute_s, launches=launches, tokens=tokens,
            flops=flops, tokens_per_s=tok_s, mfu=mfu,
            first_call=first_call, meta=dict(meta or {}),
            hbm_peak_bytes=hbm_peak, steps=max(1, steps))
        _records.append(rec)
    _observe_metrics(rec)
    _ensure_drainer()
    return rec


def profiled_call(kind: str, fn, args: Tuple = (), kwargs=None, *,
                  key: Any = None, name: str = "", tokens: int = 0,
                  flops: float = 0.0, launches: int = 1, steps: int = 1,
                  meta: Optional[Dict[str, Any]] = None):
    """Run ``fn(*args, **kwargs)`` as one profiled step.

    ``key`` identifies the compiled program: its first call through here
    books the host-side call time as ``compile_s`` (jit compiles
    synchronously inside that call), later calls book it as ``dispatch_s``.
    Keys must be STABLE program identities (config/shape tuples, or a
    counter minted when the program is built) — never ``id()`` of a
    collectable object, which CPython reuses. Caveat: a program evicted
    from an lru cache and recompiled under the same key books its
    recompile as dispatch; the outlier is visible in the records.
    Disabled ⇒ straight call, no fence, no record.
    """
    kwargs = kwargs or {}
    if not _enabled:
        return fn(*args, **kwargs)
    first = False
    if key is not None:
        with _lock:
            first = key not in _seen_keys
    t_epoch = time.time()
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    t1 = time.perf_counter()
    if first:
        # book the key only on success: a failed first call (OOM, shape
        # error) must not make the retry's real compile look like dispatch
        with _lock:
            _seen_keys.add(key)
    import jax

    jax.block_until_ready(out)  # non-array leaves pass through
    t2 = time.perf_counter()
    record(kind, name=name, t_start=t_epoch, wall_s=t2 - t0,
           compile_s=(t1 - t0) if first else 0.0,
           dispatch_s=0.0 if first else (t1 - t0),
           execute_s=t2 - t1, launches=launches, tokens=tokens,
           flops=flops, first_call=first, steps=steps, meta=meta)
    return out


# ---- access -----------------------------------------------------------------

def records(kind: Optional[str] = None) -> List[StepRecord]:
    with _lock:
        out = list(_records)
    return [r for r in out if kind is None or r.kind == kind]


def summary(kind: Optional[str] = None) -> Dict[str, Any]:
    """Aggregates for the ``rt profile`` table: steady-state means exclude
    first-call (compile) steps so one compile doesn't drown N executes."""
    rs = records(kind)
    if not rs:
        return {}
    steady = [r for r in rs if not r.first_call] or rs
    n = len(steady)
    wall = sum(r.wall_s for r in steady)
    launches = sum(r.launches for r in rs)
    steps = sum(getattr(r, "steps", 1) for r in rs)
    launch_source = None
    if kind is not None:
        with _lock:
            src = _launch_sources.get(kind)
        if src is not None:
            try:
                joined = src()
            except Exception:  # noqa: BLE001 — a broken source must not
                joined = None  # take the profile table down
            if joined and joined.get("launches"):
                launches = int(joined["launches"])
                steps = int(joined.get("steps", steps))
                launch_source = "recorder"
    return {
        **({"launch_source": launch_source} if launch_source else {}),
        "records": len(rs),
        "compile_s": sum(r.compile_s for r in rs),
        "mean_wall_s": wall / n,
        "mean_dispatch_s": sum(r.dispatch_s for r in steady) / n,
        "mean_execute_s": sum(r.execute_s for r in steady) / n,
        "launches": launches,
        "steps": steps,
        # fused-K attribution: how many optimizer steps each device launch
        # amortizes, and the true per-STEP wall once fused (mean_wall_s is
        # per RECORD — one launch — so divide by the fusion factor)
        "mean_steps_per_launch": steps / max(1, launches),
        "per_step_wall_s": (wall / sum(getattr(r, "steps", 1)
                                       for r in steady)) if n else 0.0,
        "tokens": sum(r.tokens for r in rs),
        "tokens_per_s": (sum(r.tokens for r in steady) / wall
                         if wall > 0 else 0.0),
        "mean_mfu": sum(r.mfu for r in steady) / n,
        "peak_hbm_bytes": max((r.hbm_peak_bytes for r in rs), default=0),
    }


# ---- metrics ----------------------------------------------------------------

_MFU_BUCKETS = (0.01, 0.02, 0.05, 0.1, 0.15, 0.2, 0.3, 0.4, 0.6, 0.8, 1.0)
_TOKS_BUCKETS = (10.0, 100.0, 1e3, 1e4, 1e5, 1e6, 1e7)
_hists: Optional[Dict[str, Any]] = None


def _observe_metrics(rec: StepRecord) -> None:
    global _hists
    try:
        from ray_tpu.util import metrics as M

        if _hists is None:
            _hists = {
                "wall": M.get_or_create(
                    M.Histogram, "rt_step_time_seconds",
                    "Step wall time", tag_keys=("kind",)),
                "device": M.get_or_create(
                    M.Histogram, "rt_step_device_time_seconds",
                    "Step device-execution stall (post-dispatch fence)",
                    tag_keys=("kind",)),
                "mfu": M.get_or_create(
                    M.Histogram, "rt_step_mfu",
                    "Analytic model-FLOPs utilization per step",
                    boundaries=_MFU_BUCKETS, tag_keys=("kind",)),
                "toks": M.get_or_create(
                    M.Histogram, "rt_step_tokens_per_s",
                    "Tokens per second per step",
                    boundaries=_TOKS_BUCKETS, tag_keys=("kind",)),
                "launches": M.get_or_create(
                    M.Counter, "rt_step_launches_total",
                    "Device dispatches recorded by the step profiler",
                    tag_keys=("kind",)),
            }
        tags = {"kind": rec.kind}
        _hists["wall"].observe(rec.wall_s, tags)
        _hists["device"].observe(rec.execute_s, tags)
        if rec.flops > 0:
            _hists["mfu"].observe(rec.mfu, tags)
        if rec.tokens > 0:
            _hists["toks"].observe(rec.tokens_per_s, tags)
        _hists["launches"].inc(float(rec.launches), tags)
    except Exception:  # noqa: BLE001 — metrics must never break the step
        pass


# ---- structured event log drain ---------------------------------------------

_DRAIN_INTERVAL_S = 5.0
_drainer: Optional[threading.Thread] = None


def _ensure_drainer() -> None:
    """A daemon thread that drains the ring buffer on an interval — the
    path that gets SERVE/worker-process records into the event store
    (nothing in a replica ever calls drain() explicitly; same pattern as
    the metrics pusher)."""
    global _drainer
    if _drainer is not None and _drainer.is_alive():
        return
    _drainer = threading.Thread(target=_drain_loop, daemon=True,
                                name="rt-step-drain")
    _drainer.start()


def _drain_loop() -> None:
    while True:
        time.sleep(_DRAIN_INTERVAL_S)
        if not _enabled:
            continue
        try:
            drain()
        except Exception:  # noqa: BLE001 — observability must never
            pass  # take the workload down


def drain() -> int:
    """Push not-yet-drained records into the GCS task-event store (the
    table ``ray_tpu.timeline()`` exports). Best-effort and idempotent per
    record: each carries a process-global ``seq`` watermark. Returns the
    number of records shipped."""
    global _drained_seq
    try:
        import ray_tpu

        if not ray_tpu.is_initialized():
            return 0
        backend = ray_tpu.global_worker()._require_backend()
        if not hasattr(backend, "_gcs"):
            return 0  # local_mode: no event store
    except Exception:  # noqa: BLE001
        return 0
    with _lock:
        pending = [r for r in _records if r.seq > _drained_seq]
        epoch = _epoch
    if not pending:
        return 0
    node = os.uname().nodename
    pid = os.getpid()
    events = [{
        "task_id": f"step:{node}:{pid}:{epoch}:{r.seq}",
        "name": f"{r.kind}:{r.name}" if r.name else r.kind,
        "state": "FINISHED", "node_id": node,
        "times": {"RUNNING": r.t_start,
                  "FINISHED": r.t_start + r.wall_s},
        "profile": r.to_dict()} for r in pending]

    try:
        # one batched RPC for the whole ring — a streamed decode can have
        # thousands of pending records, and a round-trip each would pin
        # the drainer (and the GCS) for seconds
        backend.io.run(backend._gcs.call("task_events", {"events": events}))
    except Exception:  # noqa: BLE001 — observability must not take
        return 0  # the workload down
    with _lock:
        if _epoch == epoch:  # a reset() mid-push restarted the seq space;
            # advancing the watermark then would orphan the new records
            _drained_seq = max(_drained_seq, pending[-1].seq)
    return len(pending)
