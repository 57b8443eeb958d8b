"""Engine flight recorder: per-tick phase attribution, request lifecycle
records, and SLO/goodput accounting for ``ContinuousEngine``.

Every observability layer so far (step profiler, task phase tracing,
memory/failure planes, serve request spans, placement receipts) stops at
the engine boundary — the continuous-batching tick loop is a black-box
background thread. This module is the missing lens: the engine thread
stamps bounded, lock-light records on every tick and every request, and a
separate drain thread ships the derived telemetry everywhere the other
planes already live.

What one TICK record holds — a partition of the engine thread's wall,
from the end of one tick's token delivery to the end of the next, into the
phases the loop actually runs. ``models/serving.py`` stamps each with
``recorder_core.span``, so the same extents are ``bench:<phase>`` events on
the profiler's clock while a device trace runs:

  admission       reap of cancellations, swap check, queue pop, slot
                  bookkeeping, emit of the first token
  kv_restore      prefix-cache lookup + retained-page upload for warm
                  admissions (the TTFT-collapse path)
  prefill         staging the prompt, the compiled prefill call AND the
                  host read of its first token (the fence: the device is
                  busy under this phase, not under ``admission``)
  decode_step     the fused ``step_many(k)`` launch across active slots;
                  ``decode_parts`` beside it splits the same wall into
                  decode_stage (index build + host->device uploads),
                  decode_launch (compiled call through the token read:
                  the device is busy) and decode_book (per-slot
                  bookkeeping after the read, KV capture included)
  token_delivery  handing each tick's token bursts to their consumers
  swap_barrier    applying a drain-barrier weight swap, when one landed
  record          the recorder's own calls and the ``on_tick`` hook
  idle_wait       parked on the condition variable: no live request (a
                  parked stretch is a tick of its own, closed on waking)

plus active-slot count, the bucket the decode launch compiled for
(lone-row vs full-engine), the k-step fusion stride, and the decode
TICK-GAP: the wall between consecutive decode launches while slots were
active — the single number that spikes when a long-prompt prefill (or
anything else) starves decode, and the diagnostic baseline the
prefill/decode disaggregation arc is judged against.

What one REQUEST record holds: ``queue_s`` (submit -> popped for
admission: waited for a slot), ``queue_wait_s`` (submit -> first token; the
name is older than the split and is TTFT), cached-vs-computed prefill
tokens (from the batcher's ``last_admission``), decode ticks, TTFT, TPOT,
the terminal state (done / cancelled) and, for a request that came through
the HTTP proxy, ``front_in_s`` (proxy receipt -> engine submit) and
``replica_in_s`` (replica entry -> engine submit). The stream pump reports
per burst how long the replica's event loop took to pick it up
(``pump_lag``). Requests submitted under an
ambient serve request context JOIN the request span tree: the drain emits
an ``engine:<name>`` span parented on the serve span, so ``rt trace
<request-id>`` descends from proxy→replica into engine phases.

Derived SLO/goodput accounting (``summary()``): rolling TTFT/TPOT
SLO-attainment ratios against configurable targets
(``RT_ENGINE_TTFT_SLO_MS`` / ``RT_ENGINE_TPOT_SLO_MS``), goodput tok/s
(tokens of SLO-attaining requests) vs the raw-capacity estimate
(``bucket × k`` tokens per decode launch), and occupancy-weighted decode
efficiency (tokens actually emitted / slot-tokens the launches paid for).

Discipline (the PR 15 ``@memkv/`` lesson, measured: a blocking GCS push
on the tick path froze admission AND decode, warm p99 181 ms → 2.6 s):
the tick path ONLY appends to bounded in-process deques under a
microsecond lock — metrics observation, span emission, the ``@engine/``
KV snapshot and the timeline event push all happen on the drain thread.
The ring-buffer + watermark-drain + self-timing substrate lives in
``util/recorder_core.py`` (shared with the RLHF and train recorders);
this module owns only the engine-specific vocabulary and accounting.
The recorder times itself: ``overhead_s`` accumulates the wall spent
inside recorder calls on the engine thread, and ``summary()`` reports it
as a fraction of recorded tick wall (the bench gate holds it ≤ 2%).

Disable with ``RT_ENGINE_RECORDER=0`` — every hook then costs one
predicate check per tick.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Tuple

from ray_tpu.util.recorder_core import (RecorderCore, RecorderRegistry,
                                        pct as _pct)

_ENABLED_DEFAULT = os.environ.get("RT_ENGINE_RECORDER", "1") \
    not in ("", "0", "false")
_CAP = int(os.environ.get("RT_ENGINE_RECORDER_CAP", "2048"))
_SLO_WINDOW = int(os.environ.get("RT_ENGINE_SLO_WINDOW", "256"))
_DRAIN_S = float(os.environ.get("RT_ENGINE_DRAIN_S", "2.0"))
_KV_PREFIX = "@engine/"

#: canonical tick-phase vocabulary, in tick-loop order (the timeline
#: tick lane and ``rt engine ticks`` render phases in this order)
TICK_PHASES = ("record", "admission", "kv_restore", "prefill", "decode_step",
               "token_delivery", "swap_barrier", "idle_wait")
#: the three spans ``step_many`` splits ``decode_step``'s wall into
DECODE_PARTS = ("decode_stage", "decode_launch", "decode_book")
# a sparse model's routing counters, in the order the programs return them
# (``models/moe.py:SERVED_STATS``): over launches the first four add up and
# the last is a maximum
MOE_COUNTERS = ("moe_assignments", "moe_rows_computed", "moe_experts_touched",
                "moe_expert_slots", "moe_max_expert_rows")

_REGISTRY = RecorderRegistry()


def live_recorders() -> List["EngineRecorder"]:
    """Every recorder constructed in this process and not yet closed —
    the local engine_stats path and tests read through this."""
    return _REGISTRY.live()


class EngineRecorder(RecorderCore):
    """Bounded flight recorder for one ``ContinuousEngine``.

    The ENGINE THREAD is the only writer of tick records and the only
    caller of ``request_admitted`` / ``request_tokens``; ``request_done``
    may additionally fire from client threads (cancel). All shared state
    lives behind one lock held for O(1) appends — never across a device
    call, an RPC, or a metrics observation.
    """

    KV_PREFIX = _KV_PREFIX
    DRAIN_S = _DRAIN_S
    THREAD_NAME = "rt-engine-rec"
    REGISTRY = _REGISTRY

    def __init__(self, name: str = "engine", *, max_slots: int = 8,
                 ttft_slo_s: Optional[float] = None,
                 tpot_slo_s: Optional[float] = None,
                 cap: int = _CAP, enabled: Optional[bool] = None):
        self.name = name or "engine"
        self.max_slots = max(1, int(max_slots))
        self.enabled = _ENABLED_DEFAULT if enabled is None else bool(enabled)
        self.ttft_slo_s = float(
            os.environ.get("RT_ENGINE_TTFT_SLO_MS", "1500")) / 1e3 \
            if ttft_slo_s is None else float(ttft_slo_s)
        self.tpot_slo_s = float(
            os.environ.get("RT_ENGINE_TPOT_SLO_MS", "150")) / 1e3 \
            if tpot_slo_s is None else float(tpot_slo_s)
        cap = max(64, int(cap))
        self._init_core(self.name)
        self._ticks: "deque[Dict[str, Any]]" = deque(maxlen=cap)  # rt: guarded-by(_lock)
        self._active: "OrderedDict[int, Dict[str, Any]]" = \
            OrderedDict()  # rt: guarded-by(_lock)
        self._done: "deque[Dict[str, Any]]" = deque(maxlen=cap)  # rt: guarded-by(_lock)
        self._window: "deque[Dict[str, Any]]" = \
            deque(maxlen=_SLO_WINDOW)  # rt: guarded-by(_lock)
        # (wall time, lag) per delivered burst: as many as the tick ring's
        # launches can hold at full occupancy
        self._pump: "deque[Tuple[float, float]]" = \
            deque(maxlen=cap * self.max_slots)  # rt: guarded-by(_lock)
        #: what each compiled decode program does to the slot cache, as the
        #: engine's batcher found it in the compiled program (``bucket``,
        #: ``k``, ``cache_donated``, ``cache_copy_bytes_per_step``,
        #: ``cache_bytes``, and with recurrent layers ``state_donated``,
        #: ``state_copy_bytes_per_step``, ``state_bytes``); the engine
        #: points this at the batcher's list
        self.decode_programs: List[Dict[str, Any]] = []
        #: static, of a model with recurrent layers: its layers by kind and
        #: what a slot holds for them (``layers``, ``state_bytes_per_row``,
        #: ``kv_bytes_per_position``; with window layers also ``kinds``,
        #: ``sliding_window``, ``window_bytes_per_row`` and ``kv_readers``,
        #: the layers that read the shared keys and values); None for a
        #: model without
        self.state_layout: Optional[Dict[str, Any]] = None
        self._overhead_tick_s = 0.0  # rt: guarded-by(_lock)
        self._tick_seq = 0  # rt: guarded-by(_lock)
        self._req_seq = 0  # rt: guarded-by(_lock)
        self._swaps = 0  # rt: guarded-by(_lock)
        self._last_swap: Optional[Dict[str, Any]] = None  # rt: guarded-by(_lock)
        self._requests_total = 0  # rt: guarded-by(_lock)
        self._cancelled_total = 0  # rt: guarded-by(_lock)
        # drain-side watermarks (drain thread only; the lock still guards
        # the snapshot reads that feed them)
        self._metrics_tick_wm = 0
        self._metrics_req_wm = 0
        self._span_req_wm = 0
        self._event_tick_wm = 0
        self._event_req_wm = 0

    # -- tick path (engine thread) ---------------------------------------

    def _charge_locked(self, t0: float) -> None:
        """Book a recorder call's own wall: to the lifetime total and to
        the tick being gathered. Caller holds ``_lock``."""
        dt = time.perf_counter() - t0
        self._overhead_s += dt
        self._overhead_tick_s += dt

    def record_tick(self, *, t_start: float, wall_s: float,
                    phases: Dict[str, float], active: int, pending: int,
                    bucket: int, k: int, tokens: int, admitted: int,
                    gap_s: Optional[float],
                    decode_parts: Optional[Dict[str, float]] = None,
                    moe: Optional[Dict[str, List[int]]] = None,
                    scan_chunks: int = 0,
                    kv_positions: Tuple[int, int] = (0, 0),
                    window_positions: Tuple[int, int] = (0, 0),
                    prefill_layer_tokens: Tuple[int, int] = (0, 0)) -> None:
        """One engine tick: phase partition + the decode tick-gap. The
        ONLY thing this does is append to a bounded deque — no metrics,
        no I/O (drained off-thread). ``decode_parts`` is ``decode_step``'s
        wall again, split three ways; it stays out of ``phases`` so that
        they still sum to the tick. ``moe`` is what a sparse model's
        launches of this tick said of their routing (``MOE_COUNTERS``
        values under "prefill" and "decode"); a dense model's is empty.
        ``scan_chunks``: chunks the recurrent layers' scans ran over in
        this tick's prefills (0 without such layers). ``kv_positions``:
        (positions the tick's decode launch had attention read, positions
        its active rows had live): ``ContinuousBatcher.take_kv_positions``;
        (0, 0) for a tick that launched no decode. ``window_positions``:
        the same of the window layers' rings
        (``take_window_positions``). ``prefill_layer_tokens``: (computed,
        whole) layer-tokens of the tick's prefills
        (``take_prefill_layer_tokens``). Both (0, 0) for a model without
        window layers, or whose prefill runs every layer over every token."""
        if not self.enabled:
            return
        t0 = time.perf_counter()
        rec = {"t": t_start, "wall_s": wall_s,
               "phases": {p: phases.get(p, 0.0) for p in TICK_PHASES
                          if phases.get(p, 0.0) > 0.0},
               "active": active, "pending": pending, "bucket": bucket,
               "k": k, "tokens": tokens, "admitted": admitted}
        if gap_s is not None:
            rec["gap_s"] = gap_s
        if decode_parts:
            rec["decode_parts"] = {p: decode_parts.get(p, 0.0)
                                   for p in DECODE_PARTS}
        if moe:
            rec["moe"] = moe
        if scan_chunks:
            rec["scan_chunks"] = scan_chunks
        if kv_positions[0]:
            rec["kv_positions_read"], rec["kv_positions_live"] = kv_positions
        if window_positions[0]:
            (rec["window_positions_read"],
             rec["window_positions_live"]) = window_positions
        if prefill_layer_tokens[1]:
            (rec["prefill_layer_tokens"],
             rec["prefill_layer_tokens_whole"]) = prefill_layer_tokens
        with self._lock:
            self._tick_seq += 1
            rec["seq"] = self._tick_seq
            # recorder calls since the last tick; this call's own wall
            # is the next tick's first entry
            rec["overhead_s"] = self._overhead_tick_s
            self._overhead_tick_s = 0.0
            self._ticks.append(rec)
            self._wall_total_s += wall_s
            self._charge_locked(t0)
        self._ensure_drainer()

    def request_admitted(self, rid: int, *, t_submit: float, t_admit: float,
                         prompt_tokens: int, cached_tokens: int,
                         prefill_s: float, kv_restore_s: float,
                         slot: int = -1,
                         t_admit_start: Optional[float] = None,
                         obs_ctx: Optional[Dict[str, Any]] = None) -> None:
        """Lifecycle start: admission produced the first token, so
        ``t_admit`` IS the TTFT stamp (queue_wait = admission - submit, a
        name kept for its readers). ``t_admit_start`` is when the engine
        popped the request for admission: ``queue_s`` is the wait for a
        slot alone. A context stamped by the HTTP proxy (``t_ingress``)
        and the replica (``t_replica``), all on one host's ``time.time()``,
        gives the front's share per request."""
        if not self.enabled:
            return
        t0 = time.perf_counter()
        rec = {"rid": rid, "t_submit": t_submit, "t_admit": t_admit,
               "t_first": t_admit, "queue_wait_s": max(0.0,
                                                       t_admit - t_submit),
               "queue_s": max(0.0, (t_admit if t_admit_start is None
                                    else t_admit_start) - t_submit),
               "prompt_tokens": int(prompt_tokens),
               "cached_tokens": int(cached_tokens),
               "computed_tokens": int(prompt_tokens) - int(cached_tokens),
               "prefill_s": prefill_s, "kv_restore_s": kv_restore_s,
               "slot": slot, "tokens": 1, "decode_ticks": 0,
               "state": "active"}
        if obs_ctx:
            rec["request_id"] = obs_ctx.get("request_id")
            rec["parent_span_id"] = obs_ctx.get("span_id")
            for key, stamp in (("front_in_s", "t_ingress"),
                               ("replica_in_s", "t_replica")):
                if stamp in obs_ctx:
                    rec[key] = max(0.0, t_submit - obs_ctx[stamp])
        with self._lock:
            self._requests_total += 1
            self._active[rid] = rec
            while len(self._active) > self._done.maxlen:
                self._active.popitem(last=False)  # runaway-leak backstop
            self._charge_locked(t0)

    def request_tokens(self, rid: int, n: int, t: float,
                       done: bool = False) -> None:
        """A decode tick delivered ``n`` tokens to request ``rid``."""
        if not self.enabled:
            return
        t0 = time.perf_counter()
        with self._lock:
            rec = self._active.get(rid)
            if rec is not None:
                rec["tokens"] += n
                rec["decode_ticks"] += 1
                rec["t_last"] = t
            self._charge_locked(t0)
        if done:
            self.request_done(rid, t=t, state="done")

    def request_done(self, rid: int, *, t: float,
                     state: str = "done") -> None:
        """Finalize a lifecycle record: compute TTFT/TPOT, move it to the
        done ring, and enter it into the rolling SLO window."""
        if not self.enabled:
            return
        t0 = time.perf_counter()
        with self._lock:
            rec = self._active.pop(rid, None)
            if rec is None:
                self._charge_locked(t0)
                return
            rec["state"] = state
            rec["t_done"] = t
            rec["ttft_s"] = max(0.0, rec["t_first"] - rec["t_submit"])
            n = rec["tokens"]
            rec["tpot_s"] = (max(0.0, t - rec["t_first"]) / (n - 1)
                             if n > 1 else 0.0)
            self._req_seq += 1
            rec["seq"] = self._req_seq
            self._done.append(rec)
            if state == "done":
                self._window.append(_window_entry(rec))
            else:
                self._cancelled_total += 1
            self._charge_locked(t0)

    def pump_lag(self, lag_s: float) -> None:
        """One burst crossed from the engine thread to its consumer's
        event loop ``lag_s`` after ``emit_many`` handed it over (called
        on that loop: the first boundary after the engine thread)."""
        if not self.enabled:
            return
        t0 = time.perf_counter()
        with self._lock:
            self._pump.append((time.time(), max(0.0, lag_s)))
            self._charge_locked(t0)

    def record_swap(self, apply_s: float, drained_reqs: int = 0) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._swaps += 1
            # the swap-barrier join: the RLHF transfer receipt reads this
            # back so one record shows ship -> fetch -> barrier -> swap
            self._last_swap = {"t": time.time(),
                               "apply_s": round(apply_s, 6),
                               "drained_reqs": int(drained_reqs)}

    def set_slo(self, *, ttft_slo_s: Optional[float] = None,
                tpot_slo_s: Optional[float] = None) -> None:
        """Retune the SLO targets; attainment is computed against the
        CURRENT targets at summary time, so this applies retroactively
        to the rolling window (bench calibration uses it)."""
        if ttft_slo_s is not None:
            self.ttft_slo_s = float(ttft_slo_s)
        if tpot_slo_s is not None:
            self.tpot_slo_s = float(tpot_slo_s)

    # -- derived accounting ----------------------------------------------

    def ticks(self, limit: int = 0) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._ticks)
        return out[-limit:] if limit else out

    def requests(self, limit: int = 0) -> List[Dict[str, Any]]:
        with self._lock:
            out = list(self._done)
        return out[-limit:] if limit else out

    def summary(self) -> Dict[str, Any]:
        """The rolling SLO/goodput picture: what ``engine_stats()``,
        ``rt engine stats``, the doctor findings and the gauges read."""
        with self._lock:
            ticks = list(self._ticks)
            window = list(self._window)
            pump = list(self._pump)
            active = len(self._active)
            base = {"requests_total": self._requests_total,
                    "cancelled_total": self._cancelled_total,
                    "swaps": self._swaps, "ticks_total": self._tick_seq}
            if self._last_swap is not None:
                base["last_swap"] = dict(self._last_swap)
        out = self._aggregate(ticks, window, [lag for _, lag in pump])
        out.update(base)
        out["name"] = self.name
        out["active"] = active
        out["max_slots"] = self.max_slots
        out["ttft_slo_s"] = self.ttft_slo_s
        out["tpot_slo_s"] = self.tpot_slo_s
        self._overhead_fields(out)
        return out

    def window_summary(self, t0: float, t1: float) -> Dict[str, Any]:
        """Same aggregates restricted to records stamped in [t0, t1) —
        the bench legs carve steady/burst/recovery windows with this."""
        with self._lock:
            ticks = [t for t in self._ticks if t0 <= t["t"] < t1]
            window = [_window_entry(r) for r in self._done
                      if r["state"] == "done" and t0 <= r["t_done"] < t1]
            pump = list(self._pump)  # filtered off the lock: the ring is long
        return self._aggregate(ticks, window,
                               [lag for t, lag in pump if t0 <= t < t1])

    def _aggregate(self, ticks: List[Dict[str, Any]],
                   window: List[Dict[str, Any]],
                   lags: List[float]) -> Dict[str, Any]:
        phase_totals = {p: 0.0 for p in TICK_PHASES}
        part_totals = {p: 0.0 for p in DECODE_PARTS}
        wall = 0.0
        overhead = 0.0
        gaps: List[float] = []
        cap_tokens = 0
        tokens_emitted = 0
        occ_weighted = 0.0
        decode_wall = 0.0
        for t in ticks:
            wall += t["wall_s"]
            overhead += t.get("overhead_s", 0.0)
            for p, v in t["phases"].items():
                phase_totals[p] = phase_totals.get(p, 0.0) + v
            for p, v in t.get("decode_parts", {}).items():
                part_totals[p] += v
            if "gap_s" in t:
                gaps.append(t["gap_s"])
            d = t["phases"].get("decode_step", 0.0)
            if d > 0.0:
                # capacity this launch paid for: bucket rows × k fused
                # steps would emit bucket*k tokens at full occupancy
                cap_tokens += t["bucket"] * t["k"]
                decode_wall += d
                occ_weighted += d * (t["active"] / self.max_slots)
        tokens_emitted = sum(t["tokens"] for t in ticks)
        gaps.sort()
        phase_sum = sum(phase_totals.values())
        out: Dict[str, Any] = {
            "window_ticks": len(ticks),
            "tick_wall_s": round(wall, 6),
            "phase_s": {p: round(v, 6) for p, v in phase_totals.items()
                        if v > 0.0},
            "phase_sum_ratio": round(phase_sum / wall, 4) if wall > 0
            else 0.0,
            "tick_gap_p50_s": round(_pct(gaps, 0.50), 6),
            "tick_gap_p99_s": round(_pct(gaps, 0.99), 6),
            "tick_gap_max_s": round(gaps[-1], 6) if gaps else 0.0,
            # the doctor's "sustained" signal: the last few gaps, newest
            # last (all above the warn threshold = sustained starvation)
            "gap_recent": [round(t["gap_s"], 6) for t in ticks
                           if "gap_s" in t][-8:],
            "tokens": tokens_emitted,
            "decode_wall_s": round(decode_wall, 6),
            "decode_efficiency": round(tokens_emitted / cap_tokens, 4)
            if cap_tokens else 0.0,
            "occupancy": round(occ_weighted / decode_wall, 4)
            if decode_wall > 0 else 0.0,
            "capacity_tok_s": round(cap_tokens / decode_wall, 1)
            if decode_wall > 0 else 0.0,
            "decode_parts_s": {p: round(v, 6)
                               for p, v in part_totals.items() if v > 0.0},
            # what stalls cost, by the engine thread's own clock: a tick
            # (and, inside it, a launch) counts for what it took beyond
            # twice the median of its (bucket, k) peers
            "tick_excess_s": round(_excess(
                ticks, lambda t: t["wall_s"]), 6),
            "launch_excess_s": round(_excess(
                ticks, lambda t: t.get("decode_parts", {}).get(
                    "decode_launch", 0.0)), 6),
            "overhead_frac": round(overhead / wall, 6) if wall > 0 else 0.0,
            "pump_bursts": len(lags),
            "decode_programs": [dict(p) for p in self.decode_programs],
        }
        out.update(_moe_totals(ticks))
        out.update(_kv_read_totals(ticks))
        for key in ("window_positions_read", "window_positions_live",
                    "prefill_layer_tokens", "prefill_layer_tokens_whole"):
            total = sum(t.get(key, 0) for t in ticks)
            if total:  # a model that records none of them shows none
                out[key] = total
        if self.state_layout is not None:
            out["state_layout"] = dict(self.state_layout)
            out["ssm_scan_chunks"] = sum(t.get("scan_chunks", 0)
                                         for t in ticks)
        if lags:
            lags = sorted(lags)
            out["pump_lag_p50_s"] = round(_pct(lags, 0.50), 6)
            out["pump_lag_p99_s"] = round(_pct(lags, 0.99), 6)
            out["pump_lag_max_s"] = round(lags[-1], 6)
        for key, qs in (("queue", (50, 90)), ("front_in", (50, 90)),
                        ("replica_in", (50,))):
            vals = sorted(w[key + "_s"] for w in window if key + "_s" in w)
            for q in qs if vals else ():
                out[f"{key}_p{q}_s"] = round(_pct(vals, q / 100), 6)
        n = len(window)
        out["window_completed"] = n
        if n:
            ttft_ok = sum(1 for w in window
                          if w["ttft_s"] <= self.ttft_slo_s)
            # single-token requests have no inter-token interval; they
            # trivially attain TPOT
            tpot_ok = sum(1 for w in window
                          if w["tpot_s"] <= self.tpot_slo_s)
            out["ttft_attainment"] = round(ttft_ok / n, 4)
            out["tpot_attainment"] = round(tpot_ok / n, 4)
            ttfts = sorted(w["ttft_s"] for w in window)
            tpots = sorted(w["tpot_s"] for w in window)
            out["ttft_p50_s"] = round(_pct(ttfts, 0.50), 6)
            out["ttft_p99_s"] = round(_pct(ttfts, 0.99), 6)
            out["tpot_p50_s"] = round(_pct(tpots, 0.50), 6)
            out["tpot_p99_s"] = round(_pct(tpots, 0.99), 6)
            span = max(w["t"] for w in window) - min(w["t"] for w in window)
            good = sum(w["tokens"] for w in window
                       if w["ttft_s"] <= self.ttft_slo_s
                       and w["tpot_s"] <= self.tpot_slo_s)
            total = sum(w["tokens"] for w in window)
            if span > 0:
                out["goodput_tok_s"] = round(good / span, 1)
                out["window_tok_s"] = round(total / span, 1)
            out["goodput_frac"] = round(good / total, 4) if total else 0.0
        return out

    def snapshot(self, ticks_limit: int = 64,
                 requests_limit: int = 64) -> Dict[str, Any]:
        """The ``@engine/`` KV payload: summary + record tails, compact
        enough to push every couple of seconds."""
        out = self._snapshot_header()
        out["summary"] = self.summary()
        out["ticks"] = [self._compact_tick(t)
                        for t in self.ticks(ticks_limit)]
        out["requests"] = [self._compact_req(r)
                           for r in self.requests(requests_limit)]
        return out

    @staticmethod
    def _compact_tick(t: Dict[str, Any]) -> Dict[str, Any]:
        out = {"seq": t["seq"], "t": round(t["t"], 4),
               "wall_ms": round(t["wall_s"] * 1e3, 3),
               "phases_ms": {p: round(v * 1e3, 3)
                             for p, v in t["phases"].items()},
               "active": t["active"], "pending": t["pending"],
               "bucket": t["bucket"], "k": t["k"], "tokens": t["tokens"],
               "admitted": t["admitted"]}
        if "gap_s" in t:
            out["gap_ms"] = round(t["gap_s"] * 1e3, 3)
        if "decode_parts" in t:
            out["decode_parts_ms"] = {p: round(v * 1e3, 3)
                                      for p, v in t["decode_parts"].items()}
        return out

    @staticmethod
    def _compact_req(r: Dict[str, Any]) -> Dict[str, Any]:
        out = {"rid": r["rid"], "state": r["state"],
               "queue_wait_ms": round(r["queue_wait_s"] * 1e3, 3),
               "queue_ms": round(r.get("queue_s", 0.0) * 1e3, 3),
               "prompt_tokens": r["prompt_tokens"],
               "cached_tokens": r["cached_tokens"],
               "computed_tokens": r["computed_tokens"],
               "tokens": r["tokens"], "decode_ticks": r["decode_ticks"],
               "slot": r["slot"]}
        if "ttft_s" in r:
            out["ttft_ms"] = round(r["ttft_s"] * 1e3, 3)
            out["tpot_ms"] = round(r["tpot_s"] * 1e3, 3)
        if r.get("request_id"):
            out["request_id"] = r["request_id"]
        return out

    # -- off-tick drain (template in recorder_core; hooks below) ----------

    def _pending_since(self, wm_attr: str, ticks: bool) -> List[Dict]:
        with self._lock:
            src = self._ticks if ticks else self._done
            wm = getattr(self, wm_attr)
            return [r for r in src if r.get("seq", 0) > wm]

    def _drain_metrics(self) -> int:
        try:
            from ray_tpu.util import metrics as M
        except Exception:  # noqa: BLE001
            return 0
        h = _metric_handles(M)
        tags = {"engine": self.name}
        new_ticks = self._pending_since("_metrics_tick_wm", ticks=True)
        for t in new_ticks:
            for p, v in t["phases"].items():
                h["phase"].observe(v, tags={"engine": self.name,
                                            "phase": p})
            if "gap_s" in t:
                h["gap"].observe(t["gap_s"], tags=tags)
            h["ticks"].inc(1.0, tags=tags)
        new_reqs = self._pending_since("_metrics_req_wm", ticks=False)
        for r in new_reqs:
            h["requests"].inc(1.0, tags={"engine": self.name,
                                         "state": r["state"]})
            if "ttft_s" in r and r["state"] == "done":
                h["ttft"].observe(r["ttft_s"], tags=tags)
                if r["tokens"] > 1:
                    h["tpot"].observe(r["tpot_s"], tags=tags)
        if new_ticks:
            self._metrics_tick_wm = new_ticks[-1]["seq"]
        if new_reqs:
            self._metrics_req_wm = new_reqs[-1]["seq"]
        summ = self.summary()
        if summ.get("window_completed"):
            h["slo"].set(summ["ttft_attainment"],
                         tags={"engine": self.name, "slo": "ttft"})
            h["slo"].set(summ["tpot_attainment"],
                         tags={"engine": self.name, "slo": "tpot"})
            h["goodput"].set(summ.get("goodput_tok_s", 0.0), tags=tags)
        if summ.get("window_ticks"):
            h["eff"].set(summ["decode_efficiency"], tags=tags)
            h["overhead"].set(summ["overhead_frac"], tags=tags)
        return len(new_ticks) + len(new_reqs)

    def _drain_spans(self) -> int:
        """Completed requests with a serve context become children of
        their serve span — ``rt trace <rid>`` descends into the engine."""
        pending = self._pending_since("_span_req_wm", ticks=False)
        if not pending:
            return 0
        # advance past everything seen (context-less requests included) so
        # a cluster-less drain doesn't re-emit the same spans every pass
        self._span_req_wm = pending[-1]["seq"]
        new_reqs = [r for r in pending if r.get("request_id")]
        if not new_reqs:
            return 0
        try:
            from ray_tpu.serve import obs
        except Exception:  # noqa: BLE001
            return 0
        n = 0
        for r in new_reqs:
            try:
                span = obs.new_span_id()
                # the two counts ride ``phases`` the way the replica's
                # per-request kv span carried them (`rt trace <rid>`)
                phases = {"queue_wait": r["queue_wait_s"],
                          "prefill": r["prefill_s"],
                          "cached_tokens": float(r["cached_tokens"]),
                          "prompt_tokens": float(r["prompt_tokens"])}
                if r["kv_restore_s"] > 0:
                    phases["kv_restore"] = r["kv_restore_s"]
                if "t_done" in r:
                    phases["decode"] = max(0.0, r["t_done"] - r["t_first"])
                obs.emit_span(
                    f"serve:{r['request_id']}:engine:{span[:8]}",
                    f"engine:{self.name}",
                    request_id=r["request_id"], span_id=span,
                    parent_span_id=r.get("parent_span_id"),
                    t_start=r["t_submit"],
                    t_end=r.get("t_done", r["t_first"]),
                    phases=phases,
                    state="FINISHED" if r["state"] == "done"
                    else "CANCELLED")
                n += 1
            except Exception:  # noqa: BLE001 — span plane best-effort
                pass
        return n

    def _build_events(self, node: str, pid: int):
        """Tick + request records as GCS task events; the advance
        closure runs only after a successful push."""
        events = []
        new_ticks = self._pending_since("_event_tick_wm", ticks=True)
        for t in new_ticks[-256:]:
            events.append({
                "task_id": f"engtick:{node}:{pid}:{self.name}:{t['seq']}",
                "name": f"tick:{self.name}", "state": "FINISHED",
                "node_id": node,
                "times": {"RUNNING": t["t"],
                          "FINISHED": t["t"] + t["wall_s"]},
                "engine_tick": {**t, "engine": self.name}})
        new_reqs = self._pending_since("_event_req_wm", ticks=False)
        for r in new_reqs[-256:]:
            events.append({
                "task_id": f"engreq:{node}:{pid}:{self.name}:{r['seq']}",
                "name": f"req:{r['rid']}", "state": "FINISHED",
                "node_id": node,
                "times": {"RUNNING": r["t_submit"],
                          "FINISHED": r.get("t_done", r["t_first"])},
                "engine_request": {**{k: v for k, v in r.items()
                                      if not k.startswith("parent_")},
                                   "engine": self.name}})

        def advance() -> None:
            if new_ticks:
                self._event_tick_wm = new_ticks[-1]["seq"]
            if new_reqs:
                self._event_req_wm = new_reqs[-1]["seq"]

        return events, advance


def _window_entry(r: Dict[str, Any]) -> Dict[str, Any]:
    """What the aggregates read of one finished request."""
    out = {"t": r["t_done"], "ttft_s": r["ttft_s"], "tpot_s": r["tpot_s"],
           "tokens": r["tokens"], "decode_ticks": r["decode_ticks"]}
    for key in ("queue_s", "front_in_s", "replica_in_s"):
        if key in r:
            out[key] = r[key]
    return out


def _moe_totals(ticks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """``MOE_COUNTERS`` over the ticks' launches, and under ``moe_decode``
    over the decode launches alone (a prefill's rows an expert are another
    population than a decode step's). Nothing for a dense model."""
    def fold(kinds) -> Dict[str, int]:
        rows = [t["moe"][k] for t in ticks if "moe" in t
                for k in kinds if k in t["moe"]]
        if not rows:
            return {}
        return {name: (max if name == MOE_COUNTERS[-1] else sum)(
            r[i] for r in rows) for i, name in enumerate(MOE_COUNTERS)}

    out: Dict[str, Any] = fold(("prefill", "decode"))
    if out:
        out["moe_decode"] = fold(("decode",))
    return out


def _kv_read_totals(ticks: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Over the ticks' decode launches: positions attention read (rows x
    the step's bound), positions the active rows had live, and the first
    over the second (1.0 would be a read of the live positions alone).
    Nothing where no tick carries the counter."""
    read = sum(t.get("kv_positions_read", 0) for t in ticks)
    live = sum(t.get("kv_positions_live", 0) for t in ticks)
    if not read:
        return {}
    return {"kv_positions_read": read, "kv_positions_live": live,
            "kv_read_ratio": round(read / live, 4) if live else 0.0}


def _excess(ticks: List[Dict[str, Any]], wall) -> float:
    """Sum over the ticks that launched a decode of what ``wall(tick)``
    took beyond twice the median of the ticks of the same ``(bucket, k)``
    (the same program at the same stride): 0 while the engine ticks
    evenly, a stall of ``d`` seconds shows as ``d`` less one median."""
    groups: Dict[Tuple[int, int], List[float]] = {}
    for t in ticks:
        if t["phases"].get("decode_step", 0.0) > 0.0:
            groups.setdefault((t["bucket"], t["k"]), []).append(wall(t))
    total = 0.0
    for walls in groups.values():
        limit = 2.0 * _pct(sorted(walls), 0.50)
        total += sum(w - limit for w in walls if w > limit)
    return total


_metric_cache: Optional[Dict[str, Any]] = None
_GAP_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                2.5, 5.0)
_TTFT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                 5.0, 10.0)
_TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                 0.5, 1.0)


def _metric_handles(M) -> Dict[str, Any]:
    """Lazily registered ``rt_engine_*`` series (drain thread only)."""
    global _metric_cache
    if _metric_cache is None:
        _metric_cache = {
            "phase": M.get_or_create(
                M.Histogram, "rt_engine_tick_phase_seconds",
                "Per-tick engine phase wall (admission / kv_restore / "
                "prefill / decode_step / token_delivery / swap_barrier / "
                "record / idle_wait)",
                boundaries=_GAP_BUCKETS, tag_keys=("engine", "phase")),
            "gap": M.get_or_create(
                M.Histogram, "rt_engine_tick_gap_seconds",
                "Wall between consecutive decode launches while slots "
                "were active (spikes when prefill starves decode)",
                boundaries=_GAP_BUCKETS, tag_keys=("engine",)),
            "ticks": M.get_or_create(
                M.Counter, "rt_engine_ticks_total",
                "Engine ticks recorded by the flight recorder",
                tag_keys=("engine",)),
            "requests": M.get_or_create(
                M.Counter, "rt_engine_requests_total",
                "Engine request lifecycles completed, by terminal state",
                tag_keys=("engine", "state")),
            "ttft": M.get_or_create(
                M.Histogram, "rt_engine_ttft_seconds",
                "Engine-level time to first token (submit to admission's "
                "first token, transport excluded)",
                boundaries=_TTFT_BUCKETS, tag_keys=("engine",)),
            "tpot": M.get_or_create(
                M.Histogram, "rt_engine_tpot_seconds",
                "Engine-level time per output token (mean inter-token "
                "interval per completed request)",
                boundaries=_TPOT_BUCKETS, tag_keys=("engine",)),
            "slo": M.get_or_create(
                M.Gauge, "rt_engine_slo_attainment",
                "Rolling fraction of completed requests meeting the SLO "
                "target, slo=ttft|tpot",
                tag_keys=("engine", "slo")),
            "goodput": M.get_or_create(
                M.Gauge, "rt_engine_goodput_tokens_per_s",
                "Rolling tok/s from requests that met BOTH SLO targets",
                tag_keys=("engine",)),
            "eff": M.get_or_create(
                M.Gauge, "rt_engine_decode_efficiency",
                "Tokens emitted / slot-tokens the decode launches paid "
                "for (occupancy-weighted decode efficiency)",
                tag_keys=("engine",)),
            "overhead": M.get_or_create(
                M.Gauge, "rt_engine_recorder_overhead_ratio",
                "Recorder self-time as a fraction of recorded tick wall",
                tag_keys=("engine",)),
        }
    return _metric_cache
