"""Chrome-trace timeline export from the GCS task-event store.

Reference analog: ``ray.timeline()`` (``_private/state.py:865``) — dump task
execution spans as a Chrome ``chrome://tracing`` / Perfetto JSON file. Spans
come from the per-state transition times the raylets report to the GCS task
store (PENDING -> RUNNING -> FINISHED/FAILED).

Traced tasks additionally carry a per-phase breakdown (``util/tracing.py``
``PHASE_ORDER``): each phase becomes its own span on a ``<task>:phases``
track, laid out consecutively from the task's enqueue time — queue-wait,
worker-acquire (spawn vs warm), arg-fetch, execute, result-store line up
under the task's main lane.

Engine flight-recorder records (``util/engine_recorder.py``) export as
``engine:<name>:*`` lanes: the tick-phase lane (record / admission /
kv_restore / prefill / decode_step / token_delivery / swap_barrier /
idle_wait partition per tick, with decode tick-gap stalls as their own spans) and per-slot
request lanes (queued + decode span per lifecycle) — a prefill burst
starving decode is visible as a widening gap between decode launches.

RLHF flight-recorder records (``util/pipeline_recorder.py``) export as
``rlhf:<name>:*`` lanes: one PER-ROLE lane (generator / reference /
reward / learner) carrying each role's actor-side phase intervals, plus
an iteration lane with the driver's full-round span — the strict-phase
bubble is literally visible as the white space on three role lanes while
the fourth works, and an interrupted iteration (chaos kill) lands as an
instant marker at the phase it died in.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional

import ray_tpu


def timeline(filename: Optional[str] = None) -> List[Dict[str, Any]]:
    """Build (and optionally write) Chrome trace events for recent tasks."""
    backend = ray_tpu.global_worker()._require_backend()
    events = backend.io.run(backend._gcs.call(
        "list_tasks", {"limit": 10000, "profile": "include",
                       "serve": "include"}))
    trace: List[Dict[str, Any]] = []
    for ev in events:
        etick = ev.get("engine_tick")
        if etick:
            trace.extend(_engine_tick_lanes(ev, etick))
            continue
        ereq = ev.get("engine_request")
        if ereq:
            trace.extend(_engine_request_lanes(ev, ereq))
            continue
        rit = ev.get("rlhf_iter")
        if rit:
            trace.extend(_rlhf_iter_lanes(ev, rit))
            continue
        tl = ev.get("train_launch")
        if tl:
            trace.extend(_train_launch_lanes(ev, tl))
            continue
        is_serve = str(ev.get("task_id", "")).startswith("serve:")
        times = ev.get("times", {})
        start = times.get("RUNNING") or times.get("PENDING")
        end = times.get("FINISHED") or times.get("FAILED")
        if start is None:
            continue
        if end is None:
            end = start  # still running: zero-length marker
        trace.append({
            "name": ev.get("name") or "task",
            "cat": "serve" if is_serve else "task",
            "ph": "X",
            "ts": start * 1e6,
            "dur": max(0.0, (end - start) * 1e6),
            "pid": ev.get("node_id") or "node",
            # serve request spans share one lane so the proxy/route/
            # replica hops of all requests line up against task lanes
            "tid": "serve" if is_serve else ev["task_id"][:8],
            "args": {"task_id": ev["task_id"], "state": ev.get("state")},
        })
        pend = times.get("PENDING")
        if pend is not None and times.get("RUNNING"):
            trace.append({
                "name": f"{ev.get('name') or 'task'}:queued",
                "cat": "scheduling", "ph": "X",
                "ts": pend * 1e6,
                "dur": max(0.0, (times["RUNNING"] - pend) * 1e6),
                "pid": ev.get("node_id") or "node",
                "tid": ev["task_id"][:8],
            })
        if ev.get("phases"):
            trace.extend(_phase_lanes(ev))
    trace.extend(_memory_instants(backend))
    trace.extend(_failure_instants(backend))
    trace.extend(_serve_decision_instants(backend))
    trace.extend(_placement_instants(backend))
    if filename:
        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


def _memory_instants(backend) -> List[Dict[str, Any]]:
    """Spill / restore / oom_kill instant markers on a per-node ``memory``
    track, merged from the GCS mem-event store (cluster/raylet.py stamps
    them; `rt memory --oom` replays the oom_kill payloads)."""
    try:
        events = backend.io.run(backend._gcs.call(
            "list_mem_events", {"limit": 2000}))
    except Exception:  # noqa: BLE001 — older GCS / local backend
        return []
    out: List[Dict[str, Any]] = []
    for ev in events or ():
        kind = ev.get("kind", "mem")
        name = kind
        args: Dict[str, Any] = {}
        if kind in ("spill", "restore"):
            name = f"{kind} {str(ev.get('oid', ''))[:8]}"
            args = {"oid": ev.get("oid"), "size": ev.get("size"),
                    "seconds": ev.get("seconds")}
        elif kind == "oom_kill":
            victim = ev.get("victim", {})
            name = f"oom_kill {str(victim.get('worker_id', ''))[:8]}"
            args = {"victim": victim, "node_memory": ev.get("node_memory")}
        out.append({
            "name": name, "cat": "memory", "ph": "i", "s": "t",
            "ts": ev.get("t", 0.0) * 1e6,
            "pid": ev.get("node_id") or "node", "tid": "memory",
            "args": args,
        })
    return out


def _failure_instants(backend) -> List[Dict[str, Any]]:
    """Categorized FailureEvents as instant markers on a per-node
    ``errors`` track (cluster/gcs.py ``failure_events`` store — the same
    feed behind `rt errors` and `/api/errors`), so deaths line up against
    the task lanes they interrupted."""
    try:
        events = backend.io.run(backend._gcs.call(
            "list_failure_events", {"limit": 2000}))
    except Exception:  # noqa: BLE001 — older GCS / local backend
        return []
    out: List[Dict[str, Any]] = []
    for ev in events or ():
        cat = ev.get("category", "unknown")
        who = (ev.get("name") or ev.get("task_id") or ev.get("actor_id")
               or ev.get("worker_id") or "")
        name = f"{cat} {str(who)[:12]}".strip()
        count = ev.get("count", 1)
        if count > 1:
            name += f" x{count}"
        out.append({
            "name": name, "cat": "error", "ph": "i", "s": "t",
            "ts": ev.get("t", 0.0) * 1e6,
            "pid": ev.get("node_id") or "node", "tid": "errors",
            "args": {k: v for k, v in ev.items() if k != "t"},
        })
    return out


def _serve_decision_instants(backend) -> List[Dict[str, Any]]:
    """Autoscaler decision records as instant markers on the ``serve``
    lane (GCS ``serve_decisions`` store — the same records behind
    ``rt serve status --verbose``), so "why did it scale?" lines up
    against the request spans that produced the load."""
    try:
        events = backend.io.run(backend._gcs.call(
            "list_serve_events", {"limit": 500}))
    except Exception:  # noqa: BLE001 — older GCS / local backend
        return []
    out: List[Dict[str, Any]] = []
    for ev in events or ():
        out.append({
            "name": (f"scale {ev.get('deployment')} "
                     f"{ev.get('old_target')}->{ev.get('new_target')}"),
            "cat": "serve", "ph": "i", "s": "t",
            "ts": ev.get("t", 0.0) * 1e6,
            "pid": "serve", "tid": "autoscaler",
            "args": {k: v for k, v in ev.items() if k != "t"},
        })
    return out


def _placement_instants(backend) -> List[Dict[str, Any]]:
    """Placement decision receipts as instant markers on a per-node
    ``placement`` lane (GCS ``placement_events`` store — the same records
    behind ``rt sched decisions`` and ``/api/sched``), so "why did this
    task land here / hop there?" lines up against the task lanes."""
    try:
        events = backend.io.run(backend._gcs.call(
            "list_placement_events", {"limit": 500}))
    except Exception:  # noqa: BLE001 — older GCS / local backend
        return []
    out: List[Dict[str, Any]] = []
    for ev in events or ():
        kind = ev.get("kind", "place")
        who = (ev.get("name") or ev.get("task_id") or ev.get("actor_id")
               or ev.get("pg_id") or "")
        name = f"{kind} {str(who)[:12]}".strip()
        if ev.get("kind") == "spillback":
            name += (f" {str(ev.get('from_node', ''))[:8]}"
                     f"→{str(ev.get('node_id', ''))[:8]}")
        count = ev.get("count", 1)
        if count > 1:
            name += f" x{count}"
        out.append({
            "name": name, "cat": "placement", "ph": "i", "s": "t",
            "ts": ev.get("t", 0.0) * 1e6,
            "pid": ev.get("node_id") or "node", "tid": "placement",
            "args": {k: v for k, v in ev.items() if k != "t"},
        })
    return out


def _engine_tick_lanes(ev: Dict[str, Any], tick: Dict[str, Any]
                       ) -> List[Dict[str, Any]]:
    """One engine tick (util/engine_recorder.py) -> the tick-phase lane:
    the full tick span on ``engine:<name>:ticks`` with its phase
    partition laid out consecutively underneath on ``...:phases``, plus
    a ``gap`` span BEFORE the tick when the decode tick-gap was nonzero —
    a prefill-burst starvation stall is visible as a widening gap span
    between decode launches."""
    pid = ev.get("node_id") or "node"
    name = tick.get("engine", "engine")
    ts = tick["t"] * 1e6
    out = [{
        "name": f"tick k={tick.get('k', 0)}",
        "cat": "engine", "ph": "X", "ts": ts,
        "dur": max(0.0, tick.get("wall_s", 0.0)) * 1e6,
        "pid": pid, "tid": f"engine:{name}:ticks",
        "args": {"active": tick.get("active"),
                 "pending": tick.get("pending"),
                 "bucket": tick.get("bucket"), "k": tick.get("k"),
                 "tokens": tick.get("tokens"),
                 "admitted": tick.get("admitted"),
                 "gap_s": tick.get("gap_s")},
    }]
    gap = tick.get("gap_s") or 0.0
    if gap > 0:
        out.append({"name": "gap", "cat": "engine", "ph": "X",
                    "ts": ts - gap * 1e6, "dur": gap * 1e6,
                    "pid": pid, "tid": f"engine:{name}:gap"})
    from ray_tpu.util.tracing import sorted_phases

    t = ts
    for pname, secs in sorted_phases(tick.get("phases") or {}):
        dur = max(0.0, secs) * 1e6
        out.append({"name": pname, "cat": "engine_phase", "ph": "X",
                    "ts": t, "dur": dur, "pid": pid,
                    "tid": f"engine:{name}:phases",
                    "args": {"seconds": secs}})
        t += dur
    return out


def _engine_request_lanes(ev: Dict[str, Any], req: Dict[str, Any]
                          ) -> List[Dict[str, Any]]:
    """One engine request lifecycle -> its slot's lane: a ``queued``
    span (submit -> admission) followed by the decode span on
    ``engine:<name>:slot<N>`` — per-slot occupancy reads directly off
    the lane, and a starved slot shows its queued span stretching."""
    pid = ev.get("node_id") or "node"
    name = req.get("engine", "engine")
    slot = req.get("slot", -1)
    tid = f"engine:{name}:slot{slot}" if slot >= 0 \
        else f"engine:{name}:requests"
    t_submit = req.get("t_submit")
    t_admit = req.get("t_admit")
    t_done = req.get("t_done") or req.get("t_first") or t_admit
    if t_admit is None:
        return []
    out = []
    if t_submit is not None and t_admit > t_submit:
        out.append({"name": f"req {req.get('rid')}:queued",
                    "cat": "engine", "ph": "X", "ts": t_submit * 1e6,
                    "dur": (t_admit - t_submit) * 1e6,
                    "pid": pid, "tid": tid})
    out.append({
        "name": f"req {req.get('rid')} [{req.get('state', '?')}]",
        "cat": "engine", "ph": "X", "ts": t_admit * 1e6,
        "dur": max(0.0, (t_done - t_admit)) * 1e6,
        "pid": pid, "tid": tid,
        "args": {"rid": req.get("rid"), "state": req.get("state"),
                 "prompt_tokens": req.get("prompt_tokens"),
                 "cached_tokens": req.get("cached_tokens"),
                 "tokens": req.get("tokens"),
                 "decode_ticks": req.get("decode_ticks"),
                 "ttft_s": req.get("ttft_s"),
                 "tpot_s": req.get("tpot_s"),
                 "request_id": req.get("request_id")},
    })
    return out


def _rlhf_iter_lanes(ev: Dict[str, Any], rit: Dict[str, Any]
                     ) -> List[Dict[str, Any]]:
    """One RLHF pipeline iteration (util/pipeline_recorder.py) -> its
    per-role lanes: each actor-side interval becomes a phase span on
    ``rlhf:<name>:<role>``, the driver's full round lands on
    ``rlhf:<name>:iters``, and an interrupted record becomes an instant
    marker naming the phase it died in. Three idle role lanes under one
    busy one IS the strict-phase bubble, visually."""
    pid = ev.get("node_id") or "node"
    name = rit.get("pipeline", "rlhf")
    if rit.get("state") == "interrupted":
        return [{"name": f"interrupt:{rit.get('phase', '?')}",
                 "cat": "rlhf", "ph": "i", "s": "t",
                 "ts": rit.get("t", 0.0) * 1e6,
                 "pid": pid, "tid": f"rlhf:{name}:iters",
                 "args": {"phase": rit.get("phase"),
                          "error": rit.get("error")}}]
    out = [{
        "name": f"iter {rit.get('iteration')}",
        "cat": "rlhf", "ph": "X", "ts": rit.get("t", 0.0) * 1e6,
        "dur": max(0.0, rit.get("wall_s", 0.0)) * 1e6,
        "pid": pid, "tid": f"rlhf:{name}:iters",
        "args": {"iteration": rit.get("iteration"),
                 "bubble_fraction": rit.get("bubble_fraction"),
                 "coverage": rit.get("coverage"),
                 "staleness": rit.get("staleness"),
                 "tokens": rit.get("tokens"),
                 "restart_gap_s": rit.get("restart_gap_s")},
    }]
    for iv in rit.get("intervals") or ():
        t0, t1 = iv.get("t0"), iv.get("t1")
        if t0 is None or t1 is None:
            continue
        out.append({"name": iv.get("phase", "phase"), "cat": "rlhf",
                    "ph": "X", "ts": t0 * 1e6,
                    "dur": max(0.0, t1 - t0) * 1e6, "pid": pid,
                    "tid": f"rlhf:{name}:{iv.get('role', 'role')}",
                    "args": {"seconds": round(max(0.0, t1 - t0), 6)}})
    return out


def _train_launch_lanes(ev: Dict[str, Any], tl: Dict[str, Any]
                        ) -> List[Dict[str, Any]]:
    """One fused-K train launch (util/train_recorder.py) -> its lanes:
    the full launch span on ``train:<name>:launches`` with the phase
    partition laid out consecutively on ``...:phases`` (launch order:
    data_wait -> h2d -> dispatch/compile -> device_compute), plus a
    ``gap`` span BEFORE the launch when dispatch starvation was stamped —
    a data-starved run reads as wide data_wait spans, a host-bound run
    as gap spans between back-to-back launches."""
    pid = ev.get("node_id") or "node"
    name = tl.get("driver", "train")
    ts = tl.get("t", 0.0) * 1e6
    phases = tl.get("phases") or {}
    out = [{
        "name": f"launch k={tl.get('k', 0)}",
        "cat": "train", "ph": "X", "ts": ts,
        "dur": max(0.0, tl.get("wall_s", 0.0)) * 1e6,
        "pid": pid, "tid": f"train:{name}:launches",
        "args": {"seq": tl.get("seq"), "k": tl.get("k"),
                 "tokens": tl.get("tokens"),
                 "batch_shape": tl.get("batch_shape"),
                 "flops": tl.get("flops"), "gap_s": tl.get("gap_s")},
    }]
    gap = tl.get("gap_s") or 0.0
    if gap > 0:
        # the devices idled for `gap` before this dispatch with a stacked
        # batch in hand — anchor the span at dispatch start, minus gap
        disp_t = ts + (phases.get("data_wait", 0.0)
                       + phases.get("h2d", 0.0)) * 1e6
        out.append({"name": "gap", "cat": "train", "ph": "X",
                    "ts": disp_t - gap * 1e6, "dur": gap * 1e6,
                    "pid": pid, "tid": f"train:{name}:gap"})
    from ray_tpu.util.train_recorder import LAUNCH_PHASES

    t = ts
    for pname in LAUNCH_PHASES:
        if pname == "host_tax":
            continue  # overlaps device_compute — not part of the chain
        secs = phases.get(pname) or 0.0
        if secs <= 0.0:
            continue
        dur = secs * 1e6
        out.append({"name": pname, "cat": "train_phase", "ph": "X",
                    "ts": t, "dur": dur, "pid": pid,
                    "tid": f"train:{name}:phases",
                    "args": {"seconds": secs}})
        t += dur
    tax = phases.get("host_tax") or 0.0
    if tax > 0:
        # host_tax runs concurrently with device_compute (the callback
        # fires after dispatch returns) — its own lane, not the chain
        disp_end = ts + sum((phases.get(p) or 0.0) * 1e6
                            for p in ("data_wait", "h2d", "dispatch",
                                      "compile"))
        out.append({"name": "host_tax", "cat": "train_phase", "ph": "X",
                    "ts": disp_end, "dur": tax * 1e6, "pid": pid,
                    "tid": f"train:{name}:host_tax",
                    "args": {"seconds": tax}})
    return out


def _phase_lanes(ev: Dict[str, Any]) -> List[Dict[str, Any]]:
    """One traced task's phase breakdown -> consecutive Perfetto sub-spans
    on a ``<task>:phases`` track, anchored at the task's enqueue time.
    ``driver_get`` trails the reply, so it lays out after the partition."""
    from ray_tpu.util.tracing import sorted_phases, timed_phases

    times = ev.get("times", {})
    start = times.get("PENDING") or times.get("RUNNING")
    if start is None:
        return []
    pid = ev.get("node_id") or "node"
    tid = f"{ev['task_id'][:8]}:phases"
    out: List[Dict[str, Any]] = []
    # PENDING is stamped at raylet enqueue — the submit phase precedes it
    t = (start - max(0.0, ev["phases"].get("submit", 0.0))) * 1e6
    for name, secs in sorted_phases(timed_phases(ev["phases"])):
        dur = max(0.0, secs) * 1e6
        args = {"seconds": secs}
        if name == "worker_acquire" and ev.get("worker_source"):
            args["worker_source"] = ev["worker_source"]
        out.append({"name": name, "cat": "phase", "ph": "X",
                    "ts": t, "dur": dur, "pid": pid, "tid": tid,
                    "args": args})
        t += dur
    return out
