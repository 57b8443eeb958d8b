"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, the operations that took most
of it, the idle gaps by what the host was doing, kernel and collective time.

What a TPU trace looks like (read by hand first, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds the operations as the core
ran them, one after another, each named by its whole HLO line
(``%fusion.3 = bf16[..] fusion(..)``); ``XLA Modules`` holds the programs;
``Async XLA Ops`` holds copies and collectives in flight beside the core and
is not busy time. ``/host:CPU`` has a line per thread, and a
``jax.profiler.TraceAnnotation`` is an event on its thread's line. Host and
device share a clock to within a millisecond.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmark.lib import arithmetic

ANNOTATION_PREFIX = "bench:"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast")
MIN_GAP_S = 20e-6  # shorter pauses between two operations are the core's own

Interval = Tuple[float, float]


def newest_xplane(log_dir: str) -> Optional[str]:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def instruction(event_name: str) -> str:
    """``%fusion.3`` of ``%fusion.3 = bf16[..] fusion(..)``."""
    return event_name.split(" = ", 1)[0]


def opcode(event_name: str) -> str:
    m = _OPCODE.search(event_name.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def is_collective(event_name: str) -> bool:
    return bool(_COLLECTIVE.search(instruction(event_name))
                or _COLLECTIVE.search(opcode(event_name)))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def _events(line) -> List[Tuple[float, float, str]]:
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def reduce_trace(path: str, idle_label: str = "host-unattributed",
                 top: int = 10) -> Optional[Dict[str, Any]]:
    """The reduction. None where the trace holds no device operation."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[2].startswith(ANNOTATION_PREFIX)]
    devices = {d: evs for d, evs in devices.items() if evs}
    if not devices:
        return None
    every = [ev for evs in devices.values() for ev in evs]
    t0 = min([a for a, _, _ in every] + [a for a, _, _ in spans])
    t1 = max([b for _, b, _ in every] + [b for _, b, _ in spans])
    n = len(devices)

    busy_s = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    kernel_s = collective_s = 0.0
    flash = {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0}
    for evs in devices.values():
        busy = union((a, b) for a, b, _ in evs)
        busy_s += sum(b - a for a, b in busy)
        for name, own in _self_times(evs):
            op_s[instruction(name)] += own
        for a, b, name in evs:
            if is_collective(name):
                collective_s += b - a
            if "tpu_custom_call" in name:
                kernel_s += b - a
                call = arithmetic.flash_call_kind(name)
                if call:
                    flops, nbytes = arithmetic.flash_call_cost(*call)
                    flash["seconds"] += b - a
                    flash["flops"] += flops
                    flash["bytes"] += nbytes
                    flash["calls"] += 1
        # the gaps: before, between and after the busy stretches
        edges = [(t0, t0)] + busy + [(t1, t1)]
        for (_, end), (start, _) in zip(edges, edges[1:]):
            if start - end < MIN_GAP_S:
                continue
            gap = (end, start)
            best, best_s = idle_label, 0.0
            for a, b, name in spans:
                s = _overlap(gap, (a, b))
                if s > best_s:
                    best, best_s = name[len(ANNOTATION_PREFIX):], s
            gap_s[best] += start - end
    return {
        "devices": n, "window_s": t1 - t0, "busy_s": busy_s / n,
        "kernel_s": kernel_s / n, "collective_s": collective_s / n,
        "device_ops": _top(op_s, n, top), "idle_gaps": _top(gap_s, n, top),
        "flash": {k: v / n for k, v in flash.items()},
    }


def flash_roofline(reduced: Dict[str, Any], device_kind: str
                   ) -> Optional[Dict[str, float]]:
    """The flash kernels' share of their roofline: the least time the chip
    could take for the calls in the trace (the larger of operations over peak
    FLOP/s and bytes over peak bytes/s; ``compute_bound`` says which) over
    the time they took."""
    f = reduced["flash"]
    if not f["calls"] or f["seconds"] <= 0:
        return None
    peak = arithmetic.peaks(device_kind)
    by_flops, by_bytes = f["flops"] / peak["flops"], f["bytes"] / peak["hbm_bytes_s"]
    return {"share": max(by_flops, by_bytes) / f["seconds"],
            "compute_bound": by_flops >= by_bytes,
            "achieved_flops_s": f["flops"] / f["seconds"]}


def idle_share(reduced: Optional[Dict[str, Any]]) -> Optional[float]:
    """Percent of the traced window in which no operation ran on the device,
    mean over the chips; None without a trace."""
    if not reduced or not reduced["window_s"]:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def _self_times(events: List[Tuple[float, float, str]]
                ) -> List[Tuple[str, float]]:
    """Each event's own time: its length less the events nested in it. A
    ``while`` or a ``call`` lies on the line over the operations of its body,
    so lengths alone would count a loop's body twice."""
    out: List[List[Any]] = []
    open_: List[Tuple[float, int]] = []  # (end, index into out)
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while open_ and open_[-1][0] <= a:
            open_.pop()
        if open_:
            out[open_[-1][1]][1] -= min(b, open_[-1][0]) - a
        out.append([name, b - a])
        open_.append((b, len(out) - 1))
    return [(name, max(0.0, own)) for name, own in out]


def _top(seconds: Dict[str, float], n: int, top: int) -> List[List[Any]]:
    ranked = sorted(seconds.items(), key=lambda kv: -kv[1])[:top]
    return [[name[:120], s / n] for name, s in ranked]
