"""From a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy and idle time, the operations that took most
of it, device time by program and scope, the idle gaps by what the host was
doing, kernel and collective time.

What a TPU trace looks like (read by hand first, PR 22): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds the operations as the core
ran them, one after another, each named by its whole HLO line
(``%fusion.3 = bf16[..] fusion(..)``); ``XLA Modules`` holds the programs;
``Async XLA Ops`` holds copies and collectives in flight beside the core and
is not busy time. ``/host:CPU`` has a line per thread, and a
``jax.profiler.TraceAnnotation`` is an event on its thread's line. Host and
device share a clock to within a millisecond.

Where an operation says which program and scope it belongs to (read by hand,
PR 25: the raw bytes of ``tests/benchmark/data/small.xplane.pb`` and of
traces of the cells on the chip). Not on the event: an ``XLA Ops`` event
carries ``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier`` and nothing else, and ``jax.profiler.ProfileData`` shows an
event's own stats only. The rest hangs on the event's *metadata*
(``XPlane.event_metadata``, one entry per HLO instruction, found again by the
event's name): ``tf_op`` is the instruction's ``op_name``, the path of jits,
transforms and ``jax.named_scope`` names that ``/`` separates and the
primitive ends (``jit(rt_decode)/jit(main)/while/body/attn/dot_general:``),
and ``program_id`` is the number in the name of the program's ``XLA Modules``
event (``jit_rt_decode(1273..)``). Beside them lie ``hlo_category``,
``flops``, ``bytes_accessed`` and ``source``: the compiler's own counts,
which no metric here reads. ``_op_metadata`` takes the two stats off the
file's wire format (the protobuf schema comes only with tensorflow, too
heavy to import beside an open window) and skips the lines, so its cost does
not grow with the length of the trace.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterable, Iterator, List, Optional, Tuple

from benchmark.lib import arithmetic, spec

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


# ---- program and scope of an operation ---------------------------------------

def _varint(buf: memoryview, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: memoryview) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a varint, a
    memoryview for a length-delimited or fixed field. No schema, no copy."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in a trace file")
            value = buf[i:i + size]
            i += size
        yield key >> 3, value


def _op_metadata(path: str) -> Dict[int, Dict[str, Tuple[int, str]]]:
    """{chip: {event name: (program_id, tf_op)}} of every device plane.
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata
    = 5 (maps: key 1, value 2); XEventMetadata.name = 2, .stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, .uint64_value = 3,
    .int64_value = 4, .str_value = 5, .ref_value = 7 (a stat metadata's id,
    whose name is the string)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[int, Dict[str, Tuple[int, str]]] = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stats = "", [], {}
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(value)
            elif number == 5:
                entry = dict(_fields(value))
                stats[entry[1]] = bytes(dict(_fields(entry[2])).get(2, b"")).decode()
        m = _DEVICE_PLANE.match(name)
        if not m:
            continue
        ops = out.setdefault(int(m.group(1)), {})
        for entry in events:
            op_name, program, tf_op = "", 0, ""
            for number, value in _fields(dict(_fields(entry))[2]):
                if number == 2:
                    op_name = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    kind = stats.get(stat.get(1))
                    if kind == "program_id":
                        program = stat.get(3, stat.get(4, 0))
                    elif kind == "tf_op":
                        tf_op = (bytes(stat[5]).decode() if 5 in stat
                                 else stats.get(stat.get(7), ""))
            ops[op_name] = (program, tf_op)
    return out


# what JAX itself writes into an op_name between the program and the primitive
_STRUCTURAL = re.compile(
    r"^(while|body|cond|branch_\d+_fun|checkpoint|rematted_computation"
    r"|closed_call|shard_map)$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_IDENTIFIER = re.compile(r"^[A-Za-z_]\w*$")


def scope_of(tf_op: str) -> str:
    """The outermost ``jax.named_scope`` of an ``op_name``: the first part of
    the path, the primitive at its end left out, that is a plain name (an
    einsum's ``bqhgd,bkhd->bhgqk`` and a merged ``mul;while`` are parts
    too, a ``jit(silu)`` is a function of JAX's own) once a transform's
    wrapper is taken off (``transpose(jvp(attn))`` is ``attn``), and is not
    one of JAX's own words. ``other`` where there is none."""
    for part in tf_op.rstrip(":").split("/")[:-1]:
        while (m := _WRAPPED.match(part)) and m.group(1) != "jit":
            part = m.group(2)
        if _IDENTIFIER.match(part) and not _STRUCTURAL.match(part):
            return part
    return "other"


def scope_key(program: str, tf_op: str, event_name: str) -> str:
    """``<program>/<scope>`` of one operation; a collective's own line,
    ``<program>/<scope>/collective``, because a gradient's all-reduce carries
    the ``op_name`` of the product it reduces and would pass for that scope's
    arithmetic (seen: the experts' in Mixtral's step)."""
    key = f"{program}/{scope_of(tf_op)}"
    return key + "/collective" if is_collective(event_name) else key


def reduce_trace(path: str, idle_label: str = "host-unattributed",
                 top: int = 10, root: str = spec.ROOT
                 ) -> Optional[Dict[str, Any]]:
    """The reduction. None where the trace holds no device operation.
    ``root`` is where ``benchmark/kernels/`` is looked for."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Tuple[float, float, str]]] = {}
    programs: Dict[int, str] = {}  # program_id -> jit_rt_decode
    spans: List[Tuple[float, float, str]] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = _events(line)
                elif line.name == "XLA Modules":
                    for e in line.events:
                        name, _, number = e.name.rpartition("(")
                        if number.rstrip(")").isdigit():
                            programs[int(number.rstrip(")"))] = name
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
    matchers = {k: m.match for k, m in spec.load_kernels(root).items()}
    metadata = _op_metadata(path)

    busy_s = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    scope_s: Dict[str, float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    kernel_s = collective_s = 0.0
    kernels = {k: {"seconds": 0.0, "flops": 0.0, "bytes": 0.0, "calls": 0}
               for k in matchers}
    for chip, evs in devices.items():
        busy = union((a, b) for a, b, _ in evs)
        busy_s += sum(b - a for a, b in busy)
        # what a name says of itself, worked out once per distinct name
        where: Dict[str, str] = {}
        costs: Dict[str, List[Tuple[str, float, float]]] = {}
        ops = metadata.get(chip, {})
        for name, own in _self_times(evs):
            op_s[instruction(name)] += own
            if name not in where:
                program, tf_op = ops.get(name, (0, ""))
                where[name] = scope_key(programs.get(program, "unnamed"),
                                        tf_op, name)
            scope_s[where[name]] += own
        for a, b, name in evs:
            if is_collective(name):
                collective_s += b - a
            if "tpu_custom_call" in name:
                kernel_s += b - a
            if name not in costs:
                found = ((k, match(name)) for k, match in matchers.items())
                costs[name] = [(k, *cost) for k, cost in found if cost]
            for k, flops, nbytes in costs[name]:
                kernels[k]["seconds"] += b - a
                kernels[k]["flops"] += flops
                kernels[k]["bytes"] += nbytes
                kernels[k]["calls"] += 1
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
        "by_scope": {k: s / n for k, s in sorted(scope_s.items())},
        "kernels": {k: {key: v / n for key, v in totals.items()}
                    for k, totals in kernels.items()},
    }


def kernel_roofline(reduced: Dict[str, Any], kernel: str, device_kind: str
                    ) -> Optional[Dict[str, float]]:
    """A kernel's share of its roofline: the least time the chip could take
    for its calls in the trace (the larger of operations over peak FLOP/s and
    bytes over peak bytes/s; ``compute_bound`` says which) over the time
    they took. ``kernel`` is a file of ``benchmark/kernels/``."""
    k = reduced["kernels"].get(kernel)
    if not k or not k["calls"] or k["seconds"] <= 0:
        return None
    peak = arithmetic.peaks(device_kind)
    by_flops, by_bytes = k["flops"] / peak["flops"], k["bytes"] / peak["hbm_bytes_s"]
    return {"share": max(by_flops, by_bytes) / k["seconds"],
            "compute_bound": by_flops >= by_bytes,
            "achieved_flops_s": k["flops"] / k["seconds"]}


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
