"""What a compiled program does, read off its optimized HLO: to a large
buffer it was meant to step in place, and across chips.

The serving engine's decode program takes the slot cache donated and is
written so that nothing cache-sized is copied; whether the compiler agreed
is in the compiled module, on any backend, with nothing run:
``cache_traffic`` counts the bytes of every cache-shaped result that is a
fresh buffer (a ``copy``, a slice that was materialised, a transposed or
re-stacked piece), and of every cache-shaped update written back in place,
each weighted by the trip counts of the loops it sits in; for a recurrent
layer's state, which every step must read and write, the reads as well. A refactor that
brings a copy back shows here on a CPU run; what a copy costs in time only
a chip run says.

A sharded train step's collectives are GSPMD's choice, made from where the
parameters were placed: ``collectives`` lists them with their results and
the product each completes, ``collective_inventory`` sums them by kind.
"""

from __future__ import annotations

import functools
import re
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\)\s+->\s+.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_ARRAY = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_CALLEE = re.compile(r"\b(body|calls|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_TRIPS = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CONDITION = re.compile(r"\bcondition=%?([\w.\-]+)")
_CONSTANT = re.compile(r"\bconstant\((\d+)\)")
# results that are no new buffer: views, plumbing, and the loops and calls
# whose bodies are walked themselves
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
              "conditional", "call", "constant", "after-all",
              "optimization-barrier"}
# updated where they lie (the compiler adds an explicit ``copy`` where it
# cannot): what moves is the update, which operand that is
_IN_PLACE = {"dynamic-update-slice": 1, "scatter": 2}


def _split_result(rest: str) -> Tuple[str, str, str]:
    """``<shape> <opcode>(<operands>)<attributes>`` -> (shape, opcode,
    what follows the opcode's opening parenthesis)."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode, _, tail = rest.partition("(")
    return shape, opcode.strip(), tail


def _operands(tail: str) -> List[str]:
    """Names of the operands in ``a, bf16[2]{0} %b), attr=...``."""
    depth, end = 1, len(tail)
    for i, ch in enumerate(tail):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if depth == 0:
            end = i
            break
    names, depth, cur = [], 0, ""
    for ch in tail[:end] + ",":
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == "," and depth == 0:
            if cur.strip():
                names.append(cur.split()[-1].lstrip("%"))
            cur = ""
        else:
            cur += ch
    return names


class _Module:
    """Computations of an HLO module's text: name -> its instructions as
    (name, result shape text, opcode, operand names, whole line)."""

    def __init__(self, text: str):
        self.computations: Dict[str, List[Tuple[str, str, str, List[str],
                                                str]]] = {}
        self.entry: Optional[str] = None
        current = None
        for line in text.splitlines():
            head = _COMPUTATION.match(line)
            if head:
                current = self.computations.setdefault(head.group(2), [])
                if head.group(1):
                    self.entry = head.group(2)
                continue
            if line.startswith("}"):
                current = None
                continue
            inst = _INSTRUCTION.match(line) if current is not None else None
            if inst:
                shape, opcode, tail = _split_result(inst.group(2))
                current.append((inst.group(1), shape, opcode,
                                _operands(tail), line))

    def trips(self, line: str) -> int:
        """How often a ``while`` runs its body: the count the compiler
        wrote down, or the bound its condition compares a counter from
        zero against (what a ``lax.scan`` lowers to); 1 where neither is
        there to read."""
        known = _TRIPS.search(line)
        if known:
            return int(known.group(1))
        cond = _CONDITION.search(line)
        insts = self.computations.get(cond.group(1), []) if cond else []
        root = insts[-1] if insts else None
        if root and root[2] == "compare" and "direction=LT" in root[4]:
            for name, _, opcode, _, text in insts:
                bound = _CONSTANT.search(text)
                if opcode == "constant" and name in root[3] and bound:
                    return int(bound.group(1))
        return 1

    def walk(self, fusions: bool = False
             ) -> Iterator[Tuple[int, Tuple, List[Tuple]]]:
        """Every instruction that runs, outside fused computations (inside
        them too with ``fusions``), with how often it runs in one execution
        of the module and the instructions of its computation."""
        todo, seen = [(self.entry, 1)], set()
        while todo:
            name, times = todo.pop()
            if (name, times) in seen or name not in self.computations:
                continue
            seen.add((name, times))
            for inst in self.computations[name]:
                yield times, inst, self.computations[name]
                opcode, line = inst[2], inst[4]
                if opcode == "fusion" and not fusions:
                    continue
                trips = self.trips(line) if opcode == "while" else 1
                for kind, callee in _CALLEE.findall(line):
                    todo.append((callee, times * trips if kind == "body"
                                 else times))
                for group in _BRANCHES.findall(line):
                    todo += [(c.strip().lstrip("%"), times)
                             for c in group.split(",")]

    def fold(self, value, pick=max, fusions: bool = False) -> int:
        """``value(instruction, its computation's instructions)`` summed
        over one execution of the module: a loop's body times its trips,
        and of a conditional's branches, which are alternatives, the one
        ``pick`` takes (``max``: an upper limit whatever the predicate)."""
        memo: Dict[str, int] = {}

        def total(name: str) -> int:
            if name not in memo:
                memo[name] = 0
                insts = self.computations.get(name, [])
                memo[name] = sum(value(i, insts) + called(i) for i in insts)
            return memo[name]

        def called(inst) -> int:
            opcode, line = inst[2], inst[4]
            if opcode == "fusion" and not fusions:
                return 0
            trips = self.trips(line) if opcode == "while" else 1
            out, branches = 0, []
            for kind, callee in _CALLEE.findall(line):
                if kind.endswith("_computation"):  # a two-way conditional's
                    branches.append(callee)
                else:
                    out += total(callee) * (trips if kind == "body" else 1)
            for group in _BRANCHES.findall(line):
                branches += [c.strip().lstrip("%") for c in group.split(",")]
            return out + (pick(map(total, branches)) if branches else 0)

        return total(self.entry)


def _arrays(shape: str) -> List[Tuple[str, Tuple[int, ...]]]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _ARRAY.findall(shape)]


_HLO_TYPE = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}
# ``ops/pallas/ssm_update.py``'s call, or ``s6_update.py``'s: its result is
# the whole stacked state, aliased to its operand; what it moves is in its
# name, the rows and a row's sizes
_STATE_KERNEL = re.compile(r"(?:ssm_update_r(\d+)_h(\d+)_p(\d+)_n(\d+)"
                           r"|s6_update_r(\d+)_n(\d+)_c(\d+))")


# ``ops/pallas/kv_write.py``'s call: its results are whole K/V buffers, each
# aliased to its operand; what it moves of each is rows x a tile, in its name
_KV_KERNEL = re.compile(r"kv_write_r(\d+)_h(\d+)_t(\d+)_d(\d+)")


def _fused_writers(module: _Module, inst: Tuple) -> Tuple[List[Tuple],
                                                           List[Tuple]]:
    """For a ``fusion``: (the in-place updates in its computation whose
    result is the fusion's own, i.e. the fusion's result is its operand,
    updated; the computation's instructions)."""
    callee = dict(_CALLEE.findall(inst[4])).get("calls")
    fused = module.computations.get(callee, [])
    return [i for i in fused if i[2] in _IN_PLACE and _arrays(i[1])
            and _arrays(i[1])[0] in _arrays(inst[1])], fused


def cache_traffic(compiled: Any, cache: Any, *, rows: int, steps: int,
                  bounds: Sequence[int] = (), length_axis: int = 2
                  ) -> Dict[str, Any]:
    """For a compiled decode program over a slot cache, stepping ``rows``
    rows ``steps`` times a launch. ``cache`` is the slot tree
    (``generate.init_cache``'s buffers by name, arrays or their shapes:
    ``k`` and ``v`` [L, slots, max_len, hkv, hd], with window layers their
    rings ``wk`` and ``wv`` [L, slots, window, hkv, hd], and with recurrent
    layers ``ssm`` [L, slots, ...] and ``conv``), or one array shaped like K
    and like V. ``bounds``: the lengths below ``max_len`` a step's read may
    stop at (``generate.kv_read_bounds``), each a branch of a conditional.
    ``length_axis``: where positions lie in ``k`` (the config's
    ``kv_length_axis``: 2 as above, 3 for [L, slots, hkv, max_len, hd]).

    - ``cache_donated``: the program's input-output aliases cover the
      whole tree;
    - ``cache_copy_bytes_per_step``: bytes, per decode step, of results and
      in-place updates that are K/V-shaped (the cache's type, ``head_dim``
      and one of the lengths, ``max_len`` or a bound, among the dimensions)
      and at least one layer's ``rows`` large at that length, times the
      trip counts of the loops round them, and with the read at its full
      length (of a conditional's branches the costliest counts): an upper
      limit whatever the rows' positions;
    - ``cache_read_bytes_per_step``: bytes, per step, that slices so shaped
      read out of the cache, alone or inside a fusion, at the full length
      again; ``cache_read_bytes_per_step_least``: the same with every
      conditional taking its cheapest branch, what a step reads while its
      rows are short (equal to the other where the read has no bound);
    - ``cache_bytes``: K and V together, and the rings, to read the others
      against; with rings also ``window_bytes``, theirs alone.

    With recurrent layers also ``state_donated`` (the same aliases),
    ``state_bytes`` (the ``ssm`` buffer) and ``state_copy_bytes_per_step``:
    bytes, per decode step, that are state-shaped (the state's type, its
    last two dimensions, at least one layer's ``rows`` large) and are read
    by a slice (alone or inside a fusion), written by an in-place update,
    or a new buffer. A step that reads each row's state once and writes it
    once where it lies moves 2.0 x ``rows / slots`` of ``state_bytes``."""
    tree = cache if isinstance(cache, dict) else {"k": cache, "v": cache}
    module = _Module(compiled.as_text())
    mem = compiled.memory_analysis()
    tree_bytes = sum(int(np.prod(buf.shape, dtype=np.int64))
                     * np.dtype(buf.dtype).itemsize for buf in tree.values())
    donated = bool(mem is not None and mem.alias_size_in_bytes >= tree_bytes)
    row = list(tree["k"].shape[2:])
    max_len = row.pop(length_axis - 2)
    hkv, hd = row
    itemsize = np.dtype(tree["k"].dtype).itemsize
    dtype = _HLO_TYPE[str(tree["k"].dtype)]
    rings = [tree[name] for name in ("wk", "wv") if name in tree]
    lengths = sorted({max_len, *bounds,
                      *(r.shape[length_axis] for r in rings)})

    @functools.lru_cache(maxsize=None)  # three passes ask the same shapes
    def counted(shape: str) -> int:
        total = 0
        for dt, dims in _arrays(shape):
            nbytes = int(np.prod(dims, dtype=np.int64)) * itemsize
            if dt == dtype and hd in dims and any(
                    n in dims and nbytes >= rows * n * hkv * hd * itemsize
                    for n in lengths):
                total += nbytes
        return total

    def moved(inst, within) -> int:
        """Bytes one execution of ``inst`` moves: its result if that is
        a new buffer, the update if it writes in place. ``within``: the
        instructions among which its operands are defined."""
        name, shape, opcode, operands, _ = inst
        if opcode in _NO_BUFFER:
            return 0
        if opcode in _IN_PLACE:
            update = operands[_IN_PLACE[opcode]]
            return sum(counted(i[1]) for i in within if i[0] == update)
        kernel = _KV_KERNEL.match(name) if opcode == "custom-call" else None
        if kernel:  # a tile a row and buffer, read and written back
            return 2 * len(_arrays(shape)) * itemsize * int(np.prod(
                [int(v) for v in kernel.groups()], dtype=np.int64))
        if opcode == "fusion":
            writers, fused = _fused_writers(module, inst)
            if writers:  # the fusion's result is its operand, updated
                return sum(moved(i, fused) for i in writers)
        return counted(shape)

    def sliced(inst, within) -> int:
        return counted(inst[1]) if inst[2] in ("dynamic-slice", "slice") else 0

    out = {"cache_donated": donated,
           "cache_copy_bytes_per_step": module.fold(moved) // steps,
           "cache_read_bytes_per_step": module.fold(
               sliced, fusions=True) // steps,
           "cache_read_bytes_per_step_least": module.fold(
               sliced, min, fusions=True) // steps,
           "cache_bytes": sum(int(np.prod(tree[name].shape, dtype=np.int64))
                              for name in ("k", "v", "wk", "wv")
                              if name in tree) * itemsize}
    if rings:
        out["window_bytes"] = sum(int(np.prod(r.shape, dtype=np.int64))
                                  for r in rings) * itemsize
    if "ssm" in tree:
        out.update(state_donated=donated, **_state_traffic(
            module, tree["ssm"], rows, steps))
    return out


def _state_traffic(module: _Module, state: Any, rows: int, steps: int
                   ) -> Dict[str, int]:
    """``cache_traffic``'s two state counters for the ``ssm`` buffer."""
    itemsize = np.dtype(state.dtype).itemsize
    dtype = _HLO_TYPE[str(state.dtype)]
    last_two = tuple(state.shape[-2:])
    floor = rows * int(np.prod(state.shape[2:], dtype=np.int64)) * itemsize

    def counted(shape: str) -> int:
        return sum(nbytes for dt, dims in _arrays(shape)
                   for nbytes in [int(np.prod(dims, dtype=np.int64)) * itemsize]
                   if dt == dtype and nbytes >= floor
                   and dims[-2:] == last_two)

    def touched(inst, within) -> Optional[int]:
        """Bytes a slice reads or an in-place update writes; None for an
        instruction that is neither."""
        _, shape, opcode, operands, _ = inst
        if opcode in ("dynamic-slice", "slice"):
            return counted(shape)
        if opcode in _IN_PLACE:
            update = operands[_IN_PLACE[opcode]]
            return sum(counted(i[1]) for i in within if i[0] == update)
        return None

    total = 0
    for times, inst, peers in module.walk():
        kernel = _STATE_KERNEL.match(inst[0]) if inst[2] == "custom-call" \
            else None
        if kernel:  # steps the rows its name says where they lie: read, written
            total += times * 2 * itemsize * int(np.prod(
                [int(v) for v in kernel.groups() if v], dtype=np.int64))
            continue
        own = touched(inst, peers)
        if own is not None:
            total += times * own
        elif inst[2] == "fusion":
            writers, fused = _fused_writers(module, inst)
            total += times * sum(touched(i, fused) or 0 for i in fused)
            if not writers:  # its result is a buffer of its own
                total += times * counted(inst[1])
        elif inst[2] not in _NO_BUFFER:
            total += times * counted(inst[1])
    return {"state_copy_bytes_per_step": int(total // steps),
            "state_bytes": int(np.prod(state.shape, dtype=np.int64)) * itemsize}


#: the opcodes that move data between chips; an asynchronous pair counts
#: once, at its ``-done``, whose result is the collective's own
COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_ITEMSIZE = re.compile(r"[a-z]+(\d+)")


def _nbytes(dtype: str, dims: Tuple[int, ...]) -> int:
    bits = _ITEMSIZE.fullmatch(dtype)
    return int(np.prod(dims, dtype=np.int64)) * (
        int(bits.group(1)) if bits else 8) // 8


def collectives(compiled: Any) -> List[Dict[str, Any]]:
    """Every collective of a compiled program, the TPU compiler's fused
    ones included: its ``kind``, its ``name``, its result's ``arrays``
    [(dtype, dims)] and their ``bytes``, how often one execution of the
    program ``runs`` it (the trip counts of the loops round it), and the
    ``op_name`` of the source operation it completes. An all-reduce that
    is only sliced (how a reduce-scatter is spelt where the compiler fuses
    the two) is a ``reduce-scatter`` with the slice's result."""
    out = []
    for times, (name, shape, opcode, _, line), peers in _Module(
            compiled.as_text()).walk(fusions=True):
        kind = opcode[:-len("-done")] if opcode.endswith("-done") else opcode
        if kind not in COLLECTIVE_KINDS:
            continue
        if kind == "all-reduce":
            users = [p for p in peers if name in p[3]]
            if users and all(p[2] == "dynamic-slice" and p[3][0] == name
                             for p in users):
                kind, shape = "reduce-scatter", users[0][1]
        arrays = _arrays(shape)
        op_name = _OP_NAME.search(line)
        out.append({"kind": kind, "name": name, "arrays": arrays,
                    "bytes": sum(_nbytes(dt, dims) for dt, dims in arrays),
                    "runs": times,
                    "op_name": op_name.group(1) if op_name else ""})
    return out


def collective_inventory(compiled: Any) -> Dict[str, Dict[str, int]]:
    """``{kind: {count, runs, bytes}}`` of a compiled program: collectives
    of that kind in the program, their executions in one execution of the
    program, and the bytes of their results over those executions (what a
    chip ends up holding, not what crosses a link: a ring moves (n-1)/n of
    an all-gather's result and twice that of an all-reduce's)."""
    kinds: Dict[str, Dict[str, int]] = {}
    for c in collectives(compiled):
        k = kinds.setdefault(c["kind"], {"count": 0, "runs": 0, "bytes": 0})
        k["count"] += 1
        k["runs"] += c["runs"]
        k["bytes"] += c["runs"] * c["bytes"]
    return kinds


#: ``step_memory``'s keys and the compiler's names for them
_MEMORY = {"peak_bytes": "peak_memory_in_bytes",
           "temp_bytes": "temp_size_in_bytes",
           "argument_bytes": "argument_size_in_bytes",
           "output_bytes": "output_size_in_bytes",
           "alias_bytes": "alias_size_in_bytes"}


def step_memory(compiled: Any) -> Dict[str, int]:
    """What a compiled program needs of one device's memory by the
    compiler's own account (``memory_analysis()``): its peak, and the
    temporaries, arguments, results and the results that alias an argument
    (a donated state) which the peak is made of. Live arrays
    (``memory_stats``) show the state between launches; the temporaries of
    a launch show only here. {} where the backend gives no account."""
    mem = compiled.memory_analysis()
    if mem is None:
        return {}
    return {key: int(getattr(mem, name)) for key, name in _MEMORY.items()}
