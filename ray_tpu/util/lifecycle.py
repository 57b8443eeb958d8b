"""A run's set-up and tear-down on one record.

The engine (PR 23) and the train launches (PR 20) have recorders for what
happens inside a window; this is the record of what happens before it and
after it: which processes the runtime spawned and how each one ended, where
``init()``, ``serve.run()`` and ``JaxTrainer.fit()`` spent their seconds,
and whether ``shutdown()`` left anybody behind. Built on
``recorder_core.span`` and the recorders' discipline (append in memory under
a short lock, no I/O); there is no drain thread, KV key, gauge or switch.
It is always on and costs a few dozen clock reads a process, none of them
inside a window.

Two kinds of entry, both kept in the process that makes them:

  spans   ``{name, t0, t1, pid, parent, session}`` on the WALL clock
          (``time.time()``: a span's ends are compared across processes,
          as the engine recorder's ``t`` and ``recorder_window`` are; the
          length is ``perf_counter``'s). A child names its parent; a
          worker's spans reach the raylet's process on messages that
          exist (the ``create_actor`` reply) and are merged here.
  rows    one :class:`ProcRow` per ``Popen`` of ``Raylet._spawn_worker``,
          from ``t_spawn`` to ``t_gone``. A row that is not yet seen gone
          is never dropped: ``Raylet.stop`` takes its processes from the
          rows. Of the gone ones the newest ``ROWS_CAP`` are kept.

Read it in the process that called ``ray_tpu.init()`` (the raylet lives
there): ``processes()``, ``spans()``, ``last_shutdown()``.
"""

from __future__ import annotations

import asyncio
import os
import signal
import sys
import threading
import time
from collections import deque
from typing import Any, Deque, Dict, Iterable, List, Optional

from ray_tpu.util import recorder_core

ROWS_CAP = 256
SPANS_CAP = 512

_lock = threading.Lock()
_spans: Deque[Dict[str, Any]] = deque(maxlen=SPANS_CAP)  # rt: guarded-by(_lock)
_rows: List["ProcRow"] = []  # rt: guarded-by(_lock)
_session = ""
_self_s = 0.0  # rt: guarded-by(_lock) — the record's own seconds
_last_shutdown: Optional[Dict[str, Any]] = None

#: every stamp a row can carry, in the order a life passes them
STAMPS = ("t_asked", "t_spawn", "t_main", "t_ready", "t_actor_init0",
          "t_actor_init1", "t_exit_asked", "t_term", "t_kill", "t_gone")


def set_session(name: str) -> None:
    """Spans closed from here on carry this session's name."""
    global _session
    _session = name


def session() -> str:
    return _session


def overhead_s() -> float:
    """Seconds this process has spent keeping the record (what
    ``overhead_frac`` is to the engine recorder)."""
    with _lock:
        return _self_s


# ---- spans ------------------------------------------------------------------

def record(name: str, t0: float, t1: float, *, parent: Optional[str] = None,
           pid: Optional[int] = None, **fields: Any) -> Dict[str, Any]:
    """A span whose ends somebody else stamped (a row, another process)."""
    global _self_s
    t = time.perf_counter()
    entry = {"name": name, "t0": t0, "t1": t1,
             "pid": os.getpid() if pid is None else pid,
             "parent": parent, "session": _session, **fields}
    with _lock:
        _spans.append(entry)
        _self_s += time.perf_counter() - t
    return entry


class span(recorder_core.span):
    """``with lifecycle.span("gcs_start", parent="init"):``: the block's
    extent on the wall clock into this process's record, and for the same
    extent what ``recorder_core.span`` gives (a ``bench:`` annotation where
    JAX is up). Lifecycle spans lie outside every traced window, so they
    may nest where a recorder's may not."""

    __slots__ = ("_parent", "_fields", "_wall0", "_dur", "entry")

    def __init__(self, name: str, *, parent: Optional[str] = None,
                 **fields: Any):
        self._dur: Dict[str, float] = {}
        super().__init__(name, self._dur)
        self._parent = parent
        self._fields = fields
        self.entry: Optional[Dict[str, Any]] = None

    def __enter__(self) -> "span":
        self._wall0 = time.time()
        super().__enter__()
        return self

    def __exit__(self, *exc: Any) -> None:
        super().__exit__(*exc)
        self.entry = record(self._name, self._wall0,
                            self._wall0 + self._dur[self._name],
                            parent=self._parent, **self._fields)


def merge(spans_: Iterable[Dict[str, Any]], **fields: Any) -> None:
    """Spans another process made, as they came on one of its replies."""
    t = time.perf_counter()
    global _self_s
    with _lock:
        for s in spans_:
            _spans.append({**s, **fields})
        _self_s += time.perf_counter() - t


def spans(name: Optional[str] = None, *, session: Optional[str] = None
          ) -> List[Dict[str, Any]]:
    """This process's spans (and those merged into it), oldest first; of
    one session (default: the current or last one, ``"*"`` for all)."""
    want = _session if session is None else session
    with _lock:
        out = [dict(s) for s in _spans]
    return [s for s in out if (want == "*" or s["session"] == want)
            and (name is None or s["name"] == name)]


def last(name: str, *, session: Optional[str] = None
         ) -> Optional[Dict[str, Any]]:
    found = spans(name, session=session)
    return found[-1] if found else None


def waterfall(*, session: Optional[str] = None) -> List[str]:
    """The session's spans as lines an operator can read: seconds from the
    first span's start, the span's length, its name under its parent's."""
    found = sorted(spans(session=session), key=lambda s: (s["t0"], -s["t1"]))
    if not found:
        return []
    parents = {s["name"]: s["parent"] for s in found}

    def depth(name: Optional[str]) -> int:
        seen = 0
        while parents.get(name) is not None and seen < 8:
            name, seen = parents[name], seen + 1
        return seen

    zero = found[0]["t0"]
    return [f"{s['t0'] - zero:9.3f} {s['t1'] - s['t0']:8.3f}  "
            f"{'  ' * depth(s['name'])}{s['name']}"
            + "".join(f" {k}={v:.3f}" if isinstance(v, float) else f" {k}={v}"
                      for k, v in s.items()
                      if k not in ("name", "t0", "t1", "parent", "session",
                                   "worker_id", "node_id")
                      and not isinstance(v, (list, dict)))
            for s in found]


# ---- rows -------------------------------------------------------------------

class ProcRow:
    """One spawned process, from ``Popen`` to "seen gone". ``proc`` needs
    ``poll``, ``terminate`` and ``kill`` (and ``pid``); everything else is
    plain data, ``as_dict`` gives it."""

    __slots__ = ("proc", "worker_id", "pid", "chips", "kind", "cause",
                 "label", "node_id", "session", "exit", "ended_by") + STAMPS

    def __init__(self, proc: Any, worker_id: str, *, chips: Iterable[int] = (),
                 kind: str = "task", cause: Optional[str] = None,
                 node_id: str = "", session: str = ""):
        self.proc = proc
        self.worker_id = worker_id
        self.pid: Optional[int] = getattr(proc, "pid", None)
        self.chips = tuple(chips)
        self.kind = kind
        self.cause = cause
        self.label: Optional[str] = None
        self.node_id = node_id
        self.session = session
        self.exit: Optional[int] = None
        self.ended_by: Optional[str] = None
        for s in STAMPS:
            setattr(self, s, None)
        self.t_spawn = time.time()

    def poll(self) -> Optional[int]:
        """``proc.poll()``; the first one that is not None closes the row."""
        rc = self.proc.poll()
        if rc is not None and self.t_gone is None:
            self.t_gone = time.time()
            self.exit = rc
            self.ended_by = self._ended_by(rc)
        return rc

    @property
    def alive(self) -> bool:
        return self.poll() is None

    def _ended_by(self, rc: int) -> str:
        if self.t_kill is not None and rc == -signal.SIGKILL:
            return "sigkill"
        if self.t_term is not None and rc == -signal.SIGTERM:
            return "sigterm"
        if self.t_exit_asked is not None and rc == 0:
            return "exit_rpc"
        if rc == 0:
            # nobody asked: the worker's own watch saw its raylet closed
            return "orphan_watch"
        if self.t_kill is not None:
            return "sigkill"
        if self.t_term is not None:
            return "sigterm"
        return "crash"

    # each signal is stamped at its first sending, and sent only to the living
    def ask_exit_stamp(self) -> None:
        if self.t_exit_asked is None:
            self.t_exit_asked = time.time()

    def terminate(self) -> None:
        if self.poll() is not None:
            return
        if self.t_term is None:
            self.t_term = time.time()
        try:
            self.proc.terminate()
        except ProcessLookupError:
            pass

    def kill(self) -> None:
        if self.poll() is not None:
            return
        if self.t_kill is None:
            self.t_kill = time.time()
        try:
            self.proc.kill()
        except ProcessLookupError:
            pass

    def as_dict(self) -> Dict[str, Any]:
        return {k: (list(v) if k == "chips" else v) for k, v in
                ((k, getattr(self, k)) for k in self.__slots__ if k != "proc")}

    def describe(self) -> str:
        return (f"worker {self.worker_id[:8]} pid {self.pid} chips "
                f"{','.join(map(str, self.chips)) or '-'}")


async def wait_gone(rows_: Iterable[ProcRow], timeout_s: float
                    ) -> List[ProcRow]:
    """Wait, up to ``timeout_s``, until every row's process has been seen
    gone; returns the rows that have not."""
    deadline = time.monotonic() + timeout_s
    while True:
        left = [row for row in rows_ if row.alive]
        if not left or time.monotonic() >= deadline:
            return left
        await asyncio.sleep(0.02)


def add_row(row: ProcRow) -> ProcRow:
    """Take a new row into the books. Gone rows beyond ``ROWS_CAP`` leave,
    oldest first; a row not yet seen gone never does."""
    global _self_s
    t = time.perf_counter()
    with _lock:
        _rows.append(row)
        extra = len(_rows) - ROWS_CAP
        if extra > 0:
            for old in [r for r in _rows if r.t_gone is not None][:extra]:
                _rows.remove(old)
        _self_s += time.perf_counter() - t
    return row


def rows(*, node_id: Optional[str] = None, session: Optional[str] = None
         ) -> List[ProcRow]:
    with _lock:
        out = list(_rows)
    return [r for r in out if (node_id is None or r.node_id == node_id)
            and (session is None or r.session == session)]


def not_gone(*, node_id: Optional[str] = None, session: Optional[str] = None
             ) -> List[ProcRow]:
    """Every ``Popen`` of the node (or session) not yet seen gone."""
    return [r for r in rows(node_id=node_id, session=session) if r.alive]


def processes(*, session: Optional[str] = None) -> List[Dict[str, Any]]:
    """The rows as plain data, oldest first; of one session (default: the
    current or last one, ``"*"`` for all)."""
    want = _session if session is None else session
    return [r.as_dict() for r in rows(session=None if want == "*" else want)]


# ---- the shutdown record ----------------------------------------------------

_abandoned: List[str] = []  # rt: guarded-by(_lock)


def note_abandoned(what: str) -> None:
    """A wait of the shutdown that ran out, or a step of it that raised:
    said in the ``rt-shutdown`` line, never swallowed."""
    with _lock:
        _abandoned.append(what)


def close_shutdown(session: Optional[str] = None) -> Dict[str, Any]:
    """Called as ``ray_tpu.shutdown()`` returns: count the session's rows,
    keep the record for ``last_shutdown()`` and say one line on stderr if
    anything was killed, left or abandoned."""
    global _last_shutdown
    want = _session if session is None else session
    mine = rows(session=want)
    left = [r for r in mine if r.alive]
    killed = [r for r in mine if r.ended_by == "sigkill"]
    with _lock:
        abandoned, _abandoned[:] = list(_abandoned), []
    rec: Dict[str, Any] = {
        "session": want,
        "procs_spawned": len(mine),
        "procs_exited_on_request": sum(r.ended_by == "exit_rpc" for r in mine),
        "procs_exited_on_term": sum(r.ended_by == "sigterm" for r in mine),
        "procs_killed": len(killed),
        "procs_alive_at_return": len(left),
        "abandoned": abandoned,
        "rows": [r.as_dict() for r in mine],
        "spans": _from_first(spans(session=want),
                             ("serve_shutdown", "shutdown", "raylet_stop")),
        "line": None,
    }
    if killed or left or abandoned:
        def how(r: ProcRow) -> str:
            first = min(t for t in (r.t_exit_asked, r.t_term, r.t_kill)
                        if t is not None)
            return (f"{r.describe()} after {r.t_kill - first:.1f} s, gone "
                    f"{r.t_gone - r.t_kill:.1f} s later")

        parts = [f"{len(mine)} spawned",
                 f"{rec['procs_exited_on_request']} gone on request",
                 f"{rec['procs_exited_on_term']} gone on SIGTERM",
                 f"{len(killed)} killed" + (
                     f" ({'; '.join(map(how, killed))})" if killed else ""),
                 f"{len(left)} left" + (
                     f" ({'; '.join(r.describe() for r in left)})"
                     if left else "")]
        if abandoned:
            parts.append("abandoned: " + "; ".join(abandoned))
        rec["line"] = "rt-shutdown: " + ", ".join(parts)
        print(rec["line"], file=sys.stderr, flush=True)
    _last_shutdown = rec
    return rec


def _from_first(found: List[Dict[str, Any]], names: Iterable[str]
                ) -> List[Dict[str, Any]]:
    """The spans that start no earlier than the first span of ``names``."""
    starts = [s["t0"] for s in found if s["name"] in names]
    return [s for s in found if starts and s["t0"] >= min(starts)]


def last_shutdown() -> Optional[Dict[str, Any]]:
    """What the last ``ray_tpu.shutdown()`` of this process recorded."""
    return _last_shutdown
