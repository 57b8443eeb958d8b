"""The record of a run's set-up and tear-down (``ray_tpu/util/lifecycle.py``):
a row per spawned process from ``Popen`` to "seen gone", the spans of
``init``, ``serve.run`` and the two shutdowns, and the repair the rows make
possible: ``shutdown()`` returns when nobody is left, not when 5 s are up.
"""

import signal
import time

import pytest

import ray_tpu
from ray_tpu._private.config import get_config
from ray_tpu.cluster import raylet as raylet_mod
from ray_tpu.cluster.driver_backend import ClusterHandle


class StandIn:
    """What a row needs of a process (``poll / terminate / kill``), with a
    temper: it may ignore ``terminate``, and it is gone only some seconds
    after the signal it does heed."""

    pid = 4242

    def __init__(self, *, heeds_term: bool, gone_after_s: float):
        self.heeds_term, self.gone_after_s = heeds_term, gone_after_s
        self.hit_at = self.rc = None

    def _hit(self, rc: int) -> None:
        if self.hit_at is None:
            self.hit_at, self.rc = time.monotonic(), rc

    def poll(self):
        if self.hit_at is not None and \
                time.monotonic() - self.hit_at >= self.gone_after_s:
            return self.rc
        return None

    def terminate(self):
        if self.heeds_term:
            self._hit(-signal.SIGTERM)

    def kill(self):
        self._hit(-signal.SIGKILL)


@pytest.fixture
def node():
    """(cluster, raylet): a head and one node on an io thread, no driver."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster = ClusterHandle()
    cluster.start_gcs()
    raylet = cluster.add_node(num_cpus=1, num_tpus=0)
    yield cluster, raylet
    if cluster.raylets:
        cluster.shutdown()


def _spawn(cluster, raylet, stand_in, monkeypatch, *, warm=False):
    """One ``_spawn_worker`` whose ``Popen`` gives the stand-in."""
    async def go():
        if warm:
            raylet._prestarting += 1
            await raylet._prestart_worker()
            return next(e for e in raylet._workers.values()
                        if e.proc is stand_in)
        return raylet._spawn_worker(((), None), [], None)

    with monkeypatch.context() as patched:
        patched.setattr(raylet_mod.subprocess, "Popen",
                        lambda *a, **k: stand_in)
        return cluster.io.run(go())


# (a) ---- the race, shown and closed ------------------------------------------

def test_shutdown_returns_only_when_a_killed_process_is_gone(node, monkeypatch):
    """A worker that ignores SIGTERM and outlasts its SIGKILL by 3 s. On the
    parent's code (``HEAD~1``) a 5 s cap cut the wait and ``shutdown()``
    returned with it alive: the first assertion fails there."""
    cluster, raylet = node
    proc = StandIn(heeds_term=False, gone_after_s=3.0)
    entry = _spawn(cluster, raylet, proc, monkeypatch)
    cluster.shutdown()
    assert proc.poll() is not None, "shutdown() returned with a process alive"

    from ray_tpu.util import lifecycle

    rec = lifecycle.close_shutdown(cluster.session_name)
    row = next(r for r in rec["rows"] if r["worker_id"] == entry.worker_id)
    assert row["ended_by"] == "sigkill" and row["exit"] == -signal.SIGKILL
    assert row["t_gone"] - row["t_kill"] == pytest.approx(3.0, abs=0.3)
    assert row["t_kill"] - row["t_term"] == pytest.approx(
        raylet._TERM_GRACE_S, abs=0.3)
    assert rec["procs_killed"] == 1 and rec["procs_alive_at_return"] == 0
    assert rec["line"].startswith("rt-shutdown: 1 spawned") \
        and "1 killed (worker" in rec["line"] and "0 left" in rec["line"]
    names = {s["name"]: s for s in rec["spans"]}
    assert names["kill_wait"]["t1"] - names["kill_wait"]["t0"] >= 2.7
    assert names["term_grace"]["parent"] == "raylet_stop"


def test_a_wait_that_runs_out_is_said_with_the_pid(node, monkeypatch, capsys):
    cluster, raylet = node
    monkeypatch.setattr(raylet_mod.Raylet, "_TERM_GRACE_S", 0.1)
    monkeypatch.setattr(raylet_mod.Raylet, "_KILL_WAIT_S", 0.2)
    proc = StandIn(heeds_term=False, gone_after_s=60.0)
    _spawn(cluster, raylet, proc, monkeypatch)
    cluster.shutdown()

    from ray_tpu.util import lifecycle

    rec = lifecycle.close_shutdown(cluster.session_name)
    assert rec["procs_alive_at_return"] == 1
    assert "1 left (worker" in rec["line"] and "pid 4242" in rec["line"]
    assert any("waited" in a and "pid 4242" in a for a in rec["abandoned"])
    assert rec["line"] in capsys.readouterr().err


# (b) ---- a row leaves the books only once it has been seen gone --------------

def _watch(raylet, entry, seconds):
    """Poll the books: True if the entry was ever out of ``_workers`` while
    its row had no ``t_gone``; also whether it was seen in them, signalled
    and alive."""
    dropped_alive = held_while_dying = False
    deadline = time.monotonic() + seconds
    while time.monotonic() < deadline:
        in_books = entry.worker_id in raylet._workers
        gone = entry.row.t_gone is not None
        dropped_alive |= not in_books and not gone
        held_while_dying |= in_books and not gone and (
            entry.row.t_term or entry.row.t_kill) is not None
        if not in_books and gone:
            break
        time.sleep(0.02)
    return dropped_alive, held_while_dying


def test_the_idle_reaper_keeps_its_row_until_poll_collects_it(
        node, monkeypatch):
    cluster, raylet = node
    monkeypatch.setattr(get_config(), "idle_worker_ttl_s", 0.05)
    procs = [StandIn(heeds_term=True, gone_after_s=0.8) for _ in range(2)]
    entries = [_spawn(cluster, raylet, p, monkeypatch) for p in procs]

    async def idle():
        for e in entries:
            raylet._release_worker(e)
        entries[0].idle_since -= 10.0  # the older one: beyond the soft limit

    cluster.io.run(idle())
    dropped_alive, held_while_dying = _watch(raylet, entries[0], 5.0)
    assert not dropped_alive and held_while_dying
    assert entries[0].worker_id not in raylet._workers
    assert entries[0].row.ended_by == "sigterm"
    assert entries[0].row.t_gone - entries[0].row.t_term >= 0.7
    assert entries[1].worker_id in raylet._workers  # within the soft limit


def test_a_worker_whose_connection_is_down_is_not_pooled_again(
        node, monkeypatch):
    """A killed worker closes its sockets a moment before ``poll()`` can
    collect it. Pooled in that moment it was handed the owner's retries one
    after another and failed each at once: a task with three retries died of
    one crash (one run in six of ``scripts/chaos_smoke.sh``'s first leg)."""
    cluster, raylet = node

    class Client:
        _closed = True

    proc = StandIn(heeds_term=False, gone_after_s=0.8)  # dying, not yet gone
    entry = _spawn(cluster, raylet, proc, monkeypatch)
    entry.client = Client()

    async def release():
        raylet._release_worker(entry)

    cluster.io.run(release())
    assert entry not in raylet._idle.get(entry.key, [])
    assert entry.row.t_kill is not None
    dropped_alive, held_while_dying = _watch(raylet, entry, 5.0)
    assert not dropped_alive and held_while_dying
    assert entry.worker_id not in raylet._workers


def test_the_prestart_timeout_keeps_its_row_until_poll_collects_it(
        node, monkeypatch):
    cluster, raylet = node
    monkeypatch.setattr(get_config(), "process_startup_timeout_s", 0.1)
    proc = StandIn(heeds_term=False, gone_after_s=0.8)
    before = raylet._sched_stats["prestarted"]
    entry = _spawn(cluster, raylet, proc, monkeypatch, warm=True)
    # never ready: killed at the timeout, and still in the books
    assert entry.row.t_kill is not None and entry.row.kind == "warm"
    assert raylet._sched_stats["prestarted"] == before + 1  # the row's event
    dropped_alive, held_while_dying = _watch(raylet, entry, 5.0)
    assert not dropped_alive and held_while_dying
    assert entry.row.ended_by == "sigkill"
    assert entry.row.t_gone - entry.row.t_kill >= 0.7


def test_stop_takes_its_processes_from_the_rows(node, monkeypatch):
    """A live process that ``_workers`` no longer knows of is still
    ``stop``'s to end."""
    cluster, raylet = node
    proc = StandIn(heeds_term=True, gone_after_s=0.2)
    entry = _spawn(cluster, raylet, proc, monkeypatch)
    raylet._workers.pop(entry.worker_id)
    cluster.shutdown()
    assert proc.poll() == -signal.SIGTERM and entry.row.ended_by == "sigterm"


# (c) ---- a real cluster ------------------------------------------------------

def test_a_real_cluster_leaves_three_closed_rows_and_says_nothing(capsys):
    from ray_tpu.util import lifecycle

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=2, _system_config={"worker_adopt_for_actors": False})
    try:
        @ray_tpu.remote
        def together(seconds):
            time.sleep(seconds)
            return 1

        @ray_tpu.remote(num_cpus=0)
        class Counter:
            def __init__(self):
                time.sleep(0.05)

            def one(self):
                return 1

        pair = [together.remote(1.0) for _ in range(2)]  # two task workers
        actor = Counter.remote()
        assert ray_tpu.get(pair) == [1, 1] and ray_tpu.get(actor.one.remote()) == 1
        live = lifecycle.processes()
        assert len(live) == 3 and all(r["t_gone"] is None for r in live)
    finally:
        get_config().worker_adopt_for_actors = True
        ray_tpu.shutdown()
    rec = lifecycle.last_shutdown()
    assert rec["procs_spawned"] == 3 and rec["procs_alive_at_return"] == 0
    assert rec["procs_killed"] == 0 and rec["line"] is None
    assert "rt-shutdown" not in capsys.readouterr().err
    for row in rec["rows"]:
        assert row["t_spawn"] <= row["t_main"] <= row["t_ready"] <= row["t_gone"]
        assert row["ended_by"] in ("exit_rpc", "sigterm") and row["pid"] > 0
    kinds = sorted(r["kind"] for r in rec["rows"])
    assert kinds == ["actor", "task", "task"]
    actor_row = next(r for r in rec["rows"] if r["kind"] == "actor")
    assert actor_row["label"] == "Counter" and actor_row["cause"]
    assert actor_row["t_asked"] <= actor_row["t_spawn"]
    assert actor_row["t_ready"] <= actor_row["t_actor_init0"]
    assert actor_row["t_actor_init1"] - actor_row["t_actor_init0"] >= 0.05
    spans = {s["name"]: s for s in lifecycle.spans()}
    for child in ("gcs_start", "raylet_start", "driver_connect"):
        assert spans[child]["parent"] == "init"
        assert spans["init"]["t0"] <= spans[child]["t0"] \
            and spans[child]["t1"] <= spans["init"]["t1"] + 1e-3
    for child in ("raylet_stop", "gcs_stop", "io_stop", "backend_disconnect"):
        assert spans[child]["parent"] == "shutdown"
    assert lifecycle.overhead_s() < 0.1


# (d) ---- serve.run's children ------------------------------------------------

def test_serve_run_is_tiled_by_its_children():
    from ray_tpu import serve
    from ray_tpu.util import lifecycle

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    try:
        @serve.deployment
        class Hello:
            def __init__(self):
                time.sleep(0.2)

            def __call__(self, request):
                return "hello"

        handle = serve.run(Hello.bind(), name="tiled", route_prefix="/tiled",
                           http_options=serve.HTTPOptions(port=0))
        assert handle.remote(None).result(timeout=30) == "hello"
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    whole = lifecycle.last("serve_run")
    kids = sorted((s for s in lifecycle.spans() if s["parent"] == "serve_run"),
                  key=lambda s: s["t0"])
    assert [s["name"] for s in kids] == [
        "controller_start", "proxy_start", "replica_start", "healthy_wait"]
    # the children cover the parent: what no child names is under 50 ms
    reached, uncovered = whole["t0"], 0.0
    for s in kids:
        assert whole["t0"] - 1e-3 <= s["t0"] and s["t1"] <= whole["t1"] + 1e-3
        uncovered += max(0.0, s["t0"] - reached)
        reached = max(reached, s["t1"])
    uncovered += max(0.0, whole["t1"] - reached)
    assert uncovered < 0.05, (uncovered, kids)
    by_name = {s["name"]: s for s in kids}
    wait = by_name["healthy_wait"]
    assert wait["t1"] - wait["t0"] >= 0.0 and wait["t1"] == whole["t1"]
    start = by_name["replica_start"]
    assert start["t1"] == wait["t0"] and start["t1"] - start["t0"] >= 0.2
    assert start["pid"] != whole["pid"]  # the replica's process
    down = {s["name"]: s for s in lifecycle.last_shutdown()["spans"]}
    for child in ("replicas_stop", "proxies_stop", "controller_stop"):
        assert down[child]["parent"] == "serve_shutdown"
        assert down["serve_shutdown"]["t0"] - 1e-3 <= down[child]["t0"]
    assert down["replicas_stop"]["pid"] != down["serve_shutdown"]["pid"]


# ---- the record itself ---------------------------------------------------------

class _Rc:
    pid = 7

    def __init__(self, rc):
        self.rc = rc

    def poll(self):
        return self.rc


@pytest.mark.parametrize("stamps, rc, ended_by", [
    (("t_exit_asked",), 0, "exit_rpc"),
    (("t_term",), -signal.SIGTERM, "sigterm"),
    (("t_exit_asked", "t_term"), -signal.SIGTERM, "sigterm"),
    (("t_term", "t_kill"), -signal.SIGKILL, "sigkill"),
    (("t_exit_asked", "t_term", "t_kill"), 0, "exit_rpc"),
    ((), 0, "orphan_watch"),
    ((), 1, "crash"),
    ((), -signal.SIGSEGV, "crash"),
])
def test_how_a_row_says_it_ended(stamps, rc, ended_by):
    from ray_tpu.util import lifecycle

    row = lifecycle.ProcRow(_Rc(None), "w")
    assert row.alive and row.t_gone is None
    for s in stamps:
        setattr(row, s, time.time())
    row.proc.rc = rc
    assert row.poll() == rc and row.ended_by == ended_by and row.exit == rc
    first = row.t_gone
    assert row.poll() == rc and row.t_gone == first  # the first poll closes it


def test_gone_rows_are_bounded_and_live_ones_are_never_dropped(monkeypatch):
    from ray_tpu.util import lifecycle

    monkeypatch.setattr(lifecycle, "_rows", [])
    monkeypatch.setattr(lifecycle, "ROWS_CAP", 8)
    live = [lifecycle.add_row(lifecycle.ProcRow(_Rc(None), f"live{i}",
                                                session="s")) for i in range(6)]
    for i in range(20):
        row = lifecycle.add_row(lifecycle.ProcRow(_Rc(0), f"gone{i}",
                                                  session="s"))
        row.poll()
    kept = lifecycle.rows(session="s")
    assert len(kept) <= 9 and all(r in kept for r in live)
    assert [r.worker_id for r in kept if r.t_gone][-1] == "gone19"
    assert lifecycle.not_gone(session="s") == live
    for i in range(10):  # more alive than the cap: all of them stay
        live.append(lifecycle.add_row(lifecycle.ProcRow(_Rc(None), f"more{i}",
                                                        session="s")))
    assert lifecycle.not_gone(session="s") == live
