"""Shared fixtures. Platform scrubbing happens in the repo-root conftest."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import faulthandler  # noqa: E402

import pytest  # noqa: E402


def _dump_io_tasks(reason: str) -> None:
    """Print the driver io-loop's asyncio task stacks to stderr — OS-thread
    dumps (faulthandler) show loops idle in select(); the wedge lives in
    task await graphs."""
    import asyncio
    import traceback

    try:
        from ray_tpu.core.worker import global_worker

        backend = global_worker().backend
        if backend is None:
            return
        loops = {"driver": backend.io.loop}
        cluster = getattr(backend, "_cluster", None)
        if cluster is not None and getattr(cluster, "io", None) is not None:
            loops["cluster(gcs+raylet)"] = cluster.io.loop

        def dump(tag, loop):
            def _go():
                print(f"\n===== {tag} asyncio tasks ({reason}) =====",
                      file=sys.stderr)
                for t in asyncio.all_tasks(loop):
                    print(f"-- {t!r}", file=sys.stderr)
                    for fr in t.get_stack():
                        traceback.print_stack(fr, limit=1, file=sys.stderr)
                sys.stderr.flush()
            return _go

        for tag, loop in loops.items():
            loop.call_soon_threadsafe(dump(tag, loop))
        import time as _t

        _t.sleep(1.0)
    except Exception as e:  # noqa: BLE001 — diagnostics must not raise
        print(f"io task dump failed: {e}", file=sys.stderr)


# ---- session leak guard ----------------------------------------------------
# The chaos-smoke lesson (PR 7/9): a test that leaks a node daemon poisons
# every LATER pytest run on the machine — silently. Fail THIS run loudly
# instead: at session start record the already-running node daemons; at
# session finish, any new daemon still alive (or any non-daemon thread a
# test left running) flips the exit status and names the culprit. Leaked
# daemons are then killed so the next run starts clean. Under xdist a
# worker's guard sees only what its own worker started (``_started_here``):
# a worker that runs out of work does not reap the head node of a script
# another worker is still running (seen, PR 29).
# RT_LEAK_GUARD=0 disables; RT_LEAK_GUARD_KILL=0 reports without reaping.

# what xdist sets in a worker and every process the worker starts inherits:
# the run's id and the worker's own
_XDIST_TAGS = ("PYTEST_XDIST_TESTRUNUID", "PYTEST_XDIST_WORKER")

def _is_node_daemon(pid):
    """cmdline-verified: never trust a bare PID (a stale state file's pid
    can be recycled by the OS for an innocent process mid-session)."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return b"ray_tpu.cluster.node_main" in f.read()
    except OSError:
        return False


def _started_here(pid, environ=None):
    """Did this session's process, or one it started, start ``pid``: its
    environment carries this xdist worker's tags (a daemon detaches from the
    process tree, its environment stays). Without xdist there is no other
    worker to spare and every daemon counts, as it always did; one whose
    environment cannot be read is nobody's to reap."""
    mine = {k: (os.environ if environ is None else environ).get(k)
            for k in _XDIST_TAGS}
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            its = dict(kv.split("=", 1) for kv in
                       f.read().decode(errors="replace").split("\0")
                       if "=" in kv)
    except OSError:
        return False
    return all(its.get(k) == v for k, v in mine.items())


def _node_daemon_pids():
    """PIDs verifiably running ray_tpu.cluster.node_main that this session
    may answer for (``_started_here``): the /proc scan (Linux),
    cross-checked with the state-dir records — every candidate must pass
    the cmdline check before it can be reported or reaped."""
    pids = set()
    try:
        for name in os.listdir("/proc"):
            if name.isdigit() and _is_node_daemon(int(name)):
                pids.add(int(name))
    except OSError:
        pass
    try:
        from ray_tpu.cluster import node_main

        for fn in os.listdir(node_main.state_dir()):
            try:
                import json

                with open(os.path.join(node_main.state_dir(), fn)) as f:
                    pid = json.load(f)["pid"]
                if _is_node_daemon(pid):
                    pids.add(pid)
            except (OSError, ValueError, KeyError):
                continue
    except Exception:  # noqa: BLE001 — guard must never break collection
        pass
    return {pid for pid in pids if _started_here(pid)}


def _leaked_threads(baseline=()):
    """Non-daemon threads a test left behind: everything except the main
    thread, executor workers (ThreadPoolExecutor joins them at
    interpreter exit — they are parked, not leaked), and threads that
    were already alive before the session started (an embedding host
    app's workers are not ours to report)."""
    import threading

    out = []
    for t in threading.enumerate():
        if t is threading.main_thread() or t.daemon or not t.is_alive():
            continue
        if any(t is b for b in baseline):
            continue
        target_mod = getattr(getattr(t, "_target", None), "__module__", "")
        if target_mod.startswith("concurrent.futures"):
            continue
        out.append(t)
    return out


def pytest_sessionstart(session):
    if os.environ.get("RT_LEAK_GUARD", "1") == "0":
        return
    import threading

    session.config._rt_preexisting_daemons = _node_daemon_pids()
    # Thread OBJECTS, not idents: the OS recycles idents, so a leaked
    # thread could silently alias a dead baseline thread's ident
    session.config._rt_preexisting_threads = list(threading.enumerate())


def pytest_sessionfinish(session, exitstatus):
    if os.environ.get("RT_LEAK_GUARD", "1") == "0":
        return
    import time

    baseline = getattr(session.config, "_rt_preexisting_daemons", None)
    if baseline is None:
        return
    thread_baseline = getattr(session.config,
                              "_rt_preexisting_threads", set())
    # wind-down grace: teardowns signal daemons/threads asynchronously
    leaked_pids, leaked_thr = set(), []
    for _ in range(8):
        leaked_pids = _node_daemon_pids() - baseline
        leaked_thr = _leaked_threads(thread_baseline)
        if not leaked_pids and not leaked_thr:
            return
        time.sleep(0.25)
    print("\n===== RT LEAK GUARD: this run leaked =====", file=sys.stderr)
    for pid in sorted(leaked_pids):
        print(f"  node daemon pid={pid} (ray_tpu.cluster.node_main) still "
              f"alive — it would silently wedge every later pytest run",
              file=sys.stderr)
    for t in leaked_thr:
        print(f"  non-daemon thread {t.name!r} still alive (target="
              f"{getattr(t, '_target', None)!r})", file=sys.stderr)
    if leaked_pids and os.environ.get("RT_LEAK_GUARD_KILL", "1") != "0":
        import signal as _signal

        for pid in leaked_pids:
            try:
                if _is_node_daemon(pid):  # re-verify at kill time
                    os.kill(pid, _signal.SIGKILL)
                    print(f"  reaped pid={pid}", file=sys.stderr)
            except OSError:
                pass
    print("==========================================", file=sys.stderr)
    session.exitstatus = 1


#: seconds a test may take, its function fixtures' set-up and teardown
#: included (a module fixture's set-up is not: it is built before this one)
TEST_DEADLINE_S = 300


@pytest.fixture(autouse=True)
def _hang_watchdog(request):
    """A test past its deadline FAILS ALONE: at ``TEST_DEADLINE_S`` (or the
    seconds an indirect parameter names) a ``SIGALRM`` handler writes every
    thread's stack to the process's own stderr, dumps the io-loop's asyncio
    task stacks (the only place an await-graph deadlock is visible) and
    raises ``pytest.fail`` in the test; the worker and every test it still
    holds go on. A main thread held inside a C call takes the signal when
    the call returns: ten seconds on, faulthandler's timer writes the stacks
    from its own thread, and ends nothing."""
    import signal

    limit = getattr(request, "param", TEST_DEADLINE_S)
    name = request.node.name

    def past_deadline(signum, frame):
        faulthandler.dump_traceback(file=sys.__stderr__)
        _dump_io_tasks(f"test {name} exceeded {limit}s")
        pytest.fail(f"{name} exceeded its deadline of {limit} s "
                    f"(tests/conftest.py:_hang_watchdog)")

    faulthandler.dump_traceback_later(limit + 10, exit=False,
                                      file=sys.__stderr__)
    was = signal.signal(signal.SIGALRM, past_deadline)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)
        faulthandler.cancel_dump_traceback_later()


@pytest.fixture(scope="session", autouse=True)
def _serve_port_per_xdist_worker():
    """Most serve tests start the HTTP proxy on ``HTTPOptions``' default
    port. xdist runs files side by side, so two such files that happen to
    overlap fight over the one port (seen: 16 tests refused with EADDRINUSE
    after a new test file shifted the schedule). Each worker gets a default
    port of its own; tests ask ``serve.http_port()`` and name none."""
    worker = os.environ.get("PYTEST_XDIST_WORKER", "")
    if not worker.startswith("gw"):
        yield
        return
    from ray_tpu.serve import config

    init = config.HTTPOptions.__init__
    shared, mine = init.__defaults__, \
        config.DEFAULT_HTTP_PORT + 1 + int(worker[2:])
    init.__defaults__ = tuple(mine if d == config.DEFAULT_HTTP_PORT else d
                              for d in shared)
    yield
    init.__defaults__ = shared


@pytest.fixture
def rt_local():
    """A fresh in-process runtime per test."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(local_mode=True, num_cpus=4, num_tpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def rt_cluster():
    """A fresh single-node multiprocess cluster per test."""
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=4)
    yield ray_tpu
    ray_tpu.shutdown()
