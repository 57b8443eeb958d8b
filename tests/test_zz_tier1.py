"""What keeps one slow test from costing others theirs (PR 61): a compiled
fixture is built in a set-up and never inside a test's call, a test past its
deadline fails alone, and the session's leak guard reaps only what its own
worker started. And the one seat that decides the test process's compiler
(PR 64): eight CPU devices, compiled the cheap way."""

import importlib.util
import os
import signal
import subprocess
import sys
import threading
import time
import types

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def guard():
    """``tests/conftest.py`` as a plain module (not registered again)."""
    return _load(os.path.join(_ROOT, "tests", "conftest.py"), "_rt_guard")


def test_the_test_process_compiles_for_eight_cpu_devices_the_cheap_way(
        monkeypatch):
    """``rt_test_platform.py`` is the seat: this process (an xdist worker
    inherits the re-exec's environment) has the device count and the CPU
    compiler's two cheap-mode flags in ``XLA_FLAGS`` once each, a process
    that has the platform and the count but not the flags is sent round
    again with what it lacks and nothing twice, and a run on the real chip
    (``RT_TESTS_KEEP_PLATFORM=1``) is left as it came."""
    import rt_test_platform as seat

    if os.environ.get("RT_TESTS_KEEP_PLATFORM"):
        pytest.skip("a run on the real chip: the seat left it as it came")
    flags = os.environ["XLA_FLAGS"].split()
    for flag in seat._CPU_XLA_FLAGS:
        assert flags.count(flag) == 1, (flag, flags)
    assert os.environ["JAX_NUM_CPU_DEVICES"] == "8"
    import jax

    assert jax.device_count() == 8 and jax.default_backend() == "cpu"

    sent = []
    monkeypatch.setattr(os, "execve", lambda *call: sent.append(call))
    seat._reexec_on_cpu()  # this process has all of it: let through
    assert not sent
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        [f for f in flags if f != seat._CPU_XLA_FLAGS[-1]] + ["--mine=1"]))
    seat._reexec_on_cpu()
    (_, argv, env), = sent
    assert argv[:3] == [sys.executable, "-m", "pytest"]
    assert sorted(env["XLA_FLAGS"].split()) == sorted(flags + ["--mine=1"])
    assert env["JAX_PLATFORMS"] == "cpu"

    del sent[:]
    for name in ("XLA_FLAGS", "JAX_PLATFORMS", "JAX_NUM_CPU_DEVICES"):
        monkeypatch.delenv(name)
    monkeypatch.setenv("RT_TESTS_KEEP_PLATFORM", "1")
    before = dict(os.environ)
    seat._reexec_on_cpu()
    assert not sent and dict(os.environ) == before


def test_no_test_builds_a_module_fixture_inside_its_call():
    """A compiled fixture (a cell's whole step for the described chip takes
    100-230 s of a loaded worker) is named as an ARGUMENT of the tests that
    read it, so that it is built in a set-up, outside the 300 s a test's
    call may take and once for all of them; ``request.getfixturevalue``
    inside a call built ``kimi_step`` under the deadline of whichever case
    came first on a worker (PR 61: the tables of cases that fetched their
    fixture by name became one file a compiled step,
    ``tests/test_aot_step_*.py`` over ``tests/_aot.py``)."""
    here = os.path.join(_ROOT, "tests")
    found = []
    for folder, _, names in os.walk(here):
        for name in names:
            path = os.path.join(folder, name)
            if name.endswith(".py") and path != os.path.abspath(__file__):
                with open(path) as f:
                    if "getfixturevalue" in f.read():
                        found.append(os.path.relpath(path, _ROOT))
    assert not found, found


@pytest.mark.parametrize("_hang_watchdog", [2], indirect=True)
def test_a_test_past_its_deadline_fails_alone(_hang_watchdog):
    """The watchdog armed at 2 s through the fixture's own parameter:
    ``pytest.fail`` is raised IN the test that overslept, and the process
    (an xdist worker, with every test it still holds) lives on."""
    handler = signal.getsignal(signal.SIGALRM)
    assert callable(handler)
    t0 = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"exceeded its deadline of 2 s"):
        time.sleep(30)
    assert 1.9 <= time.monotonic() - t0 < 15
    # one shot: nothing is left armed, and the fixture's teardown hands the
    # signal back to whoever had it
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)
    assert signal.getsignal(signal.SIGALRM) is handler


def test_the_watchdog_is_disarmed_between_tests(guard):
    """...and the next test starts with a whole deadline of its own."""
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert guard.TEST_DEADLINE_S - 5 < left <= guard.TEST_DEADLINE_S


def _fake_daemon(env):
    """A process whose command line passes the guard's check."""
    return subprocess.Popen(
        [sys.executable, "-c", "import time; time.sleep(120)",
         "ray_tpu.cluster.node_main"], env=env, start_new_session=True)


def test_the_leak_guard_leaves_a_daemon_another_session_started(guard):
    """Two daemons newer than the session: one with this worker's
    environment, one that another run's worker ``gw7`` started. The guard
    answers for the first alone, and its reaping leaves the second running
    (it was the head node of a script another worker was still in, PR 29)."""
    other_env = dict(os.environ, PYTEST_XDIST_TESTRUNUID="another-run",
                     PYTEST_XDIST_WORKER="gw7")
    mine, other = _fake_daemon(dict(os.environ)), _fake_daemon(other_env)
    try:
        deadline = time.monotonic() + 10
        while not (guard._is_node_daemon(mine.pid)
                   and guard._is_node_daemon(other.pid)):
            assert time.monotonic() < deadline
            time.sleep(0.05)
        assert guard._started_here(mine.pid)
        assert not guard._started_here(other.pid)
        # the same worker id in another run is another session too
        assert not guard._started_here(mine.pid, {
            **os.environ, "PYTEST_XDIST_TESTRUNUID": "another-run"})
        seen = guard._node_daemon_pids()
        assert mine.pid in seen and other.pid not in seen
        session = types.SimpleNamespace(
            exitstatus=0, config=types.SimpleNamespace(
                _rt_preexisting_daemons=seen - {mine.pid},
                _rt_preexisting_threads=list(threading.enumerate())))
        guard.pytest_sessionfinish(session, 0)
        assert session.exitstatus == 1
        assert mine.wait(timeout=10) == -signal.SIGKILL
        assert other.poll() is None and guard._is_node_daemon(other.pid)
    finally:
        for p in (mine, other):
            p.kill()
            p.wait()
