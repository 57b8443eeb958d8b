"""Cluster-backend tests: real worker processes, shm object plane, GCS.

Covers the reference's core distributed semantics (``test_basic.py`` /
``test_actor.py`` analogs) against the multiprocess runtime.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError, TaskError, WorkerCrashedError


def test_cluster_task_roundtrip(rt_cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(1, 2)) == 3


def test_cluster_large_object_via_plasma(rt_cluster):
    @ray_tpu.remote
    def make_array(n):
        return np.arange(n, dtype=np.float64)

    ref = make_array.remote(500_000)  # ~4 MB -> plasma path
    arr = ray_tpu.get(ref)
    assert arr.shape == (500_000,)
    assert arr[-1] == 499_999.0


def test_cluster_large_arg_promoted(rt_cluster):
    big = np.ones(300_000, dtype=np.float64)

    @ray_tpu.remote
    def total(x):
        return float(x.sum())

    assert ray_tpu.get(total.remote(big)) == 300_000.0


def test_cluster_ref_passing_between_tasks(rt_cluster):
    @ray_tpu.remote
    def produce():
        return np.ones(200_000)  # plasma

    @ray_tpu.remote
    def consume(x):
        return float(x.sum())

    assert ray_tpu.get(consume.remote(produce.remote())) == 200_000.0


def test_cluster_put_get(rt_cluster):
    small = ray_tpu.put({"k": 1})
    big = ray_tpu.put(np.zeros(300_000))
    assert ray_tpu.get(small) == {"k": 1}
    assert ray_tpu.get(big).shape == (300_000,)


def test_cluster_error_propagation(rt_cluster):
    @ray_tpu.remote
    def boom():
        raise RuntimeError("cluster boom")

    with pytest.raises(TaskError, match="cluster boom"):
        ray_tpu.get(boom.remote())


def test_cluster_nested_tasks_no_deadlock(rt_cluster):
    @ray_tpu.remote
    def inner(x):
        return x * 2

    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(inner.remote(x)) + 1

    assert ray_tpu.get(outer.remote(10)) == 21


def test_cluster_actor_basic(rt_cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

    c = Counter.remote(100)
    assert ray_tpu.get(c.inc.remote()) == 101
    assert ray_tpu.get(c.inc.remote(9)) == 110


def test_cluster_actor_ordering(rt_cluster):
    @ray_tpu.remote
    class Log:
        def __init__(self):
            self.items = []

        def append(self, x):
            self.items.append(x)

        def get_items(self):
            return self.items

    log = Log.remote()
    for i in range(30):
        log.append.remote(i)
    assert ray_tpu.get(log.get_items.remote()) == list(range(30))


def test_cluster_named_actor(rt_cluster):
    @ray_tpu.remote
    class Svc:
        def ping(self):
            return "pong"

    Svc.options(name="svc2").remote()
    h = ray_tpu.get_actor("svc2")
    assert ray_tpu.get(h.ping.remote()) == "pong"


def test_cluster_actor_handle_in_task(rt_cluster):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def inc(self):
            self.n += 1
            return self.n

    @ray_tpu.remote
    def bump(c):
        return ray_tpu.get(c.inc.remote())

    c = Counter.remote()
    assert ray_tpu.get(bump.remote(c)) == 1


def test_cluster_kill_actor(rt_cluster):
    @ray_tpu.remote
    class A:
        def m(self):
            return 1

    a = A.remote()
    assert ray_tpu.get(a.m.remote()) == 1
    ray_tpu.kill(a)
    with pytest.raises(ActorDiedError):
        ray_tpu.get(a.m.remote())


def test_cluster_actor_restart(rt_cluster):
    @ray_tpu.remote(max_restarts=1)
    class Flaky:
        def __init__(self):
            self.n = 0

        def crash(self):
            import os

            os._exit(1)

        def value(self):
            self.n += 1
            return self.n

    f = Flaky.remote()
    assert ray_tpu.get(f.value.remote()) == 1
    f.crash.remote()
    time.sleep(2.0)  # restart backoff + respawn
    # State is reset after restart (fresh __init__).
    assert ray_tpu.get(f.value.remote(), timeout=30) == 1


def test_cluster_wait(rt_cluster):
    @ray_tpu.remote
    def sleepy(t):
        time.sleep(t)
        return t

    fast = sleepy.remote(0.05)
    slow = sleepy.remote(10)
    ready, not_ready = ray_tpu.wait([fast, slow], num_returns=1, timeout=5)
    assert ready == [fast]
    assert not_ready == [slow]


def test_cluster_resources_visible(rt_cluster):
    total = ray_tpu.cluster_resources()
    assert total["CPU"] == 4
    assert total["TPU"] == 4


def test_cluster_tpu_task_gets_visible_chips(rt_cluster):
    @ray_tpu.remote(num_tpus=2)
    def which_chips():
        return ray_tpu.get_runtime_context().get_tpu_ids()

    chips = ray_tpu.get(which_chips.remote())
    assert len(chips) == 2
    assert set(chips) <= {0, 1, 2, 3}


def _device_env_fn():
    """A function (nested, so it travels to the worker by value) that
    returns the worker's pid and what its environment says of devices."""

    def device_env():
        import os

        keys = ("JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
                "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
                "JAX_COMPILATION_CACHE_DIR")
        return os.getpid(), {k: os.environ[k] for k in keys
                             if k in os.environ}

    return device_env


def test_cluster_worker_without_chips_cannot_open_one(rt_cluster, monkeypatch):
    """A chip belongs to one process at a time, so a worker that was granted
    none is held to the CPU platform whatever the driver's environment says:
    the first data worker, controller or proxy to touch JAX must not take the
    device from the worker that was granted it."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")

    device_env = _device_env_fn()

    @ray_tpu.remote(runtime_env={"env_vars": {"FRESH_WORKER": "1"}})
    def plain():
        import jax

        return device_env()[1], jax.devices()[0].platform

    env, platform = ray_tpu.get(plain.remote())
    assert env["JAX_PLATFORMS"] == "cpu" and platform == "cpu"
    assert "TPU_VISIBLE_CHIPS" not in env


def test_cluster_tpu_worker_gets_its_chips_and_no_others(rt_cluster,
                                                         monkeypatch):
    """A subset of the host's chips comes with the bounds libtpu needs to
    open it as a one-host slice of its own; the whole host keeps the
    machine's bounds. Either way the worker shares the run's one compile
    cache, at the fixed in-checkout path unless the environment names one."""
    from ray_tpu.util import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    one = ray_tpu.remote(num_tpus=1)(_device_env_fn())
    whole = ray_tpu.remote(num_tpus=4)(_device_env_fn())
    _, env = ray_tpu.get(one.remote())
    assert env["TPU_VISIBLE_CHIPS"] in {"0", "1", "2", "3"}
    assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert env["TPU_HOST_BOUNDS"] == "1,1,1"
    assert env["JAX_COMPILATION_CACHE_DIR"] == compile_cache.DEFAULT_DIR
    _, env = ray_tpu.get(whole.remote())
    assert env["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
    assert "TPU_CHIPS_PER_HOST_BOUNDS" not in env


def test_cluster_chip_changes_hands_only_when_its_holder_is_gone(rt_cluster):
    """The pool's accounting frees a chip as soon as its task returns, while
    the task's worker idles in the pool with its backend up. Before another
    process is given that chip the idle holder is retired, and waited for."""
    import os

    pid, _ = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(_device_env_fn()).remote())

    @ray_tpu.remote(num_tpus=4)
    class Gang:
        def holder_alive(self, pid):
            try:
                os.kill(pid, 0)
            except ProcessLookupError:
                return False
            # a zombie is gone for this purpose: it holds no device
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0] != "Z"

    gang = Gang.remote()
    assert ray_tpu.get(gang.holder_alive.remote(pid)) is False


def test_worker_chip_env_unit():
    from ray_tpu._private.accelerator import worker_chip_env

    assert worker_chip_env([], 4, {"JAX_PLATFORMS": "tpu,cpu",
                                   "TPU_VISIBLE_CHIPS": "0"}) == {
        "JAX_PLATFORMS": "cpu"}
    assert worker_chip_env([2], 4, {}) == {
        "TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1"}
    assert worker_chip_env([0], 1, {}) == {"TPU_VISIBLE_CHIPS": "0"}
    assert worker_chip_env([0, 1, 2, 3], 4, {"X": "y"}) == {
        "X": "y", "TPU_VISIBLE_CHIPS": "0,1,2,3"}


def test_autodetect_counts_the_device_nodes_a_v5e_host_has(monkeypatch):
    """A v5e host shows its chips as /dev/vfio/<n> (one chip: /dev/vfio/3
    alone, as the chip tool's one-chip machine does); older generations as
    /dev/accel<n>. RT_NUM_TPUS overrides."""
    from ray_tpu._private import accelerator

    nodes = {"/dev/accel*": [], "/dev/vfio/[0-9]*": ["/dev/vfio/3"]}
    monkeypatch.delenv("RT_NUM_TPUS", raising=False)
    monkeypatch.setattr(accelerator.glob, "glob", lambda pat: nodes[pat])
    assert accelerator.autodetect_num_tpu_chips() == 1
    nodes["/dev/vfio/[0-9]*"] = [f"/dev/vfio/{i}" for i in range(4)]
    assert accelerator.autodetect_num_tpu_chips() == 4
    nodes["/dev/vfio/[0-9]*"] = []
    assert accelerator.autodetect_num_tpu_chips() == 0
    monkeypatch.setenv("RT_NUM_TPUS", "2")
    assert accelerator.autodetect_num_tpu_chips() == 2


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set it is left alone and no other
    path is set; otherwise the cache goes to one fixed place in the checkout
    (a path that moves never hits: it is part of the cache's key)."""
    import os

    from ray_tpu.util import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.configure() == os.path.join(repo, ".jax_cache")
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == compile_cache.DEFAULT_DIR
    for moving in ("/tmp", str(os.getpid())):
        assert moving not in compile_cache.DEFAULT_DIR


def test_cluster_worker_reuse(rt_cluster):
    @ray_tpu.remote
    def my_pid():
        import os

        return os.getpid()

    pid1 = ray_tpu.get(my_pid.remote())
    pid2 = ray_tpu.get(my_pid.remote())
    assert pid1 == pid2  # idle worker was reused


def test_cluster_parallel_tasks_distinct_workers(rt_cluster):
    @ray_tpu.remote
    def slow_pid():
        import os
        import time as t

        t.sleep(0.4)
        return os.getpid()

    pids = ray_tpu.get([slow_pid.remote() for _ in range(3)])
    assert len(set(pids)) == 3


def test_node_resurrects_after_spurious_death(rt_cluster):
    """A heartbeat from a node marked dead (e.g. the shared event loop
    stalled past node_death_timeout_s on a loaded host) must resurrect it —
    otherwise every later actor/task placement wedges in PENDING_CREATION
    (pick_node skips dead nodes forever). Reference contrast:
    gcs_node_manager.cc kills the raylet and it re-registers; an in-proc
    raylet can't restart, so the GCS revives it in place."""
    import asyncio

    from ray_tpu.core.worker import global_worker

    @ray_tpu.remote
    class A:
        def m(self):
            return 1

    a0 = A.remote()
    assert ray_tpu.get(a0.m.remote()) == 1

    backend = global_worker().backend
    gcs = backend._cluster.gcs

    async def kill_nodes():
        for e in list(gcs.nodes.values()):
            await gcs._mark_node_dead(e, "simulated heartbeat timeout")

    asyncio.run_coroutine_threadsafe(kill_nodes(), backend.io.loop).result(10)
    time.sleep(2.5)  # a couple of live heartbeats arrive and resurrect

    a = A.remote()
    assert ray_tpu.get(a.m.remote(), timeout=20) == 1


def test_worker_logs_stream_to_driver(rt_cluster, capfd):
    """Worker prints are echoed to the driver's stderr with a worker prefix
    (reference: _private/log_monitor.py + worker.print_logs)."""
    @ray_tpu.remote
    def noisy():
        print("log-line-for-driver")
        return 1

    assert ray_tpu.get(noisy.remote()) == 1
    deadline = time.time() + 10
    seen = ""
    while time.time() < deadline:
        seen += capfd.readouterr().err
        if "log-line-for-driver" in seen and "(worker " in seen:
            return
        time.sleep(0.3)
    raise AssertionError(f"worker log never reached driver: {seen[-500:]}")


def test_actor_concurrency_groups(rt_cluster):
    """Named concurrency groups isolate method pools (reference:
    ConcurrencyGroupManager): a saturated compute group must not block io
    methods, while same-group calls still queue behind each other."""
    @ray_tpu.remote(concurrency_groups={"io": 2, "compute": 1})
    class Worker:
        @ray_tpu.method(concurrency_group="compute")
        def crunch(self):
            time.sleep(1.5)
            return "crunched"

        @ray_tpu.method(concurrency_group="io")
        def ping(self):
            return "pong"

    w = Worker.remote()
    slow = w.crunch.remote()
    time.sleep(0.2)  # let crunch occupy its group's single consumer
    t0 = time.time()
    assert ray_tpu.get(w.ping.remote(), timeout=10) == "pong"
    io_latency = time.time() - t0
    assert io_latency < 1.0, f"io method starved: {io_latency:.2f}s"
    assert ray_tpu.get(slow, timeout=10) == "crunched"


def test_actor_concurrency_group_validation(rt_cluster):
    """Undeclared group names error loudly; zero-size groups are rejected at
    creation (a 0-consumer queue would hang its callers forever)."""
    @ray_tpu.remote(concurrency_groups={"io": 1})
    class A:
        @ray_tpu.method(concurrency_group="oi")  # typo
        def m(self):
            return 1

    a = A.remote()
    with pytest.raises(Exception, match="concurrency group"):
        ray_tpu.get(a.m.remote(), timeout=20)

    @ray_tpu.remote(concurrency_groups={"bad": 0})
    class B:
        def m(self):
            return 1

    b = B.remote()
    with pytest.raises(Exception, match="positive int"):
        ray_tpu.get(b.m.remote(), timeout=30)


def test_idle_workers_reaped_beyond_soft_limit(rt_cluster):
    """Pooled workers beyond the soft limit that sit idle past the TTL
    are retired (reference: raylet idle-worker killing) — env-cycling
    jobs must not accumulate processes forever."""
    import time as _time

    from ray_tpu._private import config as config_mod
    from ray_tpu._private.config import get_config

    get_config().num_workers_soft_limit = 1
    get_config().idle_worker_ttl_s = 1.0
    try:
        # distinct runtime envs -> distinct pool keys -> distinct workers
        @ray_tpu.remote
        def pid():
            import os

            return os.getpid()

        pids = set()
        for i in range(3):
            ref = pid.options(
                runtime_env={"env_vars": {"POOL_KEY": str(i)}}).remote()
            pids.add(ray_tpu.get(ref))
        assert len(pids) == 3  # three live pooled workers

        import psutil

        deadline = _time.time() + 15
        while _time.time() < deadline:
            alive = [p for p in pids if psutil.pid_exists(p)]
            if len(alive) <= 1:
                break
            _time.sleep(0.5)
        assert len(alive) <= 1, f"idle workers not reaped: {alive}"

        # the pool still works after reaping
        assert isinstance(ray_tpu.get(pid.remote()), int)
    finally:
        config_mod.reset_config_for_tests()
