"""Chaos plane: deterministic fault injection (util/chaos.py), hardened
recovery paths (reconnect backoff, degraded raylet, restart damping), and
the gang leg — every scenario ASSERTS recovery on the PR 5 failure plane
(categorized `rt errors` rows, retry/restart/reconstruction counters,
`rt doctor` exit codes), not on sleeps/markers alone.

Reference analogs: Ray's ``NodeKiller`` chaos injectors
(``_private/test_utils.py:1401``) and the lineage fault-tolerance story of
Moritz et al. (arXiv 1712.05889). Named ``test_zz_*`` so it sorts late.
"""

import os
import signal
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import failure as F
from ray_tpu.util import chaos as C


@pytest.fixture(autouse=True)
def _disarmed():
    """Chaos state is process-global: every test starts and ends disarmed."""
    C.disarm()
    yield
    C.disarm()
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def _backend():
    return ray_tpu.global_worker()._require_backend()


def _counter(name, tags=None):
    from ray_tpu.util import metrics as M

    for m in M._registry.snapshot():
        if m["name"] == name and m["type"] == "counter":
            return sum(v for labels, v in m["samples"]
                       if tags is None or all(labels.get(k) == tv
                                              for k, tv in tags.items()))
    return 0.0


def _events(backend, timeout_s=10.0, want=1, **payload):
    payload.setdefault("limit", 500)
    deadline = time.monotonic() + timeout_s
    events = []
    while time.monotonic() < deadline:
        events = backend.io.run(
            backend._gcs.call("list_failure_events", dict(payload)))
        if len(events) >= want:
            break
        time.sleep(0.2)
    return events


# ---- the plan itself (pure) -------------------------------------------------

def test_chaos_plan_validation():
    with pytest.raises(ValueError):
        C.ChaosPlan(0, [{"site": "no.such.site"}])
    with pytest.raises(ValueError):
        C.ChaosPlan(0, [{"site": "worker.kill", "tpyo": 1}])
    with pytest.raises(ValueError):
        C.ChaosPlan(0, [{"site": "rpc.drop", "prob": 1.5}])
    with pytest.raises(ValueError):
        C.ChaosPlan(0, [])
    plan = C.ChaosPlan.from_value(
        '{"seed": 3, "faults": [{"site": "rpc.drop", "prob": 0.5}]}')
    assert plan.seed == 3
    assert C.ChaosPlan.from_value(plan.to_dict()).to_json() == plan.to_json()


def test_chaos_seeded_determinism():
    """Same plan + seed => identical fire sequence; a different seed
    diverges — a chaos test is a replay, not a dice roll."""
    plan = {"seed": 11, "faults": [{"site": "rpc.drop", "prob": 0.4}]}

    def run(p):
        C.arm(p)
        seq = [C.maybe_fire("rpc.drop", target="kv_get") is not None
               for _ in range(200)]
        C.disarm()
        return seq

    s1, s2 = run(plan), run(plan)
    assert s1 == s2
    assert any(s1) and not all(s1)
    s3 = run(dict(plan, seed=12))
    assert s3 != s1


def test_maybe_fire_semantics():
    """at / after / max_fires / target gating, per-site hit counters."""
    C.arm({"seed": 0, "faults": [
        {"site": "worker.kill", "at": 3, "target": "victim"},
        {"site": "rpc.delay", "after": 2, "max_fires": 2, "delay_s": 0.1},
    ]})
    # target mismatch never fires, even on hit 3
    assert all(C.maybe_fire("worker.kill", target="other") is None
               for _ in range(5))
    C.arm({"seed": 0, "faults": [
        {"site": "worker.kill", "at": 3, "target": "victim"},
        {"site": "rpc.delay", "after": 2, "max_fires": 2, "delay_s": 0.1},
    ]})
    fires = [C.maybe_fire("worker.kill", target="my_victim_fn") is not None
             for _ in range(5)]
    assert fires == [False, False, True, False, False]
    fires = [C.maybe_fire("rpc.delay") is not None for _ in range(6)]
    assert fires == [False, False, True, True, False, False]  # max_fires=2
    st = C.status()
    assert st["armed"] and st["fires"] == {"worker.kill": 1, "rpc.delay": 2}
    assert st["hits"]["worker.kill"] == 5
    # unarmed is inert
    C.disarm()
    assert C.maybe_fire("worker.kill", target="my_victim_fn") is None
    assert C.status() == {"armed": False}


def test_restart_backoff_damping_pure():
    """backoff_with_jitter: capped exponential, jitter bounded +-25%."""
    import random

    rng = random.Random(0)
    seq = [F.backoff_with_jitter(n, 0.5, 30.0, rng) for n in range(1, 12)]
    for n, b in enumerate(seq, start=1):
        ideal = min(30.0, 0.5 * 2 ** (n - 1))
        assert 0.75 * ideal <= b <= 1.25 * ideal, (n, b)
    # jitter ranges of consecutive attempts are disjoint below the cap:
    # a crash loop is GUARANTEED to slow down, not just on average
    assert seq[1] > seq[0] and seq[3] > seq[2]
    assert max(seq) <= 30.0 * 1.25


# ---- injection sites end-to-end --------------------------------------------

def test_worker_kill_site_fires_and_recovers():
    """`raylet.kill_worker` kills the worker once; the owner's retry
    recovers. Asserted on the failure plane: a chaos-origin worker_crash
    row, rt_task_retries_total + rt_chaos_injections_total ticks, and
    `rt doctor` back to exit 0 once the window passes."""
    ray_tpu.init(num_cpus=2)
    b = _backend()
    retries_before = _counter("rt_task_retries_total")
    inj_before = _counter("rt_chaos_injections_total",
                          {"site": "raylet.kill_worker"})
    reply = b.io.run(b._gcs.call("chaos_arm", {"plan": {
        "seed": 1,
        "faults": [{"site": "raylet.kill_worker", "at": 1,
                    "max_fires": 1}]}}))
    assert reply.get("ok"), reply

    @ray_tpu.remote(max_retries=2)
    def survivor(x):
        return x * 2

    assert ray_tpu.get(survivor.remote(21), timeout=120) == 42
    # the injection is on the feed, distinguishable from organic failures
    chaos_evs = _events(b, origin="chaos")
    assert chaos_evs and chaos_evs[-1]["category"] == F.WORKER_CRASH
    assert chaos_evs[-1]["site"] == "raylet.kill_worker"
    organic = _events(b, origin="organic", want=0)
    assert all(e.get("origin") != "chaos" for e in organic)
    assert _counter("rt_task_retries_total") > retries_before
    assert _counter("rt_chaos_injections_total",
                    {"site": "raylet.kill_worker"}) > inj_before

    # rt errors renders the origin tag + --origin filters (CLI surface)
    from argparse import Namespace

    from ray_tpu.scripts import cli
    import io as _io
    import contextlib

    out = _io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = cli.cmd_errors(Namespace(address=b.gcs_address, category=None,
                                      limit=100, json=False,
                                      origin="chaos"))
    assert rc == 0 and "[chaos]" in out.getvalue()

    # doctor: unhealthy while the kill is recent, healthy once windowed out
    from ray_tpu.util import doctor

    _, rc = doctor.run(b.gcs_address, window_s=600.0)
    assert rc == 1
    b.io.run(b._gcs.call("chaos_disarm", {}))
    time.sleep(2.5)
    text, rc = doctor.run(b.gcs_address, window_s=2.0)
    assert rc == 0, text


def test_rpc_delay_and_drop_sites():
    """rpc partition sites: delay stalls the targeted method; drop raises
    ConnectionLost once; the buffered injection events reach the feed."""
    from ray_tpu.cluster.rpc import ConnectionLost

    ray_tpu.init(num_cpus=1)
    b = _backend()
    C.arm({"seed": 0, "faults": [
        {"site": "rpc.delay", "at": 1, "delay_s": 0.4,
         "target": "cluster_resources"}]})
    t0 = time.monotonic()
    ray_tpu.cluster_resources()
    assert time.monotonic() - t0 >= 0.4
    C.arm({"seed": 0, "faults": [
        {"site": "rpc.drop", "at": 1, "target": "cluster_resources"}]})
    with pytest.raises((ConnectionLost, RuntimeError)):
        ray_tpu.cluster_resources()
    assert ray_tpu.cluster_resources()  # next call is fine again
    # the rpc fires were buffered (no GCS handle at the site) and drain
    # via the raylet heartbeat loop
    evs = _events(b, timeout_s=15.0, origin="chaos", want=1)
    assert any(e.get("site") in ("rpc.delay", "rpc.drop") for e in evs), evs


def test_object_lose_site_forces_reconstruction():
    """`object.lose` eats a sealed plasma return (location registered,
    payload gone): the owner's lineage reconstruction rebuilds it —
    asserted via rt_object_reconstructions_total and the chaos-origin
    object_lost row."""
    ray_tpu.init(num_cpus=2)
    b = _backend()
    rec_before = _counter("rt_object_reconstructions_total",
                          {"outcome": "ok"})

    @ray_tpu.remote
    def produce():
        return np.full((400, 200), 7.0, dtype=np.float32)  # -> plasma

    # warm up the worker + export BEFORE arming so the only seal the
    # chaos sees is our produce() return
    assert ray_tpu.get(produce.remote(), timeout=60)[0, 0] == 7.0
    C.arm({"seed": 0, "faults": [
        {"site": "object.lose", "after": 0, "max_fires": 1}]})
    ref = produce.remote()
    value = ray_tpu.get(ref, timeout=120)
    assert float(value[0, 0]) == 7.0
    assert _counter("rt_object_reconstructions_total",
                    {"outcome": "ok"}) > rec_before
    evs = _events(b, origin="chaos")
    assert any(e.get("site") == "object.lose"
               and e.get("category") == F.OBJECT_LOST for e in evs), evs


def test_oom_pressure_site():
    """`oom.pressure` fakes node memory at 99%: the real OOM-kill path
    runs (victim picked, post-mortem stamped) and the caller sees
    OutOfMemoryError with the categorized cause."""
    from ray_tpu.exceptions import OutOfMemoryError

    ray_tpu.init(num_cpus=2)
    b = _backend()

    @ray_tpu.remote(max_retries=0)
    def hog():
        time.sleep(60)

    ref = hog.remote()
    time.sleep(1.0)  # let the task occupy its worker
    C.arm({"seed": 0, "faults": [
        {"site": "oom.pressure", "at": 1, "max_fires": 1, "value": 0.99}]})
    with pytest.raises(OutOfMemoryError) as exc_info:
        ray_tpu.get(ref, timeout=60)
    assert (exc_info.value.cause_info or {}).get("category") == F.OOM_KILL
    evs = _events(b, origin="chaos")
    assert any(e.get("site") == "oom.pressure" for e in evs), evs


def test_chaos_distribution_via_heartbeat_and_status():
    """`rt chaos arm` -> GCS KV -> heartbeat rev -> raylet armed; status
    reports both the stored plan and local counters; disarm propagates."""
    ray_tpu.init(num_cpus=1)
    b = _backend()
    raylet = ray_tpu.global_worker().backend._cluster.raylets[0]
    reply = b.io.run(b._gcs.call("chaos_arm", {"plan": {
        "seed": 5, "faults": [{"site": "spill.slow", "prob": 0.0}]}}))
    rev = reply["rev"]
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and raylet._chaos_seen_rev != rev:
        time.sleep(0.2)
    assert raylet._chaos_seen_rev == rev
    assert C.armed() and C.current_rev() == rev
    status = b.io.run(b._gcs.call("chaos_status", {}))
    assert status["armed"] and status["plan"]["seed"] == 5
    # malformed plans are rejected at arm time, loudly
    bad = b.io.run(b._gcs.call("chaos_arm",
                               {"plan": {"faults": [{"site": "nope"}]}}))
    assert "error" in bad
    reply = b.io.run(b._gcs.call("chaos_disarm", {}))
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline and C.armed():
        time.sleep(0.2)
    assert not C.armed()
    assert not b.io.run(b._gcs.call("chaos_status", {}))["armed"]


# ---- hardened recovery ------------------------------------------------------

def test_degraded_raylet_through_gcs_outage(tmp_path):
    """Kill the GCS under a live raylet: local tasks (including plasma
    seals) keep succeeding, bookkeeping defers, and on restart the
    locations resync and the degraded period lands on the feed. The
    reconnect counter proves the backoff path ran."""
    from ray_tpu.cluster.cluster_utils import Cluster

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2},
                gcs_persist_path=str(tmp_path / "gcs_state"))
    try:
        c.connect_driver()
        b = _backend()
        rec_before = _counter("rt_rpc_reconnects_total")

        @ray_tpu.remote(max_retries=0)
        def big(i):
            return np.full((300, 200), float(i), dtype=np.float32)

        assert ray_tpu.get(big.remote(1), timeout=60)[0, 0] == 1.0
        raylet = c.head_node
        c.kill_gcs()
        time.sleep(0.5)
        # sequential: the warm worker keeps serving — the degraded-mode
        # guarantee (fresh workers can't load NEW functions GCS-less)
        for i in range(2, 5):
            assert float(ray_tpu.get(big.remote(i), timeout=60)[0, 0]) == i
        assert raylet._degraded_since is not None
        assert len(raylet._deferred_gcs) >= 3
        c.restart_gcs()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline \
                and raylet._degraded_since is not None:
            time.sleep(0.3)
        assert raylet._degraded_since is None, "degraded mode never exited"
        time.sleep(1.0)
        locs = b.io.run(b._gcs.call("list_objects", {}))
        assert len(locs) >= 3, "deferred locations never resynced"
        evs = _events(b, origin="recovery")
        assert any("degraded" in e.get("message", "") for e in evs), evs
        # the auto-reconnect clients re-dialed with backoff
        assert _counter("rt_rpc_reconnects_total") > rec_before
        # and the cluster is healthy again (fresh window)
        from ray_tpu.util import doctor

        time.sleep(2.5)
        text, rc = doctor.run(b.gcs_address, window_s=2.0)
        assert rc == 0, text
    finally:
        c.shutdown()


def test_restart_backoff_damping_recorded():
    """A crash-looping actor's consecutive restarts back off exponentially
    (recorded on the GCS entry), and the restart counter ticks."""
    ray_tpu.init(num_cpus=2)
    restarts_before = _counter("rt_actor_restarts_total")

    @ray_tpu.remote(max_restarts=2)
    class Phoenix:
        def pid(self):
            return os.getpid()

    a = Phoenix.remote()
    handle = ray_tpu.global_worker().backend._cluster
    entry = handle.gcs.actors[a._actor_id.hex()]
    base = 0.5

    pid = ray_tpu.get(a.pid.remote(), timeout=60)
    os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            new_pid = ray_tpu.get(a.pid.remote(), timeout=30)
            if new_pid != pid:
                break
        except Exception:
            time.sleep(0.3)
    first = entry.last_restart_backoff_s
    assert 0.75 * base <= first <= 1.25 * base, first
    os.kill(new_pid, signal.SIGKILL)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            if ray_tpu.get(a.pid.remote(), timeout=30) != new_pid:
                break
        except Exception:
            time.sleep(0.3)
    second = entry.last_restart_backoff_s
    # attempt-2 jitter range [1.5b, 2.5b] is disjoint from attempt-1's
    assert second > first and 0.75 * 2 * base <= second <= 1.25 * 2 * base
    assert _counter("rt_actor_restarts_total") >= restarts_before + 2


def test_rendezvous_cpu_graceful(monkeypatch):
    """A failed jax.distributed bootstrap on a CPU-only host degrades to
    local jax (the gang still runs); RT_RENDEZVOUS_STRICT makes it fatal."""
    import jax

    from ray_tpu.collective.rendezvous import bootstrap_jax_distributed

    ray_tpu.init(num_cpus=1)

    def boom(*a, **k):
        raise RuntimeError("no coordinator for you")

    monkeypatch.setattr(jax.distributed, "initialize", boom, raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    # graceful: rank 0 publishes the coordinator, init fails, bootstrap
    # returns instead of killing the rank
    bootstrap_jax_distributed(2, 0, "zz_graceful_test", timeout_s=5.0)
    monkeypatch.setenv("RT_RENDEZVOUS_STRICT", "1")
    with pytest.raises(RuntimeError):
        bootstrap_jax_distributed(2, 0, "zz_strict_test", timeout_s=5.0)


# ---- the gang leg -----------------------------------------------------------

def test_gang_leg_kill_recover_doctor_2_1_0(tmp_path):
    """The multi-host product leg under chaos: a STRICT_PACK JaxTrainer
    gang loses a rank mid-train, FailureConfig restarts it from the last
    checkpoint, and recovery is proven on the failure plane — `rt doctor`
    walking 2 (unreachable) -> 1 (unhealthy) -> 0 (recovered), a
    gang-restart FailureEvent, and rt_actor_restarts_total ticking."""
    from ray_tpu.train import (Checkpoint, FailureConfig, JaxTrainer,
                               RunConfig, ScalingConfig)
    from ray_tpu.util import doctor

    # 2: no cluster at this address
    _, rc = doctor.run("127.0.0.1:1", window_s=1.0)
    assert rc == 2

    ray_tpu.init(num_cpus=5)
    b = _backend()
    restarts_before = _counter("rt_actor_restarts_total")
    pids = str(tmp_path / "pids")
    attempts = str(tmp_path / "attempts")

    def loop(config):
        from ray_tpu import train

        ckpt = train.get_checkpoint()
        start = ckpt.to_dict()["step"] + 1 if ckpt else 0
        ctx = train.get_context()
        with open(config["attempts"], "a") as f:
            f.write(f"{ctx.get_world_rank()}:{start}\n")
        with open(config["pids"] + f".{ctx.get_world_rank()}", "w") as f:
            f.write(str(os.getpid()))
        for step in range(start, 5):
            time.sleep(0.4)
            train.report({"step": step},
                         checkpoint=Checkpoint.from_dict({"step": step}))

    def killer():
        deadline = time.monotonic() + 40
        while time.monotonic() < deadline:
            try:
                pid = int(open(pids + ".1").read())
                time.sleep(1.0)  # let a checkpoint land
                os.kill(pid, signal.SIGKILL)
                return
            except (FileNotFoundError, ValueError, ProcessLookupError):
                time.sleep(0.2)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    result = JaxTrainer(
        loop, train_loop_config={"pids": pids, "attempts": attempts},
        scaling_config=ScalingConfig(num_workers=2, cpus_per_worker=1,
                                     placement_strategy="STRICT_PACK"),
        run_config=RunConfig(name="zz_chaos_gang", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=2))
    ).fit()
    t.join(timeout=10)
    assert result.error is None
    assert result.metrics["step"] == 4
    starts = open(attempts).read().split()
    assert len(starts) >= 4, f"gang never restarted: {starts}"
    assert any(int(s.split(":")[1]) > 0 for s in starts[2:]), \
        f"restart did not resume from a checkpoint: {starts}"

    # failure plane: the gang restart is a categorized, feed-visible event
    evs = _events(b)
    gang = [e for e in evs if e.get("gang_restart")]
    assert gang, f"gang restart missing from the feed: {evs}"
    assert gang[-1]["category"] in (F.WORKER_CRASH, F.TASK_ERROR)
    assert gang[-1].get("name") == "JaxTrainer"
    assert _counter("rt_actor_restarts_total") > restarts_before

    # 1: the kill is recent -> unhealthy; 0: recovered once windowed out
    _, rc = doctor.run(b.gcs_address, window_s=600.0)
    assert rc == 1
    time.sleep(3.0)
    text, rc = doctor.run(b.gcs_address, window_s=2.0)
    assert rc == 0, text


@pytest.mark.slow
def test_chaos_smoke_script():
    """scripts/chaos_smoke.sh: the one-shot CI gate — start a real node
    daemon, arm a kill-worker plan from the CLI, run a workload through
    the kill, and require `rt doctor` to exit 0 after recovery."""
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a smaller flood than the script's 5000 default: the overload leg
    # drains the whole flood before its health checks, and on a loaded
    # 1-2 core CI box the full drain alone can blow the budget (the
    # 5k-deep probe case is asserted in-process by
    # test_zz_sched_fairness); a timed-out bash leaves the node daemon
    # alive and wedges every later test in the session
    proc = subprocess.run(
        ["bash", os.path.join(root, "scripts", "chaos_smoke.sh")],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", RT_SMOKE_FLOOD="1500"))
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
