"""Memory observability plane: ownership ledger, memory_summary(),
spill/restore/OOM telemetry, pin-purge timer, HBM fallback.

Reference analogs: ``ray memory`` / ``memory_summary`` over the core
worker's ReferenceCounter, plus the raylet's LocalObjectManager spill
accounting. Named ``test_zz_*`` so it sorts late in the suite.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import config as config_mod
from ray_tpu.core import object_ledger


@pytest.fixture
def small_store_cluster(monkeypatch):
    """Cluster whose object store spills beyond ~2MB."""
    monkeypatch.setenv("RT_OBJECT_STORE_MEMORY_BYTES", str(2 * 1024 * 1024))
    monkeypatch.setenv("RT_OBJECT_SPILL_THRESHOLD", "1.0")
    config_mod.reset_config_for_tests()
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()
    config_mod.reset_config_for_tests()


@pytest.fixture
def plain_cluster():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


def _driver_raylet():
    from ray_tpu.core.worker import global_worker

    return global_worker().backend._cluster.raylets[0]


def _hist_count(name: str) -> int:
    from ray_tpu.util import metrics as M

    for m in M._registry.snapshot():
        if m["name"] == name and m["type"] == "histogram":
            return sum(h["count"] for _, h in m["samples"])
    return 0


# ---- object states across put/get/spill/restore/free -----------------------

def test_states_across_put_spill_restore_free(small_store_cluster):
    from ray_tpu.util.memory import memory_snapshot

    refs = [ray_tpu.put(np.full((1024, 256), i, dtype=np.float32))
            for i in range(6)]
    snap = memory_snapshot(limit=100)
    node = snap["nodes"][0]
    states = {o["oid"]: o["state"] for o in node["objects"]}
    assert len(states) == 6
    assert "spilled" in states.values(), "overfill did not spill"
    store = node["store"]
    assert store["spilled_count"] >= 1
    assert store["spills"] >= 1
    assert store["capacity_bytes"] == 2 * 1024 * 1024
    spills_before = _hist_count("rt_object_spill_seconds")
    assert spills_before >= 1, "spill histogram never observed"

    # Restoring books a restore + its histogram sample. The driver itself
    # still holds the spilled object's mmap (zero-copy cache), so the
    # restore must be driven from a FRESH process: a worker fetching the
    # spilled ref as a task argument goes through the raylet's
    # restore-from-spill path.
    spilled_oid = next(o for o, s in states.items() if s == "spilled")
    target = next(r for r in refs if r.hex() == spilled_oid)

    @ray_tpu.remote
    def shape(a):
        return a.shape

    assert ray_tpu.get(shape.remote(target), timeout=60) == (1024, 256)
    snap = memory_snapshot(limit=100)
    assert snap["nodes"][0]["store"]["restores"] >= 1
    assert _hist_count("rt_object_restore_seconds") >= 1

    # free removes the objects from the store table entirely
    ray_tpu.internal_free(refs)
    snap = memory_snapshot(limit=100)
    assert snap["nodes"][0]["store"]["num_objects"] == 0
    # and the ledger marks them freed (absent from the owner snapshot)
    led_oids = {o["oid"] for led in snap["ledgers"]
                for o in led.get("objects", ())}
    assert not led_oids & set(states)


def test_spill_restore_timeline_instants(small_store_cluster):
    refs = [ray_tpu.put(np.ones((1024, 256), dtype=np.float32) * i)
            for i in range(5)]
    _ = ray_tpu.get(refs[0], timeout=60)
    deadline = time.monotonic() + 10
    kinds = set()
    while time.monotonic() < deadline and "spill" not in kinds:
        trace = ray_tpu.timeline()
        kinds = {t["name"].split()[0] for t in trace
                 if t.get("cat") == "memory"}
        time.sleep(0.2)
    assert "spill" in kinds, f"no spill instants on the timeline: {kinds}"


def test_memory_summary_text_and_owner_table(small_store_cluster):
    ref = ray_tpu.put(np.ones((1024, 512), dtype=np.float32))  # 2MB
    text = ray_tpu.memory_summary(limit=50)
    assert "Per-node object store usage" in text
    assert "Objects by owner" in text
    # the owner table carries this put, keyed tail-wise (index bits)
    assert ref.hex()[-8:] in text
    del ref


# ---- leak suspects ----------------------------------------------------------

def test_leak_suspect_flagging(small_store_cluster):
    from ray_tpu.util.memory import memory_snapshot

    ref = ray_tpu.put(np.ones((1024, 300), dtype=np.float32))
    suspects = object_ledger.get_ledger().leak_suspects(age_s=0.0)
    assert any(s["oid"] == ref.hex() for s in suspects), \
        "driver-local-only ref not flagged"
    # the aggregated (ledger-join) path flags it too — this is what a
    # fresh `rt memory` driver or the dashboard actor actually computes
    agg = memory_snapshot(limit=50, leak_age_s=0.0)["leak_suspects"]
    assert any(s["oid"] == ref.hex() for s in agg)
    # consuming the ref as a task arg clears the suspicion

    @ray_tpu.remote
    def shape(a):
        return a.shape

    assert ray_tpu.get(shape.remote(ref), timeout=60) == (1024, 300)
    suspects = object_ledger.get_ledger().leak_suspects(age_s=0.0)
    assert not any(s["oid"] == ref.hex() for s in suspects)
    # freeing drops the entry entirely
    ray_tpu.internal_free([ref])
    assert not any(s["oid"] == ref.hex()
                   for s in object_ledger.get_ledger().leak_suspects(0.0))


def test_ref_creation_sites_flag(monkeypatch, plain_cluster):
    monkeypatch.setenv("RT_RECORD_REF_CREATION_SITES", "1")
    config_mod.reset_config_for_tests()
    object_ledger.reset_enabled_for_tests()
    try:
        ref = ray_tpu.put(b"x" * 200_000)
        snap = object_ledger.get_ledger().snapshot()
        entry = next(o for o in snap if o["oid"] == ref.hex())
        assert "test_zz_memory_obs.py" in entry["call_site"]
        # the call site surfaces in the summary text too
        assert "test_zz_memory_obs.py" in ray_tpu.memory_summary(limit=50)
    finally:
        config_mod.reset_config_for_tests()
        object_ledger.reset_enabled_for_tests()


# ---- local backend ----------------------------------------------------------

def test_memory_summary_local_backend():
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    ray_tpu.init(local_mode=True)
    try:
        ref = ray_tpu.put(np.ones((256, 256), dtype=np.float32))
        from ray_tpu.util.memory import memory_snapshot

        snap = memory_snapshot(limit=50)
        node = snap["nodes"][0]
        assert node["store"]["num_objects"] >= 1
        # the put's nbytes estimate lands in the per-object table
        sizes = {o["oid"]: o["size"] for o in node["objects"]}
        assert sizes.get(ref.hex()) == 256 * 256 * 4
        text = ray_tpu.memory_summary()
        assert "Per-node object store usage" in text
        ray_tpu.internal_free([ref])
        snap = memory_snapshot(limit=50)
        assert all(o["oid"] != ref.hex()
                   for o in snap["nodes"][0]["objects"])
    finally:
        ray_tpu.shutdown()


# ---- OOM post-mortem --------------------------------------------------------

def test_oom_postmortem_event_contents(plain_cluster):
    from ray_tpu.exceptions import OutOfMemoryError
    from ray_tpu.util.memory import format_oom_reports, oom_reports

    raylet = _driver_raylet()
    big = ray_tpu.put(np.ones((512, 512), dtype=np.float32))

    @ray_tpu.remote(max_retries=0)
    def hog():
        time.sleep(60)

    ref = hog.remote()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if any(e.busy for e in raylet._workers.values()):
            break
        time.sleep(0.1)
    raylet._memory_info_fn = lambda: {"total": 1000, "used": 990}
    try:
        with pytest.raises(OutOfMemoryError):
            ray_tpu.get(ref, timeout=60)
    finally:
        raylet._memory_info_fn = None
    deadline = time.monotonic() + 10
    reps = []
    while time.monotonic() < deadline and not reps:
        reps = oom_reports()
        time.sleep(0.2)
    assert reps, "oom_kill event never reached the GCS"
    ev = reps[-1]
    assert ev["node_memory"] == {"total": 1000, "used": 990}
    assert ev["victim"]["task"] == "hog"
    assert ev["victim"]["rss"] > 0
    assert any(o["oid"] == big.hex() for o in ev["top_objects"]), \
        "largest live object missing from the post-mortem"
    text = format_oom_reports(reps)
    assert "hog" in text and "oom_kill" in text
    # the kill is also countable: cumulative stat + counter series
    assert raylet._mem_stats["oom_kills"] >= 1
    # and rides the timeline as an instant marker
    names = {t["name"] for t in ray_tpu.timeline()
             if t.get("cat") == "memory"}
    assert any(n.startswith("oom_kill") for n in names)


# ---- pin-purge timer --------------------------------------------------------

def test_stale_pin_purged_by_timer(plain_cluster):
    raylet = _driver_raylet()
    stale = "ab" * 24
    raylet._pinned[stale] = {"count": 1,
                             "t": time.monotonic() - raylet._PIN_TTL_S - 5}
    raylet._last_pin_purge = 0.0  # make the reap-loop gate fire on next tick
    deadline = time.monotonic() + 8
    while time.monotonic() < deadline and stale in raylet._pinned:
        time.sleep(0.2)
    assert stale not in raylet._pinned, "timer never purged the leaked pin"
    assert raylet._mem_stats["pin_purges"] >= 1
    # purges surface in the node's memory report
    snap_purges = None
    from ray_tpu.util.memory import memory_snapshot

    for n in memory_snapshot(limit=10)["nodes"]:
        if n["node_id"] == raylet.node_id:
            snap_purges = n["store"]["pin_purges"]
    assert snap_purges and snap_purges >= 1


# ---- worker RSS / memory report ---------------------------------------------

def test_memory_report_includes_worker_rss(plain_cluster):
    @ray_tpu.remote
    def noop():
        return os.getpid()

    pid = ray_tpu.get(noop.remote(), timeout=60)
    from ray_tpu.util.memory import memory_snapshot

    node = memory_snapshot(limit=10)["nodes"][0]
    workers = node.get("workers") or []
    assert any(w["pid"] == pid and w["rss"] > 0 for w in workers)
    assert node["node_memory"]["total"] > 0


# ---- dashboard: Memory tab payload + log viewer -----------------------------

def test_dashboard_memory_and_logs_endpoints(plain_cluster):
    import json
    import urllib.request

    from ray_tpu.dashboard import start_dashboard

    def _get_json(port, path):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
            return json.loads(resp.read())

    @ray_tpu.remote
    def chatty():
        print("hello-from-memory-obs")
        return np.ones((512, 512), dtype=np.float32)

    got = ray_tpu.get(chatty.remote(), timeout=60)
    assert got.shape == (512, 512)
    port = start_dashboard()

    snap = _get_json(port, "/api/memory")
    node = snap["nodes"][0]
    assert "store" in node and node["store"]["num_objects"] >= 1
    assert "ledgers" in snap and "leak_suspects" in snap

    # the log viewer serves the raylet's ring (satellite: VERDICT #7);
    # the pump tails worker files every 0.3s — poll until the line lands
    deadline = time.monotonic() + 15
    entries = []
    while time.monotonic() < deadline:
        entries = [e for e in _get_json(port, "/api/logs?limit=500")
                   if "hello-from-memory-obs" in e.get("line", "")]
        if entries:
            break
        time.sleep(0.3)
    assert entries, "worker print never reached /api/logs"
    wid = entries[0]["worker_id"]
    filtered = _get_json(port, f"/api/logs?worker={wid[:6]}&limit=500")
    assert filtered and all(
        e["worker_id"].startswith(wid[:6]) for e in filtered)
    # a bogus worker filter returns nothing (filtering, not echoing)
    assert _get_json(port, "/api/logs?worker=zzzzzz") == []


# ---- HBM fallback -----------------------------------------------------------

def test_hbm_stats_graceful_on_cpu():
    from ray_tpu.util.memory import device_memory_stats, publish_hbm_gauges

    stats = device_memory_stats()
    assert isinstance(stats, list) and stats, "no jax devices visible"
    for d in stats:
        assert set(d) >= {"id", "platform", "bytes_in_use",
                          "peak_bytes_in_use", "available"}
        if not d["available"]:
            assert d["bytes_in_use"] is None  # absent, never fake-zero
    publish_hbm_gauges(stats)  # must not raise whichever backend


def test_ledger_deref_is_lock_free():
    """A weakref finalizer can fire via the cyclic GC on a thread that is
    ALREADY inside one of the ledger's locked regions (any allocation under
    the lock can trigger collection). ``_deref`` must therefore never take
    the lock — it enqueues, and the next locked operation drains. The old
    locking ``_deref`` self-deadlocked the whole process (every
    ``ObjectRef.__init__`` blocked forever) under replica-kill churn."""
    import threading

    led = object_ledger.OwnershipLedger()
    with led._lock:
        e = led._entry_locked("deadbeef")
        e.local_refs = 2
        # simulate the GC firing the finalizer while THIS thread holds the
        # lock; run it in a helper thread so a regression fails the test
        # instead of hanging the whole session
        t = threading.Thread(target=led._deref, args=("deadbeef",),
                             daemon=True)
        t.start()
        t.join(timeout=5.0)
        assert not t.is_alive(), "_deref blocked on the ledger lock"
        assert e.local_refs == 2  # deferred, not applied in-finalizer
    led.record_get("deadbeef")  # any locked op drains the backlog
    assert led._entries["deadbeef"].local_refs == 1
