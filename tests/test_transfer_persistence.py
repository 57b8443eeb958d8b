"""Chunked object transfer + GCS snapshot persistence.

Reference analogs: ``src/ray/object_manager/chunk_object_reader.h`` (chunked
node-to-node transfer), ``src/ray/gcs/store_client/redis_store_client.cc``
(GCS table persistence behind restarts).
"""

import asyncio
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import config as config_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chunked_cluster(monkeypatch):
    """Two-node cluster with a tiny transfer chunk so a modest object takes
    many chunks."""
    monkeypatch.setenv("RT_OBJECT_TRANSFER_CHUNK_BYTES", str(256 * 1024))
    config_mod.reset_config_for_tests()
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    from ray_tpu.cluster.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 2})
    node2 = cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.connect_driver()
    yield cluster
    cluster.shutdown()
    config_mod.reset_config_for_tests()


def test_chunked_cross_node_transfer(chunked_cluster):
    """An 8MB object crosses nodes in 256KB chunks (32+ round trips),
    arriving intact."""
    arr = np.arange(2 * 1024 * 1024, dtype=np.float32)  # 8MB
    ref = ray_tpu.put(arr)

    @ray_tpu.remote(resources={"side": 1})
    def consume(got):
        # the ref arg resolves IN the node-2 worker: that dependency fetch
        # is the chunked cross-node pull under test
        return float(got.sum()), got.shape[0]

    total, n = ray_tpu.get(consume.remote(ref), timeout=120)
    assert n == arr.shape[0]
    assert total == float(arr.sum())


def test_chunk_rpc_serves_spilled(chunked_cluster):
    """get_object_chunk serves from the spill file as well as shm."""
    backend = ray_tpu.global_worker()._require_backend()
    raylet = chunked_cluster.head_node
    arr = np.ones(256 * 1024, dtype=np.float32)  # 1MB -> plasma
    ref = ray_tpu.put(arr)
    # force-spill the object out of shm
    raylet._spill_blocking_for_tests = None

    async def spill_then_read():
        # move it to disk by hand via the spill helpers
        import os as _os

        _os.makedirs(raylet._spill_dir, exist_ok=True)
        view = raylet.store.read(ref.id())
        payload = bytes(view)
        with open(raylet._spill_path(ref.hex()), "wb") as f:
            f.write(payload)
        raylet.store.delete(ref.id())
        raylet._object_meta[ref.hex()]["spilled"] = True
        first = await raylet.rpc_get_object_chunk(
            {"oid": ref.hex(), "offset": 0, "size": 100})
        rest = await raylet.rpc_get_object_chunk(
            {"oid": ref.hex(), "offset": 100, "size": 4 << 20})
        return payload, first, rest

    payload, first, rest = backend.io.run(spill_then_read())
    assert first["total"] == len(payload)  # serialized size, not nbytes
    assert len(first["data"]) == 100
    assert first["data"] + rest["data"] == payload


def test_gcs_snapshot_restore(tmp_path):
    """Actors/PGs/KV/locations survive a GcsServer restart via snapshot."""
    from ray_tpu.cluster.gcs import ACTOR_ALIVE, GcsServer

    path = str(tmp_path / "snap.pkl")

    async def first_life():
        g = GcsServer(persist_path=path)
        await g.rpc_kv_put({"key": "persist-me", "value": b"42"})
        await g.rpc_register_actor({"spec": {
            "actor_id": "a" * 24, "class_name": "Worker", "name": "keeper",
            "namespace": "default", "resources": {}, "args": [], "kwargs": {},
            "max_restarts": 0, "scheduling_strategy": None, "pg": None,
            "owner": "x", "method_meta": {}, "lifetime": "detached",
            "get_if_exists": False, "max_task_retries": 0,
            "max_concurrency": 1, "class_id": "cid", "job_id": "0" * 8}})
        await g.rpc_add_object_location({"oid": "o" * 16, "node_id": "n1",
                                         "size": 123})
        g.actors["a" * 24].state = ACTOR_ALIVE
        g.mark_dirty()
        await g.stop()

    async def second_life():
        g = GcsServer(persist_path=path)
        assert g.kv.get("persist-me") == b"42"
        assert "a" * 24 in g.actors
        assert g.actors["a" * 24].spec["class_name"] == "Worker"
        info = await g.rpc_kv_get({"key": "persist-me"})
        assert info["value"] == b"42"
        assert "o" * 16 in g.object_locations
        await g.stop()

    asyncio.run(first_life())
    assert os.path.exists(path)
    asyncio.run(second_life())


def _cli(env, *args, timeout=90):
    return subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.cli", *args],
        env=env, capture_output=True, text=True, timeout=timeout)


def test_cli_head_restart_preserves_kv(tmp_path):
    """Kill and restart the head daemon with the same session name: GCS KV
    written before the crash is visible after restart."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["RT_SESSION_DIR_ROOT"] = str(tmp_path)
    head = _cli(env, "start", "--head", "--num-cpus", "1",
                "--session-name", "persist_sess")
    assert head.returncode == 0, head.stderr
    gcs1 = [ln.split()[-1] for ln in head.stdout.splitlines()
            if "gcs_address" in ln][0]
    try:
        os.environ["RT_SESSION_DIR_ROOT"] = str(tmp_path)
        config_mod.reset_config_for_tests()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        ray_tpu.init(address=gcs1)
        backend = ray_tpu.global_worker()._require_backend()
        backend.kv_put("survive", b"yes")
        time.sleep(1.5)  # let the snapshot loop persist
        ray_tpu.shutdown()

        # hard-kill the head (no graceful stop)
        import json as _json

        states = os.listdir(os.path.join(str(tmp_path), "nodes"))
        for name in states:
            with open(os.path.join(str(tmp_path), "nodes", name)) as f:
                st = _json.load(f)
            os.kill(st["pid"], 9)
        time.sleep(0.5)
        for name in os.listdir(os.path.join(str(tmp_path), "nodes")):
            os.unlink(os.path.join(str(tmp_path), "nodes", name))

        head2 = _cli(env, "start", "--head", "--num-cpus", "1",
                     "--session-name", "persist_sess")
        assert head2.returncode == 0, head2.stderr
        gcs2 = [ln.split()[-1] for ln in head2.stdout.splitlines()
                if "gcs_address" in ln][0]
        config_mod.reset_config_for_tests()
        ray_tpu.init(address=gcs2)
        backend = ray_tpu.global_worker()._require_backend()
        assert backend.kv_get("survive") == b"yes"
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RT_SESSION_DIR_ROOT", None)
        config_mod.reset_config_for_tests()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        _cli(env, "stop", "--force")


def test_gcs_kv_wal_str_and_bytes_roundtrip(tmp_path):
    """The KV WAL (native LogKV) must preserve value TYPES across restart:
    callers store both str (json configs) and bytes (pickled blobs)."""
    import asyncio

    from ray_tpu.cluster.gcs import GcsServer

    path = str(tmp_path / "gcs_state")

    async def run():
        g = GcsServer(persist_path=path)
        await g.rpc_kv_put({"key": "s", "value": "json-string"})
        await g.rpc_kv_put({"key": "b", "value": b"\x00raw"})
        await g.rpc_kv_put({"key": "gone", "value": "x"})
        await g.rpc_kv_del({"key": "gone"})
        await g.stop()
        g2 = GcsServer(persist_path=path)
        assert g2.kv["s"] == "json-string"
        assert g2.kv["b"] == b"\x00raw"
        assert "gone" not in g2.kv
        await g2.stop()

    asyncio.run(run())


def test_gcs_kv_degraded_wal_run_merges_on_reopen(tmp_path, monkeypatch):
    """A run whose WAL failed to open acks puts into the snapshot only; the
    next restart that re-opens the WAL must merge those puts back instead
    of silently replacing kv with the (older) WAL contents."""
    import asyncio

    from ray_tpu.cluster.gcs import GcsServer

    path = str(tmp_path / "gcs_state")

    async def run():
        # healthy run writes durable keys through the WAL
        g = GcsServer(persist_path=path)
        assert g._kv_log is not None
        await g.rpc_kv_put({"key": "wal-key", "value": "v1"})
        await g.rpc_kv_put({"key": "both", "value": "old"})
        await g.stop()

        # degraded run: WAL open fails (simulated), puts land snapshot-only
        import ray_tpu._native as nat

        def boom(path):
            raise OSError("simulated WAL open failure")

        monkeypatch.setattr(nat, "LogKV", boom)
        g2 = GcsServer(persist_path=path)
        assert g2._kv_log is None
        await g2.rpc_kv_put({"key": "degraded-key", "value": "v2"})
        await g2.rpc_kv_put({"key": "both", "value": "new"})
        await g2.stop()
        monkeypatch.undo()

        # healthy restart: WAL re-opens; degraded puts must survive
        g3 = GcsServer(persist_path=path)
        assert g3._kv_log is not None
        assert g3.kv["wal-key"] == "v1"
        assert g3.kv["degraded-key"] == "v2"
        assert g3.kv["both"] == "new"
        await g3.stop()

        # and they are now IN the WAL (snapshot kv is blanked again)
        g4 = GcsServer(persist_path=path)
        assert g4.kv["degraded-key"] == "v2"
        assert g4.kv["both"] == "new"
        await g4.stop()

    asyncio.run(run())


@pytest.mark.slow
def test_large_object_transfer_under_small_store(monkeypatch):
    """A 512MB object crosses nodes with a 128MB store cap: the source
    spills it, chunks serve from the spill file, the destination restores
    under its own cap — bounded memory end to end (a 1 GiB broadcast,
    scaled to CI time)."""
    monkeypatch.setenv("RT_OBJECT_STORE_MEMORY_BYTES", str(128 * 1024 * 1024))
    config_mod.reset_config_for_tests()
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    from ray_tpu.cluster.cluster_utils import Cluster

    cluster = Cluster(head_node_args={"num_cpus": 2})
    cluster.add_node(num_cpus=2, resources={"side": 1})
    cluster.connect_driver()
    try:
        arr = np.arange(128 * 1024 * 1024, dtype=np.float32)  # 512MB
        ref = ray_tpu.put(arr)

        @ray_tpu.remote(resources={"side": 1})
        def consume(got):
            return float(got[::65536].sum()), got.shape[0]

        total, n = ray_tpu.get(consume.remote(ref), timeout=600)
        assert n == arr.shape[0]
        assert total == float(arr[::65536].sum())
    finally:
        cluster.shutdown()
        config_mod.reset_config_for_tests()


def test_cli_head_restart_recovers_named_actor(tmp_path):
    """A detached named actor with restart budget survives a hard head
    restart: its table entry restores from the snapshot, the first call
    after restart finds the old worker gone and the restart machinery
    recreates it (reference: GCS FT for detached actors)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env["RT_SESSION_DIR_ROOT"] = str(tmp_path)
    head = _cli(env, "start", "--head", "--num-cpus", "2",
                "--session-name", "actor_sess")
    assert head.returncode == 0, head.stderr
    gcs1 = [ln.split()[-1] for ln in head.stdout.splitlines()
            if "gcs_address" in ln][0]
    try:
        os.environ["RT_SESSION_DIR_ROOT"] = str(tmp_path)
        config_mod.reset_config_for_tests()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        ray_tpu.init(address=gcs1)

        @ray_tpu.remote(max_restarts=-1, lifetime="detached",
                        name="phoenix")
        class Counter:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get(c.bump.remote(), timeout=60) == 1
        time.sleep(1.5)  # snapshot persists the actor table
        ray_tpu.shutdown()

        import json as _json

        for name in os.listdir(os.path.join(str(tmp_path), "nodes")):
            with open(os.path.join(str(tmp_path), "nodes", name)) as f:
                st = _json.load(f)
            os.kill(st["pid"], 9)
        time.sleep(0.5)
        for name in os.listdir(os.path.join(str(tmp_path), "nodes")):
            os.unlink(os.path.join(str(tmp_path), "nodes", name))

        head2 = _cli(env, "start", "--head", "--num-cpus", "2",
                     "--session-name", "actor_sess")
        assert head2.returncode == 0, head2.stderr
        gcs2 = [ln.split()[-1] for ln in head2.stdout.splitlines()
                if "gcs_address" in ln][0]
        config_mod.reset_config_for_tests()
        ray_tpu.init(address=gcs2)
        c2 = ray_tpu.get_actor("phoenix")
        # fresh __init__ after recreation: state resets, actor is LIVE
        val = ray_tpu.get(c2.bump.remote(), timeout=120)
        assert val == 1, val
        assert ray_tpu.get(c2.bump.remote(), timeout=60) == 2
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RT_SESSION_DIR_ROOT", None)
        config_mod.reset_config_for_tests()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        _cli(env, "stop", "--force")
