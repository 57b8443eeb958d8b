"""GCP TPU-pod node provider: REST client surface, slice lifecycle, and the
autoscaler end-to-end against a fake TPU API that boots REAL local nodes
(reference pattern: ``autoscaler/_private/fake_multi_node/node_provider.py``
— fake the cloud, keep the runtime below it real)."""

import time

import pytest

import ray_tpu
from ray_tpu.autoscaler import (
    FakeTpuRestHttp,
    GcpTpuPodProvider,
    StandardAutoscaler,
    TpuRestClient,
)
from ray_tpu.core.resources import LABEL_SLICE_NAME, LABEL_SLICE_TOPOLOGY


class RecordingHttp:
    """Unit seam for the REST client: records requests, plays back replies."""

    def __init__(self, replies=None):
        self.calls = []
        self.replies = list(replies or [])

    def __call__(self, method, url, headers, body):
        self.calls.append((method, url, headers, body))
        return self.replies.pop(0) if self.replies else (200, {})


def test_rest_client_request_shapes():
    http = RecordingHttp(replies=[(200, {"name": "op1"}),
                                  (200, {"nodes": []}),
                                  (200, {})])
    client = TpuRestClient("proj", "us-central2-b", http=http,
                           token_provider=lambda: "tok123")
    client.create_node("slice-a", {"acceleratorType": "v5p-16"})
    client.list_nodes()
    client.delete_node("slice-a")

    (m1, u1, h1, b1), (m2, u2, _, _), (m3, u3, _, _) = http.calls
    base = "https://tpu.googleapis.com/v2/projects/proj/locations/us-central2-b"
    assert (m1, u1) == ("POST", f"{base}/nodes?nodeId=slice-a")
    assert h1["Authorization"] == "Bearer tok123"
    assert b1["acceleratorType"] == "v5p-16"
    assert (m2, u2) == ("GET", f"{base}/nodes")
    assert (m3, u3) == ("DELETE", f"{base}/nodes/slice-a")


def test_rest_client_error_raises():
    http = RecordingHttp(replies=[(403, {"error": {"message": "denied"}})])
    client = TpuRestClient("proj", "z", http=http,
                           token_provider=lambda: "t")
    with pytest.raises(RuntimeError, match="HTTP 403"):
        client.list_nodes()


def _provider(fake, gcs_address="unused"):
    rest = TpuRestClient("proj", "zone", http=fake,
                         token_provider=lambda: "fake-token")
    return GcpTpuPodProvider(
        gcs_address, "proj", "zone", cluster_name="rt-test",
        node_types={
            "v5e_2x4": {"accelerator_type": "v5e-8", "topology": "2x4",
                        "chip_generation": "V5LITE_POD", "num_hosts": 2,
                        "resources": {"CPU": 2.0, "TPU": 8.0}}},
        rest=rest)


def test_provider_lifecycle_against_fake_api(tmp_path):
    """create → list (with slice labels) → terminate, no cluster involved."""
    fake = FakeTpuRestHttp.__new__(FakeTpuRestHttp)  # no booting: stub it
    FakeTpuRestHttp.__init__(fake, "unused", {"2x4": (2, 4)})
    fake._boot_hosts = lambda *a, **k: None
    provider = _provider(fake)

    pid = provider.create_node("v5e_2x4", {"CPU": 2.0, "TPU": 8.0},
                               {"autoscaler_node_type": "v5e_2x4"})
    assert pid.startswith("rt-test-v5e_2x4-")
    nodes = provider.non_terminated_nodes()
    assert len(nodes) == 1
    assert nodes[0]["provider_node_id"] == pid
    assert nodes[0]["node_type"] == "v5e_2x4"
    assert nodes[0]["labels"][LABEL_SLICE_NAME] == pid
    assert nodes[0]["labels"][LABEL_SLICE_TOPOLOGY] == "2x4"
    assert nodes[0]["num_hosts"] == 2
    provider.terminate_node(pid)
    assert provider.non_terminated_nodes() == []
    # cluster filter: nodes of another cluster are invisible
    fake.nodes["other"] = {"name": "other", "state": "READY",
                           "labels": {"rt-cluster": "not-ours"}}
    assert provider.non_terminated_nodes() == []


def test_startup_script_registers_slice_labels():
    fake = FakeTpuRestHttp.__new__(FakeTpuRestHttp)
    FakeTpuRestHttp.__init__(fake, "gcs:123", {"2x4": (2, 4)})
    boots = []
    fake._boot_hosts = lambda *a: boots.append(a)
    provider = _provider(fake, gcs_address="gcs:123")
    pid = provider.create_node("v5e_2x4", {}, {})
    script = provider._startup_script(pid, provider.node_types["v5e_2x4"])
    assert "--address gcs:123" in script
    assert LABEL_SLICE_NAME in script and pid in script
    assert boots and boots[0][0] == pid  # fake booted the slice's hosts


def test_no_relaunch_while_slice_is_booting():
    """Cloud slices provision asynchronously: between create and the hosts
    joining the GCS, the gang demand is still pending — the autoscaler must
    count the in-flight slice as capacity, not launch another (regression:
    the reconcile loop double-provisioned during boot)."""
    fake = FakeTpuRestHttp.__new__(FakeTpuRestHttp)
    FakeTpuRestHttp.__init__(fake, "unused", {"2x4": (2, 4)})
    fake._boot_hosts = lambda *a, **k: None
    provider = _provider(fake)
    node_types = provider.node_types
    load = [{"node_id": "@pending_pg_bundles", "alive": True, "labels": {},
             "total": {}, "available": {},
             "queued_demands": [{"resources": {"TPU": 4.0, "CPU": 0.5},
                                 "count": 2}]}]
    a = StandardAutoscaler({"max_workers": 4, "node_types": node_types},
                           provider, gcs_address="unused")
    a._cluster_load = lambda: load
    assert a.update()["launched"] == 1      # first pass: provision
    assert a.update()["launched"] == 0      # still booting: do NOT repeat
    assert len(fake.nodes) == 1


def test_autoscaler_scales_fake_tpu_slice_for_slice_group():
    """The full TPU gang flow: a pending slice_group() placement group (2
    hosts x 4 chips, STRICT_SPREAD) drives the autoscaler to provision ONE
    fake pod slice; its two REAL node daemons join the GCS with slice
    labels; the PG commits; releasing it idles the slice and the autoscaler
    terminates it as a unit."""
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.util.placement_group import (
        remove_placement_group,
        slice_group,
    )

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 1})
    fake = None
    autoscaler = None
    try:
        c.connect_driver()
        gcs_addr = c.gcs_address
        fake = FakeTpuRestHttp(gcs_addr, {"2x4": (2, 4)},
                               cpus_per_host=1)
        provider = _provider(fake, gcs_address=gcs_addr)
        autoscaler = StandardAutoscaler(
            {"min_workers": 0, "max_workers": 4, "idle_timeout_s": 1.0,
             "node_types": {"v5e_2x4": provider.node_types["v5e_2x4"]}},
            provider, gcs_address=gcs_addr, update_interval_s=0.5)

        pg = slice_group(num_hosts=2, chips_per_host=4, cpus_per_host=0.5)
        # demand visible -> one slice launched
        deadline = time.monotonic() + 30
        launched = 0
        while time.monotonic() < deadline and not launched:
            launched = autoscaler.update()["launched"]
            time.sleep(0.5)
        assert launched == 1
        assert len(fake.nodes) == 1

        # the slice's two hosts join and the gang reservation commits
        assert pg.wait(timeout=60)
        nodes = {n["node_id"]: n for n in
                 ray_tpu.global_worker()._require_backend().nodes()}
        slice_nodes = [n for n in nodes.values()
                       if n["labels"].get(LABEL_SLICE_NAME)]
        assert len(slice_nodes) == 2
        assert {n["labels"]["tpu-worker-id"] for n in slice_nodes} == \
            {"0", "1"}

        # release the gang -> slice idles -> terminated as a unit
        remove_placement_group(pg)
        deadline = time.monotonic() + 30
        terminated = 0
        while time.monotonic() < deadline and not terminated:
            terminated = autoscaler.update()["terminated"]
            time.sleep(0.5)
        assert terminated == 1
        assert fake.nodes == {}
    finally:
        if fake is not None:
            fake.shutdown()
        c.shutdown()
