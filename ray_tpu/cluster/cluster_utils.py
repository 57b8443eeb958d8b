"""Multi-node-in-one-process test cluster.

Reference analog: ``python/ray/cluster_utils.py`` (``Cluster``, ``add_node
:168``, ``remove_node :241``) — real control planes with FAKE resource
counts, so scheduler/placement tests run anywhere: a "TPU node" here is a
raylet that claims ``num_tpus=4``; tasks scheduled to it get chip indices
assigned without any hardware (the chips only matter when user code actually
touches jax).
"""

from __future__ import annotations

from typing import Dict, Optional

from ray_tpu._private.ids import JobID
from ray_tpu.cluster.driver_backend import ClusterHandle
from ray_tpu.cluster.raylet import Raylet


class Cluster:
    def __init__(self, initialize_head: bool = True,
                 head_node_args: Optional[Dict] = None,
                 gcs_persist_path: Optional[str] = None):
        self._handle = ClusterHandle()
        self._handle.start_gcs(persist_path=gcs_persist_path)
        self.head_node: Optional[Raylet] = None
        if initialize_head:
            self.head_node = self.add_node(**(head_node_args or {}))

    @property
    def gcs_address(self) -> str:
        return self._handle.gcs_address

    def add_node(self, *, num_cpus: float = 1, num_tpus: float = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None) -> Raylet:
        return self._handle.add_node(num_cpus=num_cpus, num_tpus=num_tpus,
                                     resources=resources, labels=labels)

    def remove_node(self, node: Raylet) -> None:
        self._handle.remove_node(node)

    def kill_gcs(self) -> None:
        """Chaos: crash the head (reference NodeKiller-style fault
        injection, ``_private/test_utils.py:1401``)."""
        self._handle.kill_gcs()

    def restart_gcs(self) -> str:
        return self._handle.restart_gcs()

    def connect_driver(self, namespace: Optional[str] = None):
        """Attach the global worker to this cluster as a driver."""
        import ray_tpu
        from ray_tpu.cluster.worker_core import ClusterBackend
        from ray_tpu.core.worker import global_worker

        job_id = JobID.from_random()
        raylet = self.head_node or self._handle.raylets[0]
        backend = ClusterBackend(
            gcs_address=self.gcs_address,
            raylet_address=raylet.server.address,
            node_id=raylet.node_id,
            session_name=self._handle.session_name,
            job_id=job_id, role="driver")
        backend.connect()
        global_worker().connect(backend, job_id, "driver")
        return backend

    def shutdown(self) -> None:
        import ray_tpu

        from ray_tpu.util import lifecycle

        if ray_tpu.is_initialized():
            # tears down the backend; the handle still owns the components
            ray_tpu.shutdown()
        self._handle.shutdown()
        lifecycle.close_shutdown(self._handle.session_name)
