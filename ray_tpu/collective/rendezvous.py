"""jax.distributed bootstrap through the GCS KV.

The TPU replacement for the reference's NCCL process-group setup
(``train/torch/config.py:64`` — rank-0 TCP rendezvous + env vars): rank 0
publishes its coordinator address under a KV key; other ranks poll the key;
then every rank calls ``jax.distributed.initialize`` and XLA's collectives
see the full multi-host device set. The KV plays the role the named
rendezvous actor plays for NCCL unique ids in the reference
(``collective_group/nccl_util.py``).
"""

from __future__ import annotations

import os
import socket
import time
from typing import Optional


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _kv_key(group_name: str) -> str:
    return f"@rendezvous/{group_name}/coordinator"


def _publish_or_await_coordinator(backend, key: str, rank: int,
                                  coordinator_ip: Optional[str],
                                  timeout_s: float, what: str) -> str:
    """Rank 0 publishes ip:port under ``key``; other ranks poll it.
    The one rendezvous used by both the jax and torch bootstraps."""
    if rank == 0:
        ip = coordinator_ip or socket.gethostbyname(socket.gethostname())
        address = f"{ip}:{_free_port()}"
        backend.kv_put(key, address.encode())
        return address
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        raw = backend.kv_get(key)
        if raw:
            return raw.decode()
        time.sleep(0.1)
    raise TimeoutError(
        f"{what}: coordinator address not published within {timeout_s}s")


def bootstrap_jax_distributed(world_size: int, rank: int,
                              group_name: str = "train",
                              coordinator_ip: Optional[str] = None,
                              timeout_s: float = 60.0,
                              local_device_ids=None,
                              instance_token: Optional[str] = None) -> None:
    """Call from every member of a gang (one process per host).

    Single-process gangs (world_size == 1) skip distributed init entirely —
    jax sees its local devices and meshes work unchanged.

    ``instance_token``, when given, namespaces the rendezvous key so a rank
    can never pick up the coordinator address a *previous* gang with the
    same group_name left in the KV. Callers may equivalently bake a fresh
    uuid into ``group_name`` itself — that is what ``JaxTrainer`` does
    (``train/trainer.py`` generates a per-restart group name), so the token
    is the explicit form of the same convention. Without either, the key is
    deleted after a successful init (rank 0, once every rank has connected)
    to keep sequential reuse of the default name safe.
    """
    import ray_tpu
    from ray_tpu.core.worker import global_worker

    if world_size <= 1:
        return
    backend = global_worker()._require_backend()
    key = _kv_key(group_name if instance_token is None
                  else f"{group_name}/{instance_token}")
    try:
        address = _publish_or_await_coordinator(
            backend, key, rank, coordinator_ip, timeout_s,
            f"rendezvous {group_name!r}")
    except TimeoutError as e:
        # CPU-graceful covers the await too: when a CPU gang's rank 0
        # degraded (and cleaned its key), the peers must degrade with it
        # rather than die on the missing coordinator
        if _rendezvous_strict() or not _cpu_only_backend():
            raise
        import logging

        logging.getLogger("ray_tpu.rendezvous").warning(
            "rendezvous for %r timed out on a CPU-only host (%s); rank %d "
            "continues with local jax", group_name, e, rank)
        # "local jax" must actually be local: a pooled worker may still
        # hold the PREVIOUS gang's coordinator client (see the teardown
        # note below) — shut it down on this degrade path too
        _shutdown_previous_gang()
        return
    import jax

    _shutdown_previous_gang()

    try:
        # bound the rendezvous: a gang member that died pre-connect must
        # fail THIS rank loudly in timeout_s, not hang the whole gang on a
        # default 5-minute wait
        jax.distributed.initialize(
            coordinator_address=address, num_processes=world_size,
            process_id=rank, local_device_ids=local_device_ids,
            initialization_timeout=max(1, int(timeout_s)))
    except Exception as e:  # noqa: BLE001
        # CPU-graceful: on a CPU-only host a failed process-group bootstrap
        # degrades to local (un-distributed) jax — the gang still runs, each
        # rank seeing its own devices — so the multi-host product path can
        # be exercised (and chaos-tested) without TPUs. On real accelerator
        # hosts, or with RT_RENDEZVOUS_STRICT=1, the failure is fatal: a
        # silent single-host fallback there would train the wrong program.
        if _rendezvous_strict() or not _cpu_only_backend():
            raise
        import logging

        logging.getLogger("ray_tpu.rendezvous").warning(
            "jax.distributed bootstrap for %r failed on a CPU-only host "
            "(%s: %s); rank %d continues with local jax "
            "(set RT_RENDEZVOUS_STRICT=1 to make this fatal)",
            group_name, type(e).__name__, e, rank)
        if rank == 0:
            # clean the rendezvous key on the degrade path too — a stale
            # coordinator address must not greet the next gang reusing
            # this group_name (peers that miss it degrade the same way
            # via the await-timeout branch above)
            try:
                backend.kv_del(key)
            except Exception:  # noqa: BLE001
                pass
        return
    if rank == 0:
        # initialize() returns only after every process connected, so all
        # ranks have read the key — safe to clear it now.
        try:
            backend.kv_del(key)
        except Exception:
            pass


def _shutdown_previous_gang() -> None:
    """Elastic-restart lifecycle (SURVEY.md §7 hard part: "jax.distributed
    lifecycle across actor restarts"): a pooled/reused worker process may
    carry a previous gang's coordinator client whose peers are gone — tear
    it down and drop cached backends so the new device topology can
    register (or so a degraded rank truly runs LOCAL jax). NCCL's
    equivalent is destroy_process_group before re-init."""
    import jax

    if jax.distributed.is_initialized():
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001
            # The old gang's coordinator may already be dead (that's often
            # WHY we're re-bootstrapping) — a failed goodbye to it must not
            # fail the new gang's hello.
            pass
        import jax.extend.backend as _jeb

        _jeb.clear_backends()


def _rendezvous_strict() -> bool:
    return os.environ.get("RT_RENDEZVOUS_STRICT", "").lower() in (
        "1", "true", "yes", "on")


def _cpu_only_backend() -> bool:
    """True when this process's jax sees no accelerator platform (the
    CPU-graceful degrade gate). Conservative: unknown -> True only for
    explicit JAX_PLATFORMS=cpu; a probe failure assumes accelerators."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return True
    try:
        import jax

        return jax.default_backend() == "cpu"
    except Exception:  # noqa: BLE001 — can't tell: don't mask a TPU gang
        return False


def clear_rendezvous(group_name: str = "train") -> None:
    from ray_tpu.core.worker import global_worker

    global_worker()._require_backend().kv_del(_kv_key(group_name))


def bootstrap_torch_distributed(world_size: int, rank: int,
                                group_name: str = "train",
                                backend_name: str = "gloo",
                                timeout_s: float = 60.0) -> None:
    """torch.distributed process-group bootstrap through the same GCS-KV
    rendezvous (reference: ``train/torch/config.py:64`` —
    ``_setup_torch_process_group`` with rank-0 TCP store). CPU torch uses
    gloo; the coordinator address rides the KV exactly like the jax path."""
    import ray_tpu  # noqa: F401 — backend access below
    from ray_tpu.core.worker import global_worker

    if world_size <= 1:
        return
    backend = global_worker()._require_backend()
    key = _kv_key(f"torch/{group_name}")
    address = _publish_or_await_coordinator(
        backend, key, rank, None, timeout_s,
        f"torch rendezvous {group_name!r}")
    import datetime

    import torch.distributed as dist

    host, port = address.rsplit(":", 1)
    dist.init_process_group(
        backend_name, init_method=f"tcp://{host}:{port}",
        rank=rank, world_size=world_size,
        timeout=datetime.timedelta(seconds=timeout_s))
    if rank == 0:
        try:
            backend.kv_del(key)
        except Exception:  # noqa: BLE001
            pass
