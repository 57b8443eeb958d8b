"""JaxTrainer: the data-parallel trainer driving a gang of JAX workers.

Reference analog: ``DataParallelTrainer`` (``train/data_parallel_trainer.py:59``)
+ ``BackendExecutor`` (``_internal/backend_executor.py:46``): create the gang
in a placement group, bootstrap the collective backend, run the user loop on
every rank, drain reported (metrics, checkpoint) rounds, restart the gang
from the last checkpoint on failure (``FailureConfig.max_failures`` —
elastic-restart, like the reference). The torch/NCCL process-group bootstrap
(``train/torch/config.py:64``) is replaced by ``jax.distributed`` over the
GCS-KV rendezvous.
"""

from __future__ import annotations

import os
import time
import uuid
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.train.worker_group import WorkerGroup
from ray_tpu.util import lifecycle

DEFAULT_STORAGE = "/tmp/ray_tpu_results"


class TrainingFailedError(RuntimeError):
    pass


class Result:
    def __init__(self, metrics: Optional[Dict], checkpoint: Optional[Checkpoint],
                 path: str, error: Optional[str] = None,
                 metrics_history: Optional[List[Dict]] = None):
        self.metrics = metrics
        self.checkpoint = checkpoint
        self.path = path
        self.error = error
        self.metrics_history = metrics_history or []

    def __repr__(self):
        return (f"Result(metrics={self.metrics}, checkpoint={self.checkpoint}, "
                f"error={self.error})")


class JaxTrainer:
    def __init__(self, train_loop_per_worker: Callable,
                 *, train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 use_jax_distributed: bool = False,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.train_fn = train_loop_per_worker
        self.train_config = train_loop_config or {}
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.use_jax_distributed = use_jax_distributed
        self.resume_checkpoint = resume_from_checkpoint

    @property
    def _dist_bootstrap(self):
        return ("bootstrap_jax_distributed" if self.use_jax_distributed
                else None)

    # -- dataset sharding -----------------------------------------------------
    def _shard_datasets(self, rank: int, world: int) -> Dict[str, Any]:
        shards = {}
        datasets = getattr(self, "_attempt_datasets", None) or self.datasets
        for name, ds in datasets.items():
            split = getattr(ds, "streaming_split", None)
            if split is not None:
                shards[name] = ds.streaming_split(world)[rank]
            elif isinstance(ds, (list, tuple)):
                shards[name] = list(ds[rank::world])
            else:
                shards[name] = ds  # caller shards by rank inside the loop
        return shards

    # -- the fit loop ---------------------------------------------------------
    def fit(self) -> Result:
        self._t_fit = time.time()
        name = self.run_config.name or f"JaxTrainer_{uuid.uuid4().hex[:8]}"
        storage = self.run_config.storage_path or DEFAULT_STORAGE
        run_dir = os.path.join(storage, name)
        os.makedirs(run_dir, exist_ok=True)
        ckpt_cfg = self.run_config.checkpoint_config
        manager = CheckpointManager(
            run_dir, num_to_keep=ckpt_cfg.num_to_keep,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order)

        failures_left = self.run_config.failure_config.max_failures
        latest_checkpoint = self.resume_checkpoint
        metrics_history: List[Dict] = []
        last_error: Optional[str] = None

        while True:
            # per-attempt dataset copies: a retry after worker death must
            # re-execute the dataset (fresh coordinator), and concurrent
            # trials sharing one Dataset object (Train-on-Tune, local
            # backend) must not see each other's split caches — Dataset's
            # __getstate__ scrubs the cache, so copy() isolates it
            import copy as _copy

            self._attempt_datasets = {
                name: (_copy.copy(ds)
                       if hasattr(ds, "reset_streaming_split") else ds)
                for name, ds in self.datasets.items()}
            group = WorkerGroup(self.scaling, name)
            with lifecycle.span("gang_placed", parent="trainer_start"):
                group.start()
            try:
                if self._dist_bootstrap and self.scaling.num_workers > 1:
                    group.run(self._dist_bootstrap,
                              f"{name}:{uuid.uuid4().hex[:6]}", timeout=300)
                n = self.scaling.num_workers
                ray_tpu.get([
                    w.start.remote(self.train_fn, self.train_config,
                                   latest_checkpoint,
                                   self._shard_datasets(i, n),
                                   self.run_config.fast_path)
                    for i, w in enumerate(group.workers)], timeout=300)
                error = self._drain_results(group, manager, metrics_history)
                if error is None:
                    final = metrics_history[-1] if metrics_history else None
                    return Result(final, manager.best_checkpoint
                                  or manager.latest_checkpoint,
                                  run_dir, None, metrics_history)
                last_error = error
                if failures_left == 0:
                    raise TrainingFailedError(
                        f"training failed (no restart budget left): {error}")
                failures_left -= 1
                self._emit_gang_restart(
                    name, error,
                    self.run_config.failure_config.max_failures
                    - failures_left)
                latest_checkpoint = manager.latest_checkpoint or latest_checkpoint
            finally:
                group.shutdown()

    @staticmethod
    def _emit_gang_restart(name: str, error: str, restart_num: int) -> None:
        """Stamp a FailureConfig-driven gang restart into the failure plane
        (PR 5): a FailureEvent on the feed (visible in `rt errors` /
        `rt doctor`) plus a `rt_actor_restarts_total` tick, so train-level
        recovery is observable like every other restart. Best-effort —
        recovery must not fail on telemetry."""
        try:
            from ray_tpu.core import failure as F
            from ray_tpu.core.worker import global_worker

            backend = global_worker().backend
            if backend is not None and hasattr(backend, "_gcs"):
                err = ((error or "").strip().splitlines() or [""])[0][:300]
                category = (F.WORKER_CRASH if "died" in err
                            else F.TASK_ERROR)
                F.emit(backend.io.spawn, backend._gcs, category,
                       f"JaxTrainer gang restart {restart_num} "
                       f"(from last checkpoint): {err}",
                       name="JaxTrainer", experiment=name,
                       restarting=True, gang_restart=True)
            from ray_tpu.util import metrics as M

            M.get_or_create(
                M.Counter, "rt_actor_restarts_total",
                "Actor restarts scheduled by the GCS after a failure").inc()
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _drain_results(self, group: WorkerGroup, manager: CheckpointManager,
                       history: List[Dict]) -> Optional[str]:
        """Drain symmetric report rounds; returns error string on failure."""
        active = list(group.workers)
        while active:
            try:
                round_results = ray_tpu.get(
                    [w.next_result.remote() for w in active])
            except Exception as e:  # actor died (worker process crash)
                return f"worker died: {e!r}"
            if active[0] is group.workers[0]:
                self._note_launches(round_results[0].get("launches"))
            errors = [r for r in round_results if r["type"] == "error"]
            if errors:
                return errors[0].get("message", "unknown") + "\n" + \
                    errors[0].get("traceback", "")
            reports = [(w, r) for w, r in zip(active, round_results)
                       if r["type"] == "report"]
            if reports:
                rank0_report = reports[0][1]
                self._note_first_launch(rank0_report.get("first_launch"))
                metrics = dict(rank0_report["metrics"])
                ckpt = rank0_report.get("checkpoint")
                if ckpt is not None:
                    saved = manager.register(ckpt, metrics)
                    metrics["checkpoint_path"] = saved.path
                metrics["_round"] = len(history)
                metrics["_timestamp"] = time.time()
                history.append(metrics)
            active = [w for w, r in zip(active, round_results)
                      if r["type"] == "report"]
        return None


    @staticmethod
    def _note_launches(totals: Optional[Dict[str, Any]]) -> None:
        """``train_launches`` on the lifecycle record when rank 0 says its
        loop is done: the extent of its ``StepDriver``'s launches and what
        their recorder counted (launches, steps, a sparse model's routing
        counters), kept here because the worker and its recorder are gone
        by the time anyone asks."""
        if totals:
            totals = dict(totals)
            lifecycle.record("train_launches", totals.pop("t0"),
                             totals.pop("t1"), **totals)

    def _note_first_launch(self, first: Optional[Dict[str, float]]) -> None:
        """``trainer_start`` and its child ``first_launch`` on the lifecycle
        record, when rank 0's reply says its first launch has returned:
        ``fit``'s entry until then, and from the gang's placing until then
        (the worker's loop up to and with its first launch)."""
        placed = lifecycle.last("gang_placed")
        if first is None or placed is None:
            return
        lifecycle.record("first_launch", placed["t1"], first["t_done"],
                         parent="trainer_start",
                         compile_s=first["compile_s"])
        lifecycle.record("trainer_start", self._t_fit, first["t_done"])


class TorchTrainer(JaxTrainer):
    """Data-parallel torch training (reference: ``train/torch/TorchTrainer``).

    Same gang/report/checkpoint machinery as JaxTrainer; the collective
    backend is a torch.distributed gloo process group bootstrapped through
    the GCS-KV rendezvous (CPU torch — this framework's compute path is
    JAX/TPU, but torch users keep their Train API). The user loop calls
    ``torch.distributed`` collectives / wraps modules in DDP as usual.
    """

    def __init__(self, *args, use_torch_distributed: bool = True, **kwargs):
        kwargs.pop("use_jax_distributed", None)
        super().__init__(*args, **kwargs)
        self.use_torch_distributed = use_torch_distributed

    @property
    def _dist_bootstrap(self):
        return ("bootstrap_torch_distributed" if self.use_torch_distributed
                else None)
