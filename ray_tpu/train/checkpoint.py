"""Checkpoints: directory-backed, with orbax pytree helpers.

Reference analog: ``ray.train.Checkpoint`` (``train/_checkpoint.py``) — a
handle to a directory — plus ``CheckpointManager``
(``_internal/checkpoint_manager.py``, top-k retention). TPU-native: pytree
state saves through orbax (async-capable, works with sharded jax.Array);
plain files work too.

Async saves (``save_pytree(..., blocking=False)``) run on a writer thread
so the step loop never waits on serialization; the **completion fence**
(``wait_pending``) runs at ack boundaries only — a checkpoint is fenced
before it crosses a process boundary (``__reduce__``) and before
``CheckpointManager.register`` admits it, so a gang restart can never
resume from a half-written directory.
"""

from __future__ import annotations

import heapq
import json
import os
import shutil
import tempfile
import threading
from typing import Any, Dict, List, Optional

import cloudpickle

# One orbax save at a time in a process: two that overlap (each writer
# thread with a checkpointer of its own) break each other's temporary
# directories or wait for each other without end.
_SAVE_LOCK = threading.Lock()


class Checkpoint:
    """A handle to a checkpoint directory."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self._pending_lock = threading.Lock()
        # rt: guarded-by(_pending_lock) — in-flight async save threads
        self._pending: List[threading.Thread] = []
        self._pending_errors: List[BaseException] = []

    @classmethod
    def from_directory(cls, path: str) -> "Checkpoint":
        return cls(path)

    @classmethod
    def from_dict(cls, data: Dict[str, Any]) -> "Checkpoint":
        """Small state dicts — serialized as a single file."""
        d = tempfile.mkdtemp(prefix="rt_ckpt_")
        with open(os.path.join(d, "_dict_checkpoint.pkl"), "wb") as f:
            cloudpickle.dump(data, f)
        return cls(d)

    def to_dict(self) -> Dict[str, Any]:
        with open(os.path.join(self.path, "_dict_checkpoint.pkl"), "rb") as f:
            return cloudpickle.load(f)

    def as_directory(self) -> str:
        return self.path

    # ---- pytree state (orbax) ----------------------------------------------
    def _save_pytree_sync(self, tree: Any, name: str) -> None:
        import orbax.checkpoint as ocp

        path = os.path.join(self.path, name)
        with _SAVE_LOCK:
            shutil.rmtree(path, ignore_errors=True)
            with ocp.StandardCheckpointer() as ckptr:
                ckptr.save(path, tree)

    def save_pytree(self, tree: Any, name: str = "state", *,
                    blocking: Optional[bool] = None) -> None:
        """Save a pytree under this checkpoint directory.

        ``blocking=False`` hands the serialization to a writer thread and
        returns immediately — the off-step-path product configuration. The
        save is only guaranteed durable after :meth:`wait_pending` (called
        automatically when the checkpoint is pickled across a process
        boundary, and by ``CheckpointManager.register``). Default
        (``blocking=None``): inside a train session the trainer's
        ``FastPathConfig.async_checkpoint`` decides; standalone saves
        block (durable on return, the pre-fast-path contract).
        """
        if blocking is None:
            from ray_tpu.train import session as _session_mod

            live = _session_mod._session  # None outside a train loop
            blocking = (True if live is None
                        else not live.fast_path.async_checkpoint)
        if blocking:
            self._save_pytree_sync(tree, name)
            return
        # Donation safety: the step loop donates (params, opt_state) into
        # the NEXT launch, which would delete the buffers this writer is
        # about to serialize. Snapshot device arrays with an on-device copy
        # (async dispatch — no host sync on the calling thread).
        try:
            import jax
            import jax.numpy as jnp

            tree = jax.tree.map(
                lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x,
                tree)
        except ImportError:  # host-only trees save as-is
            pass

        def writer():
            try:
                self._save_pytree_sync(tree, name)
            except BaseException as e:  # noqa: BLE001 — re-raised at fence
                with self._pending_lock:
                    self._pending_errors.append(e)

        t = threading.Thread(target=writer, daemon=True,
                             name="rt-ckpt-writer")
        with self._pending_lock:
            self._pending.append(t)
        t.start()

    def wait_pending(self, timeout: Optional[float] = None) -> None:
        """The completion fence: block until every async save of this
        checkpoint is durable; re-raise the first writer failure. Idempotent
        and cheap when nothing is pending."""
        with self._pending_lock:
            pending = list(self._pending)
        for t in pending:
            t.join(timeout)
            if t.is_alive():
                raise TimeoutError(
                    f"checkpoint save still running after {timeout}s "
                    f"({self.path})")
        with self._pending_lock:
            self._pending = [t for t in self._pending if t.is_alive()]
            if self._pending_errors:
                err = self._pending_errors[0]
                self._pending_errors = []
                raise err

    def load_pytree(self, name: str = "state", abstract_tree: Any = None) -> Any:
        import orbax.checkpoint as ocp

        self.wait_pending()
        path = os.path.join(self.path, name)
        with ocp.StandardCheckpointer() as ckptr:
            return ckptr.restore(path, abstract_tree) if abstract_tree is not None \
                else ckptr.restore(path)

    def __reduce__(self):
        # pickling IS an ack boundary: the receiving process (driver,
        # another worker) must never observe a half-written directory
        self.wait_pending()
        return (Checkpoint, (self.path,))

    def __repr__(self):
        return f"Checkpoint({self.path})"


class CheckpointManager:
    """Top-k retention by score (reference: ``_internal/checkpoint_manager.py``).

    Each entry's score is computed ONCE at ``register`` and kept on a heap
    keyed (score, age): eviction pops the worst entry directly instead of
    re-scoring and re-sorting the full retention list per call.
    """

    def __init__(self, run_dir: str, num_to_keep: Optional[int] = None,
                 score_attribute: Optional[str] = None, score_order: str = "max"):
        self.run_dir = run_dir
        self.num_to_keep = num_to_keep
        self.score_attribute = score_attribute
        self.score_order = score_order
        self._entries: List[Dict] = []
        self._heap: List = []  # (rank_key, seq, entry) — min = evict first
        self._counter = 0
        os.makedirs(run_dir, exist_ok=True)

    def register(self, checkpoint: Checkpoint, metrics: Dict[str, Any]) -> Checkpoint:
        """Move the checkpoint under the run dir and apply retention.

        Fences any in-flight async save first: an unfinished checkpoint is
        never acked into the manager (the gang-restart recovery source).
        """
        checkpoint.wait_pending()
        dest = os.path.join(self.run_dir, f"checkpoint_{self._counter:06d}")
        seq = self._counter
        self._counter += 1
        if checkpoint.path != dest:
            shutil.move(checkpoint.path, dest)
        entry = {"path": dest, "metrics": dict(metrics),
                 "score": self._score_value(metrics), "seq": seq}
        self._entries.append(entry)
        # rank_key: keep-most-recent mode ranks purely by age (seq breaks
        # the tie anyway); score mode ranks by the once-computed score
        rank = entry["score"] if self.score_attribute else 0.0
        heapq.heappush(self._heap, (rank, seq, entry))
        with open(os.path.join(dest, "_metrics.json"), "w") as f:
            json.dump(entry["metrics"], f, default=str)
        self._apply_retention()
        return Checkpoint(dest)

    def _score_value(self, metrics: Dict[str, Any]) -> float:
        v = metrics.get(self.score_attribute, 0.0) \
            if self.score_attribute else 0.0
        try:
            v = float(v)
        except (TypeError, ValueError):
            v = 0.0
        return v if self.score_order == "max" else -v

    def _apply_retention(self) -> None:
        if self.num_to_keep is None:
            return
        while len(self._entries) > self.num_to_keep and self._heap:
            # entries leave _entries only here, right after their pop, so
            # a popped entry is always live
            _, _, entry = heapq.heappop(self._heap)
            shutil.rmtree(entry["path"], ignore_errors=True)
            self._entries.remove(entry)

    @property
    def best_checkpoint(self) -> Optional[Checkpoint]:
        if not self._entries:
            return None
        if self.score_attribute:
            entry = max(self._entries, key=lambda e: e["score"])
        else:
            entry = self._entries[-1]
        return Checkpoint(entry["path"])

    @property
    def latest_checkpoint(self) -> Optional[Checkpoint]:
        return Checkpoint(self._entries[-1]["path"]) if self._entries else None
