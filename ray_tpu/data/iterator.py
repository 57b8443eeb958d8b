"""Consumption: batch iteration and streaming splits.

Reference analogs: ``data/_internal/block_batching/iter_batches.py``
(batching across block boundaries + prefetch), ``DataIterator``
(``data/iterator.py``), and ``streaming_split`` /
``_internal/iterator/stream_split_iterator.py`` (a coordinator actor hands
blocks to N concurrent consumers — Train workers — round-robin).
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

import ray_tpu
from ray_tpu.data import block as B


def batches_from_blocks(blocks: Iterator[B.Block], batch_size: Optional[int],
                        batch_format: str = "numpy", drop_last: bool = False,
                        local_shuffle_buffer_size: Optional[int] = None,
                        seed: Optional[int] = None) -> Iterator[Any]:
    """Re-chunk a stream of blocks into fixed-size batches."""
    rng = np.random.default_rng(seed)
    buf: List[B.Block] = []
    buffered = 0
    min_buffer = local_shuffle_buffer_size or 0

    def drain(final: bool) -> Iterator[Any]:
        nonlocal buf, buffered
        while buf and (batch_size is None or buffered >= batch_size
                       or (final and buffered > 0)):
            if batch_size is None:
                merged, buf, buffered = B.concat(buf), [], 0
                yield B.to_batch(merged, batch_format)
                return
            merged = B.concat(buf)
            if local_shuffle_buffer_size and B.num_rows(merged) > 1:
                merged = B.take_rows(
                    merged, rng.permutation(B.num_rows(merged)))
            take = min(batch_size, B.num_rows(merged))
            if take < batch_size and not final:
                buf, buffered = [merged], B.num_rows(merged)
                return
            if take < batch_size and drop_last:
                buf, buffered = [], 0
                return
            yield B.to_batch(B.slice_block(merged, 0, take), batch_format)
            rest = B.slice_block(merged, take, B.num_rows(merged))
            buf = [rest] if B.num_rows(rest) else []
            buffered = B.num_rows(rest)

    for blk in blocks:
        if B.num_rows(blk) == 0:
            continue
        buf.append(blk)
        buffered += B.num_rows(blk)
        if batch_size is not None and buffered >= max(batch_size, min_buffer):
            yield from drain(final=False)
    yield from drain(final=True)


def prefetched(it: Iterator[Any], depth: int) -> Iterator[Any]:
    """Run the upstream iterator in a thread, `depth` items ahead.

    The producer must not block forever when the consumer abandons the
    iterator early (``break`` mid-epoch) — a stop event unwinds it and
    releases its buffered blocks.
    """
    if depth <= 0:
        yield from it
        return
    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _END = object()
    err: List[BaseException] = []
    stop = threading.Event()

    def producer():
        try:
            for item in it:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:
            err.append(e)
        finally:
            # the END sentinel must arrive even when the queue is full —
            # keep trying unless the consumer already stopped
            while not stop.is_set():
                try:
                    q.put(_END, timeout=0.1)
                    break
                except queue.Full:
                    continue

    t = threading.Thread(target=producer, daemon=True)
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        stop.set()


class JaxBatchIterator:
    """Iterator of jnp device batches with ingest-vs-compute accounting.

    The time THIS iterator spends producing a batch (pipeline pull +
    host→device put) is **ingest**; the time the consumer holds the batch
    between ``next()`` calls (their train step) is **compute**.
    ``report()`` states which side gates the run — the number VERDICT asks
    for ("host-side input pipelines that keep chips fed"): a training loop
    is *ingest-limited* when the chips wait on data, *compute-limited* when
    the pipeline keeps up.

    ``stack`` advertises the K-stacking factor (``iter_jax_batches(stack=K)``
    yields [k, B, ...] leaves, k == K except a ragged tail; None: per-step
    [B, ...] batches) — the StepDriver keys its fused-vs-single dispatch
    off it.
    """

    def __init__(self, inner: Iterator[Dict[str, Any]],
                 stack: Optional[int] = None):
        self._inner = inner
        self.stack = stack
        self.ingest_s = 0.0
        self.compute_s = 0.0
        # the first pull pays pipeline spin-up (dataset execution, actor
        # round trips, prefetch warmup) — booked separately so the verdict
        # describes the steady state, like bench excludes compile/warmup
        self.cold_start_s = 0.0
        self.batches = 0
        self._t_resume: Optional[float] = None

    def __iter__(self) -> "JaxBatchIterator":
        return self

    def __next__(self) -> Dict[str, Any]:
        t0 = time.perf_counter()
        if self._t_resume is not None:
            self.compute_s += t0 - self._t_resume
        try:
            batch = next(self._inner)
        except StopIteration:
            self._t_resume = None
            raise
        if self.batches == 0:
            self.cold_start_s += time.perf_counter() - t0
        else:
            self.ingest_s += time.perf_counter() - t0
        self._t_resume = time.perf_counter()
        self.batches += 1
        return batch

    def report(self) -> Dict[str, Any]:
        total = self.ingest_s + self.compute_s
        verdict = ("ingest-limited" if self.ingest_s > self.compute_s
                   else "compute-limited")
        return {
            "verdict": verdict,
            "ingest_s": round(self.ingest_s, 4),
            "compute_s": round(self.compute_s, 4),
            "cold_start_s": round(self.cold_start_s, 4),
            "ingest_frac": round(self.ingest_s / total, 4) if total else 0.0,
            "batches": self.batches,
            "batches_per_s": (round(self.batches / total, 2)
                              if total else 0.0),
        }

    def verdict(self) -> str:
        r = self.report()
        return (f"{r['verdict']}: ingest {r['ingest_s']:.3f}s vs compute "
                f"{r['compute_s']:.3f}s over {r['batches']} batch(es) "
                f"(ingest fraction {r['ingest_frac']:.0%})")


class DataIterator:
    """One consumer's view of a stream of blocks."""

    def __init__(self, block_iter_fn):
        self._block_iter_fn = block_iter_fn

    def _blocks(self) -> Iterator[B.Block]:
        for ref in self._block_iter_fn():
            yield ray_tpu.get(ref) if hasattr(ref, "hex") else ref

    def iter_batches(self, *, batch_size: Optional[int] = 256,
                     batch_format: str = "numpy", drop_last: bool = False,
                     prefetch_batches: int = 1,
                     local_shuffle_buffer_size: Optional[int] = None,
                     local_shuffle_seed: Optional[int] = None) -> Iterator[Any]:
        it = batches_from_blocks(
            self._blocks(), batch_size, batch_format, drop_last,
            local_shuffle_buffer_size, local_shuffle_seed)
        return prefetched(it, prefetch_batches)

    def iter_rows(self) -> Iterator[Dict[str, Any]]:
        for blk in self._blocks():
            yield from B.iter_rows(blk)

    def iter_torch_batches(self, *, batch_size: int = 256,
                           drop_last: bool = False, dtypes=None,
                           device: Optional[str] = None,
                           prefetch_batches: int = 2
                           ) -> Iterator[Dict[str, Any]]:
        """Batches as torch tensors (reference: ``iter_torch_batches``) —
        the feed path for TorchTrainer loops."""
        import torch

        for batch in self.iter_batches(batch_size=batch_size,
                                       drop_last=drop_last,
                                       prefetch_batches=prefetch_batches):
            out = {}
            for k, v in batch.items():
                t = torch.as_tensor(v)
                dt = (dtypes.get(k) if isinstance(dtypes, dict) else dtypes) \
                    if dtypes is not None else None
                if dt is not None or device is not None:
                    t = t.to(device=device, dtype=dt)  # one cast+transfer
                out[k] = t
            yield out

    def iter_jax_batches(self, *, batch_size: int = 256,
                         drop_last: bool = True, dtype=None,
                         prefetch_batches: int = 2,
                         stack: Optional[int] = None) -> "JaxBatchIterator":
        """Batches as jnp device arrays — the TPU feed path (host numpy →
        device put; drop_last defaults True to keep shapes static for jit).

        ``stack=K`` groups K consecutive batches into one [K, B, ...] tree
        (host-side ``np.stack``, then one device put) — the fused-K launch
        feed, for every K >= 1: a launch of one step is a group of one,
        [1, B, ...], so a loop written for K reads the same at 1. Without
        ``stack`` the batches come a step at a time, [B, ...].
        A ragged tail yields [k < K, B, ...]; the StepDriver
        single-steps it. The device conversion itself runs ``prefetch_batches``
        ahead on a bounded lookahead thread, so at steady state the
        consumer's ``next()`` returns an already-materialized device batch
        and ``report()`` can honestly say compute-limited. Caveat: the put
        lands on the default device — on a MULTI-device mesh the driver's
        plan placement re-shards each group (one extra device copy); feed
        the driver host batches there and let it stack+place instead.

        Returns a ``JaxBatchIterator``: iterate as before, and call
        ``.report()`` / ``.verdict()`` afterwards for the
        ingest-vs-compute breakdown ("is the pipeline keeping the chips
        fed?")."""
        import numpy as np

        import jax.numpy as jnp

        if stack is not None and stack < 1:
            raise ValueError(f"stack must be >= 1 or None, got {stack}")

        def host_gen():
            pend = []
            for batch in self.iter_batches(batch_size=batch_size,
                                           drop_last=drop_last,
                                           prefetch_batches=prefetch_batches):
                batch = {k: (np.asarray(v) if dtype is None
                             else np.asarray(v).astype(dtype))
                         for k, v in batch.items()}
                if stack is None:
                    yield batch
                    continue
                if pend and any(
                        np.shape(batch[k]) != np.shape(pend[0][k])
                        for k in pend[0]):
                    # a ragged-B batch (drop_last=False) can't stack with
                    # full ones — flush the group, let it ride alone
                    yield {k: np.stack([b[k] for b in pend])
                           for k in pend[0]}
                    pend = []
                pend.append(batch)
                if len(pend) == stack:
                    yield {k: np.stack([b[k] for b in pend])
                           for k in pend[0]}
                    pend = []
            if pend:  # ragged tail: [k < K, B, ...]
                yield {k: np.stack([b[k] for b in pend]) for k in pend[0]}

        def device_gen():
            for batch in host_gen():
                yield {k: jnp.asarray(v) for k, v in batch.items()}

        return JaxBatchIterator(prefetched(device_gen(), prefetch_batches),
                                stack=stack)


@ray_tpu.remote
class _SplitCoordinator:
    """Hands out block *refs* of one executing dataset to N consumers.

    Reference: ``StreamSplitDataIterator`` — blocks are assigned first-come
    (each consumed exactly once); ``equal=True`` balances by row count.
    Only refs flow through this actor — the payloads resolve directly from
    the object plane at each consumer (no coordinator copy bottleneck).

    There is exactly ONE coordinator per ``streaming_split`` call, shared by
    all N iterators, so every consumer sees a split of the *same* dataset
    execution (a private per-rank execution would silently duplicate/drop
    rows under unseeded shuffles). Multi-epoch: when every split has drained
    its queue and requests the next epoch, the dataset is re-executed —
    a barrier across splits, matching the reference's per-epoch re-execution.
    """

    def __init__(self, n: int, equal: bool):
        self._n = n
        self._equal = equal
        self._lock = threading.Lock()
        self._payload = None
        self._filled_epoch = -1
        self._requested = [0] * n
        self._queues: List[collections.deque] = [collections.deque()
                                                 for _ in range(n)]

    def start(self, dataset_payload) -> None:
        """Registers the dataset to execute (first caller wins)."""
        with self._lock:
            if self._payload is None:
                self._payload = dataset_payload

    def _fill(self) -> None:
        # caller holds self._lock
        refs = list(self._payload._execute_refs())
        if self._equal:
            from ray_tpu.data.dataset import _num_rows_task

            rows = ray_tpu.get(
                [_num_rows_task.remote(r) for r in refs])
            order = np.argsort(rows)[::-1]
            loads = [0] * self._n
            for i in order:
                j = int(np.argmin(loads))
                self._queues[j].append(refs[i])
                loads[j] += rows[i]
        else:
            for i, r in enumerate(refs):
                self._queues[i % self._n].append(r)

    def next_block_ref(self, split_idx: int, epoch: int):
        """Returns ("block", ref) | ("end", None) | ("wait", None)."""
        with self._lock:
            if epoch > self._requested[split_idx]:
                # requesting epoch e declares all earlier epochs finished for
                # this split — drop any abandoned remainder (consumer broke
                # out of the iterator mid-epoch) so the barrier can't
                # deadlock on undrained refs
                self._requested[split_idx] = epoch
                self._queues[split_idx].clear()
            if epoch > self._filled_epoch:
                # Next epoch starts only once EVERY split asked for it
                # (each having thereby abandoned/finished the previous one).
                if min(self._requested) >= epoch:
                    for q in self._queues:
                        q.clear()
                    self._fill()
                    self._filled_epoch = epoch
                else:
                    return ("wait", None)
            q = self._queues[split_idx]
            if q:
                return ("block", q.popleft())
            return ("end", None)


class StreamSplitIterator(DataIterator):
    """One consumer's split. Re-iterating starts the next epoch (the dataset
    re-executes once all sibling splits also finish the current epoch)."""

    def __init__(self, coordinator, split_idx: int, dataset):
        self._coord = coordinator
        self._idx = split_idx
        self._ds = dataset
        self._started = False
        self._epoch = 0
        super().__init__(self._pull_blocks)

    def _pull_blocks(self):
        import time

        if not self._started:
            # ship the dataset (plan closures) once, not per block
            ray_tpu.get(self._coord.start.remote(self._ds))
            self._started = True
        epoch = self._epoch
        self._epoch += 1
        delay = 0.02
        while True:
            status, ref = ray_tpu.get(
                self._coord.next_block_ref.remote(self._idx, epoch))
            if status == "wait":
                # barrier wait with backoff: a straggler sibling can lag a
                # whole epoch — don't hammer the coordinator at 20Hz
                time.sleep(delay)
                delay = min(delay * 1.6, 1.0)
                continue
            delay = 0.02
            if status == "end":
                return
            yield ray_tpu.get(ref)
