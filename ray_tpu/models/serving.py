"""Continuous batching for autoregressive serving.

The vLLM-style capability (no reference counterpart — Ray pairs with
external engines for this), designed static-shape for XLA/TPU instead of
paged dynamic memory:

- ONE static KV cache [L, max_slots, max_len, hkv, hd]; a request
  occupies a SLOT for its lifetime. No paging, no dynamic shapes — the
  compiled programs never change as requests come and go. The cache is
  one tree of buffers by layer kind (``generate.init_cache``): ``k`` and
  ``v`` for the layers that attend and, for a model with recurrent layers
  (``models/hybrid.py``), their state ``ssm`` [L, max_slots, n, h p]
  (channels minor: ``ops/ssm.py`` says why) and
  convolution tail ``conv`` beside them, and for one with window layers
  (``models/sambay.py``) their rings ``wk``/``wv`` of the last
  ``sliding_window`` positions. Every engine program takes the
  tree's buffers DONATED and returns them aliased, and the batcher rebinds
  the whole tree from each result, so the device holds it once and no
  program copies it.
- Admission is a per-request prefill that writes the prompt's KV into
  the free slot's row (`dynamic_update_slice` on the slot axis, in
  place) and returns the first generated token. A recurrent layer's row
  takes the state and the tail AFTER the prompt's last token, so a slot
  that is taken again starts from its own prefill and never from what
  the last request left.
- Every engine tick is ONE compiled launch decoding the ACTIVE slots
  together, in place: one batched forward over the bucket's rows with
  per-row positions (rope, causal mask), each layer writing its rows'
  new K/V at ``(layer, slot, pos)`` with one indexed update and reading
  the layer's rows where they lie (``generate.decode_step_in_place``),
  each row below the step's ``generate.kv_read_bound`` only: the
  positions the launch's furthest row has reached, rounded up to a
  chunk, not the ``max_len`` a slot was allocated. The batcher counts
  what that read (``take_kv_positions``: rows x bound against the
  active rows' live positions) from the positions it staged.
  Two buckets: the full engine, whose rows are the slots in slot order
  (a free slot computes a row nobody reads at position 0, where it holds
  no bound up, into a row the next prefill overwrites whole), and a lone
  straggler, which pays one row addressed by a dynamic slice. A
  ``lax.scan`` fuses K decode steps per launch (dispatch overhead
  amortized K-fold — the decode-side ``make_multi_step``). Stale KV in freed slots is never observed: the
  next admission prefills the slot from position 0.
- Greedy decoding — each request's output is EXACTLY
  ``generate.generate(...)`` on its own prompt, regardless of what else
  shares the batch (the test asserts this token-for-token).

Prefill compiles once per (batch=1, prompt_len) via the module's lru
cache; production use would bucket prompt lengths — admission cost, not
a steady-state one (the decode step is length-independent).
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import queue as _queue
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import generate as G
from ray_tpu.models import llama
from ray_tpu.ops import ssm
from ray_tpu.util import engine_recorder as _rec
from ray_tpu.util import hlo_copies
from ray_tpu.util import lifecycle
from ray_tpu.util import prefix_hash as PH
from ray_tpu.util.recorder_core import span as _span

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Prefix/KV-cache reuse (ROADMAP item 4): retain completed slots' KV pages,
# admit shared-prefix requests by restoring them so prefill runs only on
# the uncached suffix.
# ---------------------------------------------------------------------------


class _PrefixEntry:
    __slots__ = ("key", "length", "k", "v", "nbytes", "chunk_keys",
                 "chunk_digests", "created_at")

    def __init__(self, key: bytes, length: int, k: np.ndarray, v: np.ndarray,
                 chunk_keys: List[bytes], chunk_digests: List[str]):
        self.key = key
        self.length = length
        self.k = k
        self.v = v
        self.nbytes = int(k.nbytes + v.nbytes)
        self.chunk_keys = chunk_keys
        self.chunk_digests = chunk_digests
        self.created_at = time.time()


# live caches in this process, for `rt memory` (util/memory.py reads this
# registry for the local view; remote replicas publish @memkv/ snapshots)
_kv_registry_lock = threading.Lock()
_kv_registry: "weakref.WeakSet" = weakref.WeakSet()  # rt: guarded-by(_kv_registry_lock)


def live_kv_cache_stats() -> List[Dict[str, Any]]:
    """Stats of every live PrefixKVCache in this process (memory plane)."""
    with _kv_registry_lock:
        caches = list(_kv_registry)
    return [c.stats() for c in caches]


class PrefixKVCache:
    """Bytes-budgeted LRU of chunk-aligned token-prefix KV pages.

    Pages are host numpy copies ``[L, c, hkv, hd]`` of a slot row's first
    ``c`` positions, keyed by the EXACT token bytes of the prefix (no
    hash-collision risk; equality is byte equality). One entry of length
    ``n`` serves every chunk-aligned prefix ``c <= n`` through the chunk
    index, so a multi-turn session's growing context is one entry, not a
    ladder of copies. Eviction is LRU by entry under a bytes budget
    (``RT_KV_CACHE_BYTES`` default when unset); a weight swap must
    :meth:`clear` the whole cache — every page was computed under the old
    weights and would silently corrupt post-swap prefills.

    Thread-safe: the engine thread mutates, stats/digest readers come
    from replica RPC threads.
    """

    def __init__(self, *, chunk: Optional[int] = None,
                 max_bytes: Optional[int] = None, label: str = ""):
        self.chunk = int(chunk or PH.chunk_size())
        if max_bytes is None:
            max_bytes = int(os.environ.get("RT_KV_CACHE_BYTES",
                                           str(256 * 1024 * 1024)))
        self.max_bytes = int(max_bytes)
        self.label = label
        self._lock = threading.Lock()
        # full-prefix key -> entry, in LRU order (oldest first)
        self._entries: "OrderedDict[bytes, _PrefixEntry]" = \
            OrderedDict()  # rt: guarded-by(_lock)
        # chunk-aligned prefix key -> full key of an entry covering it
        self._index: Dict[bytes, bytes] = {}  # rt: guarded-by(_lock)
        self._bytes = 0  # rt: guarded-by(_lock)
        self._hits = 0  # rt: guarded-by(_lock)
        self._misses = 0  # rt: guarded-by(_lock)
        self._evictions = 0  # rt: guarded-by(_lock)
        self._inserts = 0  # rt: guarded-by(_lock)
        self._invalidations = 0  # rt: guarded-by(_lock)
        self._hit_tokens = 0  # rt: guarded-by(_lock)
        with _kv_registry_lock:
            _kv_registry.add(self)

    def aligned(self, n: int) -> int:
        return PH.aligned_len(n, self.chunk)

    def lookup(self, tokens: np.ndarray
               ) -> Optional[Tuple[int, np.ndarray, np.ndarray]]:
        """Longest cached QUANTIZED prefix of ``tokens``:
        ``(c, k_pages[L, c, hkv, hd], v_pages)`` or None. ``c`` is capped
        at ``len(tokens) - 1`` — admission always prefills at least one
        suffix token (the first generated token comes from the last
        prompt position's logits) — and the probe ladder is GEOMETRIC
        (power-of-two multiples of the chunk): the warm prefill compiles
        one XLA program per (cached, suffix) shape on the engine thread,
        where a mid-serve compile stalls every live stream, so restore
        lengths are quantized to bound the program set at O(log) per
        prompt length instead of one per chunk multiple."""
        cmax = self.aligned(len(tokens) - 1)
        if cmax < self.chunk:
            return None
        # largest power-of-two multiple of chunk <= cmax
        c = self.chunk * (1 << ((cmax // self.chunk).bit_length() - 1))
        buf = PH.token_key(tokens, c)  # pack once, slice per length
        with self._lock:
            while c >= self.chunk:
                key = buf[:PH.TOKEN_WIDTH * c]
                fk = self._index.get(key)
                if fk is None:
                    c //= 2
                    continue
                e = self._entries.get(fk)
                if e is None or e.length < c or not e.key.startswith(key):
                    self._index.pop(key, None)  # stale index row
                    c //= 2
                    continue
                self._entries.move_to_end(fk)
                self._hits += 1
                self._hit_tokens += c
                return (c, e.k[:, :c], e.v[:, :c])
            self._misses += 1
        return None

    def cached_len(self, tokens: np.ndarray) -> int:
        """Longest cached aligned prefix length WITHOUT touching hit/miss
        counters or LRU order (capture-skip probe)."""
        cmax = self.aligned(len(tokens))
        if cmax < self.chunk:
            return 0
        buf = PH.token_key(tokens, cmax)
        with self._lock:
            for c in range(cmax, 0, -self.chunk):
                fk = self._index.get(buf[:PH.TOKEN_WIDTH * c])
                if fk is None:
                    continue
                e = self._entries.get(fk)
                if e is not None and e.length >= c:
                    return c
        return 0

    def insert(self, tokens: np.ndarray, k_pages: np.ndarray,
               v_pages: np.ndarray) -> bool:
        """Retain ``tokens``' KV pages (length must be chunk-aligned).
        Returns False when already resident or larger than the budget."""
        n = len(tokens)
        key = PH.token_key(tokens, n)
        nbytes = int(k_pages.nbytes + v_pages.nbytes)
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                return False
            if nbytes > self.max_bytes:
                return False
            chunk_keys = [key[:PH.TOKEN_WIDTH * c]
                          for c in range(self.chunk, n + 1, self.chunk)]
            chunk_digests = PH.chunked_digests(key, self.chunk)
            # coalesce: an older entry that IS a prefix of this one is now
            # fully covered — drop it, or a growing session would retain a
            # ladder of duplicate unreachable pages against the budget
            ck_set = set(chunk_keys)
            for fk in [fk for fk in self._entries if fk in ck_set]:
                covered = self._entries.pop(fk)
                self._bytes -= covered.nbytes
            e = _PrefixEntry(key, n, k_pages, v_pages, chunk_keys,
                             chunk_digests)
            self._entries[key] = e
            self._bytes += nbytes
            self._inserts += 1
            for ck in chunk_keys:
                self._index[ck] = key  # newest entry serves the prefix
            while self._bytes > self.max_bytes and len(self._entries) > 1:
                self._evict_one_locked()
            if self._bytes > self.max_bytes:  # lone oversized survivor
                self._evict_one_locked()
                return False
        return True

    def _evict_one_locked(self) -> None:
        _, old = self._entries.popitem(last=False)
        self._bytes -= old.nbytes
        self._evictions += 1
        for ck in old.chunk_keys:
            if self._index.get(ck) != old.key:
                continue
            # repoint to a surviving covering entry (sessions that share
            # only a short prefix overlap on its chunk rows) — deleting
            # outright would stop resident entries serving those hits.
            # token_key is fixed-width per token, so byte-prefix equality
            # IS token-prefix equality.
            for fk in reversed(self._entries):  # MRU first
                if fk.startswith(ck):
                    self._index[ck] = fk
                    break
            else:
                del self._index[ck]

    def clear(self) -> int:
        """Weight-swap invalidation: every page was computed under the
        old weights — poisoned, drop them all. Returns pages dropped."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self._index.clear()
            self._bytes = 0
            self._invalidations += n
        return n

    def digests(self, limit: int = 2 * PH.MAX_PROBE_CHUNKS) -> List[str]:
        """Chunk digests of resident entries (residency report for
        cache-affinity routing), bounded. INTERLEAVED round-robin across
        entries in MRU order, longest-prefix-first within each — one
        long entry (64 chunks fills the whole report) must not hide
        every other resident context from the router; the router scores
        by set membership, so coverage beats order."""
        per_entry: List[List[str]] = []
        with self._lock:
            # this runs on EVERY handle_request reply: bound the work
            # under the lock to O(limit^2) worst case — at most `limit`
            # MRU entries, at most `limit` digests each (reverse slice,
            # not a whole-list copy)
            for e in reversed(self._entries.values()):
                if len(per_entry) >= limit:
                    break
                per_entry.append(e.chunk_digests[:-limit - 1:-1])
        out: List[str] = []
        for i in range(max((len(d) for d in per_entry), default=0)):
            for d in per_entry:
                if i < len(d):
                    out.append(d[i])
                    if len(out) >= limit:
                        return out
        return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"label": self.label, "chunk": self.chunk,
                    "bytes": self._bytes, "max_bytes": self.max_bytes,
                    "pages": len(self._entries),
                    "hits": self._hits, "misses": self._misses,
                    "evictions": self._evictions, "inserts": self._inserts,
                    "invalidations": self._invalidations,
                    "hit_tokens": self._hit_tokens}


class _Request:
    __slots__ = ("req_id", "slot", "remaining", "tokens", "prompt")

    def __init__(self, req_id: int, slot: int, remaining: int,
                 prompt: Optional[np.ndarray] = None):
        self.req_id = req_id
        self.slot = slot
        self.remaining = remaining
        self.tokens: List[int] = []
        self.prompt = prompt


class SlotCacheLost(RuntimeError):
    """A compiled call failed after it had consumed the donated slot
    cache. The batcher has rebuilt a zeroed cache and dropped every
    active request (their rows went with the buffers); it admits again."""


def _refuse_prefix_cache(cfg) -> None:
    if cfg.n_recurrent_layers:
        raise ValueError(
            f"prefix cache (kv_cache_bytes > 0) on a model with "
            f"{cfg.n_recurrent_layers} recurrent layers: a retained prefix "
            f"is pages of K and V, and restoring them restores nothing of a "
            f"recurrent layer's state after that prefix, nor of a window "
            f"layer's ring (it would need a snapshot of both per retained "
            f"prefix). Serve it with kv_cache_bytes=0")


class ContinuousBatcher:
    """Slot-based continuous batching engine around one model: its slot
    cache is the tree of buffers ``generate.init_cache`` makes for the
    model's layer kinds (keys and values, and a recurrent layer's state
    and tail), one row of each a slot."""

    def __init__(self, params: Params, cfg: llama.LlamaConfig, *,
                 max_slots: int = 8, max_len: int = 512,
                 prefix_cache: Optional[PrefixKVCache] = None,
                 sampling: bool = False):
        self.cfg = cfg
        self.max_slots = max_slots
        self.max_len = max_len
        #: per decode program this batcher has asked for, what its
        #: compiled form does to the cache (``hlo_copies.cache_traffic``);
        #: the engine's recorder shows it
        self.program_stats: List[Dict[str, Any]] = []
        self.params = params
        if prefix_cache is not None:
            _refuse_prefix_cache(cfg)
        # ONE tree of buffers, each donated to every compiled call and the
        # whole tree rebound from its result (``_donating``, ``_launch``)
        with lifecycle.span("cache_alloc", parent="engine_init"):
            self._cache = jax.block_until_ready(self._zero_cache())
        self._free: List[int] = list(range(max_slots))
        self._active: Dict[int, _Request] = {}  # slot -> request
        self._cur = np.zeros(max_slots, np.int32)   # token AT pos, per slot
        self._pos = np.zeros(max_slots, np.int32)   # absolute position
        self._ids = itertools.count()
        # prefix/KV reuse: retained pages of completed/cancelled slots
        self.prefix_cache = prefix_cache
        # sampling decode: per-slot temperature / top-k / PRNG-key chain.
        # Built into the compiled programs only when enabled — a greedy
        # engine compiles the exact PR 9 programs.
        self.sampling = bool(sampling)
        self._temp = np.zeros(max_slots, np.float32)
        self._topk = np.zeros(max_slots, np.int32)
        self._keys = np.zeros((max_slots, 2), np.uint32)
        # set by every submit_ex: admission telemetry the engine reads
        # (cached_tokens rides the request span; TTFT-collapse evidence;
        # admission_s/kv_restore_s/prefill_s are the spans the call was
        # made of and feed the flight recorder's tick phases)
        self.last_admission: Dict[str, Any] = {}
        # set by every step_many that launched: the wall of its three
        # spans (decode_stage, decode_launch, decode_book), in seconds
        self.last_step: Dict[str, float] = {}
        # a sparse model's routing counters (``moe.SERVED_STATS``) of the
        # launches since ``take_moe_stats``, by kind of launch; a dense
        # model's stay empty
        self._moe_stats: Dict[str, np.ndarray] = {}
        # chunks the recurrent layers' scans ran over in the prefills since
        # ``take_scan_chunks`` (layers x chunks of the prompt); 0 without
        # recurrent layers
        self._scan_chunks = 0
        # positions the decode launches since ``take_kv_positions`` had
        # attention read, and positions their active rows had live; the
        # same of the window layers' rings (a model with such layers)
        self._kv_positions = [0, 0]
        self._window_positions = [0, 0]
        # layer-tokens the prefills since ``take_prefill_layer_tokens``
        # computed, and what every layer over every token would have been
        # (a model whose prefill leaves layers out for all but the last
        # token: ``sambay.forward_with_cache``)
        self._prefill_layer_tokens = [0, 0]

    # the attention layers' buffers by name (tests and ``_capture``)
    _ck = property(lambda self: self._cache["k"])
    _cv = property(lambda self: self._cache["v"])

    @property
    def params(self) -> Params:
        return self._params

    @params.setter
    def params(self, params: Params) -> None:
        # an executable takes the arguments it was compiled for: the
        # weights' shapes, types and placement are part of a decode
        # program's key, so that it follows them as ``jit`` would
        leaves, tree = jax.tree.flatten(params)
        self._weights = (tree, tuple(jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x),
            sharding=getattr(x, "sharding", None)) for x in leaves))
        # a sparse model's programs return routing counters behind their
        # tokens (``_with_stats``); ``generate`` tells by the same key
        self._sparse = "router" in params["layers"]
        self._params = params

    def check_params(self, params: Params) -> None:
        """Raise where ``params`` is not a tree of the engine's model: a
        swap takes weights of the same tree and shapes (the slot cache, the
        programs and the config were made for them)."""
        tree, leaves = self._weights
        new_leaves, new_tree = jax.tree.flatten(params)
        if new_tree != tree:
            raise ValueError(
                f"load_params: the weights are another model's tree "
                f"({new_tree.num_leaves} leaves; this engine serves "
                f"{type(self.cfg).__name__} with {tree.num_leaves}: "
                f"{new_tree} != {tree})"[:600])
        for path, had, new in zip(jax.tree.leaves_with_path(params), leaves,
                                  new_leaves):
            if tuple(jnp.shape(new)) != tuple(had.shape):
                raise ValueError(
                    f"load_params: {jax.tree_util.keystr(path[0])} is "
                    f"{tuple(jnp.shape(new))}, this engine's is "
                    f"{tuple(had.shape)}")

    def _zero_cache(self) -> Dict[str, jax.Array]:
        return G.init_cache(self.cfg, self.max_slots, self.max_len)

    def _launch(self, fn, *args):
        """``fn(params, *the tree's buffers, *args)``: rebinds the tree
        from the front of the result and returns the rest."""
        names = G.cache_names(self.cfg)
        out = fn(self.params, *(self._cache[name] for name in names), *args)
        self._cache = dict(zip(names, out))
        return out[len(names):]

    @contextlib.contextmanager
    def _donating(self):
        """Round a compiled call that takes the slot tree donated
        (``_launch``), from the call through the host read that fences it.
        A failure before the call consumed the buffers (a compile error, a
        bad argument) leaves the cache as it was and is the caller's to
        handle. One after (a buffer is deleted, or the results poisoned)
        has lost every slot's rows: the batcher starts over on a tree
        zeroed in every buffer with no active request and raises
        :class:`SlotCacheLost`, so that it never holds a deleted buffer."""
        held = list(self._cache.values())
        try:
            yield
        except BaseException as e:
            if not any(buf.is_deleted() for buf in held):
                raise
            self._cache = self._zero_cache()
            self._active.clear()
            self._free = list(range(self.max_slots))
            self._pos[:] = 0
            if not isinstance(e, Exception):
                raise  # an interrupt stays one
            raise SlotCacheLost(
                f"slot cache lost in a failed launch: "
                f"{type(e).__name__}: {e}"[:300]) from e

    def _note_moe_stats(self, kind: str, stats: np.ndarray) -> None:
        had = self._moe_stats.get(kind)
        self._moe_stats[kind] = stats if had is None else np.concatenate(
            [had[:4] + stats[:4], np.maximum(had[4:], stats[4:])])

    def take_moe_stats(self) -> Dict[str, List[int]]:
        """The routing counters gathered since the last call, as
        {"prefill" | "decode": ``moe.SERVED_STATS`` values}: the engine
        takes them once a tick."""
        out, self._moe_stats = self._moe_stats, {}
        return {kind: [int(v) for v in stats] for kind, stats in out.items()}

    def take_scan_chunks(self) -> int:
        """Chunks the recurrent layers' prefill scans ran over since the
        last call (0 for a model without them): the engine takes it once
        a tick."""
        out, self._scan_chunks = self._scan_chunks, 0
        return out

    def take_kv_positions(self) -> Tuple[int, int]:
        """(read, live) over the decode launches since the last call: rows
        x ``generate.kv_read_bound`` of each fused step, reckoned from the
        positions the launch was staged with (what the program computes
        from them; no device read), and the active rows' ``pos + 1`` over
        the steps whose tokens they took. (0, 0) where none launched."""
        out, self._kv_positions = self._kv_positions, [0, 0]
        return out[0], out[1]

    def take_window_positions(self) -> Tuple[int, int]:
        """``take_kv_positions`` for the window layers' rings: a step
        reads every row's ring whole (``sliding_window`` positions), and an
        active row has ``min(pos + 1, sliding_window)`` of them live. (0, 0)
        for a model without window layers."""
        out, self._window_positions = self._window_positions, [0, 0]
        return out[0], out[1]

    def take_prefill_layer_tokens(self) -> Tuple[int, int]:
        """(computed, whole) layer-tokens of the prefills since the last
        call; (0, 0) where every prefill runs every layer over every token."""
        out, self._prefill_layer_tokens = self._prefill_layer_tokens, [0, 0]
        return out[0], out[1]

    def _release(self, slot: int) -> None:
        """Free ``slot``: its position goes back to 0, so that the row of a
        request that has left holds no launch's ``kv_read_bound`` up
        (nothing reads a free row; its next admission prefills from 0)."""
        self._pos[slot] = 0
        self._free.append(slot)

    # -- admission --------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0,
               seed: int = 0) -> int:
        """Admit one request (prompt: int array [S]); returns req_id.
        Raises RuntimeError when no slot is free (caller queues/retries —
        admission control belongs to the serving layer)."""
        return self.submit_ex(prompt, max_new_tokens,
                              temperature=temperature, top_k=top_k,
                              seed=seed)[0]

    def submit_ex(self, prompt: np.ndarray, max_new_tokens: int, *,
                  temperature: float = 0.0, top_k: int = 0,
                  seed: int = 0) -> Tuple[int, int, bool]:
        """``submit`` plus the prefill's first token: returns
        (req_id, first_token, done) — the streaming engine needs the
        token the admission itself produced (for a 1-token request the
        slot is already freed and no ``step()`` will ever report it).

        With a prefix cache attached, admission restores the longest
        cached chunk-aligned prefix into the slot and prefills ONLY the
        uncached suffix — the TTFT-collapse path. The restored pages were
        produced by the identical per-position math (K/V at position i
        depends only on tokens <= i and every op is row-independent), so
        warm output is token-exact vs a cold prefill (asserted in
        tests/test_zz_kv_cache.py)."""
        ph: Dict[str, float] = {}
        with _span("admission", ph):
            if not self._free:
                raise RuntimeError("no free slots")
            s = len(prompt)
            if s + max_new_tokens + 1 > self.max_len:
                raise ValueError(f"prompt {s} + new {max_new_tokens} "
                                 f"exceeds max_len {self.max_len}")
            if (temperature > 0 or top_k > 0) and not self.sampling:
                raise ValueError(
                    "sampling request on a greedy engine: construct the "
                    "batcher/engine with sampling=True")
            slot = self._free.pop()
            prompt_arr = np.asarray(prompt, np.int32)
        cached = 0
        pages = None
        try:
            if self.prefix_cache is not None:
                # warm admission's restore cost: the lookup + uploading
                # the retained pages (the compiled call scatters them)
                with _span("kv_restore", ph):
                    hit = self.prefix_cache.lookup(prompt_arr)
                    if hit is not None:
                        cached, pk, pv = hit
                        pages = (jnp.asarray(pk), jnp.asarray(pv))
            # staging, the compiled call AND the host read of the first
            # token: the read is the fence, so the device's prefill time
            # is inside this span and not in the bookkeeping after it
            with _span("prefill", ph):
                if pages is not None:
                    fn = _compiled_cached_prefill(
                        self.cfg, cached, s - cached, self.max_slots,
                        self.max_len, self.sampling)
                    args = (*pages, jnp.asarray(prompt_arr[cached:])[None, :],
                            slot)
                else:
                    fn = _compiled_slot_prefill(
                        self.cfg, s, self.max_slots, self.max_len,
                        self.sampling)
                    args = (jnp.asarray(prompt_arr)[None, :], slot)
                if self.sampling:
                    args += (jnp.float32(temperature), jnp.int32(top_k),
                             jnp.asarray(np.asarray(
                                 jax.random.PRNGKey(int(seed)), np.uint32)))
                with self._donating():
                    first, *new_key = self._launch(fn, *args)
                    if self._sparse:  # the counters ride behind the token
                        first = np.asarray(first)
                        self._note_moe_stats("prefill", first[1:])
                    first_tok = int(first[0])
                self._scan_chunks += scan_chunks(self.cfg, s - cached)
                if hasattr(self.cfg, "prefill_layer_tokens"):
                    for i, n in enumerate(self.cfg.prefill_layer_tokens(s)):
                        self._prefill_layer_tokens[i] += n
        except SlotCacheLost:
            raise  # every slot is free again
        except BaseException:
            # a failed prefill must not leak the slot: callers (the
            # engine's admit loop) catch and continue, and a leaked slot
            # per transient XLA error would silently shrink the engine
            # to zero capacity with no recovery path
            self._free.append(slot)
            raise
        with _span("admission", ph):
            req = _Request(next(self._ids), slot, max_new_tokens, prompt_arr)
            req.tokens.append(first_tok)
            req.remaining -= 1
            self._cur[slot] = first_tok
            self._pos[slot] = s
            if self.sampling:
                self._temp[slot] = temperature
                self._topk[slot] = top_k
                self._keys[slot] = np.asarray(new_key[0])
            done = req.remaining <= 0
            if done:
                self._capture(slot, req)
                self._release(slot)
            else:
                self._active[slot] = req
        self.last_admission = {"cached_tokens": cached, "prompt_tokens": s,
                               "slot": slot,
                               "admission_s": ph["admission"],
                               "kv_restore_s": ph.get("kv_restore", 0.0),
                               "prefill_s": ph["prefill"]}
        return req.req_id, first_tok, done

    def _capture(self, slot: int, req: _Request) -> None:
        """Retain the freed slot's KV pages: the valid span is
        ``[0, pos)`` — prompt plus the generated tokens whose KV a decode
        step actually wrote (the final emitted token's KV is only written
        by the step that would produce its successor). Skipped when the
        aligned prefix is already resident (the common warm-hit case —
        re-capturing the shared system prompt per request would be pure
        copy overhead)."""
        cache = self.prefix_cache
        if cache is None or req.prompt is None:
            return
        pos = int(self._pos[slot])
        cap = cache.aligned(min(pos, self.max_len))
        if cap < cache.chunk:
            return
        gen_used = max(0, pos - len(req.prompt))
        tokens = req.prompt
        if gen_used:
            tokens = np.concatenate(
                [req.prompt, np.asarray(req.tokens[:gen_used], np.int32)])
        tokens = tokens[:cap]
        if cache.cached_len(tokens) >= cap:
            return
        # one gather per aligned length (bounded program count): host
        # copies so retained pages survive slot reuse and weight swaps
        k = np.asarray(self._ck[:, slot, :cap])
        v = np.asarray(self._cv[:, slot, :cap])
        cache.insert(tokens, k, v)

    # -- the engine tick --------------------------------------------------

    def step(self) -> List[Tuple[int, int, bool]]:
        """ONE decode step for every active slot; returns
        [(req_id, token, done)] for requests that produced a token."""
        return [(rid, toks[0], done)
                for rid, toks, done in self.step_many(1)]

    def step_many(self, k: int = 1) -> List[Tuple[int, List[int], bool]]:
        """Up to ``k`` FUSED decode steps for every active slot in ONE
        compiled program; returns [(req_id, tokens, done)].

        Two launch-amortization levers compose here (this runtime's
        measured per-launch overhead is ~ms — the make_multi_step story,
        applied to decode):

        - Bucketed active-slot stepping: a lone straggler on an 8-slot
          engine pays one row, not eight (buckets: {1, max_slots}). The
          full bucket's rows are the slots themselves, in slot order: a
          free slot's row computes from whatever ``_cur`` still holds at
          position 0 (``_release``), writes into its own free row, and
          nobody reads its token.
        - K-step fusion: a ``lax.scan`` decodes ``k`` tokens per launch,
          so dispatch overhead is paid once per K tokens instead of per
          token. A request finishing mid-tick just has its surplus
          tokens discarded (its rows compute independently; the freed
          slot's stale KV is overwritten by the next prefill).

        The program steps the slot cache in place (it is donated and
        rebound here). Two programs (lone-row, full-engine) compile per
        distinct ``k``. Raises :class:`SlotCacheLost` when a launch
        failed after it had consumed the cache.
        """
        if not self._active:
            return []
        parts = self.last_step = {}
        with _span("decode_stage", parts):
            slots = sorted(self._active)
            n = len(slots)
            # two buckets only — a lone row or the full engine: K-fusion
            # already amortizes dispatch, so finer occupancy buckets buy
            # little compute but each costs a warmup compile (~seconds);
            # the lone-straggler case is the one worth its own program
            bucket = 1 if n == 1 else self.max_slots
            fn = self._program(bucket, k)
            rows, args = self._stage(bucket, slots[0] if n == 1 else 0)
        # the compiled call through the host read of its tokens: the
        # device is busy under this span and idle outside it
        with _span("decode_launch", parts, k=k, bucket=bucket, active=n), \
                self._donating():
            toks, *new_keys = self._launch(fn, *args)
            toks = np.asarray(toks)  # [k, bucket]
            if self._sparse:  # the counters ride behind the tokens
                self._note_moe_stats("decode", toks[k * bucket:])
                toks = toks[:k * bucket].reshape(k, bucket)
            if self.sampling:
                self._keys[rows] = np.asarray(new_keys[0])
        with _span("decode_book", parts):
            # from the positions the launch was staged with, still held
            self._kv_positions[0] += bucket * sum(
                int(G.kv_read_bound(self._pos[rows] + j, self.max_len, np))
                for j in range(k))
            window = (self.cfg.sliding_window if self.cfg.n_window_layers
                      else 0)
            self._window_positions[0] += bucket * k * window
            out = []
            for slot in slots:
                req = self._active[slot]
                take = min(k, req.remaining)
                if window:
                    self._window_positions[1] += sum(
                        min(int(self._pos[slot]) + j + 1, window)
                        for j in range(take))
                mine = [int(t) for t in toks[:take, slot - rows.start]]
                req.tokens.extend(mine)
                req.remaining -= take
                self._cur[slot] = mine[-1]
                # positions 0 .. pos + j, for each of the steps it took
                self._kv_positions[1] += (take * (int(self._pos[slot]) + 1)
                                          + take * (take - 1) // 2)
                self._pos[slot] += take
                done = req.remaining <= 0
                if done:
                    self._capture(slot, req)
                    del self._active[slot]
                    self._release(slot)
                out.append((req.req_id, mine, done))
            # freeing the staged device inputs is host time between two
            # launches too: done here, it is booked; left to the frame's
            # teardown it would fall between the spans
            del args, toks
        return out

    def _stage(self, bucket: int, slot0: int) -> Tuple[slice, Tuple]:
        """The rows ``slot0 .. slot0 + bucket`` and a decode program's
        arguments for them, after the weights and the cache."""
        rows = slice(slot0, slot0 + bucket)
        args = (jnp.asarray(self._cur[rows]), jnp.asarray(self._pos[rows]),
                jnp.int32(slot0))
        if self.sampling:
            args += (jnp.asarray(self._temp[rows]),
                     jnp.asarray(self._topk[rows]),
                     jnp.asarray(self._keys[rows]))
        return rows, args

    def _program(self, bucket: int, k: int):
        """The decode executable for ``(bucket, k)``, compiled on first
        use (``warmup`` asks for all of them before traffic)."""
        fn, stats = _decode_executable(
            self.cfg, bucket, self.max_slots, self.max_len, k, self.sampling,
            self._weights)
        if stats not in self.program_stats:
            self.program_stats.append(stats)
        return fn

    @property
    def num_active(self) -> int:
        return len(self._active)

    @property
    def max_remaining(self) -> int:
        return max((r.remaining for r in self._active.values()), default=0)

    def warmup(self, prompt_lens: Tuple[int, ...] = (),
               strides: Tuple[int, ...] = (1,)) -> None:
        """Compile every decode program (the {1, max_slots} buckets
        step_many uses, for each tick stride) and optionally the
        prefills for the given prompt lengths, BEFORE traffic arrives.
        Without this the first request at each new occupancy level pays
        a mid-flight XLA compile that stalls every active stream —
        under Poisson load the stall backlog saturates the slots and
        never recovers. Keep this bucket set in lockstep with
        step_many's choice. The programs run for real, on the donated
        cache: a decode step rewrites, for each row, the K/V its next
        real step writes again (and steps a recurrent state that nobody
        reads), and a prefill goes to a free slot, whose row the next
        admission overwrites whole."""
        for k in sorted(set(strides)):
            for bucket in sorted({1, self.max_slots}):
                fn = self._program(bucket, int(k))
                with self._donating():
                    np.asarray(self._launch(fn, *self._stage(bucket, 0)[1])[0])
        for s in prompt_lens:
            if not self._free:
                raise RuntimeError("no free slot to warm a prefill in")
            fn = _compiled_slot_prefill(self.cfg, int(s), self.max_slots,
                                        self.max_len, self.sampling)
            args = (jnp.zeros((1, int(s)), jnp.int32), self._free[-1])
            if self.sampling:
                args += (jnp.float32(0.0), jnp.int32(0),
                         jnp.asarray(self._keys[0]))
            with self._donating():
                np.asarray(self._launch(fn, *args)[0])

    def cancel(self, req_id: int) -> bool:
        """Free a request's slot mid-flight (client disconnect). The slot's
        stale KV needs no scrub: the next admission prefills from 0. The
        written span is still retained in the prefix cache — a dropped
        multi-turn session's context stays warm for its next turn."""
        for slot, req in list(self._active.items()):
            if req.req_id == req_id:
                self._capture(slot, req)
                del self._active[slot]
                self._release(slot)
                return True
        return False

    def run_to_completion(self) -> Dict[int, List[int]]:
        """Drain all active requests; returns req_id -> generated tokens
        (convenience for tests/batch jobs; serving calls step())."""
        results: Dict[int, List[int]] = {
            r.req_id: r.tokens for r in self._active.values()}
        while self._active:
            reqs = {r.req_id: r for r in self._active.values()}
            for rid, tok, done in self.step():
                results.setdefault(rid, reqs[rid].tokens)
        return results


_STREAM_END = None  # sentinel a token stream's queue yields when done


class _EngineRequest:
    __slots__ = ("prompt", "max_new_tokens", "out", "on_token", "req_id",
                 "cancelled", "temperature", "top_k", "seed",
                 "t_submit", "obs_ctx")

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 on_token: Optional[Callable[[Optional[int]], None]] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 obs_ctx: Optional[Dict[str, str]] = None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.on_token = on_token
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.t_submit = time.time()  # queue-wait starts here
        # ambient serve span context ({request_id, span_id}), when the
        # submitter rode a serve request — the flight recorder parents
        # the engine lifecycle span on it so `rt trace <rid>` descends
        # from proxy/replica into engine phases
        self.obs_ctx = obs_ctx
        # at most max_new_tokens items + the end sentinel ever sit here,
        # so an unbounded queue is bounded in practice and the shared
        # engine thread can never block on a slow consumer
        self.out: Optional["_queue.Queue"] = (
            None if on_token is not None else _queue.Queue())
        self.req_id: Optional[int] = None  # assigned at admission
        self.cancelled = False

    def emit(self, tok: Optional[int]) -> None:
        self.emit_many([tok])

    def emit_many(self, toks: List[Optional[int]]) -> None:
        """Hand a tick's token burst to the consumer in ONE callback —
        per-token cross-thread wakeups (call_soon_threadsafe pipe writes)
        were a measurable share of the serve path's token ceiling."""
        if self.on_token is not None:
            try:
                self.on_token(toks)
            except Exception:  # noqa: BLE001 — a consumer callback must
                pass           # never take the shared engine thread down
        else:
            for tok in toks:
                self.out.put(tok)


class ContinuousEngine:
    """The slot-admission loop that makes :class:`ContinuousBatcher` live.

    ONE background thread owns the model: it admits pending requests into
    free slots (per-request prefill) and runs the rowwise decode step
    across all active slots, pushing each token into the submitting
    request's thread-safe queue the moment it is sampled. Serving wraps
    the queue in an async generator, so tokens flow out through the
    replica stream pump / proxy ``_stream_response`` path with per-token
    latency — and admission happens MID-FLIGHT: a request arriving while
    others decode joins the next tick instead of waiting for a batch
    boundary (the continuous-batching property the static ``@serve.batch``
    control lacks).

    ``on_tick(active_slots, max_slots)`` fires after every decode step —
    the serve layer hangs slot-occupancy telemetry on it without this
    module importing serve.
    """

    def __init__(self, params: Params, cfg: llama.LlamaConfig, *,
                 max_slots: int = 8, max_len: int = 512,
                 decode_stride: int = 8,
                 on_tick: Optional[Callable[[int, int], None]] = None,
                 warmup: bool = True,
                 kv_cache_bytes: Optional[int] = None,
                 kv_label: str = "", sampling: bool = False):
        # kv_cache_bytes > 0 attaches the prefix/KV reuse plane (retained
        # pages budgeted in bytes, LRU-evicted, weight-swap-invalidated);
        # 0 keeps the exact PR 9 cold-prefill engine; None reads
        # RT_KV_CACHE_BYTES (default 0) so bare engines get the
        # documented env knob without the serve layer's explicit sizing.
        # The chunk size is
        # deliberately NOT a per-engine knob: the handle router hashes
        # request prefixes at the global RT_KV_CHUNK granularity, and a
        # drifting engine chunk would silently zero the affinity scores.
        if kv_cache_bytes is None:
            kv_cache_bytes = int(os.environ.get("RT_KV_CACHE_BYTES", "0"))
        cache = (PrefixKVCache(max_bytes=kv_cache_bytes, label=kv_label)
                 if kv_cache_bytes > 0 else None)
        self.decode_stride = max(1, int(decode_stride))
        # the engine's share of a replica's set-up on the lifecycle record:
        # the slot tree's allocation and one span per decode program
        with lifecycle.span("engine_init", max_slots=max_slots,
                            max_len=max_len):
            self._batcher = ContinuousBatcher(params, cfg,
                                              max_slots=max_slots,
                                              max_len=max_len,
                                              prefix_cache=cache,
                                              sampling=sampling)
            if warmup:
                # pay every decode-program compile HERE (replica init — the
                # controller's readiness probe covers it) instead of at the
                # first request of each occupancy level
                self._batcher.warmup(
                    strides=(1, self.decode_stride) if self.decode_stride > 1
                    else (1,))
        self.max_slots = max_slots
        self.max_len = max_len
        self._on_tick = on_tick
        self._pending: "deque[_EngineRequest]" = deque()  # rt: guarded-by(_work)
        self._live: Dict[int, _EngineRequest] = {}  # rt: guarded-by(_work)
        self._admitting: Optional[_EngineRequest] = None  # mid-prefill
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stopped = False
        self._dead: Optional[str] = None  # fatal engine error, if any
        self._steps = 0
        self._admitted = 0
        self._tokens_out = 0
        self._requests_completed = 0  # rt: guarded-by(_work)
        self._weight_swaps = 0  # rt: guarded-by(_work)
        # (new_params, state dict) queued by load_params; applied by the
        # engine thread once every active slot has drained
        self._pending_swap: Optional[Tuple] = None  # rt: guarded-by(_work)
        # flight recorder: the engine thread stamps tick/request records
        # into its bounded deques; a separate drain thread ships metrics/
        # spans/KV snapshots (NO GCS or metrics I/O on the tick path)
        self._recorder = _rec.EngineRecorder(kv_label or "engine",
                                             max_slots=max_slots)
        # the batcher's own list: a program compiled later shows too
        self._recorder.decode_programs = self._batcher.program_stats
        if cfg.n_recurrent_layers:
            self._recorder.state_layout = {
                "layers": {"attention": cfg.n_attention_layers,
                           "recurrent": cfg.n_recurrent_layers},
                "state_bytes_per_row": cfg.state_bytes_per_row(),
                "kv_bytes_per_position": cfg.kv_bytes_per_position()}
            if cfg.n_window_layers:  # the layers by the model's own kinds
                self._recorder.state_layout.update(
                    kinds={kind: cfg.layer_types.count(kind)
                           for kind in dict.fromkeys(cfg.layer_types)},
                    sliding_window=cfg.sliding_window,
                    window_bytes_per_row=cfg.window_bytes_per_row(),
                    kv_readers=cfg.kv_readers)
        # engine-thread-confined tick state (never touched off-thread):
        # end of the previous decode launch (the tick-gap anchor; reset
        # to None when the engine goes idle), and the tick being
        # gathered: seconds by phase since the last recorded tick, and
        # when that one closed on both clocks. Every span of the loop
        # adds to ``_ph``, so the ticks tile the thread's time.
        self._last_decode_end: Optional[float] = None
        self._ph: Dict[str, float] = {}
        self._t_tick0 = time.perf_counter()
        self._t_wall0 = time.time()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="rt-cb-engine")
        self._thread.start()

    # -- client side ------------------------------------------------------

    def submit_stream(self, prompt: np.ndarray, max_new_tokens: int, *,
                      temperature: float = 0.0, top_k: int = 0,
                      seed: int = 0, obs_ctx: Optional[Dict] = None
                      ) -> "_queue.Queue":
        """Queue one request; returns its token queue (ints, then the
        ``None`` end sentinel). Admission control beyond the pending queue
        belongs to the serving layer (``max_ongoing_requests``).
        ``temperature``/``top_k``/``seed`` select sampled decode (engine
        must be built with ``sampling=True``); the default stays greedy.
        ``obs_ctx`` (a serve {request_id, span_id}) joins the request's
        flight-recorder lifecycle to the serve span tree."""
        return self._submit(prompt, max_new_tokens, None,
                            temperature=temperature, top_k=top_k,
                            seed=seed, obs_ctx=obs_ctx).out

    def submit_cb(self, prompt: np.ndarray, max_new_tokens: int,
                  on_token: Callable[[List[Optional[int]]], None], *,
                  temperature: float = 0.0, top_k: int = 0,
                  seed: int = 0, obs_ctx: Optional[Dict] = None):
        """Callback form: ``on_token(burst)`` fires from the engine
        thread with each tick's token burst (a list of ints; a ``None``
        element marks end-of-stream). Zero consumer threads — an asyncio
        server bridges with ONE ``loop.call_soon_threadsafe`` per burst
        instead of parking an executor thread per stream on a queue (the
        thread-starvation ceiling a 2-core box hits at ~6 streams).
        Returns an opaque handle for :meth:`cancel`."""
        return self._submit(prompt, max_new_tokens, on_token,
                            temperature=temperature, top_k=top_k,
                            seed=seed, obs_ctx=obs_ctx)

    def _submit(self, prompt: np.ndarray, max_new_tokens: int,
                on_token, *, temperature: float = 0.0, top_k: int = 0,
                seed: int = 0,
                obs_ctx: Optional[Dict] = None) -> "_EngineRequest":
        s = len(prompt)
        if s + max_new_tokens + 1 > self.max_len:
            raise ValueError(f"prompt {s} + new {max_new_tokens} exceeds "
                             f"max_len {self.max_len}")
        if (temperature > 0 or top_k > 0) and not self._batcher.sampling:
            raise ValueError("sampling request on a greedy engine: pass "
                             "sampling=True at engine construction")
        req = _EngineRequest(np.asarray(prompt, np.int32), max_new_tokens,
                             on_token, temperature=float(temperature),
                             top_k=int(top_k), seed=int(seed),
                             obs_ctx=obs_ctx)
        with self._work:
            if self._stopped:
                raise RuntimeError("engine is shut down")
            if self._dead is not None:
                raise RuntimeError(f"engine died: {self._dead}")
            self._pending.append(req)
            self._work.notify()
        return req

    def cancel(self, handle) -> None:
        """Drop a request (disconnect): pending requests unqueue, active
        ones free their slot on the next tick. The stream still ends
        with the ``None`` sentinel — a consumer that is NOT the
        canceller (a supervisor thread timing the request out) must not
        block on the queue forever. ``handle`` is the queue
        ``submit_stream`` returned or the handle from ``submit_cb``."""
        with self._work:
            for req in list(self._pending):
                if req is handle or req.out is handle:
                    req.cancelled = True
                    self._pending.remove(req)
                    req.emit_many([_STREAM_END])
                    return
            admitting = self._admitting
            if admitting is not None and (admitting is handle
                                          or admitting.out is handle):
                # mid-prefill (the engine thread runs admission outside
                # the lock): flag it — the post-prefill bookkeeping
                # frees the slot and ends the stream
                admitting.cancelled = True
                return
            for req in self._live.values():
                if req is handle or req.out is handle:
                    req.cancelled = True
                    self._work.notify()
                    return

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {"active": len(self._live),
                   "pending": len(self._pending),
                   "max_slots": self.max_slots,
                   "steps": self._steps,
                   "admitted": self._admitted,
                   "tokens_out": self._tokens_out,
                   # monotonic counters (never reset for the engine's
                   # lifetime): the RLHF bench and `rt serve status`
                   # difference these across polls instead of sampling
                   # instantaneous slot occupancy
                   "tokens_generated": self._tokens_out,
                   "requests_completed": self._requests_completed,
                   "weight_swaps": self._weight_swaps}
            if self._dead is not None:
                out["dead"] = self._dead
        cache = self._batcher.prefix_cache
        if cache is not None:
            # kv stats ride replica stats_window -> controller win_stats
            # -> `rt serve status` hit-rate column / dashboard Serve tab
            out["kv"] = cache.stats()
        if self._recorder.enabled:
            # flight-recorder rollup (tick phases, tick-gap, SLO
            # attainment, goodput) — computed off the engine lock; rides
            # the same replica stats_window path into `rt serve status`
            out["recorder"] = self._recorder.summary()
        return out

    def note_pump_lag(self, lag_s: float) -> None:
        """A ``submit_cb`` consumer that forwards bursts to another
        thread reports here how long after the engine's callback the
        burst was picked up there (the flight recorder's ``pump_lag``)."""
        self._recorder.pump_lag(lag_s)

    def kv_stats(self) -> Optional[Dict[str, Any]]:
        """Prefix-cache counters WITHOUT touching the engine lock (the
        cache has its own): the per-tick metric publisher reads this —
        taking ``_work`` there would contend with submit/cancel on every
        decode launch for four numbers the cache already exposes."""
        cache = self._batcher.prefix_cache
        return cache.stats() if cache is not None else None

    def kv_residency(self) -> List[str]:
        """Chunk digests of the prefixes this engine holds warm — the
        replica reports these so the handle router can bias power-of-two
        choice toward the replica whose cache already covers a request's
        prompt (cache-affinity routing)."""
        cache = self._batcher.prefix_cache
        return cache.digests() if cache is not None else []

    def load_params(self, params: Params,
                    timeout_s: float = 120.0) -> Dict[str, Any]:
        """Drain-barrier weight swap: queue ``params`` as the engine's
        next weights and block until the engine thread has applied them.

        The swap CANNOT be immediate — every active slot's KV cache was
        prefilled with the old weights, and decoding old-KV rows under
        new weights would produce tokens belonging to neither model. So
        the engine thread (a) stops admitting new requests the moment a
        swap is queued (pending requests stay queued, nothing is
        dropped), (b) decodes the active slots to completion under the
        OLD weights — in-flight streams stay token-exact — and then
        (c) swaps and resumes admission, so every later request runs
        token-exact under the NEW weights. A second ``load_params``
        racing the first simply replaces the queued weights (latest
        wins; both callers unblock when the final swap lands).
        """
        state = {"event": threading.Event(), "applied": False,
                 "error": None}
        t0 = time.perf_counter()
        # commit the leaves to the device HERE, once: shipped weights
        # arrive as numpy arrays, and installing those raw would make
        # every subsequent decode tick re-transfer the full model
        # host-to-device when jit commits its arguments
        params = jax.tree_util.tree_map(jnp.asarray, params)
        self._batcher.check_params(params)
        with self._work:
            if self._stopped:
                raise RuntimeError("engine is shut down")
            if self._dead is not None:
                raise RuntimeError(f"engine died: {self._dead}")
            prev = self._pending_swap
            self._pending_swap = (params, [state])
            if prev is not None:
                # coalesce: the superseded swap's waiters ride this one
                self._pending_swap[1].extend(prev[1])
            self._work.notify()
        if not state["event"].wait(timeout_s):
            raise TimeoutError(
                f"weight swap did not drain within {timeout_s}s "
                f"(active requests still decoding)")
        if state["error"] is not None:
            raise RuntimeError(f"weight swap failed: {state['error']}")
        return {"drain_s": round(time.perf_counter() - t0, 4),
                "apply_s": round(state.get("apply_s", 0.0), 6),
                "weight_swaps": self._weight_swaps}

    def check_alive(self) -> None:
        """Raise if the engine thread died on a fatal decode error — the
        serve replica's health check calls this so the controller
        replaces a wedged replica instead of routing into a black hole."""
        with self._lock:
            if self._dead is not None:
                raise RuntimeError(f"continuous engine died: {self._dead}")

    def stopped(self) -> bool:
        """True once the engine was shut down or its thread died — loops
        keyed on the engine's lifetime (the replica's kv-push thread)
        use this as their exit condition."""
        with self._lock:
            return self._stopped or self._dead is not None

    def shutdown(self, timeout_s: float = 5.0) -> None:
        with self._work:
            self._stopped = True
            self._work.notify()
        self._thread.join(timeout=timeout_s)
        # stop the drain thread and drop the @engine/ KV snapshot — the
        # doctor must not grade a dead engine's numbers
        self._recorder.close()

    # -- the engine thread ------------------------------------------------

    def _admit_all(self) -> int:
        """Prefill pending requests into free slots. The jax prefill —
        which can hide a multi-second XLA compile for a new prompt
        length — runs OUTSIDE the lock, so submit/cancel/stats/
        check_alive stay responsive while it compiles (the batcher
        itself is engine-thread-owned and needs no lock); only the
        pending/live bookkeeping is locked.

        Returns how many requests it admitted. Its spans (``admission``
        here, ``kv_restore`` and ``prefill`` inside ``submit_ex``,
        ``record``) follow one another and add to the tick being
        gathered."""
        ph = self._ph
        admitted = 0
        while True:
            with _span("admission", ph):
                with self._work:
                    # honor shutdown BEFORE paying another prefill (each
                    # can hide a multi-second compile) — the stopped
                    # branch in _run ends the remaining streams
                    if self._stopped:
                        return admitted
                    if self._pending_swap is not None:
                        # drain barrier: a queued weight swap holds
                        # admission (a prefill under the old weights
                        # admitted now would decode under the new ones
                        # after the swap)
                        return admitted
                    if not (self._pending and self._batcher._free):
                        return admitted
                    req = self._pending.popleft()
                    if req.cancelled:
                        continue
                    self._admitting = req
                t_pop = time.time()  # the wait for a slot ends here
            try:
                req_id, first_tok, done = self._batcher.submit_ex(
                    req.prompt, req.max_new_tokens,
                    temperature=req.temperature, top_k=req.top_k,
                    seed=req.seed)
            except Exception as e:  # noqa: BLE001 — ONE request's prefill
                # failing (bad shape, transient XLA error) must fail that
                # request, not wedge the shared engine thread. One that
                # took the donated cache with it fails the active
                # requests too: the batcher dropped them with their KV
                with self._work:
                    self._admitting = None
                    lost: Dict[int, _EngineRequest] = {}
                    if isinstance(e, SlotCacheLost):
                        lost, self._live = self._live, {}
                req.emit_many([_STREAM_END])
                for rid, live in lost.items():
                    live.emit_many([_STREAM_END])
                    self._recorder.request_done(rid, t=time.time(),
                                                state="cancelled")
                continue
            la = self._batcher.last_admission
            for phase in ("admission", "kv_restore", "prefill"):
                ph[phase] = ph.get(phase, 0.0) + la[phase + "_s"]
            with _span("admission", ph):
                with self._work:
                    self._admitting = None
                    req.req_id = req_id
                    cancelled = req.cancelled
                    if cancelled:
                        # cancelled mid-prefill: free the slot, end the
                        # stream
                        if not done:
                            self._batcher.cancel(req_id)
                        req.emit_many([_STREAM_END])
                    else:
                        self._admitted += 1
                        req.emit_many([first_tok, _STREAM_END] if done
                                      else [first_tok])
                        self._tokens_out += 1
                        if done:
                            self._requests_completed += 1
                        else:
                            self._live[req_id] = req
            admitted += 1
            # lifecycle record, OUTSIDE the engine lock: admission just
            # produced the first token, so this stamp is the TTFT stamp
            with _span("record", ph):
                now = time.time()
                self._recorder.request_admitted(
                    req_id, t_submit=req.t_submit, t_admit=now,
                    t_admit_start=t_pop,
                    prompt_tokens=len(req.prompt),
                    cached_tokens=la["cached_tokens"],
                    prefill_s=la["prefill_s"],
                    kv_restore_s=la["kv_restore_s"],
                    slot=la["slot"], obs_ctx=req.obs_ctx)
                if cancelled:
                    self._recorder.request_done(req_id, t=now,
                                                state="cancelled")
                elif done:
                    self._recorder.request_done(req_id, t=now, state="done")

    def _maybe_swap_locked(self) -> None:
        """Apply a queued weight swap once the engine is fully drained
        (no active slots, no prefill in flight). Caller holds _work and
        has no span open: the apply is the ``swap_barrier`` span (drain
        time shows up as the preceding ticks' shrinking active counts,
        not here)."""
        if (self._pending_swap is None or self._live
                or self._admitting is not None):
            return
        with _span("swap_barrier", self._ph):
            t_swap0 = time.perf_counter()
            params, waiters = self._pending_swap
            self._pending_swap = None
            self._batcher.params = params
            if self._batcher.prefix_cache is not None:
                # every retained page was computed under the OLD weights:
                # a post-swap prefill restoring one would emit tokens
                # belonging to neither model — invalidate the whole cache
                # at the swap
                self._batcher.prefix_cache.clear()
            self._weight_swaps += 1
            apply_s = time.perf_counter() - t_swap0
            for st in waiters:
                st["apply_s"] = apply_s
                st["applied"] = True
                st["event"].set()
            self._recorder.record_swap(apply_s)

    def _fail_swap_locked(self, reason: str) -> None:
        """Unblock load_params waiters when the engine stops or dies
        before their swap could land. Caller holds _work."""
        if self._pending_swap is None:
            return
        _, waiters = self._pending_swap
        self._pending_swap = None
        for st in waiters:
            st["error"] = reason
            st["event"].set()

    def _close_tick(self, tok_events=(), *, tick: Optional[int] = None,
                    **fields: Any) -> None:
        """Record the tick gathered since the last one closed and open
        the next at this instant. The recorder's own calls and the
        ``on_tick`` hook are the ``record`` span, the new tick's first."""
        t_close, ph = time.perf_counter(), self._ph
        wall_s, t_start = t_close - self._t_tick0, self._t_wall0
        self._ph, self._t_tick0, self._t_wall0 = {}, t_close, time.time()
        with _span("record", self._ph):
            for rid, nburst, done in tok_events:
                self._recorder.request_tokens(rid, nburst, self._t_wall0,
                                              done)
            self._recorder.record_tick(t_start=t_start, wall_s=wall_s,
                                       phases=ph,
                                       moe=self._batcher.take_moe_stats(),
                                       scan_chunks=self._batcher
                                       .take_scan_chunks(),
                                       kv_positions=self._batcher
                                       .take_kv_positions(),
                                       window_positions=self._batcher
                                       .take_window_positions(),
                                       prefill_layer_tokens=self._batcher
                                       .take_prefill_layer_tokens(),
                                       **fields)
            if tick is not None and self._on_tick is not None:
                try:
                    self._on_tick(tick, self.max_slots)
                except Exception:  # noqa: BLE001 — telemetry only
                    pass

    def _run(self) -> None:
        """The loop, as spans that follow one another and never nest
        (``recorder_core.span``): each adds its seconds to the tick being
        gathered and is a ``bench:<phase>`` event on the profiler's
        clock, so a device trace says what the host did in every gap."""
        rec = self._recorder
        while True:
            with _span("admission", self._ph):
                with self._work:
                    # reap cancellations before admitting into their slots
                    doomed = [rid for rid, r in self._live.items()
                              if r.cancelled]
                    for rid in doomed:
                        self._live[rid].emit_many([_STREAM_END])
                        del self._live[rid]
                # slot free + KV capture OUTSIDE the lock: _capture syncs
                # the device and copies the slot's pages to host — under
                # _work that stall would block every submit/cancel (the
                # batcher itself is engine-thread-confined, like
                # step_many). Captures must land BEFORE the swap check: a
                # swap clears the cache, and a doomed slot's pages are
                # old-weight poison the moment it applies.
                for rid in doomed:
                    self._batcher.cancel(rid)
                    rec.request_done(rid, t=time.time(), state="cancelled")
            with self._work:
                self._maybe_swap_locked()
            admitted = self._admit_all()
            with self._work:
                if self._stopped:
                    self._fail_swap_locked("engine shut down mid-drain")
                    for req in list(self._live.values()):
                        req.emit_many([_STREAM_END])
                    self._live.clear()
                    for req in list(self._pending):
                        req.emit_many([_STREAM_END])
                    self._pending.clear()
                    return
                if not self._live:
                    self._maybe_swap_locked()
                    if admitted or self._ph.get("swap_barrier"):
                        # admission-only tick (every admitted request
                        # finished at its first token, or a swap landed)
                        self._close_tick(
                            active=0, pending=len(self._pending), bucket=0,
                            k=0, tokens=admitted, admitted=admitted,
                            gap_s=None)
                    # engine going idle: the next decode launch starts a
                    # fresh gap baseline (an idle engine is not starved)
                    self._last_decode_end = None
                    if self._pending or self._pending_swap is not None:
                        continue  # freshly unblocked work: no idle wait
                    with _span("idle_wait", self._ph):
                        self._work.wait(timeout=0.5)
                    if self._pending or self._pending_swap is not None:
                        # woken for work: the parked stretch is a tick
                        # of its own, so the next one starts with its
                        # work and no launch's tick holds a wait
                        self._close_tick(
                            active=0, pending=len(self._pending), bucket=0,
                            k=0, tokens=0, admitted=0, gap_s=None)
                    continue
            # decode OUTSIDE the lock: submit/cancel stay responsive
            # while the step runs (the jax call is the long pole).
            # Tick stride: fuse decode_stride steps per launch while any
            # active request still wants that many; drop to single steps
            # for the stragglers' tail so no request overruns its budget
            # by a whole stride of discarded work.
            k = (self.decode_stride
                 if self._batcher.max_remaining >= self.decode_stride
                 else 1)
            n_active = self._batcher.num_active
            bucket = 1 if n_active == 1 else self.max_slots
            t_dec0 = time.perf_counter()
            # tick-gap: decode-launch start minus the previous launch's
            # end, while slots stayed active — THE starvation signal (a
            # long-prompt prefill burst between launches shows up here)
            gap_s = (t_dec0 - self._last_decode_end
                     if self._last_decode_end is not None else None)
            try:
                # step_many is three spans of its own (last_step)
                emitted = self._batcher.step_many(k)
            except Exception as e:  # noqa: BLE001 — a failed decode step
                # poisons the shared cache state: end every stream NOW
                # (clients see truncation, not a hang) and mark the
                # engine dead so the replica health check fails and the
                # controller replaces the replica
                with self._work:
                    self._dead = f"{type(e).__name__}: {e}"[:300]
                    self._fail_swap_locked(self._dead)
                    for req in list(self._live.values()):
                        req.emit_many([_STREAM_END])
                    self._live.clear()
                    for req in list(self._pending):
                        req.emit_many([_STREAM_END])
                    self._pending.clear()
                return
            t_dec1 = time.perf_counter()
            self._last_decode_end = t_dec1
            self._ph["decode_step"] = t_dec1 - t_dec0
            tick_tokens = admitted
            tok_events: List[Tuple[int, int, bool]] = []
            with _span("token_delivery", self._ph):
                with self._work:
                    self._steps += 1
                    for rid, toks, done in emitted:
                        req = self._live.get(rid)
                        if req is None:
                            continue  # cancelled between step and dispatch
                        burst = [int(t) for t in toks]
                        self._tokens_out += len(burst)
                        tick_tokens += len(burst)
                        tok_events.append((rid, len(burst), done))
                        if done:
                            burst.append(_STREAM_END)
                            del self._live[rid]
                            self._requests_completed += 1
                        req.emit_many(burst)
                    live_n = len(self._live)
                    pending_n = len(self._pending)
            self._close_tick(
                tok_events, tick=live_n, active=n_active, pending=pending_n,
                bucket=bucket, k=k, tokens=tick_tokens, admitted=admitted,
                gap_s=gap_s, decode_parts=self._batcher.last_step)


def _row_sample(logits, temp, top_k, sub):
    """One row's token rule: greedy when ``temp <= 0`` (selected by
    ``where`` so a greedy row in a sampling engine is bit-identical to
    the greedy program), else temperature softmax sampling, optionally
    top-k truncated (``top_k`` is a traced per-row value; 0 disables).
    The one sampling rule of ``generate._sample_token``, per-row."""
    greedy = jnp.argmax(logits).astype(jnp.int32)
    scaled = logits / jnp.where(temp > 0, temp, 1.0)
    v = logits.shape[-1]
    srt = jnp.sort(scaled)  # ascending
    kth = srt[jnp.clip(v - top_k, 0, v - 1)]
    thresh = jnp.where(top_k > 0, kth, -jnp.inf)
    masked = jnp.where(scaled < thresh, -jnp.inf, scaled)
    sampled = jax.random.categorical(sub, masked).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


def _first_token(logits_last, sample: bool, temp=None, top_k=None,
                 key=None):
    """Admission's first token from the last prompt position's logits
    ([1, V]); sampling consumes one split of the request's key chain."""
    if not sample:
        return jnp.argmax(logits_last, axis=-1).astype(jnp.int32), None
    key, sub = jax.random.split(key)
    return _row_sample(logits_last[0], temp, top_k, sub)[None], key


def _with_stats(toks, stats):
    """A program's tokens and, from a sparse model, its routing counters
    (``moe.SERVED_STATS``) behind them in ONE int32 vector: the host reads
    both in the read that fences the launch. A dense model's tokens go
    out as they are."""
    if stats is None:
        return toks
    return jnp.concatenate([toks.reshape(-1), stats])


def scan_chunks(cfg, tokens: int) -> int:
    """Chunks a prefill of ``tokens`` runs its recurrent layers' scans
    over, all layers together; 0 for a model without such layers."""
    if not cfg.n_recurrent_layers:
        return 0
    return cfg.n_recurrent_layers * ssm.n_chunks(tokens, cfg.mamba_chunk_size)


def _write_row(cache: Dict[str, jax.Array], row: Dict[str, jax.Array], slot
               ) -> Dict[str, jax.Array]:
    """A prefilled row into its slot of the (donated) slot tree, in place,
    buffer by buffer (its K and V; a recurrent layer's final state and
    tail): one row's bytes move, not the cache's."""
    with jax.named_scope("kv_scatter"):
        return {name: jax.lax.dynamic_update_slice(
                    buf, row[name], (0, slot) + (0,) * (buf.ndim - 2))
                for name, buf in cache.items()}


def _on_slot_tree(cfg, program):
    """``program(params, cache, *args) -> (cache, *results)`` on the slot
    tree, as the jitted function the batcher calls: ``(params, *the tree's
    buffers, *args) -> (*the tree's buffers, *results)``, the buffers in
    ``generate.cache_names``'s order and every one of them donated. It
    keeps ``program``'s name, which is the program's in a device trace."""
    names = G.cache_names(cfg)
    n = len(names)

    @functools.wraps(program)
    def flat(params, *args):
        cache, *results = program(params, dict(zip(names, args[:n])),
                                  *args[n:])
        return (*(cache[name] for name in names), *results)

    return jax.jit(flat, donate_argnums=tuple(range(1, 1 + n)))


@functools.lru_cache(maxsize=64)
def _compiled_slot_prefill(cfg, s: int, max_slots: int, max_len: int,
                           sample: bool = False):
    """Prefill ONE prompt into ONE slot of the shared cache, which the
    program takes donated; returns the cache and the first token (greedy,
    or sampled off the request's key when the engine runs the sampling
    programs, which also return the key)."""

    # the program's name in a device trace (``jit_rt_prefill``)
    def rt_prefill(params, cache, prompt, slot, temp=None, top_k=None,
                   key=None):
        logits, row, stats = G._forward_with_cache_stats(
            params, prompt, cfg, G.init_cache(cfg, 1, max_len), 0)
        with jax.named_scope("head_sample"):
            first, key = _first_token(logits[:, -1, :], sample, temp, top_k,
                                      key)
        cache = _write_row(cache, row, slot)
        first = _with_stats(first, stats)
        return (cache, first, key) if sample else (cache, first)

    return _on_slot_tree(cfg, rt_prefill)


@functools.lru_cache(maxsize=256)
def _compiled_cached_prefill(cfg, c: int, sl: int, max_slots: int,
                             max_len: int, sample: bool = False):
    """Warm admission: restore ``c`` cached prefix positions into the
    slot row and prefill ONLY the ``sl``-token suffix at offset ``c`` —
    prefill compute scales with the uncached suffix, which is the TTFT
    collapse on shared-prefix traffic. Token-exact vs the cold path: the
    restored K/V are the same per-position values a full prefill would
    recompute (each position's K/V depends only on tokens <= it, and
    attention always masks over the same full-length row cache). The
    cache is donated, as in the cold prefill. Pages of K and V say nothing
    of a recurrent layer's state: a model with such layers is refused."""
    _refuse_prefix_cache(cfg)

    def rt_cached_prefill(params, cache, pk, pv, suffix, slot, temp=None,
                          top_k=None, key=None):
        row = G.init_cache(cfg, 1, max_len)
        row = {"k": row["k"].at[:, 0, :c].set(pk.astype(cfg.compute_dtype)),
               "v": row["v"].at[:, 0, :c].set(pv.astype(cfg.compute_dtype))}
        logits, row, stats = G._forward_with_cache_stats(params, suffix, cfg,
                                                         row, c)
        with jax.named_scope("head_sample"):
            first, key = _first_token(logits[:, -1, :], sample, temp, top_k,
                                      key)
        cache = _write_row(cache, row, slot)
        first = _with_stats(first, stats)
        return (cache, first, key) if sample else (cache, first)

    return _on_slot_tree(cfg, rt_cached_prefill)


@functools.lru_cache(maxsize=128)
def _compiled_bucket_scan(cfg, bucket: int, max_slots: int, max_len: int,
                          k: int, sample: bool = False):
    """``k`` fused decode steps, in place on the donated slot tree, for
    the ``bucket`` rows from slot ``slot0`` on: a ``lax.scan`` of
    ``generate.decode_step_on_slots`` with the tree in its carry, which
    returns the tree and the [k, bucket] token block (from a sparse
    model with its routing counters behind it: ``_with_stats``). One launch per K
    tokens per occupancy bucket — the decode-side make_multi_step. The
    full bucket's rows are all the slots (``slot0`` is not looked at);
    the lone row is addressed by a dynamic slice at ``slot0``. The
    sampling variant additionally carries each row's PRNG key through
    the scan (one split per token, so a request's draw chain is
    independent of batch composition and tick stride — seeded
    determinism) and returns the keys [bucket, 2]."""

    # the program's name in a device trace (``jit_rt_decode``)
    def rt_decode(params, cache, cur, pos, slot0, temp=None, topk=None,
                  keys=None):
        first = slot0 if bucket < max_slots else 0

        def body(carry, _):
            cache, cur, pos, keys = carry
            logits, cache, stats = G.decode_step_on_slots(
                params, cur, cfg, cache, first, pos)
            with jax.named_scope("head_sample"):
                if sample:
                    keys, subs = jnp.moveaxis(
                        jax.vmap(jax.random.split)(keys), 1, 0)
                    nxt = jax.vmap(_row_sample)(logits, temp, topk, subs)
                else:
                    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt, pos + 1, keys), (nxt, stats)

        (cache, _, _, keys), (toks, stats) = jax.lax.scan(
            body, (cache, cur, pos, keys), None, length=k)
        toks = _with_stats(toks, G._fold_stats(stats))
        return (cache, toks, keys) if sample else (cache, toks)

    return _on_slot_tree(cfg, rt_decode)


@functools.lru_cache(maxsize=128)
def _decode_executable(cfg, bucket: int, max_slots: int, max_len: int,
                       k: int, sample: bool, weights: Tuple):
    """``_compiled_bucket_scan``'s program compiled ahead of time for
    ``weights`` (the tree and the leaves' shapes, types and shardings),
    and what the compiled form does to the slot tree (``bucket``, ``k``
    and ``hlo_copies.cache_traffic``): read off the very executable that
    runs, on whatever backend this is. One compile per key for the
    process, as with ``jit``'s own cache."""
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
    cache = jax.eval_shape(lambda: G.init_cache(cfg, max_slots, max_len))
    args = (i32((bucket,)), i32((bucket,)), i32(()))
    if sample:
        args += (jax.ShapeDtypeStruct((bucket,), jnp.float32), i32((bucket,)),
                 jax.ShapeDtypeStruct((bucket, 2), jnp.uint32))
    tree, leaves = weights
    # the program's own seconds, by what they went on; they join its
    # entry in ``decode_programs`` and its span in the lifecycle record
    took: Dict[str, float] = {}
    with lifecycle.span("decode_program", parent="engine_init",
                        bucket=bucket, k=k) as whole:
        with _span("lower_s", took):
            lowered = _compiled_bucket_scan(cfg, bucket, max_slots, max_len,
                                            k, sample).lower(
                jax.tree.unflatten(tree, leaves),
                *(cache[name] for name in G.cache_names(cfg)), *args)
        with _span("compile_s", took):
            fn = lowered.compile()
        with _span("traffic_s", took):
            traffic = hlo_copies.cache_traffic(
                fn, cache, rows=bucket, steps=k,
                bounds=G.kv_read_bounds(max_len),
                length_axis=cfg.kv_length_axis)
    whole.entry.update(took)
    return fn, dict(bucket=bucket, k=k, **traffic, **took)

