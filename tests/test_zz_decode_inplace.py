"""The engine's decode step in place on the donated slot cache.

Three things are held here, none of which needs a chip:

- what the compiled decode program does to the cache, read off the
  executable the batcher runs (``cache_donated``,
  ``cache_copy_bytes_per_step``): a refactor that brings a cache-sized
  copy back shows on this backend's compile;
- that the batched in-place step emits, per request, exactly
  ``generate.generate``'s tokens, whatever shares the launch;
- the hazards donation brings: every holder of the old buffers must be
  gone or rebound before the next call, and a call that fails must not
  leave the batcher holding a deleted buffer.

(The TPU compiler's own copies are guarded in ``test_aot_tpu_compile.py``.)
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate as G
from ray_tpu.models import llama, moe, serving
from ray_tpu.models.serving import (ContinuousBatcher, ContinuousEngine,
                                    PrefixKVCache, SlotCacheLost)

CONFIGS = {"dense": (llama.PRESETS["debug"], llama.init_params),
           "moe": (moe.PRESETS["moe-debug"], moe.init_params)}
SLOTS, MAX_LEN = 4, 64


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def model(request):
    cfg, init = CONFIGS[request.param]
    return cfg, init(jax.random.key(0), cfg)


def _prompt(cfg, n, salt):
    return np.random.default_rng(salt).integers(
        0, cfg.vocab_size, size=n).astype(np.int32)


def _expected(params, cfg, prompt, n, sampled, seed):
    """``generate.generate`` on the request alone: greedy, or the engine's
    per-request key chain (``PRNGKey(seed)``, one split per token)."""
    kw = dict(temperature=0.8, top_k=7, key=jax.random.PRNGKey(seed)) \
        if sampled else {}
    out = G.generate(params, jnp.asarray(prompt)[None, :], cfg,
                     max_new_tokens=n, max_len=MAX_LEN, **kw)
    return np.asarray(out)[0].tolist()


# ---------------------------------------------------------------------------
# the counter the mechanism brings
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("bucket", [1, SLOTS])
@pytest.mark.parametrize("sampling", [False, True],
                         ids=["greedy", "sampling"])
def test_decode_program_steps_the_cache_in_place(sampling, bucket, k):
    """Donated, and per step at most one cache-worth materialised (the
    layer's rows, for the attention product; a whole-cache copy or a
    per-layer stack-back would each add a cache). A float32 cache: the
    CPU compiler has no bf16 scatter and converts the whole cache round
    one, which is this backend's cost and not the program's."""
    cfg = dataclasses.replace(llama.PRESETS["debug"],
                              compute_dtype=jnp.float32)
    b = ContinuousBatcher(llama.init_params(jax.random.key(0), cfg), cfg,
                          max_slots=SLOTS, max_len=MAX_LEN, sampling=sampling)
    b._program(bucket, k)
    (stats,) = b.program_stats
    assert (stats["bucket"], stats["k"]) == (bucket, k)
    assert stats["cache_donated"] is True
    assert stats["cache_bytes"] == 2 * b._ck.nbytes
    assert stats["cache_copy_bytes_per_step"] \
        <= stats["cache_bytes"] * bucket // SLOTS, stats


@pytest.mark.parametrize("bucket", [1, SLOTS])
def test_the_counter_sees_a_bounded_read(bucket, monkeypatch):
    """With the read bounded (chunk 16 of ``max_len`` 64: four branches) the
    counter still has something to hold to the limit above: it knows the
    branches' shapes, takes the costliest branch for the step's upper
    limit, and says what the slices read at the full bound (the rows'
    share of the cache, as before) and at the least (a chunk of each row).
    On this backend a branch materialises its slice and the slice
    transposed (it is held to the cache's own layout, for the chip's
    compiler: ``generate.attend_in_place``): twice the rows, no more."""
    monkeypatch.setattr(G, "KV_CHUNK", 16)
    serving._compiled_bucket_scan.cache_clear()
    serving._decode_executable.cache_clear()
    cfg = dataclasses.replace(llama.PRESETS["debug"],
                              compute_dtype=jnp.float32)
    b = ContinuousBatcher(llama.init_params(jax.random.key(0), cfg), cfg,
                          max_slots=SLOTS, max_len=MAX_LEN)
    try:
        b._program(bucket, 8)
    finally:
        serving._compiled_bucket_scan.cache_clear()
        serving._decode_executable.cache_clear()
    (stats,) = b.program_stats
    share = stats["cache_bytes"] * bucket // SLOTS
    assert stats["cache_donated"] is True
    assert stats["cache_read_bytes_per_step"] == share
    assert stats["cache_read_bytes_per_step_least"] == share * 16 // MAX_LEN
    assert 0 < stats["cache_copy_bytes_per_step"] <= 2 * share, stats
    # the whole-row program, for the same rows: one length, one number
    monkeypatch.setattr(G, "KV_CHUNK", MAX_LEN)
    whole = ContinuousBatcher(b.params, cfg, max_slots=SLOTS, max_len=MAX_LEN)
    whole._program(bucket, 8)
    (stats,) = whole.program_stats
    assert stats["cache_read_bytes_per_step"] \
        == stats["cache_read_bytes_per_step_least"] == share


def test_the_counter_sees_a_copy_that_comes_back():
    """The reader itself: the same step with the cache as the layer
    scan's ``xs``/``ys`` (sliced out and stacked back every layer, as the
    decode program did before it stepped in place) must read as more
    than one cache a step, and without donation as not donated."""
    cfg = dataclasses.replace(llama.PRESETS["debug"],
                              compute_dtype=jnp.float32)
    params = llama.init_params(jax.random.key(0), cfg)
    cache = jnp.zeros((cfg.n_layers, SLOTS, MAX_LEN, cfg.n_kv_heads,
                       cfg.head_dim), cfg.compute_dtype)

    def stacked(params, ck, cv, cur, pos):
        def body(carry, _):
            ck, cv, cur = carry
            logits, c = G._forward_with_cache(
                params, cur[:, None], cfg, {"k": ck, "v": cv}, pos)
            nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
            return (c["k"], c["v"], nxt), nxt
        (ck, cv, _), toks = jax.lax.scan(body, (ck, cv, cur), None, length=8)
        return ck, cv, toks

    compiled = jax.jit(stacked).lower(
        params, cache, cache, jnp.zeros(SLOTS, jnp.int32),
        jnp.int32(3)).compile()
    got = serving.hlo_copies.cache_traffic(compiled, cache, rows=SLOTS,
                                           steps=8)
    assert got["cache_donated"] is False
    assert got["cache_copy_bytes_per_step"] > got["cache_bytes"], got


def test_engine_stats_carry_the_decode_programs():
    cfg = llama.PRESETS["debug"]
    eng = ContinuousEngine(llama.init_params(jax.random.key(0), cfg), cfg,
                           max_slots=SLOTS, max_len=MAX_LEN, decode_stride=4,
                           kv_cache_bytes=0, kv_label="inplace")
    try:
        progs = eng.stats()["recorder"]["decode_programs"]
        assert sorted((p["bucket"], p["k"]) for p in progs) == [
            (1, 1), (1, 4), (SLOTS, 1), (SLOTS, 4)]
        assert all(p["cache_donated"] for p in progs)
        window = eng._recorder.window_summary(0.0, 1e12)
        assert window["decode_programs"] == progs
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# token exactness of the new step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("occupancy", [1, 3, 4])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "seeded"])
def test_in_place_step_is_token_exact(model, sampled, k, occupancy):
    """``occupancy`` of 4 slots filled with staggered prompt lengths; one
    request ends mid-launch (3 tokens under k=8), its slot is reused by a
    new prompt while the others are mid-flight, and every request's tokens
    equal ``generate.generate``'s on that request alone."""
    cfg, params = model
    b = ContinuousBatcher(params, cfg, max_slots=SLOTS, max_len=MAX_LEN,
                          sampling=sampled)
    lens, wants = (5, 9, 7, 11), (3, 14, 10, 17)
    reqs = {}  # req_id -> (prompt, n, seed)
    got = {}

    def admit(n_prompt, n_new, seed):
        prompt = _prompt(cfg, n_prompt, seed)
        kw = dict(temperature=0.8, top_k=7, seed=seed) if sampled else {}
        rid, first, _ = b.submit_ex(prompt, n_new, **kw)
        reqs[rid] = (prompt, n_new, seed)
        got[rid] = [first]

    for i in range(occupancy):
        admit(lens[i], wants[i], seed=20 + i)
    reused = False
    while b.num_active:
        for rid, toks, done in b.step_many(k):
            got[rid].extend(toks)
            if done and not reused:
                # the freed slot, at once, beside rows that are mid-flight
                reused = True
                admit(6, 9, seed=31)
    assert reused and len(reqs) == occupancy + 1
    for rid, (prompt, n, seed) in reqs.items():
        assert got[rid] == _expected(params, cfg, prompt, n, sampled,
                                     seed), (rid, len(prompt), n)


# ---------------------------------------------------------------------------
# donation hazards
# ---------------------------------------------------------------------------


def _aliases_the_cache(fn, *args) -> bool:
    """Whether the lowered program takes K and V donated and returns them
    aliased: behaviour alone would pass on a backend that ignores
    donation."""
    mem = fn.lower(*args).compile().memory_analysis()
    return mem.alias_size_in_bytes >= args[1].nbytes + args[2].nbytes


def _greedy_batcher(**kw):
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    return cfg, params, ContinuousBatcher(params, cfg, max_slots=SLOTS,
                                          max_len=MAX_LEN, **kw)


def test_every_engine_program_aliases_the_cache():
    cfg, params, b = _greedy_batcher()
    cache = (b._ck, b._cv)
    prompt = jnp.zeros((1, 8), jnp.int32)
    assert _aliases_the_cache(
        serving._compiled_slot_prefill(cfg, 8, SLOTS, MAX_LEN),
        params, *cache, prompt, 0)
    pages = jnp.zeros((cfg.n_layers, 16, cfg.n_kv_heads, cfg.head_dim),
                      cfg.compute_dtype)
    assert _aliases_the_cache(
        serving._compiled_cached_prefill(cfg, 16, 8, SLOTS, MAX_LEN),
        params, *cache, pages, pages, prompt, 0)
    for bucket in (1, SLOTS):
        assert _aliases_the_cache(
            serving._compiled_bucket_scan(cfg, bucket, SLOTS, MAX_LEN, 8),
            params, *cache, jnp.zeros(bucket, jnp.int32),
            jnp.zeros(bucket, jnp.int32), jnp.int32(0))


def test_warmup_then_submit():
    """``warmup`` runs the programs on the donated cache and must rebind
    it: a batcher left holding the buffers it gave away fails here."""
    cfg, params, b = _greedy_batcher()
    before = (b._ck, b._cv)
    b.warmup(prompt_lens=(7,), strides=(1, 4))
    assert before[0].is_deleted() and before[1].is_deleted()
    assert not (b._ck.is_deleted() or b._cv.is_deleted())
    prompt = _prompt(cfg, 7, 1)
    rid = b.submit(prompt, 9)
    assert b.run_to_completion()[rid] == _expected(params, cfg, prompt, 9,
                                                   False, 0)


def test_decode_follows_weights_on_another_device():
    """The decode executables are compiled ahead of time, for the weights'
    placement too: weights committed to another device take the (never
    committed) cache with them, as they do through ``jit``."""
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    cfg = llama.PRESETS["debug"]
    there = jax.devices()[1]
    params = jax.device_put(llama.init_params(jax.random.key(0), cfg), there)
    b = ContinuousBatcher(params, cfg, max_slots=SLOTS, max_len=MAX_LEN)
    prompt = _prompt(cfg, 7, 12)
    rid = b.submit(prompt, 9)
    assert b.run_to_completion()[rid] == _expected(params, cfg, prompt, 9,
                                                   False, 0)
    assert b._ck.devices() == {there}


def test_load_params_between_two_launches():
    """A weight swap lands between launches that donate the cache: the
    engine reads nothing stale, and the request after the swap is exact
    under the new weights."""
    cfg = llama.PRESETS["debug"]
    old = llama.init_params(jax.random.key(0), cfg)
    new = llama.init_params(jax.random.key(5), cfg)
    eng = ContinuousEngine(old, cfg, max_slots=SLOTS, max_len=MAX_LEN,
                           decode_stride=4, kv_cache_bytes=0)
    try:
        prompt = _prompt(cfg, 9, 2)
        a = list(iter(eng.submit_stream(prompt, 10).get, None))
        eng.load_params(new, timeout_s=60)
        c = list(iter(eng.submit_stream(prompt, 10).get, None))
    finally:
        eng.shutdown()
    assert a == _expected(old, cfg, prompt, 10, False, 0)
    assert c == _expected(new, cfg, prompt, 10, False, 0)


def test_capture_after_a_donated_launch():
    """With the prefix cache on, a finished request's pages are read from
    the cache after the launch that donated it (the rebound buffers), and
    a second request restores them token-exact."""
    cache = PrefixKVCache(chunk=8, max_bytes=1 << 20)
    cfg, params, b = _greedy_batcher(prefix_cache=cache)
    prompt = _prompt(cfg, 21, 3)
    rid = b.submit(prompt, 6)
    cold = b.run_to_completion()[rid]
    assert cache.stats()["pages"] == 1
    rid = b.submit(prompt, 6)
    assert b.last_admission["cached_tokens"] == 16
    assert b.run_to_completion()[rid] == cold \
        == _expected(params, cfg, prompt, 6, False, 0)


@pytest.mark.parametrize("where", ["decode", "prefill"])
def test_a_failed_launch_leaves_no_deleted_buffer(monkeypatch, where):
    """An error out of the compiled call after it consumed the cache: the
    batcher starts over on a zeroed cache with every slot free, says so
    (``SlotCacheLost``), and admits again, token-exact."""
    cfg, params, b = _greedy_batcher()
    prompt = _prompt(cfg, 7, 4)
    b.submit(prompt, 12)
    b.step_many(4)

    def consumed_then_failed(real):
        def run(params, ck, cv, *args):
            real(params, ck, cv, *args)  # takes the donated buffers
            raise RuntimeError("injected device error")
        return run

    if where == "decode":
        real = b._program(SLOTS, 4)
        b.submit(_prompt(cfg, 5, 5), 12)  # two active: the full bucket
        monkeypatch.setattr(b, "_program",
                            lambda *a: consumed_then_failed(real))
        with pytest.raises(SlotCacheLost, match="injected"):
            b.step_many(4)
    else:
        real = serving._compiled_slot_prefill
        monkeypatch.setattr(
            serving, "_compiled_slot_prefill",
            lambda *a, **k: consumed_then_failed(real(*a, **k)))
        with pytest.raises(SlotCacheLost, match="injected"):
            b.submit(_prompt(cfg, 5, 5), 12)
    monkeypatch.undo()
    assert not (b._ck.is_deleted() or b._cv.is_deleted())
    assert b.num_active == 0 and sorted(b._free) == list(range(SLOTS))
    rid = b.submit(prompt, 12)
    assert b.run_to_completion()[rid] == _expected(params, cfg, prompt, 12,
                                                   False, 0)


def test_a_call_that_fails_before_it_runs_keeps_the_cache(monkeypatch):
    """An error before the program consumed its arguments (here: raised
    in place of the call) loses nothing: the active request goes on."""
    cfg, params, b = _greedy_batcher()
    prompt = _prompt(cfg, 7, 6)
    rid = b.submit(prompt, 12)

    def refuses(*a, **k):
        raise ValueError("bad argument")

    monkeypatch.setattr(serving, "_compiled_slot_prefill",
                        lambda *a, **k: refuses)
    with pytest.raises(ValueError):
        b.submit(_prompt(cfg, 5, 7), 4)
    monkeypatch.undo()
    assert b.num_active == 1 and len(b._free) == SLOTS - 1
    assert b.run_to_completion()[rid] == _expected(params, cfg, prompt, 12,
                                                   False, 0)


def test_engine_fails_live_streams_when_a_prefill_loses_the_cache(
        monkeypatch):
    """Through the engine: the stream whose prefill failed ends, the live
    streams whose KV went with the cache end too, and the next request is
    served, token-exact."""
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    eng = ContinuousEngine(params, cfg, max_slots=SLOTS, max_len=MAX_LEN,
                           decode_stride=1, kv_cache_bytes=0)
    real = serving._compiled_slot_prefill
    poisoned = 13  # the prompt length whose prefill fails

    def prefill(cfg_, s, *a, **k):
        fn = real(cfg_, s, *a, **k)
        if s != poisoned:
            return fn

        def run(params, ck, cv, *args):
            fn(params, ck, cv, *args)
            raise RuntimeError("injected device error")
        return run

    monkeypatch.setattr(serving, "_compiled_slot_prefill", prefill)
    try:
        live = eng.submit_stream(_prompt(cfg, 9, 8), 40)
        assert live.get(timeout=60) is not None  # admitted and decoding
        bad = eng.submit_stream(_prompt(cfg, poisoned, 9), 4)
        assert list(iter(lambda: bad.get(timeout=60), None)) == []
        cut = list(iter(lambda: live.get(timeout=60), None))
        assert len(cut) < 39  # ended early, with its end sentinel
        eng.check_alive()
        prompt = _prompt(cfg, 7, 10)
        q = eng.submit_stream(prompt, 8)
        again = list(iter(lambda: q.get(timeout=60), None))
        assert again == _expected(params, cfg, prompt, 8, False, 0)
    finally:
        eng.shutdown()
