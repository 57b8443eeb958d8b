"""Decode attention reads below the launch's furthest row
(``generate.kv_read_bound``), not the positions a slot was allocated.

Held here, on the CPU at a tiny size:

- the bound is one rule for the program (jnp) and the recorder (numpy);
- the bounded step against the whole-row form on the same cache (the module's
  chunk set to ``max_len``: one branch, the slice-and-``mha`` of before), for
  a dense GQA config with rotation, an OLMoE-like MHA config with QK-norm and
  a hybrid (no rotation, ``attn_scale``), for the lone-row and the full
  bucket, with rows on both sides of a chunk edge, at ``max_len - 1`` and
  past ``max_len``, and through 8 fused steps that cross an edge;
- a scripted batcher run token for token against the parent's tokens
  (digests taken in a checkout of commit 724dd89, PR 31, by
  ``scripted_tokens_digest`` below, float32 throughout);
- the recorder's two counters against a hand count, for all three families,
  and what ``rt engine stats`` prints.

(What the chip's compiler makes of the branches is in
``test_aot_tpu_compile.py``.)
"""

import contextlib
import dataclasses
import hashlib
import io
import os
import sys
import time
from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import spec  # noqa: E402

from ray_tpu.models import generate as G  # noqa: E402
from ray_tpu.models import llama, moe, serving  # noqa: E402
from ray_tpu.models.serving import ContinuousBatcher, ContinuousEngine  # noqa: E402
from ray_tpu.util import engine_recorder as ER  # noqa: E402

F32 = dict(param_dtype=jnp.float32, compute_dtype=jnp.float32)
HYBRID = {"config": {
    "attention_multiplier": 0.125, "embedding_multiplier": 12,
    "hidden_size": 64, "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "logits_scaling": 8, "mamba_chunk_size": 8, "mamba_d_conv": 4,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_n_heads": 8, "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "shared_intermediate_size": 128,
    "tie_word_embeddings": True, "vocab_size": 256}, "assumed": {}}


def _dense(max_len):
    cfg = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=128, max_seq_len=max_len,
                            rope_theta=1e6, **F32)
    return cfg, llama.init_params(jax.random.key(1), cfg)


def _olmoe_like(max_len):
    cfg = moe.MoEConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=4, d_ff=32, max_seq_len=max_len,
                        n_experts=8, top_k=3, norm_topk_prob=False,
                        qk_norm=True, **F32)
    return cfg, moe.init_params(jax.random.key(1), cfg)


def _hybrid(max_len):
    family = spec.load_family("ssm_hybrid")
    cfg = dataclasses.replace(
        family.program_config(HYBRID, 4, max_seq_len=max_len),
        compute_dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, family.init_params(jax.random.key(1), cfg)


FAMILIES = {"dense-gqa-rope": _dense, "olmoe-like-mha-qknorm": _olmoe_like,
            "hybrid-nope-scale": _hybrid}


@pytest.fixture
def chunk(monkeypatch):
    """Sets the module's chunk for a test (and back), with the compiled
    decode programs of either setting dropped: the chunk is no part of
    their key, being a constant of the module."""
    def set_to(n):
        monkeypatch.setattr(G, "KV_CHUNK", n)
        serving._compiled_bucket_scan.cache_clear()
        serving._decode_executable.cache_clear()

    yield set_to
    serving._compiled_bucket_scan.cache_clear()
    serving._decode_executable.cache_clear()


# ---- the bound ---------------------------------------------------------------

@pytest.mark.parametrize("max_len", [64, 600, 2048])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_bound_is_one_rule_for_program_and_recorder(seed, max_len):
    rng = np.random.default_rng(seed)
    for rows in (1, 5, 64):
        pos = rng.integers(0, max_len + 40, rows).astype(np.int32)
        on_host = G.kv_read_bound(pos, max_len, np)
        in_program = jax.jit(lambda p: G.kv_read_bound(p, max_len))(pos)
        assert int(on_host) == int(in_program)
        assert int(on_host) in G.kv_read_bounds(max_len)
        assert min(int(pos.max()) + 1, max_len) <= int(on_host) \
            < min(int(pos.max()) + 1, max_len) + G.KV_CHUNK
    assert G.KV_CHUNK % 128 == 0
    assert G.kv_read_bounds(2048) == tuple(range(256, 2049, 256))
    assert int(G.kv_read_bound(np.array([0, 5000]), 600, np)) == 600
    assert int(G.kv_read_bound(np.array([0, 255]), 600, np)) == 256
    assert int(G.kv_read_bound(np.array([256, 3]), 600, np)) == 512


# ---- the bounded step against the whole-row form -----------------------------

SLOTS, MAX_LEN, EDGE = 5, 72, 16  # bounds 16, 32, 48, 64, 72
# rows on both sides of a chunk edge, one at the last position, one past the
# end (it writes nothing; nobody reads its token), one short
POSITIONS = [EDGE - 1, EDGE, MAX_LEN - 1, MAX_LEN + 3, 2]


def _filled_cache(cfg, salt):
    keys = iter(jax.random.split(jax.random.key(salt), 8))
    return {name: jax.random.normal(next(keys), buf.shape, jnp.float32
                                    ).astype(buf.dtype)
            for name, buf in G.init_cache(cfg, SLOTS, MAX_LEN).items()}


def _stepper(params, cfg):
    """(cache, tokens, first slot, positions) -> (logits, cache) as a
    program of its own, traced at the chunk that stands at its first call;
    the first slot is an argument, as it is the engine's, so the five lone
    rows are one program a chunk and not five."""
    return jax.jit(lambda c, t, s, p: G.decode_step_on_slots(
        params, t, cfg, c, s, p)[:2])


@pytest.mark.parametrize("bucket", ["lone-row", "full"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_bounded_step_equals_the_whole_row_step(family, bucket, chunk):
    cfg, params = FAMILIES[family](MAX_LEN)
    cache = _filled_cache(cfg, 3)
    tok = np.asarray([7, 11, 13, 17, 19], np.int32)
    pos = np.asarray(POSITIONS, np.int32)
    launches = ([(0, slice(0, SLOTS))] if bucket == "full"
                else [(i, slice(i, i + 1)) for i in range(SLOTS)])

    def run():
        step = _stepper(params, cfg)
        return [step(cache, tok[rows], jnp.int32(slot0), pos[rows])
                for slot0, rows in launches]

    chunk(MAX_LEN)  # one branch: every allocated position, as before
    wholes = run()
    chunk(EDGE)
    assert len(G.kv_read_bounds(MAX_LEN)) == 5
    for (_, rows), (whole, whole_cache), (got, got_cache) in zip(
            launches, wholes, run()):
        whole, got = np.asarray(whole), np.asarray(got)
        # (a row past its end attends to nothing it wrote: nobody reads it)
        live = pos[rows] < MAX_LEN
        off = np.abs(got - whole)[live]
        assert off.max(initial=0.0) < 2e-6 * float(np.abs(whole).max())
        assert (got.argmax(-1) == whole.argmax(-1))[live].all()
        for name in got_cache:  # what a step writes is what it wrote before
            np.testing.assert_allclose(got_cache[name], whole_cache[name],
                                       rtol=0, atol=1e-5)


@pytest.mark.parametrize("bucket", [1, SLOTS])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_eight_fused_steps_cross_a_chunk_edge(family, bucket, chunk):
    """The bound advances with ``pos`` inside the fused scan: rows that start
    three positions before an edge read one more chunk from the fourth step
    on, and emit the whole-row program's tokens."""
    cfg, params = FAMILIES[family](MAX_LEN)
    names = G.cache_names(cfg)
    cache = _filled_cache(cfg, 4)
    cur = jnp.asarray([7, 11, 13, 17, 19][:bucket], jnp.int32)
    pos = jnp.asarray([2 * EDGE - 3, 5, EDGE - 3, 3 * EDGE - 3,
                       MAX_LEN - 4][:bucket], jnp.int32)

    def launch():
        fn = serving._compiled_bucket_scan(cfg, bucket, SLOTS, MAX_LEN, 8)
        out = fn(params, *(jnp.copy(cache[n]) for n in names), cur, pos,
                 jnp.int32(0))
        return np.asarray(out[len(names)]).ravel()[:8 * bucket].reshape(
            8, bucket), out[:len(names)]

    chunk(MAX_LEN)
    whole, whole_cache = launch()
    chunk(EDGE)
    got, got_cache = launch()
    steps_in = np.asarray(pos)[None, :] + np.arange(8)[:, None] < MAX_LEN
    assert (got == whole)[steps_in].all()
    for mine, ref in zip(got_cache, whole_cache):
        np.testing.assert_allclose(mine, ref, rtol=0, atol=1e-5)


# ---- a scripted batcher run, token for token against the parent's -----------

def scripted_tokens_digest(family: str) -> str:
    """A batcher of 4 slots x 600 positions (bounds 256, 512, 600 at the
    module's chunk) through admissions, fused launches of 8 that cross both
    edges, a request that leaves early (its slot stays free for a while) and
    ``run_to_completion``: the digest of every request's tokens. Float32
    throughout. Run in a checkout of the parent it gives the parent's."""
    cfg, params = FAMILIES[family](600)
    rng = np.random.default_rng(5)
    b = ContinuousBatcher(params, cfg, max_slots=4, max_len=600)

    def prompt(n):
        return rng.integers(0, cfg.vocab_size, n).astype(np.int32)

    tokens = {}

    def note(results):
        for rid, toks, _ in results:
            tokens.setdefault(rid, []).extend(toks)

    first = {}
    for n, new in ((250, 40), (500, 60), (30, 12)):
        rid, tok, _ = b.submit_ex(prompt(n), new)
        first[rid] = tok
    for _ in range(2):  # the 30-token prompt's request leaves after these
        note(b.step_many(8))
    rid, tok, _ = b.submit_ex(prompt(254), 30)  # crosses 256 in a launch
    first[rid] = tok
    note(b.step_many(8))
    note(b.step_many(1))
    rid, tok, _ = b.submit_ex(prompt(7), 9)
    first[rid] = tok
    note(b.step_many(8))
    emitted = {rid: [first[rid]] + [int(t) for t in toks]
               for rid, toks in tokens.items()}
    # every request's whole list: of those still active from the batcher
    emitted.update({rid: [int(t) for t in toks]
                    for rid, toks in b.run_to_completion().items()})
    assert sorted(emitted) == sorted(first)
    text = repr(sorted(emitted.items()))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# taken by ``scripted_tokens_digest`` in a checkout of commit 724dd89 (the
# parent of PR 32, whose attention reads every allocated position)
PARENT_TOKENS = {
    "dense-gqa-rope": "f9cc0799d3eaea3f",
    "olmoe-like-mha-qknorm": "bf1106a8fb30c0ab",
    "hybrid-nope-scale": "03478865839bc0a5",
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_batcher_emits_the_parents_tokens(family):
    assert scripted_tokens_digest(family) == PARENT_TOKENS[family]


# ---- the counters ---------------------------------------------------------

def _bound(pos, max_len):
    """The rule again, written out by hand for the count below."""
    top = min(max(pos), max_len - 1) + 1
    return min(-(-top // G.KV_CHUNK) * G.KV_CHUNK, max_len)


def test_counters_of_a_scripted_run_equal_the_hand_count(chunk):
    """Three requests on four slots, chunk 16: every launch's rows x bound
    and the active rows' live positions, reckoned here from the positions
    alone. A request that has left holds no later bound up: its slot's
    position goes back to 0."""
    chunk(16)
    cfg, params = _dense(96)
    b = ContinuousBatcher(params, cfg, max_slots=4, max_len=96)
    assert b.take_kv_positions() == (0, 0)
    rng = np.random.default_rng(0)
    plan = {}  # slot -> [position, tokens still to come]
    for n, new in ((40, 5), (10, 30), (14, 12)):
        b.submit_ex(rng.integers(0, 256, n).astype(np.int32), new)
        plan[b.last_admission["slot"]] = [n, new - 1]
    assert b.take_kv_positions() == (0, 0)  # a prefill records none
    read = live = 0
    for k in (4, 4, 1, 8, 8, 8):
        if not any(left for _, left in plan.values()):
            break
        bucket = 1 if sum(1 for _, left in plan.values() if left) == 1 else 4
        staged = [plan[s][0] if s in plan and plan[s][1] else 0
                  for s in range(4)]
        if bucket == 1:
            staged = [p for s, p in enumerate(staged)
                      if s in plan and plan[s][1]]
        read += bucket * sum(_bound([p + j for p in staged], 96)
                             for j in range(k))
        for state in plan.values():
            take = min(k, state[1])
            live += sum(state[0] + j + 1 for j in range(take))
            state[0], state[1] = state[0] + take, state[1] - take
        b.step_many(k)
        assert b.take_kv_positions() == (read, live), (k, plan)
        read = live = 0
    assert not b._active and (b._pos == 0).all()


def test_a_row_that_left_does_not_set_the_bound(chunk):
    """A request 70 positions long leaves; the short rows still decoding
    read one chunk, not the five its stale position would have asked for."""
    chunk(16)
    cfg, params = _dense(96)
    b = ContinuousBatcher(params, cfg, max_slots=4, max_len=96)
    rng = np.random.default_rng(1)
    b.submit_ex(rng.integers(0, 256, 70).astype(np.int32), 2)
    b.submit_ex(rng.integers(0, 256, 3).astype(np.int32), 12)
    b.submit_ex(rng.integers(0, 256, 4).astype(np.int32), 12)
    b.step_many(1)  # the long one's last token
    assert b.take_kv_positions()[0] == 4 * 80
    assert b.num_active == 2
    b.step_many(4)
    assert b.take_kv_positions()[0] == 4 * 4 * 16


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_family_engine_records_the_counters(family):
    cfg, params = FAMILIES[family](160)
    eng = ContinuousEngine(params, cfg, max_slots=3, max_len=160,
                           decode_stride=4, kv_cache_bytes=0,
                           kv_label=f"kv-{family}")
    try:
        prompt = (np.arange(24) % cfg.vocab_size).astype(np.int32)
        queues = [eng.submit_stream(prompt, 21) for _ in range(2)]
        for q in queues:
            assert len(list(iter(q.get, None))) == 21
        time.sleep(0.1)
        rec = eng._recorder.window_summary(0.0, 1e12)
        ticks = eng._recorder.ticks()
    finally:
        eng.shutdown()
    launching = [t for t in ticks if t["phases"].get("decode_step")]
    assert launching and all(t["kv_positions_read"] >= t["kv_positions_live"]
                             > 0 for t in launching)
    for t in ticks:  # an admission-only or a parked tick records none
        if not t["phases"].get("decode_step"):
            assert "kv_positions_read" not in t
    # at max_len 160 one chunk covers every row: rows x 160 a step
    assert rec["kv_positions_read"] == sum(
        t["bucket"] * t["k"] * 160 for t in launching)
    assert rec["kv_positions_live"] == sum(
        t["kv_positions_live"] for t in launching)
    assert rec["kv_read_ratio"] == pytest.approx(
        rec["kv_positions_read"] / rec["kv_positions_live"], abs=1e-4)
    assert eng.stats()["recorder"]["kv_read_ratio"] == rec["kv_read_ratio"]


def test_rt_engine_stats_prints_the_ratio(rt_cluster):
    import ray_tpu
    from ray_tpu.scripts import cli

    rec = ER.EngineRecorder("kvread", max_slots=2, enabled=True)
    try:
        rec.record_tick(t_start=time.time(), wall_s=0.010,
                        phases={"decode_step": 0.008, "token_delivery": 0.002},
                        active=2, pending=0, bucket=2, k=4, tokens=8,
                        admitted=0, gap_s=0.003, kv_positions=(2048, 512))
        rec.record_tick(t_start=time.time(), wall_s=0.010,
                        phases={"prefill": 0.008}, active=0, pending=0,
                        bucket=0, k=0, tokens=1, admitted=1, gap_s=None)
        assert rec.summary()["kv_read_ratio"] == 4.0
        assert rec.drain_now()["kv"] == 1
        b = ray_tpu.global_worker()._require_backend()
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_engine(Namespace(address=b.gcs_address, name="kvread",
                                          limit=5, json=False,
                                          engine_cmd="stats"))
        assert rc == 0
        assert "read 2048 positions for 512 live (kv_read_ratio 4.00)" \
            in out.getvalue()
    finally:
        rec.close()
