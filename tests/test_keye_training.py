"""Keye-VL-2.0-30B-A3B's block trained (PR 63), at a tiny size with seeded
weights on the CPU: grouped-query attention over the positions a learned
indexer picks (``models/mixers.sparse_half``, ``ops/sparse_index.py``), the
flash kernels under that choice as a mask (``ops/pallas/flash.py``'s
``select=``), the indexer's own loss, rotation by sections
(``ops/rope.py``), a softmax router without bias, shared expert or leading
dense layer, each against the sparse_keye family's float32 reference
(``benchmark/families/sparse_keye.py``) or a form written out by hand here.

(a) the kernels under a choice; (b) the indexer: scores, threshold, choice,
loss; (c) the rotation by sections; (d) the whole model: logits and loss
with the program's own choice handed to the reference, the choice itself,
every leaf's gradient; (e) the shares add up; (f) the counts, the flops, the
refusals, the plan; (g) the steps that were: ``select=None`` traces as it did.
"""

import dataclasses
import functools
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, llama, mixers, moe, serving
from ray_tpu.ops import sparse_index
from ray_tpu.ops.pallas import flash
from ray_tpu.ops.rope import (apply_rope, apply_rope_by_position, rope_angles,
                              rope_angles_by_sections, section_pairs)
from ray_tpu.parallel import train_step as ts
from ray_tpu.util import flops, plans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "benchmark"))
from benchmark.lib import spec  # noqa: E402
# the program's choice a layer, the stream walked through the step's own
# functions: the chip check's, which reads it at the cell's size
from keye_chip_check import program_choices as _program_choices  # noqa: E402

# config.json's keys at a tiny size: two layers, four of 16 experts held, an
# indexer of 4 heads of 8 that keeps 16 of up to 64 past positions, sections
# 2 | 2 | 4 of a head's 8 frequency pairs (1 | 1 | 2 of the indexer's 4)
TINY = {
    "head_dim": 16, "hidden_size": 32, "moe_intermediate_size": 24,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 2,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 2, 4], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 16},
    "tie_word_embeddings": False, "vocab_size": 96}
CFG_FILE = {"config": TINY, "assumed": {
    "capacity_factor": 1.25, "balance_coefficient": 0.008,
    "index_loss_coef": 1.0}}
SEQ, DEPTH = 64, 2
TOKENS = jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0, 96)


@pytest.fixture(scope="module")
def family():
    return spec.load_family("sparse_keye")


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 16 rows in two spans, so that 64 positions walk what 16,384
    do: several blocks a span, a span that sees half the keys."""
    monkeypatch.setattr(sparse_index, "BLOCK_ROWS", 16)
    monkeypatch.setattr(sparse_index, "_SPANS", 2)


def _cfg(family, attn_impl="xla", depth=DEPTH, **changes):
    cfg = family.program_config(CFG_FILE, depth, max_seq_len=SEQ,
                                attn_impl=attn_impl, loss_chunk=16)
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32, **changes)


@pytest.fixture(scope="module")
def model(family):
    """(What a test below does not hold to the bit it computes as ONE
    compiled program, references and inputs too: op by op every small
    operation is a program for the CPU backend to build, and those were
    half of this file's time, PR 64.)"""
    cfg = _cfg(family)

    @jax.jit
    def make():
        params = moe.init_params(jax.random.key(0), cfg)
        # a LayerNorm's bias off zero, as a trained one is
        bias = params["layers"]["sparse"]["index_k_norm_b"]
        params["layers"]["sparse"]["index_k_norm_b"] = 0.1 * jax.random.normal(
            jax.random.key(5), bias.shape)
        return params

    return cfg, make()


def _gap(a, b):
    """max |a - b| on the host."""
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())


# ---- (a) the kernels under a choice ---------------------------------------------------

def _masked_attention(q, k, v, select):
    b, s, h, d = q.shape
    k, v = (jnp.repeat(a, h // k.shape[2], 2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    seen = (select[:, None] != 0) & jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p * seen.any(-1, keepdims=True), v)


@pytest.mark.parametrize("b,s,h,hkv,d,block", [
    (2, 96, 4, 2, 32, 32),     # grouped heads, two batch rows, nine tiles
    (1, 200, 2, 1, 64, None)])  # a planned tile, padded rows and keys
def test_the_kernels_attend_the_chosen_keys_alone(b, s, h, hkv, d, block):
    """Forward, dq and dkv read the choice's tile of their (q block, k
    block) step and AND it with the causal test; a row that chose nothing
    gives zeros."""
    @jax.jit
    def inputs():
        ks = jax.random.split(jax.random.key(s), 5)
        select = (jax.random.uniform(ks[3], (b, s, s)) < 0.3).astype(jnp.int8)
        return (jax.random.normal(ks[0], (b, s, h, d)),
                jax.random.normal(ks[1], (b, s, hkv, d)),
                jax.random.normal(ks[2], (b, s, hkv, d)),
                select.at[:, 5].set(0),            # a row without a key
                jax.random.normal(ks[4], (b, s, h, d)))

    q, k, v, select, w = inputs()
    kernel = lambda q, k, v: flash.flash_attention(  # noqa: E731
        q, k, v, select=select, topk=8, block_q=block, block_k=block)
    plain = lambda q, k, v: _masked_attention(q, k, v, select)  # noqa: E731
    want = jax.jit(plain)(q, k, v)
    got, lse = jax.jit(lambda q, k, v: flash.flash_attention_chosen(
        q, k, v, select, topk=8, block_q=block, block_k=block))(q, k, v)
    got, lse = np.asarray(got), np.asarray(lse)
    assert _gap(got, want) < 2e-6
    assert not got[:, 5].any() and lse[:, :, 5].max() < -1e8
    assert _gap(jax.jit(kernel)(q, k, v), want) < 2e-6
    g = jax.jit(jax.grad(lambda *a: (kernel(*a) * w).sum(), (0, 1, 2)))(
        q, k, v)
    r = jax.jit(jax.grad(lambda *a: (plain(*a) * w).sum(), (0, 1, 2)))(
        q, k, v)
    for got, want in zip(g, r):
        assert _gap(got, want) < 1e-5


def test_a_call_under_a_choice_says_so_in_its_name_and_plan():
    q = jnp.zeros((1, 256, 2, 128))
    select = jnp.ones((1, 256, 256), jnp.int8)
    noted = {}
    with plans.noting(noted):
        text = str(jax.make_jaxpr(jax.grad(lambda q: flash.flash_attention(
            q, q, q, select=select, topk=2048).sum()))(q))
    for kind in flash.KINDS:
        assert f"flash_{kind}_bh2_q256_k256_d128_c1_w0_t2048" in text
    assert [p["topk"] for p in noted["flash_plans"]] == [2048] * 3
    # every live step applies the choice: none is clear
    assert all(p["edge_steps"] == p["live_steps"] for p in noted["flash_plans"])
    assert "under a choice of 2048 keys" in plans._flash(noted["flash_plans"][0])
    # and a plan without one is what it was
    assert flash.plan(4096, 4096, 128, 2, True, "fwd") == flash.Plan(
        "fwd", 1024, 1024, 16, 10, 4, (1024, 1024), 25690112, 25690112)
    chosen = flash.plan(4096, 4096, 128, 2, True, "fwd", select=True)
    assert chosen.vmem_bytes - 25690112 == 6 * 1024 * 1024
    with pytest.raises(ValueError, match="needs topk"):
        flash.flash_attention(q, q, q, select=select)
    with pytest.raises(ValueError, match="int8"):
        flash.flash_attention(q, q, q, select=select.astype(jnp.int32),
                              topk=8)


@pytest.mark.parametrize("kind", flash.KINDS)
@pytest.mark.parametrize("seq,live", [(4096, 10), (16384, 136), (1000, 1)])
def test_a_plan_under_a_choice_has_no_clear_step(kind, seq, live):
    """The tile's side is the plan's without a choice (the choice's 6 bytes
    an element fit the budget at 128 wide), every live step applies the
    mask, and the working set grows by the tile's int8 twice and its
    widening."""
    plain = flash.plan(seq, seq, 128, 2, True, kind)
    chosen = flash.plan(seq, seq, 128, 2, True, kind, select=True)
    assert (chosen.block_q, chosen.block_k) == (plain.block_q, plain.block_k)
    assert chosen.live_steps == plain.live_steps == chosen.edge_steps == live
    assert chosen.vmem_bytes - plain.vmem_bytes \
        == 6 * chosen.block_q * chosen.block_k
    assert chosen.vmem_limit_bytes <= 64 << 20


# ---- (b) the indexer ------------------------------------------------------------------

@pytest.mark.parametrize("seq,topk,pairs", [
    (16384, 2048, 31_458_304), (2048, 2048, 2048 * 2049 // 2),
    (1000, 2048, 1000 * 1001 // 2), (64, 16, 136 + 48 * 16), (1, 1, 1)])
def test_the_chosen_pairs_are_counted_in_closed_form(seq, topk, pairs):
    assert sparse_index.chosen_pairs(seq, topk) == pairs \
        == sum(min(t + 1, topk) for t in range(seq))
    plan = sparse_index.plan(seq, 16, 64, topk)
    assert plan["pairs_chosen"] == pairs
    assert plan["pairs_live"] == seq * (seq + 1) // 2


@pytest.mark.parametrize("seq,blocks,spans", [
    (16384, 64, 8), (4096, 16, 8), (2048, 8, 8), (1024, 4, 1), (100, 1, 1)])
def test_the_walk_is_planned_in_whole_blocks_and_spans(seq, blocks, spans,
                                                       monkeypatch):
    """Blocks of 256 rows in 8 causal spans (the module's own sizes, which
    this file's other tests shrink) where the sequence is whole spans of
    whole blocks, else one span (of one block where it is not whole blocks
    either)."""
    monkeypatch.setattr(sparse_index, "BLOCK_ROWS", 256)
    monkeypatch.setattr(sparse_index, "_SPANS", 8)
    plan = sparse_index.plan(seq, 16, 64, 2048)
    assert (plan["blocks"], plan["spans"]) == (blocks, spans)
    assert plan["block_rows"] * plan["blocks"] == seq
    assert plan["threshold_passes"] * sparse_index._BITS == 32
    assert plan["choice_bytes"] == seq * seq
    # the loss's target: XLA's lines unless a tile is handed in, which is
    # ``kernel_tile``'s where the caller's kernels run (Keye's heads, bf16)
    assert (plan["target_impl"], plan["target_tile"]) == ("xla", None)
    tile = sparse_index.kernel_tile("flash", seq, 32, 4, 128, 2)
    assert tile == {16384: 512, 4096: 512, 2048: 256, 1024: 512, 100: None}[seq]
    assert sparse_index.kernel_tile("xla", seq, 32, 4, 128, 2) is None
    planned = sparse_index.plan(seq, 16, 64, 2048, target_tile=tile)
    assert planned == {**plan, **sparse_index.target_plan(tile)}
    assert planned["target_impl"] == ("pallas" if tile else "xla")
    covered = sparse_index._spans(seq)
    assert [a for a, _ in covered] == [i * seq // spans for i in range(spans)]
    assert sum(n for _, n in covered) == seq


@functools.partial(jax.jit, static_argnums=range(5))
def _indexer_inputs(seed=0, b=2, s=128, j=4, e=16):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (b, s, j, e)),
            jax.random.normal(ks[1], (b, s, e)),
            0.1 * jax.random.normal(ks[2], (b, s, j)))


def _whole_scores(q_idx, k_idx, w):
    return jnp.einsum("bjru,brj->bru", jax.nn.relu(
        jnp.einsum("brje,bue->bjru", q_idx, k_idx)), w)


@pytest.mark.parametrize("k", [1, 12, 33, 64])
def test_the_threshold_is_the_kth_largest_exactly(k):
    """By counts over the scores' order-preserving integers, against a
    sort: negative scores, ties, a row with fewer than k entries, a row of
    exactly 12."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 40, 64)).astype(np.float32)
    x[0, 0, :8] = 0.25                      # ties at the threshold
    x[1, 3] = -np.abs(x[1, 3])              # all negative
    x[2, 5, ::2] = 0.0                      # zeros among the signs
    seen = rng.uniform(size=x.shape) < 0.7
    seen[0, 1] = False
    seen[0, 1, :5] = True                   # fewer than k
    seen[0, 2] = False
    seen[0, 2, :12] = True                  # exactly k
    tau = np.asarray(sparse_index.threshold(jnp.asarray(x), jnp.asarray(seen),
                                            k))
    for i in np.ndindex(3, 40):
        vals = np.sort(x[i][seen[i]])[::-1]
        want = vals[k - 1] if len(vals) >= k else -np.inf
        assert tau[i] == want, (i, tau[i], want)


def test_the_choice_is_the_scores_at_or_over_the_threshold():
    q_idx, k_idx, w = _indexer_inputs()
    chosen, tau, counted = jax.jit(
        lambda *a: sparse_index.choose(*a, 24))(q_idx, k_idx, w)
    s = q_idx.shape[1]
    causal = np.tril(np.ones((s, s), bool))

    @jax.jit
    def by_sorting(q_idx, k_idx, w):
        scores = jnp.where(causal, _whole_scores(q_idx, k_idx, w), -jnp.inf)
        return scores, jax.lax.top_k(scores, 24)[0][..., -1]

    scores, kth = map(np.asarray, by_sorting(q_idx, k_idx, w))
    tau = np.asarray(tau)
    assert (tau[:, 23:] == kth[:, 23:]).all()
    assert np.isinf(tau[:, :23]).all()
    want = causal & (scores >= kth[..., None])
    assert chosen.dtype == jnp.int8
    assert ((np.asarray(chosen) != 0) == want).all()
    # every row keeps min(t + 1, k), and ties more
    kept = want.sum(-1)
    assert (kept >= np.minimum(np.arange(s) + 1, 24)).all()
    assert dict(zip(sparse_index.COUNTERS, map(int, counted))) == {
        "index_pairs_live": 2 * s * (s + 1) // 2,
        "index_pairs_chosen": int(want.sum()),
        "index_rows_over_k": int((kept > 24).sum())}
    assert sparse_index.plan(s, 4, 16, 24, 2)["pairs_chosen"] \
        == sparse_index.chosen_pairs(s, 24) \
        == sum(min(t + 1, 24) for t in range(s))


def test_the_indexers_loss_and_its_gradients_are_the_written_out_forms():
    """``KL(p || softmax_S(I))`` a row, the target from the main attention's
    q, k and the log-sum-exp its kernel returned; the rule's three gradients
    are ``jax.grad``'s through the form written out whole; q, k and lse take
    none."""
    q_idx, k_idx, w = _indexer_inputs(1)
    b, s = q_idx.shape[:2]

    @jax.jit
    def rest(q_idx, k_idx, w):
        ks = jax.random.split(jax.random.key(9), 3)
        q = jax.random.normal(ks[0], (b, s, 4, 32))
        k, v = (jax.random.normal(key, (b, s, 2, 32)) for key in ks[1:])
        chosen, _, _ = sparse_index.choose(q_idx, k_idx, w, 24)
        _, lse = sparse_index.dense_attention(q, k, v, chosen, 32 ** -0.5)
        _, lse_kernel = flash.flash_attention_chosen(
            q, k, v, chosen, topk=24, block_q=32, block_k=32)
        return q, k, chosen, lse, lse_kernel

    q, k, chosen, lse, lse_kernel = rest(q_idx, k_idx, w)
    assert _gap(lse, lse_kernel) < 1e-5
    sel = np.asarray(chosen) != 0

    def whole(q_idx, k_idx, w):
        scores = _whole_scores(q_idx, k_idx, w)
        kk = jnp.repeat(k, 2, 2)
        p = jax.nn.softmax(jnp.where(sel[:, None], jnp.einsum(
            "bqhd,bkhd->bhqk", q, kk) * 32 ** -0.5, -jnp.inf), -1).sum(1)
        p = p / p.sum(-1, keepdims=True)
        log_i = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), -1)
        return jnp.where(sel, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                   - jnp.where(sel, log_i, 0.0)),
                         0.0).sum(-1).mean()

    rule = lambda *a: sparse_index.index_loss(  # noqa: E731
        *a, q, k, lse, chosen, 32 ** -0.5)
    got, grads = jax.jit(jax.value_and_grad(rule, (0, 1, 2)))(q_idx, k_idx, w)
    want, refs = jax.jit(jax.value_and_grad(whole, (0, 1, 2)))(q_idx, k_idx, w)
    assert float(want) > 0.05 and abs(float(got) - float(want)) < 1e-6
    for g, r in zip(grads, refs):
        assert _gap(g, r) < 2e-6 * max(1.0, float(np.abs(np.asarray(r)).max()))
    others = jax.jit(jax.grad(lambda q, k, lse: sparse_index.index_loss(
        q_idx, k_idx, w, q, k, lse, chosen, 32 ** -0.5), (0, 1, 2)))(q, k, lse)
    assert not any(np.asarray(g).any() for g in others)


# ---- (c) the rotation by sections -----------------------------------------------------

def test_equal_streams_rotate_as_the_plain_tables_do_to_the_bit():
    x = jax.random.normal(jax.random.key(0), (2, 40, 3, 128)).astype(
        jnp.bfloat16)
    plain = apply_rope(x, *rope_angles(40, 128, 1e7, jnp.bfloat16))
    streams = jnp.broadcast_to(jnp.arange(40), (3, 2, 40))
    tables = rope_angles_by_sections(streams, 128, 1e7, (16, 24, 24),
                                     jnp.bfloat16)
    assert tables[0].shape == (2, 40, 64)
    assert bool((apply_rope_by_position(x, *tables) == plain).all())


def test_unequal_streams_turn_each_pair_by_its_sections_position(family):
    """Pair i of 64 by stream 0 for i < 16, stream 1 for 16 <= i < 40,
    stream 2 from 40: against the rotation written out a pair at a time, and
    against the family's reference's."""
    assert section_pairs((16, 24, 24), 64) == (16, 24, 24)
    assert section_pairs((16, 24, 24), 32) == (8, 12, 12)
    with pytest.raises(ValueError, match="do not scale"):
        section_pairs((2, 3, 3), 4)
    pos = jax.random.randint(jax.random.key(1), (3, 2, 10), 0, 500)
    x = jax.random.normal(jax.random.key(2), (2, 10, 2, 128))
    got = np.asarray(apply_rope_by_position(x, *rope_angles_by_sections(
        pos, 128, 1e7, (16, 24, 24))))
    xs, ps = np.asarray(x, np.float64), np.asarray(pos)
    for i in (0, 15, 16, 39, 40, 63):
        stream = 0 if i < 16 else 1 if i < 40 else 2
        ang = ps[stream] * (1e7 ** (-2.0 * i / 128))           # [b, s]
        a, b = xs[..., 2 * i], xs[..., 2 * i + 1]
        c, s = np.cos(ang)[..., None], np.sin(ang)[..., None]
        assert np.abs(got[..., 2 * i] - (a * c - b * s)).max() < 2e-4, i
        assert np.abs(got[..., 2 * i + 1] - (a * s + b * c)).max() < 2e-4, i
    want = family._rotate(x, pos.astype(jnp.float32), 1e7, (16, 24, 24))
    assert float(jnp.abs(got - want).max()) < 1e-5
    # streams that differ give another rotation than equal ones
    same = jnp.broadcast_to(pos[:1], pos.shape)
    assert float(jnp.abs(got - apply_rope_by_position(
        x, *rope_angles_by_sections(same, 128, 1e7, (16, 24, 24)))).max()) > 0.5


# ---- (d) the whole model --------------------------------------------------------------

POSITIONS = jnp.stack([jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ)),
                       jnp.broadcast_to(jnp.arange(SEQ) // 2, (2, SEQ)),
                       jnp.broadcast_to(jnp.arange(SEQ) % 7, (2, SEQ))])


def test_the_logits_are_the_references_under_the_programs_choice(
        family, model, attn_impl="flash", positions=None):
    """Through the kernels (interpret mode), equal streams; three unequal
    streams go through the whole model in the gradient test below."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, attn_impl=attn_impl)
    tokens = TOKENS[:, :-1]
    with jax.default_matmul_precision("highest"):
        @jax.jit
        def program(p, t):
            x, head, _, _ = moe.forward_hidden(p, t, cfg, None, positions)
            return (x @ head).astype(jnp.float32)

        @jax.jit
        def reference(p, t):
            chose, _ = _program_choices(cfg, p, t, positions)
            x, *_ = family.hidden(p, t, CFG_FILE, cfg.capacity_factor,
                                  positions=positions, choices=chose)
            return x @ p["lm_head"]

        got, want = program(params, tokens), np.asarray(
            reference(params, tokens))
    assert np.abs(want).max() > 1.0
    assert _gap(got, want) < 5e-5


def test_the_choice_is_the_references_but_inside_a_band_of_the_threshold(
        family, model):
    """float32 against float32: the two choices differ, if at all, only in
    pairs whose score lies within ``BAND`` of the row's threshold (the sums'
    order), and the thresholds agree to it."""
    BAND = 1e-5
    cfg, params = model
    tokens = TOKENS[:, :-1]
    with jax.default_matmul_precision("highest"):
        chose, tau = map(np.asarray, jax.jit(
            lambda p, t: _program_choices(cfg, p, t))(params, tokens))
        want, want_tau = map(np.asarray, jax.jit(lambda p, t: family.hidden(
            p, t, CFG_FILE, cfg.capacity_factor, keep=True)[3:])(
                params, tokens))
    assert chose.shape == want.shape == (DEPTH, 2, SEQ, SEQ)
    live = ~np.isinf(want_tau)
    assert (np.isinf(tau) == ~live).all()
    assert int(live.sum()) == DEPTH * 2 * (SEQ - 15)
    with np.errstate(invalid="ignore"):   # -inf less -inf where none is live
        assert np.abs(np.where(live, tau - want_tau, 0.0)).max() < BAND
    # (at most a few rows: a pair that differs has its score on the threshold)
    differ = ((chose != 0) != want).any(-1)
    assert int(differ.sum()) <= 4, np.argwhere(differ)
    kept = (chose != 0).sum(-1)
    assert (kept >= np.minimum(np.arange(SEQ) + 1, 16)).all()
    # from position 16 on a row leaves keys out: the choice is no causal mask
    assert int(kept[..., -1].max()) < 24 and int(kept[..., 15].min()) == 16


def test_the_loss_and_every_leafs_gradient_are_the_references(family, model,
                                                              capsys):
    """Under three unequal position streams. The three terms; every leaf's
    gradient with the program's own choice handed to the reference, the
    readings printed; the trunk's leaves take
    nothing from the indexer's loss and the indexer's nothing from the
    cross entropy (exact zeros)."""
    cfg, params = model
    batch = {"tokens": TOKENS, "position_ids": POSITIONS}
    index_leaves = set(family.INDEX_LEAVES)

    def terms(p):  # the step's loss as its two parts, which add up to it
        loss, stats = moe.loss_and_stats(p, batch, cfg)
        index = cfg.index_loss_coef * stats["index_loss"]
        return jnp.stack([index, loss - index]), (loss, stats)

    with jax.default_matmul_precision("highest"):
        def both(p):  # one forward, each part's gradient pulled back
            _, pull, aux = jax.vjp(terms, p, has_aux=True)
            return (pull(jnp.array([1.0, 0.0]))[0],
                    pull(jnp.array([0.0, 1.0]))[0], aux)

        from_index, from_rest, (loss, stats) = jax.jit(both)(params)
        grads = jax.tree.map(np.add, *jax.tree.map(
            np.asarray, (from_index, from_rest)))

        @jax.jit
        def reference(p):
            chose, _ = _program_choices(cfg, p, TOKENS[:, :-1], POSITIONS)
            how = dict(choices=chose, positions=POSITIONS)
            return (chose, family.loss(p, TOKENS, CFG_FILE, **how),
                    family.loss_and_grads(p, TOKENS, CFG_FILE, **how))

        chose, ref, (want_loss, want) = reference(params)
        # and the streams matter: equal ones give another loss
        plain, _ = jax.jit(lambda p: moe.loss_and_stats(
            p, {"tokens": TOKENS}, cfg))(params)
    assert abs(float(plain) - float(loss)) > 1e-3
    assert abs(float(loss) - float(ref["loss"])) < 2e-5
    assert abs(float(want_loss) - float(ref["loss"])) < 1e-6
    assert abs(float(stats["index_loss"]) - float(ref["index_loss"])) < 2e-6
    # all three terms are in it
    assert float(ref["index_loss"]) > 0.05 and float(ref["aux"]) > 0.5
    assert abs(float(ref["loss"]) - float(
        ref["ce"] + 0.008 * ref["aux"] + 1.0 * ref["index_loss"])) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path(grads)
    refs = jax.tree.leaves(want)
    assert len(flat) == len(refs) == 20
    readings = {}
    for (path, g), w, gi, gr in zip(flat, refs, jax.tree.leaves(from_index),
                                    jax.tree.leaves(from_rest)):
        name = jax.tree_util.keystr(path)
        w, gi, gr = np.asarray(w), np.asarray(gi), np.asarray(gr)
        scale = float(np.linalg.norm(w))
        assert scale > 1e-6, name
        readings[name] = float(np.linalg.norm(g - w)) / scale
        assert readings[name] < 2e-3, (name, readings[name])
        if name.split("'")[-2] in index_leaves:
            assert not gr.any(), name     # nothing from ce or aux
            assert gi.any(), name
        else:
            assert not gi.any(), name     # nothing from L_I
    with capsys.disabled():
        print("\nkeye tiny: a leaf's gradient against the reference's, "
              "|g - w| / |w|: " + ", ".join(
                  f"{k} {v:.1e}" for k, v in sorted(readings.items())))
    assert int(stats["index_pairs_live"]) == DEPTH * 2 * SEQ * (SEQ + 1) // 2
    assert int(stats["index_pairs_chosen"]) == int(
        (np.asarray(chose) != 0).sum())
    assert int(stats["moe_assignments"]) == DEPTH * 2 * SEQ * 2


def test_a_driver_launch_moves_every_leaf_and_notes_the_sparse_plan(model):
    """Three fused steps through ``StepDriver`` with the kernels under the
    choice: the loss falls, no leaf is left where it was (the indexer's
    five among them), the metrics carry the three counters and the
    indexer's loss, the recorder the plan and its sentence."""
    from ray_tpu.train.driver import StepDriver

    cfg, params = model
    cfg = dataclasses.replace(cfg, attn_impl="flash")
    opt = ts.default_optimizer(lr=3e-3, warmup_steps=1, total_steps=10)
    driver = StepDriver(cfg, opt, steps_per_launch=3)
    before = jax.tree.map(jnp.copy, params)
    seen = []
    new, _, _ = driver.run(jax.tree.map(jnp.copy, params), opt.init(params),
                           iter([{"tokens": TOKENS}] * 3),
                           on_launch=seen.append)
    metrics = seen[0]
    # (no selection bias: the loads are no metric, ``buffer_updates``)
    assert set(metrics) == {"loss", "grad_norm", "index_loss",
                            *moe.ROUTING_COUNTERS, *moe.INDEX_COUNTERS}
    assert float(metrics["loss"][2]) < float(metrics["loss"][0])
    assert metrics["index_pairs_live"].dtype == jnp.int32
    still = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree.leaves(before))
        if bool((a == b).all())]
    assert not still, still
    summary = driver.recorder.summary()
    plan = summary["sparse_plan"]
    assert plan == sparse_index.plan(SEQ, 4, 8, 16, 2)
    assert (plan["blocks"], plan["spans"], plan["threshold_passes"]) == (4, 2, 16)
    # heads of 16 are no whole lanes: the loss's target stays XLA's lines
    # under "flash" too, and the plan says so
    assert (plan["target_impl"], plan["target_tile"]) == ("xla", None)
    assert any("the loss's target in XLA (xla)" in s for s in
               plans.sentences(summary))
    assert plans.for_span(driver.recorder.plans)["sparse_plan"] == plan
    said = list(plans.sentences(summary))
    assert any("an indexer of 4 heads of 8 keeps 16 keys" in s for s in said)
    assert any("causal pairs chosen" in s for s in said), said
    assert {p["topk"] for p in summary["flash_plans"]} == {16}
    driver.recorder.close()


# ---- (e) the shares add up ------------------------------------------------------------

def test_eight_shares_of_2_of_16_give_the_uncut_layer(family):
    """An expert half's routed sum over chips 0-7, each holding experts
    2c, 2c + 1 of 16 through the program's layer (a chip's share holds the
    first two: the others' are this one's with the experts and the router's
    columns rolled), is the reference's half with all 16 held; there is no
    shared expert to count once, and what every chip computes alike (the
    router, the balancing term) is the same on each."""
    hf = dict(family._static(CFG_FILE, None))
    d, f, E = 32, 24, 16

    @jax.jit
    def inputs():
        ks = jax.random.split(jax.random.key(20), 4)
        return {"router": jax.random.normal(ks[0], (d, E)) / math.sqrt(d),
                "e_gate": jax.random.normal(ks[1], (E, d, f)) / math.sqrt(d),
                "e_up": jax.random.normal(ks[2], (E, d, f)) / math.sqrt(d),
                "e_down": jax.random.normal(ks[3], (E, f, d)) / math.sqrt(f)
                }, jax.random.normal(jax.random.key(21), (2 * SEQ, d))

    layer, h = inputs()
    cfg = dataclasses.replace(_cfg(family), n_experts_held=2,
                              capacity_factor=64.0)

    @jax.jit
    def share(layer, h, chip):  # one program for the eight chips
        roll = lambda a, axis: jnp.roll(a, -2 * chip, axis=axis)
        mine = {"router": roll(layer["router"], 1),
                **{k: roll(layer[k], 0)[:2]
                   for k in ("e_gate", "e_up", "e_down")}}
        out, aux, _ = moe._moe_ffn(cfg, h.reshape(2, SEQ, d), mine)
        # the reference's share is the same part
        return out.reshape(-1, d), aux, family._experts(
            h, mine, {**hf, "num_experts": 2}, 2)[0]

    with jax.default_matmul_precision("highest"):
        want, want_aux = jax.jit(lambda h, layer: family._experts(
            h, layer, {**hf, "num_experts": 16}, 2))(h, layer)
        parts, auxes, refs = zip(*(map(np.asarray, share(
            layer, h, jnp.int32(chip))) for chip in range(8)))
    assert np.abs(np.asarray(want)).max() > 0.5
    assert _gap(sum(parts), want) < 1e-5
    assert _gap(parts[0], want) > 0.1
    for part, ref in zip(parts, refs):
        assert _gap(part, ref) < 1e-5
    assert max(abs(float(a) - float(want_aux)) for a in auxes) < 1e-5


# ---- (f) counts, flops, refusals ------------------------------------------------------

def test_the_leaves_are_counted(family, model):
    cfg, params = model
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    d = 32
    own = (2 * d * 64 + 2 * d * 32 + 2 * 16          # q o, k v, two head norms
           + d * (4 * 8 + 8 + 4) + 2 * 8)            # the indexer
    assert mixers.sparse_params(cfg) == own
    assert cfg.mixer_params("sparse") == own + d
    assert set(params["layers"]["sparse"]) == {
        "wq", "wk", "wv", "wo", "q_norm", "k_norm", *family.INDEX_LEAVES}
    # no leading dense layer, no shared expert, no selection bias
    assert "dense_layers" not in params
    assert not {"s_gate", "router_bias", "wq"} & set(params["layers"])
    assert cfg.num_params() == 2 * 96 * d + d + DEPTH * (
        own + d + 4 * 3 * d * 24 + d * 16 + d)
    assert cfg.active_params() == cfg.num_params() - DEPTH * int(
        (4 - 2 * 4 / 16) * 3 * d * 24)


def test_the_flops_count_the_chosen_pairs_and_the_indexer(family):
    cfg = _cfg(family)
    chosen = sum(min(t + 1, 16) for t in range(SEQ)) / SEQ
    layer = 4 * 16 * chosen * (2 + 1 / 3) + 4 * 8 * (SEQ + 1) / 2
    assert math.isclose(flops._attention_madds(cfg, SEQ), DEPTH * layer)
    assert math.isclose(family.attention_flops_per_token(TINY, DEPTH, SEQ),
                        DEPTH * layer)
    assert family.chosen_pairs(SEQ, 16) == chosen * SEQ
    assert family.chosen_pairs(16384, 2048) == 31_458_304
    assert family.indexer_matmul_params(TINY) == 32 * (4 * 8 + 8 + 4)
    assert math.isclose(
        flops.train_flops_per_token(cfg, SEQ),
        6 * (cfg.active_params() + DEPTH * layer))


def test_the_walk_refuses_what_it_does_not_compute(family, model):
    cfg, params = model
    with pytest.raises(ValueError, match="index_heads"):
        dataclasses.replace(cfg, index_topk=0)
    with pytest.raises(ValueError, match="prediction module"):
        dataclasses.replace(cfg, n_mtp_modules=1)
    with pytest.raises(NotImplementedError, match="segment_ids"):
        moe.forward_hidden(params, TOKENS[:, :-1], cfg,
                           jnp.zeros((2, SEQ), jnp.int32))
    with pytest.raises(NotImplementedError, match="no ring under a choice"):
        moe.forward_hidden(params, TOKENS[:, :-1],
                           dataclasses.replace(cfg, attn_impl="ring"))


@pytest.mark.parametrize("constructor", [
    "init_cache", "generate", "ContinuousBatcher", "ContinuousEngine"])
def test_every_serving_constructor_refuses_the_kind_by_name(model, constructor):
    cfg, params = model
    with pytest.raises(NotImplementedError,
                       match=r"kind \['sparse'\].*indexer's keys"):
        if constructor == "init_cache":
            generate.init_cache(cfg, 2, 16)
        elif constructor == "generate":
            generate.generate(params, jnp.zeros((1, 4), jnp.int32), cfg,
                              max_new_tokens=2)
        elif constructor == "ContinuousBatcher":
            serving.ContinuousBatcher(params, cfg, max_slots=2, max_len=16)
        else:
            serving.ContinuousEngine(params, cfg, max_slots=2, max_len=16,
                                     warmup=False)
    llama.refuse_trained_only(llama.PRESETS["debug"])   # and no one else


# ---- (g) the steps that were ----------------------------------------------------------

def test_a_walk_without_the_kind_hands_back_what_it_did(family):
    """A patterned config with no ``sparse`` layer: a layer's tuple has four
    parts, the walk's fifth is None and the step's metrics carry neither
    the indexer's loss nor its counters (Trinity's, Kimi's and Xing4's
    lowered steps are held to their parents' by their own digest tests)."""
    cfg = moe.MoEConfig(
        vocab_size=96, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=24,
        max_seq_len=SEQ, param_dtype=jnp.float32, compute_dtype=jnp.float32,
        layer_kinds=("full", "full"), n_experts=4, top_k=2, balance="sequence")
    params = jax.eval_shape(lambda: moe.init_params(jax.random.key(0), cfg))
    out = jax.eval_shape(lambda p: moe._patterned_layer(
        cfg, "full", dense=False)(
        llama.embed(p, cfg, TOKENS[:, :-1]),
        moe._pick(p["layers"], cfg.layer_kinds, 0), None, None, None), params)
    assert len(out) == 4
    _, stats = jax.eval_shape(
        lambda p: moe.loss_and_stats(p, {"tokens": TOKENS}, cfg), params)
    assert set(stats) == {*moe.ROUTING_COUNTERS, "router_load"}
