"""OLMoE's block against its plain reference, at a small size on the CPU:
QK-norm, un-renormalised top-k gates and the served expert path whose work
follows the routed tokens (``moe.served_ffn_half``).

Logits are compared and not tokens. The tolerance is 1e-4 of the logits'
largest magnitude: both sides compute in float32 here (the test dtype), where
two layers' rounding leaves ~1e-6 of that scale (measured 7e-7); bf16
compute in place of it leaves 2e-2 and fails, as do a missing QK-norm
(0.9) and renormalised gates (0.4). A test below holds each of the three.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import spec
from ray_tpu.models import generate as G
from ray_tpu.models import llama, moe, serving
from ray_tpu.ops.pallas import grouped_matmul
from ray_tpu.util import engine_recorder

TOL = 1e-4  # of the reference logits' largest magnitude

OLMOE_HF = {"hidden_size": 64, "intermediate_size": 32,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "num_experts": 8, "num_experts_per_tok": 3,
            "norm_topk_prob": False, "num_hidden_layers": 2,
            "rms_norm_eps": 1e-5, "rope_theta": 10000,
            "tie_word_embeddings": False, "vocab_size": 256,
            "max_position_embeddings": 128}
CFG_FILE = {"name": "tiny-olmoe", "family": "olmoe", "source": "test",
            "config": OLMOE_HF, "reduced": {}, "assumed": {}}


@pytest.fixture(scope="module")
def family():
    return spec.load_family("olmoe")


@pytest.fixture(scope="module")
def cfg(family):
    """The family's own program config, in float32 throughout; the training
    path's capacity covers every selection so that ``moe.forward`` drops
    nothing either."""
    return dataclasses.replace(
        family.program_config(CFG_FILE, 2, max_seq_len=128),
        param_dtype=jnp.float32, compute_dtype=jnp.float32, capacity_factor=8.0)


@pytest.fixture(scope="module")
def params(cfg):
    """Seeded weights; the QK-norm's own are moved off 1 so that where and
    how they are applied shows."""
    p = moe.init_params(jax.random.key(0), cfg)
    for i, name in enumerate(("q_norm", "k_norm")):
        p["layers"][name] = 1 + 0.5 * jax.random.normal(
            jax.random.key(5 + i), p["layers"][name].shape)
    return p


def served_logits(params, tokens, cfg):
    """[B, S, V] through the cached forward, whose sparse half is the served
    expert path."""
    b, s = tokens.shape
    return G._forward_with_cache(params, tokens, cfg, G.init_cache(cfg, b, s),
                                 0, last_only=False)[0]


def rel_err(got, ref):
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 40), 0, 256)


@pytest.mark.parametrize("path", ["forward", "served"])
def test_logits_match_the_reference(family, cfg, params, tokens, path):
    ref = family.logits(params, tokens, CFG_FILE)
    got = (moe.forward(params, tokens, cfg) if path == "forward"
           else served_logits(params, tokens, cfg))
    assert rel_err(got, ref) < TOL


@pytest.mark.parametrize("what,change", [
    ("no QK-norm", {"qk_norm": False}),
    ("renormalised gates", {"norm_topk_prob": True}),
    ("bf16 compute", {"compute_dtype": jnp.bfloat16}),
])
def test_the_tolerance_refuses(family, cfg, params, tokens, what, change):
    """Leaving the QK-norm out, dividing the gates by their sum, or computing
    in the precision below the stated one is not inside the tolerance."""
    ref = family.logits(params, tokens, CFG_FILE)
    got = served_logits(params, tokens, dataclasses.replace(cfg, **change))
    assert rel_err(got, ref) > 20 * TOL, what


def test_prefill_then_decode_on_the_slot_cache(family, cfg, params):
    """Two requests of different lengths through ``ContinuousBatcher``: the
    prefill programs, then 36 decode steps in launches of 4 fused steps over
    rows at different positions. What it streamed is then replayed step by
    step on a slot cache (``decode_step_in_place``, the program the launches
    scan) for the logits, which must agree with the reference's full
    forward at every position, and under which every streamed token must be
    the best."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 256, n).astype(np.int32) for n in (9, 21)]
    new = 37
    batcher = serving.ContinuousBatcher(params, cfg, max_slots=4, max_len=96)
    ids = [batcher.submit(p, new) for p in prompts]
    slots = {r.req_id: s for s, r in batcher._active.items()}
    streamed = {i: list(batcher._active[slots[i]].tokens) for i in ids}
    for _ in range((new - 1) // 4):
        for rid, toks, _ in batcher.step_many(4):
            streamed[rid] += toks
    assert all(len(streamed[i]) == new for i in ids)

    seqs = [np.concatenate([p, streamed[i][:-1]]) for p, i in zip(prompts, ids)]
    ref = [family.logits(params, jnp.asarray(s)[None], CFG_FILE)[0] for s in seqs]
    scale = max(float(jnp.abs(r).max()) for r in ref)

    # the replay: prefill each row alone, then step both rows together
    ck, cv = (jnp.zeros((cfg.n_layers, 2, 96, cfg.n_kv_heads, cfg.head_dim),
                        jnp.float32) for _ in range(2))
    for row, p in enumerate(prompts):
        logits, one = G._forward_with_cache(params, jnp.asarray(p)[None], cfg,
                                            G.init_cache(cfg, 1, 96), 0)
        ck, cv = serving._write_row({"k": ck, "v": cv}, one, row).values()
        assert float(jnp.abs(logits[0, -1] - ref[row][len(p) - 1]).max()) \
            < TOL * scale
    step = jax.jit(lambda tok, ck, cv, pos: G.decode_step_in_place(
        params, tok, cfg, ck, cv, 0, pos))
    worst = 0.0
    for t in range(new - 1):
        tok = jnp.asarray([streamed[i][t] for i in ids], jnp.int32)
        pos = jnp.asarray([len(p) + t for p in prompts], jnp.int32)
        logits, ck, cv = step(tok, ck, cv, pos)
        for row, i in enumerate(ids):
            want = ref[row][len(prompts[row]) + t]
            worst = max(worst, float(jnp.abs(logits[row] - want).max()))
            # the batcher's fused launch chose this token: it is the best
            # under the reference but for a tie inside the tolerance
            assert float(want.max() - want[streamed[i][t + 1]]) < TOL * scale
    assert worst < TOL * scale


# ---- the sorted dispatch against the one-hot drop-free form ------------------

SHAPES = {
    "mixtral-like": dict(n_experts=4, top_k=2, norm_topk_prob=True),
    "olmoe-like": dict(n_experts=8, top_k=3, norm_topk_prob=False),
}


def _layer(shape, seed=0):
    c = dataclasses.replace(moe.PRESETS["moe-debug"], n_layers=1,
                            compute_dtype=jnp.float32, **SHAPES[shape])
    return c, jax.jit(lambda: jax.tree.map(
        lambda x: x[0], moe.init_params(jax.random.key(seed), c)["layers"]))()


def _served(c, x, layer):
    """``served_ffn_half`` on one layer's own weights: a stack of one. (One
    program, as the engine runs it and as ``_one_hot_form`` below is: op by
    op each was a hundred small programs for the CPU backend to build.)"""
    def run(x, layer):
        experts = {name: layer[name][None] for name in moe.EXPERT_WEIGHTS}
        return moe.served_ffn_half(c, x, layer, experts, 0)

    return jax.jit(run)(x, layer)


def _one_hot_form(c, x, layer):
    """The capacity dispatch with room for every selection: what the served
    path was before, and still what training runs at a smaller capacity."""
    ample = dataclasses.replace(c, capacity_factor=float(c.n_experts))

    def run(x, layer):
        h = llama.rmsnorm(x, layer["mlp_norm"], c.norm_eps)
        return x + moe._moe_ffn(ample, h, layer)[0]

    return jax.jit(run)(x, layer)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("b,s", [(1, 5), (2, 40), (16, 1)])
def test_sorted_dispatch_equals_the_one_hot_form(shape, b, s):
    """Also with more tokens than E * K (2 x 40), where the one-hot form's
    tensors are largest, and at a decode step's shape (16 x 1)."""
    c, layer = _layer(shape)
    x = jax.random.normal(jax.random.key(2), (b, s, c.d_model), jnp.float32)
    got, stats = _served(c, x, layer)
    np.testing.assert_allclose(got, _one_hot_form(c, x, layer), atol=2e-5)
    counts = dict(zip(moe.SERVED_STATS, np.asarray(stats)))
    g, e, k = b * s, c.n_experts, c.top_k
    assert counts["moe_assignments"] == g * k
    # every touched expert's group is padded to whole tiles, no further
    tile = grouped_matmul.tile_rows(g * k, e)
    assert g * k <= counts["moe_rows_computed"] <= g * k + e * tile
    assert counts["moe_rows_computed"] % tile == 0
    assert counts["moe_rows_computed"] \
        <= g * k + counts["moe_experts_touched"] * (tile - 1)
    assert counts["moe_expert_slots"] == e
    assert 1 <= counts["moe_experts_touched"] <= e
    assert g * k / e <= counts["moe_max_expert_rows"] <= g


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_one_expert_takes_every_token_and_another_none(shape):
    """The routing that no capacity short of G holds: expert 0 is every
    token's first choice and the last expert is never chosen."""
    c, layer = _layer(shape)
    # tokens with a common offset, and a router whose logit along it is
    # large for expert 0 and as small for the last expert, for every token
    x = 2.0 + jax.random.normal(jax.random.key(4), (2, 24, c.d_model), jnp.float32)
    h = llama.rmsnorm(x, layer["mlp_norm"], c.norm_eps).reshape(-1, c.d_model)
    along = jnp.ones(c.d_model) / c.d_model ** 0.5
    assert float((h @ along).min()) > 1
    router = layer["router"].at[:, 0].set(50 * along).at[:, -1].set(-50 * along)
    layer = dict(layer, router=router)
    got, stats = _served(c, x, layer)
    np.testing.assert_allclose(got, _one_hot_form(c, x, layer), atol=2e-5)
    counts = dict(zip(moe.SERVED_STATS, np.asarray(stats)))
    assert counts["moe_max_expert_rows"] == 2 * 24
    assert counts["moe_experts_touched"] < c.n_experts


# ---- the counters, from the launch to the recorder's window --------------------

def test_counters_add_up_from_launch_to_window(cfg, params):
    assert engine_recorder.MOE_COUNTERS == moe.SERVED_STATS
    slots, k = 4, 4
    per_row = cfg.top_k * cfg.n_layers
    batcher = serving.ContinuousBatcher(params, cfg, max_slots=slots, max_len=64)
    for n in (7, 12):
        batcher.submit(np.arange(1, n + 1, dtype=np.int32), 20)
    taken = batcher.take_moe_stats()
    assert set(taken) == {"prefill"}
    prefill = dict(zip(moe.SERVED_STATS, taken["prefill"]))
    assert prefill["moe_assignments"] == (7 + 12) * per_row
    assert prefill["moe_expert_slots"] == 2 * cfg.n_experts * cfg.n_layers

    batcher.step_many(k)
    batcher.step_many(k)
    taken = batcher.take_moe_stats()
    assert set(taken) == {"decode"} and batcher.take_moe_stats() == {}
    decode = dict(zip(moe.SERVED_STATS, taken["decode"]))
    # the full bucket's rows are all the slots, free ones included
    assert decode["moe_assignments"] == slots * per_row * k * 2
    tile = grouped_matmul.tile_rows(slots * cfg.top_k, cfg.n_experts)
    assert decode["moe_assignments"] <= decode["moe_rows_computed"] \
        <= decode["moe_assignments"] + decode["moe_expert_slots"] * tile
    assert decode["moe_expert_slots"] == cfg.n_experts * cfg.n_layers * k * 2
    assert 0 < decode["moe_experts_touched"] <= decode["moe_expert_slots"]
    assert slots * cfg.top_k / cfg.n_experts \
        <= decode["moe_max_expert_rows"] <= slots

    rec = engine_recorder.EngineRecorder("moe", max_slots=slots, enabled=True)
    tick = dict(wall_s=0.01, phases={"decode_step": 0.01}, active=2, pending=0,
                bucket=slots, k=k, tokens=8, admitted=0, gap_s=None)
    rec.record_tick(t_start=10.0, moe={"prefill": list(prefill.values())}, **tick)
    rec.record_tick(t_start=11.0, moe=taken, **tick)
    rec.record_tick(t_start=12.0, moe=taken, **tick)
    rec.record_tick(t_start=13.0, moe={}, **tick)
    out = rec.window_summary(0.0, 100.0)
    rec.close()
    assert out["moe_assignments"] == (prefill["moe_assignments"]
                                      + 2 * decode["moe_assignments"])
    assert out["moe_decode"]["moe_assignments"] == 2 * decode["moe_assignments"]
    assert out["moe_decode"]["moe_max_expert_rows"] == decode["moe_max_expert_rows"]
    assert out["moe_max_expert_rows"] == max(prefill["moe_max_expert_rows"],
                                             decode["moe_max_expert_rows"])


def test_a_dense_model_reports_no_counters():
    cfg = llama.PRESETS["debug"]
    batcher = serving.ContinuousBatcher(
        llama.init_params(jax.random.key(0), cfg), cfg, max_slots=2, max_len=32)
    batcher.submit(np.arange(1, 6, dtype=np.int32), 6)
    batcher.step_many(2)
    assert batcher.take_moe_stats() == {}
    rec = engine_recorder.EngineRecorder("dense", max_slots=2, enabled=True)
    rec.record_tick(t_start=1.0, wall_s=0.01, phases={"decode_step": 0.01},
                    active=1, pending=0, bucket=1, k=2, tokens=2, admitted=0,
                    gap_s=None, moe=batcher.take_moe_stats())
    out = rec.window_summary(0.0, 10.0)
    rec.close()
    assert not [key for key in out if key.startswith("moe")]


# ---- what the two new flags leave alone -----------------------------------------

def _digest(fn, *args):
    """A jaxpr's text without the addresses of the functions it names, the
    training blocks' remat policy (``llama.remat_block``, PR 45) under the
    name its predecessor had: to these programs, which hold no flash kernel,
    it keeps what that one kept."""
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(fn)(*args)))
    text = text.replace(
        "<function save_from_both_policies.<locals>.policy>",
        "<function dots_with_no_batch_dims_saveable>")
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _shapes(init, cfg):
    return jax.eval_shape(lambda: init(jax.random.key(0), cfg))


DENSE, SPARSE = llama.PRESETS["debug"], moe.PRESETS["moe-debug"]
TOKENS = jax.ShapeDtypeStruct((2, 17), jnp.int32)
ROWS = jax.ShapeDtypeStruct((4,), jnp.int32)
CACHE = jax.ShapeDtypeStruct(
    (DENSE.n_layers, 4, 32, DENSE.n_kv_heads, DENSE.head_dim), DENSE.compute_dtype)

# the digests of the programs as commit edd70c5 (PR 25) traced them, taken
# by this function in a checkout of that commit: with ``qk_norm`` off and
# ``norm_topk_prob`` on, the defaults, nothing these programs compute or
# the order they compute it in has changed. A later PR that changes one of
# them on purpose takes its digest anew (PR 32: the in-place decode step
# reckons ``generate.kv_read_bound`` from ``pos`` before its layer loop; at
# this ``max_len`` the bound takes one value and the step lowers as before,
# ``tests/test_hybrid_serving.py``; PR 41: the two Mixtral programs, whose
# ``_moe_ffn`` moves its routed rows by index where it multiplied by one-hot
# tensors, held to the one-hot form by value in ``tests/test_model_moe.py``).
PROGRAMS = {
    "dense_forward": ("1279e2a80f1d1bb7", lambda: (
        lambda p, t: llama.forward(p, t, DENSE),
        _shapes(llama.init_params, DENSE), TOKENS)),
    "dense_lm_loss": ("58b39139aec97150", lambda: (
        lambda p, t: llama.lm_loss(p, {"tokens": t}, DENSE),
        _shapes(llama.init_params, DENSE), TOKENS)),
    "mixtral_forward": ("b325c0cc911d56e5", lambda: (
        lambda p, t: moe.forward(p, t, SPARSE),
        _shapes(moe.init_params, SPARSE), TOKENS)),
    "mixtral_lm_loss": ("a6d6ed438232bb64", lambda: (
        lambda p, t: moe.lm_loss(p, {"tokens": t}, SPARSE),
        _shapes(moe.init_params, SPARSE), TOKENS)),
    "dense_decode_in_place": ("083088822a7edc17", lambda: (
        lambda p, t, ck, cv, pos: G.decode_step_in_place(
            p, t, DENSE, ck, cv, 0, pos),
        _shapes(llama.init_params, DENSE), ROWS, CACHE, CACHE, ROWS)),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_the_defaults_trace_to_the_jaxprs_they_had(program):
    want, make = PROGRAMS[program]
    fn, *args = make()
    assert _digest(fn, *args) == want


def test_the_flags_make_the_weights_they_need():
    on = dataclasses.replace(SPARSE, qk_norm=True)
    layers = _shapes(moe.init_params, on)["layers"]
    assert layers["q_norm"].shape == (on.n_layers, on.n_heads * on.head_dim)
    assert layers["k_norm"].shape == (on.n_layers, on.n_kv_heads * on.head_dim)
    assert "q_norm" not in _shapes(moe.init_params, SPARSE)["layers"]
    assert on.num_params() - SPARSE.num_params() == on.n_layers * (
        on.n_heads + on.n_kv_heads) * on.head_dim
    # the sharding's rule for a layer's norms covers them
    rules = moe.sharding_rules()
    assert rules.spec_for("layers/q_norm") == rules.spec_for("layers/attn_norm")
