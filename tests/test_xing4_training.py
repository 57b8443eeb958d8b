"""Xing4.0-29B-A4B's block trained (PR 56), at a tiny size with seeded
weights on the CPU: a four-row residual stream under Sinkhorn-normalised
hyper-connections (``ray_tpu/ops/hyper.py``), latent attention with a
rotary part under YaRN and a low-rank query (``models/mixers.mla_half``), a
share of the experts held, and a multi-token-prediction module in the loss
(``models/moe._mtp``), each against the xingchen_xing4 family's float32
reference (``benchmark/families/xingchen_xing4.py``) or a form written out
by hand here.

(a) the hyper-connection's parts; (b) the rotary part and the low-rank
query; (c) the whole model: logits of both heads, the loss, every leaf's
gradient; (d) the shares add up; (e) the counts, the flops, the refusals,
the recorder's plan.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, llama, mixers, moe, serving
from ray_tpu.ops import hyper
from ray_tpu.ops.rope import Yarn, apply_rope, rope_angles
from ray_tpu.parallel import train_step as ts
from ray_tpu.util import flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import spec  # noqa: E402

# config.json's keys at a tiny size: six published layers, two of them
# dense, of which the first dense one and two expert layers run; four of 16
# experts held; an original context of 16 positions grown four times; six
# Sinkhorn iterations a half layer (they are unrolled: a whole model's
# compile grows with them; the configuration's twenty are held on the
# operation itself, section (a))
TINY = {
    "first_k_dense_replace": 2, "hidden_size": 32, "intermediate_size": 64,
    "kv_lora_rank": 16, "q_lora_rank": 24, "moe_intermediate_size": 24,
    "n_routed_experts": 4, "n_routed_experts_published": 16,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 6, "layers_run": [0, 2, 3],
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 6,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "vocab_size": 96}
CFG_FILE = {"config": TINY, "assumed": {
    "capacity_factor": 1.25, "balance_coefficient": 0.01, "mtp_weight": 0.3}}
SEQ, DEPTH = 40, 3
TOKENS = jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0, 96)


@pytest.fixture(scope="module")
def family():
    return spec.load_family("xingchen_xing4")


def _cfg(family, attn_impl="xla", depth=DEPTH, **changes):
    cfg = family.program_config(CFG_FILE, depth, max_seq_len=SEQ,
                                attn_impl=attn_impl, loss_chunk=8)
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32, **changes)


@pytest.fixture(scope="module")
def model(family):
    cfg = _cfg(family)
    return cfg, jax.jit(lambda: moe.init_params(jax.random.key(0), cfg))()


# ---- (a) the hyper-connection ------------------------------------------------------

def _a_half(seed, n, d, layers=1):
    half = hyper.init(jax.random.key(seed), n, d, layers, jnp.float32)
    return jax.tree.map(lambda a: a[0], half)


def _sinkhorn_loop(m, iters, eps):
    """[..., n, n], the plain loop: a column's sum, then a row's."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def test_sinkhorns_result_is_doubly_stochastic():
    """(Twenty iterations bring logits of this spread there; of three times
    the spread, to 4e-2: the count is the configuration's, not a tolerance's.)"""
    m = jnp.exp(0.7 * jax.random.normal(jax.random.key(2), (4, 4, 7, 50)))
    out = hyper.sinkhorn(m, 20, 1e-6)
    assert float(jnp.abs(out.sum(0) - 1).max()) < 1e-5      # every column
    assert float(jnp.abs(out.sum(1) - 1).max()) < 1e-5      # every row
    assert float(out.min()) >= 0
    # it is the plain loop's, tokens last here and first there
    want = _sinkhorn_loop(jnp.moveaxis(m, (0, 1), (-2, -1)), 20, 1e-6)
    assert float(jnp.abs(jnp.moveaxis(out, (0, 1), (-2, -1)) - want).max()) < 1e-6


def test_sinkhorns_gradient_is_the_plain_loops():
    logits = jax.random.normal(jax.random.key(3), (4, 4, 33))
    w = jax.random.normal(jax.random.key(4), (4, 4, 33))
    # (each side one program: op by op the loop's backward alone is two
    # hundred small ones for the CPU backend to build, PR 64)
    got = jax.jit(jax.grad(
        lambda a: (hyper.sinkhorn(jnp.exp(a), 20, 1e-6) * w).sum()))(logits)
    to_last = lambda a: jnp.moveaxis(a, (0, 1), (-2, -1))
    want = np.asarray(jax.jit(jax.grad(
        lambda a: (_sinkhorn_loop(jnp.exp(a), 20, 1e-6)
                   * to_last(w)).sum()))(to_last(logits)))
    assert np.abs(want).max() > 0.05
    assert np.abs(np.asarray(to_last(got)) - want).max() < 1e-6


def test_the_mixes_are_the_equations(family):
    """``mix_in`` and ``mix_out`` on rows-first slabs against the reference's
    einsums on rows next to ``d``, and the clamp at work."""
    n, d = 4, 32
    half = _a_half(5, n, d)
    half["b"] = half["b"].at[2 * n].set(100.0)    # exp(100) is no float32
    x = jax.random.normal(jax.random.key(6), (n, 2, 9, d))
    y = jax.random.normal(jax.random.key(7), (2, 9, d))
    hf = {"rms_norm_eps": 1e-6, "hc_iters": 20, "hc_eps": 1e-6,
          "hc_clamp": (-30, 30)}
    with jax.default_matmul_precision("highest"):
        h, mix = hyper.mix_in(x, half, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
                              norm_eps=1e-6)
        out = hyper.mix_out(x, y, mix)
        X = jnp.moveaxis(x, 0, 2)
        want_h, post, res = family._read(X, half, hf)
        want = family._write(X, y, post, res)
    assert float(jnp.abs(h - want_h).max()) < 1e-5
    assert float(jnp.abs(jnp.moveaxis(out, 0, 2) - want).max()) < 1e-5
    assert float(jnp.abs(mix.res - res).max()) < 1e-6
    # the matrices moved off their fresh values, and differ token to token
    assert float(jnp.abs(mix.res - jnp.eye(n)).max()) > 0.2
    assert float(mix.res.std(axis=(0, 1)).max()) > 5e-3
    assert bool(jnp.isfinite(out).all())
    unclamped = hyper.mix_in(x, half, iters=20, eps=1e-6, clamp=(-200.0, 200.0),
                             norm_eps=1e-6)[1]
    assert not bool(jnp.isfinite(unclamped.res).all())


def test_a_fresh_half_is_the_plain_sum_on_the_summed_stream():
    """At ``neutral_bias`` and ``alpha`` 0 a half mixes nothing: ``h`` is the
    rows' mean, and the rows' sum after it is their sum before plus the
    branch."""
    n, d = 4, 16
    half = {"g": jnp.ones((n * d,)), "phi": _a_half(8, n, d)["phi"],
            "b": hyper.neutral_bias(n), "alpha": jnp.zeros((3,))}
    x = jax.random.normal(jax.random.key(9), (n, 1, 5, d))
    y = jax.random.normal(jax.random.key(10), (1, 5, d))
    h, mix = hyper.mix_in(x, half, iters=20, eps=1e-6, clamp=(-30.0, 30.0),
                          norm_eps=1e-6)
    assert float(jnp.abs(h - x.mean(0)).max()) < 1e-6
    out = hyper.mix_out(x, y, mix)
    assert float(jnp.abs(out.sum(0) - (x.sum(0) + y)).max()) < 1e-5
    assert float(jnp.abs(mix.res - jnp.eye(n)).max()) < 3e-6


def test_one_row_with_neutral_b_is_the_old_form_to_the_bit(family):
    """``hc_mult`` 1 at ``neutral_bias(1)``, ``alpha`` 0 and no ``eps`` in
    Sinkhorn's sums (with it ``1 / (1 + 1e-6)`` is not 1): all three
    matrices are exactly 1, and a layer's output is the bits of the same
    layer under the plain sum."""
    plain = _cfg(family, hc_mult=0, n_mtp_modules=0)
    wide = dataclasses.replace(plain, hc_mult=1, hc_eps=0.0)
    params = moe.init_params(jax.random.key(0), wide)
    for seg in ("dense_layers", "layers"):
        for half in ("attn", "mlp"):
            shape = params[seg][f"hc_{half}_b"].shape
            params[seg][f"hc_{half}_b"] = jnp.broadcast_to(
                hyper.neutral_bias(1), shape)
            params[seg][f"hc_{half}_alpha"] = jnp.zeros_like(
                params[seg][f"hc_{half}_alpha"])
    old = {k: ({n: a for n, a in v.items() if not n.startswith("hc_")}
               if isinstance(v, dict) else v) for k, v in params.items()}
    got = moe.forward(params, TOKENS[:, :-1], wide)
    want = moe.forward(old, TOKENS[:, :-1], plain)
    assert float(jnp.abs(want).max()) > 0.5
    assert bool((got == want).all())


# ---- (b) the rotary part, YaRN's table, the low-rank query -------------------------

def test_yarns_table_is_the_formula_at_three_positions(family):
    yarn, dim, theta = Yarn(64.0, 4096, 32.0, 1.0, 1.0, 1.0), 64, 10000.0
    sin, cos = rope_angles(8192, dim, theta, jnp.float32, yarn=yarn)
    # a pair's frequency: its own up to pair 10, a 64th of it from pair 23,
    # the blend between (i(32) = 10.47, i(1) = 22.51)
    def freq(i):
        own = theta ** (-2.0 * i / dim)
        ramp = min(max((i - 10) / (23 - 10), 0.0), 1.0)
        return own / 64.0 * ramp + own * (1 - ramp)

    for pos in (1, 1000, 8191):
        for i in (0, 10, 11, 16, 22, 23, 31):
            assert math.isclose(float(sin[pos, i]), math.sin(pos * freq(i)),
                                abs_tol=2e-3), (pos, i)
            assert math.isclose(float(cos[pos, i]), math.cos(pos * freq(i)),
                                abs_tol=2e-3), (pos, i)
    assert freq(10) == theta ** (-20 / 64) and math.isclose(
        freq(23), theta ** (-46 / 64) / 64)
    # the tables' factor m(mscale) / m(mscale_all_dim) and the softmax's m^2
    assert yarn.table_scale() == 1.0
    assert math.isclose(yarn.softmax_scale(), (0.1 * math.log(64) + 1) ** 2)
    assert math.isclose(Yarn(64.0, 4096, 32.0, 1.0, 1.0, 0.0).table_scale(),
                        0.1 * math.log(64) + 1)
    # and the family's own list of frequencies, written from the formula
    want = family.yarn_inv_freq(dim, theta, {
        "factor": 64, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1})
    np.testing.assert_allclose(yarn.inv_freq(dim, theta), want, rtol=1e-6)


def _mla_layer(cfg, seed=11):
    layer = jax.tree.map(lambda a: a[0], mixers.init_mla(
        jax.random.key(seed), cfg, 1))
    layer["attn_norm"] = 1 + 0.1 * jax.random.normal(
        jax.random.key(seed + 1), (cfg.d_model,))
    return layer


def test_the_rotary_part_is_rope_applied_by_hand(family):
    """``mla_half`` against the same layer written out here: both products
    of the low-rank query, ``ops/rope.apply_rope`` on ``k_r`` and on each
    head's last ``rope`` query columns before the concatenation, the
    softmax at ``m^2 / sqrt(nope + rope)``."""
    cfg = _cfg(family)
    layer = _mla_layer(cfg)
    x = jax.random.normal(jax.random.key(13), (2, SEQ, cfg.d_model))
    tables = mixers.mla_rope_tables(cfg, SEQ)
    half = lambda cfg, tables: jax.jit(lambda x, layer: mixers.mla_half(  # noqa: E731
        cfg, x, layer, None, tables))(x, layer)
    got = half(cfg, tables)
    want = np.asarray(jax.jit(lambda x, layer: _mla_by_hand(cfg, x, layer))(
        x, layer))
    assert np.abs(want).max() > 0.1
    assert np.abs(np.asarray(got) - want).max() < 2e-5
    # rotation, the softmax's factor and the tables' frequencies all tell
    for change in ({"mla_rope": False, "mla_yarn": None}, {"mla_yarn": None}):
        other = dataclasses.replace(cfg, **change)
        out = half(other, mixers.mla_rope_tables(other, SEQ))
        assert np.abs(np.asarray(out) - want).max() > 1e-3, change
    # and the kernels at two widths take the same scale
    flash = half(dataclasses.replace(cfg, attn_impl="flash"), tables)
    assert np.abs(np.asarray(flash) - want).max() < 2e-5


def _mla_by_hand(cfg, x, layer):
    """The layer of ``test_the_rotary_part_is_rope_applied_by_hand``,
    written out."""
    H, r, nope, rope, dv = 4, 16, 16, 8, 16
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * layer["attn_norm"]
    cq = h @ layer["wq_a"]
    cq = cq * jax.lax.rsqrt(jnp.mean(cq * cq, -1, keepdims=True) + 1e-6) \
        * layer["q_norm"]
    q = (cq @ layer["wq_b"]).reshape(2, SEQ, H, nope + rope)
    down = h @ layer["wkv_a"]
    c = down[..., :r]
    c = c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + 1e-6) \
        * layer["kv_norm"]
    up = (c @ layer["wkv_b"]).reshape(2, SEQ, H, nope + dv)
    sin, cos = rope_angles(SEQ, rope, cfg.rope_theta, jnp.float32,
                           yarn=cfg.mla_yarn)
    q = jnp.concatenate([q[..., :nope], apply_rope(q[..., nope:], sin, cos)], -1)
    k_r = apply_rope(down[:, :, None, r:], sin, cos)
    k = jnp.concatenate([up[..., :nope],
                         jnp.broadcast_to(k_r, (2, SEQ, H, rope))], -1)
    scale = (nope + rope) ** -0.5 * (0.1 * math.log(4) + 1) ** 2
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    scores = jnp.where(jnp.tril(jnp.ones((SEQ, SEQ), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     up[..., nope:])
    return out.reshape(2, SEQ, H * dv) @ layer["wo"]


def test_the_low_rank_query_is_its_two_products(family):
    """With ``wq = wq_a @ wq_b`` and the norm between them at gain 1 over an
    input whose ``c_q`` already has unit mean square, a full-rank layer
    computes what the low-rank one does; and the leaves are counted."""
    cfg = _cfg(family)
    full = dataclasses.replace(cfg, q_lora_rank=0)
    assert mixers.mla_params(cfg) - mixers.mla_params(full) == (
        32 * 24 + 24 + 24 * 4 * 24 - 32 * 4 * 24)
    low = mixers.init_mla(jax.random.key(14), cfg, 1)
    assert {k: v.shape[1:] for k, v in low.items() if "q" in k} == {
        "wq_a": (32, 24), "q_norm": (24,), "wq_b": (24, 96)}
    assert "wq" in mixers.init_mla(jax.random.key(14), full, 1)


# ---- (c) the whole model against the family's reference ------------------------------

def test_both_heads_logits_are_the_references(family, model):
    cfg, params = model
    drop_free = dataclasses.replace(cfg, capacity_factor=64.0)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: moe.forward(p, TOKENS[:, :-1], drop_free))(
            params)
        want = np.asarray(jax.jit(lambda p: family.logits(
            p, TOKENS[:, :-1], CFG_FILE))(params))
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < 2e-4

    # the module's: position t reads the trunk at t and token t + 1
    def module(params):
        inputs, targets = TOKENS[:, :-1], TOKENS[:, 1:]
        x, _, _, _, tables = moe._trunk(params, inputs, drop_free, None)
        seen = {}

        def spy(x, head, *a):
            seen["x"] = x
            return jnp.float32(0)

        whole, llama.chunked_ce = llama.chunked_ce, spy
        try:
            moe._mtp(params, drop_free, x, targets, None, None, None, tables)
        finally:
            llama.chunked_ce = whole
        return seen["x"] @ params["lm_head"]

    with jax.default_matmul_precision("highest"):
        got = jax.jit(module)(params)
        want = np.asarray(jax.jit(lambda p: family.module_logits(
            p, TOKENS, CFG_FILE))(params))
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < 2e-4


def test_the_loss_and_every_leafs_gradient_are_the_references(family, model):
    cfg, params = model
    batch = {"tokens": TOKENS}
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p: moe.loss_and_stats(p, batch, cfg), has_aux=True))(params)
        ref = jax.jit(lambda p: family.loss(p, TOKENS, CFG_FILE))(params)
        want_loss, want = jax.jit(lambda p: family.loss_and_grads(
            p, TOKENS, CFG_FILE))(params)
    assert abs(float(loss) - float(ref["loss"])) < 2e-5
    assert abs(float(want_loss) - float(ref["loss"])) < 1e-6
    # both cross entropies and the balancing term are in it
    assert float(ref["ce_mtp"]) > 3.0 and float(ref["aux"]) > 0.5
    assert abs(float(ref["loss"]) - float(
        ref["ce"] + 0.3 * ref["ce_mtp"] + 0.01 * ref["aux"])) < 1e-6
    flat = jax.tree_util.tree_leaves_with_path(grads)
    refs = jax.tree.leaves(want)
    assert len(flat) == len(refs)
    buffers = 0
    for (path, g), w in zip(flat, refs):
        name = jax.tree_util.keystr(path)
        g, w = np.asarray(g), np.asarray(w)
        if "router_bias" in name:   # buffers: no gradient on either side
            assert not g.any() and not w.any(), name
            buffers += 1
            continue
        scale = float(np.linalg.norm(w))
        assert scale > 1e-5, name
        assert float(np.linalg.norm(g - w)) < 2e-3 * scale, name
    assert buffers == 4
    # the counters count the module's layer too: 2 + 1 layers' choices
    assert int(stats["moe_assignments"]) == 2 * SEQ * 2 * 3
    assert stats["router_load"].shape == (2, 16)
    assert stats["mtp_router_load"].shape == (1, 16)
    assert int(stats["mtp_router_load"].sum()) == 2 * SEQ * 2


def test_a_driver_launch_moves_every_leaf_and_notes_the_hyper_plan(family,
                                                                   model):
    """Three fused steps through ``StepDriver``: the loss falls, no leaf is
    left where it was (both selection biases among them), the metrics are
    the loss, the gradients' norm and the counters, and the recorder carries
    the plan ``mix_in`` was traced with."""
    from ray_tpu.train.driver import StepDriver

    cfg, params = model
    opt = ts.default_optimizer(lr=3e-3, warmup_steps=1, total_steps=10)
    driver = StepDriver(cfg, opt, steps_per_launch=3)
    # (a copy: the step donates its arguments)
    before = jax.tree.map(jnp.copy, params)
    seen = []
    new, _, _ = driver.run(jax.tree.map(jnp.copy, params), opt.init(params),
                           iter([{"tokens": TOKENS}] * 3),
                           on_launch=seen.append)
    metrics = seen[0]
    assert set(metrics) == {"loss", "grad_norm", *moe.ROUTING_COUNTERS}
    # (the schedule's first step is at rate 0: the third sees one update)
    assert float(metrics["loss"][2]) < float(metrics["loss"][0])
    still = [jax.tree_util.keystr(path) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(new), jax.tree.leaves(before))
        if bool((a == b).all())]
    assert not still, still
    plan = driver.recorder.summary()["hyper_plan"]
    assert plan == hyper.plan(4, 32, 4, 6)
    assert (plan["stream_bytes_fwd"], plan["stream_bytes_bwd"]) == (
        14 * 32 * 4, 23 * 32 * 4)
    # the CPU's yardstick: XLA's form (``attn_impl="xla"``, and 32 is no
    # whole number of lanes), which says so and what it cannot know
    assert (plan["impl"], plan["tile_tokens"], plan["stream_bytes_moved_fwd"],
            plan["stream_bytes_moved_bwd"]) == ("xla", None, None, None)
    driver.recorder.close()


# ---- (d) the shares add up ------------------------------------------------------------

def test_four_shares_of_4_of_16_give_the_uncut_layer(family):
    """An expert layer's routed sum over chips 0-3, each holding experts
    4c .. 4c + 3 of 16 (a chip's share holds the first four: the others'
    are this one's with the experts and the router's columns rolled), plus
    the shared expert once, is the reference's layer with all 16 held."""
    hf = dict(family._static(CFG_FILE, None))
    whole_hf = {**hf, "num_experts": 16}
    d, f, E = 32, 24, 16
    ks = jax.random.split(jax.random.key(20), 8)
    layer = {"router": jax.random.normal(ks[0], (d, E)) / math.sqrt(d),
             "router_bias": 1e-2 * jax.random.normal(ks[1], (E,)),
             "e_gate": jax.random.normal(ks[2], (E, d, f)) / math.sqrt(d),
             "e_up": jax.random.normal(ks[3], (E, d, f)) / math.sqrt(d),
             "e_down": jax.random.normal(ks[4], (E, f, d)) / math.sqrt(f),
             "s_gate": jax.random.normal(ks[5], (d, f)) / math.sqrt(d),
             "s_up": jax.random.normal(ks[6], (d, f)) / math.sqrt(d),
             "s_down": jax.random.normal(ks[7], (f, d)) / math.sqrt(f)}
    h = jax.random.normal(jax.random.key(21), (2 * SEQ, d))
    afmoe = spec.load_family("trinity_afmoe")
    with jax.default_matmul_precision("highest"):
        want, _ = afmoe._experts(h, layer, whole_hf, 2)
        shared = family._afmoe()._experts(
            h, {**layer, "e_gate": layer["e_gate"][:0],
                "e_up": layer["e_up"][:0], "e_down": layer["e_down"][:0]},
            {**hf, "num_experts": 0}, 2)[0]
        cfg = dataclasses.replace(_cfg(family), capacity_factor=64.0)
        parts = []
        for chip in range(4):
            roll = lambda a, axis: jnp.roll(a, -4 * chip, axis=axis)
            mine = {"router": roll(layer["router"], 1),
                    "router_bias": roll(layer["router_bias"], 0),
                    **{k: roll(layer[k], 0)[:4]
                       for k in ("e_gate", "e_up", "e_down")}}
            out, _, _ = moe._moe_ffn(cfg, h.reshape(2, SEQ, d), mine)
            parts.append(out.reshape(-1, d))
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(sum(parts) + shared - want).max()) < 1e-5
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.1


# ---- (e) counts, flops, refusals, the plan -------------------------------------------

def test_the_leaves_are_counted(family, model):
    cfg, params = model
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    d, n = 32, 4
    one_half = n * d * 24 + n * d + 24 + 3
    assert hyper.params(n, d) == one_half
    assert cfg.mtp_params(cfg.experts_held) == (
        2 * d * d + 3 * d + cfg.mixer_params("mla")
        + cfg._ffn_params(4) + 2 * one_half)
    plain = dataclasses.replace(cfg, hc_mult=0, n_mtp_modules=0)
    assert cfg.num_params() - plain.num_params() == (
        3 * 2 * one_half + cfg.mtp_params(4))


def test_the_flops_count_the_module_and_the_querys_two_factors(family):
    cfg = _cfg(family)
    plain = dataclasses.replace(cfg, n_mtp_modules=0)
    d, v, f = 32, 96, 24
    module = (2 * d * d + 3 * d + cfg.mixer_params("mla")
              + cfg._ffn_params(0) + cfg._hyper_params()
              + 2 * 4 / 16 * 3 * d * f + d * v)
    attn = 4 * (16 + 8 + 16) * SEQ / 2.0
    assert math.isclose(
        flops.train_flops_per_token(cfg, SEQ)
        - flops.train_flops_per_token(plain, SEQ), 6 * (module + attn),
        rel_tol=1e-3)
    hf = TINY
    assert family.mla_matmul_params(hf) == (
        32 * 24 + 24 * 4 * 24 + 32 * 24 + 16 * 4 * 32 + 4 * 16 * 32)
    assert family.attention_flops_per_token(hf, 3, SEQ) == 4 * attn
    assert family.hyper_stream_bytes_per_token(hf, 3) == 2 * 3 * 37 * 32 * 2
    assert hyper.stream_bytes(4, 32, 2) == (14 * 32 * 2, 23 * 32 * 2)


def test_the_walk_refuses_what_it_does_not_compute(family):
    cfg = _cfg(family)
    with pytest.raises(ValueError, match="second module"):
        dataclasses.replace(cfg, n_mtp_modules=2)
    with pytest.raises(ValueError, match="mla_rope"):
        dataclasses.replace(cfg, mla_rope=False)
    old_stack = moe.MoEConfig(vocab_size=96, d_model=32, n_layers=2, n_heads=4,
                              n_kv_heads=4, d_ff=24, hc_mult=4)
    with pytest.raises(NotImplementedError, match="hc_mult"):
        moe.forward_hidden(moe.init_params(jax.random.key(0), old_stack),
                           TOKENS[:, :-1], old_stack)


@pytest.mark.parametrize("what,change", [
    ("hc_mult=4", {"n_mtp_modules": 0}),
    ("n_mtp_modules", {"hc_mult": 0}),
    (r"kind \['mla'\]", {"hc_mult": 0, "n_mtp_modules": 0})])
@pytest.mark.parametrize("constructor", [
    "init_cache", "generate", "ContinuousBatcher", "ContinuousEngine"])
def test_every_serving_constructor_refuses_by_name(family, model, constructor,
                                                   what, change):
    """Each makes its cache through ``generate.init_cache``, which asks
    ``llama.refuse_trained_only``: the widened stream, the module and the
    layer kind are each named, all that the config has at once."""
    cfg = dataclasses.replace(_cfg(family), **change)
    params = model[1]
    with pytest.raises(NotImplementedError, match=what):
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
