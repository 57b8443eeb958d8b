"""Kimi Linear's program on the patterned training path at a tiny size on
the CPU: ``models/moe.py`` with ``kda`` and ``mla`` layers, a leading dense
layer, 4 of 16 experts held, against the benchmark's plain reference
(``benchmark/families/moonshot_kimi_linear.py``, which imports nothing of
``ray_tpu``) on seeded weights; the chip's share against the uncut layer;
the step under a mesh; the plan a step notes; and who refuses the two kinds.
(The kernels and a layer alone: ``test_kimi_linear_training.py``.)"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, llama, mixers, moe
from ray_tpu.ops import kda
from ray_tpu.parallel import train_step as ts
from ray_tpu.util import flops, plans

from _kimi import (CFG_FILE, DEPTH, REPO, SEQ, TOKENS, _cfg,  # noqa: F401
                   family, spec)


# ---- (c) the program against the plain reference -----------------------------------

def test_the_family_builds_the_patterned_config(family):
    family.require_program()
    cfg = _cfg(family)
    assert cfg.layer_kinds == ("kda", "kda", "kda", "kda", "mla")
    assert cfg.n_dense_layers == 1 and cfg.period() == ("kda", "kda", "kda", "mla")
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (16, 4, 2)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_taps) == (4, 16, 4)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (16, 16, 8, 16)
    assert cfg.router_aux_coef == 0.0 and cfg.route_scale == 2.446
    params = family.init_params(jax.random.key(0), cfg)
    # the mixers' leaves by kind: three kda layers and one mla layer among
    # the four expert layers, one kda layer in the dense segment
    assert params["layers"]["kda"]["wq"].shape == (3, 32, 64)
    assert params["layers"]["mla"]["wq"].shape == (1, 32, 4 * 24)
    assert params["layers"]["mla"]["wkv_a"].shape == (1, 32, 16 + 8)
    assert params["layers"]["mla"]["wkv_b"].shape == (1, 16, 4 * 32)
    assert params["dense_layers"]["kda"]["wo"].shape == (1, 64, 32)
    assert "mla" not in params["dense_layers"]
    assert not set(moe._ATTN_LEAVES) & set(params["layers"])
    assert params["layers"]["kda"]["A_log"].dtype == jnp.float32
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()


@pytest.mark.parametrize("bad,match", [
    (dict(kda_heads=0), "kda_heads"),
    (dict(kv_lora_rank=0), "kv_lora_rank"),
    (dict(layer_kinds=("kda", "rnn", "kda", "kda", "mla")), "kinds")])
def test_a_kind_without_its_sizes_is_refused(family, bad, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(_cfg(family), **bad)


@pytest.fixture(scope="module")
def both(family):
    """The program's loss and gradients (flash kernels interpreted) and the
    reference's, on one seeded tree. Both float32; the program's products at
    ``highest`` too, so that what is left is the order of the sums: the
    chunked form against the token's, the kernels' blocks."""
    cfg = _cfg(family)
    params = family.init_params(jax.random.key(3), cfg)
    # gains and biases off their initial ones and zeros, so that a norm or a
    # bias left out would show
    key = jax.random.key(9)
    for seg in ("layers", "dense_layers"):
        for kind in ("kda", "mla"):
            for name in ("o_norm", "g_bias", "kv_norm"):
                if name in params[seg].get(kind, {}):
                    a = params[seg][kind][name]
                    key = jax.random.fold_in(key, 1)
                    params[seg][kind][name] = a + 0.3 * jax.random.normal(
                        key, a.shape)
    # ONE jitted loss-and-gradients, as a step runs them (taken apart and
    # op by op they were 28 s of the 38 this fixture took alone)
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p: moe.loss_and_stats(p, {"tokens": TOKENS}, cfg),
            has_aux=True))(params)
    # and the reference's the same way (PR 64: its backward op by op)
    ref = jax.jit(lambda p: family.loss(p, TOKENS, CFG_FILE))(params)
    ref_grads = jax.jit(jax.grad(
        lambda p: family.loss(p, TOKENS, CFG_FILE)["loss"]))(params)
    return dict(cfg=cfg, params=params, loss=loss, stats=stats, grads=grads,
                ref=ref, ref_grads=ref_grads)


def test_the_loss_agrees_with_the_reference(both):
    # both float32 at highest: what differs is the order of the sums
    assert float(both["loss"]) == pytest.approx(float(both["ref"]["loss"]),
                                                rel=2e-6)
    assert float(both["ref"]["loss"]) == float(both["ref"]["ce"])  # coefficient 0


def test_logits_agree_with_the_reference(family, both):
    cfg = dataclasses.replace(both["cfg"], capacity_factor=1e3)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p: moe.forward(
            p, TOKENS[:, :-1], cfg))(both["params"]))
    want = np.asarray(jax.jit(lambda p: family.logits(
        p, TOKENS[:, :-1], CFG_FILE))(both["params"]))
    assert np.abs(got - want).max() < 2e-5 * float(np.abs(want).max())


def test_every_gradient_agrees_with_the_reference(both):
    """Leaf by leaf, to 1e-4 of the leaf's largest entry (float32 both; the
    chunked backward sums in another order than the token's), the selection
    bias and its momentum left out: they take no gradient."""
    got = dict(jax.tree.leaves_with_path(both["grads"]))
    want = dict(jax.tree.leaves_with_path(both["ref_grads"]))
    assert got.keys() == want.keys()
    seen = set()
    for path, g in got.items():
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            continue
        scale = float(jnp.abs(want[path]).max())
        assert scale > 0, name       # every leaf is read
        assert float(jnp.abs(g - want[path]).max()) < 1e-4 * scale, name
        seen.add(name.split("'")[-2])
    assert {"A_log", "dt_bias", "conv_q", "f_down", "g_bias", "o_norm", "wb",
            "wkv_a", "wkv_b", "kv_norm", "s_gate", "e_down", "router"} <= seen


def test_the_xla_path_computes_the_same_step(family, both):
    cfg = dataclasses.replace(both["cfg"], attn_impl="xla")
    with jax.default_matmul_precision("highest"):
        loss = moe.lm_loss(both["params"], {"tokens": TOKENS}, cfg)
    assert float(loss) == pytest.approx(float(both["loss"]), rel=1e-6)


def test_the_step_counts_its_routing(both):
    stats, cfg = both["stats"], both["cfg"]
    assert int(stats["moe_assignments"]) == 4 * 2 * SEQ * cfg.top_k
    assert int(stats["moe_kept"]) + int(stats["moe_dropped"]) \
        == int(stats["moe_held"])
    assert stats["router_load"].shape == (4, 16)


def test_one_precision_down_is_another_loss(family, both):
    """The control of the cell's loss limit at this size: the reference with
    its weights and residual stream through bfloat16 lies well outside what
    separates the program from the float32 reference."""
    low = family.loss(both["params"], TOKENS, CFG_FILE, round_to=jnp.bfloat16)
    ref = float(both["ref"]["loss"])
    assert abs(float(low["loss"]) - ref) > 50 * abs(float(both["loss"]) - ref)


# ---- (d) the chip's share against the whole layer ------------------------------------

@pytest.mark.parametrize("capacity_factor", [8.0, 1.0])
def test_the_four_shares_add_up_to_the_uncut_reference_layer(family,
                                                             capacity_factor):
    """Sixteen experts over four chips: each share routes over all sixteen,
    computes its own four experts' part for the tokens routed to them and
    leaves the rest out; the parts, with the shared expert (which every chip
    computes alike) counted once, are the uncut reference layer's output,
    under this family's key names and its 2-of-16 routing."""
    from benchmark.lib import reference as ref

    cfg = dataclasses.replace(_cfg(family), capacity_factor=capacity_factor)
    whole = dataclasses.replace(cfg, n_experts_held=None)
    layer = moe._pick(family.init_params(jax.random.key(3), whole)["layers"],
                      cfg.period(), 1)
    assert layer["e_gate"].shape == (16, 32, 24)
    h = jax.random.normal(jax.random.key(5), (2, SEQ, 32), jnp.float32)
    hf = {**dict(family._static(CFG_FILE, capacity_factor)), "num_experts": 16}
    with jax.default_matmul_precision("highest"):
        want, _ = family._afmoe()._experts(h.reshape(-1, 32), layer, hf, 2)
        parts, held = [], 0
        for j in range(4):
            order = jnp.roll(jnp.arange(16), -4 * j)
            mine = {**layer, "router": layer["router"][:, order],
                    "router_bias": layer["router_bias"][order],
                    **{k: layer[k][4 * j:4 * j + 4]
                       for k in ("e_gate", "e_up", "e_down")}}
            out, _, routing = moe._moe_ffn(cfg, h, mine)
            parts.append(out.reshape(-1, 32))
            held += int((routing["topk_idx"] < cfg.experts_held).sum())
        shared = ref.swiglu(h.reshape(-1, 32), layer["s_gate"], layer["s_up"],
                            layer["s_down"])
    assert held == 2 * SEQ * cfg.top_k  # every assignment lives on one chip
    got = sum(parts) + shared
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.1


# ---- the counts, the recorder, the refusals ------------------------------------------

def test_the_programs_counts_are_by_kind(family):
    cfg = _cfg(family)
    d, h, w, t = 32, 4, 16, 4
    per_kda = (4 * d * h * w + 3 * t * h * w + 2 * (d * w + w * h * w) + h * w
               + d * h + h + h * w + w)
    assert mixers.kda_params(cfg) == per_kda
    assert mixers.mla_params(cfg) == (d * 4 * 24 + d * 24 + 16
                                      + 16 * 4 * 32 + 4 * 16 * d)
    assert cfg.mixer_params("kda") == per_kda + d
    # an mla layer's scores at 24 and values at 16 over half the keys; a kda
    # layer's chunk form, five products of chunk x width and three of width^2
    seq = 4096
    madds = 4 * h * w * (5 * 64 + 3 * w) + 4 * (24 + 16) * seq / 2
    assert flops._attention_madds(cfg, seq) == madds
    assert flops.train_flops_per_token(cfg, seq) == pytest.approx(
        6.0 * cfg.active_params() + 6.0 * madds)


@pytest.mark.parametrize("width,seq,depth,impl,mix,launched", [
    (16, SEQ, DEPTH, "xla", "xla", True),
    # a chunk of 64 and a head of whole lanes
    (128, 64, 2, "pallas_insides", "pallas", False)])
def test_the_recorder_carries_the_kda_plan(family, width, seq, depth, impl,
                                           mix, launched):
    """What a step's kernels note reaches the driver's recorder. The first
    case launches, through ``StepDriver.run``, which opens the scope, and
    counts its routing; the kernels' case traces the driver's fused program
    under the same scope and does not run it: a plan is static per traced
    shape (``util/plans.py``), a launch adds the counters the first case
    holds, and the interpreted kernels' step was 150 s of tier-1's time."""
    from ray_tpu.train.driver import StepDriver

    cfg = dataclasses.replace(_cfg(family, depth=depth), max_seq_len=seq,
                              kda_head_dim=width)
    opt = ts.default_optimizer(total_steps=100)
    params = family.init_params(jax.random.key(3), cfg)
    # one step a launch, as the cell runs: two launches
    driver = StepDriver(cfg, opt, steps_per_launch=1)
    tokens = jax.random.randint(jax.random.key(1), (2, seq + 1), 0, 96)
    rec = driver.recorder
    if launched:
        batches = [{"tokens": np.asarray(tokens)} for _ in range(2)]
        driver.run(params, jax.jit(opt.init)(params), batches)
        assert driver.launches == 2
    else:
        with plans.noting(rec.plans):
            driver._multi._jit.lower(params, jax.eval_shape(opt.init, params),
                                     {"tokens": tokens[None]})
    try:
        deadline = time.time() + 30
        while time.time() < deadline and rec.summary()["in_flight"]:
            time.sleep(0.01)
        summ = rec.summary()
        assert summ["kda_plan"] == kda.plan(seq, 4, width, width, batch=2)
        assert (summ["kda_plan"]["impl"], summ["kda_plan"]["mix"]) == (impl, mix)
        assert rec.window_summary(0.0, 1e18)["kda_plan"] == summ["kda_plan"]
        assert {p.get("value_dim") for p in summ["flash_plans"]} == (
            {16} if depth == DEPTH else set())    # two layers hold no ``mla``
        if launched:
            assert summ["routing"]["moe_assignments"] == (
                2 * (depth - 1) * 2 * seq * cfg.top_k)
    finally:
        rec.close()


def test_the_serving_constructors_refuse_both_kinds_by_name(family):
    cfg = _cfg(family)
    with pytest.raises(NotImplementedError, match=r"\['kda', 'mla'\]"):
        generate.init_cache(cfg, 1, 64)
    llama.refuse_trained_only(llama.PRESETS["debug"])   # and no one else
    with pytest.raises(NotImplementedError, match="kda layer"):
        moe.lm_loss(family.init_params(jax.random.key(0), cfg), {
            "tokens": TOKENS, "segment_ids": jnp.zeros_like(TOKENS[:, :-1])},
            dataclasses.replace(cfg, attn_impl="xla"))


# ---- the walk the benchmark already trains ---------------------------------------------

@pytest.mark.parametrize("attn_impl,loss_chunk,parent", [
    ("flash", 8, "fb8b8b5966e4bfe5"), ("xla", 8, "21914aa40d5fb441"),
    ("flash", 0, "bab56c94956a4485"), ("xla", 0, "b4c98a0ce1659c33")])
def test_a_walk_of_window_and_full_layers_traces_to_the_parents_jaxpr(
        attn_impl, loss_chunk, parent):
    """The stacks by kind leave a segment of ``window`` and ``full`` layers
    as it was: the fused step of ``tests/test_trinity_training.py``'s tiny
    Trinity, traced with that file's ``_digest``, was commit ce6ce1f's (PR
    48's parent, where the two digests were taken with the same function),
    equation for equation. (At the cell's own shapes the gradient's jaxpr
    was the parent's too, and Mistral's and Mixtral's with their flash
    plans' records: compared once by hand, PERF.md section 6.) Since PR 60
    the two with the chunked loss hold ``llama._looped_ce``'s rule where
    the jaxpr held a rematted scan and its transpose (they were
    94d2651053756d6e and 023f9347d58d09bf); with ``loss_chunk`` 0 the step
    runs no loop and its two are commit 23fff03's, PR 60's parent: the walk
    did not move."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import test_trinity_training as tt

    cfg = tt._cfg(spec.load_family("trinity_afmoe"), attn_impl=attn_impl)
    assert cfg.loss_chunk == 8
    cfg = dataclasses.replace(cfg, loss_chunk=loss_chunk)
    assert tt._digest(cfg) == parent


# ---- (g) under a mesh ------------------------------------------------------------------

def test_the_by_kind_stacks_have_rules_and_the_sharded_step_agrees(family):
    """Every leaf of the by-kind tree resolves under the family's rules, and
    the fused step on four devices (ep 2 x fsdp 2) gives the single
    device's loss and counters."""
    from ray_tpu.parallel.plan import compile_plan
    from ray_tpu.train.driver import StepDriver

    if len(jax.devices()) < 4:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = _cfg(family, attn_impl="xla")
    opt = ts.default_optimizer(total_steps=100)
    params = family.init_params(jax.random.key(0), cfg)
    want, stats = jax.jit(lambda p: moe.loss_and_stats(
        p, {"tokens": TOKENS}, cfg))(params)

    mesh, _ = ts.auto_mesh(4, jax.devices()[:4], tp=1, ep=2)
    plan = compile_plan(cfg, mesh)
    p_sh, _ = plan.state_shardings(opt)
    assert jax.tree.structure(p_sh) == jax.tree.structure(params)
    P = jax.sharding.PartitionSpec
    kda_sh, mla_sh = p_sh["layers"]["kda"], p_sh["layers"]["mla"]
    assert kda_sh["wq"].spec == mla_sh["wq"].spec == P(None, "fsdp", "tp")
    assert kda_sh["wo"].spec == mla_sh["wo"].spec == P(None, "tp", "fsdp")
    assert kda_sh["f_down"].spec == mla_sh["wkv_a"].spec == P(None, "fsdp", None)
    assert kda_sh["g_up"].spec == mla_sh["wkv_b"].spec == P(None, None, "tp")
    assert kda_sh["A_log"].spec == kda_sh["o_norm"].spec == P(None)
    assert p_sh["dense_layers"]["kda"]["wq"].spec == kda_sh["wq"].spec
    driver = StepDriver(cfg, opt, mesh=mesh, steps_per_launch=2)
    try:
        state = ts.init_sharded_state(jax.random.key(0), cfg, mesh, opt)
        batch = {"tokens": np.asarray(TOKENS)}
        _, _, metrics = driver.run(*state, [batch, batch])
        np.testing.assert_allclose(float(metrics["loss"][0]), float(want),
                                   rtol=1e-5)
        for name in moe.ROUTING_COUNTERS:
            assert int(metrics[name][0]) == int(stats[name]), name
    finally:
        driver.recorder.close()
