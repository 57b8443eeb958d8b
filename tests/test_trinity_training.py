"""The patterned training path (``models/moe.py``: Trinity's afmoe block) at a
tiny size on the CPU: window 8 at s 32, 16 experts top-2 of which 4 are held,
two kinds of layer, a leading dense layer, a selection bias that changes
selections. The program against the benchmark's plain reference
(``benchmark/families/trinity_afmoe.py``, which imports nothing of ``ray_tpu``) on
seeded weights; the chip's share against the uncut layer; the steps of the
families the benchmark already trains against the parent's jaxprs; the
routing counters from the step to the recorder's summary."""

import dataclasses
import functools
import hashlib
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.parallel import train_step as ts
from ray_tpu.util import flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import spec  # noqa: E402

# config.json's keys at a tiny size: six published layers (2 dense, window
# and full alternating) of which the first dense one and a whole period run
TINY = {
    "head_dim": 16, "hidden_size": 32, "intermediate_size": 64,
    "layer_types": ["sliding_attention", "full_attention"] * 3,
    "load_balance_coeff": 5e-2, "moe_intermediate_size": 24,
    "mup_enabled": True, "num_attention_heads": 4, "num_dense_layers": 2,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_tok": 2,
    "num_hidden_layers": 6, "layers_run": [0, 2, 3, 4, 5],
    "num_key_value_heads": 2, "num_shared_experts": 1, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
    "score_func": "sigmoid", "sliding_window": 8,
    "tie_word_embeddings": False, "vocab_size": 96}
CFG_FILE = {"config": TINY, "assumed": {"capacity_factor": 1.25}}
SEQ, DEPTH = 32, 5


@pytest.fixture(scope="module")
def family():
    return spec.load_family("trinity_afmoe")


def _cfg(family, attn_impl="flash", cfg_file=CFG_FILE, depth=DEPTH):
    cfg = family.program_config(cfg_file, depth, max_seq_len=SEQ,
                                attn_impl=attn_impl, loss_chunk=8)
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32)


@functools.cache
def _params_program(family, cfg, seed):
    def make():
        params = family.init_params(jax.random.key(seed), cfg)
        # a bias as large as the scores' spread: it decides selections
        params["layers"]["router_bias"] = params["layers"]["router_bias"] * 20
        return params

    return jax.jit(make)


def _params(family, cfg, seed=3):
    """A fresh tree a call (a step donates its arguments), one program."""
    return _params_program(family, cfg, seed)()


TOKENS = jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0, 96)


def test_the_family_builds_the_patterned_config(family):
    cfg = _cfg(family)
    assert cfg.layer_kinds == ("window", "window", "full", "window", "full")
    assert (cfg.n_dense_layers, cfg.n_expert_layers) == (1, 4)
    assert cfg.period() == ("window", "full")
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (16, 4, 2)
    assert (cfg.head_dim, cfg.n_heads * cfg.head_dim) == (16, 64)  # 2 x d
    params = _params(family, cfg)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    shapes = {k: v.shape for k, v in params["layers"].items()}
    assert shapes["router"] == (4, 32, 16) and shapes["router_bias"] == (4, 16)
    assert shapes["router_bias_m"] == (4, 16)
    assert shapes["e_gate"] == (4, 4, 32, 24) and shapes["s_up"] == (4, 32, 24)
    assert shapes["wg"] == (4, 32, 64) and shapes["q_norm"] == (4, 16)
    assert params["dense_layers"]["w_gate"].shape == (1, 32, 64)
    # a token visits top_k * held / published of the held experts' worth
    d, f = 32, 24
    assert cfg.num_params() - cfg.active_params() == 4 * (4 - 0.5) * 3 * d * f


@pytest.mark.parametrize("bad", [
    dict(layer_kinds=("window",)),                      # not one a layer
    dict(layer_kinds=("window", "ring") * 2 + ("full",)),   # no such kind
    dict(n_dense_layers=5),                             # no expert layer left
    dict(sliding_window=None),                          # a band of no width
])
def test_a_pattern_that_does_not_fit_is_refused(family, bad):
    with pytest.raises(ValueError):
        dataclasses.replace(_cfg(family), **bad)


@pytest.fixture(scope="module")
def both(family):
    """Program and reference on the same seeded weights and batch: logits
    under the capacity, the loss, its gradients and the step's counters."""
    cfg = _cfg(family)
    params = _params(family, cfg)
    batch = {"tokens": TOKENS}
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p: moe.loss_and_stats(p, batch, cfg), has_aux=True)
        )(params)
        logits = jax.jit(lambda p: moe.forward(p, TOKENS[:, :-1], cfg))(params)

    from benchmark.lib import reference as ref

    # the reference's three as one program each (op by op its backward alone
    # was hundreds of programs for the CPU backend to build, PR 64)
    ref_logits = jax.jit(lambda p: ref._project(family.hidden(
        p, TOKENS[:, :-1], CFG_FILE, 1.25)[0], p["lm_head"]))(params)
    want = jax.jit(lambda p: family.loss(p, TOKENS, CFG_FILE))(params)

    def ref_loss(p):
        with jax.default_matmul_precision("highest"):
            return family.loss(p, TOKENS, CFG_FILE)["loss"]

    return {"cfg": cfg, "params": params, "loss": loss, "grads": grads,
            "stats": {k: int(v) for k, v in stats.items() if not v.ndim},
            "logits": logits, "ref_logits": ref_logits,
            "ref": {k: float(v) for k, v in want.items()},
            "ref_grads": jax.jit(jax.grad(ref_loss))(params)}


def test_logits_agree_with_the_reference(both):
    # float32 on both sides: what is left is the order of the sums
    got, want = np.asarray(both["logits"]), np.asarray(both["ref_logits"])
    scale = float(np.abs(want).max())
    assert scale > 1.0
    assert np.abs(got - want).max() < 1e-4 * scale


def test_the_loss_agrees_with_the_reference(both):
    ref = both["ref"]
    assert float(both["loss"]) == pytest.approx(ref["loss"], rel=1e-5)
    # the balancing term is in it, at a weight that shows: E/K * sum f P is
    # about 1 when the router favours nobody
    assert 0.5 < ref["aux"] < 4.0
    assert ref["loss"] == pytest.approx(ref["ce"] + 5e-2 * ref["aux"], rel=1e-6)


def test_every_gradient_agrees_with_the_reference(both):
    got = dict(jax.tree_util.tree_leaves_with_path(both["grads"]))
    want = dict(jax.tree_util.tree_leaves_with_path(both["ref_grads"]))
    assert got.keys() == want.keys() and len(got) == 37
    for path, g in got.items():
        name = jax.tree_util.keystr(path)
        g, w = np.asarray(g), np.asarray(want[path])
        scale = float(np.abs(w).max())
        if "router_bias" in name:
            # the bias chooses and is no weight: nothing flows into it
            assert scale == 0.0 and not g.any()
            continue
        assert scale > 1e-4, name
        assert np.abs(g - w).max() < 1e-4 * scale, name


def test_the_steps_counters_count_the_routing(both):
    st, cfg = both["stats"], both["cfg"]
    tokens, layers = 2 * SEQ, cfg.n_expert_layers
    assert st["moe_assignments"] == layers * tokens * cfg.top_k
    assert 0 < st["moe_held"] < st["moe_assignments"]
    assert st["moe_kept"] + st["moe_dropped"] == st["moe_held"]
    # the capacity is reckoned from the published count: 1.25 * 64 * 2 / 16
    # = 10 rows an expert; a biased router overfills somebody
    assert st["moe_max_expert_rows"] > 10 and st["moe_dropped"] > 0
    assert st["moe_kept"] <= layers * cfg.experts_held * 10


def test_the_bias_decides_selections_and_the_band_is_seen(family, both):
    """The checks of the checks: without the bias, and with every window
    layer seeing its whole past, the reference moves by far more than the
    agreement above allows."""
    params = both["params"]
    flat = {**params, "layers": {**params["layers"], "router_bias":
                                 jnp.zeros_like(params["layers"]["router_bias"])}}
    assert abs(float(jax.jit(lambda p: family.loss(
        p, TOKENS, CFG_FILE)["loss"])(flat)) - both["ref"]["loss"]) > 1e-3
    from benchmark.lib import reference as ref

    unbanded = jax.jit(lambda p: ref._project(family.hidden(
        p, TOKENS[:, :-1], CFG_FILE, 1.25, window=False)[0], p["lm_head"]))(
            params)
    late = slice(TINY["sliding_window"], None)  # rows with a past below the band
    assert np.abs(np.asarray(unbanded) - np.asarray(both["ref_logits"]))[
        :, late].max() > 1e-2


def test_the_xla_path_computes_the_same_step(family, both):
    cfg = dataclasses.replace(both["cfg"], attn_impl="xla")
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p: moe.lm_loss(p, {"tokens": TOKENS}, cfg))(
            both["params"])
    assert float(loss) == pytest.approx(float(both["loss"]), rel=1e-6)


# ---- the chip's share against the whole layer ------------------------------------

@pytest.mark.parametrize("capacity_factor", [8.0, 1.0])
def test_the_four_shares_add_up_to_the_uncut_reference_layer(family,
                                                             capacity_factor):
    """Sixteen experts over four chips: each share routes over all sixteen,
    computes its own four experts' part for the tokens routed to them and
    leaves the rest out; the parts, with the shared expert (which every chip
    computes alike) counted once, are the uncut reference layer's output.
    At capacity 1.0 rows drop, the same rows in a share's queue as in the
    whole layer's: an expert's queue is its own."""
    from benchmark.lib import reference as ref

    cfg = dataclasses.replace(_cfg(family), capacity_factor=capacity_factor)
    whole = dataclasses.replace(cfg, n_experts_held=None)
    layer = jax.tree.map(lambda a: a[1], _params(family, whole)["layers"])
    assert layer["e_gate"].shape == (16, 32, 24)
    h = jax.random.normal(jax.random.key(5), (2, SEQ, 32), jnp.float32)

    hf = {**TINY, "num_experts": 16, "capacity_factor": capacity_factor}
    with jax.default_matmul_precision("highest"):
        want, _ = family._experts(h.reshape(-1, 32), layer, hf, 2)
        parts, held, dropped = [], 0, 0
        for j in range(4):
            # share j's experts first: the program holds experts 0 .. held-1
            order = jnp.roll(jnp.arange(16), -4 * j)
            mine = {**layer, "router": layer["router"][:, order],
                    "router_bias": layer["router_bias"][order],
                    **{k: layer[k][4 * j:4 * j + 4]
                       for k in ("e_gate", "e_up", "e_down")}}
            out, _, routing = moe._moe_ffn(cfg, h, mine)
            parts.append(out.reshape(-1, 32))
            here = int((routing["topk_idx"] < cfg.experts_held).sum())
            held += here
            dropped += here - int(routing["keep"].sum())
        shared = ref.swiglu(h.reshape(-1, 32), layer["s_gate"], layer["s_up"],
                            layer["s_down"])
    assert held == 2 * SEQ * cfg.top_k  # every assignment lives on one chip
    assert (dropped > 0) == (capacity_factor == 1.0)
    got = sum(parts) + shared
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(got - want).max()) < 1e-5
    # and one share alone is not the layer
    assert float(jnp.abs(parts[0] + shared - want).max()) > 0.1
    # the whole layer through the program, nothing held elsewhere
    out, _, _ = moe._moe_ffn(whole, h, layer)
    assert float(jnp.abs(out.reshape(-1, 32) + shared - want).max()) < 1e-5


# ---- the steps the benchmark already trains: the parent's jaxprs -------------------

def _digest(cfg):
    """The fused two-step program's jaxpr, addresses and the flash calls'
    names taken out (PR 43 renamed them; the parent's were ``flash_fwd``,
    ``flash_bwd_dq``, ``flash_bwd_dkv``)."""
    opt = ts.default_optimizer(lr=3e-4, warmup_steps=10, total_steps=100)
    fam = ts.model_family(cfg)
    params = jax.eval_shape(lambda r: fam.init_params(r, cfg),
                            jax.random.key(0))
    batch = {"tokens": jax.ShapeDtypeStruct((2, 1, 65), jnp.int32)}
    step = ts.make_multi_step(cfg, opt, 2)
    # from empty caches: JAX prints a sub-jaxpr that two call sites share
    # once, hoisted, and which sites share one (``jnp.where`` at a shape an
    # earlier test used, an entry since evicted) depends on what the process
    # traced before, so the text would depend on the tests that ran before
    # on this worker
    jax.clear_caches()
    text = str(jax.make_jaxpr(step._jit)(
        params, jax.eval_shape(opt.init, params), batch))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    # the blocks' policy (``llama.remat_block``) under the name the parent's
    # had: to a block without the flash kernel it is that policy
    text = text.replace(
        "<function save_from_both_policies.<locals>.policy>",
        "<function dots_with_no_batch_dims_saveable>")
    text = re.sub(r"flash_(fwd|bwd_dq|bwd_dkv|dq|dkv)\w*", "flash", text)
    text = re.sub(r"\s+", " ", text)  # a longer name wraps a line elsewhere
    return hashlib.sha256(text.encode()).hexdigest()[:16]


_SHAPE = dict(vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
              d_ff=96, max_seq_len=64, param_dtype=jnp.bfloat16, loss_chunk=16)

# Mistral's and Mixtral's blocks (grouped-query, chunked loss, top-2 of 4
# experts) with the flash kernels and without, and the same four steps with
# ``loss_chunk`` 0, which run no loop. The four with the loop are PR 60's:
# that PR made the chunked loss ``llama._looped_ce``'s rule, a ``custom_vjp``
# whose forward loop forms both gradients, where the jaxpr held a rematted
# scan and its transpose (they were cbc5c26018a3bf58, 5eaa6356b79991ed,
# ffce3c5afcb4fa55 and 13aeed2041cd205e: PR 45's with the kernels, which keep
# the forward kernel's output and log-sum-exp, and PR 41's parent's without).
# The four without the loop are commit 23fff03's (PR 60's parent) by the
# function above: nothing but the loop moved. That PR also made the function
# start from empty caches: on a worker that had traced other tests first
# every digest here came out otherwise, and the values are a fresh process's
PARENT = {("dense", "flash", 16): "a172b282cdc1a4c7",
          ("dense", "xla", 16): "3e4a4cc6ec53d4bd",
          ("moe", "flash", 16): "2e699e0aaa931136",
          ("moe", "xla", 16): "ba3652f6b27da927",
          ("dense", "flash", 0): "6bab30ef2ec83d19",
          ("dense", "xla", 0): "b146de5e389c56e4",
          ("moe", "flash", 0): "b9d79a264182b4b6",
          ("moe", "xla", 0): "ad6ea5226ec1a578"}


@pytest.mark.parametrize("family_name,attn_impl,loss_chunk", sorted(PARENT))
def test_the_old_steps_trace_to_the_parents_jaxpr(family_name, attn_impl,
                                                  loss_chunk):
    """With ``window=None`` and the new config fields at their defaults the
    steps of the cells the benchmark already trains are the parent's."""
    shape = dict(_SHAPE, loss_chunk=loss_chunk)
    if family_name == "dense":
        cfg = llama.LlamaConfig(**shape, attn_impl=attn_impl)
    else:
        cfg = moe.MoEConfig(**shape, attn_impl=attn_impl, n_experts=4,
                            top_k=2, router_aux_coef=0.02)
    assert _digest(cfg) == PARENT[family_name, attn_impl, loss_chunk]


def test_the_defaults_count_what_they_counted():
    dense = llama.LlamaConfig(**_SHAPE)
    sparse = moe.MoEConfig(**_SHAPE, n_experts=4, top_k=2)
    d, f, v, hd = 64, 96, 128, 16
    attn = 2 * d * 4 * hd + 2 * d * 2 * hd
    assert dense.head_dim == hd
    assert dense.num_params() == 2 * v * d + d + 2 * (attn + 3 * d * f + 2 * d)
    assert sparse.num_params() == 2 * v * d + d + 2 * (
        attn + 4 * 3 * d * f + d * 4 + 2 * d)
    assert sparse.active_params() == 2 * v * d + d + 2 * (
        attn + 2 * 3 * d * f + d * 4 + 2 * d)
    for cfg in (dense, sparse):
        n = cfg.active_params() if cfg is sparse else cfg.num_params()
        assert flops.train_flops_per_token(cfg, 64) == 6.0 * n + 6 * 2 * 64 * 4 * hd


def test_the_programs_flops_count_the_band(family):
    cfg = _cfg(family)
    # a window layer's query sees w - w^2 / 2s keys on average, a full
    # layer's s / 2: 3 window layers and 2 full ones at s 32, w 8
    keys = 3 * (8 - 64 / 64) + 2 * 16
    assert flops.train_flops_per_token(cfg, SEQ) == pytest.approx(
        6.0 * cfg.active_params() + 12 * keys * 4 * 16)
    # and the benchmark's own arithmetic says the same of the attention
    assert family.attention_flops_per_token(TINY, DEPTH, SEQ) == 2 * 64 * keys


# ---- from the step to the record ----------------------------------------------------

def _bias_step(load, m):
    """The balancing rule by hand, in numpy: (the bias's move, its new
    momentum) from the layers' load [L, E]."""
    rate, momentum = moe.ROUTER_BIAS_RATE, moe.ROUTER_BIAS_MOMENTUM
    load = np.asarray(load, np.float64)
    mean = load.mean(-1, keepdims=True)
    step = rate * np.tanh((mean - load) / mean)
    step -= step.mean(-1, keepdims=True)
    m = momentum * np.asarray(m, np.float64) + (1 - momentum) * step
    return m, m


@pytest.fixture(scope="module")
def launched(family):
    """One ``StepDriver`` for the two tests below that need its programs and
    are not about the optimizer, (config, optimizer, driver): four steps in
    two launches through ``run`` first (a launch's trace is what notes the
    kernels' plans in the recorder), then its single step and its fused
    launch of two for who calls them bare. The two tests built the fused
    program once each."""
    from ray_tpu.train.driver import StepDriver

    cfg = _cfg(family)
    opt = ts.default_optimizer(lr=1e-2, warmup_steps=1, total_steps=10)
    params = _params(family, cfg)
    driver = StepDriver(cfg, opt, steps_per_launch=2)
    batches = [{"tokens": np.asarray(TOKENS)} for _ in range(4)]
    driver.run(params, jax.jit(opt.init)(params), batches)
    yield cfg, opt, driver
    driver.recorder.close()


def test_a_step_moves_the_bias_by_the_load_alone(family, launched):
    """The selection bias takes no gradient and no decay: a step moves it
    by the balancing rule from what the step's routers chose, toward the
    experts under their share, held here or not; the router and every
    weight move by the optimizer."""
    cfg, opt, driver = launched
    params = _params(family, cfg)
    before = jax.tree.map(np.asarray, params["layers"])
    _, stats = jax.jit(lambda p: moe.loss_and_stats(
        p, {"tokens": TOKENS}, cfg))(params)
    load = np.asarray(stats["router_load"])
    assert load.shape == (4, 16) and (load.sum(-1) == 2 * SEQ * 2).all()
    assert int(stats["moe_held"]) == load[:, :4].sum()
    assert int(stats["moe_max_expert_rows"]) == load[:, :4].max()

    after, _, one = driver._single(_params(family, cfg), opt.init(params),
                           {"tokens": TOKENS})
    assert set(one) == {"loss", "grad_norm", *moe.ROUTING_COUNTERS}
    move, m = _bias_step(load, before["router_bias_m"])
    np.testing.assert_allclose(
        after["layers"]["router_bias"] - before["router_bias"], move,
        atol=1e-7)
    np.testing.assert_allclose(after["layers"]["router_bias_m"], m, atol=1e-8)
    # an expert nobody chose rises, the busiest falls, the mean stays
    moved = np.asarray(after["layers"]["router_bias"]) - before["router_bias"]
    assert (moved[load == 0] > 0).all() and (load == 0).any()
    assert (moved[np.arange(4), load.argmax(-1)] < 0).all()
    assert np.abs(moved.mean(-1)).max() < 1e-8

    # the fused launch: the second step starts from the first's momentum
    after2, _, metrics = driver._multi(_params(family, cfg), opt.init(params),
                               {"tokens": jnp.stack([TOKENS, TOKENS])})
    assert set(metrics) == {"loss", "grad_norm", *moe.ROUTING_COUNTERS}
    assert all(metrics[k].shape == (2,) for k in metrics)
    assert int(one["moe_assignments"]) == int(metrics["moe_assignments"][0])
    assert not np.array_equal(after2["layers"]["router_bias"],
                              after["layers"]["router_bias"])
    for name in ("router", "s_gate", "wg", "attn_post_norm", "e_down"):
        assert not np.array_equal(after2["layers"][name], before[name]), name

    # a caller's own loss counts nothing: the bias keeps still, nothing fails
    own = ts.make_train_step(cfg, opt, loss_fn=moe.lm_loss)
    kept, _, m_own = own(_params(family, cfg), opt.init(params),
                         {"tokens": TOKENS})
    assert set(m_own) == {"loss", "grad_norm"}
    assert np.array_equal(kept["layers"]["router_bias"], before["router_bias"])


def test_the_bias_holds_the_held_experts_to_their_share(family):
    """Forty steps on one batch: a router trained on this chip's share alone
    learns to choose the experts that live elsewhere (they cost the loss
    nothing); the rule's bias keeps the held experts near their 4 of 16."""
    cfg = _cfg(family)
    opt = ts.default_optimizer(lr=3e-3, warmup_steps=1, total_steps=1000)
    batch = {"tokens": jnp.stack([TOKENS] * 20)}

    def held_share(rate):
        old, moe.ROUTER_BIAS_RATE = moe.ROUTER_BIAS_RATE, rate
        try:
            params = family.init_params(jax.random.key(5), cfg)
            state, shares = (params, opt.init(params)), []
            step = ts.make_multi_step(cfg, opt, 20)  # traced at this rate
            for _ in range(2):
                *state, m = step(*state, batch)
                shares += list(np.asarray(m["moe_held"])
                               / np.asarray(m["moe_assignments"]))
            return np.mean(shares[20:])
        finally:
            moe.ROUTER_BIAS_RATE = old

    with_rule, without = held_share(moe.ROUTER_BIAS_RATE), held_share(0.0)
    assert abs(with_rule - 0.25) < 0.05
    assert abs(with_rule - 0.25) < abs(without - 0.25)


@pytest.mark.parametrize("bad", [dict(balance="first_choice")])
def test_first_choice_balance_is_refused_with_a_share_held(family, bad):
    with pytest.raises(ValueError, match="first_choice"):
        dataclasses.replace(_cfg(family), **bad)


def test_the_recorder_carries_the_routing_and_the_window(launched):
    cfg, _, driver = launched
    rec = driver.recorder
    deadline = time.time() + 30  # the watcher closes a record behind it
    while time.time() < deadline and rec.summary()["in_flight"]:
        time.sleep(0.01)
    summ = rec.summary()
    per_step = cfg.n_expert_layers * 2 * SEQ * cfg.top_k
    routing = summ["routing"]
    assert routing["moe_assignments"] == 4 * per_step
    assert routing["moe_kept"] + routing["moe_dropped"] == routing["moe_held"]
    assert 0 < routing["moe_max_expert_rows"] <= 2 * SEQ
    assert rec.window_summary(0.0, 1e18)["routing"] == routing
    launches = [r for r in rec.launches() if "counters" in r]
    assert len(launches) == 2
    assert launches[0]["counters"]["moe_assignments"] == 2 * per_step
    totals = rec.launch_totals()
    assert totals["launches"] == 2 and totals["steps"] == 4
    assert totals["moe_held"] == routing["moe_held"]
    assert [c["moe_assignments"] for c in totals["per_launch"]] == [
        2 * per_step] * 2
    assert totals["t0"] < totals["t1"]
    # window layers and full ones plan their kernels apart
    plans = {(p["kind"], p["window"]) for p in summ["flash_plans"]}
    assert plans == {(k, w) for k in ("fwd", "dq", "dkv") for w in (8, None)}
    # one tile a call at this length, crossed by the diagonal (and the
    # band), and too short to be cut: the tile is its own sub-tile
    for p in summ["flash_plans"]:
        assert (p["grid_steps"], p["live_steps"], p["edge_steps"]) == (
            1, 1, 1), p
        assert p["sub_block"] == (p["block_q"], p["block_k"]), p


def test_a_dense_steps_record_has_no_routing():
    from ray_tpu.util.train_recorder import TrainRecorder

    rec = TrainRecorder()
    try:
        assert rec.window_summary(0.0, 1e18)["routing"] == {}
        assert rec.launch_totals() is None
        assert rec._read_counters({"loss": np.zeros(2)}) is None
        # whatever integers the metrics carry, the family's maxima as such
        rec.counter_maxima = ("moe_max_expert_rows",)
        assert rec._read_counters({
            "loss": np.zeros(2), "moe_held": np.array([3, 4]),
            "moe_max_expert_rows": np.array([5, 9])}) == {
                "moe_held": 7, "moe_max_expert_rows": 9}
    finally:
        rec.close()


# ---- under a mesh ---------------------------------------------------------------------

def test_the_new_leaves_have_rules_and_the_sharded_step_agrees(family):
    """Every leaf of the patterned tree resolves under the family's rules,
    and the fused step on four devices (ep 2 x fsdp 2: one of the four held
    experts on each) gives the single device's loss and counters."""
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
    assert plan.expert_placement() == "expert"
    p_sh, _ = plan.state_shardings(opt)
    assert jax.tree.structure(p_sh) == jax.tree.structure(params)
    assert "ep" in p_sh["layers"]["e_gate"].spec[1]
    assert p_sh["layers"]["router_bias"].spec == jax.sharding.PartitionSpec(None)
    assert p_sh["layers"]["s_gate"].spec == p_sh["dense_layers"]["w_gate"].spec
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
