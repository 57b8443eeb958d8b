"""Sparse-MoE model family: routing semantics + expert-parallel sharding.

Static top-k capacity dispatch must be exact where capacity allows, drop
overflow tokens (residual carries them), balance via the aux loss, and
train sharded over the mesh's ``ep`` axis.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe


@pytest.fixture(scope="module")
def cfg():
    return moe.PRESETS["moe-debug"]


def test_moe_forward_backward_finite(cfg):
    params = moe.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 33), 0,
                                cfg.vocab_size)
    loss, grads = jax.value_and_grad(
        lambda p: moe.lm_loss(p, {"tokens": tokens}, cfg))(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree_util.tree_leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in leaves)
    # the router and experts actually receive gradient
    assert float(jnp.linalg.norm(grads["layers"]["router"])) > 0
    assert float(jnp.linalg.norm(grads["layers"]["e_gate"])) > 0


def test_moe_dispatch_identity_with_ample_capacity(cfg):
    """With top_k=1 and capacity >= all tokens, every token's MoE output
    must equal ITS OWN chosen expert's dense FFN on that token — dispatch
    and combine are exact, not approximate."""
    c = dataclasses.replace(cfg, n_layers=1, top_k=1, capacity_factor=8.0)
    params = moe.init_params(jax.random.key(0), c)
    layer = jax.tree_util.tree_map(lambda x: x[0], params["layers"])

    h = jax.random.normal(jax.random.key(3), (2, 8, c.d_model),
                          c.compute_dtype)
    out, _, _ = moe._moe_ffn(c, h, layer)

    tokens = h.reshape(-1, c.d_model)
    logits = tokens @ layer["router"].astype(jnp.float32)
    chosen = np.asarray(jnp.argmax(logits, axis=-1))
    o = np.asarray(out.reshape(-1, c.d_model), np.float32)
    for g in range(tokens.shape[0]):
        e = int(chosen[g])
        t = tokens[g][None, :]
        gate = jax.nn.silu(t @ layer["e_gate"][e].astype(t.dtype))
        up = t @ layer["e_up"][e].astype(t.dtype)
        dense = np.asarray((gate * up) @ layer["e_down"][e].astype(t.dtype),
                           np.float32)[0]
        np.testing.assert_allclose(o[g], dense, rtol=3e-2, atol=3e-2)


def test_moe_capacity_overflow_drops_tokens(cfg):
    """Tiny capacity: overflowed tokens contribute ZERO FFN output (the
    block's residual carries them) — never garbage."""
    c = dataclasses.replace(cfg, n_layers=1, top_k=1, capacity_factor=0.01)
    params = moe.init_params(jax.random.key(0), c)
    layer = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    router = np.zeros_like(np.asarray(layer["router"], np.float32))
    router[:, 1] = 100.0  # everyone wants expert 1; capacity ~1 slot
    layer = dict(layer)
    layer["router"] = jnp.asarray(router, layer["router"].dtype)

    h = jax.random.normal(jax.random.key(3), (1, 16, c.d_model),
                          c.compute_dtype)
    out, _, _ = moe._moe_ffn(c, h, layer)
    flat = np.asarray(out.reshape(16, -1), np.float32)
    zero_rows = (np.abs(flat).max(axis=1) < 1e-6).sum()
    assert zero_rows >= 14  # ~1 slot served, rest dropped


def _one_hot_moe_ffn(cfg, h, layer):
    """``moe._moe_ffn`` as it was before the rows moved by index: dense
    ``[G, E, C]`` dispatch and combine tensors and the rows through matrix
    products against them (the GShard einsum formulation). Kept as the
    reference the index form is held to; the router is the program's own
    lines, so the two differ only in how the rows move."""
    b, s, d = h.shape
    E, K = cfg.n_experts, cfg.top_k
    G = b * s
    C = max(1, int(cfg.capacity_factor * G * K / E))
    tokens = h.reshape(G, d)
    logits = (tokens @ layer["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_probs, topk_idx = jax.lax.top_k(probs, K)
    if cfg.norm_topk_prob:
        topk_probs = topk_probs / (topk_probs.sum(-1, keepdims=True) + 1e-9)
    sel_onehot = jax.nn.one_hot(topk_idx, E, dtype=jnp.int32)         # [G, K, E]
    flat = sel_onehot.transpose(1, 0, 2).reshape(K * G, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat
    pos = pos_flat.reshape(K, G, E).transpose(1, 0, 2)
    slot = jnp.sum(pos * sel_onehot, axis=-1)                         # [G, K]
    keep = slot < C
    gates = topk_probs * keep
    slot_onehot = jax.nn.one_hot(slot, C, dtype=h.dtype)              # [G, K, C]
    dispatch = jnp.einsum("gke,gkc->gec",
                          sel_onehot.astype(h.dtype) * keep[..., None],
                          slot_onehot)
    combine = jnp.einsum("gke,gkc,gk->gec", sel_onehot.astype(h.dtype),
                         slot_onehot, gates.astype(h.dtype))
    expert_in = jnp.einsum("gd,gec->ecd", tokens, dispatch)           # [E, C, d]
    gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                  layer["e_gate"].astype(h.dtype)))
    up = jnp.einsum("ecd,edf->ecf", expert_in, layer["e_up"].astype(h.dtype))
    expert_out = jnp.einsum("ecf,efd->ecd", gate * up,
                            layer["e_down"].astype(h.dtype))
    out = jnp.einsum("ecd,gec->gd", expert_out, combine)
    frac = jnp.mean(sel_onehot[:, 0, :].astype(jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    return out.reshape(b, s, d), aux


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_experts,top_k,capacity_factor,norm", [
    (4, 2, 1.25, True),
    (4, 2, 0.5, True),      # overflow in both choices
    (4, 1, 1.0, False),     # Switch's: one gate, the softmax's own value
                            # (renormalised it is 1 and its gradient noise)
    (16, 8, 1.25, False),   # OLMoE's routing: top-8, the softmax's own gates
], ids=["top2", "top2-overflow", "top1", "top8-of-16"])
def test_rows_by_index_agree_with_the_one_hot_products(
        cfg, dtype, n_experts, top_k, capacity_factor, norm):
    """Moving the routed rows by index at the same capacity is the same
    mathematics as the one-hot products: the same rows in the same slots,
    the same drops, the same gates. ``aux`` and the experts' gradients are
    the reference's to the bit, and so is the layer's output up to top-2 (a
    one-hot product's output row has one nonzero term a slot, and both
    forms sum a token's K terms in float32); the gradients of ``h`` and of
    the router, where the two forms sum in different orders, agree to the
    dtype's rounding."""
    c = dataclasses.replace(cfg, n_layers=1, n_experts=n_experts, top_k=top_k,
                            capacity_factor=capacity_factor,
                            norm_topk_prob=norm, compute_dtype=dtype,
                            param_dtype=dtype)
    params = moe.init_params(jax.random.key(0), c)
    layer = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    h = jax.random.normal(jax.random.key(3), (2, 64, c.d_model), dtype)
    weigh = jax.random.normal(jax.random.key(4), h.shape, jnp.float32)

    def run(ffn):
        def scalar(h, layer):
            out, aux, *_ = ffn(c, h, layer)
            return (out.astype(jnp.float32) * weigh).sum() + aux, (out, aux)

        # "to the bit" is said of the CPU backend's own compile (level 2).
        # The test process compiles at level 0 (``rt_test_platform.py``, PR
        # 64), where the by-index form's float32 output moves by one last
        # place in a fifth of its entries (its K weighted terms are summed
        # in a fused loop, whose multiply-add LLVM contracts at level 2 as
        # the one-hot product's kernel does at either) and the one-hot
        # form's by none: so these two programs ask for level 2 themselves
        step = jax.jit(jax.value_and_grad(scalar, argnums=(0, 1),
                                          has_aux=True))
        (_, (out, aux)), grads = step.lower(h, layer).compile(
            compiler_options={"xla_backend_optimization_level": 2})(h, layer)
        return out, aux, grads

    want_out, want_aux, (want_h, want_layer) = run(_one_hot_moe_ffn)
    out, aux, (got_h, got_layer) = run(moe._moe_ffn)
    if capacity_factor < 1:  # the case does drop assignments
        assert (np.abs(np.asarray(want_out, np.float32)).max(-1) == 0).any()
    f32 = lambda x: np.asarray(x, np.float32)
    eps = float(jnp.finfo(dtype).eps)

    def close(got, want, name):
        np.testing.assert_allclose(
            f32(got), f32(want), rtol=0, err_msg=name,
            atol=4 * eps * np.abs(f32(want)).max())

    if top_k <= 2:  # two terms sum to the same whichever comes first
        np.testing.assert_array_equal(f32(out), f32(want_out))
    else:
        close(out, want_out, "out")
    assert float(aux) == float(want_aux)
    for name in ("e_gate", "e_up", "e_down"):
        np.testing.assert_array_equal(f32(got_layer[name]),
                                      f32(want_layer[name]), err_msg=name)
    close(got_h, want_h, "h")
    close(got_layer["router"], want_layer["router"], "router")
    # every weight the reference gives a gradient has one here
    assert set(got_layer) == set(want_layer)


def test_moe_aux_loss_prefers_balance(cfg):
    """Aux loss is minimal (=1) under a uniform router and larger under a
    collapsed one."""
    c = dataclasses.replace(cfg, n_layers=1)
    params = moe.init_params(jax.random.key(0), c)
    layer = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    h = jax.random.normal(jax.random.key(5), (2, 32, c.d_model),
                          c.compute_dtype)

    uniform = dict(layer)
    uniform["router"] = jnp.zeros_like(layer["router"])
    _, aux_uniform, _ = moe._moe_ffn(c, h, uniform)

    collapsed = dict(layer)
    r = np.zeros_like(np.asarray(layer["router"], np.float32))
    r[:, 0] = 100.0
    collapsed["router"] = jnp.asarray(r, layer["router"].dtype)
    _, aux_collapsed, _ = moe._moe_ffn(c, h, collapsed)

    assert float(aux_collapsed) > float(aux_uniform)
    assert abs(float(aux_uniform) - 1.0) < 0.2


def test_moe_sharded_train_step_ep_axis(cfg):
    """Full sharded train step on the 8-device CPU mesh with ep=2:
    expert-parallel state + a real optimizer update."""
    from ray_tpu.parallel import train_step as ts

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    mesh, _ = ts.auto_mesh(8, tp=2, ep=2)
    optimizer = ts.default_optimizer(total_steps=10)
    params, opt_state = ts.init_sharded_state(
        jax.random.key(0), cfg, mesh, optimizer)
    # expert dim is genuinely sharded over ep
    spec = params["layers"]["e_gate"].sharding.spec
    assert "ep" in str(spec)
    step = ts.make_train_step(cfg, optimizer, mesh=mesh)
    tokens = jax.random.randint(jax.random.key(1), (8, 33), 0,
                                cfg.vocab_size)
    batch = ts.shard_batch({"tokens": tokens}, mesh)
    losses = []
    for _ in range(3):  # step 1 is a warmup-LR no-op (schedule starts at 0)
        params, opt_state, metrics = step(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    # warmup-LR adam on one batch need not descend monotonically, but the
    # update must have APPLIED: the loss moves once lr > 0
    assert losses[2] != losses[1]


@pytest.mark.parametrize("n_experts,n,axes,placement", [
    (4, 4, dict(tp=1), "expert"),             # fsdp 4
    (4, 4, dict(tp=1, ep=2), "expert"),       # ep 2 x fsdp 2
    (4, 8, dict(tp=2, ep=2), "expert"),       # tp 2 x ep 2, fsdp 2 is left
    (6, 4, dict(tp=1), "model_dim"),          # fsdp 4 does not split 6 experts
], ids=["fsdp4", "ep2-fsdp2", "tp2-ep2", "e6-fsdp4"])
def test_sharded_step_agrees_with_one_device(cfg, n_experts, n, axes,
                                             placement):
    """Where the experts split evenly over ep x fsdp a chip owns whole
    experts, where they do not fsdp splits the model dim as before; either
    way the sharded step computes what one device computes (float32, so
    that the order of a sum is the only difference), and the driver's
    recorder says which placement the plan resolved and what the compiled
    step moves across chips."""
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.plan import compile_plan
    from ray_tpu.train.driver import StepDriver

    if len(jax.devices()) < n:
        pytest.skip("needs the 8-device CPU mesh")
    c = dataclasses.replace(cfg, n_experts=n_experts,
                            compute_dtype=jnp.float32)
    mesh, _ = ts.auto_mesh(n, jax.devices()[:n], **axes)
    optimizer = ts.default_optimizer(total_steps=10)
    params = moe.init_params(jax.random.key(0), c)
    tokens = jax.random.randint(jax.random.key(1), (8, 33), 0, c.vocab_size)
    loss_and_grads = jax.value_and_grad(
        lambda p, b: moe.lm_loss(p, b, c))
    want_loss, want = jax.jit(loss_and_grads)(params, {"tokens": tokens})

    plan = compile_plan(c, mesh)
    assert plan.expert_placement() == placement
    p_sh, _ = plan.state_shardings(optimizer)
    e_axes = p_sh["layers"]["e_gate"].spec[1]
    assert ("fsdp" in e_axes) == (placement == "expert"), e_axes
    assert "ep" in e_axes
    got_loss, got = jax.jit(
        loss_and_grads,
        in_shardings=(p_sh, plan.batch_sharding(2, False, False)),
        out_shardings=(plan.replicated(), p_sh))(params, {"tokens": tokens})
    np.testing.assert_allclose(float(got_loss), float(want_loss), rtol=1e-5)
    for path, g in jax.tree_util.tree_leaves_with_path(got):
        w = want
        for key in path:
            w = w[key.key]
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-3,
                                   atol=2e-6, err_msg=str(path))

    # the fused driver: two steps a launch from sharded state; its loss is
    # the one above and its recorder carries the placement and the program
    driver = StepDriver(c, optimizer, mesh=mesh, steps_per_launch=2)
    try:
        state = ts.init_sharded_state(jax.random.key(0), c, mesh, optimizer)
        batch = {"tokens": np.asarray(tokens)}
        _, _, metrics = driver.run(*state, [batch, batch])
        np.testing.assert_allclose(float(metrics["loss"][0]),
                                   float(want_loss), rtol=1e-5)
        summ = driver.recorder.summary()
        assert summ["expert_placement"] == placement
        kinds = summ["collectives"]
        assert kinds and all(k["count"] > 0 and k["bytes"] > 0
                             and k["runs"] >= k["count"]
                             for k in kinds.values()), kinds
        assert driver.compile_count() == 1  # reading it compiled nothing
    finally:
        driver.recorder.close()


@pytest.mark.parametrize("n_experts,axes,e_gate,e_down", [
    (8, dict(fsdp=4), (None, ("ep", "fsdp"), None, "tp"),
     (None, ("ep", "fsdp"), "tp", None)),
    (8, dict(ep=2, fsdp=2), (None, ("ep", "fsdp"), None, "tp"),
     (None, ("ep", "fsdp"), "tp", None)),
    (64, dict(fsdp=2, tp=2), (None, ("ep", "fsdp"), None, "tp"),
     (None, ("ep", "fsdp"), "tp", None)),
    (6, dict(fsdp=4), (None, "ep", "fsdp", "tp"), (None, "ep", "tp", "fsdp")),
    (6, dict(ep=2, fsdp=2), (None, "ep", "fsdp", "tp"),
     (None, "ep", "tp", "fsdp")),
    (8, None, (None, ("ep", "fsdp"), None, "tp"),
     (None, ("ep", "fsdp"), "tp", None)),
], ids=["e8-fsdp4", "e8-ep2-fsdp2", "e64-fsdp2-tp2", "e6-fsdp4",
        "e6-ep2-fsdp2", "no-mesh"])
def test_rules_place_experts_by_what_divides(cfg, n_experts, axes, e_gate,
                                             e_down):
    """The placement is read off the sizes: the experts' dimension takes
    ep x fsdp where ``n_experts`` splits evenly over them, else today's
    (experts over ep, the model dim over fsdp); adam's moments follow their
    parameter by path and shape; asked without a mesh a rule answers with
    its first choice. No option selects it."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    c = dataclasses.replace(cfg, n_experts=n_experts)
    rules = moe.sharding_rules()
    shapes = jax.eval_shape(lambda: moe.init_params(jax.random.key(0), c))
    if axes is None:
        assert rules.spec_for("layers/e_gate") == P(*e_gate)
        assert rules.tree_specs(shapes)["layers"]["e_down"] == P(*e_down)
        return
    mesh = make_mesh(MeshConfig(**axes), jax.devices()[:4])
    state = jax.eval_shape(ts.default_optimizer(total_steps=10).init, shapes)
    placed = rules.tree_shardings({"params": shapes, "opt": state}, mesh)
    found = {}
    for path, sh in jax.tree_util.tree_leaves_with_path(placed):
        name = str(getattr(path[-1], "key", ""))
        if name.startswith("e_"):
            found.setdefault(name, set()).add(sh.spec)
    # the parameter and both of its moments, one placement each
    assert found == {"e_gate": {P(*e_gate)}, "e_up": {P(*e_gate)},
                     "e_down": {P(*e_down)}}, found
    assert moe.expert_placement(P(*e_gate)) == (
        "expert" if n_experts != 6 else "model_dim")


def test_checkpoint_of_the_old_placement_restores_into_the_new(cfg, tmp_path):
    """State saved with the experts' model dim over fsdp (the placement
    before experts were placed by expert) restores into the plan's
    placement, value for value: the restore reshards by the target's
    shardings, whatever the file was written under."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.plan import compile_plan
    from ray_tpu.parallel.sharding import ShardingRules
    from ray_tpu.train.checkpoint import Checkpoint

    if len(jax.devices()) < 4:
        pytest.skip("needs four CPU devices")
    mesh, _ = ts.auto_mesh(4, jax.devices()[:4], tp=1)
    optimizer = ts.default_optimizer(total_steps=10)
    old = ShardingRules([
        (r"layers/e_(gate|up)$", P(None, "ep", "fsdp", "tp")),
        (r"layers/e_down$", P(None, "ep", "tp", "fsdp"))])
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                              optimizer, rules=old)
    assert params["layers"]["e_gate"].sharding.spec == P(None, "ep", "fsdp",
                                                         "tp")
    ckpt = Checkpoint.from_directory(str(tmp_path / "ck"))
    ckpt.save_pytree({"params": params, "opt_state": opt_state}, "state")

    p_sh, o_sh = compile_plan(cfg, mesh).state_shardings(optimizer)
    target = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        {"params": params, "opt_state": opt_state},
        {"params": p_sh, "opt_state": o_sh})
    back = ckpt.load_pytree("state", target)
    assert "fsdp" in back["params"]["layers"]["e_gate"].sharding.spec[1]
    for a, b, s in zip(jax.tree.leaves((params, opt_state)),
                       jax.tree.leaves((back["params"], back["opt_state"])),
                       jax.tree.leaves((p_sh, o_sh))):
        assert b.sharding == s
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_moe_param_counts(cfg):
    params = moe.init_params(jax.random.key(0), cfg)
    actual = sum(int(np.prod(x.shape))
                 for x in jax.tree_util.tree_leaves(params))
    assert actual == cfg.num_params()
    assert cfg.active_params() < cfg.num_params()


def test_llama_loss_unchanged_after_ce_refactor():
    """chunked_ce extraction must preserve llama's loss values (chunked ==
    unchunked paths)."""
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 65), 0,
                                cfg.vocab_size)
    full = llama.lm_loss(params, {"tokens": tokens}, cfg)
    chunked = llama.lm_loss(
        params, {"tokens": tokens},
        dataclasses.replace(cfg, loss_chunk=16))
    np.testing.assert_allclose(float(full), float(chunked), rtol=1e-5)
