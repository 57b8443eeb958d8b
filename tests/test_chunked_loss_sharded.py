"""The chunked loss under a mesh: the loop's head is gathered once before
the loop (``llama.head_for_loss_loop``), where the rules shard its model dim,
and the same placement stands on the head inside the loop's body (the loop's
operand is the partitioner's to shard, and it chose d split).

The arithmetic must be the single device's, the head's gradient must come
home in the head's stored sharding, and the decision must follow what the
step can observe (the ambient mesh, the rule that places the head): with no
mesh, a mesh that does not split d, no loop or the pipelined rules, the
traced program holds no constraint and is what it was. What the compiled
four-chip step does with the constraint is in ``test_aot_step_1b.py`` and
``test_aot_step_mixtral.py``.

The loop is ``llama._looped_ce``'s: both gradients formed forward, the
head's a product a group of chunks. Groups are two chunks here
(``HEAD_GRAD_ROWS`` patched), so every case sums two groups' products over
a batch the mesh splits; the rule alone is in ``test_chunked_loss.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import llama, moe
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.context import mesh_scope
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.plan import compile_plan

# fp32 compute: the comparison is of placements, not of roundings
DENSE = dataclasses.replace(llama.PRESETS["debug"], loss_chunk=16,
                            compute_dtype=jnp.float32)
SPARSE = dataclasses.replace(moe.PRESETS["moe-debug"], loss_chunk=16,
                             compute_dtype=jnp.float32)
FAMILY = {"dense": (llama, DENSE), "sparse": (moe, SPARSE)}
BATCH, SEQ = 8, 64


@pytest.fixture(autouse=True)
def two_chunks_a_group(monkeypatch):
    monkeypatch.setattr(llama, "HEAD_GRAD_ROWS", 32)
    assert llama._chunks_a_group(SEQ // 16, 16) == 2


def _batch(cfg, masked):
    batch = {"tokens": jax.random.randint(
        jax.random.key(1), (BATCH, SEQ + 1), 0, cfg.vocab_size, jnp.int32)}
    if masked:
        batch["loss_mask"] = (jax.random.uniform(
            jax.random.key(3), (BATCH, SEQ)) > 0.3).astype(jnp.float32)
    return batch


def _mesh(**axes):
    n = int(np.prod(list(axes.values())))
    return make_mesh(MeshConfig(**axes), jax.devices()[:n])


def _constraints(jaxpr):
    """The ``sharding_constraint`` equations of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sharding_constraint":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _constraints(sub)
    return found


def _traced_constraints(fam, cfg, mesh, grad=False):
    params = jax.eval_shape(lambda: fam.init_params(jax.random.key(0), cfg))
    batch = jax.eval_shape(lambda: _batch(cfg, False))
    fn = lambda p, b: fam.lm_loss(p, b, cfg)
    if grad:
        fn = jax.grad(fn)
    with mesh_scope(mesh):  # None: no ambient mesh, as outside any scope
        return _constraints(jax.make_jaxpr(fn)(params, batch).jaxpr)


def _assert_close(got, want, rtol=2e-5):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max() + 1e-8
    assert np.abs(got - want).max() / scale < rtol, (
        np.abs(got - want).max() / scale)


@pytest.mark.parametrize("family,masked,tied", [
    ("dense", False, False), ("sparse", False, False),
    ("dense", True, False), ("sparse", True, False),
    ("dense", False, True), ("sparse", False, True),
])
def test_sharded_loss_and_grads_equal_the_single_device(family, masked, tied):
    """``fsdp 4``, ``loss_chunk`` 16 over 64 positions: the loss and every
    gradient leaf are the unsharded values, and the head's gradient (the
    embedding's where they are tied) is back in its stored sharding."""
    fam, cfg = FAMILY[family]
    cfg = dataclasses.replace(cfg, tie_embeddings=tied)
    params = fam.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg, masked)
    grad_fn = jax.value_and_grad(lambda p, b: fam.lm_loss(p, b, cfg))
    loss_1, grads_1 = jax.jit(grad_fn)(params, batch)

    mesh = _mesh(fsdp=4)
    plan = compile_plan(cfg, mesh)
    p_sh, _ = plan.state_shardings(ts.default_optimizer(total_steps=5))
    sharded = jax.device_put(params, p_sh)
    with mesh_scope(mesh):
        # before the loop, and on the head inside its body
        assert len(_traced_constraints(fam, cfg, mesh)) == 2
        # gradients pinned where the step's optimizer state holds them
        loss_4, grads_4 = jax.jit(
            grad_fn, out_shardings=(plan.replicated(), p_sh))(
                sharded, ts.shard_batch(batch, mesh))
    np.testing.assert_allclose(float(loss_4), float(loss_1), rtol=1e-6)
    jax.tree.map(_assert_close, grads_4, grads_1)
    leaf = "embed" if tied else "lm_head"
    assert grads_4[leaf].sharding.is_equivalent_to(p_sh[leaf], 2), (
        grads_4[leaf].sharding, p_sh[leaf])


@pytest.mark.parametrize("axes,v_axis", [({"fsdp": 4}, None),
                                         ({"fsdp": 2, "tp": 2}, "tp")])
def test_the_heads_gradient_leaves_the_rule_as_the_head_came(axes, v_axis):
    """On the way out of ``_looped_ce``, before the constraint's transpose
    takes it home: the head's gradient in the head's dtype, whole along d on
    every chip and V where the head had it (the groups' products over a
    split batch summed into what each chip holds whole), its values and the
    hidden's the single device's; the hidden's stays on the batch's axis."""
    k = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(k[0], (BATCH, SEQ, 32)).astype(jnp.bfloat16)
    head = (0.3 * jax.random.normal(k[1], (32, 64))).astype(jnp.bfloat16)
    targets = jax.random.randint(k[2], (BATCH, SEQ), 0, 64, jnp.int32)
    grad = jax.jit(jax.grad(
        lambda x, h: llama.chunked_ce(x, h, targets, None, 16), (0, 1)))
    dx_1, dh_1 = grad(x, head)
    mesh = _mesh(**axes)
    came = NamedSharding(mesh, P(None, v_axis))
    with mesh_scope(mesh):
        dx, dh = grad(jax.device_put(x, NamedSharding(mesh, P("fsdp"))),
                      jax.device_put(head, came))
    assert dh.dtype == head.dtype and dx.dtype == x.dtype
    assert dh.sharding.is_equivalent_to(came, 2), dh.sharding
    assert dx.sharding.is_equivalent_to(NamedSharding(mesh, P("fsdp")), 3)
    _assert_close(dh, dh_1, rtol=1e-2)  # bfloat16: a group's rounding
    _assert_close(dx, dx_1, rtol=1e-2)


def test_fsdp_by_tp_keeps_the_vocabulary_on_tp():
    """``fsdp 2 x tp 2``: the constraint, before the loop and in its body,
    is whole along d and leaves V on ``tp``; tied, the embedding's rule is
    read the other way round."""
    mesh = _mesh(fsdp=2, tp=2)
    for tied in (False, True):
        cfg = dataclasses.replace(DENSE, tie_embeddings=tied)
        before, inside = _traced_constraints(llama, cfg, mesh)
        for eqn in (before, inside):
            assert eqn.params["sharding"] == NamedSharding(mesh, P(None, "tp"))
            assert eqn.invars[0].aval.shape == (cfg.d_model, cfg.vocab_size)
            assert eqn.invars[0].aval.dtype == cfg.compute_dtype
    # and the backward holds the first one's transpose, the same constraint
    # once more (the rule's own backward is two scalings)
    both = _traced_constraints(llama, DENSE, mesh, grad=True)
    assert [e.params["sharding"].spec for e in both] == [P(None, "tp")] * 3


@pytest.mark.parametrize("case", ["no-mesh", "fsdp-1", "tp-only", "no-loop",
                                  "one-chunk"])
def test_where_d_is_whole_already_the_program_is_the_parents(case):
    """No mesh, no axis over 1 on d, or no loop: ``lm_loss`` traces no
    sharding constraint at all."""
    chunk, mesh = DENSE.loss_chunk, None
    if case == "fsdp-1":
        mesh = _mesh(fsdp=1)
    elif case == "tp-only":
        mesh = _mesh(tp=4)
    elif case == "no-loop":
        chunk, mesh = 0, _mesh(fsdp=4)
    elif case == "one-chunk":  # S == chunk: chunked_ce runs no loop
        chunk, mesh = SEQ, _mesh(fsdp=4)
    for fam, cfg in FAMILY.values():
        cfg = dataclasses.replace(cfg, loss_chunk=chunk)
        assert _traced_constraints(fam, cfg, mesh) == []
        assert _traced_constraints(fam, cfg, mesh, grad=True) == []


def test_rules_that_replicate_the_head_leave_it_as_it_came():
    """The pipelined rules: on a mesh that would split d the head comes
    back the same object."""
    rules = llama.sharding_rules(pipeline=True)
    assert rules.spec_for("lm_head") == P()
    head = jnp.zeros((DENSE.d_model, DENSE.vocab_size), DENSE.compute_dtype)
    with mesh_scope(_mesh(fsdp=4)):
        assert llama.head_for_loss_loop(head, rules, DENSE, SEQ) is head


@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipelined_step_with_a_chunked_loss_matches_the_flat_model(schedule):
    """Two stages x ``fsdp 2`` with ``loss_chunk`` 16: the pipelined rules
    replicate the head, so ``lm_loss`` adds no constraint of the head's
    (``gpipe``), and 1f1b's loss, inside its ``shard_map``, still traces;
    both give the flat model's loss and gradients."""
    flat = dataclasses.replace(DENSE, loss_chunk=16)
    cfg = dataclasses.replace(flat, pipeline_axis="pp",
                              pipeline_microbatches=4,
                              pipeline_schedule=schedule)
    params = llama.init_params(jax.random.key(0), flat)
    batch = _batch(flat, True)
    loss_1, grads_1 = jax.jit(jax.value_and_grad(
        lambda p, b: llama.lm_loss(p, b, flat)))(params, batch)
    mesh, _ = ts.auto_mesh(4, tp=1, pp=2)
    assert mesh.shape["fsdp"] == 2
    if schedule == "gpipe":
        fn = jax.value_and_grad(lambda p, b: llama.lm_loss(p, b, cfg))
    else:
        fn = lambda p, b: llama.lm_loss_and_grads_1f1b(p, b, cfg)
    with mesh_scope(mesh):
        heads = [e for e in _constraints(jax.make_jaxpr(fn)(params, batch).jaxpr)
                 if e.invars[0].aval.shape == (cfg.d_model, cfg.vocab_size)]
        assert heads == []
        loss_p, grads_p = jax.jit(fn)(params, batch)
    np.testing.assert_allclose(float(loss_p), float(loss_1), rtol=1e-5)
    jax.tree.map(lambda a, b: _assert_close(a, b, rtol=1e-4),
                 grads_p, grads_1)


def test_a_sharded_train_step_moves_the_head_as_the_single_device_does():
    """One optimizer step of ``make_train_step`` on ``fsdp 4`` against the
    same step on no mesh: the updated head is the same and stays in its
    stored sharding (the gradient reached the optimizer in it)."""
    cfg = SPARSE
    batch = _batch(cfg, False)
    heads = {}
    for name, mesh in (("one", None), ("four", _mesh(fsdp=4))):
        opt = ts.default_optimizer(lr=1e-2, warmup_steps=0, total_steps=5)
        if mesh is None:
            params = moe.init_params(jax.random.key(0), cfg)
            state, fed = opt.init(params), batch
        else:
            params, state = ts.init_sharded_state(jax.random.key(0), cfg,
                                                  mesh, opt)
            fed = ts.shard_batch(batch, mesh)
            stored = params["lm_head"].sharding
        params, _, metrics = ts.make_train_step(cfg, opt, mesh=mesh)(
            params, state, fed)
        heads[name] = (params["lm_head"], float(metrics["loss"]))
    assert heads["four"][0].sharding.is_equivalent_to(stored, 2)
    np.testing.assert_allclose(heads["four"][1], heads["one"][1], rtol=1e-6)
    # (adam's first step is lr * sign(g): a rounding of a tiny g shows)
    _assert_close(heads["four"][0], heads["one"][0], rtol=1e-4)
