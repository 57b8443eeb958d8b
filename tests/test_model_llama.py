"""Model + sharding tests on the virtual 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.ops.attention import mha
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_angles
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.mesh import MeshConfig, make_mesh


def test_rmsnorm_matches_reference():
    x = jax.random.normal(jax.random.key(0), (2, 8, 16))
    w = jax.random.normal(jax.random.key(1), (16,))
    out = rmsnorm(x, w)
    expected = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * w
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-5)


def test_rope_preserves_norm():
    x = jax.random.normal(jax.random.key(0), (1, 16, 2, 8))
    sin, cos = rope_angles(16, 8)
    out = apply_rope(x, sin, cos)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


def test_rope_relative_property():
    # <rope(q, m), rope(k, n)> depends only on m - n.
    q = jax.random.normal(jax.random.key(0), (1, 1, 1, 8))
    k = jax.random.normal(jax.random.key(1), (1, 1, 1, 8))
    sin, cos = rope_angles(32, 8)

    def dot_at(m, n):
        pos_q = jnp.array([[m]])
        pos_k = jnp.array([[n]])
        rq = apply_rope(q, sin, cos, pos_q)
        rk = apply_rope(k, sin, cos, pos_k)
        return float(jnp.sum(rq * rk))

    assert dot_at(3, 1) == pytest.approx(dot_at(10, 8), rel=1e-4)


def test_mha_causal_masking():
    q = jax.random.normal(jax.random.key(0), (1, 4, 2, 8))
    k = jax.random.normal(jax.random.key(1), (1, 4, 2, 8))
    v = jax.random.normal(jax.random.key(2), (1, 4, 2, 8))
    out_full = mha(q, k, v, causal=True)
    # Changing future keys/values must not affect earlier outputs.
    k2 = k.at[:, 3].set(99.0)
    v2 = v.at[:, 3].set(99.0)
    out_masked = mha(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out_full[:, :3]),
                               np.asarray(out_masked[:, :3]), rtol=1e-5)


def test_mha_gqa_matches_repeated_heads():
    b, s, hkv, g, d = 1, 6, 2, 2, 8
    q = jax.random.normal(jax.random.key(0), (b, s, hkv * g, d))
    k = jax.random.normal(jax.random.key(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.key(2), (b, s, hkv, d))
    out_gqa = mha(q, k, v, causal=True)
    k_rep = jnp.repeat(k, g, axis=2)
    v_rep = jnp.repeat(v, g, axis=2)
    out_rep = mha(q, k_rep, v_rep, causal=True)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_rep), rtol=1e-5)


def test_segment_ids_block_cross_attention():
    q = k = v = jax.random.normal(jax.random.key(0), (1, 4, 1, 8))
    seg_packed = jnp.array([[0, 0, 1, 1]])
    out_packed = mha(q, k, v, causal=True, segment_ids=seg_packed)
    out_single = mha(q[:, 2:], k[:, 2:], v[:, 2:], causal=True)
    np.testing.assert_allclose(np.asarray(out_packed[:, 2:]),
                               np.asarray(out_single), rtol=1e-5, atol=1e-6)


def test_forward_shapes_and_finite():
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = llama.forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_num_params_matches_tree():
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert n == cfg.num_params()


def test_loss_decreases_single_device():
    cfg = llama.PRESETS["debug"]
    opt = ts.default_optimizer(lr=1e-2, warmup_steps=1, total_steps=50)
    mesh = make_mesh(MeshConfig(), jax.devices()[:1])
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh, opt)
    step = ts.make_train_step(cfg, opt)
    tokens = jax.random.randint(jax.random.key(1), (4, 33), 0, cfg.vocab_size,
                                dtype=jnp.int32)
    batch = {"tokens": tokens}
    losses = []
    for _ in range(10):
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], f"loss did not decrease: {losses}"


def test_sharded_step_matches_single_device():
    """The 8-way (dp2,fsdp2,tp2) step computes the same loss as 1 device."""
    cfg = llama.PRESETS["debug"]
    opt = ts.default_optimizer(lr=1e-3, warmup_steps=1, total_steps=50)
    tokens = jax.random.randint(jax.random.key(1), (8, 33), 0, cfg.vocab_size,
                                dtype=jnp.int32)

    def run(mesh):
        params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh, opt)
        step = ts.make_train_step(cfg, opt)
        batch = ts.shard_batch({"tokens": tokens}, mesh)
        losses = []
        for _ in range(3):
            params, opt_state, m = step(params, opt_state, batch)
            losses.append(float(m["loss"]))
        return losses

    single = run(make_mesh(MeshConfig(), jax.devices()[:1]))
    sharded = run(make_mesh(MeshConfig(dp=2, fsdp=2, tp=2), jax.devices()))
    np.testing.assert_allclose(single, sharded, rtol=2e-2)


def test_init_sharded_state_shards_the_optimizer_state_too():
    """Adam's moments are zeros_like: they depend on no input, so no
    sharding propagates to them, and left unpinned they all land on the
    first device (seen on a four-chip v5e host: 4.6 GiB on chip 0, 0.5 on
    the others). Every matrix of the state lies on every device."""
    cfg = llama.PRESETS["debug"]
    mesh = make_mesh(MeshConfig(fsdp=2, tp=2), jax.devices()[:4])
    params, opt_state = ts.init_sharded_state(
        jax.random.key(0), cfg, mesh, ts.default_optimizer())
    flat = jax.tree_util.tree_flatten_with_path((params, opt_state))[0]
    matrices = [(path, x) for path, x in flat
                if x.ndim >= 2 and "norm" not in jax.tree_util.keystr(path)]
    assert len(matrices) == 3 * 9  # params, mu, nu
    for path, x in matrices:
        devices = {s.device for s in x.addressable_shards}
        assert len(devices) == 4, jax.tree_util.keystr(path)
        assert x.addressable_shards[0].data.size == x.size // 4


def test_sharding_rules_cover_all_params():
    from jax.sharding import PartitionSpec as P

    cfg = llama.PRESETS["debug"]
    params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
    rules = llama.sharding_rules()
    specs = rules.tree_specs(params)
    # Every matrix >= 2D must be sharded on at least one axis.
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    for path, spec in flat:
        keys = [p.key for p in path]
        if any("norm" in k for k in keys):
            continue  # norm scales are vectors (stacked: [L, D]); replicated
        leaf = params
        for k in keys:
            leaf = leaf[k]
        if len(leaf.shape) >= 2:
            assert spec != P(), f"unsharded matrix at {path}"


def test_graft_entry_single_device():
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert bool(jnp.isfinite(out).all())


def test_graft_dryrun_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)
