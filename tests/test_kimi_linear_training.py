"""Kimi Linear's two mixers at the kernels' and a layer's level, at a tiny
size on the CPU: the chunked delta rule (``ops/kda.py``) against the
recurrence a token at a time, forward and gradients; the flash kernels at
two head widths (interpreted) against the explicit softmax.
The Pallas kernel pair for everything of a chunk that does not read the
state (``ops/pallas/kda_insides.py``, interpreted) against ``_insides``' XLA
form at 128-wide heads, its routines one by one, and when a step takes it;
the kernels for a layer's
elementwise chains outside the recurrence (``ops/pallas/kda_mix.py``,
interpreted) against the XLA form ``mixers.kda_half`` keeps, and when a
layer takes them.

The program against the benchmark's plain reference, the step under a mesh
and the plan a step notes are ``test_kimi_linear_model.py``'s: they share
one module fixture and these share none, and the file was 811 s alone."""

import dataclasses
import functools
import hashlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, mixers
from ray_tpu.ops import kda
from ray_tpu.ops.attention import mha
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas import flash
from ray_tpu.ops.ssm import causal_conv
from ray_tpu.util import plans

from _kimi import REPO, SEQ, _cfg, family  # noqa: F401 (a fixture)

# ---- (a) the chunked delta rule against the recurrence ----------------------------

def _kda_inputs(seed, b, s, h, dk, dv, decay):
    ks = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(key, (b, s, h, dk)) for key in ks[:2])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, dv))
    # a log-decay a channel spread over two orders of magnitude round ``decay``
    g = -decay * jnp.exp(2 * jax.random.normal(ks[3], (b, s, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, s, h, dv))


@functools.lru_cache(maxsize=None)
def _both_forms(chunk, sub):
    """The two forms and their gradients, jitted once a (chunk, sub-block):
    the three decays of a shape share a compile (the interpreted kernel's
    is most of a case's time)."""
    def chunked(*a):
        return kda.kda_chunked(*a, chunk=chunk, sub_block=sub)

    def recurrent(*a):
        return kda.kda_recurrent(*a)[0]

    def grad(fn):
        return jax.jit(jax.grad(lambda w, *a: (fn(*a) * w).sum(), range(1, 6)))

    return jax.jit(chunked), grad(chunked), jax.jit(recurrent), grad(recurrent)


# decays near 1 (alpha = exp(-1e-3)), middling, and near 0 (alpha down to
# exp(-30 e^2..): G of minus thousands inside a chunk, the exp(-G) case);
# the limits follow float32's own loss in a cumulative sum of that size
@pytest.mark.parametrize("decay,tol", [(1e-3, 5e-6), (0.3, 5e-5), (30.0, 2e-3)])
@pytest.mark.parametrize("chunk,sub,seq,dk,dv", [
    (16, 4, 37, 16, 24),      # a sequence of no whole number of chunks
    (32, 16, 64, 32, 16),
    (64, 16, 100, 16, 16),    # the shipped chunk and sub-block
    (16, 8, 16 * 32, 8, 8),   # four segments of 8 chunks
    (64, 16, 100, 128, 16)])  # a wide key, a value of no whole lanes
def test_the_chunked_form_is_the_recurrence(decay, tol, chunk, sub, seq, dk, dv):
    args, w = _kda_inputs(1, 2, seq, 3, dk, dv, decay)
    p = kda.plan(seq, 3, dk, dv, 2, chunk, sub)
    assert p["segments"] == 1 + 3 * (seq > 500)
    assert p["impl"] == "xla"   # (the kernel's shapes: section (a') below)
    chunked, chunked_grad, recurrent, recurrent_grad = _both_forms(chunk, sub)
    with jax.default_matmul_precision("highest"):
        got, want = chunked(*args), recurrent(*args)
        grads, wants = chunked_grad(w, *args), recurrent_grad(w, *args)
    # (the query is scaled by dk ** -0.5: a wide head's outputs are smaller)
    assert float(jnp.abs(want).max()) > (0.1 if dk < 128 else 0.04)
    assert float(jnp.abs(got - want).max()) < tol
    for name, a, b in zip("q k v g beta".split(), grads, wants):
        assert bool(jnp.isfinite(a).all()), name
        scale = float(jnp.abs(b).max())
        assert float(jnp.abs(a - b).max()) < 3 * tol * max(scale, 1.0), name


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_a_fast_channel_underflows_to_a_true_zero_and_never_overflows(form):
    """Every channel forgets by e^-60 a token: ``exp(-G)`` over a chunk
    would be e^3840. The state carries nothing, so a token's output is its
    own ``beta (q . k) v``; through the kernel for a chunk's insides
    (``_INSIDES``' shape), ``T`` is ``Diag(beta)`` and ``A_qk`` a token's own
    ``q . k``."""
    if form == "kernel":
        q, k, v, _, beta = _insides_inputs(2, 1.0, shape=_ONE_CHUNK)
        g = jnp.full(q.shape, -60.0)
        W, U, Aqk, Qg, Kend, gend = _INSIDES["kernel"][0](q, k, v, g, beta)
        assert float(jnp.abs(U - beta[..., None] * v).max()) < 1e-6
        own = jnp.einsum("...cd,...cd->...c", q, k)
        assert float(jnp.abs(Aqk - own[..., None] * jnp.eye(64)).max()) < 1e-7
        assert float(jnp.abs(Kend[..., -1, :] - k[..., -1, :]).max()) == 0
        # e^-120, two rows from the chunk's end, is below float32's least
        assert not bool(gend.any()) and not bool(Kend[..., :-2, :].any())
        grads = _INSIDES["kernel"][1](
            [jnp.ones_like(a) for a in (W, U, Aqk, Qg, Kend, gend)],
            q, k, v, g, beta)
        assert all(bool(jnp.isfinite(a).all()) for a in grads)
        return
    (q, k, v, _, beta), _ = _kda_inputs(2, 1, 70, 2, 16, 16, 1.0)
    g = jnp.full(q.shape, -60.0)
    got = kda.kda_chunked(q, k, v, g, beta)
    own = (beta * jnp.einsum("bshd,bshd->bsh", q, k))[..., None] * v
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - own).max()) < 1e-6
    grads = jax.grad(lambda g: kda.kda_chunked(q, k, v, g, beta).sum())(g)
    assert bool(jnp.isfinite(grads).all())


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_the_inverse_where_keys_repeat_and_nothing_decays(form):
    """The worst case of the Neumann product inside a 16-row block: every
    earlier key is this one, beta 1, no decay, so ``A`` is all ones below the
    diagonal and its powers reach C(15, 7) before they cancel. The inverse
    is the bidiagonal [1, -1]; a tenth less, it is still one to 1e-4.
    Through the kernel for a chunk's insides (which substitutes where the
    XLA form multiplies powers) ``U = T V`` reads the same inverse: a
    token's value less the one before's, and ``(I + 0.9 L) U = 0.9 V``."""
    if form == "kernel":
        q, k, v, _, _ = _insides_inputs(6, 1.0, shape=_ONE_CHUNK)
        k = jnp.broadcast_to(k[..., :1, :], k.shape)   # unit keys, all one
        g = jnp.zeros(q.shape)
        U = _INSIDES["kernel"][0](q, k, v, g, jnp.ones(q.shape[:-1]))[1]
        want = v - jnp.pad(v, [(0, 0)] * 3 + [(1, 0), (0, 0)])[..., :-1, :]
        assert float(jnp.abs(U - want).max()) < 1e-5
        U = _INSIDES["kernel"][0](q, k, v, g, jnp.full(q.shape[:-1], 0.9))[1]
        back = U + 0.9 * jnp.einsum(
            "ij,...jv->...iv", jnp.tril(jnp.ones((64, 64)), -1), U,
            precision="highest")
        assert float(jnp.abs(back - 0.9 * v).max()) < 2e-4
        return
    ones = jnp.tril(jnp.ones((2, 64, 64)), -1)
    got = kda._unit_lower_inverse(ones, 16)
    assert float(jnp.abs(got[0] - (jnp.eye(64) - jnp.eye(64, k=-1))).max()) < 1e-5
    got = kda._unit_lower_inverse(0.9 * ones, 16)
    back = jnp.einsum("bij,bjk->bik", got, jnp.eye(64) + 0.9 * ones,
                      precision="highest")
    assert float(jnp.abs(back - jnp.eye(64)).max()) < 2e-4
    # and its own backward is the inverse's: against JAX's through a solve
    a = 0.3 * jnp.tril(jax.random.normal(jax.random.key(0), (2, 64, 64)), -1)
    w = jax.random.normal(jax.random.key(1), (2, 64, 64))
    mine = jax.grad(lambda a: (kda._unit_lower_inverse(a, 16) * w).sum())(a)
    want = jax.grad(lambda a: (jnp.linalg.inv(
        jnp.eye(64) + jnp.tril(a, -1)) * w).sum())(a)
    assert float(jnp.abs(mine - want).max()) < 1e-3 * float(jnp.abs(want).max())


# ---- (a') the kernel pair for everything of a chunk that reads no state ------------

_INSIDES_SHAPE = (1, 2, 2, 64, 128)   # batch, heads, chunks, a chunk, a head
# (an interpreted kernel's compile is most of a case and grows with the
# chunks a trip of its loop works: two for the float32 comparison, whose
# inverses lie side by side in one tile; one for every other case)
_ONE_CHUNK = (1, 2, 1, 64, 128)


def _insides_inputs(seed, decay, dtype=jnp.float32, shape=_INSIDES_SHAPE):
    """``kda._insides``' five arguments [b, h, n, C, ..], as ``_kda_inputs``
    draws them, ``v`` as wide as ``q``."""
    b, h, n, C, dk = shape
    args, _ = _kda_inputs(seed, b, n * C, h, dk, dk, decay)
    q, k, v, g, beta = (jnp.moveaxis(a, 2, 1).reshape(b, h, n, C, *a.shape[3:])
                        for a in args)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _insides_form(impl):
    """(the six results, the five cotangents of their sums weighed by ``ws``)
    of ``kda._insides`` in one form, each jitted once: the tests share a
    shape, so a compile (the interpreted kernels' is most of a case)."""
    fn = lambda *a: kda._insides(*a, 16, impl)

    def loss(ws, *a):
        return sum((o.astype(jnp.float32) * w).sum() for o, w in zip(fn(*a), ws))

    return jax.jit(fn), jax.jit(jax.grad(loss, range(1, 6)))


_INSIDES = {"kernel": _insides_form("pallas_insides"),
            "xla": _insides_form("xla")}
_INSIDES_NAMES = "W U A_qk Qg Kend gend".split()


# float32: what differs is the order of the sums, and of the running sum of
# the gates first (the kernel's is a product with a triangle of ones), whose
# rounding at G of minus thousands an exponent's difference carries: the
# limits are ``test_the_chunked_form_is_the_recurrence``'s but the last: at
# decay 30 ``G`` reaches -145,000, whose last place is 0.016, and either
# form's ``Kend`` stands 1e-3 of its size from a float64 one. bfloat16: the
# kernel rounds ``beta k`` where the XLA form rounds ``k`` and multiplies by
# beta after the product, and keeps float32 cotangents where JAX rounds them
# to the operands' dtype: one limit at every decay. (A case a dtype and the
# decays inside it: an interpreted kernel's compile is most of a case, 27 s
# at two chunks and 16 s at one, and cases that tier-1's workers take apart
# would each pay it.)
@pytest.mark.parametrize("dtype,shape,tols", [
    (jnp.float32, _INSIDES_SHAPE, {1e-3: 5e-6, 0.3: 5e-5, 30.0: 4e-3}),
    (jnp.bfloat16, _ONE_CHUNK, {1e-3: 2e-2, 0.3: 2e-2, 30.0: 2e-2})])
def test_the_insides_kernel_is_the_form_it_replaces(dtype, shape, tols):
    """All six results and, through ``jax.grad``, all five cotangents against
    ``_insides``' XLA form, at the three decays of
    ``test_the_chunked_form_is_the_recurrence``."""
    for decay, tol in tols.items():
        args = _insides_inputs(3, decay, dtype, shape)
        with jax.default_matmul_precision("highest"):
            want = _INSIDES["xla"][0](*args)
            ws = [jax.random.normal(jax.random.key(i), o.shape)
                  for i, o in enumerate(want)]
            got = _INSIDES["kernel"][0](*args)
            grads = _INSIDES["kernel"][1](ws, *args)
            wants = _INSIDES["xla"][1](ws, *args)
        for name, a, b in zip(_INSIDES_NAMES, got, want):
            assert (a.dtype, a.shape) == (b.dtype, b.shape), name
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert bool(jnp.isfinite(a).all()), (name, decay)
            assert float(jnp.abs(a - b).max()) <= tol * max(
                float(jnp.abs(b).max()), 1e-30), (name, decay)
        assert not bool(jnp.triu(got[2], 1).any())  # A_qk above the diagonal
        for name, a, b in zip("q k v g beta".split(), grads, wants):
            assert (a.dtype, a.shape) == (b.dtype, b.shape), name
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            assert float(jnp.abs(b).max()) > 0.01, (name, decay)
            assert float(jnp.abs(a - b).max()) < 3 * tol * max(
                float(jnp.abs(b).max()), 1.0), (name, decay)


def _routines():
    from ray_tpu.ops.pallas import kda_insides

    return kda_insides


@pytest.mark.parametrize("sub,sums", [(8, 5), (16, 17)])
def test_every_pair_of_a_block_rides_in_one_sum(sub, sums):
    """``_packed``: every (vreg of eight rows, column) pair of a diagonal
    block with a row at or below the diagonal goes through exactly one sum
    along the lanes, alone or in the dead rows of a partner whose live rows
    fill them: 17 sums a 16-row block, where a vreg a column takes 24."""
    pairs = _routines()._packed(sub)
    assert len(pairs) == sums
    seen = []
    for ro, i, a, partner in pairs:
        assert a == max(i - ro, 0) and i < ro + 8
        seen.append((ro, i))
        if partner is not None:
            assert a and max(partner[1] - partner[0], 0) == 8 - a
            seen.append(partner)
    assert sorted(seen) == [(ro, i) for ro in range(0, sub, 8)
                            for i in range(ro + 8)]


@pytest.mark.parametrize("reverse", [False, True])
def test_the_running_sum_is_float32s_own(reverse):
    """``_running_sum``: three bfloat16 pieces of the gates against a triangle
    of ones carry float32's 24 bits. At decay 30 (``G`` to minus tens of
    thousands) it stands from a float64 sum within two of float32's last
    places, as ``jnp.cumsum`` does."""
    g = _insides_inputs(7, 30.0, shape=_ONE_CHUNK)[3][0, 0, 0]   # [64, 128]
    g64 = np.asarray(g, np.float64)
    want = np.cumsum(g64[::-1], 0)[::-1] if reverse else np.cumsum(g64, 0)
    assert np.abs(want).max() > 1e4
    got = np.asarray(_routines()._running_sum(g, reverse), np.float64)
    assert (np.abs(got - want) <= 2 ** -22 * np.abs(want)).all()


@pytest.mark.parametrize("block", ["drawn", "ones"])
def test_a_blocks_inverse_by_columns_is_the_inverse(block):
    """``_block_inverse`` from the columns as the sums leave them (eight
    rows of a column spread over the lanes; rows not below the column hold
    anything) against numpy's inverse, on a drawn block and on the Neumann
    product's worst, all ones: the substitution is exact there."""
    rng = np.random.default_rng(0)
    D = 0.3 * rng.normal(size=(16, 16)) if block == "drawn" else np.ones((16, 16))
    D = np.tril(D, -1).astype(np.float32)
    held = D + np.triu(rng.normal(size=D.shape)).astype(np.float32)
    cols = {(ro, i): jnp.broadcast_to(held[ro:ro + 8, i:i + 1], (8, 64))
            for ro in (0, 8) for i in range(ro + 8)}
    got = np.asarray(_routines()._block_inverse(cols, 16, 64))
    want = np.linalg.inv(np.eye(16) + D.astype(np.float64))
    assert np.abs(got[:, :16] - want).max() < 2e-6 * np.abs(want).max()
    assert not got[:, 16:].any()


def test_a_trip_of_a_kernels_loop_is_whole_tiles_of_the_inverses():
    """Whatever the chunks a segment: a grid step's chunks divide them, the
    inverses lie one or two a tile, and a trip of either loop is whole
    tiles that divide the step; the cell's segment of eight is two chunks a
    trip forward and four backward."""
    ki = _routines()
    for n in range(1, 41):
        chunks = ki._steps_chunks(n)
        side = ki._side(chunks)
        assert n % chunks == 0 and chunks <= 8 and chunks % side == 0
        for most in (ki._TOGETHER, ki._TOGETHER_BACK):
            together = ki._together(chunks, most)
            assert chunks % together == 0 and together % side == 0
            assert together <= max(most, side)
    assert [ki._steps_chunks(8), ki._side(8), ki._together(8, ki._TOGETHER),
            ki._together(8, ki._TOGETHER_BACK)] == [8, 2, 2, 4]


@pytest.mark.parametrize("args,kwargs,impl,mix", [
    ((16384, 32, 128, 128), {}, "pallas_insides", "pallas"),  # the cell's shape
    # two lane tiles a head; the values' 16 (and 64) are no whole lanes
    ((100, 3, 256, 16), {"batch": 2}, "xla", "xla"),
    ((16384, 32, 128, 64), {}, "xla", "xla"),
    ((100, 3, 256, 128), {"batch": 2}, "pallas_insides", "pallas"),
    # a shrunk chunk (48); the chains outside take any length
    ((37, 3, 128, 128), {}, "xla", "pallas"),
    ((16384, 32, 64, 64), {}, "xla", "xla"),                 # half a lane tile
    ((16384, 32, 192, 128), {}, "xla", "xla"),
    ((16384, 32, 128, 128), {"chunk": 32}, "xla", "pallas"),
    ((16384, 32, 128, 128), {"sub_block": 8}, "xla", "pallas"),
    ((16384, 32, 128, 128), {"impl": "xla"}, "xla", "xla"),  # asked for
    ((16384, 32, 128, 128), {"impl": "flash"}, "pallas_insides", "pallas"),
    # the rows before a block come in as one block of eight
    ((16384, 32, 128, 128), {"conv_taps": 9}, "pallas_insides", "pallas"),
    ((16384, 32, 128, 128), {"conv_taps": 10}, "pallas_insides", "xla")])
def test_when_the_plan_takes_the_kernel(args, kwargs, impl, mix):
    assert kda.plan(*args, **kwargs)["impl"] == impl
    assert kda.plan(*args, **kwargs)["mix"] == mix
    # and what a call is traced with is the plan's
    noted = {}
    b, (s, h, dk, dv) = kwargs.get("batch", 1), args
    shapes = [jax.ShapeDtypeStruct((b, s, h, w), jnp.bfloat16)
              for w in (dk, dk, dv)] + [
        jax.ShapeDtypeStruct((b, s, h, dk), jnp.float32),
        jax.ShapeDtypeStruct((b, s, h), jnp.float32)]
    kwargs = {k: v for k, v in kwargs.items() if k != "batch"}
    with plans.noting(noted):
        jaxpr = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, **kwargs))(*shapes)
    noted = noted["kda_plan"]
    assert (noted["impl"], noted["mix"]) == (impl, mix)
    assert ("kda_insides_fwd" in str(jaxpr)) == (impl == "pallas_insides")


def _a_layer(cfg):
    """One ``kda`` layer's leaves and its input at 64 tokens, as shapes."""
    layer = jax.eval_shape(lambda r: jax.tree.map(
        lambda a: a[0], mixers.init_kda(r, cfg, 1)), jax.random.key(0))
    layer["attn_norm"] = jax.ShapeDtypeStruct((cfg.d_model,), jnp.float32)
    return jax.ShapeDtypeStruct((2, 64, cfg.d_model), cfg.compute_dtype), layer


@pytest.mark.parametrize("devices,impl,mix", [(1, "pallas_insides", "pallas"),
                                              (2, "xla", "xla")])
def test_a_mesh_of_several_chips_keeps_the_xla_form(family, devices, impl, mix):
    """Mosaic's calls are not partitioned: where the ambient mesh has more
    than one device a ``kda`` layer is the XLA form GSPMD splits, at any
    width, the recurrence and the chains round it; alone on its chip it
    takes the kernels."""
    from ray_tpu.parallel.context import mesh_scope
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = dataclasses.replace(_cfg(family), kda_heads=1, kda_head_dim=128)
    mesh = None if devices == 1 else make_mesh(MeshConfig(dp=devices),
                                               jax.devices()[:devices])
    noted = {}
    with mesh_scope(mesh), plans.noting(noted):
        jaxpr = str(jax.make_jaxpr(
            lambda x, l: mixers.kda_half(cfg, x, l))(*_a_layer(cfg)))
    noted = noted["kda_plan"]
    assert (noted["impl"], noted["mix"]) == (impl, mix)
    for call in ("kda_mix_conv_unit_fwd", "kda_mix_conv_fwd",
                 "kda_mix_decay_fwd", "kda_mix_norm_gate_fwd"):
        assert (call in jaxpr) == (mix == "pallas"), call


@functools.lru_cache(maxsize=None)
def _who_loads_what():
    """What a fresh process has loaded of ``ray_tpu.ops.pallas`` after it
    traced Mistral's step, then the recurrence, then a ``kda`` layer."""
    code = """if True:
        import sys
        import jax, jax.numpy as jnp
        import ray_tpu.ops.kda as kda
        from ray_tpu.models import llama
        from ray_tpu.parallel import train_step as ts
        import dataclasses
        cfg = dataclasses.replace(llama.PRESETS["debug"], attn_impl="flash")
        opt = ts.default_optimizer(total_steps=100)
        params = jax.eval_shape(lambda r: llama.init_params(r, cfg), jax.random.key(0))
        step = ts.make_multi_step(cfg, opt, 2)
        jaxpr = jax.make_jaxpr(step._jit)(
            params, jax.eval_shape(opt.init, params),
            {"tokens": jax.ShapeDtypeStruct((2, 1, 65), jnp.int32)})
        assert "flash_fwd" in str(jaxpr)
        loaded = lambda: sorted(m.rsplit(".", 1)[1] for m in sys.modules
                                if m.startswith("ray_tpu.ops.pallas.kda_"))
        print("mistral", *loaded())
        a = jax.ShapeDtypeStruct((1, 64, 1, 128), jnp.float32)
        jax.eval_shape(kda.kda_chunked, a, a, a, a,
                       jax.ShapeDtypeStruct((1, 64, 1), jnp.float32))
        print("recurrence", *loaded())
        from ray_tpu.models import mixers, moe
        cfg = moe.MoEConfig(d_model=32, kda_heads=1, kda_head_dim=128,
                            attn_impl="flash")
        layer = jax.eval_shape(lambda r: jax.tree.map(
            lambda a: a[0], mixers.init_kda(r, cfg, 1)), jax.random.key(0))
        layer["attn_norm"] = jax.ShapeDtypeStruct((32,), jnp.float32)
        jax.eval_shape(lambda x, l: mixers.kda_half(cfg, x, l),
                       jax.ShapeDtypeStruct((1, 64, 32), cfg.compute_dtype), layer)
        print("layer", *loaded())
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    return {line.split()[0]: line.split()[1:]
            for line in done.stdout.splitlines()}


@pytest.mark.parametrize("module,first_after", [("kda_insides", "recurrence"),
                                                ("kda_mix", "layer")])
def test_who_has_no_kda_layer_never_loads_the_kernel(module, first_after):
    """A process that imports ``ray_tpu.ops.kda`` and traces a step with no
    ``kda`` layer (Mistral's, flash kernels and all) has not loaded either
    kernel module; tracing the recurrence loads its own, and a layer that
    takes the kernels round it theirs."""
    loaded = _who_loads_what()
    stages = ["mistral", "recurrence", "layer"]
    for stage in stages:
        assert (module in loaded[stage]) == (
            stages.index(stage) >= stages.index(first_after)), (stage, loaded)


def test_the_plan_of_the_cells_shape():
    p = kda.plan(16384, 32, 128, 128)
    assert p == {"chunk": 64, "sub_block": 16, "chunks": 256, "segments": 32,
                 "heads": 32, "d_k": 128, "d_v": 128,
                 "boundary_state_bytes": 32 * 32 * 128 * 128 * 4,
                 "impl": "pallas_insides", "mix": "pallas"}
    assert p["boundary_state_bytes"] == 67_108_864   # a state a segment
    short = kda.plan(37, 3, 16, 24, batch=2, chunk=16, sub_block=4)
    assert (short["chunk"], short["chunks"], short["segments"]) == (16, 3, 1)
    assert kda.plan(5, 1, 8, 8)["chunk"] == 16   # shrunk to whole sub-blocks


# ---- (a'') the kernels for a layer's chains outside the recurrence ----------------------
#
# 300 tokens are a row block of 256 and one of 44: the second block's first
# rows read the first's last, the first's last rows (backward) the second's
# cotangent, and the second is no whole block.

_MIX_SHAPE = (2, 300, 2, 128)   # batch, tokens, heads, a head's width
_MIX_TAPS = 4


def _conv_xla(p, taps, w, unit, scale):
    """``mixers.kda_half``'s XLA form of a projection's chain."""
    b, s, ch = p.shape
    y, _ = causal_conv(p, jnp.zeros((b, taps.shape[0] - 1, ch), p.dtype), taps,
                       0.0)
    y = jax.nn.silu(y).reshape(b, s, ch // w, w)
    if unit:
        y32 = y.astype(jnp.float32)
        y = (y32 * jax.lax.rsqrt(jnp.sum(y32 * y32, -1, keepdims=True)
                                 + mixers.L2_EPS)).astype(p.dtype)
    return (y * jnp.asarray(scale, p.dtype)).reshape(b, s, ch)


def _conv_kernel(p, taps, w, unit, scale):
    from ray_tpu.ops.pallas import kda_mix

    y = kda_mix.conv_silu_unit(p, taps, w, unit, scale, mixers.L2_EPS)
    return jnp.moveaxis(y, 1, 2).reshape(p.shape)      # it is heads first


def _gate_xla(o, weight, gate, bias, eps):
    b, s, ch = o.shape
    w = weight.shape[0]
    return (rmsnorm(o.reshape(b, s, ch // w, w), weight, eps)
            * jax.nn.sigmoid(gate + bias).reshape(b, s, ch // w, w)
            ).reshape(b, s, ch)


def _gate_kernel(o, weight, *a):
    from ray_tpu.ops.pallas import kda_mix

    b, s, _ = o.shape
    heads_first = jnp.moveaxis(o.reshape(b, s, -1, weight.shape[0]), 2, 1)
    return kda_mix.norm_gate(heads_first, weight, *a)


def _decay_xla(lin, bias, a_log, w):
    """``kda_half``'s XLA form of the log-decay a channel."""
    b, s, ch = lin.shape
    a = lin.astype(jnp.float32) + bias
    return (-jnp.exp(a_log)[:, None] * jax.nn.softplus(a).reshape(
        b, s, ch // w, w)).reshape(b, s, ch)


def _decay_kernel(lin, bias, a_log, w):
    from ray_tpu.ops.pallas import kda_mix

    g = kda_mix.decay(lin, bias, jnp.repeat(-jnp.exp(a_log), w), w)
    return jnp.moveaxis(g, 1, 2).reshape(lin.shape)    # it is heads first


@functools.lru_cache(maxsize=None)
def _pulled(fn, *static):
    """``fn``'s value and its cotangents' pull-back of one ``dy``, jitted
    once a (function, static arguments)."""
    def run(dy, *a):
        y, pull = jax.vjp(lambda *a: fn(*a, *static), *a)
        return (y, *pull(dy))

    return jax.jit(run)


def _mix_inputs(dtype, shape=_MIX_SHAPE):
    b, s, h, w = shape
    ks = jax.random.split(jax.random.key(7), 7)
    draw = lambda key, *dims: jax.random.normal(key, dims).astype(dtype)
    return dict(
        p=draw(ks[0], b, s, h * w), dy=draw(ks[1], b, s, h * w),
        taps=(jax.random.normal(ks[2], (_MIX_TAPS, h * w)) / 2).astype(dtype),
        weight=(1 + 0.3 * jax.random.normal(ks[3], (w,))).astype(dtype),
        gate=draw(ks[4], b, s, h * w), bias=draw(ks[5], h * w))


def _close(got, want, tol, names):
    for name, a, b in zip(names, got, want):
        assert (a.dtype, a.shape) == (b.dtype, b.shape), name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert float(jnp.abs(b).max()) > 0.01, name
        assert float(jnp.abs(a - b).max()) < tol * float(jnp.abs(b).max()), name


# bf16: the XLA form rounds at every step where the kernel rounds once
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("unit,scale", [(True, 128 ** -0.5), (False, 1.0)])
def test_the_convolutions_kernel_is_the_form_it_replaces(dtype, tol, unit,
                                                         scale):
    """The convolution, SiLU and a head's L2 norm, and the cotangents of the
    projection's result and of the taps, against ``causal_conv``,
    ``jax.nn.silu`` and the norm as ``kda_half`` writes them and ``jax.grad``
    through them."""
    i = _mix_inputs(dtype)
    got = _pulled(_conv_kernel, 128, unit, scale)(i["dy"], i["p"], i["taps"])
    want = _pulled(_conv_xla, 128, unit, scale)(i["dy"], i["p"], i["taps"])
    _close(got, want, tol, ("y", "dp", "dtaps"))


def test_q_and_k_are_one_traced_call(monkeypatch):
    """A head's scale is an operand, no part of a call's key: q (``dk **
    -0.5``) and k (1) trace the kernel's body once between them, v (no
    norm) once more."""
    from ray_tpu.ops.pallas import kda_mix

    traced = []
    body = kda_mix._conv_fwd_kernel
    monkeypatch.setattr(kda_mix, "_conv_fwd_kernel", lambda *a, **k: (
        traced.append(k["unit"]), body(*a, **k))[1])
    i = _mix_inputs(jnp.float32, (1, 96, 1, 128))   # no other test's shape
    q, k, v = (_conv_kernel(i["p"], i["taps"], 128, unit, scale)
               for unit, scale in ((True, 128 ** -0.5), (True, 1.0),
                                   (False, 1.0)))
    assert traced == [True, False]
    assert float(jnp.abs(q - k * 128 ** -0.5).max()) < 1e-7
    assert float(jnp.abs(k - v).max()) > 0.1


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 3e-2)])
def test_the_output_norms_kernel_is_the_form_it_replaces(dtype, tol):
    """``rmsnorm`` over a head's width times the gate's sigmoid, and the
    cotangents of the output, the norm's weight, the gate and its bias."""
    i = _mix_inputs(dtype)
    args = (i["dy"], i["p"], i["weight"], i["gate"], i["bias"])
    _close(_pulled(_gate_kernel, 1e-5)(*args), _pulled(_gate_xla, 1e-5)(*args),
           tol, ("y", "do", "dweight", "dgate", "dbias"))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6),
                                       (jnp.bfloat16, 1e-2)])
def test_the_decays_kernel_is_the_form_it_replaces(dtype, tol):
    """``-exp(A_log) * softplus(a + dt_bias)``, float32 whatever the
    product's dtype (both forms widen it first; the product's cotangent is
    rounded to its dtype once by both), and the cotangents of the product,
    the bias and ``A_log``."""
    i = _mix_inputs(dtype)
    ks = jax.random.split(jax.random.key(8), 2)
    args = (i["dy"].astype(jnp.float32), 3 * i["p"],
            jax.random.normal(ks[0], (i["p"].shape[-1],)),
            jax.random.normal(ks[1], (_MIX_SHAPE[2],)))
    _close(_pulled(_decay_kernel, 128)(*args), _pulled(_decay_xla, 128)(*args),
           tol, ("g", "dlin", "dbias", "dA_log"))


def test_a_blocks_first_rows_read_the_block_before_and_a_sequences_none():
    """One token of the first block's last row: the second block's first
    ``K - 1`` rows hold its taps' SiLU (zeros if the rows before a block were
    not brought in), and backward one cotangent on the second block's first
    row reaches the first block's last three. A sequence's own first rows
    see zeros before them, the batch's second sequence too, whose block
    before would be its own."""
    from ray_tpu.ops.pallas import kda_mix

    b, s, h, w = _MIX_SHAPE
    rows = kda_mix._tiles(s, h, w)[0]
    assert 0 < s - rows < rows                       # two blocks, one short
    i = _mix_inputs(jnp.float32)
    at = jnp.zeros((b, s, h * w)).at[:, rows - 1].set(1.0)
    run = _pulled(_conv_kernel, w, False, 1.0)
    y = run(at, at * i["p"], i["taps"])[0]
    hit = i["p"][:, rows - 1]
    for ahead in range(_MIX_TAPS):
        want = jax.nn.silu(i["taps"][_MIX_TAPS - 1 - ahead] * hit)
        assert float(jnp.abs(want).max()) > 0.1
        assert float(jnp.abs(y[:, rows - 1 + ahead] - want).max()) < 1e-6
    assert not bool(y[:, rows + _MIX_TAPS - 1:].any())
    # no token at all: silu'(0) = 1/2 on every row
    dp = run(jnp.roll(at, 1, axis=1), 0 * at, i["taps"])[1]
    for back in range(1, _MIX_TAPS):
        want = 0.5 * i["taps"][_MIX_TAPS - 1 - back]
        assert float(jnp.abs(dp[:, rows - back] - want).max()) < 1e-6, back
    y = _conv_kernel(i["p"], i["taps"], w, False, 1.0)
    for t in range(_MIX_TAPS - 1):
        want = jax.nn.silu(sum(i["taps"][_MIX_TAPS - 1 - j] * i["p"][:, t - j]
                               for j in range(t + 1)))
        assert float(jnp.abs(y[:, t] - want).max()) < 1e-6, t


@pytest.mark.parametrize("shape", [(1, 37, 10, 128),   # two slabs of lanes
                                   (1, 7, 1, 256)])    # under one row block
def test_the_kernels_at_other_shapes(shape):
    i = _mix_inputs(jnp.float32, shape)
    w = shape[-1]
    _close(_pulled(_conv_kernel, w, True, 0.5)(i["dy"], i["p"], i["taps"]),
           _pulled(_conv_xla, w, True, 0.5)(i["dy"], i["p"], i["taps"]),
           2e-6, ("y", "dp", "dtaps"))
    args = (i["dy"], i["p"], i["weight"], i["gate"], i["bias"])
    _close(_pulled(_gate_kernel, 1e-5)(*args), _pulled(_gate_xla, 1e-5)(*args),
           2e-6, ("y", "do", "dweight", "dgate", "dbias"))


def test_more_taps_than_the_kernel_reads_rows_back_are_refused():
    """``kda_half`` never asks (the plan's ``mix`` is ``"xla"`` there); a
    caller of its own is told."""
    from ray_tpu.ops.pallas import kda_mix

    with pytest.raises(ValueError, match="10 taps"):
        kda_mix.conv_silu_unit(jnp.zeros((1, 32, 128)), jnp.zeros((10, 128)),
                               128, False)


def _kda_half_digest(cfg, mesh=None):
    """The jaxpr of a ``kda`` layer and its gradient, addresses taken out."""
    from ray_tpu.parallel.context import mesh_scope

    with mesh_scope(mesh):
        text = str(jax.make_jaxpr(jax.grad(
            lambda x, l: llama.join(x, mixers.kda_half(cfg, x, l)).astype(
                jnp.float32).sum(),
            (0, 1)))(*_a_layer(cfg)))
    text = re.sub(r"\s+", " ", re.sub(r" at 0x[0-9a-f]+", "", text))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("change,devices,parent", [
    ({"attn_impl": "xla"}, 1, "f9f42ce4c3d976c4"),
    ({"kda_heads": 4, "kda_head_dim": 16}, 1, "68d9d4ab5ff54e5c"),
    ({}, 2, "f9f42ce4c3d976c4"),
    ({"kda_conv_taps": 10}, 1, "4539a7f0266813dc")])
def test_where_the_kernels_are_not_taken_the_layer_is_the_parents(
        family, change, devices, parent, monkeypatch):
    """With ``attn_impl="xla"``, a head of no whole lanes, a mesh of several
    chips or more taps than a halo block holds, ``kda_half`` and its
    gradient trace to the jaxpr of commit f1edc9a (PR 51's parent, where the
    four digests were taken with the function above at bf16, 2 heads x 128
    but where a case says otherwise); with none of them they do not. The
    last case is about the chains round the recurrence alone: a plan's
    ``impl`` is held to ``"xla"`` on both sides, because the kernel inside
    the recurrence at this shape was another at f1edc9a (PR 50's pair for
    the two decayed products; as the plan stood there the digest was
    c639d1f9653399b1)."""
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    if "kda_conv_taps" in change:
        whole = kda.plan
        monkeypatch.setattr(kda, "plan", lambda *a, **kw: {
            **whole(*a, **kw), "impl": "xla"})
    cfg = dataclasses.replace(_cfg(family), compute_dtype=jnp.bfloat16,
                              kda_heads=2, kda_head_dim=128)
    mesh = None if devices == 1 else make_mesh(MeshConfig(dp=devices),
                                               jax.devices()[:devices])
    assert _kda_half_digest(dataclasses.replace(cfg, **change), mesh) == parent
    if not change and devices == 1:
        return
    assert _kda_half_digest(cfg) not in (parent, "1a89903f0d0501d9")


def test_a_layer_with_the_kernels_is_the_layer_without(family):
    """``kda_half`` at the tiny family's sizes but a head of 128: the layer
    and every gradient with the kernels round the recurrence against
    ``attn_impl="xla"``, float32."""
    cfg = dataclasses.replace(_cfg(family), kda_heads=2, kda_head_dim=128)
    ks = jax.random.split(jax.random.key(5), 4)
    layer = jax.tree.map(lambda a: a[0], mixers.init_kda(ks[0], cfg, 1))
    layer["g_bias"] = 0.5 * jax.random.normal(ks[1], layer["g_bias"].shape)
    layer["attn_norm"] = 1 + 0.1 * jax.random.normal(ks[2], (cfg.d_model,))
    x = jax.random.normal(ks[3], (2, SEQ, cfg.d_model))

    def both(cfg):
        noted = {}
        with plans.noting(noted):
            out = jax.jit(jax.value_and_grad(lambda x, l: (mixers.kda_half(
                cfg, x, l) ** 2).sum(), (0, 1)))(x, layer)
        return noted["kda_plan"]["mix"], out

    (mix, (loss, (dx, dl))) = both(cfg)
    (xla, (want, (wx, wl))) = both(dataclasses.replace(cfg, attn_impl="xla"))
    assert (mix, xla) == ("pallas", "xla")
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    leaves = {"x": (dx, wx), **{k: (dl[k], wl[k]) for k in wl}}
    for name, (a, b) in leaves.items():
        assert float(jnp.abs(a - b).max()) < 2e-5 * max(
            float(jnp.abs(b).max()), 1e-3), name


# ---- (b) the flash kernels at two widths -----------------------------------------

@pytest.mark.parametrize("blocks", [None, (16, 16)])
def test_flash_at_two_widths_is_the_softmax(blocks):
    ks = jax.random.split(jax.random.key(0), 4)
    b, s, h, d, dv = 1, 40, 2, 24, 16
    q, k = (jax.random.normal(key, (b, s, h, d)) for key in ks[:2])
    v = jax.random.normal(ks[2], (b, s, h, dv))
    w = jax.random.normal(ks[3], (b, s, h, dv))
    how = {} if blocks is None else dict(block_q=blocks[0], block_k=blocks[1])
    got = flash.flash_attention(q, k, v, **how)
    assert got.shape == (b, s, h, dv)
    want = mha(q, k, v)
    assert float(jnp.abs(got - want).max()) < 2e-6
    grads = jax.grad(lambda *a: (flash.flash_attention(*a, **how) * w).sum(),
                     (0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: (mha(*a) * w).sum(), (0, 1, 2))(q, k, v)
    for a, b_ in zip(grads, wants):
        assert a.shape == b_.shape
        assert float(jnp.abs(a - b_).max()) < 1e-5


def _names(fn, *args):
    return [str(e.params.get("name")) for e in _pallas_calls(
        jax.make_jaxpr(fn)(*args).jaxpr)]


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_a_call_of_one_width_keeps_its_name_plan_and_record():
    """The second width shows in a call's name, its plan's VMEM count and
    its noted record only where the widths differ."""
    q = jnp.zeros((1, 64, 2, 32))
    v = jnp.zeros((1, 64, 2, 16))
    loss = lambda q, k, v: flash.flash_attention(q, k, v).sum()
    one, two = {}, {}
    with plans.noting(one):
        names = _names(jax.grad(loss, (0, 1, 2)), q, q, q)
    with plans.noting(two):
        names2 = _names(jax.grad(loss, (0, 1, 2)), q, q, v)
    one, two = one["flash_plans"], two["flash_plans"]
    assert sorted(set(names)) == sorted(
        f"flash_{kind}_bh2_q64_k64_d32_c1_w0" for kind in flash.KINDS)
    assert sorted(set(names2)) == sorted(
        f"flash_{kind}_bh2_q64_k64_d32v16_c1_w0" for kind in flash.KINDS)
    assert all("value_dim" not in p for p in one)
    assert [p["value_dim"] for p in two] == [16] * 3
    for kind in flash.KINDS:
        same = flash.plan(4096, 4096, 128, 2, True, kind)
        assert flash.plan(4096, 4096, 128, 2, True, kind, value_dim=128) == same
        # 192 is two lane tiles, the second half empty: VMEM is counted at 256
        wide = flash.plan(16384, 16384, 192, 2, True, kind, value_dim=128)
        assert wide.vmem_bytes == flash._vmem_bytes(
            kind, wide.block_q, wide.block_k, 256, 2, 128)
        assert wide.vmem_bytes <= flash._VMEM_BUDGET_BYTES
        assert (wide.block_q, wide.block_k) == (1024, 1024)
