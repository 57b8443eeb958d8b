"""Kimi Linear's two mixers on the patterned training path at a tiny size on
the CPU: the chunked delta rule (``ops/kda.py``) against the recurrence a
token at a time, forward and gradients; the flash kernels at two head widths
(interpreted) against the explicit softmax; the program (``models/moe.py``
with ``kda`` and ``mla`` layers, a leading dense layer, 4 of 16 experts held)
against the benchmark's plain reference
(``benchmark/families/moonshot_kimi_linear.py``, which imports nothing of
``ray_tpu``) on seeded weights; the chip's share against the uncut layer;
the step under a mesh; the plan a step notes; and who refuses the two kinds.
The Pallas kernel pair for everything of a chunk that does not read the
state (``ops/pallas/kda_insides.py``, interpreted) against ``_insides``' XLA
form at 128-wide heads, its routines one by one, and when a step takes it;
the kernels for a layer's
elementwise chains outside the recurrence (``ops/pallas/kda_mix.py``,
interpreted) against the XLA form ``mixers.kda_half`` keeps, and when a
layer takes them."""

import dataclasses
import functools
import hashlib
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, llama, mixers, moe
from ray_tpu.ops import kda
from ray_tpu.ops.attention import mha
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas import flash
from ray_tpu.ops.ssm import causal_conv
from ray_tpu.parallel import train_step as ts
from ray_tpu.util import flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import spec  # noqa: E402

# config.json's keys at a tiny size: eight published layers, KDA KDA KDA MLA
# twice, of which the leading dense one and the second period run
TINY = {
    "first_k_dense_replace": 1, "head_dim": 8, "hidden_size": 32,
    "intermediate_size": 64, "kv_lora_rank": 16,
    "linear_attn_config": {
        "full_attn_layers": [4, 8], "head_dim": 16,
        "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
        "short_conv_kernel_size": 4},
    "mla_use_nope": True, "moe_intermediate_size": 24, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 4,
    "num_experts": 4, "num_experts_published": 16, "num_experts_per_token": 2,
    "num_hidden_layers": 8, "layers_run": [1, 5, 6, 7, 8],
    "num_key_value_heads": 4, "num_shared_experts": 1, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "v_head_dim": 16, "vocab_size": 96}
CFG_FILE = {"config": TINY,
            "assumed": {"capacity_factor": 1.25, "balance_coefficient": 0.0}}
SEQ, DEPTH = 40, 5
TOKENS = jax.random.randint(jax.random.key(1), (2, SEQ + 1), 0, 96)


@pytest.fixture(scope="module")
def family():
    return spec.load_family("moonshot_kimi_linear")


def _cfg(family, attn_impl="flash", depth=DEPTH):
    cfg = family.program_config(CFG_FILE, depth, max_seq_len=SEQ,
                                attn_impl=attn_impl, loss_chunk=8)
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               compute_dtype=jnp.float32)


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
    with kda.noting_plan(noted):
        jaxpr = jax.make_jaxpr(lambda *a: kda.kda_chunked(*a, **kwargs))(*shapes)
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
    with mesh_scope(mesh), kda.noting_plan(noted):
        jaxpr = str(jax.make_jaxpr(
            lambda x, l: mixers.kda_half(cfg, x, l))(*_a_layer(cfg)))
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
        with kda.noting_plan(noted):
            out = jax.jit(jax.value_and_grad(lambda x, l: (mixers.kda_half(
                cfg, x, l) ** 2).sum(), (0, 1)))(x, layer)
        return noted["mix"], out

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
    one, two = [], []
    with flash.noting_plans(one):
        names = _names(jax.grad(loss, (0, 1, 2)), q, q, q)
    with flash.noting_plans(two):
        names2 = _names(jax.grad(loss, (0, 1, 2)), q, q, v)
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
    with jax.default_matmul_precision("highest"):
        loss, stats = moe.loss_and_stats(params, {"tokens": TOKENS}, cfg)
        grads = jax.grad(lambda p: moe.lm_loss(p, {"tokens": TOKENS}, cfg))(
            params)
    ref = family.loss(params, TOKENS, CFG_FILE)
    ref_grads = jax.grad(
        lambda p: family.loss(p, TOKENS, CFG_FILE)["loss"])(params)
    return dict(cfg=cfg, params=params, loss=loss, stats=stats, grads=grads,
                ref=ref, ref_grads=ref_grads)


def test_the_loss_agrees_with_the_reference(both):
    # both float32 at highest: what differs is the order of the sums
    assert float(both["loss"]) == pytest.approx(float(both["ref"]["loss"]),
                                                rel=2e-6)
    assert float(both["ref"]["loss"]) == float(both["ref"]["ce"])  # coefficient 0


def test_logits_agree_with_the_reference(family, both):
    with jax.default_matmul_precision("highest"):
        got = moe.forward(both["params"], TOKENS[:, :-1], dataclasses.replace(
            both["cfg"], capacity_factor=1e3))
    want = family.logits(both["params"], TOKENS[:, :-1], CFG_FILE)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(jnp.abs(want).max())


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


@pytest.mark.parametrize("width,seq,depth,impl,mix", [
    (16, SEQ, DEPTH, "xla", "xla"),
    # a chunk of 64 and a head of whole lanes
    (128, 64, 2, "pallas_insides", "pallas")])
def test_the_recorder_carries_the_kda_plan(family, width, seq, depth, impl,
                                           mix):
    from ray_tpu.train.driver import StepDriver

    cfg = dataclasses.replace(_cfg(family, depth=depth), max_seq_len=seq,
                              kda_head_dim=width)
    opt = ts.default_optimizer(total_steps=100)
    params = family.init_params(jax.random.key(3), cfg)
    # one step a launch, as the cell runs: two launches
    driver = StepDriver(cfg, opt, steps_per_launch=1)
    tokens = jax.random.randint(jax.random.key(1), (2, seq + 1), 0, 96)
    batches = [{"tokens": np.asarray(tokens)} for _ in range(2)]
    driver.run(params, jax.jit(opt.init)(params), batches)
    rec = driver.recorder
    assert driver.launches == 2
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
