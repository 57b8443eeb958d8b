"""``ops/pallas/hyper_mix.py``'s four calls (PR 57) against ``ops/hyper.py``'s
XLA form, interpreted on the CPU at small shapes: a ``d`` of two lane tiles,
a tile that divides the tokens, a stream of four rows and of one.

(a) each call's results; (b) every leaf's and the rows' gradient through
both mixes with a branch between them, and the planted fault of
``tests/benchmark/xing4_chip_check.py``; (c) which path runs, and what the
plan says of it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import hyper
from ray_tpu.ops.pallas import hyper_mix
from ray_tpu.parallel.context import mesh_scope
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.util import plans

D, B, S = 256, 2, 128          # 256 tokens: two tiles of 128, one of 256
RULE = dict(iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
F32 = jnp.float32


def _half(seed, n, dtype=F32):
    half = hyper.init(jax.random.key(seed), n, D, 1, dtype)
    half = jax.tree.map(lambda a: a[0], half)
    # a column at the clamp, and a g that is not all ones
    half["b"] = half["b"].at[2 * n].set(100.0)
    half["g"] = (1.0 + 0.1 * jax.random.normal(jax.random.key(seed + 1),
                                               (n * D,))).astype(dtype)
    return half


def _rows(seed, n, dtype=F32):
    return jax.random.normal(jax.random.key(seed), (n, B, S, D), F32
                             ).astype(dtype)


def _near(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) <= tol * scale, (
        float(np.abs(got - want).max()), scale)


def _coefficients(held, n):
    """A ``Held``'s lanes as ``hyper.coefficients`` lays them."""
    co = held.coef.reshape(B, S, -1)
    return (co[..., :n], co[..., n:2 * n],
            co[..., 2 * n:2 * n + n * n].reshape(B, S, n, n))


# ---- (a) the calls' results ----------------------------------------------------------

@pytest.mark.parametrize("n", [4, 1])
@pytest.mark.parametrize("tiles", [(256, 128), (128,)])
def test_the_forward_calls_are_the_xla_form(n, tiles, monkeypatch):
    monkeypatch.setattr(hyper_mix, "TILES", tiles)
    half, x = _half(3, n), _rows(4, n)
    y = jax.random.normal(jax.random.key(5), (B, S, D))
    want_h, want = hyper.mix_in(x, half, **RULE)
    pre, _, _ = hyper.coefficients(x, half, **RULE)
    h, held = hyper.mix_in(x, half, **RULE, impl="pallas")
    assert isinstance(held, hyper.Held) and held.tile == tiles[0]
    assert held.rows.shape == (n, B * S, D)
    got = _coefficients(held, n)
    for a, b in zip(got, (pre, want.post, want.res)):
        _near(a, b, 2e-6)
    assert not np.asarray(held.coef[:, n * n + 2 * n:]).any()
    _near(h, want_h, 2e-6)
    _near(hyper.mix_out(x, y, held), hyper.mix_out(x, y, want), 2e-6)


@pytest.mark.parametrize("n", [4, 1])
def test_the_backward_calls_are_the_xla_forms_pull_backs(n):
    """``mix_out``'s three cotangents and ``mix_in``'s (the rows' with what
    came in so far added, the leaves') against ``jax.vjp`` of the XLA form,
    each call alone."""
    half, x = _half(6, n), _rows(7, n)
    y = jax.random.normal(jax.random.key(8), (B, S, D))
    ks = jax.random.split(jax.random.key(9), 4)
    g = jax.random.normal(ks[0], x.shape)
    _, held = hyper.mix_in(x, half, **RULE, impl="pallas")
    _, mix = hyper.mix_in(x, half, **RULE)
    # mix_out: (rows, branch, coefficients)
    _, back = jax.vjp(lambda x, y, m: hyper.mix_out(x, y, m), x, y, mix)
    want_dx, want_dy, want_dmix = back(g)
    _, back = jax.vjp(lambda r, y, c: hyper_mix.mix_out(r, y, c, held.tile),
                      held.rows, y.reshape(-1, D), held.coef)
    dx, dy, dcoef = back(g.reshape(n, -1, D))
    _near(dx.reshape(x.shape), want_dx, 1e-5)
    _near(dy.reshape(y.shape), want_dy, 1e-5)
    got = _coefficients(held._replace(coef=dcoef), n)
    _near(got[1], want_dmix.post, 1e-5)
    _near(got[2], want_dmix.res, 1e-5)
    # mix_in: cotangents of h, of the coefficients and of the rows so far
    dh = jax.random.normal(ks[1], (B, S, D))
    dpost = jax.random.normal(ks[2], (B, S, n))
    dres = jax.random.normal(ks[3], (B, S, n, n))
    _, back = jax.vjp(lambda x, half: hyper.mix_in(x, half, **RULE), x, half)
    want_x, want_half = back((dh, hyper.Mix(dpost, dres)))

    def kernels(x, half):
        h, held = hyper.mix_in(x, half, **RULE, impl="pallas")
        return h, held.coef, held.rows

    _, back = jax.vjp(kernels, x, half)
    dcoef = jnp.zeros((B, S, hyper_mix.LANES)).at[..., n:2 * n].set(dpost).at[
        ..., 2 * n:2 * n + n * n].set(dres.reshape(B, S, -1))
    got_x, got_half = back((dh, dcoef.reshape(-1, hyper_mix.LANES),
                            g.reshape(n, -1, D)))
    _near(got_x, want_x + g, 1e-5)
    for name in hyper.LEAVES:
        _near(got_half[name], want_half[name], 2e-5)


# ---- (b) both mixes, a branch between them ----------------------------------------------

def _scalar(impl, dtype=F32, fault=False):
    def loss(x, half, wb, weight):
        h, mix = hyper.mix_in(x, half, **RULE, impl=impl)
        branch = jnp.tanh(h.astype(F32) @ wb).astype(dtype)
        out = hyper.mix_out(x, branch, mix)
        return jnp.mean(out.astype(F32) * weight)

    def run(*args):
        whole = hyper.sinkhorn
        if fault:   # xing4_chip_check.py's: H_res hands no cotangent back
            hyper.sinkhorn = lambda *a: jax.lax.stop_gradient(whole(*a))
        try:
            return jax.value_and_grad(loss, (0, 1))(*args)
        finally:
            hyper.sinkhorn = whole

    return run


def _distance(got, want):
    got, want = (np.asarray(a, np.float32).ravel() for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


@pytest.mark.parametrize("n,dtype,tol", [(4, F32, 2e-5), (1, F32, 2e-5),
                                         (4, jnp.bfloat16, 0.03)])
def test_every_gradient_through_both_mixes_is_the_xla_forms(n, dtype, tol):
    """float32: to rounding. bfloat16 (the cell's dtype): both forms against
    the float32 one, the kernels' no further from it than twice XLA's."""
    half, x = _half(11, n, dtype), _rows(12, n, dtype)
    wb = jax.random.normal(jax.random.key(13), (D, D)) / 16.0
    weight = jax.random.normal(jax.random.key(14), x.shape)
    up = lambda tree: jax.tree.map(lambda a: a.astype(F32), tree)  # noqa: E731
    want_loss, (want_x, want_half) = _scalar("xla")(up(x), up(half), wb, weight)
    loss, (dx, dhalf) = _scalar("pallas", dtype)(x, half, wb, weight)
    assert abs(float(loss) - float(want_loss)) <= tol * abs(float(want_loss))
    got = {"rows": dx, **dhalf}
    want = {"rows": want_x, **want_half}
    assert got["rows"].dtype == dtype and dhalf["phi"].dtype == dtype
    assert dhalf["b"].dtype == dhalf["alpha"].dtype == F32
    far = {name: _distance(got[name], want[name]) for name in want}
    assert max(far.values()) < tol, far
    if dtype != F32:
        _, (xla_x, xla_half) = _scalar("xla", dtype)(x, half, wb, weight)
        xla = {"rows": xla_x, **xla_half}
        assert all(far[k] < 2.0 * _distance(xla[k], want[k]) + 1e-3
                   for k in want), far


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_planted_fault_fails_it(impl):
    """``H_res`` under ``stop_gradient``: the sixteen entries of ``b`` that
    feed ``H_res`` alone read 1.0 in both paths, ``H_post``'s keep theirs."""
    n = 4
    half, x = _half(15, n), _rows(16, n)
    wb = jax.random.normal(jax.random.key(17), (D, D)) / 16.0
    weight = jax.random.normal(jax.random.key(18), x.shape)
    _, (_, want) = _scalar("xla")(x, half, wb, weight)
    _, (_, cut) = _scalar(impl, fault=True)(x, half, wb, weight)
    assert _distance(cut["b"][2 * n:], want["b"][2 * n:]) == 1.0
    assert _distance(cut["b"][n:2 * n], want["b"][n:2 * n]) < 1e-4
    assert _distance(cut["phi"], want["phi"]) > 0.05


# ---- (c) which path runs ------------------------------------------------------------------

@pytest.mark.parametrize("case,impl,d,tokens,devices,tile", [
    ("the cell's", "pallas", 256, 256, 1, 256),
    ("a mesh of one", "pallas", 256, 128, 1, 128),
    ("attn_impl xla", "xla", 256, 256, 0, None),
    ("a mesh of two", "pallas", 256, 256, 2, None),
    ("d 192", "pallas", 192, 256, 0, None),
    ("tokens no tile divides", "pallas", 256, 192, 0, None)])
def test_a_shape_the_kernels_refuse_takes_the_xla_path(case, impl, d, tokens,
                                                       devices, tile):
    """...and ``hyper_plan`` says which, with what each moves."""
    import contextlib

    n = 4
    half = jax.tree.map(lambda a: a[0], hyper.init(jax.random.key(0), n, d, 1,
                                                   jnp.bfloat16))
    x = jnp.ones((n, 1, tokens, d), jnp.bfloat16)
    scope = (mesh_scope(make_mesh(MeshConfig(fsdp=devices),
                                  jax.devices()[:devices]))
             if devices else contextlib.nullcontext())
    noted = {}
    with scope, plans.noting(noted):
        h, mix = jax.eval_shape(
            lambda x: hyper.mix_in(x, half, **RULE, impl=impl), x)
    plan = noted["hyper_plan"]
    assert isinstance(mix, hyper.Held if tile else hyper.Mix), case
    assert plan == hyper.plan(n, d, 2, 20, tile)
    assert (plan["impl"], plan["tile_tokens"]) == (
        "pallas" if tile else "xla", tile)
    assert (plan["stream_bytes_fwd"], plan["stream_bytes_bwd"]) == (
        14 * d * 2, 23 * d * 2)
    if tile:
        # the rows' cotangent through H_res written and read, the float32
        # coefficients a pass and the two small residuals
        assert plan["stream_bytes_moved_fwd"] == 14 * d * 2 + 2 * 512 + 100
        assert plan["stream_bytes_moved_bwd"] == 31 * d * 2 + 3 * 512 + 100
    else:
        assert plan["stream_bytes_moved_fwd"] is None \
            and plan["stream_bytes_moved_bwd"] is None
