"""The indexer loss's target as a kernel (``ops/pallas/index_target.py``, PR
65), in interpret mode on the CPU: the call against the XLA lines it stands
in for (``sparse_index.block_target``), ``index_loss`` and its three
gradients through both forms and against the form written out whole, and
the rule that picks the form, with what the plan says of it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from ray_tpu.ops import sparse_index
from ray_tpu.ops.pallas import index_target
from ray_tpu.parallel.context import mesh_scope
from ray_tpu.util import plans

D = 128


def _gap(a, b):
    """max |a - b| on the host."""
    return float(np.abs(np.asarray(a, np.float64)
                        - np.asarray(b, np.float64)).max())


@functools.partial(jax.jit, static_argnums=range(8))
def _block(h, hkv, rows, keys, first, dtype, b=2, seed=0):
    """A block of ``rows`` rows from position ``first`` against ``keys``
    keys: q, k as a normed, rotated head's (unit scale), a log-sum-exp near
    the logits', a choice of about half of each row's causal past, one row
    with nothing chosen and one with its own position alone."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, rows, h, D)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, keys, D)).astype(dtype)
    lse = 3.0 + jax.random.normal(ks[2], (b, h, rows))
    at = first + jnp.arange(rows)
    past = jnp.arange(keys)[None, :] <= at[:, None]
    sel = (jax.random.uniform(ks[3], (b, rows, keys)) < 0.5) & past[None]
    sel = sel.at[:, 3].set(False)
    sel = sel.at[:, 5].set(jnp.arange(keys) == at[5])
    return q, k, lse, sel


def _both(q, k, lse, sel, first):
    tile = index_target.tile_keys(q.shape[1], k.shape[2], q.shape[2],
                                  k.shape[1], D, q.dtype.itemsize)
    want = jax.jit(sparse_index.block_target, static_argnums=4)(
        q, k, lse, sel, D ** -0.5)
    got = jax.jit(functools.partial(index_target.index_target,
                                    scale=D ** -0.5, tile=tile))(
        q, k, lse, sel.astype(jnp.int8), first)
    return np.asarray(want), np.asarray(got), tile


@pytest.mark.parametrize("h,hkv,rows,keys,first,tile", [
    (4, 2, 32, 128, 96, 128),      # one tile, the block its last rows
    (8, 1, 64, 512, 448, 512),     # one kv head for all, one tile of 512
    (32, 4, 32, 768, 736, 256),    # Keye's heads, three tiles
    (4, 2, 64, 384, 320, 128),     # three tiles of 128
    (8, 1, 32, 1024, 992, 512),    # two tiles of 512
    (4, 2, 32, 512, 64, 512),      # a block early in a long span
])
def test_the_call_is_the_xla_lines(h, hkv, rows, keys, first, tile):
    """``p`` before its normalisation, float32 inputs: within 2e-6 of the
    largest weight of the block, the rows of nothing and of one key among
    them; zero wherever nothing is chosen, to the bit."""
    q, k, lse, sel = _block(h, hkv, rows, keys, first, jnp.float32)
    want, got, planned = _both(q, k, lse, sel, first)
    assert planned == tile
    assert got.shape == (2, rows, keys) and got.dtype == np.float32
    assert want.max() > 1e-3
    assert _gap(got, want) < 2e-6 * want.max()
    assert not got[~np.asarray(sel)].any()
    assert not got[:, 3].any() and (got[:, 5] != 0).sum() == 2


@pytest.mark.parametrize("first,live_tiles", [(0, 1), (96, 1), (128, 2),
                                               (352, 3), (480, 4)])
def test_a_tile_after_the_blocks_last_row_is_zeros_and_not_read(first,
                                                                live_tiles):
    """Four tiles of 128 keys, a block of 32 rows from ``first``: the tiles
    wholly after its last row come back zero whatever the keys there hold
    (NaNs: they are not multiplied), and the live ones are the XLA lines'."""
    rows, keys = 32, 512
    q, k, lse, sel = _block(4, 2, rows, keys, first, jnp.float32, seed=first)
    assert index_target._last_live_tile(first, rows, 128) == live_tiles - 1
    edge = 128 * live_tiles
    want, _, _ = _both(q, k, lse, sel, first)
    poisoned = k.at[:, :, edge:].set(jnp.nan)
    got = np.asarray(jax.jit(functools.partial(
        index_target.index_target, scale=D ** -0.5, tile=128))(
        q, poisoned, lse, sel.astype(jnp.int8), jnp.int32(first)))
    assert np.isfinite(got).all() and not got[:, :, edge:].any()
    assert _gap(got, want) < 2e-6 * want.max()


@pytest.mark.parametrize("h,hkv,rows,keys", [(4, 2, 32, 256),
                                           (32, 4, 64, 512)])
def test_bf16_inputs_multiply_as_the_xla_lines_do(h, hkv, rows, keys):
    """bf16 products with float32 sums in both forms: the two agree as in
    float32 (the same products, another order of 32 terms), and lie within
    bf16's rounding of the logits from the float32 inputs' target."""
    first = keys - rows
    q, k, lse, sel = _block(h, hkv, rows, keys, first, jnp.bfloat16, seed=3)
    want, got, _ = _both(q, k, lse, sel, first)
    assert _gap(got, want) < 4e-6 * want.max()
    exact, _, _ = _both(q.astype(jnp.float32), k.astype(jnp.float32), lse,
                        sel, first)
    assert _gap(got, exact) < 2e-6 * exact.max()  # the inputs are bf16's own
    rough, _, _ = _both(*_block(h, hkv, rows, keys, first, jnp.float32,
                                seed=3)[:2], lse, sel, first)
    # |logit| <= ~5 at unit scale, each operand rounded to 2 ** -9 relative
    assert 0 < _gap(got, rough) < 0.05 * rough.max()


def test_what_the_call_refuses():
    q, k, lse, sel = _block(4, 2, 32, 256, 224, jnp.float32)
    call = functools.partial(index_target.index_target, scale=1.0)
    with pytest.raises(ValueError, match="whole tiles"):
        call(q, k[:, :, :200], lse, sel[:, :, :200].astype(jnp.int8), 0,
             tile=128)
    with pytest.raises(ValueError, match="whole tiles"):
        call(q[:, :24], k, lse[:, :, :24], sel[:, :24].astype(jnp.int8), 0,
             tile=128)
    with pytest.raises(ValueError, match="a choice is int8"):
        call(q, k, lse, sel, 0, tile=128)
    assert index_target.tile_keys(32, 256, 4, 2, 64, 4) is None
    assert index_target.tile_keys(24, 256, 4, 2, D, 4) is None
    assert index_target.tile_keys(32, 200, 4, 2, D, 4) is None
    # Keye's block: 27 MiB of the 40 a step may hold
    assert index_target.tile_keys(256, 2048, 32, 4, D, 2) == 512
    assert index_target.vmem_bytes(256, 512, 32, 4, D, 2) < 40 << 20


# ---- the loss through both forms ------------------------------------------------------

S, J, E, H, HKV, TOPK = 256, 4, 16, 4, 2, 40


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 32 rows in two spans of 128: four blocks a span, the first
    span against half the keys, one tile of 128 keys and then two."""
    monkeypatch.setattr(sparse_index, "BLOCK_ROWS", 32)
    monkeypatch.setattr(sparse_index, "_SPANS", 2)


@functools.partial(jax.jit, static_argnums=0)
def _loss_inputs(dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(7), 6)
    q_idx = jax.random.normal(ks[0], (2, S, J, E))
    k_idx = jax.random.normal(ks[1], (2, S, E))
    w = 0.1 * jax.random.normal(ks[2], (2, S, J))
    q = jax.random.normal(ks[3], (2, S, H, D)).astype(dtype)
    k, v = (jax.random.normal(key, (2, S, HKV, D)).astype(dtype)
            for key in ks[4:])
    chosen, _, _ = sparse_index.choose(q_idx, k_idx, w, TOPK)
    _, lse = sparse_index.dense_attention(q, k, v, chosen, D ** -0.5)
    return q_idx, k_idx, w, q, k, lse, chosen


def _whole(q_idx, k_idx, w, q, k, chosen):
    """``test_keye_training``'s written-out form: the softmax over the
    choice whole, its heads summed, the KL a row, the rows' mean."""
    sel = chosen != 0
    scores = jnp.einsum("bjru,brj->bru", jax.nn.relu(
        jnp.einsum("brje,bue->bjru", q_idx, k_idx)), w)
    kk = jnp.repeat(k, H // HKV, 2)
    p = jax.nn.softmax(jnp.where(sel[:, None], jnp.einsum(
        "bqhd,bkhd->bhqk", q, kk) * D ** -0.5, -jnp.inf), -1).sum(1)
    p = p / p.sum(-1, keepdims=True)
    log_i = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), -1)
    return jnp.where(sel, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                               - jnp.where(sel, log_i, 0.0)),
                     0.0).sum(-1).mean()


def test_the_loss_and_its_gradients_are_one_through_both_forms(small_blocks):
    """``index_loss`` under ``impl="flash"`` (the kernel: d 128, blocks of
    32 rows, spans of 128 keys) and ``"xla"``: the loss and the three
    gradients agree with each other and with ``jax.grad`` through the form
    written out whole; ``q``, ``k`` and ``lse`` take exactly zero from
    both; the plan says which ran."""
    q_idx, k_idx, w, q, k, lse, chosen = _loss_inputs()
    out = {}
    for impl in ("flash", "xla"):
        noted = {}
        with plans.noting(noted):
            out[impl] = jax.jit(jax.value_and_grad(
                lambda *a, impl=impl: sparse_index.index_loss(
                    *a, q, k, lse, chosen, D ** -0.5, impl), (0, 1, 2)))(
                q_idx, k_idx, w)
        assert noted["sparse_plan"] == sparse_index.target_plan(
            128 if impl == "flash" else None)
    assert sparse_index.target_plan(128) == {"target_impl": "pallas",
                                             "target_tile": 128}
    want, refs = jax.jit(jax.value_and_grad(
        lambda *a: _whole(*a, q, k, chosen), (0, 1, 2)))(q_idx, k_idx, w)
    (got, grads), (plain, plain_grads) = out["flash"], out["xla"]
    assert float(want) > 0.05
    assert abs(float(got) - float(want)) < 1e-6
    assert abs(float(got) - float(plain)) < 1e-6
    for g, x, r in zip(grads, plain_grads, refs):
        scale = max(1.0, float(np.abs(np.asarray(r)).max()))
        assert _gap(g, r) < 2e-6 * scale and _gap(g, x) < 2e-6 * scale
    others = jax.jit(jax.grad(lambda q, k, lse: sparse_index.index_loss(
        q_idx, k_idx, w, q, k, lse, chosen, D ** -0.5, "flash"),
        (0, 1, 2)))(q, k, lse)
    assert not any(np.asarray(g).any() for g in others)


def test_the_kernel_stands_in_the_losss_program_under_flash_alone(
        small_blocks):
    """A call a span, named by its shape, in the program ``impl="flash"``
    traces; none in ``"xla"``'s, whose jaxpr holds the einsum as before."""
    q_idx, k_idx, w, q, k, lse, chosen = _loss_inputs()

    def text(impl):
        return str(jax.make_jaxpr(lambda *a: sparse_index.index_loss(
            *a, q, k, lse, chosen, D ** -0.5, impl))(q_idx, k_idx, w))

    flash_text, xla_text = text("flash"), text("xla")
    for keys in (128, 256):
        assert f"index_target_bh{2 * H}_r32_k{keys}_d{D}_g{H // HKV}" \
            in flash_text
    assert "flash_" not in flash_text and "index_target" not in xla_text


# ---- the rule ------------------------------------------------------------------------

@pytest.mark.parametrize("impl,seq,d,chips,tile", [
    ("flash", 16384, 128, 1, 512),   # the cell: blocks of 256, spans of 2,048
    ("flash", 4096, 128, 1, 512),    # spans of 512
    ("flash", 2048, 128, 1, 256),    # spans of one block
    ("flash", 1024, 128, 1, 512),    # one span, the whole sequence
    ("flash", 16384, 256, 1, 512),   # two lane tiles a head
    ("xla", 16384, 128, 1, None),    # the caller's kernels are off
    ("ring", 16384, 128, 1, None),
    ("flash", 16384, 128, 4, None),  # a mesh of several chips
    ("flash", 16384, 64, 1, None),   # half a lane tile
    ("flash", 16384, 192, 1, None),  # a lane tile and a half
    ("flash", 1000, 128, 1, None),   # ragged: one block of 1,000 rows
    ("flash", 96, 128, 1, None),     # whole int8 tiles, no tile of keys
])
def test_the_rule_that_picks_the_form(impl, seq, d, chips, tile):
    """The kernel where the caller's ``attn_impl`` is "flash", on one chip,
    heads of whole lanes, rows and keys whole tiles; XLA's lines else; and
    ``plan`` says it."""
    pick = functools.partial(sparse_index.kernel_tile, impl, seq, 32, 4, d, 2)
    if chips > 1:
        devices = np.asarray(jax.devices()[:chips])
        with mesh_scope(Mesh(devices, ("data",))):
            got = pick()
    else:
        got = pick()
    assert got == tile
    plan = sparse_index.plan(seq, 16, 64, 2048, target_tile=got)
    assert (plan["target_impl"], plan["target_tile"]) == (
        "pallas" if tile else "xla", tile)
    said = plans.DESCRIBE["sparse_plan"](plan)
    assert ((f"a Pallas call of {tile} keys a grid step" in said) if tile
            else ("the loss's target in XLA" in said)), said
