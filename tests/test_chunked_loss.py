"""The looped cross entropy's own rule (``llama._looped_ce``): both gradients
formed in the loop that holds a chunk's logits, the head's as one product a
group of chunks.

The yardstick is plain autodiff of the loop-free path (``loss_chunk`` 0) at
float32; at bfloat16 the loss is held to the bit to the loop this rule took
the place of (``_parents_loop`` below, kept here as it was). Groups are made
small (``HEAD_GRAD_ROWS`` patched) so that shapes a CPU can afford run
several of them. What the rule does under a mesh is in
``test_chunked_loss_sharded.py``, what the chip's compiler makes of it in
``test_aot_step_*.py`` (``_aot.names_all_of_itself``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama

D, V = 16, 40


@pytest.fixture(autouse=True)
def small_groups(monkeypatch):
    """Two chunks of 16 a group where the chunks allow it."""
    monkeypatch.setattr(llama, "HEAD_GRAD_ROWS", 32)


def _inputs(B, S, mask, n=1, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(B * 1000 + S), 4)
    x = jax.random.normal(k[0], (B, S, D), jnp.float32).astype(dtype)
    head = (0.3 * jax.random.normal(k[1], (D, n * V))).astype(dtype)
    targets = jax.random.randint(k[2], (B, S), 0, V, jnp.int32)
    live = {"none": None, "zeros": jnp.zeros((B, S), jnp.float32),
            "some": (jax.random.uniform(k[3], (B, S)) > 0.3)
            .astype(jnp.float32)}[mask]
    return x, head, targets, live


def _close(got, want, tol=1e-6):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max()), (
        np.abs(got - want).max())


def _parents_loop(x, head, targets, mask, chunk):
    """``chunked_ce``'s loop as it stood before the rule: a fully rematted
    scan over the chunks under plain autodiff."""
    n_chunks = targets.shape[1] // chunk
    xs = x.reshape(x.shape[0], n_chunks, chunk, -1).swapaxes(0, 1)
    ts = targets.reshape(targets.shape[0], n_chunks, chunk).swapaxes(0, 1)
    ms = (jnp.ones_like(ts, jnp.float32) if mask is None
          else mask.reshape(mask.shape[0], n_chunks, chunk).swapaxes(0, 1)
          .astype(jnp.float32))

    def chunk_nll(carry, sl):
        xc, tc, mc = sl
        logits = (xc @ head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        s, cnt = carry
        return (s + (nll * mc).sum(), cnt + mc.sum()), None

    body = jax.checkpoint(
        chunk_nll, policy=jax.checkpoint_policies.nothing_saveable)
    (total, count), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ts, ms))
    return total / jnp.maximum(count, 1)


@pytest.mark.parametrize("n_chunks,chunk,rows,per", [
    (16, 256, 2048, 8), (64, 256, 2048, 8), (4, 16, 32, 2),
    (6, 16, 64, 3),      # regrouped: three and three, not four and two
    (7, 16, 64, 1),      # a prime count of chunks: a product a chunk
    (2, 4096, 2048, 1),  # a chunk longer than a group is its own
    (3, 16, 1024, 3)])   # a sequence shorter than a group is one
def test_a_groups_length_follows_the_shapes(monkeypatch, n_chunks, chunk,
                                            rows, per):
    monkeypatch.setattr(llama, "HEAD_GRAD_ROWS", rows)
    assert llama._chunks_a_group(n_chunks, chunk) == per


@pytest.mark.parametrize("B,S,chunk,mask,scale", [
    (1, 64, 16, "none", 1.0), (4, 64, 16, "none", 1.0),
    (1, 64, 16, "some", 1.0), (4, 64, 16, "some", 1.0),
    (4, 64, 16, "zeros", 1.0),         # count 0: a loss of 0, no gradient
    (4, 96, 16, "some", 1.0),          # six chunks: groups of three
    (1, 112, 16, "some", 1.0),         # seven: a product a chunk
    (4, 64, 32, "some", 1.0),          # a group is one chunk
    (4, 64, 16, "some", 0.3),          # a loss scaled by ``mtp_weight``
    (1, 96, 16, "none", -2.5)])
def test_one_heads_rule_against_autodiff_of_the_loop_free_path(
        B, S, chunk, mask, scale):
    """Loss equal, ``dx`` and ``dhead`` to 1e-6 at float32, whatever the
    mask, the batch, the grouping and the cotangent that comes in."""
    x, head, targets, live = _inputs(B, S, mask)
    assert llama._loss_chunks(S, chunk)

    def loss(x, head, chunk):
        return scale * llama.chunked_ce(x, head, targets, live, chunk)

    want, (dx_0, dh_0) = jax.value_and_grad(loss, (0, 1))(x, head, 0)
    got, (dx, dh) = jax.jit(jax.value_and_grad(loss, (0, 1)),
                            static_argnums=2)(x, head, chunk)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    _close(dx, dx_0)
    _close(dh, dh_0)
    if mask == "zeros":
        assert float(got) == 0.0 and not np.asarray(dh).any()
    # and where nothing is differentiated the loop forms no gradient
    alone = jax.jit(loss, static_argnums=2)
    np.testing.assert_allclose(float(alone(x, head, chunk)), float(want),
                               rtol=1e-6)
    assert "pbcd,pbcv->dv" not in str(
        jax.make_jaxpr(loss, static_argnums=2)(x, head, chunk))


@pytest.mark.parametrize("mask", ["none", "some"])
def test_a_tied_heads_gradient_reaches_the_embedding(mask):
    """``head`` the embedding read the other way round: its cotangent
    crosses the transpose outside the rule."""
    x, head, targets, live = _inputs(4, 64, mask)
    embed = head.T

    def loss(x, embed, chunk):
        return llama.chunked_ce(x, embed.T, targets, live, chunk)

    want = jax.grad(loss, (0, 1))(x, embed, 0)
    got = jax.jit(jax.grad(loss, (0, 1)), static_argnums=2)(x, embed, 16)
    assert got[1].shape == embed.shape
    jax.tree.map(_close, got, want)


@pytest.mark.parametrize("B,S,n,mask", [
    (1, 64, 8, "none"), (4, 64, 8, "some"), (4, 96, 8, "some"),
    (2, 64, 3, "zeros")])
def test_n_heads_are_n_times_the_same_rule(B, S, n, mask):
    """``multi_head_ce`` through the same core: ``n`` heads' targets and
    weights read ``i`` positions on, each head its own count."""
    x, head, targets, live = _inputs(B, S, mask, n=n)

    def loss(x, head, chunk):
        return 0.7 * llama.multi_head_ce(x, head, targets, live, chunk, n)

    want, (dx_0, dh_0) = jax.value_and_grad(loss, (0, 1))(x, head, 0)
    got, (dx, dh) = jax.jit(jax.value_and_grad(loss, (0, 1)),
                            static_argnums=2)(x, head, 16)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    _close(dx, dx_0)
    _close(dh, dh_0)


@pytest.mark.parametrize("B,S,mask", [(1, 64, "none"), (4, 96, "some"),
                                      (4, 64, "zeros")])
def test_at_bfloat16_the_loss_is_the_parents_to_the_bit(B, S, mask):
    """The forward's arithmetic is the old loop's: the same product rounded
    to the compute dtype, the same ``log_softmax``, the sums in the same
    order. The gradients come back in their primals' dtypes and agree with
    the old loop's to bfloat16's rounding (one rounding of the head's where
    the old carry rounded once a chunk)."""
    x, head, targets, live = _inputs(B, S, mask, dtype=jnp.bfloat16)
    new = jax.jit(jax.value_and_grad(
        lambda x, h: llama.chunked_ce(x, h, targets, live, 16), (0, 1)))
    old = jax.jit(jax.value_and_grad(
        lambda x, h: _parents_loop(x, h, targets, live, 16), (0, 1)))
    (got, (dx, dh)), (want, (dx_0, dh_0)) = new(x, head), old(x, head)
    assert float(got) == float(want), (float(got), float(want))
    assert dx.dtype == x.dtype and dh.dtype == head.dtype
    _close(dx, dx_0, 2e-2)
    _close(dh, dh_0, 2e-2)
