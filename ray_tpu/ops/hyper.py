"""Manifold-constrained hyper-connections (arXiv:2512.24880): a residual
stream of ``n`` rows that every half layer reads through one learned mix and
writes through another, the rows' own mixing matrix held doubly stochastic
by Sinkhorn's iterations.

A half layer (attention or feed-forward) owns ``g`` [n d], ``phi``
[n d, n^2 + 2n], ``b`` [n^2 + 2n] and three scalars ``alpha``. Per token,
``X`` [n, d] the stream's rows:

- ``u = rms(vec(X)) * g``; ``[p | q | R] = u @ phi`` (n, n and n^2 columns);
- ``H_pre = sigmoid(alpha[0] p + b_pre)``; ``H_post = 2 sigmoid(alpha[1] q +
  b_post)``; ``M = exp(clip(alpha[2] mat(R) + b_res, lo, hi))`` (``mat``
  row by row: ``M[i, j]`` is column ``i n + j``), then ``iters`` times ``M /=
  colsum(M) + eps`` and ``M /= rowsum(M) + eps``: ``H_res``;
- ``h = sum_i H_pre[i] X[i]`` goes into the half's branch ``y = F(norm(h))``
  (``mix_in``); ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` is the
  stream after it (``mix_out``, which ``models/llama.join`` calls).

Layout. The stream is ``[n, b, s, d]``, rows first: a row is a plain
``[b, s, d]`` slab whose last two dimensions tile as every other
activation's do (with the rows next to ``d`` four of them would share a
tile of sixteen). The coefficients are ``[n^2 + 2n, b, s]`` float32, the
tokens on the lanes: Sinkhorn's forty normalisations are elementwise over
``[n, n, b s]`` with every lane at work, where ``[b, s, n, n]`` would put
four values in a row of 128; the three results are turned tokens first
once, 24 values a token, for the mixes, where a coefficient multiplies a
row's ``[b, s, d]`` slab along ``d``. (On a v5e the compiler keeps each
coefficient a vector on the lanes all the same and lays the slabs
tokens-minor to suit it, turning a row inside the fusions that feed the
branch's products: ``PERF.md`` section 5.) ``phi``'s product is ``n`` products ``[b s, d]
@ [d, n^2 + 2n]``, ``g`` folded into ``phi`` (``rms(X) g phi = rsqrt(ms)
X (g * phi)``), so that the normed rows are never written. Everything but
the rows and that product's operands is float32; a row is rounded to the
stream's dtype once, as it is written.

Two paths that share no logic, chosen by ``mix_in``'s ``impl`` and by what
it can observe (``kernels_tile``):

``"pallas"``  (``attn_impl="flash"``, one chip, ``d`` whole lanes of 128 and
    the tokens whole tiles) ``ops/pallas/hyper_mix.py``: the two mixes as
    ``jax.custom_vjp``s, each pass forward and backward one Pallas call that
    holds a tile of tokens' ``n`` rows in VMEM and reads them once, the
    iterations in VMEM; the coefficients one ``[tokens, 128]`` float32
    array, a coefficient a lane (``Held``).
``"xla"``     (the CPU tests' yardstick, and what a mesh of several chips
    runs, because a Mosaic call is not partitioned) everything below under
    plain autodiff, the iterations included; under
    ``models/llama.remat_block`` the forward of a layer's two mixes runs
    again in its backward. It is the reference the kernels are tested
    against (``tests/test_hyper_mix.py``).

What a remat block can keep of the kernels' path (``RESIDUAL_NAMES``):
``mix_in``'s ``h``, its coefficients, and ``phi``'s normed product and the
norm's ``rsqrt``, which its backward reads: a block that saves by these
names runs that call, with its norm, product and iterations, once.

Scopes: everything here lies under ``hyper_mix``, forward and backward,
which its callers keep outermost (``models/moe._patterned_layer``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.util import plans

Params = Dict[str, Any]
F32 = jnp.float32

#: a half layer's leaves, under ``hc_<half>_<name>`` in a layer's tree
LEAVES = ("g", "phi", "b", "alpha")
#: what the kernels' ``mix_in`` names of its forward call's results
#: (``jax.ad_checkpoint.checkpoint_name``): h, the coefficients, and the two
#: its backward call reads beside the rows
RESIDUAL_NAMES = ("hc_h", "hc_coef", "hc_z", "hc_inv")
#: what ``init`` draws, "as trained" (seeded weights stand in for trained
#: ones, as ``moe.ROUTER_BIAS_INIT`` does for the selection bias; a half
#: would START from ``alpha`` 0.01 and ``neutral_bias``): ``alpha`` uniform in
#: ``ALPHA_DRAWN``, normal noise of ``BIAS_NOISE`` on ``b``, and the
#: identity's head start in ``b_res`` cut to ``RES_DIAG_DRAWN``, so that all
#: three matrices differ from row to row and token to token
ALPHA_DRAWN = (0.05, 0.15)
BIAS_NOISE = 0.5
RES_DIAG_DRAWN = 1.0
#: the diagonal's head start in a fresh ``b_res``: off it ``H_res`` starts
#: at ``exp(-2 * 8)`` of the diagonal, the identity to float32
RES_DIAG_INIT = 8.0


class Mix(NamedTuple):
    """What a half read of the stream for its write: float32, tokens first
    (as a row's slab has them, so that a coefficient spreads along ``d``)."""
    post: jax.Array    # [b, s, n]
    res: jax.Array     # [b, s, n, n], [i, j]: row j's share of new row i


class Held(NamedTuple):
    """What the kernels' ``mix_in`` hands ``mix_out``: the rows as its
    custom_vjp passed them through (``mix_out`` reads THESE, so that the
    rows' cotangent so far enters ``mix_in``'s backward call and is summed
    there), the coefficients [tokens, 128] float32 (``H_pre``, ``H_post``,
    ``H_res`` row by row, a lane each) and the tokens a grid step."""
    rows: jax.Array    # [n, b s, d]
    coef: jax.Array
    tile: int


def columns(n: int) -> int:
    return n * n + 2 * n


def neutral_bias(n: int) -> jax.Array:
    """``b`` [n^2 + 2n] at which, ``alpha`` 0, a half is ``x + F(norm(x))``
    on the summed stream: ``H_pre`` 1 / n a row (the branch's norm takes the
    scale out; 1 where n is 1), ``H_post`` 1 / n a row (they sum to 1), and
    ``H_res`` the identity to float32 (any doubly stochastic ``H_res`` keeps
    the rows' sum)."""
    pre = 30.0 if n == 1 else math.log(1.0 / (n - 1))      # logit(1 / n)
    post = -math.log(2.0 * n - 1.0)                        # logit(1 / 2n)
    res = RES_DIAG_INIT * (2.0 * jnp.eye(n, dtype=F32) - 1.0)
    return jnp.concatenate([jnp.full((n,), pre, F32), jnp.full((n,), post, F32),
                            res.reshape(-1)])


def init(rng: jax.Array, n: int, d: int, layers: int, dtype) -> Params:
    """``layers`` halves' leaves, stacked, drawn as the constants above say.
    ``b`` and ``alpha`` are float32 whatever ``dtype``: a bias of 8 and a
    scale of 0.01 lose what a step moves them by in eight bits."""
    k_phi, k_b, k_a = jax.random.split(rng, 3)
    c = columns(n)
    drawn = neutral_bias(n).at[2 * n:].set(
        (RES_DIAG_DRAWN * jnp.eye(n, dtype=F32)).reshape(-1))
    return {
        "g": jnp.ones((layers, n * d), dtype),
        "phi": (jax.random.normal(k_phi, (layers, n * d, c), F32)
                / math.sqrt(n * d)).astype(dtype),
        "b": drawn + BIAS_NOISE * jax.random.normal(k_b, (layers, c), F32),
        "alpha": jax.random.uniform(k_a, (layers, 3), F32, *ALPHA_DRAWN),
    }


def params(n: int, d: int) -> int:
    """One half's leaves."""
    return n * d * (1 + columns(n)) + columns(n) + 3


def stream_bytes(n: int, d: int, itemsize: int) -> Tuple[int, int]:
    """(forward, backward) bytes a token and half layer by the least passes
    over the stream, rows ``d`` wide: forward the mix-in reads the rows and
    writes ``h``, the mix-out reads the rows and the branch and writes the
    rows, ``(3n + 2) d``; backward the mix-out's reads the rows' cotangent,
    the rows and the branch and writes the branch's, the mix-in's reads
    ``h``'s cotangent, the rows and the rows' cotangent again and writes the
    rows', ``(5n + 3) d``. The coefficients (``n^2 + 2n`` a token) are not
    counted, nor a pass that remat runs again."""
    return (3 * n + 2) * d * itemsize, (5 * n + 3) * d * itemsize


# --------------------------------------------------------------- the plan

def plan(n: int, d: int, itemsize: int, iters: int,
         tile: Optional[int] = None) -> Dict[str, Any]:
    """What ``mix_in`` does at one shape; pure. ``tile``: the tokens a grid
    step of the kernels' (``kernels_tile``), None for XLA's form.
    ``stream_bytes_moved_*``: what the four calls' block specs move a token
    and half layer (``hyper_mix.bytes_moved``), beside the least passes'
    ``stream_bytes_*``; None where XLA decides what moves."""
    fwd, bwd = stream_bytes(n, d, itemsize)
    moved = (None, None)
    if tile:
        from ray_tpu.ops.pallas import hyper_mix

        moved = hyper_mix.bytes_moved(n, d, itemsize)
    return {"rows": n, "d_model": d, "sinkhorn_iters": iters,
            "layout": "rows first [n, b, s, d]; coefficients "
                      + ("[b s, 128] float32, a coefficient a lane" if tile
                         else "[n*n + 2n, b, s] float32, tokens on the lanes"),
            "stream_bytes_fwd": fwd, "stream_bytes_bwd": bwd,
            "impl": "pallas" if tile else "xla", "tile_tokens": tile,
            "stream_bytes_moved_fwd": moved[0],
            "stream_bytes_moved_bwd": moved[1]}


def kernels_tile(impl: str, tokens: int, d: int) -> Optional[int]:
    """The tokens a grid step of ``ops/pallas/hyper_mix.py``'s calls where
    they run, None where XLA's form does: ``impl`` ``"pallas"`` (the
    caller's ``attn_impl == "flash"``), one chip (``context.single_chip``),
    ``d`` whole lanes and the tokens whole tiles."""
    from ray_tpu.ops.pallas import hyper_mix
    from ray_tpu.parallel.context import single_chip

    if impl != "pallas" or not single_chip() or d % 128:
        return None
    return hyper_mix.tile_tokens(tokens)


# ------------------------------------------------------------ the parts

def widen(x: jax.Array, n: int) -> jax.Array:
    """[b, s, d] -> the stream's ``n`` rows, each a copy."""
    with jax.named_scope("hyper_mix"):
        return jnp.broadcast_to(x[None], (n, *x.shape))


def narrow(x: jax.Array) -> jax.Array:
    """The rows' sum, summed in float32: what the final norm reads."""
    with jax.named_scope("hyper_mix"):
        return jnp.sum(x.astype(F32), axis=0).astype(x.dtype)


def _g_phi(half: Params) -> jax.Array:
    """``g`` folded into ``phi``, float32 [n d, n^2 + 2n]."""
    return half["g"].astype(F32)[:, None] * half["phi"].astype(F32)


def sinkhorn(m: jax.Array, iters: int, eps: float, made=None) -> jax.Array:
    """m [n, n, ...] positive -> ``iters`` times its columns and then its
    rows divided by their sums (``+ eps``). ``made``: the result where a
    kernel has formed it already (``hyper_mix.mix_in``'s call); it is handed
    on as it is, so that both paths' ``H_res`` enters the mixes here and
    nowhere else (what cuts its cotangent here cuts it in both:
    ``tests/benchmark/xing4_chip_check.py``'s planted fault)."""
    if made is not None:
        return made
    for _ in range(iters):
        m = m / (m.sum(0, keepdims=True) + eps)
        m = m / (m.sum(1, keepdims=True) + eps)
    return m


def coefficients(x: jax.Array, half: Params, *, iters: int, eps: float,
                 clamp: Tuple[float, float], norm_eps: float
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [n, b, s, d], a half's ``LEAVES`` -> (H_pre [b, s, n], H_post
    [b, s, n], H_res [b, s, n, n]), float32."""
    n, _, _, d = x.shape
    ms = jnp.mean(jnp.square(x.astype(F32)), axis=(0, 3))            # [b, s]
    w = _g_phi(half).astype(x.dtype).reshape(n, d, -1)
    # (a product's result comes tokens first; the 24 columns are turned
    # onto the sublanes after it)
    raw = sum(jnp.einsum("bsd,dc->bsc", x[i], w[i],
                         preferred_element_type=F32) for i in range(n))
    z = jnp.moveaxis(raw, -1, 0) * jax.lax.rsqrt(ms + norm_eps)
    alpha = half["alpha"].astype(F32)
    b = half["b"].astype(F32)[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * z[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip(alpha[2] * z[2 * n:] + b[2 * n:], *clamp))
    res = sinkhorn(m.reshape(n, n, *m.shape[1:]), iters, eps)
    return (jnp.moveaxis(pre, 0, -1), jnp.moveaxis(post, 0, -1),
            jnp.moveaxis(res, (0, 1), (-2, -1)))


def _mix_in_kernels(x, half, tile, *, iters, eps, clamp, norm_eps):
    """``mix_in`` through ``ops/pallas/hyper_mix.py``: ``g`` folded into
    ``phi`` in float32 and turned [n, columns, d] outside the call (its
    gradient's way back to both is autodiff's)."""
    from ray_tpu.ops.pallas import hyper_mix

    n, b, s, d = x.shape
    c = columns(n)
    h, coef, rows = hyper_mix.mix_in(
        x.reshape(n, b * s, d),
        _g_phi(half).reshape(n, d, c).transpose(0, 2, 1), half["b"],
        half["alpha"], hyper_mix.Rule(iters, eps, tuple(clamp), norm_eps),
        tile)
    made = sinkhorn(None, iters, eps, coef)
    if made is not coef:
        # something stands in ``sinkhorn``'s place (the planted fault): its
        # result takes H_res's lanes and no other
        lane = jnp.arange(coef.shape[-1])
        coef = jnp.where((lane >= 2 * n) & (lane < c), made, coef)
    return h.reshape(b, s, d), Held(rows, coef, tile)


def mix_in(x: jax.Array, half: Params, *, iters: int, eps: float,
           clamp: Tuple[float, float], norm_eps: float, impl: str = "xla"):
    """The stream's rows [n, b, s, d] -> (what the half's branch reads
    [b, s, d], what ``mix_out`` needs to write its result back: a ``Mix``,
    or a ``Held`` where the kernels run, ``kernels_tile``)."""
    n, b, s, d = x.shape
    tile = kernels_tile(impl, b * s, d)
    plans.note("hyper", plan(n, d, x.dtype.itemsize, iters, tile))
    with jax.named_scope("hyper_mix"):
        if tile:
            return _mix_in_kernels(x, half, tile, iters=iters, eps=eps,
                                   clamp=clamp, norm_eps=norm_eps)
        pre, post, res = coefficients(x, half, iters=iters, eps=eps,
                                      clamp=clamp, norm_eps=norm_eps)
        h = sum(pre[..., i, None] * x[i].astype(F32)
                for i in range(x.shape[0]))
        return h.astype(x.dtype), Mix(post, res)


def mix_out(x: jax.Array, branch: jax.Array, mix) -> jax.Array:
    """The rows after the half: ``H_res`` over the rows plus ``H_post`` of
    the branch [b, s, d]. ``mix``: what ``mix_in`` returned of ``x``."""
    with jax.named_scope("hyper_mix"):
        if isinstance(mix, Held):
            from ray_tpu.ops.pallas import hyper_mix

            return hyper_mix.mix_out(
                mix.rows, branch.reshape(-1, branch.shape[-1]), mix.coef,
                mix.tile).reshape(x.shape)
        n = x.shape[0]
        rows = [x[j].astype(F32) for j in range(n)]
        y = branch.astype(F32)
        return jnp.stack([
            (sum(mix.res[..., i, j, None] * rows[j] for j in range(n))
             + mix.post[..., i, None] * y).astype(x.dtype)
            for i in range(n)])
