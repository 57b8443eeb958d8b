"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The capability the reference lacks entirely (SURVEY.md §5 "Long-context /
sequence parallelism: absent") built TPU-first:

- **Ring attention**: K/V chunks rotate around the ``sp`` mesh axis via
  ``lax.ppermute`` (neighbor hops = ICI-local); each step computes one
  blockwise-attention chunk with the pallas flash kernel's ``(out, lse)``
  form and merges via streaming log-sum-exp. Peak memory is O(seq/P) per
  chip, enabling million-token contexts. Exact — not an approximation.
- **Ulysses**: ``lax.all_to_all`` re-shards [b, s/P, h, d] -> [b, s, h/P, d]
  so each chip runs full-sequence attention on a head subset; cheaper
  collectives for moderate sequence lengths, bounded by head count.

Both run INSIDE ``shard_map`` over the mesh; ``sequence_parallel_attention``
is the jit-friendly entry that wraps them (mesh from the ambient
``mesh_scope``, set by the Train layer's step builder).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.pallas.flash import (
    NEG_INF,
    flash_attention,
    flash_attention_with_lse,
    flash_vjp_chunk,
)

_CURRENT_MESH: contextvars.ContextVar[Optional[Mesh]] = contextvars.ContextVar(
    "ray_tpu_mesh", default=None)

@contextlib.contextmanager
def mesh_scope(mesh: Mesh):
    """Make ``mesh`` the ambient mesh for model-internal shard_map regions."""
    token = _CURRENT_MESH.set(mesh)
    try:
        yield mesh
    finally:
        _CURRENT_MESH.reset(token)


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH.get()


def single_chip() -> bool:
    """May a Mosaic call run here: the compiler does not partition one, so
    under an ambient mesh of several chips a layer takes the XLA form GSPMD
    splits. The one place the rule lives."""
    mesh = current_mesh()
    return mesh is None or mesh.size == 1


def _merge(o1, lse1, o2, lse2):
    """Merge two partial-softmax results; lse: [b,h,s], o: [b,s,h,d]."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m <= NEG_INF / 2, 0.0, m)
    w1 = jnp.where(lse1 <= NEG_INF / 2, 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(lse2 <= NEG_INF / 2, 0.0, jnp.exp(lse2 - m_safe))
    denom = w1 + w2
    denom_safe = jnp.where(denom == 0.0, 1.0, denom)
    to_o = lambda w: (w / denom_safe).transpose(0, 2, 1)[..., None]
    o = o1 * to_o(w1) + o2 * to_o(w2)
    lse = jnp.where(denom == 0.0, NEG_INF, m_safe + jnp.log(denom_safe))
    return o.astype(o1.dtype), lse


def _ring_perm(axis_name):
    p = lax.axis_size(axis_name)
    return [(i, (i + 1) % p) for i in range(p)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention(q, k, v, axis_name: str, causal: bool = True,
                   scale: Optional[float] = None):
    """Exact attention over a sequence sharded on ``axis_name``.

    Call inside shard_map; q/k/v per-shard [b, s_loc, h, d] holding this
    rank's contiguous sequence chunk (rank r owns positions
    [r*s_loc, (r+1)*s_loc)). Differentiable (custom VJP rotates dk/dv home
    alongside the k/v ring).
    """
    o, _lse = _ring_fwd_loop(q, k, v, axis_name, causal, scale)
    return o


def _ring_fwd_loop(q, k, v, axis_name, causal, scale):
    p = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_loc = q.shape[1]
    b, _, hq, d = q.shape

    o0 = jnp.zeros((b, s_loc, hq, d), jnp.float32)
    lse0 = jnp.full((b, hq, s_loc), NEG_INF, jnp.float32)
    perm = _ring_perm(axis_name)

    def step(carry, t):
        o, lse, kt, vt = carry
        src = (my - t) % p
        q_off = (my - src) * s_loc
        ot, lset = flash_attention_with_lse(
            q, kt, vt, causal=causal, scale=scale, q_offset=q_off)
        o, lse = _merge(o, lse, ot.astype(jnp.float32), lset)
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        return (o, lse, kt, vt), None

    (o, lse, _, _), _ = lax.scan(
        step, (o0, lse0, k, v), jnp.arange(p))
    return o.astype(q.dtype), lse


def _ring_fwd(q, k, v, axis_name, causal, scale):
    o, lse = _ring_fwd_loop(q, k, v, axis_name, causal, scale)
    return o, (q, k, v, o, lse)


def _ring_bwd(axis_name, causal, scale, res, do):
    q, k, v, o, lse = res
    p = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    s_loc = q.shape[1]
    perm = _ring_perm(axis_name)

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)

    def step(carry, t):
        dq, kt, vt, dkt, dvt = carry
        src = (my - t) % p
        q_off = (my - src) * s_loc
        dq_c, dk_c, dv_c = flash_vjp_chunk(
            q, kt, vt, o, do, lse, q_offset=q_off, causal=causal, scale=scale)
        dq = dq + dq_c.astype(jnp.float32)
        dkt = dkt + dk_c.astype(jnp.float32)
        dvt = dvt + dv_c.astype(jnp.float32)
        kt = lax.ppermute(kt, axis_name, perm)
        vt = lax.ppermute(vt, axis_name, perm)
        dkt = lax.ppermute(dkt, axis_name, perm)
        dvt = lax.ppermute(dvt, axis_name, perm)
        return (dq, kt, vt, dkt, dvt), None

    (dq, _, _, dk, dv), _ = lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(p))
    # After p steps + p rotations the accumulators are back at the rank that
    # owns their k/v chunk.
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = True,
                      scale: Optional[float] = None,
                      use_flash: bool = True):
    """All-to-all sequence parallelism: re-shard seq->heads, attend, undo.

    Per-shard q: [b, s/P, hq, d]. Requires hq % P == 0; kv heads are
    repeated up to hq first if P doesn't divide them (GQA). Differentiable
    through ``lax.all_to_all``.
    """
    p = lax.axis_size(axis_name)
    hq, hkv = q.shape[2], k.shape[2]
    if hq % p:
        raise ValueError(f"ulysses: q heads {hq} not divisible by sp={p}")
    if hkv % p:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    a2a = lambda x: lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                   tiled=True)
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    if use_flash:
        og = flash_attention(qg, kg, vg, causal=causal, scale=scale)
    else:
        from ray_tpu.ops.attention import mha
        og = mha(qg, kg, vg, causal=causal, scale=scale)
    return lax.all_to_all(og, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def flash_attention_on_mesh(q, k, v, *, causal: bool = True,
                            scale: Optional[float] = None,
                            window: Optional[int] = None,
                            mesh: Optional[Mesh] = None):
    """The flash kernel under the ambient mesh. The TPU compiler does not
    partition a Mosaic kernel ("cannot be automatically partitioned"), so
    on more than one device the kernel runs per shard in the layout GSPMD
    already gives q/k/v: batch over (dp, fsdp), heads over tp. KV heads
    that tp does not divide are repeated up to the q heads first."""
    mesh = mesh or current_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               window=window)
    if k.shape[2] % mesh.shape["tp"]:
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    spec = P(("dp", "fsdp"), None, "tp", None)
    return jax.shard_map(
        functools.partial(flash_attention, causal=causal, scale=scale,
                          window=window),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def sequence_parallel_attention(q, k, v, *,
                                impl: str = "ring",
                                axis_name: str = "sp",
                                mesh: Optional[Mesh] = None,
                                causal: bool = True,
                                scale: Optional[float] = None):
    """Jit-level entry: shard_map the chosen SP attention over the mesh.

    q/k/v are GLOBAL [b, s, h, d] (seq sharded over ``axis_name`` by GSPMD);
    batch rides (dp, fsdp), heads ride tp. Grad-capable.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        raise ValueError(
            "sequence_parallel_attention needs a mesh (use parallel.context."
            "mesh_scope(mesh) around the step, or pass mesh=).")
    qspec = P(("dp", "fsdp"), axis_name, "tp", None)

    def local(qq, kk, vv):
        if impl == "ring":
            return ring_attention(qq, kk, vv, axis_name, causal, scale)
        elif impl == "ulysses":
            return ulysses_attention(qq, kk, vv, axis_name, causal, scale)
        raise ValueError(f"unknown sp impl {impl!r}")

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qspec, qspec, qspec),
        out_specs=qspec,
        check_vma=False,
    )(q, k, v)
