"""Pipeline parallelism: microbatch pipelining over a mesh axis.

TPU-first design: the pipeline is a single SPMD program — every rank runs the
same ``lax.scan`` over ticks; activations hop to the next stage with
``lax.ppermute`` (one ICI neighbor hop per tick). No per-stage processes, no
host round-trips: XLA overlaps the permute with the next tick's compute. The
reference has no pipeline parallelism of its own (it delegates to
torch/DeepSpeed — SURVEY.md §2.3 "other backends"); here it is a mesh axis
(``pp``) like any other.

Two schedules:
  - **GPipe** (``pipeline_spmd``/``pipeline_apply``): forward scan, backward
    by autodiff of the scan. Activation stash grows with M (all microbatch
    inputs live until the transposed scan consumes them) — simple, fully
    differentiable, good for small M.
  - **1F1B** (``pipeline_1f1b``): forward AND backward interleaved in one
    scan — every tick runs one stage forward and one per-stage ``jax.vjp``
    backward on an older microbatch, so at most 2P-1 microbatch inputs are
    ever stashed, independent of M. That O(P) activation memory is what lets
    M (and therefore utilization) scale: at a fixed stash budget, 1F1B runs
    a much larger M and a smaller bubble fraction than GPipe (see
    ``schedule_stats``). Cost: the loss head is evaluated on every rank
    (cotangent-masked to the last stage) — a few percent of stage FLOPs.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _microbatch(tree: Any, m: int):
    """Reshape every [B, ...] leaf to [m, B/m, ...]."""
    return jax.tree.map(
        lambda a: a.reshape(m, a.shape[0] // m, *a.shape[1:]), tree)


def _mb_index(tree: Any, idx):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, idx, 0, keepdims=False), tree)


def pipeline_spmd(stage_fn: Callable,
                  stage_params: Any,
                  x: jax.Array,
                  axis_name: str,
                  num_microbatches: int,
                  extras: Any = None) -> jax.Array:
    """Run ``x`` through P pipeline stages (call INSIDE shard_map).

    ``stage_fn(stage_params, mb)`` — or ``stage_fn(stage_params, mb,
    extras_mb)`` when ``extras`` is given: this rank's slice of the network
    applied to one microbatch. ``x``: per-shard [B, ...]; B must divide by
    ``num_microbatches``. ``extras``: optional pytree of [B, ...] arrays
    (segment ids, positions) — microbatched alongside ``x`` but indexed
    locally per tick rather than transported through the pipe (every rank
    holds the full batch copy of them). Returns the final-stage output,
    replicated to all pp ranks (so downstream loss code is rank-agnostic).
    Differentiable.
    """
    p = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    m = num_microbatches
    if x.shape[0] % m:
        raise ValueError(f"batch {x.shape[0]} not divisible by {m} microbatches")
    xs = x.reshape(m, x.shape[0] // m, *x.shape[1:])
    exs = None if extras is None else _microbatch(extras, m)
    ticks = m + p - 1
    perm = [(i, (i + 1) % p) for i in range(p)]

    def tick(carry, t):
        outputs, recv = carry
        # Stage r works on microbatch (t - r); rank 0 reads fresh input.
        in_idx = jnp.clip(t, 0, m - 1)
        x0 = lax.dynamic_index_in_dim(xs, in_idx, 0, keepdims=False)
        x_in = jnp.where(r == 0, x0, recv).astype(xs.dtype)
        if exs is None:
            y = stage_fn(stage_params, x_in)
        else:
            # This rank is on microbatch (t - r) — index ITS extras, not
            # rank 0's input index.
            my_idx = jnp.clip(t - r, 0, m - 1)
            y = stage_fn(stage_params, x_in, _mb_index(exs, my_idx))
        # Last stage finishes microbatch (t - (p-1)).
        out_idx = jnp.clip(t - (p - 1), 0, m - 1)
        valid = (t >= p - 1) & (r == p - 1)
        prev = lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, y, prev), out_idx, 0)
        recv = lax.ppermute(y, axis_name, perm)
        return (outputs, recv), None

    out0 = jnp.zeros((m, *xs.shape[1:]), x.dtype)
    (outputs, _), _ = lax.scan(tick, (out0, jnp.zeros_like(xs[0])),
                               jnp.arange(ticks))
    # Outputs live on the last rank; replicate so every rank returns them.
    outputs = lax.psum(jnp.where(r == p - 1, outputs, 0.0), axis_name)
    return outputs.reshape(x.shape)


def pipeline_1f1b(stage_fn: Callable,
                  head_loss_fn: Callable,
                  layer_params: Any,
                  head_params: Any,
                  x: jax.Array,
                  targets: jax.Array,
                  mesh: Mesh,
                  *,
                  axis_name: str = "pp",
                  num_microbatches: int = 4,
                  batch_axes: Tuple = ("dp", "fsdp", "tp"),
                  segments: Optional[jax.Array] = None,
                  loss_mask: Optional[jax.Array] = None):
    """Interleaved forward/backward (1F1B) pipeline with manual per-stage
    VJPs. Returns ``(loss, layer_grads, head_grads, x_grads)``.

    Schedule: one ``lax.scan`` over T = M + 2P - 1 ticks. At tick t, rank r
    runs the FORWARD of microbatch ``t - r`` and the BACKWARD (a
    ``jax.vjp`` of stage+loss, i.e. recompute-forward + backward — full
    rematerialization by construction) of microbatch ``t - 2P + 1 + r``;
    activations hop forward and cotangents hop backward via ``ppermute``
    each tick. A microbatch input is stashed for the 2P-1-2r ticks between
    its F and B on a rank, so peak stash is 2P-1 microbatches regardless of
    M — versus M for GPipe-by-autodiff. That is the entire point: memory no
    longer caps M, and bubble fraction falls as M grows.

    The loss head runs inside the pipeline (backward must START there), so
    ``head_loss_fn(head_params, y_mb, tgt_mb, mask_mb) -> mean_nll`` is
    evaluated by every rank each backward tick with its cotangent masked to
    the last stage — wasted FLOPs bounded by head-cost/stage-cost, the price
    of a uniform SPMD program (a data-dependent branch on rank would lower
    to ``select`` and compute both sides anyway).

    ``layer_params`` leaves are the [L, ...] stacked-layer arrays sharded
    P(axis_name) on dim 0; ``head_params`` replicated; ``x``/``targets``/
    ``segments``/``loss_mask`` batch-sharded over ``batch_axes``.
    """
    m = num_microbatches
    pspec = jax.tree.map(lambda _: P(axis_name), layer_params)
    hspec = jax.tree.map(lambda _: P(), head_params)
    xspec = P(batch_axes)
    dspec = P(batch_axes)

    mask = (jnp.ones(targets.shape, jnp.float32) if loss_mask is None
            else loss_mask.astype(jnp.float32))
    segs = segments  # may be None (captured statically)

    def body(w, head, xx, tt, mm, *rest):
        ss = rest[0] if rest else None
        p = lax.axis_size(axis_name)
        r = lax.axis_index(axis_name)
        if xx.shape[0] % m:
            raise ValueError(
                f"batch {xx.shape[0]} not divisible by {m} microbatches")
        xs = _microbatch(xx, m)
        ts = _microbatch(tt, m)
        ms = _microbatch(mm, m)
        sg = None if ss is None else _microbatch(ss, m)
        mb_shape = xs.shape[1:]
        n_slots = 2 * p - 1
        ticks = m + 2 * p - 1
        # Global token count, known upfront: the loss is a global MEAN, so
        # each microbatch's cotangent is its share cnt_mb/total (grads then
        # come out mean-scaled, matching value_and_grad of lm_loss).
        total_cnt = jnp.maximum(lax.psum(mm.sum(), tuple(batch_axes)), 1.0)
        perm_f = [(i, (i + 1) % p) for i in range(p)]
        perm_b = [(i, (i - 1) % p) for i in range(p)]

        def tick(carry, t):
            stash, f_recv, b_recv, gw, gh, nll, cnt, gx = carry

            # ---- backward STASH READ first: B(m, r=0) at tick m+2P-1 and
            # F(m+2P-1, r=0) share a tick AND a stash slot — the read must
            # see the old microbatch, so it precedes the forward's write.
            mb = t - 2 * (p - 1) + r - 1
            b_valid = (mb >= 0) & (mb < m)
            mb_c = jnp.clip(mb, 0, m - 1)
            a_b = lax.dynamic_index_in_dim(stash, mb_c % n_slots, 0, False)

            # ---- forward: microbatch t - r --------------------------------
            mf = t - r
            f_valid = (mf >= 0) & (mf < m)
            mf_c = jnp.clip(mf, 0, m - 1)
            a_f = jnp.where(r == 0,
                            lax.dynamic_index_in_dim(xs, mf_c, 0, False),
                            f_recv).astype(xs.dtype)
            seg_f = None if sg is None else lax.dynamic_index_in_dim(
                sg, mf_c, 0, False)
            y_f = stage_fn(w, a_f, seg_f)
            slot_f = mf_c % n_slots
            prev = lax.dynamic_index_in_dim(stash, slot_f, 0, False)
            stash = lax.dynamic_update_index_in_dim(
                stash, jnp.where(f_valid, a_f, prev), slot_f, 0)

            # ---- backward: microbatch t - 2P + 1 + r ----------------------
            tgt_b = lax.dynamic_index_in_dim(ts, mb_c, 0, False)
            msk_b = lax.dynamic_index_in_dim(ms, mb_c, 0, False)
            seg_b = None if sg is None else lax.dynamic_index_in_dim(
                sg, mb_c, 0, False)

            def stage_and_loss(w_, head_, a_):
                y_ = stage_fn(w_, a_, seg_b)
                return y_, head_loss_fn(head_, y_, tgt_b, msk_b)

            (_, mean_nll), vjp = jax.vjp(stage_and_loss, w, head, a_b)
            is_last = r == p - 1
            cnt_b = msk_b.sum()
            # Cotangent routing: interior ranks are driven by the received
            # activation cotangent; the last rank by the loss (scaled
            # mean->sum so microbatch means accumulate exactly).
            g_y = jnp.where(is_last | ~b_valid, 0.0, b_recv).astype(xs.dtype)
            l_cot = jnp.where(is_last & b_valid, cnt_b, 0.0) / total_cnt
            gw_d, gh_d, g_a = vjp((g_y, l_cot))
            gw = jax.tree.map(jnp.add, gw, gw_d)
            gh = jax.tree.map(jnp.add, gh, gh_d)
            picked = is_last & b_valid
            nll = nll + jnp.where(picked, mean_nll * cnt_b, 0.0)
            cnt = cnt + jnp.where(picked, cnt_b, 0.0)
            gx_prev = lax.dynamic_index_in_dim(gx, mb_c, 0, False)
            gx = lax.dynamic_update_index_in_dim(
                gx, jnp.where(b_valid & (r == 0), g_a, gx_prev), mb_c, 0)

            # ---- hop ------------------------------------------------------
            f_recv = lax.ppermute(y_f, axis_name, perm_f)
            b_recv = lax.ppermute(g_a, axis_name, perm_b)
            return (stash, f_recv, b_recv, gw, gh, nll, cnt, gx), None

        init = (
            jnp.zeros((n_slots, *mb_shape), xs.dtype),      # stash
            jnp.zeros(mb_shape, xs.dtype),                  # f_recv
            jnp.zeros(mb_shape, xs.dtype),                  # b_recv
            jax.tree.map(jnp.zeros_like, w),                # gw
            jax.tree.map(jnp.zeros_like, head),             # gh
            jnp.zeros((), jnp.float32),                     # nll sum
            jnp.zeros((), jnp.float32),                     # token count
            jnp.zeros((m, *mb_shape), xs.dtype),            # gx
        )
        carry, _ = lax.scan(tick, init, jnp.arange(ticks))
        _, _, _, gw, gh, nll, cnt, gx = carry

        data_axes = tuple(batch_axes)
        gw = lax.psum(gw, data_axes)                 # DP reduce, not over pp
        gh = lax.psum(gh, data_axes + (axis_name,))  # only last rank nonzero
        nll = lax.psum(nll, data_axes + (axis_name,))
        cnt = lax.psum(cnt, data_axes + (axis_name,))
        gx = lax.psum(gx, (axis_name,))              # only rank 0 nonzero
        loss = nll / jnp.maximum(cnt, 1.0)
        return loss, gw, gh, gx.reshape(xx.shape)

    args = [layer_params, head_params, x, targets, mask]
    specs = [pspec, hspec, xspec, dspec, dspec]
    if segs is not None:
        args.append(segs)
        specs.append(dspec)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=tuple(specs),
        out_specs=(P(), pspec, hspec, xspec),
        check_vma=False,
    )(*args)


def schedule_stats(schedule: str, p: int, m: int) -> dict:
    """Analytic cost model for the two schedules (unit = one stage-forward;
    a backward is 2 units, as is standard).

    Used by tests and capacity planning: at a FIXED activation-stash budget,
    1F1B's O(P) stash admits a much larger M and therefore a smaller bubble
    (idle) fraction — the honest form of the 1F1B claim. At equal M the two
    schedules' total durations are comparable (1F1B's uniform F+B ticks pay
    ~2P extra stage-computes of warmup/cooldown waste; GPipe pays 2(P-1)
    idle), so the win comes entirely from memory-enabled scale-up of M.
    """
    if schedule == "gpipe":
        useful = 3 * m                     # m fwd + m bwd(=2)
        total = 3 * (m + p - 1)            # fwd scan + transposed scan
        return {"ticks": m + p - 1, "stage_computes": total,
                "idle_stage_computes": total - useful,
                "idle_fraction": (total - useful) / total,
                "peak_stash_microbatches": m}
    if schedule == "1f1b":
        ticks = m + 2 * p - 1              # every tick = 1 F + 1 B
        useful = 3 * m
        total = 3 * ticks
        # The kernel statically allocates a 2P-1-slot stash regardless of M
        # (pipeline_1f1b init), so that is the honest planning number.
        return {"ticks": ticks, "stage_computes": total,
                "idle_stage_computes": total - useful,
                "idle_fraction": (total - useful) / total,
                "peak_stash_microbatches": 2 * p - 1}
    raise ValueError(f"unknown schedule {schedule!r}")


def max_microbatches_for_stash(schedule: str, p: int, stash_budget: int) -> int:
    """Largest M whose activation stash fits ``stash_budget`` microbatches."""
    if schedule == "gpipe":
        return stash_budget
    if schedule == "1f1b":
        return 10 ** 9 if stash_budget >= 2 * p - 1 else 0
    raise ValueError(f"unknown schedule {schedule!r}")


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   params: Any,
                   x: jax.Array,
                   mesh: Mesh,
                   *,
                   axis_name: str = "pp",
                   num_microbatches: int = 4,
                   batch_axes: Tuple = (("dp", "fsdp"),),
                   param_layer_axis: int = 0,
                   remat: bool = True,
                   extras: Any = None) -> jax.Array:
    """Jit-level pipeline entry: shard_map over ``axis_name``.

    ``params``: pytree whose leaves stack ALL layers on ``param_layer_axis``
    (the llama layout); the leading axis is split across pp ranks, so each
    rank's ``stage_fn`` sees [L/P, ...] leaves and scans over them.
    ``x``: global activations [B, ...] (batch sharded over ``batch_axes``).
    ``extras``: optional pytree of per-example side inputs (segment ids)
    batch-sharded like ``x`` and fed to ``stage_fn(params, mb, extras_mb)``.
    """
    fn = jax.checkpoint(stage_fn) if remat else stage_fn
    pspec = jax.tree.map(
        lambda _: P(*([None] * param_layer_axis), axis_name), params)
    xspec = P(*batch_axes)

    if extras is None:
        def body(pp, xx):
            return pipeline_spmd(fn, pp, xx, axis_name, num_microbatches)

        in_specs = (pspec, xspec)
        args = (params, x)
    else:
        def body(pp, xx, ex):
            return pipeline_spmd(fn, pp, xx, axis_name, num_microbatches,
                                 extras=ex)

        in_specs = (pspec, xspec, jax.tree.map(lambda _: xspec, extras))
        args = (params, x, extras)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=in_specs,
        out_specs=xspec,
        check_vma=False,
    )(*args)
