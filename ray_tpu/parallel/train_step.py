"""Sharded train-step construction: init, step, and mesh auto-layout.

The jit-compiled training step that every trainer in the Train layer runs.
Parameters and optimizer state are placed by the model's rules, each leaf
resolved against its shape and the mesh (a sparse model's experts whole on
a chip where they split evenly over ``ep`` x ``fsdp``); the step pins those
shardings and GSPMD derives every collective from them: all-gathers of the
fsdp-sharded weights, reduce-scatters of their gradients, all-reduces over
tp, and the experts' ``[E, C, d]`` rows to their owners and back, on ICI.
What a compiled step ended up with is in its driver's recorder
(``TrainRecorder.collectives``).
Gradient synchronization never touches the object plane — the property the
reference maintains with NCCL outside Ray (SURVEY.md §3.4), achieved here by
construction.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.plan import Plan, compile_plan, compile_step, placement_plan
from ray_tpu.parallel.sharding import ShardingRules


#: the outermost ``jax.named_scope`` of every device operation a train step
#: writes, whichever stack it trains (``llama._block``, ``moe._moe_block``,
#: ``moe._patterned_layer``): the batch's way in, a layer's mixer by its
#: kind (a sparse layer's indexer under three of its own: its scores, its
#: threshold and choice, its loss), its dense or routed feed-forward, a
#: widened stream's hyper-connections, a prediction module (everything of
#: it), the loss, the update. A device
#: trace is read by these names (``benchmark/lib/trace.py:scope_of`` keeps
#: an operation's outermost one, so no scope stands round a scan of layers:
#: it would swallow every name inside). What carries none is the scans'
#: own stacking and slicing of their per-layer operands and results.
STEP_SCOPES = ("embed", "attn_full", "attn_window", "attn_kda", "attn_mla",
               "attn_eva", "attn_sparse", "index_scores", "index_select",
               "index_loss", "hyper_mix", "mlp", "moe_router", "moe_dispatch",
               "moe_experts", "moe_combine", "moe_shared", "mtp", "loss_head",
               "optimizer")


def default_optimizer(lr: float = 3e-4, weight_decay: float = 0.1,
                      warmup_steps: int = 100, total_steps: int = 10000,
                      grad_clip: float = 1.0) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, lr, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=0.9, b2=0.95, weight_decay=weight_decay),
    )


def model_family(cfg):
    """The module implementing ``cfg``'s family (init_params / lm_loss /
    sharding_rules) — llama-family dense models or the sparse-MoE family."""
    from ray_tpu.models import moe

    return moe if isinstance(cfg, moe.MoEConfig) else llama


def _value_and_grad(cfg, loss_fn: Optional[Callable]) -> Callable:
    """``(params, batch) -> (loss, grads, stats)`` of the family's
    ``loss_and_stats``, or of ``loss_fn`` (a loss alone: no stats).
    ``stats`` is what the family's forward counts beside its loss, by name
    (a patterned sparse model's routing); most count nothing."""
    if loss_fn is None:
        loss_and_stats = model_family(cfg).loss_and_stats
    else:
        def loss_and_stats(params, batch, cfg):
            return loss_fn(params, batch, cfg), {}

    def grad_fn(params, batch):
        (loss, stats), grads = jax.value_and_grad(
            lambda p: loss_and_stats(p, batch, cfg), has_aux=True)(params)
        return loss, grads, stats
    return grad_fn


def _update(cfg, optimizer, params, opt_state, grads, stats):
    """What a step does once it has its gradients, under the scope
    ``optimizer`` of a device trace: ``(params, opt_state, metrics)`` after
    the optimizer's update (the clip by their global norm, AdamW), with the
    gradients' norm and ``stats`` among the metrics. A family with buffers
    (leaves the step reads and no gradient moves: ``buffer_updates``) puts
    their own movement in the optimizer's place and takes what it read out
    of ``stats``."""
    with jax.named_scope("optimizer"):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        move = getattr(model_family(cfg), "buffer_updates", None)
        if move is not None:
            updates, stats = move(cfg, params, updates, stats)
        params = optax.apply_updates(params, updates)
        gnorm = optax.global_norm(grads)
    return params, opt_state, {"grad_norm": gnorm, **stats}


def init_sharded_state(rng: jax.Array, cfg: llama.LlamaConfig, mesh: Mesh,
                       optimizer: optax.GradientTransformation,
                       rules: Optional[ShardingRules] = None):
    """Initialize params+opt state directly into their target shardings.

    Params are produced BY a jitted init with explicit out_shardings, so no
    host-side full copy ever materializes (essential for 7B+). The optimizer
    state is pinned the same way, by the same path rules (its paths embed
    the param paths): adam's moments are ``zeros_like`` and depend on no
    input, so nothing propagates to them and left alone they all land on
    the first device.
    """
    fam = model_family(cfg)
    rules = rules or fam.sharding_rules(pipeline=cfg.pipeline_axis is not None)
    abstract = jax.eval_shape(lambda r: fam.init_params(r, cfg), rng)
    out_shardings = rules.tree_shardings(abstract, mesh)
    params = jax.jit(lambda r: fam.init_params(r, cfg),
                     out_shardings=out_shardings)(rng)
    opt_shardings = rules.tree_shardings(
        jax.eval_shape(optimizer.init, abstract), mesh)
    opt_state = jax.jit(optimizer.init, out_shardings=opt_shardings)(params)
    return params, opt_state


def make_train_step(cfg: llama.LlamaConfig,
                    optimizer: optax.GradientTransformation,
                    loss_fn: Callable = None,
                    mesh: Optional[Mesh] = None,
                    plan: Optional[Plan] = None) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics), donated.

    ``mesh`` makes itself ambient during tracing (``context.mesh_scope``) so
    model-internal shard_map regions (ring attention, pipeline stages) can
    find it. With a mesh (or an explicit ``plan``), the step compiles
    through the sharding :class:`Plan` — pjit with pinned in/out shardings
    for pure-GSPMD bodies, the shard_map fallback for manual-region bodies
    — instead of re-deriving placement per call site.
    """
    custom_loss = loss_fn is not None
    use_1f1b = not supports_multi_step(cfg)
    if use_1f1b:
        if loss_fn is not None:
            raise ValueError("1f1b computes its own loss inside the "
                             "pipeline; custom loss_fn unsupported")
        if model_family(cfg) is not llama:
            raise NotImplementedError("1f1b schedule: dense llama only")

        def grad_fn(params, batch):
            return (*llama.lm_loss_and_grads_1f1b(params, batch, cfg), {})
    else:
        grad_fn = _value_and_grad(cfg, loss_fn)

    def step(params, opt_state, batch):
        loss, grads, stats = grad_fn(params, batch)
        params, opt_state, metrics = _update(cfg, optimizer, params,
                                             opt_state, grads, stats)
        return params, opt_state, {"loss": loss, **metrics}

    if plan is None and mesh is not None:
        plan = compile_plan(cfg, mesh)
    jstep = compile_step(step, plan,
                         **_plan_shardings(plan, optimizer, custom_loss,
                                           stacked=False))
    return _with_mesh(jstep, mesh, plan)


def _plan_shardings(plan: Optional[Plan], optimizer, custom_loss: bool,
                    stacked: bool) -> Dict[str, Any]:
    """compile_step kwargs for the pjit path: explicit state shardings from
    the plan, the batch pinned by a prefix sharding (batch dim over
    (dp, fsdp) — the same placement ``shard_batch`` applies), metrics
    replicated. A custom loss_fn may train custom params the family rules
    don't describe, so it stays on sharding inference."""
    from ray_tpu.parallel.plan import PJIT

    if plan is None or plan.mode != PJIT or custom_loss:
        return {}
    params_sh, opt_sh = plan.state_shardings(optimizer)
    batch_sh = plan.batch_sharding(2, False, stacked)
    return {"in_shardings": (params_sh, opt_sh, batch_sh),
            "out_shardings": (params_sh, opt_sh, plan.replicated())}


def supports_multi_step(cfg) -> bool:
    """Whether ``make_multi_step`` can fuse K steps for this config — the
    1f1b schedule's manual interleave cannot ride a ``lax.scan`` carry, so
    fused drivers must degrade to single-step there."""
    return not (getattr(cfg, "pipeline_axis", None) is not None
                and getattr(cfg, "pipeline_schedule", "gpipe") == "1f1b")


def _batch_tokens(batch, stacked: bool = False) -> Tuple[int, int]:
    """(trained tokens, seq len) of one step's batch. Token batches are
    [B, S+1] ([K, B, S+1] stacked): S positions train per row. Custom
    loss_fn batches without a usable token-shaped leaf yield (0, 1): the
    driver's recorder then counts launches without tokens."""
    need = 3 if stacked else 2
    leaf = batch.get("tokens") if isinstance(batch, dict) else None
    if leaf is None or getattr(leaf, "ndim", 0) < need:
        cands = [x for x in jax.tree.leaves(batch)
                 if getattr(x, "ndim", 0) >= need]
        if not cands:
            return 0, 1
        leaf = cands[0]
    if stacked:
        k, b, s1 = leaf.shape[0], leaf.shape[1], leaf.shape[2]
        return k * b * max(1, s1 - 1), max(1, s1 - 1)
    b, s1 = leaf.shape[0], leaf.shape[1]
    return b * max(1, s1 - 1), max(1, s1 - 1)


def _with_mesh(jstep, mesh, plan: Optional[Plan] = None):
    """The (params, opt_state, batch) entry point every trainer calls:
    ``jstep`` under the ambient mesh."""

    def call(params, opt_state, batch):
        if mesh is None:
            return jstep(params, opt_state, batch)
        from ray_tpu.parallel.context import mesh_scope

        with mesh_scope(mesh):
            return jstep(params, opt_state, batch)

    # the compiled program and plan ride along so drivers can assert
    # single-launch fusion via the jit cache and reuse the placement plan
    call._jit = jstep
    call._plan = plan
    return call


def make_multi_step(cfg: llama.LlamaConfig,
                    optimizer: optax.GradientTransformation,
                    n_steps: int,
                    loss_fn: Callable = None,
                    mesh: Optional[Mesh] = None,
                    plan: Optional[Plan] = None) -> Callable:
    """K train steps fused into ONE compiled program via ``lax.scan``.

    (params, opt_state, batches) -> (params, opt_state, metrics) where each
    leaf of ``batches`` is stacked [K, ...] (one slice per step) and
    ``metrics`` holds per-step [K] arrays.

    TPU-idiomatic launch amortization: one dispatch executes K optimizer
    steps back to back on-device, so per-launch host/runtime overhead
    (dispatch, XLA launch latency) is paid once per K
    steps instead of per step — the standard trick for host-bound training
    loops.
    Works under any mesh: the scanned body is the same sharded step GSPMD
    already compiles.
    """
    if not supports_multi_step(cfg):
        raise NotImplementedError("multi-step scan over the 1f1b schedule "
                                  "is unsupported; use gpipe or single-step "
                                  "(StepDriver degrades automatically)")
    custom_loss = loss_fn is not None
    grad_fn = _value_and_grad(cfg, loss_fn)

    def body(carry, batch):
        params, opt_state = carry
        loss, grads, stats = grad_fn(params, batch)
        params, opt_state, metrics = _update(cfg, optimizer, params,
                                             opt_state, grads, stats)
        return (params, opt_state), {"loss": loss, **metrics}

    def steps(params, opt_state, batches):
        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), batches, length=n_steps)
        return params, opt_state, metrics

    if plan is None and mesh is not None:
        plan = compile_plan(cfg, mesh)
    jsteps = compile_step(steps, plan,
                          **_plan_shardings(plan, optimizer, custom_loss,
                                            stacked=True))
    return _with_mesh(jsteps, mesh, plan)


def shard_batch(batch: Dict[str, jax.Array], mesh: Mesh,
                stacked: bool = False) -> Dict[str, jax.Array]:
    """Place a host batch onto the mesh: batch dim over (dp, fsdp), sequence
    over sp when the mesh has a non-trivial sp axis (context parallelism).
    ``stacked=True`` handles multi-step batches [K, B, ...] (make_multi_step):
    the leading step axis stays replicated, batch/seq shard as usual.

    Delegates to the per-mesh cached :class:`Plan` (``plan.placement_plan``)
    so the NamedShardings are derived once per mesh, not per call.
    Sequence rides sp only when it divides evenly (token batches are
    [B, S+1] — odd — so they stay seq-replicated; GSPMD re-shards the
    [B, S] slice at the shard_map boundary)."""
    return placement_plan(mesh).place_batch(batch, stacked=stacked)


def auto_mesh(n_devices: int, devices=None, *, tp: Optional[int] = None,
              sp: int = 1, pp: int = 1, dp: int = 1, ep: int = 1
              ) -> Tuple[Mesh, MeshConfig]:
    """A sensible layout for n devices: fsdp-dominant with a tp=min(4, n)
    inner axis when n allows — the FSDP+TP sweet spot at the 7B scale.
    sp/pp/ep carve off sequence/pipeline/expert axes."""
    if tp is None:
        tp = 1
        for cand in (4, 2):
            if (n_devices % (cand * sp * pp * dp * ep) == 0
                    and n_devices >= cand * 2):
                tp = cand
                break
    cfg = MeshConfig.for_devices(n_devices, tp=tp, sp=sp, pp=pp, dp=dp, ep=ep)
    return make_mesh(cfg, devices), cfg
