"""Sharding-plan compiler: shardings as a first-class, carried object.

Before this module, every call site re-derived placement on its own:
``shard_batch`` rebuilt ``NamedSharding`` objects per batch, the step
builders left in/out shardings to GSPMD inference, and the donated-carry
convention (``donate_argnums=(0, 1)``) was repeated at each ``jax.jit``
call. A :class:`Plan` computes all of that ONCE per (config, mesh) and
carries it through ``compile_step`` → ``make_train_step`` /
``make_multi_step`` → batch placement — the Ray-Train analogy is the
placement group the Train layer carries instead of re-solving placement
per task (arxiv 1712.05889), applied to shardings.

Mode selection (the SNIPPETS ``compile_step_with_plan`` shape): a step
function whose traced body is pure GSPMD compiles under **pjit** with the
plan's explicit in/out shardings pinned; a body containing manual
``shard_map`` regions (pipeline stages, ring/Ulysses attention over the
``sp`` axis) compiles under the **shard_map** fallback — a plain ``jit``
whose manual regions bind the ambient mesh (``context.mesh_scope``), since
pinning top-level shardings across manual regions over-constrains GSPMD.

The ``jax-purity`` lint checker guards every body compiled here: a host
sync (``.item()`` / ``np.asarray`` / ``float()``) inside the traced step
is a machine-checked finding, not a code-review hope.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PJIT = "pjit"
SHARD_MAP = "shard_map"


class PlanError(ValueError):
    """A plan/compile request that cannot be satisfied, with a hint."""

    def __init__(self, message: str, hint: str = ""):
        super().__init__(message + (f" ({hint})" if hint else ""))
        self.hint = hint


def plan_mode(cfg: Any, mesh: Optional[Mesh]) -> str:
    """Pick pjit vs shard_map for ``cfg``'s step function.

    shard_map when the traced body contains manual-collective regions that
    bind the ambient mesh: a pipeline axis (gpipe/1f1b stages), a
    non-trivial ``sp`` mesh axis, or a sequence-parallel attention impl.
    Everything else is pure GSPMD → pjit with explicit shardings.
    """
    if getattr(cfg, "pipeline_axis", None) is not None:
        return SHARD_MAP
    if getattr(cfg, "attn_impl", "") in ("ring", "ulysses"):
        return SHARD_MAP
    if mesh is not None and mesh.shape.get("sp", 1) > 1:
        return SHARD_MAP
    return PJIT


@dataclasses.dataclass
class Plan:
    """In/out shardings + donation policy for one (config, mesh) pair.

    State shardings (params + optimizer state) are derived lazily from the
    model family's :class:`ShardingRules` via ``eval_shape`` — no params
    ever materialize — and cached. Batch placements are cached per
    (rank, seq-divisibility, stacked) key so repeated ``place_batch``
    calls reuse the same ``NamedSharding`` objects instead of
    reconstructing them per step.
    """

    mesh: Mesh
    mode: str                       # PJIT | SHARD_MAP
    cfg: Any = None                 # model config (state-sharding source)
    rules: Any = None               # ShardingRules (lazy from cfg's family)
    donate_argnums: Tuple[int, ...] = (0, 1)   # donated carries

    def __post_init__(self):
        self._lock = threading.Lock()
        # rt: guarded-by(_lock)
        self._batch_cache: Dict[Tuple, NamedSharding] = {}
        # rt: guarded-by(_lock)
        self._state_shardings: Dict[int, Tuple[Any, Any]] = {}
        # rt: guarded-by(_lock)
        self._opt_refs: Dict[int, Any] = {}

    # ---- state (params / opt_state) shardings -------------------------------
    def _rules(self):
        if self.rules is None:
            from ray_tpu.parallel.train_step import model_family

            fam = model_family(self.cfg)
            self.rules = fam.sharding_rules(
                pipeline=getattr(self.cfg, "pipeline_axis", None) is not None)
        return self.rules

    def state_shardings(self, optimizer) -> Tuple[Any, Any]:
        """(params_shardings, opt_state_shardings) trees for ``cfg`` under
        ``optimizer`` — computed once per optimizer identity via
        ``eval_shape`` (abstract; no arrays allocated)."""
        key = id(optimizer)
        with self._lock:
            # pin the optimizer so a collected object can't hand its id
            # (and this cache entry) to a different optimizer
            self._opt_refs[key] = optimizer
            hit = self._state_shardings.get(key)
        if hit is not None:
            return hit
        from ray_tpu.parallel.train_step import model_family

        fam = model_family(self.cfg)
        rules = self._rules()
        abstract = jax.eval_shape(lambda r: fam.init_params(r, self.cfg),
                                  jax.random.key(0))
        params_sh = rules.tree_shardings(abstract, self.mesh)
        # optimizer-state paths embed the param subtree paths (mu/nu/...),
        # so the same path rules resolve them; scalars fall to replicated
        opt_abstract = jax.eval_shape(optimizer.init, abstract)
        opt_sh = rules.tree_shardings(opt_abstract, self.mesh)
        out = (params_sh, opt_sh)
        with self._lock:
            self._state_shardings[key] = out
        return out

    def expert_placement(self) -> Optional[str]:
        """How the plan places a sparse family's expert matrices on this
        mesh (``moe.expert_placement`` of what the rules resolve for
        ``layers/e_gate``); None for a family without experts."""
        from ray_tpu.parallel.train_step import model_family

        placement = getattr(model_family(self.cfg), "expert_placement", None)
        if placement is None:
            return None
        c = self.cfg
        return placement(self._rules().spec_for(
            "layers/e_gate",
            (c.n_expert_layers, c.experts_held, c.d_model, c.d_ff),
            self.mesh))

    # ---- batch placement ----------------------------------------------------
    def batch_sharding(self, ndim: int, shard_seq: bool,
                       stacked: bool) -> NamedSharding:
        """The cached NamedSharding for one batch leaf: batch dim over
        (dp, fsdp); sequence over sp when it divides (shard_seq); a
        stacked [K, ...] leaf keeps its leading step axis replicated."""
        key = (ndim, shard_seq, stacked)
        with self._lock:
            sh = self._batch_cache.get(key)
            if sh is None:
                lead = (None,) if stacked else ()
                if shard_seq:
                    spec = P(*lead, ("dp", "fsdp"), "sp")
                else:
                    spec = P(*lead, ("dp", "fsdp"))
                sh = NamedSharding(self.mesh, spec)
                self._batch_cache[key] = sh
            return sh

    def place_batch(self, batch: Any, stacked: bool = False) -> Any:
        """Place a host batch onto the mesh (the one implementation behind
        ``train_step.shard_batch``): batch dim over (dp, fsdp), sequence
        over a non-trivial sp axis when it divides evenly. ``stacked``
        handles multi-step batches [K, B, ...]."""
        sp = self.mesh.shape.get("sp", 1)
        bdim = 1 if stacked else 0

        def place(x):
            shard_seq = (x.ndim >= bdim + 2 and sp > 1
                         and x.shape[bdim + 1] % sp == 0)
            target = self.batch_sharding(x.ndim, shard_seq, stacked)
            if getattr(x, "sharding", None) == target:
                return x  # already placed (pre-stacked device feed)
            return jax.device_put(x, target)

        return jax.tree.map(place, batch)

    def replicated(self) -> NamedSharding:
        """The fully-replicated sharding (metrics outputs)."""
        with self._lock:
            sh = self._batch_cache.get("replicated")
            if sh is None:
                sh = NamedSharding(self.mesh, P())
                self._batch_cache["replicated"] = sh
            return sh


def compile_plan(cfg: Any, mesh: Mesh, rules: Any = None,
                 donate_argnums: Tuple[int, ...] = (0, 1)) -> Plan:
    """Build the sharding plan for ``cfg`` on ``mesh``."""
    if mesh is None:
        raise PlanError("compile_plan needs a mesh",
                        "pass the Mesh the step will run under")
    return Plan(mesh=mesh, mode=plan_mode(cfg, mesh), cfg=cfg, rules=rules,
                donate_argnums=donate_argnums)


def compile_step(body: Callable, plan: Optional[Plan], *,
                 in_shardings: Any = None, out_shardings: Any = None,
                 donate_argnums: Optional[Tuple[int, ...]] = None,
                 static_argnums: Tuple[int, ...] = ()) -> Callable:
    """Compile one step function under the plan.

    pjit mode: ``jax.jit`` with the plan's explicit in/out shardings
    (both or neither — one without the other is a config bug, the
    SNIPPETS contract). shard_map mode: plain ``jax.jit`` with donation
    only; the body's manual regions bind the ambient ``mesh_scope`` and
    GSPMD infers the rest from the (already plan-placed) arguments.

    No plan ⇒ legacy single-process behavior (``jax.jit`` + donation),
    so mesh-less callers (unit profiling, host-only tests) keep working.
    """
    donate = donate_argnums if donate_argnums is not None else \
        (plan.donate_argnums if plan is not None else (0, 1))
    kwargs: Dict[str, Any] = {"donate_argnums": donate}
    if static_argnums:
        kwargs["static_argnums"] = static_argnums
    if plan is None or plan.mode == SHARD_MAP:
        if (in_shardings is None) != (out_shardings is None):
            raise PlanError(
                "compile_step requires both in_shardings and out_shardings "
                "when either is given",
                "pass both or neither; shard_map mode infers from args")
        return jax.jit(body, **kwargs)
    if (in_shardings is None) != (out_shardings is None):
        raise PlanError(
            "compile_step requires both in_shardings and out_shardings "
            "when using pjit",
            "pass both sharding arguments or omit them to infer from args")
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
        kwargs["out_shardings"] = out_shardings
    return jax.jit(body, **kwargs)


# ---- per-mesh placement-plan cache ------------------------------------------
# shard_batch (train_step.py) is called per batch from every trainer loop;
# the plan cache keeps one placement Plan per mesh so the NamedShardings
# are derived once, not per call site.
_placement_lock = threading.Lock()
_placement_plans: Dict[Mesh, Plan] = {}  # rt: guarded-by(_placement_lock)


def placement_plan(mesh: Mesh) -> Plan:
    """The cached batch-placement plan for ``mesh`` (mode-agnostic)."""
    with _placement_lock:
        plan = _placement_plans.get(mesh)
        if plan is None:
            if len(_placement_plans) > 64:  # meshes are few; tests make many
                _placement_plans.clear()
            plan = Plan(mesh=mesh, mode=PJIT)
            _placement_plans[mesh] = plan
        return plan
