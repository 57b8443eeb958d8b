"""StepDriver: the fused-K product training fast path.

The promotion ROADMAP item 2 asks for: ``make_multi_step``'s ``lax.scan``
fusion (bench-proved launch amortization — PR 9's decode-side ``step_many``
is the same Podracer/Anakin discipline, arxiv 2104.06272) becomes the
Train layer's configured step driver instead of a bench-only instrument.

One driver owns the whole step path:

- **K-fused launches**: ``steps_per_launch`` batches stack into one
  [K, B, ...] tree and ONE compiled program executes K optimizer steps
  back-to-back on-device (host dispatch paid once per K). K = 1 is a
  launch like any other: a group of one, the same program shape, stamped
  by the recorder (a sequence long enough that a step is a second needs
  no amortizing, and is still watched).
- **Graceful degrade**: the 1f1b pipeline schedule (no scan support) and
  ragged tails (fewer than K batches left) fall back to the single-step
  program — loss/param-exact either way, machine-asserted in
  ``tests/test_zz_train_fast.py``.
- **Plan-carried shardings**: both programs compile through the same
  :class:`~ray_tpu.parallel.plan.Plan`, and batch placement reuses its
  cached NamedShardings (no per-call re-derivation).
- **Compute-limited accounting**: the driver splits loop wall into host
  (batch pull + stack + place) vs step (dispatch + on-device) time and
  publishes ``rt_train_steps_per_launch`` / ``rt_train_host_overhead_ratio``
  so "is the orchestration touching the gradient path?" is a metric, not
  a bench archaeology project.
- **Flight recorder**: every fused launch (K >= 1) stamps its phase walls
  {data_wait, h2d, dispatch, device_compute, host_tax, compile} plus
  K/tokens/shape/analytic-FLOPs into :class:`~ray_tpu.util.train_recorder.
  TrainRecorder` (``self.recorder``) — device-done lands via an async
  done-hook on the launch's metrics buffers, never a ``block_until_ready``
  on the step path. ``RT_TRAIN_RECORDER=0`` reduces this to one predicate
  per launch.

The K knob comes from ``FastPathConfig.steps_per_launch``
(``RunConfig.fast_path``) when the driver is built inside a
``train_loop_per_worker``; standalone callers pass it explicitly.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

from ray_tpu.util import metrics as M
from ray_tpu.util import plans

_LAUNCH_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0)


def _abstract(tree: Any) -> Any:
    """``tree``'s arrays as shapes, types and shardings."""
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=getattr(x, "sharding", None)), tree)


def _instruments():
    return (
        M.get_or_create(M.Histogram, "rt_train_steps_per_launch",
                        "Optimizer steps fused into one device launch by "
                        "the train StepDriver",
                        boundaries=_LAUNCH_BUCKETS),
        M.get_or_create(M.Gauge, "rt_train_host_overhead_ratio",
                        "Host-side fraction of the StepDriver loop (batch "
                        "pull/stack/place + report handoff vs compiled "
                        "step time)"),
    )


class StepDriver:
    """Drives (params, opt_state) through a stream of batches, K steps per
    compiled launch.

    ``batches`` may yield per-step host batches (dict leaves shaped
    [B, ...] — the driver stacks K of them) or pre-stacked [k, B, ...]
    trees from ``iter_jax_batches(stack=K)`` (the iterator advertises via
    its ``stack`` attribute), K = 1 included: a group of one is [1, B, ...].
    Anything with ``k == steps_per_launch`` runs fused; smaller tails run
    step-by-step through the single-step program.
    """

    def __init__(self, cfg: Any, optimizer: Any, *,
                 mesh: Any = None, loss_fn: Optional[Callable] = None,
                 steps_per_launch: Optional[int] = None,
                 plan: Any = None):
        from ray_tpu.parallel import train_step as ts

        if steps_per_launch is None:
            from ray_tpu.train.session import get_fast_path

            steps_per_launch = get_fast_path().steps_per_launch
        self.requested_steps_per_launch = steps_per_launch
        self.fused = ts.supports_multi_step(cfg)
        self.steps_per_launch = steps_per_launch if self.fused else 1
        if mesh is not None and plan is None:
            from ray_tpu.parallel.plan import compile_plan

            plan = compile_plan(cfg, mesh)
        self.plan = plan
        self.cfg = cfg
        self._mesh = mesh
        self._ts = ts
        # the training flight recorder (PR 20): per-launch phase records,
        # launch-gap accounting and the MFU-gap waterfall — only the fused
        # path stamps it, so a driver degraded to the single-step program
        # (the 1f1b schedule) carries a dormant recorder
        try:
            from ray_tpu.util.train_recorder import TrainRecorder

            self.recorder: Optional[Any] = TrainRecorder()
        except Exception:  # noqa: BLE001 — observability must not block
            self.recorder = None
        # what the step's kernels note of themselves as a launch traces
        # them (``util/plans``): into the recorder's own dict, so a program
        # compiled later shows too
        self._plans: Dict[str, Any] = (
            self.recorder.plans if self.recorder is not None else {})
        if self.recorder is not None and plan is not None:
            self.recorder.expert_placement = plan.expert_placement()
        if self.recorder is not None:
            self.recorder.counter_maxima = getattr(
                ts.model_family(cfg), "COUNTER_MAXIMA", ())
        self._fpt_cache: Dict[int, float] = {}
        self._single = ts.make_train_step(cfg, optimizer, loss_fn, mesh,
                                          plan=plan)
        self._multi = (ts.make_multi_step(cfg, optimizer,
                                          self.steps_per_launch, loss_fn,
                                          mesh, plan=plan)
                       if self.fused else None)
        self.launches = 0
        self.steps = 0
        self.host_s = 0.0
        self.step_s = 0.0
        # (params, opt_state) AFTER the latest launch — what an on_launch
        # checkpoint must serialize (the pre-launch trees were donated into
        # the launch and their buffers are gone)
        self.state: Optional[Tuple[Any, Any]] = None
        self._hist, self._gauge = _instruments()

    # ---- introspection ------------------------------------------------------
    def compile_count(self) -> int:
        """jit-cache entries of the ACTIVE fused program — the PR 12-style
        single-launch assertion (K steps, one executable, forever 1)."""
        fn = self._multi if self._multi is not None else self._single
        return int(fn._jit._cache_size())

    def host_overhead_ratio(self) -> float:
        total = self.host_s + self.step_s
        return (self.host_s / total) if total > 0 else 0.0

    def reset_attribution(self) -> None:
        """Zero the host/step wall accounting (call after warmup so the
        reported ratio describes the steady state, not compile time).
        Launch/step counters are left alone — callers diff those."""
        self.host_s = 0.0
        self.step_s = 0.0

    def report(self) -> Dict[str, Any]:
        """Loop-side attribution (the ``rt_train_*`` series, as a dict)."""
        return {
            "steps": self.steps,
            "launches": self.launches,
            "steps_per_launch": self.steps_per_launch,
            "host_s": round(self.host_s, 4),
            "step_s": round(self.step_s, 4),
            "host_overhead_ratio": round(self.host_overhead_ratio(), 4),
        }

    # ---- batch plumbing -----------------------------------------------------
    def _place(self, batch: Any, stacked: bool) -> Any:
        if self.plan is None:
            return batch
        return self.plan.place_batch(batch, stacked=stacked)

    @staticmethod
    def _stack(batches: List[Dict[str, Any]]) -> Dict[str, Any]:
        import numpy as np

        import jax

        return jax.tree.map(lambda *xs: np.stack(xs), *batches)

    @staticmethod
    def _lead(batch: Any) -> int:
        import jax

        leaves = jax.tree.leaves(batch)
        return leaves[0].shape[0] if leaves else 0

    def _launch_meta(self, batch: Any) -> Tuple[int, int, Tuple[int, ...]]:
        """(tokens, seq, lead-leaf shape) of a stacked batch — the
        recorder's FLOPs join reads these (shape inspection only, no
        device sync)."""
        import jax

        leaves = jax.tree.leaves(batch)
        shape = tuple(int(d) for d in leaves[0].shape) if leaves else ()
        tokens, seq = self._ts._batch_tokens(batch, stacked=True)
        return tokens, seq, shape

    def _launch_flops(self, tokens: int, seq: int) -> float:
        """Analytic FLOPs for one fused launch via ``util.flops`` —
        per-token cost cached per seq length (custom-loss configs without
        transformer geometry record launches without an MFU join)."""
        if tokens <= 0:
            return 0.0
        fpt = self._fpt_cache.get(seq)
        if fpt is None:
            try:
                from ray_tpu.util import flops as F

                fpt = float(F.train_flops_per_token(self.cfg, seq))
            except Exception:  # noqa: BLE001 — non-transformer cfg
                fpt = 0.0
            self._fpt_cache[seq] = fpt
        return tokens * fpt

    def _read_compiled(self, abstract: Tuple[Any, Any, Any]
                       ) -> Tuple[Dict[str, Dict[str, int]], Dict[str, int]]:
        """The fused program's collectives by kind and what it needs of a
        device's memory, read off the executable the launch just compiled:
        lowering the same shapes and placements again is answered from
        ``jit``'s own caches, with no second compile, and the walk of its
        text happens once, on the host, while the device runs the launch."""
        from ray_tpu.parallel.context import mesh_scope
        from ray_tpu.util import hlo_copies

        try:
            with (mesh_scope(self._mesh) if self._mesh is not None
                  else contextlib.nullcontext()):
                compiled = self._multi._jit.lower(*abstract).compile()
            return (hlo_copies.collective_inventory(compiled),
                    hlo_copies.step_memory(compiled))
        except Exception:  # noqa: BLE001 — observability must not block
            return {}, {}

    # ---- the loop -----------------------------------------------------------
    def run(self, params: Any, opt_state: Any, batches: Iterable[Any],
            on_launch: Optional[Callable[[Dict[str, Any]], None]] = None,
            stacked: Optional[bool] = None
            ) -> Tuple[Any, Any, Optional[Dict[str, Any]]]:
        """Drive the whole iterator; returns (params, opt_state, metrics of
        the last launch — leaves stay on-device; each fused metrics leaf is
        a [k] per-step array). ``on_launch`` fires once per device launch
        with those metrics (hand them to ``session.report`` — coercion is
        the drainer's job, not the loop's). ``stacked`` overrides the
        pre-stacked autodetection (``batches.stack``) for wrappers that
        lose the attribute."""
        prestacked = (getattr(batches, "stack", None) is not None
                      if stacked is None else stacked)
        K = self.steps_per_launch
        adv = getattr(batches, "stack", None)
        if prestacked and self.fused and adv is not None and adv != K:
            raise ValueError(
                f"iterator stacks {adv} batches per group but the driver "
                f"fuses {K} steps per launch — every group would silently "
                f"degrade to single-step; use iter_jax_batches(stack={K})")
        last_metrics: Optional[Dict[str, Any]] = None
        pend: List[Dict[str, Any]] = []
        it = iter(batches)
        rec = self.recorder if (self.recorder is not None
                                and self.recorder.enabled
                                and self.fused) else None
        rec_data_s = 0.0  # data_wait accumulated toward the pending launch
        rec_t0: Optional[float] = None  # epoch start of its wall
        while True:
            if rec is not None and rec_t0 is None:
                rec_t0 = time.time()
            t0 = time.perf_counter()
            try:
                batch = next(it)
            except StopIteration:
                batch = None
            if rec is not None and batch is not None:
                rec_data_s += time.perf_counter() - t0
            if batch is not None and not prestacked and self.fused:
                pend.append(batch)
                if len(pend) < K:
                    self.host_s += time.perf_counter() - t0
                    continue
                t_stack = time.perf_counter()
                batch, pend = self._stack(pend), []
                if rec is not None:
                    # the K-batch np.stack is the loader's wall too
                    rec_data_s += time.perf_counter() - t_stack
                stacked = True
            elif batch is not None:
                stacked = prestacked and self._lead(batch) >= 1
            if batch is None:
                # ragged tail of a self-stacked run: fewer than K batches
                # left — single-step them
                tail, pend = pend, []
                for b in tail:
                    params, opt_state, last_metrics = self._run_single(
                        params, opt_state, b, t_host0=t0, on_launch=on_launch)
                    t0 = time.perf_counter()
                self.host_s += time.perf_counter() - t0
                break
            if stacked and self._lead(batch) == K and self._multi is not None:
                if rec is not None:
                    data_ready_t = time.time()  # stacked batch in hand
                    tokens, seq_len, shape = self._launch_meta(batch)
                t_h2d = time.perf_counter()
                placed = self._place(batch, stacked=True)
                h2d_s = time.perf_counter() - t_h2d
                self.host_s += time.perf_counter() - t0
                n_exec = self.compile_count() if rec is not None else 0
                # the launch that compiles the program: its arguments by
                # shape and placement, taken before they are donated
                unread = (_abstract((params, opt_state, placed))
                          if rec is not None and n_exec == 0 else None)
                t1 = time.perf_counter()
                with plans.noting(self._plans):
                    params, opt_state, metrics = self._multi(
                        params, opt_state, placed)
                dispatch_s = time.perf_counter() - t1
                t_disp_end = time.time() if rec is not None else 0.0
                self.step_s += dispatch_s
                self.launches += 1
                self.steps += K
                self._observe(K)
                last_metrics = metrics
                self.state = (params, opt_state)
                seq = 0
                if rec is not None:
                    # a call that grew the jit cache spent its wall
                    # tracing+compiling — book it as compile, not dispatch
                    # (step-profiler convention, so the two can't drift)
                    compiled = self.compile_count() > n_exec
                    if unread is not None:
                        rec.collectives, rec.step_memory = \
                            self._read_compiled(unread)
                    seq = rec.record_launch(
                        t_start=rec_t0, data_wait_s=rec_data_s,
                        h2d_s=h2d_s,
                        dispatch_s=0.0 if compiled else dispatch_s,
                        compile_s=dispatch_s if compiled else 0.0,
                        data_ready_t=data_ready_t,
                        t_dispatch_end=t_disp_end, k=K, tokens=tokens,
                        batch_shape=shape,
                        flops=self._launch_flops(tokens, seq_len))
                    # async done-hook: the watcher blocks on the METRICS
                    # leaves (never the donated params) off the step path
                    rec.watch_outputs(seq, metrics)
                    rec_data_s, rec_t0 = 0.0, None
                if on_launch is not None:
                    # callback work (report handoff, checkpoint snapshot
                    # dispatch) is host-side loop time — attribute it
                    tc = time.perf_counter()
                    on_launch(metrics)
                    tax = time.perf_counter() - tc
                    self.host_s += tax
                    if rec is not None and seq:
                        rec.add_host_tax(seq, tax)
            elif stacked:
                # pre-stacked ragged tail (k < K, or any stacked input
                # once the driver degraded to K=1) — slice and single-step
                rec_data_s, rec_t0 = 0.0, None
                import jax

                k = self._lead(batch)
                if self.fused and k > K:
                    # a tail group is always SMALLER than K; a bigger one
                    # means the feed stacks more than the driver fuses and
                    # launch amortization would silently turn off — refuse
                    raise ValueError(
                        f"stacked group of {k} batches exceeds "
                        f"steps_per_launch {K}: the feed's stacking does "
                        f"not match the driver's fusion factor")
                self.host_s += time.perf_counter() - t0
                for i in range(k):
                    b = jax.tree.map(lambda x, idx=i: x[idx], batch)
                    params, opt_state, last_metrics = self._run_single(
                        params, opt_state, b, on_launch=on_launch)
            else:
                rec_data_s, rec_t0 = 0.0, None
                params, opt_state, last_metrics = self._run_single(
                    params, opt_state, batch, t_host0=t0,
                    on_launch=on_launch)
        self._gauge.set(self.host_overhead_ratio())
        return params, opt_state, last_metrics

    def _run_single(self, params, opt_state, batch, *, t_host0=None,
                    on_launch=None):
        t0 = t_host0 if t_host0 is not None else time.perf_counter()
        placed = self._place(batch, stacked=False)
        self.host_s += time.perf_counter() - t0
        t1 = time.perf_counter()
        with plans.noting(self._plans):
            params, opt_state, metrics = self._single(params, opt_state,
                                                      placed)
        self.step_s += time.perf_counter() - t1
        self.launches += 1
        self.steps += 1
        self._observe(1)
        self.state = (params, opt_state)
        if on_launch is not None:
            tc = time.perf_counter()
            on_launch(metrics)
            self.host_s += time.perf_counter() - tc
        return params, opt_state, metrics

    def _observe(self, k: int) -> None:
        try:
            self._hist.observe(float(k))
            if self.launches % 8 == 0:
                self._gauge.set(self.host_overhead_ratio())
        except Exception:  # noqa: BLE001 — telemetry must not fail the step
            pass
