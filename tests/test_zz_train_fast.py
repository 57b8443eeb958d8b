"""Fused-K training fast path (ROADMAP item 2, PR 13).

Covers the four tentpole legs end to end:
  - StepDriver fused-K loss/param exactness vs K single steps (fixed
    seeds), single-launch-per-K via the jit cache (PR 12 style), and the
    1f1b / ragged-tail graceful degrade;
  - the sharding-plan compiler's pjit-vs-shard_map selection and cached
    batch placement parity with shard_batch;
  - off-step-path reporting: the step loop never blocks on a slow
    checkpoint, metrics reach the driver as host scalars;
  - the async checkpoint fence (an unfinished save can't be acked) and
    the CheckpointManager's score-once heap retention;
  - the stacked, prefetched jax-batch data plane and its
    compute-limited verdict.

Named test_zz_* so it sorts late (tier-1 ordering discipline).
"""

import os
import pickle
import threading
import time

import numpy as np
import pytest


# ---- fused driver ----------------------------------------------------------

def test_fused_driver_parity_ragged_tail_and_single_launch():
    """StepDriver at K=4 over 10 batches (2 fused launches + a ragged tail
    of 2 single steps) matches 10 sequential single steps bit-for-tolerance
    on fixed seeds, and the timed launches add ZERO jit-cache entries."""
    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.train.driver import StepDriver

    N, K = 10, 4
    cfg = llama.PRESETS["debug"]
    mesh, _ = ts.auto_mesh(8, tp=2)
    optimizer = ts.default_optimizer(total_steps=100)
    toks = np.asarray(jax.random.randint(
        jax.random.key(7), (N, 4, 65), 0, cfg.vocab_size, dtype=jnp.int32))

    # reference: N single steps
    p1, s1 = ts.init_sharded_state(jax.random.key(0), cfg, mesh, optimizer)
    step = ts.make_train_step(cfg, optimizer, mesh=mesh)
    losses = []
    for k in range(N):
        b = ts.shard_batch({"tokens": toks[k]}, mesh)
        p1, s1, m = step(p1, s1, b)
        losses.append(float(m["loss"]))

    # fused driver over the same batches
    p2, s2 = ts.init_sharded_state(jax.random.key(0), cfg, mesh, optimizer)
    driver = StepDriver(cfg, optimizer, mesh=mesh, steps_per_launch=K)
    seen = []
    p2, s2, _ = driver.run(
        p2, s2, ({"tokens": toks[i]} for i in range(N)),
        on_launch=lambda m: seen.append(np.atleast_1d(np.asarray(m["loss"])))
    )
    assert driver.steps == N
    assert driver.launches == 2 + 2  # 2 fused + 2 ragged singles
    fused_losses = np.concatenate(seen)
    np.testing.assert_allclose(fused_losses, np.asarray(losses), rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)

    # single-launch per K, PR 12 style: further launches must never
    # recompile (the cache may hold the init-type + steady-type pair, but
    # it stops growing once warm)
    cache_warm = driver.compile_count()
    p2, s2, _ = driver.run(p2, s2, ({"tokens": toks[i]} for i in range(K)))
    assert driver.compile_count() == cache_warm
    # the driver's loop-side attribution moved
    rep = driver.report()
    assert rep["steps"] == N + K and rep["launches"] == 5
    assert 0.0 <= rep["host_overhead_ratio"] <= 1.0


def test_a_launch_of_one_step_is_a_launch():
    """K=1 is no special case (PR 48): a group of one, [1, B, S+1], runs
    the fused program and the recorder stamps it; per-step batches are
    stacked by the driver like any K; both give the single-step program's
    losses and parameters."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.train.driver import StepDriver

    N = 3
    cfg = llama.PRESETS["debug"]
    opt = ts.default_optimizer(total_steps=100)
    toks = np.asarray(jax.random.randint(
        jax.random.key(5), (N, 2, 33), 0, cfg.vocab_size, dtype=jnp.int32))

    def fresh():
        params = llama.init_params(jax.random.key(0), cfg)
        return params, jax.jit(opt.init)(params)

    p1, s1 = fresh()
    step = ts.make_train_step(cfg, opt)
    want = []
    for i in range(N):
        p1, s1, m = step(p1, s1, {"tokens": toks[i]})
        want.append(float(m["loss"]))

    for stacked in (True, False):
        p2, s2 = fresh()
        driver = StepDriver(cfg, opt, steps_per_launch=1)
        assert driver.fused and driver.steps_per_launch == 1
        feed = ({"tokens": toks[i][None] if stacked else toks[i]}
                for i in range(N))
        seen = []
        p2, s2, _ = driver.run(p2, s2, feed, stacked=stacked,
                               on_launch=lambda m: seen.append(m["loss"]))
        assert driver.launches == N and driver.steps == N
        assert all(np.shape(x) == (1,) for x in seen)  # a [k] leaf, k = 1
        np.testing.assert_allclose(np.concatenate(seen), want, rtol=2e-4)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        rec = driver.recorder
        deadline = time.time() + 10.0
        while time.time() < deadline and rec.summary().get("in_flight"):
            time.sleep(0.01)
        recs = rec.launches()
        assert len(recs) == N and all(r["k"] == 1 and "t_done" in r
                                      for r in recs)
        assert driver.compile_count() == 1  # one program, every launch
        rec.close()


def test_driver_refuses_oversized_stacked_groups():
    """A feed stacking MORE batches per group than the driver fuses would
    silently single-step everything — the driver refuses instead."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.train.driver import StepDriver

    cfg = llama.PRESETS["debug"]
    opt = ts.default_optimizer(total_steps=10)
    params = llama.init_params(jax.random.key(0), cfg)
    opt_state = jax.jit(opt.init)(params)
    driver = StepDriver(cfg, opt, steps_per_launch=2)
    toks = jnp.zeros((4, 2, 33), dtype=jnp.int32)  # group of 4 > K=2

    class Feed:
        stack = 4

        def __iter__(self):
            yield {"tokens": toks}

    with pytest.raises(ValueError, match="stack"):
        driver.run(params, opt_state, Feed())
    with pytest.raises(ValueError, match="exceeds"):
        driver.run(params, opt_state, iter([{"tokens": toks}]),
                   stacked=True)


def test_save_pytree_default_follows_session_async_checkpoint(tmp_path):
    """blocking=None resolves from FastPathConfig.async_checkpoint inside
    a session (and blocks standalone)."""
    from ray_tpu.train import session as session_mod
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.config import FastPathConfig
    from ray_tpu.train.session import TrainContext, TrainSession

    calls = []
    orig = Checkpoint.save_pytree
    orig_sync = Checkpoint._save_pytree_sync

    def spying_sync(self, tree, name):
        calls.append(("sync-write", name))

    ckpt = Checkpoint.from_directory(str(tmp_path / "ck"))
    os.makedirs(ckpt.path, exist_ok=True)
    try:
        Checkpoint._save_pytree_sync = spying_sync
        # standalone: default blocks (write happens before return)
        orig(ckpt, {"x": np.zeros(2)})
        assert calls == [("sync-write", "state")]
        # in-session with async_checkpoint=True: returns with the write
        # pending on the writer thread
        session_mod.init_session(TrainSession(
            TrainContext(0, 1),
            fast_path=FastPathConfig(async_checkpoint=True)))
        slow = threading.Event()
        Checkpoint._save_pytree_sync = \
            lambda self, tree, name: slow.wait(2)
        orig(ckpt, {"x": np.zeros(2)})
        assert ckpt._pending, "async default did not use the writer thread"
        slow.set()
        ckpt.wait_pending()
    finally:
        Checkpoint._save_pytree_sync = orig_sync
        session_mod.clear_session()


def test_driver_1f1b_degrades_to_single_step():
    """The 1f1b schedule can't ride lax.scan: make_multi_step refuses, and
    the StepDriver degrades the requested K to 1 instead of crashing."""
    import dataclasses

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.train.driver import StepDriver

    cfg = dataclasses.replace(llama.PRESETS["debug"], pipeline_axis="pp",
                              pipeline_schedule="1f1b")
    assert not ts.supports_multi_step(cfg)
    with pytest.raises(NotImplementedError):
        ts.make_multi_step(cfg, ts.default_optimizer(), 4)
    driver = StepDriver(cfg, ts.default_optimizer(), steps_per_launch=4)
    assert driver.requested_steps_per_launch == 4
    assert driver.steps_per_launch == 1 and not driver.fused
    assert ts.supports_multi_step(llama.PRESETS["debug"])


# ---- sharding-plan compiler ------------------------------------------------

def test_plan_mode_selection_and_placement_parity():
    """pjit for pure-GSPMD configs; shard_map for manual-region bodies
    (pipeline axis, sp mesh axis, ring/ulysses attention). place_batch is
    shard_batch (same shardings) with the NamedShardings cached."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.plan import (
        PJIT,
        SHARD_MAP,
        compile_plan,
        placement_plan,
        plan_mode,
    )

    cfg = llama.PRESETS["debug"]
    mesh, _ = ts.auto_mesh(8, tp=2)
    assert plan_mode(cfg, mesh) == PJIT
    assert plan_mode(
        dataclasses.replace(cfg, pipeline_axis="pp"), mesh) == SHARD_MAP
    assert plan_mode(
        dataclasses.replace(cfg, attn_impl="ring"), mesh) == SHARD_MAP
    sp_mesh, _ = ts.auto_mesh(8, tp=1, sp=2)
    assert plan_mode(cfg, sp_mesh) == SHARD_MAP

    plan = compile_plan(cfg, mesh)
    toks = jnp.zeros((8, 33), dtype=jnp.int32)
    via_plan = plan.place_batch({"tokens": toks})
    via_shard_batch = ts.shard_batch({"tokens": toks}, mesh)
    assert via_plan["tokens"].sharding == via_shard_batch["tokens"].sharding
    # stacked placement keeps the leading step axis replicated
    stacked = plan.place_batch({"tokens": jnp.zeros((2, 8, 33), jnp.int32)},
                               stacked=True)
    spec = stacked["tokens"].sharding.spec
    assert spec[0] is None
    # the cache hands back the SAME NamedSharding object per key
    sh1 = plan.batch_sharding(2, False, False)
    sh2 = plan.batch_sharding(2, False, False)
    assert sh1 is sh2
    # shard_batch's per-mesh plan is cached too
    assert placement_plan(mesh) is placement_plan(mesh)

    # explicit state shardings match what init_sharded_state produces
    optimizer = ts.default_optimizer(total_steps=10)
    params_sh, _opt_sh = plan.state_shardings(optimizer)
    params, _ = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                      optimizer)
    live = jax.tree_util.tree_leaves(
        jax.tree.map(lambda x: x.sharding, params))
    planned = jax.tree_util.tree_leaves(params_sh)
    assert live == planned


def test_compile_step_requires_both_shardings():
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.plan import PlanError, compile_plan, compile_step

    plan = compile_plan(llama.PRESETS["debug"], ts.auto_mesh(8, tp=2)[0])
    with pytest.raises(PlanError, match="both"):
        compile_step(lambda x: x, plan, in_shardings=(None,),
                     donate_argnums=())


# ---- off-step-path reporting ----------------------------------------------

class _SlowCheckpoint:
    """Checkpoint stand-in whose fence takes `delay` seconds."""

    def __init__(self, delay):
        self.delay = delay
        self.fenced = threading.Event()

    def wait_pending(self, timeout=None):
        time.sleep(self.delay)
        self.fenced.set()


def test_report_drainer_never_blocks_step_loop():
    """Three reports with a slow checkpoint return in ~0 time on the
    calling thread; the drainer fences each checkpoint BEFORE the driver
    sees its round, and metrics arrive as host scalars."""
    import jax.numpy as jnp

    from ray_tpu.train.session import TrainContext, TrainSession

    session = TrainSession(TrainContext(0, 1))
    slow = [_SlowCheckpoint(0.15) for _ in range(3)]
    # made before the clock starts: the product compiles in a cold process
    # (0.36 s), and the 0.1 s is for the hand-off
    losses = [jnp.float32(i) * 2 for i in range(3)]
    t0 = time.perf_counter()
    for i, (ck, loss) in enumerate(zip(slow, losses)):
        session.report({"step": i, "loss": loss}, ck)
    handoff_s = time.perf_counter() - t0
    assert handoff_s < 0.1, f"report blocked the loop: {handoff_s:.3f}s"
    session.finish()
    rounds = [session.results.get(timeout=5) for _ in range(4)]
    assert [r["type"] for r in rounds] == ["report"] * 3 + ["done"]
    for i, r in enumerate(rounds[:3]):
        assert r["metrics"]["step"] == i
        # coerced on the drainer: a python float, not a live jax.Array
        assert isinstance(r["metrics"]["loss"], float)
        assert r["metrics"]["loss"] == pytest.approx(2.0 * i)
        assert r["checkpoint"].fenced.is_set(), \
            "an unfenced checkpoint crossed the ack boundary"


def test_report_sync_mode_coerces_on_caller():
    from ray_tpu.train.config import FastPathConfig
    from ray_tpu.train.session import TrainContext, TrainSession

    session = TrainSession(TrainContext(0, 1),
                           fast_path=FastPathConfig(async_report=False))
    ck = _SlowCheckpoint(0.05)
    t0 = time.perf_counter()
    session.report({"v": np.float64(1.5)}, ck)
    assert time.perf_counter() - t0 >= 0.05  # fence ran on the caller
    got = session.results.get(timeout=2)
    assert got["metrics"]["v"] == 1.5 and isinstance(got["metrics"]["v"],
                                                     float)
    session.finish()
    assert session.results.get(timeout=2)["type"] == "done"


def test_drainer_error_surfaces_as_error_round():
    from ray_tpu.train.session import TrainContext, TrainSession

    class _BrokenCheckpoint:
        def wait_pending(self, timeout=None):
            raise RuntimeError("disk gone")

    session = TrainSession(TrainContext(0, 1))
    session.report({"ok": 1}, _BrokenCheckpoint())
    got = session.results.get(timeout=5)
    assert got["type"] == "error"
    assert "disk gone" in repr(got["error"])


# ---- async checkpoint fence -------------------------------------------------

def test_async_save_pytree_fence_and_pickle(tmp_path):
    import jax.numpy as jnp

    from ray_tpu.train.checkpoint import Checkpoint

    tree = {"w": jnp.arange(8.0), "b": jnp.float32(3.0)}
    ckpt = Checkpoint.from_directory(str(tmp_path / "ck"))
    os.makedirs(ckpt.path, exist_ok=True)
    ckpt.save_pytree(tree, "state", blocking=False)
    # pickling IS the ack boundary: the reconstructed handle must see a
    # complete directory
    clone = pickle.loads(pickle.dumps(ckpt))
    back = clone.load_pytree("state")
    np.testing.assert_array_equal(np.asarray(back["w"]),
                                  np.arange(8.0))
    assert float(back["b"]) == 3.0


def test_two_overlapping_async_saves_are_both_durable(tmp_path):
    """Two ``save_pytree(blocking=False)`` of different trees to two names,
    started back to back: one writer at a time, so neither breaks the
    other's temporary directory and both are whole after the fence."""
    import jax.numpy as jnp

    from ray_tpu.train.checkpoint import Checkpoint

    for r in range(20):
        ckpt = Checkpoint.from_directory(str(tmp_path / f"ck{r}"))
        os.makedirs(ckpt.path, exist_ok=True)
        a = {"w": jnp.arange(64.0) + r, "n": jnp.int32(r)}
        b = {"m": jnp.ones((4, 8)) * r, "v": {"k": jnp.arange(3) - r}}
        ckpt.save_pytree(a, "params", blocking=False)
        ckpt.save_pytree(b, "opt_state", blocking=False)
        ckpt.wait_pending(timeout=120)
        back_a, back_b = ckpt.load_pytree("params"), ckpt.load_pytree("opt_state")
        np.testing.assert_array_equal(np.asarray(back_a["w"]),
                                      np.arange(64.0) + r)
        assert int(back_a["n"]) == r
        np.testing.assert_array_equal(np.asarray(back_b["m"]),
                                      np.ones((4, 8)) * r)
        np.testing.assert_array_equal(np.asarray(back_b["v"]["k"]),
                                      np.arange(3) - r)


def test_async_save_error_raises_at_fence(tmp_path, monkeypatch):
    from ray_tpu.train.checkpoint import Checkpoint

    ckpt = Checkpoint.from_directory(str(tmp_path / "ck2"))
    monkeypatch.setattr(
        Checkpoint, "_save_pytree_sync",
        lambda self, tree, name: (_ for _ in ()).throw(
            RuntimeError("writer exploded")))
    ckpt.save_pytree({"x": np.zeros(2)}, blocking=False)
    with pytest.raises(RuntimeError, match="writer exploded"):
        ckpt.wait_pending()
    ckpt.wait_pending()  # error consumed; fence is idempotent


def test_checkpoint_manager_heap_retention(tmp_path):
    from ray_tpu.train.checkpoint import Checkpoint, CheckpointManager

    def mk(v):
        ck = Checkpoint.from_dict({"v": v})
        return ck

    # score mode: keep the top-2 by score
    mgr = CheckpointManager(str(tmp_path / "runs"), num_to_keep=2,
                            score_attribute="acc", score_order="max")
    kept = {}
    for i, acc in enumerate([0.1, 0.9, 0.5, 0.7]):
        kept[acc] = mgr.register(mk(i), {"acc": acc})
    assert sorted(e["score"] for e in mgr._entries) == [0.7, 0.9]
    assert os.path.isdir(kept[0.9].path) and os.path.isdir(kept[0.7].path)
    assert not os.path.isdir(kept[0.1].path)
    assert mgr.best_checkpoint.path == kept[0.9].path

    # recency mode: keep the last 2
    mgr2 = CheckpointManager(str(tmp_path / "runs2"), num_to_keep=2)
    handles = [mgr2.register(mk(i), {}) for i in range(4)]
    assert not os.path.isdir(handles[0].path)
    assert not os.path.isdir(handles[1].path)
    assert os.path.isdir(handles[2].path) and os.path.isdir(handles[3].path)
    assert mgr2.latest_checkpoint.path == handles[3].path


# ---- data plane -------------------------------------------------------------

def test_iter_jax_batches_stack_prefetch_compute_limited(rt_cluster):
    """stack=K yields [K, B, ...] trees with a ragged [k < K] tail; with
    bounded lookahead prefetch the steady-state verdict is
    compute-limited under a realistic (sleeping) consumer, and cold-start
    is booked separately."""
    pytest.importorskip("jax")
    from ray_tpu import data as rt_data

    toks = np.arange(33 * 4 * 33, dtype=np.int32).reshape(33 * 4, 33)
    ds = rt_data.from_numpy(toks)
    # a group of one is a group; without ``stack``, a step at a time
    one = ds.iter_jax_batches(batch_size=4, stack=1)
    assert one.stack == 1 and tuple(next(one)["data"].shape) == (1, 4, 33)
    plain = ds.iter_jax_batches(batch_size=4)
    assert plain.stack is None and tuple(next(plain)["data"].shape) == (4, 33)
    with pytest.raises(ValueError, match="stack"):
        ds.iter_jax_batches(batch_size=4, stack=0)
    it = ds.iter_jax_batches(batch_size=4, stack=4)
    assert it.stack == 4
    shapes = []
    for b in it:
        shapes.append(tuple(b["data"].shape))
        time.sleep(0.01)  # the "train step"
    assert shapes[:-1] == [(4, 4, 33)] * 8
    assert shapes[-1] == (1, 4, 33)  # ragged tail
    rep = it.report()
    assert rep["verdict"] == "compute-limited", rep
    assert rep["cold_start_s"] > 0
    assert rep["batches"] == 9


def test_trainer_threads_fast_path_config(rt_cluster, tmp_path):
    """RunConfig.fast_path reaches the worker session: the loop reads the
    configured steps_per_launch via train.get_fast_path()."""
    from ray_tpu.train import (
        FastPathConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )

    def loop(config):
        from ray_tpu import train

        fp = train.get_fast_path()
        train.report({"k": fp.steps_per_launch,
                      "async_report": fp.async_report})

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="fp", storage_path=str(tmp_path),
            fast_path=FastPathConfig(steps_per_launch=3))).fit()
    assert result.metrics["k"] == 3
    assert result.metrics["async_report"] is True
