"""Train-layer tests: gang orchestration, reporting, checkpointing, restart,
and the MNIST-MLP-style data-parallel config with
host-collective gradient sync across real worker processes.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.train import (
    Checkpoint,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
)


def test_single_worker_report_flow(rt_cluster, tmp_path):
    def loop(config):
        from ray_tpu import train

        ctx = train.get_context()
        for step in range(3):
            train.report({"step": step, "rank": ctx.get_world_rank(),
                          "lr": config["lr"]})

    result = JaxTrainer(
        loop, train_loop_config={"lr": 0.1},
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t1", storage_path=str(tmp_path))).fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["lr"] == 0.1
    assert len(result.metrics_history) == 3


def test_multi_worker_ranks_and_world(rt_cluster, tmp_path):
    def loop(config):
        from ray_tpu import train

        ctx = train.get_context()
        train.report({"rank": ctx.get_world_rank(),
                      "world": ctx.get_world_size()})

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=3, cpus_per_worker=1),
        run_config=RunConfig(name="t2", storage_path=str(tmp_path))).fit()
    assert result.metrics["world"] == 3
    assert result.metrics["rank"] == 0  # driver keeps rank-0 metrics


def test_checkpoint_save_and_resume(rt_cluster, tmp_path):
    def loop(config):
        from ray_tpu import train

        start = 0
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict()["step"] + 1
        for step in range(start, start + 2):
            train.report({"step": step},
                         checkpoint=Checkpoint.from_dict({"step": step}))

    run_cfg = RunConfig(name="t3", storage_path=str(tmp_path))
    r1 = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1),
                    run_config=run_cfg).fit()
    assert r1.metrics["step"] == 1
    r2 = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1),
                    run_config=RunConfig(name="t3b", storage_path=str(tmp_path)),
                    resume_from_checkpoint=r1.checkpoint).fit()
    assert r2.metrics["step"] == 3  # resumed from step 1


def test_failure_restart_from_checkpoint(rt_cluster, tmp_path):
    def loop(config):
        from ray_tpu import train

        ckpt = train.get_checkpoint()
        start = ckpt.to_dict()["step"] + 1 if ckpt else 0
        for step in range(start, 4):
            if step == 2 and ckpt is None:
                raise RuntimeError("injected failure at step 2")
            train.report({"step": step},
                         checkpoint=Checkpoint.from_dict({"step": step}))

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="t4", storage_path=str(tmp_path),
                             failure_config=FailureConfig(max_failures=1))).fit()
    assert result.error is None
    assert result.metrics["step"] == 3  # resumed at 2 after failing


def test_failure_without_budget_raises(rt_cluster, tmp_path):
    def loop(config):
        raise ValueError("always fails")

    from ray_tpu.train.trainer import TrainingFailedError

    with pytest.raises(TrainingFailedError, match="always fails"):
        JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1),
                   run_config=RunConfig(name="t5", storage_path=str(tmp_path))).fit()


def test_dataset_sharding_lists(rt_cluster, tmp_path):
    def loop(config):
        from ray_tpu import train

        shard = train.get_dataset_shard("train")
        train.report({"shard": list(shard)})

    data = list(range(10))
    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="t6", storage_path=str(tmp_path)),
        datasets={"train": data}).fit()
    assert result.metrics["shard"] == data[0::2]  # rank 0's slice


def test_data_parallel_mlp_with_psum_grads(rt_cluster, tmp_path):
    """BASELINE config 2 shape: MLP, 2 workers, gradient all-reduce each
    step (host-plane collectives between real processes), loss decreases and
    replicas stay in sync."""
    def loop(config):
        import numpy as np

        from ray_tpu import collective as col
        from ray_tpu import train

        ctx = train.get_context()
        rank, world = ctx.get_world_rank(), ctx.get_world_size()
        col.init_collective_group(world, rank, "mlp")

        rng = np.random.RandomState(0)
        w = rng.randn(4, 1) * 0.1          # same init on all ranks
        data_rng = np.random.RandomState(rank)
        losses = []
        for step in range(8):
            x = data_rng.randn(16, 4)
            y = x @ np.array([[1.0], [-2.0], [0.5], [3.0]])
            pred = x @ w
            grad = 2 * x.T @ (pred - y) / len(x)
            grad = col.allreduce(grad, "mlp") / world
            w -= 0.05 * grad
            losses.append(float(((pred - y) ** 2).mean()))
        train.report({"first_loss": losses[0], "last_loss": losses[-1],
                      "w_checksum": float(np.sum(w))})

    result = JaxTrainer(
        loop, scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="mlp", storage_path=str(tmp_path))).fit()
    assert result.metrics["last_loss"] < result.metrics["first_loss"] * 0.5


def test_torch_trainer_ddp_gloo(rt_cluster):
    """TorchTrainer: 2-worker gloo process group over the KV rendezvous;
    an all_reduce proves the group is real (reference: TorchTrainer +
    _setup_torch_process_group)."""
    from ray_tpu.train import ScalingConfig, TorchTrainer

    def loop(config):
        import torch
        import torch.distributed as dist

        from ray_tpu import train

        rank = dist.get_rank()
        world = dist.get_world_size()
        t = torch.tensor([float(rank + 1)])
        dist.all_reduce(t)  # 1 + 2 = 3 across 2 workers
        train.report({"sum": float(t.item()), "rank": rank,
                      "world": world})

    trainer = TorchTrainer(
        loop, train_loop_config={},
        scaling_config=ScalingConfig(num_workers=2, cpus_per_worker=1))
    result = trainer.fit()
    assert result.metrics["sum"] == 3.0
    assert result.metrics["world"] == 2


def test_sharded_checkpoint_roundtrip_and_reshard(tmp_path):
    """Orbax pytree checkpointing of MESH-SHARDED params: save under one
    layout, restore into the same layout AND into a different one
    (fsdp/tp swapped) — the 7B-scale checkpoint path where no host ever
    materializes the full tree."""
    import jax
    import numpy as np
    import pytest as _pytest

    if len(jax.devices()) < 8:
        _pytest.skip("needs the 8-device CPU mesh")
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.train.checkpoint import Checkpoint

    cfg = llama.PRESETS["debug"]
    optimizer = ts.default_optimizer(total_steps=10)
    mesh_a, _ = ts.auto_mesh(8, tp=4)
    params, _ = ts.init_sharded_state(jax.random.key(0), cfg, mesh_a,
                                      optimizer)
    ckpt = Checkpoint.from_directory(str(tmp_path / "ck"))
    ckpt.save_pytree(params, "params")

    # restore into the SAME shardings
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        params)
    back = ckpt.load_pytree("params", abstract)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # restore into a DIFFERENT layout (tp/fsdp swapped): orbax reshards
    mesh_b, _ = ts.auto_mesh(8, tp=2)
    rules = llama.sharding_rules()
    shardings_b = rules.tree_shardings(params, mesh_b)
    abstract_b = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        params, shardings_b)
    resharded = ckpt.load_pytree("params", abstract_b)
    leaf_a = params["layers"]["wq"]
    leaf_b = resharded["layers"]["wq"]
    assert leaf_a.sharding != leaf_b.sharding  # genuinely a new layout
    np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def test_multi_step_scan_matches_single_steps():
    """make_multi_step (K optimizer steps fused into one lax.scan program
    — the launch-amortization path for host-bound loops) produces the
    SAME params/metrics as K sequential make_train_step calls, on a real
    sharded mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pytest as _pytest

    if len(jax.devices()) < 8:
        _pytest.skip("needs the 8-device CPU mesh")
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts

    K = 3
    cfg = llama.PRESETS["debug"]
    mesh, _ = ts.auto_mesh(8, tp=2)
    optimizer = ts.default_optimizer(total_steps=100)
    toks = jax.random.randint(jax.random.key(7), (K, 4, 65), 0,
                              cfg.vocab_size, dtype=jnp.int32)

    # K single steps
    p1, s1 = ts.init_sharded_state(jax.random.key(0), cfg, mesh, optimizer)
    step = ts.make_train_step(cfg, optimizer, mesh=mesh)
    losses = []
    for k in range(K):
        b = ts.shard_batch({"tokens": toks[k]}, mesh)
        p1, s1, m = step(p1, s1, b)
        losses.append(float(m["loss"]))

    # ONE fused scan over the same batches
    p2, s2 = ts.init_sharded_state(jax.random.key(0), cfg, mesh, optimizer)
    multi = ts.make_multi_step(cfg, optimizer, K, mesh=mesh)
    bd = ts.shard_batch({"tokens": toks}, mesh, stacked=True)
    p2, s2, m2 = multi(p2, s2, bd)

    np.testing.assert_allclose(np.asarray(m2["loss"]), np.asarray(losses),
                               rtol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    # public exports exist (ray_tpu.parallel lazy surface)
    from ray_tpu import parallel

    assert parallel.make_multi_step is ts.make_multi_step
    assert parallel.shard_batch is ts.shard_batch
