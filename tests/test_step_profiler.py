"""Step profiler: FLOPs/MFU formulas, record splits, overhead guard,
metrics registration, timeline round-trip, and the ``rt profile`` CLI."""

import json
import time

import pytest

import ray_tpu


@pytest.fixture(autouse=True)
def _profiler_off_after():
    """Profiler state is process-global: never leak an enabled profiler
    (or one test's records) into the next test."""
    from ray_tpu.util import step_profiler as SP

    yield
    SP.disable()
    SP.reset()


# ---- analytic FLOPs / MFU (hand-computed expectations) ----------------------

def test_llama_flops_hand_computed():
    from ray_tpu.models import llama
    from ray_tpu.util import flops as F

    cfg = llama.LlamaConfig(vocab_size=10, d_model=4, n_layers=2,
                            n_heads=2, n_kv_heads=1, d_ff=8)
    # head_dim=2; per layer: wq 4*2*2=16, wk+wv 2*(4*1*2)=16, wo 16,
    # ffn 3*4*8=96, norms 2*4=8 -> 152; total 10*4 + 2*152 + 4 + 4*10 = 388
    assert cfg.num_params() == 388
    # train: 6*N + causal attn 6*L*S*d = 6*388 + 6*2*3*4 = 2472 per token
    assert F.train_flops_per_token(cfg, seq=3) == 2472
    assert F.train_step_flops(cfg, batch=2, seq=3) == 2 * 3 * 2472
    # decode at ctx=5: 2*N + 4*L*d*ctx = 776 + 4*2*4*5 = 936
    assert F.decode_flops_per_token(cfg, context=5) == 936
    # prefill: per token 2*N + 2*L*S*d = 776 + 2*2*3*4 = 824
    assert F.prefill_flops(cfg, batch=1, seq=3) == 3 * 824
    gen = F.generate_flops(cfg, batch=1, prompt_len=3, new_tokens=4)
    assert gen == 3 * 824 + 4 * F.decode_flops_per_token(cfg, 3 + 2.0)


def test_moe_uses_active_params():
    from ray_tpu.models import moe
    from ray_tpu.util import flops as F

    cfg = moe.MoEConfig(vocab_size=10, d_model=4, n_layers=1, n_heads=2,
                        n_kv_heads=2, d_ff=8, n_experts=4, top_k=2)
    assert cfg.active_params() < cfg.num_params()
    assert F._flops_params(cfg) == cfg.active_params()


def test_vit_flops_hand_computed():
    from ray_tpu.models import vit
    from ray_tpu.util import flops as F

    cfg = vit.ViTConfig(image_size=8, patch_size=4, channels=1, d_model=4,
                        n_layers=2, n_heads=2, d_ff=8, num_classes=3)
    # patches (8/4)^2=4 -> tokens 5; params: patch 1*16*4+4=68,
    # pos+cls (4+1)*4+4=24, per layer 4*16+2*32+16+8+4=156 -> 312,
    # final ln 8, head 4*3+3=15 => 427
    assert cfg.num_params() == 427
    # per token: 6N + non-causal attn 12*L*T*d = 2562 + 12*2*5*4 = 3042
    assert F.vit_step_flops(cfg, batch=2) == 2 * 5 * 3042


def test_mfu_formula():
    from ray_tpu.util import flops as F

    assert F.mfu(1e12, 1.0, 1, peak_per_chip=2e12) == 0.5
    assert F.mfu(1e12, 2.0, 2, peak_per_chip=1e12) == 0.25
    assert F.mfu(0.0, 1.0) == 0.0
    assert F.mfu(1e12, 0.0) == 0.0


def test_peak_flops_unknown_device_raises(monkeypatch):
    """Peaks are keyed by device_kind; a device that is not in the table is
    an error, the CPU included, and no environment variable overrides it."""
    from ray_tpu.util import flops as F

    assert F.peak_flops_per_chip("TPU v5 lite") == 197e12
    monkeypatch.setenv("RT_PEAK_FLOPS", "123.0")
    assert F.peak_flops_per_chip("TPU v5 lite") == 197e12
    for kind in ("TPU v9", "tpu", "cpu"):
        with pytest.raises(ValueError, match="no peak"):
            F.peak_flops_per_chip(kind)
    with pytest.raises(ValueError, match="no peak"):
        F.peak_flops_per_chip()  # this process's device: the CPU
    with pytest.raises(ValueError, match="no peak"):
        F.mfu(1e12, 1.0)


# ---- record mechanics -------------------------------------------------------

def test_profiled_call_compile_execute_split():
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import step_profiler as SP

    SP.reset()
    SP.enable()
    jitted = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    for _ in range(2):
        SP.profiled_call("train", jitted, (x,), key=("t", id(jitted)),
                         tokens=64, flops=1e6)
    first, second = SP.records("train")
    assert first.first_call and first.compile_s > 0
    assert first.dispatch_s == 0.0
    assert not second.first_call and second.compile_s == 0.0
    assert second.dispatch_s > 0 and second.execute_s > 0
    assert second.wall_s >= second.execute_s
    # the CPU has no peak on record: rates are reported, MFU is not
    assert second.tokens_per_s > 0 and second.mfu == 0.0
    assert second.step == 1 and second.seq > first.seq


def test_disabled_is_near_zero_overhead_and_records_nothing():
    from ray_tpu.util import step_profiler as SP

    SP.disable()
    SP.reset()

    def f(x):
        return x

    t0 = time.perf_counter()
    for i in range(10_000):
        SP.profiled_call("train", f, (i,), key="k")
    dt = time.perf_counter() - t0
    assert dt < 0.5  # < 50 us per disabled call, very generously
    assert SP.records() == []


def test_train_step_hot_path_records(rt_local):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.util import step_profiler as SP

    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    optimizer = ts.default_optimizer()
    opt_state = jax.jit(optimizer.init)(params)
    step = ts.make_train_step(cfg, optimizer)
    batch = {"tokens": jax.random.randint(
        jax.random.key(1), (2, 17), 0, cfg.vocab_size, jnp.int32)}

    SP.reset()
    params, opt_state, _ = step(params, opt_state, batch)  # disabled
    assert SP.records() == []

    SP.enable()
    for _ in range(2):
        params, opt_state, _ = step(params, opt_state, batch)
    recs = SP.records("train")
    assert len(recs) == 2
    assert all(r.tokens == 2 * 16 for r in recs)
    assert all(r.flops > 0 for r in recs)


def test_step_metrics_auto_registered():
    from ray_tpu.util import metrics as M
    from ray_tpu.util import step_profiler as SP

    SP.enable()
    SP.record("train", wall_s=0.01, execute_s=0.005, tokens=100, flops=1e9)
    text = M.prometheus_text(M._registry.snapshot())
    for name in ("rt_step_time_seconds", "rt_step_device_time_seconds",
                 "rt_step_mfu", "rt_step_tokens_per_s",
                 "rt_step_launches_total"):
        assert name in text, name
    assert 'rt_step_time_seconds_bucket{kind="train"' in text


def test_metrics_get_or_create_idempotent():
    from ray_tpu.util import metrics as M

    c1 = M.get_or_create(M.Counter, "rt_test_goc", "x")
    c1.inc(2.0)
    c2 = M.get_or_create(M.Counter, "rt_test_goc", "x")
    assert c1 is c2  # same live object: accumulated samples survive


# ---- event log drain + timeline lanes ---------------------------------------

def test_timeline_step_lanes_roundtrip(rt_cluster, tmp_path):
    import jax
    import jax.numpy as jnp

    from ray_tpu.util import step_profiler as SP

    @ray_tpu.remote
    def probe():
        return 1

    ray_tpu.get(probe.remote(), timeout=60)

    SP.reset()
    SP.enable()
    jitted = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    for _ in range(3):
        SP.profiled_call("train", jitted, (x,), key=("tl", id(jitted)),
                         tokens=32, flops=1e6)
    # the interval drainer may ship some records first; between the two
    # paths everything lands exactly once (seq watermark)
    assert SP.drain() <= 3
    assert SP.drain() == 0  # watermark: nothing re-shipped

    out = tmp_path / "trace.json"
    deadline = time.time() + 10
    while time.time() < deadline:
        trace = ray_tpu.timeline(str(out))
        cats = {e.get("cat") for e in trace}
        if {"step", "compile", "sync", "task"} <= cats:
            break
        time.sleep(0.2)
    assert {"step", "compile", "sync"} <= cats
    assert "task" in cats  # step lanes live ALONGSIDE the task lanes
    loaded = json.loads(out.read_text())
    steps = [e for e in loaded if e.get("cat") == "step"]
    assert len(steps) == 3
    assert all(e["tid"] == "step:train" for e in steps)
    assert all("mfu" in e["args"] for e in steps)
    # sync sub-span sits inside its step span
    sync = [e for e in loaded if e.get("cat") == "sync"][0]
    parent = steps[0]
    assert sync["ts"] >= parent["ts"] - 1  # (1us float slack)


def test_rt_profile_cli(rt_cluster, tmp_path, capsys):
    from ray_tpu.scripts import profile as P
    from ray_tpu.util import step_profiler as SP

    SP.reset()
    out = tmp_path / "trace.json"
    rc = P.main(["--preset", "debug", "--steps", "2", "--batch", "2",
                 "--seq", "8", "--out", str(out)])
    assert rc == 0
    text = capsys.readouterr().out
    # the per-step breakdown table
    for col in ("wall ms", "compile ms", "dispatch ms", "sync ms",
                "tok/s", "MFU"):
        assert col in text, col
    assert "steady-state:" in text
    # step histograms ride the Prometheus page
    assert "rt_step_time_seconds_bucket" in text
    trace = json.loads(out.read_text())
    cats = {e.get("cat") for e in trace}
    assert {"step", "compile", "sync"} <= cats


def test_dashboard_steps_api(rt_cluster):
    import urllib.request

    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.util import step_profiler as SP

    SP.reset()
    SP.enable()
    SP.record("train", name="dash", wall_s=0.02, execute_s=0.01,
              tokens=10, flops=1e6)
    SP.drain()  # (the interval drainer may already have shipped it)
    port = start_dashboard()
    deadline = time.time() + 10
    while time.time() < deadline:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/steps", timeout=30) as r:
            rows = json.loads(r.read().decode())
        if any((row.get("profile") or {}).get("name") == "dash"
               for row in rows):
            break
        time.sleep(0.2)
    assert any((row.get("profile") or {}).get("name") == "dash"
               for row in rows)
    # and the UI page carries the steps tab
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/", timeout=30) as r:
        html = r.read().decode()
    assert "/api/steps" in html
