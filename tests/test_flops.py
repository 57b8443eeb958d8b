"""``util/flops.py``'s analytic FLOPs and MFU formulas against
hand-computed expectations, and the metric registry's get-or-create."""

import pytest


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


def test_moe_uses_active_params():
    from ray_tpu.models import moe
    from ray_tpu.util import flops as F

    cfg = moe.MoEConfig(vocab_size=10, d_model=4, n_layers=1, n_heads=2,
                        n_kv_heads=2, d_ff=8, n_experts=4, top_k=2)
    assert cfg.active_params() < cfg.num_params()
    assert F._flops_params(cfg) == cfg.active_params()


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


def test_metrics_get_or_create_idempotent():
    from ray_tpu.util import metrics as M

    c1 = M.get_or_create(M.Counter, "rt_test_goc", "x")
    c1.inc(2.0)
    c2 = M.get_or_create(M.Counter, "rt_test_goc", "x")
    assert c1 is c2  # same live object: accumulated samples survive
