"""Autoregressive decode with KV cache: exact equivalence with the full
(uncached) forward, sampling controls, and serve integration."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import generate, llama, moe


@pytest.fixture(scope="module")
def fp32_cfg():
    # fp32 so cached-vs-full numerics agree to ~1e-6 (argmax never flips)
    return dataclasses.replace(llama.PRESETS["debug"],
                               compute_dtype=jnp.float32)


def test_greedy_decode_matches_full_forward(fp32_cfg):
    cfg = fp32_cfg
    params = llama.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (2, 7), 0, cfg.vocab_size)
    toks = generate.generate(params, prompt, cfg, max_new_tokens=10)
    assert toks.shape == (2, 10)
    seq = np.asarray(prompt)
    # (one program a length: op by op a forward is twenty small ones)
    forward = jax.jit(lambda p, seq: llama.forward(p, seq, cfg)[:, -1, :])
    for t in range(10):
        expect = np.asarray(forward(params, seq)).argmax(-1)
        got = np.asarray(toks[:, t])
        assert (expect == got).all(), f"step {t}: {expect} != {got}"
        seq = np.concatenate([seq, got[:, None]], axis=1)


def test_gqa_decode(fp32_cfg):
    """Grouped-query attention (kv heads < q heads) through the cache."""
    cfg = dataclasses.replace(fp32_cfg, n_heads=4, n_kv_heads=2)
    params = llama.init_params(jax.random.key(0), cfg)
    prompt = jax.random.randint(jax.random.key(1), (1, 5), 0, cfg.vocab_size)
    toks = generate.generate(params, prompt, cfg, max_new_tokens=6)
    seq = np.asarray(prompt)
    forward = jax.jit(lambda p, seq: llama.forward(p, seq, cfg)[:, -1, :])
    for t in range(6):
        expect = np.asarray(forward(params, seq)).argmax(-1)
        assert (expect == np.asarray(toks[:, t])).all()
        seq = np.concatenate([seq, np.asarray(toks[:, t])[:, None]], axis=1)


@pytest.mark.parametrize("routing", [
    dict(n_experts=4, top_k=2, norm_topk_prob=True),    # Mixtral's shape
    dict(n_experts=8, top_k=3, norm_topk_prob=False),   # OLMoE's
], ids=["e4-k2-renormalised", "e8-k3-as-they-are"])
def test_moe_decode_matches_dropfree_forward(routing):
    """The served expert path (assignments sorted by expert, grouped
    products) decodes what the capacity dispatch computes with room for
    every selection."""
    base = dataclasses.replace(moe.PRESETS["moe-debug"],
                               compute_dtype=jnp.float32, **routing)
    cfg_ref = dataclasses.replace(base,
                                  capacity_factor=float(base.n_experts))
    params = moe.init_params(jax.random.key(0), base)
    prompt = jax.random.randint(jax.random.key(1), (1, 5), 0,
                                base.vocab_size)
    toks = generate.generate(params, prompt, base, max_new_tokens=6)
    seq = np.asarray(prompt)
    for t in range(6):
        logits = moe.forward(params, jnp.asarray(seq), cfg_ref)
        expect = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
        assert (expect == np.asarray(toks[:, t])).all()
        seq = np.concatenate([seq, np.asarray(toks[:, t])[:, None]], axis=1)


def test_sampling_controls(fp32_cfg):
    cfg = fp32_cfg
    params = llama.init_params(jax.random.key(0), cfg)
    prompt = jnp.zeros((1, 4), jnp.int32)
    a = generate.generate(params, prompt, cfg, max_new_tokens=8,
                          temperature=1.0, key=jax.random.key(1))
    b = generate.generate(params, prompt, cfg, max_new_tokens=8,
                          temperature=1.0, key=jax.random.key(2))
    assert a.shape == b.shape == (1, 8)
    assert not np.array_equal(np.asarray(a), np.asarray(b))  # keys differ
    # top_k=1 at any temperature is greedy
    g = generate.generate(params, prompt, cfg, max_new_tokens=8)
    t1 = generate.generate(params, prompt, cfg, max_new_tokens=8,
                           temperature=1.0, top_k=1, key=jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(g), np.asarray(t1))


def test_generation_behind_serve(rt_cluster):
    """The inference stack end-to-end: a serve deployment holding model
    params generates tokens for HTTP-shaped requests."""
    from ray_tpu import serve

    @serve.deployment
    class LM:
        def __init__(self):
            self.cfg = dataclasses.replace(llama.PRESETS["debug"],
                                           compute_dtype=jnp.float32)
            self.params = llama.init_params(jax.random.key(0), self.cfg)

        def __call__(self, prompt_ids):
            prompt = jnp.asarray([prompt_ids], jnp.int32)
            toks = generate.generate(self.params, prompt, self.cfg,
                                     max_new_tokens=4)
            return np.asarray(toks)[0].tolist()

    handle = serve.run(LM.bind(), name="lm", route_prefix=None)
    try:
        out = handle.remote([1, 2, 3]).result(timeout=120)
        assert len(out) == 4
        assert all(0 <= t < 256 for t in out)
    finally:
        serve.shutdown()
        serve._forget_controller_for_tests()


def test_token_streaming_behind_serve(rt_cluster):
    """LLM token streaming end-to-end: a serve deployment yields tokens
    incrementally through the streaming-response path."""
    from ray_tpu import serve

    @serve.deployment
    class StreamLM:
        def __init__(self):
            self.cfg = dataclasses.replace(llama.PRESETS["debug"],
                                           compute_dtype=jnp.float32)
            self.params = llama.init_params(jax.random.key(0), self.cfg)

        def __call__(self, prompt_ids):
            prompt = jnp.asarray([prompt_ids], jnp.int32)
            toks = generate.generate(self.params, prompt, self.cfg,
                                     max_new_tokens=5)
            for tok in np.asarray(toks)[0]:
                yield int(tok)

    handle = serve.run(StreamLM.bind(), name="slm", route_prefix=None)
    try:
        gen = handle.remote([1, 2, 3]).result(timeout=180)
        toks = list(gen)
        assert len(toks) == 5
        assert all(isinstance(t, int) for t in toks)
    finally:
        serve.shutdown()
        serve._forget_controller_for_tests()


def test_batched_generation_with_serve_batch(rt_cluster):
    """Continuous-batching shape: concurrent single-prompt requests fuse
    into ONE batched generate call via @serve.batch (the MXU wants big
    batches; per-request decode would waste it)."""
    from ray_tpu import serve

    @serve.deployment(max_ongoing_requests=16)
    class BatchedLM:
        def __init__(self):
            self.cfg = dataclasses.replace(llama.PRESETS["debug"],
                                           compute_dtype=jnp.float32)
            self.params = llama.init_params(jax.random.key(0), self.cfg)
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.3)
        async def gen(self, prompts):
            self.batch_sizes.append(len(prompts))
            batch = jnp.asarray(prompts, jnp.int32)
            toks = generate.generate(self.params, batch, self.cfg,
                                     max_new_tokens=3)
            return [np.asarray(t).tolist() for t in toks]

        async def __call__(self, prompt_ids):
            return await self.gen(prompt_ids)

        def seen_batches(self):
            return self.batch_sizes

    handle = serve.run(BatchedLM.bind(), name="blm", route_prefix=None)
    try:
        rs = [handle.remote([1, 2, i]) for i in range(6)]
        outs = [r.result(timeout=180) for r in rs]
        assert all(len(o) == 3 for o in outs)
        sizes = handle.seen_batches.remote().result(timeout=30)
        assert max(sizes) > 1, f"requests never fused: {sizes}"
    finally:
        serve.shutdown()
        serve._forget_controller_for_tests()
