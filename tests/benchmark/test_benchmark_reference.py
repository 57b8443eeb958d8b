"""Each family's float32 reference against the program's own models, at a
small size on the CPU: ``llama.forward``, ``moe.forward``, the losses, and
prefill-then-decode through the engine's cache. The third family exists in
a copy only (``benchmark_testlib``), as a later PR's would."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import spec  # noqa: E402
from ray_tpu.models import llama, moe, serving  # noqa: E402

# With float32 compute the program and the reference do the same mathematics
# in another order (fused scan, stacked experts, one-hot dispatch): 1e-4 of a
# logit scale of ~3 is float32 rounding through two layers, and a missing
# norm, a wrong rotary pairing or a mask off by one moves logits by tenths.
F32_TOL = 3e-4
# With the served bf16 compute every product rounds to 8 bits: the bound is
# the chip's own (PR 21, finding 7), 1/32 of the logits' scale.
BF16_SCALE_SHARE = 1 / 32


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_copy(str(tmp_path_factory.mktemp("bench-ref")))


def _family(name, root=spec.ROOT):
    return spec.load_family(lib.CONFIGS[name]["family"], root)


def _cfg(name, root=spec.ROOT, **over):
    cfg = _family(name, root).program_config(lib.CONFIGS[name], 2, max_seq_len=96)
    return dataclasses.replace(cfg, param_dtype=jnp.float32, **over)


def _tokens(seed, shape, vocab=256):
    return jnp.asarray(np.random.default_rng(seed).integers(1, vocab, shape),
                       jnp.int32)


FAMILIES = [("tiny-dense", llama), ("tiny-moe", moe), ("tiny-third", llama)]


@pytest.mark.parametrize("name,fam", FAMILIES)
def test_forward_matches_reference_in_float32(root, name, fam):
    cfg = _cfg(name, root, compute_dtype=jnp.float32, remat=False)
    params = _family(name, root).init_params(jax.random.key(0), cfg)
    tokens = _tokens(1, (2, 48))
    if fam is moe:
        # the reference's plain forward routes without dropping, as Mixtral
        # does; give the program the capacity that never drops
        cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts))
    got = np.asarray(fam.forward(params, tokens, cfg))
    want = np.asarray(_family(name, root).logits(params, tokens, lib.CONFIGS[name]))
    assert np.abs(got - want).max() < F32_TOL * np.abs(want).max()


@pytest.mark.parametrize("name,fam", FAMILIES)
def test_loss_matches_reference(root, name, fam):
    """The training loss: chunked cross entropy, and with experts the
    capacity drops over the whole batch and the weighted balancing loss."""
    cfg = _cfg(name, root, compute_dtype=jnp.float32, loss_chunk=16)
    family = _family(name, root)
    params = family.init_params(jax.random.key(2), cfg)
    tokens = _tokens(3, (4, 65))
    got = float(fam.lm_loss(params, {"tokens": tokens}, cfg))
    want = family.loss(params, tokens, lib.CONFIGS[name])
    assert abs(got - float(want["loss"])) < F32_TOL * got
    if fam is moe:
        assert float(want["aux"]) > 0.9  # ~1 when routing is balanced
        dropless = family.loss(params, tokens, {**lib.CONFIGS[name], "assumed": {}})
        assert float(dropless["ce"]) != float(want["ce"])  # something dropped


def test_prefill_then_decode_through_the_cache():
    """Greedy tokens from the engine's slot prefill and fused decode steps,
    in the served bf16 compute, each judged under the reference's full
    forward over the prompt and the answer so far."""
    cfg = _cfg("tiny-dense")
    params = llama.init_params(jax.random.key(4), cfg)
    batcher = serving.ContinuousBatcher(params, cfg, max_slots=4, max_len=96)
    prompts = [np.asarray(_tokens(5 + i, (n,))) for i, n in enumerate((8, 16, 32))]
    ids = [batcher.submit(p, 24) for p in prompts]
    out = batcher.run_to_completion()
    for rid, prompt in zip(ids, prompts):
        toks = out[rid]
        assert len(toks) == 24
        first = len(prompt) - 1
        ctx = np.zeros((1, 96), np.int32)  # padded, as the replica pads
        ctx[0, :first + 24] = np.concatenate([prompt, toks[:-1]])
        following = np.zeros(96, np.int32)
        following[first:first + 24] = toks
        m = _family("tiny-dense").token_margins(
            params, jnp.asarray(ctx), jnp.asarray(following),
            lib.CONFIGS["tiny-dense"], rows=(first, first + 24))
        rows = slice(first, first + 24)
        assert bool(np.asarray(m["finite"])[rows].all())
        assert (np.asarray(m["margin"])[rows].max()
                <= np.asarray(m["scale"])[rows].max() * BF16_SCALE_SHARE)


def test_a_wrong_token_fails_the_margin():
    cfg = _cfg("tiny-dense")
    params = llama.init_params(jax.random.key(4), cfg)
    ctx = _tokens(9, (1, 32))
    logits = np.asarray(_family("tiny-dense").logits(params, ctx, lib.CONFIGS["tiny-dense"]))[0]
    worst = logits.max(-1) - logits.min(-1)  # the least likely token
    assert worst.min() > np.abs(logits).max() * BF16_SCALE_SHARE
