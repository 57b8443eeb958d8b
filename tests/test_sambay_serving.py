"""The decoder-hybrid-decoder (``models/sambay.py``: Mamba-1 layers beside
window attention, one full layer whose keys and values the cross layers read,
gated memory units, differential heads) served from a slot tree that holds
rings, one shared buffer, a recurrent state and a convolution tail.

Every numerical test compares LOGITS with the sambay family's plain float32
reference (``benchmark/families/sambay.py``: the recurrence a scan over time,
two softmaxes a pair, no cache, no ring, every layer over every token) on
seeded weights, at a tiny size (window 8, ``max_len`` 96, 3 x (mamba, window),
(mamba, full), 2 x (gmu, cross)), on the CPU. Two tolerances, each with its
reason:

- ``EXACT`` = 2e-5 of the logits' scale, for a program computed in float32
  throughout: program and reference then differ by the order of float32 sums
  alone (measured 2e-6). Under it a window off by one, a lambda without its
  ``lam_init``, a missing sub-norm, a bf16 recurrent state and a bf16 softmax
  all fail (shown below).
- ``SERVED`` = 1/16 of the logits' scale for the served types (bf16
  activations; float32 residual stream, state, convolution, gate and
  softmaxes, every product summed in float32): at this size (12 layers of
  width 64) the largest of 256 logits' differences over a sequence is 2-3%
  of the scale and their root mean square 0.4-0.5% (a dense model of the same
  depth and width: 1.7% and 0.36%; with bf16 activations throughout this one
  read 5-6% and 1.0%). The benchmark's own limit, 1/32 (``benchmark/lib/results.py``),
  is of another quantity, how far a served token's logit lies under the
  reference's best, and is held on the chip at the published widths.
"""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from benchmark.lib import spec  # noqa: E402

from ray_tpu.models import generate as G  # noqa: E402
from ray_tpu.models import hybrid, llama, sambay, serving  # noqa: E402
from ray_tpu.models.serving import (ContinuousBatcher, ContinuousEngine,  # noqa: E402
                                    PrefixKVCache)
from ray_tpu.ops import attention, ssm  # noqa: E402
from ray_tpu.ops.pallas.kv_write import kv_write_in_place  # noqa: E402
from ray_tpu.ops.pallas.s6_update import s6_update_in_place  # noqa: E402
from ray_tpu.util import hlo_copies  # noqa: E402

EXACT, SERVED = 2e-5, 1 / 16
W = 8
TINY = {"hidden_size": 64, "intermediate_size": 128, "layer_norm_eps": 1e-5,
        "mb_per_layer": 2, "num_attention_heads": 8, "num_hidden_layers": 12,
        "num_key_value_heads": 4, "sliding_window": W,
        "tie_word_embeddings": True, "vocab_size": 256}
CFG_FILE = {"config": TINY, "assumed": {
    "softmax_scale": {"value": 8 ** -0.5},
    "mamba": {"d_state": 16, "d_conv": 4, "expand": 2, "dt_rank": 4}}}
LAYERS, SLOTS, MAX_LEN = 12, 3, 96


@pytest.fixture(scope="module")
def family():
    return spec.load_family("sambay")


def _perturbed(params, dtype):
    """The norms' biases, the projections' biases and the sub-norm moved off
    their neutral initial values, so that a test sees each of them."""
    leaves, tree = jax.tree.flatten_with_path(params)
    keys = jax.random.split(jax.random.key(7), len(leaves))
    out = []
    for (path, x), key in zip(leaves, keys):
        name = jax.tree_util.keystr(path)
        if any(name.endswith(f"['{n}']") for n in (
                "bq", "bk", "bv", "bo", "subln", "final_norm_b")) \
                or name.endswith("_norm_b']"):
            x = (x.astype(jnp.float32) + 0.1 * jax.random.normal(
                key, x.shape)).astype(x.dtype)
        out.append(x)
    return jax.tree.unflatten(tree, [o.astype(dtype) if o.dtype != jnp.float32
                                     or dtype == jnp.float32 else o for o in out])


@pytest.fixture(scope="module")
def f32(family):
    """The program computed in float32 throughout, and its weights."""
    cfg = dataclasses.replace(
        family.program_config(CFG_FILE, LAYERS, max_seq_len=MAX_LEN),
        compute_dtype=jnp.float32, param_dtype=jnp.float32)
    return cfg, _perturbed(family.init_params(jax.random.key(1), cfg),
                           jnp.float32)


@pytest.fixture(scope="module")
def served(family):
    """The served types: bf16 weights and activations, float32 state."""
    cfg = family.program_config(CFG_FILE, LAYERS, max_seq_len=MAX_LEN)
    return cfg, _perturbed(family.init_params(jax.random.key(1), cfg),
                           jnp.bfloat16)


def _tokens(n, salt, rows=1):
    return jnp.asarray(np.random.default_rng(salt).integers(
        0, TINY["vocab_size"], (rows, n)), jnp.int32)


def _prefill_op_by_op(params, cfg, tokens, last_only=False):
    return G._forward_with_cache(params, tokens, cfg,
                                 G.init_cache(cfg, tokens.shape[0], MAX_LEN),
                                 0, last_only=last_only)


@functools.lru_cache(maxsize=None)
def _prefill_of(cfg, last_only):
    return jax.jit(lambda params, tokens: _prefill_op_by_op(
        params, cfg, tokens, last_only))


def _prefill(params, cfg, tokens, last_only=False):
    """A prefill into a fresh tree, jitted once a (config, shape): twelve
    layers op by op were 20 s a call, and the tests share their lengths."""
    return _prefill_of(cfg, last_only)(params, tokens)


@functools.lru_cache(maxsize=None)
def _reference(family, what):
    """The family's reference ``what`` (``logits``, ``final_states``) of
    (params, tokens) as one program a shape: op by op every small operation
    of it was a program for the CPU backend to build, at every new length
    again."""
    return jax.jit(lambda params, tokens: getattr(family, what)(
        params, tokens, CFG_FILE))


def _off(got, ref):
    """Largest difference as a share of the reference logits' scale."""
    return float(jnp.abs(got - ref).max() / jnp.abs(ref).max())


def test_the_order_is_walked_in_segments(f32):
    cfg, _ = f32
    assert cfg.layer_types == ("mamba", "window") * 3 + ("mamba", "full") \
        + ("gmu", "cross") * 2
    assert cfg.segments() == [(("mamba", "window"), 3), (("mamba", "full"), 1),
                              (("gmu", "cross"), 2)]
    real = sambay.layer_types_for(32)
    assert real[16:20] == ("mamba", "full", "gmu", "cross")
    assert sambay._segments(real) == [(("mamba", "window"), 8),
                                      (("mamba", "full"), 1),
                                      (("gmu", "cross"), 7)]
    assert sambay._segments(real[:17])[-1] == (("mamba",), 1)
    assert G.cache_names(cfg) == ("k", "v", "wk", "wv", "ssm", "conv")
    tree = jax.eval_shape(lambda: G.init_cache(cfg, SLOTS, MAX_LEN))
    assert tree["k"].shape == (1, SLOTS, 2, MAX_LEN, 16)   # pairs, length, 2 hd
    assert tree["wk"].shape == (3, SLOTS, 2, W, 16)
    assert tree["ssm"].shape == (4, SLOTS, 16, 128) and tree["ssm"].dtype == jnp.float32


# ---- (a) prefill against the reference --------------------------------------

LENGTHS = [1, W - 1, W, W + 1, 2 * W + 3, 3 * W + 5]


@pytest.mark.parametrize("n", LENGTHS)
def test_prefill_logits_match_the_reference(family, f32, served, n):
    """A prompt inside the window, of exactly one, past three: every position's
    logits, the state handed on, and what the rings hold."""
    tokens = _tokens(n, n)
    ref = _reference(family, "logits")(f32[1], tokens)
    got, cache = _prefill(f32[1], f32[0], tokens)
    assert _off(got, ref) < EXACT
    states = _reference(family, "final_states")(f32[1], tokens[0])
    assert float(jnp.abs(cache["ssm"][:, 0] - states).max()) \
        < EXACT * float(jnp.abs(states).max())
    ref = _reference(family, "logits")(served[1], tokens)
    assert _off(_prefill(served[1], served[0], tokens)[0], ref) < SERVED


@pytest.mark.parametrize("n", [2, W + 1, 3 * W + 5])
def test_the_prefill_that_skips_gives_the_logits_of_one_that_does_not(f32, n):
    """The engine's prefill (the last token's logits alone) runs the layers
    behind the full layer's keys and values for that token alone: the same
    logits, and the same cache in every buffer, as every layer over every
    token."""
    cfg, params = f32
    tokens = _tokens(n, 70 + n, rows=2)
    whole, a = _prefill(params, cfg, tokens)
    last, b = _prefill(params, cfg, tokens, last_only=True)
    assert last.shape == (2, 1, TINY["vocab_size"])
    assert _off(last[:, 0], whole[:, -1]) < EXACT
    for name in G.cache_names(cfg):
        assert float(jnp.abs(a[name] - b[name]).max()) < 1e-5, name
    assert cfg.prefill_layer_tokens(n) == (7 * n + 5, 12 * n)


@pytest.mark.parametrize("what,change", [
    ("a window one position wider", lambda c: dataclasses.replace(
        c, sliding_window=W + 1)),
    ("softmax scale 1/sqrt(2 hd)", lambda c: dataclasses.replace(
        c, attn_scale=16 ** -0.5)),
])
def test_the_exact_tolerance_refuses_another_config(family, f32, what, change):
    tokens = _tokens(3 * W + 5, 5)
    ref = _reference(family, "logits")(f32[1], tokens)
    assert _off(_prefill(f32[1], change(f32[0]), tokens)[0], ref) > 10 * EXACT, what


# ---- (b) differential attention, the S6 recurrence, the write ------------------

def _two_softmaxes(q, k, v, lam, lam_init, subln, eps, scale, window=None):
    """The formula as it is published: q [s, hq, hd]; k, v [s, hkv, hd]."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = (i >= j) if window is None else (i >= j) & (i - j < window)
    out = []
    for pair in range(hq // 2):
        g = pair // (hq // hkv)
        a1 = jax.nn.softmax(jnp.where(
            seen, q[:, 2 * pair] @ k[:, 2 * g].T * scale, -jnp.inf), -1)
        a2 = jax.nn.softmax(jnp.where(
            seen, q[:, 2 * pair + 1] @ k[:, 2 * g + 1].T * scale, -jnp.inf), -1)
        o = (a1 - lam * a2) @ jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * subln
        out.append((1 - lam_init) * o)
    return jnp.stack(out, 1).reshape(s, hq * hd)


@pytest.mark.parametrize("hq,hkv,window", [(8, 4, None), (8, 4, 5), (12, 4, None),
                                           (4, 4, 3)])
def test_differential_attention_is_the_two_softmax_formula(hq, hkv, window):
    """``mha`` on zero-padded queries over paired keys and values, then the
    combination, against two explicit softmaxes a pair: with one, two and
    three pairs a key pair, causal and windowed."""
    rng = np.random.default_rng(hq + hkv)
    s, hd = 13, 16
    q, k, v = (jnp.asarray(rng.normal(size=(s, h, hd)), jnp.float32)
               for h in (hq, hkv, hkv))
    subln = jnp.asarray(rng.normal(size=(2 * hd,)), jnp.float32)
    lam, lam_init = 0.37, 0.55
    want = _two_softmaxes(q, k, v, lam, lam_init, subln, 1e-5, 0.25, window)
    got = attention.diff_attention(
        q[None], k.reshape(1, s, hkv // 2, 2 * hd),
        v.reshape(1, s, hkv // 2, 2 * hd), lam, lam_init, subln, eps=1e-5,
        scale=0.25, window=window)
    assert float(jnp.abs(got[0] - want).max()) < 1e-5
    # the same with a head's positions together, as the cache keeps them
    out = attention.mha(attention.pad_diff_queries(q[None]),
                        k.reshape(1, s, hkv // 2, 2 * hd).swapaxes(1, 2),
                        v.reshape(1, s, hkv // 2, 2 * hd).swapaxes(1, 2),
                        scale=0.25, window=window, kv_heads_major=True)
    again = attention.diff_combine(out, lam, lam_init, subln, 1e-5)
    assert float(jnp.abs(again[0] - want).max()) < 1e-5


def test_a_ring_needs_no_mask_but_the_causal_one():
    """A ring written at ``pos % window`` and read whole with the mask
    ``slot <= pos`` is the window: nothing is rotated, so a softmax does not
    care where in the ring a position lies."""
    rng = np.random.default_rng(3)
    s, h, hd, w = 21, 2, 8, 6
    q, k, v = (jnp.asarray(rng.normal(size=(1, s, h, hd)), jnp.float32)
               for _ in range(3))
    want = attention.mha(q, k, v, window=w)
    ring_k, ring_v = jnp.zeros((1, w, h, hd)), jnp.zeros((1, w, h, hd))
    for pos in range(s):
        ring_k = ring_k.at[:, pos % w].set(k[:, pos])
        ring_v = ring_v.at[:, pos % w].set(v[:, pos])
        got = attention.mha(q[:, pos:pos + 1], ring_k, ring_v, q_offset=pos)
        assert float(jnp.abs(got[:, 0] - want[:, pos]).max()) < 1e-5, pos
    assert float(jnp.abs(sambay._ring_of(k, w) - ring_k).max()) == 0.0
    assert sambay._ring_of(k[:, :4], w).shape == ring_k.shape


@pytest.mark.parametrize("s", [1, 7, 8, 9, 27])
def test_the_s6_update_is_the_scan_is_the_references_recurrence(s):
    """``s6_update`` a token at a time, ``s6_scan`` over the sequence, the
    Pallas update in place and the reference's ``lax.scan`` (state [c, n]),
    from a state that is not zero: every output and the final state.
    float32, so they differ by summation order alone."""
    rng = np.random.default_rng(s)
    b, c, n = 2, 128, 16
    x = jnp.asarray(rng.normal(size=(b, s, c)), jnp.float32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.5, size=(b, s, c)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 16.0, size=(n, c)), jnp.float32)
    bm, cm = (jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
              for _ in range(2))
    h0 = jnp.asarray(rng.normal(size=(b, n, c)), jnp.float32)
    y, last = ssm.s6_scan(x, dt, a, bm, cm, h0)
    state, stack, ys, zs = h0, jnp.stack([h0, h0]), [], []
    for t in range(s):
        y_t, state = ssm.s6_update(state, x[:, t], dt[:, t], a, bm[:, t], cm[:, t])
        z_t, stack = s6_update_in_place(stack, 1, 0, x[:, t], dt[:, t], a,
                                        bm[:, t], cm[:, t])
        ys.append(y_t)
        zs.append(z_t)

    def step(h, t):  # the reference's: h [c, n]
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t[:, None] * a.T) * h + (dt_t * x_t)[:, None] * b_t[None, :]
        return h, h @ c_t

    for row in range(b):
        ref_state, ref_y = jax.lax.scan(
            step, h0[row].T, (x[row], dt[row], bm[row], cm[row]))
        assert float(jnp.abs(y[row] - ref_y).max()) < 1e-4
        assert float(jnp.abs(last[row] - ref_state.T).max()) < 1e-4
    for other in (jnp.stack(ys, 1), jnp.stack(zs, 1)):
        assert float(jnp.abs(y - other).max()) < 1e-4
    assert float(jnp.abs(last - state).max()) < 1e-4
    assert float(jnp.abs(stack[1] - state).max()) < 1e-4
    assert bool((stack[0] == h0).all())  # the other layer: not touched


@pytest.mark.parametrize("rows,slot0", [(4, 0), (1, 2), (2, 1)])
def test_the_write_kernel_puts_one_position_a_row_and_nothing_else(rows, slot0):
    rng = np.random.default_rng(rows)
    bufs = [jnp.asarray(rng.normal(size=(2, 4, 3, 32, 8)), jnp.float32)
            for _ in range(2)]
    new = [jnp.asarray(rng.normal(size=(rows, 3, 8)), jnp.float32)
           for _ in range(2)]
    at = jnp.asarray([5, 31, 32, 16][:rows], jnp.int32)  # 32: past the end
    got = kv_write_in_place(bufs, 1, slot0, at, new)
    for buf, out, x in zip(bufs, got, new):
        want = np.asarray(buf).copy()
        for r in range(rows):
            if int(at[r]) < 32:
                want[1, slot0 + r, :, int(at[r])] = np.asarray(x[r])
        assert np.array_equal(np.asarray(out), want)


# ---- (c) prefill, then decoding on the slot tree ----------------------------

@functools.lru_cache(maxsize=None)
def _decode_step(cfg):
    """``decode_step_on_slots`` jitted once a config, the weights an argument:
    the tests that replay at one config and as many rows share a compile
    (eight replays compiled the step eight times, the weights closed over)."""
    return jax.jit(lambda params, tok, cache, pos: G.decode_step_on_slots(
        params, tok, cfg, cache, 0, pos)[:2])


def _replay(params, cfg, prompts, new):
    """The engine's own programs' bodies on a slot tree of as many rows: each
    prompt prefilled alone, as the engine does it (the last token's logits
    alone), and written into its row (``_write_row``), then ``new`` steps of
    all rows together (``decode_step_on_slots``), fed the reference's tokens.
    Returns per row the logits of every position from the prompt's last on,
    and the tree."""
    cache = G.init_cache(cfg, len(prompts), MAX_LEN)
    out = [[] for _ in prompts]
    for row, p in enumerate(prompts):
        logits, one = _prefill(params, cfg, p[None, :-new], last_only=True)
        cache = serving._write_row(cache, one, row)
        out[row].append(logits[0, -1])
    step = _decode_step(cfg)
    pos = jnp.asarray([len(p) - new for p in prompts], jnp.int32)
    for t in range(new):
        tok = jnp.asarray([p[len(p) - new + t] for p in prompts], jnp.int32)
        logits, cache = step(params, tok, cache, pos + t)
        for row in range(len(prompts)):
            out[row].append(logits[row])
    return [jnp.stack(o) for o in out], cache


NEW = 40  # decode steps: five windows, so every ring wraps whatever the prompt


def test_prefill_then_decode_on_the_slot_tree(family, f32, served):
    """Three rows at different positions, a prompt inside the window, one of
    exactly the window and one past two, 40 steps each (the rings wrap five
    times): every step's logits against the reference's full forward over
    prompt and answer."""
    seqs = [_tokens(3 + NEW, 11)[0], _tokens(W + NEW, 12)[0],
            _tokens(2 * W + 5 + NEW, 13)[0]]
    for (cfg, params), tol in ((f32, EXACT), (served, SERVED)):
        got, _ = _replay(params, cfg, seqs, NEW)
        for seq, mine in zip(seqs, got):
            ref = _reference(family, "logits")(params, seq[None])[0]
            assert _off(mine, ref[len(seq) - NEW - 1:]) < tol


def test_a_cross_layer_reads_exactly_what_the_full_layer_wrote(f32):
    """The shared buffer after a prefill and 10 steps is the full layer's keys
    and values of every position, and nothing else of the tree belongs to a
    cross or a gmu layer: the buffer with one position changed moves the
    logits, the rings' unread slots and the rest of the row past the position
    do not."""
    cfg, params = f32
    seq = _tokens(5 + 10, 21)[0]
    (_,), cache = _replay(params, cfg, [seq], 10)
    _, whole = _prefill(params, cfg, seq[None])
    for name in ("k", "v"):  # [1, rows, pairs, length, 2 hd]
        assert float(jnp.abs(cache[name][:, :, :, :15]
                             - whole[name][:, :, :, :15]).max()) < 1e-5, name
        assert float(jnp.abs(cache[name][:, :, :, 15:]).max()) == 0.0
    assert cache["k"].shape[0] == 1  # one layer's, for the eight that read it
    tok, pos = seq[-1:], jnp.asarray([15], jnp.int32)
    base = G.decode_step_on_slots(params, tok, cfg, cache, 0, pos)[0]
    moved = {**cache, "k": cache["k"].at[0, 0, 0, 3].add(1.0)}
    assert _off(G.decode_step_on_slots(params, tok, cfg, moved, 0, pos)[0], base) > 1e-3
    unread = {**cache, "k": cache["k"].at[0, 0, :, 40].add(1.0)}
    assert _off(G.decode_step_on_slots(params, tok, cfg, unread, 0, pos)[0], base) == 0.0


@pytest.mark.parametrize("what,change,least", [
    ("a bf16 recurrent state", {"state_dtype": jnp.bfloat16}, 2),
])
def test_a_bf16_state_fails_the_tolerance(family, f32, what, change, least):
    """Why the state is float32: the same program with the state kept in bf16
    (everything else float32) leaves the exact tolerance within the 40 steps,
    and its state after them is off by 1e-3 of its scale where the float32
    state's is off by 1e-6."""
    seq = _tokens(5 + NEW, 11)[0]
    ref = _reference(family, "logits")(f32[1], seq[None])[0][4:]
    states = _reference(family, "final_states")(f32[1], seq)

    def state_off(cache):
        return float(jnp.abs(cache["ssm"][:, 0].astype(jnp.float32) - states
                             ).max() / jnp.abs(states).max())

    rounded = dataclasses.replace(f32[0], **change)
    (got,), cache = _replay(f32[1], rounded, [seq], NEW)
    assert _off(got, ref) > least * EXACT and state_off(cache) > 1e-3, what
    (good,), cache = _replay(f32[1], f32[0], [seq], NEW)
    assert _off(good, ref) < EXACT and state_off(cache) < 1e-5


def test_a_bf16_softmax_fails_the_tolerance(family, f32, monkeypatch):
    """Why the softmaxes are float32: the same float32 program with the
    attention weights computed from bf16 scores leaves the exact tolerance."""
    tokens = _tokens(3 * W + 5, 9)
    ref = _reference(family, "logits")(f32[1], tokens)
    real = jax.nn.softmax
    monkeypatch.setattr(jax.nn, "softmax", lambda x, axis=-1: real(
        x.astype(jnp.bfloat16), axis=axis).astype(x.dtype))
    # (op by op: a program traced before the patch would not see it)
    assert _off(_prefill_op_by_op(f32[1], f32[0], tokens)[0], ref) > 10 * EXACT


@pytest.mark.parametrize("p,k", [(3, 3), (W, W), (W + 3, 2 * W)])
def test_decode_steps_leave_the_tree_a_longer_prefill_leaves(f32, p, k):
    cfg, params = f32
    seq = _tokens(p + k, 100 + p)[0]
    (_,), stepped = _replay(params, cfg, [seq], k)
    _, whole = _prefill(params, cfg, seq[None])
    for name in ("ssm", "conv", "wk", "wv"):  # the rings: position p at p % W
        assert float(jnp.abs(stepped[name] - whole[name]).max()) < 1e-5, name


# ---- (d) slots: reuse, buckets, the lone row ---------------------------------

def _expected(params, cfg, prompt, n):
    out = G.generate(params, jnp.asarray(prompt)[None, :], cfg,
                     max_new_tokens=n, max_len=MAX_LEN)
    return np.asarray(out)[0].tolist()


def _prompt(n, salt):
    return np.asarray(_tokens(n, salt)[0])


@pytest.mark.parametrize("k", [1, 4])
def test_the_engine_is_token_exact_and_a_reused_slot_starts_afresh(f32, k):
    """Staggered prompts on every slot, one request ending early and its slot
    taken at once by another beside rows that are mid-flight: every request's
    tokens are ``generate.generate``'s on that request alone (its decode goes
    a token at a time through ``forward_with_cache``, the other road through
    the same layers), which starts from a zeroed tree."""
    cfg, params = f32
    b = ContinuousBatcher(params, cfg, max_slots=SLOTS, max_len=MAX_LEN)
    reqs, got = {}, {}

    def admit(n_prompt, n_new, salt):
        prompt = _prompt(n_prompt, salt)
        rid, first, _ = b.submit_ex(prompt, n_new)
        reqs[rid], got[rid] = (prompt, n_new), [first]

    for i, (n_prompt, n_new) in enumerate([(5, 3), (W + 1, 22), (7, 18)]):
        admit(n_prompt, n_new, 20 + i)
    reused = False
    while b.num_active:
        for rid, toks, done in b.step_many(k):
            got[rid].extend(toks)
            if done and not reused:
                reused = True
                admit(2 * W + 1, 9, 31)
    assert reused and len(reqs) == 4
    for rid, (prompt, n) in reqs.items():
        assert got[rid] == _expected(params, cfg, prompt, n), rid
    # what the batcher counted on the way: the shared buffer's positions
    # (read: rows x the bound), the rings' (read whole; live: at most W a
    # row), and the prefills' layer-tokens
    read, live = b.take_kv_positions()
    assert read >= live > 0
    ring_read, ring_live = b.take_window_positions()
    assert ring_read % (SLOTS * W) == 0 or ring_read % W == 0
    assert 0 < ring_live <= ring_read
    computed, whole = b.take_prefill_layer_tokens()
    lengths = [5, W + 1, 7, 2 * W + 1]
    assert whole == 12 * sum(lengths)
    assert computed == sum(7 * n + 5 for n in lengths)
    assert b.take_window_positions() == (0, 0)


def test_rows_outside_a_launch_keep_their_tree_bit_for_bit(served):
    """The lone-row bucket steps the row at ``slot0`` and writes no other row
    of any buffer of the tree (state, tails, rings, the shared buffer); its
    tokens are still exact."""
    cfg, params = served
    b = ContinuousBatcher(params, cfg, max_slots=SLOTS, max_len=MAX_LEN)
    for i in range(SLOTS):  # every row holds something
        b.submit(_prompt(5 + i, 50 + i), 2)
    b.run_to_completion()
    prompt = _prompt(W + 2, 60)
    b.submit(prompt, 12)
    (slot,) = b._active
    compiled = len(b.program_stats)
    before = {name: np.asarray(buf) for name, buf in b._cache.items()}
    toks = [b._active[slot].tokens[0]]
    while b.num_active:
        for _, new, _ in b.step_many(4):  # one active row: bucket 1
            toks.extend(new)
    assert [p["bucket"] for p in b.program_stats[compiled:]] == [1]
    others = [s for s in range(SLOTS) if s != slot]
    for name, buf in b._cache.items():
        after = np.asarray(buf)
        assert np.array_equal(after[:, others], before[name][:, others]), name
        assert not np.array_equal(after[:, slot], before[name][:, slot]), name
    assert toks == _expected(params, cfg, prompt, 12)


# ---- (e) what refuses this model ----------------------------------------------

def test_the_prefix_cache_refuses_the_model(served):
    cfg, params = served
    with pytest.raises(ValueError, match="recurrent layers.*window layer's ring"):
        ContinuousBatcher(params, cfg, max_slots=SLOTS, max_len=MAX_LEN,
                          prefix_cache=PrefixKVCache(chunk=8, max_bytes=1 << 20))
    with pytest.raises(ValueError, match="kv_cache_bytes=0"):
        serving._compiled_cached_prefill(cfg, 8, 4, SLOTS, MAX_LEN)


def test_load_params_refuses_a_tree_of_another_shape(served):
    cfg, params = served
    b = ContinuousBatcher(params, cfg, max_slots=SLOTS, max_len=MAX_LEN)
    granite = hybrid.init_params(jax.random.key(0), hybrid.PRESETS["hybrid-debug"])
    with pytest.raises(ValueError, match="another model's tree"):
        b.check_params(granite)
    wider = dataclasses.replace(cfg, mamba_d_state=8)
    with pytest.raises(ValueError, match=r"A_log.*is \(4, 8, 128\)"):
        b.check_params(sambay.init_params(jax.random.key(0), wider))
    b.check_params(params)


def test_the_training_blocks_refuse_what_only_the_served_path_computes(served):
    cfg, params = served
    with pytest.raises(NotImplementedError, match="served only.*sambay"):
        llama.forward_hidden(params, _tokens(8, 1), cfg)


def test_a_cache_shorter_than_the_window_is_refused(served):
    cfg, _ = served
    with pytest.raises(ValueError, match="max_len 4 under the sliding window 8"):
        G.init_cache(cfg, 1, 4)


def test_a_multi_token_step_into_a_ring_is_refused(served):
    cfg, params = served
    with pytest.raises(NotImplementedError, match="ring takes a prompt"):
        G._forward_with_cache(params, _tokens(3, 1), cfg,
                              G.init_cache(cfg, 1, MAX_LEN), 5)


@pytest.mark.parametrize("types,why", [
    (("mamba", "window", "mamba", "full", "gmu", "window"), "mamba and window"),
    (("mamba", "cross", "mamba", "full"), "then the one full layer"),
    (("window", "mamba", "mamba", "full"), "a mamba layer first"),
    (("mamba", "attention", "mamba", "full"), "the kinds are"),
])
def test_a_config_whose_layer_types_do_not_fit_is_refused(types, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(sambay.PRESETS["sambay-debug"], n_layers=len(types),
                            layer_types=types)
    with pytest.raises(ValueError, match="pair the key/value heads"):
        dataclasses.replace(sambay.PRESETS["sambay-debug"], n_kv_heads=1)


# ---- (f) donation, the counters, the recorder ---------------------------------

def test_every_engine_program_aliases_every_buffer_of_the_tree(f32):
    cfg, params = f32
    shapes = jax.eval_shape(lambda: params)
    tree = jax.eval_shape(lambda: G.init_cache(cfg, SLOTS, MAX_LEN))
    bufs = [tree[name] for name in G.cache_names(cfg)]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    nbytes = sum(x.size * x.dtype.itemsize for x in bufs)
    for bucket in (SLOTS, 1):
        compiled = serving._compiled_bucket_scan(
            cfg, bucket, SLOTS, MAX_LEN, 4).lower(
            shapes, *bufs, i32(bucket), i32(bucket), i32()).compile()
        traffic = hlo_copies.cache_traffic(
            compiled, tree, rows=bucket, steps=4,
            bounds=G.kv_read_bounds(MAX_LEN), length_axis=cfg.kv_length_axis)
        assert traffic["cache_donated"] and traffic["state_donated"]
        assert compiled.memory_analysis().alias_size_in_bytes >= nbytes
        assert traffic["cache_bytes"] == 2 * (tree["k"].size + tree["wk"].size) * 4
        assert traffic["window_bytes"] == 2 * tree["wk"].size * 4
        # (what the state moves is the Pallas call's to say, on the chip's
        # compile: ``tests/test_aot_tpu_compile.py``; interpreted here, the
        # call is loops of slices)
        assert traffic["state_copy_bytes_per_step"] > 0
    compiled = serving._compiled_slot_prefill(cfg, 11, SLOTS, MAX_LEN).lower(
        shapes, *bufs, i32(1, 11), i32()).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= nbytes


def test_the_recorder_shows_the_new_counters_and_other_models_none_of_them(served):
    cfg, params = served
    engine = ContinuousEngine(params, cfg, max_slots=SLOTS, max_len=MAX_LEN,
                              decode_stride=2, kv_cache_bytes=0)
    try:
        streams = [engine.submit_stream(_prompt(5 + 4 * i, 80 + i), 14)
                   for i in range(2)]
        for s in streams:
            assert len(list(iter(s.get, None))) == 14
        out = engine._recorder.window_summary(0.0, 1e12)
    finally:
        engine.shutdown()
    layout = out["state_layout"]
    assert layout["kinds"] == {"mamba": 4, "window": 3, "full": 1, "gmu": 2,
                               "cross": 2}
    assert layout["layers"] == {"attention": 1, "recurrent": 4}
    assert layout["kv_readers"] == 3 and layout["sliding_window"] == W
    assert layout["window_bytes_per_row"] == 2 * 3 * W * 4 * 8 * 2
    assert layout["kv_bytes_per_position"] == 2 * 4 * 8 * 2
    assert layout["state_bytes_per_row"] == 4 * (128 * 16 * 4 + 3 * 128 * 2)
    assert out["kv_positions_read"] >= out["kv_positions_live"] > 0
    assert out["window_positions_read"] >= out["window_positions_live"] > 0
    assert out["prefill_layer_tokens"] == 7 * 14 + 2 * 5
    assert out["prefill_layer_tokens_whole"] == 12 * 14
    progs = [p for p in out["decode_programs"] if p["bucket"] > 1]
    assert progs and all(p["state_donated"] and p["cache_donated"]
                         and p["window_bytes"] == 2 * 3 * SLOTS * W * 4 * 8 * 2
                         for p in progs)
    # a dense engine and a Granite-like one record none of the new ones
    for other, init in ((llama.PRESETS["debug"], llama.init_params),
                        (hybrid.PRESETS["hybrid-debug"], hybrid.init_params)):
        engine = ContinuousEngine(init(jax.random.key(0), other), other,
                                  max_slots=2, max_len=64, decode_stride=2,
                                  kv_cache_bytes=0)
        try:
            assert len(list(iter(engine.submit_stream(
                _prompt(5, 1), 6).get, None))) == 6
            out = engine._recorder.window_summary(0.0, 1e12)
        finally:
            engine.shutdown()
        assert not [k for k in out if k.startswith(("window_positions", "prefill_layer"))]
        assert "kinds" not in out.get("state_layout", {})
        assert not [p for p in out["decode_programs"] if "window_bytes" in p]
