"""EvaByte's block through the training path (``models/llama.py`` with
``attn_kind="eva"``, ``ops/eva.py``, ``ops/pallas/eva_attn.py``) at a small
size on the CPU, seeded: hidden 64, 4 heads of 16, window 32, chunk 4, 8
prediction heads over 320 ids, sequences of 128 (four whole windows) and 112
(the last window part full). Held to the benchmark's plain reference
(``benchmark/families/multibyte_eva.py``, which imports nothing of
``ray_tpu``): the logits of all eight heads, the loss, every parameter's
gradient; and to what the mechanism says of itself: the summaries against a
loop, window 0 against plain causal attention, a moved key against the exact
part alone, every query's visible set and the kernels' visits against
brute-force lists.

Tolerances. The program is traced in float32 here (operands, sums and
parameters), so the two differ by the order of their float32 sums alone: the
kernels' running softmax against one softmax a row, the loss in chunks
against one mean, 1/sqrt(d) applied to the scores against to q. Logits of
magnitude ~1 agree to 2e-5 (a few hundred float32 roundings of 6e-8 through
two layers), the loss to 2e-6 of itself, a gradient to 2e-5 of its largest
entry. One bf16 case holds the cell's own dtypes to 2e-2: bf16's 3 decimal
digits through two layers.
"""

import dataclasses
import functools
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import spec  # noqa: E402
from ray_tpu.models import generate, llama  # noqa: E402
from ray_tpu.ops import eva  # noqa: E402
from ray_tpu.ops.attention import mha  # noqa: E402
from ray_tpu.ops.pallas import eva_attn, eva_mix  # noqa: E402
from ray_tpu.ops.rope import apply_rope, rope_angles  # noqa: E402
from ray_tpu.parallel import train_step as ts  # noqa: E402
from ray_tpu.util import flops  # noqa: E402

WINDOW, CHUNK, HEADS, WIDTH, VOCAB, PRED = 32, 4, 4, 16, 320, 8
HF = {"attention_class": "eva", "chunk_size": CHUNK, "window_size": WINDOW,
      "fp32_skip_add": True, "norm_add_unit_offset": True, "hidden_size": 64,
      "intermediate_size": 128, "num_attention_heads": HEADS,
      "num_key_value_heads": HEADS, "num_hidden_layers": 2,
      "num_pred_heads": PRED, "rms_norm_eps": 1e-5, "rope_theta": 100000,
      "tie_word_embeddings": False, "vocab_size": VOCAB}
CFG_FILE = {"config": HF, "assumed": {}}
SEQS = (128, 112)
IMPLS = ("flash", "xla")


@pytest.fixture(scope="module")
def family():
    return spec.load_family("multibyte_eva")


def _cfg(family, impl="flash", seq=128, dtype=jnp.float32, loss_chunk=32):
    cfg = family.program_config(CFG_FILE, 2, max_seq_len=seq, attn_impl=impl,
                                loss_chunk=loss_chunk)
    return dataclasses.replace(cfg, param_dtype=dtype, compute_dtype=dtype)


@functools.cache
def _params(family):
    """Seeded weights with the norms' offsets, ``phi`` and ``mu`` moved off
    their small starts, so that none of them is a bystander. (Made, as
    everything below that is not itself under test, as one compiled program:
    op by op each small operation is a program for the CPU backend to build,
    and they were half of this file's time, PR 64.)"""
    cfg = _cfg(family)

    @jax.jit
    def make():
        params = family.init_params(jax.random.key(7), cfg)
        keys = iter(jax.random.split(jax.random.key(8), 8))
        layers = params["layers"]
        for name in ("attn_norm", "mlp_norm"):
            layers[name] = 0.1 * jax.random.normal(next(keys),
                                                   layers[name].shape)
        params["final_norm"] = 0.1 * jax.random.normal(next(keys), (64,))
        for name in ("eva_phi", "eva_mu"):
            layers[name] = 0.5 * jax.random.normal(next(keys),
                                                   layers[name].shape)
        return params

    return make()


@functools.cache
def _tokens(seq):
    return jax.random.randint(jax.random.key(seq), (2, seq + 1), 0, VOCAB)


@pytest.fixture(scope="module")
def both(family):
    """{(impl, seq): the program's (loss, grads)} and {seq: the
    reference's}, each computed once."""
    params = _params(family)
    program, reference = {}, {}
    for seq in SEQS:
        batch = {"tokens": _tokens(seq)}
        for impl in IMPLS:
            cfg = _cfg(family, impl, seq, loss_chunk=32 if seq == 128 else 0)
            program[impl, seq] = jax.jit(jax.value_and_grad(
                lambda p: llama.lm_loss(p, batch, cfg)))(params)
        reference[seq] = jax.jit(lambda p, t: family.loss_and_grads(
            p, t, CFG_FILE))(params, batch["tokens"])
    return params, program, reference


# ---- program against reference ---------------------------------------------------

@pytest.mark.parametrize("seq", SEQS)
@pytest.mark.parametrize("impl", IMPLS)
def test_logits_of_all_eight_heads_agree_with_the_reference(family, impl, seq):
    params, tokens = _params(family), _tokens(seq)[:, :-1]
    cfg = _cfg(family, impl, seq)
    got = np.asarray(jax.jit(lambda p, t: llama.forward(p, t, cfg))(
        params, tokens))
    want = np.asarray(jax.jit(lambda p, t: family.logits(p, t, CFG_FILE))(
        params, tokens))
    assert got.shape == want.shape == (2, seq, PRED * VOCAB)
    assert np.abs(want).max() > 1.0
    # per head, so that no head hides behind another's scale
    per_head = np.abs(got - want).reshape(2, seq, PRED, VOCAB).max((0, 1, 3))
    assert per_head.max() < 2e-5, per_head


@pytest.mark.parametrize("seq", SEQS)
@pytest.mark.parametrize("impl", IMPLS)
def test_the_loss_agrees_with_the_reference(both, impl, seq):
    _, program, reference = both
    got, want = float(program[impl, seq][0]), float(reference[seq][0])
    assert 5.0 < want < 7.5      # about log 320 of random weights
    assert abs(got - want) < 2e-6 * want, (got, want)


LEAVES = ["embed", "final_norm", "lm_head"] + ["layers/" + n for n in (
    "attn_norm", "eva_mu", "eva_phi", "mlp_norm", "w_down", "w_gate", "w_up",
    "wk", "wo", "wq", "wv")]


@pytest.mark.parametrize("leaf", LEAVES)
def test_every_gradient_agrees_with_the_reference(both, leaf):
    """Both implementations and both sequence lengths a leaf: the kernels'
    backward (``dq``, ``dkv``, ``dsum``) and the summaries' autodiff against
    ``jax.grad`` through the reference's dense softmax."""
    params, program, reference = both

    def at(tree):
        for part in leaf.split("/"):
            tree = tree[part]
        return tree

    assert at(params).size   # the tree has no leaf this list leaves out
    assert sorted(LEAVES) == sorted(
        "/".join(str(k.key) for k in path)
        for path, _ in jax.tree_util.tree_leaves_with_path(params))
    for seq in SEQS:
        want = np.asarray(at(reference[seq][1]))
        scale = float(np.abs(want).max())
        assert scale > 0, (leaf, seq)
        for impl in IMPLS:
            worst = float(np.abs(
                np.asarray(at(program[impl, seq][1])) - want).max())
            assert worst < 2e-5 * scale, (leaf, impl, seq, worst, scale)


def test_the_cells_own_dtypes_hold_the_loss(family, both):
    """bf16 parameters and operands, the float32 stream, as the cell runs."""
    params, _, reference = both
    cfg = _cfg(family, "flash", 128, dtype=jnp.bfloat16)
    got = float(jax.jit(lambda p, t: llama.lm_loss(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), p), {"tokens": t},
        cfg))(params, _tokens(128)))
    assert abs(got - float(reference[128][0])) < 2e-2 * got


def test_one_precision_down_is_another_loss(family, both):
    """The reference through ``float8_e5m2`` (the loss limit's control) lies
    further from itself than the program does by orders."""
    params, _, reference = both
    want = float(reference[128][0])
    low = float(jax.jit(lambda p, t: family.loss(
        p, t, CFG_FILE, round_to=jnp.float8_e5m2)["loss"])(
            params, _tokens(128)))
    assert abs(low - want) > 1e-4 * want   # fifty times the limit above


# ---- the mechanism by itself ---------------------------------------------------

@functools.partial(jax.jit, static_argnums=range(6),
                   static_argnames=("seed", "dtype"))
def _qkv(seq, seed=0, heads=HEADS, width=WIDTH, batch=2, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(key, (batch, seq, heads, width)).astype(dtype)
               for key in keys[:3])
    phi, mu = (0.5 * jax.random.normal(key, (heads, width)).astype(dtype)
               for key in keys[3:])
    return q, k, v, phi, mu


@functools.partial(jax.jit, static_argnums=(0, 1))
def _tables(seq, dtype=jnp.float32):
    """(sin, cos) as ``llama._rope_tables`` makes them, and longer than the
    sequence: a reader takes its first ``seq`` rows."""
    return rope_angles(seq + 8, WIDTH, 100000.0, dtype)


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_the_summaries_against_a_loop(chunk):
    _, k, v, phi, mu = _qkv(64, seed=chunk)
    heads_first = lambda a: a.transpose(0, 2, 1, 3).reshape(2 * HEADS, 64, WIDTH)
    ks, vs = jax.jit(lambda k, v, phi, mu: eva.summaries(
        heads_first(k), heads_first(v), jnp.tile(phi, (2, 1)),
        jnp.tile(mu, (2, 1)), chunk))(k, v, phi, mu)
    assert ks.shape == vs.shape == (2 * HEADS, 64 // chunk, WIDTH)
    ks, vs = np.asarray(ks), np.asarray(vs)
    k, v, phi, mu = map(np.asarray, (k, v, phi, mu))
    for b in range(2):
        for a in range(HEADS):
            for j in range(64 // chunk):
                at = slice(chunk * j, chunk * (j + 1))
                logit = k[b, at, a] @ phi[a]
                alpha = np.exp(logit - logit.max())
                alpha /= alpha.sum()
                np.testing.assert_allclose(
                    ks[b * HEADS + a, j], mu[a] + alpha @ k[b, at, a], atol=2e-6)
                np.testing.assert_allclose(
                    vs[b * HEADS + a, j], alpha @ v[b, at, a], atol=2e-6)


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_query_in_window_0_sees_plain_causal_attention(impl):
    """No summary is visible there, whatever ``phi`` and ``mu`` are."""
    q, k, v, phi, mu = _qkv(128, seed=1)
    sin, cos = _tables(128)
    run = jax.jit(lambda phi, mu: eva.eva_attention(
        q, k, v, sin, cos, phi, mu, window=WINDOW, chunk=CHUNK, impl=impl))
    out = np.asarray(run(phi, mu))
    plain = jax.jit(lambda q, k, v: mha(
        apply_rope(q, sin, cos)[:, :WINDOW],
        apply_rope(k, sin, cos)[:, :WINDOW], v[:, :WINDOW], causal=True))(
            q, k, v)
    np.testing.assert_allclose(out[:, :WINDOW], plain, atol=2e-6)
    other = np.asarray(run(3 * phi, mu + 1))
    np.testing.assert_array_equal(out[:, :WINDOW], other[:, :WINDOW])
    assert np.abs(out[:, WINDOW:] - other[:, WINDOW:]).max() > 1e-3


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_a_moved_key_reaches_its_own_window_through_the_exact_part_alone(impl):
    """Key 37 (window 1, chunk 9) moved: queries before it and in window 0
    are unmoved; the queries of window 1 at or after it see the moved key
    itself and NOT the moved summary (their outputs are those of the moved
    key with the old summaries); later windows see it through its chunk's
    summary alone (the moved summary with the old keys)."""
    q, k, v, phi, mu = _qkv(128, seed=2)
    sin, cos = _tables(128)
    moved = k.at[:, 37].add(1.0)
    run = jax.jit(lambda k_: eva.eva_attention(
        q, k_, v, sin, cos, phi, mu, window=WINDOW, chunk=CHUNK, impl=impl))
    before, after = np.asarray(run(k)), np.asarray(run(moved))
    np.testing.assert_array_equal(before[:, :37], after[:, :37])
    assert np.abs(after[:, 37:64] - before[:, 37:64]).min(1).max() > 1e-4

    @jax.jit
    def dense(keys_from, summaries_from):
        first = lambda a: a.transpose(0, 2, 1, 3).reshape(2 * HEADS, 128, WIDTH)
        turned = lambda a: first(apply_rope(a, sin, cos))
        ks, vs = eva.summaries(turned(summaries_from), first(v),
                               jnp.tile(phi, (2, 1)), jnp.tile(mu, (2, 1)), CHUNK)
        o = eva._attend_dense(turned(q), turned(keys_from), first(v), ks, vs,
                              window=WINDOW, chunk=CHUNK, scale=WIDTH ** -0.5)
        return o.reshape(2, HEADS, 128, WIDTH).transpose(0, 2, 1, 3)

    np.testing.assert_allclose(after[:, 32:64], dense(moved, k)[:, 32:64],
                               atol=2e-6)
    np.testing.assert_allclose(after[:, 64:], dense(k, moved)[:, 64:],
                               atol=2e-6)
    assert np.abs(after[:, 64:] - before[:, 64:]).max() > 1e-5


def _seen_by_hand(seq, window, chunk):
    """{t: (keys, summaries)} from the rule as it is worded."""
    return {t: ({m for m in range(seq) if m // window == t // window and m <= t},
                {j for j in range(seq // chunk)
                 if (chunk * j) // window < t // window})
            for t in range(seq)}


@pytest.mark.parametrize("seq,window,chunk", [
    (128, 32, 4), (112, 32, 4), (96, 32, 8), (64, 64, 16), (48, 16, 16)])
def test_the_visible_set_of_every_query(seq, window, chunk):
    mask = np.asarray(eva.visible(seq, window, chunk))
    assert mask.shape == (seq, seq + seq // chunk)
    for t, (keys, pooled) in _seen_by_hand(seq, window, chunk).items():
        assert set(np.flatnonzero(mask[t, :seq])) == keys, t
        assert set(np.flatnonzero(mask[t, seq:])) == pooled, t
    # the cost files count the same pairs
    fam = spec.load_family("multibyte_eva")
    kernel = spec.load_kernels()["eva_attn"]
    pairs = (int(mask[:, :seq].sum()), int(mask[:, seq:].sum()))
    assert fam.visible_pairs(seq, window, chunk) == pairs
    assert kernel.visible_pairs(seq, window, chunk) == pairs


@pytest.mark.parametrize("windows,blocks_per_window", [(1, 1), (4, 1), (3, 2),
                                                       (8, 2), (2, 4)])
def test_the_kernels_visit_the_tiles_that_hold_a_visible_pair_and_no_other(
        windows, blocks_per_window):
    """``eva_attn.visits`` against a brute-force list at a block of 8 rows
    and a chunk of 4, in all three orders; a q block's (a key block's)
    visits lie together, the first and last flagged; the diagonal tiles and
    no others are the masked ones."""
    block, chunk = 8, 4
    window = block * blocks_per_window
    seq, per = windows * window, window // chunk
    mask = np.asarray(eva.visible(seq, window, chunk))
    local = {(i, j) for i in range(seq // block) for j in range(seq // block)
             if mask[i * block:(i + 1) * block, j * block:(j + 1) * block].any()}
    pooled = {(i, j) for i in range(seq // block) for j in range(windows)
              if mask[i * block:(i + 1) * block,
                      seq + j * per:seq + (j + 1) * per].any()}
    need = eva_attn.tiles_needed(windows, blocks_per_window)
    assert (need["local"], need["summary"]) == (len(local), len(pooled))
    for kind in ("fwd", "dq"):
        v = eva_attn.visits(windows, blocks_per_window, kind)
        is_sum = v["kind"] == eva_attn.SUMMARY
        assert set(zip(v["q"][~is_sum], v["l"][~is_sum])) == local
        assert set(zip(v["q"][is_sum], v["s"][is_sum])) == pooled
        assert len(v["q"]) == len(local) + len(pooled)
        diagonal = v["kind"] == eva_attn.DIAGONAL
        assert (v["q"][diagonal] == v["l"][diagonal]).all()
        assert (v["l"][v["kind"] == eva_attn.CLEAR]
                < v["q"][v["kind"] == eva_attn.CLEAR]).all()
        assert list(v["q"]) == sorted(v["q"])
        edges = np.flatnonzero(np.diff(v["q"])) + 1
        assert list(np.flatnonzero(v["first"])) == [0, *edges]
        assert list(np.flatnonzero(v["last"])) == [*(edges - 1), len(v["q"]) - 1]
    v = eva_attn.visits(windows, blocks_per_window, "dkv")
    assert set(zip(v["q"], v["k"])) == local and len(v["q"]) == len(local)
    assert list(v["k"]) == sorted(v["k"])
    v = eva_attn.visits(windows, blocks_per_window, "dsum")
    assert set(zip(v["q"], v["k"])) == pooled and len(v["q"]) == len(pooled)
    assert (v["kind"] == eva_attn.CLEAR).all()
    p = eva.plan(seq, HEADS, WIDTH, window, chunk)
    assert p["windows"] == windows and p["chunks"] == seq // chunk
    assert p["summaries_seen"] == (windows - 1) * per


@pytest.mark.parametrize("blocks_per_window", [2, 4])
def test_a_window_of_several_blocks_is_the_dense_form(monkeypatch,
                                                      blocks_per_window):
    """The published window is two blocks of 1,024: here a window of 32 in
    blocks of 16 and of 8, values and all five gradients."""
    monkeypatch.setattr(eva_attn, "_TARGET_BLOCK", WINDOW // blocks_per_window)
    q, k, v, phi, mu = _qkv(112, seed=3)
    sin, cos = _tables(112)
    weigh = jax.random.normal(jax.random.key(9), q.shape)

    def run(impl):
        return jax.jit(jax.value_and_grad(lambda q, k, v, phi, mu: jnp.sum(
            weigh * eva.eva_attention(q, k, v, sin, cos, phi, mu, window=WINDOW,
                                      chunk=CHUNK, impl=impl)),
            argnums=range(5)))(q, k, v, phi, mu)

    (got, got_grads), (want, want_grads) = run("pallas"), run("xla")
    assert abs(float(got - want)) < 1e-4
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, atol=2e-5)


# ---- the passes between the projections and the kernels (``eva_mix``) ------------

def _xla_mix(q, k, v, sin, cos, phi, mu, window, chunk):
    """What ``eva_mix.mix`` replaces, as ``eva_attention(impl="xla")`` does
    it: ``apply_rope``, a transpose a stream, the pad to whole windows,
    ``eva.summaries``."""
    b, s, _ = q.shape
    h, d = phi.shape
    pad = -(-s // window) * window - s

    def heads_first(a):
        a = a.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        return jnp.pad(a, ((0, 0), (0, pad), (0, 0)))

    q, k = (apply_rope(a.reshape(b, s, h, d), sin, cos) for a in (q, k))
    q, k, v = heads_first(q), heads_first(k), heads_first(v.reshape(b, s, h, d))
    return (q, k, v) + eva.summaries(k, v, jnp.tile(phi, (b, 1)),
                                     jnp.tile(mu, (b, 1)), chunk)


# (sequence, rows a block): four whole windows; a last window part full; a
# window of several row blocks, whole and part full
MIX_CASES = [(128, 256), (112, 256), (128, 8), (112, 16)]


def _mix_operands(seq, dtype):
    q, k, v, phi, mu = _qkv(seq, seed=seq, dtype=dtype)
    flat = lambda a: np.asarray(a).reshape(2, seq, HEADS * WIDTH)
    return (flat(q), flat(k), flat(v), *_tables(seq, dtype), phi, mu)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("seq,rows", MIX_CASES)
def test_the_pair_makes_the_kernels_operands_as_xla_did(monkeypatch, seq, rows,
                                                        dtype):
    """Rotated q, k and v heads first and padded to whole windows, ``ks``,
    ``vs``: the forward call against ``apply_rope`` + the transposes +
    ``eva.summaries``. float32: to the order of the sums. bfloat16: to two
    of bf16's last places (XLA's rotation rounds each of its three
    operations to bf16, the call's is float32 rounded once; a summary is a
    sum of sixteen such keys)."""
    monkeypatch.setattr(eva_mix, "_ROWS", rows)
    args = _mix_operands(seq, dtype)
    got = jax.jit(lambda *a: eva_mix.mix(*a, WINDOW, CHUNK))(*args)
    want = jax.jit(lambda *a: _xla_mix(*a, WINDOW, CHUNK))(*args)
    padded = -(-seq // WINDOW) * WINDOW
    assert [a.shape for a in got] == [(2 * HEADS, padded, WIDTH)] * 3 + [
        (2 * HEADS, padded // CHUNK, WIDTH)] * 2
    rtol, atol = (0, 1e-6) if dtype == jnp.float32 else (2 ** -6, 2 ** -8)
    for name, g, w in zip(eva.RESIDUAL_NAMES, got, want):
        assert g.dtype == w.dtype == dtype, name
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), w, err_msg=name,
                                   rtol=rtol, atol=atol * np.abs(w).max())
    # the rows past the sequence: keys of zeros, and their chunks' ks is mu
    assert not np.asarray(got[1][:, seq:], np.float32).any()
    np.testing.assert_array_equal(
        np.asarray(got[3][:HEADS, seq // CHUNK:], np.float32),
        np.broadcast_to(np.asarray(args[-1], np.float32)[:, None],
                        (HEADS, (padded - seq) // CHUNK, WIDTH)))


@pytest.mark.parametrize("leaf", ["dq", "dk", "dv", "dphi", "dmu"])
@pytest.mark.parametrize("seq,rows", MIX_CASES)
def test_every_cotangent_of_the_pair_against_autodiff_of_xlas_form(
        monkeypatch, seq, rows, leaf):
    """The backward call (the pooling's pull-back recomputed from the kept
    rotated k and v, the rotation turned back, ``dphi`` and ``dmu`` gathered
    over the row blocks) against ``jax.grad`` through ``apply_rope`` and
    ``eva.summaries``, under a random cotangent on all five results."""
    monkeypatch.setattr(eva_mix, "_ROWS", rows)
    args = _mix_operands(seq, jnp.float32)
    at = ["dq", "dk", "dv", None, None, "dphi", "dmu"].index(leaf)

    def loss(fn, x):
        keys = jax.random.split(jax.random.key(11), 5)
        outs = fn(*args[:at], x, *args[at + 1:], WINDOW, CHUNK)
        return sum(jnp.sum(jax.random.normal(key, o.shape) * o)
                   for key, o in zip(keys, outs))

    got = jax.jit(jax.grad(lambda x: loss(eva_mix.mix, x)))(args[at])
    want = np.asarray(jax.jit(jax.grad(lambda x: loss(_xla_mix, x)))(args[at]))
    assert got.shape == want.shape and np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5 * float(np.abs(want).max()))


def test_the_last_windows_summaries_get_no_gradient():
    """No query sees them (``dsum`` never visits them): the attention's
    pull-back hands the pair zeros there, and what the pair makes of zeros
    is no cotangent of the pooling's: ``phi`` and ``mu`` hear of the earlier
    windows alone."""
    q, k, v, phi, mu = _qkv(128, seed=5)
    sin, cos = _tables(128)
    flat = lambda a: a.reshape(2, 128, HEADS * WIDTH)
    outs, pull_mix = jax.vjp(lambda phi, mu: eva_mix.mix(
        flat(q), flat(k), flat(v), sin, cos, phi, mu, WINDOW, CHUNK), phi, mu)
    o, pull = jax.vjp(lambda *a: eva_attn.eva_attend(
        *a, window=WINDOW, chunk=CHUNK, scale=WIDTH ** -0.5), *outs)
    *_, dks, dvs = pull(jax.random.normal(jax.random.key(6), o.shape))
    last = (128 - WINDOW) // CHUNK
    assert not np.asarray(dks[:, last:]).any() and not np.asarray(dvs[:, last:]).any()
    assert np.abs(np.asarray(dks[:, :last])).min(axis=(0, 2)).max() > 0
    zero = [jnp.zeros_like(a) for a in outs[:3]]
    whole = pull_mix((*zero, dks, dvs))
    # the same from the earlier windows' cotangents alone, the last
    # window's replaced by anything: they are zeros, so nothing changes
    assert float(jnp.abs(whole[0]).max()) > 1e-3
    none = pull_mix((*zero, jnp.zeros_like(dks), jnp.zeros_like(dvs)))
    assert not np.asarray(none[0]).any() and not np.asarray(none[1]).any()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_the_plan_says_who_made_the_kernels_operands(impl):
    plan = eva.plan(128, HEADS, WIDTH, WINDOW, CHUNK, impl=impl)
    assert plan["mix"] == plan["impl"] == impl


# ---- what is refused ----------------------------------------------------------------

@pytest.mark.parametrize("seq", [126, 130])
def test_a_sequence_of_no_whole_number_of_chunks_is_refused_by_name(family, seq):
    with pytest.raises(ValueError, match="whole number of chunks of 4 "
                                         r"\(eva_chunk\)"):
        llama.forward(_params(family), _tokens(seq)[:, :-1], _cfg(family))
    with pytest.raises(spec.SpecError, match="whole chunks of 4"):
        family.logits(_params(family), _tokens(seq)[:, :-1], CFG_FILE)


def test_the_serving_constructors_refuse_the_kind_by_name(family):
    cfg = _cfg(family)
    with pytest.raises(NotImplementedError, match=r"\['eva'\] are trained only"):
        generate.init_cache(cfg, 1, 64)
    with pytest.raises(NotImplementedError, match=r"\['eva'\]"):
        llama.refuse_trained_only(cfg)
    llama.refuse_trained_only(llama.PRESETS["debug"])   # and no one else
    with pytest.raises(NotImplementedError, match="attn_kind='eva'"):
        llama.lm_loss(_params(family), {
            "tokens": _tokens(128),
            "segment_ids": jnp.zeros((2, 128), jnp.int32)}, cfg)
    with pytest.raises(NotImplementedError, match="n_kv_heads must equal"):
        llama.forward(_params(family), _tokens(128)[:, :-1],
                      dataclasses.replace(cfg, n_kv_heads=2))


# ---- what the program counts -----------------------------------------------------

def test_the_programs_counts(family):
    cfg = _cfg(family)
    params = family.init_params(jax.random.key(0), cfg)
    assert cfg.num_params() == sum(a.size for a in jax.tree.leaves(params))
    assert params["lm_head"].shape == (64, PRED * VOCAB)
    assert params["layers"]["eva_phi"].shape == (2, HEADS, WIDTH)
    # phi and mu: init_std, cut off at one deviation; norms from zero
    for name in ("eva_phi", "eva_mu"):
        leaf = np.asarray(params["layers"][name])
        assert 0 < np.abs(leaf).max() <= eva.INIT_STD + 1e-9
    assert not np.asarray(params["final_norm"]).any()
    # a token's mixer products at s 128: the windows' causal halves and 8
    # summaries a window before, a score and a value product each, and the
    # pooling's two sums a position
    local, pooled = 4 * 32 * 33 // 2, 8 * 32 * (0 + 1 + 2 + 3)
    assert flops._attention_madds(cfg, 128) == pytest.approx(
        2 * HEADS * WIDTH * (2 * (local + pooled) / 128 + 2))
    assert flops._attention_madds(cfg, 128) == pytest.approx(
        family.attention_flops_per_token(HF, 2, 128))
    assert flops._attention_madds(llama.PRESETS["debug"], 128) == 2 * (
        2 * 64 * 4 * 16)
    rules = llama.sharding_rules()
    assert tuple(rules.spec_for("layers/eva_phi")) == (None, "tp", None)


@pytest.mark.parametrize("impl,visited", [("flash", 10), ("xla", 32)])
def test_the_recorder_carries_the_eva_plan(family, impl, visited):
    """``StepDriver`` notes the plan as its launch traces it; the summary,
    a window's summary and the totals the trainer keeps all carry it."""
    from ray_tpu.train.driver import StepDriver

    cfg = _cfg(family, impl)
    opt = ts.default_optimizer(total_steps=100)
    params = family.init_params(jax.random.key(3), cfg)
    driver = StepDriver(cfg, opt, steps_per_launch=1)
    batches = [{"tokens": np.asarray(_tokens(128))} for _ in range(2)]
    driver.run(params, jax.jit(opt.init)(params), batches)
    rec = driver.recorder
    try:
        deadline = time.time() + 30
        while time.time() < deadline and rec.summary()["in_flight"]:
            time.sleep(0.01)
        plan = rec.summary()["eva_plan"]
        assert plan == eva.plan(128, HEADS, WIDTH, WINDOW, CHUNK, batch=2,
                                impl="pallas" if impl == "flash" else "xla")
        assert (plan["windows"], plan["chunks"], plan["summaries_seen"]) \
            == (4, 32, 24)
        assert (plan["tiles_visited"], plan["tiles_needed"]) == (visited, 10)
        assert rec.window_summary(0.0, 1e18)["eva_plan"] == plan
        assert rec.launch_totals()["eva_plan"] == plan
        assert rec.summary()["kda_plan"] == {}
    finally:
        rec.close()


def test_who_has_no_eva_mixer_never_loads_the_kernels():
    """A process that traces Mistral's step, flash kernels and all, has not
    loaded ``ops/pallas/eva_attn.py``; tracing an EVA layer loads it."""
    code = """if True:
        import sys
        import jax, jax.numpy as jnp
        from ray_tpu.models import llama
        from ray_tpu.parallel import train_step as ts
        cfg = llama.LlamaConfig(vocab_size=64, d_model=32, n_layers=1,
                                n_heads=2, n_kv_heads=2, d_ff=64,
                                attn_impl="flash")
        opt = ts.default_optimizer(total_steps=10)
        step = ts.make_multi_step(cfg, opt, 1)
        params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
        batch = {"tokens": jax.ShapeDtypeStruct((1, 1, 65), jnp.int32)}
        text = str(jax.make_jaxpr(step._jit)(
            params, jax.eval_shape(opt.init, params), batch))
        assert "flash_fwd" in text
        print("mistral", "ray_tpu.ops.pallas.eva_attn" in sys.modules)
        import dataclasses
        cfg = dataclasses.replace(cfg, attn_kind="eva", eva_window=32,
                                  eva_chunk=4)
        step = ts.make_multi_step(cfg, opt, 1)
        params = jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg))
        text = str(jax.make_jaxpr(step._jit)(
            params, jax.eval_shape(opt.init, params), batch))
        assert "eva_attn_fwd_bh2_s64_d16_w32_c4" in text
        print("evabyte", "ray_tpu.ops.pallas.eva_attn" in sys.modules)
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.split() == ["mistral", "False", "evabyte", "True"]
