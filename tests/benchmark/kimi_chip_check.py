"""What the cell ``kimilinear-train-16k`` cannot show by its first loss,
shown where it can be repeated (PR 48): the BACKWARD of the chunked delta
rule at the timed shape and dtype. A first loss is a forward; the CPU tests
hold the gradients to the recurrence at widths 16-32 in float32. Here, on the
chip through

    chiprun -- python3 tests/benchmark/kimi_chip_check.py gradient [--seed N]

(and at a tiny size on the CPU by ``test_benchmark_kimi_linear.py``), one
layer's recurrence at the cell's own shape (b1 x s16384, 32 heads of 128:
256 chunks, ``ops/kda.SEGMENT`` chunks a segment), operands in bf16 as the
step hands them on, gates drawn as the layer's initialisation spreads them
(``mixers.A_RANGE``, ``DT_RANGE``: decays from 0.85 to 0.99999 a token and
channel, so ``G`` of minus hundreds inside a chunk where a channel forgets
fast):

``gradient``   ``jax.grad`` of ``sum(o * w)`` through ``kda.kda_chunked``
               against the same through ``kda.kda_recurrent`` (a token at a
               time, float32, products at ``highest``, rematted in blocks of
               256 tokens so that a state a token is never held for more
               than a block), leaf by leaf (``q k v g beta``) as the norm of
               the difference over the norm of the reference's; the worst
               leaf is judged against ``GRAD_TOL``. Beside it two planted
               faults, each a backward that is wrong in one place and has to
               read beyond ``GRAD_TOL``: ``dropped_segment`` (one segment of
               the output's cotangent never reaches the inputs) and
               ``cut_state`` (the cotangent of the state a segment starts
               with is dropped, so no segment hears of a later one).

Prints one JSON line last and exits 1 where the check fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

#: the worst leaf's distance the program has to stay within. Between two
#: readings (my chip runs, PR 48, three seeds at the cell's shape; PERF.md
#: section 6): the program's largest, 0.0049 (``v``; 0.0047-0.0049 over the
#: seeds: bf16 operands), and the nearer planted fault's smallest, 0.176
#: (``dropped_segment``, one of 32 segments: sqrt(1/32); ``cut_state`` reads
#: 0.23-0.30), six times of room on either side
GRAD_TOL = 0.03
LEAVES = ("q", "k", "v", "g", "beta")
BLOCK = 256


def _inputs(seed: int, s: int, h: int, w: int):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mixers

    ks = jax.random.split(jax.random.key(seed), 8)
    f32, cdt = jnp.float32, jnp.bfloat16

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + mixers.L2_EPS)

    q, k, v = (jax.nn.silu(jax.random.normal(key, (1, s, h, w), f32))
               for key in ks[:3])
    q, k = unit(q) * w ** -0.5, unit(k)
    # the gates as ``mixers.init_kda`` spreads them, the low-rank pair's
    # output a unit normal as it is at initialisation
    a_log = jnp.log(jax.random.uniform(ks[3], (h,), f32, *mixers.A_RANGE))
    step = jnp.exp(jax.random.uniform(
        ks[4], (h, w), f32, *map(jnp.log, jnp.asarray(mixers.DT_RANGE))))
    dt_bias = step + jnp.log(-jnp.expm1(-step))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        jax.random.normal(ks[5], (1, s, h, w), f32) + dt_bias)
    beta = jax.nn.sigmoid(jax.random.normal(ks[6], (1, s, h), f32))
    weight = jax.random.normal(ks[7], (1, s, h, w), f32).astype(cdt).astype(f32)
    return (q.astype(cdt), k.astype(cdt), v.astype(cdt), g, beta), weight


def _recurrent(q, k, v, g, beta):
    """``kda.kda_recurrent`` in blocks of ``BLOCK`` tokens, each rematted."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    b, s, h, dk = q.shape
    block = min(BLOCK, s)

    def cut(a):
        a = a.astype(jnp.float32)
        return jnp.moveaxis(a.reshape(b, s // block, block, *a.shape[2:]), 1, 0)

    @jax.checkpoint
    def one(S, xs):
        o, S = kda.kda_recurrent(*xs, S0=S)
        return S, o

    _, o = jax.lax.scan(one, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        tuple(map(cut, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).reshape(b, s, h, -1)


def gradient(seed: int, s: int, h: int, w: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    args, weight = _inputs(seed, s, h, w)
    plan = kda.plan(s, h, w, w)
    per_segment = s // plan["segments"]

    def loss(fn, weight):
        return lambda *a: (fn(*a).astype(jnp.float32) * weight).sum()

    def distances(got, want):
        out = {}
        for name, a, b in zip(LEAVES, got, want):
            a, b = a.astype(jnp.float32), b.astype(jnp.float32)
            out[name] = float(jnp.linalg.norm((a - b).ravel())
                              / jnp.linalg.norm(b.ravel()))
        return out

    with jax.default_matmul_precision("highest"):
        want_o = jax.jit(_recurrent)(*args)
        want = jax.jit(jax.grad(loss(_recurrent, weight), range(5)))(*args)
    got_o = jax.jit(kda.kda_chunked)(*args)
    got = jax.jit(jax.grad(loss(kda.kda_chunked, weight), range(5)))(*args)

    # planted: the cotangent of one segment's outputs never arrives
    at = plan["segments"] // 2
    holed = weight.at[:, at * per_segment:(at + 1) * per_segment].set(0.0)
    dropped = jax.jit(jax.grad(loss(kda.kda_chunked, holed), range(5)))(*args)
    # planted: a segment's backward hands nothing back to the one before it
    whole = kda._segment
    kda._segment = lambda S, *a: whole(jax.lax.stop_gradient(S), *a)
    try:
        cut = jax.jit(jax.grad(loss(lambda *a: kda.kda_chunked(*a), weight),
                               range(5)))(*args)
    finally:
        kda._segment = whole

    out = {
        "check": "gradient", "seed": seed, "shape": [1, s, h, w],
        "plan": {key: plan[key] for key in ("chunk", "sub_block", "chunks",
                                            "segments")},
        "g_min": float(args[3].min()),
        "forward": float(jnp.linalg.norm((got_o.astype(jnp.float32)
                                          - want_o).ravel())
                         / jnp.linalg.norm(want_o.ravel())),
        "program": distances(got, want),
        "dropped_segment": distances(dropped, want),
        "cut_state": distances(cut, want),
        "tol": GRAD_TOL,
    }
    worst = {key: max(out[key].values())
             for key in ("program", "dropped_segment", "cut_state")}
    out["worst"] = worst
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all()) for x in got)
    out["ok"] = bool(finite and worst["program"] < GRAD_TOL
                     and (plan["segments"] == 1 or min(
                         worst["dropped_segment"], worst["cut_state"]) > GRAD_TOL))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("check", choices=("gradient",))
    ap.add_argument("--seed", type=int, default=4000000007)
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--width", type=int, default=128)
    a = ap.parse_args(argv)
    import jax

    out = gradient(a.seed, a.seq, a.heads, a.width)
    out["device"] = jax.devices()[0].device_kind
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
