"""What the cell ``evabyte-train-16k`` cannot show by its first loss, shown
where it can be repeated (PR 52), at the published widths on the chip through

    chiprun -- python3 tests/benchmark/evabyte_chip_check.py <check> [--seed N ..]

(and at a tiny size on the CPU by ``test_benchmark_evabyte.py``):

``gradient``   one layer at s 4,096 (two windows: the second's queries see
               128 summaries) in the cell's own dtypes, bf16 parameters and
               operands, the kernels of ``ray_tpu/ops/pallas/eva_attn.py``:
               ``jax.grad`` of the program's loss against ``jax.grad``
               through the family's plain reference (float32, products at
               ``highest``, a dense mask), leaf by leaf as the norm of the
               difference over the norm of the reference's; the worst leaf
               is judged against ``GRAD_TOL``. A first loss is a forward:
               this is the backward of the kernels (``dq``, ``dkv``,
               ``dsum``) and of the summaries. Beside it a planted fault
               that has to read beyond ``GRAD_TOL``: ``no_summary_grad``
               (the summaries' cotangent never reaches the keys, ``phi`` and
               ``mu``).
``precision``  the loss limit's control at the cell's own size: the
               family's reference computed one precision below the
               configuration's (weights and residual stream through
               ``float8_e5m2``) has to FAIL the harness's comparison where
               the program's own first loss passes it.

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

CELL = "evabyte-train-16k"
LOW = "float8_e5m2"
#: the worst leaf's distance the program has to stay within: between the
#: program's largest reading and the planted fault's smallest (PERF.md
#: section 6 has both)
GRAD_TOL = 0.05


def _program(cell, n_layers: int, seq: int):
    return cell.family.program_config(
        cell.config, n_layers, max_seq_len=seq,
        attn_impl=cell.traffic["attn_impl"], loss_chunk=cell.traffic["loss_chunk"])


def _tokens(cell, seed: int, seq: int):
    import jax.numpy as jnp

    from benchmark.lib import train_driver

    return jnp.asarray(train_driver.synthetic_tokens(
        seed, cell.config["config"]["vocab_size"], 1, seq + 1,
        cell.traffic["data"]))


def _spread_small_leaves(params, seed: int):
    """The norms' offsets, ``phi`` and ``mu`` as a trained layer would have
    moved them (0.1 and 0.5 x normal): at their starts (zeros, 0.013) the
    pooling is a plain mean and their gradients are small beside rounding."""
    import jax

    keys = iter(jax.random.split(jax.random.key(seed + 1), 8))
    layers = dict(params["layers"])
    for name, scale in (("attn_norm", 0.1), ("mlp_norm", 0.1),
                        ("eva_phi", 0.5), ("eva_mu", 0.5)):
        layers[name] = (scale * jax.random.normal(
            next(keys), layers[name].shape)).astype(layers[name].dtype)
    return {**params, "layers": layers}


def gradient(cell, seed: int, seq: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops import eva

    fam = cell.family
    cfg = _program(cell, 1, seq)
    params = _spread_small_leaves(jax.jit(
        lambda r: fam.init_params(r, cfg))(jax.random.key(seed)), seed)
    tokens = _tokens(cell, seed, seq)

    def program():
        return jax.jit(jax.value_and_grad(
            lambda p: llama.lm_loss(p, {"tokens": tokens}, cfg)))(params)

    want_loss, want = fam.loss_and_grads(params, tokens, cell.config)
    got_loss, got = program()
    # planted: the summaries hand no cotangent back
    whole = eva.summaries
    eva.summaries = lambda *a: jax.lax.stop_gradient(whole(*a))
    try:
        _, cut = program()
    finally:
        eva.summaries = whole

    def distances(grads):
        flat = jax.tree_util.tree_leaves_with_path(grads)
        refs = jax.tree.leaves(want)
        return {"/".join(str(k.key) for k in path): float(
            jnp.linalg.norm((g.astype(jnp.float32) - w).ravel())
            / jnp.linalg.norm(w.ravel())) for (path, g), w in zip(flat, refs)}

    out = {"check": "gradient", "seed": seed, "seq": seq, "tol": GRAD_TOL,
           "plan": eva.plan(seq, cfg.n_heads, cfg.head_dim, cfg.eva_window,
                            cfg.eva_chunk),
           "loss": {"program": float(got_loss), "reference": float(want_loss)},
           "program": distances(got), "no_summary_grad": distances(cut)}
    out["worst"] = {key: max(out[key].values())
                    for key in ("program", "no_summary_grad")}
    finite = all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
                 for g in jax.tree.leaves(got))
    out["ok"] = bool(finite and out["worst"]["program"] < GRAD_TOL
                     and (out["plan"]["windows"] == 1
                          or out["worst"]["no_summary_grad"] > GRAD_TOL))
    return out


def precision(cell, seed: int) -> Dict[str, Any]:
    """``cell``: anything with ``family``, ``config``, ``traffic``, ``chips``
    and ``n_layers()`` (``spec.Cell``, or a test's tiny stand-in)."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import results
    from ray_tpu.models import llama

    fam, traffic = cell.family, cell.traffic
    cfg = _program(cell, cell.n_layers(), traffic["seq"])
    params = jax.jit(lambda r: fam.init_params(r, cfg))(jax.random.key(seed))
    tokens = _tokens(cell, seed, traffic["seq"])
    ref = float(fam.loss(params, tokens, cell.config)["loss"])
    low = float(fam.loss(params, tokens, cell.config,
                         round_to=getattr(jnp, LOW))["loss"])
    program = float(jax.jit(lambda p, t: llama.lm_loss(p, {"tokens": t}, cfg))(
        params, tokens))

    def judged(first_loss):  # the harness's comparison, the loss alone at issue
        return results.verdict(cell, {
            "device": {"platform": "tpu", "count": cell.chips},
            "window_compiles": 0, "reference": {"loss": ref},
            "train": {"finite": True, "first_loss": first_loss,
                      "probe_loss_after": first_loss - 1.0, "launches": 1}})

    out = {"check": "precision", "seed": seed,
           "loss_rel_tol": float(traffic["loss_rel_tol"]),
           "reference": ref, "program": program, "low": low, "low_dtype": LOW,
           "program_rel": abs(program - ref) / ref,
           "low_rel": abs(low - ref) / ref,
           "program_correct": judged(program)[0],
           "low_correct": judged(low)[0], "low_why": judged(low)[1]}
    out["ok"] = out["program_correct"] and not out["low_correct"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("check", choices=("gradient", "precision"))
    ap.add_argument("--seed", type=int, nargs="+", default=[4000000007])
    ap.add_argument("--seq", type=int, default=4096)
    a = ap.parse_args(argv)
    import jax

    from benchmark.lib import spec

    cell = spec.Cell(CELL)
    outs = [gradient(cell, seed, a.seq) if a.check == "gradient"
            else precision(cell, seed) for seed in a.seed]
    for out in outs[:-1]:
        print(json.dumps(out), flush=True)
    last = {**outs[-1], "device": jax.devices()[0].device_kind,
            "all_ok": all(o["ok"] for o in outs)}
    print(json.dumps(last))
    return 0 if last["all_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
