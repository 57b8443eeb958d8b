"""From what a driver gathered (``run``) to what the last line says: the
end-to-end values by name, the verdict ``correct``, and the line itself."""

from __future__ import annotations

import re
from statistics import median
from typing import Any, Dict, List, Optional, Tuple

from benchmark.lib import loadgen
from benchmark.lib.spec import Cell, layer_values

_PERCENTILE = re.compile(r"^(ttft|tpot)_p(\d{1,2})_ms$")


def end_to_end_value(name: str, cell: Cell, run: Dict[str, Any]) -> Optional[float]:
    """An end-to-end metric by its name; None where this run has no such
    thing. ``ttft_p<q>_ms`` and ``tpot_p<q>_ms`` take any percentile, so a
    later cell can judge another one without new code."""
    if name == "setup_s":
        return run["setup_s"]
    m = _PERCENTILE.match(name)
    if m:
        return loadgen.client_percentile(run, m.group(1) + "_ms", int(m.group(2)))
    if name == "out_tok_s" and run.get("client", {}).get("segment_rates"):
        return median(run["client"]["segment_rates"])
    if name == "train_tok_s_chip" and run.get("train", {}).get("span_s"):
        return run["train"]["tokens"] / run["train"]["span_s"] / cell.chips
    return None


def verdict(cell: Cell, run: Dict[str, Any]) -> Tuple[bool, List[str]]:
    """``correct``, and why not where it is not."""
    why: List[str] = []
    dev, traffic = run["device"], cell.traffic
    if dev["platform"] != "tpu" or dev["count"] != cell.chips:
        why.append(f"ran on {dev['platform']} x{dev['count']}, not on "
                   f"{cell.chips} TPU chip(s)")
    if run["window_compiles"]:
        why.append(f"{run['window_compiles']} programs compiled in the window")
    if "train" in run:
        tr, ref = run["train"], run["reference"]["loss"]
        tol = float(traffic["loss_rel_tol"])
        if not tr["finite"]:
            why.append("a loss is not finite")
        if abs(tr["first_loss"] - ref) > tol * abs(ref):
            why.append(f"first loss {tr['first_loss']:.5f} is not within "
                       f"{tol:g} of the reference's {ref:.5f}")
        if not tr["probe_loss_after"] < tr["first_loss"]:
            why.append("the loss on the first batch did not fall")
        if not tr["launches"]:
            why.append("no launch finished inside the window")
    else:
        for i, s in enumerate(run["reference"]):
            # 1/32 of the logits' scale, eight bf16 steps: a near-tie broken
            # the other way passes, a wrong token lies units below (PR 21)
            if not s["finite"] or s["worst_margin"] > s["logit_scale"] / 32:
                why.append(f"sample {i}: a streamed token lies "
                           f"{s['worst_margin']:.4f} under the reference's "
                           f"best (scale {s['logit_scale']:.3f})")
        if len(run["reference"]) < int(traffic["reference_sample"]):
            why.append("too few requests completed to sample the reference")
        need = int(traffic.get("min_requests", 1))
        if run["client"]["attempted"] < need:
            why.append(f"{run['client']['attempted']} requests in the window, "
                       f"{need} needed")
    return not why, why


def info_lines(cell: Cell, run: Dict[str, Any]) -> List[str]:
    """What is worth reading beside the judged numbers."""
    out = [f"setup: {run['setup_s']:.1f}s = " + ", ".join(
        f"{k[:-2]} {v:.1f}" for k, v in sorted(run["setup"].items()))]
    c = run.get("client", {})
    for key in ("ttft_ms", "tpot_ms", "late_ms"):
        if c.get(key):
            out.append(f"{key}: n={len(c[key])} mean={sum(c[key]) / len(c[key]):.2f} "
                       + " ".join(f"p{q}={loadgen.percentile(c[key], q):.2f}"
                                  for q in (50, 75, 90, 95))
                       + f" max={max(c[key]):.2f}")
    if "burst_span_s" in c:
        out.append(f"tokens: {c['window_tokens']} in the window, "
                   f"{c['burst_tokens']} in whole bursts over {c['burst_span_s']:.3f}s; "
                   f"by segment {[round(r, 1) for r in c['segment_rates']]}")
    if c:
        out.append(f"requests: attempted {c['attempted']}, failed "
                   f"{c['failed']} {c.get('errors') or ''}")
    if "train" in run:
        t = run["train"]
        out.append(f"train: {t['launches']} launches x K={t['steps_per_launch']}"
                   f" in {t['span_s']:.2f}s on mesh {t['mesh'] or 'one device'}")
    if run.get("trace"):
        by_scope = sorted(run["trace"]["by_scope"].items(), key=lambda kv: -kv[1])
        out.append("by_scope: device seconds a chip by program/scope: "
                   + ", ".join(f"{k} {s:.4f}" for k, s in by_scope))
    return out


def last_line(cell: Cell, run: Dict[str, Any], traced: bool) -> Dict[str, Any]:
    correct, why = verdict(cell, run)
    dev = run["device"]
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": dev["memory_peak_bytes"]}
    out: Dict[str, Any] = {"correct": correct}
    if "train" in run:
        out.update(attempted=run["train"]["launches"],
                   failed=0 if run["train"]["finite"] else run["train"]["launches"])
    else:
        out.update(attempted=run["client"]["attempted"],
                   failed=run["client"]["failed"])
    if traced:
        out["metrics"] = layer_values(cell, run)
        reduced = run.get("trace")
        if reduced:
            device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    else:
        values = {m["name"]: end_to_end_value(m["name"], cell, run)
                  for m in cell.end_to_end}
        why += [f"no value for {name}" for name, v in values.items() if v is None]
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {name: {"value": float(v), "unit": units[name]}
                          for name, v in values.items() if v is not None}
        out["correct"] = not why
    out["device"] = device
    if why:
        out["why_not_correct"] = why
    return out
