#!/usr/bin/env python3
"""Find an open-loop cell's knee: one replica, one warm-up, then a window at
each of a few fixed rates, with the share of requests that met the traffic
file's limits and whether the backlog grew. Run once when a cell is defined
(PERF.md records the sweep); the cell then fixes its rate in its file.

    python benchmark/sweep.py --workload <cell> --rates 1,2,3 --seconds 30
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from statistics import median
from typing import Optional, Sequence

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmark.lib import loadgen, serve_driver, spec

    cell = spec.Cell(args.workload)
    if cell.traffic["driver"] != "serve_open":
        raise SystemExit("a knee is a property of an open-loop cell")
    spec.configure_environment()
    import ray_tpu

    ray_tpu.init()
    try:
        if ray_tpu.cluster_resources().get("TPU", 0) < 1:
            raise SystemExit("a sweep runs on a TPU and nowhere else")
        say = lambda text: print(f"[sweep] {text}", flush=True)  # noqa: E731
        call, url = serve_driver.deploy(cell, seed=args.seed, on_chip=True,
                                        trace_dir="", say=say)
        vocab = cell.config["config"]["vocab_size"]
        serve_driver.warm_up(url, cell.traffic["prompt"]["grid"], vocab, args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            traffic = {**cell.traffic, "rate_rps": rate}
            work, _ = serve_driver.make_work(traffic, args.seconds, args.seed, vocab)
            t = time.perf_counter()
            run = serve_driver.measure(call, url, traffic, work, args.seconds, False)
            c, sent = run["client"], run["sent"]
            half = [[(r.token_times[0] - run["t_zero"] - r.due) * 1e3 for r in sent
                     if r.ok and lo <= r.due < lo + args.seconds / 2]
                    for lo in (0.0, args.seconds / 2)]
            print(json.dumps({
                "rate_rps": rate, "attempted": c["attempted"], "failed": c["failed"],
                "slo_met_share": c["slo_met"] / max(1, c["attempted"]),
                "ttft_p50_ms": median(c["ttft_ms"]),
                "ttft_p90_ms": loadgen.percentile(c["ttft_ms"], 90),
                "tpot_p50_ms": median(c["tpot_ms"]),
                "tpot_p90_ms": loadgen.percentile(c["tpot_ms"], 90),
                "ttft_p50_ms_first_half": median(half[0]) if half[0] else None,
                "ttft_p50_ms_second_half": median(half[1]) if half[1] else None,
                "late_p99_ms": loadgen.percentile(c["late_ms"], 99),
                "drain_s": time.perf_counter() - run["t_zero"] - args.seconds,
                "occupancy": run["engine"].get("occupancy"),
                "window_compiles": run["window_compiles"],
                "engine_ttft_p50_s": run["engine"].get("ttft_p50_s"),
            }), flush=True)
        from ray_tpu import serve

        print(json.dumps(call("device_facts")), flush=True)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
