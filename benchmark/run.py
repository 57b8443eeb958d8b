#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; everything else is found by
those names (``benchmark/lib/spec.py``). This process never imports JAX: it
starts the runtime, and the worker that is granted the cell's chips holds
them. Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result. ``--rehearse`` is for the sandbox: the same
flow on whatever JAX finds, ``correct`` false in the last line and exit code
3, so that a rehearsal can never pass for a measurement.

The last line of standard output is the result; lines before it are notes.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional, Sequence  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def say(text: str) -> None:
    print(f"[bench {time.perf_counter() - T_PROCESS:7.1f}s] {text}", flush=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run off the chip; the result says so and fails")
    args = ap.parse_args(argv)

    from benchmark.lib import results, serve_driver, spec, train_driver

    cell = spec.Cell(args.workload)
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(",") and not args.rehearse:
        print(f"benchmark: JAX_PLATFORMS={platforms!r} keeps JAX off the TPU; "
              f"a cell runs on a TPU and nowhere else", file=sys.stderr)
        return 1
    spec.configure_environment()
    trace_dir = os.path.join(ROOT, ".bench_trace", f"{cell.name}-{os.getpid()}")

    import ray_tpu

    ray_tpu.init()
    try:
        offered = ray_tpu.cluster_resources().get("TPU", 0)
        if offered < cell.chips and not args.rehearse:
            print(f"benchmark: this host offers {offered:g} TPU chip(s) and "
                  f"{cell.name} needs {cell.chips}", file=sys.stderr)
            return 1
        driver = train_driver if cell.traffic["driver"] == "train" else serve_driver
        run = driver.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace),
                         on_chip=offered >= cell.chips, t_process=T_PROCESS,
                         trace_dir=trace_dir, say=say)
    finally:
        t = time.perf_counter()
        ray_tpu.shutdown()
        say(f"runtime shut down in {time.perf_counter() - t:.1f}s")
        shutil.rmtree(trace_dir, ignore_errors=True)  # reduced in the worker
    run["seconds"] = args.seconds
    run["cell"] = {"config": cell.config, "traffic": cell.traffic,
                   "n_layers": cell.n_layers(), "chips": cell.chips,
                   "family": cell.family}
    if "jax" in sys.modules:
        raise RuntimeError("the parent process imported jax")
    for line in results.info_lines(cell, run):
        say(line)
    line = results.last_line(cell, run, traced=bool(args.trace))
    print(json.dumps(line), flush=True)
    on_chip = (run["device"]["platform"] == "tpu"
               and run["device"]["count"] == cell.chips)
    return 0 if on_chip else 3


if __name__ == "__main__":
    sys.exit(main())
