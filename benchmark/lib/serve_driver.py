"""The two serve drivers, ``serve_open`` and ``serve_closed``: deploy the
replica on the chip through ``serve.run``, warm the shapes the schedule will
use, send the schedule through the HTTP proxy, and gather what the clients,
the engine's recorder, the compile counter and the device say of the window.
Runs in the parent, which never imports JAX.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Any, Callable, Dict, List, Sequence

import numpy as np

from benchmark.lib import loadgen
from benchmark.lib.spec import Cell

RPC_TIMEOUT_S = 300.0


def _at(t: float, fn: Callable[[], Any], box: Dict[str, Any], key: str
        ) -> threading.Thread:
    """Call ``fn`` at ``time.perf_counter() == t`` and keep what it returns."""
    def later() -> None:
        time.sleep(max(0.0, t - time.perf_counter()))
        try:
            box[key] = fn()
        except Exception as e:  # noqa: BLE001 -- reported with the run
            box[key + "_error"] = f"{type(e).__name__}: {e}"

    th = threading.Thread(target=later, daemon=True, name=f"at-{key}")
    th.start()
    return th


def deploy(cell: Cell, *, seed: int, on_chip: bool, trace_dir: str,
           say: Callable[[str], None]):
    """``serve.run`` of the cell's replica. Returns (call, url): ``call``
    reaches a method of the replica through the handle."""
    from ray_tpu import serve

    from benchmark.lib.served import BenchLLM

    app_args = dict(cell.traffic["app"])
    t = time.perf_counter()
    dep = serve.deployment(BenchLLM).options(
        name="bench", max_ongoing_requests=2 * app_args["max_slots"],
        ray_actor_options={"num_tpus": 1} if on_chip else None)
    handle = serve.run(
        dep.bind(cell.config, cell.n_layers(), trace_dir=trace_dir, seed=seed,
                 **app_args),
        name="bench", route_prefix="/bench",
        http_options=serve.HTTPOptions(port=0))
    url = f"http://127.0.0.1:{serve.http_port()}/bench/"
    say(f"replica healthy after {time.perf_counter() - t:.1f}s at {url}")

    def call(method: str, *args: Any) -> Any:
        return getattr(handle, method).remote(*args).result(timeout=RPC_TIMEOUT_S)

    return call, url


def warm_up(url: str, lengths: Sequence[int], vocab: int, seed: int) -> None:
    """One request for every prompt length the schedule holds: the engine
    compiles a prefill per exact length, and the 9 tokens take the 8-step
    and the 1-step decode programs. All at once, so that the full bucket
    runs too."""
    rng = np.random.default_rng([seed, 0x3A93])
    warm = [loadgen.Request(-1 - i, 0.0, rng.integers(1, vocab, n).tolist(), 9)
            for i, n in enumerate(sorted(set(lengths)))]
    threads = [threading.Thread(target=loadgen.stream_request, args=(url, r))
               for r in warm]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    bad = [r.error for r in warm if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up requests failed: {bad[:3]}")


def measure(call, url: str, traffic: Dict[str, Any], work, seconds: float,
            trace: bool) -> Dict[str, Any]:
    """Send ``work`` (an open schedule or a closed plan) round a window of
    ``seconds`` and gather what the clients, the engine's recorder and the
    compile counter say of it. ``t_zero`` is the window's start on this
    process's clock."""
    is_open = traffic["driver"] == "serve_open"
    lead = float(traffic.get("lead_s", 0) if is_open else traffic["warm_s"])
    t_zero = time.perf_counter() + lead + 0.1
    wall_zero = time.time() + (t_zero - time.perf_counter())
    box: Dict[str, Any] = {}
    timers = [_at(t_zero, lambda: call("mark"), box, "mark0"),
              _at(t_zero + seconds, lambda: call("mark"), box, "mark1")]
    if trace:
        at = t_zero + min(float(traffic["trace"]["start_s"]), 0.5 * seconds)
        length = min(float(traffic["trace"]["seconds"]), 0.4 * seconds)
        timers += [_at(at, lambda: call("start_trace"), box, "trace_on"),
                   _at(at + length, lambda: call("stop_trace"), box, "trace")]
    if is_open:
        loadgen.run_open(lambda r: loadgen.stream_request(url, r), work, t_zero)
        client = loadgen.open_loop_stats(work, t_zero, traffic["slo"])
        sent = [r for r in work if r.measured]
    else:
        sent = loadgen.run_closed(
            lambda r, stop: loadgen.stream_request(url, r, stop=stop), work,
            t_zero + seconds, float(traffic["ramp_s"]))
        client = _closed_loop_stats(sent, t_zero, seconds)
    for th in timers:
        th.join(RPC_TIMEOUT_S)
    errors = {k: v for k, v in box.items() if k.endswith("_error")}
    if errors:
        raise RuntimeError(f"a call beside the window failed: {errors}")
    return {
        "t_zero": t_zero, "client": client, "sent": sent,
        "engine": call("recorder_window", wall_zero, wall_zero + seconds),
        "window_compiles": box["mark1"]["programs"] - box["mark0"]["programs"],
        "compile_s_at_window": box["mark0"]["compile_s"],
        "trace": box.get("trace"),
        "requests": [[len(r.prompt), len(r.tokens)] for r in sent if r.tokens],
    }


def make_work(traffic: Dict[str, Any], seconds: float, seed: int, vocab: int):
    """(work for ``measure``, every request in it)."""
    max_len = traffic["app"]["max_len"]
    if traffic["driver"] == "serve_open":
        schedule = loadgen.open_schedule(traffic, seconds, seed, vocab, max_len)
        return schedule, schedule
    plan = loadgen.closed_plan(
        traffic, seed, vocab, max_len,
        per_client=2 + int((seconds + traffic["warm_s"]) / traffic["min_request_s"]))
    return plan, [r for mine in plan for r in mine]


def run(cell: Cell, *, seed: int, seconds: float, trace: bool, on_chip: bool,
        t_process: float, trace_dir: str, say: Callable[[str], None]
        ) -> Dict[str, Any]:
    from ray_tpu import serve

    traffic = cell.traffic
    vocab = cell.config["config"]["vocab_size"]
    setup = {"runtime_s": time.perf_counter() - t_process}
    t = time.perf_counter()
    call, url = deploy(cell, seed=seed, on_chip=on_chip, trace_dir=trace_dir,
                       say=say)
    setup["replica_s"] = time.perf_counter() - t
    work, every = make_work(traffic, seconds, seed, vocab)
    t = time.perf_counter()
    warm_up(url, [len(r.prompt) for r in every], vocab, seed)
    setup["warmup_s"] = time.perf_counter() - t

    run_ = measure(call, url, traffic, work, seconds, trace)
    run_["setup_s"] = run_.pop("t_zero") - t_process
    run_["setup"] = setup
    # the reference, outside the window: a seeded sample of what was served
    good = [r for r in run_.pop("sent") if r.ok]
    pick = np.random.default_rng([seed, 0x5A3]).permutation(len(good))
    sample = [good[i] for i in pick[:int(traffic["reference_sample"])]]
    t = time.perf_counter()
    run_["reference"] = call("reference_check", [
        {"prompt": r.prompt, "tokens": r.tokens} for r in sample])
    setup["reference_check_s"] = time.perf_counter() - t
    run_["device"] = call("device_facts")
    t = time.perf_counter()
    serve.shutdown()
    setup["serve_shutdown_s"] = time.perf_counter() - t
    return run_


BURST_GAP_S = 0.04  # arrivals further apart than this belong to two bursts
SEGMENTS = 10


def _closed_loop_stats(sent: List[loadgen.Request], t_zero: float,
                       seconds: float) -> Dict[str, Any]:
    """Tokens the clients received inside the window, and their rate.

    The engine delivers a burst per launch (8 steps x 16 rows every ~0.3 s),
    so tokens over a fixed length jump by a burst with where the window's
    ends fall (0.5% at 51 s: seen). Rates are therefore taken over whole
    bursts: from one burst's first arrival to a later burst's first arrival.
    ``burst_tokens`` over ``burst_span_s`` is that rate over all of them. One
    run in seven also lost ~2.4 s in one piece to a stall of the host (seen;
    cause unknown), which no bound could hold; so the bursts are cut into
    ``SEGMENTS`` runs of equal count and ``segment_rates`` holds each one's
    rate: their median is the steady rate, untouched by one stall but moved
    by anything that recurs, and what the stall cost shows beside it.

    A request counts as attempted if any of its life fell inside the window;
    one the window's end cut off is not a failure."""
    t_end = t_zero + seconds
    live = [r for r in sent if r.t_send is not None and r.t_send < t_end
            and (not r.token_times or r.token_times[-1] >= t_zero or not r.ok)]
    failed = [r for r in live if r.error not in (None, "stopped")]
    times = sorted(t for r in sent for t in r.token_times if t_zero <= t < t_end)
    starts = times[:1] + [b for a, b in zip(times, times[1:]) if b - a > BURST_GAP_S]
    out = {"attempted": len(live), "failed": len(failed),
           "errors": sorted({r.error for r in failed})[:5],
           "window_tokens": len(times),
           "completed": sum(1 for r in live if r.ok)}
    inner = starts[1:]  # the window's start may have cut the first burst
    if len(inner) > 2 * SEGMENTS:
        cuts = [inner[round(i * (len(inner) - 1) / SEGMENTS)]
                for i in range(SEGMENTS + 1)]
        at = [bisect.bisect_left(times, c) for c in cuts]
        out["segment_rates"] = [(n1 - n0) / (c1 - c0) for n0, n1, c0, c1
                                in zip(at, at[1:], cuts, cuts[1:])]
        out["burst_tokens"], out["burst_span_s"] = at[-1] - at[0], cuts[-1] - cuts[0]
    else:  # delivered without pauses: there is no burst to cut
        out["segment_rates"] = [len(times) / seconds]
        out["burst_tokens"], out["burst_span_s"] = len(times), seconds
    return out
