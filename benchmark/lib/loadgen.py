"""The one general load generator: schedules from a traffic file and a seed,
an open loop and a closed loop that run them, and the HTTP streaming client.

A traffic file holds parameters only. Every seed draws the same multiset of
lengths (the quantiles of the distribution, taken evenly) in a seeded order,
and an open loop sends exactly ``rate * seconds`` requests at uniform order
statistics: seeds differ in order and spacing, not in load, so that runs of
one cell can be compared.

Open-loop requests are timed from the instant they were *due*, not from when
a thread got round to sending them, so a stall costs the requests behind it
what it would cost their users; how late the generator itself ran is
reported beside them.
"""

from __future__ import annotations

import http.client
import json
import math
import queue
import threading
import time
import urllib.parse
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np


@dataclass
class Request:
    """One request of a schedule and, once sent, what came back."""

    index: int
    due: float                      # seconds from the window's start
    prompt: List[int]
    n_new: int
    measured: bool = True           # lead-in requests are sent, not judged
    t_send: Optional[float] = None  # absolute, time.perf_counter()
    token_times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    error: Optional[str] = None
    done: bool = False

    @property
    def ok(self) -> bool:
        return self.done and self.error is None and len(self.tokens) == self.n_new


# ---- lengths -----------------------------------------------------------------

def draw_lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator
                 ) -> List[int]:
    """``n`` lengths of the distribution ``spec`` describes: its quantiles
    at (i + 1/2) / n, snapped and clipped as it says, in a seeded order."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        vals = spec["min"] + q * (spec["max"] - spec["min"])
    elif dist == "choice":
        vals = np.array([spec["values"][i % len(spec["values"])]
                         for i in range(n)], float)
    else:
        raise ValueError(f"no length distribution {dist!r}")
    if "grid" in spec:
        grid = np.array(sorted(spec["grid"]), float)
        vals = grid[np.abs(np.log(vals[:, None]) - np.log(grid[None, :])).argmin(1)]
    vals = np.clip(np.rint(vals), spec.get("min", 1), spec.get("max", math.inf))
    out = [int(v) for v in vals]
    rng.shuffle(out)
    return out


def _requests(traffic: Dict[str, Any], dues: Sequence[float], measured: bool,
              vocab: int, max_len: int, rng: np.random.Generator,
              first_index: int = 0) -> List[Request]:
    n = len(dues)
    prompts = draw_lengths(traffic["prompt"], n, rng)
    answers = draw_lengths(traffic["answer"], n, rng)
    out = []
    for i, due in enumerate(dues):
        # the engine wants prompt + new + 1 <= max_len
        n_new = max(1, min(answers[i], max_len - prompts[i] - 1))
        prompt = rng.integers(1, vocab, prompts[i]).tolist()  # unshared
        out.append(Request(first_index + i, float(due), prompt, n_new,
                           measured=measured))
    return out


def _arrivals(spec: Optional[Dict[str, Any]], low: float, high: float, n: int,
              rng: np.random.Generator) -> np.ndarray:
    """``n`` sorted arrival times in [low, high), their number fixed whatever
    the seed. No ``spec``: the sorted values of as many uniform draws (the
    order statistics of Poisson arrivals given their number). ``{"dist":
    "gamma", "cv": c}``: ``n + 1`` gaps drawn from a gamma distribution of
    that coefficient of variation (shape 1/c^2; 1 is Poisson again, above 1
    arrivals come in bursts), their running sums rescaled so that the last
    gap ends at ``high``."""
    if spec is None:
        return np.sort(rng.uniform(low, high, n))
    if spec.get("dist") != "gamma":
        raise ValueError(f"no arrival distribution {spec.get('dist')!r}")
    gaps = rng.gamma(1.0 / float(spec["cv"]) ** 2, size=n + 1)
    return low + (high - low) * np.cumsum(gaps[:n]) / gaps.sum()


def open_schedule(traffic: Dict[str, Any], seconds: float, seed: int,
                  vocab: int, max_len: int) -> List[Request]:
    """Exactly ``rate * lead_s`` lead-in requests before the window and
    ``rate * seconds`` inside it, each set arriving as ``traffic["arrivals"]``
    says (``_arrivals``; absent: Poisson)."""
    rng = np.random.default_rng([seed, 0x10AD])
    rate, lead = float(traffic["rate_rps"]), float(traffic.get("lead_s", 0))
    n_lead, n_win = int(round(rate * lead)), int(round(rate * seconds))
    how = traffic.get("arrivals")
    # the window's lengths are drawn apart from the lead-in's, so that the
    # measured multiset is the same whatever the seed
    before = _requests(traffic, _arrivals(how, -lead, 0.0, n_lead, rng),
                       False, vocab, max_len, rng)
    return before + _requests(traffic, _arrivals(how, 0.0, seconds, n_win, rng),
                              True, vocab, max_len, rng, first_index=n_lead)


def closed_plan(traffic: Dict[str, Any], seed: int, vocab: int, max_len: int,
                per_client: int) -> List[List[Request]]:
    """For each client of a closed loop, the requests it sends one after
    another (``per_client`` of them: more than a window can use up)."""
    rng = np.random.default_rng([seed, 0xC105ED])
    clients = int(traffic["clients"])
    flat = _requests(traffic, [0.0] * (clients * per_client), True, vocab,
                     max_len, rng)
    return [flat[c::clients] for c in range(clients)]


# ---- the client --------------------------------------------------------------

def stream_request(url: str, req: Request, timeout_s: float = 300.0,
                   stop: Optional[threading.Event] = None) -> None:
    """POST the prompt, read the streamed answer (one JSON token a line) and
    stamp each token as it arrives. Never raises: a failure is the
    request's ``error``. ``stop`` set mid-stream closes the connection, which
    the replica takes as a cancel."""
    u = urllib.parse.urlsplit(url)
    body = json.dumps({"tokens": req.prompt, "max_new_tokens": req.n_new})
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout_s)
    try:
        req.t_send = time.perf_counter()
        conn.request("POST", u.path or "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            req.error = f"HTTP {resp.status}: {resp.read(200)!r}"
            return
        for line in resp:
            if stop is not None and stop.is_set():
                req.error = "stopped"
                return
            if line.strip():
                req.token_times.append(time.perf_counter())
                req.tokens.append(int(json.loads(line)))
        if len(req.tokens) != req.n_new:
            req.error = f"{len(req.tokens)} tokens of {req.n_new}"
    except (OSError, http.client.HTTPException, ValueError) as e:
        req.error = f"{type(e).__name__}: {e}"
    finally:
        req.done = True
        conn.close()


Send = Callable[[Request], None]


def run_open(send: Send, schedule: List[Request], t_zero: float,
             workers: int = 48) -> None:
    """Send every request of ``schedule`` at ``t_zero + due`` and return when
    all have ended. One thread keeps time and hands each request, when it is
    due, to a fixed pool; a request that finds the pool busy waits its turn,
    and that wait is in its lateness and in its times."""
    todo: "queue.Queue[Optional[Request]]" = queue.Queue()

    def work() -> None:
        while (req := todo.get()) is not None:
            send(req)
            if not req.done:  # a send that forgot to say so
                req.done = True

    pool = [threading.Thread(target=work, daemon=True, name=f"load-{i}")
            for i in range(workers)]
    for t in pool:
        t.start()
    for req in sorted(schedule, key=lambda r: r.due):
        delay = t_zero + req.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        todo.put(req)
    for _ in pool:
        todo.put(None)
    for t in pool:
        t.join()


def run_closed(send: Callable[[Request, threading.Event], None],
               plan: List[List[Request]], t_stop: float, ramp_s: float
               ) -> List[Request]:
    """Every client sends its requests one after another, the first after
    its share of ``ramp_s`` so that the clients are out of step, until
    ``t_stop`` (absolute); what is in flight then is cut off. Returns the
    requests that were sent."""
    stop = threading.Event()
    sent: List[List[Request]] = [[] for _ in plan]
    t_start = time.perf_counter()

    def client(c: int, mine: Iterator[Request]) -> None:
        time.sleep(max(0.0, t_start + ramp_s * c / len(plan) - time.perf_counter()))
        for req in mine:
            if stop.is_set():
                return
            sent[c].append(req)
            send(req, stop)

    threads = [threading.Thread(target=client, args=(c, iter(p)), daemon=True,
                                name=f"client-{c}") for c, p in enumerate(plan)]
    for t in threads:
        t.start()
    time.sleep(max(0.0, t_stop - time.perf_counter()))
    stop.set()
    for t in threads:
        t.join()
    return [r for mine in sent for r in mine]


# ---- what the clients saw ----------------------------------------------------

def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty sample."""
    vals = sorted(values)
    return vals[min(len(vals) - 1, max(0, math.ceil(q / 100.0 * len(vals)) - 1))]


def client_percentile(run: Dict[str, Any], key: str, q: float) -> Optional[float]:
    """Percentile ``q`` of the clients' ``key`` list of a run; None without one."""
    values = run.get("client", {}).get(key)
    return percentile(values, q) if values else None


def open_loop_stats(schedule: List[Request], t_zero: float,
                    slo: Dict[str, float]) -> Dict[str, Any]:
    """Times of the measured requests, each from its due instant. A request
    that failed has no time and misses every limit."""
    mine = [r for r in schedule if r.measured]
    good = [r for r in mine if r.ok]
    ttft = [(r.token_times[0] - (t_zero + r.due)) * 1e3 for r in good]
    send_ttft = [(r.token_times[0] - r.t_send) * 1e3 for r in good]
    tpot = [(r.token_times[-1] - r.token_times[0]) / (len(r.tokens) - 1) * 1e3
            for r in good if len(r.tokens) > 1]
    late = [(r.t_send - (t_zero + r.due)) * 1e3 for r in mine
            if r.t_send is not None]
    met = sum(1 for r in good
              if (r.token_times[0] - (t_zero + r.due)) * 1e3 <= slo["ttft_ms"]
              and (len(r.tokens) < 2 or
                   (r.token_times[-1] - r.token_times[0]) / (len(r.tokens) - 1)
                   * 1e3 <= slo["tpot_ms"]))
    return {"attempted": len(mine), "failed": len(mine) - len(good),
            "errors": sorted({r.error for r in mine if r.error})[:5],
            "ttft_ms": ttft, "send_ttft_ms": send_ttft, "tpot_ms": tpot,
            "late_ms": late, "slo_met": met,
            "tokens": sum(len(r.tokens) for r in good)}
