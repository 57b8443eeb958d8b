"""The load generator: the schedule is a function of the traffic file and
the seed, requests are timed from their due instant, and how late the
generator ran is reported."""

import hashlib
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import benchmark_testlib as lib  # noqa: E402

sys.path.insert(0, lib.REPO)
from benchmark.lib import loadgen  # noqa: E402

CHAT = {"driver": "serve_open", "rate_rps": 4, "lead_s": 2,
        "prompt": {"dist": "lognormal", "median": 384, "sigma": 0.9,
                   "grid": [64, 128, 256, 384, 512, 768, 1024, 1536]},
        "answer": {"dist": "lognormal", "median": 96, "sigma": 0.9,
                   "min": 16, "max": 512}}


def _shape(schedule):
    return [(r.due, r.prompt, r.n_new) for r in schedule]


def test_schedule_is_a_function_of_the_seed():
    a = loadgen.open_schedule(CHAT, 20, 7, 32768, 2048)
    assert _shape(a) == _shape(loadgen.open_schedule(CHAT, 20, 7, 32768, 2048))
    b = loadgen.open_schedule(CHAT, 20, 8, 32768, 2048)
    assert _shape(a) != _shape(b)


def test_every_seed_offers_the_same_load():
    a, b = (loadgen.open_schedule(CHAT, 20, seed, 32768, 2048) for seed in (1, 2))
    for sched in (a, b):
        win = [r for r in sched if r.measured]
        assert len(win) == 80 and len(sched) - len(win) == 8  # rate x seconds
        assert all(0 <= r.due < 20 for r in win)
        assert all(-2 <= r.due < 0 for r in sched if not r.measured)
        assert all(len(r.prompt) in CHAT["prompt"]["grid"] for r in sched)
        assert all(16 <= r.n_new <= 512 for r in sched)
        assert all(len(r.prompt) + r.n_new + 1 <= 2048 for r in sched)
    lengths = lambda s: sorted(len(r.prompt) for r in s if r.measured)  # noqa: E731
    assert lengths(a) == lengths(b)  # the multiset; the order differs
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[20].prompt[:8] != b[20].prompt[:8]  # unshared tokens


def test_without_arrivals_the_schedule_is_the_parents_to_the_bit():
    """``chat-steady`` at 51 s, seed 1, as the parent commit (PR 24) drew
    it: a hash over every due time (as hex), prompt, answer length and
    whether it is measured."""
    with open(os.path.join(lib.REPO, "benchmark/traffic/chat-steady.json")) as f:
        chat = json.load(f)
    assert "arrivals" not in chat
    sched = loadgen.open_schedule(chat, 51, 1, 32768, 2048)
    assert len(sched) == 118 and sched[0].due == -7.838710365928552
    digest = hashlib.sha256(json.dumps(
        [[float(r.due).hex(), r.prompt, r.n_new, r.measured] for r in sched]
    ).encode()).hexdigest()
    assert digest == ("4c98c72cd56960dbfc3ce2d2dc36be23"
                      "04feb2d7eefb39d6dc4cdd5aff862293")


def test_gamma_arrivals_come_in_bursts_and_offer_the_same_load():
    bursty = {**CHAT, "rate_rps": 200, "arrivals": {"dist": "gamma", "cv": 3}}
    lengths = []
    for seed in (1, 2, 4000000007):
        sched = loadgen.open_schedule(bursty, 50, seed, 32768, 4096)  # no clipping
        win = [r for r in sched if r.measured]
        assert len(win) == 10_000 and len(sched) - len(win) == 400  # exact
        assert all(0 <= r.due < 50 for r in win)
        assert all(-2 <= r.due < 0 for r in sched if not r.measured)
        assert [r.due for r in win] == sorted(r.due for r in win)
        gaps = np.diff([r.due for r in win])
        assert abs(gaps.std() / gaps.mean() - 3.0) < 0.3
        lengths.append((sorted(len(r.prompt) for r in win),
                        sorted(r.n_new for r in win)))
    assert lengths[0] == lengths[1] == lengths[2]  # the multisets, whatever the seed
    steady = loadgen.open_schedule({**bursty, "arrivals": {"dist": "gamma", "cv": 1}},
                                   50, 1, 32768, 2048)
    gaps = np.diff([r.due for r in steady if r.measured])
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1  # cv 1 is Poisson again
    with pytest.raises(ValueError, match="no arrival distribution"):
        loadgen.open_schedule({**CHAT, "arrivals": {"dist": "weibull"}}, 5, 1, 100, 2048)


def test_closed_plan_gives_each_client_its_own_requests():
    mix = {"clients": 3, "prompt": {"dist": "choice", "values": [64, 128]},
           "answer": {"dist": "uniform", "min": 256, "max": 768}}
    plan = loadgen.closed_plan(mix, 1, 1000, 2048, per_client=4)
    assert [len(p) for p in plan] == [4, 4, 4]
    assert {len(r.prompt) for p in plan for r in p} == {64, 128}
    assert all(256 <= r.n_new <= 768 for p in plan for r in p)


def _fake_server(service_s, stall_at=None, stall_s=0.0):
    """One request at a time, like a server with a single slot; the
    ``stall_at``-th request holds it ``stall_s`` longer."""
    lock, count = threading.Lock(), [0]

    def send(req):
        req.t_send = time.perf_counter()
        with lock:
            count[0] += 1
            time.sleep(service_s + (stall_s if count[0] == stall_at else 0.0))
            now = time.perf_counter()
        req.tokens, req.token_times = [1, 2], [now, now + 0.001]
        req.done = True

    return send


def _open(n, gap):
    return [loadgen.Request(i, i * gap, [1], 2) for i in range(n)]


def test_times_run_from_the_due_instant_so_a_stall_costs_those_behind_it():
    """Margins are wide: the suite runs beside five other workers."""
    slo = {"ttft_ms": 1000, "tpot_ms": 80}
    calm, stalled = _open(12, 0.03), _open(12, 0.03)
    t0 = time.perf_counter() + 0.3
    loadgen.run_open(_fake_server(0.002), calm, t0, workers=8)
    t1 = time.perf_counter() + 0.3
    loadgen.run_open(_fake_server(0.002, stall_at=3, stall_s=0.6), stalled, t1,
                     workers=8)
    calm_s = loadgen.open_loop_stats(calm, t0, slo)
    stall_s = loadgen.open_loop_stats(stalled, t1, slo)
    assert sorted(calm_s["ttft_ms"])[6] < 100
    # requests 4..11 were due while the third held the server: each waited
    behind = sorted(stall_s["ttft_ms"])[-8:]
    assert min(behind) > 250 and max(behind) > 450
    assert sorted(stall_s["ttft_ms"])[6] > 3 * sorted(calm_s["ttft_ms"])[6]
    assert stall_s["attempted"] == 12 and stall_s["failed"] == 0


def test_a_starved_generator_reports_its_lateness():
    """Two sender threads and a server that takes 100 ms: the pool is busy
    when the third request is due, so it is sent late; the lateness is
    reported and is inside the time from the due instant."""
    slow = _open(6, 0.005)
    t0 = time.perf_counter() + 0.3

    def send(req):
        req.t_send = time.perf_counter()
        time.sleep(0.1)
        req.tokens, req.token_times = [1, 2], [time.perf_counter()] * 2
        req.done = True

    loadgen.run_open(send, slow, t0, workers=2)
    stats = loadgen.open_loop_stats(slow, t0, {"ttft_ms": 1000, "tpot_ms": 80})
    assert loadgen.percentile(stats["late_ms"], 99) > 150  # the third pair's wait
    assert min(stats["late_ms"]) < 50
    for ttft, sent, late in zip(stats["ttft_ms"], stats["send_ttft_ms"],
                                stats["late_ms"]):
        assert abs(ttft - (sent + late)) < 1.0


def test_a_failed_request_misses_and_counts():
    reqs = _open(4, 0.001)

    def send(req):
        req.t_send = time.perf_counter()
        if req.index == 2:
            req.error = "HTTP 503"
        else:
            req.tokens, req.token_times = [1, 2], [time.perf_counter()] * 2
        req.done = True

    t0 = time.perf_counter()
    loadgen.run_open(send, reqs, t0)
    stats = loadgen.open_loop_stats(reqs, t0, {"ttft_ms": 1000, "tpot_ms": 80})
    assert (stats["attempted"], stats["failed"], stats["slo_met"]) == (4, 1, 3)
    assert stats["errors"] == ["HTTP 503"]


def test_closed_loop_keeps_every_client_busy_until_the_stop():
    plan = [[loadgen.Request(10 * c + i, 0.0, [1], 2) for i in range(50)]
            for c in range(3)]

    def send(req, stop):
        req.t_send = time.perf_counter()
        time.sleep(0.01)
        req.tokens, req.token_times = [1, 2], [time.perf_counter()] * 2
        req.done = True

    t_stop = time.perf_counter() + 0.3
    sent = loadgen.run_closed(send, plan, t_stop, ramp_s=0.06)
    assert 3 * 5 < len(sent) < 3 * 31
    firsts = sorted(min(r.t_send for r in sent if r.index // 10 == c) for c in range(3))
    assert firsts[2] - firsts[0] > 0.02  # started out of step


def test_percentile_is_nearest_rank():
    vals = list(range(1, 101))
    assert loadgen.percentile(vals, 90) == 90
    assert loadgen.percentile(vals, 50) == 50
    assert loadgen.percentile([5.0], 99) == 5.0


def test_closed_loop_rate_counts_whole_bursts_and_shrugs_off_one_stall():
    """16 streams get 8 tokens each every 0.25 s, a few ms apart: 512 tokens/s
    wherever the window's ends fall among the bursts; a single stall of 2.4 s
    lowers the rate over all bursts and leaves the segments' median alone."""
    from statistics import median

    from benchmark.lib import serve_driver

    def stream(i, stall_after=None):
        r = loadgen.Request(i, 0.0, [1] * 8, 8 * 400)
        r.t_send, r.done = 0.0, True
        r.token_times = [0.25 * k + 0.0004 * i + 0.0001 * j
                         + (2.4 if stall_after is not None and k > stall_after else 0.0)
                         for k in range(400) for j in range(8)]
        r.tokens = [1] * len(r.token_times)
        return r

    sent = [stream(i) for i in range(16)]
    for t_zero in (10.0, 10.06, 10.13, 10.249, 10.251):
        c = serve_driver._closed_loop_stats(sent, t_zero, 51.0)
        assert abs(c["window_tokens"] / 51.0 - 512) < 512 * 0.006  # a burst or so
        assert abs(c["burst_tokens"] / c["burst_span_s"] - 512.0) < 1e-6
        assert len(c["segment_rates"]) == 10
        assert all(abs(r - 512.0) < 1e-6 for r in c["segment_rates"])
        assert c["attempted"] == 16 and c["failed"] == 0
    stalled = serve_driver._closed_loop_stats(
        [stream(i, stall_after=120) for i in range(16)], 10.0, 51.0)
    assert abs(median(stalled["segment_rates"]) - 512.0) < 1e-6
    assert stalled["burst_tokens"] / stalled["burst_span_s"] < 512 * 0.96
    assert min(stalled["segment_rates"]) < 512 * 0.7
