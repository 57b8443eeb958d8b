"""Engine flight recorder (``util/engine_recorder.py``): per-tick phase
attribution, request lifecycle records joining the serve span tree,
SLO/goodput math, the ``/api/engine`` + ``rt engine`` surfaces, and the
bounded-memory property. Named ``test_zz_*`` so it sorts late."""

import contextlib
import io
import json
import time
import urllib.request
from argparse import Namespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.models import llama, serving  # noqa: E402
from ray_tpu.util import engine_recorder as ER  # noqa: E402


# ---------------------------------------------------------------------------
# one shared engine run: cold request, weight swap, warm (prefix-cached)
# request — the record set the engine-level tests read
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_run():
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    eng = serving.ContinuousEngine(params, cfg, max_slots=2, max_len=96,
                                   decode_stride=4, warmup=True,
                                   kv_cache_bytes=64 << 20,
                                   kv_label="obs-test")
    prompt = (np.arange(24) % cfg.vocab_size).astype(np.int32)
    q1 = eng.submit_stream(prompt, 8)
    toks1 = list(iter(q1.get, None))
    # same prompt again -> prefix-cache hit (the swap comes AFTER: a
    # weight swap invalidates every cached page by design)
    q2 = eng.submit_stream(prompt, 8, obs_ctx={"request_id": "req-obs-2",
                                               "span_id": "parentspan01"})
    toks2 = list(iter(q2.get, None))
    la = dict(eng._batcher.last_admission)
    eng.load_params(params)  # swap -> swap_barrier tick
    time.sleep(0.3)  # the final record_tick lands just after the tokens
    yield eng, la, toks1, toks2
    eng.shutdown()


def test_tick_phase_sum_within_tolerance(engine_run):
    """The six phases partition each tick: their sum must account for the
    tick wall to within 10% (unattributed time = reap + lock waits)."""
    eng, _, toks1, toks2 = engine_run
    assert len(toks1) == 8 and len(toks2) == 8
    rec = eng._recorder
    ticks = rec.ticks()
    assert ticks, "engine produced no tick records"
    for t in ticks:
        phase_sum = sum(t["phases"].values())
        assert phase_sum <= t["wall_s"] * 1.02, (t["phases"], t["wall_s"])
    summ = rec.summary()
    assert 0.90 <= summ["phase_sum_ratio"] <= 1.02, summ
    # decode ticks carry the launch geometry the efficiency math needs
    decoded = [t for t in ticks if t["phases"].get("decode_step")]
    assert decoded and all(t["bucket"] >= 1 and t["k"] >= 1
                           for t in decoded)
    assert summ["recorded_wall_s"] > 0
    assert summ["overhead_frac"] < 0.02  # the ISSUE's overhead budget


def test_cached_prefill_attribution_matches_last_admission(engine_run):
    """The warm request's lifecycle record must carry the SAME cached/
    computed split the batcher attributed at admission."""
    eng, la, _, _ = engine_run
    assert la["cached_tokens"] > 0, "prefix cache never hit"
    reqs = eng._recorder.requests()
    warm = [r for r in reqs if r.get("request_id") == "req-obs-2"]
    assert warm, [r.get("request_id") for r in reqs]
    r = warm[-1]
    assert r["cached_tokens"] == la["cached_tokens"]
    assert r["prompt_tokens"] == la["prompt_tokens"]
    assert r["computed_tokens"] == r["prompt_tokens"] - r["cached_tokens"]
    assert r["kv_restore_s"] >= 0 and r["prefill_s"] > 0
    # 8 delivered tokens total: the first lands at admission, the rest
    # over decode ticks
    assert r["state"] == "done" and r["tokens"] == 8
    assert r["decode_ticks"] >= 1
    assert r["ttft_s"] >= 0 and r["tpot_s"] >= 0


def test_swap_barrier_phase_visible(engine_run):
    """load_params between requests must surface as a swap_barrier phase
    on some tick (and count in the summary)."""
    eng, _, _, _ = engine_run
    summ = eng._recorder.summary()
    assert summ["swaps"] >= 1
    assert summ["phase_s"].get("swap_barrier", 0.0) > 0.0, summ["phase_s"]


def test_request_record_joins_serve_span_tree(engine_run):
    """Draining a completed request that carries a serve obs_ctx emits a
    child span under the serve request's span tree (same request_id,
    parent_span_id = the serve span) — `rt trace <rid>` descends."""
    from ray_tpu.serve import obs

    eng, _, _, _ = engine_run
    # the drain thread (every 2 s) may have been here first, or be in the
    # middle of its pass: either way the request gets exactly one span
    eng._recorder._drain_spans()
    deadline = time.time() + 20
    while True:
        with obs._span_lock:
            spans = [dict(e) for e in obs._span_buf]
        mine = [e for e in spans
                if e["trace"]["trace_id"] == "req-obs-2"]
        if mine or time.time() > deadline:
            break
        time.sleep(0.05)
    assert len(mine) == 1, [e.get("task_id") for e in spans]
    ev = mine[-1]
    assert ev["task_id"].startswith("serve:req-obs-2:engine:")
    assert ev["trace"]["parent_span_id"] == "parentspan01"
    assert ev["name"] == "engine:obs-test"
    ph = ev["phases"]
    assert set(ph) >= {"queue_wait", "prefill", "decode"}
    # watermarked: a second drain pass must not duplicate the span
    assert eng._recorder._drain_spans() == 0


# ---------------------------------------------------------------------------
# SLO/goodput math (synthetic records — no engine, no jax dispatch)
# ---------------------------------------------------------------------------

def _synthetic_recorder():
    rec = ER.EngineRecorder("slo-math", max_slots=4, enabled=True,
                            ttft_slo_s=0.100, tpot_slo_s=0.010)
    t0 = 1000.0
    # req 1: TTFT 50ms ok, TPOT 5ms ok (11 tokens over 50ms decode)
    rec.request_admitted(1, t_submit=t0, t_admit=t0 + 0.050,
                         prompt_tokens=8, cached_tokens=0,
                         prefill_s=0.04, kv_restore_s=0.0)
    rec.request_tokens(1, 10, t0 + 0.100, done=True)
    # req 2: TTFT 200ms violates; TPOT 5ms ok
    rec.request_admitted(2, t_submit=t0, t_admit=t0 + 0.200,
                         prompt_tokens=8, cached_tokens=0,
                         prefill_s=0.19, kv_restore_s=0.0)
    rec.request_tokens(2, 10, t0 + 0.250, done=True)
    # req 3: TTFT 50ms ok; TPOT 50ms violates (11 tokens over 500ms)
    rec.request_admitted(3, t_submit=t0, t_admit=t0 + 0.050,
                         prompt_tokens=8, cached_tokens=0,
                         prefill_s=0.04, kv_restore_s=0.0)
    rec.request_tokens(3, 10, t0 + 0.550, done=True)
    # req 4: cancelled — must NOT enter the SLO window
    rec.request_admitted(4, t_submit=t0, t_admit=t0 + 0.010,
                         prompt_tokens=8, cached_tokens=0,
                         prefill_s=0.005, kv_restore_s=0.0)
    rec.request_done(4, t=t0 + 0.020, state="cancelled")
    return rec


def test_slo_attainment_math():
    rec = _synthetic_recorder()
    try:
        s = rec.summary()
        assert s["window_completed"] == 3  # the cancel is excluded
        assert s["requests_total"] == 4 and s["cancelled_total"] == 1
        assert s["ttft_attainment"] == pytest.approx(2 / 3, abs=1e-4)
        assert s["tpot_attainment"] == pytest.approx(2 / 3, abs=1e-4)
        # goodput: only req 1 meets BOTH SLOs -> 11 tokens over the
        # window span (first done t0+0.1 .. last done t0+0.55 = 0.45s)
        assert s["goodput_tok_s"] == pytest.approx(11 / 0.45, abs=0.06)
        assert s["window_tok_s"] == pytest.approx(33 / 0.45, abs=0.06)
        assert s["goodput_frac"] == pytest.approx(11 / 33, abs=1e-4)
        # retroactive retune: loosening both SLOs lifts attainment to 1.0
        # over the SAME window (bench calibration depends on this)
        rec.set_slo(ttft_slo_s=1.0, tpot_slo_s=1.0)
        s2 = rec.summary()
        assert s2["ttft_attainment"] == 1.0
        assert s2["tpot_attainment"] == 1.0
        assert s2["goodput_frac"] == 1.0
    finally:
        rec.close()


def test_window_summary_carves_time_ranges():
    rec = _synthetic_recorder()
    try:
        # ticks at t=1000 and t=2000; only the first lands in [999, 1500)
        rec.record_tick(t_start=1000.0, wall_s=0.010,
                        phases={"decode_step": 0.008,
                                "token_delivery": 0.002},
                        active=2, pending=0, bucket=4, k=4, tokens=8,
                        admitted=0, gap_s=0.001)
        rec.record_tick(t_start=2000.0, wall_s=0.010,
                        phases={"decode_step": 0.008}, active=1,
                        pending=0, bucket=4, k=4, tokens=4, admitted=0,
                        gap_s=0.5)
        w = rec.window_summary(999.0, 1500.0)
        assert w["window_ticks"] == 1 and w["tokens"] == 8
        assert w["tick_gap_max_s"] == pytest.approx(0.001)
        # capacity: bucket*k=16 possible, 8 emitted -> efficiency 0.5;
        # occupancy = active/max_slots = 2/4
        assert w["decode_efficiency"] == pytest.approx(0.5)
        assert w["occupancy"] == pytest.approx(0.5)
        assert w["window_completed"] == 3  # dones at t0+0.1..0.55
        w2 = rec.window_summary(1500.0, 2500.0)
        assert w2["window_ticks"] == 1 and w2["window_completed"] == 0
        assert w2["tick_gap_max_s"] == pytest.approx(0.5)
    finally:
        rec.close()


def test_recorder_bounded_under_sustained_load():
    """The flight recorder is a ring: unbounded traffic must not grow it
    past its cap (ticks, done ring, SLO window, leaked actives)."""
    rec = ER.EngineRecorder("bounded", max_slots=4, cap=128, enabled=True)
    try:
        for i in range(5000):
            rec.record_tick(t_start=float(i), wall_s=0.001,
                            phases={"decode_step": 0.001}, active=1,
                            pending=0, bucket=4, k=1, tokens=1,
                            admitted=0, gap_s=None)
            rec.request_admitted(i, t_submit=float(i), t_admit=float(i),
                                 prompt_tokens=4, cached_tokens=0,
                                 prefill_s=0.0, kv_restore_s=0.0)
            if i % 2 == 0:
                rec.request_tokens(i, 4, float(i) + 0.01, done=True)
            # odd rids never finish: the _active backstop must bound them
        assert len(rec.ticks()) <= 128
        assert len(rec.requests()) <= 128
        assert len(rec._active) <= 128
        assert len(rec._window) <= ER._SLO_WINDOW
        s = rec.summary()
        assert s["ticks_total"] == 5000 and s["requests_total"] == 5000
        # snapshot stays compact enough for the 2s KV push cadence
        assert len(json.dumps(rec.snapshot())) < 64_000
    finally:
        rec.close()


def test_kill_switch_records_nothing():
    rec = ER.EngineRecorder("off", max_slots=2, enabled=False)
    try:
        rec.record_tick(t_start=0.0, wall_s=1.0, phases={}, active=0,
                        pending=0, bucket=0, k=0, tokens=0, admitted=0,
                        gap_s=None)
        rec.request_admitted(1, t_submit=0.0, t_admit=0.0,
                             prompt_tokens=1, cached_tokens=0,
                             prefill_s=0.0, kv_restore_s=0.0)
        assert not rec.ticks() and not rec.requests()
        assert rec.summary()["ticks_total"] == 0
    finally:
        rec.close()


def test_doctor_engine_findings():
    """Sustained tick-gap and SLO-attainment findings from a synthetic
    report; stale snapshots skipped; WARN level (doctor stays exit 0)."""
    from ray_tpu.util import doctor

    now = time.time()
    snap = {"t": now, "node": "n1", "name": "eng", "summary": {
        "gap_recent": [0.6, 0.7, 0.8], "window_completed": 10,
        "ttft_attainment": 0.5, "tpot_attainment": 0.95,
        "ttft_slo_s": 1.5, "tpot_slo_s": 0.15}}
    node = {"node_id": "n1deadbeef", "alive": True, "resources": {},
            "available": {}}
    report = {"nodes": [node], "actors": [], "failures": [], "ooms": [],
              "engines": [snap], "window_s": 600.0}
    findings = doctor.diagnose(report)
    msgs = [m for lvl, m in findings if lvl == doctor.WARN]
    assert any("tick-gap sustained" in m for m in msgs), findings
    assert any("TTFT SLO attainment 0.50" in m for m in msgs), findings
    assert not any("TPOT SLO" in m for m in msgs)  # 0.95 attains
    assert not any(lvl == doctor.CRITICAL for lvl, _ in findings)
    # healthy gaps below the threshold: no finding
    snap2 = dict(snap, summary=dict(snap["summary"],
                                    gap_recent=[0.01, 0.02, 0.01],
                                    ttft_attainment=0.99))
    findings = doctor.diagnose(dict(report, engines=[snap2]))
    assert not any("tick-gap" in m for _, m in findings)
    # stale snapshot (dead pusher): skipped entirely
    stale = dict(snap, t=now - 120.0)
    findings = doctor.diagnose(dict(report, engines=[stale]))
    assert not any("engine" in m for _, m in findings), findings
    # idle engine (zero completed): no SLO grading
    idle = dict(snap, summary=dict(snap["summary"], window_completed=0,
                                   gap_recent=[]))
    findings = doctor.diagnose(dict(report, engines=[idle]))
    assert not any("SLO" in m for _, m in findings)


# ---------------------------------------------------------------------------
# the cluster surfaces: @engine/ KV -> /api/engine + rt engine --json
# ---------------------------------------------------------------------------

def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def test_api_engine_and_cli_json(rt_cluster):
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.scripts import cli
    import ray_tpu

    rec = ER.EngineRecorder("surfaced", max_slots=2, enabled=True)
    try:
        rec.record_tick(t_start=time.time(), wall_s=0.010,
                        phases={"decode_step": 0.008,
                                "token_delivery": 0.002},
                        active=1, pending=0, bucket=2, k=4, tokens=4,
                        admitted=0, gap_s=0.003)
        rec.request_admitted(7, t_submit=time.time() - 0.05,
                             t_admit=time.time(), prompt_tokens=16,
                             cached_tokens=8, prefill_s=0.01,
                             kv_restore_s=0.002)
        rec.request_tokens(7, 4, time.time(), done=True)
        counts = rec.drain_now()
        assert counts["kv"] == 1, counts  # the @engine/ snapshot landed

        port = start_dashboard()
        payload = _get_json(port, "/api/engine")
        snaps = [s for s in payload["engines"]
                 if s.get("name") == "surfaced"]
        assert snaps, payload
        snap = snaps[-1]
        assert snap["summary"]["window_ticks"] == 1
        assert snap["ticks"] and snap["ticks"][-1]["phases_ms"]
        assert snap["requests"][-1]["cached_tokens"] == 8

        b = ray_tpu.global_worker()._require_backend()
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_engine(Namespace(address=b.gcs_address,
                                          name="surfaced", limit=5,
                                          json=True, engine_cmd="stats"))
        assert rc == 0
        stats = json.loads(out.getvalue())
        assert stats and stats[0]["summary"]["window_completed"] == 1
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_engine(Namespace(address=b.gcs_address,
                                          name="surfaced", limit=5,
                                          json=True, engine_cmd="ticks"))
        assert rc == 0
        ticks = json.loads(out.getvalue())
        assert ticks[0]["ticks"][-1]["gap_ms"] == pytest.approx(3.0)
        # human rendering smoke (no --json): one line per surface
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_engine(Namespace(address=b.gcs_address,
                                          name="surfaced", limit=5,
                                          json=False, engine_cmd="stats"))
        assert rc == 0 and "recorder overhead" in out.getvalue()
    finally:
        rec.close()


# ---------------------------------------------------------------------------
# the tick loop and the request path as spans (``recorder_core.span``): one
# vocabulary on the recorder's clock and on the profiler's
# ---------------------------------------------------------------------------

VOCABULARY = set(ER.TICK_PHASES) - {"decode_step"} | set(ER.DECODE_PARTS)


def _tiny_engine(**kw):
    cfg = llama.PRESETS["debug"]
    params = llama.init_params(jax.random.key(0), cfg)
    args = dict(max_slots=4, max_len=160, decode_stride=4, warmup=True,
                kv_cache_bytes=0, kv_label="spans")
    args.update(kw)
    eng = serving.ContinuousEngine(params, cfg, **args)
    prompt = (np.arange(24) % cfg.vocab_size).astype(np.int32)
    eng._batcher.warmup(prompt_lens=(len(prompt),))
    return eng, prompt


def _drain(queues):
    return [list(iter(q.get, None)) for q in queues]


def test_new_phases_partition_the_tick():
    """Ticks tile the engine thread's time: ``record`` and ``idle_wait``
    are phases, the phases sum to the wall within 2%, and ``decode_step``
    keeps its extent: the sum of its three parts."""
    eng, prompt = _tiny_engine()
    try:
        t0 = time.time()
        _drain([eng.submit_stream(prompt, 60) for _ in range(6)])
        time.sleep(0.7)  # the engine parks: idle_wait, carried forward
        _drain([eng.submit_stream(prompt, 8)])
        time.sleep(0.1)
        w = eng._recorder.window_summary(t0, time.time())
        assert 0.98 <= w["phase_sum_ratio"] <= 1.0, w
        assert w["phase_s"]["record"] > 0 and w["phase_s"]["idle_wait"] > 0.5
        assert set(w["phase_s"]) <= set(ER.TICK_PHASES)
        assert set(w["decode_parts_s"]) == set(ER.DECODE_PARTS)
        assert 0 < w["overhead_frac"] < 0.02
        decoded = [t for t in eng._recorder.ticks() if "decode_parts" in t]
        assert decoded
        for t in decoded:
            assert sum(t["decode_parts"].values()) \
                <= t["phases"]["decode_step"], t
        # what lies between the three spans is a few lines of interpreter
        assert sum(w["decode_parts_s"].values()) \
            >= 0.93 * w["phase_s"]["decode_step"], w
        # consecutive ticks abut: one's start is the last one's end
        ticks = eng._recorder.ticks()
        for a, b in zip(ticks, ticks[1:]):
            assert abs(a["t"] + a["wall_s"] - b["t"]) < 5e-3, (a, b)
    finally:
        eng.shutdown()


def test_prefill_phase_contains_the_first_token_read(monkeypatch):
    """The wait for the device is the read of the first token, not the
    call: a prefill whose read is slow must show in ``prefill``, and
    ``admission`` must stay host bookkeeping."""
    eng, prompt = _tiny_engine()

    class SlowFirst:
        def __init__(self, first):
            self.first = first

        def __getitem__(self, i):
            time.sleep(0.15)  # the device finishes only now
            return self.first[i]

    real = serving._compiled_slot_prefill

    def slow_prefill(*a, **k):
        fn = real(*a, **k)

        def run(*args):
            ck, cv, first = fn(*args)
            return ck, cv, SlowFirst(first)
        return run

    monkeypatch.setattr(serving, "_compiled_slot_prefill", slow_prefill)
    try:
        t0 = time.time()
        _drain([eng.submit_stream(prompt, 4)])
        time.sleep(0.1)
        w = eng._recorder.window_summary(t0, time.time())
        assert w["phase_s"]["prefill"] >= 0.15, w["phase_s"]
        assert w["phase_s"]["admission"] < 0.05, w["phase_s"]
        r = eng._recorder.requests()[-1]
        assert r["prefill_s"] >= 0.15
        # popped at once, first token only after the slow prefill
        assert r["queue_s"] < 0.05 < 0.15 <= r["ttft_s"]
    finally:
        eng.shutdown()


def test_request_queue_and_front_stamps():
    """``queue_s`` is the wait for a slot (submit -> popped), never more
    than TTFT; the front's figures exist only for a request whose context
    a proxy and a replica stamped."""
    eng, prompt = _tiny_engine(max_slots=2)
    try:
        t0 = time.time()
        stamped = {"request_id": "via-proxy", "span_id": "s1",
                   "t_ingress": t0 - 0.030, "t_replica": t0 - 0.010}
        qs = [eng.submit_stream(prompt, 40, obs_ctx=stamped)]
        qs += [eng.submit_stream(prompt, 40,
                                 obs_ctx={"request_id": "direct",
                                          "span_id": "s2"})]
        qs += [eng.submit_stream(prompt, 40) for _ in range(3)]
        _drain(qs)
        time.sleep(0.1)
        reqs = eng._recorder.requests()
        assert len(reqs) == 5
        for r in reqs:
            assert 0.0 <= r["queue_s"] <= r["ttft_s"] == r["queue_wait_s"], r
        # two slots, five requests: the later ones waited for a slot
        assert max(r["queue_s"] for r in reqs) > 0.005
        by_id = {r.get("request_id"): r for r in reqs}
        assert 0.030 <= by_id["via-proxy"]["front_in_s"] < 0.5
        assert 0.010 <= by_id["via-proxy"]["replica_in_s"] \
            <= by_id["via-proxy"]["front_in_s"]
        assert "front_in_s" not in by_id["direct"]
        assert "replica_in_s" not in by_id["direct"]
        w = eng._recorder.window_summary(t0 - 1, time.time())
        assert w["front_in_p50_s"] == pytest.approx(
            by_id["via-proxy"]["front_in_s"], abs=1e-5)
        assert w["queue_p50_s"] <= w["queue_p90_s"] <= w["ttft_p99_s"]
    finally:
        eng.shutdown()


def test_tick_excess_is_zero_on_even_ticks():
    rec = ER.EngineRecorder("even", max_slots=4, enabled=True)
    try:
        for i in range(30):
            rec.record_tick(
                t_start=100.0 + i, wall_s=0.010,
                phases={"decode_step": 0.008, "token_delivery": 0.002},
                decode_parts={"decode_stage": 0.001, "decode_launch": 0.006,
                              "decode_book": 0.001},
                active=4, pending=0, bucket=4, k=4 if i % 3 else 1,
                tokens=4, admitted=0, gap_s=0.002)
        w = rec.window_summary(0.0, 1000.0)
        assert w["tick_excess_s"] == 0.0 and w["launch_excess_s"] == 0.0
        # a parked stretch is a tick without a launch: not a stall
        rec.record_tick(t_start=200.0, wall_s=30.0,
                        phases={"idle_wait": 30.0}, active=0, pending=1,
                        bucket=0, k=0, tokens=0, admitted=0, gap_s=None)
        assert rec.window_summary(0.0, 1000.0)["tick_excess_s"] == 0.0
    finally:
        rec.close()


def test_tick_excess_sees_a_tick_made_to_sleep():
    """One tick of a live engine sleeps 0.2 s on the host (in ``on_tick``,
    so outside the launch): the engine thread's excess holds the delay
    less one median tick, and none of it is the launch's."""
    calls = []

    def on_tick(active, slots):
        calls.append(active)
        if len(calls) == 6:
            time.sleep(0.2)

    eng, prompt = _tiny_engine(on_tick=on_tick)
    try:
        t0 = time.time()
        _drain([eng.submit_stream(prompt, 100) for _ in range(4)])
        time.sleep(0.1)
        w = eng._recorder.window_summary(t0, time.time())
        assert len(calls) > 12
        assert 0.15 <= w["tick_excess_s"] <= 0.26, w
        assert w["launch_excess_s"] < 0.03, w
        assert w["tick_gap_max_s"] >= 0.2
    finally:
        eng.shutdown()


def test_pump_lag_sees_a_blocked_event_loop():
    """The stream pump's first boundary: a burst the engine hands over
    while the replica's event loop is blocked waits for it, and the
    recorder says for how long."""
    import asyncio

    from ray_tpu.serve.llm import ContinuousLLM

    llm = ContinuousLLM("debug", max_slots=2, max_len=320, decode_stride=2,
                        name="pump", kv_cache_bytes=0)
    try:
        async def main():
            gen = await llm({"tokens": list(range(1, 17)),
                             "max_new_tokens": 200})
            toks = [await gen.__anext__()]
            time.sleep(0.2)  # the loop is blocked; the engine ticks on
            toks += [t async for t in gen]
            return toks

        t0 = time.time()
        toks = asyncio.run(main())
        assert len(toks) == 200
        time.sleep(0.1)
        w = llm.engine._recorder.window_summary(t0, time.time())
        assert w["pump_bursts"] >= 50
        assert 0.1 <= w["pump_lag_max_s"] <= 0.5, w
        assert w["pump_lag_p50_s"] <= w["pump_lag_p99_s"] \
            <= w["pump_lag_max_s"]
        assert llm.engine.stats()["recorder"]["pump_bursts"] >= 50
    finally:
        llm.engine.shutdown()


def test_engine_spans_on_the_profilers_clock(tmp_path):
    """A ``jax.profiler`` trace of a live engine: the engine thread's line
    holds ``bench:`` events of the vocabulary only, no two overlap, and
    from one decode launch to the next they cover the loop."""
    eng, prompt = _tiny_engine()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            _drain([eng.submit_stream(prompt, 120) for _ in range(6)])
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.shutdown()
    from ray_tpu.util.recorder_core import TRACE_PREFIX

    paths = sorted(tmp_path.rglob("*.xplane.pb"))
    assert paths, list(tmp_path.rglob("*"))
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    lines = [[(e.start_ns, e.start_ns + e.duration_ns, e.name)
              for e in line.events if e.name.startswith(TRACE_PREFIX)]
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    engine = [sorted(evs) for evs in lines
              if any(n == TRACE_PREFIX + "decode_launch" for _, _, n in evs)]
    assert len(engine) == 1, [len(evs) for evs in lines]
    evs = engine[0]
    assert {n[len(TRACE_PREFIX):] for _, _, n in evs} <= VOCABULARY
    assert {"admission", "prefill", "decode_stage", "decode_launch",
            "decode_book", "token_delivery",
            "record"} <= {n[len(TRACE_PREFIX):] for _, _, n in evs}
    for (_, end, a), (start, _, b) in zip(evs, evs[1:]):
        assert end <= start, (a, b, end - start)
    launches = [e for e in evs if e[2] == TRACE_PREFIX + "decode_launch"]
    assert len(launches) > 20
    period = covered = gap = gap_covered = 0
    for (a0, a1, _), (b0, _, _) in zip(launches, launches[1:]):
        inside = [(max(s, a1), min(e, b0)) for s, e, _ in evs
                  if e > a1 and s < b0]
        period += b0 - a0
        gap += b0 - a1
        gap_covered += sum(e - s for s, e in inside)
    covered = gap_covered + period - gap
    # launch to launch the spans cover the loop; in the gaps alone, on
    # this CPU, ~10 us of interpreter between spans weigh against a gap
    # of ~1 ms (on the chip a gap is several ms)
    assert covered / period >= 0.95, covered / period
    assert gap_covered / gap >= 0.80, gap_covered / gap


def test_engine_span_carries_the_token_counts():
    """The replica's per-request ``kv:`` span is gone; the engine's
    lifecycle span carries its two counts, and they are not seconds."""
    from ray_tpu.serve import obs
    from ray_tpu.util import tracing

    rec = ER.EngineRecorder("counts", max_slots=2, enabled=True)
    try:
        rec.request_admitted(1, t_submit=10.0, t_admit=10.2,
                             prompt_tokens=48, cached_tokens=32,
                             prefill_s=0.1, kv_restore_s=0.05,
                             obs_ctx={"request_id": "req-counts",
                                      "span_id": "p1"})
        rec.request_tokens(1, 7, 10.5, done=True)
        assert rec._drain_spans() == 1
        with obs._span_lock:
            ev = [dict(e) for e in obs._span_buf
                  if e["trace"]["trace_id"] == "req-counts"][-1]
        assert ev["phases"]["cached_tokens"] == 32.0
        assert ev["phases"]["prompt_tokens"] == 48.0
        timed = tracing.timed_phases(ev["phases"])
        assert set(timed) == {"queue_wait", "prefill", "kv_restore",
                              "decode"}
        assert tracing._span_duration(ev) == pytest.approx(
            sum(timed.values()))
        assert tracing.critical_path([ev])[0][1] in timed
        text = tracing.format_trace([ev])
        assert "cached_tokens" in text and "32" in text
    finally:
        rec.close()


def test_front_stamps_reach_the_engine_through_the_http_proxy(rt_cluster):
    """Proxy receipt and replica entry ride the request context through
    handle and replica into the engine's request record: the front's
    figure is there for a request that came by HTTP and absent for a
    direct handle call."""
    import requests

    from ray_tpu import serve
    from ray_tpu.serve.llm import continuous_llm_app

    try:
        serve.run(continuous_llm_app("debug", max_slots=2, max_len=96,
                                     decode_stride=2, name="Front",
                                     kv_cache_bytes=0),
                  name="front", route_prefix="/front",
                  http_options=serve.HTTPOptions(port=0))
        h = serve.get_deployment_handle("Front", "front")
        body = {"tokens": list(range(1, 13)), "max_new_tokens": 6}

        assert len(list(h.remote(body).result())) == 6
        direct = h.engine_stats.remote().result()["recorder"]
        assert direct["window_completed"] == 1
        assert "queue_p50_s" in direct
        # no proxy saw it; it did enter a replica
        assert "front_in_p50_s" not in direct
        assert 0.0 < direct["replica_in_p50_s"] < 5.0

        r = requests.post(f"http://127.0.0.1:{serve.http_port()}/front/",
                          json=body, timeout=60)
        assert r.status_code == 200 and len(r.text.split()) == 6
        via = h.engine_stats.remote().result()["recorder"]
        assert via["window_completed"] == 2
        # proxy -> handle -> replica -> executor -> engine, on one host
        assert 0.0 < via["replica_in_p50_s"] <= via["front_in_p50_s"] < 5.0
        assert via["queue_p50_s"] <= via["ttft_p99_s"]
        assert via["pump_bursts"] >= 2
    finally:
        serve.shutdown()
        serve._forget_controller_for_tests()
