"""Train flight recorder (``util/train_recorder.py``): per-launch phase
attribution on a real fused StepDriver run, launch-gap semantics, the
MFU-gap waterfall math, the ``/api/train`` + ``rt train`` surfaces,
doctor findings, and the bounded-memory property. Named ``test_zz_*`` so
it sorts late."""

import contextlib
import io
import json
import time
import urllib.request
from argparse import Namespace

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.models import llama  # noqa: E402
from ray_tpu.util import train_recorder as TR  # noqa: E402


# ---------------------------------------------------------------------------
# one shared fused-K run on the real driver — the record set the
# end-to-end attribution tests read
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def driver_run():
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.train.driver import StepDriver

    cfg = llama.PRESETS["debug"]
    K, BATCH, SEQ = 2, 2, 16
    opt = ts.default_optimizer(total_steps=100)
    params = llama.init_params(jax.random.key(0), cfg)
    opt_state = jax.jit(opt.init)(params)
    driver = StepDriver(cfg, opt, steps_per_launch=K)
    assert driver.fused and driver.recorder is not None
    # how often the run reads the compiled step (its collectives and its
    # memory): once, at the launch that compiled it
    driver.compiled_reads = []
    read = driver._read_compiled

    def counted(abstract):
        driver.compiled_reads.append(read(abstract))
        return driver.compiled_reads[-1]

    driver._read_compiled = counted
    rng = np.random.default_rng(3)

    def batches(n):
        for _ in range(n):
            yield {"tokens": rng.integers(
                0, cfg.vocab_size, (BATCH, SEQ + 1)).astype(np.int32)}

    # on_launch keeps the launch's loss as the device buffer it is and
    # reads it after the run: a host read inside the callback waits for
    # the launch, and the same milliseconds then count as host_tax and
    # as device_compute, so the phases of one launch sum past its wall
    held = []
    params, opt_state, _m = driver.run(
        params, opt_state, batches(4 * K),
        on_launch=lambda m: held.append(m["loss"]))
    taxes = [float(np.asarray(loss).ravel()[-1]) for loss in held]
    rec = driver.recorder
    deadline = time.time() + 10.0
    while time.time() < deadline and rec.summary().get("in_flight"):
        time.sleep(0.01)  # let the done-hook watcher close the records
    yield driver, rec, taxes
    rec.close()


def test_launch_phase_sums_and_overhead(driver_run):
    """The stamped phases partition each launch's wall to within the
    tentpole's ±5%/10% honesty bar, every record closes through the
    async done-hook, and the recorder's self-timed overhead stays under
    the 2% budget."""
    driver, rec, taxes = driver_run
    assert len(taxes) == 4  # 4 fused launches of K=2
    summ = rec.summary()
    assert summ["in_flight"] == 0, summ  # the watcher closed every record
    assert summ["window_launches"] == 4 and summ["steps"] == 8
    assert summ["launches_total"] == 4 and summ["steps_total"] == 8
    assert 0.90 <= summ["phase_sum_ratio"] <= 1.05, summ
    assert summ["overhead_frac"] < 0.02, summ  # the ISSUE's overhead bar
    recs = rec.launches()
    assert all("t_done" in r and r["wall_s"] > 0 for r in recs)
    for r in recs:
        assert sum(r["phases"].values()) <= r["wall_s"] * 1.10, r
    # the first launch compiles: its host call wall books as compile,
    # not dispatch (step-profiler convention); warm launches invert
    assert summ["compiles"] >= 1
    assert recs[0]["phases"]["compile"] > 0.0
    assert recs[0]["phases"]["dispatch"] == 0.0
    assert recs[-1]["phases"]["dispatch"] > 0.0
    assert recs[-1]["phases"]["compile"] == 0.0
    # host_tax merged in from the on_launch callback wall
    assert summ["phase_s"].get("host_tax", 0.0) >= 0.0
    # K/tokens/shape geometry: [K, B, S+1] at K=2, B=2, S=16
    assert all(r["k"] == 2 and r["batch_shape"] == [2, 2, 17]
               for r in recs)
    assert all(r["tokens"] == 2 * 2 * 16 and r["flops"] > 0 for r in recs)


# ---------------------------------------------------------------------------
# launch-gap + waterfall math (synthetic records — no driver, no jax
# dispatch; n_devices/peak pinned so the MFU arithmetic is exact)
# ---------------------------------------------------------------------------

def _synthetic(name="synth", cap=2048):
    return TR.TrainRecorder(name, cap=cap, n_devices=1, peak_flops=1e9,
                            enabled=True)


def test_launch_gap_semantics_and_dry_reset():
    """A gap is stamped ONLY when the stacked batch was ready before the
    previous launch's device-done; a late batch is a dry reset (the
    loader's fault, counted, never blamed on the devices)."""
    rec = _synthetic("gap")
    try:
        s1 = rec.record_launch(t_start=1000.0, data_wait_s=0.01,
                               h2d_s=0.01, dispatch_s=0.02,
                               t_dispatch_end=1000.04)
        recs = rec.launches()
        assert "gap_s" not in recs[-1]  # first launch: nothing to gap to
        rec.finalize_launch(s1, 1000.10)
        # batch ready at 1000.05 < prev_done 1000.10, dispatch starts at
        # 1000.22 -> the devices idled 0.12s with data in hand
        rec.record_launch(t_start=1000.20, data_wait_s=0.01, h2d_s=0.01,
                          dispatch_s=0.02, data_ready_t=1000.05,
                          t_dispatch_end=1000.24)
        r2 = rec.launches()[-1]
        assert r2["gap_s"] == pytest.approx(0.12)
        rec.finalize_launch(r2["seq"], 1000.30)
        # batch only ready AFTER prev_done: genuinely dry -> no gap
        rec.record_launch(t_start=1000.40, data_wait_s=0.05, h2d_s=0.01,
                          dispatch_s=0.02, data_ready_t=1000.45,
                          t_dispatch_end=1000.48)
        r3 = rec.launches()[-1]
        assert "gap_s" not in r3
        rec.finalize_launch(r3["seq"], 1000.50)
        assert rec.summary()["dry_resets"] == 1
        # explicit loader_dry (epoch boundary): the next launch must not
        # stamp a gap even with an early data_ready_t
        rec.loader_dry()
        rec.record_launch(t_start=1000.60, data_wait_s=0.01, h2d_s=0.01,
                          dispatch_s=0.02, data_ready_t=1000.40,
                          t_dispatch_end=1000.64)
        assert "gap_s" not in rec.launches()[-1]
        summ = rec.summary()
        assert summ["dry_resets"] == 2
        assert summ["launch_gap_max_s"] == pytest.approx(0.12)
        assert summ["gap_recent"] == [pytest.approx(0.12)]
    finally:
        rec.close()


def test_mfu_waterfall_math():
    """raw -> achieved decomposes exactly: each bucket's MFU cost is
    raw_mfu * bucket_s / span, attributions over-explaining the measured
    lost wall are scaled down onto it, and the bucket costs + uncovered
    sum back to the raw-achieved gap."""
    rec = _synthetic("wf")
    try:
        # L1: 0.2s data_wait, 0.1 h2d, 0.1 dispatch, 0.1 device -> 0.5s
        s1 = rec.record_launch(t_start=1000.0, data_wait_s=0.2,
                               h2d_s=0.1, dispatch_s=0.1,
                               t_dispatch_end=1000.4, flops=0.2e9,
                               k=2, tokens=100)
        rec.finalize_launch(s1, 1000.5)
        # L2: batch ready early -> 0.1s gap; 0.1 data_wait, 0.1 dispatch,
        # 0.2 device
        s2 = rec.record_launch(t_start=1000.5, data_wait_s=0.1,
                               h2d_s=0.0, dispatch_s=0.1,
                               data_ready_t=1000.45,
                               t_dispatch_end=1000.7, flops=0.3e9,
                               k=2, tokens=100)
        rec.add_host_tax(s2, 0.05)
        rec.finalize_launch(s2, 1000.9)

        s = rec.summary()
        # span 0.9s; device busy = dispatch 0.2 + device_compute 0.3
        assert s["span_s"] == pytest.approx(0.9)
        assert s["device_s"] == pytest.approx(0.5)
        # raw = 0.5e9 / (0.5 * 1e9) = 1.0; achieved = 0.5e9 / 0.9e9
        assert s["raw_mfu"] == pytest.approx(1.0)
        assert s["achieved_mfu"] == pytest.approx(0.5 / 0.9, abs=1e-4)
        assert s["mfu_gap_frac"] == pytest.approx(1 - 0.5 / 0.9, abs=1e-3)
        wf = s["waterfall"]
        # lost wall 0.4s; raw attributions 0.3 dw + 0.1 gap + 0.05 tax
        # = 0.45 over-explain it -> scaled by 0.4/0.45
        assert wf["lost_s"] == pytest.approx(0.4)
        scale = 0.4 / 0.45
        assert wf["buckets_s"]["data_wait"] == pytest.approx(0.3 * scale,
                                                            abs=1e-4)
        assert wf["buckets_s"]["launch_gap"] == pytest.approx(0.1 * scale,
                                                             abs=1e-4)
        assert wf["buckets_s"]["host_tax"] == pytest.approx(0.05 * scale,
                                                           abs=1e-4)
        assert wf["buckets_s"]["compile"] == 0.0
        assert wf["uncovered_s"] == pytest.approx(0.0, abs=1e-4)
        # the exact decomposition: bucket costs + uncovered = raw - achieved
        total_cost = sum(wf["mfu_cost"].values())
        assert total_cost == pytest.approx(
            s["raw_mfu"] - s["achieved_mfu"], abs=1e-3)
        assert wf["mfu_cost"]["data_wait"] == pytest.approx(
            1.0 * 0.3 * scale / 0.9, abs=1e-3)
        # marginal series: per-launch flops / (wall * peak)
        assert s["marginal_mfu"] == pytest.approx(0.3 / 0.4, abs=1e-3)
        assert len(s["marginal_mfu_recent"]) == 2
    finally:
        rec.close()


def test_waterfall_uncovered_residual():
    """Attributions UNDER-explaining the lost wall surface the residual
    as ``uncovered`` — the waterfall never stretches blame to fit."""
    rec = _synthetic("uncov")
    try:
        # fully-covered case first: 0.05s lost, 0.05s attributed
        s1 = rec.record_launch(t_start=2000.0, data_wait_s=0.05,
                               h2d_s=0.0, dispatch_s=0.1,
                               t_dispatch_end=2000.15, flops=0.1e9)
        rec.finalize_launch(s1, 2000.5)  # 0.35s device_compute
        s = rec.summary()
        # device = 0.1 dispatch + 0.35 device_compute = 0.45; span 0.5
        assert s["device_s"] == pytest.approx(0.45)
        wf = s["waterfall"]
        assert wf["lost_s"] == pytest.approx(0.05)
        assert wf["buckets_s"]["data_wait"] == pytest.approx(0.05)
        assert wf["uncovered_s"] == pytest.approx(0.0, abs=1e-6)
        rec2 = _synthetic("uncov2")
        try:
            # a launch whose wall is mostly unattributed host wall: the
            # derived dispatch-end fallback books it as device_compute,
            # so here we pin dispatch-end late and stamp nothing for it
            t1 = rec2.record_launch(t_start=3000.0, data_wait_s=0.02,
                                    h2d_s=0.0, dispatch_s=0.1,
                                    t_dispatch_end=3000.4, flops=0.1e9)
            rec2.finalize_launch(t1, 3000.5)
            s2 = rec2.summary()
            wf2 = s2["waterfall"]
            # lost = 0.5 - (0.1 + 0.1) = 0.3; only 0.02 attributed
            assert wf2["lost_s"] == pytest.approx(0.3)
            assert wf2["uncovered_s"] == pytest.approx(0.28, abs=1e-4)
            assert wf2["mfu_cost"]["uncovered"] > 0
        finally:
            rec2.close()
    finally:
        rec.close()


def test_the_compiled_steps_memory_rides_with_the_launches(driver_run):
    """The launch that compiled the fused program reads what the compiler
    says it needs of a device's memory off the same executable as its
    collectives (``util/hlo_copies.step_memory``), once: the later launches
    lower nothing. The recorder hands it on wherever ``eva_plan`` rides:
    ``summary()``, ``window_summary()``, ``launch_totals()`` and from there
    the trainer's ``train_launches`` span, where a reader that has neither
    the worker nor JAX finds it."""
    from ray_tpu.train.trainer import JaxTrainer
    from ray_tpu.util import lifecycle

    driver, rec, _ = driver_run
    assert len(driver.compiled_reads) == 1 and driver.launches == 4
    assert driver.compile_count() == 1
    mem = rec.summary()["step_memory"]
    assert sorted(mem) == ["alias_bytes", "argument_bytes", "output_bytes",
                           "peak_bytes", "temp_bytes"]
    assert mem["peak_bytes"] >= mem["temp_bytes"] > 0, mem
    # the state is donated: what comes back lies where it came from
    assert 0 < mem["alias_bytes"] <= mem["argument_bytes"], mem
    assert driver.compiled_reads[0][1] == mem == rec.step_memory
    assert rec.window_summary(0.0, 1e18)["step_memory"] == mem
    assert rec.launch_totals()["step_memory"] == mem
    JaxTrainer._note_launches(rec.launch_totals())
    span = lifecycle.last("train_launches")
    assert span["step_memory"] == mem and span["launches"] == 4


def test_window_summary_carves_launches():
    rec = _synthetic("win")
    try:
        s1 = rec.record_launch(t_start=1000.0, data_wait_s=0.01,
                               h2d_s=0.0, dispatch_s=0.05,
                               t_dispatch_end=1000.06, tokens=64, k=2)
        rec.finalize_launch(s1, 1000.1)
        s2 = rec.record_launch(t_start=2000.0, data_wait_s=0.20,
                               h2d_s=0.0, dispatch_s=0.05,
                               t_dispatch_end=2000.25, tokens=32, k=2)
        rec.finalize_launch(s2, 2000.3)
        w = rec.window_summary(999.0, 1500.0)
        assert w["window_launches"] == 1 and w["tokens"] == 64
        assert w["phase_s"]["data_wait"] == pytest.approx(0.01)
        w2 = rec.window_summary(1500.0, 2500.0)
        assert w2["window_launches"] == 1 and w2["tokens"] == 32
        assert w2["data_wait_frac"] == pytest.approx(0.2 / 0.3, abs=1e-3)
        # an empty window still says how the step was tiled, placed and
        # what it moves across chips (static per compiled shape; this
        # synthetic recorder traced no flash kernel and compiled no step)
        assert rec.window_summary(0.0, 999.0) == {
            "window_launches": 0, "flash_plans": [], "kda_plan": {},
            "eva_plan": {}, "hyper_plan": {}, "sparse_plan": {},
            "expert_placement": None,
            "collectives": {},
            "step_memory": {}, "routing": {}}
        # full summary spans both
        assert rec.summary()["window_launches"] == 2
    finally:
        rec.close()


def test_recorder_bounded_and_snapshot_compact():
    """The ring must not grow past its cap under unbounded launches —
    including records whose done-hook never fires — and the @train/ KV
    snapshot stays under the 64 KB push budget."""
    rec = TR.TrainRecorder("bounded", cap=64, n_devices=1,
                           peak_flops=1e9, enabled=True)
    try:
        for i in range(2000):
            seq = rec.record_launch(t_start=float(i), data_wait_s=0.001,
                                    h2d_s=0.001, dispatch_s=0.002,
                                    t_dispatch_end=float(i) + 0.004,
                                    k=4, tokens=128, flops=1e6,
                                    batch_shape=(4, 2, 17))
            if i % 2 == 0:
                rec.finalize_launch(seq, float(i) + 0.01)
            # odd seqs never finalize: the _open backstop must bound them
        assert len(rec.launches()) <= 64
        with rec._lock:
            assert len(rec._open) <= 64
        s = rec.summary()
        assert s["launches_total"] == 2000 and s["steps_total"] == 8000
        assert len(json.dumps(rec.snapshot())) < 64_000
    finally:
        rec.close()


def test_kill_switch_records_nothing():
    rec = TR.TrainRecorder("off", enabled=False)
    try:
        seq = rec.record_launch(t_start=0.0, data_wait_s=1.0, h2d_s=0.0,
                                dispatch_s=1.0)
        assert seq == 0  # the driver's hooks all no-op on seq 0
        rec.watch_outputs(seq, {"loss": 1.0})
        rec.add_host_tax(seq, 1.0)
        rec.finalize_launch(seq, 2.0)
        rec.loader_dry()
        assert not rec.launches()
        s = rec.summary()
        assert s["launches_total"] == 0 and s["window_launches"] == 0
        assert s["dry_resets"] == 0
    finally:
        rec.close()


def test_doctor_train_findings():
    """Sustained launch-gap and data-starvation findings from a synthetic
    report; stale and idle snapshots skipped; WARN level only (doctor
    stays exit 0)."""
    from ray_tpu.util import doctor

    now = time.time()
    snap = {"t": now, "node": "n1", "name": "drv", "summary": {
        "window_launches": 6, "gap_recent": [0.01, 0.3, 0.4, 0.5],
        "data_wait_frac": 0.40,
        "waterfall": {"mfu_cost": {"data_wait": 0.120}}}}
    node = {"node_id": "n1deadbeef", "alive": True, "resources": {},
            "available": {}}
    report = {"nodes": [node], "actors": [], "failures": [], "ooms": [],
              "trains": [snap], "window_s": 600.0}
    findings = doctor.diagnose(report)
    msgs = [m for lvl, m in findings if lvl == doctor.WARN]
    assert any("launch-gap sustained" in m for m in msgs), findings
    assert any("data-starved" in m and "costing 0.120 MFU" in m
               for m in msgs), findings
    assert not any(lvl == doctor.CRITICAL for lvl, _ in findings)
    # thresholds are tunable from the CLI flags
    f2 = doctor.diagnose(report, launch_gap_warn_s=0.6,
                         data_wait_warn=0.5)
    assert not any("train driver" in m for _, m in f2), f2
    # one wide gap is a checkpoint fence, not sustained starvation
    healthy = dict(snap, summary=dict(snap["summary"],
                                      gap_recent=[0.01, 0.5, 0.01],
                                      data_wait_frac=0.05))
    f3 = doctor.diagnose(dict(report, trains=[healthy]))
    assert not any("train driver" in m for _, m in f3), f3
    # stale snapshot (the @train/ key deliberately outlives the driver):
    # skipped entirely, never failed
    stale = dict(snap, t=now - 120.0)
    f4 = doctor.diagnose(dict(report, trains=[stale]))
    assert not any("train driver" in m for _, m in f4), f4
    # idle driver (no launches in the window): nothing to grade
    idle = dict(snap, summary=dict(snap["summary"], window_launches=0))
    f5 = doctor.diagnose(dict(report, trains=[idle]))
    assert not any("train driver" in m for _, m in f5), f5


def test_timeline_launch_lanes():
    """A drained train_launch event renders as Perfetto lanes: the launch
    span, the consecutive phase partition, and the gap span anchored
    BEFORE dispatch."""
    from ray_tpu.util.timeline import _train_launch_lanes

    rec_payload = {"seq": 3, "t": 1000.0, "k": 2, "tokens": 64,
                   "wall_s": 0.5, "gap_s": 0.1, "driver": "tl",
                   "flops": 1e9, "batch_shape": [2, 2, 17],
                   "t_done": 1000.5,
                   "phases": {"data_wait": 0.2, "h2d": 0.05,
                              "dispatch": 0.05, "device_compute": 0.15,
                              "host_tax": 0.02, "compile": 0.0}}
    ev = {"task_id": "trainlaunch:n1:1:tl:3", "node_id": "n1",
          "times": {"RUNNING": 1000.0, "FINISHED": 1000.5}}
    lanes = _train_launch_lanes(ev, rec_payload)
    tids = {s["tid"] for s in lanes}
    assert {"train:tl:launches", "train:tl:phases",
            "train:tl:gap"} <= tids
    launch = [s for s in lanes if s["tid"] == "train:tl:launches"][0]
    assert launch["ts"] == pytest.approx(1000.0 * 1e6)
    assert launch["dur"] == pytest.approx(0.5 * 1e6)
    # the gap span sits before dispatch start (t + data_wait + h2d)
    gap = [s for s in lanes if s["tid"] == "train:tl:gap"][0]
    assert gap["dur"] == pytest.approx(0.1 * 1e6)
    assert gap["ts"] + gap["dur"] == pytest.approx(
        (1000.0 + 0.2 + 0.05) * 1e6)
    # phases partition consecutively in launch order
    phases = sorted((s for s in lanes if s["tid"] == "train:tl:phases"),
                    key=lambda s: s["ts"])
    assert [p["name"] for p in phases] == ["data_wait", "h2d",
                                           "dispatch", "device_compute"]
    for a, b in zip(phases, phases[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"])


# ---------------------------------------------------------------------------
# the cluster surfaces: @train/ KV -> /api/train + rt train --json, and
# the postmortem error discipline
# ---------------------------------------------------------------------------

def _get_json(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as resp:
        return json.loads(resp.read())


def _four_plans():
    """A plan of each kind that has a sentence, hand-made."""
    from ray_tpu.ops import hyper

    return {
        "flash_plans": [{
            "kind": "fwd", "seq_q": 8192, "seq_k": 8192, "head_dim": 128,
            "block_q": 1024, "block_k": 1024, "live_steps": 30,
            "edge_steps": 12, "sub_block": (512, 512),
            "grid_steps": 64, "window": 4096}],
        "kda_plan": dict(
            chunk=64, sub_block=16, chunks=256, segments=4, heads=32,
            d_k=128, d_v=128, boundary_state_bytes=536_870_912,
            impl="pallas_insides"),
        "eva_plan": dict(
            impl="pallas", batch=1, heads=32, head_dim=128, seq=16384,
            window=2048, chunk=16, windows=8, chunks=1024,
            summaries_seen=896, block=1024, summary_block=128,
            tiles_needed=80, tiles_visited=80),
        "hyper_plan": hyper.plan(4, 3584, 2, 20, 256)}


def test_a_plan_is_noted_through_one_seam():
    """A kind nobody has heard of, noted inside ``plans.noting``, lands in
    the recorder's summaries under ``<kind>_plan`` with no edit to driver or
    recorder; a nested scope hands the outer sink back, another thread sees
    none, and nothing is noted outside a scope."""
    import threading

    from ray_tpu.util import plans

    rec = _synthetic("seam")
    try:
        plans.note("stub", {"lost": 1})
        inner, seen = {}, {}
        with plans.noting(rec.plans):
            plans.note("stub", {"tile": 8})
            with plans.noting(inner):
                plans.note("stub", {"tile": 16})
            plans.note("stub", {"impl": "xla"})
            for _ in range(2):
                plans.note("flash", {"kind": "fwd", "block_q": 128})
            plans.note("flash", {"kind": "dq", "block_q": 64})
            t = threading.Thread(target=lambda: (
                plans.note("stub", {"tile": 32}),
                seen.update(into=getattr(plans._noting, "into", None))))
            t.start()
            t.join()
        plans.note("stub", {"lost": 2})
        assert seen == {"into": None}
        assert inner == {"stub_plan": {"tile": 16}}
        assert rec.plans == {
            "stub_plan": {"tile": 8, "impl": "xla"},
            "flash_plans": [{"kind": "fwd", "block_q": 128},
                            {"kind": "dq", "block_q": 64}]}
        for summ in (rec.summary(), rec.window_summary(0.0, 1e18)):
            assert summ["stub_plan"] == {"tile": 8, "impl": "xla"}
            assert summ["flash_plans"] == rec.plans["flash_plans"]
            assert summ["kda_plan"] == summ["eva_plan"] == {}
            assert summ["hyper_plan"] == {}
        # a copy: a reader's edit does not reach the recorder
        rec.summary()["stub_plan"]["tile"] = 0
        rec.summary()["flash_plans"][0]["kind"] = "?"
        assert rec.plans["stub_plan"]["tile"] == 8
        assert rec.plans["flash_plans"][0]["kind"] == "fwd"
        # the span carries the keys a reader without the worker takes, where
        # they are not empty, and nothing of flash or kda
        s1 = rec.record_launch(t_start=1000.0, data_wait_s=0.0, h2d_s=0.0,
                               dispatch_s=0.05, t_dispatch_end=1000.05,
                               tokens=64, k=2)
        rec.finalize_launch(s1, 1000.1)
        assert not {"flash_plans", "stub_plan", "kda_plan", "eva_plan",
                    "hyper_plan"} & set(rec.launch_totals())
        rec.plans.update(_four_plans())
        totals = rec.launch_totals()
        assert totals["eva_plan"] == rec.plans["eva_plan"]
        assert totals["hyper_plan"] == rec.plans["hyper_plan"]
        assert not {"flash_plans", "kda_plan", "stub_plan"} & set(totals)
    finally:
        rec.close()


def test_rt_train_stats_prints_each_plan_as_before():
    """``plans.DESCRIBE``'s four sentences are, to the letter, what
    ``cli.cmd_train`` printed for the same plans before they moved there
    (PR 60's commit, lines 1201-1244), both branches of each."""
    from ray_tpu.util import plans

    four = _four_plans()
    four["flash_plans"].append({
        "kind": "dkv", "seq_q": 4096, "seq_k": 4096, "head_dim": 64,
        "block_q": 512, "block_k": 1024, "live_steps": 16, "edge_steps": 0,
        "sub_block": (512, 512), "grid_steps": 32, "window": None})
    assert list(plans.DESCRIBE) == ["flash_plans", "kda_plan", "eva_plan",
                                    "hyper_plan", "sparse_plan"]
    assert [plans.DESCRIBE["flash_plans"](p) for p in four["flash_plans"]] == [
        "flash fwd s8192x8192 d128: tile 1024x1024, 30 of 64 grid steps "
        "live, 12 of them crossed by an edge, sub-tile 512x512, window 4096",
        "flash dkv s4096x4096 d64: tile 512x1024, 16 of 32 grid steps live"]
    kda_says = (
        "kda: 256 chunks of 64 in 4 segment(s), sub-block 16, 32 heads "
        "128x128, states at the chunks' starts 512 MiB a layer, a chunk's "
        "insides: ")
    assert plans.DESCRIBE["kda_plan"](four["kda_plan"]) == (
        kda_says + "a Pallas kernel pair (pallas_insides)")
    assert plans.DESCRIBE["kda_plan"]({**four["kda_plan"], "impl": "xla"}) \
        == kda_says + "XLA (xla)"
    assert plans.DESCRIBE["eva_plan"](four["eva_plan"]) == (
        "eva: 8 window(s) of 2048, 1024 chunks of 16 a row, a query sees at "
        "most 896 summaries, 32 heads of 128; score tiles (1024 rows x 1024 "
        "keys or 128 summaries) visited / needed 80 / 80 a head (pallas)")
    hyper_says = (
        "hyper-connections: a stream of 4 rows of 3584, 20 Sinkhorn "
        "iterations a half layer; the least passes over the stream move "
        "100.4 KB forward and 164.9 KB backward a token and half layer")
    assert plans.DESCRIBE["hyper_plan"](four["hyper_plan"]) == (
        hyper_says + ", the four calls' blocks 101.5 and 223.8 KB, 256 "
        "tokens a grid step (pallas; rows first [n, b, s, d]; coefficients "
        "[b s, 128] float32, a coefficient a lane)")
    from ray_tpu.ops import hyper
    assert plans.DESCRIBE["hyper_plan"](hyper.plan(4, 3584, 2, 20)) == (
        hyper_says + " (xla; rows first [n, b, s, d]; coefficients "
        "[n*n + 2n, b, s] float32, tokens on the lanes)")
    # the fifth, with the two keys PR 65 gave it: which form builds the
    # indexer loss's target, and the keys a grid step where it is the kernel
    from ray_tpu.ops import sparse_index
    sparse_says = (
        "sparse attention: an indexer of 16 heads of 64 keeps 2048 keys a "
        "query of 16384: 31458304 of 134225920 causal pairs a row of the "
        "batch (23.4%); scores 256 rows a block in 8 span(s), 256 MiB a "
        "block, the threshold in 16 passes of 3 counts, the choice 256 MiB "
        "a layer; the loss's target ")
    plan = sparse_index.plan(16384, 16, 64, 2048, target_tile=512)
    assert (plan["target_impl"], plan["target_tile"]) == ("pallas", 512)
    assert plans.DESCRIBE["sparse_plan"](plan) == (
        sparse_says + "a Pallas call of 512 keys a grid step (pallas)")
    plan = sparse_index.plan(16384, 16, 64, 2048)
    assert (plan["target_impl"], plan["target_tile"]) == ("xla", None)
    assert plans.DESCRIBE["sparse_plan"](plan) == sparse_says + "in XLA (xla)"


def test_train_stats_missing_snapshot_is_an_error(rt_cluster):
    """Grading a run that never recorded is a mistake worth failing:
    exactly one stderr line, exit 1, nothing on stdout."""
    import ray_tpu
    from ray_tpu.scripts import cli

    b = ray_tpu.global_worker()._require_backend()
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cli.cmd_train(Namespace(address=b.gcs_address, name=None,
                                     limit=8, json=False,
                                     train_cmd="stats"))
    assert rc == 1
    assert out.getvalue() == ""
    lines = [ln for ln in err.getvalue().splitlines() if ln]
    assert len(lines) == 1, lines
    assert "no train flight-recorder snapshot" in lines[0]
    assert "RT_TRAIN_RECORDER=0" in lines[0]


def test_api_train_and_cli_json(rt_cluster):
    import ray_tpu
    from ray_tpu.dashboard import start_dashboard
    from ray_tpu.scripts import cli

    rec = TR.TrainRecorder("surfaced", n_devices=1, peak_flops=1e9,
                           enabled=True)
    try:
        s1 = rec.record_launch(t_start=time.time() - 0.3,
                               data_wait_s=0.05, h2d_s=0.01,
                               dispatch_s=0.1, k=4, tokens=256,
                               batch_shape=(4, 2, 17), flops=5e7)
        rec.finalize_launch(s1, time.time(), {
            "moe_assignments": 4096, "moe_held": 128, "moe_kept": 120,
            "moe_dropped": 8, "moe_max_expert_rows": 40})
        rec.plans.update(_four_plans())
        rec.expert_placement = "expert"
        rec.collectives = {"all-gather": {"count": 2, "runs": 6,
                                          "bytes": 3_000_000_000}}
        rec.step_memory = {
            "peak_bytes": 15_794_000_000, "temp_bytes": 9_830_000_000,
            "argument_bytes": 5_110_000_000, "output_bytes": 5_110_000_000,
            "alias_bytes": 5_110_000_000}
        counts = rec.drain_now()
        assert counts["kv"] == 1, counts  # the @train/ snapshot landed
        assert counts["events"] >= 1, counts  # the timeline lane shipped

        port = start_dashboard()
        payload = _get_json(port, "/api/train")
        snaps = [s for s in payload["drivers"]
                 if s.get("name") == "surfaced"]
        assert snaps, payload
        snap = snaps[-1]
        assert snap["summary"]["window_launches"] == 1
        assert snap["launches"][-1]["done"]
        assert snap["launches"][-1]["phases_ms"]["data_wait"] == \
            pytest.approx(50.0, abs=1.0)

        b = ray_tpu.global_worker()._require_backend()
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_train(Namespace(address=b.gcs_address,
                                         name="surfaced", limit=8,
                                         json=True, train_cmd="stats"))
        assert rc == 0
        stats = json.loads(out.getvalue())
        assert stats and stats[-1]["summary"]["launches_total"] == 1
        assert stats[-1]["summary"]["steps_total"] == 4
        # human rendering smoke: the waterfall + overhead lines print
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_train(Namespace(address=b.gcs_address,
                                         name="surfaced", limit=8,
                                         json=False, train_cmd="stats"))
        text = out.getvalue()
        assert rc == 0
        assert "MFU waterfall" in text and "recorder overhead" in text
        assert "launch gap" in text
        assert "experts placed by expert" in text
        assert "all-gather 2 (6 runs, 3.00 GB)" in text
        assert ("memory: the compiled step peaks at 14.71 GiB a device "
                "(temporaries 9.15, arguments 4.76)") in text
        assert ("routing: 4096 assignments, 128 to experts held here "
                "(3.12%), 120 kept, 8 dropped beyond capacity, busiest "
                "expert's queue 40 rows") in text
        assert ("30 of 64 grid steps live, 12 of them crossed by an edge, "
                "sub-tile 512x512, window 4096") in text
        assert ("kda: 256 chunks of 64 in 4 segment(s), sub-block 16, 32 "
                "heads 128x128, states at the chunks' starts 512 MiB a "
                "layer, a chunk's insides: a Pallas kernel pair "
                "(pallas_insides)") in text
        assert ("eva: 8 window(s) of 2048, 1024 chunks of 16 a row, a query "
                "sees at most 896 summaries, 32 heads of 128; score tiles "
                "(1024 rows x 1024 keys or 128 summaries) visited / needed "
                "80 / 80 a head (pallas)") in text
        assert ("hyper-connections: a stream of 4 rows of 3584, 20 Sinkhorn "
                "iterations a half layer; the least passes over the stream "
                "move 100.4 KB forward and 164.9 KB backward a token and "
                "half layer, the four calls' blocks 101.5 and 223.8 KB, 256 "
                "tokens a grid step (pallas;") in text
        # the postmortem property: the snapshot SURVIVES close() —
        # `rt train stats` works after the driver is gone
        rec.close()
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = cli.cmd_train(Namespace(address=b.gcs_address,
                                         name="surfaced", limit=8,
                                         json=True, train_cmd="stats"))
        assert rc == 0
        assert json.loads(out.getvalue())[-1]["summary"][
            "launches_total"] == 1
    finally:
        rec.close()


_STEP_HLO = """\
HloModule jit_steps, is_scheduled=true

%add (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[] parameter(0)
  %y = bf16[] parameter(1)
  ROOT %add.0 = bf16[] add(%x, %y)
}

%all-reduce-scatter.1 (input: bf16[8,64,32]) -> bf16[2,64,32] {
  %input = bf16[8,64,32]{2,1,0} parameter(0)
  %all-reduce.7 = bf16[8,64,32]{2,1,0} all-reduce(%input), replica_groups={{0,1,2,3}}, to_apply=%add
  %offset = s32[] constant(0)
  ROOT %dynamic-slice.3 = bf16[2,64,32]{2,1,0} dynamic-slice(%all-reduce.7, %offset, %offset, %offset), dynamic_slice_sizes={2,64,32}
}

%layer (p: (s32[], bf16[8,64,32])) -> (s32[], bf16[8,64,32]) {
  %p = (s32[], bf16[8,64,32]{2,1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %rows = bf16[8,64,32]{2,1,0} get-tuple-element(%p), index=1
  %fusion.1 = bf16[2,64,32]{2,1,0} fusion(%rows), kind=kCustom, calls=%all-reduce-scatter.1
  %all-gather.4 = bf16[8,64,32]{2,1,0} all-gather(%fusion.1), dimensions={0}, metadata={op_name="jit(steps)/while/body/moe_combine/ecd,gec->gd/dot_general"}
  %all-reduce.9 = bf16[8,64,128]{2,1,0} all-reduce(%all-gather.4), replica_groups={{0,1,2,3}}, to_apply=%add, metadata={op_name="jit(steps)/while/body/moe_experts/ecd,edf->ecf/dot_general"}
  ROOT %next = (s32[], bf16[8,64,32]{2,1,0}) tuple(%i, %all-gather.4)
}

%more (p.1: (s32[], bf16[8,64,32])) -> pred[] {
  %p.1 = (s32[], bf16[8,64,32]{2,1,0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%p.1), index=0
  %three = s32[] constant(3)
  ROOT %lt = pred[] compare(%i.1, %three), direction=LT
}

ENTRY %main (a: bf16[8,64,32]) -> bf16[8,64,32] {
  %a = bf16[8,64,32]{2,1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], bf16[8,64,32]{2,1,0}) tuple(%zero, %a)
  %while.1 = (s32[], bf16[8,64,32]{2,1,0}) while(%init), condition=%more, body=%layer
  %out = bf16[8,64,32]{2,1,0} get-tuple-element(%while.1), index=1
  %permute-start = (bf16[8,64,32]{2,1,0}, bf16[8,64,32]{2,1,0}) collective-permute-start(%out), source_target_pairs={{0,1},{1,0}}
  ROOT %permute-done = bf16[8,64,32]{2,1,0} collective-permute-done(%permute-start)
}
"""


def test_collective_inventory_of_a_step_program():
    """What the recorder's ``collectives`` is read with: every collective
    that runs, the fused ones too, weighted by the loops round it; an
    all-reduce that is only sliced is the reduce-scatter it stands for,
    with the slice's bytes; an asynchronous pair counts once; each carries
    the product it completes, so a test can ask for the experts'."""
    import types

    from ray_tpu.util import hlo_copies

    program = types.SimpleNamespace(as_text=lambda: _STEP_HLO)
    found = {c["name"]: c for c in hlo_copies.collectives(program)}
    assert sorted(found) == ["all-gather.4", "all-reduce.7", "all-reduce.9",
                             "permute-done"]
    assert found["all-reduce.7"]["kind"] == "reduce-scatter"
    assert found["all-reduce.7"]["arrays"] == [("bf16", (2, 64, 32))]
    assert found["all-reduce.9"]["runs"] == 3
    assert "moe_experts" in found["all-reduce.9"]["op_name"]
    assert found["permute-done"]["kind"] == "collective-permute"
    rows = 8 * 64 * 32 * 2
    assert hlo_copies.collective_inventory(program) == {
        "reduce-scatter": {"count": 1, "runs": 3, "bytes": 3 * rows // 4},
        "all-gather": {"count": 1, "runs": 3, "bytes": 3 * rows},
        "all-reduce": {"count": 1, "runs": 3, "bytes": 3 * rows * 4},
        "collective-permute": {"count": 1, "runs": 1, "bytes": rows}}
