#!/usr/bin/env bash
# chaos_smoke.sh — one-shot chaos/recovery CI gate.
#
# Starts a real node daemon (`rt start --head`), arms a kill-worker chaos
# plan from the CLI, drives a workload THROUGH the injected kill (task
# retries recover it), verifies the injection is visible on the failure
# feed (`rt errors --origin chaos`), and requires `rt doctor` to exit 0
# once the recovery window passes — gating CI on recovery, not liveness.
#
# Also runnable as a slow-marked test: tests/test_zz_chaos_plane.py
# ::test_chaos_smoke_script.
set -euo pipefail

RT="python -m ray_tpu.scripts.cli"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
# an isolated session root so a developer's running cluster is untouched
export RT_SESSION_DIR_ROOT="${RT_SESSION_DIR_ROOT:-$(mktemp -d /tmp/rt_chaos_smoke.XXXXXX)}"

cleanup() { $RT stop --force >/dev/null 2>&1 || true; }
trap cleanup EXIT

echo "== pre-flight: rt lint (static invariants, ratcheted baseline) =="
# cheapest gate first: a concurrency/hot-path/purity violation fails in
# seconds here instead of minutes into the chaos legs
$RT lint

echo "== start head node =="
$RT start --head --num-cpus 4

echo "== arm chaos: kill the first task's worker, once =="
$RT chaos arm --site raylet.kill_worker --at 1 --max-fires 1 --seed 1
$RT chaos status
sleep 2  # the plan rides the next heartbeat reply to the raylet

echo "== run workload through the kill (retries must recover) =="
python - <<'EOF'
import ray_tpu

ray_tpu.init(address="auto")

@ray_tpu.remote(max_retries=3)
def f(x):
    return x * 2

got = ray_tpu.get([f.remote(i) for i in range(4)], timeout=180)
assert got == [0, 2, 4, 6], got
print("workload recovered:", got)
ray_tpu.shutdown()
EOF

echo "== injected fault visible + distinguishable on the feed =="
$RT chaos disarm
$RT errors --origin chaos | grep -q "chaos" \
    || { echo "FAIL: no chaos-origin event on the feed"; exit 1; }

echo "== doctor must return to exit 0 after the recovery window =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy:", [f["message"] for f in d["findings"]])
'

echo "== train leg: fused-K gang restart recovers from the last FENCED checkpoint =="
# Arm worker.kill against the gang worker's next_result entry: the actor
# dies while its training thread runs fused-K launches; JaxTrainer's
# drain sees the death and FailureConfig restarts the gang from the last
# checkpoint the async-save FENCE acked into the CheckpointManager (an
# unfinished orbax save must never be a recovery source — load_pytree on
# a partial dir would fail the resume). at=5 → 4 launches ack per
# attempt, so the run makes progress through repeated kills (the plan
# re-arms in each restarted worker process).
$RT chaos arm --site worker.kill --target next_result --at 5 --max-fires 1 --seed 5
sleep 2.5  # the plan rides the next heartbeat to raylet + live workers
python - <<'EOF'
import ray_tpu
from ray_tpu.train import (FailureConfig, FastPathConfig, JaxTrainer,
                           RunConfig, ScalingConfig)

ray_tpu.init(address="auto")


def loop(config):
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import train_step as ts
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.train.driver import StepDriver

    K, total, batch, seq = 4, 8, 2, 32
    cfg = llama.PRESETS["debug"]
    mesh = make_mesh(MeshConfig(), jax.devices())
    opt = ts.default_optimizer(total_steps=1000)
    params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg,
                                              mesh, opt)
    start = 0
    ck = train.get_checkpoint()
    if ck is not None:
        start = ck.to_dict()["launch"] + 1
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), params)
        # a partial (unfenced) orbax dir would fail right here — restoring
        # proves the manager only ever acked completed saves
        params = ck.load_pytree("state", abstract)
    driver = StepDriver(cfg, opt, mesh=mesh, steps_per_launch=K)
    rng = np.random.default_rng(start)
    for launch in range(start, total):
        batches = ({"tokens": rng.integers(
            0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)}
            for _ in range(K))
        params, opt_state, m = driver.run(params, opt_state, batches)
        ckpt = Checkpoint.from_dict({"launch": launch})
        ckpt.save_pytree(driver.state[0], "state", blocking=False)
        train.report({"launch": launch, "loss": m["loss"][-1],
                      "resumed_from": start}, checkpoint=ckpt)
    train.report({"launches_done": total, "resumed_from": start,
                  "complete": True})


result = JaxTrainer(
    loop,
    scaling_config=ScalingConfig(num_workers=1, cpus_per_worker=1),
    run_config=RunConfig(
        name="chaos-train-fast",
        failure_config=FailureConfig(max_failures=2),
        fast_path=FastPathConfig(steps_per_launch=4)),
).fit()
assert result.error is None, result.error
assert result.metrics.get("complete") is True, result.metrics
assert result.metrics["resumed_from"] > 0, \
    f"no restart-resume happened: {result.metrics}"
print(f"train leg OK: fused-K run completed through the kills, "
      f"final attempt resumed at launch {result.metrics['resumed_from']} "
      f"from a fenced checkpoint")
ray_tpu.shutdown()
EOF
$RT chaos disarm
$RT errors --origin chaos | grep -q "worker.kill" \
    || { echo "FAIL: train-leg worker.kill not on the chaos feed"; exit 1; }

echo "== doctor must exit 0 after the train leg drains =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after train leg")
'

echo "== overload leg: probe under a deep flood (fair dispatch) =="
# Flood one scheduling class, then submit a 1-task probe in ANOTHER class:
# round-robin dispatch must answer it in < 1 s instead of making it wait
# out the whole backlog (what one FIFO queue made it do).
FLOOD="${RT_SMOKE_FLOOD:-5000}"
T0=$(python -c 'import time; print(time.time())')
python - "$FLOOD" <<'EOF'
import sys
import time

import ray_tpu

flood_n = int(sys.argv[1])
ray_tpu.init(address="auto")

@ray_tpu.remote
def bulk():
    return 0

@ray_tpu.remote
def probe_task():
    return 42

refs = [bulk.remote() for _ in range(flood_n)]
t0 = time.perf_counter()
assert ray_tpu.get(probe_task.remote(), timeout=60) == 42
probe_s = time.perf_counter() - t0
print(f"probe under {flood_n}-deep flood: {probe_s * 1000:.0f} ms")
assert probe_s < 1.0, f"probe took {probe_s:.2f}s behind {flood_n} tasks"
ray_tpu.get(refs, timeout=900)  # full drain before the health checks
ray_tpu.shutdown()
EOF

echo "== overload must leave no organic failures on the feed =="
# scoped to the overload leg: the earlier kill-worker leg legitimately
# left its (chaos-caused but organically-stamped) worker_crash residue
$RT errors --origin organic --json | python -c "
import json, sys
t0 = float('$T0')
events = [e for e in json.load(sys.stdin)
          if e.get('last_t', e.get('t', 0)) >= t0]
assert events == [], f'organic failures under overload: {events}'
print('feed clean: no organic failures from the overload leg')
"
$RT doctor --window 5 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after overload")
'

echo "== serve leg: 2-replica app survives an injected replica kill =="
# Deploy a 2-replica app, drive HTTP traffic, arm worker.kill against the
# replica method, assert traffic continues through the failover, the
# serve error-rate counter moves, and the controller restarts the
# replica (visible on `rt serve status`).
python - <<'EOF'
import json
import subprocess
import sys
import time
import urllib.request

import ray_tpu
from ray_tpu import serve

RT = [sys.executable, "-m", "ray_tpu.scripts.cli"]
ray_tpu.init(address="auto")

@serve.deployment(num_replicas=2, max_ongoing_requests=8,
                  health_check_period_s=0.5)
class Smoke:
    def __call__(self, request):
        return {"ok": True}

serve.run(Smoke.bind(), name="smoke", route_prefix="/smoke")
port = serve.http_port()
base = f"http://127.0.0.1:{port}/smoke/"

def hit(timeout=30):
    with urllib.request.urlopen(base, timeout=timeout) as r:
        return r.status

for _ in range(10):
    assert hit() == 200
print("serve baseline: 10/10 OK on port", port)

# arm: kill the worker at its next replica handle_request entry, once
subprocess.run(RT + ["chaos", "arm", "--site", "worker.kill",
                     "--target", "handle_request", "--at", "1",
                     "--max-fires", "1", "--seed", "7"], check=True)
time.sleep(2.5)  # plan rides the next heartbeat to raylet + live workers
try:
    code = hit()
    print("request through the kill:", code)
except Exception as e:  # noqa: BLE001 — the kill may surface here
    print("request through the kill raised:", type(e).__name__)
subprocess.run(RT + ["chaos", "disarm"], check=True)
time.sleep(2.5)  # disarm rides the heartbeat too

ok = 0
for _ in range(15):
    for attempt in range(3):
        try:
            if hit() == 200:
                ok += 1
                break
        except Exception:  # noqa: BLE001 — retry through the failover
            time.sleep(0.5)
assert ok >= 14, f"traffic did not continue: {ok}/15"
print(f"traffic continued: {ok}/15 OK through the failover")

# the serve error-rate counter moved (handle counted the dead replica)
proxy = ray_tpu.get_actor("RT_SERVE_PROXY")
ray_tpu.get(proxy.flush_metrics.remote())
from ray_tpu.util.metrics import metrics_text
text = metrics_text()
err_lines = [ln for ln in text.splitlines()
             if ln.startswith("rt_serve_errors_total")
             and "replica_died" in ln]
assert err_lines and any(float(ln.rsplit(" ", 1)[1]) > 0
                         for ln in err_lines), \
    "rt_serve_errors_total{kind=replica_died} did not move"
print("error counter moved:", err_lines[0])

# recovery: the controller restarts the killed replica
deadline = time.time() + 60
while time.time() < deadline:
    deps = serve.status()["smoke"]["deployments"]["Smoke"]
    if deps["replicas"] == 2:
        break
    time.sleep(0.5)
assert deps["replicas"] == 2, deps
print("replica set recovered: 2/2")
ray_tpu.shutdown()
EOF

echo "== recovery visible on rt serve status =="
$RT serve status | tee /dev/stderr | grep -q "replicas 2/2" \
    || { echo "FAIL: rt serve status does not show recovery"; exit 1; }
$RT serve shutdown

echo "== stream leg: pushed stream falls back to pull under rpc.drop =="
# Arm rpc.drop against the live push channel (target stream_push): the
# channel breaks mid-stream, the consumer transparently falls back to
# the pull path, and the stream completes token-exact (the push
# binding's replay buffer + resume_pull hand the tail over).
python - <<'EOF'
import json
import os
import time

# the consumer (this driver) is the process the push site fires in:
# arm from env so connect() also starts the chaos-event drain loop
os.environ["RT_CHAOS_PLAN_JSON"] = json.dumps({
    "seed": 3, "faults": [{"site": "rpc.drop", "target": "stream_push",
                           "at": 25, "max_fires": 1}]})
import ray_tpu
from ray_tpu import serve

ray_tpu.init(address="auto")

@serve.deployment
class TokenStream:
    async def __call__(self, n: int):
        import asyncio

        async def gen():
            for i in range(n):
                await asyncio.sleep(0.01)
                yield i

        return gen()

serve.run(TokenStream.bind(), name="stream-smoke",
          route_prefix="/streamsmoke")
h = serve.get_deployment_handle("TokenStream", "stream-smoke")
assert list(h.remote(3).result()) == [0, 1, 2]  # warm: replica + conn
gen = h.remote(60).result()
toks = list(gen)
assert toks == list(range(60)), f"token drift through fallback: {toks[:10]}"
assert gen._transport == "fallback", gen._transport
print(f"stream leg: 60/60 tokens exact through '{gen._transport}' "
      f"({gen._rpcs} rpcs)")
time.sleep(2.5)  # the driver's chaos drain loop ships the buffered event
serve.delete("stream-smoke")
ray_tpu.shutdown()
EOF

$RT errors --origin chaos | grep -q "rpc.drop" \
    || { echo "FAIL: stream-leg rpc.drop not on the chaos feed"; exit 1; }

echo "== doctor must exit 0 after the stream leg drains =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after stream leg")
'

echo "== kv-cache leg: kill the warm replica — cold serves exact, hit-rate recovers =="
# Warm one replica's prefix cache with shared-prefix traffic (affinity
# routing concentrates it), arm worker.kill against handle_request so
# the NEXT shared-prefix request kills exactly the warm replica, then
# assert: traffic continues on the cold replica with byte-identical
# tokens (misses counted — a cold cache must never mean wrong output),
# and after the controller restarts the replica the hit-rate recovers.
python - <<'EOF'
import subprocess
import sys
import time

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm import continuous_llm_app

RT = [sys.executable, "-m", "ray_tpu.scripts.cli"]
ray_tpu.init(address="auto")

app = continuous_llm_app(
    "debug", max_slots=4, max_len=192, decode_stride=4, name="KV",
    num_replicas=2, kv_cache_bytes=32 << 20)
serve.run(app, name="kv-smoke", route_prefix="/kvsmoke")
h = serve.get_deployment_handle("KV", "kv-smoke")

PROBE = {"tokens": list(range(1, 129)) + [200, 201, 202, 203],
         "max_new_tokens": 8}


def probe(retries=1):
    last = None
    for _ in range(retries):
        try:
            return list(h.remote(dict(PROBE)).result())
        except Exception as e:  # noqa: BLE001 — retry through failover
            last = e
            time.sleep(0.5)
    raise last


def kv_stats():
    d = serve.detailed_status()["applications"]["kv-smoke"]
    return d["deployments"]["KV"]["stats"]


def wait_kv(cond, what, timeout=45.0):
    # the controller's stats window is a polled snapshot — give the
    # poll cadence time to surface the engines' monotonic counters
    deadline = time.time() + timeout
    while time.time() < deadline:
        st = kv_stats()
        if cond(st):
            return st
        time.sleep(0.5)
    raise AssertionError(f"{what}: {kv_stats()}")


ref = probe()
assert len(ref) == 8, ref
for _ in range(4):  # warm + concentrate: residency biases the router
    assert probe() == ref, "warm-path token drift"
st = wait_kv(lambda s: s.get("kv_hits", 0) > 0, "cache never warmed")
print(f"warm: hits {st['kv_hits']}, misses {st['kv_misses']}, "
      f"hit-rate {st['kv_hit_rate']}")

# the next shared-prefix request routes to the warm replica (affinity)
# and dies at handle_request entry
subprocess.run(RT + ["chaos", "arm", "--site", "worker.kill",
                     "--target", "handle_request", "--at", "1",
                     "--max-fires", "1", "--seed", "23"], check=True)
time.sleep(2.5)  # plan rides the heartbeat to raylet + live workers
try:
    probe()
    print("kill-probe: reply arrived (kill may land on teardown)")
except Exception as e:  # noqa: BLE001 — the kill surfaces here
    print("kill-probe raised:", type(e).__name__)
subprocess.run(RT + ["chaos", "disarm"], check=True)
time.sleep(2.5)  # disarm rides the heartbeat too

# traffic continues on the cold replica: token-exact (greedy decode on
# identical seed-0 params — a cold cache means misses, never drift)
for i in range(6):
    assert probe(retries=6) == ref, f"cold-replica token drift at {i}"
st = wait_kv(lambda s: s.get("kv_misses", 0) > 0,
             "cold replica counted no misses")
print(f"traffic continued cold: 6/6 token-exact "
      f"(misses now {st['kv_misses']})")

# the controller restarts the killed replica; its re-warmed cache +
# the survivor's make the hit-rate recover
deadline = time.time() + 60
while time.time() < deadline:
    deps = serve.status()["kv-smoke"]["deployments"]["KV"]
    if deps["replicas"] == 2:
        break
    time.sleep(0.5)
assert deps["replicas"] == 2, deps
before = wait_kv(lambda s: s.get("kv_hits", 0) > 0,
                 "no settled post-restart snapshot")["kv_hits"]
for _ in range(6):
    assert probe(retries=6) == ref, "post-restart token drift"
st = wait_kv(lambda s: s.get("kv_hits", 0) >= before + 4,
             f"hit-rate did not recover past {before}")
print(f"recovered: 2/2 replicas, hits {before} -> {st['kv_hits']}, "
      f"hit-rate {st['kv_hit_rate']}")
serve.delete("kv-smoke")
ray_tpu.shutdown()
EOF
$RT errors --origin chaos | grep -q "worker.kill" \
    || { echo "FAIL: kv-leg worker.kill not on the chaos feed"; exit 1; }

echo "== doctor must exit 0 after the kv-cache leg drains =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after kv-cache leg")
'

echo "== rlhf leg: weight sync survives rpc.drop on the oid-frame fetch =="
# One full generate -> train -> weight-sync iteration with rpc.drop armed
# against the push channel the generator fetches the shipped weights
# over: the fetch must fall back to the reclaim RPC leaf-exact, the
# engine swap must still land, and the iteration must complete.
$RT chaos arm --site rpc.drop --target stream_push --at 1 --max-fires 1 --seed 11
sleep 2.5  # plan rides the heartbeat to raylet + live workers
python - <<'EOF'
import ray_tpu
from ray_tpu.rl.rlhf import RLHFPipeline

ray_tpu.init(address="auto")
p = RLHFPipeline(preset="debug", num_prompts=3, prompt_len=6,
                 max_new_tokens=8, max_slots=2, decode_stride=2)
try:
    r = p.run_iteration()
    print(f"rlhf iteration through the drop: reward={r['reward_mean']:.4f} "
          f"sync_transport={r['sync_transport']} "
          f"sync_bytes={r['sync_bytes']}")
    assert r["tokens_generated"] == 3 * 8, r
    assert r["sync_transport"] == "fallback", \
        f"expected the armed drop to force the pull fallback: {r}"
    eng = ray_tpu.get(p.group["generator"].engine_stats.remote())
    assert eng["weight_swaps"] == 1, eng
    print("rlhf leg OK: weights landed leaf-exact through the fallback, "
          "drain-barrier swap applied")
finally:
    p.shutdown()
    ray_tpu.shutdown()
EOF
$RT chaos disarm
$RT errors --origin chaos | grep -q "rpc.drop" \
    || { echo "FAIL: rlhf-leg rpc.drop not on the chaos feed"; exit 1; }

echo "== doctor must exit 0 after the rlhf leg drains =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after rlhf leg")
'

echo "== placement leg: spillback receipts + cross-node balance under rpc.delay =="
# A second 1-CPU host joins; the whole flood submits to the 4-CPU head, so
# the backlog is one-sided and the spill heuristic must shed it. rpc.delay
# armed against the raylet's submit_task forwards stretches the hand-offs,
# keeping the skew visible across several 1 s balance ticks.
GCS_ADDR=$(python - <<'EOF'
from ray_tpu.scripts.cli import _resolve_gcs
print(_resolve_gcs(None))
EOF
)
$RT start --address "$GCS_ADDR" --num-cpus 1
$RT chaos arm --site rpc.delay --target submit_task --after 0 \
    --max-fires 30 --delay 0.05 --seed 7
sleep 2  # plan rides the heartbeat to the raylets
python - <<'EOF'
import time

import ray_tpu

ray_tpu.init(address="auto")
backend = ray_tpu.global_worker()._require_backend()


def balance():
    return backend.io.run(backend._gcs.call("sched_balance", {"limit": 120}))


@ray_tpu.remote
def spin():
    time.sleep(0.15)
    return 0


pending = [spin.remote() for _ in range(120)]
peak = 0.0
deadline = time.time() + 120
while pending and time.time() < deadline:
    _, pending = ray_tpu.wait(pending, num_returns=len(pending), timeout=1.0)
    peak = max(peak, float(balance()["cov"] or 0.0))
assert not pending, f"flood did not drain: {len(pending)} left"
assert peak > 0.3, f"imbalance gauge never moved (peak cov {peak})"
# recovery: once drained, the balance tick must come back down
cov = peak
for _ in range(12):
    cov = float(balance()["cov"] or 0.0)
    if cov < 0.3:
        break
    time.sleep(1.0)
assert cov < 0.3, f"imbalance did not recover after the drain: cov {cov}"
sp = backend.io.run(backend._gcs.call(
    "list_placement_events", {"kind": "spillback", "limit": 100}))
assert sp, "no spillback receipts after the skewed flood"
hops = sum(int(e.get("count", 1)) for e in sp)
assert all(e.get("candidates") for e in sp), "receipt without candidates"
print(f"placement leg: peak cov {peak:.2f} recovered to {cov:.2f}, "
      f"{hops} spillback hop(s) across {len(sp)} receipt(s)")
ray_tpu.shutdown()
EOF
$RT chaos disarm
$RT sched decisions --kind spillback | grep -q "spillback" \
    || { echo "FAIL: rt sched decisions --kind spillback is empty"; exit 1; }
$RT sched balance >/dev/null \
    || { echo "FAIL: rt sched balance unreachable"; exit 1; }

echo "== doctor must exit 0 after the placement leg drains =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after placement leg")
'
echo "== engine leg: prefill burst dips SLO attainment on the flight recorder, then recovers =="
# The fault here is workload-shaped, not injected: a dense long-prompt
# burst on a colocated 2-slot engine starves the decode launches. The
# flight recorder must show it (tick-gap spike + TPOT attainment dip in
# rt engine stats) and show the recovery, with the doctor back to exit 0
# once the burst drains.
python - <<'EOF'
import threading
import time

import numpy as np
import jax

import ray_tpu
from ray_tpu.models import llama, serving

ray_tpu.init(address="auto")
cfg = llama.PRESETS["debug"]
params = llama.init_params(jax.random.key(0), cfg)
eng = serving.ContinuousEngine(params, cfg, max_slots=2, max_len=96,
                               decode_stride=4, warmup=True,
                               kv_cache_bytes=0, kv_label="chaos-engine")
rec = eng._recorder
assert rec.enabled, "flight recorder disabled (RT_ENGINE_RECORDER=0?)"

short = (np.arange(16) % cfg.vocab_size).astype(np.int32)
long_p = (np.arange(80) % cfg.vocab_size).astype(np.int32)


def run(prompt, n):
    q = eng.submit_stream(prompt, n)

    def drain():
        while q.get() is not None:
            pass

    t = threading.Thread(target=drain, daemon=True)
    t.start()
    return t


# warm both prompt-length shapes so XLA compiles stay out of the windows
for warm in (short, long_p):
    run(warm, 4).join(60)
time.sleep(0.2)

# steady leg: short decode traffic only
t0 = time.time()
threads = []
for i in range(10):
    threads.append(run(short, 16))
    time.sleep(0.06)
for t in threads:
    t.join(60)
t1 = time.time()
steady = rec.window_summary(t0, t1)
assert steady["window_completed"] >= 8, steady
rec.set_slo(ttft_slo_s=max(steady["ttft_p99_s"] * 1.5, 0.020),
            tpot_slo_s=max(steady["tpot_p99_s"] * 1.25, 0.0005))
steady = rec.window_summary(t0, t1)
assert steady["tpot_attainment"] == 1.0, steady

# burst leg: the whole long-prompt queue lands at once on live short
# decodes — staggering would let this (tiny) engine drain each long
# before the next arrives and never show the stall
threads = [run(short, 16) for _ in range(4)]
threads += [run(long_p, 4) for _ in range(18)]
threads += [run(short, 16) for _ in range(4)]
for t in threads:
    t.join(60)
time.sleep(0.2)
t2 = time.time()
burst = rec.window_summary(t1, t2)
spike = burst["tick_gap_p99_s"] / max(steady["tick_gap_p99_s"], 1e-6)
assert spike > 3.0, (steady, burst)
assert burst["tpot_attainment"] < 0.9, burst

# recovery leg: steady traffic again — attainment must come back
t2b = time.time()
threads = []
for i in range(10):
    threads.append(run(short, 16))
    time.sleep(0.06)
for t in threads:
    t.join(60)
t3 = time.time()
recovery = rec.window_summary(t2b, t3)
assert recovery["tpot_attainment"] >= 0.9, recovery
assert recovery["tpot_attainment"] > burst["tpot_attainment"], (
    burst, recovery)

counts = rec.drain_now()
assert counts["kv"] >= 1, counts  # snapshot visible to rt engine / doctor
print(f"engine leg: gap spike {spike:.1f}x, TPOT attainment "
      f"{steady['tpot_attainment']} -> {burst['tpot_attainment']} -> "
      f"{recovery['tpot_attainment']}")
# deliberately NO eng.shutdown(): close() drops the @engine/ KV snapshot,
# and the next check reads it postmortem through the GCS — the whole
# point of the no-driver-attach path
ray_tpu.shutdown()
EOF

echo "== burst visible + recovered on rt engine stats =="
$RT engine stats --json | python -c '
import json, sys
snaps = json.load(sys.stdin)
eng = [s for s in snaps if s.get("name") == "chaos-engine"]
assert eng, [s.get("name") for s in snaps]
s = eng[0]["summary"]
assert s["ticks_total"] > 0 and s["requests_total"] > 0, s
assert s.get("window_completed", 0) > 0 and "tpot_attainment" in s, s
print("rt engine stats sees the chaos-engine snapshot")
'

echo "== doctor must exit 0 after the engine leg drains =="
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after engine leg")
'

echo "== rlhf-obs leg: kill the generator mid-iteration — recorder stamps the interrupted phase, restart gap, and staleness =="
# Iteration 1 completes clean (learner ships v1, generator swaps to it).
# Then worker.kill lands on the generator's next generate entry: the
# iteration dies mid-phase and the flight recorder stamps
# phase="generate" interrupted. max_restarts=1 rebuilds the generator on
# the SEED weights (decoded version back to 0), so iteration 3's
# staleness stamp must read 1 — the restart silently regressed the
# decode weights, and only the recorder makes that visible. The driver
# exits WITHOUT shutdown so the @rlhf/ snapshot survives for the
# postmortem `rt rlhf stats` read below (no-driver-attach path).
python - <<'EOF'
import subprocess
import sys
import time

import ray_tpu
from ray_tpu.rl.rlhf import RLHFPipeline

RT = [sys.executable, "-m", "ray_tpu.scripts.cli"]
ray_tpu.init(address="auto")
p = RLHFPipeline(preset="debug", num_prompts=3, prompt_len=6,
                 max_new_tokens=8, max_slots=2, decode_stride=2)
r1 = p.run_iteration()
assert r1["staleness"] == 0 and r1["weights_version"] == 1, r1

# arm AFTER the clean iteration: the next generate entry dies
subprocess.run(RT + ["chaos", "arm", "--site", "worker.kill",
                     "--target", "generate", "--at", "1",
                     "--max-fires", "1", "--seed", "19"], check=True)
time.sleep(2.5)  # plan rides the heartbeat to raylet + live workers
try:
    p.run_iteration()
    raise SystemExit("FAIL: armed kill did not interrupt the iteration")
except Exception as e:  # noqa: BLE001 — the kill surfaces here
    print("iteration 2 interrupted:", type(e).__name__)
subprocess.run(RT + ["chaos", "disarm"], check=True)
time.sleep(2.5)  # disarm rides the heartbeat too

r3 = p.run_iteration()  # restarted generator decodes the SEED weights
assert r3["staleness"] == 1, \
    f"restart weight regression not stamped: {r3['staleness']}"
assert r3["decoded_version"] == 0 and r3["weights_version"] == 2, r3
summ = p.stats()["recorder"]
assert summ["interrupted_total"] == 1, summ
assert summ["interrupted_last"]["phase"] == "generate", summ
assert summ["restart_gaps_s"] and summ["restart_gaps_s"][-1] > 0, summ
counts = p.recorder.drain_now()
assert counts["kv"] >= 1, counts
print(f"rlhf-obs leg: interrupted in 'generate', restart gap "
      f"{summ['restart_gaps_s'][-1]:.2f}s, staleness {r3['staleness']} "
      f"after the seed-weight restart")
# deliberately NO p.shutdown(): close() drops the @rlhf/ KV snapshot,
# and the next check reads it postmortem through the GCS
ray_tpu.shutdown()
EOF
$RT errors --origin chaos | grep -q "worker.kill" \
    || { echo "FAIL: rlhf-obs worker.kill not on the chaos feed"; exit 1; }

echo "== interrupt + restart gap visible postmortem on rt rlhf stats =="
$RT rlhf stats --json | python -c '
import json, sys
snaps = json.load(sys.stdin)
assert snaps, "no @rlhf/ snapshot survived the driver exit"
s = snaps[-1]["summary"]
assert s["interrupted_total"] == 1, s
assert s["interrupted_last"]["phase"] == "generate", s
assert s["restart_gaps_s"], s
assert s["staleness"]["last"] == 1, s["staleness"]
states = [r["state"] for r in snaps[-1]["iterations"]]
assert "interrupted" in states and states[-1] == "ok", states
print("rt rlhf stats sees the interrupt, restart gap, and staleness")
'

echo "== doctor must exit 0 after the rlhf-obs leg drains =="
# the interrupt WAS recovered (a later iteration stamped the restart
# gap), so the unrecovered-interrupt finding must NOT fire
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after rlhf-obs leg")
'

echo "== train-obs leg: throttle the loader mid-run — recorder stamps the data-wait spike and the recovery =="
# The StepDriver runs in-process against the live cluster: its flight
# recorder's drain thread pushes @train/ KV snapshots through the GCS,
# so the `rt train stats` check below reads the run POSTMORTEM with no
# driver attach. The loader reads RT_TRAIN_LOADER_THROTTLE_S per batch,
# so starving it mid-run is a plain env flip between driver.run calls.
python - <<'EOF'
import os
import time

import numpy as np

import jax

import ray_tpu
from ray_tpu.models import llama
from ray_tpu.parallel import train_step as ts
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.train.driver import StepDriver

ray_tpu.init(address="auto")
cfg = llama.PRESETS["debug"]
K, BATCH, SEQ = 4, 2, min(16, cfg.max_seq_len)
mesh = make_mesh(MeshConfig(), jax.devices())
optimizer = ts.default_optimizer(total_steps=1000)
params, opt_state = ts.init_sharded_state(jax.random.key(0), cfg, mesh,
                                          optimizer)
driver = StepDriver(cfg, optimizer, mesh=mesh, steps_per_launch=K)
rec = driver.recorder
assert rec is not None and rec.enabled, "train recorder must be live"
rng = np.random.default_rng(7)


def batches(n):
    for _ in range(n):
        thr = float(os.environ.get("RT_TRAIN_LOADER_THROTTLE_S", "0") or 0)
        if thr > 0:
            time.sleep(thr)  # the env-throttled loader
        yield {"tokens": rng.integers(
            0, cfg.vocab_size, (BATCH, SEQ + 1)).astype(np.int32)}


def settle(timeout=10.0):
    # wait for the done-hook watcher so the window carve sees every
    # launch of the leg it just timed
    t_end = time.perf_counter() + timeout
    while time.perf_counter() < t_end:
        if not rec.summary().get("in_flight"):
            return
        time.sleep(0.01)


def leg(n_launches):
    global params, opt_state
    t0 = time.time()
    params, opt_state, _m = driver.run(params, opt_state,
                                       batches(n_launches * K))
    settle()
    return rec.window_summary(t0, time.time())


leg(2)  # warmup: compile + post-update leaf types
steady = leg(6)
os.environ["RT_TRAIN_LOADER_THROTTLE_S"] = "0.05"
try:
    starved = leg(6)
finally:
    os.environ.pop("RT_TRAIN_LOADER_THROTTLE_S", None)
recovered = leg(6)

sdw = steady.get("data_wait_frac", 0.0)
vdw = starved.get("data_wait_frac", 0.0)
rdw = recovered.get("data_wait_frac", 0.0)
spike = vdw / max(sdw, 0.005)
assert spike > 3.0, (sdw, vdw)
assert rdw < vdw / 3.0, (vdw, rdw)  # throttle lifted -> share recovers
counts = rec.drain_now()
assert counts["kv"] >= 1, counts  # snapshot visible to rt train / doctor
print(f"train-obs leg: data_wait share {sdw:.3f} -> {vdw:.3f} "
      f"({spike:.1f}x spike) -> {rdw:.3f} recovered")
# deliberately NO teardown: the @train/ KV snapshot survives the driver
# and the next check reads it postmortem through the GCS (the whole
# point of the no-driver-attach path)
ray_tpu.shutdown()
EOF

echo "== starvation run visible postmortem on rt train stats =="
$RT train stats --json | python -c '
import json, sys
snaps = json.load(sys.stdin)
assert snaps, "no @train/ snapshot survived the driver exit"
s = snaps[-1]["summary"]
assert s["launches_total"] >= 18, s
assert s.get("dry_resets", 0) > 0, s  # the starved leg went loader-dry
assert s.get("phase_sum_ratio", 0) > 0.9, s
assert s.get("overhead_frac", 1.0) < 0.02, s
launches = snaps[-1].get("launches") or []
assert launches and all(l.get("done") for l in launches), launches
print("rt train stats sees the run postmortem: %d launches, "
      "%d dry resets, phase coverage %.3f"
      % (s["launches_total"], s["dry_resets"], s["phase_sum_ratio"]))
'

echo "== doctor must exit 0 after the train-obs leg drains =="
# the starved leg may leave a data-wait WARN on the postmortem snapshot
# — WARNs are advisory and must not flip the exit code
sleep 3
$RT doctor --window 2 --json | python -c '
import json, sys
d = json.load(sys.stdin)
assert d["exit_code"] == 0 and d["healthy"], d["findings"]
print("doctor healthy after train-obs leg")
'

echo "chaos smoke OK"
