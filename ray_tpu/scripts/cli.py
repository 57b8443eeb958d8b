"""``rt`` — the cluster lifecycle CLI.

Reference analog: ``python/ray/scripts/scripts.py`` (``ray start/stop/status``)
— minus the cloud-provider plumbing (autoscaler handles provisioning).
Invoked as ``python -m ray_tpu.scripts.cli <cmd>`` (no pip install step).

  rt start --head [--port N] [--num-cpus N] [--num-tpus N]
  rt start --address=<gcs-host:port>      # join as a worker host
  rt status
  rt stop
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from ray_tpu._private.config import get_config
from ray_tpu.cluster import node_main
from ray_tpu.util import plans


def _list_node_states() -> List[Dict]:
    out = []
    d = node_main.state_dir()
    try:
        names = os.listdir(d)
    except FileNotFoundError:
        return out
    for name in names:
        try:
            with open(os.path.join(d, name)) as f:
                out.append(json.load(f))
        except (ValueError, FileNotFoundError):
            pass
    return out


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
        return True
    except (ProcessLookupError, PermissionError):
        return False


def cmd_start(args: argparse.Namespace) -> int:
    daemon_args = [sys.executable, "-m", "ray_tpu.cluster.node_main",
                   "--host", args.host]
    if args.head:
        daemon_args += ["--head", "--port", str(args.port)]
        if args.session_name:
            daemon_args += ["--session-name", args.session_name]
    else:
        daemon_args += ["--address", args.address]
    if args.num_cpus is not None:
        daemon_args += ["--num-cpus", str(args.num_cpus)]
    if args.num_tpus is not None:
        daemon_args += ["--num-tpus", str(args.num_tpus)]
    if args.resources:
        daemon_args += ["--resources", args.resources]

    log_dir = os.path.join(get_config().session_dir_root, "logs")
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(log_dir, f"node-{int(time.time())}-{os.getpid()}.log")
    log_file = open(log_path, "ab")
    proc = subprocess.Popen(
        daemon_args, stdout=subprocess.PIPE, stderr=log_file,
        start_new_session=True)  # detach: survives this CLI process
    log_file.close()

    # Block until the daemon prints its ready line (or dies) — readline
    # gated by select so --timeout holds even if the daemon never writes.
    import select

    deadline = time.monotonic() + args.timeout
    state = None
    buf = b""
    while time.monotonic() < deadline:
        ready, _, _ = select.select([proc.stdout], [], [],
                                    max(0.0, deadline - time.monotonic()))
        if not ready:
            break
        chunk = os.read(proc.stdout.fileno(), 4096)
        if not chunk:
            break
        buf += chunk
        # only parse COMPLETE lines — the ready json may straddle a read
        complete, _, buf = buf.rpartition(b"\n")
        for line in complete.decode(errors="replace").splitlines():
            if line.startswith("RT_NODE_READY "):
                state = json.loads(line[len("RT_NODE_READY "):])
                break
        if state is not None:
            break
    if state is None:
        rc = proc.poll()
        if rc is None:
            proc.terminate()  # half-started daemon: don't leave it dangling
        print(f"rt start: node daemon failed to come up "
              f"(rc={rc}); log: {log_path}", file=sys.stderr)
        return 1
    role = "head" if state["head"] else "worker"
    print(f"started {role} node {state['node_id'][:8]} pid={state['pid']}")
    print(f"  gcs_address:    {state['gcs_address']}")
    print(f"  raylet_address: {state['raylet_address']}")
    print(f"  session:        {state['session_name']}")
    if state["head"]:
        print(f"\njoin another host with:\n"
              f"  rt start --address={state['gcs_address']}\n"
              f"attach a driver with:\n"
              f"  ray_tpu.init(address=\"{state['gcs_address']}\")")
    return 0


def cmd_stop(args: argparse.Namespace) -> int:
    states = _list_node_states()
    if not states:
        print("no running nodes found")
        return 0
    # workers first, head last — workers need the GCS to deregister
    states.sort(key=lambda s: s["head"])
    stopped = 0
    for st in states:
        pid = st["pid"]
        if not _pid_alive(pid):
            _cleanup_state(st)
            continue
        try:
            os.kill(pid, signal.SIGTERM)
            stopped += 1
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + args.timeout
    while time.monotonic() < deadline:
        if not any(_pid_alive(s["pid"]) for s in states):
            break
        time.sleep(0.1)
    for st in states:
        if args.force and _pid_alive(st["pid"]):
            try:
                os.kill(st["pid"], signal.SIGKILL)
            except ProcessLookupError:
                pass
        _cleanup_state(st)
    print(f"stopped {stopped} node(s)")
    return 0


def _cleanup_state(st: Dict) -> None:
    for path in (os.path.join(node_main.state_dir(), f"{st['node_id']}.json"),):
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
    if st.get("head"):
        latest = node_main.read_session_latest()
        if latest and latest.get("node_id") == st["node_id"]:
            try:
                os.unlink(node_main.session_latest_path())
            except FileNotFoundError:
                pass


def _gcs_call(address: str, method: str, payload: Dict) -> Dict:
    from ray_tpu.cluster.rpc import RpcClient

    async def _go():
        client = RpcClient(address, peer_id="rt-cli")
        await client.connect()
        try:
            return await client.call(method, payload, timeout=10.0)
        finally:
            await client.close()

    return asyncio.run(_go())


def _resolve_gcs(address: Optional[str]) -> Optional[str]:
    if address and address not in ("auto",):
        return address
    latest = node_main.read_session_latest()
    return latest["gcs_address"] if latest else None


def cmd_status(args: argparse.Namespace) -> int:
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("no running cluster found (no session_latest.json; "
              "pass --address)", file=sys.stderr)
        return 1
    try:
        nodes = _gcs_call(gcs, "list_nodes", {})
    except Exception as e:
        print(f"cannot reach GCS at {gcs}: {e!r}", file=sys.stderr)
        return 1
    print(f"cluster at {gcs}: {sum(n['alive'] for n in nodes)} alive / "
          f"{len(nodes)} total nodes")
    for n in nodes:
        state = "ALIVE" if n["alive"] else "DEAD"
        role = n.get("labels", {}).get("node_role", "worker")
        print(f"  {n['node_id'][:8]} {state:5} {role:6} {n['address']:>21} "
              f"total={n['resources']} available={n['available']}")
        # scheduling plane (heartbeat sched summary): per-class queue
        # depth + warm-pool occupancy/hit-rate — the overload story at a
        # glance (which class is deep, whether dispatch pays cold boots)
        sched = n.get("sched") or {}
        warm = sched.get("warm") or {}
        if warm:
            hits = warm.get("warm_hits", 0)
            cold = warm.get("cold_spawns", 0)
            rate = (f"{100.0 * hits / (hits + cold):.0f}%"
                    if hits + cold else "n/a")
            extras = []
            if warm.get("actor_adoptions"):
                extras.append(f"{warm['actor_adoptions']} actor adoption(s)")
            if sched.get("backpressure_total"):
                extras.append(
                    f"{sched['backpressure_total']} backpressured")
            if sched.get("deadline_evictions_total"):
                extras.append(f"{sched['deadline_evictions_total']} "
                              f"deadline-evicted")
            print(f"           warm pool: {warm.get('idle', 0)} idle / "
                  f"floor {warm.get('floor', 0)}, warm-hit rate {rate} "
                  f"({hits} warm / {cold} cold)"
                  + (f"; {', '.join(extras)}" if extras else ""))
        classes = sched.get("classes") or []
        if classes:
            desc = ", ".join(
                f"{c.get('class')}:{c.get('depth')}"
                + (f" (p99 {c['wait_p99_s']}s)"
                   if c.get("wait_p99_s") is not None else "")
                for c in classes[:5])
            print(f"           queued by class: {desc}")
    return 0


def _attach_driver(address: Optional[str]):
    import ray_tpu

    gcs = _resolve_gcs(address)
    if gcs is None:
        print("no running cluster found (pass --address or start one with "
              "`rt start --head`)", file=sys.stderr)
        raise SystemExit(1)
    ray_tpu.init(address=gcs, ignore_reinit_error=True)
    return ray_tpu


def cmd_job(args: argparse.Namespace) -> int:
    from ray_tpu import job as rt_job

    rt = _attach_driver(args.address)
    try:
        if args.job_cmd == "submit":
            import shlex

            parts = list(args.entrypoint or [])
            if parts and parts[0] == "--":
                parts = parts[1:]  # only the leading separator
            entrypoint = " ".join(shlex.quote(p) for p in parts)
            if not entrypoint:
                print("rt job submit: empty entrypoint", file=sys.stderr)
                return 1
            env_vars = dict(kv.split("=", 1) for kv in (args.env or []))
            job_id = rt_job.submit_job(entrypoint, env_vars=env_vars)
            print(job_id)
            if args.wait:
                return _follow_job(rt_job, job_id, from_start=True)
            return 0
        if args.job_cmd == "status":
            meta = rt_job.job_status(args.job_id)
            print(json.dumps(meta, indent=2))
            return 0 if meta["status"] in ("RUNNING", "SUCCEEDED", "PENDING") \
                else 1
        if args.job_cmd == "logs":
            if args.follow:
                return _follow_job(rt_job, args.job_id, from_start=True)
            print(rt_job.tail_job_logs(args.job_id)["data"], end="")
            return 0
        if args.job_cmd == "stop":
            print("stopped" if rt_job.stop_job(args.job_id)
                  else "already finished")
            return 0
        if args.job_cmd == "list":
            for meta in rt_job.list_jobs():
                print(f"{meta['job_id']}  {meta['status']:9}  "
                      f"{meta.get('entrypoint', '')}")
            return 0
        return 1
    finally:
        rt.shutdown()


def _follow_job(rt_job, job_id: str, from_start: bool = False) -> int:
    offset = 0
    while True:
        chunk = rt_job.tail_job_logs(job_id, offset)
        if chunk["data"]:
            print(chunk["data"], end="", flush=True)
        offset = chunk["next_offset"]
        if chunk["done"]:
            break
        time.sleep(0.3)
    status = rt_job.job_status(job_id)["status"]
    print(f"\n--- job {job_id}: {status}", file=sys.stderr)
    return 0 if status == "SUCCEEDED" else 1


_LIST_RPCS = {"nodes": "list_nodes", "actors": "list_actors",
              "placement-groups": "list_placement_groups",
              "tasks": "list_tasks", "objects": "list_objects",
              "errors": "list_failure_events"}


def cmd_list(args: argparse.Namespace) -> int:
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("no running cluster found (pass --address)", file=sys.stderr)
        return 1
    if args.what == "jobs":
        return cmd_job(argparse.Namespace(address=args.address,
                                          job_cmd="list"))
    rows = _gcs_call(gcs, _LIST_RPCS[args.what], {"limit": args.limit})
    print(json.dumps(rows, indent=2, default=str))
    return 0


def cmd_serve(args: argparse.Namespace) -> int:
    """rt serve deploy/status/shutdown (reference: ``serve/scripts.py``)."""
    from ray_tpu import serve
    from ray_tpu.serve import schema

    _attach_driver(args.address)
    if args.serve_cmd == "deploy":
        sys.path.insert(0, os.getcwd())  # import_path resolves from cwd
        names = schema.deploy_config(schema.load_config_file(args.config))
        for n in names:
            print(f"deployed application {n!r}")
        return 0
    if args.serve_cmd == "status":
        if getattr(args, "json", False):
            print(json.dumps(serve.detailed_status(), indent=2, default=str))
            return 0
        st = serve.detailed_status()
        apps = st.get("applications", {})
        if not apps:
            # the decision log outlives the apps it scaled (post-mortem of
            # a deleted deployment) — only the non-verbose view can stop
            print("no serve applications")
            if not getattr(args, "verbose", False):
                return 0
        proxies = st.get("proxies") or []
        if len(proxies) > 1:
            print("proxies: " + ", ".join(
                f"{p.get('proxy')}:{p.get('port')}" for p in proxies))
        for app, meta in apps.items():
            print(f"app {app!r}  route={meta.get('route_prefix')}  "
                  f"ingress={meta.get('ingress')}")
            for name, d in (meta.get("deployments") or {}).items():
                s = d.get("stats") or {}
                cb = (f"  slots {s['cb_active']}/{s['cb_slots']}"
                      f"  tokens {s.get('cb_tokens_generated', 0)}"
                      f"  completed {s.get('cb_requests_completed', 0)}"
                      if "cb_slots" in s else "")
                if "kv_hit_rate" in s:
                    cb += (f"  kv {100 * s['kv_hit_rate']:.0f}%"
                           f" {s.get('kv_bytes', 0) / 1e6:.1f}MB")
                if "eng_ttft_att" in s:
                    # engine flight-recorder rollup: SLO attainment +
                    # goodput + worst decode tick-gap across the fleet
                    cb += (f"  slo {s['eng_ttft_att']:.2f}/"
                           f"{s['eng_tpot_att']:.2f}"
                           f"  goodput {s.get('eng_goodput_tok_s', 0):.0f}"
                           f"tok/s"
                           f"  gap {1e3 * s.get('eng_gap_p99_s', 0):.0f}ms")
                print(f"  {name:<24} replicas {d.get('replicas', 0)}/"
                      f"{d.get('target', 0)}"
                      f"{' (+%d starting)' % d['starting'] if d.get('starting') else ''}"
                      f"  ongoing {s.get('ongoing', 0)}"
                      f"  queue {s.get('queue_depth', 0)}"
                      f"{cb}"
                      f"  p50 {1e3 * (s.get('p50_s') or 0):.1f}ms"
                      f"  p99 {1e3 * (s.get('p99_s') or 0):.1f}ms"
                      f"  qps {s.get('qps', 0)}")
        if getattr(args, "verbose", False):
            decisions = st.get("decisions") or []
            print(f"\nautoscaler decisions ({len(decisions)} recent):")
            for d in decisions:
                trig = d.get("trigger") or {}
                hyst = trig.get("hysteresis")
                when = time.strftime("%H:%M:%S",
                                     time.localtime(d.get("t", 0)))
                line = (f"  [{when}] {d['app']}/{d['deployment']} "
                        f"target {d.get('old_target')} -> "
                        f"{d.get('new_target')} ({d.get('direction')}; "
                        f"signal={trig.get('signal', 'ongoing')} "
                        f"ongoing_avg={trig.get('ongoing_avg', 0)} "
                        f"queue={trig.get('queue_depth', 0)} "
                        f"p99={1e3 * (trig.get('p99_s') or 0):.1f}ms "
                        f"qps={trig.get('qps', 0)})")
                if hyst:
                    line += (f" [held {hyst.get('held_s')}s of "
                             f"{hyst.get('delay_s')}s]")
                print(line)
        return 0
    if args.serve_cmd == "shutdown":
        serve.shutdown()
        print("serve stopped")
        return 0
    return 1


def cmd_rl(args: argparse.Namespace) -> int:
    """rt rl train/evaluate (reference: ``rllib/train.py``,
    ``rllib/evaluate.py``)."""
    import ray_tpu
    from ray_tpu.rl import train as rl_train

    if args.rl_cmd == "examples":  # pure listing: no cluster needed
        for name in rl_train.list_tuned_examples():
            print(name)
        return 0
    if args.rl_cmd == "rlhf":
        return _run_rlhf(args)
    if args.rl_cmd == "train" and not args.run \
            and not getattr(args, "file", None):
        print("rt rl train: pass --run ALGO or -f TUNED_EXAMPLE",
              file=sys.stderr)
        return 2
    if args.rl_cmd == "train" and getattr(args, "file", None) \
            and (args.run or args.env or args.config or args.config_file):
        # a tuned example fully specifies algo/env/config; silently
        # training something other than what the flag says would mislead
        print("rt rl train: -f is exclusive with --run/--env/--config/"
              "--config-file (stop flags still apply)", file=sys.stderr)
        return 2
    owns_session = False
    if args.address:
        _attach_driver(args.address)
        owns_session = True
    elif not ray_tpu.is_initialized():
        ray_tpu.init()  # standalone local cluster, like `rllib train`
        owns_session = True
    try:
        if args.rl_cmd == "train":
            if getattr(args, "file", None):
                rl_train.run_tuned_example(
                    args.file, checkpoint_dir=args.checkpoint_dir,
                    stop_iters=args.stop_iters,
                    stop_reward=args.stop_reward,
                    stop_timesteps=args.stop_timesteps)
                return 0
            rl_train.run_train(
                args.run, env=args.env, config_json=args.config,
                config_file=args.config_file,
                stop_iters=(args.stop_iters if args.stop_iters is not None
                            else 10),
                stop_reward=args.stop_reward,
                stop_timesteps=args.stop_timesteps,
                checkpoint_dir=args.checkpoint_dir)
            return 0
        if args.rl_cmd == "evaluate":
            rl_train.run_evaluate(args.checkpoint, run=args.run,
                                  episodes=args.episodes)
            return 0
        return 1
    finally:
        if owns_session:  # don't tear down a borrowed live session
            ray_tpu.shutdown()


def _run_rlhf(args: argparse.Namespace) -> int:
    """rt rl rlhf: the end-to-end RLHF pipeline (placed policy /
    reference / reward / generation roles, ContinuousEngine generate
    phase, streamed weight sync) for N iterations, one JSON line per
    iteration. The printed trace id replays the placement + phase story
    through `rt trace <id>`."""
    import json as _json

    import ray_tpu
    from ray_tpu.rl.rlhf import RLHFPipeline

    owns_session = False
    if args.address:
        _attach_driver(args.address)
        owns_session = True
    elif not ray_tpu.is_initialized():
        # a standalone session must be able to reserve the four
        # one-CPU role bundles even on a small box (init()'s default
        # CPU count is the machine's core count — 1 in CI)
        ray_tpu.init(num_cpus=6)
        owns_session = True
    pipeline = None
    try:
        pipeline = RLHFPipeline(
            preset=args.preset, num_prompts=args.prompts,
            prompt_len=args.prompt_len, max_new_tokens=args.max_new,
            max_slots=args.slots, seed=args.seed)
        print(f"rlhf: roles placed "
              f"({', '.join(r['role'] for r in pipeline.group.describe())})"
              f"; trace {pipeline.trace_id}", flush=True)
        for _ in range(args.iters):
            print(_json.dumps(pipeline.run_iteration()), flush=True)
        s = pipeline.stats()
        print(f"rlhf: {s['iterations']} iteration(s), "
              f"{s['tokens_generated']} tokens generated, "
              f"{s['sync_bytes_total']} weight-sync bytes; "
              f"rt trace {s['trace_id']} shows the placement story")
        return 0
    finally:
        if pipeline is not None:
            pipeline.shutdown()
        if owns_session:
            ray_tpu.shutdown()


def cmd_trace(args: argparse.Namespace) -> int:
    """rt trace <task_id|trace_id|span_id>: print the span tree with the
    per-phase latency tables and the named critical path (reads the GCS
    task-event store directly, no driver attach)."""
    from ray_tpu.util.tracing import format_trace

    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("no running cluster found (pass --address)", file=sys.stderr)
        return 1
    try:
        events = _gcs_call(gcs, "list_tasks",
                           {"limit": args.limit, "serve": "include"})
    except Exception as e:  # noqa: BLE001 — one line, not a stack trace
        print(f"rt trace: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    ident = args.id

    def ctx(e):
        return e.get("trace") or {}

    trace_id = None
    if any(ctx(e).get("trace_id") == ident for e in events):
        trace_id = ident
    else:
        for e in events:
            if (e.get("task_id", "").startswith(ident)
                    or ctx(e).get("span_id") == ident):
                trace_id = ctx(e).get("trace_id")
                if trace_id is None:
                    # untraced task: still print its event (+ phases if the
                    # task ran with phase tracing from an ambient span)
                    print(format_trace([e]))
                    return 0
                break
    if trace_id is None:
        print(f"rt trace: no task or trace matching {ident!r} in the "
              f"event store (traces are bounded; re-run with tracing on)",
              file=sys.stderr)
        return 1
    spans = [e for e in events if ctx(e).get("trace_id") == trace_id]
    print(format_trace(spans))
    return 0


def cmd_memory(args: argparse.Namespace) -> int:
    """rt memory: the byte-side twin of `rt trace` (reference: `ray
    memory` + memory_summary). Default: per-node store usage + per-object
    owner tables + leak suspects; --oom replays OOM post-mortems straight
    from the GCS (no driver attach); --device adds the HBM table."""
    from ray_tpu.util.memory import format_oom_reports

    if args.oom:
        gcs = _resolve_gcs(args.address)
        if gcs is None:
            print("no running cluster found (pass --address)",
                  file=sys.stderr)
            return 1
        try:
            events = _gcs_call(gcs, "list_mem_events",
                               {"kind": "oom_kill", "limit": args.limit})
        except Exception as e:  # noqa: BLE001 — one line, no stack trace
            print(f"rt memory: cannot reach GCS at {gcs}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 1
        if args.id:
            # filter to one victim / object / node; an unknown or expired
            # id gets a clear one-liner + nonzero, never an empty table
            ident = args.id
            events = [
                ev for ev in events
                if str((ev.get("victim") or {}).get("worker_id", ""))
                .startswith(ident)
                or str(ev.get("node_id", "")).startswith(ident)
                or any(str(o.get("oid", "")).startswith(ident)
                       or str(o.get("oid", "")).endswith(ident)
                       for o in ev.get("top_objects") or ())]
            if not events:
                print(f"rt memory --oom: no OOM post-mortem matching "
                      f"{ident!r} (the event store is bounded — it may "
                      f"have expired)", file=sys.stderr)
                return 1
        print(format_oom_reports(events))
        return 0
    if args.id:
        print("rt memory: an id filter only applies with --oom",
              file=sys.stderr)
        return 2
    rt = _attach_driver(args.address)
    try:
        print(rt.memory_summary(limit=args.limit, top_n=args.top,
                                leak_age_s=args.leak_age,
                                include_devices=args.device))
        return 0
    finally:
        rt.shutdown()


def cmd_errors(args: argparse.Namespace) -> int:
    """rt errors: tail/filter the cluster's categorized FailureEvent feed
    (cluster/gcs.py failure_events store — the death-cause taxonomy of
    core/failure.py). Reads the GCS directly, no driver attach."""
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("no running cluster found (pass --address)", file=sys.stderr)
        return 1
    payload = {"limit": args.limit}
    if args.category:
        payload["category"] = args.category
    if getattr(args, "origin", None):
        # "chaos" = injected by the chaos plane; "organic" = everything else
        payload["origin"] = args.origin
    try:
        events = _gcs_call(gcs, "list_failure_events", payload)
    except Exception as e:  # noqa: BLE001 — one line, no stack trace
        print(f"rt errors: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(events, indent=2, default=str))
        return 0
    if not events:
        what = (f"category {args.category!r}" if args.category
                else "any category")
        print(f"(no failure events recorded for {what})")
        return 0
    for ev in events:
        # last_t: a deduped crash loop shows when it LAST fired, like the
        # dashboard — not the 30s-old first occurrence
        when = time.strftime("%H:%M:%S", time.localtime(
            ev.get("last_t", ev.get("t", 0))))
        who = " ".join(
            f"{k}={str(ev[k])[:12]}" for k in
            ("name", "task_id", "actor_id", "worker_id") if ev.get(k))
        count = f" x{ev['count']}" if ev.get("count", 1) > 1 else ""
        origin = f"[{ev['origin']}] " if ev.get("origin") else ""
        print(f"{when}  {str(ev.get('node_id', '?'))[:8]:<8} "
              f"{ev.get('category', 'unknown'):<24}{count:<5} "
              f"{origin}{who + '  ' if who else ''}{ev.get('message', '')}")
    return 0


def cmd_sched(args: argparse.Namespace) -> int:
    """rt sched decisions/balance: the placement-receipt plane — every
    scheduling decision's record (kind, chosen node, reason, candidate
    feature vectors; GCS placement_events store) and the cross-node
    queued+running balance snapshot behind rt_sched_node_imbalance.
    Reads the GCS directly, no driver attach."""
    kinds = ("dispatch_local", "spillback", "actor_place", "pg_place",
             "warm_adopt", "gang_place")
    if (args.sched_cmd == "decisions" and args.kind
            and args.kind not in kinds):
        # local usage errors must not masquerade as cluster unreachability
        print(f"rt sched decisions: unknown --kind {args.kind!r} "
              f"(one of: {', '.join(kinds)})", file=sys.stderr)
        return 2
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("rt sched: no running cluster found (pass --address)",
              file=sys.stderr)
        return 1
    try:
        if args.sched_cmd == "balance":
            reply = _gcs_call(gcs, "sched_balance", {})
            if args.json:
                print(json.dumps(reply, indent=2, default=str))
                return 0
            print(f"cross-node imbalance (CoV of queued+running load): "
                  f"{reply.get('cov', 0.0):.3f}")
            for row in reply.get("nodes") or ():
                print(f"  {str(row.get('node_id', '?'))[:8]:<8} "
                      f"queued={row.get('queued', 0):<6} "
                      f"running={row.get('running', 0):<6} "
                      f"load={row.get('load', 0)}")
            hist = reply.get("history") or []
            if hist:
                series = " ".join(f"{h.get('cov', 0.0):.2f}"
                                  for h in hist[-10:])
                print(f"recent ticks: {series}")
            return 0
        # decisions
        payload: Dict = {"limit": args.limit}
        if args.kind:
            payload["kind"] = args.kind
        if args.node:
            payload["node"] = args.node
        events = _gcs_call(gcs, "list_placement_events", payload)
        if args.json:
            print(json.dumps(events, indent=2, default=str))
            return 0
        if not events:
            what = f"kind {args.kind!r}" if args.kind else "any kind"
            print(f"(no placement decisions recorded for {what})")
            return 0
        for ev in events:
            when = time.strftime("%H:%M:%S", time.localtime(
                ev.get("last_t", ev.get("t", 0))))
            who = " ".join(
                f"{k}={str(ev[k])[:12]}" for k in
                ("name", "task_id", "actor_id", "pg_id") if ev.get(k))
            count = f" x{ev['count']}" if ev.get("count", 1) > 1 else ""
            hop = ""
            if ev.get("kind") == "spillback":
                hop = (f" {str(ev.get('from_node', '?'))[:8]}"
                       f"->{str(ev.get('node_id', '?'))[:8]}"
                       f" hops={ev.get('hops', 1)}")
            print(f"{when}  {str(ev.get('node_id', '?'))[:8]:<8} "
                  f"{ev.get('kind', '?'):<15}{count:<7} "
                  f"reason={ev.get('reason', '?'):<20}"
                  f"{hop} {who}  "
                  f"candidates={len(ev.get('candidates') or ())}")
        return 0
    except Exception as e:  # noqa: BLE001 — one line, no stack trace
        print(f"rt sched: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1


def cmd_chaos(args: argparse.Namespace) -> int:
    """rt chaos arm/disarm/status: drive the fault-injection plane
    (util/chaos.py) against a live cluster. The plan ships through the GCS
    KV (@chaos/plan) and a revision on every heartbeat reply — raylets arm
    themselves and their workers within a heartbeat."""
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("rt chaos: no running cluster found (pass --address)",
              file=sys.stderr)
        return 1
    if args.chaos_cmd == "arm" and args.plan:
        # local usage errors must not masquerade as cluster unreachability
        try:
            with open(args.plan) as f:
                plan_from_file = json.load(f)
        except (OSError, ValueError) as e:
            print(f"rt chaos arm: cannot read plan file {args.plan!r}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2
    try:
        if args.chaos_cmd == "arm":
            if args.plan:
                plan = plan_from_file
            else:
                if not args.site:
                    print("rt chaos arm: pass --plan FILE or --site SITE",
                          file=sys.stderr)
                    return 2
                fault: Dict = {"site": args.site}
                for flag, field in (("at", "at"), ("after", "after"),
                                    ("prob", "prob"),
                                    ("max_fires", "max_fires"),
                                    ("delay", "delay_s"),
                                    ("value", "value"),
                                    ("target", "target")):
                    v = getattr(args, flag)
                    if v is not None:
                        fault[field] = v
                plan = {"seed": args.seed, "faults": [fault]}
            reply = _gcs_call(gcs, "chaos_arm", {"plan": plan})
            if reply.get("error"):
                print(f"rt chaos arm: {reply['error']}", file=sys.stderr)
                return 1
            faults = reply.get("plan", {}).get("faults", [])
            print(f"chaos armed (rev {reply.get('rev')}): "
                  f"{len(faults)} fault(s) at "
                  f"{', '.join(f['site'] for f in faults)}")
            return 0
        if args.chaos_cmd == "disarm":
            reply = _gcs_call(gcs, "chaos_disarm", {})
            print(f"chaos disarmed (rev {reply.get('rev')})")
            return 0
        if args.chaos_cmd == "status":
            print(json.dumps(_gcs_call(gcs, "chaos_status", {}),
                             indent=2, default=str))
            return 0
        return 1
    except Exception as e:  # noqa: BLE001 — one line, no stack trace
        print(f"rt chaos: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1


def cmd_doctor(args: argparse.Namespace) -> int:
    """rt doctor: one-shot cluster health report (util/doctor.py) — node/
    actor/worker liveness, recent failure categories ranked, OOM
    post-mortems + leak suspects from the memory plane, queue-depth and
    spill pressure. Exit 0 healthy / 1 unhealthy / 2 unreachable."""
    from ray_tpu.util import doctor

    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("rt doctor: no running cluster found (pass --address)",
              file=sys.stderr)
        return 2
    text, rc = doctor.run(gcs, window_s=args.window,
                          queue_warn=args.queue_warn,
                          queue_wait_warn_s=args.queue_wait_warn,
                          serve_p99_warn_s=args.serve_p99_warn,
                          imbalance_warn=args.imbalance_warn,
                          tick_gap_warn_s=args.tick_gap_warn,
                          slo_warn=args.slo_warn,
                          bubble_warn=args.bubble_warn,
                          launch_gap_warn_s=args.launch_gap_warn,
                          data_wait_warn=args.data_wait_warn,
                          as_json=args.json)
    print(text, file=sys.stderr if rc == 2 else sys.stdout)
    return rc


def cmd_engine(args: argparse.Namespace) -> int:
    """rt engine stats/ticks/requests: the ContinuousEngine flight-
    recorder plane (util/engine_recorder.py). Each live engine's drain
    thread pushes an @engine/ KV snapshot (summary + tick/request record
    tails); this reads them straight off the GCS — no driver attach, so
    it works while the engine is saturated."""
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("rt engine: no running cluster found (pass --address)",
              file=sys.stderr)
        return 1
    try:
        keys = _gcs_call(gcs, "kv_keys",
                         {"prefix": "@engine/"}).get("keys") or []
        snaps = []
        for k in sorted(keys):
            raw = _gcs_call(gcs, "kv_get", {"key": k}).get("value")
            if not raw:
                continue
            try:
                snaps.append(json.loads(raw))
            except ValueError:
                continue
    except Exception as e:  # noqa: BLE001 — one line, no stack trace
        print(f"rt engine: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if args.name:
        snaps = [s for s in snaps
                 if args.name in f"{s.get('node')}:{s.get('name')}"]
    if args.json:
        if args.engine_cmd == "stats":
            print(json.dumps(snaps, indent=2, default=str))
        else:
            key = "ticks" if args.engine_cmd == "ticks" else "requests"
            print(json.dumps(
                [{"engine": f"{s.get('node')}:{s.get('name')}",
                  key: (s.get(key) or [])[-args.limit:]} for s in snaps],
                indent=2, default=str))
        return 0
    if not snaps:
        print("(no engine flight-recorder snapshots — no live "
              "ContinuousEngine, or RT_ENGINE_RECORDER=0)")
        return 0
    for s in snaps:
        label = f"{s.get('node')}:{s.get('pid')}:{s.get('name')}"
        summ = s.get("summary") or {}
        if args.engine_cmd == "stats":
            print(f"engine {label}")
            print(f"  ticks {summ.get('ticks_total', 0)}  active "
                  f"{summ.get('active', 0)}  requests "
                  f"{summ.get('requests_total', 0)} "
                  f"({summ.get('cancelled_total', 0)} cancelled)  swaps "
                  f"{summ.get('swaps', 0)}")
            phases = summ.get("phase_s") or {}
            if phases:
                total = sum(phases.values()) or 1.0
                parts = "  ".join(f"{p}={1e3 * v:.1f}ms"
                                  f"({100 * v / total:.0f}%)"
                                  for p, v in phases.items())
                print(f"  phases [{summ.get('window_ticks', 0)} ticks, "
                      f"sum/wall {summ.get('phase_sum_ratio', 0):.2f}]: "
                      f"{parts}")
            print(f"  tick-gap p50 {1e3 * summ.get('tick_gap_p50_s', 0):.2f}"
                  f"ms  p99 {1e3 * summ.get('tick_gap_p99_s', 0):.2f}ms  "
                  f"max {1e3 * summ.get('tick_gap_max_s', 0):.2f}ms")
            if summ.get("window_completed"):
                print(f"  slo[{summ['window_completed']} reqs]: ttft "
                      f"{summ.get('ttft_attainment', 0):.2f} "
                      f"(p99 {1e3 * summ.get('ttft_p99_s', 0):.0f}ms vs "
                      f"{1e3 * summ.get('ttft_slo_s', 0):.0f}ms)  tpot "
                      f"{summ.get('tpot_attainment', 0):.2f} "
                      f"(p99 {1e3 * summ.get('tpot_p99_s', 0):.1f}ms vs "
                      f"{1e3 * summ.get('tpot_slo_s', 0):.1f}ms)")
                print(f"  goodput {summ.get('goodput_tok_s', 0):.1f} tok/s"
                      f" of {summ.get('window_tok_s', 0):.1f} tok/s "
                      f"(capacity est {summ.get('capacity_tok_s', 0):.1f})"
                      f"  decode-eff {summ.get('decode_efficiency', 0):.2f}"
                      f"  occupancy {summ.get('occupancy', 0):.2f}")
            if "queue_p50_s" in summ:
                front = (f"front-in p50 "
                         f"{1e3 * summ['front_in_p50_s']:.1f}ms  "
                         if "front_in_p50_s" in summ else "")
                print(f"  {front}slot-wait p50 "
                      f"{1e3 * summ['queue_p50_s']:.1f}ms p90 "
                      f"{1e3 * summ.get('queue_p90_s', 0):.1f}ms  pump-lag "
                      f"p99 {1e3 * summ.get('pump_lag_p99_s', 0):.2f}ms max "
                      f"{1e3 * summ.get('pump_lag_max_s', 0):.1f}ms  "
                      f"stall excess {summ.get('tick_excess_s', 0):.2f}s "
                      f"(launch {summ.get('launch_excess_s', 0):.2f}s)")
            for prog in summ.get("decode_programs") or ():
                print(f"  decode program bucket={prog['bucket']} "
                      f"k={prog['k']}: cache_donated "
                      f"{prog['cache_donated']}  "
                      f"cache_copy_bytes_per_step "
                      f"{prog['cache_copy_bytes_per_step']} "
                      f"({prog['cache_copy_bytes_per_step'] / max(1, prog['cache_bytes']):.2f}"
                      f" of the cache)")
                if "state_bytes" in prog:  # a model with recurrent layers
                    print(f"    state_donated {prog['state_donated']}  "
                          f"state_copy_bytes_per_step "
                          f"{prog['state_copy_bytes_per_step']} "
                          f"({prog['state_copy_bytes_per_step'] / max(1, prog['state_bytes']):.2f}"
                          f" of the recurrent state; 2.00 x the launch's "
                          f"share of the rows is in place)")
            layout = summ.get("state_layout")
            if layout:
                print(f"  state layout: {layout['layers']['recurrent']} "
                      f"recurrent + {layout['layers']['attention']} "
                      f"attention layers, "
                      f"{layout['state_bytes_per_row']} state bytes a row, "
                      f"{layout['kv_bytes_per_position']} K/V bytes a "
                      f"position; prefill scan chunks "
                      f"{summ.get('ssm_scan_chunks', 0)}")
            if layout and layout.get("kinds"):
                print(f"  layers by kind: "
                      + ", ".join(f"{n} {kind}" for kind, n in
                                  layout["kinds"].items())
                      + f"; rings of {layout['sliding_window']} positions, "
                      f"{layout['window_bytes_per_row']} bytes a row; "
                      f"{layout['kv_readers']} layers read the shared K/V")
            if summ.get("window_positions_read"):
                print(f"  window rings read "
                      f"{summ['window_positions_read']} positions for "
                      f"{summ.get('window_positions_live', 0)} live")
            if summ.get("prefill_layer_tokens_whole"):
                print(f"  prefills computed "
                      f"{summ.get('prefill_layer_tokens', 0)} layer-tokens "
                      f"of {summ['prefill_layer_tokens_whole']} "
                      f"(every layer over every token)")
            if summ.get("kv_positions_read"):
                print(f"  decode attention read "
                      f"{summ['kv_positions_read']} positions for "
                      f"{summ['kv_positions_live']} live "
                      f"(kv_read_ratio {summ['kv_read_ratio']:.2f})")
            if summ.get("moe_assignments"):
                print(f"  moe: {summ['moe_assignments']} assignments, "
                      f"{summ['moe_rows_computed']} rows computed, experts "
                      f"touched {summ['moe_experts_touched']} of "
                      f"{summ['moe_expert_slots']}, busiest expert "
                      f"{summ['moe_max_expert_rows']} rows in one layer")
            print(f"  recorder overhead "
                  f"{100 * summ.get('overhead_frac', 0):.3f}% of tick wall")
        elif args.engine_cmd == "ticks":
            print(f"engine {label} — last {args.limit} tick(s)")
            for t in (s.get("ticks") or [])[-args.limit:]:
                when = time.strftime("%H:%M:%S",
                                     time.localtime(t.get("t", 0)))
                phases = "  ".join(f"{p}={v:.1f}"
                                   for p, v in (t.get("phases_ms")
                                                or {}).items())
                gap = (f"  gap={t['gap_ms']:.1f}ms"
                       if "gap_ms" in t else "")
                print(f"  {when} #{t.get('seq'):<6} "
                      f"wall={t.get('wall_ms', 0):.1f}ms "
                      f"active={t.get('active')}/{t.get('bucket')} "
                      f"k={t.get('k')} tok={t.get('tokens')}{gap}  "
                      f"[{phases}]")
        else:  # requests
            print(f"engine {label} — last {args.limit} request(s)")
            for r in (s.get("requests") or [])[-args.limit:]:
                rid_note = (f" rid={r['request_id'][:8]}"
                            if r.get("request_id") else "")
                print(f"  #{r.get('rid'):<5} {r.get('state'):<9} "
                      f"queue={r.get('queue_wait_ms', 0):.1f}ms "
                      f"prompt={r.get('prompt_tokens')} "
                      f"(cached {r.get('cached_tokens')}) "
                      f"tok={r.get('tokens')} "
                      f"ticks={r.get('decode_ticks')} "
                      f"ttft={r.get('ttft_ms', 0):.1f}ms "
                      f"tpot={r.get('tpot_ms', 0):.2f}ms{rid_note}")
    return 0


def cmd_rlhf(args: argparse.Namespace) -> int:
    """rt rlhf stats: the RLHF pipeline flight-recorder plane
    (util/pipeline_recorder.py). The driver's drain thread pushes an
    @rlhf/ KV snapshot (bubble/staleness/transfer rollup + iteration
    record tail); this reads it straight off the GCS — so it works
    POSTMORTEM, after the pipeline driver exited. A missing snapshot is
    an ERROR here (exit 1), unlike `rt engine stats`: you run this to
    grade a pipeline, and grading nothing is a mistake worth failing."""
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("rt rlhf: no running cluster found (pass --address)",
              file=sys.stderr)
        return 1
    try:
        keys = _gcs_call(gcs, "kv_keys",
                         {"prefix": "@rlhf/"}).get("keys") or []
        snaps = []
        for k in sorted(keys):
            raw = _gcs_call(gcs, "kv_get", {"key": k}).get("value")
            if not raw:
                continue
            try:
                snaps.append(json.loads(raw))
            except ValueError:
                continue
    except Exception as e:  # noqa: BLE001 — one line, no stack trace
        print(f"rt rlhf: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if args.name:
        snaps = [s for s in snaps
                 if args.name in f"{s.get('node')}:{s.get('name')}"]
    if not snaps:
        what = (f"matching {args.name!r} " if args.name else "")
        print(f"rt rlhf: no pipeline flight-recorder snapshot {what}"
              f"under @rlhf/ (pipeline never ran, recorder closed, or "
              f"RT_RLHF_RECORDER=0)", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(snaps, indent=2, default=str))
        return 0
    now = time.time()
    for s in snaps:
        label = f"{s.get('node')}:{s.get('pid')}:{s.get('name')}"
        summ = s.get("summary") or {}
        age = max(0.0, now - (s.get("t") or now))
        print(f"rlhf {label}  (snapshot {age:.0f}s old)")
        stale = summ.get("staleness") or {}
        print(f"  iterations {summ.get('iterations_total', 0)} "
              f"({summ.get('interrupted_total', 0)} interrupted)  "
              f"tokens {summ.get('tokens', 0)}  bubble "
              f"{summ.get('bubble_fraction', 0):.3f} (last "
              f"{summ.get('bubble_last', 0):.3f})  coverage "
              f"{summ.get('coverage', 0):.3f}  staleness last "
              f"{stale.get('last', 0)} p99 {stale.get('p99', 0)} "
              f"max {stale.get('max', 0)}")
        busy = summ.get("role_busy_frac") or {}
        if busy:
            parts = "  ".join(f"{r}={100 * v:.0f}%"
                              for r, v in busy.items())
            print(f"  role busy share of pipeline span: {parts}")
        actor = summ.get("actor_s") or {}
        driver = summ.get("driver_s") or {}
        tax = summ.get("tax_s") or {}
        if driver:
            parts = "  ".join(
                f"{p}={1e3 * driver.get(p, 0):.0f}ms"
                f"(tax {1e3 * tax.get(p, 0):.0f}ms)" for p in driver)
            print(f"  driver phases (orchestration tax): {parts}")
        if actor:
            parts = "  ".join(f"{p}={1e3 * v:.0f}ms"
                              for p, v in actor.items())
            print(f"  actor phases: {parts}")
        rcpt = summ.get("receipt_last") or {}
        if rcpt:
            print(f"  transfer[v{rcpt.get('version', 0)} "
                  f"{rcpt.get('transport', '?')}]: "
                  f"{rcpt.get('nbytes', 0) / 1e6:.2f}MB "
                  f"{rcpt.get('n_leaves', 0)} leaves "
                  f"({rcpt.get('oid_leaves', 0)} oid / "
                  f"{rcpt.get('inline_leaves', 0)} inline)  pump "
                  f"{1e3 * rcpt.get('pump_wall_s', 0):.1f}ms  fetch "
                  f"{1e3 * rcpt.get('fetch_wall_s', 0):.1f}ms  barrier "
                  f"{1e3 * rcpt.get('barrier_drain_s', 0):.1f}ms  swap "
                  f"{1e3 * rcpt.get('swap_apply_s', 0):.2f}ms")
        intr = summ.get("interrupted_last")
        if intr:
            when = time.strftime("%H:%M:%S",
                                 time.localtime(intr.get("t", 0)))
            gaps = summ.get("restart_gaps_s") or []
            gap_note = (f"  restart gap {gaps[-1]:.2f}s"
                        if gaps else "")
            print(f"  last interrupt: {intr.get('phase')} @ {when} "
                  f"({intr.get('error', '')[:60]}){gap_note}")
        print(f"  recorder overhead "
              f"{100 * summ.get('overhead_frac', 0):.3f}% of iteration "
              f"wall")
        for r in (s.get("iterations") or [])[-args.limit:]:
            when = time.strftime("%H:%M:%S",
                                 time.localtime(r.get("t", 0)))
            if r.get("state") == "interrupted":
                print(f"  {when} #{r.get('seq'):<4} INTERRUPTED in "
                      f"{r.get('phase')} ({r.get('error', '')[:50]})")
                continue
            gap = (f" gap={r['restart_gap_s']:.2f}s"
                   if "restart_gap_s" in r else "")
            print(f"  {when} #{r.get('seq'):<4} iter "
                  f"{r.get('iteration')} wall={r.get('wall_ms', 0):.0f}"
                  f"ms bubble={r.get('bubble_fraction', 0):.3f} "
                  f"cov={r.get('coverage', 0):.2f} "
                  f"stale={r.get('staleness', 0)}{gap}")
    return 0


def cmd_train(args: argparse.Namespace) -> int:
    """rt train stats: the StepDriver flight-recorder plane
    (util/train_recorder.py). The driver's drain thread pushes an
    @train/ KV snapshot (phase rollup, launch-gap accounting, the
    MFU-gap waterfall + launch record tail); this reads it straight off
    the GCS — so it works POSTMORTEM, after the training run finished
    (the @train/ key deliberately survives the recorder). A missing
    snapshot is an ERROR (exit 1), same discipline as `rt rlhf stats`:
    you run this to grade a training run, and grading nothing is a
    mistake worth failing."""
    gcs = _resolve_gcs(args.address)
    if gcs is None:
        print("rt train: no running cluster found (pass --address)",
              file=sys.stderr)
        return 1
    try:
        keys = _gcs_call(gcs, "kv_keys",
                         {"prefix": "@train/"}).get("keys") or []
        snaps = []
        for k in sorted(keys):
            raw = _gcs_call(gcs, "kv_get", {"key": k}).get("value")
            if not raw:
                continue
            try:
                snaps.append(json.loads(raw))
            except ValueError:
                continue
    except Exception as e:  # noqa: BLE001 — one line, no stack trace
        print(f"rt train: cannot reach GCS at {gcs}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 1
    if args.name:
        snaps = [s for s in snaps
                 if args.name in f"{s.get('node')}:{s.get('name')}"]
    if not snaps:
        what = (f"matching {args.name!r} " if args.name else "")
        print(f"rt train: no train flight-recorder snapshot {what}"
              f"under @train/ (no fused launch ran, or "
              f"RT_TRAIN_RECORDER=0)", file=sys.stderr)
        return 1
    if args.json:
        print(json.dumps(snaps, indent=2, default=str))
        return 0
    now = time.time()
    for s in snaps:
        label = f"{s.get('node')}:{s.get('pid')}:{s.get('name')}"
        summ = s.get("summary") or {}
        age = max(0.0, now - (s.get("t") or now))
        print(f"train {label}  (snapshot {age:.0f}s old)")
        print(f"  launches {summ.get('launches_total', 0)} "
              f"({summ.get('compiles', 0)} compiled)  steps "
              f"{summ.get('steps_total', 0)}  tokens "
              f"{summ.get('tokens', 0)}  "
              f"{summ.get('tokens_per_s', 0):.0f} tok/s  phase coverage "
              f"{summ.get('phase_sum_ratio', 0):.3f} of launch wall")
        phases = summ.get("phase_s") or {}
        if phases:
            parts = "  ".join(f"{p}={1e3 * v:.1f}ms"
                              for p, v in phases.items())
            print(f"  phases (window sums): {parts}")
        gp50 = 1e3 * summ.get("launch_gap_p50_s", 0)
        gp99 = 1e3 * summ.get("launch_gap_p99_s", 0)
        gmax = 1e3 * summ.get("launch_gap_max_s", 0)
        print(f"  launch gap p50={gp50:.1f}ms p99={gp99:.1f}ms "
              f"max={gmax:.1f}ms  dry-resets {summ.get('dry_resets', 0)}"
              f"  data_wait {100 * summ.get('data_wait_frac', 0):.1f}% "
              f"of wall")
        wf = summ.get("waterfall") or {}
        if wf:
            print(f"  MFU waterfall: raw {wf.get('raw_mfu', 0):.4f} -> "
                  f"achieved {wf.get('achieved_mfu', 0):.4f}  (gap "
                  f"{100 * summ.get('mfu_gap_frac', 0):.1f}%, marginal "
                  f"{summ.get('marginal_mfu', 0):.4f})")
            cost = wf.get("mfu_cost") or {}
            parts = "  ".join(f"{b}={v:.4f}"
                              for b, v in cost.items() if v > 0)
            if parts:
                print(f"  gap attribution (MFU cost): {parts}")
        print(f"  recorder overhead "
              f"{100 * summ.get('overhead_frac', 0):.3f}% of launch wall")
        for sentence in plans.sentences(summ):
            print(f"  {sentence}")
        routing = summ.get("routing") or {}
        if routing.get("moe_assignments"):
            held = routing.get("moe_held", 0)
            print(f"  routing: {routing['moe_assignments']} assignments, "
                  f"{held} to experts held here "
                  f"({100 * held / routing['moe_assignments']:.2f}%), "
                  f"{routing.get('moe_kept', 0)} kept, "
                  f"{routing.get('moe_dropped', 0)} dropped beyond capacity, "
                  f"busiest expert's queue "
                  f"{routing.get('moe_max_expert_rows', 0)} rows")
        mem = summ.get("step_memory") or {}
        if mem:
            print(f"  memory: the compiled step peaks at "
                  f"{mem['peak_bytes'] / 2**30:.2f} GiB a device "
                  f"(temporaries {mem['temp_bytes'] / 2**30:.2f}, "
                  f"arguments {mem['argument_bytes'] / 2**30:.2f})")
        if summ.get("expert_placement"):
            print(f"  experts placed by {summ['expert_placement']}")
        coll = summ.get("collectives") or {}
        if coll:
            parts = "  ".join(
                f"{kind} {c['count']} ({c['runs']} runs, "
                f"{c['bytes'] / 1e9:.2f} GB)" for kind, c in coll.items())
            print(f"  collectives a launch: {parts}")
        for r in (s.get("launches") or [])[-args.limit:]:
            when = time.strftime("%H:%M:%S",
                                 time.localtime(r.get("t", 0)))
            pm = r.get("phases_ms") or {}
            parts = " ".join(f"{p}={v:.1f}" for p, v in pm.items())
            gap = (f" gap={r['gap_ms']:.1f}ms" if "gap_ms" in r else "")
            done = "" if r.get("done") else "  IN-FLIGHT"
            print(f"  {when} #{r.get('seq'):<4} k={r.get('k')} "
                  f"wall={r.get('wall_ms', 0):.1f}ms [{parts}]"
                  f"{gap}{done}")
    return 0


def cmd_metrics(args: argparse.Namespace) -> int:
    from ray_tpu.util.metrics import metrics_text

    rt = _attach_driver(args.address)
    try:
        print(metrics_text(), end="")
        return 0
    finally:
        rt.shutdown()


def cmd_export_grafana(args: argparse.Namespace) -> int:
    """rt metrics-export-grafana: turnkey Grafana/Prometheus provisioning
    (reference: ``dashboard/modules/metrics/grafana_dashboard_factory``)."""
    from ray_tpu.dashboard.grafana import export_grafana, \
        snapshot_user_metrics

    user = []
    if args.address:
        rt = _attach_driver(args.address)
        try:
            user = snapshot_user_metrics()
        finally:
            rt.shutdown()
    paths = export_grafana(args.out, prom_url=args.prom_url,
                           metrics_target=args.metrics_target,
                           user_metrics=user)
    for k, v in paths.items():
        print(f"{k}: {v}")
    return 0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] == ["lint"]:
        # passthrough: one parser (analysis/runner.py), one source of
        # truth (`rt lint [--json] [--baseline-update] [paths...]`)
        from ray_tpu.analysis import runner as _lint

        return _lint.main(argv[1:])
    parser = argparse.ArgumentParser(prog="rt")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p_start = sub.add_parser("start", help="start a head or worker node")
    p_start.add_argument("--head", action="store_true")
    p_start.add_argument("--address", default=None)
    p_start.add_argument("--host", default="127.0.0.1")
    p_start.add_argument("--port", type=int, default=0)
    p_start.add_argument("--num-cpus", type=float, default=None)
    p_start.add_argument("--num-tpus", type=float, default=None)
    p_start.add_argument("--resources", default=None)
    p_start.add_argument("--session-name", default=None)
    p_start.add_argument("--timeout", type=float, default=30.0)
    p_start.set_defaults(fn=cmd_start)

    p_stop = sub.add_parser("stop", help="stop all nodes on this machine")
    p_stop.add_argument("--force", action="store_true")
    p_stop.add_argument("--timeout", type=float, default=10.0)
    p_stop.set_defaults(fn=cmd_stop)

    p_status = sub.add_parser("status", help="show cluster nodes")
    p_status.add_argument("--address", default=None)
    p_status.set_defaults(fn=cmd_status)

    p_job = sub.add_parser("job", help="submit / inspect jobs")
    job_sub = p_job.add_subparsers(dest="job_cmd", required=True)
    pj_submit = job_sub.add_parser("submit")
    pj_submit.add_argument("--address", default=None)
    pj_submit.add_argument("--env", action="append", metavar="K=V")
    pj_submit.add_argument("--wait", action="store_true",
                           help="stream logs until the job finishes")
    pj_submit.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for name in ("status", "logs", "stop"):
        pj = job_sub.add_parser(name)
        pj.add_argument("--address", default=None)
        pj.add_argument("job_id")
        if name == "logs":
            pj.add_argument("--follow", action="store_true")
    pj_list = job_sub.add_parser("list")
    pj_list.add_argument("--address", default=None)
    p_job.set_defaults(fn=cmd_job)

    p_list = sub.add_parser("list", help="state API listings")
    p_list.add_argument("what", choices=sorted(_LIST_RPCS) + ["jobs"])
    p_list.add_argument("--address", default=None)
    p_list.add_argument("--limit", type=int, default=200)
    p_list.set_defaults(fn=cmd_list)

    # `rt lint` is routed in main() before parsing (analysis/runner.py
    # owns the flag set); this stub only makes it show up in `rt --help`
    sub.add_parser(
        "lint", add_help=False,
        help="concurrency/runtime-invariant static analysis with a "
             "ratcheted baseline (ray_tpu/analysis)")

    p_micro = sub.add_parser("microbenchmark",
                             help="core-ops throughput sweep")
    p_micro.set_defaults(fn=lambda a: __import__(
        "ray_tpu.scripts.microbenchmark",
        fromlist=["main"]).main(a))

    p_serve = sub.add_parser("serve", help="deploy/inspect serve apps")
    serve_sub = p_serve.add_subparsers(dest="serve_cmd", required=True)
    ps_deploy = serve_sub.add_parser("deploy")
    ps_deploy.add_argument("config", help="YAML config (serve/schema.py)")
    ps_deploy.add_argument("--address", default=None)
    for name in ("status", "shutdown"):
        ps = serve_sub.add_parser(name)
        ps.add_argument("--address", default=None)
        if name == "status":
            ps.add_argument("-v", "--verbose", action="store_true",
                            help="include the autoscaler decision log")
            ps.add_argument("--json", action="store_true",
                            help="full detailed-status payload as JSON")
    p_serve.set_defaults(fn=cmd_serve)

    p_rl = sub.add_parser("rl", help="train / evaluate RL algorithms")
    rl_sub = p_rl.add_subparsers(dest="rl_cmd", required=True)
    pr_train = rl_sub.add_parser("train")
    pr_train.add_argument("--run", default=None,
                          help="algorithm name (PPO, DQN, SAC, ...)")
    pr_train.add_argument("-f", "--file", default=None,
                          help="tuned-example YAML (path or bundled name; "
                               "see `rt rl examples`)")
    pr_train.add_argument("--env", default=None)
    pr_train.add_argument("--config", default=None,
                          help="JSON dict of AlgorithmConfig overrides")
    pr_train.add_argument("--config-file", default=None,
                          help="YAML/JSON file of config overrides")
    pr_train.add_argument("--stop-iters", type=int, default=None,
                          help="iteration cap (default 10; with -f, the "
                               "YAML's stop block)")
    pr_train.add_argument("--stop-reward", type=float, default=None)
    pr_train.add_argument("--stop-timesteps", type=int, default=None)
    pr_train.add_argument("--checkpoint-dir", default=None)
    pr_train.add_argument("--address", default=None)
    pr_eval = rl_sub.add_parser("evaluate")
    pr_eval.add_argument("checkpoint", help="checkpoint dir from train")
    pr_eval.add_argument("--run", default=None)
    pr_eval.add_argument("--episodes", type=int, default=10)
    pr_eval.add_argument("--address", default=None)
    pr_rlhf = rl_sub.add_parser(
        "rlhf", help="run the end-to-end RLHF pipeline (placed roles, "
                     "continuous-engine generation, streamed weight sync)")
    pr_rlhf.add_argument("--address", default=None)
    pr_rlhf.add_argument("--preset", default="debug",
                         help="llama preset for all roles (default debug)")
    pr_rlhf.add_argument("--iters", type=int, default=2)
    pr_rlhf.add_argument("--prompts", type=int, default=4,
                         help="sequences per iteration")
    pr_rlhf.add_argument("--prompt-len", type=int, default=8)
    pr_rlhf.add_argument("--max-new", type=int, default=16)
    pr_rlhf.add_argument("--slots", type=int, default=4,
                         help="generation engine decode slots")
    pr_rlhf.add_argument("--seed", type=int, default=0)

    pr_ex = rl_sub.add_parser("examples",
                              help="list bundled tuned examples")
    pr_ex.add_argument("--address", default=None)
    p_rl.set_defaults(fn=cmd_rl)

    p_graf = sub.add_parser(
        "metrics-export-grafana",
        help="write Grafana dashboards + provisioning + prometheus.yml")
    p_graf.add_argument("--out", required=True)
    p_graf.add_argument("--prom-url", default="http://127.0.0.1:9090")
    p_graf.add_argument("--metrics-target", default="127.0.0.1:8265")
    p_graf.add_argument("--address", default=None,
                        help="live cluster to harvest user metrics from")
    p_graf.set_defaults(fn=cmd_export_grafana)

    p_metrics = sub.add_parser("metrics",
                               help="aggregated Prometheus metrics page")
    p_metrics.add_argument("--address", default=None)
    p_metrics.set_defaults(fn=cmd_metrics)

    p_mem = sub.add_parser(
        "memory",
        help="memory plane: per-node store usage, per-object owner table, "
             "leak suspects (util/memory.py; `ray memory` analog)")
    p_mem.add_argument("--address", default=None)
    p_mem.add_argument("--oom", action="store_true",
                       help="replay recent OOM-kill post-mortems")
    p_mem.add_argument("--device", action="store_true",
                       help="include the per-device HBM table")
    p_mem.add_argument("--limit", type=int, default=200,
                       help="per-owner / per-node object rows")
    p_mem.add_argument("--top", type=int, default=10,
                       help="rows in the largest-objects view")
    p_mem.add_argument("--leak-age", type=float, default=None,
                       help="leak-suspect age threshold seconds "
                            "(default RT_MEMORY_LEAK_AGE_S)")
    p_mem.add_argument("id", nargs="?", default=None,
                       help="with --oom: filter post-mortems by victim "
                            "worker id, object id, or node id prefix")
    p_mem.set_defaults(fn=cmd_memory)

    p_err = sub.add_parser(
        "errors",
        help="tail the categorized FailureEvent feed (death-cause "
             "taxonomy; GCS failure_events store)")
    p_err.add_argument("--address", default=None)
    p_err.add_argument("--category", default=None,
                       help="only this death-cause category "
                            "(e.g. worker_crash, oom_kill, task_error)")
    p_err.add_argument("--limit", type=int, default=200)
    p_err.add_argument("--json", action="store_true")
    p_err.add_argument("--origin", default=None,
                       choices=("chaos", "organic", "recovery"),
                       help="only chaos-injected, recovery-plane, or "
                            "organic failures")
    p_err.set_defaults(fn=cmd_errors)

    p_sched = sub.add_parser(
        "sched",
        help="placement receipts: scheduling decision records and the "
             "cross-node balance snapshot (GCS placement_events store)")
    sched_sub = p_sched.add_subparsers(dest="sched_cmd", required=True)
    ps_dec = sched_sub.add_parser(
        "decisions", help="tail the placement decision feed")
    ps_dec.add_argument("--address", default=None)
    ps_dec.add_argument("--kind", default=None,
                        help="only this decision kind (dispatch_local, "
                             "spillback, actor_place, pg_place, "
                             "warm_adopt, gang_place)")
    ps_dec.add_argument("--node", default=None,
                        help="only decisions whose chosen or origin node "
                             "id starts with this prefix")
    ps_dec.add_argument("--limit", type=int, default=200)
    ps_dec.add_argument("--json", action="store_true")
    ps_bal = sched_sub.add_parser(
        "balance", help="per-node queued+running load + imbalance CoV")
    ps_bal.add_argument("--address", default=None)
    ps_bal.add_argument("--json", action="store_true")
    p_sched.set_defaults(fn=cmd_sched)

    p_chaos = sub.add_parser(
        "chaos",
        help="fault injection: arm/disarm a seeded ChaosPlan against the "
             "live cluster (util/chaos.py)")
    chaos_sub = p_chaos.add_subparsers(dest="chaos_cmd", required=True)
    pc_arm = chaos_sub.add_parser("arm")
    pc_arm.add_argument("--address", default=None)
    pc_arm.add_argument("--plan", default=None,
                        help="JSON plan file ({seed, faults: [...]})")
    pc_arm.add_argument("--site", default=None,
                        help="single-fault shorthand: injection site name "
                             "(worker.kill, raylet.kill_worker, rpc.drop, "
                             "object.lose, oom.pressure, ...)")
    pc_arm.add_argument("--at", type=int, default=None,
                        help="fire exactly on the Nth hit of the site")
    pc_arm.add_argument("--after", type=int, default=None,
                        help="fire on every hit after the Nth")
    pc_arm.add_argument("--prob", type=float, default=None,
                        help="fire with this (seeded) probability")
    pc_arm.add_argument("--max-fires", type=int, default=None,
                        dest="max_fires")
    pc_arm.add_argument("--delay", type=float, default=None,
                        help="delay_s for rpc.delay / spill.slow")
    pc_arm.add_argument("--value", type=float, default=None,
                        help="effect value (oom.pressure fraction)")
    pc_arm.add_argument("--target", default=None,
                        help="substring match on the site's target "
                             "(fn/method/rpc name, object id)")
    pc_arm.add_argument("--seed", type=int, default=0)
    for name in ("disarm", "status"):
        pc = chaos_sub.add_parser(name)
        pc.add_argument("--address", default=None)
    p_chaos.set_defaults(fn=cmd_chaos)

    p_doc = sub.add_parser(
        "doctor",
        help="one-shot cluster health report; exit 0 healthy / 1 "
             "unhealthy / 2 unreachable (util/doctor.py)")
    p_doc.add_argument("--address", default=None)
    p_doc.add_argument("--window", type=float, default=600.0,
                       help="recency window (s) for failure/OOM findings")
    p_doc.add_argument("--queue-warn", type=int, default=100,
                       help="raylet queue depth that warrants a warning")
    p_doc.add_argument("--queue-wait-warn", type=float, default=10.0,
                       help="per-scheduling-class queue-wait p99 (s) that "
                            "grades the class as starving")
    p_doc.add_argument("--serve-p99-warn", type=float, default=5.0,
                       help="serve request p99 (s) that grades a "
                            "deployment as degraded")
    p_doc.add_argument("--imbalance-warn", type=float, default=0.5,
                       help="cross-node load CoV that, sustained over 3 "
                            "ticks, grades the cluster as imbalanced")
    p_doc.add_argument("--tick-gap-warn", type=float, default=0.5,
                       help="engine decode tick-gap (s) that, sustained "
                            "over 3 launches, grades decode as starved")
    p_doc.add_argument("--slo-warn", type=float, default=0.9,
                       help="engine TTFT/TPOT SLO-attainment ratio below "
                            "which a loaded engine is graded degraded")
    p_doc.add_argument("--bubble-warn", type=float, default=0.75,
                       help="RLHF pipeline bubble fraction that, "
                            "sustained over 3 iterations, grades the "
                            "dataflow as phase-serialized waste")
    p_doc.add_argument("--launch-gap-warn", type=float, default=0.25,
                       help="train launch-gap (s) that, sustained over 3 "
                            "launches with a stacked batch available, "
                            "grades the devices as host-starved")
    p_doc.add_argument("--data-wait-warn", type=float, default=0.25,
                       help="train data_wait share of window wall above "
                            "which the driver is graded data-starved")
    p_doc.add_argument("--json", action="store_true")
    p_doc.set_defaults(fn=cmd_doctor)

    p_eng = sub.add_parser(
        "engine",
        help="ContinuousEngine flight recorder: tick phase attribution, "
             "request lifecycles, SLO/goodput rollup (@engine/ KV "
             "snapshots, util/engine_recorder.py)")
    eng_sub = p_eng.add_subparsers(dest="engine_cmd", required=True)
    for name, what in (("stats", "per-engine SLO/goodput/phase rollup; per "
                        "compiled decode program what it does to the slot "
                        "cache (cache_donated, cache_copy_bytes_per_step) "
                        "and, for a hybrid model (models/hybrid.py), its "
                        "recurrent state (state_layout, state_donated, "
                        "state_copy_bytes_per_step, ssm_scan_chunks); "
                        "positions decode attention read over positions "
                        "live (kv_read_ratio)"),
                       ("ticks", "tail the per-tick phase records"),
                       ("requests", "tail the request lifecycle records")):
        pe = eng_sub.add_parser(name, help=what)
        pe.add_argument("--address", default=None)
        pe.add_argument("--name", default=None,
                        help="only engines whose node:name contains this")
        pe.add_argument("--limit", type=int, default=20)
        pe.add_argument("--json", action="store_true")
    p_eng.set_defaults(fn=cmd_engine)

    p_rlhf_top = sub.add_parser(
        "rlhf",
        help="RLHF pipeline flight recorder: per-role bubble "
             "attribution, orchestration tax, staleness and transfer "
             "receipts (@rlhf/ KV snapshots, util/pipeline_recorder.py)")
    rlhf_sub = p_rlhf_top.add_subparsers(dest="rlhf_cmd", required=True)
    pr_stats = rlhf_sub.add_parser(
        "stats", help="per-pipeline bubble/staleness/transfer rollup "
                      "(works postmortem — reads the GCS snapshot)")
    pr_stats.add_argument("--address", default=None)
    pr_stats.add_argument("--name", default=None,
                          help="only pipelines whose node:name contains "
                               "this")
    pr_stats.add_argument("--limit", type=int, default=8,
                          help="iteration-record tail to render")
    pr_stats.add_argument("--json", action="store_true")
    p_rlhf_top.set_defaults(fn=cmd_rlhf)

    p_train_top = sub.add_parser(
        "train",
        help="StepDriver flight recorder: per-launch phase attribution, "
             "launch-gap/data-starvation accounting, MFU-gap waterfall "
             "(@train/ KV snapshots, util/train_recorder.py)")
    train_sub = p_train_top.add_subparsers(dest="train_cmd", required=True)
    pt_stats = train_sub.add_parser(
        "stats", help="per-driver phase/gap/MFU-waterfall rollup (works "
                      "postmortem — the @train/ snapshot survives the "
                      "run)")
    pt_stats.add_argument("--address", default=None)
    pt_stats.add_argument("--name", default=None,
                          help="only drivers whose node:name contains "
                               "this")
    pt_stats.add_argument("--limit", type=int, default=8,
                          help="launch-record tail to render")
    pt_stats.add_argument("--json", action="store_true")
    p_train_top.set_defaults(fn=cmd_train)

    p_trace = sub.add_parser(
        "trace",
        help="span tree + per-phase latency tables for a task or trace "
             "(util/tracing.py phase records)")
    p_trace.add_argument("id", help="task_id (prefix ok), trace_id, "
                                    "or span_id")
    p_trace.add_argument("--address", default=None)
    p_trace.add_argument("--limit", type=int, default=10000)
    p_trace.set_defaults(fn=cmd_trace)

    args = parser.parse_args(argv)
    if args.cmd == "start" and not args.head and not args.address:
        parser.error("rt start needs --head or --address=<gcs>")
    try:
        return args.fn(args)
    except BrokenPipeError:
        # downstream reader (grep -q, head) closed the pipe after it got
        # what it wanted — success, not failure; repoint stdout at
        # /dev/null so the interpreter's exit-time flush can't re-raise
        os.dup2(os.open(os.devnull, os.O_WRONLY), sys.stdout.fileno())
        return 0


if __name__ == "__main__":
    sys.exit(main())
