"""The dashboard's browser UI: one self-contained HTML page.

Reference analog: ``dashboard/client/`` (a 183-file React SPA). Redesigned
for a zero-egress TPU pod: a single static page with no external assets,
rendered from the same ``/api/*`` REST endpoints the CLI uses (state
listings, jobs, serve apps, cluster resources, Prometheus text). Served at
``GET /`` by ``dashboard/head.py``.
"""

INDEX_HTML = r"""<!doctype html>
<html lang="en">
<head>
<meta charset="utf-8">
<meta name="viewport" content="width=device-width, initial-scale=1">
<title>ray_tpu dashboard</title>
<style>
:root {
  color-scheme: light;
  --surface-1: #fcfcfb; --surface-2: #f1f1ef;
  --text-primary: #0b0b0b; --text-secondary: #52514e; --text-muted: #7a7974;
  --border: #dddcd8;
  --series-1: #2a78d6; --series-2: #eb6834; --series-3: #1baf7a;
  --good: #0ca30c; --warning: #fab219; --serious: #ec835a;
  --critical: #d03b3b;
}
@media (prefers-color-scheme: dark) {
  :root:not([data-theme="light"]) {
    color-scheme: dark;
    --surface-1: #1a1a19; --surface-2: #242423;
    --text-primary: #ffffff; --text-secondary: #c3c2b7;
    --text-muted: #8f8e86; --border: #3a3a38;
    --series-1: #3987e5; --series-2: #d95926; --series-3: #199e70;
  }
}
:root[data-theme="dark"] {
  color-scheme: dark;
  --surface-1: #1a1a19; --surface-2: #242423;
  --text-primary: #ffffff; --text-secondary: #c3c2b7;
  --text-muted: #8f8e86; --border: #3a3a38;
  --series-1: #3987e5; --series-2: #d95926; --series-3: #199e70;
}
* { box-sizing: border-box; }
body {
  margin: 0; background: var(--surface-1); color: var(--text-primary);
  font: 14px/1.45 system-ui, -apple-system, "Segoe UI", sans-serif;
}
header {
  display: flex; align-items: baseline; gap: 12px;
  padding: 14px 20px 10px;
  border-bottom: 1px solid var(--border);
}
header h1 { font-size: 17px; margin: 0; font-weight: 650; }
header .sub { color: var(--text-muted); font-size: 12px; }
header .spacer { flex: 1; }
header button {
  background: var(--surface-2); color: var(--text-secondary);
  border: 1px solid var(--border); border-radius: 6px;
  padding: 3px 10px; font-size: 12px; cursor: pointer;
}
.tiles {
  display: grid; grid-template-columns: repeat(auto-fit, minmax(150px, 1fr));
  gap: 10px; padding: 14px 20px;
}
.tile {
  background: var(--surface-2); border: 1px solid var(--border);
  border-radius: 8px; padding: 10px 14px;
}
.tile .label {
  font-size: 11px; letter-spacing: .04em; text-transform: uppercase;
  color: var(--text-muted);
}
.tile .value { font-size: 24px; font-weight: 650; margin-top: 2px;
  font-variant-numeric: tabular-nums; }
.tile .detail { font-size: 11px; color: var(--text-secondary); }
.muted { color: var(--text-muted); font-size: 12px; font-weight: 400; }
.meter {
  margin-top: 6px; height: 6px; border-radius: 4px;
  background: color-mix(in srgb, var(--border) 60%, var(--surface-2));
  overflow: hidden;
}
.meter > div {
  height: 100%; border-radius: 4px; background: var(--series-1);
  transition: width .4s;
}
/* stacked bar of the tasks tab's per-phase breakdown; 2px surface gaps
   separate the fills. */
.bk-track {
  display: flex; gap: 2px; width: 140px; height: 8px;
  border-radius: 4px; overflow: hidden;
  background: color-mix(in srgb, var(--border) 60%, var(--surface-2));
}
.bk-seg { height: 100%; border-radius: 2px; }
/* task phase colors: wait-ish phases warm, work-ish phases cool */
.ph-queue_wait { background: var(--warning); }
.ph-worker_acquire { background: var(--serious); }
.ph-execute { background: var(--series-1); }
.ph-arg_fetch { background: var(--series-3); }
.ph-result_store { background: var(--series-2); }
.ph-other { background: var(--text-muted); }
/* engine tick-phase bar: admission/prefill warm-ish, decode cool */
.phase-bar { display: flex; gap: 2px; height: 10px; margin: 6px 0 10px;
  max-width: 420px; }
.phase-bar .ph { display: inline-block; height: 100%; border-radius: 2px;
  background: var(--text-muted); }
.ph-admission { background: var(--warning); }
.ph-kv_restore { background: var(--series-3); }
.ph-prefill { background: var(--series-2); }
.ph-decode_step { background: var(--series-1); }
.ph-token_delivery { background: var(--serious); }
.ph-swap_barrier { background: var(--critical, #d33); }
.legend { display: flex; gap: 14px; margin: 0 0 10px;
  font-size: 12px; color: var(--text-secondary); }
.legend .chip { display: inline-block; width: 9px; height: 9px;
  border-radius: 2px; margin-right: 5px; }
nav { display: flex; gap: 2px; padding: 0 20px; flex-wrap: wrap;
  border-bottom: 1px solid var(--border); }
nav button {
  background: none; border: none; border-bottom: 2px solid transparent;
  color: var(--text-secondary); padding: 7px 12px; font-size: 13px;
  cursor: pointer;
}
nav button.active {
  color: var(--text-primary); border-bottom-color: var(--series-1);
  font-weight: 600;
}
main { padding: 14px 20px 40px; }
table { border-collapse: collapse; width: 100%; font-size: 13px; }
th {
  text-align: left; color: var(--text-muted); font-weight: 600;
  font-size: 11px; letter-spacing: .04em; text-transform: uppercase;
  padding: 6px 10px; border-bottom: 1px solid var(--border);
  position: sticky; top: 0; background: var(--surface-1);
}
td {
  padding: 6px 10px; border-bottom: 1px solid var(--border);
  color: var(--text-secondary); font-variant-numeric: tabular-nums;
  max-width: 380px; overflow: hidden; text-overflow: ellipsis;
  white-space: nowrap;
}
td.id { font-family: ui-monospace, monospace; font-size: 12px; }
.status { display: inline-flex; align-items: center; gap: 5px; }
.status .dot { width: 8px; height: 8px; border-radius: 50%; }
.s-good .dot { background: var(--good); }
.s-warning .dot { background: var(--warning); }
.s-serious .dot { background: var(--serious); }
.s-critical .dot { background: var(--critical); }
.s-muted .dot { background: var(--text-muted); }
.empty { color: var(--text-muted); padding: 24px 0; }
tr.clickable { cursor: pointer; }
tr.clickable:hover td { background: var(--surface-2); }
tr.detail td { background: var(--surface-2); }
table.kv { width: auto; margin: 6px 0; }
table.kv th { text-align: left; padding-right: 14px;
  color: var(--text-secondary); border: none; }
table.kv td { border: none; font-family: ui-monospace, monospace;
  font-size: 12px; }
.stack-btn {
  background: var(--surface-1); color: var(--text-secondary);
  border: 1px solid var(--border); border-radius: 6px;
  padding: 3px 10px; font-size: 12px; cursor: pointer; margin: 4px 0;
}
.stack-out { max-height: 300px; overflow: auto; font-size: 11px; }
.tl-head { color: var(--text-muted); font-size: 12px; margin: 4px 0 10px; }
.tl-row { display: flex; align-items: center; gap: 8px; height: 18px; }
.tl-label {
  width: 180px; flex: none; overflow: hidden; text-overflow: ellipsis;
  white-space: nowrap; font-size: 11px; color: var(--text-secondary);
  font-family: ui-monospace, monospace;
}
.tl-track {
  position: relative; flex: 1; height: 12px;
  background: var(--surface-2); border-radius: 3px; overflow: hidden;
}
.tl-bar { position: absolute; top: 0; height: 100%; border-radius: 2px;
  background: var(--series-1); opacity: .9; }
.tl-bar.s-good { background: var(--good); }
.tl-bar.s-critical { background: var(--critical); }
.tl-bar.s-warning { background: var(--warning); }
.tl-wait {
  position: absolute; top: 0; height: 100%;
  background: repeating-linear-gradient(45deg, transparent,
    transparent 3px, var(--border) 3px, var(--border) 5px);
}
#error { color: var(--critical); font-size: 12px; padding: 0 20px; }
</style>
</head>
<body>
<header>
  <h1>ray_tpu</h1>
  <span class="sub" id="version"></span>
  <span class="spacer"></span>
  <span class="sub" id="updated"></span>
  <button id="pause">pause</button>
  <button id="theme">theme</button>
</header>
<div class="tiles" id="tiles"></div>
<div id="error"></div>
<nav id="tabs"></nav>
<main id="content"></main>
<script>
"use strict";
const TABS = [
  {id: "nodes", label: "Nodes", url: "/api/nodes"},
  {id: "actors", label: "Actors", url: "/api/actors"},
  {id: "jobs", label: "Jobs", url: "/api/jobs"},
  {id: "placement_groups", label: "Placement groups",
   url: "/api/placement_groups"},
  {id: "tasks", label: "Tasks", url: "/api/tasks?limit=200"},
  {id: "errors", label: "Errors", url: "/api/errors?limit=200"},
  {id: "timeline", label: "Timeline", url: "/api/tasks?limit=500"},
  {id: "objects", label: "Objects", url: "/api/objects?limit=200"},
  {id: "memory", label: "Memory", url: "/api/memory?limit=100"},
  {id: "logs", label: "Logs", url: "/api/logs?limit=300"},
  {id: "serve", label: "Serve", url: "/api/serve"},
  {id: "sched", label: "Scheduling", url: "/api/sched?limit=200"},
  {id: "engine", label: "Engine", url: "/api/engine"},
  {id: "rlhf", label: "RLHF", url: "/api/rlhf"},
  {id: "train", label: "Train", url: "/api/train"},
];
let active = "nodes", paused = false, data = {};

// --- status rendering: icon + label, never color alone ---
const STATUS_CLASS = {
  ALIVE: "s-good", RUNNING: "s-good", CREATED: "s-good",
  SUCCEEDED: "s-good", FINISHED: "s-good", COMMITTED: "s-good",
  HEALTHY: "s-good",
  PENDING: "s-warning", PENDING_CREATION: "s-warning",
  DEPLOYING: "s-warning", PREPARED: "s-warning", QUEUED: "s-warning",
  UPDATING: "s-warning",
  RESTARTING: "s-serious", RECONSTRUCTING: "s-serious",
  DEAD: "s-critical", FAILED: "s-critical", STOPPED: "s-critical",
  UNHEALTHY: "s-critical",
  // failure-plane categories (core/failure.py taxonomy)
  OOM_KILL: "s-critical", WORKER_CRASH: "s-critical",
  NODE_DEATH: "s-critical", ACTOR_RESTART_EXHAUSTED: "s-critical",
  OWNER_DIED: "s-critical", TASK_ERROR: "s-serious",
  OBJECT_LOST: "s-serious", RUNTIME_ENV_SETUP: "s-serious",
  GET_TIMEOUT: "s-warning", SCHEDULING_TIMEOUT: "s-warning",
  PG_REMOVED: "s-warning", CANCELLED: "s-muted",
};
function esc(s) {
  return String(s ?? "").replace(/[&<>"]/g,
    c => ({"&": "&amp;", "<": "&lt;", ">": "&gt;", '"': "&quot;"}[c]));
}
function statusCell(s) {
  const cls = STATUS_CLASS[String(s).toUpperCase()] || "s-muted";
  return `<span class="status ${cls}"><span class="dot"></span>` +
         `${esc(s)}</span>`;
}
function fmtRes(r) {
  if (!r || typeof r !== "object") return "";
  return Object.entries(r).map(([k, v]) => `${esc(k)}:${esc(v)}`)
    .join(" ");
}

// --- per-tab table definitions: [header, row -> cell html] ---
const COLS = {
  nodes: [
    ["Node", r => `<td class="id">${esc(r.node_id)}</td>`],
    ["Address", r => `<td>${esc(r.address || "")}</td>`],
    ["State", r => `<td>${statusCell(r.alive === false ? "DEAD"
                                     : "ALIVE")}</td>`],
    ["Labels", r => `<td>${fmtRes(r.labels)}</td>`],
    ["Total", r => `<td>${fmtRes(r.resources_total || r.resources)}</td>`],
    ["Available", r => `<td>${fmtRes(r.resources_available
                                     || r.available)}</td>`],
    ["Queued", r => `<td>${esc(r.queue_depth ?? "")}</td>`],
    ["Classes", r => `<td>${((r.sched || {}).classes || [])
      .slice(0, 3)
      .map(c => `${esc(c["class"])}:${esc(c.depth)}` +
           (c.wait_p99_s != null ? ` (p99 ${esc(c.wait_p99_s)}s)` : ""))
      .join(" ")}</td>`],
    ["Warm pool", r => { const w = (r.sched || {}).warm || {};
      const served = (w.warm_hits || 0) + (w.cold_spawns || 0);
      if (!served && !w.idle && !w.floor) return "<td></td>";
      const rate = served
        ? ` hit ${Math.round(100 * (w.warm_hits || 0) / served)}%` : "";
      return `<td>${esc(w.idle ?? 0)} idle / floor ${esc(w.floor ?? 0)}` +
             `${rate}</td>`; }],
  ],
  actors: [
    ["Actor", r => `<td class="id">${esc(r.actor_id)}</td>`],
    ["Name", r => `<td>${esc(r.name || "")}</td>`],
    ["Class", r => `<td>${esc(r.class_name || "")}</td>`],
    ["State", r => `<td>${statusCell(r.state)}</td>`],
    ["Node", r => `<td class="id">${esc(r.node_id || "")}</td>`],
    ["Restarts", r => `<td>${esc(r.num_restarts ?? 0)}</td>`],
  ],
  jobs: [
    ["Job", r => `<td class="id">${esc(r.job_id || r.submission_id)}</td>`],
    ["Entrypoint", r => `<td>${esc(r.entrypoint || "")}</td>`],
    ["Status", r => `<td>${statusCell(r.status)}</td>`],
    ["Message", r => `<td>${esc(r.message || "")}</td>`],
  ],
  placement_groups: [
    ["Group", r => `<td class="id">${esc(r.pg_id)}</td>`],
    ["Name", r => `<td>${esc(r.name || "")}</td>`],
    ["Strategy", r => `<td>${esc(r.strategy || "")}</td>`],
    ["State", r => `<td>${statusCell(r.state)}</td>`],
    ["Bundles", r => `<td>${esc((r.bundles || []).length)}</td>`],
  ],
  tasks: [
    ["Task", r => `<td class="id">${esc(r.task_id)}</td>`],
    ["Name", r => `<td>${esc(r.name || r.func_name || "")}</td>`],
    ["State", r => `<td>${statusCell(r.state || r.status)}</td>`],
    ["Node", r => `<td class="id">${esc(r.node_id || "")}</td>`],
    ["Duration", r => {
      const t = r.times || {};
      const end = t.FINISHED || t.FAILED, start = t.RUNNING || t.PENDING;
      return `<td>${end && start
        ? ((end - start).toFixed(2) + "s") : ""}</td>`;
    }],
    ["Queue ms", r => `<td>${r.phases
      ? ms(r.phases.queue_wait) : ""}</td>`],
    ["Phases", r => `<td>${phaseBar(r)}</td>`],
  ],
  objects: [
    ["Object", r => `<td class="id">${esc(r.object_id)}</td>`],
    ["Size", r => `<td>${esc(r.size ?? "")}</td>`],
    ["Locations", r => `<td class="id">${esc(
      (r.locations || []).join(" "))}</td>`],
  ],
  // failure plane: the categorized FailureEvent feed (/api/errors)
  errors: [
    ["When", r => `<td>${esc(new Date(1000 * (r.last_t || r.t || 0))
      .toLocaleTimeString())}</td>`],
    ["Category", r => `<td>${statusCell(r.category || "unknown")}</td>`],
    ["Node", r => `<td class="id">${esc(
      String(r.node_id || "").slice(0, 8))}</td>`],
    ["What", r => `<td class="id">${esc(r.name || r.task_id
      || r.actor_id || r.worker_id || "")}</td>`],
    ["Count", r => `<td>${esc(r.count ?? 1)}</td>`],
    ["Message", r => `<td>${esc(r.message || "")}</td>`],
  ],
};
function ms(v) { return v == null ? "" : (1000 * v).toFixed(2); }

// task-lifecycle phase drill-down (traced tasks; util/tracing.PHASE_ORDER)
const PHASE_ORDER = ["submit", "queue_wait", "spillback", "worker_acquire",
  "transfer", "arg_fetch", "execute", "result_store", "driver_get"];
const PHASE_CLASS = {queue_wait: "ph-queue_wait",
  worker_acquire: "ph-worker_acquire", execute: "ph-execute",
  arg_fetch: "ph-arg_fetch", result_store: "ph-result_store"};
function phaseBar(r) {
  const p = r.phases;
  if (!p) return "";
  const keys = PHASE_ORDER.filter(k => p[k] > 0)
    .concat(Object.keys(p).filter(k => !PHASE_ORDER.includes(k)));
  const total = keys.reduce((a, k) => a + (p[k] || 0), 0);
  if (!total) return "";
  const segs = keys.map(k => {
    const pct = Math.max(0, Math.min(100, 100 * p[k] / total));
    const src = k === "worker_acquire" && r.worker_source
      ? ` (${r.worker_source})` : "";
    return pct < 0.5 ? "" :
      `<div class="bk-seg ${PHASE_CLASS[k] || "ph-other"}"` +
      ` style="width:${pct.toFixed(1)}%"` +
      ` title="${esc(k)}${esc(src)} ${ms(p[k])}ms"></div>`;
  });
  return `<div class="bk-track">${segs.join("")}</div>`;
}
const PHASE_LEGEND = `<div class="legend">` +
  `<span><span class="chip ph-queue_wait"></span>queue wait</span>` +
  `<span><span class="chip ph-worker_acquire"></span>worker acquire</span>` +
  `<span><span class="chip ph-arg_fetch"></span>arg fetch</span>` +
  `<span><span class="chip ph-execute"></span>execute</span>` +
  `<span><span class="chip ph-result_store"></span>result store</span>` +
  `<span><span class="chip ph-other"></span>other</span></div>`;

function renderTiles() {
  const res = data.resources || {};
  const total = res.total || {}, avail = res.available || {};
  const nodes = data.nodes || [], actors = data.actors || [];
  const jobs = data.jobs || [];
  const tiles = [];
  const aliveN = nodes.filter(n => n.alive !== false).length;
  tiles.push(tile("Nodes", `${aliveN}`,
    nodes.length > aliveN ? `${nodes.length - aliveN} dead` : "alive"));
  const aliveA = actors.filter(a =>
    String(a.state).toUpperCase() === "ALIVE").length;
  tiles.push(tile("Actors", `${aliveA}`, `${actors.length} total`));
  const runJ = jobs.filter(j =>
    ["RUNNING", "PENDING"].includes(String(j.status).toUpperCase())).length;
  tiles.push(tile("Jobs", `${runJ}`, `${jobs.length} total`));
  for (const key of ["CPU", "TPU"]) {
    if (!(key in total)) continue;
    const t = total[key] || 0, a = avail[key] ?? t;
    const used = Math.max(0, t - a);
    const pct = t ? Math.round(100 * used / t) : 0;
    tiles.push(tile(`${key} in use`, `${used}/${t}`,
      `${pct}%`, pct));
  }
  document.getElementById("tiles").innerHTML = tiles.join("");
}
function tile(label, value, detail, meterPct) {
  const meter = meterPct === undefined ? "" :
    `<div class="meter"><div style="width:${meterPct}%"></div></div>`;
  return `<div class="tile"><div class="label">${esc(label)}</div>` +
    `<div class="value">${esc(value)}</div>` +
    `<div class="detail">${esc(detail)}</div>${meter}</div>`;
}

// --- task timeline: horizontal bars over the shared time window ---
function renderTimeline(el) {
  const rows = (data.timeline || []).filter(r => r.times
    && (r.times.RUNNING || r.times.PENDING));
  if (!rows.length) {
    el.innerHTML = `<div class="empty">no task events yet</div>`;
    return;
  }
  const now = Date.now() / 1000;
  const start = Math.min(...rows.map(r =>
    r.times.PENDING || r.times.RUNNING));
  const end = Math.max(now, ...rows.map(r =>
    r.times.FINISHED || r.times.FAILED || now));
  const span = Math.max(0.001, end - start);
  const pct = t => (100 * (t - start) / span).toFixed(2);
  const byNode = {};
  for (const r of rows) {
    (byNode[r.node_id || "(unscheduled)"] ??= []).push(r);
  }
  const lane = r => {
    const t = r.times;
    const s = t.RUNNING || t.PENDING;
    const e = t.FINISHED || t.FAILED || now;
    const state = String(r.state || r.status || "").toUpperCase();
    const cls = STATUS_CLASS[state] || "s-muted";
    const wait = t.RUNNING && t.PENDING
      ? `<div class="tl-wait" style="left:${pct(t.PENDING)}%;` +
        `width:${Math.max(0.2, pct(t.RUNNING) - pct(t.PENDING))}%"></div>`
      : "";
    return `<div class="tl-row" title="${esc(r.name || r.func_name || "")}` +
      ` ${esc(state)} ${(e - s).toFixed(2)}s">` +
      `<span class="tl-label">${esc(r.name || r.func_name || r.task_id)}` +
      `</span><div class="tl-track">${wait}` +
      `<div class="tl-bar ${cls}" style="left:${pct(s)}%;` +
      `width:${Math.max(0.3, pct(e) - pct(s))}%"></div></div></div>`;
  };
  el.innerHTML = `<div class="tl-head">window ${span.toFixed(1)}s ` +
    `(${rows.length} tasks; hatched = queued wait)</div>` +
    Object.entries(byNode).map(([n, rs]) =>
      `<h3 class="id">${esc(n)}</h3>` +
      rs.sort((a, b) => (a.times.PENDING || a.times.RUNNING || 0)
                      - (b.times.PENDING || b.times.RUNNING || 0))
        .map(lane).join("")).join("");
}

// --- per-actor drill-down: expandable full record + live stack ---
let openActor = null;
function actorDetail(r) {
  const rows = Object.entries(r).map(([k, v]) =>
    `<tr><th>${esc(k)}</th><td>${esc(
       typeof v === "object" ? JSON.stringify(v) : v)}</td></tr>`);
  return `<tr class="detail"><td colspan="6"><table class="kv">` +
    rows.join("") + `</table>` +
    `<button class="stack-btn" data-node="${esc(r.node_id || "")}">` +
    `fetch live stacks on this node</button>` +
    `<pre class="stack-out" id="stack-out"></pre></td></tr>`;
}
async function fetchStacks(nodeId) {
  const out = document.getElementById("stack-out");
  out.textContent = "collecting…";
  try {
    const d = await fetchJson(
      `/api/stacks?node_id=${encodeURIComponent(nodeId)}&timeout=3`);
    out.textContent = JSON.stringify(d, null, 2);
  } catch (e) { out.textContent = String(e); }
}

// --- memory tab: store usage by node + owner ledger + OOM post-mortems ---
function fmtBytes(n) {
  if (n == null || n < 0) return "?";
  const units = ["B", "KiB", "MiB", "GiB", "TiB"];
  let i = 0;
  while (Math.abs(n) >= 1024 && i < units.length - 1) { n /= 1024; i++; }
  return `${n.toFixed(i ? 1 : 0)} ${units[i]}`;
}
function shortOid(oid) {
  oid = String(oid || "");
  return oid.length <= 18 ? oid
    : `${oid.slice(0, 8)}..${oid.slice(-8)}`;
}
function renderMemory(el) {
  const snap = data.memory || {};
  const nodes = snap.nodes || [];
  if (!nodes.length) {
    el.innerHTML = `<div class="empty">no memory reports yet</div>`;
    return;
  }
  const nodeRows = nodes.map(n => {
    if (n.error) return `<tr><td class="id">${esc(n.node_id)}</td>` +
      `<td colspan="8">${esc(n.error)}</td></tr>`;
    const s = n.store || {};
    return `<tr><td class="id">${esc((n.node_id || "").slice(0, 8))}</td>` +
      `<td>${fmtBytes(s.used_bytes)}</td>` +
      `<td>${fmtBytes(s.capacity_bytes)}</td>` +
      `<td>${fmtBytes(s.in_mem_bytes)}</td>` +
      `<td>${fmtBytes(s.spilled_bytes)} (${esc(s.spilled_count ?? 0)})</td>` +
      `<td>${esc(s.pinned_count ?? 0)}</td>` +
      `<td>${esc(s.num_objects ?? 0)}</td>` +
      `<td>${esc(s.spills ?? 0)}/${esc(s.restores ?? 0)}</td>` +
      `<td>${esc(s.oom_kills ?? 0)}/${esc(s.pin_purges ?? 0)}</td></tr>`;
  }).join("");
  const objs = nodes.flatMap(n => (n.objects || []).map(o =>
    ({...o, node: (n.node_id || "").slice(0, 8)})))
    .sort((a, b) => (b.size || 0) - (a.size || 0)).slice(0, 30);
  const objRows = objs.map(o =>
    `<tr><td class="id">${esc(o.node)}</td>` +
    `<td class="id">${esc(shortOid(o.oid))}</td>` +
    `<td>${fmtBytes(o.size)}</td><td>${statusCell(o.state)}</td>` +
    `<td>${(o.age_s ?? 0).toFixed(1)}s</td>` +
    `<td class="id">${esc(o.owner || "")}</td>` +
    `<td>${esc(o.call_site || "")}</td></tr>`).join("");
  const suspects = (snap.leak_suspects || []).map(o =>
    `<tr><td class="id">${esc(shortOid(o.oid))}</td>` +
    `<td>${fmtBytes(o.size)}</td><td>${esc(o.local_refs ?? "")}</td>` +
    `<td>${(o.age_s ?? 0).toFixed(0)}s</td>` +
    `<td>${esc(o.call_site || "")}</td></tr>`).join("");
  const ooms = (snap.oom_kills || []).map(ev => {
    const v = ev.victim || {}, m = ev.node_memory || {};
    return `<tr><td>${esc(new Date(1000 * (ev.t || 0))
        .toLocaleTimeString())}</td>` +
      `<td class="id">${esc((ev.node_id || "").slice(0, 8))}</td>` +
      `<td>${esc(v.role || "")} ${esc((v.worker_id || "").slice(0, 8))}` +
      `</td><td>${fmtBytes(v.rss)}</td>` +
      `<td>${esc(v.task || v.actor_id || "(idle)")}</td>` +
      `<td>${fmtBytes(m.used)} / ${fmtBytes(m.total)}</td></tr>`;
  }).join("");
  el.innerHTML =
    `<h3>Object store by node</h3><table><tr><th>Node</th>` +
    `<th>Shm used</th><th>Capacity</th><th>In-mem</th><th>Spilled</th>` +
    `<th>Pins</th><th>Objects</th><th>Spills/restores</th>` +
    `<th>OOM/pin-purges</th></tr>${nodeRows}</table>` +
    `<h3>Largest objects</h3>` +
    (objs.length ? `<table><tr><th>Node</th><th>Object</th><th>Size</th>` +
      `<th>State</th><th>Age</th><th>Owner</th><th>Call site</th></tr>` +
      `${objRows}</table>` : `<div class="empty">store empty</div>`) +
    `<h3>Leak suspects</h3>` +
    (suspects ? `<table><tr><th>Object</th><th>Size</th>` +
      `<th>Local refs</th><th>Age</th><th>Call site</th></tr>` +
      `${suspects}</table>` : `<div class="empty">none</div>`) +
    `<h3>OOM kills</h3>` +
    (ooms ? `<table><tr><th>When</th><th>Node</th><th>Victim</th>` +
      `<th>RSS</th><th>Running</th><th>Node memory</th></tr>` +
      `${ooms}</table>` : `<div class="empty">none recorded</div>`);
}

// --- logs tab: the raylets' worker-log rings ---
function renderLogs(el) {
  const rows = data.logs || [];
  if (!rows.length) {
    el.innerHTML = `<div class="empty">no worker log lines yet</div>`;
    return;
  }
  el.innerHTML = `<div class="tl-head">${rows.length} line(s) — filter ` +
    `with /api/logs?node=&amp;worker=</div>` +
    `<pre class="stack-out" style="max-height:70vh">` +
    rows.map(e => `${esc((e.node_id || "").slice(0, 8))} ` +
      `${esc((e.worker_id || "").slice(0, 8))} ${esc(e.line)}`)
      .join("\n") + `</pre>`;
}

// --- scheduling tab: placement decision receipts + cross-node balance ---
function renderSched(el) {
  const payload = data.sched || {};
  const bal = payload.balance || {};
  const nodes = bal.nodes || [];
  const maxLoad = Math.max(1, ...nodes.map(n => n.load || 0));
  const bars = nodes.map(n =>
    `<tr><td class="id">${esc((n.node_id || "").slice(0, 8))}</td>` +
    `<td>${esc(n.queued ?? 0)}</td><td>${esc(n.running ?? 0)}</td>` +
    `<td style="min-width:180px"><div class="meter"><div ` +
    `style="width:${Math.round(100 * (n.load || 0) / maxLoad)}%">` +
    `</div></div></td><td>${esc(n.load ?? 0)}</td></tr>`).join("");
  const rows = (payload.decisions || []).slice().reverse().map(d => {
    const when = d.last_t || d.t
      ? new Date(1000 * (d.last_t || d.t)).toLocaleTimeString() : "";
    const who = d.name || d.task_id || d.actor_id || d.pg_id || "";
    const hop = d.kind === "spillback"
      ? `${esc(String(d.from_node || "").slice(0, 8))} &rarr; ` +
        `${esc(String(d.node_id || "").slice(0, 8))} ` +
        `(hops ${esc(d.hops ?? 1)})` : "";
    return `<tr><td>${esc(when)}</td>` +
      `<td>${esc(d.kind || "")}</td>` +
      `<td class="id">${esc(String(d.node_id || "").slice(0, 8))}</td>` +
      `<td>${esc(d.reason || "")}</td>` +
      `<td class="id">${esc(String(who).slice(0, 16))}</td>` +
      `<td>${esc(d.count ?? 1)}</td><td>${hop}</td>` +
      `<td>${esc((d.candidates || []).length)}</td></tr>`;
  }).join("");
  const cov = typeof bal.cov === "number" ? bal.cov.toFixed(3) : "?";
  el.innerHTML =
    `<h3>Cross-node balance <span class="muted">load CoV ${esc(cov)}` +
    `</span></h3>` +
    (nodes.length ? `<table><tr><th>Node</th><th>Queued</th>` +
      `<th>Running</th><th>Load</th><th></th></tr>${bars}</table>`
      : `<div class="empty">no balance samples yet</div>`) +
    `<h3>Placement decisions</h3>` +
    (rows ? `<table><tr><th>When</th><th>Kind</th><th>Node</th>` +
      `<th>Reason</th><th>What</th><th>Count</th><th>Hop</th>` +
      `<th>Candidates</th></tr>${rows}</table>`
      : `<div class="empty">none recorded</div>`);
}

// --- engine tab: ContinuousEngine flight-recorder snapshots ---
const ENGINE_PHASES = ["record", "admission", "kv_restore", "prefill",
                       "decode_step", "token_delivery", "swap_barrier",
                       "idle_wait"];
function renderEngine(el) {
  const payload = data.engine || {};
  const engines = payload.engines || [];
  if (!engines.length) {
    el.innerHTML = `<div class="empty">no engine flight-recorder ` +
      `snapshots — start a ContinuousEngine (RT_ENGINE_RECORDER=1)</div>`;
    return;
  }
  const ms = v => v == null ? "" : (1e3 * v).toFixed(1);
  el.innerHTML = engines.map(snap => {
    const s = snap.summary || {};
    const phases = s.phase_s || {};
    const wall = Math.max(1e-9, s.tick_wall_s || 0);
    const bar = ENGINE_PHASES.filter(p => phases[p] > 0).map(p =>
      `<span class="ph ph-${esc(p)}" title="${esc(p)} ` +
      `${(100 * phases[p] / wall).toFixed(1)}%" style="width:` +
      `${Math.max(1, Math.round(100 * phases[p] / wall))}px"></span>`)
      .join("");
    const att = (label, v, p99, tgt) => v == null ? "" :
      `${label} ${(100 * v).toFixed(1)}%` +
      (p99 != null ? ` (p99 ${ms(p99)}ms / tgt ${ms(tgt)}ms)` : "");
    const reqs = (snap.requests || []).slice().reverse().map(r =>
      `<tr><td class="id">${esc(String(r.request_id ?? r.rid ?? "")
        .slice(0, 16))}</td>` +
      `<td>${statusCell(String(r.state || "").toUpperCase())}</td>` +
      `<td>${esc(r.queue_wait_ms ?? "")}</td>` +
      `<td>${esc(r.prompt_tokens ?? 0)}/${esc(r.cached_tokens ?? 0)}</td>` +
      `<td>${esc(r.tokens ?? 0)}</td><td>${esc(r.decode_ticks ?? 0)}</td>` +
      `<td>${esc(r.ttft_ms ?? "")}</td><td>${esc(r.tpot_ms ?? "")}</td>` +
      `</tr>`).join("");
    return `<h3>${esc(snap.name || "engine")} <span class="muted">` +
      `${esc(String(snap.node || "").slice(0, 8))}:${esc(snap.pid || "")}` +
      `</span></h3>` +
      `<div class="muted">ticks ${esc(s.window_ticks ?? 0)} · active ` +
      `${esc(s.active ?? 0)}/${esc(s.max_slots ?? "?")} · ` +
      `${att("TTFT", s.ttft_attainment, s.ttft_p99_s, s.ttft_slo_s)} · ` +
      `${att("TPOT", s.tpot_attainment, s.tpot_p99_s, s.tpot_slo_s)} · ` +
      `goodput ${(s.goodput_tok_s || 0).toFixed(1)} tok/s ` +
      `(capacity ${(s.capacity_tok_s || 0).toFixed(1)}) · ` +
      `decode-eff ${((s.decode_efficiency || 0) * 100).toFixed(1)}% · ` +
      `gap p99 ${ms(s.tick_gap_p99_s)}ms · overhead ` +
      `${((s.overhead_frac || 0) * 100).toFixed(3)}%</div>` +
      `<div class="phase-bar">${bar}</div>` +
      (reqs ? `<table><tr><th>Request</th><th>State</th>` +
        `<th>Queue ms</th><th>Prompt/cached</th><th>Tokens</th>` +
        `<th>Ticks</th><th>TTFT ms</th><th>TPOT ms</th></tr>${reqs}` +
        `</table>` : `<div class="empty">no request records yet</div>`);
  }).join("");
}

// --- rlhf tab: RLHFPipeline flight-recorder snapshots ---
const RLHF_ROLES = ["generator", "reference", "reward", "learner"];
function renderRlhf(el) {
  const payload = data.rlhf || {};
  const pipes = payload.pipelines || [];
  if (!pipes.length) {
    el.innerHTML = `<div class="empty">no RLHF flight-recorder ` +
      `snapshots — run an RLHFPipeline (RT_RLHF_RECORDER=1)</div>`;
    return;
  }
  const pct = v => v == null ? "" : (100 * v).toFixed(1) + "%";
  el.innerHTML = pipes.map(snap => {
    const s = snap.summary || {};
    const stale = s.staleness || {};
    const busy = s.role_busy_frac || {};
    const idle = s.role_idle_frac || {};
    const roles = RLHF_ROLES.filter(r => r in busy || r in idle).map(r =>
      `<tr><td>${esc(r)}</td><td>${pct(busy[r])}</td>` +
      `<td>${pct(idle[r])}</td></tr>`).join("");
    const rc = s.receipt_last || {};
    const receipt = Object.keys(rc).length ?
      `<div class="muted">last shipment v${esc(rc.version ?? "?")} · ` +
      `${((rc.nbytes || 0) / 1e6).toFixed(1)}MB/${esc(rc.n_leaves ?? 0)} ` +
      `leaves · pump ${((rc.pump_wall_s || 0) * 1e3).toFixed(1)}ms · ` +
      `fetch ${((rc.fetch_wall_s || 0) * 1e3).toFixed(1)}ms · barrier ` +
      `${((rc.barrier_drain_s || 0) * 1e3).toFixed(1)}ms · swap ` +
      `${((rc.swap_apply_s || 0) * 1e3).toFixed(1)}ms</div>` : "";
    const iters = (snap.iterations || []).slice().reverse().map(r =>
      r.state === "interrupted" ?
        `<tr><td>${esc(r.seq ?? "")}</td>` +
        `<td>${statusCell("FAILED")}</td>` +
        `<td colspan="5">interrupted in ${esc(r.phase || "?")} ` +
        `${esc(String(r.error || "").slice(0, 60))}</td></tr>` :
        `<tr><td>${esc(r.iteration ?? r.seq ?? "")}</td>` +
        `<td>${statusCell("FINISHED")}</td>` +
        `<td>${esc(r.wall_ms ?? "")}</td><td>${pct(r.bubble_fraction)}</td>` +
        `<td>${pct(r.coverage)}</td><td>${esc(r.staleness ?? 0)}</td>` +
        `<td>${esc(r.tokens ?? 0)}</td></tr>`).join("");
    return `<h3>${esc(snap.name || "rlhf")} <span class="muted">` +
      `${esc(String(snap.node || "").slice(0, 8))}:${esc(snap.pid || "")}` +
      `</span></h3>` +
      `<div class="muted">iterations ${esc(s.iterations_total ?? 0)} ` +
      `(${esc(s.interrupted_total ?? 0)} interrupted) · bubble ` +
      `${pct(s.bubble_fraction)} (last ${pct(s.bubble_last)}) · coverage ` +
      `${pct(s.coverage)} · staleness p99 ${esc(stale.p99 ?? 0)} ` +
      `(max ${esc(stale.max ?? 0)}) · overhead ` +
      `${((s.overhead_frac || 0) * 100).toFixed(3)}%</div>` +
      (roles ? `<table><tr><th>Role</th><th>Busy</th><th>Idle</th></tr>` +
        `${roles}</table>` : "") + receipt +
      (iters ? `<table><tr><th>Iter</th><th>State</th><th>Wall ms</th>` +
        `<th>Bubble</th><th>Coverage</th><th>Staleness</th><th>Tokens</th>` +
        `</tr>${iters}</table>` :
        `<div class="empty">no iteration records yet</div>`);
  }).join("");
}

// --- train tab: StepDriver flight-recorder snapshots ---
function renderTrain(el) {
  const payload = data.train || {};
  const drivers = payload.drivers || [];
  if (!drivers.length) {
    el.innerHTML = `<div class="empty">no train flight-recorder ` +
      `snapshots — run a fused StepDriver (RT_TRAIN_RECORDER=1)</div>`;
    return;
  }
  const pct = v => v == null ? "" : (100 * v).toFixed(1) + "%";
  const mfu = v => v == null ? "" : v.toFixed(4);
  el.innerHTML = drivers.map(snap => {
    const s = snap.summary || {};
    const wf = s.waterfall || {};
    const cost = wf.mfu_cost || {};
    const buckets = Object.entries(cost).filter(([, v]) => v > 0).map(
      ([b, v]) => `<tr><td>${esc(b)}</td>` +
        `<td>${esc(((wf.buckets_s || {})[b] ?? wf.uncovered_s ?? 0)
          .toFixed(3))}s</td><td>${mfu(v)}</td></tr>`).join("");
    const launches = (snap.launches || []).slice().reverse().map(r => {
      const pm = r.phases_ms || {};
      return `<tr><td>${esc(r.seq ?? "")}</td>` +
        `<td>${statusCell(r.done ? "FINISHED" : "RUNNING")}</td>` +
        `<td>${esc(r.k ?? "")}</td>` +
        `<td>${esc((r.wall_ms ?? 0).toFixed(1))}</td>` +
        `<td>${esc((pm.data_wait ?? 0).toFixed(1))}</td>` +
        `<td>${esc((pm.dispatch ?? 0).toFixed(1))}</td>` +
        `<td>${esc((pm.device_compute ?? 0).toFixed(1))}</td>` +
        `<td>${esc((pm.host_tax ?? 0).toFixed(1))}</td>` +
        `<td>${r.gap_ms != null ? esc(r.gap_ms.toFixed(1)) : ""}</td>` +
        `<td>${esc(r.tokens ?? 0)}</td></tr>`;
    }).join("");
    return `<h3>${esc(snap.name || "train")} <span class="muted">` +
      `${esc(String(snap.node || "").slice(0, 8))}:${esc(snap.pid || "")}` +
      `</span></h3>` +
      `<div class="muted">launches ${esc(s.launches_total ?? 0)} ` +
      `(${esc(s.compiles ?? 0)} compiled) · steps ` +
      `${esc(s.steps_total ?? 0)} · ${esc(s.tokens_per_s ?? 0)} tok/s · ` +
      `phase coverage ${pct(s.phase_sum_ratio)} · gap p99 ` +
      `${((s.launch_gap_p99_s || 0) * 1e3).toFixed(1)}ms · data_wait ` +
      `${pct(s.data_wait_frac)} · overhead ` +
      `${((s.overhead_frac || 0) * 100).toFixed(3)}%</div>` +
      (wf.raw_mfu != null ?
        `<div class="muted">MFU waterfall: raw ${mfu(wf.raw_mfu)} → ` +
        `achieved ${mfu(wf.achieved_mfu)} (gap ${pct(s.mfu_gap_frac)}, ` +
        `marginal ${mfu(s.marginal_mfu)})</div>` : "") +
      (buckets ? `<table><tr><th>Lost to</th><th>Wall</th>` +
        `<th>MFU cost</th></tr>${buckets}</table>` : "") +
      (launches ? `<table><tr><th>Launch</th><th>State</th><th>K</th>` +
        `<th>Wall ms</th><th>Data ms</th><th>Dispatch ms</th>` +
        `<th>Device ms</th><th>Host-tax ms</th><th>Gap ms</th>` +
        `<th>Tokens</th></tr>${launches}</table>` :
        `<div class="empty">no launch records yet</div>`);
  }).join("");
}

function renderTable() {
  const el = document.getElementById("content");
  if (active === "timeline") { renderTimeline(el); return; }
  if (active === "memory") { renderMemory(el); return; }
  if (active === "logs") { renderLogs(el); return; }
  if (active === "sched") { renderSched(el); return; }
  if (active === "engine") { renderEngine(el); return; }
  if (active === "rlhf") { renderRlhf(el); return; }
  if (active === "train") { renderTrain(el); return; }
  if (active === "serve") {
    const payload = data.serve || {};
    const apps = payload.applications || payload;
    const decisions = payload.decisions || [];
    const proxies = payload.proxies || [];
    const names = Object.keys(apps);
    const ms = v => v ? (1e3 * v).toFixed(1) : "0.0";
    el.innerHTML = (proxies.length > 1 ?
      `<div class="muted">proxies: ` + proxies.map(p =>
        `${esc(p.proxy)}:${esc(p.port)}`).join(", ") + `</div>` : "") +
    (names.length ? "" :
      `<div class="empty">no serve applications</div>`) + names.map(n => {
      const app = apps[n] || {};
      const deps = app.deployments || app;
      return `<h3>${esc(n)} ${statusCell(app.status || "RUNNING")}` +
        (app.route_prefix ? ` <span class="muted">${esc(app.route_prefix)}` +
         `</span>` : ``) + `</h3>` +
        `<table><tr><th>Deployment</th><th>Replicas</th><th>Target</th>` +
        `<th>Ongoing</th><th>Queue</th><th>Slots</th><th>KV hit</th>` +
        `<th>p50</th><th>p99</th><th>QPS</th></tr>` +
        Object.entries(deps).map(([d, info]) => {
          const s = (info && info.stats) || {};
          const slots = s.cb_slots
            ? `${esc(s.cb_active ?? 0)}/${esc(s.cb_slots)}` : "";
          const kv = ("kv_hit_rate" in s)
            ? `${Math.round(100 * s.kv_hit_rate)}% ` +
              `${((s.kv_bytes || 0) / 1e6).toFixed(1)}MB` : "";
          return `<tr><td>${esc(d)}</td>` +
            `<td>${esc((info && (info.num_replicas ?? info.replicas))
                       ?? "")}</td>` +
            `<td>${esc((info && info.target) ?? "")}</td>` +
            `<td>${esc(s.ongoing ?? 0)}</td>` +
            `<td>${esc(s.queue_depth ?? 0)}</td>` +
            `<td>${slots}</td>` +
            `<td>${kv}</td>` +
            `<td>${ms(s.p50_s)} ms</td><td>${ms(s.p99_s)} ms</td>` +
            `<td>${esc(s.qps ?? 0)}</td></tr>`;
        }).join("") + `</table>`;
    }).join("") +
    `<h3>Autoscaler decisions</h3>` +
    (decisions.length ? `<table><tr><th>When</th><th>Deployment</th>` +
      `<th>Target</th><th>Why</th></tr>` +
      decisions.slice().reverse().map(d => {
        const trig = d.trigger || {};
        const when = d.t ? new Date(d.t * 1000).toLocaleTimeString() : "";
        return `<tr><td>${esc(when)}</td>` +
          `<td>${esc(d.app)}/${esc(d.deployment)}</td>` +
          `<td>${esc(d.old_target)} &rarr; ${esc(d.new_target)} ` +
          `(${esc(d.direction || "")})</td>` +
          `<td>ongoing_avg=${esc(trig.ongoing_avg ?? 0)} ` +
          `queue=${esc(trig.queue_depth ?? 0)} ` +
          `p99=${ms(trig.p99_s)}ms qps=${esc(trig.qps ?? 0)}</td></tr>`;
      }).join("") + `</table>`
      : `<div class="empty">none recorded</div>`);
    return;
  }
  let rows = data[active] || [];
  if (active === "errors") rows = rows.slice().reverse();  // newest first
  const cols = COLS[active];
  if (!rows.length) {
    el.innerHTML = `<div class="empty">no ${esc(active)} yet</div>`;
    return;
  }
  el.innerHTML = (active === "tasks" && rows.some(r => r.phases)
    ? PHASE_LEGEND : "") + `<table><tr>` +
    cols.map(c => `<th>${esc(c[0])}</th>`).join("") + `</tr>` +
    rows.map(r => {
      const id = active === "actors" ? r.actor_id : null;
      const open = id && id === openActor;
      return `<tr${id ? ` class="clickable" data-actor="${esc(id)}"`
                      : ""}>` +
        cols.map(c => c[1](r)).join("") + `</tr>` +
        (open ? actorDetail(r) : "");
    }).join("") + `</table>`;
}

function renderTabs() {
  document.getElementById("tabs").innerHTML = TABS.map(t =>
    `<button data-id="${t.id}" class="${t.id === active ? "active" : ""}">` +
    `${esc(t.label)}</button>`).join("");
}

async function fetchJson(url) {
  const resp = await fetch(url);
  if (!resp.ok) throw new Error(`${url}: HTTP ${resp.status}`);
  return resp.json();
}
async function refresh(force) {
  if (paused && !force) return;
  try {
    const [nodes, actors, jobs, resources, tab] = await Promise.all([
      fetchJson("/api/nodes"), fetchJson("/api/actors"),
      fetchJson("/api/jobs"), fetchJson("/api/cluster_resources"),
      fetchJson(TABS.find(t => t.id === active).url),
    ]);
    data.nodes = nodes; data.actors = actors; data.jobs = jobs;
    data.resources = resources;
    data[active] = active === "serve" ? (tab || {}) : tab;
    renderTiles(); renderTable();
    document.getElementById("updated").textContent =
      "updated " + new Date().toLocaleTimeString();
    document.getElementById("error").textContent = "";
  } catch (e) {
    document.getElementById("error").textContent = String(e);
  }
}

document.getElementById("tabs").addEventListener("click", e => {
  const id = e.target.dataset && e.target.dataset.id;
  if (!id) return;
  active = id; renderTabs();
  refresh(true);  // tab switch renders even while paused
});
document.getElementById("content").addEventListener("click", e => {
  const btn = e.target.closest(".stack-btn");
  if (btn) { fetchStacks(btn.dataset.node); return; }
  const row = e.target.closest("tr[data-actor]");
  if (!row) return;
  const id = row.dataset.actor;
  openActor = openActor === id ? null : id;
  renderTable();
});
document.getElementById("pause").addEventListener("click", e => {
  paused = !paused;
  e.target.textContent = paused ? "resume" : "pause";
});
document.getElementById("theme").addEventListener("click", () => {
  const root = document.documentElement;
  const cur = root.dataset.theme ||
    (matchMedia("(prefers-color-scheme: dark)").matches ? "dark" : "light");
  root.dataset.theme = cur === "dark" ? "light" : "dark";
});
fetchJson("/api/version").then(v => {
  document.getElementById("version").textContent =
    `${v.framework} ${v.version}`;
}).catch(() => {});
renderTabs();
refresh();
setInterval(refresh, 5000);
</script>
</body>
</html>
"""
