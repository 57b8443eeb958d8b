"""What a kernel notes of itself: how, under which key, and in which words.

A kernel's plan (a tiling, a chunking, which implementation a shape takes)
is static per traced shape, so it is noted where the kernel is traced:
``note(kind, plan)`` in the kernel's own module, inside a ``noting(into)``
scope that whoever traces opens (``StepDriver`` round its launches, into
its recorder's ``plans``). ``TrainRecorder`` hands the dict on as it is and
``rt train stats`` prints ``sentences`` of it: a new kernel adds a
``note`` call in its file and a sentence here, and edits neither the
driver, the recorder nor the CLI.

Imports neither ``jax`` nor anything of ``ray_tpu``: ``rt train stats``
runs in a process that loads no backend.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, Dict, Iterator

_noting = threading.local()

# the one kind of which a step has several (a flash kernel a shape and
# direction): its key holds a list of distinct plans, every other a dict
LISTED = "flash"
# the keys ``TrainRecorder.launch_totals()``, and through it the trainer's
# ``train_launches`` span, carries where they are not empty: what a reader
# without the worker takes (``benchmark/layer_metrics/eva_tile_waste.py``)
SPAN_KEYS = ("eva_plan", "hyper_plan", "sparse_plan")


def key(kind: str) -> str:
    return f"{kind}_plans" if kind == LISTED else f"{kind}_plan"


@contextlib.contextmanager
def noting(into: Dict[str, Any]) -> Iterator[None]:
    """Within the scope, what a kernel traced in this thread notes of
    itself is kept in ``into`` under ``key(kind)``. A plan is static per
    traced shape, so the scope belongs round the call that traces."""
    was = getattr(_noting, "into", None)
    _noting.into = into
    try:
        yield
    finally:
        _noting.into = was


def note(kind: str, plan: Dict[str, Any]) -> None:
    """Keep ``plan`` under ``key(kind)`` in the scope's sink; nothing
    outside a scope."""
    into = getattr(_noting, "into", None)
    if into is None:
        return
    if kind == LISTED:
        kept = into.setdefault(key(kind), [])
        if plan not in kept:
            kept.append(plan)
    else:
        into.setdefault(key(kind), {}).update(plan)


def copied(plans: Dict[str, Any]) -> Dict[str, Any]:
    """``plans`` as a reader may keep it: each plan a copy, and every key
    that has a sentence present, empty where the step has no such kernel."""
    out = {k: ([] if k == key(LISTED) else {}) for k in DESCRIBE}
    for k, p in plans.items():
        out[k] = [dict(q) for q in p] if isinstance(p, list) else dict(p)
    return out


def for_span(plans: Dict[str, Any]) -> Dict[str, Any]:
    """``SPAN_KEYS``' plans, each a copy, where they are not empty."""
    return {k: dict(plans[k]) for k in SPAN_KEYS if plans.get(k)}


# ------------------------------------------------ what `rt train stats` prints

def _flash(fp: Dict[str, Any]) -> str:
    return (f"flash {fp['kind']} s{fp['seq_q']}x{fp['seq_k']} "
            f"d{fp['head_dim']}: tile {fp['block_q']}x"
            f"{fp['block_k']}, {fp['live_steps']} of "
            f"{fp['grid_steps']} grid steps live"
            + (f", {fp['edge_steps']} of them crossed by an edge, "
               f"sub-tile {fp['sub_block'][0]}x{fp['sub_block'][1]}"
               if fp.get("edge_steps") else "")
            + (f", window {fp['window']}" if fp.get("window") else "")
            + (f", under a choice of {fp['topk']} keys a query"
               if fp.get("topk") else ""))


def _kda(kp: Dict[str, Any]) -> str:
    return (f"kda: {kp['chunks']} chunks of {kp['chunk']} in "
            f"{kp['segments']} segment(s), sub-block {kp['sub_block']}, "
            f"{kp['heads']} heads {kp['d_k']}x{kp['d_v']}, states at "
            f"the chunks' starts "
            f"{kp['boundary_state_bytes'] / 2**20:.0f} MiB a layer, "
            f"a chunk's insides: "
            + ("a Pallas kernel pair" if kp["impl"] == "pallas_insides"
               else "XLA") + f" ({kp['impl']})")


def _eva(ep: Dict[str, Any]) -> str:
    return (f"eva: {ep['windows']} window(s) of {ep['window']}, "
            f"{ep['chunks']} chunks of {ep['chunk']} a row, a query "
            f"sees at most {ep['summaries_seen']} summaries, "
            f"{ep['heads']} heads of {ep['head_dim']}; score tiles "
            f"({ep['block']} rows x {ep['block']} keys or "
            f"{ep['summary_block']} summaries) visited / needed "
            f"{ep['tiles_visited']} / {ep['tiles_needed']} a head "
            f"({ep['impl']})")


def _hyper(hp: Dict[str, Any]) -> str:
    return (f"hyper-connections: a stream of {hp['rows']} rows of "
            f"{hp['d_model']}, {hp['sinkhorn_iters']} Sinkhorn "
            f"iterations a half layer; the least passes over the "
            f"stream move {hp['stream_bytes_fwd'] / 1e3:.1f} KB forward"
            f" and {hp['stream_bytes_bwd'] / 1e3:.1f} KB backward a "
            f"token and half layer"
            + (f", the four calls' blocks "
               f"{hp['stream_bytes_moved_fwd'] / 1e3:.1f} and "
               f"{hp['stream_bytes_moved_bwd'] / 1e3:.1f} KB, "
               f"{hp['tile_tokens']} tokens a grid step"
               if hp.get("tile_tokens") else "")
            + f" ({hp['impl']}; {hp['layout']})")


def _sparse(sp: Dict[str, Any]) -> str:
    return (f"sparse attention: an indexer of {sp['index_heads']} heads of "
            f"{sp['index_head_dim']} keeps {sp['topk']} keys a query of "
            f"{sp['seq']}: {sp['pairs_chosen']} of {sp['pairs_live']} causal "
            f"pairs a row of the batch ("
            f"{100 * sp['pairs_chosen'] / sp['pairs_live']:.1f}%); scores "
            f"{sp['block_rows']} rows a block in {sp['spans']} span(s), "
            f"{sp['block_score_bytes'] / 2**20:.0f} MiB a block, the "
            f"threshold in {sp['threshold_passes']} passes of "
            f"{sp['counts_a_pass']} counts, the choice "
            f"{sp['choice_bytes'] / 2**20:.0f} MiB a layer; the loss's target "
            + (f"a Pallas call of {sp['target_tile']} keys a grid step"
               if sp.get("target_tile") else "in XLA")
            + f" ({sp.get('target_impl', 'xla')})")


def _chosen(routing: Dict[str, Any]) -> Iterator[str]:
    """What a step counted of its indexers' choices (``summary()``'s
    ``routing``, where the counters of every kind are folded)."""
    live = routing.get("index_pairs_live")
    if live:
        kept = routing.get("index_pairs_chosen", 0)
        yield (f"sparse attention: {kept} of {live} causal pairs chosen "
               f"({100 * kept / live:.2f}%), "
               f"{routing.get('index_rows_over_k', 0)} rows where ties kept "
               f"more than the indexer's count")


DESCRIBE: Dict[str, Callable[[Dict[str, Any]], str]] = {
    key("flash"): _flash, key("kda"): _kda, key("eva"): _eva,
    key("hyper"): _hyper, key("sparse"): _sparse}


def sentences(summary: Dict[str, Any]) -> Iterator[str]:
    """A sentence a plan that ``summary`` (``TrainRecorder.summary()``, or a
    snapshot of it) holds and ``DESCRIBE`` has words for."""
    for k, describe in DESCRIBE.items():
        noted = summary.get(k) or []
        for plan in noted if isinstance(noted, list) else [noted]:
            yield describe(plan)
    yield from _chosen(summary.get("routing") or {})
