"""Mixtral-style sparse-MoE transformer — the second flagship model family.

TPU-first design (no reference counterpart — Ray ships no model code; the
recipe is the public GShard/Switch capacity formulation): the router performs
STATIC top-k capacity dispatch, so every tensor shape is fixed at trace
time and XLA tiles the expert FFNs onto the MXU as one batched einsum over
the ``[E, C, d]`` capacity buffers. The routed rows move into and out of
those buffers by index (``_dispatch``, ``_combine``: a slot reads its
token's row, a token sums its K slots' rows, and each move's backward is the
other move); no tensor is ``[G, E, C]``. Where ``n_experts`` splits evenly
over the mesh's ``ep`` x ``fsdp`` a device owns whole experts
(``sharding_rules``): the tokens ``[G, d]`` are all-gathered to the
experts' owners, each of which reads the rows of its own slots; back, each
owner reads for every token what its own experts hold and the partial
``[G, d]`` outputs are reduce-scattered over the tokens' axes; nothing
``[E, C, .]`` crosses devices. Where it does not, experts go over ``ep``
alone and fsdp splits the model dim, which costs an all-reduce of every
``[E, C, f]`` product. Attention blocks, RoPE, norms and the chunked loss
are shared with :mod:`ray_tpu.models.llama`.

Routing (per token): softmax router logits -> top-k experts -> each chosen
token takes a slot in its expert's capacity buffer
(``capacity_factor * tokens * top_k / n_experts`` slots per expert);
overflow tokens drop that expert (standard Switch behavior — the residual
stream carries them).
Load-balancing aux loss: ``n_experts * sum_e(fraction_e * prob_e)``.

The patterned form (``MoEConfig.layer_kinds`` set; Trinity's ``afmoe``,
Kimi Linear): leading dense layers and then expert layers, each layer's
mixer one of the five ``ATTN_KINDS`` (attention rotated inside a band
(``"window"``) or unrotated and full (``"full"``), both
``llama.attention_half``; a delta-rule linear attention (``"kda"``),
latent attention (``"mla"``) or grouped-query attention over the positions
a learned indexer picks (``"sparse"``), all ``models/mixers.py``), a router
that may be a sigmoid whose selection adds a bias the gates do not see, a
shared expert beside the routed ones where the config has one, and a layer
that may hold a share of its experts:
``n_experts_held`` of ``n_experts``, the first ones, as one chip of an
expert-parallel layer does. The router keeps its whole width and its K
choices, capacity is reckoned from the whole count, the buffers are
``[held, C, d]``, and an assignment to an expert that lives elsewhere is no
drop and adds nothing here: what the absent experts would have added is
left out (on one chip the layer runs without its exchange, and nothing
stands in for the other chips). Two more things a patterned config may have
(``ops/hyper.py``, ``_mtp``): a residual stream of ``hc_mult`` rows that
every half layer reads and writes through a hyper-connection in place of the
plain sum (``_read``, ``_join``; the halves themselves return their branch
and add nothing), and a multi-token-prediction module in the loss, one more
expert layer on a stream started from the trunk's last hidden state and the
next token's embedding, whose cross entropy of the token after next joins
the loss at ``mtp_weight``. ``forward_hidden`` walks the dense segment
layer by layer and the expert layers period by period (``_walk``), the
kinds inside a period static and the mixers' leaves stacked by kind, since a
``kda`` layer and an ``mla`` layer hold different ones (``_pick``); it also
counts its routing
(``ROUTING_COUNTERS``), and a step moves the selection bias by what it
counted (``buffer_updates``). A ``sparse`` layer hands back a loss term
of its own, the indexer's, and three counts (``INDEX_COUNTERS``): the walk
sums them over the layers and ``loss_and_stats`` adds the term at
``index_loss_coef`` times its mean over all layers; a config without the
kind carries neither.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, mixers
from ray_tpu.ops import hyper, sparse_index
from ray_tpu.ops.rope import Yarn
from ray_tpu.parallel.sharding import ShardingRules
from jax.sharding import PartitionSpec as P

Params = Dict[str, Any]

#: a patterned config's kinds of mixer: attention with rotated queries and
#: keys inside a band of ``sliding_window``, or with no rotation and the
#: whole past; a delta-rule linear attention with a decay a channel; latent
#: attention, uncompressed, over the whole past; grouped-query attention,
#: rotated by sections, over the keys a learned indexer picks
ATTN_KINDS = ("window", "full", "kda", "mla", "sparse")
#: where a kind's mixer keeps its leaves in a segment's tree: beside the
#: layer's other leaves (``""``: the two kinds of ``llama.attention_half``
#: hold alike ones, ``_ATTN_LEAVES``), or in a sub-tree of its own; either
#: way stacked over the segment's layers of that stack alone
_STACK = {"window": "", "full": "", "kda": "kda", "mla": "mla",
          "sparse": "sparse"}
#: a kind's own leaves where it has a stack of its own: (their count a
#: layer, their initialiser), in the order ``_segment_mixers`` seeds them
_OWN = {"kda": (mixers.kda_params, mixers.init_kda),
        "mla": (mixers.mla_params, mixers.init_mla),
        "sparse": (mixers.sparse_params, mixers.init_sparse)}
_ATTN_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm", "wg")


@dataclasses.dataclass(frozen=True)
class MoEConfig(llama.LlamaConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # top-k gates divided by their sum (Mixtral's convention); off, they
    # are the softmax's own values (OLMoE's ``norm_topk_prob: false``)
    norm_topk_prob: bool = True
    # ---- the patterned form (module docstring); neutral by default --------
    # one of ATTN_KINDS per layer, the dense layers first; () is the old
    # stack: every layer rotated and full, all of them expert layers
    layer_kinds: Tuple[str, ...] = ()
    sliding_window: Optional[int] = None
    n_dense_layers: int = 0
    d_ff_dense: int = 0
    # experts 0 .. n_experts_held-1 live here (None: all ``n_experts``)
    n_experts_held: Optional[int] = None
    # shared experts every token goes through, run as one SwiGLU of width
    # ``n_shared_experts * d_ff``
    n_shared_experts: int = 0
    # "softmax" over the router's logits, or "sigmoid" of each
    router_score: str = "softmax"
    # a buffer ``router_bias`` [E] added to the scores for the selection
    # only; it takes no gradient, and a step moves it toward the experts
    # that saw fewer rows than their share (``buffer_updates``)
    router_bias: bool = False
    route_scale: float = 1.0
    # the balancing term: "first_choice" (Switch: E * sum_e f_e P_e over
    # first choices and the whole batch) or "sequence" (E/K * sum_e f_e P_e
    # a sequence, f over all K choices, P the scores normalised to sum 1)
    balance: str = "first_choice"
    # a ``kda`` layer's heads, their width (keys and values alike; the two
    # low-rank gates pass through it too) and its convolutions' taps
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_taps: int = 4
    # an ``mla`` layer's latent and its three head widths (``n_heads`` heads)
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # its query through a low rank (``wq_a``, a norm, ``wq_b``; 0: one
    # full-rank ``wq``), and its rotary part: the ``qk_rope_head_dim``
    # columns rotated, under YaRN's frequencies and softmax factor where
    # the config publishes a ``rope_scaling`` of that type
    q_lora_rank: int = 0
    mla_rope: bool = False
    mla_yarn: Optional[Yarn] = None
    # hyper-connections (``ops/hyper.py``): the residual stream's rows (0:
    # one row and the plain sum), Sinkhorn's iterations and the ``eps`` in
    # its sums, and the clamp on ``H_res``'s logits
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    # multi-token-prediction modules in the loss (0 or 1: an expert layer
    # of the last layer's kind) and the weight of its cross entropy
    n_mtp_modules: int = 0
    mtp_weight: float = 0.3
    # a ``sparse`` layer's indexer (``ops/sparse_index.py``): its query
    # heads over one key a position, their width, the keys a query keeps
    # and the weight of the indexer's loss, meaned over all layers, in the
    # step's; and the frequency pairs a position stream of its rotation by
    # sections (a published ``mrope_section``; ``head_dim / 2`` in all)
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    index_loss_coef: float = 1.0
    rope_sections: Tuple[int, ...] = ()

    def __post_init__(self):
        if self.balance == "first_choice" and self.experts_held != self.n_experts:
            raise ValueError(
                "balance 'first_choice' counts first choices over the experts "
                "held here; with a share of them held use 'sequence'")
        if not self.layer_kinds:
            return
        if (len(self.layer_kinds) != self.n_layers
                or set(self.layer_kinds) - set(ATTN_KINDS)
                or not 0 <= self.n_dense_layers < self.n_layers):
            raise ValueError(
                f"layer_kinds names {len(self.layer_kinds)} layers of kinds "
                f"{sorted(set(self.layer_kinds))}; n_layers is "
                f"{self.n_layers}, {self.n_dense_layers} of them dense, and "
                f"the kinds are {ATTN_KINDS}")
        if "window" in self.layer_kinds and not self.sliding_window:
            raise ValueError("a window layer needs sliding_window")
        if "kda" in self.layer_kinds and not (
                self.kda_heads and self.kda_head_dim):
            raise ValueError("a kda layer needs kda_heads and kda_head_dim")
        if "mla" in self.layer_kinds and not (
                self.kv_lora_rank and self.qk_nope_head_dim
                and self.v_head_dim):
            raise ValueError("an mla layer needs kv_lora_rank, "
                             "qk_nope_head_dim and v_head_dim")
        if "sparse" in self.layer_kinds:
            if not (self.index_heads and self.index_head_dim
                    and self.index_topk and self.rope_sections):
                raise ValueError("a sparse layer needs index_heads, "
                                 "index_head_dim, index_topk and rope_sections")
            if self.n_mtp_modules:
                raise ValueError(
                    "a prediction module of kind 'sparse': nothing adds its "
                    "indexer's loss to the step's")
        if self.mla_yarn is not None and not self.mla_rope:
            raise ValueError("mla_yarn scales a rotary part: set mla_rope")
        if self.n_mtp_modules not in (0, 1):
            raise ValueError(
                f"n_mtp_modules {self.n_mtp_modules}: a second module would "
                f"read the first's stream, and nothing here chains them")

    @property
    def experts_held(self) -> int:
        return self.n_experts if self.n_experts_held is None \
            else self.n_experts_held

    @property
    def n_expert_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def n_routed_layers(self) -> int:
        """Expert layers with the prediction module's counted."""
        return self.n_expert_layers + self.n_mtp_modules

    def period(self) -> Tuple[str, ...]:
        """The shortest run of kinds that the expert layers repeat whole."""
        kinds = self.layer_kinds[self.n_dense_layers:]
        n = len(kinds)
        return next(kinds[:p] for p in range(1, n + 1)
                    if n % p == 0 and kinds[:p] * (n // p) == kinds)

    def _ffn_params(self, experts: int) -> int:
        """An expert layer's feed-forward half with ``experts`` routed
        experts counted: them, the shared one, the router, its bias and the
        bias's momentum, and the norms round the half."""
        d, f = self.d_model, self.d_ff
        return ((experts + self.n_shared_experts) * 3 * d * f
                + d * self.n_experts + 2 * self.router_bias * self.n_experts
                + (1 + self.sandwich_norm) * d)

    def _hyper_params(self) -> int:
        """A layer's two hyper-connections' leaves."""
        return 2 * hyper.params(self.hc_mult, self.d_model) \
            if self.hc_mult else 0

    def mtp_params(self, experts: int) -> int:
        """The prediction module: its projection of two normed inputs, its
        expert layer and its final norm (it shares embedding and head)."""
        if not self.n_mtp_modules:
            return 0
        d = self.d_model
        return (2 * d * d + 3 * d + self.mixer_params(self.layer_kinds[-1])
                + self._ffn_params(experts) + self._hyper_params())

    def mixer_params(self, kind: str) -> int:
        """One layer's mixer of ``kind`` with the norms round its branch."""
        if _STACK[kind] == "":
            return self.attn_params()
        return _OWN[kind][0](self) + (1 + self.sandwich_norm) * self.d_model

    def _params(self, experts: int) -> int:
        d, v = self.d_model, self.vocab_size
        kinds = self.layer_kinds or ("full",) * self.n_layers
        mixing = sum(map(self.mixer_params, kinds))
        dense = self.n_dense_layers * (3 * d * self.d_ff_dense
                                       + (1 + self.sandwich_norm) * d)
        sparse = self.n_expert_layers * self._ffn_params(experts)
        head = 0 if self.tie_embeddings else d * v
        extra = self.n_layers * self._hyper_params() + self.mtp_params(experts)
        return v * d + mixing + dense + sparse + d + head + extra

    def num_params(self) -> int:
        """Parameters held here (``n_experts_held`` experts a layer)."""
        return self._params(self.experts_held)

    def active_params(self) -> int:
        """Params touched per token (top-k experts) — the FLOPs-relevant
        count for MFU estimates. With a share of the experts held, the
        ``top_k * held / n_experts`` a token visits here on average."""
        d, f = self.d_model, self.d_ff
        visits = self.top_k * self.experts_held / self.n_experts
        return int(self._params(0)
                   + self.n_routed_layers * visits * 3 * d * f)


PRESETS: Dict[str, MoEConfig] = {
    "moe-debug": MoEConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                           n_kv_heads=4, d_ff=128, max_seq_len=256,
                           n_experts=4, top_k=2),
    "8x160m": MoEConfig(vocab_size=32000, d_model=768, n_layers=12,
                        n_heads=12, n_kv_heads=12, d_ff=2048,
                        max_seq_len=2048, n_experts=8, top_k=2),
    "8x410m": MoEConfig(vocab_size=32000, d_model=1024, n_layers=24,
                        n_heads=16, n_kv_heads=16, d_ff=2816,
                        max_seq_len=2048, n_experts=8, top_k=2),
}


#: the selection bias's scale at initialisation: enough to change some
#: selections of a sigmoid router's scores, which lie in (0, 1)
ROUTER_BIAS_INIT = 1e-2
#: the rule that moves it (``buffer_updates``): the largest step of one
#: expert's bias, and the share of the last steps' movement a step keeps
ROUTER_BIAS_RATE = 1e-2
ROUTER_BIAS_MOMENTUM = 0.5


def _segment_mixers(rng: jax.Array, cfg: MoEConfig, layers: Params,
                    kinds: Tuple[str, ...]) -> None:
    """Make ``layers``, a llama stack over a segment's ``len(kinds)``
    layers, hold each mixer's leaves by its stack (``_STACK``): the
    attention leaves over the segment's ``window`` and ``full`` layers alone
    (gone where it has none), and a sub-tree for each other kind, stacked
    over the layers of that kind."""
    n_attn = sum(_STACK[k] == "" for k in kinds)
    if n_attn < len(kinds):
        for name in set(_ATTN_LEAVES) & set(layers):
            if n_attn:
                layers[name] = layers[name][:n_attn]
            else:
                del layers[name]
    for i, (kind, (_, init)) in enumerate(_OWN.items()):
        n = kinds.count(kind)
        if n:
            layers[kind] = init(jax.random.fold_in(rng, 20 + i), cfg, n)


def _segment_hyper(rng: jax.Array, cfg: MoEConfig, layers: Params,
                   n: int) -> None:
    """Give ``layers``, a segment of ``n`` layers, each half's
    hyper-connection (``hc_attn_*``, ``hc_mlp_*``: ``hyper.LEAVES``) where
    the config widens the stream."""
    if not cfg.hc_mult:
        return
    for i, half in enumerate(("attn", "mlp")):
        made = hyper.init(jax.random.fold_in(rng, i), cfg.hc_mult,
                          cfg.d_model, n, cfg.param_dtype)
        layers.update({f"hc_{half}_{name}": a for name, a in made.items()})


def _init_mtp(rng: jax.Array, cfg: MoEConfig) -> Params:
    """The prediction module's leaves: the two norms and the projection of
    its input, one expert layer of the last layer's kind (a segment of one,
    as ``init_params`` makes the expert layers) and its final norm."""
    d, kind = cfg.d_model, cfg.layer_kinds[-1]
    one = init_params(jax.random.fold_in(rng, 0), dataclasses.replace(
        cfg, n_layers=1, layer_kinds=(kind,), n_dense_layers=0,
        n_mtp_modules=0, vocab_size=8))
    # (a buffer each: a step donates its parameters leaf by leaf)
    norms = {name: jnp.ones((d,), cfg.param_dtype)
             for name in ("hnorm", "enorm", "final_norm")}
    return {**norms,
            "proj": (jax.random.normal(jax.random.fold_in(rng, 1), (2 * d, d),
                                       jnp.float32) / math.sqrt(2 * d)
                     ).astype(cfg.param_dtype),
            "layers": one["layers"]}


def init_params(rng: jax.Array, cfg: MoEConfig) -> Params:
    """Llama init plus stacked expert FFNs [L, E, ...] and routers; ``E``
    the experts held here, the router ``n_experts`` wide. A patterned
    config's leading dense layers are a llama stack of their own under
    ``dense_layers``, its shared expert ``s_gate`` / ``s_up`` / ``s_down``
    and its selection bias ``router_bias`` (with the momentum of its
    movement, ``router_bias_m``) lie with the expert layers, and in either
    segment a ``kda`` or ``mla`` layer's mixer lies in a sub-tree of its
    kind's name, stacked over the segment's layers of that kind
    (``_segment_mixers``). Where the stream is widened every layer of
    either segment holds its two hyper-connections' leaves
    (``_segment_hyper``), and a prediction module lies under ``mtp``
    (``_init_mtp``)."""
    d, f, E, L = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_expert_layers
    H = cfg.experts_held
    base = llama.init_params(rng, dataclasses.replace(
        cfg, n_layers=L, layer_kinds=(), n_dense_layers=0))
    k = jax.random.fold_in(rng, 7)
    k1, k2, k3, k4 = jax.random.split(k, 4)

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(cfg.param_dtype)

    layers = base["layers"]
    for name in ("w_gate", "w_up", "w_down"):  # dense FFN -> experts
        del layers[name]
    layers["router"] = norm_init(k1, (L, d, E), d)
    layers["e_gate"] = norm_init(k2, (L, H, d, f), d)
    layers["e_up"] = norm_init(k3, (L, H, d, f), d)
    layers["e_down"] = norm_init(k4, (L, H, f, d), f)
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * f
        k5, k6, k7 = jax.random.split(jax.random.fold_in(rng, 8), 3)
        layers["s_gate"] = norm_init(k5, (L, d, fs), d)
        layers["s_up"] = norm_init(k6, (L, d, fs), d)
        layers["s_down"] = norm_init(k7, (L, fs, d), fs)
    if cfg.router_bias:
        layers["router_bias"] = ROUTER_BIAS_INIT * jax.random.normal(
            jax.random.fold_in(rng, 9), (L, E), jnp.float32)
        layers["router_bias_m"] = jnp.zeros((L, E), jnp.float32)
    if cfg.layer_kinds:
        _segment_mixers(jax.random.fold_in(rng, 11), cfg, layers,
                        cfg.layer_kinds[cfg.n_dense_layers:])
        _segment_hyper(jax.random.fold_in(rng, 13), cfg, layers, L)
    if cfg.n_dense_layers:
        base["dense_layers"] = llama.init_params(
            jax.random.fold_in(rng, 10), dataclasses.replace(
                cfg, n_layers=cfg.n_dense_layers, d_ff=cfg.d_ff_dense,
                layer_kinds=(), n_dense_layers=0, vocab_size=8))["layers"]
        _segment_mixers(jax.random.fold_in(rng, 12), cfg,
                        base["dense_layers"],
                        cfg.layer_kinds[:cfg.n_dense_layers])
        _segment_hyper(jax.random.fold_in(rng, 14), cfg,
                       base["dense_layers"], cfg.n_dense_layers)
    if cfg.n_mtp_modules:
        base["mtp"] = _init_mtp(jax.random.fold_in(rng, 15), cfg)
    return base


def buffer_updates(cfg: MoEConfig, params: Params, updates: Params,
                   stats: Dict[str, jax.Array]
                   ) -> Tuple[Params, Dict[str, jax.Array]]:
    """The optimizer's ``updates`` with the buffers' own movement in place of
    what it made of their zero gradient and the decay, and ``stats`` less
    what that took (``router_load``); for a config without a selection
    bias the updates as they came and ``stats`` less the loads, which move
    nothing there.

    The bias moves by the balancing rule of the models that select by
    ``score + bias`` (no gradient: the bias is not a weight), in the form
    Trinity Large's report names, soft-clamped momentum updates: with
    ``n`` [L, E] the (token, expert) choices each expert of each layer got
    this step, over all ``n_experts`` (an expert that lives elsewhere is
    still chosen, and its bias is every chip's), and ``mean`` their mean,
    ``v = (mean - n) / mean`` is how far under its share an expert is (1
    for one nobody chose), ``step = RATE * tanh(v)`` the soft-clamped move,
    centred over the experts so that the biases' mean stays; ``m' =
    MOMENTUM * m + (1 - MOMENTUM) * step`` and ``bias' = bias + m'``. A
    prediction module's layer has a bias of its own, moved alike by what
    that layer counted (``mtp_router_load``)."""
    if not cfg.router_bias:
        # (the loads move nothing here, and a [L, E] array is no metric)
        return updates, {name: v for name, v in stats.items()
                         if name not in ("router_load", "mtp_router_load")}
    stats = dict(stats)

    def moved(layers: Params, upd: Params, counted: str) -> Params:
        m = layers["router_bias_m"]
        if counted in stats:
            load = stats.pop(counted).astype(jnp.float32)             # [L, E]
            mean = load.mean(-1, keepdims=True)
            step = ROUTER_BIAS_RATE * jnp.tanh((mean - load) / mean)
            step = step - step.mean(-1, keepdims=True)
        else:  # a caller's own loss counted nothing: the bias keeps its course
            step = jnp.zeros_like(m)
        m_new = ROUTER_BIAS_MOMENTUM * m + (1 - ROUTER_BIAS_MOMENTUM) * step
        return {**upd, "router_bias": m_new, "router_bias_m": m_new - m}

    updates = {**updates, "layers": moved(params["layers"], updates["layers"],
                                          "router_load")}
    if cfg.n_mtp_modules:
        updates["mtp"] = {**updates["mtp"], "layers": moved(
            params["mtp"]["layers"], updates["mtp"]["layers"],
            "mtp_router_load")}
    return updates, stats


def _fill_take(rows: jax.Array, index: jax.Array) -> jax.Array:
    """``rows[index]`` along the first dimension; an index past the end
    reads a row of zeros."""
    return jnp.take(rows, index, axis=0, mode="fill", fill_value=0)


def _row_placement(cfg: MoEConfig, G: int):
    """Where the routed rows live under the ambient mesh: ``(mesh, token
    axes, expert axes)``, the mesh axes of more than one device that split
    the tokens' ``G`` and the experts' ``E``, the latter as
    ``sharding_rules`` resolve ``layers/e_gate`` on this mesh. None with no
    mesh, on one device, or where an axis does not split its size evenly:
    the rows then move by plain ``take``s."""
    from ray_tpu.parallel.context import current_mesh
    from ray_tpu.parallel.sharding import BATCH_AXES, axes_size

    mesh = current_mesh()
    if mesh is None:
        return None
    e_axes = sharding_rules().spec_for(
        "layers/e_gate",
        (cfg.n_expert_layers, cfg.experts_held, cfg.d_model, cfg.d_ff),
        mesh)[1]
    e_axes = e_axes if isinstance(e_axes, tuple) else (e_axes,)
    over, among = (tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
                   for axes in (BATCH_AXES, e_axes))
    if (not over + among or G % axes_size(over, mesh)
            or cfg.experts_held % axes_size(among, mesh)):
        return None
    return mesh, over, among


def _rows_to_slots(place, x: jax.Array, token: jax.Array) -> jax.Array:
    """x [G, d], token [E, C] -> [E, C, d]: every capacity slot reads its
    token's row, an empty one (``token == G``) zeros. Under a mesh the
    tokens are gathered to the experts' owners (one all-gather of
    ``[G, d]``) and each chip reads the rows of its own experts' slots."""
    if place is None:
        return _fill_take(x, token)
    mesh, over, among = place

    def local(x, token):
        if over:
            x = jax.lax.all_gather(x, over, axis=0, tiled=True)
        return _fill_take(x, token)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(over or None), P(among or None)),
        out_specs=P(among or None), check_vma=False)(x, token)


def _rows_to_tokens(place, y: jax.Array, w: jax.Array, dest: jax.Array
                    ) -> jax.Array:
    """y [E, C, d], w and dest [G, K] -> [G, d]: every token sums the rows
    of its K slots weighted by ``w``; a dropped assignment (``dest >=
    E * C``) reads zeros. Under a mesh a chip reads what its own experts
    hold (another chip's rows are zeros) for the tokens of the chips it
    shares the experts out with, and the partial ``[G, d]`` is
    reduce-scattered back over the tokens' axes."""
    E, C, d = y.shape

    def weighted(rows, w, at):
        # read as [K, G, d]: with K next to d the chip lays it on the
        # sublanes, two to a tile, and the read takes three times as long
        return jnp.einsum("kg,kgd->gd", w.T, _fill_take(rows, at.T))

    if place is None:
        return weighted(y.reshape(E * C, d), w, dest)
    mesh, over, among = place
    # the tokens' axes that also split the experts; the others (dp) hold
    # every expert among themselves and keep their tokens to themselves
    shared = tuple(a for a in over if a in among)
    # (gathered below as one contiguous block of G: the tokens' innermost)
    assert over[len(over) - len(shared):] == shared, (over, among)
    summed = tuple(a for a in among if a not in shared)

    def local(y, w, dest):
        if shared:
            w, dest = (jax.lax.all_gather(v, shared, axis=0, tiled=True)
                       for v in (w, dest))
        mine = y.shape[0] * C
        at = dest - (jax.lax.axis_index(among) * mine if among else 0)
        at = jnp.where((at >= 0) & (at < mine), at, mine)
        part = weighted(y.reshape(mine, d), w, at)
        if summed:
            part = jax.lax.psum(part, summed)
        if shared:
            part = jax.lax.psum_scatter(part, shared, scatter_dimension=0,
                                        tiled=True)
        return part

    tok = P(over or None)
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(among or None), tok, tok),
        out_specs=tok, check_vma=False)(y, w, dest)


# Dispatch and combine are one partial permutation read from its two ends,
# so each one's backward is the other. Left to autodiff, a gather's
# transpose is a scatter-add of d-wide rows, which the TPU runs row by row.

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(place, x, src, dest):
    """Tokens' rows into the capacity slots. ``src`` [E, C] is each slot's
    assignment ``g * K + k`` (``G * K``: empty), ``dest`` [G, K] each
    assignment's slot ``e * C + c`` (``>= E * C``: dropped)."""
    return _rows_to_slots(place, x, src // dest.shape[1])


def _dispatch_fwd(place, x, src, dest):
    return _dispatch(place, x, src, dest), dest


def _dispatch_bwd(place, dest, ct):
    kept = (dest < ct.shape[0] * ct.shape[1]).astype(ct.dtype)
    return _rows_to_tokens(place, ct, kept, dest), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(place, y, w, src, dest):
    """Slots' rows back to their tokens, weighted by the gates ``w``
    [G, K]; ``src`` and ``dest`` as in ``_dispatch``."""
    return _rows_to_tokens(place, y, w, dest)


def _combine_fwd(place, y, w, src, dest):
    return _combine(place, y, w, src, dest), (y, w, src, dest)


def _combine_bwd(place, res, ct):
    y, w, src, dest = res
    rows = _rows_to_slots(place, ct, src // w.shape[1])           # [E, C, d]
    slot_w = _fill_take(w.reshape(-1), src)                       # [E, C]
    # a gate's cotangent is its slot's <row, cotangent>, summed in float32
    dots = jnp.sum(rows.astype(jnp.float32) * y.astype(jnp.float32), axis=-1)
    d_w = _fill_take(dots.reshape(-1), dest).astype(w.dtype)
    return rows * slot_w[..., None], d_w, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _route(cfg: MoEConfig, tokens: jax.Array, layer: Params
           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """tokens [G, d] -> (scores [G, E] float32, the K chosen experts'
    scores [G, K], their indices [G, K]). The scores are the softmax of the
    router's logits or the sigmoid of each; the choice is by score, plus the
    layer's ``router_bias`` where it has one (the bias chooses and is not a
    weight: the gates are the scores themselves)."""
    logits = (tokens @ layer["router"].astype(jnp.float32)).astype(jnp.float32)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)                      # [G, E]
    if "router_bias" not in layer:
        return (scores, *jax.lax.top_k(scores, cfg.top_k))
    _, topk_idx = jax.lax.top_k(
        scores + jax.lax.stop_gradient(layer["router_bias"]), cfg.top_k)
    return scores, jnp.take_along_axis(scores, topk_idx, axis=-1), topk_idx


def _balance(cfg: MoEConfig, scores: jax.Array, topk_idx: jax.Array,
             sel_onehot: jax.Array, b: int) -> jax.Array:
    """The balancing term of ``cfg.balance`` (its coefficient is
    ``router_aux_coef``, applied by ``lm_loss``)."""
    E, K = cfg.n_experts, cfg.top_k
    if cfg.balance == "first_choice":
        # Switch aux loss: balance token fraction vs router probability mass
        frac = jnp.mean(sel_onehot[:, 0, :].astype(jnp.float32), axis=0)  # top-1
        return E * jnp.sum(frac * jnp.mean(scores, axis=0))
    if cfg.balance != "sequence":
        raise ValueError(f"balance {cfg.balance!r}")
    # a sequence at a time: the share of its tokens that chose an expert
    # (over all K choices, over all E experts, held or not) times the mean
    # of the scores normalised to sum 1
    chose = jax.nn.one_hot(topk_idx, E, dtype=jnp.float32).sum(1)     # [G, E]
    frac = chose.reshape(b, -1, E).mean(1)                            # [b, E]
    mass = (scores / scores.sum(-1, keepdims=True)).reshape(b, -1, E).mean(1)
    return (E / K) * jnp.mean(jnp.sum(frac * mass, axis=-1))


#: what a patterned step counts of its routing beside its loss, int32 each,
#: over the expert layers (``routing_counters``); a recorder adds them up
#: over steps, but for ``COUNTER_MAXIMA``, of which it keeps the largest
ROUTING_COUNTERS = ("moe_assignments",      # (token, expert) pairs routed
                    "moe_held",             # of them, to an expert held here
                    "moe_kept",             # of those, inside the capacity
                    "moe_dropped",          # of those, beyond it
                    "moe_max_expert_rows")  # the busiest held expert's queue
COUNTER_MAXIMA = ("moe_max_expert_rows",)
#: what a config with a ``sparse`` layer counts of its choices beside them,
#: int32 each, summed over the layers (``ops/sparse_index.COUNTERS``: the
#: causal (query, key) pairs, those chosen, the rows ties kept over ``topk``)
INDEX_COUNTERS = sparse_index.COUNTERS


def routing_counters(cfg: MoEConfig, load: jax.Array, kept: jax.Array
                     ) -> Dict[str, jax.Array]:
    """``ROUTING_COUNTERS`` by name from the expert layers' ``load`` [L, E]
    (choices each expert got, over all ``n_experts``) and ``kept`` [L]
    (assignments that took a slot in a held expert's buffer)."""
    here = load[:, :cfg.experts_held]
    held, kept = here.sum(dtype=jnp.int32), kept.sum(dtype=jnp.int32)
    return dict(zip(ROUTING_COUNTERS, (
        load.sum(dtype=jnp.int32), held, kept, held - kept, here.max())))


def _moe_ffn(cfg: MoEConfig, h: jax.Array, layer: Params
             ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """[B, S, d] -> ([B, S, d], aux_loss, routing). Static-shape top-k
    capacity dispatch: every shape is fixed at trace time, and the routed
    rows move into and out of the ``[H, C, d]`` capacity buffers by index,
    ``H`` the experts held here (all ``E`` unless the config says less).
    ``routing`` is what the routing already computed, for a caller that
    counts it: the choices ``topk_idx`` [G, K] and which of them took a
    slot, ``keep``."""
    b, s, d = h.shape
    E, K, H = cfg.n_experts, cfg.top_k, cfg.experts_held
    G = b * s
    C = max(1, int(cfg.capacity_factor * G * K / E))
    place = _row_placement(cfg, G)

    # scopes are names only: they group the layer's operations in a
    # device trace and change nothing that is computed
    with jax.named_scope("moe_router"):
        tokens = h.reshape(G, d)
        probs, topk_probs, topk_idx = _route(cfg, tokens, layer)
        if cfg.norm_topk_prob:
            # renormalize the selected gates (Mixtral convention)
            topk_probs = topk_probs / (topk_probs.sum(-1, keepdims=True) + 1e-9)
        if cfg.route_scale != 1.0:
            topk_probs = topk_probs * cfg.route_scale

        # capacity slots: position of each token within its expert's queue,
        # counted over the flattened [K, G] selection order; an expert that
        # lives elsewhere has no queue here (its one-hot row is zeros)
        sel_onehot = jax.nn.one_hot(topk_idx, H, dtype=jnp.int32)     # [G, K, H]
        flat = sel_onehot.transpose(1, 0, 2).reshape(K * G, H)        # [K*G, H]
        pos_flat = jnp.cumsum(flat, axis=0) - flat                    # slot idx
        pos = pos_flat.reshape(K, G, H).transpose(1, 0, 2)            # [G, K, H]
        slot = jnp.sum(pos * sel_onehot, axis=-1)                     # [G, K]
        keep = slot < C                                               # overflow
        if H < E:
            keep = keep & (topk_idx < H)
        gates = topk_probs * keep                                      # [G, K]

    with jax.named_scope("moe_dispatch"):
        # the routing from its two ends: an assignment's slot, a slot's
        # assignment (no two kept assignments share a slot)
        dest = jnp.where(keep, topk_idx * C + slot, H * C)            # [G, K]
        src = jnp.full((H * C,), G * K, jnp.int32).at[dest.reshape(-1)].set(
            jnp.arange(G * K, dtype=jnp.int32), mode="drop").reshape(H, C)
        expert_in = _dispatch(place, tokens, src, dest)               # [H, C, d]

    with jax.named_scope("moe_experts"):
        gate = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                      layer["e_gate"].astype(h.dtype)))
        up = jnp.einsum("ecd,edf->ecf", expert_in,
                        layer["e_up"].astype(h.dtype))
        expert_out = jnp.einsum("ecf,efd->ecd", gate * up,
                                layer["e_down"].astype(h.dtype))

    with jax.named_scope("moe_combine"):
        out = _combine(place, expert_out, gates.astype(h.dtype), src, dest)

    with jax.named_scope("moe_router"):
        aux = _balance(cfg, probs, topk_idx, sel_onehot, b)
    return out.reshape(b, s, d), aux, {"topk_idx": topk_idx, "keep": keep}


def ffn_half(cfg: MoEConfig, x: jax.Array, layer: Params
             ) -> Tuple[jax.Array, jax.Array]:
    """Pre-norm MoE FFN joined to the stream; returns (hidden, aux_loss).
    The norm lies under the scope of its first reader (``moe_router``), the
    residual sum under ``moe_combine``, whose result it takes."""
    with jax.named_scope("moe_router"):
        h = llama.rmsnorm(x, layer["mlp_norm"].astype(cfg.compute_dtype),
                          cfg.norm_eps)
    ffn, aux, _ = _moe_ffn(cfg, h, layer)
    with jax.named_scope("moe_combine"):
        return llama.join(x, ffn), aux


# What one served MoE layer says of its routing, an int32 vector in this
# order. Over layers, steps and launches the first four add up and the
# last is a maximum (``fold_served_stats``, the engine's recorder).
SERVED_STATS = ("moe_assignments",      # (token, expert) pairs routed: G*K
                "moe_rows_computed",    # rows the grouped products ran
                "moe_experts_touched",  # experts with at least one row
                "moe_expert_slots",     # experts there were: E
                "moe_max_expert_rows")  # the busiest expert's rows


def fold_served_stats(stats: jax.Array) -> jax.Array:
    """[n, 5] of ``SERVED_STATS`` (a scan's layers or steps) -> [5]."""
    return jnp.concatenate([stats[:, :4].sum(0), stats[:, 4:].max(0)])


#: a layer's weights that the served block reads where they lie, stacked
#: over the layers, and that a layer scan must therefore not slice
EXPERT_WEIGHTS = ("e_gate", "e_up", "e_down")


def served_ffn_half(cfg: MoEConfig, x: jax.Array, layer: Params,
                    experts: Params, index) -> Tuple[jax.Array, jax.Array]:
    """The served block's pre-norm MoE FFN + residual: [B, S, d] ->
    ([B, S, d], ``SERVED_STATS``). Inference routing drops nothing
    (capacity is a training-time load-balancing construct), and an
    expert may be chosen by every token at once, so there is no capacity
    buffer: the G*K (token, expert) assignments are sorted by expert, the
    tokens' rows gathered in that order with every expert's group padded
    to whole tiles, each tile multiplied by its expert's matrices
    (``ops/pallas/grouped_matmul``) and every token sums its K rows,
    weighted by its gates. Rows computed are at most G*K + E * tile;
    memory and arithmetic are linear in G*K; no tensor is [G, E, .] but the
    router's probabilities. The four scopes stand for themselves in a
    device trace (not under ``mlp``).

    ``experts`` are ``EXPERT_WEIGHTS`` stacked over the layers ([L, E, ..])
    with ``index`` this layer's: inside a layer scan they are read where
    they lie and no layer's worth of them is sliced out."""
    from ray_tpu.ops.pallas import grouped_matmul as gmm

    b, s, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    G, cdt = b * s, cfg.compute_dtype
    A = G * K
    tile = gmm.tile_rows(A, E)
    tiles = gmm.n_tiles(G, K, E, tile)

    with jax.named_scope("moe_router"):
        h = llama.rmsnorm(x, layer["mlp_norm"].astype(cdt), cfg.norm_eps)
        tokens = h.reshape(G, d)
        logits = (tokens @ layer["router"].astype(jnp.float32)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)                       # [G, E]
        gates, chosen = jax.lax.top_k(probs, K)                       # [G, K]
        if cfg.norm_topk_prob:
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)

    with jax.named_scope("moe_dispatch"):
        # assignment a = g * K + k; ``order`` lists them expert by expert,
        # a token's before a later token's (the sort is stable)
        expert = chosen.reshape(A)
        order = jnp.argsort(expert, stable=True)
        by_expert = expert[order]
        starts = jnp.searchsorted(by_expert, jnp.arange(E + 1))
        sizes = jnp.diff(starts)                     # rows an expert
        group_tiles = -(-sizes // tile)              # tiles that hold them
        ends = jnp.cumsum(group_tiles)               # its tiles end here
        used = ends[-1:]
        # where a sorted assignment's row lies once every group is padded
        # to whole tiles: its group's first tile, then its rank in the group
        first = (ends - group_tiles)[by_expert] * tile
        dest = first + jnp.arange(A) - starts[by_expert]
        source = jnp.full((tiles * tile,), G, jnp.int32).at[dest].set(
            order // K, indices_are_sorted=True, unique_indices=True)
        rows = jnp.take(tokens, source, axis=0, mode="fill", fill_value=0)
        # a tile past the last one in use names that one's expert
        tile_expert = jnp.searchsorted(
            ends, jnp.minimum(jnp.arange(tiles), used - 1), side="right")

    with jax.named_scope("moe_experts"):
        args = (tile_expert, used, jnp.asarray(index, jnp.int32).reshape(1))
        act = gmm.grouped_matmul(rows, *args, experts["e_gate"],
                                 experts["e_up"])
        out_rows = gmm.grouped_matmul(act, *args, experts["e_down"])

    with jax.named_scope("moe_combine"):
        back = jnp.zeros((A,), jnp.int32).at[order].set(
            dest, unique_indices=True)               # an assignment's row
        mine = out_rows[back].reshape(G, K, d)
        out = x + jnp.einsum("gk,gkd->gd", gates.astype(cdt),
                             mine).reshape(b, s, d)
        stats = jnp.stack([jnp.int32(A), used[0] * tile,
                           (sizes > 0).sum(dtype=jnp.int32), jnp.int32(E),
                           sizes.max()])
    return out, stats


def _moe_block(cfg: MoEConfig, x: jax.Array, layer: Params,
               sin: jax.Array, cos: jax.Array,
               segment_ids) -> Tuple[jax.Array, jax.Array]:
    """Shared llama attention half, under the scope ``attn_full``, + MoE
    FFN; returns (hidden, aux_loss)."""
    with jax.named_scope("attn_full"):
        x = llama.join(x, llama.attention_half(cfg, x, layer, sin, cos,
                                               segment_ids))
    return ffn_half(cfg, x, layer)


def _pick(tree: Params, kinds: Tuple[str, ...], j: int) -> Params:
    """Layer ``j`` of a segment whose layers are of ``kinds``, as one flat
    dict: the leaves every layer has at ``j``, and its mixer's from its
    kind's stack (``_STACK``) at the number of earlier layers that share
    that stack. (Leaf by leaf in the tree's own order: a segment of
    ``window`` and ``full`` layers alone traces as it did when the walk
    indexed one tree by position.)"""
    stack = _STACK[kinds[j]]
    at = sum(_STACK[k] == stack for k in kinds[:j])
    layer = {name: a[at if name in _ATTN_LEAVES else j]
             for name, a in sorted(tree.items())
             if not isinstance(a, dict)
             and not (stack and name in _ATTN_LEAVES)}
    if stack:
        layer.update(jax.tree.map(lambda a: a[at], tree[stack]))
    return layer


def _mixer_half(cfg: MoEConfig, kind: str, h, layer, sin, cos, segment_ids,
                mla_tables=None, sparse_tables=None):
    """The layer's mixer of ``kind``, a pre-norm branch on ``h``, under the
    scope ``attn_<kind>``; a ``sparse`` layer's opens its scopes itself and
    returns its loss and counts beside the branch (``mixers.sparse_half``)."""
    if kind == "sparse":
        return mixers.sparse_half(cfg, h, layer, segment_ids, sparse_tables)
    if kind == "kda" and segment_ids is not None:
        raise NotImplementedError(
            "segment_ids (packed sequences) through a kda layer: the "
            "recurrence would have to reset its state at a boundary")
    with jax.named_scope("attn_" + kind):
        if kind == "kda":
            return mixers.kda_half(cfg, h, layer)
        if kind == "mla":
            return mixers.mla_half(cfg, h, layer, segment_ids, mla_tables)
        return llama.attention_half(
            cfg, h, layer, sin, cos, segment_ids, rotate=kind == "window",
            window=cfg.sliding_window if kind == "window" else None)


def _read(cfg: MoEConfig, x: jax.Array, layer: Params, half: str):
    """What the ``half`` ("attn" or "mlp") of a layer reads of the stream
    ``x``, and what its ``_join`` needs beside the branch: the stream itself
    and nothing for the plain sum; the hyper-connection's mix of the rows
    and its two matrices for the write (``hyper.mix_in``, under
    ``hyper_mix``) where the stream is widened."""
    if not cfg.hc_mult:
        return x, None
    return hyper.mix_in(
        x, {name: layer[f"hc_{half}_{name}"] for name in hyper.LEAVES},
        iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps, clamp=cfg.hc_clamp,
        norm_eps=cfg.norm_eps,
        impl="pallas" if cfg.attn_impl == "flash" else "xla")


def _join(scope: str, x: jax.Array, branch: jax.Array, mix) -> jax.Array:
    """``llama.join``: the plain sum under the half's own ``scope``, the
    hyper-connection's write under its own (``hyper_mix``, outermost)."""
    if mix is not None:
        return llama.join(x, branch, mix)
    with jax.named_scope(scope):
        return llama.join(x, branch)


def _patterned_layer(cfg: MoEConfig, kind: str, dense: bool):
    """One layer of a patterned config as ``(x, layer) -> (x, aux, load,
    kept)``, its kind static: the mixer of its kind (``_mixer_half``: the
    attention half rotated inside the band or unrotated and full, the delta
    rule, latent attention, attention over a learned choice), then a dense
    SwiGLU (no ``aux``, ``load`` or
    ``kept``: None) or the shared expert beside the routed ones, every
    branch normed before and after where the config says so, read from the
    stream by ``_read`` and joined to it by ``_join``. ``load`` [E]
    is the choices each of the ``n_experts`` got, ``kept`` how many took a
    slot in a held expert's buffer. Where the config has a ``sparse`` layer
    anywhere every layer's tuple ends with one more, ``(the indexer's loss,
    its counts [3])``, zeros from a layer of another kind."""
    cdt = cfg.compute_dtype
    indexed = "sparse" in cfg.layer_kinds

    def run(x, layer, sin, cos, segment_ids, mla_tables=None,
            sparse_tables=None):
        h, mix = _read(cfg, x, layer, "attn")
        branch = _mixer_half(cfg, kind, h, layer, sin, cos, segment_ids,
                             mla_tables, sparse_tables)
        if kind == "sparse":
            branch, *own = branch
        else:
            own = (jnp.zeros((), jnp.float32),
                   jnp.zeros((len(INDEX_COUNTERS),), jnp.int32))
        own = (tuple(own),) if indexed else ()
        x = _join("attn_" + kind, x, branch, mix)
        h, mix = _read(cfg, x, layer, "mlp")
        if dense:
            with jax.named_scope("mlp"):
                branch = llama.ffn_half(cfg, h, layer)
            return (_join("mlp", x, branch, mix), None, None, None, *own)
        with jax.named_scope("moe_router"):
            h = llama.rmsnorm(h, layer["mlp_norm"].astype(cdt), cfg.norm_eps)
        ffn, aux, routing = _moe_ffn(cfg, h, layer)
        if cfg.n_shared_experts:
            with jax.named_scope("moe_shared"):
                gate = jax.nn.silu(h @ layer["s_gate"].astype(cdt))
                ffn = ffn + (gate * (h @ layer["s_up"].astype(cdt))
                             ) @ layer["s_down"].astype(cdt)
        with jax.named_scope("moe_router"):
            load = jnp.zeros((cfg.n_experts,), jnp.int32).at[
                routing["topk_idx"].reshape(-1)].add(1)
            kept = routing["keep"].sum(dtype=jnp.int32)
        with jax.named_scope("moe_combine"):
            ffn = llama.post_norm(cfg, ffn, layer, "mlp_post_norm")
        return (_join("moe_combine", x, ffn, mix), aux, load, kept, *own)

    return run


def _walk(params: Params, x: jax.Array, cfg: MoEConfig, sin, cos,
          segment_ids, mla_tables=None, sparse_tables=None):
    """A patterned config's layers over ``x``: the leading dense layers one
    after another (their kinds need repeat nothing), then a ``lax.scan``
    over the repeats of the expert layers' shortest repeating pattern, the
    layers of one period written out inside it with their kinds static
    (``models/hybrid._walk`` is the served precedent) and each taking its
    leaves from its kind's stack (``_pick``). Every layer is its own remat
    block. Returns (x, the expert layers' summed aux, their
    ``load`` [L, E] and ``kept`` [L], and of a config with a ``sparse``
    layer all layers' summed (indexer's loss, counts [3]), else None)."""
    indexed = "sparse" in cfg.layer_kinds
    tables = (mla_tables, sparse_tables) if indexed else (mla_tables,)
    owns = []  # where indexed: (indexer's loss, counts) a dense layer, a scan
    leading = cfg.layer_kinds[:cfg.n_dense_layers]
    for i, kind in enumerate(leading):
        layer = _pick(params["dense_layers"], leading, i)
        run = _patterned_layer(cfg, kind, dense=True)
        # (of a dense layer's tuple the stream and, where there is one, the
        # fifth part)
        x, *own = llama.remat_block(cfg, lambda x, layer, run=run: run(
            x, layer, sin, cos, segment_ids, *tables)[::4])(x, layer)
        owns += own

    period = cfg.period()
    runs = [llama.remat_block(cfg, lambda x, layer, run=_patterned_layer(
        cfg, kind, dense=False): run(x, layer, sin, cos, segment_ids,
                                     *tables))
        for kind in period]

    def body(carry, layers):
        x, aux = carry
        counts = []
        for j, run in enumerate(runs):
            x, a, *count = run(x, _pick(layers, period, j))
            aux = aux + a
            counts.append(count)
        load, kept, *own = zip(*counts)
        with jax.named_scope("moe_router"):
            stacked = (jnp.stack(load), jnp.stack(kept))
        if indexed:  # a period's layers' (loss, counts), summed
            stacked += (jax.tree.map(lambda *a: sum(a), *own[0]),)
        return (x, aux), stacked

    # every stack [repeats, its layers a period, ...]: a stack holds as many
    # layers as the periods have of its kinds
    repeats = cfg.n_expert_layers // len(period)
    by_period = jax.tree.map(
        lambda a: a.reshape(repeats, -1, *a.shape[1:]), params["layers"])
    (x, aux), (load, kept, *own) = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), by_period)
    owns += [jax.tree.map(lambda a: a.sum(0), o) for o in own]
    index = jax.tree.map(lambda *a: sum(a), *owns) if owns else None
    return x, aux, load.reshape(-1, cfg.n_experts), kept.reshape(-1), index


def _trunk(params: Params, tokens: jax.Array, cfg: MoEConfig, segment_ids,
           positions=None):
    """tokens [b, s] -> (the stream after the last layer, before the final
    norm, its rows summed where it is widened; the expert layers' summed
    aux; their ``load`` [L, E] and ``kept`` [L], None for the old stack,
    which counts nothing; an ``mla`` layer's rotary tables; and, of a config
    with a ``sparse`` layer alone, a sixth: ``_walk``'s summed (indexer's
    loss, counts)). ``positions`` [sections, b, s]: the position streams of
    a rotation by sections, where a batch carries them."""
    if cfg.pipeline_axis is not None:
        raise NotImplementedError(
            "pipeline parallelism for the MoE family is not implemented "
            "(use dp/fsdp/tp/ep); silently ignoring pipeline_axis would "
            "train an unpipelined model under pipeline shardings")
    cdt = cfg.compute_dtype
    if cfg.layer_kinds:
        llama.refuse_served_only(cfg)
    elif cfg.hc_mult or cfg.n_mtp_modules:
        raise NotImplementedError(
            "hc_mult and n_mtp_modules are the patterned form's: the old "
            "stack's layers join their branches by the plain sum and its "
            "loss has one term (set layer_kinds)")
    with jax.named_scope("embed"):
        x = llama.embed(params, cfg, tokens)
    # the rotary tables under the scope of the layers that read them: a
    # patterned config rotates inside its window layers alone
    with jax.named_scope("attn_window" if cfg.layer_kinds else "attn_full"):
        sin, cos = llama.rope_angles(tokens.shape[1], cfg.head_dim,
                                     cfg.rope_theta, cdt)

    def body(carry, layer):
        x, aux = carry
        x, a = _moe_block(cfg, x, layer, sin, cos, segment_ids)
        return (x, aux + a), None

    if not cfg.layer_kinds:
        (x, aux), _ = jax.lax.scan(llama.remat_block(cfg, body),
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["layers"])
        return x, aux, None, None, None
    with jax.named_scope("attn_mla"):
        mla_tables = mixers.mla_rope_tables(cfg, tokens.shape[1])
    sparse_tables = None
    if "sparse" in cfg.layer_kinds:
        with jax.named_scope("attn_sparse"):
            sparse_tables = mixers.sparse_rope_tables(cfg, *tokens.shape,
                                                      positions)
    if cfg.hc_mult:
        x = hyper.widen(x, cfg.hc_mult)
    x, aux, load, kept, index = _walk(params, x, cfg, sin, cos, segment_ids,
                                      mla_tables, sparse_tables)
    if cfg.hc_mult:
        x = hyper.narrow(x)
    return (x, aux, load, kept, mla_tables,
            *(() if index is None else (index,)))


def _mtp(params: Params, cfg: MoEConfig, x: jax.Array, targets: jax.Array,
         head: jax.Array, mask, segment_ids, mla_tables):
    """The prediction module, everything of it under the scope ``mtp``: x
    [b, s, d] the trunk's stream before the final norm, ``targets`` [b, s]
    the next tokens. ``h' = [rms(x) ; rms(E[next token])] @ proj``; one
    expert layer of the last layer's kind on a stream started from ``h'``
    (``hc_mult`` copies of it where the stream is widened); its own final
    norm; the shared ``head``; the cross entropy of the token after next,
    which lies inside the row for all positions but the last (``mask``, the
    next tokens', is read at the target's place). Returns (that cross
    entropy, the layer's aux, its ``load`` [1, E] and ``kept`` [1])."""
    cdt, m = cfg.compute_dtype, params["mtp"]
    kind = cfg.layer_kinds[-1]
    with jax.named_scope("mtp"):
        joined = jnp.concatenate([
            llama.rmsnorm(x, m["hnorm"].astype(cdt), cfg.norm_eps),
            llama.rmsnorm(llama.embed(params, cfg, targets),
                          m["enorm"].astype(cdt), cfg.norm_eps)], axis=-1)
        x = joined @ m["proj"].astype(cdt)
        sin, cos = (llama.rope_angles(targets.shape[1], cfg.head_dim,
                                      cfg.rope_theta, cdt)
                    if kind == "window" else (None, None))
        if cfg.hc_mult:
            x = hyper.widen(x, cfg.hc_mult)
        run = _patterned_layer(cfg, kind, dense=False)
        x, aux, load, kept = llama.remat_block(
            cfg, lambda x, layer: run(x, layer, sin, cos, segment_ids,
                                      mla_tables))(
            x, _pick(m["layers"], (kind,), 0))
        if cfg.hc_mult:
            x = hyper.narrow(x)
        x = llama.rmsnorm(x, m["final_norm"].astype(cdt), cfg.norm_eps)
        after = jnp.roll(targets, -1, axis=1)
        live = jnp.ones(targets.shape, jnp.float32) if mask is None \
            else jnp.roll(mask.astype(jnp.float32), -1, axis=1)
        live = live.at[:, -1].set(0.0)
        ce = llama.chunked_ce(x, head, after, live, cfg.loss_chunk,
                              _head_placement(cfg, targets.shape[1]))
    return ce, aux, load[None], kept[None]


def forward_hidden(params: Params, tokens: jax.Array, cfg: MoEConfig,
                   segment_ids=None, positions=None
                   ) -> Tuple[jax.Array, jax.Array, jax.Array, Dict[str, Any]]:
    """-> (hidden, head, total_aux_loss, stats). ``stats`` is what the
    patterned form counts of its routing: ``ROUTING_COUNTERS`` by name and
    the layers' ``router_load`` [L, E], which moves the selection bias
    (``buffer_updates``); {} for the old stack, which counts nothing. A
    prediction module is the loss's (``loss_and_stats``) and no part of
    this. A config with a ``sparse`` layer also has ``index_loss``, the
    indexers' loss meaned over all layers (float32, which the loss adds at
    ``index_loss_coef``), and ``INDEX_COUNTERS`` by name."""
    x, aux, load, kept, _, *index = _trunk(params, tokens, cfg, segment_ids,
                                           positions)
    stats = {}
    if cfg.layer_kinds:
        with jax.named_scope("moe_router"):
            stats = {**routing_counters(cfg, load, kept), "router_load": load}
    if index:
        (loss, counts), = index
        with jax.named_scope("index_loss"):
            stats = {**stats, "index_loss": loss / cfg.n_layers,
                     **dict(zip(INDEX_COUNTERS, counts))}
    x, head = _final(params, cfg, x)
    return x, head, aux / cfg.n_expert_layers, stats


def _head_placement(cfg: MoEConfig, S: int):
    """``llama.head_for_loss_loop`` by this family's rules for a loss over
    ``S`` positions: what places the head before the chunked loss's loop,
    and again inside it."""
    return functools.partial(llama.head_for_loss_loop, rules=sharding_rules(),
                             cfg=cfg, S=S)


def _final(params: Params, cfg: MoEConfig, x: jax.Array):
    """The final norm of the trunk's stream and the head, under
    ``loss_head``."""
    cdt = cfg.compute_dtype
    with jax.named_scope("loss_head"):
        x = llama.rmsnorm(x, params["final_norm"].astype(cdt), cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(cdt)
    return x, head


def forward(params: Params, tokens: jax.Array, cfg: MoEConfig,
            segment_ids=None) -> jax.Array:
    x, head, _, _ = forward_hidden(params, tokens, cfg, segment_ids)
    return (x @ head).astype(jnp.float32)


def loss_and_stats(params: Params, batch: Dict[str, jax.Array], cfg: MoEConfig
                   ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token CE + router aux loss (llama's chunked CE reused, its
    loop's head gathered once before it under a mesh), and what the forward
    counted (``forward_hidden``'s ``stats``)."""
    inputs, targets = llama.inputs_and_targets(batch["tokens"])
    if cfg.n_mtp_modules:
        return _loss_with_mtp(params, batch, cfg, inputs, targets)
    x, head, aux, stats = forward_hidden(params, inputs, cfg,
                                         batch.get("segment_ids"),
                                         batch.get("position_ids"))
    with jax.named_scope("loss_head"):
        place = _head_placement(cfg, targets.shape[1])
        ce = llama.chunked_ce(x, place(head), targets,
                              batch.get("loss_mask"), cfg.loss_chunk, place)
    loss = ce + cfg.router_aux_coef * aux
    if "index_loss" in stats:
        with jax.named_scope("index_loss"):
            loss = loss + cfg.index_loss_coef * stats["index_loss"]
    return loss, stats


def _loss_with_mtp(params: Params, batch: Dict[str, jax.Array],
                   cfg: MoEConfig, inputs: jax.Array, targets: jax.Array):
    """``loss_and_stats`` of a config with a prediction module: the main
    head's cross entropy plus ``mtp_weight`` times the module's (``_mtp``),
    each a mean over its counted positions, plus the balancing term meaned
    over the trunk's expert layers and the module's. The counters count the
    module's layer too; its choices move its own bias
    (``mtp_router_load``)."""
    segment_ids, mask = batch.get("segment_ids"), batch.get("loss_mask")
    trunk, aux, load, kept, mla_tables = _trunk(params, inputs, cfg,
                                                segment_ids)
    x, head = _final(params, cfg, trunk)
    with jax.named_scope("loss_head"):
        place = _head_placement(cfg, targets.shape[1])
        head = place(head)
        ce = llama.chunked_ce(x, head, targets, mask, cfg.loss_chunk, place)
    ce_mtp, aux_mtp, load_mtp, kept_mtp = _mtp(
        params, cfg, trunk, targets, head, mask, segment_ids, mla_tables)
    with jax.named_scope("moe_router"):
        stats = {**routing_counters(cfg, jnp.concatenate([load, load_mtp]),
                                    jnp.concatenate([kept, kept_mtp])),
                 "router_load": load, "mtp_router_load": load_mtp}
    aux = (aux + aux_mtp) / cfg.n_routed_layers
    return ce + cfg.mtp_weight * ce_mtp + cfg.router_aux_coef * aux, stats


def lm_loss(params: Params, batch: Dict[str, jax.Array],
            cfg: MoEConfig) -> jax.Array:
    """``loss_and_stats``'s loss alone."""
    return loss_and_stats(params, batch, cfg)[0]


def sharding_rules(pipeline: bool = False) -> ShardingRules:
    """Llama rules + expert tensors, the expert matrices' ff dim over
    ``tp``. Where ``n_experts`` splits evenly over ``ep`` x ``fsdp`` the
    expert dimension takes both axes and the model dim stays whole: a chip
    owns whole experts, its share of every ``[E, C, .]`` buffer is local,
    and what crosses chips is ``[G, d]``: the tokens gathered to the
    experts' owners, the owners' partial outputs reduce-scattered back
    (``_rows_to_slots``, ``_rows_to_tokens``, which read this placement off
    the ambient mesh). Where it does not (6 experts on ``fsdp``
    4), experts go over ``ep`` and fsdp shards the model dim like the
    dense path: the contraction over d is then split, and GSPMD all-reduces
    the partial ``[E, C, f]`` products. Which one a mesh gets is resolved
    per leaf from its shape and the mesh (``ShardingRules``)."""
    if pipeline:
        raise NotImplementedError(
            "pipeline parallelism for the MoE family is not implemented")
    whole = ("ep", "fsdp")  # over the expert dimension: whole experts a chip
    return ShardingRules([
        (r"embed$", P("tp", "fsdp")),
        (r"lm_head$", P("fsdp", "tp")),
        (r"layers/w[qkvg]$", P(None, "fsdp", "tp")),
        (r"layers/wo$", P(None, "tp", "fsdp")),
        # the mixers that lie in a sub-tree of their kind's name: the
        # matrices into the heads like wq, out of them like wo, the narrow
        # ones (a latent, a low rank, a scalar a head) whole on that side
        (r"layers/(kda|mla|sparse)/w[qkv]$", P(None, "fsdp", "tp")),
        # an indexer's three matrices: narrow on the heads' side, whole there
        (r"layers/sparse/index_w[qkw]$", P(None, "fsdp", None)),
        (r"layers/mla/wq_a$", P(None, "fsdp", None)),
        (r"layers/mla/wq_b$", P(None, None, "tp")),
        # a hyper-connection's leaves: phi's long side like a matrix's model
        # dim, the rest (a gain the stream's width, 24 biases, 3 scalars) whole
        (r"layers/hc_\w+_phi$", P(None, "fsdp", None)),
        (r"layers/hc_\w+_(g|b|alpha)$", P(None)),
        (r"mtp/proj$", P("fsdp", "tp")),
        (r"layers/(kda|mla|sparse)/wo$", P(None, "tp", "fsdp")),
        (r"layers/(kda/(wb|[fg]_down)|mla/wkv_a)$", P(None, "fsdp", None)),
        (r"layers/(kda/[fg]_up|mla/wkv_b|kda/conv_[qkv])$",
         P(None, None, "tp")),
        (r"layers/kda/(dt|g)_bias$", P(None, "tp")),
        (r"layers/kda/A_log$", P(None)),
        # a patterned config's dense layers and shared expert, as llama's
        (r"layers/[ws]_(gate|up)$", P(None, "fsdp", "tp")),
        (r"layers/[ws]_down$", P(None, "tp", "fsdp")),
        (r"layers/router$", P(None, "fsdp", None)),
        (r"layers/router_bias(_m)?$", P(None)),
        (r"layers/e_(gate|up)$", [P(None, whole, None, "tp"),
                                  P(None, "ep", "fsdp", "tp")]),
        (r"layers/e_down$", [P(None, whole, "tp", None),
                             P(None, "ep", "tp", "fsdp")]),
        (r"layers/.*norm", P(None)),
        (r"norm", P()),
    ])


def expert_placement(e_gate_spec: P) -> str:
    """What a plan resolved for ``layers/e_gate``: ``"expert"`` where fsdp
    splits the expert dimension (chips own whole experts), ``"model_dim"``
    where it splits d."""
    axes = e_gate_spec[1]
    return "expert" if axes is not None and "fsdp" in axes else "model_dim"
