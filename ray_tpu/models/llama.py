"""Llama-family decoder-only transformer, TPU-first.

Design choices (vs. a torch port):
  - Params are a plain pytree; layers are STACKED along a leading axis and the
    forward pass is one ``lax.scan`` over them — a single compiled layer body
    regardless of depth (fast compiles, friendly to pipeline partitioning).
  - bf16 compute / fp32 params + fp32 softmax+loss accumulation.
  - ``jax.checkpoint`` (remat) around the scanned block body with a
    dots-saveable policy: trades HBM for recompute, the standard TPU recipe
    (``remat_block``: the flash forward's two results are kept as well).
  - Sharding is declarative: ``sharding_rules()`` returns rules mapping the
    param tree onto a (dp, fsdp, tp) mesh; batch rides (dp, fsdp), matrices
    shard (fsdp, tp). XLA inserts the collectives.

Capability parity note: the reference has no model zoo of its own — its Train
library wraps torch models (SURVEY.md §2.3). Here models are first-class
because the flagship benchmark (BASELINE.md config 3: Llama-7B tokens/s/chip)
lives inside the framework.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import mha
from ray_tpu.ops.norms import layernorm, rmsnorm
from ray_tpu.ops.rope import apply_rope, rope_angles
from ray_tpu.parallel.sharding import ShardingRules, axes_size
from jax.sharding import NamedSharding, PartitionSpec as P

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    remat: bool = True
    # Cross-entropy sequence chunk: >0 computes the loss in [B, chunk, V]
    # slices so the full fp32 logits tensor never materializes (at 32k vocab
    # the [B,S,V] logits + cotangent dominate HBM and cap the batch size).
    loss_chunk: int = 0
    # Attention backend: "xla" (fused einsum), "flash" (pallas kernel),
    # "ring" / "ulysses" (sequence-parallel over the mesh "sp" axis; needs
    # an ambient mesh_scope).
    attn_impl: str = "xla"
    # Pipeline parallelism: set to "pp" to split the layer stack over that
    # mesh axis (incompatible with ring/ulysses attn). Schedule: "gpipe"
    # (fwd scan + autodiff backward, stash grows with M) or "1f1b"
    # (interleaved manual-VJP schedule, stash is O(P) — pipeline.py).
    pipeline_axis: Optional[str] = None
    pipeline_microbatches: int = 4
    pipeline_schedule: str = "gpipe"
    # QK-norm as OLMoE has it: a learned RMSNorm over the whole q and the
    # whole k projection, before the split into heads and before RoPE
    qk_norm: bool = False
    # Granite's knobs, each neutral by default (a neutral one adds no
    # operation to a program): queries and keys rotated or not ("nope"), the
    # softmax scale where it is not 1/sqrt(head_dim), and the scalars on the
    # embedding, on every residual branch and under the logits. The cached
    # (served) blocks and ``ffn_half`` read them; the training blocks
    # refuse a config that sets one (``forward_hidden``).
    use_rope: bool = True
    attn_scale: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # Trinity's (afmoe's) knobs, neutral by default and read by the training
    # blocks only: a head size that is not ``d_model // n_heads``; QK-norm a
    # head (one gain [head_dim] for q and one for k, after the split into
    # heads, before RoPE) beside OLMoE's whole-projection form above; a
    # sigmoid gate ``wg`` [d, heads * hd] of the normed input on the
    # attention's output, before ``wo``; and a norm after each branch
    # (``attn_post_norm``, ``mlp_post_norm``) beside the one before it
    attn_head_dim: Optional[int] = None
    qk_norm_head: bool = False
    attn_gate: bool = False
    sandwich_norm: bool = False
    # EvaByte's knobs, neutral by default and read by the training blocks
    # only. ``attn_kind`` "eva": every layer's mixer is EVA attention
    # (``ops/eva.py``: exact inside ``eva_window`` positions, one learned
    # summary an ``eva_chunk`` of every earlier window, one softmax over
    # both; ``eva_phi`` / ``eva_mu`` [heads, head_dim] a layer), else "full".
    # ``norm_unit_offset``: every RMSNorm scales by ``1 + w`` (w from
    # zeros). ``residual_f32``: the residual stream is float32, the branches'
    # products stay in the compute dtype. ``n_pred_heads`` n > 1: the head is
    # [d, n * V], columns ``i V .. (i + 1) V`` predicting the token at
    # ``t + 1 + i``, and the loss the mean over the n of each one's mean
    # cross-entropy over the positions whose target lies inside the row
    attn_kind: str = "full"
    eva_window: int = 2048
    eva_chunk: int = 16
    norm_unit_offset: bool = False
    residual_f32: bool = False
    n_pred_heads: int = 1

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def n_attention_layers(self) -> int:
        """Layers that keep keys and values (``models/hybrid.py`` has
        layers that keep a recurrent state instead)."""
        return self.n_layers

    @property
    def n_recurrent_layers(self) -> int:
        return 0

    @property
    def n_window_layers(self) -> int:
        """Layers that keep the last ``sliding_window`` keys and values in
        a ring (``models/sambay.py`` has them)."""
        return 0

    #: where positions lie in ``generate.init_cache``'s ``k`` and ``v``
    #: [L, rows, ...]: before the heads (``models/sambay.py`` keeps a head's
    #: positions together, axis 3)
    kv_length_axis = 2

    def kv_shape(self, length: int) -> Tuple[int, int, int]:
        """What a cache row keeps of ``length`` positions' keys (values)."""
        return length, self.n_kv_heads, self.head_dim

    def qk_norm_params(self) -> int:
        """One layer's ``q_norm`` and ``k_norm`` weights (0 without them)."""
        if self.qk_norm_head:
            return 2 * self.head_dim
        return ((self.n_heads + self.n_kv_heads) * self.head_dim
                if self.qk_norm else 0)

    def attn_params(self) -> int:
        """One layer's attention half: the four projections, the gate where
        there is one, and the norms before, after and on q and k."""
        d, q = self.d_model, self.n_heads * self.head_dim
        return ((2 + self.attn_gate) * d * q
                + 2 * d * self.n_kv_heads * self.head_dim
                + (1 + self.sandwich_norm) * d + self.qk_norm_params()
                + (2 * q if self.attn_kind == "eva" else 0))

    def num_params(self) -> int:
        d, f, v, l = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        per_layer = (self.attn_params() + 3 * d * f
                     + (1 + self.sandwich_norm) * d)
        head = 0 if self.tie_embeddings else d * v * self.n_pred_heads
        return v * d + l * per_layer + d + head


PRESETS: Dict[str, LlamaConfig] = {
    "debug": LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                         n_kv_heads=2, d_ff=128, max_seq_len=128),
    "160m": LlamaConfig(vocab_size=32000, d_model=768, n_layers=12, n_heads=12,
                        n_kv_heads=12, d_ff=2048, max_seq_len=2048),
    "410m": LlamaConfig(vocab_size=32000, d_model=1024, n_layers=24, n_heads=16,
                        n_kv_heads=16, d_ff=2816, max_seq_len=2048),
    "1b": LlamaConfig(vocab_size=32000, d_model=2048, n_layers=22, n_heads=32,
                      n_kv_heads=4, d_ff=5632, max_seq_len=2048),
    "7b": LlamaConfig(),
}


def init_params(rng: jax.Array, cfg: LlamaConfig) -> Params:
    """Scaled-normal init; layer params stacked on a leading [n_layers] axis."""
    d, f = cfg.d_model, cfg.d_ff
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    L = cfg.n_layers
    keys = jax.random.split(rng, 8)

    def norm_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(cfg.param_dtype)

    # a norm's weight: ones, or zeros where the norm adds the unit itself
    unit = jnp.zeros if cfg.norm_unit_offset else jnp.ones
    params: Params = {
        "embed": norm_init(keys[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": unit((L, d), cfg.param_dtype),
            "wq": norm_init(keys[1], (L, d, hq * hd), d),
            "wk": norm_init(keys[2], (L, d, hkv * hd), d),
            "wv": norm_init(keys[3], (L, d, hkv * hd), d),
            "wo": norm_init(keys[4], (L, hq * hd, d), hq * hd),
            "mlp_norm": unit((L, d), cfg.param_dtype),
            "w_gate": norm_init(keys[5], (L, d, f), d),
            "w_up": norm_init(keys[6], (L, d, f), d),
            "w_down": norm_init(keys[7], (L, f, d), f),
        },
        "final_norm": unit((d,), cfg.param_dtype),
    }
    if cfg.attn_kind == "eva":
        from ray_tpu.ops import eva

        for n, name in enumerate(("eva_phi", "eva_mu")):
            params["layers"][name] = (eva.INIT_STD * jnp.clip(
                jax.random.normal(jax.random.fold_in(rng, 96 + n),
                                  (L, hq, hd), jnp.float32), -1.0, 1.0)
            ).astype(cfg.param_dtype)
    if cfg.qk_norm_head:
        params["layers"]["q_norm"] = jnp.ones((L, hd), cfg.param_dtype)
        params["layers"]["k_norm"] = jnp.ones((L, hd), cfg.param_dtype)
    elif cfg.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((L, hq * hd), cfg.param_dtype)
        params["layers"]["k_norm"] = jnp.ones((L, hkv * hd), cfg.param_dtype)
    if cfg.attn_gate:
        params["layers"]["wg"] = norm_init(
            jax.random.fold_in(rng, 98), (L, d, hq * hd), d)
    if cfg.sandwich_norm:
        for name in ("attn_post_norm", "mlp_post_norm"):
            params["layers"][name] = jnp.ones((L, d), cfg.param_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm_init(
            jax.random.fold_in(rng, 99),
            (d, cfg.vocab_size * cfg.n_pred_heads), d)
    return params


def project_qk(cfg: LlamaConfig, h: jax.Array, layer: Params,
               which: str) -> jax.Array:
    """The ``which`` ("q" or "k") projection of the normed hidden ``h``
    [B, S, d], still [B, S, heads * hd]: with ``qk_norm`` it goes through
    its RMSNorm whole, before the split into heads and before RoPE. Shared
    by the training blocks and the cached ones (``generate._qkv``)."""
    cdt = cfg.compute_dtype
    y = h @ layer["w" + which].astype(cdt)
    if cfg.qk_norm and not cfg.qk_norm_head:
        y = rmsnorm(y, layer[which + "_norm"].astype(cdt), cfg.norm_eps)
    return y


def embed(params: Params, cfg: LlamaConfig, tokens: jax.Array) -> jax.Array:
    """The tokens' embeddings in the compute dtype, times
    ``embedding_multiplier`` where the config has one."""
    x = params["embed"].astype(cfg.compute_dtype)[tokens]
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def post_norm(cfg: LlamaConfig, branch: jax.Array, layer: Params, name: str
              ) -> jax.Array:
    """A branch on its way to the residual stream: through the RMSNorm
    ``layer[name]`` where the config norms a branch's output too."""
    if not cfg.sandwich_norm:
        return branch
    return rmsnorm(branch, layer[name].astype(cfg.compute_dtype), cfg.norm_eps)


def attention_half(cfg: LlamaConfig, x: jax.Array, layer: Params,
                   sin: jax.Array, cos: jax.Array,
                   segment_ids: Optional[jax.Array], *,
                   rotate: bool = True,
                   window: Optional[int] = None) -> jax.Array:
    """The attention half's branch, pre-norm, which its caller joins to the
    stream (``join``) — shared by every model family (llama's dense blocks,
    moe's expert blocks). ``rotate`` and ``window`` are the layer's kind
    where a model has more than one (``models/moe.py``: rotated inside a
    band, or unrotated and full)."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cdt = cfg.compute_dtype

    h = rmsnorm(x, layer["attn_norm"].astype(cdt), cfg.norm_eps)
    q = project_qk(cfg, h, layer, "q").reshape(b, s, hq, hd)
    k = project_qk(cfg, h, layer, "k").reshape(b, s, hkv, hd)
    v = (h @ layer["wv"].astype(cdt)).reshape(b, s, hkv, hd)
    if cfg.qk_norm_head:
        q = rmsnorm(q, layer["q_norm"].astype(cdt), cfg.norm_eps)
        k = rmsnorm(k, layer["k_norm"].astype(cdt), cfg.norm_eps)
    if rotate:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    banded = {} if window is None else {"window": window}
    if cfg.attn_impl != "xla" and segment_ids is not None:
        raise NotImplementedError(
            f"segment_ids (packed sequences) require attn_impl='xla'; "
            f"got {cfg.attn_impl!r} — failing loudly rather than attending "
            f"across document boundaries")
    if cfg.attn_impl in ("ring", "ulysses"):
        from ray_tpu.parallel.context import sequence_parallel_attention

        if window is not None:
            raise NotImplementedError(
                "a window over a sequence split across chips: the ring "
                "would have to skip the hops below the band")
        attn = sequence_parallel_attention(q, k, v, impl=cfg.attn_impl,
                                           causal=True)
    elif cfg.attn_impl == "flash":
        from ray_tpu.ops.pallas.flash import flash_attention
        from ray_tpu.parallel.context import flash_attention_on_mesh

        # a pipeline stage already runs per device inside its shard_map
        attend = (flash_attention if cfg.pipeline_axis is not None
                  else flash_attention_on_mesh)
        attn = attend(q, k, v, causal=True, **banded)
    else:
        attn = mha(q, k, v, causal=True, segment_ids=segment_ids, **banded)
    attn = attn.reshape(b, s, hq * hd)
    if cfg.attn_gate:
        attn = attn * jax.nn.sigmoid(h @ layer["wg"].astype(cdt))
    return post_norm(cfg, attn @ layer["wo"].astype(cdt), layer,
                     "attn_post_norm")


def join(x: jax.Array, branch: jax.Array, mix=None) -> jax.Array:
    """A half layer's ``branch`` joined to the residual stream ``x``: where
    every training block, and the feed-forward half the served blocks share
    with them, writes its stream. The plain sum ``x + branch`` [b, s, d]; or,
    where the stream is a hyper-connection's rows [n, b, s, d] and ``mix``
    what ``ops/hyper.mix_in`` read of them for this half, its mix-out
    ``H_res x + H_post branch``. The halves themselves (``attention_half``,
    ``ffn_half``, ``eva_half``, ``mixers.kda_half``, ``mixers.mla_half``,
    ``moe._moe_ffn``) return their branch and add nothing."""
    if mix is None:
        return x + branch
    from ray_tpu.ops import hyper

    return hyper.mix_out(x, branch, mix)


def pre_norm(cfg: LlamaConfig, x: jax.Array, layer: Params, name: str
             ) -> jax.Array:
    """A block's norm before a branch: an RMSNorm with the weight
    ``layer[name]``, or, where the layer also has a bias ``name + "_b"``
    (``models/sambay.py``), a LayerNorm with both, in the compute dtype
    whatever ``x`` comes in."""
    cdt = cfg.compute_dtype
    if cfg.norm_unit_offset:
        return unit_offset_norm(cfg, x, layer[name])
    if name + "_b" in layer:  # (of a residual stream kept in float32 too)
        return layernorm(x, layer[name].astype(x.dtype),
                         layer[name + "_b"].astype(x.dtype),
                         cfg.norm_eps).astype(cdt)
    return rmsnorm(x, layer[name].astype(cdt), cfg.norm_eps)


#: what ``ffn_half`` calls its two products where the stream is float32
#: (``residual_f32``): the names ``remat_block`` keeps them by
FFN_RESIDUAL_NAMES = ("ffn_gate", "ffn_up")


def unit_offset_norm(cfg: LlamaConfig, x: jax.Array, w: jax.Array
                     ) -> jax.Array:
    """``x * rsqrt(mean x^2 + eps) * (1 + w)`` in ``x``'s dtype (the unit is
    added in it: a bfloat16 ``1 + w`` would lose a small ``w``), handed on in
    the compute dtype."""
    return rmsnorm(x, 1.0 + w.astype(x.dtype), cfg.norm_eps).astype(
        cfg.compute_dtype)


def ffn_half(cfg: LlamaConfig, x: jax.Array, layer: Params) -> jax.Array:
    """The pre-norm SwiGLU MLP's branch (``join`` adds it to the stream) —
    shared by train and decode paths."""
    cdt = cfg.compute_dtype
    h = pre_norm(cfg, x, layer, "mlp_norm")
    if cfg.residual_f32:
        # a trained block whose stream alone is float32: the products'
        # results stay in the compute dtype (at 16k tokens a float32 gate
        # and up are 1.4 GB a layer), the sum is taken in the stream's
        gate, up = (checkpoint_name(h @ layer[w].astype(cdt), name)
                    for w, name in zip(("w_gate", "w_up"), FFN_RESIDUAL_NAMES))
        gate = jax.nn.silu(gate)
        return ((gate * up) @ layer["w_down"].astype(cdt)).astype(x.dtype)
    if x.dtype != cdt:
        # a residual stream kept wider than the compute dtype
        # (``models/sambay.py``): every product is summed to the stream's
        # type and only what goes INTO a product is rounded
        def mm(a, w):
            return jnp.matmul(a.astype(cdt), layer[w].astype(cdt),
                              preferred_element_type=x.dtype)

        return on_residual(
            cfg, mm(jax.nn.silu(mm(h, "w_gate")) * mm(h, "w_up"), "w_down"))
    gate = jax.nn.silu(h @ layer["w_gate"].astype(cdt))
    up = h @ layer["w_up"].astype(cdt)
    return on_residual(cfg, post_norm(
        cfg, (gate * up) @ layer["w_down"].astype(cdt), layer,
        "mlp_post_norm"))


def on_residual(cfg: LlamaConfig, branch: jax.Array) -> jax.Array:
    """A block's branch as it joins the residual stream: times
    ``residual_multiplier`` where the config has one."""
    if cfg.residual_multiplier == 1.0:
        return branch
    return branch * jnp.asarray(cfg.residual_multiplier, branch.dtype)


def eva_half(cfg: LlamaConfig, x: jax.Array, layer: Params,
             sin: jax.Array, cos: jax.Array,
             segment_ids: Optional[jax.Array]) -> jax.Array:
    """Pre-norm EVA attention's branch (``ops/eva.py``), in the stream's
    dtype, under the scope ``attn_eva``: the norm, the three projections,
    and from their results to the mixer's output ``eva.eva_attention``:
    RoPE on q and k at their absolute positions, the chunk summaries and the attention over the
    window's keys and the earlier windows' summaries; then ``wo``. The
    kernels run where ``attn_impl`` is ``"flash"`` and no mesh of several
    chips is ambient (a Mosaic call is not partitioned): one call that
    rotates, turns heads first and pools (``eva_mix``) and four of attention
    (``eva_attend``), whose ``o`` XLA turns back for ``wo``. Everywhere else
    ``apply_rope``, transposes, the summaries under autodiff and the dense
    masked form."""
    from ray_tpu.ops import eva
    from ray_tpu.parallel.context import single_chip

    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids (packed sequences) under attn_kind='eva': a "
            "window's summaries would cross document boundaries")
    if cfg.n_kv_heads != cfg.n_heads:
        raise NotImplementedError("attn_kind='eva' summarises a head's own "
                                  "keys: n_kv_heads must equal n_heads")
    b, s, _ = x.shape
    hq, hd, cdt = cfg.n_heads, cfg.head_dim, cfg.compute_dtype
    kernels = cfg.attn_impl == "flash" and single_chip()
    with jax.named_scope("attn_eva"):
        h = pre_norm(cfg, x, layer, "attn_norm")
        q, k, v = ((h @ layer[w].astype(cdt)).reshape(b, s, hq, hd)
                   for w in ("wq", "wk", "wv"))
        attn = eva.eva_attention(
            q, k, v, sin, cos, layer["eva_phi"].astype(cdt),
            layer["eva_mu"].astype(cdt), window=cfg.eva_window,
            chunk=cfg.eva_chunk, impl="pallas" if kernels else "xla")
        out = attn.reshape(b, s, hq * hd) @ layer["wo"].astype(cdt)
        return out.astype(x.dtype)


def _rope_tables(cfg: LlamaConfig, seq: int) -> Tuple[jax.Array, jax.Array]:
    """The rotary tables of ``seq`` positions, under the scope of the mixer
    that reads them (a layer loop rebuilds them where it stands)."""
    with jax.named_scope("attn_eva" if cfg.attn_kind == "eva"
                         else "attn_full"):
        return rope_angles(seq, cfg.head_dim, cfg.rope_theta,
                           cfg.compute_dtype)


def _block(cfg: LlamaConfig, x: jax.Array, layer: Params,
           sin: jax.Array, cos: jax.Array,
           segment_ids: Optional[jax.Array]) -> jax.Array:
    """One decoder block as a train step runs it: pre-norm attn + pre-norm
    SwiGLU MLP, each half under its scope of a device trace
    (``parallel/train_step.STEP_SCOPES``; ``eva_half`` opens its own)."""
    if cfg.attn_kind == "eva":
        branch = eva_half(cfg, x, layer, sin, cos, segment_ids)
        with jax.named_scope("attn_eva"):
            x = join(x, branch)
    else:
        with jax.named_scope("attn_full"):
            x = join(x, attention_half(cfg, x, layer, sin, cos, segment_ids))
    with jax.named_scope("mlp"):
        return join(x, ffn_half(cfg, x, layer))


def _stage_scan(cfg: LlamaConfig, stage_layers: Params, h: jax.Array,
                seg: Optional[jax.Array]) -> jax.Array:
    """One pipeline stage: scan this rank's layer slice over ``h`` — the
    stage body shared by the GPipe and 1F1B schedules. RoPE tables are
    recomputed inside (cheap, XLA-hoisted) so the shard_map body closes
    over no tracers."""
    sin, cos = _rope_tables(cfg, h.shape[1])
    body = lambda hh, layer: (_block(cfg, hh, layer, sin, cos, seg), None)
    h, _ = jax.lax.scan(body, h, stage_layers)
    return h


def _pipelined_layers(layers: Params, x: jax.Array, cfg: LlamaConfig,
                      segment_ids: Optional[jax.Array]) -> jax.Array:
    """Layer stack split over the ``pp`` mesh axis, GPipe-microbatched.

    RoPE tables are recomputed inside the stage (cheap, XLA-hoisted) so the
    shard_map body closes over no tracers. Ring/Ulysses attention can't nest
    inside the pipeline shard_map — validated here.
    """
    from ray_tpu.parallel.context import current_mesh
    from ray_tpu.parallel.pipeline import pipeline_apply

    if cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError("pipeline_axis is incompatible with ring/ulysses "
                         "attention (nested shard_map); use attn_impl="
                         "'flash' or 'xla'")
    mesh = current_mesh()
    if mesh is None:
        raise ValueError("pipeline_axis needs an ambient mesh "
                         "(parallel.context.mesh_scope)")

    def stage(stage_layers, h, seg=None):
        return _stage_scan(cfg, stage_layers, h, seg)

    # Batch rides (dp, fsdp, tp) inside the pipeline region: tp lanes would
    # otherwise run fully redundant stage compute (stage weights are
    # replicated across them at the shard_map boundary — v1 limitation; a
    # manual-collective FSDP-within-stage layout is the follow-up).
    return pipeline_apply(
        stage, layers, x, mesh,
        axis_name=cfg.pipeline_axis,
        num_microbatches=cfg.pipeline_microbatches,
        batch_axes=(("dp", "fsdp", "tp"),),
        remat=cfg.remat,
        extras=segment_ids)


def refuse_served_only(cfg: LlamaConfig) -> None:
    """What no training block computes yet (the embedding's multiplier they
    do: ``embed``)."""
    if (not cfg.use_rope or cfg.attn_scale is not None
            or cfg.n_recurrent_layers
            or (cfg.residual_multiplier, cfg.logits_scaling) != (1.0, 1.0)):
        raise NotImplementedError(
            "the training blocks compute attention at 1/sqrt(head_dim) with "
            "no multiplier on a branch or under the logits and no recurrent "
            "layers; this config is served only (models/generate.py, "
            "models/hybrid.py, models/sambay.py)")


#: kinds of a patterned config's layers (``models/moe.py``) that the
#: training blocks compute and no served block does
TRAINED_ONLY_KINDS = ("kda", "mla", "sparse")


def refuse_trained_only(cfg: LlamaConfig) -> None:
    """What no served block computes yet: called where a cache is made
    (``generate.init_cache``), which every serving constructor does."""
    kinds = sorted(set(getattr(cfg, "layer_kinds", ()))
                   & set(TRAINED_ONLY_KINDS))
    if getattr(cfg, "attn_kind", "full") == "eva":
        raise NotImplementedError(
            "layers of kind ['eva'] are trained only (models/llama.py's "
            "dense training block): no served block keeps a window buffer "
            "that empties every eva_window positions beside a store of "
            "chunk summaries that is read a window late")
    why = []
    if kinds:
        why.append(
            f"layers of kind {kinds} are trained only (models/moe.py's "
            f"patterned walk): no served block keeps a latent slot cache "
            f"('mla'), a matrix state a slot with its update ('kda'), or a "
            f"cache of an indexer's keys with a gather of the positions it "
            f"picks inside the cached read ('sparse')")
    if getattr(cfg, "hc_mult", 0):
        why.append(
            f"a residual stream of hc_mult={cfg.hc_mult} rows under "
            f"hyper-connections is trained only (models/moe.py's patterned "
            f"walk, ops/hyper.py): no served block widens its stream")
    if getattr(cfg, "n_mtp_modules", 0):
        why.append(
            "a multi-token-prediction module (n_mtp_modules) is trained "
            "only (models/moe.py's loss): no engine drafts from one")
    if why:
        raise NotImplementedError("; ".join(why))


def remat_block(cfg: LlamaConfig, fn):
    """``fn``, a layer, as a remat block where the config asks for one. It
    keeps the results of its matrix products and, where a flash forward ran
    inside it, that kernel's output and log-sum-exp, which its backward
    kernels read: a ``pallas_call`` is no product, so the dots policy alone
    runs the forward kernel a second time in the backward. Likewise where a
    chunked delta rule ran inside it (``ops/kda.py``): the states at the
    chunks' starts and the recurrence's output, which are a scan's results
    and no product's, so that the backward does not walk the chunks a second
    time forward. A block without either (``attn_impl="xla"``, no ``kda``
    layer) carries no such name and keeps what the dots policy keeps, which
    in a ``kda`` layer is its projections' results too (q, k, v and the two
    low-rank gates, ~55 KB a token): recomputing them would cost a third
    more of the layer's products and the memory is there. Likewise where
    the hyper-connections' kernels ran inside it (``ops/hyper.py``,
    ``impl="pallas"``): ``mix_in``'s ``h``, coefficients and the two small
    results its backward call reads, so that the backward does not run the
    norm over the rows, ``phi``'s product and the iterations a second time
    (``h`` is 58.7 MB a half layer at Xing4's cell: 13.56 GiB compiled for
    13.32). Likewise where a learned choice ran inside it
    (``ops/sparse_index.py``): the choice itself, an int8 mask [b, s, s]
    that the backward kernels read as the forward did (268 MB a layer at
    16k: rebuilding it from a kept threshold would run the indexer's scores
    again, and a recomputed score one rounding off would choose another
    key), and the indexer's three gradients, which its loss's rule forms in
    its forward pass, so that the backward walks neither the scores, the
    threshold nor the loss's blocks again. An ``eva`` block keeps by name
    alone (below)."""
    if not cfg.remat:
        return fn
    from ray_tpu.ops import hyper, kda, sparse_index
    from ray_tpu.ops.pallas import flash

    policies = jax.checkpoint_policies
    if cfg.attn_kind == "eva":
        from ray_tpu.ops import eva

        # at 16k tokens and EvaByte's widths the dots policy's block does
        # not fit beside four layers' state (15.42 GiB of 15.75 by the
        # described-chip compile): this block keeps by name what its
        # backward reads and no product recomputes, q, k, v as the kernels
        # take them and the summaries (the five results of
        # ``ops/pallas/eva_mix.py``'s forward call, which its backward call
        # needs k and v of and no projection's raw result: the backward
        # runs neither that call nor wq, wk, wv's products again), the
        # kernels' o and lse, the feed-forward's gate and up (15.01 GiB),
        # and rebuilds the norms, SiLU, the product with wo and the
        # residual's float32 copies
        return jax.checkpoint(fn, policy=policies.save_only_these_names(
            *flash.RESIDUAL_NAMES, *eva.RESIDUAL_NAMES, *FFN_RESIDUAL_NAMES))
    return jax.checkpoint(fn, policy=policies.save_from_both_policies(
        policies.dots_with_no_batch_dims_saveable,
        policies.save_only_these_names(*flash.RESIDUAL_NAMES,
                                       *kda.RESIDUAL_NAMES,
                                       *hyper.RESIDUAL_NAMES,
                                       *sparse_index.RESIDUAL_NAMES)))


def forward_hidden(params: Params, tokens: jax.Array, cfg: LlamaConfig,
                   segment_ids: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """tokens [batch, seq] -> (final-norm hidden [batch, seq, d], head [d, V]),
    both in compute dtype — callers project to logits (possibly chunked)."""
    cdt = cfg.compute_dtype
    refuse_served_only(cfg)
    with jax.named_scope("embed"):
        x = embed(params, cfg, tokens)
        if cfg.residual_f32:
            x = x.astype(jnp.float32)
    sin, cos = _rope_tables(cfg, tokens.shape[1])

    if cfg.pipeline_axis is not None:
        x = _pipelined_layers(params["layers"], x, cfg, segment_ids)
    else:
        body = lambda x, layer: (_block(cfg, x, layer, sin, cos, segment_ids), None)
        x, _ = jax.lax.scan(remat_block(cfg, body), x, params["layers"])

    with jax.named_scope("loss_head"):
        if cfg.norm_unit_offset:
            x = unit_offset_norm(cfg, x, params["final_norm"])
        else:
            x = rmsnorm(x, params["final_norm"].astype(cdt), cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(cdt)
    return x, head


def forward(params: Params, tokens: jax.Array, cfg: LlamaConfig,
            segment_ids: Optional[jax.Array] = None) -> jax.Array:
    """tokens [batch, seq] -> logits [batch, seq, vocab] (fp32)."""
    x, head = forward_hidden(params, tokens, cfg, segment_ids)
    return (x @ head).astype(jnp.float32)


def lm_loss(params: Params, batch: Dict[str, jax.Array], cfg: LlamaConfig) -> jax.Array:
    """Next-token cross entropy; ``batch`` has tokens [B, S+1] (+opt. mask).

    With ``cfg.loss_chunk`` set (and dividing S), the vocab projection +
    softmax run chunk-by-chunk under a ``lax.scan`` that forms both
    gradients while it holds a chunk's logits (``_looped_ce``), so peak HBM
    holds one [B, chunk, V] fp32 slice and a group's cotangent in the
    compute dtype instead of [B, S, V] plus its cotangent — the logits, not
    the activations, are what cap batch size at 32k vocab. Under a mesh
    that shards the head's model dim the loop closes over a head gathered
    once before it (``head_for_loss_loop``): no chunk moves the head, and
    its gradient crosses the chips once after the loop.
    """
    inputs, targets = inputs_and_targets(batch["tokens"])
    x, head = forward_hidden(params, inputs, cfg, batch.get("segment_ids"))
    with jax.named_scope("loss_head"):
        place = functools.partial(
            head_for_loss_loop,
            rules=sharding_rules(cfg.pipeline_axis is not None), cfg=cfg,
            S=targets.shape[1])
        head = place(head)
        if cfg.n_pred_heads > 1:
            return multi_head_ce(x, head, targets, batch.get("loss_mask"),
                                 cfg.loss_chunk, cfg.n_pred_heads, place)
        return chunked_ce(x, head, targets, batch.get("loss_mask"),
                          cfg.loss_chunk, place)


def inputs_and_targets(tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """A batch's rows [B, S+1] as the S positions that are read and the S
    that are predicted, cut under the scope ``embed`` (the batch's way in)."""
    with jax.named_scope("embed"):
        return tokens[:, :-1], tokens[:, 1:]


def loss_and_stats(params: Params, batch: Dict[str, jax.Array],
                   cfg: LlamaConfig) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``lm_loss`` in the form a train step takes of every family: the loss
    and what the forward counted beside it, which here is nothing."""
    return lm_loss(params, batch, cfg), {}


def lm_loss_and_grads_1f1b(params: Params, batch: Dict[str, jax.Array],
                           cfg: LlamaConfig):
    """(loss, grads) via the interleaved 1F1B pipeline (manual per-stage
    VJPs — ``parallel/pipeline.py:pipeline_1f1b``). The embedding lookup is
    differentiated OUTSIDE the pipeline (its vjp scatter-adds the collected
    per-microbatch input cotangents); final norm + head live INSIDE the last
    stage's loss so the backward can start there. Selected by
    ``cfg.pipeline_schedule == "1f1b"`` in ``make_train_step``.
    """
    from ray_tpu.parallel import pipeline as pl
    from ray_tpu.parallel.context import current_mesh

    if cfg.tie_embeddings:
        raise NotImplementedError(
            "1f1b needs untied embeddings (the head lives inside the "
            "pipeline's last stage; the embedding outside it)")
    if cfg.attn_impl in ("ring", "ulysses"):
        raise ValueError("pipeline schedules are incompatible with "
                         "ring/ulysses attention (nested shard_map); use "
                         "attn_impl='flash' or 'xla'")
    mesh = current_mesh()
    if mesh is None:
        raise ValueError("1f1b needs an ambient mesh "
                         "(parallel.context.mesh_scope)")
    cdt = cfg.compute_dtype
    inputs, targets = inputs_and_targets(batch["tokens"])
    segs = batch.get("segment_ids")
    mask = batch.get("loss_mask")

    def embed_fn(embed_w):
        with jax.named_scope("embed"):
            return embed_w.astype(cdt)[inputs]

    x, embed_vjp = jax.vjp(embed_fn, params["embed"])

    def stage_fn(stage_layers, h, seg):
        return _stage_scan(cfg, stage_layers, h, seg)

    def head_loss_fn(head_bundle, y, tgt, msk):
        with jax.named_scope("loss_head"):
            y = rmsnorm(y, head_bundle["final_norm"].astype(cdt),
                        cfg.norm_eps)
            head = head_bundle["lm_head"].astype(cdt)
            return chunked_ce(y, head, tgt, msk, cfg.loss_chunk)

    head_bundle = {"final_norm": params["final_norm"],
                   "lm_head": params["lm_head"]}
    loss, g_layers, g_head, g_x = pl.pipeline_1f1b(
        stage_fn, head_loss_fn, params["layers"], head_bundle, x, targets,
        mesh,
        axis_name=cfg.pipeline_axis,
        num_microbatches=cfg.pipeline_microbatches,
        batch_axes=("dp", "fsdp", "tp"),
        segments=segs, loss_mask=mask)
    g_embed, = embed_vjp(g_x)
    grads = {"embed": g_embed, "layers": g_layers,
             "final_norm": g_head["final_norm"],
             "lm_head": g_head["lm_head"]}
    return loss, grads


def _loss_chunks(S: int, chunk: int) -> int:
    """How many chunks ``chunked_ce``'s loop makes of S positions; 0 where
    it runs no loop."""
    return S // chunk if chunk and S % chunk == 0 and S > chunk else 0


def head_for_loss_loop(head: jax.Array, rules: ShardingRules, cfg: Any,
                       S: int) -> jax.Array:
    """``head`` [d, V], in the compute dtype, as ``chunked_ce``'s loop
    should close over it: whole along d on every chip, V left on the axes
    the family's ``rules`` give it.

    The rules store the head with d over ``fsdp``. Left so, the product
    ``xc @ head`` inside the loop's body makes GSPMD gather the head in
    every chunk and reduce-scatter its gradient chunk by chunk: at
    ``[4096, 32000]`` over ``fsdp 4`` with 16 chunks, dozens of collectives
    of 262 MB a step where 2 do. Constrained here, before the loop, the
    gather happens once, and the head's gradient accumulates whole per chip
    in the loop's carry and is summed over the chips once after it; the
    loop repeats this placement on the head inside its body
    (``chunked_ce``'s ``place``), where the partitioner would otherwise
    shard the loop's operand as it liked.

    What tells is what the step can observe: the ambient mesh and the rule
    that places the head. With no mesh, no loop, every axis on d of size 1
    or a head the rules replicate (the pipelined ones, whose 1f1b loss runs
    inside a ``shard_map`` where a constraint on mesh axes is an error),
    the head comes back as it came and the program is what it was.

    Cost: ``d * V / tp`` elements of the compute dtype a chip for the
    gathered head and as much for its gradient's carry (262 MB each at
    4096 x 32000 bf16). A head too large for that (a 256k vocabulary)
    wants a loop over vocabulary shards with the log-sum-exp reduced
    instead, which nothing here builds.
    """
    from ray_tpu.parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is None or not _loss_chunks(S, cfg.loss_chunk):
        return head
    # a tied head is the embedding [V, d] read the other way round; a spec
    # names no axis for the dimensions it leaves out
    leaf, shape = (("embed", head.shape[::-1]) if cfg.tie_embeddings
                   else ("lm_head", head.shape))
    axes = (tuple(rules.spec_for(leaf, shape, mesh)) + (None, None))[:2]
    d_axes, v_axes = axes[::-1] if cfg.tie_embeddings else axes
    if axes_size(d_axes, mesh) == 1:
        return head
    return jax.lax.with_sharding_constraint(
        head, NamedSharding(mesh, P(None, v_axes)))


# Positions of a sequence whose logits' cotangent ``_looped_ce`` keeps before
# it multiplies them into the head's gradient as one product. That product
# contracts over the positions, so against its accumulator [d, V] (read and
# written in the head's dtype, 4 bytes an element a pass) it does
# ``positions / 2`` FLOP a byte: the chip's ridge (v5e: 197 TFLOP/s over
# 819 GB/s, 240 FLOP a byte) wants 1,024 and more, where a chunk of 256
# alone has the accumulator's traffic pace the product. What it costs is the
# kept cotangent, ``B * 2048 * V`` of the compute dtype: four chunks' float32
# logits at ``loss_chunk`` 256. On the chip 1,024, 2,048 and 4,096 lie within
# 1.5% of one another at every train cell's shape and 2,048 is the best or
# within 0.5 ms of it in each (``PERF.md`` section 6, PR 60).
HEAD_GRAD_ROWS = 2048


def _chunks_a_group(n_chunks: int, chunk: int) -> int:
    """How many of the loop's ``n_chunks`` chunks share one product into the
    head's gradient: the most that cover no more than ``HEAD_GRAD_ROWS``
    positions of a sequence and divide ``n_chunks``, so that every group is
    as long as every other (six chunks of 512 go three and three, not four
    and two)."""
    most = max(1, min(HEAD_GRAD_ROWS // chunk, n_chunks))
    return max(g for g in range(1, most + 1) if n_chunks % g == 0)


def _ce_loop(x, head, ts, ms, chunk, n, logits_dtype, place, grads):
    """The chunked cross entropy's loop over x [B, S, d], ``head``
    [d, n * V], the heads' targets ``ts`` and float32 weights ``ms``
    [B, S, n]: (the mean over the heads of each one's mean over its counted
    positions, (dx, dhead) of that mean or None).

    A chunk's logits are summed into ``logits_dtype`` and read as float32;
    ``log_softmax``, the targets' weighted sum and the weights' sum are the
    loop-free path's operations. With ``grads``, while the float32 logits
    are in hand, the chunk also forms the cotangent autodiff would hand the
    two backward products, ``(softmax - onehot) * m / (n * max(count, 1))``
    cast to x's dtype, multiplies it into the chunk's place of ``dx``, and
    keeps it for the rest of its group (``_chunks_a_group``); a group ends
    with ONE product ``x_group^T @ g_group``, summed in float32 and added
    to ``dhead``. No product runs twice, and the accumulator is read and
    written once a group, not once a chunk.

    One scan over the groups, a group's chunks unrolled inside it.
    ``place`` (``head_for_loss_loop`` by the caller's rules; nothing with no
    mesh) is repeated on the head INSIDE the loop's body: the partitioner
    gives a loop's operand a sharding of its own choosing, and with the
    head's gradient in the carry it chose the stored one, d split, and
    gathered the head a group and a group's cotangent over the batch a chunk
    (``fsdp 4`` on the described chips; with a second scan inside,
    ``fsdp 2 x tp 2`` gathered the head a chunk). Placed in the body, the
    head is gathered once a step before the loop, and the sum over a split
    batch leaves the loop as one sum over the chips. On one chip a second
    scan and the unrolled inside run the train cells within 0.1% of each
    other (``PERF.md`` section 6, PR 60).

    ``dhead`` is carried in the head's dtype: the float32 ``[d, V]`` this
    could be is alive together with its cast when the loop ends, and that
    made the loss the step's peak on the described chip (Mistral 14.90 GiB
    for 14.71, Trinity 15.20 for 15.06) whatever the group's length. A
    group's product still sums in float32; a step rounds the carry once a
    group, ``S / 2048`` times."""
    B, S, d = x.shape
    V = head.shape[1] // n
    n_chunks = S // chunk
    per = _chunks_a_group(n_chunks, chunk) if grads else n_chunks
    f32 = jnp.float32

    def cut(a):  # [B, S, ...] -> [groups, chunks a group, B, chunk, ...]
        return jnp.moveaxis(
            a.reshape(B, n_chunks // per, per, chunk, *a.shape[2:]), 0, 2)

    # what a position's nll weighs in the result: its head's mean over the
    # counted positions, the heads' mean
    weight = 1.0 / (n * jnp.maximum(ms.sum((0, 1)), 1))
    columns = jnp.arange(V, dtype=ts.dtype)

    def one_chunk(head, carry, sl):
        xc, tc, mc = sl
        logits = jnp.matmul(xc, head, preferred_element_type=logits_dtype)
        logp = jax.nn.log_softmax(
            logits.astype(f32).reshape(B, chunk, n, V), -1)
        took = jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        total, count = carry
        carry = (total - (took * mc).sum((0, 1)), count + mc.sum((0, 1)))
        if not grads:
            return carry, None
        g = (jnp.exp(logp) - (tc[..., None] == columns)) \
            * (mc * weight)[..., None]
        g = g.astype(x.dtype).reshape(B, chunk, n * V)
        dxc = jax.lax.dot_general(g, head, (((2,), (1,)), ((), ())),
                                  preferred_element_type=x.dtype)
        return carry, (g, dxc)

    def one_group(carry, sl):
        *sums, dhead = carry
        sums, (g, dxg) = jax.lax.scan(
            functools.partial(one_chunk, place(head)), tuple(sums), sl,
            unroll=True)
        dhead = (dhead + jnp.einsum("pbcd,pbcv->dv", sl[0], g,
                                    preferred_element_type=f32)
                 ).astype(head.dtype)
        return (*sums, dhead), dxg

    sums = (jnp.zeros((n,), f32),) * 2
    slices = (cut(x), cut(ts), cut(ms))
    if grads:
        (*sums, dhead), dx = jax.lax.scan(
            one_group, (*sums, jnp.zeros_like(head)), slices)
        made = (jnp.moveaxis(dx, 2, 0).reshape(B, S, d), dhead)
    else:  # one group of every chunk
        sums, made = jax.lax.scan(
            lambda carry, sl: one_chunk(place(head), carry, sl), sums,
            jax.tree.map(lambda a: a[0], slices))
    total, count = sums
    return (total / jnp.maximum(count, 1)).mean(), made


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _looped_ce(x, head, ts, ms, chunk, n, logits_dtype, place):
    """``_ce_loop``'s mean under the rule that forms both gradients in the
    loop that holds the logits; where nothing is differentiated the loop
    forms none."""
    return _ce_loop(x, head, ts, ms, chunk, n, logits_dtype, place, False)[0]


def _looped_ce_fwd(x, head, ts, ms, chunk, n, logits_dtype, place):
    return _ce_loop(x, head, ts, ms, chunk, n, logits_dtype, place, True)


def _looped_ce_bwd(chunk, n, logits_dtype, place, kept, ct):
    """Both gradients were formed for a cotangent of one: the incoming
    scalar scales them (elementwise, into what reads them). Targets and
    weights take none."""
    return (*((a * ct).astype(a.dtype) for a in kept), None, None)


_looped_ce.defvjp(_looped_ce_fwd, _looped_ce_bwd)


def _as_it_came(head: jax.Array) -> jax.Array:
    return head


def chunked_ce(x: jax.Array, head: jax.Array, targets: jax.Array,
               mask: Optional[jax.Array], chunk: int,
               place: Callable[[jax.Array], jax.Array] = _as_it_came
               ) -> jax.Array:
    """Cross entropy from final hiddens; shared by every model family.

    Knows no mesh: the loop multiplies by ``head`` as it is handed in, in
    every chunk, so a caller under a mesh passes it through
    ``head_for_loss_loop`` first, and hands that placement in as ``place``
    for the loop to repeat on the head inside its body. Where it loops
    (``_loss_chunks``) it is ``_looped_ce`` of one head, the logits rounded
    to the compute dtype as the loop-free path below and ``forward`` round
    them."""
    if _loss_chunks(targets.shape[1], chunk):
        live = jnp.ones(targets.shape, jnp.float32) if mask is None \
            else mask.astype(jnp.float32)
        return _looped_ce(x, head, targets[..., None], live[..., None],
                          chunk, 1, jnp.result_type(x, head), place)

    logits = (x @ head).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        return nll.mean()
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)


def multi_head_ce(x: jax.Array, head: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array], chunk: int, n: int,
                  place: Callable[[jax.Array], jax.Array] = _as_it_came
                  ) -> jax.Array:
    """Cross entropy of ``n`` prediction heads from final hiddens x
    [B, S, d]: ``head`` [d, n * V], its columns ``i V .. (i + 1) V`` the
    logits for the token ``1 + i`` positions on; ``targets`` [B, S] the next
    tokens (head 0's). Head ``i``'s target at position ``t`` is
    ``targets[t + i]``, which lies inside the row for ``S - i`` positions;
    ``mask`` [B, S] (None: all ones) is read at the target's place. The mean
    over the heads of each one's mean over its counted positions; float32
    logits, summed from the compute dtype's operands. In ``chunk``-position
    slices under ``_looped_ce``'s loop where ``chunked_ce`` would loop."""
    B, S = targets.shape
    V = head.shape[1] // n
    reach = jnp.arange(S)[:, None] + jnp.arange(n)[None, :]      # [S, n]
    live = jnp.ones((B, S), jnp.float32) if mask is None \
        else mask.astype(jnp.float32)
    past = ((0, 0), (0, n - 1))
    ts = jnp.pad(targets, past)[:, reach]                        # [B, S, n]
    ms = jnp.pad(live, past)[:, reach]
    if _loss_chunks(S, chunk):
        return _looped_ce(x, head, ts, ms, chunk, n, jnp.float32, place)

    logits = jnp.matmul(x, head, preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits.reshape(B, S, n, V), -1)
    took = jnp.take_along_axis(logp, ts[..., None], axis=-1)[..., 0]
    total, count = -(took * ms).sum((0, 1)), ms.sum((0, 1))      # [n], [n]
    return (total / jnp.maximum(count, 1)).mean()


def sharding_rules(pipeline: bool = False) -> ShardingRules:
    """Param partitioning over the (pp, dp, fsdp, tp) mesh (scaling-book
    layout).

    The leading stacked-layer axis is sharded over ``pp`` when pipelining
    (else unsharded); matrices put their contracting/output dims on
    (fsdp, tp) so forward matmuls all-gather over fsdp (ZeRO-3) and reduce
    over tp.

    Pipelined layer weights keep their non-layer dims REPLICATED: the
    pipeline shard_map consumes stage weights whole (``pipeline_apply``
    in_specs = P("pp")), and storing them fsdp/tp-sharded would force a
    replicate-then-partition reshard at the boundary — the
    ``spmd_partitioner`` "involuntary full rematerialization" warning — on
    every step's backward transpose. Storage layout == consumption layout;
    the embed/lm_head (outside the pipeline region) stay fsdp/tp-sharded.
    """
    if pipeline:
        # Embed/head replicated too: feature-sharded embeddings make GSPMD
        # carry feature-tiled activations into/out of the batch-tiled
        # pipeline region — the same boundary reshard in disguise.
        return ShardingRules([
            (r"layers/", P("pp")),
            (r".*", P()),
        ])
    return ShardingRules([
        (r"embed$", P("tp", "fsdp")),
        (r"lm_head$", P("fsdp", "tp")),
        (r"layers/w[qkv]$", P(None, "fsdp", "tp")),
        (r"layers/wo$", P(None, "tp", "fsdp")),
        (r"layers/w_(gate|up)$", P(None, "fsdp", "tp")),
        (r"layers/w_down$", P(None, "tp", "fsdp")),
        (r"layers/.*norm", P(None)),
        (r"layers/eva_(phi|mu)$", P(None, "tp", None)),
        (r"norm", P()),
    ])


def data_rules() -> ShardingRules:
    return ShardingRules([(r".*", P(("dp", "fsdp"), None))])
