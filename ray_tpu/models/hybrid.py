"""A decoder whose layers are of two kinds in a published order: Mamba-2
state-space layers beside grouped-query attention (IBM's Granite 4.0 "H":
transformers' ``granitemoehybrid``). Served only; it has no training path.

Per token, ``x`` the residual stream (``eps`` = ``norm_eps``)::

    x = embedding_multiplier * E[token]
    every layer:  x = x + residual_multiplier * Mixer(RMSNorm(x))
                  x = x + residual_multiplier * SwiGLU(RMSNorm(x))
    logits = RMSNorm(x) E^T / logits_scaling

An ``attention`` layer's mixer is the Llama block's (``generate._qkv``,
``ops.attention.mha``) without rotation (``use_rope`` false) and with the
softmax scale the config gives (``attn_scale``). A ``mamba`` layer's mixer,
``u`` its normed input:

1. ``[z | xBC | dt] = u W_in`` (widths ``d_inner``, ``d_inner + 2 g n``, ``h``);
2. a causal depthwise convolution over the last ``mamba_d_conv`` positions
   of ``xBC``, with bias, then ``silu``; split into ``x`` (h heads of p),
   ``B`` and ``C`` (g groups of n);
3. ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, one scalar a head;
4. the recurrence ``h_t = exp(dt A) h_{t-1} + dt x (x) B``, ``y = h_t C + D x``
   (``ops/ssm.py``): chunked over a prompt, one step for a decode token;
5. ``RMSNorm(y * silu(z)) * w`` over all of ``d_inner``, then ``W_out``.

The SwiGLU half is ``llama.ffn_half`` and the head ``generate._head``.

Parameters are stacked BY LAYER KIND (``params["layers"]["mamba"]``,
``["attention"]``), and the forward walks ``layer_types`` period by period
(``_walk``, which ``models/sambay.py`` shares for an order of several
segments): a ``lax.scan`` over the repeats of the order's shortest
repeating pattern (Granite's ten layers: five mamba, one attention, four
mamba), inside it each run of one kind a ``lax.scan`` over that kind's stack
from where the kind's last run ended. The outer loop is what keeps the
cache in a loop's carry for every layer: four attention blocks written out
one after another made the TPU compiler copy K and V round each (PERF.md,
PR 31). So is the cache: ``k``/``v`` for the attention layers,
and for the mamba layers ``ssm`` [L, rows, n, h p] in float32 (state element
major, channels minor, head ``i``'s channels the lanes ``i p .. (i + 1) p``:
the layout S6's state has, for ``ops/ssm.py``'s reason: a decode step's
decay, ``dt x`` and ``y`` are then lane-dense rows) and ``conv``
[L, rows, d_conv - 1, d_inner + 2 g n], the convolution's tail.

Departures from the published code, all of precision: activations are the
compute dtype (bf16), the recurrent state, the decays and ``dt`` are float32
(``ops/ssm.py`` says why), ``A_log``, ``dt_bias`` and ``D`` are float32
parameters whatever ``param_dtype`` is.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import generate as G
from ray_tpu.models import llama
from ray_tpu.ops import ssm
from ray_tpu.ops.norms import rmsnorm
from ray_tpu.ops.pallas.ssm_update import ssm_update_in_place

Params = Dict[str, Any]
KINDS = ("mamba", "attention")


@dataclasses.dataclass(frozen=True)
class HybridConfig(llama.LlamaConfig):
    # one of KINDS per layer, in the model's order; ``n_layers`` is its length
    layer_types: Tuple[str, ...] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    state_dtype: Any = jnp.float32

    #: the cache tree's buffers a layer of each kind reads and writes
    #: (``_walk`` carries them through the kind's run)
    BUFFERS: ClassVar[Mapping[str, Tuple[str, ...]]] = {
        "mamba": ("ssm", "conv"), "attention": ("k", "v")}

    def __post_init__(self):
        check_layer_types(self, KINDS)

    @property
    def n_attention_layers(self) -> int:
        return self.layer_types.count("attention")

    @property
    def n_recurrent_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels the convolution runs over: x, B and C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def pattern(self) -> Tuple[str, ...]:
        """The shortest run of kinds that ``layer_types`` repeats whole."""
        n = self.n_layers
        return next(self.layer_types[:p] for p in range(1, n + 1)
                    if n % p == 0
                    and self.layer_types[:p] * (n // p) == self.layer_types)

    def segments(self) -> List[Tuple[Tuple[str, ...], int]]:
        """The order as ``_walk`` takes it, [(pattern, repeats)]: here the
        one pattern the order repeats whole."""
        pattern = self.pattern()
        return [(pattern, self.n_layers // len(pattern))]

    def state_bytes_per_row(self) -> int:
        """A row's recurrent state and convolution tails, all layers."""
        state = (self.mamba_n_heads * self.mamba_d_head * self.mamba_d_state
                 * jnp.dtype(self.state_dtype).itemsize)
        tail = ((self.mamba_d_conv - 1) * self.conv_dim
                * jnp.dtype(self.compute_dtype).itemsize)
        return self.n_recurrent_layers * (state + tail)

    def kv_bytes_per_position(self) -> int:
        return (2 * self.n_attention_layers * self.n_kv_heads * self.head_dim
                * jnp.dtype(self.compute_dtype).itemsize)

    def num_params(self) -> int:
        d, f, v = self.d_model, self.d_ff, self.vocab_size
        hd, h = self.head_dim, self.mamba_n_heads
        mlp = 3 * d * f + 2 * d  # and the layer's two norms
        attention = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        mamba = (d * (self.d_inner + self.conv_dim + h)
                 + (self.mamba_d_conv + 1) * self.conv_dim + 3 * h
                 + self.d_inner + self.d_inner * d)
        head = 0 if self.tie_embeddings else d * v
        return (v * d + d + head + self.n_attention_layers * (attention + mlp)
                + self.n_recurrent_layers * (mamba + mlp))


def check_layer_types(cfg, kinds: Tuple[str, ...]) -> None:
    if len(cfg.layer_types) != cfg.n_layers or set(
            cfg.layer_types) - set(kinds):
        raise ValueError(
            f"layer_types names {len(cfg.layer_types)} layers of kinds "
            f"{sorted(set(cfg.layer_types))}; n_layers is "
            f"{cfg.n_layers} and the kinds are {kinds}")


def model_of(cfg):
    """The module that computes ``cfg``'s layers (``init_state``,
    ``forward_with_cache``, ``decode_step_in_place``): this one, or the one
    a config of another module names."""
    import importlib

    return importlib.import_module(type(cfg).__module__)


PRESETS: Dict[str, HybridConfig] = {
    "hybrid-debug": HybridConfig(
        vocab_size=256, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, tie_embeddings=True, use_rope=False,
        attn_scale=0.125, embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16, mamba_chunk_size=8),
}


def init_params(rng: jax.Array, cfg: HybridConfig) -> Params:
    """Matrices normal at 1/sqrt(fan-in), norm weights ones, and the Mamba
    parameters as ``mamba_ssm`` makes them, so that the state has a long
    memory: ``A_log = log(1..h)``, ``dt_bias`` the inverse softplus of a
    log-uniform draw in [1e-3, 1e-1], ``D`` ones, the convolution and its
    bias uniform in +-1/sqrt(d_conv). Stacked by layer kind."""
    d, f, pdt = cfg.d_model, cfg.d_ff, cfg.param_dtype
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h, di, ch, k = (cfg.mamba_n_heads, cfg.d_inner, cfg.conv_dim,
                    cfg.mamba_d_conv)
    la, lm = cfg.n_attention_layers, cfg.n_recurrent_layers
    keys = iter(jax.random.split(rng, 16))

    def normal(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(pdt)

    def uniform(shape, bound):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound,
                                  bound).astype(pdt)

    def mlp(n):
        return {"mlp_norm": jnp.ones((n, d), pdt),
                "w_gate": normal((n, d, f), d), "w_up": normal((n, d, f), d),
                "w_down": normal((n, f, d), f)}

    dt = jnp.exp(jax.random.uniform(next(keys), (lm, h), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed": normal((cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), pdt),
        "layers": {
            "attention": {
                "attn_norm": jnp.ones((la, d), pdt),
                "wq": normal((la, d, hq * hd), d),
                "wk": normal((la, d, hkv * hd), d),
                "wv": normal((la, d, hkv * hd), d),
                "wo": normal((la, hq * hd, d), hq * hd), **mlp(la)},
            "mamba": {
                "ssm_norm": jnp.ones((lm, d), pdt),
                # [out, in] as the checkpoint has it: 8512 is no multiple
                # of the chip's 128 lanes, so an [in, out] stack is kept
                # with 2048 minor and every program transposed all of it
                # before its layer loop (3.8 ms a launch; PERF.md, PR 31)
                "in_proj": normal((lm, di + ch + h, d), d),
                "conv_w": uniform((lm, k, ch), 1 / math.sqrt(k)),
                "conv_b": uniform((lm, ch), 1 / math.sqrt(k)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)), (lm, h)),
                "D": jnp.ones((lm, h), jnp.float32),
                "gate_norm": jnp.ones((lm, di), pdt),
                "out_proj": normal((lm, di, d), di), **mlp(lm)},
        },
    }


def init_state(cfg: HybridConfig, batch: int) -> Dict[str, jax.Array]:
    """The mamba layers' zeroed part of ``generate.init_cache``'s tree."""
    lm = cfg.n_recurrent_layers
    return {"ssm": jnp.zeros((lm, batch, cfg.mamba_d_state, cfg.d_inner),
                             cfg.state_dtype),
            "conv": jnp.zeros((lm, batch, cfg.mamba_d_conv - 1, cfg.conv_dim),
                              cfg.compute_dtype)}


def _layer_of(stack: Params, i) -> Params:
    """Layer ``i`` of a kind's stacked parameters, read where it lies."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), stack)


def _mamba_in(cfg: HybridConfig, x, layer: Params, tail):
    """Steps 1 to 3 for ``x`` [B, S, d] after the ``tail`` [B, d_conv - 1,
    conv_dim] of inputs before it. Returns (z, x [B, S, h, p], B, C
    [B, S, g, n], dt [B, S, h] float32, A [h], the new tail)."""
    cdt, di, gn = cfg.compute_dtype, cfg.d_inner, (cfg.mamba_n_groups,
                                                    cfg.mamba_d_state)
    b, s, _ = x.shape
    with jax.named_scope("ssm_proj"):
        u = rmsnorm(x, layer["ssm_norm"].astype(cdt), cfg.norm_eps)
        zxbcdt = jnp.einsum("bsd,nd->bsn", u, layer["in_proj"].astype(cdt))
        z, xbc, dt = jnp.split(zxbcdt, [di, di + cfg.conv_dim], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc, tail = ssm.causal_conv(xbc, tail, layer["conv_w"].astype(cdt),
                                    layer["conv_b"].astype(cdt))
        xbc = jax.nn.silu(xbc)
        xs, bm, cm = jnp.split(xbc, [di, di + gn[0] * gn[1]], axis=-1)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + layer["dt_bias"])
        a = -jnp.exp(layer["A_log"].astype(jnp.float32))
    return (z, xs.reshape(b, s, cfg.mamba_n_heads, cfg.mamba_d_head),
            bm.reshape(b, s, *gn), cm.reshape(b, s, *gn), dt, a, tail)


def _mamba_out(cfg: HybridConfig, x, y, xs, z, layer: Params):
    """The skip term, step 5 and the residual, then the SwiGLU half."""
    cdt = cfg.compute_dtype
    b, s, _ = x.shape
    with jax.named_scope("ssm_proj"):
        y = y + xs * layer["D"].astype(cdt)[:, None]
        y = y.reshape(b, s, cfg.d_inner) * jax.nn.silu(z)
        y = rmsnorm(y, layer["gate_norm"].astype(cdt), cfg.norm_eps)
        x = x + llama.on_residual(cfg, y @ layer["out_proj"].astype(cdt))
    with jax.named_scope("mlp"):
        return llama.join(x, llama.ffn_half(cfg, x, layer))


def _mamba_block(cfg: HybridConfig, x, layer: Params, state, tail):
    """A mamba block over [B, S, d] from ``state`` [B, n, h p] and
    ``tail``; returns (hidden, the state and the tail after token S - 1)."""
    z, xs, bm, cm, dt, a, tail = _mamba_in(cfg, x, layer, tail)
    with jax.named_scope("ssm_scan"):
        y, state = ssm.ssd_scan(xs, dt, a, bm, cm, chunk=cfg.mamba_chunk_size,
                                h0=state)
    return _mamba_out(cfg, x, y, xs, z, layer), state, tail


def _runs(pattern: Tuple[str, ...]) -> List[Tuple[str, int, int]]:
    """``pattern`` as runs of one kind: (kind, how many layers of the kind
    the pattern has before the run, layers in the run)."""
    out: List[Tuple[str, int, int]] = []
    done: Dict[str, int] = {}
    i = 0
    while i < len(pattern):
        kind, j = pattern[i], i
        while j < len(pattern) and pattern[j] == kind:
            j += 1
        out.append((kind, done.get(kind, 0), j - i))
        done[kind] = done.get(kind, 0) + j - i
        i = j
    return out


def _walk(cfg, x, cache: Dict, blocks: Dict,
          segments: Optional[List[Tuple[Tuple[str, ...], int]]] = None,
          start: Optional[Dict[str, int]] = None) -> Tuple[Any, Dict]:
    """``x`` through the layers of ``segments`` (``cfg.segments()``, the
    whole model in its order, left None), the cache tree in the loops'
    carry. A segment is a pattern of kinds and how often it repeats: a
    ``lax.scan`` over the repeats, inside it each run of one kind a
    ``lax.scan`` over that kind's stack from where the kind's last run
    ended. ``blocks[kind](x, bufs, i) -> (x, bufs)`` runs layer ``i`` of the
    kind's stack on the kind's buffers (``cfg.BUFFERS``). ``start``: layers
    of each kind that lie before the first segment (none, left None)."""
    done = dict(start or {})
    cache = dict(cache)
    for pattern, repeats in (cfg.segments() if segments is None
                             else segments):
        def one_period(carry, rep, pattern=pattern, done=dict(done)):
            x, cache = carry
            for kind, before, n in _runs(pattern):
                names = cfg.BUFFERS[kind]

                def layer(c, i, block=blocks[kind]):
                    x, bufs = block(c[0], c[1:], i)
                    return (x, *bufs), None

                first = rep * pattern.count(kind) + (before
                                                     + done.get(kind, 0))
                (x, *bufs), _ = jax.lax.scan(
                    layer, (x, *(cache[name] for name in names)),
                    first + jnp.arange(n))
                cache = {**cache, **dict(zip(names, bufs))}
            return (x, cache), None

        (x, cache), _ = jax.lax.scan(one_period, (x, cache),
                                     jnp.arange(repeats))
        for kind in set(pattern):
            done[kind] = done.get(kind, 0) + repeats * pattern.count(kind)
    return x, cache


def forward_with_cache(params: Params, tokens: jax.Array, cfg: HybridConfig,
                       cache: Dict, pos, last_only: bool = True
                       ) -> Tuple[jax.Array, Dict]:
    """``generate._forward_with_cache`` for this model: tokens [B, S] at
    absolute position ``pos`` after what ``cache`` holds (the attention
    layers' keys and values below ``pos``, the mamba layers' state and
    tail after token ``pos - 1``) -> (logits, the cache after token
    ``pos + S - 1``). A prefill is ``pos`` 0 on a zeroed cache."""
    layers = params["layers"]
    sin, cos = G._rope_table(cfg, cache["k"].shape[2])

    def mamba(x, bufs, i):
        st, tl = bufs
        x, new_st, new_tl = _mamba_block(cfg, x, _layer_of(layers["mamba"], i),
                                         st[i], tl[i])
        return x, (st.at[i].set(new_st.astype(st.dtype)), tl.at[i].set(new_tl))

    def attention(x, bufs, i):
        ck, cv = bufs
        x, k, v, _ = G._block_with_cache(
            cfg, x, _layer_of(layers["attention"], i), ck[i], cv[i], sin, cos,
            pos)
        return x, (ck.at[i].set(k), cv.at[i].set(v))

    x, cache = _walk(cfg, G.embed(params, cfg, tokens), cache,
                     {"mamba": mamba, "attention": attention})
    with jax.named_scope("head_sample"):
        logits = G._head(params, cfg, x[:, -1:, :] if last_only else x)
    return logits, cache


def decode_step_in_place(params: Params, tok: jax.Array, cfg: HybridConfig,
                         cache: Dict, slot0, pos: jax.Array
                         ) -> Tuple[jax.Array, Dict]:
    """One decode step for the ``B`` rows ``slot0 .. slot0 + B`` of a slot
    cache (``generate.init_cache``'s tree over all the slots): ``tok`` [B]
    is each row's token at its own position ``pos`` [B]. Returns (logits
    [B, V] float32, the tree).

    The whole tree rides the layer loops' carry. An attention layer is
    ``generate.attend_in_place``, reading below the step's
    ``generate.kv_read_bound``. A mamba layer steps its rows' state where
    it lies (``ops/pallas/ssm_update.py``: read once, written once) and its
    tail at ``(layer, slot0)``: with the tree donated by the caller nothing
    state-sized is copied, and rows outside the launch keep theirs bit for
    bit. A position plays no part in a mamba layer."""
    b = tok.shape[0]
    layers = params["layers"]
    sin, cos = G._rope_table(cfg, cache["k"].shape[2])
    rows = slot0 + jnp.arange(b)
    # once a step, for the four attention layers
    bound = G.kv_read_bound(pos, cache["k"].shape[2])

    def rows_of(buf, i):  # [B, ...] of layer i, where they lie
        return jax.lax.dynamic_slice(
            buf, (i, slot0) + (0,) * (buf.ndim - 2),
            (1, b) + buf.shape[2:])[0]

    def put_rows(buf, i, new):
        return jax.lax.dynamic_update_slice(
            buf, new[None].astype(buf.dtype),
            (i, slot0) + (0,) * (buf.ndim - 2))

    def mamba(x, bufs, i):
        st, tl = bufs
        layer = _layer_of(layers["mamba"], i)
        z, xs, bm, cm, dt, a, tail = _mamba_in(cfg, x, layer, rows_of(tl, i))
        with jax.named_scope("ssm_conv"):
            tl = put_rows(tl, i, tail)
        with jax.named_scope("ssm_update"):
            y, st = ssm_update_in_place(st, i, slot0, xs[:, 0], dt[:, 0], a,
                                        bm[:, 0], cm[:, 0])
        return _mamba_out(cfg, x, y[:, None], xs, z, layer), (st, tl)

    def attention(x, bufs, i):
        x, ck, cv, _ = G.attend_in_place(
            cfg, x, _layer_of(layers["attention"], i), *bufs, i, slot0, rows,
            pos, sin, cos, bound)
        return x, (ck, cv)

    x, cache = _walk(cfg, G.embed(params, cfg, tok)[:, None, :], cache,
                     {"mamba": mamba, "attention": attention})
    with jax.named_scope("head_sample"):
        logits = G._head(params, cfg, x)[:, 0, :]
    return logits, cache
