"""A decoder-hybrid-decoder ("SambaY": Microsoft's Phi-4-mini-flash-reasoning,
``model_type`` ``phi4flash``): Mamba-1 layers beside window attention, then
ONE full-attention layer whose keys and values every later attention layer
reads, those later layers alternating with gated memory units that read the
last Mamba layer's output. Served only; it has no training path.

Per token, ``x`` the residual stream, ``LN`` a LayerNorm with weight and bias
(``eps`` = ``norm_eps``), no rotation anywhere::

    x = E[token]
    every layer:  x = x + Mixer(LN(x));  x = x + SwiGLU(LN(x))
    logits = LN(x) E^T

The mixer by the layer's kind (``layer_types``), ``u`` its normed input:

- ``mamba`` (S6): ``[xs | z] = u W_in``; ``xs = silu(conv(xs) + b)``, causal
  and depthwise over ``mamba_d_conv`` positions; ``[delta | B | C] = xs W_x``
  (widths ``mamba_dt_rank``, ``n``, ``n``); ``dt = softplus(delta W_dt +
  b_dt)``; ``A = -exp(A_log)``; the recurrence of ``ops/ssm.py`` (S6) on a
  state ``[n, d_inner]``; ``y = h C + D xs``; out ``= (y * silu(z)) W_out``.
  ``y`` (before the gate) is also the MEMORY the ``gmu`` layers read: the
  last mamba layer's, the same token's.
- ``window``: differential attention (``ops/attention.py``) over its own keys
  and values, query ``i`` sees key ``j`` iff ``0 <= i - j < sliding_window``.
- ``full``: differential attention, causal over everything. Its keys and
  values are the SHARED buffer.
- ``gmu``: out ``= (memory * silu(u W_1)) W_2``. No state of its own.
- ``cross``: differential attention with queries only; keys and values are
  the ``full`` layer's.

Differential attention pairs heads in stripes: pair ``j`` is queries ``2j``
and ``2j + 1`` against keys ``2g`` and ``2g + 1``, ``g = j // (hq / hkv)``,
both weighting the value pair ``[v[2g] | v[2g+1]]``; ``lam = exp(lq1 . lk1) -
exp(lq2 . lk2) + lam_init(l)``, ``lam_init(l) = 0.8 - 0.6 exp(-0.3 l)`` at
depth ``l``; ``(1 - lam_init) RMSNorm((A_1 - lam A_2) vv) * w``. A pair of
key (value) heads lies side by side as ONE head of ``2 head_dim`` = 128
lanes (``kv_layout``), so the whole of it is ``ops.attention.mha`` on
zero-padded queries and a combination after it.

What a slot keeps (``generate.init_cache``), by kind: ``k``/``v``
[1, slots, hkv/2, max_len, 2hd] the shared buffer, written by the one
``full`` layer and read by it and every ``cross`` layer below the step's
``generate.kv_read_bound``; ``wk``/``wv`` [L_window, slots, hkv/2,
sliding_window, 2hd] rings written at ``pos % sliding_window`` and read whole (no
rotation, so a softmax does not care where in the ring a position lies: the
mask is the plain ``slot <= pos``); ``ssm`` [L_mamba, slots, n, d_inner]
float32 and ``conv`` [L_mamba, slots, d_conv - 1, d_inner]. Parameters are
stacked by kind and the forward walks the order in SEGMENTS
(``hybrid._walk``): 8 x (mamba, window), (mamba, full), 7 x (gmu, cross).
The memory rides the walk as one more buffer, ``mem``, which every mamba
layer overwrites and the gmu layers read.

A prefill that wants the last token's logits alone (the engine's) runs the
layers up to the ``full`` one and that layer's keys and values over the whole
prompt, and everything after for the last token alone: nothing a later token
reads of this prompt lies behind the ``full`` layer's K and V.

Departures from the published code, all of precision and layout: activations
are rounded to the compute dtype (bf16) where they go INTO a matrix product
and float32 everywhere else: the residual stream, every product's sum, the
convolution, the gates (``_mm``; with activations bf16 throughout the logits'
noise was three times a dense model's of the same depth and a served token
lay outside the benchmark's limit on the chip: PERF.md, PR 34); the state, the decays and ``dt`` float32; the
softmaxes, the difference and the sub-norm float32; ``A_log`` is kept
``[n, d_inner]`` (published ``[d_inner, n]``), ``x_proj`` ``[out, in]``.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import math
from typing import Any, ClassVar, Dict, List, Mapping, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import generate as G
from ray_tpu.models import hybrid, llama
from ray_tpu.ops import ssm
from ray_tpu.ops.attention import diff_combine, mha, pad_diff_queries
from ray_tpu.ops.pallas.kv_write import kv_write_in_place
from ray_tpu.ops.pallas.s6_update import s6_update_in_place

Params = Dict[str, Any]
KINDS = ("mamba", "window", "full", "gmu", "cross")
F32 = jnp.float32


def _segments(types: Tuple[str, ...]) -> List[Tuple[Tuple[str, ...], int]]:
    """An order of kinds as ``hybrid._walk``'s segments: the layers two by
    two, like pairs that follow one another one segment, a last odd layer
    one of its own."""
    pairs = zip(types[::2], types[1::2])
    out = [(pair, len(list(run))) for pair, run in itertools.groupby(pairs)]
    return out + ([((types[-1],), 1)] if len(types) % 2 else [])


@dataclasses.dataclass(frozen=True)
class SambaYConfig(llama.LlamaConfig):
    # one of KINDS per layer, in the model's order; ``n_layers`` is its length
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 512
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    # the prefill's scan steps a token at a time (``serving.scan_chunks``)
    mamba_chunk_size: int = 1
    state_dtype: Any = jnp.float32

    BUFFERS: ClassVar[Mapping[str, Tuple[str, ...]]] = {
        "mamba": ("ssm", "conv", "mem"), "window": ("wk", "wv"),
        "full": ("k", "v"), "gmu": ("mem",), "cross": ("k", "v")}

    def __post_init__(self):
        hybrid.check_layer_types(self, KINDS)
        types = self.layer_types
        if (types.count("full") != 1 or types[0] != "mamba"
                or set(types[:self.full_layer]) - {"mamba", "window"}
                or set(types[self.full_layer + 1:]) - {"gmu", "cross"}):
            raise ValueError(
                "layer_types: mamba and window layers (a mamba layer first), "
                "then the one full layer, then gmu and cross layers, which "
                "read the last mamba layer's output and the full layer's "
                f"keys and values; got {types}")
        if self.n_kv_heads % 2 or self.n_heads % self.n_kv_heads or self.use_rope:
            raise ValueError(
                "differential heads pair the key/value heads two by two, "
                "share them among whole groups of query heads and rotate "
                f"nothing; got {self.n_heads} over {self.n_kv_heads}, "
                f"use_rope {self.use_rope}")

    @property
    def full_layer(self) -> int:
        return self.layer_types.index("full")

    @property
    def n_attention_layers(self) -> int:
        """Layers that keep keys and values at every position: the one
        whose buffer the cross layers share."""
        return self.layer_types.count("full")

    @property
    def n_window_layers(self) -> int:
        return self.layer_types.count("window")

    @property
    def kv_readers(self) -> int:
        """Layers that read the shared keys and values in a decode step."""
        return self.layer_types.count("full") + self.layer_types.count("cross")

    @property
    def n_recurrent_layers(self) -> int:
        return self.layer_types.count("mamba")

    #: a head's positions together: ``[pairs, length, 2 hd]`` a row. With
    #: positions before the heads a tile of the chip's memory would hold 10
    #: pair-heads of 16 (the compiler padded every buffer 1.6 x to compute
    #: on it, and copied it whole to do so: PERF.md, PR 34)
    kv_length_axis = 3

    @property
    def kv_layout(self) -> Tuple[int, int]:
        """Key/value heads in pairs, and a pair's width."""
        return self.n_kv_heads // 2, 2 * self.head_dim

    def kv_shape(self, length: int) -> Tuple[int, int, int]:
        pairs, wide = self.kv_layout
        return pairs, length, wide

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def depths(self, kind: str) -> Tuple[int, ...]:
        """Where in the model the layers of ``kind`` lie, 0-based."""
        return tuple(l for l, t in enumerate(self.layer_types) if t == kind)

    def segments(self) -> List[Tuple[Tuple[str, ...], int]]:
        return _segments(self.layer_types)

    def state_bytes_per_row(self) -> int:
        """A row's recurrent state and convolution tails, all layers."""
        state = (self.d_inner * self.mamba_d_state
                 * jnp.dtype(self.state_dtype).itemsize)
        tail = ((self.mamba_d_conv - 1) * self.d_inner
                * jnp.dtype(self.compute_dtype).itemsize)
        return self.n_recurrent_layers * (state + tail)

    def kv_bytes_per_position(self) -> int:
        """The shared buffer's keys and values of one position."""
        return (2 * self.n_attention_layers * self.n_kv_heads * self.head_dim
                * jnp.dtype(self.compute_dtype).itemsize)

    def window_bytes_per_row(self) -> int:
        """A row's rings, all window layers."""
        return (2 * self.n_window_layers * self.sliding_window
                * self.n_kv_heads * self.head_dim
                * jnp.dtype(self.compute_dtype).itemsize)

    def prefill_layer_tokens(self, s: int) -> Tuple[int, int]:
        """(layer-tokens a prefill of ``s`` tokens computes, what a pass of
        every layer over every token would): the layers behind the full
        layer's keys and values see the last token alone."""
        f, n = self.full_layer, self.n_layers
        return f * s + (n - f), n * s

    def num_params(self) -> int:
        d, f, di = self.d_model, self.d_ff, self.d_inner
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        mlp = 3 * d * f + 4 * d  # and the layer's two norms, weight and bias
        heads = 4 * self.head_dim + 2 * self.head_dim  # lambdas, sub-norm
        attention = d * q + q + 2 * (d * kv + kv) + q * d + d + heads
        cross = d * q + q + q * d + d + heads
        mamba = (d * 2 * di + (self.mamba_d_conv + 1) * di
                 + di * (self.mamba_dt_rank + 2 * self.mamba_d_state)
                 + self.mamba_dt_rank * di + di + self.mamba_d_state * di
                 + di + di * d)
        count = collections.Counter(self.layer_types)
        head = 0 if self.tie_embeddings else d * self.vocab_size
        return (self.vocab_size * d + 2 * d + head + self.n_layers * mlp
                + count["mamba"] * mamba + count["gmu"] * 2 * d * di
                + (count["window"] + count["full"]) * attention
                + count["cross"] * cross)


def layer_types_for(n_layers: int) -> Tuple[str, ...]:
    """The published rule (``phi4flash``, ``mb_per_layer`` 2) for a depth
    whose half is even: even layers are mamba in the first half and at its
    end, gated memory units after; odd layers are window attention in the
    first half, the full layer at ``n / 2 + 1``, cross attention after."""
    half = n_layers // 2
    if n_layers % 4:
        raise ValueError(f"n_layers {n_layers}: the rule needs an even half")
    return tuple(
        ("mamba" if l <= half else "gmu") if l % 2 == 0 else
        ("window" if l < half else "full" if l == half + 1 else "cross")
        for l in range(n_layers))


PRESETS: Dict[str, SambaYConfig] = {
    "sambay-debug": SambaYConfig(
        vocab_size=256, d_model=64, n_layers=8, n_heads=8, n_kv_heads=4,
        d_ff=128, max_seq_len=128, tie_embeddings=True, use_rope=False,
        attn_scale=0.25, layer_types=layer_types_for(8), sliding_window=8,
        mamba_d_state=4, mamba_dt_rank=4),
}


def init_params(rng: jax.Array, cfg: SambaYConfig) -> Params:
    """Matrices normal at 1/sqrt(fan-in), their biases zero, LayerNorms one
    and zero, the sub-norm ones, the lambda vectors normal at 0.1 (the
    differential transformer's), and the Mamba parameters as ``mamba_ssm``
    makes them: ``A_log = log(1..n)`` for every channel, ``dt``'s bias the
    inverse softplus of a log-uniform draw in [1e-3, 1e-1], its projection
    uniform in +-1/sqrt(dt_rank), ``D`` ones, the convolution and its bias
    uniform in +-1/sqrt(d_conv). Stacked by layer kind."""
    d, f, pdt = cfg.d_model, cfg.d_ff, cfg.param_dtype
    q, kv, hd = (cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim,
                 cfg.head_dim)
    di, n, r, k = (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                   cfg.mamba_d_conv)
    count = collections.Counter(cfg.layer_types)
    keys = iter(jax.random.split(rng, 64))

    def normal(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(keys), shape, F32) * scale
                / math.sqrt(fan_in)).astype(pdt)

    def uniform(shape, bound):
        return jax.random.uniform(next(keys), shape, F32, -bound,
                                  bound).astype(pdt)

    def norm(name, l):
        return {name: jnp.ones((l, d), pdt), name + "_b": jnp.zeros((l, d), pdt)}

    def mlp(l):
        return {**norm("mlp_norm", l),
                "w_gate": normal((l, d, f), d), "w_up": normal((l, d, f), d),
                "w_down": normal((l, f, d), f)}

    def attention(l, own_kv: bool):
        out = {**norm("attn_norm", l),
               # the three projections [out, in], as the checkpoint has
               # them: stored [in, out] the chip's compiler transposed each
               # kind's stack whole, every launch (PERF.md, PR 34)
               "wq": normal((l, q, d), d), "bq": jnp.zeros((l, q), pdt),
               "wo": normal((l, q, d), q), "bo": jnp.zeros((l, d), pdt),
               # lq1, lk1, lq2, lk2
               "lambdas": normal((l, 4, hd), 1.0, 0.1).astype(F32),
               "subln": jnp.ones((l, 2 * hd), pdt), **mlp(l)}
        if own_kv:
            out.update(wk=normal((l, kv, d), d), bk=jnp.zeros((l, kv), pdt),
                       wv=normal((l, kv, d), d), bv=jnp.zeros((l, kv), pdt))
        return out

    lm = count["mamba"]
    dt = jnp.exp(jax.random.uniform(next(keys), (lm, di), F32,
                                    math.log(1e-3), math.log(1e-1)))
    return {
        "embed": normal((cfg.vocab_size, d), d),
        "final_norm": jnp.ones((d,), pdt), "final_norm_b": jnp.zeros((d,), pdt),
        "layers": {
            "mamba": {
                **norm("ssm_norm", lm),
                "in_proj": normal((lm, d, 2 * di), d),
                "conv_w": uniform((lm, k, di), 1 / math.sqrt(k)),
                "conv_b": uniform((lm, di), 1 / math.sqrt(k)),
                # [out, in]: 192 outputs are no multiple of the chip's 128
                # lanes (``hybrid.init_params`` says what that costs)
                "x_proj": normal((lm, r + 2 * n, di), di),
                "dt_proj": uniform((lm, r, di), 1 / math.sqrt(r)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=F32))[:, None],
                    (lm, n, di)),
                "D": jnp.ones((lm, di), F32),
                "out_proj": normal((lm, di, d), di), **mlp(lm)},
            "window": attention(count["window"], True),
            "full": attention(count["full"], True),
            "gmu": {**norm("gmu_norm", count["gmu"]),
                    "w1": normal((count["gmu"], d, di), d),
                    "w2": normal((count["gmu"], di, d), di),
                    **mlp(count["gmu"])},
            "cross": attention(count["cross"], False),
        },
    }


def init_state(cfg: SambaYConfig, batch: int) -> Dict[str, jax.Array]:
    """The mamba layers' zeroed part of ``generate.init_cache``'s tree."""
    lm = cfg.n_recurrent_layers
    return {"ssm": jnp.zeros((lm, batch, cfg.mamba_d_state, cfg.d_inner),
                             cfg.state_dtype),
            "conv": jnp.zeros((lm, batch, cfg.mamba_d_conv - 1, cfg.d_inner),
                              cfg.compute_dtype)}


# ---- the mixers' pieces, shared by the prefill and the decode step -------------

def _mm(cfg: SambaYConfig, a, w, how: str = "...d,dn->...n"):
    """A product with a weight: what goes in is rounded to the compute
    dtype, what comes out is the float32 it was summed in (the residual
    stream and every elementwise step between two products are float32:
    on a chip whose decode step waits for the weights that costs nothing,
    and it is half the logits' bf16 noise; PERF.md, PR 34)."""
    cdt = cfg.compute_dtype
    return jnp.einsum(how, a.astype(cdt), w.astype(cdt),
                      preferred_element_type=F32)


def _mamba_in(cfg: SambaYConfig, x, layer: Params, tail):
    """Everything before the recurrence for ``x`` [B, S, d] after the
    ``tail`` [B, d_conv - 1, d_inner] of inputs before it. Returns (z, xs
    [B, S, d_inner] float32, dt [B, S, d_inner] float32, B, C [B, S, n], A
    [n, d_inner], the new tail)."""
    cdt, di, r, n = (cfg.compute_dtype, cfg.d_inner, cfg.mamba_dt_rank,
                     cfg.mamba_d_state)
    with jax.named_scope("ssm_proj"):
        u = llama.pre_norm(cfg, x, layer, "ssm_norm")
        xs, z = jnp.split(_mm(cfg, u, layer["in_proj"]), [di], axis=-1)
    with jax.named_scope("ssm_conv"):
        # the convolution's inputs are rounded (the tail keeps them in the
        # compute dtype), its sum and everything after is float32
        xs, new_tail = ssm.causal_conv(
            xs.astype(cdt).astype(F32), tail.astype(F32),
            layer["conv_w"].astype(F32), layer["conv_b"].astype(F32))
        xs, tail = jax.nn.silu(xs), new_tail.astype(tail.dtype)
    with jax.named_scope("ssm_proj"):
        dbc = _mm(cfg, xs, layer["x_proj"], "bsc,nc->bsn")
        delta, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
        dt = jax.nn.softplus(_mm(cfg, delta, layer["dt_proj"])
                             + layer["dt_bias"])
        a = -jnp.exp(layer["A_log"].astype(F32))
    return z, xs, dt, bm, cm, a, tail


def _mamba_out(cfg: SambaYConfig, x, y, xs, z, layer: Params):
    """The skip term, the gate, the output projection and the residual, then
    the SwiGLU half. Returns (hidden, the memory: ``y`` before the gate)."""
    cdt = cfg.compute_dtype
    with jax.named_scope("ssm_proj"):
        mem = y + xs * layer["D"]                        # float32
        x = x + _mm(cfg, mem * jax.nn.silu(z), layer["out_proj"])
    with jax.named_scope("mlp"):
        return llama.join(x, llama.ffn_half(cfg, x, layer)), mem


def _queries(cfg: SambaYConfig, u, layer: Params):
    """The padded queries [B, S, hq, 2 hd] of a normed input."""
    b, s, _ = u.shape
    q = _mm(cfg, u, layer["wq"], "bsd,nd->bsn") + layer["bq"].astype(F32)
    return pad_diff_queries(q.astype(cfg.compute_dtype).reshape(
        b, s, cfg.n_heads, cfg.head_dim))


def _keys_values(cfg: SambaYConfig, u, layer: Params):
    """K and V [B, S, hkv / 2, 2 hd] of a normed input: the heads two by
    two, side by side, which is how they come out of the projection."""
    b, s, _ = u.shape
    return tuple((_mm(cfg, u, layer["w" + n], "bsd,nd->bsn")
                  + layer["b" + n].astype(F32)).astype(cfg.compute_dtype
                                                       ).reshape(
                      b, s, *cfg.kv_layout) for n in "kv")


def _after_attention(cfg: SambaYConfig, x, out, layer: Params, depth):
    """The differential combination of ``mha``'s result, the output
    projection and the residual; ``depth`` is where the layer lies."""
    cdt = cfg.compute_dtype
    lv = layer["lambdas"].astype(F32)
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = (jnp.exp(jnp.sum(lv[0] * lv[1])) - jnp.exp(jnp.sum(lv[2] * lv[3]))
           + lam_init)
    y = diff_combine(out, lam, lam_init, layer["subln"], cfg.norm_eps)
    return x + (_mm(cfg, y, layer["wo"]) + layer["bo"].astype(F32))


def _attend(cfg: SambaYConfig, q, k, v, **how):
    return mha(q, k, v, causal=True, scale=cfg.attn_scale, **how)


def _heads_major(kv: jax.Array) -> jax.Array:
    """[B, S, pairs, wide] as the cache keeps it, [B, pairs, S, wide]."""
    return kv.swapaxes(1, 2)


def _gmu_block(cfg: SambaYConfig, x, mem, layer: Params):
    cdt = cfg.compute_dtype
    with jax.named_scope("gmu"):
        u = llama.pre_norm(cfg, x, layer, "gmu_norm")
        x = x + _mm(cfg, mem * jax.nn.silu(_mm(cfg, u, layer["w1"])),
                    layer["w2"])
    with jax.named_scope("mlp"):
        return llama.join(x, llama.ffn_half(cfg, x, layer))


def _depths(cfg: SambaYConfig) -> Dict[str, jax.Array]:
    return {kind: jnp.asarray(cfg.depths(kind), F32)
            for kind in ("window", "full", "cross")}


def _ring_of(kv: jax.Array, window: int) -> jax.Array:
    """What a ring [B, window, ...] holds after positions ``0 .. s - 1`` of
    ``kv`` [B, s, ...]: position ``p`` at ``p % window``, the last
    ``window`` of them."""
    s = kv.shape[1]
    if s <= window:
        return jnp.pad(kv, [(0, 0), (0, window - s)] + [(0, 0)] * (kv.ndim - 2))
    return jnp.roll(kv[:, s - window:], (s - window) % window, axis=1)


# ---- tokens at one position for all rows: prefill, ``generate`` ---------------

def forward_with_cache(params: Params, tokens: jax.Array, cfg: SambaYConfig,
                       cache: Dict, pos, last_only: bool = True
                       ) -> Tuple[jax.Array, Dict]:
    """``generate._forward_with_cache`` for this model: tokens [B, S] at
    absolute position ``pos`` after what ``cache`` holds -> (logits, the
    cache after token ``pos + S - 1``). A prefill (``pos`` the integer 0, on
    a zeroed cache) of any length, or one token at any position; a prefill
    that wants the last token's logits alone computes the layers behind the
    full layer's keys and values for that token alone."""
    b, s = tokens.shape
    prefill = isinstance(pos, int) and pos == 0
    if not prefill and s != 1:
        raise NotImplementedError(
            f"{s} tokens at position {pos}: a window layer's ring takes a "
            f"prompt from position 0, or one token at a time")
    w, layers = cfg.sliding_window, params["layers"]
    depth = _depths(cfg)
    of = hybrid._layer_of

    def mamba(x, bufs, i):
        st, tl, _ = bufs
        layer = of(layers["mamba"], i)
        z, xs, dt, bm, cm, a, tail = _mamba_in(cfg, x, layer, tl[i])
        with jax.named_scope("ssm_scan"):
            y, state = ssm.s6_scan(xs, dt, a, bm, cm, st[i])
        x, mem = _mamba_out(cfg, x, y, xs, z, layer)
        return x, (st.at[i].set(state.astype(st.dtype)), tl.at[i].set(tail),
                   mem)

    def window(x, bufs, i):
        wk, wv = bufs
        layer = of(layers["window"], i)
        with jax.named_scope("attn_window"):
            u = llama.pre_norm(cfg, x, layer, "attn_norm")
            k, v = _keys_values(cfg, u, layer)
            if prefill:  # over the prompt itself; the rings take its end
                out = _attend(cfg, _queries(cfg, u, layer), k, v, window=w)
                ring_k = _heads_major(_ring_of(k, w))
                ring_v = _heads_major(_ring_of(v, w))
            else:
                ring_k = jax.lax.dynamic_update_slice(
                    wk[i], _heads_major(k), (0, 0, pos % w, 0))
                ring_v = jax.lax.dynamic_update_slice(
                    wv[i], _heads_major(v), (0, 0, pos % w, 0))
                out = _attend(cfg, _queries(cfg, u, layer), ring_k, ring_v,
                              q_offset=pos, kv_heads_major=True)
            x = _after_attention(cfg, x, out, layer, depth["window"][i])
        with jax.named_scope("mlp"):
            x = llama.join(x, llama.ffn_half(cfg, x, layer))
        return x, (wk.at[i].set(ring_k), wv.at[i].set(ring_v))

    def shared(x, bufs, i, kind, last: bool):
        """A ``full`` layer (writes the shared buffer, then reads it) or a
        ``cross`` layer (reads it); ``last``: ``x`` is the last of the
        ``s`` tokens alone (a full layer then takes all of them and hands
        the last one on)."""
        ck, cv = bufs
        layer = of(layers[kind], i)
        with jax.named_scope("attn_" + kind):
            u = llama.pre_norm(cfg, x, layer, "attn_norm")
            if kind == "full":
                k, v = _keys_values(cfg, u, layer)
                ck = ck.at[i].set(jax.lax.dynamic_update_slice(
                    ck[i], _heads_major(k), (0, 0, pos, 0)))
                cv = cv.at[i].set(jax.lax.dynamic_update_slice(
                    cv[i], _heads_major(v), (0, 0, pos, 0)))
                if last:
                    x, u = x[:, -1:], u[:, -1:]
            # a prefill reads the prompt's own positions, one token all
            seen = slice(0, s) if prefill else slice(None)
            first = pos + s - 1 if last else pos
            kl = 0 if kind == "cross" else i  # the one buffer there is
            out = _attend(cfg, _queries(cfg, u, layer), ck[kl][:, :, seen],
                          cv[kl][:, :, seen], q_offset=first,
                          kv_heads_major=True)
            x = _after_attention(cfg, x, out, layer, depth[kind][i])
        with jax.named_scope("mlp"):
            x = llama.join(x, llama.ffn_half(cfg, x, layer))
        return x, (ck, cv)

    def gmu(x, bufs, i):
        return _gmu_block(cfg, x, bufs[0], of(layers["gmu"], i)), bufs

    def blocks(last: bool):
        return {"mamba": mamba, "window": window, "gmu": gmu,
                "full": lambda x, bufs, i: shared(x, bufs, i, "full", last),
                "cross": lambda x, bufs, i: shared(x, bufs, i, "cross", last)}

    x = G.embed(params, cfg, tokens).astype(F32)
    cache = {**cache, "mem": jnp.zeros((b, s, cfg.d_inner), F32)}
    if last_only and s > 1:
        types, f = cfg.layer_types, cfg.full_layer
        x, cache = hybrid._walk(cfg, x, cache, blocks(False),
                                _segments(types[:f]))
        x, kv = blocks(True)["full"](x, (cache["k"], cache["v"]), 0)
        cache = {**cache, "k": kv[0], "v": kv[1], "mem": cache["mem"][:, -1:]}
        x, cache = hybrid._walk(cfg, x, cache, blocks(True),
                                _segments(types[f + 1:]),
                                collections.Counter(types[:f + 1]))
    else:
        x, cache = hybrid._walk(cfg, x, cache, blocks(False))
    del cache["mem"]
    with jax.named_scope("head_sample"):
        logits = G._head(params, cfg, x[:, -1:, :] if last_only else x)
    return logits, cache


# ---- one token a row at its own position, in place on the slot tree -----------

def decode_step_in_place(params: Params, tok: jax.Array, cfg: SambaYConfig,
                         cache: Dict, slot0, pos: jax.Array
                         ) -> Tuple[jax.Array, Dict]:
    """``hybrid.decode_step_in_place`` for this model: one decode step for
    the ``B`` rows ``slot0 .. slot0 + B`` of a slot cache. The whole tree
    rides the segments' loops' carry. A window layer writes its rows' new K
    and V at ``(layer, row, pos % sliding_window)`` and reads its rows'
    rings whole, where they lie, whatever ``max_len`` is; the full layer is
    ``generate.attend_in_place``'s write and bounded read on the shared
    buffer; a cross layer is that read alone and writes nothing; a mamba
    layer steps its rows' state where it lies
    (``ops/pallas/s6_update.py``) and hands its output on as the memory.
    The new K and V go in through ``ops/pallas/kv_write.py`` (a position
    past ``max_len`` writes nothing). With the tree donated nothing cache-
    or state-sized is copied, and rows outside the launch keep everything
    bit for bit."""
    b = tok.shape[0]
    w, layers = cfg.sliding_window, params["layers"]
    # once a step, for the full layer and every cross layer
    bound = G.kv_read_bound(pos, cache["k"].shape[3])
    held = G.held_as_it_lies(cache["k"].shape[3])
    depth = _depths(cfg)
    of = hybrid._layer_of

    def rows_of(buf, i):  # [B, ...] of layer i, where they lie
        return jax.lax.dynamic_slice(
            buf, (i, slot0) + (0,) * (buf.ndim - 2),
            (1, b) + buf.shape[2:])[0]

    def mamba(x, bufs, i):
        st, tl, _ = bufs
        layer = of(layers["mamba"], i)
        z, xs, dt, bm, cm, a, tail = _mamba_in(cfg, x, layer, rows_of(tl, i))
        with jax.named_scope("ssm_conv"):
            tl = jax.lax.dynamic_update_slice(
                tl, tail[None].astype(tl.dtype), (i, slot0, 0, 0))
        with jax.named_scope("ssm_update"):
            y, st = s6_update_in_place(st, i, slot0, xs[:, 0], dt[:, 0], a,
                                       bm[:, 0], cm[:, 0])
        x, mem = _mamba_out(cfg, x, y[:, None], xs, z, layer)
        return x, (st, tl, mem)

    def window(x, bufs, i):
        wk, wv = bufs
        layer = of(layers["window"], i)
        with jax.named_scope("attn_window"):
            u = llama.pre_norm(cfg, x, layer, "attn_norm")
            k, v = _keys_values(cfg, u, layer)
            wk, wv = kv_write_in_place((wk, wv), i, slot0, pos % w,
                                       (k[:, 0], v[:, 0]))
            out = _attend(cfg, _queries(cfg, u, layer), rows_of(wk, i),
                          rows_of(wv, i), q_offset=pos, kv_heads_major=True)
            x = _after_attention(cfg, x, out, layer, depth["window"][i])
        with jax.named_scope("mlp"):
            x = llama.join(x, llama.ffn_half(cfg, x, layer))
        return x, (wk, wv)

    def shared(x, bufs, i, kind):
        ck, cv = bufs
        layer = of(layers[kind], i)
        with jax.named_scope("attn_" + kind):
            u = llama.pre_norm(cfg, x, layer, "attn_norm")
            if kind == "full":
                k, v = _keys_values(cfg, u, layer)
                ck, cv = kv_write_in_place((ck, cv), i, slot0, pos,
                                           (k[:, 0], v[:, 0]))
            out = G.read_below(
                _queries(cfg, u, layer), ck, cv, 0 if kind == "cross" else i,
                slot0, pos, bound, axis=3, scale=cfg.attn_scale,
                kv_heads_major=True)
            # (``attend_in_place`` says why the projection is held too)
            x = _after_attention(cfg, x, out, {**layer, "wo": held(layer["wo"])},
                                 depth[kind][i])
        with jax.named_scope("mlp"):
            x = llama.join(x, llama.ffn_half(cfg, x, layer))
        return x, (ck, cv)

    def gmu(x, bufs, i):
        return _gmu_block(cfg, x, bufs[0], of(layers["gmu"], i)), bufs

    x = G.embed(params, cfg, tok)[:, None, :].astype(F32)
    cache = {**cache, "mem": jnp.zeros((b, 1, cfg.d_inner), F32)}
    x, cache = hybrid._walk(cfg, x, cache, {
        "mamba": mamba, "window": window, "gmu": gmu,
        "full": lambda x, bufs, i: shared(x, bufs, i, "full"),
        "cross": lambda x, bufs, i: shared(x, bufs, i, "cross")})
    del cache["mem"]
    with jax.named_scope("head_sample"):
        logits = G._head(params, cfg, x)[:, 0, :]
    return logits, cache
