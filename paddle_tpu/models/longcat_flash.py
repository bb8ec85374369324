"""LongCat-Flash's language model (LongCat-Flash-Omni 560B-A27B,
https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json;
the LongCat-Flash technical report, Meituan LongCat team, 2025).

Each of ``num_layers`` is a DOUBLE layer. With ``x`` its input:

    a0  = x  + MLA_0(RMSNorm(x))
    h0  = RMSNorm(a0)
    m   = MoE(h0)                 # the shortcut: from the first half's stream
    d0  = a0 + FFN_0(h0)          # dense SwiGLU
    a1  = d0 + MLA_1(RMSNorm(d0))
    h1  = RMSNorm(a1)
    out = a1 + FFN_1(h1) + m

**The MoE.** ``s = softmax(h W_r)`` in float32 over ``n_routed_experts +
zero_expert_num`` outputs; the ``moe_topk`` largest of ``s + b`` are chosen
(``b``: ``e_score_correction_bias``, in the choice only); ``g_j =
routed_scaling_factor * s_j``, not renormalised. A routed expert ``j <
n_routed_experts`` is a SwiGLU of ``expert_ffn_hidden_size``; a zero-compute
expert is the identity, ``E_j(h) = h``: no weights and no compute. A layer
holds the routed experts ``expert_offset .. + num_local_experts`` and
computes their part of the result (``sdar_moe.expert_ffn``, where a
zero-compute pick is one label past the routed experts, which no layer
holds, so it never reaches a grouped product) and the zero experts' part,
``sum g_j h``, which every holder computes for its own tokens.

**The MLA.** ``q = RMSNorm(x W_qa) W_qb * sqrt(H / q_lora_rank)``, per head
``[q_nope | q_rope]``; ``[c_raw | kr_raw] = x W_kva``; ``c = RMSNorm(c_raw) *
sqrt(H / kv_lora_rank)``; ``[k_nope | v] = c W_kvb`` per head; ``k_rope =
RoPE(kr_raw)``, one key for all heads; ``score = (q_nope . k_nope +
RoPE(q_rope) . k_rope) / sqrt(qk_nope + qk_rope)``; ``out = (softmax . v)
W_o``. A prefill (more than one position) computes that EXPANDED form over
its own positions, a query chunk at a time against the keys it may see
(``_QUERY_CHUNK``); a prefill starts its sequence, as every session's admit
does, and one padded to its bucket runs at about its prompt's length
(``prefill_arrays``). A one-token step
computes the ABSORBED form over the cache: ``q~ = q_nope W_UK^T`` (the
``k_nope`` columns of ``W_kvb``), ``score = q~ . c + q_rope . k_rope``,
``out = (sum p c) W_UV``; its products take their operands in the cache's
dtype and accumulate in float32, and ``W_UK`` / ``W_UV`` are slices of
``W_kvb`` taken in the call. On a TPU the score, mask, softmax and ``p . c``
are one Pallas program that reads each sequence's valid rows of the cache
and keeps the scores on chip (``decode_attention.latent_decode_attention``;
``attn.latent_dispatch`` counts the form a traced step took). The cache
holds ``c`` and the rotated ``k_rope`` of each position
(``decode.LatentCache``).

The model is pure functions over a dict of its parameters
(``forward_arrays``, ``double_layer``, ``mla``, ``moe``); the ``nn.Layer``s
hold the parameters and run it as one op. Serving
contract (``ContinuousBatchingSession``): ``init_cache``,
``forward_with_cache`` and, for the expert load, ``EXPERT_LOAD_LEN``,
``forward_with_expert_load``, ``count_expert_load``. Inference only.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import run_op
from paddle_tpu.models.llama import rope
from paddle_tpu.models.sdar_moe import _rms_norm, expert_ffn
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability import metrics as _met

#: queries a prefill's expanded attention takes at a time, against the
#: keys before their last: [heads, 256, <= positions] float32 scores, at
#: most 256 MiB at 64 heads and 4,096 positions
_QUERY_CHUNK = 256
#: a prefill padded to s positions runs at a multiple of this many
#: (``prefill_lengths``)
_PREFILL_STEP = 512


@dataclasses.dataclass
class LongCatFlashConfig:
    """The keys of the model's public ``config.json`` (the catalog's
    names; ``num_layers`` counts double layers), and what a holder of a
    share of it needs besides. ``__post_init__`` refuses the values this
    implementation does not compute."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_rope_head_dim: int = 64
    qk_nope_head_dim: int = 128
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000000.0
    attention_method: str = "MLA"
    attention_bias: bool = False
    # the routed experts held here (None: all of them)
    num_local_experts: int = None
    expert_offset: int = 0
    param_dtype: str = "float32"
    cache_dtype: str = "bfloat16"
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.n_routed_experts - self.expert_offset
        unsupported = {
            "attention_bias": self.attention_bias,
            "attention_method": self.attention_method != "MLA",
            "mla_scale_q_lora": not self.mla_scale_q_lora,
            "mla_scale_kv_lora": not self.mla_scale_kv_lora,
            "zero_expert_type": self.zero_expert_type != "identity"}
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"LongCatFlashConfig: not supported here: {bad}")
        if not 0 <= self.expert_offset <= self.expert_offset \
                + self.num_local_experts <= self.n_routed_experts:
            raise ValueError("experts held must lie within n_routed_experts")

    num_heads = property(lambda self: self.num_attention_heads)

    @staticmethod
    def tiny(**kw):
        """Widths whose ratios are the published ones' kind: the latent
        wider than a head, the rope part narrower."""
        base = dict(vocab_size=96, hidden_size=32, ffn_hidden_size=48,
                    expert_ffn_hidden_size=16, num_layers=2,
                    num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
                    qk_rope_head_dim=4, qk_nope_head_dim=8, v_head_dim=8,
                    n_routed_experts=8, zero_expert_num=4, moe_topk=3,
                    max_position_embeddings=256, rope_theta=1e4)
        base.update(kw)
        return LongCatFlashConfig(**base)


# ------------------------------------------------------------------ MLA

def _count_attention(kernel):
    """Ticked where an MLA sublayer is traced, by its form."""
    if _met._ENABLED:
        _met.REGISTRY.counter("attn.dispatch", kernel=kernel).inc()


def _mla_inputs(cfg, p, u, lens):
    """u [B, S, H] normed -> q_nope [B, S, nh, dn], q_rope [B, S, nh, dr]
    rotated, c [B, S, kv_lora_rank], k_rope [B, S, dr] rotated."""
    b, s, hid = u.shape
    nh, dn, dr = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps = cfg.rms_norm_eps
    q = _rms_norm(jnp.dot(u, p["q_a_proj"]), p["q_a_layernorm"], eps)
    q = jnp.dot(q, p["q_b_proj"], preferred_element_type=jnp.float32) \
        * math.sqrt(hid / cfg.q_lora_rank)
    q = q.astype(u.dtype).reshape(b, s, nh, dn + dr)
    kv = jnp.dot(u, p["kv_a_proj"], preferred_element_type=jnp.float32)
    c = _rms_norm(kv[..., :cfg.kv_lora_rank], p["kv_a_layernorm"], eps) \
        * math.sqrt(hid / cfg.kv_lora_rank)
    k_rope = rope(kv[..., None, cfg.kv_lora_rank:].astype(u.dtype), lens,
                  cfg.rope_theta, half_split=True)[:, :, 0]
    q_rope = rope(q[..., dn:], lens, cfg.rope_theta, half_split=True)
    return q[..., :dn], q_rope, c.astype(u.dtype), k_rope


@jax.named_scope("mla_expanded")
def _expanded(cfg, p, q_nope, q_rope, c, k_rope):
    """Causal attention of s positions among themselves, keys and values
    expanded from their latents: -> [B, s, nh, dv]."""
    b, s, nh, dn = q_nope.shape
    kv = jnp.dot(c, p["kv_b_proj"]).reshape(b, s, nh, dn + cfg.v_head_dim)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = 1.0 / math.sqrt(dn + cfg.qk_rope_head_dim)

    def attend(first, stop):
        # the queries [first, stop) against the keys they may see, [0, stop)
        score = (jnp.einsum("bqhd,bkhd->bhqk", q_nope[:, first:stop],
                            k_nope[:, :stop],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhr,bkr->bhqk", q_rope[:, first:stop],
                              k_rope[:, :stop],
                              preferred_element_type=jnp.float32)) * scale
        row = first + jnp.arange(stop - first)
        score = jnp.where(jnp.arange(stop)[None, :] <= row[:, None], score,
                          -1e30)
        prob = jax.nn.softmax(score, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", prob, v[:, :stop],
                          preferred_element_type=jnp.float32).astype(v.dtype)

    return jnp.concatenate(
        [attend(first, min(first + _QUERY_CHUNK, s))
         for first in range(0, s, _QUERY_CHUNK)], axis=1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
@jax.named_scope("latent_attention")    # a profile names the kernel after it
def _latent_kernel(q_lat, q_rope, cbuf, krbuf, lens, scale, interpret):
    """The kernel as a program of its own, traced once a shape and not
    once a sublayer (as ``decode._decode_kernel``)."""
    from paddle_tpu.ops.pallas.decode_attention import latent_decode_attention
    return latent_decode_attention(q_lat, q_rope, cbuf, krbuf, lens,
                                   scale=scale, interpret=interpret)


def _attend_latent_kernel(q_lat, q_rope, cbuf, krbuf, valid, scale):
    """The absorbed step's attention on a TPU: the length-aware kernel,
    which reads each slot's valid blocks of the latent cache and keeps the
    scores on chip. None on every other backend, or where its gate refuses
    the shape (counted, as ``decode._attend_decode_kernel`` counts its
    own); ``attn.latent_dispatch`` says which form took the call."""
    from paddle_tpu.inference import decode
    take = decode._kernel_backend()
    if take:
        from paddle_tpu.ops.pallas.decode_attention import latent_gate_reason
        reason = latent_gate_reason(q_lat.shape, cbuf.shape, krbuf.shape[2],
                                    cbuf.dtype)
        take = reason is None
        if not take and _met._ENABLED:
            _met.REGISTRY.counter("attn.dispatch_fallback",
                                  reason=reason).inc()
    if _met._ENABLED:
        _met.REGISTRY.counter("attn.latent_dispatch",
                              kernel="ragged" if take else "einsum").inc()
    if not take:
        return None
    return _latent_kernel(q_lat, q_rope[:, 0].astype(cbuf.dtype), cbuf,
                          krbuf, valid - 1, scale,
                          interpret=jax.default_backend() != "tpu")


@jax.named_scope("mla_absorbed")
def _absorbed(cfg, p, q_nope, q_rope, cbuf, krbuf, valid):
    """One position a sequence against its cached latents: q_nope [B, 1,
    nh, dn], q_rope [B, 1, nh, dr]; cbuf [B, C, R], krbuf [B, C, dr];
    ``valid`` [B] rows that count (the new one included) -> [B, 1, nh,
    dv]."""
    nh, dn = cfg.num_heads, cfg.qk_nope_head_dim
    w = p["kv_b_proj"].reshape(cfg.kv_lora_rank, nh, dn + cfg.v_head_dim)
    w_uk, w_uv = w[..., :dn], w[..., dn:]
    dt = cbuf.dtype
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk,
                       preferred_element_type=jnp.float32).astype(dt)
    o_lat = _attend_latent_kernel(q_lat, q_rope, cbuf, krbuf, valid,
                                  1.0 / math.sqrt(dn + cfg.qk_rope_head_dim))
    if o_lat is None:
        score = jnp.einsum("bhc,btc->bht", q_lat, cbuf,
                           preferred_element_type=jnp.float32) \
            + jnp.einsum("bhr,btr->bht", q_rope[:, 0].astype(dt), krbuf,
                         preferred_element_type=jnp.float32)
        score = score / math.sqrt(dn + cfg.qk_rope_head_dim)
        col = jnp.arange(cbuf.shape[1])
        score = jnp.where(col[None, None, :] < valid[:, None, None], score,
                          -1e30)
        prob = jax.nn.softmax(score, axis=-1).astype(dt)
        o_lat = jnp.einsum("bht,btc->bhc", prob, cbuf,
                           preferred_element_type=jnp.float32)
    out = jnp.einsum("bhc,chd->bhd", o_lat.astype(w.dtype), w_uv,
                     preferred_element_type=jnp.float32)
    return out.astype(q_nope.dtype)[:, None]


def mla(cfg, p, u, cache=None):
    """One MLA sublayer on raw arrays: u [B, s, H] normed; ``p`` its
    parameters by their names without the sublayer's suffix; ``cache``
    None (the s positions are the whole sequence, from 0) or (cbuf, krbuf,
    lens). Returns (out [B, s, H], cache')."""
    b, s, _ = u.shape
    lens = jnp.zeros((b,), jnp.int32) if cache is None else cache[2]
    q_nope, q_rope, c, k_rope = _mla_inputs(cfg, p, u, lens)
    if cache is not None:
        from paddle_tpu.inference.decode import latent_write
        cbuf, krbuf = latent_write(cache[0], cache[1], c, k_rope, lens)
        cache = (cbuf, krbuf, lens + jnp.int32(s))
    if s == 1 and cache is not None:
        _count_attention("latent_absorbed")
        out = _absorbed(cfg, p, q_nope, q_rope, cbuf, krbuf, cache[2])
    else:
        _count_attention("mla_expanded")
        out = _expanded(cfg, p, q_nope, q_rope, c, k_rope)
    return jnp.dot(out.reshape(b, s, -1), p["o_proj"]), cache


# ------------------------------------------------------------ the FFNs

@jax.named_scope("dense_mlp")
def dense_mlp(u, w_gate_up, w_down):
    """SwiGLU, ``w_gate_up`` [H, 2F] (gate columns first)."""
    f = w_down.shape[0]
    gate_up = jnp.dot(u, w_gate_up)
    return jnp.dot(jax.nn.silu(gate_up[..., :f]) * gate_up[..., f:], w_down)


@jax.named_scope("moe_router")
def route(h, w_router, bias, top_k, scale):
    """h [T, H] -> (weights [T, k] float32, index [T, k] int32 over routed
    and zero-compute experts): the softmax in float32, the k largest of
    score + bias, the chosen SCORES times ``scale``."""
    logits = jnp.dot(h, w_router, preferred_element_type=jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    _, index = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    return jnp.take_along_axis(scores, index, -1) * scale, \
        index.astype(jnp.int32)


def moe(cfg, p, h):
    """The held experts' and the zero experts' part of the MoE: h [T, H]
    -> ([T, H], index [T, k])."""
    weights, index = route(h, p["router"], p["router_bias"], cfg.moe_topk,
                           cfg.routed_scaling_factor)
    routed = index < cfg.n_routed_experts
    with jax.named_scope("zero_experts"):
        zero = jnp.sum(jnp.where(routed, 0.0, weights), -1, keepdims=True) \
            * h.astype(jnp.float32)
    # every zero-compute pick is one label past the routed experts: an
    # expert that no layer holds, whose rows no grouped product multiplies
    picks = jnp.where(routed, index, cfg.n_routed_experts)
    y = expert_ffn(h, weights, picks, p["experts_gate_up"],
                   p["experts_down"], cfg.expert_offset,
                   cfg.n_routed_experts + 1)
    return (y.astype(jnp.float32) + zero).astype(h.dtype), index


# ------------------------------------------------------- the double layer

_MLA = ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj",
        "kv_a_layernorm", "kv_b_proj", "o_proj")
_HALF = ("input_layernorm",) + _MLA + ("post_attention_layernorm",
                                       "mlp_gate_up", "mlp_down")
_LAYER_PARAMS = tuple(f"{n}_{i}" for i in (0, 1) for n in _HALF) + (
    "router", "router_bias", "experts_gate_up", "experts_down")


def _half(p, i):
    return {n: p[f"{n}_{i}"] for n in _HALF}


def double_layer(cfg, x, p, caches=None):
    """One double layer on raw arrays. x [B, S, H]; p: its parameters by
    name; caches: None or two (cbuf, krbuf, lens), one an MLA sublayer.
    Returns (x, caches', expert index [B, S, k])."""
    b, s, hid = x.shape
    eps = cfg.rms_norm_eps
    p0, p1 = _half(p, 0), _half(p, 1)
    out, c0 = mla(cfg, p0, _rms_norm(x, p0["input_layernorm"], eps),
                  caches and caches[0])
    a0 = x + out
    h0 = _rms_norm(a0, p0["post_attention_layernorm"], eps)
    m, index = moe(cfg, p, h0.reshape(b * s, hid))
    d0 = a0 + dense_mlp(h0, p0["mlp_gate_up"], p0["mlp_down"])
    out, c1 = mla(cfg, p1, _rms_norm(d0, p1["input_layernorm"], eps),
                  caches and caches[1])
    a1 = d0 + out
    h1 = _rms_norm(a1, p1["post_attention_layernorm"], eps)
    x = a1 + dense_mlp(h1, p1["mlp_gate_up"], p1["mlp_down"]) \
        + m.reshape(b, s, hid)
    return x, (caches and (c0, c1)), index.reshape(b, s, -1)


def _shapes(cfg):
    h, nh = cfg.hidden_size, cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    f, fe, e = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size, \
        cfg.num_local_experts
    half = {"input_layernorm": (h,), "q_a_proj": (h, cfg.q_lora_rank),
            "q_a_layernorm": (cfg.q_lora_rank,),
            "q_b_proj": (cfg.q_lora_rank, nh * (dn + dr)),
            "kv_a_proj": (h, cfg.kv_lora_rank + dr),
            "kv_a_layernorm": (cfg.kv_lora_rank,),
            "kv_b_proj": (cfg.kv_lora_rank, nh * (dn + dv)),
            "o_proj": (nh * dv, h), "post_attention_layernorm": (h,),
            "mlp_gate_up": (h, 2 * f), "mlp_down": (f, h)}
    out = {f"{n}_{i}": shape for i in (0, 1) for n, shape in half.items()}
    experts = cfg.n_routed_experts + cfg.zero_expert_num
    out.update(router=(h, experts), router_bias=(experts,),
               experts_gate_up=(e, h, 2 * fe), experts_down=(e, fe, h))
    return out


def forward_arrays(cfg, params, ids, caches=None):
    """The whole model on raw arrays. params: {"embed_tokens", "norm",
    "lm_head", "layers": one dict of a double layer's parameters by name
    each}; ids [B, S]; caches: None or one (cbuf, krbuf, lens) an MLA
    sublayer, two a double layer. Returns (logits [B, S, V], caches' or
    None, the routing: one [B, S, k] int32 array a double layer, indices
    over routed and zero-compute experts)."""
    x = params["embed_tokens"][ids]
    new_caches, routing = [], []
    for i, p in enumerate(params["layers"]):
        x, pair, index = double_layer(
            cfg, x, p, None if caches is None else caches[2 * i:2 * i + 2])
        new_caches.extend(pair or ())
        routing.append(index)
    x = _rms_norm(x, params["norm"], cfg.rms_norm_eps)
    return jnp.dot(x, params["lm_head"]), \
        None if caches is None else new_caches, routing


def prefill_lengths(s):
    """The lengths a prefill of ``s`` padded positions may run at: the
    multiples of ``_PREFILL_STEP`` past ``s / 2``, and ``s``."""
    return [n for n in range(_PREFILL_STEP, s, _PREFILL_STEP)
            if 2 * n > s] + [s]


def prefill_arrays(cfg, params, ids, caches, take):
    """``forward_arrays`` over a prefill from position 0 whose lanes count
    their first ``take`` [B] positions of ``ids`` [B, s]: it runs the first
    n alone, n the least of ``prefill_lengths(s)`` that holds them all, so
    that a prompt padded to its bucket costs about its own length. The
    logits and the routing past position n are zeros, and the caches'
    rows past n are left as they were (dead rows: past every length)."""
    b, s = ids.shape
    lengths = prefill_lengths(s)
    if len(lengths) == 1:
        return forward_arrays(cfg, params, ids, caches)

    def first(n):
        def run(ids, caches):
            logits, new, routing = forward_arrays(cfg, params, ids[:, :n],
                                                  caches)
            pad = ((0, 0), (0, s - n), (0, 0))
            return (jnp.pad(logits, pad), new,
                    [jnp.pad(r, pad) for r in routing])
        return run

    which = jnp.sum(jnp.asarray(lengths[:-1]) < jnp.max(take))
    return lax.switch(which, [first(n) for n in lengths], ids, caches)


class LongCatFlashDoubleLayer(nn.Layer):
    """One double layer's parameters; ``double_layer`` computes it."""

    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__()
        normal = I.Normal(0.0, cfg.initializer_range)
        # drawn leaf by leaf in param_dtype: the whole model in float32
        # would not fit the chip before a cast
        for name, shape in _shapes(cfg).items():
            init = I.Constant(0.0) if name == "router_bias" else \
                I.Constant(1.0) if len(shape) == 1 else normal
            setattr(self, name, self.create_parameter(
                shape, dtype=cfg.param_dtype, default_initializer=init))


class LongCatFlashForCausalLM(nn.Layer):
    """Inference only. The output head is untied from the embedding."""

    def __init__(self, cfg: LongCatFlashConfig):
        super().__init__()
        self.cfg = cfg
        std = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.param_dtype,
            default_initializer=std)
        self.layers = nn.LayerList([LongCatFlashDoubleLayer(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), dtype=cfg.param_dtype,
            default_initializer=std)

    def _run(self, input_ids, caches):
        """``forward_arrays`` as one op over the parameters -> (logits,
        caches', routing as raw arrays)."""
        from paddle_tpu.inference.decode import LatentCache, check_capacity
        held, take = (), ()
        if caches is not None:
            for cache in caches:
                check_capacity(cache.length, input_ids.shape[1],
                               cache.c.shape[1])
            held = tuple(a for cache in caches for a in cache[:3])
            if caches[0].take is not None:
                take = (caches[0].take,)
        leaves = [self.embed_tokens, self.norm, self.lm_head] + [
            getattr(layer, n) for layer in self.layers for n in _LAYER_PARAMS]
        n_layer = len(_LAYER_PARAMS)

        def f(ids, *rest):
            lens, rest = rest[:len(take)], rest[len(take):]
            flat = rest[len(held):]
            params = {"embed_tokens": flat[0], "norm": flat[1],
                      "lm_head": flat[2],
                      "layers": [dict(zip(_LAYER_PARAMS,
                                          flat[3 + i:3 + i + n_layer]))
                                 for i in range(0, len(flat) - 3, n_layer)]}
            entries = [rest[i:i + 3] for i in range(0, len(held), 3)]
            if lens:
                logits, new, routing = prefill_arrays(
                    self.cfg, params, ids, entries, lens[0])
            else:
                logits, new, routing = forward_arrays(
                    self.cfg, params, ids, entries if held else None)
            return (logits, *(a for e in new or () for a in e), *routing)
        logits, *out = run_op(
            "longcat_forward", f, input_ids, *take, *held, *leaves,
            n_outputs=1 + len(held) + self.cfg.num_layers,
            differentiable=False)
        new = [LatentCache(*out[i:i + 3]) for i in range(0, len(held), 3)]
        return logits, new, [r._data for r in out[len(held):]]

    @paddle.no_grad()
    def forward(self, input_ids):
        """[B, S] ids -> [B, S, V] logits of the whole sequence from
        position 0."""
        return self._run(input_ids, None)[0]

    def init_cache(self, batch_size, max_length):
        """One ``LatentCache`` an MLA sublayer, two a double layer."""
        from paddle_tpu.inference.decode import init_latent_cache
        cfg = self.cfg
        return [init_latent_cache(batch_size, max_length, cfg.kv_lora_rank,
                                  cfg.qk_rope_head_dim, cfg.cache_dtype)
                for _ in range(2 * cfg.num_layers)]

    def forward_with_cache(self, input_ids, caches):
        """The sessions' contract: (ids [B, s], caches) -> (logits,
        caches), the s positions following each sequence's cached length
        (s > 1: a prefill, from length 0)."""
        return self._run(input_ids, caches)[:2]

    #: what ``forward_with_expert_load`` sums: picks in the active lanes,
    #: of them those to the experts held and those to zero-compute experts,
    #: the busiest held expert's picks, expert layers run, held experts
    #: that any lane reached (what the pass had to read)
    EXPERT_LOAD_LEN = 6

    def forward_with_expert_load(self, input_ids, caches, active):
        """``forward_with_cache`` and, third, the pass's expert load as
        ``EXPERT_LOAD_LEN`` int32 sums over the layers (``active`` [B]:
        the lanes whose picks count)."""
        logits, caches, routing = self._run(input_ids, caches)
        cfg = self.cfg
        held = cfg.expert_offset + jnp.arange(cfg.num_local_experts)
        total = on_held = zero = busiest = touched = jnp.int32(0)
        lane = active[:, None, None]
        for index in routing:
            hit = index[..., None] == held                   # [B, S, k, E]
            per_expert = jnp.sum(hit & lane[..., None], axis=(0, 1, 2),
                                 dtype=jnp.int32)
            total += jnp.sum(jnp.broadcast_to(lane, index.shape),
                             dtype=jnp.int32)
            zero += jnp.sum((index >= cfg.n_routed_experts) & lane,
                            dtype=jnp.int32)
            on_held += jnp.sum(per_expert)
            busiest += jnp.max(per_expert)
            touched += jnp.sum(jnp.any(hit, axis=(0, 1, 2)), dtype=jnp.int32)
        return logits, caches, jnp.stack(
            [total, on_held, zero, busiest, jnp.int32(len(routing)),
             touched])

    @staticmethod
    def count_expert_load(load):
        """Ticks the ``moe.*`` counters by a fetched sum of load vectors."""
        if _met._ENABLED:
            r = _met.REGISTRY
            r.counter("moe.assignments").inc(int(load[0]))
            r.counter("moe.held_assignments").inc(int(load[1]))
            r.counter("moe.zero_assignments").inc(int(load[2]))
            r.counter("moe.busiest_expert_assignments").inc(int(load[3]))
            r.counter("moe.layer_passes").inc(int(load[4]))
            r.counter("moe.experts_touched").inc(int(load[5]))
