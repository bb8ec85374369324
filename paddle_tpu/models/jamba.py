"""Jamba decoder LM (``model_type: jamba``; AI21-Jamba2-3B,
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json): a
hybrid of attention and Mamba-1 state-space layers.

Layer ``i`` mixes by attention where ``(i - attn_layer_offset) %
attn_layer_period == 0`` (grouped-query, causal, no bias, NO positional
encoding) and by a Mamba-1 mixer otherwise: a depthwise causal convolution
of ``mamba_d_conv`` taps, an input-dependent step ``delta`` and the pair
``B``, ``C`` (each through its own RMSNorm, Jamba's addition), and the
selective scan ``h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t * x_t) (x)
B_t``, ``y_t = h_t C_t + D * x_t`` over ``mamba_d_state`` states a channel.
Every layer then has a dense SwiGLU (``num_experts`` is 1); RMSNorm before
each half; the output head is the embedding.

Serving contract (``DecodeSession``, ``ContinuousBatchingSession``):
``init_cache`` gives a ``StaticCache`` for an attention layer and a
``RecurrentCache`` for a Mamba layer: the window of the last ``d_conv - 1``
inputs of the convolution and the scan state, float32, whatever the length.
``forward_with_cache`` computes the ``s`` positions that follow what the
caches hold. A recurrent entry's ``take`` (None: all of them) says how many
of the ``s`` count in each lane: a position that does not count has ``delta
= 0``, so the state passes through it, and does not shift the window. That
is how a padded prefill ends in the state of the unpadded prompt and how a
lane that does not step keeps its state, without a copy of it.

Layouts: matrices [in, out]; ``conv_weight`` [d_conv, I]; ``A_log`` and the
scan state state-major, [N, I] and [B, N, I], the window [d_conv - 1, B, I]:
a TPU tiles an array's last two dims (8 x 128), so the channels lie along
the lanes and nothing is padded (a state of [I, 16] would take eight times
its bytes).
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import run_op
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability import metrics as _met


@dataclasses.dataclass
class JambaConfig:
    """The keys of the model's public ``config.json`` (``num_layers`` and
    ``num_heads`` under this repo's names), and what a builder needs
    besides. Keys the layer equations do not read are kept so that the
    file can be passed whole; ``__post_init__`` refuses the values this
    implementation does not compute."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_layers: int = 28                 # num_hidden_layers
    num_heads: int = 20                  # num_attention_heads
    num_key_value_heads: int = 1
    head_dim: int = None                 # hidden_size // num_heads
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 160
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    max_position_embeddings: int = 262144
    sliding_window: object = None
    use_mamba_kernels: bool = True
    num_logits_to_keep: int = 1
    model_type: str = "jamba"
    param_dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        unsupported = {
            "num_experts": self.num_experts != 1,
            "mamba_proj_bias": self.mamba_proj_bias,
            "mamba_conv_bias": not self.mamba_conv_bias,
            "tie_word_embeddings": not self.tie_word_embeddings,
            "sliding_window": self.sliding_window is not None,
            "hidden_act": self.hidden_act != "silu"}
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"JambaConfig: not supported here: {bad}")

    @property
    def mamba_inner(self):
        return self.mamba_expand * self.hidden_size

    def layer_kind(self, i):
        """"attention" or "mamba" (Jamba's rule; ``config.json`` gives the
        period and the offset, not the order)."""
        return "attention" if i % self.attn_layer_period \
            == self.attn_layer_offset % self.attn_layer_period else "mamba"

    @staticmethod
    def tiny(**kw):
        """Two periods of four layers, both kinds, one KV head."""
        base = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                    num_layers=8, num_heads=4, num_key_value_heads=1,
                    attn_layer_period=4, attn_layer_offset=1,
                    mamba_d_state=4, mamba_dt_rank=6,
                    max_position_embeddings=256)
        base.update(kw)
        return JambaConfig(**base)


# ---------------------------------------------------------------- arrays

def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _kernel_backend():
    """Where a prefill's scan goes through the Pallas kernel."""
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames="interpret")
@jax.named_scope("selective_scan")      # a profile names the kernel after it
def _scan_kernel(x, delta, bm, cm, a, d, h, interpret):
    """The kernel as a program of its own: the layers of a model call it
    at one shape, so it is traced once a shape and not once a layer."""
    from paddle_tpu.ops.pallas.selective_scan import selective_scan
    return selective_scan(x, delta, bm, cm, a, d, h, interpret=interpret)


@jax.named_scope("selective_scan")
def _scan(x, delta, bm, cm, a, d, h):
    """A prefill's scan (s > 1): on a TPU the chunked kernel, and a shape
    it cannot take raises; every other backend runs the same arithmetic as
    a ``lax.scan`` over time. Neither is a fallback of the other."""
    from paddle_tpu.ops.pallas import selective_scan as ss
    chunked = _kernel_backend()
    if _met._ENABLED:
        _met.REGISTRY.counter(
            "ssm.scan_dispatch",
            kernel="chunked" if chunked else "sequential").inc()
    if chunked:
        return _scan_kernel(x, delta, bm, cm, a, d, h,
                            interpret=jax.default_backend() != "tpu")
    return ss.sequential(x, delta, bm, cm, a, d, h)


@jax.named_scope("ssm_step")
def _ssm_step(x, delta, bm, cm, a, d, h):
    """The one-token step: x, delta [B, I]; bm, cm [B, N]; h [B, N, I].
    The state is read once and written once."""
    h = jnp.exp(delta[:, None, :] * a) * h \
        + (delta * x)[:, None, :] * bm[:, :, None]
    return jnp.sum(h * cm[:, :, None], axis=1) + d * x, h


@jax.named_scope("causal_conv")
def _causal_conv(xs, w, bias, window, take):
    """xs [B, s, I] float32 after the window [K - 1, B, I] of the inputs
    before them. Returns (silu(conv) [B, s, I], the window after the
    first ``take`` of them; None: after all s)."""
    taps = w.shape[0]
    s = xs.shape[1]
    if s == 1:
        x1 = xs[:, 0]
        out = bias + w[taps - 1] * x1 \
            + sum(w[j] * window[j] for j in range(taps - 1))
        shifted = jnp.concatenate([window[1:], x1[None]], axis=0)
        if take is not None:
            shifted = jnp.where(take[None, :, None] > 0, shifted, window)
        return jax.nn.silu(out)[:, None], shifted
    seq = jnp.concatenate([jnp.swapaxes(window, 0, 1), xs], axis=1)
    out = bias + sum(w[j] * seq[:, j:j + s] for j in range(taps))
    if take is None:
        after = seq[:, s:]
    else:
        after = jax.vmap(
            lambda row, n: lax.dynamic_slice_in_dim(row, n, taps - 1, 0)
        )(seq, take)
    return jax.nn.silu(out), jnp.swapaxes(after, 0, 1)


@jax.named_scope("mamba_mixer")
def mamba_mixer(cfg, u, p, window, state, take=None):
    """u [B, s, H], the layer's normed input; window [K - 1, B, I] and
    state [B, N, I], float32, as the positions before left them; take [B]
    int32: how many of the s positions count in each lane (None: all).
    Returns (out [B, s, H], window', state')."""
    f32 = jnp.float32
    s = u.shape[1]
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    xs, z = jnp.split(jnp.dot(u, p["in_proj"]), 2, axis=-1)
    xc, window = _causal_conv(xs.astype(f32), p["conv_weight"].astype(f32),
                              p["conv_bias"].astype(f32), window, take)
    dbc = jnp.dot(xc.astype(u.dtype), p["x_proj"],
                  preferred_element_type=f32)
    dr, bm, cm = jnp.split(dbc, [r, r + n], axis=-1)
    eps = cfg.rms_norm_eps
    dr = _rms_norm(dr, p["dt_layernorm"], eps)
    bm = _rms_norm(bm, p["b_layernorm"], eps)
    cm = _rms_norm(cm, p["c_layernorm"], eps)
    delta = jax.nn.softplus(
        jnp.dot(dr.astype(u.dtype), p["dt_proj"], preferred_element_type=f32)
        + p["dt_bias"].astype(f32))
    if take is not None:
        # a position that does not count leaves the state as it was
        counts = jnp.arange(s)[None, :, None] < take[:, None, None]
        delta = jnp.where(counts, delta, 0.0)
    a = -jnp.exp(p["A_log"].astype(f32))
    d = p["D"].astype(f32)
    if s == 1:
        y, state = _ssm_step(xc[:, 0], delta[:, 0], bm[:, 0], cm[:, 0], a,
                             d, state)
        y = y[:, None]
    else:
        y, state = _scan(xc, delta, bm, cm, a, d, state)
    y = (y * jax.nn.silu(z.astype(f32))).astype(u.dtype)
    return jnp.dot(y, p["out_proj"]), window, state


@jax.named_scope("attention_mixer")
def attention_mixer(cfg, u, p, kv=None):
    """u [B, s, H]; kv: None (the s positions are the whole sequence) or
    (kbuf, vbuf, lens). Returns (out, kv')."""
    from paddle_tpu.inference import decode
    b, s, _h = u.shape
    nh, nkv, d = cfg.num_heads, cfg.num_key_value_heads, cfg.head_dim
    q = jnp.dot(u, p["q_proj"]).reshape(b, s, nh, d)
    k = jnp.dot(u, p["k_proj"]).reshape(b, s, nkv, d)
    v = jnp.dot(u, p["v_proj"]).reshape(b, s, nkv, d)
    if kv is None:
        out = decode._attend_einsum(
            q, k, v, jnp.zeros((b,), jnp.int32)).astype(u.dtype)
    else:
        out, *kv = decode._cache_attention(q, k, v, *kv)
    return jnp.dot(out.reshape(b, s, nh * d), p["o_proj"]), kv


def _mlp(cfg, x, p):
    u = _rms_norm(x, p["pre_ff_layernorm"], cfg.rms_norm_eps)
    act = jax.nn.silu(jnp.dot(u, p["gate_proj"])) * jnp.dot(u, p["up_proj"])
    return x + jnp.dot(act, p["down_proj"])


# ---------------------------------------------------------------- layers

_BOTH = ("input_layernorm", "pre_ff_layernorm", "gate_proj", "up_proj",
         "down_proj")
_MIXER = {
    "attention": ("q_proj", "k_proj", "v_proj", "o_proj"),
    "mamba": ("in_proj", "conv_weight", "conv_bias", "x_proj",
              "dt_layernorm", "b_layernorm", "c_layernorm", "dt_proj",
              "dt_bias", "A_log", "D", "out_proj")}


class _StepBias(I.Initializer):
    """``dt_bias``: the inverse softplus of steps drawn log-uniformly in
    [lo, hi] (Mamba's own initialisation)."""

    def __init__(self, lo=1e-3, hi=1e-1):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype):
        u = I.Uniform(math.log(self.lo), math.log(self.hi))(shape, "float32")
        step = jnp.exp(u)
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)


class JambaDecoderLayer(nn.Layer):
    def __init__(self, cfg: JambaConfig, index: int):
        super().__init__()
        self.cfg, self.kind = cfg, cfg.layer_kind(index)
        h, f, inner = cfg.hidden_size, cfg.intermediate_size, cfg.mamba_inner
        n, r, taps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
        q, kv = cfg.num_heads * cfg.head_dim, \
            cfg.num_key_value_heads * cfg.head_dim
        normal = I.Normal(0.0, cfg.initializer_range)
        one = I.Constant(1.0)
        # drawn leaf by leaf in param_dtype: shape, initializer
        leaves = {
            "input_layernorm": ((h,), one), "pre_ff_layernorm": ((h,), one),
            "gate_proj": ((h, f), normal), "up_proj": ((h, f), normal),
            "down_proj": ((f, h), normal),
            "q_proj": ((h, q), normal), "k_proj": ((h, kv), normal),
            "v_proj": ((h, kv), normal), "o_proj": ((q, h), normal),
            "in_proj": ((h, 2 * inner), normal),
            "conv_weight": ((taps, inner), normal),
            "conv_bias": ((inner,), I.Constant(0.0)),
            "x_proj": ((inner, r + 2 * n), normal),
            "dt_layernorm": ((r,), one), "b_layernorm": ((n,), one),
            "c_layernorm": ((n,), one),
            "dt_proj": ((r, inner), normal), "dt_bias": ((inner,), _StepBias()),
            "A_log": ((n, inner), I.Assign(np.log(np.broadcast_to(
                np.arange(1, n + 1, dtype=np.float32)[:, None],
                (n, inner))))),
            "D": ((inner,), one), "out_proj": ((inner, h), normal)}
        self._names = _MIXER[self.kind] + _BOTH
        for name in self._names:
            shape, init = leaves[name]
            setattr(self, name, self.create_parameter(
                shape, dtype=cfg.param_dtype, default_initializer=init))

    def forward(self, x, cache=None):
        """cache: None, a StaticCache (attention) or a RecurrentCache
        (Mamba). Returns (x, cache')."""
        from paddle_tpu.inference.decode import check_capacity
        cfg, kind = self.cfg, self.kind
        if cache is not None and kind == "attention":
            check_capacity(cache.length, x.shape[1], cache.k.shape[1])
        held = tuple(t for t in (cache or ()) if t is not None)

        def f(x, *rest):
            p = dict(zip(self._names, rest[len(held):]))
            u = _rms_norm(x, p["input_layernorm"], cfg.rms_norm_eps)
            if kind == "attention":
                out, new = attention_mixer(cfg, u, p, rest[:len(held)] or None)
            else:
                out, *new = self._mamba(u, p, *rest[:len(held)])
            return (_mlp(cfg, x + out, p), *(new or ()))
        out, *new = run_op(
            "jamba_layer", f, x, *held,
            *(getattr(self, name) for name in self._names),
            n_outputs=1 + (len(held) and 3), differentiable=False)
        return out, type(cache)(*new) if new else None

    def _mamba(self, u, p, window=None, state=None, length=None, take=None):
        cfg = self.cfg
        if window is None:      # the whole sequence, from nothing
            b = u.shape[0]
            window = jnp.zeros((cfg.mamba_d_conv - 1, b, cfg.mamba_inner),
                               jnp.float32)
            state = jnp.zeros((b, cfg.mamba_d_state, cfg.mamba_inner),
                              jnp.float32)
            return mamba_mixer(cfg, u, p, window, state)[:1]
        out, window, state = mamba_mixer(cfg, u, p, window, state, take)
        took = jnp.int32(u.shape[1]) if take is None else take
        return out, window, state, length + took


class JambaForCausalLM(nn.Layer):
    """Inference only: the scan has no backward pass here."""

    def __init__(self, cfg: JambaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.param_dtype,
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        self.layers = nn.LayerList([JambaDecoderLayer(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.final_layernorm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))

    def _run(self, input_ids, caches):
        x = run_op("jamba_embed", lambda w, i: w[i], self.embed_tokens,
                   input_ids, differentiable=False)
        new_caches = []
        for i, layer in enumerate(self.layers):
            x, cache = layer(x, None if caches is None else caches[i])
            new_caches.append(cache)
        eps = self.cfg.rms_norm_eps
        logits = run_op(
            "jamba_head",
            lambda x, g, w: jnp.einsum("bsh,vh->bsv", _rms_norm(x, g, eps), w),
            x, self.final_layernorm, self.embed_tokens, differentiable=False)
        return logits, new_caches

    @paddle.no_grad()
    def forward(self, input_ids):
        """[B, S] ids -> [B, S, V] logits of the whole sequence from
        position 0."""
        return self._run(input_ids, None)[0]

    def init_cache(self, batch_size, max_length):
        from paddle_tpu.inference.decode import (init_recurrent_cache,
                                                 init_static_cache)
        cfg = self.cfg
        return [init_static_cache(batch_size, max_length,
                                  cfg.num_key_value_heads, cfg.head_dim)
                if layer.kind == "attention" else
                init_recurrent_cache(batch_size, cfg.mamba_inner,
                                     cfg.mamba_d_state, cfg.mamba_d_conv - 1)
                for layer in self.layers]

    def forward_with_cache(self, input_ids, caches):
        """The sessions' contract: (ids [B, s], caches) -> (logits,
        caches), the s positions following what each cache holds."""
        return self._run(input_ids, caches)
