"""Llama-family decoder LM (BASELINE.json config 4: Llama-2 7B hybrid).

Reference analog: test/auto_parallel/hybrid_strategy/
semi_auto_parallel_llama_model.py + incubate fused ops (fused_rms_norm,
fused_rotary_position_embedding, swiglu — here XLA fuses the jnp graphs;
attention goes through scaled_dot_product_attention → Pallas flash on TPU).
Supports GQA (num_kv_heads < num_heads).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import run_op
from paddle_tpu.nn import functional as F


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    use_tensor_parallel: bool = False

    @staticmethod
    def llama2_7b():
        return LlamaConfig()

    @staticmethod
    def tiny():
        return LlamaConfig(vocab_size=256, hidden_size=64,
                           intermediate_size=128, num_layers=2, num_heads=4,
                           num_kv_heads=2, max_seq_len=64)


def apply_rotary_pos_emb(x, position_offset=0, theta=10000.0,
                         half_split=False):
    """RoPE on [B, S, H, D] (reference:
    incubate/nn/functional/fused_rotary_position_embedding.py).
    position_offset may be a python int or a [B] int32 tensor (the decode
    path's per-sequence cache lengths). By default the rotated pairs are
    the interleaved columns (2i, 2i+1); ``half_split`` pairs column i with
    column i + D/2 (``x * cos + rotate_half(x) * sin``, the Hugging Face
    layout), which is the interleaved form up to a fixed permutation of
    the columns."""
    return run_op("rope", lambda a, off: rope(a, off, theta, half_split),
                  x, position_offset)


def rope(a, off, theta, half_split=False):
    """The rotation on raw arrays (``apply_rotary_pos_emb``'s body)."""
    b, s, h, d = a.shape
    pos = (off.reshape(-1, 1).astype(jnp.float32)
           + jnp.arange(s, dtype=jnp.float32)[None, :])   # [B|1, S]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = pos[..., None] * inv                   # [B|1, S, D/2]
    cos = jnp.cos(freqs)[:, :, None, :]
    sin = jnp.sin(freqs)[:, :, None, :]
    if half_split:
        x1 = a[..., :d // 2].astype(jnp.float32)
        x2 = a[..., d // 2:].astype(jnp.float32)
    else:
        x1 = a[..., 0::2].astype(jnp.float32)
        x2 = a[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    if half_split:
        out = jnp.concatenate([o1, o2], axis=-1)
    else:
        out = jnp.stack([o1, o2], axis=-1).reshape(a.shape)
    return out.astype(a.dtype)


class LlamaAttention(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        d = h // cfg.num_heads
        kv_out = cfg.num_kv_heads * d
        if cfg.use_tensor_parallel:
            from paddle_tpu.distributed import fleet
            mk = lambda i, o: fleet.ColumnParallelLinear(  # noqa: E731
                i, o, has_bias=False, gather_output=False)
            self.q_proj = mk(h, h)
            self.k_proj = mk(h, kv_out)
            self.v_proj = mk(h, kv_out)
            self.o_proj = fleet.RowParallelLinear(h, h, has_bias=False,
                                                  input_is_parallel=True)
        else:
            self.q_proj = nn.Linear(h, h, bias_attr=False)
            self.k_proj = nn.Linear(h, kv_out, bias_attr=False)
            self.v_proj = nn.Linear(h, kv_out, bias_attr=False)
            self.o_proj = nn.Linear(h, h, bias_attr=False)

    def forward(self, x, position_offset=0, cache=None):
        from paddle_tpu.inference.decode import StaticCache, cache_attention
        cfg = self.cfg
        b, s, h = x.shape
        d = h // cfg.num_heads
        q = self.q_proj(x).reshape([b, s, cfg.num_heads, d])
        k = self.k_proj(x).reshape([b, s, cfg.num_kv_heads, d])
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, d])
        if isinstance(cache, StaticCache):
            # fixed-capacity decode path: RoPE offsets come from the
            # per-sequence cache lengths; ONE static-shape program per
            # (B, s) — no recompiles, no reallocating concat
            q = apply_rotary_pos_emb(q, cache.length, cfg.rope_theta)
            k = apply_rotary_pos_emb(k, cache.length, cfg.rope_theta)
            out, cache = cache_attention(q, k, v, cache)
            out = out.reshape([b, s, h])
            return self.o_proj(out), cache
        q = apply_rotary_pos_emb(q, position_offset, cfg.rope_theta)
        k = apply_rotary_pos_emb(k, position_offset, cfg.rope_theta)
        if cache is not None:
            pk, pv = cache
            k = paddle.concat([pk, k], axis=1)
            v = paddle.concat([pv, v], axis=1)
            cache = (k, v)
        if cfg.num_kv_heads != cfg.num_heads:
            rep = cfg.num_heads // cfg.num_kv_heads
            k = k.repeat_interleave(rep, axis=2)
            v = v.repeat_interleave(rep, axis=2)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        out = out.reshape([b, s, h])
        out = self.o_proj(out)
        return out if cache is None else (out, cache)


class LlamaMLP(nn.Layer):
    """SwiGLU (reference incubate swiglu fused op)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        h, m = cfg.hidden_size, cfg.intermediate_size
        if cfg.use_tensor_parallel:
            from paddle_tpu.distributed import fleet
            self.gate_proj = fleet.ColumnParallelLinear(
                h, m, has_bias=False, gather_output=False)
            self.up_proj = fleet.ColumnParallelLinear(
                h, m, has_bias=False, gather_output=False)
            self.down_proj = fleet.RowParallelLinear(
                m, h, has_bias=False, input_is_parallel=True)
        else:
            self.gate_proj = nn.Linear(h, m, bias_attr=False)
            self.up_proj = nn.Linear(h, m, bias_attr=False)
            self.down_proj = nn.Linear(m, h, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(cfg.hidden_size,
                                          epsilon=cfg.rms_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = nn.RMSNorm(cfg.hidden_size,
                                                   epsilon=cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def forward(self, x, position_offset=0, cache=None):
        attn_out = self.self_attn(self.input_layernorm(x),
                                  position_offset, cache)
        if cache is not None:
            attn_out, cache = attn_out
        x = x + attn_out
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x if cache is None else (x, cache)


class LlamaModel(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        if cfg.use_tensor_parallel:
            from paddle_tpu.distributed import fleet
            self.embed_tokens = fleet.VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size)
        else:
            self.embed_tokens = nn.Embedding(cfg.vocab_size,
                                             cfg.hidden_size)
        self.layers = nn.LayerList([LlamaBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                 bias_attr=False)

    def forward(self, input_ids, position_offset=0, caches=None):
        x = self.embed_tokens(input_ids)
        new_caches = []
        for i, blk in enumerate(self.layers):
            if caches is None:
                x = blk(x, position_offset)
            else:
                x, c = blk(x, position_offset, caches[i])
                new_caches.append(c)
        x = self.norm(x)
        logits = self.lm_head(x)
        return logits if caches is None else (logits, new_caches)

    def init_cache(self, batch_size, max_length=None):
        """max_length=None: legacy growing concat cache (recompiles per
        step — test/back-compat only). max_length=C: fixed-capacity
        static cache for the compiled decode path."""
        d = self.cfg.hidden_size // self.cfg.num_heads
        if max_length is not None:
            from paddle_tpu.inference.decode import init_static_cache
            return [init_static_cache(batch_size, max_length,
                                      self.cfg.num_kv_heads, d)
                    for _ in range(self.cfg.num_layers)]
        z = paddle.zeros([batch_size, 0, self.cfg.num_kv_heads, d])
        return [(z, z) for _ in range(self.cfg.num_layers)]


class LlamaForCausalLM(nn.Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.llama = LlamaModel(cfg)
        self.loss_fn = nn.CrossEntropyLoss()

    def forward(self, input_ids, labels=None):
        logits = self.llama(input_ids)
        if labels is None:
            return logits
        return self.loss_fn(
            logits[:, :-1].reshape([-1, logits.shape[-1]]),
            labels[:, 1:].reshape([-1]))

    def init_cache(self, batch_size, max_length=None):
        return self.llama.init_cache(batch_size, max_length)

    def forward_with_cache(self, input_ids, caches):
        """DecodeSession contract: (ids, caches) -> (logits, caches)."""
        return self.llama(input_ids, 0, caches)

    @paddle.no_grad()
    def generate(self, input_ids, max_new_tokens=16, temperature=0.0,
                 top_p=None, seed=None, max_length=None,
                 decode_block=None):
        """Compiled static-shape generation (decode = ONE executable
        reused every token; the cache is a donated fixed-capacity buffer
        updated with dynamic_update_slice). Replaces the round-2
        per-token-recompiling concat path."""
        from paddle_tpu.inference.decode import cached_generate
        self.eval()
        return cached_generate(self, input_ids, max_new_tokens,
                               temperature=temperature, top_p=top_p,
                               seed=seed, max_length=max_length,
                               decode_block=decode_block,
                               seq_ceiling=self.llama.cfg.max_seq_len,
                               hard_limit=False)
