"""Mixture-of-Experts layers (BASELINE.json config 5).

Reference: incubate/distributed/models/moe — MoELayer (moe_layer.py:263)
with gshard/switch/naive gates (gate/*.py) over global_scatter/
global_gather all-to-alls.

TPU-native: GShard dense-dispatch einsums with the expert dim sharded over
the 'dp' (expert-parallel) mesh axis; XLA partitions the dispatch/combine
einsums into all-to-alls over ICI. Top-1 (switch) and top-2 (gshard)
gating with capacity + load-balancing aux loss.

These are the TRAINING layers: a capacity drops tokens and every expert
multiplies every token (``einsum("te,th->eth")``). The dropless layer
(softmax over all experts, top-k renormalised, no capacity, sorted pairs
through a grouped product so that only routed pairs are multiplied) is
``models/sdar_moe.expert_ffn``; serving uses that one.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import run_op
from paddle_tpu.nn import functional as F


class TopKGate(nn.Layer):
    """switch (k=1) / gshard (k=2) gate with aux load-balancing loss."""

    def __init__(self, hidden_size, num_experts, top_k=2,
                 capacity_factor=1.25):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.weight = self.create_parameter((hidden_size, num_experts),
                                            None)
        self._last_aux_loss = None

    def forward(self, x):
        """x: [T, H] -> (dispatch [T, E], combine [T, E], aux_loss)."""
        def f(tokens, w):
            logits = tokens.astype(jnp.float32) @ w.astype(jnp.float32)
            probs = jax.nn.softmax(logits, -1)
            e = self.num_experts
            topv, topi = jax.lax.top_k(probs, self.top_k)
            disp = jnp.zeros_like(probs)
            for j in range(self.top_k):
                disp = disp + jax.nn.one_hot(topi[:, j], e,
                                             dtype=probs.dtype)
            combine = probs * disp
            combine = combine / jnp.maximum(
                jnp.sum(combine, -1, keepdims=True), 1e-9)
            # load-balancing aux loss (Switch Transformer eq. 4)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(disp, axis=0)
            aux = e * jnp.sum(me * ce)
            return disp, combine, aux
        return run_op("topk_gate", f, x, self.weight)


class ExpertFFN(nn.Layer):
    """E parallel FFNs stored stacked [E, ...] (shard dim 0 over 'dp'/ep)."""

    def __init__(self, num_experts, hidden_size, intermediate_size,
                 activation="gelu"):
        super().__init__()
        from paddle_tpu.nn import initializer as I
        self.w1 = self.create_parameter(
            (num_experts, hidden_size, intermediate_size), None,
            default_initializer=I.XavierNormal())
        self.b1 = self.create_parameter((num_experts, intermediate_size),
                                        None, is_bias=True)
        self.w2 = self.create_parameter(
            (num_experts, intermediate_size, hidden_size), None,
            default_initializer=I.XavierNormal())
        self.b2 = self.create_parameter((num_experts, hidden_size), None,
                                        is_bias=True)
        self.act = activation

    def forward(self, xin):
        """xin: [E, T, H] -> [E, T, H]"""
        def f(a, w1, b1, w2, b2):
            h = jnp.einsum("eth,ehm->etm", a, w1) + b1[:, None]
            h = jax.nn.gelu(h) if self.act == "gelu" else jax.nn.relu(h)
            return jnp.einsum("etm,emh->eth", h, w2) + b2[:, None]
        return run_op("expert_ffn", f, xin, self.w1, self.b1, self.w2,
                      self.b2)


class MoELayer(nn.Layer):
    """reference moe_layer.py:263 equivalent."""

    def __init__(self, hidden_size, intermediate_size, num_experts,
                 top_k=2, capacity_factor=1.25, gate="gshard",
                 aux_loss_weight=0.01):
        super().__init__()
        k = 1 if gate == "switch" else top_k
        self.gate = TopKGate(hidden_size, num_experts, k, capacity_factor)
        self.experts = ExpertFFN(num_experts, hidden_size,
                                 intermediate_size)
        self.aux_loss_weight = aux_loss_weight
        self._aux_loss = None

    def forward(self, x):
        b, s, h = x.shape
        tokens = x.reshape([b * s, h])
        disp, combine, aux = self.gate(tokens)
        self._aux_loss = aux
        def f(t, d, c):
            xin = jnp.einsum("te,th->eth", d.astype(t.dtype), t)
            return xin
        xin = run_op("moe_dispatch", f, tokens, disp, combine)
        expert_out = self.experts(xin)
        def g(c, eo):
            return jnp.einsum("te,eth->th", c.astype(eo.dtype), eo)
        out = run_op("moe_combine", g, combine, expert_out)
        return out.reshape([b, s, h])

    @property
    def aux_loss(self):
        return self._aux_loss


class MoETransformerBlock(nn.Layer):
    def __init__(self, hidden_size, num_heads, intermediate_size,
                 num_experts, top_k=2):
        super().__init__()
        self.ln1 = nn.LayerNorm(hidden_size)
        self.attn = nn.MultiHeadAttention(hidden_size, num_heads)
        self.ln2 = nn.LayerNorm(hidden_size)
        self.moe = MoELayer(hidden_size, intermediate_size, num_experts,
                            top_k)

    def forward(self, x, mask=None):
        x = x + self.attn(self.ln1(x), attn_mask=mask)
        x = x + self.moe(self.ln2(x))
        return x
