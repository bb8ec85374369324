"""Dense GPT (GPT-3 family: learned positions, pre-LN blocks, tanh GELU,
tied embedding): what the mathematics requires, and a plain reference.

Sizes come from a configuration file's ``sizes``: ``num_layers`` L,
``hidden_size`` h, ``num_heads``, ``ffn_mult`` (FFN width f = ffn_mult*h),
``vocab_size`` V (as held, padding included), ``max_seq_len``.

* ``train_flops_per_token`` — operations the forward and backward passes
  require per trained token. Causal attention is counted once (a token at
  position t attends to t+1 keys), recomputation is not counted.
* ``decode_step_bytes`` — bytes one decode step must read: every weight
  once, plus the VALID cached tokens at the cache's dtype.
* ``reference_logits`` — the forward pass in straightforward float32
  ``jax.numpy`` (no kernel, no cache, no batching tricks), on parameters in
  ``gpt_hybrid.init_params``'s layout; ``from_serving_state`` brings
  ``GPTForCausalLM``'s state dict into that layout.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(sizes):
    h = sizes["hidden_size"]
    return sizes["num_layers"], h, sizes["ffn_mult"] * h, sizes["vocab_size"]


def weight_count(sizes):
    """Parameters a forward pass reads (the tied embedding once, as the
    output head; the position table is a row lookup and is left out)."""
    L, h, f, V = _dims(sizes)
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return L * per_layer + V * h + 2 * h


def forward_flops_per_token(sizes, seq):
    """Multiply-adds count two. Per layer: QKV 6h^2, projection 2h^2, FFN
    4hf, attention 4h keys-attended with (seq+1)/2 keys on average under
    the causal mask; the head 2hV."""
    L, h, f, V = _dims(sizes)
    attn = 4 * h * (seq + 1) / 2
    return L * (8 * h * h + 4 * h * f + attn) + 2 * h * V


def train_flops_per_token(sizes, seq):
    """Forward plus backward (twice the forward: one product for the input
    gradient, one for the weight gradient)."""
    return 3 * forward_flops_per_token(sizes, seq)


def cache_bytes_per_token(sizes, cache_itemsize):
    L, h, _f, _V = _dims(sizes)
    return 2 * L * h * cache_itemsize


def decode_step_bytes(sizes, valid_tokens, weight_itemsize, cache_itemsize):
    """``valid_tokens``: cached positions of the running slots, summed."""
    return weight_count(sizes) * weight_itemsize \
        + valid_tokens * cache_bytes_per_token(sizes, cache_itemsize)


# ------------------------------------------------------------ reference

def _layer_norm(x, g, b, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def reference_logits(params, ids, num_heads):
    """[B, S] token ids -> [B, S, V] float32 logits. ``params`` may hold any
    float dtype; everything is cast to float32 and every product runs at
    ``highest`` precision (on a TPU a float32 matmul otherwise runs in bf16
    passes)."""
    with jax.default_matmul_precision("highest"):
        return _reference_logits(params, jnp.asarray(ids), num_heads)


@functools.partial(jax.jit, static_argnums=2)   # one program, kept in the cache
def _reference_logits(p, ids, num_heads):
    p = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    b, s = ids.shape
    x = p["wte"][ids] + p["wpe"][:s][None]
    h = x.shape[-1]
    d = h // num_heads
    mask = jnp.tril(jnp.ones((s, s), bool))
    blocks = p["blocks"]
    for i in range(blocks["qkv_w"].shape[0]):
        lp = {k: v[i] for k, v in blocks.items()}
        y = _layer_norm(x, lp["ln1_g"], lp["ln1_b"])
        qkv = y @ lp["qkv_w"] + lp["qkv_b"]
        q, k, v = (t.reshape(b, s, num_heads, d)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + attn.reshape(b, s, h) @ lp["proj_w"] + lp["proj_b"]
        y = _layer_norm(x, lp["ln2_g"], lp["ln2_b"])
        y = jax.nn.gelu(y @ lp["fc1_w"] + lp["fc1_b"], approximate=True)
        x = x + y @ lp["fc2_w"] + lp["fc2_b"]
    x = _layer_norm(x, p["lnf_g"], p["lnf_b"])
    return jnp.einsum("bsh,vh->bsv", x, p["wte"])


def from_serving_state(state, num_layers):
    """``GPTForCausalLM.state_dict()`` (name -> array) -> the layout above.
    ``nn.Linear`` keeps its weight as [in, out], as ``gpt_hybrid`` does."""
    def get(name):
        a = state[name]
        a = a.numpy() if hasattr(a, "numpy") else a
        return np.asarray(a).astype(np.float32)

    def stack(fmt):
        return np.stack([get(fmt.format(i)) for i in range(num_layers)])

    names = {"ln1_g": "ln1.weight", "ln1_b": "ln1.bias",
             "qkv_w": "attn.qkv.weight", "qkv_b": "attn.qkv.bias",
             "proj_w": "attn.proj.weight", "proj_b": "attn.proj.bias",
             "ln2_g": "ln2.weight", "ln2_b": "ln2.bias",
             "fc1_w": "mlp.fc1.weight", "fc1_b": "mlp.fc1.bias",
             "fc2_w": "mlp.fc2.weight", "fc2_b": "mlp.fc2.bias"}
    return {"wte": get("gpt.wte.weight"), "wpe": get("gpt.wpe.weight"),
            "blocks": {k: stack("gpt.blocks.{}." + v)
                       for k, v in names.items()},
            "lnf_g": get("gpt.ln_f.weight"), "lnf_b": get("gpt.ln_f.bias")}
