"""Jamba (``model_type: jamba``; AI21-Jamba2-3B,
https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json): a
decoder whose layers mix by attention every ``attn_layer_period`` layers
(from ``attn_layer_offset``) and by a Mamba-1 selective state space
otherwise; what the mathematics requires, and a plain reference.

Sizes come from a configuration file's ``sizes``: ``num_layers`` L,
``hidden_size`` h, ``num_heads`` x ``head_dim``, ``num_key_value_heads``,
``intermediate_size`` f (every FFN is the dense SwiGLU: ``num_experts`` 1),
``mamba_expand`` (inner width I = expand x h), ``mamba_d_state`` N,
``mamba_d_conv`` K, ``mamba_dt_rank`` R, ``vocab_size`` V (tied head),
``attn_layer_period`` / ``attn_layer_offset``.

The layer equations (float32; ``u`` the layer's normed input):

* model: ``x = E[ids]`` (no position term); layer i: ``x += Mixer_i(
  RMSNorm(x))``; ``x += (silu(u Wg) * (u Wu)) Wd`` on ``u = RMSNorm(x)``;
  after the last layer RMSNorm, logits ``x E^T``.
* attention: q as ``num_heads`` heads, k and v as ``num_key_value_heads``,
  causal ``softmax(q k^T / sqrt(d)) v``, then ``Wo``. No bias, no rotary.
* Mamba: ``[xs, z] = u W_in``; ``xc_t = silu(b + sum_j w[j] * xs_{t-K+1+j})``
  (depthwise, causal, zeros before the sequence); ``[dr, B, C] = xc W_x``,
  each through its own RMSNorm; ``delta = softplus(dr W_dt + b_dt)``;
  ``A = -exp(A_log)``; ``h_t = exp(delta_t (x) A) * h_{t-1} + (delta_t *
  xc_t) (x) B_t``; ``y_t = h_t C_t + D * xc_t``; out ``(y * silu(z)) W_out``.

The reference: those equations in float32 ``jax.numpy`` at ``highest``
matmul precision, the scan a plain ``lax.scan`` over time, no cache, no
kernel. It runs A LAYER AT A TIME: ``from_serving_state`` keeps the model's
own leaves on the host in their dtype, and each layer's are put on the
device, cast and dropped in turn, so that 28 float32 layers never lie on
the chip at once. It imports nothing from ``paddle_tpu.models``.

Layouts of the state dict it reads (``JambaForCausalLM.state_dict()``):
matrices [in, out]; ``conv_weight`` [K, I]; ``A_log`` [N, I] (state-major,
as the serving state is held: on a TPU the last dim lies along the lanes).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def layer_kind(sizes, i):
    """"attention" or "mamba": layer i attends where ``(i - offset) %
    period == 0`` (the catalog gives offset and period, not the order;
    this is Jamba's own rule)."""
    period, offset = sizes["attn_layer_period"], sizes["attn_layer_offset"]
    return "attention" if i % period == offset % period else "mamba"


def layer_counts(sizes):
    kinds = [layer_kind(sizes, i) for i in range(sizes["num_layers"])]
    return kinds.count("mamba"), kinds.count("attention")


def _inner(sizes):
    return sizes["mamba_expand"] * sizes["hidden_size"]


def mamba_weight_count(sizes):
    h, inner = sizes["hidden_size"], _inner(sizes)
    n, k, r = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    return (h * 2 * inner + inner * k + inner + inner * (r + 2 * n)
            + (r + 2 * n) + r * inner + inner + inner * n + inner
            + inner * h)


def attention_weight_count(sizes):
    h, d = sizes["hidden_size"], sizes["head_dim"]
    return 2 * h * sizes["num_heads"] * d \
        + 2 * h * sizes["num_key_value_heads"] * d


def mlp_weight_count(sizes):
    """The SwiGLU and the layer's two norm vectors."""
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"] \
        + 2 * sizes["hidden_size"]


def weight_count(sizes):
    """Every parameter; the tied embedding once."""
    n_mamba, n_attn = layer_counts(sizes)
    return n_mamba * (mamba_weight_count(sizes) + mlp_weight_count(sizes)) \
        + n_attn * (attention_weight_count(sizes) + mlp_weight_count(sizes)) \
        + sizes["vocab_size"] * sizes["hidden_size"] + sizes["hidden_size"]


def state_bytes_per_slot(sizes, state_itemsize=4):
    """What a slot keeps whatever its length: every Mamba layer's scan
    state [I, N] and its window of the last K - 1 ``xs``."""
    n_mamba, _ = layer_counts(sizes)
    return n_mamba * _inner(sizes) * (sizes["mamba_d_state"]
                                      + sizes["mamba_d_conv"] - 1) \
        * state_itemsize


def cache_bytes_per_token(sizes, cache_itemsize):
    _, n_attn = layer_counts(sizes)
    return 2 * n_attn * sizes["num_key_value_heads"] * sizes["head_dim"] \
        * cache_itemsize


def decode_step_bytes(sizes, running_slots, valid_tokens, weight_itemsize,
                      cache_itemsize, state_itemsize=4):
    """Bytes one decode step must move: every weight once, the running
    slots' recurrent state read and written, their VALID cached tokens
    read."""
    return weight_count(sizes) * weight_itemsize \
        + 2 * running_slots * state_bytes_per_slot(sizes, state_itemsize) \
        + valid_tokens * cache_bytes_per_token(sizes, cache_itemsize)


def scan_bytes(sizes, positions, itemsize=4):
    """Bytes one layer's prefill scan over ``positions`` positions of one
    sequence must move by its contract: ``xc``, ``delta`` in and ``y`` out
    once, ``B`` and ``C`` once, the state in and out."""
    inner, n = _inner(sizes), sizes["mamba_d_state"]
    return itemsize * (3 * positions * inner + 2 * positions * n
                       + 2 * inner * n)


# ------------------------------------------------------------ reference

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _mlp(lp, x, eps):
    u = _rms(x, lp["pre_ff_layernorm"], eps)
    return x + (jax.nn.silu(u @ lp["gate_proj"]) * (u @ lp["up_proj"])) \
        @ lp["down_proj"]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _attention_layer(lp, x, num_heads, eps):
    lp = _f32(lp)
    b, s, _h = x.shape
    u = _rms(x, lp["input_layernorm"], eps)
    d = lp["q_proj"].shape[1] // num_heads
    q = (u @ lp["q_proj"]).reshape(b, s, num_heads, d)
    k = (u @ lp["k_proj"]).reshape(b, s, -1, d)
    v = (u @ lp["v_proj"]).reshape(b, s, -1, d)
    g = num_heads // k.shape[2]
    q = q.reshape(b, s, k.shape[2], g, d)
    scores = jnp.einsum("bqkgd,bckd->bkgqc", q, k) / math.sqrt(d)
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    out = jnp.einsum("bkgqc,bckd->bqkgd", jax.nn.softmax(scores, -1), v)
    x = x + out.reshape(b, s, num_heads * d) @ lp["o_proj"]
    return _mlp(lp, x, eps)


def mamba_mixer(lp, u, eps, h0=None):
    """The mixer on float32 arrays, the whole sequence from position 0
    (``h0``: a state to start from, [B, N, I]; None: zeros). Returns (out
    [B, S, h], the scan state after the last position [B, N, I])."""
    b, s, _h = u.shape
    k, inner = lp["conv_weight"].shape
    n, r = lp["b_layernorm"].shape[0], lp["dt_layernorm"].shape[0]
    xs, z = jnp.split(u @ lp["in_proj"], 2, axis=-1)
    padded = jnp.pad(xs, ((0, 0), (k - 1, 0), (0, 0)))
    xc = lp["conv_bias"] + sum(lp["conv_weight"][j] * padded[:, j:j + s]
                               for j in range(k))
    xc = jax.nn.silu(xc)
    dr, bm, cm = jnp.split(xc @ lp["x_proj"], [r, r + n], axis=-1)
    dr = _rms(dr, lp["dt_layernorm"], eps)
    bm = _rms(bm, lp["b_layernorm"], eps)
    cm = _rms(cm, lp["c_layernorm"], eps)
    delta = jax.nn.softplus(dr @ lp["dt_proj"] + lp["dt_bias"])
    a = -jnp.exp(lp["A_log"])                               # [N, I]

    def step(h, t):
        xc_t, d_t, b_t, c_t = t                  # [B, I] [B, I] [B, N] [B, N]
        h = jnp.exp(d_t[:, None, :] * a) * h \
            + (d_t * xc_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h0 = jnp.zeros((b, n, inner), jnp.float32) if h0 is None else h0
    time_major = [jnp.swapaxes(t, 0, 1) for t in (xc, delta, bm, cm)]
    h, y = jax.lax.scan(step, h0, time_major)
    y = jnp.swapaxes(y, 0, 1) + lp["D"] * xc
    return (y * jax.nn.silu(z)) @ lp["out_proj"], h


@functools.partial(jax.jit, static_argnums=(2,))
def _mamba_layer(lp, x, eps):
    lp = _f32(lp)
    out, h = mamba_mixer(lp, _rms(x, lp["input_layernorm"], eps), eps)
    return _mlp(lp, x + out, eps), h


def _hidden(params, ids, num_heads, rows, states=None):
    """[B, S] ids -> the final norm's output [B, S, h], float32 on the
    device; the layers one at a time, ``rows`` sequences at a time.
    ``states``: a list that is given every Mamba layer's scan state after
    the last position, [B, N, I] on the host, in the layers' order."""
    eps = params["rms_norm_eps"]
    ids = np.asarray(ids)
    x = jnp.asarray(params["embed_tokens"])[jnp.asarray(ids)] \
        .astype(jnp.float32)
    for lp in params["layers"]:
        on_chip = jax.tree_util.tree_map(jnp.asarray, lp)
        parts, after = [], []
        for at in range(0, x.shape[0], rows):
            part = x[at:at + rows]
            if "q_proj" in lp:
                parts.append(_attention_layer(on_chip, part, num_heads, eps))
            else:
                part, h = _mamba_layer(on_chip, part, eps)
                parts.append(part)
                if states is not None:
                    after.append(np.asarray(h))
        x = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        if after:
            states.append(np.concatenate(after))
    return _rms(x, jnp.asarray(params["final_layernorm"])
                .astype(jnp.float32), eps)


def reference_logits(params, ids, num_heads, rows=4):
    """[B, S] token ids -> [B, S, V] float32 logits. ``params``:
    ``from_serving_state``'s, in any float dtype; everything is cast to
    float32 on the device and every product runs at ``highest`` precision
    (on a TPU a float32 matmul otherwise runs in bf16 passes)."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, ids, num_heads, rows)
        return x @ jnp.asarray(params["embed_tokens"]) \
            .astype(jnp.float32).T


def reference_states(params, ids, num_heads, rows=4):
    """[B, S] token ids -> every Mamba layer's scan state after the S
    positions, a list of [B, N, I] float32 arrays on the host."""
    states = []
    with jax.default_matmul_precision("highest"):
        _hidden(params, ids, num_heads, rows, states)
    return states


@jax.jit
def _top2(x, embed):
    top = jax.lax.top_k(x @ embed.astype(jnp.float32).T, 2)
    return top[1][..., 0], top[0][..., 0] - top[0][..., 1]


def reference_top2(params, ids, num_heads, rows=4):
    """What a replay compares, without the [B, S, V] logits: (argmax
    [B, S] int, top-two margin [B, S] float32), on the host."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, ids, num_heads, rows)
        embed = jnp.asarray(params["embed_tokens"])
        out = [_top2(x[at:at + 1], embed) for at in range(x.shape[0])]
    return (np.concatenate([np.asarray(a) for a, _m in out]),
            np.concatenate([np.asarray(m) for _a, m in out]))


_MAMBA = ("in_proj", "conv_weight", "conv_bias", "x_proj", "dt_layernorm",
          "b_layernorm", "c_layernorm", "dt_proj", "dt_bias", "A_log", "D",
          "out_proj")
_ATTENTION = ("q_proj", "k_proj", "v_proj", "o_proj")
_BOTH = ("input_layernorm", "pre_ff_layernorm", "gate_proj", "up_proj",
         "down_proj")


def from_serving_state(state, num_layers, rms_norm_eps=1e-6):
    """``JambaForCausalLM.state_dict()`` (name -> array) -> the reference's
    parameters: the leaves as numpy arrays ON THE HOST, in the dtype they
    have. A layer's kind is read from the names it holds."""
    def get(name):
        a = state[name]
        return np.asarray(a.numpy() if hasattr(a, "numpy") else a)

    layers = []
    for i in range(num_layers):
        mixer = _MAMBA if f"layers.{i}.in_proj" in state else _ATTENTION
        layers.append({k: get(f"layers.{i}.{k}") for k in mixer + _BOTH})
    return {"embed_tokens": get("embed_tokens"),
            "final_layernorm": get("final_layernorm"), "layers": layers,
            "rms_norm_eps": float(rms_norm_eps)}
