"""SDAR-MoE (``model_type: sdar_moe``: a Qwen3-MoE-shaped decoder that
generates by diffusion over blocks): what the mathematics requires, and a
plain reference.

Sizes come from the configuration file's top-level keys, named as in the
model's public ``config.json``: ``num_hidden_layers`` L, ``hidden_size`` h,
``num_attention_heads`` x ``head_dim``, ``num_key_value_heads``,
``num_experts`` E of width ``moe_intermediate_size`` i,
``num_experts_per_tok`` k, ``vocab_size`` V (untied head).

* ``forward_bytes`` — bytes one forward pass over ``lane_tokens`` new
  positions must read: attention and router weights, the experts TOUCHED
  (their expected number under even routing), the head if the pass samples,
  the embedding rows, and the VALID cached tokens at the cache's dtype.
* ``forward_flops`` — operations of the same pass, routed products only.
* ``reference_logits`` / ``reference_forward`` — the forward pass in plain
  float32 ``jax.numpy`` at ``highest`` precision: no cache, no sorting
  (every expert over every token, masked to the routed pairs), the block
  mask over the whole sequence. ``reference_generate`` — the published
  generation loop (SDAR's ``block_diffusion_generate``) on top of it.
* ``reference_passes`` / ``pass_stats`` — many passes over blocks of one
  finished sequence through the same layer function, the sequence's own
  float32 keys and values standing for what each block sees before it:
  what the replay of a deep model at long contexts can afford.

Departures from the published procedure, made in the system and here
alike: the mask token's logit is held at -inf when sampling (with random
weights it would otherwise be drawn now and then and reopen a position);
open-ness is a boolean kept beside the ids, not read back from them; a
pass transfers open positions only (where fewer are open than the
schedule's count, as in a first block that holds prompt tokens, a top-k
over the confidences would reach a closed position); confidences that tie
go to the lower position.
"""
import collections.abc
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def _dims(c):
    return (c["num_hidden_layers"], c["hidden_size"],
            c["num_attention_heads"] * c["head_dim"],
            c["num_key_value_heads"] * c["head_dim"], c["num_experts"],
            c["moe_intermediate_size"], c["num_experts_per_tok"],
            c["vocab_size"])


def attention_weight_count(c):
    """One layer: q, k, v, o and the four norm vectors."""
    _L, h, q, kv, _E, _i, _k, _V = _dims(c)
    return h * q + 2 * h * kv + q * h + 2 * h + 2 * c["head_dim"]


def expert_weight_count(c):
    """One expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def experts_touched(c, tokens):
    """Expected number of one layer's experts that ``tokens`` tokens reach
    when every token picks its k distinct experts evenly: E (1 - (1 -
    k/E)^tokens). Skewed routing touches fewer: this is the most a pass of
    that many tokens has a reason to read."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    return E * (1.0 - (1.0 - k / E) ** tokens)


def cache_bytes_per_token(c, cache_itemsize):
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] \
        * c["head_dim"] * cache_itemsize


def forward_bytes(c, lane_tokens, valid_tokens, weight_itemsize,
                  cache_itemsize, head=True, touched=None):
    """``lane_tokens``: positions the pass computes (slots x block, or a
    prefill's length); ``valid_tokens``: cached positions it attends to,
    summed over its lanes; ``head``: whether the pass's logits are used (a
    commit pass writes the cache and samples nothing); ``touched``: the
    experts of a layer the pass reached where the program counted them,
    else their expected number under even routing."""
    L, h, _q, _kv, E, _i, _k, V = _dims(c)
    if touched is None:
        touched = experts_touched(c, lane_tokens)
    per_layer = attention_weight_count(c) + h * E \
        + touched * expert_weight_count(c)
    weights = L * per_layer + lane_tokens * h + h + (h * V if head else 0)
    return weights * weight_itemsize \
        + valid_tokens * cache_bytes_per_token(c, cache_itemsize)


def forward_flops(c, lane_tokens, valid_tokens, head=True):
    """Multiply-adds count two; expert products for the routed pairs only
    (k experts a token, three matrices an expert); attention over the
    valid cached tokens plus the pass's own positions."""
    L, h, q, kv, E, i, k, V = _dims(c)
    per_token = 2 * h * (2 * q + 2 * kv) + 2 * h * E + k * 6 * h * i
    attend = 4 * q * (valid_tokens + lane_tokens)
    return L * (lane_tokens * per_token + attend) \
        + (2 * h * V * lane_tokens if head else 0)


# ------------------------------------------------------------ reference

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def _rope(x, pos, theta):
    """x [B, S, H, D] at the absolute positions ``pos`` [B, S]:
    x cos + rotate_half(x) sin."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, :, None, :]
    return x * jnp.cos(ang) + _rotate_half(x) * jnp.sin(ang)


def static_config(c):
    """The numbers the reference needs, hashable."""
    return (c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_experts_per_tok"],
            bool(c["norm_topk_prob"]), float(c["rms_norm_eps"]),
            float(c["rope_theta"]), int(c["mask_token_id"]))


def _f32(a):
    return a.astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(7,))
def _layer(lp, x, pos, own_mask, ctx_k, ctx_v, ctx_mask, cfg):
    """One layer in float32, whatever the leaves' dtype (an expert's
    weights are cast where they are used, so the stack is never held
    twice). x [b, s, h] at positions ``pos`` [b, s] attends to the
    context's keys and values (``ctx_k``/``ctx_v`` [b, c, kv heads, d]
    where ``ctx_mask`` [b, s, c] says so; c may be 0) and to its own s
    positions where ``own_mask`` [b, s, s] says so. Returns (x, its own
    keys, values [b, s, kv heads, d], router margin [b, s]: the relative
    gap between the k-th and (k+1)-th router probability)."""
    nh, nkv, d, top_k, norm_topk, eps, theta, _mask_id = cfg
    b, s, _ = x.shape
    h = _rms(x, _f32(lp["input_layernorm"]), eps)
    q = _rms((h @ _f32(lp["q_proj"])).reshape(b, s, nh, d),
             _f32(lp["q_norm"]), eps)
    k = _rms((h @ _f32(lp["k_proj"])).reshape(b, s, nkv, d),
             _f32(lp["k_norm"]), eps)
    v = (h @ _f32(lp["v_proj"])).reshape(b, s, nkv, d)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    keys = jnp.repeat(jnp.concatenate([ctx_k, k], 1), nh // nkv, axis=2)
    vals = jnp.repeat(jnp.concatenate([ctx_v, v], 1), nh // nkv, axis=2)
    sees = jnp.concatenate([ctx_mask, own_mask], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, keys) / math.sqrt(d)
    scores = jnp.where(sees[:, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), vals)
    x = x + attn.reshape(b, s, nh * d) @ _f32(lp["o_proj"])
    h = _rms(x, _f32(lp["post_attention_layernorm"]), eps)
    probs = jax.nn.softmax(h @ _f32(lp["router"]), -1)           # [b, s, E]
    top, _ = jax.lax.top_k(probs, top_k + 1)
    margin = (top[..., -2] - top[..., -1]) / top[..., -2]
    chosen = jnp.where(probs >= top[..., -2:-1], probs, 0.0)
    if norm_topk:
        chosen = chosen / jnp.sum(chosen, -1, keepdims=True)

    def one_expert(y, w):
        gate, up, down, share = w            # share [b, s]: 0 if not routed
        out = (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)
        return y + share[..., None] * out, None
    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["gate_proj"], lp["up_proj"], lp["down_proj"],
         jnp.moveaxis(chosen, -1, 0)))
    return x + y, k, v, margin


@jax.jit
def _embed(table, ids):
    return _f32(table[ids])


@functools.partial(jax.jit, static_argnums=(3,))
def _head(norm, lm_head, x, eps):
    return _rms(x, _f32(norm), eps) @ _f32(lm_head)


def reference_forward(params, ids, open_mask, block, cfg):
    """[B, S] ids (positions from 0; open positions are shown as the mask
    token whatever ``ids`` holds there) -> (logits [B, S, V] float32,
    router margin [B, S]: over the layers the least relative gap between
    the k-th and (k+1)-th router probability, (p_k - p_k+1) / p_k).
    ``cfg``: the static numbers, ``static_config(config)``."""
    ids = np.where(open_mask, cfg[-1], ids)
    b, s = ids.shape
    pos = np.broadcast_to(np.arange(s), (b, s))
    sees = (pos[:, None, :] // block) <= (pos[:, :, None] // block)
    none = jnp.zeros((b, 0, cfg[1], cfg[2]), jnp.float32)
    margin = jnp.full((b, s), jnp.inf)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], ids)
        for lp in params["layers"]:      # one layer's parameters at a time
            x, _k, _v, m = _layer(lp, x, pos, sees, none, none,
                                  np.zeros((b, s, 0), bool), cfg)
            margin = jnp.minimum(margin, m)
        return _head(params["norm"], params["lm_head"], x, cfg[5]), margin


def reference_logits(params, ids, open_mask, block, cfg):
    return reference_forward(params, ids, open_mask, block, cfg)[0]


def reference_passes(params, seqs, starts, opens, block, cfg):
    """Many passes over blocks of the same sequences for the price of one
    forward each. ``seqs`` [R, S]: finished sequences, nothing open;
    row n of sequence r is a pass over the block at ``starts[r, n]`` (a
    multiple of ``block``) whose positions ``opens[r, n]`` [block] were
    open. By the block mask a position's state depends on no later block,
    so what the row's block sees of the positions before it is what the
    whole sequence's forward computes there: the sequences go through
    ``_layer`` once, layer by layer, and beside them the rows' blocks,
    which attend to the sequence's keys and values below their start and
    to themselves. Held to ``reference_forward`` of each row's own whole
    sequence by ``tests/test_blocks_cell_cpu.py``. Returns (hidden
    states after the last layer [R, N, block, h], router margin [R, N,
    block]); ``pass_stats`` reads them."""
    seqs, starts, opens = map(np.asarray, (seqs, starts, opens))
    r, s = seqs.shape
    n = starts.shape[1]
    pos_seq = np.arange(s)[None]
    sees_seq = (pos_seq[:, None, :] // block) <= (pos_seq[:, :, None] // block)
    pos_row = (starts[..., None] + np.arange(block)).reshape(r, 1, n * block)
    row_of = np.repeat(np.arange(n), block)
    sees_row = (row_of[None, :] == row_of[:, None])[None]
    sees_ctx = np.arange(s)[None, None, None, :] \
        < np.repeat(starts, block, axis=1)[:, None, :, None]
    ids_row = np.where(opens.reshape(r, 1, n * block), cfg[-1],
                       np.take_along_axis(seqs[:, None], pos_row, axis=2))
    none = jnp.zeros((1, 0, cfg[1], cfg[2]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        x_seq = [_embed(params["embed"], seqs[i:i + 1]) for i in range(r)]
        x_row = [_embed(params["embed"], ids_row[i]) for i in range(r)]
        margin = [jnp.full((1, n * block), jnp.inf)] * r
        for lp in params["layers"]:      # one layer's parameters at a time
            for i in range(r):           # and one sequence's scores
                x_seq[i], k, v, _m = _layer(
                    lp, x_seq[i], pos_seq, sees_seq, none, none,
                    np.zeros((1, s, 0), bool), cfg)
                x_row[i], _k, _v, m = _layer(
                    lp, x_row[i], pos_row[i], sees_row, k, v, sees_ctx[i],
                    cfg)
                margin[i] = jnp.minimum(margin[i], m)
    return jnp.concatenate(x_row).reshape(r, n, block, -1), \
        np.asarray(jnp.concatenate(margin)).reshape(r, n, block)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _pass_stats(norm, lm_head, x, eps, mask_id):
    logits = _rms(x, _f32(norm), eps) @ _f32(lm_head)
    logits = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                       logits)
    top2, index = jax.lax.top_k(logits, 2)
    conf = 1.0 / jnp.sum(jnp.exp(logits - top2[..., :1]), -1)
    return index[..., 0], top2[..., 0] - top2[..., 1], conf


def pass_stats(params, hidden, cfg):
    """What a pass decides from, by position, the mask token's logit at
    -inf: (the argmax token, the top-two logit margin, the confidence:
    the softmax at the argmax). ``hidden`` [R, ..., h]: states after the
    last layer; the logits, made one sequence's rows at a time, are never
    brought to the host."""
    with jax.default_matmul_precision("highest"):
        rows = [_pass_stats(params["norm"], params["lm_head"], x, cfg[5],
                            cfg[-1]) for x in hidden]
    return tuple(np.stack([np.asarray(a) for a in col])
                 for col in zip(*rows))


def num_transfer_tokens(block, steps):
    """SDAR's ``get_num_transfer_tokens``: how many positions pass t
    fixes."""
    base, rem = divmod(block, steps)
    return [base + (1 if t < rem else 0) for t in range(steps)]


def choose_transfer(conf, open_now, count, remasking, threshold):
    """One lane, one pass: which open positions are fixed. ``conf``: the
    confidences [B]. Static: the ``count`` most confident open positions;
    dynamic: every open position over ``threshold`` where those are at
    least ``count``, else the static choice. Ties go to the lower
    position; never a closed position."""
    conf = np.where(open_now, conf, -np.inf)
    order = np.argsort(-conf, kind="stable")
    static = np.zeros_like(open_now)
    static[order[:count]] = True
    static &= open_now
    if remasking == "low_confidence_dynamic":
        high = open_now & (conf > threshold)
        if high.sum() >= count:
            return high
    return static


def reference_generate(params, prompt, new_tokens, block, steps, cfg,
                       remasking="low_confidence_static", threshold=0.9):
    """The published b=1 loop, greedy, for one prompt (1-D ids): blocks
    aligned at multiples of ``block``; the prompt's whole blocks are
    context, its remainder opens the first block already fixed; each block
    takes up to ``steps`` passes of the WHOLE sequence so far through
    ``reference_logits``. Returns (tokens [new_tokens], commit_steps
    [new_tokens]: the pass that fixed each)."""
    prompt = np.asarray(prompt)
    plen, mask_id = len(prompt), cfg[-1]
    total = -(-(plen + new_tokens) // block) * block
    ids = np.full((total,), mask_id, np.int64)
    ids[:plen] = prompt
    is_open = np.arange(total) >= plen
    fixed_at = np.full((total,), -1)
    counts = num_transfer_tokens(block, steps)
    for start in range(plen // block * block, total, block):
        sl = slice(start, start + block)
        for t in range(steps):
            if not is_open[sl].any():
                break
            logits = np.array(reference_logits(
                params, ids[None, :start + block],
                is_open[None, :start + block], block, cfg)[0, sl])
            logits[:, mask_id] = -np.inf
            x0 = logits.argmax(-1)
            z = logits - logits.max(-1, keepdims=True)
            conf = 1.0 / np.exp(z).sum(-1)         # softmax at the argmax
            move = choose_transfer(conf, is_open[sl], counts[t], remasking,
                                   threshold)
            ids[sl] = np.where(move, x0, ids[sl])
            fixed_at[sl] = np.where(move, t, fixed_at[sl])
            is_open[sl] &= ~move
    return ids[plen:plen + new_tokens], fixed_at[plen:plen + new_tokens]


class _Layers(collections.abc.Sequence):
    """Layer i's parameters, made when asked for: gate and up are cut out
    of the model's one stack, a copy, and a deep model's copies would not
    all fit beside it."""

    def __init__(self, state, num_layers):
        self.state, self.num_layers = state, num_layers

    def __len__(self):
        return self.num_layers

    def __getitem__(self, i):
        if not 0 <= i < self.num_layers:
            raise IndexError(i)
        names = ("input_layernorm", "q_proj", "k_proj", "v_proj", "q_norm",
                 "k_norm", "o_proj", "post_attention_layernorm", "router",
                 "down_proj")
        out = {n: _array(self.state[f"layers.{i}.{n}"]) for n in names}
        inter = out["down_proj"].shape[-2]
        gate_up = _array(self.state[f"layers.{i}.gate_up_proj"])
        out["gate_proj"] = gate_up[..., :inter]
        out["up_proj"] = gate_up[..., inter:]
        return out


def _array(a):
    return jnp.asarray(getattr(a, "_data", a))


def from_serving_state(state, num_layers):
    """``SDARMoeForCausalLM.state_dict()`` (name -> array) -> the
    reference's parameters in the model's own dtype (the reference
    computes in float32 whatever they are), gate and up apart; the layers
    a sequence of dicts made one at a time."""
    return {"embed": _array(state["embed_tokens"]),
            "layers": _Layers(state, num_layers),
            "norm": _array(state["norm"]),
            "lm_head": _array(state["lm_head"])}
