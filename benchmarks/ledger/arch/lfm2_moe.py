"""LFM2-MoE (``model_type: lfm2_moe``: gated short convolutions beside
grouped-query attention, a leading dense SwiGLU layer, then sparse SwiGLU
experts chosen by a sigmoid router with a per-expert bias; tied embedding):
what the mathematics requires, and a plain reference. Nothing here imports
the program's models.

Sizes come from the configuration file's ``sizes`` (the keyword arguments
of ``LFM2MoeConfig``): the stack is the first ``num_layers`` entries of
``layer_types`` (``conv`` | ``full_attention``), its first
``num_dense_layers`` layers have the dense SwiGLU of ``intermediate_size``,
the others ``num_experts`` experts of ``moe_intermediate_size``,
``num_experts_per_tok`` a token, of which ``num_local_experts`` from
``expert_offset`` on are held here; ``vocab_size`` as held.

* ``weight_count`` — parameters held (the tied embedding once; the router's
  bias counts).
* ``train_flops_per_token`` — operations the forward and backward passes
  require a trained token: multiply-add = 2, backward twice the forward,
  causal attention once, no recomputation; the routed experts' products at
  the EXPECTED held share (``num_local_experts / num_experts`` of the
  ``num_experts_per_tok`` pairs a token); the router and the convolution
  counted.
* ``grouped_flops`` — the six grouped products (forward, rows' cotangent,
  weights' cotangent, of gate|up and of down) for a given count of pairs
  routed to held experts.
* ``reference_logits`` / ``reference_loss_and_grads`` — the layer equations
  in float32 ``jax.numpy`` at ``highest`` precision on
  ``lfm2_moe.init_params``' layout: the convolution as shifted products,
  attention a head at a time (8192 x 8192 float32 scores are 268 MB a head),
  the expert layer as a loop over the held experts, each over every token
  and masked to the tokens that chose it: no sort, no grouped product, no
  kernel. What the absent experts would add is left out, as in the program.
  The gradients may be taken along another's routing (the program's), and
  come with the reference's own choice and its margin, so that a comparison
  can tell a rounding's flip from a wrong rule. Each sequence, each layer,
  each head and each expert is its own ``jax.checkpoint``, so that the
  backward pass of two sequences of 8,192 fits beside the weights; the
  values are the same.
  Sizes beyond ``num_heads`` are read from the tree's shapes and keys; what
  no shape gives (``HYPER``) is the published config's unless passed.
"""
import functools

import jax
import jax.numpy as jnp

#: the published ``config.json``'s values of what the tree's shapes do not
#: give; ``expert_offset`` 0 is the share the benchmark's cell holds
HYPER = {"num_experts_per_tok": 4, "norm_eps": 1e-5, "rope_theta": 1e6,
         "norm_topk_prob": True, "routed_scaling_factor": 1.0,
         "expert_offset": 0}

#: leaves of the parameter tree that are no weights: the routers' biases
#: (no gradient; the choice reads them) and the running counts
NOT_TRAINED = ("expert_bias", "expert_load", "expert_peak")


# ------------------------------------------------------------------ counts

def _kinds(sizes):
    types = sizes["layer_types"][:sizes.get("num_layers",
                                            len(sizes["layer_types"]))]
    return [(op, "dense" if i < sizes["num_dense_layers"] else "experts")
            for i, op in enumerate(types)]


def _held(sizes):
    held = sizes.get("num_local_experts")
    return sizes["num_experts"] if held is None else held


def _head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def operator_weight_count(sizes, op):
    h, d = sizes["hidden_size"], _head_dim(sizes)
    if op == "conv":
        return h * 3 * h + h * sizes.get("conv_L_cache", 3) + h * h
    q, kv = sizes["num_attention_heads"] * d, \
        sizes["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + 2 * d


def ff_weight_count(sizes, ff):
    h = sizes["hidden_size"]
    if ff == "dense":
        return 3 * h * sizes["intermediate_size"]
    return h * sizes["num_experts"] + sizes["num_experts"] \
        + _held(sizes) * 3 * h * sizes["moe_intermediate_size"]


def weight_count(sizes):
    h = sizes["hidden_size"]
    return sum(operator_weight_count(sizes, op) + ff_weight_count(sizes, ff)
               + 2 * h for op, ff in _kinds(sizes)) \
        + sizes["vocab_size"] * h + h


def forward_flops_per_token(sizes, seq):
    h, d = sizes["hidden_size"], _head_dim(sizes)
    q, kv = sizes["num_attention_heads"] * d, \
        sizes["num_key_value_heads"] * d
    taps = sizes.get("conv_L_cache", 3)
    share = sizes["num_experts_per_tok"] * _held(sizes) \
        / sizes["num_experts"]
    per = {
        # in_proj, out_proj, the taps, the two gates
        "conv": 2 * h * 3 * h + 2 * h * h + 2 * taps * h + 2 * h,
        # q, o, k, v; scores and their product with v over (seq+1)/2 keys
        "full_attention": 4 * h * q + 4 * h * kv + 4 * q * (seq + 1) / 2,
        "dense": 6 * h * sizes["intermediate_size"],
        "experts": 2 * h * sizes["num_experts"]
        + share * 6 * h * sizes["moe_intermediate_size"],
    }
    return sum(per[op] + per[ff] for op, ff in _kinds(sizes)) \
        + 2 * h * sizes["vocab_size"]


def train_flops_per_token(sizes, seq):
    return 3 * forward_flops_per_token(sizes, seq)


def grouped_flops(sizes, pairs_held):
    """Forward and the two backward products of the two grouped matmuls of
    one expert layer, for ``pairs_held`` (token, expert) rows."""
    return 3 * pairs_held * 6 * sizes["hidden_size"] \
        * sizes["moe_intermediate_size"]


# --------------------------------------------------------------- reference

def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(a, theta):
    """a [S, heads, d], positions 0..S-1, the halves of d rotated against
    each other."""
    s, _heads, d = a.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    freqs = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(freqs)[:, None], jnp.sin(freqs)[:, None]
    x1, x2 = a[..., :d // 2], a[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _short_conv(u, p):
    s = u.shape[0]
    gate_b, gate_c, z = jnp.split(u @ p["in_proj"], 3, axis=-1)
    g = gate_b * z
    taps = p["conv"].shape[0]
    c = sum(p["conv"][j] * jnp.pad(g, ((taps - 1 - j, 0), (0, 0)))[:s]
            for j in range(taps))
    return (gate_c * c) @ p["out_proj"]


def _attention(u, p, num_heads, hyper):
    s = u.shape[0]
    d = p["q_norm"].shape[0]
    eps, theta = hyper["norm_eps"], hyper["rope_theta"]
    q = _rope(_rms_norm((u @ p["q_proj"]).reshape(s, num_heads, d),
                        p["q_norm"], eps), theta)
    k = _rope(_rms_norm((u @ p["k_proj"]).reshape(s, -1, d),
                        p["k_norm"], eps), theta)
    v = (u @ p["v_proj"]).reshape(s, -1, d)
    group = num_heads // k.shape[1]
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def head(qkv):
        q_i, k_i, v_i = qkv
        scores = q_i @ k_i.T / jnp.sqrt(jnp.float32(d))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ v_i

    out = jax.lax.map(head, tuple(
        jnp.repeat(a, r, axis=1).transpose(1, 0, 2)
        for a, r in ((q, 1), (k, group), (v, group))))      # [heads, S, d]
    return out.transpose(1, 0, 2).reshape(s, num_heads * d) @ p["o_proj"]


def _experts(u, p, bias, hyper, forced=None):
    """-> (the held experts' part of the layer's output; the reference's own
    choice [S, k]; its margin [S]: how far the k-th largest of score + bias
    stands over the next). The router, the choice and the normalisation are
    over ALL the experts. ``forced`` [S, k]: another's choice to compute the
    output with (the program's, so that both follow one discrete path); the
    weights are this router's own scores of those experts."""
    k = hyper["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ p["router"])
    ranked, own = jax.lax.top_k(scores + bias, k + 1)
    index = own[:, :k] if forced is None else forced
    weights = jnp.take_along_axis(scores, index, axis=-1)
    if hyper["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    weights = weights * hyper["routed_scaling_factor"]
    inter = p["down"].shape[1]

    @jax.checkpoint
    def expert(y, held):
        w_gate_up, w_down, e = held
        chose = index == e + hyper["expert_offset"]          # [S, k]
        gate_up = u @ w_gate_up
        act = jax.nn.silu(gate_up[:, :inter]) * gate_up[:, inter:]
        return y + jnp.sum(weights * chose, -1, keepdims=True) \
            * (act @ w_down), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(u), (
        p["gate_up"], p["down"], jnp.arange(p["gate_up"].shape[0])))
    return y, own[:, :k], ranked[:, k - 1] - ranked[:, k]


def _layer(x, lp, bias, forced, num_heads, hyper):
    """-> (x after the layer; own choice [S, k] and margin [S], or None
    twice for a dense layer)."""
    eps = hyper["norm_eps"]
    u = _rms_norm(x, lp["operator_norm"], eps)
    x = x + (_short_conv(u, lp) if "in_proj" in lp
             else _attention(u, lp, num_heads, hyper))
    u = _rms_norm(x, lp["ffn_norm"], eps)
    if "w1" in lp:
        y = (jax.nn.silu(u @ lp["w1"]) * (u @ lp["w3"])) @ lp["w2"]
        return x + y, None, None
    y, own, margin = _experts(u, lp, bias, hyper, forced)
    return x + y, own, margin


def _sequence_logits(p, ids, num_heads, hyper, routing=None):
    """One sequence [S] -> ([S, V] logits, own choice [expert layers, S,
    k], margin [expert layers, S]); ``routing`` [expert layers, S, k]: the
    choice to follow."""
    x = p["embed"][ids]
    chosen, margins = [], []
    for lp in p["layers"]:
        i = len(chosen)
        sparse = "router" in lp
        x, own, margin = jax.checkpoint(functools.partial(
            _layer, num_heads=num_heads, hyper=hyper))(
            x, lp, p["expert_bias"][i] if sparse else None,
            routing[i] if sparse and routing is not None else None)
        if sparse:
            chosen.append(own)
            margins.append(margin)
    logits = _rms_norm(x, p["final_norm"], hyper["norm_eps"]) @ p["embed"].T
    return logits, jnp.stack(chosen), jnp.stack(margins)


def _f32(params):
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _logits(params, ids, num_heads, hyper):
    p = _f32(params)
    return jnp.stack([_sequence_logits(p, row, num_heads, dict(hyper))[0]
                      for row in ids])


def _hyper(overrides):
    unknown = set(overrides) - set(HYPER)
    if unknown:
        raise TypeError(f"not a hyper-parameter: {sorted(unknown)}")
    return tuple(sorted({**HYPER, **overrides}.items()))


def reference_logits(params, ids, num_heads, **hyper):
    """[B, S] token ids -> [B, S, V] float32 logits. ``params`` may hold
    any float dtype; everything is cast to float32 and every product runs
    at ``highest`` precision."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, jnp.asarray(ids), num_heads, _hyper(hyper))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _loss_and_grads(params, ids, routing, num_heads, hyper):
    p = _f32(params)
    fixed = {k: p[k] for k in NOT_TRAINED}

    @jax.checkpoint
    def sequence(trained, row, followed):
        logits, own, margin = _sequence_logits(
            {**trained, **fixed}, row, num_heads, dict(hyper), followed)
        logits = logits[:-1]
        picked = jnp.take_along_axis(logits, row[1:, None], -1)[..., 0]
        return (jnp.sum(jax.scipy.special.logsumexp(logits, -1) - picked),
                own, margin)

    def loss(trained):
        if routing is None:
            ce, own, margin = jax.lax.map(
                lambda row: sequence(trained, row, None), ids)
        else:
            ce, own, margin = jax.lax.map(
                lambda rf: sequence(trained, *rf),
                (ids, routing.swapaxes(0, 1)))
        return (jnp.sum(ce) / (ids.shape[0] * (ids.shape[1] - 1)),
                (own.swapaxes(0, 1), margin.swapaxes(0, 1)))

    (value, (own, margin)), grads = jax.value_and_grad(loss, has_aux=True)(
        {k: v for k, v in p.items() if k not in NOT_TRAINED})
    return value, grads, own, margin


def reference_loss_and_grads(params, ids, num_heads, routing=None, **hyper):
    """(next-token mean cross-entropy; its gradient by ``jax.grad`` for
    every trained leaf; the reference's own choice of experts [expert
    layers, B, S, k]; its margin [expert layers, B, S]) in float32 at
    ``highest`` precision. ``routing`` [expert layers, B, S, k]: a choice
    to follow in place of its own (the program's: rounding flips a choice
    whose margin it exceeds, and one flipped token would hide every other
    difference), each layer's own choice then being made on the followed
    path."""
    with jax.default_matmul_precision("highest"):
        return _loss_and_grads(
            params, jnp.asarray(ids),
            None if routing is None else jnp.asarray(routing), num_heads,
            _hyper(hyper))
