"""LFM2-MoE decoder LM on the training path (``model_type: lfm2_moe``;
LFM2-24B-A2B, https://huggingface.co/LiquidAI/LFM2-24B-A2B).

Layer ``i``: ``x += Op_i(RMSNorm(x))``, ``x += FF_i(RMSNorm(x))``. ``Op_i``
by ``layer_types[i]``: ``conv``, a double-gated depthwise causal convolution
of ``conv_L_cache`` taps (``[B, C, z] = u W_in``; ``c = conv(B * z)``;
``(C * c) W_out``), or ``full_attention``, grouped-query attention with an
RMSNorm over each head of q and k, half-split RoPE and a causal mask.
``FF_i``: a dense SwiGLU in the first ``num_dense_layers`` layers, and after
them ``num_experts`` SwiGLU experts, ``num_experts_per_tok`` a token, chosen
by a sigmoid router with a per-expert bias that is used for the choice only
and has no gradient: after a step each bias moves by
``EXPERT_BIAS_UPDATE_RATE`` towards the mean load (the auxiliary-loss-free
rule; the public config says only that the bias is used). DROPLESS, no
auxiliary loss. Tied embedding.

The expert layer is ``sdar_moe.expert_ffn``: told which experts it holds
(``expert_offset``, ``num_local_experts``), it routes over all of them and
computes its own experts' part of the result. Attention goes through
``gpt_hybrid._attend``'s kernel chain, K and V repeated to the query heads.

Pure functions over a parameter dict, and ``setup`` through
``gpt_hybrid``'s engine (its mesh, AdamW, step builder and loss head): this
module brings ``TRAIN_MODEL`` and no optimizer of its own. One chip.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models import gpt_hybrid as gh
from paddle_tpu.models.llama import rope
from paddle_tpu.models.sdar_moe import _rms_norm, expert_ffn
from paddle_tpu.observability import metrics as _met

#: what the public config does not give, one value each (PERF.md, PR 34):
#: AdamW's rate after a linear warm-up from 0 over ``LR_WARMUP_STEPS``
#: steps. From a constant 3e-4 at the first step AdamW's sign-sized updates
#: turn the router away from the experts held within ten steps where a chip
#: holds a share of them: what the absent experts would add is left out, so
#: routing to them is a way around the layer
LEARNING_RATE = 3e-4
LR_WARMUP_STEPS = 2000
#: the bias rule's step: the one that held the load on the chip through 200
#: steps of 16,384 tokens (1e-3 lost it to a few experts after a hundred)
EXPERT_BIAS_UPDATE_RATE = 1e-2


@dataclasses.dataclass
class LFM2MoeConfig:
    """The keys of the model's public ``config.json`` (``rope_theta`` out of
    its ``rope_parameters``), and what a holder of a share of it needs
    besides. The stack is the first ``num_layers`` entries of
    ``layer_types``."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: tuple = None        # None: attention where i % 4 == 2
    num_dense_layers: int = 2
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    model_type: str = "lfm2_moe"
    # not in config.json
    num_layers: int = None           # None: all of layer_types
    num_local_experts: int = None    # the experts held here (None: all)
    expert_offset: int = 0
    initializer_range: float = 0.02  # as the sibling models' configs

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                "full_attention" if i % 4 == 2 else "conv"
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if self.num_layers is None:
            self.num_layers = len(self.layer_types)
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts - self.expert_offset
        bad = [t for t in self.layer_types
               if t not in ("conv", "full_attention")]
        if bad or self.conv_bias or not self.use_expert_bias \
                or self.num_layers > len(self.layer_types):
            raise ValueError("LFM2MoeConfig: not supported here: "
                             f"layer_types {bad}, conv_bias "
                             f"{self.conv_bias}, use_expert_bias "
                             f"{self.use_expert_bias}, num_layers "
                             f"{self.num_layers}")
        if not 0 <= self.expert_offset <= self.expert_offset \
                + self.num_local_experts <= self.num_experts:
            raise ValueError("experts held must lie within num_experts")

    num_heads = property(lambda self: self.num_attention_heads)
    head_dim = property(
        lambda self: self.hidden_size // self.num_attention_heads)

    def kinds(self):
        """[(operator, feed-forward)] of the stack: (``conv`` |
        ``full_attention``, ``dense`` | ``experts``)."""
        return [(op, "dense" if i < self.num_dense_layers else "experts")
                for i, op in enumerate(self.layer_types[:self.num_layers])]

    @property
    def num_expert_layers(self):
        return sum(ff == "experts" for _op, ff in self.kinds())

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                    moe_intermediate_size=16, num_hidden_layers=4,
                    num_attention_heads=4, num_key_value_heads=2,
                    layer_types=("conv", "full_attention", "conv", "conv"),
                    num_dense_layers=1, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128)
        base.update(kw)
        return LFM2MoeConfig(**base)


# ---------------------------------------------------------------- layers

@jax.named_scope("short_conv")
def short_conv(u, p):
    """u [b, s, H] -> [b, s, H]. ``p["conv"]`` [taps, H]: tap j multiplies
    the gated input ``taps - 1 - j`` positions back, zeros before the
    sequence. Three shifted multiply-adds that XLA fuses."""
    s = u.shape[1]
    gate_b, gate_c, z = jnp.split(
        checkpoint_name(jnp.dot(u, p["in_proj"]), "in_proj"), 3, axis=-1)
    g = (gate_b * z).astype(jnp.float32)
    w = p["conv"].astype(jnp.float32)
    taps = w.shape[0]
    c = sum(w[j] * jnp.pad(g, ((0, 0), (taps - 1 - j, 0), (0, 0)))[:, :s]
            for j in range(taps))
    return jnp.dot(gate_c * c.astype(u.dtype), p["out_proj"])


@jax.named_scope("attention_operator")
def attention(u, p, cfg, mesh):
    """u [b, s, H] -> [b, s, H]: query head h attends over KV head
    ``h // (heads / kv heads)``."""
    b, s, _ = u.shape
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    q = jnp.dot(u, p["q_proj"]).reshape(b, s, nh, d)
    k = jnp.dot(u, p["k_proj"]).reshape(b, s, nkv, d)
    v = jnp.dot(u, p["v_proj"]).reshape(b, s, nkv, d)
    start = jnp.zeros((b,), jnp.int32)
    q = rope(_rms_norm(q, p["q_norm"], cfg.norm_eps), start, cfg.rope_theta,
             half_split=True)
    k = rope(_rms_norm(k, p["k_norm"], cfg.norm_eps), start, cfg.rope_theta,
             half_split=True)
    q, k, v = (checkpoint_name(a, "qkv") for a in (q, k, v))
    # no training kernel takes groups: K and V at the query heads' count
    k, v = (jnp.repeat(a, nh // nkv, axis=2).reshape(b, s, nh * d)
            for a in (k, v))
    out = checkpoint_name(
        gh._attend(q.reshape(b, s, nh * d), k, v, nh, mesh), "attn_out")
    return jnp.dot(out, p["o_proj"])


@jax.named_scope("dense_mlp")
def dense_mlp(u, p):
    gate = checkpoint_name(jnp.dot(u, p["w1"]), "ffn1")
    up = checkpoint_name(jnp.dot(u, p["w3"]), "ffn1")
    return jnp.dot(jax.nn.silu(gate) * up, p["w2"])


@jax.named_scope("moe_router")
def route(h, w_router, bias, top_k, norm_topk_prob=True, scaling=1.0):
    """h [T, H] -> (weights [T, k] float32, expert index [T, k] int32):
    sigmoid scores over all experts in float32; the k largest of score +
    bias; the chosen scores themselves, over their sum + 1e-6."""
    scores = jax.nn.sigmoid(
        jnp.dot(h, w_router, preferred_element_type=jnp.float32))
    _, index = lax.top_k(scores + bias, top_k)
    # the choice is kept for the backward pass, never made again: a
    # recomputed score may round otherwise, and a token whose k-th and next
    # score tie within that rounding would be differentiated along another
    # choice than the forward pass took (PERF.md, PR 34)
    index = checkpoint_name(index.astype(jnp.int32), "routing")
    weights = jnp.take_along_axis(scores, index, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    return weights * scaling, index


def moe_ffn(u, p, bias, cfg):
    """-> (the held experts' part of the layer's output [b, s, H], the
    routing: each token's experts [b, s, k] int32 over ALL the experts)."""
    b, s, hid = u.shape
    h = u.reshape(b * s, hid)
    weights, index = route(h, p["router"], bias, cfg.num_experts_per_tok,
                           cfg.norm_topk_prob, cfg.routed_scaling_factor)
    y = expert_ffn(h, weights, index, p["gate_up"], p["down"],
                   cfg.expert_offset, cfg.num_experts)
    return y.reshape(b, s, hid), index.reshape(b, s, -1)


def expert_load(routing, num_experts):
    """Routing [..., b, s, k] -> the pairs routed to each expert [...,
    num_experts] int32."""
    return jnp.sum(routing[..., None] == jnp.arange(num_experts),
                   axis=(-4, -3, -2), dtype=jnp.int32)


def _layer(x, p, bias, *, kind, cfg, mesh):
    """One layer; ``bias``: the layer's expert bias or None. -> (x, the
    layer's routing or None)."""
    op, ff = kind
    u = _rms_norm(x, p["operator_norm"], cfg.norm_eps)
    x = x + (short_conv(u, p) if op == "conv"
             else attention(u, p, cfg, mesh))
    u = _rms_norm(x, p["ffn_norm"], cfg.norm_eps)
    if ff == "dense":
        return x + dense_mlp(u, p), None
    y, routing = moe_ffn(u, p, bias, cfg)
    return x + y, routing


def _remat(fn, pcfg):
    """``jax.checkpoint`` a layer as the engine's ``_stack_apply`` does its
    block; whatever the policy, the routing is saved (``route``)."""
    if not pcfg.remat:
        return fn
    policies = jax.checkpoint_policies
    keep = policies.save_only_these_names(
        "routing", *(pcfg.remat_save_names
                     if pcfg.remat_policy == "names" else ()))
    if pcfg.remat_policy == "dots":
        keep = policies.save_from_both_policies(policies.dots_saveable, keep)
    return jax.checkpoint(fn, policy=keep)


# ----------------------------------------------------------------- model

#: leaves the optimizer does not see: the routers' biases (the model's own
#: rule moves them), the assignments summed over the steps, and each step's
#: busiest expert's assignments summed over the steps
FROZEN = ("expert_bias", "expert_load", "expert_peak")


def init_params(cfg: LFM2MoeConfig, pcfg, key):
    """Normal(0, ``initializer_range``) matrices, norms 1, biases 0, in
    ``pcfg.param_dtype``; ``expert_bias`` float32 and ``expert_load`` int32
    [expert layers, num_experts], ``expert_peak`` int32 [expert layers].
    The two counts are int32 and wrap in silence: an expert that took every
    one of a step's 65,536 pairs would wrap its sum after 32,768 steps, an
    even load after 2 million; ``count_expert_load`` reads them, and a run
    longer than that zeroes them when it does."""
    h, d, dt = cfg.hidden_size, cfg.head_dim, pcfg.param_dtype
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    inter, e = cfg.moe_intermediate_size, cfg.num_local_experts
    shapes = {
        "conv": {"in_proj": (h, 3 * h), "conv": (cfg.conv_L_cache, h),
                 "out_proj": (h, h)},
        "full_attention": {"q_proj": (h, nh * d), "k_proj": (h, nkv * d),
                           "v_proj": (h, nkv * d), "o_proj": (nh * d, h)},
        "dense": {"w1": (h, cfg.intermediate_size),
                  "w3": (h, cfg.intermediate_size),
                  "w2": (cfg.intermediate_size, h)},
        "experts": {"router": (h, cfg.num_experts),
                    "gate_up": (e, h, 2 * inter), "down": (e, inter, h)},
    }
    keys = iter(jax.random.split(key, 8 * cfg.num_layers + 1))

    def layer(op, ff):
        p = {"operator_norm": jnp.ones((h,), dt),
             "ffn_norm": jnp.ones((h,), dt)}
        if op == "full_attention":
            p.update(q_norm=jnp.ones((d,), dt), k_norm=jnp.ones((d,), dt))
        for name, shape in {**shapes[op], **shapes[ff]}.items():
            p[name] = gh._init(next(keys), shape, cfg.initializer_range, dt)
        return p

    stats = (cfg.num_expert_layers, cfg.num_experts)
    return {"embed": gh._init(next(keys), (cfg.vocab_size, h),
                              cfg.initializer_range, dt),
            "layers": [layer(op, ff) for op, ff in cfg.kinds()],
            "final_norm": jnp.ones((h,), dt),
            "expert_bias": jnp.zeros(stats, jnp.float32),
            "expert_load": jnp.zeros(stats, jnp.int32),
            "expert_peak": jnp.zeros(stats[:1], jnp.int32)}


def param_specs(params):
    """Every leaf whole on every device: one chip is what this module
    honours."""
    return jax.tree_util.tree_map(lambda _: P(), params)


def shard_params(params, mesh, cfg, pcfg):
    specs = param_specs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs), specs


def forward_hidden(params, input_ids, cfg: LFM2MoeConfig, pcfg, mesh):
    """-> (the final norm's output [b, s, H], the routing [expert layers,
    b, s, k] int32). A Python loop over the layers, each its own
    ``jax.checkpoint``."""
    cdt = pcfg.compute_dtype
    x = params["embed"][input_ids].astype(cdt)
    routing = []
    for kind, p in zip(cfg.kinds(), params["layers"]):
        bias = params["expert_bias"][len(routing)] \
            if kind[1] == "experts" else None
        fn = _remat(functools.partial(_layer, kind=kind, cfg=cfg, mesh=mesh),
                    pcfg)
        x, chosen = fn(x, jax.tree_util.tree_map(lambda a: a.astype(cdt), p),
                       bias)
        if chosen is not None:
            routing.append(chosen)
    routing = jnp.stack(routing) if routing else jnp.zeros(
        (0, *input_ids.shape, cfg.num_experts_per_tok), jnp.int32)
    return (_rms_norm(x, params["final_norm"].astype(cdt), cfg.norm_eps),
            routing)


def forward(params, input_ids, cfg, pcfg, mesh):
    """[b, s] ids -> [b, s, V] logits (tied head)."""
    x, _routing = forward_hidden(params, input_ids, cfg, pcfg, mesh)
    return jnp.einsum("bsh,vh->bsv", x,
                      params["embed"].astype(pcfg.compute_dtype))


def loss_and_routing(params, batch, cfg, pcfg, mesh):
    """(next-token mean CE through the engine's loss head, the batch's
    routing): what the engine's step differentiates."""
    input_ids, labels = batch
    x, routing = forward_hidden(params, input_ids, cfg, pcfg, mesh)
    return gh._ce_from_hidden(x, params["embed"], labels, pcfg), routing


def loss_fn(params, batch, cfg, pcfg, mesh):
    return loss_and_routing(params, batch, cfg, pcfg, mesh)[0]


@jax.named_scope("expert_bias_update")
def update_routing(frozen, routing, rate=EXPERT_BIAS_UPDATE_RATE):
    """After a step, per expert layer: ``b_e += rate * sign(mean load -
    load_e)`` over ALL the experts, the load being the step's pairs an
    expert; the step's load, and its busiest expert's, are added to the
    running sums."""
    load = expert_load(routing, frozen["expert_bias"].shape[-1])
    n = load.astype(jnp.float32)
    step = rate * jnp.sign(jnp.mean(n, -1, keepdims=True) - n)
    return {"expert_bias": frozen["expert_bias"] + step,
            "expert_load": frozen["expert_load"] + load,
            "expert_peak": frozen["expert_peak"] + jnp.max(load, -1)}


#: this model as the engine takes one
TRAIN_MODEL = gh.TrainModel(init_params, shard_params, loss_and_routing,
                            frozen=FROZEN, update_frozen=update_routing)


def learning_rate(step):
    """The schedule ``setup`` hands the engine: linear over
    ``LR_WARMUP_STEPS`` steps to ``LEARNING_RATE``, then constant."""
    return LEARNING_RATE * jnp.minimum(
        1.0, step.astype(jnp.float32) / LR_WARMUP_STEPS)


def setup(cfg: LFM2MoeConfig, pcfg, seed=0, devices=None):
    """(mesh, params, opt_state, step) through ``gpt_hybrid.setup``;
    ``step(params, opt_state, (ids, labels))`` as the GPT's. Raises by name
    for a ``ParallelConfig`` this module cannot honour."""
    asked = {"dp": pcfg.dp > 1, "tp": pcfg.tp > 1, "pp": pcfg.pp > 1,
             "sp": pcfg.sp, "num_experts": pcfg.num_experts > 0,
             "collective_matmul": pcfg.collective_matmul,
             "gradient_merge_steps": pcfg.gradient_merge_steps > 1}
    bad = [name for name, on in asked.items() if on]
    if bad:
        raise ValueError(
            f"lfm2_moe trains on one chip (dp = tp = pp = 1; its experts "
            f"are told by LFM2MoeConfig, not by ParallelConfig): {bad} "
            "not honoured")
    return gh.setup(cfg, pcfg, seed=seed, devices=devices,
                    model=TRAIN_MODEL, lr=learning_rate)


def count_expert_load(params, cfg: LFM2MoeConfig):
    """Fetches the state's running counts once and ticks the ``moe.*``
    counters by them: every pair, each layer's busiest expert's of each
    step, the pairs routed to the experts held here, the expert layers.
    -> the assignments [expert layers, num_experts] int64."""
    load, peak = (a.astype("int64") for a in jax.device_get(
        (params["expert_load"], params["expert_peak"])))
    if _met._ENABLED and load.size:
        r = _met.REGISTRY
        held = slice(cfg.expert_offset,
                     cfg.expert_offset + cfg.num_local_experts)
        r.counter("moe.assignments").inc(int(load.sum()))
        r.counter("moe.busiest_expert_assignments").inc(int(peak.sum()))
        r.counter("moe.held_assignments").inc(int(load[:, held].sum()))
        r.counter("moe.layer_passes").inc(int(load.shape[0]))
    return load
