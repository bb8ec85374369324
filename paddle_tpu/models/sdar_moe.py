"""SDAR-MoE decoder LM (``model_type: sdar_moe``; SDAR-30B-A3B-Chat,
https://huggingface.co/JetLM/SDAR-30B-A3B-Chat): a Qwen3-MoE-shaped decoder
that generates by diffusion over blocks.

Every layer: RMSNorm, GQA attention with RMSNorm over each head of q and k
(QK-norm) and half-split RoPE, under the BLOCK mask (position i sees j iff
``j // B <= i // B``: a block of B positions sees itself in both directions
and everything before it), then RMSNorm and a mixture of ``num_experts``
SwiGLU experts without biases: softmax over all experts in float32, the
``num_experts_per_tok`` largest, renormalised, DROPLESS (no capacity, no
auxiliary loss: this is the serving layer).

The expert layer holds its experts stacked and is told which it holds
(``expert_offset``, ``num_local_experts``): it routes over all of them and
computes its own experts' part of the result, which is what expert
parallelism asks of it; on one chip that holds all of them it is the whole
layer. Only routed (token, expert) pairs are multiplied: the pairs are
sorted by expert and go through a grouped product (``_grouped``), never
the ``[E, T, H]`` dispatch of ``models/moe.py``.

Serving contract (``ContinuousBatchingSession(generation=
"block_diffusion")``): ``init_cache``, ``forward_with_cache``,
``block_length``, ``mask_token_id``, and for the expert load
``EXPERT_LOAD_LEN``, ``forward_with_expert_load``, ``count_expert_load``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.dispatch import run_op
from paddle_tpu.models.llama import rope
from paddle_tpu.nn import initializer as I
from paddle_tpu.observability import metrics as _met


@dataclasses.dataclass
class SDARMoeConfig:
    """The keys of the model's public ``config.json``, and what a holder of
    a share of it needs besides. Keys the layer equations do not read are
    kept so that the file can be passed whole; ``__post_init__`` refuses
    the values this implementation does not compute."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 6144        # dense width; no layer is dense
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    attention_bias: bool = False
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    rope_scaling: object = None
    sliding_window: object = None
    use_sliding_window: bool = False
    max_window_layers: int = 48
    model_type: str = "sdar_moe"
    # not in config.json: the release's generation defaults
    block_length: int = 4
    mask_token_id: int = 151669
    # the experts held here, of num_experts (None: all of them)
    num_local_experts: int = None
    expert_offset: int = 0
    param_dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.num_local_experts is None:
            self.num_local_experts = self.num_experts - self.expert_offset
        unsupported = {
            "attention_bias": self.attention_bias,
            "mlp_only_layers": bool(self.mlp_only_layers),
            "rope_scaling": self.rope_scaling is not None,
            "use_sliding_window": self.use_sliding_window,
            "tie_word_embeddings": self.tie_word_embeddings,
            "decoder_sparse_step": self.decoder_sparse_step != 1,
            "hidden_act": self.hidden_act != "silu"}
        bad = [k for k, v in unsupported.items() if v]
        if bad:
            raise ValueError(f"SDARMoeConfig: not supported here: {bad}")
        if not 0 <= self.expert_offset <= self.expert_offset \
                + self.num_local_experts <= self.num_experts:
            raise ValueError("experts held must lie within num_experts")

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=160, hidden_size=32, moe_intermediate_size=16,
                    num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=8, num_experts=8,
                    num_experts_per_tok=2, max_position_embeddings=128,
                    mask_token_id=159)
        base.update(kw)
        return SDARMoeConfig(**base)


# ---------------------------------------------------------------- arrays

def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("moe_router")
def route(h, w_router, top_k, norm_topk_prob):
    """h [T, H] -> (weights [T, k] float32, expert index [T, k] int32):
    softmax over all experts in float32, the k largest, renormalised."""
    logits = jnp.dot(h, w_router, preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, index = lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    return weights, index.astype(jnp.int32)


def _kernel_backend():
    """Where the grouped product goes through the Pallas kernel."""
    return jax.default_backend() == "tpu"


def _gmm_tiling(k, n):
    """(rows, whole contraction, columns): each grid step streams one
    expert's [k, tn] weight tile, tn the widest divisor of n that is a
    multiple of 128 and keeps the tile within 2 MB (measured on the v5e,
    PERF.md, PR 28: 640-680 GB/s of expert weights at 1024 rows, against
    65-90 GB/s at the library's default of 128 cubed)."""
    tn = max((c for c in range(128, n + 1, 128)
              if n % c == 0 and k * c * 2 <= 2 ** 21), default=n)
    return (128, k, tn)


def _widest(n, most):
    """The widest divisor of n that is a multiple of 128 and at most
    ``most`` (n itself where there is none)."""
    return max((c for c in range(128, n + 1, 128)
                if n % c == 0 and c <= most), default=n)


def _row_tile(m):
    """256 rows where the (padded) row count divides, else the 128 it is
    padded to."""
    return 256 if m % 256 == 0 else 128


def _gmm_dx_tiling(m, k, n, itemsize=2):
    """Tiles of the rows' cotangent, [m, k] x [held, n, k]^T: 256 rows, the
    whole contraction, a weight tile within 3 MB. Measured on the v5e at
    the train cell's shapes (65,536 rows in 64 groups, 16 held, bf16;
    PERF.md, PR 34; even split / a skewed one): [., 3072] -> 2048 at (256,
    3072, 512) 2.25 / 2.36 ms, (128, 3072, 256) 2.71 / 2.76, (512, 1536,
    1024) 2.04 / 2.56; [., 2048] -> 1536 at (256, 2048, 768) 1.38 / 1.47,
    (128, 2048, 512) 1.50 / 1.56. Rows of 512 win on an even split and
    lose on a skewed one, which is what routing gives. Operands wider than
    bf16 (``itemsize``) take proportionally narrower tiles."""
    return (_row_tile(m), k, _widest(n, 3 * 2 ** 20 // (itemsize * k)))


def _gmm_dw_tiling(m, k, n, itemsize=2):
    """Tiles (rows, k, n) of the weights' cotangent, [k, m] x [m, n] ->
    [held, k, n]: 256 rows a step into a [tk, tn] float32 accumulator of up
    to 1024 x 1024. Same measurement: [2048, .] x [., 3072] at (256, 1024,
    1024) 1.52 / 1.80 ms, (128, 512, 1024) 2.07 / 2.30, (512, 1024, 1024)
    1.38 / 1.92; [1536, .] x [., 2048] at (256, 768, 1024) 0.87 / 1.00,
    (128, 512, 1024) 1.10 / 1.22, (512, 768, 1024) 0.79 / 1.06. The
    forward's tiles stay PR 28's: at these shapes the forward's time is
    its passes over the [65536, .] output, and no tile moved it by more
    than 6 % (4.17 ms at (128, 2048, 512), 3.93 at the best)."""
    return (_row_tile(m), _widest(k, 2048 // itemsize),
            _widest(n, 2048 // itemsize))


def _count_dispatch(which):
    """Ticked where a backward pass is built (``_gmm_fwd``, ``_gmm_bwd``):
    a forward-only trace (serving) moves no ``moe.*`` name unless its
    model counts the expert load, and ``ragged_dot`` differentiates
    itself, out of this module's sight."""
    if _met._ENABLED:
        _met.REGISTRY.counter("moe.grouped_dispatch", kernel="gmm",
                              **{"pass": which}).inc()


def _pad_rows(x):
    """Rows up to the kernels' row tile of 128."""
    return jnp.pad(x, ((0, -x.shape[0] % 128), (0, 0)))


def _gmm_forward(lhs, rhs, group_sizes, out_dtype, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    m, k = lhs.shape
    return gmm(_pad_rows(lhs), rhs, group_sizes,
               preferred_element_type=out_dtype,
               tiling=_gmm_tiling(k, rhs.shape[2]), interpret=interpret)[:m]


#: the kernel's product [m, k] x [held, k, n] -> [m, n] (float32
#: accumulation, stored as ``out_dtype``: a float32 [65536, 3072] result
#: that is rounded afterwards costs a pass over 805 MB) with a backward
#: pass (the raw kernel has no differentiation rule)
_gmm = jax.custom_vjp(_gmm_forward, nondiff_argnums=(3, 4))


def _gmm_fwd(lhs, rhs, group_sizes, out_dtype, interpret):
    _count_dispatch("fwd")
    return (_gmm_forward(lhs, rhs, group_sizes, out_dtype, interpret),
            (lhs, rhs, group_sizes))


def _gmm_bwd(out_dtype, interpret, residuals, g):
    """Cotangent of the rows: the same grouped product against the held
    weights transposed; of the weights: the transposed grouped product
    (``tgmm``) of rows and cotangent over the same groups, the held ones
    only. Both accumulate in float32 and come out in their primal's dtype;
    rows of absent groups receive zero (the kernel does not visit them and
    zeroes what it left) and contribute to no weight."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    lhs, rhs, group_sizes = residuals
    m, k = lhs.shape
    n = rhs.shape[2]
    with jax.named_scope("moe_experts_bwd"):
        _count_dispatch("dx")
        _count_dispatch("dw")
        g = _pad_rows(g.astype(lhs.dtype))
        d_lhs = gmm(g, rhs, group_sizes, preferred_element_type=lhs.dtype,
                    tiling=_gmm_dx_tiling(g.shape[0], n, k, g.dtype.itemsize),
                    transpose_rhs=True,
                    interpret=interpret)[:m]
        d_rhs = tgmm(_pad_rows(lhs).swapaxes(0, 1), g, group_sizes,
                     preferred_element_type=rhs.dtype,
                     tiling=_gmm_dw_tiling(g.shape[0], k, n,
                                           g.dtype.itemsize),
                     num_actual_groups=rhs.shape[0], interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped(lhs, rhs, group_sizes, out_dtype, interpret=False):
    """Rows of ``lhs`` [m, k] in consecutive groups, group g of
    ``group_sizes[g]`` rows times ``rhs[g]`` [k, n]; ``rhs`` holds the
    first groups only, and the rows of the others come out as zeros.

    On a TPU the megablox grouped matmul (a Pallas kernel that visits
    only the (row tile, group) pairs that exist, so each touched expert's
    weights are streamed about once), differentiable through ``_gmm_bwd``;
    elsewhere ``jax.lax.ragged_dot`` and its own differentiation, which on
    the v5e read the same weights at less than half the rate (PERF.md,
    PR 28)."""
    held = rhs.shape[0]
    if not (_kernel_backend() or interpret):
        out = lax.ragged_dot(lhs, rhs, group_sizes[:held],
                             preferred_element_type=jnp.float32)
    else:
        out = _gmm(lhs, rhs, group_sizes, out_dtype, interpret)
    if held < group_sizes.shape[0]:
        rows = jnp.arange(lhs.shape[0])[:, None]
        out = jnp.where(rows < jnp.sum(group_sizes[:held]), out, 0.0)
    return out.astype(out_dtype)


#: the rows a holder of a share of the experts works on at a time, over the
#: held experts' even share of the (token, expert) pairs (rounded up to the
#: kernels' row tile of 256). The sort puts the held experts' pairs first, and
#: the layer walks them this many rows a trip, so every [T*k, .] array of the
#: layer is [rows, .]; a step whose routing gives the held experts more pairs
#: takes another trip, so no pair is dropped and no capacity enters the
#: mathematics: the slack sets how often a step takes two trips, never a
#: result. 1.5: the train cell's loop holds a run's held share to 23-28 % of
#: the pairs where even is 25 %, and 37.5 % fits; one expert's 1.22 x of its
#: even load is one expert's, not sixteen together (PERF.md, PR 37)
_HELD_ROWS_SLACK = 1.5


def _held_rows(pairs, held, num_experts):
    even = pairs * held / num_experts
    return min(pairs, -(-int(even * _HELD_ROWS_SLACK) // 256) * 256)


def _count_row_buffer():
    """Ticked where an ``expert_ffn`` that holds a share of the experts and
    walks its rows a buffer at a time is traced."""
    if _met._ENABLED:
        _met.REGISTRY.counter("moe.row_buffer", rows="held").inc()


def _sort_pairs(index, expert_offset, num_experts):
    """Routing [T, k] -> (the pairs in the order of their experts, the held
    experts first so that their rows lead it; pairs an expert, in that
    order)."""
    label = (index.reshape(-1) - expert_offset) % num_experts
    return (jnp.argsort(label, stable=True),
            jnp.zeros((num_experts,), jnp.int32).at[label].add(1))


def _ffn_rows(m, first, order, group_sizes, h, weights, w_gate_up, w_down):
    """The layer's part from the ``m`` rows of the sorted order that start
    at ``first`` (0 where ``m`` is all T*k) -> [T, H]: in ``h``'s dtype
    where every expert is held, else the float32 sum the caller rounds."""
    t, k = weights.shape
    held, inter = w_down.shape[:2]
    every_pair_has_a_row = held == group_sizes.shape[0]
    if m == t * k:
        pair = order
    else:
        pair = lax.dynamic_slice(jnp.pad(order, (0, -(t * k) % m)),
                                 (first,), (m,))
        # sizes that are true of these rows: what of each held group lies
        # among them, and the rest as one more group without weights, as an
        # absent expert's rows are
        end = jnp.cumsum(group_sizes[:held])
        inside = jnp.clip(jnp.minimum(end, first + m) - jnp.maximum(
            end - group_sizes[:held], first), 0)
        group_sizes = jnp.append(inside, m - jnp.sum(inside))
    token = pair // k
    rows = h[token]                                         # [m, H]
    gate_up = _grouped(rows, w_gate_up, group_sizes, h.dtype)
    act = jax.nn.silu(gate_up[:, :inter]) * gate_up[:, inter:]
    out = _grouped(act, w_down, group_sizes, jnp.float32)
    out = out * weights.reshape(-1)[pair][:, None]
    if every_pair_has_a_row:
        back = jnp.argsort(order)                           # pair -> row
        return jnp.sum(out[back].reshape(t, k, -1), axis=1).astype(h.dtype)
    # rows of no held expert are zeros and add nothing
    return jnp.zeros((t, out.shape[1]), jnp.float32).at[token].add(out)


def _over_held_rows(rows, group_sizes, held, trip, start):
    """``trip(first, carry)`` for ``first`` = 0, ``rows``, ... while held
    pairs lie at or past it: one trip in a step whose held experts drew no
    more than ``rows`` pairs."""
    n_held = jnp.sum(group_sizes[:held])
    return lax.while_loop(
        lambda c: c[0] < n_held,
        lambda c: (c[0] + rows, trip(*c)), (jnp.int32(0), start))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ffn_held_rows(rows, order, group_sizes, *operands):
    """``_ffn_rows`` over the held experts' pairs, ``rows`` of them a trip
    -> [T, H] float32. A loop and not a ``cond`` between this buffer and all
    T*k rows: both forms of a ``cond`` are traced and compiled (the train
    cell's set-up 71 -> 93 s warm), and differentiated through it keeps
    both forms' residuals, the idle form's as zeros (4.97 GB of temporaries
    a layer where its own rule took 2.09; PERF.md, PR 37). So the rule is
    its own: the residuals are the arguments, and the backward pass walks
    the same trips, each running its forward pass again as the layer's
    ``jax.checkpoint`` does."""
    h, w_down = operands[0], operands[-1]
    return _over_held_rows(
        rows, group_sizes, w_down.shape[0],
        lambda first, y: y + _ffn_rows(rows, first, order, group_sizes,
                                       *operands),
        jnp.zeros((h.shape[0], w_down.shape[2]), jnp.float32))


def _ffn_held_rows_fwd(rows, *args):
    return _ffn_held_rows(rows, *args), args


def _ffn_held_rows_bwd(rows, args, g):
    order, group_sizes, *operands = args

    def trip(first, grads):
        # checkpointed, so that the forward pass is run again inside the
        # backward one under its own names: traced by ``jax.vjp`` itself its
        # kernels are ``jvp(jit(gmm))`` to XLA, and a reader of the device
        # trace that knows ``gmm`` and ``tgmm`` would miss them
        part = jax.vjp(jax.checkpoint(functools.partial(
            _ffn_rows, rows, first, order, group_sizes)), *operands)[1](g)
        return jax.tree_util.tree_map(jnp.add, grads, part)
    return (None, None, *_over_held_rows(
        rows, group_sizes, operands[-1].shape[0], trip,
        tuple(jnp.zeros_like(x) for x in operands)))


_ffn_held_rows.defvjp(_ffn_held_rows_fwd, _ffn_held_rows_bwd)


@jax.named_scope("moe_experts")
def expert_ffn(h, weights, index, w_gate_up, w_down, expert_offset,
               num_experts):
    """The held experts' part of ``sum_e p_e * (silu(h Wg_e) * (h Wu_e))
    Wd_e``. h [T, H]; weights/index [T, k] over ALL experts; w_gate_up
    [E_local, H, 2I] (gate columns first), w_down [E_local, I, H], the
    experts ``expert_offset .. expert_offset + E_local``. Dropless: every
    pair routed to a held expert is computed, whatever the load. A holder
    of a share of the experts walks its pairs ``_held_rows`` rows a trip:
    one trip, unless the routing gives its experts more."""
    t, k = index.shape
    order, group_sizes = _sort_pairs(index, expert_offset, num_experts)
    rows = _held_rows(t * k, w_down.shape[0], num_experts)
    if rows == t * k:
        return _ffn_rows(rows, 0, order, group_sizes, h, weights, w_gate_up,
                         w_down).astype(h.dtype)
    _count_row_buffer()
    return _ffn_held_rows(rows, order, group_sizes, h, weights, w_gate_up,
                          w_down).astype(h.dtype)


def decoder_layer(cfg, x, p, cache=None):
    """One layer on raw arrays. x [B, S, H]; p: the layer's parameters by
    name; cache: None (the S positions are the whole sequence, from 0) or
    (kbuf, vbuf, lens). Returns (x, cache', expert index [B, S, k])."""
    from paddle_tpu.inference.decode import block_attention
    b, s, hid = x.shape
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    eps, blk = cfg.rms_norm_eps, cfg.block_length
    h = _rms_norm(x, p["input_layernorm"], eps)
    q = jnp.dot(h, p["q_proj"]).reshape(b, s, nh, d)
    k = jnp.dot(h, p["k_proj"]).reshape(b, s, nkv, d)
    v = jnp.dot(h, p["v_proj"]).reshape(b, s, nkv, d)
    q = _rms_norm(q, p["q_norm"], eps)
    k = _rms_norm(k, p["k_norm"], eps)
    lens = jnp.zeros((b,), jnp.int32) if cache is None else cache[2]
    q = rope(q, lens, cfg.rope_theta, half_split=True)
    k = rope(k, lens, cfg.rope_theta, half_split=True)
    attn, cache = block_attention(q, k, v, blk, cache)
    x = x + jnp.dot(attn.reshape(b, s, nh * d), p["o_proj"])
    h = _rms_norm(x, p["post_attention_layernorm"], eps).reshape(b * s, hid)
    weights, index = route(h, p["router"], cfg.num_experts_per_tok,
                           cfg.norm_topk_prob)
    y = expert_ffn(h, weights, index, p["gate_up_proj"], p["down_proj"],
                   cfg.expert_offset, cfg.num_experts)
    return x + y.reshape(b, s, hid), cache, index.reshape(b, s, -1)


# ---------------------------------------------------------------- layers

_LAYER_PARAMS = ("input_layernorm", "q_proj", "k_proj", "v_proj", "q_norm",
                 "k_norm", "o_proj", "post_attention_layernorm", "router",
                 "gate_up_proj", "down_proj")


class SDARMoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: SDARMoeConfig):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
        e, inter = cfg.num_local_experts, cfg.moe_intermediate_size
        # drawn leaf by leaf in param_dtype: the whole model in float32
        # would not fit the chip before a cast
        shapes = {"q_proj": (h, nh * d), "k_proj": (h, nkv * d),
                  "v_proj": (h, nkv * d), "o_proj": (nh * d, h),
                  "router": (h, cfg.num_experts),
                  "gate_up_proj": (e, h, 2 * inter),
                  "down_proj": (e, inter, h)}
        norms = {"input_layernorm": h, "post_attention_layernorm": h,
                 "q_norm": d, "k_norm": d}
        for name in _LAYER_PARAMS:
            if name in norms:
                param = self.create_parameter(
                    (norms[name],), dtype=cfg.param_dtype,
                    default_initializer=I.Constant(1.0))
            else:
                param = self.create_parameter(
                    shapes[name], dtype=cfg.param_dtype,
                    default_initializer=I.Normal(0.0, cfg.initializer_range))
            setattr(self, name, param)

    def forward(self, x, cache=None):
        """cache: None or a StaticCache. Returns (x, cache', index)."""
        from paddle_tpu.inference.decode import StaticCache, check_capacity
        if cache is not None:
            check_capacity(cache.length, x.shape[1], cache.k.shape[1])
        held = tuple(cache or ())

        def f(x, *rest):
            out, new, index = decoder_layer(
                self.cfg, x, dict(zip(_LAYER_PARAMS, rest[len(held):])),
                rest[:len(held)] or None)
            return (out, *(new or ()), index)
        out, *new, index = run_op(
            "sdar_moe_layer", f, x, *held,
            *(getattr(self, n) for n in _LAYER_PARAMS),
            n_outputs=2 + len(held), differentiable=False)
        return out, StaticCache(*new) if new else None, index


class SDARMoeForCausalLM(nn.Layer):
    """Inference only: the block-diffusion training objective needs a
    noise schedule that the public config does not give."""

    def __init__(self, cfg: SDARMoeConfig):
        super().__init__()
        self.cfg = cfg
        std = I.Normal(0.0, cfg.initializer_range)
        self.embed_tokens = self.create_parameter(
            (cfg.vocab_size, cfg.hidden_size), dtype=cfg.param_dtype,
            default_initializer=std)
        self.layers = nn.LayerList([SDARMoeDecoderLayer(cfg)
                                    for _ in range(cfg.num_hidden_layers)])
        self.norm = self.create_parameter(
            (cfg.hidden_size,), dtype=cfg.param_dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_size), dtype=cfg.param_dtype,
            default_initializer=std)

    block_length = property(lambda self: self.cfg.block_length)
    mask_token_id = property(lambda self: self.cfg.mask_token_id)

    def _run(self, input_ids, caches):
        """-> (logits, caches', the routing: one [B, S, k] int32 array a
        layer, indices over all ``num_experts``)."""
        x = run_op("sdar_embed", lambda w, i: w[i], self.embed_tokens,
                   input_ids, differentiable=False)
        new_caches, routing = [], []
        for i, layer in enumerate(self.layers):
            x, cache, index = layer(x, None if caches is None else caches[i])
            new_caches.append(cache)
            routing.append(index._data)
        eps = self.cfg.rms_norm_eps
        logits = run_op(
            "sdar_head", lambda x, g, w: jnp.dot(_rms_norm(x, g, eps), w),
            x, self.norm, self.lm_head, differentiable=False)
        return logits, new_caches, routing

    @paddle.no_grad()
    def forward(self, input_ids):
        """[B, S] ids -> [B, S, V] logits of the whole sequence from
        position 0, under the block mask."""
        return self._run(input_ids, None)[0]

    def init_cache(self, batch_size, max_length):
        from paddle_tpu.inference.decode import init_static_cache
        return [init_static_cache(batch_size, max_length,
                                  self.cfg.num_key_value_heads,
                                  self.cfg.head_dim)
                for _ in range(self.cfg.num_hidden_layers)]

    def forward_with_cache(self, input_ids, caches):
        """The sessions' contract: (ids [B, s], caches) -> (logits,
        caches), the s positions following each sequence's cached length,
        under the block mask."""
        return self._run(input_ids, caches)[:2]

    #: what ``forward_with_expert_load`` sums: (token, expert) pairs routed
    #: in the active lanes; the busiest expert's pairs; expert layers run;
    #: experts any lane reached (what the pass had to read)
    EXPERT_LOAD_LEN = 4

    def forward_with_expert_load(self, input_ids, caches, active):
        """``forward_with_cache`` and, third, the pass's expert load as
        ``EXPERT_LOAD_LEN`` int32 sums over the layers, reduced where the
        routing is (``active`` [B]: the lanes whose pairs count). A session
        adds the vectors of a dispatch's passes and hands the sum to
        ``count_expert_load`` when it has fetched it."""
        logits, caches, routing = self._run(input_ids, caches)
        experts = jnp.arange(self.cfg.num_experts)
        total = busiest = touched = jnp.int32(0)
        for index in routing:
            hit = index[..., None] == experts
            touched += jnp.sum(jnp.any(hit, axis=(0, 1, 2)),
                               dtype=jnp.int32)
            per_expert = jnp.sum(hit & active[:, None, None, None],
                                 axis=(0, 1, 2), dtype=jnp.int32)
            total += jnp.sum(per_expert)
            busiest += jnp.max(per_expert)
        return logits, caches, jnp.stack(
            [total, busiest, jnp.int32(len(routing)), touched])

    @staticmethod
    def count_expert_load(load):
        """Ticks the ``moe.*`` counters by a fetched sum of load vectors."""
        if _met._ENABLED:
            r = _met.REGISTRY
            r.counter("moe.assignments").inc(int(load[0]))
            r.counter("moe.busiest_expert_assignments").inc(int(load[1]))
            r.counter("moe.layer_passes").inc(int(load[2]))
            r.counter("moe.experts_touched").inc(int(load[3]))
