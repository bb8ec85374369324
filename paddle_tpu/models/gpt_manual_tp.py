"""Manual-tp stage bodies + vocab-parallel head: zero-bubble x tp>1.

Why this module exists: the compiled zero-bubble schedules (ZBH1,
ZB-V/ZBVPP — parallel/pipeline_1f1b.py) cond-gate their F/B/W phases on
device-varying pipeline-stage predicates. With tp left GSPMD-auto, the
partitioner inserts tp collectives INSIDE those branches with replica
groups of its choosing — which deadlocks the mesh (round-4 finding:
half the devices wait at the in-branch collective, half at the ring
permute). Round 5 established (tests/test_pipeline_zb_tp.py pins it)
that EXPLICIT collectives over a manual 'tp' axis are safe inside
those branches: the predicate varies
only over 'pp', so every member of a tp subgroup takes the same branch
and the collective's participants always rendezvous.

So this module rebuilds the hybrid-GPT stage body in manual-tp form —
Megatron column/row-parallel matmuls with explicit lax.psum, and the
sequence-parallel variant with explicit all_gather/psum_scatter — plus
a Megatron vocab-parallel cross-entropy head, and wires them into the
zero-bubble pipelines via a shard_map manual over BOTH {'pp','tp'}
(dp stays GSPMD-auto: its gradient psum sits outside the gated region).

Reference parity target: the reference's zero-bubble passes schedule
under any hybrid strategy — mp collectives inside a chunk are just ops
the host issues (pipeline_zero_bubble.py:62,:151; VPP/ZB job lists,
pipeline_scheduler_pass/). This gives the compiled schedules the same
composability on the tp axis. The vocab-parallel CE mirrors the
reference's parallel_cross_entropy
(fleet/meta_parallel/parallel_layers/mp_ops.py _c_softmax_with_ce).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map as _shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .gpt import GPTConfig


# ------------------------- block (manual tp) -------------------------

from .gpt_hybrid import _layer_norm as _ln  # single home of the LN math


def block_manual_tp(x, lp, cfg: GPTConfig, pcfg, tp_axis="tp"):
    """One transformer block with EXPLICIT tp collectives.

    Local param shapes (h=hidden, hl=h/tp, m=ffn, ml=m/tp):
      qkv_w [h, 3, hl]  (column-parallel, heads grouped per shard —
                         gpt_hybrid.shard_params stores it [h, 3, h] so
                         the last dim shards per-matrix, not across
                         the q|k|v concat)
      qkv_b [3, hl]     proj_w [hl, h] (row-parallel)   proj_b [h]
      fc1_w [h, ml]     fc1_b [ml]     fc2_w [ml, h]    fc2_b [h]
      ln*_g/b [h]       (replicated)

    Non-sp: x [b, s, h] tp-invarying in, tp-invarying out (the psum
    after each row-parallel matmul strips tp-variance).
    sp: x [b, s/tp, h] tp-varying; all_gather before the column
    matmuls, psum_scatter after the row matmuls (Megatron-LM SP).
    sp + collective_matmul: the gather/matmul and matmul/scatter pairs
    become ring collective matmuls (collective_matmul.sp_*_matmul_local
    — tp is ALREADY manual here, so no nested region and no Shardy
    wall: this is how collective-matmul overlap reaches pp>1, closing
    the round-4 'cm under pp' hole; the GSPMD engines' nested
    formulation stays walled, see gpt_hybrid._use_cm).
    All collectives are explicit and legal inside the zero-bubble
    cond-gated phases (tp-uniform predicates).
    """
    from jax.ad_checkpoint import checkpoint_name
    sp = pcfg.sp
    # ring collective matmuls ONLY on the lockstep 1F1B route: ppermute
    # lowers to ONE collective-permute spanning the whole mesh (the tp
    # pairs of every pp row merged into a single op), so inside a
    # cond-gated zero-bubble phase the idle pp stages never arrive and
    # the op cross-matches or deadlocks (round-5 finding).
    # psum/all_gather/psum_scatter lower to SUBGROUP replica_groups and
    # stay legal.
    cm = bool(pcfg.collective_matmul) and sp \
        and pcfg.pp_schedule == "1f1b"
    nh_local = cfg.num_heads // pcfg.tp

    def gather(h):
        return lax.all_gather(h, tp_axis, axis=1, tiled=True) if sp \
            else h

    def reduce_out(part):
        if sp:
            return lax.psum_scatter(part, tp_axis, scatter_dimension=1,
                                    tiled=True)
        return lax.psum(part, tp_axis)

    from paddle_tpu.models.gpt_hybrid import _attend
    if cm:
        from paddle_tpu.parallel.collective_matmul import (
            sp_column_matmul_local, sp_row_matmul_local)

        def column(hx_local, w):        # [.., sl, K] x [K, Fl] -> [.., s, Fl]
            return sp_column_matmul_local(hx_local, w, tp_axis)

        def row(full, w):               # [.., s, Kl] x [Kl, F] -> [.., sl, F]
            return sp_row_matmul_local(full, w, tp_axis)
    else:
        def column(hx_local, w):
            return gather(hx_local) @ w

        def row(full, w):
            return reduce_out(full @ w)

    h = x.shape[-1]
    hres = x
    hx = _ln(x, lp["ln1_g"], lp["ln1_b"])
    qkv = checkpoint_name(
        column(hx, lp["qkv_w"].reshape(h, -1))
        .reshape(hx.shape[0], -1, 3, lp["qkv_w"].shape[-1])
        + lp["qkv_b"], "qkv")
    attn = checkpoint_name(
        _attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], nh_local),
        "attn_out")
    attn = checkpoint_name(
        row(attn, lp["proj_w"]) + lp["proj_b"], "proj")
    x = hres + attn
    hres = x
    hx = _ln(x, lp["ln2_g"], lp["ln2_b"])
    ff = checkpoint_name(
        row(jax.nn.gelu(checkpoint_name(
            column(hx, lp["fc1_w"]) + lp["fc1_b"], "ffn1")),
            lp["fc2_w"]) + lp["fc2_b"], "ffn2")
    return hres + ff


def _remat_wrap(fn, pcfg):
    """The engine's remat-policy dispatch (gpt_hybrid._stack_apply),
    shared by every manual stage stack. The policies replay the
    explicit collectives in backward — in-branch recompute collectives
    are covered by the same uniform-predicate argument as forward."""
    if not pcfg.remat:
        return fn
    if pcfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable)
    if pcfg.remat_policy == "names":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies
            .save_only_these_names(*pcfg.remat_save_names))
    return jax.checkpoint(fn)


def _require_sequential_cpu_scheduler(what):
    """Fail fast with a diagnosis instead of a 40s rendezvous-timeout
    crash: XLA:CPU's concurrency-optimized thunk scheduler issues
    data-independent manual collectives in divergent per-device orders
    and deadlocks (round-5 finding; TPU executes one uniform program
    order and is unaffected)."""
    import os
    if jax.default_backend() == "cpu" and \
            "xla_cpu_enable_concurrency_optimized_scheduler=false" not \
            in os.environ.get("XLA_FLAGS", ""):
        raise RuntimeError(
            f"{what} on the XLA:CPU backend requires XLA_FLAGS to "
            "include --xla_cpu_enable_concurrency_optimized_scheduler"
            "=false (set before jax initializes); the concurrency-"
            "optimized thunk scheduler deadlocks the manual "
            "collectives' rendezvous")


def stack_apply_manual_tp(blocks, x, cfg, pcfg, tp_axis="tp"):
    """lax.scan over the local layer stack (manual-tp `_stack_apply`)."""
    def body(h, lp):
        fn = _remat_wrap(
            functools.partial(block_manual_tp, cfg=cfg, pcfg=pcfg,
                              tp_axis=tp_axis), pcfg)
        return fn(h, lp), None
    out, _ = lax.scan(body, x, blocks, unroll=max(1, pcfg.scan_unroll))
    return out


# -------------------- vocab-parallel CE (manual) ---------------------

def ce_vocab_parallel(h, wte_local, labels, tp_axis="tp",
                      valid_vocab=None):
    """Next-token CE with the vocab dim sharded over manual `tp_axis`
    (Megatron parallel_cross_entropy; reference mp_ops
    _c_softmax_with_cross_entropy). `h` [b, s, hid] is full-sequence
    (the sp caller gathers first); `wte_local` [Vp/tp, hid] is this
    shard's vocab rows; `labels` [b, s] full. Returns the mean CE over
    the b*(s-1) next-token positions — matching
    gpt_hybrid._ce_from_hidden.

    `valid_vocab`: the TRUE vocab size when the embedding was padded up
    to a multiple of tp (train_grads_zb_manual_tp does this so
    non-divisible vocabs — e.g. GPT-2's 50257 — keep working instead of
    failing at build). Padded rows are masked to -inf, so they carry no
    probability mass and their wte grads are exactly zero."""
    b, s, hid = h.shape
    vl = wte_local.shape[0]
    logits = jnp.einsum("bsh,vh->bsv", h, wte_local.astype(h.dtype))
    logits = logits[:, :-1].astype(jnp.float32)
    if valid_vocab is not None:
        rows = lax.axis_index(tp_axis) * vl + jnp.arange(vl)
        logits = jnp.where((rows < valid_vocab)[None, None],
                           logits, -jnp.inf)
    tgt = labels[:, 1:]
    # numerically stable logsumexp over the sharded vocab: global max
    # as all_gather + max (pmax lacks an AD rule; the shift is
    # stop-gradient anyway — it cancels in the CE gradient)
    mx = lax.stop_gradient(jnp.max(
        lax.all_gather(jnp.max(logits, axis=-1), tp_axis, axis=0,
                       tiled=False), axis=0))
    se = jnp.sum(jnp.exp(logits - mx[..., None]), axis=-1)
    # the correct-class logit lives on exactly one shard
    base = lax.axis_index(tp_axis) * vl
    loc = tgt - base
    in_range = (loc >= 0) & (loc < vl)
    picked_l = jnp.take_along_axis(
        logits, jnp.clip(loc, 0, vl - 1)[..., None], axis=-1)[..., 0]
    picked = lax.psum(jnp.where(in_range, picked_l, 0.0), tp_axis)
    # CE = mean(log(sum_exp_shifted) + mx - picked). The mx term rides
    # through an all_gather, so its TYPE is tp-varying even though its
    # VALUES are tp-identical — and jax has no varying->invarying
    # demotion. Emit it as psum(mean(mx))/tp instead (same value,
    # tp-clean type, stop-gradient so no AD impact); everything else is
    # tp-invarying after its psum.
    loss = jnp.mean(jnp.log(lax.psum(se, tp_axis)) - picked)
    return loss + lax.psum(jnp.mean(mx), tp_axis) / lax.axis_size(
        tp_axis)


# --------------------- train-grads entry point -----------------------

def _manual_blk_flat_specs(moe: bool):
    """Per-layer (no stacking dims) manual partition entries for the
    block tree as gpt_hybrid.shard_params lays it out (qkv_w [h, 3, h],
    qkv_b [3, h]); the leading stacking dims ('pp' + chunk/layer)
    are prepended per-leaf by rank in `_manual_blk_specs`. moe=True is
    the manual-EP layout (tp=1): expert dims shard over 'dp', dense
    weights replicate."""
    if moe:
        return {
            "ln1_g": (None,), "ln1_b": (None,),
            "qkv_w": (None, None, None), "qkv_b": (None, None),
            "proj_w": (None, None), "proj_b": (None,),
            "ln2_g": (None,), "ln2_b": (None,),
            "gate_w": (None, None),
            "fc1_w": ("dp", None, None), "fc1_b": ("dp", None),
            "fc2_w": ("dp", None, None), "fc2_b": ("dp", None),
        }
    return {
        "ln1_g": (None,), "ln1_b": (None,),
        "qkv_w": (None, None, "tp"), "qkv_b": (None, "tp"),
        "proj_w": ("tp", None), "proj_b": (None,),
        "ln2_g": (None,), "ln2_b": (None,),
        "fc1_w": (None, "tp"), "fc1_b": ("tp",),
        "fc2_w": ("tp", None), "fc2_b": (None,),
    }


def _manual_blk_specs(blocks, moe: bool):
    """P('pp', <stacking Nones>, <flat tail>) per leaf — works for the
    linear [pp, Lc, ...], interleaved [pp, v, Lc, ...] and ZB-V
    [pp, 2, Lc, ...] stackings alike (rank-driven)."""
    flat = _manual_blk_flat_specs(moe)
    return {
        k: P("pp",
             *((None,) * (v.ndim - 1 - len(flat[k]))),
             *flat[k])
        for k, v in blocks.items()
    }


def train_grads_zb_manual_tp(params, batch, cfg: GPTConfig, pcfg, mesh):
    """Loss + grads via the compiled zero-bubble pipelines with a
    MANUAL-tp stage body: shard_map over {'pp','tp'} (dp stays auto).
    The tp>1 counterpart of gpt_hybrid._train_grads_1f1b's zbh1/zbvpp
    arms — same embedding-outside / head-as-last-stage-seed structure,
    same return contract."""
    from paddle_tpu.parallel.pipeline import pipeline_microbatch
    from paddle_tpu.parallel.pipeline_1f1b import (
        pipeline_train_1f1b, pipeline_train_zbh1, pipeline_train_zbvpp)
    from paddle_tpu.models.gpt_hybrid import _constrain

    input_ids, labels = batch
    cdt = pcfg.compute_dtype
    b, s = input_ids.shape
    m = pcfg.microbatches
    if pcfg.sp and s % pcfg.tp:
        raise ValueError(f"sp requires seq len {s} % tp {pcfg.tp} == 0")
    if cfg.num_heads % pcfg.tp:
        raise ValueError(
            f"manual-tp stage needs num_heads {cfg.num_heads} % tp "
            f"{pcfg.tp} == 0 (heads are the column-parallel unit)")
    _require_sequential_cpu_scheduler(
        "manual-tp pipeline stage bodies (zero-bubble with tp>1, or "
        "1F1B with collective_matmul at pp>1)")
    if pcfg.fused_ce:
        # the manual head is the (unfused) vocab-parallel CE: the
        # fused chunked LM-head+CE kernel assumes a replicated wte and
        # GSPMD sharding, neither of which holds in the manual region.
        # Warn rather than refuse — fused_ce defaults True and the
        # math is identical; only the [T, V/tp] logits materialization
        # differs.
        import warnings
        warnings.warn(
            "fused_ce is not available on the manual-tp pipeline "
            "route; using the vocab-parallel CE head (identical math, "
            "materializes [tokens, vocab/tp] logits per microbatch)",
            stacklevel=3)

    def embed(wte, wpe):
        return wte[input_ids].astype(cdt) + wpe[:s][None].astype(cdt)

    x, embed_vjp = jax.vjp(embed, params["wte"], params["wpe"])
    x = _constrain(x, P("dp", None, None), mesh)
    mb = pipeline_microbatch(x, m)                    # [m, b/m, s, h]
    lbl_mb = pipeline_microbatch(labels, m)
    blocks = jax.tree_util.tree_map(lambda p: p.astype(cdt),
                                    params["blocks"])
    # non-divisible vocab: pad the head's wte rows up to a multiple of
    # tp (ce_vocab_parallel masks the pad rows to -inf, so they carry
    # no mass and zero grads); the embedding side keeps the true wte.
    # Keeps planner-driven zero_bubble configs runnable for any vocab.
    V = cfg.vocab_size
    vpad = (-V) % pcfg.tp
    wte_head = params["wte"] if vpad == 0 else jnp.pad(
        params["wte"], ((0, vpad), (0, 0)))
    head_params = {"wte": wte_head, "lnf_g": params["lnf_g"],
                   "lnf_b": params["lnf_b"]}

    def stage_fn(stage_params, xm):
        return stack_apply_manual_tp(stage_params, xm, cfg, pcfg)

    def body(blocks, mb, lbl_mb, head_params):
        def last_grad(y, hp, mb_idx):
            lbl = lbl_mb[mb_idx]

            def head_loss(hp_, y_):
                if pcfg.sp:
                    y_ = lax.all_gather(y_, "tp", axis=1, tiled=True)
                hh = _ln(y_, hp_["lnf_g"].astype(cdt),
                         hp_["lnf_b"].astype(cdt))
                return ce_vocab_parallel(
                    hh, hp_["wte"], lbl,
                    valid_vocab=V if vpad else None) / m

            (l, (ghp, gy)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(hp, y)
            return l, gy, ghp

        # serialize_phases: the manual collectives inside the cond-gated
        # phases must issue in one canonical order on every device —
        # see _phase_after (XLA:CPU thunk-executor rendezvous deadlock)
        if pcfg.pp_schedule == "zbvpp":
            return pipeline_train_zbvpp(stage_fn, blocks, mb, last_grad,
                                        head_params=head_params,
                                        serialize_phases=True)
        if pcfg.pp_schedule == "1f1b":
            # lockstep 1F1B with the manual-tp body: no cond-gated
            # phases, so collectives are unconditional and need no
            # serialization — this is the route that gives the ring
            # collective matmuls pp>1 composition
            return pipeline_train_1f1b(stage_fn, blocks, mb, last_grad,
                                       head_params=head_params)
        return pipeline_train_zbh1(stage_fn, blocks, mb, last_grad,
                                   head_params=head_params,
                                   serialize_phases=True)

    blk_specs = _manual_blk_specs(blocks, pcfg.num_experts > 0)
    mb_spec = P(None, None, "tp", None) if pcfg.sp else P(None)
    hp_specs = {"wte": P("tp", None), "lnf_g": P(), "lnf_b": P()}
    dx0_spec = mb_spec
    loss, bgrads, hgrads, dx0 = _shard_map(
        body, mesh=mesh, axis_names={"pp", "tp"},
        in_specs=(blk_specs, mb_spec, P(None), hp_specs),
        out_specs=(P(), blk_specs, hp_specs, dx0_spec))(
            blocks, mb, lbl_mb, head_params)

    dwte_e, dwpe = embed_vjp(dx0.reshape(b, s, -1).astype(x.dtype))
    return loss, {
        "wte": dwte_e.astype(jnp.float32)
        + (hgrads["wte"] if vpad == 0 else hgrads["wte"][:V]),
        "wpe": dwpe.astype(jnp.float32),
        "blocks": bgrads,
        "lnf_g": hgrads["lnf_g"],
        "lnf_b": hgrads["lnf_b"],
    }


# ------------------- manual-ep MoE stage (zb x MoE) -------------------

def moe_ffn_manual_ep(x, lp, num_experts, ep_axis="dp"):
    """GShard switch-MoE with an EXPLICIT all-to-all over the manual
    `ep_axis` (EP=DP) — the in-branch-legal form of gpt_hybrid._moe_ffn
    (probe leg F: all_to_all lowers with subgroup replica_groups, so a
    divergent pipeline predicate cannot strand it, unlike ppermute).

    Local shapes: x [bl, s, h] (this member's batch rows);
    fc1_w [E_local, h, m], fc2_w [E_local, m, h] (experts sharded over
    ep_axis); gate_w [h, E] replicated. Dense dispatch: every member
    routes its tokens to all E experts, the all-to-all exchanges the
    expert dim for the token dim, local experts compute, and the
    reverse all-to-all brings the rows home."""
    bl, s, h = x.shape
    e = num_experts
    tokens = x.reshape(bl * s, h)
    gate_logits = tokens.astype(jnp.float32) @ \
        lp["gate_w"].astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, -1)
    top = jnp.argmax(probs, -1)
    gate = jnp.max(probs, -1).astype(x.dtype)
    disp = jax.nn.one_hot(top, e, dtype=x.dtype)           # [Tl, E]
    xin = jnp.einsum("te,th->eth", disp, tokens)           # [E, Tl, h]
    # exchange: expert shards out, token shards in ->
    # [E_local, Tl * ep, h]
    xin = lax.all_to_all(xin, ep_axis, split_axis=0, concat_axis=1,
                         tiled=True)
    hmid = jax.nn.gelu(
        jnp.einsum("eth,ehm->etm", xin, lp["fc1_w"])
        + lp["fc1_b"][:, None, :])
    hout = jnp.einsum("etm,emh->eth", hmid, lp["fc2_w"]) \
        + lp["fc2_b"][:, None, :]
    # reverse exchange: token shards out, expert shards in -> [E, Tl, h]
    hout = lax.all_to_all(hout, ep_axis, split_axis=1, concat_axis=0,
                          tiled=True)
    combined = jnp.einsum("te,eth->th", disp, hout) * gate[:, None]
    return combined.reshape(bl, s, h)


def block_manual_ep(x, lp, cfg: GPTConfig, pcfg, ep_axis="dp"):
    """Transformer block for the zb x MoE stage: attention is local
    per batch row (tp=1 — _validate_pp_schedule rejects tp>1 with
    MoE), the FFN is the manual-ep MoE."""
    from jax.ad_checkpoint import checkpoint_name
    from paddle_tpu.models.gpt_hybrid import _attend
    hres = x
    hx = _ln(x, lp["ln1_g"], lp["ln1_b"])
    qkv = checkpoint_name(
        jnp.einsum("bsh,hkj->bskj", hx, lp["qkv_w"])
        + lp["qkv_b"], "qkv")
    attn = checkpoint_name(
        _attend(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                cfg.num_heads), "attn_out")
    attn = checkpoint_name(attn @ lp["proj_w"] + lp["proj_b"], "proj")
    x = hres + attn
    hres = x
    hx = _ln(x, lp["ln2_g"], lp["ln2_b"])
    ff = checkpoint_name(
        moe_ffn_manual_ep(hx, lp, pcfg.num_experts, ep_axis), "ffn2")
    return hres + ff


def stack_apply_manual_ep(blocks, x, cfg, pcfg, ep_axis="dp"):
    def body(h, lp):
        fn = _remat_wrap(
            functools.partial(block_manual_ep, cfg=cfg, pcfg=pcfg,
                              ep_axis=ep_axis), pcfg)
        return fn(h, lp), None
    out, _ = lax.scan(body, x, blocks, unroll=max(1, pcfg.scan_unroll))
    return out


def train_grads_zb_manual_ep(params, batch, cfg: GPTConfig, pcfg,
                             mesh):
    """Zero-bubble pipelines with an EP-MoE stage body: shard_map
    manual over {'pp','dp'} — the batch shards over dp, expert weights
    shard their E dim over dp, the GShard all-to-all is explicit (and
    in-branch legal), and the dp grad reduction for replicated params
    falls out of AD's pvary transpose psums (the same mechanism that
    makes the manual-tp body work). tp must be 1."""
    from paddle_tpu.parallel.pipeline import pipeline_microbatch
    from paddle_tpu.parallel.pipeline_1f1b import (
        pipeline_train_zbh1, pipeline_train_zbvpp)
    from paddle_tpu.models.gpt_hybrid import _constrain

    assert pcfg.tp == 1 and pcfg.num_experts > 0 and pcfg.dp > 1
    if pcfg.num_experts % pcfg.dp:
        raise ValueError(
            f"manual-ep stage needs num_experts {pcfg.num_experts} % "
            f"dp {pcfg.dp} == 0 (experts shard over the dp axis)")
    _require_sequential_cpu_scheduler("zero-bubble x MoE")
    if pcfg.fused_ce or pcfg.sp:
        import warnings
        warnings.warn(
            "the manual-ep zero-bubble route supports neither fused_ce "
            "(the head materializes [tokens, vocab] logits per "
            "microbatch) nor sp — both are ignored on this route",
            stacklevel=3)

    input_ids, labels = batch
    cdt = pcfg.compute_dtype
    b, s = input_ids.shape
    m = pcfg.microbatches
    if b % m or (b // m) % pcfg.dp:
        raise ValueError(
            f"manual-ep needs batch {b} divisible by microbatches {m} "
            f"and each microbatch's {b // m if b % m == 0 else '?'} "
            f"rows divisible by dp {pcfg.dp} (the batch shards over "
            "the manual dp axis)")

    def embed(wte, wpe):
        return wte[input_ids].astype(cdt) + wpe[:s][None].astype(cdt)

    x, embed_vjp = jax.vjp(embed, params["wte"], params["wpe"])
    x = _constrain(x, P("dp", None, None), mesh)
    mb = pipeline_microbatch(x, m)                 # [m, b/m, s, h]
    lbl_mb = pipeline_microbatch(labels, m)
    blocks = jax.tree_util.tree_map(lambda p: p.astype(cdt),
                                    params["blocks"])
    head_params = {"wte": params["wte"], "lnf_g": params["lnf_g"],
                   "lnf_b": params["lnf_b"]}

    def stage_fn(stage_params, xm):
        return stack_apply_manual_ep(stage_params, xm, cfg, pcfg)

    def body(blocks, mb, lbl_mb, head_params):
        ndp = lax.axis_size("dp")

        def last_grad(y, hp, mb_idx):
            lbl = lbl_mb[mb_idx]

            def head_loss(hp_, y_):
                hh = _ln(y_, hp_["lnf_g"].astype(cdt),
                         hp_["lnf_b"].astype(cdt))
                # local-rows CE scaled by 1/dp: the global loss is the
                # mean over dp members' local means, so each member's
                # cotangents (restricted to its rows) carry the 1/dp
                logits = jnp.einsum(
                    "bsh,vh->bsv", hh,
                    hp_["wte"].astype(hh.dtype))[:, :-1]
                logits = logits.astype(jnp.float32)
                tgt = lbl[:, 1:]
                logz = jax.scipy.special.logsumexp(logits, axis=-1)
                picked = jnp.take_along_axis(
                    logits, tgt[..., None], axis=-1)[..., 0]
                return jnp.mean(logz - picked) / (m * ndp)

            (l, (ghp, gy)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(hp, y)
            return l, gy, ghp

        if pcfg.pp_schedule == "zbvpp":
            loss, bgrads, hgrads, dx0 = pipeline_train_zbvpp(
                stage_fn, blocks, mb, last_grad,
                head_params=head_params, serialize_phases=True)
        else:
            loss, bgrads, hgrads, dx0 = pipeline_train_zbh1(
                stage_fn, blocks, mb, last_grad,
                head_params=head_params, serialize_phases=True)
        # rank-0 dp-varying values cannot ride out_specs — emit the
        # per-member partial losses as a [1] vector (P('dp') -> [dp])
        return loss[None], bgrads, hgrads, dx0

    blk_specs = _manual_blk_specs(blocks, moe=True)
    mb_spec = P(None, "dp", None, None)
    hp_specs = {"wte": P(), "lnf_g": P(), "lnf_b": P()}
    loss, bgrads, hgrads, dx0 = _shard_map(
        body, mesh=mesh, axis_names={"pp", "dp"},
        in_specs=(blk_specs, mb_spec, P(None, "dp", None), hp_specs),
        out_specs=(P("dp"), blk_specs, hp_specs,
                   P(None, "dp", None, None)))(
            blocks, mb, lbl_mb, head_params)

    # the per-member losses are partial (1/dp-scaled local means):
    # their sum is the global loss
    loss = jnp.sum(loss)
    dwte_e, dwpe = embed_vjp(dx0.reshape(b, s, -1).astype(x.dtype))
    return loss, {
        "wte": dwte_e.astype(jnp.float32) + hgrads["wte"],
        "wpe": dwpe.astype(jnp.float32),
        "blocks": bgrads,
        "lnf_g": hgrads["lnf_g"],
        "lnf_b": hgrads["lnf_b"],
    }
