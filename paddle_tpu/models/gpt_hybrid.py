"""Hybrid-parallel GPT training engine (the compiled perf path).

Re-designs the reference's fleet hybrid-parallel train loop (SURVEY §3.5:
PipelineParallel.train_batch + TP layers + sharding + MoE all-to-all) as
ONE jitted SPMD program over a (dp, pp, tp) mesh:

- dp  : batch sharded; grad psum inserted by XLA (replaces EagerReducer)
- tp  : Megatron shardings on qkv/proj/fc weights; collectives from GSPMD
        (replaces mp_ops allreduce/allgather PyLayers)
- sp  : activations between blocks sequence-sharded over the tp axis
        (Megatron-LM SP, sequence_parallel_utils.py equivalent)
- pp  : stages stacked on a leading axis, manual shard_map over 'pp' with
        ppermute microbatch rotation (replaces 1F1B host scheduling);
        dp/tp stay GSPMD-auto inside the manual region (axis_names={'pp'})
- ep  : MoE expert dim sharded over the dp axis (DeepSpeed-MoE style
        EP=DP); GShard dense-dispatch einsum → XLA emits the all-to-alls
        (replaces global_scatter/global_gather, moe_layer.py:263)
- ZeRO-1/2: optimizer moments sharded over dp via sharding constraints
  (replaces DygraphShardingOptimizer)
- remat: jax.checkpoint per block (replaces RecomputeFunction)

Everything below is pure-functional jax (no eager Tensor) — this is the
engine the paddle-style wrappers lower to, and what the train cells of
BENCHMARK.json measure.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .gpt import GPTConfig


@dataclass
class ParallelConfig:
    dp: int = 1
    pp: int = 1
    tp: int = 1
    sp: bool = False          # sequence-shard activations over tp axis
    num_experts: int = 0      # >0 turns MLP into MoE (EP over dp axis)
    microbatches: int = 1     # pipeline microbatches (pp>1)
    # "gpipe": forward rotation + jax.grad (activation liveness grows
    # with microbatches); "1f1b": explicit forward/backward interleave
    # with O(pp) liveness (parallel/pipeline_1f1b.py — the compiled
    # analog of the reference 1F1B, pipeline_parallel.py:547);
    # "zbh1"/"zbvpp": zero-bubble schedules with cond-gated phases and
    # dx/dW-split backward (reference pipeline_zero_bubble.py:62/:151).
    # tp>1 composes via the manual-tp stage body, EP-MoE via the
    # manual-ep body (explicit in-branch collectives,
    # models/gpt_manual_tp.py, round 5); only tp>1 AND MoE combined
    # is refused (no combined manual body).
    # "zbvpp" runs TWO model chunks per device in the V placement
    # (layers split 2*pp ways; num_layers % (2*pp) == 0)
    pp_schedule: str = "gpipe"
    # virtual pipeline chunks per device (interleaved VPP,
    # PipelineParallelWithInterleave pipeline_parallel.py:1143): the
    # stage's layers split into v chunks; backward recomputation spans
    # L/(pp*v) layers instead of L/pp. Requires pp>1 + pp_schedule 1f1b
    vpp_chunks: int = 1
    remat: bool = True
    # remat granularity: "full" recomputes the whole block (min memory);
    # "dots" saves matmul/einsum outputs and recomputes only elementwise
    # (cuts the ~1/3 recompute FLOPs of full remat at modest memory cost)
    remat_policy: str = "full"
    # names saved by the "names" policy (v5e-tuned: saving MORE than
    # these hurts via memory pressure, fewer recomputes the flash
    # kernel in backward)
    remat_save_names: tuple = ("attn_out", "ffn1", "qkv")
    # k-step gradient merge INSIDE the compiled step: the batch is split
    # into k chunks, grads accumulate across a lax.scan and the
    # optimizer applies the averaged grad once — the reference
    # auto_parallel_gradient_merge pass, with the deferred reduction
    # falling out of XLA compiling the whole loop as one program
    gradient_merge_steps: int = 1
    # sp matmuls become ring collective matmuls (all_gather@W and
    # X@W->reduce_scatter decomposed inside shard_map so the ICI
    # permute overlaps the MXU block GEMMs — parallel/collective_matmul
    # .py; the reference overlaps these with CUDA streams,
    # sequence_parallel_utils.py:240-340). Opt-in: wins only when the
    # gather/scatter is bandwidth-bound on real multi-chip ICI.
    # pp==1: GSPMD route via a top-level tp shard_map (_use_cm).
    # pp>1 (round 5): manual-tp 1F1B route — needs sp, tp>1,
    # vpp_chunks=1, no MoE, fused_ce=False (the nested-region
    # formulation stays Shardy-walled — _use_cm).
    # Incompatible with the zero-bubble schedules (whole-mesh ppermute
    # in a cond-gated phase — _validate_pp_schedule refuses)
    collective_matmul: bool = False
    zero1: bool = True        # shard adam moments over dp
    # Adam moment storage dtype. None (default) INHERITS the param
    # dtype — the original zeros_like behavior every recorded bench ran
    # under (bf16 moments for the bf16-param flagship). Explicit f32
    # doubles moment HBM (+5.2 GB at 1.3B — does NOT fit v5e alongside
    # the step's working set); parity of bf16 vs f32 moments measured
    # at 1.45e-6 max rel deviation over 30 steps (a probe on an earlier
    # chip, asserted < 5e-3; the script is in git history before PR 30)
    moment_dtype: Any = None
    fused_ce: bool = True     # chunked LM-head+CE (ops/fused_ce.py);
                              # never materializes [T, V] logits
    scan_unroll: int = 1      # lax.scan unroll over layers (full unroll
                              # buys ~4% on v5e at higher compile time)
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16


def build_mesh(pcfg: ParallelConfig, devices=None) -> Mesh:
    devs = devices if devices is not None else jax.devices()
    n = pcfg.dp * pcfg.pp * pcfg.tp
    if n > len(devs):
        raise ValueError(f"need {n} devices, have {len(devs)}")
    arr = np.asarray(devs[:n]).reshape(pcfg.dp, pcfg.pp, pcfg.tp)
    return Mesh(arr, ("dp", "pp", "tp"))


# ------------------------------ init ---------------------------------------
def _init(key, shape, scale, dtype):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_params(cfg: GPTConfig, pcfg: ParallelConfig, key) -> Dict:
    h = cfg.hidden_size
    m = h * cfg.ffn_mult
    L = cfg.num_layers
    dt = pcfg.param_dtype
    std = 0.02
    ks = jax.random.split(key, 16)
    blocks: Dict[str, Any] = {
        "ln1_g": jnp.ones((L, h), dt), "ln1_b": jnp.zeros((L, h), dt),
        "qkv_w": _init(ks[0], (L, h, 3 * h), std, dt),
        "qkv_b": jnp.zeros((L, 3 * h), dt),
        "proj_w": _init(ks[1], (L, h, h), std / math.sqrt(2 * L), dt),
        "proj_b": jnp.zeros((L, h), dt),
        "ln2_g": jnp.ones((L, h), dt), "ln2_b": jnp.zeros((L, h), dt),
    }
    if pcfg.num_experts > 0:
        e = pcfg.num_experts
        blocks.update({
            "gate_w": _init(ks[2], (L, h, e), std, dt),
            "fc1_w": _init(ks[3], (L, e, h, m), std, dt),
            "fc1_b": jnp.zeros((L, e, m), dt),
            "fc2_w": _init(ks[4], (L, e, m, h), std / math.sqrt(2 * L), dt),
            "fc2_b": jnp.zeros((L, e, h), dt),
        })
    else:
        blocks.update({
            "fc1_w": _init(ks[3], (L, h, m), std, dt),
            "fc1_b": jnp.zeros((L, m), dt),
            "fc2_w": _init(ks[4], (L, m, h), std / math.sqrt(2 * L), dt),
            "fc2_b": jnp.zeros((L, h), dt),
        })
    params = {
        "wte": _init(ks[5], (cfg.vocab_size, h), std, dt),
        "wpe": _init(ks[6], (cfg.max_seq_len, h), std, dt),
        "blocks": blocks,
        "lnf_g": jnp.ones((h,), dt), "lnf_b": jnp.zeros((h,), dt),
    }
    return params


def param_specs(cfg: GPTConfig, pcfg: ParallelConfig) -> Dict:
    """NamedSharding specs: tp = Megatron; pp = leading stage dim; ep = dp.
    They are the specs of the tree shard_params makes, in which qkv_w lies
    [L, h, 3, h] and qkv_b [L, 3, h] (``_qkv_per_matrix``)."""
    pp = "pp" if pcfg.pp > 1 else None
    moe = pcfg.num_experts > 0
    blocks = {
        "ln1_g": P(pp, None), "ln1_b": P(pp, None),
        "qkv_w": P(pp, None, None, "tp"), "qkv_b": P(pp, None, "tp"),
        "proj_w": P(pp, "tp", None), "proj_b": P(pp, None),
        "ln2_g": P(pp, None), "ln2_b": P(pp, None),
    }
    if moe:
        blocks.update({
            "gate_w": P(pp, None, None),
            "fc1_w": P(pp, "dp", None, "tp"), "fc1_b": P(pp, "dp", "tp"),
            "fc2_w": P(pp, "dp", "tp", None), "fc2_b": P(pp, "dp", None),
        })
    else:
        blocks.update({
            "fc1_w": P(pp, None, "tp"), "fc1_b": P(pp, "tp"),
            "fc2_w": P(pp, "tp", None), "fc2_b": P(pp, None),
        })
    return {
        # vocab-sharded embedding (Megatron VocabParallelEmbedding)
        # when the vocab divides tp; replicated storage otherwise so
        # odd vocabs (e.g. GPT-2's 50257) stay runnable at any tp —
        # the manual-tp zero-bubble head re-pads to a tp multiple
        # internally (gpt_manual_tp.train_grads_zb_manual_tp)
        "wte": P("tp", None) if cfg.vocab_size % max(pcfg.tp, 1) == 0
        else P(None, None),
        "wpe": P(None, None),
        "blocks": blocks,
        "lnf_g": P(None), "lnf_b": P(None),
    }


def _block_stack_dims(pcfg):
    """Leading layer-stack dims of a ``params["blocks"]`` leaf as
    shard_params lays it out: [L, ...] at pp == 1, [pp, L/pp, ...] under
    pp, [pp, chunk, Lc, ...] where a stage holds several chunks."""
    if pcfg.pp == 1:
        return 1
    return 2 + (pcfg.vpp_chunks > 1 or pcfg.pp_schedule == "zbvpp")


def _qkv_per_matrix(blocks, cfg):
    """qkv_w [L, h, 3h] -> [L, h, 3, h] and qkv_b [L, 3h] -> [L, 3, h], so
    that a shard of the last dim over tp is a rank's own heads of q, of k
    and of v, which is what ``_attend`` runs on (a shard of the flat 3h
    straddles the q|k boundary, and XLA then moves every layer's product
    and gathers its weight). Row-major: W[..., i, c*h + j] == W'[..., i,
    c, j], the same function of the same stored numbers, so a tree saved in
    the flat shape is read by this reshape and one already in the new shape
    passes through."""
    L, h = cfg.num_layers, cfg.hidden_size
    return {**blocks,
            "qkv_w": blocks["qkv_w"].reshape(L, h, 3, h),
            "qkv_b": blocks["qkv_b"].reshape(L, 3, h)}


def shard_params(params, mesh, cfg, pcfg):
    """``init_params``' tree (or one saved by an earlier version) laid out
    for the mesh and placed on it: returns (params, specs). The one door to
    a mesh of more than one device: ``_block`` reads a flat qkv leaf too,
    but only this layout keeps a tp rank's columns its own heads'."""
    specs = param_specs(cfg, pcfg)
    params = {**params, "blocks": _qkv_per_matrix(params["blocks"], cfg)}
    if pcfg.pp > 1:
        # blocks leaves [L, ...] -> [pp, L/pp, ...] (vpp>1:
        # [pp, v, L/(pp*v), ...] — virtual stage sigma = j*pp + s lives
        # at [s, j]); stage dim carries 'pp', chunk/per-layer dims are
        # unsharded, trailing dims keep their tp/ep spec
        L = cfg.num_layers
        v = pcfg.vpp_chunks
        if pcfg.pp_schedule == "zbvpp":
            # ZB-V placement: virtual stage sigma (of 2*pp) owns layers
            # [sigma*Lc, (sigma+1)*Lc); device s holds vstage s at
            # [s, 0] and vstage 2*pp-1-s at [s, 1]
            ng = 2 * pcfg.pp
            if L % ng:
                raise ValueError(
                    f"num_layers {L} not divisible by 2*pp {ng} "
                    "(pp_schedule='zbvpp' splits the model into 2*pp "
                    "V-placed chunks)")
            Lc = L // ng
            vidx = np.stack([np.arange(pcfg.pp),
                             ng - 1 - np.arange(pcfg.pp)], axis=1)
            params["blocks"] = jax.tree_util.tree_map(
                lambda x: x.reshape((ng, Lc) + x.shape[1:])[vidx],
                params["blocks"])
        elif v > 1:
            if L % (pcfg.pp * v):
                raise ValueError(
                    f"num_layers {L} not divisible by pp*vpp_chunks "
                    f"{pcfg.pp}*{v}")
            # virtual stage sigma = j*pp + s owns layers
            # [sigma*Lc, (sigma+1)*Lc): reorder [pp*v, Lc] -> [pp, v, Lc]
            Lc = L // (pcfg.pp * v)
            params["blocks"] = jax.tree_util.tree_map(
                lambda x: x.reshape((v, pcfg.pp, Lc) + x.shape[1:])
                .swapaxes(0, 1),
                params["blocks"])
        else:
            params["blocks"] = jax.tree_util.tree_map(
                lambda x: x.reshape((pcfg.pp, L // pcfg.pp)
                                    + x.shape[1:]),
                params["blocks"])
        flat_specs = param_specs(
            cfg, ParallelConfig(**{**pcfg.__dict__, "pp": 1}))["blocks"]
        specs = dict(specs)
        unsharded = (None,) * (_block_stack_dims(pcfg) - 1)
        specs["blocks"] = jax.tree_util.tree_map(
            lambda s: P("pp", *unsharded, *tuple(s)[1:]), flat_specs)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs), specs


# ---------------------------- forward --------------------------------------
def _layer_norm(x, g, b, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * g + b


@jax.named_scope("attend")
def _attend(q, k, v, nh, mesh=None):
    """Causal self-attention over [b, s, h] projections.

    ``mesh``: the GSPMD mesh of an auto-partitioned caller. Attention is
    independent per (sequence, head), and a Mosaic kernel cannot be
    partitioned automatically (jax refuses to lower it), so on a mesh of
    more than one device the call is shard_mapped: batch over 'dp', heads
    over 'tp' — the layout the Megatron qkv sharding already produces.
    Callers inside a manual region (the pp>1 stage bodies, the manual-tp
    bodies) hold local shards already and pass no mesh."""
    b, s, h = q.shape
    d = h // nh
    q = q.reshape(b, s, nh, d)
    k = k.reshape(b, s, nh, d)
    v = v.reshape(b, s, nh, d)

    def attend(q, k, v):
        # Pallas kernel on TPU (phi flash_attn_kernel.cu analog); XLA
        # einsum attention where a shape gate or the platform says no
        from paddle_tpu.ops.pallas.flash_attention import \
            flash_attention_maybe
        out = flash_attention_maybe(q, k, v, causal=True)
        if out is None:
            logits = jnp.einsum(
                "bqhd,bkhd->bhqk", q, k,
                preferred_element_type=jnp.float32) / math.sqrt(d)
            iq = lax.broadcasted_iota(jnp.int32, (s, s), 0)
            ik = lax.broadcasted_iota(jnp.int32, (s, s), 1)
            logits = jnp.where((iq >= ik)[None, None], logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return out

    if mesh is not None and mesh.size > 1 \
            and not jax.sharding.get_abstract_mesh().manual_axes:
        spec = P("dp" if b % mesh.shape["dp"] == 0 else None, None,
                 "tp" if nh % mesh.shape["tp"] == 0 else None, None)
        attend = jax.shard_map(attend, mesh=mesh, in_specs=(spec,) * 3,
                               out_specs=spec, check_vma=False)
    return attend(q, k, v).reshape(b, s, h)


def _constrain(x, spec, mesh):
    # Inside a manual (shard_map) region — the pp>1 stage bodies — no
    # constraint is placed: the concrete all-Auto mesh is rejected
    # there ("Axes mentioned in vma ... should be Manual"), which the
    # old blanket try/except hid, so activation constraints have never
    # applied under pp. Naming the context mesh instead makes them
    # apply, and the dp x pp x tp + MoE + vpp composition then dies in
    # XLA's SPMD partitioner (spmd_partitioner_util.cc:495 check, XLA:CPU,
    # jax 0.9.0 — __graft_entry__.dryrun_multichip's first leg). Until
    # that is sorted the skip is explicit; anywhere else a failed
    # constraint raises.
    if jax.sharding.get_abstract_mesh().manual_axes:
        return x
    return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _moe_ffn(x, lp, pcfg, mesh):
    """GShard-style dense-dispatch switch MoE; expert dim sharded over dp
    (EP=DP) → XLA emits all-to-all over ICI."""
    b, s, h = x.shape
    e = pcfg.num_experts
    tokens = x.reshape(b * s, h)
    gate_logits = tokens.astype(jnp.float32) @ \
        lp["gate_w"].astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, -1)
    top = jnp.argmax(probs, -1)
    gate = jnp.max(probs, -1).astype(x.dtype)
    disp = jax.nn.one_hot(top, e, dtype=x.dtype)          # [T, E]
    xin = jnp.einsum("te,th->eth", disp, tokens)          # dispatch
    hmid = jax.nn.gelu(
        jnp.einsum("eth,ehm->etm", xin, lp["fc1_w"])
        + lp["fc1_b"][:, None, :])
    hout = jnp.einsum("etm,emh->eth", hmid, lp["fc2_w"]) \
        + lp["fc2_b"][:, None, :]
    combined = jnp.einsum("te,eth->th", disp, hout) * gate[:, None]
    return combined.reshape(b, s, h)


def _use_cm(pcfg):
    # pp>1 exclusion RE-CONFIRMED in round 4 (not a design choice; a
    # Shardy expressibility wall, re-probed with minimal reproducers —
    # tests/test_collective_matmul.py::test_cm_under_pp_upstream_wall):
    # an inner tp-manual region whose operands vary over the outer pp
    # axis hits, depending on structure, (a) 'manual axes must come
    # before free axes' when a rank-1 operand's vma {pp,tp} squashes
    # both onto dim 0, (b) 'operates on axis already bound by parent'
    # when the vma widening pcast sits inside the inner region, or
    # (c) scan-carry vma mismatches. The canary test asserts (a) still
    # reproduces — when a jax upgrade clears it, the test fails and
    # this gate should be retried (the cm ring itself already handles
    # nested-context meshes + vma unions).
    return pcfg.collective_matmul and pcfg.sp and pcfg.tp > 1 \
        and pcfg.pp == 1


def _cm_column(x, w, b, mesh):
    """allgather(x, seq)@W as a ring collective matmul over 'tp'."""
    from paddle_tpu.parallel.collective_matmul import sp_column_matmul
    return sp_column_matmul(x, w, mesh, "tp") + b


def _cm_row(x, w, b, mesh):
    """X@W -> ring reduce_scatter onto the seq dim over 'tp'."""
    from paddle_tpu.parallel.collective_matmul import sp_row_matmul
    return sp_row_matmul(x, w, mesh, "tp") + b


@jax.named_scope("block")
def _block(x, lp, cfg, pcfg, mesh):
    from jax.ad_checkpoint import checkpoint_name
    act_spec = P("dp", "tp", None) if pcfg.sp else P("dp", None, None)
    cm = _use_cm(pcfg)
    x = _constrain(x, act_spec, mesh)
    hres = x
    hx = _layer_norm(x, lp["ln1_g"], lp["ln1_b"])
    # [h, 3, h/tp] as shard_params lays it, or a free view of
    # init_params' flat [h, 3h]: columns [q | k | v] either way
    qkv_w = lp["qkv_w"].reshape(hx.shape[-1], 3, -1)
    qkv_b = lp["qkv_b"].reshape(3, -1)
    # the product comes out [3, b, s, h/tp]: q, k and v are whole slabs
    # (as [b, s, 3, h/tp] each is strided, and the one-chip train cells
    # read -0.30 % and +0.07 % where this order reads +0.13 % and +0.63 %:
    # PERF.md section 6, PR 33)
    if cm:
        qkv = jnp.moveaxis(_cm_column(hx, qkv_w, qkv_b, mesh), 2, 0)
    else:
        qkv = jnp.einsum("bsh,hcn->cbsn", hx, qkv_w) + qkv_b[:, None, None]
    qkv = checkpoint_name(qkv, "qkv")
    attn = checkpoint_name(_attend(*qkv, cfg.num_heads, mesh), "attn_out")
    if cm:
        attn = checkpoint_name(
            _cm_row(attn, lp["proj_w"], lp["proj_b"], mesh), "proj")
    else:
        attn = checkpoint_name(attn @ lp["proj_w"] + lp["proj_b"],
                               "proj")
    x = hres + attn
    x = _constrain(x, act_spec, mesh)
    hres = x
    hx = _layer_norm(x, lp["ln2_g"], lp["ln2_b"])
    with jax.named_scope("mlp"):
        if pcfg.num_experts > 0:
            ff = _moe_ffn(hx, lp, pcfg, mesh)
        elif cm:
            ff = checkpoint_name(
                _cm_row(jax.nn.gelu(checkpoint_name(
                    _cm_column(hx, lp["fc1_w"], lp["fc1_b"], mesh),
                    "ffn1")), lp["fc2_w"], lp["fc2_b"], mesh), "ffn2")
        else:
            ff = checkpoint_name(
                jax.nn.gelu(checkpoint_name(
                    hx @ lp["fc1_w"] + lp["fc1_b"], "ffn1"))
                @ lp["fc2_w"] + lp["fc2_b"], "ffn2")
    x = hres + ff
    return _constrain(x, act_spec, mesh)


def _stack_apply(blocks, x, cfg, pcfg, mesh):
    """lax.scan over the (local) layer stack — one compiled block body."""
    def body(h, lp):
        fn = functools.partial(_block, cfg=cfg, pcfg=pcfg, mesh=mesh)
        if pcfg.remat:
            if pcfg.remat_policy == "dots":
                # save every matmul output, recompute elementwise only
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies.dots_saveable)
            elif pcfg.remat_policy == "names":
                # surgical: keep the expensive tensors (attention
                # output, qkv, ffn up-projection), recompute the cheap
                # rest — the flash kernel never re-runs in backward.
                # Measured best on an earlier v5e (probes in git history
                # before PR 30); saving proj/ffn2 as well LOWERS
                # throughput (memory pressure)
                fn = jax.checkpoint(
                    fn, policy=jax.checkpoint_policies
                    .save_only_these_names(*pcfg.remat_save_names))
            else:
                fn = jax.checkpoint(fn)
        return fn(h, lp), None
    out, _ = lax.scan(body, x, blocks, unroll=max(1, pcfg.scan_unroll))
    return out


def forward_hidden(params, input_ids, cfg: GPTConfig,
                   pcfg: ParallelConfig, mesh: Mesh):
    cdt = pcfg.compute_dtype
    b, s = input_ids.shape
    x = params["wte"][input_ids].astype(cdt) + \
        params["wpe"][:s][None].astype(cdt)
    x = _constrain(x, P("dp", None, None), mesh)
    blocks = jax.tree_util.tree_map(lambda p: p.astype(cdt),
                                    params["blocks"])

    if pcfg.pp > 1:
        if pcfg.pp_schedule == "zbvpp":
            # relayout the ZB-V [pp, 2, Lc, ...] stacking back to the
            # plain [pp, L/pp, ...] eval layout: virtual stage sigma
            # lives at [sigma, 0] for sigma < pp and [2*pp-1-sigma, 1]
            # past the turnaround; gathering in sigma order recovers
            # the layer sequence (same one-relayout cost as VPP eval)
            npp = pcfg.pp
            L = cfg.num_layers
            ds = np.concatenate([np.arange(npp),
                                 np.arange(npp - 1, -1, -1)])
            ls = np.concatenate([np.zeros(npp, np.int64),
                                 np.ones(npp, np.int64)])
            blocks = jax.tree_util.tree_map(
                lambda p: p[ds, ls]
                .reshape((L,) + p.shape[3:])
                .reshape((npp, L // npp) + p.shape[3:]),
                blocks)
        elif pcfg.vpp_chunks > 1:
            # relayout the interleaved [pp, v, Lc, ...] stacking back to
            # the plain [pp, L/pp, ...] eval layout: virtual stage
            # sigma = j*pp + s lives at [s, j], so [pp, v] -> [v, pp]
            # -> flat [L] recovers layer order; the re-split across pp
            # is a resharding GSPMD handles (eval pays one relayout,
            # training keeps the interleaved stacking untouched)
            v = pcfg.vpp_chunks
            L = cfg.num_layers
            blocks = jax.tree_util.tree_map(
                lambda p: p.swapaxes(0, 1)
                .reshape((L,) + p.shape[3:])
                .reshape((pcfg.pp, L // pcfg.pp) + p.shape[3:]),
                blocks)
        from paddle_tpu.parallel.pipeline import (pipeline_apply,
                                                  pipeline_microbatch)
        mb = pipeline_microbatch(x, pcfg.microbatches)

        def stage_fn(stage_params, xm):
            return _stack_apply(stage_params, xm, cfg, pcfg, mesh)

        def pp_body(blocks_stacked, mb):
            my = jax.tree_util.tree_map(lambda p: p[0], blocks_stacked)
            n = lax.axis_size("pp")
            idx = lax.axis_index("pp")
            m_count = mb.shape[0]
            state = lax.pcast(jnp.zeros_like(mb[0]), ("pp",), to='varying')
            outs = lax.pcast(jnp.zeros_like(mb), ("pp",), to='varying')
            perm = [(i, (i + 1) % n) for i in range(n)]

            def compute(t, state, outs):
                x_in = jnp.where(idx == 0, mb[jnp.clip(t, 0, m_count - 1)],
                                 state)
                y = stage_fn(my, x_in)
                slot = jnp.clip(t - (n - 1), 0, m_count - 1)
                write = (idx == n - 1) & (t >= n - 1)
                outs = lax.cond(
                    write,
                    lambda o: lax.dynamic_update_index_in_dim(
                        o, y, slot, 0),
                    lambda o: o, outs)
                return y, outs

            # permute at the top of steps 1..T-1 (no discarded rotation)
            total = m_count + n - 1
            y, outs = compute(0, state, outs)

            def step(carry, t):
                y_prev, outs = carry
                state = lax.ppermute(y_prev, "pp", perm)
                y, outs = compute(t, state, outs)
                return (y, outs), None

            if total > 1:
                (y, outs), _ = lax.scan(step, (y, outs),
                                        jnp.arange(1, total))
            outs = lax.psum(
                jnp.where(idx == n - 1, outs, jnp.zeros_like(outs)), "pp")
            return outs

        from jax import shard_map
        blk_specs = jax.tree_util.tree_map(lambda _: P("pp"),
                                           blocks)
        out_mb = shard_map(
            pp_body, mesh=mesh, axis_names={"pp"},
            in_specs=(blk_specs, P(None)), out_specs=P(None))(blocks, mb)
        x = out_mb.reshape((b, s, cfg.hidden_size))
    else:
        x = _stack_apply(blocks, x, cfg, pcfg, mesh)

    return _layer_norm(x, params["lnf_g"].astype(cdt),
                       params["lnf_b"].astype(cdt))


def forward(params, input_ids, cfg: GPTConfig, pcfg: ParallelConfig,
            mesh: Mesh):
    x = forward_hidden(params, input_ids, cfg, pcfg, mesh)
    return jnp.einsum("bsh,vh->bsv", x,
                      params["wte"].astype(pcfg.compute_dtype))


@jax.named_scope("lm_head_ce")
def _ce_from_hidden(h, wte, labels, pcfg):
    """Next-token CE from the final (post-LN) hidden states [b, s, hid]
    — the single home of the LM-head+loss math, shared by loss_fn and
    the compiled-1F1B last-stage head."""
    b, s, hid = h.shape
    if pcfg.fused_ce:
        from paddle_tpu.ops.fused_ce import fused_lm_ce
        # next-token targets with the final position masked out
        tgt = jnp.concatenate([labels[:, 1:],
                               jnp.zeros((b, 1), labels.dtype)], axis=1)
        mask = jnp.concatenate(
            [jnp.ones((b, s - 1), jnp.float32),
             jnp.zeros((b, 1), jnp.float32)], axis=1)
        # the mask must carry h's varying spec at the custom-vjp
        # boundary: its cotangent is computed from h-derived values, and
        # shard_map manual-axis type checking rejects a varying
        # cotangent against an unvarying (literal) primal
        mask = mask + h.ravel()[0].astype(jnp.float32) * 0
        return fused_lm_ce(h.reshape(b * s, hid), wte.astype(h.dtype),
                           tgt.reshape(b * s), mask.reshape(b * s))
    logits = jnp.einsum("bsh,vh->bsv", h, wte.astype(h.dtype))
    logits = logits[:, :-1].astype(jnp.float32)
    tgt = labels[:, 1:]
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, tgt[..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def loss_fn(params, batch, cfg, pcfg, mesh):
    input_ids, labels = batch
    # forward_hidden already applies the final layer norm
    x = forward_hidden(params, input_ids, cfg, pcfg, mesh)
    return _ce_from_hidden(x, params["wte"], labels, pcfg)


# --------------------------- optimizer -------------------------------------
def moment_specs(params, pcfg, specs):
    """P-spec tree for the Adam moments: the param spec, with ZeRO-1
    additionally sharding each not-already-dp-sharded leaf over dp on
    its first divisible free dim (DygraphShardingOptimizer's
    rank-ownership, expressed as a sharding instead of per-rank slicing).
    A stacked block leaf takes a dim INSIDE the layer while one divides:
    each layer's gradient is produced whole on every dp rank, so only a
    shard within the layer lets its dp sum land as a reduce-scatter in
    the owner's shard (a shard over the layers costs a full all-reduce,
    then a send of the reduced layer to its owner). The layer dim is the
    fallback of a leaf whose own dims do not divide. Counted once a leaf
    in ``zero1.moment_shard{dim}``."""
    from paddle_tpu import observability as obs

    def spec_of(path, x, s):
        entry = list(tuple(s)) + [None] * (x.ndim - len(tuple(s)))
        if not (pcfg.zero1 and pcfg.dp > 1):
            return P(*entry)
        stack = _block_stack_dims(pcfg) \
            if path[0] == jax.tree_util.DictKey("blocks") else 0
        took = "none"
        if "dp" not in jax.tree_util.tree_leaves(entry):
            free = [i for i, e in enumerate(entry) if e is None
                    and x.shape[i] % pcfg.dp == 0]
            in_layer = [i for i in free if i >= stack]
            if free:
                entry[(in_layer or free)[0]] = "dp"
                took = "in_layer" if in_layer else "layer"
        if obs.enabled():
            obs.counter("zero1.moment_shard", dim=took).inc()
        return P(*entry)
    return jax.tree_util.tree_map_with_path(spec_of, params, specs)


def adamw_init(params, pcfg, mesh, specs, mspecs=None):
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, pcfg.moment_dtype or p.dtype),
        params)
    if mesh is not None:
        # commit every piece of state to the mesh: an UNcommitted moment
        # tree makes the first jitted step's outputs (which carry the
        # mesh context) a different cache key than the inputs — i.e. a
        # silent SECOND compile of the full train program
        # (tests/test_perf_gate.py::test_train_step_executable_count_stable)
        # mspecs, when passed by setup, is the SAME tree that pins the
        # step's out_shardings — input and output shardings agree
        # structurally, not by parallel construction
        if mspecs is None:
            if specs is None:
                # legacy callers passed specs=None when it was dead
                # (dp=1 / zero1 off): moments replicate
                specs = jax.tree_util.tree_map(lambda _: P(), params)
            mspecs = moment_specs(params, pcfg, specs)
        zeros = jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            zeros, mspecs)
        step0 = jax.device_put(jnp.zeros((), jnp.int32),
                               NamedSharding(mesh, P()))
    else:
        step0 = jnp.zeros((), jnp.int32)
    return {"m": zeros,
            "v": jax.tree_util.tree_map(jnp.zeros_like, zeros),
            "step": step0}


def _state_out_shardings(mesh, pspecs, mspecs):
    """(params, opt_state, scalar) NamedSharding trees — the ONE home of
    the train-state output-sharding layout shared by every jitted engine
    (build_train_step, build_accum_steps)."""
    def ns(tree):
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), tree)
    scalar = NamedSharding(mesh, P())
    return (ns(pspecs),
            {"m": ns(mspecs), "v": ns(mspecs), "step": scalar},
            scalar)


@jax.named_scope("adamw_update")
def adamw_update(params, grads, opt_state, lr=3e-4, b1=0.9, b2=0.95,
                 eps=1e-8, wd=0.1):
    step = opt_state["step"] + 1

    def upd(p, g, m, v):
        return _adamw_leaf(p, m, v, g, step, lr, b1, b2, eps, wd)

    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(opt_state["m"])
    flat_v = jax.tree_util.tree_leaves(opt_state["v"])
    out = [upd(p, g, m, v) for p, g, m, v
           in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(tdef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "step": step}


# --------------------------- train step ------------------------------------
@dataclass(frozen=True)
class TrainModel:
    """What the engine asks of a model: ``build_train_step`` and ``setup``
    take one, the GPT of this module (``GPT``) where none is given. The
    mesh, AdamW and its moments' specs, ``_grads_to_owner``, the jitted and
    donated step and ``_ce_from_hidden`` are the engine's and the same for
    every model.

    ``init_params(cfg, pcfg, key)``, ``shard_params(params, mesh, cfg,
    pcfg) -> (params, specs)`` and ``loss_fn(params, batch, cfg, pcfg,
    mesh)`` as the GPT's are. ``frozen``: top-level keys of the parameter
    tree that the optimizer must not touch: no gradient is taken with
    respect to them, they get no moments and no decay. A model that names
    any returns ``(loss, aux)`` from its ``loss_fn`` and brings
    ``update_frozen(frozen leaves, aux) -> frozen leaves``, its own update
    of them, which the step applies after AdamW's."""
    init_params: Callable
    shard_params: Callable
    loss_fn: Callable
    frozen: Tuple[str, ...] = ()
    update_frozen: Optional[Callable] = None

    def split(self, tree):
        """(what the optimizer sees, the frozen leaves) of a parameter
        tree or of a tree of its shape (its specs)."""
        return ({k: v for k, v in tree.items() if k not in self.frozen},
                {k: tree[k] for k in self.frozen})


def _train_grads_1f1b(params, batch, cfg, pcfg, mesh):
    """Loss + grads via the compiled-1F1B pipeline (O(pp) activation
    liveness — parallel/pipeline_1f1b.py) instead of jax.grad over the
    GPipe rotation. Embedding runs (and is differentiated) outside the
    pipeline; the head (final LN + logits + CE) is the pipeline's
    last-stage seed, with tied-wte grads summed from both paths."""
    from jax import shard_map

    from paddle_tpu.parallel.pipeline import pipeline_microbatch
    from paddle_tpu.parallel.pipeline_1f1b import pipeline_train_1f1b

    if pcfg.pp_schedule in ("zbh1", "zbvpp") and pcfg.tp == 1 \
            and pcfg.num_experts > 0 and pcfg.dp > 1:
        # zero-bubble x EP-MoE: the manual-ep stage body (explicit
        # all-to-all over the manual dp axis — in-branch legal: the
        # predicate varies only over pp, so a dp subgroup rendezvouses)
        from paddle_tpu.models.gpt_manual_tp import \
            train_grads_zb_manual_ep
        return train_grads_zb_manual_ep(params, batch, cfg, pcfg, mesh)

    use_manual_tp = pcfg.tp > 1 and pcfg.num_experts == 0 and (
        pcfg.pp_schedule in ("zbh1", "zbvpp")
        or (pcfg.pp_schedule == "1f1b" and pcfg.vpp_chunks == 1
            and pcfg.collective_matmul and pcfg.sp
            # fused_ce has no manual-tp form: when BOTH the fused CE
            # and the ring are requested, the fused CE's memory win
            # (never materializing [T, V] logits) outranks the ring
            # overlap — keep the GSPMD route (the nonroutable warning
            # in _validate_pp_schedule names the trade)
            and not pcfg.fused_ce))
    if use_manual_tp:
        # manual-tp stage body (models/gpt_manual_tp.py):
        # - zero-bubble under tp>1: the cond-gated phases need EXPLICIT
        #   tp collectives — GSPMD-auto ones deadlock in-branch
        #   (round-4 wall; round-5 manual-tp formulation);
        # - 1F1B + collective_matmul + sp at pp>1: the ring collective
        #   matmuls need tp manual at the SAME level as pp (the nested
        #   formulation is Shardy-walled — _use_cm)
        from paddle_tpu.models.gpt_manual_tp import \
            train_grads_zb_manual_tp
        return train_grads_zb_manual_tp(params, batch, cfg, pcfg, mesh)

    input_ids, labels = batch
    cdt = pcfg.compute_dtype
    b, s = input_ids.shape
    m = pcfg.microbatches

    def embed(wte, wpe):
        return wte[input_ids].astype(cdt) + wpe[:s][None].astype(cdt)

    x, embed_vjp = jax.vjp(embed, params["wte"], params["wpe"])
    x = _constrain(x, P("dp", None, None), mesh)
    mb = pipeline_microbatch(x, m)
    lbl_mb = pipeline_microbatch(labels, m)
    blocks = jax.tree_util.tree_map(lambda p: p.astype(cdt),
                                    params["blocks"])
    head_params = {"wte": params["wte"], "lnf_g": params["lnf_g"],
                   "lnf_b": params["lnf_b"]}

    def stage_fn(stage_params, xm):
        return _stack_apply(stage_params, xm, cfg, pcfg, mesh)

    def body(blocks, mb, lbl_mb, head_params):
        def last_grad(y, hp, mb_idx):
            # mb_idx is device-varying, so this gather (and everything
            # derived from lbl) is too — matching y's spec
            lbl = lbl_mb[mb_idx]

            def head_loss(hp_, y_):
                h = _layer_norm(y_, hp_["lnf_g"].astype(cdt),
                                hp_["lnf_b"].astype(cdt))
                return _ce_from_hidden(h, hp_["wte"], lbl, pcfg) / m

            (l, (ghp, gy)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(hp, y)
            return l, gy, ghp

        if pcfg.vpp_chunks > 1:
            from paddle_tpu.parallel.pipeline_1f1b import \
                pipeline_train_interleaved
            return pipeline_train_interleaved(
                stage_fn, blocks, mb, last_grad,
                head_params=head_params, num_chunks=pcfg.vpp_chunks)
        if pcfg.pp_schedule == "zbh1":
            from paddle_tpu.parallel.pipeline_1f1b import \
                pipeline_train_zbh1
            return pipeline_train_zbh1(stage_fn, blocks, mb, last_grad,
                                       head_params=head_params)
        if pcfg.pp_schedule == "zbvpp":
            from paddle_tpu.parallel.pipeline_1f1b import \
                pipeline_train_zbvpp
            return pipeline_train_zbvpp(stage_fn, blocks, mb,
                                        last_grad,
                                        head_params=head_params)
        return pipeline_train_1f1b(stage_fn, blocks, mb, last_grad,
                                   head_params=head_params)

    blk_specs = jax.tree_util.tree_map(lambda _: P("pp"), blocks)
    loss, bgrads, hgrads, dx0 = shard_map(
        body, mesh=mesh, axis_names={"pp"},
        in_specs=(blk_specs, P(None), P(None), P(None)),
        out_specs=(P(), blk_specs, P(), P(None)))(
            blocks, mb, lbl_mb, head_params)

    dwte_e, dwpe = embed_vjp(dx0.reshape(b, s, -1).astype(x.dtype))
    grads = {
        "wte": dwte_e.astype(jnp.float32) + hgrads["wte"],
        "wpe": dwpe.astype(jnp.float32),
        "blocks": bgrads,
        "lnf_g": hgrads["lnf_g"],
        "lnf_b": hgrads["lnf_b"],
    }
    return loss, grads


def _grads_to_owner(pcfg, mesh, state_specs):
    """ZeRO-1's half of the gradient reduction: constrain the gradient
    tree to the moments' specs before the update, so that the dp sum of
    a leaf lowers to a reduce-scatter into the shard that owns its
    moments (an all-reduce, then a send of the layer to its owner,
    otherwise) and the update runs on the shard. Placed once, after any
    accumulation. Identity where no moment is dp-sharded."""
    if state_specs is None or not (pcfg.zero1 and pcfg.dp > 1):
        return lambda grads: grads
    mspecs = state_specs[1]
    return lambda grads: jax.tree_util.tree_map(
        lambda g, s: lax.with_sharding_constraint(
            g, NamedSharding(mesh, s)), grads, mspecs)


def _validate_pp_schedule(pcfg):
    """Shared pp-schedule validation for every engine builder (fused
    train step, split accum engines) — the deadlock/compat guards must
    not depend on which builder dispatches the pipeline."""
    if pcfg.pp_schedule not in ("gpipe", "1f1b", "zbh1", "zbvpp"):
        raise ValueError(
            f"pp_schedule must be 'gpipe', '1f1b', 'zbh1' or 'zbvpp', "
            f"got {pcfg.pp_schedule!r}")
    if pcfg.vpp_chunks > 1 and (pcfg.pp <= 1
                                or pcfg.pp_schedule != "1f1b"):
        raise ValueError(
            "vpp_chunks > 1 requires pp > 1 with pp_schedule='1f1b' "
            "(the interleaved schedule generalizes the compiled 1F1B; "
            "'zbvpp' brings its own two V-placed chunks)")
    if pcfg.pp_schedule in ("zbh1", "zbvpp") and pcfg.num_experts > 0 \
            and pcfg.tp > 1:
        raise ValueError(
            f"pp_schedule={pcfg.pp_schedule!r} with BOTH tp>1 and "
            "expert-parallel MoE: the manual stage bodies exist per "
            "axis (manual-tp, manual-ep — models/gpt_manual_tp.py) but "
            "not combined. Use tp=1 for zb x MoE, or '1f1b' for the "
            "full tp x ep hybrid.")
    if pcfg.pp_schedule in ("zbh1", "zbvpp") and pcfg.num_experts > 0 \
            and pcfg.dp > 1 and pcfg.num_experts % pcfg.dp:
        raise ValueError(
            f"zb x MoE shards experts over dp: num_experts "
            f"{pcfg.num_experts} must divide by dp {pcfg.dp}")
    if pcfg.pp_schedule == "zbvpp" and pcfg.pp <= 1:
        raise ValueError("pp_schedule='zbvpp' requires pp > 1 (the "
                         "V placement spans a pipeline ring)")
    if pcfg.pp_schedule in ("zbh1", "zbvpp") and pcfg.tp > 1 \
            and pcfg.collective_matmul:
        raise ValueError(
            "collective_matmul does not compose with the zero-bubble "
            "schedules: the ring's tp ppermute lowers to ONE "
            "collective-permute spanning the whole mesh, and inside a "
            "cond-gated phase the idle pipeline stages never reach it "
            "(cross-matched data or rendezvous deadlock). Use "
            "pp_schedule='1f1b' for the ring under pp>1, or drop "
            "collective_matmul for zero-bubble.")
    if pcfg.collective_matmul and pcfg.pp > 1 and not (
            pcfg.pp_schedule == "1f1b" and pcfg.vpp_chunks == 1
            and pcfg.sp and pcfg.tp > 1 and pcfg.num_experts == 0
            and not pcfg.fused_ce):
        # the ring at pp>1 rides the manual-tp 1F1B route only; for
        # every other pp>1 shape the knob has no effect — say so
        # instead of silently running without the overlap the planner
        # cost model assumed
        import warnings
        warnings.warn(
            "collective_matmul requested but not routable at pp>1 "
            "(needs pp_schedule='1f1b', vpp_chunks=1, sp=True, tp>1, "
            "no MoE, fused_ce=False — the manual-tp route; with "
            "fused_ce=True the fused-CE memory win keeps the GSPMD "
            "route); running WITHOUT the ring overlap", stacklevel=3)


def _lr_at(lr, opt_state):
    """The step's learning rate: ``lr`` itself, or ``lr(step)`` of a
    schedule (the count of the step being taken, 1 at the first)."""
    return lr(opt_state["step"] + 1) if callable(lr) else lr


def build_train_step(cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh,
                     lr=3e-4, state_specs=None, model: TrainModel = None):
    """The jitted, donated ``step(params, opt_state, batch) -> (params,
    opt_state, loss)`` of ``model`` (the GPT where none is given).
    ``state_specs``: (the parameters' specs, the moments' specs), the
    second over the leaves the optimizer sees. ``lr``: a number, or a
    schedule ``lr(step)`` (``_lr_at``; the gradient-merge step takes a
    number)."""
    model = model or GPT
    _validate_pp_schedule(pcfg)
    # pin the step's outputs to the INPUT state shardings: left to
    # GSPMD, the output spec can drift (e.g. wte P('tp',None) ->
    # P(None,'tp')), which both reshards every step and makes the
    # second call a new executable-cache entry (a silent double compile
    # of the full program — caught by tests/test_perf_gate.py)
    out_sh = None
    if state_specs is not None:
        out_sh = _state_out_shardings(mesh, *state_specs)
    to_owner = _grads_to_owner(pcfg, mesh, state_specs)
    if model.frozen:
        return jax.jit(
            _frozen_train_step(cfg, pcfg, mesh, lr, model, to_owner),
            donate_argnums=(0, 1), out_shardings=out_sh)

    if pcfg.pp > 1 and pcfg.pp_schedule in ("1f1b", "zbh1", "zbvpp"):
        def grads_of(params, batch):
            return _train_grads_1f1b(params, batch, cfg, pcfg, mesh)
    else:
        def grads_of(params, batch):
            return jax.value_and_grad(
                lambda p: model.loss_fn(p, batch, cfg, pcfg, mesh))(params)

    k = pcfg.gradient_merge_steps
    if k > 1:
        def train_step(params, opt_state, batch):
            # split the global batch into k merge chunks and scan:
            # the grad accumulator lives in HBM across the loop and the
            # dp reduction is compiled once (gradient-merge semantics)
            b0 = jax.tree_util.tree_leaves(batch)[0].shape[0]
            if b0 % k:
                raise ValueError(
                    f"global batch {b0} is not divisible by "
                    f"gradient_merge_steps={k}")
            chunks = jax.tree_util.tree_map(
                lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                batch)

            def body(carry, mb):
                acc, lsum = carry
                loss, grads = grads_of(params, mb)
                acc = jax.tree_util.tree_map(jnp.add, acc, grads)
                return (acc, lsum + loss), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (acc, lsum), _ = jax.lax.scan(body, (zeros, 0.0), chunks)
            grads = to_owner(jax.tree_util.tree_map(lambda g: g / k, acc))
            new_params, new_opt = adamw_update(params, grads, opt_state,
                                               lr=lr)
            return new_params, new_opt, lsum / k

        return jax.jit(train_step, donate_argnums=(0, 1),
                       out_shardings=out_sh)

    def train_step(params, opt_state, batch):
        loss, grads = grads_of(params, batch)
        new_params, new_opt = adamw_update(params, to_owner(grads),
                                           opt_state,
                                           lr=_lr_at(lr, opt_state))
        return new_params, new_opt, loss

    return jax.jit(train_step, donate_argnums=(0, 1),
                   out_shardings=out_sh)


def _frozen_train_step(cfg, pcfg, mesh, lr, model, to_owner):
    """``build_train_step``'s step for a model that keeps leaves from the
    optimizer: the gradient is taken with respect to the others alone,
    AdamW updates those, and the model's own rule the frozen ones from what
    its ``loss_fn`` returned beside the loss."""
    if pcfg.pp > 1 or pcfg.gradient_merge_steps > 1:
        raise ValueError("a model with frozen leaves trains at pp == 1 "
                         "and gradient_merge_steps == 1")

    def train_step(params, opt_state, batch):
        train, frozen = model.split(params)
        (loss, aux), grads = jax.value_and_grad(
            lambda t: model.loss_fn({**t, **frozen}, batch, cfg, pcfg,
                                    mesh), has_aux=True)(train)
        new_train, new_opt = adamw_update(train, to_owner(grads), opt_state,
                                          lr=_lr_at(lr, opt_state))
        return ({**new_train, **model.update_frozen(frozen, aux)},
                new_opt, loss)

    return train_step


def _make_grad_acc(cfg, pcfg, mesh):
    """The accumulate-into-tree gradient step of build_accum_steps.
    Under pp>1 the per-chunk gradient comes from the compiled 1F1B ring
    — the same grads_of the fused train step uses, so gradient merge
    composes with pipeline identically in both engines (reference:
    auto_parallel_gradient_merge composing with the pipeline passes)."""
    _validate_pp_schedule(pcfg)
    if pcfg.pp > 1 and pcfg.pp_schedule in ("1f1b", "zbh1", "zbvpp"):
        def grads_of(params, batch):
            return _train_grads_1f1b(params, batch, cfg, pcfg, mesh)
    else:
        # pp>1 + gpipe rides loss_fn's pipeline_apply forward (GPipe
        # activation liveness — fine for small configs)
        def grads_of(params, batch):
            return jax.value_and_grad(
                lambda p: loss_fn(p, batch, cfg, pcfg, mesh))(params)

    def grad_acc(params, acc, batch):
        loss, grads = grads_of(params, batch)
        acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(a.dtype), acc, grads)
        return acc, loss
    return grad_acc


def build_accum_steps(cfg: GPTConfig, pcfg: ParallelConfig, mesh: Mesh,
                      lr=3e-4, state_specs=None):
    """Two-program gradient accumulation (the split form of
    gradient_merge_steps): `grad_step(params, acc, batch) -> (acc',
    loss)` runs one microbatch's fwd+bwd and fuses the += into the
    backward epilogue (acc donated — no extra HBM pass), and
    `apply_step(params, opt_state, acc, k) -> (params', opt_state',
    zeroed acc)` pays the bandwidth-bound AdamW update once per k
    chunks. Each program's HLO stays bench-sized, which matters on
    toolchains that choke on the k-times-larger fused-merge program.
    Under pp>1+1f1b each chunk's gradient runs the compiled pipeline
    ring (see _make_grad_acc), so gradient merge composes with pp in
    the split engine exactly as in the fused one."""
    grad_step = _make_grad_acc(cfg, pcfg, mesh)
    to_owner = _grads_to_owner(pcfg, mesh, state_specs)

    def apply_step(params, opt_state, acc, k):
        grads = to_owner(jax.tree_util.tree_map(lambda a: a / k, acc))
        new_p, new_o = adamw_update(params, grads, opt_state, lr=lr)
        zeroed = jax.tree_util.tree_map(jnp.zeros_like, acc)
        return new_p, new_o, zeroed

    # pin output shardings for the same reason as build_train_step:
    # GSPMD output-spec drift would reshard per call AND double-compile
    gs_out = ap_out = None
    if state_specs is not None:
        psh, osh, scalar = _state_out_shardings(mesh, *state_specs)
        gs_out = (psh, scalar)
        ap_out = (psh, osh, psh)
    return (jax.jit(grad_step, donate_argnums=(1,), out_shardings=gs_out),
            jax.jit(apply_step, donate_argnums=(0, 1, 2),
                    static_argnums=(3,), out_shardings=ap_out))


def init_grad_accum(params):
    """Zeroed grad accumulator matching the param tree (param dtype —
    bf16 accumulation over <=8 chunks is well within tolerance and
    halves the accumulator's HBM)."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def _adamw_leaf(p, m, v, g, step, lr, b1=0.9, b2=0.95, eps=1e-8,
                wd=0.1):
    """The single home of the per-leaf AdamW update math (f32 compute,
    storage dtypes preserved)."""
    gf = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    m_new = b1 * m.astype(jnp.float32) + (1 - b1) * gf
    v_new = b2 * v.astype(jnp.float32) + (1 - b2) * gf * gf
    sf = step.astype(jnp.float32) if hasattr(step, "astype") else \
        jnp.float32(step)
    c1 = 1 - b1 ** sf
    c2 = 1 - b2 ** sf
    upd = (m_new / c1) / (jnp.sqrt(v_new / c2) + eps) + wd * pf
    return ((pf - lr * upd).astype(p.dtype),
            m_new.astype(m.dtype), v_new.astype(v.dtype))


#: the model of this module, and the engine's default
GPT = TrainModel(init_params, shard_params, loss_fn)


def setup(cfg: GPTConfig, pcfg: ParallelConfig, seed=0, devices=None,
          model: TrainModel = None, lr=3e-4):
    """Returns (mesh, params, opt_state, train_step) of ``model`` (the GPT
    where none is given); moments only for what its optimizer sees."""
    model = model or GPT
    mesh = build_mesh(pcfg, devices)
    key = jax.random.PRNGKey(seed)
    params = model.init_params(cfg, pcfg, key)
    with mesh:
        params, specs = model.shard_params(params, mesh, cfg, pcfg)
        seen, seen_specs = model.split(params)[0], model.split(specs)[0]
        mspecs = moment_specs(seen, pcfg, seen_specs)
        opt_state = adamw_init(seen, pcfg, mesh, seen_specs, mspecs=mspecs)
    step_fn = build_train_step(cfg, pcfg, mesh, lr=lr,
                               state_specs=(specs, mspecs), model=model)
    return mesh, params, opt_state, step_fn
