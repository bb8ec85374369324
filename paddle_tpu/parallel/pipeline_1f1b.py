"""Compiled 1F1B: forward/backward-interleaved pipeline in ONE XLA
program with O(stages) activation liveness.

Reference being re-designed: PipelineParallel.forward_backward_pipeline
(fleet/meta_parallel/pipeline_parallel.py:547) — the host-driven 1F1B
loop whose point is bounding live activations at pipeline depth instead
of the microbatch count.

Why the GPipe-compiled path (parallel/pipeline.py) cannot bound memory:
its backward is jax.grad of a forward scan, and grad-of-scan saves the
per-tick residuals for ALL M+N-1 ticks — activation liveness grows with
M exactly like host GPipe. Here the backward is written explicitly:

  one lax.scan over T = M + 2(N-1) clock ticks; at tick t stage s
    F:  computes microbatch  m_f = t - s                (0 <= m_f < M)
    B:  computes microbatch  m_b = t - 2(N-1) + s       (0 <= m_b < M)
  activations hop forward with collective-permute, cotangents hop
  backward with the reverse permute, and each stage keeps a RING BUFFER
  of K = 2(N-1)+1 stage inputs — the in-flight window of the schedule.
  Backward recomputes the stage forward under jax.vjp from the stashed
  input (stage-granular rematerialization), so residuals are tick-local.

Peak live activations per stage: 2(N-1-s)+1 <= 2N-1, independent of M
(vs M for F-then-B/GPipe) — the same bound class as host 1F1B, achieved
with compiled collectives instead of NCCL p2p + host scheduling.

Trade-offs:
ramp ticks execute masked compute (SPMD stages run one program), so
wall-clock efficiency is M/(M+2(N-1)) per leg — the usual pipeline
bubble; and the last-stage head/loss runs (masked) on every stage.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.parallel.pp_schedule import PipeOp, Schedule


def _varying_cast(axis_name: str, x):
    """Idempotent cast-to-varying over `axis_name` (lax.cond branches and
    scan carries must agree on the varying-manual-axes type; zeros
    literals start unvarying)."""
    def one(a):
        vma = getattr(jax.typeof(a), "vma", frozenset())
        return a if axis_name in vma else lax.pcast(
            a, (axis_name,), to="varying")
    return jax.tree_util.tree_map(one, x)


def _vma_of(x) -> frozenset:
    return getattr(jax.typeof(x), "vma", frozenset())


def _make_za(x_microbatches, axis_name):
    """Factory for the activation-typed-zeros helper shared by every
    pipeline variant: vma = x_microbatches' vma + the pipeline axis
    (manual-tp callers feed tp-varying activations under sp, so cond
    branches / scan carries / vjp cotangents built from zeros must
    match that type, not just the pipeline axis)."""
    x_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype

    def _za(shape=None, dt=None):
        return _zeros_matching_vma(
            x_microbatches, shape=x_shape if shape is None else shape,
            dtype=dtype if dt is None else dt, extra=(axis_name,))

    return _za


def _zeros_matching_vma(ref, shape=None, dtype=None, extra=()):
    """Fresh zeros whose varying-manual-axes type matches `ref`'s vma
    (plus `extra` axes). Zero literals start unvarying on every manual
    axis; scan carries, cond branches and vjp cotangents must agree on
    vma, and under manual-tp stage bodies (round 5) different leaves
    legitimately carry different vma — tp-sharded weight grads are
    tp-varying while ln/bias grads are tp-invarying — so a blanket cast
    over the pipeline axis is not enough."""
    z = jnp.zeros(ref.shape if shape is None else shape,
                  ref.dtype if dtype is None else dtype)
    need = tuple((set(_vma_of(ref)) | set(extra)) - _vma_of(z))
    return lax.pcast(z, need, to="varying") if need else z


def _pipeline_epilogue(axis_name, s, n, loss, head, dx0_buf, grads,
                       grad_dtype, dtype, head_stage=None):
    """Shared final psums of every compiled pipeline variant: loss and
    head grads live on the head stage (the last *virtual* stage's
    device: n-1 for linear placements, 0 for the ZB-V placement), dx0
    on stage 0 — psum replicates them (masked elsewhere-zero). The dx0
    psum runs in f32: a bf16 dx0 all-reduce gets combined with the f32
    grad all-reduces into one variadic op, and XLA:CPU's
    AllReducePromotion pass CHECK-crashes cloning a mixed-dtype
    variadic all-reduce (TPU is unaffected)."""
    hs = n - 1 if head_stage is None else head_stage
    loss = lax.psum(jnp.where(s == hs, loss, 0.0), axis_name)
    if head is not None:
        head = jax.tree_util.tree_map(
            lambda g: lax.psum(jnp.where(s == hs, g,
                                         jnp.zeros_like(g)), axis_name),
            head)
    dx0 = lax.psum(
        jnp.where(s == 0, dx0_buf, jnp.zeros_like(dx0_buf))
        .astype(grad_dtype), axis_name).astype(dtype)
    grads = jax.tree_util.tree_map(lambda g: g[None], grads)
    return loss, grads, head, dx0


def _record_schedule_metrics(kind: str, builder, *dims):
    """Publish the compiled schedule's analytic cost as observability
    gauges — bubble fraction, makespan, geometry — keyed by schedule
    kind. Runs at TRACE time only (these pipeline bodies execute once,
    inside shard_map tracing), so the compiled program carries zero
    instrumentation; the numbers are the per-stage phase timing of the
    timeline the program actually executes (Schedule.simulate's
    event-driven model), which is the honest compiled-pipeline analog
    of host per-stage phase timers."""
    from paddle_tpu.observability import metrics as _met
    if not _met._ENABLED:
        return
    try:
        makespan, bubble = builder(*dims).simulate()
        r = _met.REGISTRY
        r.gauge("pipeline.bubble_fraction", schedule=kind).set(bubble)
        r.gauge("pipeline.makespan_ticks", schedule=kind).set(makespan)
        r.gauge("pipeline.stages", schedule=kind).set(dims[0])
        r.gauge("pipeline.microbatches", schedule=kind).set(dims[1])
        r.counter("pipeline.traces", schedule=kind).inc()
    except Exception:
        pass        # cost accounting must never break a train trace


def compiled_1f1b_schedule(n_stages: int, n_microbatches: int) -> Schedule:
    """The (stage, tick) -> op timeline this module compiles, as a
    pp_schedule.Schedule — so its dependency validity, makespan and
    peak-activation bound are checkable with the same machinery as the
    host schedules (the VERDICT 'schedule equivalence' artifact)."""
    n, m = n_stages, n_microbatches
    per_stage = []
    for s in range(n):
        ops = []
        for t in range(m + 2 * (n - 1)):
            mf = t - s
            if 0 <= mf < m:
                ops.append(PipeOp("F", s, mf))
            mb = t - 2 * (n - 1) + s
            if 0 <= mb < m:
                ops.append(PipeOp("B", s, mb))
        per_stage.append(ops)
    return Schedule("compiled-1F1B", n, m, per_stage)


def pipeline_train_1f1b(stage_fn: Callable, stage_params, x_microbatches,
                        last_stage_grad: Callable,
                        head_params=None,
                        axis_name: str = "pp",
                        grad_dtype=jnp.float32,
                        side_inputs=None):
    """Run the interleaved pipeline inside shard_map.

    stage_fn(params, x) -> y                   same signature per stage
        (with `side_inputs`: stage_fn(params, x, side) -> y)
    side_inputs: optional pytree of [M, ...] per-microbatch values every
        stage reads alongside its activation (attention masks, position
        ids — the reference PipelineLayer's tuple-valued stage IO,
        pp_layers.py:56). They are NON-differentiated side inputs: the
        forward leg indexes them at its microbatch, the backward leg's
        recompute closes over the SAME microbatch's values, and no
        cotangent is produced for them (masks/ids carry none).
    stage_params: pytree with leading dim 1 on each device (stage-
        stacked weights sharded over `axis_name`, as inside shard_map)
    x_microbatches: [M, ...] microbatched stage-0 input (replicated)
    last_stage_grad(y, head_params, mb_idx) -> (loss, dy, head_grads):
        the head + loss on the final stage's output; mb_idx is the
        microbatch index of this y (clipped during masked ramp ticks —
        use it to fetch labels/targets); dy is dLoss/dy. head_grads may
        be None. Runs (masked) on every stage per tick.
    head_params: the pytree handed to last_stage_grad. It is pcast to
        device-varying FIRST — differentiating wrt a replicated
        (unvarying) value inside shard_map inserts an automatic psum in
        the transpose, which would leak every stage's masked garbage
        head-gradients into the last stage's. Do NOT close over head
        weights inside last_stage_grad; pass them here.

    Returns (loss_total, stage_param_grads [leading dim 1],
             head_grads_total, dx0 [M, ...] input cotangents at stage 0)
    """
    n = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    t_total = m + 2 * (n - 1)
    k = 2 * (n - 1) + 1
    _record_schedule_metrics("1f1b", compiled_1f1b_schedule, n, m)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [((i + 1) % n, i) for i in range(n)]

    my_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    def _varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    head_params_v = (None if head_params is None else
                     jax.tree_util.tree_map(_varying, head_params))

    def _stage(params, x, mb_idx):
        if side_inputs is None:
            return stage_fn(params, x)
        side = jax.tree_util.tree_map(lambda l: l[mb_idx], side_inputs)
        return stage_fn(params, x, side)

    x_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype
    _za = _make_za(x_microbatches, axis_name)
    act0 = _za()
    cot0 = _za()
    stash0 = _za((k,) + x_shape)
    grads0 = jax.tree_util.tree_map(
        lambda p: _zeros_matching_vma(p, dtype=grad_dtype,
                                      extra=(axis_name,)), my_params)
    # structure probe (unused outputs are DCE'd by XLA)
    probe_l, _, probe_hg = last_stage_grad(_za(), head_params_v,
                                           jnp.zeros((), jnp.int32))
    head0 = None if probe_hg is None else jax.tree_util.tree_map(
        lambda g: _zeros_matching_vma(g, dtype=grad_dtype,
                                      extra=(axis_name,)), probe_hg)
    # the loss carry matches the head's own vma (a manual-ep head
    # returns per-member partial losses, dp-varying)
    loss0 = _zeros_matching_vma(probe_l, shape=(), dtype=grad_dtype,
                                extra=(axis_name,))
    dx0_buf0 = _za((m,) + x_shape)

    def tick(carry, t):
        act_in, cot_in, stash, grads, head, loss, dx0_buf = carry
        # ---------------- forward leg: microbatch m_f = t - s
        mf = t - s
        f_active = (mf >= 0) & (mf < m)
        f_act = jnp.where(s == 0, x_microbatches[jnp.clip(mf, 0, m - 1)],
                          act_in)
        y = _stage(my_params, f_act, jnp.clip(mf, 0, m - 1))
        # stash this tick's stage input (ring slot t mod K) BEFORE the
        # backward read: the last stage's B reads its own tick's slot
        stash = lax.dynamic_update_index_in_dim(
            stash, f_act, jnp.mod(t, k), 0)
        # ---------------- last-stage seed: loss + dLoss/dy of THIS y
        loss_mb, dy_seed, hgrads = last_stage_grad(
            y, head_params_v, jnp.clip(mf, 0, m - 1))
        is_last = s == n - 1
        # ---------------- backward leg: microbatch m_b = t - 2(N-1) + s
        mb = t - 2 * (n - 1) + s
        b_active = (mb >= 0) & (mb < m)
        cot = jnp.where(is_last, dy_seed, cot_in)
        x_b = stash[jnp.mod(t - 2 * (n - 1 - s), k)]
        mb_c = jnp.clip(mb, 0, m - 1)
        _, vjp = jax.vjp(lambda p, xx: _stage(p, xx, mb_c),
                         my_params, x_b)
        dp, dx = vjp(cot.astype(y.dtype))
        gmask = b_active
        grads = jax.tree_util.tree_map(
            lambda g, d: g + jnp.where(gmask, d.astype(g.dtype), 0),
            grads, dp)
        if head is not None:
            hmask = is_last & f_active
            head = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(hmask, d.astype(g.dtype), 0),
                head, hgrads)
        loss = loss + jnp.where(is_last & f_active, loss_mb, 0.0)
        # stage-0 input cotangents (for the embedding backward outside)
        dx0_buf = lax.cond(
            (s == 0) & b_active,
            lambda buf: lax.dynamic_update_index_in_dim(
                buf, dx.astype(dtype), jnp.clip(mb, 0, m - 1), 0),
            lambda buf: buf, dx0_buf)
        # ---------------- message hops
        act_out = lax.ppermute(y, axis_name, fwd_perm)
        cot_out = lax.ppermute(dx, axis_name, bwd_perm)
        return (act_out, cot_out, stash, grads, head, loss,
                dx0_buf), None

    carry0 = (act0, cot0, stash0, grads0, head0, loss0, dx0_buf0)
    carry, _ = lax.scan(tick, carry0, jnp.arange(t_total))
    _, _, _, grads, head, loss, dx0_buf = carry
    return _pipeline_epilogue(axis_name, s, n, loss, head, dx0_buf,
                              grads, grad_dtype, dtype)


# ---------------------------------------------------------------------
# Compiled interleaved virtual-pipeline (VPP) — round 3
# ---------------------------------------------------------------------

def compiled_interleaved_schedule(n_stages: int, n_microbatches: int,
                                  n_chunks: int) -> Schedule:
    """The lockstep timeline `pipeline_train_interleaved` compiles, as a
    checkable pp_schedule.Schedule (reference analog:
    PipelineParallelWithInterleave, pipeline_parallel.py:1143 /
    pipeline_vpp.py).

    Virtual stage of (chunk j, device s) is sigma = j*n + s: consecutive
    virtual stages sit on consecutive ring devices, with the chunk
    boundary riding the ring's (n-1 -> 0) wrap — so ONE collective
    permute per tick serves both intra- and inter-chunk activation
    transfer. At tick t, virtual stage sigma forwards microbatch t -
    sigma and backwards t - 2(Ng-1) + sigma (Ng = n*v virtual stages).
    """
    n, m, v = n_stages, n_microbatches, n_chunks
    ng = n * v
    per_stage = []
    for s in range(n):
        ops = []
        for t in range(m + 2 * (ng - 1)):
            for j in range(v):
                sigma = j * n + s
                mf = t - sigma
                if 0 <= mf < m:
                    ops.append(PipeOp("F", s, mf, j))
                mb = t - 2 * (ng - 1) + sigma
                if 0 <= mb < m:
                    ops.append(PipeOp("B", s, mb, j))
        per_stage.append(ops)
    return Schedule(f"compiled-VPP{v}", n, m, per_stage, n_chunks=v)


def pipeline_train_interleaved(stage_fn: Callable, stage_params,
                               x_microbatches,
                               last_stage_grad: Callable,
                               head_params=None,
                               axis_name: str = "pp",
                               num_chunks: int = 2,
                               grad_dtype=jnp.float32):
    """Interleaved VPP inside shard_map: each device runs `num_chunks`
    virtual-stage "lanes"; lane j on device s is virtual stage j*n + s
    of an (n*v)-deep pipeline. Consecutive virtual stages sit on ring
    neighbors, so ONE ppermute per tick serves both intra- and
    inter-chunk hops (the chunk boundary rides the n-1 -> 0 wrap).

    Same contract as pipeline_train_1f1b except stage_params leaves
    carry per-device leading dims [1, v, ...] (stage dim sharded over
    `axis_name`, chunk dim local); returned grads match that layout.

    Memory design: the per-tick lane work runs as INNER lax.scans
    (forward lanes ascending, then the head once, then backward lanes),
    so only ONE lane's vjp residuals are live at a time — the
    rematerialization window shrinks from L/pp layers (1F1B) to
    L/(pp*v), which is VPP's activation-memory lever. The stash grows
    to v rings of 2(nv-1)+1 microbatch inputs (cheap next to
    residuals at transformer scale).
    """
    n = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    v = num_chunks
    ng = n * v
    m = x_microbatches.shape[0]
    t_total = m + 2 * (ng - 1)
    k = 2 * (ng - 1) + 1
    _record_schedule_metrics(f"vpp{v}", compiled_interleaved_schedule,
                             n, m, v)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [((i + 1) % n, i) for i in range(n)]

    # [v, ...] per-device chunk-stacked params
    lane_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    def _varying(x):
        return lax.pcast(x, (axis_name,), to="varying")

    head_params_v = (None if head_params is None else
                     jax.tree_util.tree_map(_varying, head_params))

    x_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype
    acts0 = _varying(jnp.zeros((v,) + x_shape, dtype))
    cots0 = _varying(jnp.zeros((v,) + x_shape, dtype))
    stash0 = _varying(jnp.zeros((v, k) + x_shape, dtype))
    grads0 = jax.tree_util.tree_map(
        lambda p: _varying(jnp.zeros(p.shape, grad_dtype)), lane_params)
    _, _, probe_hg = last_stage_grad(jnp.zeros(x_shape, dtype),
                                     head_params_v,
                                     jnp.zeros((), jnp.int32))
    head0 = None if probe_hg is None else jax.tree_util.tree_map(
        lambda g: _varying(jnp.zeros(g.shape, grad_dtype)), probe_hg)
    dx0_buf0 = _varying(jnp.zeros((m,) + x_shape, dtype))
    lane_idx = jnp.arange(v, dtype=jnp.int32)

    def tick(carry, t):
        acts_in, cots_in, stash, grads, head, loss, dx0_buf = carry
        sigma = lane_idx * n + s                       # [v]
        mf = t - sigma
        # lane j's forward input: lane j-1's (permuted) output at the
        # chunk boundary (s==0), else lane j's own ring input; lane 0
        # at s==0 reads the microbatch stream
        src0 = jnp.concatenate(
            [x_microbatches[jnp.clip(t - s, 0, m - 1)][None],
             acts_in[:-1]], axis=0)
        act_sel = jnp.where(s == 0, src0, acts_in)

        # vectorized stash write (outside the lane scans so the big
        # [v, k, ...] buffer is never copied through scan outputs)
        stash = lax.dynamic_update_slice_in_dim(
            stash, act_sel[:, None], jnp.mod(t, k), 1)

        def fwd_body(_, xs):
            act_j, params_j = xs
            return None, stage_fn(params_j, act_j)

        _, ys = lax.scan(fwd_body, None, (act_sel, lane_params))

        # head/loss: the LAST virtual stage is lane v-1 on device n-1;
        # paid once per tick (as in 1F1B)
        mf_last = t - ((v - 1) * n + s)
        f_active_last = (mf_last >= 0) & (mf_last < m)
        loss_mb, dy_seed, hgrads = last_stage_grad(
            ys[v - 1], head_params_v, jnp.clip(mf_last, 0, m - 1))
        is_last = s == n - 1
        if head is not None:
            hmask = is_last & f_active_last
            head = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(hmask, d.astype(g.dtype), 0),
                head, hgrads)
        loss = loss + jnp.where(is_last & f_active_last, loss_mb, 0.0)

        # lane j's cotangent: lane j+1's (permuted) dx at the chunk
        # boundary (s==n-1), else lane j's own ring input; lane v-1 at
        # s==n-1 seeds from the head
        cot_next = jnp.concatenate(
            [cots_in[1:], dy_seed.astype(dtype)[None]], axis=0)
        cot_sel = jnp.where(s == n - 1, cot_next, cots_in)
        mb = t - 2 * (ng - 1) + sigma                  # [v]
        b_active = (mb >= 0) & (mb < m)

        def bwd_body(_, xs):
            jidx, cot_j, stash_j, params_j, grads_j = xs
            sig = jidx * n + s
            x_b = stash_j[jnp.mod(t - 2 * (ng - 1 - sig), k)]
            _, vjp = jax.vjp(stage_fn, params_j, x_b)
            dp, dx = vjp(cot_j.astype(x_b.dtype))
            ba = (t - 2 * (ng - 1) + sig >= 0) & \
                (t - 2 * (ng - 1) + sig < m)
            grads_j = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(ba, d.astype(g.dtype), 0),
                grads_j, dp)
            return None, (dx, grads_j)

        _, (dxs, grads) = lax.scan(
            bwd_body, None,
            (lane_idx, cot_sel, stash, lane_params, grads))

        dx0_buf = lax.cond(
            (s == 0) & b_active[0],
            lambda buf: lax.dynamic_update_index_in_dim(
                buf, dxs[0].astype(dtype), jnp.clip(mb[0], 0, m - 1), 0),
            lambda buf: buf, dx0_buf)

        acts_out = lax.ppermute(ys, axis_name, fwd_perm)
        cots_out = lax.ppermute(dxs.astype(dtype), axis_name, bwd_perm)
        return (acts_out, cots_out, stash, grads, head, loss,
                dx0_buf), None

    carry0 = (acts0, cots0, stash0, grads0, head0,
              _varying(jnp.zeros((), grad_dtype)), dx0_buf0)
    carry, _ = lax.scan(tick, carry0, jnp.arange(t_total))
    _, _, _, grads, head, loss, dx0_buf = carry
    return _pipeline_epilogue(axis_name, s, n, loss, head, dx0_buf,
                              grads, grad_dtype, dtype)


# ---------------------------------------------------------------------
# Compiled zero-bubble ZBH1 — round 4
# ---------------------------------------------------------------------

def _zb_w_recurrence(ng: int, m: int, sigma: int):
    """The (static) W-firing recurrence of virtual stage `sigma` in an
    ng-deep pipeline: at tick t, with nW W's already retired, fire iff
    pending B's exist AND (the stage's F lane is idle — cooldown/drain
    — OR the backlog exceeds sigma, the zero-bubble 'defer the first
    sigma weight-grads' policy, pp_schedule.py schedule_zbh1). Yields
    (t, fired) until all m W's retire. ZBH1 instantiates it with
    ng = n_stages, sigma = s; ZB-V with ng = 2n and the V-placement
    virtual depths."""
    nW, t = 0, 0
    while nW < m:
        nB = min(max(t - 2 * (ng - 1) + sigma + 1, 0), m)
        f_active = 0 <= t - sigma < m
        pending = nB - nW
        fired = pending > 0 and ((not f_active) or pending > sigma)
        if fired:
            nW += 1
        yield t, fired
        t += 1


def zbh1_extra_ticks(n_stages: int, n_microbatches: int) -> int:
    """Drain ticks past the 1F1B grid that the deferred W backlog
    needs (worst on the last stage, which has no F-idle cooldown)."""
    T = n_microbatches + 2 * (n_stages - 1)
    extra = 0
    for s in range(n_stages):
        last = max(t for t, f in _zb_w_recurrence(
            n_stages, n_microbatches, s) if f)
        extra = max(extra, last + 1 - T)
    return max(extra, 0)


def compiled_zbh1_schedule(n_stages: int, n_microbatches: int) -> Schedule:
    """The exact (stage, tick) -> phases timeline `pipeline_train_zbh1`
    compiles, as a checkable Schedule (the VERDICT schedule-equivalence
    artifact). F/B ride the compiled-1F1B grid; B is input-grad ONLY
    (cost 2: stage-granular forward recompute + dx) and the deferred W
    (cost 2: recompute + dW) fires per the backlog recurrence. The
    fused compiled 1F1B's honest durations are {F:1, B:3} (recompute +
    dx + dW); zero-bubble pays one extra recompute unit per microbatch
    to move W off the critical path into cond-skipped idle ticks.

    Reference: pipeline_zero_bubble.py:62 (ZBH1's B/W split and
    W-fills-bubbles placement)."""
    n, m = n_stages, n_microbatches
    T = m + 2 * (n - 1) + zbh1_extra_ticks(n, m)
    per_stage = []
    for s in range(n):
        fires = dict(_zb_w_recurrence(n, m, s))
        ops = []
        nW = 0
        for t in range(T):
            mf = t - s
            if 0 <= mf < m:
                ops.append(PipeOp("F", s, mf))
            mb = t - 2 * (n - 1) + s
            if 0 <= mb < m:
                ops.append(PipeOp("B", s, mb))
            if fires.get(t, False):
                ops.append(PipeOp("W", s, nW))
                nW += 1
        per_stage.append(ops)
    return Schedule("compiled-ZBH1", n, m, per_stage,
                    durations={"F": 1.0, "B": 2.0, "W": 2.0})


def _phase_after(x, *deps):
    """Order phase `x`'s computation after EVERY leaf of `deps` via an
    optimization_barrier data dependency. Needed when the stage body
    carries manual collectives: XLA's concurrent thunk executor may
    issue data-independent in-branch collectives in DIFFERENT orders on
    different devices, and two devices of the same subgroup blocked on
    each other's pending collective deadlock the rendezvous (observed
    on XLA:CPU for zbvpp+sp, round 5). All leaves matter — a
    single-leaf dep leaves the other leaves' producing collectives
    off-chain and the race stands. A plain `+ 0*dep` would be
    algebraically simplified away; the barrier survives.

    vma hygiene: optimization_barrier UNIFIES the varying-manual-axes
    type across its operands, so a dep leaf varying over axes `x` does
    not vary over (e.g. a tp-sharded weight grad vs a tp-invarying
    cotangent) would widen x's type and break downstream vjp typing.
    Deps are reduced to per-leaf scalars (an op-level dependency — XLA
    cannot partially execute the producing op), and scalars with
    excess axes are psum'd over exactly those axes (the psum is itself
    a uniform unconditional collective, correctly ordered after the
    dep's producers)."""
    xv = _vma_of(x)
    toks, excess = [], {}
    for d in deps:
        for leaf in jax.tree_util.tree_leaves(d):
            t = jnp.ravel(leaf)[0]
            lv = _vma_of(leaf)
            if lv <= xv:
                toks.append(t)
            else:
                ax = tuple(sorted(lv - xv))
                excess.setdefault(ax, []).append(t.astype(jnp.float32))
    for ax, ts in excess.items():
        toks.append(lax.psum(sum(ts), ax))
    out = lax.optimization_barrier((x, *toks))
    return out[0]


def pipeline_train_zbh1(stage_fn: Callable, stage_params, x_microbatches,
                        last_stage_grad: Callable,
                        head_params=None,
                        axis_name: str = "pp",
                        grad_dtype=jnp.float32,
                        side_inputs=None,
                        serialize_phases: bool = False):
    """Zero-bubble ZBH1 on the compiled 1F1B ring.

    Two departures from `pipeline_train_1f1b`:

    1. CONDITIONAL phases. The lockstep 1F1B executes masked compute on
       every ramp/cooldown tick — the pipeline bubble is paid as wasted
       FLOPs. Here each phase is a `lax.cond` on a device-varying
       predicate (legal inside shard_map: each core branches on its own
       scalar), so inactive phases cost ~nothing and the collectives
       stay uniform (every core reaches both ppermutes every tick).

    2. SPLIT backward. B computes input-grads only (vjp wrt x — the
       inter-stage critical path); the weight-grad W is deferred into a
       (x, gy) stash and retired by the backlog recurrence — same tick
       when the backlog exceeds s (steady state), every tick once the
       F lane goes idle (cooldown), plus `zbh1_extra_ticks` drain ticks
       after the grid (W-only, no collectives). Reference:
       pipeline_zero_bubble.py:62. Memory premium over 1F1B: the
       (n+1)-deep W stash — reported by the memory probe.

    Same contract and return values as pipeline_train_1f1b, including
    `side_inputs` (non-differentiated [M, ...] per-microbatch values:
    the forward leg indexes them at its microbatch, the B recompute at
    its, and the deferred W recompute at the microbatch it retires —
    W's fire in microbatch order, so nW IS that index).

    `serialize_phases=True` (the manual-tp caller) additionally orders
    the ring permutes after the W phase via `_phase_after`: with
    collectives inside the cond-gated phases, a permute racing a
    pending subgroup collective on another device deadlocks the
    rendezvous. F->head->B->W are already serialized by true data deps
    (dy_seed, the W stash).
    """
    n = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    # static mirror of m/n for the python-level drain-tick count
    t_total = m + 2 * (n - 1)
    k = 2 * (n - 1) + 1
    wk = n + 1                     # W backlog bound: s+1 <= n
    _record_schedule_metrics("zbh1", compiled_zbh1_schedule, n, m)
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [((i + 1) % n, i) for i in range(n)]

    my_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    def _v(x):
        return _varying_cast(axis_name, x)

    def _stage(params, x, mb_idx):
        if side_inputs is None:
            return stage_fn(params, x)
        side = jax.tree_util.tree_map(lambda l: l[mb_idx], side_inputs)
        return stage_fn(params, x, side)

    head_params_v = (None if head_params is None else
                     jax.tree_util.tree_map(_v, head_params))

    x_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype
    _za = _make_za(x_microbatches, axis_name)
    act0 = _za()
    cot0 = _za()
    stash0 = _za((k,) + x_shape)
    wstash_x0 = _za((wk,) + x_shape)
    wstash_gy0 = _za((wk,) + x_shape)
    # grad accumulators match each PARAM leaf's vma (tp-sharded leaves
    # are tp-varying under a manual-tp stage body, ln/bias leaves not)
    grads0 = jax.tree_util.tree_map(
        lambda p: _zeros_matching_vma(p, dtype=grad_dtype,
                                      extra=(axis_name,)), my_params)
    probe_l, _, probe_hg = last_stage_grad(_za(), head_params_v,
                                           jnp.zeros((), jnp.int32))
    head0 = None if probe_hg is None else jax.tree_util.tree_map(
        lambda g: _zeros_matching_vma(g, dtype=grad_dtype,
                                      extra=(axis_name,)), probe_hg)
    # the loss carry matches the head's own vma (a manual-ep head
    # returns per-member partial losses, dp-varying)
    loss0 = _zeros_matching_vma(probe_l, shape=(), dtype=grad_dtype,
                                extra=(axis_name,))
    dx0_buf0 = _za((m,) + x_shape)

    def w_phase(nW, grads, wstash_x, wstash_gy, fire):
        """Retire ONE deferred weight-grad when `fire`: recompute the
        stage forward from the stashed input under vjp wrt params and
        accumulate dW. Identity (skipped work) otherwise. W's retire in
        microbatch order, so nW doubles as the side-input index."""
        def do(g):
            x_w = wstash_x[jnp.mod(nW, wk)]
            gy_w = wstash_gy[jnp.mod(nW, wk)]
            mb_w = jnp.clip(nW, 0, m - 1)
            _, vjpp = jax.vjp(lambda pp: _stage(pp, x_w, mb_w),
                              my_params)
            (dp,) = vjpp(gy_w)
            return _v(jax.tree_util.tree_map(
                lambda a, d: a + d.astype(a.dtype), g, dp))
        grads = lax.cond(fire, do, lambda g: _v(g), grads)
        return nW + jnp.where(fire, 1, 0), grads

    def tick(carry, t):
        (act_in, cot_in, stash, wstash_x, wstash_gy, nW, grads, head,
         loss, dx0_buf) = carry
        # ---------------- forward (cond-gated)
        mf = t - s
        f_active = (mf >= 0) & (mf < m)
        mf_c = jnp.clip(mf, 0, m - 1)
        f_act = jnp.where(s == 0, x_microbatches[mf_c], act_in)
        y = lax.cond(f_active,
                     lambda: _v(_stage(my_params, f_act, mf_c)),
                     lambda: _za())
        stash = lax.dynamic_update_index_in_dim(
            stash, f_act, jnp.mod(t, k), 0)
        # ---------------- last-stage loss seed (masked adds, as 1F1B)
        loss_mb, dy_seed, hgrads = last_stage_grad(
            y, head_params_v, jnp.clip(mf, 0, m - 1))
        is_last = s == n - 1
        if head is not None:
            hmask = is_last & f_active
            head = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(hmask, d.astype(g.dtype), 0),
                head, hgrads)
        loss = loss + jnp.where(is_last & f_active, loss_mb, 0.0)
        # ---------------- backward dx (cond-gated, input-grad ONLY)
        mb = t - 2 * (n - 1) + s
        b_active = (mb >= 0) & (mb < m)
        cot = jnp.where(is_last, dy_seed, cot_in)
        if serialize_phases:
            # B strictly after the WHOLE head vjp (its param-grad
            # collectives are off the dy_seed dataflow path)
            cot = _phase_after(cot, loss_mb,
                               hgrads if hgrads is not None else ())
        x_b = stash[jnp.mod(t - 2 * (n - 1 - s), k)]
        mb_c = jnp.clip(mb, 0, m - 1)

        def b_do():
            _, vjpx = jax.vjp(
                lambda xx: _stage(my_params, xx, mb_c), x_b)
            (dx,) = vjpx(cot.astype(y.dtype))
            return _v(dx)

        dx = lax.cond(b_active, b_do, lambda: _za(dt=y.dtype))
        # stash (x, gy) for the deferred weight-grad; slot nB mod wk
        nB_prev = jnp.clip(t - 2 * (n - 1) + s, 0, m)  # B's before t
        wslot = jnp.mod(nB_prev, wk)
        wstash_x, wstash_gy = lax.cond(
            b_active,
            lambda wx, wg: (
                lax.dynamic_update_index_in_dim(wx, x_b, wslot, 0),
                lax.dynamic_update_index_in_dim(
                    wg, cot.astype(dtype), wslot, 0)),
            lambda wx, wg: (wx, wg), wstash_x, wstash_gy)
        # ---------------- deferred weight-grad (backlog recurrence)
        nB = jnp.clip(t - 2 * (n - 1) + s + 1, 0, m)
        pending = nB - nW
        fire = (pending > 0) & (~f_active | (pending > s))
        nW, grads = w_phase(nW, grads, wstash_x, wstash_gy, fire)
        # ---------------- stage-0 input cotangents
        dx0_buf = lax.cond(
            (s == 0) & b_active,
            lambda buf: lax.dynamic_update_index_in_dim(
                buf, dx.astype(dtype), jnp.clip(mb, 0, m - 1), 0),
            lambda buf: buf, dx0_buf)
        # ---------------- hops
        y_h, dx_h = y, dx
        if serialize_phases:
            y_h = _phase_after(y, grads)
            dx_h = _phase_after(dx, y_h)
        act_out = lax.ppermute(y_h, axis_name, fwd_perm)
        if serialize_phases:
            dx_h = _phase_after(dx_h, act_out)
        cot_out = lax.ppermute(dx_h, axis_name, bwd_perm)
        return (act_out, cot_out, stash, wstash_x, wstash_gy, nW, grads,
                head, loss, dx0_buf), None

    carry0 = (act0, cot0, stash0, wstash_x0, wstash_gy0,
              _v(jnp.zeros((), jnp.int32)), grads0, head0,
              loss0, dx0_buf0)
    carry, _ = lax.scan(tick, carry0, jnp.arange(t_total))
    (_, _, _, wstash_x, wstash_gy, nW, grads, head, loss,
     dx0_buf) = carry

    # drain: retire the remaining W backlog. Under a manual-tp stage
    # body the W vjp recompute DOES replay tp collectives in its
    # fire-gated cond — safe because the fire predicate is uniform
    # across each tp subgroup (it depends only on the pp stage index)
    n_extra = zbh1_extra_ticks(
        int(n) if isinstance(n, int) else n, m)

    def drain(carry, _t):
        nW, grads = carry
        fire = nW < m
        nW, grads = w_phase(nW, grads, wstash_x, wstash_gy, fire)
        return (nW, grads), None

    if n_extra > 0:
        (nW, grads), _ = lax.scan(drain, (nW, grads),
                                  jnp.arange(n_extra))

    return _pipeline_epilogue(axis_name, s, n, loss, head, dx0_buf,
                              grads, grad_dtype, dtype)


# ---------------------------------------------------------------------
# Compiled zero-bubble ZB-V (ZBVPP) — round 4
# ---------------------------------------------------------------------

def zbvpp_extra_ticks(n_stages: int, n_microbatches: int) -> int:
    """Drain ticks past the ZB-V grid (m + 2(2n-1) ticks) that the
    deferred W backlogs need, worst over both lanes of every device."""
    ng = 2 * n_stages
    T = n_microbatches + 2 * (ng - 1)
    extra = 0
    for sigma in range(ng):
        last = max(t for t, f in _zb_w_recurrence(
            ng, n_microbatches, sigma) if f)
        extra = max(extra, last + 1 - T)
    return max(extra, 0)


def compiled_zbvpp_schedule(n_stages: int,
                            n_microbatches: int) -> Schedule:
    """The exact (device, tick) -> phases timeline `pipeline_train_zbvpp`
    compiles, as a checkable Schedule (chunk_dirs=[1,-1]: the ZB-V
    placement — device s holds virtual stages s and 2n-1-s, so both
    chunk turnarounds are device-local and the last virtual stage sits
    on DEVICE 0). F/B ride the lockstep grid of the 2n-deep virtual
    pipeline; B is input-grad only (cost 2: stage recompute + dx) and
    each virtual stage's deferred W (cost 2: recompute + dW) fires per
    the zero-bubble backlog recurrence with defer bound sigma.

    Reference: pipeline_zero_bubble.py:151 (ZBVPP's B/W split and V
    placement)."""
    n, m = n_stages, n_microbatches
    ng = 2 * n
    T = m + 2 * (ng - 1) + zbvpp_extra_ticks(n, m)
    per_stage = []
    for s in range(n):
        sig = {0: s, 1: ng - 1 - s}
        fires = {c: dict(_zb_w_recurrence(ng, m, sig[c]))
                 for c in (0, 1)}
        nw = {0: 0, 1: 0}
        ops = []
        for t in range(T):
            for c in (0, 1):
                mf = t - sig[c]
                if 0 <= mf < m:
                    ops.append(PipeOp("F", s, mf, c))
            # backward order lane1-then-lane0 mirrors the compiled
            # tick (lane0's cot at device n-1 is lane1's previous dx)
            for c in (1, 0):
                mb = t - 2 * (ng - 1) + sig[c]
                if 0 <= mb < m:
                    ops.append(PipeOp("B", s, mb, c))
            for c in (0, 1):
                if fires[c].get(t, False):
                    ops.append(PipeOp("W", s, nw[c], c))
                    nw[c] += 1
        per_stage.append(ops)
    return Schedule("compiled-ZBVPP", n, m, per_stage, n_chunks=2,
                    chunk_dirs=[1, -1],
                    durations={"F": 1.0, "B": 2.0, "W": 2.0})


def pipeline_train_zbvpp(stage_fn: Callable, stage_params,
                         x_microbatches, last_stage_grad: Callable,
                         head_params=None,
                         axis_name: str = "pp",
                         grad_dtype=jnp.float32,
                         side_inputs=None,
                         serialize_phases: bool = False):
    """Zero-bubble ZB-V on the compiled ring: interleaved VPP with TWO
    chunks in V placement + the ZBH1 dx/dW split, in ONE XLA program.

    Reference being re-designed: pipeline_zero_bubble.py:151 (ZBVPP) —
    there a pass emits B/W-split job lists per rank; here the whole
    schedule is a lax.scan whose phases are cond-gated per device.

    Placement (the 'V'): device s holds virtual stages s (lane 0,
    forward direction) and 2n-1-s (lane 1, reverse direction). Both
    chunk boundaries are device-local hops:
      - vstage n-1 -> n: lane 0's output on device n-1 feeds lane 1
        there NEXT tick (carried, no collective);
      - vstage n's dx -> vstage n-1: lane 1's dx on device n-1 feeds
        lane 0's backward there next tick.
    The last virtual stage (2n-1) sits on DEVICE 0: the head/loss are
    masked to s==0, and — since vstage 0 is also on device 0 — the
    input cotangents dx0 never leave it. Ring traffic per tick is two
    ppermutes: the forward ring carries (lane-0 activations, lane-1
    cotangents), the reverse ring carries (lane-1 activations, lane-0
    cotangents).

    Grid: virtual stage sigma forwards microbatch t - sigma and
    backwards (dx only) t - 2(2n-1) + sigma; each lane defers its
    weight-grads into an (x, gy) stash retired by the backlog
    recurrence with defer bound sigma (`_zb_w_recurrence`), plus
    `zbvpp_extra_ticks` collective-free drain ticks.

    Same contract as pipeline_train_1f1b except stage_params leaves
    carry per-device leading dims [1, 2, ...]: [s][0] = vstage s
    params, [s][1] = vstage 2n-1-s params; returned grads match. The
    stage body must be collective-free (the ZBH1 cond-gating
    constraint, _validate_pp_schedule). `side_inputs` follows the
    1f1b/zbh1 contract (non-differentiated [M, ...] per-microbatch
    values; every lane's F/B/W recompute indexes them at its own
    microbatch — W's retire in mb order so nW is that index).
    """
    n = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    ng = 2 * n
    t_total = m + 2 * (ng - 1)
    _record_schedule_metrics("zbvpp", compiled_zbvpp_schedule, n, m)
    k0 = 2 * (ng - 1) + 1       # lane-0 F->B lag 2(2n-1-s), worst s=0
    k1 = 2 * (n - 1) + 1        # lane-1 F->B lag 2s, worst s=n-1
    wk0 = n + 1                 # lane-0 W backlog <= s+1 <= n
    wk1 = ng + 1                # lane-1 W backlog <= sigma1+1 <= 2n
    fwd_perm = [(i, (i + 1) % n) for i in range(n)]
    bwd_perm = [((i + 1) % n, i) for i in range(n)]

    lane_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    params0 = jax.tree_util.tree_map(lambda p: p[0], lane_params)
    params1 = jax.tree_util.tree_map(lambda p: p[1], lane_params)
    sigma1 = ng - 1 - s

    def _v(x):
        return _varying_cast(axis_name, x)

    def _stage(params, x, mb_idx):
        if side_inputs is None:
            return stage_fn(params, x)
        side = jax.tree_util.tree_map(lambda l: l[mb_idx], side_inputs)
        return stage_fn(params, x, side)

    head_params_v = (None if head_params is None else
                     jax.tree_util.tree_map(_v, head_params))

    x_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype
    _za = _make_za(x_microbatches, axis_name)
    zact = _za
    grads0 = jax.tree_util.tree_map(
        lambda p: _zeros_matching_vma(p, dtype=grad_dtype,
                                      extra=(axis_name,)), lane_params)
    probe_l, _, probe_hg = last_stage_grad(_za(), head_params_v,
                                           jnp.zeros((), jnp.int32))
    head0 = None if probe_hg is None else jax.tree_util.tree_map(
        lambda g: _zeros_matching_vma(g, dtype=grad_dtype,
                                      extra=(axis_name,)), probe_hg)
    loss0 = _zeros_matching_vma(probe_l, shape=(), dtype=grad_dtype,
                                extra=(axis_name,))

    def w_phase(lane_p, wk, nW, lane_grads, wx, wgy, fire):
        """Retire ONE deferred weight-grad of one lane when `fire`.
        W's retire in microbatch order, so nW is the side index."""
        def do(g):
            x_w = wx[jnp.mod(nW, wk)]
            gy_w = wgy[jnp.mod(nW, wk)]
            mb_w = jnp.clip(nW, 0, m - 1)
            _, vjpp = jax.vjp(lambda pp: _stage(pp, x_w, mb_w), lane_p)
            (dp,) = vjpp(gy_w)
            return _v(jax.tree_util.tree_map(
                lambda a, d: a + d.astype(a.dtype), g, dp))
        lane_grads = lax.cond(fire, do, lambda g: _v(g), lane_grads)
        return nW + jnp.where(fire, 1, 0), lane_grads

    def tick(carry, t):
        (a0_in, a1_in, c0_in, c1_in, y0_prev, dx1_prev,
         stash0, stash1, wx0, wgy0, wx1, wgy1, nW0, nW1,
         grads, head, loss, dx0_buf) = carry
        g0 = jax.tree_util.tree_map(lambda g: g[0], grads)
        g1 = jax.tree_util.tree_map(lambda g: g[1], grads)
        # ---------------- forward lane 0 (vstage s)
        mf0 = t - s
        f0_active = (mf0 >= 0) & (mf0 < m)
        mf0_c = jnp.clip(mf0, 0, m - 1)
        x0 = jnp.where(s == 0, x_microbatches[mf0_c], a0_in)
        y0 = lax.cond(f0_active,
                      lambda: _v(_stage(params0, x0, mf0_c)), zact)
        stash0 = lax.dynamic_update_index_in_dim(
            stash0, x0, jnp.mod(t, k0), 0)
        # ---------------- forward lane 1 (vstage 2n-1-s)
        mf1 = t - sigma1
        f1_active = (mf1 >= 0) & (mf1 < m)
        mf1_c = jnp.clip(mf1, 0, m - 1)
        x1 = jnp.where(s == n - 1, y0_prev, a1_in)
        if serialize_phases:
            # the two lanes have no natural data dep within a tick
            # (x1 comes from LAST tick's y0) — with collectives in the
            # stage body they must issue in one canonical order:
            # F0 -> F1 -> head -> B1 -> B0 -> W0 -> W1 -> hops
            x1 = _phase_after(x1, y0)
        y1 = lax.cond(f1_active,
                      lambda: _v(_stage(params1, x1, mf1_c)), zact)
        stash1 = lax.dynamic_update_index_in_dim(
            stash1, x1, jnp.mod(t, k1), 0)
        # ---------------- head/loss: vstage 2n-1 lives on DEVICE 0
        loss_mb, dy_seed, hgrads = last_stage_grad(
            y1, head_params_v, jnp.clip(mf1, 0, m - 1))
        is_head = s == 0
        if head is not None:
            hmask = is_head & f1_active
            head = jax.tree_util.tree_map(
                lambda g, d: g + jnp.where(hmask, d.astype(g.dtype), 0),
                head, hgrads)
        loss = loss + jnp.where(is_head & f1_active, loss_mb, 0.0)
        # ---------------- backward lane 1 (dx only)
        mb1 = t - 2 * (ng - 1) + sigma1
        b1_active = (mb1 >= 0) & (mb1 < m)
        mb1_c = jnp.clip(mb1, 0, m - 1)
        cot1 = jnp.where(is_head, dy_seed, c1_in)
        if serialize_phases:
            # B1 strictly after the WHOLE head vjp — see zbh1
            cot1 = _phase_after(cot1, loss_mb,
                                hgrads if hgrads is not None else ())
        x_b1 = stash1[jnp.mod(t - 2 * s, k1)]

        def b1_do():
            _, vjpx = jax.vjp(
                lambda xx: _stage(params1, xx, mb1_c), x_b1)
            (dx,) = vjpx(cot1.astype(y1.dtype))
            return _v(dx)

        dx1 = lax.cond(b1_active, b1_do, lambda: _za(dt=y1.dtype))
        wslot1 = jnp.mod(jnp.clip(mb1, 0, m), wk1)
        wx1, wgy1 = lax.cond(
            b1_active,
            lambda wx, wg: (
                lax.dynamic_update_index_in_dim(wx, x_b1, wslot1, 0),
                lax.dynamic_update_index_in_dim(
                    wg, cot1.astype(dtype), wslot1, 0)),
            lambda wx, wg: (wx, wg), wx1, wgy1)
        # ---------------- backward lane 0 (dx only)
        mb0 = t - 2 * (ng - 1) + s
        b0_active = (mb0 >= 0) & (mb0 < m)
        mb0_c = jnp.clip(mb0, 0, m - 1)
        cot0 = jnp.where(s == n - 1, dx1_prev, c0_in)
        if serialize_phases:
            cot0 = _phase_after(cot0, dx1)   # B0 after B1
        x_b0 = stash0[jnp.mod(t - 2 * (ng - 1 - s), k0)]

        def b0_do():
            _, vjpx = jax.vjp(
                lambda xx: _stage(params0, xx, mb0_c), x_b0)
            (dx,) = vjpx(cot0.astype(y0.dtype))
            return _v(dx)

        dx0 = lax.cond(b0_active, b0_do, lambda: _za(dt=y0.dtype))
        wslot0 = jnp.mod(jnp.clip(mb0, 0, m), wk0)
        wx0, wgy0 = lax.cond(
            b0_active,
            lambda wx, wg: (
                lax.dynamic_update_index_in_dim(wx, x_b0, wslot0, 0),
                lax.dynamic_update_index_in_dim(
                    wg, cot0.astype(dtype), wslot0, 0)),
            lambda wx, wg: (wx, wg), wx0, wgy0)
        # ---------------- deferred weight-grads (backlog recurrences)
        nB0 = jnp.clip(t - 2 * (ng - 1) + s + 1, 0, m)
        pend0 = nB0 - nW0
        fire0 = (pend0 > 0) & (~f0_active | (pend0 > s))
        nW0, g0 = w_phase(params0, wk0, nW0, g0, wx0, wgy0, fire0)
        nB1 = jnp.clip(t - 2 * (ng - 1) + sigma1 + 1, 0, m)
        pend1 = nB1 - nW1
        fire1 = (pend1 > 0) & (~f1_active | (pend1 > sigma1))
        wgy1_w = _phase_after(wgy1, g0) if serialize_phases else wgy1
        nW1, g1 = w_phase(params1, wk1, nW1, g1, wx1, wgy1_w, fire1)
        grads = jax.tree_util.tree_map(
            lambda a, b_: jnp.stack([a, b_]), g0, g1)
        # ---------------- input cotangents: vstage 0 is on device 0
        dx0_buf = lax.cond(
            (s == 0) & b0_active,
            lambda buf: lax.dynamic_update_index_in_dim(
                buf, dx0.astype(dtype), jnp.clip(mb0, 0, m - 1), 0),
            lambda buf: buf, dx0_buf)
        # ---------------- hops: fwd ring (y0, dx1), bwd ring (y1, dx0)
        y0_h, dx1_h, y1_h, dx0_h = y0, dx1, y1, dx0
        if serialize_phases:
            y0_h = _phase_after(y0, g1)
            a0_out = lax.ppermute(y0_h, axis_name, fwd_perm)
            dx1_h = _phase_after(dx1, a0_out)
            c1_out = lax.ppermute(dx1_h, axis_name, fwd_perm)
            y1_h = _phase_after(y1, c1_out)
            a1_out = lax.ppermute(y1_h, axis_name, bwd_perm)
            dx0_h = _phase_after(dx0, a1_out)
            c0_out = lax.ppermute(dx0_h, axis_name, bwd_perm)
        else:
            a0_out = lax.ppermute(y0_h, axis_name, fwd_perm)
            c1_out = lax.ppermute(dx1_h, axis_name, fwd_perm)
            a1_out = lax.ppermute(y1_h, axis_name, bwd_perm)
            c0_out = lax.ppermute(dx0_h, axis_name, bwd_perm)
        return (a0_out, a1_out, c0_out, c1_out, y0, dx1,
                stash0, stash1, wx0, wgy0, wx1, wgy1, nW0, nW1,
                grads, head, loss, dx0_buf), None

    carry0 = (zact(), zact(), zact(), zact(), zact(), zact(),
              _za((k0,) + x_shape),
              _za((k1,) + x_shape),
              _za((wk0,) + x_shape),
              _za((wk0,) + x_shape),
              _za((wk1,) + x_shape),
              _za((wk1,) + x_shape),
              _v(jnp.zeros((), jnp.int32)),
              _v(jnp.zeros((), jnp.int32)),
              grads0,
              head0, loss0,
              _za((m,) + x_shape))
    carry, _ = lax.scan(tick, carry0, jnp.arange(t_total))
    (_, _, _, _, _, _, _, _, wx0, wgy0, wx1, wgy1, nW0, nW1,
     grads, head, loss, dx0_buf) = carry

    # drain: retire remaining W backlogs (manual-tp: the recompute
    # replays tp collectives — tp-subgroup-uniform fire predicates,
    # and serialize_phases orders W0 before W1, as in the main grid)
    n_extra = zbvpp_extra_ticks(int(n) if isinstance(n, int) else n, m)

    def drain(carry, _t):
        nW0, nW1, grads = carry
        g0 = jax.tree_util.tree_map(lambda g: g[0], grads)
        g1 = jax.tree_util.tree_map(lambda g: g[1], grads)
        nW0, g0 = w_phase(params0, wk0, nW0, g0, wx0, wgy0, nW0 < m)
        wgy1_d = _phase_after(wgy1, g0) if serialize_phases else wgy1
        nW1, g1 = w_phase(params1, wk1, nW1, g1, wx1, wgy1_d, nW1 < m)
        grads = jax.tree_util.tree_map(
            lambda a, b_: jnp.stack([a, b_]), g0, g1)
        return (nW0, nW1, grads), None

    if n_extra > 0:
        (nW0, nW1, grads), _ = lax.scan(
            drain, (nW0, nW1, grads), jnp.arange(n_extra))

    return _pipeline_epilogue(axis_name, s, n, loss, head, dx0_buf,
                              grads, grad_dtype, dtype, head_stage=0)
