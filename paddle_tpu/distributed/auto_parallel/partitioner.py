"""Generic-model partitioner: impose TP/PP on arbitrary Layer models.

Reference being re-designed: the static auto-parallel partitioner +
parallelizer (/root/reference/python/paddle/distributed/auto_parallel/
static/partitioner.py, engine.py:98) — there, a traced program is split
per rank and dist-attrs are completed over it.

TPU-native decomposition:
  * TP ("completion"): parameters of Linear/Embedding layers are
    auto-annotated with mp-axis shardings; the XLA SPMD partitioner
    propagates them through the traced program and inserts the
    collectives (the mp_layers shardings ARE the annotations — this
    generalizes them to layers the user never marked).
  * PP ("partitioner"): the model's dominant homogeneous LayerList is
    located; its blocks' parameters are stacked [L, ...] and the chain
    is compiled onto the 1F1B interleave (parallel/pipeline_1f1b.py).
    The computation BEFORE the blocks (prologue) and AFTER them
    (epilogue + loss) is extracted from the model's own forward by
    shimming the blocks during tracing:
      - prologue: block 0 raises a capture carrying its (traced) input;
      - epilogue: every block becomes identity and the last block
        returns an injected value, so everything downstream computes on
        it (the upstream recompute is dead code XLA eliminates).
    No program-IR surgery — the model's python forward IS the program,
    cut at block boundaries, which is exactly what the reference's
    partitioner does to its static IR.

Contract (same as the reference's PipelineLayer requirement): pp > 1
needs a LayerList/Sequential of structurally identical blocks applied
sequentially; prologue/epilogue may be arbitrary. tp/dp work on ANY
model.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.core.tensor import Tensor


# ------------------------------------------------------------------ TP
def annotate_tp(model, mesh: Mesh, axis: str = "mp"):
    """Auto-annotate Linear/Embedding parameters over the mp axis.

    Policy (a generic Megatron-ish completion): Linear weights shard
    their output dim (column) when divisible — falling back to the
    input dim (row) — with column biases sharded to match; Embedding
    weights shard the embedding dim. Everything else stays replicated.
    GSPMD propagates activations/collectives from these seeds, so any
    choice is CORRECT; this one keeps the big GEMM operands sharded.
    Returns the number of annotated parameters.
    """
    from paddle_tpu.nn.layer.common import Linear, Embedding
    tp = mesh.shape[axis]
    if tp <= 1:
        return 0
    n = 0

    def put(t, spec):
        t._assign_array(jax.device_put(
            t._data, NamedSharding(mesh, spec)))
        t._sharding_hint = NamedSharding(mesh, spec)

    for _, sub in model.named_sublayers():
        if isinstance(sub, Linear):
            w = sub.weight
            din, dout = w.shape
            if dout % tp == 0:
                put(w, P(None, axis))
                n += 1
                if sub.bias is not None and sub.bias.shape[0] % tp == 0:
                    put(sub.bias, P(axis))
                    n += 1
            elif din % tp == 0:
                put(w, P(axis, None))
                n += 1
        elif isinstance(sub, Embedding):
            w = sub.weight
            if w.shape[1] % tp == 0:
                put(w, P(None, axis))
                n += 1
    return n


# ------------------------------------------------------------------ PP
def find_pipeline_blocks(model):
    """Locate the dominant homogeneous LayerList: the one with >= 2
    children whose parameter pytrees match in structure AND shapes,
    holding the most parameters. Returns the list of block Layers, or
    None."""
    from paddle_tpu.nn.layer.layers import LayerList, Sequential
    seq_types = (LayerList, Sequential)
    best, best_size = None, 0
    for _, sub in model.named_sublayers():
        if not isinstance(sub, seq_types):
            continue
        children = list(sub)
        if len(children) < 2:
            continue
        sigs = [tuple((name, tuple(p.shape))
                      for name, p in c.named_parameters())
                for c in children]
        if any(s != sigs[0] for s in sigs[1:]):
            continue
        size = sum(int(np.prod(shape)) for _, shape in sigs[0]) \
            * len(children)
        if size > best_size:
            best, best_size = children, size
    return best


class _BlockCapture(Exception):
    def __init__(self, value):
        self.value = value


class PipelinePartition:
    """The pp execution plan for one model: blocks + shim machinery."""

    def __init__(self, model, loss_fn, blocks, mesh: Mesh, pp: int,
                 microbatches: int, pp_schedule: str = "1f1b"):
        if len(blocks) % pp:
            raise ValueError(
                f"{len(blocks)} pipeline blocks not divisible by "
                f"pp={pp}")
        if pp_schedule not in ("1f1b", "zbh1", "zbvpp"):
            raise ValueError(
                f"partitioner pp_schedule must be '1f1b', 'zbh1' or "
                f"'zbvpp', got {pp_schedule!r}")
        if pp_schedule in ("zbh1", "zbvpp") and "mp" in mesh.shape \
                and mesh.shape["mp"] > 1:
            raise ValueError(
                f"pp_schedule={pp_schedule!r} requires a "
                "collective-free stage "
                "body (tp=1): the zero-bubble phases are cond-gated "
                "per stage and GSPMD tp collectives inside a cond "
                "branch deadlock the mesh (gpt_hybrid."
                "_validate_pp_schedule has the full diagnosis)")
        if pp_schedule == "zbvpp" and len(blocks) % (2 * pp):
            raise ValueError(
                f"{len(blocks)} pipeline blocks not divisible by "
                f"2*pp={2 * pp} (pp_schedule='zbvpp' splits the chain "
                "into 2*pp V-placed chunks)")
        self.model = model
        self.loss_fn = loss_fn
        self.blocks = blocks
        self.mesh = mesh
        self.pp = pp
        self.pp_schedule = pp_schedule
        self.microbatches = microbatches
        self.template = blocks[0]
        # param bookkeeping: block params (stacked into the pipeline)
        # vs the rest (prologue+epilogue, differentiated outside)
        self.block_params = []               # [L][(name, Tensor)]
        block_ids = set()
        for b in blocks:
            ps = list(b.named_parameters())
            self.block_params.append(ps)
            block_ids.update(id(p) for _, p in ps)
        self.other_params = [
            (n, p) for n, p in model.named_parameters()
            if id(p) not in block_ids]

    # -- shims ---------------------------------------------------------
    def _run_with_shims(self, shims: dict, x):
        """Run model.forward with selected blocks' forwards replaced."""
        saved = []
        try:
            for b, fn in shims.items():
                saved.append((b, b.__dict__.get("forward")))
                b.__dict__["forward"] = fn
            return self.model(x)
        finally:
            for b, fwd in saved:
                if fwd is None:
                    b.__dict__.pop("forward", None)
                else:
                    b.__dict__["forward"] = fwd

    def prologue(self, x: Tensor):
        """Everything the model computes before block 0, extracted by
        capture-aborting at block 0's entry. Returns (block0_input,
        extra_args, extra_kwargs) — models whose blocks take extra
        arguments (attention masks, position ids: the reference
        PipelineLayer's tuple-valued stage IO, pp_layers.py:56) have
        those captured too; Tensor extras become per-microbatch
        NON-differentiated side inputs of every stage, non-Tensor
        extras stay static."""
        def capture(inp, *a, **k):
            raise _BlockCapture((inp, a, k))
        try:
            self._run_with_shims({self.blocks[0]: capture}, x)
        except _BlockCapture as c:
            return c.value
        raise RuntimeError(
            "pipeline blocks were not reached by model.forward — the "
            "LayerList is not on the forward path")

    def epilogue_loss(self, y: Tensor, x_probe: Tensor, labels):
        """Everything after the last block + the loss, extracted by
        making blocks identity and injecting y at the last block.

        x_probe is THIS microbatch's raw input, so models whose
        epilogue consumes the input or prologue output directly (skip
        connections, loss masks read from ids) stay CORRECT: the
        recomputed prologue inside this call carries the direct-path
        gradient contribution, while the pipeline's dx0 -> prologue
        vjp carries the block-path one; when no skip exists the
        recompute is dead code XLA eliminates."""
        shims = {b: (lambda inp, *a, **k: inp) for b in self.blocks}
        shims[self.blocks[-1]] = lambda inp, *a, **k: y
        out = self._run_with_shims(shims, x_probe)
        if self.loss_fn is not None:
            return self.loss_fn(out, labels)
        return out

    def run_template(self, x: Tensor, param_arrays: List,
                     extra_args=(), extra_kwargs=None) -> Tensor:
        """One block's forward with its params rebound to given arrays
        (the scanned per-layer slices)."""
        tpl = list(self.template.named_parameters())
        saved = [p._data for _, p in tpl]
        try:
            for (_, p), a in zip(tpl, param_arrays):
                p._data = a
            return self.template(x, *extra_args,
                                 **(extra_kwargs or {}))
        finally:
            for (_, p), s in zip(tpl, saved):
                p._data = s

    # -- the pure compiled step ---------------------------------------
    def stacked_blocks(self):
        """[L, ...] arrays per block-param position, sharded
        [pp-on-leading] when placed under the mesh."""
        names = [n for n, _ in self.block_params[0]]
        out = []
        for i, _ in enumerate(names):
            stacked = jnp.stack(
                [self.block_params[li][i][1]._data
                 for li in range(len(self.blocks))])
            out.append(stacked)
        return out

    def train_grads(self, x: Tensor, labels: Tensor):
        """Forward+backward through prologue -> compiled 1F1B over the
        stacked blocks -> epilogue/loss. Returns (loss_Tensor, and sets
        .grad on every model parameter). Runs traced under
        jit.to_static (the Engine wraps it)."""
        import paddle_tpu as paddle
        pp, m = self.pp, self.microbatches
        L = len(self.blocks)
        mesh = self.mesh

        # --- prologue on the full batch (its vjp gives input-side
        # grads for embedding etc.)
        other = self.other_params

        def prologue_fn(other_arrays, x_arr):
            saved = [p._data for _, p in other]
            try:
                for (_, p), a in zip(other, other_arrays):
                    p._data = a
                with paddle.no_grad():
                    h0, a_, _k = self.prologue(Tensor._wrap(x_arr,
                                                            True))
                sides = tuple(a_[i]._data for i in side_pos)
                return (h0._data,) + sides
            finally:
                for (_, p), s in zip(other, saved):
                    p._data = s

        # probe the block-entry signature: record EVERY block's extra
        # call args in one real forward (pass-through shims), so models
        # whose blocks receive per-block-varying extras are rejected
        # loudly instead of silently replaying block 0's values
        records = []

        def _recorder(b):
            orig = b.forward

            def fn(inp, *a, **k):
                records.append((a, k))
                return orig(inp, *a, **k)
            return fn

        with paddle.no_grad():
            self._run_with_shims(
                {b: _recorder(b) for b in self.blocks}, x)
        if len(records) != len(self.blocks):
            raise RuntimeError(
                f"expected {len(self.blocks)} block calls in "
                f"model.forward, saw {len(records)} — blocks must be "
                "applied exactly once each")
        probe_a, probe_k = records[0]
        for kk, vv in probe_k.items():
            if isinstance(vv, Tensor):
                raise NotImplementedError(
                    f"pipeline blocks taking Tensor KWARGS ({kk!r}) "
                    "are not supported — pass tensor side inputs "
                    "positionally")
        def _same_extra(v0, vi):
            """Per-block equality that never silently passes: same
            traced Tensor object => provably same value; otherwise a
            type-aware comparison (array-likes via np.array_equal —
            a bare != would raise ambiguous-truth on them)."""
            if v0 is vi:
                return True
            if isinstance(v0, Tensor) or isinstance(vi, Tensor):
                return False      # distinct (or mixed) tensor objects
            try:
                return bool(v0 == vi)
            except Exception:
                try:
                    return bool(np.array_equal(v0, vi))
                except Exception:
                    return False

        for bi, (a_, k_) in enumerate(records[1:], 1):
            if len(a_) != len(probe_a) or set(k_) != set(probe_k):
                raise NotImplementedError(
                    "pipeline blocks must share one call signature; "
                    f"block {bi} differs from block 0")
            for i, (v0, vi) in enumerate(zip(probe_a, a_)):
                if not _same_extra(v0, vi):
                    raise NotImplementedError(
                        f"block argument {i} varies per block "
                        f"(block 0 vs block {bi}) — the scanned stage "
                        "replays ONE value for all layers; per-block-"
                        "varying extras are not supported by the "
                        "generic partitioner")
            for kk in probe_k:
                if not _same_extra(probe_k[kk], k_[kk]):
                    raise NotImplementedError(
                        f"block kwarg {kk!r} varies per block "
                        f"(block 0 vs block {bi}) — the scanned stage "
                        "replays ONE value for all layers")
        side_pos = [i for i, v in enumerate(probe_a)
                    if isinstance(v, Tensor)]
        if side_pos:
            import warnings
            warnings.warn(
                "pipeline blocks receive tensor side inputs (args "
                f"{side_pos}); these are treated as NON-differentiated "
                "(mask/position-id semantics) — if a side input "
                "depends on trainable prologue parameters, that "
                "gradient path is dropped", stacklevel=2)
        static_args = {i: v for i, v in enumerate(probe_a)
                       if not isinstance(v, Tensor)}
        static_kwargs = dict(probe_k)

        other_arrays = [p._data for _, p in other]
        (x0, *side_arrays), prologue_vjp = jax.vjp(
            prologue_fn, other_arrays, x._data)

        # --- microbatch + stack blocks
        b = x0.shape[0]
        if b % m:
            raise ValueError(f"batch {b} not divisible by "
                             f"microbatches={m}")
        x0 = lax.with_sharding_constraint(
            x0, NamedSharding(mesh, P("dp", *[None] * (x0.ndim - 1)))) \
            if "dp" in mesh.shape and mesh.shape["dp"] > 1 else x0
        mb = x0.reshape((m, b // m) + x0.shape[1:])
        lbl = labels._data
        lbl_mb = lbl.reshape((m, b // m) + lbl.shape[1:])
        # tensor extras become [M, ...] side inputs. Batch-carrying vs
        # batch-free is decided STRUCTURALLY (an eval_shape of the
        # prologue at a different batch size — no compute), not by the
        # leading-dim==batch heuristic, which misfires when a shared
        # [seq, seq] mask happens to have seq == batch
        if side_arrays:
            probe_b = max(1, b // m)
            if probe_b == b:
                probe_b = max(1, b // 2)
            shapes_small = jax.eval_shape(
                prologue_fn,
                [jax.ShapeDtypeStruct(a.shape, a.dtype)
                 for a in other_arrays],
                jax.ShapeDtypeStruct((probe_b,) + x._data.shape[1:],
                                     x._data.dtype))[1:]
            batchful = [
                sa.ndim >= 1 and sa.shape[0] == b
                and len(ss.shape) >= 1 and ss.shape[0] == probe_b
                and probe_b != b
                for sa, ss in zip(side_arrays, shapes_small)]
        else:
            batchful = []
        side_mb = tuple(
            sa.reshape((m, b // m) + sa.shape[1:]) if bf
            else jnp.broadcast_to(sa[None], (m,) + sa.shape)
            for sa, bf in zip(side_arrays, batchful))

        stacked = self.stacked_blocks()
        if self.pp_schedule == "zbvpp":
            # ZB-V placement: virtual stage sigma owns block chunk
            # sigma; device s holds chunks s (lane 0) and 2pp-1-s
            # (lane 1) -> leaves [pp, 2, Lc, ...]
            Lc = L // (2 * pp)
            vidx = np.stack([np.arange(pp),
                             2 * pp - 1 - np.arange(pp)], axis=1)
            stacked = [
                lax.with_sharding_constraint(
                    s.reshape((2 * pp, Lc) + s.shape[1:])[vidx],
                    NamedSharding(mesh, P("pp", *[None] * (s.ndim + 1))))
                for s in stacked]
        else:
            stacked = [
                lax.with_sharding_constraint(
                    s.reshape((pp, L // pp) + s.shape[1:]),
                    NamedSharding(mesh, P("pp", *[None] * s.ndim)))
                for s in stacked]

        def stage_fn(stage_params, xm, side=()):
            extra = []
            si = iter(side)
            for i in range(len(probe_a)):
                if i in static_args:
                    extra.append(static_args[i])
                else:
                    extra.append(Tensor._wrap(next(si), True))

            def body(h, lp):
                with paddle.no_grad():
                    out = self.run_template(Tensor._wrap(h, True),
                                            list(lp), tuple(extra),
                                            static_kwargs)
                return out._data, None
            h, _ = lax.scan(body, xm, tuple(stage_params))
            return h

        x_mb = x._data.reshape((m, b // m) + x._data.shape[1:])

        def last_grad(y, hp, mb_idx):
            t = lbl_mb[mb_idx]
            x_probe = x_mb[mb_idx]

            def head_loss(hp_, y_):
                saved = [p._data for _, p in other]
                try:
                    for (_, p), a in zip(other, hp_):
                        p._data = a
                    with paddle.no_grad():
                        loss = self.epilogue_loss(
                            Tensor._wrap(y_, True),
                            Tensor._wrap(x_probe, True),
                            Tensor._wrap(t, True))
                    return loss._data / m
                finally:
                    for (_, p), s in zip(other, saved):
                        p._data = s
            (l, (ghp, gy)) = jax.value_and_grad(
                head_loss, argnums=(0, 1))(hp, y)
            return l, gy, ghp

        from paddle_tpu.parallel.pipeline_1f1b import (
            pipeline_train_1f1b, pipeline_train_zbh1,
            pipeline_train_zbvpp)
        from jax import shard_map
        blk_specs = tuple(P("pp") for _ in stacked)
        pipe_fn = {"zbh1": pipeline_train_zbh1,
                   "zbvpp": pipeline_train_zbvpp,
                   "1f1b": pipeline_train_1f1b}[self.pp_schedule]

        def body(stacked, mb, lbl_mb_, head_arrays, side_mb_):
            return pipe_fn(
                stage_fn, tuple(stacked), mb,
                last_grad, head_params=list(head_arrays),
                side_inputs=side_mb_ if side_mb_ else None)

        loss, sgrads, hgrads, dx0 = shard_map(
            body, mesh=mesh, axis_names={"pp"},
            in_specs=(blk_specs, P(None), P(None), P(None), P(None)),
            out_specs=(P(), blk_specs, P(None), P(None)))(
                tuple(stacked), mb, lbl_mb, other_arrays, side_mb)

        # --- prologue backward from the pipeline's input cotangents
        # (side inputs are non-differentiated: zero cotangents)
        dx0_full = dx0.reshape((b,) + dx0.shape[2:])
        pgrads, _dx = prologue_vjp(
            (dx0_full,) + tuple(jnp.zeros_like(sa)
                                for sa in side_arrays))

        # --- write grads back onto the model's parameters
        for i, (name, p) in enumerate(other):
            g = pgrads[i] + hgrads[i]
            self._acc_grad(p, g)
        for pos in range(len(stacked)):
            if self.pp_schedule == "zbvpp":
                # invert the V gather: chunk sigma's grads sit at
                # [sigma, 0] (sigma < pp) / [2pp-1-sigma, 1]
                g = sgrads[pos]                    # [pp, 2, Lc, ...]
                Lc = L // (2 * pp)
                ds = np.concatenate([np.arange(pp),
                                     np.arange(pp - 1, -1, -1)])
                ls = np.concatenate([np.zeros(pp, np.int64),
                                     np.ones(pp, np.int64)])
                flat = g[ds, ls].reshape((L,) + g.shape[3:])
            else:
                flat = sgrads[pos].reshape(
                    (L,) + sgrads[pos].shape[2:])
            for li in range(L):
                self._acc_grad(self.block_params[li][pos][1], flat[li])
        return Tensor._wrap(loss, True)

    @staticmethod
    def _acc_grad(p, g):
        g = g.astype(p._data.dtype)
        if p.grad is None:
            p.grad = Tensor._wrap(g, True)
        else:
            p.grad = Tensor._wrap(p.grad._data + g, True)
