"""Chip smoke: the quickest proof that the main path still starts on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # one host, four chips, one process

Drives GPT-3 1.3B (h2048, L24, 16x128 heads, vocab 50304, bf16, random
seeded weights) through the entry points a user calls:

* kernels — every in-tree Pallas attention kernel, forward and backward,
  compiled for the chip at the shape of the regime it is routed for and
  compared with a float32 ``jax.numpy`` attention; the decode attention
  kernel (forward only) against the float32 einsums of the XLA path; the
  cache write's program against ``dynamic_update_slice``, bit for bit; the
  expert layer on the rows of the experts it holds against all its rows;
* train   — ``gpt_hybrid.setup`` + a few steps at B4xS1024 on a fixed batch;
* serve   — ``ContinuousBatchingSession`` answering sixteen requests, with
  request 0 checked against ``DecodeSession.generate``, every one-token
  step through the decode attention kernel and none fallen back, every
  step's cache write through its one program.

A phase that fails raises; nothing is caught and summarised. Without a TPU
backend the script exits non-zero before building anything — there is no CPU
branch. ``tests/test_chip_smoke.py`` drives the same phase functions at tiny
size on the CPU mesh. No number printed here is a benchmark.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
from importlib import metadata


#: lax.scan unroll of the 24-layer stack in the full-width train phases
#: (the train cells' configs state the same): the full unroll compiles in
#: ~70 s and fits the chip — CHANGES.md, PR 21
SCAN_UNROLL = 24


# --------------------------------------------------------------- reporting

def device_report():
    """Print what is installed and attached; return the device dict of the
    final JSON line. Creates the backend."""
    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"[smoke] jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu} default_backend={jax.default_backend()} "
          f"device_kind={dev.device_kind!r} device_count={device['count']}",
          flush=True)
    return device


def _moved_counters(delta, prefix="attn."):
    """Counter movement of one obs.window() under ``prefix``, as
    {name{labels}: count}."""
    out = {}
    for c in delta.changed():
        if c["name"].startswith(prefix):
            labels = ",".join(f"{k}={v}" for k, v in
                              sorted(c["labels"].items()))
            out[f"{c['name']}{{{labels}}}"] = int(c["value"])
    return out


def _peak_bytes(device):
    """(peak_bytes_in_use, peak_bytes_reserved) of the process so far, or
    (None, None) where the backend reports nothing. On the v5e runtime
    live arrays are counted under *in_use* and a running program's
    temporaries under *reserved*; their sum is what must fit the chip."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use"), stats.get("peak_bytes_reserved")


# ------------------------------------------------------------------ kernels

#: (kernel, [B,H,S,D], causal, (block_q, block_kv) or None) — the shapes of
#: the regime each kernel is routed for (flash_attention_maybe's chain)
KERNEL_CASES = (
    ("simple", (4, 16, 1024, 128), True, None),
    ("causal_skip", (4, 8, 2048, 128), True, None),
    ("qblock", (4, 8, 2048, 128), False, None),
    ("blocked", (2, 8, 4096, 128), True, (512, 512)),
    ("blocked", (2, 8, 4096, 128), False, (512, 512)),
    ("blocked", (2, 8, 4096, 128), True, (256, 512)),
    ("blocked", (2, 8, 4096, 128), False, (256, 512)),
    ("blocked", (2, 8, 4096, 128), True, (512, 1024)),
    ("blocked", (2, 8, 4096, 128), False, (512, 1024)),
    # train-lfm2-ep4-l5-s8192's own call: head width 64 at 8k tokens
    ("blocked", (2, 32, 8192, 64), True, (512, 512)),
    ("library_flash", (2, 8, 4096, 128), True, None),
)


def _kernel_fn(name, causal, blocks, interpret):
    """The kernel under its own entry point, in [B,H,S,D] layout."""
    import importlib

    import jax.numpy as jnp

    if name == "library_flash":
        if interpret:
            raise ValueError("the library flash wrapper has no interpret "
                             "mode; leave it out of interpret runs")
        from paddle_tpu.ops.pallas.flash_attention import flash_attention

        def run(q, k, v):           # wrapper layout is [B,S,H,D]
            qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
            return jnp.swapaxes(
                flash_attention(qt, kt, vt, causal=causal), 1, 2)
        return run
    module = {"simple": "simple_attention", "causal_skip": "causal_attention",
              "qblock": "simple_attention2", "blocked": "blocked_flash"}[name]
    m = importlib.import_module(f"paddle_tpu.ops.pallas.{module}")
    kw = {"block_q": blocks[0], "block_kv": blocks[1]} if blocks else {}
    return lambda q, k, v: m.attention_bhsd(q, k, v, causal=causal,
                                            interpret=interpret, **kw)


def _reference_attention(q, k, v, causal):
    """float32 jax.numpy attention, [B,H,S,D]; HIGHEST precision so the
    chip does not run the reference's matmuls in bf16 passes."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=hi) \
        / math.sqrt(q.shape[-1])
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=hi)


def check_kernel(name, shape, causal, blocks, interpret=False, tol=2e-2,
                 dtype=None):
    """One kernel, forward and backward, against the float32 reference.
    Errors are max-abs, normalised by the reference's max-abs; ``tol`` is
    the bf16 tolerance. Returns the four errors; raises on a mismatch (a
    kernel the compiler refuses raises from the call itself)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    dtype = dtype or jnp.bfloat16
    kq, kk, kv, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, w = (jax.random.normal(key, shape, dtype)
                  for key in (kq, kk, kv, kw))
    run = _kernel_fn(name, causal, blocks, interpret)

    def fwd_bwd(fn, q, k, v, w):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w.astype(out.dtype))

    def reference(*qkvw):
        # a head at a time: at [2, 32, 8192, 64] the scores of all heads
        # at once are 17 GB in float32
        def one(head):
            return tuple(a[0, 0] for a in fwd_bwd(
                lambda q, k, v: _reference_attention(q, k, v, causal),
                *(a[None, None] for a in head)))
        heads = tuple(a.astype(jnp.float32).reshape((-1,) + a.shape[2:])
                      for a in qkvw)
        return tuple(a.reshape(shape) for a in jax.lax.map(one, heads))

    got = jax.jit(lambda *qkvw: fwd_bwd(run, *qkvw))(q, k, v, w)
    want = jax.jit(reference)(q, k, v, w)
    errs = {}
    for label, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if not np.all(np.isfinite(g)):
            raise AssertionError(f"{name} {label}: non-finite values")
        errs[label] = float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
    tag = f"{name} {list(shape)} causal={causal}" + \
        (f" blocks={blocks}" if blocks else "")
    print(f"[smoke] kernel {tag}: " +
          " ".join(f"{k}={e:.2e}" for k, e in errs.items()), flush=True)
    bad = {k: e for k, e in errs.items() if not e <= tol}
    if bad:
        raise AssertionError(f"kernel {tag} off its float32 reference "
                             f"beyond {tol}: {bad}")
    return errs


#: the longctx cell's cache of one layer, [B,C,Hkv,D] float32
DECODE_KERNEL_SHAPE = (12, 2048, 16, 128)


def check_decode_kernel(shape=DECODE_KERNEL_SHAPE, interpret=False,
                        tol=1e-5):
    """The length-aware decode attention kernel (one query a slot, ragged
    lengths from 0 to the capacity, the last two slots past it as a decode
    block steps a lane past its budget) against ``_attend_einsum``, the
    float32 einsums it stands in for, at HIGHEST precision. Max-abs error,
    as tests/test_decode_attention_kernel.py holds it on the CPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.inference.decode import _attend_einsum
    from paddle_tpu.ops.pallas.decode_attention import decode_attention

    b, c, hkv, d = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (b, 1, hkv, d), jnp.bfloat16)
    kbuf = jax.random.normal(kk, shape, jnp.float32)
    vbuf = jax.random.normal(kv, shape, jnp.float32)
    lens = np.linspace(0, c - 1, b).astype(np.int32)
    lens[-2:] = c, c + 40
    lens = jnp.asarray(lens)
    got = jax.jit(lambda *a: decode_attention(*a, interpret=interpret))(
        q, kbuf, vbuf, lens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(_attend_einsum)(q, kbuf, vbuf, lens)
    got = np.asarray(got, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError("decode_ragged: non-finite values")
    err = float(np.max(np.abs(got - np.asarray(want))))
    print(f"[smoke] kernel decode_ragged {list(shape)} float32, lengths 0.."
          f"{c + 40} of {c}: out={err:.2e} (max-abs)", flush=True)
    if not err <= tol:
        raise AssertionError(f"kernel decode_ragged {list(shape)} off the "
                             f"float32 einsums beyond {tol}: {err}")
    return err


#: the chat cell's cache of one layer, [B,C,Hkv,D] float32
CACHE_WRITE_SHAPE = (24, 1024, 16, 128)


def check_cache_write(shape=CACHE_WRITE_SHAPE, s=1, interpret=False):
    """The cache write's Pallas program (``s`` new positions a slot, lengths
    from 0 to the capacity, the last two slots past it) against the vmapped
    ``dynamic_update_slice`` it stands in for: the same K and V buffers,
    bit for bit. Returns the number of elements that differ, 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from paddle_tpu.ops.pallas.cache_write import write_rows

    b, c, hkv, d = shape
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    kbuf, vbuf = (jax.random.normal(k, shape, jnp.float32) for k in keys[:2])
    kn, vn = (jax.random.normal(k, (b, s, hkv, d), jnp.float32)
              for k in keys[2:])
    lens = np.linspace(0, c - 1, b).astype(np.int32)
    lens[-2:] = c, c + 40
    lens = jnp.asarray(lens)

    @jax.jit
    def update_slice(buf, new):
        return jax.vmap(
            lambda x, n, l: lax.dynamic_update_slice(x, n, (l, 0, 0))
        )(buf, new, lens)
    got = jax.jit(lambda *a: write_rows(*a, interpret=interpret))(
        kbuf, vbuf, kn, vn, lens)
    want = update_slice(kbuf, kn), update_slice(vbuf, vn)
    differ = sum(int(np.sum(np.asarray(g) != np.asarray(w)))
                 for g, w in zip(got, want))
    print(f"[smoke] kernel cache_write {list(shape)} float32, {s} new a "
          f"slot, lengths 0..{c + 40} of {c}: {differ} elements of K and V "
          "differ from dynamic_update_slice", flush=True)
    if differ:
        raise AssertionError(f"kernel cache_write {list(shape)}: {differ} "
                             "elements differ from dynamic_update_slice")
    return differ


#: the LFM2 train cell's expert layer: tokens a step, hidden, expert width,
#: experts routed over, experts held, experts a token
EXPERT_ROWS_SHAPE = (16384, 2048, 1536, 64, 16, 4)


def check_expert_rows(shape=EXPERT_ROWS_SHAPE, dtype="bfloat16", tol=1e-2):
    """``sdar_moe.expert_ffn`` holding a share of the experts (on a TPU
    through the grouped kernels) under a uniform routing, which the held
    experts' row buffer must hold in one trip: the layer against one pass
    over all ``T x k`` rows, in value and in ``h``'s gradient. Returns the
    two relative L2 errors."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.observability as obs
    from paddle_tpu.models import sdar_moe

    t, hid, inter, experts, held, top_k = shape
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    h = jax.random.normal(keys[0], (t, hid), dtype)
    gate_up, down = (
        (0.02 * jax.random.normal(k, s)).astype(dtype) for k, s in (
            (keys[1], (held, hid, 2 * inter)), (keys[2], (held, inter, hid))))
    weights, index = jax.lax.top_k(
        jax.random.uniform(keys[3], (t, experts)), top_k)
    probe = jax.random.normal(keys[4], (t, hid), jnp.float32)
    rows = sdar_moe._held_rows(t * top_k, held, experts)
    held_pairs = int(jnp.sum(index < held))

    def through(layer):
        def loss(h, *operands):
            y = layer(h, *operands)
            return jnp.sum(y.astype(jnp.float32) * probe), y
        (_, y), d_h = jax.jit(jax.value_and_grad(loss, has_aux=True))(
            h, weights, index, gate_up, down)
        return y, d_h

    with obs.window() as w:
        got = through(lambda h, weights, index, *ws: sdar_moe.expert_ffn(
            h, weights, index, *ws, 0, experts))
    moved = _moved_counters(w.delta, "moe.")
    want = through(lambda h, weights, index, *ws: sdar_moe._ffn_rows(
        t * top_k, 0, *sdar_moe._sort_pairs(index, 0, experts), h, weights,
        *ws).astype(h.dtype))

    def rel(a, b):
        a, b = (x.astype(jnp.float32) for x in (a, b))
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    errs = tuple(map(rel, got, want))
    print(f"[smoke] expert rows [{t}, {hid}] {jnp.dtype(dtype).name}, {held} "
          f"of {experts} experts held, top-{top_k}: {held_pairs} held pairs "
          f"in a buffer of {rows} rows against all {t * top_k}: "
          f"out={errs[0]:.2e} dh={errs[1]:.2e}; counters {moved}", flush=True)
    if held_pairs > rows or "moe.row_buffer{rows=held}" not in moved:
        raise AssertionError(
            f"expert rows: one trip over the held experts' buffer of {rows} "
            f"rows is not what ran: {held_pairs} held pairs, {moved}")
    if not max(errs) <= tol:
        raise AssertionError(f"expert rows [{t}, {hid}]: the buffer of "
                             f"{rows} rows off all {t * top_k} beyond "
                             f"{tol}: {errs}")
    return errs


def kernel_phase(cases=KERNEL_CASES, interpret=False, tol=2e-2, dtype=None):
    """Every case of ``cases`` through check_kernel; the first failure
    raises."""
    t0 = time.perf_counter()
    results = [check_kernel(*case, interpret=interpret, tol=tol,
                            dtype=dtype) for case in cases]
    print(f"[smoke] kernel phase: {len(results)} kernel variants matched "
          f"their float32 reference in {time.perf_counter() - t0:.1f}s "
          "(compile included)", flush=True)
    return results


# -------------------------------------------------------------------- train

def _seeded_ids(cfg, batch, seq, seed):
    import jax.numpy as jnp
    import numpy as np
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (batch, seq)))


def _step_collectives(hlo_text, kinds=("collective-permute", "all-to-all")):
    """{"<kind> <result>": count} of the collectives of ``kinds`` that a
    compiled step spells out (an asynchronous one by its start)."""
    import collections
    import re
    found = re.findall(
        r"= \(?(\w+\[[\d,]*\])\S*(?: [^=]*)? (%s)(?:-start)?\("
        % "|".join(kinds), hlo_text)
    return dict(collections.Counter(
        f"{kind} {shape}" for shape, kind in found))


def train_phase(cfg, batch, seq, steps, *, scan_unroll, warmup=2, dp=1,
                tp=1, sp=False, zero1=True, devices=None,
                expect_kernel=None, seed=0):
    """``gpt_hybrid.setup`` + ``warmup`` + ``steps`` train steps on one fixed
    seeded batch, bf16 params/compute/moments, remat_policy="names".

    Checks: step-0 loss within 0.5 of ln(vocab); every loss finite; the last
    loss below the first; nothing traced or compiled after warm-up; when
    ``expect_kernel`` is given, that Pallas kernel was dispatched and no
    attention dispatch fell back on an error; where ZeRO-1 shards the
    moments (dp > 1), no weight matrix of the layer stack has dp on its
    layer dim (``zero1.moment_shard{dim}`` is printed). On more than one
    device the compiled step's collective-permutes and all-to-alls are
    printed by result shape. Returns a dict of what it saw.
    """
    import jax
    import jax.numpy as jnp

    import paddle_tpu.observability as obs
    from paddle_tpu.models.gpt_hybrid import ParallelConfig, setup

    devices = list(devices if devices is not None else jax.devices()[:1])
    pcfg = ParallelConfig(dp=dp, pp=1, tp=tp, sp=sp, zero1=zero1,
                          remat=True, remat_policy="names",
                          scan_unroll=scan_unroll,
                          param_dtype=jnp.bfloat16,
                          compute_dtype=jnp.bfloat16, moment_dtype=None)
    ids = _seeded_ids(cfg, batch, seq, seed)
    tag = f"train dp={dp} tp={tp} sp={sp} B{batch}xS{seq} " \
          f"unroll={scan_unroll}"

    with obs.window() as w:
        mesh, params, opt_state, step = setup(cfg, pcfg, seed=seed,
                                              devices=devices)
        over_layers = sorted(
            k for k, m in opt_state["m"]["blocks"].items()
            if k.endswith("_w") and m.sharding.spec[0] == "dp")
        losses = []
        with mesh:
            t0 = time.perf_counter()
            with obs.count_compiles() as cold:
                params, opt_state, loss = step(params, opt_state,
                                               (ids, ids))
                jax.block_until_ready(loss)
            first_step_s = time.perf_counter() - t0
            losses.append(loss)
            for _ in range(warmup - 1):
                params, opt_state, loss = step(params, opt_state,
                                               (ids, ids))
                losses.append(loss)
            jax.block_until_ready(loss)
            with obs.count_compiles() as compiles, \
                    obs.count_traces() as traces:
                t0 = time.perf_counter()
                for _ in range(steps):
                    params, opt_state, loss = step(params, opt_state,
                                                   (ids, ids))
                    losses.append(loss)
                jax.block_until_ready(loss)
                steady_s = time.perf_counter() - t0
        # a qkv leaf laid so that a tp rank's columns are not its own
        # heads shows here as activation-sized permutes and all-to-alls
        collectives = _step_collectives(
            step.lower(params, opt_state, (ids, ids)).compile().as_text()) \
            if len(devices) > 1 else {}
    losses = [float(x) for x in losses]
    dispatch = _moved_counters(w.delta)
    moments = _moved_counters(w.delta, prefix="zero1.moment_shard")
    peak = _peak_bytes(devices[0])

    print(f"[smoke] {tag}: attention dispatch {dispatch or '{}'}")
    print(f"[smoke] {tag}: moment leaves by the dim dp took "
          f"{moments or '{} (no moment is dp-sharded)'}")
    if len(devices) > 1:
        print(f"[smoke] {tag}: the step's collective-permutes and "
              f"all-to-alls by result {collectives or '{} (none)'}")
    print(f"[smoke] {tag}: compile {cold.seconds:.1f}s in {cold()} "
          f"executables (first step {first_step_s:.1f}s wall)")
    print(f"[smoke] {tag}: steady {steady_s / steps * 1e3:.1f} ms/step "
          f"over {steps} steps (smoke observation, not a benchmark)")
    print(f"[smoke] {tag}: peak_bytes_in_use {peak[0]} + "
          f"peak_bytes_reserved {peak[1]} (this process, so far; None = "
          "not reported)")
    print(f"[smoke] {tag}: losses {[round(x, 4) for x in losses]}",
          flush=True)

    want0 = math.log(cfg.vocab_size)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{tag}: non-finite loss in {losses}")
    if abs(losses[0] - want0) > 0.5:
        raise AssertionError(f"{tag}: step-0 loss {losses[0]:.4f} is not "
                             f"within 0.5 of ln(vocab)={want0:.4f}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{tag}: loss did not fall: {losses}")
    if compiles() or traces():
        raise AssertionError(
            f"{tag}: {traces()} traces / {compiles()} compiles after "
            "warm-up (expected none)")
    if over_layers:
        raise AssertionError(
            f"{tag}: ZeRO-1 put dp on the layer dim of {over_layers} "
            f"({moments}): their dp gradient sum cannot be a "
            "reduce-scatter")
    errors = {k: n for k, n in dispatch.items()
              if k.startswith("attn.dispatch_fallback") and "error" in k}
    if errors:
        raise AssertionError(f"{tag}: attention dispatch errors {errors}")
    if expect_kernel is not None and not dispatch.get(
            f"attn.dispatch{{kernel={expect_kernel}}}"):
        raise AssertionError(
            f"{tag}: expected the {expect_kernel!r} Pallas kernel to be "
            f"dispatched, saw {dispatch}")

    out = {"losses": losses, "dispatch": dispatch, "moments": moments,
           "collectives": collectives,
           "param_devices": len(
               params["blocks"]["qkv_w"].sharding.device_set),
           "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                            for d in devices]}
    # free the train state: the next phase needs the memory
    del params, opt_state, step, loss
    gc.collect()
    jax.clear_caches()
    return out


def reference_step0_loss(cfg, batch, seq, seed=0):
    """Step-0 loss of the same seeded weights and batch on ONE device,
    forward only (no optimizer state) — what a multi-chip run's step-0 loss
    must agree with."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt_hybrid import (ParallelConfig, build_mesh,
                                              init_params, loss_fn)

    pcfg = ParallelConfig(dp=1, pp=1, tp=1, remat=False,
                          param_dtype=jnp.bfloat16,
                          compute_dtype=jnp.bfloat16)
    mesh = build_mesh(pcfg, jax.devices()[:1])
    params = init_params(cfg, pcfg, jax.random.PRNGKey(seed))
    ids = _seeded_ids(cfg, batch, seq, seed)
    with mesh:
        loss = float(jax.jit(
            lambda p, b: loss_fn(p, b, cfg, pcfg, mesh))(params, (ids, ids)))
    del params
    gc.collect()
    jax.clear_caches()
    return loss


def multichip_phase(cfg, batch, seq, steps, *, scan_unroll, layouts,
                    n_devices=4, expect_kernel=None):
    """The train phase on ``n_devices`` chips in this process, once per
    layout. Checks per layout: every device holds state (bytes_in_use
    non-zero, max within 1.3x of min), a parameter leaf lives on all
    devices, and step-0 loss agrees with the one-device reference to 1e-2.
    """
    import jax

    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise RuntimeError(f"need {n_devices} devices, have {len(devices)}")
    ref = reference_step0_loss(cfg, batch, seq)
    print(f"[smoke] one-device reference step-0 loss (B{batch}xS{seq}): "
          f"{ref:.4f}", flush=True)
    results = []
    for layout in layouts:
        r = train_phase(cfg, batch, seq, steps, scan_unroll=scan_unroll,
                        devices=devices, expect_kernel=expect_kernel,
                        **layout)
        tag = f"multichip {layout}"
        print(f"[smoke] {tag}: bytes_in_use per device {r['bytes_in_use']}, "
              f"param leaf on {r['param_devices']} devices, step-0 loss "
              f"{r['losses'][0]:.4f} vs one-device {ref:.4f}", flush=True)
        used = r["bytes_in_use"]
        if all(u is None for u in used):
            print(f"[smoke] {tag}: backend reports no memory stats; "
                  "per-device bytes not checked")
        elif not all(used) or max(used) > 1.3 * min(used):
            raise AssertionError(f"{tag}: devices do not hold roughly "
                                 f"equal state: {used}")
        if r["param_devices"] != n_devices:
            raise AssertionError(f"{tag}: parameter leaf on "
                                 f"{r['param_devices']} devices")
        if abs(r["losses"][0] - ref) > 1e-2:
            raise AssertionError(f"{tag}: step-0 loss {r['losses'][0]} vs "
                                 f"one-device {ref}")
        results.append(r)
    return results


# -------------------------------------------------------------------- serve

def serve_phase(cfg, *, max_slots, max_length, decode_block, n_requests,
                prompt_range, budget_range, seed=0, expect_kernel=None,
                expect_write=None):
    """A ContinuousBatchingSession over a bf16 GPTForCausalLM(cfg) answers
    ``n_requests`` seeded requests (the first ``max_slots`` up front, the
    rest after the first step — overlapping lifetimes).

    Checks: every request ends DONE with exactly its budget;
    serving.step_retries and serving.quarantined stay 0; request 0's greedy
    continuation agrees with DecodeSession.generate on the same prompt at
    token 0 (the first differing index is printed); no attention dispatch
    fell back and, where ``expect_kernel`` is given, that kernel was
    dispatched; where ``expect_write`` is given, the cache was written
    through that form (``cache.write_dispatch{kernel}``).
    """
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.observability as obs
    from paddle_tpu.inference.decode import (ContinuousBatchingSession,
                                             DecodeSession, RequestState)
    from paddle_tpu.models.gpt import GPTForCausalLM

    paddle.seed(seed)
    model = GPTForCausalLM(cfg).bfloat16()
    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(0, cfg.vocab_size,
                         (int(rng.randint(*prompt_range)),)).astype(np.int32),
             int(rng.randint(*budget_range))) for _ in range(n_requests)]

    t0 = time.perf_counter()
    with obs.count_compiles() as compiles:
        with obs.window() as w, ContinuousBatchingSession(
                model, max_slots=max_slots, max_length=max_length,
                decode_block=decode_block) as cbs:
            rids = [cbs.submit(p, b) for p, b in reqs[:max_slots]]
            cbs.step()
            rids += [cbs.submit(p, b) for p, b in reqs[max_slots:]]
            results = cbs.results()
        serve_s = time.perf_counter() - t0
        prompt0, budget0 = reqs[0]
        with DecodeSession(model, max_length) as ds:
            ref = np.asarray(ds.generate(prompt0[None], budget0,
                                         seed=seed).numpy())[0]

    for rid, (prompt, budget) in zip(rids, reqs):
        res = results[rid]
        n_new = len(res.ids) - len(prompt)
        if res.state is not RequestState.DONE or n_new != budget:
            raise AssertionError(
                f"serve: request {rid} ended {res.state} with {n_new} "
                f"of {budget} tokens (error={res.error})")
    retries = w.value("serving.step_retries", default=0) or 0
    quarantined = w.value("serving.quarantined", default=0) or 0
    dispatch = _moved_counters(w.delta)
    writes = _moved_counters(w.delta, prefix="cache.write_dispatch")
    got = results[rids[0]].ids
    n0 = len(prompt0)
    diff = next((i for i in range(budget0)
                 if got[n0 + i] != ref[n0 + i]), None)
    total = sum(b for _p, b in reqs)
    print(f"[smoke] serve: {n_requests} requests DONE, {total} tokens in "
          f"{serve_s:.1f}s wall incl. compile ({compiles()} executables, "
          f"{compiles.seconds:.1f}s compiling; smoke observation, not a "
          "benchmark)")
    print(f"[smoke] serve: step_retries={int(retries)} "
          f"quarantined={int(quarantined)}")
    print(f"[smoke] serve: attention dispatch {dispatch or '{}'}")
    print(f"[smoke] serve: cache write {writes or '{}'}")
    print("[smoke] serve: request 0 vs DecodeSession.generate: " +
          (f"identical over {budget0} tokens" if diff is None
           else f"first difference at generated token {diff} of {budget0}"),
          flush=True)
    if retries or quarantined:
        raise AssertionError(f"serve: step_retries={retries} "
                             f"quarantined={quarantined} (expected 0)")
    if diff == 0:
        raise AssertionError("serve: request 0 differs from "
                             "DecodeSession.generate at token 0")
    fallbacks = {k: n for k, n in dispatch.items()
                 if k.startswith("attn.dispatch_fallback")}
    if fallbacks:
        raise AssertionError(f"serve: attention fell back: {fallbacks}")
    if expect_kernel is not None and not dispatch.get(
            f"attn.dispatch{{kernel={expect_kernel}}}"):
        raise AssertionError(
            f"serve: expected the {expect_kernel!r} Pallas kernel to be "
            f"dispatched, saw {dispatch}")
    if expect_write is not None and not writes.get(
            f"cache.write_dispatch{{kernel={expect_write}}}"):
        raise AssertionError(
            f"serve: expected the cache written through {expect_write!r}, "
            f"saw {writes}")
    del model
    gc.collect()
    jax.clear_caches()
    return {"requests": n_requests, "tokens": total, "first_diff": diff,
            "dispatch": dispatch, "writes": writes, "serve_s": serve_s}


# --------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + train + serve on one chip (default); "
                         "4: the train path on four chips, dp=4/zero1 and "
                         "dp=2 x tp=2 + sp")
    args = ap.parse_args(argv)

    device = device_report()
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: the attached device is "
                 f"{device['platform']!r}, not 'tpu' — this script only "
                 "runs on a chip")

    from paddle_tpu import compile_cache, native
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.ops.pallas import autotune

    cache_dir = compile_cache.enable()
    entries0 = compile_cache.entry_count(cache_dir)
    print(f"[smoke] compile cache {cache_dir}: {entries0} entries at start")
    native.get_lib()
    print(f"[smoke] native library: {native.status()}")
    table = autotune._cache_path()
    print(f"[smoke] attention autotune table {table}: " +
          ("PRESENT (overrides the static chain)" if os.path.exists(table)
           else "absent (the static chain decides)"), flush=True)

    # the flagship width: GPT-3 1.3B, as configs/gpt3-1.3b.json has it
    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=1024)
    if args.chips == 4:
        multichip_phase(cfg, batch=16, seq=1024, steps=3,
                        scan_unroll=SCAN_UNROLL, expect_kernel="simple",
                        layouts=({"dp": 4, "zero1": True},
                                 {"dp": 2, "tp": 2, "sp": True}))
    else:
        kernel_phase()
        check_decode_kernel()
        check_cache_write()
        check_expert_rows()
        train_phase(cfg, batch=4, seq=1024, steps=4,
                    scan_unroll=SCAN_UNROLL, expect_kernel="simple")
        serve_phase(GPTConfig.gpt3_1p3b(), max_slots=8, max_length=512,
                    decode_block=16, n_requests=16,
                    prompt_range=(32, 128), budget_range=(64, 128),
                    expect_kernel="decode_ragged", expect_write="row_dma")

    print(f"[smoke] compile cache {cache_dir}: "
          f"{compile_cache.entry_count(cache_dir)} entries at end "
          f"({entries0} at start)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
