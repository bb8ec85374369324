"""Serving with a backlog, checked twice: ``serve_backlog.py``'s set-up and
window, then a REPLAY of what the window generated.

Before the window ``serving.Served`` holds a small session of every layer
to the float32 reference (``serving.reference_check``). That says the model
and the session are right at a few hundred positions and a handful of
lanes. It does not say that the TIMED session was: 256 lanes in and out of
step, six prefill buckets, lanes paused, emptied and reused, contexts of
thousands of positions. So after the window a sample of the requests that
finished in it (``reference.replay_requests``, spread evenly over the
prompt lengths, the shortest and the longest among them) is teacher-forced
through the reference: the prompt and the generated tokens go in, and every
generated token must be the reference's argmax wherever the reference's top
two logits differ by more than ``reference.replay_margin_tolerance``; no
fewer than ``reference.replay_compared_floor`` tokens may be compared.

Tokens do not see the precision of the recurrent state, so a third check,
``state_matches_reference``, holds a small session's scan states to the
reference's after the same tokens (``state_readings``; limits and the chip
readings behind them in ``reference.state``). On the chip a fourth,
``scan_through_the_kernel_alone``: ``ssm.scan_dispatch{kernel=chunked}`` is
the only form that ticked.

``serving.Served.finish`` fetches the session's results and drops them;
``Kept`` keeps them (``serving_blocks``' ``finish`` and sampling, which are
not about blocks). The reference's model is built again from the seed
after the session has been closed (the same seed draws the same leaves):
its leaves go to the host, and the reference runs a layer at a time.
"""
import gc
import statistics
import time

import numpy as np
import paddle_tpu.observability as obs

import generate
import serving
import serving_blocks


class Kept(serving.Served):
    """``serving.Served`` whose results outlive ``finish``: the
    block-diffusion loop's ``finish`` (results kept as ``self.results``,
    the session closed and let go, so that its cache and programs leave
    the chip to the reference)."""
    finish = serving_blocks.ServedBlocks.finish


def replay(arch, params, num_heads, requests, tolerance):
    """``requests``: [(prompt ids, generated ids, _)], as
    ``serving_blocks.sample_for_replay`` gives them. Returns
    counts: generated tokens ``compared`` (the reference's top-two margin
    over ``tolerance``), ``skipped``, ``differ`` (compared, and not the
    reference's argmax), and the six widest margins at which a generated
    token was not the argmax whatever the tolerance (what the tolerance is
    set against)."""
    texts = [np.concatenate([prompt, new]) for prompt, new, _ in requests]
    longest = max(len(ids) for ids in texts)
    width = -(-(longest - 1) // 256) * 256     # one shape, one compile
    batch = np.zeros((len(texts), width), np.int32)
    for i, ids in enumerate(texts):
        batch[i, :len(ids) - 1] = ids[:-1]
    argmax, margin = arch.reference_top2(params, batch, num_heads)
    out = {"compared": 0, "skipped": 0, "differ": 0}
    at_differing = []
    for i, (prompt, new, _) in enumerate(requests):
        # the positions that predict the new tokens
        at = slice(len(prompt) - 1, len(prompt) + len(new) - 1)
        sure = margin[i, at] > tolerance
        differs = argmax[i, at] != new
        out["compared"] += int(sure.sum())
        out["skipped"] += int((~sure).sum())
        out["differ"] += int((sure & differs).sum())
        at_differing += margin[i, at][differs].tolist()
    out["widest_margins_at_differing_tokens"] = [
        round(m, 4) for m in sorted(at_differing, reverse=True)[:6]]
    return out


def slow_entries(layer, rate):
    """[N, I] bool: the entries of a Mamba layer's scan state that forget
    less than ``rate`` of themselves a position, by the layer's own leaves
    (``softplus(dt_bias) * exp(A_log)``: the step a channel takes at rest
    times the state's decay constant)."""
    step = np.logaddexp(0.0, np.asarray(layer["dt_bias"]).astype(np.float32))
    decay = np.exp(np.asarray(layer["A_log"]).astype(np.float32))
    return step[None, :] * decay < rate


def state_readings(ctx, model, cfg, params):
    """What the tokens cannot show: the recurrent state a session leaves
    behind. A small session over ``model`` runs one prompt of each of
    ``reference.state.prompt_tokens`` lengths, each padded to its bucket,
    for ``reference.state.new_tokens`` tokens (one from the admit and
    whole decode blocks, so the state has consumed what the host was given
    and no more); its recurrent entries are fetched through
    ``cache_entries()`` and each sequence's slot is found by its length.
    Returns (errors, slow, dtypes). ``errors[sequence, Mamba layer]``: the
    norm of (the slot's scan state - the reference's after the same tokens)
    over the norm of the reference's: what a scan over padding, a lane
    stepped out of turn or a state leaked between requests moves by its
    own size, in every layer. ``slow[sequence]``: the same over the first
    layer's ``slow_entries`` alone, and that one sees the state's
    PRECISION. The first layer's inputs are the embedding's rows, the same
    on both sides, so its error is the rounding of the session's own
    arithmetic: bf16 products that each new term brings once, the same
    share of every entry however long it remembers, against a rounding of
    the state itself, which an entry that remembers hundreds of positions
    takes hundreds of times. ``dtypes``: of the recurrent leaves."""
    state = ctx.config["reference"]["state"]
    rng = np.random.RandomState(ctx.seed + 2)
    prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
               for n in state["prompt_tokens"]]
    session, _ = serving.open_session(ctx, model, len(prompts),
                                      state["capacity"])
    with session:
        rids = [session.submit(p, state["new_tokens"]) for p in prompts]
        results = session.results()
        held = [e for e in session.cache_entries() if e.kind == "recurrent"]
        consumed = np.asarray(held[0].length)
        states = [np.asarray(e.ssm) for e in held]       # [slots, N, I]
        dtypes = {str(a.dtype) for e in held for a in (e.conv, e.ssm)}
    first = next(lp for lp in params["layers"] if "in_proj" in lp)
    among = slow_entries(first, state["slow_rate"])
    errors = np.zeros((len(prompts), len(states)))
    slow = np.zeros(len(prompts))
    for at, rid in enumerate(rids):
        ids = results[rid].ids
        # the last token was sampled and never fed back
        slot, = np.flatnonzero(consumed == len(ids) - 1)
        want = ctx.arch.reference_states(params, ids[None, :-1],
                                         cfg.num_heads)
        for layer, (got, h) in enumerate(zip(states, want)):
            errors[at, layer] = np.linalg.norm(got[slot] - h[0]) \
                / np.linalg.norm(h[0])
        slow[at] = np.linalg.norm((states[0][slot] - want[0][0])[among]) \
            / np.linalg.norm(want[0][0][among])
    return errors, slow, dtypes


def check_against_reference(ctx, served, since):
    """(``replay_matches_reference``, ``state_matches_reference``), the
    replay being of the requests that finished at or after ``since``;
    call after ``finish``."""
    ref = ctx.config["reference"]
    t_ref = time.perf_counter()
    requests = serving_blocks.sample_for_replay(served, since,
                                                ref["replay_requests"])
    model, cfg = serving.build_model(ctx)
    params = ctx.arch.from_serving_state(
        model.state_dict(), cfg.num_layers, cfg.rms_norm_eps)
    errors, slow, dtypes = state_readings(ctx, model, cfg, params)
    del model
    gc.collect()
    ctx.note(f"recurrent state {sorted(dtypes)} of {errors.shape[0]} "
             f"sequences against the reference's: worst of "
             f"{errors.shape[1]} layers {errors.max():.3e} relative, a "
             f"layer {[float(f'{e:.2e}') for e in errors.max(0)]}; the "
             f"first layer's slow entries "
             f"{[float(f'{e:.3e}') for e in slow]}")
    state_ok = bool(errors.max() <= ref["state"]["tolerance"]
                    and slow.max() <= ref["state"]["slow_tolerance"]
                    and dtypes == {ctx.config["build"]["serve"]
                                   ["state_dtype"]})
    if not requests:
        return False, state_ok
    counts = replay(ctx.arch, params, cfg.num_heads, requests,
                    ref["replay_margin_tolerance"])
    ctx.note(f"reference replay of {len(requests)} requests, prompts "
             f"{[len(prompt) for prompt, _new, _ in requests]}: {counts}; "
             f"{time.perf_counter() - t_ref:.1f}s after the window")
    return (counts["differ"] == 0
            and counts["compared"] >= ref["replay_compared_floor"]), state_ok


def run(ctx):
    t = ctx.traffic
    with obs.window() as counters:
        served = Kept(ctx)
        schedule = generate.schedule(t, ctx.seconds, served.slots, ctx.seed,
                                     served.vocab)
        buckets = served.warm(schedule)
        ctx.note(f"{len(schedule)} requests, prefill buckets {buckets}")
        for _due, ids, new in schedule:
            served.submit(ids, new, 0.0, 0.0)
        for _ in range(t["ramp_steps"]):
            served.step()
        delivered = obs.counter("serving.decode_tokens")
        with ctx.window():
            t0 = time.perf_counter()
            tokens0 = delivered.value
            while ctx.elapsed() < ctx.seconds:
                ctx.tick()
                with ctx.span("bench.step"):
                    served.step()
            tokens = delivered.value - tokens0
            ctx.close()
        backlog_left = len(served.waiting)
        finished, wrong = served.finish()
        replayed, state_held = check_against_reference(ctx, served,
                                                       since=t0)
    in_window = [rid for rid in finished if served.req[rid]["done"] >= t0]
    tpot = served.tpot(since=t0)
    checks = dict(served.checks, **serving.counter_checks(counters.delta))
    checks["replay_matches_reference"] = replayed
    checks["state_matches_reference"] = state_held
    checks["backlog_never_empty"] = backlog_left > 0
    checks["every_request_done_with_its_budget"] = not wrong and bool(tpot)
    scans = {c["labels"].get("kernel") for c in counters.delta.changed()
             if c["name"] == "ssm.scan_dispatch"}
    if ctx.on_chip:
        checks["scan_through_the_kernel_alone"] = scans == {"chunked"}
    ctx.note(f"{tokens:.0f} tokens, {len(in_window)} requests finished in "
             f"{ctx.window_s:.3f}s, {backlog_left} still queued")
    return {"metrics": {"serve_tokens_per_s": tokens / ctx.window_s,
                        "serve_tpot_p50_s": statistics.median(tpot)
                        if tpot else float("nan")},
            "attempted": len(in_window), "failed": len(wrong),
            "checks": checks,
            "counts": {"samples": [(s - t0, r, v)
                                   for s, r, v in served.samples if s >= t0],
                       "slots": served.slots}}
