"""Serving by diffusion over blocks with a backlog: every request is
submitted before the window, more than it can finish, so a request always
waits for the next free slot and arrivals play no part. What the window
reads is the block dispatch itself: ``denoising_steps`` passes and a commit
pass over every slot, up to ``block_length`` tokens a lane.

As ``serve_backlog.py``: set-up ends after ``ramp_steps`` calls of
``step()`` on the same traffic; counted are the tokens delivered to the
host in the window (the program's ``serving.decode_tokens`` counter) and
the requests that finished in it. ``matches_reference`` is the replay of
``serving_blocks.py``: a sample of those requests, as the timed session
generated them, through the float32 reference after the window has closed.
"""
import statistics
import time

import paddle_tpu.observability as obs

import generate
import serving
import serving_blocks


def run(ctx):
    t = ctx.traffic
    with obs.window() as counters:
        served = serving_blocks.ServedBlocks(ctx)
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
        served.check_against_reference(since=t0)
    in_window = [rid for rid in finished if served.req[rid]["done"] >= t0]
    tpot = served.tpot(since=t0)
    checks = dict(served.checks, **serving.counter_checks(counters.delta))
    checks["backlog_never_empty"] = backlog_left > 0
    checks["every_request_done_with_its_budget"] = not wrong and bool(tpot)
    ctx.note(f"{tokens:.0f} tokens, {len(in_window)} requests finished in "
             f"{ctx.window_s:.3f}s, {backlog_left} still queued")
    return {"metrics": {"serve_tokens_per_s": tokens / ctx.window_s,
                        "serve_tpot_p50_s": statistics.median(tpot)
                        if tpot else float("nan")},
            "attempted": len(in_window), "failed": len(wrong),
            "checks": checks,
            "counts": {"samples": [(s - t0, r, v)
                                   for s, r, v in served.samples if s >= t0],
                       "slots": served.slots,
                       "passes": t["denoising_steps"] + 1}}
