"""Open-loop serving: the loop an application would write around the session,
in one thread: submit what is due, ``step()``, read what finished. There is
no server process in the repo and the benchmark invents none, so a request
due while a decode block runs is submitted when ``step()`` returns, and its
time to first token, counted from when it was DUE, includes that wait.

Counted: requests due from ``ramp_s`` on (the first blocks fill an empty
session) up to the last arrival, ``drain_s`` before the window ends so that
each can get its first token inside it; one that did not is a failure.
"""
import statistics
import time

import numpy as np

import paddle_tpu.observability as obs

import generate
import serving


def drive(served, schedule, seconds, span, tick=lambda: None):
    """The loop itself, for ``seconds`` from now. Returns its start (a
    ``time.perf_counter()``; the schedule's due times count from it) and how
    many of the schedule's requests were submitted."""
    t0 = time.perf_counter()
    nxt = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            return t0, nxt
        tick()
        with span("bench.submit"):
            while nxt < len(schedule) and schedule[nxt][0] <= now:
                due, ids, new = schedule[nxt]
                served.submit(ids, new, t0 + due, time.perf_counter())
                nxt += 1
        if served.idle():
            wait = schedule[nxt][0] - now if nxt < len(schedule) \
                else seconds - now
            time.sleep(max(0.0, min(wait, 0.002)))
            continue
        with span("bench.step"):
            served.step()


def run(ctx):
    t = ctx.traffic
    arr = t["arrivals"]
    with obs.window() as counters:
        served = serving.Served(ctx)
        schedule = generate.schedule(t, ctx.seconds, served.slots, ctx.seed,
                                     served.vocab)
        buckets = served.warm(schedule)
        ctx.note(f"{len(schedule)} requests, prefill buckets {buckets}")
        with ctx.window():
            t0, nxt = drive(served, schedule, ctx.seconds, ctx.span, ctx.tick)
            ctx.close()
        finished, wrong = served.finish()
    ramp = t0 + arr["ramp_s"]
    counted = [r for r in served.req.values() if r["due"] >= ramp]
    unanswered = [r for r in counted if r["first"] is None]
    ttft = [r["first"] - r["due"] for r in counted if r["first"] is not None]
    tpot = served.tpot(since=ramp)
    if ctx.trace_from_s is not None:
        # starting the profiler stalls this thread for a second or two: the
        # traced run's own counts stop where the trace starts
        counted = [r for r in counted if r["due"] < t0 + ctx.trace_from_s]
        ttft = [r["first"] - r["due"] for r in counted
                if r["first"] is not None]
    late = [r["submit"] - r["due"] for r in counted]
    checks = dict(served.checks, **serving.counter_checks(counters.delta))
    checks["every_arrival_submitted"] = nxt == len(schedule)
    checks["every_request_done_with_its_budget"] = not wrong and bool(tpot)
    checks["every_counted_request_answered"] = not unanswered and bool(ttft)
    ctx.note(f"{len(counted)} counted requests, {len(finished)} finished in "
             f"{ctx.window_s:.3f}s")
    nan = float("nan")
    return {"metrics": {"serve_ttft_p90_s": float(np.percentile(ttft, 90))
                        if ttft else nan,
                        "serve_tpot_p50_s": statistics.median(tpot)
                        if tpot else nan},
            "attempted": len(counted),
            "failed": len(unanswered) + len(wrong), "checks": checks,
            "counts": {"samples": [(s - t0, r, v)
                                   for s, r, v in served.samples if s >= ramp],
                       "slots": served.slots, "ttft_s": ttft,
                       "lateness_s": late}}
