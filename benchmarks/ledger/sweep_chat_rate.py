#!/usr/bin/env python3
"""Finds the knee of an open-loop serving mix, once: the highest offered rate
the session sustains without a growing queue. A scratch script, not a mode of
the harness: the cell's rate is then WRITTEN into its traffic file as a
number (a fraction of the knee), and the benchmark never searches.

    python benchmarks/ledger/sweep_chat_rate.py <config> <traffic> \
        <seconds per rate> <rate> [<rate> ...]

One process, one session; each rate gets the same generator as the cell
(``generate.schedule`` with ``rate_per_s`` replaced), then the session is
run dry before the next. Per rate it prints the requests offered, the
median and 90th percentile of time to first token, the most requests seen
waiting for a slot in the first and in the second half, and the requests
still unanswered at the end: a queue that grows through the run shows in
the second half.
"""
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


def main(argv):
    config_name, traffic_name, seconds = argv[0], argv[1], float(argv[2])
    rates = [float(r) for r in argv[3:]]
    import jax
    import generate
    import serving
    from peaks import peaks_for
    config = harness.load_json(HERE, "configs", config_name + ".json")
    traffic = harness.load_json(HERE, "traffic", traffic_name + ".json")
    if jax.default_backend() != "tpu":
        sys.exit("sweep: only on the chip")
    cell = {"name": "sweep", "chips": 1}
    ctx = harness.Context(cell, config, traffic, 1, seconds, 0,
                          jax.devices()[:1],
                          peaks_for(jax.devices()[0].device_kind))
    loop = harness.load_module("loops", traffic["loop"])
    served = serving.Served(ctx)
    schedule = generate.schedule(traffic, seconds, served.slots, 1,
                                 served.vocab)
    served.warm(schedule)
    rows = []
    for n, rate in enumerate(rates):
        t = dict(traffic, arrivals=dict(traffic["arrivals"], rate_per_s=rate))
        schedule = generate.schedule(t, seconds, served.slots, 100 + n,
                                     served.vocab)
        served.req.clear()
        served.samples.clear()
        queue = []                      # (time, requests waiting)
        t0, _ = loop.drive(
            served, schedule, seconds, ctx.span,
            lambda: queue.append((time.perf_counter(), len(served.waiting))))
        reqs = list(served.req.values())
        ttft = [r["first"] - r["due"] for r in reqs if r["first"] is not None]
        unanswered = sum(r["first"] is None for r in reqs)
        half = [max((q for s, q in queue if lo <= s - t0 < hi), default=0)
                for lo, hi in ((0, seconds / 2), (seconds / 2, seconds))]
        busy = float(np.mean([r for _s, r, _v in served.samples])) \
            / served.slots if served.samples else 0.0
        row = {"rate_per_s": rate, "offered": len(schedule),
               "ttft_p50_s": float(np.median(ttft)),
               "ttft_p90_s": float(np.percentile(ttft, 90)),
               "waiting_max_first_half": half[0],
               "waiting_max_second_half": half[1],
               "unanswered_at_end": unanswered,
               "finished_per_s": sum(r["done"] is not None for r in reqs)
               / seconds, "slot_occupancy": busy}
        print(json.dumps(row), flush=True)
        rows.append(row)
        while not served.idle():        # run dry before the next rate
            served.step()
    served.session.close()
    return rows


if __name__ == "__main__":
    main(sys.argv[1:])
