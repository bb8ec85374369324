"""Median host time of one un-awaited ``step(...)`` call, in ms: the
enqueue, not the step. Source: the loop's clock around each call."""
import statistics


def read(reduced, counts, config, peaks):
    calls = counts.get("dispatch_s")
    return statistics.median(calls) * 1e3 if calls else None
