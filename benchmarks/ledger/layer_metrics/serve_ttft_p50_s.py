"""Median of the time to first token (host sees it - request was due), over
the same requests as ``serve_ttft_p90_s``."""
import statistics


def read(reduced, counts, config, peaks):
    ttft = counts.get("ttft_s")
    return statistics.median(ttft) if ttft else None
