"""Model FLOP/s utilisation, in %: operations the forward and backward
passes REQUIRE per token (``arch``: causal attention once, no
recomputation) times the tokens per second of this (traced) run's whole
window, over chips times the published peak. An end-to-end utilisation,
not a kernel's roofline share."""
from byname import load_module


def read(reduced, counts, config, peaks):
    if "tokens_per_s" not in counts:
        return None
    arch = load_module("arch", config["arch"])
    flops = arch.train_flops_per_token(config["sizes"], counts["seq"])
    return 100.0 * flops * counts["tokens_per_s"] \
        / (counts["chips"] * peaks["flops_per_s"])
