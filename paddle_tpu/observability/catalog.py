"""Canonical metric-name catalog — the single registry of record.

Every ``obs.counter/gauge/histogram`` (and ``_count`` wrapper) call
site in ``paddle_tpu/`` must use a name declared here; a lint-style
test (``tests/test_metric_catalog.py``) AST-walks the package and
fails on any emission whose name is missing, so dashboards, the
Prometheus scrape endpoint and the benchmark's readers can never
silently drift from what the code actually emits.

Each entry: ``kind`` (counter|gauge|histogram), ``help`` (one line,
doubles as dashboard description), ``labels`` (tuple of label KEYS the
site may attach — values are free-form; label-set cardinality is
bounded by ``Registry.max_series_per_name``). Entries with
``internal=True`` are registered by the observability layer itself
rather than through a walker-visible call site.
"""
from __future__ import annotations


def _m(kind, help, labels=(), internal=False):  # noqa: A002 (help)
    return {"kind": kind, "help": help, "labels": tuple(labels),
            "internal": internal}


CATALOG = {
    # ------------------------------------------------------- training
    "train.steps": _m("counter", "optimizer steps completed"),
    "train.step_time_s": _m("histogram", "wall time per optimizer step"),
    "train.samples": _m("counter", "training samples consumed"),
    "train.samples_per_s": _m("gauge", "samples/s of the last step"),
    "train.tokens": _m("counter", "training tokens consumed"),
    "train.tokens_per_s": _m("gauge", "tokens/s of the last step"),
    "train.mfu": _m("gauge",
                    "achieved model-flops utilization of the last step"),
    # ------------------------------------- training robustness (ISSUE 15)
    "train.nan_steps": _m(
        "counter", "train steps whose loss/grads were non-finite "
        "(step guard detections)"),
    "train.skipped_steps": _m(
        "counter", "optimizer updates skipped by the step guard or "
        "the AMP loss scaler"),
    "train.hang_aborts": _m(
        "counter", "train steps aborted by the stall/collective "
        "watchdog instead of hanging"),
    "train.straggler_ranks": _m(
        "gauge", "straggler ranks named by the last hang report"),
    "train.preemptions": _m(
        "counter", "preemption notices honored with a committed "
        "checkpoint flush before exit"),
    "train.checkpoint_saves": _m(
        "counter", "committed train-state checkpoints written"),
    "train.restarts": _m(
        "counter", "supervised in-process restarts (run_resilient)"),
    # ------------------------------------------------- jit / compiles
    "jit.xla_compiles": _m("counter",
                           "XLA executable builds process-wide"),
    "jit.trace_s": _m(
        "counter", "seconds jax spent tracing functions to jaxprs "
        "(nested traces each count their own)"),
    "jit.lower_s": _m(
        "counter", "seconds jax spent lowering jaxprs to MLIR modules"),
    "jit.backend_compile_s": _m(
        "counter", "seconds in the backend's compile, or in the "
        "retrieval on a persistent-cache hit"),
    "jit.compile_wall_s": _m(
        "counter", "wall seconds jax spent tracing, lowering and "
        "compiling: the length of the union of the three events' time "
        "spans, so nested and repeated traces count once"),
    "jit.cache_hits": _m(
        "counter", "executables found in the persistent compile cache"),
    "jit.cache_misses": _m(
        "counter", "executables compiled and written to the persistent "
        "compile cache"),
    "jit.fn_calls": _m("counter", "StaticFunction calls", ("fn",)),
    "jit.fn_cache_hits": _m("counter",
                            "StaticFunction spec-cache hits", ("fn",)),
    "jit.fn_probes": _m("counter",
                        "StaticFunction eager probe runs", ("fn",)),
    "jit.fn_builds": _m("counter",
                        "StaticFunction specialization builds", ("fn",)),
    "jit.fn_graph_breaks": _m("counter",
                              "StaticFunction graph breaks", ("fn",)),
    "jit.static_functions": _m("gauge",
                               "live StaticFunction count (collector)"),
    "jit.specializations": _m("gauge",
                              "total jit specializations (collector)"),
    "jit.xla_executables": _m("gauge",
                              "total cached executables (collector)"),
    "jit.graph_breaks": _m("gauge",
                           "total graph breaks (collector)"),
    # ------------------------------------------------------ pipelines
    "pipeline.bubble_fraction": _m(
        "gauge", "analytic bubble fraction at trace time", ("schedule",)),
    "pipeline.makespan_ticks": _m(
        "gauge", "schedule makespan in ticks", ("schedule",)),
    "pipeline.stages": _m("gauge", "pipeline stages", ("schedule",)),
    "pipeline.microbatches": _m(
        "gauge", "pipeline microbatches", ("schedule",)),
    "pipeline.traces": _m(
        "counter", "schedule trace events", ("schedule",)),
    # -------------------------------------------------------- serving
    "serving.generate_calls": _m("counter", "DecodeSession.generate calls"),
    "serving.prefill_tokens": _m("counter", "prompt tokens prefilled"),
    "serving.decode_tokens": _m("counter", "tokens decoded"),
    "serving.generate_latency_s": _m(
        "histogram", "end-to-end generate() latency"),
    "serving.request_latency_s": _m(
        "histogram", "submit-to-retire latency per request"),
    "serving.prefill_padded_tokens": _m(
        "counter", "prompt tokens prefilled, padding to the bucket "
        "included"),
    "serving.first_tokens": _m(
        "counter", "first tokens delivered (the admit program's; part "
        "of serving.decode_tokens)"),
    "serving.decode_lane_steps": _m(
        "counter", "decode lane-steps dispatched: max_slots x steps, "
        "whether or not a lane held a request within its budget"),
    "serving.decode_cache_positions": _m(
        "counter", "cached positions of the stepping lanes at each decode "
        "dispatch x its steps: what decode attention has a reason to read"),
    "serving.decode_cache_capacity": _m(
        "counter", "max_slots x capacity x steps at each decode dispatch: "
        "the whole cache buffer, the denominator of the valid share"),
    "serving.block_dispatches": _m(
        "counter", "block-diffusion dispatches: one block for every "
        "stepping lane, its denoising passes and its commit pass"),
    "serving.block_lane_passes": _m(
        "counter", "block-diffusion lane-passes dispatched: max_slots x "
        "(denoising_steps + 1) a dispatch, whoever sat in the lane"),
    "serving.block_open_positions": _m(
        "counter", "positions open at a block dispatch over its stepping "
        "lanes: the tokens there were to generate"),
    "serving.block_discarded_tokens": _m(
        "counter", "tokens of a fetched block that its request no longer "
        "took (past the budget, after eos, evicted)"),
    "serving.step_s": _m("histogram", "wall time of one step()"),
    "serving.step_host_s": _m(
        "histogram", "step() less the seconds it waited in its fetch: "
        "the host's own time"),
    "serving.step_phase_s": _m(
        "histogram", "wall time of one phase of step(): admit (one "
        "request), dispatch, fetch, deliver", ("phase",)),
    "serving.cycle_s": _m(
        "counter", "a session's wall seconds from its first step()'s "
        "entry to its last one's return, each in exactly one part: "
        "caller (a return to the next entry, work held), no_work (the "
        "same gap, none held), expire, admit, dispatch, fetch_wait (the "
        "chip runs the block), fetch_copy (its tokens cross), deliver, "
        "other (the rest of step()); the parts sum to the wall",
        ("part",)),
    "serving.starved_s": _m(
        "counter", "the seconds of serving.cycle_s between a fetch's "
        "wait returning and the next device call's return, while the "
        "session held work: by its own books the chip had nothing of its "
        "to run (a lower bound on the idle the host causes)", ("part",)),
    "serving.cycle_part_s": _m(
        "histogram", "seconds of one part (caller, fetch_copy: the two "
        "a median is read of; serving.cycle_s carries every part's sum) "
        "in one cycle (a step()'s return to the next one's return) that "
        "dispatched a decode block, zeros included", ("part",)),
    "serving.cycle_starved_s": _m(
        "histogram", "starved seconds, all parts, of one cycle that "
        "dispatched a decode block"),
    "serving.queue_wait_s": _m(
        "histogram", "submit to admit program dispatched, per request"),
    "serving.first_token_hold_s": _m(
        "histogram", "admit program dispatched to first token on the "
        "host, per request"),
    "serving.ttft_s": _m(
        "histogram", "submit to first token on the host, per request"),
    "serving.tpot_s": _m(
        "histogram", "(done - first token) / (tokens - 1), per DONE "
        "request of more than one token"),
    "serving.requests_submitted": _m("counter", "requests submitted"),
    "serving.requests_completed": _m("counter", "requests retired"),
    "serving.admits": _m("counter", "slot admissions"),
    "serving.steps": _m("counter", "continuous-batching steps"),
    "serving.queue_depth": _m("gauge", "requests waiting for a slot"),
    "serving.slots_active": _m("gauge", "slots currently decoding"),
    "serving.slot_utilization": _m("gauge", "active slots / max slots"),
    "serving.inflight_requests": _m(
        "gauge", "submitted-but-undelivered requests"),
    # -------------------------------------- serving robustness (ISSUE 14)
    "serving.rejected": _m(
        "counter", "requests shed by admission control (fast "
        "rejections + priority-lane evictions)"),
    "serving.timed_out": _m(
        "counter", "requests evicted at a TTFT/total deadline"),
    "serving.cancelled": _m(
        "counter", "requests cancelled by the caller or session close"),
    "serving.step_retries": _m(
        "counter", "device-step retries inside the backoff envelope"),
    "serving.quarantined": _m(
        "counter", "poison requests failed+isolated by step-failure "
        "recovery (admit-time or bisection)"),
    "serving.degraded": _m(
        "gauge", "1 while readiness reports degraded "
        "(queue/slot pressure past thresholds)"),
    # ---------------------------------------------- mixture of experts
    "moe.assignments": _m(
        "counter", "(token, expert) pairs routed, all layers: in the "
        "stepping lanes of the block-diffusion passes and of the one-token "
        "decode steps (zero-compute experts' picks included); in training, "
        "over every step (ticked once from the train state's running sum)"),
    "moe.busiest_expert_assignments": _m(
        "counter", "pairs routed to the busiest expert of a layer in a "
        "pass (in training: in a step; where a share of the experts is "
        "held and served: the busiest held expert), summed over layers and "
        "passes: x num_experts / moe.assignments is 1.0 where the load is "
        "even"),
    "moe.layer_passes": _m(
        "counter", "expert layers run: layers x passes of the "
        "block-diffusion dispatches or x steps of the one-token decode "
        "blocks; in training, the expert layers whose running sums were "
        "read"),
    "moe.experts_touched": _m(
        "counter", "experts held here that a token of any lane reached, "
        "summed over moe.layer_passes: the expert weights the passes had to "
        "read"),
    "moe.zero_assignments": _m(
        "counter", "pairs routed to a zero-compute expert (the identity: "
        "no weights, no grouped product), of moe.assignments, in the "
        "stepping lanes of the one-token decode steps"),
    "moe.held_assignments": _m(
        "counter", "pairs routed to the experts held here (expert_offset "
        ".. + num_local_experts), of moe.assignments: what the grouped "
        "products really multiplied"),
    "moe.grouped_dispatch": _m(
        "counter", "grouped products of a differentiated expert layer at "
        "trace time, by kernel (gmm: the megablox Pallas kernels, on a "
        "TPU; jax.lax.ragged_dot, elsewhere, differentiates itself and is "
        "not counted) and pass (fwd; dx: the rows' cotangent; dw: the "
        "weights', tgmm); a forward-only trace ticks nothing",
        ("kernel", "pass")),
    "moe.row_buffer": _m(
        "counter", "expert layers that hold a share of the experts and "
        "walk the held experts' pairs a buffer of rows a trip (rows=held: "
        "their even share of the pairs x 1.5), at trace time; a layer that "
        "holds every expert passes over all its pairs once and ticks "
        "nothing", ("rows",)),
    # ------------------------------------------- state-space layers
    "ssm.scan_dispatch": _m(
        "counter", "prefill scans of a state-space mixer at trace time, "
        "by the form the backend chose (chunked: the Pallas kernel, on a "
        "TPU; sequential: a lax.scan over time, elsewhere); neither is a "
        "fallback", ("kernel",)),
    "cache.bytes": _m(
        "gauge", "bytes of a serving session's cache at its "
        "construction, by the kind of entry (kv: keys and values by "
        "position; latent: latent attention's normed latent and rope key "
        "by position; recurrent: a state-space layer's window and scan "
        "state, whatever the length)", ("kind",)),
    # ----------------------------------------------------- dataloader
    "dataloader.fetch_wait_s": _m(
        "histogram", "time the consumer waited on the loader"),
    "dataloader.batches": _m("counter", "batches produced"),
    # ---------------------------------------------------- collectives
    "collective.calls": _m("counter", "collective op launches", ("op",)),
    "collective.bytes": _m("counter", "bytes moved by collectives",
                           ("op",)),
    # -------------------------------------------------- eager dispatch
    "eager.op_dispatches": _m("counter", "eager op dispatches"),
    "eager.grad_ops": _m("counter", "ops recorded on the eager tape"),
    # ------------------------------------------------------ attention
    "attn.dispatch": _m("counter",
                        "attention kernel dispatches at trace time (an "
                        "MLA sublayer's by its form: latent_absorbed, the "
                        "one-token step over the latent cache; "
                        "mla_expanded, a prefill)",
                        ("kernel",)),
    "attn.latent_dispatch": _m(
        "counter", "absorbed latent-attention steps at trace time, by the "
        "form that took them (ragged: the Pallas kernel over each slot's "
        "valid rows, scores on chip; einsum: XLA over the whole capacity, "
        "every backend but the TPU and a shape the kernel's gate refuses)",
        ("kernel",)),
    "attn.dispatch_fallback": _m(
        "counter", "shape-gate rejections falling back to XLA",
        ("reason",)),
    "attn.matmul_operands": _m(
        "counter", "traced calls of an attention kernel, by the dtype "
        "its matrix products take their operands in (the arrays' own; "
        "every product accumulates in float32)", ("kernel", "dtype")),
    "cache.write_dispatch": _m(
        "counter", "KV cache writes at trace time, by the form the shape "
        "and backend chose (row_dma: one Pallas program of copies; "
        "update_slice: XLA's dynamic_update_slice)", ("kernel",)),
    "zero1.moment_shard": _m(
        "counter", "Adam moment leaves at trace time, by the dim ZeRO-1 "
        "gave to dp (in_layer: a dim of the leaf's own shape, where a "
        "layer's dp gradient sum lands as a reduce-scatter; layer: a "
        "layer-stack dim, the fallback of a leaf whose own dims do not "
        "divide; none: the leaf carries dp already or no dim divides)",
        ("dim",)),
    "attn.autotune_candidate_errors": _m(
        "counter", "autotune candidates the compiler or runtime "
        "refused (text kept in the table entry)", ("kernel",)),
    # ------------------------------------------------------ autotuner
    "autotuner.trials": _m("counter",
                           "auto-tuner candidates measured", ("source",)),
    "autotuner.trials_skipped": _m(
        "counter", "candidates satisfied from the warm-start trial log"),
    "autotuner.pruned": _m("counter",
                           "candidates refused before measurement",
                           ("reason",)),
    "autotuner.best_score": _m("gauge",
                               "score of the best candidate so far"),
    # -------------------------------------------------- observability
    "metrics.scrapes": _m("counter", "/metrics HTTP scrapes served"),
    "metrics.dropped_series": _m(
        "counter",
        "metric lookups dropped by the per-name cardinality cap",
        internal=True),
}

#: every ``jax.named_scope`` the package opens: the operation metadata of
#: the compiled programs, which a device trace's per-kernel shares read.
#: ``tests/test_metric_catalog.py`` holds it to the literals in the code.
SCOPES = {
    # models/gpt_hybrid.py (training)
    "block": "one transformer block of the scanned stack",
    "attend": "the block's attention, inside its shard_map",
    "mlp": "the block's feed-forward",
    "lm_head_ce": "the head and the fused cross-entropy",
    "adamw_update": "the optimizer's update",
    # inference/decode.py (serving)
    "admit": "an admit program: a b=1 prefill into a slot",
    "decode_step": "one masked step of a decode block",
    "cache_attention": "write-then-attend against a KV cache",
    "write_kv": "the cache write (row-DMA program or XLA's update)",
    "sample": "on-device sampling",
    "block_denoise": "a denoising pass of a block-diffusion dispatch",
    "block_commit": "the commit pass of a block-diffusion dispatch",
    # models/sdar_moe.py, lfm2_moe.py, longcat_flash.py (experts)
    "moe_router": "the router: scores and the choice of experts",
    "moe_experts": "the held experts' grouped products (expert_ffn)",
    "moe_experts_bwd": "the grouped products' backward pass",
    "zero_experts": "the zero-compute (identity) experts' part",
    "expert_bias_update": "the routing bias rule after a train step",
    "dense_mlp": "a dense SwiGLU",
    # models/lfm2_moe.py, jamba.py (operators)
    "short_conv": "a gated short convolution",
    "attention_operator": "an attention operator of a hybrid stack",
    "mamba_mixer": "a Mamba-1 mixer",
    "causal_conv": "the mixer's causal convolution",
    "selective_scan": "a prefill's selective scan",
    "ssm_step": "the one-token state update",
    "attention_mixer": "an attention layer of a hybrid stack",
    # models/longcat_flash.py (latent attention)
    "mla_expanded": "latent attention expanded to heads: a prefill",
    "mla_absorbed": "latent attention over the latent cache: one step",
    "latent_attention": "the absorbed step's kernel (score, softmax, p . c)",
}


def names() -> set:
    return set(CATALOG)


def internal_names() -> set:
    """Names registered by the observability layer itself (no
    walker-visible literal call site required)."""
    return {n for n, d in CATALOG.items() if d["internal"]}


def check(name: str) -> None:
    """Raise KeyError with a pointed message for an uncataloged name
    (used by tests; production emission never pays this check)."""
    if name not in CATALOG:
        raise KeyError(
            f"metric {name!r} is not in observability/catalog.py — add "
            "it there (one canonical home) before emitting it")
