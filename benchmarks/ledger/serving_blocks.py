"""What the block-diffusion serving loop needs beside ``serving.py``: the
model built in its weights' dtype leaf by leaf, the session in its
block-diffusion mode, the comparison with the plain reference as a REPLAY of
what the timed session generated, and the bookkeeping of requests whose
first tokens arrive with their first block.

The replay. Under generation by diffusion over blocks a pass's input is the
block as the earlier passes left it, so the reference cannot be run ahead of
the system: a near-tie that rounds the other way would send the two down
different texts. Instead every pass is rebuilt from what the session
returned (tokens and, for each, the pass that fixed it,
``RequestResult.commit_steps``). What is replayed is a sample of the
requests that FINISHED IN THE WINDOW, from the timed session itself: every
lane live, every prefill bucket of the traffic, the cache at its real
lengths, every layer of the model. The reference (``arch.reference_passes``:
float32, ``highest``, the model's own leaves cast a layer at a time) gives
for every pass over every whole block of a sampled request what the pass
decides from, and the session is held to it three times: (i) every token a
pass fixed is the reference's argmax there; (ii) the positions a pass chose
are those the reference's confidences choose; (iii) pair by pair, a
position the pass fixed has no lower confidence than one it left open. A
position counts only where the reference is sure: its top-two logits, the
confidences compared, and in every layer the router's k-th and (k+1)-th
probability each differ by more than the configuration's tolerance (bf16
activations flip near-tied experts, and a flipped expert is about a tenth
of the layer's output). (ii) needs every open position of the block sure in
every layer, which a deep model rarely grants; (iii) needs two. The check
fails where anything compared differs, or where fewer were compared than
the configuration's floors (``reference.compared_floor``: what it names).

Beside that strict form a statistical one, since across six layers only a
few positions in a hundred are sure everywhere: of ALL tokens whose top-two
margin is over a lower bound, and of all pairs whose gap is, whatever the
router margins, the share that differs may not pass the configuration's
``reference.differing_share``. A flipped expert moves a few such tokens in
a thousand; a coarser product or a missing expert moves several times as
many (PERF.md has the readings the limits lie between).
"""
import collections
import dataclasses
import gc
import time

import jax
import numpy as np

import serving


def build_model(ctx):
    """The model in ``weights_dtype`` from the first leaf on: the whole of
    it in float32 would not fit the chip before a cast."""
    import paddle_tpu as paddle
    serve = ctx.config["build"]["serve"]
    config_cls = ctx.resolve(serve["model_config"])
    fields = {f.name for f in dataclasses.fields(config_cls)}
    sizes = {k: v for k, v in ctx.config.items() if k in fields}
    sizes.update(ctx.config["generation"])
    cfg = config_cls(**sizes, param_dtype=serve["weights_dtype"])
    paddle.seed(ctx.seed)
    return ctx.resolve(serve["entry"])(cfg), cfg


def session_kwargs(ctx):
    t = ctx.traffic
    kwargs = dict(ctx.config["build"]["serve"]["session_kwargs"],
                  denoising_steps=t["denoising_steps"],
                  remasking=t["remasking"])
    if "confidence_threshold" in t:
        kwargs["confidence_threshold"] = t["confidence_threshold"]
    return kwargs


def open_session(ctx, model, slots, capacity):
    serve = ctx.config["build"]["serve"]
    buckets = serving.default_buckets(capacity)
    session = ctx.resolve(serve["session"])(
        model, max_slots=slots, max_length=capacity,
        prefill_buckets=buckets, seed=ctx.seed, **session_kwargs(ctx))
    return session, buckets


# ------------------------------------------------------------ the replay

def replay(arch, params, static, requests, block, steps, remasking,
           threshold, tol, length, share_over=None, detail=None):
    """``requests``: [(prompt ids, generated ids, commit_steps)], each no
    longer than ``length``. Every whole block of a request is replayed; a
    last block that the budget cut is not (which pass fixed its dropped
    positions is not known). ``tol``: ``logit_margin``, ``confidence_gap``
    (relative), ``router_margin`` (relative). Returns counts: ``tokens``,
    ``choices`` and ``pairs`` each ``_compared``, ``_skipped`` and
    ``_differ``, and the widest margins at which the system differed
    whatever the tolerances (what the tolerances are set against).
    ``share_over``: {"token" | "pair": margin or gap}; those over it are
    counted as ``_over`` and ``_over_differ`` whatever the router margin.
    ``detail``: a list that is given every candidate as ("token" |
    "choice" | "pair", margin or gap, router margin, differs)."""
    schedule = arch.num_transfer_tokens(block, steps)
    seqs = np.zeros((len(requests), length), np.int32)
    fixed_at = np.full(seqs.shape, steps)     # -1 for a prompt token
    rows = []                                 # a request: [(start, pass)]
    for i, (prompt, new, commit_steps) in enumerate(requests):
        total = len(prompt) + len(new)
        seqs[i, :total] = np.concatenate([prompt, new])
        fixed_at[i, :len(prompt)] = -1
        fixed_at[i, len(prompt):total] = commit_steps
        rows.append([(start, t)
                     for start in range(len(prompt) // block * block,
                                        total // block * block, block)
                     for t in range(steps)
                     if (fixed_at[i, start:start + block] >= t).any()])
    most = max(map(len, rows))
    starts = np.zeros((len(requests), most), np.int64)
    opens = np.zeros((len(requests), most, block), bool)
    for i, rows_i in enumerate(rows):
        for n, (start, t) in enumerate(rows_i):
            starts[i, n] = start
            opens[i, n] = fixed_at[i, start:start + block] >= t
    hidden, router = arch.reference_passes(params, seqs, starts, opens,
                                           block, static)
    x0, margin, conf = arch.pass_stats(params, hidden, static)

    out = collections.Counter()
    widest = {"logit_margin_at_a_differing_token": 0.0,
              "confidence_gap_at_a_differing_pair": 0.0}

    def count(kind, sure, differs, *seen):
        out[f"{kind}s_compared" if sure else f"{kind}s_skipped"] += 1
        out[f"{kind}s_differ"] += bool(sure and differs)
        if seen[0] > (share_over or {}).get(kind, np.inf):
            out[f"{kind}s_over"] += 1
            out[f"{kind}s_over_differ"] += bool(differs)
        if detail is not None:
            detail.append((kind, *map(float, seen), bool(differs)))

    for i, rows_i in enumerate(rows):
        for n, (start, t) in enumerate(rows_i):
            sl = slice(start, start + block)
            open_now, chosen = opens[i, n], fixed_at[i, sl] == t
            sure_at = router[i, n] > tol["router_margin"]
            # (i) the tokens this pass fixed
            for j in np.flatnonzero(chosen):
                differs = seqs[i, sl][j] != x0[i, n, j]
                count("token", margin[i, n, j] > tol["logit_margin"]
                      and sure_at[j], differs, margin[i, n, j],
                      router[i, n, j])
                if differs:
                    key = "logit_margin_at_a_differing_token"
                    widest[key] = max(widest[key], float(margin[i, n, j]))
            if schedule[t] >= open_now.sum() \
                    and remasking == "low_confidence_static":
                continue                  # the pass had nothing to choose
            # (ii) the positions this pass chose
            want = arch.choose_transfer(conf[i, n], open_now, schedule[t],
                                        remasking, threshold)
            ranked = np.sort(conf[i, n][open_now])[::-1]
            k = int(want.sum())
            gap = (ranked[k - 1] - ranked[k]) / ranked[k - 1] \
                if k < len(ranked) else np.inf
            if remasking == "low_confidence_dynamic":
                gap = min(gap, np.abs(ranked / threshold - 1.0).min())
            count("choice", gap > tol["confidence_gap"]
                  and sure_at[open_now].all(), (want != chosen).any(), gap,
                  router[i, n][open_now].min())
            # (iii) each position fixed against each left open
            for a in np.flatnonzero(chosen):
                for c in np.flatnonzero(open_now & ~chosen):
                    ca, cc = conf[i, n, a], conf[i, n, c]
                    gap = abs(ca - cc) / max(ca, cc)
                    count("pair", gap > tol["confidence_gap"]
                          and sure_at[a] and sure_at[c], cc > ca, gap,
                          min(router[i, n, a], router[i, n, c]))
                    if cc > ca:
                        key = "confidence_gap_at_a_differing_pair"
                        widest[key] = max(widest[key], float(gap))
    return dict(out), widest


def sample_for_replay(served, since, how_many):
    """Of the requests that finished ``DONE`` at or after ``since``,
    ``how_many`` spread evenly over the prompt lengths, the shortest and
    the longest among them (so every prefill bucket that several requests
    used is in): [(prompt ids, generated ids, commit_steps)]."""
    done = sorted((r["plen"], rid) for rid, r in served.req.items()
                  if r["done"] is not None and r["done"] >= since
                  and served.results[rid].state.name == "DONE")
    if not done:
        return []
    at = np.unique(np.linspace(0, len(done) - 1, how_many).round()
                   .astype(int))
    out = []
    for plen, rid in (done[i] for i in at):
        result = served.results[rid]
        out.append((result.ids[:plen], result.ids[plen:],
                    result.commit_steps))
    return out


def judge(counts, ref):
    """``matches_reference`` from a replay's counts and the
    configuration's ``reference``: nothing compared differs, no fewer were
    compared than ``compared_floor`` names, and no larger share of those
    over ``differing_share``'s margins differs than it allows."""
    strict = not any(counts.get(k + "s_differ", 0)
                     for k in ("token", "choice", "pair")) \
        and all(counts.get(k + "_compared", 0) >= least
                for k, least in ref["compared_floor"].items())
    shares = all(counts.get(k + "s_over_differ", 0)
                 <= spec["at_most"] * counts.get(k + "s_over", 0)
                 for k, spec in ref.get("differing_share", {}).items())
    return strict and shares


def reference_check(ctx, served, since):
    """The timed session's own requests replayed through the reference.
    Returns (ok, counts, widest)."""
    t, ref = ctx.traffic, ctx.config["reference"]
    requests = sample_for_replay(served, since, ref["replay_requests"])
    if not requests:
        return False, {}, {}
    params = ctx.arch.from_serving_state(
        served.model.state_dict(), ctx.config["num_hidden_layers"])
    static = ctx.arch.static_config(dict(ctx.config,
                                         **ctx.config["generation"]))
    counts, widest = replay(
        ctx.arch, params, static, requests,
        ctx.config["generation"]["block_length"], t["denoising_steps"],
        t["remasking"], t.get("confidence_threshold", 0.9),
        ref["tolerances"], served.capacity,
        {k: spec["over"] for k, spec in
         ref.get("differing_share", {}).items()})
    return judge(counts, ref), counts, widest


# ------------------------------------------------------------ the window

class ServedBlocks(serving.Served):
    """``serving.Served`` for a block-diffusion session: its own model and
    reference check (after the window, of what the window generated),
    prefill buckets by the prompt's whole blocks, and a request's
    first-token time taken at the return of the ``step()`` that delivered
    its first block (the session's ``generated`` says so), not the one in
    which it left ``QUEUED``: admit yields no token in this mode.
    ``submit`` and ``tpot`` are the parent's."""

    def __init__(self, ctx):      # the parent's builds the GPT-3 pieces
        t = ctx.traffic
        self.ctx = ctx
        self.slots, self.capacity = t["slots"], t["capacity"]
        self.block = ctx.config["generation"]["block_length"]
        self.checks = {}
        self.model, cfg = build_model(ctx)
        ctx.note("model built")
        self.vocab = cfg.mask_token_id      # ids are drawn below the mask
        self.session, self.buckets = open_session(ctx, self.model,
                                                  self.slots, self.capacity)
        self.req = {}                       # rid -> dict
        self.waiting = collections.deque()  # submitted, still queued
        self.admitted = []                  # out of the queue, no token yet
        self.running = {}                   # rid -> positions cached
        self.samples = []                   # (time, running, cached)
        self.results = {}                   # rid -> RequestResult, at finish

    def finish(self):
        """The parent's, with the session's results kept for the replay
        (the parent's own fetch would take them from the session) and the
        session let go: its cache and programs leave the chip to the
        reference."""
        cut = [rid for rid, r in self.req.items() if r["done"] is None]
        for rid in cut:
            self.session.cancel(rid)
        self.results = self.session.results()
        self.session.close()
        self.session = None
        jax.clear_caches()
        gc.collect()
        finished = [rid for rid, r in self.req.items()
                    if r["done"] is not None]
        wrong = [rid for rid in finished
                 if self.results[rid].state.name != "DONE"
                 or len(self.results[rid].ids) - self.req[rid]["plen"]
                 != self.req[rid]["new"]]
        stray = [rid for rid in cut
                 if self.results[rid].state.name != "CANCELLED"]
        return finished, wrong + stray

    def check_against_reference(self, since):
        """``matches_reference``, of the requests that finished at or
        after ``since``; call after ``finish``."""
        t_ref = time.perf_counter()
        ok, counts, widest = reference_check(self.ctx, self, since)
        self.ctx.note(f"reference replay: {counts}; widest margins where "
                      f"the system differed: {widest}; "
                      f"{time.perf_counter() - t_ref:.1f}s after the window")
        self.checks["matches_reference"] = ok

    def prefilled(self, plen):
        return plen // self.block * self.block

    def warm(self, schedule):
        """One request for every prefill bucket the schedule uses (the
        longest prompt of each), and with them the block program."""
        longest = {}
        for _due, ids, _new in schedule:
            b = serving.bucket_of(self.buckets, self.prefilled(len(ids)))
            if len(ids) > len(longest.get(b, ())):
                longest[b] = ids
        for ids in longest.values():
            self.session.submit(ids, 2)
        self.session.results()
        self.ctx.note("warm")
        return sorted(longest)

    def step(self):
        """One ``session.step()``. Returns the requests that finished in
        it."""
        done = self.session.step()
        now = time.perf_counter()
        for rid in self.running:
            self.running[rid] += self.block
        while self.waiting and \
                self.session.status(self.waiting[0]).name != "QUEUED":
            self.admitted.append(self.waiting.popleft())
        still = []
        for rid in self.admitted:
            if self.session.generated(rid):
                self.req[rid]["first"] = now
                self.running[rid] = self.prefilled(self.req[rid]["plen"]) \
                    + self.block
            else:
                still.append(rid)
        self.admitted = still
        if self.running:
            # the block just run attended to what was cached before it
            self.samples.append((now, len(self.running),
                                 sum(self.running.values())
                                 - self.block * len(self.running)))
        for rid in done:
            self.req[rid]["done"] = now
            self.running.pop(rid, None)
        return done
