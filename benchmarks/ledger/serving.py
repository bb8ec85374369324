"""What the serving loops share: building the session from the
configuration's ``build.serve``, the comparison with the plain reference,
warm-up of the cell's own prefill buckets, and the bookkeeping of requests
through the session's public calls alone (``submit``, ``step``, ``status``,
``cancel``, ``results``).

What the host can see, and when: ``step()`` admits what fits, runs one
decode block and fetches its tokens, so a request that has left ``QUEUED``
when ``step()`` returns has its first token on the host (with up to
``decode_block`` more), and every further ``step()`` brings ``decode_block``
tokens until its budget. First-token and last-token times are therefore
the return times of ``step()`` calls; the session has no finer clock
(PERF.md, Open questions).
"""
import collections
import time

import numpy as np


def default_buckets(max_length):
    """The session's own default prefill buckets (powers of two from 16, then
    the capacity), passed to it explicitly so that the benchmark knows which
    admit programs its traffic needs."""
    b, out = 16, []
    while b < max_length:
        out.append(b)
        b *= 2
    return out + [max_length]


def bucket_of(buckets, plen):
    return next(b for b in buckets if b >= plen)


def build_model(ctx, num_layers=None):
    import paddle_tpu as paddle
    serve = ctx.config["build"]["serve"]
    sizes = dict(ctx.config["sizes"])
    if num_layers is not None:
        sizes["num_layers"] = num_layers
    cfg = ctx.resolve(serve["model_config"])(**sizes)
    paddle.seed(ctx.seed)
    model = ctx.resolve(serve["entry"])(cfg)
    return getattr(model, serve["weights_dtype"])(), cfg


def open_session(ctx, model, slots, capacity):
    serve = ctx.config["build"]["serve"]
    buckets = default_buckets(capacity)
    session = ctx.resolve(serve["session"])(
        model, max_slots=slots, max_length=capacity,
        prefill_buckets=buckets, seed=ctx.seed, **serve["session_kwargs"])
    return session, buckets


def reference_check(ctx):
    """Prefill and decoding through the session's cache against the plain
    float32 forward pass, at the cell's full widths and ``reference.layers``
    layers: the session's greedy tokens must be the reference's argmax at
    every position where the reference's top two logits differ by more than
    the tolerance. Returns (tokens compared, tokens that differ)."""
    ref = ctx.config["reference"]
    model, cfg = build_model(ctx, ref["layers"])
    plen, new = ref["serve_prompt_tokens"], ref["serve_new_tokens"]
    rng = np.random.RandomState(ctx.seed + 1)
    prompts = [rng.randint(0, cfg.vocab_size, plen).astype(np.int32)
               for _ in range(ref["sequences"])]
    session, _ = open_session(ctx, model, ref["sequences"], 2 * plen)
    with session:
        rids = [session.submit(p, new) for p in prompts]
        results = session.results()
    full = np.stack([results[r].ids for r in rids])
    params = ctx.arch.from_serving_state(model.state_dict(), ref["layers"])
    logits = np.asarray(ctx.arch.reference_logits(params, full[:, :-1],
                                                  cfg.num_heads))
    logits = logits[:, plen - 1:]                  # predicts the new tokens
    top2 = np.sort(logits, axis=-1)[..., -2:]
    sure = (top2[..., 1] - top2[..., 0]) > ref["serve_margin_tolerance"]
    differ = (np.argmax(logits, -1) != full[:, plen:]) & sure
    return int(sure.sum()), int(differ.sum())


class Served:
    """One session under one traffic mix, with every request's times."""

    def __init__(self, ctx):
        t = ctx.traffic
        self.ctx = ctx
        self.slots, self.capacity = t["slots"], t["capacity"]
        self.block = ctx.config["build"]["serve"]["session_kwargs"][
            "decode_block"]
        t_ref = time.perf_counter()
        compared, differ = reference_check(ctx)
        ctx.note(f"reference: {differ} of {compared} compared greedy tokens "
                 "differ from the float32 argmax, "
                 f"{time.perf_counter() - t_ref:.1f}s of set-up")
        self.checks = {"matches_reference": compared > 0 and differ == 0}
        model, cfg = build_model(ctx)
        ctx.note("model built")
        self.vocab = cfg.vocab_size
        self.session, self.buckets = open_session(ctx, model, self.slots,
                                                  self.capacity)
        self.req = {}                       # rid -> dict
        self.waiting = collections.deque()  # submitted, no first token yet
        self.running = {}                   # rid -> tokens generated so far
        self.samples = []                   # (time, slots busy, cached)

    def warm(self, schedule):
        """One request for every prefill bucket the schedule uses (the
        longest prompt of each), and with them the decode block."""
        longest = {}
        for _due, ids, _new in schedule:
            b = bucket_of(self.buckets, len(ids))
            if len(ids) > len(longest.get(b, ())):
                longest[b] = ids
        for ids in longest.values():
            self.session.submit(ids, 2)
        self.session.results()
        self.ctx.note("warm")
        return sorted(longest)

    def submit(self, ids, new, due, now):
        rid = self.session.submit(ids, new)
        self.req[rid] = {"plen": len(ids), "new": new, "due": due,
                         "submit": now, "first": None, "done": None}
        self.waiting.append(rid)

    def step(self):
        """One ``session.step()``. Returns the requests that finished in it.
        Every time kept here is ``time.perf_counter()``'s."""
        done = self.session.step()
        now = time.perf_counter()
        for rid in self.running:
            self.running[rid] += self.block
        while self.waiting and \
                self.session.status(self.waiting[0]).name != "QUEUED":
            rid = self.waiting.popleft()
            self.req[rid]["first"] = now
            self.running[rid] = 1 + self.block
        if self.running:
            # the block just run: cached positions at its middle
            cached = sum(self.req[r]["plen"]
                         + min(g, self.req[r]["new"]) - (self.block + 2) // 2
                         for r, g in self.running.items())
            self.samples.append((now, len(self.running), cached))
        for rid in done:
            self.req[rid]["done"] = now
            self.running.pop(rid, None)
        return done

    def idle(self):
        return not self.waiting and not self.running

    def finish(self):
        """Cancels what the window's end cut, collects every result and
        holds the finished ones to their budgets. Returns (finished, wrong):
        requests that ended inside the window, and those of them that did
        not end DONE with exactly their budget."""
        cut = [rid for rid, r in self.req.items() if r["done"] is None]
        for rid in cut:
            self.session.cancel(rid)
        results = self.session.results()
        self.session.close()
        finished = [rid for rid, r in self.req.items()
                    if r["done"] is not None]
        wrong = [rid for rid in finished
                 if results[rid].state.name != "DONE"
                 or len(results[rid].ids) - self.req[rid]["plen"]
                 != self.req[rid]["new"]]
        stray = [rid for rid in cut if results[rid].state.name != "CANCELLED"]
        return finished, wrong + stray

    def tpot(self, since):
        """Per request finished at or after the time ``since``:
        (last token seen - first token seen) / (tokens - 1)."""
        return [(r["done"] - r["first"]) / (r["new"] - 1)
                for r in self.req.values()
                if r["done"] is not None and r["done"] >= since
                and r["done"] > r["first"]]


def counter_checks(delta):
    """The program's own counters over the run: nothing retried,
    quarantined or rejected, and no attention dispatch fell back."""
    def moved(name):
        return sum(c["value"] for c in delta.changed() if c["name"] == name)
    return {"no_step_retries": moved("serving.step_retries") == 0,
            "none_quarantined": moved("serving.quarantined") == 0,
            "none_rejected": moved("serving.rejected") == 0,
            "no_attention_fallback":
                moved("attn.dispatch_fallback") == 0}
