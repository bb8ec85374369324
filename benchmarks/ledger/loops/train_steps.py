"""Training loop: seeded batches made on the host and fed every step.

The configuration's ``build.train`` names the program's entry points
(``setup`` returning (mesh, params, opt_state, step), ``forward``, ``loss``,
``init_params``, ``shard_params``, ``build_mesh``) and the keyword arguments
of its model and parallel configurations; the traffic file gives ``batch``,
``seq``, the token distribution and ``in_flight`` (steps the host may run
ahead of the device: without a limit the un-awaited dispatch would queue the
whole window in its first second; with only two, a host that stalls for half a
second idles the chip. A few seconds' worth, as a training loop that syncs
only to log would have, keeps the device fed through such stalls).

Counted: every step dispatched in the window, closed by ``block_until_ready``
on the last; the window's length is taken there.
"""
import math
import time

import numpy as np


class TokenSource:
    """Token ids from a fixed Zipf distribution over the vocabulary
    (p_k ~ 1/(k+1)^exponent), so that a few tens of steps have a unigram
    distribution to learn; uniform ids would leave the loss at ln(V)."""

    def __init__(self, vocab, exponent, seed):
        p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
        self._cdf = np.cumsum(p / p.sum())
        self._vocab = vocab
        self._rng = np.random.RandomState(seed)

    def batch(self, batch, seq):
        ids = np.searchsorted(self._cdf, self._rng.random_sample((batch, seq)))
        return np.minimum(ids, self._vocab - 1).astype(np.int32)


def _configs(ctx, num_layers=None):
    import jax.numpy as jnp
    build = ctx.config["build"]["train"]
    sizes = dict(ctx.config["sizes"])
    if num_layers is not None:
        sizes["num_layers"] = num_layers
    parallel = {k: (jnp.dtype(v) if k.endswith("_dtype") and v else v)
                for k, v in build["parallel"].items()}
    return (ctx.resolve(build["model_config"])(**sizes),
            ctx.resolve(build["parallel_config"])(**parallel))


def reference_check(ctx, seq):
    """The program's logits through ``forward`` on the cell's mesh, at the
    cell's full widths and ``reference.layers`` layers, against the plain
    float32 reference on the same seeded weights. Returns the largest
    absolute difference over the reference's largest absolute logit."""
    import jax
    import jax.numpy as jnp
    build = ctx.config["build"]["train"]
    ref = ctx.config["reference"]
    cfg, pcfg = _configs(ctx, ref["layers"])
    mesh = ctx.resolve(build["build_mesh"])(pcfg, ctx.devices)
    params = ctx.resolve(build["init_params"])(
        cfg, pcfg, jax.random.PRNGKey(ctx.seed))
    ids = TokenSource(cfg.vocab_size, 1.0, ctx.seed + 1).batch(
        ref["sequences"], seq)
    forward = ctx.resolve(build["forward"])
    with mesh:
        sharded, _specs = ctx.resolve(build["shard_params"])(
            params, mesh, cfg, pcfg)
        got = jax.jit(lambda p, i: forward(p, i, cfg, pcfg, mesh))(
            sharded, jnp.asarray(ids))
    # the reference on the unsharded weights, on one chip; compared there,
    # so that only two scalars cross to the host
    want = ctx.arch.reference_logits(params, ids, cfg.num_heads)
    got = jax.device_put(got.astype(jnp.float32),
                         next(iter(want.devices())))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def run(ctx):
    import jax
    import jax.numpy as jnp
    import paddle_tpu.observability as obs

    t = ctx.traffic
    batch, seq = t["batch"], t["seq"]
    build = ctx.config["build"]["train"]
    checks = {}

    with obs.window() as counters:
        t_ref = time.perf_counter()
        err = reference_check(ctx, seq)
        tol = ctx.config["reference"]["train_logits_tolerance"]
        ctx.note(f"reference: normalised max-abs logit error {err:.3e} "
                 f"(tolerance {tol}), {time.perf_counter() - t_ref:.1f}s "
                 "of set-up")
        checks["matches_reference"] = err <= tol

        cfg, pcfg = _configs(ctx)
        mesh, params, opt_state, step = ctx.resolve(build["entry"])(
            cfg, pcfg, seed=ctx.seed, devices=ctx.devices)
        loss_fn = ctx.resolve(build["loss"])
        probe_loss = jax.jit(lambda p, b: loss_fn(p, b, cfg, pcfg, mesh))
        source = TokenSource(cfg.vocab_size, t["tokens"]["exponent"],
                             ctx.seed)
        probe = jnp.asarray(source.batch(batch, seq))
        uniform = jnp.asarray(np.random.RandomState(ctx.seed).randint(
            0, cfg.vocab_size, (batch, seq)).astype(np.int32))
        losses = []
        with mesh:
            # before any update, on uniform ids: on the Zipf ids the loss
            # swings with the few frequent tokens' embeddings
            loss0 = float(probe_loss(params, (uniform, uniform)))
            before = float(probe_loss(params, (probe, probe)))
            for _ in range(t.get("warmup_steps", 2)):
                ids = jnp.asarray(source.batch(batch, seq))
                params, opt_state, loss = step(params, opt_state, (ids, ids))
                losses.append(loss)
            jax.block_until_ready(loss)

            dispatch_s, done_at = [], []
            in_flight = t["in_flight"]
            with ctx.window():
                while ctx.elapsed() < ctx.seconds:
                    ctx.tick()
                    with ctx.span("bench.feed"):
                        ids = jnp.asarray(source.batch(batch, seq))
                    with ctx.span("bench.step"):
                        t1 = time.perf_counter()
                        params, opt_state, loss = step(params, opt_state,
                                                       (ids, ids))
                        dispatch_s.append(time.perf_counter() - t1)
                    losses.append(loss)
                    if len(dispatch_s) > in_flight:
                        with ctx.span("bench.wait"):
                            losses[-1 - in_flight].block_until_ready()
                        done_at.append(ctx.elapsed())
                with ctx.span("bench.wait"):
                    jax.block_until_ready(loss)
                ctx.close()
            after = float(probe_loss(params, (probe, probe)))
    steps = len(dispatch_s)
    losses = [float(x) for x in losses]

    dispatch = {}
    for c in counters.delta.changed():
        if c["name"].startswith("attn."):
            labels = ",".join(f"{k}={v}" for k, v in sorted(c["labels"].items()))
            dispatch[f"{c['name']}{{{labels}}}"] = int(c["value"])
    gaps = sorted(((b - a, round(a, 2)) for a, b in zip(done_at, done_at[1:])),
                  reverse=True)[:3]
    ctx.note(f"attention dispatch {dispatch}; loss on uniform ids before "
             f"training {loss0:.4f}; losses {losses[0]:.4f} .. "
             f"{losses[-1]:.4f}; probe {before:.4f} -> {after:.4f}; "
             f"{steps} steps in {ctx.window_s:.3f}s; longest waits between "
             f"finished steps (s, at) {gaps}; slowest dispatch "
             f"{max(dispatch_s):.4f}s")
    # ln(V) plus half the variance of an untrained tied head's logits (0.41
    # at d_model 2048, 0.82 at 4096: "within 0.5 of ln(V)" fits only one)
    untrained = math.log(cfg.vocab_size) + 0.5 * cfg.hidden_size \
        * ctx.config["reference"]["init_std"] ** 2
    checks["loss_step0_as_untrained"] = abs(loss0 - untrained) <= 0.1
    checks["loss_finite"] = all(math.isfinite(x) for x in losses) \
        and math.isfinite(after)
    checks["probe_loss_fell"] = after < before
    checks["no_attention_fallback"] = not any(
        k.startswith("attn.dispatch_fallback") for k in dispatch)
    if ctx.on_chip:
        want = ctx.config["attention_kernel_by_seq"][str(seq)]
        checks["attention_kernel_as_named"] = \
            dispatch.get(f"attn.dispatch{{kernel={want}}}", 0) > 0 and \
            sum(k.startswith("attn.dispatch{") for k in dispatch) == 1

    tokens_per_s = batch * seq * steps / ctx.window_s
    return {"metrics": {"train_tokens_per_s": tokens_per_s},
            "attempted": steps, "failed": 0, "checks": checks,
            "counts": {"seq": seq, "tokens_per_s": tokens_per_s,
                       "dispatch_s": dispatch_s}}
