"""``train_steps`` for a model with sparse experts: the same window, feed,
checks and counts (this file loads ``loops/train_steps.py`` by name and calls
it), and what that loop cannot carry:

* before it, at the cell's full widths, ``reference.layers`` layers and
  ``reference.grads.seq`` tokens, the program's loss, gradient (on a TPU
  through the grouped kernels' backward pass) and routing against
  ``arch.reference_loss_and_grads`` in float32. A bf16 program flips the
  choice of a token whose k-th and next score tie within its rounding, and
  one flipped token hides every other difference, so the comparison is in
  two parts: ``routing_matches_reference``: the program's choice is the
  reference's own for every token whose margin in the reference is over
  ``reference.grads.margin``, all but ``routing_mismatch_tolerance`` of them;
  ``grads_match_reference``: along the program's routing, the loss and the
  relative L2 error of each leaf of ``reference.grads.leaves``. The routers'
  biases are drawn from the seed (they start at zero, and a zero bias would
  not show a choice that ignores it);
* ``step_matches_reference`` (``step_check``): ONE STEP OF THE PROGRAM THE
  WINDOW TIMES, the entry's own jitted ``step`` at the traffic's batch and
  sequence on a seeded batch, from the seeded weights and seeded biases,
  against the reference on the same batch along the reference's OWN routing:
  the loss; the step's gradient, read back from AdamW's first moment
  (``m / (1 - beta1)`` after one step from zero), relative L2 error of EVERY
  leaf (a sparse layer's ``router``, ``gate_up``, ``down`` and ``ffn_norm``
  to ``routed_tolerance``: a token that bf16 routes otherwise moves them and
  little else); the parameters' change against AdamW at the schedule's first
  rate applied to the step's own moments, as the norm of the difference over
  the norm of the expected change, worst leaf (a state left unchanged reads
  1); the step's load an expert against the reference's own choice; the
  model's own update of the biases and counts. The state is then made anew
  from the same seed, and the window runs the same compiled ``step``;
* after it, outside the window: the program ticks its ``moe.*`` counters from
  the running counts in the train state (one fetch), and the counts the
  expert layer's readers need are added; ``held_share_in_band``: the share of
  the run's pairs that went to the experts held is within
  ``reference.held_share_band`` (a router that leaves the experts held sheds
  their work and reads faster);
* the check ``grouped_through_the_kernels`` (on a TPU): every pass of the
  grouped product (``moe.grouped_dispatch{kernel=gmm, pass=fwd|dx|dw}``) was
  traced through the kernel.

The configuration's ``build.train`` names ``loss_and_routing`` and
``count_expert_load`` beside the entry points ``train_steps`` reads.
"""
import time

import numpy as np

from byname import load_module

train_steps = load_module("loops", "train_steps")


def _leaf(tree, path):
    for key in path.split("."):
        tree = tree[int(key) if key.isdigit() else key]
    return tree


def grads_check(ctx):
    """({leaf path: relative L2 error}, the two losses, the tokens whose
    margin is clear, those of them the program routed otherwise)."""
    import jax
    import jax.numpy as jnp
    build = ctx.config["build"]["train"]
    ref = ctx.config["reference"]
    spec = ref["grads"]
    cfg, pcfg = train_steps._configs(ctx, ref["layers"])
    mesh = ctx.resolve(build["build_mesh"])(pcfg, ctx.devices)
    key = jax.random.PRNGKey(ctx.seed)
    params = ctx.resolve(build["init_params"])(cfg, pcfg, key)
    params["expert_bias"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1), params["expert_bias"].shape)
    ids = train_steps.TokenSource(cfg.vocab_size, 1.0, ctx.seed + 2).batch(
        spec["sequences"], spec["seq"])
    loss_and_routing = ctx.resolve(build["loss_and_routing"])
    fixed = {k: params[k] for k in ctx.arch.NOT_TRAINED}

    def loss(trained, ids):
        return loss_and_routing({**trained, **fixed}, (ids, ids), cfg, pcfg,
                                mesh)

    with mesh:
        sharded, _specs = ctx.resolve(build["shard_params"])(
            params, mesh, cfg, pcfg)
        (got_loss, routing), got = jax.jit(
            jax.value_and_grad(loss, has_aux=True))(
            {k: v for k, v in sharded.items() if k not in fixed},
            jnp.asarray(ids))
    want_loss, want, own, margin = ctx.arch.reference_loss_and_grads(
        params, ids, cfg.num_heads, routing=routing)
    errors = {}
    for path in spec["leaves"]:
        g = _leaf(got, path).astype(jnp.float32)
        w = _leaf(want, path)
        # a leaf no token reached has no gradient on either side: 0, not 0/0
        errors[path] = float(jnp.linalg.norm(g - w)
                             / jnp.maximum(jnp.linalg.norm(w), 1e-30))
    clear = np.asarray(margin) > spec["margin"]
    same = (np.sort(np.asarray(routing), -1)
            == np.sort(np.asarray(own), -1)).all(-1)
    return (errors, float(got_loss), float(want_loss), int(clear.sum()),
            int((clear & ~same).sum()), clear.size)


def _relative(diff_sq, want_sq):
    """sqrt(diff_sq / want_sq); 0 where both are nothing (a leaf that
    nothing reached, or whose every change is under its rounding)."""
    if not want_sq:
        return 0.0 if not diff_sq else float("inf")
    return float(np.sqrt(diff_sq / want_sq))


def step_check(ctx, mesh, params, opt_state, step):
    """One ``step`` of the entry's own program on a seeded batch of the
    traffic's shape (the arguments are donated to it) -> the readings of
    ``step_matches_reference`` (module docstring)."""
    import jax
    import jax.numpy as jnp
    spec = ctx.config["reference"]["step"]
    adam = spec["adamw"]
    cfg, _pcfg = train_steps._configs(ctx)
    t = ctx.traffic
    fixed = ctx.arch.NOT_TRAINED
    key = jax.random.fold_in(jax.random.PRNGKey(ctx.seed), 1)
    with mesh:
        bias = params["expert_bias"]
        params = dict(params, expert_bias=jax.device_put(
            0.1 * jax.random.normal(key, bias.shape, bias.dtype),
            bias.sharding))
        before = jax.tree_util.tree_map(jnp.copy, params)
        ids = train_steps.TokenSource(
            cfg.vocab_size, t["tokens"]["exponent"], ctx.seed + 2).batch(
                t["batch"], t["seq"])
        after, opt, loss = step(params, opt_state, (jnp.asarray(ids),) * 2)

    @jax.jit
    def change(p0, p1, m, v):
        """(|the change - AdamW's on these moments|^2, |AdamW's|^2)."""
        p0f, m, v = (a.astype(jnp.float32) for a in (p0, m, v))
        update = (m / (1 - adam["beta1"])) / (
            jnp.sqrt(v / (1 - adam["beta2"])) + adam["eps"]) \
            + adam["weight_decay"] * p0f
        # rounded as the stored weight is (an astype pair inside a fusion
        # is dropped on the TPU: PERF.md, PR 32)
        kind = jnp.finfo(p0.dtype)
        want = jax.lax.reduce_precision(
            p0f - adam["lr"] * update, kind.nexp, kind.nmant) - p0f
        got = p1.astype(jnp.float32) - p0f
        return jnp.sum((got - want) ** 2), jnp.sum(want ** 2)

    paths = jax.tree_util.tree_flatten_with_path(opt["m"])[0]
    names = [jax.tree_util.keystr(path) for path, _ in paths]

    def trained(tree):
        return jax.tree_util.tree_leaves(
            {k: v for k, v in tree.items() if k not in fixed})

    changed = {name: _relative(*map(float, change(*leaves)))
               for name, *leaves in zip(names, trained(before), trained(after),
                                        trained(opt["m"]), trained(opt["v"]))}
    frozen = {k: np.asarray(jax.device_get(after[k])) for k in fixed}
    bias0 = np.asarray(jax.device_get(before["expert_bias"]))
    steps = int(jax.device_get(opt["step"]))
    # the step's gradient, on the host while the reference runs
    got = [np.asarray(jax.device_get(leaf)) for leaf in trained(opt["m"])]
    loss = float(loss)
    del after, opt, params, opt_state

    want_loss, want, own, _margin = ctx.arch.reference_loss_and_grads(
        before, ids, cfg.num_heads)
    del before

    @jax.jit
    def against(m, w):
        g = m.astype(jnp.float32) / (1 - adam["beta1"])
        return jnp.sum((g - w) ** 2), jnp.sum(w ** 2)

    errors = {name: _relative(*map(float, against(jnp.asarray(m), w)))
              for name, m, w in zip(names, got, trained(want))}
    experts = frozen["expert_load"].shape[-1]
    own_load = np.stack([np.bincount(layer.ravel(), minlength=experts)
                         for layer in np.asarray(own)])
    load = frozen["expert_load"]
    rule = bias0 + spec["expert_bias_update_rate"] * np.sign(
        load.mean(-1, keepdims=True) - load)
    return {
        "loss": loss, "reference_loss": float(want_loss),
        "gradient_errors": errors, "change_errors": changed,
        "steps": steps,
        "load_mismatch": float(np.abs(load - own_load).sum() / load.sum()),
        "frozen_as_the_rule": bool(
            np.allclose(frozen["expert_bias"], rule, atol=1e-6)
            and (frozen["expert_peak"] == load.max(-1)).all()
            and (load.sum(-1) == ids.size * own.shape[-1]).all())}


def _routed(name, names):
    """A leaf a flipped choice moves: a sparse layer's experts, its router
    and the norm before them."""
    layer = name.rsplit("[", 1)[0]
    return f"{layer}['router']" in names and name.endswith(
        ("['router']", "['gate_up']", "['down']", "['ffn_norm']"))


def run(ctx):
    import jax
    import paddle_tpu.observability as obs

    build = ctx.config["build"]["train"]
    spec = ctx.config["reference"]["grads"]
    last = {}
    resolve = ctx.resolve

    def keeping_state(dotted):
        """``train_steps`` keeps the state and the step to itself: the entry
        is wrapped to check one step of its program before handing it over,
        and the step to remember its newest outputs (references, no
        fetch)."""
        found = resolve(dotted)
        if dotted != build["entry"]:
            return found

        def entry(*args, **kwargs):
            mesh, params, opt_state, step = found(*args, **kwargs)
            t0 = time.perf_counter()
            last["step_check"] = step_check(ctx, mesh, params, opt_state,
                                            step)
            del params, opt_state
            # the same seed's state again; its own jitted step is never
            # called, so the window runs the program that was checked
            _mesh, params, opt_state, _step = found(*args, **kwargs)
            ctx.note(f"one step of the timed program against the reference: "
                     f"{time.perf_counter() - t0:.1f}s of set-up")

            def stepping(params, opt_state, batch):
                out = step(params, opt_state, batch)
                last["params"], last["opt_state"] = out[0], out[1]
                return out
            return mesh, params, opt_state, stepping
        return entry

    with obs.window() as counters:
        t0 = time.perf_counter()
        errors, got_loss, want_loss, clear, strayed, tokens = \
            grads_check(ctx)
        worst = max(errors, key=errors.get)
        ctx.note(f"against the float32 reference at {spec['sequences']} x "
                 f"{spec['seq']} tokens, along the program's routing: loss "
                 f"{got_loss:.5f} (reference {want_loss:.5f}); relative L2 "
                 f"error a leaf {({k: round(v, 5) for k, v in errors.items()})}"
                 f"; worst {worst} {errors[worst]:.3e} (tolerance "
                 f"{spec['tolerance']}); of {tokens} token-layers {clear} "
                 f"have a margin over {spec['margin']} and the program "
                 f"routed {strayed} of them otherwise (tolerance "
                 f"{spec['routing_mismatch_tolerance']} of them), "
                 f"{time.perf_counter() - t0:.1f}s of set-up")
        ctx.resolve = keeping_state
        try:
            result = train_steps.run(ctx)
        finally:
            ctx.resolve = resolve
    checks = dict(result["checks"])
    checks["grads_match_reference"] = all(
        np.isfinite(e) and e <= spec["tolerance"] for e in errors.values()) \
        and abs(got_loss - want_loss) <= spec["loss_tolerance"]
    checks["routing_matches_reference"] = \
        clear >= spec["clear_floor"] * tokens and \
        strayed <= spec["routing_mismatch_tolerance"] * clear

    one = last["step_check"]
    errors = one["gradient_errors"]
    routed = {k: v for k, v in errors.items() if _routed(k, errors)}
    tight = {k: v for k, v in errors.items() if k not in routed}
    worst = {name: max(group, key=group.get) for name, group in (
        ("gradient", tight), ("routed gradient", routed),
        ("change", one["change_errors"]))}
    step_spec = ctx.config["reference"]["step"]
    ctx.note(f"one step of the timed program at {ctx.traffic['batch']} x "
             f"{ctx.traffic['seq']} tokens against the float32 reference "
             f"along its own routing: loss {one['loss']:.5f} (reference "
             f"{one['reference_loss']:.5f}); gradient from the first moment, "
             f"relative L2 error, worst leaf {worst['gradient']} "
             f"{tight[worst['gradient']]:.3e} (tolerance "
             f"{step_spec['tolerance']}), worst behind a router "
             f"{worst['routed gradient']} "
             f"{routed[worst['routed gradient']]:.3e} (tolerance "
             f"{step_spec['routed_tolerance']}); the parameters' change "
             f"against AdamW at lr {step_spec['adamw']['lr']}, worst leaf "
             f"{worst['change']} "
             f"{one['change_errors'][worst['change']]:.3e} (tolerance "
             f"{step_spec['change_tolerance']}); load an expert against the "
             f"reference's choice {one['load_mismatch']:.4f} of the pairs "
             f"(tolerance {step_spec['load_mismatch_tolerance']}); biases and "
             f"counts as the rule: {one['frozen_as_the_rule']}; every leaf "
             f"{ {k: round(v, 4) for k, v in errors.items()} }; change "
             f"{ {k: round(v, 4) for k, v in one['change_errors'].items()} }")
    checks["step_matches_reference"] = \
        abs(one["loss"] - one["reference_loss"]) \
        <= step_spec["loss_tolerance"] \
        and all(np.isfinite(e) and e <= step_spec["tolerance"]
                for e in tight.values()) \
        and all(np.isfinite(e) and e <= step_spec["routed_tolerance"]
                for e in routed.values()) \
        and all(e <= step_spec["change_tolerance"]
                for e in one["change_errors"].values()) \
        and one["load_mismatch"] <= step_spec["load_mismatch_tolerance"] \
        and one["frozen_as_the_rule"] and one["steps"] == 1

    grouped = {c["labels"]["pass"]: int(c["value"])
               for c in counters.delta.changed()
               if c["name"] == "moe.grouped_dispatch"
               and c["labels"].get("kernel") == "gmm"}
    if ctx.on_chip:
        checks["grouped_through_the_kernels"] = all(
            grouped.get(which, 0) > 0 for which in ("fwd", "dx", "dw"))

    cfg, _pcfg = train_steps._configs(ctx)
    load = ctx.resolve(build["count_expert_load"])(last["params"], cfg)
    steps = int(jax.device_get(last["opt_state"]["step"]))
    held = load[:, cfg.expert_offset:
                cfg.expert_offset + cfg.num_local_experts]
    held_share = float(held.sum()) / max(1, int(load.sum()))
    low, high = ctx.config["reference"]["held_share_band"]
    checks["held_share_in_band"] = low <= held_share <= high
    ctx.note(f"grouped dispatch {grouped}; {steps} steps counted; "
             f"assignments a layer {load.sum(axis=1).tolist()}, to the held "
             f"experts {held.sum(axis=1).tolist()} ({held_share:.4f} of all, "
             f"band {low}-{high}), busiest over the run "
             f"{load.max(axis=1).tolist()}")
    counts = dict(result["counts"], expert_layers=int(load.shape[0]),
                  steps_counted=steps,
                  held_pairs_per_layer_step=float(held.sum())
                  / max(1, load.shape[0] * steps))
    return dict(result, checks=checks, counts=counts)
