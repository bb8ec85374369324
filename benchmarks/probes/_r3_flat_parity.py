"""CPU parity: flat-accum window (k=1) == classic fused step."""
import os
import sys

sys.path.insert(0, ".")


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from paddle_tpu._testing import force_cpu
    force_cpu()
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models import gpt_hybrid as GH

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=4, max_seq_len=32)
    pcfg = GH.ParallelConfig(dp=1, pp=1, tp=1, remat=False,
                             param_dtype=jnp.float32,
                             compute_dtype=jnp.float32)
    mesh, params, opt_state, step = GH.setup(cfg, pcfg, seed=0,
                                             devices=jax.devices()[:1])
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (4, 32)))
    with mesh:
        ref_params, _, ref_loss = step(params, opt_state, (ids, ids))

    init_state, train_window, unflatten = GH.build_flat_accum_bench(
        cfg, pcfg, mesh)
    pf, m, v, acc = init_state(seed=0)
    with mesh:
        pf, m, v, acc, loss = train_window(pf, m, v, acc,
                                           [(ids, ids)], 1, 1)
    got = unflatten(pf)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    print("FLAT == CLASSIC (loss and updated params)")

    # k=2 matches a 2x-batch classic step
    ids2 = jnp.asarray(np.random.RandomState(1).randint(0, 256,
                                                        (8, 32)))
    mesh2, params2, opt2, step2 = GH.setup(cfg, pcfg, seed=0,
                                           devices=jax.devices()[:1])
    with mesh2:
        refp, _, _ = step2(params2, opt2, (ids2, ids2))
    pf, m, v, acc = init_state(seed=0)
    with mesh:
        pf, m, v, acc, loss = train_window(
            pf, m, v, acc,
            [(ids2[:4], ids2[:4]), (ids2[4:], ids2[4:])], 1, 2)
    got = unflatten(pf)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(refp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    print("k=2 WINDOW == 2x-BATCH CLASSIC STEP")


if __name__ == "__main__":
    main()
