"""Leaf-accum engine: CPU parity then TPU throughput."""
import os
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def parity():
    from paddle_tpu._testing import force_cpu
    force_cpu()
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
        refp, _, refl = step(params, opt_state, (ids, ids))
    init_state, train_window = GH.build_leaf_accum_bench(cfg, pcfg, mesh)
    p, m, v, acc = init_state(seed=0)
    with mesh:
        p, m, v, acc, loss = train_window(p, m, v, acc, [(ids, ids)],
                                          1, 1)
    np.testing.assert_allclose(float(loss), float(refl), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(refp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    print("LEAF == CLASSIC")


def bench():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.models import gpt_hybrid as GH
    cfg = GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=1024)
    seq = 1024
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, seq)))
    sel = os.environ.get("VARIANT", "")
    allv = (("24/names", 24, "names"), ("1/names", 1, "names"),
            ("1/full", 1, "full"))
    allv = [v for v in allv if not sel or v[0] == sel]
    for _tag, unroll, policy in allv:
        try:
            pcfg = GH.ParallelConfig(dp=1, pp=1, tp=1, remat=True,
                                     remat_policy=policy,
                                     scan_unroll=unroll,
                                     param_dtype=jnp.bfloat16,
                                     compute_dtype=jnp.bfloat16,
                                     moment_dtype=jnp.bfloat16)
            mesh = GH.build_mesh(pcfg, jax.devices()[:1])
            init_state, train_window = GH.build_leaf_accum_bench(
                cfg, pcfg, mesh)
            k = int(os.environ.get("K", "1"))
            if k == 1:
                p, m, v, acc = init_state.noacc(seed=0)
            else:
                p, m, v, acc = init_state(seed=0)
            chunks = [(ids, ids)] * k
            with mesh:
                p, m, v, acc, loss = train_window(p, m, v, acc, chunks,
                                                  1, k)
                float(loss)
                t0 = time.perf_counter()
                outer = 3
                for w in range(outer):
                    p, m, v, acc, loss = train_window(
                        p, m, v, acc, chunks, 2 + w, k)
                float(loss)
                dt = (time.perf_counter() - t0) / outer
            tok = 4 * seq * k / dt
            print(f"{unroll}/{policy} k={k}: {dt*1e3:.0f} ms/window  "
                  f"{tok:.0f} tok/s  loss={float(loss):.4f}",
                  flush=True)
            break
        except Exception as e:
            print(f"{unroll}/{policy}: failed {type(e).__name__}: "
                  f"{e}"[:160], flush=True)


if __name__ == "__main__":
    if os.environ.get("PARITY") == "1":
        os.environ["JAX_PLATFORMS"] = "cpu"
        parity()
    else:
        bench()
